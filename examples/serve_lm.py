"""Serving example: batched prefill + autoregressive decode with a KV
cache, optionally resuming weights from examples/train_lm.py.

    PYTHONPATH=src python examples/serve_lm.py [--tokens 32] [--batch 4]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import ARCHS, get_config, reduced
from repro.models import get_model
from repro.serve import generate


def main():
    use_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="smollm-360m")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.8)
    args = p.parse_args()

    cfg = reduced(get_config(args.arch))
    mod = get_model(cfg)
    params = mod.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)))}

    t0 = time.perf_counter()
    out = generate(params, cfg, batch, max_new_tokens=args.tokens,
                   temperature=args.temperature, key=jax.random.key(1))
    dt = time.perf_counter() - t0
    total = args.batch * args.tokens
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.tokens}")
    print(f"generated {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s incl. compile)")
    print("sample token ids:", np.asarray(out[0])[:16].tolist())


if __name__ == "__main__":
    main()
