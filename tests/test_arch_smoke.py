"""Per-architecture smoke tests: REDUCED config of the same family, one
forward + one train step + one prefill/decode round on CPU; asserts
output shapes and no NaNs. The FULL configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import get_model
from repro.train import adamw_init, make_train_step
from repro.train.optimizer import AdamWConfig

B, S = 2, 16


def _batch(cfg, rng):
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_audio_frames, cfg.d_model)),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_and_train_step(arch):
    cfg = reduced(ARCHS[arch])
    rng = np.random.default_rng(0)
    mod = get_model(cfg)
    params = mod.init(cfg, jax.random.key(0))

    batch = _batch(cfg, rng)
    logits = mod.forward(params, batch, cfg)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), "NaN/inf in forward logits"

    batch["labels"] = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))
    step = make_train_step(cfg, AdamWConfig(lr=1e-3))
    opt_state = adamw_init(params, AdamWConfig(lr=1e-3))
    params2, opt_state, metrics = jax.jit(step)(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"])), "NaN loss"
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    moved = any(
        float(jnp.abs(a - b).max()) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_decode_consistency(arch):
    cfg = reduced(ARCHS[arch])
    rng = np.random.default_rng(1)
    mod = get_model(cfg)
    params = mod.init(cfg, jax.random.key(1))
    batch = _batch(cfg, rng)

    logits_full = mod.forward(params, batch, cfg)
    prefix = cfg.n_patches if cfg.family == "vlm" else 0
    cache = mod.init_cache(cfg, B, S + prefix + 4)
    last, cache = mod.prefill(params, batch, cfg, cache)
    assert bool(jnp.isfinite(last).all())
    np.testing.assert_allclose(
        np.asarray(last[:, 0], np.float32),
        np.asarray(logits_full[:, -1], np.float32), atol=2e-3, rtol=1e-2)

    nxt = jnp.argmax(last[:, -1:], axis=-1)
    step_logits, cache = mod.decode_step(params, nxt, cache, cfg)
    b2 = dict(batch)
    b2["tokens"] = jnp.concatenate([batch["tokens"], nxt], axis=1)
    full2 = mod.forward(params, b2, cfg)
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0], np.float32),
        np.asarray(full2[:, -1], np.float32), atol=2e-3, rtol=1e-2)


def test_moe_grouped_dispatch_matches_gshard():
    cfg = reduced(ARCHS["qwen3-moe-235b-a22b"])
    from repro.models import moe as moe_mod
    rng = np.random.default_rng(2)
    params = moe_mod.init(cfg, jax.random.key(2))
    p1 = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.asarray(rng.standard_normal((B * S, cfg.d_model)), jnp.float32)
    w, ids, _ = moe_mod._route(p1, x, cfg)
    y1 = moe_mod._experts_gshard(p1, x, w, ids, cfg)
    y2 = moe_mod._experts_grouped(p1, x, w, ids, cfg, impl="interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


def test_generate_runs():
    from repro.serve import generate

    cfg = reduced(ARCHS["smollm-360m"])
    mod = get_model(cfg)
    params = mod.init(cfg, jax.random.key(3))
    rng = np.random.default_rng(3)
    out = generate(params, cfg, _batch(cfg, rng), max_new_tokens=5)
    assert out.shape == (B, 5)
    assert bool((out >= 0).all()) and bool((out < cfg.vocab).all())
