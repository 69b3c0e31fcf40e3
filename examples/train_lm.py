"""End-to-end driver: train a ~100M-param smollm-family model for a few
hundred steps on synthetic data (CPU-feasible), with checkpointing.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

This is a thin wrapper over the production launcher
(repro.launch.train); pass --arch/--batch/--seq to explore. The ~100M
config: smollm trunk at 12 layers × d=512.
"""
import sys

from repro.compile_cache import use_compile_cache
from repro.launch.train import main

if __name__ == "__main__":
    use_compile_cache()
    argv = sys.argv[1:]
    if not any(a.startswith("--arch") for a in argv):
        argv = ["--arch", "smollm-360m", "--reduced100m"] + argv
    # translate the convenience flag into launcher args
    if "--reduced100m" in argv:
        argv.remove("--reduced100m")
        argv += ["--steps", "300", "--batch", "8", "--seq", "128",
                 "--ckpt-dir", "/tmp/repro_ckpt_100m"]
        # ~100M params: tweak via the reduced config path below
        import repro.configs as C

        base = C.get_config("smollm-360m")
        cfg100 = base.replace(n_layers=12, d_model=512, n_heads=8,
                              n_kv_heads=4, head_dim=64, d_ff=1536,
                              vocab=8192, param_dtype="float32",
                              compute_dtype="float32", remat=False,
                              act_shard="none")
        C.ARCHS["smollm-100m"] = cfg100
        argv = ["--arch", "smollm-100m"] + argv
    raise SystemExit(main(argv))
