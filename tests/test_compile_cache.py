"""Where entry points keep JAX's persistent compilation cache: in
``$JAX_COMPILATION_CACHE_DIR`` when it is set (read by JAX itself), else
at the fixed ``<checkout>/.jax_cache``. Each case runs in a fresh
interpreter, since the cache directory is process-global JAX state."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _run(env_dir, action: str):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", SCRIPT, action], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


@pytest.mark.parametrize("where", ("environment", "checkout"))
def test_cache_directory(tmp_path, where):
    if where == "environment":
        returned, configured = _run(tmp_path, "compile")
        assert returned == configured == str(tmp_path)
        assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
    else:
        returned, configured = _run(None, "none")
        assert returned == configured == str(ROOT / ".jax_cache")
