"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.er.similarity import cosine_scores
from repro.kernels import ops, pair_sim, ref

RNG = np.random.default_rng(42)


def _unit_rows(n, d, dtype):
    x = RNG.standard_normal((n, d)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# pair_sim
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d", [(64, 64, 32), (200, 130, 64),
                                   (128, 128, 256), (257, 31, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("triangular", [False, True])
def test_pair_scores_sweep(m, n, d, dtype, triangular):
    if triangular and m != n:
        n = m
    a = _unit_rows(m, d, dtype)
    b = a if triangular else _unit_rows(n, d, dtype)
    got = ops.pair_scores(a, b, threshold=0.3, triangular=triangular,
                          impl="interpret")
    want = ref.pair_scores_ref(a, b, threshold=0.3, triangular=triangular)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("block", [64, 128])
def test_pair_scores_blocks(block):
    a = _unit_rows(192, 64, jnp.float32)
    got = ops.pair_scores(a, a, threshold=0.5, triangular=True,
                          block_m=block, block_n=block, impl="interpret")
    want = ref.pair_scores_ref(a, a, threshold=0.5, triangular=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


_CAT = np.zeros((2, pair_sim.NCOLS), np.int32)
_SCORERS = {
    "dense": lambda a: ops.pair_scores(a, a, impl="interpret"),
    "catalog": lambda a: ops.pair_scores_catalog(a, a, _CAT,
                                                 impl="interpret"),
    "compact": lambda a: ops.pair_scores_catalog_compact(
        a, a, _CAT, capacity=64, impl="interpret"),
    "dense_ref": lambda a: ref.pair_scores_ref(a, a),
    "catalog_ref": lambda a: ref.pair_scores_catalog_ref(a, a, _CAT),
    "raw_ref": lambda a: ref.pair_scores_catalog_raw_ref(a, a, _CAT),
    "paired": lambda a: cosine_scores(a, a),
}


@pytest.mark.parametrize("name", sorted(_SCORERS))
def test_cosine_scores_contract_in_full_f32(name):
    """Every stage-1 score dot asks for full f32 precision: the TPU's
    default rounds f32 operands to bf16, which moves pairs across the
    stage-1 cut against the f32 host reference. (A no-op on the CPU,
    so the jaxpr is what can be checked here.)"""
    a = jnp.zeros((256, 32), jnp.float32)
    assert "Precision.HIGHEST" in str(jax.make_jaxpr(_SCORERS[name])(a))


def test_pallas_impl_refuses_to_run_off_the_tpu(monkeypatch):
    """impl='pallas' raises off the TPU instead of quietly interpreting;
    'auto' picks the XLA twin, 'interpret' stays as asked."""
    from repro.er import ERConfig, make_products, run_er

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.resolve_impl("auto") == "xla"
    assert ops.resolve_impl("interpret") == "interpret"
    a = _unit_rows(64, 32, jnp.float32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ops.pair_scores(a, a, threshold=0.5, impl="pallas")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        run_er(make_products(600, seed=5).titles,
               ERConfig(r=4, kernel_impl="pallas"))
