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


# ---------------------------------------------------------------------------
# grouped_mm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,d,e,f,bt", [(256, 32, 2, 48, 128),
                                        (384, 64, 3, 128, 128),
                                        (64, 16, 8, 24, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(t, d, e, f, bt, dtype):
    x = jnp.asarray(RNG.standard_normal((t, d)), dtype)
    te = jnp.asarray(RNG.integers(0, e, t // bt), jnp.int32)
    te = jnp.sort(te)
    w = jnp.asarray(RNG.standard_normal((e, d, f)) * 0.1, dtype)
    got = ops.grouped_matmul(x, te, w, block_t=bt, impl="interpret")
    want = ref.grouped_matmul_ref(x, te, w, block_t=bt)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kvh,s,d", [(1, 4, 4, 128, 32),
                                         (2, 4, 2, 256, 32),
                                         (1, 8, 1, 512, 64),
                                         (2, 2, 2, 384, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, kvh, s, d, dtype):
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, kvh, s, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, kvh, s, d)), dtype)
    got = ops.attention(q, k, v, causal=True, block_q=128, block_k=128,
                        impl="interpret")
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)), jnp.float32)
    got = ops.attention(q, k, v, causal=False, block_q=128, block_k=128,
                        impl="interpret")
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_chunked_xla_attention_equals_ref():
    """The scanned-q XLA path (production fallback) vs plain softmax.

    gqa_attention uses the g-major flat-head layout (flat h = g·KV + k —
    see sharding.attn_logits_constrain); attention_ref repeats kv heads
    (kv-major, h = k·G + g), so the reference's head axis is permuted
    before comparison."""
    from repro.models.layers import gqa_attention

    h, kv = 6, 3
    q = jnp.asarray(RNG.standard_normal((2, 2048, h, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 2048, kv, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 2048, kv, 16)), jnp.float32)
    got = gqa_attention(q, k, v, causal=True)   # chunked (S > 1024)
    # g-major: flat query head h attends kv head (h % kv); expand k/v
    # accordingly and compare against a plain MHA reference
    idx = jnp.arange(h) % kv
    want = ref.attention_ref(q.transpose(0, 2, 1, 3),
                             k[:, :, idx].transpose(0, 2, 1, 3),
                             v[:, :, idx].transpose(0, 2, 1, 3), causal=True
                             ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
