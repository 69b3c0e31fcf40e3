"""jit'd public wrappers around the Pallas kernels, with XLA fallbacks.

Every op takes ``impl``:
  * "pallas"    — the TPU kernel (compiled; TPU target). Raises when
                  JAX's default backend is not a TPU: nothing silently
                  stands in for the chip.
  * "interpret" — the kernel body interpreted on the host (correctness
                  path of the CPU tests),
  * "xla"       — the pure-jnp reference,
  * "auto"      — "pallas" on a TPU, "xla" elsewhere.
"""
from __future__ import annotations

import jax

from . import ref
from .flash_attention import flash_attention as _flash
from .grouped_mm import grouped_matmul as _gmm, pad_groups  # noqa: F401
from .pair_sim import COMPACT_CAPACITY
from .pair_sim import pair_scores as _pair_scores
from .pair_sim import pair_scores_catalog as _pair_scores_catalog
from .pair_sim import \
    pair_scores_catalog_compact as _pair_scores_catalog_compact

__all__ = ["pair_scores", "pair_scores_catalog",
           "pair_scores_catalog_raw", "pair_scores_catalog_compact",
           "grouped_matmul", "attention", "pad_groups", "resolve_impl"]


def resolve_impl(impl: str) -> str:
    """Resolve ``"auto"`` against the default backend and refuse
    ``"pallas"`` off the TPU."""
    backend = jax.default_backend()
    if impl == "auto":
        return "pallas" if backend == "tpu" else "xla"
    if impl == "pallas" and backend != "tpu":
        raise RuntimeError(
            f"impl='pallas' needs a TPU, but JAX's default backend is "
            f"{backend!r}; pass impl='interpret' or 'auto' to run "
            f"without one")
    return impl


def pair_scores(a, b, *, threshold: float = 0.8, triangular: bool = False,
                block_m: int = 128, block_n: int = 128, impl: str = "pallas"):
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.pair_scores_ref(a, b, threshold=threshold, triangular=triangular)
    return _pair_scores(a, b, threshold=threshold, triangular=triangular,
                        block_m=block_m, block_n=block_n,
                        interpret=(impl == "interpret"))


def pair_scores_catalog(a, b, catalog, *, threshold: float = 0.8,
                        block_m: int = 128, block_n: int = 128,
                        impl: str = "pallas"):
    """Tile-catalog survivor masks (see pair_sim.pair_scores_catalog).
    ``impl="xla"`` is the production CPU path (batched dynamic-slice
    matmul), not just a test oracle — interpret mode is Python-slow."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.pair_scores_catalog_ref(
            a, b, catalog, threshold=threshold,
            block_m=block_m, block_n=block_n)
    return _pair_scores_catalog(a, b, catalog, threshold=threshold,
                                block_m=block_m, block_n=block_n,
                                interpret=(impl == "interpret"))


def pair_scores_catalog_raw(a, b, catalog, *, block_m: int = 128,
                            block_n: int = 128, impl: str = "pallas"):
    """UNthresholded, UNmasked tile scores (see
    ref.pair_scores_catalog_raw_ref) — the model-parallel partial-score
    path, where the threshold and the catalog predicates only make sense
    AFTER a psum over the model axis. Every ``impl`` routes to the
    batched dynamic-slice ``dot_general`` — on any backend that matmul IS
    the MXU/compute path; a fused Pallas raw variant would only re-fuse
    the slice, and the predicate epilogue it normally fuses is exactly
    what partial scores must defer."""
    del impl
    return ref.pair_scores_catalog_raw_ref(
        a, b, catalog, block_m=block_m, block_n=block_n)


def pair_scores_catalog_compact(a, b, catalog, *, threshold: float = 0.8,
                                block_m: int = 128, block_n: int = 128,
                                capacity: int = COMPACT_CAPACITY,
                                impl: str = "pallas"):
    """Tile-catalog survivors packed on device (see
    pair_sim.pair_scores_catalog_compact): ``(packed, counts)`` instead
    of a dense mask — the serving stage 1 uses this so the host never
    runs ``np.nonzero`` over T·bm·bn cells."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.pair_scores_catalog_compact_ref(
            a, b, catalog, threshold=threshold,
            block_m=block_m, block_n=block_n, capacity=capacity)
    return _pair_scores_catalog_compact(
        a, b, catalog, threshold=threshold, block_m=block_m,
        block_n=block_n, capacity=capacity,
        interpret=(impl == "interpret"))


def grouped_matmul(x, tile_expert, w, *, block_t: int = 128,
                   block_f: int = 128, impl: str = "pallas"):
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.grouped_matmul_ref(x, tile_expert, w, block_t=block_t)
    return _gmm(x, tile_expert, w, block_t=block_t, block_f=block_f,
                interpret=(impl == "interpret"))


def attention(q, k, v, *, causal: bool = True, scale: float | None = None,
              block_q: int = 512, block_k: int = 512, impl: str = "xla"):
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.attention_ref(q, k, v, causal=causal, scale=scale)
    return _flash(q, k, v, causal=causal, scale=scale,
                  block_q=block_q, block_k=block_k,
                  interpret=(impl == "interpret"))
