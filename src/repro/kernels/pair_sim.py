"""Pallas TPU kernel: tiled pair-similarity — the paper's reduce-phase
hot spot (§III-A: "the reduce phase consumes ... more than 95% of the
overall runtime").

A match task (BlockSplit tile or PairRange range segment) reduces to
scoring A @ Bᵀ over two strips of the entity-feature matrix — pure MXU
work once titles are encoded as L2-normalized n-gram vectors
(er/encode.py). The kernel tiles (M, N) into (block_m, block_n) MXU-
aligned tiles chosen from the autotuning lattice ``GEOMETRY_LATTICE``
(er/compiler/tune.py picks per-catalog geometry from the block-size
histogram); each grid step keeps one (block_m, d) LHS strip and one
(block_n, d) RHS strip in VMEM, computes the dot, applies the threshold,
and the entry's validity window / triangular mask / corner cuts via
global row/col indices.

The catalog kernels stream their strips through *double-buffered* manual
DMA: inputs stay in HBM (``memory_space=ANY``); two-deep VMEM strip
buffers prefetch tile t+1's LHS/RHS strips while tile t computes, so the
strip copy-in overlaps the MXU work instead of serializing ahead of it.

VMEM per step (f32, d feature dim), as Mosaic's compiler for TPU v5e
counts it against its 16 MiB default scoped-VMEM limit:
  strips   2 · (bm + bn) · d · 4 B          (two slots each side)
  score    2 · (bm + bn) · d · 4 B          (the full-f32 contraction's
           + bm · ⌈bn⌉₁₂₈ · 4 B             operand splits, accumulator)
  mask     2 · bm · ⌈bn⌉₁₂₈ · 4 B          (double-buffered output block,
                                             lanes padded to 128)
  compact  (bm² + bn² + bm·bn) · 4 B        (prefix-sum planes, slot ids)
           + 576 B per packed slot          (the row loop's (1, capacity)
                                             values, sublane-padded)
:func:`catalog_vmem_bytes` is that model plus 64 KiB of fixed overhead,
fitted to the compiler's own figures over every lattice geometry at
d = 256 (capacities 0–16,384 slots, never more than the tile's bm·bn
cells) and at d = 128 and 512 on six of them, and never below them, so
whatever :func:`check_vmem` admits under ``VMEM_BUDGET_BYTES``
compiles. The score term is loose for tiles no wider than 128 (the
compiler keeps those splits out of scoped VMEM) and within 3 % of the
compiler at 256 × 256.
Worst lattice candidate at the default capacity (256 × 256, d = 256,
1,024 slots): ≈ 3.6 MiB. er/compiler/tune.py filters lattice candidates
with the same model.

Two entry points:
  * :func:`pair_scores` — dense (M, N) scoring of two full matrices
    (kept as the simple test target and the building block the dense
    benchmarks use).
  * :func:`pair_scores_catalog` — the *tile-catalog* variant driving the
    fused plan executor (er/executor.py, DESIGN.md §Catalog): the grid is
    one-dimensional over catalog entries; a scalar-prefetch operand (the
    catalog, SMEM) feeds the strip DMAs so each grid step pulls the two
    feature strips named by the current entry. The kernel applies the
    entry's validity window, triangular mask and PairRange corner cuts
    in-kernel and writes a per-tile survivor mask; the host compacts
    survivors and runs the exact verifier only on them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pair_scores", "pair_scores_catalog",
           "pair_scores_catalog_compact", "catalog_tile_mask", "NCOLS",
           "GEOMETRY_LATTICE", "VMEM_BUDGET_BYTES", "COMPACT_CAPACITY",
           "SCORE_PRECISION", "resolve_capacity", "catalog_vmem_bytes",
           "check_vmem"]

# Every cosine score — the kernels here, their XLA twins and the paired
# dots of the distributed executors — is a full-f32 contraction, as the
# host reference's ``np.einsum`` is. At Mosaic's default precision the
# DS1-sized corpus kept 58,235 stage-1 survivors on a TPU v5e where the
# reference keeps 58,289, and the final match set lost two pairs and
# gained one.
SCORE_PRECISION = jax.lax.Precision.HIGHEST

# Catalog entry layout (int32 columns) — shared with er/executor.py and
# kernels/ref.py. Rows/cols below are *global* row indices of the feature
# matrices; a tile covers rows [a_tile·bm, (a_tile+1)·bm) × cols
# [b_tile·bn, (b_tile+1)·bn).
#   0 a_tile   LHS strip index (units of block_m)
#   1 b_tile   RHS strip index (units of block_n)
#   2 r0, 3 r1 valid row window [r0, r1)   (task bounds)
#   4 c0, 5 c1 valid col window [c0, c1)
#   6 tri      1 → keep only row < col (intra-block tasks)
#   7 lb_r, 8 lb_c   lower corner cut: keep (row > lb_r) | (col >= lb_c)
#   9 ub_r, 10 ub_c  upper corner cut: keep (row < ub_r) | (col <= ub_c)
#  11 band     > 0 → keep only col − row < band (Sorted Neighborhood's
#              window-w diagonal band, band = w; 0 = unconstrained)
#  12 reducer  owning reduce task (host-side attribution / device routing)
NCOLS = 13

# MXU-aligned (block_m, block_n) candidates the tile-geometry autotuner
# (er/compiler/tune.py) sweeps. Finite and static: a resident service
# compiles at most |lattice| kernel variants during warmup, then pins
# the winner — the zero-steady-state-recompile contract holds.
GEOMETRY_LATTICE = ((32, 32), (32, 64), (32, 128), (32, 256),
                    (64, 32), (64, 64), (64, 128), (64, 256),
                    (128, 32), (128, 64), (128, 128), (128, 256),
                    (256, 32), (256, 64), (256, 128), (256, 256))

# Mosaic's default scoped-VMEM limit on v5e is 16 MiB; the budget keeps
# headroom below it for compiler temporaries the model does not itemize.
VMEM_BUDGET_BYTES = 12 * 2 ** 20

# Lane width of a TPU vreg: the compact kernel's per-tile count row is
# one lane-dense (1, _LANES) block (Mosaic refuses a (1, 1) block), and
# a mask block narrower than a vreg still occupies whole lanes in VMEM.
_LANES = 128

# Packed survivor slots per tile of the compact kernel. The epilogue's
# work and VMEM grow linearly in the capacity, and a tile past it only
# costs an exact mask-path re-score of its chunk. The DS1-scale corpus
# (``make_products(114_000)``) peaks at 265 stage-1 survivors in a
# 128 × 128 tile, so 1,024 slots leave about 4× headroom.
COMPACT_CAPACITY = 1024


def resolve_capacity(block_m: int, block_n: int,
                     capacity: Optional[int] = None) -> int:
    """The capacity the compact kernel runs at for a (block_m, block_n)
    tile: ``capacity`` (default ``COMPACT_CAPACITY``) capped at the
    tile's bm·bn cells — more slots than cells can hold nothing. The
    executor, the mesh scorer and the autotuner's VMEM filter all
    resolve it here."""
    if capacity is None:
        capacity = COMPACT_CAPACITY
    return min(block_m * block_n, int(capacity))


def catalog_vmem_bytes(block_m: int, block_n: int, d: int,
                       capacity: int = 0) -> int:
    """VMEM bytes the compiler scopes for one catalog kernel: the mask
    kernel when ``capacity`` is 0, the compact kernel otherwise (see the
    module docstring for the fit). Shared with er/compiler/tune.py,
    which drops lattice candidates this model puts over
    ``VMEM_BUDGET_BYTES``."""
    lanes_n = -(-block_n // _LANES) * _LANES
    strips = 2 * (block_m + block_n) * d * 4
    score = strips + block_m * lanes_n * 4
    if capacity:
        body = ((block_m * block_m + block_n * block_n
                 + block_m * block_n) * 4 + 576 * capacity)
    else:
        body = 2 * block_m * lanes_n * 4
    return strips + score + body + 64 * 2 ** 10


def check_vmem(block_m: int, block_n: int, d: int, capacity: int = 0) -> None:
    """Lowering-time guard: raise before tracing a kernel whose step
    working set cannot fit VMEM, or a compact kernel with more slots than
    its tile has cells (outside the model's fit, and never useful)."""
    if capacity > block_m * block_n:
        raise ValueError(
            f"capacity={capacity} exceeds the {block_m}x{block_n} tile's "
            f"{block_m * block_n} cells; see resolve_capacity")
    need = catalog_vmem_bytes(block_m, block_n, d, capacity)
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"tile geometry ({block_m}, {block_n}) with d={d}"
            f"{f', capacity={capacity}' if capacity else ''} needs "
            f"{need} B VMEM per step > budget {VMEM_BUDGET_BYTES} B")


def catalog_tile_mask(entry, gi, gj):
    """The membership predicate of one catalog entry, shared by the Pallas
    kernel and the XLA reference. ``entry`` holds the NCOLS int32 scalars,
    ``gi``/``gj`` the (bm, bn) global row/col index grids."""
    keep = (gi >= entry[2]) & (gi < entry[3])
    keep &= (gj >= entry[4]) & (gj < entry[5])
    keep &= (entry[6] == 0) | (gi < gj)
    keep &= (gi > entry[7]) | (gj >= entry[8])
    keep &= (gi < entry[9]) | (gj <= entry[10])
    keep &= (entry[11] == 0) | (gj - gi < entry[11])
    return keep


def _kernel(a_ref, b_ref, o_ref, *, threshold: float, triangular: bool,
            block_m: int, block_n: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    a = a_ref[...]                       # (block_m, d)
    b = b_ref[...]                       # (block_n, d)
    s = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32)        # (block_m, block_n) MXU
    keep = s >= threshold
    if triangular:
        rows = i * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = j * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = keep & (rows < cols)
    o_ref[...] = jnp.where(keep, s, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("threshold", "triangular", "block_m", "block_n", "interpret"))
def pair_scores(a, b, *, threshold: float = 0.8, triangular: bool = False,
                block_m: int = 128, block_n: int = 128,
                interpret: bool = False):
    """Thresholded similarity scores of every (row of a) × (row of b).

    a: (M, d), b: (N, d) — rows L2-normalized. Returns (M, N) f32 with 0
    where score < threshold (or masked by x < y when ``triangular``).
    M, N are padded to tile multiples internally.
    """
    m, d = a.shape
    n = b.shape[0]
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    a_p = jnp.zeros((mp, d), a.dtype).at[:m].set(a)
    b_p = jnp.zeros((np_, d), b.dtype).at[:n].set(b)

    out = pl.pallas_call(
        functools.partial(
            _kernel, threshold=threshold, triangular=triangular,
            block_m=block_m, block_n=block_n),
        grid=(mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Catalog kernels: double-buffered strip DMA
# ---------------------------------------------------------------------------

def _strip_dma(pltpu, cat_ref, hbm, buf, sem, slot, idx, col, blk):
    """Async copy of the ``blk``-row strip named by catalog column ``col``
    of entry ``idx`` from HBM into scratch slot ``slot``."""
    return pltpu.make_async_copy(
        hbm.at[pl.ds(cat_ref[idx, col] * blk, blk), :],
        buf.at[slot], sem.at[slot])


def _load_strips(cat_ref, a_hbm, b_hbm, a_buf, b_buf, a_sem, b_sem,
                 block_m: int, block_n: int):
    """The double-buffer schedule shared by both catalog kernels: kick
    off entry t+1's strip DMAs into slot (t+1) % 2, then wait on slot
    t % 2 (started by step t−1; by step t itself at the grid edge) and
    return this entry's (block_m, d) / (block_n, d) strips. Safe because
    the TPU grid is sequential: slot s is only overwritten two steps
    after the step that computed from it."""
    from jax.experimental.pallas import tpu as pltpu

    t = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = jax.lax.rem(t, 2)
    nxt = jax.lax.rem(t + 1, 2)

    def start(s, idx):
        _strip_dma(pltpu, cat_ref, a_hbm, a_buf, a_sem, s, idx, 0,
                   block_m).start()
        _strip_dma(pltpu, cat_ref, b_hbm, b_buf, b_sem, s, idx, 1,
                   block_n).start()

    @pl.when(t == 0)
    def _():                              # warm-up: nobody prefetched t=0
        start(slot, t)

    @pl.when(t + 1 < nt)
    def _():                              # prefetch t+1 while t computes
        start(nxt, t + 1)

    _strip_dma(pltpu, cat_ref, a_hbm, a_buf, a_sem, slot, t, 0,
               block_m).wait()
    _strip_dma(pltpu, cat_ref, b_hbm, b_buf, b_sem, slot, t, 1,
               block_n).wait()
    return a_buf[slot], b_buf[slot]


def _entry_keep(cat_ref, a, b, *, threshold: float, block_m: int,
                block_n: int):
    """Score the current entry's strips and apply its predicate."""
    t = pl.program_id(0)
    s = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32)        # (block_m, block_n) MXU
    entry = [cat_ref[t, c] for c in range(NCOLS)]
    gi = entry[0] * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    gj = entry[1] * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return (s >= threshold) & catalog_tile_mask(entry, gi, gj)


def _catalog_kernel(cat_ref, a_hbm, b_hbm, o_ref, a_buf, b_buf, a_sem,
                    b_sem, *, threshold: float, block_m: int, block_n: int):
    a, b = _load_strips(cat_ref, a_hbm, b_hbm, a_buf, b_buf, a_sem, b_sem,
                        block_m, block_n)
    keep = _entry_keep(cat_ref, a, b, threshold=threshold,
                       block_m=block_m, block_n=block_n)
    o_ref[...] = keep[None].astype(jnp.float32)


def pltpu_prefetch(grid_spec: pl.GridSpec, num_scalar_prefetch: int,
                   scratch_shapes=None):
    """Build a PrefetchScalarGridSpec from a plain GridSpec.

    ``scratch_shapes`` (e.g. ``pltpu.VMEM`` buffers and DMA semaphores
    for manual double-buffered strip copies) pass through verbatim.
    """
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid_spec.grid,
        in_specs=grid_spec.in_specs,
        out_specs=grid_spec.out_specs,
        scratch_shapes=tuple(scratch_shapes or ()),
    )


def _catalog_specs(block_m: int, block_n: int, d: int, a_dtype, b_dtype):
    """HBM-resident input specs + double-buffered scratch for the catalog
    kernels: the features stay in ANY (= HBM) and the kernel pulls strips
    itself via :func:`_load_strips`."""
    from jax.experimental.pallas import tpu as pltpu

    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    scratch = [pltpu.VMEM((2, block_m, d), a_dtype),
               pltpu.VMEM((2, block_n, d), b_dtype),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.SemaphoreType.DMA((2,))]
    return in_specs, scratch


@functools.partial(
    jax.jit, static_argnames=("threshold", "block_m", "block_n", "interpret"))
def pair_scores_catalog(a, b, catalog, *, threshold: float = 0.8,
                        block_m: int = 128, block_n: int = 128,
                        interpret: bool = False):
    """Survivor masks for a flat catalog of (block_m, block_n) tiles.

    a: (M, d), b: (N, d) feature matrices (same array for single-source
    plans); catalog: (T, NCOLS) int32 — see the column layout above.
    Returns (T, block_m, block_n) f32 ∈ {0, 1}: 1 where the pair belongs
    to the entry's task AND its score passes ``threshold``.

    The catalog is the scalar-prefetch operand (SMEM); the features stay
    in HBM and each grid step's strips arrive by double-buffered manual
    DMA — entry t+1's strips are in flight while entry t's dot runs — so
    the whole plan executes as ONE pallas_call regardless of how many
    match tasks / blocks it covers, with copy-in off the critical path.
    """
    m, d = a.shape
    n = b.shape[0]
    t = catalog.shape[0]
    check_vmem(block_m, block_n, d)
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    a_p = jnp.zeros((mp, d), a.dtype).at[:m].set(a)
    b_p = jnp.zeros((np_, d), b.dtype).at[:n].set(b)

    in_specs, scratch = _catalog_specs(block_m, block_n, d,
                                       a_p.dtype, b_p.dtype)
    grid_spec = pl.GridSpec(
        grid=(t,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_m, block_n), lambda i, cat: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_catalog_kernel, threshold=threshold,
                          block_m=block_m, block_n=block_n),
        grid_spec=pltpu_prefetch(grid_spec, num_scalar_prefetch=1,
                                 scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((t, block_m, block_n), jnp.float32),
        interpret=interpret,
    )(catalog, a_p, b_p)


def _catalog_compact_kernel(cat_ref, a_hbm, b_hbm, packed_ref, count_ref,
                            a_buf, b_buf, a_sem, b_sem, dest_ref, *,
                            threshold: float, block_m: int, block_n: int,
                            capacity: int):
    a, b = _load_strips(cat_ref, a_hbm, b_hbm, a_buf, b_buf, a_sem, b_sem,
                        block_m, block_n)
    keep = _entry_keep(cat_ref, a, b, threshold=threshold,
                       block_m=block_m, block_n=block_n)
    kf = keep.astype(jnp.float32)

    # Row-major survivor ranks without scatter/sort (neither lowers to
    # Mosaic): prefix sums become triangular-ones matmuls, MXU-native.
    # Every operand is 0/1, so the sums are exact at any MXU precision.
    cc = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)
    upper = (cc < jj).astype(jnp.float32)          # strict upper (bn, bn)
    excl = jax.lax.dot_general(                    # within-row exclusive
        kf, upper, (((1,), (0,)), ((), ())),       # prefix of the mask
        preferred_element_type=jnp.float32)
    ii = jax.lax.broadcasted_iota(jnp.int32, (block_m, block_m), 0)
    rr = jax.lax.broadcasted_iota(jnp.int32, (block_m, block_m), 1)
    lower = (rr < ii).astype(jnp.float32)          # strict lower (bm, bm)
    above = jax.lax.dot_general(                   # survivors in rows
        lower, kf, (((1,), (0,)), ((), ())),       # above, per column
        preferred_element_type=jnp.float32)
    row_off = jnp.sum(above, axis=1, keepdims=True)            # (bm, 1)
    dest_ref[...] = jnp.where(keep, row_off + excl,
                              -1.0).astype(jnp.int32)  # slot, −1 = dead

    # packed[k] = Σ_p [dest_p == k] · (r·bn + j_p), one row r at a time:
    # the (capacity, bn) one-hot plane of row r contracts against
    # [column ids; ones], and the row offset r·bn is added after the
    # dot, so the MXU only ever sees integers ≤ 255 (exact in bf16).
    # Slots beyond the survivor count (and survivors past ``capacity``)
    # match nothing and stay 0.
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (capacity, block_n), 0)
    sel = jax.lax.broadcasted_iota(jnp.int32, (2, block_n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (2, block_n), 1)
    lhs = jnp.where(sel == 0, cols, 1).astype(jnp.float32)   # (2, bn)

    def row(r, acc):
        d_r = dest_ref[pl.ds(r, 1), :]                       # (1, bn)
        onehot = (d_r == k_iota).astype(jnp.float32)         # (capacity, bn)
        got = jax.lax.dot_general(
            lhs, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (2, capacity)
        base = (r * block_n).astype(jnp.float32)
        return acc + got[0:1] + base * got[1:2]

    acc = jax.lax.fori_loop(
        0, block_m, row, jnp.zeros((1, capacity), jnp.float32))
    packed_ref[...] = acc.astype(jnp.int32)[None]
    count = jnp.sum(jnp.sum(kf, axis=1, keepdims=True), axis=0,
                    keepdims=True)                           # (1, 1)
    count_ref[...] = jnp.broadcast_to(count.astype(jnp.int32),
                                      (1, _LANES))[None]


@functools.partial(
    jax.jit, static_argnames=("threshold", "block_m", "block_n", "capacity",
                              "interpret"))
def pair_scores_catalog_compact(a, b, catalog, *, threshold: float = 0.8,
                                block_m: int = 128, block_n: int = 128,
                                capacity: int = COMPACT_CAPACITY,
                                interpret: bool = False):
    """:func:`pair_scores_catalog` with an on-device survivor-compaction
    epilogue: instead of a (T, bm, bn) mask the host must ``np.nonzero``,
    each tile returns its survivors packed into ``capacity`` slots.

    Returns ``(packed, counts)``:
      * packed (T, capacity) int32 — tile-local flat pair ids
        ``i·block_n + j`` of the survivors, in row-major order; slots at
        index >= min(count, capacity) are 0.
      * counts (T, 1) int32 — the EXACT survivor count per tile, even
        when it exceeds ``capacity`` (the host detects overflow and
        falls back to the mask path; survivors past ``capacity`` are
        dropped from ``packed``).

    The epilogue is scatter-free (Mosaic has no scatter/sort): survivor
    pack slots come from prefix sums expressed as triangular-ones
    matmuls, and packing is a one-hot dot contraction — all MXU/VPU
    primitives, computed per tile while the scores are still in VMEM.
    Strips arrive by the same double-buffered DMA as the mask variant.
    """
    from jax.experimental.pallas import tpu as pltpu

    m, d = a.shape
    n = b.shape[0]
    t = catalog.shape[0]
    check_vmem(block_m, block_n, d, capacity)
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    a_p = jnp.zeros((mp, d), a.dtype).at[:m].set(a)
    b_p = jnp.zeros((np_, d), b.dtype).at[:n].set(b)

    in_specs, scratch = _catalog_specs(block_m, block_n, d,
                                       a_p.dtype, b_p.dtype)
    grid_spec = pl.GridSpec(
        grid=(t,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, capacity), lambda i, cat: (i, 0, 0)),
            pl.BlockSpec((1, 1, _LANES), lambda i, cat: (i, 0, 0)),
        ],
    )
    packed, counts = pl.pallas_call(
        functools.partial(_catalog_compact_kernel, threshold=threshold,
                          block_m=block_m, block_n=block_n,
                          capacity=capacity),
        grid_spec=pltpu_prefetch(
            grid_spec, num_scalar_prefetch=1,
            scratch_shapes=scratch + [pltpu.VMEM((block_m, block_n),
                                                 jnp.int32)]),
        out_shape=(jax.ShapeDtypeStruct((t, 1, capacity), jnp.int32),
                   jax.ShapeDtypeStruct((t, 1, _LANES), jnp.int32)),
        interpret=interpret,
    )(catalog, a_p, b_p)
    return packed.reshape(t, capacity), counts[:, 0, :1]
