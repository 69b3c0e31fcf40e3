"""The stage-1 catalog kernels through the TPU compiler, without a chip.

Every kernel here is compiled for one chip of a described ``v5e:2x2``
topology at d = 256 and the DS1 corpus's 114,000 feature rows, with a
1,024-tile catalog: the mask kernel and the compact kernel at its
resolved default capacity, at the default 128×128 tile and at the four
corners of ``GEOMETRY_LATTICE``. Each compile runs with Mosaic's scoped
VMEM limit set to what ``catalog_vmem_bytes`` predicts, so a pass shows
both that the kernel lowers and that the VMEM model is never below the
compiler's own figure — whatever ``check_vmem`` admits compiles. The
RepSN halo scorer is compiled for all four chips of the topology at the
``ds2_sn_mesh4`` cell's size.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.kernels import pair_sim

D, N_ROWS, TILES = 256, 114_000, 1024
GEOMETRIES = ((128, 128), (32, 32), (32, 256), (256, 32), (256, 256))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, monkeypatch, bm: int, bn: int, capacity: int):
    """Compile one catalog kernel (compact when ``capacity`` > 0) with
    the scoped VMEM limit at the model's figure; returns the HLO text."""
    from jax.experimental.pallas import tpu as pltpu

    limit = pair_sim.catalog_vmem_bytes(bm, bn, D, capacity)
    real = pl.pallas_call

    def limited(*args, **kwargs):
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=limit)
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", limited)
    feats = jax.ShapeDtypeStruct((N_ROWS, D), jnp.float32,
                                 sharding=one_chip)
    cat = jax.ShapeDtypeStruct((TILES, pair_sim.NCOLS), jnp.int32,
                               sharding=one_chip)
    kw = dict(threshold=0.55, block_m=bm, block_n=bn)
    if capacity:
        kernel = pair_sim.pair_scores_catalog_compact.__wrapped__
        kw["capacity"] = capacity
    else:
        kernel = pair_sim.pair_scores_catalog.__wrapped__
    fn = jax.jit(lambda a, b, c: kernel(a, b, c, **kw))
    return fn.lower(feats, feats, cat).compile().as_text()


@pytest.mark.parametrize("kernel", ("mask", "compact"))
@pytest.mark.parametrize("geom", GEOMETRIES)
def test_catalog_kernel_compiles_for_v5e(one_chip, monkeypatch, geom,
                                         kernel):
    bm, bn = geom
    capacity = pair_sim.resolve_capacity(bm, bn) if kernel == "compact" \
        else 0
    pair_sim.check_vmem(bm, bn, D, capacity)   # the model admits it ...
    text = _compile(one_chip, monkeypatch, bm, bn, capacity)
    assert "tpu_custom_call" in text           # ... and Mosaic agrees


def test_sn_halo_scorer_compiles_for_four_v5e_chips(topo, monkeypatch):
    """The RepSN halo scorer at ``ds2_sn_mesh4``'s size (173,750 rows
    over four chips, w = 1,000): the Pallas kernel, fed by neighbour
    ``collective-permute`` hops and no all-gather."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.er.compiler import make_scorer
    from repro.kernels import ops

    monkeypatch.setattr(ops, "resolve_impl", lambda impl: impl)
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    scorer = make_scorer(mesh, "data", mode="halo", threshold=0.55,
                         impl="pallas", halo=999, compact=True)
    on_data = NamedSharding(mesh, P("data"))
    feats = jax.ShapeDtypeStruct((4 * -(-173_750 // 4), D), jnp.float32,
                                 sharding=on_data)
    tiles = jax.ShapeDtypeStruct((4, TILES, pair_sim.NCOLS), jnp.int32,
                                 sharding=on_data)
    text = scorer._fn.lower(feats, tiles).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text and "all-gather" not in text
