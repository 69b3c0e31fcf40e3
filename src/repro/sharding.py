"""The ER executor's device mesh: corpus rows over ``data``, the
hashed-n-gram feature dimension over ``model``."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_er_mesh"]


def make_er_mesh(n_data: int, n_model: int = 1) -> Mesh:
    """The ER executor's 2-D ``(data, model)`` mesh: corpus rows shard
    over ``data``, the hashed-n-gram feature dimension over ``model``
    (``compiler.execute(model_axis="model")`` psums the partial tile
    scores). ``n_model=1`` is the classic 1-D data mesh every existing
    ER path runs on. Devices reshape row-major — the ``model`` axis
    varies fastest, keeping a model group's devices adjacent (the
    higher-bandwidth hop)."""
    devices = np.asarray(jax.devices())
    if devices.size < n_data * n_model:
        raise ValueError(f"need {n_data * n_model} devices for a "
                         f"({n_data}, {n_model}) mesh, "
                         f"have {devices.size}")
    grid = devices[:n_data * n_model].reshape(n_data, n_model)
    return Mesh(grid, ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
