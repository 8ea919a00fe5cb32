"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state. Every mesh uses `Auto` axes: the model code places values with
`with_sharding_constraint`, which refuses the `Explicit` axes
`jax.make_mesh` now gives by default.
"""
from __future__ import annotations

import numpy as np


def _auto_mesh(shape, axes, devices):
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod, 256 chips) or 2×16×16 (2 pods, 512 chips)."""
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"need {need} devices for {shape}, have {len(devices)} — run via "
            "launch/dryrun.py (it forces 512 host devices)")
    return _auto_mesh(shape, axes, devices[:need])


def make_engine_mesh(data: int, model: int = 1):
    """Mesh for the SHARDED real serving plane: one data-axis rank per
    decode DP unit (the merged paged cache's slot/pool dims shard over
    "data"), `model` ranks of tensor parallelism inside each DP, over
    the first data×model devices the process sees."""
    import jax
    need = data * model
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"sharded plane needs {need} devices for a ({data},{model}) "
            f"data×model mesh, have {len(devices)}")
    return _auto_mesh((data, model), ("data", "model"), devices[:need])


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires forced host device count)."""
    import jax
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    need = int(np.prod(shape))
    return _auto_mesh(shape, axes, jax.devices()[:need])
