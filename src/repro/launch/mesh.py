"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state — jax locks the device count on first
backend initialisation, and only launch/dryrun.py (which sets XLA_FLAGS
before any import) should ever see 512 placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips single pod, or 2 pods x 16 x 16 = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_sweep_mesh(n_devices: "int | None" = None, axis: str = "data"):
    """1-D mesh over the sweep `data` axis (stacked config batches).

    The sharded sweep engine (repro.distributed.sweep.MeshPlan) splits
    each structure group's leading config axis across this mesh; a
    single named axis keeps the shard_map specs and the solver's
    cross-shard convergence pmax trivially aligned. The axis is
    `AxisType.Auto`: the engine trims pad lanes and indexes per-config
    outputs with plain slicing, which an explicit-sharding axis (the
    `jax.make_mesh` default) rejects for a sharded operand.
    """
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def make_dev_mesh(n_devices: "int | None" = None):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = n_devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"))
