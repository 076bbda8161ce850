"""Production mesh builders (assignment contract).

Functions, not module-level constants, so importing this module never
touches jax device state. The production target is TPU v5e:
  single pod : (16, 16)    -> ("data", "model"), 256 chips
  multi-pod  : (2, 16, 16) -> ("pod", "data", "model"), 512 chips
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the sharding constraints and
    shard_map specs of this repo name bare PartitionSpecs and leave
    propagation to the compiler, which Explicit axes (the make_mesh
    default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))


def make_tp_mesh(tp: int):
    """The serving `--tp N` path: a ("data","model") mesh with an N-way
    model axis for the tensor-parallel paged engine. Validates the device
    count up front with an actionable message (on CPU, force devices with
    XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    n = len(jax.devices())
    if tp < 1:
        raise ValueError(f"--tp must be >= 1, got {tp}")
    if n % tp != 0:
        raise ValueError(
            f"--tp {tp} does not divide the {n} visible jax devices; on "
            f"CPU set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"before jax import to fake a multi-device host")
    return make_host_mesh(model_parallel=tp)
