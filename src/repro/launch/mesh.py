"""Mesh construction: every mesh of the program is built by `make_mesh`.

NOTE: importing this module never touches jax device state; meshes are built
inside functions only (the dry-run forces 512 host devices *before* any jax
import — see dryrun.py).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """A mesh over the first prod(shape) devices, every axis `Auto`.

    jax.make_mesh defaults to `AxisType.Explicit` axes, under which any
    gather whose output sharding JAX cannot infer — the query-padding
    slice of `distributed_search` among them — raises ShardingTypeError.
    The shard_map executors of this program place data themselves, so
    their meshes are Auto: the compiler propagates shardings as before.
    """
    shape, axes = tuple(shape), tuple(axes)
    n = int(np.prod(shape))
    devices = jax.devices()[:n] if devices is None else devices
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (single pod, 256 chips) or 2x16x16 (two pods, 512 chips).

    REPRO_MESH_OVERRIDE="4,4" (or "2,4,4" for multi-pod) substitutes a
    smaller mesh — used by the test suite to exercise the dry-run machinery
    on a handful of forced host devices.
    """
    import os
    override = os.environ.get("REPRO_MESH_OVERRIDE")
    if override:
        shape = tuple(int(v) for v in override.split(","))
        axes = ("pod", "data", "model")[-len(shape):]
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — "
            "run under dryrun.py (XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512) or on real hardware")
    return make_mesh(shape, axes, devices=devices[:n])


# TPU v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW_PER_LINK = 50e9       # bytes/s per link (~50 GB/s)
