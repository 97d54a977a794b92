"""Production meshes. Functions only — importing this module never touches
jax device state.

Single pod: (16, 16) ("data", "model")    = 256 chips (one v5e pod)
Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips

The ``pod`` axis is the expensive fabric (DCN / cross-pod): the cMPI-derived
rule is that it must carry thin traffic only (hierarchical collectives,
optionally compressed) — see distributed/schedules.py.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"=512 before any jax import (launch/dryrun.py does)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over the first devices: a forced host device count on
    the CPU, or real chips (``chip_smoke.py --four-chips`` puts (2, 2)
    and (4,) meshes on a four-chip host)."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


MESHES = {
    "single": dict(multi_pod=False, chips=256, tag="pod16x16"),
    "multi": dict(multi_pod=True, chips=512, tag="pod2x16x16"),
}
