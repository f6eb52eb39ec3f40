"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets XLA_FLAGS before first
jax init and everything else must see the default single device.
"""
from __future__ import annotations

import math

import jax

from ..runtime.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods over DCI).

    Uses the first prod(shape) devices so a 512-placeholder dry-run can
    build the single-pod mesh too.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices for {shape}, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            f"sets this automatically)"
        )
    return make_mesh(shape, axes, devices=devs[:need])


def make_debug_mesh(data: int = 2, model: int = 4):
    """Small mesh for multi-device CPU tests (8 fake devices)."""
    return make_mesh((data, model), ("data", "model"))


__all__ = ["make_production_mesh", "make_debug_mesh"]
