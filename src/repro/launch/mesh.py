"""Production mesh construction (dry-run contract).

Single pod: (16, 16) -> ("data", "model") — one v5e pod, 256 chips.
Multi-pod:  (2, 16, 16) -> ("pod", "data", "model") — 512 chips.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()[:need]
    if len(devices) < need:
        raise RuntimeError(
            f"need {need} devices for the production mesh, have {len(devices)}"
            " (dry-run sets --xla_force_host_platform_device_count=512)"
        )
    return jax.make_mesh(
        shape, axes, (AxisType.Auto,) * len(axes), devices=devices
    )
