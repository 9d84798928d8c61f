"""CoMet mesh construction.

Axes ("pf", "pv", "pr") match the paper's three parallelism axes (vector
elements / vector number / round-robin).  The ring runs over "pv".
``make_comet_mesh`` walks the chips in boustrophedon order over their grid
coordinates and makes "pv" the fastest-varying axis of that walk, so
consecutive "pv" coordinates are ICI neighbours: on a v5e 2x2 the n_pv=4
ring is (0,0) (1,0) (1,1) (0,1), closing back to (0,0) over a link, and
each n_pv=2 pair is one link (the paper needed a *random* rank permutation
to dodge Cray Gemini throttling — on a chip grid the ring maps natively).
Devices without grid coordinates (CPU) keep ``jax.devices()`` order.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

__all__ = ["make_comet_mesh"]

COMET_AXES = ("pf", "pv", "pr")


def _ici_order(devices) -> list:
    """Chips sorted into a boustrophedon walk of their (x, y) coordinates,
    so consecutive chips share an ICI link; coordinate-less devices keep
    their order."""
    if not all(hasattr(d, "coords") for d in devices):
        return devices

    def key(d):
        x, y, *rest = d.coords
        return (tuple(rest), y, x if y % 2 == 0 else -x)

    return sorted(devices, key=key)


def make_comet_mesh(n_pf: int = 1, n_pv: int = 1, n_pr: int = 1, devices=None) -> Mesh:
    devices = list(jax.devices()) if devices is None else list(devices)
    need = n_pf * n_pv * n_pr
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    walk = np.array(_ici_order(devices)[:need])
    # "pv" innermost in the walk: ring neighbours are walk neighbours
    arr = walk.reshape(n_pf, n_pr, n_pv).transpose(0, 2, 1)
    return Mesh(arr, COMET_AXES)

