"""Mesh construction helpers.

Two mesh families:

* **CoMet meshes** — axes ("pf", "pv", "pr") matching the paper's three
  parallelism axes (vector elements / vector number / round-robin).  The ring
  runs over "pv".  ``make_comet_mesh`` walks the chips in boustrophedon
  order over their grid coordinates and makes "pv" the fastest-varying
  axis of that walk, so consecutive "pv" coordinates are ICI neighbours:
  on a v5e 2x2 the n_pv=4 ring is (0,0) (1,0) (1,1) (0,1), closing back to
  (0,0) over a link, and each n_pv=2 pair is one link (the paper needed a
  *random* rank permutation to dodge Cray Gemini throttling — on a chip
  grid the ring maps natively).  Devices without grid coordinates (CPU)
  keep ``jax.devices()`` order.

* **Production LM meshes** — built in ``repro.launch.mesh`` per the dry-run
  contract: (16, 16) -> ("data", "model") and (2, 16, 16) ->
  ("pod", "data", "model").

``comet_mesh_from_production`` reinterprets a production mesh's device array
for the similarity engine so the same launcher serves both workload families:
"pv" <- data (x pod), and "model" splits into pf x pr.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

__all__ = ["make_comet_mesh", "comet_mesh_from_production"]

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


def comet_mesh_from_production(mesh: Mesh, n_pf: int = 1) -> Mesh:
    """Reshape a ("data","model") or ("pod","data","model") mesh into the
    comet ("pf","pv","pr") axes: pv <- (pod x) data, model splits pf x pr."""
    devs = mesh.devices  # (data, model) or (pod, data, model)
    if devs.ndim == 3:
        devs = devs.reshape(-1, devs.shape[-1])  # fold pod into data
    n_pv, n_model = devs.shape
    assert n_model % n_pf == 0, (n_model, n_pf)
    n_pr = n_model // n_pf
    arr = devs.reshape(n_pv, n_pf, n_pr).transpose(1, 0, 2)
    return Mesh(arr, COMET_AXES)
