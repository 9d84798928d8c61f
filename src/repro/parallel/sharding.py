"""Sharding rules: logical parallelism mapping for the LM stack.

Megatron-style tensor parallelism over the "model" axis, data parallelism
over "data" (x "pod"), realized through GSPMD:

* params — column-parallel QKV / gate-up (shard the output feature dim),
  row-parallel out/down projections (shard the input feature dim),
  vocab-parallel embedding + logits; MoE experts shard their hidden (d_ff)
  dim over "model" ("expert-internal TP" — exact for any expert count,
  no capacity/divisibility coupling to the mesh; see DESIGN.md §4).
* activations — batch over ("pod","data"); the residual stream is kept
  replicated over "model" between blocks, with XLA inserting the Megatron
  all-reduces after row-parallel matmuls.

``shard()`` applies a constraint only when a mesh with the named axes is
active, so the same model code runs on a laptop CPU (no mesh), under the
512-device dry-run, and on a real pod.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE: list[Mesh] = []
_DP_ONLY: list[bool] = []
DATA_AXES = ("pod", "data")  # folded batch axes (pod may be absent)


@contextmanager
def dp_only_mode():
    """ZeRO-3 axis remapping (§Perf): the "model" axis joins data
    parallelism — batch shards over ("data","model"), tensor-parallel
    entries are dropped, parameters fully shard over all axes.  Constraints
    written for the TP layout are translated on the fly."""
    _DP_ONLY.append(True)
    try:
        yield
    finally:
        _DP_ONLY.pop()


def dp_only_active() -> bool:
    return bool(_DP_ONLY)


def _translate_dp_only(spec: P) -> P:
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            if tuple(entry) == DATA_AXES:
                out.append(("data", "model"))  # batch over both in-pod axes
            else:
                out.append(tuple(a for a in entry if a != "model") or None)
        else:
            out.append(None if entry == "model" else entry)
    return P(*out)


@contextmanager
def use_mesh(mesh: Mesh | None):
    if mesh is None:
        yield
        return
    _ACTIVE.append(mesh)
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _ACTIVE.pop()


def active_mesh() -> Mesh | None:
    return _ACTIVE[-1] if _ACTIVE else None


def _filter_spec(spec: P, mesh: Mesh, shape: tuple | None = None) -> P:
    """Drop axis names the active mesh doesn't have (e.g. 'pod' single-pod)
    and entries that don't divide the dimension (JAX rejects uneven input
    shardings — e.g. granite's vocab 49155 on a 16-wide axis stays
    replicated)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = tuple(a for a in axes if a in mesh.axis_names)
        if shape is not None and kept:
            size = 1
            for a in kept:
                size *= mesh.shape[a]
            if i >= len(shape) or shape[i] % size != 0:
                # try the first axis alone before giving up
                kept = tuple(
                    a for a in kept if shape[i] % mesh.shape[a] == 0
                )[:1]
        if not kept:
            out.append(None)
        elif len(kept) == 1 and not isinstance(entry, (tuple, list)):
            out.append(kept[0])
        else:
            out.append(kept)
    return P(*out)


def shard(x, *spec_entries):
    """with_sharding_constraint if a mesh is active, else identity."""
    mesh = active_mesh()
    if mesh is None:
        return x
    spec = P(*spec_entries)
    if dp_only_active():
        spec = _translate_dp_only(spec)
    spec = _filter_spec(spec, mesh, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_spec(*rest) -> tuple:
    """(('pod','data'), *rest) — batch dim over the folded data axes."""
    return (DATA_AXES, *rest)


def named_sharding(mesh: Mesh, *entries, shape: tuple | None = None) -> NamedSharding:
    return NamedSharding(mesh, _filter_spec(P(*entries), mesh, shape=shape))
