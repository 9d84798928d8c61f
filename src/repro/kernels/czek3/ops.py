"""jit'd wrappers for the fused 3-way slice kernels.

These are the entry points the ``TileExecutor`` dispatches 3-way pipeline
slices to (``TileExecutor.threeway_slice``) — they select interpret mode
off-TPU and forward to the Pallas kernels in ``kernel.py``.  The
``*_levels`` variant consumes packed bit-planes in the documented
(levels, kb, w) uint8 layout (docs/BITPLANE_FORMAT.md); on the plane-ring
campaign path those planes are byte-range views of the ring payload.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode

from .kernel import (
    threeway_batch_levels_pallas,
    threeway_batch_pallas,
    threeway_step_pallas,
)


def threeway_step(own, x, right, *, combine, **kw):
    """Metric-generic fused 3-way pipeline step (X_j never touches HBM).

    own (n_f, m), x (n_f,) single pipeline column, right (n_f, n) ->
    (m, n).  Single-column form kept for benchmarks/oracles; the executor
    runs the batched variants below."""
    kw.setdefault("interpret", interpret_mode())
    return threeway_step_pallas(own, x, right, combine=combine, **kw)


def threeway_batch(own, X, right, *, combine, **kw):
    """All L pipeline columns of one slice in a single fused launch.

    own (n_f, m), X (n_f, L), right (n_f, n) -> (L, m, n) value-operand
    form (``path3 == "fused-vpu"``)."""
    kw.setdefault("interpret", interpret_mode())
    return threeway_batch_pallas(own, X, right, combine=combine, **kw)


def threeway_batch_levels(Pown, PX, Pright, **kw):
    """Level-decomposed batched slice on packed bit-planes (min combine).

    Pown (levels, kb, m), PX (levels, kb, L), Pright (levels, kb, n) ->
    (L, m, n).  The X_j plane is a packed AND in VMEM (one VPU op per 8
    fields), the contraction runs on the MXU; operands arrive pre-encoded
    (ring payload or ``encode_bitplanes``), never re-encoded here."""
    kw.setdefault("interpret", interpret_mode())
    return threeway_batch_levels_pallas(Pown, PX, Pright, **kw)


def czek3_step(own, x, right, **kw):
    kw.setdefault("combine", jnp.minimum)
    return threeway_step(own, x, right, **kw)
