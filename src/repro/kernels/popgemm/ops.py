"""jit'd wrappers for the binary popcount bit-GEMM path.

No ``register_impl`` here: popcount is not an ``impl`` name — it is the
``levels == 1`` specialization of ``impl="levels"``, selected by the
``TileExecutor`` (``path == "fused-popcount"``), so request knobs stay
unchanged and binary campaigns speed up without opting into anything.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode

from .kernel import (
    metric2_pop_pallas,
    metric2_pop_tri_pallas,
    threeway_batch_pop_pallas,
)


def metric2_pop(Pa, Pb, sa, sb, *, epilogue, **kw):
    """Fused metric kernel on a binary packed plane (rectangular grid)."""
    kw.setdefault("interpret", interpret_mode())
    return metric2_pop_pallas(Pa, Pb, sa, sb, epilogue=epilogue, **kw)


def metric2_pop_tri(P, s, *, epilogue, **kw):
    """Fused diagonal-block popcount kernel (triangular tile schedule)."""
    kw.setdefault("interpret", interpret_mode())
    return metric2_pop_tri_pallas(P, s, epilogue=epilogue, **kw)


def pop_planes(Pa, Pb, **kw):
    """Popcount-contraction-only kernel: the raw-numerator form used when
    the reduction is split over ranks (``n_pf > 1``) or deferred across
    streamed chunks and the epilogue must wait for the psum/merge."""
    kw.setdefault("interpret", interpret_mode())
    za = jnp.zeros((Pa.shape[2],), jnp.float32)
    zb = jnp.zeros((Pb.shape[2],), jnp.float32)
    return metric2_pop_pallas(Pa, Pb, za, zb, epilogue=None, **kw)


def threeway_batch_pop(Pown, PX, Pright, **kw):
    """3-way pipeline-slice popcount kernel (packed AND stays packed)."""
    kw.setdefault("interpret", interpret_mode())
    return threeway_batch_pop_pallas(Pown, PX, Pright, **kw)
