"""jit'd public wrappers for the mGEMM Pallas kernels + impl registration.

Wrappers interpret automatically off-TPU (kernel-body-on-CPU), which is how
the CPU test harness and CI drive every kernel path.
"""
from __future__ import annotations

from repro.core.mgemm import register_impl
from repro.kernels import interpret_mode

from .kernel import (
    czek2_metric_pallas,
    metric2_pallas,
    metric2_tri_pallas,
    mgemm_pallas,
)


def mgemm(A, B, **kw):
    """Pallas mGEMM; interprets automatically off-TPU (kernel-body-on-CPU)."""
    kw.setdefault("interpret", interpret_mode())
    return mgemm_pallas(A, B, **kw)


def czek2_metric(A, B, sa, sb, **kw):
    kw.setdefault("interpret", interpret_mode())
    return czek2_metric_pallas(A, B, sa, sb, **kw)


def metric2_tiles(A, B, sa, sb, *, combine, epilogue, **kw):
    """Generated fused metric kernel, rectangular tile grid."""
    kw.setdefault("interpret", interpret_mode())
    return metric2_pallas(A, B, sa, sb, combine=combine, epilogue=epilogue, **kw)


def metric2_tri(A, B, sa, sb, *, combine, epilogue, **kw):
    """Generated fused metric kernel, triangular (diagonal-block) grid.

    Returns packed (P, bt, bt) tiles; see ``unpack_tri_tiles``."""
    kw.setdefault("interpret", interpret_mode())
    return metric2_tri_pallas(A, B, sa, sb, combine=combine, epilogue=epilogue, **kw)


register_impl("pallas", mgemm)
