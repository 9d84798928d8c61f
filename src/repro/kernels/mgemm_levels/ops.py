"""jit'd wrappers + impl registration for the MXU level-decomposition path."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.mgemm import register_impl
from repro.kernels import interpret_mode

from .kernel import (
    metric2_levels_pallas,
    metric2_levels_tri_pallas,
    mgemm_levels_pallas,
)
from .planes import decode_bitplanes


def mgemm_levels(A, B, *, levels: int = 2, **kw):
    kw.setdefault("interpret", interpret_mode())
    return mgemm_levels_pallas(A, B, levels=levels, **kw)


def mgemm_levels_xla(A, B, *, levels: int = 2, out_dtype=jnp.float32):
    """XLA (non-Pallas) realization — what the distributed engines call on
    CPU, and what the dry-run lowers on the v5e mesh (plain dots partition
    cleanly under GSPMD)."""
    acc = jnp.zeros((A.shape[0], B.shape[1]), jnp.float32)
    for t in range(1, levels + 1):
        at = (A >= t).astype(jnp.bfloat16)
        bt = (B >= t).astype(jnp.bfloat16)
        acc += jnp.dot(at, bt, preferred_element_type=jnp.float32)
    return acc.astype(out_dtype)


# -- packed bit-plane entry points (planes built once, not per call) --------


def metric2_levels(Pa, Pb, sa, sb, *, epilogue, **kw):
    """Fused metric kernel on pre-encoded packed planes (rectangular grid)."""
    kw.setdefault("interpret", interpret_mode())
    return metric2_levels_pallas(Pa, Pb, sa, sb, epilogue=epilogue, **kw)


def metric2_levels_tri(P, s, *, epilogue, **kw):
    """Fused diagonal-block plane kernel (triangular tile schedule)."""
    kw.setdefault("interpret", interpret_mode())
    return metric2_levels_tri_pallas(P, s, epilogue=epilogue, **kw)


def mgemm_levels_planes(Pa, Pb, **kw):
    """Plane-contraction-only MXU kernel: the unfused numerator when the
    reduction is split over ranks (``n_pf > 1``) and the epilogue must wait
    for the psum."""
    kw.setdefault("interpret", interpret_mode())
    za = jnp.zeros((Pa.shape[2],), jnp.float32)
    zb = jnp.zeros((Pb.shape[2],), jnp.float32)
    return metric2_levels_pallas(Pa, Pb, za, zb, epilogue=None, **kw)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def mgemm_levels_planes_xla(Pa, Pb, *, out_dtype=jnp.float32):
    """XLA plane contraction: unpack once, then ``levels`` plain MXU/CPU
    dots.  The hoisted form of ``mgemm_levels_xla`` — comparisons against
    fp32 data are gone from the hot loop entirely.  The A-side planes are
    transposed to row-major before the dots (a one-off (L, K, m) shuffle);
    contracting the leading axis directly lowers ~4x slower on CPU."""
    at = decode_bitplanes(Pa).astype(jnp.bfloat16).transpose(0, 2, 1)
    bt = decode_bitplanes(Pb).astype(jnp.bfloat16)  # (levels, K, n)
    acc = jnp.zeros((Pa.shape[2], Pb.shape[2]), jnp.float32)
    for t in range(Pa.shape[0]):
        acc += jnp.dot(at[t], bt[t], preferred_element_type=jnp.float32)
    return acc.astype(out_dtype)


register_impl("levels", mgemm_levels)
register_impl("levels_xla", mgemm_levels_xla)
