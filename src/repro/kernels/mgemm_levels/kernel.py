"""Pallas TPU kernel: level-decomposition mGEMM on the MXU (beyond-paper).

For inputs quantized to integer levels {0, 1, ..., L}:

    min(a, b) = sum_{t=1}^{L} 1[a >= t] * 1[b >= t]

so the min-plus contraction equals a sum of L *ordinary* GEMMs of 0/1
indicator matrices — which the 128x128 MXU executes at bf16 peak
(197 TFLOP/s on v5e) instead of the ~1 TOP/s VPU rate of the faithful
kernel.  Exact for integer data with values <= L (SNP allele counts are
{0,1,2}; the paper's companion CCC work uses 2-3 bit codes).  This is the
TPU-native generalization of the paper's §2.3 observation that the binary
(Sorenson) case maps to fast bit arithmetic.

Indicator construction happens in VMEM per tile (on the VPU, overlapped by
the MXU matmuls), so HBM traffic is identical to a plain GEMM of the raw
operands.

Cost: L * 2*M*N*K MXU FLOPs; for L <= 4 a ~25-50x win over the VPU kernel on
the compute roofline term (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mgemm.kernel import _tri_decode, store_tri_tile

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512
# packed-plane kernels tile the contraction in BYTES (8 fields per byte)
DEFAULT_BKB = 64


def _levels_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k_steps: int, levels: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc = jnp.zeros_like(acc_ref)
    for t in range(1, levels + 1):  # static unroll: L MXU matmuls per tile
        at = (a >= t).astype(jnp.bfloat16)
        bt = (b >= t).astype(jnp.bfloat16)
        acc += jnp.dot(at, bt, preferred_element_type=jnp.float32)
    acc_ref[...] += acc

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("levels", "bm", "bn", "bk", "interpret", "out_dtype")
)
def mgemm_levels_pallas(
    A,
    B,
    *,
    levels: int,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Exact min-plus GEMM for integer-valued A, B in [0, levels]."""
    m, k = A.shape
    k2, n = B.shape
    assert k == k2
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        A = jnp.pad(A, ((0, mp), (0, kp)))  # pad 0 -> indicator 0 -> no contribution
    if np_ or kp:
        B = jnp.pad(B, ((0, kp), (0, np_)))
    M, K = A.shape
    N = B.shape[1]
    n_k_steps = K // bk
    grid = (M // bm, N // bn, n_k_steps)
    out = pl.pallas_call(
        functools.partial(_levels_kernel, n_k_steps=n_k_steps, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(A, B)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Packed bit-plane kernels (the fused campaign path)
#
# Operands are pre-encoded packed planes in the documented wire layout
# (docs/BITPLANE_FORMAT.md; encoders in ``planes.py``): (levels, kb, w)
# uint8, field-major, 8 plane-bits per byte LSB-first along the
# contraction axis.  ``_unpack_plane_tile`` / ``_plane_matmuls`` below are
# THE shared realization of that layout — the 3-way slice kernel
# (kernels/czek3) imports them so the engines can never drift.
# Each K-tile unpacks its byte tile in VMEM (VPU work,
# overlapped by the MXU) and performs ``levels`` MXU ``dot_general``s into a
# fp32 VMEM accumulator; the flush applies the metric's ``assemble_tile``
# epilogue in place, so — like the VPU fused path — the numerator block
# never round-trips HBM.  Bit-planes are built ONCE per campaign instead of
# ``(V >= t)`` per ring step, and the packed operands are what the ring
# carries (L/32 of the fp32 wire traffic).
# ---------------------------------------------------------------------------


def _unpack_plane_tile(bytes_u8):
    """(bkb, w) packed uint8 -> (8*bkb, w) bf16 indicator tile, LSB-first."""
    kb, w = bytes_u8.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    bits = (bytes_u8.astype(jnp.int32)[:, None, :] >> shifts) & 1
    return bits.reshape(kb * 8, w).astype(jnp.bfloat16)


def _plane_matmuls(pa, pb, levels: int):
    """sum_t unpack(pa[t])^T-free field-major contraction on the MXU.

    pa (levels, bkb, bm), pb (levels, bkb, bn) packed tiles; contracts the
    unpacked field axis (axis 0 of each plane tile) -> (bm, bn) fp32."""
    acc = None
    for t in range(levels):  # static unroll: L MXU matmuls per K-tile
        at = _unpack_plane_tile(pa[t])
        bt = _unpack_plane_tile(pb[t])
        part = jax.lax.dot_general(
            at, bt, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part
    return acc


def _levels_fused_kernel(
    pa_ref, pb_ref, sa_ref, sb_ref, o_ref, acc_ref,
    *, n_k_steps: int, levels: int, epilogue,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _plane_matmuls(pa_ref[...], pb_ref[...], levels)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        vals = acc if epilogue is None else epilogue(
            acc, sa_ref[...], sb_ref[...]
        )
        o_ref[...] = vals.astype(o_ref.dtype)


def _levels_fused_tri_kernel(
    pa_ref, pb_ref, sa_ref, sb_ref, o_ref, acc_ref,
    *, n_k_steps: int, levels: int, epilogue, T: int,
):
    """Triangular-schedule plane kernel for diagonal blocks (paper §5):
    grid axis 0 walks only the ``tj >= ti`` tiles; on-diagonal tiles are
    masked to the strict upper triangle at flush."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _plane_matmuls(pa_ref[...], pb_ref[...], levels)
    p = pl.program_id(0)

    @pl.when(pl.program_id(1) == n_k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        vals = acc if epilogue is None else epilogue(
            acc, sa_ref[...], sb_ref[...]
        )
        store_tri_tile(o_ref, vals, p, T)


def _pad_planes(P, last_pad: int, kb_pad: int):
    """Zero-pad packed planes: zero bytes are zero plane bits -> inert."""
    if last_pad or kb_pad:
        P = jnp.pad(P, ((0, 0), (0, kb_pad), (0, last_pad)))
    return P


def _pad_stat(s, pad: int):
    """Stats pad with ZERO so ``safe_denom`` covers pad rows/columns exactly
    like all-zero real vectors (same contract as mgemm._pad_operands)."""
    return jnp.pad(jnp.asarray(s, jnp.float32).reshape(-1), (0, pad))


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "bm", "bn", "bkb", "interpret", "out_dtype"),
)
def metric2_levels_pallas(
    Pa,
    Pb,
    sa,
    sb,
    *,
    epilogue,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkb: int = DEFAULT_BKB,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Fused 2-way metric kernel on packed bit-planes (rectangular grid).

    Pa (levels, kb, m) / Pb (levels, kb, n) packed planes of the two vector
    blocks; sa (m,) / sb (n,) per-vector stats.  Returns
    ``epilogue(sum_t plane_t(A)^T @ plane_t(B), sa, sb)`` — for leveled
    integer data this is exactly the metric on the min-plus numerator.
    ``epilogue=None`` returns the raw fp32 numerator (the unfused plane
    contraction used when ``n_pf > 1`` splits the reduction across ranks).
    """
    levels, kb, m = Pa.shape
    n = Pb.shape[2]
    assert Pb.shape[:2] == (levels, kb), (Pa.shape, Pb.shape)
    mp, np_, kbp = (-m) % bm, (-n) % bn, (-kb) % bkb
    Pa = _pad_planes(Pa, mp, kbp)
    Pb = _pad_planes(Pb, np_, kbp)
    sa = _pad_stat(sa, mp)[:, None]
    sb = _pad_stat(sb, np_)[None, :]
    M, N, KB = m + mp, n + np_, kb + kbp
    n_k_steps = KB // bkb
    grid = (M // bm, N // bn, n_k_steps)
    out = pl.pallas_call(
        functools.partial(
            _levels_fused_kernel, n_k_steps=n_k_steps, levels=levels,
            epilogue=epilogue,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((levels, bkb, bm), lambda i, j, t: (0, t, i)),
            pl.BlockSpec((levels, bkb, bn), lambda i, j, t: (0, t, j)),
            pl.BlockSpec((bm, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(Pa, Pb, sa, sb)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "bt", "bkb", "interpret", "out_dtype"),
)
def metric2_levels_tri_pallas(
    P,
    s,
    *,
    epilogue,
    bt: int = DEFAULT_BM,
    bkb: int = DEFAULT_BKB,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Fused diagonal-block plane kernel on the triangular tile schedule.

    P (levels, kb, m) are the packed planes of ONE vector block (both
    operand orientations read the same array); only the T(T+1)/2 tiles with
    ``tj >= ti`` are enumerated.  Returns the packed tile list (P, bt, bt)
    in ``tri_tile_coords`` order, like ``metric2_tri_pallas``."""
    levels, kb, m = P.shape
    mp, kbp = (-m) % bt, (-kb) % bkb
    P = _pad_planes(P, mp, kbp)
    sp = _pad_stat(s, mp)
    sa, sb = sp[:, None], sp[None, :]
    M, KB = m + mp, kb + kbp
    T = M // bt
    nP = T * (T + 1) // 2
    n_k_steps = KB // bkb

    def a_map(p, t):
        return (0, t, _tri_decode(p, T)[0])

    def b_map(p, t):
        return (0, t, _tri_decode(p, T)[1])

    def sa_map(p, t):
        return (_tri_decode(p, T)[0], 0)

    def sb_map(p, t):
        return (0, _tri_decode(p, T)[1])

    out = pl.pallas_call(
        functools.partial(
            _levels_fused_tri_kernel, n_k_steps=n_k_steps, levels=levels,
            epilogue=epilogue, T=T,
        ),
        grid=(nP, n_k_steps),
        in_specs=[
            pl.BlockSpec((levels, bkb, bt), a_map),
            pl.BlockSpec((levels, bkb, bt), b_map),
            pl.BlockSpec((bt, 1), sa_map),
            pl.BlockSpec((1, bt), sb_map),
        ],
        out_specs=pl.BlockSpec((1, bt, bt), lambda p, t: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nP, bt, bt), out_dtype),
        scratch_shapes=[pltpu.VMEM((bt, bt), jnp.float32)],
        interpret=interpret,
    )(P, P, sa, sb)
    return out
