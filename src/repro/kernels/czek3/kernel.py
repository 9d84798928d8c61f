"""Pallas TPU kernel: fused 3-way inner step (paper §3.2, Algorithm 3).

One pipeline step of the 3-way computation:

    B_j[i, k] = sum_q combine(own[q, i], x[q], right[q, k])

where ``x = pipe[:, j]`` is the current pipeline column and ``combine`` is
the metric's elementwise pairing op (``min`` for Czekanowski, ``*`` for the
correlation family).  The paper materializes X_j = combine(V, v_j) and then
runs a 2-way mGEMM; this kernel fuses the X_j construction into the
contraction so X_j never touches HBM — eliminating one full (n_f x n_vp)
HBM write + read per pipeline step.

These kernels are NOT stand-alone demonstrations: the ``TileExecutor``
routes every 3-way pipeline slice of the distributed engine through them —
``threeway_batch_pallas`` under ``impl="pallas"`` (``path3 ==
"fused-vpu"``), ``threeway_batch_levels_pallas`` under ``impl="levels"``
(``path3 == "fused-levels"`` / ``"fused-levels-ring"``).  On the plane
ring the packed operands arrive exactly as ring-carried, with no per-slice
re-encode.

Plane-layout invariant: the packed-plane variant consumes the
(levels, kb, w) uint8 LSB-first layout specified in
docs/BITPLANE_FORMAT.md.  Its unpack helper and MXU accumulation
(``_plane_matmuls``) are imported from ``mgemm_levels.kernel`` — shared
with the 2-way plane kernels precisely so the bit layout and dot shapes
can never drift between the engines.

Value operands arrive field-major ((n_f, m) blocks), matching how the
distributed engine stores vector blocks, so the kernels contract over the
*leading* axis; plane operands put the same fields at 8-per-byte along
their middle (byte) axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mgemm.kernel import K_CHUNK, _accumulate, select_column
# shared with the 2-way plane kernels so the bit layout and the MXU
# accumulation (dot shape, preferred_element_type) can never drift
from repro.kernels.mgemm_levels.kernel import DEFAULT_BKB, _plane_matmuls

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _xj_contract(xo, right_ref, xo_ref, combine, k_chunk):
    """sum_q combine(xo[q, i], right[q, k]) for one K-tile: the fused X_j
    tile is stored vector-major in VMEM scratch so the 2-way contraction
    (``mgemm._accumulate``) reads it as its A operand."""
    xo_ref[...] = xo.T
    return _accumulate(xo_ref, right_ref, combine, k_chunk)


def _threeway_kernel(
    own_ref, x_ref, right_ref, o_ref, acc_ref, xo_ref,
    *, n_k_steps, k_chunk, combine,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fused X_j tile (bk, bm) — never written to HBM
    xo = combine(own_ref[...], x_ref[...])
    acc_ref[...] += _xj_contract(xo, right_ref, xo_ref, combine, k_chunk)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("combine", "bm", "bn", "bk", "k_chunk", "interpret",
                     "out_dtype"),
)
def threeway_step_pallas(
    own,
    x,
    right,
    *,
    combine,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    k_chunk: int = K_CHUNK,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """B[i, k] = sum_q combine(own[q, i], x[q], right[q, k]).

    own (n_f, m), x (n_f,) or (n_f, 1), right (n_f, n).  Valid for any
    metric whose 3-way term chains its elementwise ``combine`` (min-plus and
    product metrics both do — ``MetricSpec.combine_sum_contract``)."""
    if x.ndim == 1:
        x = x[:, None]
    k, m = own.shape
    n = right.shape[1]
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        own = jnp.pad(own, ((0, kp), (0, mp)))
    if kp:
        x = jnp.pad(x, ((0, kp), (0, 0)))
    if np_ or kp:
        right = jnp.pad(right, ((0, kp), (0, np_)))
    K, M = own.shape
    N = right.shape[1]
    n_k_steps = K // bk
    grid = (M // bm, N // bn, n_k_steps)
    out = pl.pallas_call(
        functools.partial(
            _threeway_kernel, n_k_steps=n_k_steps, k_chunk=k_chunk,
            combine=combine,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, t: (t, i)),
            pl.BlockSpec((bk, 1), lambda i, j, t: (t, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bk), own.dtype),
        ],
        interpret=interpret,
    )(own, x, right)
    return out[:m, :n]


def _threeway_batch_kernel(
    own_ref, x_ref, right_ref, o_ref, acc_ref, xo_ref,
    *, n_k_steps, k_chunk, combine,
):
    """Batched variant: grid axis 0 walks the pipeline columns, so a whole
    (n_fp, L) slice runs as ONE kernel launch (the accumulator still lives
    across the innermost K axis only)."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = select_column(x_ref[...], pl.program_id(0))  # (bk, 1)
    xo = combine(own_ref[...], x)  # fused X_j tile — never written to HBM
    acc_ref[...] += _xj_contract(xo, right_ref, xo_ref, combine, k_chunk)

    @pl.when(pl.program_id(3) == n_k_steps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("combine", "bm", "bn", "bk", "k_chunk", "interpret",
                     "out_dtype"),
)
def threeway_batch_pallas(
    own,
    X,
    right,
    *,
    combine,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    k_chunk: int = K_CHUNK,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """B[t, i, k] = sum_q combine(own[q, i], X[q, t], right[q, k]).

    own (n_f, m), X (n_f, L) pipeline columns, right (n_f, n) -> (L, m, n).
    One launch for the whole pipeline slice: the grid is (L, m/bm, n/bn,
    K/bk), so trace/compile cost is O(1) in L instead of L separate
    pallas_calls."""
    k, m = own.shape
    L = X.shape[1]
    n = right.shape[1]
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        own = jnp.pad(own, ((0, kp), (0, mp)))
    if kp:
        X = jnp.pad(X, ((0, kp), (0, 0)))
    if np_ or kp:
        right = jnp.pad(right, ((0, kp), (0, np_)))
    K, M = own.shape
    N = right.shape[1]
    n_k_steps = K // bk
    grid = (L, M // bm, N // bn, n_k_steps)
    out = pl.pallas_call(
        functools.partial(
            _threeway_batch_kernel, n_k_steps=n_k_steps, k_chunk=k_chunk,
            combine=combine,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda l, i, j, t: (t, i)),
            pl.BlockSpec((bk, L), lambda l, i, j, t: (t, 0)),
            pl.BlockSpec((bk, bn), lambda l, i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda l, i, j, t: (l, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bk), own.dtype),
        ],
        interpret=interpret,
    )(own, X, right)
    return out[:, :m, :n]


# -- packed bit-plane variant (level-decomposed min on the MXU) --------------
#
# For leveled integer data, min(a, x, b) = sum_t 1[a>=t] 1[x>=t] 1[b>=t]:
# the X_j = min(own, x) tile is a bitwise AND of *packed* plane bytes (one
# VPU op per 8 fields, still never written to HBM), and the contraction is
# ``levels`` MXU dot_generals per K-tile — the 3-way analogue of
# ``mgemm_levels.metric2_levels_pallas``, sharing its unpack helper
# (imported at top) so the plane kernels can never disagree on bit layout.


def _threeway_levels_kernel(
    own_ref, x_ref, right_ref, o_ref, acc_ref, *, n_k_steps, levels
):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # packed AND == plane of min(own, x); this step's column
    # (levels, bkb, 1) broadcasts over own's vectors
    x = select_column(x_ref[...].astype(jnp.int32), pl.program_id(0))
    xo = own_ref[...].astype(jnp.int32) & x
    acc_ref[...] += _plane_matmuls(xo, right_ref[...], levels)

    @pl.when(pl.program_id(3) == n_k_steps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bkb", "interpret", "out_dtype"),
)
def threeway_batch_levels_pallas(
    Pown,
    PX,
    Pright,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkb: int = DEFAULT_BKB,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """B[t, i, k] = sum_q min(own[q, i], X[q, t], right[q, k]) on packed
    bit-planes.

    Pown (levels, kb, m), PX (levels, kb, L) pipeline columns, Pright
    (levels, kb, n) -> (L, m, n); operands use the documented wire layout
    (docs/BITPLANE_FORMAT.md) — on the plane-ring campaign path they are
    byte-range views of the ring payload, fed in unmodified.  Exact for
    leveled integer data; one launch for the whole pipeline slice like
    ``threeway_batch_pallas``."""
    levels, kb, m = Pown.shape
    L = PX.shape[2]
    n = Pright.shape[2]
    mp, np_, kbp = (-m) % bm, (-n) % bn, (-kb) % bkb
    if mp or kbp:
        Pown = jnp.pad(Pown, ((0, 0), (0, kbp), (0, mp)))
    if kbp:
        PX = jnp.pad(PX, ((0, 0), (0, kbp), (0, 0)))
    if np_ or kbp:
        Pright = jnp.pad(Pright, ((0, 0), (0, kbp), (0, np_)))
    M, N, KB = m + mp, n + np_, kb + kbp
    n_k_steps = KB // bkb
    grid = (L, M // bm, N // bn, n_k_steps)
    out = pl.pallas_call(
        functools.partial(
            _threeway_levels_kernel, n_k_steps=n_k_steps, levels=levels,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((levels, bkb, bm), lambda l, i, j, t: (0, t, i)),
            pl.BlockSpec((levels, bkb, L), lambda l, i, j, t: (0, t, 0)),
            pl.BlockSpec((levels, bkb, bn), lambda l, i, j, t: (0, t, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda l, i, j, t: (l, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(Pown, PX, Pright)
    return out[:, :m, :n]
