"""Pallas TPU kernels: blocked min-plus GEMM (the paper's mGEMM, §3.1) and
the generated fused-epilogue metric kernels behind the ``TileExecutor``.

TPU adaptation of the paper's modified-MAGMA GEMM.  The MXU cannot evaluate
``min`` inside its systolic array, so the contraction runs on the VPU:
HBM -> VMEM tiles via BlockSpec, fp32 accumulation in a VMEM scratch
accumulator, one (bm, 1) x (1, bn) broadcast-combine per field.

Grid: (M/bm, N/bn, K/bk), K innermost so the accumulator tile stays resident
in VMEM across the contraction (standard Pallas matmul pattern).

Default tile (bm, bn, bk) = (128, 128, 512):
  VMEM working set = A tile 128*512*4 B + B tile 512*128*4 B + acc 128*128*4 B
                   = 256 KiB + 256 KiB + 64 KiB ≈ 0.6 MiB  « 16 MiB VMEM,
leaving room for double buffering of the input streams.  The inner k-chunk
(128 fields) is one lane-aligned slice of the A tile: the fori_loop walks
the chunks, the fields of a chunk are unrolled, and no intermediate is
larger than the (bm, bn) accumulator.

Fused metric kernels (paper §3.1 epilogue fusion + §5 symmetry)
---------------------------------------------------------------
``metric2_pallas`` generates, for ANY metric spec with a Pallas-composable
``assemble_tile`` epilogue, the fused kernel: the contraction accumulates
``sum_q combine(a, b)`` in VMEM and the flush divides the tile in place —
the dense numerator matrix never exists in HBM.

``metric2_tri_pallas`` is the diagonal-block (Va == Vb) variant realizing
the paper's §5 block-triangle scheme IN the grid: the schedule enumerates
only the T(T+1)/2 tiles with ``tj >= ti`` (a 1-D grid whose index maps
decode the packed triangular index arithmetically), so the redundant lower
triangle is never computed rather than computed-then-masked.  Output is the
packed tile list (P, bt, bt); ``unpack_tri_tiles`` scatters it to a dense
strictly-upper block when a caller needs one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.metric_spec import czek_assemble_tile

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512
K_CHUNK = 128

__all__ = [
    "mgemm_pallas",
    "czek2_metric_pallas",
    "metric2_pallas",
    "metric2_tri_pallas",
    "tri_tile_coords",
    "unpack_tri_tiles",
]


def _accumulate(a_ref, b_ref, combine, k_chunk):
    """One (bm, bk) x (bk, bn) combine-sum contraction in fp32.

    Walks ``k_chunk``-field slices of the refs (Mosaic lowers slices of
    refs, not ``dynamic_slice`` of loaded values); inside a slice each
    field is one (bm, 1) x (1, bn) broadcast-combine, statically unrolled.
    On the TPU a slice start on A's lane axis must be a multiple of 128,
    hence ``K_CHUNK``; a tile that ``k_chunk`` does not divide runs as one
    slice."""
    bm, bk = a_ref.shape
    bn = b_ref.shape[1]
    if bk % k_chunk:
        k_chunk = bk

    def chunk(off, acc):
        a = a_ref[:, pl.ds(off, k_chunk)]
        b = b_ref[pl.ds(off, k_chunk), :]
        for q in range(k_chunk):
            acc = acc + combine(a[:, q:q + 1], b[q:q + 1, :]).astype(jnp.float32)
        return acc

    acc = jnp.zeros((bm, bn), jnp.float32)
    n_chunks = bk // k_chunk
    if n_chunks == 1:
        return chunk(0, acc)
    return jax.lax.fori_loop(
        0, n_chunks,
        lambda c, acc: chunk(pl.multiple_of(c * k_chunk, k_chunk), acc), acc,
    )


def _mgemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k_steps: int, k_chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _accumulate(a_ref, b_ref, jnp.minimum, k_chunk)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fused2_kernel(
    a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref,
    *, n_k_steps, k_chunk, combine, epilogue,
):
    """Generated fused metric kernel: contraction + in-VMEM epilogue.

    The flush applies the metric's ``assemble_tile`` to the accumulator
    tile, so the numerator block is divided in VMEM and only metric values
    reach HBM (the §3.1 epilogue-fusion bandwidth win)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _accumulate(a_ref, b_ref, combine, k_chunk)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        o_ref[...] = epilogue(acc_ref[...], sa_ref[...], sb_ref[...]).astype(
            o_ref.dtype
        )


def _fused2_tri_kernel(
    a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref,
    *, n_k_steps, k_chunk, combine, epilogue, T,
):
    """Triangular-schedule fused kernel for diagonal blocks (paper §5).

    Grid axis 0 walks the packed tile list (only ``tj >= ti``); the flush
    zeroes the redundant lower-and-diagonal entries of on-diagonal tiles
    in place."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _accumulate(a_ref, b_ref, combine, k_chunk)
    p = pl.program_id(0)

    @pl.when(pl.program_id(1) == n_k_steps - 1)
    def _flush():
        vals = epilogue(acc_ref[...], sa_ref[...], sb_ref[...])
        store_tri_tile(o_ref, vals, p, T)


def _tri_decode(p, T: int):
    """Packed triangular index -> (ti, tj), tj >= ti, row-major.

    Pure scalar arithmetic (no captured constants) so it is legal inside a
    BlockSpec index map.  The float sqrt estimate is corrected branchlessly,
    keeping the decode exact for any practical tile count."""
    q = T * (T + 1) // 2 - 1 - p
    qf = jnp.asarray(q).astype(jnp.float32)
    r = ((jnp.sqrt(8.0 * qf + 1.0) - 1.0) / 2.0).astype(jnp.int32)
    r = jnp.where((r + 1) * (r + 2) // 2 <= q, r + 1, r)
    r = jnp.where(r * (r + 1) // 2 > q, r - 1, r)
    o = q - r * (r + 1) // 2
    return T - 1 - r, T - 1 - o


def store_tri_tile(o_ref, vals, p, T: int):
    """Flush packed triangular tile ``p`` (its grid position, read outside
    any ``pl.when``, where interpret mode cannot lower ``program_id``).
    ``p`` decodes to the tile's (ti, tj) in the kernel, as a tile-index
    input block would break the TPU's (8, 128) block rule; an on-diagonal
    tile keeps only its strict upper triangle."""
    ti, tj = _tri_decode(p, T)
    li = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    keep = jnp.logical_or(ti != tj, li < lj)
    o_ref[0] = jnp.where(keep, vals, 0.0).astype(o_ref.dtype)


def select_column(tile, l):
    """Column ``l`` (a grid index) of a (..., L) tile, as (..., 1).

    The 3-way kernels read all L pipeline columns in one block, because the
    TPU cannot DMA a width-1 block of a last axis narrower than 128, and
    pick this step's column with a lane mask: an exact sum of one value
    and zeros."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, tile.ndim - 1)
    return jnp.sum(jnp.where(lane == l, tile, 0), axis=-1, keepdims=True)


def tri_tile_coords(T: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side (ti, tj) arrays of the packed triangular schedule."""
    ti = np.array([i for i in range(T) for _ in range(i, T)], np.int32)
    tj = np.array([j for i in range(T) for j in range(i, T)], np.int32)
    return ti, tj


def unpack_tri_tiles(packed, m: int, bt: int):
    """Scatter packed (P, bt, bt) tiles to a dense (m, m) strictly-upper block.

    The lower triangle was never computed; it reads back as zeros, matching
    the compute-both-then-mask layout bit for bit."""
    T = -(-m // bt)
    ti, tj = tri_tile_coords(T)
    dense = jnp.zeros((T, T, bt, bt), packed.dtype).at[ti, tj].set(packed)
    dense = dense.transpose(0, 2, 1, 3).reshape(T * bt, T * bt)
    return dense[:m, :m]


def _pad_operands(A, B, sa, sb, bm, bn, bk):
    """Block-pad operands; stats pad with ZERO so the epilogue's
    ``safe_denom`` guard covers pad columns exactly like all-zero real
    columns (0/eps = 0), instead of a bypassing pad constant."""
    m, k = A.shape
    n = B.shape[1]
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        A = jnp.pad(A, ((0, mp), (0, kp)))
    if np_ or kp:
        B = jnp.pad(B, ((0, kp), (0, np_)))
    sa = jnp.pad(jnp.asarray(sa, jnp.float32).reshape(-1), (0, mp))[:, None]
    sb = jnp.pad(jnp.asarray(sb, jnp.float32).reshape(-1), (0, np_))[None, :]
    return A, B, sa, sb


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "k_chunk", "interpret", "out_dtype"),
)
def mgemm_pallas(
    A,
    B,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    k_chunk: int = K_CHUNK,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """out[i, j] = sum_k min(A[i, k], B[k, j]).  A (m, k), B (k, n)."""
    m, k = A.shape
    k2, n = B.shape
    assert k == k2
    # pad every dim to its block multiple; k pads with zeros on both operands
    # => min(0, 0) = 0 contributes nothing.
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        A = jnp.pad(A, ((0, mp), (0, kp)))
    if np_ or kp:
        B = jnp.pad(B, ((0, kp), (0, np_)))
    M, K = A.shape
    N = B.shape[1]
    n_k_steps = K // bk
    grid = (M // bm, N // bn, n_k_steps)
    out = pl.pallas_call(
        functools.partial(_mgemm_kernel, n_k_steps=n_k_steps, k_chunk=k_chunk),
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


@functools.partial(
    jax.jit,
    static_argnames=(
        "combine", "epilogue", "bm", "bn", "bk", "k_chunk", "interpret",
        "out_dtype",
    ),
)
def metric2_pallas(
    A,
    B,
    sa,
    sb,
    *,
    combine,
    epilogue,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    k_chunk: int = K_CHUNK,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Generated fused 2-way metric kernel (rectangular tile grid).

    out[i, j] = epilogue(sum_k combine(A[i, k], B[k, j]), sa_i, sb_j) for any
    registered metric whose contraction is the combine-sum reduction."""
    m, k = A.shape
    n = B.shape[1]
    A, B, sa, sb = _pad_operands(A, B, sa, sb, bm, bn, bk)
    M, K = A.shape
    N = B.shape[1]
    n_k_steps = K // bk
    grid = (M // bm, N // bn, n_k_steps)
    out = pl.pallas_call(
        functools.partial(
            _fused2_kernel, n_k_steps=n_k_steps, k_chunk=k_chunk,
            combine=combine, epilogue=epilogue,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((bm, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(A, B, sa, sb)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "combine", "epilogue", "bt", "bk", "k_chunk", "interpret", "out_dtype",
    ),
)
def metric2_tri_pallas(
    A,
    B,
    sa,
    sb,
    *,
    combine,
    epilogue,
    bt: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    k_chunk: int = K_CHUNK,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Fused diagonal-block metric kernel on the triangular tile schedule.

    A (m, k) and B (k, m) are the two orientations of the SAME vector block;
    only the T(T+1)/2 tiles with ``tj >= ti`` are enumerated (paper §5), and
    on-diagonal tiles are masked to the strict upper triangle at flush.
    Returns the packed tile list (P, bt, bt) in ``tri_tile_coords`` order —
    the packed upper-triangular storage form."""
    m, k = A.shape
    assert B.shape == (k, m), "triangular schedule needs a square block"
    A, B, sa, sb = _pad_operands(A, B, sa, sb, bt, bt, bk)
    M, K = A.shape
    T = M // bt
    P = T * (T + 1) // 2
    n_k_steps = K // bk

    def a_map(p, t):
        return (_tri_decode(p, T)[0], t)

    def b_map(p, t):
        return (t, _tri_decode(p, T)[1])

    def sa_map(p, t):
        return (_tri_decode(p, T)[0], 0)

    def sb_map(p, t):
        return (0, _tri_decode(p, T)[1])

    out = pl.pallas_call(
        functools.partial(
            _fused2_tri_kernel, n_k_steps=n_k_steps, k_chunk=k_chunk,
            combine=combine, epilogue=epilogue, T=T,
        ),
        grid=(P, n_k_steps),
        in_specs=[
            pl.BlockSpec((bt, bk), a_map),
            pl.BlockSpec((bk, bt), b_map),
            pl.BlockSpec((bt, 1), sa_map),
            pl.BlockSpec((1, bt), sb_map),
        ],
        out_specs=pl.BlockSpec((1, bt, bt), lambda p, t: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, bt, bt), out_dtype),
        scratch_shapes=[pltpu.VMEM((bt, bt), jnp.float32)],
        interpret=interpret,
    )(A, B, sa, sb)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "k_chunk", "interpret", "out_dtype"),
)
def czek2_metric_pallas(
    A,
    B,
    sa,
    sb,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    k_chunk: int = K_CHUNK,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Fused 2-way Czekanowski: out[i,j] = 2*Σ min / safe_denom(sa_i + sb_j).

    One instantiation of the generated ``metric2_pallas`` kernel.  The
    denominator runs through the unified ``safe_denom`` guard (stats pad
    with zero), so all-zero real columns yield 0 exactly like the XLA path
    instead of hitting 0/0."""
    return metric2_pallas(
        A, B, sa, sb,
        combine=jnp.minimum, epilogue=czek_assemble_tile,
        bm=bm, bn=bn, bk=bk, k_chunk=k_chunk, interpret=interpret,
        out_dtype=out_dtype,
    )
