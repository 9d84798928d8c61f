"""Pallas TPU kernel: popcount bit-GEMM for binary (levels=1) planes.

For binary data the single bit-plane IS the data and, for a, b in {0, 1},

    min(a, b) = a AND b

so the min-plus numerator collapses to pure bit arithmetic over the
*packed* bytes (paper §2.3 — the same trick second-generation PLINK uses
for biobank-scale binary genotype arithmetic):

    N[i, j] = sum_q popcount(Pa[q, i] AND Pb[q, j])

Where the levels path inflates each byte tile 8x into bf16 indicators
before contracting, these kernels AND the byte tiles directly, group 4
bytes of a K-tile into one int32 word per lane, and accumulate
``lax.population_count`` of the AND outer product — no unpack shuffle and
1/8 the VMEM indicator footprint on the hottest binary-workload loop.

Operand layout is unchanged: ``(1, kb, w)`` uint8 packed planes in the
documented wire format (docs/BITPLANE_FORMAT.md) — ring payloads, store
shards, and pipeline byte-range views feed in unmodified.  Zero pad bytes
AND to zero and contribute zero popcount, so padding is inert exactly as
the format promises for the dot formulation.

Exactness: every numerator is an integer <= n_f, exactly representable in
fp32, so campaign checksums stay bit-identical to ``impl="xla"`` across
every decomposition, chunking, and path — popcount partials also ADD
exactly, which is what keeps the streamed/merge paths on this kernel.

Mosaic note: ``lax.population_count`` on int32 words lowers for the v5e
(``tests/test_tpu_compile.py`` compiles every kernel here for a described
chip), and on one v5e chip the popcount campaign's checksum equals
``impl="xla"``'s, so no SWAR fallback is needed.  What Mosaic refused was
the loop around it: ``dynamic_slice`` of loaded values, hence one
unrolled broadcast AND per word row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mgemm.kernel import _tri_decode, select_column, store_tri_tile
from repro.kernels.mgemm_levels.kernel import _pad_planes, _pad_stat

DEFAULT_BM = 256
DEFAULT_BN = 256
# byte tile of the contraction axis; wrappers round it up to whole
# WORD_ALIGN-byte units so the four quarter slices of ``_pack_words`` start
# on 8-row (sublane) boundaries
DEFAULT_BKB = 64
WORD_ALIGN = 32
DEFAULT_BM3 = 128
DEFAULT_BN3 = 128


def _pack_words(tile):
    """(bkb, w) packed uint8 -> (bkb//4, w) int32 words.

    Word r holds bytes r, r + q, r + 2q and r + 3q (q = bkb // 4).  AND and
    popcount act bit by bit, so any byte grouping both operands share gives
    the same count; quarter slices need no reshape of the sublane axis.
    The int32 may wrap negative —
    the bit pattern (what ``population_count`` sees) is still exact."""
    q = tile.shape[0] // 4
    b = tile.astype(jnp.int32)
    return b[:q] | (b[q:2 * q] << 8) | (b[2 * q:3 * q] << 16) | (b[3 * q:] << 24)


def _pop_contract(pa, pb):
    """out[i, j] = sum_q popcount(pa[q, i] & pb[q, j]) for one K-tile.

    pa (bkb, bm), pb (bkb, bn) packed bytes -> (bm, bn) fp32.  Each int32
    word row is one (bm, 1) & (1, bn) broadcast AND + popcount, statically
    unrolled (bkb // 4 words per tile), counted in int32 and converted
    once."""
    wa = _pack_words(pa).T  # (bm, nw): A's vectors on sublanes
    wb = _pack_words(pb)  # (nw, bn)
    acc = jnp.zeros((wa.shape[0], wb.shape[1]), jnp.int32)
    for r in range(wb.shape[0]):
        acc += jax.lax.population_count(wa[:, r:r + 1] & wb[r:r + 1, :])
    return acc.astype(jnp.float32)


def _word_align(bkb: int) -> int:
    """Round a byte-tile size up to whole ``WORD_ALIGN`` units."""
    return -(-bkb // WORD_ALIGN) * WORD_ALIGN


def _pop_fused_kernel(
    pa_ref, pb_ref, sa_ref, sb_ref, o_ref, acc_ref,
    *, n_k_steps: int, epilogue,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _pop_contract(pa_ref[0], pb_ref[0])

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        vals = acc if epilogue is None else epilogue(
            acc, sa_ref[...], sb_ref[...]
        )
        o_ref[...] = vals.astype(o_ref.dtype)


def _pop_fused_tri_kernel(
    pa_ref, pb_ref, sa_ref, sb_ref, o_ref, acc_ref,
    *, n_k_steps: int, epilogue, T: int,
):
    """Triangular-schedule popcount kernel for diagonal blocks (paper §5):
    grid axis 0 walks only the ``tj >= ti`` tiles; on-diagonal tiles are
    masked to the strict upper triangle at flush."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _pop_contract(pa_ref[0], pb_ref[0])
    p = pl.program_id(0)

    @pl.when(pl.program_id(1) == n_k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        vals = acc if epilogue is None else epilogue(
            acc, sa_ref[...], sb_ref[...]
        )
        store_tri_tile(o_ref, vals, p, T)


@functools.partial(
    jax.jit,
    static_argnames=(
        "epilogue", "bm", "bn", "bkb", "interpret", "out_dtype"
    ),
)
def metric2_pop_pallas(
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
    """Fused 2-way metric kernel on a binary packed plane (rectangular grid).

    Pa (1, kb, m) / Pb (1, kb, n) single-plane payloads; sa (m,) / sb (n,)
    per-vector stats (= the plane popcounts for binary data).  Returns
    ``epilogue(popcount(Pa AND Pb), sa, sb)``; ``epilogue=None`` returns
    the raw fp32 numerator (the deferred-flush form for ``n_pf > 1`` psums
    and streamed chunk programs).
    """
    levels, kb, m = Pa.shape
    n = Pb.shape[2]
    assert levels == 1 and Pb.shape[:2] == (1, kb), (Pa.shape, Pb.shape)
    bkb = _word_align(bkb)
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
            _pop_fused_kernel, n_k_steps=n_k_steps,
            epilogue=epilogue,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bkb, bm), lambda i, j, t: (0, t, i)),
            pl.BlockSpec((1, bkb, bn), lambda i, j, t: (0, t, j)),
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
    static_argnames=(
        "epilogue", "bt", "bkb", "interpret", "out_dtype"
    ),
)
def metric2_pop_tri_pallas(
    P,
    s,
    *,
    epilogue,
    bt: int = DEFAULT_BM,
    bkb: int = DEFAULT_BKB,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Fused diagonal-block popcount kernel on the triangular tile schedule.

    P (1, kb, m) is the packed plane of ONE vector block (both operand
    orientations read the same array); only the T(T+1)/2 tiles with
    ``tj >= ti`` are enumerated.  Returns the packed tile list (nP, bt, bt)
    in ``tri_tile_coords`` order, like ``metric2_levels_tri_pallas``."""
    levels, kb, m = P.shape
    assert levels == 1, P.shape
    bkb = _word_align(bkb)
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
            _pop_fused_tri_kernel, n_k_steps=n_k_steps,
            epilogue=epilogue, T=T,
        ),
        grid=(nP, n_k_steps),
        in_specs=[
            pl.BlockSpec((1, bkb, bt), a_map),
            pl.BlockSpec((1, bkb, bt), b_map),
            pl.BlockSpec((bt, 1), sa_map),
            pl.BlockSpec((1, bt), sb_map),
        ],
        out_specs=pl.BlockSpec((1, bt, bt), lambda p, t: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nP, bt, bt), out_dtype),
        scratch_shapes=[pltpu.VMEM((bt, bt), jnp.float32)],
        interpret=interpret,
    )(P, P, sa, sb)
    return out


# -- 3-way pipeline-slice variant --------------------------------------------
#
# min(a, x, b) = a AND x AND b on binary planes: the X_j = min(own, x)
# tile is a bitwise AND of packed bytes that STAYS packed — the whole
# slice contraction never unpacks a byte.  The 3-way analogue of
# ``czek3.threeway_batch_levels_pallas`` with the popcount contraction in
# place of the plane dot_generals.


def _threeway_pop_kernel(
    own_ref, x_ref, right_ref, o_ref, acc_ref, *, n_k_steps
):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # packed AND == plane of min(own, x); this step's column (bkb, 1)
    # broadcasts over own's vectors
    x = select_column(x_ref[0].astype(jnp.int32), pl.program_id(0))
    acc_ref[...] += _pop_contract(own_ref[0].astype(jnp.int32) & x, right_ref[0])

    @pl.when(pl.program_id(3) == n_k_steps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bkb", "interpret", "out_dtype"),
)
def threeway_batch_pop_pallas(
    Pown,
    PX,
    Pright,
    *,
    bm: int = DEFAULT_BM3,
    bn: int = DEFAULT_BN3,
    bkb: int = DEFAULT_BKB,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """B[t, i, k] = sum_q popcount-min(own[q, i], X[q, t], right[q, k]) on
    binary packed planes.

    Pown (1, kb, m), PX (1, kb, L) pipeline columns, Pright (1, kb, n) ->
    (L, m, n); operands use the documented wire layout — on the plane-ring
    campaign path they are byte-range views of the ring payload, fed in
    unmodified.  One launch for the whole pipeline slice like
    ``threeway_batch_levels_pallas``."""
    levels, kb, m = Pown.shape
    assert levels == 1, Pown.shape
    L = PX.shape[2]
    n = Pright.shape[2]
    bkb = _word_align(bkb)
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
            _threeway_pop_kernel, n_k_steps=n_k_steps,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bkb, bm), lambda l, i, j, t: (0, t, i)),
            pl.BlockSpec((1, bkb, L), lambda l, i, j, t: (0, t, 0)),
            pl.BlockSpec((1, bkb, bn), lambda l, i, j, t: (0, t, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda l, i, j, t: (l, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(Pown, PX, Pright)
    return out[:, :m, :n]
