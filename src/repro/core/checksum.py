"""Exact result checksums — paper §5.

The paper validates its parallel decompositions with "a checksum feature using
extended precision integer arithmetic [that] computes a bit-for-bit exact
checksum of computed results".  We reproduce that contract:

* every computed metric value is identified by its *global* index tuple
  ``(i, j)`` or ``(i, j, k)`` (canonicalized: sorted ascending) plus the IEEE
  bit pattern of its value;
* the checksum is a multiset hash — an order-independent sum over entries of
  ``mix(index) * bits(value)`` in unbounded python integers, reduced modulo
  2**192 — so any parallel decomposition that computes exactly the unique
  result set, with bit-identical values, yields the identical checksum;
* duplicated or missing results change the checksum with overwhelming
  probability; so does any single-ULP numerical difference.

This is the primary cross-decomposition validation used by the tests.

On the device
-------------

A campaign whose output blocks are still on the device, with values of
32 bits or fewer, does not rebuild its index tiles on the host: the
engines dispatch ``partials_program`` on the output right after the
campaign program, and the host folds what it returns (``fold_partials``).
The program rebuilds each slot's global indices and mask with iotas from a
small per-slot descriptor (block offsets, item kind, sixth bounds,
``n_v``), packs the same keys as the host, and computes
``mix(key) * (bits + 1)`` exactly in uint32 arithmetic: 64-bit words are
(hi, lo) pairs of uint32, and each 32 x 32 -> 64-bit product is built from
four 16 x 16-bit products (``mul32``).  The product is taken as
``mix * bits + mix``, so ``bits + 1 == 2**32`` needs no special case.  Its
value below 2**96 is split into six 16-bit limb positions, each the sum of
at most three 16-bit halves of 32-bit words, so a position reads below
3 * 2**16 for one entry.  Summed over a segment of at most 2**14 entries
(``_SEG``) it stays below 3 * 2**30, and the uint32 sums cannot wrap.  The
host adds the segments' sums with Python integers and weights position
``k`` by 2**(16 k): an exact integer identity, so the fold equals
``_raw_total`` mod 2**192, and ``combine()`` then gives ``checksum()``'s
value bit for bit.  The segments' masked counts add up to the result
count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = [
    "checksum_pairs", "checksum_triples", "combine", "MOD",
    "device_dtype", "fold_partials", "mul32", "mix32", "partials_program",
]

MOD = 1 << 192
_GOLD = 0x9E3779B97F4A7C15


def _mix(x: int) -> int:
    """splitmix64 finalizer — deterministic index mixing."""
    x = (x + _GOLD) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return x ^ (x >> 31)


def _mix_np(x: np.ndarray) -> np.ndarray:
    """``_mix`` over a uint64 array (multiplication wraps mod 2**64)."""
    x = x + np.uint64(_GOLD)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _value_bits(values: np.ndarray) -> np.ndarray:
    v = np.asarray(values)
    if v.dtype == np.float64:
        return v.view(np.uint64)
    if v.dtype == np.float32:
        return v.view(np.uint32)
    if v.dtype.itemsize == 2:  # float16 / bfloat16 (ml_dtypes) metric outputs
        return v.view(np.uint16)
    raise TypeError(f"unsupported dtype {v.dtype}")


_LO32 = np.uint64(0xFFFFFFFF)
_BLOCK = 1 << 24  # entries per partial sum: 32-bit limbs stay below 2**56


def _raw_total(keys: np.ndarray, values) -> int:
    """sum(mix(key) * (bits(value) + 1)) mod MOD over uint64 ``keys``.

    Exact, in numpy: for 32- and 16-bit values both 32-bit halves of
    ``mix(key)`` times ``bits + 1`` fit in uint64, and their 32-bit limbs
    are summed in blocks small enough not to wrap.  64-bit values take the
    per-entry Python-integer loop."""
    keys = np.asarray(keys, np.uint64).ravel()
    bits = _value_bits(values).ravel()
    if bits.dtype == np.uint64:
        total = 0
        for k, b in zip(keys.tolist(), bits.tolist()):
            total = (total + _mix(k) * (b + 1)) % MOD
        return total
    total = 0
    for s in range(0, keys.size, _BLOCK):
        mixed = _mix_np(keys[s:s + _BLOCK])
        b1 = bits[s:s + _BLOCK].astype(np.uint64) + np.uint64(1)
        for half, shift in ((mixed >> np.uint64(32), 32), (mixed & _LO32, 0)):
            prod = half * b1
            total += (
                (int((prod >> np.uint64(32)).sum()) << (shift + 32))
                + (int((prod & _LO32).sum()) << shift)
            )
    return total % MOD


def _pair_keys(i, j) -> np.ndarray:
    """(i, j) canonicalized to i < j, packed as lo << 32 | hi."""
    i = np.asarray(i, np.int64)
    j = np.asarray(j, np.int64)
    lo = np.minimum(i, j).astype(np.uint64)
    hi = np.maximum(i, j).astype(np.uint64)
    return (lo << np.uint64(32)) | hi


def _triple_keys(i, j, k) -> np.ndarray:
    """(i, j, k) canonicalized ascending, packed 21 bits per index."""
    idx = np.sort(
        np.stack([np.asarray(i), np.asarray(j), np.asarray(k)], -1), -1
    ).astype(np.uint64)
    return (
        (idx[..., 0] << np.uint64(42))
        | (idx[..., 1] << np.uint64(21))
        | idx[..., 2]
    )


def checksum_pairs(i, j, values) -> int:
    """Checksum of 2-way results. (i, j) canonicalized to i < j."""
    return combine([raw_pairs(i, j, values)])


def checksum_triples(i, j, k, values) -> int:
    """Checksum of 3-way results. (i, j, k) canonicalized ascending."""
    return combine([raw_triples(i, j, k, values)])


def combine(parts) -> int:
    """Combine per-rank checksums.  Sums are order-independent by design, but
    each part already includes its own count term, so combine by summing the
    *raw* totals is wrong; instead parts must be raw (count-free).  To keep
    the API simple, per-rank code passes raw entry sums via this helper:
    combine() adds them and appends the global count mix."""
    total = 0
    count = 0
    for t, c in parts:
        total = (total + t) % MOD
        count += c
    return (total + _mix(count)) % MOD


def raw_pairs(i, j, values) -> tuple[int, int]:
    """Count-free partial checksum for combine()."""
    keys = _pair_keys(i, j)
    return _raw_total(keys, values), keys.size


def raw_triples(i, j, k, values) -> tuple[int, int]:
    keys = _triple_keys(i, j, k)
    return _raw_total(keys, values), keys.size


# -- on the device -----------------------------------------------------------

_M32 = (1 << 32) - 1
#: entries per device partial sum (see the module docstring)
_SEG = 1 << 14
#: 16-bit limb positions of mix(key) * (bits + 1) < 2**96
_POSITIONS = 6


def device_dtype(dtype) -> bool:
    """True where the device partials cover values of ``dtype``: the float
    types of 32 bits or fewer, whose bits ``_value_bits`` reads."""
    return jnp.dtype(dtype) in (jnp.float32, jnp.float16, jnp.bfloat16)


def mul32(a, b):
    """Full product of uint32 arrays as (hi, lo) uint32 words, from four
    16 x 16-bit products, each below 2**32."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)
    lo = (p00 & 0xFFFF) | (mid << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


def mix32(hi, lo):
    """``_mix`` over 64-bit keys held as (hi, lo) uint32 words; products
    wrap mod 2**64 as the host's do."""
    lo2 = lo + np.uint32(_GOLD & _M32)
    hi = hi + np.uint32(_GOLD >> 32) + (lo2 < lo).astype(jnp.uint32)
    lo = lo2
    for shift, mult in ((30, 0xBF58476D1CE4E5B9), (27, 0x94D049BB133111EB),
                        (31, None)):
        hi, lo = hi ^ (hi >> shift), lo ^ ((lo >> shift) | (hi << (32 - shift)))
        if mult is not None:
            m_hi, m_lo = np.uint32(mult >> 32), np.uint32(mult & _M32)
            p_hi, p_lo = mul32(lo, m_lo)
            hi, lo = p_hi + hi * m_lo + lo * m_hi, p_lo
    return hi, lo


def _positions(hi, lo, bits):
    """The six 16-bit limb positions of mix * (bits + 1) = mix * bits + mix
    for each entry, mix = (hi, lo): position k weighs 2**(16 k) and sums at
    most three 16-bit halves."""
    a_hi, a_lo = mul32(lo, bits)
    b_hi, b_lo = mul32(hi, bits)
    return (
        (a_lo & 0xFFFF) + (lo & 0xFFFF),
        (a_lo >> 16) + (lo >> 16),
        (a_hi & 0xFFFF) + (b_lo & 0xFFFF) + (hi & 0xFFFF),
        (a_hi >> 16) + (b_lo >> 16) + (hi >> 16),
        b_hi & 0xFFFF,
        b_hi >> 16,
    )


def _segment_sums(key_hi, key_lo, vals, mask):
    """(7, ..., R/r, C'/c) uint32: the six limb positions of the masked
    entries and their count, each summed over segments of r x c entries of
    the trailing (R, C) grid (r * c <= ``_SEG``; rows longer than
    ``_SEG`` split into equal parts, padded with zeros to C')."""
    bits = jax.lax.bitcast_convert_type(
        vals, jnp.uint32 if vals.dtype.itemsize == 4 else jnp.uint16
    ).astype(jnp.uint32)
    hi, lo = mix32(key_hi, key_lo)
    zero = jnp.uint32(0)
    hi, lo = jnp.where(mask, hi, zero), jnp.where(mask, lo, zero)
    R, C = vals.shape[-2:]
    c = -(-C // -(-C // _SEG))
    r = max(d for d in range(1, min(R, _SEG // c) + 1) if R % d == 0)
    pad = [(0, 0)] * (vals.ndim - 1) + [(0, -C % c)]
    shape = vals.shape[:-2] + (R // r, r, -(-C // c), c)
    return jnp.stack([
        jnp.pad(t, pad).reshape(shape).sum(axis=(-3, -1), dtype=jnp.uint32)
        for t in _positions(hi, lo, bits) + (mask.astype(jnp.uint32),)
    ])


def _pair_slot(vals, desc):
    """Partials of one 2-way slot: ``vals`` (m, m), ``desc`` uint32
    (row offset, column offset, diagonal, computed, n_v)."""
    row0, col0, diag, valid, n_v = (desc[i] for i in range(5))
    a = jax.lax.broadcasted_iota(jnp.uint32, vals.shape, 0)
    b = jax.lax.broadcasted_iota(jnp.uint32, vals.shape, 1)
    i, j = row0 + a, col0 + b
    mask = ((valid > 0) & (i < n_v) & (j < n_v)
            & ((diag == 0) | (a < b)))
    return _segment_sums(jnp.minimum(i, j), jnp.maximum(i, j), vals, mask)


def _triple_slot(vals, desc):
    """Partials of one 3-way slot: ``vals`` (L, m, m), ``desc`` uint32
    (pipe, left and right block offsets, sixth start ``lo``, rows below
    the pipe index, columns above it, computed, n_v)."""
    pipe0, left0, right0, lo, lt, gt, valid, n_v = (desc[i] for i in range(8))
    t = jax.lax.broadcasted_iota(jnp.uint32, vals.shape, 0)
    lr = jax.lax.broadcasted_iota(jnp.uint32, vals.shape, 1)
    rr = jax.lax.broadcasted_iota(jnp.uint32, vals.shape, 2)
    jg = lo + t
    i, j, k = pipe0 + jg, left0 + lr, right0 + rr
    mask = ((valid > 0) & (i < n_v) & (j < n_v) & (k < n_v)
            & ((lt == 0) | (lr < jg)) & ((gt == 0) | (rr > jg)))
    s0 = jnp.minimum(jnp.minimum(i, j), k)
    s2 = jnp.maximum(jnp.maximum(i, j), k)
    s1 = i + j + k - s0 - s2
    return _segment_sums((s0 << 10) | (s1 >> 11), (s1 << 21) | s2, vals, mask)


def partials_program(way: int, mesh):
    """The device checksum partials of a campaign's output blocks, per rank
    of ``mesh``: ``(blocks, slots) -> partials``.

    ``blocks`` is the campaign program's output, (n_pv, n_pr, slots, m, m)
    for 2-way or (n_pv, n_pr, slots, L, m, m) for 3-way, sharded over
    ("pv", "pr"); ``slots`` the (n_pv, n_pr, slots, D) uint32 descriptors
    that ``checksum_slots`` of ``repro.core.twoway`` or
    ``repro.core.threeway`` builds.  Each rank reads only its own blocks;
    only the partials move.  Descriptors are runtime arrays, so one
    executable serves every campaign and stage of one geometry."""
    slot = _pair_slot if way == 2 else _triple_slot

    def per_rank(blocks, slots):
        out = jax.lax.map(lambda a: slot(*a), (blocks[0, 0], slots[0, 0]))
        return out[None, None]

    return jax.shard_map(
        per_rank, mesh=mesh, in_specs=(P("pv", "pr"), P("pv", "pr")),
        out_specs=P("pv", "pr"), check_vma=False,
    )


def fold_partials(parts) -> tuple[int, int]:
    """``(raw total mod MOD, count)`` from ``partials_program``'s output:
    the same value as ``_raw_total`` and the number of entries over the
    same masked tiles."""
    parts = np.asarray(parts)
    sums = np.moveaxis(parts, 3, 0).reshape(_POSITIONS + 1, -1).sum(
        axis=1, dtype=np.uint64)
    total = sum(int(s) << (16 * k) for k, s in enumerate(sums[:_POSITIONS]))
    return total % MOD, int(sums[_POSITIONS])
