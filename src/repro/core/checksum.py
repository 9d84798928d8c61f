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
"""
from __future__ import annotations

import numpy as np

__all__ = ["checksum_pairs", "checksum_triples", "combine", "MOD"]

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
