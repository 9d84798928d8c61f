"""The plain reference: the metric from its definition in float64 numpy, and
the paper's §5 exact checksum written out again.  Nothing here imports the
program under test.
"""
from __future__ import annotations

import numpy as np


def _ratio(num, den):
    """num / den in float64, 0 where den is 0."""
    num, den = np.asarray(num, np.float64), np.asarray(den, np.float64)
    return np.where(den > 0, num / np.where(den > 0, den, 1.0), 0.0)


def _columns(V, idx):
    """The vectors ``idx`` of ``V`` (n_f, n_v) as rows, (len(idx), n_f)."""
    return np.ascontiguousarray(V.T)[np.asarray(idx)]


def _sum(x):
    return x.sum(axis=1, dtype=np.int64)


def pair_reference(V, I, J):
    """Proportional Similarity from its definition:
    2 sum_q min(a_q, b_q) / (sum_q a_q + sum_q b_q) (0 when both are 0),
    sums exact in integers, the ratio in float64."""
    A, B = _columns(V, I), _columns(V, J)
    return _ratio(2 * _sum(np.minimum(A, B)), _sum(A) + _sum(B))


def triple_reference(V, I, J, K):
    """The paper's 3-way Proportional Similarity:
    3/2 sum_q [min(a,b) + min(a,c) + min(b,c) - min(a,b,c)] / sum_q (a+b+c),
    sums exact in integers, the ratio in float64."""
    A, B, C = _columns(V, I), _columns(V, J), _columns(V, K)
    ab = np.minimum(A, B)
    num = (_sum(ab) + _sum(np.minimum(A, C)) + _sum(np.minimum(B, C))
           - _sum(np.minimum(ab, C)))
    return _ratio(1.5 * num, _sum(A) + _sum(B) + _sum(C))


def values(V, index):
    return (pair_reference if len(index) == 2 else triple_reference)(V, *index)


# -- the exact multiset checksum (paper §5) ----------------------------------

MOD = 1 << 192
_M64 = (1 << 64) - 1
_LO32 = np.uint64(0xFFFFFFFF)
_BLOCK = 1 << 24  # entries per partial sum, so 32-bit limb sums cannot wrap


def _mix_int(x: int) -> int:
    """splitmix64 finalizer."""
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def _mix(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def keys(index) -> np.ndarray:
    """Canonical uint64 key of each result: the sorted index tuple, packed
    as lo << 32 | hi for pairs and 21 bits per index for triples."""
    if len(index) == 2:
        i, j = (np.asarray(a, np.int64) for a in index)
        lo = np.minimum(i, j).astype(np.uint64)
        return (lo << np.uint64(32)) | np.maximum(i, j).astype(np.uint64)
    i, j, k = (np.asarray(a, np.int64) for a in index)
    lo = np.minimum(np.minimum(i, j), k)
    hi = np.maximum(np.maximum(i, j), k)
    mid = i + j + k - lo - hi
    return ((lo.astype(np.uint64) << np.uint64(42))
            | (mid.astype(np.uint64) << np.uint64(21)) | hi.astype(np.uint64))


def checksum(keys_: np.ndarray, vals: np.ndarray) -> int:
    """sum(mix(key) * (bits(value) + 1)) + mix(count), mod 2**192, over
    float32 or 16-bit values."""
    bits = np.ascontiguousarray(vals).view(
        np.uint32 if vals.dtype.itemsize == 4 else np.uint16)
    total = 0
    for s in range(0, keys_.size, _BLOCK):
        mixed = _mix(keys_[s:s + _BLOCK])
        b1 = bits[s:s + _BLOCK].astype(np.uint64) + np.uint64(1)
        for half, shift in ((mixed >> np.uint64(32), 32), (mixed & _LO32, 0)):
            prod = half * b1
            total += ((int((prod >> np.uint64(32)).sum()) << (shift + 32))
                      + (int((prod & _LO32).sum()) << shift))
    return (total + _mix_int(keys_.size)) % MOD
