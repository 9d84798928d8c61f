"""Checksum contract tests (paper §5 validation machinery)."""
import numpy as np
import pytest

from repro.core import checksum as ck


def _pairs(n=50, seed=0):
    rng = np.random.default_rng(seed)
    i, j = np.triu_indices(12, k=1)
    v = rng.random(len(i)).astype(np.float32)
    return i, j, v


def test_order_invariance():
    i, j, v = _pairs()
    a = ck.checksum_pairs(i, j, v)
    perm = np.random.default_rng(1).permutation(len(i))
    b = ck.checksum_pairs(i[perm], j[perm], v[perm])
    assert a == b


def test_index_canonicalization():
    i, j, v = _pairs()
    assert ck.checksum_pairs(i, j, v) == ck.checksum_pairs(j, i, v)


def test_single_ulp_sensitivity():
    i, j, v = _pairs()
    a = ck.checksum_pairs(i, j, v)
    v2 = v.copy()
    v2[3] = np.nextafter(v2[3], np.float32(np.inf))
    assert a != ck.checksum_pairs(i, j, v2)


def test_missing_and_duplicate_sensitivity():
    i, j, v = _pairs()
    a = ck.checksum_pairs(i, j, v)
    assert a != ck.checksum_pairs(i[:-1], j[:-1], v[:-1])
    i2 = np.concatenate([i, i[:1]])
    j2 = np.concatenate([j, j[:1]])
    v2 = np.concatenate([v, v[:1]])
    assert a != ck.checksum_pairs(i2, j2, v2)


def test_combine_matches_monolithic():
    i, j, v = _pairs()
    whole = ck.checksum_pairs(i, j, v)
    parts = [ck.raw_pairs(i[:20], j[:20], v[:20]), ck.raw_pairs(i[20:], j[20:], v[20:])]
    assert ck.combine(parts) == whole


def _loop_raw(keys, bits):
    """The checksum's definition, one Python integer per entry."""
    total = 0
    for k, b in zip(keys, bits):
        total = (total + ck._mix(int(k)) * (int(b) + 1)) % ck.MOD
    return total, len(keys)


@pytest.mark.parametrize("way", [2, 3])
@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.float64])
def test_vectorized_matches_per_entry_loop(monkeypatch, way, dtype):
    monkeypatch.setattr(ck, "_BLOCK", 7)  # several partial-sum blocks
    rng = np.random.default_rng(4)
    n = 50
    top = (1 << 31) if way == 2 else (1 << 21)
    idx = rng.integers(0, top, size=(way, n))
    idx[:, 0] = top - 1  # the widest key the packing allows
    v = (rng.random(n) * 4).astype(dtype)
    v[1] = np.finfo(dtype).max  # all value bits set but the sign
    bits = v.view({4: np.uint32, 2: np.uint16, 8: np.uint64}[v.itemsize])
    s = np.sort(idx, axis=0)
    if way == 2:
        keys = [(int(a) << 32) | int(b) for a, b in zip(*s)]
        got = ck.raw_pairs(idx[1], idx[0], v)
    else:
        keys = [(int(a) << 42) | (int(b) << 21) | int(c) for a, b, c in zip(*s)]
        got = ck.raw_triples(idx[2], idx[0], idx[1], v)
    assert got == _loop_raw(keys, bits)


def test_triples_order_and_canonicalization():
    rng = np.random.default_rng(2)
    idx = np.array([(a, b, c) for a in range(6) for b in range(a + 1, 6) for c in range(b + 1, 6)])
    v = rng.random(len(idx)).astype(np.float64)
    a = ck.checksum_triples(idx[:, 0], idx[:, 1], idx[:, 2], v)
    # permute entry order and scramble index order within each triple
    b = ck.checksum_triples(idx[:, 2], idx[:, 0], idx[:, 1], v)
    assert a == b
    parts = [
        ck.raw_triples(idx[:7, 0], idx[:7, 1], idx[:7, 2], v[:7]),
        ck.raw_triples(idx[7:, 0], idx[7:, 1], idx[7:, 2], v[7:]),
    ]
    assert ck.combine(parts) == a
