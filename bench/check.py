"""The comparison that decides ``correct``, run once the window has closed,
over what the timed campaigns themselves produced.

For each checked campaign it reads the result the way a user does
(``result.tiles()``: global indices from the host assembly, values read
back from the device) and compares three numbers with the configuration's
limits:

* ``index_faults``: campaigns whose index set is not the expected one:
  a wrong count, an index out of range or repeated inside a tuple, or a
  result present twice;
* ``checksum_mismatch``: campaigns whose ``result.checksum()`` differs from
  the §5 checksum recomputed here over the same indices and values;
* ``value_gap``: the largest relative gap between a value the campaign
  produced and the float64 definition (``reference.py``), over entries
  sampled from the seed.
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference

#: entries per campaign whose values are compared with the definition
SAMPLES = 2048
#: share of the window's campaigns compared, and at most how many: a sample
#: drawn from the seed, so that the comparison takes less time than the
#: window (a full-size window holds 5 to 15 campaigns)
SHARE = 0.5
MAX_COMPARED = 8
#: gaps are taken relative to the reference, or to this where it is
#: smaller: below every nonzero value the configurations can produce (at
#: least 1.5 / (3 levels n_f) > 1e-5), so a zero reference reads as an
#: absolute gap
FLOOR = 1e-6


def campaign(result, V, expected: int, rng: np.random.Generator) -> dict:
    """Readings of one campaign (see the module docstring)."""
    tiles = list(result.tiles())
    index = [np.concatenate([t.index[a] for t in tiles])
             for a in range(result.way)]
    vals = np.concatenate([t.values for t in tiles])
    n_v = V.shape[1]
    keys = reference.keys(index)
    bad = (len(vals) != expected
           or any(int(a.min()) < 0 or int(a.max()) >= n_v for a in index)
           or any((index[a] == index[b]).any()
                  for a in range(len(index)) for b in range(a))
           or np.unique(keys).size != keys.size)
    mismatch = result.checksum() != reference.checksum(keys, vals)
    pick = rng.integers(0, len(vals), size=min(SAMPLES, len(vals)))
    ref = reference.values(V, [a[pick] for a in index])
    got = vals[pick].astype(np.float64)
    gap = np.abs(got - ref) / np.maximum(np.abs(ref), FLOOR)
    return {"index_faults": int(bad), "checksum_mismatch": int(mismatch),
            "value_gap": float(gap.max())}


def readings(done, pool, expected: int, seed: int) -> dict:
    """Combine the readings of a seeded sample of ``done``, the window's
    (cohort index, result) pairs.  Counts add up, gaps take the largest."""
    rng = np.random.default_rng([seed, 1])
    n = min(math.ceil(len(done) * SHARE), MAX_COMPARED)
    done = [done[i] for i in sorted(rng.choice(len(done), n, replace=False))]
    total = {"index_faults": 0, "checksum_mismatch": 0, "value_gap": 0.0,
             "compared": n}
    for cohort, result in done:
        r = campaign(result, pool[cohort], expected, rng)
        total["index_faults"] += r["index_faults"]
        total["checksum_mismatch"] += r["checksum_mismatch"]
        total["value_gap"] = max(total["value_gap"], r["value_gap"])
    return total


def verdict(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for the printed line, every reading
    beside its limit."""
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
