"""Binary presence/absence screens (``gwas2-bin``): the engine's
``fused-popcount`` path against the benchmark's plain reference on seeded
carrier cohorts, the reference against the set form of Sorensen-Dice, and
the carrier data kind."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generate, reference  # noqa: E402

CFG = json.loads((ROOT / "bench" / "configs" / "gwas2-bin.json").read_text())
SPEC = CFG["data"]
SEED = 2**31 + 4099  # benchmark seeds pass 32 signed bits


def _carrier(n_f, n_v, seed=SEED):
    return generate._maker(SPEC["kind"])(
        np.random.default_rng([seed, 0]), n_f, n_v, SPEC)


def _campaign(V):
    from repro.api import SimilarityEngine, SimilarityRequest

    fields = generate.request_fields(CFG, {"stages": None})
    return SimilarityEngine().run(SimilarityRequest(**fields), V)


# small, the configuration's rehearsal size, and a shape that fills no
# whole 256-vector tile nor a whole 32-field word
@pytest.mark.parametrize("n_f,n_v", [
    (64, 40), (CFG["rehearsal"]["n_f"], CFG["rehearsal"]["n_v"]), (203, 77),
])
def test_campaign_matches_the_reference(n_f, n_v):
    V = _carrier(n_f, n_v)
    result = _campaign(V)
    assert result.path == "fused-popcount"
    assert result.checksum_source == "device"
    tiles = list(result.tiles())
    I = np.concatenate([t.index[0] for t in tiles])
    J = np.concatenate([t.index[1] for t in tiles])
    vals = np.concatenate([t.values for t in tiles])
    assert vals.dtype == np.float32 and len(vals) == n_v * (n_v - 1) // 2
    assert result.num_results() == len(vals)
    assert result.checksum() == reference.checksum(
        reference.keys((I, J)), vals)
    pick = np.random.default_rng(SEED).integers(0, len(vals), 512)
    ref = reference.pair_reference(V, I[pick], J[pick])
    np.testing.assert_allclose(vals[pick], ref, rtol=1e-6, atol=0)


def test_pair_reference_is_the_set_form():
    """On {0,1} data 2 sum min(a,b) / (sum a + sum b) is
    2|A and B| / (|A| + |B|), counted here with boolean ANDs."""
    V = _carrier(300, 50)
    V[:, 7] = 0  # an empty set: both forms read 0 against another empty one
    V[:, 8] = 0
    rng = np.random.default_rng(1)
    I, J = rng.integers(0, 50, 400), rng.integers(0, 50, 400)
    I[:2], J[:2] = (7, 7), (8, 3)
    B = V.astype(bool)
    inter = (B[:, I] & B[:, J]).sum(axis=0)
    size = B[:, I].sum(axis=0) + B[:, J].sum(axis=0)
    want = np.where(size > 0, 2 * inter / np.maximum(size, 1), 0.0)
    np.testing.assert_array_equal(reference.pair_reference(V, I, J), want)


def test_carrier_cohorts():
    """{0,1} values in the fixed shape, the same cohort for the same seed,
    and a carrier share per vector near 1-(1-p)^2 in [0.0975, 0.75]."""
    a, b = _carrier(4000, 300), _carrier(4000, 300)
    c = _carrier(4000, 300, SEED + 1)
    assert a.dtype == np.uint8 and a.shape == (4000, 300)
    assert set(np.unique(a)) == {0, 1}
    assert (a == b).all() and not (a == c).all()
    share = a.mean(axis=0)
    # 4,000 draws a vector: within 0.03 of its expectation at 4 sigma
    assert ((share > 0.0975 - 0.03) & (share < 0.75 + 0.03)).all()
    assert share.min() < 0.2 and share.max() > 0.65
    pool = generate.cohorts(dict(CFG, n_f=100, n_v=20), {"pool": 3}, SEED)
    assert [v.shape for v in pool] == [(100, 20)] * 3


SCENARIOS = ["sound", "control", "stale", "half", "altered"]


@pytest.fixture(scope="module")
def scenario_readings():
    """The cell's comparison under each fault, at its rehearsal size on
    the CPU (``scenarios.py``)."""
    import os
    import subprocess

    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "scenarios.py"),
         "--workload", "gwas2-bin.closed", "--scenarios", ",".join(SCENARIOS),
         "--seeds", "4000000007", "--seconds", "0.3", "--rehearsal"],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {r["scenario"]: r for r in map(json.loads, (
        row for row in proc.stdout.splitlines() if row.startswith("{")))}


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_scenario_verdict(scenario_readings, scenario):
    """The sound program passes; the bfloat16 control fails ``value_gap``
    alone; each planted fault fails at least one number."""
    r = scenario_readings[scenario]
    assert r["attempted"] >= 1
    failing = [k for k, c in r["checks"].items() if c["value"] > c["limit"]]
    if scenario == "sound":
        assert failing == []
    elif scenario == "control":
        assert failing == ["value_gap"]
    else:
        assert failing, f"{scenario} passed the comparison: {r}"
