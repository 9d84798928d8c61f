"""The comparison that decides ``correct`` fails each fault a cell can have
and the lower-precision control, and passes the sound program: each cell's
run driven at its rehearsal size on CPU devices (``scenarios.py``)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FAULTS = {
    "gwas2-snp.closed": ["stale", "half", "altered"],
    "gwas3-snp.stage": ["stale", "half", "altered"],
    "gwas3-snp-2x2.stage": ["stale", "half", "altered", "no_exchange"],
}
CASES = [(cell, s) for cell, faults in FAULTS.items()
         for s in ["sound", "control", *faults]]


@pytest.fixture(scope="module")
def readings():
    out = {}
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    for cell, faults in FAULTS.items():
        proc = subprocess.run(
            [sys.executable, str(ROOT / "bench" / "tests" / "scenarios.py"),
             "--workload", cell, "--scenarios",
             ",".join(["sound", "control", *faults]),
             "--seeds", "4000000007", "--seconds", "0.3", "--rehearsal"],
            capture_output=True, text=True, env=env, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        for row in proc.stdout.splitlines():
            if row.startswith("{"):
                r = json.loads(row)
                out[cell, r["scenario"]] = r
    return out


def _failing(r):
    return [k for k, c in r["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell,scenario", CASES)
def test_scenario_verdict(readings, cell, scenario):
    r = readings[cell, scenario]
    assert r["attempted"] >= 1
    if scenario == "sound":
        assert _failing(r) == []
    elif scenario == "control":
        assert _failing(r) == ["value_gap"]
    else:
        assert _failing(r), f"{scenario} passed the comparison: {r}"
