"""End-to-end integration tests: the campaign CLI round trip and the dedup stage."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def _run(args, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-m", *args], capture_output=True,
                          text=True, env=env, timeout=timeout)


@pytest.mark.slow
def test_similarity_cli_roundtrip(tmp_path):
    """The campaign launcher writes blocks + manifest with an exact checksum
    that is invariant to the decomposition (run twice, different decomps)."""
    out1 = str(tmp_path / "a")
    out2 = str(tmp_path / "b")
    r1 = _run(["repro.launch.similarity", "--way", "2", "--n-f", "64",
               "--n-v", "48", "--out", out1])
    assert r1.returncode == 0, r1.stderr[-1500:]
    r2 = _run(["repro.launch.similarity", "--way", "2", "--n-f", "64",
               "--n-v", "48", "--n-pv", "4", "--devices", "4", "--out", out2])
    assert r2.returncode == 0, r2.stderr[-1500:]
    m1 = json.load(open(os.path.join(out1, "manifest.json")))
    m2 = json.load(open(os.path.join(out2, "manifest.json")))
    assert m1["checksum"] == m2["checksum"]
    assert m1["results"] == 48 * 47 // 2 == m2["results"]


def test_dedup_finds_planted_duplicates():
    from repro.data.dedup import find_near_duplicates

    rng = np.random.default_rng(1)
    docs = [rng.integers(0, 5000, 300) for _ in range(20)]
    dup = docs[3].copy()
    dup[:20] = rng.integers(0, 5000, 20)
    docs.append(dup)
    hits = find_near_duplicates(docs, 5000, threshold=0.85)
    assert any({i, j} == {3, 20} for i, j, _ in hits)
