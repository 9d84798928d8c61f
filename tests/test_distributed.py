"""Runs the multi-device decomposition-invariance harness in a subprocess
(device count must be set before jax initializes; the main pytest process
keeps the default single CPU device).

The harness runs once per module and writes one record per case of
tests/distributed_cases.py; each case is one test, which fails when its
record is missing or not ok."""
import json
import os
import subprocess
import sys

import pytest

from distributed_cases import CASE_IDS

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


@pytest.fixture(scope="module")
def harness(tmp_path_factory):
    """(records by case id, the harness's exit status, its output's tail)."""
    records_path = tmp_path_factory.mktemp("harness") / "records.jsonl"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "distributed_harness.py"),
             "--out", str(records_path)],
            capture_output=True,
            text=True,
            env=env,
            timeout=900,
        )
        status, tail = proc.returncode, proc.stdout[-2000:] + proc.stderr[-4000:]
    except subprocess.TimeoutExpired as e:
        status, tail = "timed out", str(e)
    records = {}
    if records_path.exists():
        for line in records_path.read_text().splitlines():
            record = json.loads(line)
            records[record["id"]] = record
    return records, status, tail


@pytest.mark.slow
@pytest.mark.parametrize("case", CASE_IDS)
def test_decomposition_invariance(harness, case):
    records, status, tail = harness
    record = records.get(case)
    assert record is not None, (
        f"the harness wrote no record for {case} (exit {status}):\n{tail}"
    )
    assert record["ok"], record["error"]
