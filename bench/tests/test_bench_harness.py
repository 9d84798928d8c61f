"""The harness reads its cells from data: every name in BENCHMARK.json finds
its files, every cell's CPU rehearsal prints a well-formed last line, and
without a TPU (or without the program) the command fails and prints no
result."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _applies(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits its 43,200 s
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_bounds():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    for n in names:
        assert NAME.match(n), n
    assert len(set(CELLS)) == len(CELLS)
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_text_fields_fit():
    texts = [c[k] for c in SPEC["configs"] for k in ("source", "why")]
    texts += [w["why"] for w in SPEC["workloads"]]
    texts += [m["layer"] for m in SPEC["per_layer"]] + SPEC["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_four_chip_cells_within_the_share():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])
    assert len(four) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files_by_name(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert len(w["why"]) <= 200
    entry = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    cfg_file = ROOT / entry["file"]
    assert cfg_file.is_file() and entry["file"].startswith("bench/")
    cfg = json.loads(cfg_file.read_text())
    assert cfg["name"] == w["config"]
    for key in entry["reduced"]:
        assert key in cfg["published"]
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert (ROOT / "bench" / "data" / f"{cfg['data']['kind']}.py").is_file()
    e2e = [m["name"] for m in SPEC["end_to_end"] if _applies(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    for name in e2e:
        assert (ROOT / "bench" / "e2e" / f"{name}.py").is_file()
    layers = [m for m in SPEC["per_layer"] if _applies(m, cell)]
    assert layers
    for m in layers:
        assert (ROOT / "bench" / "layers" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e


def _run(args, cwd=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)  # the harness finds the program itself
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line(cell, trace):
    proc = _run(["bench/run.py", "--workload", cell, "--seed", "3000000019",
                 "--seconds", "0.2", "--trace", str(trace),
                 "--cpu-rehearsal"])
    assert proc.returncode == 1, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert list(line)[-1] == "checks"
    assert line["correct"] is False
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert line["device"]["platform"] == "cpu"
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert line["device"]["count"] == w["chips"]
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in SPEC[kind] if _applies(m, cell)}
    assert line["metrics"] and set(line["metrics"]) <= set(allowed)
    for name, m in line["metrics"].items():
        assert m["unit"] == allowed[name] and m["value"] > 0
    if not trace:
        assert set(line["metrics"]) == set(allowed)
    # the CPU has no device plane: no device metric, busy time or breakdown
    assert "idle_share" not in line["metrics"]
    assert "breakdown" not in line and "busy_s" not in line["device"]
    last = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(s.startswith("check ") for s in last)


def test_refuses_without_a_tpu():
    proc = _run(["bench/run.py", "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert "TPU" in proc.stderr and not proc.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(["bench/run.py", "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", "--trace", "0", "--cpu-rehearsal"],
                cwd=tmp_path)
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_unknown_workload():
    proc = _run(["bench/run.py", "--workload", "no-such.cell", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0 and "no workload" in proc.stderr


def test_cohorts_follow_the_seed():
    import numpy as np

    sys.path.insert(0, str(ROOT))
    from bench import generate

    cfg = json.loads((ROOT / "bench" / "configs" / "gwas2-snp.json").read_text())
    cfg = dict(cfg, n_f=4000, n_v=500)
    traffic = {"pool": 3}
    seed = 2**31 + 977  # the driver's seeds pass 32 signed bits
    a, b = generate.cohorts(cfg, traffic, seed), generate.cohorts(cfg, traffic, seed)
    c = generate.cohorts(cfg, traffic, seed + 1)
    assert len(a) == 3 and all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all() and not (a[0] == a[1]).all()
    V = a[0]
    assert V.dtype == np.uint8 and V.shape == (4000, 500) and V.max() == 2
    # Hardy-Weinberg: per SNP, the share of 2s is about p^2 where p is the
    # minor-allele frequency read back from the counts
    p = V.mean(0) / 2
    assert ((0.03 < p) & (p < 0.53)).all()
    assert abs((V == 2).mean(0) - p * p).max() < 0.03
