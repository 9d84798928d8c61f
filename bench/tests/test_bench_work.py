"""bench/work.py: result counts against what the engine returns on CPU
devices, the pinned counts of the benchmark's configurations, and the
peaks table."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402

# (way, n_v, n_pv, n_pr, n_st, stages): single and multi-block, padded
# blocks, volume blocks (n_pv=3) and the benchmark's own 3-way geometries
GEOMETRIES = [
    (2, 13, 1, 1, 1, None), (2, 14, 2, 2, 1, None), (2, 17, 3, 1, 1, None),
    (3, 48, 1, 1, 2, (0,)), (3, 48, 1, 1, 2, (1,)), (3, 40, 2, 2, 2, (0,)),
    (3, 36, 3, 1, 2, (1,)), (3, 30, 1, 1, 1, None),
    (3, 1152, 1, 1, 48, (0,)), (3, 1152, 2, 2, 48, (0,)),
]

ENGINE_COUNTS = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.api import SimilarityEngine, SimilarityRequest
out = []
for way, n_v, n_pv, n_pr, n_st, stages in json.loads(sys.argv[1]):
    V = np.random.default_rng(n_v).integers(0, 3, (8, n_v), dtype=np.uint8)
    engine = SimilarityEngine(devices=jax.devices()[:n_pv * n_pr])
    request = SimilarityRequest(way=way, n_pv=n_pv, n_pr=n_pr, n_st=n_st,
                                stages=None if stages is None else tuple(stages))
    out.append(engine.run(request, V).num_results())
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def engine_counts():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", ENGINE_COUNTS, json.dumps(GEOMETRIES)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _cfg(way, n_v, n_pv, n_pr, n_st):
    return dict(way=way, n_v=n_v, n_pv=n_pv, n_pr=n_pr, n_st=n_st)


@pytest.mark.parametrize("i", range(len(GEOMETRIES)))
def test_results_match_the_engine(engine_counts, i):
    way, n_v, n_pv, n_pr, n_st, stages = GEOMETRIES[i]
    assert work.results(_cfg(way, n_v, n_pv, n_pr, n_st), stages) \
        == engine_counts[i]


@pytest.mark.parametrize("name,stages,expected", [
    ("gwas2-snp", None, 8_386_560),
    ("gwas3-snp", [0], 5_156_232),
    ("gwas3-snp-2x2", [0], 5_909_736),
])
def test_configuration_counts(name, stages, expected):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    assert work.results(cfg, stages) == expected
    assert work.comparisons(cfg, stages) == expected * cfg["n_f"]
    assert work.ops(cfg, stages) == 2 * cfg["levels"] * expected * cfg["n_f"]
    payload = cfg["levels"] * cfg["n_f"] // 8 * cfg["n_v"]
    assert work.nbytes(cfg, stages) == payload + 4 * expected


def test_padded_volume_blocks_refused():
    with pytest.raises(ValueError, match="volume"):
        work.results(_cfg(3, 34, 3, 1, 2), [0])


def test_peaks_table():
    table = json.loads(work.PEAKS_FILE.read_text())
    assert "TPU v5e" in table["source"]
    v5e = work.peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_least_time_takes_the_larger_bound():
    cfg = json.loads((ROOT / "bench" / "configs" / "gwas2-snp.json").read_text())
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_time_s(cfg, None, peak, 1)
    assert bound == "ops"
    assert t == pytest.approx(work.ops(cfg) / 393e12)
    t4, _ = work.least_time_s(cfg, None, peak, 4)
    assert t4 == pytest.approx(t / 4)
    slow_compute = dict(peak, int8_ops_per_s=1e6, hbm_bytes_per_s=1e15)
    mem = dict(peak, int8_ops_per_s=1e18, hbm_bytes_per_s=1e6)
    assert work.least_time_s(cfg, None, slow_compute, 1)[1] == "ops"
    assert work.least_time_s(cfg, None, mem, 1)[1] == "bytes"
