"""``bench/layers/popcount_roofline.py``: the popcount kernels' share of
the campaign's least time, read only where every campaign of the process
took the ``fused-popcount`` path."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, work  # noqa: E402

CFG = json.loads((ROOT / "bench" / "configs" / "gwas2-bin.json").read_text())
READ = run.reader("layers", "popcount_roofline")
POP_S = 0.0512  # device seconds of the popcount kernel over the window


@pytest.fixture
def registry(monkeypatch):
    """A fresh process registry, as a process that ran nothing has."""
    from repro.obs import metrics

    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "_DEFAULT", reg)
    return reg


def _run(ops=(("metric2_pop_tri_pallas", POP_S), ("fusion", 0.004)),
         campaigns=4, trace=True):
    reduced = {"campaigns": campaigns, "device_ops": list(ops),
               "busy_s": [POP_S + 0.004], "window_s": 2.0, "chips": 1}
    return SimpleNamespace(
        cfg=CFG, stages=None, chips=1, work=work,
        peak=work.peaks("TPU v5 lite"), trace=reduced if trace else None,
        campaigns=[{"ok": True}] * campaigns)


def test_reads_the_popcount_kernels(registry):
    registry.counter("path.fused-popcount").inc(5)  # 4 timed + the warm-up
    least = work.ops(CFG) / 393e12  # ops-bound at the int8 peak
    assert work.least_time_s(CFG, None, work.peaks("TPU v5 lite"), 1)[1] \
        == "ops"
    assert READ(_run()) == pytest.approx(100 * least / (POP_S / 4))
    # every metric2_pop* op counts: the rectangular kernel too
    two = _run(ops=(("metric2_pop_tri_pallas", POP_S / 2),
                    ("metric2_pop_pallas", POP_S / 2)))
    assert READ(two) == pytest.approx(100 * least / (POP_S / 4))
    assert 0 < READ(_run()) < 100


def test_nothing_without_a_trace(registry):
    registry.counter("path.fused-popcount").inc(5)
    assert READ(_run(trace=False)) is None
    assert READ(SimpleNamespace(**dict(vars(_run()), peak=None))) is None


def test_nothing_without_a_popcount_op(registry):
    registry.counter("path.fused-popcount").inc(5)
    assert READ(_run(ops=(("metric2_levels_tri_pallas", POP_S),))) is None


@pytest.mark.parametrize("counts", [
    {},  # the counter absent, as in a program that has none
    {"path.fused-popcount": 4},  # the warm-up not counted
    {"path.fused-popcount": 5, "path.unfused": 1},  # one campaign fell back
])
def test_nothing_unless_every_campaign_took_the_path(registry, counts):
    for name, n in counts.items():
        registry.counter(name).inc(n)
    assert READ(_run()) is None


RECORDED = ROOT / "bench" / "testdata" / "gwas2-bin.closed.xplane.pb"


def test_recorded_trace_through_the_reader(registry):
    """One gwas2-bin.closed campaign traced on a TPU v5e (one chip), kept
    by ``bench.run.measure(cell, 11, 0.1, True, trace_dir=...)``: the
    popcount kernel is the largest device op, and the reader reads it."""
    from bench import trace

    recorded = trace.reduce(trace.load(RECORDED))
    assert recorded["chips"] == 1 and recorded["campaigns"] == 1
    assert recorded["busy_s"] == pytest.approx([0.005221387], rel=1e-9)
    assert recorded["device_ops"][0] == ("metric2_pop_tri_pallas",
                                         pytest.approx(0.004269853, rel=1e-9))
    ctx = SimpleNamespace(cfg=CFG, stages=None, chips=1, trace=recorded,
                          peak=work.peaks("TPU v5 lite"), work=work,
                          campaigns=[{"ok": True}])
    assert READ(ctx) is None  # no campaign counted in this process
    registry.counter("path.fused-popcount").inc(2)  # the window + warm-up
    roof = READ(ctx)
    assert roof == pytest.approx(100 * work.ops(CFG) / 393e12 / 0.004269853)
    assert 0 < roof < 100
    # the whole step's share reads the same least time over all busy time
    whole = run.reader("layers", "device_roofline")(ctx)
    assert whole == pytest.approx(roof * 0.004269853 / 0.005221387)
