"""bench/spans.py: the program-span readers and the idle time by span,
pinned on a synthetic trace with nested ``repro.*`` spans, on the recorded
chip trace in ``bench/testdata`` and on a CPU rehearsal."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run, spans, trace  # noqa: E402
from repro.obs import metrics  # noqa: E402

READERS = {"encode_s": "encode", "dispatch_s": "dispatch",
           "readback_s": "readback", "entries_s": "entries",
           "hash_s": "hash"}


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("repro.encode", 20, 40),  # before the window: not read
        _ev("bench.window", 100, 1000),
        _ev("engine.run", 100, 400),
        _ev("repro.campaign", 110, 370),
        _ev("repro.encode", 120, 60),
        _ev("repro.dispatch", 190, 70),
        _ev("lower_sharding_computation", 195, 35),
        _ev("repro.ring-step", 260, 120),
        _ev("repro.readback", 380, 45),
        _ev("checksum", 500, 300),
        _ev("repro.entries", 520, 90), _ev("repro.hash", 610, 150),
        _ev("engine.run", 800, 300),
        _ev("repro.campaign", 810, 280),
        _ev("repro.dispatch", 820, 80),
        _ev("backend_compile_and_load", 830, 40),
        _ev("repro.encode", 950, 50),
    ])])
    chip0 = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("fusion.1", 50, 100),      # clipped to the window: 50 ns
        _ev("fusion.1", 200, 100),
        _ev("collective-permute-done", 250, 100),
        _ev("kernel", 850, 100),
    ])])
    chip1 = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        _ev("kernel", 850, 50)])])
    return NS(planes=[host, chip0, chip1])


def test_synthetic_idle_by_span():
    """The busiest chip's idle time, summed by the innermost program span
    around it; a gap under no program span keeps the benchmark's label
    (``engine.run``, ``checksum``), and only the top ten are kept."""
    by_span = spans.idle_by_span(_synthetic())
    # idle on chip 0: [150,200) + [350,850) + [950,1100) = 700 ns
    assert [n for n, _ in by_span] == [
        "repro.campaign", "repro.hash", "repro.entries", "repro.encode",
        "checksum", "repro.readback", "engine.run", "repro.ring-step",
        "backend_compile_and_load", "repro.dispatch"]
    assert dict(by_span) == pytest.approx({
        # [180,190) + [425,480) + [810,820) + [1000,1090)
        "repro.campaign": 165e-9,
        "repro.hash": 150e-9, "repro.entries": 90e-9,
        "repro.encode": 80e-9,     # [150,180) + [950,1000)
        "checksum": 60e-9,         # [500,520) + [760,800)
        "repro.readback": 45e-9,
        "engine.run": 40e-9,       # [480,500) + [800,810) + [1090,1100)
        "repro.ring-step": 30e-9,
        "backend_compile_and_load": 20e-9,
        "repro.dispatch": 15e-9,   # [190,195) + [820,830)
    })  # lower_sharding_computation's 5 ns, [195,200), is eleventh


def test_idle_by_span_needs_window_and_device():
    data = _synthetic()
    data.planes = data.planes[:1]
    assert spans.idle_by_span(data) is None
    data = _synthetic()
    data.planes[0].lines[0].events = [
        ev for ev in data.planes[0].lines[0].events
        if ev.name != "bench.window"]
    assert spans.idle_by_span(data) is None


RECORDED = ROOT / "bench" / "testdata" / "gwas2-snp.closed.xplane.pb"


def test_recorded_trace_keeps_the_benchmark_labels():
    """A chip trace of a program without ``repro.*`` spans (it predates
    them): all of the busiest chip's idle time keeps the benchmark's
    labels, and the checksum holds the most of it."""
    data = trace.load(RECORDED)
    r = trace.reduce(data)
    by_span = dict(spans.idle_by_span(data))
    assert set(by_span) <= {"engine.run", "checksum", "harness"}
    assert sum(by_span.values()) == pytest.approx(
        r["window_s"] - max(r["busy_s"]), rel=1e-9)
    assert by_span["checksum"] > by_span["engine.run"]


@pytest.fixture
def registry(monkeypatch):
    """A fresh default registry, as a benchmark process starts with."""
    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "_DEFAULT", fresh)
    return fresh


def test_program_span_readers(registry):
    for i, span in enumerate(READERS.values()):
        for v in (0.25 * (i + 1), 0.75 * (i + 1)):
            registry.histogram("span." + span).observe(v)
    ctx = NS(campaigns=[{}, {}])
    got = {name: run.reader("layers", name)(ctx) for name in READERS}
    assert got == pytest.approx({"encode_s": 0.5, "dispatch_s": 1.0,
                                 "readback_s": 1.5, "entries_s": 2.0,
                                 "hash_s": 2.5})
    assert run.reader("layers", "encode_s")(NS(campaigns=[])) is None


def test_readers_read_nothing_without_program_spans(registry):
    """A program that keeps no span totals (or a window the profiler did
    not record) gives no reading, and no error."""
    registry.counter("jit.lowerings").inc()
    registry.histogram("span.campaign").observe(1.0)
    ctx = NS(campaigns=[{}])
    for name in READERS:
        assert run.reader("layers", name)(ctx) is None


def test_spans_rehearsal():
    """The traced CPU rehearsal of a cell reports every engine and result
    span, the five readers among them; a CPU has no device plane, so no
    idle time by span."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/spans.py", "--workload", "gwas3-snp.stage",
         "--seed", "3000000019", "--seconds", "0.2", "--cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["idle_by_span"] is None
    per = out["per_campaign_s"]
    assert {"campaign", "encode", "stage", "dispatch", "ring-step",
            "readback", "count", "entries", "hash"} <= set(per)
    assert all(v > 0 for v in per.values())
    got = out["line"]["metrics"]
    for name, span in READERS.items():
        assert got[name]["value"] == pytest.approx(per[span])
