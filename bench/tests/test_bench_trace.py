"""bench/trace.py: the reduction from a profiler trace to device busy time,
collective time, top ops and labelled idle gaps; pinned on a synthetic
trace here and on a recorded chip trace in ``bench/testdata``."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 100, 1000),
        _ev("engine.run", 100, 400), _ev("checksum", 500, 300),
        _ev("engine.run", 800, 300),
    ])])
    chip0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_prog", 150, 700)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 50, 100),      # clipped to the window: 50 ns
            _ev("fusion.1", 200, 100),
            _ev("collective-permute-done", 250, 100),  # overlaps fusion.1
            _ev("kernel", 850, 100),
        ]),
    ])
    chip1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[_ev("kernel", 850, 50)]),
        # an async permute in flight 300..700, started by a short op on
        # the ops line: counted once, and not as busy time
        NS(name="Async XLA Ops", events=[
            _ev("collective-permute-start.1", 300, 400),
            _ev("copy-start", 100, 900)]),
    ])
    other = NS(name="/device:TPU:0 SparseCore", lines=[NS(
        name="XLA Ops", events=[_ev("ignored", 100, 1000)])])
    return NS(planes=[host, chip0, chip1, other])


def test_synthetic_reduction():
    r = trace.reduce(_synthetic())
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0: [100,150) + [200,350) + [850,950) = 300 ns busy
    assert r["busy_s"] == pytest.approx([300e-9, 50e-9])
    assert r["collective_s"] == pytest.approx([100e-9, 400e-9])
    assert r["campaigns"] == 2
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(150e-9 / 2)
    assert ops["kernel"] == pytest.approx(150e-9 / 2)
    assert "ignored" not in ops
    # gaps of the busiest chip, cut at host span edges, longest first:
    # [150,200) engine.run; [350,500) engine.run + [500,800) checksum +
    # [800,850) engine.run; [950,1100) engine.run
    assert r["idle_gaps"] == [
        ("checksum", pytest.approx(300e-9)),
        ("engine.run", pytest.approx(150e-9)),
        ("engine.run", pytest.approx(150e-9)),
        ("engine.run", pytest.approx(50e-9)),
        ("engine.run", pytest.approx(50e-9)),
    ]


def test_no_device_plane_reads_nothing():
    data = _synthetic()
    data.planes = data.planes[:1]
    assert trace.reduce(data) is None


RECORDED = ROOT / "bench" / "testdata" / "gwas2-snp.closed.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """One gwas2-snp.closed campaign traced on a TPU v5e (one chip), kept
    by ``bench.run.measure(cell, 11, 3, True, trace_dir=...)``."""
    return trace.reduce(trace.load(RECORDED))


def test_recorded_trace(recorded):
    r = recorded
    assert r["chips"] == 1 and r["campaigns"] == 1
    assert r["window_s"] == pytest.approx(3.957003375, rel=1e-9)
    assert r["busy_s"] == pytest.approx([0.00447424], rel=1e-9)
    assert r["collective_s"] == [0.0]
    assert r["device_ops"][0] == ("metric2_levels_tri_pallas",
                                  pytest.approx(0.003640635, rel=1e-9))
    assert [n for n, _ in r["device_ops"][:4]] == [
        "metric2_levels_tri_pallas", "fusion", "convert_reduce_fusion",
        "broadcast_in_dim"]
    assert r["idle_gaps"][:3] == [
        ("checksum", pytest.approx(2.85154151, rel=1e-9)),
        ("engine.run", pytest.approx(0.662727247, rel=1e-9)),
        ("engine.run", pytest.approx(0.438218633, rel=1e-9)),
    ]


def test_recorded_trace_through_the_readers(recorded):
    import json
    from types import SimpleNamespace

    from bench import run, work

    cfg = json.loads((ROOT / "bench" / "configs" / "gwas2-snp.json").read_text())
    ctx = SimpleNamespace(cfg=cfg, stages=None, chips=1, trace=recorded,
                          peak=work.peaks("TPU v5 lite"), work=work)
    idle = run.reader("layers", "idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 0.00447424 / 3.957003375))
    roof = run.reader("layers", "device_roofline")(ctx)
    assert roof == pytest.approx(100 * work.ops(cfg) / 393e12 / 0.00447424)
    assert 0 < roof < 100
    assert run.reader("layers", "collective_s")(ctx) is None
    dev = run.trace_device(recorded)
    assert dev == {"busy_s": pytest.approx(0.00447424),
                   "window_s": pytest.approx(3.957003375)}
    b = run.breakdown(recorded)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 and all(len(e) == 2 for e in v)
               for v in b.values())
