"""repro.obs: the tracing/metrics contract.

Pins the observability design constraints (docs/OBSERVABILITY.md):

* **Disabled is free** — with no tracer and no profiler session
  ``span()`` returns ONE shared no-op singleton (no allocation), and a
  traced-then-untraced campaign is checksum **bit-identical** on the
  streamed and the delta paths, under either sink;
* inside a JAX profiler session every span lands in the ``.xplane.pb``
  as ``repro.<name>``, nested as the code nests it;
* ``meta["obs"]["jit"]`` counts each campaign's lowerings and compiles;
* spans nest through the contextvar stack and cross threads via
  ``copy_context`` — a ``ShardPrefetcher`` staging span and a
  ``SimilarityService`` worker span both record the submitting
  context's campaign span as their ``parent``;
* histogram percentiles are exact nearest-rank over the bounded window;
* every exported trace is valid Chrome trace-event JSON — property-
  tested over random span trees and cross-checked by the rejection
  cases ``validate_chrome_trace`` must catch;
* ``format_phase_table`` prints every canonical phase row even at count
  0 (the zero-encode proof for dataset campaigns is a ROW, not an
  absence), so CI can grep unconditionally.
"""
import os
import threading
import time

import jax
import numpy as np
import pytest

try:  # property tests run under hypothesis when present (CI installs it);
    # a seeded deterministic sweep covers the same generator otherwise
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.api import InputSpec, SimilarityEngine, SimilarityRequest
from repro.core.synthetic import random_integer_vectors
from repro.obs import trace
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.stream.prefetch import ShardPrefetcher
from repro.store import append_dataset, write_dataset


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test leaves the process untraced (disabled is the default)."""
    trace.disable()
    yield
    trace.disable()


# -- disabled mode: zero overhead --------------------------------------------


def test_disabled_span_is_shared_singleton():
    assert not trace.enabled()
    assert trace.get_tracer() is None
    s1, s2 = trace.span("a"), trace.span("b", {"k": 1})
    assert s1 is s2  # one process-wide null object, no allocation
    with s1 as sp:
        assert sp.add(bytes=10) is sp  # no-ops, chainable


def test_span_is_singleton_again_after_a_profiler_session(tmp_path):
    """Inside a profiler session a span is a live annotation even with no
    Chrome tracer; once the session ends, ``span()`` is free again."""
    from jax.profiler import TraceAnnotation

    with jax.profiler.trace(str(tmp_path)):
        assert TraceAnnotation.is_enabled()
        sp = trace.span("a")
        assert sp is not trace.span("b")
        with sp as inner:
            assert inner.add(bytes=1) is inner
    assert not TraceAnnotation.is_enabled()
    assert trace.span("a") is trace.span("b")


@pytest.mark.parametrize("chrome", [False, True])
def test_profiled_spans_total_in_the_registry(tmp_path, monkeypatch, chrome):
    """Each span the profiler sink records also observes its seconds in
    the default registry's ``span.<name>`` histogram; spans outside the
    session add nothing there."""
    from repro.obs import metrics

    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "_DEFAULT", registry)
    if chrome:
        trace.enable()
    try:
        with trace.span("outside"):
            pass
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(3):
                with trace.span("outer"):
                    with trace.span("inner"):
                        time.sleep(0.002)
        with trace.span("outside"):
            pass
    finally:
        trace.disable()
    snap = registry.snapshot()
    assert set(snap) == {"span.outer", "span.inner"}
    assert snap["span.outer"]["count"] == snap["span.inner"]["count"] == 3
    assert snap["span.inner"]["sum"] >= 3 * 0.002
    assert snap["span.outer"]["sum"] >= snap["span.inner"]["sum"]


# -- enabled: nesting, attrs, aggregation ------------------------------------


def test_span_nesting_records_parent_path():
    t = trace.enable()
    with trace.span("campaign"):
        assert trace.current_path() == ("campaign",)
        with trace.span("ring-step") as sp:
            sp.add(steps=3)
    trace.disable()
    evs = t.events()
    kinds = [(ph, name) for ph, name, *_ in evs]
    assert kinds == [("B", "campaign"), ("B", "ring-step"),
                     ("E", "ring-step"), ("E", "campaign")]
    b_inner = evs[1]
    assert b_inner[4] == {"parent": "campaign"}
    e_inner = evs[2]
    assert e_inner[4] == {"steps": 3}
    agg = t.phase_stats()
    assert agg["ring-step"]["count"] == 1
    assert 0.0 <= agg["ring-step"]["seconds"] <= agg["campaign"]["seconds"]


def test_complete_virtual_lane_keeps_nesting_wellformed():
    """An externally measured interval overlapping the thread's own spans
    goes on a virtual tid lane — the exported trace still validates."""
    t = trace.enable()
    with trace.span("serve-compute"):
        now = t._clock()
        t.complete("serve-queue-wait", now - 5_000_000, now,
                   {"wait_seconds": 0.005}, tid=0)
    trace.disable()
    assert trace.validate_chrome_trace(t.chrome_trace()) == 4
    waits = [e for e in t.events() if e[1] == "serve-queue-wait"]
    assert {e[3] for e in waits} == {0}


def test_prefetcher_spans_nest_under_campaign_across_threads():
    t = trace.enable()
    buffers = [np.zeros(4, np.uint8) for _ in range(2)]
    seen_tids = set()

    def fill(idx, buf):
        buf[:] = idx
        seen_tids.add(threading.get_ident())

    with trace.span("campaign"):
        # prefetcher constructed INSIDE the span: copy_context carries it
        with ShardPrefetcher(fill, 3, buffers) as pf:
            for idx, buf in pf:
                assert buf[0] == idx
                pf.release(buf)
    trace.disable()
    assert seen_tids and threading.get_ident() not in seen_tids
    stages = [e for e in t.events() if e[0] == "B" and e[1] == "prefetch-stage"]
    assert len(stages) == 3
    assert all(e[4] == {"parent": "campaign"} for e in stages)
    assert trace.validate_chrome_trace(t.chrome_trace()) == t.event_count()


def test_service_worker_spans_carry_submitter_context():
    from repro.serve.engine import SimilarityService

    V = random_integer_vectors(24, 10, max_value=2, seed=0)
    t = trace.enable()
    with trace.span("client"):
        with SimilarityService() as svc:
            svc.submit(SimilarityRequest(way=2, metric="czekanowski"), V)
    trace.disable()
    names = {e[1] for e in t.events()}
    assert {"serve-queue-wait", "serve-compute", "campaign"} <= names
    b_compute = next(e for e in t.events()
                     if e[0] == "B" and e[1] == "serve-compute")
    assert b_compute[4] == {"parent": "client"}
    assert trace.validate_chrome_trace(t.chrome_trace()) == t.event_count()


# -- metrics registry ---------------------------------------------------------


def test_histogram_nearest_rank_percentiles():
    h = Histogram(threading.RLock())
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50) == 50.0
    assert h.percentile(90) == 90.0
    assert h.percentile(99) == 99.0
    assert h.percentile(100) == 100.0
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["mean"] == 50.5
    assert snap["p50"] == 50.0 and snap["max"] == 100.0


def test_histogram_empty_and_bounded_window():
    h = Histogram(threading.RLock(), max_samples=4)
    assert h.percentile(50) == 0.0 and h.snapshot()["p99"] == 0.0
    for v in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        h.observe(v)
    # count/sum see everything; the window retains the most recent 4
    assert h.count == 6 and h.sum == 21.0
    assert h.percentile(100) == 6.0 and h.percentile(1) == 3.0


def test_registry_single_lock_and_type_guard():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    assert reg.counter("hits") is c
    with pytest.raises(TypeError, match="Counter"):
        reg.gauge("hits")
    with reg.locked():
        c.inc()  # RLock: metric ops re-enter under the held registry lock
        reg.gauge("depth").inc(2)
    assert reg.snapshot() == {"hits": 1, "depth": 2.0}


# -- Chrome trace format: property test + rejection cases ---------------------

_SPAN_NAMES = ("encode", "ring-step", "merge", "x")


def _emit(node):
    if isinstance(node, str):
        with trace.span(node):
            pass
    else:
        name, kids = node
        with trace.span(name):
            for k in kids:
                _emit(k)


def _random_tree(rng, depth=0):
    name = _SPAN_NAMES[rng.integers(len(_SPAN_NAMES))]
    if depth >= 3 or rng.random() < 0.4:
        return name
    return (name, [_random_tree(rng, depth + 1)
                   for _ in range(rng.integers(0, 4))])


def _check_forest(forest):
    t = trace.enable()
    for node in forest:
        _emit(node)
    ts = t._clock()
    t.complete("external", ts, ts, {"seconds": 0.0})
    trace.disable()
    payload = t.chrome_trace()
    assert trace.validate_chrome_trace(payload) == t.event_count()
    assert all(ev["ts"] >= 0.0 for ev in payload["traceEvents"])


if HAVE_HYPOTHESIS:
    _NAMES = st.sampled_from(_SPAN_NAMES)
    _TREES = st.recursive(
        _NAMES, lambda kids: st.tuples(_NAMES, st.lists(kids, max_size=3)),
        max_leaves=12,
    )

    @settings(max_examples=40, deadline=None)
    @given(st.lists(_TREES, max_size=4))
    def test_random_span_trees_export_valid_chrome_traces(forest):
        _check_forest(forest)
else:
    def test_random_span_trees_export_valid_chrome_traces():
        for seed in range(40):
            rng = np.random.default_rng(seed)
            _check_forest([_random_tree(rng)
                           for _ in range(rng.integers(0, 5))])


def test_validator_rejections():
    pid, tid = 1, 1

    def ev(ph, name, ts):
        return {"name": name, "ph": ph, "ts": ts, "pid": pid, "tid": tid}

    with pytest.raises(ValueError, match="traceEvents"):
        trace.validate_chrome_trace(["not", "a", "dict"])
    with pytest.raises(ValueError, match="missing field 'tid'"):
        trace.validate_chrome_trace(
            {"traceEvents": [{"name": "a", "ph": "B", "ts": 0, "pid": 1}]}
        )
    with pytest.raises(ValueError, match="monotonic"):
        trace.validate_chrome_trace(
            {"traceEvents": [ev("B", "a", 5.0), ev("E", "a", 1.0)]}
        )
    with pytest.raises(ValueError, match="does not match"):
        trace.validate_chrome_trace(
            {"traceEvents": [ev("B", "a", 0.0), ev("E", "b", 1.0)]}
        )
    with pytest.raises(ValueError, match="unclosed"):
        trace.validate_chrome_trace({"traceEvents": [ev("B", "a", 0.0)]})
    with pytest.raises(ValueError, match="not 'B'/'E'"):
        trace.validate_chrome_trace({"traceEvents": [ev("X", "a", 0.0)]})
    assert trace.validate_chrome_trace({"traceEvents": []}) == 0


# -- phase table --------------------------------------------------------------


def test_phase_table_always_prints_canonical_rows():
    table = trace.format_phase_table({})
    lines = table.splitlines()
    assert lines[0].split() == ["phase", "count", "seconds", "share"]
    for name in trace.CANONICAL_PHASES:
        assert any(ln.startswith(name + " ") for ln in lines[1:]), name
    # recorded extras appear after the canonical rows
    table = trace.format_phase_table({
        "campaign": {"count": 1, "seconds": 2.0},
        "ring-step": {"count": 4, "seconds": 1.0},
    })
    assert table.splitlines()[-1].startswith("campaign ")
    row = next(ln for ln in table.splitlines() if ln.startswith("ring-step"))
    assert row.split() == ["ring-step", "4", "1.000000", "33.3%"]


# -- bit-identity: tracing must not change results ----------------------------


def _streamed_request(path):
    return SimilarityRequest(
        way=2, metric="czekanowski", impl="levels", levels=2,
        streaming="on", max_host_bytes=400,
        input=InputSpec(source="planes", path=path),
    )


def test_traced_streamed_campaign_is_bit_identical(tmp_path):
    path = os.path.join(str(tmp_path), "ds")
    write_dataset(path, random_integer_vectors(64, 20, max_value=2, seed=7),
                  levels=2, n_shards=2)
    engine = SimilarityEngine()
    plain = engine.run(_streamed_request(path))

    t = trace.enable()
    traced = engine.run(_streamed_request(path))
    trace.disable()

    assert traced.checksum() == plain.checksum()
    # untraced results still carry the normalized obs block...
    obs_plain = plain.meta["obs"]
    assert obs_plain["comparisons"] > 0 and "phases" not in obs_plain
    # ...and always-on overlap accounting
    assert plain.meta["stream"]["stall_seconds"] >= 0.0
    assert plain.meta["stream"]["compute_seconds"] > 0.0
    # traced run: per-phase breakdown beside the always-on jit counts
    obs_traced = traced.meta["obs"]
    phases = obs_traced["phases"]
    assert phases["ring-step"]["count"] == plain.meta["stream"]["chunks"]
    assert phases["prefetch-stage"]["count"] == phases["ring-step"]["count"]
    assert phases["merge"]["count"] == 1 and "encode" not in phases
    assert set(obs_traced["jit"]) == {"lowerings", "compiles"}
    assert trace.validate_chrome_trace(t.chrome_trace()) == t.event_count()


def test_traced_delta_campaign_is_bit_identical(tmp_path):
    path = os.path.join(str(tmp_path), "ds")
    V0 = random_integer_vectors(32, 12, max_value=2, seed=8)
    Vn = random_integer_vectors(32, 5, max_value=2, seed=9)
    write_dataset(path, V0, levels=2, n_shards=1)
    base = dict(way=2, metric="czekanowski", impl="levels", levels=2)
    engine = SimilarityEngine()
    req = SimilarityRequest(**base, input=InputSpec(source="planes",
                                                    path=path))
    prior = engine.run(req)
    append_dataset(path, Vn)

    plain = engine.run_delta(req, prior)

    t = trace.enable()
    traced = engine.run_delta(req, prior)
    trace.disable()

    assert traced.checksum() == plain.checksum()
    phases = traced.meta["obs"]["phases"]
    assert phases["delta-border"]["count"] == 1
    assert phases["merge"]["count"] == 1
    assert "ring-step" not in phases  # delta campaigns have no ring
    # border-proportional comparisons, not N^2
    d = traced.meta["delta"]
    assert traced.meta["obs"]["comparisons"] == d["computed_entries"] * 32
    assert trace.validate_chrome_trace(t.chrome_trace()) == t.event_count()


# -- the profiler sink --------------------------------------------------------

_ENGINE_SPANS = {"validate", "encode", "stage", "dispatch", "ring-step",
                 "readback"}


def _campaign_request(way):
    extra = {"n_st": 2, "stages": (0,)} if way == 3 else {}
    return SimilarityRequest(way=way, metric="czekanowski", impl="levels",
                             levels=2, encoding="bitplane", **extra)


def _profiled(tmp_path, fn):
    """Run ``fn()`` inside a JAX profiler session; return its result and
    the session's ``repro.*`` host events as (name, start, end)."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    events = [(ev.name[len(trace.PROFILER_PREFIX):], ev.start_ns,
               ev.start_ns + ev.duration_ns)
              for plane in data.planes if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(trace.PROFILER_PREFIX)]
    return out, events


@pytest.mark.parametrize("way,chrome", [(2, False), (3, True)])
def test_profiler_sink_nests_repro_spans(tmp_path, way, chrome):
    """Every engine and result span lands in the profiler trace as
    ``repro.<name>``; the engine's nest inside ``repro.campaign``, the
    device checksum's ``entries`` (descriptors) and ``hash`` (partials)
    among them, the result count that follows scans no tile, no two spans
    overlap without nesting, and the checksum is the untraced one, with
    or without the Chrome tracer on as well."""
    V = random_integer_vectors(64, 36, max_value=2, seed=11)
    engine = SimilarityEngine()
    plain = engine.run(_campaign_request(way), V).checksum()

    def campaign():
        result = engine.run(_campaign_request(way), V)
        return result.checksum()

    tracer = trace.enable() if chrome else None
    try:
        checksum, events = _profiled(tmp_path, campaign)
    finally:
        trace.disable()
    assert checksum == plain
    names = {n for n, _, _ in events}
    assert _ENGINE_SPANS | {"campaign", "count", "entries", "hash"} <= names
    (c0, c1), = [(s, e) for n, s, e in events if n == "campaign"]
    (n0, n1), = [(s, e) for n, s, e in events if n == "count"]
    for n, s, e in events:
        if n in _ENGINE_SPANS | {"entries", "hash"}:
            assert c0 <= s and e <= c1, n
    # the result count follows the campaign and reads the device count
    assert c1 <= n0
    assert not any(n == "entries" and n0 <= s and e <= n1
                   for n, s, e in events)
    for (n1, s1, e1) in events:
        for (n2, s2, e2) in events:
            # any two spans are disjoint or one holds the other
            assert e1 <= s2 or e2 <= s1 or (s1 <= s2 and e2 <= e1) \
                or (s2 <= s1 and e1 <= e2), (n1, n2)
    if chrome:
        recorded = {e[1] for e in tracer.events()}
        assert _ENGINE_SPANS | {"campaign", "entries", "hash"} <= recorded


@pytest.mark.parametrize("source", ["streamed", "delta"])
def test_profiled_campaign_is_bit_identical(tmp_path, source):
    path = os.path.join(str(tmp_path), "ds")
    write_dataset(path, random_integer_vectors(64, 20, max_value=2, seed=7),
                  levels=2, n_shards=2)
    engine = SimilarityEngine()
    req = _streamed_request(path)
    if source == "streamed":
        def campaign():
            return engine.run(req)
    else:
        prior = engine.run(req)
        append_dataset(path, random_integer_vectors(64, 5, max_value=2,
                                                    seed=9))

        def campaign():
            return engine.run_delta(req, prior)
    plain = campaign()
    profiled, events = _profiled(tmp_path / "trace", campaign)
    assert profiled.checksum() == plain.checksum()
    span = "ring-step" if source == "streamed" else "delta-border"
    assert {"campaign", span, "merge"} <= {n for n, _, _ in events}


@pytest.mark.parametrize("way", [2, 3])
def test_campaign_jit_counts(way):
    """``meta["obs"]["jit"]`` counts the campaign's own lowerings and
    compiles: a repeated campaign of either way reuses its cached program
    and lowers and compiles nothing."""
    V = random_integer_vectors(48, 24, max_value=2, seed=5)
    engine = SimilarityEngine()
    first = engine.run(_campaign_request(way), V).meta["obs"]["jit"]
    again = engine.run(_campaign_request(way), V).meta["obs"]["jit"]
    assert set(first) == set(again) == {"lowerings", "compiles"}
    assert again == {"lowerings": 0, "compiles": 0}


@pytest.mark.parametrize("metric,levels,path", [
    ("sorenson", 1, "fused-popcount"),
    ("czekanowski", 2, "fused-levels"),
])
def test_campaign_path_counter(metric, levels, path):
    """Each campaign counts the contraction path its ``TileExecutor``
    resolved as ``path.<path>`` in the default registry, once, and
    records it in ``meta["obs"]["path"]`` beside the checksum source."""
    from repro.obs.metrics import default_registry

    reg = default_registry()
    V = random_integer_vectors(48, 24, max_value=levels, seed=6)
    before = {k: v for k, v in reg.snapshot().items()
              if k.startswith("path.")}
    result = SimilarityEngine().run(SimilarityRequest(
        way=2, metric=metric, impl="levels", levels=levels), V)
    after = {k: v for k, v in reg.snapshot().items() if k.startswith("path.")}
    counted = {k: after[k] - before.get(k, 0) for k in after}
    assert {k: n for k, n in counted.items() if n} == {f"path.{path}": 1}
    assert result.path == path
    assert result.meta["obs"]["path"] == path
    assert result.meta["obs"]["checksum"] == "device"


def test_campaign_path_on_every_engine_path(tmp_path):
    """3-way, batched, streamed and delta campaigns record their path too;
    a loaded result keeps the path its campaign recorded in ``meta``."""
    engine = SimilarityEngine()
    V = random_integer_vectors(32, 14, max_value=1, seed=3)
    req = dict(way=2, metric="sorenson", impl="levels", levels=1)
    threeway = engine.run(SimilarityRequest(
        way=3, metric="sorenson", impl="levels", levels=1, n_st=2,
        encoding="bitplane"), V)
    assert threeway.meta["obs"]["path"] == "fused-popcount-ring"
    batched = engine.run(SimilarityRequest(
        **req, metrics=("czekanowski",)), V)
    assert batched.meta["obs"]["path"] == "fused-popcount"
    assert all(r.path == "fused-popcount" for _, _, r in batched)

    path = os.path.join(str(tmp_path), "ds")
    write_dataset(path, V, levels=1, n_shards=2)
    sreq = SimilarityRequest(**req, streaming="on", max_host_bytes=400,
                             input=InputSpec(source="planes", path=path))
    streamed = engine.run(sreq)
    assert streamed.meta["obs"]["path"] == "streamed-fused-popcount"
    saved = os.path.join(str(tmp_path), "saved")
    streamed.save(saved)
    loaded = type(streamed).load(saved)
    assert loaded.path is None
    assert loaded.meta["obs"]["path"] == "streamed-fused-popcount"
    append_dataset(path, random_integer_vectors(32, 4, max_value=1, seed=4))
    delta = engine.run_delta(sreq, streamed)
    assert delta.meta["obs"]["path"] == "streamed-fused-popcount"
