"""Thread-aware span tracer with zero overhead when disabled.

One span API, two sinks.  ``span(name)`` records into whichever is on:

* the Chrome tracer installed by ``enable()`` — B/E events under
  ``name``, which ``meta["obs"]["phases"]`` and the CLI table read;
* a JAX profiler session (``jax.profiler.trace`` / ``start_trace``) — a
  ``jax.profiler.TraceAnnotation`` named ``"repro." + name``, which lands
  in the session's ``.xplane.pb`` on the same clock as the device ops,
  its seconds also observed in ``default_registry()``'s
  ``"span." + name`` histogram.

Design constraints (pinned by tests/test_obs.py):

* **Disabled is free.**  With neither sink on, ``span()`` returns ONE
  shared no-op singleton — a traced call site costs a global read, an
  ``is None`` branch and the profiler's static ``is_enabled()`` check,
  with no allocation, no lock and no clock read.  The engines therefore
  leave their span calls in place permanently; campaign checksums and
  hot-path timings are untouched by either sink.

* **Thread-aware.**  Events carry ``threading.get_ident()`` as the
  Chrome ``tid``; span nesting is tracked in a ``contextvars.ContextVar``
  so callers that hop threads (``ShardPrefetcher``'s staging worker,
  ``SimilarityService``'s campaign workers) can carry their logical
  parent across via ``contextvars.copy_context()`` — the B event records
  the parent path in ``args["parent"]``.

* **Chrome trace-event output.**  ``Tracer.chrome_trace()`` emits
  strictly matched B/E duration pairs (ts in microseconds, monotonic
  clock) that load directly in Perfetto / ``chrome://tracing``;
  ``validate_chrome_trace`` is the stdlib-only schema checker CI runs on
  the exported file.

* **No added synchronisation.**  Spans never wait for the device.  The
  engines' ``ring-step`` span covers a ``block_until_ready`` that runs
  whether or not anything is tracing, because the host read on the next
  line would block anyway.
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time

from repro.obs.metrics import default_registry

__all__ = [
    "Tracer",
    "aggregate_phases",
    "current_path",
    "disable",
    "enable",
    "enabled",
    "format_phase_table",
    "get_tracer",
    "span",
    "validate_chrome_trace",
    "CANONICAL_PHASES",
]

#: Canonical campaign phases, in pipeline order.  ``format_phase_table``
#: always prints a row for each (count 0 when the phase never ran — an
#: encode row at 0 on a dataset campaign is the zero-encode proof), so
#: consumers can grep for a phase unconditionally.
CANONICAL_PHASES = (
    "validate",
    "encode",
    "stage",
    "dispatch",
    "prefetch-stage",
    "ring-step",
    "readback",
    "delta-border",
    "merge",
    "count",
    "entries",
    "hash",
)

#: Prefix of every span's name in the profiler sink.
PROFILER_PREFIX = "repro."
#: Prefix of the ``default_registry()`` histogram that totals the seconds
#: of each span recorded in the profiler sink.
REGISTRY_PREFIX = "span."

_tracer: "Tracer | None" = None  # None == disabled (the zero-overhead path)

_SPAN_STACK: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_obs_span_stack", default=()
)

#: ``jax.profiler.TraceAnnotation``, imported on the first ``span()`` call
#: so that importing this module does not import jax.
_annotation = None


def _profiler_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class _NullSpan:
    """The shared disabled-mode span: every method is a no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class _ProfilerSpan:
    """A span in the profiler sink: the ``repro.<name>`` annotation, and
    its seconds observed in ``default_registry()``'s ``span.<name>``
    histogram, so a profiled process reads its own split without parsing
    the trace."""

    __slots__ = ("_ann", "_seconds", "_t0")

    def __init__(self, name):
        self._ann = _profiler_annotation()(PROFILER_PREFIX + name)
        self._seconds = default_registry().histogram(REGISTRY_PREFIX + name)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._seconds.observe(time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)
        return False

    def add(self, **attrs):
        return self  # attributes are Chrome-only


class _Span:
    __slots__ = ("_tracer", "name", "_attrs", "_token", "_profiled")

    def __init__(self, tracer, name, attrs, profiler=False):
        self._tracer = tracer
        self.name = name
        self._attrs = dict(attrs) if attrs else {}
        self._profiled = _ProfilerSpan(name) if profiler else None

    def add(self, **attrs):
        """Attach attributes (byte counts, step counts, ...) to the span;
        they ride on the closing E event."""
        self._attrs.update(attrs)
        return self

    def __enter__(self):
        stack = _SPAN_STACK.get()
        self._token = _SPAN_STACK.set(stack + (self.name,))
        args = {"parent": "/".join(stack)} if stack else None
        self._tracer._emit("B", self.name, self._tracer._clock(), args)
        if self._profiled is not None:
            self._profiled.__enter__()
        return self

    def __exit__(self, *exc):
        if self._profiled is not None:
            self._profiled.__exit__(*exc)
        self._tracer._emit(
            "E", self.name, self._tracer._clock(), self._attrs or None
        )
        _SPAN_STACK.reset(self._token)
        return False


class Tracer:
    """Collects B/E trace events; install with ``enable()``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events = []  # (ph, name, ts_ns, tid, args)
        self._clock = time.perf_counter_ns
        self._t0 = self._clock()

    # -- recording -----------------------------------------------------------

    def span(self, name: str, attrs: dict = None,
             profiler: bool = False) -> _Span:
        """A span recorded here and, with ``profiler``, also in the
        active profiler session."""
        return _Span(self, name, attrs, profiler)

    def _emit(self, ph, name, ts_ns, args):
        tid = threading.get_ident()
        with self._lock:
            self._events.append((ph, name, ts_ns, tid, args))

    def complete(self, name: str, t0_ns: int, t1_ns: int,
                 attrs: dict = None, tid: int = None) -> None:
        """Record an interval measured externally (e.g. a queue wait whose
        endpoints live in different threads) as a matched B/E pair.

        ``tid`` overrides the thread id — intervals that OVERLAP a
        thread's own spans (a queue wait that began while the worker was
        still computing the previous request) go on a virtual lane so B/E
        nesting stays well-formed per (pid, tid)."""
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            self._events.append(("B", name, t0_ns, tid, None))
            self._events.append(("E", name, t1_ns, tid, attrs or None))

    # -- reading -------------------------------------------------------------

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    def events(self, since: int = 0) -> list:
        """Snapshot of recorded events (optionally from index ``since``)."""
        with self._lock:
            return list(self._events[since:])

    def phase_stats(self, since: int = 0) -> dict:
        return aggregate_phases(self.events(since))

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (``{"traceEvents": [...]}``).

        Events are sorted by timestamp; the sort is stable, so same-thread
        same-tick B/E pairs keep their recorded (correct) order.
        """
        pid = os.getpid()
        out = []
        for ph, name, ts, tid, args in sorted(
            self.events(), key=lambda e: e[2]
        ):
            ev = {
                "name": name,
                "ph": ph,
                "ts": (ts - self._t0) / 1000.0,  # ns -> microseconds
                "pid": pid,
                "tid": tid,
            }
            if args:
                ev["args"] = args
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
            f.write("\n")


# -- module-level API (the form instrumented code calls) ----------------------


def enable(tracer: Tracer = None) -> Tracer:
    """Install (and return) the process tracer; spans record from now on."""
    global _tracer
    _tracer = tracer if tracer is not None else Tracer()
    return _tracer


def disable() -> "Tracer | None":
    """Remove the process tracer (span calls become no-ops again) and
    return it, so the caller can still export what was recorded."""
    global _tracer
    t, _tracer = _tracer, None
    return t


def enabled() -> bool:
    return _tracer is not None


def get_tracer() -> "Tracer | None":
    return _tracer


def span(name: str, attrs: dict = None):
    """Open a span: ``with span("encode", {"bytes": n}) as sp: ...``.

    Records into the Chrome tracer when one is installed and into the
    active profiler session (as ``"repro." + name``) when there is one.
    With neither, this returns the shared no-op singleton — no
    allocation.  (The ``attrs`` dict literal at an instrumented call site
    WOULD allocate even when disabled; hot paths therefore pass attrs via
    ``sp.add(...)`` inside the span or not at all.)"""
    profiling = (_annotation or _profiler_annotation()).is_enabled()
    t = _tracer
    if t is None:
        return _ProfilerSpan(name) if profiling else _NULL_SPAN
    return t.span(name, attrs, profiling)


def current_path() -> tuple:
    """The context's open-span name stack (propagates with copy_context)."""
    return _SPAN_STACK.get()


# -- aggregation + formatting -------------------------------------------------


def aggregate_phases(events) -> dict:
    """``{name: {"count", "seconds"}}`` from matched B/E pairs (per tid)."""
    stacks, agg = {}, {}
    for ph, name, ts, tid, _args in sorted(events, key=lambda e: e[2]):
        if ph == "B":
            stacks.setdefault(tid, []).append((name, ts))
        elif ph == "E":
            st = stacks.get(tid)
            if st and st[-1][0] == name:
                _, t0 = st.pop()
                a = agg.setdefault(name, {"count": 0, "seconds": 0.0})
                a["count"] += 1
                a["seconds"] += (ts - t0) / 1e9
    return agg


def format_phase_table(phases: dict) -> str:
    """Human-readable per-phase table (what the CLI prints after --trace).

    Every canonical phase gets a row even at count 0; extra recorded
    phases follow in name order.  Self-time is not computed — nested
    spans (a merge inside a campaign) each report their own wall time.
    """
    names = list(CANONICAL_PHASES) + sorted(
        n for n in phases if n not in CANONICAL_PHASES
    )
    total = sum(phases.get(n, {}).get("seconds", 0.0) for n in names) or 1.0
    rows = ["phase            count     seconds    share"]
    for n in names:
        p = phases.get(n, {"count": 0, "seconds": 0.0})
        rows.append(
            f"{n:<16s} {p['count']:>5d} {p['seconds']:>11.6f} "
            f"{100.0 * p['seconds'] / total:>7.1f}%"
        )
    return "\n".join(rows)


# -- stdlib-only trace-file checker (used by CI and the property test) --------


def validate_chrome_trace(payload) -> int:
    """Raise ValueError unless ``payload`` is a well-formed Chrome
    trace-event object as this tracer emits it: a ``traceEvents`` list of
    B/E events with the required fields, timestamps monotonically
    non-decreasing, and every E matching the innermost open B of the same
    name on its (pid, tid) stack.  Returns the event count."""
    if not isinstance(payload, dict) or "traceEvents" not in payload:
        raise ValueError("not a Chrome trace object: missing 'traceEvents'")
    events = payload["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    last_ts = None
    stacks = {}
    for i, ev in enumerate(events):
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in ev:
                raise ValueError(f"traceEvents[{i}] missing field {field!r}")
        if ev["ph"] not in ("B", "E"):
            raise ValueError(
                f"traceEvents[{i}] phase {ev['ph']!r} is not 'B'/'E'"
            )
        if not isinstance(ev["ts"], (int, float)):
            raise ValueError(f"traceEvents[{i}].ts must be a number")
        if last_ts is not None and ev["ts"] < last_ts:
            raise ValueError(
                f"traceEvents[{i}].ts {ev['ts']} < previous {last_ts} "
                "(timestamps must be monotonic)"
            )
        last_ts = ev["ts"]
        key = (ev["pid"], ev["tid"])
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev["name"])
        else:
            st = stacks.get(key)
            if not st or st[-1] != ev["name"]:
                raise ValueError(
                    f"traceEvents[{i}]: E {ev['name']!r} does not match "
                    f"open B {st[-1] if st else None!r} on {key}"
                )
            st.pop()
    dangling = {k: v for k, v in stacks.items() if v}
    if dangling:
        raise ValueError(f"unclosed B events: {dangling}")
    return len(events)
