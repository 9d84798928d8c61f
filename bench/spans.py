"""The program's own spans in a traced run: seconds per campaign in each
``repro.obs`` span, and the busiest chip's idle time by the span the host
was in.

    python bench/spans.py --workload gwas3-snp.stage --seed 7 --seconds 20

runs the cell once with ``--trace 1`` and prints one JSON line: the run's
result line, ``per_campaign_s`` (every span's seconds per campaign) and
``idle_by_span`` (the busiest chip's idle seconds over the window, summed by
the innermost ``repro.*`` span, or JAX's own lowering and compile spans,
around them; elsewhere the benchmark's ``engine.run`` / ``checksum`` label
or ``harness``; the top ten).  ``--keep DIR`` keeps the raw trace.

While a profiler session records, ``repro.obs`` observes each span's
seconds in its default registry's ``span.<name>`` histogram; the benchmark
traces only the window, so those totals are the window's.
``per_campaign`` reads them for the readers in ``bench/layers``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

#: the program's spans in the profiler trace, and in its registry
PROFILER_PREFIX, REGISTRY_PREFIX = "repro.", "span."
#: JAX's own host annotations around lowering and compiling a program
JAX_SPANS = ("lower_sharding_computation", "backend_compile_and_load")


def _registry_spans() -> dict:
    """``{name: {"count", "sum", ...}}`` of the program's span histograms;
    empty where the program keeps none."""
    try:
        from repro.obs.metrics import default_registry
    except ImportError:
        return {}
    return {k[len(REGISTRY_PREFIX):]: v
            for k, v in default_registry().snapshot().items()
            if k.startswith(REGISTRY_PREFIX)}


def per_campaign(run, name: str):
    """Seconds per campaign in the program's span ``name`` over the traced
    window, or None where the program recorded no such span."""
    seconds = _registry_spans().get(name)
    if not seconds or not seconds["count"] or not run.campaigns:
        return None
    return seconds["sum"] / len(run.campaigns)


def _innermost(spans):
    """Possibly nested (start, end, label) spans flattened into sorted,
    disjoint pieces, each labelled by the innermost span covering it (the
    covering span that started last)."""
    edges = sorted({x for a, b, _ in spans for x in (a, b)})
    starts = sorted(spans)
    out, active, k = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= lo:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[1] > lo]
        if active:
            label = max(active, key=lambda sp: (sp[0], -sp[1]))[2]
            if out and out[-1][1] == lo and out[-1][2] == label:
                out[-1][1] = hi
            else:
                out.append([lo, hi, label])
    return out


def _label_time(idle, pieces) -> dict:
    """ns of the sorted, disjoint ``idle`` intervals under each label of the
    sorted, disjoint labelled ``pieces``; time under none is the harness's."""
    out, k = {}, 0
    for s, e in idle:
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        covered, j = 0, k
        while j < len(pieces) and pieces[j][0] < e:
            a, b, label = pieces[j]
            ns = min(b, e) - max(a, s)
            out[label] = out.get(label, 0) + ns
            covered += ns
            j += 1
        if e - s > covered:
            out[trace.IDLE_LABEL] = out.get(trace.IDLE_LABEL, 0) + e - s - covered
    return out


def idle_by_span(data) -> list | None:
    """[(label, seconds)] of the busiest chip's idle time in the window,
    longest first, top ten; None without a window span or a device plane."""
    window, chips, spans = None, {}, []
    for plane, line, name, s, e in trace._events(data):
        m = trace.DEVICE_PLANE.match(plane)
        if m and line == trace.OPS_LINE:
            chips.setdefault(int(m.group(1)), []).append((s, e))
        elif not m and name == trace.WINDOW_SPAN:
            window = (s, e)
        elif not m and (name in trace.HOST_SPANS or name in JAX_SPANS
                        or name.startswith(PROFILER_PREFIX)):
            spans.append((s, e, name))
    if window is None or not chips:
        return None
    w0, w1 = window
    busy = max((trace._union((max(s, w0), min(e, w1)) for s, e in iv
                             if min(e, w1) > max(s, w0))
                for iv in chips.values()),
               key=lambda u: sum(e - s for s, e in u))
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    ns = _label_time(idle, _innermost(spans))
    return sorted(((label, v * 1e-9) for label, v in ns.items()),
                  key=lambda kv: -kv[1])[:trace.TOP]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="keep the raw trace in this directory")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on CPU devices, as bench/run.py")
    args = ap.parse_args(argv)
    from bench import run

    cell = run.load_cell(args.workload)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips} "
            + os.environ.get("XLA_FLAGS", ""))
    with tempfile.TemporaryDirectory(prefix="bench-spans-") as tmp:
        log_dir = args.keep or tmp
        line = run.measure(cell, args.seed, args.seconds, True,
                           rehearsal=args.cpu_rehearsal, trace_dir=log_dir)
        data = trace.load(trace.find_xplane(log_dir))
        by_span = idle_by_span(data)
    n = line["attempted"]
    out = {"line": line,
           "per_campaign_s": {name: h["sum"] / n
                              for name, h in _registry_spans().items()},
           "idle_by_span": by_span}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
