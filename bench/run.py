"""Run one benchmark cell once; print its result as one JSON line.

    python bench/run.py --workload gwas2-snp.closed --seed 7 --seconds 20 --trace 0

A cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each metric is read by its own file,
``bench/e2e/<name>.py`` or ``bench/layers/<name>.py``.

One run: set-up (imports, devices, the persistent compilation cache, the
cohorts from ``--seed``, one warm-up campaign), then a closed loop of
campaigns, each ``SimilarityEngine.run`` followed by
``SimilarityResult.checksum()``, back to back over the traffic's pool of
cohorts.  No campaign starts after ``--seconds``; the window ends when the
last one ends.  Once it has closed, ``bench/check.py`` compares what the
campaigns produced with the plain reference.  ``--trace 1`` profiles the
window and reports the per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits 1.  ``--cpu-rehearsal`` runs the cell at the
configuration's tiny ``rehearsal`` sizes on CPU devices instead; its line
always says ``"correct": false`` and it exits 1.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class CellError(SystemExit):
    """The cell cannot run here; the message says why."""


def load_cell(name: str) -> SimpleNamespace:
    """The cell's configuration, traffic and metric entries, found by name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise CellError(f"error: no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def applies(metric):
        return name in metric.get("workloads", [name])

    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    if (traffic["loop"], traffic["clients"]) != ("closed", 1):
        raise CellError(f"error: traffic {cell['traffic']!r}: only a closed "
                        "loop with one client is implemented")
    return SimpleNamespace(
        name=name,
        chips=cell["chips"],
        cfg=json.loads((ROOT / entry["file"]).read_text()),
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
    )


def reader(kind: str, name: str):
    """The ``read`` function of ``bench/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python call events would swamp the trace
    opts.host_tracer_level = 1  # keeps the benchmark's own annotations
    return opts


def trace_device(reduced: dict) -> dict:
    """The traced window's device seconds: busy (averaged over the chips)
    and the window's length."""
    return {"busy_s": sum(reduced["busy_s"]) / reduced["chips"],
            "window_s": reduced["window_s"]}


def breakdown(reduced: dict) -> dict:
    """The ops that took the most device time and the longest idle gaps,
    each labelled by what the host was doing."""
    return {"device_ops": [list(kv) for kv in reduced["device_ops"]],
            "idle_gaps": [list(g) for g in reduced["idle_gaps"]]}


def measure(cell, seed: int, seconds: float, trace: bool,
            rehearsal: bool = False, trace_dir=None) -> dict:
    """One run of ``cell``; returns the result line as a dict.  With
    ``trace_dir`` the raw trace is kept there (how ``bench/testdata`` was
    recorded)."""
    cfg = dict(cell.cfg, **cell.cfg["rehearsal"]) if rehearsal else cell.cfg
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.similarity import init_compile_cache

    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu"
                          or len(devices) < cell.chips):
        raise CellError(f"error: cell {cell.name} needs {cell.chips} TPU "
                        f"chip(s); JAX found {len(devices)} "
                        f"{devices[0].platform} device(s)")
    if len(devices) < cell.chips:
        raise CellError(f"error: rehearsal needs {cell.chips} devices, "
                        f"found {len(devices)}")
    devices = devices[:cell.chips]
    dev = devices[0]

    from bench import check, generate, work

    peak = None if rehearsal else work.peaks(dev.device_kind)
    from repro.api import SimilarityEngine, SimilarityRequest

    pool = generate.cohorts(cfg, cell.traffic, seed)
    fields = generate.request_fields(cfg, cell.traffic)
    stages = fields.get("stages")
    expected = work.results(cfg, stages)
    comparisons = work.comparisons(cfg, stages)
    request = SimilarityRequest(**fields)
    engine = SimilarityEngine(devices=devices)
    warm = engine.run(request, pool[-1])  # the window starts on pool[0]
    warm.checksum()
    del warm
    setup_s = time.perf_counter() - T_START

    log_dir = trace_dir or (tempfile.mkdtemp(prefix="bench-trace-")
                            if trace else None)
    if trace:
        jax.profiler.start_trace(log_dir, profiler_options=trace_options())
    campaigns, done, i = [], [], 0
    annotate = jax.profiler.TraceAnnotation
    with annotate("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            cohort, i = i % len(pool), i + 1
            ta = time.perf_counter()
            tb, ok = ta, False
            try:
                with annotate("engine.run"):
                    result = engine.run(request, pool[cohort])
                tb = time.perf_counter()
                with annotate("checksum"):
                    result.checksum()
                ok = result.num_results() == expected
                done.append((cohort, result))
            except Exception:  # a failed campaign is counted, not fatal
                traceback.print_exc()
            tc = time.perf_counter()
            campaigns.append({"engine_s": tb - ta, "assemble_s": tc - tb,
                              "ok": ok, "comparisons": comparisons * ok})
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    reduced = None
    if trace:
        from bench import trace as trace_mod

        reduced = trace_mod.reduce(
            trace_mod.load(trace_mod.find_xplane(log_dir)))
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)

    failed = sum(not c["ok"] for c in campaigns)
    t_check = time.perf_counter()
    readings = check.readings(done, pool, expected, seed)
    print(f"compared {readings['compared']} of {len(done)} campaigns with "
          f"the reference in {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)
    readings["failed"] = failed
    checks = check.verdict(readings, dict(cfg["limits"], failed=0))
    correct = (check.passes(checks) and bool(campaigns) and not rehearsal)

    run = SimpleNamespace(cfg=cfg, stages=stages, chips=cell.chips,
                          campaigns=campaigns, window_s=window_s,
                          setup_s=setup_s, trace=reduced, peak=peak,
                          work=work)
    kind, entries = (("layers", cell.per_layer) if trace
                     else ("e2e", cell.end_to_end))
    metrics = {}
    for m in entries:
        value = reader(kind, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": len(campaigns), "failed": failed,
            "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(trace_device(reduced))
        line["breakdown"] = breakdown(reduced)
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on CPU devices; always correct=false")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips} "
            + os.environ.get("XLA_FLAGS", ""))
    line = measure(cell, args.seed, args.seconds, bool(args.trace),
                   rehearsal=args.cpu_rehearsal)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 1 if args.cpu_rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
