"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
benchmark reports: per chip the union of device-op intervals (busy time),
collective time and time per op name, and the longest idle gaps of the
busiest chip, cut where the host passes from one benchmark span to the
next and labelled by the span (``engine.run``, ``checksum``, or the
harness between them).

Only JAX's own reader (``jax.profiler.ProfileData``) is used.  Device planes
are ``/device:TPU:<n>``; their ops are the events of the ``XLA Ops`` line,
and collective time is the union of collective events there and on the
``Async XLA Ops`` line, where an asynchronous collective stays in flight.
The window is the benchmark's ``bench.window`` annotation on the host.
"""
from __future__ import annotations

import re
from glob import glob
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: asynchronous ops in flight, from their start to their done: an async
#: collective's transfer shows here, not on the ops line
ASYNC_LINE = "Async XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("engine.run", "checksum")
IDLE_LABEL = "harness"
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter"
    r"|send|recv|ppermute", re.IGNORECASE)
TOP = 10


def find_xplane(log_dir) -> Path:
    """The one ``.xplane.pb`` a trace session wrote under ``log_dir``."""
    found = glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {found}")
    return Path(found[0])


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _pieces(s, e, spans):
    """[s, e) cut at the host spans' edges: (label, ns) of each piece,
    labelled by the span that covers it, or the harness between spans."""
    out, t = [], s
    for a, b, label in spans:
        if b <= t or a >= e:
            continue
        if a > t:
            out.append((IDLE_LABEL, a - t))
        out.append((label, min(b, e) - max(a, t)))
        t = min(b, e)
    if t < e:
        out.append((IDLE_LABEL, e - t))
    return out


def op_name(event_name: str) -> str:
    """A device op's stable name: its HLO instruction name without the
    leading ``%`` and the numeric suffix (``%fusion.12 = f32[...] ...`` ->
    ``fusion``), so that the same kernel reads alike across compilations."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _events(data):
    """(plane name, line name, event name, start ns, end ns) of every event."""
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name, ev.start_ns,
                       ev.start_ns + ev.duration_ns)


def reduce(data) -> dict | None:
    """Numbers of one traced window; None when the trace holds no window
    span or no device plane (a CPU run, say)."""
    window, spans, chips = None, {n: [] for n in HOST_SPANS}, {}
    in_flight = {}
    for plane, line, name, s, e in _events(data):
        m = DEVICE_PLANE.match(plane)
        if m and line == OPS_LINE:
            chips.setdefault(int(m.group(1)), []).append((name, s, e))
        elif m and line == ASYNC_LINE and COLLECTIVE.search(name):
            in_flight.setdefault(int(m.group(1)), []).append((s, e))
        elif not m and name == WINDOW_SPAN:
            window = (s, e)
        elif not m and name in spans:
            spans[name].append((s, e))
    if window is None or not chips:
        return None
    w0, w1 = window
    per_chip = []
    for chip in sorted(chips):
        ops, iv = {}, []
        coll = [(max(s, w0), min(e, w1)) for s, e in in_flight.get(chip, [])]
        for name, s, e in chips[chip]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            iv.append((s, e))
            ops[op_name(name)] = ops.get(op_name(name), 0.0) + (e - s)
            if COLLECTIVE.search(name):
                coll.append((s, e))
        busy = _union(iv)
        per_chip.append({"chip": chip, "busy": busy, "ops": ops,
                         "busy_ns": sum(e - s for s, e in busy),
                         "collective_ns": sum(e - s for s, e in _union(
                             [(s, e) for s, e in coll if e > s]))})
    busiest = max(per_chip, key=lambda c: c["busy_ns"])
    edges = [w0] + [x for iv in busiest["busy"] for x in iv] + [w1]
    host = sorted((a, b, n) for n in HOST_SPANS for a, b in spans[n])
    gaps = [(label, ns * 1e-9)
            for s, e in zip(edges[::2], edges[1::2]) if e > s
            for label, ns in _pieces(s, e, host)]
    gaps.sort(key=lambda g: -g[1])
    n = len(per_chip)
    ops = {}
    for c in per_chip:
        for name, ns in c["ops"].items():
            ops[name] = ops.get(name, 0.0) + ns * 1e-9 / n
    return {
        "window_s": (w1 - w0) * 1e-9,
        "chips": n,
        "busy_s": [c["busy_ns"] * 1e-9 for c in per_chip],
        "collective_s": [c["collective_ns"] * 1e-9 for c in per_chip],
        "campaigns": sum(1 for s, e in spans["engine.run"]
                         if w0 <= s and e <= w1),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": gaps[:TOP],
    }


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))
