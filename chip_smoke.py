"""Smoke run of the similarity campaign on a TPU, through the public API.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the paper's three-axis decompositions, 2x2 host
    python chip_smoke.py --cpu-rehearsal   # tiny shapes on the CPU; always ends "ok": false

One chip runs three campaigns at the paper's per-rank widths:

* 2-way Proportional Similarity (czekanowski) on SNP levels {0,1,2}, §6.6
  shape n_f=10,000 x n_v=12,288, on the fused bit-plane kernels
  (``path=fused-levels``);
* 2-way Sorenson on binary {0,1} data at the same shape, on the popcount
  kernels (``path=fused-popcount``);
* one stage of the 3-way campaign at the §6.7 shape n_f=20,000 x
  n_v=2,880, n_st=48, on the packed plane ring (``path3=fused-levels-ring``).

``--chips 4`` runs only the decompositions (n_pf, n_pv, n_pr) = (1,4,1),
(1,2,2), (2,2,1) of one 2-way cohort (n_f=10,000, n_v=24,576: n_vp=6,144
per rank at n_pv=4, so the single-device run of the cohort, 2.4 GB of fp32
output, fits one chip) and the 3-way (1,2,2) decomposition of the cohort's
first 480 vectors (n_st=1, so the checksum covers the whole campaign),
each against the single-device checksum of the same data (paper §5).

Every campaign is checked two ways: its exact checksum must equal the
``impl="xla"`` reference's (or, with ``--chips 4``, the single-device
run's), and a plain numpy evaluation of the metric's definition must agree
on 1,000 seeded random pairs or triples of the result.  Data come from
``--seed``.  Printed wall seconds are smoke timings with compilation
included, not a benchmark.  The last line is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; any failed phase
raises, exits non-zero and prints no such line.  Without a TPU the script
refuses to run unless ``--cpu-rehearsal`` is given, and that run ends with
``"ok": false`` and exit code 1.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
N_SAMPLES = 1000

# (n_f, n_v) of each phase; the rehearsal sizes only exercise control flow
TPU_SIZES = dict(two=(10_000, 12_288), three=(20_000, 2_880), n_st=48,
                 cohort=(10_000, 24_576), three_chips=480)
CPU_SIZES = dict(two=(200, 96), three=(160, 48), n_st=2,
                 cohort=(160, 192), three_chips=48)


class SmokeFailure(AssertionError):
    """A comparison disagreed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- the plain numpy reference (independent of the package under test) ------


def _safe_ratio(num, den):
    return np.where(den > 0, num / np.where(den > 0, den, 1.0), 0.0)


def pair_reference(V, I, J):
    """Proportional Similarity from its definition, float64:
    2 sum_q min(a_q, b_q) / (sum_q a_q + sum_q b_q) (0 when both are 0)."""
    A = V[:, I].astype(np.float64)
    B = V[:, J].astype(np.float64)
    return _safe_ratio(2.0 * np.minimum(A, B).sum(0), A.sum(0) + B.sum(0))


def triple_reference(V, I, J, K):
    """The paper's 3-way Proportional Similarity, float64:
    3/2 sum_q [min(a,b) + min(a,c) + min(b,c) - min(a,b,c)] / sum_q (a+b+c)."""
    A, B, C = (V[:, x].astype(np.float64) for x in (I, J, K))
    num = (np.minimum(A, B) + np.minimum(A, C) + np.minimum(B, C)
           - np.minimum(np.minimum(A, B), C)).sum(0)
    return _safe_ratio(1.5 * num, (A + B + C).sum(0))


def check_sample(result, V, rng, label: str) -> int:
    """Compare ``N_SAMPLES`` seeded random entries of ``result`` against the
    numpy reference; returns the number compared."""
    tiles = list(result.tiles())
    index = [np.concatenate([t.index[a] for t in tiles]) for a in range(result.way)]
    values = np.concatenate([t.values for t in tiles]).astype(np.float64)
    pick = rng.choice(len(values), size=min(N_SAMPLES, len(values)), replace=False)
    ref = (pair_reference if result.way == 2 else triple_reference)(
        V, *(ix[pick] for ix in index)
    )
    bad = ~np.isclose(values[pick], ref, rtol=1e-6, atol=0.0)
    check(not bad.any(),
          f"{label}: {int(bad.sum())} of {len(pick)} sampled entries differ "
          f"from the numpy reference")
    return len(pick)


# -- campaigns through the public entry points --------------------------------


def resolved_path(*, way, metric, levels, n_f, n_v, extra=()) -> str:
    """The executor path the CLI's ``--dry-run`` resolves for this campaign
    shape (same metric, impl, levels and leveled data); nothing is timed."""
    from repro.launch.similarity import main as cli

    argv = ["--dry-run", "--way", str(way), "--metric", metric,
            "--impl", "levels", "--levels", str(levels), "--max-value",
            str(levels), "--n-f", str(n_f), "--n-v", str(n_v), *extra]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli(argv)
    check(rc == 0, f"dry-run {argv} exited {rc}")
    rows = [r for r in buf.getvalue().splitlines() if r.startswith("path=")]
    check(len(rows) == 1, f"dry-run printed {rows}")
    return rows[0].split()[0].split("=", 1)[1]


def run(engine, V, **request):
    """One campaign; returns (result, checksum, wall seconds incl. checksum)."""
    from repro.api import SimilarityRequest

    t0 = time.perf_counter()
    result = engine.run(SimilarityRequest(**request), V)
    checksum = result.checksum()
    return result, checksum, time.perf_counter() - t0


def phase_vs_xla(engine, V, rng, *, label, path_key, expect_path, way,
                 metric, levels, extra=(), fused=None, **request) -> None:
    """Fused-kernel campaign vs the ``impl="xla"`` reference on the same data,
    plus the numpy sample.  ``fused`` holds request fields of the fused run
    only (the XLA reference has no plane encoding)."""
    n_f, n_v = V.shape
    path = resolved_path(way=way, metric=metric, levels=levels, n_f=n_f,
                         n_v=n_v, extra=extra)
    check(path == expect_path, f"{label}: resolved {path_key}={path}, "
                               f"expected {expect_path}")
    print(f"[{label}] {path_key}={path} n_f={n_f} n_v={n_v}", flush=True)
    result, ck_fused, t_fused = run(engine, V, way=way, metric=metric,
                                    impl="levels", levels=levels,
                                    **request, **(fused or {}))
    _, ck_xla, t_xla = run(engine, V, way=way, metric=metric, impl="xla",
                           **request)
    check(ck_fused == ck_xla,
          f"{label}: checksum {hex(ck_fused)} != xla {hex(ck_xla)}")
    n = check_sample(result, V, rng, label)
    print(f"[{label}] results={result.num_results()} checksum={hex(ck_fused)} "
          f"xla_checksum={hex(ck_xla)} match=True numpy_sample={n} ok=True",
          flush=True)
    print(f"[{label}] smoke timing (compile included, not a benchmark): "
          f"fused_wall_s={t_fused:.3f} xla_wall_s={t_xla:.3f}", flush=True)


def one_chip(engine, rng, sizes) -> None:
    n_f, n_v = sizes["two"]
    V = rng.integers(0, 3, size=(n_f, n_v), dtype=np.uint8)
    phase_vs_xla(engine, V, rng, label="2way-levels", path_key="path",
                 expect_path="fused-levels", way=2, metric="czekanowski",
                 levels=2)
    V = (rng.random((n_f, n_v)) < 0.3).astype(np.uint8)
    phase_vs_xla(engine, V, rng, label="2way-binary", path_key="path",
                 expect_path="fused-popcount", way=2, metric="sorenson",
                 levels=1)
    n_f, n_v = sizes["three"]
    n_st = sizes["n_st"]
    V = rng.integers(0, 3, size=(n_f, n_v), dtype=np.uint8)
    phase_vs_xla(engine, V, rng, label="3way-plane-ring", path_key="path3",
                 expect_path="fused-levels-ring", way=3,
                 metric="czekanowski", levels=2,
                 extra=("--n-st", str(n_st), "--encoding", "bitplane"),
                 n_st=n_st, stages=(0,), fused={"encoding": "bitplane"})


def four_chips(rng, sizes) -> None:
    """Decomposition invariance on one cohort: every (n_pf, n_pv, n_pr) run
    must reproduce the single-device checksum bit for bit."""
    import jax

    from repro.api import SimilarityEngine
    from repro.parallel.mesh import make_comet_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    devices = devices[:4]
    n_f, n_v = sizes["cohort"]
    V = rng.integers(0, 3, size=(n_f, n_v), dtype=np.uint8)
    V3 = np.ascontiguousarray(V[:, :sizes["three_chips"]])
    single = SimilarityEngine(devices=devices[:1])
    cases = [(2, V, (1, 4, 1)), (2, V, (1, 2, 2)), (2, V, (2, 2, 1)),
             (3, V3, (1, 2, 2))]
    refs = {}
    for way, data in ((2, V), (3, V3)):
        result, ck, t = run(single, data, way=way, metric="czekanowski",
                            impl="levels", levels=2)
        n = check_sample(result, data, rng, f"{way}way-single")
        refs[way] = ck
        print(f"[{way}way-single] n_f={data.shape[0]} n_v={data.shape[1]} "
              f"results={result.num_results()} checksum={hex(ck)} "
              f"numpy_sample={n} smoke_wall_s={t:.3f}", flush=True)
        del result
    for way, data, decomp in cases:
        mesh = make_comet_mesh(*decomp, devices=devices)
        used = {d.id for d in mesh.devices.flat}
        check(len(used) == 4, f"mesh {decomp} uses devices {sorted(used)}")
        engine = SimilarityEngine(mesh=mesh)
        n_pf, n_pv, n_pr = decomp
        result, ck, t = run(engine, data, way=way, metric="czekanowski",
                            impl="levels", levels=2, n_pf=n_pf, n_pv=n_pv,
                            n_pr=n_pr)
        check(ck == refs[way], f"{way}way {decomp}: checksum {hex(ck)} != "
                               f"single-device {hex(refs[way])}")
        print(f"[{way}way-{n_pf}x{n_pv}x{n_pr}] devices={sorted(used)} "
              f"checksum={hex(ck)} single_device_match=True "
              f"smoke_wall_s={t:.3f}", flush=True)
        del result
    peaks = [d.memory_stats() for d in devices]
    if all(p for p in peaks):  # the CPU backend reports no memory stats
        peak = [p.get("peak_bytes_in_use", 0) for p in peaks]
        print(f"[4chip] peak_bytes_in_use={peak}", flush=True)
        check(min(peak) > 0, f"a device did no work: {peak}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run at tiny sizes without a TPU (ends ok=false)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.similarity import init_compile_cache

    init_compile_cache()
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print(f"error: no TPU (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} backend={jax.default_backend()}", flush=True)
    sizes = TPU_SIZES if on_tpu else CPU_SIZES
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(rng, sizes)
    else:
        from repro.api import SimilarityEngine

        one_chip(SimilarityEngine(devices=devices[:1]), rng, sizes)
    print(f"smoke total wall seconds (not a benchmark): "
          f"{time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": on_tpu, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0 if on_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
