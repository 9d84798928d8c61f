"""Paper Figs 6-10: strong + weak scaling of the distributed engines.

Strong (Fig 6): fixed problem, ranks 1..8 — report time vs ranks + parallel
efficiency.  Weak (Figs 7/8 2-way, 9/10 3-way): fixed per-rank work —
report comparisons/sec/rank (the paper's right-hand graphs; flat = ideal).

Runs in a subprocess with 8 virtual CPU devices (one jax startup for the
whole sweep); the measured efficiencies are structural (ring + round-robin
overheads), with CPU compute standing in for the GPU mGEMM.  On any other
backend it refuses: the calling process already holds the accelerator, and
a child that needs it would fail or hang.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.util import row

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(HERE, "..", "results", "scaling.json")


def run_harness():
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"the scaling sweep runs on 8 virtual CPU devices in a child "
            f"process; this process holds the {backend} backend, which the "
            "child cannot share — run it on a CPU host (JAX_PLATFORMS=cpu)"
        )
    env = dict(os.environ)
    src = os.path.join(HERE, "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "scaling_harness.py")],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    data = json.loads(proc.stdout.splitlines()[-1])
    os.makedirs(os.path.dirname(CACHE), exist_ok=True)
    with open(CACHE, "w") as f:
        json.dump(data, f, indent=2)
    return data


def main():
    data = run_harness()
    rows = []
    for key in ("strong_2way", "strong_3way"):
        base = data[key][0]
        for r in data[key]:
            ranks = r["n_pv"] * r["n_pr"]
            eff = base["seconds"] / (r["seconds"] * ranks)
            rows.append(row(f"fig6/{key}/ranks{ranks}", r["seconds"],
                            f"efficiency={eff:.2f}"))
    for key in ("weak_2way", "weak_3way"):
        base = data[key][0]
        for r in data[key]:
            ranks = r["n_pv"] * r["n_pr"]
            rel = r["rate_per_rank"] / base["rate_per_rank"]
            rows.append(row(f"fig7_10/{key}/ranks{ranks}", r["seconds"],
                            f"rate_per_rank={r['rate_per_rank']:.3e}_rel={rel:.2f}"))
    return rows


if __name__ == "__main__":
    from benchmarks.util import print_rows

    print_rows(main())
