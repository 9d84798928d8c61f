"""Run a cell with its timed path broken underneath, or with the program's
lower-precision path as the control, and print the comparison's readings.

    python bench/tests/scenarios.py --workload gwas2-snp.closed \
        --scenarios sound,control --seeds 5,6,7 --seconds 20
    python bench/tests/scenarios.py --workload gwas3-snp-2x2.stage \
        --scenarios no_exchange --seeds 1 --seconds 0.5 --rehearsal

Each scenario runs ``bench/run.py``'s whole measurement (set-up, window,
comparison) with one fault planted in the program:

* ``sound``: nothing changed;
* ``control``: the configuration's float32 output computed in bfloat16,
  the program's own lower-precision path (``out_dtype="bfloat16"``);
* ``stale``: the engine returns the previous campaign's result, a step
  that leaves its state unchanged;
* ``half``: half of every campaign's results are left out;
* ``altered``: every 16th answer of each result block is altered where the
  engine produces it;
* ``no_exchange``: the ``ppermute`` exchange between chips is left out.

``--rehearsal`` skips the look for a chip and runs the configuration's tiny
rehearsal sizes on CPU devices.  One JSON line per (scenario, seed).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]


@contextlib.contextmanager
def planted(name: str, cell):
    """Plant fault ``name`` for the duration of the block."""
    if name in ("sound", "control"):
        yield
        return
    import jax
    import numpy as np

    from repro.api import SimilarityEngine, SimilarityResult

    if name == "stale":
        run, last = SimilarityEngine.run, []

        def stale(self, request, V=None):
            if not last:
                last.append(run(self, request, V))
            return last[0]

        with mock.patch.object(SimilarityEngine, "run", stale):
            yield
    elif name == "half":
        tiles = SimilarityResult.tiles

        def half(self):
            for t in tiles(self):
                keep = len(t) // 2
                yield type(t)(way=t.way, index=tuple(a[:keep] for a in t.index),
                              values=t.values[:keep], stage=t.stage)

        with mock.patch.object(SimilarityResult, "tiles", half):
            yield
    elif name == "altered":
        inner = SimilarityEngine._run

        def altered(self, request, V=None):
            result = inner(self, request, V)
            for out in result.outputs:
                blocks = np.array(out.blocks)
                blocks.reshape(-1)[::16] += np.asarray(0.01, blocks.dtype)
                out.blocks = blocks
            return result

        with mock.patch.object(SimilarityEngine, "_run", altered):
            yield
    elif name == "no_exchange":
        with mock.patch.object(jax.lax, "ppermute",
                               lambda x, axis_name, perm: x):
            yield
    else:
        raise ValueError(f"unknown scenario {name!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scenarios", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import run

    cell = run.load_cell(args.workload)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips} "
            + os.environ.get("XLA_FLAGS", ""))
    base = cell.cfg
    for name in args.scenarios.split(","):
        cell.cfg = dict(base, out_dtype="bfloat16") if name == "control" \
            else base
        for seed in (int(s) for s in args.seeds.split(",")):
            with planted(name, cell):
                line = run.measure(cell, seed, args.seconds, False,
                                   rehearsal=args.rehearsal)
            print(json.dumps({"scenario": name, "seed": seed,
                              "attempted": line["attempted"],
                              "failed": line["failed"],
                              "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
