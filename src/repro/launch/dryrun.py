import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh ((16,16) single-pod / (2,16,16) multi-pod),
  2. builds the step function + ShapeDtypeStruct inputs with shardings,
  3. jit(...).lower(...).compile()  — no allocation, proves the sharding
     config is coherent and fits,
  4. prints memory_analysis()/cost_analysis() and derives the roofline terms,
  5. appends the result to a JSON cache (incremental across invocations).

Usage:
  python -m repro.launch.dryrun --list
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]

results/dryrun is regenerable scratch (not committed); comet cells worth
versioning are copied to results/comet — see results/README.md.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import specs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.parallel.sharding import use_mesh  # noqa: E402
from repro.roofline.analysis import analyze_compiled, model_flops  # noqa: E402

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "../../../results/dryrun")


def _cell_path(out_dir, arch, shape, multi_pod):
    mesh_tag = "multipod_2x16x16" if multi_pod else "pod_16x16"
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_tag}.json")


def _param_stats(arch):
    """(total_params, active_fraction) for MODEL_FLOPS."""
    from repro.configs.registry import get_config
    from repro.models import api

    cfg = get_config(arch)
    struct = jax.eval_shape(
        lambda k: api.init_model(cfg, k), jax.random.PRNGKey(0)
    )
    leaves_with_path = jax.tree_util.tree_flatten_with_path(struct)[0]
    total = sum(int(np.prod(l.shape)) for _, l in leaves_with_path)
    expert = sum(
        int(np.prod(l.shape))
        for p, l in leaves_with_path
        if any("moe" in str(k) for k in p) and not any("router" in str(k) for k in p)
    )
    if cfg.family == "moe" and expert:
        active = total - expert + expert * cfg.experts_per_token / cfg.n_experts
        return total, active / total
    return total, 1.0


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             force: bool = False, tag: str = "", overrides=None) -> dict:
    path = _cell_path(out_dir, arch, shape, multi_pod)
    if tag:
        path = path.replace(".json", f"__{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    os.makedirs(out_dir, exist_ok=True)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    if arch.startswith("comet"):
        fn, args, meta = specs.build_comet_cell(arch, mesh, multi_pod, overrides)
        vpu_fraction = 0.0 if "mxu" in arch or (
            overrides or {}).get("impl", "").startswith("levels") else 1.0
    else:
        fn, args, meta = specs.build_cell(arch, shape, mesh, overrides)
        vpu_fraction = 0.0
    if overrides:
        meta = dict(meta, overrides={k: str(v) for k, v in overrides.items()})
    from contextlib import nullcontext

    # trace under the mesh context so with_sharding_constraint() inside the
    # model code binds to the production mesh; comet cells shard_map over
    # their own (pf, pv, pr) reinterpretation and need no ambient mesh.
    ctx = nullcontext() if arch.startswith("comet") else use_mesh(mesh)
    with ctx:
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    print(f"== {arch} x {shape} ({'2x16x16' if multi_pod else '16x16'}) ==")
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis()
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})

    terms = analyze_compiled(compiled, n_dev, vpu_fraction=vpu_fraction)
    if "work_fraction" in meta:
        # comet engines: rescale static cond-branch counts to the per-rank
        # round-robin share (see build_comet_cell)
        wf = meta["work_fraction"]
        terms["t_compute_static"] = terms["t_compute"]
        terms["t_memory_static"] = terms["t_memory"]
        terms["t_compute"] *= wf
        terms["t_memory"] *= wf
        terms["bottleneck"] = max(
            ("compute", terms["t_compute"]),
            ("memory", terms["t_memory"]),
            ("collective", terms["t_collective"]),
            key=lambda kv: kv[1],
        )[0]
        tb = max(terms["t_compute"], terms["t_memory"], terms["t_collective"])
        terms["roofline_fraction"] = terms["t_compute"] / tb if tb else 0.0
    result = dict(meta)
    result.update(
        multi_pod=multi_pod,
        mesh="2x16x16" if multi_pod else "16x16",
        lower_s=t_lower,
        compile_s=t_compile,
        roofline=terms,
    )
    if not arch.startswith("comet"):
        n_params, active_frac = _param_stats(arch)
        tokens = meta["batch"] * (meta["seq"] if meta["kind"] != "decode" else 1)
        mf = model_flops(n_params, tokens, meta["kind"], active_frac)
        hlo_total = terms["flops_per_device"] * n_dev
        result.update(
            n_params=n_params,
            active_fraction=active_frac,
            model_flops=mf,
            useful_flops_ratio=(mf / hlo_total) if hlo_total else 0.0,
        )
    else:
        # comparisons for the paper's metric: unique pairs/triples * n_f
        n_v = meta["n_v"]
        if meta["kind"] == "comet2way":
            comps = n_v * (n_v - 1) / 2 * meta["n_f"]
        else:
            comps = n_v * (n_v - 1) * (n_v - 2) / 6 * meta["n_f"] / meta["n_st"]
        result["elementwise_comparisons"] = comps
    with open(path, "w") as f:
        json.dump(result, f, indent=2, default=str)
    print(json.dumps({k: result[k] for k in ("arch", "shape", "mesh", "compile_s")},
                     default=str))
    print(f"  terms: compute={terms['t_compute']:.4e}s memory={terms['t_memory']:.4e}s"
          f" collective={terms['t_collective']:.4e}s -> {terms['bottleneck']}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", default="paper")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(DEFAULT_OUT))
    ap.add_argument("--tag", default="", help="suffix for experiment variants")
    ap.add_argument("--override", action="append", default=[],
                    help="config override key=value (repeatable)")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        overrides[k] = v

    if args.list:
        for arch, shape in specs.cells():
            print(f"{arch:28s} {shape}")
        return 0

    todo = []
    if args.all:
        for arch, shape in specs.cells():
            meshes = [False, True] if args.both_meshes else [args.multi_pod]
            for mp in meshes:
                todo.append((arch, shape, mp))
    else:
        assert args.arch, "--arch required (or --all)"
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            todo.append((args.arch, args.shape, mp))

    failures = []
    for arch, shape, mp in todo:
        try:
            run_cell(arch, shape, mp, args.out, force=args.force, tag=args.tag,
                     overrides=overrides or None)
        except Exception:
            traceback.print_exc()
            failures.append((arch, shape, mp))
    if failures:
        print("FAILED CELLS:", failures)
        return 1
    print(f"all {len(todo)} cells OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
