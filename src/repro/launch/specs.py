"""Input specs + step builders for every (arch x shape) dry-run cell.

Shapes (assignment):
    train_4k     seq=4096,   global_batch=256   -> train_step
    prefill_32k  seq=32768,  global_batch=32    -> prefill_step (cache fill)
    decode_32k   seq=32768,  global_batch=128   -> serve_step (1 new token)
    long_500k    seq=524288, global_batch=1     -> serve_step; SSM/hybrid only

``long_500k`` is skipped for pure full-attention archs (quadratic attention
at 524k; DESIGN.md §5) and runs for mamba2 (SSM) and zamba2 (hybrid).

Everything here returns ShapeDtypeStructs (weak-type-correct, shardable, no
device allocation) — the dry-run lowers + compiles against them.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.models import api
from repro.models.common import ModelConfig
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.optim.schedule import warmup_cosine
from repro.parallel.sharding import named_sharding
from repro.train.step import make_train_step

DATA = ("pod", "data")


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str  # train | prefill | decode
    seq: int
    batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

LM_ARCHS = [
    "qwen1.5-0.5b",
    "llama3-8b",
    "command-r-plus-104b",
    "deepseek-67b",
    "qwen2-vl-2b",
    "grok-1-314b",
    "granite-moe-3b-a800m",
    "zamba2-1.2b",
    "mamba2-1.3b",
    "seamless-m4t-large-v2",
]
COMET_ARCHS = ["comet_2way", "comet_3way", "comet_2way_mxu", "comet_3way_mxu"]


def applicable(arch: str, shape: str) -> tuple[bool, str]:
    cfg = get_config(arch)
    if shape == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, "full quadratic attention at 524k seq — skipped (DESIGN §5)"
    return True, ""


def cells(include_comet: bool = True):
    """All runnable (arch, shape) dry-run cells."""
    out = []
    for arch in LM_ARCHS:
        for shape in SHAPES:
            ok, _ = applicable(arch, shape)
            if ok:
                out.append((arch, shape))
    if include_comet:
        out += [(a, "paper") for a in COMET_ARCHS]
    return out


def _prep_cfg(cfg: ModelConfig, kind: str) -> ModelConfig:
    # production lowering settings: bf16 compute, remat for training
    return cfg.replace(
        compute_dtype="bfloat16",
        param_dtype="float32",
        remat="full" if kind == "train" else "none",
    )


def _with_sharding(struct_tree, spec_tree, mesh):
    """Attach NamedShardings (PartitionSpec leaves in spec_tree) to structs."""

    def one(st, spec):
        return jax.ShapeDtypeStruct(
            st.shape, st.dtype, sharding=named_sharding(mesh, *spec, shape=st.shape)
        )

    flat_s, treedef = jax.tree.flatten(struct_tree)
    flat_spec = treedef.flatten_up_to(spec_tree)
    return treedef.unflatten([one(s, sp) for s, sp in zip(flat_s, flat_spec)])


def param_structs(cfg: ModelConfig, mesh: Mesh):
    struct = jax.eval_shape(partial(api.init_model, cfg), jax.random.PRNGKey(0))
    rules = api.param_sharding_rules(cfg)
    return _with_sharding(struct, rules, mesh)


def opt_structs(cfg: ModelConfig, params_struct, mesh: Mesh):
    struct = jax.eval_shape(adamw_init, params_struct)
    rules = api.param_sharding_rules(cfg)
    opt_rules = {"mu": rules, "nu": rules, "count": P()}
    return _with_sharding(struct, opt_rules, mesh)


def _sds(mesh, shape, dtype, *spec):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=named_sharding(mesh, *spec, shape=shape)
    )


def batch_structs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh):
    B, S = shape.batch, shape.seq
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    DATA = ("data", "model") if cfg.dp_only else globals()["DATA"]
    batch = {"labels": _sds(mesh, (B, S), i32, DATA, None)}
    if cfg.family == "encdec":
        batch["src_embeds"] = _sds(mesh, (B, S, cfg.d_model), bf16, DATA, None, None)
        batch["tokens"] = _sds(mesh, (B, S), i32, DATA, None)
    elif cfg.family == "vlm":
        batch["embeds"] = _sds(mesh, (B, S, cfg.d_model), bf16, DATA, None, None)
    else:
        batch["tokens"] = _sds(mesh, (B, S), i32, DATA, None)
    return batch


def cache_structs(cfg: ModelConfig, batch: int, max_len: int, mesh: Mesh):
    if cfg.family == "encdec":
        # built by hand: init_cache runs the encoder, which the dry-run skips
        kv_shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
        return {
            "enc": _sds(mesh, (batch, max_len, cfg.d_model), cfg.cdt,
                        DATA, None, None),
            "kv": {
                "k": _sds(mesh, kv_shape, cfg.cdt, None, DATA, "model", None, None),
                "v": _sds(mesh, kv_shape, cfg.cdt, None, DATA, "model", None, None),
            },
        }
    struct = jax.eval_shape(lambda: api.init_cache(cfg, None, batch, max_len))
    spec_map = {}
    if "kv" in struct:
        spec_map["kv"] = {
            "k": P(None, DATA, "model", None, None),
            "v": P(None, DATA, "model", None, None),
        }
    if "mamba" in struct:
        spec_map["mamba"] = {
            "conv": P(None, DATA, None, "model"),
            "ssm": P(None, DATA, "model", None, None),
        }
    return _with_sharding(struct, spec_map, mesh)


def build_cell(arch: str, shape_name: str, mesh: Mesh, overrides=None):
    """Returns (step_fn, arg_structs, meta) ready for jit(...).lower(*args)."""
    shape = SHAPES[shape_name]
    cfg = _prep_cfg(get_config(arch), shape.kind)
    if overrides:
        cfg = cfg.replace(**overrides)
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "seq": shape.seq, "batch": shape.batch}

    if shape.kind == "train":
        opt_cfg = AdamWConfig(schedule=warmup_cosine(2000, 100000))
        step = make_train_step(cfg, opt_cfg)
        if cfg.dp_only:
            from repro.parallel.sharding import dp_only_mode

            inner = step

            def step(params, opt_state, batch):
                with dp_only_mode():
                    return inner(params, opt_state, batch)

        params = param_structs(cfg, mesh)
        opt = opt_structs(cfg, params, mesh)
        batch = batch_structs(cfg, shape, mesh)
        return step, (params, opt, batch), meta

    if shape.kind == "prefill":
        params = param_structs(cfg, mesh)
        cache = cache_structs(cfg, shape.batch, shape.seq, mesh)
        toks = _sds(mesh, (shape.batch, shape.seq), jnp.int32, DATA, None)
        if cfg.family == "vlm":
            # stub frontend: prefill consumes tokens for lowering purposes
            pass

        def prefill(params, cache, tokens):
            return api.decode_step(cfg, params, cache, tokens, 0)

        return prefill, (params, cache, toks), meta

    # decode
    params = param_structs(cfg, mesh)
    cache = cache_structs(cfg, shape.batch, shape.seq, mesh)
    toks = _sds(mesh, (shape.batch, 1), jnp.int32, DATA, None)
    idx = shape.seq - 1

    def decode(params, cache, tokens):
        return api.decode_step(cfg, params, cache, tokens, idx)

    return decode, (params, cache, toks), meta


# ------------------------------------------------------------- comet ----


def build_comet_cell(arch: str, mesh: Mesh, multi_pod: bool, overrides=None):
    """Lowerable distributed similarity engine over the pod's devices."""
    from repro.configs.registry import get_config as _gc
    from repro.core.plan2 import TwoWayPlan
    from repro.core.plan3 import ThreeWayPlan
    from repro.core.threeway import _threeway_program
    from repro.core.twoway import CometConfig, _twoway_program
    from repro.parallel.mesh import make_comet_mesh

    ccfg = _gc(arch)
    if overrides:
        import dataclasses
        ccfg = dataclasses.replace(ccfg, **overrides)
    chips = mesh.devices.size
    n_pf, n_pv, n_pr = ccfg.decomposition(chips, multi_pod)
    comet_cfg = CometConfig(
        n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, n_st=ccfg.n_st,
        impl=ccfg.impl, levels=ccfg.levels or 2, out_dtype=ccfg.out_dtype,
        ring_dtype=ccfg.ring_dtype,
    )
    cmesh = make_comet_mesh(n_pf, n_pv, n_pr, devices=mesh.devices.ravel())
    n_v = ccfg.n_vp * n_pv
    V = jax.ShapeDtypeStruct(
        (ccfg.n_f, n_v), jnp.dtype(ccfg.ring_dtype),
        sharding=NamedSharding(cmesh, P("pf", "pv")),
    )
    out_dtype = jnp.dtype(ccfg.out_dtype)
    if ccfg.way == 2:
        plan = TwoWayPlan(n_pv, n_pr)
        fn = jax.shard_map(
            partial(_twoway_program, cfg=comet_cfg, plan=plan, out_dtype=out_dtype),
            mesh=cmesh, in_specs=P("pf", "pv"),
            out_specs=P("pv", "pr", None, None, None), check_vma=False,
        )
    else:
        plan = ThreeWayPlan(n_pv, n_pr, ccfg.n_st)
        fn = jax.shard_map(
            partial(_threeway_program, cfg=comet_cfg, plan=plan, stage=0,
                    out_dtype=out_dtype),
            mesh=cmesh, in_specs=P("pf", "pv"),
            out_specs=P("pv", "pr", None, None, None, None), check_vma=False,
        )
    # cost_analysis statically counts EVERY round-robin cond branch; a rank
    # executes only its share at runtime.  work_fraction rescales the
    # compute/memory terms (collectives run unconditionally on the ring).
    if ccfg.way == 2:
        work_fraction = plan.slots_per_rank / plan.n_steps
    else:
        work_fraction = plan.slots_per_rank / plan.items_per_slab
    meta = {
        "arch": arch, "shape": "paper", "kind": f"comet{ccfg.way}way",
        "n_f": ccfg.n_f, "n_v": n_v, "n_pf": n_pf, "n_pv": n_pv, "n_pr": n_pr,
        "n_st": ccfg.n_st, "impl": ccfg.impl, "work_fraction": work_fraction,
    }
    return fn, (V,), meta
