"""Streamed campaigns: disk -> host -> device chunks + cross-shard merge.

``stream_twoway`` / ``stream_threeway`` run the SAME block-circulant /
tetrahedral schedules as the in-memory engines, but over the store's byte
axis one chunk at a time:

1. ``StreamPlan`` cuts the payload byte (field) axis into fixed-shape
   chunks (``repro.stream.plan``);
2. ``ShardPrefetcher`` stages chunk ``s+1`` from the shard mmaps while the
   device runs chunk ``s`` (``repro.stream.prefetch``);
3. each chunk runs a deferred-flush device program (``_twoway_deferred_
   program`` / ``_threeway_program(deferred=True)``) that emits raw fp32
   numerator partials psummed over "pf", plus the chunk's per-vector stat
   partial;
4. the host accumulates partials across chunks in fp32, and the **cross-
   shard merge epilogue** applies the metric assembly + symmetry masks
   once — producing ``TwoWayOutput`` / ``ThreeWayOutput`` blocks laid out
   exactly like an in-memory run's.

Bit-exactness: the byte axis is the CONTRACTION axis, numerator and stat
partials of leveled integer data are exact fp32 integers, and fp32
addition of exact integers is associative — so chunk-order accumulation is
bit-identical to the in-memory single-pass psum, and the merged assembly
(the same ``assemble2`` / ``assemble3`` fp32 ops) yields bit-identical
checksums across ANY chunking (pinned in tests/test_stream.py against
``impl="xla"`` in-memory runs).

Peak host payload memory is ``StreamPlan.peak_host_bytes`` — the staging
buffers, bounded by ``max_host_bytes`` — never the dataset size.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.obs import trace as obs

from repro.core.metric_spec import (
    CZEKANOWSKI,
    MetricSpec,
    batch_lead,
    group_families,
)
from repro.core.plan2 import TwoWayPlan
from repro.core.plan3 import ItemKind, ThreeWayPlan
from repro.core.threeway import ThreeWayOutput, _threeway_program
from repro.core.tile_executor import TileExecutor
from repro.core.twoway import (
    CometConfig,
    TwoWayOutput,
    _twoway_deferred_batched_program,
    _twoway_deferred_program,
    batch_accounting,
    resolve_config,
)
from repro.stream.plan import StreamPlan, fill_chunk
from repro.stream.prefetch import ShardPrefetcher

__all__ = [
    "stream_twoway",
    "stream_threeway",
    "stream_twoway_batched",
    "stream_threeway_batched",
    "stream_twoway_delta",
]


def _as_sharded(dataset):
    """Accept a dataset path, DatasetReader, or ShardedPlanes handle."""
    from repro.store.reader import DatasetReader, ShardedPlanes

    if isinstance(dataset, ShardedPlanes):
        return dataset
    if isinstance(dataset, DatasetReader):
        return dataset.sharded()
    return DatasetReader(dataset).sharded()


def _stream_info(splan: StreamPlan, cfg: CometConfig, n_shards: int) -> dict:
    """The accounting block engines record as ``meta["stream"]``."""
    return {
        "chunks": splan.n_chunks,
        "chunk_kb": splan.chunk_kb,
        "chunk_bytes": splan.chunk_nbytes,
        "n_buffers": splan.n_buffers,
        "peak_host_bytes": splan.peak_host_bytes,
        "max_host_bytes": cfg.max_host_bytes,
        "n_shards": n_shards,
    }


def _run_chunks(sh, splan: StreamPlan, jfn, accs, stat_acc):
    """Drive the prefetch/compute loop: stage each chunk, run the deferred
    program, fold the fp32 partials into the host accumulators.

    ``accs`` is a list of numpy accumulator arrays matching the program's
    leading outputs; the last program output is always the stat partial,
    folded into ``stat_acc``.  Returns ``(staged_bytes, overlap)`` —
    measured peak staged bytes (the buffers actually allocated, the
    number ``max_host_bytes`` bounds) and the staging-vs-compute overlap
    accounting (``stage_seconds``, ``stall_seconds``, ``compute_seconds``)
    that joins ``meta["stream"]``.
    """
    chunks = splan.chunks()
    buffers = [np.zeros(splan.chunk_shape, np.uint8)
               for _ in range(splan.n_buffers)]
    shard_cache = {}

    def shard_of(rank):
        if rank not in shard_cache:
            shard_cache[rank] = sh.reader.shard(rank)
        return shard_cache[rank]

    def fill(idx, buf):
        fill_chunk(buf, chunks[idx], shard_of, splan.n_v_data)

    compute_s = 0.0
    with ShardPrefetcher(fill, len(chunks), buffers) as pf:
        for _idx, buf in pf:
            t0 = time.perf_counter()
            with obs.span("ring-step") as sp:
                outs = jfn(jnp.asarray(buf))
                # np.asarray blocks until the chunk program is done (GIL
                # released inside XLA — the prefetch thread fills the next
                # buffer meanwhile); only then is the staging buffer reusable
                for acc, out in zip(accs, outs[:-1]):
                    np.add(acc, np.asarray(out).reshape(acc.shape), out=acc)
                np.add(stat_acc, np.asarray(outs[-1]).reshape(stat_acc.shape),
                       out=stat_acc)
                sp.add(chunk=_idx, chunk_bytes=int(buf.nbytes))
            compute_s += time.perf_counter() - t0
            pf.release(buf)
        overlap = {
            "stage_seconds": pf.stage_seconds,
            "stall_seconds": pf.stall_seconds,
            "compute_seconds": compute_s,
        }
    return sum(b.nbytes for b in buffers), overlap


def _merge_twoway_blocks(cfg, plan, executor, acc, stats) -> np.ndarray:
    """Cross-shard merge epilogue for ONE metric: assemble every computed
    block once from its complete fp32 numerator/stat partials.  ``acc`` is
    (n_pv, n_pr, slots, m, m), ``stats`` (n_pv, m) — the single-metric
    slices; batched campaigns call this once per metric over the shared
    per-family accumulators."""
    blocks = np.zeros(acc.shape, executor.out_dtype)
    for p_v in range(cfg.n_pv):
        for p_r in range(cfg.n_pr):
            for d in plan.steps_of_pr(p_r):
                if not plan.rank_computes(p_v, p_r, d):
                    continue
                row, col = plan.block_of(p_v, d)
                blocks[p_v, p_r, d // cfg.n_pr] = np.asarray(
                    executor.merge_pair(
                        acc[p_v, p_r, d // cfg.n_pr],
                        stats[row], stats[col], diagonal=(d == 0),
                    )
                )
    return blocks


def stream_twoway(
    dataset, mesh, cfg: CometConfig, metric: MetricSpec = None,
) -> tuple:
    """Streamed 2-way campaign over a ``repro.store`` dataset.

    Returns ``(TwoWayOutput, info)`` — the output bit-identical to
    ``twoway_distributed`` on the materialized payload, ``info`` the
    streaming accounting (chunks, peak host bytes).
    """
    metric = metric or CZEKANOWSKI
    sh = _as_sharded(dataset)
    cfg = resolve_config(cfg, sh, metric)  # plane path or raises
    n_v = sh.n_v
    n_vp = -(-n_v // cfg.n_pv)
    plan = TwoWayPlan(cfg.n_pv, cfg.n_pr)
    splan = StreamPlan.for_reader(
        sh.reader, n_v=cfg.n_pv * n_vp, n_pf=cfg.n_pf,
        max_host_bytes=cfg.max_host_bytes,
    )

    jfn = jax.jit(jax.shard_map(
        partial(_twoway_deferred_program, cfg=cfg, plan=plan, metric=metric),
        mesh=mesh,
        in_specs=P(None, "pf", "pv"),
        out_specs=(P("pv", "pr", None, None, None), P("pv", None)),
        check_vma=False,
    ))

    acc = np.zeros(
        (cfg.n_pv, cfg.n_pr, plan.slots_per_rank, n_vp, n_vp), np.float32
    )
    stats = np.zeros((cfg.n_pv, n_vp), np.float32)
    staged, overlap = _run_chunks(sh, splan, jfn, [acc], stats)

    # -- cross-shard merge epilogue: assemble once from complete partials --
    executor = TileExecutor(
        cfg=cfg, metric=metric, out_dtype=jnp.dtype(cfg.out_dtype),
        axis=None, deferred=True,
    )
    with obs.span("merge") as sp:
        blocks = _merge_twoway_blocks(cfg, plan, executor, acc, stats)
        sp.add(blocks=int(blocks.size))
    out = TwoWayOutput(blocks=blocks, plan=plan, n_v=n_v, n_vp=n_vp,
                       path=executor.path)
    info = _stream_info(splan, cfg, sh.n_shards)
    info["staged_bytes"] = staged
    info.update(overlap)
    return out, info


def _merge_threeway_blocks(
    cfg, plan, stage, executor, needs, accs, stats, L, n_vp,
) -> np.ndarray:
    """Cross-shard 3-way merge epilogue for ONE metric (mask logic mirrors
    ``ThreeWayOutput.entries()``).  ``accs`` is the single-metric 4-tuple
    of slot-partial accumulators, ``stats`` the metric's (n_pv, m) stat
    rows; batched campaigns call this once per metric over its family's
    slices of the shared accumulators."""
    B_acc, pl_acc, pr_acc, lr_acc = accs
    blocks = np.zeros(B_acc.shape, executor.out_dtype)
    li = np.arange(n_vp)
    for p_v in range(cfg.n_pv):
        for p_r in range(cfg.n_pr):
            for slot, it in enumerate(plan.items_of(p_v, p_r)):
                own, bj, bk = it.blocks(p_v, cfg.n_pv)
                lo, _ = plan.sixth_bounds(n_vp, it.slice_idx, stage)
                jg = lo + np.arange(L)
                if it.kind == ItemKind.DIAG:
                    pipe_b = left_b = right_b = own
                    mask = (li[None, :, None] < jg[:, None, None]) & (
                        li[None, None, :] > jg[:, None, None]
                    )
                elif it.kind == ItemKind.FACE:
                    pipe_b, left_b, right_b = bj, own, bj
                    mask = np.broadcast_to(
                        li[None, None, :] > jg[:, None, None],
                        (L, n_vp, n_vp),
                    )
                else:
                    if it.slice_axis == 0:
                        pipe_b, left_b, right_b = own, bj, bk
                    elif it.slice_axis == 1:
                        pipe_b, left_b, right_b = bj, own, bk
                    else:
                        pipe_b, left_b, right_b = bk, own, bj
                    mask = np.ones((L, n_vp, n_vp), bool)
                c3 = np.asarray(executor.merge_three(
                    B_acc[p_v, p_r, slot],
                    pl_acc[p_v, p_r, slot] if needs else None,
                    pr_acc[p_v, p_r, slot] if needs else None,
                    lr_acc[p_v, p_r, slot] if needs else None,
                    stats[pipe_b][jg], stats[left_b], stats[right_b],
                ))
                blocks[p_v, p_r, slot] = np.where(mask, c3, 0)
    return blocks


def stream_threeway(
    dataset, mesh, cfg: CometConfig, stage: int = 0,
    metric: MetricSpec = None,
) -> tuple:
    """Streamed 3-way campaign stage over a ``repro.store`` dataset.

    Returns ``(ThreeWayOutput, info)`` bit-identical to
    ``threeway_distributed`` on the materialized payload.
    """
    metric = metric or CZEKANOWSKI
    sh = _as_sharded(dataset)
    cfg = resolve_config(cfg, sh, metric)
    n_v = sh.n_v
    unit = 6 * cfg.n_st
    n_vp = -(-n_v // cfg.n_pv)
    n_vp += (-n_vp) % unit
    L = n_vp // unit
    plan = ThreeWayPlan(cfg.n_pv, cfg.n_pr, cfg.n_st)
    slots = plan.slots_per_rank
    splan = StreamPlan.for_reader(
        sh.reader, n_v=cfg.n_pv * n_vp, n_pf=cfg.n_pf,
        max_host_bytes=cfg.max_host_bytes,
    )

    out_dtype = jnp.dtype(cfg.out_dtype)
    jfn = jax.jit(jax.shard_map(
        partial(_threeway_program, cfg=cfg, plan=plan, stage=stage,
                out_dtype=out_dtype, metric=metric, deferred=True),
        mesh=mesh,
        in_specs=P(None, "pf", "pv"),
        out_specs=(
            P("pv", "pr", None, None, None, None),  # 3-way numerators
            P("pv", "pr", None, None, None),  # pipe x left
            P("pv", "pr", None, None, None),  # pipe x right
            P("pv", "pr", None, None, None),  # left x right
            P("pv", None),  # stat partial
        ),
        check_vma=False,
    ))

    shape = (cfg.n_pv, cfg.n_pr, slots)
    accs = [
        np.zeros(shape + (L, n_vp, n_vp), np.float32),
        np.zeros(shape + (L, n_vp), np.float32),
        np.zeros(shape + (L, n_vp), np.float32),
        np.zeros(shape + (n_vp, n_vp), np.float32),
    ]
    stats = np.zeros((cfg.n_pv, n_vp), np.float32)
    staged, overlap = _run_chunks(sh, splan, jfn, accs, stats)

    # -- cross-shard merge epilogue (mask logic mirrors entries()) ---------
    executor = TileExecutor(cfg=cfg, metric=metric, out_dtype=out_dtype,
                            axis=None, deferred=True)
    with obs.span("merge") as sp:
        blocks = _merge_threeway_blocks(
            cfg, plan, stage, executor, metric.needs_pair_terms, accs, stats,
            L, n_vp,
        )
        sp.add(blocks=int(blocks.size))
    out = ThreeWayOutput(blocks=blocks, plan=plan, n_v=n_v, n_vp=n_vp,
                         stage=stage, path=executor.path3)
    info = _stream_info(splan, cfg, sh.n_shards)
    info["staged_bytes"] = staged
    info.update(overlap)
    return out, info


def stream_twoway_delta(
    dataset, n_old: int, mesh, cfg: CometConfig, metric: MetricSpec = None,
) -> tuple:
    """Streamed border-block delta over a ``repro.store`` dataset whose
    first ``n_old`` columns a prior result already covers (``core.delta``).

    The chunk loop stages each byte chunk into a PAIR of staging buffers —
    the sharded old columns and the replicated new columns — following the
    overlap-staging idiom of the streamed full campaign: the prefetch
    thread splits chunk ``s+1``'s columns while the device contracts chunk
    ``s``.  Each chunk runs ``_twoway_delta_deferred_program`` (raw fp32
    rectangle/triangle partials + stat partials, no ring), the host
    accumulates, and the merge epilogue assembles once — bit-identical to
    the in-memory border and therefore to a full recompute.

    Returns ``(rect, tri, cfg, dinfo, sinfo)`` — the assembled border
    blocks (merge with ``core.delta.merge_delta``), the resolved config,
    the ``meta["delta"]`` accounting and the usual streaming accounting.
    """
    from repro.core.delta import _twoway_delta_deferred_program, delta_accounting

    metric = metric or CZEKANOWSKI
    sh = _as_sharded(dataset)
    cfg = resolve_config(cfg, sh, metric)  # plane path or raises
    n_v = sh.n_v
    if not 1 <= n_old < n_v:
        raise ValueError(f"n_old={n_old} must be in [1, n_v={n_v})")
    m = n_v - n_old
    R = cfg.n_pv * cfg.n_pr
    n_op = -(-n_old // R)
    n_op_total = n_op * R
    splan = StreamPlan.for_reader(
        sh.reader, n_v=n_op_total + m, n_pf=cfg.n_pf,
        max_host_bytes=cfg.max_host_bytes,
    )

    jfn = jax.jit(jax.shard_map(
        partial(_twoway_delta_deferred_program, cfg=cfg, metric=metric),
        mesh=mesh,
        in_specs=(P(None, "pf", ("pv", "pr")), P(None, "pf", None)),
        out_specs=(
            P(("pv", "pr"), None),  # rectangle partial
            P(("pv", "pr"), None, None),  # triangle partial (rank 0 only)
            P(("pv", "pr")),  # old stat partial
            P(("pv", "pr"), None),  # new stat partial (replicated)
        ),
        check_vma=False,
    ))

    rect_acc = np.zeros((n_op_total, m), np.float32)
    tri_acc = np.zeros((m, m), np.float32)
    so_acc = np.zeros((n_op_total,), np.float32)
    sn_acc = np.zeros((m,), np.float32)

    chunks = splan.chunks()
    buffers = [
        (np.zeros((splan.levels, splan.chunk_kb, n_op_total), np.uint8),
         np.zeros((splan.levels, splan.chunk_kb, m), np.uint8))
        for _ in range(splan.n_buffers)
    ]
    shard_cache = {}

    def shard_of(rank):
        if rank not in shard_cache:
            shard_cache[rank] = sh.reader.shard(rank)
        return shard_cache[rank]

    def fill(idx, bufs):
        ob, nb = bufs
        chunk = chunks[idx]
        for rank, lo, hi, off in chunk.spans:
            sv = shard_of(rank)
            ob[:, off:off + (hi - lo), :n_old] = sv[:, lo:hi, :n_old]
            nb[:, off:off + (hi - lo), :] = sv[:, lo:hi, n_old:]
        used = chunk.nbytes_valid
        if used < ob.shape[1]:
            ob[:, used:, :] = 0
            nb[:, used:, :] = 0

    compute_s = 0.0
    with ShardPrefetcher(fill, len(chunks), buffers) as pf:
        for _idx, bufs in pf:
            t0 = time.perf_counter()
            with obs.span("delta-border") as sp:
                outs = jfn(jnp.asarray(bufs[0]), jnp.asarray(bufs[1]))
                np.add(rect_acc, np.asarray(outs[0]).reshape(rect_acc.shape),
                       out=rect_acc)
                np.add(tri_acc, np.asarray(outs[1])[0], out=tri_acc)
                np.add(so_acc, np.asarray(outs[2]).reshape(so_acc.shape),
                       out=so_acc)
                np.add(sn_acc, np.asarray(outs[3])[0], out=sn_acc)
                sp.add(chunk=_idx,
                       chunk_bytes=sum(int(b.nbytes) for b in bufs))
            compute_s += time.perf_counter() - t0
            pf.release(bufs)
        overlap = {
            "stage_seconds": pf.stage_seconds,
            "stall_seconds": pf.stall_seconds,
            "compute_seconds": compute_s,
        }
    staged = sum(b.nbytes for bufs in buffers for b in bufs)

    executor = TileExecutor(
        cfg=cfg, metric=metric, out_dtype=jnp.dtype(cfg.out_dtype),
        axis=None, deferred=True,
    )
    with obs.span("merge") as sp:
        rect = np.asarray(executor.merge_pair(rect_acc, so_acc, sn_acc))
        tri = np.asarray(
            executor.merge_pair(tri_acc, sn_acc, sn_acc, diagonal=True)
        )
        sp.add(entries=int(rect.size + tri.size))
    sinfo = _stream_info(splan, cfg, sh.n_shards)
    sinfo["staged_bytes"] = staged
    sinfo.update(overlap)
    dinfo = delta_accounting(
        cfg, n_old=n_old, n_new=m, n_op=n_op,
        payload_bytes=splan.chunk_nbytes * splan.n_chunks, streamed=True,
    )
    return rect, tri, cfg, dinfo, sinfo


def stream_twoway_batched(dataset, mesh, cfg: CometConfig, specs) -> tuple:
    """Streamed batched 2-way campaigns: one chunked ring traversal, one
    ``TwoWayOutput`` per metric (request order), each bit-identical to its
    sequential streamed/in-memory run.

    The chunk program accumulates ONE raw numerator partial per metric
    FAMILY (plus per-family stat partials); after the last chunk the merge
    epilogue fans each family's accumulator out through every member's
    assembly.  Returns ``(outputs, binfo, info)`` — the batched ring
    accounting plus the usual streaming accounting.
    """
    specs = list(specs)
    sh = _as_sharded(dataset)
    cfg = resolve_config(cfg, sh, batch_lead(specs))
    groups = group_families(specs)
    flat = [s for grp in groups for s in grp]
    gidx = {s.name: g for g, grp in enumerate(groups) for s in grp}
    n_v = sh.n_v
    n_vp = -(-n_v // cfg.n_pv)
    plan = TwoWayPlan(cfg.n_pv, cfg.n_pr)
    splan = StreamPlan.for_reader(
        sh.reader, n_v=cfg.n_pv * n_vp, n_pf=cfg.n_pf,
        max_host_bytes=cfg.max_host_bytes,
    )

    jfn = jax.jit(jax.shard_map(
        partial(_twoway_deferred_batched_program, cfg=cfg, plan=plan,
                groups=groups),
        mesh=mesh,
        in_specs=P(None, "pf", "pv"),
        out_specs=(P("pv", "pr", None, None, None, None),
                   P("pv", None, None)),
        check_vma=False,
    ))

    G = len(groups)
    acc = np.zeros(
        (cfg.n_pv, cfg.n_pr, G, plan.slots_per_rank, n_vp, n_vp), np.float32
    )
    stats = np.zeros((cfg.n_pv, G, n_vp), np.float32)
    staged, overlap = _run_chunks(sh, splan, jfn, [acc], stats)

    by_name = {}
    with obs.span("merge") as sp:
        for s in flat:
            g = gidx[s.name]
            executor = TileExecutor(
                cfg=cfg, metric=s, out_dtype=jnp.dtype(cfg.out_dtype),
                axis=None, deferred=True,
            )
            blocks = _merge_twoway_blocks(
                cfg, plan, executor, acc[:, :, g], stats[:, g]
            )
            by_name[s.name] = TwoWayOutput(
                blocks=blocks, plan=plan, n_v=n_v, n_vp=n_vp,
                path=executor.path,
            )
        sp.add(metrics=len(flat))
    info = _stream_info(splan, cfg, sh.n_shards)
    info["staged_bytes"] = staged
    info.update(overlap)
    binfo = batch_accounting(
        splan.chunk_nbytes * splan.n_chunks, cfg, plan, groups, n_vp,
        planes=True, way=2,
    )
    return [by_name[s.name] for s in specs], binfo, info


def stream_threeway_batched(
    dataset, mesh, cfg: CometConfig, specs, stage: int = 0,
) -> tuple:
    """Streamed batched 3-way campaign stage; see ``stream_twoway_batched``.

    Returns ``(outputs, binfo, info)`` with one ``ThreeWayOutput`` per
    metric in request order.
    """
    specs = list(specs)
    sh = _as_sharded(dataset)
    cfg = resolve_config(cfg, sh, batch_lead(specs))
    groups = group_families(specs)
    flat = [s for grp in groups for s in grp]
    gidx = {s.name: g for g, grp in enumerate(groups) for s in grp}
    n_v = sh.n_v
    unit = 6 * cfg.n_st
    n_vp = -(-n_v // cfg.n_pv)
    n_vp += (-n_vp) % unit
    L = n_vp // unit
    plan = ThreeWayPlan(cfg.n_pv, cfg.n_pr, cfg.n_st)
    slots = plan.slots_per_rank
    splan = StreamPlan.for_reader(
        sh.reader, n_v=cfg.n_pv * n_vp, n_pf=cfg.n_pf,
        max_host_bytes=cfg.max_host_bytes,
    )

    out_dtype = jnp.dtype(cfg.out_dtype)
    jfn = jax.jit(jax.shard_map(
        partial(_threeway_program, cfg=cfg, plan=plan, stage=stage,
                out_dtype=out_dtype, groups=groups, deferred=True),
        mesh=mesh,
        in_specs=P(None, "pf", "pv"),
        out_specs=(
            P("pv", "pr", None, None, None, None, None),  # 3-way numerators
            P("pv", "pr", None, None, None, None),  # pipe x left
            P("pv", "pr", None, None, None, None),  # pipe x right
            P("pv", "pr", None, None, None, None),  # left x right
            P("pv", None, None),  # per-family stat partials
        ),
        check_vma=False,
    ))

    G = len(groups)
    shape = (cfg.n_pv, cfg.n_pr, slots, G)
    accs = [
        np.zeros(shape + (L, n_vp, n_vp), np.float32),
        np.zeros(shape + (L, n_vp), np.float32),
        np.zeros(shape + (L, n_vp), np.float32),
        np.zeros(shape + (n_vp, n_vp), np.float32),
    ]
    stats = np.zeros((cfg.n_pv, G, n_vp), np.float32)
    staged, overlap = _run_chunks(sh, splan, jfn, accs, stats)

    by_name = {}
    with obs.span("merge") as sp:
        for s in flat:
            g = gidx[s.name]
            executor = TileExecutor(cfg=cfg, metric=s, out_dtype=out_dtype,
                                    axis=None, deferred=True)
            blocks = _merge_threeway_blocks(
                cfg, plan, stage, executor, s.needs_pair_terms,
                [a[:, :, :, g] for a in accs], stats[:, g], L, n_vp,
            )
            by_name[s.name] = ThreeWayOutput(
                blocks=blocks, plan=plan, n_v=n_v, n_vp=n_vp, stage=stage,
                path=executor.path3,
            )
        sp.add(metrics=len(flat))
    info = _stream_info(splan, cfg, sh.n_shards)
    info["staged_bytes"] = staged
    info.update(overlap)
    binfo = batch_accounting(
        splan.chunk_nbytes * splan.n_chunks, cfg, plan, groups, n_vp,
        planes=True, way=3,
    )
    return [by_name[s.name] for s in specs], binfo, info
