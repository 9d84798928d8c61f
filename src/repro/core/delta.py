"""Border-block delta campaigns: incremental 2-way results for appended vectors.

When a cohort grows from ``n`` to ``n + m`` vectors (``repro.store``'s
``append_dataset``), the full triangular campaign wastes almost all of the
work already paid for: the prior result covers every pair inside ``[0, n)``.
The only NEW pairs are the **border** —

* the rectangle: old ``i in [0, n)`` vs new ``j in [n, n + m)``, and
* the small new-vs-new triangle inside ``[n, n + m)``

— ``n*m + m*(m-1)/2`` entries instead of ``(n+m)(n+m-1)/2``.  This module
computes exactly that border on the mesh and merges it with a prior
``TwoWayOutput`` into packed upper-triangular storage.

SPMD mapping: there is NO ring.  The old block shards its vector axis over
the combined ("pv", "pr") mesh axes (each rank holds ``n_op = ceil(n /
(n_pv * n_pr))`` old vectors), the new block is replicated, and fields
shard over "pf" exactly as in the full engine (numerator psums over "pf").
Each rank computes its own ``(n_op, m)`` slice of the rectangle through
``TileExecutor.pair_block`` — the SAME fused-levels / popcount / unfused
kernels as full campaigns — and rank (pv=0, pr=0) additionally computes the
new-vs-new triangle on the triangular tile schedule (``lax.cond`` skips it
elsewhere, mirroring the full engine's half-step masking).  Ring payload
bytes are zero by construction; ``delta_accounting`` records the
``m·n``-proportional compute so ``meta["delta"]`` can prove it.

Bit-exactness: border numerators are the same exact fp32 integer
contractions (any kernel path) and the same ``assemble_tile`` /
``assemble2`` elementwise assembly as the full engine's off-diagonal and
diagonal blocks, so the merged result's checksum is bit-identical to a
from-scratch recompute of the grown cohort at ANY decomposition — pinned in
tests/test_delta.py and tests/distributed_harness.py ``check_delta``.

Merged storage: a single-rank ``TwoWayPlan(1, 1)`` packed upper-triangular
``TwoWayOutput`` (``N(N-1)/2`` values in ``np.triu_indices`` row-major
order) — a valid prior for the NEXT append, so deltas chain.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.metric_spec import CZEKANOWSKI, MetricSpec
from repro.core.plan2 import TwoWayPlan
from repro.core.tile_executor import TileExecutor
from repro.obs import trace as obs
from repro.core.twoway import (
    CometConfig,
    TwoWayOutput,
    _cached_jit,
    resolve_config,
)

__all__ = [
    "twoway_delta",
    "merge_delta",
    "delta_accounting",
    "packed_upper_index",
]


def packed_upper_index(i, j, N: int):
    """Flat position of strict-upper pair (i < j) in ``np.triu_indices(N, 1)``
    row-major order — the packed single-rank layout ``TwoWayOutput``
    unpacks with ``out[np.triu_indices(m, 1)] = flat``."""
    return i * (2 * N - i - 1) // 2 + (j - i - 1)


def delta_accounting(
    cfg: CometConfig, *, n_old: int, n_new: int, n_op: int,
    payload_bytes: int, streamed: bool = False, ring_payload_bytes: int = 0,
) -> dict:
    """The ``meta["delta"]`` block: proof that border-mode compute scales
    with ``m·n + m²/2`` entries, not ``n²``.

    ``computed_entries`` counts what the devices actually evaluate —
    including the inert padding rows of the old-vector shards — so the
    border proportionality is honest; ``ring_payload_bytes`` is zero for
    the in-memory border (no ppermute exists in the program) and the
    chunked staging bytes for the streamed border."""
    N = n_old + n_new
    tri = n_new * (n_new - 1) // 2
    return {
        "n_old": int(n_old),
        "n_new": int(n_new),
        "border_entries": int(n_old * n_new + tri),
        "full_entries": int(N * (N - 1) // 2),
        "computed_entries": int(cfg.n_pv * cfg.n_pr * n_op * n_new + tri),
        "ring_payload_bytes": int(ring_payload_bytes),
        "payload_bytes": int(payload_bytes),
        "decomposition": [cfg.n_pf, cfg.n_pv, cfg.n_pr],
        "streamed": bool(streamed),
    }


def _prep_delta_payload(V, n_old: int, cfg: CometConfig, metric: MetricSpec):
    """Resolve the config and split the payload into the sharded old block
    and the replicated new block.

    Vector-axis slicing commutes with the bit-plane encoding (packing is
    along the field axis — ``slice_planes_vectors`` property), so a
    pre-encoded ``PackedPlanes`` payload splits by byte-column view with no
    re-encode; value matrices encode old/new separately when the plane path
    resolves (identical bytes to slicing a whole-matrix encode).  The old
    block pads its vector axis to ``n_op * n_pv * n_pr`` with inert zero
    columns.  Returns ``(cfg, args, in_specs, planes, n_op, m)``.
    """
    from repro.kernels.mgemm_levels.planes import PackedPlanes, pad_planes

    R = cfg.n_pv * cfg.n_pr
    if isinstance(V, PackedPlanes):
        n_v = V.n_v
        if not 1 <= n_old < n_v:
            raise ValueError(f"n_old={n_old} must be in [1, n_v={n_v})")
        cfg = resolve_config(cfg, V, metric)  # plane path or raises
        m = n_v - n_old
        n_op = -(-n_old // R)
        Po = pad_planes(
            np.ascontiguousarray(V.planes[:, :, :n_old]),
            byte_align=cfg.n_pf, n_v=n_op * R,
        )
        Pn = pad_planes(
            np.ascontiguousarray(V.planes[:, :, n_old:]),
            byte_align=cfg.n_pf,
        )
        return (
            cfg, (jnp.asarray(Po), jnp.asarray(Pn)),
            (P(None, "pf", ("pv", "pr")), P(None, "pf", None)),
            True, n_op, m,
        )
    V = np.asarray(V)
    n_v = V.shape[1]
    if not 1 <= n_old < n_v:
        raise ValueError(f"n_old={n_old} must be in [1, n_v={n_v})")
    cfg = resolve_config(cfg, V, metric)
    m = n_v - n_old
    n_op = -(-n_old // R)
    planes = cfg.encoding == "bitplane"
    field_align = (8 if planes else 1) * cfg.n_pf
    fp = (-V.shape[0]) % field_align
    Vp = np.pad(V, ((0, fp), (0, 0))) if fp else V
    Vo = Vp[:, :n_old]
    Vn = np.ascontiguousarray(Vp[:, n_old:])
    vp = n_op * R - n_old
    if vp:
        Vo = np.pad(Vo, ((0, 0), (0, vp)))
    if planes:
        from repro.kernels.mgemm_levels import encode_bitplanes_np

        return (
            cfg,
            (jnp.asarray(encode_bitplanes_np(Vo, cfg.levels)),
             jnp.asarray(encode_bitplanes_np(Vn, cfg.levels))),
            (P(None, "pf", ("pv", "pr")), P(None, "pf", None)),
            True, n_op, m,
        )
    dt = jnp.dtype(cfg.ring_dtype)
    return (
        cfg, (jnp.asarray(Vo, dt), jnp.asarray(Vn, dt)),
        (P("pf", ("pv", "pr")), P("pf", None)),
        False, n_op, m,
    )


def _twoway_delta_program(
    Vo, Vn, *, cfg: CometConfig, out_dtype, metric: MetricSpec = None,
    planes: bool = False,
):
    """Per-device border program (inside shard_map, NO ring).

    ``Vo``: this rank's old-vector shard — (n_f/n_pf, n_op) values or
    (levels, kb/n_pf, n_op) packed planes; ``Vn``: the replicated new
    block.  Emits the rank's (n_op, m) rectangle slice, plus — on rank
    (pv=0, pr=0) only, under ``lax.cond`` like the full engine's half-step
    masking — the (m, m) strict-upper new-vs-new triangle on the
    triangular tile schedule."""
    metric = metric or CZEKANOWSKI
    executor = TileExecutor(cfg=cfg, metric=metric, out_dtype=out_dtype,
                            axis="pf")
    if planes:
        from repro.kernels.mgemm_levels import values_from_planes

        Wo, Wn = values_from_planes(Vo), values_from_planes(Vn)
    else:
        Wo, Wn = Vo, Vn
    so = jax.lax.psum(metric.stat(Wo), "pf")
    sn = jax.lax.psum(metric.stat(Wn), "pf")
    m = Vn.shape[-1]
    rect = executor.pair_block(Vo, so, Vn, sn, diagonal=False)
    first = jnp.logical_and(
        jax.lax.axis_index("pv") == 0, jax.lax.axis_index("pr") == 0
    )
    tri = jax.lax.cond(
        first,
        lambda: executor.pair_block(Vn, sn, Vn, sn, diagonal=True),
        lambda: jnp.zeros((m, m), out_dtype),
    )
    return rect, tri[None]


def _twoway_delta_deferred_program(
    Po, Pn, *, cfg: CometConfig, metric: MetricSpec = None,
):
    """Deferred-flush border chunk program (``repro.stream``): one byte-axis
    chunk of the old/new payloads emits the rank's raw fp32 rectangle
    partial (psummed over "pf"), the rank-(0,0) new-vs-new triangle
    partial, and both stat partials; the host accumulates all four across
    chunks and the merge epilogue assembles once — bit-identical to the
    in-memory border (cross-shard merge guarantee)."""
    from repro.kernels.mgemm_levels import values_from_planes

    metric = metric or CZEKANOWSKI
    executor = TileExecutor(cfg=cfg, metric=metric, out_dtype=jnp.float32,
                            axis="pf", deferred=True)
    so = jax.lax.psum(metric.stat(values_from_planes(Po)), "pf")
    sn = jax.lax.psum(metric.stat(values_from_planes(Pn)), "pf")
    m = Pn.shape[-1]
    rect = executor.pair_partial(Po, Pn)
    first = jnp.logical_and(
        jax.lax.axis_index("pv") == 0, jax.lax.axis_index("pr") == 0
    )
    tri = jax.lax.cond(
        first,
        lambda: executor.pair_partial(Pn, Pn),
        lambda: jnp.zeros((m, m), jnp.float32),
    )
    return rect, tri[None], so, sn[None]


def twoway_delta(
    V, n_old: int, mesh, cfg: CometConfig, metric: MetricSpec = None,
) -> tuple:
    """Compute the border blocks of an appended cohort on the mesh.

    ``V`` is the FULL grown payload (values or ``PackedPlanes``) whose
    first ``n_old`` columns the prior result already covers.  Returns
    ``(rect, tri, cfg, info)``: the assembled ``(n_op * n_pv * n_pr, m)``
    rectangle (row ``i`` = old vector ``i``; padding rows past ``n_old``
    are inert), the ``(m, m)`` strict-upper new-vs-new triangle, the
    resolved config, and the ``delta_accounting`` dict.  Merge with a
    prior via ``merge_delta``."""
    metric = metric or CZEKANOWSKI
    cfg, args, in_specs, planes, n_op, m = _prep_delta_payload(
        V, n_old, cfg, metric
    )
    out_dtype = jnp.dtype(cfg.out_dtype)
    fn = _cached_jit(
        ("delta", mesh, cfg, metric.name, str(out_dtype), planes),
        lambda: jax.shard_map(
            partial(_twoway_delta_program, cfg=cfg, out_dtype=out_dtype,
                    metric=metric, planes=planes),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(("pv", "pr"), None), P(("pv", "pr"), None, None)),
            check_vma=False,
        ),
    )
    payload_bytes = sum(int(a.nbytes) for a in args)
    with obs.span("delta-border") as sp:
        rect, tri = fn(*args)
        rect, tri = np.asarray(rect), np.asarray(tri)[0]
        sp.add(n_old=int(n_old), n_new=int(m), payload_bytes=payload_bytes)
    info = delta_accounting(
        cfg, n_old=n_old, n_new=m, n_op=n_op, payload_bytes=payload_bytes,
    )
    return rect, tri, cfg, info


def merge_delta(
    prior: TwoWayOutput, rect: np.ndarray, tri: np.ndarray,
    n_old: int, n_new: int, out_dtype, path: str = None,
) -> TwoWayOutput:
    """Merge a prior result and its border blocks into packed storage.

    ``prior`` may be ANY ``TwoWayOutput`` covering vectors ``[0, n_old)``
    — dense or packed, any plan (including a previous ``merge_delta``
    output, so deltas chain across appends).  The merged output is a
    single-rank ``TwoWayPlan(1, 1)`` packed upper triangle over
    ``N = n_old + n_new`` vectors whose entries — and therefore checksum —
    are bit-identical to a full recompute.  ``path`` is the border
    blocks' contraction path (``TileExecutor.path``), recorded on the
    merged output."""
    if prior.n_v != n_old:
        raise ValueError(
            f"prior covers n_v={prior.n_v} vectors, delta says n_old={n_old}"
        )
    N = n_old + n_new
    with obs.span("merge") as sp:
        flat = np.zeros((1, 1, N * (N - 1) // 2), np.dtype(out_dtype))
        buf = flat[0, 0]
        for I, J, vals in prior.entries():
            lo, hi = np.minimum(I, J), np.maximum(I, J)
            buf[packed_upper_index(lo, hi, N)] = vals
        i = np.arange(n_old)[:, None]
        j = n_old + np.arange(n_new)[None, :]
        buf[packed_upper_index(i, j, N).ravel()] = (
            rect[:n_old].astype(buf.dtype).ravel()
        )
        a, b = np.triu_indices(n_new, 1)
        buf[packed_upper_index(n_old + a, n_old + b, N)] = tri[a, b]
        sp.add(entries=int(buf.size), n_old=int(n_old), n_new=int(n_new))
    return TwoWayOutput(
        blocks=flat, plan=TwoWayPlan(1, 1), n_v=N, n_vp=N, storage="packed",
        path=path,
    )
