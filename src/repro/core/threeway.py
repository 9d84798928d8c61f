"""Distributed 3-way Proportional Similarity engine — paper §4.2, Algs 2-3.

SPMD structure per rank (p_v, p_r) on the ("pf", "pv", "pr") mesh, computing
stage ``s_t`` of the tetrahedral schedule in ``repro.core.plan3``:

  Phase A (diagonal-edge block): 6 slices of the strict tetrahedron
           a < b < c inside the rank's own block.
  Phase B (face blocks): ring over dj; for each received block J, 6 slices of
           the prism {(a in own) x (b < c in J)}.
  Phase C (volume blocks): doubly-nested ring over (dk, dj) — Algorithm 2's
           communication pipeline — computing ONE oriented 1/6-slice per
           block (middle-id rule, ``plan3.vol_slice_rule``).

Each slice runs Algorithm 3's inner pipeline through the ``TileExecutor``:
on the XLA path the pipeline axis (length L = n_vp/(6 n_st)) is folded into
the GEMM M dimension via X[q, (l, t)] = min(left[q, l], pipe[q, j0 + t]), so

    B[t, l, r] = sum_q min(pipe[q, j0+t], left[q, l], right[q, r])

is one (m*L, n_fp) x (n_fp, m) min-plus GEMM — the TPU-friendly realization
of the paper's "sequence of 2-way operations" that maximizes mGEMM size
(their stated goal for the staging knob).  On the Pallas path the executor
instead runs the fused X_j kernel per pipeline column, so X never touches
HBM (kernels/czek3).  Pairwise numerators for the metric assembly are two
(L, m) sliced contractions + one (m, m) full contraction; all partials are
psummed over "pf" in one fused collective per item.

Round-robin: item sb executes iff sb % n_pr == p_r (lax.cond — compute is
skipped, not masked).  Phases B/C run under ``lax.fori_loop`` with the ring
``ppermute`` in the loop body, so the compiled program size is O(1) in n_pv
(306 items at n_pv=16 compile as two nested loops).

Packed bit-plane ring (resolved ``encoding == "bitplane"``): V is encoded
ONCE into packed uint8 planes before ``shard_map`` and the doubly-nested
ring carries the (levels, kb, n_vp) plane shards themselves — 1/16 of the
fp32 wire volume for {0,1,2} SNP data.  Pipeline slices are byte-range
views along the vector axis (packing is along the FIELD axis, so no bit
surgery is ever needed) and feed the level-decomposed slice kernels
directly; nothing re-encodes inside the ring loop.  Wire/storage layout:
docs/BITPLANE_FORMAT.md.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import checksum as ck
from repro.core.metric_spec import (
    CZEKANOWSKI,
    MetricSpec,
    batch_lead,
    group_families,
    plane_native,
)
from repro.core.plan3 import ItemKind, ThreeWayPlan, PERMS
from repro.core.tile_executor import TileExecutor
from repro.core.twoway import (
    CometConfig,
    _cached_jit,
    _run_program,
    batch_accounting,
    checksum_launcher,
)
from repro.obs import trace as obs

__all__ = [
    "ThreeWayOutput",
    "threeway_distributed",
    "threeway_batched",
    "czek3_distributed",
]

# lookup: (rank_own, rank_J, rank_K) base-3 -> permutation index (plan3.PERMS)
_PERM_LUT = np.zeros(27, np.int32)
for _i, _p in enumerate(PERMS):
    _PERM_LUT[_p[0] * 9 + _p[1] * 3 + _p[2]] = _i


def _vol_rule_traced(own, bj, bk):
    """Traced (slice_axis, slice_idx) — must match plan3.vol_slice_rule."""
    r_own = (own > bj).astype(jnp.int32) + (own > bk).astype(jnp.int32)
    r_j = (bj > own).astype(jnp.int32) + (bj > bk).astype(jnp.int32)
    r_k = 3 - r_own - r_j
    axis = (r_j == 1) * 1 + (r_k == 1) * 2  # 0 if own is the middle id
    idx = jnp.asarray(_PERM_LUT)[r_own * 9 + r_j * 3 + r_k]
    return axis, idx


def _item_metrics(
    pipe, left, right, s_p, s_l, s_r, j0, *, kind: ItemKind, L: int,
    execs, groups, out_dtype, deferred: bool = False,
):
    """Masked metric slices for one work item — (M, L, m, m), one per
    requested metric in flattened family order.

    pipe/left/right: (n_fp, m) field-major value blocks, or (levels, kb, m)
    packed uint8 bit-planes on the plane ring (docs/BITPLANE_FORMAT.md);
    s_*: (G, m) per-FAMILY stats (already psummed over pf) — ``groups`` is
    the ``group_families`` partition of the requested metrics, ``execs``
    the parallel per-group executor lists (``execs[g][0]`` is the family's
    contraction lead).  Each family contracts ONCE; members differ only in
    their ``assemble3`` epilogue.  Product-family groups riding a plane
    ring reconstruct exact values via ``values_from_planes`` first.  All
    families' numerators psum in ONE fused collective, so the item costs
    one collective regardless of metric count.  j0: traced pipeline offset.

    ``deferred=True`` (streamed chunk programs, ``repro.stream``) stops
    after the psum and returns the RAW fp32 numerator partials
    ``(B, n2_pl, n2_pr, n2_lr)`` — shapes (G, L, m, m), (G, L, m),
    (G, L, m), (G, m, m), zeros standing in when the family needs no pair
    terms — so the cross-shard merge epilogue can assemble and mask once
    per campaign instead of once per chunk.
    """
    m = pipe.shape[-1]
    planes = pipe.ndim == 3
    if planes:
        # packed bit-plane ring: pipeline slicing along the vector axis is
        # a plain byte-range view of the (levels, kb, m) payload — the
        # field axis (where bits pack 8-per-byte) is untouched
        from repro.kernels.mgemm_levels import slice_planes_vectors

        ps = slice_planes_vectors(pipe, j0, L)
    else:
        n_fp = pipe.shape[0]
        ps = jax.lax.dynamic_slice(pipe, (0, j0), (n_fp, L))  # (n_fp, L)
    if planes and any(not plane_native(grp[0]) for grp in groups):
        # product-family members can't contract packed planes; V = Σ plane_t
        # is exact, so they ride the SAME ring payload at full precision
        from repro.kernels.mgemm_levels import values_from_planes

        W_ps = values_from_planes(ps)
        W_left = values_from_planes(left)
        W_right = W_left if right is left else values_from_planes(right)

    # one contraction per family, all partials fused into a single psum
    parts, needs_of = [], []
    for g, grp in enumerate(groups):
        ex = execs[g][0]
        if planes and not plane_native(grp[0]):
            ops = (W_ps, W_left, W_right)
        else:
            ops = (ps, left, right)
        B = ex.threeway_slice(*ops)
        needs = any(s.needs_pair_terms for s in grp)
        needs_of.append(needs)
        parts.append(B)
        if needs:
            parts.append(ex.pair_numerator(ops[0], ops[1]))  # (L, m)
            parts.append(ex.pair_numerator(ops[0], ops[2]))  # (L, m)
            parts.append(ex.pair_numerator(ops[1], ops[2]))  # (m, m)
    parts = jax.lax.psum(tuple(parts), "pf")

    # unpack per group: (B, n2_pl, n2_pr, n2_lr) with None where unneeded
    group_res, cursor = [], 0
    for g in range(len(groups)):
        if needs_of[g]:
            group_res.append(tuple(parts[cursor:cursor + 4]))
            cursor += 4
        else:
            group_res.append((parts[cursor], None, None, None))
            cursor += 1

    if deferred:
        zero_lm = jnp.zeros((L, m), jnp.float32)
        zero_mm = jnp.zeros((m, m), jnp.float32)
        return tuple(
            jnp.stack(bufs)
            for bufs in zip(*[
                (
                    B.astype(jnp.float32),
                    zero_lm if pl is None else pl.astype(jnp.float32),
                    zero_lm if pr is None else pr.astype(jnp.float32),
                    zero_mm if lr is None else lr.astype(jnp.float32),
                )
                for B, pl, pr, lr in group_res
            ])
        )

    jg = j0 + jnp.arange(L)  # global-in-block pipeline indices
    li = jnp.arange(m)
    if kind == ItemKind.DIAG:
        mask = (li[None, :, None] < jg[:, None, None]) & (
            li[None, None, :] > jg[:, None, None]
        )
    elif kind == ItemKind.FACE:
        mask = jnp.broadcast_to(
            li[None, None, :] > jg[:, None, None], (L, m, m)
        )
    else:
        mask = jnp.ones((L, m, m), bool)

    outs = []
    for g, grp in enumerate(groups):
        B, n2_pl, n2_pr, n2_lr = group_res[g]
        sp = jax.lax.dynamic_slice(s_p[g], (j0,), (L,))
        for spec in grp:
            use = spec.needs_pair_terms
            c3 = spec.assemble3(
                B,
                n2_pl if use else None,
                n2_pr if use else None,
                n2_lr if use else None,
                sp, s_l[g], s_r[g],
            )
            outs.append(jnp.where(mask, c3, 0).astype(out_dtype))
    return jnp.stack(outs)


def _threeway_program(
    Vl, *, cfg: CometConfig, plan: ThreeWayPlan, stage: int, out_dtype,
    metric: MetricSpec = None, groups=None, deferred: bool = False,
):
    """Per-device program. Vl: (n_f/n_pf, n_vp) values, or — on the plane
    ring (resolved ``encoding == "bitplane"``) — the rank's packed plane
    shard (levels, n_fb/n_pf, n_vp) uint8.  With planes, Phases B and C
    ring-carry the packed payload itself (the same ``ppermute``s, 8 fields
    per byte per plane on the wire) and every pipeline slice is a
    byte-range view fed straight to the level-decomposed kernels — no
    per-slice re-encode.

    ``groups`` (batched campaigns) is the ``group_families`` partition of
    several requested metrics: every item contracts once per family and
    fans out through each member's epilogue, and the output gains a metric
    axis — (slots, M, L, m, m), flattened family order.  When ``groups``
    is None (the sequential API) the single ``metric`` runs as the
    degenerate one-family batch and the metric axis is squeezed away, so
    both entry points share one schedule implementation and the sequential
    output layout is unchanged.  The payload ring is identical either way
    — batching never adds a ppermute; only the (G, m) stat rows scale with
    family count.

    ``deferred=True`` (streamed chunk programs): identical schedule and
    ring, but every item stores its raw fp32 numerator partials — a
    4-tuple of slot buffers, with a leading family axis under ``groups``
    — and the per-vector stat partial is returned alongside, so
    ``repro.stream`` can accumulate across byte-axis chunks and assemble
    once in the cross-shard merge epilogue."""
    squeeze = groups is None
    if squeeze:
        groups = [[metric or CZEKANOWSKI]]
    planes = Vl.ndim == 3  # plane shards are 3-D, value shards 2-D
    n_pv, n_pr, n_st = cfg.n_pv, cfg.n_pr, cfg.n_st
    m = Vl.shape[-1]
    assert m % (6 * n_st) == 0, "n_vp must divide 6*n_st"
    L = m // (6 * n_st)
    n_groups = len(groups)
    n_metrics = sum(len(grp) for grp in groups)
    execs = [
        [TileExecutor(cfg=cfg, metric=s, out_dtype=out_dtype,
                      axis="pf", deferred=deferred) for s in grp]
        for grp in groups
    ]
    slots = plan.slots_per_rank

    pv = jax.lax.axis_index("pv")
    pr = jax.lax.axis_index("pr")
    perm = [((i + 1) % n_pv, i) for i in range(n_pv)]  # receive from upward

    if planes:
        # stats from the exact value reconstruction V = sum_t plane_t
        from repro.kernels.mgemm_levels import values_from_planes

        W = values_from_planes(Vl)
    else:
        W = Vl
    # (G, m): one psummed stat row per family, ring-carried as one array
    s_own = jnp.stack(
        [jax.lax.psum(grp[0].stat(W), "pf") for grp in groups]
    )
    if deferred:
        out0 = (
            jnp.zeros((slots, n_groups, L, m, m), jnp.float32),  # 3-way
            jnp.zeros((slots, n_groups, L, m), jnp.float32),  # pipe x left
            jnp.zeros((slots, n_groups, L, m), jnp.float32),  # pipe x right
            jnp.zeros((slots, n_groups, m, m), jnp.float32),  # left x right
        )
    else:
        out0 = jnp.zeros((slots, n_metrics, L, m, m), out_dtype)

    def j0_of(idx):
        return L * (stage + n_st * idx)

    def slot_of(sb):
        return sb // n_pr + (pr < (sb % n_pr)).astype(sb.dtype if hasattr(sb, "dtype") else jnp.int32)

    def emit(out, sb, execute, thunk):
        """Conditionally compute a slice and store it at this rank's slot."""
        def do(o):
            c3 = thunk()
            if deferred:  # c3 is the raw-partials 4-tuple
                return tuple(
                    jax.lax.dynamic_update_slice(
                        oo, cc[None], (slot_of(sb),) + (0,) * cc.ndim
                    )
                    for oo, cc in zip(o, c3)
                )
            return jax.lax.dynamic_update_slice(
                o, c3[None], (slot_of(sb),) + (0,) * c3.ndim
            )
        return jax.lax.cond(execute, do, lambda o: o, out)

    # ---- Phase A: diagonal-edge block, 6 static slices --------------------
    out = out0
    for s in range(6):
        execute = (s % n_pr) == pr
        out = emit(
            out,
            jnp.int32(s),
            execute,
            lambda s=s: _item_metrics(
                Vl, Vl, Vl, s_own, s_own, s_own, j0_of(s),
                kind=ItemKind.DIAG, L=L, execs=execs, groups=groups,
                out_dtype=out_dtype, deferred=deferred,
            ),
        )

    # ---- Phase B: face blocks, ring over dj -------------------------------
    def face_body(dj, carry):
        bufj, sbj, out = carry
        bufj = jax.lax.ppermute(bufj, "pv", perm)
        sbj = jax.lax.ppermute(sbj, "pv", perm)
        for s in range(6):  # pipe = right = J; left = own
            sb = 6 + s * (n_pv - 1) + (dj - 1)
            execute = (sb % n_pr) == pr
            out = emit(
                out,
                sb,
                execute,
                lambda s=s, bufj=bufj, sbj=sbj: _item_metrics(
                    bufj, Vl, bufj, sbj, s_own, sbj, j0_of(s),
                    kind=ItemKind.FACE, L=L, execs=execs, groups=groups,
                    out_dtype=out_dtype, deferred=deferred,
                ),
            )
        return bufj, sbj, out

    bufj, sbj, out = jax.lax.fori_loop(
        1, n_pv, face_body, (Vl, s_own, out)
    ) if n_pv > 1 else (Vl, s_own, out)
    # realign bufj to own block (it has advanced n_pv - 1 steps)
    if n_pv > 1:
        bufj = jax.lax.ppermute(bufj, "pv", perm)
        sbj = jax.lax.ppermute(sbj, "pv", perm)

    # ---- Phase C: volume blocks, doubly-nested ring (Algorithm 2) ---------
    sb_base = 6 + 6 * (n_pv - 1)

    def vol_inner(dj, carry):
        dk, bufk, sbk, bufj, sbj, sb, out = carry
        bufj = jax.lax.ppermute(bufj, "pv", perm)
        sbj = jax.lax.ppermute(sbj, "pv", perm)
        is_item = dj != dk
        execute = jnp.logical_and(is_item, (sb % n_pr) == pr)

        def thunk(bufk=bufk, sbk=sbk, bufj=bufj, sbj=sbj):
            bj_id = jnp.remainder(pv + dj, n_pv)
            bk_id = jnp.remainder(pv + dk, n_pv)
            axis, idx = _vol_rule_traced(pv, bj_id, bk_id)
            j0 = L * (stage + n_st * idx)
            # roles by sliced axis: 0 -> own, 1 -> J, 2 -> K is the pipe
            pipe, s_p = (
                jax.lax.switch(
                    axis,
                    [
                        lambda: (Vl, s_own),
                        lambda: (bufj, sbj),
                        lambda: (bufk, sbk),
                    ],
                )
            )
            left, s_l = jax.lax.switch(
                axis,
                [lambda: (bufj, sbj), lambda: (Vl, s_own), lambda: (Vl, s_own)],
            )
            right, s_r = jax.lax.switch(
                axis,
                [lambda: (bufk, sbk), lambda: (bufk, sbk), lambda: (bufj, sbj)],
            )
            return _item_metrics(
                pipe, left, right, s_p, s_l, s_r, j0,
                kind=ItemKind.VOL, L=L, execs=execs, groups=groups,
                out_dtype=out_dtype, deferred=deferred,
            )

        out = emit(out, sb, execute, thunk)
        sb = sb + is_item.astype(sb.dtype)
        return dk, bufk, sbk, bufj, sbj, sb, out

    def vol_outer(dk, carry):
        bufk, sbk, bufj, sbj, sb, out = carry
        bufk = jax.lax.ppermute(bufk, "pv", perm)
        sbk = jax.lax.ppermute(sbk, "pv", perm)
        dk_, bufk, sbk, bufj, sbj, sb, out = jax.lax.fori_loop(
            1, n_pv, vol_inner, (dk, bufk, sbk, bufj, sbj, sb, out)
        )
        # realign bufj to own block
        bufj = jax.lax.ppermute(bufj, "pv", perm)
        sbj = jax.lax.ppermute(sbj, "pv", perm)
        return bufk, sbk, bufj, sbj, sb, out

    if n_pv > 1:
        _, _, _, _, _, out = jax.lax.fori_loop(
            1, n_pv, vol_outer,
            (Vl, s_own, bufj, sbj, jnp.int32(sb_base), out),
        )
    if deferred:
        if squeeze:  # drop the one-family axis (sequential streamed API)
            out = tuple(o[:, 0] for o in out)
            return tuple(o[None, None] for o in out) + (s_own[0][None],)
        return tuple(o[None, None] for o in out) + (s_own[None],)
    if squeeze:  # drop the one-metric axis (sequential API layout)
        out = out[:, 0]
    return out[None, None]


@dataclass
class ThreeWayOutput:
    blocks: np.ndarray  # (n_pv, n_pr, slots, L, m, m)
    plan: ThreeWayPlan
    n_v: int
    n_vp: int
    stage: int
    #: (raw checksum total, result count) folded from the device partials
    #: of the blocks (``ck.partials_program``), or None: read on the host
    device_raw: tuple = None
    #: the contraction path the campaign's ``TileExecutor`` resolved
    #: (``TileExecutor.path3``, e.g. "fused-levels-ring"), or None where
    #: no campaign produced the blocks (``load()``)
    path: str = None

    def entries(self):
        """Yield (i, j, k, value) for every unique computed triple.

        Each item's index and value gather is an ``entries`` span, closed
        before the item is yielded."""
        n_pv, n_pr = self.plan.n_pv, self.plan.n_pr
        L = self.blocks.shape[3]
        li = np.arange(self.n_vp)
        for p_v in range(n_pv):
            for p_r in range(n_pr):
                items = self.plan.items_of(p_v, p_r)
                assert len(items) <= self.blocks.shape[2]
                for slot, it in enumerate(items):
                    with obs.span("entries"):
                        entry = self._item_entries(p_v, p_r, slot, it, L, li)
                    if entry is not None:
                        yield entry

    def _item_entries(self, p_v, p_r, slot, it, L, li):
        """(i, j, k, value) arrays of one computed item, or None when it
        holds no triple below ``n_v``."""
        m = self.n_vp
        pipe_b, left_b, right_b = _item_roles(it, p_v, self.plan.n_pv)
        lo, _ = self.plan.sixth_bounds(m, it.slice_idx, self.stage)
        jg = lo + np.arange(L)
        vals = self.blocks[p_v, p_r, slot]  # (L, m, m)
        if it.kind == ItemKind.DIAG:
            mask = (li[None, :, None] < jg[:, None, None]) & (
                li[None, None, :] > jg[:, None, None]
            )
        elif it.kind == ItemKind.FACE:
            mask = np.broadcast_to(
                li[None, None, :] > jg[:, None, None], vals.shape
            )
        else:
            mask = np.ones(vals.shape, bool)
        T, Ll, R = np.meshgrid(jg, li, li, indexing="ij")
        gi = pipe_b * m + T
        gj = left_b * m + Ll
        gk = right_b * m + R
        mask = mask & (gi < self.n_v) & (gj < self.n_v) & (gk < self.n_v)
        if not mask.any():
            return None
        return gi[mask], gj[mask], gk[mask], vals[mask]

    def dense(self) -> np.ndarray:
        out = np.zeros((self.n_v,) * 3, self.blocks.dtype)
        for I, J, K, V in self.entries():
            idx = np.sort(np.stack([I, J, K]), axis=0)
            out[idx[0], idx[1], idx[2]] = V
        return out

    def checksum(self) -> int:
        return ck.combine([ck.raw_triples(I, J, K, V) for I, J, K, V in self.entries()])

    def num_triples(self) -> int:
        return sum(len(I) for I, _, _, _ in self.entries())


def _item_roles(it, p_v: int, n_pv: int) -> tuple[int, int, int]:
    """(pipe, left, right) block ids of an item: the blocks its (L, m, m)
    slot's pipeline, row and column axes index."""
    own, bj, bk = it.blocks(p_v, n_pv)
    if it.kind == ItemKind.DIAG:
        return own, own, own
    if it.kind == ItemKind.FACE:
        return bj, own, bj
    return ((own, bj, bk), (bj, own, bk), (bk, own, bj))[it.slice_axis]


def checksum_slots(plan: ThreeWayPlan, n_vp: int, n_v: int,
                   stage: int) -> np.ndarray:
    """(n_pv, n_pr, slots, 8) uint32 descriptors of one stage's output
    slots for the device checksum (``ck.partials_program``): the pipe,
    left and right block offsets, the sixth's start, whether rows must lie
    below the pipe index (DIAG) and columns above it (DIAG, FACE), whether
    the slot was computed, and ``n_v``; the masks of
    ``ThreeWayOutput._item_entries``."""
    desc = np.zeros((plan.n_pv, plan.n_pr, plan.slots_per_rank, 8),
                    np.uint32)
    for p_v in range(plan.n_pv):
        for p_r in range(plan.n_pr):
            for slot, it in enumerate(plan.items_of(p_v, p_r)):
                pipe, left, right = _item_roles(it, p_v, plan.n_pv)
                lo, _ = plan.sixth_bounds(n_vp, it.slice_idx, stage)
                desc[p_v, p_r, slot] = (
                    pipe * n_vp, left * n_vp, right * n_vp, lo,
                    it.kind == ItemKind.DIAG, it.kind != ItemKind.VOL, 1, n_v)
    return desc


def _prep_payload3(V, cfg: CometConfig, metric: MetricSpec):
    """Resolve the config against V and build the sharded 3-way payload.

    Shared by the sequential and batched entry points (identical payload
    bytes either way).  Returns ``(cfg, arg, in_specs, n_vp, n_v)``.

    With the resolved ``encoding == "bitplane"`` the campaign encodes
    packed bit-planes ONCE here and the doubly-nested ring carries THEM
    through Phases B/C (for {0,1,2} SNP data 1/16 of the fp32 wire
    volume; see docs/BITPLANE_FORMAT.md) — otherwise the ring carries
    values (int8 auto-selection still quarters the fp32 wire traffic).

    Algorithm 3's pipeline geometry needs the per-rank block size to split
    into 6 sixths x n_st stages: round n_vp up to a multiple of 6*n_st and
    zero-pad.  All pad columns land at the global tail, so global index ==
    padded column index and entries() masks them with < n_v.
    """
    from repro.kernels.mgemm_levels.planes import PackedPlanes, pad_planes

    from repro.core.twoway import resolve_config

    unit = 6 * cfg.n_st
    if isinstance(V, PackedPlanes):
        n_v = V.n_v
        cfg = resolve_config(cfg, V, metric)  # always "bitplane" (or raises)
        n_vp = -(-n_v // cfg.n_pv)
        n_vp += (-n_vp) % unit
        Pp = pad_planes(V.planes, byte_align=cfg.n_pf, n_v=cfg.n_pv * n_vp)
        with obs.span("stage"):
            arg = jnp.asarray(Pp)
        return cfg, arg, P(None, "pf", "pv"), n_vp, n_v
    n_v = V.shape[1]
    with obs.span("encode") as sp:
        V = np.asarray(V)
        cfg = resolve_config(cfg, V, metric)
        n_vp = -(-n_v // cfg.n_pv)
        n_vp += (-n_vp) % unit
        fp = (-V.shape[0]) % cfg.n_pf
        Vp = np.pad(V, ((0, fp), (0, cfg.n_pv * n_vp - n_v)))
        if cfg.encoding == "bitplane":
            # field_align pads fields to 8*n_pf so the BYTE axis splits
            # evenly over "pf" (planes.py owns the rule); pad bits are inert
            from repro.kernels.mgemm_levels import encode_bitplanes_np

            host = encode_bitplanes_np(Vp, cfg.levels, field_align=cfg.n_pf)
            dtype, in_specs = None, P(None, "pf", "pv")
        else:
            host = Vp
            dtype, in_specs = jnp.dtype(cfg.ring_dtype), P("pf", "pv")
        sp.add(bytes=int(host.nbytes), levels=int(cfg.levels))
    with obs.span("stage"):
        arg = jnp.asarray(host, dtype=dtype)
    return cfg, arg, in_specs, n_vp, n_v


def threeway_distributed(
    V, mesh: Mesh, cfg: CometConfig, stage: int = 0,
    metric: MetricSpec = None,
) -> ThreeWayOutput:
    """Compute one stage of the unique 3-way metrics of V's columns.

    ``V``: (n_f, n_v) value matrix, or a pre-encoded ``PackedPlanes``
    payload (``repro.store`` zero-encode loading) — re-padded packed, never
    re-encoded on the host."""
    metric = metric or CZEKANOWSKI
    cfg, arg, in_specs, n_vp, n_v = _prep_payload3(V, cfg, metric)
    plan = ThreeWayPlan(cfg.n_pv, cfg.n_pr, cfg.n_st)
    out_dtype = jnp.dtype(cfg.out_dtype)

    fn = _cached_jit(
        ("threeway", mesh, cfg, plan, stage, metric.name, str(out_dtype),
         in_specs),
        lambda: jax.shard_map(
            partial(_threeway_program, cfg=cfg, plan=plan, stage=stage,
                    out_dtype=out_dtype, metric=metric),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P("pv", "pr", None, None, None, None),
            check_vma=False,
        ),
    )
    L = n_vp // (6 * cfg.n_st)
    with obs.span("entries"):
        slots = checksum_slots(plan, n_vp, n_v, stage)
    blocks, raw = _run_program(
        fn, arg,
        (cfg.n_pv, cfg.n_pr, plan.slots_per_rank, L, n_vp, n_vp),
        checksum=checksum_launcher(3, mesh, slots), stage=int(stage),
    )
    return ThreeWayOutput(blocks=blocks, plan=plan, n_v=n_v, n_vp=n_vp,
                          stage=stage, device_raw=raw,
                          path=TileExecutor(cfg=cfg, metric=metric).path3)


def threeway_batched(
    V, mesh: Mesh, cfg: CometConfig, specs, stage: int = 0,
) -> tuple:
    """Batched 3-way campaigns: one tetrahedral traversal, one result per
    metric.

    ``specs``: MetricSpecs sharing the SAME payload ('auto' knobs resolve
    against ``batch_lead(specs)``).  Returns ``(outputs, binfo)``:
    per-spec ``ThreeWayOutput`` in request order, each bit-identical to
    its sequential ``threeway_distributed`` run, plus the per-stage
    ring-traffic accounting (payload hops independent of metric count).
    """
    specs = list(specs)
    cfg, arg, in_specs, n_vp, n_v = _prep_payload3(V, cfg, batch_lead(specs))
    groups = group_families(specs)
    flat = [s for grp in groups for s in grp]
    plan = ThreeWayPlan(cfg.n_pv, cfg.n_pr, cfg.n_st)
    out_dtype = jnp.dtype(cfg.out_dtype)

    names = tuple(tuple(s.name for s in grp) for grp in groups)
    fn = _cached_jit(
        ("threeway-batched", mesh, cfg, plan, stage, names, str(out_dtype),
         in_specs),
        lambda: jax.shard_map(
            partial(_threeway_program, cfg=cfg, plan=plan, stage=stage,
                    out_dtype=out_dtype, groups=groups),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P("pv", "pr", None, None, None, None, None),
            check_vma=False,
        ),
    )
    L = n_vp // (6 * cfg.n_st)
    blocks, _ = _run_program(
        fn, arg,
        (cfg.n_pv, cfg.n_pr, plan.slots_per_rank, len(flat), L, n_vp, n_vp),
        stage=int(stage), metrics=len(flat),
    )
    # every member rides its family lead's slice contraction
    paths = {s.name: TileExecutor(cfg=cfg, metric=grp[0]).path3
             for grp in groups for s in grp}
    by_name = {
        s.name: ThreeWayOutput(
            blocks=np.ascontiguousarray(blocks[:, :, :, i]), plan=plan,
            n_v=n_v, n_vp=n_vp, stage=stage, path=paths[s.name],
        )
        for i, s in enumerate(flat)
    }
    binfo = batch_accounting(
        int(arg.nbytes), cfg, plan, groups, n_vp,
        planes=(arg.ndim == 3), way=3,
    )
    return [by_name[s.name] for s in specs], binfo


def czek3_distributed(
    V: np.ndarray, mesh: Mesh, cfg: CometConfig, stage: int = 0
) -> ThreeWayOutput:
    """Proportional Similarity 3-way campaign (pre-registry entry point)."""
    return threeway_distributed(V, mesh, cfg, stage=stage, metric=CZEKANOWSKI)
