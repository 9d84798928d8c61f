"""TileExecutor — the single tiled hot path under both distributed engines.

Before this layer existed, ``_twoway_program`` / ``_threeway_program`` built
their own contraction pipelines: a plain mGEMM via ``cfg.impl_fn()``, the
metric assembly in XLA *outside* the kernel (one HBM round-trip of every
numerator block), and diagonal blocks computed in full before masking one
triangle with ``jnp.where``.  The executor owns all of that now:

* **Kernel dispatch** across the implementation registry (``xla`` /
  ``pallas`` / ``levels*``) plus the *generated fused paths*: any metric
  with a Pallas-composable ``assemble_tile`` epilogue and a combine-sum
  contraction gets a fused kernel — the VPU kernel of
  ``repro.kernels.mgemm`` under ``impl="pallas"`` (``path ==
  "fused-vpu"``), or the packed bit-plane MXU kernel of
  ``repro.kernels.mgemm_levels`` under ``impl="levels"`` with a
  min-combine metric (``path == "fused-levels"``; for binary campaigns —
  ``levels == 1`` — the popcount bit-GEMM of ``repro.kernels.popgemm``
  serves the same role as ``path == "fused-popcount"``, packed AND +
  popcount with no plane unpack).  Either way the
  numerator tile is divided in VMEM and never written to HBM (paper §3.1's
  epilogue fusion, for every registered metric instead of a hard-coded
  Czekanowski one-off).  ``path`` / ``path_reason`` surface the 2-way
  decision; ``path3`` / ``path3_reason`` the 3-way one, where
  ``"fused-levels-ring"`` additionally means the doubly-nested ring
  carries packed bit-planes end to end (docs/BITPLANE_FORMAT.md) instead
  of values.  See docs/ARCHITECTURE.md for the full fallback matrix.
* **In-kernel symmetry elimination** (paper §5): diagonal blocks run the
  triangular tile schedule — the Pallas grid enumerates only tiles with
  ``tj >= ti`` — replacing compute-both-then-mask.
* **Block padding / tile selection**: operands are padded to tile multiples
  inside the kernels; tile sizes adapt to the block shape (capped at the
  TPU-sized defaults, 8-aligned for the VPU register shape) so interpret
  mode on CPU does not pay for 128x512 padding of a 12-vector test block.

Bit-exactness contract: the fused path performs op-for-op the same fp32
arithmetic as the out-of-kernel assembly (exact integer numerators, then
``assemble_tile`` == ``assemble2`` division), so every campaign checksum is
bit-identical across ``impl="xla"`` and ``impl="pallas"`` on integer data —
verified in tests/distributed_harness.py and tests/test_fused_epilogue.py.

The fused epilogue needs the *complete* numerator at flush time.  When the
contraction is split over ranks (``n_pf > 1``) the levels path now keeps
the fused MXU contraction and runs the kernels with ``epilogue=None`` (raw
fp32 numerator, triangular diagonal schedule preserved), then psums over
"pf" and applies the metric assembly out of kernel — the **merge
epilogue** (``path == "fused-levels"`` with reason ``"n_pf>1 merge
epilogue engaged"``).  The VPU path has no raw-numerator kernel form and
still falls back to unfused.

**Deferred-flush accumulator mode** (``deferred=True``) is the streamed
variant of the same idea (``repro.stream``): blocks emit raw psummed fp32
numerator partials only (``pair_partial``), the host accumulates them
across byte-axis chunks, and ``merge_pair`` / ``merge_three`` apply the
metric assembly once after the last chunk.  Partial numerators and stats
are exact fp32 integers, so chunk-order addition is bit-identical to the
single-pass contraction — the cross-shard merge guarantee
(docs/BITPLANE_FORMAT.md, "Cross-shard merge").
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.metric_spec import CZEKANOWSKI, MetricSpec

__all__ = ["TileExecutor"]

_TILE_ALIGN = 8  # VPU sublane multiple; real TPU tiles stay (8k, 128)-shaped


def _auto_tile(extent: int, cap: int) -> int:
    """Smallest 8-aligned tile covering ``extent``, capped at the default."""
    return int(min(cap, -(-extent // _TILE_ALIGN) * _TILE_ALIGN))


@dataclass(frozen=True)
class TileExecutor:
    """Tile-level kernel dispatch for one (config, metric, out_dtype) triple.

    ``axis`` is the mesh axis numerator partials are psummed over on the
    unfused path ("pf" inside the distributed programs); ``None`` outside
    shard_map (single-process tests).
    """

    cfg: Any  # CometConfig (duck-typed to avoid a core.twoway import cycle)
    metric: MetricSpec = None
    out_dtype: Any = jnp.float32
    axis: Optional[str] = "pf"
    #: deferred-flush accumulator mode (streamed campaigns): blocks emit
    #: raw psummed fp32 numerator partials; the metric assembly waits for
    #: the cross-shard merge epilogue (``merge_pair`` / ``merge_three``)
    deferred: bool = False

    def __post_init__(self):
        if self.metric is None:
            object.__setattr__(self, "metric", CZEKANOWSKI)

    # -- dispatch predicates ------------------------------------------------

    def _path_decision(self) -> tuple:
        """(path, reason): which 2-way kernel family serves this executor.

        ``path`` is ``"fused-vpu"`` (combine-sum VPU kernel + in-kernel
        epilogue), ``"fused-levels"`` (bit-plane MXU kernel; epilogue
        in-kernel, or — ``n_pf > 1`` — applied after the psum by the merge
        epilogue), ``"unfused"``, or a ``"streamed-*"`` deferred-flush
        variant.  ``reason`` says why the plain in-kernel epilogue is not
        running (empty on the fully fused paths), so fallbacks and merge
        modes are inspectable (``launch.similarity --dry-run``)."""
        if self.deferred:
            return self._deferred_path()
        if self.metric.assemble_tile is None:
            return "unfused", (
                "metric has no Pallas-composable assemble_tile epilogue"
            )
        if not self.metric.contract_is_combine_sum:
            return "unfused", "metric contraction is not a combine-sum"
        if self.cfg.n_pf > 1:
            if (
                self.cfg.impl == "levels"
                and self.metric.combine is jnp.minimum
            ):
                # raw-numerator kernel form + psum + out-of-kernel assembly:
                # the fused contraction and the triangular diagonal
                # schedule survive the field split
                return self._levels_pair_path(), "n_pf>1 merge epilogue engaged"
            return "unfused", (
                f"n_pf={self.cfg.n_pf} splits the contraction across ranks; "
                "the in-kernel epilogue needs the complete numerator"
            )
        if self.cfg.impl == "pallas":
            return "fused-vpu", ""
        if self.cfg.impl == "levels":
            if self.metric.combine is not jnp.minimum:
                return "unfused", (
                    "level decomposition is exact only for combine == min"
                )
            return self._levels_pair_path(), ""
        return "unfused", f"impl={self.cfg.impl!r} has no fused kernel"

    def _levels_pair_path(self) -> str:
        """Which plane kernel family serves ``impl="levels"`` 2-way blocks:
        for binary data (``levels == 1``) the single plane is the data and
        min == AND, so the popcount bit-GEMM replaces the bf16 plane dots —
        same wire format, same epilogue, no unpack."""
        return "fused-popcount" if self.cfg.levels == 1 else "fused-levels"

    def _deferred_path(self) -> tuple:
        """Path naming for deferred-flush (streamed) executors: chunks emit
        raw fp32 numerator partials either way; the name says which
        contraction kernel produces them."""
        if (
            self.cfg.impl == "levels"
            and self.metric.contract_is_combine_sum
            and self.metric.combine is jnp.minimum
        ):
            return "streamed-" + self._levels_pair_path(), (
                "deferred flush: cross-shard merge epilogue assembles "
                "after the last chunk"
            )
        return "streamed-unfused", (
            "deferred flush: raw partials accumulated across chunks, "
            f"impl={self.cfg.impl!r} contraction"
        )

    @property
    def path(self) -> str:
        """'fused-popcount' | 'fused-levels' | 'fused-vpu' | 'unfused' for
        2-way blocks (plus the 'streamed-*' deferred-flush variants)."""
        return self._path_decision()[0]

    @property
    def path_reason(self) -> str:
        """Why fusion was declined ('' when a fused path is active)."""
        return self._path_decision()[1]

    @property
    def fused(self) -> bool:
        """True when 2-way blocks run a fused Pallas contraction kernel
        (epilogue in-kernel, or deferred to the merge epilogue)."""
        return "unfused" not in self.path

    def _path3_decision(self) -> tuple:
        """(path, reason) for the 3-way pipeline slice.  Unlike 2-way, no
        ``n_pf`` condition: the slice kernel emits a non-psummed numerator
        and the assembly runs outside the kernel either way.

        ``"fused-levels-ring"`` is the end-to-end plane campaign: the 3-way
        doubly-nested ring carries packed uint8 planes (encoded once before
        ``shard_map``) and every slice kernel reads them directly.  Plain
        ``"fused-levels"`` means the same slice kernel but a value ring —
        planes re-encoded per pipeline slice (``encoding="none"`` opt-out,
        or an executor built from an unresolved config).  Deferred
        executors prefix the same names with ``"streamed-"`` (raw partials
        accumulated across chunks, assembly in the merge epilogue)."""
        if self.deferred:
            base, _ = self._path3_base()
            return "streamed-" + base, (
                "deferred flush: cross-shard merge epilogue assembles "
                "after the last chunk"
            )
        return self._path3_base()

    def _path3_base(self) -> tuple:
        if not self.metric.contract_is_combine_sum:
            return "unfused", "metric contraction is not a combine-sum"
        if self.cfg.impl == "pallas":
            return "fused-vpu", ""
        if self.cfg.impl == "levels":
            if self.metric.combine is not jnp.minimum:
                return "unfused", (
                    "level decomposition is exact only for combine == min"
                )
            base = self._levels_pair_path()  # popcount when levels == 1
            if self.cfg.encoding == "bitplane":
                return base + "-ring", ""
            return base, (
                f"encoding={self.cfg.encoding!r}: ring carries "
                f"{self.cfg.ring_dtype} values, planes encoded per slice"
            )
        return "unfused", f"impl={self.cfg.impl!r} has no fused kernel"

    @property
    def path3(self) -> str:
        """'fused-popcount-ring' | 'fused-popcount' | 'fused-levels-ring' |
        'fused-levels' | 'fused-vpu' | 'unfused' for 3-way slices."""
        return self._path3_decision()[0]

    @property
    def path3_reason(self) -> str:
        return self._path3_decision()[1]

    @property
    def fused3(self) -> bool:
        """True when 3-way pipeline steps run a fused X_j Pallas kernel."""
        return "unfused" not in self.path3


    # -- internals ----------------------------------------------------------

    def _psum(self, x):
        return jax.lax.psum(x, self.axis) if self.axis is not None else x

    def contract(self, A, B):
        """Numerator contraction via the metric's registry dispatch."""
        return self.metric.contract_fn(self.cfg)(A, B)

    # -- 2-way --------------------------------------------------------------

    def _pair_planes(self, Va, Vb):
        """Packed bit-planes of the two operand blocks.

        Accepts either pre-encoded planes (3-D uint8 — the campaign path,
        where encoding happened once before the ring) or raw field-major
        value blocks (standalone/benchmark calls), encoded on the fly."""
        from repro.kernels.mgemm_levels import encode_bitplanes

        if Va.ndim == 3:
            return Va, Vb
        Pa = encode_bitplanes(Va, self.cfg.levels)
        Pb = Pa if Vb is Va else encode_bitplanes(Vb, self.cfg.levels)
        return Pa, Pb

    def pair_block(self, Va, sa, Vb, sb, *, diagonal: bool = False):
        """One (m, n) block of 2-way metric values.

        Va / Vb are field-major vector blocks — (n_fp, m) / (n_fp, n) values,
        or (levels, kb, m) / (levels, kb, n) packed bit-planes when the
        campaign pre-encoded them (``cfg.encoding == "bitplane"``, resolved
        by ``core.twoway.resolve_config``).  sa / sb the psummed
        per-vector stats.  ``diagonal`` marks Va and Vb as the same block:
        only the strict upper triangle is returned (zeros elsewhere),
        computed on the triangular tile schedule on the fused paths.
        """
        m = Va.shape[-1]
        n = Vb.shape[-1]
        path = self.path
        if path == "fused-vpu":
            # late import: kernels register against core.mgemm at import time
            from repro.kernels.mgemm import (
                metric2_tiles,
                metric2_tri,
                unpack_tri_tiles,
            )
            from repro.kernels.mgemm.kernel import (
                DEFAULT_BK,
                DEFAULT_BM,
                DEFAULT_BN,
            )

            k = Va.shape[0]
            kw = dict(
                combine=self.metric.combine,
                epilogue=self.metric.assemble_tile,
                bk=_auto_tile(k, DEFAULT_BK),
                out_dtype=jnp.dtype(self.out_dtype),
            )
            if diagonal:
                bt = _auto_tile(m, DEFAULT_BM)
                packed = metric2_tri(Va.T, Vb, sa, sb, bt=bt, **kw)
                return unpack_tri_tiles(packed, m, bt)
            return metric2_tiles(
                Va.T, Vb, sa, sb,
                bm=_auto_tile(m, DEFAULT_BM), bn=_auto_tile(n, DEFAULT_BN),
                **kw,
            )
        if path in ("fused-levels", "fused-popcount"):
            from repro.kernels.mgemm import unpack_tri_tiles

            if path == "fused-popcount":
                # binary fast path: packed AND + popcount, no plane unpack
                from repro.kernels.popgemm import (
                    metric2_pop as metric2_fn,
                    metric2_pop_tri as metric2_tri_fn,
                )
                from repro.kernels.popgemm.kernel import (
                    DEFAULT_BKB,
                    DEFAULT_BM as LEVELS_BM,
                    DEFAULT_BN as LEVELS_BN,
                )
            else:
                from repro.kernels.mgemm_levels import (
                    metric2_levels as metric2_fn,
                    metric2_levels_tri as metric2_tri_fn,
                )
                from repro.kernels.mgemm_levels.kernel import (
                    DEFAULT_BKB,
                    DEFAULT_BM as LEVELS_BM,
                    DEFAULT_BN as LEVELS_BN,
                )

            # n_pf > 1: the kernels run with ``epilogue=None`` (raw fp32
            # numerator, triangular diagonal schedule preserved) and the
            # merge epilogue — psum over "pf", then the SAME assemble2 ops
            # as the unfused path — flushes out of kernel.
            merge = self.cfg.n_pf > 1
            Pa, Pb = self._pair_planes(Va, Vb if not diagonal else Va)
            kw = dict(
                epilogue=None if merge else self.metric.assemble_tile,
                bkb=max(1, min(DEFAULT_BKB, Pa.shape[1])),
                out_dtype=jnp.float32 if merge
                else jnp.dtype(self.out_dtype),
            )
            if diagonal:
                bt = _auto_tile(m, LEVELS_BM)
                packed = metric2_tri_fn(Pa, sa, bt=bt, **kw)
                vals = unpack_tri_tiles(packed, m, bt)
            else:
                vals = metric2_fn(
                    Pa, Pb, sa, sb,
                    bm=_auto_tile(m, LEVELS_BM), bn=_auto_tile(n, LEVELS_BN),
                    **kw,
                )
            if merge:
                vals = self.merge_pair(
                    self._psum(vals), sa, sb, diagonal=diagonal
                )
            return vals
        # unfused: contraction (registry impl, or the hoisted plane
        # contraction when the campaign pre-encoded bit-planes) + psum +
        # out-of-kernel assembly — op-for-op the pre-executor arithmetic.
        if Va.ndim == 3:
            n2 = self._contract_planes(Va, Vb)
        else:
            n2 = self.contract(Va.T, Vb)
        n2 = self._psum(n2.astype(jnp.float32))
        vals = self.metric.assemble2(n2, sa[:, None], sb[None, :]).astype(
            self.out_dtype
        )
        if diagonal:
            tri = jnp.triu(jnp.ones((m, n), bool), k=1)
            vals = jnp.where(tri, vals, 0)
        return vals

    def pair_raw(self, Va, sa, Vb, sb, *, diagonal: bool = False):
        """Raw psummed fp32 numerator of one 2-way block — the batched-
        campaign contraction primitive.

        One call produces the COMPLETE numerator a whole metric *family*
        shares; the batched programs then fan it out through each member's
        ``merge_pair`` epilogue (same ``assemble2`` fp ops as the in-kernel
        ``assemble_tile``, so batched values stay bit-identical to the
        sequential run).  On the levels paths this is exactly the
        ``n_pf > 1`` merge-epilogue contraction: the fused kernels run with
        ``epilogue=None`` and the triangular diagonal schedule preserved.
        Product-family metrics riding a plane ring reconstruct exact values
        via ``values_from_planes`` first (integer sums stay below the fp32
        mantissa limit, so this is lossless).
        """
        if diagonal:
            Vb = Va
        if Va.ndim == 3 and not (
            self.metric.contract_is_combine_sum
            and self.metric.combine is jnp.minimum
        ):
            # plane payload, non-min metric (e.g. CCC): V = Σ plane_t exactly
            from repro.kernels.mgemm_levels import values_from_planes

            Wa = values_from_planes(Va)
            Wb = Wa if Vb is Va else values_from_planes(Vb)
            return self._psum(
                self.contract(Wa.T, Wb).astype(jnp.float32)
            )
        path = self.path
        if path in ("fused-levels", "fused-popcount"):
            from repro.kernels.mgemm import unpack_tri_tiles

            if path == "fused-popcount":
                from repro.kernels.popgemm import (
                    metric2_pop as metric2_fn,
                    metric2_pop_tri as metric2_tri_fn,
                )
                from repro.kernels.popgemm.kernel import (
                    DEFAULT_BKB,
                    DEFAULT_BM as LEVELS_BM,
                    DEFAULT_BN as LEVELS_BN,
                )
            else:
                from repro.kernels.mgemm_levels import (
                    metric2_levels as metric2_fn,
                    metric2_levels_tri as metric2_tri_fn,
                )
                from repro.kernels.mgemm_levels.kernel import (
                    DEFAULT_BKB,
                    DEFAULT_BM as LEVELS_BM,
                    DEFAULT_BN as LEVELS_BN,
                )

            m = Va.shape[-1]
            n = Vb.shape[-1]
            Pa, Pb = self._pair_planes(Va, Vb)
            kw = dict(
                epilogue=None,
                bkb=max(1, min(DEFAULT_BKB, Pa.shape[1])),
                out_dtype=jnp.float32,
            )
            if diagonal:
                bt = _auto_tile(m, LEVELS_BM)
                raw = unpack_tri_tiles(metric2_tri_fn(Pa, sa, bt=bt, **kw), m, bt)
            else:
                raw = metric2_fn(
                    Pa, Pb, sa, sb,
                    bm=_auto_tile(m, LEVELS_BM), bn=_auto_tile(n, LEVELS_BN),
                    **kw,
                )
            return self._psum(raw)
        return self._psum(self.pair_numerator(Va, Vb).astype(jnp.float32))

    def pair_partial(self, Va, Vb):
        """Deferred-flush block contraction: the raw fp32 numerator partial
        psummed over the contraction axis — what streamed chunk programs
        emit instead of assembled metric values.  Partials are exact fp32
        integers for leveled data, so host-side accumulation across chunks
        commutes bit-for-bit with the single-pass contraction."""
        return self._psum(self.pair_numerator(Va, Vb).astype(jnp.float32))

    # -- merge epilogue (deferred flush / n_pf > 1) --------------------------

    def merge_pair(self, n2, sa, sb, *, diagonal: bool = False):
        """Assemble one 2-way block from a COMPLETE numerator: the same
        ``assemble2`` arithmetic the unfused path runs after its psum, plus
        the diagonal strict-upper mask.  Called in-program on the n_pf > 1
        merge path and on the host by ``repro.stream`` after the last
        chunk's partials have been accumulated."""
        n2 = jnp.asarray(n2, jnp.float32)
        vals = self.metric.assemble2(
            n2, jnp.asarray(sa)[:, None], jnp.asarray(sb)[None, :]
        ).astype(self.out_dtype)
        if diagonal:
            m, n = vals.shape
            tri = jnp.triu(jnp.ones((m, n), bool), k=1)
            vals = jnp.where(tri, vals, 0)
        return vals

    def merge_three(self, B, n2_pl, n2_pr, n2_lr, sp, sl, sr):
        """Assemble one 3-way slice from complete numerators (the streamed
        twin of the in-program ``metric.assemble3`` call); masking is the
        caller's job — it depends on the plan item's kind."""
        B = jnp.asarray(B, jnp.float32)
        if n2_pl is not None:
            n2_pl = jnp.asarray(n2_pl, jnp.float32)
            n2_pr = jnp.asarray(n2_pr, jnp.float32)
            n2_lr = jnp.asarray(n2_lr, jnp.float32)
        return self.metric.assemble3(
            B, n2_pl, n2_pr, n2_lr,
            jnp.asarray(sp), jnp.asarray(sl), jnp.asarray(sr),
        ).astype(self.out_dtype)

    def pair_numerator(self, Va, Vb):
        """Raw (m, n) pairwise numerator block, NOT psummed.

        Accepts (k, m)/(k, n) field-major values or (levels, kb, m)/
        (levels, kb, n) packed bit-planes (docs/BITPLANE_FORMAT.md) — the
        3-way engine calls this for the pairwise terms of the metric
        assembly, so the plane ring serves them without decoding."""
        if Va.ndim == 3:
            return self._contract_planes(Va, Vb)
        return self.contract(Va.T, Vb)

    def _contract_planes(self, Pa, Pb):
        """Unfused numerator from pre-encoded planes: the per-ring-step
        ``(V >= t)`` indicator construction is gone from the hot loop.
        Binary planes (``levels == 1``) contract via the popcount bit-GEMM
        — this one routing point serves ``pair_partial`` (streamed chunks),
        ``pair_numerator`` (3-way pair terms), and the unfused-plane 3-way
        slice alike."""
        if self.cfg.impl == "levels":
            if self.cfg.levels == 1:
                from repro.kernels.popgemm import pop_planes
                from repro.kernels.popgemm.kernel import (
                    DEFAULT_BKB as POP_BKB,
                )

                return pop_planes(
                    Pa, Pb, bkb=max(1, min(POP_BKB, Pa.shape[1]))
                )
            from repro.kernels.mgemm_levels import mgemm_levels_planes
            from repro.kernels.mgemm_levels.kernel import DEFAULT_BKB

            return mgemm_levels_planes(
                Pa, Pb, bkb=max(1, min(DEFAULT_BKB, Pa.shape[1]))
            )
        from repro.kernels.mgemm_levels import mgemm_levels_planes_xla

        return mgemm_levels_planes_xla(Pa, Pb)

    # -- 3-way --------------------------------------------------------------

    def threeway_slice(self, ps, left, right):
        """Batched 3-way numerator B[t, l, r] = Σ_q combine(ps_t, left_l,
        right_r) for one pipeline slice.  NOT psummed — the caller fuses the
        psum with the pairwise terms into one collective.

        Operands are (n_fp, ·) field-major value blocks, or — on the plane
        ring (``path3 == "fused-levels-ring"``, and the unfused plane
        contraction under ``impl="levels_xla"``) — (levels, kb, ·) packed
        uint8 bit-planes exactly as ring-carried (docs/BITPLANE_FORMAT.md);
        the per-slice re-encode only runs when values arrive with
        ``impl="levels"`` (``encoding="none"`` opt-out).

        Fused path: one batched ``threeway_batch`` launch (the pipeline axis
        is a kernel grid dimension, so trace/compile cost is O(1) in L), the
        X_j = combine(left, ps_t) tiles built in VMEM (never HBM).  Unfused:
        the pipeline axis folds into the GEMM M dimension (one batched
        contraction), exactly the pre-executor formulation.
        """
        planes = ps.ndim == 3
        L = ps.shape[-1]
        m = left.shape[-1]
        n = right.shape[-1]
        if self.fused3:
            from repro.kernels.czek3 import threeway_batch
            from repro.kernels.czek3.kernel import (
                DEFAULT_BK,
                DEFAULT_BKB,
                DEFAULT_BM,
                DEFAULT_BN,
            )

            if self.cfg.impl == "levels":
                # level-decomposed slice: X_j is a packed AND of plane
                # bytes, the contraction L MXU dot_generals per K-tile —
                # or, for binary planes, a popcount of the packed AND (the
                # whole slice never unpacks a byte).  On the plane ring the
                # operands arrive pre-encoded.
                if self.cfg.levels == 1:
                    from repro.kernels.popgemm import threeway_batch_pop as batch_fn
                    from repro.kernels.popgemm.kernel import (
                        DEFAULT_BKB,
                        DEFAULT_BM3 as BM3,
                        DEFAULT_BN3 as BN3,
                    )
                else:
                    from repro.kernels.czek3 import (
                        threeway_batch_levels as batch_fn,
                    )

                    BM3, BN3 = DEFAULT_BM, DEFAULT_BN
                if planes:
                    Pl, Pp, Pr = left, ps, right
                else:
                    from repro.kernels.mgemm_levels import encode_bitplanes

                    lv = self.cfg.levels
                    Pl = encode_bitplanes(left, lv)
                    Pp = encode_bitplanes(ps, lv)
                    Pr = Pl if right is left else encode_bitplanes(right, lv)
                return batch_fn(
                    Pl, Pp, Pr,
                    bm=_auto_tile(m, BM3),
                    bn=_auto_tile(n, BN3),
                    bkb=max(1, min(DEFAULT_BKB, Pl.shape[1])),
                )
            return threeway_batch(
                left, ps, right,
                combine=self.metric.combine,
                bm=_auto_tile(m, DEFAULT_BM),
                bn=_auto_tile(n, DEFAULT_BN),
                bk=_auto_tile(ps.shape[0], DEFAULT_BK),
            )
        if planes:
            # plane of min(left_l, ps_t) == packed AND of the plane bytes;
            # fold the pipeline axis into the GEMM M dimension and run the
            # (unfused) plane contraction — no decode, no re-encode
            levels, kb = ps.shape[:2]
            Xp = (left[:, :, :, None] & ps[:, :, None, :]).reshape(
                levels, kb, m * L
            )
            return self._contract_planes(Xp, right).reshape(
                m, L, n
            ).transpose(1, 0, 2)
        n_fp = ps.shape[0]
        X = self.metric.combine(left[:, :, None], ps[:, None, :]).reshape(
            n_fp, m * L
        )
        return self.contract(X.T, right).reshape(m, L, n).transpose(1, 0, 2)
