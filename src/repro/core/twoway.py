"""Distributed 2-way Proportional Similarity engine — paper §4.1, Algorithm 1.

SPMD mapping (shard_map over a ("pf", "pv", "pr") mesh):

* V (n_f, n_v) is sharded over "pf" (vector elements) and "pv" (vector
  number), replicated over "pr".
* Ring: at step d, every rank holds block (p_v + d) mod n_pv via
  ``jax.lax.ppermute`` (the paper's pipelined send/recv; XLA's async
  collective-permute scheduler overlaps it with the mGEMM, replacing the
  paper's hand-rolled double buffering).
* Block-circulant schedule: rank row p_v computes block (p_v, p_v + d);
  the final step of an even ring is computed by the lower half only.
* "pr" round-robin: step d executes on ranks with d % n_pr == p_r under
  ``lax.cond`` (compute genuinely skipped, not masked).
* "pf" reduction: numerator partials are ``psum`` over "pf"; row-sum
  denominators are psummed once and ring-carried alongside V.

Per-block compute is owned by the ``TileExecutor`` (kernel dispatch, fused
metric epilogues, triangular diagonal-block schedule) — see
``repro.core.tile_executor``.

Bit-exactness contract (paper §5): with integer-valued inputs every
numerator is an exact fp integer regardless of summation order, so any
(n_pf, n_pv, n_pr) decomposition — and any executor path — produces
bit-identical metric values, verified by checksum in
tests/distributed_harness.py.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import checksum as ck
from repro.obs import trace as obs
from repro.obs.metrics import default_registry
from repro.core.metric_spec import (
    CZEKANOWSKI,
    MetricSpec,
    batch_lead,
    group_families,
)
from repro.core.mgemm import get_impl
from repro.core.plan2 import TwoWayPlan, global_pairs_of_block
from repro.core.tile_executor import TileExecutor

__all__ = [
    "CometConfig",
    "TwoWayOutput",
    "twoway_distributed",
    "twoway_batched",
    "czek2_distributed",
    "pad_vectors",
    "resolve_config",
]


@dataclass(frozen=True)
class CometConfig:
    """Decomposition + implementation knobs (paper's n_pf / n_pv / n_pr / n_st)."""

    n_pf: int = 1
    n_pv: int = 1
    n_pr: int = 1
    n_st: int = 1  # 3-way staging
    impl: str = "xla"  # mgemm implementation registry key
    levels: int = 2  # for impl='levels*'
    out_dtype: str = "float32"
    # ring payload dtype (beyond-paper §Perf): int8 quarters the ICI wire
    # traffic of the V ring — EXACT for integer data with values <= 127
    # (SNP {0,1,2} codes); metric math still accumulates in fp32.
    # "auto" (default) selects int8 whenever the input is integer-valued
    # with |values| <= 127, instead of silently ring-carrying fp32; pass
    # ring_dtype="float32" to opt out explicitly.
    ring_dtype: str = "auto"
    # contraction-axis chunk of the XLA mgemm (memory/speed trade-off)
    chunk: int = 128
    # bit-plane pre-encoding for the levels path: "auto" encodes V once
    # into packed uint8 planes (8 plane-bits/byte, docs/BITPLANE_FORMAT.md)
    # and ring-carries THOSE — in BOTH engines, 2-way ring and 3-way
    # doubly-nested ring alike — whenever impl='levels*', the metric
    # combine is min, and the data is integer-valued in [0, levels];
    # "bitplane" forces it (ValueError if ineligible); "none" keeps the
    # value ring with per-step/per-slice (V >= t) construction.
    encoding: str = "auto"
    # out-of-core streaming (repro.stream): "auto" streams store-backed
    # multi-shard datasets (or whenever max_host_bytes is set), "on"
    # forces it (ValueError without a store-backed input), "off" keeps
    # the in-memory single-pass campaign.
    streaming: str = "auto"
    # peak HOST bytes the streamed staging buffers may occupy (0 =
    # unbounded: one disk shard per chunk).  Bounds the double-buffered
    # chunk pipeline, NOT the dataset size — see repro.stream.StreamPlan.
    max_host_bytes: int = 0

    @property
    def n_ranks(self) -> int:
        return self.n_pf * self.n_pv * self.n_pr

    def impl_fn(self):
        fn = get_impl(self.impl)
        if self.impl.startswith("levels"):
            return partial(fn, levels=self.levels)
        if self.impl == "xla":
            return partial(fn, chunk=self.chunk)
        return fn


def pad_vectors(
    V: np.ndarray, cfg: CometConfig, *, field_align: int = 1
) -> np.ndarray:
    """Pad fields to n_pf multiple and vectors to n_pv multiple with zeros.

    Zero padding is inert: pad vectors produce zero numerators and are
    excluded by index bookkeeping on the host side.  ``field_align`` further
    aligns the field count (8*n_pf for the packed bit-plane payload, whose
    byte axis must split evenly over "pf")."""
    n_f, n_v = V.shape
    fp = (-n_f) % (cfg.n_pf * field_align)
    vp = (-n_v) % cfg.n_pv
    if fp or vp:
        V = np.pad(V, ((0, fp), (0, vp)))
    return V


def _values_int8_safe(V: np.ndarray) -> bool:
    """True when ring-carrying V as int8 is value-exact."""
    if V.size == 0:
        return False
    if not np.issubdtype(V.dtype, np.integer):
        if not np.isfinite(V).all() or not (V == np.floor(V)).all():
            return False
    return bool(V.min() >= -128 and V.max() <= 127)


def _values_leveled(V: np.ndarray, levels: int) -> bool:
    """True when V is integer-valued in [0, levels] — the exactness domain
    of the level decomposition AND of the bit-plane encoding."""
    if V.size == 0:
        return False
    if not np.issubdtype(V.dtype, np.integer):
        if not np.isfinite(V).all() or not (V == np.floor(V)).all():
            return False
    return bool(V.min() >= 0 and V.max() <= levels)


def _plane_eligible(cfg: CometConfig, metric: MetricSpec) -> bool:
    """The ONE plane-path eligibility predicate (impl + metric), shared by
    the value and pre-encoded branches of ``resolve_config``."""
    return (
        cfg.impl in ("levels", "levels_xla")
        and metric.combine is jnp.minimum
    )


def _plane_ineligible_msg(prefix: str, cfg: CometConfig, metric: MetricSpec) -> str:
    return (
        f"{prefix} needs impl='levels'/'levels_xla' and a min-combine metric "
        f"(got impl={cfg.impl!r}, metric={metric.name!r})"
    )


def resolve_config(
    cfg: CometConfig, V, metric: MetricSpec
) -> CometConfig:
    """Resolve the 'auto' knobs (ring_dtype, encoding) against actual data.

    The distributed entry points call this once per campaign, so the device
    programs and the TileExecutor only ever see concrete settings.

    ``V`` may be a value matrix, a pre-encoded ``PackedPlanes`` payload
    (``repro.store`` campaign loading), or a LAZY ``ShardedPlanes`` handle
    (``DatasetReader.sharded()`` — the streamed campaign's input, which
    shares every plane-path eligibility rule without materializing a
    byte).  Pre-encoded input HAS no value form on the host, so it must
    resolve to the plane path: eligibility failures (impl / metric /
    levels mismatch, explicit ``encoding="none"``) raise instead of
    falling back.

    The ``streaming`` knob resolves here too (this is the one place
    eligibility rules live): "auto" -> "on" for a lazy store handle with
    multiple shards or an explicit ``max_host_bytes`` budget, "off"
    otherwise; "on" without store-backed input raises — a value matrix is
    already resident, there is nothing to stream."""
    from dataclasses import replace

    from repro.kernels.mgemm_levels.planes import PackedPlanes
    from repro.store.reader import ShardedPlanes

    if cfg.streaming not in ("auto", "on", "off"):
        raise ValueError(
            f"streaming must be 'auto', 'on' or 'off', got {cfg.streaming!r}"
        )
    if isinstance(V, ShardedPlanes):
        streaming = cfg.streaming
        if streaming == "auto":
            streaming = "on" if (V.n_shards > 1 or cfg.max_host_bytes > 0) \
                else "off"
        cfg = replace(cfg, streaming=streaming)
    elif cfg.streaming == "on":
        raise ValueError(
            "streaming='on' needs a store-backed dataset input "
            "(InputSpec(source='planes') / DatasetReader.sharded()); "
            "value matrices and materialized PackedPlanes are already "
            "resident in host memory"
        )
    else:
        cfg = replace(cfg, streaming="off")

    if isinstance(V, (PackedPlanes, ShardedPlanes)):
        if cfg.encoding == "none":
            raise ValueError(
                "pre-encoded plane input cannot run with encoding='none' "
                "(there are no host-side values to ring-carry) — load the "
                "matrix instead, or drop encoding='none'"
            )
        if not _plane_eligible(cfg, metric):
            raise ValueError(
                _plane_ineligible_msg("pre-encoded plane input", cfg, metric)
            )
        if V.levels != cfg.levels:
            raise ValueError(
                f"dataset is encoded with levels={V.levels}, request says "
                f"levels={cfg.levels}"
            )
        ring = cfg.ring_dtype
        if ring == "auto":  # plane payloads are uint8; value ring unused
            ring = "int8" if cfg.levels <= 127 else "float32"
        return replace(cfg, ring_dtype=ring, encoding="bitplane")

    V = np.asarray(V)
    ring = cfg.ring_dtype
    if ring == "auto":
        ring = "int8" if _values_int8_safe(V) else "float32"
    enc = cfg.encoding
    if enc not in ("auto", "bitplane", "none"):
        raise ValueError(f"unknown encoding {enc!r}")
    if enc != "none":
        eligible = _plane_eligible(cfg, metric)
        leveled = _values_leveled(V, cfg.levels)
        if enc == "bitplane":
            if not eligible:
                raise ValueError(
                    _plane_ineligible_msg("encoding='bitplane'", cfg, metric)
                )
            if not leveled:
                raise ValueError(
                    "encoding='bitplane' needs integer data in "
                    f"[0, levels={cfg.levels}]"
                )
        else:
            enc = "bitplane" if (eligible and leveled) else "none"
    return replace(cfg, ring_dtype=ring, encoding=enc)


@dataclass
class TwoWayOutput:
    """Per-rank metric blocks + the metadata to read them.

    Two storage modes:

    * ``dense`` — ``blocks`` is (n_pv, n_pr, slots, m, m), one full square
      per computed ring step (what the device program emits).
    * ``packed`` — ``blocks`` is (n_pv, n_pr, packed_len): each rank's
      computed steps concatenated, the diagonal block (step 0, where only
      the strict upper triangle carries results) stored as its m(m-1)/2
      packed triangle values and off-diagonal blocks as flat m*m squares.
      The layout is derived from the plan, so nothing beyond the flat array
      needs persisting.  Packing is a HOST-side storage transform (the
      device program still emits dense slots; ``pack()`` converts after the
      transfer), so the saving applies to the retained / persisted result
      buffer — roughly half for diagonal-dominated small-``n_pv`` runs (one
      slot, one diagonal block) — not to peak device memory.
    """

    blocks: np.ndarray
    plan: TwoWayPlan
    n_v: int  # true (unpadded) vector count
    n_vp: int  # padded block size
    storage: str = "dense"  # "dense" | "packed"
    #: (raw checksum total, result count) folded from the device partials
    #: of the blocks (``ck.partials_program``), or None: read on the host
    device_raw: tuple = None
    #: the contraction path the campaign's ``TileExecutor`` resolved
    #: (``TileExecutor.path``, e.g. "fused-popcount"), or None where no
    #: campaign produced the blocks (``load()``)
    path: str = None

    # -- packed layout (deterministic from the plan) -----------------------

    def _packed_layout(self, p_r: int):
        """[(d, offset, size)] for one round-robin rank's packed buffer."""
        m = self.n_vp
        tri = m * (m - 1) // 2
        out, off = [], 0
        for d in self.plan.steps_of_pr(p_r):
            size = tri if d == 0 else m * m
            out.append((d, off, size))
            off += size
        return out

    def _block_values(self, p_v: int, p_r: int, d: int) -> np.ndarray:
        """(m, m) values of the block rank (p_v, p_r) computed at step d."""
        m = self.n_vp
        if self.storage == "dense":
            return self.blocks[p_v, p_r, d // self.plan.n_pr]
        off, size = next(
            (o, s) for dd, o, s in self._packed_layout(p_r) if dd == d
        )
        flat = self.blocks[p_v, p_r, off:off + size]
        if d == 0:
            out = np.zeros((m, m), flat.dtype)
            out[np.triu_indices(m, 1)] = flat
            return out
        return flat.reshape(m, m)

    def pack(self) -> "TwoWayOutput":
        """Convert to packed upper-triangular storage (values unchanged —
        identical entries and checksum, verified in tests)."""
        if self.storage == "packed":
            return self
        m = self.n_vp
        iu = np.triu_indices(m, 1)
        layouts = [self._packed_layout(p_r) for p_r in range(self.plan.n_pr)]
        length = max((lay[-1][1] + lay[-1][2]) if lay else 0 for lay in layouts)
        packed = np.zeros(
            (self.plan.n_pv, self.plan.n_pr, length), self.blocks.dtype
        )
        for p_v in range(self.plan.n_pv):
            for p_r in range(self.plan.n_pr):
                for d, off, size in layouts[p_r]:
                    blk = self.blocks[p_v, p_r, d // self.plan.n_pr]
                    packed[p_v, p_r, off:off + size] = (
                        blk[iu] if d == 0 else blk.ravel()
                    )
        return TwoWayOutput(
            blocks=packed, plan=self.plan, n_v=self.n_v, n_vp=self.n_vp,
            storage="packed", device_raw=self.device_raw, path=self.path,
        )

    @property
    def nbytes(self) -> int:
        return self.blocks.nbytes

    # -- reads --------------------------------------------------------------

    def entries(self):
        """Yield (i, j, value) for every unique computed pair (i < j).

        Each block's index and value gather is an ``entries`` span, closed
        before the block is yielded."""
        n_pv, n_pr = self.plan.n_pv, self.plan.n_pr
        for p_v in range(n_pv):
            for p_r in range(n_pr):
                for d in self.plan.steps_of_pr(p_r):
                    if not self.plan.rank_computes(p_v, p_r, d):
                        continue
                    with obs.span("entries"):
                        row, col = self.plan.block_of(p_v, d)
                        I, J, mask = global_pairs_of_block(row, col,
                                                           self.n_vp)
                        mask = mask & (I < self.n_v) & (J < self.n_v)
                        vals = self._block_values(p_v, p_r, d)
                        entry = I[mask], J[mask], vals[mask]
                    yield entry

    def dense(self) -> np.ndarray:
        """(n_v, n_v) symmetric metric matrix (tests / small problems)."""
        out = np.zeros((self.n_v, self.n_v), self.blocks.dtype)
        for I, J, V in self.entries():
            lo, hi = np.minimum(I, J), np.maximum(I, J)
            out[lo, hi] = V
            out[hi, lo] = V
        return out

    def checksum(self) -> int:
        return ck.combine([ck.raw_pairs(I, J, V) for I, J, V in self.entries()])

    def num_pairs(self) -> int:
        return sum(len(I) for I, _, _ in self.entries())


def checksum_slots(plan: TwoWayPlan, n_vp: int, n_v: int) -> np.ndarray:
    """(n_pv, n_pr, slots, 5) uint32 descriptors of the dense output slots
    for the device checksum (``ck.partials_program``): the block's row and
    column offsets, whether it is diagonal, whether the slot was computed,
    and ``n_v``; the masks of ``global_pairs_of_block`` and ``entries``."""
    desc = np.zeros((plan.n_pv, plan.n_pr, plan.slots_per_rank, 5),
                    np.uint32)
    for p_v in range(plan.n_pv):
        for p_r in range(plan.n_pr):
            for d in plan.steps_of_pr(p_r):
                if plan.rank_computes(p_v, p_r, d):
                    row, col = plan.block_of(p_v, d)
                    desc[p_v, p_r, d // plan.n_pr] = (
                        row * n_vp, col * n_vp, row == col, 1, n_v)
    return desc


#: Compiled-program cache for every campaign program (2-way, 3-way,
#: batched, delta, device checksum).  ``jax.jit`` memoizes per function
#: object, so a fresh ``partial`` per campaign would pay trace, lowering and
#: compile (or a persistent-cache load) on every request.  Keying the jitted
#: callable lets a hot process — and ``SimilarityService.warmup`` — reuse
#: the executable across requests; jit still retraces on a shape change.
#: A key must hold every value its ``build`` closure captures: mesh,
#: resolved cfg, plan, metric name (per-family name tuples for a batch),
#: output dtype, payload kind (``planes`` or its ``in_specs``) and, on
#: 3-way, the ``stage`` the program bakes in.  ``_cached_jit`` adds the
#: ring exchange in force, ``jax.lax.ppermute``: the programs look it up
#: when traced, so a program traced with a stand-in (the benchmark's
#: ``no_exchange`` fault scenario plants one) is never served with the
#: real exchange, nor the other way round.  ``default_registry()`` counts
#: the lookups as ``program_cache.hit`` / ``program_cache.miss``.
_PROGRAM_CACHE: "OrderedDict" = None


def _cached_jit(key, build):
    """Return (building if absent) the jitted program for ``key``."""
    global _PROGRAM_CACHE
    if _PROGRAM_CACHE is None:
        from collections import OrderedDict

        _PROGRAM_CACHE = OrderedDict()
    key += (jax.lax.ppermute,)
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        default_registry().counter("program_cache.miss").inc()
        fn = _PROGRAM_CACHE[key] = jax.jit(build())
        while len(_PROGRAM_CACHE) > 128:
            _PROGRAM_CACHE.popitem(last=False)
    else:
        default_registry().counter("program_cache.hit").inc()
        _PROGRAM_CACHE.move_to_end(key)
    return fn


def _twoway_program(
    Vl, *, cfg: CometConfig, plan: TwoWayPlan, out_dtype,
    metric: MetricSpec = None, planes: bool = False,
):
    """Per-device program (inside shard_map). Vl: (n_f/n_pf, n_vp) values,
    or — on the bit-plane campaign path (``planes=True``) — the rank's
    packed plane shard (levels, n_fb/n_pf, n_vp) uint8.

    All block compute goes through the TileExecutor: on the fused Pallas
    paths the metric epilogue runs in-kernel (no dense numerator block in
    HBM) and the step-0 diagonal block runs the triangular tile schedule
    (only ``tj >= ti`` tiles enumerated, per paper §5).  With planes, the
    ring carries the packed representation — L/32 of the fp32 wire volume —
    and ``(V >= t)`` never runs inside the ring loop."""
    metric = metric or CZEKANOWSKI
    executor = TileExecutor(cfg=cfg, metric=metric, out_dtype=out_dtype,
                            axis="pf")
    n_pv, n_pr = cfg.n_pv, cfg.n_pr
    m = Vl.shape[-1]
    if planes:
        # stats from the exact value reconstruction V = sum_t plane_t
        from repro.kernels.mgemm_levels import values_from_planes

        s_own = jax.lax.psum(metric.stat(values_from_planes(Vl)), "pf")
    else:
        s_own = jax.lax.psum(metric.stat(Vl), "pf")  # (m,)
    pv = jax.lax.axis_index("pv")
    pr = jax.lax.axis_index("pr")
    # receive from upward neighbour: src (i+1) -> dst i
    perm = [((i + 1) % n_pv, i) for i in range(n_pv)]

    Vr, sr = Vl, s_own
    out = jnp.zeros((plan.slots_per_rank, m, m), out_dtype)
    for d in range(plan.n_steps):
        if d > 0:
            Vr = jax.lax.ppermute(Vr, "pv", perm)
            sr = jax.lax.ppermute(sr, "pv", perm)
        execute = (d % n_pr) == pr
        if plan.is_half_step(d):
            execute = jnp.logical_and(execute, pv < n_pv // 2)

        def compute(o, Vr=Vr, sr=sr, d=d):
            vals = executor.pair_block(Vl, s_own, Vr, sr, diagonal=(d == 0))
            return o.at[d // n_pr].set(vals)

        out = jax.lax.cond(execute, compute, lambda o: o, out)
    return out[None, None]  # leading (pv=1, pr=1) device dims


def _twoway_deferred_program(
    Pl, *, cfg: CometConfig, plan: TwoWayPlan, metric: MetricSpec = None,
):
    """Deferred-flush chunk program (``repro.stream``): one byte-axis chunk
    of the campaign payload runs the SAME block-circulant ring as
    ``_twoway_program``, but every block emits its raw fp32 numerator
    partial (psummed over "pf") instead of assembled metric values, and
    the per-vector stat partial rides along.  ``Pl`` is the rank's packed
    plane shard of ONE chunk — (levels, chunk_kb/n_pf, n_vp) uint8.

    The stats ring is gone entirely: raw numerators need no stats, so the
    chunk ring carries only the plane payload (the merge epilogue reads
    chunk-summed global stats instead).  Returns ``(partials, s_own)`` —
    (slots, m, m) fp32 and (m,) fp32.
    """
    from repro.kernels.mgemm_levels import values_from_planes

    metric = metric or CZEKANOWSKI
    executor = TileExecutor(cfg=cfg, metric=metric, out_dtype=jnp.float32,
                            axis="pf", deferred=True)
    n_pv, n_pr = cfg.n_pv, cfg.n_pr
    m = Pl.shape[-1]
    s_own = jax.lax.psum(metric.stat(values_from_planes(Pl)), "pf")
    pv = jax.lax.axis_index("pv")
    pr = jax.lax.axis_index("pr")
    perm = [((i + 1) % n_pv, i) for i in range(n_pv)]

    Pr = Pl
    out = jnp.zeros((plan.slots_per_rank, m, m), jnp.float32)
    for d in range(plan.n_steps):
        if d > 0:
            Pr = jax.lax.ppermute(Pr, "pv", perm)
        execute = (d % n_pr) == pr
        if plan.is_half_step(d):
            execute = jnp.logical_and(execute, pv < n_pv // 2)

        def compute(o, Pr=Pr, d=d):
            return o.at[d // n_pr].set(executor.pair_partial(Pl, Pr))

        out = jax.lax.cond(execute, compute, lambda o: o, out)
    return out[None, None], s_own[None]


def checksum_launcher(way: int, mesh: Mesh, slots: np.ndarray):
    """``out -> device partials``: dispatches the device checksum program
    (``ck.partials_program``, one executable per output geometry) on a
    campaign's output blocks; None where their value dtype has no device
    path (``ck.device_dtype``)."""
    def launch(out):
        if not ck.device_dtype(out.dtype):
            return None
        fn = _cached_jit(
            ("checksum", way, mesh, out.shape, str(out.dtype), slots.shape),
            lambda: ck.partials_program(way, mesh),
        )
        return fn(out, slots)
    return launch


def _run_program(fn, arg, shape, checksum=None, **attrs):
    """Run a jitted campaign program on its staged payload and read its
    blocks back into a host array of ``shape``.  Returns ``(blocks, raw)``:
    ``raw`` is the ``(raw checksum total, result count)`` folded from the
    device partials that ``checksum`` (a ``checksum_launcher``) dispatches,
    or None.

    Three spans split the time: ``dispatch`` (the call until it returns:
    the enqueue, and on a program's first call or a new shape also trace,
    lowering, compile or cache load), ``ring-step`` (the
    wait for the device program) and ``readback`` (the copy to the host).
    The wait costs nothing extra: the readback right after it would
    block until the program is done anyway.  The checksum partials are
    dispatched before the wait, so they run during the copy; their launch,
    and their wait and fold after the readback, are ``hash`` spans."""
    with obs.span("dispatch"):
        out = fn(arg)
    parts = None
    if checksum is not None:
        with obs.span("hash"):
            parts = checksum(out)
    with obs.span("ring-step") as sp:
        jax.block_until_ready(out)
        sp.add(payload_bytes=int(arg.nbytes), **attrs)
    with obs.span("readback"):
        blocks = np.asarray(out).reshape(shape)
    if parts is None:
        return blocks, None
    with obs.span("hash"):
        return blocks, ck.fold_partials(parts)


def _prep_payload(V, cfg: CometConfig, metric: MetricSpec):
    """Resolve the config against V and build the sharded ring payload.

    The one payload-preparation path shared by the sequential and batched
    2-way entry points (so a batched campaign's payload is byte-identical
    to the sequential campaign's).  Returns
    ``(cfg, arg, in_specs, planes, n_vp, n_v)``.
    """
    from repro.kernels.mgemm_levels.planes import PackedPlanes, pad_planes

    if isinstance(V, PackedPlanes):
        n_v = V.n_v
        cfg = resolve_config(cfg, V, metric)  # always "bitplane" (or raises)
        Pp = pad_planes(
            V.planes, byte_align=cfg.n_pf,
            n_v=n_v + (-n_v) % cfg.n_pv,
        )
        with obs.span("stage"):
            arg = jnp.asarray(Pp)
        return cfg, arg, P(None, "pf", "pv"), True, \
            Pp.shape[2] // cfg.n_pv, n_v
    n_v = V.shape[1]
    with obs.span("encode") as sp:
        V = np.asarray(V)
        cfg = resolve_config(cfg, V, metric)
        planes = cfg.encoding == "bitplane"
        if planes:
            # encode ONCE before shard_map; the byte axis shards over "pf"
            from repro.kernels.mgemm_levels import encode_bitplanes_np

            Vp = pad_vectors(V, cfg, field_align=8)
            host, dtype = encode_bitplanes_np(Vp, cfg.levels), None
            in_specs = P(None, "pf", "pv")
        else:
            Vp = pad_vectors(V, cfg)
            host, dtype = Vp, jnp.dtype(cfg.ring_dtype)
            in_specs = P("pf", "pv")
        sp.add(bytes=int(host.nbytes), levels=int(cfg.levels))
    with obs.span("stage"):
        arg = jnp.asarray(host, dtype=dtype)
    return cfg, arg, in_specs, planes, Vp.shape[1] // cfg.n_pv, n_v


def twoway_distributed(
    V, mesh: Mesh, cfg: CometConfig, metric: MetricSpec = None
) -> TwoWayOutput:
    """Compute all unique 2-way metrics of V's columns on the mesh.

    ``V``: (n_f, n_v) value matrix, or a pre-encoded ``PackedPlanes``
    payload (``repro.store`` zero-encode loading) — the packed planes are
    re-padded with inert zero bytes/columns to the campaign geometry and
    ring-carried directly; the host encoder never runs."""
    metric = metric or CZEKANOWSKI
    cfg, arg, in_specs, planes, n_vp, n_v = _prep_payload(V, cfg, metric)
    plan = TwoWayPlan(cfg.n_pv, cfg.n_pr)
    out_dtype = jnp.dtype(cfg.out_dtype)

    fn = _cached_jit(
        ("twoway", mesh, cfg, plan, metric.name, str(out_dtype), planes),
        lambda: jax.shard_map(
            partial(_twoway_program, cfg=cfg, plan=plan, out_dtype=out_dtype,
                    metric=metric, planes=planes),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P("pv", "pr", None, None, None),
            check_vma=False,
        ),
    )
    with obs.span("entries"):
        slots = checksum_slots(plan, n_vp, n_v)
    blocks, raw = _run_program(
        fn, arg, (cfg.n_pv, cfg.n_pr, plan.slots_per_rank, n_vp, n_vp),
        checksum=checksum_launcher(2, mesh, slots), steps=int(plan.n_steps),
    )
    return TwoWayOutput(blocks=blocks, plan=plan, n_v=n_v, n_vp=n_vp,
                        device_raw=raw,
                        path=TileExecutor(cfg=cfg, metric=metric).path)


def _twoway_batched_program(
    Vl, *, cfg: CometConfig, plan: TwoWayPlan, out_dtype,
    groups, planes: bool = False,
):
    """Batched-campaign per-device program: ONE ring traversal, M results.

    ``groups`` is the ``group_families`` partition of the requested
    metrics: each family shares a numerator contraction per ring step
    (``TileExecutor.pair_raw``) and fans it out through every member's
    ``merge_pair`` epilogue — extra metrics in a family cost one extra
    elementwise assembly, never another contraction or ring step.  The
    payload ring (``Vr``) is metric-agnostic and moves EXACTLY the bytes
    of the sequential single-metric program; only the small per-family
    (m,) stat vectors scale with family count.

    Emits (M, slots, m, m) metric values, M = total metrics in flattened
    family order (the entry point restores request order).
    """
    from repro.kernels.mgemm_levels import values_from_planes

    n_pv, n_pr = cfg.n_pv, cfg.n_pr
    m = Vl.shape[-1]
    execs = [
        [TileExecutor(cfg=cfg, metric=s, out_dtype=out_dtype, axis="pf")
         for s in grp]
        for grp in groups
    ]
    W = values_from_planes(Vl) if planes else Vl
    # one psummed stat per family (members share the stat by definition
    # of family_key) — bitwise the sequential program's s_own
    stats = tuple(
        jax.lax.psum(grp[0].stat(W), "pf") for grp in groups
    )
    n_metrics = sum(len(grp) for grp in groups)
    pv = jax.lax.axis_index("pv")
    pr = jax.lax.axis_index("pr")
    perm = [((i + 1) % n_pv, i) for i in range(n_pv)]

    Vr, srs = Vl, stats
    out = jnp.zeros((n_metrics, plan.slots_per_rank, m, m), out_dtype)
    for d in range(plan.n_steps):
        if d > 0:
            Vr = jax.lax.ppermute(Vr, "pv", perm)
            srs = tuple(jax.lax.ppermute(s, "pv", perm) for s in srs)
        execute = (d % n_pr) == pr
        if plan.is_half_step(d):
            execute = jnp.logical_and(execute, pv < n_pv // 2)

        def compute(o, Vr=Vr, srs=srs, d=d):
            vals = []
            for g, ex_grp in enumerate(execs):
                raw = ex_grp[0].pair_raw(
                    Vl, stats[g], Vr, srs[g], diagonal=(d == 0)
                )
                vals.extend(
                    ex.merge_pair(raw, stats[g], srs[g], diagonal=(d == 0))
                    for ex in ex_grp
                )
            return o.at[:, d // n_pr].set(jnp.stack(vals))

        out = jax.lax.cond(execute, compute, lambda o: o, out)
    return out[None, None]  # leading (pv=1, pr=1) device dims


def _twoway_deferred_batched_program(
    Pl, *, cfg: CometConfig, plan: TwoWayPlan, groups,
):
    """Deferred-flush batched chunk program (streamed batched campaigns):
    one byte-axis chunk, one ring, one raw fp32 numerator partial per
    metric FAMILY (members share it) plus per-family stat partials.
    Returns ``(partials (G, slots, m, m) fp32, stats (G, m) fp32)`` — the
    host accumulates both across chunks and fans the merge epilogue out
    per metric after the last chunk."""
    from repro.kernels.mgemm_levels import values_from_planes

    n_pv, n_pr = cfg.n_pv, cfg.n_pr
    m = Pl.shape[-1]
    execs = [
        TileExecutor(cfg=cfg, metric=grp[0], out_dtype=jnp.float32,
                     axis="pf", deferred=True)
        for grp in groups
    ]
    W = values_from_planes(Pl)
    stats = jnp.stack([jax.lax.psum(grp[0].stat(W), "pf") for grp in groups])
    pv = jax.lax.axis_index("pv")
    pr = jax.lax.axis_index("pr")
    perm = [((i + 1) % n_pv, i) for i in range(n_pv)]

    Pr = Pl
    out = jnp.zeros((len(groups), plan.slots_per_rank, m, m), jnp.float32)
    for d in range(plan.n_steps):
        if d > 0:
            Pr = jax.lax.ppermute(Pr, "pv", perm)
        execute = (d % n_pr) == pr
        if plan.is_half_step(d):
            execute = jnp.logical_and(execute, pv < n_pv // 2)

        def compute(o, Pr=Pr, d=d):
            parts = jnp.stack(
                [ex.pair_raw(Pl, None, Pr, None) for ex in execs]
            )
            return o.at[:, d // n_pr].set(parts)

        out = jax.lax.cond(execute, compute, lambda o: o, out)
    return out[None, None], stats[None]


def twoway_batched(
    V, mesh: Mesh, cfg: CometConfig, specs,
) -> tuple:
    """Batched 2-way campaigns: one ring traversal, one result per metric.

    ``specs`` is a sequence of MetricSpecs sharing the SAME payload; the
    config's 'auto' knobs resolve against ``batch_lead(specs)`` (the
    plane-native member constrains encoding the most).  Returns
    ``(outputs, binfo)``: per-spec ``TwoWayOutput`` in request order —
    each bit-identical to its sequential ``twoway_distributed`` run — and
    the ring-traffic accounting dict behind ``meta["batch"]``
    (``ring_payload_bytes`` is a function of payload shape and plan ONLY,
    independent of how many metrics ride the traversal).
    """
    specs = list(specs)
    cfg, arg, in_specs, planes, n_vp, n_v = _prep_payload(
        V, cfg, batch_lead(specs)
    )
    groups = group_families(specs)
    flat = [s for grp in groups for s in grp]
    plan = TwoWayPlan(cfg.n_pv, cfg.n_pr)
    out_dtype = jnp.dtype(cfg.out_dtype)

    names = tuple(tuple(s.name for s in grp) for grp in groups)
    fn = _cached_jit(
        ("twoway-batched", mesh, cfg, plan, names, str(out_dtype), planes),
        lambda: jax.shard_map(
            partial(_twoway_batched_program, cfg=cfg, plan=plan,
                    out_dtype=out_dtype, groups=groups, planes=planes),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P("pv", "pr", None, None, None, None),
            check_vma=False,
        ),
    )
    blocks, _ = _run_program(
        fn, arg,
        (cfg.n_pv, cfg.n_pr, len(flat), plan.slots_per_rank, n_vp, n_vp),
        steps=int(plan.n_steps), metrics=len(flat),
    )
    # every member rides its family lead's contraction (``pair_raw``)
    paths = {s.name: TileExecutor(cfg=cfg, metric=grp[0]).path
             for grp in groups for s in grp}
    by_name = {
        s.name: TwoWayOutput(
            blocks=np.ascontiguousarray(blocks[:, :, i]), plan=plan,
            n_v=n_v, n_vp=n_vp, path=paths[s.name],
        )
        for i, s in enumerate(flat)
    }
    binfo = batch_accounting(
        int(arg.nbytes), cfg, plan, groups, n_vp, planes=planes, way=2
    )
    return [by_name[s.name] for s in specs], binfo


def batch_accounting(
    payload_nbytes: int, cfg: CometConfig, plan, groups,
    n_vp: int, *, planes: bool, way: int,
) -> dict:
    """Ring-traffic accounting for one batched traversal (either way).

    ``ring_payload_bytes`` counts the V/plane payload actually ppermuted:
    per-rank shard bytes x the plan's ``ring_steps`` x ranks —
    deliberately independent of metric count (that is the whole point of
    batching).  The per-family (m,) fp32 stat vectors are the only traffic
    that scales with the batch; they are reported separately and are
    negligible next to the payload (m floats vs m payload columns)."""
    shard = payload_nbytes // (cfg.n_pf * cfg.n_pv)
    return {
        "way": way,
        "families": len(groups),
        "metrics": [s.name for grp in groups for s in grp],
        "planes": planes,
        "payload_bytes_per_rank": shard,
        "ring_steps": plan.ring_steps,
        "n_ranks": cfg.n_ranks,
        "ring_payload_bytes": shard * plan.ring_steps * cfg.n_ranks,
        "stat_ring_bytes": (
            len(groups) * n_vp * 4 * plan.ring_steps * cfg.n_ranks
        ),
    }


def czek2_distributed(V: np.ndarray, mesh: Mesh, cfg: CometConfig) -> TwoWayOutput:
    """Proportional Similarity 2-way campaign (pre-registry entry point)."""
    return twoway_distributed(V, mesh, cfg, metric=CZEKANOWSKI)
