"""SimilarityEngine: the one way to run a similarity campaign.

The engine owns everything between a ``SimilarityRequest`` and a
``SimilarityResult``: metric resolution via the registry, request
validation against the device pool, comet-mesh construction (cached per
decomposition so repeated requests reuse compiled programs), input
materialization, padding (inside the core engines), plan selection and
2-way/3-way dispatch including the staged 3-way sweep.

    from repro.api import SimilarityEngine, SimilarityRequest

    engine = SimilarityEngine()
    result = engine.run(SimilarityRequest(metric="czekanowski", way=2), V)
    for tile in result.tiles():
        ...
"""
from __future__ import annotations

import time

import numpy as np

from repro.api.registry import get_metric
from repro.api.request import SimilarityRequest
from repro.api.result import SimilarityResult
from repro.core.threeway import threeway_distributed
from repro.core.twoway import twoway_distributed
from repro.obs import trace as obs
from repro.obs.metrics import count_jit_events, default_registry, jit_counts
from repro.parallel.mesh import COMET_AXES, make_comet_mesh

__all__ = ["SimilarityEngine"]


def _campaign_comparisons(result) -> int:
    """Achieved element-comparison count — the paper's comparisons/s
    numerator: result entries x vector length, summed over a batch's
    campaigns.  Delta campaigns count only the border entries actually
    computed (that is the work the engine did)."""
    if hasattr(result, "campaigns"):  # BatchedSimilarityResult
        return sum(
            int(r.num_results()) * int(r.n_f)
            for _m, _s, r in result.campaigns
        )
    d = result.meta.get("delta")
    if d is not None:
        return int(d["computed_entries"]) * int(result.n_f)
    return int(result.num_results()) * int(result.n_f)


def _obs_block(comparisons, seconds, tracer, i0, jit0, checksum,
               path) -> dict:
    """The normalized ``meta["obs"]`` block every campaign result carries.

    Always: achieved ``comparisons``, wall ``seconds``,
    ``comparisons_per_s``, ``jit``, the JAX lowerings and compiles
    counted in this process since ``jit0`` was read (a campaign that
    reuses its compiled program reads 0 and 0), and ``checksum``, where
    the result's checksum and count come from ("device" or "host"), and
    ``path``, the contraction path the executor resolved.  When tracing
    was enabled for the run, also the per-phase breakdown from the span
    events recorded since index ``i0``."""
    jit = jit_counts()
    block = {
        "comparisons": int(comparisons),
        "seconds": float(seconds),
        "comparisons_per_s": float(comparisons) / max(float(seconds), 1e-12),
        "jit": {k: jit[k] - jit0[k] for k in jit},
        "checksum": checksum,
        "path": path,
    }
    if tracer is None:
        return block
    block["phases"] = {
        n: {"count": int(p["count"]), "seconds": float(p["seconds"])}
        for n, p in sorted(tracer.phase_stats(i0).items())
    }
    return block


def _subset_positions(request, n_v: int, *, restrict: bool):
    """Validate subset indices against ``n_v`` and compute each subset's
    positions within the traversal payload.

    ``restrict=True`` (in-memory): the payload is the sorted union of all
    subset indices; each subset's positions index into the union, in
    subset order.  ``restrict=False`` (streamed): the payload keeps the
    full vector axis, so positions are the subset indices themselves.
    Returns ``(subs, union, pos)``; union/pos are None/{} for full-set
    requests."""
    subs = request.campaign_subsets()
    if not request.subsets:
        return subs, None, {}
    for name, idx in subs:
        bad = [i for i in idx if i >= n_v]
        if bad:
            raise ValueError(
                f"subset {name!r} indices {bad} out of range for n_v={n_v}"
            )
    if restrict:
        union = np.unique(np.concatenate(
            [np.asarray(idx, np.int64) for _, idx in subs]
        ))
        pos = {
            name: np.searchsorted(union, np.asarray(idx, np.int64))
            for name, idx in subs
        }
        return subs, union, pos
    pos = {name: np.asarray(idx, np.int64) for name, idx in subs}
    return subs, None, pos


class SimilarityEngine:
    """Metric-agnostic front-end over the distributed similarity engines."""

    def __init__(self, mesh=None, devices=None):
        """``mesh``: use an existing ("pf","pv","pv") comet mesh instead of
        constructing one (must match each request's decomposition).
        ``devices``: restrict mesh construction to an explicit device list.
        """
        self._mesh = mesh
        self._devices = devices
        self._mesh_cache = {}
        count_jit_events()

    # -- internals ---------------------------------------------------------

    def _device_count(self) -> int:
        if self._mesh is not None:
            return int(self._mesh.devices.size)
        if self._devices is not None:
            return len(self._devices)
        import jax

        return len(jax.devices())

    def _mesh_for(self, request: SimilarityRequest):
        key = (request.n_pf, request.n_pv, request.n_pr)
        if self._mesh is not None:
            shape = tuple(self._mesh.devices.shape)
            if self._mesh.axis_names != COMET_AXES or shape != key:
                raise ValueError(
                    f"engine mesh {self._mesh.axis_names}{shape} does not "
                    f"match request decomposition {key}"
                )
            return self._mesh
        if key not in self._mesh_cache:
            self._mesh_cache[key] = make_comet_mesh(
                *key, devices=self._devices
            )
        return self._mesh_cache[key]

    def _observed(self, run, *args):
        """``run(*args)`` inside a ``campaign`` span, with the result's
        ``meta["obs"]`` block attached.  Counting the results (the
        ``count`` span) reads the count the device partials gave, or else
        scans every tile once (``entries`` spans).  The registry counters
        ``checksum.device`` and ``checksum.host`` count the campaigns of
        each kind, and ``path.<path>`` those of each contraction path."""
        tracer = obs.get_tracer()
        i0 = tracer.event_count() if tracer is not None else 0
        jit0 = jit_counts()
        t0 = time.perf_counter()
        with obs.span("campaign"):
            result = run(*args)
        with obs.span("count"):
            comparisons = _campaign_comparisons(result)
        source = getattr(result, "checksum_source", "host")
        runs = [r for _m, _s, r in getattr(result, "campaigns", ())] \
            or [result]
        registry = default_registry()
        registry.counter(f"checksum.{source}").inc(len(runs))
        for r in runs:
            registry.counter(f"path.{r.path}").inc()
        result.meta["obs"] = _obs_block(
            comparisons, time.perf_counter() - t0, tracer, i0, jit0, source,
            result.path,
        )
        return result

    # -- public API --------------------------------------------------------

    def run(self, request: SimilarityRequest, V=None) -> SimilarityResult:
        """Execute a campaign; ``V`` overrides the request's input spec.

        ``V`` (or the materialized input) may be a value matrix, a
        pre-encoded ``PackedPlanes`` payload, or a lazy ``ShardedPlanes``
        handle — with a ``source="planes"`` input the campaign streams
        packed planes from the dataset store straight into the engines (no
        host-side encode) and the result's manifest records the dataset
        provenance (path + checksum).  When the resolved ``streaming``
        knob is "on" (multi-shard or budgeted datasets under "auto"), the
        campaign runs the out-of-core ``repro.stream`` pipeline: the
        payload never materializes in host memory beyond the double
        buffers, and ``meta["stream"]`` records the chunk accounting.

        Every result's ``meta["obs"]`` records achieved comparisons/s and
        the campaign's JAX lowerings and compiles; under an enabled
        ``repro.obs`` tracer it adds the per-phase breakdown
        (docs/OBSERVABILITY.md)."""
        if request.delta_from:
            # load() verifies the prior's checksum before we merge into it
            prior = SimilarityResult.load(request.delta_from)
            return self.run_delta(request, prior, V)
        return self._observed(self._run, request, V)

    def _run(self, request: SimilarityRequest, V=None) -> SimilarityResult:
        from repro.kernels.mgemm_levels.planes import PackedPlanes
        from repro.store.reader import ShardedPlanes

        spec = get_metric(request.metric)
        with obs.span("validate"):
            request.validate(n_devices=self._device_count(), metric_spec=spec)
        meta = {}
        if V is None:
            if request.input is None:
                raise ValueError("no input: pass V or set request.input")
            if (request.input.source == "planes"
                    and request.streaming != "off"):
                # lazy handle: streaming eligibility resolves before any
                # payload byte is read; non-streamed runs materialize below
                from repro.store import DatasetReader

                if not request.input.path:
                    raise ValueError(
                        "InputSpec(source='planes') needs a dataset path"
                    )
                V = DatasetReader(request.input.path).sharded()
            else:
                V = request.input.materialize()
            if request.input.source == "bed":
                meta["dataset"] = {
                    "path": request.input.path,
                    "kind": "bed",
                    "missing": request.input.missing,
                }
        if isinstance(V, ShardedPlanes):
            from repro.core.twoway import resolve_config

            if resolve_config(request.to_comet_config(), V, spec).streaming \
                    == "on":
                if request.is_batched:
                    return self._run_streamed_batched(request, V, meta)
                return self._run_streamed(request, V, spec, meta)
            V = V.materialize()  # in-memory PackedPlanes path below
        if isinstance(V, PackedPlanes):
            # provenance travels on the handle (DatasetReader.packed() fills
            # it from the manifest it already parsed), so it is recorded no
            # matter which entry point materialized the planes — engine or
            # the serving layer's pre-materialized submit()
            if V.origin:
                meta["dataset"] = V.origin
            n_f, n_v = V.n_f, V.n_v
        else:
            V = np.asarray(V)
            if V.ndim != 2:
                raise ValueError(f"V must be (n_f, n_v), got shape {V.shape}")
            n_f, n_v = V.shape
        mesh = self._mesh_for(request)
        cfg = request.to_comet_config()
        stages = request.resolved_stages()
        if request.is_batched:
            return self._run_batched(request, V, meta, n_f, n_v, mesh, cfg)

        t0 = time.perf_counter()
        if request.way == 2:
            outputs = [twoway_distributed(V, mesh, cfg, metric=spec)]
            if request.packed:
                outputs = [o.pack() for o in outputs]
        else:
            outputs = [
                threeway_distributed(V, mesh, cfg, stage=s, metric=spec)
                for s in stages
            ]
        seconds = time.perf_counter() - t0

        return SimilarityResult(
            way=request.way,
            metric=request.metric,
            n_v=n_v,
            n_f=n_f,
            outputs=outputs,
            decomposition=(request.n_pf, request.n_pv, request.n_pr),
            n_st=request.n_st,
            stages=stages,
            out_dtype=request.out_dtype,
            seconds=seconds,
            meta=meta,
        )

    # -- delta campaigns ----------------------------------------------------

    def run_delta(self, request: SimilarityRequest, prior: SimilarityResult,
                  V=None) -> SimilarityResult:
        """Border-block delta campaign: given ``prior`` covering the input's
        first ``prior.n_v`` vectors, compute ONLY the new-vs-all rectangle
        and new-vs-new triangle (``repro.core.delta``) and merge into packed
        upper-triangular storage — checksum bit-identical to a full
        recompute, compute proportional to the border (``meta["delta"]``).

        Lineage: when the input is an appended dataset store, its
        manifest's ``parent.checksum`` must match the dataset checksum the
        prior recorded (if it recorded one) — a delta against the wrong
        ancestor raises instead of silently merging unrelated results.
        The merged result round-trips ``save()/load()`` as a single-rank
        packed result and is itself a valid prior for the next append
        (deltas chain)."""
        return self._observed(self._run_delta, request, prior, V)

    def _run_delta(self, request, prior, V=None) -> SimilarityResult:
        from repro.core.delta import merge_delta, twoway_delta
        from repro.core.tile_executor import TileExecutor
        from repro.kernels.mgemm_levels.planes import PackedPlanes
        from repro.store.reader import ShardedPlanes

        spec = get_metric(request.metric)
        with obs.span("validate"):
            request.validate(n_devices=self._device_count(), metric_spec=spec)
        if request.way != 2 or request.is_batched:
            raise ValueError("delta campaigns are 2-way, non-batched only")
        if prior.way != 2:
            raise ValueError(f"prior result is {prior.way}-way, need 2-way")
        if prior.metric != request.metric:
            raise ValueError(
                f"prior result is metric {prior.metric!r}, request says "
                f"{request.metric!r}"
            )
        if prior.out_dtype != request.out_dtype:
            raise ValueError(
                f"prior out_dtype {prior.out_dtype!r} != request "
                f"{request.out_dtype!r} (merged storage is one array)"
            )
        meta = {}
        if V is None:
            if request.input is None:
                raise ValueError("no input: pass V or set request.input")
            if (request.input.source == "planes"
                    and request.streaming != "off"):
                from repro.store import DatasetReader

                V = DatasetReader(request.input.path).sharded()
            else:
                V = request.input.materialize()
        if isinstance(V, (PackedPlanes, ShardedPlanes)):
            n_f, n_v = V.n_f, V.n_v
            origin = dict(V.origin) if V.origin else {}
        else:
            V = np.asarray(V)
            if V.ndim != 2:
                raise ValueError(f"V must be (n_f, n_v), got shape {V.shape}")
            n_f, n_v = V.shape
            origin = {}
        n_old = prior.n_v
        m = n_v - n_old
        if m < 1:
            raise ValueError(
                f"input has n_v={n_v} vectors, prior already covers "
                f"{n_old} — nothing appended"
            )
        if prior.n_f != n_f:
            raise ValueError(
                f"prior covers n_f={prior.n_f} fields, input has {n_f} — "
                "not the same cohort"
            )
        if origin:
            meta["dataset"] = origin
            prior_ck = prior.meta.get("dataset", {}).get("checksum")
            parent = origin.get("parent")
            if prior_ck and parent and parent["checksum"] != prior_ck:
                raise ValueError(
                    f"dataset lineage mismatch: manifest parent checksum "
                    f"{parent['checksum']} != prior result's dataset "
                    f"{prior_ck}"
                )
        mesh = self._mesh_for(request)
        cfg = request.to_comet_config()

        t0 = time.perf_counter()
        dinfo = None
        if isinstance(V, ShardedPlanes):
            from repro.core.twoway import resolve_config

            if resolve_config(cfg, V, spec).streaming == "on":
                from repro.stream import stream_twoway_delta

                rect, tri, rcfg, dinfo, sinfo = stream_twoway_delta(
                    V, n_old, mesh, cfg, spec
                )
                meta["stream"] = sinfo
            else:
                V = V.materialize()
        if dinfo is None:
            rect, tri, rcfg, dinfo = twoway_delta(V, n_old, mesh, cfg, spec)
        out = merge_delta(
            prior.outputs[0], rect, tri, n_old, m, rcfg.out_dtype,
            path=TileExecutor(cfg=rcfg, metric=spec,
                              deferred="stream" in meta).path,
        )
        seconds = time.perf_counter() - t0
        dinfo["prior"] = {"n_v": n_old, "checksum": hex(prior.checksum())}
        meta["delta"] = dinfo

        # single-rank packed decomposition so save()/load() round-trips the
        # merged storage; the border's requested decomposition is recorded
        # in meta["delta"]["decomposition"]
        return SimilarityResult(
            way=2,
            metric=request.metric,
            n_v=n_v,
            n_f=n_f,
            outputs=[out],
            decomposition=(1, 1, 1),
            n_st=1,
            stages=(0,),
            out_dtype=request.out_dtype,
            seconds=seconds,
            meta=meta,
        )

    # -- batched campaigns --------------------------------------------------

    def _batch_specs(self, request):
        """Resolve every campaign metric and gate each against the way."""
        names = request.campaign_metrics()
        specs = [get_metric(n) for n in names]
        for name, s in zip(names, specs):
            if request.way not in s.ways:
                raise ValueError(
                    f"metric {name!r} supports ways {s.ways}, "
                    f"requested {request.way}"
                )
        return names, specs

    def _run_batched(self, request, V, meta, n_f, n_v, mesh, cfg):
        """In-memory batched dispatch: one ring traversal, many campaigns.

        Named subsets restrict the payload to the sorted UNION of all
        subset indices before the traversal — a vector-axis view for value
        matrices, a byte-column view (``take_planes_vectors``) for packed
        planes, so pre-encoded payloads are never re-encoded — then each
        subset's result is carved out of the union output host-side."""
        from repro.core.threeway import threeway_batched
        from repro.core.twoway import twoway_batched
        from repro.kernels.mgemm_levels.planes import (
            PackedPlanes,
            take_planes_vectors,
        )

        names, specs = self._batch_specs(request)
        subs, union, pos = _subset_positions(request, n_v, restrict=True)
        Vu = V
        if union is not None:
            if isinstance(V, PackedPlanes):
                Vu = PackedPlanes(
                    np.ascontiguousarray(take_planes_vectors(V.planes, union)),
                    n_f=V.n_f, origin=V.origin,
                )
            else:
                Vu = np.ascontiguousarray(V[:, union])
        stages = request.resolved_stages()

        t0 = time.perf_counter()
        if request.way == 2:
            outs, binfo = twoway_batched(Vu, mesh, cfg, specs)
            per_metric = [[o] for o in outs]
        else:
            per_metric = [[] for _ in specs]
            for s in stages:
                outs, binfo = threeway_batched(Vu, mesh, cfg, specs, stage=s)
                for lst, o in zip(per_metric, outs):
                    lst.append(o)
        seconds = time.perf_counter() - t0
        return self._assemble_batched(
            request, names, subs, pos, per_metric, n_f, n_v, meta, binfo,
            seconds, stages,
        )

    def _run_streamed_batched(self, request, sh, meta):
        """Out-of-core batched dispatch over a lazy ShardedPlanes handle.

        The streamed ring carries the FULL vector axis (the payload lives
        in disk shards — there is no cheap union view), so named subsets
        are extracted from the full-set outputs; ring accounting reflects
        the full payload."""
        from repro.stream import stream_threeway_batched, stream_twoway_batched

        names, specs = self._batch_specs(request)
        subs, _, pos = _subset_positions(request, sh.n_v, restrict=False)
        mesh = self._mesh_for(request)
        cfg = request.to_comet_config()
        stages = request.resolved_stages()
        if sh.origin:
            meta["dataset"] = sh.origin

        t0 = time.perf_counter()
        if request.way == 2:
            outs, binfo, sinfo = stream_twoway_batched(sh, mesh, cfg, specs)
            per_metric = [[o] for o in outs]
        else:
            per_metric = [[] for _ in specs]
            for s in stages:
                outs, binfo, sinfo = stream_threeway_batched(
                    sh, mesh, cfg, specs, stage=s
                )
                for lst, o in zip(per_metric, outs):
                    lst.append(o)
        seconds = time.perf_counter() - t0
        meta["stream"] = sinfo
        return self._assemble_batched(
            request, names, subs, pos, per_metric, sh.n_f, sh.n_v, meta,
            binfo, seconds, stages,
        )

    def _assemble_batched(self, request, names, subs, pos, per_metric,
                          n_f, n_v, meta, binfo, seconds, stages):
        """Wrap per-metric union outputs into one BatchedSimilarityResult.

        Full-set campaigns reuse the distributed outputs directly (same
        layout as a sequential run); named-subset campaigns are extracted
        into single-rank plans.  Every campaign result carries the shared
        ``meta["batch"]`` accounting."""
        from repro.api.batch import (
            BatchedSimilarityResult,
            extract_threeway,
            extract_twoway,
        )

        batch_meta = dict(binfo)
        batch_meta.update(
            campaigns=len(names) * len(subs),
            subsets=[n for n, _ in subs if n],
            encodes=1,
            traversals=1 if request.way == 2 else len(stages),
        )
        cmeta = {**meta, "batch": batch_meta}
        campaigns = []
        for mi, mname in enumerate(names):
            outs_m = per_metric[mi]
            for sname, idx in subs:
                if idx is None:  # full-set campaign
                    outputs = outs_m
                    if request.way == 2 and request.packed:
                        outputs = [o.pack() for o in outputs]
                    res = SimilarityResult(
                        way=request.way, metric=mname, n_v=n_v, n_f=n_f,
                        outputs=outputs,
                        decomposition=(request.n_pf, request.n_pv,
                                       request.n_pr),
                        n_st=request.n_st, stages=stages,
                        out_dtype=request.out_dtype, seconds=seconds,
                        meta=cmeta,
                    )
                else:
                    p = pos[sname]
                    if request.way == 2:
                        out = extract_twoway(outs_m[0], p)
                        outputs = [out.pack() if request.packed else out]
                    else:
                        outputs = [extract_threeway(outs_m, p)]
                    res = SimilarityResult(
                        way=request.way, metric=mname, n_v=len(idx), n_f=n_f,
                        outputs=outputs, decomposition=(1, 1, 1),
                        n_st=1, stages=(0,),
                        out_dtype=request.out_dtype, seconds=seconds,
                        meta=cmeta,
                    )
                campaigns.append((mname, sname, res))
        return BatchedSimilarityResult(
            campaigns=campaigns, meta=cmeta, seconds=seconds
        )

    def _run_streamed(self, request, sh, spec, meta) -> SimilarityResult:
        """Out-of-core campaign over a lazy ``ShardedPlanes`` handle.

        Dispatches to ``repro.stream``: chunked deferred-flush programs +
        the cross-shard merge epilogue.  Results are bit-identical to the
        in-memory engines; ``meta["stream"]`` records chunk/peak-host-bytes
        accounting."""
        from repro.stream import stream_threeway, stream_twoway

        mesh = self._mesh_for(request)
        cfg = request.to_comet_config()
        stages = request.resolved_stages()
        if sh.origin:
            meta["dataset"] = sh.origin

        t0 = time.perf_counter()
        outputs, sinfo = [], None
        if request.way == 2:
            out, sinfo = stream_twoway(sh, mesh, cfg, metric=spec)
            outputs = [out.pack() if request.packed else out]
        else:
            for s in stages:
                out, sinfo = stream_threeway(sh, mesh, cfg, stage=s,
                                             metric=spec)
                outputs.append(out)
        seconds = time.perf_counter() - t0
        meta["stream"] = sinfo

        return SimilarityResult(
            way=request.way,
            metric=request.metric,
            n_v=sh.n_v,
            n_f=sh.n_f,
            outputs=outputs,
            decomposition=(request.n_pf, request.n_pv, request.n_pr),
            n_st=request.n_st,
            stages=stages,
            out_dtype=request.out_dtype,
            seconds=seconds,
            meta=meta,
        )
