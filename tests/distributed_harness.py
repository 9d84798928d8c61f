"""Multi-device validation harness (run as a subprocess with 8 CPU devices).

Reproduces the paper's §5 validation: identical synthetic input, many
parallel decompositions (n_pf, n_pv, n_pr, n_st), and asserts

  1. every decomposition computes exactly the unique result set,
  2. values are BIT-FOR-BIT identical across decompositions (exact integer
     inputs => exact numerators => identical IEEE divisions),
  3. values match the O(n^2)/O(n^3) numpy oracles.

The cases are the table in tests/distributed_cases.py; each names one
``check_*`` function below and its arguments.  Every case runs in this one
process (the device count is fixed before jax starts) and writes one JSON
record ``{"id", "ok", "error", "seconds"}``, so a fault in one decomposition
fails that case alone.  Invoked by tests/test_distributed.py; standalone:

    PYTHONPATH=src python tests/distributed_harness.py [--out records.jsonl]
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

from distributed_cases import CASES  # noqa: E402
from repro.core.metrics import czek2_metric_np, czek3_metric_np  # noqa: E402
from repro.core.synthetic import random_integer_vectors  # noqa: E402
from repro.core.threeway import czek3_distributed  # noqa: E402
from repro.core.twoway import CometConfig, czek2_distributed  # noqa: E402
from repro.core import checksum as ck  # noqa: E402
from repro.parallel.mesh import make_comet_mesh  # noqa: E402

N_F, N_V = 24, 24
#: the delta cases split V's columns into n_old | N_V - n_old
N_OLD = 15
#: the datasets the store, streamed and delta cases read, made once
_TMP = tempfile.TemporaryDirectory()


@functools.cache
def _V():
    return random_integer_vectors(N_F, N_V, max_value=15, seed=42)


@functools.cache
def _Vb():
    """Binary ({0,1}) data for the popcount cases."""
    return random_integer_vectors(N_F, N_V, max_value=1, seed=43)


@functools.cache
def _oracle(way):
    fn = czek2_metric_np if way == 2 else czek3_metric_np
    return fn(_V()).astype(np.float32)


def _cfg(decomp=(1, 1, 1), **kw):
    n_pf, n_pv, n_pr = decomp
    return CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, **kw)


@functools.cache
def _run(way, cfg, binary, stage):
    V = _Vb() if binary else _V()
    mesh = make_comet_mesh(cfg.n_pf, cfg.n_pv, cfg.n_pr)
    if way == 2:
        return czek2_distributed(V, mesh, cfg)
    return czek3_distributed(V, mesh, cfg, stage=stage)


def run2(decomp=(1, 1, 1), binary=False, **kw):
    """A 2-way campaign, computed once per configuration."""
    return _run(2, _cfg(decomp, **kw), binary, 0)


def run3(decomp=(1, 1, 1), binary=False, stage=0, **kw):
    """Stage ``stage`` of a 3-way campaign, computed once per configuration."""
    return _run(3, _cfg(decomp, **kw), binary, stage)


@functools.cache
def _engine():
    from repro.api import SimilarityEngine

    return SimilarityEngine()


@functools.cache
def _dataset(name):
    """A dataset directory: ``v15`` (V at 15 levels), ``binary`` (Vb at one
    level) or ``appended`` (V's first N_OLD columns, then the rest
    appended)."""
    from repro.store import DatasetReader, append_dataset, write_dataset

    path = os.path.join(_TMP.name, name)
    if name == "v15":
        write_dataset(path, _V(), levels=15, n_shards=2)
    elif name == "binary":
        write_dataset(path, _Vb(), levels=1, n_shards=2)
    else:
        write_dataset(path, _V()[:, :N_OLD], levels=15, n_shards=2)
        append_dataset(path, _V()[:, N_OLD:])
    DatasetReader(path).validate()
    return path


@functools.cache
def _sharded(name):
    from repro.store import DatasetReader

    return DatasetReader(_dataset(name)).sharded()


# -- 2-way decomposition invariance ------------------------------------------


def check_2way(impl, decomp, levels=None):
    """xla: the pair count, the oracle, the device checksum and count at
    every decomposition.  Every other implementation — the Pallas fused
    epilogue with the triangular diagonal schedule, the exact levels
    decomposition, and the fused-levels campaign path (packed bit-planes
    encoded once, ring-carried, MXU plane kernels; n_pf=2 emits raw psummed
    partials for the merge epilogue) — must be bit-identical to it."""
    kw = {} if levels is None else {"levels": levels}
    out = run2(decomp, impl=impl, **kw)
    c = out.checksum()
    if impl == "xla":
        assert out.num_pairs() == N_V * (N_V - 1) // 2, (
            f"2way {decomp}: {out.num_pairs()} pairs"
        )
        iu = np.triu_indices(N_V, 1)
        np.testing.assert_allclose(out.dense()[iu], _oracle(2)[iu],
                                   rtol=1e-6, err_msg=f"2way {decomp} vs oracle")
        # the device partials fold to the host scan's checksum and count
        assert out.device_raw[1] == out.num_pairs(), f"2way {decomp} count"
        assert ck.combine([out.device_raw]) == c, f"2way {decomp} device"
    assert c == run2().checksum(), f"2way {impl} {decomp} changed results"


def check_2way_packed():
    """Packed upper-triangular storage: same entries, same checksum."""
    packed = run2((1, 2, 2), impl="pallas").pack()
    assert packed.storage == "packed"
    assert packed.checksum() == run2().checksum(), "packing changed results"


# -- 3-way decomposition invariance ------------------------------------------


def check_3way(impl, decomp, levels=None, encoding=None):
    """xla: the triple count, the oracle, the device checksum and count at
    every decomposition.  Every other path — the fused X_j pipeline-step
    kernels, the packed bit-plane ring (planes encoded once, ring-carried
    through Phases B/C; n_pf=2 shards the byte axis over "pf"), the ring
    with the unfused slice contraction (levels_xla), and the value ring
    (encoding="none") — must be bit-identical to it."""
    kw = {} if levels is None else {"levels": levels}
    if encoding is not None:
        kw["encoding"] = encoding
    out = run3(decomp, impl=impl, **kw)
    c = out.checksum()
    if impl == "xla":
        n_unique = N_V * (N_V - 1) * (N_V - 2) // 6
        assert out.num_triples() == n_unique, (
            f"3way {decomp}: {out.num_triples()} != {n_unique}"
        )
        d, ref = out.dense(), _oracle(3)
        err = max(abs(d[i, j, k] - ref[i, j, k])
                  for i in range(N_V) for j in range(i + 1, N_V)
                  for k in range(j + 1, N_V))
        assert err < 1e-6, f"3way {decomp}: max err {err}"
        assert out.device_raw[1] == n_unique, f"3way {decomp} count"
        assert ck.combine([out.device_raw]) == c, f"3way {decomp} device"
    assert c == run3().checksum(), f"3way {impl} {kw} {decomp} changed results"


def check_3way_staged():
    """Staging: the union over stages is the full result set, bit-identical."""
    n_unique = N_V * (N_V - 1) * (N_V - 2) // 6
    total, parts = 0, []
    for stage in range(2):
        out = run3((1, 2, 1), stage=stage, n_st=2)
        total += out.num_triples()
        parts.extend(ck.raw_triples(I, J, K, W) for I, J, K, W in out.entries())
    assert total == n_unique, f"staged union {total} != {n_unique}"
    assert ck.combine(parts) == run3().checksum(), "staged checksum mismatch"


# -- the unified engine ------------------------------------------------------


def check_engine_parity(way, decomp):
    """The unified SimilarityEngine reproduces the exact checksum of the
    direct czek2/czek3 path at this decomposition."""
    from repro.api import SimilarityRequest

    n_pf, n_pv, n_pr = decomp
    want = (run2(decomp) if way == 2 else run3(decomp)).checksum()
    got = _engine().run(
        SimilarityRequest(way=way, n_pf=n_pf, n_pv=n_pv, n_pr=n_pr), _V()
    ).checksum()
    assert got == want, f"engine {way}way checksum != direct {decomp}"


@functools.cache
def _ccc(decomp, impl="xla"):
    from repro.api import SimilarityRequest

    n_pf, n_pv, n_pr = decomp
    return _engine().run(
        SimilarityRequest(metric="ccc", way=2, n_pf=n_pf, n_pv=n_pv,
                          n_pr=n_pr, impl=impl), _V())


def check_ccc(decomp):
    """The registry's CCC metric matches its numpy oracle (fp32 tolerance)
    and its checksum does not vary with the decomposition."""
    from repro.api import get_metric

    oracle = get_metric("ccc").oracle2(_V()).astype(np.float32)
    iu = np.triu_indices(N_V, 1)
    out = _ccc(decomp)
    np.testing.assert_allclose(out.dense()[iu], oracle[iu], rtol=1e-5,
                               err_msg=f"ccc {decomp}")
    assert out.checksum() == _ccc((1, 1, 1)).checksum(), (
        "ccc checksum varies with decomposition"
    )


def check_ccc_pallas():
    """The generated fused kernel serves CCC too (metric-generic epilogue):
    integer data -> exact numerators -> bit-identical to the XLA path."""
    got = _ccc((1, 2, 1), impl="pallas").checksum()
    assert got == _ccc((1, 1, 1)).checksum(), "ccc pallas fused path changed results"


# -- store-backed campaigns --------------------------------------------------


@functools.cache
def _store_checksum(way, decomp):
    from repro.api import InputSpec, SimilarityRequest

    n_pf, n_pv, n_pr = decomp
    return _engine().run(SimilarityRequest(
        way=way, impl="levels", levels=15, n_pf=n_pf, n_pv=n_pv, n_pr=n_pr,
        input=InputSpec(source="planes", path=_dataset("v15")),
    )).checksum()


def check_plane_store(way, decomp):
    """A campaign loaded from a repro.store dataset (pre-encoded packed
    planes, mmap -> ring) is bit-identical to the in-memory matrix, also
    with byte-axis "pf" sharding of the on-disk field shards, and never
    runs the host encoder."""
    import repro.kernels.mgemm_levels as mgemm_levels
    from repro.api import SimilarityRequest

    n_pf, n_pv, n_pr = decomp
    calls = {"n": 0}
    orig = mgemm_levels.encode_bitplanes_np

    def counted(*args, **kwargs):
        calls["n"] += 1
        return orig(*args, **kwargs)

    mgemm_levels.encode_bitplanes_np = counted
    try:
        before = calls["n"]
        want = _engine().run(SimilarityRequest(
            way=way, impl="levels", levels=15, n_pf=n_pf, n_pv=n_pv,
            n_pr=n_pr), _V()).checksum()
        assert calls["n"] > before, "in-memory path should encode"
        before = calls["n"]
        got = _store_checksum(way, decomp)
        assert calls["n"] == before, (
            f"{way}-way plane-store campaign ran the host encoder"
        )
    finally:
        mgemm_levels.encode_bitplanes_np = orig
    assert got == want, f"{way}-way store checksum != in-memory {decomp}"
    assert got == _store_checksum(way, (1, 2, 1)), (
        f"{way}-way store checksum varies"
    )


# -- streamed campaigns ------------------------------------------------------


def check_streamed(way, decomp, budget):
    """A streamed campaign (repro.stream: the chunked deferred-flush
    pipeline and the cross-shard merge epilogue) is bit-identical to the
    in-memory engine, also with byte-axis "pf" sharding of the chunks and a
    budget that forces more than one chunk per shard."""
    from repro.stream import stream_threeway, stream_twoway

    sh = _sharded("v15")
    cfg = _cfg(decomp, impl="levels", levels=15, streaming="on",
               max_host_bytes=budget)
    mesh = make_comet_mesh(*decomp)
    if way == 2:
        out, info = stream_twoway(sh, mesh, cfg)
        want = run2().checksum()
    else:
        out, info = stream_threeway(sh, mesh, cfg, stage=0)
        want = run3().checksum()
    assert out.checksum() == want, f"streamed {way}way != in-memory {decomp}"
    if budget and way == 2:
        assert info["peak_host_bytes"] <= budget, info
        assert info["chunks"] > sh.n_shards, info


# -- binary data on the popcount bit-GEMM ------------------------------------


def _binary_want(way):
    run = run2 if way == 2 else run3
    return run(binary=True, impl="xla", levels=1).checksum()


def check_binary_popcount(decomp):
    """levels=1 resolves to the popcount bit-GEMM (path "fused-popcount")
    and is bit-identical to impl="xla" for 2-way and 3-way, including the
    n_pf=2 merge epilogue."""
    from repro.core.metric_spec import CZEKANOWSKI
    from repro.core.tile_executor import TileExecutor
    from repro.core.twoway import resolve_config

    cfg = _cfg(decomp, impl="levels", levels=1)
    rcfg = resolve_config(cfg, _Vb(), CZEKANOWSKI)
    ex = TileExecutor(cfg=rcfg, metric=CZEKANOWSKI, axis=None)
    assert ex.path == "fused-popcount", (decomp, ex.path)
    assert ex.path3 == "fused-popcount-ring", (decomp, ex.path3)
    got2 = run2(decomp, binary=True, impl="levels", levels=1).checksum()
    assert got2 == _binary_want(2), f"popcount 2way != xla {decomp}"
    got3 = run3(decomp, binary=True, impl="levels", levels=1).checksum()
    assert got3 == _binary_want(3), f"popcount 3way != xla {decomp}"


def check_sorenson(impl):
    """The sorenson metric on binary data: the same arithmetic, so the same
    checksum as the xla Czekanowski reference, for every impl."""
    from repro.api import SimilarityRequest

    got = _engine().run(
        SimilarityRequest(metric="sorenson", way=2, n_pv=2, impl=impl,
                          levels=1), _Vb(),
    ).checksum()
    assert got == _binary_want(2), f"sorenson {impl} != xla reference"


def check_binary_store():
    """A store-backed binary campaign stays on popcount partials."""
    from repro.api import InputSpec, SimilarityRequest

    got = _engine().run(SimilarityRequest(
        way=2, n_pv=2, impl="levels", levels=1,
        input=InputSpec(source="planes", path=_dataset("binary")),
    )).checksum()
    assert got == _binary_want(2), "binary store campaign != xla"


def check_binary_streamed():
    """A streamed binary campaign takes the streamed popcount paths and is
    bit-identical to xla for 2-way and 3-way."""
    from repro.core.tile_executor import TileExecutor
    from repro.stream import stream_threeway, stream_twoway

    dex = TileExecutor(cfg=CometConfig(impl="levels", levels=1,
                                       encoding="bitplane"), deferred=True)
    assert dex.path == "streamed-fused-popcount", dex.path
    assert dex.path3 == "streamed-fused-popcount-ring", dex.path3
    sh = _sharded("binary")
    cfg = CometConfig(n_pv=2, impl="levels", levels=1, streaming="on")
    mesh = make_comet_mesh(1, 2, 1)
    out2, _ = stream_twoway(sh, mesh, cfg)
    assert out2.checksum() == _binary_want(2), "streamed binary 2way != xla"
    out3, _ = stream_threeway(sh, mesh, cfg, stage=0)
    assert out3.checksum() == _binary_want(3), "streamed binary 3way != xla"


# -- border-block delta campaigns --------------------------------------------


@functools.cache
def _prior(impl):
    """The packed full campaign over V's first N_OLD columns."""
    base = CometConfig(impl=impl, levels=15)
    return czek2_distributed(
        _V()[:, :N_OLD], make_comet_mesh(1, 1, 1), base
    ).pack()


def check_delta(impl, decomp):
    """For the split N_OLD | N_V - N_OLD of V's columns, the delta program
    (new-vs-all rectangle + new-vs-new triangle, NO ring) merged into the
    packed prior is BIT-IDENTICAL to the full recompute, with
    border-proportional compute and zero ring payload bytes."""
    from repro.core.delta import merge_delta, twoway_delta

    want = run2(impl=impl, levels=15).checksum()
    cfg = _cfg(decomp, impl=impl, levels=15)
    rect, tri, rcfg, info = twoway_delta(_V(), N_OLD, make_comet_mesh(*decomp),
                                         cfg)
    merged = merge_delta(_prior(impl), rect, tri, N_OLD, N_V - N_OLD,
                         rcfg.out_dtype)
    assert merged.checksum() == want, f"delta {impl} != full {decomp}"
    assert info["ring_payload_bytes"] == 0, info
    assert info["computed_entries"] < info["full_entries"], info


def check_delta_streamed(decomp, budget):
    """The streamed delta over an APPENDED store dataset (byte-column
    append), also with a budget forcing more than one chunk per shard."""
    from repro.core.delta import merge_delta
    from repro.stream import stream_twoway_delta

    want = run2(impl="levels", levels=15).checksum()
    sh = _sharded("appended")
    cfg = _cfg(decomp, impl="levels", levels=15, streaming="on",
               max_host_bytes=budget)
    rect, tri, rcfg, dinfo, sinfo = stream_twoway_delta(
        sh, N_OLD, make_comet_mesh(*decomp), cfg
    )
    merged = merge_delta(_prior("levels"), rect, tri, N_OLD, N_V - N_OLD,
                         rcfg.out_dtype)
    assert merged.checksum() == want, f"streamed delta != full {decomp}"
    assert dinfo["streamed"] and dinfo["ring_payload_bytes"] == 0
    if budget:
        assert sinfo["peak_host_bytes"] <= budget, sinfo
        assert sinfo["chunks"] > sh.n_shards, sinfo


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="write one JSON record per case here")
    args = ap.parse_args()
    sink = open(args.out, "w") if args.out else None
    failed = 0
    with _TMP:
        for case_id, check, params in CASES:
            t0 = time.perf_counter()
            try:
                globals()["check_" + check](**params)
                ok, error = True, ""
            except Exception:
                ok, error = False, traceback.format_exc()[-3000:]
            seconds = time.perf_counter() - t0
            failed += not ok
            print(f"  {case_id}: {'OK' if ok else 'FAILED'} ({seconds:.2f} s)",
                  flush=True)
            if error:
                print(error, flush=True)
            if sink:
                sink.write(json.dumps({"id": case_id, "ok": ok, "error": error,
                                       "seconds": seconds}) + "\n")
                sink.flush()
    if sink:
        sink.close()
    if failed:
        print(f"{failed} of {len(CASES)} DISTRIBUTED CHECKS FAILED")
        raise SystemExit(1)
    print("ALL DISTRIBUTED CHECKS PASSED")


if __name__ == "__main__":
    main()
