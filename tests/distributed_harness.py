"""Multi-device validation harness (run as a subprocess with 8 CPU devices).

Reproduces the paper's §5 validation: identical synthetic input, many
parallel decompositions (n_pf, n_pv, n_pr, n_st), and asserts

  1. every decomposition computes exactly the unique result set,
  2. values are BIT-FOR-BIT identical across decompositions (exact integer
     inputs => exact numerators => identical IEEE divisions),
  3. values match the O(n^2)/O(n^3) numpy oracles.

Invoked by tests/test_distributed.py; standalone: python distributed_harness.py
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import numpy as np  # noqa: E402

from repro.core.metrics import czek2_metric_np, czek3_metric_np  # noqa: E402
from repro.core.synthetic import random_integer_vectors  # noqa: E402
from repro.core.threeway import czek3_distributed  # noqa: E402
from repro.core.twoway import CometConfig, czek2_distributed  # noqa: E402
from repro.core import checksum as ck  # noqa: E402
from repro.parallel.mesh import make_comet_mesh  # noqa: E402

N_F, N_V = 24, 24


def check_2way(V, ref_dense):
    ref_checksum = None
    configs = [
        (1, 1, 1),
        (1, 2, 1),
        (1, 4, 1),
        (1, 8, 1),
        (2, 2, 1),
        (1, 2, 2),
        (2, 2, 2),
        (1, 4, 2),
        (4, 2, 1),
    ]
    for n_pf, n_pv, n_pr in configs:
        cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr)
        mesh = make_comet_mesh(n_pf, n_pv, n_pr)
        out = czek2_distributed(V, mesh, cfg)
        assert out.num_pairs() == N_V * (N_V - 1) // 2, (
            f"2way {cfg}: {out.num_pairs()} pairs"
        )
        d = out.dense()
        iu = np.triu_indices(N_V, 1)
        np.testing.assert_allclose(d[iu], ref_dense[iu], rtol=1e-6,
                                   err_msg=f"2way {cfg} vs oracle")
        c = out.checksum()
        if ref_checksum is None:
            ref_checksum = c
        assert c == ref_checksum, f"2way checksum mismatch for {cfg}"
        # the device partials fold to the host scan's checksum and count
        assert out.device_raw[1] == out.num_pairs(), f"2way {cfg} count"
        assert ck.combine([out.device_raw]) == c, f"2way {cfg} device"
        print(f"  2way pf={n_pf} pv={n_pv} pr={n_pr}: OK ({hex(c)[:14]})")
    # pallas fused-epilogue path inside the distributed engine (interpret
    # mode): in-kernel assembly + triangular diagonal-block schedule must be
    # bit-identical to the XLA out-of-kernel path
    for n_pf, n_pv, n_pr in [(1, 2, 1), (1, 4, 1), (1, 2, 2)]:
        cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, impl="pallas")
        out = czek2_distributed(V, make_comet_mesh(n_pf, n_pv, n_pr), cfg)
        assert out.checksum() == ref_checksum, (
            f"pallas impl changed results ({n_pf},{n_pv},{n_pr})"
        )
        print(f"  2way pallas impl pv={n_pv} pr={n_pr}: OK")
    # packed upper-triangular storage: same entries, same checksum
    packed = out.pack()
    assert packed.storage == "packed"
    assert packed.checksum() == ref_checksum, "packing changed results"
    print("  2way packed storage: OK")
    # levels impl is exact for small-integer data
    cfg = CometConfig(n_pf=1, n_pv=2, n_pr=1, impl="levels_xla", levels=15)
    out = czek2_distributed(V, make_comet_mesh(1, 2, 1), cfg)
    assert out.checksum() == ref_checksum, "levels impl not bit-exact"
    print("  2way levels impl: OK")
    # fused-levels campaign path: packed bit-planes encoded once, ring-
    # carried, MXU plane kernels with in-kernel epilogue + triangular
    # diagonal schedule; n_pf=2 keeps the fused MXU kernels but emits raw
    # psummed partials assembled by the out-of-kernel merge epilogue.
    # All bit-identical to the xla reference.
    for n_pf, n_pv, n_pr in [(1, 2, 1), (1, 4, 1), (1, 2, 2), (2, 2, 1)]:
        cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, impl="levels",
                          levels=15)
        out = czek2_distributed(V, make_comet_mesh(n_pf, n_pv, n_pr), cfg)
        assert out.checksum() == ref_checksum, (
            f"fused-levels changed results ({n_pf},{n_pv},{n_pr})"
        )
        print(f"  2way fused-levels pf={n_pf} pv={n_pv} pr={n_pr}: OK")


def check_3way(V, ref_dense):
    ref_checksum = None
    configs = [  # (n_pf, n_pv, n_pr, n_st)
        (1, 1, 1, 1),
        (1, 2, 1, 1),
        (1, 4, 1, 1),
        (2, 2, 1, 1),
        (1, 2, 2, 1),
        (1, 2, 4, 1),
        (2, 2, 2, 1),
    ]
    n_unique = N_V * (N_V - 1) * (N_V - 2) // 6
    for n_pf, n_pv, n_pr, n_st in configs:
        cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, n_st=n_st)
        mesh = make_comet_mesh(n_pf, n_pv, n_pr)
        out = czek3_distributed(V, mesh, cfg, stage=0)
        assert out.num_triples() == n_unique, (
            f"3way {cfg}: {out.num_triples()} != {n_unique}"
        )
        d = out.dense()
        errs = []
        for i in range(N_V):
            for j in range(i + 1, N_V):
                for k in range(j + 1, N_V):
                    errs.append(abs(d[i, j, k] - ref_dense[i, j, k]))
        assert max(errs) < 1e-6, f"3way {cfg}: max err {max(errs)}"
        c = out.checksum()
        if ref_checksum is None:
            ref_checksum = c
        assert c == ref_checksum, f"3way checksum mismatch for {cfg}"
        assert out.device_raw[1] == n_unique, f"3way {cfg} count"
        assert ck.combine([out.device_raw]) == c, f"3way {cfg} device"
        print(f"  3way pf={n_pf} pv={n_pv} pr={n_pr}: OK ({hex(c)[:14]})")

    # pallas path: fused X_j pipeline-step kernels, bit-identical numerators
    cfg = CometConfig(n_pf=1, n_pv=2, n_pr=1, impl="pallas")
    out = czek3_distributed(V, make_comet_mesh(1, 2, 1), cfg, stage=0)
    assert out.checksum() == ref_checksum, "3way pallas impl changed results"
    print("  3way pallas impl: OK")

    # packed bit-plane ring (path3 == "fused-levels-ring"): planes encoded
    # once before shard_map, ring-carried through Phases B/C, pipeline
    # slices fed to the level-decomposed kernels as byte-range views.
    # n_pf=2 shards the BYTE axis over "pf"; all bit-identical to xla.
    for n_pf, n_pv, n_pr in [(1, 2, 1), (2, 2, 1), (1, 2, 2), (1, 4, 1)]:
        cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, impl="levels",
                          levels=15)
        out = czek3_distributed(V, make_comet_mesh(n_pf, n_pv, n_pr), cfg,
                                stage=0)
        assert out.checksum() == ref_checksum, (
            f"3way plane ring changed results ({n_pf},{n_pv},{n_pr})"
        )
        print(f"  3way fused-levels-ring pf={n_pf} pv={n_pv} pr={n_pr}: OK")

    # plane ring with the UNFUSED slice contraction (impl=levels_xla):
    # the ring still carries packed planes, X_j is a packed AND
    cfg = CometConfig(n_pf=2, n_pv=2, n_pr=1, impl="levels_xla", levels=15)
    out = czek3_distributed(V, make_comet_mesh(2, 2, 1), cfg, stage=0)
    assert out.checksum() == ref_checksum, "3way levels_xla plane ring"
    print("  3way plane ring unfused (levels_xla) pf=2 pv=2: OK")

    # encoding="none" opt-out keeps the value ring + per-slice encode
    cfg = CometConfig(n_pf=1, n_pv=2, n_pr=1, impl="levels", levels=15,
                      encoding="none")
    out = czek3_distributed(V, make_comet_mesh(1, 2, 1), cfg, stage=0)
    assert out.checksum() == ref_checksum, "3way value-ring fallback"
    print("  3way fused-levels value ring (encoding=none): OK")

    # staging: union over stages == the full result set, bit-identical
    cfg = CometConfig(n_pf=1, n_pv=2, n_pr=1, n_st=2)
    mesh = make_comet_mesh(1, 2, 1)
    parts = []
    total = 0
    for stage in range(2):
        out = czek3_distributed(V, mesh, cfg, stage=stage)
        total += out.num_triples()
        parts.extend(ck.raw_triples(I, J, K, W) for I, J, K, W in out.entries())
    assert total == n_unique, f"staged union {total} != {n_unique}"
    assert ck.combine(parts) == ref_checksum, "staged checksum mismatch"
    print("  3way staging n_st=2: OK")


def check_engine_parity(V):
    """The unified SimilarityEngine must reproduce the exact per-campaign
    checksums of the direct czek2/czek3 paths for several decompositions
    (the api_redesign acceptance contract), and the registry's CCC metric
    must be decomposition-invariant and match its numpy oracle."""
    from repro.api import SimilarityEngine, SimilarityRequest, get_metric

    engine = SimilarityEngine()
    for n_pf, n_pv, n_pr in [(1, 1, 1), (1, 4, 1), (2, 2, 2), (1, 2, 2)]:
        cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr)
        mesh = make_comet_mesh(n_pf, n_pv, n_pr)
        want2 = czek2_distributed(V, mesh, cfg).checksum()
        got2 = engine.run(
            SimilarityRequest(way=2, n_pf=n_pf, n_pv=n_pv, n_pr=n_pr), V
        ).checksum()
        assert got2 == want2, f"engine 2way checksum != direct ({n_pf},{n_pv},{n_pr})"
        want3 = czek3_distributed(V, mesh, cfg, stage=0).checksum()
        got3 = engine.run(
            SimilarityRequest(way=3, n_pf=n_pf, n_pv=n_pv, n_pr=n_pr), V
        ).checksum()
        assert got3 == want3, f"engine 3way checksum != direct ({n_pf},{n_pv},{n_pr})"
        print(f"  engine parity pf={n_pf} pv={n_pv} pr={n_pr}: OK")

    # CCC: decomposition-invariant checksum + oracle match (fp32 tolerance)
    ccc_ref = None
    oracle = get_metric("ccc").oracle2(V).astype(np.float32)
    iu = np.triu_indices(V.shape[1], 1)
    for n_pf, n_pv, n_pr in [(1, 1, 1), (1, 4, 1), (2, 2, 2)]:
        out = engine.run(
            SimilarityRequest(metric="ccc", way=2,
                              n_pf=n_pf, n_pv=n_pv, n_pr=n_pr), V
        )
        d = out.dense()
        np.testing.assert_allclose(d[iu], oracle[iu], rtol=1e-5,
                                   err_msg=f"ccc ({n_pf},{n_pv},{n_pr})")
        c = out.checksum()
        if ccc_ref is None:
            ccc_ref = c
        assert c == ccc_ref, "ccc checksum varies with decomposition"
        print(f"  ccc pf={n_pf} pv={n_pv} pr={n_pr}: OK ({hex(c)[:14]})")

    # the generated fused kernel serves CCC too (metric-generic epilogue):
    # integer data -> exact numerators -> bit-identical to the XLA path
    out = engine.run(
        SimilarityRequest(metric="ccc", way=2, n_pv=2, impl="pallas"), V
    )
    assert out.checksum() == ccc_ref, "ccc pallas fused path changed results"
    print("  ccc pallas fused epilogue: OK")


def check_plane_store(V):
    """Campaigns loaded from a repro.store dataset (pre-encoded packed
    planes, mmap -> ring) must be bit-identical to the in-memory matrix on
    BOTH engines across decompositions — including byte-axis "pf" sharding
    of the on-disk field shards — and must never run the host encoder."""
    import tempfile

    import repro.kernels.mgemm_levels as mgemm_levels
    from repro.api import InputSpec, SimilarityEngine, SimilarityRequest
    from repro.store import DatasetReader, write_dataset

    with tempfile.TemporaryDirectory() as tmp:
        write_dataset(tmp, V, levels=15, n_shards=2)
        DatasetReader(tmp).validate()
        engine = SimilarityEngine()
        spec = InputSpec(source="planes", path=tmp)

        calls = {"n": 0}
        orig = mgemm_levels.encode_bitplanes_np

        def counted(*args, **kwargs):
            calls["n"] += 1
            return orig(*args, **kwargs)

        mgemm_levels.encode_bitplanes_np = counted
        try:
            for way in (2, 3):
                ref = None
                for n_pf, n_pv, n_pr in [(1, 2, 1), (2, 2, 1), (1, 4, 1)]:
                    base = SimilarityRequest(
                        way=way, impl="levels", levels=15,
                        n_pf=n_pf, n_pv=n_pv, n_pr=n_pr,
                    )
                    before = calls["n"]
                    want = engine.run(base, V).checksum()
                    assert calls["n"] > before, "in-memory path should encode"
                    before = calls["n"]
                    got = engine.run(
                        SimilarityRequest(
                            way=way, impl="levels", levels=15,
                            n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, input=spec,
                        )
                    ).checksum()
                    assert calls["n"] == before, (
                        f"{way}-way plane-store campaign ran the host encoder"
                    )
                    assert got == want, (
                        f"{way}-way store checksum != in-memory "
                        f"({n_pf},{n_pv},{n_pr})"
                    )
                    if ref is None:
                        ref = got
                    assert got == ref, f"{way}-way store checksum varies"
                    print(f"  {way}-way store pf={n_pf} pv={n_pv} pr={n_pr}: "
                          f"OK (zero-encode)")
        finally:
            mgemm_levels.encode_bitplanes_np = orig


def check_streamed(V):
    """Streamed campaigns (repro.stream) under multi-device meshes: the
    chunked deferred-flush pipeline + cross-shard merge epilogue must be
    bit-identical to the in-memory engines for 2-way AND 3-way, including
    byte-axis "pf" sharding of the chunks and a budget that forces >1
    chunk per shard."""
    import tempfile

    from repro.store import DatasetReader, write_dataset
    from repro.stream import stream_twoway, stream_threeway

    want2 = czek2_distributed(
        V, make_comet_mesh(1, 1, 1), CometConfig()).checksum()
    want3 = czek3_distributed(
        V, make_comet_mesh(1, 1, 1), CometConfig(), stage=0).checksum()
    with tempfile.TemporaryDirectory() as tmp:
        write_dataset(tmp, V, levels=15, n_shards=2)
        sh = DatasetReader(tmp).sharded()
        for n_pf, n_pv, n_pr, budget in [
            (1, 2, 1, 0),          # shard-per-chunk default
            (2, 2, 1, 0),          # byte axis split over "pf" per chunk
            # tight budget -> 1-byte chunks (2 * levels * n_v * 1 = 720
            # bytes double-buffered fits; a whole shard would not)
            (1, 2, 2, 800),
        ]:
            cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr,
                              impl="levels", levels=15, streaming="on",
                              max_host_bytes=budget)
            mesh = make_comet_mesh(n_pf, n_pv, n_pr)
            out2, info2 = stream_twoway(sh, mesh, cfg)
            assert out2.checksum() == want2, (
                f"streamed 2way != in-memory ({n_pf},{n_pv},{n_pr})"
            )
            out3, info3 = stream_threeway(sh, mesh, cfg, stage=0)
            assert out3.checksum() == want3, (
                f"streamed 3way != in-memory ({n_pf},{n_pv},{n_pr})"
            )
            if budget:
                assert info2["peak_host_bytes"] <= budget, info2
                assert info2["chunks"] > sh.n_shards, info2
            print(f"  streamed pf={n_pf} pv={n_pv} pr={n_pr} "
                  f"chunks={info2['chunks']}: OK")


def check_binary_popcount(Vb):
    """Binary ({0,1}) campaigns: levels=1 resolves to the popcount bit-GEMM
    (path == "fused-popcount") on BOTH engines, in-memory / store-backed /
    streamed, with checksums bit-identical to impl="xla" across
    decompositions — and the sorenson metric rides the same machinery."""
    import tempfile

    from repro.api import InputSpec, SimilarityEngine, SimilarityRequest
    from repro.core.metric_spec import CZEKANOWSKI
    from repro.core.tile_executor import TileExecutor
    from repro.core.twoway import resolve_config
    from repro.store import DatasetReader, write_dataset
    from repro.stream import stream_twoway, stream_threeway

    want2 = czek2_distributed(
        Vb, make_comet_mesh(1, 1, 1), CometConfig(impl="xla", levels=1)
    ).checksum()
    want3 = czek3_distributed(
        Vb, make_comet_mesh(1, 1, 1), CometConfig(impl="xla", levels=1),
        stage=0,
    ).checksum()

    # in-memory, >= 3 decompositions incl. the n_pf=2 merge epilogue
    for n_pf, n_pv, n_pr in [(1, 2, 1), (1, 2, 2), (2, 2, 1), (1, 4, 1)]:
        cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, impl="levels",
                          levels=1)
        rcfg = resolve_config(cfg, Vb, CZEKANOWSKI)
        ex = TileExecutor(cfg=rcfg, metric=CZEKANOWSKI, axis=None)
        assert ex.path == "fused-popcount", (n_pf, ex.path)
        assert ex.path3 == "fused-popcount-ring", (n_pf, ex.path3)
        mesh = make_comet_mesh(n_pf, n_pv, n_pr)
        out2 = czek2_distributed(Vb, mesh, cfg)
        assert out2.checksum() == want2, (
            f"popcount 2way != xla ({n_pf},{n_pv},{n_pr})"
        )
        out3 = czek3_distributed(Vb, mesh, cfg, stage=0)
        assert out3.checksum() == want3, (
            f"popcount 3way != xla ({n_pf},{n_pv},{n_pr})"
        )
        print(f"  binary popcount pf={n_pf} pv={n_pv} pr={n_pr}: OK "
              f"(2way+3way)")

    # sorenson: same arithmetic on binary data -> same checksums, every impl
    engine = SimilarityEngine()
    for impl, levels in [("xla", 1), ("pallas", 1), ("levels", 1),
                         ("levels_xla", 1)]:
        got = engine.run(
            SimilarityRequest(metric="sorenson", way=2, n_pv=2, impl=impl,
                              levels=levels), Vb,
        ).checksum()
        assert got == want2, f"sorenson {impl} != xla reference"
    print("  sorenson parity (xla/pallas/popcount/levels_xla): OK")

    # store-backed + streamed binary campaigns stay on popcount partials
    with tempfile.TemporaryDirectory() as tmp:
        write_dataset(tmp, Vb, levels=1, n_shards=2)
        got = engine.run(
            SimilarityRequest(
                way=2, n_pv=2, impl="levels", levels=1,
                input=InputSpec(source="planes", path=tmp),
            )
        ).checksum()
        assert got == want2, "binary store campaign != xla"
        print("  binary store-backed campaign: OK")
        sh = DatasetReader(tmp).sharded()
        cfg = CometConfig(n_pv=2, impl="levels", levels=1, streaming="on")
        dex = TileExecutor(cfg=CometConfig(impl="levels", levels=1,
                                           encoding="bitplane"),
                           deferred=True)
        assert dex.path == "streamed-fused-popcount", dex.path
        assert dex.path3 == "streamed-fused-popcount-ring", dex.path3
        mesh = make_comet_mesh(1, 2, 1)
        out2, info2 = stream_twoway(sh, mesh, cfg)
        assert out2.checksum() == want2, "streamed binary 2way != xla"
        out3, info3 = stream_threeway(sh, mesh, cfg, stage=0)
        assert out3.checksum() == want3, "streamed binary 3way != xla"
        print(f"  binary streamed chunks={info2['chunks']}: OK (2way+3way)")


def check_delta(V):
    """Border-block delta campaigns under multi-device meshes: for a split
    n_old | n_new of V's columns, compute the prior on [0, n_old), run the
    delta program (new-vs-all rectangle + new-vs-new triangle, NO ring)
    across decompositions — including the n_pf=2 merge-epilogue case and a
    streamed run — merge into the packed prior, and require checksums
    BIT-IDENTICAL to the full recompute.  Accounting must report
    border-proportional compute with zero ring payload bytes."""
    import tempfile

    from repro.core.delta import merge_delta, twoway_delta
    from repro.store import DatasetReader, append_dataset, write_dataset
    from repro.stream import stream_twoway_delta

    n_old = 15
    m = N_V - n_old
    for impl, levels in [("xla", 15), ("levels", 15)]:
        base = CometConfig(impl=impl, levels=levels)
        want = czek2_distributed(V, make_comet_mesh(1, 1, 1), base).checksum()
        prior = czek2_distributed(
            V[:, :n_old], make_comet_mesh(1, 1, 1), base
        ).pack()
        for n_pf, n_pv, n_pr in [(1, 1, 1), (1, 2, 2), (2, 2, 1), (1, 4, 2),
                                 (2, 2, 2)]:
            cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, impl=impl,
                              levels=levels)
            mesh = make_comet_mesh(n_pf, n_pv, n_pr)
            rect, tri, rcfg, info = twoway_delta(V, n_old, mesh, cfg)
            merged = merge_delta(prior, rect, tri, n_old, m, rcfg.out_dtype)
            assert merged.checksum() == want, (
                f"delta {impl} != full ({n_pf},{n_pv},{n_pr})"
            )
            assert info["ring_payload_bytes"] == 0, info
            assert info["computed_entries"] < info["full_entries"], info
            print(f"  delta {impl} pf={n_pf} pv={n_pv} pr={n_pr}: OK "
                  f"({info['computed_entries']}/{info['full_entries']} "
                  f"entries)")

    # streamed delta over an APPENDED store dataset (byte-column append),
    # multi-device + a budget forcing >1 chunk per shard, incl. the n_pf=2
    # merge-epilogue case
    base = CometConfig(impl="levels", levels=15)
    want = czek2_distributed(V, make_comet_mesh(1, 1, 1), base).checksum()
    prior = czek2_distributed(
        V[:, :n_old], make_comet_mesh(1, 1, 1), base
    ).pack()
    with tempfile.TemporaryDirectory() as tmp:
        write_dataset(tmp, V[:, :n_old], levels=15, n_shards=2)
        append_dataset(tmp, V[:, n_old:])
        sh = DatasetReader(tmp).sharded()
        for n_pf, n_pv, n_pr, budget in [(1, 2, 1, 0), (2, 2, 1, 0),
                                         (1, 2, 2, 800)]:
            cfg = CometConfig(n_pf=n_pf, n_pv=n_pv, n_pr=n_pr, impl="levels",
                              levels=15, streaming="on",
                              max_host_bytes=budget)
            mesh = make_comet_mesh(n_pf, n_pv, n_pr)
            rect, tri, rcfg, dinfo, sinfo = stream_twoway_delta(
                sh, n_old, mesh, cfg
            )
            merged = merge_delta(prior, rect, tri, n_old, m, rcfg.out_dtype)
            assert merged.checksum() == want, (
                f"streamed delta != full ({n_pf},{n_pv},{n_pr})"
            )
            assert dinfo["streamed"] and dinfo["ring_payload_bytes"] == 0
            if budget:
                assert sinfo["peak_host_bytes"] <= budget, sinfo
                assert sinfo["chunks"] > sh.n_shards, sinfo
            print(f"  streamed delta pf={n_pf} pv={n_pv} pr={n_pr} "
                  f"chunks={sinfo['chunks']}: OK")


def main():
    V = random_integer_vectors(N_F, N_V, max_value=15, seed=42)
    print("2-way decomposition invariance:")
    check_2way(V, czek2_metric_np(V).astype(np.float32))
    print("3-way decomposition invariance:")
    check_3way(V, czek3_metric_np(V).astype(np.float32))
    print("unified engine parity (api redesign contract):")
    check_engine_parity(V)
    print("plane-store zero-encode campaigns (repro.store):")
    check_plane_store(V)
    print("streamed campaigns (repro.stream):")
    check_streamed(V)
    print("binary popcount campaigns (kernels/popgemm):")
    check_binary_popcount(random_integer_vectors(N_F, N_V, max_value=1,
                                                 seed=43))
    print("border-block delta campaigns (repro.core.delta):")
    check_delta(V)
    print("ALL DISTRIBUTED CHECKS PASSED")


if __name__ == "__main__":
    main()
