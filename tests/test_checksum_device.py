"""The device checksum partials (``repro.core.checksum.partials_program``):
exact against the host's §5 checksum, bit for bit, from the uint32 limb
helpers up to whole campaigns on several devices."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.api import (
    InputSpec,
    SimilarityEngine,
    SimilarityRequest,
    SimilarityResult,
)
from repro.core import checksum as ck
from repro.core.synthetic import random_integer_vectors
from repro.obs.metrics import default_registry
from repro.store import append_dataset, write_dataset

HERE = os.path.dirname(os.path.abspath(__file__))
M32 = (1 << 32) - 1
M64 = (1 << 64) - 1
EDGE32 = [0, 1, 0xFFFF, 0x10000, 0x7FFFFFFF, 0x80000000, M32]


def _u32(x):
    return jnp.asarray(np.asarray(x, np.uint64).astype(np.uint32))


def _u64(hi, lo):
    return [(int(h) << 32) | int(lo_) for h, lo_ in
            zip(np.asarray(hi).ravel(), np.asarray(lo).ravel())]


def test_mul32_matches_python_ints():
    rng = np.random.default_rng(0)
    a = np.concatenate([np.repeat(EDGE32, len(EDGE32)),
                        rng.integers(0, 1 << 32, 500, dtype=np.uint64)])
    b = np.concatenate([np.tile(EDGE32, len(EDGE32)),
                        rng.integers(0, 1 << 32, 500, dtype=np.uint64)])
    hi, lo = ck.mul32(_u32(a), _u32(b))
    assert _u64(hi, lo) == [int(x) * int(y) for x, y in zip(a, b)]


def test_mix32_matches_mix():
    rng = np.random.default_rng(1)
    keys = np.concatenate([
        np.array([0, 1, M32, 1 << 32, (1 << 64) - 1, (1 << 63)], np.uint64),
        rng.integers(0, M64, 500, dtype=np.uint64, endpoint=True),
    ])
    hi, lo = ck.mix32(_u32(keys >> np.uint64(32)),
                      _u32(keys & np.uint64(M32)))
    assert _u64(hi, lo) == [ck._mix(int(k)) for k in keys]


def test_positions_rebuild_the_product_below_the_bound():
    """Each entry's six limb positions weigh back to mix * (bits + 1), and
    none reaches 3 * 2**16, the bound the segment size rests on."""
    rng = np.random.default_rng(2)
    words = np.concatenate([EDGE32, rng.integers(0, 1 << 32, 200,
                                                 dtype=np.uint64)])
    hi, lo, bits = np.meshgrid(words[:40], words[:40], words, indexing="ij")
    pos = [np.asarray(p, np.uint64).ravel()
           for p in ck._positions(_u32(hi), _u32(lo), _u32(bits))]
    assert all(int(p.max()) < 3 << 16 for p in pos)
    mixes = _u64(hi, lo)
    for e in range(0, len(mixes), 97):
        got = sum(int(p[e]) << (16 * k) for k, p in enumerate(pos))
        assert got == mixes[e] * (int(bits.ravel()[e]) + 1)


def _edge_values(dtype, shape, rng):
    v = (rng.random(shape) * 3).astype(dtype)
    flat = v.reshape(-1)
    width = np.uint32 if flat.itemsize == 4 else np.uint16
    ones = np.iinfo(width).max
    flat.view(width)[:2] = [0, ones]  # the smallest and largest bits
    flat[2:7] = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0], dtype)
    return v


@pytest.mark.parametrize("shape,seg", [
    ((300, 70), None),  # several segments, rows not a multiple of them
    ((3, 37, 50), 64),  # leading axis; 37 rows: one row a segment
    ((129, 129), 1 << 14),  # a segment size that divides neither axis
    ((5, 67), 16),  # rows longer than a segment: split, zero-padded
])
@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16,
                                   np.float16])
def test_partials_fold_to_the_raw_total(monkeypatch, shape, seg, dtype):
    """Random keys, masked at random, with the edge value bits 0, all
    ones, NaN, +-inf and -0.0: the folded partials equal ``_raw_total``
    and the masked count, bit for bit."""
    if seg is not None:
        monkeypatch.setattr(ck, "_SEG", seg)
    rng = np.random.default_rng([shape[-1], np.dtype(dtype).itemsize])
    keys = rng.integers(0, M64, shape, dtype=np.uint64, endpoint=True)
    vals = _edge_values(dtype, shape, rng)
    mask = rng.random(shape) < 0.8
    mask.reshape(-1)[:7] = True  # the edge values count
    parts = jax.jit(ck._segment_sums)(
        _u32(keys >> np.uint64(32)), _u32(keys & np.uint64(M32)),
        jnp.asarray(vals), jnp.asarray(mask))
    got = ck.fold_partials(np.asarray(parts)[None, None, None])
    assert got == (ck._raw_total(keys[mask], vals[mask]), int(mask.sum()))


def test_device_dtype():
    assert ck.device_dtype(jnp.float32) and ck.device_dtype(jnp.bfloat16)
    assert ck.device_dtype(np.float16)
    assert not ck.device_dtype(np.float64) and not ck.device_dtype(np.int32)


# -- whole campaigns ---------------------------------------------------------

#: (way, (n_pf, n_pv, n_pr), extra request fields): 2-way over the paper's
#: three axes; 3-way with DIAG and FACE items (n_pv 2) and VOL items
#: (n_pv 4), three stages each
CAMPAIGNS = {
    "2way-111": (2, (1, 1, 1), {}),
    "2way-122": (2, (1, 2, 2), {}),
    "2way-141": (2, (1, 4, 1), {}),
    "2way-221": (2, (2, 2, 1), {}),
    "2way-122-bf16-packed": (2, (1, 2, 2),
                             {"out_dtype": "bfloat16", "packed": True}),
    "3way-111": (3, (1, 1, 1), {"n_st": 3}),
    "3way-122": (3, (1, 2, 2), {"n_st": 3}),
    "3way-141": (3, (1, 4, 1), {"n_st": 3}),
}

_PARITY = textwrap.dedent("""
    import dataclasses, json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from repro.api import SimilarityEngine, SimilarityRequest
    from repro.core.synthetic import random_integer_vectors

    # n_v pads in every decomposition: n_vp * n_pv > n_v
    V = random_integer_vectors(40, 61, max_value=2, seed=5)
    engine = SimilarityEngine()
    out = {}
    for name, (way, (pf, pv, pr), extra) in json.loads(sys.argv[1]).items():
        res = engine.run(SimilarityRequest(
            way=way, metric="czekanowski", n_pf=pf, n_pv=pv, n_pr=pr,
            **extra), V)
        host = dataclasses.replace(res, outputs=[
            dataclasses.replace(o, device_raw=None) for o in res.outputs])
        out[name] = {
            "source": res.meta["obs"]["checksum"],
            "host_source": host.checksum_source,
            "device": [hex(res.checksum()), res.num_results()],
            "host": [hex(host.checksum()), host.num_results()],
            "stages": list(res.stages),
            "comparisons": res.meta["obs"]["comparisons"],
        }
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def parity():
    """Every campaign of CAMPAIGNS, run once on four virtual CPU devices
    (the device count is fixed before JAX starts, so in a child)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(HERE), "src")
    proc = subprocess.run(
        [sys.executable, "-c", _PARITY, json.dumps(CAMPAIGNS)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", sorted(CAMPAIGNS))
def test_campaign_device_checksum_matches_host(parity, name):
    """The device-seeded checksum() and num_results() equal the host's
    scan of the same result's tiles, in every decomposition."""
    got = parity[name]
    assert got["source"] == "device" and got["host_source"] == "host"
    assert got["device"] == got["host"]
    way, _, extra = CAMPAIGNS[name]
    assert got["stages"] == list(range(extra.get("n_st", 1)))
    assert got["comparisons"] == got["host"][1] * 40


def test_checksum_source_on_every_path(tmp_path):
    """In-memory campaigns fold the device partials; streamed, delta,
    batched and loaded results keep the host path, and say so.  The
    registry counters count the campaigns of each kind."""
    reg = default_registry()
    before = {k: reg.counter(f"checksum.{k}").value
              for k in ("device", "host")}
    engine = SimilarityEngine()
    V = random_integer_vectors(32, 14, max_value=2, seed=3)
    plane_req = dict(way=2, metric="czekanowski", impl="levels", levels=2)

    plain = engine.run(SimilarityRequest(**plane_req), V)
    assert plain.meta["obs"]["checksum"] == "device"
    threeway = engine.run(SimilarityRequest(
        way=3, metric="czekanowski", n_st=2), V)
    assert threeway.meta["obs"]["checksum"] == "device"

    saved = os.path.join(str(tmp_path), "saved")
    plain.save(saved)  # the manifest holds the device checksum
    loaded = SimilarityResult.load(saved)  # verified on the host
    assert loaded.checksum_source == "host"
    assert loaded.meta["obs"]["checksum"] == "host"
    assert loaded.checksum() == plain.checksum()

    batched = engine.run(SimilarityRequest(
        **plane_req, metrics=("sorenson",)), V)
    assert batched.meta["obs"]["checksum"] == "host"
    assert all(r.checksum_source == "host" for _, _, r in batched)

    path = os.path.join(str(tmp_path), "ds")
    write_dataset(path, V, levels=2, n_shards=2)
    sreq = SimilarityRequest(**plane_req, streaming="on", max_host_bytes=400,
                             input=InputSpec(source="planes", path=path))
    streamed = engine.run(sreq)
    assert streamed.meta["obs"]["checksum"] == "host"
    assert streamed.checksum() == plain.checksum()
    append_dataset(path, random_integer_vectors(32, 4, max_value=2, seed=4))
    delta = engine.run_delta(sreq, streamed)
    assert delta.meta["obs"]["checksum"] == "host"

    after = {k: reg.counter(f"checksum.{k}").value
             for k in ("device", "host")}
    assert after["device"] - before["device"] == 2
    assert after["host"] - before["host"] == 2 + 1 + 1  # batched: two


def test_wider_values_have_no_device_partials():
    """The launcher dispatches nothing for values outside the device
    dtypes (64-bit outputs, where JAX computes them), so those results
    keep the host path."""
    from repro.core.twoway import checksum_launcher
    from repro.parallel.mesh import make_comet_mesh

    slots = np.zeros((1, 1, 1, 5), np.uint32)
    launch = checksum_launcher(2, make_comet_mesh(1, 1, 1), slots)
    assert launch(jnp.zeros((1, 1, 1, 4, 4), jnp.int32)) is None
    assert launch(jnp.zeros((1, 1, 1, 4, 4), jnp.float32)) is not None
