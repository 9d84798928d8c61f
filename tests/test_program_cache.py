"""The process-wide compiled-program cache (``repro.core.twoway._cached_jit``)
behind every campaign entry point.

A repeated campaign of the same geometry reuses its jitted program (no new
entry, a ``program_cache.hit``) and returns bit-identical blocks; a stage,
a payload kind or a batch never borrows another's program: stages 0, 1, 0
of one cohort give what a cold cache gives, a ``PackedPlanes`` payload
and the value matrix of the same cohort on the value ring
(``encoding="none"``) keep entries of their own, and so does a program
traced with a stand-in for the ring's ``jax.lax.ppermute``.  Every
case runs on (1,1,1) and on a (1,2,2) mesh of four virtual CPU devices, in
one child process (the device count is fixed before JAX starts).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

DECOMPS = {"111": (1, 1, 1), "122": (1, 2, 2)}
CASES = ("repeat", "stages", "packed-vs-values", "stand-in-exchange",
         "threeway-batched", "twoway-batched")

_CHILD = textwrap.dedent("""
    import dataclasses, hashlib, json, os, sys
    from unittest import mock
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.api import get_metric
    from repro.core import twoway
    from repro.core.synthetic import random_integer_vectors
    from repro.core.threeway import threeway_batched, threeway_distributed
    from repro.core.twoway import CometConfig, twoway_batched
    from repro.kernels.mgemm_levels import encode_bitplanes_np
    from repro.kernels.mgemm_levels.planes import PackedPlanes
    from repro.obs.metrics import default_registry
    from repro.parallel.mesh import make_comet_mesh

    V = random_integer_vectors(40, 30, max_value=2, seed=5)
    packed = PackedPlanes(encode_bitplanes_np(V, 2), n_f=V.shape[0])
    specs = [get_metric(n) for n in ("czekanowski", "sorenson", "ccc")]
    reg = default_registry()

    def keys():
        return set(twoway._PROGRAM_CACHE or ())

    def tag(key):
        # 3-way keys carry the stage in slot 4
        return f"{key[0]}:{key[4]}" if key[0].startswith("threeway") \\
            else key[0]

    def digest(out):
        h = hashlib.sha256()
        for o in out if isinstance(out, list) else [out]:
            h.update(str((o.blocks.dtype, o.blocks.shape,
                          getattr(o, "device_raw", None))).encode())
            h.update(o.blocks.tobytes())
        return h.hexdigest()

    def run(call, cold=False):
        if cold and twoway._PROGRAM_CACHE is not None:
            twoway._PROGRAM_CACHE.clear()
        before = keys()
        hits = reg.counter("program_cache.hit").value
        misses = reg.counter("program_cache.miss").value
        out = call()
        if isinstance(out, tuple):  # batched: (outputs, accounting)
            out = out[0]
        first = out[0] if isinstance(out, list) else out
        return {
            "digest": digest(out),
            "checksum": hex(first.checksum()),
            "hits": reg.counter("program_cache.hit").value - hits,
            "misses": reg.counter("program_cache.miss").value - misses,
            "new": sorted(tag(k) for k in keys() - before),
        }

    def run_patched(call):
        with mock.patch.object(jax.lax, "ppermute",
                               lambda x, axis_name, perm: x):
            return run(call)

    facts = {}
    for name, (pf, pv, pr) in json.loads(sys.argv[1]).items():
        mesh = make_comet_mesh(pf, pv, pr)
        cfg = CometConfig(n_pf=pf, n_pv=pv, n_pr=pr, n_st=2, impl="levels",
                          levels=2)
        cfg2 = dataclasses.replace(cfg, n_st=1)

        def three(stage, payload=V, c=cfg):
            return lambda: threeway_distributed(payload, mesh, c,
                                                stage=stage)

        facts[name] = {
            "repeat": [run(three(0)), run(three(0))],
            "stages": [run(three(0)), run(three(1)), run(three(0)),
                       run(three(0), cold=True), run(three(1), cold=True),
                       run(three(0), cold=True)],
            "packed-vs-values": [
                run(three(0, packed), cold=True),
                run(three(0, c=dataclasses.replace(cfg, encoding="none"))),
            ],
            "stand-in-exchange": [run(three(0)), run_patched(three(0)),
                                  run(three(0))],
            "threeway-batched": [
                run(lambda: threeway_batched(V, mesh, cfg, specs, stage=0))
                for _ in range(2)],
            "twoway-batched": [
                run(lambda: twoway_batched(V, mesh, cfg2, specs))
                for _ in range(2)],
        }
    print(json.dumps(facts))
""")


@pytest.fixture(scope="module")
def facts():
    """Every case of CASES on every decomposition of DECOMPS, run once."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(HERE), "src")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, json.dumps(DECOMPS)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _assert_repeat_hits(first, again, tag):
    """``again`` reused ``first``'s program: a hit, no miss, no new entry,
    bit-identical outputs."""
    assert tag in first["new"] and first["misses"] >= 1
    assert again["new"] == [] and again["misses"] == 0
    assert again["hits"] >= 1
    assert again["digest"] == first["digest"]


@pytest.mark.parametrize("decomp", sorted(DECOMPS))
@pytest.mark.parametrize("case", CASES)
def test_program_cache(facts, case, decomp):
    got = facts[decomp][case]
    if case == "repeat":
        # the device checksum program is cached beside the campaign's
        _assert_repeat_hits(*got, "threeway:0")
        assert got[1]["hits"] == 2
    elif case == "stages":
        s0, s1, s0b, cold0, cold1, cold0b = got
        assert s0["new"] == [] and s0["misses"] == 0
        assert s1["new"] == ["threeway:1"]
        assert s0b["new"] == [] and s0b["misses"] == 0
        # each equals its cold-cache run; the stages differ from each other
        assert s0["digest"] == cold0["digest"] == cold0b["digest"]
        assert s0b["digest"] == cold0["digest"]
        assert s1["digest"] == cold1["digest"] != s0["digest"]
    elif case == "packed-vs-values":
        plane, value = got
        assert "threeway:0" in plane["new"] and "threeway:0" in value["new"]
        assert value["misses"] >= 1
        assert plane["checksum"] == value["checksum"]
    elif case == "stand-in-exchange":
        # a program traced with a stand-in ``ppermute`` is never served
        # with the real one, nor the other way round
        real, stand_in, again = got
        assert "threeway:0" in stand_in["new"] and again["new"] == []
        assert again["digest"] == real["digest"]
        if DECOMPS[decomp][1] > 1:  # only a ring of n_pv > 1 exchanges
            assert stand_in["digest"] != real["digest"]
    else:
        tag = "threeway-batched:0" if case == "threeway-batched" \
            else "twoway-batched"
        _assert_repeat_hits(*got, tag)
