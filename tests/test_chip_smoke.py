"""chip_smoke.py on the CPU: its numpy references agree with the engine, its
sample check catches a wrong value, and off a TPU it never reports ok."""
import contextlib
import importlib.util
import io
import json
import os

import numpy as np
import pytest

from repro.api import SimilarityEngine, SimilarityRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cohort(seed=3, n_f=40, n_v=18):
    V = np.random.default_rng(seed).integers(0, 3, (n_f, n_v)).astype(np.uint8)
    V[:, 5] = 0  # an all-zero vector: both sides must give 0, not NaN
    return V


@pytest.mark.parametrize("way", [2, 3])
def test_numpy_reference_matches_engine(smoke, way):
    V = _cohort()
    result = SimilarityEngine().run(
        SimilarityRequest(metric="czekanowski", way=way, impl="xla"), V
    )
    n_v = V.shape[1]
    if way == 2:
        I, J = np.triu_indices(n_v, 1)
        ref = smoke.pair_reference(V, I, J)
        got = result.dense()[I, J]
    else:
        I, J, K = (np.array(t) for t in zip(*[
            (i, j, k) for i in range(n_v) for j in range(i + 1, n_v)
            for k in range(j + 1, n_v)
        ]))
        ref = smoke.triple_reference(V, I, J, K)
        got = result.dense()[I, J, K]
    assert np.isfinite(ref).all()
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=0)
    rng = np.random.default_rng(0)
    assert smoke.check_sample(result, V, rng, f"{way}way") == min(
        smoke.N_SAMPLES, len(ref)
    )


def test_sample_check_catches_a_wrong_value(smoke):
    V = _cohort()
    result = SimilarityEngine().run(
        SimilarityRequest(metric="czekanowski", way=2, impl="xla"), V
    )
    out = result.outputs[0]
    out.blocks = out.blocks.copy()
    out.blocks[0, 0, 0, 0, 1] += np.float32(1e-3)
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_sample(result, V, np.random.default_rng(0), "2way")


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_cpu_rehearsal_never_reports_ok(smoke):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = smoke.main(["--cpu-rehearsal"])
    lines = out.getvalue().splitlines()
    assert rc == 1
    assert any(line.startswith("[2way-levels] path=fused-levels") for line in lines)
    assert any(line.startswith("[2way-binary] path=fused-popcount") for line in lines)
    assert any(line.startswith("[3way-plane-ring] path3=fused-levels-ring")
               for line in lines)
    assert json.loads(lines[-1]) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
