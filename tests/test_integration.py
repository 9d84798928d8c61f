"""End-to-end integration tests: launchers, dedup stage, elastic restore."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def _run(args, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-m", *args], capture_output=True,
                          text=True, env=env, timeout=timeout)


@pytest.mark.slow
def test_similarity_cli_roundtrip(tmp_path):
    """The campaign launcher writes blocks + manifest with an exact checksum
    that is invariant to the decomposition (run twice, different decomps)."""
    out1 = str(tmp_path / "a")
    out2 = str(tmp_path / "b")
    r1 = _run(["repro.launch.similarity", "--way", "2", "--n-f", "64",
               "--n-v", "48", "--out", out1])
    assert r1.returncode == 0, r1.stderr[-1500:]
    r2 = _run(["repro.launch.similarity", "--way", "2", "--n-f", "64",
               "--n-v", "48", "--n-pv", "4", "--devices", "4", "--out", out2])
    assert r2.returncode == 0, r2.stderr[-1500:]
    m1 = json.load(open(os.path.join(out1, "manifest.json")))
    m2 = json.load(open(os.path.join(out2, "manifest.json")))
    assert m1["checksum"] == m2["checksum"]
    assert m1["results"] == 48 * 47 // 2 == m2["results"]


@pytest.mark.slow
def test_train_launcher_resumes(tmp_path):
    ckpt = str(tmp_path / "ck")
    r1 = _run(["repro.launch.train", "--arch", "qwen1.5-0.5b", "--smoke",
               "--steps", "4", "--batch", "2", "--seq-len", "16",
               "--ckpt-every", "2", "--ckpt-dir", ckpt])
    assert r1.returncode == 0, r1.stderr[-1500:]
    r2 = _run(["repro.launch.train", "--arch", "qwen1.5-0.5b", "--smoke",
               "--steps", "6", "--batch", "2", "--seq-len", "16",
               "--ckpt-every", "2", "--ckpt-dir", ckpt])
    assert r2.returncode == 0, r2.stderr[-1500:]
    assert "resume_step=4" in r2.stdout


def test_dedup_finds_planted_duplicates():
    from repro.data.dedup import find_near_duplicates

    rng = np.random.default_rng(1)
    docs = [rng.integers(0, 5000, 300) for _ in range(20)]
    dup = docs[3].copy()
    dup[:20] = rng.integers(0, 5000, 20)
    docs.append(dup)
    hits = find_near_duplicates(docs, 5000, threshold=0.85)
    assert any({i, j} == {3, 20} for i, j, _ in hits)


def test_elastic_restore_with_shardings(tmp_path):
    """Checkpoint saved without a mesh restores onto an explicit sharding
    (the elastic/topology-change path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.ckpt import CheckpointManager

    m = CheckpointManager(str(tmp_path), keep=1)
    tree = {"w": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones(4)}
    m.save(1, tree, blocking=True)
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,), devices=jax.devices()[:1])
    sh = {
        "w": NamedSharding(mesh, P("data", None)),
        "b": NamedSharding(mesh, P()),
    }
    got, step = m.restore(tree, shardings=sh)
    assert step == 1
    assert np.array_equal(np.asarray(got["w"]), np.arange(16.0).reshape(4, 4))
    assert got["w"].sharding == sh["w"]


def test_registry_covers_all_assigned_archs_and_paper():
    from repro.configs.registry import get_config, get_smoke_config, list_archs

    archs = list_archs()
    assert len([a for a in archs if not a.startswith("comet")]) == 10
    assert {"comet_2way", "comet_3way", "comet_2way_mxu",
            "comet_3way_mxu"} <= set(archs)
    for a in archs:
        cfg = get_config(a)
        smoke = get_smoke_config(a)
        assert cfg.name and smoke.name


def test_dryrun_cells_enumeration():
    from repro.launch.specs import applicable, cells

    cs = cells(include_comet=False)
    assert len(cs) == 32  # 40 - 8 long_500k skips
    ok, why = applicable("llama3-8b", "long_500k")
    assert not ok and "attention" in why
    ok, _ = applicable("mamba2-1.3b", "long_500k")
    assert ok
