"""How the program meets a device: where the compile cache goes, the order
``make_comet_mesh`` walks the chips in, and the refusal to start a JAX
child once this process holds an accelerator."""
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import pytest

from repro.launch.similarity import CHECKOUT_CACHE_DIR
from repro.parallel.mesh import _ici_order

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_PROBE = textwrap.dedent("""
    import os, jax, jax.numpy as jnp
    from repro.launch.similarity import init_compile_cache
    print(init_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
""")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               HOME=str(tmp_path / "home"), TMPDIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    want = CHECKOUT_CACHE_DIR
    if from_env:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    assert want == CHECKOUT_CACHE_DIR or os.listdir(want), "nothing cached"
    assert CHECKOUT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")


def test_ici_order_is_a_ring_on_a_2x2():
    chips = [SimpleNamespace(id=i, coords=(x, y, 0))
             for i, (x, y) in enumerate([(0, 0), (1, 0), (0, 1), (1, 1)])]
    walk = [d.coords[:2] for d in _ici_order(chips)]
    assert walk == [(0, 0), (1, 0), (1, 1), (0, 1)]
    # consecutive chips, and the last and the first, are one link apart
    for a, b in zip(walk, walk[1:] + walk[:1]):
        assert abs(a[0] - b[0]) + abs(a[1] - b[1]) == 1


def test_ici_order_keeps_coordinateless_devices():
    devices = jax.devices()
    assert _ici_order(devices) == devices


def test_scaling_sweep_refuses_off_the_cpu(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    from benchmarks import bench_scaling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "run", None)  # must not be reached
    with pytest.raises(RuntimeError, match="holds the tpu backend"):
        bench_scaling.run_harness()
