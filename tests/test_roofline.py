"""Loop-aware HLO cost model tests: scan trip counts, dot flops, collective
accounting — the foundation of the §Roofline numbers."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo import analyze_hlo, shape_bytes, shape_elems


def _cost(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return analyze_hlo(compiled.as_text(), 1), compiled


def test_shape_parsing():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[8]") == 16
    assert shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert shape_elems("f32[3,5]") == 15


def test_dot_flops_exact():
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    y = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    cost, _ = _cost(lambda a, b: a @ b, x, y)
    assert cost.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.01)


def test_scan_multiplies_body_cost():
    """THE critical property: while bodies are priced x trip count (XLA's own
    cost_analysis counts them once)."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(a):
        out, _ = jax.lax.scan(lambda c, _: (c @ c, None), a, None, length=10)
        return out

    cost, compiled = _cost(f, x)
    one = 2 * 128**3
    assert cost.flops == pytest.approx(10 * one, rel=0.05)
    assert float(compiled.cost_analysis()["flops"]) == pytest.approx(one, rel=0.05)


def test_nested_scan_multiplies():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        out, _ = jax.lax.scan(outer, a, None, length=3)
        return out

    cost, _ = _cost(f, x)
    assert cost.flops == pytest.approx(12 * 2 * 64**3, rel=0.05)


def test_elementwise_flops_counted():
    x = jax.ShapeDtypeStruct((1000,), jnp.float32)
    cost, _ = _cost(lambda a: jnp.minimum(a, 2.0) + a, x)
    # min + add = 2 flops/elem (allow fusion-dependent slack)
    assert 1000 <= cost.flops <= 5000


def test_bytes_nonzero_and_reasonable():
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    cost, _ = _cost(lambda a: (a @ a) * 2.0, x)
    lo = 3 * 256 * 256 * 4  # read a twice-ish + write result
    assert cost.bytes >= lo


def test_collectives_in_loop_multiplied():
    import subprocess, sys, os, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.roofline.hlo import analyze_hlo
        mesh = jax.make_mesh((4,), ("x",), (AxisType.Auto,))
        def prog(v):
            def body(i, c):
                return jax.lax.ppermute(c, "x", [(a, (a+1)%4) for a in range(4)])
            return jax.lax.fori_loop(0, 7, body, v)
        f = jax.shard_map(prog, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                          check_vma=False)
        x = jax.ShapeDtypeStruct((4, 100), jnp.float32)
        c = jax.jit(f).lower(x).compile()
        cost = analyze_hlo(c.as_text(), 4)
        n = cost.counts.get("collective-permute", 0)
        assert n == 7, f"expected 7 permutes, got {n}"
        per = 100 * 4  # one shard
        assert abs(cost.operand_bytes["collective-permute"] - 7 * per) < per
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert "OK" in r.stdout, r.stderr[-2000:]
