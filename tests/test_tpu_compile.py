"""Compile the campaign's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler that JAX ships lowers each kernel for a
``v5e:2x2`` topology description and refuses what Mosaic cannot lower (block
shapes off the (8, 128) tiling, primitives with no TPU rule) — faults the
CPU interpret-mode tests cannot see.  Nothing runs, so these tests say
nothing about results or speed.

Widths are the paper's per-rank shapes: 2-way §6.6 (n_f=10,000,
n_vp=12,288, SNP levels {0,1,2}) and one stage of 3-way §6.7 (n_f=20,000,
n_vp=2,880, L=10 pipeline columns at n_st=48), plus one ragged shape each.
Tiles are the kernels' defaults, the ones the ``TileExecutor`` picks at
these widths.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.metric_spec import czek_assemble_tile
from repro.kernels.czek3.kernel import (
    threeway_batch_levels_pallas,
    threeway_batch_pallas,
)
from repro.kernels.mgemm.kernel import metric2_pallas, metric2_tri_pallas
from repro.kernels.mgemm_levels.kernel import (
    metric2_levels_pallas,
    metric2_levels_tri_pallas,
)
from repro.kernels.popgemm.kernel import (
    metric2_pop_pallas,
    metric2_pop_tri_pallas,
    threeway_batch_pop_pallas,
)

TWO_WAY = {"6.6": (10_000, 12_288), "ragged": (9_999, 12_000)}
THREE_WAY = {"6.7": (20_000, 2_880, 10), "ragged": (19_999, 2_870, 7)}


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (an entry compiled for a described chip cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(chip, fn, shapes, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(functools.partial(fn, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel emitted"
    return compiled


def _planes(levels, n_f, n_v):
    return ((levels, -(-n_f // 8), n_v), jnp.uint8)


def _stats(n_v):
    return ((n_v,), jnp.float32)


@pytest.mark.parametrize("shape", sorted(TWO_WAY))
def test_levels_rect(chip, shape):
    n_f, n_v = TWO_WAY[shape]
    _compile(chip, metric2_levels_pallas,
             [_planes(2, n_f, n_v), _planes(2, n_f, n_v), _stats(n_v),
              _stats(n_v)], epilogue=czek_assemble_tile)


@pytest.mark.parametrize("shape", sorted(TWO_WAY))
def test_levels_tri(chip, shape):
    n_f, n_v = TWO_WAY[shape]
    _compile(chip, metric2_levels_tri_pallas,
             [_planes(2, n_f, n_v), _stats(n_v)], epilogue=czek_assemble_tile)


@pytest.mark.parametrize("shape", sorted(TWO_WAY))
def test_popcount_rect(chip, shape):
    n_f, n_v = TWO_WAY[shape]
    _compile(chip, metric2_pop_pallas,
             [_planes(1, n_f, n_v), _planes(1, n_f, n_v), _stats(n_v),
              _stats(n_v)], epilogue=czek_assemble_tile)


@pytest.mark.parametrize("shape", sorted(TWO_WAY))
def test_popcount_tri(chip, shape):
    n_f, n_v = TWO_WAY[shape]
    _compile(chip, metric2_pop_tri_pallas,
             [_planes(1, n_f, n_v), _stats(n_v)], epilogue=czek_assemble_tile)


@pytest.mark.parametrize("shape", sorted(TWO_WAY))
def test_vpu_rect(chip, shape):
    n_f, n_v = TWO_WAY[shape]
    _compile(chip, metric2_pallas,
             [((n_v, n_f), jnp.float32), ((n_f, n_v), jnp.float32),
              _stats(n_v), _stats(n_v)],
             combine=jnp.minimum, epilogue=czek_assemble_tile)


@pytest.mark.parametrize("shape", sorted(TWO_WAY))
def test_vpu_tri(chip, shape):
    n_f, n_v = TWO_WAY[shape]
    _compile(chip, metric2_tri_pallas,
             [((n_v, n_f), jnp.float32), ((n_f, n_v), jnp.float32),
              _stats(n_v), _stats(n_v)],
             combine=jnp.minimum, epilogue=czek_assemble_tile)


@pytest.mark.parametrize("shape", sorted(THREE_WAY))
def test_threeway_levels_slice(chip, shape):
    n_f, n_v, L = THREE_WAY[shape]
    _compile(chip, threeway_batch_levels_pallas,
             [_planes(2, n_f, n_v), _planes(2, n_f, L), _planes(2, n_f, n_v)])


@pytest.mark.parametrize("shape", sorted(THREE_WAY))
def test_threeway_popcount_slice(chip, shape):
    n_f, n_v, L = THREE_WAY[shape]
    _compile(chip, threeway_batch_pop_pallas,
             [_planes(1, n_f, n_v), _planes(1, n_f, L), _planes(1, n_f, n_v)])


@pytest.mark.parametrize("shape", sorted(THREE_WAY))
def test_threeway_vpu_slice(chip, shape):
    n_f, n_v, L = THREE_WAY[shape]
    _compile(chip, threeway_batch_pallas,
             [((n_f, n_v), jnp.float32), ((n_f, L), jnp.float32),
              ((n_f, n_v), jnp.float32)], combine=jnp.minimum)
