"""Correctness of the int8 ring-payload knob: it must keep results
bit-exact vs the baseline."""
from repro.core.synthetic import random_integer_vectors
from repro.core.twoway import CometConfig, czek2_distributed
from repro.core.threeway import czek3_distributed
from repro.parallel.mesh import make_comet_mesh


def _mesh1():
    return make_comet_mesh(1, 1, 1)


def test_int8_ring_bit_exact():
    """int8 ring payload must be BIT-exact for small-integer data (2-way)."""
    V = random_integer_vectors(50, 18, max_value=15, seed=3)
    base = czek2_distributed(V, _mesh1(), CometConfig())
    opt = czek2_distributed(V, _mesh1(), CometConfig(ring_dtype="int8"))
    assert base.checksum() == opt.checksum()


def test_int8_ring_bit_exact_3way():
    V = random_integer_vectors(30, 12, max_value=7, seed=4)
    base = czek3_distributed(V, _mesh1(), CometConfig(), stage=0)
    opt = czek3_distributed(V, _mesh1(), CometConfig(ring_dtype="int8"), stage=0)
    assert base.checksum() == opt.checksum()


def test_int8_ring_with_levels_impl():
    V = random_integer_vectors(40, 12, max_value=2, seed=5)  # SNP-style {0,1,2}
    base = czek2_distributed(V, _mesh1(), CometConfig())
    opt = czek2_distributed(
        V, _mesh1(),
        CometConfig(impl="levels_xla", levels=2, ring_dtype="int8"),
    )
    assert base.checksum() == opt.checksum()
