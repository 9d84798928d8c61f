"""Property-based tests (hypothesis) on the system's invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import checksum as ck  # noqa: E402
from repro.core.mgemm import mgemm_xla  # noqa: E402
from repro.core.metrics import czek2_metric_np  # noqa: E402
from repro.core.plan2 import TwoWayPlan, global_pairs_of_block  # noqa: E402
from repro.core.plan3 import ThreeWayPlan  # noqa: E402
from repro.core.synthetic import analytic_window_vectors  # noqa: E402
from repro.kernels.mgemm_levels.ref import mgemm_levels_ref  # noqa: E402

DIMS = st.integers(2, 12)


def _ref_minplus(A, B):
    return np.minimum(A[:, :, None], B[None, :, :]).sum(axis=1)


@settings(max_examples=25, deadline=None)
@given(
    m=DIMS, k=DIMS, n=DIMS,
    seed=st.integers(0, 2**31 - 1),
    scale=st.floats(0.1, 100.0),
)
def test_mgemm_matches_reference_on_floats(m, k, n, seed, scale):
    rng = np.random.default_rng(seed)
    A = (rng.random((m, k)) * scale).astype(np.float32)
    B = (rng.random((k, n)) * scale).astype(np.float32)
    got = np.asarray(mgemm_xla(jnp.asarray(A), jnp.asarray(B), chunk=4))
    np.testing.assert_allclose(got, _ref_minplus(A, B), rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(m=DIMS, k=DIMS, n=DIMS, seed=st.integers(0, 2**31 - 1))
def test_mgemm_transpose_identity(m, k, n, seed):
    """min-plus GEMM: (A ∘ B)^T == (B^T ∘ A^T)."""
    rng = np.random.default_rng(seed)
    A = rng.integers(0, 9, (m, k)).astype(np.float32)
    B = rng.integers(0, 9, (k, n)).astype(np.float32)
    ab = np.asarray(mgemm_xla(jnp.asarray(A), jnp.asarray(B)))
    ba = np.asarray(mgemm_xla(jnp.asarray(B.T), jnp.asarray(A.T)))
    assert (ab.T == ba).all()


@settings(max_examples=25, deadline=None)
@given(m=DIMS, k=DIMS, n=DIMS, seed=st.integers(0, 2**31 - 1))
def test_mgemm_monotonicity(m, k, n, seed):
    """Increasing any input entry never decreases any output entry."""
    rng = np.random.default_rng(seed)
    A = rng.integers(0, 9, (m, k)).astype(np.float32)
    B = rng.integers(0, 9, (k, n)).astype(np.float32)
    base = np.asarray(mgemm_xla(jnp.asarray(A), jnp.asarray(B)))
    i, j = rng.integers(0, m), rng.integers(0, k)
    A2 = A.copy()
    A2[i, j] += 3
    up = np.asarray(mgemm_xla(jnp.asarray(A2), jnp.asarray(B)))
    assert (up >= base - 1e-6).all()


@settings(max_examples=20, deadline=None)
@given(levels=st.integers(1, 9), m=DIMS, k=DIMS, n=DIMS,
       seed=st.integers(0, 2**31 - 1))
def test_levels_decomposition_exact(levels, m, k, n, seed):
    rng = np.random.default_rng(seed)
    A = rng.integers(0, levels + 1, (m, k)).astype(np.float32)
    B = rng.integers(0, levels + 1, (k, n)).astype(np.float32)
    got = np.asarray(mgemm_levels_ref(jnp.asarray(A), jnp.asarray(B), levels=levels))
    assert (got == _ref_minplus(A, B)).all()


@settings(max_examples=20, deadline=None)
@given(n_v=st.integers(2, 10), n_f=st.integers(2, 30),
       seed=st.integers(0, 2**31 - 1), alpha=st.floats(0.1, 10.0))
def test_czek2_scale_invariance_and_range(n_v, n_f, seed, alpha):
    rng = np.random.default_rng(seed)
    V = rng.integers(0, 9, (n_f, n_v)).astype(np.float64) + 0.5
    c = czek2_metric_np(V)
    c2 = czek2_metric_np(V * alpha)
    np.testing.assert_allclose(c, c2, rtol=1e-9)  # scale invariant
    assert (c >= 0).all() and (c <= 1 + 1e-12).all()
    np.testing.assert_allclose(np.diag(c), 1.0)


@settings(max_examples=15, deadline=None)
@given(n_pv=st.integers(1, 10), n_vp=st.integers(1, 6),
       n_pr=st.integers(1, 4))
def test_plan2_exact_cover_property(n_pv, n_vp, n_pr):
    plan = TwoWayPlan(n_pv, n_pr)
    n_v = n_pv * n_vp
    seen = set()
    for p_v, d, col in plan.all_computed_blocks():
        I, J, mask = global_pairs_of_block(p_v, col, n_vp)
        for i, j in zip(I[mask], J[mask]):
            key = (min(i, j), max(i, j))
            assert key not in seen
            seen.add(key)
    assert len(seen) == n_v * (n_v - 1) // 2


@settings(max_examples=8, deadline=None)
@given(n_pv=st.integers(1, 4), n_st=st.sampled_from([1, 2]),
       mult=st.integers(1, 2))
def test_plan3_exact_cover_property(n_pv, n_st, mult):
    n_vp = 6 * n_st * mult
    plan = ThreeWayPlan(n_pv, 1, n_st)
    n_v = n_pv * n_vp
    seen = set()
    for p_v in range(n_pv):
        for it in plan.items_of(p_v, 0):
            for stg in range(n_st):
                gi, gj, gk = plan.item_cells(p_v, it, n_vp, stg)
                for t in zip(gi, gj, gk):
                    key = tuple(sorted(t))
                    assert key not in seen
                    seen.add(key)
    assert len(seen) == n_v * (n_v - 1) * (n_v - 2) // 6


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 40))
def test_checksum_multiset_invariance(seed, n):
    rng = np.random.default_rng(seed)
    i = rng.integers(0, 100, n)
    j = rng.integers(101, 200, n)
    v = rng.random(n).astype(np.float32)
    perm = rng.permutation(n)
    assert ck.checksum_pairs(i, j, v) == ck.checksum_pairs(i[perm], j[perm], v[perm])


@settings(max_examples=10, deadline=None)
@given(n_f=st.integers(8, 60), n_v=st.integers(2, 12),
       seed=st.integers(0, 2**31 - 1))
def test_analytic_windows_closed_form(n_f, n_v, seed):
    width = max(1, n_f // 4)
    V, aw = analytic_window_vectors(n_f, n_v, width=width, seed=seed)
    n2 = np.minimum(V[:, :, None], V[:, None, :]).sum(axis=0)
    I, J = np.meshgrid(np.arange(n_v), np.arange(n_v), indexing="ij")
    np.testing.assert_allclose(aw.n2(I, J), n2)
