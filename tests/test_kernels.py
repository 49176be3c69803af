"""Per-kernel validation: shape/dtype sweeps + hypothesis, vs ref.py oracles
(interpret mode executes the kernel body, so this validates kernel logic)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stubs

# optional dep: property tests skip when hypothesis is missing, rest run
given, settings, st = hypothesis_or_stubs()

from repro.core.acf import acf_from_aggregates, extract_aggregates  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.ops import (acf_impact, agg_to_table, lag_dot,  # noqa: E402
                               window_impact)


def _setup(n, L, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = (np.sin(2 * np.pi * np.arange(n) / 24)
         + 0.2 * rng.standard_normal(n)).astype(dtype)
    y = jnp.asarray(x)
    agg = extract_aggregates(y, L)
    tab = agg_to_table(agg).astype(dtype)
    p0 = acf_from_aggregates(agg, n).astype(dtype)
    dval = jnp.asarray((0.1 * rng.standard_normal(n)).astype(dtype))
    return y, dval, tab, p0


@pytest.mark.parametrize("n,L,block", [
    (256, 4, 128), (1000, 24, 256), (4096, 48, 1024), (513, 7, 256),
    (2048, 1, 512),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("measure", ["mae", "rmse", "cheb"])
def test_acf_impact_kernel_sweep(n, L, block, dtype, measure):
    y, dval, tab, p0 = _setup(n, L, dtype)
    got = acf_impact(y, dval, tab, p0, measure=measure, block=block,
                     backend="pallas")
    want = ref.acf_impact_ref(y, dval, tab, p0, L=L, measure=measure)
    tol = 3e-5 if dtype == np.float32 else 1e-10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,L,block", [
    (256, 8, 128), (5000, 64, 512), (4096, 365, 2048), (777, 3, 256),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_lag_dot_kernel_sweep(n, L, block, dtype):
    y, *_ = _setup(n, L if L < n else n - 1, dtype, seed=1)
    got = lag_dot(y, L, block=block, backend="pallas")
    want = ref.lag_dot_ref(y, L=L)
    tol = 2e-4 if dtype == np.float32 else 1e-10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("n,L", [(512, 12), (1000, 24)])
def test_lag_dot_kernel_cross_and_halo(n, L):
    """The generalized kernel contract: cross products a·b_ext with an
    L-point halo continuation (the partitioned overlap terms)."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal(n))
    b = jnp.asarray(rng.standard_normal(n))
    halo = jnp.asarray(rng.standard_normal(L))
    got = lag_dot(a, L, b=b, halo=halo, block=256, backend="pallas")
    want = ref.lag_xdot_ref(a, jnp.concatenate([b, halo]), L=L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-10, atol=1e-10)
    # reference dispatch hits the same oracle
    got_r = lag_dot(a, L, b=b, halo=halo, backend="reference")
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(want),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("n,L,W,P", [
    (512, 12, 16, 128), (1000, 24, 64, 300), (513, 7, 32, 200),
])
@pytest.mark.parametrize("measure", ["mae", "rmse", "cheb"])
def test_acf_window_impact_kernel_sweep(n, L, W, P, measure):
    """New Eq. 9 windowed-impact kernel vs its jnp oracle (``P`` candidates:
    one whole 128-lane block, and partial last blocks)."""
    rng = np.random.default_rng(7)
    y, _, tab, p0 = _setup(n, L, np.float64, seed=7)
    starts = jnp.asarray(rng.integers(0, n - 1, P), jnp.int32)
    spans = rng.integers(1, W + 1, P)
    dwins = rng.standard_normal((P, W)) * 0.1
    dwins = jnp.asarray(dwins * (np.arange(W)[None, :] < spans[:, None]))
    got = window_impact(y, dwins, starts, tab, p0, measure=measure,
                        backend="pallas")
    want = window_impact(y, dwins, starts, tab, p0, measure=measure,
                        backend="reference")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-10, atol=1e-10)


def test_window_impact_matches_recompute():
    """Windowed impacts equal brute-force ACF-recompute deviations."""
    n, L, W = 256, 8, 16
    y, _, tab, p0 = _setup(n, L, np.float64, seed=5)
    starts = jnp.asarray([0, 100, 200, 250], jnp.int32)
    rng = np.random.default_rng(5)
    dwins_np = 0.3 * rng.standard_normal((4, W))
    for p, s in enumerate(np.asarray(starts)):
        dwins_np[p, max(0, n - s):] = 0.0        # stay inside the series
    dwins = jnp.asarray(dwins_np)
    got = window_impact(y, dwins, starts, tab, p0, measure="mae",
                        backend="pallas")
    from repro.core.acf import acf
    for p, s in enumerate(np.asarray(starts)):
        dense = np.zeros(n)
        dense[s:s + W] = dwins_np[p, : n - s]
        want = float(jnp.mean(jnp.abs(acf(y + jnp.asarray(dense), L) - p0)))
        assert abs(float(got[p]) - want) < 1e-9


@settings(max_examples=10, deadline=None)
@given(st.integers(100, 700), st.integers(1, 20), st.integers(0, 100))
def test_acf_impact_kernel_hypothesis(n, L, seed):
    y, dval, tab, p0 = _setup(n, L, np.float64, seed=seed)
    got = acf_impact(y, dval, tab, p0, measure="mae", block=128,
                     backend="pallas")
    want = ref.acf_impact_ref(y, dval, tab, p0, L=L, measure="mae")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-9, atol=1e-9)


def test_kernel_matches_cameo_core_math():
    """The kernel's impact row equals the core acf_after_single_delta +
    measure composition used by the compressor."""
    from repro.core.aggregates import acf_after_single_delta
    from repro.core.acf import Aggregates
    n, L = 512, 12
    y, dval, tab, p0 = _setup(n, L, np.float64, seed=7)
    agg = Aggregates(*[tab[i] for i in range(5)])
    rows = acf_after_single_delta(agg, y, jnp.arange(n, dtype=jnp.int32), dval)
    want = jnp.mean(jnp.abs(rows - p0[None, :]), axis=1)
    got = acf_impact(y, dval, tab, p0, measure="mae", block=256,
                     backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-9, atol=1e-9)
