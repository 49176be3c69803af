"""Parity: the batched matmul-shaped contractions of the round body vs
their retained per-lag loop oracles.

The rounds-mode hot path lowers every Eq. 7/8/9 term to O(1) einsum/gather
ops against constant shift bases (see ``kernels/README.md``); each fused
form keeps its historical per-lag oracle next to it precisely so these
property tests can pin the algebra across lag depths, aggregation factors
and deviation measures.  Tolerances are float64-tight: the contraction and
the loop differ only in reduction order.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_stubs

from repro.core.acf import aggregate_series, extract_aggregates
from repro.core.aggregates import apply_delta_dense, apply_delta_dense_ref
from repro.kernels import fused_round as fused
from repro.kernels import ref

given, settings, st = hypothesis_or_stubs()

_L = st.sampled_from([1, 4, 12])
_KAPPA = st.sampled_from([1, 4])
_MEASURE = st.sampled_from(["mae", "rmse", "cheb"])


def _target_series(seed, n, kappa):
    """A zero-padded aggregate-space series plus its valid length: raw
    signal of length ``n * kappa`` pushed through the Def. 2 tumbling
    aggregation, then padded-bucket style (zeros beyond ``ny``)."""
    rng = np.random.default_rng(seed)
    t = np.arange(n * kappa)
    x = (np.sin(2 * np.pi * t / 24) + 0.5 * np.sin(2 * np.pi * t / 7)
         + 0.2 * rng.standard_normal(n * kappa))
    y = np.asarray(aggregate_series(jnp.asarray(x), kappa))
    ny = y.shape[0]
    pad = int(rng.integers(0, 17))
    return jnp.asarray(np.pad(y, (0, pad))), ny, rng


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), _L, _KAPPA)
def test_moment_deltas_matches_loop_oracle(seed, L, kappa):
    """fused_round._moment_deltas bilinear-term lowerings — "einsum"
    (shift-basis contraction, the TPU form) and "roll" (batched
    roll-and-reduce, the CPU form) — both ≡ _moment_deltas_ref
    (L-unrolled slices).  Forms are requested explicitly so neither leg
    is vacuous regardless of the backend the test runs on."""
    y, ny, rng = _target_series(seed, 96, kappa)
    K, Wy = 5, 8
    starts = jnp.asarray(
        rng.integers(0, max(ny - Wy, 1), size=K), jnp.int32)
    d = jnp.asarray(0.3 * rng.standard_normal((K, Wy)))
    # the solo-candidate context gather (solo_moment_rows layout)
    kk = jnp.arange(Wy + 2 * L)
    ctx = jnp.pad(y, (L, L + Wy))[starts[:, None] + kk[None, :]]
    b = fused._moment_deltas_ref(d, ctx, starts, ny, L=L)
    for form in ("einsum", "roll"):
        a = fused._moment_deltas(d, ctx, starts, ny, L=L, form=form)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-11, atol=1e-11,
                                   err_msg=f"form={form}")


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), _L, _KAPPA, _MEASURE)
def test_window_delta_acf_matches_per_moment_oracle(seed, L, kappa, measure):
    """ref._window_delta_acf (one fused [P,5,W]x[P,5,W,L] contraction) ≡
    _window_delta_acf_ref (one einsum per moment row), and the ranking
    impacts derived from both rows agree for every kernel measure."""
    y, ny, rng = _target_series(seed, 128, kappa)
    agg = extract_aggregates(y[:ny], L)
    P, W = 6, 10
    starts = jnp.asarray(
        rng.integers(0, max(ny - W, 1), size=P), jnp.int32)
    dwins = jnp.asarray(0.3 * rng.standard_normal((P, W)))
    rows_ctx = ref.candidate_contexts(y[:ny], starts, L=L, W=W)
    fused_rows = ref.acf_after_window_delta_rows(
        agg, rows_ctx, starts, dwins, ny=ny)
    j = jnp.arange(W)
    l = jnp.arange(1, L + 1)
    abs_t = starts[:, None] + j[None, :]
    y_at = rows_ctx[:, L:L + W]
    y_fwd = rows_ctx[:, L + j[:, None] + l[None, :]]
    y_bwd = rows_ctx[:, L + j[:, None] - l[None, :]]
    oracle_rows = ref._window_delta_acf_ref(
        agg, dwins, abs_t, y_at, y_fwd, y_bwd, ny=ny)
    np.testing.assert_allclose(np.asarray(fused_rows),
                               np.asarray(oracle_rows),
                               rtol=1e-10, atol=1e-10)
    p0 = jnp.asarray(rng.standard_normal(L) * 0.1)
    np.testing.assert_allclose(
        np.asarray(ref.measure_rows(fused_rows, p0, measure)),
        np.asarray(ref.measure_rows(oracle_rows, p0, measure)),
        rtol=1e-10, atol=1e-10)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), _L)
def test_lag_xdot_matches_slice_oracle(seed, L):
    """ref.lag_xdot ([m] x [m, L] shift-basis matmul) ≡ lag_xdot_ref
    (one dynamic slice + reduce per lag), with a non-trivial halo."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(8, 200))
    a = jnp.asarray(rng.standard_normal(m))
    b_ext = jnp.asarray(rng.standard_normal(m + L))
    np.testing.assert_allclose(
        np.asarray(ref.lag_xdot(a, b_ext, L=L)),
        np.asarray(ref.lag_xdot_ref(a, b_ext, L=L)),
        rtol=1e-11, atol=1e-11)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), _L, _KAPPA)
def test_apply_delta_dense_matches_roll_oracle(seed, L, kappa):
    """aggregates.apply_delta_dense (Eq. 10/11) ≡ apply_delta_dense_ref
    (per-lag roll-mask-sum oracle) for both bilinear lowerings — "slices"
    ([nyb, L] shift basis with no index array, the accelerator form) and
    "roll" (batched roll-and-reduce, the CPU form) — in both the
    NamedTuple and packed-table carry forms, under padded buckets."""
    y, ny, rng = _target_series(seed, 96, kappa)
    agg = extract_aggregates(y[:ny], L)
    delta = np.zeros(y.shape[0])
    lo = int(rng.integers(0, max(ny - 12, 1)))
    delta[lo:lo + 12] = 0.4 * rng.standard_normal(min(12, ny - lo))
    delta = jnp.asarray(delta)
    oracle = apply_delta_dense_ref(agg, y, delta, ny=ny)
    table = jnp.stack(list(agg))
    for form in ("slices", "roll"):
        new = apply_delta_dense(agg, y, delta, ny=ny, form=form)
        for got, want in zip(new, oracle):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-11, atol=1e-11,
                                       err_msg=f"form={form}")
        # packed [5, L] table carry (the rounds-loop form): one fused add
        new_t = apply_delta_dense(table, y, delta, ny=ny, form=form)
        np.testing.assert_allclose(np.asarray(new_t),
                                   np.asarray(jnp.stack(list(oracle))),
                                   rtol=1e-11, atol=1e-11,
                                   err_msg=f"form={form}")


@pytest.mark.parametrize("L", [4, 12, 48])
@pytest.mark.parametrize("kappa", [1, 4])
def test_bilinear_forms_parity_deterministic(L, kappa):
    """Seeded (hypothesis-free) cross-check of every bilinear lowering:
    all _moment_deltas forms agree with the slice oracle and all
    apply_delta_dense forms agree with the roll oracle.  Runs in every
    environment — the property tests above skip without hypothesis."""
    y, ny, rng = _target_series(7 * L + kappa, 96, kappa)
    K, Wy = 5, 8
    starts = jnp.asarray(
        rng.integers(0, max(ny - Wy, 1), size=K), jnp.int32)
    d = jnp.asarray(0.3 * rng.standard_normal((K, Wy)))
    kk = jnp.arange(Wy + 2 * L)
    ctx = jnp.pad(y, (L, L + Wy))[starts[:, None] + kk[None, :]]
    want = fused._moment_deltas_ref(d, ctx, starts, ny, L=L)
    for form in ("einsum", "roll", "slices"):
        got = fused._moment_deltas(d, ctx, starts, ny, L=L, form=form)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-11, atol=1e-11,
                                   err_msg=f"form={form}")
    agg = extract_aggregates(y[:ny], L)
    delta = np.zeros(y.shape[0])
    lo = int(rng.integers(0, max(ny - 12, 1)))
    delta[lo:lo + 12] = 0.4 * rng.standard_normal(min(12, ny - lo))
    delta = jnp.asarray(delta)
    oracle = jnp.stack(list(apply_delta_dense_ref(agg, y, delta, ny=ny)))
    table = jnp.stack(list(agg))
    for form in ("slices", "roll"):
        got_t = apply_delta_dense(table, y, delta, ny=ny, form=form)
        np.testing.assert_allclose(np.asarray(got_t), np.asarray(oracle),
                                   rtol=1e-11, atol=1e-11,
                                   err_msg=f"form={form}")


@pytest.mark.parametrize("n,L", [(96, 48), (130, 4), (4096, 48),
                                 (3840, 365)])
def test_shift_basis_matches_index_basis(n, L):
    """ref.shift_basis (no index array) is exactly the index-gathered
    basis ``v_pad[t + l]`` — the rounds-program widths among the cases,
    and L = 365 (min_temp) past the series end."""
    v = np.random.default_rng(n + L).standard_normal(n)
    want = np.pad(v, (0, L))[np.arange(n)[:, None]
                             + np.arange(1, L + 1)[None, :]]
    got = np.asarray(ref.shift_basis(jnp.asarray(v), L))
    assert got.shape == (n, L)
    assert np.array_equal(got, want)


def test_dense_update_slices_form_has_no_basis_gather():
    """The accelerator form of the dense update lowers with no gather of
    rank 2 (the [nyb, L] basis) at the served window's width, L = 48,
    float64."""
    nyb, L = 4096, 48
    text = jax.jit(functools.partial(apply_delta_dense, form="slices")).lower(
        jax.ShapeDtypeStruct((5, L), jnp.float64),
        jax.ShapeDtypeStruct((nyb,), jnp.float64),
        jax.ShapeDtypeStruct((nyb,), jnp.float64)).as_text()
    gathers = re.findall(r'"stablehlo\.gather".*-> tensor<([^>]*)>', text)
    assert not [g for g in gathers if g.count("x") == 2], gathers


@pytest.mark.parametrize("L", [4, 12])
def test_window_rows_pallas_interpret_parity(L):
    """The fused tier-impact kernel (interpret mode) reproduces the
    einsum contraction's Eq. 9 ACF rows."""
    rng = np.random.default_rng(3)
    nyb, ny, K, Wy = 128, 120, 7, 16
    y = np.zeros(nyb)
    y[:ny] = rng.standard_normal(ny)
    y = jnp.asarray(y)
    dyws = jnp.asarray(0.1 * rng.standard_normal((K, Wy)))
    starts = jnp.asarray(rng.integers(0, ny - Wy, size=K), jnp.int32)
    table = jnp.stack(list(extract_aggregates(y[:ny], L)))
    a = fused.window_acf_rows(y, dyws, starts, table, ny, L=L)
    b = fused.window_rows_pallas(y, dyws, starts, table, ny, L=L,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-9, atol=1e-9)
