"""Autocorrelation (ACF) and partial autocorrelation (PACF) machinery.

Implements the paper's two ACF formulations:

* Eq. (1): stationary form (global mean/std).
* Eq. (2): non-stationary aggregate form, driven by the five per-lag
  aggregates ``sx, sx_l, sx^2, sx_l^2, sxx_l`` (Eq. 7) that CAMEO maintains
  incrementally.  All CAMEO code paths use this form.

Index conventions are 0-based: for lag ``l`` the head range is
``t in [0, n-1-l]`` and the tail range is ``t in [l, n-1]``; both have
``n - l`` elements.  ``n`` (series length) is *static* throughout CAMEO —
removal replaces values by interpolation but never shortens the series.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


class Aggregates(NamedTuple):
    """Per-lag ACF aggregates (Eq. 7). Each field has shape ``[L]``.

    Entry ``j`` corresponds to lag ``l = j + 1``.
    """

    sx: jax.Array     # sum of head values        sum_{t<=n-1-l} x_t
    sxl: jax.Array    # sum of tail values        sum_{t>=l}     x_t
    sx2: jax.Array    # sum of head squares
    sxl2: jax.Array   # sum of tail squares
    sxx: jax.Array    # lagged product            sum_{t<=n-1-l} x_t x_{t+l}


def lags_arange(L: int, dtype=jnp.float64) -> jax.Array:
    return jnp.arange(1, L + 1, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("L", "backend"))
def extract_aggregates(x: jax.Array, L: int,
                       backend: str = "auto") -> Aggregates:
    """ExtractAggregates (Algorithm 1): O(nL), dominated by ``sxx_l``.

    The four moment sums are O(n + L) prefix work; the lagged products go
    through the impact-engine backend (``kernels/ops.lag_dot`` — the Pallas
    kernel on TPU, the jnp reference elsewhere).
    """
    from repro.kernels.ops import lag_dot  # deferred: kernels sit below core
    n = x.shape[0]
    csum = _ref.cumsum(x)
    csum2 = _ref.cumsum(x * x)
    total, total2 = csum[-1], csum2[-1]
    l = jnp.arange(1, L + 1)
    # head sums: prefix up to index n-1-l.
    sx = csum[n - 1 - l]
    sx2 = csum2[n - 1 - l]
    # tail sums: total minus prefix up to l-1.
    sxl = total - csum[l - 1]
    sxl2 = total2 - csum2[l - 1]
    sxx = lag_dot(x, L, backend=backend)
    return Aggregates(sx=sx, sxl=sxl, sx2=sx2, sxl2=sxl2, sxx=sxx)


def centered(y: jax.Array, n=None, *, axis_name=None) -> jax.Array:
    """``y`` less its mean: the series whose Eq. 7 moments CAMEO carries.

    Eq. 2 does not change under a constant shift, while raw moments of a
    series far from zero lose the ACF to cancellation in
    ``m * sxx - sx * sxl``: with float64 emulated in float32 pairs (TPU) a
    deviation carried from raw moments drifted 4e-9 from a fresh float64
    measurement on electricity-demand data.  Every compressor path centres
    its target series here before it extracts moments.

    By default all of ``y`` (any shape) is one series.  ``n`` (may be
    traced) counts the valid leading entries of a zero-padded ``[nb]``
    buffer, whose padding stays zero.  Under ``shard_map``, ``axis_name``
    names the axis the series is split over and ``n`` is its global length.
    """
    if axis_name is not None:
        return y - jax.lax.psum(jnp.sum(y), axis_name) / n
    if n is None:
        return y - jnp.sum(jnp.sum(y, axis=-1)) / y.size
    valid = jnp.arange(y.shape[0]) < n
    mu = jnp.sum(jnp.where(valid, y, 0.0)) / jnp.asarray(n, y.dtype)
    return jnp.where(valid, y - mu, jnp.zeros_like(y))


def extract_aggregates_masked(x: jax.Array, L: int, n_valid,
                              backend: str = "auto") -> Aggregates:
    """ExtractAggregates over a zero-padded buffer: aggregates of
    ``x[:n_valid]`` where ``n_valid`` may be a traced scalar.

    ``x`` must be zero beyond ``n_valid`` (the padded-bucket discipline of
    the rounds mode): the tail sums and the lagged products are then exact
    as-is, and only the head prefix sums need dynamic gathers.  Not jitted —
    intended to be traced inside a caller's jit.
    """
    from repro.kernels.ops import lag_dot  # deferred: kernels sit below core
    csum = _ref.cumsum(x)
    csum2 = _ref.cumsum(x * x)
    total, total2 = csum[-1], csum2[-1]
    l = jnp.arange(1, L + 1)
    sx = csum[n_valid - 1 - l]
    sx2 = csum2[n_valid - 1 - l]
    sxl = total - csum[l - 1]
    sxl2 = total2 - csum2[l - 1]
    sxx = lag_dot(x, L, backend=backend)
    return Aggregates(sx=sx, sxl=sxl, sx2=sx2, sxl2=sxl2, sxx=sxx)


def acf_from_aggregates(agg, n: int) -> jax.Array:
    """Eq. (2).  Returns the ACF for lags ``1..L`` (shape ``[L]``).

    ``agg`` is any structure indexable as the five per-lag rows — the
    :class:`Aggregates` NamedTuple or the packed ``[5, L]`` moment table the
    rounds mode carries.
    """
    sx, sxl, sx2, sxl2, sxx = agg[0], agg[1], agg[2], agg[3], agg[4]
    L = sx.shape[-1]
    m = n - jnp.arange(1, L + 1, dtype=sx.dtype)  # n - l per lag
    num = m * sxx - sx * sxl
    var_head = m * sx2 - sx * sx
    var_tail = m * sxl2 - sxl * sxl
    denom2 = var_head * var_tail
    tiny = jnp.asarray(1e-30, sx.dtype)
    denom = jnp.sqrt(jnp.maximum(denom2, tiny))
    return jnp.where(denom2 > tiny, num / denom, jnp.zeros_like(num))


@functools.partial(jax.jit, static_argnames=("L",))
def acf(x: jax.Array, L: int) -> jax.Array:
    """Non-stationary ACF (Eq. 2) computed from scratch.  Shape ``[L]``."""
    return acf_from_aggregates(extract_aggregates(x, L), x.shape[0])


@functools.partial(jax.jit, static_argnames=("L",))
def acf_stationary(x: jax.Array, L: int) -> jax.Array:
    """Eq. (1): stationary ACF with global mean/variance (oracle/tests)."""
    n = x.shape[0]
    mu = jnp.mean(x)
    var = jnp.mean((x - mu) ** 2)
    xc = x - mu

    def one(l):
        shifted = jnp.roll(xc, -l)
        mask = jnp.arange(n) <= (n - 1 - l)
        return jnp.sum(jnp.where(mask, xc * shifted, 0.0)) / ((n - l) * var)

    return jax.vmap(one)(jnp.arange(1, L + 1))


def pacf_from_acf(r: jax.Array) -> jax.Array:
    """Durbin–Levinson recursion (Eq. 3), O(L^2).

    ``r`` is the ACF for lags 1..L; returns ``phi_{l,l}`` for l = 1..L.
    """
    L = r.shape[0]
    dtype = r.dtype

    if L == 1:
        return r

    phi0 = jnp.zeros((L,), dtype).at[0].set(r[0])  # phi_{1,k} row (k=1..L)
    diag0 = jnp.zeros((L,), dtype).at[0].set(r[0])

    def body(lm1, carry):
        # computing row l = lm1 + 1 (so lm1 ranges 1..L-1)
        phi_prev, diag = carry
        l = lm1 + 1
        k = jnp.arange(1, L + 1)
        kmask = (k <= l - 1).astype(dtype)
        # r_{l-k} for k = 1..l-1 ; clamp indices, mask handles validity.
        r_lk = r[jnp.clip(l - k - 1, 0, L - 1)]
        num = r[l - 1] - jnp.sum(phi_prev * r_lk * kmask)
        den = 1.0 - jnp.sum(phi_prev * r * kmask)
        den = jnp.where(jnp.abs(den) < 1e-12, jnp.asarray(1e-12, dtype), den)
        phi_ll = num / den
        # phi_{l,k} = phi_{l-1,k} - phi_ll * phi_{l-1,l-k}
        phi_rev = phi_prev[jnp.clip(l - k - 1, 0, L - 1)]
        phi_new = (phi_prev - phi_ll * phi_rev) * kmask
        phi_new = phi_new.at[l - 1].set(phi_ll)
        diag = diag.at[l - 1].set(phi_ll)
        return phi_new, diag

    _, diag = jax.lax.fori_loop(1, L, body, (phi0, diag0))
    return diag


@functools.partial(jax.jit, static_argnames=("L",))
def pacf(x: jax.Array, L: int) -> jax.Array:
    return pacf_from_acf(acf(x, L))


# ---------------------------------------------------------------------------
# Tumbling-window aggregation (SIP-on-Aggregates, Def. 2)
# ---------------------------------------------------------------------------

def aggregate_series(x: jax.Array, kappa: int, agg: str = "mean") -> jax.Array:
    """``AGG_kappa(X)``: tumbling windows of ``kappa`` points.

    ``n`` must be divisible by ``kappa`` (callers pad/trim in the pipeline).
    """
    if kappa == 1:
        return x
    n = x.shape[0]
    assert n % kappa == 0, f"length {n} not divisible by kappa={kappa}"
    xw = x.reshape(n // kappa, kappa)
    if agg == "mean":
        return xw.mean(axis=1)
    if agg == "sum":
        return xw.sum(axis=1)
    if agg == "max":
        return xw.max(axis=1)
    if agg == "min":
        return xw.min(axis=1)
    raise ValueError(f"unknown aggregation {agg!r}")
