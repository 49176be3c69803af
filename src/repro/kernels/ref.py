"""Pure-jnp reference implementations — the single source of truth for the
Eq. 2/8/9 per-lag math.

Every Pallas kernel in this package has its oracle here, and the ``core``
layer delegates to these functions instead of re-deriving the formulas
(``core/aggregates.py`` keeps only the *update* math of Eqs. 10-11 plus the
alive-neighbor geometry).  This module intentionally imports nothing from
``repro.core`` so the kernel layer sits at the bottom of the dependency
stack; aggregate arguments are any structure indexable as five per-lag
``[L]`` arrays ``(sx, sxl, sx2, sxl2, sxx)`` — the ``core.acf.Aggregates``
NamedTuple and the stacked ``[5, L]`` kernel table both qualify.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def cumsum(x: jax.Array, axis: int = 0) -> jax.Array:
    """``jnp.cumsum``, except float64 on a TPU, which runs as an
    ``associative_scan``: the TPU compiler takes about three minutes over
    an emulated-f64 cumulative sum at any length (a reduce-window), and
    about a second over the scan."""
    if x.dtype == jnp.float64 and jax.default_backend() == "tpu":
        return jax.lax.associative_scan(jnp.add, x, axis=axis)
    return jnp.cumsum(x, axis=axis)


def shift_basis(v: jax.Array, L: int) -> jax.Array:
    """The ``[n, L]`` lag-shift basis of ``v``: ``out[t, l-1] = v[t + l]``,
    zero past the end, with no index array (on the TPU an index gather of
    this basis ran under 1 GB/s).

    ``L + 2`` back-to-back copies of ``v`` zero-padded to ``m = n + L + 1``,
    cut into rows of ``m + 1``, start row ``r`` at flat position
    ``r * m + r``, that is at ``v_pad[r]``; ``r + t < m`` keeps each row's
    first ``n`` elements inside one copy.  A constant number of ops at any
    ``L``, where stacked per-lag slices take ``L``."""
    n = v.shape[0]
    m = n + L + 1
    v_pad = jnp.pad(v, (0, L + 1))
    flat = jnp.broadcast_to(v_pad, (L + 2, m)).reshape(-1)
    rows = flat[:(L + 1) * (m + 1)].reshape(L + 1, m + 1)
    return rows[1:, :n].T


def acf_from_moments(sx, sxl, sx2, sxl2, sxx, m):
    """Eq. 2: normalized per-lag ACF from the five moment sums.

    Broadcasts over any leading batch dims; ``m = ny - l`` per lag.
    """
    num = m * sxx - sx * sxl
    den2 = (m * sx2 - sx * sx) * (m * sxl2 - sxl * sxl)
    tiny = jnp.asarray(1e-30, num.dtype)
    den = jnp.sqrt(jnp.maximum(den2, tiny))
    return jnp.where(den2 > tiny, num / den, jnp.zeros_like(num))


def head_tail_masks(idx: jax.Array, ny: int, L: int, dtype):
    """Head/tail validity masks for absolute indices ``idx`` (shape [...]).

    Returns ``(head, tail)`` of shape ``[..., L]`` where
    ``head[..., l-1] = idx <= ny-1-l`` and ``tail[..., l-1] = idx >= l``.
    """
    l = jnp.arange(1, L + 1)
    head = (idx[..., None] <= (ny - 1 - l)).astype(dtype)
    tail = (idx[..., None] >= l).astype(dtype)
    return head, tail


def measure_rows(rows: jax.Array, p0: jax.Array, measure: str) -> jax.Array:
    """Kernel-supported deviation measures over ``[..., L]`` ACF rows."""
    diff = rows - p0[None, :]
    if measure == "mae":
        return jnp.mean(jnp.abs(diff), axis=-1)
    if measure == "rmse":
        return jnp.sqrt(jnp.mean(diff * diff, axis=-1))
    if measure == "cheb":
        return jnp.max(jnp.abs(diff), axis=-1)
    raise ValueError(measure)


KERNEL_MEASURES = ("mae", "rmse", "cheb")


def as_table(agg) -> jax.Array:
    """The packed ``[5, L]`` moment table for any aggregate structure
    (``core.acf.Aggregates`` NamedTuple or an already-stacked array)."""
    if isinstance(agg, jax.Array) or isinstance(agg, jnp.ndarray):
        return agg
    return jnp.stack([agg[0], agg[1], agg[2], agg[3], agg[4]])


def acf_from_table(rows: jax.Array, m: jax.Array) -> jax.Array:
    """Eq. 2 over packed moment rows ``[..., 5, L]`` → ACF ``[..., L]``."""
    return acf_from_moments(rows[..., 0, :], rows[..., 1, :], rows[..., 2, :],
                            rows[..., 3, :], rows[..., 4, :], m)


# ---------------------------------------------------------------------------
# Eq. 8 — hypothetical ACF after a single-point delta (Algorithm 2 ranking)
# ---------------------------------------------------------------------------

def acf_after_single_delta(agg, y: jax.Array, idx: jax.Array,
                           dval: jax.Array, *, ny=None) -> jax.Array:
    """Hypothetical ACF (per Eq. 8) after adding ``dval[p]`` at ``idx[p]``,
    independently for each p.  Returns ``[P, L]``.

    ``ny`` (optionally traced) overrides the valid length when ``y`` lives in
    a zero-padded bucket.
    """
    if ny is None:
        ny = y.shape[0]
    L = agg[0].shape[-1]
    dtype = y.dtype
    head, tail = head_tail_masks(idx, ny, L, dtype)        # [P, L]
    l = jnp.arange(1, L + 1)
    y_pad = jnp.pad(y, (L, L))
    y_fwd = y_pad[(idx + L)[:, None] + l[None, :]]         # y[i+l]
    y_bwd = y_pad[(idx + L)[:, None] - l[None, :]]         # y[i-l]
    y_at = y[idx]                                          # [P]

    d = dval[:, None]                                      # [P, 1]
    e = (dval * (2.0 * y_at + dval))[:, None]              # [P, 1]

    # Five flat [P, L] moment rows: a packed [P, 5, L] stack would be two
    # fewer dispatches but materializes 5 PL elements through a concat the
    # CPU emitter doesn't fuse — measurably slower at P = nb.
    tab = as_table(agg)
    sx = tab[0][None, :] + d * head
    sxl = tab[1][None, :] + d * tail
    sx2 = tab[2][None, :] + e * head
    sxl2 = tab[3][None, :] + e * tail
    sxx = tab[4][None, :] + d * (y_fwd * head + y_bwd * tail)

    m = (ny - l).astype(dtype)[None, :]
    return acf_from_moments(sx, sxl, sx2, sxl2, sxx, m)


@functools.partial(jax.jit, static_argnames=("L", "measure"))
def acf_impact_ref(y, dval, agg_table, p0, *, L: int, measure: str = "mae"):
    """Oracle for kernels.acf_impact: Algorithm-2 impacts for all points."""
    n = y.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rows = acf_after_single_delta(agg_table, y, idx, dval)  # [n, L]
    return measure_rows(rows, p0, measure)


# ---------------------------------------------------------------------------
# Eq. 9 — hypothetical ACF after a windowed (segment) delta
# ---------------------------------------------------------------------------

def _window_delta_acf(agg, dwins, abs_t, y_at, y_fwd, y_bwd, *, ny: int):
    """Shared Eq. 9 core: hypothetical ACF ``[P, L]`` from per-candidate
    delta windows plus pre-gathered series values.

    ``abs_t [P, W]`` are global positions; ``y_at [P, W]`` the series at the
    window, ``y_fwd``/``y_bwd [P, W, L]`` the ±lag-shifted values (zero out
    of range).  Both context layouts (shared 1-D chunk, per-candidate rows)
    reduce to this after their gathers.
    """
    L = agg[0].shape[-1]
    P, W = dwins.shape
    dtype = y_at.dtype
    head, tail = head_tail_masks(abs_t, ny, L, dtype)       # [P, W, L]

    d = dwins                                               # [P, W]
    e = d * (2.0 * y_at + d)

    l = jnp.arange(1, L + 1)
    j = jnp.arange(W)
    d_padded = jnp.pad(d, ((0, 0), (0, L)))
    d_fwd = d_padded[:, j[:, None] + l[None, :]]            # [P, W, L]

    # All five Eq. 9 moment deltas as one [P, 5, W] x [P, 5, W, L]
    # contraction: the per-row weights are d or e, the per-row bases the
    # head/tail masks (plus the shifted-context row for the bilinear term).
    coeff = jnp.stack([d, d, e, e, d], axis=1)              # [P, 5, W]
    basis = jnp.stack(
        [head, tail, head, tail,
         (y_fwd + d_fwd) * head + y_bwd * tail], axis=1)    # [P, 5, W, L]
    rows = as_table(agg)[None] + jnp.einsum("paw,pawl->pal", coeff, basis)

    m = (ny - l).astype(dtype)[None, :]
    return acf_from_table(rows, m)


def _window_delta_acf_ref(agg, dwins, abs_t, y_at, y_fwd, y_bwd, *, ny: int):
    """Per-moment-einsum oracle for :func:`_window_delta_acf` (the historical
    form with one contraction per moment row), kept for parity tests of the
    fused ``[P, 5, W] x [P, 5, W, L]`` contraction."""
    L = agg[0].shape[-1]
    dtype = y_at.dtype
    head, tail = head_tail_masks(abs_t, ny, L, dtype)       # [P, W, L]
    d = dwins
    e = d * (2.0 * y_at + d)
    dsx = jnp.einsum("pw,pwl->pl", d, head)
    dsxl = jnp.einsum("pw,pwl->pl", d, tail)
    dsx2 = jnp.einsum("pw,pwl->pl", e, head)
    dsxl2 = jnp.einsum("pw,pwl->pl", e, tail)
    l = jnp.arange(1, L + 1)
    W = dwins.shape[1]
    j = jnp.arange(W)
    d_padded = jnp.pad(d, ((0, 0), (0, L)))
    d_fwd = d_padded[:, j[:, None] + l[None, :]]            # [P, W, L]
    dsxx = jnp.einsum(
        "pw,pwl->pl", d, y_fwd * head + y_bwd * tail) + jnp.einsum(
        "pw,pwl->pl", d, d_fwd * head)
    m = (ny - l).astype(dtype)[None, :]
    return acf_from_moments(
        agg[0][None, :] + dsx, agg[1][None, :] + dsxl,
        agg[2][None, :] + dsx2, agg[3][None, :] + dsxl2,
        agg[4][None, :] + dsxx, m)


def acf_after_window_delta_ctx(agg, y_ctx: jax.Array, starts: jax.Array,
                               dwins: jax.Array, *, ny: int, off) -> jax.Array:
    """Hypothetical ACF after applying each candidate's *windowed* delta
    independently (vectorized Eq. 9).  Returns ``[P, L]``.

    This is the exact ranking form: it accounts for the full re-interpolated
    segment of a removal, including the cross-lag bilinear term, unlike the
    single-delta Algorithm-2 approximation.  The context form supports the
    coarse-grained partitioned mode: ``y_ctx`` is a local chunk with L-point
    halos on each side (+W right padding) and ``off`` is the chunk's global
    offset; out-of-series context positions must be zero.
    """
    L = agg[0].shape[-1]
    _, W = dwins.shape
    j = jnp.arange(W)
    l = jnp.arange(1, L + 1)
    loc_t = starts[:, None] + j[None, :]                    # [P, W] local
    abs_t = off + loc_t                                     # [P, W] global
    y_at = y_ctx[loc_t + L]                                 # [P, W]
    y_fwd = y_ctx[loc_t[..., None] + L + l]                 # [P, W, L]
    y_bwd = y_ctx[loc_t[..., None] + L - l]
    return _window_delta_acf(agg, dwins, abs_t, y_at, y_fwd, y_bwd, ny=ny)


def candidate_contexts(y: jax.Array, starts: jax.Array, *, L: int, W: int):
    """Per-candidate ``[P, W + 2L]`` y-context windows for the windowed
    kernel: ``ctx[p, k] = y[starts[p] - L + k]`` with zeros out of range.

    ``starts`` are *local* indices into ``y`` (callers supply haloed chunks
    plus the matching local starts in the partitioned mode).
    """
    y_pad = jnp.pad(y, (L, L + W))
    k = jnp.arange(W + 2 * L)
    return y_pad[jnp.clip(starts[:, None], 0, y.shape[0]) + k[None, :]]


def acf_after_window_delta_rows(agg, y_rows: jax.Array, starts_abs: jax.Array,
                                dwins: jax.Array, *, ny: int) -> jax.Array:
    """Eq. 9 hypothetical ACF from per-candidate ``[P, W + 2L]`` context rows
    (the kernel's input layout — see :func:`candidate_contexts`).
    Returns ``[P, L]``.
    """
    L = agg[0].shape[-1]
    _, W = dwins.shape
    j = jnp.arange(W)
    l = jnp.arange(1, L + 1)
    abs_t = starts_abs[:, None] + j[None, :]                # [P, W] global
    y_at = y_rows[:, L:L + W]                               # [P, W]
    y_fwd = y_rows[:, L + j[:, None] + l[None, :]]          # [P, W, L]
    y_bwd = y_rows[:, L + j[:, None] - l[None, :]]
    return _window_delta_acf(agg, dwins, abs_t, y_at, y_fwd, y_bwd, ny=ny)


@functools.partial(jax.jit, static_argnames=("ny", "measure"))
def acf_window_impact_ref(y_rows, dwins, starts_abs, agg_table, p0, *,
                          ny: int, measure: str = "mae"):
    """Oracle for kernels.acf_window_impact: exact Eq. 9 ranking impacts."""
    rows = acf_after_window_delta_rows(
        agg_table, y_rows, starts_abs, dwins, ny=ny)
    return measure_rows(rows, p0, measure)


# ---------------------------------------------------------------------------
# Eq. 7 — lagged products (ExtractAggregates hot term), cross/halo'd form
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("L",))
def lag_xdot(a, b_ext, *, L: int):
    """``out[l-1] = sum_{t < m} a[t] * b_ext[t + l]`` for l in 1..L, as one
    ``[m] x [m, L]`` contraction against a constant shift basis.

    ``b_ext`` has length ``m + L`` (the caller appends an L-point halo —
    zeros for a plain series, the next chunk's head for partitioned work).
    """
    m = a.shape[0]
    shifted = b_ext[jnp.arange(m)[:, None] + jnp.arange(1, L + 1)[None, :]]
    return a @ shifted


@functools.partial(jax.jit, static_argnames=("L",))
def lag_xdot_ref(a, b_ext, *, L: int):
    """Loop oracle for :func:`lag_xdot` (one dynamic slice per lag)."""
    m = a.shape[0]

    def one(l):
        seg = jax.lax.dynamic_slice(b_ext, (l,), (m,))
        return jnp.sum(a * seg)

    return jax.vmap(one)(jnp.arange(1, L + 1))


@functools.partial(jax.jit, static_argnames=("L",))
def lag_dot_ref(y, *, L: int):
    """Oracle for kernels.lag_dot: sxx[l-1] = sum_t y_t y_{t+l}."""
    return lag_xdot_ref(y, jnp.pad(y, (0, L)), L=L)
