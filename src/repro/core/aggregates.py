"""Incremental maintenance of the ACF aggregates (paper Eqs. 8-11).

This is the paper's core contribution: after removing a point (and replacing
the interior of the affected segment by linear interpolation), the five
per-lag aggregates are updated from the *delta vector* between the old and
new reconstruction — O(L) for a single-point delta, O(mL) for an m-point
segment — instead of recomputing the ACF in O(nL).

This module owns the exact *update* math (Eqs. 10-11) and the alive-neighbor
geometry:

* ``apply_delta_dense``   — exact update from a dense delta vector (used by
  the TPU batched-rounds mode: one O(nL) regular kernel per round, including
  the cross-lag bilinear term across *all* of this round's segments).
* ``apply_delta_window``  — exact update from a delta confined to a static
  window ``W`` (used by the paper-faithful sequential mode; Eq. 9).

The hypothetical-ACF *ranking* forms (Eqs. 8-9) live once in
``kernels/ref.py`` — ``acf_after_single_delta`` / ``acf_after_window_delta``
here are thin aliases kept for the core-level API, and all GetAllImpact
ranking dispatches through ``kernels/ops.py``.

All functions operate on the *target* series ``y`` (the raw series for
``kappa == 1``, or the tumbling-window aggregate series for Def. 2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.acf import Aggregates
from repro.kernels import ref as _ref

# head/tail validity masks live with the single-copy Eq. 8/9 math.
_lag_masks = _ref.head_tail_masks


# ---------------------------------------------------------------------------
# Dense exact update (rounds mode)
# ---------------------------------------------------------------------------

def apply_delta_dense(agg, y_old: jax.Array, delta: jax.Array, ny=None,
                      form: str = "auto"):
    """Exact aggregate update for an arbitrary dense delta vector.

    ``y_old`` is the reconstruction *before* the update.  Cost: O(ny + L) for
    the four moment sums (via cumulative sums) + one ``[ny] x [ny, L]``
    contraction for ``sxx`` (lag shifts gathered against a constant shift
    basis — no per-lag op chains).

    ``agg`` may be the ``Aggregates`` NamedTuple or the packed ``[5, L]``
    moment table (the rounds-mode loop carry); the update comes back in the
    same form — for the table that is a single fused add.

    ``ny`` (optionally traced) gives the valid length when ``y_old``/``delta``
    live in a zero-padded bucket; both must be zero beyond it.

    ``form`` picks the bilinear-term lowering: ``"gather"`` (two matvecs
    against the [nyb, L] shift basis), ``"roll"`` (one batched
    roll-and-reduce over the lag axis), or ``"auto"`` (roll on CPU, gather
    elsewhere — see the comment at the term).
    """
    nyb = y_old.shape[0]
    if ny is None:
        ny = nyb
    L = agg[0].shape[-1]
    l = jnp.arange(1, L + 1)

    cd = _ref.cumsum(delta)
    e = delta * (2.0 * y_old + delta)
    ce = _ref.cumsum(e)
    dtot, etot = cd[-1], ce[-1]

    dsx = cd[ny - 1 - l]
    dsx2 = ce[ny - 1 - l]
    dsxl = dtot - cd[l - 1]
    dsxl2 = etot - ce[l - 1]

    # new*new - old*old expanded over lag shifts:
    #   d_t*y_{t+l} + y_t*d_{t+l} + d_t*d_{t+l}
    #     = d_t*(y+d)_{t+l} + y_t*d_{t+l}
    # Backend-conditional trace-time form (parity-tested in
    # tests/test_contractions.py): XLA's CPU emitter runs both the [nyb, L]
    # shift-basis gather and a per-lag chain of 2L small dots an order of
    # magnitude slower than one batched roll+mask+reduce (the gather takes
    # the slow general-gather path; the dot chain is dispatch-bound).
    # Elsewhere the gathered basis keeps the whole term at
    # two matvecs against a [nyb, L] operand — matmul-shaped for the MXU.
    if form == "auto":
        form = "roll" if jax.default_backend() == "cpu" else "gather"
    if form == "roll":
        z = y_old + delta
        t = jnp.arange(nyb)

        def lag_term(ll):
            keep = (t <= (ny - 1 - ll)).astype(y_old.dtype)
            # roll wraps the head into the tail, so the validity mask is
            # load-bearing even with zero-padded operands
            return jnp.sum(keep * (delta * jnp.roll(z, -ll)
                                   + y_old * jnp.roll(delta, -ll)))

        dsxx = jax.vmap(lag_term)(l)
    else:
        z_pad = jnp.pad(y_old + delta, (0, L))
        d_pad = jnp.pad(delta, (0, L))
        t = jnp.arange(nyb)
        shift = t[:, None] + l[None, :]                   # [nyb, L]
        dsxx = delta @ z_pad[shift] + y_old @ d_pad[shift]

    dtable = jnp.stack([dsx, dsxl, dsx2, dsxl2, dsxx])
    if isinstance(agg, jax.Array):
        return agg + dtable
    return Aggregates(
        sx=agg.sx + dtable[0],
        sxl=agg.sxl + dtable[1],
        sx2=agg.sx2 + dtable[2],
        sxl2=agg.sxl2 + dtable[3],
        sxx=agg.sxx + dtable[4],
    )


def apply_delta_dense_ref(agg: Aggregates, y_old: jax.Array,
                          delta: jax.Array, ny=None) -> Aggregates:
    """Per-lag loop oracle for :func:`apply_delta_dense` (the historical
    vmapped roll-multiply-sum form), kept for parity tests of the shift-basis
    contraction."""
    nyb = y_old.shape[0]
    if ny is None:
        ny = nyb
    L = agg[0].shape[-1]
    l = jnp.arange(1, L + 1)

    cd = jnp.cumsum(delta)
    e = delta * (2.0 * y_old + delta)
    ce = jnp.cumsum(e)
    dtot, etot = cd[-1], ce[-1]

    dsx = cd[ny - 1 - l]
    dsx2 = ce[ny - 1 - l]
    dsxl = dtot - cd[l - 1]
    dsxl2 = etot - ce[l - 1]

    def lag_term(ll):
        mask = (jnp.arange(nyb) <= (ny - 1 - ll)).astype(y_old.dtype)
        y_sh = jnp.roll(y_old, -ll)
        d_sh = jnp.roll(delta, -ll)
        return jnp.sum(mask * (delta * y_sh + y_old * d_sh + delta * d_sh))

    dsxx = jax.vmap(lag_term)(l)
    return Aggregates(
        sx=agg[0] + dsx,
        sxl=agg[1] + dsxl,
        sx2=agg[2] + dsx2,
        sxl2=agg[3] + dsxl2,
        sxx=agg[4] + dsxx,
    )


# ---------------------------------------------------------------------------
# Windowed exact update (sequential mode, Eq. 9)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("W", "L"))
def apply_delta_window(
    agg: Aggregates,
    y_old: jax.Array,
    delta_win: jax.Array,   # [W] deltas for positions start .. start+W-1
    start: jax.Array,       # scalar int32: absolute index of delta_win[0]
    *,
    W: int,
    L: int,
) -> Aggregates:
    """Exact Eq. 9 update for a delta confined to ``W`` contiguous points.

    Out-of-range window positions must carry zero delta (masked by caller).
    Cost O(W * L).
    """
    ny = y_old.shape[0]
    dtype = y_old.dtype
    # Pad y by L left and L+W right so the slice below never clamps for any
    # start in [0, ny); head/tail masks null out padded contributions.
    y_pad = jnp.pad(y_old, (L, L + W))
    # ywin[j] == y_old[start - L + j] for j in [0, W + 2L)
    ywin = jax.lax.dynamic_slice(y_pad, (start,), (W + 2 * L,))
    j = jnp.arange(W)
    abs_t = start + j                                     # [W]
    head, tail = _lag_masks(abs_t, ny, L, dtype)          # [W, L]

    d = delta_win                                          # [W]
    y_at = ywin[L + j]                                     # y_old at window
    e = d * (2.0 * y_at + d)                               # [W]

    dsx = jnp.sum(d[:, None] * head, axis=0)
    dsxl = jnp.sum(d[:, None] * tail, axis=0)
    dsx2 = jnp.sum(e[:, None] * head, axis=0)
    dsxl2 = jnp.sum(e[:, None] * tail, axis=0)

    l = jnp.arange(1, L + 1)
    # y_{t+l} and y_{t-l} gathered from the padded window.
    y_fwd = ywin[(L + j)[:, None] + l[None, :]]            # [W, L]
    y_bwd = ywin[(L + j)[:, None] - l[None, :]]            # [W, L]
    # cross term d_t * d_{t+l}: pad delta window on the right by L.
    d_pad = jnp.pad(d, (0, L))
    d_fwd = d_pad[j[:, None] + l[None, :]]                 # [W, L]
    dsxx = jnp.sum(
        d[:, None] * (y_fwd * head + y_bwd * tail + d_fwd * head), axis=0
    )
    return Aggregates(
        sx=agg.sx + dsx,
        sxl=agg.sxl + dsxl,
        sx2=agg.sx2 + dsx2,
        sxl2=agg.sxl2 + dsxl2,
        sxx=agg.sxx + dsxx,
    )


# ---------------------------------------------------------------------------
# Vectorized single-delta impact (Algorithm 2 / Eq. 8) — ranking only
# ---------------------------------------------------------------------------

def acf_after_single_delta(
    agg: Aggregates,
    y: jax.Array,
    idx: jax.Array,     # [P] absolute indices receiving a delta
    dval: jax.Array,    # [P] delta magnitudes
) -> jax.Array:
    """Hypothetical ACF (per Eq. 8) after adding ``dval[p]`` at ``idx[p]``,
    independently for each p.  Returns ``[P, L]``.

    Thin alias: the math lives in ``kernels/ref.py`` (single source of
    truth, shared with the ``kernels/acf_impact`` Pallas kernel).
    """
    return _ref.acf_after_single_delta(agg, y, idx, dval)


def acf_after_window_delta_ctx(
    agg: Aggregates,
    y_ctx: jax.Array,    # [m + 2L + W] context: y_ctx[j] = y_global[off-L+j]
    starts: jax.Array,   # [P] *local* index of each window's first delta
    dwins: jax.Array,    # [P, W] per-candidate delta windows (zero-padded)
    *,
    ny: int,
    off,
) -> jax.Array:
    """Hypothetical ACF after applying each candidate's *windowed* delta
    independently (vectorized Eq. 9).  Returns ``[P, L]``.

    Thin alias for the single-copy math in ``kernels/ref.py`` (shared with
    the ``kernels/acf_window_impact`` Pallas kernel); see there for the
    context-layout contract.
    """
    return _ref.acf_after_window_delta_ctx(
        agg, y_ctx, starts, dwins, ny=ny, off=off)


def acf_after_window_delta(agg: Aggregates, y: jax.Array, starts: jax.Array,
                           dwins: jax.Array) -> jax.Array:
    """Single-partition wrapper around :func:`acf_after_window_delta_ctx`."""
    L = agg.sx.shape[0]
    W = dwins.shape[1]
    y_ctx = jnp.pad(y, (L, L + W))
    return acf_after_window_delta_ctx(
        agg, y_ctx, starts, dwins, ny=y.shape[0], off=0)


def segment_interp(xr: jax.Array, prev: jax.Array, nxt: jax.Array,
                   i: jax.Array, W: int):
    """Interpolated values over the interior of segment (prev[i], nxt[i]):
    the line between the segment endpoints, evaluated at the first ``W``
    interior positions.

    Vectorized over ``i``; returns ``(vals [..., W], absj [..., W],
    start [...], span [...])``.  ``absj`` are the absolute indices the
    values land on (clipped in-range); positions at or beyond the span
    carry garbage values the caller must mask (spans > W are truncated).
    The arithmetic matches :func:`interpolate_at` bit-for-bit, so a
    scatter of these values is exactly the reconstruction
    :func:`~repro.core.cameo._reconstruct` would produce there.
    """
    n = xr.shape[0]
    dt = xr.dtype
    p = prev[i]
    q = nxt[i]
    start = p + 1
    span = q - p - 1
    j = jnp.arange(W, dtype=jnp.int32)
    absj = jnp.clip(start[..., None] + j, 0, n - 1)
    pc = jnp.clip(p, 0, n - 1)[..., None]
    qc = jnp.clip(q, 0, n - 1)[..., None]
    denom = jnp.maximum((q - p).astype(dt), 1.0)[..., None]
    t = (absj - jnp.clip(p, 0, n - 1)[..., None]).astype(dt) / denom
    vals = xr[pc] + (xr[qc] - xr[pc]) * t
    return vals, absj, start, span


def segment_deltas(xr: jax.Array, prev: jax.Array, nxt: jax.Array,
                   i: jax.Array, W: int):
    """Delta window from removing point(s) ``i``: the interior of segment
    (prev[i], nxt[i]) is re-interpolated on the line between the endpoints.

    Vectorized over ``i``; returns ``(dwin [..., W], start [...], span [...])``
    with deltas zero beyond the span (spans > W are truncated — callers treat
    those candidates as unrankable).
    """
    dt = xr.dtype
    vals, absj, start, span = segment_interp(xr, prev, nxt, i, W)
    j = jnp.arange(W, dtype=jnp.int32)
    m = (j < span[..., None]).astype(dt)
    dwin = (vals - xr[absj]) * m
    return dwin, start, span


# ---------------------------------------------------------------------------
# Alive-neighbor machinery (replaces the paper's linked list, vectorized)
# ---------------------------------------------------------------------------

def alive_neighbors(alive: jax.Array):
    """For every index i, the nearest alive index strictly left / right.

    Returns ``(prev, nxt)`` int32 arrays; ``prev[i] = -1`` if none,
    ``nxt[i] = n`` if none.  O(n) via cumulative max/min.
    """
    n = alive.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    left_ids = jnp.where(alive, idx, jnp.int32(-1))
    prev_incl = jax.lax.associative_scan(jnp.maximum, left_ids)
    prev = jnp.concatenate([jnp.array([-1], jnp.int32), prev_incl[:-1]])
    right_ids = jnp.where(alive, idx, jnp.int32(n))
    nxt_incl = jax.lax.associative_scan(jnp.minimum, right_ids, reverse=True)
    nxt = jnp.concatenate([nxt_incl[1:], jnp.array([n], jnp.int32)])
    return prev, nxt


def neighbors_after_removal(prev: jax.Array, nxt: jax.Array,
                            removed: jax.Array):
    """``alive_neighbors`` after removing an *independent* set, by pointer
    jump: a removed point's own neighbors are alive (no two removed points
    are alive-adjacent), so any index whose neighbor was removed inherits
    that neighbor's neighbor.  O(n) gathers instead of two associative
    scans — exact (integer) equivalence with recomputing from scratch.
    """
    n = prev.shape[0]
    pj = jnp.clip(prev, 0, n - 1)
    qj = jnp.clip(nxt, 0, n - 1)
    prev_new = jnp.where(removed[pj] & (prev >= 0), prev[pj], prev)
    nxt_new = jnp.where(removed[qj] & (nxt <= n - 1), nxt[qj], nxt)
    return prev_new, nxt_new


def interpolate_at(x: jax.Array, prev: jax.Array, nxt: jax.Array, i: jax.Array):
    """Value of the line through the alive neighbors of i, evaluated at i."""
    n = x.shape[0]
    p = jnp.clip(prev, 0, n - 1)
    q = jnp.clip(nxt, 0, n - 1)
    xp, xq = x[p], x[q]
    denom = jnp.maximum((q - p).astype(x.dtype), 1.0)
    t = (i - p).astype(x.dtype) / denom
    return xp + (xq - xp) * t
