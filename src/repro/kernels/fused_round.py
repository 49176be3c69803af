"""Fused prefix-feasibility scan — the rounds-mode selection step.

One round of the batched-greedy mode picks the ``k_max`` lowest-impact
candidates (rank order), filters them to an independent set, and must then
find the largest rank prefix whose combined removal still satisfies the
deviation constraint.  The historical implementation bisected over prefix
length, re-running a dense O(nL) reconstruction + aggregate update per
probe.  This module computes the *whole deviation curve* — ``dev[j]`` =
exact deviation after applying candidates ``0..j`` — in one fused pass:

* reference backend — a closed-form vectorized evaluation.  Candidate
  segments are pairwise disjoint (independent-set invariant), so the linear
  aggregate deltas are a plain per-candidate einsum + cumulative sum; the
  quadratic terms (``sx2``/``sxl2``/``sxx``) see earlier candidates only
  through the running delta field ``D``, which is gathered per candidate
  from the exclusive cumulative delta rows.  O(K·(W + L)·L) total, no
  sequential dependence beyond two cumsums.

* pallas backend — a single kernel pass holding the running
  reconstruction ``z = y + D`` in VMEM scratch; each rank step reads its
  ``W + 2L`` context (a dynamic row offset plus one lane rotation), updates
  the five aggregates and the scratch in place, and emits that prefix's
  deviation.  This is the fused form of Eq. 9 ranking + Eq. 10/11
  maintenance the TPU path runs natively (interpret mode elsewhere, as with
  the other kernels in this package).

Both forms are exact for every ``kappa`` (the ``z``-context accounts for
boundary-bin sharing between segments mapped onto the aggregate series) and
take the valid length ``ny`` as a runtime scalar so padded-bucket callers
never recompile across lengths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import acf_window_impact as _awi
from repro.kernels import ref as _ref


# ---------------------------------------------------------------------------
# reference form: vectorized deviation curve
# ---------------------------------------------------------------------------

def _moment_deltas(d, ctx, ystarts, ny, *, L: int, form: str = "auto"):
    """Five per-lag aggregate deltas ``[K, 5, L]`` for independent windowed
    deltas ``d [K, Wy]`` given their series context ``ctx [K, Wy + 2L]``.

    ``form`` picks the bilinear-term lowering: ``"einsum"`` (shift-basis
    contraction), ``"roll"`` (one batched roll-and-reduce over the lag
    axis), ``"slices"`` (L-unrolled static slices), or ``"auto"`` (roll on
    CPU, einsum elsewhere — see the comment at the term).

    Relies on the padded-bucket discipline — the series (and hence ``ctx``)
    is zero beyond ``ny`` and before 0, and deltas only touch valid
    positions — which makes every head/tail validity mask either implicit
    (the bilinear ``sxx`` term: invalid partners are zero) or a contiguous
    cut in the window axis (the four moment sums: prefix-sum gathers
    instead of ``[K, Wy, L]`` mask einsums).
    """
    K, Wy = d.shape
    l = jnp.arange(1, L + 1)

    z_at = ctx[:, L:L + Wy]
    e = d * (2.0 * z_at + d)
    # head keeps abs_t <= ny-1-l  <=>  j < ny - l - s   (contiguous prefix);
    # tail keeps abs_t >= l       <=>  j >= l - s       (contiguous suffix).
    cdz = jnp.pad(_ref.cumsum(d, axis=1), ((0, 0), (1, 0)))
    cez = jnp.pad(_ref.cumsum(e, axis=1), ((0, 0), (1, 0)))
    c_head = jnp.clip(ny - l[None, :] - ystarts[:, None], 0, Wy)
    c_tail = jnp.clip(l[None, :] - ystarts[:, None], 0, Wy)
    dsx = jnp.take_along_axis(cdz, c_head, axis=1)
    dsx2 = jnp.take_along_axis(cez, c_head, axis=1)
    dsxl = cdz[:, -1:] - jnp.take_along_axis(cdz, c_tail, axis=1)
    dsxl2 = cez[:, -1:] - jnp.take_along_axis(cez, c_tail, axis=1)

    # Bilinear term, three equivalent lowerings.  As a contraction, the
    # lag-shifted context reads are three gathers against a constant
    # [Wy, L] shift basis, summed and contracted in one einsum — O(1)
    # emitted ops, the right shape wherever gathers run at memory speed
    # (TPU).  XLA's CPU emitter however runs that gather an order of
    # magnitude slower than contiguous reads (measured ~8ms/window on the
    # stream bench), and the historical L-unrolled static-slice chain is
    # dispatch-bound (2L+ emitted ops per call) — so on CPU the lag axis is
    # one *batched* roll-and-reduce:
    # a single vmapped op the emitter fuses into one [L, K, Wy] pass.  All
    # forms are pinned against each other by `tests/test_contractions.py`.
    d_pad = jnp.pad(d, ((0, 0), (0, L)))
    if form == "auto":
        form = "roll" if jax.default_backend() == "cpu" else "einsum"
    if form == "slices":
        dsxx = jnp.stack(
            [jnp.sum(d * (ctx[:, L + lag:L + lag + Wy]
                          + ctx[:, L - lag:L - lag + Wy]
                          + d_pad[:, lag:lag + Wy]), axis=1)
             for lag in range(1, L + 1)], axis=1)
    elif form == "roll":
        # No wraparound reaches the kept [:Wy] prefix: the largest shift is
        # L + lag <= 2L against width Wy + 2L (and lag <= L against the
        # d_pad width Wy + L), so no validity mask is needed.
        def lag_term(lag):
            g = (jnp.roll(ctx, -(L + lag), axis=1)[:, :Wy]
                 + jnp.roll(ctx, -(L - lag), axis=1)[:, :Wy]
                 + jnp.roll(d_pad, -lag, axis=1)[:, :Wy])
            return jnp.sum(d * g, axis=1)

        dsxx = jax.vmap(lag_term, out_axes=1)(l)
    else:
        w = jnp.arange(Wy)
        shift = w[:, None] + l[None, :]                   # [Wy, L]: w + lag
        G = ctx[:, L + shift] + ctx[:, (L + w[:, None]) - l[None, :]] \
            + d_pad[:, shift]
        dsxx = jnp.einsum("kw,kwl->kl", d, G)
    return jnp.stack([dsx, dsxl, dsx2, dsxl2, dsxx], axis=1)  # [K, 5, L]


def _moment_deltas_ref(d, ctx, ystarts, ny, *, L: int):
    """Loop oracle for :func:`_moment_deltas` — the historical L-unrolled
    slice-multiply-sum form of the bilinear term, kept for parity tests of
    the einsum contraction (`tests/test_contractions.py`)."""
    K, Wy = d.shape
    l = jnp.arange(1, L + 1)
    z_at = ctx[:, L:L + Wy]
    e = d * (2.0 * z_at + d)
    cdz = jnp.pad(jnp.cumsum(d, axis=1), ((0, 0), (1, 0)))
    cez = jnp.pad(jnp.cumsum(e, axis=1), ((0, 0), (1, 0)))
    c_head = jnp.clip(ny - l[None, :] - ystarts[:, None], 0, Wy)
    c_tail = jnp.clip(l[None, :] - ystarts[:, None], 0, Wy)
    dsx = jnp.take_along_axis(cdz, c_head, axis=1)
    dsx2 = jnp.take_along_axis(cez, c_head, axis=1)
    dsxl = cdz[:, -1:] - jnp.take_along_axis(cdz, c_tail, axis=1)
    dsxl2 = cez[:, -1:] - jnp.take_along_axis(cez, c_tail, axis=1)
    d_pad = jnp.pad(d, ((0, 0), (0, L)))
    dsxx = jnp.stack(
        [jnp.sum(d * (ctx[:, L + lag:L + lag + Wy]
                      + ctx[:, L - lag:L - lag + Wy]
                      + d_pad[:, lag:lag + Wy]), axis=1)
         for lag in range(1, L + 1)], axis=1)
    return jnp.stack([dsx, dsxl, dsx2, dsxl2, dsxx], axis=1)  # [K, 5, L]


def solo_moment_rows(y, dyws, ystarts, ny, *, L: int):
    """Aggregate-delta rows ``[K, 5, L]`` for each candidate applied *alone*
    on the current reconstruction (context gathered from ``y`` only)."""
    K, Wy = dyws.shape
    nyb = y.shape[0]
    dt = y.dtype
    starts = jnp.clip(ystarts, 0, nyb - 1)
    kk = jnp.arange(Wy + 2 * L)
    ctx = jnp.pad(y, (L, L + Wy))[starts[:, None] + kk[None, :]]
    return _moment_deltas(dyws.astype(dt), ctx, ystarts, ny, L=L)


def window_acf_rows(y, dyws, ystarts, agg_table, ny, *, L: int):
    """Independent per-candidate Eq. 9 ACF rows ``[K, L]`` under the
    padded-bucket discipline (mask-free form of
    ``ref.acf_after_window_delta_rows`` — the rounds-mode ranking hot path).
    """
    dt = y.dtype
    dagg = solo_moment_rows(y, dyws, ystarts, ny, L=L)
    cum = dagg + agg_table[None]
    l = jnp.arange(1, L + 1)
    m = (ny - l).astype(dt)[None, :]
    return _ref.acf_from_moments(cum[:, 0], cum[:, 1], cum[:, 2],
                                 cum[:, 3], cum[:, 4], m)


def window_rows(cfg, y, dyws, ystarts, agg_table, ny, *, L: int):
    """Backend-dispatched tier-impact rows: the Pallas kernel on a real TPU,
    the einsum contraction elsewhere (``ops.use_fused_kernel``)."""
    from repro.kernels import ops as _ops
    if _ops.use_fused_kernel(cfg.backend, y.dtype, cfg.stat, cfg.measure):
        return window_rows_pallas(y, dyws, ystarts, agg_table, ny, L=L)
    return window_acf_rows(y, dyws, ystarts, agg_table, ny, L=L)


def prefix_moment_rows(y, dyws, ystarts, ok, ny, *, L: int):
    """Per-candidate aggregate-delta rows ``[K, 5, L]`` under the running
    reconstruction that applies every earlier ``ok`` candidate.

    ``dyws [K, Wy]`` are the candidates' aggregate-space delta windows in
    rank order, starting at ``ystarts [K]``; ``ok [K]`` gates which rank
    positions actually apply (independent-set survivors).  ``ny`` is the
    (possibly traced) valid length of ``y``; ``y`` must be zero-padded
    beyond it.
    """
    K, Wy = dyws.shape
    nyb = y.shape[0]
    dt = y.dtype
    d = dyws * ok.astype(dt)[:, None]
    starts = jnp.clip(ystarts, 0, nyb - 1)

    # Exclusive running delta field D_{<j}, as dense per-candidate rows.
    place = jax.vmap(
        lambda dr, s: jax.lax.dynamic_update_slice(
            jnp.zeros((nyb + Wy,), dt), dr, (s,))[:nyb])(d, starts)
    d_ex = _ref.cumsum(place, axis=0) - place

    # Per-candidate context of the running reconstruction z = y + D_{<j}.
    kk = jnp.arange(Wy + 2 * L)
    gidx = starts[:, None] + kk[None, :]
    y_pad = jnp.pad(y, (L, L + Wy))
    dex_pad = jnp.pad(d_ex, ((0, 0), (L, L + Wy)))
    ctx = y_pad[gidx] + jnp.take_along_axis(dex_pad, gidx, axis=1)

    return _moment_deltas(d, ctx, ystarts, ny, L=L)           # [K, 5, L]


def prefix_acf_rows_ref(y, dyws, ystarts, ok, agg_table, ny, *, L: int):
    """ACF rows ``[K, L]`` after each rank-prefix of windowed removals
    (see :func:`prefix_moment_rows` for the argument contract)."""
    dt = y.dtype
    dagg = prefix_moment_rows(y, dyws, ystarts, ok, ny, L=L)
    cum = _ref.cumsum(dagg, axis=0) + agg_table[None]
    l = jnp.arange(1, L + 1)
    m = (ny - l).astype(dt)[None, :]
    return _ref.acf_from_moments(cum[:, 0], cum[:, 1], cum[:, 2],
                                 cum[:, 3], cum[:, 4], m)


# ---------------------------------------------------------------------------
# pallas form: independent per-candidate Eq. 9 rows
# ---------------------------------------------------------------------------

def _window_rows_kernel(cT_ref, cR_ref, dT_ref, s_ref, tab_ref, ny_ref,
                        out_ref, *, Wy: int, Lp: int):
    """Per-candidate trial ACF rows for one 128-candidate lane block — the
    kernel twin of :func:`window_acf_rows` (tier-impact ranking), on the
    Eq. 9 body shared with ``acf_window_impact``."""
    ny = ny_ref[...]
    moments = _awi.window_moments(cT_ref, cR_ref, dT_ref, s_ref, ny,
                                  W=Wy, Lp=Lp)
    out_ref[...] = _awi.acf_rows(tab_ref[...], moments, ny, Lp)


@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def window_rows_pallas(y, dyws, ystarts, agg_table, ny, *, L: int,
                       interpret: bool = False):
    """Pallas form of :func:`window_acf_rows`: per-candidate Eq. 9 ACF rows
    ``[K, L]``.  TPU decision path (interpret mode for parity tests only —
    same convention as :func:`prefix_devs_pallas`)."""
    K, Wy = dyws.shape
    nyb = y.shape[0]
    dtype = y.dtype
    Lp = _awi._pad8(L)
    starts = jnp.clip(ystarts, 0, nyb - 1).astype(jnp.int32)
    kk = jnp.arange(Wy + 2 * Lp)
    ctx = jnp.pad(y, (Lp, Lp + Wy))[starts[:, None] + kk[None, :]]
    cT, cR, dT, s = _awi.transposed_operands(ctx, dyws.astype(dtype), starts,
                                             Lp)
    Hc, Kp = cT.shape
    kernel = functools.partial(_window_rows_kernel, Wy=Wy, Lp=Lp)
    rows = pl.pallas_call(
        kernel,
        name="window_rows_pallas",
        grid=(Kp // _awi.LANES,),
        in_specs=_awi.window_specs(Hc, dT.shape[0], Lp),
        out_specs=pl.BlockSpec((Lp, _awi.LANES),
                               lambda i: (_awi._zero(), i)),
        out_shape=jax.ShapeDtypeStruct((Lp, Kp), dtype),
        interpret=interpret,
    )(cT, cR, dT, s, _awi.moment_table(agg_table.astype(dtype), None, Lp),
      _awi.lane_scalar(ny))
    return rows[:L, :K].T


# ---------------------------------------------------------------------------
# pallas form: one fused pass with the running reconstruction in VMEM
# ---------------------------------------------------------------------------

def _prefix_scan_kernel(s_ref, ok_ref, ny_ref, eps_ref, y_ref, d_ref,
                        tab_ref, out_ref, z_ref, *, K: int, L: int, Lp: int,
                        NR: int, measure: str, greedy: bool):
    """Walk the K candidates in rank order against the running
    reconstruction ``z`` (VMEM scratch, rows of 128 lanes, position ``q``
    at ``z[(q + Lp) // 128, (q + Lp) % 128]``).

    Candidate ``k`` reads the ``NR`` rows holding its ``Wcp``-lane context
    (dynamic row offset), aligns them with one dynamic lane rotation, and
    builds every lag-shifted view it needs with *strided* rotations: row
    ``r`` of ``roll(broadcast(v), 1, stride=1)`` is ``v`` shifted by lag
    ``r + 1``.  The backward products read ``z[t - l]`` that way; the
    forward ones are rewritten over the shifted *delta*
    (``sum_t d_t z_{t+l} = sum_u d_{u-l} z_u``), so no view needs a
    backward stride.  A committed delta is rotated back and added to the
    same rows.
    """
    dtype = z_ref.dtype
    LANES = _awi.LANES
    Wcp = d_ref.shape[1]
    Wn = NR * LANES
    z_ref[...] = y_ref[...]
    ny = ny_ref[0]
    eps = eps_ref[0]
    tab = tab_ref[...]
    lag = jax.lax.broadcasted_iota(jnp.int32, (Lp, Wcp), 0) + 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Wcp), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    zero = jnp.zeros((Lp, Wcp), dtype)
    pad_n = jnp.zeros((1, Wn - Wcp), dtype)

    def shifted(v):
        return pltpu.roll(jnp.broadcast_to(v, (Lp, Wcp)), jnp.int32(1), 1,
                          stride=1, stride_axis=0)

    def step(k, agg5):
        s = s_ref[k]
        # (lax.div/rem: jnp's floor-division lowering does not compile
        # in a kernel under x64; s >= 0, so truncation is floor)
        r0 = jax.lax.div(s, jnp.int32(LANES))
        c0 = jax.lax.rem(s, jnp.int32(LANES))
        rows = [z_ref[pl.ds(r0 + i, 1), :] for i in range(NR)]
        w = pltpu.roll(jnp.concatenate(rows, axis=1), jax.lax.rem(Wn - c0, jnp.int32(Wn)),
                       1)[:, :Wcp]                   # w[m] = z[s - Lp + m]
        d = d_ref[pl.ds(k, 1), :]                    # delta at lanes Lp + j
        pos = s - Lp + lane
        e = d * (2.0 * w + d)
        head = pos + lag <= ny - 1
        tail = pos >= lag
        fwd = pos <= ny - 1

        def lane_sum(x):
            return jnp.sum(x, axis=1, keepdims=True)

        trial = (
            agg5[0] + lane_sum(jnp.where(head, d, zero)),
            agg5[1] + lane_sum(jnp.where(tail, d, zero)),
            agg5[2] + lane_sum(jnp.where(head, e, zero)),
            agg5[3] + lane_sum(jnp.where(tail, e, zero)),
            agg5[4] + lane_sum(
                jnp.where(tail, d * shifted(w), zero)
                + jnp.where(fwd, (w + d) * shifted(d), zero)),
        )
        rho = _awi.acf_from_columns(*trial, ny=ny, Lp=Lp)
        dev = _awi.measure_lags(rho, tab[:, 5:6], L, measure)   # [1, 1]
        # deviation k goes to lane k % 128 of row k // 128
        ro = jax.lax.div(k, jnp.int32(LANES))
        out_ref[pl.ds(ro, 1), :] = jnp.where(
            out_lane == jax.lax.rem(k, jnp.int32(LANES)), dev,
            out_ref[pl.ds(ro, 1), :])
        if greedy:
            # Conditional commit: the candidate joins the running
            # reconstruction only when its trial deviation fits.
            take = (ok_ref[k] > 0) & (dev <= eps)
            d = jnp.where(take, d, jnp.zeros_like(d))
            agg5 = tuple(jnp.where(take, t, a) for t, a in zip(trial, agg5))
        else:
            agg5 = trial
        back = pltpu.roll(jnp.concatenate([d, pad_n], axis=1), c0, 1)
        for i in range(NR):
            z_ref[pl.ds(r0 + i, 1), :] = (
                rows[i] + back[:, i * LANES:(i + 1) * LANES])
        return agg5

    agg0 = tuple(tab[:, c:c + 1] for c in range(5))
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(K), step, agg0)


def _prefix_layout(nyb: int, K: int, Wy: int, L: int):
    """``(Lp, Wcp, NR, R)``: padded lags, context lanes, context rows and
    reconstruction rows of :func:`prefix_devs_pallas`."""
    LANES = _awi.LANES
    Lp = _awi._pad8(L)
    Wcp = -(-(Wy + 2 * Lp) // LANES) * LANES
    NR = Wcp // LANES + 1
    return Lp, Wcp, NR, (nyb - 1) // LANES + NR + 1


def prefix_devs_footprint(nyb: int, K: int, Wy: int, L: int):
    """``(vmem_bytes, smem_bytes)`` the fused prefix kernel holds at
    float32: the running reconstruction (input block plus scratch) and the
    candidates' delta rows in VMEM, their starts and gates in SMEM."""
    _, Wcp, _, R = _prefix_layout(nyb, K, Wy, L)
    return 4 * (2 * R * _awi.LANES + _awi._pad8(K) * Wcp), 8 * K


@functools.partial(jax.jit,
                   static_argnames=("L", "measure", "greedy", "interpret"))
def prefix_devs_pallas(y, dyws, ystarts, ok, agg_table, p0, ny, eps=None, *,
                       L: int, measure: str = "mae", greedy: bool = False,
                       interpret: bool = False):
    """Per-rank deviations [K] via the fused Pallas round kernel.

    With ``greedy=False`` every ``ok`` candidate commits and the output is
    the prefix deviation curve.  With ``greedy=True`` a candidate commits
    only when its trial deviation fits within ``eps`` — the output is each
    candidate's *trial* deviation on top of the committed set, so the taken
    mask is recovered as ``ok & (out <= eps)``.

    The whole call stays in fast memory (:func:`prefix_devs_footprint`);
    the dispatch refuses it past the chip's budget, and
    ``kernels/README.md`` records the largest buckets that compile for
    v5e.
    """
    LANES = _awi.LANES
    K, Wy = dyws.shape
    nyb = y.shape[0]
    dtype = y.dtype
    Lp, Wcp, NR, R = _prefix_layout(nyb, K, Wy, L)
    z0 = jnp.pad(y.astype(dtype), (Lp, R * LANES - nyb - Lp)).reshape(R, LANES)
    okf = ok.astype(dtype)
    Kp = _awi._pad8(K)
    d_lanes = jnp.pad(dyws.astype(dtype) * okf[:, None],
                      ((0, Kp - K), (Lp, Wcp - Lp - Wy)))
    starts = jnp.clip(ystarts, 0, nyb - 1).astype(jnp.int32)
    ny_arr = jnp.asarray(ny, jnp.int32).reshape(1)
    eps_arr = jnp.asarray(
        jnp.inf if eps is None else eps, dtype).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kernel = functools.partial(
        _prefix_scan_kernel, K=K, L=L, Lp=Lp, NR=NR, measure=measure,
        greedy=greedy)
    out = pl.pallas_call(
        kernel,
        name="prefix_devs",
        in_specs=[smem, smem, smem, smem, vmem, vmem, vmem],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((_awi._pad8(-(-K // LANES)), LANES),
                                       dtype),
        scratch_shapes=[pltpu.VMEM((R, LANES), dtype)],
        interpret=interpret,
    )(starts, okf, ny_arr, eps_arr, z0, d_lanes,
      _awi.moment_table(agg_table.astype(dtype), p0, Lp))
    return out.reshape(-1)[:K]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def greedy_feasible(cfg, y, dyws, ystarts, ok, agg, p0, ny, eps):
    """Backend-dispatched greedy feasible-subset selection for one round.

    Walks the rank-ordered candidates once, committing each candidate whose
    trial deviation on top of the already-committed set stays within
    ``eps`` — violators are *skipped*, not terminal, so the round harvests
    every boundary-compatible candidate instead of stopping at the first
    infeasible prefix.  Returns ``(take [K] bool, devs [K])`` where ``devs``
    are the per-candidate trial deviations.

    The Pallas form maintains the exact committed reconstruction in VMEM.
    The reference form scans precomputed aggregate-delta rows whose contexts
    assume every earlier ``ok`` candidate applied — a skip leaves a small
    cross-lag bilinear error in later rows, which is why callers must
    re-validate the final subset with the authoritative dense update (the
    rounds loop does, with the feasible prefix as fallback).
    """
    from repro.core import measures as _measures
    from repro.kernels import ops as _ops
    table = _ops.agg_to_table(agg)
    L = cfg.lags
    dt = y.dtype
    if _ops.use_fused_kernel(
            cfg.backend, dt, cfg.stat, cfg.measure,
            prefix_devs_footprint(y.shape[0], *dyws.shape, L)):
        devs = prefix_devs_pallas(
            y, dyws, ystarts, ok, table, p0, ny, eps, L=L,
            measure=cfg.measure, greedy=True, interpret=False)
        return ok & (devs <= eps), devs

    dagg = prefix_moment_rows(y, dyws, ystarts, ok, ny, L=L)
    l = jnp.arange(1, L + 1)
    m = (ny - l).astype(dt)
    if cfg.stat == "acf" and cfg.measure in _ref.KERNEL_MEASURES:
        def dev_fn(rho):
            return _ref.measure_rows(rho[None], p0, cfg.measure)[0]
    else:
        mfn = _measures.get_measure(cfg.measure)
        transform = _ops._transform_fn(cfg.stat)

        def dev_fn(rho):
            return mfn(transform(rho), p0)

    def step(cum, inp):
        dk, okk = inp
        trial = cum + dk
        rho = _ref.acf_from_moments(trial[0], trial[1], trial[2],
                                    trial[3], trial[4], m)
        dev = dev_fn(rho)
        take = okk & (dev <= eps)
        return jnp.where(take, trial, cum), (take, dev)

    _, (take, devs) = jax.lax.scan(step, table, (dagg, ok))
    return take, devs
