"""Coarse-grained parallel CAMEO (paper §4.4) mapped onto JAX collectives.

The paper partitions the series across T threads; each thread compresses its
partition against a local budget ``p*eps/T`` and synchronizes aggregates
lazily, with the cross-partition ``sxx_l`` overlap terms handled separately.

TPU adaptation (DESIGN.md §2): per-round synchronization of the five [L]
aggregates is a ~KB ``psum`` — negligible on ICI — so the *lockstep* variant
checks the **global** constraint every round (a strictly tighter guarantee
than the paper's local budgets) while all ranking/selection/reconstruction
work stays partition-local.  Overlap regions are L-point halos exchanged with
``ppermute`` (shard_map) or array shifts (single-device global form).

Three entry points:

* :func:`compress_partitioned`          — lockstep, global-array form
  ([T, m] stacked partitions, axis-0 reductions standing in for psum).
  Runs on any device count; used by tests and the Fig. 10/11 benchmarks.
* :func:`compress_partitioned_shardmap` — lockstep under ``shard_map`` with
  ``psum``/``ppermute``; same math, one partition per device.
* :func:`compress_partitioned_local`    — paper-faithful local-budget
  variant (independent per-partition compressions at ``p*eps/T``; exact
  global deviation reported after merging).

Partition borders are pinned alive, so interpolation never crosses chunks
(the paper's partitions behave identically).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.core.acf import (Aggregates, acf, acf_from_aggregates,
                            aggregate_series, centered)
from repro.core.cameo import (
    CameoConfig,
    CompressResult,
    _independent_set,
    _measure_fn,
    _reconstruct,
    _stat_transform,
    _x_to_y_delta,
    compress_rounds,
)
from repro.kernels import ops as _ops
from repro.kernels import ref as _ref


# ---------------------------------------------------------------------------
# per-chunk aggregate contributions (overlap terms via right halos)
# ---------------------------------------------------------------------------

def chunk_agg_contrib(y_c, halo_r, off, ny: int, L: int,
                      backend: str = "auto") -> Aggregates:
    """This chunk's contribution to the global per-lag aggregates.

    ``halo_r`` is the next chunk's first L values (zeros past the series
    end) — it carries exactly the paper's ``sxx_l(Overlap_ij)`` cross terms.
    Summing contributions over chunks (``psum``) yields the global Eq. 7
    aggregates exactly: each lag pair (t, t+l) is owned by the chunk of t.
    """
    m = y_c.shape[0]
    l = jnp.arange(1, L + 1)
    csum = _ref.cumsum(y_c)
    csum2 = _ref.cumsum(y_c * y_c)
    total, total2 = csum[-1], csum2[-1]

    # head: sum of y_c[t] with off+t <= ny-1-l
    hi = (ny - 1 - off) - l                     # local head end, may be <0/>m
    sx = jnp.where(hi >= 0, csum[jnp.clip(hi, 0, m - 1)], 0.0)
    sx2 = jnp.where(hi >= 0, csum2[jnp.clip(hi, 0, m - 1)], 0.0)
    # tail: sum of y_c[t] with off+t >= l
    lo = l - off
    sxl = jnp.where(lo <= 0, total,
                    jnp.where(lo >= m, 0.0,
                              total - csum[jnp.clip(lo - 1, 0, m - 1)]))
    sxl2 = jnp.where(lo <= 0, total2,
                     jnp.where(lo >= m, 0.0,
                               total2 - csum2[jnp.clip(lo - 1, 0, m - 1)]))
    # lagged products: the zero halo past the series end masks invalid pairs
    sxx = _ops.lag_dot(y_c, L, halo=halo_r, backend=backend)
    return Aggregates(sx=sx, sxl=sxl, sx2=sx2, sxl2=sxl2, sxx=sxx)


def chunk_delta_contrib(y_c, d_c, halo_y, halo_d, off, ny: int, L: int,
                        backend: str = "auto") -> Aggregates:
    """This chunk's contribution to the global aggregate *delta* for a dense
    per-chunk delta ``d_c`` (Eq. 9 generalized across partitions).

    ``halo_y``/``halo_d`` are the next chunk's first L old-values/deltas.
    """
    m = y_c.shape[0]
    l = jnp.arange(1, L + 1)
    e = d_c * (2.0 * y_c + d_c)
    cd, ce = _ref.cumsum(d_c), _ref.cumsum(e)
    dtot, etot = cd[-1], ce[-1]

    hi = (ny - 1 - off) - l
    dsx = jnp.where(hi >= 0, cd[jnp.clip(hi, 0, m - 1)], 0.0)
    dsx2 = jnp.where(hi >= 0, ce[jnp.clip(hi, 0, m - 1)], 0.0)
    lo = l - off
    dsxl = jnp.where(lo <= 0, dtot,
                     jnp.where(lo >= m, 0.0,
                               dtot - cd[jnp.clip(lo - 1, 0, m - 1)]))
    dsxl2 = jnp.where(lo <= 0, etot,
                      jnp.where(lo >= m, 0.0,
                                etot - ce[jnp.clip(lo - 1, 0, m - 1)]))

    # new*new - old*old expanded per lag pair:
    #   d_t y_{t+l} + y_t d_{t+l} + d_t d_{t+l}  — three halo'd lagged dots
    dsxx = (_ops.lag_dot(d_c, L, b=y_c, halo=halo_y, backend=backend)
            + _ops.lag_dot(y_c, L, b=d_c, halo=halo_d, backend=backend)
            + _ops.lag_dot(d_c, L, b=d_c, halo=halo_d, backend=backend))
    return Aggregates(sx=dsx, sxl=dsxl, sx2=dsx2, sxl2=dsxl2, sxx=dsxx)


def _chunk_select(impact, alive_c, k_dyn, k_max: int):
    mx = impact.shape[0]
    neg_vals, sel_idx = jax.lax.top_k(-impact, k_max)
    vals = -neg_vals
    rank_ok = (jnp.arange(k_max) < k_dyn) & jnp.isfinite(vals)
    sel = jnp.zeros((mx,), bool).at[sel_idx].set(rank_ok, mode="drop")
    return _independent_set(sel, impact, alive_c)


def _plan(cfg: CameoConfig, n: int, T: int):
    mx = n // T
    kap = cfg.kappa
    my = mx // kap
    ny = n // kap
    L, W = cfg.lags, cfg.window
    if n % T or mx % kap:
        raise ValueError(f"n={n} must be divisible by T*kappa={T}*{kap}")
    if my < L + W:
        raise ValueError(
            f"partition too small: my={my} < L+W={L + W}; lower T or W")
    if cfg.target_cr is not None:
        min_alive = max(2, int(np.ceil(n / cfg.target_cr)))
        eps = float("inf")
    else:
        min_alive = 2
        eps = cfg.eps
    if cfg.max_cr is not None:
        min_alive = max(min_alive, int(np.ceil(n / cfg.max_cr)))
    k_max = max(1, int(cfg.alpha * mx))
    return mx, my, ny, min_alive, eps, k_max


# ---------------------------------------------------------------------------
# lockstep partitioned compression — global-array form
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "T"))
def compress_partitioned(x: jax.Array, cfg: CameoConfig, T: int) -> CompressResult:
    dt = cfg.jdtype()
    x = x.astype(dt)
    n = x.shape[0]
    L, W, kap = cfg.lags, cfg.window, cfg.kappa
    mx, my, ny, min_alive, eps_f, k_max = _plan(cfg, n, T)
    eps = jnp.asarray(eps_f, dt)
    transform = _stat_transform(cfg)
    mfn = _measure_fn(cfg)

    xp = x.reshape(T, mx)
    offs_y = jnp.arange(T, dtype=jnp.int32) * my

    def right_halo(yparts, width):
        nxt_chunk = jnp.concatenate([yparts[1:], jnp.zeros((1, my), dt)], 0)
        return nxt_chunk[:, :width]

    def left_halo(yparts):
        prv = jnp.concatenate([jnp.zeros((1, my), dt), yparts[:-1]], 0)
        return prv[:, my - L:]

    def global_agg_from(yparts):
        contribs = jax.vmap(
            lambda yc, hr, off: chunk_agg_contrib(
                yc, hr, off, ny, L, backend=cfg.backend)
        )(yparts, right_halo(yparts, L), offs_y)
        return jax.tree.map(lambda a: a.sum(0), contribs)

    yp0 = centered(jax.vmap(lambda c: aggregate_series(c, kap))(xp))
    agg0 = global_agg_from(yp0)
    p0 = transform(acf_from_aggregates(agg0, ny))

    impacts_fn = functools.partial(_ops.chunk_ranking_impact, cfg)

    def cond(c):
        (xr, alive, yp, agg, alpha, dev, rounds, done, blocked) = c
        return (~done) & (rounds < cfg.max_rounds) & \
            (jnp.sum(alive) > min_alive)

    def body(c):
        (xr, alive, yp, agg, alpha, dev, rounds, done, blocked) = c
        inf = jnp.asarray(jnp.inf, dt)
        hl = left_halo(yp)
        hr = right_halo(yp, L + W)
        y_ctx = jnp.concatenate([hl, yp, hr], axis=1)      # [T, my+2L+W]
        impact = jax.vmap(
            lambda ctx, xc, ac, off: impacts_fn(agg, ctx, xc, ac, p0, off, ny)
        )(y_ctx, xr, alive, offs_y)                        # [T, mx]
        impact = jnp.where(blocked, inf, impact)

        alive_local = jnp.sum(alive, axis=1)
        k_dyn = jnp.maximum(1, (alpha * alive_local.astype(dt)).astype(jnp.int32))
        sel = jax.vmap(lambda im, ac, k: _chunk_select(im, ac, k, k_max))(
            impact, alive, k_dyn)
        n_sel = jnp.sum(sel)
        any_sel = n_sel > 0

        alive_new = alive & (~sel)
        xr_new = jax.vmap(_reconstruct)(xp, alive_new)
        delta_x = xr_new - xr
        dyp = jax.vmap(lambda d: _x_to_y_delta(d, kap, dt))(delta_x)
        dcontrib = jax.vmap(
            lambda yc, dc, hy, hd, off: chunk_delta_contrib(
                yc, dc, hy, hd, off, ny, L, backend=cfg.backend)
        )(yp, dyp, right_halo(yp, L), right_halo(dyp, L), offs_y)
        dagg = jax.tree.map(lambda a: a.sum(0), dcontrib)
        agg_new = jax.tree.map(lambda a, d: a + d, agg, dagg)
        dev_new = mfn(transform(acf_from_aggregates(agg_new, ny)), p0)

        accept = (dev_new <= eps) & any_sel
        single_fail = (~accept) & (n_sel <= 1) & any_sel
        blocked_new = jnp.where(
            accept, jnp.zeros_like(blocked),
            jnp.where(single_fail, blocked | sel, blocked))
        exhausted = ~jnp.any(alive & (~blocked_new) & jnp.isfinite(impact))
        done_new = done | (~any_sel) | ((~accept) & exhausted)
        alpha_new = jnp.where(accept, jnp.minimum(alpha * 1.1, cfg.alpha),
                              jnp.maximum(alpha * 0.5, jnp.asarray(1.5 / mx, dt)))

        pick = lambda newv, oldv: jnp.where(accept, newv, oldv)
        return (pick(xr_new, xr), pick(alive_new, alive), pick(yp + dyp, yp),
                jax.tree.map(pick, agg_new, agg), alpha_new,
                pick(dev_new, dev), rounds + 1, done_new, blocked_new)

    init = (xp, jnp.ones((T, mx), bool), yp0, agg0,
            jnp.asarray(cfg.alpha, dt), jnp.asarray(0.0, dt),
            jnp.asarray(0, jnp.int32), jnp.asarray(False),
            jnp.zeros((T, mx), bool))
    (xr, alive, yp, agg, _, dev, rounds, _, _) = jax.lax.while_loop(
        cond, body, init)
    stat_new = transform(acf_from_aggregates(agg, ny))
    return CompressResult(
        kept=alive.reshape(n), xr=xr.reshape(n), deviation=dev,
        n_kept=jnp.sum(alive), iters=rounds, stat_orig=p0, stat_new=stat_new)


# ---------------------------------------------------------------------------
# lockstep partitioned compression — shard_map form (one partition/device)
# ---------------------------------------------------------------------------

def compress_partitioned_shardmap(x, cfg: CameoConfig, mesh, axis: str = "data"):
    """Same algorithm as :func:`compress_partitioned`, with axis-0 reductions
    replaced by ``psum`` and halo shifts by ``ppermute``.  ``x`` must be
    evenly divisible over ``mesh.shape[axis]`` partitions."""
    T = mesh.shape[axis]
    dt = cfg.jdtype()
    n = x.shape[0]
    L, W, kap = cfg.lags, cfg.window, cfg.kappa
    mx, my, ny, min_alive, eps_f, k_max = _plan(cfg, n, T)
    eps = jnp.asarray(eps_f, dt)
    transform = _stat_transform(cfg)
    mfn = _measure_fn(cfg)
    impacts_fn = functools.partial(_ops.chunk_ranking_impact, cfg)

    fwd = [(i, i - 1) for i in range(1, T)]   # i sends to i-1 (right halo)
    bwd = [(i, i + 1) for i in range(T - 1)]  # i sends to i+1 (left halo)

    def right_halo(y_c, width):
        return jax.lax.ppermute(y_c[:width], axis, fwd)

    def left_halo(y_c):
        return jax.lax.ppermute(y_c[my - L:], axis, bwd)

    def body_shard(x_c):
        x_c = x_c.astype(dt)
        off_y = jax.lax.axis_index(axis).astype(jnp.int32) * my
        y0 = centered(aggregate_series(x_c, kap), ny, axis_name=axis)
        agg0 = jax.tree.map(
            lambda a: jax.lax.psum(a, axis),
            chunk_agg_contrib(y0, right_halo(y0, L), off_y, ny, L,
                              backend=cfg.backend))
        p0 = transform(acf_from_aggregates(agg0, ny))

        def cond(c):
            (xr, alive, y, agg, alpha, dev, rounds, done, blocked) = c
            n_alive = jax.lax.psum(jnp.sum(alive), axis)
            return (~done) & (rounds < cfg.max_rounds) & (n_alive > min_alive)

        def body(c):
            (xr, alive, y, agg, alpha, dev, rounds, done, blocked) = c
            inf = jnp.asarray(jnp.inf, dt)
            y_ctx = jnp.concatenate([left_halo(y), y, right_halo(y, L + W)])
            impact = impacts_fn(agg, y_ctx, xr, alive, p0, off_y, ny)
            impact = jnp.where(blocked, inf, impact)

            alive_local = jnp.sum(alive)
            k_dyn = jnp.maximum(1, (alpha * alive_local.astype(dt)).astype(jnp.int32))
            sel = _chunk_select(impact, alive, k_dyn, k_max)
            n_sel = jax.lax.psum(jnp.sum(sel), axis)
            any_sel = n_sel > 0

            alive_new = alive & (~sel)
            xr_new = _reconstruct(x_c, alive_new)
            delta_x = xr_new - xr
            dy = _x_to_y_delta(delta_x, kap, dt)
            dagg = jax.tree.map(
                lambda a: jax.lax.psum(a, axis),
                chunk_delta_contrib(y, dy, right_halo(y, L),
                                    right_halo(dy, L), off_y, ny, L,
                                    backend=cfg.backend))
            agg_new = jax.tree.map(lambda a, d: a + d, agg, dagg)
            dev_new = mfn(transform(acf_from_aggregates(agg_new, ny)), p0)

            accept = (dev_new <= eps) & any_sel
            single_fail = (~accept) & (n_sel <= 1) & any_sel
            blocked_new = jnp.where(
                accept, jnp.zeros_like(blocked),
                jnp.where(single_fail, blocked | sel, blocked))
            has_candidates = jax.lax.psum(
                jnp.sum(alive & (~blocked_new) & jnp.isfinite(impact)), axis)
            done_new = done | (~any_sel) | ((~accept) & (has_candidates == 0))
            alpha_new = jnp.where(
                accept, jnp.minimum(alpha * 1.1, cfg.alpha),
                jnp.maximum(alpha * 0.5, jnp.asarray(1.5 / mx, dt)))

            pick = lambda newv, oldv: jnp.where(accept, newv, oldv)
            return (pick(xr_new, xr), pick(alive_new, alive), pick(y + dy, y),
                    jax.tree.map(pick, agg_new, agg), alpha_new,
                    pick(dev_new, dev), rounds + 1, done_new, blocked_new)

        init = (x_c, jnp.ones((mx,), bool), y0, agg0,
                jnp.asarray(cfg.alpha, dt), jnp.asarray(0.0, dt),
                jnp.asarray(0, jnp.int32), jnp.asarray(False),
                jnp.zeros((mx,), bool))
        (xr, alive, y, agg, _, dev, rounds, _, _) = jax.lax.while_loop(
            cond, body, init)
        stat_new = transform(acf_from_aggregates(agg, ny))
        n_kept = jax.lax.psum(jnp.sum(alive), axis)
        return xr, alive, dev, n_kept, rounds, p0, stat_new

    shard = shd.shard_map(
        body_shard, mesh=mesh,
        in_specs=P(axis),
        out_specs=(P(axis), P(axis), P(), P(), P(), P(), P()))
    xr, alive, dev, n_kept, rounds, p0, stat_new = jax.jit(shard)(x)
    return CompressResult(kept=alive, xr=xr, deviation=dev, n_kept=n_kept,
                          iters=rounds, stat_orig=p0, stat_new=stat_new)


# ---------------------------------------------------------------------------
# paper-faithful local-budget variant (§4.4 coarse-grained semantics)
# ---------------------------------------------------------------------------

def compress_partitioned_local(x, cfg: CameoConfig, T: int, p: float = 1.0):
    """Independent per-partition compressions with local budget ``p*eps/T``
    (the paper's §4.4 semantics).  Reports the exact *global* deviation of
    the merged reconstruction (measured, not guaranteed, exactly as in the
    paper, where partitions synchronize only when exhausting their budget).
    """
    dt = cfg.jdtype()
    x = jnp.asarray(x, dt)
    n = x.shape[0]
    if n % T:
        raise ValueError(f"n={n} not divisible by T={T}")
    mx = n // T
    local_cfg = dataclasses.replace(cfg, eps=cfg.eps * p / T)
    res = jax.vmap(lambda c: compress_rounds(c, local_cfg))(x.reshape(T, mx))
    kept = res.kept.reshape(n)
    xr = res.xr.reshape(n)
    transform = _stat_transform(cfg)
    mfn = _measure_fn(cfg)
    y_orig = centered(aggregate_series(x, cfg.kappa))
    y_new = centered(aggregate_series(xr, cfg.kappa))
    s0 = transform(acf(y_orig, cfg.lags))
    s1 = transform(acf(y_new, cfg.lags))
    dev = mfn(s1, s0)
    return CompressResult(kept=kept, xr=xr, deviation=dev,
                          n_kept=jnp.sum(kept), iters=jnp.max(res.iters),
                          stat_orig=s0, stat_new=s1)
