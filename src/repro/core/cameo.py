"""CAMEO: autocorrelation-preserving lossy compression (paper §4).

Two execution modes share the same incremental-aggregate substrate:

* ``mode="sequential"`` — paper-faithful Algorithm 1: one point removed per
  iteration (heap replaced by a dense masked argmin), exact Eq. 9 windowed
  aggregate update + constraint check at pop time, and *blocking* — only the
  ``h`` alive neighbors on each side get their cached impact recomputed
  (ReHeap) after a removal.

* ``mode="rounds"`` — the TPU-native batched-greedy adaptation: every round
  computes the Algorithm-2 impact for *all* alive points as one dense O(nL)
  kernel (see ``kernels/acf_impact``), removes an independent set of the
  lowest-impact α-fraction, applies one exact dense aggregate update for the
  whole round, and accepts/rejects the round against the ε constraint
  (rejections halve α, so the mode converges to the same guarantee).

Both modes support the three problem variants of §3:
  Def. 1 (SIP)                — ``eps`` bound on D(S(X'), S(X));
  Def. 2 (SIP on aggregates)  — ``kappa > 1`` tumbling-window mean;
  Def. 3 (compression-centric)— ``target_cr`` (minimize D s.t. CR ≥ c).
and both statistics ``S ∈ {acf, pacf}``.

The guarantee discipline matches the paper: the *ranking* of candidates is a
heuristic (single-delta Eq. 8 approximation, possibly stale under blocking),
but every actual removal is validated with an exact incremental update, so
the returned deviation is exact w.r.t. the reconstruction's true ACF/PACF.

All ranking math is served by the impact-engine backend (``kernels/ops.py``,
selected via ``CameoConfig.backend``): the Pallas kernels on TPU, the
pure-jnp reference forms elsewhere.  This module holds only the greedy
control loops; ``compress_batch`` vmaps/shards the rounds mode over a fleet
of independent series.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import measures as _measures
from repro.core.acf import (
    acf_from_aggregates,
    aggregate_series,
    centered,
    extract_aggregates,
    extract_aggregates_masked,
)
from repro.core.aggregates import (
    alive_neighbors,
    apply_delta_dense,
    apply_delta_window,
    interpolate_at,
    neighbors_after_removal,
    segment_deltas,
)
from repro.kernels import fused_round as _fused
from repro.kernels import ops as _ops
from repro.kernels import ref as _ref
from repro.obs import OBS


@dataclasses.dataclass(frozen=True)
class CameoConfig:
    """Static configuration (hashable: safe to close over / pass as static)."""

    eps: float = 0.01
    lags: int = 24
    stat: str = "acf"              # "acf" | "pacf"
    measure: str = "mae"           # see core.measures
    kappa: int = 1                 # Def. 2 tumbling-window size (mean agg)
    mode: str = "rounds"           # "rounds" | "sequential"
    # -- rounds mode --
    alpha: float = 0.10            # per-round removal fraction cap
    max_rounds: int = 400
    impact_chunk: int = 4096
    rank: str = "window"           # "window" (exact Eq. 9) | "single" (Alg. 2)
    stop_policy: str = "exhaustive"  # "exhaustive" | "first_violation"
    # "backoff" (adaptive alpha, no per-round prefix search — fastest and
    # the default) | "scan" (fused prefix-deviation curve) | "bisect"
    # (dense prefix search)
    select: str = "backoff"
    bisect_probes: int = 6
    # -- sequential mode --
    hops: int = 16                 # blocking neighborhood h per side
    window: int = 64               # max re-interpolated span W (static)
    max_iters: Optional[int] = None
    # -- Def. 3 / halting --
    target_cr: Optional[float] = None   # minimize D s.t. CR >= target_cr
    max_cr: Optional[float] = None      # optional halt once CR reaches this
    dtype: str = "float64"
    # -- impact-engine backend (see kernels/ops.py for the dispatch rule):
    #    "pallas" (TPU kernels; interpret mode off-TPU; raises where a
    #    kernel cannot run, e.g. float64 operands on a TPU) | "reference"
    #    (pure-jnp) | "auto" (pallas on TPU where the kernel can run)
    backend: str = "auto"

    def jdtype(self):
        return jnp.dtype(self.dtype)


class CompressResult(NamedTuple):
    kept: jax.Array        # bool [n] — True where the original point is kept
    xr: jax.Array          # float [n] — reconstruction (kept pts bit-exact)
    deviation: jax.Array   # scalar — exact D(S(recon), S(orig))
    n_kept: jax.Array      # scalar int
    iters: jax.Array       # rounds (rounds mode) or removals (sequential)
    stat_orig: jax.Array   # [L] S of the original target series
    stat_new: jax.Array    # [L] S of the reconstruction's target series


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _stat_transform(cfg: CameoConfig):
    # single stat registry, shared with the impact-engine dispatch
    return _ops._transform_fn(cfg.stat)


def _measure_fn(cfg: CameoConfig):
    return _measures.get_measure(cfg.measure)


def _ranking_impact(cfg, agg, y, xr, alive, p0, n):
    """GetAllImpact via the impact-engine backend (see kernels/ops.py)."""
    return _ops.ranking_impact(cfg, agg, y, xr, alive, p0, n)


def _independent_set(sel: jax.Array, impact: jax.Array, alive: jax.Array,
                     prev=None, nxt=None):
    """Drop alive-adjacent picks: keep a pick iff it beats both its nearest
    *selected* alive neighbors (vectorized local-minima rule on the alive
    chain, so no two removed points ever share a segment endpoint).

    ``prev``/``nxt`` may be passed when the caller already has the alive
    neighbor maps (saves recomputing the two associative scans)."""
    n = sel.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if prev is None or nxt is None:
        prev, nxt = alive_neighbors(alive)
    inf = jnp.asarray(jnp.inf, impact.dtype)
    # impact of my adjacent alive neighbors IF they are also selected
    pc, qc = jnp.clip(prev, 0, n - 1), jnp.clip(nxt, 0, n - 1)
    left_imp = jnp.where(sel[pc] & (prev >= 0), impact[pc], inf)
    right_imp = jnp.where(sel[qc] & (nxt <= n - 1), impact[qc], inf)
    li = jnp.where(prev >= 0, prev, n)
    beats_left = (impact < left_imp) | ((impact == left_imp) & (idx < li))
    ri = jnp.where(nxt <= n - 1, nxt, -1)
    beats_right = (impact < right_imp) | ((impact == right_imp) & (idx < ri))
    return sel & beats_left & beats_right


def _reconstruct(x_kept_vals: jax.Array, alive: jax.Array) -> jax.Array:
    """Full-length reconstruction: alive points keep their value, dead points
    take the line between their alive neighbors."""
    n = alive.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    prev, nxt = alive_neighbors(alive)
    interp = interpolate_at(x_kept_vals, prev, nxt, idx)
    return jnp.where(alive, x_kept_vals, interp)


def _x_to_y_delta(delta_x: jax.Array, kappa: int, dt):
    if kappa == 1:
        return delta_x
    ny = delta_x.shape[0] // kappa
    return delta_x.reshape(ny, kappa).sum(axis=1) / jnp.asarray(kappa, dt)


# ---------------------------------------------------------------------------
# rounds mode (TPU-native batched greedy, padded-bucket fused rounds)
# ---------------------------------------------------------------------------

# Fixed-capacity eviction buffers for the tiered exact ranking: short
# segments (span <= _TIER_SMALL_W) are abundant and cheap, long ones rare
# and expensive.  Capacity overflow ranks +inf for *this* round only —
# accepted rounds or blocking free the slots, so every candidate is
# eventually ranked exactly.
_TIER_SMALL_W = 8


def _round_bucket(n: int, cfg: CameoConfig) -> int:
    """Padded length bucket for ``n`` (<= ~6% overhead, few distinct
    compiles across lengths, always a multiple of kappa)."""
    step = max(64, (1 << max(1, int(n - 1).bit_length())) // 16)
    nb = -(-n // step) * step
    if cfg.kappa > 1:
        nb = -(-nb // cfg.kappa) * cfg.kappa
    return nb


def _halting_params(n: int, cfg: CameoConfig):
    """(min_alive, eps) for the Def. 1/3 halting rules at true length n."""
    if cfg.target_cr is not None:
        min_alive = max(2, int(np.ceil(n / cfg.target_cr)))
        eps = np.inf
    else:
        min_alive = 2
        eps = float(cfg.eps)
    if cfg.max_cr is not None:
        min_alive = max(min_alive, int(np.ceil(n / cfg.max_cr)))
    return min_alive, eps


def _round_fns(cfg: CameoConfig, nb: int, n_valid: jax.Array,
               min_alive: jax.Array, eps: jax.Array, p0: jax.Array,
               tier_c: bool = True, tier_cond: bool = True,
               small_rounds="cond"):
    """``(cond, body)`` closures for the rounds loop at bucket size ``nb``.

    Shared by the run-to-completion program (:func:`_rounds_padded`) and the
    budgeted chunk program (:func:`_rounds_chunk`) that drives lane-compacted
    batching.  ``n_valid``/``min_alive``/``eps`` are (possibly per-lane
    traced) scalars and ``p0`` the [L] target stat; the aggregate rides the
    carry as the packed ``[5, L]`` moment table, so a round's accept gate and
    update are each one fused op instead of five.

    Each round runs as one fused pass: tiered exact Eq. 9 ranking into
    fixed-capacity buffers, top-k + independent-set selection, the
    prefix-deviation scan (kernels/fused_round) to pick the largest feasible
    prefix, and a dense exact Eq. 10/11 aggregate update as the
    authoritative accept check.

    ``tier_c=False`` compiles a variant with the wide-window (span > WB)
    ranking tier elided entirely.  Serial runs skip an empty tier through a
    ``lax.cond`` at run time, but under vmap a batched cond executes both
    branches every round — so the compacted batch driver starts on the
    elided program and watches the ``saw_c`` carry flag, which the body
    raises the moment any round's candidate set actually reaches the wide
    tier.  The driver then replays that chunk from its saved carry on the
    ``tier_c=True`` program, keeping results bit-identical to per-series
    runs (spans only grow, so the switch is one-way).

    ``small_rounds="cond"`` (default) adds a ``lax.cond`` fast path: when the
    candidate budget fits ``k_small``, the round runs a ``round_at``
    instantiation a third the size (shrunk ranking buffers too — tier
    overflow is correctness-neutral, unranked candidates retry next
    round).  The branch choice is trajectory-defining, so every program
    that can reach a small round must compile the same cond.  Late-game
    rounds dominate long compressions (hundreds of few-candidate rounds
    after the early mass removals), so the fast path is worth roughly a
    1.5x end-to-end speedup on real ingest traces.  Batched chunk
    programs pay both branches under vmap (cond lowers to a select), so
    the compacted driver watches for the moment *every* lane's candidate
    budget is provably pinned at or below ``k_small`` — ``n_alive`` only
    shrinks and ``alpha <= cfg.alpha`` always, making the small regime
    absorbing — and switches (one-way) to ``small_rounds="only"``: the
    small instantiation compiled unconditionally, bit-identical to the
    cond's taken branch from that point on.
    """
    dt = cfg.jdtype()
    L = cfg.lags
    kap = cfg.kappa
    W = cfg.window
    nyb = nb // kap
    idx = jnp.arange(nb, dtype=jnp.int32)
    inf = jnp.asarray(jnp.inf, dt)

    n_valid = n_valid.astype(jnp.int32)
    validm = idx < n_valid
    ny_valid = n_valid // kap

    transform = _stat_transform(cfg)
    mfn = _measure_fn(cfg)

    def rows_dev(rows):
        p0r = p0.astype(rows.dtype)
        if cfg.stat == "acf" and cfg.measure in _ref.KERNEL_MEASURES:
            return _ref.measure_rows(rows, p0r, cfg.measure)
        return jax.vmap(lambda r: mfn(transform(r), p0r))(rows)

    k_max = max(1, min(int(cfg.alpha * nb), nb - 2))
    WB = max(2, min(_TIER_SMALL_W, W))
    # Tiered eviction-buffer capacities (overflow is correctness-neutral:
    # unranked candidates retry next round).  Deliberately lean: early big
    # rounds are all span-1 candidates ranked by the shared Eq. 8 pass, and
    # by the time segments outgrow span 1 the removal fraction has usually
    # backed off — so one small-capacity program serves every round, instead
    # of the historical large-round/endgame-round branch pair that doubled
    # the lowered op count (and ran both sides under vmap).
    cap_b = min(nb, max(24, nb // 24))
    cap_c = min(nb, max(16, nb // 48))
    # Small-round fast path (serial programs only, see docstring): a
    # third-size instantiation for the late-game rounds, entered only when
    # provably equivalent to the full one.
    k_small = max(8, min(k_max, 32))
    cap_b_s = min(cap_b, max(16, nb // 32))
    cap_c_s = min(cap_c, max(8, nb // 64))

    # Ranking runs in float32: it only orders the heuristic candidate
    # selection (every accepted removal is re-validated by the exact dense
    # update in the configured dtype), and single-precision halves the
    # bandwidth of the per-round O(nL) ranking kernels.
    rdt = jnp.float32

    @jax.named_scope("rank")
    def tier_impacts(mask, xr, yr, tbl_r, prev, nxt, Wt, cap):
        """Eq. 9 ranking impacts for the first ``cap`` mask positions; +inf
        elsewhere.  Returns (impact [nb], ranked-mask [nb])."""
        taken = jnp.cumsum(mask.astype(jnp.int32))
        ranked = mask & (taken <= cap)

        def some(_):
            # first cap true indices, in index order, via rank scatter
            # (cheaper than a top_k over nb); unfilled slots read nb and
            # are dropped on the write-back below.
            slots = jnp.full((cap,), nb, jnp.int32).at[
                jnp.where(ranked, taken - 1, cap)].set(idx, mode="drop")
            cand = jnp.clip(slots, 0, nb - 1)
            dwin, start, _ = segment_deltas(xr, prev, nxt, cand, Wt)
            dyw, ystart = _ops.x_window_to_y(cfg, dwin, start)
            acf_rows = _fused.window_rows(
                cfg, yr, dyw.astype(rdt), ystart, tbl_r, ny_valid, L=L)
            imp = rows_dev(acf_rows).astype(dt)
            return jnp.full((nb,), jnp.inf, dt).at[slots].set(
                imp, mode="drop")

        if tier_cond:
            # Tier classes are often empty (all spans start at 1 and only
            # grow as removals accumulate) — skip the whole ranking pass
            # then.  Worth it only in the serial program: under vmap the
            # batched cond lowers to select-over-both-branches, and the
            # select machinery costs more than the ranking pass it guards.
            imp_full = jax.lax.cond(
                jnp.any(mask), some,
                lambda _: jnp.full((nb,), jnp.inf, dt), operand=None)
        else:
            # Unconditional variant is bit-identical: with an empty mask the
            # rank scatter writes nothing and `some` returns all-inf, same
            # as the cond's false branch.
            imp_full = some(None)
        return imp_full, ranked

    @jax.named_scope("rank")
    def single_impacts(xr, yr, tbl_r, prev, nxt):
        """Eq. 8 single-delta impacts for every point (exact at span 1)."""
        xhat = interpolate_at(xr, prev, nxt, idx)
        dx = xhat - xr
        dval = dx if kap == 1 else dx / jnp.asarray(kap, dt)
        y_idx = idx // kap
        rows = _ref.acf_after_single_delta(
            tbl_r, yr, y_idx, dval.astype(rdt), ny=ny_valid)
        return rows_dev(rows).astype(dt)

    def cond(c):
        (xr, alive, prev, nxt, y, tbl, alpha, dev, rounds, done, blocked,
         retried, saw_c) = c
        return (~done) & (rounds < cfg.max_rounds) & \
            (jnp.sum(alive) > min_alive)

    def body(c):
        (xr, alive, prev, nxt, y, tbl, alpha, dev, rounds, done, blocked,
         retried, saw_c) = c
        n_alive = jnp.sum(alive)
        # Per-lane re-check of `cond`: under vmap (compress_batch) the body
        # keeps executing for lanes whose own loop has finished as long as
        # any lane is live; gating acceptance on `live` makes those extra
        # executions exact no-ops, so batched results match per-series runs.
        live = (~done) & (rounds < cfg.max_rounds) & (n_alive > min_alive)

        removable = alive & (idx > 0) & (idx < n_valid - 1)
        cand = removable & (~blocked)
        span = nxt - prev - 1
        # Raised (one-way) as soon as a live round's candidate set reaches
        # the wide-window tier — the compacted batch driver's signal to
        # replay this chunk on the tier_c=True program (see docstring).
        if cfg.rank != "single" and WB < W:
            saw_c = saw_c | (live & jnp.any(
                cand & (span > WB) & (span <= W)))

        y_r = y.astype(rdt)
        tbl_r = tbl.astype(rdt)
        imp_sd = single_impacts(xr, y_r, tbl_r, prev, nxt)
        k_cap = jnp.maximum(
            1, jnp.minimum(
                (alpha * n_alive.astype(dt)).astype(jnp.int32),
                (n_alive - min_alive).astype(jnp.int32),
            ),
        )

        @jax.named_scope("update")
        def dense_apply(sel_idx_a, take):
            """Authoritative dense evaluation of removing the rank positions
            marked in ``take``."""
            sel = jnp.zeros((nb,), bool).at[sel_idx_a].set(take, mode="drop")
            alive_new = alive & (~sel)
            # The selection is an independent set, so the post-removal
            # neighbors come from a one-step pointer jump — no O(nb)
            # associative scans — and one vectorized interpolation pass
            # over the jumped pointers reproduces _reconstruct bit-for-bit
            # (unchanged dead points re-derive their stored value; moved
            # ones re-line against the inherited endpoints).
            prev_n, nxt_n = neighbors_after_removal(prev, nxt, sel)
            interp = interpolate_at(xr, prev_n, nxt_n, idx)
            xr_new = jnp.where(validm,
                               jnp.where(alive_new, xr, interp),
                               jnp.asarray(0.0, dt))
            dy = _x_to_y_delta(xr_new - xr, kap, dt)
            tbl_new = apply_delta_dense(tbl, y, dy, ny=ny_valid)
            dev_new = mfn(transform(acf_from_aggregates(tbl_new, ny_valid)),
                          p0)
            return dev_new, sel, alive_new, xr_new, dy, tbl_new, prev_n, nxt_n

        @jax.named_scope("rank")
        def rank_all(cb: int, cc: int):
            """Ranking impacts of every candidate (Eq. 8 at span 1, Eq. 9
            tiers above it, with tier capacities ``cb``/``cc``): returns
            (impact, exact-ranked mask, overflowed mask)."""
            if cfg.rank == "single":
                impact = jnp.where(cand, imp_sd, inf)
                exact_ranked = cand & (span == 1)
                overflowed = jnp.zeros((nb,), bool)
            else:
                a_mask = cand & (span == 1)
                b_mask = cand & (span >= 2) & (span <= WB)
                imp_b, ranked_b = tier_impacts(
                    b_mask, xr, y_r, tbl_r, prev, nxt, WB, cb)
                impact = jnp.where(a_mask, imp_sd, inf)
                impact = jnp.where(b_mask, imp_b, impact)
                exact_ranked = a_mask | (b_mask & ranked_b)
                overflowed = b_mask & (~ranked_b)
                if WB < W and tier_c:
                    c_mask = cand & (span > WB) & (span <= W)
                    imp_c, ranked_c = tier_impacts(
                        c_mask, xr, y_r, tbl_r, prev, nxt, W, cc)
                    impact = jnp.where(c_mask, imp_c, impact)
                    exact_ranked = exact_ranked | (c_mask & ranked_c)
                    overflowed = overflowed | (c_mask & (~ranked_c))
                # Overgrown segments (span > W): unrankable exactly.
                # Under a finite eps they stay unremovable; in the
                # Def. 3 regime (eps = inf) the deviation never gates
                # acceptance, so they are admitted with a large rank
                # penalty (ordered by the Eq. 8 estimate) and validated
                # by the dense authoritative update.
                over_mask = cand & (span > W)
                over_val = jnp.where(jnp.isfinite(eps), inf,
                                     jnp.asarray(1e30, dt) + imp_sd)
                impact = jnp.where(over_mask, over_val, impact)
            return impact, exact_ranked, overflowed

        def round_at(k_rows: int, cb: int, cc: int):
            """Ranking + selection at one static problem size.  Outputs are
            padded to ``k_max`` so both size branches unify shapes.  The
            named scopes ``rank``/``select``/``update`` mark the round's
            phases in the compiled program's ``op_name`` metadata (an
            operation belongs to the innermost of them)."""
            @jax.named_scope("select")
            def go(_):
                impact, exact_ranked, overflowed = rank_all(cb, cc)
                # Rank keys in float32: CPU/TPU top_k has a fast path there,
                # and ranking order only steers the heuristic selection —
                # every removal is still validated by the exact dense update
                # in the configured dtype.
                neg_vals, sel_idx = jax.lax.top_k(
                    -impact.astype(jnp.float32), k_rows)
                finite = jnp.isfinite(-neg_vals)
                rank_ok = finite & (jnp.arange(k_rows) < k_cap)
                sel_all = jnp.zeros((nb,), bool).at[sel_idx].set(
                    rank_ok, mode="drop")
                sel_surv = _independent_set(sel_all, impact, alive, prev, nxt)
                # Independent-set survival is prefix-independent under the
                # (impact, idx) total order, so one survival pass serves
                # every prefix the selection below may choose.
                ok = sel_surv[sel_idx] & rank_ok

                ar0 = jnp.arange(k_rows)
                if cfg.select == "scan":
                    dwin_k, start_k, _ = segment_deltas(
                        xr, prev, nxt, sel_idx, W)
                    dyw_k, ystart_k = _ops.x_window_to_y(cfg, dwin_k, start_k)
                    if _ops.use_fused_kernel(
                            cfg.backend, dt, cfg.stat, cfg.measure):
                        # Fused greedy kernel (real TPU): one VMEM pass walks
                        # the rank order, committing every candidate whose
                        # trial deviation on the exact running reconstruction
                        # fits and *skipping* violators.  The dense check
                        # below still gates the round, with the feasible
                        # prefix (greedy decisions up to the first skip) as
                        # the fallback proposal.
                        take_g, _ = _fused.greedy_feasible(
                            cfg, y, dyw_k, ystart_k, ok, tbl, p0,
                            ny_valid, eps)
                        out_a = dense_apply(sel_idx, take_g)
                        first_skip = jnp.min(jnp.where(
                            ok & (~take_g), ar0, jnp.int32(k_rows)))
                        take_pre = take_g & (ar0 < first_skip)
                        more = jnp.sum(take_g) > jnp.sum(take_pre)
                        out = jax.lax.cond(
                            (out_a[0] <= eps) | (~more),
                            lambda _: out_a,
                            lambda _: dense_apply(sel_idx, take_pre),
                            operand=None)
                        no_fit = ~jnp.any(take_g)
                    else:
                        # Linearized slack packing (reference path): score
                        # each survivor by the directional derivative of the
                        # deviation along its solo aggregate delta, sort by
                        # marginal ascending, and take the largest prefix
                        # whose projected deviation fits.  This packs the
                        # eps budget near-optimally — in particular it
                        # harvests the deviation-*reducing* candidates the
                        # rank-order grind would defer across many rounds —
                        # at the cost of one gradient plus one einsum.  The
                        # dense authoritative check gates the round; on a
                        # miss (linearization error) the proposal halves up
                        # to three times.
                        def dev_of_table(t5):
                            return mfn(transform(
                                acf_from_aggregates(t5, ny_valid)), p0)
                        gtbl = jax.grad(dev_of_table)(tbl)
                        dagg = _fused.solo_moment_rows(
                            y, dyw_k, ystart_k, ny_valid, L=L)
                        g = jnp.einsum("al,kal->k", gtbl, dagg)
                        gi = jnp.where(ok, g, inf)
                        order = jnp.argsort(gi)
                        gs = gi[order]
                        csum = _ref.cumsum(
                            jnp.where(jnp.isfinite(gs), gs,
                                      jnp.asarray(0.0, dt)))
                        pred = dev + csum
                        kidx = jnp.arange(1, k_rows + 1, dtype=jnp.int32)
                        finite_g = jnp.isfinite(gs)
                        rank_pos = jnp.zeros((k_rows,), jnp.int32).at[
                            order].set(ar0.astype(jnp.int32))

                        def at_k(k):
                            return dense_apply(sel_idx, ok & (rank_pos < k))

                        # Bracketed Newton search for the max dense-feasible
                        # prefix of the g-order: each dense probe calibrates
                        # the linearization bias `err`, the re-pack proposes
                        # the largest prefix fitting the corrected budget,
                        # clipped into the open feasible/infeasible bracket
                        # (degenerating to bisection when the model stalls).
                        n_ok = jnp.sum(finite_g).astype(jnp.int32)
                        out_empty = (dev, jnp.zeros((nb,), bool), alive,
                                     xr, jnp.zeros((nyb,), dt), tbl,
                                     prev, nxt)

                        # A while_loop (not a fixed fori_loop): the bracket
                        # usually closes after one or two dense probes, and a
                        # while stops there — crucially also under vmap,
                        # where a fori would charge every lane the full probe
                        # budget every round (a cond inside a batched loop
                        # runs both branches).
                        def probe_cond(carry):
                            it, k_lo, out_lo, k_hi, err = carry
                            return (it < 4) & ((k_hi - k_lo) > 1)

                        def probe(carry):
                            it, k_lo, out_lo, k_hi, err = carry
                            k_p = jnp.max(jnp.where(
                                finite_g & (pred + err <= eps), kidx,
                                jnp.int32(0)))
                            k_p = jnp.clip(k_p, k_lo + 1, k_hi - 1)
                            out_p = at_k(k_p)
                            fits = out_p[0] <= eps
                            err = out_p[0] - pred[jnp.maximum(k_p - 1, 0)]
                            out_lo = jax.tree.map(
                                lambda a, b: jnp.where(fits, a, b),
                                out_p, out_lo)
                            return (it + 1, jnp.where(fits, k_p, k_lo),
                                    out_lo, jnp.where(fits, k_hi, k_p), err)

                        _, k_lo, out, _, _ = jax.lax.while_loop(
                            probe_cond, probe,
                            (jnp.int32(0), jnp.int32(0), out_empty,
                             n_ok + 1, jnp.asarray(0.0, dt)))
                        no_fit = k_lo == 0
                elif cfg.select == "bisect":
                    def probe(_, lohi):
                        lo, hi = lohi
                        mid = (lo + hi + 1) // 2
                        dev_mid = dense_apply(sel_idx, ok & (ar0 < mid))[0]
                        fits = dev_mid <= eps
                        return (jnp.where(fits, mid, lo),
                                jnp.where(fits, hi, mid - 1))
                    lo, _ = jax.lax.fori_loop(
                        0, cfg.bisect_probes, probe,
                        (jnp.asarray(0, jnp.int32),
                         jnp.minimum(k_cap, k_rows).astype(jnp.int32)))
                    out = dense_apply(sel_idx, ok & (ar0 < lo))
                    no_fit = lo == 0
                else:                           # "backoff"
                    kf = jnp.minimum(k_cap, k_rows).astype(jnp.int32)
                    out = dense_apply(sel_idx, ok & (ar0 < kf))
                    no_fit = ~jnp.any(ok)
                return out + (impact, exact_ranked, overflowed,
                              sel_idx[0], finite[0], no_fit)
            return go

        if small_rounds == "only" and k_small < k_max:
            # Compiled only by the compacted batch driver once every lane
            # is provably inside the small regime (see docstring).
            (dev_new, sel, alive_new, xr_new, dy, agg_new, prev_new,
             nxt_new, impact, exact_ranked, overflowed, best_idx, finite0,
             no_fit) = round_at(k_small, cap_b_s, cap_c_s)(None)
        elif small_rounds and k_small < k_max:
            (dev_new, sel, alive_new, xr_new, dy, agg_new, prev_new,
             nxt_new, impact, exact_ranked, overflowed, best_idx, finite0,
             no_fit) = jax.lax.cond(
                k_cap <= k_small,
                round_at(k_small, cap_b_s, cap_c_s),
                round_at(k_max, cap_b, cap_c),
                operand=None)
        else:
            (dev_new, sel, alive_new, xr_new, dy, agg_new, prev_new,
             nxt_new, impact, exact_ranked, overflowed, best_idx, finite0,
             no_fit) = round_at(k_max, cap_b, cap_c)(None)
        n_sel = jnp.sum(sel)
        any_sel = n_sel > 0
        accept = (dev_new <= eps) & any_sel & live
        reject = (~accept) & live

        was_single = n_sel <= 1
        if cfg.stop_policy == "first_violation":
            done_new = done | (live & (((~accept) & was_single) | no_fit))
            blocked_new = blocked
            retried_new = retried
        else:
            # exhaustive: a rejected round proves every exactly-ranked
            # candidate with impact > eps cannot fit alone at the current
            # state — block them all at once, with the best candidate as a
            # backstop so no-progress rounds cannot repeat.  Blocks persist
            # across accepts (the deviation headroom only shrinks as
            # removals accumulate, so a once-unfit candidate rarely becomes
            # fit); when the candidate pool is exhausted, all blocks are
            # dropped once and the search retried from scratch — only a
            # second back-to-back exhaustion terminates.
            mass = exact_ranked & (impact > eps)
            bump = (blocked | mass).at[best_idx].set(True)
            blocked_new = jnp.where(reject & finite0, bump, blocked)
            avail = removable & (~blocked_new) & \
                (jnp.isfinite(impact) | overflowed)
            exhausted = reject & (~jnp.any(avail))
            clear_now = exhausted & (~retried)
            blocked_new = jnp.where(clear_now, jnp.zeros_like(blocked),
                                    blocked_new)
            retried_new = jnp.where(accept, jnp.asarray(False),
                                    retried | clear_now)
            done_new = done | (exhausted & retried)
        if cfg.select == "backoff":
            alpha_new = jnp.where(accept, jnp.minimum(alpha * 1.1, cfg.alpha),
                                  jnp.maximum(alpha * 0.5,
                                              jnp.asarray(1.5 / nb, dt)))
        else:
            alpha_new = alpha

        xr_out = jnp.where(accept, xr_new, xr)
        alive_out = jnp.where(accept, alive_new, alive)
        prev_out = jnp.where(accept, prev_new, prev)
        nxt_out = jnp.where(accept, nxt_new, nxt)
        y_out = jnp.where(accept, y + dy, y)
        tbl_out = jnp.where(accept, agg_new, tbl)
        dev_out = jnp.where(accept, dev_new, dev)
        return (xr_out, alive_out, prev_out, nxt_out, y_out, tbl_out,
                alpha_new, dev_out, rounds + live.astype(jnp.int32),
                done_new, blocked_new, retried_new, saw_c)

    return cond, body


def _rounds_init(xp: jax.Array, n_valid: jax.Array, cfg: CameoConfig):
    """Initial rounds carry + target stat ``p0`` for one padded series
    (plain traced function — callers jit)."""
    dt = cfg.jdtype()
    nb = xp.shape[0]
    idx = jnp.arange(nb, dtype=jnp.int32)
    n_valid = n_valid.astype(jnp.int32)
    validm = idx < n_valid
    xp = jnp.where(validm, xp.astype(dt), jnp.asarray(0.0, dt))
    ny_valid = n_valid // cfg.kappa
    y0 = centered(aggregate_series(xp, cfg.kappa), ny_valid)
    agg0 = extract_aggregates_masked(y0, cfg.lags, ny_valid,
                                     backend=cfg.backend)
    tbl0 = _ops.agg_to_table(agg0)
    p0 = _stat_transform(cfg)(acf_from_aggregates(agg0, ny_valid))
    alive0 = validm
    prev0, nxt0 = alive_neighbors(alive0)
    carry = (xp, alive0, prev0, nxt0, y0, tbl0, jnp.asarray(cfg.alpha, dt),
             jnp.asarray(0.0, dt), jnp.asarray(0, jnp.int32),
             jnp.asarray(False), jnp.zeros((nb,), bool), jnp.asarray(False),
             jnp.asarray(False))
    return carry, p0


def _rounds_result(carry, n_valid: jax.Array, p0: jax.Array,
                   cfg: CameoConfig) -> CompressResult:
    """Final carry → ``CompressResult`` (plain traced function)."""
    (xr, alive, _, _, _, tbl, _, dev, rounds, _, _, _, _) = carry
    ny_valid = n_valid.astype(jnp.int32) // cfg.kappa
    stat_new = _stat_transform(cfg)(acf_from_aggregates(tbl, ny_valid))
    return CompressResult(
        kept=alive, xr=xr, deviation=dev, n_kept=jnp.sum(alive),
        iters=rounds, stat_orig=p0, stat_new=stat_new)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _rounds_padded(xp: jax.Array, n_valid: jax.Array, min_alive: jax.Array,
                   eps: jax.Array, cfg: CameoConfig) -> CompressResult:
    """Rounds mode over a zero-padded bucket ``xp [nb]`` with runtime valid
    length ``n_valid`` — one compiled program per (bucket, cfg), running the
    whole elimination to completion in a single ``lax.while_loop``."""
    carry, p0 = _rounds_init(xp, n_valid, cfg)
    cond, body = _round_fns(cfg, xp.shape[0], n_valid, min_alive, eps, p0)
    final = jax.lax.while_loop(cond, body, carry)
    return _rounds_result(final, n_valid, p0, cfg)


def _rounds_chunk(carry, n_valid, min_alive, eps, p0, cfg: CameoConfig,
                  budget: int, tier_c: bool = True, tier_cond: bool = True,
                  small_rounds="cond"):
    """Advance the rounds loop by at most ``budget`` rounds.

    Returns ``(carry', live)`` where ``live`` is the per-lane continuation
    flag (True while the loop would keep going).  The chunk-step counter is
    a scalar shared across vmapped lanes, so a batched chunk stops early
    the moment every lane is done — finished lanes inside a chunk execute
    the body as exact no-ops (the same ``live`` gating that makes vmapped
    results bit-identical to per-series runs).
    """
    nb = carry[0].shape[0]
    cond, body = _round_fns(cfg, nb, n_valid, min_alive, eps, p0,
                            tier_c=tier_c, tier_cond=tier_cond,
                            small_rounds=small_rounds)

    def ccond(tc):
        t, c = tc
        return (t < budget) & cond(c)

    def cbody(tc):
        t, c = tc
        return t + 1, body(c)

    _, out = jax.lax.while_loop(
        ccond, cbody, (jnp.asarray(0, jnp.int32), carry))
    return out, cond(out)


def compress_rounds(x: jax.Array, cfg: CameoConfig, *,
                    pad_to: Optional[int] = None) -> CompressResult:
    """Rounds-mode compression of one series.

    The series is zero-padded to a shape bucket (see ``_round_bucket``) and
    compressed with its true length as a runtime scalar, so nearby lengths
    share one compiled program.  ``pad_to`` forces at least that bucket —
    streaming callers pass their full window length so a partial tail
    window reuses the full-window program (no per-length recompiles).
    """
    x = jnp.asarray(x, cfg.jdtype())
    n = x.shape[0]
    if cfg.kappa > 1 and n % cfg.kappa:
        raise ValueError(f"length {n} not divisible by kappa={cfg.kappa}")
    nb, avals = _rounds_signature(n, cfg, pad_to)
    xp = jnp.pad(x, (0, nb - n)) if nb > n else x
    min_alive, eps = _halting_params(n, cfg)
    res = _rounds_padded(
        *(jnp.asarray(a, s.dtype)
          for a, s in zip((xp, n, min_alive, eps), avals)), cfg)
    if nb == n:
        return res
    return res._replace(kept=res.kept[:n], xr=res.xr[:n])


def _rounds_signature(n: int, cfg: CameoConfig, pad_to: Optional[int]):
    """``(bucket, operand shapes)`` of the ``_rounds_padded`` call that
    ``compress_rounds`` makes for a length-``n`` series."""
    nb = _round_bucket(max(n, int(pad_to or 0)), cfg)
    dt = cfg.jdtype()
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    return nb, (jax.ShapeDtypeStruct((nb,), dt), i32, i32,
                jax.ShapeDtypeStruct((), dt))


def lower_rounds(n: int, cfg: CameoConfig, *, pad_to: Optional[int] = None):
    """``(bucket, lowered program)``: the rounds program ``compress_rounds``
    runs for a length-``n`` series, lowered without running it.  Compiling
    it ahead (``.compile()``) fills the persistent compile cache that the
    later call then reads, and its text shows which kernels it holds."""
    nb, avals = _rounds_signature(n, cfg, pad_to)
    return nb, _rounds_padded.lower(*avals, cfg=cfg)


# the rounds program is the streaming hot path: its compiled-variant count
# is the original no-recompile watermark (see repro.obs.recompile_watermark)
OBS.register_jit("cameo.rounds", _rounds_padded)


# ---------------------------------------------------------------------------
# sequential mode (paper-faithful Algorithm 1)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def compress_sequential(x: jax.Array, cfg: CameoConfig) -> CompressResult:
    dt = cfg.jdtype()
    x = x.astype(dt)
    n = x.shape[0]
    L = cfg.lags
    W = cfg.window
    h = cfg.hops
    kap = cfg.kappa
    y0 = centered(aggregate_series(x, kap))
    ny = y0.shape[0]
    agg0 = extract_aggregates(y0, L, backend=cfg.backend)
    transform = _stat_transform(cfg)
    mfn = _measure_fn(cfg)
    p0 = transform(acf_from_aggregates(agg0, ny))
    inf = jnp.asarray(jnp.inf, dt)

    if cfg.target_cr is not None:
        min_alive = max(2, int(np.ceil(n / cfg.target_cr)))
        eps = inf
    else:
        min_alive = 2
        eps = jnp.asarray(cfg.eps, dt)
    if cfg.max_cr is not None:
        min_alive = max(min_alive, int(np.ceil(n / cfg.max_cr)))
    max_iters = cfg.max_iters if cfg.max_iters is not None else (n - min_alive)

    # y-window size for kappa>1 windowed updates.
    Wy = W if kap == 1 else (W // kap + 2)

    def trial(agg, y, xr, prev, nxt, i):
        """Exact Eq. 9 trial removal of point i (segment (prev[i], nxt[i]));
        the impact-engine provides the delta geometry, the incremental
        aggregate update validates the removal exactly."""
        dwin, start, span = segment_deltas(xr, prev, nxt, i, W)
        dyw, ystart = _ops.x_window_to_y(cfg, dwin, start)
        agg_t = apply_delta_window(agg, y, dyw, ystart, W=Wy, L=L)
        dev_t = mfn(transform(acf_from_aggregates(agg_t, ny)), p0)
        return agg_t, dev_t, dwin, dyw, start, ystart, span <= W

    def collect_neighbors(prev, nxt, p, q):
        """h alive indices walking left from p and right from q (incl. p, q)."""
        # left walk
        def left_body(i, acc):
            ids, ptr = acc
            ids = ids.at[i].set(ptr)
            ptr = jnp.clip(prev[jnp.clip(ptr, 0, n - 1)], -1, n - 1)
            ptr = jnp.where(ptr < 0, jnp.int32(0), ptr)
            return ids, ptr
        ids_l, _ = jax.lax.fori_loop(
            0, h + 1, left_body,
            (jnp.zeros((h + 1,), jnp.int32), jnp.clip(p, 0, n - 1)))
        def right_body(i, acc):
            ids, ptr = acc
            ids = ids.at[i].set(ptr)
            ptr = jnp.clip(nxt[jnp.clip(ptr, 0, n - 1)], 0, n)
            ptr = jnp.where(ptr >= n, jnp.int32(n - 1), ptr)
            return ids, ptr
        ids_r, _ = jax.lax.fori_loop(
            0, h + 1, right_body,
            (jnp.zeros((h + 1,), jnp.int32), jnp.clip(q, 0, n - 1)))
        return jnp.concatenate([ids_l, ids_r])

    def init_impacts(agg, y, xr, prev, nxt):
        # Exact impacts are O(nWL) to initialize; Algorithm 2 initializes with
        # the O(nL) single-delta form, which is exact while all points are
        # alive (every segment has span 1).  We do the same.
        alive = jnp.ones((n,), bool)
        return _ops.ranking_impact(cfg, agg, y, xr, alive, p0, n,
                                   rank="single")

    def cond(c):
        (xr, alive, prev, nxt, imp, agg, y, dev, it, done) = c
        return (~done) & (it < max_iters) & (jnp.sum(alive) > min_alive)

    def body(c):
        (xr, alive, prev, nxt, imp, agg, y, dev, it, done) = c
        i = jnp.argmin(imp)
        best = imp[i]
        p, q = prev[i], nxt[i]
        agg_t, dev_t, dwin, dyw, start, ystart, valid = trial(
            agg, y, xr, prev, nxt, i)

        can_remove = jnp.isfinite(best) & valid & (dev_t <= eps)
        # Algorithm 1 stops at the first violation, which is sound when the
        # heap is fresh; under blocking the popped impact can be stale (the
        # paper's ReHeap keeps neighborhoods fresh, but distant entries age),
        # so a stale pop would end the run prematurely.  We block the
        # offending candidate (impact=inf; ReHeap revives neighbors later)
        # and stop only when no finite candidate remains.  With
        # stop_policy="first_violation" the paper's literal semantics apply.
        if cfg.stop_policy == "first_violation":
            violation = jnp.isfinite(best) & valid & (dev_t > eps)
            done_new = done | violation | (~jnp.isfinite(best))
        else:
            done_new = done | (~jnp.isfinite(best))

        # apply removal (no-ops when rejected)
        def windowed_add(arr, win, st, Wn):
            """arr[st + j] += win[j] with clamp-safe shifting near the end."""
            size = arr.shape[0]
            offset = jnp.clip(st, 0, size - Wn)
            shift = st - offset
            k = jnp.arange(Wn)
            buf = jnp.where(k >= shift, win[jnp.clip(k - shift, 0, Wn - 1)], 0.0)
            return jax.lax.dynamic_update_slice(
                arr, jax.lax.dynamic_slice(arr, (offset,), (Wn,)) + buf, (offset,))

        def apply(_):
            xr2 = windowed_add(xr, dwin, start, W)
            alive2 = alive.at[i].set(False)
            prev2 = prev.at[q].set(p, mode="drop")
            nxt2 = nxt.at[p].set(q, mode="drop")
            y2 = windowed_add(y, dyw, ystart, Wy)
            imp2 = imp.at[i].set(inf)
            # ReHeap: exact impact recompute for h alive neighbors per side,
            # through the impact-engine backend (exact Eq. 9 ranking).
            nbrs = collect_neighbors(prev2, nxt2, p, q)
            new_imps = _ops.window_impact_at(
                cfg, agg_t, y2, xr2, prev2, nxt2, nbrs, p0)
            # only alive points get updates (dedup: later writes win, values
            # identical for duplicated indices so order is irrelevant)
            alive_n = alive2[nbrs]
            imp2 = imp2.at[nbrs].set(
                jnp.where(alive_n, new_imps, imp2[nbrs]), mode="drop")
            return xr2, alive2, prev2, nxt2, imp2, agg_t, y2, dev_t

        def reject(_):
            # rejected candidates (span overflow or eps violation under the
            # skip policy) become unremovable until a ReHeap revives them
            imp2 = imp.at[i].set(inf)
            return xr, alive, prev, nxt, imp2, agg, y, dev

        xr2, alive2, prev2, nxt2, imp2, agg2, y2, dev2 = jax.lax.cond(
            can_remove, apply, reject, operand=None)
        return (xr2, alive2, prev2, nxt2, imp2, agg2, y2, dev2,
                it + 1, done_new)

    idx = jnp.arange(n, dtype=jnp.int32)
    prev0 = idx - 1
    nxt0 = idx + 1
    imp0 = init_impacts(agg0, y0, x, prev0, nxt0)
    init = (x, jnp.ones((n,), bool), prev0, nxt0, imp0, agg0, y0,
            jnp.asarray(0.0, dt), jnp.asarray(0, jnp.int32),
            jnp.asarray(False))
    xr, alive, prev, nxt, imp, agg, y, dev, it, _ = jax.lax.while_loop(
        cond, body, init)
    stat_new = transform(acf_from_aggregates(agg, ny))
    return CompressResult(
        kept=alive, xr=xr, deviation=dev, n_kept=jnp.sum(alive),
        iters=it, stat_orig=p0, stat_new=stat_new)


OBS.register_jit("cameo.sequential", compress_sequential)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def compress(x, cfg: CameoConfig) -> CompressResult:
    """Compress ``x`` under ``cfg``.  Trims a tail remainder so the length is
    divisible by ``kappa`` (the trimmed points are kept verbatim by callers
    that need exact framing; the registry uses divisible lengths)."""
    x = jnp.asarray(x)
    if cfg.kappa > 1:
        n = (x.shape[0] // cfg.kappa) * cfg.kappa
        x = x[:n]
    if cfg.mode == "rounds":
        return compress_rounds(x, cfg)
    if cfg.mode == "sequential":
        return compress_sequential(x, cfg)
    raise ValueError(f"unknown mode {cfg.mode!r}")


@functools.partial(jax.jit, static_argnames=("cfg",))
def _batch_init(xps, n_valid, cfg: CameoConfig):
    return jax.vmap(lambda x, nv: _rounds_init(x, nv, cfg))(xps, n_valid)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "budget", "tier_c", "small"))
def _batch_chunk(carry, n_valid, min_alive, eps, p0, cfg: CameoConfig,
                 budget: int, tier_c: bool = True, small="cond"):
    # Batched chunks always compile with tier_cond=False: under vmap the
    # empty-tier `lax.cond` lowers to a select over both branches and costs
    # more than running the ranking pass unconditionally.
    return jax.vmap(
        lambda c, nv, ma, ep, p: _rounds_chunk(c, nv, ma, ep, p, cfg, budget,
                                               tier_c=tier_c, tier_cond=False,
                                               small_rounds=small)
    )(carry, n_valid, min_alive, eps, p0)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "budget", "tier_c", "small"))
def _batch_chunk_gathered(carry, consts, sel, cfg: CameoConfig,
                          budget: int, tier_c: bool = True, small="cond"):
    """One fused super-round for a compacted lane subset: gather the lanes
    named by ``sel`` out of the full carry, advance them ``budget`` rounds,
    and scatter the results back — all in one compiled program, so the host
    driver pays a single dispatch per super-round instead of two eager
    tree-sized gather/scatter passes.  Padding duplicates in ``sel`` (the
    pow-2 bucket fill) recompute the same lane deterministically, so the
    duplicate scatter writes are value-identical and order-independent."""
    sub = jax.tree.map(lambda a: a[sel], carry)
    subc = jax.tree.map(lambda a: a[sel], consts)
    sub, live = jax.vmap(
        lambda c, nv, ma, ep, p: _rounds_chunk(c, nv, ma, ep, p, cfg, budget,
                                               tier_c=tier_c, tier_cond=False,
                                               small_rounds=small)
    )(sub, *subc)
    carry = jax.tree.map(lambda full, s: full.at[sel].set(s), carry, sub)
    return carry, live


@functools.partial(jax.jit, static_argnames=("cfg",))
def _batch_result(carry, n_valid, p0, cfg: CameoConfig):
    return jax.vmap(lambda c, nv, p: _rounds_result(c, nv, p, cfg))(
        carry, n_valid, p0)


OBS.register_jit("cameo.batch_init", _batch_init)
OBS.register_jit("cameo.batch_chunk", _batch_chunk)
OBS.register_jit("cameo.batch_result", _batch_result)

# Rounds advanced per compacted super-round: small enough that finished
# lanes drop out of the working set quickly, large enough that the host
# sync + gather/scatter per super-round stays amortized.
_BATCH_CHUNK_ROUNDS = 8


def _next_pow2(k: int) -> int:
    return 1 << max(0, (int(k) - 1)).bit_length()


def _compress_batch_compacted(xs: jax.Array, cfg: CameoConfig,
                              pad_to: Optional[int]) -> CompressResult:
    """Host-driven lane-compacted batch: the jitted chunk program advances
    every lane up to ``_BATCH_CHUNK_ROUNDS`` rounds, the host reads the
    per-lane live flags, and the next super-round gathers only the still-
    live lanes into the smallest power-of-two bucket (padded by duplicating
    a live lane, whose copy is discarded on scatter-back).  Finished lanes
    stop paying for the round body entirely — under plain vmap they execute
    both branches of every round conditional until the slowest lane drains.

    Per-lane math is untouched (same ``_round_fns`` body), so results stay
    bit-identical to per-series ``compress_rounds`` runs; the differential
    harness in ``tests/test_backend.py`` pins that.
    """
    dt = cfg.jdtype()
    B, n = xs.shape
    nb = _round_bucket(max(n, int(pad_to or 0)), cfg)
    xp = jnp.asarray(xs, dt)
    if nb > n:
        xp = jnp.pad(xp, ((0, 0), (0, nb - n)))
    min_alive, eps = _halting_params(n, cfg)
    nv = jnp.full((B,), n, jnp.int32)
    ma = jnp.full((B,), min_alive, jnp.int32)
    ep = jnp.full((B,), eps, dt)
    carry, p0 = _batch_init(xp, nv, cfg)
    consts = (nv, ma, ep, p0)

    live = np.ones(B, bool)
    occ_active = occ_slots = 0
    # Start on the program with the wide-window ranking tier compiled out —
    # under vmap the elided tier would otherwise run every round for every
    # lane, empty or not.  The first chunk whose body actually reaches the
    # tier (saw_c carry flag) is replayed from its saved carry on the full
    # program; spans only grow, so the switch is one-way and the replayed
    # trajectory is bit-identical to a per-series run.
    need_c = cfg.rank == "single"
    # The small-rounds cond (see _round_fns) runs both branches under vmap,
    # so chunks start on the dual-branch program and switch — one-way — to
    # the small-instantiation-only program once every live lane's candidate
    # budget is provably pinned at or below k_small: k_cap is bounded by
    # min(int(cfg.alpha * n_alive), n_alive - min_alive), n_alive only
    # shrinks, and alpha never exceeds cfg.alpha, so the regime is
    # absorbing and the switched trajectory stays bit-identical to the
    # serial cond's taken branch.
    k_max = max(1, min(int(cfg.alpha * nb), nb - 2))
    k_small = max(8, min(k_max, 32))
    small = "cond"

    def all_small(lanes):
        if small == "only" or k_small >= k_max:
            return small
        n_alive = np.asarray(jnp.sum(carry[1][lanes], axis=-1))
        ma_l = np.asarray(ma)[lanes]
        bound = np.minimum(
            (np.asarray(cfg.alpha, dt) *
             n_alive.astype(dt)).astype(np.int32),
            (n_alive - ma_l).astype(np.int32))
        return "only" if bool(np.all(bound <= k_small)) else "cond"

    while live.any():
        active = np.nonzero(live)[0]
        na = len(active)
        bucket = min(B, _next_pow2(na))
        saved = carry
        if bucket == B:
            # every lane live: no gather/scatter, run the chunk in place
            small = all_small(active)
            carry, sub_live = _batch_chunk(carry, *consts, cfg=cfg,
                                           budget=_BATCH_CHUNK_ROUNDS,
                                           tier_c=need_c, small=small)
            if not need_c and bool(np.asarray(carry[12]).any()):
                need_c = True
                carry, sub_live = _batch_chunk(saved, *consts, cfg=cfg,
                                               budget=_BATCH_CHUNK_ROUNDS,
                                               tier_c=True, small=small)
            live[:] = np.asarray(sub_live)
        else:
            sel = np.concatenate(
                [active, np.full(bucket - na, active[0])])
            sel_j = jnp.asarray(sel, jnp.int32)
            small = all_small(active)
            carry, sub_live = _batch_chunk_gathered(
                carry, consts, sel_j, cfg=cfg,
                budget=_BATCH_CHUNK_ROUNDS, tier_c=need_c, small=small)
            if not need_c and bool(np.asarray(carry[12][sel_j]).any()):
                need_c = True
                carry, sub_live = _batch_chunk_gathered(
                    saved, consts, sel_j, cfg=cfg,
                    budget=_BATCH_CHUNK_ROUNDS, tier_c=True, small=small)
            live[active] = np.asarray(sub_live)[:na]
        occ_active += na
        occ_slots += bucket

    res = _batch_result(carry, nv, p0, cfg)
    if OBS.enabled:
        OBS.inc("cameo.batch_rounds_total",
                int(np.asarray(jnp.sum(res.iters))))
        OBS.gauge("cameo.batch_lane_occupancy",
                  occ_active / occ_slots if occ_slots else 1.0)
    if nb > n:
        res = res._replace(kept=res.kept[:, :n], xr=res.xr[:, :n])
    return res


def compress_batch(xs, cfg: CameoConfig, mesh=None,
                   axis: str = "data", *,
                   pad_to: Optional[int] = None) -> CompressResult:
    """Batched multi-series compression — the fleet-of-sensors workload.

    ``xs`` is ``[B, n]`` (B independent series of equal length); returns a
    ``CompressResult`` whose leaves carry a leading batch axis.  Built on the
    ``rounds`` mode: per-series results are bit-identical to
    ``compress_rounds(xs[b], cfg)``.  Off-TPU the batch runs lane-compacted
    (see :func:`_compress_batch_compacted`): finished lanes are dropped from
    the working set between jitted chunks, so a mixed-convergence batch pays
    for the slowest lane only at its own width.  On TPU (or with ``mesh``)
    the whole loop stays device-resident under vmap/``shard_map`` — with
    ``mesh`` given, the batch is sharded over ``mesh.shape[axis]`` devices
    (B must divide evenly); each device vmaps its local shard.
    """
    xs = jnp.asarray(xs)
    if xs.ndim != 2:
        raise ValueError(f"compress_batch wants [B, n], got {xs.shape}")
    if cfg.mode != "rounds":
        raise ValueError("compress_batch batches the rounds mode; got "
                         f"mode={cfg.mode!r}")
    if cfg.kappa > 1:
        n = (xs.shape[1] // cfg.kappa) * cfg.kappa
        xs = xs[:, :n]
    if mesh is None:
        if xs.shape[0] > 1 and jax.default_backend() != "tpu":
            return _compress_batch_compacted(xs, cfg, pad_to)
        return jax.vmap(lambda x: compress_rounds(x, cfg, pad_to=pad_to))(xs)
    batched = jax.vmap(lambda x: compress_rounds(x, cfg, pad_to=pad_to))
    from jax.sharding import PartitionSpec as P
    from repro import sharding as shd
    T = mesh.shape[axis]
    if xs.shape[0] % T:
        raise ValueError(f"batch {xs.shape[0]} not divisible over "
                         f"{T} devices on axis {axis!r}")
    sharded = shd.shard_map(batched, mesh=mesh, in_specs=P(axis),
                            out_specs=P(axis))
    return jax.jit(sharded)(xs)


class MVCompressResult(NamedTuple):
    """Multivariate compression result: one shared kept-index stream, per-
    column values re-evaluated on it (see :func:`compress_multivariate`)."""

    kept: np.ndarray        # bool [n] — shared union kept mask
    xr: np.ndarray          # float [n, C] — per-column reconstructions
    deviation: float        # max per-column deviation (the stored headline)
    n_kept: int             # |union|
    iters: int              # total compressor rounds/removals across columns
    deviations: np.ndarray  # [C] exact measured per-column deviation
    col_n_kept: np.ndarray  # [C] per-column own kept counts (pre-union)


def _column_masks(X: np.ndarray, cfg: CameoConfig, eps_c: np.ndarray,
                  cols, pad_to: Optional[int] = None) -> tuple:
    """(masks[C, n] for the requested ``cols``, iters) — rounds mode batches
    same-eps columns through ``compress_batch``; anything else runs
    per-column ``compress``.  ``pad_to`` rides through to the rounds bucket
    (streaming tails reuse the full-window program)."""
    import jax as _jax

    masks = {}
    iters = 0
    cols = list(cols)
    if cfg.mode == "rounds":
        by_eps = {}
        for c in cols:
            by_eps.setdefault(float(eps_c[c]), []).append(c)
        for eps, group in by_eps.items():
            gcfg = dataclasses.replace(cfg, eps=eps)
            if len(group) > 1:
                res = compress_batch(X[:, group].T, gcfg, pad_to=pad_to)
                _jax.block_until_ready(res.kept)
                for i, c in enumerate(group):
                    masks[c] = np.asarray(res.kept[i])
                    iters += int(res.iters[i])
            else:
                res = compress_rounds(jnp.asarray(X[:, group[0]]), gcfg,
                                      pad_to=pad_to)
                masks[group[0]] = np.asarray(res.kept)
                iters += int(res.iters)
    else:
        for c in cols:
            res = compress(jnp.asarray(X[:, c]),
                           dataclasses.replace(cfg, eps=float(eps_c[c])))
            masks[c] = np.asarray(res.kept)
            iters += int(res.iters)
    return masks, iters


_mv_recon_jit = None


def _union_reconstruct(x_col: np.ndarray, union: np.ndarray) -> np.ndarray:
    """Canonical one-shot interpolation of one column on the shared index —
    the same jitted ``_reconstruct`` the store decode uses, so the measured
    per-column deviation is exact for what readers will actually see."""
    global _mv_recon_jit
    if _mv_recon_jit is None:
        _mv_recon_jit = jax.jit(_reconstruct)
        OBS.register_jit("cameo.mvar_reconstruct", _mv_recon_jit)
    return np.asarray(_mv_recon_jit(jnp.asarray(x_col), jnp.asarray(union)))


def _column_deviation(x_col: np.ndarray, xr_col: np.ndarray,
                      cfg: CameoConfig) -> float:
    """Exact measured D(S(recon), S(orig)) of one column (Eq. 7 path)."""
    transform = _stat_transform(cfg)
    mfn = _measure_fn(cfg)
    y0 = centered(aggregate_series(jnp.asarray(x_col, cfg.jdtype()),
                                   cfg.kappa))
    y1 = centered(aggregate_series(jnp.asarray(xr_col, cfg.jdtype()),
                                   cfg.kappa))
    ny = int(y0.shape[0])
    s0 = transform(acf_from_aggregates(
        extract_aggregates(y0, cfg.lags, backend=cfg.backend), ny))
    s1 = transform(acf_from_aggregates(
        extract_aggregates(y1, cfg.lags, backend=cfg.backend), ny))
    return float(mfn(s1, s0))


def compress_multivariate(X, cfg: CameoConfig, *,
                          eps_c=None, max_retries: int = 4,
                          pad_to: Optional[int] = None) -> MVCompressResult:
    """Compress a multivariate series ``X [n, C]`` onto one shared index.

    The Sprintz-style shared-timestamp layout: every column is compressed
    independently (``compress_batch`` over the columns in rounds mode), the
    per-column kept masks are **unioned** into a single index stream, and
    every column is then *re-evaluated on the shared index* — its stored
    values are the original ``X[idx, c]`` at every union index, so each
    column's reconstruction interpolates through strictly more original
    points than its own greedy solution kept.

    The per-column ε guarantee is *enforced by measurement*, not assumed:
    each column's exact deviation is recomputed on the shared index, and a
    column that exceeds its budget (possible in principle — the ACF is not
    monotone in pointwise error) is recompressed at half its working budget
    and the union rebuilt, up to ``max_retries`` times; a still-violating
    column finally keeps all of its points (deviation exactly 0).  With
    ``target_cr`` set there is no ε to enforce and the measured deviations
    are reported as-is.

    ``eps_c`` (length-C) gives each column its own ε budget — channels with
    different fidelity needs share one index stream while each column's
    deviation is enforced against *its* budget (``None``: every column uses
    ``cfg.eps``).  ``pad_to`` rides through to the rounds shape bucket so
    streaming tail windows reuse the full-window compiled program.

    Returns an :class:`MVCompressResult` whose ``kept``/``xr`` feed
    ``CameoStore.append_series`` (v4 shared-index block layout) directly.
    """
    X = np.asarray(X)
    if X.ndim != 2:
        raise ValueError(f"compress_multivariate wants [n, C], got {X.shape}")
    if cfg.kappa > 1:
        X = X[:(X.shape[0] // cfg.kappa) * cfg.kappa]
    n, C = X.shape
    if eps_c is None:
        budget = np.full(C, float(cfg.eps))
    else:
        budget = np.asarray(eps_c, np.float64).reshape(-1)
        if budget.shape[0] != C:
            raise ValueError(
                f"eps_c has {budget.shape[0]} budgets for {C} columns")
        if np.any(budget <= 0):
            raise ValueError("eps_c budgets must be positive")
    eps_work = budget.copy()    # halves on repair; budget stays the bar
    masks, iters = _column_masks(X, cfg, eps_work, range(C), pad_to)
    enforce = cfg.target_cr is None
    retries = 0
    while True:
        union = np.zeros(n, bool)
        for c in range(C):
            union |= masks[c]
        xr = np.stack([_union_reconstruct(X[:, c], union)
                       for c in range(C)], axis=1)
        devs = np.array([_column_deviation(X[:, c], xr[:, c], cfg)
                         for c in range(C)])
        bad = [c for c in range(C)
               if enforce and np.isfinite(budget[c]) and devs[c] > budget[c]
               and not masks[c].all()]
        if not bad:
            break
        if retries >= max_retries:
            if OBS.enabled:
                OBS.inc("mvar.keep_all_columns", len(bad))
            for c in bad:     # last resort: the column keeps everything
                masks[c] = np.ones(n, bool)
            continue          # keep-all columns measure deviation 0 next pass
        retries += 1
        if OBS.enabled:
            OBS.inc("mvar.repair_halvings", len(bad))
        eps_work[bad] = eps_work[bad] / 2.0
        new_masks, it = _column_masks(X, cfg, eps_work, bad, pad_to)
        masks.update(new_masks)
        iters += it
    if OBS.enabled:
        for c in range(C):
            if np.isfinite(budget[c]) and budget[c] > 0:
                OBS.observe("mvar.eps_headroom", float(devs[c]) / budget[c])
    # per-column counts of the masks that actually went into the union
    # (recompressed/keep-all columns included, not their discarded firsts)
    col_n_kept = np.array([int(masks[c].sum()) for c in range(C)])
    return MVCompressResult(
        kept=union, xr=xr, deviation=float(devs.max()) if C else 0.0,
        n_kept=int(union.sum()), iters=iters, deviations=devs,
        col_n_kept=col_n_kept)


def kept_points(res: CompressResult):
    """(indices, values) numpy views of the kept points."""
    kept = np.asarray(res.kept)
    idx = np.nonzero(kept)[0]
    vals = np.asarray(res.xr)[idx]
    return idx, vals


def decompress(indices, values, n: int, dtype=jnp.float64) -> jax.Array:
    """Linear-interpolation decompression (paper §4.1): one forward pass."""
    indices = jnp.asarray(indices, dtype=dtype)
    values = jnp.asarray(values, dtype=dtype)
    grid = jnp.arange(n, dtype=dtype)
    return jnp.interp(grid, indices, values)


def compression_ratio(res: CompressResult) -> float:
    return float(res.kept.shape[0]) / float(res.n_kept)
