"""Pallas TPU kernel for the exact windowed (Eq. 9) ranking impact.

For every candidate point, computes the hypothetical ACF after
re-interpolating the candidate's whole segment (the up-to-``W``-point delta
window of a removal) and, here, its deviation from the original ACF.  This is
the math behind ``rank="window"`` — the exact Eq. 9 ranking that the
single-delta Algorithm-2 form (``acf_impact``, the ``W = 1`` case of the same
kernel) only approximates.  ``fused_round.window_rows_pallas`` runs the same
body and returns the ACF rows instead.

Layout: candidates sit on the 128 lanes and lags on the sublanes.  XLA
gathers each candidate's ``[W + 2Lp]`` context once outside the kernel (the
segment starts are data-dependent) and hands it over transposed, as a
column per candidate, together with a row-reversed copy.  Window offset
``j`` then reads ``y[t + l]`` for every lag as one *static* sublane-offset
slice ``[Lp, 128]`` of the context, ``y[t - l]`` as one of the reversed
copy, and ``d[t + l]`` as one of the transposed deltas; the ``j`` loop is
unrolled at trace time.  No load has a data-dependent offset, and VMEM per
grid step is ``O((W + L) · 128)``, independent of the series length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _pad8(x: int) -> int:
    return -(-x // 8) * 8


def _zero():
    # int32 index-map constant (an x64 Python 0 would lower as int64)
    return jnp.int32(0)


def lane_scalar(v):
    """An int32 scalar as a ``[1, 128]`` lane row.  Kernels take ``ny`` this
    way, as a VMEM block: a batch axis added by ``vmap`` (``compress_batch``
    on the chip) keeps it a legal block, where a batched SMEM scalar does
    not lower."""
    return jnp.full((1, LANES), v, jnp.int32)


def lane_blocks(p: int) -> int:
    """Candidate count padded to whole 128-lane blocks."""
    return -(-max(p, 1) // LANES) * LANES


def moment_table(agg_table, p0, Lp: int):
    """``[Lp, 8]`` per-lag table: the five Eq. 7 moments, then ``p0``."""
    L = agg_table.shape[-1]
    dt = agg_table.dtype
    tab = jnp.zeros((Lp, 8), dt).at[:L, :5].set(agg_table.T)
    if p0 is not None:
        tab = tab.at[:L, 5].set(p0.astype(dt))
    return tab


def transposed_operands(ctx_rows, dwins, starts, Lp: int):
    """Kernel operands from per-candidate rows.

    ``ctx_rows [P, W + 2Lp]`` holds ``y[start - Lp + k]`` (zero out of
    range), ``dwins [P, W]`` the delta windows.  Returns the lane-blocked
    ``(cT, cR, dT, s)``: contexts ``[Hc, Pp]`` and their row reversal,
    deltas ``[Hd, Pp]`` zero past ``W``, and starts ``[1, Pp]``.
    """
    P, W = dwins.shape
    Pp = lane_blocks(P)
    Hc = _pad8(W + 2 * Lp)
    Hd = _pad8(W + Lp)
    cT = jnp.pad(ctx_rows, ((0, Pp - P), (0, Hc - ctx_rows.shape[1]))).T
    dT = jnp.pad(dwins, ((0, Pp - P), (0, Hd - W))).T
    s = jnp.pad(starts.astype(jnp.int32), (0, Pp - P)).reshape(1, Pp)
    return cT, cT[::-1], dT, s


def window_moments(cT_ref, cR_ref, dT_ref, s_ref, ny, *, W: int, Lp: int):
    """The five per-lag Eq. 9 moment deltas ``[Lp, 128]`` of one lane block.

    Row ``r`` is lag ``l = r + 1``; lane ``k`` is one candidate whose window
    starts at global position ``s[k]``.  The head/tail masks are those of
    Eq. 9 (``t <= ny-1-l`` and ``t >= l`` for window position ``t``).
    """
    Hc = cT_ref.shape[0]
    dtype = cT_ref.dtype
    lag = jax.lax.broadcasted_iota(jnp.int32, (Lp, LANES), 0) + 1
    s = s_ref[...]                                            # [1, 128]
    zero = jnp.zeros((Lp, LANES), dtype)
    sx = sxl = sx2 = sxl2 = sxx = zero
    for j in range(W):
        d = dT_ref[pl.ds(j, 1), :]                            # d[t]
        z = cT_ref[pl.ds(Lp + j, 1), :]                       # y[t]
        z_f = cT_ref[pl.ds(Lp + j + 1, Lp), :]                # y[t + l]
        z_b = cR_ref[pl.ds(Hc - Lp - j, Lp), :]               # y[t - l]
        d_f = dT_ref[pl.ds(j + 1, Lp), :]                     # d[t + l]
        e = d * (2.0 * z + d)
        t = s + j
        head = t + lag <= ny - 1
        tail = t >= lag
        sx = sx + jnp.where(head, d, zero)
        sxl = sxl + jnp.where(tail, d, zero)
        sx2 = sx2 + jnp.where(head, e, zero)
        sxl2 = sxl2 + jnp.where(tail, e, zero)
        sxx = sxx + d * (jnp.where(head, z_f + d_f, zero)
                         + jnp.where(tail, z_b, zero))
    return sx, sxl, sx2, sxl2, sxx


def acf_rows(tab, moments, ny, Lp: int):
    """Eq. 2 ACF ``[Lp, 128]`` from the table ``[Lp, 8]`` plus deltas."""
    return acf_from_columns(
        *(tab[:, c:c + 1] + moments[c] for c in range(5)), ny=ny, Lp=Lp)


def acf_from_columns(sx, sxl, sx2, sxl2, sxx, *, ny, Lp: int):
    """Eq. 2 over per-lag moment columns (row ``r`` is lag ``r + 1``)."""
    dtype = sx.dtype
    lag = jax.lax.broadcasted_iota(jnp.int32, (Lp, 1), 0) + 1
    m = (ny - lag).astype(dtype)
    num = m * sxx - sx * sxl
    den2 = (m * sx2 - sx * sx) * (m * sxl2 - sxl * sxl)
    tiny = jnp.asarray(1e-30, dtype)
    den = jnp.sqrt(jnp.maximum(den2, tiny))
    return jnp.where(den2 > tiny, num / den, jnp.zeros_like(num))


def measure_lags(rho, p0col, L: int, measure: str):
    """Deviation ``[1, n]`` over the first ``L`` lag rows of ``rho``."""
    diff = rho - p0col
    valid = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0) < L
    zero = jnp.zeros_like(diff)
    if measure == "mae":
        return jnp.sum(jnp.where(valid, jnp.abs(diff), zero), axis=0,
                       keepdims=True) / L
    if measure == "rmse":
        return jnp.sqrt(jnp.sum(jnp.where(valid, diff * diff, zero), axis=0,
                                keepdims=True) / L)
    if measure == "cheb":
        return jnp.max(jnp.where(valid, jnp.abs(diff), zero), axis=0,
                       keepdims=True)
    raise ValueError(f"kernel supports mae/rmse/cheb, got {measure!r}")


def window_specs(Hc: int, Hd: int, Lp: int):
    """BlockSpecs of ``(cT, cR, dT, s, table, ny)`` for a lane-block grid."""
    lane_block = lambda rows: pl.BlockSpec(
        (rows, LANES), lambda i: (_zero(), i))
    return [
        lane_block(Hc), lane_block(Hc), lane_block(Hd), lane_block(1),
        pl.BlockSpec((Lp, 8), lambda i: (_zero(), _zero())),
        pl.BlockSpec((1, LANES), lambda i: (_zero(), _zero())),
    ]


def _window_impact_kernel(cT_ref, cR_ref, dT_ref, s_ref, tab_ref, ny_ref,
                          out_ref, *, W: int, L: int, Lp: int, measure: str):
    ny = ny_ref[...]
    tab = tab_ref[...]
    moments = window_moments(cT_ref, cR_ref, dT_ref, s_ref, ny, W=W, Lp=Lp)
    rho = acf_rows(tab, moments, ny, Lp)
    out_ref[...] = measure_lags(rho, tab[:, 5:6], L, measure)


def window_impact_call(cT, cR, dT, s, tab, ny, *, W: int, L: int,
                       measure: str, interpret: bool):
    """Deviations ``[Pp]`` for transposed operands (see
    :func:`transposed_operands`)."""
    Hc, Pp = cT.shape
    Lp = tab.shape[0]
    kernel = functools.partial(_window_impact_kernel, W=W, L=L, Lp=Lp,
                               measure=measure)
    out = pl.pallas_call(
        kernel,
        grid=(Pp // LANES,),
        in_specs=window_specs(Hc, dT.shape[0], Lp),
        out_specs=pl.BlockSpec((1, LANES), lambda i: (_zero(), i)),
        out_shape=jax.ShapeDtypeStruct((1, Pp), cT.dtype),
        interpret=interpret,
    )(cT, cR, dT, s, tab, lane_scalar(ny))
    return out[0]


@functools.partial(
    jax.jit, static_argnames=("ny", "L", "measure", "interpret"))
def acf_window_impact_pallas(y_ctx, dwins, starts_abs, agg_table, p0, *,
                             ny: int, L: int, measure: str = "mae",
                             interpret: bool = False):
    """Windowed impacts [P] via the Pallas kernel.

    ``y_ctx`` is the per-candidate ``[P, W + 2L]`` context
    (``y_ctx[p, k] = y[start_p - L + k]``, zero out of range — see
    ``kernels.ref.candidate_contexts``); ``dwins`` the ``[P, W]`` delta
    windows (zero beyond each candidate's span); ``starts_abs`` the global
    index of each window's first position; ``agg_table`` the stacked [5, L]
    aggregate table and ``p0`` the original ACF [L].  The grid steps one
    128-lane block of candidates at a time.
    """
    P, W = dwins.shape
    dtype = y_ctx.dtype
    Lp = _pad8(L)
    ctx = jnp.pad(y_ctx, ((0, 0), (Lp - L, Lp - L)))
    cT, cR, dT, s = transposed_operands(ctx, dwins.astype(dtype), starts_abs,
                                        Lp)
    tab = moment_table(agg_table.astype(dtype), p0, Lp)
    out = window_impact_call(cT, cR, dT, s, tab, ny, W=W, L=L,
                             measure=measure, interpret=interpret)
    return out[:P]
