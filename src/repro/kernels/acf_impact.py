"""Pallas TPU kernel for GetAllImpact (paper Algorithm 2) — CAMEO's hot loop.

For every candidate point i, computes the deviation measure between the
hypothetical ACF after a single-point delta at i (Eq. 8) and the original
ACF.  A single-point delta is a windowed delta of width one, so this runs
the Eq. 9 kernel body of ``acf_window_impact`` with ``W = 1``: candidates on
the lanes, lags on the sublanes, each candidate's ``2L + 1`` context
gathered by XLA.  The candidates are processed in chunks (``lax.map``) so
the gathered contexts never exceed ``block · (2L + 1)`` values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.acf_window_impact import (_pad8, lane_blocks, moment_table,
                                             transposed_operands,
                                             window_impact_call)


@functools.partial(
    jax.jit, static_argnames=("L", "measure", "block", "interpret"))
def acf_impact_pallas(y, dval, agg_table, p0, *, idx=None, L: int,
                      measure: str = "mae", block: int = 1024,
                      interpret: bool = False):
    """Impacts [P] via the Pallas kernel.

    ``agg_table`` is the stacked [5, L] aggregate table
    (sx, sxl, sx2, sxl2, sxx); ``p0`` the original ACF [L].  Candidate ``p``
    adds ``dval[p]`` at position ``idx[p]`` of ``y`` (default: every
    position, ``idx = arange(n)``).
    """
    ny = y.shape[0]
    dtype = y.dtype
    if idx is None:
        idx = jnp.arange(ny, dtype=jnp.int32)
    P = idx.shape[0]
    Lp = _pad8(L)
    C = lane_blocks(min(block, P))
    nch = -(-P // C)
    ii = jnp.pad(idx.astype(jnp.int32), (0, nch * C - P)).reshape(nch, C)
    dd = jnp.pad(dval.astype(dtype), (0, nch * C - P)).reshape(nch, C)
    y_pad = jnp.pad(y, (Lp, Lp + 1))
    k = jnp.arange(2 * Lp + 1)
    tab = moment_table(agg_table.astype(dtype), p0, Lp)

    def chunk(args):
        i, d = args
        ctx = y_pad[jnp.clip(i, 0, ny)[:, None] + k[None, :]]
        cT, cR, dT, s = transposed_operands(ctx, d[:, None], i, Lp)
        return window_impact_call(cT, cR, dT, s, tab, ny, W=1, L=L,
                                  measure=measure, interpret=interpret)

    return jax.lax.map(chunk, (ii, dd)).reshape(-1)[:P]
