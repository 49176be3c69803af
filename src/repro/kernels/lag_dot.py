"""Pallas TPU kernel for the lagged self-products ``sxx_l`` (Eq. 7).

ExtractAggregates is O(nL), dominated by ``sxx_l = sum_t y_t * y_{t+l}``
(paper §4.2); the four moment sums are O(n + L) prefix work and stay in XLA.

The kernel makes the lag sum a matmul.  The series is laid out as rows of
128 lanes, ``A[r, c] = a[128 r + c]``, and likewise ``Bm`` for the
lag-shifted operand.  A lag ``l = 128 q + s`` then pairs row ``r`` of ``A``
with row ``r + q`` of ``Bm`` (lane ``c + s``) or row ``r + q + 1`` (lane
``c + s - 128``), so every lag is a diagonal sum of one of the ``[128, 128]``
products ``G_q = A^T Bm[q:]``.  Each grid step streams one ``[R, 128]``
block of both operands (plus an 8-row halo of ``Bm`` from the next block),
adds its ``Q = L // 128 + 2`` products to the resident ``[Q, 128, 128]``
output on the MXU (grid steps run in order on the TPU, so accumulating
into the output block is safe), and XLA takes the ``L`` diagonal sums at
the end.  Every block offset is static or tile-aligned, so nothing in the
body needs an unaligned dynamic slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _zero():
    # Index maps return int32: under jax_enable_x64 a bare Python 0 becomes
    # an int64 constant, which the TPU lowering refuses.
    return jnp.int32(0)


def lag_dot_kernel(a_ref, b_ref, bh_ref, g_ref, bs_ref, *, R: int, Q: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    # Main block plus the next block's first rows, contiguous in scratch so
    # each row shift q is one static (sublane-offset) load.
    H = bh_ref.shape[0]
    bs_ref[pl.ds(0, R), :] = b_ref[...]
    bs_ref[pl.ds(R, H), :] = bh_ref[...]
    a = a_ref[...]
    for q in range(Q):
        g_ref[q] += jax.lax.dot_general(
            a, bs_ref[pl.ds(q, R), :], (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=g_ref.dtype)


def _diagonal_index(L: int):
    """Static ``(q, c, c')`` indices with ``out[l-1] = sum_c G[q, c, c']``."""
    l = np.arange(1, L + 1)[:, None]
    c = np.arange(LANES)[None, :]
    col = c + l % LANES
    q = l // LANES + col // LANES
    return q, np.broadcast_to(c, q.shape), col % LANES


@functools.partial(jax.jit, static_argnames=("L", "block", "interpret"))
def lag_dot_pallas(y, b=None, halo=None, *, L: int, block: int = 4096,
                   interpret: bool = False):
    """``out[l-1] = sum_{t < n} a_t * b_ext_{t+l}`` for l in 1..L, shape [L].

    With the defaults (``b=None, halo=None``) this is the Eq. 7 lagged
    self-product ``sxx``.  ``b`` computes *cross* lagged products and
    ``halo`` appends an L-point continuation of ``b`` past the chunk end
    (the partitioned mode's overlap terms).  ``block`` is the number of
    series points per grid step (rounded to whole 8-row tiles).
    """
    n = y.shape[0]
    dtype = y.dtype
    Q = L // LANES + 2
    H = max(8, -(-(Q - 1) // 8) * 8)                 # halo rows per block
    R = max(H, -(-(block // LANES) // H) * H)         # rows per block
    nblk = -(-n // (R * LANES))
    rows = nblk * R
    b_base = y if b is None else b.astype(dtype)
    if halo is not None:
        b_base = jnp.concatenate([b_base, halo[:L].astype(dtype)])
    a2 = jnp.pad(y, (0, rows * LANES - n)).reshape(rows, LANES)
    b2 = jnp.pad(b_base, (0, (rows + R) * LANES - b_base.shape[0]))
    b2 = b2.reshape(rows + R, LANES)

    kernel = functools.partial(lag_dot_kernel, R=R, Q=Q)
    g = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((R, LANES), lambda i: (i, _zero())),
            pl.BlockSpec((R, LANES), lambda i: (i, _zero())),
            # the next block's first H rows (block index in units of H)
            pl.BlockSpec((H, LANES), lambda i: ((i + 1) * (R // H), _zero())),
        ],
        out_specs=pl.BlockSpec((Q, LANES, LANES),
                               lambda i: (_zero(), _zero(), _zero())),
        out_shape=jax.ShapeDtypeStruct((Q, LANES, LANES), dtype),
        scratch_shapes=[pltpu.VMEM((R + H, LANES), dtype)],
        interpret=interpret,
    )(a2, b2, b2)
    q, c, cc = _diagonal_index(L)
    return jnp.sum(g[q, c, cc], axis=1)
