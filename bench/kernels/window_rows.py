"""``window_rows_pallas`` (``repro/kernels/fused_round.py``): per-candidate
Eq. 9 trial ACF rows, the ranking kernel of the rounds loop.

One call runs a grid over 128-candidate lane blocks.  Operands, as the
compiled call lists them: contexts ``cT [Hc, Kp]`` and their row reversal
``cR [Hc, Kp]``, deltas ``dT [Hd, Kp]``, window starts ``s [1, Kp]``
(int32), the moment table ``[Lp, 8]`` and ``ny`` as a ``[1, 128]`` row; the
result is ``[Lp, Kp]``.  With ``Lp`` the lag count padded to 8,
``Hd = pad8(W + Lp)`` and ``Hc = pad8(W + 2 Lp)``, so the window width is
``W = Hd - Lp`` (exact for the rounds loop's widths 8 and 64, both
multiples of 8).

Per lane block and window position (``W`` of them) the body updates five
``[Lp, 128]`` moment accumulators with 8 floating-point operations per
element (4 for ``sx, sxl, sx2, sxl2``; 4 for ``sxx``) and forms
``d (2 z + d)`` on one ``[1, 128]`` row (3 operations); the ACF of each
lane then takes 17 operations per ``[Lp, 128]`` element (5 table adds,
10 for the Eq. 2 numerator and denominator, a square root and a division).
Masks and index arithmetic are not counted.

Bytes are the HBM traffic of the grid: every lane block of ``cT``,
``cR``, ``dT`` and ``s`` read once, the table and ``ny`` blocks read once
(their block index never changes), the result written once.
"""
from __future__ import annotations

INSTRUCTION = "window_rows_pallas"
LANES = 128

_ITEM = {"f32": 4, "s32": 4, "bf16": 2, "f16": 2, "f64": 8, "s64": 8}


def cost(operands, result) -> tuple:
    """``(flops, bytes)`` of one call, from ``[(dtype, dims), ...]`` of its
    six operands and ``(dtype, dims)`` of its result."""
    (dt, (hc, kp)), _, (_, (hd, _)), (st, _), (_, (lp, tw)), (nt, (_, nl)) \
        = operands
    rdt, (rl, rk) = result
    if (rl, rk) != (lp, kp):
        raise ValueError(f"result {result} does not match Lp={lp}, Kp={kp}")
    w = hd - lp
    blocks = kp // LANES
    flops = blocks * (lp * LANES * (8 * w + 17) + 3 * LANES * w)
    nbytes = (_ITEM[dt] * kp * (2 * hc + hd) + _ITEM[st] * kp
              + _ITEM[dt] * lp * tw + _ITEM[nt] * nl
              + _ITEM[rdt] * lp * kp)
    return float(flops), float(nbytes)
