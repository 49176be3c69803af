"""Plain numpy reference of what a CAMEO deployment guarantees.

Independent of ``repro``: the ACF (Eq. 2) as a Pearson correlation per lag,
the mean-absolute ACF deviation on kappa-aggregates, and the decode as the
straight lines between kept points.  ``acf_np`` and ``acf_deviation_np``
are copies of the bring-up smoke's reference.
"""
from __future__ import annotations

import numpy as np


def acf_np(y, L: int) -> np.ndarray:
    """Eq. 2 ACF: per lag ``l``, the Pearson correlation of ``y[:-l]`` with
    ``y[l:]`` (zero where either side is constant)."""
    y = np.asarray(y, np.float64)
    out = np.zeros(L)
    for l in range(1, L + 1):
        a, b = y[:-l] - y[:-l].mean(), y[l:] - y[l:].mean()
        den = np.sqrt(np.dot(a, a) * np.dot(b, b))
        out[l - 1] = np.dot(a, b) / den if den > 0 else 0.0
    return out


def aggregate(v, kappa: int) -> np.ndarray:
    v = np.asarray(v, np.float64)
    return v[:len(v) // kappa * kappa].reshape(-1, kappa).mean(axis=1)


def acf_deviation_np(x, xr, L: int, kappa: int) -> float:
    """Mean absolute ACF deviation (the ``mae`` measure) of ``xr`` from
    ``x``, both mean-aggregated over tumbling windows of ``kappa``."""
    return float(np.mean(np.abs(acf_np(aggregate(xr, kappa), L)
                                - acf_np(aggregate(x, kappa), L))))


def interpolate(idx, vals, n: int) -> np.ndarray:
    """The decode a kept set stands for: straight lines between kept
    points (the first and last point are always kept)."""
    return np.interp(np.arange(n, dtype=np.float64),
                     np.asarray(idx, np.float64), np.asarray(vals, np.float64))


def decode_gaps(x, idx, vals, xr) -> dict:
    """How far a read-back departs from its guarantees, both relative to
    the series' largest magnitude: ``kept_gap`` is the largest difference
    between a stored kept value and the original point, ``interp_gap`` the
    largest difference of the decode from the straight lines between the
    kept points."""
    x = np.asarray(x, np.float64)
    idx = np.asarray(idx, np.int64)
    vals = np.asarray(vals, np.float64)
    scale = max(float(np.max(np.abs(x))), 1e-300)
    kept_gap = (float(np.max(np.abs(x[idx] - vals))) / scale if len(idx)
                else np.inf)
    lines = interpolate(idx, vals, len(x))
    interp_gap = float(np.max(np.abs(np.asarray(xr, np.float64) - lines))) / scale
    return {"kept_gap": kept_gap, "interp_gap": interp_gap}
