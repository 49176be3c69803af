"""CAMEO compression: hard-guarantee semantics, both modes, all variants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import measures
from repro.core.acf import acf, aggregate_series, pacf_from_acf
from repro.core.cameo import (CameoConfig, compress, compress_multivariate,
                              compression_ratio, decompress, kept_points)


def _series(n, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    return jnp.asarray(np.sin(2 * np.pi * t / 24)
                       + 0.5 * np.sin(2 * np.pi * t / 168)
                       + 0.15 * rng.standard_normal(n))


def _true_deviation(x, res, cfg):
    idx, vals = kept_points(res)
    recon = decompress(idx, vals, x.shape[0])
    y0 = aggregate_series(x, cfg.kappa)
    y1 = aggregate_series(recon, cfg.kappa)
    s0, s1 = acf(y0, cfg.lags), acf(y1, cfg.lags)
    if cfg.stat == "pacf":
        s0, s1 = pacf_from_acf(s0), pacf_from_acf(s1)
    return float(measures.get_measure(cfg.measure)(s1, s0))


@pytest.mark.parametrize("mode", ["rounds", "sequential"])
def test_eps_guarantee_and_exact_reporting(mode):
    x = _series(512)
    cfg = CameoConfig(eps=0.02, lags=12, mode=mode, dtype="float64")
    res = compress(x, cfg)
    assert float(res.deviation) <= cfg.eps + 1e-12
    # the reported deviation is exact w.r.t. the true reconstruction
    assert abs(_true_deviation(x, res, cfg) - float(res.deviation)) < 1e-8
    assert compression_ratio(res) > 1.5


def _acf_centred_np(y, L):
    """Eq. 2 per lag in plain float64 numpy: the Pearson correlation of
    ``y[:-l]`` with ``y[l:]``, each side about its own mean."""
    y = np.asarray(y, np.float64)
    out = np.zeros(L)
    for l in range(1, L + 1):
        a, b = y[:-l] - y[:-l].mean(), y[l:] - y[l:].mean()
        out[l - 1] = a @ b / np.sqrt((a @ a) * (b @ b))
    return out


@pytest.mark.parametrize("mode", ["rounds", "sequential", "multivariate"])
def test_offset_series_matches_centred_reference(mode):
    """A series riding far from zero: the target ACF and every reported
    deviation agree with a centred numpy reference.  Raw Eq. 7 moments
    would lose both to cancellation in ``m * sxx - sx * sxl``."""
    offset = 1e6
    cfg = CameoConfig(eps=0.02, lags=12, dtype="float64",
                      mode="rounds" if mode == "multivariate" else mode)
    L = cfg.lags
    if mode == "multivariate":
        X = np.stack([np.asarray(_series(512)) + offset,
                      np.asarray(_series(512, seed=1)) - 3 * offset], axis=1)
        res = compress_multivariate(X, cfg)
        for c in range(X.shape[1]):
            dev = np.mean(np.abs(_acf_centred_np(res.xr[:, c], L)
                                 - _acf_centred_np(X[:, c], L)))
            assert abs(float(res.deviations[c]) - dev) < 1e-9
            assert dev <= cfg.eps + 1e-12
        return
    x = np.asarray(_series(512)) + offset
    res = compress(jnp.asarray(x), cfg)
    want = _acf_centred_np(x, L)
    np.testing.assert_allclose(np.asarray(res.stat_orig), want,
                               rtol=0, atol=1e-9)
    dev = np.mean(np.abs(_acf_centred_np(res.xr, L) - want))
    assert abs(float(res.deviation) - dev) < 1e-9
    assert dev <= cfg.eps + 1e-12
    assert int(res.n_kept) < x.shape[0]


def test_kept_points_bit_exact():
    x = _series(256, seed=2)
    cfg = CameoConfig(eps=0.05, lags=8, dtype="float64")
    res = compress(x, cfg)
    kept = np.asarray(res.kept)
    np.testing.assert_array_equal(np.asarray(res.xr)[kept],
                                  np.asarray(x)[kept])
    # endpoints always kept
    assert kept[0] and kept[-1]


def test_eps_zero_removes_almost_nothing():
    x = _series(256, seed=3)
    cfg = CameoConfig(eps=0.0, lags=8, dtype="float64")
    res = compress(x, cfg)
    assert compression_ratio(res) < 1.2


def test_monotone_in_eps():
    x = _series(512, seed=4)
    crs = []
    for eps in [1e-3, 1e-2, 5e-2]:
        res = compress(x, CameoConfig(eps=eps, lags=12, dtype="float64"))
        crs.append(compression_ratio(res))
    assert crs[0] <= crs[1] + 0.5 and crs[1] <= crs[2] + 0.5


def test_kappa_aggregates_variant():
    x = _series(512, seed=5)
    cfg = CameoConfig(eps=0.02, lags=8, kappa=8, dtype="float64")
    res = compress(x, cfg)
    assert float(res.deviation) <= cfg.eps + 1e-12
    assert abs(_true_deviation(x, res, cfg) - float(res.deviation)) < 1e-8


def test_pacf_variant():
    x = _series(512, seed=6)
    cfg = CameoConfig(eps=0.05, lags=8, stat="pacf", dtype="float64")
    res = compress(x, cfg)
    assert float(res.deviation) <= cfg.eps + 1e-12
    assert abs(_true_deviation(x, res, cfg) - float(res.deviation)) < 1e-8


def test_compression_centric_def3():
    x = _series(512, seed=7)
    cfg = CameoConfig(lags=8, target_cr=8.0, dtype="float64")
    res = compress(x, cfg)
    assert compression_ratio(res) >= 7.9


def test_max_cr_halt():
    x = _series(512, seed=8)
    cfg = CameoConfig(eps=1.0, lags=8, max_cr=4.0, dtype="float64")
    res = compress(x, cfg)
    assert compression_ratio(res) <= 4.3


def test_decompress_interpolation():
    idx = [0, 4, 8]
    vals = [0.0, 4.0, 0.0]
    recon = np.asarray(decompress(idx, vals, 9))
    np.testing.assert_allclose(recon, [0, 1, 2, 3, 4, 3, 2, 1, 0])


def test_measures_registry():
    a = jnp.asarray([1.0, 2.0, 3.0])
    b = jnp.asarray([1.5, 2.0, 2.0])
    assert abs(float(measures.mae(a, b)) - 0.5) < 1e-12
    assert abs(float(measures.cheb(a, b)) - 1.0) < 1e-12
    assert float(measures.rmse(a, b)) > 0
    with pytest.raises(ValueError):
        measures.get_measure("nope")
