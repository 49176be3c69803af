"""Impact-engine backend layer: pallas ≡ reference parity (ranking, whole
compressions) and the batched multi-series front-end."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.acf import acf_from_aggregates, aggregate_series, \
    extract_aggregates
from repro.core.cameo import (CameoConfig, compress_batch, compress_rounds,
                              compress_sequential)
from repro.kernels import ops


def _series(n, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    return jnp.asarray(np.sin(2 * np.pi * t / 24)
                       + 0.5 * np.sin(2 * np.pi * t / 168)
                       + 0.15 * rng.standard_normal(n))


def _ranking_setup(cfg, n, seed=0):
    x = _series(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    alive = jnp.asarray(rng.random(n) > 0.3)
    alive = alive.at[0].set(True).at[-1].set(True)
    from repro.core.cameo import _reconstruct, _stat_transform
    xr = _reconstruct(x, alive)
    y = aggregate_series(xr, cfg.kappa)
    agg = extract_aggregates(y, cfg.lags)
    p0 = _stat_transform(cfg)(acf_from_aggregates(agg, y.shape[0]))
    return x, xr, alive, y, agg, p0


@pytest.mark.parametrize("rank", ["single", "window"])
@pytest.mark.parametrize("kappa", [1, 4])
@pytest.mark.parametrize("measure", ["mae", "rmse", "cheb"])
def test_ranking_impact_backend_parity(rank, kappa, measure):
    """pallas (interpret) ≡ reference GetAllImpact, all kernel measures."""
    n = 512
    cfg = CameoConfig(lags=12, rank=rank, kappa=kappa, measure=measure,
                      backend="reference", impact_chunk=256)
    x, xr, alive, y, agg, p0 = _ranking_setup(cfg, n)
    ref_imp = ops.ranking_impact(cfg, agg, y, xr, alive, p0, n)
    pal_imp = ops.ranking_impact(
        dataclasses.replace(cfg, backend="pallas"), agg, y, xr, alive, p0, n)
    np.testing.assert_allclose(np.asarray(ref_imp), np.asarray(pal_imp),
                               rtol=1e-9, atol=1e-9)


def test_ranking_impact_pacf_falls_back(monkeypatch):
    """Configs the kernels can't serve (pacf / non-kernel measures): an
    ``"auto"`` that resolves to pallas falls back to the reference math
    (identical results), an explicit ``backend="pallas"`` raises."""
    n = 256
    monkeypatch.setenv("CAMEO_BACKEND", "pallas")
    for kw in [dict(stat="pacf"), dict(measure="mape")]:
        cfg = CameoConfig(lags=8, backend="reference", **kw)
        x, xr, alive, y, agg, p0 = _ranking_setup(cfg, n, seed=3)
        a = ops.ranking_impact(cfg, agg, y, xr, alive, p0, n)
        b = ops.ranking_impact(
            dataclasses.replace(cfg, backend="auto"),
            agg, y, xr, alive, p0, n)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-12, atol=1e-12)
        with pytest.raises(ValueError, match="no kernel"):
            ops.ranking_impact(dataclasses.replace(cfg, backend="pallas"),
                               agg, y, xr, alive, p0, n)


@pytest.mark.parametrize("rank", ["single", "window"])
@pytest.mark.parametrize("kappa", [1, 4])
def test_compress_rounds_backend_identical_kept(rank, kappa):
    """Acceptance: backend="pallas" (interpret on CPU) produces identical
    kept masks to backend="reference" end to end."""
    x = _series(768, seed=4)
    cfg = CameoConfig(eps=0.02, lags=12, mode="rounds", rank=rank,
                      kappa=kappa, backend="reference")
    a = compress_rounds(x, cfg)
    b = compress_rounds(x, dataclasses.replace(cfg, backend="pallas"))
    np.testing.assert_array_equal(np.asarray(a.kept), np.asarray(b.kept))
    assert abs(float(a.deviation) - float(b.deviation)) < 1e-9


def test_compress_backend_identical_kept_quickstart_series():
    """Acceptance criterion on the quickstart dataset (uk_elec)."""
    from repro.data.synthetic import make_dataset
    x = jnp.asarray(make_dataset("uk_elec", seed=0, length=1024))
    cfg = CameoConfig(eps=1e-2, lags=24, mode="rounds",
                      backend="reference")
    a = compress_rounds(x, cfg)
    b = compress_rounds(x, dataclasses.replace(cfg, backend="pallas"))
    np.testing.assert_array_equal(np.asarray(a.kept), np.asarray(b.kept))
    assert float(a.deviation) <= cfg.eps + 1e-12


def test_compress_sequential_backend_runs():
    """Sequential mode threads the backend through ReHeap + init impacts."""
    x = _series(384, seed=5)
    cfg = CameoConfig(eps=0.05, lags=8, mode="sequential", backend="pallas")
    res = compress_sequential(x, cfg)
    assert float(res.deviation) <= cfg.eps + 1e-12
    ref = compress_sequential(
        x, dataclasses.replace(cfg, backend="reference"))
    np.testing.assert_array_equal(np.asarray(res.kept), np.asarray(ref.kept))


def test_extract_aggregates_backend_parity():
    y = _series(1000, seed=6)
    a = extract_aggregates(y, 24, backend="reference")
    b = extract_aggregates(y, 24, backend="pallas")
    for f in a._fields:
        np.testing.assert_allclose(np.asarray(getattr(a, f)),
                                   np.asarray(getattr(b, f)),
                                   rtol=1e-10, atol=1e-10)


def test_resolve_backend():
    assert ops.resolve_backend("pallas") == "pallas"
    assert ops.resolve_backend("reference") == "reference"
    assert ops.resolve_backend("auto") in ("pallas", "reference")
    with pytest.raises(ValueError):
        ops.resolve_backend("nope")


def _steer_platform(monkeypatch, platform):
    """Make the dispatch code see ``platform`` (no option of the program
    does this; the compile itself never runs here)."""
    monkeypatch.delenv("CAMEO_BACKEND", raising=False)
    monkeypatch.delenv("CAMEO_FORCE_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)


def _rule(platform, dtype, stat, measure, backend):
    """The documented dispatch rule, written out independently of ops:
    ``"kernel"``, ``"reference"`` or ``"raises"``."""
    if backend == "auto":
        backend = "pallas" if platform == "tpu" else "reference"
        explicit = False
    else:
        explicit = True
    if backend == "reference":
        return "reference"
    servable = (stat == "acf" and measure in ("mae", "rmse", "cheb")
                and (platform != "tpu" or dtype == "float32"))
    if servable:
        return "kernel"
    return "raises" if explicit else "reference"


@pytest.mark.parametrize("backend", ["auto", "pallas", "reference"])
@pytest.mark.parametrize("measure", ["mae", "mape"])
@pytest.mark.parametrize("stat", ["acf", "pacf"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_dispatch_rule(monkeypatch, platform, dtype, stat, measure, backend):
    """``ops.use_kernel`` / ``use_fused_kernel`` for every (platform, dtype,
    stat, measure, backend): float64 never reaches a compiled kernel, an
    explicit pallas that cannot be honoured raises, and the fused-round
    twins are the decision path only when compiled."""
    _steer_platform(monkeypatch, platform)
    want = _rule(platform, dtype, stat, measure, backend)
    args = (backend, jnp.dtype(dtype), stat, measure)
    if want == "raises":
        with pytest.raises(ValueError, match="cannot run here"):
            ops.use_kernel(*args)
        with pytest.raises(ValueError, match="cannot run here"):
            ops.use_fused_kernel(*args)
        return
    assert ops.use_kernel(*args) == (want == "kernel")
    assert ops.use_fused_kernel(*args) == (want == "kernel"
                                           and platform == "tpu")


@pytest.mark.parametrize("nyb,K,Wy,L,fits", [
    (18432, 1843, 64, 48, True),         # uk_elec bucket, scan select
    (524288, 52428, 64, 48, True),       # compiled for v5e (README)
    (786432, 78643, 64, 48, False),      # ran out of VMEM
    (8739, 104868, 2, 24, True),         # solar bucket, kappa=120
    (17477, 209724, 2, 24, False),       # ran out of SMEM
])
def test_dispatch_refuses_fused_kernel_past_fast_memory(monkeypatch, nyb, K,
                                                        Wy, L, fits):
    """The fused prefix kernel keeps its whole problem on chip: past the
    fast-memory budget "auto" takes the reference form and an explicit
    pallas raises (the compiles behind each case: kernels/README.md)."""
    from repro.kernels.fused_round import prefix_devs_footprint
    _steer_platform(monkeypatch, "tpu")
    fp = prefix_devs_footprint(nyb, K, Wy, L)
    assert ops.use_fused_kernel("auto", jnp.float32, footprint=fp) == fits
    if fits:
        assert ops.use_fused_kernel("pallas", jnp.float32, footprint=fp)
    else:
        with pytest.raises(ValueError, match="budget"):
            ops.use_fused_kernel("pallas", jnp.float32, footprint=fp)


@pytest.mark.parametrize("backend", ["auto", "pallas", "reference"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_lag_dot_runs_the_dispatched_path(monkeypatch, platform, dtype,
                                          backend):
    """The Eq. 7 lagged products go to the kernel exactly when the rule
    says so, interpreted off the chip; an unservable explicit pallas
    raises instead of running the reference."""
    _steer_platform(monkeypatch, platform)
    calls = []

    def spy(a, b, halo, *, L, block, interpret):
        calls.append(interpret)
        return jnp.zeros((L,), a.dtype)

    monkeypatch.setattr(ops, "lag_dot_pallas", spy)
    y = _series(300).astype(dtype)
    want = _rule(platform, dtype, "acf", "mae", backend)
    if want == "raises":
        with pytest.raises(ValueError, match="float32 kernels only"):
            ops.lag_dot(y, 6, backend=backend)
        assert calls == []
        return
    out = ops.lag_dot(y, 6, backend=backend)
    if want == "kernel":
        assert calls == [platform != "tpu"]
    else:
        assert calls == []
        yn = np.asarray(y, np.float64)
        np.testing.assert_allclose(
            np.asarray(out), [yn[:-l] @ yn[l:] for l in range(1, 7)],
            rtol=1e-5 if dtype == "float32" else 1e-12)


def _prefix_setup(seed=9, nyb=160, ny=150, K=12, Wy=16, L=8):
    """Synthetic fused-round inputs honouring the kernel contract: y is
    zero-padded beyond ny, candidate delta windows stay inside [0, ny)."""
    from repro.core.cameo import _stat_transform
    rng = np.random.default_rng(seed)
    y = np.zeros(nyb)
    y[:ny] = np.asarray(_series(ny, seed=seed))
    starts = rng.integers(0, ny - Wy, size=K).astype(np.int32)
    dyws = 0.1 * rng.standard_normal((K, Wy))
    ok = rng.random(K) > 0.25
    agg = extract_aggregates(jnp.asarray(y[:ny]), L)
    p0 = acf_from_aggregates(agg, ny)
    table = ops.agg_to_table(agg)
    return (jnp.asarray(y), jnp.asarray(dyws), jnp.asarray(starts),
            jnp.asarray(ok), table, p0)


@pytest.mark.parametrize("measure", ["mae", "rmse", "cheb"])
def test_prefix_devs_pallas_interpret_parity(measure):
    """Fused-round parity: the Pallas prefix-deviation kernel (interpret
    mode on CPU) matches the reference prefix rows to fp tolerance — the
    accumulation orders differ, so this is allclose, not bit-equality."""
    from repro.kernels import fused_round as fr
    from repro.kernels import ref as kref
    y, dyws, starts, ok, table, p0 = _prefix_setup()
    L = int(table.shape[-1])
    ny = 150
    rows = fr.prefix_acf_rows_ref(y, dyws, starts, ok, table, ny, L=L)
    want = kref.measure_rows(rows, p0, measure)
    got = fr.prefix_devs_pallas(y, dyws, starts, ok, table, p0, ny,
                                L=L, measure=measure, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-9, atol=1e-9)


def test_greedy_feasible_pallas_interpret_matches_oracle():
    """The greedy fused pass (conditional commit in VMEM) against a pure
    numpy oracle that rebuilds the reconstruction and recomputes the ACF
    from scratch at every trial."""
    from repro.core import measures
    from repro.core.acf import acf
    from repro.kernels import fused_round as fr
    y, dyws, starts, ok, table, p0 = _prefix_setup(seed=11)
    L = int(table.shape[-1])
    ny, Wy, K = 150, dyws.shape[1], dyws.shape[0]
    eps = 0.02
    devs = fr.prefix_devs_pallas(y, dyws, starts, ok, table, p0, ny, eps,
                                 L=L, measure="mae", greedy=True,
                                 interpret=True)
    take = np.asarray(ok) & (np.asarray(devs) <= eps)

    z = np.asarray(y).copy()
    oracle_devs, oracle_take = [], []
    for k in range(K):
        s = int(starts[k])
        trial = z.copy()
        trial[s:s + Wy] += np.asarray(dyws[k]) * float(ok[k])
        dev = float(measures.mae(acf(jnp.asarray(trial[:ny]), L), p0))
        commit = bool(ok[k]) and dev <= eps
        if commit:
            z = trial
        oracle_devs.append(dev)
        oracle_take.append(commit)
    np.testing.assert_allclose(np.asarray(devs), oracle_devs,
                               rtol=1e-8, atol=1e-9)
    # decisions are tolerance-robust here: no trial lands within 1e-6 of eps
    assert min(abs(d - eps) for d in oracle_devs) > 1e-6
    np.testing.assert_array_equal(take, np.asarray(oracle_take))


def test_compress_batch_matches_per_series():
    """The batched front-end is bit-identical to per-series rounds runs."""
    n, B = 512, 3
    xs = jnp.stack([_series(n, seed=s) for s in range(B)])
    cfg = CameoConfig(eps=0.02, lags=12, mode="rounds")
    batch = compress_batch(xs, cfg)
    assert batch.kept.shape == (B, n)
    for i in range(B):
        one = compress_rounds(xs[i], cfg)
        np.testing.assert_array_equal(np.asarray(batch.kept[i]),
                                      np.asarray(one.kept))
        assert abs(float(batch.deviation[i]) - float(one.deviation)) < 1e-12
        assert int(batch.iters[i]) == int(one.iters)


def test_compress_batch_validates_inputs():
    cfg = CameoConfig(mode="sequential")
    with pytest.raises(ValueError):
        compress_batch(jnp.zeros((2, 64)), cfg)
    with pytest.raises(ValueError):
        compress_batch(jnp.zeros(64), CameoConfig())
