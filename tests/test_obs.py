"""The unified telemetry layer (``repro.obs``) — contract tests.

What is pinned here:

* ``StreamingHistogram`` quantiles against ``np.quantile`` oracles on
  random streams (the log-bucketed sketch promises ~4.4% relative error);
* the disabled path costs one attribute lookup — a microbench bounds it,
  and ``obs.span`` returns the shared ``NULL_SPAN`` identity;
* the Prometheus-style exposition is byte-deterministic (golden test);
* spans land on the profiler's trace by their bare names, nested by time,
  and in the registry; every span the program opens is in ``obs.SPANS``;
* the observer property: ingesting and querying with ``CAMEO_OBS`` on
  produces **byte-identical stores and bit-identical query answers** to
  running with it off;
* ``recompile_watermark`` covers every registered jitted entry point and
  the old ``core.streaming.compile_cache_size`` survives as a deprecated
  shim over it;
* the unified ``stats()`` schema: ``Dataset.stats()`` fast (O(1) running
  totals) vs ``deep=True`` (per-series walk) agree, and
  ``TimeSeriesService.stats()`` is a key-superset with equal shared keys;
* the acceptance snapshot: a streamed multivariate ingest plus a pushdown
  query session reports push-latency quantiles, window/queue counters,
  the recompile watermark, cache hit rates, and realized bound widths.
"""
import glob
import math
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import OBS, MetricsRegistry, NULL_SPAN, StreamingHistogram
from repro.obs import sanitize_metric_name
from repro.core.cameo import CameoConfig, compress

CFG = CameoConfig(eps=2e-2, lags=8, mode="rounds", max_rounds=60,
                  dtype="float64")


def _series(n, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    return (3 * np.sin(2 * np.pi * t / 24) + np.sin(2 * np.pi * t / 168)
            + 0.2 * rng.standard_normal(n))


@pytest.fixture
def obs_state():
    """Reset the process-wide registry on entry (a CAMEO_OBS=1 suite run
    accumulates metrics from every preceding test) and restore the
    enabled flag on exit, so suite runs with CAMEO_OBS=1 and =0 both stay
    hermetic."""
    was = obs.enabled()
    obs.reset()
    yield OBS
    obs.reset()
    OBS.enabled = was


# ---------------------------------------------------------------------------
# StreamingHistogram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,dist", [
    (0, "lognormal"), (1, "exponential"), (2, "uniform")])
def test_histogram_quantiles_vs_numpy(seed, dist):
    rng = np.random.default_rng(seed)
    n = 5000
    if dist == "lognormal":
        v = rng.lognormal(mean=-7.0, sigma=2.0, size=n)   # latency-like
    elif dist == "exponential":
        v = rng.exponential(scale=3e-3, size=n)
    else:
        v = rng.uniform(1.0, 1e4, size=n)
    h = StreamingHistogram()
    for x in v:
        h.observe(x)
    assert h.count == n
    assert h.sum == pytest.approx(float(v.sum()))
    assert h.min == float(v.min()) and h.max == float(v.max())
    for q in (0.5, 0.95, 0.99):
        got = h.quantile(q)
        want = float(np.quantile(v, q, method="inverted_cdf"))
        # one bucket of sketch error (~4.4%) plus discretization slack
        assert got == pytest.approx(want, rel=0.06), (q, got, want)


def test_histogram_edges():
    h = StreamingHistogram()
    snap = h.snapshot()
    assert snap["count"] == 0 and math.isnan(snap["p50"])
    h.observe(float("nan"))                     # dropped, not poisoned
    assert h.count == 0
    h.observe(-2.0)
    h.observe(0.0)
    h.observe(4.0)
    assert h.count == 3 and h.min == -2.0 and h.max == 4.0
    # 2/3 of the mass is non-positive: the median resolves to the min
    assert h.quantile(0.5) == -2.0
    assert h.quantile(0.99) == pytest.approx(4.0, rel=0.05)


def test_sanitize_metric_name():
    assert sanitize_metric_name("a.b-c") == "a_b_c"
    assert sanitize_metric_name("1abc") == "_1abc"
    assert sanitize_metric_name("query.kind.sum") == "query_kind_sum"


# ---------------------------------------------------------------------------
# Disabled-path cost
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop(obs_state):
    obs.disable()
    s = obs.span("anything")
    assert s is NULL_SPAN
    with s as inner:
        assert inner is NULL_SPAN
    assert obs.snapshot()["counters"] == {}
    assert obs.snapshot()["histograms"] == {}


def test_disabled_path_microbench(obs_state):
    """The guarded call site must cost about one attribute lookup: bound
    it both relative to an unguarded pass loop and absolutely."""
    obs.disable()
    n = 100_000

    def guarded():
        t0 = time.perf_counter()
        for _ in range(n):
            if OBS.enabled:
                OBS.inc("never")
        return time.perf_counter() - t0

    def bare():
        t0 = time.perf_counter()
        for _ in range(n):
            pass
        return time.perf_counter() - t0

    g = min(guarded() for _ in range(5))
    b = min(bare() for _ in range(5))
    per_iter = g / n
    # generous bounds so a loaded CI runner can't flake: an attribute
    # lookup is ~30ns; a regression to real work (dict writes, timers)
    # costs 10-100x more than either floor
    assert per_iter < 2e-6, f"disabled guard costs {per_iter * 1e9:.0f}ns"
    assert g < 20 * max(b, 1e-9) + 1e-3
    assert obs.snapshot()["counters"] == {}


def test_disabled_span_site_microbench(obs_state):
    """A ``with obs.span(...)`` site costs one call and the shared no-op's
    enter/exit when disabled: no timer, no annotation, no allocation."""
    obs.disable()
    n = 100_000

    def site():
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("server.push"):
                pass
        return time.perf_counter() - t0

    per_iter = min(site() for _ in range(5)) / n
    assert per_iter < 2e-6, f"disabled span costs {per_iter * 1e9:.0f}ns"
    assert obs.snapshot()["histograms"] == {}


# ---------------------------------------------------------------------------
# Exposition
# ---------------------------------------------------------------------------

def test_exposition_golden():
    reg = MetricsRegistry(enabled=True)
    reg.inc("a.b", 3)
    reg.gauge("g", 2.5)
    reg.observe("h", 1.0)
    assert reg.exposition() == (
        "# TYPE cameo_a_b counter\n"
        "cameo_a_b_total 3\n"
        "# TYPE cameo_g gauge\n"
        "cameo_g 2.5\n"
        "# TYPE cameo_h summary\n"
        'cameo_h{quantile="0.5"} 1\n'
        'cameo_h{quantile="0.95"} 1\n'
        'cameo_h{quantile="0.99"} 1\n'
        "cameo_h_sum 1\n"
        "cameo_h_count 1\n")


def test_exposition_labeled_golden():
    """Labeled metrics render Prometheus-style: sorted label keys,
    escaped values, one TYPE line per metric base, and the unlabeled
    series first.  The unlabeled output above is byte-unchanged."""
    reg = MetricsRegistry(enabled=True)
    reg.inc("a.b", 3)
    reg.inc("a.b", 2, labels={"tenant": "t0"})
    reg.inc("a.b", 1, labels={"tenant": "t1", "shard": 's"x\\y'})
    reg.gauge("g", 1.5, labels={"shard": "s1"})
    reg.observe("h", 1.0, labels={"tenant": "t0"})
    assert reg.exposition() == (
        "# TYPE cameo_a_b counter\n"
        "cameo_a_b_total 3\n"
        'cameo_a_b_total{shard="s\\"x\\\\y",tenant="t1"} 1\n'
        'cameo_a_b_total{tenant="t0"} 2\n'
        "# TYPE cameo_g gauge\n"
        'cameo_g{shard="s1"} 1.5\n'
        "# TYPE cameo_h summary\n"
        'cameo_h{tenant="t0",quantile="0.5"} 1\n'
        'cameo_h{tenant="t0",quantile="0.95"} 1\n'
        'cameo_h{tenant="t0",quantile="0.99"} 1\n'
        'cameo_h_sum{tenant="t0"} 1\n'
        'cameo_h_count{tenant="t0"} 1\n')


def test_exposition_groups_type_lines_by_sanitized_base():
    """A metric name that raw-sorts *between* a base and its labeled
    keys (``a.b.c`` < ``a.b{``) must not split the base family across
    two ``# TYPE`` lines — Prometheus parsers reject the duplicate."""
    reg = MetricsRegistry(enabled=True)
    reg.inc("a.b", 1)
    reg.inc("a.b", 2, labels={"tenant": "t0"})
    reg.inc("a.b.c", 3)
    text = reg.exposition()
    types = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert len(types) == len(set(types))
    assert text == (
        "# TYPE cameo_a_b counter\n"
        "cameo_a_b_total 1\n"
        'cameo_a_b_total{tenant="t0"} 2\n'
        "# TYPE cameo_a_b_c counter\n"
        "cameo_a_b_c_total 3\n")


def test_exposition_watermark_line_only_with_jits():
    reg = MetricsRegistry(enabled=True)
    assert "recompile_watermark" not in reg.exposition()
    compress(np.asarray(_series(256)), CFG)     # ensure OBS has real jits
    assert "cameo_recompile_watermark" in OBS.exposition()


def test_registry_reset_keeps_structure():
    reg = MetricsRegistry(enabled=True)
    with pytest.raises(TypeError):
        reg.register_jit("plain", lambda: None)
    import jax
    jitted = jax.jit(lambda v: v + 1)
    reg.register_jit("plain", jitted)
    reg.inc("c")
    reg.observe("h", 1.0)
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    assert snap["recompiles"]["entries"] == {"plain": 0}   # jits survive


# ---------------------------------------------------------------------------
# Spans + events
# ---------------------------------------------------------------------------

def test_span_nesting_attrs_jsonl(obs_state, tmp_path):
    """A span opened under a profiler session lands on the trace's host
    plane by its bare name, nested by time inside the span around it, and
    records its time into the registry and on the span (a span left by an
    exception too)."""
    import jax
    from jax.profiler import ProfileData
    obs.enable()
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("stream.window") as outer:
            with obs.span("stream.window.rounds") as inner:
                time.sleep(0.002)
        with pytest.raises(ValueError):
            with obs.span("query"):
                raise ValueError("x")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {e.name: (e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in obs.SPANS}
    assert set(host) == {"stream.window", "stream.window.rounds", "query"}
    (s0, e0), (s1, e1) = host["stream.window"], host["stream.window.rounds"]
    assert s0 <= s1 < e1 <= e0
    assert e1 - s1 >= 2e6 and host["query"][0] >= e0
    snap = obs.snapshot()
    assert snap["counters"]["span.stream.window.calls"] == 1
    assert snap["counters"]["span.query.calls"] == 1
    h = snap["histograms"]["span.stream.window.rounds.seconds"]
    assert h["count"] == 1 and h["sum"] == inner.seconds >= 0.002
    assert outer.seconds >= inner.seconds


def test_spans_declared_and_opened():
    """Every span name in ``obs.SPANS`` is opened somewhere in the
    program, and every span the program opens is declared there."""
    pkg = os.path.dirname(os.path.dirname(obs.__file__))
    opened = set()
    for root, _, files in os.walk(pkg):
        if os.path.basename(root) == "obs":
            continue                 # the telemetry layer's own docstrings
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    opened |= set(re.findall(r'\bspan\(\s*"([^"]+)"',
                                             fh.read()))
    assert len(obs.SPANS) == len(set(obs.SPANS))
    assert opened == set(obs.SPANS)


def test_obs_spans_without_jax():
    """``repro.obs`` imports and times spans in a process without jax
    (the annotation falls back to a null context)."""
    src = os.path.dirname(os.path.dirname(os.path.dirname(obs.__file__)))
    code = ("import sys; sys.modules['jax'] = None\n"
            "import repro.obs as o\n"
            "o.enable()\n"
            "with o.span('query') as sp: pass\n"
            "assert o.snapshot()['counters']['span.query.calls'] == 1\n"
            "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n")
    env = dict(os.environ, PYTHONPATH=src)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=60)


# ---------------------------------------------------------------------------
# Recompile watermark + shim
# ---------------------------------------------------------------------------

def test_recompile_watermark_covers_entry_points(obs_state):
    compress(np.asarray(_series(256)), CFG)
    counts = obs.recompile_counts()
    assert "cameo.rounds" in counts
    assert obs.recompile_watermark() == sum(counts.values())
    assert counts["cameo.rounds"] >= 1
    # warm repeat: no new programs
    before = obs.recompile_watermark()
    compress(np.asarray(_series(256, seed=3)), CFG)
    assert obs.recompile_watermark() == before


def test_compile_cache_size_shim_warns(obs_state):
    from repro.core.streaming import compile_cache_size
    with pytest.warns(DeprecationWarning):
        n = compile_cache_size()
    assert n == obs.recompile_watermark()


# ---------------------------------------------------------------------------
# The observer property: identical bytes and answers with obs on vs off
# ---------------------------------------------------------------------------

def _ingest_and_query(path):
    """One full session: streamed univariate + one-shot multivariate
    ingest, then a pushdown + decode query mix.  Returns the answers."""
    import repro.api as api

    x = _series(1536, seed=11)
    X = np.stack([x, 0.5 * np.roll(x, 7) + 0.1 * _series(1536, seed=12)],
                 axis=1)
    with api.open(path, CFG, mode="w", block_len=256,
                  stream_window=256) as ds:
        with ds.stream("uni", queue_depth=2) as w:
            for lo in range(0, len(x), 613):
                w.push(x[lo:lo + 613])
        ds.write("mv", X)
    ds = api.open(path, cache_bytes=1 << 20)
    s, m = ds.series("uni"), ds.series("mv")
    out = dict(
        uni_sum=s.sum(100, 1400), uni_mean=s.mean(), uni_var=s.var(),
        uni_acf=s.acf(0, 1024), uni_win=s.window(200, 700),
        uni_win_hot=s.window(200, 700),
        mv_mean=m.mean(50, 1500), mv_win=m.window(0, 300, col=1))
    stats = ds.stats()
    ds.close()
    return out, stats


def _serve_and_query(path):
    """One served session pushed 48 points at a time, so that windows
    close inside pushes (the spans of the served path: server push, journal,
    window close, rounds, aggregates, store append), then dashboard
    queries through the server's view."""
    from repro.server.catalog import DEFAULT_TENANT
    from repro.server.ingest_server import IngestServer, ServerConfig

    x = _series(1536, seed=13)
    srv = IngestServer(path, CFG, ServerConfig(
        block_len=256, stream_window=256, auto_compact=False))
    with srv.session("feed") as sess:
        for lo in range(0, len(x), 48):
            sess.push(x[lo:lo + 48])
    s = srv.view(DEFAULT_TENANT).series("feed")
    out = dict(srv_mean=s.mean(100, 1400), srv_var=s.var(),
               srv_acf=s.acf(0, 1024), srv_win=s.window(200, 700))
    srv.close()
    return out


def test_obs_on_off_differential(obs_state, tmp_path):
    p_off, p_on = str(tmp_path / "off.cameo"), str(tmp_path / "on.cameo")
    s_off, s_on = str(tmp_path / "s_off.cameo"), str(tmp_path / "s_on.cameo")
    obs.disable()
    out_off, stats_off = _ingest_and_query(p_off)
    out_off.update(_serve_and_query(s_off))
    obs.enable()
    obs.reset()
    out_on, stats_on = _ingest_and_query(p_on)
    out_on.update(_serve_and_query(s_on))
    for off, on in ((p_off, p_on), (s_off, s_on)):
        with open(off, "rb") as f1, open(on, "rb") as f2:
            assert f1.read() == f2.read(), \
                "enabling telemetry changed the stored bytes"
    for k in out_off:
        a, b = out_off[k], out_on[k]
        if isinstance(a, tuple):
            for ai, bi in zip(a, b):
                np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # unified stats totals are telemetry-independent too (cache counters
    # differ only if instrumentation changed access patterns — they must
    # not, so compare them as well)
    assert stats_off == stats_on
    # and the enabled session actually recorded the instrumentation
    snap = obs.snapshot()
    assert snap["counters"]["stream.windows"] >= 6
    assert snap["histograms"]["stream.push_seconds"]["count"] >= 1
    # with every span of the program opened on the way
    for name in obs.SPANS:
        assert snap["counters"][f"span.{name}.calls"] >= 1, name
    assert snap["histograms"]["server.lock_wait_seconds"]["count"] == 32
    assert snap["counters"]["span.server.push.calls"] == 32


# ---------------------------------------------------------------------------
# Unified stats schema
# ---------------------------------------------------------------------------

UNIFIED_KEYS = {"series", "points", "n_kept", "stored_nbytes", "raw_nbytes",
                "point_cr", "bytes_cr", "cache"}


def test_dataset_stats_fast_matches_deep(tmp_path):
    import repro.api as api

    path = str(tmp_path / "d.cameo")
    x = _series(1024, seed=5)
    X = np.stack([x, np.roll(x, 3)], axis=1)
    with api.open(path, CFG, mode="w", block_len=256,
                  stream_window=256) as ds:
        ds.write("a", x)
        ds.write("m", X)
        with ds.stream("s") as w:          # streamed series counted too
            w.push(_series(700, seed=6))
        fast = ds.stats()
        deep = ds.stats(deep=True)
    assert UNIFIED_KEYS <= set(fast)
    assert set(fast) | {"per_series"} == set(deep)
    for k in fast:
        assert fast[k] == deep[k], k
    per = deep["per_series"]
    assert set(per) == {"a", "m", "s"}
    # the O(1) running totals agree with the exhaustive walk
    assert fast["series"] == len(per)
    assert fast["points"] == sum(p["n"] * p["channels"] for p in per.values())
    assert fast["n_kept"] == sum(
        p["n_kept"] * p["channels"] for p in per.values())
    assert fast["stored_nbytes"] == sum(
        p["stored_nbytes"] for p in per.values())
    assert fast["raw_nbytes"] == sum(p["raw_nbytes"] for p in per.values())


def test_ingest_totals_survive_reopen_and_resume(tmp_path):
    import repro.api as api

    path = str(tmp_path / "r.cameo")
    x = _series(1100, seed=9)
    ds = api.open(path, CFG, mode="w", block_len=256, stream_window=256)
    w = ds.stream("s")
    w.push(x[:600])
    ds.close()                               # mid-stream: state stashed
    ds = api.open(path, CFG, mode="a", block_len=256, stream_window=256)
    w = ds.stream("s", resume=True)
    w.push(x[w.resume_from:])
    w.close()
    fast = ds.stats()
    deep = ds.stats(deep=True)["per_series"]["s"]
    ds.close()
    assert fast["points"] == deep["n"] == 1100
    assert fast["n_kept"] == deep["n_kept"]
    assert fast["stored_nbytes"] == deep["stored_nbytes"]


def test_service_stats_superset(tmp_path):
    from repro.serving.ts_service import TimeSeriesService, TsServiceConfig

    path = str(tmp_path / "svc.cameo")
    with TimeSeriesService(path, CFG, TsServiceConfig(
            block_len=256, stream_window=256)) as svc:
        with pytest.warns(DeprecationWarning):
            svc.submit("a", _series(512, seed=1))
        svc.flush()
        st = svc.stats()
        assert UNIFIED_KEYS | {"ingested", "pending", "batches",
                               "streams"} <= set(st)
        assert st["series"] == 1 and st["ingested"] == 1
        deep = svc.stats(deep=True)
        assert set(deep["per_series"]) == {"a"}
        for k in UNIFIED_KEYS - {"cache"}:
            assert st[k] == deep[k], k


# ---------------------------------------------------------------------------
# Acceptance: the end-to-end snapshot
# ---------------------------------------------------------------------------

def test_acceptance_snapshot(obs_state, tmp_path):
    """Streamed multivariate ingest + a pushdown query session must light
    up every pillar of the snapshot: push-latency quantiles, window and
    queue counters, the recompile watermark, cache hit rates, and the
    realized pushdown bound widths."""
    import repro.api as api

    obs.enable()
    obs.reset()
    path = str(tmp_path / "acc.cameo")
    rng = np.random.default_rng(21)
    n, C = 1500, 3                           # 5 full windows + a padded tail
    base = _series(n, seed=21)
    X = np.stack([base] + [
        (0.7 + 0.1 * c) * np.roll(base, 5 * c)
        + 0.05 * rng.standard_normal(n) for c in range(1, C)], axis=1)
    with api.open(path, CFG, mode="w", block_len=256,
                  stream_window=256) as ds:
        with ds.stream("rack", channels=C, queue_depth=2) as w:
            for lo in range(0, n, 521):
                w.push(X[lo:lo + 521])
    ds = api.open(path, cache_bytes=1 << 20)
    s = ds.series("rack")
    s.mean(100, 1400)
    s.acf(0, 1024)
    s.window(200, 600)
    s.window(200, 600)                       # hot decode: cache hit
    stats = ds.stats()
    ds.close()

    snap = obs.snapshot()
    c, h = snap["counters"], snap["histograms"]
    push = h["stream.push_seconds"]
    assert push["count"] == 3 and push["p50"] > 0 and push["p95"] > 0
    assert c["stream.windows"] == 6          # 5 full + 1 padded tail
    assert c["stream.pad_to_bucket_hits"] >= 1
    assert c["stream.queue_drains"] >= 1
    assert h["stream.window_eps_headroom"]["max"] <= 1.0 + 1e-9
    assert snap["recompiles"]["total"] >= 1
    assert {"cameo.rounds", "cameo.sequential", "cameo.mvar_reconstruct",
            "store.reconstruct"} <= set(snap["recompiles"]["entries"])
    assert c["store.cache.hits"] >= 1
    assert c["query.count"] == 2             # mean + acf pushdowns
    assert h["query.bound_width"]["count"] == 2
    assert np.isfinite(h["query.bound_width"]["max"])
    assert c["query.segments_meta"] >= 1
    # the unified stats view agrees with the ingest
    assert stats["series"] == 1 and stats["points"] == n * C
    # and the whole registry round-trips through the text exposition
    text = obs.exposition()
    assert "cameo_stream_windows_total 6" in text
    assert 'cameo_stream_push_seconds{quantile="0.5"}' in text
