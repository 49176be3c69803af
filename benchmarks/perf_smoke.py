"""CI perf-smoke gate for the CameoStore read path.

Runs a small synthetic fixture (seconds, not minutes) and compares
**relative** performance metrics against the committed repo-root
``BENCH_store.json`` baseline:

* vectorized-vs-loop decode speedup (gorilla / chimp value streams and the
  dod index stream),
* warm pushdown-aggregate latency vs a decode-and-aggregate scan, and
* the streaming-ingest rows: streamed-session append throughput vs the
  one-shot ``append_series`` of the same kept set (store-side only, no
  compressor — same regime on both sides, so the ratio is stable) and the
  O(window) memory ratio (raw streamed bytes over the session's peak
  python-heap working set — a collapse toward 1 means the stream started
  buffering the whole series), and
* the multivariate rows: the shared-index byte gain of one v4 store vs C
  standalone per-column stores, and the warm all-columns pushdown vs a
  decode-and-scan, and
* ``obs_overhead``: streamed compressor ingest with the ``repro.obs``
  telemetry registry enabled vs disabled — gated as an **absolute** floor
  (``CAMEO_OBS_OVERHEAD_FLOOR``, default 0.97: enabled must stay within
  3% of disabled), since the telemetry contract is machine-independent, and
* ``wal_overhead``: façade streamed ingest with the write-ahead journal
  on (default group commit) vs off — also an **absolute** floor
  (``CAMEO_WAL_OVERHEAD_FLOOR``, default 0.90: journaled ingest must stay
  within ~10% of journal-off), and
* the ingest-server rows: ``compaction_gain`` (stored bytes of small
  sealed blocks before / after the maintenance rewrite — an absolute
  floor, ``CAMEO_COMPACTION_GAIN_FLOOR`` default 1.05) and
  ``tier_hit_ratio`` (hot-tier LRU hit fraction of a repeated pushdown
  workload — an absolute floor, ``CAMEO_TIER_HIT_RATIO_FLOOR`` default
  0.90); both are deterministic counter/byte ratios, machine-independent.

Metrics present in only one of {baseline, current} are *skipped with a
note*, not failed — new rows land in the same PR as their code and are
gated once ``--write-baseline`` re-pins the ledger.

Only ratios are gated: numerator and denominator run back-to-back on the
same machine, so a >25% drop against the committed ratio signals a real
code regression rather than runner noise.  Absolute throughputs are
printed for the log but not gated.  The ratios do lean on interpreter
speed (the loop oracles are pure Python), so a CPython/numpy upgrade that
legitimately shifts them is handled by re-pinning: re-run with
``--write-baseline`` on the new toolchain and commit the result.  The
tolerance is overridable for such transitions via
``CAMEO_PERF_SMOKE_TOLERANCE`` (default 0.75 = fail below 75% of the
committed ratio).

    PYTHONPATH=src python -m benchmarks.perf_smoke                  # gate
    PYTHONPATH=src python -m benchmarks.perf_smoke --write-baseline # pin

``--write-baseline`` stores this machine's fixture numbers under
``smoke_baseline`` in BENCH_store.json; commit the result when the read
path is deliberately re-tuned.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)  # float64 store fixture

from benchmarks.common import best_of, geomean  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(REPO_ROOT, "BENCH_store.json")
TOLERANCE = float(os.environ.get("CAMEO_PERF_SMOKE_TOLERANCE", "0.75"))
# pushdown_warm divides a ~30us pure-Python path by a ms-scale
# jit+IO path — that mixed-regime ratio swings ~2-3x across machines and
# load, unlike the decode ratios whose two sides share a regime.  A real
# cache regression (warm falling back to edge decode) costs ~50-100x, so a
# much looser floor still catches it without red-flagging clean CI runs.
# stream_append_ratio mixes block writes with footer bookkeeping on one
# side only, so it also gets a looser floor; stream_mem_ratio collapses
# ~100x when O(window) state regresses to O(n) buffering, so 0.5 is ample.
# mvar_pushdown_speedup shares pushdown_warm_speedup's mixed-regime noise;
# mvar_shared_gain is a pure byte ratio (deterministic fixture) — a drop
# means the shared-index layout itself regressed, so it gets a tight floor.
PER_METRIC_TOLERANCE = {"pushdown_warm_speedup": 0.30,
                        "stream_append_ratio": 0.50,
                        "stream_mem_ratio": 0.50,
                        "mvar_pushdown_speedup": 0.30,
                        "mvar_shared_gain": 0.90,
                        # absolute pts/s, compared against the committed
                        # `stream_baseline` bench geomean — unlike the
                        # ratios above it moves with runner hardware, so
                        # the floor only catches order-of-regression
                        # events (a cold-dispatch or recompile-per-window
                        # regression costs 3-20x, well below 0.30)
                        "stream_pts_per_s": 0.30}
# obs_overhead is the telemetry-enabled/disabled ingest time ratio; it is
# gated as an *absolute* floor (enabled ingest must stay within ~3% of
# disabled), not relative to the committed baseline — the contract is
# "telemetry is nearly free", not "as cheap as last time".
OBS_OVERHEAD_FLOOR = float(os.environ.get("CAMEO_OBS_OVERHEAD_FLOOR", "0.97"))
# wal_overhead is the journal-off/journal-on façade ingest time ratio,
# also gated as an *absolute* floor: group commit must amortize the
# write-ahead journal to within ~10% of journal-off ingest (0.90 floor),
# or the durability default is too expensive to leave on.
WAL_OVERHEAD_FLOOR = float(os.environ.get("CAMEO_WAL_OVERHEAD_FLOOR", "0.90"))
# compaction_gain is the stored-bytes ratio of small sealed blocks before
# vs after the maintenance rewrite on a deterministic synthetic fixture —
# a pure byte ratio, machine-independent, gated as an absolute floor: the
# seal-small-then-compact policy must reclaim at least ~5% or compaction
# stopped merging.
COMPACTION_GAIN_FLOOR = float(
    os.environ.get("CAMEO_COMPACTION_GAIN_FLOOR", "1.05"))
# tier_hit_ratio is the decoded-block LRU hit fraction of a repeated
# pushdown workload after one warm-up pass — also an absolute floor: a
# collapse means hot-tier reads fell back to re-decoding per query.
TIER_HIT_RATIO_FLOOR = float(
    os.environ.get("CAMEO_TIER_HIT_RATIO_FLOOR", "0.90"))
# round_body_eqns counts equations in the *lowered* rounds-mode round body
# (the while-loop the compressor spends its life in) and is gated as an
# absolute ceiling: op count is machine-independent, and on CPU the round
# body is dispatch-bound, so an accidental return to unrolled per-lag
# chains shows up here as hundreds of extra equations long before any
# timing gate would notice.  The matmul-shaped body traces at ~590 eqns;
# the ceiling leaves headroom for routine maintenance but sits far below
# the ~2700 of the historical per-lag swarm.
ROUND_BODY_EQN_CEILING = int(
    os.environ.get("CAMEO_ROUND_BODY_EQN_CEILING", "750"))
_N = 16384
_STREAM_N = 262144


def _best_of(fn, *args, reps=5):
    return best_of(fn, *args, reps=reps)[1]


class _FakeResult:
    """Minimal CompressResult stand-in so the fixture skips the compressor
    (the smoke gate measures the *store*, not CAMEO itself)."""

    def __init__(self, x, kept):
        self.kept = kept
        self.xr = x
        self.n_kept = int(kept.sum())
        self.deviation = 0.0


def _fixture():
    rng = np.random.default_rng(7)
    t = np.arange(_N)
    x = (np.sin(2 * np.pi * t / 96) + 0.4 * np.sin(2 * np.pi * t / 17)
         + 0.05 * rng.standard_normal(_N))
    kept = np.zeros(_N, bool)
    kept[::5] = True                       # unit-ish strides
    kept[rng.choice(_N, _N // 20, replace=False)] = True   # jitter
    kept[0] = kept[-1] = True
    return x, kept


def run(write_baseline: bool) -> int:
    if write_baseline:
        # pin conservatively: the minimum of three passes, so the gate's
        # floor sits below ordinary machine-state drift
        passes = [_measure() for _ in range(3)]
        return _write({k: min(p[k] for p in passes) for k in passes[0]})
    # gate on the best of three passes: a loaded runner depresses the
    # loop-vs-vec ratio (the two sides respond differently to contention),
    # and a single contaminated pass must not red-flag clean code
    passes = [_measure() for _ in range(3)]
    return _gate({k: max(p[k] for p in passes) for k in passes[0]})


def _measure() -> dict:
    from repro.core.cameo import CameoConfig
    from repro.store import codec as store_codec
    from repro.store import query as squery
    from repro.store.store import CameoStore

    x, kept = _fixture()
    kept_idx = np.nonzero(kept)[0].astype(np.int64)
    metrics = {}

    value_speedups = []
    for name in ("gorilla", "chimp"):
        enc = store_codec.VALUE_ENCODERS[name](x)
        loop_s = _best_of(store_codec.VALUE_DECODERS_LOOP[name], enc, _N)
        vec_s = _best_of(store_codec.VALUE_DECODERS[name], enc, _N)
        value_speedups.append(loop_s / max(vec_s, 1e-12))
        print(f"{name}: loop {loop_s * 1e3:.2f}ms vec {vec_s * 1e3:.2f}ms "
              f"-> {value_speedups[-1]:.1f}x "
              f"({8.0 * _N / vec_s / 1e6:.0f} MB/s)")
    # gate on the geomean: per-codec ratios are noisier than the pair
    metrics["value_decode_speedup"] = geomean(value_speedups)
    # a dedicated large index stream: the store fixture's kept set is only
    # a few thousand records, whose ~0.1 ms vectorized decode is too noisy
    # to gate on
    rng = np.random.default_rng(11)
    big_idx = np.flatnonzero(rng.random(1_000_000) < 0.15).astype(np.int64)
    enc = store_codec.encode_indices(big_idx)
    loop_s = _best_of(store_codec.decode_indices_loop, enc, len(big_idx),
                      reps=3)
    vec_s = _best_of(store_codec.decode_indices, enc, len(big_idx))
    metrics["index_decode_speedup"] = loop_s / max(vec_s, 1e-12)
    print(f"index: n={len(big_idx)} loop {loop_s * 1e3:.2f}ms vec "
          f"{vec_s * 1e3:.2f}ms "
          f"-> {metrics['index_decode_speedup']:.1f}x")

    cfg = CameoConfig(eps=1e-2, lags=24, mode="rounds", dtype="float64")
    import tempfile
    with tempfile.TemporaryDirectory() as tmpdir:
        path = os.path.join(tmpdir, "smoke.cameo")
        with CameoStore.create(path, block_len=1024) as w:
            w.append_series("s", _FakeResult(x, kept), cfg, x=x)
        store = CameoStore.open(path)
        a, b = _N // 8, _N // 8 + _N // 2
        squery.window_mean(store, "s", a, b)          # warm the caches
        warm_s = _best_of(squery.window_mean, store, "s", a, b, reps=9)
        scan = CameoStore.open(path, cache_bytes=0)
        scan.read_window("s", a, b)                   # warm header cache only
        scan_s = _best_of(lambda: scan.read_window("s", a, b).mean())
    metrics["pushdown_warm_speedup"] = scan_s / max(warm_s, 1e-12)
    print(f"pushdown: warm {warm_s * 1e6:.0f}us vs scan "
          f"{scan_s * 1e6:.0f}us -> "
          f"{metrics['pushdown_warm_speedup']:.1f}x")
    metrics.update(_measure_stream(cfg))
    metrics.update(_measure_stream_compress())
    metrics.update(_measure_wal_overhead())
    metrics.update(_measure_mvar(cfg))
    metrics.update(_measure_serve(cfg))
    metrics.update(_measure_opcount())
    return metrics


def _measure_serve(cfg) -> dict:
    """Ingest-server fixture: one tenant streams the smoke series through
    a seal-small session, then compaction merges the small blocks and a
    repeated pushdown workload exercises the hot tier.  Both metrics are
    deterministic (byte and counter ratios), gated as absolute floors."""
    import tempfile

    from repro.server import IngestServer, ServerConfig

    x, _ = _fixture()
    chunk = 731
    with tempfile.TemporaryDirectory() as tmpdir:
        path = os.path.join(tmpdir, "serve.cameo")
        srv = IngestServer(path, cfg, ServerConfig(
            block_len=4096, seal_block_len=256, stream_window=1024,
            auto_compact=False, wal=False))
        srv.register_tenant("t0")
        with srv.session("s", tenant="t0") as sess:
            for lo in range(0, _N, chunk):
                sess.push(x[lo:lo + chunk])
        before = srv.catalog.usage("t0")["stored_nbytes"]
        rep = srv.compact("s", tenant="t0")
        after = srv.catalog.usage("t0")["stored_nbytes"]
        gain = before / max(after, 1)
        a, b = _N // 8, _N // 8 + _N // 2
        view = srv.view("t0")
        view.series("s").mean(a, b)                     # warm-up decode
        cs0 = srv.store.cache_stats()
        for _ in range(32):
            view.series("s").mean(a, b)
        cs1 = srv.store.cache_stats()
        dh = cs1["hits"] - cs0["hits"]
        dm = cs1["misses"] - cs0["misses"]
        ratio = dh / max(dh + dm, 1)
        srv.close()
    print(f"serve: compaction {rep['blocks_before']}->"
          f"{rep['blocks_after']} blocks, bytes {before}->{after} "
          f"(gain {gain:.2f}x), tier hit ratio {ratio:.3f}")
    return {"compaction_gain": gain, "tier_hit_ratio": ratio}


def _count_eqns(jaxpr) -> int:
    """Total equations in a jaxpr including every sub-jaxpr (cond branches,
    nested loops, pjit bodies)."""
    total = 0
    for eq in jaxpr.eqns:
        total += 1
        for v in eq.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                total += _count_eqns(inner)
            elif inner is not None and hasattr(getattr(inner, "jaxpr", None),
                                               "eqns"):
                total += _count_eqns(inner.jaxpr)
    return total


def _find_whiles(jaxpr, out):
    """Collect every `while` equation, recursing into sub-jaxprs (the
    rounds loop nests inside a pjit equation when traced under jit)."""
    for eq in jaxpr.eqns:
        if eq.primitive.name == "while":
            out.append(eq)
        for v in eq.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                _find_whiles(inner, out)
            elif inner is not None and hasattr(getattr(inner, "jaxpr", None),
                                               "eqns"):
                _find_whiles(inner.jaxpr, out)
    return out


def _measure_opcount() -> dict:
    """Equation count of the lowered rounds-mode round body (the body
    jaxpr of the outermost while loop in ``_rounds_padded``) at the stream
    bench's shape (n=1024, L=24).  Deterministic — no timing involved."""
    import jax.numpy as jnp

    from repro.core.cameo import CameoConfig, _rounds_padded

    cfg = CameoConfig(eps=1e-2, lags=24, mode="rounds", max_rounds=120,
                      dtype="float64")
    n = 1024
    x = jnp.zeros((n,), jnp.float64)
    closed = jax.make_jaxpr(lambda xp: _rounds_padded(
        xp, jnp.asarray(n), jnp.asarray(2), jnp.asarray(cfg.eps), cfg))(x)
    whiles = _find_whiles(closed.jaxpr, [])
    assert whiles, "no while loop found in the lowered rounds program"
    body = whiles[0].params["body_jaxpr"].jaxpr
    eqns = _count_eqns(body)
    print(f"round body: {eqns} lowered eqns "
          f"(ceiling {ROUND_BODY_EQN_CEILING})")
    return {"round_body_eqns": float(eqns)}


def _measure_stream_compress() -> dict:
    """Compressor-in-the-loop streamed ingest at the `stream` bench's
    per-window workload (window 1024, eps 1e-2, L=24, rounds cap 120 on
    the pedestrian series), so ``stream_pts_per_s`` is directly comparable
    to the committed ``stream_baseline`` geomean.  Also the no-recompile
    check: after the warm pass, further ingests — including the padded
    tail window, whose length differs from the bucket — must not grow the
    jit cache."""
    import tempfile

    from repro import obs
    from repro.core.cameo import CameoConfig
    from repro.core.streaming import StreamingCompressor
    from repro.data.synthetic import make_dataset
    from repro.store.store import CameoStore

    cfg = CameoConfig(eps=1e-2, lags=24, mode="rounds", max_rounds=120,
                      dtype="float64")
    wlen = 1024
    n = 4 * wlen + 520                 # 4 full windows + a padded tail
    x = np.asarray(make_dataset("pedestrian"), np.float64)[:n]

    def ingest(path):
        # wal off: this row gates the *telemetry* contract at 3%, and the
        # journal's footer-checkpoint fsyncs add millisecond-scale jitter
        # that would swamp it (durability cost has its own absolute gate,
        # ``wal_overhead``)
        sc = StreamingCompressor(cfg, wlen)
        with CameoStore.create(path, block_len=1024, wal=False) as store:
            sess = store.open_stream("s", cfg)
            for lo in range(0, n, 731):
                for w in sc.push(x[lo:lo + 731]):
                    sess.append_window(w)
            for w in sc.finish():
                sess.append_window(w)
            sess.close(deviation=sc.deviation())

    was_enabled = obs.enabled()
    obs.disable()
    fsync_prev = os.environ.get("CAMEO_FSYNC")
    os.environ["CAMEO_FSYNC"] = "0"   # same jitter argument as wal=False
    try:
        with tempfile.TemporaryDirectory() as tmp:
            ingest(os.path.join(tmp, "warm.cameo"))    # compile both buckets
            cache_n = obs.recompile_watermark()
            best = min(_best_of(ingest, os.path.join(tmp, f"t{i}.cameo"),
                                reps=1) for i in range(3))
            recompiles = obs.recompile_watermark() - cache_n
            # telemetry-enabled pass over the identical workload: the
            # one-attribute-lookup guards plus per-push/per-window
            # observations must cost a few percent at most
            obs.enable()
            obs.reset()
            best_on = min(_best_of(ingest, os.path.join(tmp, f"o{i}.cameo"),
                                   reps=1) for i in range(3))
    finally:
        if fsync_prev is None:
            os.environ.pop("CAMEO_FSYNC", None)
        else:
            os.environ["CAMEO_FSYNC"] = fsync_prev
        obs.enable() if was_enabled else obs.disable()
    assert not recompiles, \
        f"streamed ingest retraced {recompiles} program(s) after warmup — " \
        "the padded tail must reuse the full-window bucket"
    pts = n / max(best, 1e-12)
    overhead = best / max(best_on, 1e-12)
    print(f"stream compress: {best * 1e3:.0f}ms for {n} pts -> "
          f"{pts:.0f} pts/s (recompiles=0); obs-enabled "
          f"{best_on * 1e3:.0f}ms -> overhead ratio {overhead:.3f}")
    return {"stream_pts_per_s": pts, "obs_overhead": overhead}


def _measure_wal_overhead() -> dict:
    """Façade streamed ingest with the write-ahead journal on (default
    group-commit policy) vs off (``wal=False``) over the identical
    workload as ``_measure_stream_compress``.  The ratio off/on is gated
    as an absolute floor (``WAL_OVERHEAD_FLOOR``): group commit must keep
    acked-push durability within ~10% of journal-off ingest."""
    import tempfile

    from repro import api
    from repro.core.cameo import CameoConfig
    from repro.data.synthetic import make_dataset

    cfg = CameoConfig(eps=1e-2, lags=24, mode="rounds", max_rounds=120,
                      dtype="float64")
    wlen = 1024
    n = 4 * wlen + 520
    x = np.asarray(make_dataset("pedestrian"), np.float64)[:n]

    def ingest(path, use_wal):
        ds = api.open(path, cfg, block_len=1024, stream_window=wlen,
                      wal=use_wal)
        w = ds.stream("s")
        for lo in range(0, n, 731):
            w.push(x[lo:lo + 731])
        w.close()
        ds.close()

    with tempfile.TemporaryDirectory() as tmp:
        ingest(os.path.join(tmp, "warm.cameo"), True)    # compile buckets
        best_on = min(_best_of(ingest, os.path.join(tmp, f"on{i}.cameo"),
                               True, reps=1) for i in range(3))
        best_off = min(_best_of(ingest, os.path.join(tmp, f"off{i}.cameo"),
                                False, reps=1) for i in range(3))
    ratio = best_off / max(best_on, 1e-12)
    print(f"wal overhead: journal-off {best_off * 1e3:.0f}ms journal-on "
          f"{best_on * 1e3:.0f}ms -> ratio {ratio:.3f}")
    return {"wal_overhead": ratio}


def _measure_mvar(cfg) -> dict:
    """Store-side multivariate rows (no compressor): a correlated C-column
    fixture with precomputed per-column kept masks, appended once as a
    shared-index v4 series and once as C standalone univariate stores.
    ``mvar_shared_gain`` is the byte ratio (one index stream vs C), and
    ``mvar_pushdown_speedup`` the warm all-columns metadata query vs a
    decode-and-scan."""
    import tempfile

    from repro.store import query as squery
    from repro.store.store import CameoStore

    rng = np.random.default_rng(23)
    n, C = _N, 4
    t = np.arange(n)
    base = (np.sin(2 * np.pi * t / 96) + 0.4 * np.sin(2 * np.pi * t / 17)
            + 0.05 * rng.standard_normal(n))
    X = np.stack([base] + [
        (0.6 + 0.1 * c) * np.roll(base, 5 * c)
        + 0.02 * rng.standard_normal(n) for c in range(1, C)], axis=1)
    # highly-overlapping per-column masks (correlated sensors): a shared
    # stride-5 grid plus small per-column jitter
    masks = []
    for c in range(C):
        kept = np.zeros(n, bool)
        kept[::5] = True
        kept[rng.choice(n, n // 50, replace=False)] = True
        kept[0] = kept[-1] = True
        masks.append(kept)
    union = np.logical_or.reduce(masks)

    metrics = {}
    with tempfile.TemporaryDirectory() as tmp:
        pm = os.path.join(tmp, "mv.cameo")
        with CameoStore.create(pm, block_len=1024) as w:
            w.append_series("m", _FakeResult(X, union), cfg, x=X)
        mv_bytes = os.path.getsize(pm)
        percol_bytes = 0
        for c in range(C):
            pc = os.path.join(tmp, f"c{c}.cameo")
            with CameoStore.create(pc, block_len=1024) as w:
                w.append_series("s", _FakeResult(
                    np.ascontiguousarray(X[:, c]), masks[c]), cfg,
                    x=X[:, c])
            percol_bytes += os.path.getsize(pc)
        store = CameoStore.open(pm)
        a, b = n // 8, n // 8 + n // 2
        squery.query(store, "m", "mean", a, b)          # warm
        warm_s = _best_of(squery.query, store, "m", "mean", a, b, reps=9)
        scan = CameoStore.open(pm, cache_bytes=0)
        scan.read_window("m", a, b)                     # warm header cache
        scan_s = _best_of(lambda: scan.read_window("m", a, b).mean(axis=0))
        store.close()   # release mmaps before the tempdir is removed
        scan.close()
    metrics["mvar_shared_gain"] = percol_bytes / max(mv_bytes, 1)
    metrics["mvar_pushdown_speedup"] = scan_s / max(warm_s, 1e-12)
    print(f"mvar: shared {mv_bytes}B vs per-col {percol_bytes}B -> "
          f"{metrics['mvar_shared_gain']:.2f}x; pushdown warm "
          f"{warm_s * 1e6:.0f}us vs scan {scan_s * 1e6:.0f}us -> "
          f"{metrics['mvar_pushdown_speedup']:.1f}x")
    return metrics


def _measure_stream(cfg) -> dict:
    """Store-side streaming rows: a long precomputed kept set appended
    window-at-a-time through ``open_stream`` vs one-shot ``append_series``
    (byte-identity asserted), plus the O(window) peak-heap ratio."""
    import tempfile
    import tracemalloc

    from repro.store.store import CameoStore

    rng = np.random.default_rng(17)
    n, wlen = _STREAM_N, 4096
    t = np.arange(n)
    x = (np.sin(2 * np.pi * t / 96) + 0.4 * np.sin(2 * np.pi * t / 17)
         + 0.05 * rng.standard_normal(n))
    kept = np.zeros(n, bool)
    kept[::6] = True
    kept[rng.choice(n, n // 24, replace=False)] = True
    kept[0] = kept[-1] = True

    def stream_ingest(path):
        with CameoStore.create(path, block_len=1024) as store:
            sess = store.open_stream("s", cfg)
            for lo in range(0, n, wlen):
                w = slice(lo, min(lo + wlen, n))
                sess.append(lo, x[w], kept[w])
            sess.close()

    def oneshot_ingest(path):
        with CameoStore.create(path, block_len=1024) as store:
            store.append_series("s", _FakeResult(x, kept), cfg, x=x)

    metrics = {}
    with tempfile.TemporaryDirectory() as tmp:
        p1 = os.path.join(tmp, "one.cameo")
        p2 = os.path.join(tmp, "str.cameo")
        one_s = _best_of(oneshot_ingest, p1, reps=3)
        stream_s = _best_of(stream_ingest, p2, reps=3)
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            assert f1.read() == f2.read(), \
                "streamed store bytes diverged from the one-shot path"
        tracemalloc.start()
        base = tracemalloc.get_traced_memory()[0]
        stream_ingest(p2)
        peak = max(tracemalloc.get_traced_memory()[1] - base, 1)
        tracemalloc.stop()
    metrics["stream_append_ratio"] = one_s / max(stream_s, 1e-12)
    metrics["stream_mem_ratio"] = 8.0 * n / peak
    print(f"stream: oneshot {one_s * 1e3:.1f}ms streamed "
          f"{stream_s * 1e3:.1f}ms -> {metrics['stream_append_ratio']:.2f}x; "
          f"peak heap {peak} vs raw {8 * n} -> "
          f"{metrics['stream_mem_ratio']:.1f}x")
    return metrics


def _load_ledger() -> dict:
    """Missing ledger -> fresh dict (bootstrap); present-but-unreadable ->
    raise, mirroring cameo_suite._update_bench_store_json, so a bad merge
    can't be silently clobbered by a well-meaning --write-baseline."""
    if not os.path.exists(BENCH_JSON):
        return {"schema": 1, "baseline": None, "runs": []}
    with open(BENCH_JSON) as f:
        try:
            return json.load(f)
        except ValueError as e:
            raise IOError(
                f"{BENCH_JSON} is unreadable ({e}); restore it from git "
                "before re-pinning any baseline") from e


def _write(metrics: dict) -> int:
    from repro.store import _scan

    ledger = _load_ledger()
    ledger["smoke_baseline"] = dict(metrics, native_scan=bool(_scan.NATIVE))
    with open(BENCH_JSON, "w") as f:
        json.dump(ledger, f, indent=1, default=float)
    print(f"wrote smoke_baseline to {BENCH_JSON}")
    return 0


def _gate(metrics: dict) -> int:
    from repro.store import _scan

    ledger = _load_ledger()
    baseline = dict(ledger.get("smoke_baseline") or {})
    if not baseline:
        print("no smoke_baseline in BENCH_store.json — run with "
              "--write-baseline and commit it", file=sys.stderr)
        return 1
    base_native = baseline.pop("native_scan", None)
    baseline.pop("obs_overhead", None)       # gated absolutely below
    baseline.pop("wal_overhead", None)       # gated absolutely below
    baseline.pop("round_body_eqns", None)    # gated absolutely below
    baseline.pop("compaction_gain", None)    # gated absolutely below
    baseline.pop("tier_hit_ratio", None)     # gated absolutely below
    if base_native and not _scan.NATIVE:
        print("perf-smoke FAILED: the committed baseline was pinned with "
              "the native C scanner, but this environment has none (no "
              "working `cc`, or the compile failed) — the ratios below "
              "would reflect the pure-Python fallback, not a store-code "
              "regression.  Install a C compiler on the runner, or re-pin "
              "with --write-baseline if the fallback is the intended "
              "configuration.", file=sys.stderr)
        return 1
    failures = []
    for key, base in baseline.items():
        cur = metrics.get(key)
        if cur is None:
            # a committed baseline row this build doesn't measure (section
            # removed/renamed): skip with a note — re-pin to clean it up
            print(f"{key}: baseline {base:.1f}x but no current "
                  "measurement — SKIPPED (re-pin with --write-baseline)")
            continue
        floor = PER_METRIC_TOLERANCE.get(key, TOLERANCE) * base
        status = "ok" if cur >= floor else "REGRESSED"
        print(f"{key}: current {cur:.1f}x vs baseline {base:.1f}x "
              f"(floor {floor:.1f}x) {status}")
        if cur < floor:
            failures.append(key)
    for key in sorted(set(metrics) - set(baseline)
                      - {"obs_overhead", "wal_overhead", "round_body_eqns",
                         "compaction_gain", "tier_hit_ratio"}):
        # a freshly added row whose baseline section hasn't been pinned
        # yet: new rows must be able to land in the same PR as their code,
        # so this is a skip, not a failure
        print(f"{key}: current {metrics[key]:.1f}x has no committed "
              "baseline — SKIPPED (pin with --write-baseline to gate it)")
    # the ingest-throughput floor gates against the `stream` bench's own
    # re-pinned ledger entry (same per-window workload), independent of
    # whether stream_pts_per_s has been pinned into smoke_baseline yet
    sb = dict(ledger.get("stream_baseline") or {})
    cur = metrics.get("stream_pts_per_s")
    if sb.get("timing") == "warm" and cur is not None:
        base = float(sb["pts_per_s_geomean"])
        floor = PER_METRIC_TOLERANCE["stream_pts_per_s"] * base
        status = "ok" if cur >= floor else "REGRESSED"
        print(f"stream_pts_per_s: current {cur:.0f} vs stream_baseline "
              f"{base:.0f} (floor {floor:.0f}) {status}")
        if cur < floor:
            failures.append("stream_pts_per_s")
    elif cur is not None:
        print("stream_pts_per_s: no warm stream_baseline in the ledger — "
              "SKIPPED (run `python -m benchmarks.run --only stream` and "
              "commit BENCH_store.json)")
    # telemetry overhead is an absolute contract, not a baseline ratio:
    # ingest with CAMEO_OBS on must stay within (1 - floor) of disabled
    cur = metrics.get("obs_overhead")
    if cur is not None:
        status = "ok" if cur >= OBS_OVERHEAD_FLOOR else "REGRESSED"
        print(f"obs_overhead: disabled/enabled ingest ratio {cur:.3f} "
              f"(floor {OBS_OVERHEAD_FLOOR:.2f}) {status}")
        if cur < OBS_OVERHEAD_FLOOR:
            failures.append("obs_overhead")
    # journal overhead is likewise an absolute contract: default-on
    # durability must cost <= ~10% over journal-off ingest
    cur = metrics.get("wal_overhead")
    if cur is not None:
        status = "ok" if cur >= WAL_OVERHEAD_FLOOR else "REGRESSED"
        print(f"wal_overhead: journal-off/on ingest ratio {cur:.3f} "
              f"(floor {WAL_OVERHEAD_FLOOR:.2f}) {status}")
        if cur < WAL_OVERHEAD_FLOOR:
            failures.append("wal_overhead")
    # compaction must reclaim the seal-small overhead: a deterministic
    # byte ratio on a fixed fixture, gated as an absolute floor
    cur = metrics.get("compaction_gain")
    if cur is not None:
        status = "ok" if cur >= COMPACTION_GAIN_FLOOR else "REGRESSED"
        print(f"compaction_gain: stored before/after ratio {cur:.3f} "
              f"(floor {COMPACTION_GAIN_FLOOR:.2f}) {status}")
        if cur < COMPACTION_GAIN_FLOOR:
            failures.append("compaction_gain")
    # the hot tier must actually serve repeated pushdowns from the LRU
    cur = metrics.get("tier_hit_ratio")
    if cur is not None:
        status = "ok" if cur >= TIER_HIT_RATIO_FLOOR else "REGRESSED"
        print(f"tier_hit_ratio: hot-tier hit fraction {cur:.3f} "
              f"(floor {TIER_HIT_RATIO_FLOOR:.2f}) {status}")
        if cur < TIER_HIT_RATIO_FLOOR:
            failures.append("tier_hit_ratio")
    # the round-body op count is a deterministic absolute ceiling: a
    # failure means the round body regrew per-lag unrolled chains
    cur = metrics.get("round_body_eqns")
    if cur is not None:
        status = "ok" if cur <= ROUND_BODY_EQN_CEILING else "REGRESSED"
        print(f"round_body_eqns: {cur:.0f} "
              f"(ceiling {ROUND_BODY_EQN_CEILING}) {status}")
        if cur > ROUND_BODY_EQN_CEILING:
            failures.append("round_body_eqns")
    if failures:
        print(f"perf-smoke FAILED: {failures} regressed more than "
              f"{(1 - TOLERANCE) * 100:.0f}% vs the committed "
              "BENCH_store.json baseline.  If this is a real store-code "
              "regression, fix it; if the toolchain changed (new "
              "CPython/numpy shifts the loop-oracle ratios), re-pin with "
              "`python -m benchmarks.perf_smoke --write-baseline` and "
              "commit the ledger.", file=sys.stderr)
        return 1
    print("perf-smoke OK")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-baseline", action="store_true",
                    help="pin this machine's fixture numbers as the "
                         "committed smoke baseline")
    args = ap.parse_args()
    sys.exit(run(args.write_baseline))


if __name__ == "__main__":
    main()
