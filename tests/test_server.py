"""Multi-tenant ingest server (``repro.server``) — contract tests.

What is pinned here:

* **concurrent differential**: N producer threads feeding N tenants
  through one server produce per-series block bodies and catalog entries
  identical to N serial single-tenant runs — before *and* after
  background compaction;
* **crash recovery with active sessions**: a kill-anywhere crash image
  of a server with open tenant sessions replays every acked push on
  ``resume=True``, per tenant;
* **compaction**: merging runs of small streamed blocks preserves
  windows and kept points bit-exactly, keeps aggregate answers within
  their bounds, and a crash at *any byte offset* of the rewrite rolls
  back (or forward) to a consistent footer — never torn state;
* **tiers**: demoting a series cold (entropy-wrapped bodies) and
  promoting it back is answer-invariant; pin/prefetch and the per-tier
  hit/byte counters behave;
* **admission / quotas**: ``backpressure="reject"`` raises
  :class:`ServerBusy` when slots run out; a tenant's ``max_points``
  quota refuses the push *before* it is journaled/acked;
* **tenant catalog**: registration persists across close/reopen, tenant
  ε overrides are honored, and the default tenant is exactly the legacy
  unprefixed view;
* **/metrics**: the WSGI hook serves the obs exposition with per-tenant
  labeled counters.
"""
import os
import shutil
import threading

import numpy as np
import pytest

from repro.core.cameo import CameoConfig
from repro.server import (
    DEFAULT_TENANT,
    IngestServer,
    QuotaExceeded,
    ServerBusy,
    ServerConfig,
    tenant_sid,
)
from repro.store import maintenance as maint
from repro.store.store import CameoStore

CFG = CameoConfig(eps=2e-2, lags=8, mode="rounds", max_rounds=60,
                  dtype="float64")
W = 64            # stream window
SEAL = 64         # small sealed blocks (stream-latency tier)
BLK = 256         # full-size blocks (compaction target)
CHUNK = 37        # misaligned with W and SEAL on purpose
N = 1100


def _series(n=N, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    return (3 * np.sin(2 * np.pi * t / 24 + seed)
            + 0.2 * rng.standard_normal(n))


def _scfg(**kw):
    base = dict(block_len=BLK, seal_block_len=SEAL, stream_window=W,
                auto_compact=False)
    base.update(kw)
    return ServerConfig(**base)


def _feed(sess, x):
    for i in range(0, len(x), CHUNK):
        sess.push(x[i:i + CHUNK])


def _bodies(store, sid):
    """Per-series block bodies (unwrapped) + location-free block facts."""
    entry = store._series[sid]
    bodies = [bytes(b) for b in store._read_bodies(entry["blocks"])]
    facts = [(b["nbytes"], b["t0"], b["t1"]) for b in entry["blocks"]]
    return bodies, facts


def _entry_key(store, sid):
    e = store.series_meta(sid)
    return {k: e[k] for k in ("n", "n_kept", "eps", "stored_nbytes",
                              "payload_nbytes", "deviation")}


def _snapshot_crash(store, p):
    """OS-visible crash image of a live writer (see test_crash_safety)."""
    store._f.flush()
    if store._wal is not None:
        store._wal._f.flush()
    shutil.copyfile(store.path, p)
    if store._wal is not None:
        shutil.copyfile(store._wal.path, p + ".wal")


# ---------------------------------------------------------------------------
# the concurrent differential
# ---------------------------------------------------------------------------

def test_concurrent_producers_match_serial(tmp_path):
    NT = 4
    tenants = [f"t{i}" for i in range(NT)]
    feeds = {t: _series(seed=i) for i, t in enumerate(tenants)}

    # serial references: one single-tenant store per tenant, same knobs
    refs = {}
    for t in tenants:
        p = str(tmp_path / f"ref-{t}.cameo")
        srv = IngestServer(p, CFG, _scfg())
        srv.register_tenant(t)
        with srv.session("s", tenant=t) as sess:
            _feed(sess, feeds[t])
        srv.close()
        store = CameoStore.open(p)
        refs[t] = (_bodies(store, tenant_sid(t, "s")),
                   _entry_key(store, tenant_sid(t, "s")))
        store.close()

    # concurrent run: NT threads race into one server
    p = str(tmp_path / "fleet.cameo")
    srv = IngestServer(p, CFG, _scfg(max_sessions=NT))
    for t in tenants:
        srv.register_tenant(t)
    start = threading.Barrier(NT)
    errs = []

    def producer(t):
        try:
            start.wait()
            with srv.session("s", tenant=t) as sess:
                _feed(sess, feeds[t])
        except Exception as e:              # pragma: no cover
            errs.append((t, e))

    threads = [threading.Thread(target=producer, args=(t,)) for t in tenants]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs

    # pre-compaction: per-series bodies/entries identical to serial runs
    for t in tenants:
        sid = tenant_sid(t, "s")
        assert _bodies(srv.store, sid) == refs[t][0], t
        assert _entry_key(srv.store, sid) == refs[t][1], t

    # post-compaction: compact both sides, compare again
    for t in tenants:
        srv.compact("s", tenant=t)
    for t in tenants:
        pr = str(tmp_path / f"ref-{t}.cameo")
        store = CameoStore(pr, "a")
        maint.compact_series(store, tenant_sid(t, "s"), target_len=BLK)
        ref_bodies = _bodies(store, tenant_sid(t, "s"))
        ref_entry = _entry_key(store, tenant_sid(t, "s"))
        store.close()
        sid = tenant_sid(t, "s")
        assert _bodies(srv.store, sid) == ref_bodies, t
        assert _entry_key(srv.store, sid) == ref_entry, t
        got = srv.view(t).series("s").window()
        assert got.shape == feeds[t].shape
    srv.close()


def test_background_compaction_worker(tmp_path):
    """auto_compact: closing a session queues it; drain() then shows the
    merged layout and byte-identical windows."""
    x = _series(seed=9)
    p = str(tmp_path / "bg.cameo")
    srv = IngestServer(p, CFG, _scfg(auto_compact=True))
    srv.register_tenant("a")
    with srv.session("s", tenant="a") as sess:
        _feed(sess, x)
    before = srv.view("a").series("s").window()
    srv.drain_compaction()
    st = srv.stats()
    assert st["compaction"]["compacted"] == 1
    assert st["compaction"]["last_error"] is None
    assert st["tiers"]["dead_nbytes"] > 0
    after = srv.view("a").series("s").window()
    assert np.array_equal(before.view(np.uint64), after.view(np.uint64))
    srv.close()


# ---------------------------------------------------------------------------
# crash recovery with active sessions
# ---------------------------------------------------------------------------

def test_crash_recovery_with_active_sessions(tmp_path):
    tenants = ["a", "b"]
    feeds = {t: _series(seed=i + 3) for i, t in enumerate(tenants)}
    cut = 600

    live = str(tmp_path / "live.cameo")
    img = str(tmp_path / "crash.cameo")
    srv = IngestServer(live, CFG, _scfg())
    acked = {}
    sessions = {}
    for t in tenants:
        srv.register_tenant(t)
        sessions[t] = srv.session("s", tenant=t)
    for t in tenants:
        for i in range(0, cut, CHUNK):
            c = feeds[t][i:min(i + CHUNK, cut)]
            sessions[t].push(c)
            acked[t] = acked.get(t, 0) + len(c)
    _snapshot_crash(srv.store, img)          # kill -9 with sessions open
    for t in tenants:
        sessions[t].close()
    srv.close()

    srv2 = IngestServer(img, CFG, _scfg(), resume=True)
    assert sorted(srv2.catalog.tenants()) == tenants
    for t in tenants:
        sess = srv2.session("s", tenant=t, resume=True)
        assert sess.resume_from == acked[t], t   # nothing acked was lost
        for i in range(sess.resume_from, len(feeds[t]), CHUNK):
            sess.push(feeds[t][i:i + CHUNK])
        sess.close()
    srv2.close()

    # every tenant's finished series answers like a clean reference run
    for i, t in enumerate(tenants):
        pr = str(tmp_path / f"cref-{t}.cameo")
        ref = IngestServer(pr, CFG, _scfg())
        ref.register_tenant(t)
        with ref.session("s", tenant=t) as sess:
            _feed(sess, feeds[t])
        ref.close()
        a = CameoStore.open(img)
        b = CameoStore.open(pr)
        ga = a.read_window(tenant_sid(t, "s"), 0, len(feeds[t]))
        gb = b.read_window(tenant_sid(t, "s"), 0, len(feeds[t]))
        assert np.array_equal(ga.view(np.uint64), gb.view(np.uint64)), t
        assert _bodies(a, tenant_sid(t, "s")) == _bodies(b, tenant_sid(t, "s"))
        a.close()
        b.close()


def test_compaction_crash_at_every_offset_rolls_back(tmp_path):
    """Truncate the store at every offset class inside a compaction
    rewrite (paired with the pre-rewrite journal, as a real crash would
    leave it): recovery must land on the pre- or post-compaction footer,
    both of which answer identically."""
    x = _series(n=700, seed=11)
    p = str(tmp_path / "c.cameo")
    srv = IngestServer(p, CFG, _scfg())
    srv.register_tenant("a")
    with srv.session("s", tenant="a") as sess:
        _feed(sess, x)
    srv.flush()
    sid = tenant_sid("a", "s")
    want = srv.view("a").series("s").window()
    pre = str(tmp_path / "pre.cameo")
    _snapshot_crash(srv.store, pre)          # pre-rewrite image (+ .wal)
    pre_len = os.path.getsize(pre)
    srv.compact("s", tenant="a")
    srv.store._f.flush()
    final = open(p, "rb").read()
    srv.close()

    img = str(tmp_path / "img.cameo")
    for off in list(range(pre_len, len(final), 149)) + [len(final)]:
        with open(img, "wb") as f:
            f.write(final[:off])
        shutil.copyfile(pre + ".wal", img + ".wal")
        store = CameoStore(img, "a")
        got = store.read_window(sid, 0, len(x))
        assert np.array_equal(got.view(np.uint64), want.view(np.uint64)), off
        store.close()


# ---------------------------------------------------------------------------
# compaction answer equivalence
# ---------------------------------------------------------------------------

def test_compaction_preserves_answers(tmp_path):
    x = _series(seed=21)
    p = str(tmp_path / "m.cameo")
    srv = IngestServer(p, CFG, _scfg())
    srv.register_tenant("a")
    with srv.session("s", tenant="a") as sess:
        _feed(sess, x)
    s = srv.view("a").series("s")
    w0 = s.window()
    k0 = s.kept()
    aggs0 = {k: getattr(s, k)() for k in ("mean", "var", "acf")}
    nblk0 = len(srv.store.series_meta(tenant_sid("a", "s"))["blocks"])

    rep = srv.compact("s", tenant="a")
    assert rep["runs"] >= 1 and rep["blocks_after"] < rep["blocks_before"]
    assert rep["dead_nbytes"] > 0
    assert nblk0 == rep["blocks_before"]

    w1 = s.window()
    k1 = s.kept()
    assert np.array_equal(w0.view(np.uint64), w1.view(np.uint64))
    assert np.array_equal(k0[0], k1[0])
    assert np.array_equal(k0[1].view(np.uint64), k1[1].view(np.uint64))
    for kind, (v0, b0) in aggs0.items():
        v1, b1 = getattr(s, kind)()
        np.testing.assert_allclose(v1, v0, rtol=0, atol=1e-9)
        assert np.all(np.asarray(b1) >= 0)
        # the recomputed answer stays inside the old bound and vice versa
        assert np.all(np.abs(np.asarray(v1) - np.asarray(v0))
                      <= np.asarray(b0) + np.asarray(b1) + 1e-12), kind

    # idempotent: a second pass finds nothing to merge
    rep2 = srv.compact("s", tenant="a")
    assert rep2["runs"] == 0
    # survives close/reopen (footer republish is durable)
    srv.close()
    store = CameoStore.open(p)
    got = store.read_window(tenant_sid("a", "s"), 0, len(x))
    assert np.array_equal(got.view(np.uint64), w0.view(np.uint64))
    store.close()


# ---------------------------------------------------------------------------
# tiers
# ---------------------------------------------------------------------------

def test_tier_demote_promote_answer_invariant(tmp_path):
    x = np.round(_series(seed=31), 2)        # compressible bodies
    p = str(tmp_path / "t.cameo")
    srv = IngestServer(p, CFG, _scfg())
    srv.register_tenant("a")
    with srv.session("s", tenant="a") as sess:
        _feed(sess, x)
    srv.compact("s", tenant="a")
    sid = tenant_sid("a", "s")
    bodies0, _ = _bodies(srv.store, sid)
    w0 = srv.view("a").series("s").window()
    m0 = srv.view("a").series("s").mean()

    assert srv.tiers._lock is srv._lock       # rewrites serialize with pushes
    rep = srv.tiers.demote_cold(sid)
    assert rep["rewritten"] >= 1
    assert any("wrap" in b for b in srv.store._series[sid]["blocks"])
    srv.store._cache.clear()                 # force cold fetches
    w1 = srv.view("a").series("s").window()
    assert np.array_equal(w0.view(np.uint64), w1.view(np.uint64))
    assert srv.view("a").series("s").mean() == m0
    bodies1, _ = _bodies(srv.store, sid)
    assert bodies0 == bodies1                # unwrap is byte-identical
    ts = srv.tiers.stats()
    assert ts["cold"]["hits"] >= 1 and ts["cold"]["nbytes"] > 0

    rep = srv.tiers.promote_warm(sid)
    assert rep["rewritten"] >= 1
    assert all("wrap" not in b for b in srv.store._series[sid]["blocks"])
    srv.store._cache.clear()
    w2 = srv.view("a").series("s").window()
    assert np.array_equal(w0.view(np.uint64), w2.view(np.uint64))

    # cold tier survives close/reopen
    srv.tiers.demote_cold(sid)
    srv.close()
    store = CameoStore.open(p)
    got = store.read_window(sid, 0, len(x))
    assert np.array_equal(got.view(np.uint64), w0.view(np.uint64))
    store.close()


def test_tier_pin_and_prefetch(tmp_path):
    x = _series(seed=41)
    p = str(tmp_path / "pin.cameo")
    srv = IngestServer(p, CFG, _scfg())
    with srv.session("s") as sess:
        _feed(sess, x)
    sid = "s"
    bis = srv.tiers.prefetch(sid)
    assert bis and srv.store.cache_stats()["entries"] >= len(bis)
    h0 = srv.store.cache_stats()["hits"]
    srv.series("s").window(0, W)
    assert srv.store.cache_stats()["hits"] > h0   # served hot

    pinned = srv.tiers.pin(sid, 0, 2 * W)
    assert srv.store.cache_stats()["pinned"] == len(pinned)
    cache = srv.store._cache
    assert all((sid, bi) in cache._pinned for bi in pinned)
    # pinned entries survive an eviction storm
    cache.budget = 1
    cache._evict()
    assert all((sid, bi) in cache._d for bi in pinned)
    srv.tiers.unpin(sid)
    assert srv.store.cache_stats()["pinned"] == 0
    cache._evict()
    assert not cache._d                      # now evictable
    srv.close()


# ---------------------------------------------------------------------------
# admission, quotas, catalog
# ---------------------------------------------------------------------------

def test_backpressure_reject_and_slots(tmp_path):
    p = str(tmp_path / "bp.cameo")
    srv = IngestServer(p, CFG, _scfg(max_sessions=1,
                                     backpressure="reject"))
    s1 = srv.session("a")
    with pytest.raises(ServerBusy):
        srv.session("b")
    s1.push(_series(n=256, seed=1))
    s1.close()                                # slot freed
    with srv.session("b") as s2:
        s2.push(_series(n=256, seed=2))
    srv.close()

    p2 = str(tmp_path / "bp2.cameo")
    srv = IngestServer(p2, CFG, _scfg(max_sessions=4))
    s3 = srv.session("c")
    with pytest.raises(ValueError, match="already has an open session"):
        srv.session("c")                      # dup releases its slot
    s3.push(_series(n=128, seed=8))
    s3.close()
    for name in ("d", "e", "f", "g"):         # all 4 slots reusable
        s = srv.session(name)
        s.push(_series(n=128, seed=8))
        s.close()
    srv.close()


def test_quota_refused_before_ack(tmp_path):
    p = str(tmp_path / "q.cameo")
    srv = IngestServer(p, CFG, _scfg())
    srv.register_tenant("a", max_points=500)
    sess = srv.session("s", tenant="a")
    sess.push(_series(n=400, seed=1))
    n0 = sess.n_seen
    with pytest.raises(QuotaExceeded):
        sess.push(_series(n=200, seed=2))
    assert sess.n_seen == n0                  # refused before journal/ack
    sess.push(_series(n=100, seed=3))         # exactly to the cap is fine
    sess.close()
    with pytest.raises(QuotaExceeded):
        srv.write("s2", _series(n=10, seed=4), tenant="a")
    assert "s2" not in srv.view("a")
    srv.close()


def test_view_ingest_routes_through_server(tmp_path):
    """``view()`` hands out a :class:`ServerView`: its ingest methods go
    back through the server, so a view write cannot bypass the lock or
    the ``max_points`` quota, and ``view().stream()`` takes a real
    admission slot."""
    p = str(tmp_path / "vw.cameo")
    srv = IngestServer(p, CFG, _scfg(max_sessions=1,
                                     backpressure="reject"))
    srv.register_tenant("a", max_points=100)
    v = srv.view("a")
    with pytest.raises(QuotaExceeded):
        v.write("s", _series(n=10_000, seed=1))
    assert "s" not in v
    with pytest.raises(QuotaExceeded):
        v.write_batch({"s": _series(n=64, seed=1),
                       "u": _series(n=64, seed=2)})
    assert srv.catalog.usage("a")["points"] == 0

    sess = v.stream("s")                      # a full ServerSession
    with pytest.raises(ServerBusy):
        srv.session("other")                  # the view's stream holds
    with pytest.raises(QuotaExceeded):        # the only slot
        sess.push(_series(n=101, seed=3))
    sess.push(_series(n=100, seed=3))
    sess.close()
    assert srv.catalog.usage("a")["points"] == 100
    srv.close()


def test_reregister_merges_tenant_config(tmp_path):
    """Re-registering updates only the kwargs that were passed — an eps
    refresh must not silently drop an existing quota (or vice versa)."""
    p = str(tmp_path / "rr.cameo")
    srv = IngestServer(p, CFG, _scfg())
    srv.register_tenant("a", eps=5e-2, max_points=1000)
    srv.register_tenant("a", eps=8e-2)
    assert srv.catalog.config("a") == {"eps": 8e-2, "max_points": 1000}
    srv.register_tenant("a", max_points=500)
    assert srv.catalog.config("a") == {"eps": 8e-2, "max_points": 500}
    srv.close()


def test_failed_close_releases_admission_slot(tmp_path):
    """A failed writer finalize must still free the admission slot, and
    the close must stay retryable without double-releasing the bounded
    semaphore."""
    p = str(tmp_path / "fc.cameo")
    srv = IngestServer(p, CFG, _scfg(max_sessions=1,
                                     backpressure="reject"))
    sess = srv.session("s")
    sess.push(_series(n=256, seed=1))
    orig, boom = sess._w.close, {"armed": True}

    def flaky_close():
        if boom.pop("armed", None):
            raise RuntimeError("finalize failed")
        return orig()

    sess._w.close = flaky_close
    with pytest.raises(RuntimeError, match="finalize failed"):
        sess.close()
    assert not sess.closed                    # still retryable
    with srv.session("other") as s2:          # the slot was freed anyway
        s2.push(_series(n=128, seed=2))
    sess.close()                              # retry: no double release
    assert sess.closed
    srv.close()


def test_tenant_catalog_persists_and_eps_applies(tmp_path):
    p = str(tmp_path / "cat.cameo")
    srv = IngestServer(p, CFG, _scfg())
    srv.register_tenant("loose", eps=8e-2, max_points=10 ** 6)
    with srv.session("s", tenant="loose") as sess:
        sess.push(_series(n=512, seed=5))
    assert srv.store.series_meta("loose/s")["eps"] == pytest.approx(8e-2)
    with pytest.raises(KeyError, match="unknown tenant"):
        srv.session("s", tenant="ghost")
    with pytest.raises(ValueError, match="must not contain"):
        srv.register_tenant("a/b")
    srv.close()

    srv2 = IngestServer(p, CFG, _scfg(), resume=True)
    assert srv2.catalog.tenants() == ["loose"]
    assert srv2.catalog.config("loose") == {"eps": 8e-2,
                                            "max_points": 10 ** 6}
    u = srv2.catalog.usage("loose")
    assert u["series"] == 1 and u["points"] == 512
    srv2.close()


def test_default_tenant_is_legacy_view(tmp_path):
    """Unprefixed sids belong to the default tenant; a plain store footer
    stays byte-identical when no tenant is ever registered."""
    p = str(tmp_path / "d.cameo")
    pr = str(tmp_path / "dr.cameo")
    x = _series(n=512, seed=6)
    srv = IngestServer(p, CFG, _scfg())
    with srv.session("s") as sess:
        _feed(sess, x)
    srv.close()
    # a raw dataset run with the same knobs writes the same file
    import repro.api as cameo
    with cameo.open(pr, CFG, mode="w", block_len=BLK,
                    stream_window=W) as ds:
        with ds.stream("s", block_len=SEAL) as w:
            _feed(w, x)
    assert open(p, "rb").read() == open(pr, "rb").read()

    srv = IngestServer(p, CFG, _scfg(), resume=True)
    srv.register_tenant("a")
    srv.write("s", x, tenant="a")
    assert srv.catalog.series_of(DEFAULT_TENANT) == ["s"]
    assert srv.catalog.series_of("a") == ["s"]
    assert sorted(srv.store.series_ids()) == ["a/s", "s"]
    srv.close()


# ---------------------------------------------------------------------------
# /metrics
# ---------------------------------------------------------------------------

def test_metrics_endpoint_serves_labeled_exposition(tmp_path):
    import repro.obs as obs
    from repro.obs import OBS
    was = obs.enabled()
    obs.reset()
    obs.enable()
    try:
        p = str(tmp_path / "m.cameo")
        srv = IngestServer(p, CFG, _scfg())
        srv.register_tenant("acme")
        with srv.session("s", tenant="acme") as sess:
            sess.push(_series(n=256, seed=7))
        txt = srv.metrics_text()
        assert "# TYPE cameo_server_tenant_points counter" in txt
        assert ('cameo_server_tenant_points_total{tenant="acme"} 256'
                in txt)
        assert "cameo_server_pushes_total 1" in txt

        app = srv.metrics_app()
        seen = {}

        def start_response(status, headers):
            seen["status"] = status
            seen["headers"] = dict(headers)

        body = b"".join(app({"PATH_INFO": "/metrics"}, start_response))
        assert seen["status"].startswith("200")
        assert seen["headers"]["Content-Type"].startswith("text/plain")
        assert body.decode() == srv.metrics_text()
        b404 = b"".join(app({"PATH_INFO": "/other"}, start_response))
        assert seen["status"].startswith("404") and b404
        srv.close()
    finally:
        obs.reset()
        OBS.enabled = was
