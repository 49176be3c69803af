"""Served ingest: tenant sessions of ``repro.server.IngestServer`` fed by
one producer, with a dashboard querying sealed history while ingest goes on.

Traffic parameters (``traffic/<name>.json``):

* ``sessions``: tenants, one feed session each (at most ``max_sessions``);
* ``push_points``: points per push;
* ``corpus_seed``: the seed of a fixed corpus: one feed per tenant, the
  share of its first window that set-up pre-fills (uniform in
  ``[0, stream_window)``, so that windows fill at staggered times), and the
  history; ``--seed`` hands the feeds out to the tenants in another order
  and draws the queries;
* ``history_tenants``, ``history_windows``: tenants that get a closed
  ``history`` series of that many windows in set-up;
* ``dashboard_clients``, ``query_interval_ms``, ``query_kinds``,
  ``query_points``: open-loop dashboard clients, each issuing one query
  every interval over a log-uniform range of ``query_points = [lo, hi]``
  points of a random history series, the kinds in equal shares;
* ``trace_offset_s``, ``trace_s``: the traced part of a traced run;
* ``check_open_sessions``: feed sessions that compressed no window in the
  window but are closed and read back anyway (a seeded sample).

One producer thread drives the sessions round-robin, one push at a time;
each session is a closed loop (its next push follows its ack).  So the
windows fill, and compress inside the push that fills them, in an order
that the corpus fixes, and every seed does the same work.
"""
from __future__ import annotations

import contextlib
import math
import threading
import time
from pathlib import Path

import numpy as np

from bench import journal
from bench import reference as ref
from bench.data import make_series, sub_seed
from bench.entries import p95, within

# the deviation the compressor reports for each window it closes, over
# epsilon (the program's telemetry)
REPORTED = "stream.window_eps_headroom"


class Cell:
    def __init__(self, config, traffic, seed, seconds, workdir, traced):
        self.cfg, self.tr = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.path = str(Path(workdir) / "served.cameo")
        self.traced = traced
        self.span = (self._annotation if traced
                     else lambda name: contextlib.nullcontext())
        self.attempted = self.failed = 0
        self.window_stats = {}

    @staticmethod
    def _annotation(name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _push(self, key, sess, x, pos):
        """Push ``x`` (the points from ``pos`` on) to ``sess``.  Where the
        push fills windows, record the deviation the compressor reported
        for them: ``(key, first window, windows, sum of deviation / eps)``."""
        from repro import obs
        W = self.W
        fills = (pos + len(x)) // W > pos // W
        if fills:
            h = obs.OBS.histogram(REPORTED)
            c0, s0 = (h.count, h.sum) if h else (0, 0.0)
        sess.push(x)
        if fills:
            h = obs.OBS.histogram(REPORTED)
            c1, s1 = (h.count, h.sum) if h else (0, 0.0)
            self.reported.append((key, pos // W, c1 - c0, s1 - s0))

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        from repro.core.cameo import CameoConfig
        from repro.server import IngestServer, ServerConfig
        cfg, tr = self.cfg, self.tr
        self.ccfg = CameoConfig(**cfg["cameo"])
        self.scfg = ServerConfig(**cfg["server"])
        W = self.W = int(self.scfg.stream_window)
        T = int(tr["sessions"])
        if T > self.scfg.max_sessions:
            raise ValueError(f"{T} sessions > max_sessions "
                             f"{self.scfg.max_sessions}")
        self.tenants = [f"t{i:03d}" for i in range(T)]
        H = int(tr["history_tenants"])
        self.hist_tenants = self.tenants[:H]
        # the fixed corpus; --seed hands the feeds out in another order
        base = int(tr["corpus_seed"])
        rng = np.random.default_rng(sub_seed(base, 0))
        prefill = rng.integers(0, W, size=T).astype(int)
        feeds = [make_series(cfg["dataset"], sub_seed(base, 1, j),
                             int(cfg["feed_points"])) for j in range(T)]
        order = np.random.default_rng(sub_seed(self.seed, 5)).permutation(T)
        self.prefill = prefill[order]
        self.feeds = [feeds[j] for j in order]
        self.history = [make_series(cfg["dataset"], sub_seed(base, 2, i),
                                    int(tr["history_windows"]) * W)
                        for i in range(H)]
        self.reported = []

        self.srv = srv = IngestServer(self.path, self.ccfg, self.scfg)
        for t in self.tenants:
            srv.register_tenant(t)
        # history: one closed series per history tenant, compressed through
        # a session like any feed (this also loads the window's rounds
        # program and the running-aggregate programs)
        self.hist_entries = []
        for h, (t, xh) in enumerate(zip(self.hist_tenants, self.history)):
            s = srv.session("history", tenant=t)
            self._push(("history", h), s, xh, 0)
            self.hist_entries.append(s.close())
        # the dashboard's read path: decode and cache every history block,
        # and answer each query kind once
        self.hist_series = [srv.view(t).series("history")
                            for t in self.hist_tenants]
        for ser in self.hist_series:
            n = ser.n
            ser.window()
            for kind in tr["query_kinds"]:
                getattr(ser, kind)(0, n)
                getattr(ser, kind)(n // 3, 2 * n // 3)
        # feed sessions, pre-filled
        self.sessions = [srv.session("feed", tenant=t) for t in self.tenants]
        self.acked = [0] * T
        for i, s in enumerate(self.sessions):
            if self.prefill[i]:
                self._push(("feed", i), s, self.feeds[i][:self.prefill[i]], 0)
                self.acked[i] = int(self.prefill[i])

    # -- window ---------------------------------------------------------------

    def window(self, hooks):
        tr = self.tr
        T = len(self.sessions)
        P = int(tr["push_points"])
        C = int(tr["dashboard_clients"])
        interval = float(tr["query_interval_ms"]) / 1000.0
        self.push_log = [[] for _ in range(T)]     # (position, start, ack)
        self.push_fail = [0] * T
        self.queries = []                          # answers, for the check
        self.query_lat, self.query_late = [], []
        self.query_fail = 0
        self.errors = []
        qlock = threading.Lock()
        go = threading.Barrier(C + 2)
        clock = {}

        def producer():
            """Every session round-robin, one push at a time, until the
            window closes.  A traced run starts and stops the profiler
            between two pushes, so that the traced part holds whole window
            compressions."""
            go.wait()
            t0, t_end = clock["t0"], clock["t_end"]
            span = float(tr["trace_s"])
            begin = t0 + min(float(tr["trace_offset_s"]),
                             max(0.0, self.seconds - span))
            hooks_due = ([(begin, hooks.trace_begin),
                          (begin + span, hooks.trace_end)]
                         if self.traced else [])
            live = set(range(T))
            while live:
                for i in range(T):
                    if i not in live:
                        continue
                    now = time.perf_counter()
                    if now >= t_end:
                        live = set()
                        break
                    while hooks_due and now >= hooks_due[0][0]:
                        hooks_due.pop(0)[1]()
                        now = time.perf_counter()
                    pos, x = self.acked[i], self.feeds[i]
                    if pos + P > len(x):
                        live.discard(i)
                        continue
                    try:
                        with self.span("bench.push"):
                            self._push(("feed", i), self.sessions[i],
                                       x[pos:pos + P], pos)
                    except Exception as e:
                        self.push_fail[i] += 1
                        self.errors.append(f"push {i}: {e!r}")
                        live.discard(i)
                        continue
                    self.push_log[i].append((pos, now, time.perf_counter()))
                    self.acked[i] = pos + P
            for _, hook in hooks_due:       # a window too short to trace
                hook()

        def dashboard(c):
            rng = np.random.default_rng(sub_seed(self.seed, 3, c))
            kinds = list(tr["query_kinds"])
            lo, hi = (math.log(p) for p in tr["query_points"])
            go.wait()
            t0, t_end = clock["t0"], clock["t_end"]
            k = 0
            while True:
                due = t0 + (c + 0.5) / C * interval + k * interval
                if due >= t_end:
                    break
                h = int(rng.integers(len(self.hist_series)))
                ser = self.hist_series[h]
                m = min(int(round(math.exp(rng.uniform(lo, hi)))), ser.n)
                a = int(rng.integers(0, ser.n - m + 1))
                kind = kinds[k % len(kinds)]
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                start = time.perf_counter()
                try:
                    with self.span("bench.query"):
                        val, bound = getattr(ser, kind)(a, a + m)
                    done = time.perf_counter()
                    with qlock:
                        self.queries.append((h, kind, a, a + m,
                                             np.asarray(val), np.asarray(bound)))
                        self.query_lat.append(done - due)
                        self.query_late.append(start - due)
                except Exception as e:
                    with qlock:
                        self.query_fail += 1
                        self.errors.append(f"query {kind} [{a}, {a + m}): "
                                           f"{e!r}")
                k += 1

        threads = ([threading.Thread(target=producer)]
                   + [threading.Thread(target=dashboard, args=(c,))
                      for c in range(C)])
        for th in threads:
            th.start()
        clock["t0"] = time.perf_counter()
        clock["t_end"] = clock["t0"] + self.seconds
        go.wait()
        for th in threads:
            th.join()
        self.t0 = clock["t0"]

        # every push started in the window counts, and the time runs to
        # the ack of the last of them
        pushes = [p for log in self.push_log for p in log]
        t_last = max((done for _, _, done in pushes), default=math.nan)
        self.window_end_acked = list(self.acked)
        self.attempted = len(pushes) + sum(self.push_fail) \
            + len(self.query_lat) + self.query_fail
        self.failed = sum(self.push_fail) + self.query_fail
        self.window_stats = {
            "ack_p95_ms": 1000.0 * p95([d - s for _, s, d in pushes])}
        return {"ingest_pts_per_s": P * len(pushes) / (t_last - self.t0),
                "query_p95_ms": 1000.0 * p95(self.query_lat)}

    # -- check ----------------------------------------------------------------

    def check(self):
        W, L, kappa = self.W, self.ccfg.lags, self.ccfg.kappa
        eps = self.ccfg.eps
        T = len(self.sessions)
        P = int(self.tr["push_points"])
        stat = {"lost_points": 0.0, "unjournaled_pushes": 0.0,
                "kept_gap": 0.0, "interp_gap": 0.0,
                "window_dev_over_eps": 0.0, "window_dev_report_gap": 0.0,
                "query_err_over_bound": 0.0,
                "failed_requests": float(self.failed)}
        lines = []

        # journaled before ack: the journal holds every push acked in the
        # window, at its position, with the points sent (read before any
        # session closes, since a close checkpoints the journal)
        try:
            journaled = journal.pushes(self.path + ".wal")
        except (OSError, ValueError) as e:
            self.errors.append(f"journal: {e!r}")
            journaled = {}
        for i in range(T):
            recs = journaled.get(self.sessions[i].sid, {})
            x = self.feeds[i]
            for pos, _, _ in self.push_log[i]:
                got = recs.get(pos)
                if got is None or not np.array_equal(got, x[pos:pos + P]):
                    stat["unjournaled_pushes"] += 1

        # numpy's deviation of each window read back, by (series, window)
        window_dev = {}
        # the deviation a closed stream reports against its decode's: printed,
        # not compared (no guarantee of the deployment; PERF.md, section 7)
        stream_dev_gap = 0.0

        def read_back(key, ser, x, n_acked, entry):
            nonlocal stream_dev_gap
            n = ser.n
            stat["lost_points"] += abs(n_acked - n)
            if n == 0:
                return None
            x = x[:n]
            xr = ser.window()
            idx, vals = ser.kept()
            g = ref.decode_gaps(x, idx, vals, xr)
            stat["kept_gap"] = max(stat["kept_gap"], g["kept_gap"])
            stat["interp_gap"] = max(stat["interp_gap"], g["interp_gap"])
            for w in range(n // W):
                window_dev[key, w] = ref.acf_deviation_np(
                    x[w * W:(w + 1) * W], xr[w * W:(w + 1) * W], L, kappa)
            if n % W and (n % W) // kappa >= L + 2:
                window_dev[key, n // W] = ref.acf_deviation_np(
                    x[n // W * W:], xr[n // W * W:], L, kappa)
            d_all = ref.acf_deviation_np(x, xr, L, kappa)
            stream_dev_gap = max(stream_dev_gap,
                                 abs(float(entry["deviation"]) - d_all))
            return np.asarray(idx)

        # history series (closed in set-up)
        hist_xr = []
        for h, (ser, xh, entry) in enumerate(zip(
                self.hist_series, self.history, self.hist_entries)):
            read_back(("history", h), ser, xh, len(xh), entry)
            hist_xr.append(ser.window())

        # feed sessions: those that compressed a window in the window, and a
        # seeded sample of the rest, are filled to their window's end and
        # closed, then read back whole
        compressed = [i for i in range(T)
                      if self.window_end_acked[i] // W > self.prefill[i] // W]
        rest = [i for i in range(T) if i not in compressed]
        rng = np.random.default_rng(sub_seed(self.seed, 4))
        k = min(int(self.tr["check_open_sessions"]), len(rest))
        sample = sorted(rng.choice(rest, size=k, replace=False).tolist()) \
            if k else []
        kept_in_window = points_in_window = 0
        windows_in_window = 0
        for i in sorted(compressed + sample):
            sess, x = self.sessions[i], self.feeds[i]
            fill = (-self.acked[i]) % W
            fill = min(fill, len(x) - self.acked[i])
            try:
                if fill:
                    pos = self.acked[i]
                    self._push(("feed", i), sess, x[pos:pos + fill], pos)
                    self.acked[i] += fill
                entry = sess.close()
                ser = self.srv.view(self.tenants[i]).series("feed")
            except Exception as e:      # the acked points cannot be read
                self.errors.append(f"close {i}: {e!r}")
                stat["lost_points"] += self.acked[i]
                continue
            idx = read_back(("feed", i), ser, x, self.acked[i], entry)
            for w in range(self.prefill[i] // W,
                           self.window_end_acked[i] // W):
                lo, hi = w * W, (w + 1) * W
                kept_in_window += int(np.count_nonzero(
                    (idx >= lo) & (idx < hi))) if idx is not None else 0
                points_in_window += W
                windows_in_window += 1
        # the sessions left open: the server holds exactly the acked points
        for i in rest:
            if i not in sample:
                stat["lost_points"] += abs(self.sessions[i].n_seen
                                           - self.acked[i])

        # every window: within epsilon by numpy, and the deviation the
        # compressor reported for it as numpy measures it (a window the
        # store does not hold reads infinitely far)
        for key, w in window_dev:
            stat["window_dev_over_eps"] = max(stat["window_dev_over_eps"],
                                              window_dev[key, w] / eps)
        for key, w0, count, reported in self.reported:
            devs = [window_dev.get((key, w), math.inf)
                    for w in range(w0, w0 + count)]
            stat["window_dev_report_gap"] = max(
                stat["window_dev_report_gap"],
                abs(reported - sum(devs) / eps))

        # dashboard answers: mean and var of the original, acf of the decode
        for h, kind, a, b, val, bound in self.queries:
            if kind == "acf":
                want = ref.acf_np(hist_xr[h][a:b], L)
            elif kind == "mean":
                want = self.history[h][a:b].mean()
            elif kind == "var":
                want = self.history[h][a:b].var()
            else:
                raise ValueError(f"no reference for query kind {kind!r}")
            stat["query_err_over_bound"] = max(stat["query_err_over_bound"],
                                               within(val, want, bound))

        from repro import obs
        rounds = obs.OBS.histogram("stream.window_rounds")
        lines.append({"rounds": {
            "windows": int(rounds.count) if rounds else 0,
            "total": float(rounds.sum) if rounds else 0.0,
            "max": float(rounds.max) if rounds else 0.0,
            "max_rounds": self.ccfg.max_rounds,
            "windows_in_window": windows_in_window}})
        if rounds and rounds.max >= self.ccfg.max_rounds:
            lines.append({"flag": "rounds_cap",
                          "note": "a window stopped at max_rounds; its kept "
                                  "count is the cap's, not the method's"})
        self.srv.close()
        limits = {"lost_points": 0.0, "unjournaled_pushes": 0.0,
                  "kept_gap": 0.0, **self.cfg["limits"],
                  "window_dev_over_eps": 1.0,
                  "query_err_over_bound": 1.0, "failed_requests": 0.0}
        checks = {k: (stat[k], limits[k]) for k in limits}
        ratio = (points_in_window / kept_in_window if kept_in_window
                 else math.nan)
        lines.append({"stream_deviation_gap": stream_dev_gap})
        if self.errors:
            lines.append({"errors": self.errors[:20]})
        lines.append({"generator_late_ms": {
            "query_p50": 1000.0 * float(np.median(self.query_late))
            if self.query_late else math.nan,
            "query_max": 1000.0 * max(self.query_late, default=math.nan)}})
        return checks, lines, {"compression_ratio": ratio}
