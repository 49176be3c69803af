"""One-shot archive: whole series written through ``repro.api.open(...)
.write(sid, x)`` by one backfill writer, with dashboards querying the
series archived before the window.

Traffic parameters (``traffic/<name>.json``):

* ``corpus_series``, ``corpus_seed``: a fixed corpus of that many series
  of the configuration's ``dataset`` and ``series_points``.  A writer
  that takes the whole corpus before the window closes stops, and the run
  prints a ``corpus_dry`` flag;
* ``ratio_series``: the writer takes the first this many corpus series
  first, in corpus order, and the compression ratio is taken over them: a
  fixed set of series, so that neither a faster program nor another
  ``--seed`` reads it from other series (one series' ratio differs from
  another's by about 3%).  ``--seed`` permutes the rest of the corpus,
  written after them, and draws the queries.  Where fewer complete in the
  window, the ratio is over those, and the run prints a ``ratio_short``
  flag;
* ``history_series``: series of the same corpus seed written in set-up,
  decoded and cached, which the dashboards query;
* ``dashboard_clients``, ``query_interval_ms``, ``query_kinds``,
  ``query_points``: open-loop dashboard clients, each issuing one query
  every interval over a log-uniform range of ``query_points = [lo, hi]``
  points of a random history series, the kinds in equal shares, each timed
  from when it was due;
* ``trace_offset_s``, ``trace_s``: the traced part of a traced run, begun
  and ended between two writes.

The writer is a closed loop: each write starts when the previous returns.
"""
from __future__ import annotations

import contextlib
import math
import threading
import time
from pathlib import Path

import numpy as np

from bench import reference as ref
from bench.data import make_series, sub_seed
from bench.entries import p95, within

# the program's telemetry: elimination rounds per one-shot write
ROUNDS = "write.rounds"


def writer_order(n: int, lead: int, seed: int) -> list:
    """The corpus indices in the order the writer takes them: the first
    ``lead`` in corpus order, then the rest as ``seed`` permutes them."""
    rest = np.random.default_rng(sub_seed(seed, 5)).permutation(n - lead)
    return list(range(lead)) + [lead + int(j) for j in rest]


class Cell:
    def __init__(self, config, traffic, seed, seconds, workdir, traced):
        self.cfg, self.tr = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.path = str(Path(workdir) / "archive.cameo")
        self.traced = traced
        self.span = (self._annotation if traced
                     else lambda name: contextlib.nullcontext())
        self.attempted = self.failed = 0
        self.window_stats = {}

    @staticmethod
    def _annotation(name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        from repro import api
        from repro.core.cameo import CameoConfig
        cfg, tr = self.cfg, self.tr
        self.ccfg = CameoConfig(**cfg["cameo"])
        n = int(cfg["series_points"])
        base = int(tr["corpus_seed"])
        corpus = [make_series(cfg["dataset"], sub_seed(base, 1, j), n)
                  for j in range(int(tr["corpus_series"]))]
        self.queue = [corpus[j] for j in writer_order(
            len(corpus), int(tr["ratio_series"]), self.seed)]
        self.history = [make_series(cfg["dataset"], sub_seed(base, 2, i), n)
                        for i in range(int(tr["history_series"]))]
        op = cfg["open"]
        self.ds = api.open(self.path, self.ccfg, mode="w",
                           block_len=int(op["block_len"]),
                           cache_bytes=int(op["cache_bytes"]),
                           store_residuals=bool(op["store_residuals"]))
        # history: written like any series (this also loads the rounds
        # program at the series' bucket), then the dashboard's read path:
        # every history block decoded and cached, each query kind answered
        self.hist_entries = [self.ds.write(f"history{i}", x)
                             for i, x in enumerate(self.history)]
        self.hist_series = [self.ds.series(f"history{i}")
                            for i in range(len(self.history))]
        for ser in self.hist_series:
            m = ser.n
            ser.window()
            for kind in tr["query_kinds"]:
                getattr(ser, kind)(0, m)
                getattr(ser, kind)(m // 3, 2 * m // 3)

    # -- window ---------------------------------------------------------------

    def window(self, hooks):
        tr = self.tr
        C = int(tr["dashboard_clients"])
        interval = float(tr["query_interval_ms"]) / 1000.0
        self.writes = []                 # (sid, x, start, return, entry)
        self.write_fail = 0
        self.dry = False
        self.queries = []                # answers, for the check
        self.query_lat, self.query_late = [], []
        self.query_fail = 0
        self.errors = []
        qlock = threading.Lock()
        go = threading.Barrier(C + 2)
        clock = {}

        def writer():
            """The corpus in this seed's order, one write at a time, until
            the window closes.  A traced run starts and stops the profiler
            between two writes, so that the traced part holds whole
            writes."""
            go.wait()
            t0, t_end = clock["t0"], clock["t_end"]
            span = float(tr["trace_s"])
            begin = t0 + min(float(tr["trace_offset_s"]),
                             max(0.0, self.seconds - span))
            hooks_due = ([(begin, hooks.trace_begin),
                          (begin + span, hooks.trace_end)]
                         if self.traced else [])
            for k, x in enumerate(self.queue):
                now = time.perf_counter()
                if now >= t_end:
                    break
                while hooks_due and now >= hooks_due[0][0]:
                    hooks_due.pop(0)[1]()
                    now = time.perf_counter()
                sid = f"w{k:03d}"
                try:
                    with self.span("bench.write"):
                        entry = self.ds.write(sid, x)
                except Exception as e:
                    self.write_fail += 1
                    self.errors.append(f"write {sid}: {e!r}")
                    continue
                self.writes.append((sid, x, now, time.perf_counter(), entry))
            else:
                self.dry = time.perf_counter() < t_end
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
                                             np.asarray(val),
                                             np.asarray(bound)))
                        self.query_lat.append(done - due)
                        self.query_late.append((start - due, due - t0))
                except Exception as e:
                    with qlock:
                        self.query_fail += 1
                        self.errors.append(f"query {kind} [{a}, {a + m}): "
                                           f"{e!r}")
                k += 1

        threads = ([threading.Thread(target=writer)]
                   + [threading.Thread(target=dashboard, args=(c,))
                      for c in range(C)])
        for th in threads:
            th.start()
        clock["t0"] = time.perf_counter()
        clock["t_end"] = clock["t0"] + self.seconds
        go.wait()
        for th in threads:
            th.join()
        t0 = clock["t0"]

        # every write started in the window counts, and the time runs to
        # the return of the last of them
        t_last = max((done for _, _, _, done, _ in self.writes),
                     default=math.nan)
        points = sum(len(x) for _, x, _, _, _ in self.writes)
        self.attempted = (len(self.writes) + self.write_fail
                          + len(self.query_lat) + self.query_fail)
        self.failed = self.write_fail + self.query_fail
        return {"ingest_pts_per_s": points / (t_last - t0),
                "query_p95_ms": 1000.0 * p95(self.query_lat)}

    # -- check ----------------------------------------------------------------

    def check(self):
        L, kappa, eps = self.ccfg.lags, self.ccfg.kappa, self.ccfg.eps
        stat = {"kept_gap": 0.0, "interp_gap": 0.0, "dev_over_eps": 0.0,
                "dev_report_gap": 0.0, "lost_series": 0.0,
                "query_err_over_bound": 0.0,
                "failed_requests": float(self.failed)}
        lines = []

        def read_back(sid, x, entry):
            """The kept count of series ``sid`` read back, after comparing
            it with its guarantees (``None`` where it is lost)."""
            try:
                ser = self.ds.series(sid)
                n = ser.n
                xr = ser.window()
                idx, vals = ser.kept()
            except Exception as e:
                self.errors.append(f"read {sid}: {e!r}")
                stat["lost_series"] += 1
                return None
            if n != len(x):
                stat["lost_series"] += 1
                return None
            g = ref.decode_gaps(x, idx, vals, xr)
            stat["kept_gap"] = max(stat["kept_gap"], g["kept_gap"])
            stat["interp_gap"] = max(stat["interp_gap"], g["interp_gap"])
            dev = ref.acf_deviation_np(x, xr, L, kappa)
            stat["dev_over_eps"] = max(stat["dev_over_eps"], dev / eps)
            stat["dev_report_gap"] = max(
                stat["dev_report_gap"],
                abs(float(entry["deviation"]) - dev) / eps)
            return len(idx)

        hist_kept = [read_back(f"history{i}", x, entry)
                     for i, (x, entry) in enumerate(zip(self.history,
                                                        self.hist_entries))]
        kept = [read_back(sid, x, entry)
                for sid, x, _, _, entry in self.writes]
        lead = int(self.tr["ratio_series"])
        ratio_kept = kept[:lead]
        ratio = (sum(len(x) for _, x, _, _, _ in self.writes[:lead])
                 / sum(ratio_kept)
                 if ratio_kept and all(ratio_kept) else math.nan)

        # dashboard answers: mean and var of the original, acf of the decode
        hist_xr = [ser.window() for ser in self.hist_series]
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
        rounds = obs.OBS.histogram(ROUNDS)
        lines.append({"rounds": {
            "series": int(rounds.count) if rounds else 0,
            "total": float(rounds.sum) if rounds else 0.0,
            "max": float(rounds.max) if rounds else 0.0,
            "max_rounds": self.ccfg.max_rounds,
            "series_in_window": len(self.writes)}})
        if rounds and rounds.max >= self.ccfg.max_rounds:
            lines.append({"flag": "rounds_cap",
                          "note": "a write stopped at max_rounds; its kept "
                                  "count is the cap's, not the method's"})
        if len(self.writes) < int(self.tr["ratio_series"]):
            lines.append({"flag": "ratio_short",
                          "note": f"{len(self.writes)} of the "
                                  f"{self.tr['ratio_series']} series the "
                                  "ratio is taken over were written in the "
                                  "window; the ratio is over those"})
        if self.dry:
            lines.append({"flag": "corpus_dry",
                          "note": "the writer wrote the whole corpus before "
                                  "the window closed"})
        self.ds.close()
        limits = {"kept_gap": 0.0, **self.cfg["limits"],
                  "dev_over_eps": 1.0, "lost_series": 0.0,
                  "query_err_over_bound": 1.0, "failed_requests": 0.0}
        checks = {k: (stat[k], limits[k]) for k in limits}
        write_s = [done - start for _, _, start, done, _ in self.writes]
        lines.append({"writes": {
            "series": len(self.writes),
            "write_s_median": float(np.median(write_s)) if write_s
            else math.nan,
            "kept": kept}})
        if self.errors:
            lines.append({"errors": self.errors[:20]})
        # how late the dashboards started their queries: a pause in the
        # window shows as a run of late starts at one time
        late = sorted(self.query_late, reverse=True)
        lines.append({"generator_late_ms": {
            "query_p50": 1000.0 * float(np.median([d for d, _ in late]))
            if late else math.nan,
            "query_top": [[round(1000.0 * d, 3), round(at, 3)]
                          for d, at in late[:5]],
            "query_over_10ms": sum(d > 0.01 for d, _ in late)}})
        return checks, lines, {"compression_ratio": ratio}
