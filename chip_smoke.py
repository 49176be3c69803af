#!/usr/bin/env python3
"""Bring-up smoke: CAMEO's compress -> store -> serve path on a TPU.

    python chip_smoke.py              # one chip: device, archive, served
                                      # ingest, pushdown queries
    python chip_smoke.py --chips 4    # four chips: the partitioned (paper
                                      # section 4.4) and sharded-batch paths,
                                      # each against its one-device form

One process, x64 on (as every entry point runs), no child process once JAX
is touched.  The script refuses to run without a TPU, under
``CAMEO_BACKEND`` or ``CAMEO_FORCE_INTERPRET``, or outside the repository.
Every phase prints one JSON line (wall and compile seconds, the recompile
watermark, kept counts and compression ratios); any failed check raises and
the process exits non-zero.  Only when every phase passes is the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

The ACF deviations are re-measured here with plain numpy (a Pearson
correlation per lag on the kappa-aggregated series), independent of
``repro.core``.  Store files go to ``<repo>/.chip_smoke/``; the compile
cache is placed by ``repro.compile_cache``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"
EPS = 1e-2
ARCHIVE = ("uk_elec", "min_temp", "aus_elec", "solar")
# The backend="reference" twin of solar runs on its first sixteenth, next to
# the default backend on the same points: one full-length solar compression
# takes about 380 s on a v5e, and two do not fit the smoke's time limit.
TWIN_CUT = {"solar": 16}
# The four-chip phase compresses the same sixteenth of solar: its one-device
# comparison alone would take minutes of four chips at full length.
FOUR_CHIP_CUT = 16
TENANTS = 8
CHUNK = 731
SERVER_WINDOW = 4096


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=float), flush=True)


# ---------------------------------------------------------------------------
# plain numpy reference (independent of repro.core)
# ---------------------------------------------------------------------------

def acf_np(y, L: int):
    """Eq. 2 ACF: per lag ``l``, the Pearson correlation of ``y[:-l]`` with
    ``y[l:]`` (zero where either side is constant)."""
    import numpy as np
    out = np.zeros(L)
    for l in range(1, L + 1):
        a, b = y[:-l] - y[:-l].mean(), y[l:] - y[l:].mean()
        den = np.sqrt(np.dot(a, a) * np.dot(b, b))
        out[l - 1] = np.dot(a, b) / den if den > 0 else 0.0
    return out


def acf_deviation_np(x, xr, L: int, kappa: int) -> float:
    """Mean absolute ACF deviation (the ``mae`` measure) of ``xr`` from
    ``x``, both mean-aggregated over tumbling windows of ``kappa``."""
    import numpy as np

    def agg(v):
        v = np.asarray(v, np.float64)
        return v.reshape(-1, kappa).mean(axis=1)
    return float(np.mean(np.abs(acf_np(agg(xr), L) - acf_np(agg(x), L))))


# ---------------------------------------------------------------------------
# compile accounting (JAX's own monitoring events)
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
# persistent compile cache: a program read back, a program compiled and
# written (JAX writes only programs that took at least a second)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_writes"}
_seconds = {}
_seconds_lock = threading.Lock()


def _on_duration(event: str, secs: float, **_):
    with _seconds_lock:
        _seconds[event] = _seconds.get(event, 0.0) + secs


def _on_event(event: str, **_):
    if event in _CACHE_EVENTS:
        with _seconds_lock:
            _seconds[event] = _seconds.get(event, 0) + 1


class Phase:
    """Wall seconds, compile seconds (trace + lower + backend compile;
    a persistent-cache hit skips the backend compile), persistent-cache
    hits and writes, and the recompile watermark of one phase."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from repro import obs
        self.t0 = time.perf_counter()
        with _seconds_lock:
            self.c0 = dict(_seconds)
        self.w0 = obs.recompile_watermark()
        return self

    def __exit__(self, *exc):
        from repro import obs
        if exc[0] is None:
            with _seconds_lock:
                d = {k: _seconds.get(k, 0) - self.c0.get(k, 0)
                     for k in (*_COMPILE_EVENTS, *_CACHE_EVENTS)}
            say(self.name,
                wall_s=time.perf_counter() - self.t0,
                compile_s=sum(d[k] for k in _COMPILE_EVENTS),
                backend_compile_s=d[_COMPILE_EVENTS[2]],
                **{name: d[k] for k, name in _CACHE_EVENTS.items()},
                recompile_watermark=obs.recompile_watermark(),
                recompiles=obs.recompile_watermark() - self.w0)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def archive_cfg(name: str, backend: str = "auto"):
    from repro.core.cameo import CameoConfig
    from repro.data.synthetic import DATASETS
    spec = DATASETS[name]
    return CameoConfig(eps=EPS, lags=spec.lags, kappa=spec.kappa,
                       backend=backend)


def make_series(name: str, seed: int = 0):
    from repro.data.synthetic import make_dataset
    return make_dataset(name, seed=seed)


def twin_series(name: str, x):
    """The points the backend twins compare on (see ``TWIN_CUT``)."""
    kappa = archive_cfg(name).kappa
    return x[:len(x) // TWIN_CUT.get(name, 1) // kappa * kappa]


def phase_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu", f"no TPU: JAX's first device is "
                                f"{d0.platform} ({d0.device_kind})")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    say("device", **device)
    return device


def rounds_cut_short(cfg, rounds: int, deviation: float) -> bool:
    """The rounds loop stopped at its ``max_rounds`` cap with the deviation
    well below its budget: the kept count is then the cap's, not the
    method's."""
    return rounds >= cfg.max_rounds and deviation < 0.5 * cfg.eps


def write_rounds() -> float:
    """Elimination rounds of every facade write so far (telemetry)."""
    from repro import obs
    h = obs.OBS.histogram("write.rounds")
    return h.sum if h is not None else 0.0


def phase_compile(server_window: int) -> None:
    """Compile every rounds program the later phases run, in parallel
    threads: the TPU compiler spends tens of seconds on each, in one thread
    per program.  The later calls load them from the persistent cache.
    Each series' default program must hold the Pallas ranking kernel
    (``tpu_custom_call``), compiled and not interpreted; its
    ``backend="reference"`` twin must hold none."""
    from repro.core.cameo import lower_rounds
    from repro.kernels import ops
    backend, interp = ops.resolve_backend("auto"), ops.interpret_mode()
    say("dispatch", backend=backend, interpret=interp)
    check(backend == "pallas", f"backend auto resolved to {backend}")
    check(interp is False, "Pallas kernels would run interpreted")
    jobs = {}
    for name in ARCHIVE:
        x = make_series(name)
        n, n_twin = len(x), len(twin_series(name, x))
        jobs[f"{name}.auto"] = lower_rounds(n, archive_cfg(name))
        jobs[f"{name}.reference"] = lower_rounds(
            n_twin, archive_cfg(name, "reference"))
        if n_twin < n:
            jobs[f"{name}.twin.auto"] = lower_rounds(n_twin,
                                                     archive_cfg(name))
    jobs["served.auto"] = lower_rounds(server_window, archive_cfg("uk_elec"),
                                       pad_to=server_window)

    def build(label):
        t0 = time.perf_counter()
        text = jobs[label][1].compile().as_text()
        return time.perf_counter() - t0, "tpu_custom_call" in text

    with Phase("compile"):
        with concurrent.futures.ThreadPoolExecutor(
                min(len(jobs), os.cpu_count() or 1)) as pool:
            built = dict(zip(jobs, pool.map(build, jobs)))
    for label, (secs, kernel) in built.items():
        say("compile.program", program=label, bucket=jobs[label][0],
            compile_s=secs, tpu_custom_call=kernel)
        check(kernel == label.endswith(".auto"),
              f"{label}: tpu_custom_call={kernel}")


def phase_archive(path: Path) -> dict:
    """Write the four Table-1 series at full length through the dataset
    facade; re-measure each deviation on the decode; compress each again
    on the reference backend (solar on a cut, ``TWIN_CUT``).  Each line
    carries the rounds every run took; a run that stopped at the
    ``max_rounds`` cap far below its budget gets an ``archive.flag`` line."""
    import numpy as np
    from repro import api
    from repro.core.cameo import compress
    from repro.server import TierManager
    xs = {}
    with api.open(str(path), archive_cfg(ARCHIVE[0]), mode="w") as ds:
        for name in ARCHIVE:
            cfg = archive_cfg(name)
            x = make_series(name)
            xs[name] = x
            r0 = write_rounds()
            with Phase(f"archive.{name}"):
                ds.cfg = cfg
                entry = ds.write(name, x)
                xr = ds.series(name).window()
                dev_np = acf_deviation_np(x, xr, cfg.lags, cfg.kappa)
            runs = {"auto": (int(write_rounds() - r0), entry["deviation"])}
            xt = twin_series(name, x)
            with Phase(f"archive.{name}.reference"):
                ref = compress(xt, dataclasses.replace(cfg, backend="reference"))
                kept_ref = np.asarray(ref.kept)
                dev_ref_np = acf_deviation_np(xt, np.asarray(ref.xr),
                                              cfg.lags, cfg.kappa)
                runs["reference"] = (int(ref.iters), float(ref.deviation))
                if len(xt) < len(x):
                    twin = compress(xt, cfg)
                    kept = np.asarray(twin.kept)
                    runs["twin.auto"] = (int(twin.iters),
                                         float(twin.deviation))
                else:
                    kept = np.zeros(len(x), bool)
                    kept[ds.series(name).kept()[0]] = True
            n = len(x)
            say("archive", series=name, n=n, lags=cfg.lags, kappa=cfg.kappa,
                eps=EPS, n_kept=int(entry["n_kept"]),
                cr=n / int(entry["n_kept"]),
                deviation=entry["deviation"], deviation_np=dev_np,
                twin_n=len(xt), n_kept_twin=int(kept.sum()),
                n_kept_reference=int(kept_ref.sum()),
                cr_reference=len(xt) / int(kept_ref.sum()),
                deviation_reference=float(ref.deviation),
                deviation_reference_np=dev_ref_np,
                same_kept=bool(np.array_equal(kept, kept_ref)),
                max_rounds=cfg.max_rounds,
                rounds={run: r for run, (r, _) in runs.items()})
            for run, (r, d) in runs.items():
                if rounds_cut_short(cfg, r, d):
                    say("archive.flag", series=name, run=run, rounds=r,
                        deviation=d, eps=EPS,
                        note="stopped at max_rounds below eps/2: the kept "
                             "count is the cap's, not the method's")
            check(dev_np <= EPS, f"{name}: decoded ACF deviation {dev_np} > "
                                 f"eps {EPS}")
            check(abs(dev_np - entry["deviation"]) <= 1e-9,
                  f"{name}: reported deviation {entry['deviation']} vs "
                  f"numpy {dev_np}")
            check(dev_ref_np <= EPS, f"{name}: reference backend deviation "
                                     f"{dev_ref_np} > eps {EPS}")
            check(abs(dev_ref_np - float(ref.deviation)) <= 1e-9,
                  f"{name}: reference backend reported {ref.deviation} vs "
                  f"numpy {dev_ref_np}")
        # The cold tier, on the series whose blocks an entropy wrap can
        # shrink (solar's night-time zeros); the queries then read it.
        warm = ds.series("solar").window()
        demoted = TierManager(ds.store).demote_cold("solar")
        ds.store._cache.clear()
        cold = ds.series("solar").window()
        say("archive.cold", series="solar", demote_cold=demoted,
            identical=bool(np.array_equal(cold.view(np.uint64),
                                          warm.view(np.uint64))))
        check(demoted["rewritten"] > 0, "no solar block went cold")
        check(np.array_equal(cold.view(np.uint64), warm.view(np.uint64)),
              "cold read-back differs")
    return xs


def phase_served(path: Path) -> None:
    """Eight tenants stream full-length uk_elec-shaped feeds through
    journaled server sessions at once; compaction, cold demotion and a
    resumed reopen must leave every read-back bit-identical."""
    import numpy as np
    from repro.core.cameo import CameoConfig
    from repro.data.synthetic import DATASETS
    from repro.server import IngestServer, ServerConfig, tenant_sid
    spec = DATASETS["uk_elec"]
    cfg = CameoConfig(eps=EPS, lags=spec.lags, kappa=spec.kappa)
    scfg = ServerConfig(seal_block_len=512, stream_window=SERVER_WINDOW,
                        max_sessions=TENANTS, wal=True)
    feeds = {f"t{i}": make_series("uk_elec", seed=i) for i in range(TENANTS)}

    with Phase("served"):
        srv = IngestServer(str(path), cfg, scfg)
        for t in feeds:
            srv.register_tenant(t)

        def stream(tenant):
            x = feeds[tenant]
            sess = srv.session("feed", tenant=tenant)
            for lo in range(0, len(x), CHUNK):
                sess.push(x[lo:lo + CHUNK])
            return sess.close()

        with concurrent.futures.ThreadPoolExecutor(TENANTS) as pool:
            entries = dict(zip(feeds, pool.map(stream, feeds)))
        srv.drain_compaction()
        st = srv.stats()
        comp = st["compaction"]
        check(comp["last_error"] is None,
              f"compaction failed: {comp['last_error']}")
        check(comp["compacted"] == TENANTS, f"compaction: {comp}")
        want = {t: srv.view(t).series("feed").window() for t in feeds}
        demoted = srv.tiers.demote_cold(tenant_sid("t0", "feed"))
        srv.store._cache.clear()
        cold = srv.view("t0").series("feed").window()
        srv.close()
        srv = IngestServer(str(path), cfg, scfg, resume=True)
        try:
            back = {t: srv.view(t).series("feed").window() for t in feeds}
        finally:
            srv.close()

    def same(a, b):
        return a.shape == b.shape and np.array_equal(a.view(np.uint64),
                                                     b.view(np.uint64))
    n_kept = sum(int(e["n_kept"]) for e in entries.values())
    points = sum(len(x) for x in feeds.values())
    say("served", tenants=TENANTS, chunk=CHUNK, points=points,
        n_kept=n_kept, cr=points / n_kept,
        whole_series_deviation_max=max(e["deviation"] for e in entries.values()),
        compaction=comp, demote_cold=demoted,
        cold_identical=same(cold, want["t0"]),
        resume_identical=all(same(back[t], want[t]) for t in feeds))
    for t in feeds:
        check(len(want[t]) == len(feeds[t]), f"{t}: {len(want[t])} points")
    check(same(cold, want["t0"]), "cold read-back differs")
    for t in feeds:
        check(same(back[t], want[t]), f"{t}: resumed read-back differs")


def phase_queries(path: Path, xs: dict) -> None:
    """Pushdown mean/var/acf over windows that span several blocks: mean
    and var answer for the original series (residual moments are stored),
    acf for the reconstruction; each lies within its own bound."""
    import numpy as np
    from repro import api
    with Phase("queries"), api.open(str(path), mode="r") as ds:
        block = ds.store.block_len
        for name in ("uk_elec", "aus_elec", "solar"):
            s, x = ds.series(name), xs[name]
            a, b = 1000 + 7, len(x) - 1000 - 11
            check(b - a > 2 * block, f"{name}: window inside two blocks")
            xr = s.window()
            L = s.meta["lags"]
            got = {"mean": s.mean(a, b), "var": s.var(a, b),
                   "acf": s.acf(a, b)}
            want = {"mean": x[a:b].mean(), "var": x[a:b].var(),
                    "acf": acf_np(xr[a:b], L)}
            err = {k: float(np.max(np.abs(np.asarray(got[k][0]) - want[k])))
                   for k in got}
            slack = {k: float(np.min(np.asarray(got[k][1])
                                     - np.abs(np.asarray(got[k][0])
                                              - want[k])))
                     for k in got}
            say("query", series=name, window=[a, b], err=err,
                bound_slack=slack)
            for k in got:
                check(slack[k] >= 0, f"{name}: {k} outside its bound")


def phase_four_chips() -> None:
    """The paper's parallel strategy (section 4.4) on a 4-device mesh
    against the one-device partitioned form, and the sharded fleet batch
    against its one-device vmap.

    The four runs go one after another, each reported when it returns, so
    a run that never returns is named by the last line before it.  Each
    one-device form runs on a chip of its own (devices 0 and 1)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.cameo import compress_batch
    from repro.core.parallel import (compress_partitioned,
                                     compress_partitioned_shardmap)
    devs = jax.devices()[:4]
    mesh = jax.sharding.Mesh(np.array(devs), ("data",))
    spread = NamedSharding(mesh, P("data"))

    def placed(x):
        arr = jax.device_put(x, spread)
        on = {s.device for s in arr.addressable_shards}
        check(len(on) == 4, f"input shards sit on {len(on)} devices")
        return arr

    def done(res):
        return jax.tree.map(np.asarray, res)

    cfg_p, cfg_b = archive_cfg("solar"), archive_cfg("uk_elec")
    x = make_series("solar")
    n = (len(x) // FOUR_CHIP_CUT // (4 * cfg_p.kappa)) * 4 * cfg_p.kappa
    x = x[:n]
    xs = np.stack([make_series("uk_elec", seed=i) for i in range(8)])

    runs = {
        "part_mesh": lambda: compress_partitioned_shardmap(
            placed(x), cfg_p, mesh, axis="data"),
        "part_one": lambda: compress_partitioned(
            jax.device_put(x, devs[0]), cfg_p, T=4),
        "batch_mesh": lambda: compress_batch(placed(xs), cfg_b, mesh=mesh,
                                             axis="data"),
        "batch_one": lambda: compress_batch(jax.device_put(xs, devs[1]),
                                            cfg_b),
    }
    got = {}
    with Phase("four_chips"):
        for label, run in runs.items():
            say("four_chips.start", run=label)
            with Phase(f"four_chips.{label}"):
                got[label] = done(run())
    part_s, part_1 = got["part_mesh"], got["part_one"]
    batch_s, batch_1 = got["batch_mesh"], got["batch_one"]

    dev_s, dev_1 = float(part_s.deviation), float(part_1.deviation)
    dev_np = acf_deviation_np(x, part_s.xr, cfg_p.lags, cfg_p.kappa)
    say("four_chips.partitioned", series="solar", n=n, T=4,
        n_kept=int(part_s.kept.sum()), cr=n / int(part_s.kept.sum()),
        n_kept_one_device=int(part_1.kept.sum()),
        same_kept=bool(np.array_equal(part_s.kept, part_1.kept)),
        deviation=dev_s, deviation_one_device=dev_1, deviation_np=dev_np,
        rounds=int(part_s.iters), rounds_one_device=int(part_1.iters),
        max_rounds=cfg_p.max_rounds)
    check(np.array_equal(part_s.kept, part_1.kept),
          "partitioned kept sets differ")
    check(abs(dev_s - dev_1) < 1e-9, f"deviations {dev_s} vs {dev_1}")
    check(dev_s <= EPS and dev_np <= EPS, f"deviation {dev_s} / {dev_np}")

    gap = float(np.max(np.abs(batch_s.deviation - batch_1.deviation)))
    say("four_chips.batch", series="uk_elec", batch=len(xs),
        n_kept=batch_s.kept.sum(axis=1).tolist(),
        same_kept=bool(np.array_equal(batch_s.kept, batch_1.kept)),
        max_deviation=float(batch_s.deviation.max()),
        max_deviation_gap=gap, rounds=batch_s.iters.tolist())
    check(np.array_equal(batch_s.kept, batch_1.kept),
          "batched kept sets differ")
    check(gap < 1e-9, f"batched deviations differ by {gap}")
    check(float(batch_s.deviation.max()) <= EPS,
          "batched deviation above eps")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the paths that span four chips")
    args = ap.parse_args(argv)

    for var in ("CAMEO_BACKEND", "CAMEO_FORCE_INTERPRET"):
        check(var not in os.environ, f"{var} is set; the smoke runs the "
                                     "default dispatch only")
    check((ROOT / "src" / "repro").is_dir(),
          f"{ROOT} holds no repro package; run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    # Host-only set-up before JAX is touched: the native block scanner is
    # built by a compiler subprocess on first use.
    from repro.store import _scan
    native_scan = _scan._lib() is not None

    import jax
    from jax import monitoring
    jax.config.update("jax_enable_x64", True)
    from repro import obs
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    obs.enable()    # the archive phase reads each write's rounds from it

    device = phase_device(args.chips)
    say("setup", native_scan=native_scan, compile_cache=cache_dir,
        jax=jax.__version__)
    if args.chips == 4:
        phase_four_chips()
    else:
        if OUT.exists():
            shutil.rmtree(OUT)
        OUT.mkdir()
        phase_compile(SERVER_WINDOW)
        xs = phase_archive(OUT / "smoke.cameo")
        phase_served(OUT / "served.cameo")
        phase_queries(OUT / "smoke.cameo", xs)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
