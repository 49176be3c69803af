"""Run one cell of the benchmark once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``bench/configs/<config>.json``)
and a traffic mix (``bench/traffic/<traffic>.json``); the configuration's
``entry`` names the runner (``bench/entries/<entry>.py``).  The run holds
one process on the machine's TPU and fails, printing no result, without
one.  It sets up and warms the deployment from ``--seed``, measures for
``--seconds``, checks what the window produced against the plain numpy
reference (``bench/reference.py``), and prints as its last line one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` a ``breakdown``), and last ``checks``, each number
compared with its limit.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of part of the window.  Earlier lines carry the set-up's
compile accounting, the compiles inside the window (there should be
none), the rounds of each compression and any flag.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(SystemExit):
    """The run cannot stand: no result is printed, the exit code is 2."""

    def __init__(self, msg: str):
        print(f"bench.run: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def say(obj) -> None:
    print(json.dumps(obj, default=float), flush=True)


def load_spec() -> dict:
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        raise Refused(f"no BENCHMARK.json at {ROOT}")
    return json.loads(spec_path.read_text())


def assemble(cell: dict, spec: dict) -> dict:
    """A cell entry with its configuration, traffic and metrics (those that
    list it, and those that list no cells)."""
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())

    def mine(m):
        return cell["name"] in m.get("workloads", [cell["name"]])
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def load_cell(name: str) -> dict:
    """The cell ``name`` of ``BENCHMARK.json``, assembled."""
    spec = load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; known: {sorted(cells)}")
    return assemble(cells[name], spec)


def require_chips(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {d0.platform} "
                      f"({d0.device_kind})")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips, "devices": devs[:chips]}


def memory_peak(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def obs_state() -> dict:
    """The program's telemetry now: counters, and ``(count, sum)`` of each
    histogram."""
    from repro import obs
    snap = obs.OBS.snapshot()
    return {"counters": snap["counters"],
            "hists": {k: (h["count"], h["sum"])
                      for k, h in snap["histograms"].items()}}


def obs_delta(a: dict, b: dict) -> dict:
    return {"counters": {k: v - a["counters"].get(k, 0)
                         for k, v in b["counters"].items()},
            "hists": {k: (c - a["hists"].get(k, (0, 0.0))[0],
                          s - a["hists"].get(k, (0, 0.0))[1])
                      for k, (c, s) in b["hists"].items()}}


class Hooks:
    """Brackets the traced part of a window: telemetry snapshots, the
    profiler, and the ``bench.traced`` host span that marks the part."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.obs = {}
        self._span = None

    def trace_begin(self):
        import jax
        from bench import trace as tr
        tr.start(self.log_dir)
        self.obs["begin"] = obs_state()
        self._span = jax.profiler.TraceAnnotation(tr.TRACED_SPAN)
        self._span.__enter__()

    def trace_end(self):
        from bench import trace as tr
        self._span.__exit__(None, None, None)
        self.obs["end"] = obs_state()
        tr.stop()


def run(args, on_chip: bool = True, cell: dict = None) -> dict:
    """One run; returns the result line (``main`` prints it).  Tests call
    it on the CPU with ``on_chip=False``, which skips the look for a chip
    and the persistent compile cache, and with a cell of their own
    (``load_cell``'s form) at a size a test can hold."""
    c = cell or load_cell(args.workload)
    cell, config, traffic = c["cell"], c["config"], c["traffic"]
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"{ROOT} holds no program (src/repro)")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    jax.config.update("jax_enable_x64", True)
    if on_chip:
        device = require_chips(int(cell["chips"]))
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": 1, "devices": jax.devices()[:1]}
    from repro import obs
    from repro.compile_cache import enable_compile_cache
    from bench import accounting
    from bench import entries
    if getattr(args, "control", None):
        from bench import controls
        controls.apply(args.control, config)
    cache_dir = None
    if on_chip:
        cache_dir = enable_compile_cache()
        # every program, however small, goes to the persistent cache, so
        # that a second run in a checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    accounting.install()
    obs.enable()

    with tempfile.TemporaryDirectory(prefix="bench-") as work:
        Cell = entries.load(config["entry"])
        run_cell = Cell(config, traffic, args.seed, args.seconds, work,
                        bool(args.trace))
        a0 = accounting.snapshot()
        run_cell.setup()
        setup_s = time.perf_counter() - T_START
        a1 = accounting.snapshot()
        say({"setup": {"seconds": setup_s, "compile_cache": cache_dir,
                       **accounting.delta(a0, a1)}})
        hooks = Hooks(os.path.join(work, "trace"))
        o0 = obs_state()
        e2e = run_cell.window(hooks)
        o1 = obs_state()
        a2 = accounting.snapshot()
        in_window = accounting.delta(a1, a2)
        say({"window": {"compiles": in_window}})
        peak = memory_peak(device["devices"])
        checks, lines, more = run_cell.check()
        e2e.update(more)
        for line in lines:
            say(line)

        result_device = {"platform": device["platform"],
                         "kind": device["kind"], "count": device["count"],
                         "memory_peak_bytes": peak}
        out = {"correct": None, "attempted": int(run_cell.attempted),
               "failed": int(run_cell.failed)}
        if args.trace:
            metrics, breakdown, dev_extra = per_layer(
                c["per_layer"], hooks, o0, o1, device["kind"],
                run_cell.window_stats)
            result_device.update(dev_extra)
            out["breakdown"] = breakdown
        else:
            e2e["setup_s"] = setup_s
            metrics = {}
            for m in c["end_to_end"]:
                v = e2e.get(m["name"])
                if v is not None and math.isfinite(v):
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = all(v <= lim for v, lim in checks.values())
    out.update(correct=correct, metrics=metrics, device=result_device)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def per_layer(metrics, hooks: Hooks, o0, o1, device_kind: str,
              window_stats: dict):
    """Reduce the traced part to the cell's per-layer metrics."""
    from bench import metrics as mt
    from bench import trace as tr
    if "begin" not in hooks.obs or "end" not in hooks.obs:
        raise RuntimeError("the window ended before its traced part")
    t = tr.load(hooks.log_dir)
    r = mt.Readings(device_kind=device_kind, trace=t,
                    obs_window=obs_delta(o0, o1),
                    obs_traced=obs_delta(hooks.obs["begin"], hooks.obs["end"]),
                    window=window_stats)
    out = {}
    for m in metrics:
        v = mt.reader(m["name"])(r)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    if r.notes:
        say({"per_layer_notes": r.notes})
    dev = next(iter(t.ops), None)
    breakdown = {"device_ops": tr.op_breakdown(t, dev) if dev else [],
                 "idle_gaps": tr.idle_breakdown(t, dev) if dev else []}
    return out, breakdown, {"busy_s": t.mean_busy_s(),
                            "window_s": t.window_s}


def parse(argv=None):
    from bench import controls
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=controls.NAMES, default=None,
                    help="run a control: the configuration's contract "
                         "one step below the precision it states "
                         "(bench/controls.py; for setting the limits of "
                         "correct, never a cell's run)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    out = run(args)
    for name, chk in out["checks"].items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr, flush=True)
    say(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
