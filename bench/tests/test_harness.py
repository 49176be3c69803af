"""The benchmark harness on the CPU: finding a cell's parts by name, the
trace reduction, the roofline arithmetic, the numpy reference, the result
line's schema and the refusal without a TPU."""
from __future__ import annotations

import json
import math
import re
from pathlib import Path

import numpy as np
import pytest

from bench import entries, journal, metrics, reference, roofline, run as bench_run
from bench import trace as tr
from bench.kernels import window_rows
from bench.tests.cells import SERVED, cell, run_tiny, tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# -- finding the parts by name ------------------------------------------------

@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cell_parts_found_by_name(name):
    c = bench_run.load_cell(name)
    assert c["config"]["name"] == c["cell"]["config"]
    assert entries.load(c["config"]["entry"]).__name__ == "Cell"
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    for m in c["per_layer"]:
        assert callable(metrics.reader(m["name"]))
        assert m["moves"] in e2e


def test_unknown_cell_refused(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.load_cell("no_such.cell")
    assert e.value.code == 2
    assert capsys.readouterr().out == ""


def test_spec_names_and_files():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


# -- trace reduction ------------------------------------------------------------

def _xspace():
    """A device plane with a loop holding two operations (one of them the
    ranking kernel), a gap, a third operation, one program run, and a host
    plane with the traced span and a push span over the gap."""
    kernel = ('%window_rows_pallas.4 = f32[48,256]{1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call", operand_layout_'
              'constraints={f32[104,256]{1,0}, f32[104,256]{1,0}, '
              'f32[56,256]{1,0}, s32[1,256]{1,0}, f32[48,8]{1,0}, '
              's32[1,128]{1,0}}, frontend_attributes={kernel_metadata={}}')
    names = {1: "%while.1 = (f32[8]) while(%x)", 2: kernel,
             3: "%fusion.2 = f32[8] fusion(%y)", 4: "%fusion.3 = f32[8]",
             5: "jit__rounds_padded(123)", 6: "bench.traced",
             7: "bench.push"}
    ev = lambda mid, off_us, dur_us: (
        f"events {{ metadata_id: {mid} offset_ps: {int(off_us * 1e6)} "
        f"duration_ps: {int(dur_us * 1e6)} }}")
    meta = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                    f'name: {json.dumps(v)} }} }}' for k, v in names.items())
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 10, 40)} {ev(2, 12, 8)} {ev(3, 30, 10)} {ev(4, 70, 20)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {ev(5, 10, 40)} }}
  {meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 0
    {ev(6, 0, 100)} {ev(7, 52, 15)} }}
  {meta} }}
"""


def test_trace_reduction_on_a_recorded_trace():
    from jax.profiler import ProfileData
    t = tr.from_profile(ProfileData.from_text_proto(_xspace()))
    dev = "/device:TPU:0"
    assert t.window == (0.0, 100_000.0)
    # busy: [10, 50] and [70, 90] microseconds of a 100 us window
    assert t.busy_s(dev) == pytest.approx(60e-6)
    assert tr.gaps(t.ops[dev], *t.window) == [(0.0, 10_000.0),
                                              (50_000.0, 70_000.0),
                                              (90_000.0, 100_000.0)]
    self_ns = {tr.instruction_name(k): v
               for k, v in tr.self_times(t.ops[dev], *t.window).items()}
    assert self_ns == {"while.1": 22_000.0, "window_rows_pallas.4": 8_000.0,
                       "fusion.2": 10_000.0, "fusion.3": 20_000.0}
    gaps = tr.idle_breakdown(t, dev)
    assert gaps[0][0].startswith("bench.push") and gaps[0][1] == \
        pytest.approx(20e-6)
    r = metrics.Readings(device_kind="TPU v5 lite", trace=t,
                         obs_window={"counters": {}, "hists": {}},
                         obs_traced={"counters": {},
                                     "hists": {"stream.window_rounds": (1, 4.0)}})
    assert metrics.reader("device.idle_pct")(r) == pytest.approx(40.0)
    assert metrics.reader("rounds.device_ms_per_round")(r) == \
        pytest.approx(0.04 / 4)
    # the kernel: 8 us of device time for the work its shapes give
    flops, nbytes = window_rows.cost(*[tr.operand_shapes(names)
                                       for names in [t.ops[dev][1][0]]],
                                     tr.result_shape(t.ops[dev][1][0]))
    pct = metrics.reader("window_rows_roofline")(r)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 8e-6
    assert pct == pytest.approx(want)
    assert r.notes["window_rows_roofline"]["bound"] == "bytes"


def test_readers_find_nothing_and_say_so():
    r = metrics.Readings(device_kind="TPU v5 lite", trace=None,
                         obs_window={"counters": {}, "hists": {}},
                         obs_traced={"counters": {}, "hists": {}})
    for m in SPEC["per_layer"]:
        assert metrics.reader(m["name"])(r) is None


# -- roofline and peaks -----------------------------------------------------------

def test_window_rows_cost_by_hand():
    ops = [("f32", (160, 128)), ("f32", (160, 128)), ("f32", (112, 128)),
           ("s32", (1, 128)), ("f32", (48, 8)), ("s32", (1, 128))]
    flops, nbytes = window_rows.cost(ops, ("f32", (48, 128)))
    W = 112 - 48
    assert flops == 48 * 128 * (8 * W + 17) + 3 * 128 * W
    assert nbytes == 4 * 128 * (160 + 160 + 112 + 1) + 4 * 48 * 8 \
        + 4 * 128 + 4 * 48 * 128


def test_roofline_share_and_bound():
    pct, bound = roofline.share(197e12, 1.0, 2.0, "TPU v5 lite")
    assert (pct, bound) == (pytest.approx(50.0), "flops")
    pct, bound = roofline.share(1.0, 819e9, 4.0, "TPU v5 lite")
    assert (pct, bound) == (pytest.approx(25.0), "bytes")


def test_unknown_device_raises():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("cpu")


# -- the numpy reference ------------------------------------------------------------

def test_acf_reference_by_hand():
    y = np.array([1.0, 2.0, 3.0, 4.0])
    # lag 1: (1,2,3) against (2,3,4): perfectly correlated
    # lag 2: (1,2) against (3,4): perfectly correlated
    assert reference.acf_np(y, 2) == pytest.approx([1.0, 1.0])
    z = np.array([1.0, -1.0, 1.0, -1.0, 1.0])
    assert reference.acf_np(z, 2) == pytest.approx([-1.0, 1.0])
    # against the alternating (-1, +1) ACF: gaps 2 and 0 over two lags
    assert reference.acf_deviation_np(y, z[:4], 2, 1) == pytest.approx(1.0)
    # kappa 2 means of (1,2,3,4,5,6) are (1.5, 3.5, 5.5)
    assert list(reference.aggregate(np.arange(1.0, 7.0), 2)) == [1.5, 3.5,
                                                                 5.5]
    assert reference.acf_np(np.ones(5), 1) == [0.0]


def test_decode_gaps():
    x = np.array([0.0, 5.0, 2.0, 3.0, 4.0])
    idx, vals = np.array([0, 2, 4]), x[[0, 2, 4]]
    xr = reference.interpolate(idx, vals, len(x))
    assert list(xr) == [0.0, 1.0, 2.0, 3.0, 4.0]
    g = reference.decode_gaps(x, idx, vals, xr)
    assert g == {"kept_gap": 0.0, "interp_gap": 0.0}
    # relative to the largest magnitude, 5
    assert reference.decode_gaps(x, idx, vals + [0, 1e-3, 0], xr)[
        "kept_gap"] == pytest.approx(2e-4)


# -- the journal reader -------------------------------------------------------------

def _record(payload: bytes) -> bytes:
    import struct
    import zlib
    return struct.pack("<II", len(payload), zlib.crc32(payload)) + payload


def _push(sid: str, start: int, values) -> bytes:
    import struct
    sid_b = sid.encode()
    v = np.asarray(values, "<f8")
    return (struct.pack("<BBH", 2, 0, len(sid_b)) + sid_b
            + struct.pack("<QIH", start, len(v), 0) + v.tobytes())


def test_journal_reader_by_hand(tmp_path):
    p = tmp_path / "s.wal"
    good = _push("t000/feed", 96, [1.5, -2.0])
    torn = _record(_push("t000/feed", 98, [3.0]))
    p.write_bytes(journal.MAGIC + _record(b"\x01checkpoint")
                  + _record(good) + _record(_push("t001/feed", 0, [7.0]))
                  + torn[:-1] + b"\x00")
    got = journal.pushes(str(p))
    assert set(got) == {"t000/feed", "t001/feed"}
    assert list(got["t000/feed"]) == [96]
    assert list(got["t000/feed"][96]) == [1.5, -2.0]
    p.write_bytes(b"NOTAWAL\x00\x01")
    with pytest.raises(ValueError):
        journal.pushes(str(p))


def test_journal_reader_reads_the_program_journal(tmp_path):
    """The benchmark's reader and the program's writer agree on the
    format (the program's own scan is the second witness)."""
    import sys
    sys.path.insert(0, str(ROOT / "src"))
    from repro.store import wal
    path = str(tmp_path / "s.wal")
    log = wal.WriteAheadLog.start(path, wal.Checkpoint(3, 0, {}, b""), (),
                                  group_ms=0.0, group_bytes=0)
    x = np.linspace(-1.0, 1.0, 10)
    log.append_push(wal.PushRecord("a/feed", 0, x[:4]))
    log.append_push(wal.PushRecord("a/feed", 4, x[4:]))
    log.close()
    got = journal.pushes(path)
    assert list(got) == ["a/feed"] and sorted(got["a/feed"]) == [0, 4]
    assert np.array_equal(np.concatenate([got["a/feed"][0],
                                          got["a/feed"][4]]), x)
    assert [(r.sid, r.start) for r in wal.scan(path).pushes] == \
        [("a/feed", 0), ("a/feed", 4)]


# -- a whole run ----------------------------------------------------------------------

def test_refused_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", SERVED, "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert e.value.code == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


@pytest.mark.parametrize("name", [SERVED])
def test_result_line_schema(name):
    out = run_tiny(tiny(name))
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"]
                                   for m in cell(name)["end_to_end"]}
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for chk in out["checks"].values():
        assert chk["value"] <= chk["limit"]
    json.dumps(out)
