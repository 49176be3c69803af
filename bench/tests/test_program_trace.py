"""The program's marks in a recorded trace (``bench/program_trace.py``) and
the readers built on them, on the CPU: op scopes from the event metadata's
``tf_op`` stats, program spans picked by name, the phase split of the
rounds program, idle gaps named by span, finding the recorded profile of a
run, and the three readers of program spans."""
from __future__ import annotations

import json
import os

import pytest

from bench import metrics, program_trace as pt
from bench import trace as tr

DEV = "/device:TPU:0"
ROUNDS = "jit(_rounds_padded)/while/body"
SPANS = ("server.push", "stream.window", "stream.window.aggregates",
         "wal.append")


def _xspace(window_us=(0, 100), scoped=True):
    """A device plane whose rounds-program run (10-50 us) holds a loop with
    three operations, one per phase, each with its ``tf_op`` path on its
    event metadata as a TPU trace keeps it: the ranking kernel (8 us), a
    selection fusion (6 us) and an update fusion whose path is a reference
    to a stat name (10 us); the loop's own path has no phase.  After the
    run, a gap (50-70 us) and an operation of no program.  The host plane
    holds the traced span, the benchmark's push span over the gap and the
    program's spans nested inside it, and a program span with arguments
    after a ``#``."""
    kernel = ('%window_rows_pallas.4 = f32[48,256]{1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    names = {1: "%while.1 = (f32[8]) while(%x)", 2: kernel,
             3: "%fusion.2 = f64[8] fusion(%y)", 4: "%fusion.3 = f32[8]",
             5: "jit__rounds_padded(123)", 6: "bench.traced",
             7: "bench.push", 8: "%fusion.5 = f32[8] fusion(%z)",
             9: "server.push", 10: "stream.window",
             11: "stream.window.aggregates", 12: "wal.append#sid=3#",
             13: "jax.host_op"}
    tf_op = lambda path: f'stats {{ metadata_id: 20 str_value: "{path}:" }}'
    meta_stats = {
        1: tf_op(ROUNDS[:-5]),
        2: tf_op(f"{ROUNDS}/select/rank/window_rows_pallas/pallas_call"),
        3: "stats { metadata_id: 20 ref_value: 21 }",
        8: tf_op(f"{ROUNDS}/select/top_k")}
    if not scoped:
        meta_stats = {1: tf_op(ROUNDS[:-5]), 3: tf_op(f"{ROUNDS}/gather")}

    def ev(mid, off_us, dur_us):
        return (f"events {{ metadata_id: {mid} offset_ps: {int(off_us * 1e6)}"
                f" duration_ps: {int(dur_us * 1e6)} }}")
    meta = " ".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: {json.dumps(v)} '
        f'{meta_stats.get(k, "")} }} }}' for k, v in names.items())
    stat_meta = ('stat_metadata { key: 20 value { id: 20 name: "tf_op" } } '
                 'stat_metadata { key: 21 value { id: 21 name: '
                 f'"{ROUNDS}/select/update/gather:" }} }}')
    lo, hi = window_us
    return f"""
planes {{ id: 1 name: "{DEV}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 10, 40)} {ev(2, 12, 8)} {ev(8, 22, 6)} {ev(3, 30, 10)}
    {ev(4, 70, 20)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {ev(5, 10, 40)} }}
  {meta} {stat_meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 0
    {ev(6, lo, hi - lo)} {ev(7, 52, 15)} {ev(9, 52.5, 14)} {ev(10, 53, 13)}
    {ev(11, 54, 12)} {ev(12, 91, 2)} {ev(13, 1, 2)} }}
  {meta} }}
"""


def _recorded(text):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    return ProfileData.from_serialized_xspace(raw), raw


def _readings(t, rounds=4.0, window=None):
    return metrics.Readings(
        device_kind="TPU v5 lite", trace=t,
        obs_window=window or {"counters": {}, "hists": {}},
        obs_traced={"counters": {},
                    "hists": {"stream.window_rounds": (1, rounds)}})


def test_marks_scopes_and_spans():
    pd, raw = _recorded(_xspace())
    m = pt.from_profile(pd, raw, names=SPANS)
    assert [s[0] for s in m.spans] == ["server.push", "stream.window",
                                       "stream.window.aggregates",
                                       "wal.append"]
    labels = [n for n, _, _ in m.ops[DEV]]
    assert labels[1:4] == ["rank", "select", "update"]
    assert labels[0].startswith("%while.1") and labels[4].startswith(
        "%fusion.3")
    assert m.phased
    # the paths live on the event metadata, which only the file holds
    tf_ops = pt.metadata_tf_ops(raw)[DEV]
    assert tf_ops["%while.1 = (f32[8]) while(%x)"] == ROUNDS[:-5] + ":"
    assert tf_ops["%fusion.2 = f64[8] fusion(%y)"].endswith("/gather:")
    assert not pt.from_profile(pd, None, SPANS).phased
    assert pt.phase_of("a/update/b/select/rank/while/body") == "rank"
    assert pt.phase_of("jit(f)/while/body/add") is None


def test_rounds_phase_readers_on_a_recorded_trace():
    pd, raw = _recorded(_xspace())
    t = tr.from_profile(pd)
    t.program_marks = pt.from_profile(pd, raw, names=SPANS)
    r = _readings(t)
    per = {p: metrics.reader(f"rounds.{p}_ms_per_round")(r)
           for p in pt.PHASES}
    # self time in the 40-us run over 4 rounds; the loop keeps 16 us
    assert per == {"rank": pytest.approx(0.002),
                   "select": pytest.approx(0.0015),
                   "update": pytest.approx(0.0025)}
    note = r.notes["rounds.phases"]
    total = metrics.reader("rounds.device_ms_per_round")(r)
    assert note["device_ms_per_round"] == pytest.approx(total)
    assert note["residual_ms_per_round"] == pytest.approx(0.004)
    assert sum(per.values()) + note["residual_ms_per_round"] == \
        pytest.approx(total)
    assert note["share_pct"]["residual"] == pytest.approx(40.0)
    assert note["residual_gaps_ms_per_round"] == pytest.approx(0.0)
    assert note["residual_top"][0][0].startswith("while.1")
    # the gap inside the push is named by the innermost program span that
    # covers most of it, the last by the one covering most, the first
    # (before the run) by none
    gaps = r.notes["idle_gaps"]
    assert [g[0].split(" @")[0] for g in gaps] == [
        "stream.window.aggregates", "host:none", "wal.append"]
    assert gaps[0][1] == pytest.approx(20e-6)
    assert gaps[0][2] == {"bench.push": 75.0, "server.push": 70.0,
                          "stream.window": 65.0,
                          "stream.window.aggregates": 60.0}


def test_idle_gap_of_many_short_spans_is_named_by_them():
    """A gap between two compressions holds many short pushes, none of
    which covers half of it: it is named by the innermost span name whose
    spans together do."""
    t = tr.Trace(window=(0.0, 100.0),
                 ops={DEV: [("%a", 0.0, 10.0), ("%b", 90.0, 100.0)]},
                 host=[("bench.push", 10.0 + 8 * i, 16.0 + 8 * i)
                       for i in range(10)])
    m = pt.Marks(spans=[("server.push", 10.5 + 8 * i, 15.5 + 8 * i)
                        for i in range(10)])
    (gap,) = pt.idle_gaps(t, m, DEV)
    assert gap[0] == "server.push @0.000s" and gap[1] == pytest.approx(8e-8)
    assert gap[2] == {"bench.push": 75.0, "server.push": 62.5}


def test_rounds_phase_readers_find_nothing_without_scopes():
    """A program older than its scopes and spans: the readers return
    ``None`` and note nothing, and the gaps keep no program span."""
    pd, raw = _recorded(_xspace(scoped=False))
    t = tr.from_profile(pd)
    t.program_marks = pt.from_profile(pd, raw, names=())
    r = _readings(t)
    for p in pt.PHASES:
        assert metrics.reader(f"rounds.{p}_ms_per_round")(r) is None
    assert "rounds.phases" not in r.notes and "idle_gaps" not in r.notes
    assert metrics.reader("rounds.device_ms_per_round")(r) == \
        pytest.approx(0.01)


def test_recorded_profile_found_by_its_traced_span(tmp_path):
    """A run's profile is found under its work directory by the window of
    its ``bench.traced`` span; another run's profile is passed over."""
    for run, window in (("bench-a", (0, 100)), ("bench-b", (0, 99))):
        d = tmp_path / run / "trace" / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(
            _recorded(_xspace(window_us=window))[1])
    os.utime(tmp_path / "bench-b" / "trace" / "plugins" / "profile" / "x"
             / "host.xplane.pb", (2e9, 2e9))        # the newest
    t = tr.from_profile(_recorded(_xspace())[0])
    m = pt.recorded(t, root=str(tmp_path))
    assert m is not None and m.phased
    assert pt.recorded(tr.from_profile(_recorded(
        _xspace(window_us=(0, 98)))[0]), root=str(tmp_path)) is None


def test_program_span_readers_by_hand():
    hists = {"span.server.push.seconds": (10, 0.05),
             "span.stream.window.seconds": (2, 0.03),
             "span.stream.window.rounds.seconds": (2, 0.02),
             "span.store.append.seconds": (2, 0.004),
             "span.query.seconds": (4, 0.002)}
    r = _readings(None, window={"counters": {}, "hists": hists})
    # (30 - 20 + 4) ms over 2 windows; (50 - 30 - 4) ms over 10 pushes
    assert metrics.reader("window.outside_rounds_ms")(r) == \
        pytest.approx(7.0)
    assert metrics.reader("push.path_ms")(r) == pytest.approx(1.6)
    assert metrics.reader("query.program_ms")(r) == pytest.approx(0.5)
    empty = _readings(None)
    for name in ("window.outside_rounds_ms", "push.path_ms",
                 "query.program_ms"):
        assert metrics.reader(name)(empty) is None
