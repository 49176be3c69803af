"""The one-shot archive cell on the CPU, cut to a size a test can hold:
its parts found by name, one whole run with every end-to-end metric the
harness assigns it, and what decides ``correct`` failing when the float32
control runs or when a fault is planted in the window."""
from __future__ import annotations

import math

import numpy as np
import pytest

from bench import entries, metrics
from bench import run as bench_run
from bench.entries import archive
from bench.tests.cells import run_tiny

ARCHIVE = "archive_uk_elec.oneshot"


def tiny_archive() -> dict:
    c = bench_run.load_cell(ARCHIVE)
    c["config"]["series_points"] = 1024
    c["config"]["cameo"].update(lags=8)
    c["config"]["open"].update(block_len=256)
    c["traffic"].update(history_series=2,
                        query_points=[10, 1024], query_interval_ms=50,
                        trace_offset_s=0.5, trace_s=1.0)
    return c


def test_archive_parts_found_by_name():
    c = bench_run.load_cell(ARCHIVE)
    assert c["config"]["name"] == c["cell"]["config"] == "archive_uk_elec"
    assert entries.load(c["config"]["entry"]).__name__ == "Cell"
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert {m["name"] for m in c["per_layer"]} == {
        "write.device_ms_per_round", "write.rank_ms_per_round",
        "write.select_ms_per_round", "write.update_ms_per_round",
        "write.outside_rounds_ms", "archive.idle_pct",
        "archive.window_rows_roofline", "archive.query_program_ms",
        "archive.blocks_per_query", "archive.cache_hit_pct"}
    for m in c["per_layer"]:
        assert callable(metrics.reader(m["name"]))
        assert m["moves"] in e2e


@pytest.mark.parametrize("seeds", [(1, 2), (3000016001, 2147483659)])
def test_archive_writer_order(seeds):
    """The series the ratio is taken over lead in corpus order on every
    seed; the seed orders the rest."""
    lead = bench_run.load_cell(ARCHIVE)["traffic"]["ratio_series"]
    a, b = (archive.writer_order(48, lead, s) for s in seeds)
    assert a[:lead] == b[:lead] == list(range(lead))
    assert sorted(a) == sorted(b) == list(range(48))
    assert a[lead:] != b[lead:]


def test_archive_result_line():
    c = tiny_archive()
    out = run_tiny(c, seconds=3.0)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # every end-to-end metric the harness assigns the cell is on the line
    assert set(out["metrics"]) == {m["name"] for m in c["end_to_end"]}
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert out["checks"]["kept_gap"]["value"] == 0.0
    assert list(out)[-1] == "checks"


def test_archive_traced_run_reads_program_metrics():
    """A traced run reads every per-layer metric that the program's spans
    and counters feed (the device-trace ones need a chip's trace)."""
    out = run_tiny(tiny_archive(), seconds=3.0, trace=1)
    assert out["correct"] is True, out["checks"]
    assert {"write.outside_rounds_ms", "archive.query_program_ms",
            "archive.blocks_per_query", "archive.cache_hit_pct"} <= set(
        out["metrics"]), out["metrics"]


def test_archive_control_in_float32_is_not_correct():
    out = run_tiny(tiny_archive(), seconds=1.5, control="float32")
    assert out["correct"] is False
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert {"kept_gap", "dev_report_gap"} <= failed, out["checks"]


def _in_window(monkeypatch, patches):
    """Apply ``patches`` from the start of the measured window on."""
    import bench.entries.archive as archive
    window = archive.Cell.window

    def faulty(self, hooks):
        for obj, attr, value in patches:
            monkeypatch.setattr(obj, attr, value)
        return window(self, hooks)
    monkeypatch.setattr(archive.Cell, "window", faulty)


def _unstored():
    """A write returns its entry without storing the series."""
    from repro.api.dataset import Dataset
    return [(Dataset, "write",
             lambda self, sid, x, eps=None: {"deviation": 0.0})]


def _altered_decode():
    from repro.store.store import CameoStore
    read_window = CameoStore.read_window

    def altered(self, sid, a, b, col=None):
        out = read_window(self, sid, a, b, col=col).copy()
        if len(out) > 2:
            out[len(out) // 2] += 1.0
        return out
    return [(CameoStore, "read_window", altered)]


def _misreported():
    """The write reports its deviation a millionth off."""
    from repro.api.dataset import Dataset
    write = Dataset.write

    def altered(self, sid, x, eps=None):
        entry = dict(write(self, sid, x, eps=eps))
        entry["deviation"] *= 1 + 1e-6
        return entry
    return [(Dataset, "write", altered)]


def _altered_answer():
    import repro.store.query as query
    q = query.query

    def altered(*a, **kw):
        val, bound = q(*a, **kw)
        return np.asarray(val) + 1.0, bound
    return [(query, "query", altered)]


@pytest.mark.parametrize("fault,check", [
    (_unstored, "lost_series"), (_altered_decode, "interp_gap"),
    (_misreported, "dev_report_gap"),
    (_altered_answer, "query_err_over_bound")])
def test_archive_fault_is_not_correct(monkeypatch, fault, check):
    _in_window(monkeypatch, fault())
    out = run_tiny(tiny_archive(), seconds=1.5)
    assert out["correct"] is False
    chk = out["checks"][check]
    assert chk["value"] > chk["limit"], out["checks"]
