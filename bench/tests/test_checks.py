"""What decides ``correct`` must fail when the timed path is wrong.

The controls (``bench/controls.py``: the configuration's float64 contract
computed in float32, with the ingest buffer in float32 or kept in float64)
and each fault the served cell can have, planted in the program from the
start of the measured window, must each turn a tiny run's ``correct``
false.  Faults: a push acknowledged with the store left unchanged; half of
each push left out; a push acknowledged without its journal record; an
answer altered where it is produced (the store's decode, a query's value,
the deviation the compressor reports).
"""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests.cells import SERVED, run_tiny, tiny


def _control(monkeypatch, name, control):
    from repro.core import streaming
    # the control switches itself on for the process; undo it afterwards
    monkeypatch.setattr(streaming, "compress_rounds",
                        streaming.compress_rounds)
    out = run_tiny(tiny(name), control=control)
    assert out["correct"] is False
    return out["checks"], {k for k, c in out["checks"].items()
                           if c["value"] > c["limit"]}


@pytest.mark.parametrize("name", [SERVED])
def test_control_in_float32_is_not_correct(monkeypatch, name):
    checks, failed = _control(monkeypatch, name, "float32")
    assert {"kept_gap", "window_dev_report_gap"} <= failed, checks


@pytest.mark.parametrize("name", [SERVED])
def test_control_in_compute32_is_not_correct(monkeypatch, name):
    checks, failed = _control(monkeypatch, name, "compute32")
    assert "window_dev_report_gap" in failed, checks
    # the points stored are the written ones: only the computation is in
    # float32
    assert checks["kept_gap"]["value"] == 0.0


def _in_window(monkeypatch, patches):
    """Apply ``patches`` (``[(obj, attr, value)]``) from the start of the
    cell's measured window on (set-up runs unbroken)."""
    import bench.entries.server as srv_entry
    cls = srv_entry.Cell
    window = cls.window

    def faulty(self, hooks):
        for obj, attr, value in patches:
            monkeypatch.setattr(obj, attr, value)
        return window(self, hooks)
    monkeypatch.setattr(cls, "window", faulty)


def _unchanged():
    from repro.server.ingest_server import ServerSession
    return [(ServerSession, "push", lambda self, chunk: 0)]


def _half():
    from repro.server.ingest_server import ServerSession
    push = ServerSession.push
    return [(ServerSession, "push",
             lambda self, chunk: push(self, chunk[:len(chunk) // 2]))]


def _unjournaled():
    from repro.store.wal import WriteAheadLog
    return [(WriteAheadLog, "append_push", lambda self, rec: None)]


def _altered_decode():
    """The store's read path returns one altered point (the served path
    stores the window's original points, not the compressor's decode)."""
    from repro.store.store import CameoStore
    read_window = CameoStore.read_window

    def altered(self, sid, a, b, col=None):
        out = read_window(self, sid, a, b, col=col).copy()
        if len(out) > 2:
            out[len(out) // 2] += 1.0
        return out
    return [(CameoStore, "read_window", altered)]


def _altered_answer():
    import repro.store.query as query
    q = query.query

    def altered(*a, **kw):
        val, bound = q(*a, **kw)
        return np.asarray(val) + 1.0, bound
    return [(query, "query", altered)]


def _misreported():
    """The compressor reports a window's deviation a millionth off."""
    from repro.core import streaming
    compress_rounds = streaming.compress_rounds

    def altered(x, cfg, **kw):
        res = compress_rounds(x, cfg, **kw)
        return res._replace(deviation=res.deviation * (1 + 1e-6))
    return [(streaming, "compress_rounds", altered)]


# each fault with the check that catches it
CASES = [(SERVED, "unchanged", "lost_points"),
         (SERVED, "half", "lost_points"),
         (SERVED, "altered_decode", "interp_gap"),
         (SERVED, "altered_answer", "query_err_over_bound"),
         (SERVED, "unjournaled", "unjournaled_pushes"),
         (SERVED, "misreported", "window_dev_report_gap")]
FAULTS = {"unchanged": _unchanged, "half": _half, "unjournaled": _unjournaled,
          "altered_decode": _altered_decode,
          "altered_answer": _altered_answer, "misreported": _misreported}


@pytest.mark.parametrize("name,fault,check", CASES)
def test_fault_is_not_correct(monkeypatch, name, fault, check):
    _in_window(monkeypatch, FAULTS[fault]())
    out = run_tiny(tiny(name), seconds=1.5)
    assert out["correct"] is False
    chk = out["checks"][check]
    assert chk["value"] > chk["limit"], out["checks"]
