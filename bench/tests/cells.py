"""The benchmark's cell at a size a CPU test run can hold.

``tiny()`` is the served cell (``load_cell``'s form) with the deployment
and the mix cut down: shorter windows, series and lags, fewer sessions, a
window of a few seconds.  ``run_tiny`` drives one whole run of it on the
CPU (no look for a chip) and returns its result line.
"""
from __future__ import annotations

import argparse
import contextlib

from bench import run as bench_run

SERVED = "served_uk_elec.daily64"


def cell(name: str = SERVED) -> dict:
    return bench_run.load_cell(name)


def tiny(name: str = SERVED, **cameo) -> dict:
    c = cell(name)
    c["config"]["cameo"].update(lags=8, **cameo)
    c["config"]["server"].update(stream_window=128, block_len=128,
                                 max_sessions=4)
    c["config"]["feed_points"] = 2048
    c["traffic"].update(sessions=4, history_tenants=2, history_windows=2,
                        query_points=[10, 128], dashboard_clients=2,
                        query_interval_ms=50, trace_offset_s=0.5,
                        trace_s=0.5, check_open_sessions=1)
    return c


@contextlib.contextmanager
def telemetry_restored():
    """A run turns the program's telemetry on; give other tests of the
    worker the state they had."""
    from repro import obs
    was = obs.OBS.enabled
    try:
        yield
    finally:
        obs.OBS.reset()
        if not was:
            obs.OBS.disable()


def run_tiny(cell: dict, seed: int = 2**33 + 5, seconds: float = 2.0,
             trace: int = 0, control: str = None) -> dict:
    args = argparse.Namespace(workload=cell["cell"]["name"], seed=seed,
                              seconds=seconds, trace=trace, control=control)
    with telemetry_restored():
        return bench_run.run(args, on_chip=False, cell=cell)
