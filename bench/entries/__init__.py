"""Runners of a deployment's entry point, one module per entry.

``entries/<entry>.py`` (the configuration's ``"entry"``) defines ``Cell``:
``Cell(config, traffic, seed, seconds, workdir, traced)`` with

* ``setup()``: builds the deployment and warms every program the window
  will run, from ``seed`` alone;
* ``window(hooks) -> dict``: drives the traffic for ``seconds`` and
  returns the end-to-end metrics (``hooks.trace_begin()`` and
  ``hooks.trace_end()`` bracket the traced part in a traced run; they
  take telemetry snapshots and start and stop the profiler);
* ``attempted``, ``failed``: requests started in the window, and those
  that raised; ``window_stats``: host-clock readings of the window that
  per-layer readers take (``Readings.window``);
* ``check() -> (checks, lines, metrics)``: after the window, compares
  what the window produced with the plain reference (``reference.py``);
  ``checks`` maps each compared number's name to ``(value, limit)``, a
  number passing when ``value <= limit``; ``lines`` are records printed
  before the result; ``metrics`` are end-to-end metrics counted from the
  read-back (``compression_ratio``).
"""
from __future__ import annotations

import importlib
import math

import numpy as np


def load(entry: str):
    return importlib.import_module(f"bench.entries.{entry}").Cell


def p95(values) -> float:
    """95th percentile (linear interpolation between order statistics)."""
    if not len(values):
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), 95))


def within(got, want, bound) -> float:
    """Largest ``|got - want| / bound`` over elements (0 where both the
    error and the bound are 0, infinite where only the bound is)."""
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    bound = np.broadcast_to(np.asarray(bound, np.float64), err.shape)
    ratio = np.where(err == 0, 0.0,
                     np.where(bound > 0, err / np.where(bound > 0, bound, 1),
                              np.inf))
    return float(np.max(ratio)) if ratio.size else 0.0
