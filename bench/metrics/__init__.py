"""Per-layer metrics: one reader per metric, found by the metric's name.

``metrics/<name>.py`` defines ``read(r: Readings) -> float | None``.  A
reader that finds nothing to read returns ``None`` and the metric is left
out of the run's line; a share of a roofline or a peak is never given as 0.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Optional, Tuple

HERE = Path(__file__).resolve().parent


@dataclass
class Readings:
    """What a traced run hands its readers.

    ``trace`` is the reduced profiler trace of the traced part of the
    window.  ``obs_window`` and ``obs_traced`` are the program's telemetry
    (``repro.obs``) accumulated over the whole measured window and over the
    traced part alone: ``counters`` by name, ``hists`` as ``(count, sum)``
    by name.  ``notes`` collects what a reader wants printed beside its
    value (such as which roofline bound applies).  ``window`` holds the
    cell runner's own host-clock readings of the window
    (``Cell.window_stats``).
    """

    device_kind: str
    trace: Optional[object]
    obs_window: Dict[str, dict]
    obs_traced: Dict[str, dict]
    window: Dict[str, float] = field(default_factory=dict)
    notes: Dict[str, object] = field(default_factory=dict)

    def counter(self, name: str, traced: bool = False) -> float:
        obs = self.obs_traced if traced else self.obs_window
        return obs["counters"].get(name, 0)

    def hist(self, name: str, traced: bool = False) -> Tuple[int, float]:
        obs = self.obs_traced if traced else self.obs_window
        return obs["hists"].get(name, (0, 0.0))


def path(name: str) -> Path:
    return HERE / f"{name}.py"


def reader(name: str):
    """The ``read`` function of metric ``name`` (file names may hold dots,
    so the file is loaded by path)."""
    p = path(name)
    if not p.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {p}")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics._{name.replace('.', '_')}", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
