"""Profiler traces: recording a part of the window, and reducing it.

A traced run records one part of its measured window with the JAX
profiler.  The benchmark marks that part with a host span named
``bench.traced`` and marks its own calls into the system with ``bench.*``
spans; both land in the profiler's trace on the same clock as the device's
operations.  The reduction here turns the trace into plain lists that the
per-layer readers (``metrics/``) and the breakdown read:

* device operations from each device plane's ``XLA Ops`` line (the HLO
  instruction text is the event's name) and program runs from its
  ``XLA Modules`` line;
* busy time as the union of the operation intervals (operations nest:
  a loop's event covers its body's events), idle gaps between them;
* host spans of the benchmark (``bench.*``), which label the idle gaps.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

TRACED_SPAN = "bench.traced"
SPAN_PREFIX = "bench."

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclass
class Trace:
    """One traced part of a window, reduced to plain lists."""

    window: Tuple[float, float]                      # ns, the traced part
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # device
    host: List[Event] = field(default_factory=list)  # bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, device: str) -> float:
        return union_ns(self.ops.get(device, []), *self.window) * 1e-9

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the traced devices."""
        if not self.ops:
            return 0.0
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def start(log_dir: str) -> None:
    """Start the profiler without its Python tracer (whose cost would be a
    large share of a host-bound window); host spans still record."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`.  The
    traced part is the ``bench.traced`` host span; without one, the span
    of all device operations."""
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    traced = [h for h in host if h[0] == TRACED_SPAN]
    if traced:
        window = (traced[0][1], traced[0][2])
    else:
        evs = [e for v in ops.values() for e in v]
        window = ((min(e[1] for e in evs), max(e[2] for e in evs))
                  if evs else (0.0, 0.0))
    return Trace(window=window, ops=ops, modules=modules,
                 host=[h for h in host if h[0] != TRACED_SPAN])


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(newest_xplane(log_dir)))


def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def merged(events: List[Event], lo: float, hi: float):
    """The union of the events' intervals inside ``[lo, hi]``, as sorted
    disjoint ``(start, end)`` pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(clip(events, lo, hi), key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(events: List[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(events, lo, hi))


def gaps(events: List[Event], lo: float, hi: float):
    """Idle ``(start, end)`` intervals of ``[lo, hi]`` outside the union."""
    out, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Per-operation time in ns with nested operations' time taken out
    (a loop's own time is what its body's operations leave over)."""
    evs = sorted(clip(events, lo, hi), key=lambda ev: (ev[1], -ev[2]))
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end_ns, child_ns, start_ns]

    def close(frame, end):
        name, _, child, start = frame
        out[name] = out.get(name, 0.0) + (end - start) - child

    for name, s, e in evs:
        while stack and s >= stack[-1][1]:
            frame = stack.pop()
            close(frame, frame[1])
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, s])
    while stack:
        frame = stack.pop()
        close(frame, frame[1])
    return out


_INSTR = re.compile(r"^%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}\s*,\s*\w+=")


def instruction_name(op_text: str) -> str:
    """``fusion.92`` from ``%fusion.92 = f32[...] fusion(...)``."""
    m = _INSTR.match(op_text)
    return m.group(1) if m else op_text.split(" ", 1)[0]


def short_name(op_text: str) -> str:
    """An operation's instruction name with its result type and, where the
    text has one, the tail of its source path, for the breakdown
    (``fusion.92 f32[196608]``)."""
    name = instruction_name(op_text)
    res = result_shape(op_text)
    if res:
        name += f" {res[0]}[{','.join(map(str, res[1]))}]"
    m = _OP_NAME.search(op_text)
    if m:
        name += " <- " + "/".join(m.group(1).split("/")[-3:])
    return name[:160]


def operand_shapes(op_text: str) -> Optional[List[Tuple[str, Tuple[int, ...]]]]:
    """``[(dtype, dims), ...]`` of a custom call's operands, read from its
    ``operand_layout_constraints``; ``None`` where the text has none."""
    m = _OPERANDS.search(op_text)
    if not m:
        return None
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(m.group(1))]


def result_shape(op_text: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    m = re.match(r"^%?[\w.\-]+\s*=\s*([a-z]+\d*)\[([\d,]*)\]", op_text)
    if not m:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def idle_breakdown(trace: Trace, device: str, top: int = 10):
    """The longest idle gaps on ``device``, each named by the benchmark's
    host spans that cover most of it (``host:none`` where none does)."""
    lo, hi = trace.window
    out = []
    for s, e in gaps(trace.ops.get(device, []), lo, hi):
        cover: Dict[str, float] = {}
        for name, hs, he in clip(trace.host, s, e):
            cover[name] = cover.get(name, 0.0) + (he - hs)
        label = (max(cover, key=cover.get) if cover else "host:none")
        out.append((f"{label} @{(s - lo) * 1e-9:.3f}s", (e - s) * 1e-9))
    out.sort(key=lambda g: -g[1])
    return [list(g) for g in out[:top]]


def op_breakdown(trace: Trace, device: str, top: int = 10):
    """The device operations with the most self time, in seconds."""
    st = self_times(trace.ops.get(device, []), *trace.window)
    agg: Dict[str, float] = {}
    for text, ns in st.items():
        key = short_name(text)
        agg[key] = agg.get(key, 0.0) + ns
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9] for k, v in rows]
