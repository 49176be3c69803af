"""The program's own marks in a traced run's profile: its host spans and
the phases of its device operations.

``bench/trace.py`` reduces the profile to the device's operations and the
benchmark's ``bench.*`` spans.  The program marks its layers as well:

* host spans, one profiler annotation per ``repro.obs`` span, with the
  names the program declares in ``repro.obs.SPANS`` (a name is cut at any
  ``#``, where the profiler appends arguments);
* the phases of its rounds program, the ``jax.named_scope`` scopes
  ``rank``, ``select`` and ``update`` in each operation's ``op_name`` path.
  A TPU trace keeps that path in the ``tf_op`` stat of the operation's
  event metadata, which ``ProfileData`` does not expose, so
  :func:`metadata_tf_ops` reads it from the file.  An operation belongs to
  the innermost of the three in its path; a fusion carries its root's
  path, so the split is exact only to a fusion boundary.

:func:`marks` reads both from the profile that a ``Readings``' trace was
reduced from, once, and keeps them on the trace.  On a program without
them (one older than its spans and scopes) it finds none, and the readers
built on it return ``None``.
"""
from __future__ import annotations

import bisect
import glob
import os
import tempfile
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from bench import trace as tr

PHASES = ("rank", "select", "update")
PROGRAM = "jit__rounds_padded"


@dataclass
class Marks:
    """``spans``: the program's host spans.  ``ops``: each device's
    operations, named by their phase where one of :data:`PHASES` is in
    their scope path, and by their own text where none is."""

    spans: List[tr.Event] = field(default_factory=list)
    ops: Dict[str, List[tr.Event]] = field(default_factory=dict)

    @property
    def phased(self) -> bool:
        return any(n in PHASES for evs in self.ops.values()
                   for n, _, _ in evs)


def program_spans() -> tuple:
    """The span names the program declares (none on a program that
    declares none)."""
    try:
        from repro import obs
    except ImportError:
        return ()
    return tuple(getattr(obs, "SPANS", ()))


def phase_of(path: str) -> Optional[str]:
    """The innermost of :data:`PHASES` in an ``op_name`` path (a ``tf_op``
    stat's ends in ``:`` and the operation's type)."""
    for part in reversed(path.rsplit(":", 1)[0].split("/")):
        if part in PHASES:
            return part
    return None


# ---------------------------------------------------------------------------
# what ProfileData does not expose, read from the serialized XSpace
# ---------------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a protobuf message's fields; a
    length-delimited value is a ``memoryview`` slice."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _planes(serialized: bytes):
    """``(plane name, [event metadata], {stat id: stat name})`` of each
    plane of a serialized ``XSpace`` (planes 1; plane name 2,
    event_metadata 4 and stat_metadata 5, maps of id 1 to message 2; stat
    metadata name 2).  The planes' lines are skipped unread."""
    for num, plane in _fields(memoryview(serialized)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = _text(
                    dict(_fields(entry.get(2, b""))).get(2, b""))
        yield name, events, stat_names


def _stats(event_meta, stat_names) -> Dict[str, object]:
    """An event metadata's string stats (name 2, display_name 4, stats 5;
    a stat's metadata_id 1, str_value 5, ref_value 7), by stat name; ``""``
    holds the names."""
    out, names = {}, []
    for f, v in _fields(event_meta):
        if f in (2, 4):
            names.append(_text(v))
        elif f == 5:
            st = dict(_fields(v))
            key = stat_names.get(st.get(1), "")
            if 5 in st:
                out[key] = _text(st[5])
            elif 7 in st:
                out[key] = stat_names.get(st[7], "")
    out[""] = names
    return out


def metadata_tf_ops(serialized: bytes) -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: tf_op}}`` from the device planes'
    event metadata in a serialized ``XSpace``, where a TPU trace keeps each
    operation's ``op_name`` path (with a ``:`` and its type after it)."""
    out = {}
    for name, events, stat_names in _planes(serialized):
        if not name.startswith("/device:"):
            continue
        table = out[name] = {}
        for em in events:
            st = _stats(em, stat_names)
            if isinstance(st.get("tf_op"), str):
                for n in st[""]:
                    table[n] = st["tf_op"]
    return out


# ---------------------------------------------------------------------------
# reading the marks
# ---------------------------------------------------------------------------

def from_profile(pd, serialized: bytes = None, names=None) -> Marks:
    """The marks of a ``jax.profiler.ProfileData``; each operation's scope
    path is read from ``serialized``, the ``XSpace`` it came from."""
    names = set(program_spans() if names is None else names)
    tf_ops = metadata_tf_ops(serialized) if serialized else {}
    out = Marks()
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    n = e.name.split("#", 1)[0]
                    if n in names:
                        out.spans.append((n, e.start_ns, e.end_ns))
        elif plane.name.startswith("/device:"):
            paths = tf_ops.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.ops[plane.name] = [
                        (phase_of(paths.get(e.name, "")) or e.name,
                         e.start_ns, e.end_ns) for e in line.events]
    return out


def _traced_window(pd):
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tr.TRACED_SPAN:
                        return (e.start_ns, e.end_ns)
    return None


def recorded(trace, root: str = None) -> Optional[Marks]:
    """The marks of the recorded profile that ``trace`` was reduced from:
    the newest ``*.xplane.pb`` under a run's work directory
    (``bench-*/trace`` in the temporary directory, or ``root``) whose
    ``bench.traced`` span is the trace's window."""
    from jax.profiler import ProfileData
    root = root or tempfile.gettempdir()
    paths = glob.glob(os.path.join(root, "bench-*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True)[:4]:
        try:
            with open(path, "rb") as f:
                raw = f.read()
            pd = ProfileData.from_serialized_xspace(raw)
        except Exception:       # a profile being written, or torn
            continue
        if _traced_window(pd) == tuple(trace.window):
            return from_profile(pd, raw)
    return None


def marks(r) -> Optional[Marks]:
    """The marks of the profile behind ``r.trace`` (kept on the trace as
    ``program_marks``; a test may set that itself), and on first reading a
    note of the traced part's idle gaps named by span (``idle_gaps``)."""
    t = r.trace
    if t is None:
        return None
    m = getattr(t, "program_marks", None)
    if m is None:
        m = t.program_marks = recorded(t) or Marks()
    if m.spans and "idle_gaps" not in r.notes and t.ops:
        r.notes["idle_gaps"] = idle_gaps(t, m, next(iter(t.ops)))
    return m


# ---------------------------------------------------------------------------
# what the readers compute
# ---------------------------------------------------------------------------

def idle_gaps(trace, m: Marks, device: str, top: int = 10):
    """The longest idle gaps on ``device``.  Each is named by the innermost
    span name, of the benchmark's or the program's, whose spans together
    cover more than half of it (else the one covering most; ``host:none``
    where none does), and carries each name's share of it (names over 1%):
    a gap between two window compressions holds many short pushes, none of
    which covers half."""
    lo, hi = trace.window
    by_name: Dict[str, List[tr.Event]] = {}
    for ev in trace.host + m.spans:
        by_name.setdefault(ev[0], []).append(ev)
    out = []
    longest = sorted(tr.gaps(trace.ops.get(device, []), lo, hi),
                     key=lambda g: g[0] - g[1])[:top]
    for s, e in longest:
        share = {n: tr.union_ns(evs, s, e) / (e - s)
                 for n, evs in by_name.items()}
        share = {n: v for n, v in share.items() if v > 0.01}
        # the innermost: whose spans over the gap are the shortest in all
        length = {n: sum(he - hs for _, hs, he in by_name[n]
                         if he > s and hs < e) for n in share}
        most = [n for n, v in share.items() if v > 0.5]
        label = (min(most, key=length.get) if most
                 else max(share, key=share.get) if share else "host:none")
        out.append([f"{label} @{(s - lo) * 1e-9:.3f}s", (e - s) * 1e-9,
                    {n: round(100.0 * v, 1) for n, v in sorted(
                        share.items(), key=lambda kv: -kv[1])}])
    return out


def phase_split(r) -> Optional[Dict[str, float]]:
    """Device milliseconds per round of each phase in the rounds program's
    runs wholly inside the traced part (self time, so a loop's operation
    does not count its body twice), over the rounds ``stream.window_rounds``
    counted there.  Notes (``rounds.phases``) the residual — the runs' time
    in no phase: loop control, the accept and block bookkeeping, the gaps
    between operations — each share, and the residual's largest
    operations; the phases and the residual add up to
    ``rounds.device_ms_per_round``.  ``None`` where no operation of the
    runs carries a phase."""
    if "rounds.phases" in r.notes:
        return r.notes["rounds.phases"]["ms_per_round"]
    t = r.trace
    m = marks(r)
    rounds = r.hist("stream.window_rounds", traced=True)[1]
    if m is None or not m.phased or rounds <= 0:
        return None
    lo, hi = t.window
    run_ns = 0.0
    self_ns: Dict[str, float] = {}
    for dev, mods in t.modules.items():
        ops = sorted(m.ops.get(dev, []), key=lambda ev: ev[1])
        starts = [ev[1] for ev in ops]
        for name, s, e in mods:
            if not (name.startswith(PROGRAM) and s >= lo and e <= hi):
                continue
            run_ns += e - s
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_right(starts, e)]
            for label, ns in tr.self_times(inside, s, e).items():
                self_ns[label] = self_ns.get(label, 0.0) + ns
    if run_ns <= 0 or not any(p in self_ns for p in PHASES):
        return None
    per = lambda ns: ns * 1e-6 / rounds
    ms = {p: per(self_ns.get(p, 0.0)) for p in PHASES}
    total = per(run_ns)
    residual = total - sum(ms.values())
    other = sorted(((tr.short_name(k), per(v)) for k, v in self_ns.items()
                    if k not in PHASES), key=lambda kv: -kv[1])
    r.notes["rounds.phases"] = {
        "ms_per_round": ms,
        "device_ms_per_round": total,
        "residual_ms_per_round": residual,
        "share_pct": {**{p: 100.0 * v / total for p, v in ms.items()},
                      "residual": 100.0 * residual / total},
        "residual_gaps_ms_per_round": per(run_ns - sum(self_ns.values())),
        "residual_top": [[k, v] for k, v in other[:8]],
    }
    return ms
