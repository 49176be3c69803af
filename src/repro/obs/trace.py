"""Spans on the profiler's clock, and the ``profile()`` bracket.

A span times a block of host code with one pair of clock reads.  The
duration goes to the registry (a ``span.<name>.seconds`` histogram and a
``span.<name>.calls`` counter) and stays on the span as ``seconds``, so a
layer whose own histogram covers the same interval observes it from there
instead of reading the clock again.  The span also opens a
``jax.profiler.TraceAnnotation`` of its bare name: under a profiler
session it lands on the host plane of the trace, on the same clock as the
device's operations, nested by time in the spans around it; with no
session active the annotation is a no-op inside the profiler's C++
``TraceMe``.  When the registry is disabled, ``span(...)`` returns a
shared no-op object: no allocation, no timer, no annotation.

jax is imported on the first span entered (a process that enables
telemetry has it loaded already), so ``repro.obs`` itself imports without
it.  ``profile(logdir)`` is the opt-in ``jax.profiler`` bracket for
TPU/CPU trace capture.
"""
from __future__ import annotations

import contextlib
import os
import time

_ANNOTATION = None


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported once; a null context where
    jax is not installed."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = contextlib.nullcontext
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _NullSpan:
    """Shared no-op span returned when the registry is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Span:
    __slots__ = ("registry", "name", "seconds", "_t0", "_ann")

    def __init__(self, registry, name):
        self.registry = registry
        self.name = name
        self.seconds = 0.0

    def __enter__(self):
        self._ann = _annotation()(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        reg = self.registry
        reg.observe(f"span.{self.name}.seconds", self.seconds)
        reg.inc(f"span.{self.name}.calls")
        return False


@contextlib.contextmanager
def profile(logdir=None):
    """Opt-in ``jax.profiler`` bracket: traces device + host activity
    for the wrapped region into ``logdir`` (viewable with TensorBoard
    or Perfetto).  Usable regardless of the ``CAMEO_OBS`` flag — the
    explicit call *is* the opt-in.  Never raises: if the profiler is
    unavailable or already active the region simply runs untraced.
    """
    import tempfile

    if logdir is None:
        logdir = os.environ.get("CAMEO_OBS_PROFILE_DIR") or os.path.join(
            tempfile.gettempdir(), "cameo_profile")
    started = False
    try:
        import jax

        jax.profiler.start_trace(logdir)
        started = True
    except Exception:
        pass
    try:
        yield logdir
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
