"""Profiler front-end: host+device tracing with named annotations.

Reference: ``paddle/fluid/platform/profiler.h:127,209`` (RAII RecordEvent +
EnableProfiler/DisableProfiler), the CUPTI ``DeviceTracer``
(``platform/device_tracer.h:43``) correlating kernels to host events, the
Python front-end ``python/paddle/fluid/profiler.py`` and the Chrome-trace
exporter ``tools/timeline.py:273``.

TPU-native mapping: ``jax.profiler`` already is the merged host+device
tracer — ``start_trace``/``stop_trace`` capture a TensorBoard/xplane
timeline (including every XLA kernel on TPU, the CUPTI role), and
annotations are two-sided:

- ``jax.named_scope`` tags the *compiled HLO* so ops carry the training-
  step phase name in the trace (the RecordEvent-inside-op-dispatch role);
- ``core.trace.span`` marks *host* spans (dispatch, data feed) as
  ``jax.profiler.TraceAnnotation``s, the host-side RecordEvent role.

``RecordEvent`` here fuses both so one annotation covers either context.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator

import jax

from paddle_tpu.core import trace as _trace

__all__ = ["start_profiler", "stop_profiler", "profiler", "RecordEvent",
           "record_function", "annotate"]

_active_logdir: str | None = None


def start_profiler(logdir: str = "./profile") -> None:
    """Begin capturing a timeline (EnableProfiler analogue). The artifact
    is a TensorBoard xplane under ``logdir`` — view with TensorBoard's
    profile plugin or ``xprof``."""
    global _active_logdir
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    _active_logdir = logdir


def stop_profiler() -> str | None:
    """End the capture and return the logdir holding the timeline."""
    global _active_logdir
    jax.profiler.stop_trace()
    logdir, _active_logdir = _active_logdir, None
    return logdir


@contextlib.contextmanager
def profiler(logdir: str = "./profile") -> Iterator[None]:
    """``with profiler.profiler("logs"): train()`` — scoped capture
    (the ``with profiler.profiler(...)`` front-end of the reference)."""
    start_profiler(logdir)
    try:
        yield
    finally:
        stop_profiler()


class RecordEvent:
    """Named annotation usable as context manager or decorator, inside or
    outside jit (reference RAII ``RecordEvent``, ``profiler.h:127``).

    Inside a jit trace it lowers to a named_scope (op metadata in the
    device timeline; the host half then times tracing, not the device).
    At host level it is a ``core.trace`` span: a ``TraceAnnotation`` in
    a live capture and a ring record, on the same timeline as the
    framework's own spans (the reference RecordEvent → timeline.py
    pipeline); a no-op while nothing records.
    """

    def __init__(self, name: str):
        self.name = name
        self._stack = None

    def __enter__(self):
        self._stack = contextlib.ExitStack()
        # named_scope tags ops when tracing; the span times the host
        # when executing — entering both covers either context
        self._stack.enter_context(jax.named_scope(self.name))
        self._stack.enter_context(_trace.span(self.name))
        return self

    def __exit__(self, *exc):
        self._stack.close()
        self._stack = None
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with RecordEvent(self.name):
                return fn(*a, **k)
        return wrapped


def record_function(name: str) -> RecordEvent:
    """Decorator alias (paddle.profiler.RecordEvent usage pattern)."""
    return RecordEvent(name)


annotate = RecordEvent
