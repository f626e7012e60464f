"""In-process span recorder on the profiler's clock.

Reference role: the RAII ``RecordEvent`` span stack of
``paddle/fluid/platform/profiler.h:127,209`` plus the Chrome-trace
exporter ``tools/timeline.py:273`` — but framework-level rather than
CUPTI-level: spans cover the *system* paths the device trace cannot see
(the engine loop's phases, the train step's host side, wire round-trips,
PS ops, checkpoint uploads, retries/sheds), and a trace id crosses the
wire so one client request yields a joined client→server timeline.

Design constraints, in order:

1. **Recording follows the profiler.** Spans record while
   ``FLAGS_trace`` is on (the operator's switch) *or* a
   ``jax.profiler`` capture is live — whoever captures a profile of a
   process gets its spans with no flag to remember. :func:`recording`
   is that predicate; off, it costs one attribute read and one
   ``TraceAnnotation.is_enabled()`` (~0.1 µs), and :func:`span` returns
   a shared no-op object.
2. **One clock with the device.** An open span is also a
   ``jax.profiler.TraceAnnotation`` carrying its attributes and
   ``span_id``: in a capture it is an event of its thread's line in the
   ``/host:CPU`` plane, beside the device planes. The ring record keeps
   ``ts`` from the realtime clock the profiler stamps with (what joins
   it to the capture's event) and ``mono`` / ``dur`` from the monotonic
   one: ``ts`` and ``mono`` are two reads, so order, nesting and self
   time are judged on ``mono`` alone. ``cpu`` is the thread's own CPU
   time inside the span: ``dur - cpu`` is what the thread spent waiting
   (a lock, the interpreter lock, a blocking call), which a wall clock
   cannot tell from work.
3. **Bounded memory, outliving the capture.** Records land in one
   process-wide ring (``FLAGS_trace_buffer`` entries) that is read
   after the capture has ended (:func:`get_spans`, :func:`snapshot`)
   and emptied by :func:`clear`; what it had to evict it counts
   (``dropped``).
4. **Wire-portable.** A span is a plain JSON-safe dict; the wire
   ``trace_dump`` op (``core/wire.py``) ships them to remote scrapers
   and ``tools/obs_dump.py`` merges multiple services into one
   Chrome/Perfetto timeline by trace id.

Usage::

    set_flags({"trace": True})            # or: inside jax.profiler.trace(...)
    with trace.span("train/epoch", epoch=3):
        ...
    trace.export_chrome("timeline.json")      # chrome://tracing / Perfetto

Cross-process linkage: the client side stamps its ``trace_id``/``span_id``
into the request header; the server opens :func:`server_span` with those
ids, so both halves share one trace id and the server span's parent is
the client span.

Compiles: one listener on jax's own compile event keeps a per-thread
count (:func:`thread_compiles`) from which a caller learns whether the
call it just made built a program.

The per-message wire paths (``core/wire.py``) trace on the operator's
switch alone (:func:`flag_on`): a capture of a serving replica would
otherwise be mostly its clients' polls.
"""

from __future__ import annotations

import json
import random
import threading
import time
from collections import deque
from typing import Any

import jax
from jax.profiler import TraceAnnotation as _Annotation

from paddle_tpu.core.flags import flag

__all__ = ["span", "server_span", "recording", "flag_on", "configure",
           "resize", "current", "get_spans", "clear", "snapshot",
           "export_chrome", "to_chrome_events", "new_id",
           "thread_compiles"]


class _Ring:
    """Thread-safe span ring buffer that counts what it evicts."""

    def __init__(self, capacity: int):
        self.capacity = max(int(capacity), 1)
        self.dropped = 0
        self._lock = threading.Lock()
        self._buf: deque[dict] = deque(maxlen=self.capacity)

    def record(self, span_dict: dict) -> None:
        with self._lock:
            if len(self._buf) == self.capacity:
                self.dropped += 1
            self._buf.append(span_dict)

    def spans(self) -> list[dict]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0

    def resize(self, capacity: int) -> None:
        """Keep the newest spans that still fit (shrinking evicts only
        the oldest tail, and counts it)."""
        with self._lock:
            self.capacity = max(int(capacity), 1)
            self.dropped += max(len(self._buf) - self.capacity, 0)
            self._buf = deque(self._buf, maxlen=self.capacity)


def _flag_capacity() -> int:
    try:
        return int(flag("trace_buffer"))
    except KeyError:               # flag not registered yet (import order)
        return 16384


_RING = _Ring(_flag_capacity())
_FLAG_ON = False                  # FLAGS_trace, set through configure()
_ctx = threading.local()          # per-thread (trace_id, span_id) stack
_capture_live = _Annotation.is_enabled


def recording() -> bool:
    """True while spans are recorded: ``FLAGS_trace`` is on or a
    ``jax.profiler`` capture is live. The hot paths' only guard."""
    return _FLAG_ON or _capture_live()


def flag_on() -> bool:
    """True while ``FLAGS_trace`` itself is on, capture or no capture:
    the guard of the per-message wire paths."""
    return _FLAG_ON


def configure(enable: bool) -> None:
    """Wired to ``FLAGS_trace``. Switching the flag off empties the
    ring, as it always did; a capture's spans stay until
    :func:`clear`."""
    global _FLAG_ON
    if _FLAG_ON and not enable:
        _RING.clear()
    _FLAG_ON = bool(enable)


def resize(capacity: int) -> None:
    """Wired to ``FLAGS_trace_buffer``: live resize of the ring."""
    _RING.resize(capacity)


def new_id() -> str:
    return f"{random.getrandbits(64):016x}"


def current() -> tuple[str, str] | None:
    """(trace_id, span_id) of this thread's innermost open span."""
    stack = getattr(_ctx, "stack", None)
    return stack[-1] if stack else None


class _NoopSpan:
    """What :func:`span` returns while nothing records: every operation
    a no-op, shared singleton (no per-call allocation)."""

    __slots__ = ()
    trace_id = None
    span_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    """One open span: a ``TraceAnnotation`` while it is open, a ring
    record on exit. ``t0``/``t1`` (``perf_counter_ns``) are its two
    clock reads, for a caller that times the same section; the thread's
    CPU clock is read inside them, so ``cpu <= dur`` but for a tick."""

    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "t0", "t1", "_ts", "_c0", "_ann")

    def __init__(self, name: str, attrs: dict,
                 trace_id: str | None = None,
                 parent_id: str | None = None):
        self.name = name
        self.attrs = attrs
        if trace_id is None:
            cur = current()
            if cur is not None:
                trace_id, parent_id = cur
            else:
                trace_id = new_id()
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.span_id = new_id()

    def set(self, **attrs) -> None:
        """Attach attributes to an open span (e.g. retry counts known
        only at the end of the operation); they reach the ring record,
        not the annotation, which took its own on entry."""
        self.attrs.update(attrs)

    def __enter__(self):
        stack = getattr(_ctx, "stack", None)
        if stack is None:
            stack = _ctx.stack = []
        stack.append((self.trace_id, self.span_id))
        self._ann = _Annotation(self.name, span_id=self.span_id,
                                **self.attrs)
        self._ann.__enter__()
        self._ts = time.time_ns()          # realtime: the profiler's clock
        self.t0 = time.perf_counter_ns()   # monotonic: order, duration
        self._c0 = time.thread_time_ns()   # this thread's CPU time
        return self

    def __exit__(self, exc_type, exc, tb):
        cpu = time.thread_time_ns() - self._c0
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        stack = getattr(_ctx, "stack", None)
        if stack:
            stack.pop()
        if recording():                    # ended mid-span: drop it
            if exc_type is not None:
                self.attrs["error"] = exc_type.__name__
            _RING.record({
                "name": self.name, "ts": self._ts * 1e-9,
                "mono": self.t0 * 1e-9, "dur": (self.t1 - self.t0) * 1e-9,
                "cpu": cpu * 1e-9, "tid": threading.get_ident(),
                "trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "attrs": self.attrs})
        return False


def span(name: str, **attrs: Any):
    """Open a span: ``with trace.span("ckpt/save", step=3): ...``.
    Returns a shared no-op while nothing records — safe (and cheap) to
    call unconditionally outside the per-request hot paths."""
    if not recording():
        return _NOOP
    return _Span(name, attrs)


def server_span(name: str, trace_id: str | None, parent_id: str | None,
                **attrs: Any):
    """Open a span linked to a remote parent (the server half of a wire
    round-trip). ``trace_id=None`` (untraced client) starts a fresh
    trace, so a traced server still records its side."""
    if not recording():
        return _NOOP
    return _Span(name, attrs, trace_id=trace_id, parent_id=parent_id)


def get_spans() -> list[dict]:
    """Snapshot of the ring (oldest first), recording or not."""
    return _RING.spans()


def clear() -> None:
    _RING.clear()


def snapshot(clear_after: bool = False) -> dict:
    """JSON-safe dump for the wire ``trace_dump`` op and obs_dump."""
    doc = {"enabled": recording(), "capacity": _RING.capacity,
           "dropped": _RING.dropped, "spans": _RING.spans()}
    if clear_after:
        _RING.clear()
    return doc


# ---------------------------------------------------------------------------
# compiles, from jax's own events
# ---------------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def thread_compiles() -> int:
    """Programs this thread has built so far (compiled, or loaded from
    the persistent cache). jax compiles on the calling thread, so a
    change across a call means that call built one."""
    return getattr(_ctx, "compiles", 0)


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _ctx.compiles = thread_compiles() + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


# ---------------------------------------------------------------------------
# Chrome-trace export (reference tools/timeline.py:273)
# ---------------------------------------------------------------------------

def to_chrome_events(spans: list[dict], pid: int | str = 0,
                     pid_name: str | None = None) -> list[dict]:
    """Spans → Chrome trace-event dicts (``ph: "X"`` complete events,
    microsecond timestamps). ``pid``/``pid_name`` group one process'
    spans in the viewer — obs_dump gives each endpoint its own pid."""
    events: list[dict] = []
    if pid_name:
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": pid_name}})
    for s in spans:
        args = {"trace_id": s["trace_id"], "span_id": s["span_id"]}
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        if "cpu" in s:                  # a record of an older peer has none
            args["cpu"] = s["cpu"]
        args.update(s.get("attrs") or {})
        events.append({
            "name": s["name"], "ph": "X",
            "ts": s["ts"] * 1e6, "dur": s["dur"] * 1e6,
            "pid": pid, "tid": s["tid"], "cat": s["name"].split("/")[0],
            "args": args})
    return events


def export_chrome(path: str | None = None,
                  spans: list[dict] | None = None) -> dict:
    """Write the buffered spans (or an explicit span list) as a Chrome
    trace JSON loadable in ``chrome://tracing`` / Perfetto; returns the
    document (and writes it to ``path`` when given)."""
    doc = {"traceEvents": to_chrome_events(
        get_spans() if spans is None else spans),
        "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc
