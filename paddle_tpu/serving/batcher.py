"""Cross-request dynamic micro-batching for the inference server.

Reference role: what Paddle Serving's request scheduler does in front of
a predictor pool, in the Orca/Clipper shape: concurrent ``infer``
requests for the same model are queued, coalesced up to
``FLAGS_serving_batch_max`` total rows or ``FLAGS_serving_batch_timeout_s``
of waiting, run as ONE ``Predictor.run`` over the concatenated batch,
and split back per caller. On a TPU (and under XLA's per-call dispatch
overhead generally) one run of ``k`` rows costs far less than ``k`` runs
of one row — the device-side batching lever, applied across the wire.

Mechanics:

- **Leader/follower coalescing.** Each request enqueues onto its model's
  queue; whichever handler thread finds no active leader becomes one,
  waits out the batching window (or until the row cap is hit), takes the
  FIFO prefix that fits, executes it, and distributes results. Followers
  just wait; leftover requests elect the next leader immediately.
- **Load watermark.** Coalescing taxes idle traffic: a lone request
  pays the full ``serving_batch_timeout_s`` window for a batch that is
  never coming. A request that finds fewer than
  ``FLAGS_serving_batch_min_queue`` concurrent submits for its model —
  and no batch already forming — bypasses the queue and runs
  immediately (``serving/batch_bypass``); under real concurrency the
  watermark is crossed and coalescing engages as before. 0 restores
  unconditional coalescing.
- **Bucketed padding.** The concatenated batch is padded with zero rows
  up to the next power-of-two bucket (capped at ``serving_batch_max``),
  so the number of distinct shapes XLA compiles stays logarithmic in the
  cap. Padding rows are sliced away before replies; row-independent
  models (anything exported per-example) are unaffected by them.
- **Dynamic-batch artifacts only.** Coalescing needs a predictor whose
  batch axis is symbolic (``save_inference_model(...,
  dynamic_batch=True)``); fixed-shape models pass through unbatched.
- **Hard-off default.** With ``serving_batch_max`` at 0/1 (default) the
  server never constructs or consults the batcher — the serving path is
  byte-identical to the unbatched one (the ``FLAGS_trace`` pattern).

Observability: ``serving/batch_size`` + ``serving/batch_requests`` +
``serving/batch_wait_s`` histograms, ``serving/batches`` /
``serving/batched_requests`` / ``serving/batch_pad_rows`` counters, and
(when tracing) a ``serving/batch_wait`` span per request nested under
its wire server span, with the leader's ``serving/predict`` span showing
the shared execution — amortization reads directly off the timeline.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from paddle_tpu.core import fault as _fault
from paddle_tpu.core import trace as _trace
from paddle_tpu.core.flags import flag
from paddle_tpu.core.monitor import observe, stat_add

__all__ = ["DynamicBatcher"]


def _bucket_rows(rows: int, max_rows: int) -> int:
    """Smallest power-of-two >= rows, capped at max_rows (oversized
    single requests run at their own size, unpadded)."""
    if rows >= max_rows:
        return rows
    b = 1
    while b < rows:
        b <<= 1
    return min(b, max_rows)


class _Pending:
    """One queued request: inputs in, outputs/error out."""

    __slots__ = ("inputs", "rows", "outputs", "error", "t0", "tenant")

    def __init__(self, inputs: list[np.ndarray], rows: int,
                 tenant: str | None = None):
        self.inputs = inputs
        self.rows = rows
        self.outputs: list[np.ndarray] | None = None
        self.error: BaseException | None = None
        self.t0 = time.perf_counter()
        self.tenant = tenant


class _ModelQueue:
    __slots__ = ("cv", "items", "leading", "inflight")

    def __init__(self):
        self.cv = threading.Condition()
        self.items: list[_Pending] = []
        self.leading = False
        self.inflight = 0     # concurrent submit() calls (load signal)


class DynamicBatcher:
    """Per-server coalescer of concurrent same-model infer requests.

    One instance per :class:`~paddle_tpu.io.serving.InferenceServer`
    (model names are only unique within a server). ``submit`` blocks the
    calling handler thread until its slice of a batch (or its solo run)
    completes, and raises whatever the combined execution raised.
    """

    def __init__(self, tenant_book=None):
        self._lock = threading.Lock()
        self._queues: dict[str, _ModelQueue] = {}
        # per-tenant infer attribution (serving/ledger.py TenantBook,
        # passed by the server when FLAGS_gen_ledger is on): a coalesced
        # run's wall clock splits evenly across its riders. None — the
        # default — books nothing and costs one is-None check per run.
        self._book = tenant_book
        # the replica's GenScheduler (FLAGS_gen_sched, installed by
        # InferenceServer.add_generator): consulted per submit for a
        # coalescing bypass while interactive SLO burn runs hot. None —
        # the default — costs one is-None check.
        self._sched = None

    def set_sched(self, sched) -> None:
        """Route this batcher's shed/bypass hints through the replica's
        generation scheduler (the one-shed-brain contract)."""
        self._sched = sched

    @staticmethod
    def can_batch(pred) -> bool:
        """Only dynamic-batch predictors participate; anything else
        (fixed-shape artifacts, duck-typed predictor objects) takes the
        ordinary unbatched path."""
        return bool(getattr(pred, "supports_batching", False))

    def submit(self, model: str, pred, inputs: list[np.ndarray],
               tenant: str | None = None) -> list[np.ndarray]:
        # Validate against the specs BEFORE enqueueing: a malformed
        # request must fail alone, never poison the batch it would have
        # ridden in (its peers' runs share one exported call).
        self._validate(pred, inputs)
        if not inputs:
            return self._run(pred, model, inputs, batched=False)
        rows = int(inputs[0].shape[0])
        q = self._queue(model)
        min_q = int(flag("serving_batch_min_queue"))
        with q.cv:
            q.inflight += 1
            # below the load watermark with no batch forming: skip the
            # coalescing window entirely — idle traffic must not pay the
            # timeout tax for a batch that is never coming
            solo = min_q > 0 and q.inflight < min_q and not q.items
        if (not solo and self._sched is not None
                and self._sched.infer_bypass(tenant)):
            # scheduler hint: interactive TTFT burn is hot — skip the
            # coalescing window so this request does not pay the
            # batching tax while latency budget is being spent
            solo = True
            stat_add("serving/batch_sched_bypass")
        try:
            if solo:
                stat_add("serving/batch_bypass")
                if self._book is None:
                    return self._run(pred, model, inputs, batched=False)
                t0 = time.perf_counter()
                outs = self._run(pred, model, inputs, batched=False)
                self._book.add(tenant, requests=1,
                               chip_s=time.perf_counter() - t0)
                return outs
            p = _Pending(inputs, rows, tenant)
            if _trace.recording():
                with _trace.span("serving/batch_wait", model=model,
                                 rows=rows):
                    self._submit(q, pred, model, p)
            else:
                self._submit(q, pred, model, p)
        finally:
            with q.cv:
                q.inflight -= 1
        if p.error is not None:
            raise p.error
        assert p.outputs is not None
        return p.outputs

    # -- internals ---------------------------------------------------------
    @staticmethod
    def _validate(pred, inputs: list[np.ndarray]) -> None:
        specs = pred.input_specs
        if len(inputs) != len(specs):
            raise ValueError(
                f"expected {len(specs)} inputs, got {len(inputs)}")
        rows = None
        for i, (a, spec) in enumerate(zip(inputs, specs)):
            if len(a.shape) != len(spec["shape"]) or any(
                    e is not None and d != e
                    for d, e in zip(a.shape, spec["shape"])):
                raise ValueError(
                    f"input {i}: shape {list(a.shape)} != exported "
                    f"{spec['shape']}")
            if a.dtype.name != spec["dtype"]:
                raise ValueError(
                    f"input {i}: dtype {a.dtype} != exported "
                    f"{spec['dtype']}")
            if rows is None:
                rows = int(a.shape[0])
            elif int(a.shape[0]) != rows:
                raise ValueError(
                    "all inputs must share the batch-axis size; got "
                    f"{rows} vs {int(a.shape[0])} (input {i})")
            if rows == 0:
                raise ValueError("empty batch (0 rows)")

    def _queue(self, model: str) -> _ModelQueue:
        with self._lock:
            q = self._queues.get(model)
            if q is None:
                q = self._queues[model] = _ModelQueue()
            return q

    def pending(self, model: str) -> int:
        """Requests currently inside :meth:`submit` for ``model`` —
        queued on the batching window or executing. The ``unload_model``
        admin op consults this so an unload can fail clean (typed error)
        instead of yanking a predictor out from under a forming batch."""
        with self._lock:
            q = self._queues.get(model)
        if q is None:
            return 0
        with q.cv:
            return q.inflight

    def _submit(self, q: _ModelQueue, pred, model: str, p: _Pending
                ) -> None:
        with q.cv:
            q.items.append(p)
            q.cv.notify_all()        # a counting leader may now be full
            while p.outputs is None and p.error is None:
                if not q.leading:
                    q.leading = True
                    try:
                        self._lead(q, pred, model)
                    finally:
                        q.leading = False
                        q.cv.notify_all()
                else:
                    # followers poll with a bound: the post-execution
                    # notify_all normally wakes them immediately
                    q.cv.wait(0.05)

    def _lead(self, q: _ModelQueue, pred, model: str) -> None:
        """Called with ``q.cv`` held: wait out the batching window,
        take the FIFO prefix that fits, execute it outside the lock."""
        max_rows = max(int(flag("serving_batch_max")), 1)
        deadline = (time.perf_counter()
                    + float(flag("serving_batch_timeout_s")))
        while True:
            if sum(it.rows for it in q.items) >= max_rows:
                break
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                break
            q.cv.wait(remaining)
        take: list[_Pending] = []
        total = 0
        for it in q.items:
            if take and total + it.rows > max_rows:
                break
            take.append(it)
            total += it.rows
        del q.items[:len(take)]
        q.cv.release()
        try:
            self._execute(pred, model, take, total, max_rows)
        finally:
            q.cv.acquire()

    def _execute(self, pred, model: str, take: list[_Pending],
                 total_rows: int, max_rows: int) -> None:
        t_exec = time.perf_counter()
        for it in take:
            observe("serving/batch_wait_s", t_exec - it.t0)
        try:
            # injection site for the whole coalesced execution: a flush
            # failure must fan out to every rider, never hang one
            _fault.inject("batcher.flush")
            if len(take) == 1:
                # solo flush: no concat/pad — identical to a direct run
                take[0].outputs = self._run(pred, model, take[0].inputs,
                                            batched=False)
            else:
                bucket = _bucket_rows(total_rows, max_rows)
                pad = bucket - total_rows
                cat = [
                    np.concatenate([it.inputs[i] for it in take], axis=0)
                    for i in range(len(take[0].inputs))]
                if pad:
                    cat = [np.concatenate(
                        [c, np.zeros((pad,) + c.shape[1:], c.dtype)],
                        axis=0) for c in cat]
                    stat_add("serving/batch_pad_rows", pad)
                outs = self._run(pred, model, cat, batched=True,
                                 requests=len(take))
                off = 0
                for it in take:
                    it.outputs = [np.asarray(o[off:off + it.rows])
                                  for o in outs]
                    off += it.rows
            stat_add("serving/batches")
            stat_add("serving/batched_requests", len(take))
            observe("serving/batch_size", total_rows)
            observe("serving/batch_requests", len(take))
            if self._book is not None:
                # one run served every rider: split its wall evenly
                share = (time.perf_counter() - t_exec) / len(take)
                for it in take:
                    self._book.add(it.tenant, requests=1, chip_s=share)
        except BaseException as e:  # every caller gets the failure
            for it in take:
                it.error = e

    @staticmethod
    def _run(pred, model: str, inputs, *, batched: bool,
             requests: int = 1) -> list[np.ndarray]:
        with _trace.span("serving/predict", model=model, batched=batched,
                         requests=requests):
            outs = pred.run(*inputs)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        return [np.asarray(o) for o in outs]
