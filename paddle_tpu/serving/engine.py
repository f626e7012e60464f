"""Continuous-batching generation engine: slot-based KV-cache scheduling
with streaming token delivery.

Reference role: the serving story the reference never had for its decode
loops (``operators/beam_search_op.cc`` + the dygraph sampling loops run
one request to completion, so a long generation starves every other
caller). This module applies iteration-level scheduling (Orca, OSDI '22)
and slot-based KV-cache management (the fixed-slot precursor of vLLM's
paged cache, SOSP '23) to the framework's autoregressive path:

- **One fixed-shape batched cache.** The engine owns ``slots`` KV caches
  of ``max_len`` positions each, allocated once (leaves
  ``[slots, L, 1, Hkv, S, D]``). Shapes never depend on the request mix,
  so XLA compiles exactly one decode step and one prefill per prompt
  bucket — no recompiles as traffic changes.
- **Iteration-level scheduling.** A background loop admits queued
  prompts into free slots (bucketed prefill), steps *all* active slots
  through ONE fused decode (``jax.vmap`` over
  ``model.forward_with_cache`` with per-slot positions — row-
  independent compute, so co-tenants never change a row), and retires
  slots on EOS,
  ``max_new_tokens``, cancel, or poll-TTL expiry (client disconnect).
  A request admitted mid-flight shares the very next decode step with
  the requests already running.
- **Host-side request state, device-side cache.** Per-slot prompt
  length, position, RNG key, and sampling params ride the jitted state;
  emitted tokens stream into host buffers that :meth:`~GenerationEngine.
  poll` drains incrementally (the wire ops ``generate_start`` /
  ``generate_poll`` / ``generate_cancel`` in ``io/serving.py``).
- **Paged mode** (``FLAGS_gen_paged``, off by default). The contiguous
  per-slot regions above make a 16-token completion pay HBM for
  ``max_len`` positions; paged mode (vLLM PagedAttention, SOSP '23)
  replaces them with a pool of ``FLAGS_gen_pages`` physical pages of
  ``FLAGS_gen_page_tokens`` tokens plus per-slot page tables
  (``models.generation.init_paged_cache``). The three paged programs
  (step, prefill chunk, speculative verify) hand the model a
  ``generation.PagedCache`` — the pool and the slot's table row — in
  the cache's place. The decode step on one TPU chip attends through
  ``ptpu_paged_decode_attn`` — over a latent (MLA) pool through
  ``ptpu_paged_latent_decode_attn`` — one call a layer for all slots,
  which reads each slot's live pages out of the pool through the row;
  a prefill chunk, a verify window, the CPU and a multi-device mesh
  gather ONE layer's pages through the row for the einsum arm
  (``models._common.cached_attention`` / ``latent_attention`` pick;
  ``stats()["decode_attn"]`` says which the step took, for either
  family: ``"paged_copy_kernel"`` where the K/V kernel copies a float
  pool's pages itself, ``"paged_kernel"``, ``"gather"``). Either way no slot's
  all-layers view is
  ever built, and the chunk's new k/v go into the donated pool by
  in-place page updates (``generation.paged_write``): a step moves the
  pages it reads, never the pool. A generation reserves pages for its
  *declared* worst case (prompt + ``max_new_tokens``) at admission — capacity
  becomes ``pool / actual-need`` instead of ``slots`` — and admission
  stalls on page-pool exhaustion, not slot count. A radix prefix cache
  over full prompt pages maps generations sharing a prompt prefix onto
  the same refcounted physical pages, so the shared prefix prefills
  once (``gen/prefix_hits`` / ``gen/prefix_tokens_saved``; cached pages
  are LRU-evicted under pool pressure). Chunked prefill
  (``FLAGS_gen_prefill_chunk``) admits long prompts in token slices
  interleaved with decode steps, so active streams keep emitting
  during a long prefill instead of stalling behind it.
- **Layer groups** (a model whose cache is one entry a layer kind,
  ``cache_groups``). A paged engine holds one full group of pages and,
  beside it, either a *window* group — a second pool whose rows let go
  of the pages behind a stream's window (``_WindowGroup``) — or a
  *state* group: recurrent state of O(1) a sequence (Kimi-Linear's KDA
  layers), kept a SLOT, not a page: ``[slots, layers, ...]`` rows that
  every decode step reads and replaces in place (idle and prefilling
  slots step with length 0, the identity). Pages alone cannot serve a
  prefix there, so prefix reuse is by **state snapshot**: a prefill
  chunk that ends on a page boundary writes its end state into a
  bounded pool (``state_snapshots`` entries, ``_SnapshotPool``; the
  least recently used evicted), the prefix entry of that page carries
  the snapshot's id, a match is cut back to the deepest entry that has
  one, and the admitted stream's FIRST prefill chunk starts from the
  snapshot's rows (from the zero entry on a miss) — restore, chunk and
  snapshot are one program a bucket, nothing is dispatched at
  admission. A finished stream gives its rows back by doing nothing.
- **Block diffusion** (a model that ``generates_by = "block_diffusion"``:
  ``models/sdar.py``; paged engines only). A step no longer gives a
  slot one token: it forwards the slot's block of B positions at the
  block's first position (block-causal: the context before the block,
  the block's own rows both ways — ``ptpu_paged_block_attn`` on one
  chip), writes the B rows' K/V into the block's page in place, and on
  the device fixes the masked positions of highest confidence, as many
  as the linear transfer schedule gives that step
  (``generation.block_pick``, ``transfer_schedule``); a block with
  nothing masked is committed by the same program — its final rows
  overwrite the page — and the slot moves B on. The block, its step
  count and the step each position was fixed at live in the donated
  state; the readback is ``[slots, 2B + 3]``, and a block's tokens
  reach the stream at the step that fixes its last position, and a
  finished stream's final poll hands back its blocks (``blocks``). A
  prompt is
  prefilled in whole blocks (chunks end on block boundaries, so prefix
  reuse by page stays exact); its remainder rides the first generated
  block, whose prompt positions are never masked. Greedy streams equal
  ``generation.block_diffusion_generate`` byte for byte.
- **Speculative decoding** (``FLAGS_gen_spec_k``, off by default).
  Decode is memory-bandwidth-bound, so the only way past the roofline
  is fewer serial target-model steps: a cheap drafter proposes up to
  ``k`` tokens per slot — the model-free n-gram lookup of
  ``models.generation.ngram_propose`` (``FLAGS_gen_spec_mode=ngram``,
  zero extra weights) or a small draft model sharing the cache
  contract (``mode=draft``, ``draft_model=``) — and ONE fused verify
  forward of the target model over the ``k+1`` proposed positions
  (the multi-token prefill machinery) yields the target's pick at
  every position; the longest matching draft prefix is accepted plus
  the target's own pick at the first mismatch, so a slot emits 1..k+1
  tokens per step and every emitted token is exactly what
  non-speculative decode would produce. Rejected drafts roll back by
  position-pointer arithmetic (contiguous mode: attention masks
  positions at/past the decode index, later writes overwrite them;
  paged mode: rejected in-page offsets are scattered to the null page
  — refcount-safe truncation), and each generation reserves ``k``
  scratch positions past its declared worst case so a full-width
  verify near the end of generation stays in bounds. Speculation is
  per-slot and load-adaptive: the draft budget sheds to 0 above
  ``FLAGS_gen_spec_shed_occupancy`` slot occupancy (batched decode
  already fills the MXU then), and mixed speculating/non-speculating
  slots coexist in one compiled verify call (draft length 0 = a plain
  step for that slot; an all-shed iteration runs the original fused
  step unchanged). One ``key`` split is consumed per EMITTED token
  regardless of acceptance pattern, so sampled streams replay
  identically with speculation on or off and ``rng_skip`` stream
  resumption composes unchanged.

Determinism: a greedy (``temperature=0``) generation through the engine
is byte-identical to a solo :func:`paddle_tpu.models.generation.generate`
call — right-padded bucketed prefill and co-tenant slots cannot change a
row's logits (causal masking; row-independent compute). Sampled requests
are deterministic per ``(prompt, seed)`` — each slot splits its own key
once per emitted token — but follow a different key schedule than solo
``generate``.

Self-healing (``FLAGS_gen_engine_rebuilds`` / ``FLAGS_gen_watchdog_s``
/ ``FLAGS_gen_quarantine_after``, all hard-off): a decode-loop trap no
longer bricks the engine forever — the active generations fail loudly
(their error carries the ``engine reset:`` marker, which the routed
client treats as resumable), the cache pool and slot state are rebuilt,
and work is re-admitted, up to ``gen_engine_rebuilds`` *consecutive*
traps. A watchdog thread detects a stuck decode step (loop heartbeat
older than ``gen_watchdog_s`` with active work), fails the stranded
generations so their clients resume elsewhere, and sheds new starts
until the stuck call returns and the loop rebuilds. Crash quarantine
fingerprints the request under a trap (prompt bytes + sampling params);
a fingerprint that traps ``gen_quarantine_after`` times is rejected at
:meth:`~GenerationEngine.start` with the typed
:class:`RequestQuarantined` instead of being retried into every replica
in the fleet. Fault-injection sites ``engine.prefill`` /
``engine.decode_step`` / ``paged.alloc`` (``core/fault.py``) make every
one of these paths deterministically testable.

Observability: ``gen/slots_active`` / ``gen/queue_depth`` /
``gen/pages_free`` gauges, ``gen/prefill_s`` / ``gen/prefill_chunk_s`` /
``gen/decode_step_s`` / ``gen/ttft_s`` (enqueue → first token — the
autoscaling SLO signal) / ``gen/spec_verify_s`` (the fused verify
forward) / ``gen/spec_accept_len`` (draft tokens accepted per verify)
histograms, ``gen/spec_proposed`` / ``gen/spec_accepted`` /
``gen/spec_rejected`` counters plus per-engine acceptance rate and
``tokens_per_step`` in :meth:`~GenerationEngine.stats` (shipped in the
serving ``health`` op next to slot occupancy, so the controller sees
speculation efficiency), ``gen/tokens`` / ``gen/evictions`` /
``gen/prefix_hits`` / ``gen/prefix_tokens_saved`` /
``gen/prefix_evictions`` / ``gen/state_restores`` /
``gen/state_snapshots`` / ``gen/state_snapshot_evictions`` (a state
group's prefix hits, snapshots kept and snapshots evicted) /
``gen/block_slot_steps`` / ``gen/block_tokens_fixed`` /
``gen/block_commits`` (a block-diffusion engine's live slot-steps,
positions fixed and blocks committed; ``stats()["block_diffusion"]``) /
``gen/traps`` / ``gen/rebuilds`` /
``gen/stuck`` / ``gen/quarantined`` / ``gen/quarantine_rejected`` /
``gen/expired_polls`` counters, and slot + page-pool occupancy in the
serving ``health`` op. Spans (``core.trace``: recorded while
``FLAGS_trace`` is on or a ``jax.profiler`` capture is live, each a
``TraceAnnotation`` on the capture's host plane), all on the loop
thread and none per token: ``gen/loop`` (one iteration; ``queue``,
``active``) is the parent of ``gen/idle_wait``, ``gen/admit`` (``gen``,
``waited_ms``, ``prefix_tokens``, ``pages``; under it ``gen/kv_fetch``
and, for an admission that restores a state snapshot,
``gen/state_restore`` with ``slot``, ``snapshot``, ``tokens``),
``gen/dev_ops``, ``gen/table_upload`` (a dirty page table going up),
``gen/prefill`` / ``gen/prefill_chunk`` (under them ``gen/prefill_wait``
round the readback of a first token), ``gen/decode_step`` (``active``,
``spec``, ``compiled``, ``sort_slots`` — the live slots whose request
restricts its sampling, the steps that have one counted under
``gen/sample_sorted_steps`` — a plain paged step's ``decode_attn``; a
block step's ``fixing`` and ``committing``, its slots with masked
positions and those whose block is whole;
under it ``gen/step_dispatch`` and ``gen/step_wait``, or
``gen/spec_verify`` around both), ``gen/draft`` and ``gen/emit``
(``emitted``, ``retired``; under a prefill chunk's, for a snapshot kept,
``gen/state_snapshot`` with ``tokens``, ``evicted``). What the loop puts
on the chip and what it has seen finish: ``gen/launch`` (``seq``,
``entry``) is the child span round exactly the call into a compiled
engine program, numbered by one counter on the loop thread
(``_launch``), and the span that blocks on a result carries ``landed``,
the ``seq`` it waited for (``gen/step_wait``, ``gen/prefill_wait``,
``gen/draft``); between a landing that leaves nothing outstanding and
the next launch the chip's queue is empty. One helper, ``_phase``,
times each section with two clock reads that also feed the section's
histogram and goodput bucket.
"""

from __future__ import annotations

import functools
import hashlib
import queue
import random as _random_mod
import threading
import time
import uuid
from collections import deque
from typing import Any

import numpy as np

from paddle_tpu.core import fault as _fault
from paddle_tpu.core import trace as _trace
from paddle_tpu.core.flags import flag
from paddle_tpu.core.monitor import observe, stat_add, stat_set

__all__ = ["GenerationEngine", "Generation", "EngineOverloaded",
           "RequestQuarantined", "GenerationExpired", "RESET_MARKER",
           "QUARANTINE_MARKER", "EXPIRED_MARKER", "stream_fingerprint"]

_UNSET = object()

# Marker prefixes for typed failures as they cross the wire (the frame
# protocol carries error strings; clients re-raise the typed class when
# they see the marker — the io/serving ``ModelBusyError`` pattern).
RESET_MARKER = "engine reset:"          # resumable: slot state lost,
#                                         engine (and replica) still up
QUARANTINE_MARKER = "request quarantined:"   # typed give-up, never retry
EXPIRED_MARKER = "generation expired:"       # poll-TTL reap, not unknown

# private shed-jitter stream: synchronized clients whose starts were all
# shed in the same instant must not re-stampede in the same instant
_jitter_rng = _random_mod.Random()


def _jittered(base: float) -> float:
    """``base`` scaled by U[0.5, 1.5) — the retry hint synchronized
    shed clients back off by must de-synchronize them."""
    return base * (0.5 + _jitter_rng.random())


class _Phase:
    """One timed section of the loop thread. Two clock reads, on entry
    and on exit, feed everything that times the section: the span while
    one records (the reads are then the span's own), the ``observe()``
    histogram, and the goodput bucket while the ledger is on. ``dt``
    (seconds) and ``t0``/``t1`` (``perf_counter_ns``) stay readable
    after the block.

    ``entry=(name, signature)`` marks a call into a compiled entry
    point: whether THIS call built a program comes from jax's own
    compile events on this thread (``trace.thread_compiles``), goes on
    the span as ``compiled``, into the engine's signature book, and
    books the section under ``recompile`` instead of its own bucket. A
    section left by an exception records its span (with the error) and
    feeds nothing else."""

    __slots__ = ("_eng", "_span", "_bucket", "_hist", "_entry", "_built",
                 "t0", "t1", "dt", "compiled")

    def __init__(self, eng, span, bucket, hist, entry):
        self._eng, self._span = eng, span
        self._bucket, self._hist, self._entry = bucket, hist, entry
        self.compiled = False

    @property
    def recording(self) -> bool:
        """Whether this section has a span (attributes worth
        computing)."""
        return self._span is not _trace._NOOP

    def set(self, **attrs) -> None:
        self._span.set(**attrs)

    def __enter__(self):
        if self._entry is not None:
            self._built = _trace.thread_compiles()
        sp = self._span
        if sp is _trace._NOOP:
            self.t0 = time.perf_counter_ns()
        else:
            sp.__enter__()
            self.t0 = sp.t0
        return self

    def __exit__(self, exc_type, exc, tb):
        sp = self._span
        if self._entry is not None:
            self.compiled = _trace.thread_compiles() != self._built
            sp.set(compiled=int(self.compiled))
        if sp is _trace._NOOP:
            self.t1 = time.perf_counter_ns()
        else:
            sp.__exit__(exc_type, exc, tb)
            self.t1 = sp.t1
        self.dt = dt = (self.t1 - self.t0) * 1e-9
        if exc_type is not None:
            return False
        eng = self._eng
        if self._hist is not None:
            observe(self._hist, dt)
        if self._entry is not None:
            eng._note_compile(*self._entry, dt, self.compiled)
        if self._bucket is not None and eng._goodput is not None:
            eng._goodput.note(
                "recompile" if self.compiled else self._bucket, dt)
        return False


class _NoopPhase:
    """What :meth:`GenerationEngine._phase` returns for a section that
    has nothing to feed: no span records and it has no histogram, no
    compiled entry and no goodput bucket to fill, and whose caller
    asks for no clock read. Shared; allocates nothing, reads no
    clock."""

    __slots__ = ()
    recording = False

    def set(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_PHASE = _NoopPhase()


def stream_fingerprint(prompt, temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, seed: int = 0) -> str:
    """Crash fingerprint of a stream — the quarantine identity. One
    recipe shared by the engine (every :class:`Generation` hashes its
    own request) and the resuming router client (which passes the
    ORIGINAL stream's fingerprint on replay attempts, wire header
    ``fp``, because the replay prompt grew by the delivered tokens and
    would otherwise hash fresh — letting resumed poison dodge
    quarantine)."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    return hashlib.sha1(
        prompt.tobytes()
        + f"|{float(temperature)}|{int(top_k)}|{float(top_p)}"
          f"|{int(seed)}".encode()
    ).hexdigest()[:16]


class EngineOverloaded(RuntimeError):
    """Every slot is busy and the admit queue is full; the request was
    NOT enqueued. Safe to retry after ``retry_after_s`` — the serving
    layer maps this to the wire's retryable ``CODE_SHED`` status."""

    def __init__(self, msg: str, retry_after_s: float = 0.25):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class RequestQuarantined(RuntimeError):
    """This request's crash fingerprint (prompt bytes + sampling
    params) has trapped the engine ``FLAGS_gen_quarantine_after``
    times; it is rejected at admission instead of being retried into
    every replica in the fleet. NOT retryable — the typed give-up the
    stream-resumption layer must surface, never resume past."""

    def __init__(self, msg: str, fingerprint: str = ""):
        super().__init__(msg)
        self.fingerprint = fingerprint


class GenerationExpired(KeyError):
    """The polled generation existed here but was reaped by the poll
    TTL (client presumed disconnected). Distinct from a plain
    ``KeyError`` — "expired" is a fact about THIS replica, "unknown"
    may mean the caller polled the wrong replica entirely."""


class _EpochChanged(RuntimeError):
    """Internal: the watchdog failed this step's generations while the
    compiled call was in flight — its results (and the state it
    returned) must be discarded, and the loop must rebuild or break."""


class Generation:
    """Host-side record of one generation request (the engine's unit of
    scheduling). ``tokens`` grows as decode steps emit; ``slot`` is None
    while queued and again after retirement."""

    __slots__ = ("gen_id", "prompt", "max_new_tokens", "temperature",
                 "top_k", "top_p", "eos_token_id", "seed", "tokens",
                 "done", "error", "slot", "created", "last_poll",
                 "cancelled", "pages", "shared", "prefilling",
                 "prefill_pos", "prefill_t0", "delivered", "fingerprint",
                 "rng_skip", "spec_proposed", "spec_accepted", "trace_id",
                 "tenant", "admitted_ts", "first_tok_ts", "done_ts",
                 "chip_s", "ledgered", "dev_ops", "pclass", "folded",
                 "queue_booked", "sched_seq", "sched_vft", "sched_ts",
                 "win", "sorts", "snap_src", "wake", "waiting",
                 "blocks", "bstart", "bphase", "bneed")

    def __init__(self, gen_id: str, prompt: np.ndarray,
                 max_new_tokens: int, temperature: float, top_k: int,
                 top_p: float, eos_token_id: int | None, seed: int):
        self.gen_id = gen_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        # the request restricts its sampling: live, it pulls the steps'
        # sampler into the sort (:func:`_sample`, on the float32 it sees)
        self.sorts = bool(temperature > 0.0 and (
            top_k > 0 or np.float32(top_p) < 1.0))
        self.eos_token_id = eos_token_id
        self.seed = seed
        self.tokens: list[int] = []
        self.done = False
        self.error: str | None = None
        self.slot: int | None = None
        self.created = time.monotonic()
        self.last_poll = self.created
        self.cancelled = False
        # a poll response carried done=True with every token: the client
        # has everything — the signal a sticky drain waits on
        self.delivered = False
        # paged mode: mapped physical pages (shared prefix first), how
        # many of them are prefix-cache hits, and chunked-prefill cursor
        self.pages: list[int] = []
        self.shared = 0
        self.prefilling = False
        self.prefill_pos = 0
        self.prefill_t0 = 0.0
        # crash fingerprint (quarantine identity) and the RNG position a
        # resumed sampled stream replays (splits consumed before this
        # stream's first token — 0 for a fresh stream)
        self.fingerprint = stream_fingerprint(prompt, temperature,
                                              top_k, top_p, seed)
        self.rng_skip = 0
        # stream trace id (wire header "st"): the fleet-unique identity
        # of the LOGICAL stream this generation serves — minted once at
        # the first generate_start and replayed verbatim by failover
        # resume, so one stream's slot events merge across replicas
        self.trace_id: str | None = None
        # speculative-decoding acceptance accounting (draft tokens this
        # generation proposed / had accepted; stays 0 with spec off)
        self.spec_proposed = 0
        self.spec_accepted = 0
        # latency-ledger books (wire header "tn" + monotonic phase
        # stamps + attributed device seconds); stamps stay 0.0 and
        # ledgered stays False for the engine's whole life when
        # FLAGS_gen_ledger is off
        self.tenant: str | None = None
        self.admitted_ts = 0.0
        self.first_tok_ts = 0.0
        self.done_ts = 0.0
        self.chip_s = 0.0
        self.ledgered = False
        # lazily built device-side per-request operands (starting PRNG
        # key with rng_skip applied, temperature/top_k/top_p scalars) —
        # immutable for the generation's lifetime, so chunked prefill
        # stops re-materializing them every chunk
        self.dev_ops: tuple | None = None
        # scheduler books (FLAGS_gen_sched; inert defaults otherwise):
        # priority class, tokens already folded into the prompt by a
        # preemption park, queue wait booked live at admission, and the
        # fair-queue tag/sequence/admission-stamp the scheduler assigns
        # a layer-group engine's window-group row (a _WinRow) while the
        # generation holds a slot; None everywhere else
        self.win = None
        # a state-group engine: the snapshot the first prefill chunk
        # starts from (0 = the zero state; None once a chunk has run),
        # pinned in the snapshot pool until that chunk is dispatched
        self.snap_src: int | None = None
        self.pclass = "batch"
        self.folded = 0
        self.queue_booked = 0.0
        self.sched_seq = 0
        self.sched_vft = 0.0
        self.sched_ts = 0.0
        # the stream's own wake-up: a poll waits on it (never on the
        # engine's lock) and counts itself in ``waiting``; whatever hands
        # the stream a token, an end or an error puts one in
        # (``GenerationEngine._wake``), which never blocks the loop
        self.wake: queue.SimpleQueue = queue.SimpleQueue()
        self.waiting = 0
        # a block-diffusion engine: every finished block as ``(first
        # position, ids, the denoising step each was fixed at)``; whether
        # the next step starts the stream's first block; the steps taken
        # in the current block and the denoising steps it needs
        self.blocks: list = []
        self.bstart = False
        self.bphase = 0
        self.bneed = 0


class _PagePool:
    """Host-side refcounted allocator over the physical page pool.
    Usable page ids are ``1 .. num_pages``; id 0 is the reserved null
    page (unmapped table entries, masked padding writes). All methods
    run under the engine's condition lock."""

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)
        self._free = list(range(self.num_pages, 0, -1))   # pop() -> 1 first
        self._ref = [0] * (self.num_pages + 1)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int]:
        _fault.inject("paged.alloc")
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, free {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        for pid in out:
            self._ref[pid] = 1
        return out

    def retain(self, pid: int) -> None:
        self._ref[pid] += 1

    def release(self, pid: int) -> None:
        self._ref[pid] -= 1
        if self._ref[pid] == 0:
            self._free.append(pid)
        elif self._ref[pid] < 0:        # double free = allocator bug
            raise AssertionError(f"page {pid} refcount underflow")

    def refcount(self, pid: int) -> int:
        return self._ref[pid]


class _SnapshotPool:
    """Host-side refcounted books of a state group's snapshot pool:
    ``num`` entries of one slot's state rows each (every KDA layer's
    state and convolution tail). Usable ids are ``1 .. num``; id 0 is
    the ZERO snapshot (never written: what a stream with no prefix hit
    starts from) and ``num + 1`` the scratch entry (where a prefill
    chunk's end state goes when nobody keeps it). The prefix cache holds
    one reference for the entry a snapshot hangs on, an admitted stream
    one more until its first chunk has read it. All methods run under
    the engine's condition lock."""

    def __init__(self, num: int):
        self.num = int(num)
        self.scratch = self.num + 1
        self._free = list(range(self.num, 0, -1))
        self._ref = [0] * (self.num + 2)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        """A free entry with one reference, or 0 where none is free."""
        if not self._free:
            return 0
        sid = self._free.pop()
        self._ref[sid] = 1
        return sid

    def retain(self, sid: int) -> None:
        if sid:
            self._ref[sid] += 1

    def release(self, sid: int) -> None:
        if not sid:
            return
        self._ref[sid] -= 1
        if self._ref[sid] == 0:
            self._free.append(sid)
        elif self._ref[sid] < 0:
            raise AssertionError(f"snapshot {sid} refcount underflow")

    def refcount(self, sid: int) -> int:
        return self._ref[sid]


class _WinRow:
    """One stream's row in a window layer group (host side): the live
    logical pages ``base .. base + len(pages)``, which of them the
    stream drew fresh from the pool and still answers for (``own``),
    how many more it may draw before it lets one go (``need``), and the
    position its next decode step writes (``pos``)."""

    __slots__ = ("base", "pages", "own", "need", "pos", "total")

    def __init__(self, base: int, pages: list[int], need: int, total: int):
        self.base, self.pages, self.need = base, pages, need
        self.total = total       # logical pages of the declared worst case
        self.own: set[int] = set()
        self.pos = 0


class _WindowGroup:
    """Host-side books of a window layer group: its pool, its table
    (``pt`` [slots, 1 + row_pages]: a row's base, then the page ids of
    logical pages ``base, base + 1, ...``; 0 = null page) and the
    promise that keeps a running stream from ever finding the pool
    empty.

    A stream holds only the pages a program may still read: those
    covering ``[first - window + 1, end)`` for a program that starts at
    position ``first`` and writes up to ``end`` — never more than
    ``row_pages``. :meth:`cover` lets go of what lies wholly behind and
    maps fresh pages ahead, before the program that needs them is
    dispatched. ``debt`` is the sum over live streams of the fresh pages
    each may still draw (``_WinRow.need``); ``pool.free_count >= debt``
    always holds: admission reserves a stream's most (:meth:`budget`),
    a draw and a page given back move both sides alike, and a fresh
    page handed to the prefix cache (which then pins it) is paid for
    from :meth:`spare` pages or not handed over at all. All methods run
    under the engine's condition lock."""

    def __init__(self, window: int, num_pages: int, page_tokens: int,
                 slots: int, chunk: int, maxp: int):
        self.window, self.P = int(window), int(page_tokens)
        # pages covering [first - window + 1, first + chunk) at any
        # alignment: window/P + 1 + chunk/P where P divides both
        self.row_pages = min(
            maxp, -(-(self.window - 1 + chunk) // self.P) + 1)
        if num_pages <= 0:           # every slot its fullest row
            num_pages = slots * self.row_pages
        self.pool = _PagePool(num_pages)
        self.pt = np.zeros((slots, 1 + self.row_pages), np.int32)
        self.debt = 0
        self.slid = 0            # pages streams let go behind the window
        self.peak = 0            # most pages one stream ever held

    def reset(self) -> None:
        """Fresh books over a replaced device pool: no row is mapped,
        nothing is promised (``slid`` and ``peak`` stay: they count)."""
        self.pool = _PagePool(self.pool.num_pages)
        self.pt[:] = 0
        self.debt = 0

    def spare(self) -> int:
        """Free pages no live stream has been promised."""
        return self.pool.free_count - self.debt

    def budget(self, total_pages: int, matched: int) -> int:
        """The most fresh pages a stream of ``total_pages`` logical
        pages, ``matched`` of them cached, holds at once."""
        return min(total_pages - matched, self.row_pages)

    def first_page(self, first: int) -> int:
        """The logical page of the first position a program starting at
        position ``first`` still reads."""
        return max(first - self.window + 1, 0) // self.P

    def admit(self, slot: int, matched_wpages: list[int], total: int):
        """A stream of ``total`` logical pages takes its slot behind
        ``len(matched_wpages)`` cached prompt pages: it maps those its
        first chunk still reads, and is promised its budget of fresh
        ones."""
        m = len(matched_wpages)
        base = self.first_page(m * self.P)
        need = self.budget(total, m)
        row = _WinRow(base, matched_wpages[base:], need, total)
        for pid in row.pages:
            self.pool.retain(pid)
        self.debt += need
        self._write(slot, row)
        return row

    def cover(self, row: _WinRow, slot: int, first: int, end: int) -> int:
        """Before a program that starts at position ``first`` and writes
        ``[first, end)``: let go of the pages wholly behind its window,
        map fresh ones up to ``end``. Returns the pages let go."""
        keep = self.first_page(first)
        let_go = min(max(keep - row.base, 0), len(row.pages))
        for pid in row.pages[:let_go]:
            if pid in row.own:
                self._disown(row, pid)
            self.pool.release(pid)
        if let_go:
            del row.pages[:let_go]
            self.slid += let_go
        row.base = max(row.base, keep)
        # (a lookahead step past a stream's end writes to the null page)
        fresh = ((min(end, row.total * self.P) - 1) // self.P
                 - (row.base + len(row.pages)) + 1)
        if fresh > 0:
            got = self.pool.alloc(fresh)
            row.pages += got
            row.own.update(got)
            row.need -= fresh
            self.debt -= fresh
        if let_go or fresh > 0:
            self.peak = max(self.peak, len(row.pages))
            self._write(slot, row)
        return let_go

    def _disown(self, row: _WinRow, pid: int) -> None:
        """A fresh page leaves the stream's account (back to the pool,
        or pinned by the cache): the stream may draw one more."""
        row.own.discard(pid)
        row.need += 1
        self.debt += 1

    def hand_to_cache(self, row: _WinRow, i: int) -> int:
        """The stream's page for logical page ``i``, retained for the
        prefix cache, or 0 where the stream no longer holds it or the
        pool cannot spare the page the cache would pin."""
        j = i - row.base
        if not 0 <= j < len(row.pages):
            return 0
        pid = row.pages[j]
        if pid in row.own:
            if self.spare() < 1:
                return 0
            self._disown(row, pid)
        self.pool.retain(pid)
        return pid

    def release(self, row: _WinRow) -> None:
        """The stream is gone (retired, cancelled, reaped): every page
        it holds, and what it was still promised. Its table row is the
        caller's to zero, with the full group's."""
        for pid in row.pages:
            self.pool.release(pid)
        self.debt -= row.need
        row.pages, row.need = [], 0
        row.own.clear()

    def _write(self, slot: int, row: _WinRow) -> None:
        self.pt[slot] = 0
        self.pt[slot, 0] = row.base
        self.pt[slot, 1:1 + len(row.pages)] = row.pages


class _PrefixEntry:
    __slots__ = ("key", "page", "parent_page", "children", "last_used",
                 "wpage", "snap")

    def __init__(self, key, page: int, parent_page: int, wpage: int = 0):
        self.key = key
        self.page = page
        # the same prompt page in a layer-group engine's window pool
        self.wpage = wpage
        # a state-group engine: the snapshot of the recurrent state at
        # this page's END (0 = none: the page cannot end a match)
        self.snap = 0
        self.parent_page = parent_page
        self.children = 0
        self.last_used = 0


class _PrefixCache:
    """Radix cache over FULL prompt pages: entry key = (parent page id,
    the page's token bytes), so two prompts share exactly their common
    whole-page prefix. Only pages fully covered by a prompt are ever
    registered (decode writes start at the prompt length — registered
    pages are immutable), and a match is capped so at least one prompt
    token remains to prefill (the sampled first token needs its logits).
    The cache holds its own +1 refcount per registered page, so shared
    pages outlive their last generation until LRU-evicted under pool
    pressure (leaf entries first — a parent is only evictable once its
    children are gone).

    ``second`` (a layer-group engine's window pool): every entry then
    holds one page of EACH pool for its prompt page, with a refcount of
    its own in both, and leaves with both — the key stays the full
    group's page id.

    ``snaps`` (a state-group engine's snapshot pool): pages alone
    cannot serve a prefix there — a stream also needs the recurrent
    state at the prefix's end. An entry may carry a snapshot of it
    (``snap``); :meth:`match_state` cuts a match back to the deepest
    entry that has one, an entry that leaves releases its snapshot, and
    :meth:`evict_snapshot` takes the least recently used snapshot from
    an entry that stays (its pages can then only be matched THROUGH, on
    the way to a deeper snapshot)."""

    def __init__(self, page_tokens: int, second: _PagePool | None = None,
                 snaps: _SnapshotPool | None = None):
        self._P = int(page_tokens)
        self._second = second
        self._snaps = snaps
        self._entries: dict[tuple, _PrefixEntry] = {}
        self._by_page: dict[int, _PrefixEntry] = {}
        self._clock = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _touch(self, e: _PrefixEntry) -> None:
        self._clock += 1
        e.last_used = self._clock

    def match(self, prompt: np.ndarray, pool: _PagePool) -> list[int]:
        """Longest cached whole-page prefix of ``prompt``; each matched
        page is retained for the caller (release on failure/retire)."""
        P = self._P
        cap = (int(prompt.size) - 1) // P
        pages: list[int] = []
        parent = 0
        for i in range(cap):
            e = self._entries.get((parent, prompt[i * P:(i + 1) * P]
                                   .tobytes()))
            if e is None:
                break
            self._touch(e)
            pool.retain(e.page)
            pages.append(e.page)
            parent = e.page
        return pages

    def match_state(self, prompt: np.ndarray,
                    pool: _PagePool) -> tuple[list[int], int]:
        """:meth:`match` cut back to the deepest entry that holds a
        snapshot: ``(pages, snapshot id)``, both retained for the caller
        (``([], 0)`` where no entry on the chain has one)."""
        pages = self.match(prompt, pool)
        keep = max((i + 1 for i, p in enumerate(pages)
                    if self._by_page[p].snap), default=0)
        for pid in pages[keep:]:
            pool.release(pid)
        snap = self._by_page[pages[keep - 1]].snap if keep else 0
        self._snaps.retain(snap)
        return pages[:keep], snap

    def find(self, prompt: np.ndarray) -> _PrefixEntry | None:
        """The entry of ``prompt``'s last whole page, if its whole chain
        is cached (no touch, nothing retained)."""
        P, parent, e = self._P, 0, None
        for i in range(int(prompt.size) // P):
            e = self._entries.get((parent, prompt[i * P:(i + 1) * P]
                                   .tobytes()))
            if e is None:
                return None
            parent = e.page
        return e

    def _drop_snapshot(self, e: _PrefixEntry) -> None:
        self._snaps.release(e.snap)
        e.snap = 0

    def evict_snapshot(self) -> bool:
        """Free one snapshot: the least recently used among those no
        admitted stream still has to read. Its entry stays."""
        cands = [e for e in self._entries.values()
                 if e.snap and self._snaps.refcount(e.snap) == 1]
        if not cands:
            return False
        self._drop_snapshot(min(cands, key=lambda c: c.last_used))
        stat_add("gen/state_snapshot_evictions")
        return True

    def wpages(self, pages: list[int]) -> list[int]:
        """The window-pool pages of matched entries, by their full-pool
        page ids (as :meth:`match` returned them)."""
        return [self._by_page[p].wpage for p in pages]

    def insert(self, prompt: np.ndarray, gen_pages: list[int],
               pool: _PagePool, second=None, snap: int = 0) -> None:
        """Register a finished prefill's full prompt pages. Pages whose
        chain key is already cached (matched, or raced by a concurrent
        identical prompt) are touched, not replaced — the generation
        keeps its private copy in that case. ``second(i)`` (a
        layer-group engine): the window-pool page the caller hands over
        for prompt page ``i``, already retained for the cache, or 0 —
        the chain then ends at ``i`` (the stream let that page go, or
        the pool has none to spare). ``snap`` (a state-group engine): a
        snapshot of the state at ``prompt``'s end, a whole number of
        pages, with the one reference that now becomes the last entry's
        (given back where that entry holds a snapshot already)."""
        P = self._P
        parent = 0
        e = None
        for i in range(int(prompt.size) // P):
            key = (parent, prompt[i * P:(i + 1) * P].tobytes())
            e = self._entries.get(key)
            if e is None:
                wpage = 0
                if second is not None:
                    wpage = second(i)
                    if not wpage:
                        break
                e = _PrefixEntry(key, gen_pages[i], parent_page=parent,
                                 wpage=wpage)
                self._entries[key] = e
                self._by_page[e.page] = e
                pool.retain(e.page)
                pe = self._by_page.get(parent)
                if pe is not None:
                    pe.children += 1
            self._touch(e)
            parent = e.page
        if snap:
            if e is None or e.snap:
                self._snaps.release(snap)
            else:
                e.snap = snap

    def evict(self, n: int, pool: _PagePool, demote=None) -> int:
        """Free up to ``n`` pages by dropping LRU leaf entries no live
        generation references (page refcount 1 = cache-only). With a
        ``demote`` callback (the KV-store hook), each victim is handed
        over — still registered, page still live — before release, so
        eviction demotes the page to the store instead of dropping it.
        With a second pool an entry leaves only when no stream holds
        either of its pages, and frees one page in each."""
        freed = 0
        second = self._second
        while freed < n:
            cands = [e for e in self._entries.values()
                     if e.children == 0 and pool.refcount(e.page) == 1
                     and (second is None
                          or second.refcount(e.wpage) == 1)]
            if not cands:
                break
            e = min(cands, key=lambda c: c.last_used)
            if demote is not None:
                demote(e)
            del self._entries[e.key]
            self._by_page.pop(e.page, None)
            pe = self._by_page.get(e.parent_page)
            if pe is not None:
                pe.children -= 1
            pool.release(e.page)
            if second is not None:
                second.release(e.wpage)
            if e.snap:
                self._drop_snapshot(e)
            freed += 1
        if freed:
            stat_add("gen/prefix_evictions", freed)
        return freed

    def chain_tokens(self, e: _PrefixEntry) -> list[bytes] | None:
        """Root-to-leaf token bytes of ``e``'s radix chain (each element
        is one full page's int32 token bytes) — the input to the store's
        :func:`~paddle_tpu.serving.kvstore.page_chain_keys`. A parent is
        only evictable after its children, so the walk is complete for
        any live entry; returns None on a broken chain (mid-rebuild)."""
        chain: list[bytes] = []
        cur = e
        while True:
            chain.append(cur.key[1])
            if cur.parent_page == 0:
                break
            cur = self._by_page.get(cur.parent_page)
            if cur is None:
                return None
        chain.reverse()
        return chain


def _sample(logits, keys, temperature, top_k, top_p, live):
    """Next-token picks for ``[N, V]`` logits with fully-traced per-row
    sampling params (one compiled program serves every request mix), the
    work following what the ``live`` rows of THIS call ask for — a
    ``lax.switch`` on scalars reduced over them (a retired slot keeps
    its last occupant's params in the state and arms nothing):

    0. no live row has ``temperature > 0``: ``argmax`` alone — bit-equal
       to ``sample_logits``'s greedy path;
    1. some live row samples, none restricts (``top_k <= 0`` keeps all,
       ``top_p >= 1`` keeps all): ``categorical(key, logits /
       temperature)``, what ``sample_logits`` does for such a request;
    2. some live row restricts: ONE descending sort a row. The top-k
       cut applies in the sorted row (ties with the k-th value kept),
       which is the row the nucleus reads.

    A row's pick does not depend on the arm its co-tenants pulled the
    call into: in every arm a greedy row yields its ``argmax`` and an
    unrestricted sampling row ``categorical(key, logits / temperature)``
    — the masks of arm 2 apply only where the row itself asks.
    Returns ``(tokens [N] int32, arm)``."""
    import jax
    import jax.numpy as jnp

    def greedy(logits, *_):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def scaled(logits, temperature):
        return (logits.astype(jnp.float32)
                / jnp.maximum(temperature, 1e-6)[:, None])

    def drawn(logits, keys, temperature, lt):
        sampled = jax.vmap(jax.random.categorical)(keys, lt)
        return jnp.where(temperature <= 0.0, greedy(logits),
                         sampled.astype(jnp.int32))

    def arm_plain(logits, keys, temperature, top_k, top_p):
        return drawn(logits, keys, temperature, scaled(logits, temperature))

    def arm_sorted(logits, keys, temperature, top_k, top_p):
        lt = scaled(logits, temperature)
        V = lt.shape[-1]
        top_p = top_p[:, None]
        # values alone: stability orders nothing a pick can see, and the
        # unstable sort moves no index operand beside them
        desc = jnp.sort(lt, axis=-1, stable=False)[:, ::-1]
        # top-k via the kth-largest threshold, k traced
        k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
        kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
        desc = jnp.where(desc < kth, -jnp.inf, desc)
        # nucleus over what survived top-k (the sample_logits ordering)
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p              # always keeps the top-1
        thr = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                      keepdims=True)
        # the float32 cumulative sum rounds to 1.0 before the row ends:
        # a row that sets no top_p keeps its whole tail
        thr = jnp.where(top_p >= 1.0, -jnp.inf, thr)
        lt = jnp.where((lt < kth) | (lt < thr), -jnp.inf, lt)
        return drawn(logits, keys, temperature, lt)

    with jax.named_scope("sample"):
        sampling = live & (temperature > 0.0)
        restricted = sampling & ((top_k > 0) | (top_p < 1.0))
        arm = (jnp.any(sampling).astype(jnp.int32)
               + jnp.any(restricted).astype(jnp.int32))
        tokens = jax.lax.switch(
            arm, (greedy, arm_plain, arm_sorted), logits, keys,
            temperature, top_k, top_p)
        return tokens, arm


def _sample_one(logits, key, temperature, top_k, top_p):
    """:func:`_sample` for one row with the request's own scalars (a
    prefill's first token)."""
    import jax.numpy as jnp

    tokens, _ = _sample(
        logits[None], key[None],
        jnp.asarray(temperature, jnp.float32)[None],
        jnp.asarray(top_k, jnp.int32)[None],
        jnp.asarray(top_p, jnp.float32)[None], jnp.ones((1,), bool))
    return tokens[0]


class GenerationEngine:
    """Slot-scheduled continuous-batching decode over one model.

    ``model`` is any object with ``init_cache(B, S, dtype=...)`` and
    ``forward_with_cache(ids, cache, index)`` (the ``models/generation``
    contract — Llama/GPT/MoE). ``slots`` defaults to ``FLAGS_gen_slots``
    (0 = generation serving disabled: constructing without an explicit
    ``slots`` raises); ``max_len``/``queue_max``/``ttl_s`` default to
    ``FLAGS_gen_max_len``/``FLAGS_gen_queue_max``/``FLAGS_gen_poll_ttl_s``.

    ``paged``/``page_tokens``/``pages``/``prefill_chunk``/``prefix_cache``
    default to the ``FLAGS_gen_paged``/``gen_page_tokens``/``gen_pages``/
    ``gen_prefill_chunk``/``gen_prefix_cache`` flags; with paging off
    (the default) the engine keeps the PR-5 contiguous per-slot cache
    byte-identically. Greedy output is byte-identical to solo
    ``generate()`` in both modes, under any co-tenant mix, page reuse,
    and chunked prefill.

    ``state_snapshots`` (a paged engine of a model with a recurrent
    state group only, see the module docstring): the entries of the
    snapshot pool that prefix hits restore from, one slot's state rows
    each.

    ``mesh_tp`` defaults to ``FLAGS_gen_mesh_tp`` (0 = no mesh: the
    single-device path, byte-identical to the pre-sharding build). A
    positive degree builds the engine over a tensor-parallel device
    mesh — params column/row-split, KV cache/page pool sharded on the
    KV-head axis, every compiled entry point given explicit in/out
    shardings (``serving/layout.py``). Token streams stay
    byte-identical across layouts, so failover/resume compose with any
    mix of sharded and unsharded replicas; ``stats()['device']`` ships
    the topology.

    ``quarantine_after``/``rebuilds``/``watchdog_s`` default to the
    ``gen_quarantine_after``/``gen_engine_rebuilds``/``gen_watchdog_s``
    flags (all 0 = the pre-resilience behavior: no quarantine books, the
    first decode-loop trap breaks the engine terminally, no watchdog
    thread). See the module docstring's self-healing section.

    The background loop starts on construction; :meth:`close` retires it.
    All device state is touched only by the loop thread — the public
    surface (:meth:`start`/:meth:`poll`/:meth:`cancel`) is host-side and
    lock-guarded.
    """

    def __init__(self, model, *, slots: int | None = None,
                 max_len: int | None = None, queue_max: int | None = None,
                 ttl_s: float | None = None, eos_token_id: int | None = None,
                 pad_token_id: int = 0, cache_dtype=None,
                 min_bucket: int = 8, step_wait_s: float = 0.0,
                 paged: bool | None = None, page_tokens: int | None = None,
                 pages: int | None = None, prefill_chunk: int | None = None,
                 prefix_cache: bool | None = None,
                 quarantine_after: int | None = None,
                 rebuilds: int | None = None,
                 watchdog_s: float | None = None,
                 spec_k: int | None = None, spec_mode: str | None = None,
                 draft_model=None, spec_ngram: int | None = None,
                 spec_shed_occupancy: float | None = None,
                 mesh_tp: int | None = None, ledger=None,
                 kv_store=None, role: str | None = None,
                 device_pt: bool | None = None,
                 async_depth: int | None = None,
                 sched=None, state_snapshots: int = 16):
        if slots is None:
            slots = int(flag("gen_slots"))
        if slots <= 0:
            raise ValueError(
                "generation serving is disabled (FLAGS_gen_slots=0); set "
                "the flag or pass slots= explicitly")
        self.slots = int(slots)
        self.max_len = int(flag("gen_max_len") if max_len is None
                           else max_len)
        cfg_max = getattr(getattr(model, "config", None), "max_seq_len",
                          None)
        if cfg_max is not None:
            self.max_len = min(self.max_len, int(cfg_max))
        self._queue_max = int(flag("gen_queue_max") if queue_max is None
                              else queue_max)
        self._ttl_s = float(flag("gen_poll_ttl_s") if ttl_s is None
                            else ttl_s)
        self._eos_default = eos_token_id
        self._pad = int(pad_token_id)
        self._min_bucket = max(int(min_bucket), 1)
        # pacing knob: minimum gap between fused decode steps (throttle
        # a host-loop-bound engine, or make scheduling windows
        # deterministic in tests/chaos checks); 0 = run flat out
        self.step_wait_s = float(step_wait_s)
        self._model = model
        self._cache_dtype = cache_dtype
        self._paged = bool(flag("gen_paged") if paged is None else paged)
        # decode hot-loop knobs (hard-off by default; flags read HERE
        # only, never per token): a device-resident page table (paged
        # engines only — inert otherwise) and the async dispatch
        # lookahead depth (0 = the fully synchronous loop)
        self._device_pt = self._paged and bool(
            flag("gen_device_pt") if device_pt is None else device_pt)
        self._async_depth = max(0, int(flag("gen_async_depth")
                                       if async_depth is None
                                       else async_depth))
        self._prefill_chunk = int(flag("gen_prefill_chunk")
                                  if prefill_chunk is None
                                  else prefill_chunk)
        # self-healing knobs (all hard-off by default; see module doc)
        self._quarantine_after = int(flag("gen_quarantine_after")
                                     if quarantine_after is None
                                     else quarantine_after)
        self._rebuild_max = int(flag("gen_engine_rebuilds")
                                if rebuilds is None else rebuilds)
        self._watchdog_s = float(flag("gen_watchdog_s")
                                 if watchdog_s is None else watchdog_s)
        # speculative decoding (hard-off by default: gen_spec_k=0 keeps
        # the compiled surface and decode path byte-identical to the
        # pre-speculation build — flags are read HERE only, never on
        # the data path)
        self._spec_k = int(flag("gen_spec_k") if spec_k is None
                           else spec_k)
        self._spec_mode = str(flag("gen_spec_mode") if spec_mode is None
                              else spec_mode)
        self._spec_ngram = int(flag("gen_spec_ngram") if spec_ngram is None
                               else spec_ngram)
        self._spec_shed = float(flag("gen_spec_shed_occupancy")
                                if spec_shed_occupancy is None
                                else spec_shed_occupancy)
        self._draft_model = draft_model
        if self._spec_k > 0:
            if self._spec_mode not in ("ngram", "draft"):
                raise ValueError(
                    f"unknown gen_spec_mode {self._spec_mode!r}; expected "
                    "'ngram' or 'draft'")
            if self._spec_mode == "draft" and draft_model is None:
                raise ValueError(
                    "gen_spec_mode=draft needs a draft_model= (any model "
                    "with the init_cache/forward_with_cache contract)")
        else:
            self._spec_mode = "off"
        # tensor-parallel device layout (hard-off by default:
        # gen_mesh_tp=0 builds no mesh — DeviceLayout is the identity,
        # every compiled entry point is the plain single-device jit,
        # byte-identical to the pre-sharding build. The flag is read
        # HERE only, never on the decode hot path). Sharded params are
        # committed before any cache/entry-point construction so the
        # partitioner sees one consistent layout.
        if getattr(model, "latent_cache", False) and draft_model is not None:
            raise ValueError(
                "a draft model beside a latent (MLA) cache is not "
                "implemented: the lookahead keeps a per-head K/V scratch "
                "cache of its own bucket; use spec_mode='ngram' or none")
        # counts the model's layers record per position on a state tape
        # (an expert layer's routed picks): summed over LIVE positions
        # on the device, in the donated state, and fetched by stats().
        # A model that names none compiles the programs it always did.
        self._count_names = tuple(getattr(model, "live_counts", ()))
        self._counts_host = dict.fromkeys(self._count_names, 0)
        self._counts_base = dict(self._counts_host)
        self._counts_req: threading.Event | None = None
        from paddle_tpu.serving.layout import DeviceLayout
        self._layout = DeviceLayout(int(flag("gen_mesh_tp")
                                        if mesh_tp is None else mesh_tp))
        if self._layout.sharded:
            self._model = model = self._layout.shard_model(model)
            if self._draft_model is not None:
                self._draft_model = self._layout.shard_model(
                    self._draft_model)
        # per-bucket compiled draft-model proposers (mode=draft only)
        self._draft_fns: dict[int, Any] = {}
        # tokens_per_step books: decode-step emitted tokens over decode
        # iterations — distinguishes speculation wins (>1 per slot-step)
        # from batching wins; spec acceptance totals ride along
        self._emit_total = 0
        self._decode_iters = 0
        # decode steps whose sampler sorted (a live request restricts)
        self._sample_sorted_steps = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_verify_steps = 0
        # XLA compile books: (entry point, shape signature) pairs seen.
        # The first call with a new signature IS the compile (jit caches
        # thereafter), so its wall clock approximates compile time; a
        # second-or-later signature on one entry point is a RECOMPILE —
        # the classic silent TPU perf killer this surfaces in health
        self._compiled_seen: set[tuple[str, Any]] = set()
        self._recompiles = 0
        self._recompile_ts: deque[float] = deque(maxlen=256)
        # performance-attribution books (hard-off by default:
        # gen_ledger=False builds neither, and every hot-path gate is a
        # single is-None attribute check — the FLAGS_trace pattern.
        # Flags are read HERE only, never per token). ledger= accepts
        # True/False to force, or a RequestLedger to share one.
        led = flag("gen_ledger") if ledger is None else ledger
        if led:
            from paddle_tpu.serving.ledger import GoodputMeter, RequestLedger
            self._ledger = (led if isinstance(led, RequestLedger)
                            else RequestLedger(int(flag(
                                "gen_ledger_records"))))
            self._goodput = GoodputMeter()
        else:
            self._ledger = None
            self._goodput = None
        # disaggregated-serving KV store (hard-off by default:
        # gen_kv_store=False builds no store and no role machinery —
        # every hot-path gate below is a single is-None check on
        # self._kv, same discipline as the ledger. Flags are read HERE
        # only). kv_store= accepts True/False to force, or a KVStore to
        # share one (how the in-proc tests model a fleet). The store
        # lives OUTSIDE _rebuild's pool/prefix replacement: serialized
        # host bytes survive engine self-healing by design.
        self._role = str(flag("gen_role") if role is None else role)
        if self._role not in ("prefill", "decode", "both"):
            raise ValueError(f"unknown gen_role {self._role!r}; expected "
                             "'prefill', 'decode' or 'both'")
        kv = flag("gen_kv_store") if kv_store is None else kv_store
        if kv:
            if not self._paged:
                raise ValueError("gen_kv_store requires the paged engine "
                                 "(gen_paged / paged=True): only paged "
                                 "KV is a transferable unit")
            from paddle_tpu.serving.kvstore import KVStore
            self._kv_owned = not isinstance(kv, KVStore)
            peers = tuple(p.strip() for p in
                          str(flag("gen_kv_peers")).split(",") if p.strip())
            self._kv = kv if isinstance(kv, KVStore) else KVStore(
                pages=int(flag("gen_kv_store_pages")),
                spill=str(flag("gen_kv_spill_dir")) or None,
                fetch_timeout_s=float(flag("gen_kv_fetch_timeout_s")),
                hedge_ms=float(flag("gen_kv_hedge_ms")),
                breaker=int(flag("gen_kv_breaker")),
                breaker_backoff_s=float(flag("gen_kv_breaker_backoff_s")),
                peers=peers)
            # admission-level fetch budget across one gen's page chain
            self._kv_admit_s = float(flag("gen_kv_admit_timeout_s"))
            # prefill-tier replicas are producers: they publish but
            # never fetch; decode-tier (and 'both') replicas fetch at
            # admission. Whoever ran a prefill publishes its pages —
            # that write is what makes the store fleet-wide.
            self._kv_fetch = self._role in ("decode", "both")
            self._kv_published = 0       # pages this engine put
            self._kv_fetched_pages = 0   # pages admitted from the store
            self._kv_fetched_bytes = 0
            self._kv_demoted = 0         # prefix evictions demoted, not
            self._kv_recomputed = 0      # dropped; resumed-prefill debt
            self._kv_degraded = 0        # fetches degraded to recompute
        else:
            self._kv = None
            self._kv_owned = False
            self._kv_fetch = False
            self._kv_admit_s = 0.0
        # SLO-aware tenant-fair scheduler (hard-off by default:
        # gen_sched=False builds none, and every hot-path gate below is
        # a single is-None attribute check — the ledger discipline.
        # Flags are read HERE only, never per iteration). sched=
        # accepts True/False to force, or a GenScheduler to share one —
        # how the serving layer routes FrameService/DynamicBatcher shed
        # decisions through the same policy object as the loop.
        sc = flag("gen_sched") if sched is None else sched
        if sc:
            from paddle_tpu.serving.scheduler import GenScheduler
            self._sched = (sc if isinstance(sc, GenScheduler)
                           else GenScheduler())
            if self._ledger is not None:
                self._sched.attach_book(self._ledger.book)
        else:
            self._sched = None
        # the scheduler's decision for the CURRENT loop iteration
        # (None whenever gen_sched is off — hot paths gate on it)
        self._plan = None
        # layer groups (a model whose cache is one entry a layer kind,
        # ``cache_groups``: SmallThinker's full and window layers): a
        # paged engine gives each group a pool and a table of its own.
        # None for every other model, whose programs stay as they were.
        self._groups = self._layer_groups(model)
        self._win: _WindowGroup | None = None
        # a recurrent state group (``cache_groups`` names its kind
        # "state": Kimi-Linear's KDA layers) is slot-indexed, not paged:
        # its host books are the snapshot pool that prefix hits restore
        # from. None for every other model.
        self._snaps: _SnapshotPool | None = None
        if self._paged and self._groups and self._groups[-1][1] == "state":
            if int(state_snapshots) < 1:
                raise ValueError(
                    f"state_snapshots must be >= 1, got {state_snapshots}")
            self._snaps = _SnapshotPool(int(state_snapshots))
        # [admissions, those that restored a snapshot]: stats()
        self._state_admits = [0, 0]
        # which arm the step's KDA layers took, "kernel" or "xla":
        # decided where the step is traced, None until then
        self._kda_step: str | None = None

        if self._paged:
            P = int(flag("gen_page_tokens") if page_tokens is None
                    else page_tokens)
            if P < 1:
                raise ValueError(f"page_tokens must be >= 1, got {P}")
            self._page_tokens = P
            self._maxp = -(-self.max_len // P)       # pages per table
            if pages is None:
                pages = int(flag("gen_pages"))
            # ``pages``: one count, or one a layer group
            # (a state group has no pages: one count)
            n_groups = (1 if self._snaps is not None
                        else len(self._groups or (None,)))
            per_group = (tuple(int(n) for n in pages)
                         if isinstance(pages, (list, tuple))
                         else (int(pages),) * n_groups)
            if len(per_group) != n_groups:
                raise ValueError(
                    f"pages={pages!r}: the model's cache has {n_groups} "
                    "layer group(s); give one count, or one a group")
            npages = per_group[0]
            if npages <= 0:
                # equal HBM to the contiguous layout by default
                npages = self.slots * self._maxp
            self._pool = _PagePool(npages)
            if self._groups is not None and self._snaps is None:
                chunk = (self._prefill_chunk if self._prefill_chunk > 0
                         else self.max_len)
                self._win = _WindowGroup(
                    self._groups[1][1], per_group[1], P, self.slots, chunk,
                    self._maxp)
            self._prefix = (_PrefixCache(P, self._win and self._win.pool,
                                         self._snaps)
                            if (flag("gen_prefix_cache")
                                if prefix_cache is None else prefix_cache)
                            else None)
            # host-side page tables, uploaded per compiled call (0 =
            # null page); rows zero whenever the slot is free
            self._pt = np.zeros((self.slots, self._maxp), np.int32)
            stat_set("gen/pages_free", self._pool.free_count)
        else:
            self._pool = None
            self._prefix = None
            self._pt = None
        # gen_device_pt: device-resident mirror of the host table,
        # updated with dirty-row .at[slot].set writes on admit/retire
        # (the host array stays the scheduler's source of truth).
        # Default path instead caches ONE whole-table upload per
        # schedule change (_sched_pt) so an unchanged table is not
        # re-shipped every iteration — prefill chunks, plain steps and
        # the spec step's second upload all share it.
        self._pt_dev = (self._pt_place() if self._device_pt else None)
        self._sched_pt = None
        # block diffusion (a model that ``generates_by`` it: SDAR): a
        # step forwards a block of B positions a slot, fixes the masked
        # positions it is most confident of and commits whole blocks to
        # the pool (``_build_block_step``); None for every other model,
        # whose programs stay as they were
        self._blockdiff: dict | None = None
        if getattr(model, "generates_by", None) == "block_diffusion":
            self._blockdiff = self._block_setup(model)
        self._state: dict[str, Any] = self._init_state()
        # topology for stats()/health: static for the engine's lifetime
        # (the cache pool never resizes), so computed once here
        import jax
        leaves = jax.tree_util.tree_leaves(self._state["cache"])
        kv_bytes = sum(int(x.nbytes) for x in leaves)
        if self._snaps is not None:
            # the state group is per slot, not per token: its rows (and
            # the snapshot pool's) are device bytes of the cache, and
            # ``bytes_per_slot`` in stats()["groups"]
            state_rows = jax.tree_util.tree_leaves(self._state["cache"][1])
            self._state_bytes_per_slot = sum(
                int(x.nbytes) // self.slots for x in state_rows)
            kv_bytes += sum(int(x.nbytes) for x in
                            jax.tree_util.tree_leaves(self._state["snaps"]))
            leaves = jax.tree_util.tree_leaves(self._state["cache"][0])
        self._device_info = self._layout.describe(kv_bytes)
        # bytes one token position takes in the cache leaves as they are
        # allocated (every layer, every leaf): pool pages x page tokens,
        # or slots x max_len
        self._kv_bytes_per_token = sum(
            x.nbytes / (x.shape[0] * (self._page_tokens if self._paged
                                      else self.max_len))
            for x in leaves)
        # how the paged step's attention reads the pool,
        # "paged_copy_kernel", "paged_kernel" or "gather": decided where
        # the step is traced, None until then
        self._decode_attn: str | None = None
        # the same a layer kind, ``{window: {Hq, Hkv, arm, kernel}}``
        self._attn_layers: dict = {}
        if self._paged:
            self._step = self._build_paged_step()
            self._prefill_fn = self._build_paged_prefill()
            self._spec_step = (self._build_paged_spec_step()
                               if self._spec_k > 0 else None)
        else:
            self._step = self._build_step()
            self._prefill_fn = self._build_prefill()
            self._spec_step = (self._build_spec_step()
                               if self._spec_k > 0 else None)

        # the loop thread's lock and wake-up: admission, stepping and
        # retirement books; a poll waits on its stream's own wake-up
        self._cond = threading.Condition()
        # polls that had to wait, wake-ups that found tokens or an end,
        # and those that found nothing (``stats()["poll"]``), and each
        # stream's count of waiting polls; a lock of their own, which
        # the loop thread never takes
        self._poll_lock = threading.Lock()
        self._poll_counts = {"waits": 0, "wakes": 0, "wakes_empty": 0}
        self._queue: deque[Generation] = deque()
        # gen_async_depth lookahead books: dispatched decode steps whose
        # token readback is deferred — entries are (stepped snapshot,
        # device tokens, epoch at dispatch, chip share, launch number);
        # oldest first
        self._pending: deque[tuple] = deque()
        self._launched = 0      # compiled engine programs enqueued so far
        self._slot_gen: list[Generation | None] = [None] * self.slots
        self._gens: dict[str, Generation] = {}
        self._stopping = False
        self._broken: str | None = None
        # self-healing books: crash fingerprints, quarantine set, reaped
        # tombstones (typed GenerationExpired instead of unknown-id),
        # rebuild/trap counters, watchdog heartbeat + stuck latch, and
        # the state epoch that invalidates an in-flight compiled call's
        # results after the watchdog failed its generations
        self._crash_counts: dict[str, int] = {}
        # co-tenant-ambiguous (fused decode / watchdog) trap books:
        # "suspect" fingerprints need 2 independent hits before
        # quarantine so a neighbor's poison can't evict bystanders
        self._suspect_counts: dict[str, int] = {}
        self._quarantined: dict[str, str] = {}
        self._expired: dict[str, float] = {}
        self._rebuilds = 0
        self._consec_traps = 0
        self._epoch = 0
        self._stuck = False
        # generation currently blocked in _kv_admit_fetch (lock held by
        # no one while the store I/O runs): the watchdog counts it as
        # busy work and fails it resumable when the beat goes stale
        self._admitting: Generation | None = None
        self._last_beat = time.monotonic()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="gen-engine")
        self._thread.start()
        self._watch_stop = threading.Event()
        self._watchdog: threading.Thread | None = None
        if self._watchdog_s > 0:
            self._watchdog = threading.Thread(target=self._watchdog_loop,
                                              daemon=True,
                                              name="gen-watchdog")
            self._watchdog.start()

    def _layer_groups(self, model):
        """The model's cache groups (``cache_groups``: ``(layers, window
        or None)`` each, in ``init_cache``'s order) where it names more
        than one kind of layer, else None. What a pool of two groups
        does not carry is refused here, by name."""
        groups = getattr(model, "cache_groups", None)
        if groups is None:
            return None
        groups = tuple((int(n), w if w in (None, "state") else int(w))
                       for n, w in groups)
        state = any(w == "state" for _, w in groups)
        if not self._paged:
            if state:
                raise ValueError(
                    "a recurrent state group on the contiguous engine is "
                    "not implemented: its bucketed prefill pads the prompt "
                    "and hands the model no true length, and a recurrence "
                    "cannot mask what attention masks; use paged=True")
            return groups            # contiguous: every position, masked
        if (len(groups) != 2 or groups[0][1] is not None
                or groups[1][1] is None):
            raise ValueError(
                f"cache groups {groups!r}: the paged engine holds one full "
                "group followed by one window group or by one state group; "
                "other mixes are not implemented")
        refused = {
            "gen_spec_k (speculation)": self._spec_k > 0,
            "gen_kv_store / gen_role (the KV store's page frames)":
                self._kv is not None or self._role != "both",
            "gen_sched (preemption parks a stream by folding its pages)":
                self._sched is not None,
        }
        why = ("layer groups (a window group that frees pages behind a "
               "stream) is not implemented: a page id no longer covers "
               "every layer")
        if state:
            refused["an int8 cache (cache_dtype)"] = (
                self._cache_dtype is not None
                and np.dtype(self._cache_dtype) == np.int8)
            why = ("a recurrent state group is not implemented: a stream's "
                   "pages no longer hold all of its cache (a rejected "
                   "draft cannot be rolled back out of a state, a page "
                   "frame or a folded prompt carries no snapshot)")
        for what, on in refused.items():
            if on:
                raise ValueError(
                    f"{what} with {why}; serve this model without it")
        return groups

    def _block_setup(self, model) -> dict:
        """A block-diffusion model's books: its block, denoising steps,
        ``[MASK]`` and the transfer schedule as data (``[B + 1, steps]``:
        the positions each step fixes of a block that began with that
        many masked), and the totals ``stats()`` reports. What the block
        step does not carry is refused here, by name (``gen_mesh_tp`` by
        the model's ``shard_for_inference``, earlier)."""
        B = int(model.block_length)
        steps = int(model.denoising_steps)
        refused = {
            "the contiguous engine (paged=False)": not self._paged,
            "gen_spec_k (speculation)": self._spec_k > 0,
            "a window or state layer group (cache_groups)":
                self._groups is not None,
            "gen_kv_store / gen_role (the KV store's page frames)":
                self._kv is not None or self._role != "both",
            "gen_sched (preemption parks a stream by folding its pages)":
                self._sched is not None,
            "an int8 cache (cache_dtype)": (
                self._cache_dtype is not None
                and np.dtype(self._cache_dtype) == np.int8),
        }
        for what, on in refused.items():
            if on:
                raise ValueError(
                    f"{what} with block diffusion is not implemented: a "
                    "step forwards a block of positions a slot and commits "
                    "whole blocks; serve this model without it")
        if self._page_tokens % B or (self._prefill_chunk > 0
                                     and self._prefill_chunk % B):
            raise ValueError(
                f"page_tokens ({self._page_tokens}) and prefill_chunk "
                f"({self._prefill_chunk}) must be whole blocks of {B}: a "
                "block never crosses a page and a chunk ends on a block")
        from paddle_tpu.models.generation import transfer_schedule
        return {"B": B, "steps": steps, "mask": int(model.mask_token_id),
                "sched": np.asarray([transfer_schedule(m, steps)
                                     for m in range(B + 1)], np.int32),
                "slot_steps": 0, "tokens_fixed": 0, "commits": 0}

    def _reserve(self, prompt_len: int, max_new: int) -> int:
        """Positions a request may write: prompt + ``max_new_tokens`` (+
        the speculative scratch); a block-diffusion request's last block
        whole."""
        if self._blockdiff is None:
            return prompt_len + max_new + self._spec_k
        B = self._blockdiff["B"]
        return -(-(prompt_len + max_new) // B) * B

    def _init_state(self) -> dict[str, Any]:
        """Fresh device-side engine state (the batched KV cache/page
        pool plus per-slot token/position/key/sampling arrays). Called
        at construction AND by :meth:`_rebuild` — self-healing replaces
        the whole device state, never patches a possibly-poisoned one."""
        import jax
        import jax.numpy as jnp

        proto = self._model.init_cache(1, self.max_len,
                                       dtype=self._cache_dtype)
        if self._win is not None:
            from paddle_tpu.models.generation import init_paged_cache
            cache = tuple(
                init_paged_cache(g, pool.num_pages, self._page_tokens)
                for g, pool in zip(proto, (self._pool, self._win.pool)))
        elif self._snaps is not None:
            # the latent group paged as ever; the state group's rows a
            # slot, slot-major ([slots, L, 1, ...]: a slot's rows are the
            # model's own layout, and the step's vmap over slots maps
            # axis 0 of both without a copy), and the snapshot pool in
            # the same layout
            from paddle_tpu.models.generation import init_paged_cache
            cache = (init_paged_cache(proto[0], self._pool.num_pages,
                                      self._page_tokens),
                     tuple(jnp.zeros((self.slots,) + r.shape, r.dtype)
                           for r in proto[1].rows))
        elif self._paged:
            from paddle_tpu.models.generation import init_paged_cache
            cache = init_paged_cache(proto, self._pool.num_pages,
                                     self._page_tokens)
        else:
            cache = jax.tree_util.tree_map(
                lambda x: jnp.zeros((self.slots,) + x.shape, x.dtype),
                proto)
        state = {
            "cache": cache,
            "tok": jnp.zeros((self.slots,), jnp.int32),
            "pos": jnp.zeros((self.slots,), jnp.int32),
            "keys": jnp.zeros((self.slots, 2), jnp.uint32),
            "temp": jnp.zeros((self.slots,), jnp.float32),
            "top_k": jnp.zeros((self.slots,), jnp.int32),
            "top_p": jnp.ones((self.slots,), jnp.float32),
        }
        if self._count_names:
            # (hi, lo) words of 30 bits a name: exact past 2**31
            state["counts"] = jnp.zeros((len(self._count_names), 2),
                                        jnp.int32)
        if self._snaps is not None:
            state["snaps"] = tuple(
                jnp.zeros((self._snaps.num + 2,) + r.shape, r.dtype)
                for r in proto[1].rows)
        if self._blockdiff is not None:
            # a slot's block: its ids ([MASK] where not yet fixed), the
            # denoising steps taken, the step each position was fixed at
            # (-1 not yet, -2 the prompt's); ``pos`` is its first position
            B = self._blockdiff["B"]
            state.update(
                blk=jnp.full((self.slots, B), self._blockdiff["mask"],
                             jnp.int32),
                bstep=jnp.zeros((self.slots,), jnp.int32),
                bfix=jnp.full((self.slots, B), -1, jnp.int32))
        # commit to the device layout (identity at gen_mesh_tp=0): KV
        # leaves land sharded on the KV-head axis, scalars replicated,
        # matching the explicit shardings every entry point compiles with
        return self._layout.place_state(state, paged=self._paged)

    # -- compiled pieces ---------------------------------------------------
    def _forward(self, model, ids, cache, index):
        """``forward_with_cache`` and, for a model that names
        ``live_counts``, what its layers recorded on the tape: ``[T,
        names]`` int32, one row a position of the chunk (else None)."""
        if not self._count_names:
            return (*model.forward_with_cache(ids, cache, index=index),
                    None)
        import jax.numpy as jnp

        from paddle_tpu.nn.stateful import collect_counts, tape_call
        (logits, cache), tape = tape_call(model.forward_with_cache, ids,
                                          cache, index=index)
        got = collect_counts(tape)
        return logits, cache, jnp.stack(
            [got[n][0] for n in self._count_names], axis=-1)

    def _counted(self, state, counts, live):
        """``state`` with the counts of the live positions added
        (``counts`` [..., names], ``live`` broadcastable to its
        positions): padding, rejected drafts and idle slots route too
        and are left out."""
        if counts is None:
            return state
        import jax.numpy as jnp

        add = jnp.sum(jnp.where(live, counts, 0).reshape(
            -1, counts.shape[-1]), axis=0)
        lo = state["counts"][:, 1] + add
        return dict(state, counts=jnp.stack(
            [state["counts"][:, 0] + (lo >> 30), lo & ((1 << 30) - 1)],
            axis=1))

    def _advance(self, state, cache, logits, keys, subs, cnt, active):
        """A fused step's end, whatever its cache: the live slots' picks
        (outside the vmap: the sampler's arm is one scalar), their
        positions moved on, their counts booked. Returns ``(state,
        tokens)``."""
        import jax.numpy as jnp

        nxt, _ = _sample(logits, subs, state["temp"], state["top_k"],
                         state["top_p"], active)
        tok = jnp.where(active, nxt, state["tok"])
        pos = state["pos"] + active.astype(jnp.int32)
        state = self._counted(state, cnt, active[:, None, None])
        return dict(state, cache=cache, tok=tok, pos=pos, keys=keys), tok

    def _landed(self, state, logits, cnt, padded, true_len, slot, index,
                key, temp, top_k, top_p, **leaves):
        """A prefill's end, whatever its cache: the first token sampled
        at the last true position of the chunk that started at ``index``
        and the slot's state recorded as if this were the final chunk (a
        later chunk overwrites it). ``leaves``: the state entries the
        program replaced (``cache``, a state group's ``snaps``).
        Returns ``(state, first token)``."""
        import jax
        import jax.numpy as jnp

        key, sub = jax.random.split(key)
        tok0 = _sample_one(logits[0, true_len - 1], sub, temp, top_k, top_p)
        state = self._counted(
            state, cnt, (jnp.arange(padded.shape[0]) < true_len)[:, None])
        return dict(
            state, **leaves,
            tok=state["tok"].at[slot].set(tok0),
            pos=state["pos"].at[slot].set(index + true_len),
            keys=state["keys"].at[slot].set(key),
            temp=state["temp"].at[slot].set(temp),
            top_k=state["top_k"].at[slot].set(jnp.asarray(top_k,
                                                          jnp.int32)),
            top_p=state["top_p"].at[slot].set(top_p),
        ), tok0

    def _build_step(self):
        """ONE fused decode for all slots: vmap the model's single-token
        cached forward over the slot axis with per-slot positions/keys/
        sampling params. Inactive slots compute too (fixed cost, fixed
        shapes) but their token/position state is frozen by the mask and
        their cache garbage is overwritten at the next admit."""
        import jax
        import jax.numpy as jnp

        def one(model, cache, tok, idx, key):
            logits, cache, cnt = self._forward(model, tok[None, None],
                                               cache, idx)
            key, sub = jax.random.split(key)
            return cache, logits[0, -1], key, sub, cnt

        def step(model, state, active):
            cache, logits, keys, subs, cnt = jax.vmap(
                functools.partial(one, model))(
                state["cache"], state["tok"], state["pos"], state["keys"])
            return self._advance(state, cache, logits, keys, subs, cnt,
                                 active)

        return self._layout.jit_entry(step, self._model, self._state,
                                      paged=False, n_in=1, n_out=1)

    def _build_prefill(self):
        """Prefill one slot from a right-padded prompt bucket (compiled
        once per bucket length; ``slot``/``true_len`` are traced). The
        whole slot cache is overwritten, so stale state from the previous
        occupant never leaks into the new generation."""
        import jax
        import jax.numpy as jnp

        S, cache_dtype = self.max_len, self._cache_dtype

        def prefill(model, state, slot, padded, true_len, key, temp, top_k,
                    top_p):
            b1 = model.init_cache(1, S, dtype=cache_dtype)
            logits, b1, cnt = self._forward(model, padded[None], b1, 0)
            cache = jax.tree_util.tree_map(
                lambda big, sm: big.at[slot].set(sm), state["cache"], b1)
            return self._landed(state, logits, cnt, padded, true_len, slot,
                                0, key, temp, top_k, top_p, cache=cache)

        return self._layout.jit_entry(prefill, self._model, self._state,
                                      paged=False, n_in=7, n_out=1)

    @staticmethod
    def _group_caches(pool, row):
        """One slot's cache as a layer-group model reads it: a
        ``PagedCache`` a group, the window group's with its row's base
        (``row[1]`` is ``[base, page ids...]``)."""
        from paddle_tpu.models.generation import PagedCache
        return (PagedCache(pool[0], row[0]),
                PagedCache(pool[1], row[1][1:], row[1][0]))

    def _group_step_writes(self, pool, pt, pos, active, new):
        """A decode step's new position into both groups' pools: the
        full group's page as ever, the window group's counted from its
        row's base (a row that does not hold the page — an idle slot, a
        lookahead step past a stream's end — writes to the null page)."""
        import jax.numpy as jnp

        from paddle_tpu.models.generation import paged_write
        P, slot = self._page_tokens, jnp.arange(self.slots)
        pages = jnp.where(
            active, pt[0][slot, jnp.clip(pos // P, 0, self._maxp - 1)], 0)
        at = pos // P - pt[1][:, 0]
        held = active & (at >= 0) & (at < self._win.row_pages)
        wpages = jnp.where(
            held, pt[1][slot, 1 + jnp.clip(at, 0, self._win.row_pages - 1)],
            0)
        return (paged_write(pool[0], pages, pos % P, new[0]),
                paged_write(pool[1], wpages, pos % P, new[1]))

    def _build_paged_step(self):
        """ONE fused decode for all slots in paged mode: each slot runs
        the same single-token cached forward as the contiguous step on
        a ``PagedCache`` (the pool and its page-table row), so
        attention reads one layer's pages at a time — the paged kernel
        over the slot axis, or a gather (``cached_attention`` or
        ``latent_attention`` picks; the arm this trace took is kept for
        :meth:`stats`) — and no slot's all-layers view exists; the new position's k/v come back as the
        payload and go into the donated pool in place, outside the vmap
        (inactive/masked slots write to the null page)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models._common import (attn_arm_since,
                                               attn_layers_since,
                                               paged_attn_arms,
                                               paged_attn_layers)
        from paddle_tpu.models.generation import PagedCache, paged_write

        P, maxp = self._page_tokens, self._maxp
        slots = self.slots
        grouped = self._win is not None
        if self._snaps is not None:
            return self._build_state_step()
        if self._blockdiff is not None:
            return self._build_block_step()

        def one(model, pt_row, tok, idx, key, pool):
            cache = (self._group_caches(pool, pt_row) if grouped
                     else PagedCache(pool, pt_row))
            logits, new, cnt = self._forward(
                model, tok[None, None], cache, idx)
            key, sub = jax.random.split(key)
            new = jax.tree_util.tree_map(lambda n: n[:, 0, :, 0], new)
            return logits[0, -1], key, sub, new, cnt

        def step(model, state, pt, active):
            pool = state["cache"]
            arms = paged_attn_arms.copy()
            kinds = paged_attn_layers.copy()
            logits, keys, subs, new, cnt = jax.vmap(
                functools.partial(one, model),
                in_axes=(0, 0, 0, 0, None))(
                pt, state["tok"], state["pos"], state["keys"], pool)
            self._decode_attn = attn_arm_since(arms)
            self._attn_layers = attn_layers_since(kinds)
            if grouped:
                pool = self._group_step_writes(pool, pt, state["pos"],
                                               active, new)
            else:
                pidx = jnp.clip(state["pos"] // P, 0, maxp - 1)
                pages = jnp.where(active, pt[jnp.arange(slots), pidx], 0)
                pool = paged_write(pool, pages, state["pos"] % P, new)
            return self._advance(state, pool, logits, keys, subs, cnt,
                                 active)

        return self._layout.jit_entry(step, self._model, self._state,
                                      paged=True, n_in=2, n_out=1)

    def _build_state_step(self):
        """The paged step of a model with a state group: the latent
        group through its ``PagedCache`` as :meth:`_build_paged_step`
        does it, and each slot's state rows mapped beside its table row
        — a slot's rows ARE the model's layout, so the vmap's axis is
        the rows' first and the KDA step (``ops.kda.kda_step``: the
        kernel's own batching rule folds the slot axis) updates the
        donated group in place. An idle or still-prefilling slot steps
        with a length of 0: padding, which is the identity on its rows
        (a prefill between two of its chunks must find them as it left
        them)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models._common import attn_arm_since, paged_attn_arms
        from paddle_tpu.models.generation import (PagedCache, StateCache,
                                                  paged_write)
        from paddle_tpu.ops.kda import step_arms

        P, maxp, slots = self._page_tokens, self._maxp, self.slots

        def one(model, pt_row, tok, idx, key, rows, live, pool):
            cache = (PagedCache(pool, pt_row), StateCache(rows, live))
            logits, (new, st), cnt = self._forward(
                model, tok[None, None], cache, idx)
            key, sub = jax.random.split(key)
            new = jax.tree_util.tree_map(lambda n: n[:, 0, :, 0], new)
            return logits[0, -1], key, sub, new, st.rows, cnt

        def step(model, state, pt, active):
            pool, rows = state["cache"]
            arms = paged_attn_arms.copy()
            kda_kernel = step_arms["kernel"]
            logits, keys, subs, new, rows, cnt = jax.vmap(
                functools.partial(one, model),
                in_axes=(0, 0, 0, 0, 0, 0, None))(
                pt, state["tok"], state["pos"], state["keys"], rows,
                active.astype(jnp.int32), pool)
            self._decode_attn = attn_arm_since(arms)
            self._kda_step = ("kernel" if step_arms["kernel"] > kda_kernel
                              else "xla")
            pidx = jnp.clip(state["pos"] // P, 0, maxp - 1)
            pages = jnp.where(active, pt[jnp.arange(slots), pidx], 0)
            pool = paged_write(pool, pages, state["pos"] % P, new)
            return self._advance(state, (pool, rows), logits, keys, subs,
                                 cnt, active)

        return self._layout.jit_entry(step, self._model, self._state,
                                      paged=True, n_in=2, n_out=1)

    def _build_block_step(self):
        """ONE block step for all slots of a block-diffusion model. The
        operand ``ops`` [slots, B + 3] int32 is, a slot, ``[live, first
        position of a block that starts here or -1, how many of its
        positions the prompt gives, its B ids]``. Each
        live slot forwards its block's B positions at the block's first
        position on its ``PagedCache`` — block-causal: the context ends
        there, the B rows see each other — through
        ``ptpu_paged_block_attn`` or the gather arm (``cached_attention``
        picks; the arm is kept for :meth:`stats`), and writes the B rows'
        K/V into the block's page in place (``block/commit``): a
        denoising step's rows are never read, since a later context
        starts past them only once the commit — the same program on a
        block with nothing masked — has written the final ones. Then the
        pick on the device (``block/pick``, ``generation.block_pick``):
        the schedule's count of the masked positions of highest
        confidence are fixed. A position is masked while no step has
        fixed it (``bfix`` -1; -2 a prompt's), whatever its id. A
        committing slot moves on to a fresh block B later. Returns
        ``(state, out)``, ``out`` [slots, 2B + 3]: the block's ids after
        the step, the denoising step each position was fixed at (-1 not
        yet, -2 the prompt's), whether this step fixed the block's last
        masked position, how many it fixed, and whether it committed."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models._common import attn_arm_since, paged_attn_arms
        from paddle_tpu.models.generation import (PagedCache, block_pick,
                                                  paged_write_block)

        P, maxp, slots = self._page_tokens, self._maxp, self.slots
        bd = self._blockdiff
        B, MASK, steps = bd["B"], bd["mask"], bd["steps"]
        sched = bd["sched"]

        def one(model, pt_row, ids, p0, pool):
            logits, new, cnt = self._forward(model, ids[None],
                                             PagedCache(pool, pt_row), p0)
            new = jax.tree_util.tree_map(lambda n: n[:, 0], new)
            return logits[0], new, cnt

        def step(model, state, pt, ops):
            live = ops[:, 0] > 0
            begin = ops[:, 1] >= 0
            blk = jnp.where(begin[:, None], ops[:, 3:], state["blk"])
            p0 = jnp.where(begin, ops[:, 1], state["pos"])
            at = jnp.where(begin, 0, state["bstep"])
            given = jnp.arange(B)[None, :] < ops[:, 2:3]
            bfix = jnp.where(begin[:, None], jnp.where(given, -2, -1),
                             state["bfix"])
            arms = paged_attn_arms.copy()
            with jax.named_scope("block/attn"):
                logits, new, cnt = jax.vmap(
                    functools.partial(one, model),
                    in_axes=(0, 0, 0, None))(pt, blk, p0, state["cache"])
            self._decode_attn = attn_arm_since(arms)
            with jax.named_scope("block/commit"):
                page = pt[jnp.arange(slots), jnp.clip(p0 // P, 0, maxp - 1)]
                pool = paged_write_block(state["cache"],
                                         jnp.where(live, page, 0), p0 % P,
                                         new)
            with jax.named_scope("block/pick"):
                masked = jnp.any(bfix == -1, axis=1)
                # the schedule of a block that began with every position
                # the prompt did not give masked
                n = jnp.asarray(sched)[jnp.sum(bfix != -2, axis=1),
                                       jnp.clip(at, 0, steps - 1)]
                x0, fix = block_pick(logits, bfix == -1,
                                     jnp.where(live, n, 0), MASK)
                blk = jnp.where(fix, x0, blk)
                bfix = jnp.where(fix, at[:, None], bfix)
                fixing = live & masked
                commit = live & ~masked
                done = fixing & ~jnp.any(bfix == -1, axis=1)
                out = jnp.concatenate(
                    [blk, bfix, done[:, None], jnp.sum(fix, axis=1)[:, None],
                     commit[:, None]], axis=1).astype(jnp.int32)
            state = self._counted(state, cnt, live[:, None, None])
            return dict(
                state, cache=pool,
                blk=jnp.where(commit[:, None], MASK, blk),
                pos=jnp.where(commit, p0 + B, p0),
                bstep=jnp.where(commit, 0, at + fixing),
                bfix=jnp.where(commit[:, None], -1, bfix)), out

        return self._layout.jit_entry(step, self._model, self._state,
                                      paged=True, n_in=2, n_out=1)

    def _build_state_prefill(self):
        """A prefill chunk of a model with a state group:
        :meth:`_build_paged_prefill`'s chunk with the slot's state rows
        beside its pages, and two operands more. ``src``: where the
        rows the chunk starts from lie — ``-1`` the slot's own (a later
        chunk), else a snapshot id (0 = the zero snapshot: no prefix
        hit). ``dst``: the snapshot that keeps the chunk's end state
        (the pool's scratch entry where nobody does). So an admission's
        restore or zeroing, the chunk and the snapshot are ONE program a
        bucket, warmed with it."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.generation import (PagedCache, StateCache,
                                                  paged_scatter)

        P = self._page_tokens

        def prefill(model, state, pt, slot, padded, index, true_len, key,
                    temp, top_k, top_p, src, dst):
            pool, rows = state["cache"]
            snaps = state["snaps"]
            start = tuple(
                jnp.where(src >= 0, s[jnp.maximum(src, 0)], r[slot])
                for r, s in zip(rows, snaps))
            row = pt[slot]
            logits, (chunk, st), cnt = self._forward(
                model, padded[None],
                (PagedCache(pool, row), StateCache(start, true_len)), index)
            pool = paged_scatter(pool, row, chunk, index, P,
                                 length=true_len)
            rows = tuple(r.at[slot].set(n) for r, n in zip(rows, st.rows))
            snaps = tuple(s.at[dst].set(n) for s, n in zip(snaps, st.rows))
            return self._landed(state, logits, cnt, padded, true_len, slot,
                                index, key, temp, top_k, top_p,
                                cache=(pool, rows), snaps=snaps)

        return self._layout.jit_entry(prefill, self._model, self._state,
                                      paged=True, n_in=11, n_out=1)

    def _build_paged_prefill(self):
        """Prefill ONE chunk of one slot's prompt (compiled per padded
        chunk length): forward the chunk at its absolute index on the
        slot's ``PagedCache`` — attention reads the shared-prefix
        context already in the slot's pages, one layer at a time —
        write the chunk's k/v into those pages in place (padding
        redirected to the null page), and record the slot state as if
        this were the final chunk — a later chunk simply overwrites it,
        so the last chunk's sample/key/position land without a traced
        branch."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.generation import PagedCache, paged_scatter

        P = self._page_tokens
        if self._snaps is not None:
            return self._build_state_prefill()

        def prefill(model, state, pt, slot, padded, index, true_len, key,
                    temp, top_k, top_p):
            pool = state["cache"]
            if self._win is not None:
                row = tuple(t[slot] for t in pt)
                logits, chunk, cnt = self._forward(
                    model, padded[None], self._group_caches(pool, row),
                    index)
                # the window row counts its pages from its base
                starts = (index, index - row[1][0] * P)
                pool = tuple(
                    paged_scatter(g, r, c, at, P, length=true_len)
                    for g, r, c, at in zip(pool, (row[0], row[1][1:]),
                                           chunk, starts))
            else:
                row = pt[slot]
                logits, chunk, cnt = self._forward(
                    model, padded[None], PagedCache(pool, row), index)
                pool = paged_scatter(pool, row, chunk, index, P,
                                     length=true_len)
            return self._landed(state, logits, cnt, padded, true_len, slot,
                                index, key, temp, top_k, top_p, cache=pool)

        return self._layout.jit_entry(prefill, self._model, self._state,
                                      paged=True, n_in=9, n_out=1)

    def _spec_keys(self, jax, jnp, key):
        """One slot's key schedule over the K+1 forwarded positions:
        ``keys[i]`` is the slot key after ``i+1`` splits and ``subs[i]``
        the subkey position ``i``'s pick draws from — the exact
        per-emitted-token schedule of plain steps."""
        keys, subs, cur = [], [], key
        for _ in range(self._spec_k + 1):
            cur, sub = jax.random.split(cur)
            keys.append(cur)
            subs.append(sub)
        return jnp.stack(keys), jnp.stack(subs)

    def _spec_pick_accept(self, jax, jnp, logits, keys, subs, state,
                          active, drafts, dlens):
        """Shared verify core of both spec steps, over all slots:
        compute the target's pick at every one of the K+1 forwarded
        positions (``logits [slots, K+1, V]``, keys of
        :meth:`_spec_keys`) in one :func:`_sample` call outside the
        vmap, then accept the longest draft prefix matching those
        picks. Returns ``(out [slots, K+1], emit, new_keys)`` where
        ``out[s, :emit[s]]`` are the emitted tokens (accepted drafts +
        the target's pick at the first mismatch) and ``new_keys[s]`` is
        the slot key advanced by exactly ``emit[s]`` splits, so a slot's
        key schedule is indistinguishable from ``emit`` plain steps."""
        K = self._spec_k

        def rows(a):                      # a slot's value at each position
            return jnp.repeat(a, K + 1, axis=0)

        picks, _ = _sample(
            logits.reshape((-1, logits.shape[-1])),
            subs.reshape((-1,) + subs.shape[2:]), rows(state["temp"]),
            rows(state["top_k"]), rows(state["top_p"]), rows(active))

        def accept(picks, keys, draft, dlen):
            good = (picks[:K] == draft) & (jnp.arange(K) < dlen)
            acc = jnp.sum(jnp.cumprod(good.astype(jnp.int32)))
            j = jnp.arange(K + 1)
            out = jnp.where(j < acc, jnp.concatenate([draft, draft[-1:]]),
                            picks)
            return out, acc + 1, keys[acc]   # acc+1 = emit splits in

        return jax.vmap(accept)(picks.reshape((-1, K + 1)), keys, drafts,
                                dlens)

    def _build_spec_step(self):
        """ONE fused speculative verify for all slots (contiguous mode):
        each slot forwards ``[pending, draft_1..draft_K]`` at its
        position — the multi-token prefill machinery — and accepts the
        longest draft prefix matching the target's per-position picks.
        Mixed speculating/non-speculating slots coexist: draft length 0
        degrades to a plain single-token step for that slot (identical
        pick at position 0; causal masking makes the extra positions
        inert). Rollback is position-pointer arithmetic: rejected-draft
        KV sits at positions >= the new decode index, which attention
        masks and later writes overwrite; admission reserved ``spec_k``
        scratch positions so the fixed K+1 write window never clamps."""
        import jax
        import jax.numpy as jnp

        def one(model, cache, tok, idx, key, draft):
            ids = jnp.concatenate([tok[None], draft])[None]   # [1, K+1]
            logits, cache, cnt = self._forward(model, ids, cache, idx)
            return (cache, logits[0], *self._spec_keys(jax, jnp, key), cnt)

        def step(model, state, active, drafts, dlens):
            cache, logits, keys, subs, cnt = jax.vmap(
                functools.partial(one, model))(
                state["cache"], state["tok"], state["pos"], state["keys"],
                drafts)
            out, emit, keys = self._spec_pick_accept(
                jax, jnp, logits, keys, subs, state, active, drafts, dlens)
            emit = jnp.where(active, emit, 0)
            state = self._counted(
                state, cnt,
                (jnp.arange(self._spec_k + 1)[None] < emit[:, None])[..., None])
            last = jnp.take_along_axis(
                out, jnp.maximum(emit - 1, 0)[:, None], axis=1)[:, 0]
            tok = jnp.where(active, last, state["tok"])
            pos = state["pos"] + emit
            return dict(state, cache=cache, tok=tok, pos=pos,
                        keys=keys), out, emit

        return self._layout.jit_entry(step, self._model, self._state,
                                      paged=False, n_in=3, n_out=2)

    def _build_paged_spec_step(self):
        """Speculative verify in paged mode: forward each slot's
        K+1-token window on its ``PagedCache``, then write ONLY the
        emitted positions through the page table — the rejected tail is
        redirected to the null page (page-refcount-safe truncation:
        rejected drafts never land in a live page, so rollback cannot
        interact with prefix-shared pages or refcounts)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.generation import PagedCache, paged_write

        P, maxp = self._page_tokens, self._maxp
        K = self._spec_k

        def one(model, pt_row, tok, idx, key, draft, pool):
            ids = jnp.concatenate([tok[None], draft])[None]
            logits, chunk, cnt = self._forward(
                model, ids, PagedCache(pool, pt_row), idx)
            # [K+1, L, Hkv, *rest]: one row a position
            return (logits[0], *self._spec_keys(jax, jnp, key), tuple(
                jnp.moveaxis(c[:, 0], 2, 0) for c in chunk), cnt)

        def step(model, state, pt, active, drafts, dlens):
            pool = state["cache"]
            logits, keys, subs, chunks, cnt = jax.vmap(
                functools.partial(one, model),
                in_axes=(0,) * 5 + (None,))(
                pt, state["tok"], state["pos"], state["keys"], drafts, pool)
            out, emit, keys = self._spec_pick_accept(
                jax, jnp, logits, keys, subs, state, active, drafts, dlens)
            emit = jnp.where(active, emit, 0)
            j = jnp.arange(K + 1)
            state = self._counted(
                state, cnt, (j[None, :] < emit[:, None])[..., None])
            pos = state["pos"][:, None] + j[None, :]      # [slots, K+1]
            pidx = jnp.clip(pos // P, 0, maxp - 1)
            pages = jnp.take_along_axis(pt, pidx, axis=1)
            # truncation: positions past the accept point (and every
            # position of inactive slots, emit 0) go to the null page
            pages = jnp.where(j[None, :] < emit[:, None], pages, 0)
            pool = paged_write(
                pool, pages.reshape(-1), (pos % P).reshape(-1),
                tuple(c.reshape((-1,) + c.shape[2:]) for c in chunks))
            last = jnp.take_along_axis(
                out, jnp.maximum(emit - 1, 0)[:, None], axis=1)[:, 0]
            tok = jnp.where(active, last, state["tok"])
            pos1 = state["pos"] + emit
            return dict(state, cache=pool, tok=tok, pos=pos1,
                        keys=keys), out, emit

        return self._layout.jit_entry(step, self._model, self._state,
                                      paged=True, n_in=4, n_out=2)

    # -- drafters (host side) ----------------------------------------------
    def _propose(self, ctx: np.ndarray, cap: int) -> np.ndarray:
        """Draft up to ``cap`` tokens for one slot from its own context
        (prompt + emitted tokens so far). May return fewer (or none —
        the slot then takes a plain step this iteration)."""
        if self._spec_mode == "draft":
            return self._draft_propose(ctx, cap)
        from paddle_tpu.models.generation import ngram_propose
        return ngram_propose(ctx, cap, max_ngram=self._spec_ngram)

    def _draft_propose(self, ctx: np.ndarray, cap: int) -> np.ndarray:
        import jax.numpy as jnp

        T = int(ctx.size)
        bucket = self._bucket(T)
        fn = self._draft_fns.get(bucket)
        if fn is None:
            fn = self._draft_fns[bucket] = self._build_draft_fn(bucket)
        padded = np.full((bucket,), self._pad, np.int32)
        padded[:T] = ctx
        with self._phase("gen/draft", entry=("draft", bucket)) as call:
            ops = jnp.asarray(padded), jnp.asarray(T, jnp.int32)
            with self._launch("draft"):
                out = fn(*ops)
            del ops
            out = np.asarray(out)
            call.set(landed=self._launched)
        return out[:cap]

    def _build_draft_fn(self, bucket: int):
        """Compiled greedy K-token lookahead of the draft model over a
        right-padded context bucket (one compile per pow-2 bucket, the
        prefill discipline): prefill the context, then argmax-decode K
        tokens against the draft's own scratch cache. The decode tail is
        a ``lax.fori_loop`` — one traced body regardless of K, so draft
        compile time (the ``gen/compile_s`` histogram) no longer grows
        with ``spec_k`` the way the former K−1-times-unrolled graph did.
        The draft cache is call-local — the draft never holds persistent
        per-slot state, so engine rebuilds and slot churn cannot
        desynchronize it."""
        import jax
        import jax.numpy as jnp

        K, dtype = self._spec_k, self._cache_dtype

        def fn(draft, padded, true_len):
            cache = draft.init_cache(1, bucket + K, dtype=dtype)
            logits, cache = draft.forward_with_cache(padded[None], cache,
                                                     index=0)
            tok0 = jnp.argmax(logits[0, true_len - 1]).astype(jnp.int32)
            idx = jnp.asarray(true_len, jnp.int32)

            def body(i, carry):
                out, cache = carry
                logits, cache = draft.forward_with_cache(
                    out[i - 1][None, None], cache, index=idx + i - 1)
                nxt = jnp.argmax(logits[0, -1]).astype(jnp.int32)
                return out.at[i].set(nxt), cache

            out0 = jnp.zeros((K,), jnp.int32).at[0].set(tok0)
            out, _ = jax.lax.fori_loop(1, K, body, (out0, cache))
            return out

        return self._layout.jit_aux(fn, self._draft_model, n_in=2)

    def _bucket(self, n: int) -> int:
        b = self._min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def lowered(self, prompt_len: int) -> dict[str, Any]:
        """The two compiled entry points a request of ``prompt_len``
        tokens runs — ``{"prefill": ..., "decode": ...}`` (the prefill
        at that length's bucket, the fused decode step) — as
        ``jax.stages.Lowered``, from the live state's shapes and
        shardings. Nothing executes and nothing is donated; call it on
        an idle engine (the loop thread owns the state). ``.compile()``
        gives the optimized program: its ``memory_analysis()`` and the
        buffers it holds are how the tests see that a paged step keeps
        no copy of the pool."""
        import jax
        import jax.numpy as jnp

        i32 = jnp.zeros((), jnp.int32)
        f32 = jnp.zeros((), jnp.float32)
        sampling = (jax.random.PRNGKey(0), f32, i32, f32)
        padded = jnp.zeros((self._bucket(int(prompt_len)),), jnp.int32)
        active = jnp.zeros((self.slots,), bool)
        if self._paged:
            pt = self._pt_upload(jnp)
            prefill = (self._state, pt, i32, padded, i32, i32, *sampling)
            if self._snaps is not None:
                prefill += (i32, i32)        # source and kept snapshot
            decode = (self._state, pt, active)
            if self._blockdiff is not None:
                decode = (self._state, pt, jnp.zeros(
                    (self.slots, self._blockdiff["B"] + 3), jnp.int32))
        else:
            prefill = (self._state, i32, padded, i32, *sampling)
            decode = (self._state, active)
        return {"prefill": self._prefill_fn.lower(*prefill),
                "decode": self._step.lower(*decode)}

    def lowered_text(self, prompt_len: int) -> dict[str, str]:
        """StableHLO text of :meth:`lowered`'s two programs. Pallas
        kernels appear in the text under their ``name=``, which is how
        ``chip_smoke.py`` checks what the engine really dispatches; the
        named scopes (``kv/gather``, ``kv/write``, ``sample``, the
        model's own) ride in the operations' locations."""
        return {name: low.as_text(debug_info=True)
                for name, low in self.lowered(prompt_len).items()}

    # -- stream-lifecycle tracing + compile observability -------------------
    def _phase(self, name: str, goodput: str | None = None,
               hist: str | None = None, entry: tuple | None = None,
               gen: Generation | None = None, clock: bool = False,
               **attrs) -> _Phase | _NoopPhase:
        """A timed section of the loop (:class:`_Phase`): span ``name``
        while one records, histogram ``hist``, bucket ``goodput``.
        ``gen`` links the span under the generation's stream trace id
        when it carries one (the cross-replica stream timeline obs_dump
        merges), its parent still the loop's open span. ``clock``: the
        caller reads the section's ``t0`` / ``t1`` itself. A section
        with nothing to feed is the shared no-op."""
        if not _trace.recording():
            if (not clock and hist is None and entry is None
                    and (goodput is None or self._goodput is None)):
                return _NOOP_PHASE
            span = _trace._NOOP
        elif gen is not None and gen.trace_id is not None:
            cur = _trace.current()
            span = _trace.server_span(name, gen.trace_id,
                                      cur[1] if cur else None,
                                      gen=gen.gen_id, **attrs)
        else:
            span = _trace.span(name, **attrs)
        return _Phase(self, span, goodput, hist, entry)

    def _launch(self, entry: str) -> _Phase | _NoopPhase:
        """The section round exactly one call into a compiled engine
        program (``gen/launch``: ``seq``, ``entry``), numbered as it is
        enqueued. The device runs one stream in order, so the readback
        that names this ``seq`` as ``landed`` proves every earlier
        launch finished too; what the enclosing span holds outside this
        one is operand staging. The request's key program and operand
        transfers are staging, not launches. Loop thread only."""
        self._launched += 1
        ph = self._phase("gen/launch", seq=self._launched)
        if ph is not _NOOP_PHASE:
            ph.set(entry=entry)
        return ph

    def _gen_event(self, gen: Generation, name: str, **attrs) -> None:
        """Zero-duration stream-lifecycle event (admitted / retire /
        spec accept) recorded under the stream trace id. No-op unless
        spans record AND the generation carries a stream id."""
        if gen.trace_id is None or not _trace.recording():
            return
        with _trace.server_span(name, gen.trace_id, None,
                                gen=gen.gen_id, **attrs):
            pass

    def _note_compile(self, entry: str, sig, dt: float,
                      compiled: bool) -> None:
        """Bookkeep one call into a compiled entry point. The book of
        (entry, shape-signature) pairs is what :meth:`stats` counts as
        ``compiles``. ``compiled`` — jax built a program during THIS
        call, by its own events — puts ``dt``, the call's wall clock,
        into the ``gen/compile_s`` histogram; on an entry point that had
        a program already it is a recompile, and their recent-window
        count is the recompile-storm gauge in :meth:`stats`."""
        key = (entry, sig)
        if not compiled and key in self._compiled_seen:
            return
        with self._cond:
            again = any(k[0] == entry for k in self._compiled_seen)
            self._compiled_seen.add(key)
            if compiled and again:
                self._recompiles += 1
                self._recompile_ts.append(time.monotonic())
        if compiled:
            observe("gen/compile_s", dt)
            stat_add("gen/compiles")
            if again:
                stat_add("gen/recompiles")

    def _ledger_finalize(self, gen: Generation, outcome: str) -> None:
        """Finalize the generation's ledger record exactly once (caller
        holds the lock; every retire path calls this). The gated
        ``gen/ledger`` event makes the finalize visible in the stream
        trace, so obs_dump joins phase records to the same stream id a
        failover resume carries across replicas."""
        if self._ledger is None or gen.ledgered:
            return
        gen.ledgered = True
        rec = self._ledger.finalize(gen, outcome)
        self._gen_event(gen, "gen/ledger", outcome=outcome,
                        e2e_s=round(rec["e2e_s"], 6),
                        resumed=int(gen.rng_skip > 0))

    @property
    def sched(self):
        """The engine's :class:`~paddle_tpu.serving.scheduler.
        GenScheduler`, or None with ``FLAGS_gen_sched`` off — how the
        serving layer routes FrameService/batcher shed decisions
        through the same policy object."""
        return self._sched

    # -- public surface ----------------------------------------------------
    def start(self, prompt, max_new_tokens: int, *, temperature: float = 0.0,
              top_k: int = 0, top_p: float = 1.0, eos_token_id=_UNSET,
              seed: int = 0, rng_skip: int = 0,
              trace_id: str | None = None,
              tenant: str | None = None,
              fingerprint: str | None = None,
              priority: str | None = None) -> str:
        """Enqueue a generation; returns its id immediately. Raises
        :class:`EngineOverloaded` (retryable) when every slot is busy and
        the admit queue is at ``queue_max``, and the typed
        :class:`RequestQuarantined` when the request's crash fingerprint
        is quarantined. ``rng_skip`` advances the per-(prompt, seed)
        sampling-key schedule by that many splits before the first
        token — how a resumed sampled stream replays its RNG position
        (see ``models.generation.advance_key``); greedy requests ignore
        it. ``trace_id`` is the caller's stream trace id (wire header
        ``st``): when tracing is on, the engine records this
        generation's slot-lifecycle events under it. ``tenant`` (wire
        header ``tn``) is the caller's attribution identity — the
        ledger books this generation's tokens/chip-seconds/queue-wait
        under it when ``FLAGS_gen_ledger`` is on. ``fingerprint``
        (wire header ``fp``) overrides the crash fingerprint computed
        from the request itself: a resumed stream's replay prompt grew
        by the delivered tokens, so the resuming client passes the
        ORIGINAL stream's fingerprint — quarantine then recognizes
        resumed poison instead of admitting it under a fresh hash.
        ``priority`` (wire header ``pc``) is the request's scheduling
        class (interactive / batch / best_effort) — consulted only when
        ``FLAGS_gen_sched`` built a scheduler; ignored (recorded but
        inert) otherwise."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        rng_skip = int(rng_skip)
        if rng_skip < 0:
            raise ValueError("rng_skip must be >= 0")
        if self._blockdiff is not None and float(temperature) > 0.0:
            raise ValueError(
                "sampled requests (temperature > 0) with block diffusion "
                "are not implemented: the block step picks greedily; send "
                "temperature=0")
        # with speculation on, a slot's verify step writes a fixed
        # K+1-token window at the decode position — the last emitted
        # token can sit at prompt+max_new-1, so spec_k scratch positions
        # past the declared worst case keep that write in bounds
        # (dynamic_update_slice clamps its start; an out-of-bounds
        # window would silently shift live positions); a block-diffusion
        # request writes its last block whole
        reserve = self._reserve(prompt.size, max_new_tokens)
        if reserve > self.max_len:
            spec = (f" + spec_k ({self._spec_k}) scratch"
                    if self._spec_k else "")
            fix = (" or lower FLAGS_gen_spec_k" if self._spec_k else "")
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}){spec} exceeds the engine's per-slot "
                f"capacity ({self.max_len}); raise FLAGS_gen_max_len"
                + fix)
        if self._paged:
            need = -(-reserve // self._page_tokens)
            if need > self._pool.num_pages:
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self._pool.num_pages}; raise FLAGS_gen_pages")
            if (self._win is not None and self._win.budget(need, 0)
                    > self._win.pool.num_pages):
                raise ValueError(
                    f"request needs {self._win.budget(need, 0)} window-"
                    "group pages at once but that pool only has "
                    f"{self._win.pool.num_pages}; raise FLAGS_gen_pages")
        eos = self._eos_default if eos_token_id is _UNSET else eos_token_id
        gen = Generation(uuid.uuid4().hex[:16], prompt, max_new_tokens,
                         float(temperature), int(top_k), float(top_p),
                         None if eos is None else int(eos), int(seed))
        gen.rng_skip = rng_skip
        if fingerprint:
            gen.fingerprint = str(fingerprint)
        if trace_id:
            gen.trace_id = str(trace_id)
        if tenant:
            gen.tenant = str(tenant)
        if self._sched is not None:
            gen.pclass = self._sched.classify(priority)
        with self._cond:
            if self._stopping:
                raise RuntimeError("GenerationEngine is stopped")
            if self._broken is not None:
                raise RuntimeError(
                    f"GenerationEngine is broken: {self._broken}")
            if (self._quarantine_after > 0
                    and gen.fingerprint in self._quarantined):
                stat_add("gen/quarantine_rejected")
                raise RequestQuarantined(
                    f"{QUARANTINE_MARKER} request {gen.fingerprint} "
                    f"trapped the engine "
                    f"{self._crash_counts.get(gen.fingerprint, 0)} "
                    f"time(s) (last: "
                    f"{self._quarantined[gen.fingerprint]}); refusing "
                    "to re-admit it", fingerprint=gen.fingerprint)
            if self._stuck:
                # the decode loop is wedged in a device call; shed
                # retryably so the routed layer sends work elsewhere
                stat_add("gen/shed")
                raise EngineOverloaded(
                    "engine stuck: decode loop unresponsive "
                    f"(gen_watchdog_s={self._watchdog_s:g}); retry "
                    "elsewhere", retry_after_s=_jittered(0.5))
            free = sum(g is None for g in self._slot_gen)
            pending = len(self._queue) - free
            shed = (self._sched.shed_start(gen.pclass, pending,
                                           self._queue_max)
                    if self._sched is not None
                    else (self._queue_max > 0
                          and pending >= self._queue_max))
            if shed:
                stat_add("gen/shed")
                pool = ("" if not self._paged else
                        f", {self._pool.free_count}/"
                        f"{self._pool.num_pages} pages free")
                raise EngineOverloaded(
                    f"engine full: {self.slots} slots busy, "
                    f"{len(self._queue)} queued (queue_max="
                    f"{self._queue_max}){pool}",
                    retry_after_s=_jittered(0.25))
            if self._sched is not None:
                self._sched.on_enqueue(gen)
            self._queue.append(gen)
            self._gens[gen.gen_id] = gen
            stat_set("gen/queue_depth", len(self._queue))
            self._cond.notify_all()
        return gen.gen_id

    def poll(self, gen_id: str, start: int = 0,
             wait_s: float = 0.0) -> dict:
        """Drain tokens past ``start``; blocks up to ``wait_s`` for new
        ones (long-poll). Returns ``{"tokens", "done", "error",
        "queued"}``, and a block-diffusion stream's final poll
        ``"blocks"`` too: ``[[first position, ids, the denoising step
        each was fixed at (-2 the prompt's)], ...]``, the last block
        whole even where the stream ended inside it. Polling refreshes the generation's TTL — a client
        that stops polling for ``ttl_s`` is treated as disconnected and
        its slot reclaimed.

        A poll takes the engine's lock only for a stream that has ended
        (its final poll) or an id it does not know; one that waits,
        waits on its stream's own wake-up. Without the lock it reads
        what the loop may be writing, which is sound because a stream's
        tokens are only ever appended, ``done`` is set after the last of
        them and before the slot is let go, and ``error`` is read under
        the lock."""
        start = max(int(start), 0)
        gen = self._gens.get(gen_id)
        if gen is None:
            with self._cond:         # a reap's pop and tombstone, whole
                if gen_id in self._expired:
                    # reaped by the TTL (possibly while this poll was
                    # in flight): typed, so the caller can tell "your
                    # stream expired HERE" from "never started here"
                    stat_add("gen/expired_polls")
                    raise GenerationExpired(
                        f"{EXPIRED_MARKER} generation {gen_id} was "
                        "reaped by the poll TTL (client presumed "
                        "disconnected); restart it")
            raise KeyError(f"unknown generation {gen_id!r} "
                           "(finished long ago, evicted, or never "
                           "started here)")
        gen.last_poll = now = time.monotonic()
        if not self._polled(gen, start) and float(wait_s) > 0:
            self._poll_wait(gen, start, now + float(wait_s))
        slot = gen.slot
        if gen.done:
            with self._cond:
                # this response tells the caller the generation finished
                # and hands over every token past ``start`` — fully
                # delivered (the condition a sticky drain waits on
                # before a replica may stop)
                gen.delivered = True
                self._ledger_finalize(
                    gen, "complete" if gen.error is None else "failed")
                doc = {"tokens": gen.tokens[start:], "done": True,
                       "error": gen.error, "queued": False}
                if self._blockdiff is not None:
                    # the stream's record: each block as it ended, and
                    # the denoising step each position was fixed at
                    doc["blocks"] = [[int(p0), ids.tolist(), at.tolist()]
                                     for p0, ids, at in gen.blocks]
                return doc
        return {"tokens": gen.tokens[start:], "done": False, "error": None,
                "queued": slot is None}

    def _polled(self, gen: Generation, start: int) -> bool:
        """A poll from ``start`` has something to return: a token past
        it, the stream's end, or the engine's."""
        return gen.done or len(gen.tokens) > start or self._stopping

    def _poll_wait(self, gen: Generation, start: int,
                   deadline: float) -> None:
        """Wait on the stream's own wake-up until :meth:`_polled` or
        ``deadline``. Clear, then re-check, then wait: a wake-up put in
        after the clear is kept, so none falls between the check and
        the wait, and one left over from an earlier poll is cleared."""
        wake = gen.wake
        wakes = empty = 0
        woke = False
        with self._poll_lock:
            gen.waiting += 1
        try:
            while True:
                try:
                    while True:
                        wake.get_nowait()
                except queue.Empty:
                    pass
                if self._polled(gen, start):
                    wakes += woke
                    break
                empty += woke
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    woke = wake.get(timeout=remaining)
                except queue.Empty:
                    woke = False
                gen.last_poll = time.monotonic()
        finally:
            with self._poll_lock:
                gen.waiting -= 1
                others = gen.waiting
                counts = self._poll_counts
                counts["waits"] += 1
                counts["wakes"] += wakes
                counts["wakes_empty"] += empty
            if others:              # another poll of this stream waits:
                wake.put(True)      # hand the wake-up on
        stat_add("gen/poll_waits")
        if wakes:
            stat_add("gen/poll_wakes", wakes)
        if empty:
            stat_add("gen/poll_wakes_empty", empty)

    @staticmethod
    def _wake(gens) -> int:
        """Hand each stream of ``gens`` its wake-up: a poll waiting on
        it returns with what it was given. Called once ``_cond`` is let
        go where the site allows, so that a woken poll never finds it
        held; takes no lock a poll may hold. Returns how many of the
        streams had a poll waiting."""
        woken = 0
        for gen in gens:
            if gen.wake.empty():    # else one is in already
                gen.wake.put(True)
            woken += gen.waiting > 0
        return woken

    def cancel(self, gen_id: str) -> bool:
        """Cancel a generation and free its slot (idempotent; unknown
        ids return False). A freed slot is eligible for the very next
        admit."""
        with self._cond:
            gen = self._gens.pop(gen_id, None)
            if gen is None:
                return False
            gen.cancelled = True
            if not gen.done:
                gen.done = True
                gen.error = gen.error or "cancelled"
                self._release_slot_locked(gen, evicted=True)
                try:
                    self._queue.remove(gen)
                except ValueError:
                    pass
                stat_set("gen/queue_depth", len(self._queue))
                self._gen_event(gen, "gen/retire", reason="cancelled",
                                tokens=len(gen.tokens))
            # covers the done-but-undelivered case too: a cancel is the
            # last event this engine will ever see for the generation
            self._ledger_finalize(gen, "cancelled")
            self._cond.notify_all()        # the loop: a slot is free
        self._wake((gen,))
        return True

    def stats(self) -> dict:
        """Slot + page-pool occupancy snapshot (shipped in the serving
        ``health`` op — routers/probes see generation capacity AND, in
        paged mode, how much of the page pool and prefix cache is
        live)."""
        counts = self._live_counts()
        with self._cond:
            active = sum(g is not None for g in self._slot_gen)
            doc = {"slots": self.slots, "active": active,
                   "free": self.slots - active,
                   "queued": len(self._queue),
                   "generations": len(self._gens),
                   # running, queued, or finished-but-not-yet-polled-to-
                   # done: the work a sticky drain must wait out (done
                   # generations whose final poll already went out do
                   # NOT count — the client has everything)
                   "undelivered": sum(
                       1 for g in self._gens.values()
                       if not (g.done and g.delivered)),
                   "max_len": self.max_len,
                   "broken": self._broken,
                   "stuck": self._stuck,
                   "rebuilds": self._rebuilds,
                   "quarantined": len(self._quarantined),
                   # emitted tokens per fused decode iteration: >1.0
                   # means speculation is landing (batching wins show up
                   # in aggregate tokens/s, not here — this isolates the
                   # per-stream speedup the controller cares about)
                   "tokens_per_step": (
                       self._emit_total / self._decode_iters
                       if self._decode_iters else 0.0),
                   "sample_sorted_steps": self._sample_sorted_steps,
                   # XLA compile observability: total distinct compiled
                   # (entry, shape) signatures, how many were re-compiles
                   # of an already-compiled entry point, and the storm
                   # gauge (recompiles in the last 60s — sustained churn
                   # here means traffic shapes defeat the bucketing)
                   "compiles": len(self._compiled_seen),
                   "recompiles": self._recompiles,
                   "recompile_storm": sum(
                       1 for t in self._recompile_ts
                       if time.monotonic() - t < 60.0),
                   # device topology (static per engine): platform,
                   # device count, mesh axis sizes (None mesh =
                   # unsharded), total + per-device KV bytes — the
                   # placement inputs a control plane reads next to
                   # occupancy. A mesh-backed engine is ONE replica;
                   # this block is how its N devices stay visible.
                   "device": dict(self._device_info),
                   "paged": self._paged,
                   # decode hot-loop knobs (gen_device_pt /
                   # gen_async_depth) + current lookahead occupancy, so
                   # bench/chaos harnesses can assert which loop ran
                   "device_pt": self._device_pt,
                   "async_depth": self._async_depth,
                   "pending_steps": len(self._pending),
                   "kv_bytes_per_token": self._kv_bytes_per_token}
            with self._poll_lock:
                doc["poll"] = dict(self._poll_counts)
            if self._paged:
                doc["decode_attn"] = self._decode_attn
            if self._snaps is not None:
                doc["kda_step"] = self._kda_step
            if self._blockdiff is not None:
                bd = self._blockdiff
                doc["block_diffusion"] = {
                    "block_length": bd["B"],
                    "denoising_steps": bd["steps"],
                    "slot_steps": bd["slot_steps"],
                    "tokens_fixed": bd["tokens_fixed"],
                    "commits": bd["commits"],
                    "attn": self._decode_attn}
            # the model's live counts (absent for a model that names
            # none): monotone, summed on the device over live positions
            doc.update(counts)
            if self._spec_k > 0:
                prop = self._spec_proposed
                doc["spec"] = {
                    "k": self._spec_k,
                    "mode": self._spec_mode,
                    "proposed": prop,
                    "accepted": self._spec_accepted,
                    "rejected": prop - self._spec_accepted,
                    "accept_rate": (self._spec_accepted / prop
                                    if prop else 0.0),
                    "verify_steps": self._spec_verify_steps,
                    "shed_occupancy": self._spec_shed,
                }
            if self._paged:
                doc.update(
                    page_tokens=self._page_tokens,
                    pages=self._pool.num_pages,
                    pages_free=self._pool.free_count,
                    prefix_entries=(0 if self._prefix is None
                                    else len(self._prefix)))
            if self._win is not None:
                # a pool a layer group: ``pages`` / ``pages_free`` are
                # the totals, a prefix entry holds a page of each group
                win = self._win
                live = [g for g in self._slot_gen if g is not None]
                doc["groups"] = [
                    {"name": "full", "layers": self._groups[0][0],
                     "pages": doc["pages"], "pages_free": doc["pages_free"],
                     "stream_pages_max": max(
                         (len(g.pages) for g in live), default=0)},
                    {"name": "window", "layers": self._groups[1][0],
                     "window": win.window, "row_pages": win.row_pages,
                     "pages": win.pool.num_pages,
                     "pages_free": win.pool.free_count,
                     "stream_pages_max": max(
                         (len(g.win.pages) for g in live
                          if g.win is not None), default=0),
                     "stream_pages_peak": win.peak,
                     "pages_slid": win.slid}]
                doc["pages"] += win.pool.num_pages
                doc["pages_free"] += win.pool.free_count
                # how the step attends a group: query and KV heads of its
                # layers, the arm and the kernel's trace name (the name
                # alone before the step is traced)
                doc["attention"] = [
                    dict({"name": name},
                         **self._attn_layers.get(window, {}))
                    for name, window in (("full", None),
                                         ("window", win.window))]
            if self._snaps is not None:
                # the paged group as a layer-group engine names it, and
                # the slot-indexed state group with its snapshot pool
                doc["groups"] = [
                    {"name": "full", "layers": self._groups[0][0],
                     "pages": doc["pages"], "pages_free": doc["pages_free"],
                     "stream_pages_max": max(
                         (len(g.pages) for g in self._slot_gen
                          if g is not None), default=0)},
                    {"name": "state", "layers": self._groups[1][0],
                     "bytes_per_slot": self._state_bytes_per_slot,
                     "snapshots": self._snaps.num,
                     "snapshots_free": self._snaps.free_count,
                     "admissions": self._state_admits[0],
                     "restores": self._state_admits[1]}]
            # performance attribution (FLAGS_gen_ledger only): the loop
            # goodput taxonomy and per-tenant books ride health's
            # generators block, so MetricsHub rolls them up fleet-wide
            # with no extra wire surface
            if self._goodput is not None:
                doc["goodput"] = self._goodput.snapshot()
            if self._ledger is not None:
                doc["tenants"] = self._ledger.tenants()
            # scheduler books (FLAGS_gen_sched only): preemption/shed/
            # quota counters + class weights. Absent with the scheduler
            # off so the default health doc is byte-identical.
            if self._sched is not None:
                doc["sched"] = self._sched.snapshot()
            # disaggregated serving (FLAGS_gen_kv_store only): store
            # tiers + this engine's produce/consume counters. Absent
            # with the store off so the default health doc is
            # byte-identical to the pre-store build.
            if self._kv is not None:
                doc["kv"] = dict(self._kv.snapshot(),
                                 role=self._role,
                                 published=self._kv_published,
                                 fetched_pages=self._kv_fetched_pages,
                                 fetched_bytes=self._kv_fetched_bytes,
                                 demoted=self._kv_demoted,
                                 prefill_recomputed=self._kv_recomputed,
                                 fetch_degraded=self._kv_degraded)
            return doc

    def _live_counts(self) -> dict:
        """The model's ``live_counts`` as of the loop's next iteration
        boundary: the device words live in the donated state, which only
        the loop thread may read, so a reader asks and waits for the
        loop to fetch them (one small readback a ``stats()`` call, none
        a step). With the loop gone, the last fetch stands."""
        if not self._count_names:
            return {}
        asked = None
        with self._cond:
            if (self._thread.is_alive() and not self._stopping
                    and threading.current_thread() is not self._thread):
                asked = self._counts_req = (self._counts_req
                                            or threading.Event())
                self._cond.notify_all()
        if asked is not None:
            asked.wait(timeout=2.0)
        return dict(self._counts_host)

    def _fetch_counts_locked(self) -> None:
        """Loop thread, between compiled calls: read the count words."""
        words = np.asarray(self._state["counts"]).astype(np.int64)
        for name, (hi, lo) in zip(self._count_names, words):
            total = self._counts_base[name] + (int(hi) << 30) + int(lo)
            self._counts_host[name] = total
            stat_set(f"gen/{name}", total)
        if self._counts_req is not None:
            self._counts_req.set()
            self._counts_req = None

    def ledger_dump(self, limit: int | None = None) -> dict | None:
        """Finalized per-request phase records + tenant book + goodput
        snapshot (the ``ledger_dump`` wire op's per-engine payload), or
        None while ``FLAGS_gen_ledger`` is off."""
        if self._ledger is None:
            return None
        doc = {"records": self._ledger.records(limit),
               "tenants": self._ledger.tenants()}
        if self._goodput is not None:
            doc["goodput"] = self._goodput.snapshot()
        return doc

    def clear_prefix_cache(self) -> int:
        """Drop every prefix-cache entry no live generation references
        (an operational memory-pressure valve; also how the tests assert
        the pool drains back to full). Returns pages freed."""
        with self._cond:
            if self._prefix is None:
                return 0
            freed = self._prefix.evict(self._pool.num_pages, self._pool,
                                       demote=(self._kv_demote
                                               if self._kv is not None
                                               else None))
            stat_set("gen/pages_free", self._pages_free())
            return freed

    def canary(self, timeout_s: float = 5.0, prompt_token: int = 1) -> dict:
        """One-token liveness decode through the real admit → prefill →
        sample path: *engine* liveness as distinct from *wire* liveness
        ("device healthy" vs "port open") — what the serving ``health``
        op ships per generator under ``deep=True``. A full engine counts
        as alive (``busy=True``: it is making progress for someone);
        broken/stuck/timed-out engines report ``ok=False`` with the
        error. Returns ``{"ok", "busy", "latency_s", "error"}``."""
        t0 = time.monotonic()
        try:
            gid = self.start(np.asarray([int(prompt_token)], np.int32), 1)
        except EngineOverloaded:
            return {"ok": True, "busy": True,
                    "latency_s": time.monotonic() - t0, "error": None}
        except RuntimeError as e:        # broken / quarantined canary
            return {"ok": False, "busy": False,
                    "latency_s": time.monotonic() - t0,
                    "error": f"{type(e).__name__}: {e}"}
        deadline = time.monotonic() + max(float(timeout_s), 0.0)
        ok, err = False, f"canary timed out after {timeout_s:g}s"
        try:
            while time.monotonic() < deadline:
                doc = self.poll(gid, wait_s=min(0.25, float(timeout_s)))
                if doc["done"]:
                    ok = doc["error"] is None
                    err = doc["error"]
                    break
        except (KeyError, RuntimeError) as e:
            err = f"{type(e).__name__}: {e}"
        finally:
            self.cancel(gid)
        return {"ok": ok, "busy": False,
                "latency_s": time.monotonic() - t0, "error": err}

    def close(self) -> None:
        """Stop the loop; error out queued/active generations."""
        with self._cond:
            if self._stopping:
                return
            self._stopping = True
            self._cond.notify_all()
        self._watch_stop.set()
        if self._watchdog is not None:
            self._watchdog.join(timeout=2.0)
        self._thread.join(timeout=10.0)
        with self._cond:
            gens = list(self._gens.values())
            for gen in gens:
                if not gen.done:
                    gen.done = True
                    gen.error = gen.error or "engine stopped"
                    gen.slot = None
                    self._gen_event(gen, "gen/retire", reason="stopped",
                                    tokens=len(gen.tokens))
                self._ledger_finalize(gen, "stopped")
                gen.pages = []
            self._slot_gen = [None] * self.slots
            self._queue.clear()
            self._pending.clear()
            if self._paged:
                self._pt_clear_locked()
        # a poll that waited through the loop's last iteration returns
        # with the stream's end (one that comes now returns at once)
        self._wake(gens)
        if self._kv is not None and self._kv_owned:
            self._kv.close()   # shared stores outlive their engines

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- scheduler loop ----------------------------------------------------
    def _loop(self) -> None:
        import jax.numpy as jnp

        stop = False
        while not stop:
            with self._phase("gen/loop",
                             clock=self._goodput is not None) as it:
                stop = self._iterate(jnp, it)
            if self._counts_req is not None or (stop and self._count_names
                                                and not self._broken):
                with self._cond:
                    self._fetch_counts_locked()
            if self._goodput is not None:
                # close this iteration's taxonomy on the iteration's own
                # last clock read: the un-noted remainder is host-side
                # gather/bookkeeping (or the stuck latch, while the
                # watchdog has it marked)
                self._goodput.tick("watchdog_stuck" if self._stuck
                                   else "host_gather", now=it.t1 * 1e-9)

    def _iterate(self, jnp, it: _Phase) -> bool:
        """One iteration of the scheduler loop, inside its ``gen/loop``
        span (whose self time is lock waits, reaping and planning).
        True when the loop has to end."""
        with self._cond:
            if self._stopping:
                return True
            self._last_beat = time.monotonic()   # watchdog heartbeat
            if it.recording:
                it.set(queue=len(self._queue),
                       active=sum(g is not None for g in self._slot_gen))
            if (not self._queue
                    and not any(g is not None for g in self._slot_gen)):
                # idle: wake on new work, and periodically anyway so
                # TTL reaping runs while nothing is streaming
                with self._phase("gen/idle_wait", "admission_idle"):
                    self._cond.wait(timeout=0.25)
                if self._stopping:
                    return True
        try:
            if self._stuck:
                # the watchdog failed this loop's generations while
                # a call was (apparently) wedged; whatever state the
                # call left behind is garbage — rebuild or break
                raise _EpochChanged("watchdog marked the engine "
                                    "stuck")
            self._reap_expired()
            if self._sched is not None:
                # one brain, once per iteration: re-order the wait
                # queue (class rank + fair tags) and fix this
                # iteration's budgets; park victims when an
                # interactive head is waiting on a full engine
                with self._cond:
                    self._plan = self._sched.plan(self._queue,
                                                  self._slot_gen)
                if self._plan.preempt:
                    self._preempt_tick()
            if self._paged:
                progressed = self._admit_paged()
                progressed |= self._prefill_tick()
                progressed |= self._decode_step(jnp)
                if not progressed:
                    # queue blocked on pages and nothing to step:
                    # wait for a cancel/TTL/poll to free capacity
                    # instead of spinning
                    with self._phase("gen/idle_wait", "admission_idle"), \
                            self._cond:
                        if not self._stopping:
                            self._cond.wait(timeout=0.05)
            else:
                self._admit()
                self._decode_step(jnp)
        except Exception as e:   # device-side failure: fail loudly
            with self._cond:
                self._consec_traps += 1
                consec = self._consec_traps
            if self._rebuild_max > 0 and consec <= self._rebuild_max:
                try:              # self-heal: fail active gens,
                    self._rebuild(e)   # fresh state, re-admit
                    return False
                except Exception as e2:   # rebuild itself trapped
                    self._break(e2)
                    return True
            self._break(e)       # terminal: refuse new work,
            return True          # keep pollers sane
        return False

    def _note_trap(self, gens: list[Generation], e: BaseException, *,
                   exact: bool = False) -> None:
        """Record a prefill/decode trap against the implicated
        generations' crash fingerprints; a fingerprint that reaches its
        quarantine threshold is quarantined — its future starts get the
        typed :class:`RequestQuarantined`. Prefill traps implicate
        exactly the prefilling request (``exact=True``: threshold is
        ``gen_quarantine_after`` as configured). Fused-decode and
        watchdog traps implicate every stepped generation — when more
        than one was stepped those fingerprints are co-tenant-
        AMBIGUOUS: booked separately as "suspect" and requiring at
        least 2 independent hits before quarantine, so a neighbor's
        poison request can't get a well-behaved bystander quarantined
        off one shared trap. A trap implicating exactly one generation
        is exact by pigeonhole regardless of the site."""
        stat_add("gen/traps")
        if self._quarantine_after <= 0 or not gens:
            return
        exact = exact or len(gens) == 1
        need = (self._quarantine_after if exact
                else max(2, self._quarantine_after))
        books = self._crash_counts if exact else self._suspect_counts
        msg = f"{type(e).__name__}: {e}"
        with self._cond:
            for gen in gens:
                fp = gen.fingerprint
                books[fp] = books.get(fp, 0) + 1
                if not exact:
                    stat_add("gen/suspect_traps")
                if books[fp] >= need and fp not in self._quarantined:
                    self._quarantined[fp] = msg
                    stat_add("gen/quarantined")
            while len(books) > 1024:            # bounded books
                books.pop(next(iter(books)))

    # -- page-table device residency (gen_device_pt) -----------------------
    def _pt_sync_row_locked(self, slot: int) -> None:
        """Host table row ``slot`` changed (admit/retire): mirror ONLY
        that row to the device-resident table and drop the default
        path's cached whole-table upload. Caller holds the lock. The
        functional ``.at`` update leaves any snapshot an in-flight
        dispatch captured untouched."""
        if self._pt_dev is not None and self._win is not None:
            self._pt_dev = tuple(
                d.at[slot].set(h[slot])
                for d, h in zip(self._pt_dev, (self._pt, self._win.pt)))
        elif self._pt_dev is not None:
            self._pt_dev = self._pt_dev.at[slot].set(self._pt[slot])
        self._sched_pt = None

    def _pt_place(self):
        """The host table(s) as a committed device-resident operand."""
        if self._win is not None:
            return (self._layout.place_pt(self._pt),
                    self._layout.place_pt(self._win.pt))
        return self._layout.place_pt(self._pt)

    def _pt_upload(self, jnp):
        """One upload of the host table — of both tables for a
        layer-group engine: the page-table operand of a compiled call."""
        if self._win is not None:
            # copies: a window row changes every few steps, and a CPU
            # backend's operand may alias the host array it was made
            # from while the program that reads it is still in flight
            return (jnp.asarray(self._pt.copy()),
                    jnp.asarray(self._win.pt.copy()))
        return jnp.asarray(self._pt)

    def _pt_clear_locked(self) -> None:
        """No slot is mapped any more (reset/break/close): zero the
        host table(s) and what mirrors them. Caller holds the lock."""
        self._pt[:] = 0
        if self._win is not None:
            self._win.pt[:] = 0
        self._pt_sync_full_locked()

    def _pt_sync_full_locked(self) -> None:
        """The whole host table changed (reset/rebuild/break): rebuild
        the device-resident table wholesale and drop the cached
        upload. Caller holds the lock."""
        if self._pt_dev is not None:
            self._pt_dev = self._pt_place()
        self._sched_pt = None

    def _pt_device_locked(self, jnp):
        """The page-table operand for a compiled call. gen_device_pt:
        the incrementally maintained device-resident table.
        Default path: ONE whole-table upload cached until admit/retire
        dirties it — the fix for re-shipping an unchanged table every
        iteration (prefill chunks and the spec path's second upload
        included). Caller holds the lock; the returned array is a
        snapshot (functional updates never mutate it in place)."""
        if self._pt_dev is not None:
            return self._pt_dev
        if self._sched_pt is None:
            with self._phase("gen/table_upload"):
                self._sched_pt = self._pt_upload(jnp)
        return self._sched_pt

    def _fail_active_locked(self, msg: str) -> list[Generation]:
        """Fail every slotted generation loudly (queued generations
        never touched the device — they stay queued and survive the
        reset). Caller holds the lock and is about to discard/rebuild
        the device state, so pages are NOT returned to the old pool.
        Returns the failed generations."""
        victims = [g for g in self._slot_gen if g is not None]
        for g in victims:
            if not g.done:
                g.done = True
                g.error = msg
                self._gen_event(g, "gen/retire", reason="failed",
                                tokens=len(g.tokens))
                self._ledger_finalize(g, "failed")
            g.slot = None
            g.prefilling = False
            g.pages = []
            g.win = None
        self._slot_gen = [None] * self.slots
        if self._paged:
            self._pt_clear_locked()
        self._pending.clear()         # deferred readbacks die with the
        self._epoch += 1              # epoch: in-flight compiled results
        stat_set("gen/slots_active", 0)   # are garbage from here on
        return victims

    def _rebuild(self, e: Exception) -> None:
        """Self-heal after a decode-loop trap: fail the active
        generations with the resumable ``engine reset:`` marker, replace
        the device state (cache pool, page books, prefix cache) wholesale,
        and re-admit — queued work proceeds, new starts are accepted.
        Raises if rebuilding itself fails (the caller then breaks)."""
        msg = f"{RESET_MARKER} {type(e).__name__}: {e}"
        stat_add("gen/rebuilds")
        fresh = self._init_state()           # allocate outside the lock
        with self._cond:
            self._rebuilds += 1
            victims = self._fail_active_locked(msg)
            if self._paged:
                self._reset_pools_locked()
                stat_set("gen/pages_free", self._pages_free())
            self._state = fresh
            # the words start again at nought: what stats() last saw
            # stays counted, the tail since then is lost with the state
            self._counts_base = dict(self._counts_host)
            self._stuck = False
        self._wake(victims)

    def _watchdog_loop(self) -> None:
        """Stuck-step detection: active work but no loop heartbeat for
        ``gen_watchdog_s`` → fail the stranded generations loudly (their
        clients resume elsewhere), shed new starts, and let the loop
        rebuild/break when the wedged call finally returns."""
        period = max(min(self._watchdog_s / 4.0, 1.0), 0.05)
        while not self._watch_stop.wait(period):
            victims: list[Generation] = []
            with self._cond:
                if self._stopping:
                    return
                if self._stuck or self._broken is not None:
                    continue
                # an admission-time KV fetch counts as busy work: the
                # admitting generation holds no slot yet, but a wedged
                # store read stalls the whole loop exactly like a
                # wedged compiled call
                admitting = self._admitting
                busy = (any(g is not None for g in self._slot_gen)
                        or admitting is not None)
                stalled = time.monotonic() - self._last_beat
                if not busy or stalled <= self._watchdog_s:
                    continue
                stat_add("gen/stuck")
                victims = self._fail_active_locked(
                    f"{RESET_MARKER} stuck step: decode loop "
                    f"unresponsive for {stalled:.1f}s "
                    f"(gen_watchdog_s={self._watchdog_s:g})")
                if admitting is not None and not admitting.done:
                    # stranded mid-admission (PR 8 contract): fail it
                    # resumable too — it was never slotted, so
                    # _fail_active_locked can't see it
                    admitting.done = True
                    admitting.error = (
                        f"{RESET_MARKER} stuck step: admission kv "
                        f"fetch unresponsive for {stalled:.1f}s "
                        f"(gen_watchdog_s={self._watchdog_s:g})")
                    self._gen_event(admitting, "gen/retire",
                                    reason="failed",
                                    tokens=len(admitting.tokens))
                    self._ledger_finalize(admitting, "failed")
                    victims = victims + [admitting]
                self._stuck = True
            self._wake(victims)
            self._note_trap(victims,
                            TimeoutError("stuck decode step"))

    def _break(self, e: Exception) -> None:
        msg = f"{type(e).__name__}: {e}"
        with self._cond:
            self._broken = msg
            self._stuck = False       # broken supersedes stuck
            gens = list(self._gens.values())
            for gen in gens:
                if not gen.done:
                    gen.done = True
                    gen.error = msg
                    gen.slot = None
                    self._gen_event(gen, "gen/retire", reason="broken",
                                    tokens=len(gen.tokens))
                self._ledger_finalize(gen, "broken")
                gen.pages = []
                gen.win = None
            self._slot_gen = [None] * self.slots
            self._queue.clear()
            if self._paged:           # nothing runs on a broken engine;
                self._pt_clear_locked()   # reset the books for stats()
                self._reset_pools_locked()
            self._pending.clear()
        self._wake(gens)

    def _reset_pools_locked(self) -> None:
        """Fresh page books (rebuild / break): nothing of the old device
        state is mapped any more."""
        self._pool = _PagePool(self._pool.num_pages)
        if self._win is not None:
            self._win.reset()
        if self._snaps is not None:
            self._snaps = _SnapshotPool(self._snaps.num)
        if self._prefix is not None:
            self._prefix = _PrefixCache(self._page_tokens,
                                        self._win and self._win.pool,
                                        self._snaps)

    def _pages_free(self) -> int:
        """Free pages of the pool — of both groups' pools together."""
        free = self._pool.free_count
        if self._win is not None:
            free += self._win.pool.free_count
        return free

    def _release_slot_locked(self, gen: Generation,
                             evicted: bool = False) -> None:
        if gen.slot is not None and self._slot_gen[gen.slot] is gen:
            self._slot_gen[gen.slot] = None
            if self._paged:
                self._pt[gen.slot] = 0
                if gen.win is not None:
                    self._win.pt[gen.slot] = 0
                self._pt_sync_row_locked(gen.slot)
            if evicted:
                stat_add("gen/evictions")
        if gen.win is not None:
            self._win.release(gen.win)
            gen.win = None
        if gen.snap_src:
            # left before its first chunk: the pin goes; the slot's rows
            # are given back by doing nothing (the next admission's
            # first chunk starts from its own snapshot)
            self._snaps.release(gen.snap_src)
        gen.snap_src = None
        if self._paged and gen.pages:
            # drop this generation's references; pages the prefix cache
            # also holds stay allocated (shareable) until evicted
            for pid in gen.pages:
                self._pool.release(pid)
            gen.pages = []
            stat_set("gen/pages_free", self._pages_free())
        gen.slot = None
        gen.prefilling = False
        stat_set("gen/slots_active",
                 sum(g is not None for g in self._slot_gen))

    def _tombstone_locked(self, gen_id: str) -> None:
        """Remember a reaped generation id (bounded) so a late poll
        gets the typed :class:`GenerationExpired`, not unknown-id."""
        self._expired[gen_id] = time.monotonic()
        while len(self._expired) > 256:        # oldest first (dict order)
            self._expired.pop(next(iter(self._expired)))

    def _reap_expired(self) -> None:
        if self._ttl_s <= 0:
            return
        now = time.monotonic()
        with self._cond:
            expired = [g for g in self._gens.values()
                       if now - max(g.created, g.last_poll) > self._ttl_s]
        for gen in expired:
            with self._cond:
                g = self._gens.get(gen.gen_id)
                if g is None:
                    continue
                # re-check under the lock: a poll that arrived while
                # this reap was walking the candidates refreshed the
                # TTL — it must keep its generation, not observe a
                # half-reclaimed slot
                if (time.monotonic() - max(g.created, g.last_poll)
                        <= self._ttl_s):
                    continue
                self._gens.pop(g.gen_id, None)
                self._tombstone_locked(g.gen_id)
                if not g.done:
                    g.done = True
                    g.error = (f"{EXPIRED_MARKER} poll TTL exceeded "
                               "(client gone?)")
                    self._gen_event(g, "gen/retire", reason="expired",
                                    tokens=len(g.tokens))
                    self._release_slot_locked(g, evicted=True)
                    try:
                        self._queue.remove(g)
                    except ValueError:
                        pass
                # done-but-never-delivered generations retire here too:
                # the reap is the last event this engine sees for them
                self._ledger_finalize(g, "expired")
            self._wake((g,))

    def _admit(self) -> None:
        while True:
            with self._cond:
                free = [s for s, g in enumerate(self._slot_gen)
                        if g is None]
                if not free or not self._queue:
                    stat_set("gen/queue_depth", len(self._queue))
                    return
                gen = self._queue.popleft()
                if gen.done:          # cancelled while queued
                    continue
                with self._phase("gen/admit", gen=gen) as ph:
                    slot = free[0]
                    self._slot_gen[slot] = gen
                    gen.slot = slot
                    self._note_admitted_locked(gen, ph)
                    stat_set("gen/slots_active",
                             sum(g is not None for g in self._slot_gen))
                    self._gen_event(gen, "gen/admitted", slot=slot,
                                    prompt_len=int(gen.prompt.size))
            self._prefill(gen, slot)

    def _note_admitted_locked(self, gen: Generation, ph: _Phase) -> None:
        """A request got its slot: one clock read stamps the ledger's
        admission and the ``gen/admit`` span's ``waited_ms`` (enqueue →
        now), the request's share of time to first token that is spent
        waiting for the loop to come round."""
        if self._ledger is not None or ph.recording:
            now = time.monotonic()
            if self._ledger is not None:
                gen.admitted_ts = now
                self._ledger.book_admission(gen, now)
            if ph.recording:
                ph.set(gen=gen.gen_id,
                       waited_ms=round((now - gen.created) * 1e3, 3))
        if self._sched is not None:
            self._sched.note_admitted(gen)

    def _admit_paged(self) -> bool:
        """Assign free slots + page reservations to queued prompts, in
        FIFO order. A generation reserves pages for its declared worst
        case (prompt + max_new_tokens) minus whatever whole-page prefix
        the radix cache already holds; when the pool cannot cover the
        queue head even after LRU-evicting unreferenced cached pages,
        admission stalls (head-of-line — predictable under pressure;
        pages return via retire/cancel/TTL). Prefill itself happens
        chunk-by-chunk in :meth:`_prefill_tick`."""
        progressed = False
        while True:
            with self._cond:
                free = [s for s, g in enumerate(self._slot_gen)
                        if g is None]
                if not free or not self._queue:
                    stat_set("gen/queue_depth", len(self._queue))
                    return progressed
                gen = self._queue[0]
                if gen.done:                # cancelled while queued
                    self._queue.popleft()
                    continue
                with self._phase("gen/admit", gen=gen,
                                 clock=True) as ph:
                    P = self._page_tokens
                    # spec_k extra positions: the verify step's fixed-width
                    # scatter may touch one page past the declared worst
                    # case (rejected offsets are null-page-masked, but the
                    # ACCEPTED prefix must land in owned pages)
                    # a parked (preempted) generation folded its emitted
                    # tokens into the prompt: max_new shrinks by the same
                    # amount, so its reservation never grows past the
                    # original worst case (folded is 0 for fresh requests)
                    need = -(-self._reserve(gen.prompt.size,
                                            gen.max_new_tokens - gen.folded)
                             // P)
                    matched: list[int] = []
                    snap = 0        # a state group: the hit's snapshot
                    if self._prefix is not None and self._snaps is not None:
                        matched, snap = self._prefix.match_state(
                            gen.prompt, self._pool)
                    elif self._prefix is not None:
                        matched = self._prefix.match(gen.prompt, self._pool)
                    if (self._kv is not None and self._kv_fetch
                            and self._prefix is not None):
                        epoch0 = self._epoch
                        matched += self._kv_admit_fetch(gen, matched)
                        if self._epoch != epoch0 or self._stuck:
                            # the store fetch ran with the lock released
                            # and a rebuild/watchdog reset landed under it:
                            # matched pages belong to the replaced pool —
                            # do NOT release them into the fresh one
                            return progressed
                        if gen.done:        # cancelled while fetching
                            for pid in matched:
                                self._pool.release(pid)
                            stat_set("gen/pages_free", self._pool.free_count)
                            continue        # loop top pops the dead head
                        if gen.rng_skip:
                            # a resumed stream's original prompt is
                            # prompt[:-rng_skip] (replay appended the
                            # delivered tokens); whatever of it the cache +
                            # store did not cover is recomputed prefill —
                            # the debt KV-native failover exists to zero
                            debt = max(0, (int(gen.prompt.size)
                                           - int(gen.rng_skip))
                                       - len(matched) * P)
                            self._kv_recomputed += debt
                            if debt:
                                stat_add("gen/kv_prefill_recomputed", debt)
                    short = (need - len(matched)) - self._pool.free_count
                    win = self._win
                    if win is not None:
                        # the window group promises the stream the most
                        # fresh pages it can hold at once
                        wneed = win.budget(need, len(matched))
                        short = max(short, wneed - win.spare())
                    if short > 0 and self._prefix is not None:
                        self._prefix.evict(short, self._pool,
                                           demote=(self._kv_demote
                                                   if self._kv is not None
                                                   else None))
                    if (need - len(matched) > self._pool.free_count
                            or (win is not None and wneed > win.spare())):
                        for pid in matched:     # give the hits back; retry
                            self._pool.release(pid)   # when pages free up
                        if snap:
                            self._snaps.release(snap)
                        if (self._plan is not None
                                and self._plan.hol_window > 0
                                and self._hol_bypass_locked()):
                            continue        # a smaller request jumped ahead
                        stat_set("gen/queue_depth", len(self._queue))
                        stat_set("gen/pages_free", self._pages_free())
                        return progressed
                    self._queue.popleft()
                    gen.pages = matched + self._pool.alloc(need - len(matched))
                    gen.shared = len(matched)
                    slot = free[0]
                    if win is not None:
                        gen.win = win.admit(
                            slot, self._prefix.wpages(matched)
                            if matched else [], need)
                    self._slot_gen[slot] = gen
                    gen.slot = slot
                    if self._snaps is not None:
                        # the first chunk's program starts from this
                        # snapshot (0: from zeros); pinned until then
                        gen.snap_src = snap
                        self._state_admits[0] += 1
                        self._state_admits[1] += bool(snap)
                        if snap:
                            with self._phase("gen/state_restore",
                                             slot=slot, snapshot=snap,
                                             tokens=len(matched) * P):
                                stat_add("gen/state_restores")
                    self._note_admitted_locked(gen, ph)
                    ph.set(prefix_tokens=len(matched) * P,
                           pages=len(gen.pages))
                    gen.prefilling = True
                    gen.prefill_pos = len(matched) * P
                    gen.prefill_t0 = ph.t0 * 1e-9
                    if (self._blockdiff is not None
                            and gen.prefill_pos >= self._prefilled(gen)):
                        # whole blocks of the prompt all cached (or none
                        # to prefill): the next step starts its block
                        gen.prefilling = False
                        gen.bstart = True
                    self._pt[slot] = 0
                    self._pt[slot, :len(gen.pages)] = gen.pages
                    self._pt_sync_row_locked(slot)
                    if matched:
                        stat_add("gen/prefix_hits")
                        stat_add("gen/prefix_tokens_saved", len(matched) * P)
                    stat_set("gen/pages_free", self._pages_free())
                    stat_set("gen/slots_active",
                             sum(g is not None for g in self._slot_gen))
                    stat_set("gen/queue_depth", len(self._queue))
                    self._gen_event(gen, "gen/admitted", slot=slot,
                                    prompt_len=int(gen.prompt.size),
                                    pages=len(gen.pages), shared=gen.shared)
                progressed = True

    # -- scheduler mechanics (FLAGS_gen_sched; never run otherwise) --------
    def _hol_bypass_locked(self) -> bool:
        """The queue head is blocked on pages: scan the plan's bounded
        window past it for a request whose worst case fits the free
        pool RIGHT NOW and rotate it to the front. The scheduler
        re-orders the queue every iteration, so the bypassed head
        returns to the front as soon as pages free up — bounded, not
        starvation. Caller holds the lock; True when a candidate
        moved (the admit loop then retries)."""
        P = self._page_tokens
        limit = min(len(self._queue), self._plan.hol_window + 1)
        for i in range(1, limit):
            g = self._queue[i]
            if g.done:
                continue
            need = -(-(g.prompt.size + g.max_new_tokens - g.folded
                       + self._spec_k) // P)
            if need <= self._pool.free_count:
                del self._queue[i]
                self._queue.appendleft(g)
                stat_add("gen/sched_hol_bypass")
                return True
        return False

    def _preempt_tick(self) -> None:
        """An interactive request heads the queue with every slot busy:
        park the scheduler's chosen victim (strictly lower class, most
        recently admitted) so the next admit tick seats the interactive
        stream. Paged engines only — parking releases pages, and resume
        rides the chunked-prefill path. Loop thread only."""
        if not self._paged:
            return
        # flush the async dispatch lookahead first: no in-flight step
        # may hold a snapshot of a slot this tick is about to clear
        # (their lagged tokens would hit the identity guard anyway, but
        # draining keeps every parked stream's token list final)
        self._drain_pending()
        with self._cond:
            if not self._queue:
                return
            head = self._queue[0]
            if head.done or head.slot is not None:
                return
            if any(g is None for g in self._slot_gen):
                return                  # a slot freed meanwhile
            cands = [(s, g) for s, g in enumerate(self._slot_gen)
                     if g is not None and not g.prefilling
                     and not g.done]
            for _s, victim in self._sched.choose_victims(
                    cands, head.pclass, 1):
                self._park_locked(victim)

    def _park_locked(self, gen: Generation) -> None:
        """Preempt a decoding generation without losing a byte: fold
        the tokens it has emitted into its prompt and advance
        ``rng_skip`` by the same count (one sampling split per emitted
        token — exactly the cross-replica resume contract the
        determinism tests pin), release its slot and pages, and
        re-queue it. Re-admission chunk-prefills the folded prompt —
        the prefix cache turns that into a table rebuild when the pages
        survived — and decode continues byte-identically. Delivered
        tokens stay on ``gen.tokens``; pollers never notice beyond the
        pause. Caller holds the lock."""
        new = np.asarray(gen.tokens[gen.folded:], np.int32)
        if new.size:
            gen.prompt = np.concatenate([gen.prompt, new])
            gen.rng_skip += int(new.size)
            gen.folded = len(gen.tokens)
            gen.dev_ops = None          # PRNG start moved with rng_skip
        gen.prefill_pos = 0
        self._release_slot_locked(gen)
        self._sched.note_parked(gen)
        self._sched.on_enqueue(gen)     # re-tag at current virtual time
        self._queue.append(gen)
        stat_add("gen/preemptions")
        stat_set("gen/queue_depth", len(self._queue))
        self._gen_event(gen, "gen/parked", tokens=len(gen.tokens),
                        folded=int(gen.folded))

    def _page_frame(self, pid: int) -> bytes:
        """Serialize pool page ``pid`` (one device->host fetch per
        cache leaf) into a wire frame. Works for both layouts — the
        int8 quantized pool just has 4 leaves instead of 2."""
        from paddle_tpu.models.generation import serialize_page
        return serialize_page([np.asarray(leaf[pid])
                               for leaf in self._state["cache"]])

    def _kv_demote(self, e: _PrefixEntry) -> None:
        """Prefix-cache eviction hook: publish the victim page to the
        KV store (under its full radix chain key) before the pool
        releases it — eviction demotes instead of dropping."""
        chain = self._prefix.chain_tokens(e)
        if chain is None:
            return
        from paddle_tpu.serving.kvstore import page_chain_keys
        toks = np.frombuffer(b"".join(chain), np.int32)
        key = page_chain_keys(toks, self._page_tokens)[-1]
        if self._kv.contains(key) or self._kv.put(key,
                                                  self._page_frame(e.page)):
            self._kv_demoted += 1
            stat_add("gen/kv_demotions")

    def _kv_publish(self, gen: Generation) -> None:
        """Publish every full prompt page of a finished prefill to the
        store (prefill/'both' tier AND decode tier — whoever computed
        pages shares them; the store's content-addressed put makes
        re-publication a no-op)."""
        from paddle_tpu.serving.kvstore import page_chain_keys
        keys = page_chain_keys(gen.prompt, self._page_tokens)
        for i, key in enumerate(keys):
            if self._kv.contains(key):
                continue
            frame = self._page_frame(gen.pages[i])
            if self._kv.put(key, frame):
                self._kv_published += 1
                stat_add("gen/kv_puts")
                stat_add("gen/kv_put_bytes", len(frame))

    def _kv_admit_fetch(self, gen: Generation,
                        matched: list[int]) -> list[int]:
        """Admission-time store fetch: extend the local radix match
        with pages fetched from the KV store, so the miss becomes a
        transfer instead of a prefill recompute. Fetched pages are
        scattered into the pool host-side and registered in the prefix
        cache (page tables are rehydrated from the page-id list like
        any matched page). Stops at the first miss / corrupt frame /
        page shortage; capped like ``match`` so at least one prompt
        token remains to prefill.

        The store I/O runs with the scheduler lock RELEASED (the
        caller holds it): a slow or dead tier must not freeze pollers,
        cancels, or the watchdog heartbeat. ``self._admitting`` marks
        the generation as busy work for the watchdog; after
        re-acquiring, an epoch change or stuck latch means the pool we
        were admitting into is gone — everything is dropped. Every
        budget overrun, tier failure or corrupt frame degrades the
        remainder of the chain to local prefill recompute
        (byte-identical by construction) and books
        ``gen/kv_fetch_degraded``."""
        from paddle_tpu.models.generation import deserialize_page
        from paddle_tpu.serving.kvstore import page_chain_keys
        import jax.numpy as jnp
        P = self._page_tokens
        cap = (int(gen.prompt.size) - 1) // P
        start = len(matched)
        if start >= cap:
            return []
        kv_budget = self._kv_admit_s
        if self._plan is not None:
            # scheduler budget: tighten the fetch window under
            # interactive SLO pressure (the miss degrades to local
            # recompute — byte-identical, just compute instead of I/O)
            kv_budget *= self._plan.kv_scale
        keys = page_chain_keys(gen.prompt, P, limit=cap)
        shapes = [(tuple(pl.shape[1:]), pl.dtype)
                  for pl in self._state["cache"]]
        epoch0 = self._epoch
        self._admitting = gen
        with self._phase("gen/kv_fetch", "kv_fetch", clock=True) as fetch:
            self._cond.release()
            frames: list[tuple[tuple, int]] = []   # (validated leaves, nbytes)
            degraded = False
            try:
                for key in keys[start:]:
                    if gen.done or self._stuck or self._stopping:
                        break
                    if (kv_budget > 0 and (time.perf_counter_ns() - fetch.t0)
                            * 1e-9 > kv_budget):
                        # admission-level budget across the whole chain:
                        # the rest is recompute debt, not a wedge
                        degraded = True
                        stat_add("gen/kv_admit_timeouts")
                        break
                    try:
                        frame, deg = self._kv.fetch(key)
                    except Exception:
                        frame, deg = None, True
                    if frame is None:
                        degraded |= deg
                        break
                    try:
                        leaves = deserialize_page(frame)
                    except ValueError:
                        # corrupt/truncated store entry: a miss, but a
                        # DEGRADED one — the bytes existed and were bad
                        degraded = True
                        stat_add("gen/kv_corrupt")
                        break
                    if (len(leaves) != len(shapes)
                            or any(l.shape != shp or l.dtype != dt
                                   for l, (shp, dt) in zip(leaves, shapes))):
                        break                # foreign layout: not our pool
                    frames.append((leaves, len(frame)))
            finally:
                self._cond.acquire()
                self._admitting = None
        if degraded:
            self._kv_degraded += 1
            stat_add("gen/kv_fetch_degraded")
        if gen.done or self._epoch != epoch0 or self._stuck:
            # cancelled / watchdog-failed / rebuilt while unlocked: the
            # caller re-evaluates; nothing was alloc'd yet
            return []
        fetched: list[int] = []
        nbytes = 0
        for leaves, flen in frames:
            if self._pool.free_count == 0 and self._prefix.evict(
                    1, self._pool, demote=self._kv_demote) == 0:
                break
            pid = self._pool.alloc(1)[0]
            self._state["cache"] = tuple(
                pl.at[pid].set(jnp.asarray(l)) for pl, l
                in zip(self._state["cache"], leaves))
            fetched.append(pid)
            nbytes += flen
        if fetched:
            # register the fetched chain so the NEXT admission is a
            # local radix hit; insert gives the cache its +1 ref, the
            # alloc above is the generation's ref — same accounting as
            # a matched page
            cov = start + len(fetched)
            self._prefix.insert(gen.prompt[:cov * P], matched + fetched,
                                self._pool)
            self._kv_fetched_pages += len(fetched)
            self._kv_fetched_bytes += nbytes
            stat_add("gen/kv_hits")
            stat_add("gen/kv_fetch_pages", len(fetched))
            stat_add("gen/kv_fetch_bytes", nbytes)
            stat_add("gen/kv_fetch_tokens_saved", len(fetched) * P)
        else:
            stat_add("gen/kv_miss")
        return fetched

    def _gen_dev_ops(self, gen: Generation, jax, jnp) -> tuple:
        """Per-request device operands (starting PRNG key with
        ``rng_skip`` applied, temperature/top_k/top_p scalars), built
        once and cached on the generation — they never change for its
        lifetime, so chunked prefill stops re-materializing four host
        arrays per chunk."""
        if gen.dev_ops is None:
            with self._phase("gen/dev_ops"):
                key = jax.random.PRNGKey(gen.seed)
                if gen.rng_skip:
                    from paddle_tpu.models.generation import advance_key
                    key = advance_key(key, gen.rng_skip)
                gen.dev_ops = (key,
                               jnp.asarray(gen.temperature, jnp.float32),
                               jnp.asarray(gen.top_k, jnp.int32),
                               jnp.asarray(gen.top_p, jnp.float32))
        return gen.dev_ops

    # -- delivery: the one place a token reaches a stream ---------------------
    def _deliver_locked(self, gen: Generation, toks) -> tuple[int, int]:
        """Append ``toks`` in order; at EOS or ``max_new_tokens`` retire
        the stream and stop: accepted drafts past the end are dropped
        on the host (the device state behind them is garbage, but the
        slot is released right here). Returns ``(emitted, retired)``;
        ``_cond`` held."""
        emitted = 0
        for tok in toks:
            gen.tokens.append(tok)
            emitted += 1
            if ((gen.eos_token_id is not None
                 and tok == gen.eos_token_id)
                    or len(gen.tokens) >= gen.max_new_tokens):
                gen.done = True
                if self._ledger is not None:
                    gen.done_ts = time.monotonic()
                self._gen_event(gen, "gen/retire", reason="complete",
                                tokens=len(gen.tokens))
                self._release_slot_locked(gen)
                return emitted, 1
        return emitted, 0

    def _first_token_locked(self, gen: Generation, tok0: int) -> None:
        """A prefill's sampled token. TTFT = enqueue -> first token
        (queue wait included): the latency an interactive SLO is about
        and the signal the control plane autoscales on. A parked
        stream's resume-prefill is NOT a first token (its TTFT was
        observed before the preemption), hence the two guards; a
        contiguous stream is never parked (``_preempt_tick`` returns at
        ``not self._paged``), so both always pass there. ``_cond``
        held."""
        if self._ledger is not None and gen.first_tok_ts == 0.0:
            gen.first_tok_ts = time.monotonic()
        if gen.folded == 0:
            observe("gen/ttft_s", time.monotonic() - gen.created)
            if self._sched is not None and gen.tenant:
                # per-tenant split: the fairness input
                # MetricsHub.burn_rates(tenant=) reads
                observe(f"gen/ttft_s/{gen.tenant}",
                        time.monotonic() - gen.created)
        stat_add("gen/tokens")
        self._deliver_locked(gen, (tok0,))

    def _emit_step(self, stepped, chip_share, toks, accepted=None) -> None:
        """A decode step's ``gen/emit``, sync or lagged: under ``_cond``
        every stepped slot its generation still holds (not cancelled
        mid-step, not retired by an earlier lagged entry) takes its
        share of the step's chip-seconds and its token ``toks[slot]``
        — of a speculative step, the tokens ``accepted(slot, gen)``;
        then, with the lock let go, those streams' polls are woken."""
        emitted = retired = 0
        moved = []
        with self._phase("gen/emit") as emit_ph:
            with self._cond:
                if accepted is not None:
                    self._spec_verify_steps += 1
                for s, gen in stepped:
                    if self._slot_gen[s] is not gen:
                        continue
                    if self._ledger is not None:
                        gen.chip_s += chip_share
                    e, r = self._deliver_locked(
                        gen, (toks[s],) if accepted is None
                        else accepted(s, gen))
                    emitted += e
                    retired += r
                    moved.append(gen)
                self._emit_total += emitted
                self._decode_iters += 1
                if emitted:
                    stat_add("gen/tokens", emitted)
            emit_ph.set(emitted=emitted, retired=retired,
                        woken=self._wake(moved))

    def _slide_locked(self, rows) -> None:
        """Before the upload of the tables for the programs about to be
        dispatched: every ``(slot, gen, first, end)`` row's window group
        lets go of the pages wholly behind the window of a program that
        starts at ``first`` and maps fresh ones up to ``end``
        (:meth:`_WindowGroup.cover`). A ``gen/kv_slide`` span where a
        page is due to go, the counter ``gen/kv_pages_slid``; ``_cond``
        held."""
        win = self._win
        due = any(win.first_page(first) > g.win.base
                  for _, g, first, _ in rows)
        with (self._phase("gen/kv_slide") if due else _NOOP_PHASE) as ph:
            slid = 0
            for slot, gen, first, end in rows:
                before = gen.win.base, len(gen.win.pages)
                slid += win.cover(gen.win, slot, first, end)
                if before != (gen.win.base, len(gen.win.pages)):
                    self._pt_sync_row_locked(slot)
            if slid:
                ph.set(pages=slid)
                stat_add("gen/kv_pages_slid", slid)
                stat_set("gen/pages_free", self._pages_free())

    def _prefix_insert_groups_locked(self, gen: Generation, a: int,
                                     b: int) -> None:
        """Register the whole prompt pages below ``b`` (a chunk ``[a,
        b)`` has just been dispatched), both groups' page each. The
        cache pins the window page it takes, so pages are evicted first
        where the pool has none to spare, and the chain ends where that
        does not help. ``_cond`` held."""
        P, win = self._page_tokens, self._win
        short = (b // P - a // P) - win.spare()
        if short > 0:
            self._prefix.evict(short, self._pool)
        self._prefix.insert(
            gen.prompt[:b], gen.pages, self._pool,
            second=lambda i: win.hand_to_cache(gen.win, i))

    def _snapshot_for_locked(self, gen: Generation, b: int) -> tuple[int, int]:
        """Before a prefill chunk that ends at position ``b`` is
        dispatched: ``(the snapshot that will keep its end state or 0,
        whether one was evicted for it)``. A snapshot is taken where a
        chunk ends on a page boundary and no entry keeps that state yet;
        with no entry free the least recently used one goes, and where
        every snapshot is pinned the chunk's state is not kept.
        ``_cond`` held."""
        if self._prefix is None or b % self._page_tokens:
            return 0, 0
        e = self._prefix.find(gen.prompt[:b])
        if e is not None and e.snap:
            return 0, 0
        sid = self._snaps.alloc()
        if sid:
            return sid, 0
        if self._prefix.evict_snapshot():
            return self._snaps.alloc(), 1
        return 0, 0

    def _keep_snapshot_locked(self, gen: Generation, b: int, sid: int,
                              evicted: int) -> None:
        """The chunk that wrote snapshot ``sid`` (the state at position
        ``b``) has been dispatched: the prompt's pages below ``b`` enter
        the prefix cache, the last one's entry holding the snapshot — a
        prompt's pages are entered only up to its deepest snapshot, the
        ones past it could never be hit. ``_cond`` held."""
        with self._phase("gen/state_snapshot", tokens=b, evicted=evicted):
            self._prefix.insert(gen.prompt[:b], gen.pages, self._pool,
                                snap=sid)
            stat_add("gen/state_snapshots")

    def _prefilled(self, gen: Generation) -> int:
        """Prompt positions the prefill writes: all of them, or of a
        block-diffusion prompt its whole blocks — the remainder rides
        the first generated block."""
        if self._blockdiff is None:
            return int(gen.prompt.size)
        B = self._blockdiff["B"]
        return int(gen.prompt.size) // B * B

    def _prefill_tick(self) -> bool:
        """Advance every prefilling slot by ONE chunk (then the loop
        runs a decode step — chunked prefill interleaves with decode
        instead of stalling every active stream for a full-prompt
        prefill). The final chunk samples the first token and flips the
        slot into decode."""
        import jax
        import jax.numpy as jnp

        def chunk_of(gen):
            T0 = self._prefilled(gen)
            a = gen.prefill_pos
            C = self._prefill_chunk if self._prefill_chunk > 0 else T0 - a
            if self._plan is not None and self._plan.prefill_chunk:
                # scheduler budget: clamp this iteration's chunk so a
                # long batch prefill cannot monopolize the loop while
                # interactive work waits
                C = min(C, self._plan.prefill_chunk)
            return T0, a, min(T0, a + C)

        with self._cond:
            work = [(s, g) for s, g in enumerate(self._slot_gen)
                    if g is not None and g.prefilling]
            if self._win is not None and work:
                # each chunk's window-group pages, before the upload
                self._slide_locked([(s, g, *chunk_of(g)[1:])
                                    for s, g in work])
            pt_dev = None if not work else self._pt_device_locked(jnp)
            epoch0 = self._epoch
            # a state group: the snapshot each chunk's end state goes to
            kept = ({s: self._snapshot_for_locked(g, chunk_of(g)[2])
                     for s, g in work} if self._snaps is not None else {})
        ticked = False
        for slot, gen in work:
            T0, a, b = chunk_of(gen)
            state_ops, (keep, evicted) = (), kept.get(slot, (0, 0))
            if self._snaps is not None:
                src = -1 if gen.snap_src is None else gen.snap_src
                state_ops = (jnp.asarray(src, jnp.int32),
                             jnp.asarray(keep or self._snaps.scratch,
                                         jnp.int32))
            final = b >= T0
            smax = self._maxp * self._page_tokens
            # cap the padded length so the traced write window stays in
            # bounds (dynamic_update_slice clamps its start — an
            # overflowing pad window would silently shift real tokens)
            bucket = min(self._bucket(b - a), smax - a)
            padded = np.full((bucket,), self._pad, np.int32)
            padded[:b - a] = gen.prompt[a:b]
            key, temp, top_k, top_p = self._gen_dev_ops(gen, jax, jnp)
            try:
                with self._phase("gen/prefill_chunk", "prefill",
                                 "gen/prefill_chunk_s",
                                 ("paged_prefill", bucket), gen, slot=slot,
                                 index=a, tokens=b - a,
                                 final=final) as chunk:
                    _fault.inject("engine.prefill")
                    ops = (jnp.asarray(slot, jnp.int32), jnp.asarray(padded),
                           jnp.asarray(a, jnp.int32),
                           jnp.asarray(b - a, jnp.int32))
                    with self._launch("paged_prefill"):
                        self._state, tok0 = self._prefill_fn(
                            self._state, pt_dev, *ops, key,
                            temp, top_k, top_p, *state_ops)
                    # let go while the call is in flight, as a temporary
                    # would be: the runtime then frees the operands off
                    # this thread (freed after the readback, the loop
                    # pays for it: 0.6 ms a step on the chip, PR 37)
                    del ops
                    if final and self._blockdiff is None:
                        # a chunk that is not the last reads nothing
                        # back: it launches and lands nothing (nor does
                        # a block-diffusion prompt's last: its first
                        # tokens come from the block step)
                        with self._phase("gen/prefill_wait",
                                         landed=self._launched):
                            tok0 = int(tok0)
                    else:
                        tok0 = None
            except Exception as e:       # a prefill trap implicates
                self._note_trap([gen], e, exact=True)  # exactly this one
                raise
            if self._ledger is not None:
                gen.chip_s += chunk.dt
            self._last_beat = time.monotonic()
            self._consec_traps = 0       # real device work succeeded
            if self._epoch != epoch0:
                raise _EpochChanged("prefill chunk outlived the "
                                    "watchdog deadline")
            ticked = True
            with self._phase("gen/emit", emitted=int(
                    final and self._blockdiff is None)) as emit_ph:
                with self._cond:
                    if self._slot_gen[slot] is not gen:
                        if keep:            # cancelled/reaped mid-chunk
                            self._snaps.release(keep)
                        continue
                    gen.prefill_pos = b
                    if self._snaps is not None:
                        # the chunk has read its source: the pin goes
                        self._snaps.release(gen.snap_src or 0)
                        gen.snap_src = None
                        if keep:
                            self._keep_snapshot_locked(gen, b, keep,
                                                       evicted)
                    if self._win is not None and self._prefix is not None:
                        # a window row holds a prompt page only until the
                        # stream has passed it: the cache takes both pages
                        # of every whole page as soon as a chunk filled it
                        self._prefix_insert_groups_locked(gen, a, b)
                    if not final:
                        continue
                    gen.prefilling = False
                    observe("gen/prefill_s",
                            chunk.t1 * 1e-9 - gen.prefill_t0)
                    if gen.win is not None:
                        gen.win.pos = int(T0)
                    elif self._prefix is not None and self._snaps is None:
                        self._prefix.insert(gen.prompt, gen.pages,
                                            self._pool)
                    if self._kv is not None:
                        self._kv_publish(gen)
                    if self._blockdiff is not None:
                        gen.bstart = True       # the next step begins
                        continue                # its first block
                    self._first_token_locked(gen, tok0)
                emit_ph.set(woken=self._wake((gen,)))
        return ticked

    def _prefill(self, gen: Generation, slot: int) -> None:
        import jax
        import jax.numpy as jnp

        T0 = gen.prompt.size
        bucket = self._bucket(T0)
        padded = np.full((bucket,), self._pad, np.int32)
        padded[:T0] = gen.prompt
        key, temp, top_k, top_p = self._gen_dev_ops(gen, jax, jnp)
        epoch0 = self._epoch
        try:
            with self._phase("gen/prefill", "prefill", "gen/prefill_s",
                             ("prefill", bucket), gen, slot=slot,
                             prompt_len=T0, bucket=bucket) as call:
                _fault.inject("engine.prefill")
                ops = (jnp.asarray(slot, jnp.int32), jnp.asarray(padded),
                       jnp.asarray(T0, jnp.int32))
                with self._launch("prefill"):
                    self._state, tok0 = self._prefill_fn(
                        self._state, *ops, key, temp, top_k, top_p)
                del ops             # see _prefill_tick
                with self._phase("gen/prefill_wait", landed=self._launched):
                    tok0 = int(tok0)
        except Exception as e:           # a prefill trap implicates
            self._note_trap([gen], e, exact=True)     # exactly this one
            raise
        if self._ledger is not None:
            gen.chip_s += call.dt
        self._last_beat = time.monotonic()
        self._consec_traps = 0           # real device work succeeded
        if self._epoch != epoch0:
            raise _EpochChanged("prefill outlived the watchdog deadline")
        with self._cond:
            if self._slot_gen[slot] is not gen:   # cancelled mid-prefill
                return
            self._first_token_locked(gen, tok0)
        self._wake((gen,))

    def _block_step(self, jnp) -> bool:
        """The loop's step of a block-diffusion engine: every slot past
        its prefill takes one block step (:meth:`_build_block_step`); a
        slot whose prompt has just been prefilled begins its first block
        — the prompt's remainder, ``[MASK]`` after it — in the same
        program. The host knows from the schedule which slots fix and
        which commit (``gen/decode_step``'s ``fixing``, ``committing``);
        the tokens come back with the step's readback, lagged as the
        plain step's under ``gen_async_depth``."""
        bd = self._blockdiff
        B, MASK, steps = bd["B"], bd["mask"], bd["steps"]
        with self._cond:
            stepped = [(s, g) for s, g in enumerate(self._slot_gen)
                       if g is not None and not g.prefilling]
            if not stepped and not self._pending:
                return False
            ops = np.zeros((self.slots, B + 3), np.int32)
            ops[:, 1] = -1
            fixing = 0
            for s, g in stepped:
                ops[s, 0] = 1
                if g.bstart:
                    g.bstart = False
                    p0 = self._prefilled(g)
                    rem = g.prompt[p0:]
                    ops[s, 1:3] = p0, rem.size
                    ops[s, 3:] = MASK
                    ops[s, 3:3 + rem.size] = rem
                    g.bphase, g.bneed = 0, min(B - rem.size, steps)
                if g.bphase < g.bneed:
                    g.bphase += 1
                    fixing += 1
                else:                   # a commit; then a fresh block
                    g.bphase, g.bneed = 0, min(B, steps)
            pt_dev = self._pt_device_locked(jnp) if stepped else None
            epoch0 = self._epoch
        if not stepped:
            self._drain_pending()
            return True
        lookahead = self._async_depth > 0
        try:
            with self._phase("gen/decode_step", "decode",
                             "gen/decode_step_s", ("paged_step", 0),
                             active=len(stepped), spec=0, sort_slots=0,
                             fixing=fixing,
                             committing=len(stepped) - fixing) as call:
                _fault.inject("engine.decode_step")
                with self._phase("gen/step_dispatch"):
                    dev_ops = jnp.asarray(ops)
                    with self._launch("paged_step"):
                        self._state, out = self._step(self._state, pt_dev,
                                                      dev_ops)
                    del dev_ops             # see _prefill_tick
                call.set(decode_attn=self._decode_attn)
                if not lookahead:
                    with self._phase("gen/step_wait",
                                     landed=self._launched):
                        out = np.asarray(out)
        except Exception as e:
            self._note_trap([g for _, g in stepped], e)
            raise
        chip_share = (call.dt / len(stepped)
                      if self._ledger is not None else 0.0)
        self._last_beat = time.monotonic()
        if lookahead:
            self._pending.append((stepped, out, epoch0, chip_share,
                                  self._launched))
            while len(self._pending) > self._async_depth:
                self._drain_pending(1)
            self._pace()
            return True
        self._consec_traps = 0           # real device work succeeded
        if self._epoch != epoch0:
            raise _EpochChanged("decode step outlived the watchdog "
                                "deadline")
        self._emit_block(stepped, chip_share, out)
        self._pace()
        return True

    def _emit_block(self, stepped, chip_share, out) -> None:
        """A block step's ``gen/emit``, sync or lagged: under ``_cond``
        the step's totals (live slot-steps, positions fixed, commits:
        ``stats()["block_diffusion"]`` and the ``gen/block_*`` counters)
        and, for every stepped slot its generation still holds whose
        block this step completed, the block recorded (first position,
        ids, the step each was fixed at) and its tokens delivered — the
        prompt's share of a first block left out, tokens past EOS or
        ``max_new_tokens`` dropped by ``_deliver_locked``. Then, with the
        lock let go, those streams' polls are woken."""
        bd = self._blockdiff
        B = bd["B"]
        emitted = retired = 0
        moved = []
        rows = [s for s, _ in stepped]
        fixed = int(out[rows, 2 * B + 1].sum())
        commits = int(out[rows, 2 * B + 2].sum())
        with self._phase("gen/emit") as emit_ph:
            with self._cond:
                bd["slot_steps"] += len(stepped)
                bd["tokens_fixed"] += fixed
                bd["commits"] += commits
                for s, gen in stepped:
                    if self._slot_gen[s] is not gen:
                        continue
                    if self._ledger is not None:
                        gen.chip_s += chip_share
                    if not out[s, 2 * B]:
                        continue
                    p0 = self._prefilled(gen) + B * len(gen.blocks)
                    ids = out[s, :B].copy()
                    gen.blocks.append((p0, ids, out[s, B:2 * B].copy()))
                    if not gen.tokens:
                        if self._ledger is not None:
                            gen.first_tok_ts = time.monotonic()
                        observe("gen/ttft_s", time.monotonic() - gen.created)
                    e, r = self._deliver_locked(
                        gen, ids[max(int(gen.prompt.size) - p0, 0):].tolist())
                    emitted += e
                    retired += r
                    moved.append(gen)
                self._emit_total += emitted
                self._decode_iters += 1
                if emitted:
                    stat_add("gen/tokens", emitted)
            stat_add("gen/block_slot_steps", len(stepped))
            if fixed:
                stat_add("gen/block_tokens_fixed", fixed)
            if commits:
                stat_add("gen/block_commits", commits)
            emit_ph.set(emitted=emitted, retired=retired,
                        woken=self._wake(moved))

    def _decode_step(self, jnp) -> bool:
        if self._blockdiff is not None:
            return self._block_step(jnp)
        if self._pending and self._spec_k > 0:
            # speculative drafting (and the occupancy-shed decision)
            # reads host-side context — flush the dispatch lookahead
            # first so drafts see up-to-date tokens and slots
            self._drain_pending()
        with self._cond:
            stepped = [(s, g) for s, g in enumerate(self._slot_gen)
                       if g is not None and not g.prefilling]
            if not stepped and not self._pending:
                return False
            active = np.zeros((self.slots,), bool)
            for s, _ in stepped:
                active[s] = True
            sort_slots = sum(g.sorts for _, g in stepped)
            if self._win is not None and stepped:
                # the step writes each stream's next position
                self._slide_locked([(s, g, g.win.pos, g.win.pos + 1)
                                    for s, g in stepped])
                for _, g in stepped:
                    g.win.pos += 1
            pt_dev = (self._pt_device_locked(jnp)
                      if self._paged and stepped else None)
            epoch0 = self._epoch
            specable: list[tuple[int, np.ndarray, int]] = []
            spec_k = self._spec_k
            if (spec_k > 0 and self._plan is not None
                    and self._plan.spec_budget is not None):
                # scheduler budget: 0 sheds speculation outright this
                # iteration (interactive work is waiting — the verify
                # step's extra width would delay it); otherwise a cap
                spec_k = min(spec_k, self._plan.spec_budget)
            if spec_k > 0:
                # load-adaptive shedding: above the occupancy threshold
                # batched decode already fills the device — speculative
                # FLOPs would only starve co-tenant slots, so the whole
                # iteration falls back to the plain fused step
                occ = (sum(g is not None for g in self._slot_gen)
                       / self.slots)
                if occ <= self._spec_shed:
                    specable = [
                        (s,
                         np.concatenate(
                             [g.prompt,
                              np.asarray(g.tokens, np.int32)]),
                         min(spec_k,
                             g.max_new_tokens - len(g.tokens) - 1))
                        for s, g in stepped]
        if not stepped:
            # nothing new to dispatch: drain the lagged in-flight steps
            # so their retirements land and pages free up
            self._drain_pending()
            return True
        use_spec = False
        if specable:
            # drafting happens OUTSIDE the lock (ngram is host-side
            # numpy; draft-model lookahead is its own compiled call)
            dlens = np.zeros((self.slots,), np.int32)
            drafts = np.zeros((self.slots, self._spec_k), np.int32)
            for s, ctx, cap in specable:
                if cap <= 0:
                    continue       # last token due: nothing to verify
                d = self._propose(ctx, cap)
                if d.size:
                    dlens[s] = d.size
                    drafts[s, :d.size] = d
            # no slot produced a draft -> the plain step is strictly
            # cheaper (width 1 vs K+1) and byte-identical
            use_spec = bool(dlens.any())
        lookahead = self._async_depth > 0 and not use_spec
        args = (pt_dev,) if self._paged else ()
        entry = ("spec_step" if use_spec
                 else ("paged_step" if self._paged else "step"))
        try:
            # gen/step_dispatch: operand staging, then gen/launch round
            # the call until it returns (the device starts before it
            # does); gen/step_wait: the host blocked on the device for
            # the tokens of the launch it names as landed
            with self._phase(
                    "gen/decode_step",
                    "spec_verify" if use_spec else "decode",
                    "gen/decode_step_s", (entry, 0),
                    active=len(stepped), spec=int(use_spec),
                    sort_slots=sort_slots) as call:
                _fault.inject("engine.decode_step")
                if sort_slots:
                    self._sample_sorted_steps += 1
                    stat_add("gen/sample_sorted_steps")
                if use_spec:
                    with self._phase("gen/spec_verify",
                                     hist="gen/spec_verify_s",
                                     drafted=int(dlens.sum())):
                        with self._phase("gen/step_dispatch"):
                            ops = (jnp.asarray(active), jnp.asarray(drafts),
                                   jnp.asarray(dlens))
                            with self._launch(entry):
                                self._state, out, emit = self._spec_step(
                                    self._state, *args, *ops)
                            del ops         # see _prefill_tick
                        with self._phase("gen/step_wait",
                                         landed=self._launched):
                            out = np.asarray(out)
                            emit = np.asarray(emit)
                else:
                    with self._phase("gen/step_dispatch"):
                        mask = jnp.asarray(active)
                        with self._launch(entry):
                            self._state, toks = self._step(
                                self._state, *args, mask)
                        del mask            # see _prefill_tick
                    if self._paged:
                        call.set(decode_attn=self._decode_attn)
                    if not lookahead:
                        with self._phase("gen/step_wait",
                                         landed=self._launched):
                            toks = np.asarray(toks)
        except Exception as e:
            # the fused step shares one compiled call: every stepped
            # generation is implicated (co-tenant counts — see
            # _note_trap's threshold note)
            self._note_trap([g for _, g in stepped], e)
            raise
        # chip-second attribution: one fused step serves every stepped
        # slot — split its device wall evenly across them
        chip_share = (call.dt / len(stepped)
                      if self._ledger is not None else 0.0)
        self._last_beat = time.monotonic()
        if lookahead:
            # defer the blocking token readback (gen_async_depth): the
            # autoregressive chain feeds itself on device, so the next
            # loop iteration dispatches step i+1 before step i's tokens
            # come back; delivery/retirement bookkeeping runs against
            # the lagged tokens when the entry drains — <= depth steps
            # late, safe because post-EOS steps write only pads.
            # _consec_traps is NOT reset here: only the readback in
            # _finish_step proves the device work actually ran.
            self._pending.append((stepped, toks, epoch0, chip_share,
                                  self._launched))
            while len(self._pending) > self._async_depth:
                self._drain_pending(1)
            self._pace()
            return True
        self._consec_traps = 0           # real device work succeeded
        if self._epoch != epoch0:
            raise _EpochChanged("decode step outlived the watchdog "
                                "deadline")

        def accepted(s, gen):
            n = int(emit[s])
            dlen = int(dlens[s])
            if dlen:
                acc = n - 1
                gen.spec_proposed += dlen
                gen.spec_accepted += acc
                self._spec_proposed += dlen
                self._spec_accepted += acc
                stat_add("gen/spec_proposed", dlen)
                stat_add("gen/spec_accepted", acc)
                stat_add("gen/spec_rejected", dlen - acc)
                observe("gen/spec_accept_len", float(acc))
                self._gen_event(gen, "gen/spec_accept", slot=s,
                                proposed=dlen, accepted=acc)
            return [int(t) for t in out[s, :n]]

        if use_spec:
            self._emit_step(stepped, chip_share, None, accepted)
        else:
            self._emit_step(stepped, chip_share, toks.tolist())
        self._pace()
        return True

    def _pace(self) -> None:
        """``step_wait_s``: a deliberate pacing gap after a decode step
        — idle by configuration, not work."""
        if self.step_wait_s > 0:
            with self._phase("gen/idle_wait", "admission_idle"):
                time.sleep(self.step_wait_s)

    # -- async dispatch lookahead (gen_async_depth) ------------------------
    def _drain_pending(self, n: int | None = None) -> None:
        """Retire deferred readbacks, oldest first: block on each
        entry's device tokens and run the delivery/retirement
        bookkeeping the sync loop does inline. ``n`` bounds how many
        entries drain (None = all). Loop thread only; the reset paths
        may clear the deque concurrently, hence the guarded pop."""
        while self._pending and (n is None or n > 0):
            try:
                entry = self._pending.popleft()
            except IndexError:       # cleared under our feet (reset)
                return
            self._finish_step(*entry)
            if n is not None:
                n -= 1

    def _finish_step(self, stepped, toks_dev, epoch0, chip_share,
                     seq) -> None:
        """Second half of a lookahead decode step: the now-explicit
        blocking readback — measured and booked as ``host_gather``
        instead of swept in by ``tick`` — followed by the same
        bookkeeping as the sync path. Deferred device errors surface
        HERE (np.asarray is where XLA delivers them) and implicate the
        entry's generations exactly like a sync trap. A slot retired
        or reassigned by an earlier entry is skipped by the identity
        guard, so lagged post-EOS tokens are never delivered."""
        try:
            with self._phase("gen/step_wait", "host_gather", landed=seq):
                toks = np.asarray(toks_dev)
        except Exception as e:
            self._note_trap([g for _, g in stepped], e)
            raise
        self._last_beat = time.monotonic()
        self._consec_traps = 0           # real device work succeeded
        if self._epoch != epoch0:
            # the watchdog failed this entry's generations while it was
            # in flight — its tokens are garbage; the loop's stuck
            # latch forces the rebuild/break decision
            return
        if self._blockdiff is not None:
            self._emit_block(stepped, chip_share, toks)
            return
        self._emit_step(stepped, chip_share, toks.tolist())
