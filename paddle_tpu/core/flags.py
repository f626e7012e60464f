"""Global flag registry.

The reference centralizes ~60 gflags in ``paddle/fluid/platform/flags.cc``
and exposes them to Python through
``paddle/fluid/pybind/global_value_getter_setter.cc`` (``paddle.set_flags``).
Here flags are a plain validated registry; flags that map onto XLA/JAX
behavior apply themselves (e.g. deterministic ops), the rest configure
framework-level features (nan/inf checking, logging verbosity, allocator
tuning for the host pipeline).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable

__all__ = ["define_flag", "set_flags", "get_flags", "flag"]


@dataclass
class _Flag:
    name: str
    default: Any
    help: str
    on_set: Callable[[Any], None] | None = None
    value: Any = None


_REGISTRY: dict[str, _Flag] = {}
_lock = threading.Lock()


def define_flag(name: str, default: Any, help: str = "",
                on_set: Callable[[Any], None] | None = None) -> None:
    with _lock:
        if name in _REGISTRY:
            raise KeyError(f"flag {name!r} already defined")
        env = os.environ.get(f"FLAGS_{name}")
        value = default if env is None else _coerce(env, default)
        _REGISTRY[name] = _Flag(name, default, help, on_set, value)
    if env is not None and _REGISTRY[name].on_set:
        _REGISTRY[name].on_set(value)


def _coerce(raw: str, default: Any) -> Any:
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def set_flags(flags: dict[str, Any]) -> None:
    """``paddle.set_flags`` equivalent."""
    for name, value in flags.items():
        with _lock:
            if name not in _REGISTRY:
                raise KeyError(f"unknown flag {name!r}")
            f = _REGISTRY[name]
            f.value = value
        if f.on_set is not None:
            f.on_set(value)


def get_flags(names: list[str] | str | None = None) -> dict[str, Any]:
    """``paddle.get_flags`` equivalent."""
    if names is None:
        names = list(_REGISTRY)
    if isinstance(names, str):
        names = [names]
    return {n: _REGISTRY[n].value for n in names}


def flag(name: str) -> Any:
    """Fast read of a single flag value."""
    return _REGISTRY[name].value


# ---------------------------------------------------------------------------
# Core flags (the subset of platform/flags.cc that is meaningful on TPU).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "After each training step, sweep outputs/grads for NaN/Inf "
            "(reference FLAGS_check_nan_inf, platform/flags.cc:44)")
define_flag("benchmark", False,
            "Block on each step for timing (reference FLAGS_benchmark)")
define_flag("v", 0, "Logging verbosity (glog-style VLOG level)")
define_flag("host_prefetch_buffer", 4,
            "Host data-pipeline prefetch depth (reference reader capacity)")
define_flag("deterministic", False,
            "Force deterministic XLA reductions where possible")
define_flag("amp_dtype", "bfloat16",
            "Autocast compute dtype for AMP (bf16 is TPU-native; fp16 kept "
            "for parity with reference AMP lists)")

# --- fault-tolerance layer (core/fault.py, core/wire.py, io/checkpoint.py) ---
define_flag("wire_timeout_s", 60.0,
            "Connect + per-request deadline for frame-protocol clients "
            "(serving, PS, ptfs). <= 0 disables the deadline (the old "
            "block-forever behavior)")
define_flag("wire_retries", 2,
            "Retry budget for idempotent wire requests after a connection "
            "failure/timeout (transparent reconnect between attempts). "
            "0 disables retry")
define_flag("wire_backoff_s", 0.05,
            "Base of the exponential retry backoff (doubles per attempt, "
            "+/-50% jitter)")
define_flag("wire_backoff_max_s", 2.0,
            "Cap on a single retry backoff sleep")
# --- server-side overload protection (core/wire.py FrameService) ---
define_flag("wire_max_inflight", 0,
            "Cap on concurrent in-flight requests per FrameService; excess "
            "requests are shed fast with the retryable status code 2 "
            "(header carries retry_after_s) instead of queueing "
            "unboundedly. 0 = unlimited")
define_flag("wire_max_conns", 0,
            "Cap on accepted connections per FrameService; an over-cap "
            "connection gets one shed frame (code 2, closing) in reply to "
            "its first request and is closed. 0 = unlimited")
define_flag("wire_server_idle_s", 0.0,
            "Per-connection server idle timeout: a client silent this long "
            "is reaped (wire/idle_closed stat) instead of pinning a "
            "handler thread forever. 0 = off")
define_flag("wire_drain_s", 5.0,
            "Graceful-drain deadline used by the wire 'stop' ops and "
            "io.PreemptionHandler: stop accepting, let in-flight requests "
            "finish for this many seconds, then sever")
define_flag("ps_barrier_timeout_s", 120.0,
            "Server-side wait bound for the PS generation barrier; the "
            "client's barrier request deadline tracks it +10s. "
            "<= 0 waits forever")
# --- serving at scale (paddle_tpu/serving: batcher + router) ---
define_flag("serving_batch_max", 0,
            "Cross-request dynamic batching in InferenceServer: max rows "
            "(batch-axis elements) coalesced into one Predictor run. "
            "0 or 1 — the default — disables batching entirely; the "
            "serving path is then byte-identical to the unbatched one "
            "(one flag read per infer, the FLAGS_trace pattern). Only "
            "models exported with dynamic_batch=True participate")
define_flag("serving_batch_timeout_s", 0.005,
            "How long an infer request may wait for co-batchable requests "
            "before the partial batch is flushed (the Orca/Clipper-style "
            "batching window). Only read when serving_batch_max > 1")
define_flag("serving_batch_min_queue", 2,
            "Load watermark for cross-request batching: a request that "
            "finds fewer than this many concurrent submits for its "
            "model (and no batch forming) bypasses leader/follower "
            "coalescing and runs immediately, so idle traffic never "
            "pays the serving_batch_timeout_s window tax (measured "
            "0.57x at concurrency 1 before the watermark). 0 restores "
            "unconditional coalescing")
define_flag("serving_probe_interval_s", 1.0,
            "Health-probe cadence of serving.RoutedClient: each replica's "
            "universal health op is polled this often to drive routed "
            "membership (unreachable/draining replicas stop receiving "
            "new requests; recovered ones rejoin)")
# --- continuous-batching generation engine (serving/engine.py) ---
define_flag("gen_slots", 0,
            "Slot count of the continuous-batching GenerationEngine: one "
            "fixed-shape batched KV cache holds this many concurrent "
            "generations, admitted/retired at decode-step granularity "
            "(iteration-level scheduling). 0 — the default — disables "
            "generation serving entirely; InferenceServer.add_generator "
            "then requires an explicit slots=, and the plain serving "
            "path is byte-identical to the engine-less build")
define_flag("gen_max_len", 512,
            "Per-slot KV-cache capacity of the GenerationEngine "
            "(prompt + generated tokens); the engine allocates "
            "slots x this once, so shapes stay static across requests "
            "(no XLA recompiles)")
define_flag("gen_queue_max", 8,
            "How many prompts may queue for a free engine slot before "
            "generate_start is shed with the retryable CODE_SHED status "
            "(header carries retry_after_s). 0 = unbounded queue")
define_flag("gen_poll_ttl_s", 30.0,
            "Reap a generation whose client has not polled for this "
            "long (disconnected/crashed callers must not pin a slot "
            "forever; gen/evictions counts the reclaims). <= 0 disables")
# --- paged KV cache + prefix sharing + chunked prefill (serving/engine.py) ---
define_flag("gen_paged", False,
            "Paged KV-cache mode for the GenerationEngine: the cache "
            "becomes a pool of fixed-size pages plus per-slot page "
            "tables (vLLM PagedAttention, SOSP '23), so a short "
            "completion pays HBM for the tokens it actually holds and "
            "admission sheds on page-pool exhaustion, not slot count. "
            "Hard-off default: the PR-5 contiguous per-slot layout "
            "stays byte-identical")
define_flag("gen_page_tokens", 16,
            "Tokens per physical KV page in paged mode. Smaller pages "
            "waste less tail capacity per generation and share prefixes "
            "at finer grain; larger pages mean fewer gather indices per "
            "decode step")
define_flag("gen_pages", 0,
            "Physical pages in the paged KV pool. 0 — the default — "
            "sizes the pool to gen_slots x ceil(gen_max_len / "
            "gen_page_tokens): exactly the HBM of the contiguous "
            "layout, so capacity gains come purely from short "
            "completions and shared prefixes")
define_flag("gen_prefill_chunk", 0,
            "Chunked prefill: admit a prompt in slices of this many "
            "tokens, interleaved with decode steps, so a long prompt "
            "no longer stalls every active stream for a full-prompt "
            "prefill. 0 — the default — prefills the whole prompt "
            "(tail past any shared prefix) in one forward")
define_flag("gen_prefix_cache", True,
            "Radix prefix cache over full prompt pages (paged mode "
            "only): generations sharing a prompt prefix map their "
            "early pages to the same refcounted physical pages and "
            "prefill runs once per unique prefix "
            "(gen/prefix_hits, gen/prefix_tokens_saved). Cached pages "
            "are LRU-evicted under pool pressure")
# --- end-to-end generation resilience (serving/engine.py, router.py) ---
define_flag("gen_resume_budget", 0,
            "Client-side stream-resumption budget: when a replica dies "
            "(or its engine resets) under an in-flight generation "
            "stream, RoutedClient/StickySession.generate replays "
            "prompt + tokens-already-delivered to a freshly picked "
            "replica as a prefill-from-prefix and keeps emitting from "
            "where the stream broke — byte-identical for greedy decode, "
            "RNG-position-replayed for sampled — up to this many "
            "restarts per stream, then the typed StreamResumeExhausted "
            "surfaces. 0 — the default — disables resumption entirely: "
            "mid-stream replica loss surfaces GenerationFailed exactly "
            "as before")
define_flag("gen_quarantine_after", 0,
            "Crash quarantine: a request whose prefill/decode traps the "
            "engine this many times (by crash fingerprint — prompt "
            "bytes + sampling params) is rejected at generate_start "
            "with the typed RequestQuarantined instead of being "
            "retried into every replica in the fleet. 0 — the default "
            "— disables quarantine (no fingerprint bookkeeping)")
define_flag("gen_engine_rebuilds", 0,
            "Engine self-healing: how many consecutive decode-loop "
            "traps the GenerationEngine absorbs by failing the active "
            "generations loudly (error carries the 'engine reset:' "
            "marker — resumable), rebuilding the cache pool and slot "
            "state, and re-admitting work — before falling back to the "
            "terminal broken state. A successful decode/prefill resets "
            "the consecutive-trap count. 0 — the default — keeps the "
            "pre-resilience behavior: the first trap bricks the engine")
define_flag("gen_watchdog_s", 0.0,
            "Stuck-step watchdog for the GenerationEngine decode loop: "
            "when active work exists but the loop has not completed an "
            "iteration for this long, the watchdog fails the active "
            "generations loudly (clients resume elsewhere), sheds new "
            "starts, and the loop rebuilds when the stuck call "
            "returns. Must comfortably exceed worst-case XLA compile "
            "time for the engine's buckets. 0 — the default — no "
            "watchdog thread at all")
# --- speculative decoding (models/generation.py, serving/engine.py) ---
define_flag("gen_spec_k", 0,
            "Speculative-decoding draft length for the GenerationEngine: "
            "a cheap drafter proposes up to k tokens that ONE batched "
            "target forward verifies (accept the longest matching "
            "prefix), turning k memory-bound decode steps into one "
            "compute-denser step. Greedy output stays byte-identical to "
            "non-speculative decode; sampled streams keep the one-split-"
            "per-emitted-token key schedule, so rng_skip stream "
            "resumption composes unchanged. 0 — the default — disables "
            "speculation entirely: the engine compiles the PR-5 fused "
            "step only and the decode path is byte-identical to the "
            "pre-speculation build")
define_flag("gen_spec_mode", "ngram",
            "Drafter for speculative decoding: 'ngram' (model-free "
            "prompt-lookup — propose the continuation of the most "
            "recent prior occurrence of the stream's own suffix; zero "
            "extra weights, the right default for serving) or 'draft' "
            "(a small draft model with the same init_cache/"
            "forward_with_cache contract, passed as draft_model= to "
            "the engine). Ignored while gen_spec_k=0")
define_flag("gen_spec_ngram", 3,
            "Longest suffix n-gram the model-free drafter tries to "
            "match against the stream's own prompt + emitted tokens "
            "(falls back to shorter n-grams down to 1). Ignored unless "
            "gen_spec_k > 0 and gen_spec_mode=ngram")
define_flag("gen_spec_shed_occupancy", 0.5,
            "Slot-occupancy fraction above which the engine sheds "
            "speculation (per-slot draft budget drops to 0): batched "
            "decode already fills the MXU under load, so speculative "
            "extra FLOPs would only steal from co-tenants. Speculation "
            "resumes as occupancy falls. Ignored while gen_spec_k=0")
# --- sharded serving: tensor-parallel engine mesh (serving/layout.py) ---
define_flag("gen_mesh_tp", 0,
            "Tensor-parallel degree of the GenerationEngine device mesh: "
            "the engine is built over the first N local devices on a "
            "'tp' mesh axis, model params column/row-split on the "
            "attention/MLP projections (Megatron-LM) and the KV "
            "cache/page pool sharded on the KV-head axis, with every "
            "compiled entry point given explicit in/out shardings so "
            "XLA's SPMD partitioner inserts the collectives. A "
            "mesh-backed engine is ONE logical replica (one endpoint); "
            "token streams are byte-identical to the unsharded engine. "
            "0 — the default — builds no mesh at all: the single-device "
            "path is byte-identical to the pre-sharding build and the "
            "flag is read only at engine construction, never on the "
            "decode hot path")
# --- performance attribution (serving/ledger.py) ---
define_flag("gen_ledger", False,
            "Per-request latency ledger + engine goodput accounting + "
            "per-tenant attribution (serving/ledger.py): every "
            "generation gets a finalized phase record (admit-wait / "
            "prefill / decode / deliver, partitioning its end-to-end "
            "latency), the engine loop's wall-clock is classified into "
            "a 7-bucket taxonomy summing to 100% (goodput = useful-"
            "token time / total), and tokens/chip-seconds/queue-wait "
            "are booked per tenant (wire header 'tn'). Records ride "
            "stats()/health and the ledger_dump wire op. Hard-off "
            "default: the engine builds no books, the serving path is "
            "byte-identical, and the flag is read only at "
            "construction — hot-path gates are is-None attribute "
            "checks (the FLAGS_trace pattern)")
define_flag("gen_ledger_records", 256,
            "Ring capacity of finalized per-request ledger records "
            "kept per engine (oldest evicted first). Read only at "
            "engine construction, and only while gen_ledger is on")
# --- disaggregated serving (serving/kvstore.py KVStore) ---
define_flag("gen_kv_store", False,
            "Tiered fleet-wide KV page store (serving/kvstore.py): "
            "prefill publishes completed prompt pages under their "
            "radix chain key, admission probes the store and fetches "
            "matching prefixes before prefilling, and prefix-cache "
            "eviction demotes pages to the store instead of dropping "
            "them — a cache miss on one replica becomes a fetch, not "
            "a recompute. Hard-off default: the engine builds no "
            "store, the serving path is byte-identical, and the flag "
            "is read only at construction — hot-path gates are "
            "is-None attribute checks (the gen_ledger pattern)")
define_flag("gen_kv_store_pages", 256,
            "Host-RAM LRU tier capacity of the KV store, in pages. "
            "Overflow demotes the least-recently-used page to the "
            "spill tier (gen_kv_spill_dir) or drops it when no spill "
            "tier is configured. Read only at engine construction, "
            "and only while gen_kv_store is on")
define_flag("gen_kv_spill_dir", "",
            "Spill-tier root for the KV store: a local directory or "
            "a WireFS endpoint (ptfs://host:port/kv). Pointing every "
            "replica at the same root is what makes the store fleet-"
            "wide — pages published or demoted by one replica are "
            "fetchable by any other. Empty (default) keeps the store "
            "RAM-only and replica-local. Read only at engine "
            "construction, and only while gen_kv_store is on")
define_flag("gen_role", "both",
            "Replica serving role for the prefill/decode split: "
            "'prefill' replicas run prefill and kv_put the resulting "
            "pages but never fetch (they are the producers), 'decode' "
            "replicas probe/fetch at admission and admit straight "
            "into decode, 'both' (default) does both. Inert unless "
            "gen_kv_store is on; read only at engine construction")
define_flag("gen_kv_fetch_timeout_s", 0.0,
            "Per-page deadline for a cold KV-store fetch (spill/peer "
            "tiers): a fetch still pending at the deadline is "
            "abandoned and answers a degraded miss — the engine "
            "recomputes the prefix locally (gen/kv_fetch_degraded "
            "books the debt) instead of wedging admission on a slow "
            "tier. 0 (default) = unbounded, inline, thread-free "
            "fetches, byte-identical to the pre-hardening path. Read "
            "only at engine construction, only while gen_kv_store is "
            "on")
define_flag("gen_kv_admit_timeout_s", 0.0,
            "Admission-level budget across ALL page fetches of one "
            "generation's prefix chain: once exceeded, remaining "
            "pages degrade to local prefill recompute (the PR 14 miss "
            "path — byte-identical by construction). 0 (default) = "
            "unbounded. Read only at engine construction, only while "
            "gen_kv_store is on")
define_flag("gen_kv_hedge_ms", 0.0,
            "Hedged-fetch latency threshold in milliseconds: a spill-"
            "tier read still pending after this long races a peer "
            "replica's wire kv_get (gen_kv_peers); the first valid "
            "frame wins and the loser is abandoned. 0 (default) = no "
            "hedging. Read only at engine construction, only while "
            "gen_kv_store is on")
define_flag("gen_kv_breaker", 0,
            "Consecutive tier failures that open a KV-store tier's "
            "circuit breaker (spill and peer tiers; the control.py "
            "spawner-breaker idiom with exp-backoff half-open "
            "probes). While open the tier is skipped — puts stay RAM-"
            "only, eviction of unspilled frames drops loudly, fetches "
            "degrade to recompute, and the replica stops advertising "
            "KV placement (kv_probe answers no-match). 0 (default) = "
            "no breakers, no extra state. Read only at engine "
            "construction, only while gen_kv_store is on")
define_flag("gen_kv_breaker_backoff_s", 0.5,
            "Half-open probe backoff base for an open KV tier "
            "breaker, doubled per failed probe and capped at 32x. "
            "Inert unless gen_kv_breaker > 0; read only at engine "
            "construction")
define_flag("gen_kv_peers", "",
            "Comma-separated peer replica endpoints (host:port) for "
            "the KV store's peer tier: hedged/fallback kv_get fetches "
            "when the spill tier is slow, broken, or absent. Empty "
            "(default) = no peer tier. Read only at engine "
            "construction, only while gen_kv_store is on")
define_flag("gen_device_pt", False,
            "Keep the paged engine's per-slot page table resident on "
            "device, updated incrementally with dirty-row .at[slot]"
            ".set writes on admit/alloc/retire, so paged_step/"
            "paged_spec_step/chunked-prefill stop re-uploading the "
            "whole table host->device every iteration. Byte-identical "
            "to the host-table path; sharded engines replicate the "
            "table across the mesh. Inert unless gen_paged; read only "
            "at engine construction")
define_flag("gen_async_depth", 0,
            "Decode-loop dispatch lookahead: dispatch step i+1 before "
            "blocking on step i's token readback, doing delivery/"
            "retirement/ledger bookkeeping against the lagged tokens. "
            "0 (default) is the fully synchronous loop. Retirement "
            "lands <=depth steps late, which is safe because post-EOS "
            "steps write only pad tokens; greedy AND sampled streams "
            "stay byte-identical to the sync loop. Read only at "
            "engine construction")
define_flag("gen_sched", False,
            "SLO-aware tenant-fair scheduler (serving/scheduler.py): one "
            "admission/preemption brain for the engine loop. Owns queue "
            "ordering (priority classes + weighted-fair queueing across "
            "tenants), SLO-aware preemption of batch decode slots by "
            "interactive streams (park via prompt-fold, byte-identical "
            "resume), and per-iteration budgets for prefill-chunk size, "
            "spec-k, page admission and KV-fetch admission driven by "
            "MetricsHub burn rates and the goodput meter. Hard-off by "
            "default: the engine keeps its FIFO loop byte-identical and "
            "reads no sched flags on the hot path. Read only at engine "
            "construction")
define_flag("gen_sched_w_interactive", 4.0,
            "Class weight for 'interactive' priority streams under "
            "gen_sched weighted-fair queueing. Interactive also ranks "
            "strictly ahead of lower classes for admission and may "
            "preempt batch decode slots. Read only at engine "
            "construction, only while gen_sched is on")
define_flag("gen_sched_w_batch", 2.0,
            "Class weight for 'batch' priority streams (the default "
            "class when a request carries no priority header) under "
            "gen_sched weighted-fair queueing. Read only at engine "
            "construction, only while gen_sched is on")
define_flag("gen_sched_w_best_effort", 1.0,
            "Class weight for 'best_effort' priority streams under "
            "gen_sched weighted-fair queueing; best-effort is shed "
            "earliest under load and never preempts. Read only at "
            "engine construction, only while gen_sched is on")
define_flag("gen_sched_quotas", "",
            "Per-tenant quota hints for the gen_sched scheduler as "
            "'tenant=share' pairs, comma-separated (e.g. "
            "'alice=2,bob=1'). Shares scale each tenant's fair-queue "
            "weight; tenants running over their share (by TenantBook "
            "chip-seconds) are throttled, not starved. Empty = all "
            "tenants weighted equally. Read only at engine "
            "construction, only while gen_sched is on")
define_flag("gen_sched_chunk", 32,
            "Prefill-chunk budget the scheduler clamps to when "
            "interactive streams are queued or the TTFT burn rate runs "
            "hot, so a long batch prefill cannot monopolize an "
            "iteration. <= 0 leaves the engine's gen_prefill_chunk "
            "untouched. Read only at engine construction, only while "
            "gen_sched is on")
define_flag("gen_sched_headroom", 2,
            "Extra queue/inflight slots granted to interactive streams "
            "past the configured shed caps (gen_queue_max, "
            "wire_max_inflight) before the scheduler sheds them too; "
            "best-effort is shed at half the cap. Read only at engine "
            "construction, only while gen_sched is on")
# --- serving control plane (serving/control.py ServingController) ---
define_flag("control_interval_s", 1.0,
            "Cadence of the ServingController reconcile loop (signal "
            "collection, eviction, scale decisions). <= 0 disables the "
            "background thread — the controller then only acts on "
            "explicit tick()/scale_to() calls (how the tests drive it "
            "deterministically)")
define_flag("control_warm_models", 0,
            "Warm-tier capacity of the multi-model multiplexer: max "
            "models kept resident per replica; beyond it the controller "
            "unloads the least-recently-used cold-tier models (per-model "
            "last-used/bytes stats ship in health). 0 — the default — "
            "disables eviction entirely: every loaded model stays "
            "resident, byte-identical to the pre-control-plane fleet")
define_flag("control_min_replicas", 1,
            "Floor of the managed replica set: scale-down never goes "
            "below it, and start() spawns up to it")
define_flag("control_max_replicas", 0,
            "Ceiling of the managed replica set. 0 — the default — "
            "disables autoscaling entirely: the controller never spawns "
            "or retires replicas on its own (manual scale_to still "
            "works), so constructing one changes nothing")
define_flag("control_target_ttft_s", 0.0,
            "Time-to-first-token SLO: when the fleet-merged p99 of the "
            "gen/ttft_s histogram (enqueue -> first token, per control "
            "interval window) exceeds it, that's scale-up pressure. "
            "0 disables the TTFT signal")
define_flag("control_queue_high", 1.0,
            "Scale-up pressure when queued generations per replica "
            "reach this (a queued prompt means demand already exceeds "
            "slot/page capacity). <= 0 disables the queue signal")
define_flag("control_occupancy_high", 0.9,
            "Scale-up pressure when mean generation-slot occupancy "
            "(active/slots across replicas) reaches this — a fleet this "
            "full cannot absorb a burst. > 1 disables")
define_flag("control_occupancy_low", 0.25,
            "Scale-down eligibility: the fleet must idle below this "
            "occupancy (and show zero pressure signals) for "
            "control_idle_ticks consecutive ticks")
define_flag("control_inflight_high", 0.0,
            "Scale-up pressure when mean in-flight wire requests per "
            "replica reach this — the load signal for engine-less "
            "(plain infer) fleets. 0 disables")
define_flag("control_breach_ticks", 2,
            "Hysteresis: consecutive breaching ticks required before a "
            "scale-up fires (one noisy sample never scales)")
define_flag("control_idle_ticks", 5,
            "Hysteresis: consecutive fully-idle ticks required before a "
            "scale-down fires (longer than breach_ticks on purpose — "
            "adding capacity is cheap, removing it churns)")
define_flag("control_cooldown_s", 5.0,
            "Minimum gap between automatic scale events; decisions made "
            "inside the cooldown are recorded as held, not acted on — "
            "with breach/idle ticks this is what makes the loop "
            "flap-proof")
define_flag("control_drain_s", 10.0,
            "Sticky-drain deadline at scale-down: the cordoned victim "
            "gets this long for in-flight generations and infers to "
            "finish before it is stopped (a forced stop past the "
            "deadline is counted and logged, never silent)")
define_flag("control_spawn_breaker", 0,
            "Circuit breaker on ReplicaSpawner failures: after this "
            "many consecutive failed spawns (scale-up or dead-replica "
            "replace), the controller stops calling the spawner and "
            "backs off exponentially (control_spawn_backoff_s base, "
            "doubling per further failure) — a poisoned artifact "
            "degrades the fleet instead of hot-looping crash spawns. "
            "One trial spawn is allowed when the backoff elapses "
            "(half-open); success closes the breaker. 0 — the default "
            "— disables the breaker: every scale decision calls the "
            "spawner, exactly the pre-resilience behavior")
define_flag("control_spawn_backoff_s", 2.0,
            "Base of the spawn circuit-breaker backoff (doubles per "
            "consecutive failure past the breaker threshold, capped at "
            "32x). Only read once control_spawn_breaker > 0 opens the "
            "breaker path")
define_flag("control_slo_budget", 0.1,
            "SLO error budget as a fraction of observations allowed to "
            "violate the TTFT target (burn rate = violating fraction / "
            "this budget; burn 1.0 == burning the budget exactly as "
            "fast as allowed)")
define_flag("control_burn_fast_ticks", 5,
            "Fast burn-rate window in controller ticks: a scale-up "
            "needs the burn rate over this window above "
            "control_burn_threshold (catches an acute breach quickly)")
define_flag("control_burn_slow_ticks", 60,
            "Slow burn-rate window in controller ticks: the same burn "
            "threshold must also hold over this window (filters "
            "single-tick noise a raw p99 check would chase)")
define_flag("control_burn_threshold", 1.0,
            "Burn-rate level both windows must exceed before TTFT "
            "pressure fires (1.0 = consuming the error budget exactly "
            "at the allowed rate)")
define_flag("control_ha_lease_dir", "",
            "Control-plane HA root: a shared directory (or ptfs:// "
            "WireFS path) holding the leader lease file and the durable "
            "fleet-state journal (serving/ha.py). Non-empty turns the "
            "controller into one of N lease contenders: exactly one "
            "acts, standbys take over within one TTL, and a new leader "
            "replays the journal to the exact managed set. Empty — the "
            "default — disables HA entirely: no lease probes, no "
            "journal writes, byte-identical to the single-controller "
            "build. Read only at controller construction")
define_flag("control_ha_lease_ttl_s", 3.0,
            "Leader lease TTL: the holder renews once per controller "
            "tick; standbys treat a lease older than this as expired "
            "and claim it with a bumped term. Must comfortably exceed "
            "control_interval_s (a leader that cannot renew within one "
            "TTL is deposed). Only read once control_ha_lease_dir is "
            "set")
define_flag("control_ha_holder", "",
            "Stable identity this controller claims the lease under "
            "(shows in the lease file, journal records, and the "
            "leader/term health block). Empty — the default — derives "
            "host:pid:nonce. Only read once control_ha_lease_dir is "
            "set")
define_flag("control_ha_compact_records", 256,
            "Journal records accumulated before the leader compacts "
            "the fleet-state journal into a checkpoint snapshot "
            "(replay cost stays bounded). Only read once "
            "control_ha_lease_dir is set")
define_flag("ckpt_manifest", True,
            "Write + verify per-step checkpoint manifests (leaf names and "
            "checksums); corrupt steps then fall back to the newest "
            "verifiable one instead of crashing the resume")
define_flag("serving_emb", False,
            "PS-backed sparse embedding serving "
            "(serving/sparse.py EmbeddingServingTier): inference "
            "replicas pull/cache hot embedding rows from the parameter-"
            "server fleet, batched CTR lookups ride the DynamicBatcher, "
            "and trainer-published table versions roll over online with "
            "no restart. Hard-off default: the server never constructs "
            "the tier and the serving path is byte-identical (the "
            "FLAGS_trace pattern). Read only at server construction")
define_flag("serving_emb_cache_rows", 4096,
            "Per-table hot-row LRU capacity (rows) for the embedding "
            "serving tier; misses pull de-duplicated batches from the "
            "PS. Read only at tier construction, only while serving_emb "
            "is on")
define_flag("serving_emb_ttl_s", 0.0,
            "Seconds a cached embedding row stays servable before it is "
            "re-pulled (bounds staleness against async trainer pushes "
            "between version rollovers). <=0 — the default — never "
            "expires rows within a version; rollover still invalidates "
            "the whole generation. Read only at tier construction, only "
            "while serving_emb is on")


# --- observability (core/trace.py, core/monitor.py, core/logging.py) ---

def _on_trace(v) -> None:
    from paddle_tpu.core import trace

    trace.configure(bool(v))


def _on_trace_buffer(v) -> None:
    from paddle_tpu.core import trace

    trace.resize(int(v))           # live resize; keeps the newest spans


def _on_log_json(v) -> None:
    from paddle_tpu.core import logging as logging_mod

    logging_mod.set_json(bool(v))


define_flag("trace_buffer", 16384,
            "Span ring-buffer capacity for the in-process tracer "
            "(core/trace.py); oldest spans are evicted first and "
            "counted as dropped. The ring holds memory only while spans "
            "record; an 8 s capture of a serving engine at a 15 ms step "
            "records some 5 000",
            on_set=_on_trace_buffer)
define_flag("trace", False,
            "Record framework spans (engine-loop phases, train steps, "
            "wire round-trips incl. cross-wire trace-id propagation, PS "
            "ops, checkpoint save/load, serving predicts) into the "
            "in-process ring buffer. All but the per-message wire spans "
            "are also recorded, flag or no flag, while a jax.profiler "
            "capture is live. Hard-off default: the hot paths pay one "
            "check",
            on_set=_on_trace)
define_flag("log_json", False,
            "Structured logging: one JSON object per line (ts, level, "
            "msg, trace_id of the active span) instead of the human "
            "format — lets log lines join the trace timeline",
            on_set=_on_log_json)


def _on_fault_seed(v) -> None:
    try:
        spec = flag("fault_inject")
    except KeyError:
        # fault_inject is defined right after fault_seed; its own on_set
        # (re)configures with the seed set here (the env-var import path)
        return
    from paddle_tpu.core import fault

    fault.configure(spec, seed=int(v))


def _on_fault_inject(v) -> None:
    from paddle_tpu.core import fault

    fault.configure(v)


# fault_seed must be defined BEFORE fault_inject: fault.configure reads it,
# and a FLAGS_fault_inject env var fires on_set during this import.
define_flag("fault_seed", 0,
            "Seed for the deterministic per-site fault-injection RNGs "
            "(set before fault_inject)", on_set=_on_fault_seed)
define_flag("fault_inject", "",
            "Fault-injection spec, e.g. 'wire.send=1.0@2,fs.upload=0.5' "
            "(site=probability, optional @N total-fire cap). Empty string "
            "— the default — disables injection entirely; production "
            "paths then pay a single global read per site",
            on_set=_on_fault_inject)
