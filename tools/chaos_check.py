#!/usr/bin/env python
"""Chaos harness: drive the fault-tolerance layer end to end with
injection enabled and assert the recovery stats.

Scenarios (all CPU-only, single process):

1. **serving-wire**: an InferenceClient keeps answering through injected
   ``wire.send`` faults (retry/reconnect) AND through a real
   kill-and-restart of the server on the same port.
2. **checkpoint**: a corrupted latest step (bit-flip + truncation) rolls
   back to the newest verifiable step on load.
3. **elastic-resume**: a TrainEpochRange run crashed by an injected
   ``ckpt.save`` fault resumes from the previous verifiable step.
4. **overload**: with ``wire_max_inflight=1`` a concurrent infer burst is
   shed with the retryable status code 2, every client succeeds after
   backoff, the health op answers throughout, and ``drain()`` finishes
   in-flight work before severing.
5. **obs**: with ``FLAGS_trace`` on, a wire exchange under fault
   injection + an admission-cap shed records spans for the round-trip,
   the retries, and the shed waits — one trace id joins client and
   server — and the Chrome export parses as valid JSON.
6. **serving-routed**: one of three replicas is killed under routed,
   dynamically-batched load — zero idempotent requests are lost (the
   router fails them over to the survivors), router membership converges
   to mark the dead replica unhealthy, and cross-request batching
   demonstrably coalesced (fewer batches than batched requests).
7. **gen-engine**: three token streams share a continuous-batching
   GenerationEngine; one client is killed mid-stream (socket dropped, no
   cancel) — the poll TTL reclaims its slot, the surviving streams
   finish byte-identical to solo ``generate()``, a new generation is
   admitted into the reclaimed slot, and the ``gen/*`` counters stay
   consistent.
8. **gen-paged**: the paged engine (``FLAGS_gen_paged`` geometry: small
   pages, chunked prefill, prefix cache) under a client kill
   mid-chunked-prefill — the TTL reaps the victim BEFORE its prefill
   completes, every reserved page returns to the pool (no leaks: after
   the survivors finish and the prefix cache drains, the pool is back
   to full), survivors stay byte-identical to solo ``generate()``, and
   a prefix-sharing readmit lands in the reclaimed pages.
9. **control-plane**: (a) a subprocess replica is SIGKILLed right after
   joining a controller-driven scale-up, under live routed traffic —
   zero idempotent requests are lost and the controller's reconcile
   replaces the dead replica (typed ``replace`` decision); (b) a
   scale-down victim carrying a LIVE session-pinned generation is
   sticky-drained — the stream finishes byte-identical to solo
   ``generate()`` on the cordoned replica, zero ``GenerationFailed``,
   the drain is clean (not deadline-forced), and only then does the
   replica stop.
10. **gen-resilience**: (a) the subprocess replica holding a LIVE
    greedy stream is SIGKILLed under routed load — with a resume
    budget the stream replays prompt + delivered tokens onto the
    survivor and completes byte-identical to an uninterrupted solo
    ``generate()``, zero ``GenerationFailed`` surfaces, and the
    survivor's page pool drains back to full (zero leaked pages);
    (b) a poison request that traps an engine is quarantined by crash
    fingerprint — the typed ``RequestQuarantined`` surfaces through
    the resuming client and the second replica never crashes.
11. **gen-spec**: the subprocess replica holding a LIVE *speculating*
    stream (paged engine, ``--gen-spec-k 4`` n-gram drafter) is
    SIGKILLed — the stream resumes on the (also speculating) survivor
    byte-identical to solo ``generate()`` (``stream_resumes>=1``), the
    survivor's page pool drains back to full despite speculative
    rollback traffic, and health ships the acceptance stats.
12. **gen-sharded**: the tp=2 MESH-SHARDED subprocess replica
    (``--mesh-tp 2``: params Megatron-split, KV pool sharded on the
    KV-head axis over 2 virtual devices) holding a live stream is
    SIGKILLed under routed load — the stream resumes byte-identical on
    an UNSHARDED survivor (cross-layout determinism: the wire carries
    tokens + RNG position, never device layout), and the sharded
    replica's health shipped the ``device`` block (mesh {'tp': 2},
    per-device KV bytes half the unsharded pool).
13. **obs-fleet**: a TRACED stream (``FLAGS_trace`` inherited by the
    subprocess replicas) is SIGKILLed mid-flight and resumes on the
    survivor under the SAME stream trace id — the victim's span buffer,
    scraped moments before the kill, merges with the survivor's
    (scraped after completion) into one Chrome trace whose
    cross-endpoint stream count is >= 1 and whose merged timeline ends
    in the survivor's ``gen/retire reason=complete``; meanwhile a
    MetricsHub fed from routed ``health`` keeps answering windowed
    queries through the membership churn and prunes the dead replica.
14. **gen-disagg**: two DECODE-tier subprocess replicas (``--role
    decode --kv-store``) share one spill root; the replica holding a
    live stream whose page-aligned prompt was prefilled-and-published
    is SIGKILLed — the stream resumes byte-identical on the other
    decode replica via KV FETCH (``fetched_pages>=1``) with ZERO
    recomputed prefill tokens (``prefill_recomputed==0``: failover
    upgraded from token replay to page transfer) and zero leaked pages
    on the survivor.
15. **kv-campaign**: a seeded RANDOMIZED campaign over the KV failure
    domain — each scenario draws a store topology (shared spill / one
    shared store / peer tier), a producer/consumer role pair, hardening
    flags (fetch deadline, hedge, breaker), and a 1-3-site fault spec
    from the KV path, then asserts the invariants that must hold no
    matter what the faults did: streams byte-identical to solo
    ``generate()``, zero leaked pages, and every fired fault visible in
    the degradation ledger (tier errors/timeouts, ``fetch_degraded``).
    Ends with a deterministic breaker open → half-open → closed
    lifecycle check and a no-hot-path-flag-reads defaults check.
    ``--campaign N [--seed S]`` runs an N-scenario campaign standalone
    (defaults checks + campaign only).
16. **sparse-serve**: a PS-backed sparse-serving replica is SIGKILLed
    mid-version-rollover under routed load (two ``--emb-ps`` subprocess
    replicas over one PS fleet; the trainer publishes v1 right before
    the kill) — zero requests dropped (idempotent infers fail over),
    zero responses mixing two versions' rows, the survivor converges
    to the published version on its health tick, zero stale serves.
17. **control-ha**: the ACTIVE controller of an HA pair dies silently
    mid-flight (its last acts: a journaled-but-unfinished sticky drain
    and a spawn intent that never reported an endpoint) while a
    subprocess replica holds a LIVE token stream — the standby holds
    while the lease is live, claims it within one TTL of the silence
    (term bumped), replays the journal to the EXACT managed set,
    ADOPTS the live orphans (zero double-spawns; the in-flight stream
    rides through the takeover byte-identical to solo ``generate()``),
    surfaces the lost spawn intent, resumes the journaled drain clean,
    and fences the zombie leader's queued scale-up as a typed
    ``fenced`` decision that never executes.

Also asserts the production posture: every fault/retry/overload flag
defaults to hard-off/zero-cost (including the ``gen_spec_*`` family:
speculation is opt-in; the unflagged decode path is byte-identical to
the pre-speculation build — and ``gen_mesh_tp``: no mesh is built by
default, the engine's device layout is the identity and every compiled
entry point is the plain single-device jit).

Usage: ``JAX_PLATFORMS=cpu python tools/chaos_check.py`` for the full
suite, ``... chaos_check.py NAME [NAME ...]`` (e.g. ``control-ha``)
for the named scenarios only (defaults checks always run), or
``... chaos_check.py --campaign N [--seed S]`` for an
N-scenario randomized KV campaign standalone. Exits nonzero
(with a JSON report on stdout) if any recovery path or stat fails — a
scenario that raises is recorded as a failed check, never a bare
traceback, so the harness is CI-runnable as-is.
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

import paddle_tpu                                        # noqa: E402
from paddle_tpu import io, nn                            # noqa: E402
from paddle_tpu.core import fault, monitor, trace        # noqa: E402
from paddle_tpu.core.flags import get_flags, set_flags   # noqa: E402

CHECKS: list[tuple[str, bool, str]] = []


def check(name: str, ok: bool, detail: str = "") -> None:
    CHECKS.append((name, bool(ok), detail))


def check_defaults_off() -> None:
    f = get_flags(["fault_inject", "fault_seed", "wire_retries",
                   "wire_timeout_s", "ckpt_manifest"])
    check("defaults/injection_off", f["fault_inject"] == ""
          and not fault.enabled(), str(f))
    t = get_flags(["trace", "log_json"])
    check("defaults/trace_off", not t["trace"] and not trace.recording()
          and not t["log_json"], str(t))
    check("defaults/deadline_finite", f["wire_timeout_s"] > 0, str(f))
    o = get_flags(["wire_max_inflight", "wire_max_conns",
                   "wire_server_idle_s", "ps_barrier_timeout_s"])
    check("defaults/overload_caps_off", o["wire_max_inflight"] == 0
          and o["wire_max_conns"] == 0 and o["wire_server_idle_s"] == 0,
          str(o))
    check("defaults/barrier_timeout_finite",
          o["ps_barrier_timeout_s"] > 0, str(o))
    s = get_flags(["serving_batch_max", "serving_batch_timeout_s"])
    check("defaults/serving_batching_off", s["serving_batch_max"] == 0,
          str(s))
    g = get_flags(["gen_slots", "gen_poll_ttl_s"])
    check("defaults/gen_engine_off", g["gen_slots"] == 0
          and g["gen_poll_ttl_s"] > 0, str(g))
    p = get_flags(["gen_paged", "gen_pages", "gen_prefill_chunk",
                   "gen_page_tokens"])
    check("defaults/gen_paged_off", not p["gen_paged"]
          and p["gen_pages"] == 0 and p["gen_prefill_chunk"] == 0
          and p["gen_page_tokens"] > 0, str(p))
    mq = get_flags(["serving_batch_min_queue"])
    check("defaults/batch_watermark_sane",
          mq["serving_batch_min_queue"] >= 0, str(mq))
    cpl = get_flags(["control_max_replicas", "control_warm_models",
                     "control_interval_s", "control_cooldown_s",
                     "control_drain_s", "control_breach_ticks",
                     "control_idle_ticks"])
    check("defaults/control_plane_off",
          cpl["control_max_replicas"] == 0        # autoscaling off
          and cpl["control_warm_models"] == 0     # eviction off
          and cpl["control_drain_s"] > 0 and cpl["control_cooldown_s"] > 0
          and cpl["control_breach_ticks"] >= 1
          and cpl["control_idle_ticks"] >= cpl["control_breach_ticks"],
          str(cpl))
    rz = get_flags(["gen_resume_budget", "gen_quarantine_after",
                    "gen_engine_rebuilds", "gen_watchdog_s",
                    "control_spawn_breaker", "control_spawn_backoff_s"])
    check("defaults/gen_resilience_off",
          rz["gen_resume_budget"] == 0            # no stream resumption
          and rz["gen_quarantine_after"] == 0     # no quarantine books
          and rz["gen_engine_rebuilds"] == 0      # trap still breaks
          and rz["gen_watchdog_s"] == 0           # no watchdog thread
          and rz["control_spawn_breaker"] == 0    # spawner never skipped
          and rz["control_spawn_backoff_s"] > 0,  # sane base when opted in
          str(rz))
    sk = get_flags(["gen_spec_k", "gen_spec_mode", "gen_spec_ngram",
                    "gen_spec_shed_occupancy"])
    check("defaults/gen_spec_off",
          sk["gen_spec_k"] == 0                   # no speculation at all
          and sk["gen_spec_mode"] == "ngram"      # weight-free drafter
          and sk["gen_spec_ngram"] >= 1           # sane when opted in
          and 0.0 <= sk["gen_spec_shed_occupancy"] <= 1.0,
          str(sk))
    mt = get_flags(["gen_mesh_tp"])
    check("defaults/gen_mesh_off",
          mt["gen_mesh_tp"] == 0,                 # no mesh, identity
          str(mt))                                # layout, plain jit
    ob = get_flags(["trace", "control_slo_budget",
                    "control_burn_fast_ticks", "control_burn_slow_ticks",
                    "control_burn_threshold"])
    check("defaults/obs_burn_off",
          not ob["trace"]                         # spans only in a capture
          and ob["control_slo_budget"] > 0        # sane when opted in
          and 1 <= ob["control_burn_fast_ticks"]
          <= ob["control_burn_slow_ticks"]
          and ob["control_burn_threshold"] > 0, str(ob))
    led = get_flags(["gen_ledger", "gen_ledger_records"])
    check("defaults/gen_ledger_off",
          not led["gen_ledger"]                   # no ledger, no meter
          and led["gen_ledger_records"] > 0,      # sane when opted in
          str(led))
    hl = get_flags(["gen_device_pt", "gen_async_depth"])
    check("defaults/gen_hotloop_off",
          not hl["gen_device_pt"]                 # host page table
          and hl["gen_async_depth"] == 0,         # synchronous loop
          str(hl))
    kvs = get_flags(["gen_kv_store", "gen_role", "gen_kv_store_pages",
                     "gen_kv_spill_dir"])
    check("defaults/gen_kvstore_off",
          not kvs["gen_kv_store"]                 # no store, no tiers
          and kvs["gen_role"] == "both"           # no role split
          and kvs["gen_kv_store_pages"] > 0       # sane when opted in
          and kvs["gen_kv_spill_dir"] == "",      # no spill tier
          str(kvs))
    kvh = get_flags(["gen_kv_fetch_timeout_s", "gen_kv_admit_timeout_s",
                     "gen_kv_hedge_ms", "gen_kv_breaker",
                     "gen_kv_breaker_backoff_s", "gen_kv_peers"])
    check("defaults/gen_kv_hardening_off",
          kvh["gen_kv_fetch_timeout_s"] == 0.0    # unbounded, inline
          and kvh["gen_kv_admit_timeout_s"] == 0.0
          and kvh["gen_kv_hedge_ms"] == 0.0       # no hedging
          and kvh["gen_kv_breaker"] == 0          # no breakers
          and kvh["gen_kv_breaker_backoff_s"] > 0  # sane when opted in
          and kvh["gen_kv_peers"] == "",          # no peer tier
          str(kvh))
    # behavior at defaults: the store is THREAD-FREE — hedge/deadline
    # machinery must not exist to pay for, cold fetches are inline
    import threading as _threading

    from paddle_tpu.serving.kvstore import KVStore as _KVStore

    with tempfile.TemporaryDirectory(prefix="ptpu_kvdef_") as d:
        st = _KVStore(pages=4, spill=d)
        spawned = []
        real_thread = _threading.Thread

        def _spy_thread(*a, **k):
            spawned.append(k.get("name", "?"))
            return real_thread(*a, **k)

        _threading.Thread = _spy_thread
        try:
            st.put("k", b"x" * 8)
            got = st.get("k")
            miss = st.get("nope")
        finally:
            _threading.Thread = real_thread
            st.close()
        check("defaults/gen_kv_hardening_threadfree",
              not spawned and got == b"x" * 8 and miss is None,
              f"spawned={spawned}")

    haf = get_flags(["control_ha_lease_dir", "control_ha_lease_ttl_s",
                     "control_ha_holder", "control_ha_compact_records"])
    # behavior at defaults: the flag-default controller constructs NO
    # lease, NO journal, NO fencing wrapper, NO wire service, spawns no
    # thread, and writes no HA file — the pre-HA controller, byte for
    # byte (the HA flags are read once, at construction)
    from paddle_tpu.serving import InProcSpawner as _IPS
    from paddle_tpu.serving import ServingController as _SC
    from paddle_tpu.serving.ha import FencedSpawner as _FS

    spawned = []
    real_thread = _threading.Thread

    def _spy_thread(*a, **k):
        spawned.append(k.get("name", "?"))
        return real_thread(*a, **k)

    from paddle_tpu.serving import RoutedClient as _RC

    # probe-less router: its health-probe thread is default serving
    # behavior, not HA's — the spy must only see what HA would add
    router = _RC(probe_interval_s=0)
    _threading.Thread = _spy_thread
    try:
        ctl = _SC(_IPS(io.InferenceServer), router=router,
                  interval_s=0, min_replicas=0)
        ctl.start()
        for _ in range(3):
            d = ctl.tick()
        dump = ctl.control_dump()
        ctl.close()
    finally:
        _threading.Thread = real_thread
        router.close()
    check("defaults/control_ha_off",
          haf["control_ha_lease_dir"] == ""
          and haf["control_ha_holder"] == ""
          and haf["control_ha_lease_ttl_s"] == 3.0    # sane opt-in TTL
          and haf["control_ha_compact_records"] == 256
          and ctl._lease is None and ctl._journal is None
          and ctl._service is None
          and not isinstance(ctl._spawner, _FS)       # unwrapped
          and d.action == "hold" and "leader" not in dump
          and not spawned,
          f"flags={haf} spawned={spawned}")
    sc = get_flags(["gen_sched", "gen_sched_w_interactive",
                    "gen_sched_w_batch", "gen_sched_w_best_effort",
                    "gen_sched_quotas", "gen_sched_chunk",
                    "gen_sched_headroom"])
    check("defaults/gen_sched_off",
          not sc["gen_sched"]                     # no scheduler object
          and sc["gen_sched_quotas"] == ""        # no quota map
          # sane class-weight ordering when opted in
          and sc["gen_sched_w_interactive"] >= sc["gen_sched_w_batch"]
          >= sc["gen_sched_w_best_effort"] > 0
          and sc["gen_sched_chunk"] > 0
          and sc["gen_sched_headroom"] >= 0,
          str(sc))
    se = get_flags(["serving_emb", "serving_emb_cache_rows",
                    "serving_emb_ttl_s"])
    # behavior at defaults: attach_embeddings is a None no-op — the
    # server constructs NO tier, polls no versions, ships no "emb"
    # health block (the flag is read once, at server construction)
    _srv = io.InferenceServer({})
    check("defaults/serving_emb_off",
          not se["serving_emb"]
          and se["serving_emb_cache_rows"] > 0    # sane when opted in
          and se["serving_emb_ttl_s"] == 0.0      # no TTL by default
          and _srv.attach_embeddings(None) is None
          and _srv._emb_tier is None,
          str(se))


def scenario_serving_wire(tmp: str) -> None:
    paddle_tpu.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    path = os.path.join(tmp, "mlp")
    io.save_inference_model(path, net, [np.zeros((2, 4), np.float32)])

    srv = io.InferenceServer({"m": path}).start()
    port = srv.port
    client = io.InferenceClient(srv.endpoint, timeout=10.0)
    x = np.ones((2, 4), np.float32)
    monitor.reset_stats("wire/")
    monitor.reset_stats("fault/")

    # injected send faults ride the retry path transparently
    with fault.inject_faults({"wire.send": (1.0, 2)}, seed=7):
        (y1,) = client.infer("m", x)
    check("wire/injected_faults_fired",
          monitor.get_stat("fault/injected/wire.send") == 2)
    check("wire/retries_recovered", monitor.get_stat("wire/retries") >= 2)

    # real kill + restart on the same port
    srv.stop()
    srv2 = io.InferenceServer({"m": path}, port=port).start()
    (y2,) = client.infer("m", x)
    check("wire/survives_restart", np.allclose(y1, y2))
    check("wire/reconnects", monitor.get_stat("wire/reconnects") >= 1)
    client.stop_server()
    client.close()
    srv2.stop()


def _tpl(v=0.0, step=0):
    return {"w": jnp.full((8, 8), float(v)), "step": jnp.asarray(int(step))}


def scenario_checkpoint(tmp: str) -> None:
    d = os.path.join(tmp, "ck")
    for s in (1, 2, 3):
        io.save_checkpoint(_tpl(s, s), d, step=s)
    io.checkpoint.wait_until_finished(d)
    # corrupt the latest step: flip + truncate every substantial file
    for root, _, files in os.walk(os.path.join(d, "3")):
        for name in files:
            p = os.path.join(root, name)
            size = os.path.getsize(p)
            if size < 8:
                continue
            with open(p, "r+b") as f:
                f.seek(size // 2)
                b = f.read(1)
                f.seek(size // 2)
                f.write(bytes([(b[0] ^ 0xFF) if b else 0xFF]))
                f.truncate(max(size // 2, 8))
    monitor.reset_stats("ckpt/")
    restored, used = io.load_checkpoint(_tpl(), d, return_step=True)
    check("ckpt/fell_back_to_good_step",
          used == 2 and float(restored["w"][0, 0]) == 2.0)
    check("ckpt/rollbacks_stat", monitor.get_stat("ckpt/rollbacks") >= 1)
    check("ckpt/corrupt_steps_stat",
          monitor.get_stat("ckpt/corrupt_steps") >= 1)


def scenario_elastic_resume(tmp: str) -> None:
    d = os.path.join(tmp, "run")
    monitor.reset_stats("fault/")
    r = io.TrainEpochRange(6, d, state=_tpl(-1, -1))
    crashed = False
    try:
        for epoch in r:
            r.state = _tpl(epoch, epoch)
            if epoch == 2:
                fault.configure({"ckpt.save": 1.0}, seed=0)
    except fault.InjectedFault:
        crashed = True
    finally:
        fault.reset()
    io.checkpoint.wait_until_finished(d)
    r2 = io.TrainEpochRange(6, d, state=_tpl())
    check("resume/crashed_as_injected", crashed
          and monitor.get_stat("fault/injected/ckpt.save") == 1)
    check("resume/rolled_to_verifiable",
          r2.resumed and r2.start_epoch == 2
          and int(r2.state["step"]) == 1)


def scenario_overload(tmp: str) -> None:
    import threading
    import time

    class _SlowPredictor:
        input_specs = output_specs = []

        def run(self, x):
            time.sleep(0.05)
            return np.asarray(x)

    srv = io.InferenceServer()
    srv.add_model("slow", _SlowPredictor())
    srv.start()
    monitor.reset_stats("wire/")
    set_flags({"wire_max_inflight": 1, "wire_backoff_max_s": 0.2})
    try:
        x = np.ones((4,), np.float32)
        results, errors = [], []
        gate = threading.Barrier(6)

        def worker():
            c = io.InferenceClient(srv.endpoint, timeout=10.0, retries=32)
            try:
                gate.wait()
                results.append(c.infer("slow", x)[0])
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")
            finally:
                c.close()

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        check("overload/all_recovered_after_shed",
              not errors and len(results) == 6, repr(errors[:2]))
        check("overload/shed_fired", monitor.get_stat("wire/shed") >= 1
              and monitor.get_stat("wire/shed_server") >= 1)
        h = srv.health()
        check("overload/health_op", h["status"] == "ok"
              and h["inflight"] == 0 and h["max_inflight"] == 1, str(h))
    finally:
        set_flags({"wire_max_inflight": 0, "wire_backoff_max_s": 2.0})
    check("overload/drain_clean", srv.drain(5.0) is True)


def scenario_obs(tmp: str) -> None:
    import threading
    import time

    class _SlowPredictor:
        input_specs = output_specs = []

        def run(self, x):
            time.sleep(0.03)
            return np.asarray(x)

    srv = io.InferenceServer()
    srv.add_model("slow", _SlowPredictor())
    srv.start()
    set_flags({"trace": True, "wire_backoff_max_s": 0.2})
    monitor.reset_stats("wire/")
    trace.clear()
    try:
        x = np.ones((4,), np.float32)
        client = io.InferenceClient(srv.endpoint, timeout=10.0, retries=32)

        # 1. retries under fault injection leave wire/retry_wait spans
        with fault.inject_faults({"wire.send": (1.0, 2)}, seed=7):
            client.infer("slow", x)

        # 2. an admission-cap burst leaves wire/shed_wait spans
        set_flags({"wire_max_inflight": 1})
        gate = threading.Barrier(3)
        errors = []

        def worker():
            c = io.InferenceClient(srv.endpoint, timeout=10.0, retries=32)
            try:
                gate.wait()
                c.infer("slow", x)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")
            finally:
                c.close()

        threads = [threading.Thread(target=worker) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        set_flags({"wire_max_inflight": 0})

        spans = trace.get_spans()
        names = [s["name"] for s in spans]
        check("obs/burst_recovered", not errors, repr(errors[:2]))
        check("obs/retry_spans_recorded",
              names.count("wire/retry_wait") >= 2, str(names))
        check("obs/shed_spans_recorded", "wire/shed_wait" in names,
              str(names))
        clients = [s for s in spans if s["name"] == "wire/serving.infer"]
        servers = [s for s in spans
                   if s["name"] == "wire/InferenceServer.infer"]
        joined = {s["trace_id"] for s in clients} & {
            s["trace_id"] for s in servers}
        check("obs/cross_wire_trace_joined", len(joined) >= 1,
              f"{len(clients)} client / {len(servers)} server spans")
        check("obs/predict_spans_nested",
              any(s["name"] == "serving/predict" for s in spans))

        out = os.path.join(tmp, "chaos_trace.json")
        trace.export_chrome(out)
        with open(out) as f:
            doc = json.load(f)
        check("obs/chrome_export_parses",
              len(doc["traceEvents"]) >= len(spans))
        prom = monitor.export_prometheus("wire/")
        check("obs/prometheus_quantiles",
              'quantile="0.99"' in prom and "wire_op_latency_s" in prom)
        client.stop_server()
        client.close()
    finally:
        set_flags({"trace": False, "wire_max_inflight": 0,
                   "wire_backoff_max_s": 2.0})
        srv.stop()


def scenario_serving_routed(tmp: str) -> None:
    """Replica kill under routed + dynamically-batched load: all
    idempotent requests complete via failover, membership converges."""
    import threading
    import time

    from paddle_tpu.serving import RoutedClient

    paddle_tpu.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    path = os.path.join(tmp, "dyn_mlp")
    io.save_inference_model(path, net, [np.zeros((2, 4), np.float32)],
                            dynamic_batch=True)
    servers = [io.InferenceServer({"m": path}).start() for _ in range(3)]
    monitor.reset_stats("serving/")
    set_flags({"serving_batch_max": 8, "serving_batch_timeout_s": 0.002})
    rc = RoutedClient([s.endpoint for s in servers],
                      probe_interval_s=0.25, timeout=10.0)
    results: dict = {}
    errors: list = []
    try:
        # stop() spends ~0.5s shutting the accept loop down before it
        # severs live conns — keep traffic flowing well past the sever
        stop_at = time.perf_counter() + 1.8
        killer = threading.Timer(0.1, servers[1].stop)
        killer.start()
        gate = threading.Barrier(6)

        def worker(i):
            try:
                gate.wait()
                j = 0
                while time.perf_counter() < stop_at:
                    x = np.full((1, 4), float(i * 1000 + j), np.float32)
                    results[(i, j)] = (float(x[0, 0]), rc.infer("m", x)[0])
                    j += 1
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        killer.join()
        ref = io.Predictor(path)
        bad = sum(
            not np.allclose(
                y, np.asarray(ref.run(np.full((1, 4), v, np.float32))),
                rtol=1e-5, atol=1e-6)
            for v, y in results.values())
        check("routed/zero_lost_requests",
              not errors and len(results) > 10 and bad == 0,
              f"errors={errors[:2]} n={len(results)} bad={bad}")
        check("routed/failover_fired",
              monitor.get_stat("serving/router/failovers") >= 1)
        check("routed/batching_coalesced",
              0 < monitor.get_stat("serving/batches")
              < monitor.get_stat("serving/batched_requests"),
              str(monitor.export_stats("serving/")))
        # membership convergence (probe- or traffic-driven)
        deadline = time.time() + 5.0
        members = rc.members()
        while time.time() < deadline:
            members = rc.members()
            health = {m["endpoint"]: m["healthy"] for m in members}
            if (not health[servers[1].endpoint]
                    and health[servers[0].endpoint]
                    and health[servers[2].endpoint]):
                break
            time.sleep(0.05)
        health = {m["endpoint"]: m["healthy"] for m in members}
        check("routed/membership_converged",
              not health[servers[1].endpoint]
              and health[servers[0].endpoint]
              and health[servers[2].endpoint], str(members))
    finally:
        set_flags({"serving_batch_max": 0,
                   "serving_batch_timeout_s": 0.005})
        rc.close()
        for s in servers:
            s.stop()


def scenario_gen_engine(tmp: str) -> None:
    """Client killed mid-stream under the continuous-batching engine:
    its slot is TTL-reclaimed, surviving streams are byte-identical to
    solo generate(), and a new generation lands in the freed slot."""
    import threading
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import GenerationEngine

    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)
    monitor.reset_stats("gen/")
    # pace the loop so "mid-stream" is a real window, and shorten the
    # poll TTL so the dropped client's slot reclaims within the check
    engine = GenerationEngine(model, slots=3, max_len=32, queue_max=4,
                              ttl_s=0.6, step_wait_s=0.02)
    srv = io.InferenceServer().start()
    srv.add_generator("llm", engine)
    rs = np.random.RandomState(3)
    prompts = rs.randint(0, 96, (3, 6)).astype(np.int32)
    refs = np.asarray(generate(model, jnp.asarray(prompts), 12))[:, 6:]
    survivors: dict = {}
    errors: list = []
    try:
        victim = io.InferenceClient(srv.endpoint)
        vic_id = victim.generate_start("llm", prompts[0], 12)
        victim.generate_poll("llm", vic_id, wait_s=0.1)

        def worker(i):
            try:
                c = io.InferenceClient(srv.endpoint)
                survivors[i] = list(c.generate("llm", prompts[i], 12))
                c.close()
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in (1, 2)]
        for t in threads:
            t.start()
        # kill the victim's connection mid-stream: no cancel, no close
        # handshake — only the poll TTL can reclaim its slot
        victim.close()
        for t in threads:
            t.join(timeout=30)
        check("gen/survivors_byte_identical",
              not errors and len(survivors) == 2
              and all(np.array_equal(np.asarray(survivors[i], np.int32),
                                     refs[i]) for i in (1, 2)),
              f"errors={errors[:2]}")

        deadline = time.time() + 5.0
        st = engine.stats()
        while time.time() < deadline:
            st = engine.stats()
            if st["active"] == 0 and st["generations"] == 0:
                break
            time.sleep(0.05)
        check("gen/victim_slot_reclaimed",
              st["active"] == 0 and st["generations"] == 0
              and monitor.get_stat("gen/evictions") >= 1, str(st))

        # freed capacity admits new work; counters stay consistent
        c = io.InferenceClient(srv.endpoint)
        toks = list(c.generate("llm", prompts[0], 12))
        check("gen/readmit_after_reclaim",
              np.array_equal(np.asarray(toks, np.int32), refs[0]))
        h = c.health()
        c.close()
        emitted = sum(len(v) for v in survivors.values()) + len(toks)
        check("gen/counters_consistent",
              monitor.get_stat("gen/tokens") >= emitted
              and h["generators"]["llm"]["active"] == 0,
              f"tokens={monitor.get_stat('gen/tokens')} "
              f"emitted>={emitted} health={h.get('generators')}")
    finally:
        srv.stop()     # closes the engine too


def scenario_gen_paged(tmp: str) -> None:
    """Client killed mid-CHUNKED-PREFILL under the paged engine: the
    poll TTL reaps it before its prefill completes, all its reserved
    pages return to the pool, survivors are byte-identical to solo
    generate(), and a shared-prefix readmit reuses the cached pages."""
    import threading
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import GenerationEngine

    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)
    monitor.reset_stats("gen/")
    # 4-token pages + 1-token chunks + a paced loop: the victim's
    # 56-token prompt spans dozens of loop iterations, so a 0.45s TTL
    # fires while it is demonstrably mid-prefill
    engine = GenerationEngine(model, slots=3, max_len=64, queue_max=8,
                              ttl_s=0.45, step_wait_s=0.02, paged=True,
                              page_tokens=4, prefill_chunk=1,
                              prefix_cache=True)
    srv = io.InferenceServer().start()
    srv.add_generator("pllm", engine)
    total = engine.stats()["pages"]
    rs = np.random.RandomState(5)
    # warm the prefill-chunk + decode compiles so the TTL races real
    # scheduling, not XLA compilation, then drain the prefix cache
    wid = engine.start(rs.randint(0, 96, (5,)).astype(np.int32), 2)
    n = 0
    while True:
        doc = engine.poll(wid, start=n, wait_s=1.0)
        n += len(doc["tokens"])
        if doc["done"]:
            break
    engine.clear_prefix_cache()
    shared_prefix = rs.randint(0, 96, (9,)).astype(np.int32)
    tails = rs.randint(0, 96, (2, 3)).astype(np.int32)
    prompts = [np.concatenate([shared_prefix, t]) for t in tails]
    refs = [np.asarray(generate(model, p[None], 20))[0, len(p):]
            for p in prompts]
    victim_prompt = rs.randint(0, 96, (56,)).astype(np.int32)
    survivors: dict = {}
    errors: list = []
    try:
        # survivors first: their decode steps pace the loop
        def worker(i):
            try:
                c = io.InferenceClient(srv.endpoint)
                survivors[i] = list(c.generate("pllm", prompts[i], 20))
                c.close()
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in (0, 1)]
        for t in threads:
            t.start()
        victim = io.InferenceClient(srv.endpoint)
        vic_id = victim.generate_start("pllm", victim_prompt, 6)
        # drop the socket with no cancel: only the TTL can reap it
        victim.close()
        # watch the victim's chunked prefill advance until the reap
        # pops it from the engine; the last observation tells whether
        # the TTL really fired mid-prefill
        deadline = time.time() + 10.0
        last_pos, completed_prefill = 0, False
        while time.time() < deadline:
            with engine._cond:
                g = engine._gens.get(vic_id)
                if g is None:
                    break                    # reaped (and purged)
                if g.slot is not None and not g.prefilling:
                    completed_prefill = True
                    break                    # outlived the TTL: invalid
                last_pos = max(last_pos, g.prefill_pos)
            time.sleep(0.01)
        check("gen_paged/reaped_mid_prefill",
              not completed_prefill and g is None
              and 0 < last_pos < victim_prompt.size,
              f"last_pos={last_pos} completed={completed_prefill}")
        for t in threads:
            t.join(timeout=30)
        check("gen_paged/survivors_byte_identical",
              not errors and len(survivors) == 2
              and all(np.array_equal(np.asarray(survivors[i], np.int32),
                                     refs[i]) for i in (0, 1)),
              f"errors={errors[:2]}")
        check("gen_paged/eviction_counted",
              monitor.get_stat("gen/evictions") >= 1)

        # shared-prefix readmit into the reclaimed pages: prompts share
        # a 9-token prefix -> 2 cached 4-token pages
        c = io.InferenceClient(srv.endpoint)
        toks = list(c.generate("pllm", prompts[0], 20))
        c.close()
        check("gen_paged/readmit_after_reclaim",
              np.array_equal(np.asarray(toks, np.int32), refs[0]))
        check("gen_paged/prefix_shared",
              monitor.get_stat("gen/prefix_hits") >= 1
              and monitor.get_stat("gen/prefix_tokens_saved") >= 8,
              str(monitor.export_stats("gen/")))

        # no leaks: once the prefix cache drains, the pool is FULL
        deadline = time.time() + 5.0
        st = engine.stats()
        while time.time() < deadline:
            engine.clear_prefix_cache()
            st = engine.stats()
            if st["pages_free"] == total and st["active"] == 0:
                break
            time.sleep(0.05)
        check("gen_paged/pool_returns_to_full",
              st["pages_free"] == total and st["active"] == 0
              and st["prefix_entries"] == 0, f"{st} total={total}")
    finally:
        srv.stop()     # closes the engine too


def scenario_control_plane(tmp: str) -> None:
    """(a) SIGKILL a subprocess replica right after a controller
    scale-up, under routed traffic: zero lost requests, reconcile
    replaces it. (b) Sticky-drain a scale-down victim with a LIVE
    pinned generation: byte-identical stream, zero GenerationFailed,
    clean (unforced) drain."""
    import threading
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import (
        InProcSpawner, ServingController, SubprocessSpawner,
    )

    # -- (a) replica killed mid-scale-up (subprocess, real SIGKILL) -----
    paddle_tpu.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    path = os.path.join(tmp, "ctl_mlp")
    io.save_inference_model(path, net, [np.zeros((2, 4), np.float32)],
                            dynamic_batch=True)
    ref = io.Predictor(path)
    monitor.reset_stats("control/")
    spawner = SubprocessSpawner({"m": path})
    ctl = ServingController(spawner, interval_s=0, min_replicas=1,
                            max_replicas=3, breach_ticks=1,
                            cooldown_s=0.0)
    results: dict = {}
    errors: list = []
    try:
        ctl.start()
        stop_at = time.perf_counter() + 3.0

        def worker(i):
            try:
                j = 0
                while time.perf_counter() < stop_at:
                    x = np.full((1, 4), float(i * 1000 + j), np.float32)
                    results[(i, j)] = (float(x[0, 0]),
                                       ctl.router.infer("m", x)[0])
                    j += 1
                    time.sleep(0.005)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        before = set(ctl.router.endpoints())
        ctl.scale_to(2, reason="chaos scale-up")
        joined = next(iter(set(ctl.router.endpoints()) - before))
        spawner.kill(joined)              # SIGKILL the fresh replica
        time.sleep(0.3)
        ctl.tick()                        # reconcile: replace the corpse
        for t in threads:
            t.join(timeout=60)
        bad = sum(
            not np.allclose(
                y, np.asarray(ref.run(np.full((1, 4), v, np.float32))),
                rtol=1e-5, atol=1e-6)
            for v, y in results.values())
        check("control/zero_lost_through_kill",
              not errors and len(results) > 20 and bad == 0,
              f"errors={errors[:2]} n={len(results)} bad={bad}")
        eps = ctl.router.endpoints()
        check("control/dead_replica_replaced",
              len(eps) == 2 and joined not in eps, str(eps))
        acts = [d["action"] for d in ctl.decisions()]
        check("control/replace_decision_logged",
              "replace" in acts and "scale_up" in acts, str(acts))
    finally:
        ctl.close()

    # -- (b) sticky-drain scale-down with a live pinned generation ------
    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    def factory():
        srv = io.InferenceServer().start()
        srv.add_generator("llm", model, slots=2, max_len=32,
                          step_wait_s=0.02)
        return srv

    inproc = InProcSpawner(factory)
    ctl2 = ServingController(inproc, interval_s=0, min_replicas=1,
                             max_replicas=2, drain_s=20.0)
    try:
        ctl2.start()
        ctl2.scale_to(2, reason="chaos setup")
        rs = np.random.RandomState(9)
        prompt = rs.randint(0, 96, (5,)).astype(np.int32)
        refs = np.asarray(generate(model, prompt[None], 14))[0, 5:]
        sess = ctl2.router.session("chaos-pinned")
        it = sess.generate("llm", prompt, 14, poll_wait_s=0.05)
        toks = [next(it)]
        victim = sess.endpoint
        drained: dict = {}

        def drain():
            drained["d"] = ctl2.scale_down(victim=victim,
                                           reason="chaos drain")

        t = threading.Thread(target=drain)
        t.start()
        stream_err = None
        try:
            toks += list(it)              # rides through the drain
        except Exception as e:
            stream_err = f"{type(e).__name__}: {e}"
        t.join(timeout=60)
        d = drained.get("d")
        check("control/sticky_stream_byte_identical",
              stream_err is None
              and np.array_equal(np.asarray(toks, np.int32), refs),
              f"err={stream_err} toks={len(toks)}")
        check("control/drain_clean_and_victim_stopped",
              d is not None and d.action == "scale_down" and d.clean
              and victim not in ctl2.router.endpoints()
              and victim not in inproc.servers
              and monitor.get_stat("control/drain_forced") == 0,
              f"decision={d.as_dict() if d else None}")
        # the survivor still serves; fleet is one replica
        toks2 = list(ctl2.router.session("after-drain").generate(
            "llm", prompt, 14, poll_wait_s=0.05))
        check("control/survivor_serves_after_drain",
              len(ctl2.router.endpoints()) == 1
              and np.array_equal(np.asarray(toks2, np.int32), refs))
    finally:
        ctl2.close()


def scenario_control_ha(tmp: str) -> None:
    """The active controller of an HA pair dies silently (SIGKILL
    emulated in-process: it never ticks, renews, or closes again) with
    a live token stream on a subprocess replica, an unfinished
    journaled drain, and a spawn intent that never reported an
    endpoint. Asserts: standby holds while the lease is live; takeover
    within one TTL at term+1; journal replay reconstructs the EXACT
    managed set; live orphans adopted (zero double-spawns, the stream
    byte-identical to solo ``generate()`` across the takeover); the
    lost spawn surfaced; the drain resumed clean; the zombie's queued
    scale-up fenced at the actuator as a typed decision."""
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import ServingController, SubprocessSpawner

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)          # == every replica's weights
    monitor.reset_stats("control/")
    ha_root = os.path.join(tmp, "ha_root")
    ttl = 1.0
    # the rider stream deliberately goes unpolled across the whole
    # takeover (standby wait + adoption + a fresh subprocess spawn);
    # keep the replicas' poll TTL above that so "client paused" is not
    # mistaken for "client gone"
    os.environ["FLAGS_gen_poll_ttl_s"] = "300"
    gen_args = ("--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
                "--gen-max-len", "32", "--gen-step-wait-s", "0.05")

    def _ctl(holder):
        return ServingController(
            SubprocessSpawner(extra_args=gen_args), interval_s=0,
            min_replicas=2, max_replicas=4, drain_s=20.0,
            ha_lease_dir=ha_root, ha_lease_ttl_s=ttl, ha_holder=holder)

    c1, c2 = _ctl("primary"), _ctl("standby")
    try:
        c1.start()
        c1.tick()                          # claims term 1, bootstraps 2
        live = set(c1.router.endpoints())
        check("control/ha_leader_bootstrapped",
              c1.lease.leading and c1.lease.term == 1 and len(live) == 2,
              f"term={c1.lease.term} eps={sorted(live)}")

        rs = np.random.RandomState(61)
        prompt = rs.randint(0, 96, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 14))[0, 5:]
        sess = c1.router.session("ha-rider")
        it = sess.generate("llm", prompt, 14, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it), next(it)]        # the stream is live
        victim = next(ep for ep in live if ep != sess.endpoint)

        c1.tick()                          # one last renewal, then the
        #                                    leader dies silently. Its
        # final journaled acts: a drain begun but not finished, and a
        # spawn intent whose endpoint no one will ever learn
        c1._journal_rec("drain_begin", ep=victim)
        c1._journal_rec("spawn_intent")

        c2.start()
        d = c2.tick()
        check("control/ha_standby_holds_while_leader_live",
              d.action == "hold" and "standby" in d.reason
              and not c2.router.endpoints(), d.reason)

        time.sleep(ttl + 0.2)              # one TTL of silence
        t0 = time.monotonic()
        c2.tick()                          # claim + replay + adopt
        took = time.monotonic() - t0
        adopted = {x["endpoint"] for x in c2.decisions()
                   if x["action"] == "adopt"}
        check("control/ha_takeover_replays_exact_managed_set",
              c2.lease.leading and c2.lease.term == 2
              and adopted == live, f"term={c2.lease.term} "
              f"adopted={sorted(adopted)} expected={sorted(live)} "
              f"takeover_s={took:.2f}")
        # zero double-spawns: every live orphan was ADOPTED, never
        # respawned — the only process c2 started is the post-drain
        # bootstrap replacement, a fresh endpoint outside the old fleet
        check("control/ha_zero_double_spawns",
              set(c2._spawner.inner.procs).isdisjoint(live)
              and len(c2._spawner.inner.procs) == 1
              and sess.endpoint in c2._spawner.inner.adopted_pids
              and monitor.get_stat("control/ha_adopted") == 2,
              f"procs={list(c2._spawner.inner.procs)} "
              f"adopted={list(c2._spawner.inner.adopted_pids)}")
        acts = [x["action"] for x in c2.decisions()]
        check("control/ha_drain_resumed_clean",
              "drain_resume" in acts
              and any(x["action"] == "scale_down" and x.get("clean")
                      and x["endpoint"] == victim
                      for x in c2.decisions())
              and victim not in c2.router.endpoints()
              and monitor.get_stat("control/drain_forced") == 0,
              str(acts))
        check("control/ha_lost_spawn_surfaced",
              monitor.get_stat("control/ha_lost_spawns") == 1,
              str(monitor.get_stat("control/ha_lost_spawns")))

        err = None
        try:
            toks += list(it)               # rides through the takeover
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("control/ha_stream_byte_identical_across_takeover",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref),
              f"err={err} toks={len(toks)}")

        # the zombie: next tick deposes it; its queued scale-up is
        # fenced at the actuator — typed decision, never executed
        d = c1.tick()
        n_before = len(c1._spawner.inner.procs)
        f = c1._scale_up("zombie queued scale-up", {})
        check("control/ha_zombie_deposed_and_fenced",
              d.action == "deposed" and f.action == "fenced"
              and len(c1._spawner.inner.procs) == n_before
              and c1.decisions()[-1]["action"] == "fenced",
              f"tick={d.action} scale_up={f.action}")

        # durable truth: a fresh replay names exactly the live fleet
        from paddle_tpu.serving import FleetJournal
        st = FleetJournal(ha_root, compact_records=0).replay()
        check("control/ha_journal_names_live_fleet",
              set(st.managed) == set(c2.router.endpoints())
              and st.draining is None, str(st.as_dict()))
    finally:
        os.environ.pop("FLAGS_gen_poll_ttl_s", None)
        c1.close(stop_replicas=False)      # the corpse: fleet is c2's
        c2.close()
        for sp in (c1._spawner.inner, c2._spawner.inner):
            for ep in list(sp.procs):
                sp.kill(ep)


def scenario_gen_resilience(tmp: str) -> None:
    """(a) SIGKILL the subprocess replica holding a live greedy stream:
    with a resume budget the routed stream completes byte-identical on
    the survivor — zero GenerationFailed, zero leaked pages. (b) A
    poison request that traps an engine is quarantined typed; the
    second replica never crashes."""
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import (
        GenerationEngine, RequestQuarantined, RoutedClient,
        SubprocessSpawner,
    )

    # local reference weights: same seed + config as the --gen replicas
    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    # -- (a) SIGKILL under a live stream; resume on the survivor --------
    monitor.reset_stats("serving/router/")
    spawner = SubprocessSpawner(extra_args=(
        "--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
        "--gen-max-len", "32", "--gen-step-wait-s", "0.05",
        "--gen-paged", "--gen-page-tokens", "8"))
    eps = [spawner.spawn() for _ in range(2)]
    router = RoutedClient(eps, probe_interval_s=0)
    try:
        rs = np.random.RandomState(51)
        prompt = rs.randint(0, 96, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 12))[0, 5:]
        sess = router.session("kill-victim")
        it = sess.generate("llm", prompt, 12, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it), next(it)]          # the stream is live
        victim = sess.endpoint
        rider = router.session("rider")      # concurrent routed load
        it2 = rider.generate("llm", prompt, 12, poll_wait_s=0.05,
                             resume_budget=2)
        toks2 = [next(it2)]
        spawner.kill(victim)                 # real SIGKILL, no goodbye
        err = None
        try:
            toks += list(it)                 # resumes on the survivor
            toks2 += list(it2)
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("genres/stream_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref)
              and np.array_equal(np.asarray(toks2, np.int32), ref),
              f"err={err} toks={len(toks)}/{len(toks2)}")
        check("genres/resume_counted_no_failure_surfaced",
              err is None
              and monitor.get_stat("serving/router/stream_resumes") >= 1
              and monitor.get_stat("serving/router/resume_exhausted")
              == 0,
              str(monitor.export_stats("serving/router/")))
        survivor = next(ep for ep in eps if ep != victim)
        g = {}
        with io.InferenceClient(survivor, timeout=5.0) as c:
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                g = c.health()["generators"]["llm"]
                if (g.get("active") == 0 and g.get("pages_free", 0)
                        + g.get("prefix_entries", 0) == g.get("pages")):
                    break
                time.sleep(0.1)
        check("genres/zero_leaked_pages_on_survivor",
              g.get("pages_free", -1) + g.get("prefix_entries", 0)
              == g.get("pages"), str(g))
    finally:
        router.close()
        for ep in list(spawner.procs):
            spawner.kill(ep)

    # -- (b) quarantined poison never crashes a second replica ----------
    servers, engines = [], []
    for _ in range(2):
        eng = GenerationEngine(model, slots=1, max_len=32, rebuilds=4,
                               quarantine_after=1)
        srv = io.InferenceServer().start()
        srv.add_generator("llm", eng)
        servers.append(srv)
        engines.append(eng)
    router2 = RoutedClient([s.endpoint for s in servers],
                           probe_interval_s=0)
    try:
        rs = np.random.RandomState(52)
        poison = rs.randint(0, 96, (4,)).astype(np.int32)
        clean = rs.randint(0, 96, (4,)).astype(np.int32)
        qerr, other = None, None
        with fault.inject_faults({"engine.prefill": (1.0, 1)}):
            try:
                list(router2.session("poison").generate(
                    "llm", poison, 4, poll_wait_s=0.05, resume_budget=3))
            except RequestQuarantined as e:
                qerr = e
            except Exception as e:
                other = f"{type(e).__name__}: {e}"
        check("genres/quarantine_typed_giveup",
              qerr is not None and other is None,
              f"quarantined={qerr} other={other}")
        check("genres/second_replica_never_crashed",
              sum(e.stats()["rebuilds"] for e in engines) == 1
              and all(e.stats()["broken"] is None for e in engines),
              str([e.stats() for e in engines]))
        ref = np.asarray(generate(model, clean[None], 3))[0, 4:]
        toks = list(router2.generate("llm", clean, 3))
        check("genres/fleet_serves_after_quarantine",
              np.array_equal(np.asarray(toks, np.int32), ref),
              str(toks))
    finally:
        router2.close()
        for s in servers:
            s.stop()


def scenario_gen_spec(tmp: str) -> None:
    """SIGKILL a subprocess replica mid-stream while the stream is
    SPECULATING (paged engine, n-gram drafter): the routed resume
    replays the delivered prefix on the survivor — itself speculating —
    byte-identical, with ``stream_resumes>=1`` and zero leaked pages.
    Speculative rollback state is per-slot device state the resume
    never sees: the wire contract (delivered tokens + rng_skip) is
    unchanged, which is exactly what this scenario pins down."""
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    monitor.reset_stats("serving/router/")
    spawner = SubprocessSpawner(extra_args=(
        "--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
        "--gen-max-len", "32", "--gen-step-wait-s", "0.05",
        "--gen-paged", "--gen-page-tokens", "8",
        "--gen-spec-k", "4", "--gen-spec-mode", "ngram"))
    eps = [spawner.spawn() for _ in range(2)]
    router = RoutedClient(eps, probe_interval_s=0)
    try:
        # templated prompt: gives the n-gram drafter something to match
        # so the killed stream is genuinely speculating
        prompt = np.asarray([3, 9, 3, 9, 3], np.int32)
        ref = np.asarray(generate(model, prompt[None], 12))[0, 5:]
        sess = router.session("spec-victim")
        it = sess.generate("llm", prompt, 12, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it), next(it)]          # live speculating stream
        victim = sess.endpoint
        spawner.kill(victim)                 # real SIGKILL, no goodbye
        err = None
        try:
            toks += list(it)                 # resumes on the survivor
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("genspec/stream_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref),
              f"err={err} toks={toks} ref={ref.tolist()}")
        check("genspec/resume_counted",
              monitor.get_stat("serving/router/stream_resumes") >= 1,
              str(monitor.export_stats("serving/router/")))
        survivor = next(ep for ep in eps if ep != victim)
        g = {}
        with io.InferenceClient(survivor, timeout=5.0) as c:
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                g = c.health()["generators"]["llm"]
                if (g.get("active") == 0 and g.get("pages_free", 0)
                        + g.get("prefix_entries", 0) == g.get("pages")):
                    break
                time.sleep(0.1)
        check("genspec/zero_leaked_pages_on_survivor",
              g.get("pages_free", -1) + g.get("prefix_entries", 0)
              == g.get("pages"), str(g))
        check("genspec/acceptance_stats_in_health",
              g.get("spec", {}).get("k") == 4
              and "accept_rate" in g.get("spec", {})
              and "tokens_per_step" in g, str(g))
    finally:
        router.close()
        for ep in list(spawner.procs):
            spawner.kill(ep)


def scenario_gen_sharded(tmp: str) -> None:
    """SIGKILL the tp=2 MESH-SHARDED subprocess replica holding a live
    stream under routed load: the stream resumes byte-identical on an
    UNSHARDED survivor. Cross-layout failover is the tentpole contract
    — the wire carries tokens + RNG position, never device layout, and
    sharded decode is bit-exact with unsharded decode — so a router may
    mix tp degrees freely in one fleet. The sharded replica's health
    (scraped before the kill) must ship the ``device`` block: mesh
    {'tp': 2}, 2 devices, per-device KV bytes exactly half the
    unsharded survivor's pool."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    monitor.reset_stats("serving/router/")
    base = ("--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
            "--gen-max-len", "32", "--gen-step-wait-s", "0.05")
    # one spawner per layout (replica_main forces the virtual host
    # device count itself when --mesh-tp > 0; startup pays the larger
    # 8-device backend init, hence the longer timeout)
    sharded = SubprocessSpawner(extra_args=base + ("--mesh-tp", "2"),
                                startup_timeout_s=120.0)
    plain = SubprocessSpawner(extra_args=base)
    ep_tp = sharded.spawn()
    ep_plain = plain.spawn()
    router = RoutedClient([ep_tp, ep_plain], probe_interval_s=0)
    try:
        devs = {}
        for ep in (ep_tp, ep_plain):
            with io.InferenceClient(ep, timeout=10.0) as c:
                devs[ep] = c.health()["generators"]["llm"]["device"]
        check("gensharded/device_block_topology",
              devs[ep_tp].get("mesh") == {"tp": 2}
              and devs[ep_tp].get("devices") == 2
              and devs[ep_plain].get("mesh") is None
              and devs[ep_plain].get("devices") == 1, str(devs))
        check("gensharded/per_device_kv_half_of_pool",
              devs[ep_tp]["kv_bytes"] == devs[ep_plain]["kv_bytes"]
              and devs[ep_tp]["kv_bytes_per_device"] * 2
              == devs[ep_plain]["kv_bytes"], str(devs))

        rs = np.random.RandomState(53)
        prompt = rs.randint(0, 96, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 12))[0, 5:]
        # pin the victim stream to the SHARDED replica deterministically
        # (cordon beats least-inflight tie-breaking races), then restore
        # the unsharded survivor to membership before the kill
        router.cordon(ep_plain)
        sess = router.session("kill-sharded")
        it = sess.generate("llm", prompt, 12, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it), next(it)]          # stream live on the mesh
        router.uncordon(ep_plain)
        check("gensharded/victim_is_sharded", sess.endpoint == ep_tp,
              f"pinned={sess.endpoint}")
        rider = router.session("rider")      # concurrent routed load
        it2 = rider.generate("llm", prompt, 12, poll_wait_s=0.05,
                             resume_budget=2)
        toks2 = [next(it2)]
        sharded.kill(ep_tp)                  # real SIGKILL, no goodbye
        err = None
        try:
            toks += list(it)                 # resumes on the unsharded
            toks2 += list(it2)               # survivor, byte-identical
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("gensharded/cross_layout_resume_byte_identical",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref)
              and np.array_equal(np.asarray(toks2, np.int32), ref),
              f"err={err} toks={toks} ref={ref.tolist()}")
        check("gensharded/resume_counted_no_failure_surfaced",
              err is None
              and monitor.get_stat("serving/router/stream_resumes") >= 1
              and monitor.get_stat("serving/router/resume_exhausted")
              == 0,
              str(monitor.export_stats("serving/router/")))
    finally:
        router.close()
        for sp in (sharded, plain):
            for ep in list(sp.procs):
                sp.kill(ep)


def scenario_obs_fleet(tmp: str) -> None:
    """SIGKILL a subprocess replica holding a live TRACED stream: the
    victim's span buffer is scraped moments before the kill (a dead
    replica can't be scraped), the stream resumes on the survivor under
    the SAME stream trace id, and obs_dump merges the two scrapes —
    taken at different times — into one Chrome trace with >= 1
    cross-endpoint stream ending in the survivor's retire(complete).
    A MetricsHub fed from routed health keeps answering through the
    membership churn and prunes the dead replica."""
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner
    from paddle_tpu.serving.metrics import MetricsHub

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import obs_dump

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    saved = get_flags(["trace", "trace_buffer"])
    # the replicas are subprocesses: they read tracing from the env they
    # inherit, so export BEFORE spawning; the parent traces too (the
    # router's gen/stream_resume marker lives in this process)
    os.environ["FLAGS_trace"] = "1"
    os.environ["FLAGS_trace_buffer"] = "4096"
    set_flags({"trace_buffer": 4096, "trace": True})
    trace.clear()
    spawner = SubprocessSpawner(extra_args=(
        "--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
        "--gen-max-len", "32", "--gen-step-wait-s", "0.05"))
    eps = [spawner.spawn() for _ in range(2)]
    router = RoutedClient(eps, probe_interval_s=0)
    hub = MetricsHub(fast_ticks=2, slow_ticks=6)
    try:
        rs = np.random.RandomState(53)
        prompt = rs.randint(0, 96, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 12))[0, 5:]
        sess = router.session("traced-kill")
        it = sess.generate("llm", prompt, 12, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it), next(it)]          # the stream is live
        victim = sess.endpoint
        hub.ingest(router.health(stats_prefix="gen/", histograms=True))
        # scrape the victim WHILE IT LIVES: its half of the stream's
        # life has to come out of its buffer before the SIGKILL
        pre = obs_dump.scrape(victim, clear=False, stats_prefix=None,
                              timeout=5.0)
        spawner.kill(victim)                 # real SIGKILL, no goodbye
        err = None
        try:
            toks += list(it)                 # resumes on the survivor
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("obsfleet/stream_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref),
              f"err={err} toks={len(toks)}")
        survivor = next(ep for ep in eps if ep != victim)
        post = obs_dump.scrape(survivor, clear=False, stats_prefix=None,
                               timeout=5.0)
        # two scrapes, two moments in time, ONE stream trace
        doc = obs_dump.merge_chrome([pre, post])
        parsed = json.loads(json.dumps(doc))
        check("obsfleet/merged_chrome_trace_parses",
              len(parsed.get("traceEvents", [])) > 0,
              f"events={len(parsed.get('traceEvents', []))}")
        report = obs_dump.build_report([pre, post], doc=doc)
        crossed = report["cross_endpoint_streams"]
        check("obsfleet/failover_stream_is_one_cross_replica_trace",
              report["cross_endpoint_stream_ids"] >= 1
              and any(d["retired"] == "complete"
                      and len(d["endpoints"]) == 2
                      and "gen/admitted" in d["names"]
                      for d in crossed.values()),
              json.dumps(crossed))
        check("obsfleet/resume_marker_traced_in_router",
              any(sp["name"] == "gen/stream_resume"
                  for sp in trace.get_spans()), "")
        # the hub keeps answering through the churn: the dead replica's
        # doc goes unreachable, the survivor's deltas keep flowing, and
        # a full slow window later the victim is pruned
        hub.ingest(router.health(stats_prefix="gen/", histograms=True))
        toks2 = list(router.generate("llm", prompt, 12,
                                     poll_wait_s=0.05))
        check("obsfleet/survivor_still_serves",
              np.array_equal(np.asarray(toks2, np.int32), ref),
              f"toks={len(toks2)}")
        # six more ticks: the victim (last seen tick 1) falls a full
        # slow window behind and is pruned at tick 8, while the
        # survivor's post-kill traffic delta (tick 3) is still inside
        # the slow window — churn must not blind the windowed series
        for _ in range(6):
            hub.ingest(router.health(stats_prefix="gen/",
                                     histograms=True))
        win = hub.window_histogram("gen/ttft_s", 6)
        burn = hub.burn_rates("gen/ttft_s", 0.5, budget=0.1)
        check("obsfleet/hub_series_survive_membership_churn",
              hub.endpoints() == [survivor]
              and win is not None and win["count"] >= 1
              and all(b >= 0.0 for b in burn),
              f"eps={hub.endpoints()} win={win and win['count']} "
              f"burn={burn}")
    finally:
        router.close()
        for ep in list(spawner.procs):
            spawner.kill(ep)
        del os.environ["FLAGS_trace"]
        del os.environ["FLAGS_trace_buffer"]
        set_flags(saved)
        trace.clear()


def scenario_ledger(tmp: str) -> None:
    """SIGKILL a replica holding a live TENANTED stream with the request
    ledger on: the stream resumes byte-identically on the survivor, and
    the survivor's ledger_dump shows a finalized record that (a) carries
    the resume sub-phase (this generation was a failover replay), (b)
    still belongs to the original tenant — the router re-sends the
    tenant header on every resume attempt, so attribution survives the
    kill — and (c) obeys the partition invariant: the phase seconds sum
    to the record's end-to-end latency exactly. The survivor's goodput
    taxonomy must likewise account 100% of its loop wall clock."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    saved = get_flags(["gen_ledger"])
    # subprocess replicas read the flag from the env they inherit, so
    # export BEFORE spawning; the parent flips it too for symmetry
    os.environ["FLAGS_gen_ledger"] = "1"
    set_flags({"gen_ledger": True})
    spawner = SubprocessSpawner(extra_args=(
        "--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
        "--gen-max-len", "32", "--gen-step-wait-s", "0.05"))
    eps = [spawner.spawn() for _ in range(2)]
    router = RoutedClient(eps, probe_interval_s=0)
    try:
        rs = np.random.RandomState(59)
        prompt = rs.randint(0, 96, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 12))[0, 5:]
        sess = router.session("ledger-kill")
        it = sess.generate("llm", prompt, 12, poll_wait_s=0.05,
                           resume_budget=2, tenant="acme")
        toks = [next(it), next(it)]          # the stream is live
        victim = sess.endpoint
        spawner.kill(victim)                 # real SIGKILL, no goodbye
        err = None
        try:
            toks += list(it)                 # resumes on the survivor
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("ledger/stream_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref),
              f"err={err} toks={len(toks)}")
        survivor = next(ep for ep in eps if ep != victim)
        with io.InferenceClient(survivor, timeout=5.0) as cl:
            dump = cl.ledger_dump()
        eng = (dump.get("generators") or {}).get("llm") or {}
        recs = eng.get("records") or []
        resumed = [r for r in recs if r.get("resume")]
        check("ledger/survivor_finalized_resume_record",
              any(r["outcome"] == "complete"
                  and r["resume"].get("rng_skip", 0) >= 1
                  for r in resumed),
              json.dumps(resumed))
        check("ledger/tenant_attribution_survives_failover",
              all(r.get("tenant") == "acme" for r in resumed)
              and resumed != []
              and eng.get("tenants", {}).get("acme", {})
              .get("tokens", 0) >= len(ref) - 2,
              json.dumps(eng.get("tenants")))
        # partition invariant on the wire: phases sum to e2e exactly
        # (clamped telescoping boundaries, not independent timers)
        check("ledger/phases_partition_e2e",
              recs != []
              and all(abs(sum(r["phases"].values()) - r["e2e_s"]) < 1e-6
                      for r in recs),
              json.dumps(recs[:1]))
        gp = eng.get("goodput") or {}
        fr = gp.get("fractions") or {}
        check("ledger/goodput_accounts_all_wall_clock",
              gp.get("total_s", 0.0) > 0.0
              and abs(sum(fr.values()) - 1.0) < 1e-6,
              json.dumps(gp))
    finally:
        router.close()
        for ep in list(spawner.procs):
            spawner.kill(ep)
        del os.environ["FLAGS_gen_ledger"]
        set_flags(saved)


def scenario_gen_sched(tmp: str) -> None:
    """SIGKILL a scheduler-on replica mid-preempted-stream pair: a
    1-slot replica is decoding a batch stream when an interactive
    arrival preempts it (the batch stream parks via the prompt-fold
    contract); the replica is then SIGKILLed with BOTH streams live.
    Both resume on the survivor byte-identical, the survivor leaks no
    pages, and no parked slot is stranded anywhere."""
    import time
    import zlib

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    monitor.reset_stats("serving/router/")
    spawner = SubprocessSpawner(extra_args=(
        "--gen", "llm", "--gen-seed", "7", "--gen-slots", "1",
        "--gen-max-len", "32", "--gen-step-wait-s", "0.05",
        "--gen-paged", "--gen-page-tokens", "8", "--gen-sched"))
    eps = [spawner.spawn() for _ in range(2)]
    router = RoutedClient(eps, probe_interval_s=0)
    try:
        victim = sorted(eps)[0]
        vidx = sorted(eps).index(victim)

        def _sid(prefix):
            # sticky pin is crc32(sid) % len(healthy) over the sorted
            # membership: mint a session id that pins to the victim so
            # the interactive arrival actually contends with the batch
            # stream for its single slot
            for i in range(64):
                sid = f"{prefix}{i}"
                if zlib.crc32(sid.encode()) % len(eps) == vidx:
                    return sid
            raise AssertionError("no session id pinned to victim")

        p_batch = np.arange(1, 9, dtype=np.int32)
        p_inter = np.arange(10, 14, dtype=np.int32)
        ref_b = np.asarray(generate(model, p_batch[None], 16))[0, 8:]
        ref_i = np.asarray(generate(model, p_inter[None], 10))[0, 4:]

        sess_b = router.session(_sid("bulk-"))
        it_b = sess_b.generate("llm", p_batch, 16, poll_wait_s=0.05,
                               resume_budget=2, tenant="bulk",
                               priority="batch")
        toks_b = [next(it_b), next(it_b)]       # decoding mid-stream
        sess_i = router.session(_sid("live-"))
        it_i = sess_i.generate("llm", p_inter, 10, poll_wait_s=0.05,
                               resume_budget=2, tenant="live",
                               priority="interactive")
        # an interactive token on a 1-slot replica means the batch
        # stream was parked first — read the scheduler's own counter
        toks_i = [next(it_i)]
        with io.InferenceClient(victim, timeout=5.0) as c:
            sched = c.health()["generators"]["llm"].get("sched") or {}
        check("gensched/preempted_before_kill",
              sched.get("preemptions", 0) >= 1
              and sched.get("admitted", {}).get("interactive", 0) >= 1,
              json.dumps(sched))

        spawner.kill(victim)          # SIGKILL: interactive mid-stream,
        err = None                    # batch parked on the dead replica
        try:
            toks_i += list(it_i)
            toks_b += list(it_b)
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("gensched/preempted_interactive_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks_i, np.int32), ref_i),
              f"err={err} toks={len(toks_i)}")
        check("gensched/parked_batch_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks_b, np.int32), ref_b),
              f"err={err} toks={len(toks_b)}")
        check("gensched/resumes_counted",
              monitor.get_stat("serving/router/stream_resumes") >= 2
              and monitor.get_stat("serving/router/resume_exhausted")
              == 0,
              str(monitor.export_stats("serving/router/")))
        survivor = next(ep for ep in eps if ep != victim)
        g = {}
        with io.InferenceClient(survivor, timeout=5.0) as c:
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                g = c.health()["generators"]["llm"]
                if (g.get("active") == 0 and g.get("queued") == 0
                        and g.get("pages_free", 0)
                        + g.get("prefix_entries", 0) == g.get("pages")):
                    break
                time.sleep(0.1)
        check("gensched/no_leaked_pages_no_stranded_slots_on_survivor",
              g.get("active") == 0 and g.get("queued") == 0
              and g.get("pages_free", -1) + g.get("prefix_entries", 0)
              == g.get("pages"), str(g))
    finally:
        router.close()
        for ep in list(spawner.procs):
            spawner.kill(ep)


def scenario_gen_disagg(tmp: str) -> None:
    """SIGKILL a decode-tier replica holding a live stream with the
    tiered KV store on (two ``--role decode --kv-store`` replicas, one
    shared spill root): the victim's prefill PUBLISHED the page-aligned
    prompt's pages, so the resumed stream on the other decode replica
    admits via KV FETCH — byte-identical completion with ZERO
    recomputed prefill tokens and zero leaked pages on the survivor."""
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    monitor.reset_stats("serving/router/")
    # the router's KV-locality placement reads both at construction;
    # the subprocess replicas get their store via CLI args instead
    saved = get_flags(["gen_kv_store", "gen_page_tokens"])
    set_flags({"gen_kv_store": True, "gen_page_tokens": 8})
    spill = os.path.join(tmp, "kv_spill")
    spawner = SubprocessSpawner(extra_args=(
        "--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
        "--gen-max-len", "32", "--gen-step-wait-s", "0.05",
        "--gen-paged", "--gen-page-tokens", "8",
        "--role", "decode", "--kv-store", "--kv-spill-dir", spill))
    eps = [spawner.spawn() for _ in range(2)]
    router = RoutedClient(eps, probe_interval_s=0)
    try:
        rs = np.random.RandomState(61)
        # PAGE-ALIGNED prompt (8 tokens @ page_tokens 8): the victim's
        # prefill publishes the WHOLE original prompt, so the resumed
        # admission covers it entirely from the store — recompute debt 0
        prompt = rs.randint(0, 96, (8,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 12))[0, 8:]
        sess = router.session("disagg-victim")
        it = sess.generate("llm", prompt, 12, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it), next(it)]          # the stream is live
        victim = sess.endpoint
        spawner.kill(victim)                 # real SIGKILL, no goodbye
        err = None
        try:
            toks += list(it)                 # resumes via KV fetch
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("disagg/stream_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref),
              f"err={err} toks={len(toks)}")
        check("disagg/resume_counted_no_failure_surfaced",
              err is None
              and monitor.get_stat("serving/router/stream_resumes") >= 1
              and monitor.get_stat("serving/router/resume_exhausted")
              == 0,
              str(monitor.export_stats("serving/router/")))
        survivor = next(ep for ep in eps if ep != victim)
        g = {}
        with io.InferenceClient(survivor, timeout=5.0) as c:
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                g = c.health()["generators"]["llm"]
                if (g.get("active") == 0 and g.get("pages_free", 0)
                        + g.get("prefix_entries", 0) == g.get("pages")):
                    break
                time.sleep(0.1)
        kv = g.get("kv") or {}
        check("disagg/failover_is_kv_fetch_zero_recompute",
              kv.get("role") == "decode"
              and kv.get("fetched_pages", 0) >= 1
              and kv.get("prefill_recomputed", -1) == 0,
              str(kv))
        check("disagg/zero_leaked_pages_on_survivor",
              g.get("pages_free", -1) + g.get("prefix_entries", 0)
              == g.get("pages"), str(g))
    finally:
        router.close()
        for ep in list(spawner.procs):
            spawner.kill(ep)
        set_flags(saved)


def scenario_gen_hotloop(tmp: str) -> None:
    """SIGKILL the subprocess replica running the overhauled decode hot
    loop (``--gen-async-depth 2 --gen-device-pt``) while it holds a
    live SAMPLED stream: the delivered prefix — which under lookahead
    lags device progress by up to ``depth`` steps — resumes on a plain
    SYNCHRONOUS survivor byte-identical to the uninterrupted solo
    stream, and the survivor drains back to a full page pool. The wire
    contract (delivered tokens + rng_skip) never sees dispatch depth or
    page-table residency, which is exactly what this scenario pins."""
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)

    monitor.reset_stats("serving/router/")
    base = ("--gen", "llm", "--gen-seed", "7", "--gen-slots", "2",
            "--gen-max-len", "32", "--gen-step-wait-s", "0.05",
            "--gen-paged", "--gen-page-tokens", "8")
    # victim runs the full hot-loop overhaul; survivor is the plain
    # synchronous loop — failover must cross the dispatch-mode boundary
    hot = SubprocessSpawner(extra_args=base + ("--gen-async-depth", "2",
                                               "--gen-device-pt"))
    plain = SubprocessSpawner(extra_args=base)
    ep_hot = hot.spawn()
    ep_plain = plain.spawn()
    router = RoutedClient([ep_hot, ep_plain], probe_interval_s=0)
    try:
        rs = np.random.RandomState(53)
        prompt = rs.randint(0, 96, (5,)).astype(np.int32)
        import jax
        kw = dict(temperature=0.8, top_k=7, top_p=0.9, seed=42)
        ref = np.asarray(generate(
            model, prompt[None], 12, key=jax.random.PRNGKey(42),
            **{k: v for k, v in kw.items() if k != "seed"}))[0, 5:]
        # pin a session to the async replica so the kill hits the
        # lookahead loop mid-stream (routing hashes the session id —
        # try ids until one lands; the endpoint is set by the start)
        it = toks = None
        for n in range(32):
            sess = router.session(f"hot-victim-{n}")
            it = sess.generate("llm", prompt, 12, poll_wait_s=0.05,
                               resume_budget=2, **kw)
            first = next(it)             # start() ran: endpoint is real
            if sess.endpoint == ep_hot:
                toks = [first, next(it)]     # lookahead stream is live
                break
            list(it)                     # drain the mis-pinned stream
        check("genhot/victim_session_pinned", toks is not None,
              f"endpoint never hashed to {ep_hot}")
        hot.kill(ep_hot)                 # real SIGKILL, no goodbye
        err = None
        try:
            toks += list(it)             # resumes on the sync survivor
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        check("genhot/sampled_stream_byte_identical_through_kill",
              err is None
              and np.array_equal(np.asarray(toks, np.int32), ref),
              f"err={err} toks={toks} ref={ref.tolist()}")
        check("genhot/resume_counted",
              monitor.get_stat("serving/router/stream_resumes") >= 1,
              str(monitor.export_stats("serving/router/")))
        g = {}
        with io.InferenceClient(ep_plain, timeout=5.0) as c:
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                g = c.health()["generators"]["llm"]
                if (g.get("active") == 0 and g.get("pages_free", 0)
                        + g.get("prefix_entries", 0) == g.get("pages")):
                    break
                time.sleep(0.1)
        check("genhot/zero_leaked_pages_on_survivor",
              g.get("pages_free", -1) + g.get("prefix_entries", 0)
              == g.get("pages"), str(g))
        check("genhot/survivor_is_synchronous",
              g.get("async_depth") == 0 and g.get("device_pt") is False
              and g.get("pending_steps") == 0, str(g))
    finally:
        router.close()
        for sp in (hot, plain):
            for ep in list(sp.procs):
                sp.kill(ep)


def _campaign_drain(engine, gid, wait_s=0.5):
    toks, n = [], 0
    while True:
        doc = engine.poll(gid, start=n, wait_s=wait_s)
        toks += doc["tokens"]
        n = len(toks)
        if doc["done"]:
            return toks, doc["error"]


def run_campaign(n: int, seed: int, tmp: str) -> None:
    """Seeded randomized chaos campaign over the KV failure domain.

    ``n`` scenarios, each drawn from ``random.Random(seed)``: a random
    store topology (shared spill root / one shared store object / peer
    tier), a random producer/consumer role pair, random hardening flags
    (fetch deadline, hedge threshold, breaker), and a random fault spec
    of 1-3 sites from the KV path (``kvstore.get``, ``kvstore.put``,
    ``kvstore.spill``, ``wire.kv_get``, ``fs.download``). A producer
    engine prefills-and-publishes a prompt, then a cold consumer engine
    serves the SAME prompt — admitting via KV fetch where the tiers
    survive, degrading to local recompute where they do not. Invariants
    asserted per scenario, whatever the faults did:

    - both streams byte-identical to solo ``generate()`` (degradation
      changes WHERE prefill ran, never a single byte of output);
    - zero leaked pages on both engines;
    - every fault that FIRED is visible in the degradation ledger (tier
      errors/timeouts on a store, or ``fetch_degraded`` on an engine) —
      silent slow paths are the bug this campaign exists to catch.

    Ends with a deterministic breaker-lifecycle check (open →
    backoff → half-open probe → closed, all observable in tier health)
    and a defaults check that a hardened-flags-off engine never reads a
    ``gen_kv_*`` flag on the hot path."""
    import random
    import time

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.serving.kvstore import KVStore

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)
    rng = random.Random(seed)
    refs: dict = {}

    def ref_for(pseed, plen, new):
        key = (pseed, plen, new)
        if key not in refs:
            p = np.random.RandomState(pseed).randint(
                0, 96, (plen,)).astype(np.int32)
            refs[key] = (p, np.asarray(generate(model, p[None],
                                                new))[0, plen:])
        return refs[key]

    sites = ("kvstore.get", "kvstore.put", "kvstore.spill",
             "wire.kv_get", "fs.download")
    role_pairs = (("both", "decode"), ("prefill", "decode"),
                  ("both", "both"))
    topos = ("shared_spill", "shared_store", "peer")

    for i in range(n):
        tag = f"campaign/{i:02d}"
        pseed = rng.randrange(1000)
        plen = rng.choice((16, 24))
        new = rng.choice((4, 6))
        prod_role, cons_role = rng.choice(role_pairs)
        topo = rng.choice(topos)
        hard = dict(fetch_timeout_s=rng.choice((0.0, 0.25)),
                    hedge_ms=rng.choice((0.0, 5.0)),
                    breaker=rng.choice((0, 2)), breaker_backoff_s=0.05)
        spec = {s: (rng.choice((0.3, 0.7, 1.0)), rng.choice((1, 2, 3)))
                for s in rng.sample(sites, rng.randint(1, 3))}
        desc = (f"topo={topo} roles={prod_role}/{cons_role} "
                f"prompt=({pseed},{plen})+{new} hard={hard} spec={spec}")
        prompt, ref = ref_for(pseed, plen, new)
        stores: list = []
        try:
            if topo == "shared_spill":
                spill = os.path.join(tmp, f"kvcamp{i}")
                prod_store = KVStore(pages=64, spill=spill, **hard)
                cons_store = KVStore(pages=64, spill=spill, **hard)
                stores = [prod_store, cons_store]
            elif topo == "shared_store":
                prod_store = cons_store = KVStore(pages=64, **hard)
                stores = [prod_store]
            else:                      # peer tier: consumer reaches the
                prod_store = KVStore(pages=64)       # producer directly
                cons_store = KVStore(
                    pages=64, spill=os.path.join(tmp, f"kvcamp{i}"),
                    peers=(prod_store.get,), **hard)
                stores = [prod_store, cons_store]
            with GenerationEngine(model, slots=2, max_len=64, paged=True,
                                  page_tokens=8, kv_store=prod_store,
                                  role=prod_role) as prod, \
                 GenerationEngine(model, slots=2, max_len=64, paged=True,
                                  page_tokens=8, kv_store=cons_store,
                                  role=cons_role) as cons:
                with fault.inject_faults(spec, seed=seed * 1000 + i):
                    pt, pe = _campaign_drain(prod, prod.start(prompt, new))
                    ct, ce = _campaign_drain(cons, cons.start(prompt, new))
                    fired = {s: f for s, (_, f)
                             in fault.site_counts().items() if f}
                check(f"{tag}/streams_byte_identical",
                      pe is None and ce is None
                      and np.array_equal(np.asarray(pt, np.int32), ref)
                      and np.array_equal(np.asarray(ct, np.int32), ref),
                      f"{desc} perr={pe} cerr={ce}")
                leaks = []
                for who, eng in (("producer", prod), ("consumer", cons)):
                    g = eng.stats()
                    if g["pages_free"] + g["prefix_entries"] != g["pages"]:
                        leaks.append((who, g["pages_free"],
                                      g["prefix_entries"], g["pages"]))
                check(f"{tag}/zero_leaked_pages", not leaks,
                      f"{desc} leaks={leaks}")
                booked = sum(s["errors"] + s["timeouts"]
                             for s in (st.snapshot() for st in stores))
                booked += sum(eng.stats()["kv"]["fetch_degraded"]
                              for eng in (prod, cons))
                check(f"{tag}/degradation_explained",
                      not fired or booked > 0,
                      f"{desc} fired={fired} booked={booked}")
        finally:
            for st in stores:
                st.close()

    # deterministic tail: the full breaker lifecycle, observable in tier
    # health — consecutive spill failures OPEN the breaker (the store
    # stops being placeable), the backoff elapses, ONE half-open probe
    # goes through, and a clean answer CLOSES it again
    st = KVStore(pages=8, spill=os.path.join(tmp, "kvcamp_breaker"),
                 breaker=2, breaker_backoff_s=0.05)
    try:
        st.put("warm", b"W" * 8)
        with fault.inject_faults({"kvstore.spill": 1.0}, seed=11):
            for k in ("c1", "c2", "c3"):
                st.get(k)
        h = st.snapshot()["health"]["spill"]
        check("campaign/breaker_opens",
              h["opens"] == 1 and h["state"] in ("open", "half_open")
              and not st.placeable, str(h))
        time.sleep(0.12)               # backoff elapses -> probe window
        st.get("c1")                   # clean absence closes the tier
        h = st.snapshot()["health"]["spill"]
        check("campaign/breaker_half_opens_then_closes",
              h["half_opens"] >= 1 and h["closes"] == 1
              and h["state"] == "closed" and st.placeable, str(h))
    finally:
        st.close()

    # defaults: a hardened-flags-off engine serves byte-identical and
    # never reads a gen_kv_* flag on the hot path (construction only)
    import paddle_tpu.serving.engine as engine_mod

    prompt, ref = ref_for(3, 16, 4)
    reads: list = []
    real_flag = engine_mod.flag
    engine_mod.flag = lambda name: (reads.append(name), real_flag(name))[1]
    try:
        with GenerationEngine(model, slots=2, max_len=64, paged=True,
                              page_tokens=8) as eng:
            ctor = [r for r in reads if r.startswith("gen_kv")]
            del reads[:]
            toks, err = _campaign_drain(eng, eng.start(prompt, 4))
            hot = [r for r in reads if r.startswith("gen_kv")]
    finally:
        engine_mod.flag = real_flag
    check("campaign/defaults_no_hot_path_flag_reads",
          err is None and np.array_equal(np.asarray(toks, np.int32), ref)
          and ctor and not hot,
          f"err={err} ctor_reads={len(ctor)} hot_reads={hot}")


def scenario_sparse_serve(tmp: str) -> None:
    """SIGKILL a sparse-serving replica mid-version-rollover under
    routed load: two subprocess replicas (``--emb-ps``) serve a CTR
    endpoint over one PS fleet; the trainer publishes v1 and one
    replica is SIGKILLed before it can flip — zero requests are
    dropped (the router fails idempotent infers over), no response
    ever mixes rows of two versions, the survivor converges to the
    published version on its health tick, and zero stale serves
    happen (the PS fleet stayed healthy throughout)."""
    import threading
    import time

    from paddle_tpu.distributed.ps import ParameterServer, PSClient
    from paddle_tpu.serving import RoutedClient, SubprocessSpawner

    monitor.reset_stats("serving/router/")
    ps_srv = ParameterServer().start()
    ps = PSClient(ps_srv.endpoint)
    rc = None
    spawner = SubprocessSpawner(extra_args=(
        "--emb-ps", ps_srv.endpoint, "--emb-table", "emb:8:3"))
    try:
        ps.create_table("emb", 8, optimizer="sgd", lr=0.5, seed=3)
        eps = [spawner.spawn() for _ in range(2)]
        rc = RoutedClient(eps, probe_interval_s=0.25, timeout=10.0)
        q = np.arange(12, dtype=np.int64).reshape(4, 3)
        stop = threading.Event()
        errors: list = []
        mixed: list = []
        seen: set = set()
        n_ok = [0]
        lock = threading.Lock()

        def hammer():
            try:
                while not stop.is_set():
                    scores, ver = rc.infer("ctr", q)
                    v = int(ver[0, 0])
                    with lock:
                        n_ok[0] += 1
                        seen.add(v)
                        if not (ver == v).all():
                            mixed.append(ver.tolist())
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.4)                    # serve a while at v0
        ps.publish_version("emb")          # the trainer's push...
        spawner.kill(eps[0])               # ...and a replica dies mid-
        survivor = eps[1]                  # rollover, before it flips
        emb = {}
        deadline = time.monotonic() + 10.0
        with io.InferenceClient(survivor, timeout=5.0) as c:
            while time.monotonic() < deadline:
                emb = c.health().get("emb", {})   # health tick = flip
                if emb.get("tables", {}).get("emb", {}) \
                        .get("version") == 1:
                    break
                time.sleep(0.1)
        time.sleep(0.4)                    # serve a while at v1
        stop.set()
        for t in threads:
            t.join(timeout=30)
        check("sparse/zero_dropped_requests",
              not errors and n_ok[0] > 10,
              f"errors={errors[:2]} n={n_ok[0]}")
        check("sparse/failover_fired",
              monitor.get_stat("serving/router/failovers") >= 1,
              str(monitor.export_stats("serving/router/")))
        check("sparse/zero_mixed_version_responses", not mixed,
              str(mixed[:2]))
        check("sparse/versions_converged",
              seen == {0, 1}
              and emb.get("tables", {}).get("emb", {}).get("version") == 1
              and emb.get("rollovers") == 1,
              f"seen={seen} emb={emb}")
        check("sparse/zero_stale_serves",
              emb.get("stale_serves", -1) == 0, str(emb))
    finally:
        if rc is not None:
            rc.close()
        for ep in list(spawner.procs):
            spawner.kill(ep)
        ps.close()
        ps_srv.stop()


def scenario_kv_campaign(tmp: str) -> None:
    """A small fixed slice of the randomized KV chaos campaign (see
    ``run_campaign``): 5 scenarios at seed 0, plus the deterministic
    breaker-lifecycle and defaults tails. ``--campaign N --seed S``
    runs a larger campaign standalone."""
    run_campaign(5, 0, tmp)


def _report() -> int:
    ok = all(c[1] for c in CHECKS)
    print(json.dumps({
        "ok": ok,
        "checks": {name: passed for name, passed, _ in CHECKS},
        "failures": [{"check": n, "detail": d}
                     for n, p, d in CHECKS if not p],
        "stats": {k: v for k, v in monitor.export_stats().items()
                  if k.split("/")[0] in ("wire", "ckpt", "fault", "train",
                                         "serving", "gen", "control",
                                         "kv")},
    }, indent=2))
    return 0 if ok else 1


SCENARIOS = (scenario_serving_wire, scenario_checkpoint,
             scenario_elastic_resume, scenario_overload,
             scenario_obs, scenario_serving_routed,
             scenario_gen_engine, scenario_gen_paged,
             scenario_control_plane, scenario_control_ha,
             scenario_gen_resilience,
             scenario_gen_spec, scenario_gen_sharded,
             scenario_obs_fleet, scenario_ledger,
             scenario_gen_disagg,
             scenario_gen_hotloop,
             scenario_gen_sched,
             scenario_sparse_serve,
             scenario_kv_campaign)


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    campaign_n = None
    seed = 0
    if "--campaign" in argv:
        campaign_n = int(argv[argv.index("--campaign") + 1])
    if "--seed" in argv:
        seed = int(argv[argv.index("--seed") + 1])
    # positional args name scenarios to run (e.g. ``control-ha``); the
    # defaults checks always run
    by_name = {fn.__name__[len("scenario_"):].replace("_", "-"): fn
               for fn in SCENARIOS}
    names, skip = [], False
    for a in argv:
        if skip:
            skip = False
        elif a in ("--campaign", "--seed"):
            skip = True
        elif not a.startswith("-"):
            if a not in by_name:
                print(f"unknown scenario {a!r}; one of "
                      f"{', '.join(sorted(by_name))}", file=sys.stderr)
                return 2
            names.append(a)
    scenarios = [by_name[n] for n in names] if names else SCENARIOS
    check_defaults_off()
    with tempfile.TemporaryDirectory(prefix="ptpu_chaos_") as tmp:
        os.environ["PADDLE_CKPT_CACHE_ROOT"] = os.path.join(tmp, "cache")
        if campaign_n is not None:     # campaign-only run: defaults +
            try:                       # the randomized KV campaign
                run_campaign(campaign_n, seed, tmp)
            except Exception as e:
                check("run_campaign/completed", False,
                      f"{type(e).__name__}: {e}")
            return _report()
        for scenario in scenarios:
            try:
                scenario(tmp)
            except Exception as e:   # a crash is a failed check, not a
                check(f"{scenario.__name__}/completed", False,   # traceback
                      f"{type(e).__name__}: {e}")
    return _report()


if __name__ == "__main__":
    sys.exit(main())
