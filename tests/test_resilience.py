"""End-to-end generation resilience: lossless stream resumption across
replica death, engine self-healing (trap → rebuild → re-admit), crash
quarantine, the spawn circuit breaker, and the typed poll-TTL expiry.

The load-bearing property is the resumption determinism contract: a
greedy stream whose replica dies mid-decode, resumed on a survivor by
replaying prompt + delivered tokens as a prefill-from-prefix, is
byte-identical to an uninterrupted run — replica loss becomes invisible
to the caller instead of a GenerationFailed.
"""

import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core import fault, trace
from paddle_tpu.core.flags import flag, get_flags, set_flags
from paddle_tpu.core.monitor import get_stat
from paddle_tpu.io.serving import InferenceClient, InferenceServer
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import advance_key, generate
from paddle_tpu.serving import (
    GenerationEngine, GenerationExpired, GenerationFailed, ReplicaSpawner,
    RequestQuarantined, RoutedClient, ServingController,
    StreamResumeExhausted,
)
from paddle_tpu.serving.engine import RESET_MARKER

pytestmark = pytest.mark.resilience

VOCAB = 96


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    return LlamaForCausalLM(cfg)


def _drain(engine, gen_id, wait_s=0.5):
    toks, n = [], 0
    while True:
        doc = engine.poll(gen_id, start=n, wait_s=wait_s)
        toks += doc["tokens"]
        n = len(toks)
        if doc["done"]:
            return toks, doc["error"]


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


# -- tentpole: lossless stream resumption -----------------------------------

def test_resume_after_replica_kill_greedy_identical(model):
    """Kill the replica holding a live greedy stream: with a resume
    budget the routed stream replays prompt + delivered tokens onto the
    survivor and completes byte-identical to an uninterrupted solo
    generate() — zero GenerationFailed surfaced to the caller."""
    servers, engines = [], []
    for _ in range(2):
        eng = GenerationEngine(model, slots=2, max_len=32,
                               step_wait_s=0.03)
        srv = InferenceServer().start()
        srv.add_generator("llm", eng)
        servers.append(srv)
        engines.append(eng)
    router = RoutedClient([s.endpoint for s in servers],
                          probe_interval_s=0)
    try:
        rs = np.random.RandomState(31)
        prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 10))[0, 5:]
        resumes0 = get_stat("serving/router/stream_resumes")

        sess = router.session("victim-stream")
        it = sess.generate("llm", prompt, 10, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it), next(it)]            # stream is live
        pinned = sess.endpoint
        victim = next(s for s in servers if s.endpoint == pinned)
        victim.stop()                          # SIGKILL-equivalent sever
        toks += list(it)                       # resumes on the survivor

        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        assert get_stat("serving/router/stream_resumes") == resumes0 + 1
        survivor = next(e for s, e in zip(servers, engines)
                        if s.endpoint != pinned)
        assert _wait(lambda: survivor.stats()["active"] == 0)
    finally:
        router.close()
        for s in servers:
            s.stop()


@pytest.mark.obs
def test_failover_stream_is_one_trace_across_replicas(model):
    """A traced stream that fails over keeps ONE trace id: the victim's
    admission and the survivor's completion land under the same stream
    trace id (what obs_dump merges into a single cross-replica
    timeline), joined by the router's gen/stream_resume marker."""
    saved = get_flags(["trace", "trace_buffer"])
    set_flags({"trace_buffer": 4096, "trace": True})
    trace.clear()
    servers, engines = [], []
    try:
        for _ in range(2):
            eng = GenerationEngine(model, slots=2, max_len=32,
                                   step_wait_s=0.03)
            srv = InferenceServer().start()
            srv.add_generator("llm", eng)
            servers.append(srv)
            engines.append(eng)
        router = RoutedClient([s.endpoint for s in servers],
                              probe_interval_s=0)
        try:
            rs = np.random.RandomState(43)
            prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
            ref = np.asarray(generate(model, prompt[None], 10))[0, 5:]
            sess = router.session("traced-victim")
            it = sess.generate("llm", prompt, 10, poll_wait_s=0.05,
                               resume_budget=2)
            toks = [next(it), next(it)]
            pinned = sess.endpoint
            victim = next(s for s in servers if s.endpoint == pinned)
            victim.stop()
            toks += list(it)
            np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                          ref)

            spans = trace.get_spans()
            # stream-lifecycle spans: per-generation events (they carry
            # the gen id) plus the router's resume marker — NOT the
            # engine-wide gen/decode_step spans, which mint their own
            # trace ids
            stream_ids = {sp["trace_id"] for sp in spans
                          if sp["name"].startswith("gen/")
                          and ("gen" in (sp.get("attrs") or {})
                               or sp["name"] == "gen/stream_resume")}
            assert len(stream_ids) == 1    # whole life under ONE id
            sid, = stream_ids
            mine = [sp for sp in spans if sp["trace_id"] == sid]
            # both replicas admitted the stream: the in-proc servers
            # share one process tracer, so the engine loop thread id is
            # what tells the two replicas' spans apart
            admits = [sp for sp in mine if sp["name"] == "gen/admitted"]
            assert len(admits) == 2
            assert len({sp["tid"] for sp in admits}) == 2
            names = {sp["name"] for sp in mine}
            assert "gen/stream_resume" in names
            assert any((sp.get("attrs") or {}).get("reason")
                       == "complete" for sp in mine
                       if sp["name"] == "gen/retire")
        finally:
            router.close()
    finally:
        for s in servers:
            s.stop()
        set_flags(saved)
        trace.clear()


def test_resume_budget_exhaustion_surfaces_typed(model):
    """When every resume attempt fails (no replica left), the stream
    gives up with the typed StreamResumeExhausted — which still IS a
    GenerationFailed for existing handlers — after exactly budget+1
    attempts."""
    eng = GenerationEngine(model, slots=1, max_len=32, step_wait_s=0.03)
    srv = InferenceServer().start()
    srv.add_generator("llm", eng)
    router = RoutedClient([srv.endpoint], probe_interval_s=0)
    try:
        rs = np.random.RandomState(32)
        prompt = rs.randint(0, VOCAB, (4,)).astype(np.int32)
        ex0 = get_stat("serving/router/resume_exhausted")
        it = router.session("doomed").generate(
            "llm", prompt, 12, poll_wait_s=0.05, resume_budget=1)
        next(it)
        srv.stop()
        with pytest.raises(StreamResumeExhausted) as ei:
            list(it)
        assert isinstance(ei.value, GenerationFailed)
        assert ei.value.attempts == 2          # budget 1 + the last try
        assert get_stat("serving/router/resume_exhausted") == ex0 + 1
    finally:
        router.close()
        srv.stop()


def test_sampled_resume_replays_rng_position(model):
    """A sampled stream resumed as prefill-from-prefix with
    rng_skip=len(delivered) continues the exact per-(prompt, seed) key
    schedule: the resumed tail equals the uninterrupted stream's."""
    with GenerationEngine(model, slots=2, max_len=32) as eng:
        rs = np.random.RandomState(33)
        prompt = rs.randint(0, VOCAB, (6,)).astype(np.int32)
        kw = dict(temperature=0.8, top_k=7, top_p=0.9, seed=42)
        full, err = _drain(eng, eng.start(prompt, 6, **kw))
        assert err is None and len(full) == 6
        # resume after 3 delivered tokens: replay prompt+delivered,
        # fast-forward the key schedule by 3 splits
        replay = np.concatenate([prompt,
                                 np.asarray(full[:3], np.int32)])
        tail, err = _drain(eng, eng.start(replay, 3, rng_skip=3, **kw))
        assert err is None
        assert tail == full[3:]


def test_advance_key_matches_engine_schedule():
    """advance_key(key, n) is exactly n split-and-keep-first steps (the
    engine's per-token schedule)."""
    import jax

    key = jax.random.PRNGKey(42)
    manual = key
    for _ in range(5):
        manual = jax.random.split(manual)[0]
    np.testing.assert_array_equal(np.asarray(advance_key(key, 5)),
                                  np.asarray(manual))
    np.testing.assert_array_equal(np.asarray(advance_key(key, 0)),
                                  np.asarray(key))


# -- engine self-healing ----------------------------------------------------

def test_engine_rebuild_readmits(model):
    """A decode-loop trap with rebuilds enabled fails the active
    generations loudly (resumable 'engine reset:' error), rebuilds the
    device state, and re-admits new work — no terminal broken state."""
    with GenerationEngine(model, slots=2, max_len=32,
                          rebuilds=2) as eng:
        rs = np.random.RandomState(34)
        prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 4))[0, 5:]
        with fault.inject_faults({"engine.decode_step": (1.0, 1)}):
            toks, err = _drain(eng, eng.start(prompt, 4))
            assert err is not None and RESET_MARKER in err
        st = eng.stats()
        assert st["broken"] is None and st["rebuilds"] == 1
        assert st["active"] == 0
        # re-admitted work is byte-identical on the rebuilt state
        toks, err = _drain(eng, eng.start(prompt, 4))
        assert err is None
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)


def test_rebuilds_off_keeps_terminal_break(model):
    """Default gen_engine_rebuilds=0: the first trap still bricks the
    engine (the pre-resilience contract, unchanged)."""
    assert int(flag("gen_engine_rebuilds")) == 0
    with GenerationEngine(model, slots=1, max_len=32) as eng:
        rs = np.random.RandomState(35)
        prompt = rs.randint(0, VOCAB, (4,)).astype(np.int32)
        with fault.inject_faults({"engine.decode_step": (1.0, 1)}):
            toks, err = _drain(eng, eng.start(prompt, 4))
            assert err is not None
        assert _wait(lambda: eng.stats()["broken"] is not None)
        with pytest.raises(RuntimeError, match="broken"):
            eng.start(prompt, 2)


def test_quarantine_after_n_traps(model):
    """A request whose prefill traps gen_quarantine_after times is
    rejected at start with the typed RequestQuarantined; other requests
    are untouched."""
    with GenerationEngine(model, slots=2, max_len=32, rebuilds=4,
                          quarantine_after=1) as eng:
        rs = np.random.RandomState(36)
        poison = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        other = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        q0 = get_stat("gen/quarantined")
        with fault.inject_faults({"engine.prefill": (1.0, 1)}):
            toks, err = _drain(eng, eng.start(poison, 4))
            assert err is not None and RESET_MARKER in err
        assert get_stat("gen/quarantined") == q0 + 1
        # same (prompt, sampling params) fingerprint: typed rejection
        with pytest.raises(RequestQuarantined) as ei:
            eng.start(poison, 4)
        assert ei.value.fingerprint
        assert eng.stats()["quarantined"] == 1
        # an innocent request (different fingerprint) runs fine
        toks, err = _drain(eng, eng.start(other, 3))
        assert err is None and len(toks) == 3


def test_quarantined_start_surfaces_typed_over_wire(model):
    """The quarantine rejection crosses the wire typed (marker →
    RequestQuarantined), so a routed client can give up instead of
    walking the poison request across the fleet."""
    eng = GenerationEngine(model, slots=1, max_len=32, rebuilds=4,
                           quarantine_after=1)
    srv = InferenceServer().start()
    srv.add_generator("llm", eng)
    client = InferenceClient(srv.endpoint)
    try:
        rs = np.random.RandomState(37)
        poison = rs.randint(0, VOCAB, (4,)).astype(np.int32)
        with fault.inject_faults({"engine.prefill": (1.0, 1)}):
            toks, err = _drain(eng, eng.start(poison, 3))
            assert err is not None
        with pytest.raises(RequestQuarantined):
            client.generate_start("llm", poison, 3)
    finally:
        client.close()
        srv.stop()


def test_fused_decode_trap_is_suspect_needs_two_hits(model):
    """Satellite: a fused-decode trap implicates EVERY stepped
    generation — co-tenant-ambiguous attribution. One shared trap must
    not quarantine anyone (a poison request would take its innocent
    co-tenants down with it, even at quarantine_after=1); a second
    independent hit on the same fingerprint convicts. Prefill traps
    (exact) keep their configured threshold of 1 — see
    test_quarantine_after_n_traps."""
    with GenerationEngine(model, slots=2, max_len=32, rebuilds=8,
                          quarantine_after=1, step_wait_s=0.03) as eng:
        rs = np.random.RandomState(61)
        a = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        b = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        s0 = get_stat("gen/suspect_traps")
        for hit in (1, 2):
            # both streams must ride the SAME fused step when the trap
            # fires, or attribution degenerates to exact-by-pigeonhole
            g1, g2 = eng.start(a, 12), eng.start(b, 12)
            assert _wait(lambda: (len(eng.poll(g1)["tokens"]) > 0
                                  and len(eng.poll(g2)["tokens"]) > 0
                                  and not eng.poll(g1)["done"]
                                  and not eng.poll(g2)["done"]),
                         timeout=10.0)
            with fault.inject_faults({"engine.decode_step": (1.0, 1)}):
                _, err1 = _drain(eng, g1)
                _, err2 = _drain(eng, g2)
            assert err1 is not None and err2 is not None
            if hit == 1:
                # one ambiguous hit: suspects, not convicts — the next
                # round's eng.start(a/b) below must be admissible
                assert eng.stats()["quarantined"] == 0
        assert get_stat("gen/suspect_traps") >= s0 + 4
        # two independent ambiguous hits: now both are convicted
        with pytest.raises(RequestQuarantined):
            eng.start(a, 4)
        with pytest.raises(RequestQuarantined):
            eng.start(b, 4)
        assert eng.stats()["quarantined"] == 2


def test_watchdog_fails_stuck_generations(model):
    """A wedged decode loop (heartbeat older than gen_watchdog_s with
    active work) gets its generations failed loudly with the resumable
    reset marker, and new starts shed retryably while stuck."""
    with GenerationEngine(model, slots=1, max_len=32, rebuilds=2,
                          watchdog_s=5.0) as eng:
        rs = np.random.RandomState(38)
        prompt = rs.randint(0, VOCAB, (4,)).astype(np.int32)
        # warm the compiled paths under the generous deadline (XLA
        # compile IS a legitimate long step), then tighten it
        toks, err = _drain(eng, eng.start(prompt, 2))
        assert err is None
        eng._watchdog_s = 0.3
        # wedge the loop: monkeypatch the step to sleep well past the
        # watchdog (the loop thread blocks inside the "compiled call")
        real_step = eng._step

        def stuck_step(*a, **k):
            time.sleep(3.0)
            return real_step(*a, **k)

        eng._step = stuck_step
        stuck0 = get_stat("gen/stuck")
        gid = eng.start(prompt, 8)
        assert _wait(lambda: eng.poll(gid)["done"], timeout=5.0)
        doc = eng.poll(gid)
        assert doc["error"] is not None and "stuck" in doc["error"]
        assert RESET_MARKER in doc["error"]
        assert get_stat("gen/stuck") == stuck0 + 1
        eng._step = real_step
        # the loop rebuilds once the wedged call returns; re-admit works
        assert _wait(lambda: not eng.stats()["stuck"]
                     and eng.stats()["rebuilds"] >= 1, timeout=5.0)
        toks, err = _drain(eng, eng.start(prompt, 2))
        assert err is None and len(toks) == 2


# -- poll-TTL expiry + shed jitter ------------------------------------------

def test_poll_ttl_expiry_is_typed(model):
    """A poll landing after the TTL reap gets the typed
    GenerationExpired (still a KeyError for old handlers) — engine-level
    and across the wire — instead of the ambiguous unknown-id error."""
    eng = GenerationEngine(model, slots=1, max_len=32, ttl_s=0.3,
                           step_wait_s=0.05)
    srv = InferenceServer().start()
    srv.add_generator("llm", eng)
    client = InferenceClient(srv.endpoint)
    try:
        rs = np.random.RandomState(39)
        prompt = rs.randint(0, VOCAB, (4,)).astype(np.int32)
        gid = eng.start(prompt, 25)
        # TTL reaped (no polls); the loop only comes round to reaping
        # once the first prefill and step have compiled, which under a
        # loaded machine takes longer than the TTL by far
        assert _wait(lambda: eng.stats()["generations"] == 0,
                     timeout=20.0)
        with pytest.raises(GenerationExpired):
            eng.poll(gid)
        assert isinstance(GenerationExpired("x"), KeyError)
        with pytest.raises(GenerationExpired):
            client.generate_poll("llm", gid)
        # an id never seen here stays a plain unknown-id error
        with pytest.raises(RuntimeError, match="unknown generation"):
            client.generate_poll("llm", "deadbeef")
    finally:
        client.close()
        srv.stop()


def test_poll_refreshing_ttl_survives_reap_race(model):
    """A generation whose client IS polling never expires: the reap
    re-checks the TTL under the lock, so a poll that lands while retire
    walks its candidates keeps the stream alive."""
    with GenerationEngine(model, slots=1, max_len=32, ttl_s=0.4,
                          step_wait_s=0.02) as eng:
        rs = np.random.RandomState(40)
        prompt = rs.randint(0, VOCAB, (4,)).astype(np.int32)
        gid = eng.start(prompt, 20)
        toks, err = _drain(eng, gid, wait_s=0.1)   # poll faster than TTL
        assert err is None and len(toks) == 20


def test_shed_retry_after_carries_jitter(model):
    """Shed responses de-synchronize their retry hints: repeated sheds
    return varied retry_after_s within the jitter envelope."""
    with GenerationEngine(model, slots=1, max_len=32, queue_max=1,
                          step_wait_s=0.05) as eng:
        rs = np.random.RandomState(41)
        prompts = [rs.randint(0, VOCAB, (4,)).astype(np.int32)
                   for _ in range(3)]
        gids = [eng.start(p, 25) for p in prompts[:2]]  # 1 runs + 1 queued
        hints = []
        for _ in range(6):
            try:
                eng.start(prompts[2], 25)
                pytest.fail("expected EngineOverloaded")
            except Exception as e:
                hints.append(e.retry_after_s)
        assert len(set(hints)) > 1
        assert all(0.125 <= h <= 0.375 for h in hints)
        for g in gids:
            eng.cancel(g)


# -- deep health ------------------------------------------------------------

def test_deep_health_canary_distinguishes_engine_liveness(model):
    """health(deep=True) runs a one-token canary decode per generator:
    a wedged/broken engine reports ok=False while the wire-level status
    stays 'ok' — 'port open' and 'device healthy' are now separable."""
    eng = GenerationEngine(model, slots=2, max_len=32)
    srv = InferenceServer().start()
    srv.add_generator("llm", eng)
    client = InferenceClient(srv.endpoint)
    try:
        h = client.health(deep=True)
        probe = h["generators"]["llm"]["engine"]
        assert probe["ok"] and probe["latency_s"] > 0
        # shallow health never pays for a canary
        assert "engine" not in client.health()["generators"]["llm"]
        # brick the engine: the wire stays up, the deep probe notices
        with eng._cond:
            eng._broken = "induced for test"
        h = client.health(deep=True)
        assert h["status"] == "ok"                 # port open...
        assert not h["generators"]["llm"]["engine"]["ok"]   # device not
        with eng._cond:
            eng._broken = None
    finally:
        client.close()
        srv.stop()


# -- spawn circuit breaker --------------------------------------------------

class _FlakySpawner(ReplicaSpawner):
    """Spawner whose artifact is poisoned until told otherwise."""

    def __init__(self):
        self.calls = 0
        self.fail = True
        self.servers = []

    def spawn(self) -> str:
        self.calls += 1
        if self.fail:
            raise RuntimeError("poisoned artifact: replica crashed")
        srv = InferenceServer().start()
        self.servers.append(srv)
        return srv.endpoint

    def stop(self, endpoint: str, drain_s: float = 0.0) -> None:
        for srv in self.servers:
            if srv.endpoint == endpoint:
                srv.stop()

    def close(self):
        for srv in self.servers:
            srv.stop()


def test_spawn_breaker_opens_and_half_opens():
    """Consecutive spawn failures open the breaker (spawner NOT called,
    'spawn_breaker' decision recorded); after the backoff one half-open
    trial runs, and a success closes the breaker."""
    sp = _FlakySpawner()
    ctl = ServingController(sp, interval_s=0, min_replicas=0,
                            max_replicas=3, spawn_breaker=2,
                            spawn_backoff_s=0.2, cooldown_s=0)
    try:
        assert ctl._scale_up("t", {}).action == "spawn_failed"
        d = ctl._scale_up("t", {})
        assert d.action == "spawn_failed" and "OPEN" in d.reason
        assert sp.calls == 2
        # breaker open: the spawner is not even called
        d = ctl._scale_up("t", {})
        assert d.action == "spawn_breaker"
        assert sp.calls == 2
        time.sleep(0.25)                       # backoff elapses
        sp.fail = False                        # artifact fixed
        d = ctl._scale_up("t", {})             # half-open trial
        assert d.action == "scale_up" and sp.calls == 3
        assert ctl._spawn_fails == 0           # breaker closed
        actions = [x["action"] for x in ctl.decisions()]
        assert "spawn_breaker" in actions
    finally:
        ctl.close()
        sp.close()


def test_spawn_breaker_off_by_default():
    """control_spawn_breaker=0 (default): every attempt calls the
    spawner — the pre-resilience hot-loop behavior is opt-out only."""
    assert int(flag("control_spawn_breaker")) == 0
    sp = _FlakySpawner()
    ctl = ServingController(sp, interval_s=0, min_replicas=0,
                            max_replicas=3, cooldown_s=0)
    try:
        for _ in range(4):
            assert ctl._scale_up("t", {}).action == "spawn_failed"
        assert sp.calls == 4
    finally:
        ctl.close()


# -- defaults stay inert ----------------------------------------------------

def test_resilience_defaults_off(model):
    """Every new knob reads zero by default: no watchdog thread, no
    rebuilds, no quarantine books consulted, no resume wrapper — the
    unflagged path is the PR-7 behavior byte-identically."""
    for name in ("gen_resume_budget", "gen_quarantine_after",
                 "gen_engine_rebuilds", "control_spawn_breaker"):
        assert int(flag(name)) == 0, name
    assert float(flag("gen_watchdog_s")) == 0.0
    with GenerationEngine(model, slots=1, max_len=32) as eng:
        assert eng._watchdog is None
        assert eng._rebuild_max == 0 and eng._quarantine_after == 0
    srv = InferenceServer().start()
    srv.add_generator("llm", GenerationEngine(model, slots=1,
                                              max_len=32))
    router = RoutedClient([srv.endpoint], probe_interval_s=0)
    try:
        rs = np.random.RandomState(42)
        prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 4))[0, 5:]
        r0 = get_stat("serving/router/stream_resumes")
        toks = list(router.generate("llm", prompt, 4))
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        assert get_stat("serving/router/stream_resumes") == r0
    finally:
        router.close()
        srv.stop()


def test_poisoned_stream_quarantined_after_failover_hop(model):
    """Satellite (PR 8 NOTE): a resumed stream's replay prompt grew by
    the delivered tokens, so it used to hash a FRESH crash fingerprint
    on every hop — a poisoned stream could walk the fleet forever, one
    quarantine book at a time. The router now carries the ORIGINAL
    fingerprint through the resume path (header ``fp``), so the
    survivor that traps on the replay quarantines the original stream
    identity and the next resume attempt is rejected typed."""
    from paddle_tpu.serving.engine import stream_fingerprint

    servers, engines = [], []
    for _ in range(2):
        eng = GenerationEngine(model, slots=2, max_len=32,
                               step_wait_s=0.03, rebuilds=4,
                               quarantine_after=1)
        srv = InferenceServer().start()
        srv.add_generator("llm", eng)
        servers.append(srv)
        engines.append(eng)
    router = RoutedClient([s.endpoint for s in servers],
                          probe_interval_s=0)
    try:
        rs = np.random.RandomState(51)
        prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        fp = stream_fingerprint(prompt)

        sess = router.session("poison-stream")
        it = sess.generate("llm", prompt, 10, poll_wait_s=0.05,
                           resume_budget=2)
        toks = [next(it)]                      # live on the pinned replica
        pinned = sess.endpoint
        victim = next(s for s in servers if s.endpoint == pinned)
        survivor = next(e for s, e in zip(servers, engines)
                        if s.endpoint != pinned)
        victim.stop()                          # hop 1: replica death
        # the resumed replay traps on the survivor: without the fp
        # carry it would quarantine hash(prompt + delivered) and the
        # NEXT resume would walk the poison right back in
        with fault.inject_faults({"engine.decode_step": (1.0, 1)}):
            with pytest.raises(RequestQuarantined):
                toks += list(it)
        assert fp in survivor._quarantined     # the ORIGINAL identity
        assert survivor.stats()["quarantined"] == 1
        # the poison is now rejected under its original prompt too
        with pytest.raises(RequestQuarantined):
            survivor.start(prompt, 4)
    finally:
        router.close()
        for s in servers:
            s.stop()
