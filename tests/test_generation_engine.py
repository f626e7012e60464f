"""Continuous-batching generation engine: slot scheduling, streaming
wire ops, session-sticky routing, and the early-exit decode loop.

The load-bearing property is determinism: a greedy generation through
the slot engine — admitted into a shared batched KV cache, stepped
alongside arbitrary co-tenants, prefetched through a right-padded
bucket — must be byte-identical to a solo
``models.generation.generate`` call.
"""

import collections
import threading
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.flags import flag, set_flags
from paddle_tpu.core.monitor import get_stat
from paddle_tpu.core.wire import WireShedError
from paddle_tpu.io.serving import InferenceClient, InferenceServer
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate
from paddle_tpu.serving import (
    EngineOverloaded, GenerationEngine, GenerationFailed, RoutedClient,
)

pytestmark = pytest.mark.gen

VOCAB = 96


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def engine(model):
    with GenerationEngine(model, slots=3, max_len=32, queue_max=4,
                          ttl_s=10.0) as eng:
        yield eng


@pytest.fixture(scope="module")
def server(model, engine):
    srv = InferenceServer().start()
    srv.add_generator("llm", engine)   # pre-built engine: no recompile
    client = InferenceClient(srv.endpoint)
    yield srv, client
    client.close()
    srv.stop()


def _drain(engine, gen_id, wait_s=0.5):
    toks, n = [], 0
    while True:
        doc = engine.poll(gen_id, start=n, wait_s=wait_s)
        toks += doc["tokens"]
        n = len(toks)
        if doc["done"]:
            return toks, doc["error"]


def _wait_active(engine, pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred(engine.stats()):
            return True
        time.sleep(0.02)
    return False


def test_interleaved_matches_solo_generate(model, engine):
    """8 concurrent greedy generations through 3 slots (queueing forces
    admits/retires mid-flight) are byte-identical to solo generate()."""
    rs = np.random.RandomState(1)
    prompts = rs.randint(0, VOCAB, (8, 6)).astype(np.int32)
    ref = np.asarray(generate(model, prompts, 5))[:, 6:]

    out = {}

    def worker(i):
        gid = None
        while gid is None:
            try:
                gid = engine.start(prompts[i], 5)
            except EngineOverloaded as e:
                time.sleep(e.retry_after_s)
        out[i] = _drain(engine, gid)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for i in range(8):
        toks, err = out[i]
        assert err is None
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref[i],
                                      err_msg=f"request {i}")
    st = engine.stats()
    assert st["active"] == 0 and st["queued"] == 0


def test_variable_lengths_and_late_admit(model, engine):
    """Different prompt lengths (different prefill buckets) and a late
    admit into a freed slot still match solo generate exactly."""
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, VOCAB, (n,)).astype(np.int32)
               for n in (3, 9, 5)]
    gids = [engine.start(p, 4) for p in prompts]
    outs = [_drain(engine, g) for g in gids]
    for p, (toks, err) in zip(prompts, outs):
        assert err is None
        ref = np.asarray(generate(model, p[None], 4))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)


def test_eos_retires_slot_early(model, engine):
    """A request whose eos fires mid-stream stops there (stream ends
    with eos) and frees its slot without running to max_new_tokens."""
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, VOCAB, (6,)).astype(np.int32)
    ref = np.asarray(generate(model, prompt[None], 6))[0, 6:]
    eos = int(ref[2])                        # finish after 3 tokens
    gid = engine.start(prompt, 6, eos_token_id=eos)
    toks, err = _drain(engine, gid)
    assert err is None
    np.testing.assert_array_equal(np.asarray(toks, np.int32), ref[:3])
    assert engine.stats()["active"] == 0


def test_cancel_frees_slot_others_uninterrupted(model, engine):
    rs = np.random.RandomState(4)
    p_a = rs.randint(0, VOCAB, (5,)).astype(np.int32)
    p_b = rs.randint(0, VOCAB, (5,)).astype(np.int32)
    ref_b = np.asarray(generate(model, p_b[None], 10))[0, 5:]
    ev0 = get_stat("gen/evictions")
    engine.step_wait_s = 0.02     # pace the loop so "mid-flight" exists
    try:
        gid_a = engine.start(p_a, 20)
        gid_b = engine.start(p_b, 10)
        # let both stream a little, then cancel A mid-flight
        while len(engine.poll(gid_a, wait_s=0.5)["tokens"]) < 2:
            pass
        assert engine.cancel(gid_a)
        toks_b, err_b = _drain(engine, gid_b)
    finally:
        engine.step_wait_s = 0.0
    assert err_b is None
    np.testing.assert_array_equal(np.asarray(toks_b, np.int32), ref_b)
    doc = engine.poll(gid_a) if gid_a in engine._gens else None
    assert doc is None                      # cancelled gens are dropped
    assert get_stat("gen/evictions") == ev0 + 1
    assert _wait_active(engine, lambda s: s["active"] == 0)


def test_full_engine_sheds_start(model, engine):
    """slots busy + queue at queue_max -> EngineOverloaded (retryable),
    and capacity returns once generations are cancelled."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, VOCAB, (4,)).astype(np.int32)
               for _ in range(7)]
    engine.step_wait_s = 0.03     # keep slots visibly busy
    try:
        gids = [engine.start(p, 25) for p in prompts]  # 3 run + 4 queue
        assert _wait_active(engine, lambda s: s["active"] == 3
                            and s["queued"] >= 4)
        with pytest.raises(EngineOverloaded) as ei:
            engine.start(prompts[0], 25)
        assert ei.value.retry_after_s > 0
        for g in gids:
            engine.cancel(g)
    finally:
        engine.step_wait_s = 0.0
    assert _wait_active(engine, lambda s: s["active"] == 0
                        and s["queued"] == 0)
    gid = engine.start(prompts[0], 2)               # works again
    toks, err = _drain(engine, gid)
    assert err is None and len(toks) == 2


def test_poll_ttl_reaps_disconnected_client(model, engine):
    """A generation whose client stops polling is evicted after the TTL
    and its slot reclaimed — the disconnect story."""
    old = engine._ttl_s
    engine._ttl_s = 0.3
    engine.step_wait_s = 0.05     # generation outlives the TTL window
    try:
        rs = np.random.RandomState(6)
        gid = engine.start(rs.randint(0, VOCAB, (4,)).astype(np.int32),
                           25)
        assert _wait_active(engine, lambda s: s["active"] == 1)
        ev0 = get_stat("gen/evictions")
        # no polls -> TTL expires -> slot freed, gen forgotten
        assert _wait_active(engine, lambda s: s["active"] == 0
                            and s["generations"] == 0, timeout=3.0)
        assert get_stat("gen/evictions") >= ev0 + 1
        with pytest.raises(KeyError):
            engine.poll(gid)
    finally:
        engine._ttl_s = old
        engine.step_wait_s = 0.0


def test_sampled_generation_is_per_request_deterministic(model, engine):
    """Sampling params are per-slot traced state: the same (prompt,
    seed) yields the same stream regardless of co-tenants."""
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
    runs = []
    for _ in range(2):
        gid = engine.start(prompt, 6, temperature=0.8, top_k=7,
                           top_p=0.9, seed=42)
        toks, err = _drain(engine, gid)
        assert err is None
        runs.append(toks)
    assert runs[0] == runs[1]
    assert all(0 <= t < VOCAB for t in runs[0])


def test_engine_requires_slots_flag(model):
    """FLAGS_gen_slots=0 (default) keeps generation serving off: no
    engine, no background thread, the serving path untouched."""
    assert int(flag("gen_slots")) == 0
    with pytest.raises(ValueError, match="gen_slots"):
        GenerationEngine(model)
    with pytest.raises(ValueError, match="gen_slots"):
        InferenceServer().add_generator("llm", model)
    set_flags({"gen_slots": 2})
    try:
        eng = GenerationEngine(model, max_len=32)
        assert eng.slots == 2
        eng.close()
    finally:
        set_flags({"gen_slots": 0})


def test_start_validates_capacity(model, engine):
    with pytest.raises(ValueError, match="capacity"):
        engine.start(np.arange(10, dtype=np.int32), 30)   # 40 > 32
    with pytest.raises(ValueError, match="empty"):
        engine.start(np.zeros((0,), np.int32), 4)


def test_wire_stream_and_health(model, engine, server):
    """Client streaming iterator over the wire matches solo generate;
    health reports slot occupancy; breaking the stream cancels
    server-side so the slot frees immediately."""
    srv, client = server
    rs = np.random.RandomState(8)
    prompt = rs.randint(0, VOCAB, (6,)).astype(np.int32)
    ref = np.asarray(generate(model, prompt[None], 5))[0, 6:]
    toks = list(client.generate("llm", prompt, 5))
    np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)

    h = client.health()
    assert h["generators"]["llm"]["slots"] == 3

    it = client.generate("llm", prompt, 25)
    assert next(it) == int(ref[0])
    it.close()                              # break mid-stream -> cancel
    assert _wait_active(engine, lambda s: s["active"] == 0)


def test_wire_full_engine_sheds_with_retry_hint(model, engine, server):
    """A full engine sheds generate_start with CODE_SHED +
    retry_after_s — the typed, retryable WireShedError a no-retry
    client surfaces (never an opaque error; the start never ran) — and
    capacity returns once generations are cancelled."""
    srv, client = server
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, VOCAB, (4,)).astype(np.int32)
               for _ in range(7)]
    engine.step_wait_s = 0.03
    try:
        gids = [engine.start(p, 25) for p in prompts]
        assert _wait_active(engine, lambda s: s["active"] == 3
                            and s["queued"] >= 4)
        c0 = InferenceClient(srv.endpoint, retries=0)
        try:
            with pytest.raises(WireShedError, match="engine full"):
                c0.generate_start("llm", prompts[0], 25)
        finally:
            c0.close()
        for g in gids:
            engine.cancel(g)
        assert _wait_active(engine, lambda s: s["active"] == 0)
    finally:
        engine.step_wait_s = 0.0
    toks = list(client.generate("llm", prompts[0], 2))
    assert len(toks) == 2                   # capacity returned


def test_wire_unknown_generator_and_generation(server):
    srv, client = server
    with pytest.raises(RuntimeError, match="no generator"):
        client.generate_start("nope", [1, 2, 3], 4)
    with pytest.raises(RuntimeError, match="unknown generation"):
        client.generate_poll("llm", "deadbeef")


# -- paged KV cache + prefix sharing + chunked prefill ----------------------

@pytest.fixture(scope="module")
def paged_engine(model):
    """Paged mode with deliberately awkward geometry: 8-token pages,
    3-token prefill chunks (page- and chunk-misaligned prompts), pool
    sized to the contiguous equivalent."""
    with GenerationEngine(model, slots=3, max_len=32, queue_max=32,
                          ttl_s=10.0, paged=True, page_tokens=8,
                          prefill_chunk=3) as eng:
        yield eng


def test_paged_interleaved_matches_solo_generate(model, paged_engine):
    """8 concurrent greedy generations through 3 paged slots — admits,
    retires, page reuse, and chunked prefill all mid-flight — are
    byte-identical to solo generate()."""
    rs = np.random.RandomState(21)
    prompts = rs.randint(0, VOCAB, (8, 6)).astype(np.int32)
    ref = np.asarray(generate(model, prompts, 5))[:, 6:]
    out = {}

    def worker(i):
        gid = None
        while gid is None:
            try:
                gid = paged_engine.start(prompts[i], 5)
            except EngineOverloaded as e:
                time.sleep(e.retry_after_s)
        out[i] = _drain(paged_engine, gid)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for i in range(8):
        toks, err = out[i]
        assert err is None
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref[i],
                                      err_msg=f"request {i}")
    st = paged_engine.stats()
    assert st["active"] == 0 and st["queued"] == 0
    # every non-shared page came back (6+5 = 11 tokens < 1 full page of
    # prompt -> nothing prefix-cacheable here)
    assert st["pages_free"] == st["pages"]


def test_paged_prefix_sharing_matches_solo(model, paged_engine):
    """Generations sharing a 17-token prompt prefix (2 full 8-token
    pages) map their early pages to the same physical pages: prefill
    runs once per unique prefix, and each stream is still
    byte-identical to its solo generate()."""
    from paddle_tpu.core.monitor import get_stat

    rs = np.random.RandomState(22)
    prefix = rs.randint(0, VOCAB, (17,)).astype(np.int32)
    hits0 = get_stat("gen/prefix_hits")
    saved0 = get_stat("gen/prefix_tokens_saved")
    for t in range(3):
        tail = rs.randint(0, VOCAB, (3,)).astype(np.int32)
        p = np.concatenate([prefix, tail])
        ref = np.asarray(generate(model, p[None], 4))[0, len(p):]
        gid = paged_engine.start(p, 4)
        toks, err = _drain(paged_engine, gid)
        assert err is None
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref,
                                      err_msg=f"stream {t}")
    # streams 2 and 3 each matched the 2 cached prefix pages
    assert get_stat("gen/prefix_hits") == hits0 + 2
    assert get_stat("gen/prefix_tokens_saved") == saved0 + 2 * 2 * 8
    st = paged_engine.stats()
    assert st["prefix_entries"] >= 2
    # cached pages are the only ones still held
    assert st["pages_free"] == st["pages"] - st["prefix_entries"]
    paged_engine.clear_prefix_cache()
    assert paged_engine.stats()["pages_free"] == st["pages"]


def test_paged_long_prompt_chunked_prefill_matches_solo(model,
                                                        paged_engine):
    """A prompt spanning many 3-token chunks and several pages prefills
    in slices and still matches solo generate() exactly; the chunk
    histogram proves the slicing actually happened."""
    from paddle_tpu.core.monitor import get_histogram

    rs = np.random.RandomState(23)
    p = rs.randint(0, VOCAB, (26,)).astype(np.int32)
    ref = np.asarray(generate(model, p[None], 5))[0, 26:]
    h0 = (get_histogram("gen/prefill_chunk_s") or {}).get("count", 0)
    gid = paged_engine.start(p, 5)
    toks, err = _drain(paged_engine, gid)
    assert err is None
    np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
    h1 = get_histogram("gen/prefill_chunk_s")["count"]
    assert h1 - h0 >= 9                     # ceil(26 / 3) chunks


def test_paged_sampled_deterministic_per_seed(model, paged_engine):
    rs = np.random.RandomState(24)
    prompt = rs.randint(0, VOCAB, (9,)).astype(np.int32)
    runs = []
    for _ in range(2):
        gid = paged_engine.start(prompt, 6, temperature=0.8, top_k=7,
                                 top_p=0.9, seed=42)
        toks, err = _drain(paged_engine, gid)
        assert err is None
        runs.append(toks)
    assert runs[0] == runs[1]
    assert all(0 <= t < VOCAB for t in runs[0])


def test_paged_defaults_off_keeps_contiguous_layout(model):
    """FLAGS_gen_paged=0 (default) leaves the PR-5 contiguous engine in
    place: per-slot [slots, L, 1, Hkv, S, D] cache, no pool, no page
    tables."""
    assert not flag("gen_paged")
    with GenerationEngine(model, slots=2, max_len=32) as eng:
        assert not eng._paged
        assert eng._pool is None and eng._pt is None
        leaf = eng._state["cache"][0]
        assert leaf.shape[0] == 2 and leaf.shape[4] == 32
        assert not eng.stats()["paged"]
    set_flags({"gen_paged": True})
    try:
        with GenerationEngine(model, slots=2, max_len=32) as eng:
            assert eng._paged and eng.stats()["paged"]
            # default pool = slots x ceil(max_len / page_tokens)
            assert eng.stats()["pages"] == 2 * -(-32 // int(
                flag("gen_page_tokens")))
    finally:
        set_flags({"gen_paged": False})


def test_paged_wire_stream_and_health(model, paged_engine):
    """The wire path is mode-agnostic: streaming over a paged engine
    matches solo generate, and health ships page-pool occupancy."""
    srv = InferenceServer().start()
    srv.add_generator("pllm", paged_engine)
    client = InferenceClient(srv.endpoint)
    try:
        rs = np.random.RandomState(25)
        prompt = rs.randint(0, VOCAB, (7,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 5))[0, 7:]
        toks = list(client.generate("pllm", prompt, 5))
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        g = client.health()["generators"]["pllm"]
        assert g["paged"] and g["pages"] > 0
        assert g["pages_free"] + g["prefix_entries"] >= g["pages"] - 1
    finally:
        client.close()
        # the engine is module-scoped: detach it before stopping so the
        # server does not close it for later tests
        with srv._lock:
            srv._generators.clear()
        srv.stop()


# -- session-sticky routing -------------------------------------------------

def test_session_sticky_pick_and_repick_on_loss():
    """Same session id -> same replica while membership holds; member
    loss re-picks only when no generation is in flight."""
    servers = [InferenceServer().start() for _ in range(3)]
    router = RoutedClient([s.endpoint for s in servers],
                          probe_interval_s=0)
    try:
        s1 = router.session("sess-abc")
        s2 = router.session("sess-abc")
        assert s1.health()["status"] == "ok"
        assert s2.health()["status"] == "ok"
        assert s1.endpoint == s2.endpoint      # deterministic pin
        pinned = s1.endpoint
        for _ in range(3):
            s1.health()
            assert s1.endpoint == pinned       # sticky across ops

        router.remove_endpoint(pinned)
        s1.health()                            # member loss -> re-pick
        assert s1.endpoint is not None and s1.endpoint != pinned

        # an in-flight generation must NOT re-pick silently
        s3 = router.session("sess-xyz")
        s3.health()
        s3._active = 1
        router.remove_endpoint(s3.endpoint)
        with pytest.raises(GenerationFailed) as ei:
            s3.health()
        assert ei.value.endpoint not in router.endpoints()
    finally:
        router.close()
        for s in servers:
            s.stop()


@pytest.mark.slow
def test_session_generate_no_silent_failover(model):
    """Kill the replica holding a generation mid-stream: the session
    surfaces GenerationFailed naming the replica (never silently
    reroutes the poll), and a restart on the survivor succeeds."""
    paddle_tpu.seed(7)
    servers = []
    for _ in range(2):
        srv = InferenceServer().start()
        srv.add_generator("llm", model, slots=2, max_len=32)
        servers.append(srv)
    router = RoutedClient([s.endpoint for s in servers],
                          probe_interval_s=0)
    try:
        rs = np.random.RandomState(10)
        prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 4))[0, 5:]
        sess = router.session("victim")
        it = sess.generate("llm", prompt, 25, poll_wait_s=0.05)
        next(it)
        pinned = sess.endpoint
        victim = next(s for s in servers if s.endpoint == pinned)
        victim.stop()
        with pytest.raises(GenerationFailed) as ei:
            list(it)
        assert ei.value.endpoint == pinned

        sess2 = router.session("survivor-run")
        toks = list(sess2.generate("llm", prompt, 4))
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        assert sess2.endpoint != pinned
    finally:
        router.close()
        for s in servers:
            s.stop()


# -- generate(): while_loop early exit --------------------------------------

def _fori_reference(model, input_ids, max_new_tokens, *, temperature=0.0,
                    eos_token_id=None, pad_token_id=0, key=None):
    """The pre-while_loop decode loop (fixed trip count), kept here as
    the regression reference for the early-exit rewrite."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.generation import sample_logits

    input_ids = jnp.asarray(input_ids, jnp.int32)
    B, T0 = input_ids.shape
    S = T0 + int(max_new_tokens)
    cache = model.init_cache(B, S, dtype=None)
    logits, cache = model.forward_with_cache(input_ids, cache, index=0)
    seq = jnp.concatenate(
        [input_ids, jnp.full((B, max_new_tokens), pad_token_id,
                             jnp.int32)], axis=1)
    if key is None:
        key = jax.random.PRNGKey(0)

    def pick(logits, key):
        return sample_logits(logits, None if temperature == 0.0 else key,
                             temperature=temperature)

    key, sub = jax.random.split(key)
    next_tok = pick(logits[:, -1], sub)
    finished = jnp.zeros((B,), bool)
    if eos_token_id is not None:
        finished = next_tok == eos_token_id
    seq = jax.lax.dynamic_update_slice(seq, next_tok[:, None], (0, T0))

    def body(i, state):
        seq, cache, prev_tok, finished, key = state
        logits, cache = model.forward_with_cache(
            prev_tok[:, None], cache, index=T0 + i - 1)
        key, sub = jax.random.split(key)
        tok = pick(logits[:, -1], sub)
        if eos_token_id is not None:
            tok = jnp.where(finished, pad_token_id, tok)
            finished = finished | (tok == eos_token_id)
        seq = jax.lax.dynamic_update_slice(seq, tok[:, None], (0, T0 + i))
        return seq, cache, tok, finished, key

    if max_new_tokens > 1:
        seq, *_ = jax.lax.fori_loop(1, max_new_tokens, body,
                                    (seq, cache, next_tok, finished, key))
    return seq


def test_generate_while_matches_fori_reference(model):
    """The while_loop rewrite is output-identical to the old fixed-trip
    fori_loop — with an eos that fires early, and without one."""
    import jax

    rs = np.random.RandomState(11)
    prompt = rs.randint(0, VOCAB, (2, 5)).astype(np.int32)
    # greedy, eos chosen so one row finishes early
    base = np.asarray(generate(model, prompt, 8))
    eos = int(base[0, 5 + 2])
    got = np.asarray(generate(model, prompt, 8, eos_token_id=eos))
    want = np.asarray(_fori_reference(model, prompt, 8,
                                      eos_token_id=eos))
    np.testing.assert_array_equal(got, want)
    # sampled, no eos: full trip count, same key schedule
    key = jax.random.PRNGKey(3)
    got = np.asarray(generate(model, prompt, 6, temperature=0.7,
                              key=key))
    want = np.asarray(_fori_reference(model, prompt, 6, temperature=0.7,
                                      key=key))
    np.testing.assert_array_equal(got, want)


def test_generate_while_exits_early():
    """The loop really stops once every row finished: a callback-counting
    fake model sees ~2 forward calls, not max_new_tokens."""
    import jax
    import jax.numpy as jnp

    EOS, V = 3, 8
    calls = []

    class FakeModel:
        def init_cache(self, B, S, dtype=None):
            return (jnp.zeros((1, B, 1, S, 1), jnp.float32),) * 2

        def forward_with_cache(self, ids, cache, index):
            B, T = ids.shape

            def emit(ids_np):
                calls.append(1)
                logits = np.zeros((B, T, V), np.float32)
                logits[:, :, EOS] = 1.0           # always pick EOS
                return logits

            logits = jax.pure_callback(
                emit, jax.ShapeDtypeStruct((B, T, V), jnp.float32), ids)
            return logits, cache

    out = generate(FakeModel(), np.ones((2, 3), np.int32), 10,
                   eos_token_id=EOS)
    assert out.shape == (2, 13)
    # prefill picks EOS for every row -> finished before the loop; the
    # old fori_loop would have called forward 10 times regardless
    assert sum(calls) <= 2, f"loop did not exit early: {sum(calls)} calls"
    assert int(out[0, 3]) == EOS and int(out[0, 4]) == 0


def test_engine_programs_carry_kv_and_sample_scopes(paged_engine, model):
    """The step and prefill programs name their KV gather, KV write and
    sampler (``op_name`` metadata; nothing else about the programs
    changes), and the model's attention block names itself."""
    for name, text in paged_engine.lowered_text(6).items():
        for scope in ("kv/gather", "kv/write", "sample", "attn"):
            assert scope in text, f"{scope} missing from {name}"
    with GenerationEngine(model, slots=2, max_len=32, queue_max=4) as eng:
        for name, text in eng.lowered_text(6).items():
            for scope in ("kv/write", "sample", "attn"):
                assert scope in text, f"{scope} missing from {name}"


# -- a poll waits on its own stream's wake-up, not on the engine's lock ---

class _HeldLoop(GenerationEngine):
    """An engine whose loop does nothing: streams stay where the test
    seats them, and the test hands them tokens and ends itself."""

    def _iterate(self, jnp, it):
        time.sleep(0.002)
        return self._stopping


def _seat(eng, n, max_new=8, eos=None):
    """``n`` started streams moved from the queue into slots 0..n-1."""
    gids = [eng.start(np.arange(1, 4, dtype=np.int32), max_new,
                      eos_token_id=eos) for _ in range(n)]
    with eng._cond:
        gens = [eng._gens[g] for g in gids]
        for s, g in enumerate(gens):
            eng._queue.remove(g)
            eng._slot_gen[s], g.slot = g, s
    return gids, gens


def _waiting_polls(eng, gids, gens, wait_s=5.0):
    """A thread a stream, each blocked in ``poll(wait_s=...)``; returns
    the threads and ``{i: (doc, monotonic time it returned)}``."""
    out = {}

    def run(i, gid):
        doc = eng.poll(gid, wait_s=wait_s)
        out[i] = (doc, time.monotonic())

    threads = [threading.Thread(target=run, args=(i, g), daemon=True,
                                name=f"poller-{i}")
               for i, g in enumerate(gids)]
    [t.start() for t in threads]
    deadline = time.monotonic() + 5.0
    while any(g.waiting == 0 for g in gens):
        assert time.monotonic() < deadline, "polls never waited"
        time.sleep(0.005)
    return threads, out


class _CountingCond:
    """The engine's condition, counting ``with`` entries by thread."""

    def __init__(self, cond):
        self._inner = cond
        self.takes = collections.Counter()

    def __enter__(self):
        self.takes[threading.current_thread().name] += 1
        return self._inner.__enter__()

    def __exit__(self, *exc):
        return self._inner.__exit__(*exc)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture
def held(model):
    eng = _HeldLoop(model, slots=4, max_len=32, ttl_s=30.0)
    yield eng
    eng.close()


def test_a_delivery_wakes_its_own_stream_alone(held):
    gids, gens = _seat(held, 4)
    threads, out = _waiting_polls(held, gids, gens)
    before, wakes0 = held.stats()["poll"], get_stat("gen/poll_wakes")
    held._emit_step([(0, gens[0])], 0.0, [7, 0, 0, 0])
    threads[0].join(1.0)
    assert not threads[0].is_alive()
    assert out[0][0]["tokens"] == [7] and not out[0][0]["done"]
    after = held.stats()["poll"]
    assert after["wakes"] == before["wakes"] + 1
    assert after["wakes_empty"] == before["wakes_empty"]
    assert get_stat("gen/poll_wakes") == wakes0 + 1
    time.sleep(0.05)            # the other three were not woken
    assert all(t.is_alive() for t in threads[1:])
    assert [g.waiting for g in gens[1:]] == [1, 1, 1]
    for g in gids[1:]:
        held.cancel(g)
    [t.join(1.0) for t in threads]
    assert [out[i][0]["error"] for i in (1, 2, 3)] == ["cancelled"] * 3
    assert held.stats()["poll"]["waits"] == before["waits"] + 4


def test_a_waiting_poll_neither_holds_nor_takes_the_engine_lock(held):
    gids, gens = _seat(held, 4)
    held._cond = counting = _CountingCond(held._cond)
    threads, out = _waiting_polls(held, gids, gens)
    t0 = time.monotonic()
    with held._cond:                # never behind a poller
        took = time.monotonic() - t0
    assert took < 0.05
    held._emit_step([(s, g) for s, g in enumerate(gens)], 0.0,
                    [11, 12, 13, 14])
    [t.join(1.0) for t in threads]
    assert [out[i][0]["tokens"] for i in range(4)] == [[11], [12], [13],
                                                       [14]]
    assert not any(n for name, n in counting.takes.items()
                   if name.startswith("poller-"))


@pytest.mark.parametrize("end", ["complete", "eos", "cancel", "reap",
                                 "break", "close", "rebuild"])
def test_every_end_returns_a_waiting_poll(model, end):
    from paddle_tpu.serving.engine import EXPIRED_MARKER, RESET_MARKER

    eng = _HeldLoop(model, slots=2, max_len=32, ttl_s=30.0)
    try:
        gids, gens = _seat(eng, 1, max_new=1 if end == "complete" else 8,
                           eos=9 if end == "eos" else None)
        threads, out = _waiting_polls(eng, gids, gens)
        if end == "reap":
            eng._ttl_s = 0.01
            time.sleep(0.02)
        t0 = time.monotonic()
        if end in ("complete", "eos"):
            eng._emit_step([(0, gens[0])], 0.0,
                           [5 if end == "complete" else 9, 0])
        elif end == "cancel":
            eng.cancel(gids[0])
        elif end == "reap":
            eng._reap_expired()
        elif end == "break":
            eng._break(RuntimeError("boom"))
        elif end == "close":
            eng.close()
        else:
            eng._rebuild(RuntimeError("trap"))
        threads[0].join(1.0)
        assert not threads[0].is_alive()
        doc, t1 = out[0]
        assert t1 - t0 < 0.5 and doc["done"]
        err = doc["error"]
        if end in ("complete", "eos"):
            assert err is None
            assert doc["tokens"] == [5 if end == "complete" else 9]
        else:
            assert err == {"cancel": "cancelled",
                           "break": "RuntimeError: boom",
                           "close": "engine stopped"}.get(end, err)
            assert end not in ("reap", "rebuild") or err.startswith(
                EXPIRED_MARKER if end == "reap" else RESET_MARKER)
        assert eng.stats()["poll"] == {"waits": 1, "wakes": 1,
                                       "wakes_empty": 0}
    finally:
        eng.close()


def test_two_polls_of_one_stream_both_return(held):
    """A second poll of the same stream (a client that retried) is not
    left waiting out its time: the first to wake hands the wake-up on."""
    gids, gens = _seat(held, 1)
    threads, out = _waiting_polls(held, gids * 2, gens * 2)
    deadline = time.monotonic() + 5.0
    while gens[0].waiting < 2:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    t0 = time.monotonic()
    held._emit_step([(0, gens[0])], 0.0, [3, 0, 0, 0])
    [t.join(1.0) for t in threads]
    assert [out[i][0]["tokens"] for i in (0, 1)] == [[3], [3]]
    assert max(out[i][1] for i in (0, 1)) - t0 < 0.5
