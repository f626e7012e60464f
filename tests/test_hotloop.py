"""Decode hot-loop overhaul: device-resident page tables
(``FLAGS_gen_device_pt``) and async double-buffered dispatch
(``FLAGS_gen_async_depth``).

The load-bearing contract is the same byte-identity the engine has
always promised, now under lookahead: dispatching step ``i+1`` before
step ``i``'s token readback must not change a single token of any
stream — greedy or sampled, paged or contiguous, device-resident table
or host upload — because the autoregressive chain feeds itself on
device and the host bookkeeping only ever runs against tokens that HAVE
been read back. Cancel/TTL/failover land at most ``depth`` steps late,
which is safe (post-EOS steps write pads to pages the dying generation
still owns) and must leave the pool exactly full.
"""

import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core import trace
from paddle_tpu.core.flags import flag, get_flags, set_flags
from paddle_tpu.core.monitor import get_histogram
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate
from paddle_tpu.serving import GenerationEngine

pytestmark = [pytest.mark.gen, pytest.mark.hotloop]

VOCAB = 96
SAMPLE_KW = dict(temperature=0.8, top_k=7, top_p=0.9, seed=42)


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


def _wait(engine, pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred(engine.stats()):
            return True
        time.sleep(0.02)
    return False


def _sampled_ref(model, prompt, n):
    import jax
    return np.asarray(generate(
        model, prompt[None], n, temperature=SAMPLE_KW["temperature"],
        top_k=SAMPLE_KW["top_k"], top_p=SAMPLE_KW["top_p"],
        key=jax.random.PRNGKey(SAMPLE_KW["seed"])))[0, prompt.size:]


# -- byte identity across the whole flag grid -------------------------------

def test_byte_identity_grid_matches_solo_generate(model):
    """{paged, contiguous} x {greedy, sampled} x async_depth {0,1,2} x
    device_pt {off,on}: every engine config reproduces solo
    ``generate()`` byte-for-byte — lookahead and the device-resident
    table change WHERE work happens, never a token."""
    rs = np.random.RandomState(1)
    prompts = rs.randint(0, VOCAB, (4, 6)).astype(np.int32)
    greedy_ref = np.asarray(generate(model, prompts, 5))[:, 6:]
    s_prompt = rs.randint(0, VOCAB, (6,)).astype(np.int32)
    sampled_ref = _sampled_ref(model, s_prompt, 6)

    configs = [(paged, pt, depth)
               for paged in (False, True)
               for pt in ((False, True) if paged else (False,))
               for depth in (0, 1, 2)]
    for paged, pt, depth in configs:
        tag = f"paged={paged} device_pt={pt} depth={depth}"
        kw = dict(paged=paged, device_pt=pt, async_depth=depth)
        if paged:
            kw.update(page_tokens=8, pages=24)
        with GenerationEngine(model, slots=2, max_len=32, queue_max=8,
                              **kw) as eng:
            st = eng.stats()
            assert st["async_depth"] == depth and st["device_pt"] == (
                paged and pt), tag
            gids = [eng.start(p, 5) for p in prompts]
            for i, g in enumerate(gids):
                toks, err = _drain(eng, g)
                assert err is None, tag
                np.testing.assert_array_equal(
                    np.asarray(toks, np.int32), greedy_ref[i],
                    err_msg=tag)
            toks, err = _drain(eng, eng.start(s_prompt, 6, **SAMPLE_KW))
            assert err is None, tag
            np.testing.assert_array_equal(
                np.asarray(toks, np.int32), sampled_ref, err_msg=tag)
            # the trailing lagged step (pad writes only) drains on the
            # next idle loop pass
            assert _wait(eng, lambda s: s["pending_steps"] == 0), tag


# -- delivery: a token reaches a stream, and a stream ends, in one place ------

LAYOUTS = {"contiguous": dict(),
           "paged_chunked": dict(paged=True, page_tokens=8, pages=24,
                                 prefill_chunk=4)}


@pytest.fixture
def tracing():
    saved = get_flags(["trace", "trace_buffer"])
    trace.clear()
    set_flags({"trace_buffer": 4096, "trace": True})
    yield
    set_flags(saved)
    trace.clear()


@pytest.mark.parametrize("depth", (0, 1))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_delivery_ends_each_stream_once(model, tracing, layout, depth):
    """A stream that ends at its first token (the prefill's), one that
    ends on EOS mid-stream and one that runs to ``max_new_tokens``:
    each delivers solo ``generate()``'s tokens up to its end and not
    one past it (a lagged step's post-EOS token included), retires
    with exactly one ``gen/retire`` carrying its token count, observes
    ``gen/ttft_s`` once, and leaves its slot and pages free."""
    prompt = np.random.RandomState(3).randint(0, VOCAB, (6,)).astype(
        np.int32)
    ref = [int(t) for t in np.asarray(
        generate(model, prompt[None], 6))[0, 6:]]
    k = next(i for i in range(1, 6) if ref[i] not in ref[:i])
    ends = {"first_token": (dict(max_new_tokens=1), ref[:1]),
            "eos": (dict(max_new_tokens=6, eos_token_id=ref[k]),
                    ref[:k + 1]),
            "max_new": (dict(max_new_tokens=5), ref[:5])}
    with GenerationEngine(model, slots=2, max_len=32, queue_max=8,
                          async_depth=depth, **LAYOUTS[layout]) as eng:
        for end, (kw, want) in ends.items():
            tag = f"{layout} depth={depth} {end}"
            ttft0 = (get_histogram("gen/ttft_s") or {"count": 0})["count"]
            toks, err = _drain(eng, eng.start(prompt, trace_id=tag, **kw))
            assert err is None and toks == want, tag
            assert get_histogram("gen/ttft_s")["count"] == ttft0 + 1, tag
            retires = [sp["attrs"] for sp in trace.get_spans()
                       if sp["name"] == "gen/retire"
                       and sp["trace_id"] == tag]
            assert [(a["reason"], a["tokens"]) for a in retires] == [
                ("complete", len(want))], tag
            assert _wait(eng, lambda s: s["active"] == 0
                         and s["pending_steps"] == 0), tag
            st = eng.stats()
            if st["paged"]:
                assert (st["pages_free"] + st["prefix_entries"]
                        == st["pages"]), tag


# -- cancel / TTL under lookahead -------------------------------------------

def test_cancel_and_ttl_under_lookahead_return_pool_to_full(model):
    """Cancel and TTL-reap land at most ``depth`` steps late under
    async dispatch; the lagged steps write only pads into pages the
    dying generation still owns, every page comes back to the pool, and
    a dropped generation never delivers another token."""
    rs = np.random.RandomState(2)
    p_a = rs.randint(0, VOCAB, (5,)).astype(np.int32)
    p_b = rs.randint(0, VOCAB, (5,)).astype(np.int32)
    ref_b = np.asarray(generate(model, p_b[None], 8))[0, 5:]
    with GenerationEngine(model, slots=2, max_len=32, queue_max=4,
                          paged=True, page_tokens=8, pages=12,
                          prefix_cache=False, device_pt=True,
                          async_depth=2) as eng:
        full = eng.stats()["pages_free"]
        eng.step_wait_s = 0.02        # pace so "mid-flight" exists
        try:
            gid_a = eng.start(p_a, 20)
            gid_b = eng.start(p_b, 8)
            while len(eng.poll(gid_a, wait_s=0.5)["tokens"]) < 2:
                pass
            assert eng.cancel(gid_a)
            toks_b, err_b = _drain(eng, gid_b)
        finally:
            eng.step_wait_s = 0.0
        assert err_b is None
        np.testing.assert_array_equal(np.asarray(toks_b, np.int32), ref_b)
        assert gid_a not in eng._gens           # no stale delivery
        assert _wait(eng, lambda s: s["active"] == 0
                     and s["pages_free"] == full), eng.stats()

        # TTL reap mid-flight under the same lookahead
        eng._ttl_s = 0.3
        eng.step_wait_s = 0.05
        try:
            gid = eng.start(p_a, 25)
            assert _wait(eng, lambda s: s["active"] == 1)
            assert _wait(eng, lambda s: s["active"] == 0
                         and s["generations"] == 0, timeout=3.0)
        finally:
            eng._ttl_s = 10.0
            eng.step_wait_s = 0.0
        with pytest.raises(KeyError):
            eng.poll(gid)
        assert _wait(eng, lambda s: s["pages_free"] == full), eng.stats()


# -- failover resume from a lagged stream -----------------------------------

def test_failover_resume_from_lagged_async_stream(model):
    """A sampled stream served by an async_depth=2 engine dies
    mid-flight (cancel stands in for SIGKILL); the delivered prefix —
    which by construction lags device progress by up to ``depth``
    steps — resumes on a plain synchronous engine via prompt-replay +
    ``rng_skip`` and lands on the exact solo-generate tail."""
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, VOCAB, (6,)).astype(np.int32)
    ref = _sampled_ref(model, prompt, 8)
    with GenerationEngine(model, slots=2, max_len=32, paged=True,
                          page_tokens=8, pages=16, device_pt=True,
                          async_depth=2) as eng:
        eng.step_wait_s = 0.02
        try:
            gid = eng.start(prompt, 8, **SAMPLE_KW)
            while len(eng.poll(gid, wait_s=0.5)["tokens"]) < 3:
                pass
            delivered = eng.poll(gid)["tokens"]
            eng.cancel(gid)
        finally:
            eng.step_wait_s = 0.0
    k = len(delivered)
    assert 3 <= k <= 8
    np.testing.assert_array_equal(np.asarray(delivered, np.int32),
                                  ref[:k])
    with GenerationEngine(model, slots=2, max_len=32) as survivor:
        tail, err = _drain(survivor, survivor.start(
            np.concatenate([prompt, np.asarray(delivered, np.int32)]),
            8 - k, rng_skip=k, **SAMPLE_KW))
    assert err is None
    np.testing.assert_array_equal(np.asarray(tail, np.int32), ref[k:])


# -- goodput accounting at the new readback site ----------------------------

def test_goodput_host_gather_measured_under_async(model):
    """With lookahead on, the blocking ``np.asarray`` moves from the
    dispatch site into ``_finish_step`` — the meter must still see it:
    host_gather > 0 and the bucket fractions still sum to 1.0."""
    rs = np.random.RandomState(4)
    with GenerationEngine(model, slots=2, max_len=32, ledger=True,
                          async_depth=1) as eng:
        toks, err = _drain(eng, eng.start(
            rs.randint(0, VOCAB, (5,)).astype(np.int32), 8))
        assert err is None and len(toks) == 8
        gp = eng.stats()["goodput"]
    assert gp["buckets"]["host_gather"] > 0.0
    assert gp["buckets"]["decode"] > 0.0
    assert sum(gp["fractions"].values()) == pytest.approx(1.0)


# -- hard-off defaults ------------------------------------------------------

def test_defaults_off_no_hot_path_flag_reads(model, monkeypatch):
    """gen_device_pt/gen_async_depth default off, the default engine
    runs the synchronous loop with the host page table (stats prove
    it), and neither flag is read on the serve hot path — construction
    only."""
    assert flag("gen_device_pt") is False
    assert flag("gen_async_depth") == 0
    import paddle_tpu.serving.engine as engine_mod

    reads: list[str] = []
    real_flag = engine_mod.flag

    def spy(name):
        reads.append(name)
        return real_flag(name)

    monkeypatch.setattr(engine_mod, "flag", spy)
    rs = np.random.RandomState(5)
    with GenerationEngine(model, slots=2, max_len=32, paged=True,
                          page_tokens=8) as eng:
        assert "gen_device_pt" in reads and "gen_async_depth" in reads
        st = eng.stats()
        assert st["device_pt"] is False and st["async_depth"] == 0
        assert st["pending_steps"] == 0
        assert eng._pt_dev is None
        reads.clear()
        toks, err = _drain(eng, eng.start(
            rs.randint(0, VOCAB, (5,)).astype(np.int32), 6))
        assert err is None and len(toks) == 6
        assert not [r for r in reads
                    if r in ("gen_device_pt", "gen_async_depth")]
