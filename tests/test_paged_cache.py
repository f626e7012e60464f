"""Paged KV cache: allocator/refcount/prefix-cache edge cases.

The invariant under test everywhere: the page pool leaks nothing. Every
path that abandons a generation — cancel mid-chunked-prefill, poll-TTL
expiry mid-prefill, sharers retiring in either order, prefix eviction
under pool pressure — must return the pool to exactly its prior
occupancy (plus any pages the prefix cache legitimately retains, which
``clear_prefix_cache`` then drains).
"""

import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.monitor import get_stat
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import (
    generate, init_paged_cache, paged_gather, paged_scatter,
)
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.engine import _PagePool, _PrefixCache

pytestmark = pytest.mark.gen

VOCAB = 96


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(11)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    return LlamaForCausalLM(cfg)


def _drain(engine, gid, wait_s=0.5):
    toks, n = [], 0
    while True:
        doc = engine.poll(gid, start=n, wait_s=wait_s)
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


# -- host-side allocator ----------------------------------------------------

def test_page_pool_alloc_release_refcount():
    pool = _PagePool(4)
    assert pool.free_count == 4
    a = pool.alloc(3)
    assert sorted(a) == [1, 2, 3] and pool.free_count == 1
    pool.retain(a[0])                      # a second holder
    pool.release(a[0])
    assert pool.free_count == 1            # still referenced
    pool.release(a[0])
    assert pool.free_count == 2            # now actually free
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(3)
    pool.release(a[1])
    pool.release(a[2])
    assert pool.free_count == 4
    with pytest.raises(AssertionError, match="underflow"):
        pool.release(a[1])


def test_prefix_cache_chain_match_and_leaf_eviction():
    P = 4
    pool = _PagePool(8)
    cache = _PrefixCache(P)
    prompt = np.arange(12, dtype=np.int32)          # 3 full pages
    pages = pool.alloc(3)
    cache.insert(prompt, pages, pool)               # cache holds +1 each
    assert len(cache) == 3
    for pid in pages:                               # gen retires
        pool.release(pid)
    assert pool.free_count == 5                     # cache keeps 3 alive

    # chain semantics: a prompt diverging inside page 2 matches 1 page
    div = prompt.copy()
    div[6] = 77
    m = cache.match(div, pool)
    assert len(m) == 1 and m[0] == pages[0]
    pool.release(m[0])
    # full prefix (longer prompt) matches all 3; a 12-token prompt is
    # capped at (12 - 1) // 4 = 2 so one token remains to prefill
    m = cache.match(np.arange(13, dtype=np.int32), pool)
    assert m == pages
    for pid in m:
        pool.release(pid)
    m = cache.match(prompt, pool)
    assert m == pages[:2]
    for pid in m:
        pool.release(pid)

    # eviction is leaf-first: evicting 1 must free the CHAIN TAIL (page
    # 3), never a parent another entry still chains through
    freed = cache.evict(1, pool)
    assert freed == 1 and len(cache) == 2
    assert pool.refcount(pages[2]) == 0
    assert pool.refcount(pages[0]) == 1 and pool.refcount(pages[1]) == 1
    # a retained page (live generation) is not evictable
    m = cache.match(prompt, pool)
    assert m == pages[:2]
    assert cache.evict(8, pool) == 0       # both held by the "gen"
    for pid in m:
        pool.release(pid)
    assert cache.evict(8, pool) == 2
    assert pool.free_count == 8 and len(cache) == 0


# -- gather/scatter cache contract ------------------------------------------

@pytest.mark.parametrize("quant", [False, True])
def test_paged_gather_scatter_roundtrip(quant):
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    L, Hkv, S, D, P = 2, 2, 32, 4, 8
    dtype = jnp.int8 if quant else jnp.float32
    from paddle_tpu.models._common import init_kv_cache
    proto = init_kv_cache(L, 1, S, Hkv, D, dtype)
    pool = init_paged_cache(proto, num_pages=6, page_tokens=P)
    assert pool[0].shape == (7, L, Hkv, P, D)       # +1 null page
    if quant:
        assert pool[2].shape == (7, L, Hkv, P)      # scales follow

    table = jnp.asarray([3, 1, 5, 2], jnp.int32)
    chunk = tuple(
        jnp.asarray((rs.randn(L, 1, Hkv, 11, *leaf.shape[4:]) * 10)
                    .astype(leaf.dtype))
        for leaf in pool)
    pool2 = paged_scatter(pool, table, chunk, index=5, page_tokens=P,
                          length=jnp.asarray(11, jnp.int32))
    view = paged_gather(pool2, table)
    for v, ch in zip(view, chunk):
        assert v.shape[3] == 4 * P
        np.testing.assert_array_equal(np.asarray(v[:, :, :, 5:16]),
                                      np.asarray(ch))
    # null page absorbed nothing mapped: pages NOT in the table stayed 0
    for pid in (4, 6):
        assert not np.asarray(pool2[0][pid]).any()


def test_paged_scatter_padding_goes_to_null_page():
    """Writes past the true length land on the reserved null page, so a
    right-padded chunk can never clobber a live page — even when the
    padded window runs past the table."""
    import jax.numpy as jnp

    from paddle_tpu.models._common import init_kv_cache
    P = 4
    proto = init_kv_cache(1, 1, 8, 1, 2, jnp.float32)
    pool = init_paged_cache(proto, num_pages=2, page_tokens=P)
    table = jnp.asarray([1, 2], jnp.int32)
    chunk = tuple(jnp.ones((1, 1, 1, 6, 2), jnp.float32) * 7
                  for _ in range(2))
    pool2 = paged_scatter(pool, table, chunk, index=3, page_tokens=P,
                          length=jnp.asarray(2, jnp.int32))
    k = np.asarray(pool2[0])
    assert (k[1, 0, 0, 3] == 7).all() and (k[2, 0, 0, 0] == 7).all()
    assert not k[2, 0, 0, 1:].any()         # padding went to page 0
    assert k[0].any()                       # ...the null page took it


# -- engine edge cases ------------------------------------------------------

def _paced_engine(model, **kw):
    """Small pages + tiny chunks + a paced loop so 'mid-prefill' is a
    real window; prefix cache off unless a test opts in, so pool
    accounting is exact."""
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("queue_max", 8)
    kw.setdefault("paged", True)
    kw.setdefault("page_tokens", 4)
    kw.setdefault("prefill_chunk", 2)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("step_wait_s", 0.03)
    return GenerationEngine(model, **kw)


def _start_pacer(engine, rs):
    """A long-running decode stream that keeps the loop iterating (and
    sleeping step_wait_s per iteration) so chunked prefill of a later
    admit is observably incremental."""
    return engine.start(rs.randint(0, VOCAB, (4,)).astype(np.int32), 50)


def test_cancel_mid_chunked_prefill_frees_all_pages(model):
    rs = np.random.RandomState(30)
    with _paced_engine(model) as eng:
        total = eng.stats()["pages"]
        pacer = _start_pacer(eng, rs)
        victim = eng.start(rs.randint(0, VOCAB, (40,)).astype(np.int32),
                           8)
        # wait until the victim is genuinely mid-prefill (>= 2 chunks
        # in, well short of its 40-token prompt), then cancel
        assert _wait(lambda: victim in eng._gens
                     and eng._gens[victim].prefill_pos >= 4)
        assert eng._gens[victim].prefill_pos < 40
        ev0 = get_stat("gen/evictions")
        assert eng.cancel(victim)
        assert get_stat("gen/evictions") == ev0 + 1
        # every page the victim reserved came back; only the pacer holds
        pacer_pages = -(-(4 + 50) // 4)
        assert _wait(lambda: eng.stats()["pages_free"]
                     == total - pacer_pages)
        eng.cancel(pacer)
        assert _wait(lambda: eng.stats()["pages_free"] == total)
        assert eng.stats()["active"] == 0


@pytest.mark.slow
def test_ttl_expiry_mid_chunked_prefill_frees_all_pages(model):
    rs = np.random.RandomState(31)
    with _paced_engine(model, ttl_s=0.35) as eng:
        total = eng.stats()["pages"]
        victim = eng.start(rs.randint(0, VOCAB, (48,)).astype(np.int32),
                           8)
        pacer = _start_pacer(eng, rs)

        def mid_prefill():
            if victim not in eng._gens:
                return False
            eng.poll(victim)      # keep it alive while prefill ramps
            return eng._gens[victim].prefill_pos >= 4

        assert _wait(mid_prefill)
        # never poll the victim again: the TTL must reap it mid-prefill
        ev0 = get_stat("gen/evictions")
        assert _wait(lambda: victim not in eng._gens, timeout=8.0)
        assert get_stat("gen/evictions") >= ev0 + 1
        pacer_gen = eng._gens.get(pacer)
        while pacer_gen is not None and not pacer_gen.done:
            eng.poll(pacer, wait_s=0.2)     # keep the pacer alive
            if eng.stats()["pages_free"] == total - -(-(4 + 50) // 4):
                break
        eng.cancel(pacer)
        assert _wait(lambda: eng.stats()["pages_free"] == total)


@pytest.mark.slow
def test_sharer_refcounts_either_retire_order(model):
    """Two generations sharing cached prefix pages retire in either
    order; the pages survive until the cache itself lets go."""
    rs = np.random.RandomState(32)
    prefix = rs.randint(0, VOCAB, (9,)).astype(np.int32)   # 2 full pages
    tails = [rs.randint(0, VOCAB, (2,)).astype(np.int32) for _ in range(2)]
    for first_retires in (0, 1):
        with GenerationEngine(model, slots=2, max_len=64, queue_max=8,
                              paged=True, page_tokens=4, prefill_chunk=3,
                              prefix_cache=True,
                              step_wait_s=0.02) as eng:
            total = eng.stats()["pages"]
            # seed the prefix cache (runs to completion, registers pages)
            seed_gid = eng.start(np.concatenate([prefix, tails[0]]), 2)
            toks, err = _drain(eng, seed_gid)
            assert err is None
            assert eng.stats()["prefix_entries"] == 2
            shared = [e.page for e in eng._prefix._entries.values()]

            # two sharers in flight: each holds +1 on both shared pages
            gids = [eng.start(np.concatenate([prefix, tails[i]]), 12)
                    for i in (0, 1)]
            assert _wait(lambda: all(
                eng._gens[g].slot is not None
                and not eng._gens[g].prefilling for g in gids))
            for pid in shared:
                assert eng._pool.refcount(pid) == 3    # cache + 2 gens

            eng.cancel(gids[first_retires])
            for pid in shared:
                assert eng._pool.refcount(pid) == 2
            toks, err = _drain(eng, gids[1 - first_retires])
            assert err is None
            # solo-generate byte-identity survived the sharer's exit
            p = np.concatenate([prefix, tails[1 - first_retires]])
            ref = np.asarray(generate(model, p[None], 12))[0, len(p):]
            np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
            for pid in shared:
                assert eng._pool.refcount(pid) == 1    # cache only
            assert eng.stats()["pages_free"] == total - 2
            assert eng.clear_prefix_cache() == 2
            assert eng.stats()["pages_free"] == total


@pytest.mark.slow
def test_prefix_eviction_under_pool_pressure(model):
    """A pool-starved admit LRU-evicts cached prefix pages instead of
    stalling forever — and sheds only when live generations truly hold
    the pool."""
    rs = np.random.RandomState(33)
    with GenerationEngine(model, slots=2, max_len=32, queue_max=2,
                          paged=True, page_tokens=4, pages=8,
                          prefix_cache=True) as eng:
        # fill the cache: prompt of 8 -> 2 registered pages
        a = rs.randint(0, VOCAB, (8,)).astype(np.int32)
        toks, err = _drain(eng, eng.start(a, 4))
        assert err is None
        assert eng.stats()["prefix_entries"] == 2
        assert eng.stats()["pages_free"] == 6
        ev0 = get_stat("gen/prefix_evictions")
        # a request needing 7 of 8 pages: must evict at least one
        # cached page to fit
        b = rs.randint(0, VOCAB, (20,)).astype(np.int32)
        ref = np.asarray(generate(model, b[None], 8))[0, 20:]
        toks, err = _drain(eng, eng.start(b, 8))
        assert err is None
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        assert get_stat("gen/prefix_evictions") >= ev0 + 1
        assert eng.stats()["prefix_entries"] >= 1   # b registered pages


def test_start_rejects_request_larger_than_pool(model):
    with GenerationEngine(model, slots=2, max_len=32, paged=True,
                          page_tokens=4, pages=4) as eng:
        with pytest.raises(ValueError, match="pages"):
            eng.start(np.arange(10, dtype=np.int32), 16)   # needs 7 > 4
        # a fitting request still works
        toks, err = _drain(eng, eng.start(np.arange(6, dtype=np.int32),
                                          2))
        assert err is None and len(toks) == 2


def test_paged_engine_holds_2x_streams_at_equal_cache_memory(model):
    """Equal cache memory (2 slots x 64 positions == 8 pages x 16
    tokens), streams of prompt 8 + 8 new = exactly one page: the
    contiguous engine holds 2 and queues 6, the paged one holds all 8
    at once — and every stream is still solo ``generate()``."""
    N = 8
    prompts = np.random.RandomState(35).randint(
        0, VOCAB, (N, 8)).astype(np.int32)
    ref = np.asarray(generate(model, prompts, 8))[:, 8:]
    for mode, kw in (("contiguous", dict(slots=2)),
                     ("paged", dict(slots=N, paged=True, page_tokens=16,
                                    pages=N, prefix_cache=False))):
        with GenerationEngine(model, max_len=64, queue_max=16,
                              step_wait_s=0.05, **kw) as eng:
            gids = [eng.start(p, 8) for p in prompts]
            want = kw["slots"]          # 2 held against 8: 4x (floor 2x)
            assert _wait(lambda: eng.stats()["active"] == want
                         and eng.stats()["queued"] == N - want), mode
            for i, g in enumerate(gids):
                toks, err = _drain(eng, g)
                assert err is None, mode
                np.testing.assert_array_equal(
                    np.asarray(toks, np.int32), ref[i], err_msg=mode)


@pytest.mark.slow
def test_admission_stalls_then_resumes_when_pages_free(model):
    """When live generations hold the whole pool the queue head waits
    (head-of-line) and admits as soon as a retire returns pages."""
    rs = np.random.RandomState(34)
    with GenerationEngine(model, slots=4, max_len=32, queue_max=8,
                          paged=True, page_tokens=4, pages=6,
                          prefix_cache=False, step_wait_s=0.02) as eng:
        # compile the solo reference FIRST: anything slow between the
        # holder pinning the pool and the waiter enqueueing would let
        # the holder finish and deflate the test
        holder_p = rs.randint(0, VOCAB, (8,)).astype(np.int32)
        waiter_p = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, waiter_p[None], 3))[0, 5:]
        holder = eng.start(holder_p, 14)           # 22 tokens -> 6 pages
        assert _wait(lambda: eng.stats()["pages_free"] == 0)
        waiter = eng.start(waiter_p, 3)            # 2 pages: must wait
        time.sleep(0.15)
        st = eng.stats()
        assert st["queued"] == 1 and eng._gens[waiter].slot is None
        eng.cancel(holder)                         # pages return
        toks, err = _drain(eng, waiter)
        assert err is None
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        assert _wait(lambda: eng.stats()["pages_free"] == 6)


@pytest.mark.slow
@pytest.mark.parametrize("cache_dtype", ["int8"])
def test_paged_int8_cache_matches_solo(model, cache_dtype):
    """The quantized cache layout rides the same pool/page-table path
    (4 leaves: int8 buffers + f32 scales) — paged int8 decode matches
    solo int8 generate token-for-token."""
    import jax.numpy as jnp

    rs = np.random.RandomState(35)
    with GenerationEngine(model, slots=2, max_len=32, paged=True,
                          page_tokens=8, prefill_chunk=5,
                          cache_dtype=jnp.int8) as eng:
        assert len(eng._state["cache"]) == 4
        for n in (5, 11):
            p = rs.randint(0, VOCAB, (n,)).astype(np.int32)
            ref = np.asarray(generate(model, p[None], 6,
                                      cache_dtype=jnp.int8))[0, n:]
            toks, err = _drain(eng, eng.start(p, 6))
            assert err is None
            np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
