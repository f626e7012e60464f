"""SmallThinker support: window and full attention layers mixed (NoPE
full, RoPE window), a router that reads the layer's input, ReGLU
experts, and the paged engine's pool of two layer groups — the program
against the plain reference (``benchmarks/lib/reference_smallthinker.py``,
which imports nothing of it) on seeded weights, tiny widths, float32, at
contexts beyond two windows (window 32, pages of 8, chunks of 16)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.builders import common
from benchmarks.lib import reference_smallthinker as R
from benchmarks.lib import weights as W
from paddle_tpu.core import monitor, trace
from paddle_tpu.models import SmallThinkerConfig, SmallThinkerForCausalLM
from paddle_tpu.models.generation import generate
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.serving.engine import GenerationEngine, _WindowGroup

SEED = 2 ** 31 + 9
WINDOW, P, CHUNK, MAXLEN = 32, 8, 16, 192
ROW_PAGES = WINDOW // P + 1 + CHUNK // P            # 7
ARGS = dict(vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=3, sliding_window=WINDOW,
            max_seq_len=MAXLEN, dtype="float32")
CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_ffn_hidden_size": 32, "vocab_size": 256,
    "num_hidden_layers": 8, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "sliding_window_size": WINDOW,
    "sliding_window_layout": [0, 1, 1, 1] * 13,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_theta": 1.5e6,
    "rms_norm_eps": 1e-6, "torch_dtype": "float32",
    "program": {
        "model": "paddle_tpu.models.smallthinker:SmallThinkerForCausalLM",
        "config": "paddle_tpu.models.smallthinker:SmallThinkerConfig",
        "config_args": ARGS}}
ARCH = R.Arch.from_config(CFG)
# float32 sums in another order (a cache read in two pieces, a scan over
# periods against a loop over layers, all experts on every token against
# the picked ones): logits of size ~0.5 agree to a few 1e-7; 2e-5 is the
# room the other families' tests give, a hundred times under the 2e-3 a
# bf16 rounding of one operand makes
TOL = dict(atol=2e-5, rtol=1e-4)
ENGINE = dict(slots=3, max_len=MAXLEN, paged=True, page_tokens=P,
              pages=(72, 40), prefill_chunk=CHUNK, prefix_cache=True,
              queue_max=64)


@pytest.fixture(scope="module")
def model():
    template = common.model_template(CFG)
    return jax.jit(lambda k: common.seeded_model(template, k))(
        W.root_key(SEED))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(1, 256, (2, 90), dtype=np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    return np.asarray(R.forward_logits(ARCH, SEED, ids))


def drain(eng, gid):
    toks = []
    while True:
        r = eng.poll(gid, len(toks), wait_s=60.0)
        assert r["error"] is None, r["error"]
        toks += r["tokens"]
        if r["done"]:
            return toks


def solo(model, prompt, n):
    return np.asarray(generate(model, prompt[None], n))[0, prompt.size:
                                                        ].tolist()


def both_pools_full(eng):
    eng.clear_prefix_cache()
    st = eng.stats()
    assert st["pages_free"] == st["pages"], st
    assert all(g["pages_free"] == g["pages"] for g in st["groups"]), st
    assert eng._win.debt == 0
    assert not eng._pt.any() and not eng._win.pt.any()


# -- (a) the full forward, the contiguous cache ----------------------------------

def test_full_forward_agrees_with_reference(model, ids, ref_logits):
    assert ids.shape[1] > 2.5 * WINDOW
    np.testing.assert_allclose(np.asarray(model(jnp.asarray(ids))),
                               ref_logits, **TOL)


def test_contiguous_cache_agrees_with_reference(model, ids, ref_logits):
    cache = model.init_cache(2, 96)
    assert [[c.shape for c in g] for g in cache] == [
        [(2, 2, 2, 96, 16)] * 2, [(6, 2, 2, 96, 16)] * 2]
    assert model.cache_groups == ((2, None), (6, WINDOW))
    got = []
    lg, cache = model.forward_with_cache(jnp.asarray(ids[:, :40]), cache, 0)
    got.append(lg)                       # a chunk longer than the window
    lg, cache = model.forward_with_cache(jnp.asarray(ids[:, 40:77]), cache,
                                         jnp.asarray(40))
    got.append(lg)                       # a chunk behind a cached prefix
    for t in range(77, 90):              # one token at a time, t > 2 W
        lg, cache = model.forward_with_cache(jnp.asarray(ids[:, t:t + 1]),
                                             cache, jnp.asarray(t))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               ref_logits, **TOL)


def test_paged_programs_agree_with_reference(model, ids, ref_logits):
    """What the engine's paged prefill and step do, by hand, logits
    against the reference's full forward: the full group's row holds
    every page, the window group's only the live ones — its base moves
    as the position does, scattered page ids, a window that starts
    mid-page — through ``init_paged_cache`` / ``PagedCache`` (with a
    base) / ``paged_scatter`` / ``paged_write``."""
    from paddle_tpu.models.generation import (
        PagedCache, init_paged_cache, paged_scatter, paged_write,
    )
    proto = model.init_cache(1, 96)
    pools = [init_paged_cache(g, 30, P) for g in proto]
    assert [p[0].shape for p in pools] == [(31, 2, 2, P, 16),
                                           (31, 6, 2, P, 16)]
    full_row = jnp.asarray(np.random.default_rng(1).permutation(
        np.arange(1, 31))[:12], jnp.int32)             # 96 positions
    ring = np.random.default_rng(2).permutation(np.arange(1, 31))
    seq = jnp.asarray(ids[:1])
    got = []

    def caches(first, end):
        """The window row for a program over ``[first, end)``: logical
        pages from the window's first on, ids drawn by logical page."""
        base = max(first - WINDOW + 1, 0) // P
        row = np.zeros(ROW_PAGES, np.int32)
        live = np.arange(base, (end - 1) // P + 1)
        row[:live.size] = ring[live % ring.size]
        return base, jnp.asarray(row), (
            PagedCache(pools[0], full_row),
            PagedCache(pools[1], jnp.asarray(row), jnp.asarray(base)))

    for start in range(0, 64, CHUNK):                  # four prefill chunks
        base, row, cache = caches(start, start + CHUNK)
        lg, chunk = model.forward_with_cache(seq[:, start:start + CHUNK],
                                             cache, jnp.asarray(start))
        pools[0] = paged_scatter(pools[0], full_row, chunk[0], start, P,
                                 length=CHUNK)
        pools[1] = paged_scatter(pools[1], row, chunk[1], start - base * P,
                                 P, length=CHUNK)
        got.append(lg)
    for t in range(64, 78):                            # decode, t > 2 W
        base, row, cache = caches(t, t + 1)
        lg, new = model.forward_with_cache(seq[:, t:t + 1], cache,
                                           jnp.asarray(t))
        at = jnp.asarray([t % P])
        pools[0] = paged_write(pools[0], full_row[t // P][None], at,
                               tuple(n[None, :, 0, :, 0] for n in new[0]))
        pools[1] = paged_write(pools[1], row[t // P - base][None], at,
                               tuple(n[None, :, 0, :, 0] for n in new[1]))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               ref_logits[:1, :78], **TOL)


# -- (b) each mechanism, altered, fails -----------------------------------------

def layer_params(seed=SEED, i=1):
    """Entry ``i`` of the first period, its expert matrices ten times
    the N(0, 0.02) draw so that the expert layer's part of the output
    (three small matrices multiplied) stands well over the tolerance."""
    key = W.root_key(seed)
    return {n: W.layer_leaf_f32(key, R.PERIOD.format(i) + n, 0, shape, dt)
            * (10.0 if n.startswith("moe.w_") else 1.0)
            for n, (shape, dt) in ARCH.layer_shapes().items()}


def program_block(p, window=WINDOW, rope=True):
    from paddle_tpu.models.smallthinker import SmallThinkerBlock
    b = jax.eval_shape(lambda: SmallThinkerBlock(
        SmallThinkerConfig(**ARGS), window, rope,
        key=jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(
            p[jax.tree_util.keystr(path)[1:]], leaf.dtype), b)


@pytest.fixture(scope="module")
def x():
    return jnp.asarray(np.random.default_rng(5).normal(size=(1, 80, 64)),
                       jnp.float32)


def test_the_router_reads_the_layers_input(x):
    """The picks come from the block's input; a router moved behind the
    norm and attention (the usual place) picks other experts."""
    p = layer_params()
    want = np.asarray(R.layer(x, p, ARCH, True, True))
    block = program_block(p)
    np.testing.assert_allclose(np.asarray(block(x)), want, **TOL)

    h = x + block.attn(block.attn_norm(x))
    m = block.mlp_norm(h)
    usual = h + block.moe(m)[0]                      # routes from m
    assert float(jnp.abs(usual - want).max()) > 1e-3
    # and the reference's picks are those of x @ W_r, nothing else's
    expert, gate = R.route(x[0], p["moe.router"], ARCH)
    top = jax.lax.top_k(x[0] @ p["moe.router"], 3)[1]
    np.testing.assert_array_equal(np.asarray(expert), np.asarray(top))
    moved = R.route(np.asarray(m[0]), p["moe.router"], ARCH)[0]
    assert (np.asarray(moved) != np.asarray(expert)).any()


def test_nope_layers_are_not_rotated(x):
    """A full layer does not rotate: its output is that of the reference
    with ``rotated=False``, and rotating it would change it."""
    p = layer_params(i=0)
    full = program_block(p, window=None, rope=False)
    np.testing.assert_allclose(
        np.asarray(full(x)), np.asarray(R.layer(x, p, ARCH, False, False)),
        **TOL)
    rotated = np.asarray(R.layer(x, p, ARCH, False, True))
    assert np.abs(np.asarray(full(x)) - rotated).max() > 1e-3
    # without rotation attention is blind to a shift of all positions:
    # the same tokens further along the cache give the same outputs
    q = full.attn(full.attn_norm(x))
    cache = tuple(jnp.zeros((1, 1, 2, 96, 16)) for _ in range(2))
    lead, pay = full.attn(full.attn_norm(x[:, :8]), cache=cache, index=0)
    cache = tuple(jax.lax.dynamic_update_slice(c, w[None], (0, 0, 0, 0, 0))
                  for c, w in zip(cache, pay))
    rest, _ = full.attn(full.attn_norm(x[:, 8:]), cache=cache,
                        index=jnp.asarray(8))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([lead, rest], 1)),
                               np.asarray(q), **TOL)


def test_the_window_has_its_edge_where_the_config_says(x):
    """Query t sees key j iff 0 <= t - j < window: moving one key just
    outside the window of the last query changes nothing for it, moving
    one just inside does."""
    p = layer_params()
    block = program_block(p)
    base = np.asarray(block(x))
    t = x.shape[1] - 1
    for j, seen in ((t - WINDOW, False), (t - WINDOW + 1, True)):
        moved = np.asarray(block(x.at[0, j].add(1.0)))
        assert (np.abs(moved[0, t] - base[0, t]).max() > 1e-6) == seen, j
    wider = program_block(p, window=WINDOW + 1)
    assert np.abs(np.asarray(wider(x)) - base)[0, WINDOW:].max() > 1e-4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gates_sum_to_one_and_agree_with_reference(seed):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)) * 0.5, jnp.float32)
    expert, gate = R.route(h, router, ARCH)
    np.testing.assert_allclose(np.asarray(gate.sum(-1)), 1.0, rtol=1e-6)
    # equal to a softmax over the picked logits alone
    logits = jnp.take_along_axis(h @ router, expert, -1)
    np.testing.assert_allclose(np.asarray(gate),
                               np.asarray(jax.nn.softmax(logits, -1)),
                               rtol=1e-5)
    m = MoEMLP(64, 32, 8, top_k=3, held=(0, 8), norm_topk=True, act="relu",
               init_std=0.3, key=jax.random.PRNGKey(seed)
               ).replace(router=router)
    p = {"moe.w_gate": m.w_gate, "moe.w_up": m.w_up, "moe.w_down": m.w_down}
    np.testing.assert_allclose(
        np.asarray(m(h[None])[0][0]),
        np.asarray(R.experts(h, expert, gate, p, ARCH)), **TOL)
    # gates left as the softmax gives them (OLMoE's rule) are another layer
    plain = MoEMLP(64, 32, 8, top_k=3, held=(0, 8), act="relu",
                   init_std=0.3, key=jax.random.PRNGKey(seed)
                   ).replace(router=router)
    assert float(jnp.abs(plain(h[None])[0] - m(h[None])[0]).max()) > 1e-2
    silu = MoEMLP(64, 32, 8, top_k=3, held=(0, 8), norm_topk=True,
                  init_std=0.3, key=jax.random.PRNGKey(seed)
                  ).replace(router=router)
    assert float(jnp.abs(silu(h[None])[0] - m(h[None])[0]).max()) > 1e-2


def test_route_from_is_the_dropless_forms_alone():
    m = MoEMLP(64, 32, 8, top_k=2, key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="route_from"):
        m(jnp.zeros((1, 4, 64)), route_from=jnp.zeros((1, 4, 64)))
    with pytest.raises(ValueError, match="act must be"):
        MoEMLP(64, 32, 8, act="gelu", key=jax.random.PRNGKey(0))


# -- (c) the paged engine's pool of two groups -----------------------------------

@pytest.fixture(scope="module")
def template():
    return np.random.default_rng(11).integers(1, 256, 3 * WINDOW + 5,
                                              dtype=np.int32)


def prompts_of(template, tails, seed=12):
    rng = np.random.default_rng(seed)
    return [np.concatenate([template, rng.integers(1, 256, k, dtype=np.int32)])
            for k in tails]


@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "async1"])
def test_engine_logits_agree_with_reference(model, ids, ref_logits, depth):
    """Prefill in chunks and decode through the two-group pool serve the
    reference's choice at every position, 2.5 windows deep: the logits
    the engine's programs give are the reference's full forward."""
    prompt = ids[0, :70]
    with GenerationEngine(model, async_depth=depth, **ENGINE) as eng:
        got = drain(eng, eng.start(prompt, 20))
        assert eng.stats()["decode_attn"] == "gather"      # the CPU's arm
        both_pools_full(eng)
    assert got == ids[0, 70:70].tolist() + np.argmax(
        np.asarray(R.forward_logits(
            ARCH, SEED, np.concatenate([prompt, got])[None]))[0, 69:-1],
        -1).tolist()
    # and by logits, through the engine's own programs
    with GenerationEngine(model, **ENGINE) as eng:
        caches = eng._group_caches
        row = (jnp.arange(1, 25, dtype=jnp.int32),
               jnp.concatenate([jnp.zeros(1, jnp.int32),
                                jnp.arange(1, 8, dtype=jnp.int32)]))
        lg, _ = model.forward_with_cache(
            jnp.asarray(ids[:1, :16]), caches(eng._state["cache"], row),
            jnp.asarray(0))
    np.testing.assert_allclose(np.asarray(lg), ref_logits[:1, :16], **TOL)


@pytest.mark.parametrize("order", ["short_first", "long_first"])
def test_streams_sharing_a_template_equal_solo_generate(model, template,
                                                        order):
    """Two streams behind one template longer than the window, retiring
    in either order, and a third that hits the cached prefix: tokens as
    solo ``generate()``; the hit prefills only its tail."""
    a, b, c = prompts_of(template, (5, 17, 9))
    n = {"short_first": (12, 40), "long_first": (40, 12)}[order]
    saved0 = monitor.get_stat("gen/prefix_tokens_saved") or 0
    with GenerationEngine(model, async_depth=1, **ENGINE) as eng:
        ga, gb = eng.start(a, n[0]), eng.start(b, n[1])
        assert drain(eng, ga) == solo(model, a, n[0])
        assert drain(eng, gb) == solo(model, b, n[1])
        mid = monitor.get_stat("gen/prefix_tokens_saved") or 0
        assert drain(eng, eng.start(c, 30)) == solo(model, c, 30)
        # the whole pages of the template came from the cache: the third
        # stream prefilled its tail alone
        hit = (monitor.get_stat("gen/prefix_tokens_saved") or 0) - mid
        assert hit == template.size // P * P > 2 * WINDOW
        # b may have raced a's prefill, which enters the cache a chunk's
        # whole pages at a time: any whole number of pages up to the hit
        assert 0 <= mid - saved0 <= hit and (mid - saved0) % P == 0
        st = eng.stats()
        assert st["groups"][1]["stream_pages_peak"] <= ROW_PAGES
        assert st["groups"][1]["pages_slid"] > 0
        both_pools_full(eng)


def test_a_stream_never_maps_more_than_its_row(model, template):
    """Through prefill and decode 2.5 windows deep a stream's window
    group holds at most W/P + 1 + chunk/P pages; the full group holds
    the whole context."""
    (p,) = prompts_of(template, (11,))
    seen = []
    with GenerationEngine(model, async_depth=1, **ENGINE) as eng:
        assert eng._win.row_pages == ROW_PAGES == 7
        assert eng._win.pt.shape == (3, 1 + ROW_PAGES)
        real = eng._win.cover

        def cover(row, slot, first, end):
            out = real(row, slot, first, end)
            seen.append((len(row.pages), row.base, first, end))
            return out

        eng._win.cover = cover
        gid = eng.start(p, 60)
        assert drain(eng, gid) == solo(model, p, 60)
        st = eng.stats()
    assert max(n for n, *_ in seen) <= ROW_PAGES
    assert st["groups"][1]["stream_pages_peak"] == max(n for n, *_ in seen)
    # every program found the pages of its window mapped, none behind it
    for n, base, first, end in seen:
        assert base == max(first - WINDOW + 1, 0) // P
        assert (base + n) * P >= min(end, p.size + 60)
    # one page goes for every P positions once the stream is past W
    assert st["groups"][1]["pages_slid"] >= (p.size + 60 - WINDOW) // P - 1


@pytest.mark.parametrize("how", ["cancel_mid_prefill", "cancel_mid_decode",
                                 "ttl"])
def test_both_pools_come_back(model, template, how):
    (p,) = prompts_of(template, (20,))
    kw = dict(ENGINE, ttl_s=0.3) if how == "ttl" else ENGINE
    with GenerationEngine(model, step_wait_s=0.01, **kw) as eng:
        gid = eng.start(p, 60)
        if how == "cancel_mid_prefill":
            while not any(g is not None and 0 < g.prefill_pos < p.size
                          for g in eng._slot_gen):
                time.sleep(0.001)
            assert eng.cancel(gid)
        elif how == "cancel_mid_decode":
            assert len(eng.poll(gid, 0, wait_s=30.0)["tokens"]) >= 1
            assert eng.cancel(gid)
        else:
            eng.poll(gid, 0, wait_s=30.0)
            deadline = time.monotonic() + 20
            while eng.stats()["active"] and time.monotonic() < deadline:
                time.sleep(0.05)                  # never polled again
        assert eng.stats()["active"] == 0
        both_pools_full(eng)
        # and the engine still serves
        assert drain(eng, eng.start(p[:50], 8)) == solo(model, p[:50], 8)
        both_pools_full(eng)


def test_the_prefix_cache_evicts_in_both_groups(model):
    """Distinct prompts fill both pools with cached pages; later
    admissions evict the oldest in both groups and still serve right."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 256, 100, dtype=np.int32) for _ in range(7)]
    before = monitor.get_stat("gen/prefix_evictions") or 0
    with GenerationEngine(model, **dict(ENGINE, pages=(40, 28))) as eng:
        for p in prompts:
            assert drain(eng, eng.start(p, 10)) == solo(model, p, 10)
            st = eng.stats()
            full, win = st["groups"]
            # an entry holds one page of each group
            assert full["pages"] - full["pages_free"] == st["prefix_entries"]
            assert win["pages"] - win["pages_free"] == st["prefix_entries"]
        assert (monitor.get_stat("gen/prefix_evictions") or 0) > before
        both_pools_full(eng)


def test_depths_give_the_same_tokens_across_slides(model, template):
    prompts = prompts_of(template, (3, 14, 25, 8, 19), seed=13)
    ns = [45, 20, 33, 50, 12]
    out = {}
    for depth in (0, 1):
        with GenerationEngine(model, async_depth=depth, **ENGINE) as eng:
            ids_ = [eng.start(p, n) for p, n in zip(prompts, ns)]
            out[depth] = [drain(eng, g) for g in ids_]
            assert eng.stats()["groups"][1]["pages_slid"] > 20
            both_pools_full(eng)
    assert out[0] == out[1] == [solo(model, p, n)
                                for p, n in zip(prompts, ns)]


def test_stats_keep_totals_and_add_a_block_a_group(model, template):
    (p,) = prompts_of(template, (6,))
    slid0 = monitor.get_stat("gen/kv_pages_slid") or 0
    with GenerationEngine(model, **ENGINE) as eng:
        st = eng.stats()
        assert (st["pages"], st["pages_free"]) == (112, 112)
        assert [(g["name"], g["layers"], g["pages"]) for g in st["groups"]
                ] == [("full", 2, 72), ("window", 6, 40)]
        assert st["groups"][1]["window"] == WINDOW
        assert st["groups"][1]["row_pages"] == ROW_PAGES
        # both groups' leaves: 8 layers x (k, v) x 2 heads x 16 x 4 B
        assert st["kv_bytes_per_token"] == 8 * 2 * 2 * 16 * 4
        gid = eng.start(p, 40)
        eng.poll(gid, 0, wait_s=30.0)
        live = eng.stats()
        assert live["groups"][0]["stream_pages_max"] == -(-(p.size + 40) // P)
        assert 0 < live["groups"][1]["stream_pages_max"] <= ROW_PAGES
        assert live["pages_free"] == sum(g["pages_free"]
                                         for g in live["groups"])
        drain(eng, gid)
        done = eng.stats()
    slid = done["groups"][1]["pages_slid"]
    assert slid > 0
    assert (monitor.get_stat("gen/kv_pages_slid") or 0) - slid0 == slid
    assert done["moe_picks"] == done["moe_picks_held"] > 0


def test_a_slide_is_a_span_of_the_loop(model, template):
    (p,) = prompts_of(template, (4,))
    from paddle_tpu.core.flags import set_flags
    set_flags({"trace": True})
    try:
        trace.clear()
        with GenerationEngine(model, **ENGINE) as eng:
            drain(eng, eng.start(p, 30))
            slid = eng.stats()["groups"][1]["pages_slid"]
        spans = [s for s in trace.get_spans() if s["name"] == "gen/kv_slide"]
    finally:
        set_flags({"trace": False})
    assert spans and sum(s["attrs"]["pages"] for s in spans) == slid
    # none per token: fewer slides than positions written
    assert len(spans) < p.size + 30


def test_an_uploaded_table_is_a_snapshot(model):
    """A window row changes every few steps while earlier programs may
    still be in flight: what a compiled call was handed must not follow
    the host table (a CPU operand may alias the array it came from)."""
    with GenerationEngine(model, **ENGINE) as eng, eng._cond:
        for _ in range(8):          # aliasing depends on the allocation
            up = eng._pt_upload(jnp)
            eng._pt[0, 0] = 5
            eng._win.pt[0, :3] = (2, 7, 9)
            assert not np.asarray(up[0]).any()
            assert not np.asarray(up[1]).any()
            eng._pt[0, 0] = 0
            eng._win.pt[0] = 0


def test_window_group_books_hold_their_promise():
    """The promise by hand: free pages never fall under what live
    streams may still draw, whatever is shared with the cache."""
    g = _WindowGroup(WINDOW, 12, P, slots=2, chunk=CHUNK, maxp=24)
    assert g.row_pages == ROW_PAGES and g.budget(20, 0) == 7
    assert g.budget(20, 18) == 2 and g.budget(3, 0) == 3
    row = g.admit(0, [], 20)
    assert (g.debt, g.spare()) == (7, 5)
    for first in range(0, 96, CHUNK):          # a cold prefill in chunks
        g.cover(row, 0, first, first + CHUNK)
        assert len(row.pages) <= ROW_PAGES and g.pool.free_count >= g.debt
        # the cache takes the chunk's two pages while the pool can spare
        for i in range(first // P, (first + CHUNK) // P):
            g.hand_to_cache(row, i)
        assert g.pool.free_count >= g.debt >= 0
    assert g.spare() == 0                      # the cache pinned what it could
    assert g.hand_to_cache(row, 95 // P) == 0  # and is refused the rest
    g.release(row)
    assert g.debt == 0
    assert g.pool.free_count == 12 - 5         # five pages stay cached


# -- (d) what has to refuse, by name ----------------------------------------------

@pytest.mark.parametrize("kwargs,names", [
    (dict(cache_dtype=jnp.int8), "int8 cache beside layer groups"),
    (dict(mesh_tp=2), "gen_mesh_tp with layer groups"),
    (dict(spec_k=2, spec_mode="ngram"), "gen_spec_k"),
    (dict(kv_store=True), "gen_kv_store"),
    (dict(role="decode"), "gen_kv_store / gen_role"),
    (dict(sched=True), "gen_sched"),
    (dict(pages=(8, 8, 8)), "one count, or one a group"),
], ids=["int8-pool", "gen_mesh_tp", "gen_spec_k", "gen_kv_store", "gen_role",
        "gen_sched", "pages-a-group"])
def test_constructions_that_must_refuse(model, kwargs, names):
    with pytest.raises(ValueError, match=names):
        GenerationEngine(model, **dict(ENGINE, **kwargs))


def test_other_mixes_of_groups_refuse(model):
    cfg = SmallThinkerConfig(**dict(ARGS, window_pattern=(1, 1),
                                    rope_pattern=(1, 1)))
    only_window = jax.eval_shape(
        lambda: SmallThinkerForCausalLM(cfg, key=jax.random.PRNGKey(0)))
    assert only_window.cache_groups == ((8, WINDOW),)
    with pytest.raises(ValueError, match="one full group followed by one "
                                         "window group"):
        GenerationEngine(only_window, **dict(ENGINE, pages=8))
    with pytest.raises(ValueError, match="must repeat window_pattern"):
        SmallThinkerConfig(**dict(ARGS, num_layers=6))


def test_the_contiguous_engine_serves_the_model_too(model, template):
    """No pool, no groups to manage: every position is held and masked."""
    (p,) = prompts_of(template, (7,))
    with GenerationEngine(model, slots=2, max_len=MAXLEN) as eng:
        assert drain(eng, eng.start(p, 25)) == solo(model, p, 25)
        assert "groups" not in eng.stats()
