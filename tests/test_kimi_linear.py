"""Kimi-Linear support: KDA layers (a recurrent state of O(1)) beside
latent layers without position encoding, sigmoid routing over one group
with a held share, and the paged engine's state group — slot-indexed
rows next to the latent page pool, prefix hits that restore a state
snapshot — the program against the plain reference
(``benchmarks/lib/reference_kimi_linear.py``, which imports nothing of
it and runs the recurrence token by token) on seeded weights, tiny
widths, float32 (pages of 8, chunks of 16, a template of 6 chunks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.builders import common, serve_state
from benchmarks.lib import reference_kimi_linear as R
from benchmarks.lib import reference_latent
from benchmarks.lib import weights as W
from paddle_tpu.core import monitor, trace
from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM
from paddle_tpu.models.deepseek_v3 import DeepseekV3Config, MLAttention
from paddle_tpu.models.generation import PagedCache, StateCache, generate
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.serving.engine import (GenerationEngine, _PagePool,
                                       _PrefixCache, _SnapshotPool)

SEED = 2 ** 31 + 13
P, CHUNK, MAXLEN = 8, 16, 192
ARGS = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_layers=11,
            full_attn_layers=[4, 8, 11], num_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kda_heads=2, kda_head_dim=16, n_routed_experts=16,
            num_experts_per_tok=4, held=[4, 8], max_seq_len=MAXLEN,
            dtype="float32")
CFG = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 11, "first_k_dense_replace": 1,
    "linear_attn_config": {"full_attn_layers": [4, 8, 11], "head_dim": 16,
                           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10],
                           "num_heads": 2, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 256,
    "num_experts": 8, "published": {"num_experts": 16}, "held": [4, 8],
    "num_experts_per_token": 4, "num_expert_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.446, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "torch_dtype": "float32",
    "program": {
        "model": "paddle_tpu.models.kimi_linear:KimiLinearForCausalLM",
        "config": "paddle_tpu.models.kimi_linear:KimiLinearConfig",
        "config_args": ARGS}}
ARCH = R.Arch.from_config(CFG)
# float32 sums in another order (the chunked transform against a scan
# over tokens, a cache read in two pieces, all held experts on every
# token against the picked ones): logits of size ~0.5 agree to a few
# 1e-7; 2e-5 is the room the other families' tests give
TOL = dict(atol=2e-5, rtol=1e-4)
ENGINE = dict(slots=3, max_len=MAXLEN, paged=True, page_tokens=P, pages=96,
              prefill_chunk=CHUNK, prefix_cache=True, queue_max=64,
              state_snapshots=4)


@pytest.fixture(scope="module")
def model():
    template = common.model_template(CFG)
    return jax.jit(lambda k: serve_state.with_decay(
        common.seeded_model(template, k), k))(W.root_key(SEED))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(1, 256, (2, 90), dtype=np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    return np.asarray(R.forward_logits(ARCH, SEED, ids))


@pytest.fixture(scope="module")
def template():
    return np.random.default_rng(11).integers(1, 256, 6 * CHUNK,
                                              dtype=np.int32)


def prompts_of(template, tails, seed=12):
    rng = np.random.default_rng(seed)
    return [np.concatenate([template, rng.integers(1, 256, k, dtype=np.int32)])
            for k in tails]


def drain(eng, gid):
    toks = []
    while True:
        r = eng.poll(gid, len(toks), wait_s=60.0)
        assert r["error"] is None, r["error"]
        toks += r["tokens"]
        if r["done"]:
            return toks


_full = jax.jit(lambda m, ids: jnp.argmax(m(ids), -1))
_cached = jax.jit(lambda m, ids, cache, index: m.forward_with_cache(
    ids, cache, index))


def greedy(model, prompt, got):
    """Whether ``got`` is the greedy continuation of ``prompt``: every
    token the argmax of the full forward over what precedes it (padded
    to one length: one compile serves every prompt; nothing in the model
    looks ahead)."""
    seq = np.zeros((1, MAXLEN), np.int32)
    n = prompt.size + len(got)
    seq[0, :n] = np.concatenate([prompt, np.asarray(got, np.int32)])
    best = np.asarray(_full(model, jnp.asarray(seq)))[0]
    return got == best[prompt.size - 1:n - 1].tolist()


def state_block(eng):
    return next(g for g in eng.stats()["groups"] if g["name"] == "state")


def everything_back(eng):
    eng.clear_prefix_cache()
    st = eng.stats()
    assert st["pages_free"] == st["pages"], st
    assert state_block(eng)["snapshots_free"] == 4
    assert not eng._pt.any()


# an engine compiles its step and a program a prefill bucket (~20 s on
# the CPU at this depth), so the tests share three: the cell's shape, one
# slot, and no lookahead
@pytest.fixture(scope="module")
def eng(model):
    with GenerationEngine(model, async_depth=1, **ENGINE) as e:
        yield e


@pytest.fixture(scope="module")
def one_slot(model):
    with GenerationEngine(model, **dict(ENGINE, slots=1)) as e:
        yield e


def test_greedy_is_solo_generate(model, template):
    """The tests' oracle against ``generate()`` on the contiguous
    cache, once."""
    (p,) = prompts_of(template, (5,))
    got = np.asarray(generate(model, p[None], 6))[0, p.size:].tolist()
    assert greedy(model, p, got)
    assert not greedy(model, p, got[:-1] + [(got[-1] + 1) % 256])


# -- (a) the model: full forward, contiguous cache, paged programs ---------------

def test_the_layout_is_head_scan_tail(model):
    cfg = model.config
    assert cfg.kinds == ("kda",) * 3 + ("mla",) + ("kda",) * 3 + (
        "mla", "kda", "kda", "mla")
    assert (cfg.period, cfg.whole_periods) == (4, 1)
    assert len(model.head) == 4 and len(model.tail) == 3
    assert hasattr(model.head[0], "mlp") and hasattr(model.head[1], "moe")
    assert model.cache_groups == ((3, None), (8, "state"))
    # the published stack: 4 + 5 periods + 3
    full = KimiLinearConfig()
    assert (full.period, full.whole_periods, full.kinds.count("kda")) == (
        4, 5, 20)
    with pytest.raises(ValueError, match="full_attn_layers"):
        KimiLinearConfig(full_attn_layers=(1, 5))


def test_the_decay_spans_what_the_configuration_assumes(model):
    """``with_decay`` replaced N(0, 0.02) by the family's draw, the
    reference draws the same leaves, and a step's decay spans
    ~0.85-0.9999."""
    a = model.head[0].attn
    p = R.layer_params(ARCH, W.root_key(SEED), 0)
    np.testing.assert_array_equal(np.asarray(a.A_log),
                                  np.asarray(p["attn.A_log"]))
    np.testing.assert_array_equal(np.asarray(a.dt_bias),
                                  np.asarray(p["attn.dt_bias"]))
    blk = model.blocks.block.layers[1].attn                # scanned: [1, ...]
    q = R.layer_params(ARCH, W.root_key(SEED), 5)
    np.testing.assert_array_equal(np.asarray(blk.dt_bias[0]),
                                  np.asarray(q["attn.dt_bias"]))
    alpha = np.exp(-np.exp(np.asarray(a.A_log))[:, None]
                   * np.asarray(jax.nn.softplus(a.dt_bias)).reshape(2, 16))
    assert 0.8 < alpha.min() < 0.99 and alpha.max() > 0.998


def test_full_forward_agrees_with_reference(model, ids, ref_logits):
    np.testing.assert_allclose(np.asarray(model(jnp.asarray(ids))),
                               ref_logits, **TOL)


def test_contiguous_cache_agrees_with_reference(model, ids, ref_logits):
    latent, state = model.init_cache(2, 96)
    assert [c.shape for c in latent] == [(3, 2, 1, 96, 128)]
    assert [r.shape for r in state.rows] == [(8, 2, 2, 16, 16),
                                             (8, 2, 1, 288)]
    assert state.rows[0].dtype == jnp.float32
    got = []
    lg, cache = _cached(model, jnp.asarray(ids[:, :40]), (latent, state),
                        jnp.int32(0))
    got.append(lg)
    lg, cache = _cached(model, jnp.asarray(ids[:, 40:80]), cache,
                        jnp.int32(40))
    got.append(lg)
    for t in range(80, 90):
        lg, cache = _cached(model, jnp.asarray(ids[:, t:t + 1]), cache,
                            jnp.int32(t))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               ref_logits, **TOL)


def test_a_padded_bucket_is_the_unpadded_prompt(model, ids, ref_logits):
    """A prefill bucket pads its tail: told the true length, the state
    and the convolution tail come out as the unpadded prompt leaves
    them, and the next chunk goes on from there."""
    latent, state = model.init_cache(1, 96)
    pad = np.zeros((1, 64), np.int32)
    pad[:, :50] = ids[:1, :50]
    lg, (latent, padded) = _cached(
        model, jnp.asarray(pad),
        (latent, StateCache(state.rows, jnp.int32(50))), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(lg[:, :50]), ref_logits[:1, :50],
                               **TOL)
    # the same bucket with every position true, then cut: what the
    # padding would have done to the rows shows
    _, (_, spoilt) = _cached(
        model, jnp.asarray(pad),
        (model.init_cache(1, 96)[0], StateCache(state.rows, jnp.int32(64))),
        jnp.int32(0))
    assert not np.allclose(np.asarray(spoilt.rows[0]),
                           np.asarray(padded.rows[0]), atol=1e-3)
    lg, _ = _cached(model, jnp.asarray(ids[:1, 50:90]),
                    (latent, StateCache(padded.rows, jnp.int32(40))),
                    jnp.int32(50))
    np.testing.assert_allclose(np.asarray(lg), ref_logits[:1, 50:], **TOL)
    # a length of 0 is the identity on the rows, bit for bit
    _, (_, same) = _cached(
        model, jnp.asarray(ids[:1, :1]),
        (latent, StateCache(padded.rows, jnp.int32(0))), jnp.int32(50))
    for a, b in zip(same.rows, padded.rows):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_programs_agree_with_reference(model, eng, ids, ref_logits):
    """The model on the engine's own state, by hand — a chunk of a
    prompt on a ``PagedCache`` and a slot's state rows — and the engine
    itself, prefill in chunks and decode steps: the reference's logits,
    the reference's choice at every position."""
    pool, rows = eng._state["cache"]
    assert [x.shape for x in rows] == [(3, 8, 1, 2, 16, 16),
                                       (3, 8, 1, 1, 288)]
    table = jnp.arange(1, 25, dtype=jnp.int32)
    start = tuple(jnp.zeros_like(r[1]) for r in rows)
    lg, (chunk, st) = _cached(
        model, jnp.asarray(ids[:1, :16]),
        (PagedCache(pool, table), StateCache(start, jnp.int32(16))),
        jnp.int32(0))
    np.testing.assert_allclose(np.asarray(lg), ref_logits[:1, :16], **TOL)
    assert chunk[0].shape == (3, 1, 1, 16, 128)
    prompt = ids[0, :70]
    got = drain(eng, eng.start(prompt, 20))
    assert eng.stats()["decode_attn"] == "gather"      # the CPU's arms
    assert eng.stats()["kda_step"] == "xla"
    everything_back(eng)
    want = np.argmax(np.asarray(R.forward_logits(
        ARCH, SEED, np.concatenate([prompt, got])[None]))[0, 69:-1], -1)
    assert got == want.tolist()


# -- (b) the latent layers' attention ---------------------------------------------

def test_mla_without_q_rank_and_without_rotation_is_the_expanded_form():
    cfg = DeepseekV3Config(
        hidden_size=64, num_layers=2, num_heads=4, q_lora_rank=None,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope=False, rope_factor=1.0, rms_eps=1e-5,
        dtype="float32")
    attn = MLAttention(cfg, key=jax.random.PRNGKey(1))
    assert hasattr(attn, "wq") and not hasattr(attn, "wq_a")
    assert attn.scale == 24 ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 30, 64))
    p = {"attn.wq.weight": attn.wq.weight,
         "attn.wkv_a.weight": attn.wkv_a.weight,
         "attn.kv_norm.weight": attn.kv_norm.weight,
         "attn.wkv_b.weight": attn.wkv_b.weight,
         "attn.wo.weight": attn.wo.weight}
    want = jax.vmap(lambda r: R.mla_row(r, p, ARCH))(x)
    np.testing.assert_allclose(np.asarray(attn(x)), np.asarray(want), **TOL)
    # a shifted start changes nothing: there is no position anywhere
    from paddle_tpu.models._common import init_latent_cache
    cache = init_latent_cache(1, 2, 64, 24, jnp.float32)
    out, pay = attn(x[:, :20], cache=cache, index=0)
    cache = (cache[0].at[0, :, :, :20].set(pay[0]),)
    out2, _ = attn(x[:, 20:], cache=cache, index=jnp.int32(20))
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([out, out2], 1)), np.asarray(want), **TOL)
    # the low-rank pair is still what the DeepSeek configuration builds
    assert hasattr(MLAttention(DeepseekV3Config.tiny(),
                               key=jax.random.PRNGKey(0)), "wq_b")


# -- (c) the shares add up to the uncut layer ---------------------------------------

def expert_layer_params(seed):
    key = W.root_key(seed)
    a = R.Arch.from_config(dict(CFG, held=[0, 16], num_experts=16))
    shapes = a.layer_shapes(1)
    return a, {n: W.layer_leaf_f32(key, ".share." + n, 0, *shapes[n])
               for n in shapes if n.startswith("moe.")}


def share_layer(p, first, count):
    m = MoEMLP(64, 32, 16, top_k=4, route="sigmoid_group", n_group=1,
               topk_group=1, routed_scale=2.446, shared_size=32,
               held=(first, count), key=jax.random.PRNGKey(0))
    assert m.w_gate.shape == (count, 64, 32)      # nothing else allocated
    sl = slice(first, first + count)
    return m.replace(
        router=p["moe.router"], select_bias=p["moe.select_bias"],
        w_gate=p["moe.w_gate"][sl], w_up=p["moe.w_up"][sl],
        w_down=p["moe.w_down"][sl], shared_gate=p["moe.shared_gate"],
        shared_up=p["moe.shared_up"], shared_down=p["moe.shared_down"])


@pytest.mark.parametrize("count", [1, 4, 16], ids=lambda c: f"{16 // c}shares")
def test_shares_and_one_shared_expert_add_up_to_the_uncut_layer(count):
    """Guide section 4 with THIS router: one group (the group limit
    never bites), renormalised gates times 2.446."""
    a, p = expert_layer_params(SEED)
    assert (a.groups, a.groups_kept, a.routed_scale) == (1, 1, 2.446)
    h = jnp.asarray(np.random.default_rng(5).normal(size=(2, 24, 64)),
                    jnp.float32)
    routed_part, shared_part = (reference_latent.routed_part,
                                reference_latent.shared_part)
    uncut = jax.vmap(lambda r: routed_part(r, p, a) + shared_part(r, p, a)
                     )(h)
    shared = jax.vmap(lambda r: shared_part(r, p, a))(h)
    routed = sum(share_layer(p, first, count)(h)[0] - shared
                 for first in range(0, 16, count))
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(uncut), atol=1e-5, rtol=1e-4)
    expert, gate = reference_latent.route(h[0], p["moe.router"],
                                          p["moe.select_bias"], a)
    np.testing.assert_allclose(np.asarray(gate.sum(-1)), 2.446, rtol=1e-5)
    assert len({tuple(sorted(r)) for r in np.asarray(expert)}) > 1


# -- (d) the engine: snapshots -------------------------------------------------------

def test_a_prefix_hit_restores_a_snapshot_and_serves_what_a_cold_prefill_does(
        model, eng, template):
    """Streams behind one template: the first prefills it cold and leaves
    a snapshot at every chunk's end; the others restore the deepest one
    and prefill their tails alone. Every stream equals solo
    ``generate()`` — and a stream whose snapshot was thrown away would
    not."""
    ps = prompts_of(template, (7, 13, 5))
    r0 = monitor.get_stat("gen/state_restores") or 0
    s0 = monitor.get_stat("gen/state_snapshots") or 0
    b0 = state_block(eng)
    first = drain(eng, eng.start(ps[0], 12))
    assert greedy(model, ps[0], first)
    blk = state_block(eng)
    assert blk["admissions"] - b0["admissions"] == 1
    assert blk["restores"] == b0["restores"]
    # 6 chunks of 16 end on a page boundary, 4 entries: 2 evicted
    assert (monitor.get_stat("gen/state_snapshots") or 0) - s0 == 6
    assert blk["snapshots_free"] == 0
    saved0 = monitor.get_stat("gen/prefix_tokens_saved") or 0
    gids = [eng.start(p, 12) for p in ps[1:]]
    for gid, p in zip(gids, ps[1:]):
        assert greedy(model, p, drain(eng, gid))
    blk = state_block(eng)
    assert blk["admissions"] - b0["admissions"] == 3
    assert blk["restores"] - b0["restores"] == 2
    assert (monitor.get_stat("gen/state_restores") or 0) - r0 == 2
    # both hits are the whole template, 96 tokens deep
    assert (monitor.get_stat("gen/prefix_tokens_saved") or 0
            ) - saved0 == 2 * template.size
    assert blk["bytes_per_slot"] == 8 * (2 * 16 * 16 * 4 + 3 * 96 * 4)
    everything_back(eng)


def test_a_restore_from_the_wrong_snapshot_would_show(model, eng, template):
    """The oracle can tell: with the template's snapshot overwritten by
    zeros on the device, the stream that restores it no longer serves
    the greedy continuation."""
    ps = prompts_of(template, (6, 9), seed=17)
    assert greedy(model, ps[0], drain(eng, eng.start(ps[0], 10)))
    with eng._cond:
        entry = eng._prefix.find(template)
        assert entry is not None and entry.snap
        eng._state = dict(eng._state, snaps=tuple(
            s.at[entry.snap].set(0) for s in eng._state["snaps"]))
    assert not greedy(model, ps[1], drain(eng, eng.start(ps[1], 10)))
    everything_back(eng)


def test_a_match_deeper_than_the_deepest_snapshot_is_cut_back(model, eng,
                                                              template):
    """The second prompt shares the template AND 12 more tokens with the
    first (one whole page past the template's end). No snapshot lies
    past 96, so the match ends there and the shared page is prefilled
    again — from the restored state."""
    rng = np.random.default_rng(7)
    shared = np.concatenate([template, rng.integers(1, 256, 12,
                                                    dtype=np.int32)])
    a = np.concatenate([shared, rng.integers(1, 256, 3, dtype=np.int32)])
    b = np.concatenate([shared, rng.integers(1, 256, 9, dtype=np.int32)])
    assert greedy(model, a, drain(eng, eng.start(a, 6)))
    # pages entered only up to the deepest snapshot: 96 / 8
    assert eng.stats()["prefix_entries"] == 12
    saved0 = monitor.get_stat("gen/prefix_tokens_saved") or 0
    assert greedy(model, b, drain(eng, eng.start(b, 6)))
    assert (monitor.get_stat("gen/prefix_tokens_saved") or 0
            ) - saved0 == 96
    everything_back(eng)


def test_prefix_cache_books_of_snapshots():
    """By hand: a match is cut back to the deepest entry that holds a
    snapshot, taking a snapshot from an entry leaves its pages to be
    matched THROUGH, evicting an entry releases its snapshot, and a
    pinned snapshot is never the victim."""
    pool, snaps = _PagePool(16), _SnapshotPool(2)
    cache = _PrefixCache(4, None, snaps)
    prompt = np.arange(1, 30, dtype=np.int32)
    pages = pool.alloc(7)
    one, two = snaps.alloc(), snaps.alloc()
    assert (one, two, snaps.alloc(), snaps.free_count) == (1, 2, 0, 0)
    cache.insert(prompt[:8], pages, pool, snap=one)          # pages 0-1
    cache.insert(prompt[:16], pages, pool, snap=two)         # pages 0-3
    assert len(cache) == 4 and cache.find(prompt[:16]).snap == two
    assert cache.find(prompt[:20]) is None
    got, snap = cache.match_state(prompt, pool)
    assert (got, snap) == (pages[:4], two) and snaps.refcount(two) == 2
    # the deeper one is pinned by the match: the shallower one goes
    assert cache.evict_snapshot() and snaps.free_count == 1
    assert cache.find(prompt[:8]).snap == 0
    assert not cache.evict_snapshot()                        # two is pinned
    for pid in got:
        pool.release(pid)
    snaps.release(snap)
    # through the snapshot-less pages to the deeper snapshot, still
    got, snap = cache.match_state(prompt, pool)
    assert (len(got), snap) == (4, two)
    for pid in got:
        pool.release(pid)
    snaps.release(snap)
    # a second snapshot for an entry that has one is given back
    dup = snaps.alloc()
    cache.insert(prompt[:16], pages, pool, snap=dup)
    assert snaps.free_count == 1 and cache.find(prompt[:16]).snap == two
    # with its last snapshot gone nothing can be matched
    assert cache.evict_snapshot() and snaps.free_count == 2
    assert cache.match_state(prompt, pool) == ([], 0)
    # entries leave leaf-first and release what they hold
    cache.insert(prompt[:16], pages, pool, snap=snaps.alloc())
    for pid in pages:
        pool.release(pid)
    assert cache.evict(8, pool) == 4 and snaps.free_count == 2
    assert pool.free_count == 16
    with pytest.raises(AssertionError, match="underflow"):
        snaps.release(1)


def test_eviction_frees_the_snapshot(model, template):
    rng = np.random.default_rng(9)
    with GenerationEngine(model, **dict(ENGINE, pages=40)) as eng:
        (p,) = prompts_of(template, (5,))
        assert greedy(model, p, drain(eng, eng.start(p, 4)))
        assert state_block(eng)["snapshots_free"] == 0
        before = monitor.get_stat("gen/state_snapshot_evictions") or 0
        # another template of the same length: the pool of 40 pages has
        # to evict the first one's entries, and their snapshots with them
        other = rng.integers(1, 256, 101, dtype=np.int32)
        assert greedy(model, other, drain(eng, eng.start(other, 4)))
        assert (monitor.get_stat("gen/state_snapshot_evictions") or 0
                ) > before
        # the first template is prefilled cold again, and right
        (q,) = prompts_of(template, (9,), seed=4)
        assert greedy(model, q, drain(eng, eng.start(q, 4)))
        everything_back(eng)


@pytest.mark.parametrize("how", ["retired", "cancel_mid_prefill",
                                 "cancel_mid_decode"])
def test_a_reused_slot_starts_clean(model, one_slot, template, how):
    """One slot: whatever the stream before left in its rows — a
    finished generation, half a prefill, a cancelled decode — the next
    admission starts from its own snapshot (or from zeros)."""
    ps = prompts_of(template, (11, 4), seed=21)
    cold = np.random.default_rng(22).integers(1, 256, 37, dtype=np.int32)
    eng = one_slot
    gid = eng.start(ps[0], 30)
    if how == "retired":
        assert greedy(model, ps[0], drain(eng, gid))
    elif how == "cancel_mid_prefill":
        eng.cancel(gid)
    else:
        while not eng.poll(gid, 0, wait_s=60.0)["tokens"]:
            pass
        eng.cancel(gid)
    # a miss (zeros), then a hit (the template's snapshot, where the
    # first stream got far enough to leave one)
    assert greedy(model, cold, drain(eng, eng.start(cold, 8)))
    assert greedy(model, ps[1], drain(eng, eng.start(ps[1], 8)))
    everything_back(eng)


def test_depths_give_the_same_tokens(model, eng, template):
    """Four streams over three slots, with and without the lookahead
    (``eng`` dispatches a step ahead of the tokens it reads)."""
    ps = prompts_of(template, (6, 10, 3, 8), seed=31)
    gids = [eng.start(p, 14) for p in ps]
    ahead = [drain(eng, g) for g in gids]
    everything_back(eng)
    with GenerationEngine(model, async_depth=0, **ENGINE) as sync:
        gids = [sync.start(p, 14) for p in ps]
        assert [drain(sync, g) for g in gids] == ahead
        everything_back(sync)
    assert all(greedy(model, p, t) for p, t in zip(ps, ahead))


def test_restores_and_snapshots_are_spans_of_the_loop(model, eng, template):
    ps = prompts_of(template, (5, 7), seed=41)
    trace.clear()
    from paddle_tpu.core.flags import set_flags
    set_flags({"trace": True})
    try:
        for p in ps:
            drain(eng, eng.start(p, 3))
        spans = trace.get_spans()
    finally:
        set_flags({"trace": False})
    everything_back(eng)
    took = [s for s in spans if s["name"] == "gen/state_snapshot"]
    back = [s for s in spans if s["name"] == "gen/state_restore"]
    assert len(took) == 6 and len(back) == 1
    assert sorted(s["attrs"]["tokens"] for s in took) == [
        16, 32, 48, 64, 80, 96]
    assert sum(s["attrs"]["evicted"] for s in took) == 2
    assert back[0]["attrs"]["tokens"] == 96 and back[0]["attrs"]["snapshot"]


# -- (e) what is refused, by name --------------------------------------------------

@pytest.mark.parametrize("kwargs,names", [
    (dict(cache_dtype=jnp.int8), "int8 cache .* recurrent state group"),
    (dict(mesh_tp=2), "gen_mesh_tp with a recurrent state group"),
    (dict(spec_k=2, spec_mode="ngram"), "gen_spec_k .* recurrent state"),
    (dict(kv_store=True), "gen_kv_store .* recurrent state"),
    (dict(role="decode"), "gen_kv_store / gen_role"),
    (dict(sched=True), "gen_sched .* recurrent state"),
    (dict(pages=(8, 8)), "one count, or one a group"),
    (dict(state_snapshots=0), "state_snapshots must be >= 1"),
    (dict(paged=False), "recurrent state group on the contiguous engine"),
], ids=["int8-cache", "gen_mesh_tp", "gen_spec_k", "gen_kv_store",
        "gen_role", "gen_sched", "pages-a-group", "no-snapshots",
        "contiguous"])
def test_constructions_that_must_refuse(model, kwargs, names):
    with pytest.raises(ValueError, match=names):
        GenerationEngine(model, **dict(ENGINE, **kwargs))


def test_a_draft_model_and_other_mixes_refuse(model):
    with pytest.raises(ValueError, match="draft model beside a latent"):
        GenerationEngine(model, **dict(ENGINE, spec_k=2, spec_mode="draft",
                                       draft_model=model))

    class Odd:
        cache_groups = ((2, "state"), (3, None))

        def init_cache(self, *a, **k):
            raise AssertionError("never reached")

    with pytest.raises(ValueError, match="one full group followed by one "
                                         "window group or by one state"):
        GenerationEngine(Odd(), **ENGINE)


def test_without_a_prefix_cache_every_stream_starts_from_zeros(model,
                                                               template):
    ps = prompts_of(template, (5, 9), seed=51)
    s0 = monitor.get_stat("gen/state_snapshots") or 0
    with GenerationEngine(model, **dict(ENGINE, prefix_cache=False)) as eng:
        for p in ps:
            assert greedy(model, p, drain(eng, eng.start(p, 6)))
        blk = state_block(eng)
        assert blk["restores"] == 0 and blk["snapshots_free"] == 4
    assert (monitor.get_stat("gen/state_snapshots") or 0) == s0
