"""DeepSeek-V3-family support (GigaChat3.1): latent attention over a
latent cache, sigmoid group-limited routing, a shared expert, a held
share of the routed experts — the program against the plain reference
(``benchmarks/lib/reference_latent.py``, which imports nothing of it) on
seeded weights, tiny widths, float32."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.builders import common
from benchmarks.lib import reference_latent as R
from benchmarks.lib import weights as W
from paddle_tpu.models.deepseek_v3 import (
    DeepseekV3Config, DeepseekV3ForCausalLM, yarn_inv_freq, yarn_mscale,
)
from paddle_tpu.models.generation import (
    PagedCache, generate, init_paged_cache, paged_scatter, paged_write,
)
from paddle_tpu.nn.moe import MoEMLP, sigmoid_group_picks
from paddle_tpu.serving.engine import GenerationEngine

SEED = 2 ** 31 + 7
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32,
        "rope_type": "yarn"}
ARGS = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_layers=3, first_k_dense=1, num_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, max_seq_len=96, rope_base=100000.0,
    rope_factor=4.0, rope_original_max=32, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rms_eps=1e-6, dtype="float32", n_routed_experts=16,
    num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, n_shared_experts=1, held=[4, 8])
CFG = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "vocab_size": 256, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "n_routed_experts": 8, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "n_shared_experts": 1, "rope_theta": 100000.0, "rope_scaling": YARN,
    "rms_norm_eps": 1e-6, "torch_dtype": "float32", "held": [4, 8],
    "published": {"n_routed_experts": 16},
    "program": {
        "model": "paddle_tpu.models.deepseek_v3:DeepseekV3ForCausalLM",
        "config": "paddle_tpu.models.deepseek_v3:DeepseekV3Config",
        "config_args": ARGS}}
ARCH = R.Arch.from_config(CFG)


@pytest.fixture(scope="module")
def model():
    template = common.model_template(CFG)
    return jax.jit(lambda k: common.seeded_model(template, k))(
        W.root_key(SEED))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(1, 256, (2, 40), dtype=np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    return np.asarray(R.forward_logits(ARCH, SEED, ids))


# -- (a) the full forward ------------------------------------------------------

def test_full_forward_agrees_with_reference(model, ids, ref_logits):
    np.testing.assert_allclose(np.asarray(model(jnp.asarray(ids))),
                               ref_logits, atol=2e-5, rtol=1e-4)


# -- (b) prefill, a chunk behind a cached prefix, token-by-token decode ---------

def test_contiguous_cache_absorbed_form_agrees_with_reference(
        model, ids, ref_logits):
    cache = model.init_cache(2, 48)
    assert [c.shape for c in cache] == [(3, 2, 1, 48, 128)]  # 24 -> a tile
    got = []
    lg, cache = model.forward_with_cache(jnp.asarray(ids[:, :16]), cache, 0)
    got.append(lg)                       # expanded: nothing behind it
    lg, cache = model.forward_with_cache(jnp.asarray(ids[:, 16:32]), cache,
                                         jnp.asarray(16))
    got.append(lg)                       # absorbed, a cached prefix behind
    for t in range(32, 40):              # absorbed, one token at a time
        lg, cache = model.forward_with_cache(jnp.asarray(ids[:, t:t + 1]),
                                             cache, jnp.asarray(t))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               ref_logits, atol=2e-5, rtol=1e-4)


def test_paged_programs_agree_with_reference(model, ids, ref_logits):
    """What the engine's paged prefill and step do, by hand: the latent
    leaf goes through ``init_paged_cache`` / ``PagedCache.read_layer`` /
    ``paged_scatter`` / ``paged_write`` as it is."""
    P = 8
    pool = init_paged_cache(model.init_cache(1, 48), 12, P)
    assert [p.shape for p in pool] == [(13, 3, 1, P, 128)]
    row = jnp.asarray([5, 2, 9, 1, 7, 0], jnp.int32)
    seq = jnp.asarray(ids[:1])
    got = []
    for start in (0, 16):                # two prefill chunks, traced index
        lg, chunk = model.forward_with_cache(
            seq[:, start:start + 16], PagedCache(pool, row),
            jnp.asarray(start))
        pool = paged_scatter(pool, row, chunk, start, P, length=16)
        got.append(lg)
    for t in range(32, 40):
        lg, new = model.forward_with_cache(
            seq[:, t:t + 1], PagedCache(pool, row), jnp.asarray(t))
        pool = paged_write(pool, row[t // P][None], jnp.asarray([t % P]),
                           tuple(n[:, 0, :, 0][None] for n in new))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               ref_logits[:1], atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("engine", [
    dict(paged=True, prefix_cache=True, pages=48, page_tokens=8,
         prefill_chunk=16),
    dict(paged=False)], ids=["paged-prefix-chunked", "contiguous"])
def test_engine_serves_the_reference_choice(model, engine):
    """Through ``GenerationEngine``: every served token is the
    reference's first choice given what was served before it, streams
    equal solo ``generate()``, and the pick counters come out of
    ``stats()``."""
    rng = np.random.default_rng(11)
    tmpl = rng.integers(1, 256, 32, dtype=np.int32)
    prompts = [np.concatenate([tmpl, rng.integers(1, 256, n,
                                                  dtype=np.int32)])
               for n in (5, 9, 14)]
    from paddle_tpu.core import monitor
    saved = monitor.get_stat("gen/prefix_tokens_saved") or 0
    with GenerationEngine(model, slots=2, max_len=96, **engine) as eng:
        gens = [eng.start(p, 6) for p in prompts]
        served = []
        for g in gens:
            toks = []
            while True:
                r = eng.poll(g, len(toks))
                toks += list(r["tokens"])
                if r["done"]:
                    break
            served.append(np.asarray(toks, np.int32))
        st = eng.stats()
    seqs = np.zeros((3, 64), np.int32)
    spans = []
    for i, (p, t) in enumerate(zip(prompts, served)):
        np.testing.assert_array_equal(
            t, np.asarray(generate(model, p[None], 6))[0, p.size:])
        seqs[i, :p.size], seqs[i, p.size:p.size + 6] = p, t
        spans.append((p.size, p.size + 6))
    gaps, _ = R.serve_logit_gaps(ARCH, SEED, seqs, spans)
    assert float(np.concatenate(gaps).max()) == 0.0
    assert st["kv_bytes_per_token"] == 3 * 128 * 4     # as allocated
    # 2 expert layers x 4 picks a live position
    saved = (monitor.get_stat("gen/prefix_tokens_saved") or 0) - saved
    assert saved >= 32 if engine["paged"] else saved == 0
    assert st["moe_picks"] == 8 * (sum(p.size for p in prompts) - saved
                                   + 3 * 5)
    assert 0 < st["moe_picks_held"] < st["moe_picks"]


# -- (c) the router against hand-made cases -------------------------------------

def logit(p):
    return math.log(p / (1 - p))


def test_bias_moves_picks_and_not_gates():
    s = np.full((1, 8), 0.10, np.float32)
    s[0, [0, 1]] = 0.9, 0.8                   # group 0 = experts 0..3
    s[0, 2], s[0, 3] = 0.5, 0.4
    logits = jnp.asarray(np.vectorize(logit)(s), jnp.float32)
    zero = jnp.zeros((8,), jnp.float32)
    bias = zero.at[3].set(0.2)                # 0.4 + 0.2 > 0.5
    e0, g0 = sigmoid_group_picks(logits, zero, 3, 2, 1, 2.5)
    e1, g1 = sigmoid_group_picks(logits, bias, 3, 2, 1, 2.5)
    assert sorted(np.asarray(e0[0])) == [0, 1, 2]
    assert sorted(np.asarray(e1[0])) == [0, 1, 3]
    want = np.array([0.9, 0.8, 0.4]) / 2.1 * 2.5     # s, never s + b
    np.testing.assert_allclose(sorted(np.asarray(g1[0]), reverse=True), want,
                               rtol=1e-5)


def test_group_limit_excludes_a_globally_top_expert():
    s = np.full((1, 8), 0.05, np.float32)
    s[0, 0], s[0, 1] = 0.9, 0.8               # group 0: score 1.7
    s[0, 4] = 0.95                            # group 1: 0.95 + 0.05 = 1.0
    logits = jnp.asarray(np.vectorize(logit)(s), jnp.float32)
    e, _ = sigmoid_group_picks(logits, jnp.zeros((8,)), 2, 2, 1, 1.0)
    assert sorted(np.asarray(e[0])) == [0, 1]       # 4 is the global best
    e, _ = sigmoid_group_picks(logits, jnp.zeros((8,)), 2, 2, 2, 1.0)
    assert sorted(np.asarray(e[0])) == [0, 4]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gates_sum_to_the_scale_and_agree_with_reference(seed):
    rs = np.random.default_rng(seed)
    h = jnp.asarray(rs.normal(size=(32, 64)), jnp.float32)
    router = jnp.asarray(rs.normal(size=(64, 16)) * 0.3, jnp.float32)
    bias = jnp.asarray(rs.normal(size=(16,)) * 0.1, jnp.float32)
    e, g = sigmoid_group_picks(h @ router, bias, 4, 4, 2, 2.5)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 2.5, rtol=1e-5)
    re, rg = R.route(h, router, bias, ARCH)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(re))
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg), rtol=1e-5)
    # never more than topk_group groups of 4 experts
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(e))


# -- (d) the shares add up to the uncut layer -----------------------------------

def expert_layer_params(seed, experts=16):
    key = W.root_key(seed)
    a = R.Arch.from_config(dict(CFG, held=[0, experts],
                                n_routed_experts=experts))
    names = [n for n in a.layer_shapes(True) if n.startswith("moe.")]
    return a, {n: W.layer_leaf_f32(key, R.EXPERT + n, 0,
                                   *a.layer_shapes(True)[n]) for n in names}


def share_layer(p, first, count):
    m = MoEMLP(64, 32, 16, top_k=4, route="sigmoid_group", n_group=4,
               topk_group=2, routed_scale=2.5, shared_size=32,
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
    a, p = expert_layer_params(SEED)
    h = jnp.asarray(np.random.default_rng(5).normal(size=(2, 24, 64)),
                    jnp.float32)
    uncut = jax.vmap(lambda r: R.routed_part(r, p, a) + R.shared_part(r, p, a)
                     )(h)
    shared = jax.vmap(lambda r: R.shared_part(r, p, a))(h)
    routed = sum(share_layer(p, first, count)(h)[0] - shared
                 for first in range(0, 16, count))
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(uncut), atol=1e-5, rtol=1e-4)
    # and one share is what the reference gives for that share
    f = 16 - count
    one = R.routed_part(h[0], dict(p, **{
        n: p[n][f:f + count] for n in ("moe.w_gate", "moe.w_up",
                                       "moe.w_down")}), a, held=(f, count))
    np.testing.assert_allclose(
        np.asarray(share_layer(p, f, count)(h)[0][0] - shared[0]),
        np.asarray(one), atol=1e-5, rtol=1e-4)


# -- (e) dropless ---------------------------------------------------------------

@pytest.mark.parametrize("tokens", [1, 7, 64])
def test_a_chunk_that_all_picks_one_held_expert_loses_nothing(tokens):
    a, p = expert_layer_params(SEED + 1)
    # a selection bias that sends every token to experts 4..7 (one group)
    p = dict(p, **{"moe.select_bias": jnp.zeros((16,)).at[4:8].set(10.0)})
    layer = share_layer(p, 4, 2)                     # holds 4 and 5
    h = jnp.asarray(np.random.default_rng(tokens).normal(
        size=(1, tokens, 64)), jnp.float32)
    out, _ = layer(h)
    expert, gate = R.route(h[0], p["moe.router"], p["moe.select_bias"], a)
    assert (np.sort(np.asarray(expert), -1) == [4, 5, 6, 7]).all()
    want = (R.routed_part(h[0], dict(p, **{
        n: p[n][4:6] for n in ("moe.w_gate", "moe.w_up", "moe.w_down")}),
        a, held=(4, 2)) + R.shared_part(h[0], p, a))
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               atol=1e-5, rtol=1e-4)
    # every token's two held picks carry their gates: none is nought
    held_gate = np.where(np.asarray(expert) < 6, np.asarray(gate), 0).sum(-1)
    assert (held_gate > 0.1).all()


# -- (f) YaRN -------------------------------------------------------------------

def test_yarn_frequencies_and_scale_at_the_published_numbers():
    inv = yarn_inv_freq(64, 1e5, 64.0, 4096, 32.0, 1.0)
    plain = 1e5 ** (-np.arange(32) / 32.0)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e5) = 8.38 -> 8,
    # 64 ln(4096 / (2 pi)) / (2 ln 1e5) = 18.01 -> 19
    np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(inv[19:], plain[19:] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        inv[13], plain[13] * (1 - 5 / 11) + plain[13] / 64 * (5 / 11),
        rtol=1e-6)
    np.testing.assert_allclose(inv, R.yarn_inv_freq(R.Arch.from_config(dict(
        CFG, qk_rope_head_dim=64, rope_scaling=dict(
            YARN, factor=64, original_max_position_embeddings=4096)))),
        rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4159) < 1e-4 and yarn_mscale(64.0, 1.0) == m
    cfg = DeepseekV3Config(num_heads=2, num_layers=2, first_k_dense=1,
                           rope_factor=64.0, v_head_dim=192)
    attn = jax.eval_shape(
        lambda: DeepseekV3ForCausalLM(
            DeepseekV3Config.tiny(rope_factor=64.0, qk_nope_head_dim=128,
                                  qk_rope_head_dim=64),
            key=jax.random.PRNGKey(0))).blocks.block.attn
    assert abs(attn.scale - 192 ** -0.5 * m * m) < 1e-9
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == 192


@pytest.mark.parametrize("position", [5000, 200000])
def test_rope_tables_beyond_the_original_context(position):
    cfg = DeepseekV3Config.tiny(qk_rope_head_dim=64, rope_base=1e5,
                                rope_factor=64.0, rope_original_max=4096)
    attn = jax.eval_shape(lambda: DeepseekV3ForCausalLM(
        cfg, key=jax.random.PRNGKey(0))).blocks.block.attn
    cos, sin = attn.rope_tables(jnp.asarray([position]))
    plain = 1e5 ** (-np.arange(32) / 32.0)
    for i, f in ((0, plain[0]), (31, plain[31] / 64),
                 (13, plain[13] * (6 / 11) + plain[13] / 64 * (5 / 11))):
        # float32 angles: the product's rounding at 2e5 rad is ~0.02
        ang = np.float32(position) * np.float32(f)
        np.testing.assert_allclose(float(cos[0, i]), math.cos(ang), atol=1e-3)
        np.testing.assert_allclose(float(sin[0, i]), math.sin(ang), atol=1e-3)


# -- (g) what has to refuse, by name ----------------------------------------------

@pytest.mark.parametrize("kwargs,names", [
    (dict(cache_dtype=jnp.int8), "int8 latent cache"),
    (dict(mesh_tp=2), "gen_mesh_tp"),
    (dict(spec_k=2, spec_mode="draft", draft_model="model"), "draft model"),
], ids=["int8-latent-cache", "gen_mesh_tp", "draft-model"])
def test_constructions_that_must_refuse(model, kwargs, names):
    if kwargs.get("draft_model") == "model":
        kwargs = dict(kwargs, draft_model=model)
    with pytest.raises(ValueError, match=names):
        GenerationEngine(model, slots=2, max_len=32, paged=True, pages=8,
                         page_tokens=8, **kwargs)


# -- (h) the softmax layer is what it was ---------------------------------------

@pytest.mark.parametrize("mode,digest", [
    ("gather",
     "064de74851cdb6a0b6d351c6cce777990586c5405c7d666230d0f1c2f98fa11b"),
    ("einsum", None)])
def test_softmax_layer_is_bit_identical_to_the_parent(mode, digest):
    """``digest``: sha256 of this layer's float32 output bytes on this
    seed as the parent commit (c3f35b5) computed it on this CPU; the
    einsum form has to agree with the gather form as it always did."""
    m = MoEMLP(64, 32, 8, top_k=2, capacity_factor=4.0, dispatch_mode=mode,
               key=jax.random.PRNGKey(3))
    assert not hasattr(m, "held") and not hasattr(m, "_uid")
    assert [n for n, _ in m._pspecs] == ["router", "w_gate", "w_up", "w_down"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 64))
    y, aux = jax.jit(lambda m, x: m(x))(m, x)
    if digest:
        assert hashlib.sha256(np.asarray(y).tobytes()).hexdigest() == digest
        assert float(aux) == 1.0098843574523926
    else:
        ref = MoEMLP(64, 32, 8, top_k=2, capacity_factor=4.0,
                     dispatch_mode="gather", key=jax.random.PRNGKey(3))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x)[0]),
                                   atol=1e-6)
