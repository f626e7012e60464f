"""Laguna support: window and full attention layers with their own query
head counts over the same KV heads, a sigmoid gate on every head, YaRN
on half of a full layer's head and plain RoPE on all of a window
layer's, a leading dense layer, expert layers with a shared expert and a
routed scale — the program against the plain reference
(``benchmarks/lib/reference_laguna.py``, which imports nothing of it) on
seeded weights, tiny widths, float32: 4 query heads on full layers and 6
on window layers over 2 KV heads, windows of 8 and 16 positions against
prefill chunks of 16, pages of 8."""

import functools
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.builders import common
from benchmarks.lib import reference_laguna as R
from benchmarks.lib import weights as W
from paddle_tpu.core import monitor
from paddle_tpu.models import LagunaConfig, LagunaForCausalLM
from paddle_tpu.models import _common
from paddle_tpu.models.generation import (PagedCache, init_paged_cache,
                                          paged_scatter, paged_write)
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.ops.pallas import _support
from paddle_tpu.serving.engine import GenerationEngine

WINDOW, P, MAXLEN = 8, 8, 128
# float32 sums in another order (a cache read in two pieces, a scan over
# periods against a loop over layers, the held experts on every token
# against the picked ones): as the other families' tests
TOL = dict(atol=2e-5, rtol=1e-4)
ARGS = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
            num_layers=9, num_heads=4, window_heads=6, num_kv_heads=2,
            head_dim=16, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, num_experts=8,
            num_experts_per_tok=3, sliding_window=WINDOW,
            rope_original_max=32, max_seq_len=MAXLEN, dtype="float32")
# the reference's view: the catalog's keys
CFG = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 9,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "mlp_only_layers": [0],
    "sliding_window": WINDOW, "moe_routed_scaling_factor": 2.5,
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 3,
    "num_attention_heads_per_layer": ([4] + [6] * 3) * 3,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "torch_dtype": "float32"}
ARCH = R.Arch.from_config(CFG)
IDS = np.random.default_rng(3).integers(1, 256, (2, 56), dtype=np.int32)


def draw(name, shape, dtype):
    """A leaf by its name: N(0, 0.02) from numpy (jax's PRNG would
    compile a draw a leaf), norm weights 1 — the same values for the
    program and the reference."""
    if W.is_norm(name):
        return np.ones(shape, dtype)
    rs = np.random.default_rng(zlib.crc32(name.encode()))
    return (rs.standard_normal(shape) * 0.02).astype(dtype)


@pytest.fixture(scope="module")
def model():
    template = jax.eval_shape(lambda: LagunaForCausalLM(
        LagunaConfig(**ARGS), key=jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(draw(jax.tree_util.keystr(path),
                                            leaf.shape, leaf.dtype)),
        template)


def layer_params(l):
    """Layer ``l``'s leaves as the reference names them: its slice of
    the program's stacked leaf, drawn by the program's leaf name."""
    prefix, at = ARCH.leaf_at(l)
    stacked = prefix.startswith(".blocks")
    out = {}
    for n, (shape, _) in ARCH.layer_shapes(l).items():
        full = ((ARCH.layers - ARCH.dense_layers) // ARCH.period,) + shape
        v = draw(prefix + n, full if stacked else shape, np.float32)
        out[n] = v[at] if stacked else v
    return out


# one program a layer kind: dense, window, full
_layer = jax.jit(R.layer, static_argnums=(2, 3), static_argnames=("fault",))


def kind_of(l):
    return 0 if l < ARCH.dense_layers else (1 if ARCH.windowed[l] else 4)


@functools.lru_cache(maxsize=None)
def reference_logits(rows: bytes, shape: tuple, fault=None):
    """The reference's float32 logits [B, T, V] of whole rows."""
    ids = np.frombuffer(rows, np.int32).reshape(shape)
    x = draw(R.TOP["embed"], (256, 64), np.float32)[ids]
    for l in range(ARCH.layers):
        x = _layer(jnp.asarray(x), layer_params(l), ARCH, kind_of(l),
                   fault=fault)
    norm = draw(R.TOP["norm"], (64,), np.float32)
    head = draw(R.TOP["lm_head"], (64, 256), np.float32)
    return np.asarray(jax.vmap(lambda r: R.head_logits(r, norm, head,
                                                        ARCH))(x))


def ref(ids, fault=None):
    ids = np.ascontiguousarray(ids, np.int32)
    return reference_logits(ids.tobytes(), ids.shape, fault)


def test_full_forward_agrees_with_reference(model):
    """Per-kind head counts, the gate, both rotary kinds and the dense
    layer 0, against the reference's full forward — 7 windows deep,
    past YaRN's original 32 positions."""
    assert ARCH.heads == (4, 6, 6, 6, 4, 6, 6, 6, 4)
    assert ARCH.dense_layers == 1 and ARCH.period == 4
    assert model.cache_groups == ((3, None), (6, WINDOW))
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda m, ids: m(ids))(model, jnp.asarray(IDS))),
        ref(IDS), **TOL)


forward = jax.jit(lambda m, ids, cache, at: m.forward_with_cache(
    ids, cache, at))
scatter = jax.jit(lambda pool, row, rows, at: paged_scatter(
    pool, row, rows, at, P))
write = jax.jit(paged_write)


@pytest.mark.parametrize("chunk", [16, 8], ids=["window<chunk",
                                                "window=chunk"])
def test_prefill_and_paged_decode_agree_with_reference(model, chunk):
    """What the engine's paged prefill and step do, by hand, logits
    against the reference's full forward: prefill chunks through both
    groups' pools — the full group's row holds every page, the window
    group's only those a program still reads, its base moving with the
    position — then one token at a time."""
    row_pages = -(-(WINDOW - 1 + chunk) // P) + 1
    proto = model.init_cache(1, 64)
    pools = [init_paged_cache(g, 24, P) for g in proto]
    assert [p[0].shape for p in pools] == [(25, 3, 2, P, 16),
                                           (25, 6, 2, P, 16)]
    full_row = jnp.asarray(np.random.default_rng(1).permutation(
        np.arange(1, 25))[:8], jnp.int32)              # 64 positions
    ring = np.random.default_rng(2).permutation(np.arange(1, 25))
    seq = jnp.asarray(IDS[:1])
    got = []

    def caches(first, end):
        base = max(first - WINDOW + 1, 0) // P
        row = np.zeros(row_pages, np.int32)
        live = np.arange(base, (end - 1) // P + 1)
        assert live.size <= row_pages
        row[:live.size] = ring[live % ring.size]
        return base, jnp.asarray(row), (
            PagedCache(pools[0], full_row),
            PagedCache(pools[1], jnp.asarray(row), jnp.asarray(base)))

    for start in range(0, 48, chunk):
        base, row, cache = caches(start, start + chunk)
        lg, rows = forward(model, seq[:, start:start + chunk], cache,
                           jnp.asarray(start))
        pools[0] = scatter(pools[0], full_row, rows[0], start)
        pools[1] = scatter(pools[1], row, rows[1], start - base * P)
        got.append(lg)
    for t in range(48, 56):                            # decode
        base, row, cache = caches(t, t + 1)
        lg, new = forward(model, seq[:, t:t + 1], cache, jnp.asarray(t))
        at = jnp.asarray([t % P])
        pools[0] = write(pools[0], full_row[t // P][None], at,
                         tuple(n[None, :, 0, :, 0] for n in new[0]))
        pools[1] = write(pools[1], row[t // P - base][None], at,
                         tuple(n[None, :, 0, :, 0] for n in new[1]))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               ref(IDS)[:1], **TOL)


def drain(eng, gid):
    toks = []
    while True:
        r = eng.poll(gid, len(toks), wait_s=60.0)
        assert r["error"] is None, r["error"]
        toks += r["tokens"]
        if r["done"]:
            return toks


def test_engine_streams_and_a_prefix_hit_serve_what_generate_does(model):
    """The paged engine behind one template three windows long: a cold
    stream, then one that hits the cached prefix and prefills its tail
    alone, serve the tokens of solo ``generate()`` (the contiguous
    cache), which are the reference's choices; ``stats()["attention"]``
    names each group's head counts and arm."""
    rng = np.random.default_rng(11)
    template = rng.integers(1, 256, 32, dtype=np.int32)
    # prompts of three chunks, the hit's tail one: every prefill is a
    # chunk of 16
    a, b = (np.concatenate([template, rng.integers(1, 256, 16,
                                                   dtype=np.int32)])
            for _ in range(2))
    with GenerationEngine(model, slots=2, max_len=MAXLEN, paged=True,
                          page_tokens=P, pages=(24, 16), prefill_chunk=16,
                          prefix_cache=True) as eng:
        got_a = drain(eng, eng.start(a, 8))
        saved = monitor.get_stat("gen/prefix_tokens_saved") or 0
        got_b = drain(eng, eng.start(b, 8))
        hit = (monitor.get_stat("gen/prefix_tokens_saved") or 0) - saved
        st = eng.stats()
    assert hit == template.size
    # the reference's choices, its rows padded to the other tests' shape
    # (causal: the padding is never seen)
    rows = np.zeros(IDS.shape, np.int32)
    for r, (p, got) in enumerate(((a, got_a), (b, got_b))):
        rows[r, :p.size + 8] = np.concatenate([p, got])
    want = np.argmax(ref(rows)[:, a.size - 1:a.size + 7], -1)
    assert [got_a, got_b] == want.tolist()
    assert st["attention"] == [
        {"name": "full", "Hq": 4, "Hkv": 2, "arm": "gather", "kernel": None},
        {"name": "window", "Hq": 6, "Hkv": 2, "arm": "gather",
         "kernel": None}]


def test_the_held_shares_add_up_to_the_uncut_layer():
    """One chip's share of an expert-parallel layer: the parts that the
    two halves of the experts give, with the shared expert (which every
    chip computes alike) counted once, add up to what the reference
    gives for the whole layer."""
    E, I, X = 64, 32, 8
    names = {"moe.router": (E, X), "moe.w_gate": (X, E, I),
             "moe.w_up": (X, E, I), "moe.w_down": (X, I, E),
             "moe.shared_gate": (E, I), "moe.shared_up": (E, I),
             "moe.shared_down": (I, E)}
    p = {n: jnp.asarray(draw(n, s, np.float32)) * (
        1.0 if n == "moe.router" else 10.0) for n, s in names.items()}
    m = jnp.asarray(np.random.default_rng(4).standard_normal((20, E)),
                    jnp.float32)
    uncut = R.expert_mlp(m, p, ARCH)

    def share(first, count):
        layer = jax.eval_shape(lambda: MoEMLP(
            E, I, X, top_k=3, routed_scale=2.5, shared_size=I,
            held=(first, count), norm_topk=True, key=jax.random.PRNGKey(0)))
        cut = {n: v[first:first + count] if n.startswith("moe.w_") else v
               for n, v in p.items()}
        layer = jax.tree_util.tree_map_with_path(
            lambda path, leaf: cut["moe" + jax.tree_util.keystr(path)],
            layer)
        return layer(m[None])[0][0]

    shared = R.swiglu(m, p["moe.shared_gate"], p["moe.shared_up"],
                      p["moe.shared_down"])
    parts = share(0, 4) + share(4, 4) - shared
    np.testing.assert_allclose(np.asarray(parts), np.asarray(uncut), **TOL)
    # and each share is more than the shared expert: both halves count
    assert float(jnp.abs(share(0, 4) - shared).max()) > 1e-2
    assert float(jnp.abs(share(4, 4) - shared).max()) > 1e-2


def test_the_full_layers_yarn_rotation_by_hand():
    """A full layer of the tiny config (D 16, 8 rotated dims, base 5e5,
    factor 128, original 32, beta 32 / 1): the correction dims are 0 and
    1, so the first pair keeps its frequency (1) and the other three are
    interpolated (theta^(-2i/8) / 128); cos and sin carry the attention
    factor, dims 8..15 pass through; a window layer rotates all 16 dims
    at 1e4 with no factor."""
    cfg = LagunaConfig(**ARGS)
    from paddle_tpu.models.laguna import LagunaAttention
    full = jax.eval_shape(lambda: LagunaAttention(
        cfg, None, key=jax.random.PRNGKey(0)))
    inv = np.array([1.0] + [5e5 ** (-2 * i / 8) / 128 for i in (1, 2, 3)])
    af = 1.4852030263919618
    np.testing.assert_allclose(
        R.yarn_inv_freq(8, 5e5, 128, 32, 32, 1), inv, rtol=1e-12)
    np.testing.assert_allclose(
        np.asarray(full.rope_tables(jnp.asarray([1]))[0])[0],
        af * np.cos(inv), rtol=1e-6)
    x = np.random.default_rng(5).standard_normal((1, 2, 3, 16))
    cos, sin, dim = full.rope_tables(jnp.asarray([0, 5]))
    assert dim == 8
    got = np.asarray(F.apply_partial_rotary(jnp.asarray(x, jnp.float32),
                                            cos, sin, dim))
    want = x.copy()
    for t, pos in enumerate((0, 5)):
        c, s = af * np.cos(pos * inv), af * np.sin(pos * inv)
        x1, x2 = x[0, t, :, :4], x[0, t, :, 4:8]
        want[0, t, :, :4] = x1 * c - x2 * s
        want[0, t, :, 4:8] = x2 * c + x1 * s
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:].astype(np.float32))
    window = jax.eval_shape(lambda: LagunaAttention(
        cfg, 16, key=jax.random.PRNGKey(0)))
    cos, sin, dim = window.rope_tables(jnp.asarray([5]))
    assert dim == 16
    np.testing.assert_allclose(
        np.asarray(cos)[0], np.cos(5 / 1e4 ** (np.arange(0, 16, 2) / 16)),
        rtol=1e-5)


def _pallas_names(closed):
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(closed.jaxpr))


def test_the_window_kernel_name_leaves_other_programs_alone(monkeypatch):
    """SmallThinker's window layer traces to the program it had: its
    paged decode kernel keeps the default name, and naming the default
    explicitly changes nothing. Laguna's window layers take
    ``ptpu_paged_decode_attn_window``, its full layers the default."""
    from paddle_tpu.models.laguna import LagunaAttention
    from paddle_tpu.models.smallthinker import (SmallThinkerAttention,
                                                SmallThinkerConfig)
    Hkv, D = 2, 128
    st_cfg = SmallThinkerConfig.tiny(head_dim=D, sliding_window=32)
    pool = tuple(jnp.zeros((9, 2, Hkv, 8, D), jnp.float32)
                 for _ in range(2))
    cache = PagedCache(pool, jnp.arange(1, 5, dtype=jnp.int32),
                       jnp.asarray(0))
    x = jnp.ones((1, 1, st_cfg.hidden_size), jnp.float32)

    def traced(make):
        layer = jax.eval_shape(lambda: make(key=jax.random.PRNGKey(0)))
        return jax.make_jaxpr(
            lambda layer, x: layer(x, cache=cache, index=jnp.asarray(20),
                                   layer=1))(layer, x)

    st = functools.partial(SmallThinkerAttention, st_cfg, 32, True)
    with _support.force_dispatch():
        before = traced(st)
        real = _common.cached_attention
        monkeypatch.setattr(_common, "cached_attention", functools.partial(
            real, kernel_name="ptpu_paged_decode_attn"))
        explicit = traced(st)
        monkeypatch.setattr(_common, "cached_attention", real)
        lg_cfg = LagunaConfig(**dict(ARGS, head_dim=D))
        names = [_pallas_names(traced(functools.partial(
            LagunaAttention, lg_cfg, w))) for w in (None, 32)]
    assert _pallas_names(before) == ["ptpu_paged_decode_attn"]
    # the same equations, whatever addresses its closures print with
    assert re.sub("0x[0-9a-f]+", "", str(before)) == re.sub(
        "0x[0-9a-f]+", "", str(explicit))
    assert names == [["ptpu_paged_decode_attn"],
                     ["ptpu_paged_decode_attn_window"]]
