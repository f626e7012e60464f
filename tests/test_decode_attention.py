"""Pallas decode-attention kernel (ops/pallas/decode_attention.py).

OpTest discipline (reference
``python/paddle/fluid/tests/unittests/op_test.py:226``): the kernel must
reproduce the einsum fallback bit-for-bit in interpret mode (same dtype
path, same visibility set), select the right layer out of the stacked
buffers, bound its reads to the filled prefix, and fold the int8 scales
exactly.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import _common
from paddle_tpu.ops.pallas import _support, decode_attention as dk


def _mk(B=2, Hq=8, Hkv=4, S=256, D=64, L=2, dtype=jnp.float32, quant=False,
        seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, 1, Hq, D), dtype)
    k_new = jnp.asarray(rs.randn(B, Hkv, 1, D), dtype)
    v_new = jnp.asarray(rs.randn(B, Hkv, 1, D), dtype)
    if quant:
        kc = jnp.asarray(rs.randint(-127, 128, (L, B, Hkv, S, D)), jnp.int8)
        vc = jnp.asarray(rs.randint(-127, 128, (L, B, Hkv, S, D)), jnp.int8)
        ks = jnp.asarray(rs.rand(L, B, Hkv, S) * 0.05 + 0.001, jnp.float32)
        vs = jnp.asarray(rs.rand(L, B, Hkv, S) * 0.05 + 0.001, jnp.float32)
        cache = (kc, vc, ks, vs)
    else:
        cache = (jnp.asarray(rs.randn(L, B, Hkv, S, D), dtype),
                 jnp.asarray(rs.randn(L, B, Hkv, S, D), dtype))
    return q, k_new, v_new, cache


def _fallback(q, k_new, v_new, cache, layer, idx):
    """The einsum path of models._common.cached_attention, decode branch
    (q [B,1,Hq,D], chunk already in buffer layout)."""
    B, T, Hq, D = q.shape
    Hkv = k_new.shape[1]
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    sl = tuple(c[layer] for c in cache)
    if len(cache) == 4:
        k_c, v_c, k_s, v_s = sl
        kc = k_c.astype(q.dtype) * k_s.astype(q.dtype)[..., None]
        vc = v_c.astype(q.dtype) * v_s.astype(q.dtype)[..., None]
    else:
        kc, vc = sl
    S = kc.shape[2]
    qh = q.transpose(0, 2, 1, 3).reshape(B, Hkv, G, T, D)
    neg = jnp.finfo(jnp.float32).min
    s_c = jnp.einsum("bkgtd,bksd->bkgts", qh, kc) * scale
    s_c = jnp.where((jnp.arange(S) < idx)[None, None, None, None, :],
                    s_c.astype(jnp.float32), neg)
    s_n = (jnp.einsum("bkgtd,bkud->bkgtu", qh, k_new) * scale
           ).astype(jnp.float32)
    probs = jax.nn.softmax(jnp.concatenate([s_c, s_n], -1), axis=-1)
    p_c = probs[..., :S].astype(q.dtype)
    p_n = probs[..., S:].astype(q.dtype)
    out = (jnp.einsum("bkgts,bksd->bkgtd", p_c, vc)
           + jnp.einsum("bkgtu,bkud->bkgtd", p_n, v_new))
    return out.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("idx", [1, 37, 128, 255])
def test_kernel_matches_fallback(quant, idx):
    q, kn, vn, cache = _mk(quant=quant)
    with _support.force_dispatch():
        assert dk.supported(q, cache)
        got = dk.decode_attention(q, kn, vn, cache, jnp.int32(0),
                                  jnp.int32(idx), scale=1.0 / 8.0)
    want = _fallback(q, kn, vn, cache, 0, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True])
def test_kernel_selects_layer(quant):
    """The scalar-prefetched layer id must pick layer l's buffers out of
    the stack — each layer's output must match that layer's fallback."""
    q, kn, vn, cache = _mk(L=3, quant=quant, seed=7)
    for l in range(3):
        with _support.force_dispatch():
            got = dk.decode_attention(q, kn, vn, cache, jnp.int32(l),
                                      jnp.int32(90), scale=0.125)
        want = _fallback(q, kn, vn, cache, l, 90)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"l={l}")


def test_kernel_gqa_group_mapping():
    """Hq=8, Hkv=2 (G=4): each q head must read ITS kv head's cache."""
    q, kn, vn, cache = _mk(Hq=8, Hkv=2, seed=3)
    with _support.force_dispatch():
        got = dk.decode_attention(q, kn, vn, cache, jnp.int32(1),
                                  jnp.int32(100), scale=0.125)
    want = _fallback(q, kn, vn, cache, 1, 100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_ignores_stale_positions():
    """Positions >= index must not contribute: poisoning them with huge
    values changes nothing."""
    q, kn, vn, cache = _mk(seed=1)
    idx = 64
    k, v = np.asarray(cache[0]).copy(), np.asarray(cache[1]).copy()
    k[:, :, :, idx:] = 1e4
    v[:, :, :, idx:] = -1e4
    poisoned = (jnp.asarray(k), jnp.asarray(v))
    with _support.force_dispatch():
        a = dk.decode_attention(q, kn, vn, cache, jnp.int32(0),
                                jnp.int32(idx), scale=0.125)
        b = dk.decode_attention(q, kn, vn, poisoned, jnp.int32(0),
                                jnp.int32(idx), scale=0.125)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_supported_gates():
    q, _, _, cache = _mk()
    with _support.force_dispatch():
        assert dk.supported(q, cache)
        # prefill chunk (T > 1) is not the kernel's job
        assert not dk.supported(jnp.zeros((2, 4, 8, 64)), cache)
        # head_dim off the MXU grid
        assert not dk.supported(jnp.zeros((2, 1, 8, 32)), (
            jnp.zeros((2, 2, 4, 256, 32)),) * 2)
        # S not blockable
        assert not dk.supported(jnp.zeros((2, 1, 8, 64)), (
            jnp.zeros((2, 2, 4, 100, 64)),) * 2)
    # no dispatch context off-TPU → fallback (on a TPU host the bare
    # call legitimately dispatches)
    if not _support.on_tpu():
        assert not dk.supported(q, cache)


def test_cached_attention_dispatches_kernel(monkeypatch):
    """models._common.cached_attention must route supported decode
    shapes through the kernel (and produce the same payload/out as the
    fallback it replaces)."""
    rs = np.random.RandomState(5)
    B, Hq, Hkv, S, D, L = 2, 4, 4, 128, 64, 2
    q = jnp.asarray(rs.randn(B, 1, Hq, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, 1, Hkv, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, 1, Hkv, D), jnp.float32)
    cache = (jnp.asarray(rs.randn(L, B, Hkv, S, D), jnp.float32),
             jnp.asarray(rs.randn(L, B, Hkv, S, D), jnp.float32))
    calls = {}
    orig = dk.decode_attention

    def spy(*a, **kw):
        calls["hit"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(dk, "decode_attention", spy)
    with _support.force_dispatch():
        out_k, pay_k = _common.cached_attention(q, k, v, cache,
                                                jnp.int32(50), layer=1)
    assert calls.get("hit")
    out_f, pay_f = _common.cached_attention(q, k, v, cache, jnp.int32(50),
                                            layer=1)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_f),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(pay_k, pay_f):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "quant", [False, pytest.param(True, marks=pytest.mark.slow)])
def test_kernel_under_vmap_matches_per_slot(quant):
    """The GenerationEngine's fused decode vmaps
    ``forward_with_cache`` over the slot axis, so on TPU the kernel is
    invoked under ``jax.vmap`` with per-slot caches and fill positions.
    jax's pallas batching rule must reproduce the per-slot calls (and
    the einsum fallback) exactly — the gap CHANGES r5 flagged as
    untested."""
    SLOTS = 3
    qs, kns, vns, caches, idxs = [], [], [], [], [1, 100, 255]
    for s in range(SLOTS):
        q, kn, vn, cache = _mk(B=1, quant=quant, seed=10 + s)
        qs.append(q), kns.append(kn), vns.append(vn), caches.append(cache)
    q = jnp.stack(qs)
    kn, vn = jnp.stack(kns), jnp.stack(vns)
    cache = tuple(jnp.stack([c[i] for c in caches])
                  for i in range(len(caches[0])))
    idx = jnp.asarray(idxs, jnp.int32)

    def one(q, kn, vn, cache, i):
        assert dk.supported(q, cache)      # gate holds under the tracer
        return dk.decode_attention(q, kn, vn, cache, jnp.int32(1), i,
                                   scale=0.125)

    with _support.force_dispatch():
        got = jax.jit(jax.vmap(one))(q, kn, vn, cache, idx)
        want = jnp.stack([
            dk.decode_attention(qs[s], kns[s], vns[s], caches[s],
                                jnp.int32(1), jnp.int32(idxs[s]),
                                scale=0.125)
            for s in range(SLOTS)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for s in range(SLOTS):
        np.testing.assert_allclose(
            np.asarray(got[s]),
            np.asarray(_fallback(qs[s], kns[s], vns[s], caches[s], 1,
                                 idxs[s])),
            rtol=2e-5, atol=2e-5, err_msg=f"slot {s}")


def test_engine_fused_decode_dispatch_is_explicit(monkeypatch):
    """The engine's vmapped decode dispatches per backend and both arms
    are pinned: with the kernel set dispatching, the vmapped
    cached_attention routes through decode_attention; without it (plain
    CPU), supported() gates False under the same vmap and the einsum
    fallback produces matching numbers."""
    rs = np.random.RandomState(9)
    SLOTS, B, Hq, Hkv, S, D, L = 2, 1, 4, 4, 128, 64, 2
    q = jnp.asarray(rs.randn(SLOTS, B, 1, Hq, D), jnp.float32)
    k = jnp.asarray(rs.randn(SLOTS, B, 1, Hkv, D), jnp.float32)
    v = jnp.asarray(rs.randn(SLOTS, B, 1, Hkv, D), jnp.float32)
    cache = tuple(jnp.asarray(rs.randn(SLOTS, L, B, Hkv, S, D),
                              jnp.float32) for _ in range(2))
    idx = jnp.asarray([17, 90], jnp.int32)
    calls = {}
    orig = dk.decode_attention

    def spy(*a, **kw):
        calls["n"] = calls.get("n", 0) + 1
        return orig(*a, **kw)

    monkeypatch.setattr(dk, "decode_attention", spy)

    def one(q, k, v, cache, i):
        out, _ = _common.cached_attention(q, k, v, cache, i, layer=1)
        return out

    with _support.force_dispatch():
        kernel_out = jax.vmap(one)(q, k, v, cache, idx)
    assert calls.get("n", 0) >= 1          # kernel arm engaged
    calls.clear()
    fallback_out = jax.vmap(one)(q, k, v, cache, idx)   # plain CPU
    assert calls.get("n", 0) == 0          # fallback arm: gate said no
    np.testing.assert_allclose(np.asarray(kernel_out),
                               np.asarray(fallback_out),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cache_dtype", [None, jnp.int8])
def test_partitioned_kernel_under_tp_mesh(devices8, cache_dtype):
    """TP-sharded serving keeps the kernel: under a tp2 mesh with
    force_dispatch, generate() routes decode steps through the
    shard_map unit (per-shard kernels, stats prove it) and
    reproduces the single-device tokens exactly — bf16 and int8 cache
    layouts (scales shard with the heads). Shapes sized to the kernel
    gate (prompt 120 + 8 new = S 128, D=64)."""
    import paddle_tpu
    from jax.sharding import NamedSharding
    from paddle_tpu import partition_specs
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.parallel import mesh as M
    from paddle_tpu.ops.pallas import _partition

    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=256, num_layers=2,
                           num_heads=4, num_kv_heads=2, max_seq_len=128)
    m = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 96, (2, 120))
                      .astype(np.int32))
    ref = np.asarray(generate(m, ids, 8, cache_dtype=cache_dtype))

    mesh = M.create_mesh({"tp": 2, "dp": 1}, jax.devices()[:2])
    sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), partition_specs(m),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    m_sh = jax.device_put(m, sh)
    with M.MeshContext(mesh):
        with _support.force_dispatch():
            _partition.reset_stats()
            out = np.asarray(jax.jit(
                lambda mm, i: generate(mm, i, 8,
                                       cache_dtype=cache_dtype))(m_sh, ids))
        hits = dict(_partition.stats)
    assert hits.get("decode_attn:kernel", 0) > 0, hits
    np.testing.assert_array_equal(out, ref)
