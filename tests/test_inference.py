"""Inference layer (L8): export/Predictor + the generate decode loop.

Reference coverage model: C++ predictor tests per model
(``paddle/fluid/inference/tests/api/``) assert save→load→run parity;
here export→reload must be bit-identical on CPU, and the static-KV-cache
decode loop must reproduce full-recompute forward logits exactly.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu
from paddle_tpu import io
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate, sample_logits


@pytest.fixture
def tiny_llama():
    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=128, hidden_size=64, num_layers=2,
                           num_heads=4, num_kv_heads=2, max_seq_len=64)
    return LlamaForCausalLM(cfg)


def test_export_reload_bit_identical(tiny_llama, tmp_path):
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (2, 16)).astype(np.int32))
    path = str(tmp_path / "exported")
    io.save_inference_model(path, tiny_llama, [ids])

    pred = io.load_inference_model(path)
    got = pred.run(ids)
    want = jax.jit(lambda m, x: m(x))(tiny_llama, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert pred.input_specs[0]["shape"] == [2, 16]
    assert pred.output_specs[0]["shape"] == [2, 16, 128]


def test_predictor_validates_inputs(tiny_llama, tmp_path):
    ids = jnp.zeros((2, 16), jnp.int32)
    path = str(tmp_path / "exported")
    io.save_inference_model(path, tiny_llama, [ids])
    pred = io.Predictor(path)
    with pytest.raises(ValueError, match="shape"):
        pred.run(jnp.zeros((2, 8), jnp.int32))
    with pytest.raises(ValueError, match="expected 1 inputs"):
        pred.run(ids, ids)
    with pytest.raises(ValueError, match="dtype"):
        pred.run(jnp.zeros((2, 16), jnp.float32))


def test_export_function_roundtrip(tmp_path):
    def fn(x, y):
        return jnp.sin(x) @ y

    x = jnp.asarray(np.random.RandomState(1).randn(4, 8).astype(np.float32))
    y = jnp.asarray(np.random.RandomState(2).randn(8, 2).astype(np.float32))
    p = str(tmp_path / "fn.stablehlo")
    io.export_function(fn, (x, y), p)
    from jax import export as jax_export
    with open(p, "rb") as f:
        rt = jax_export.deserialize(f.read())
    np.testing.assert_array_equal(np.asarray(rt.call(x, y)),
                                  np.asarray(fn(x, y)))


def test_cache_forward_matches_full_forward(tiny_llama):
    """Prefill + per-token decode through the static KV cache must equal
    the full recompute forward at every position."""
    model = tiny_llama
    rs = np.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, 128, (2, 12)).astype(np.int32))
    T = ids.shape[1]

    full_logits = model(ids)                       # [B, T, V]

    cache = model.init_cache(2, T)
    pre = 5
    logits_pre, cache = model.forward_with_cache(ids[:, :pre], cache, index=0)
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(full_logits[:, :pre]),
                               rtol=2e-5, atol=2e-5)
    for t in range(pre, T):
        logits_t, cache = model.forward_with_cache(
            ids[:, t:t + 1], cache, index=t)
        np.testing.assert_allclose(
            np.asarray(logits_t[:, 0]), np.asarray(full_logits[:, t]),
            rtol=2e-5, atol=2e-5,
            err_msg=f"decode step {t} diverged from full forward")


def test_generate_greedy_matches_naive_loop(tiny_llama):
    """generate() (fori_loop + static cache) vs the obvious slow loop that
    recomputes the full forward every step."""
    model = tiny_llama
    ids = jnp.asarray(
        np.random.RandomState(4).randint(0, 128, (2, 6)).astype(np.int32))
    n_new = 8

    out = generate(model, ids, n_new, temperature=0.0)

    naive = ids
    for _ in range(n_new):
        logits = model(naive)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        naive = jnp.concatenate([naive, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))


def test_generate_zero_tokens_returns_prompt(tiny_llama):
    ids = jnp.asarray([[5, 67, 123]], jnp.int32)
    out = generate(tiny_llama, ids, 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ids))


def test_generate_eos_padding(tiny_llama):
    model = tiny_llama
    ids = jnp.asarray(
        np.random.RandomState(5).randint(0, 128, (1, 4)).astype(np.int32))
    # force every token to be "eos" by picking the greedy first token as eos
    first = int(jnp.argmax(model(ids)[:, -1], axis=-1)[0])
    out = generate(model, ids, 5, temperature=0.0, eos_token_id=first,
                   pad_token_id=99)
    out = np.asarray(out)
    assert out[0, 4] == first                  # eos emitted
    assert (out[0, 5:] == 99).all()            # then padding


def test_generate_jits(tiny_llama):
    model = tiny_llama
    ids = jnp.asarray(
        np.random.RandomState(6).randint(0, 128, (2, 6)).astype(np.int32))
    jitted = jax.jit(lambda m, x: generate(m, x, 4, temperature=0.0))
    out1 = jitted(model, ids)
    out2 = generate(model, ids, 4, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_sample_logits_top_k_top_p():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 10.0]])
    key = jax.random.PRNGKey(0)
    # top_k=1 → always argmax regardless of key
    for i in range(5):
        tok = sample_logits(logits, jax.random.PRNGKey(i), temperature=1.0,
                            top_k=1)
        assert int(tok[0]) == 4
    # top_p tiny → nucleus collapses to argmax
    for i in range(5):
        tok = sample_logits(logits, jax.random.PRNGKey(i), temperature=1.0,
                            top_p=0.1)
        assert int(tok[0]) == 4
    # greedy
    assert int(sample_logits(logits, None)[0]) == 4
    # plain sampling covers more than one token eventually
    seen = {int(sample_logits(logits * 0.0, jax.random.PRNGKey(i),
                              temperature=1.0)[0]) for i in range(32)}
    assert len(seen) > 1


def test_generate_sampling_reproducible(tiny_llama):
    model = tiny_llama
    ids = jnp.asarray(
        np.random.RandomState(8).randint(0, 128, (2, 5)).astype(np.int32))
    k = jax.random.PRNGKey(42)
    a = generate(model, ids, 6, temperature=0.8, top_k=10, key=k)
    b = generate(model, ids, 6, temperature=0.8, top_k=10, key=k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (2, 11)


def test_beam_search_beats_greedy_logprob():
    """Beam search must find sequences with total log-prob >= greedy's
    (the defining property), on a tiny trained-ish Llama."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import beam_search, generate

    paddle_tpu.seed(3)
    cfg = LlamaConfig.tiny(num_layers=2, vocab_size=64, max_seq_len=48)
    model = LlamaForCausalLM(cfg)
    prompt = jnp.asarray(np.random.RandomState(0).randint(
        0, 64, (2, 4)).astype(np.int32))

    greedy = generate(model, prompt, 8)
    beam = beam_search(model, prompt, 8, num_beams=4)
    assert beam.shape == greedy.shape == (2, 12)

    def seq_logprob(seq):
        logits = model(seq)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        tok_lp = jnp.take_along_axis(
            logp[:, :-1], seq[:, 1:, None], axis=-1)[..., 0]
        return jnp.sum(tok_lp[:, 3:], axis=1)  # generated part only

    g_lp = np.asarray(seq_logprob(greedy))
    b_lp = np.asarray(seq_logprob(beam))
    assert (b_lp >= g_lp - 1e-3).all(), (b_lp, g_lp)


def test_beam_search_eos_and_pad():
    """Beams that emit EOS stop scoring and pad; output stays rectangular."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import beam_search

    paddle_tpu.seed(4)
    cfg = LlamaConfig.tiny(num_layers=1, vocab_size=32, max_seq_len=32)
    model = LlamaForCausalLM(cfg)
    prompt = jnp.zeros((1, 2), jnp.int32)
    out = beam_search(model, prompt, 10, num_beams=3, eos_token_id=5,
                      pad_token_id=0)
    assert out.shape == (1, 12)
    row = np.asarray(out[0, 2:])
    if 5 in row:
        after = row[list(row).index(5) + 1:]
        assert (after == 0).all(), row


@pytest.mark.parametrize("family", ["gpt", "moe"])
def test_gpt_moe_cache_decode_matches_full_forward(family):
    """GPT and MoE decode through the shared static-KV-cache contract
    (r4): prefill logits and teacher-forced decode steps must match the
    full parallel forward, and generate() runs jitted."""
    import paddle_tpu
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM, MoEConfig,
                                   MoEForCausalLM)
    from paddle_tpu.models.generation import generate

    paddle_tpu.seed(0)
    if family == "gpt":
        m = GPTForCausalLM(GPTConfig.tiny(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=64, dropout=0.0))
    else:
        m = MoEForCausalLM(MoEConfig.tiny(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_layers=2, num_experts=4, max_seq_len=64))
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 96, (2, 10)).astype(np.int32))
    ext = jnp.asarray(np.random.RandomState(1).randint(0, 96, (2, 3))
                      .astype(np.int32))
    allids = jnp.concatenate([ids, ext], axis=1)

    cache = m.init_cache(2, 20)
    pre, cache = m.forward_with_cache(ids, cache, 0)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(m(ids)),
                               rtol=2e-4, atol=2e-5)
    full2 = np.asarray(m(allids))
    logits = []
    for t in range(3):
        lg, cache = m.forward_with_cache(allids[:, 10 + t:11 + t], cache,
                                         10 + t)
        logits.append(np.asarray(lg[:, 0]))
    np.testing.assert_allclose(np.stack(logits, 1), full2[:, 10:],
                               rtol=2e-3, atol=1e-4)
    out = np.asarray(jax.jit(lambda mm, i: generate(mm, i, 6))(m, ids))
    assert out.shape == (2, 16)
    assert (out[:, :10] == np.asarray(ids)).all()
    # beam search reorders cache leaves on axis 1 — the layout contract
    # every family's init_cache must satisfy
    from paddle_tpu.models.generation import beam_search
    bs_out = np.asarray(beam_search(m, ids, 4, num_beams=3))
    assert bs_out.shape == (2, 14)
    assert (bs_out[:, :10] == np.asarray(ids)).all()


def test_gpt_decode_beyond_max_seq_len_raises():
    """Learned positions cannot extrapolate: a decode length past
    max_seq_len must fail loudly, not silently clamp the pos gather."""
    import paddle_tpu
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle_tpu.seed(0)
    m = GPTForCausalLM(GPTConfig.tiny(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=16, dropout=0.0))
    with pytest.raises(ValueError, match="max_seq_len"):
        m.init_cache(2, 32)


def test_gpt_num_params_exact():
    """GPTConfig.num_params must equal the actual leaf count (the bench
    decode leg reports it)."""
    import paddle_tpu
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig.tiny(vocab_size=128, hidden_size=64, num_layers=3,
                         num_heads=4, max_seq_len=32)
    paddle_tpu.seed(0)
    m = GPTForCausalLM(cfg)
    actual = sum(int(np.prod(l.shape))
                 for l in jax.tree_util.tree_leaves(m)
                 if hasattr(l, "shape"))
    assert cfg.num_params() == actual, (cfg.num_params(), actual)


@pytest.mark.parametrize("family", ["llama", "gpt", "moe"])
def test_int8_kv_cache_decode_close_to_full(family):
    """Quantized KV cache (init_kv_cache(dtype=int8) via
    generate(cache_dtype=jnp.int8)): per-(position, head) absmax scales
    keep teacher-forced decode logits within a fraction of a percent of
    the full forward, and greedy generation matches the bf16-cache run
    on these shapes."""
    import paddle_tpu
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                                   LlamaForCausalLM, MoEConfig,
                                   MoEForCausalLM)
    from paddle_tpu.models.generation import generate

    paddle_tpu.seed(0)
    if family == "llama":
        m = LlamaForCausalLM(LlamaConfig.tiny(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=64))
    elif family == "gpt":
        m = GPTForCausalLM(GPTConfig.tiny(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=64, dropout=0.0))
    else:
        m = MoEForCausalLM(MoEConfig.tiny(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_layers=2, num_experts=4, max_seq_len=64))
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 96, (2, 10)).astype(np.int32))
    ext = jnp.asarray(np.random.RandomState(1).randint(0, 96, (2, 3))
                      .astype(np.int32))
    allids = jnp.concatenate([ids, ext], axis=1)
    full = np.asarray(m(allids))

    cache = m.init_cache(2, 16, dtype=jnp.int8)
    assert len(cache) == 4 and cache[0].dtype == jnp.int8
    pre, cache = m.forward_with_cache(ids, cache, 0)
    # prefill attends on the raw chunk — exact
    np.testing.assert_allclose(np.asarray(pre), full[:, :10], rtol=2e-4,
                               atol=2e-5)
    for t in range(3):
        lg, cache = m.forward_with_cache(allids[:, 10 + t:11 + t], cache,
                                         10 + t)
        rel = (np.linalg.norm(np.asarray(lg[:, 0]) - full[:, 10 + t])
               / np.linalg.norm(full[:, 10 + t]))
        assert rel < 0.02, (t, rel)

    g8 = np.asarray(generate(m, ids, 6, cache_dtype=jnp.int8))
    gf = np.asarray(generate(m, ids, 6))
    assert g8.shape == gf.shape == (2, 16)
    np.testing.assert_array_equal(g8, gf)


def test_mamba_ignores_int8_cache_dtype():
    """Mamba's recurrent state accumulates — cache_dtype=int8 falls back
    to the model float dtype instead of corrupting the state."""
    import paddle_tpu
    from paddle_tpu.models import MambaConfig, MambaForCausalLM

    paddle_tpu.seed(0)
    m = MambaForCausalLM(MambaConfig.tiny(vocab_size=64, hidden_size=32,
                                          num_layers=2, state_size=8))
    cache = m.init_cache(2, dtype=jnp.int8)
    assert jnp.issubdtype(jax.tree_util.tree_leaves(cache)[0].dtype,
                          jnp.floating)


def test_kv_cache_rejects_other_int_dtypes():
    import paddle_tpu
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle_tpu.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(vocab_size=64, hidden_size=32,
                                          num_layers=2, num_heads=4,
                                          num_kv_heads=2, max_seq_len=32))
    with pytest.raises(ValueError, match="int8"):
        m.init_cache(2, 16, dtype=jnp.int32)


def test_generate_under_tensor_parallel_sharding(devices8):
    """Serving runs TP-sharded: generate() on a Megatron-sharded model
    (weights placed by partition_specs over a tp2 mesh) must reproduce
    the single-device tokens exactly — with the bf16 AND the int8 KV
    cache. The partitioner derives the decode collectives from the
    weight shardings; no serving-specific code path exists."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import partition_specs
    from paddle_tpu.parallel import mesh as M

    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=4, num_kv_heads=2, max_seq_len=64)
    m = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 96, (2, 8))
                      .astype(np.int32))
    ref = np.asarray(generate(m, ids, 8))

    mesh = M.create_mesh({"tp": 2, "dp": 1}, jax.devices()[:2])
    sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), partition_specs(m),
        is_leaf=lambda x: isinstance(x, P))
    m_sh = jax.device_put(m, sh)
    with M.MeshContext(mesh):
        out = np.asarray(jax.jit(
            lambda mm, i: generate(mm, i, 8))(m_sh, ids))
        out8 = np.asarray(jax.jit(
            lambda mm, i: generate(mm, i, 8,
                                   cache_dtype=jnp.int8))(m_sh, ids))
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(out8, ref)


def test_attention_block_is_a_named_scope(tiny_llama):
    """``LlamaAttention`` names its operations ``attn`` (metadata only),
    in the training forward as in the cached one."""
    ids = jnp.ones((1, 8), jnp.int32)
    text = jax.jit(lambda m, x: m(x)).lower(tiny_llama, ids).as_text(
        debug_info=True)
    assert "/attn/" in text or "attn/" in text, "no attn scope"
    plain = jax.jit(lambda m, x: m(x)).lower(tiny_llama, ids).as_text()
    assert "attn" not in plain, "the scope must be metadata only"
