"""SDAR (block diffusion over blocks of B positions, head-wise q/k norm,
held dropless experts) against the plain reference
(``benchmarks/lib/reference_sdar.py``, which imports nothing of the
program) on seeded weights, tiny widths, float32: the block-causal mask,
whole or chunked; the head-wise norm; a block step through the page pool
against the reference's full forward at the masked positions; a commit's
K/V against a fresh prefill of the same tokens; and the Llama attention
of the other families unchanged with the new fields at their
defaults."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.builders import common
from benchmarks.builders.serve_blockdiff import with_qk_norm
from benchmarks.lib import reference_sdar as R
from benchmarks.lib import weights as W
from paddle_tpu.models import LlamaConfig, MoEConfig, SDARConfig
from paddle_tpu.models._common import block_mask
from paddle_tpu.models.generation import (
    PagedCache, init_paged_cache, paged_scatter, paged_write_block,
)
from paddle_tpu.models.llama import LlamaAttention

SEED = 2 ** 31 + 17
B, P, MASK = 4, 8, 255
ARGS = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=3, max_seq_len=128,
            rope_base=1e6, rms_eps=1e-6, dtype="float32", block_length=B,
            denoising_steps=4, mask_token_id=MASK)
CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "vocab_size": 256,
    "num_hidden_layers": 2, "num_experts": 8, "num_experts_per_tok": 3,
    "rope_theta": 1e6, "rms_norm_eps": 1e-6, "block_length": B,
    "denoising_steps": 4, "mask_token_id": MASK, "torch_dtype": "float32",
    "program": {"model": "paddle_tpu.models.sdar:SDARForCausalLM",
                "config": "paddle_tpu.models.sdar:SDARConfig",
                "config_args": ARGS}}
ARCH = R.Arch.from_config(CFG)
# float32 sums in another order (a cache read in two pieces, all experts
# on every token against the picked ones): the other families' room
TOL = dict(atol=2e-5, rtol=1e-4)


def seeded(cfg=CFG):
    """The model as the cell's builder seeds it: the q/k norms' weights
    drawn as the reference draws them."""
    template = common.model_template(cfg)
    return jax.jit(lambda k: with_qk_norm(common.seeded_model(template, k),
                                          k))(W.root_key(SEED))


@pytest.fixture(scope="module")
def model():
    return seeded()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(1, 255, (2, 40), dtype=np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    return np.asarray(R.forward_logits(ARCH, SEED, ids))


def test_the_block_mask_is_the_references():
    pos = np.arange(12)
    np.testing.assert_array_equal(np.asarray(block_mask(12, B)),
                                  np.asarray(R.visible(pos, pos, B)))
    assert np.asarray(block_mask(8, 4))[1].tolist() == [1] * 4 + [0] * 4


def test_full_forward_agrees_with_reference(model, ids, ref_logits):
    np.testing.assert_allclose(np.asarray(model(jnp.asarray(ids))),
                               ref_logits, **TOL)


def test_chunked_prefill_and_blocks_agree_with_reference(model, ids,
                                                         ref_logits):
    """The cold first chunk (index 0: the mask through the einsum lines),
    a chunk of three blocks behind it, then a block at a time."""
    cache = model.init_cache(2, 48)
    got = []
    lg, cache = model.forward_with_cache(jnp.asarray(ids[:, :16]), cache, 0)
    got.append(lg)
    lg, cache = model.forward_with_cache(jnp.asarray(ids[:, 16:28]), cache,
                                         jnp.asarray(16))
    got.append(lg)
    for t in range(28, 40, B):
        lg, cache = model.forward_with_cache(jnp.asarray(ids[:, t:t + B]),
                                             cache, jnp.asarray(t))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               ref_logits, **TOL)


def test_a_causal_mask_is_another_model(ids, ref_logits):
    causal = np.asarray(R.forward_logits(ARCH, SEED, ids,
                                         kind="causal_in_block"))
    assert not np.allclose(causal, ref_logits, **TOL)


def test_head_wise_qk_norm_agrees_with_reference(ids, ref_logits):
    """A model without the norms is the reference's ``no_qk_norm``, and
    another model than the one with them."""
    plain = seeded(dict(CFG, program=dict(
        CFG["program"], config_args=dict(ARGS, qk_norm=False))))
    assert not hasattr(plain.blocks.block.attn, "q_norm")
    got = np.asarray(plain(jnp.asarray(ids)))
    np.testing.assert_allclose(
        got, np.asarray(R.forward_logits(ARCH, SEED, ids, kind="no_qk_norm")),
        **TOL)
    assert not np.allclose(got, ref_logits, **TOL)


def test_a_block_step_through_the_pool_equals_the_full_forward(model, ids):
    """The engine's step by hand: a prompt's whole blocks prefilled into
    scattered pages, then the first generated block as a denoising step
    stands — two positions fixed, two masked — forwarded at its first
    position on the slot's ``PagedCache``: its logits equal the
    reference's full forward over the prompt and the block as it
    stands, at every position of the block."""
    p0 = 20
    proto = model.init_cache(1, 48)
    pool = init_paged_cache(proto, 12, P)
    row = jnp.asarray(np.random.default_rng(1).permutation(
        np.arange(1, 13))[:6], jnp.int32)
    seq = jnp.asarray(ids[:1])
    for a in (0, 16):
        n = min(16, p0 - a)
        chunk = jnp.zeros((1, 16), jnp.int32).at[:, :n].set(seq[:, a:a + n])
        _, new = model.forward_with_cache(chunk, PagedCache(pool, row),
                                          jnp.asarray(a))
        pool = paged_scatter(pool, row, new, a, P, length=n)
    state = np.asarray([ids[0, p0], MASK, ids[0, p0 + 2], MASK], np.int32)
    lg, new = model.forward_with_cache(jnp.asarray(state[None]),
                                       PagedCache(pool, row),
                                       jnp.asarray(p0))
    full = np.concatenate([ids[0, :p0], state])[None]
    want = np.asarray(R.forward_logits(ARCH, SEED, full))[0, p0:]
    np.testing.assert_allclose(np.asarray(lg)[0], want, **TOL)
    assert (state == MASK).sum() == 2


def test_a_commit_writes_what_a_fresh_prefill_writes(model, ids):
    """A block's commit (its final ids at its first position, the rows
    written whole into one page) leaves the pool as a prefill of the
    same tokens does, at every position of the block; the denoising
    step before it wrote other rows there, which the commit replaced."""
    p0 = 16
    proto = model.init_cache(1, 48)
    row = jnp.asarray([3, 5, 1, 2, 4, 6], jnp.int32)
    fresh = init_paged_cache(proto, 8, P)
    _, new = model.forward_with_cache(jnp.asarray(ids[:1, :p0 + B]),
                                      PagedCache(fresh, row), jnp.asarray(0))
    fresh = paged_scatter(fresh, row, new, 0, P, length=p0 + B)

    pool = init_paged_cache(proto, 8, P)
    _, new = model.forward_with_cache(jnp.asarray(ids[:1, :p0]),
                                      PagedCache(pool, row), jnp.asarray(0))
    pool = paged_scatter(pool, row, new, 0, P, length=p0)
    page = row[p0 // P][None]
    for blk in (np.full((B,), MASK, np.int32), ids[0, p0:p0 + B]):
        _, new = model.forward_with_cache(jnp.asarray(blk[None]),
                                          PagedCache(pool, row),
                                          jnp.asarray(p0))
        pool = paged_write_block(pool, page, jnp.asarray([p0 % P]),
                                 tuple(n[:, 0][None] for n in new))
    for f, p in zip(fresh, pool):
        np.testing.assert_allclose(np.asarray(p[row[2]]),
                                   np.asarray(f[row[2]]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(p[row[:2]]),
                                   np.asarray(f[row[:2]]), atol=1e-5)


def test_llama_attention_is_unchanged_with_the_fields_at_defaults():
    """OLMoE's and the Llama family's attention: a config that names
    none of ``head_dim``, ``qk_norm``, ``attn_block`` and one that gives
    each its default build the same module and trace the same
    program."""
    for cfg in (MoEConfig.tiny(), LlamaConfig.tiny()):
        named = types.SimpleNamespace(**vars(cfg), head_dim=None,
                                      qk_norm=False, attn_block=1)
        a, b = (LlamaAttention(c, key=jax.random.PRNGKey(0))
                for c in (cfg, named))
        assert (jax.tree_util.tree_structure(a)
                == jax.tree_util.tree_structure(b))
        assert not hasattr(a, "q_norm") and not hasattr(a, "attn_block")
        x = jnp.ones((1, 8, cfg.hidden_size), jnp.float32)
        cache = (jnp.zeros((1, 1, cfg.num_kv_heads, 16,
                            cfg.hidden_size // cfg.num_heads)),) * 2

        def text(m):
            return str(jax.make_jaxpr(
                lambda m, x: m(x, cache=cache, index=jnp.int32(4)))(m, x))

        assert text(a) == text(b)
        assert str(jax.make_jaxpr(lambda m, x: m(x))(a, x)) == str(
            jax.make_jaxpr(lambda m, x: m(x))(b, x))


def test_the_config_refuses_what_it_cannot_serve():
    with pytest.raises(ValueError, match="mask_token_id"):
        SDARConfig.tiny(mask_token_id=256)
    with pytest.raises(ValueError, match="block_length"):
        SDARConfig.tiny(block_length=0)
    assert SDARConfig().attn_block == 4
    assert (SDARConfig().num_heads, SDARConfig().head_dim,
            SDARConfig().num_experts, SDARConfig().vocab_size) == (
                32, 128, 128, 151936)
