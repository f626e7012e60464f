"""SDAR decoder (JetLM SDAR-30B-A3B-Chat, ``model_type: sdar_moe``): a
Qwen3-MoE stack that generates by **diffusion over blocks** — a block of
B positions is filled by a few denoising steps that each fix the masked
positions the model is most confident of, then committed to the cache,
and the next block starts B on.

What "supported" covers: the **serving path** — ``init_cache`` /
``forward_with_cache`` under ``generation.block_diffusion_generate`` and
the paged ``GenerationEngine``'s block step — and the full forward
``__call__``. Not built: training by the masked-diffusion loss.

One layer, for its input ``x`` [T, E] (E 2048):

    q_h = RMSNorm_D(W_q x)_h ; k_g = RMSNorm_D(W_k x)_g ; v_g = (W_v x)_g
    q_h, k_g = RoPE(q_h, pos), RoPE(k_g, pos)          (theta 1e6, halves)
    a_h = softmax(q_h . k_{h//G}^T / sqrt(D) + M) v_{h//G}
    M[t, j] = 0 if floor(pos_j / B) <= floor(pos_t / B) else -inf
    h = x + W_o concat_h(a_h)
    p = softmax(W_r RMSNorm(h)) ; S = top-k(p) ; g_e = p_e / sum_S p
    y = h + sum_{e in S} g_e W2_e(SiLU(W1_e n) * W3_e n),  n = RMSNorm(h)

— 32 query heads and 4 KV heads of D 128 (G = 8 a KV head), one q-norm
and one k-norm weight of D shared by all heads (eps 1e-6), 128 routed
experts of width 768, 8 a token, no shared expert, no dense layer. The
attention is ``llama.LlamaAttention`` with ``qk_norm`` and ``attn_block``
= B (block-causal: a position sees every earlier block and its whole own
block, both ways); the expert layer ``nn.moe.MoEMLP``'s held dropless
form over all 128 experts, softmax gates renormalised over the picks.
The head ``W_head RMSNorm(x_L)`` is untied; the logits at a position give
the distribution of that position's OWN token (no shift).

Block diffusion, greedy (``generation.block_diffusion_generate``; the
engine's block step): the prompt's first ``len // B`` blocks are
prefilled; the first generated block holds the prompt's remainder and
``[MASK]`` (``mask_token_id``) elsewhere; each denoising step fixes, at
the masked positions of highest confidence, as many tokens as the linear
transfer schedule gives (``generation.transfer_schedule``: B/steps a
step, the remainder to the first steps); a block with nothing masked is
committed (its final tokens' K/V written) and the next one begins.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.models.llama import LlamaAttention
from paddle_tpu.nn.common import Embedding, Linear
from paddle_tpu.nn.initializer import Normal
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.nn.norm import RMSNorm
from paddle_tpu.nn.scan import ScannedBlocks

__all__ = ["SDARConfig", "SDARForCausalLM"]


@dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    max_seq_len: int = 32768
    rope_base: float = 1e6
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    init_std: float = 0.02
    qk_norm: bool = True
    # block diffusion: positions a block, denoising steps a block, and the
    # id a masked position holds (the release tokenizer's [MASK])
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669

    def __post_init__(self):
        if (self.num_heads % self.num_kv_heads or self.block_length < 1
                or self.denoising_steps < 1
                or not 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                f"{self.num_kv_heads} KV heads must divide {self.num_heads}, "
                f"block_length {self.block_length} and denoising_steps "
                f"{self.denoising_steps} be >= 1, and mask_token_id "
                f"{self.mask_token_id} lie in the vocabulary")

    @property
    def attn_block(self) -> int:
        """What ``LlamaAttention`` reads: block-causal over blocks."""
        return self.block_length

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, head_dim=16,
                    moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, max_seq_len=256,
                    dtype="float32", mask_token_id=255)
        base.update(kw)
        return cls(**base)


class SDARBlock(Module):
    """One decoder layer: block-causal GQA with head-wise q/k norm, then
    the held dropless expert layer over the normed stream."""

    def __init__(self, cfg: SDARConfig, key=None):
        k1, k2 = rng.split_key(key)
        dtype = jnp.dtype(cfg.dtype)
        self.attn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                 dtype=dtype)
        self.attn = LlamaAttention(cfg, key=k1)
        self.mlp_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                dtype=dtype)
        self.moe = MoEMLP(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            top_k=cfg.num_experts_per_tok, init_std=cfg.init_std,
            num_layers=cfg.num_layers, dtype=dtype,
            held=(0, cfg.num_experts), norm_topk=True, act="silu", key=k2)

    def __call__(self, x, layer=0, *, cache=None, index=None,
                 training: bool = False):
        payload = None
        attn_out = self.attn(self.attn_norm(x), cache=cache, index=index,
                             layer=layer)
        if cache is not None:
            attn_out, payload = attn_out
        h = x + attn_out
        out = h + self.moe(self.mlp_norm(h))[0]
        return out if payload is None else (out, payload)


class SDARForCausalLM(Module):
    """Decoder of the SDAR family served by block diffusion (module
    docstring says what is supported)."""

    # names the expert layers record on a state tape, one value a
    # position: the serving engine sums them over live positions
    live_counts = ("moe_picks", "moe_picks_held")
    # how the serving engine steps it: a block of ``block_length``
    # positions a slot, not one token (``serving/engine.py``)
    generates_by = "block_diffusion"

    def __init__(self, cfg: SDARConfig, key=None):
        keys = rng.split_key(key, 2 + cfg.num_layers)
        dtype = jnp.dtype(cfg.dtype)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_init=Normal(0.0, cfg.init_std),
                               dtype=dtype, key=keys[0],
                               pspec=P("tp", "fsdp"))
        self.blocks = ScannedBlocks(
            lambda i: SDARBlock(cfg, key=keys[2 + i]), cfg.num_layers)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                            dtype=dtype)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias=False,
                              weight_init=Normal(0.0, cfg.init_std),
                              dtype=dtype, key=keys[1],
                              pspec=P("fsdp", "tp"))
        self.block_length = cfg.block_length
        self.denoising_steps = cfg.denoising_steps
        self.mask_token_id = cfg.mask_token_id
        self.config = cfg

    def __call__(self, input_ids, training: bool = False):
        """Logits [B, T, V] of whole rows under the block-causal mask
        (rows start on a block boundary)."""
        x = self.blocks(self.embed(input_ids), training=training)
        return self.lm_head(self.norm(x))

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        from paddle_tpu.models._common import init_kv_cache
        cfg = self.config
        return init_kv_cache(cfg.num_layers, batch_size, max_len,
                             cfg.num_kv_heads, cfg.head_dim,
                             jnp.dtype(dtype or cfg.dtype))

    def forward_with_cache(self, input_ids, cache, index):
        """A chunk that starts on a block boundary (a prefill of whole
        blocks, or one block of a denoising or commit step) through the
        shared cache contract; the layers' picks ride a state tape out
        of the scan (``live_counts``)."""
        from paddle_tpu.models._common import apply_cache_writes
        from paddle_tpu.nn.scan import _reemit_tape
        from paddle_tpu.nn.stateful import tape_call

        def layer(block, carry, l):
            (y, pay), tape = tape_call(block, carry, l, cache=cache,
                                       index=index)
            return y, (pay, tape)

        x, (pay, tape) = self.blocks.scan_with(
            self.embed(input_ids), jnp.arange(self.config.num_layers),
            fn=layer)
        _reemit_tape(tape)
        cache = apply_cache_writes(cache, pay, index)
        return self.lm_head(self.norm(x)), cache

    def generate(self, input_ids, max_new_tokens: int, **kwargs):
        """Greedy block diffusion (``generation.block_diffusion_generate``)
        with this model's block, steps and ``[MASK]``."""
        from paddle_tpu.models.generation import block_diffusion_generate
        return block_diffusion_generate(
            self, input_ids, max_new_tokens, block_length=self.block_length,
            denoising_steps=self.denoising_steps,
            mask_token_id=self.mask_token_id, **kwargs)

    def shard_for_inference(self, mesh):
        raise ValueError(
            "gen_mesh_tp with block diffusion is not implemented: the block "
            "step and its page writes have no sharded form; serve this "
            "model unsharded")
