"""Llama-2 family (RMSNorm + RoPE + GQA + SwiGLU), TPU-sharded.

The BASELINE.json flagship ("Llama-2 7B Fleet sharding-stage3 → TPU mesh",
"Llama-2 70B 4D hybrid-parallel"). Sharding layout is the standard
fsdp×tp recipe (see SURVEY.md §7.5/7.7): parameters carry both a ``tp``
axis (Megatron split) and an ``fsdp`` axis (ZeRO-3 split); activations are
batch-sharded over (dp, fsdp) and feature-sharded over tp where natural.

| tensor              | shape      | spec              |
|---------------------|------------|-------------------|
| embed               | [V, E]     | P("tp", "fsdp")   |
| wq/wk/wv            | [E, H]     | P("fsdp", "tp")   |
| wo                  | [H, E]     | P("tp", "fsdp")   |
| gate/up             | [E, F]     | P("fsdp", "tp")   |
| down                | [F, E]     | P("tp", "fsdp")   |
| lm_head             | [E, V]     | P("fsdp", "tp")   |
| norms               | [E]        | P()               |

Layers are scan-stacked (nn.ScannedBlocks) with optional remat — the
recompute strategy of the reference (``fluid/optimizer.py:4491``) at
layer granularity.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common import Embedding, Linear
from paddle_tpu.nn.initializer import Normal
from paddle_tpu.nn.norm import RMSNorm
from paddle_tpu.nn.scan import ScannedBlocks

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaBlock"]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_base: float = 10000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing_saveable"
    # LM-head loss path: "dense" (matmul + XLA-fused xent), "fused"
    # (Pallas linear⊗xent, [B,T,V] logits never materialized — the
    # memory-bound choice), "chunked", or "auto" (fused when supported
    # on TPU). See nn.functional.linear_cross_entropy.
    lm_head_mode: str = "dense"
    # initializer std (llama uses 0.02-ish scaled)
    init_std: float = 0.02

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def llama2_13b(cls) -> "LlamaConfig":
        return cls(hidden_size=5120, intermediate_size=13824, num_layers=40,
                   num_heads=40, num_kv_heads=40)

    @classmethod
    def llama2_70b(cls) -> "LlamaConfig":
        return cls(hidden_size=8192, intermediate_size=28672, num_layers=80,
                   num_heads=64, num_kv_heads=8)

    @classmethod
    def tiny(cls, vocab_size: int = 256, hidden_size: int = 64,
             num_layers: int = 2, num_heads: int = 4, num_kv_heads: int = 2,
             max_seq_len: int = 128, **kw) -> "LlamaConfig":
        return cls(vocab_size=vocab_size, hidden_size=hidden_size,
                   intermediate_size=hidden_size * 4 * 2 // 3 // 8 * 8 or 32,
                   num_layers=num_layers, num_heads=num_heads,
                   num_kv_heads=num_kv_heads, max_seq_len=max_seq_len,
                   dtype="float32", remat=False, **kw)

    def num_params(self) -> int:
        E, F_, V, L = (self.hidden_size, self.intermediate_size,
                       self.vocab_size, self.num_layers)
        head_dim = E // self.num_heads
        kv = self.num_kv_heads * head_dim
        per_layer = E * E + 2 * E * kv + E * E + 3 * E * F_ + 2 * E
        return V * E + L * per_layer + E + (0 if self.tie_embeddings
                                            else E * V)


class LlamaAttention(Module):
    """GQA attention of the Llama family. Three more things a config may
    ask for (each absent from a config that does not name it, and then
    absent from the module, whose tree and programs stay as they were):
    ``head_dim`` wider or narrower than ``hidden / heads`` (the q and o
    projections are ``heads x head_dim`` wide); ``qk_norm`` — one
    ``RMSNorm(head_dim)`` over every query head and one over every key
    head, before rotation (Qwen3's head-wise form); ``attn_block`` B > 1
    — block-causal attention: a position sees every earlier block of B
    positions and its whole own block, both ways
    (``_common.block_mask``)."""

    def __init__(self, cfg: LlamaConfig, key=None):
        keys = rng.split_key(key, 4)
        E = cfg.hidden_size
        head_dim = getattr(cfg, "head_dim", None) or E // cfg.num_heads
        q_dim = cfg.num_heads * head_dim
        kv_dim = cfg.num_kv_heads * head_dim
        dtype = jnp.dtype(cfg.dtype)
        init = Normal(0.0, cfg.init_std)
        out_init = Normal(0.0, cfg.init_std / math.sqrt(2 * cfg.num_layers))
        self.wq = Linear(E, q_dim, bias=False, weight_init=init, dtype=dtype,
                         key=keys[0], pspec=P("fsdp", "tp"))
        self.wk = Linear(E, kv_dim, bias=False, weight_init=init, dtype=dtype,
                         key=keys[1], pspec=P("fsdp", "tp"))
        self.wv = Linear(E, kv_dim, bias=False, weight_init=init, dtype=dtype,
                         key=keys[2], pspec=P("fsdp", "tp"))
        self.wo = Linear(q_dim, E, bias=False, weight_init=out_init,
                         dtype=dtype, key=keys[3], pspec=P("tp", "fsdp"))
        if getattr(cfg, "qk_norm", False):
            self.q_norm = RMSNorm(head_dim, epsilon=cfg.rms_eps, dtype=dtype)
            self.k_norm = RMSNorm(head_dim, epsilon=cfg.rms_eps, dtype=dtype)
        if getattr(cfg, "attn_block", 1) > 1:
            self.attn_block = int(cfg.attn_block)
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = head_dim
        self.rope_base = cfg.rope_base
        # sequence-parallel mode, set by the strategy compiler:
        # "none" | "ring" | "ulysses"
        self.seq_mode = "none"

    @jax.named_scope("attn")
    def __call__(self, x, positions=None, cache=None, index=None,
                 layer=0, training: bool = False):
        """Forward. ``cache``/``index``/``layer`` enable incremental
        decoding with a *static* KV cache: ``cache`` holds the full
        stacked read-only buffers (``(k_buf, v_buf)``
        [L, B, Hkv, S, D], or the int8 4-tuple), ``layer`` this block's
        layer id, ``index`` the write offset of this chunk. The cached
        branch returns ``(out, payload)`` — the chunk's k/v for the
        model-level stacked write (``models._common.apply_cache_writes``).
        The fixed shape means one compiled decode step serves every
        position (XLA-friendly; the reference's growing-concat Cache in
        ``python/paddle/nn/layer/transformer.py`` recompiles per length
        under jit)."""
        B, T, E = x.shape
        # tags for the "save_block_dots_qkv" remat policy (no-op
        # otherwise): saving the projections lets the attention VJP
        # recompute start from q/k/v instead of re-running the matmuls
        q = jax.ad_checkpoint.checkpoint_name(
            self.wq(x), "qkv").reshape(B, T, self.num_heads, self.head_dim)
        k = jax.ad_checkpoint.checkpoint_name(
            self.wk(x), "qkv").reshape(B, T, self.num_kv_heads,
                                       self.head_dim)
        v = jax.ad_checkpoint.checkpoint_name(
            self.wv(x), "qkv").reshape(B, T, self.num_kv_heads,
                                       self.head_dim)
        if hasattr(self, "q_norm"):
            q, k = self.q_norm(q), self.k_norm(k)
        block = getattr(self, "attn_block", 1)
        if positions is None:
            # inside a manual-sp region (pipeline∘sp) the local T is one
            # sequence slice: RoPE must rotate by absolute positions
            from paddle_tpu.parallel.ring_attention import global_positions
            positions = global_positions(T)
            if index is not None:
                positions = positions + index
        cos, sin = F.rotary_embedding(positions, self.head_dim,
                                      self.rope_base)
        q = F.apply_rotary(q, cos, sin)
        k = F.apply_rotary(k, cos, sin)
        width = self.num_heads * self.head_dim
        if cache is not None:
            from paddle_tpu.models._common import cached_attention
            out, payload = cached_attention(q, k, v, cache, index,
                                            layer=layer, block=block)
            return self.wo(out.reshape(B, T, width)), payload
        if block > 1:
            from paddle_tpu.models._common import block_mask
            out = F.scaled_dot_product_attention(q, k, v, block_mask(T, block))
            return self.wo(out.reshape(B, T, width))
        # activations: shard heads over tp inside the einsum via sharded
        # inputs; flash path kicks in on TPU for supported shapes
        if self.seq_mode != "none":
            from paddle_tpu.parallel.ring_attention import (
                ring_self_attention, ulysses_self_attention)
            attn_fn = (ring_self_attention if self.seq_mode == "ring"
                       else ulysses_self_attention)
            out = attn_fn(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, causal=True)
        return self.wo(out.reshape(B, T, width))


class LlamaMLP(Module):
    def __init__(self, cfg: LlamaConfig, key=None):
        keys = rng.split_key(key, 3)
        E, F_ = cfg.hidden_size, cfg.intermediate_size
        dtype = jnp.dtype(cfg.dtype)
        init = Normal(0.0, cfg.init_std)
        down_init = Normal(0.0, cfg.init_std / math.sqrt(2 * cfg.num_layers))
        self.gate = Linear(E, F_, bias=False, weight_init=init, dtype=dtype,
                           key=keys[0], pspec=P("fsdp", "tp"))
        self.up = Linear(E, F_, bias=False, weight_init=init, dtype=dtype,
                         key=keys[1], pspec=P("fsdp", "tp"))
        self.down = Linear(F_, E, bias=False, weight_init=down_init,
                           dtype=dtype, key=keys[2], pspec=P("tp", "fsdp"))

    def __call__(self, x):
        # tags for the "save_mlp_dots" remat policy (no-op otherwise)
        up = jax.ad_checkpoint.checkpoint_name(self.up(x), "mlp_up")
        gate = jax.ad_checkpoint.checkpoint_name(self.gate(x), "mlp_gate")
        return self.down(F.swiglu(up, gate))


class LlamaBlock(Module):
    def __init__(self, cfg: LlamaConfig, key=None):
        k1, k2 = rng.split_key(key)
        dtype = jnp.dtype(cfg.dtype)
        self.attn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                 dtype=dtype)
        self.attn = LlamaAttention(cfg, key=k1)
        self.mlp_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                dtype=dtype)
        self.mlp = LlamaMLP(cfg, key=k2)

    def __call__(self, x, layer=None, *, cache=None, index=None,
                 training: bool = False):
        attn_out = self.attn(self.attn_norm(x), cache=cache, index=index,
                             layer=0 if layer is None else layer,
                             training=training)
        new_cache = None
        if cache is not None:
            attn_out, new_cache = attn_out
        # tag for the "save_attn_out" remat policy (no-op otherwise)
        attn_out = jax.ad_checkpoint.checkpoint_name(attn_out, "attn_out")
        x = x + attn_out
        x = x + jax.ad_checkpoint.checkpoint_name(
            self.mlp(self.mlp_norm(x)), "mlp_out")
        return x if new_cache is None else (x, new_cache)


class LlamaForCausalLM(Module):
    """Decoder-only causal LM. ``__call__`` returns logits [B, T, V]."""

    def __init__(self, cfg: LlamaConfig, key=None):
        keys = rng.split_key(key, 3 + cfg.num_layers)
        dtype = jnp.dtype(cfg.dtype)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_init=Normal(0.0, cfg.init_std),
                               dtype=dtype, key=keys[0],
                               pspec=P("tp", "fsdp"))
        self.blocks = ScannedBlocks(
            lambda i: LlamaBlock(cfg, key=keys[3 + i]), cfg.num_layers,
            remat=cfg.remat, remat_policy=cfg.remat_policy)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps, dtype=dtype)
        self.lm_head = (None if cfg.tie_embeddings else
                        Linear(cfg.hidden_size, cfg.vocab_size, bias=False,
                               weight_init=Normal(0.0, cfg.init_std),
                               dtype=dtype, key=keys[1],
                               pspec=P("fsdp", "tp")))
        self.config = cfg

    def hidden_states(self, input_ids, training: bool = False):
        """Trunk (embed → blocks → final norm) without the head
        projection — shared by ``__call__`` and the fused-loss path."""
        x = self.embed(input_ids)
        x = self.blocks(x, training=training)
        return self.norm(x)

    def __call__(self, input_ids, training: bool = False):
        x = self.hidden_states(input_ids, training=training)
        if self.lm_head is not None:
            return self.lm_head(x)
        return x @ self.embed.weight.T

    def pipeline_parts(self):
        """Decomposition for schedule-managed pipelines (1F1B,
        ``paddle_tpu/parallel/pipeline_1f1b.py``): (embed, blocks, head,
        head_loss_fn, loss_denom, assemble). Tied embeddings are
        supported: the head then carries the embedding table and
        ``assemble`` sums its head-side gradient into the embedding
        gradient (the grad-contribution hop back to stage 0)."""
        tied = self.lm_head is None
        head = ((self.norm, self.embed.weight) if tied
                else (self.norm, self.lm_head))

        def head_loss_sum(head, h, labels):
            """SUM of per-token losses for one microbatch. ``labels`` are
            ALREADY next-token-shifted (and trailing-ignore-masked) by
            the schedule — full-row loss here; a head-local shift would
            drop the prediction at every sequence-parallel shard
            boundary. The pipeline divides by the global valid count, so
            uneven ignore_index distributions across microbatches/shards
            stay exactly equivalent to the full-batch mean of
            ``model.loss``."""
            norm, out = head
            if tied:
                logits = (norm(h) @ out.T).astype(jnp.float32)
            else:
                logits = out(norm(h)).astype(jnp.float32)
            return F.cross_entropy(logits, labels, reduction="sum")

        from paddle_tpu.parallel.pipeline_1f1b import default_loss_denom \
            as loss_denom

        model = self

        def assemble(dembed, dblocks_stacked, dhead):
            g = jax.tree_util.tree_map(jnp.zeros_like, model)
            if tied:
                # sum in the promoted dtype: under keep_fp32_grads the
                # head-side grad is fp32 and must stay fp32 (a downcast
                # to a cast fp16 embed dtype could overflow the scaled
                # gradient and always discards the fp32 accumulation)
                pt = jnp.promote_types(dembed.weight.dtype,
                                       dhead[1].dtype)
                demb = dembed.replace(
                    weight=dembed.weight.astype(pt)
                    + dhead[1].astype(pt))
                return g.replace(
                    embed=demb, norm=dhead[0],
                    blocks=g.blocks.replace(block=dblocks_stacked))
            return g.replace(
                embed=dembed, norm=dhead[0], lm_head=dhead[1],
                blocks=g.blocks.replace(block=dblocks_stacked))

        return (self.embed, self.blocks, head, head_loss_sum, loss_denom,
                assemble)

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Stacked static KV cache for all layers:
        ([L, B, Hkv, S, D], [L, B, Hkv, S, D]) zeros (batch on axis 1 —
        the beam-search reorder contract)."""
        from paddle_tpu.models._common import init_kv_cache
        cfg = self.config
        return init_kv_cache(cfg.num_layers, batch_size, max_len,
                             cfg.num_kv_heads,
                             cfg.hidden_size // cfg.num_heads,
                             jnp.dtype(dtype or cfg.dtype))

    def forward_with_cache(self, input_ids, cache, index):
        """Forward a chunk (prefill: the whole prompt at index 0; decode:
        one token at index t) updating the static KV cache. Returns
        (logits [B, T, V], new_cache). The stacked cache rides the scan
        as a closed-over constant — each block reads it through its
        layer id (no per-layer slice materializes; see
        ``_common.cached_attention``) and contributes its chunk k/v to
        the scan outputs; ONE stacked dynamic_update_slice then writes
        all layers — in place under the decode loop's donated carry
        (re-stacking the cache through scan outputs cost a full cache
        copy per token)."""
        from paddle_tpu.models._common import apply_cache_writes
        x = self.embed(input_ids)
        x, payload = self.blocks.scan_with(
            x, jnp.arange(self.config.num_layers), cache=cache,
            index=index)
        cache = apply_cache_writes(cache, payload, index)
        x = self.norm(x)
        if self.lm_head is not None:
            return self.lm_head(x), cache
        return x @ self.embed.weight.T, cache

    def generate(self, input_ids, max_new_tokens: int, **kwargs):
        """Autoregressive decode — see ``paddle_tpu.models.generation``."""
        from paddle_tpu.models.generation import generate
        return generate(self, input_ids, max_new_tokens, **kwargs)

    def shard_for_inference(self, mesh):
        """Place parameters under ``NamedSharding`` on ``mesh`` using
        the per-module spec map (table in the module docstring) — the
        Megatron column/row split applied at inference time. A serving
        mesh has degree 1 on every non-tp axis, so only the tp split is
        material there; the same call works on a training fsdp×tp mesh.
        Validates the head counts against the mesh's tp degree up front
        (an indivisible KV-head axis would silently pad-shard the KV
        cache) and returns the sharded model."""
        from paddle_tpu.core.module import partition_specs
        from paddle_tpu.parallel.mesh import sharding_tree
        tp = int(dict(mesh.shape).get("tp", 1))
        cfg = self.config
        if cfg.num_heads % tp or cfg.num_kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide num_heads={cfg.num_heads} and "
                f"num_kv_heads={cfg.num_kv_heads} (attention projections "
                "column-split per head; the KV cache shards on the "
                "KV-head axis)")
        return jax.device_put(self, sharding_tree(mesh,
                                                  partition_specs(self)))

    def loss(self, input_ids, labels, ignore_index: int = -100,
             training: bool = True):
        """Next-token cross entropy (labels = input shifted by caller or
        equal to inputs for standard LM training on packed sequences).

        With ``cfg.lm_head_mode != "dense"`` the head projection fuses
        into the loss so the [B, T, V] logits never materialize (shared
        dispatch: ``models._common.causal_lm_loss``). Tied embeddings
        pass the transposed [V, E] table — one O(V·E) copy per step,
        orders of magnitude below the O(N·V) logits the fusion
        removes."""
        from paddle_tpu.models._common import causal_lm_loss
        w = (self.lm_head.weight if self.lm_head is not None
             else self.embed.weight.T)
        return causal_lm_loss(self, w, input_ids, labels, ignore_index,
                              training)
