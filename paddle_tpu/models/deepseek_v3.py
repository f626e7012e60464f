"""DeepSeek-V3-family decoder (GigaChat3.1, DeepSeek-V3/R1, Kimi-K2 share
it): latent attention (MLA) over a latent cache, leading dense layers,
then expert layers with sigmoid group-limited routing and a shared
expert.

What "supported" covers: the **serving path** — ``init_cache`` /
``forward_with_cache`` under ``generate()`` and the paged
``GenerationEngine`` — for a model whose expert layers hold **a share of
the routed experts** (``held``; one chip of an expert-parallel
deployment, without the exchange), and the full forward ``__call__``.
Not held: the multi-token-prediction module
(``num_nextn_predict_layers``); no training recipe is claimed.

Attention (``MLAttention``): queries through a low-rank pair
(``wq_a`` → RMSNorm → ``wq_b``; ``q_lora_rank=None``: one ``wq``), keys
and values through ONE compressed
row a token (``wkv_a`` → [c_kv | k_rope]; RMSNorm on c_kv, RoPE on the
shared k_rope) that ``wkv_b`` expands per head into [k_nope | v]. The
cache holds the compressed row and the rope key — ``kv_lora_rank +
qk_rope_head_dim`` numbers a token a layer, nothing per head
(``_common.init_latent_cache``) — and decode reads it with the
up-projection absorbed into the query and the output
(``_common.latent_attention``). Rope dims pair by halves (``i`` with
``i + R/2``: the layout HF permutes the published adjacent pairs to);
frequencies and the softmax scale follow YaRN as DeepSeek-V3 applies it.
``rope=False`` (``models/kimi_linear.py``'s latent layers) rotates
nothing: the "rope" dims stay as one shared, unrotated key part a token
and the scale is ``(nope + rope)^-1/2``.

Layers are two ``ScannedBlocks`` stacks: ``dense_blocks`` (the leading
``first_k_dense`` layers, SwiGLU of ``intermediate_size``) and
``blocks`` (expert layers, ``nn.moe.MoEMLP`` with
``route="sigmoid_group"``). Under a state tape the expert layers record
each position's routed picks (``live_counts``); the serving engine sums
them over live positions on the device.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.models.llama import LlamaMLP
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common import Embedding, Linear
from paddle_tpu.nn.initializer import Normal
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.nn.norm import RMSNorm
from paddle_tpu.nn.scan import ScannedBlocks

__all__ = ["DeepseekV3Config", "DeepseekV3ForCausalLM", "MLAttention",
           "yarn_inv_freq", "yarn_mscale"]


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432          # the leading dense layers
    moe_intermediate_size: int = 2048       # one routed / shared expert
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    # None = one full-rank query projection (``wq``), no low-rank pair
    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_seq_len: int = 4096
    # False = NoPE (Kimi-Linear's latent layers): the ``qk_rope_head_dim``
    # dims stay, one shared key part a token, and nothing is rotated
    rope: bool = True
    rope_base: float = 10000.0
    # YaRN (rope_factor 1 = plain RoPE)
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    init_std: float = 0.02
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    n_shared_experts: int = 1
    # (first, count): the routed experts this model holds in every expert
    # layer; None = all of them
    held: tuple | None = None

    def __post_init__(self):
        if self.held is not None:       # a JSON list hashes as a tuple
            object.__setattr__(self, "held", tuple(self.held))

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_layers=3, first_k_dense=1,
                    num_heads=4, q_lora_rank=24, kv_lora_rank=16,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
                    max_seq_len=128, rope_factor=4.0, rope_original_max=32,
                    dtype="float32", n_routed_experts=16,
                    num_experts_per_tok=4, n_group=4, topk_group=2)
        base.update(kw)
        return cls(**base)


# -- YaRN as DeepSeek-V3 applies it --------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """Inverse frequencies of the ``dim`` rope dims: the blend of
    ``base^(-2i/dim)`` and the same over ``factor`` under the linear ramp
    between the correction dims of ``beta_fast`` / ``beta_slow``."""
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra.astype(np.float32)

    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


class MLAttention(Module):
    def __init__(self, cfg: DeepseekV3Config, key=None):
        keys = rng.split_key(key, 5)
        E, H, dtype = cfg.hidden_size, cfg.num_heads, jnp.dtype(cfg.dtype)
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        init = Normal(0.0, cfg.init_std)
        out_init = Normal(0.0, cfg.init_std / math.sqrt(2 * cfg.num_layers))

        def lin(i, n_in, n_out, w=init):
            return Linear(n_in, n_out, bias=False, weight_init=w,
                          dtype=dtype, key=keys[i])

        if cfg.q_lora_rank is None:
            self.wq = lin(0, E, H * qk)
        else:
            self.wq_a = lin(0, E, cfg.q_lora_rank)
            self.q_norm = RMSNorm(cfg.q_lora_rank, epsilon=cfg.rms_eps,
                                  dtype=dtype)
            self.wq_b = lin(1, cfg.q_lora_rank, H * qk)
        self.wkv_a = lin(2, E, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        self.kv_norm = RMSNorm(cfg.kv_lora_rank, epsilon=cfg.rms_eps,
                               dtype=dtype)
        self.wkv_b = lin(3, cfg.kv_lora_rank,
                         H * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.wo = lin(4, H * cfg.v_head_dim, E, out_init)
        self.cfg = cfg

    def rope_tables(self, positions):
        c = self.cfg
        inv = yarn_inv_freq(c.qk_rope_head_dim, c.rope_base, c.rope_factor,
                            c.rope_original_max, c.rope_beta_fast,
                            c.rope_beta_slow)
        ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
        m = (yarn_mscale(c.rope_factor, c.rope_mscale)
             / yarn_mscale(c.rope_factor, c.rope_mscale_all_dim))
        return jnp.cos(ang) * m, jnp.sin(ang) * m

    @property
    def scale(self) -> float:
        c = self.cfg
        if not c.rope:
            return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
        m = yarn_mscale(c.rope_factor, c.rope_mscale_all_dim)
        return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 * m * m

    def __call__(self, x, cache=None, index=None, layer=0,
                 training: bool = False):
        """``(out, payload)`` with a cache (the shared cache contract:
        the chunk's rows come back for the model's one stacked write),
        ``out`` without."""
        from paddle_tpu.models._common import latent_attention

        c = self.cfg
        B, T, _ = x.shape
        H, N, R = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        C = c.kv_lora_rank
        cos = sin = None
        if c.rope:
            positions = jnp.arange(T)
            if index is not None:
                positions = positions + index
            cos, sin = self.rope_tables(positions)
        with jax.named_scope("mla/q"):
            q = (self.wq(x) if c.q_lora_rank is None
                 else self.wq_b(self.q_norm(self.wq_a(x))))
            q = q.reshape(B, T, H, N + R)
            q_nope = q[..., :N]
            q_rope = (F.apply_rotary(q[..., N:], cos, sin) if c.rope
                      else q[..., N:])
        with jax.named_scope("mla/latent"):
            kv = self.wkv_a(x)
            c_kv = self.kv_norm(kv[..., :C])
            k_rope = (F.apply_rotary(kv[..., None, C:], cos, sin)[:, :, 0]
                      if c.rope else kv[..., C:])
        w = self.wkv_b.weight.reshape(C, H, N + c.v_head_dim)
        out, payload = latent_attention(
            q_nope, q_rope, c_kv, k_rope, w[..., :N], w[..., N:],
            self.scale, cache=cache, index=index, layer=layer)
        with jax.named_scope("mla/out"):
            out = self.wo(out.reshape(B, T, H * c.v_head_dim))
        return out if cache is None else (out, payload)


class DeepseekV3Block(Module):
    """One decoder layer: latent attention, then a dense SwiGLU
    (``moe=False``) or the expert layer."""

    def __init__(self, cfg: DeepseekV3Config, moe: bool, key=None):
        k1, k2 = rng.split_key(key)
        dtype = jnp.dtype(cfg.dtype)
        self.attn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                 dtype=dtype)
        self.attn = MLAttention(cfg, key=k1)
        self.mlp_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                dtype=dtype)
        if moe:
            self.moe = MoEMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
                init_std=cfg.init_std, num_layers=cfg.num_layers,
                dtype=dtype, route="sigmoid_group", n_group=cfg.n_group,
                topk_group=cfg.topk_group,
                routed_scale=cfg.routed_scaling_factor,
                shared_size=cfg.n_shared_experts
                * cfg.moe_intermediate_size,
                held=cfg.held, key=k2)
        else:
            self.mlp = LlamaMLP(cfg, key=k2)

    def __call__(self, x, layer=None, *, cache=None, index=None,
                 training: bool = False):
        payload = None
        attn_out = self.attn(self.attn_norm(x), cache=cache, index=index,
                             layer=0 if layer is None else layer,
                             training=training)
        if cache is not None:
            attn_out, payload = attn_out
        x = x + attn_out
        h = self.mlp_norm(x)
        x = x + (self.moe(h)[0] if hasattr(self, "moe") else self.mlp(h))
        return x if payload is None else (x, payload)


class DeepseekV3ForCausalLM(Module):
    """Decoder-only causal LM of the DeepSeek-V3 family (module
    docstring says what is supported)."""

    # names the expert layers record on a state tape, one value a
    # position: the serving engine sums them over live positions
    live_counts = ("moe_picks", "moe_picks_held")
    # one cache row a token shared by all heads: what serves per-head
    # K/V alone (int8 leaves, a KV-head mesh axis) refuses this model
    latent_cache = True

    def __init__(self, cfg: DeepseekV3Config, key=None):
        if not 0 <= cfg.first_k_dense < cfg.num_layers:
            raise ValueError(
                f"first_k_dense {cfg.first_k_dense} must leave an expert "
                f"layer among {cfg.num_layers}")
        keys = rng.split_key(key, 2 + cfg.num_layers)
        dtype = jnp.dtype(cfg.dtype)
        kd = cfg.first_k_dense
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_init=Normal(0.0, cfg.init_std),
                               dtype=dtype, key=keys[0],
                               pspec=P("tp", "fsdp"))
        self.dense_blocks = ScannedBlocks(
            lambda i: DeepseekV3Block(cfg, False, key=keys[2 + i]),
            kd) if kd else None
        self.blocks = ScannedBlocks(
            lambda i: DeepseekV3Block(cfg, True, key=keys[2 + kd + i]),
            cfg.num_layers - kd)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                            dtype=dtype)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias=False,
                              weight_init=Normal(0.0, cfg.init_std),
                              dtype=dtype, key=keys[1],
                              pspec=P("fsdp", "tp"))
        self.config = cfg

    def __call__(self, input_ids, training: bool = False):
        x = self.embed(input_ids)
        if self.dense_blocks is not None:
            x = self.dense_blocks(x, training=training)
        x = self.blocks(x, training=training)
        return self.lm_head(self.norm(x))

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """The latent cache: one leaf ``[L, B, 1, S, kv_lora_rank +
        qk_rope_head_dim]`` (``_common.init_latent_cache``)."""
        from paddle_tpu.models._common import init_latent_cache
        cfg = self.config
        return init_latent_cache(
            cfg.num_layers, batch_size, max_len,
            cfg.kv_lora_rank + cfg.qk_rope_head_dim,
            jnp.dtype(dtype or cfg.dtype))

    def forward_with_cache(self, input_ids, cache, index):
        """Prefill / decode through the shared cache contract. Both
        stacks read the one stacked cache by layer id and give their
        chunk rows back; one write lands them all. The expert stack's
        scan carries the per-layer state tape out, so the layers' pick
        counts reach whoever listens."""
        from paddle_tpu.models._common import apply_cache_writes
        from paddle_tpu.nn.scan import _reemit_tape
        from paddle_tpu.nn.stateful import tape_call

        cfg = self.config
        kd = cfg.first_k_dense
        x = self.embed(input_ids)
        rows = []
        if kd:
            x, pay = self.dense_blocks.scan_with(
                x, jnp.arange(kd), cache=cache, index=index)
            rows.append(pay)

        def expert_layer(block, carry, layer):
            (y, pay), tape = tape_call(block, carry, layer, cache=cache,
                                       index=index)
            return y, (pay, tape)

        x, (pay, tape) = self.blocks.scan_with(
            x, kd + jnp.arange(cfg.num_layers - kd), fn=expert_layer)
        _reemit_tape(tape)
        rows.append(pay)
        payload = jax.tree_util.tree_map(
            lambda *p: jnp.concatenate(p, axis=0), *rows)
        cache = apply_cache_writes(cache, payload, index)
        return self.lm_head(self.norm(x)), cache

    def generate(self, input_ids, max_new_tokens: int, **kwargs):
        from paddle_tpu.models.generation import generate
        return generate(self, input_ids, max_new_tokens, **kwargs)

    def shard_for_inference(self, mesh):
        raise ValueError(
            "gen_mesh_tp with a latent (MLA) cache is not implemented: the "
            "cache has one row a token shared by all heads and no KV-head "
            "axis to shard (POOL_KV_SPEC); serve this model unsharded")
