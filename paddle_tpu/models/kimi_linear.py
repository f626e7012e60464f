"""Kimi-Linear decoder (moonshotai Kimi-Linear-48B-A3B): layers of two
attention kinds in one stack — **KDA** (Kimi Delta Attention, a gated
delta-rule linear attention with a per-channel decay, whose cache is a
recurrent state of O(1) a sequence) and **latent attention without
position encoding** (``deepseek_v3.MLAttention`` with one full-rank
query projection and no rotation) — three KDA layers to one latent
layer; one leading dense MLP, then expert layers with sigmoid routing
over one group and a shared expert (``nn.moe.MoEMLP``,
``route="sigmoid_group"``), of which a model may hold a share
(``held``: one chip of an expert-parallel deployment, without the
exchange).

What "supported" covers: the **serving path** — ``init_cache`` /
``forward_with_cache`` under ``generate()`` (contiguous cache) and the
paged ``GenerationEngine`` (a latent page pool beside a slot-indexed
state group, prefix reuse by state snapshot) — and the full forward
``__call__``. No training recipe is claimed (the chunked scan has no
tuned backward here).

One KDA layer for its normed input ``x`` (per head ``h`` of ``H``,
``d_k = d_v = D``; ``ops/kda.py`` holds the recurrence's three forms):

    q~, k~, v~ = W_q x, W_k x, W_v x                    (E -> H*D each, no bias)
    q', k', v' = SiLU(conv4(q~)), SiLU(conv4(k~)), SiLU(conv4(v~))
                                     (causal depthwise, ``conv_kernel`` taps, no bias)
    q = l2norm(q'_h) * D^-1/2 ;  k = l2norm(k'_h) ;  v = v'_h
    g_t = -exp(A_log_h) * softplus(W_fb (W_fa x_t) + dt_bias)_h  in R^D
    beta_t = sigmoid(W_b x_t)_h
    S~ = Diag(exp(g_t)) S_{t-1} ;  S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t                                     (S in R^{D x D}, float32)
    y_t = W_o [ rmsnorm_head(o_t; w_o_norm) * sigmoid(W_gb (W_ga x_t))_h ]

(``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``; the low-rank widths of
``W_fa``/``W_ga`` are ``kda_rank``, the head size by the family's
convention.) A latent layer: ``q = W_q x`` (H x (nope + rope)),
``[c_kv | k_r] = W_kva x``, ``c = rmsnorm(c_kv)``, ``[k_nope | v]_h =
W_kvb c``, scores ``(q_nope . k_nope + q_r . k_r) (nope + rope)^-1/2``
with no rotation anywhere, causal softmax, ``W_o``. A block is pre-norm
residual twice.

Layout. The layers repeat with the period ``(KDA, ..., KDA, latent)``
(``full_attn_layers``, 1-based as published: every fourth layer, and
the last). The first period holds the leading dense MLP and is a python
loop of blocks (``head``); the whole periods after it are ONE
``ScannedBlocks`` whose body is the period's layers in a python loop
(as ``models/smallthinker.py``); what is left over — two KDA layers and
the closing latent layer of the published 27 — is a loop again
(``tail``). The cache is a tuple of two **groups** (``cache_groups``):
the latent layers' one leaf ``[Lm, B, 1, S, W]``
(``_common.init_latent_cache``) and the KDA layers'
``generation.StateCache`` — state ``[Lk, B, H, D, D]`` float32 and
convolution tail, ``K-1`` inputs of ``3*H*D`` a sequence kept as rows of
128 lanes (``tail_shape``). The state group's rows are
read AND replaced by every forward, so they ride through the layers as a
carry (in place under the callers' donation) and come back whole;
``StateCache.length`` makes padding the identity on them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.models.deepseek_v3 import DeepseekV3Config, MLAttention
from paddle_tpu.models.llama import LlamaMLP
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common import Embedding, Linear
from paddle_tpu.nn.initializer import Normal
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.nn.norm import RMSNorm
from paddle_tpu.nn.scan import ScannedBlocks
from paddle_tpu.ops import kda

__all__ = ["KimiLinearConfig", "KimiLinearForCausalLM",
           "KimiDeltaAttention"]

KDA, MLA = "kda", "mla"


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216           # the leading dense layer(s)
    moe_intermediate_size: int = 1024       # one routed / shared expert
    num_layers: int = 27
    first_k_dense: int = 1
    # the latent layers, 1-based as the published config counts them;
    # every other layer is KDA
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    # latent attention
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # KDA
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kda_rank: int | None = None             # None = kda_head_dim
    # expert layers
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.446
    n_shared_experts: int = 1
    # (first, count): the routed experts this model holds in every expert
    # layer; None = all of them
    held: tuple | None = None
    max_seq_len: int = 4096
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    init_std: float = 0.02

    def __post_init__(self):
        object.__setattr__(self, "full_attn_layers", tuple(
            int(v) for v in self.full_attn_layers))   # JSON lists hash
        if self.held is not None:
            object.__setattr__(self, "held", tuple(self.held))
        full, L = self.full_attn_layers, self.num_layers
        p = self.period
        if (not full or sorted(set(full)) != list(full) or full[-1] > L
                or full[0] < 2 or self.first_k_dense > p
                or not 0 <= self.first_k_dense < L):
            raise ValueError(
                f"full_attn_layers {full} must be increasing layers of "
                f"1..{L} with a KDA layer ahead of the first, and the "
                f"{self.first_k_dense} leading dense layer(s) lie inside "
                f"the first period of {p}")

    @property
    def kinds(self) -> tuple:
        """Layer ``l`` (0-based) -> ``"kda"`` or ``"mla"``."""
        full = set(self.full_attn_layers)
        return tuple(MLA if l + 1 in full else KDA
                     for l in range(self.num_layers))

    @property
    def period(self) -> int:
        return self.full_attn_layers[0]

    @property
    def pattern(self) -> tuple:
        return (KDA,) * (self.period - 1) + (MLA,)

    @property
    def whole_periods(self) -> int:
        """Periods after the first that repeat the pattern whole: the
        scanned stack."""
        kinds, p, n = self.kinds, self.period, 0
        while kinds[(n + 1) * p:(n + 2) * p] == self.pattern:
            n += 1
        return n

    @property
    def mla(self) -> DeepseekV3Config:
        """The latent layers' attention as ``MLAttention`` reads it."""
        return DeepseekV3Config(
            hidden_size=self.hidden_size, num_layers=self.num_layers,
            num_heads=self.num_heads, q_lora_rank=None,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope=False, rope_factor=1.0,
            rms_eps=self.rms_eps, dtype=self.dtype, init_std=self.init_std)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_layers=11,
                    full_attn_layers=(4, 8, 11), num_heads=4,
                    kv_lora_rank=16, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, kda_heads=2,
                    kda_head_dim=16, n_routed_experts=16,
                    num_experts_per_tok=4, max_seq_len=256,
                    dtype="float32")
        base.update(kw)
        return cls(**base)


def tail_shape(taps: int, width: int) -> tuple:
    """The convolution tail of one sequence, ``[taps - 1, width]``
    inputs, as the cache keeps it: rows of 128 lanes where that divides.
    A ``[3, 12288]`` bf16 leaf pads its 3 rows to a 16-row tile, and the
    TPU compiler then converts the whole stacked leaf between a packed
    and a padded layout around every decode step (four passes over 94
    MB, 6.9 of 38 ms a step: my chip run, PR 35); ``[288, 128]`` has
    nothing to pad."""
    n = (taps - 1) * width
    return (n // 128, 128) if n % 128 == 0 else (1, n)


def _at(rows, layer):
    return (rows[layer] if isinstance(layer, int) else
            jax.lax.dynamic_index_in_dim(rows, layer, 0, keepdims=False))


class KimiDeltaAttention(Module):
    """One KDA layer's attention (module docstring has the equations).
    Without ``rows`` the chunk starts from a zero state; with them it
    reads layer ``layer`` of the state group's stacked rows and hands
    the stack back with that layer replaced."""

    def __init__(self, cfg: KimiLinearConfig, key=None):
        keys = rng.split_key(key, 10)
        E, H, D = cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim
        R = cfg.kda_rank or D
        dtype = jnp.dtype(cfg.dtype)
        init = Normal(0.0, cfg.init_std)
        out_init = Normal(0.0, cfg.init_std / math.sqrt(2 * cfg.num_layers))

        def lin(i, n_in, n_out, w=init):
            return Linear(n_in, n_out, bias=False, weight_init=w,
                          dtype=dtype, key=keys[i])

        self.wq, self.wk, self.wv = (lin(i, E, H * D) for i in range(3))
        # the three depthwise filters side by side, [taps, q | k | v]
        self.conv = init(keys[3], (cfg.conv_kernel, 3 * H * D), dtype)
        self.wf_a, self.wf_b = lin(4, E, R), lin(5, R, H * D)
        self.wb = lin(6, E, H)
        self.wg_a, self.wg_b = lin(7, E, R), lin(8, R, H * D)
        self.wo = lin(9, H * D, E, out_init)
        # decay: alpha = exp(-exp(A_log) * softplus(. + dt_bias)). The
        # family draws A from U(1, 16) and dt from logU(1e-3, 1e-1) so
        # that decays span ~0.2-0.999 and the state carries a long prefix
        ka, kd = rng.split_key(keys[3])
        self.A_log = jnp.log(jax.random.uniform(ka, (H,), jnp.float32,
                                                1.0, 16.0))
        dt = jnp.exp(jax.random.uniform(kd, (H * D,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        self.dt_bias = dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1(dt)
        self.o_norm = RMSNorm(D, epsilon=cfg.rms_eps, dtype=dtype)
        self.heads, self.head_dim = H, D

    def __call__(self, x, rows=None, layer=0, length=None):
        B, T, _ = x.shape
        H, D = self.heads, self.head_dim
        f32 = jnp.float32
        with jax.named_scope("kda/proj"):
            qkv = jnp.concatenate([self.wq(x), self.wk(x), self.wv(x)], -1)
        with jax.named_scope("kda/conv"):
            # (the tail is kept as whole lane tiles: see ``tail_shape``)
            tail = (None if rows is None else
                    _at(rows[1], layer).reshape(B, -1, 3 * H * D))
            qkv, tail = kda.short_conv(qkv, self.conv, tail, length)
            q, k, v = (t.reshape(B, T, H, D).astype(f32)
                       for t in jnp.split(F.silu(qkv), 3, axis=-1))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)
                                  + 1e-6) * D ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        with jax.named_scope("kda/gate"):
            dt = jax.nn.softplus(
                self.wf_b(self.wf_a(x)).astype(f32) + self.dt_bias)
            g = -jnp.exp(self.A_log)[:, None] * dt.reshape(B, T, H, D)
            beta = jax.nn.sigmoid(self.wb(x).astype(f32))
            g, beta = kda.mask_padding(g, beta, length)
        if rows is None:
            with jax.named_scope("kda/chunk"):
                o, _ = kda.kda_chunked(q, k, v, g, beta)
        elif T == 1:
            with jax.named_scope("kda/step"):
                o, state = kda.kda_step(rows[0], layer, q[:, 0], k[:, 0],
                                        v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
        else:
            with jax.named_scope("kda/chunk"):
                o, S = kda.kda_chunked(q, k, v, g, beta,
                                       _at(rows[0], layer))
                state = jax.lax.dynamic_update_index_in_dim(
                    rows[0], S, layer, 0)
        with jax.named_scope("kda/out"):
            gate = jax.nn.sigmoid(
                self.wg_b(self.wg_a(x)).astype(f32)).reshape(B, T, H, D)
            o = F.rms_norm(o, self.o_norm.weight.astype(f32),
                           self.o_norm.epsilon) * gate
            out = self.wo(o.reshape(B, T, H * D).astype(x.dtype))
        if rows is None:
            return out
        return out, (state, jax.lax.dynamic_update_index_in_dim(
            rows[1], tail.astype(rows[1].dtype).reshape(rows[1].shape[1:]),
            layer, 0))


class KimiLinearBlock(Module):
    """One decoder layer: attention of its kind, then the dense SwiGLU
    (``moe=False``) or the expert layer."""

    def __init__(self, cfg: KimiLinearConfig, kind: str, moe: bool,
                 key=None):
        k1, k2 = rng.split_key(key)
        dtype = jnp.dtype(cfg.dtype)
        self.attn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                 dtype=dtype)
        self.attn = (KimiDeltaAttention(cfg, key=k1) if kind == KDA
                     else MLAttention(cfg.mla, key=k1))
        self.mlp_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                dtype=dtype)
        if moe:
            self.moe = MoEMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
                init_std=cfg.init_std, num_layers=cfg.num_layers,
                dtype=dtype, route="sigmoid_group", n_group=1,
                topk_group=1, routed_scale=cfg.routed_scaling_factor,
                shared_size=cfg.n_shared_experts
                * cfg.moe_intermediate_size,
                held=cfg.held, key=k2)
        else:
            self.mlp = LlamaMLP(cfg, key=k2)
        self.kind = kind

    def _mlp(self, x):
        h = self.mlp_norm(x)
        return x + (self.moe(h)[0] if hasattr(self, "moe") else self.mlp(h))

    def __call__(self, x, training: bool = False):
        return self._mlp(x + self.attn(self.attn_norm(x)))

    def cached(self, x, rows, layer, latent, index, length):
        """Through the caches: ``layer`` is the block's place in its own
        group. Returns ``(x, rows, payload)`` — a KDA block replaces its
        layer of the state rows and has no payload, a latent block
        leaves the rows alone and gives its chunk's cache row."""
        h, payload = self.attn_norm(x), None
        if self.kind == KDA:
            out, rows = self.attn(h, rows=rows, layer=layer, length=length)
        else:
            out, payload = self.attn(h, cache=latent, index=index,
                                     layer=layer)
        return self._mlp(x + out), rows, payload


class KimiLinearPeriod(Module):
    """One whole period of the pattern, every layer an expert layer: the
    scan body."""

    def __init__(self, cfg: KimiLinearConfig, key=None):
        keys = rng.split_key(key, cfg.period)
        self.layers = tuple(KimiLinearBlock(cfg, kind, True, key=k)
                            for kind, k in zip(cfg.pattern, keys))

    def __call__(self, x, training: bool = False):
        for block in self.layers:
            x = block(x)
        return x

    def cached(self, x, rows, period, latent, index, length):
        """``period`` counts from the model's first (the head is period
        0): its KDA layers are ``period * (p - 1) + i`` of the state
        group, its latent layer ``period`` of the latent group."""
        n = len(self.layers) - 1
        for i, block in enumerate(self.layers):
            x, rows, pay = block.cached(
                x, rows, period * n + i if i < n else period, latent,
                index, length)
        return x, rows, pay


class KimiLinearForCausalLM(Module):
    """Decoder-only causal LM of the Kimi-Linear family (module
    docstring says what is supported)."""

    # names the expert layers record on a state tape, one value a
    # position: the serving engine sums them over live positions
    live_counts = ("moe_picks", "moe_picks_held")
    # the latent group's one row a token is shared by all heads (what
    # serves per-head K/V alone refuses this model)
    latent_cache = True

    def __init__(self, cfg: KimiLinearConfig, key=None):
        p, n_mid = cfg.period, cfg.whole_periods
        n_tail = cfg.num_layers - p * (1 + n_mid)
        keys = rng.split_key(key, 2 + p + n_mid + n_tail)
        dtype = jnp.dtype(cfg.dtype)
        kinds = cfg.kinds
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_init=Normal(0.0, cfg.init_std),
                               dtype=dtype, key=keys[0],
                               pspec=P("tp", "fsdp"))
        self.head = tuple(
            KimiLinearBlock(cfg, kinds[l], l >= cfg.first_k_dense,
                            key=keys[2 + l]) for l in range(p))
        self.blocks = ScannedBlocks(
            lambda i: KimiLinearPeriod(cfg, key=keys[2 + p + i]),
            n_mid) if n_mid else None
        first = p * (1 + n_mid)
        self.tail = tuple(
            KimiLinearBlock(cfg, kinds[first + i], True,
                            key=keys[2 + p + n_mid + i])
            for i in range(n_tail))
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                            dtype=dtype)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias=False,
                              weight_init=Normal(0.0, cfg.init_std),
                              dtype=dtype, key=keys[1],
                              pspec=P("fsdp", "tp"))
        self.config = cfg

    @property
    def cache_groups(self) -> tuple:
        """``(layers, kind)`` of each cache group, in ``init_cache``'s
        order: the latent layers (``None``: a full paged group) and the
        KDA layers (``"state"``: slot-indexed recurrent state)."""
        kinds = self.config.kinds
        return ((kinds.count(MLA), None), (kinds.count(KDA), "state"))

    def __call__(self, input_ids, training: bool = False):
        x = self.embed(input_ids)
        for block in self.head:
            x = block(x)
        if self.blocks is not None:
            x = self.blocks(x, training=training)
        for block in self.tail:
            x = block(x)
        return self.lm_head(self.norm(x))

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """``(latent leaves, StateCache)``: the latent group's one leaf
        ``[Lm, B, 1, S, W]`` in the model's (or the asked) dtype; the
        state group's float32 state ``[Lk, B, H, D, D]`` — as the
        family's own kernels keep it, whatever ``dtype`` — and its
        convolution tail (``tail_shape``) in the model's dtype."""
        from paddle_tpu.models._common import init_latent_cache
        from paddle_tpu.models.generation import StateCache
        cfg = self.config
        (lm, _), (lk, _) = self.cache_groups
        latent = init_latent_cache(
            lm, batch_size, max_len,
            cfg.kv_lora_rank + cfg.qk_rope_head_dim,
            jnp.dtype(dtype or cfg.dtype))
        H, D = cfg.kda_heads, cfg.kda_head_dim
        return latent, StateCache((
            jnp.zeros((lk, batch_size, H, D, D), jnp.float32),
            jnp.zeros((lk, batch_size)
                      + tail_shape(cfg.conv_kernel, 3 * H * D),
                      jnp.dtype(cfg.dtype))))

    def forward_with_cache(self, input_ids, cache, index):
        """Prefill / decode through the shared cache contract, a group
        at a time: ``cache`` is ``(latent, state)`` — contiguous leaves
        or a ``PagedCache``, and a ``StateCache``. The latent layers
        read their group by layer id and give their chunk rows back for
        one write; the state group's rows ride through head, scan and
        tail as a carry and come back whole (``StateCache`` again, with
        no length: what it holds is past the padding)."""
        from paddle_tpu.models._common import apply_cache_writes
        from paddle_tpu.models.generation import StateCache
        from paddle_tpu.nn.scan import _reemit_tape
        from paddle_tpu.nn.stateful import tape_call

        cfg = self.config
        latent, state = cache
        rows, length = state.rows, state.length
        p, n_mid = cfg.period, cfg.whole_periods
        x = self.embed(input_ids)
        pays = []

        def loop(blocks, x, rows, kda_at, mla_at):
            for block in blocks:
                mine = block.kind == KDA
                x, rows, pay = block.cached(
                    x, rows, kda_at if mine else mla_at, latent, index,
                    length)
                if mine:
                    kda_at += 1
                else:
                    mla_at += 1
                    pays.append(jax.tree_util.tree_map(
                        lambda r: r[None], pay))
            return x, rows

        x, rows = loop(self.head, x, rows, 0, 0)
        if n_mid:
            def period(block, carry, at):
                (y, r, pay), tape = tape_call(block.cached, *carry, at,
                                              latent, index, length)
                return (y, r), (pay, tape)

            (x, rows), (pay, tape) = self.blocks.scan_with(
                (x, rows), 1 + jnp.arange(n_mid), fn=period)
            _reemit_tape(tape)
            pays.append(pay)
        x, rows = loop(self.tail, x, rows, (1 + n_mid) * (p - 1),
                       1 + n_mid)
        payload = jax.tree_util.tree_map(
            lambda *r: jnp.concatenate(r, axis=0), *pays)
        return self.lm_head(self.norm(x)), (
            apply_cache_writes(latent, payload, index), StateCache(rows))

    def generate(self, input_ids, max_new_tokens: int, **kwargs):
        from paddle_tpu.models.generation import generate
        return generate(self, input_ids, max_new_tokens, **kwargs)

    def shard_for_inference(self, mesh):
        raise ValueError(
            "gen_mesh_tp with a recurrent state group beside a latent "
            "(MLA) cache is not implemented: neither the latent rows "
            "(one a token, shared by all heads) nor the slot-indexed "
            "state has a sharded form here (POOL_KV_SPEC covers per-head "
            "K/V pools); serve this model unsharded")
