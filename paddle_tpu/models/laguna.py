"""Laguna decoder (poolside Laguna-S-2.1, Laguna-XS.2): sliding-window
and full attention layers mixed, each kind with **its own number of
query heads** over the same KV heads, a sigmoid **gate on every head's
output**, full layers rotated by YaRN on half of each head and window
layers by plain RoPE on all of it, a leading dense layer, then expert
layers with a shared expert and a routed scale.

What "supported" covers: the **serving path** — ``init_cache`` /
``forward_with_cache`` under ``generate()`` and the paged
``GenerationEngine`` — for a model whose expert layers may hold **a
share of the routed experts** (``held``; one chip of an expert-parallel
deployment, without the exchange), and the full forward ``__call__``.
No training recipe is claimed.

One layer, for its input ``x`` (the residual stream), ``Hq`` the layer
kind's query heads (``num_heads`` full, ``window_heads`` window), G =
Hq / Hkv:

1. ``a = RMSNorm(x)``; ``q = a W_q`` [Hq, D], ``k = a W_k``, ``v = a
   W_v`` [Hkv, D]; no bias, no q/k norm.
2. a *full* layer rotates the first ``D * partial_rotary_factor`` dims
   of q and k (pairs ``(i, i + r/2)``) by YaRN frequencies
   (``deepseek_v3.yarn_inv_freq``), cos and sin both times
   ``rope_attention_factor``; the other dims pass unrotated and
   unscaled. A *window* layer rotates all D dims by plain RoPE
   (``window_rope_base``).
3. causal attention, scale D^-1/2, query head h reads KV head h // G; a
   window layer's query at ``t`` sees key ``j`` iff ``0 <= t - j <
   sliding_window``.
4. ``o_h = sigmoid((a W_g)_h) * o_h`` — one scalar a head a position
   (``W_g`` [E, Hq]) — then ``h = x + concat_h(o_h) W_o``.
5. ``m = RMSNorm(h)``; the leading ``first_k_dense`` layers (full
   attention) give ``h + W_d(silu(W_1 m) * W_3 m)``; the others ``h +
   routed_scale * sum_picks g_e E_e(m) + E_sh(m)`` — softmax over all
   experts in float32, the ``top_k`` largest, gates divided by their
   sum (``nn.moe.MoEMLP``'s held form: ``norm_topk``, ``routed_scale``,
   ``shared_size``).

The leading dense layers sit outside the scan (``dense``, a tuple of
blocks); the rest is ONE ``ScannedBlocks`` over **periods** of
``window_pattern`` (``(1, 1, 1, 0)`` published: three window layers,
then a full one), the scan body the period's layers in a python loop.
The cache is a tuple of **groups**, one a layer kind
(``cache_groups``): the full layers' K/V leaves ``[Lf, B, Hkv, S, D]``
— the dense layers first, then each period's full layers — and the
window layers' ``[Lw, B, Hkv, S, D]``. The paged engine gives each
group a page pool of its own, and the window group's rows let go of
what has slid out (``serving/engine.py``). The window layers' paged
decode kernel is named ``ptpu_paged_decode_attn_window`` in the device
trace, so its time can be read apart from the full layers'
``ptpu_paged_decode_attn``; named scopes ``attn/full``,
``attn/window`` and ``attn/gate`` mark the step's attention.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.models.deepseek_v3 import yarn_inv_freq
from paddle_tpu.models.llama import LlamaMLP
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common import Embedding, Linear
from paddle_tpu.nn.initializer import Normal
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.nn.norm import RMSNorm
from paddle_tpu.nn.scan import ScannedBlocks

__all__ = ["LagunaConfig", "LagunaForCausalLM"]

WINDOW_KERNEL = "ptpu_paged_decode_attn_window"


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288       # the leading dense layers' SwiGLU
    num_layers: int = 48
    num_heads: int = 48                  # query heads of a full layer
    window_heads: int = 72               # query heads of a window layer
    num_kv_heads: int = 8
    head_dim: int = 128
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_tok: int = 10
    routed_scale: float = 2.5
    # the routed experts this chip holds, ``(first, count)``; None = all
    held: tuple | None = None
    first_k_dense: int = 1               # leading dense layers, full attention
    sliding_window: int = 512
    # one period of the layers after the dense ones (1 = a window layer)
    window_pattern: tuple = (1, 1, 1, 0)
    rope_base: float = 500000.0          # full layers: YaRN
    rope_factor: float = 128.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.4852030263919618
    partial_rotary_factor: float = 0.5
    window_rope_base: float = 10000.0    # window layers: plain RoPE
    max_seq_len: int = 16384
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    init_std: float = 0.02

    def __post_init__(self):
        object.__setattr__(self, "window_pattern",        # JSON lists hash
                           tuple(int(v) for v in self.window_pattern))
        if self.held is not None:
            object.__setattr__(self, "held", tuple(int(v) for v in self.held))
        n = len(self.window_pattern)
        rest = self.num_layers - self.first_k_dense
        if (n == 0 or self.first_k_dense < 0 or rest <= 0 or rest % n
                or self.num_heads % self.num_kv_heads
                or self.window_heads % self.num_kv_heads):
            raise ValueError(
                f"{self.num_layers} layers less {self.first_k_dense} dense "
                f"ones must repeat window_pattern {self.window_pattern} "
                f"whole, and {self.num_kv_heads} KV heads divide "
                f"{self.num_heads} and {self.window_heads} query heads")
        if self.head_dim * self.partial_rotary_factor % 2:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"{self.head_dim}-wide head is not a whole number of pairs")

    @property
    def periods(self) -> int:
        return (self.num_layers - self.first_k_dense) // len(
            self.window_pattern)

    @property
    def groups(self) -> tuple:
        """``(window or None, layers of that kind in one period)``, full
        layers first (the dense layers are full ones outside the
        periods)."""
        nw = sum(self.window_pattern)
        return ((None, len(self.window_pattern) - nw),
                (self.sliding_window, nw))

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_layers=9, num_heads=4, window_heads=6,
                    num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, sliding_window=32,
                    rope_original_max=32, max_seq_len=256, dtype="float32")
        base.update(kw)
        return cls(**base)


class LagunaAttention(Module):
    """GQA of one layer kind (``window`` None: a full layer) with a
    sigmoid gate on each head's output."""

    def __init__(self, cfg: LagunaConfig, window, key=None):
        keys = rng.split_key(key, 5)
        E, D = cfg.hidden_size, cfg.head_dim
        H = cfg.num_heads if window is None else cfg.window_heads
        dtype = jnp.dtype(cfg.dtype)
        init = Normal(0.0, cfg.init_std)
        out_init = Normal(0.0, cfg.init_std / math.sqrt(2 * cfg.num_layers))

        def lin(i, n_in, n_out, w, spec):
            return Linear(n_in, n_out, bias=False, weight_init=w,
                          dtype=dtype, key=keys[i], pspec=spec)

        self.wq = lin(0, E, H * D, init, P("fsdp", "tp"))
        self.wk = lin(1, E, cfg.num_kv_heads * D, init, P("fsdp", "tp"))
        self.wv = lin(2, E, cfg.num_kv_heads * D, init, P("fsdp", "tp"))
        self.wg = lin(3, E, H, init, P("fsdp", "tp"))
        self.wo = lin(4, H * D, E, out_init, P("tp", "fsdp"))
        self.num_heads, self.num_kv_heads, self.head_dim = (
            H, cfg.num_kv_heads, D)
        self.window = None if window is None else int(window)
        self.cfg = cfg

    def rope_tables(self, positions):
        """``(cos, sin, rotated dims)`` of this kind at ``positions``."""
        c = self.cfg
        if self.window is not None:
            cos, sin = F.rotary_embedding(positions, c.head_dim,
                                          c.window_rope_base)
            return cos, sin, c.head_dim
        dim = int(c.head_dim * c.partial_rotary_factor)
        inv = yarn_inv_freq(dim, c.rope_base, c.rope_factor,
                            c.rope_original_max, c.rope_beta_fast,
                            c.rope_beta_slow)
        ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
        m = c.rope_attention_factor
        return jnp.cos(ang) * m, jnp.sin(ang) * m, dim

    def __call__(self, x, cache=None, index=None, layer=0):
        """``(out, payload)`` with a cache (the shared cache contract),
        ``out`` without."""
        from paddle_tpu.models._common import cached_attention, window_mask

        B, T, _ = x.shape
        H, Hkv, D = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("attn/full" if self.window is None
                             else "attn/window"):
            q = self.wq(x).reshape(B, T, H, D)
            k = self.wk(x).reshape(B, T, Hkv, D)
            v = self.wv(x).reshape(B, T, Hkv, D)
            positions = jnp.arange(T)
            if index is not None:
                positions = positions + index
            cos, sin, dim = self.rope_tables(positions)
            q = F.apply_partial_rotary(q, cos, sin, dim)
            k = F.apply_partial_rotary(k, cos, sin, dim)
            payload = None
            if cache is None:
                out = F.scaled_dot_product_attention(
                    q, k, v, window_mask(T, self.window), causal=True)
            else:
                out, payload = cached_attention(
                    q, k, v, cache, index, layer=layer, window=self.window,
                    kernel_name=None if self.window is None
                    else WINDOW_KERNEL)
        with jax.named_scope("attn/gate"):
            gate = jax.nn.sigmoid(self.wg(x).astype(jnp.float32))
            out = out * gate[..., None].astype(out.dtype)
        out = self.wo(out.reshape(B, T, H * D))
        return out if cache is None else (out, payload)


class LagunaBlock(Module):
    """One decoder layer of a given kind: a dense SwiGLU (``moe=False``)
    or the expert layer after the gated attention."""

    def __init__(self, cfg: LagunaConfig, window, moe: bool, key=None):
        k1, k2 = rng.split_key(key)
        dtype = jnp.dtype(cfg.dtype)
        self.attn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                 dtype=dtype)
        self.attn = LagunaAttention(cfg, window, key=k1)
        self.mlp_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                dtype=dtype)
        if moe:
            self.moe = MoEMLP(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.num_experts_per_tok, init_std=cfg.init_std,
                num_layers=cfg.num_layers, dtype=dtype,
                routed_scale=cfg.routed_scale,
                shared_size=cfg.shared_expert_intermediate_size,
                held=cfg.held or (0, cfg.num_experts), norm_topk=True,
                key=k2)
        else:
            self.mlp = LlamaMLP(cfg, key=k2)

    def __call__(self, x, layer=0, *, cache=None, index=None):
        payload = None
        attn_out = self.attn(self.attn_norm(x), cache=cache, index=index,
                             layer=layer)
        if cache is not None:
            attn_out, payload = attn_out
        h = x + attn_out
        m = self.mlp_norm(h)
        out = h + (self.moe(m)[0] if hasattr(self, "moe") else self.mlp(m))
        return out if payload is None else (out, payload)


class LagunaPeriod(Module):
    """One period of the layer pattern: the scan body. With caches (one
    a group) each layer reads its group's cache at its own layer id
    there — the full group's counted after the dense layers — and the
    period gives back its layers' chunk rows by group."""

    def __init__(self, cfg: LagunaConfig, key=None):
        keys = rng.split_key(key, len(cfg.window_pattern))
        self.layers = tuple(
            LagunaBlock(cfg, cfg.sliding_window if w else None, True, key=k)
            for w, k in zip(cfg.window_pattern, keys))
        group = [int(bool(w)) for w in cfg.window_pattern]
        self.place = tuple((g, group[:i].count(g))
                           for i, g in enumerate(group))
        self.per_group = tuple(n for _, n in cfg.groups)
        self.first = (cfg.first_k_dense, 0)

    def __call__(self, x, training: bool = False):
        for block in self.layers:
            x = block(x)
        return x

    def cached(self, x, period, caches, index):
        rows = [[] for _ in self.per_group]
        for block, (g, j) in zip(self.layers, self.place):
            x, pay = block(x, self.first[g] + period * self.per_group[g] + j,
                           cache=caches[g], index=index)
            rows[g].append(pay)
        return x, tuple(
            jax.tree_util.tree_map(lambda *p: jnp.stack(p), *r)
            for r in rows)


class LagunaForCausalLM(Module):
    """Decoder-only causal LM of the Laguna family (module docstring
    says what is supported)."""

    # names the expert layers record on a state tape, one value a
    # position: the serving engine sums them over live positions
    live_counts = ("moe_picks", "moe_picks_held")

    def __init__(self, cfg: LagunaConfig, key=None):
        kd = cfg.first_k_dense
        keys = rng.split_key(key, 2 + kd + cfg.periods)
        dtype = jnp.dtype(cfg.dtype)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_init=Normal(0.0, cfg.init_std),
                               dtype=dtype, key=keys[0],
                               pspec=P("tp", "fsdp"))
        self.dense = tuple(LagunaBlock(cfg, None, False, key=keys[2 + i])
                           for i in range(kd))
        self.blocks = ScannedBlocks(
            lambda i: LagunaPeriod(cfg, key=keys[2 + kd + i]), cfg.periods)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                            dtype=dtype)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias=False,
                              weight_init=Normal(0.0, cfg.init_std),
                              dtype=dtype, key=keys[1],
                              pspec=P("fsdp", "tp"))
        self.config = cfg

    @property
    def cache_groups(self) -> tuple:
        """``(layers, window or None)`` of each cache group, in the order
        ``init_cache`` gives them: what a paged engine sizes a pool and a
        table row a group from."""
        c = self.config
        (_, nf), (window, nw) = c.groups
        return ((c.first_k_dense + nf * c.periods, None),
                (nw * c.periods, window))

    def __call__(self, input_ids, training: bool = False):
        x = self.embed(input_ids)
        for block in self.dense:
            x = block(x)
        x = self.blocks(x, training=training)
        return self.lm_head(self.norm(x))

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """One ``init_kv_cache`` pair a group; every group holds
        ``max_len`` positions (a window layer masks what has slid out)."""
        from paddle_tpu.models._common import init_kv_cache
        cfg = self.config
        dtype = jnp.dtype(dtype or cfg.dtype)
        if dtype == jnp.int8:
            raise ValueError(
                "an int8 cache beside layer groups is not implemented: the "
                "window group's rows have no scale planes of their own; "
                "use a float dtype")
        return tuple(init_kv_cache(layers, batch_size, max_len,
                                   cfg.num_kv_heads, cfg.head_dim, dtype)
                     for layers, _ in self.cache_groups)

    def forward_with_cache(self, input_ids, cache, index):
        """Prefill / decode through the shared cache contract, a group
        at a time: the dense layers read the full group at ids ``0 ..
        first_k_dense - 1``, the scan over periods carries the other
        layers' chunk rows and state tape out, and one write a group
        lands them."""
        from paddle_tpu.models._common import apply_cache_writes
        from paddle_tpu.nn.scan import _reemit_tape
        from paddle_tpu.nn.stateful import tape_call

        x = self.embed(input_ids)
        dense = []
        for i, block in enumerate(self.dense):
            x, pay = block(x, i, cache=cache[0], index=index)
            dense.append(pay)

        def period(block, carry, p):
            (y, pay), tape = tape_call(block.cached, carry, p, cache, index)
            return y, (pay, tape)

        x, (pay, tape) = self.blocks.scan_with(
            x, jnp.arange(self.config.periods), fn=period)
        _reemit_tape(tape)
        # [periods, layers a period, ...] -> [layers of the group, ...]
        pay = [jax.tree_util.tree_map(
            lambda r: r.reshape((-1,) + r.shape[2:]), p) for p in pay]
        if dense:
            pay[0] = jax.tree_util.tree_map(
                lambda r, *d: jnp.concatenate([jnp.stack(d), r]),
                pay[0], *dense)
        cache = tuple(apply_cache_writes(c, p, index)
                      for c, p in zip(cache, pay))
        return self.lm_head(self.norm(x)), cache

    def generate(self, input_ids, max_new_tokens: int, **kwargs):
        from paddle_tpu.models.generation import generate
        return generate(self, input_ids, max_new_tokens, **kwargs)

    def shard_for_inference(self, mesh):
        raise ValueError(
            "gen_mesh_tp with layer groups (window and full attention "
            "mixed) is not implemented: the window group's pool and table "
            "row have no sharded form (POOL_KV_SPEC covers one pool); "
            "serve this model unsharded")
