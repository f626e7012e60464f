"""SmallThinker decoder (PowerInfer SmallThinker-21B-A3B / 4B-A0.6B):
layers of two kinds in one stack — full attention with **no position
encoding** (NoPE) and sliding-window attention with RoPE — each followed
by an expert layer whose router reads the layer's **input**.

What "supported" covers: the **serving path** — ``init_cache`` /
``forward_with_cache`` under ``generate()`` and the paged
``GenerationEngine`` — and the full forward ``__call__``. Not built: the
"secondary experts" and the activation predictor of the family's
on-device runtime (they are not in the published config); no training
recipe is claimed.

One layer, for its input ``x`` (the residual stream):

1. router first: ``x @ W_r`` in float32, from the layer's input, ahead
   of the input norm and of attention; the ``top_k`` largest logits are
   picked, gates are the softmax over all experts at the picks divided
   by their sum.
2. ``a = RMSNorm(x)``; GQA projections ``q, k, v`` with an explicit
   ``head_dim`` (28 x 128 is wider than the hidden 2560), no bias, no
   q/k norm. A *window* layer rotates q and k (RoPE, pairs by halves); a
   *full* layer does not rotate at all.
3. causal attention; a window layer's query at ``t`` sees key ``j`` iff
   ``0 <= t - j < sliding_window``.
4. ``h = x + o @ W_o``; ``m = RMSNorm(h)``; ``y = sum_picks gate_e *
   W_down,e(relu(W_gate,e m) * (W_up,e m))`` (ReGLU); output ``h + y``.

The layer kinds repeat with a fixed period (``window_pattern`` /
``rope_pattern``, one period each: ``(0, 1, 1, 1)`` for the published
models). The stack is ONE ``ScannedBlocks`` over **periods** — the scan
body is the period's layers in a python loop, so 52 layers compile as
four — and the cache is a tuple of **groups**, one a layer kind
(``cache_groups``): the full layers' K/V leaves ``[Lf, B, Hkv, S, D]``
and the window layers' ``[Lw, B, Hkv, S, D]``. The contiguous cache of
``generate()`` holds every position in both and masks; the paged engine
gives each group a page pool of its own and lets a stream's window
group free the pages that have slid out (``serving/engine.py``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common import Embedding, Linear
from paddle_tpu.nn.initializer import Normal
from paddle_tpu.nn.moe import MoEMLP
from paddle_tpu.nn.norm import RMSNorm
from paddle_tpu.nn.scan import ScannedBlocks

__all__ = ["SmallThinkerConfig", "SmallThinkerForCausalLM"]


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 64
    num_experts_per_tok: int = 6
    sliding_window: int = 4096
    # one period of ``sliding_window_layout`` / ``rope_layout`` (1 = a
    # window layer / a rotated layer); the layers repeat it
    window_pattern: tuple = (0, 1, 1, 1)
    rope_pattern: tuple = (0, 1, 1, 1)
    max_seq_len: int = 16384
    rope_base: float = 1.5e6
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    init_std: float = 0.02

    def __post_init__(self):
        for name in ("window_pattern", "rope_pattern"):   # JSON lists hash
            object.__setattr__(self, name,
                               tuple(int(v) for v in getattr(self, name)))
        n = len(self.window_pattern)
        if (n == 0 or len(self.rope_pattern) != n or self.num_layers % n
                or self.num_heads % self.num_kv_heads):
            raise ValueError(
                f"{self.num_layers} layers must repeat window_pattern "
                f"{self.window_pattern} and rope_pattern {self.rope_pattern} "
                f"(one period each, equally long) whole, and "
                f"{self.num_kv_heads} KV heads divide {self.num_heads}")

    @property
    def periods(self) -> int:
        return self.num_layers // len(self.window_pattern)

    @property
    def groups(self) -> tuple:
        """The cache's layer groups, full layers first: ``(window or
        None, layers of that kind in one period)`` for each kind the
        pattern holds."""
        out = []
        for kind, window in ((0, None), (1, self.sliding_window)):
            n = sum(1 for k in self.window_pattern if bool(k) == bool(kind))
            if n:
                out.append((window, n))
        return tuple(out)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_layers=8,
                    num_heads=4, num_kv_heads=2, head_dim=16,
                    moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, sliding_window=32,
                    max_seq_len=256, dtype="float32")
        base.update(kw)
        return cls(**base)


class SmallThinkerAttention(Module):
    """GQA with an explicit head size; ``window`` (positions, or None)
    and ``rope`` are what the layer's kind fixes."""

    def __init__(self, cfg: SmallThinkerConfig, window, rope: bool,
                 key=None):
        keys = rng.split_key(key, 4)
        E, D = cfg.hidden_size, cfg.head_dim
        dtype = jnp.dtype(cfg.dtype)
        init = Normal(0.0, cfg.init_std)
        out_init = Normal(0.0, cfg.init_std / math.sqrt(2 * cfg.num_layers))

        def lin(i, n_in, n_out, w, spec):
            return Linear(n_in, n_out, bias=False, weight_init=w,
                          dtype=dtype, key=keys[i], pspec=spec)

        self.wq = lin(0, E, cfg.num_heads * D, init, P("fsdp", "tp"))
        self.wk = lin(1, E, cfg.num_kv_heads * D, init, P("fsdp", "tp"))
        self.wv = lin(2, E, cfg.num_kv_heads * D, init, P("fsdp", "tp"))
        self.wo = lin(3, cfg.num_heads * D, E, out_init, P("tp", "fsdp"))
        self.num_heads, self.num_kv_heads = cfg.num_heads, cfg.num_kv_heads
        self.head_dim = D
        self.rope_base = float(cfg.rope_base)
        self.window = None if window is None else int(window)
        self.rope = bool(rope)

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
            if self.rope:
                positions = jnp.arange(T)
                if index is not None:
                    positions = positions + index
                cos, sin = F.rotary_embedding(positions, D, self.rope_base)
                q = F.apply_rotary(q, cos, sin)
                k = F.apply_rotary(k, cos, sin)
            if cache is None:
                out = F.scaled_dot_product_attention(
                    q, k, v, window_mask(T, self.window), causal=True)
                return self.wo(out.reshape(B, T, H * D))
            out, payload = cached_attention(q, k, v, cache, index,
                                            layer=layer, window=self.window)
            return self.wo(out.reshape(B, T, H * D)), payload


class SmallThinkerBlock(Module):
    """One decoder layer of a given kind: the router reads the block's
    input, the experts the normed post-attention stream."""

    def __init__(self, cfg: SmallThinkerConfig, window, rope: bool,
                 key=None):
        k1, k2 = rng.split_key(key)
        dtype = jnp.dtype(cfg.dtype)
        self.attn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                 dtype=dtype)
        self.attn = SmallThinkerAttention(cfg, window, rope, key=k1)
        self.mlp_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps,
                                dtype=dtype)
        self.moe = MoEMLP(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            top_k=cfg.num_experts_per_tok, init_std=cfg.init_std,
            num_layers=cfg.num_layers, dtype=dtype,
            held=(0, cfg.num_experts), norm_topk=True, act="relu", key=k2)

    def __call__(self, x, layer=0, *, cache=None, index=None):
        payload = None
        attn_out = self.attn(self.attn_norm(x), cache=cache, index=index,
                             layer=layer)
        if cache is not None:
            attn_out, payload = attn_out
        h = x + attn_out
        out = h + self.moe(self.mlp_norm(h), route_from=x)[0]
        return out if payload is None else (out, payload)


class SmallThinkerPeriod(Module):
    """One period of the layer pattern: the scan body. With caches
    (one a group) each layer reads its group's cache at its own layer id
    there, and the period gives back its layers' chunk rows by group."""

    def __init__(self, cfg: SmallThinkerConfig, key=None):
        keys = rng.split_key(key, len(cfg.window_pattern))
        self.layers = tuple(
            SmallThinkerBlock(cfg, cfg.sliding_window if w else None,
                              bool(r), key=k)
            for w, r, k in zip(cfg.window_pattern, cfg.rope_pattern, keys))
        # group of each layer, and its place among the period's layers
        # of that group
        windows = [g[0] for g in cfg.groups]
        group = [windows.index(cfg.sliding_window if w else None)
                 for w in cfg.window_pattern]
        self.place = tuple((g, group[:i].count(g))
                           for i, g in enumerate(group))
        self.per_group = tuple(g[1] for g in cfg.groups)

    def __call__(self, x, training: bool = False):
        for block in self.layers:
            x = block(x)
        return x

    def cached(self, x, period, caches, index):
        rows = [[] for _ in self.per_group]
        for block, (g, j) in zip(self.layers, self.place):
            x, pay = block(x, period * self.per_group[g] + j,
                           cache=caches[g], index=index)
            rows[g].append(pay)
        return x, tuple(
            jax.tree_util.tree_map(lambda *p: jnp.stack(p), *r)
            for r in rows)


class SmallThinkerForCausalLM(Module):
    """Decoder-only causal LM of the SmallThinker family (module
    docstring says what is supported)."""

    # names the expert layers record on a state tape, one value a
    # position: the serving engine sums them over live positions
    live_counts = ("moe_picks", "moe_picks_held")

    def __init__(self, cfg: SmallThinkerConfig, key=None):
        keys = rng.split_key(key, 2 + cfg.periods)
        dtype = jnp.dtype(cfg.dtype)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_init=Normal(0.0, cfg.init_std),
                               dtype=dtype, key=keys[0],
                               pspec=P("tp", "fsdp"))
        self.blocks = ScannedBlocks(
            lambda i: SmallThinkerPeriod(cfg, key=keys[2 + i]), cfg.periods)
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
        return tuple((n * self.config.periods, window)
                     for window, n in self.config.groups)

    def __call__(self, input_ids, training: bool = False):
        x = self.blocks(self.embed(input_ids), training=training)
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
        at a time: ``cache`` is one entry a group (contiguous leaves or
        a ``PagedCache``), the scan over periods carries the layers'
        chunk rows and state tape out, and one write a group lands
        them."""
        from paddle_tpu.models._common import apply_cache_writes
        from paddle_tpu.nn.scan import _reemit_tape
        from paddle_tpu.nn.stateful import tape_call

        def period(block, carry, p):
            (y, pay), tape = tape_call(block.cached, carry, p, cache, index)
            return y, (pay, tape)

        x, (pay, tape) = self.blocks.scan_with(
            self.embed(input_ids), jnp.arange(self.config.periods),
            fn=period)
        _reemit_tape(tape)
        # [periods, layers a period, ...] -> [layers of the group, ...]
        pay = jax.tree_util.tree_map(
            lambda r: r.reshape((-1,) + r.shape[2:]), pay)
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
