"""Plain reference of the Laguna block (poolside Laguna-S-2.1): sliding
window layers and full layers mixed, each kind with its own number of
query heads over 8 KV heads, a sigmoid gate on every head, YaRN on half
of each full layer's head and plain RoPE on all of a window layer's, a
leading dense layer, then expert layers with a shared expert —
straightforward float32 ``jax.numpy`` at "highest" matmul precision, one
layer at a time, attention one row, one KV head's queries and one block
of queries at a time so that 9 728 positions fit. No kernels, no cache,
no batching. Imports nothing of the program and takes nothing it made:
its masks, its YaRN frequencies and attention factor and its partial
rotation are its own; the weights come from ``lib.weights`` by the
program's leaf names, the data from the seed.

One layer ``l`` for its input ``x`` [T, E], ``Hq`` =
``num_attention_heads_per_layer[l]``, G = Hq / Hkv:

- ``a = RMSNorm(x)``; ``q = a W_q`` (Hq heads x D), ``k = a W_k``, ``v =
  a W_v`` (Hkv heads x D); no bias, no q/k norm.
- ``layer_types[l] == "full_attention"``: q and k rotated on their first
  ``D * partial_rotary_factor`` dims, pairs ``(i, i + r/2)``, by the YaRN
  frequencies of ``rope_parameters["full_attention"]`` (Hugging Face's
  ramp between the correction dims of ``beta_fast`` / ``beta_slow``),
  cos and sin times ``attention_factor``; the other dims untouched.
  ``"sliding_attention"``: all D dims by plain RoPE of its own theta.
- causal attention, scale D^-1/2, query head h reads KV head h // G; a
  sliding layer's query ``t`` sees key ``j`` iff ``0 <= t - j <
  sliding_window``.
- ``o_h = sigmoid((a W_g)_h) * o_h``; ``h = x + concat(o) W_o``.
- ``m = RMSNorm(h)``; a layer of ``mlp_only_layers`` gives ``h +
  W_d(silu(W_1 m) * W_3 m)``; the others ``h + s * sum_{e in S, held}
  g_e E_e(m) + E_sh(m)``: ``p = softmax(m W_r)`` over all experts in
  float32, S its ``num_experts_per_tok`` largest, ``g_e = p_e / sum_S
  p``, ``s = moe_routed_scaling_factor``; experts and the shared expert
  SwiGLU. Every held expert's output counts for exactly the tokens that
  picked it: dropless. What the experts held elsewhere would add is left
  out, as in the program.

Assumed (the config cannot tell): the gate reads the layer's normed
input; softmax routing; the shared expert has no gate of its own; no
q/k norm; the rotated dims are the first half of each head in Hugging
Face's pairing. Departure: the layers and the experts held elsewhere
(one chip's share of an expert-parallel deployment) are not built.

``fp8`` is the control: every matmul operand except the router's and
the attention scores' passes through float8-e4m3 with a per-tensor
scale. The planted faults: ``no_window`` (every layer full),
``no_head_gate`` (no gate), ``plain_rope_full`` (full layers rotated by
plain RoPE over all D dims at their own theta, no YaRN, no factor) and
``no_routed_scale`` (s = 1).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from .reference_smallthinker import HI, _ident, _mm, fp8, head_logits, rms_norm

# leaf names as lib.weights knows them (the program's pytree paths): the
# leading dense layers are blocks of their own, the rest is scanned over
# PERIODS of the layer pattern, so layer l is entry (l - dense) % period
# of ``layers[...]`` drawn at index (l - dense) // period
DENSE = ".dense[{}]."
PERIOD = ".blocks.block.layers[{}]."
TOP = {"embed": ".embed.weight", "norm": ".norm.weight",
       "lm_head": ".lm_head.weight"}
QUERY_BLOCK = 512
FAULTS = ("no_window", "no_head_gate", "plain_rope_full", "no_routed_scale")


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden: int
    heads: tuple              # query heads of each layer
    kv_heads: int
    head_dim: int
    dense_ffn: int
    expert_ffn: int
    shared_ffn: int
    vocab: int
    layers: int
    dense_layers: int         # the leading layers with a dense MLP
    experts: int              # routed over (the published count)
    held: tuple               # (first, count) held here
    top_k: int
    routed_scale: float
    window: int
    windowed: tuple           # per layer: 1 = sliding window
    period: int               # layers of one repeat after the dense ones
    full_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    attention_factor: float
    partial: float
    window_theta: float
    eps: float
    param_dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        L = cfg["num_hidden_layers"]
        win = tuple(int(t == "sliding_attention")
                    for t in cfg["layer_types"][:L])
        dense = 0
        while dense < L and dense in cfg["mlp_only_layers"]:
            dense += 1
        rest = win[dense:]
        period = next(n for n in range(1, len(rest) + 1)
                      if len(rest) % n == 0
                      and rest == rest[:n] * (len(rest) // n))
        full = cfg["rope_parameters"]["full_attention"]
        slide = cfg["rope_parameters"]["sliding_attention"]
        if not cfg["norm_topk_prob"] or slide["partial_rotary_factor"] != 1:
            raise ValueError("the reference renormalises the picked gates "
                             "and rotates all of a window layer's head")
        n = cfg["num_experts"]
        return cls(
            hidden=cfg["hidden_size"],
            heads=tuple(cfg["num_attention_heads_per_layer"][:L]),
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            dense_ffn=cfg["intermediate_size"],
            expert_ffn=cfg["moe_intermediate_size"],
            shared_ffn=cfg["shared_expert_intermediate_size"],
            vocab=cfg["vocab_size"], layers=L, dense_layers=dense,
            experts=(cfg["published"]["num_experts"] if "held" in cfg
                     else n),
            held=tuple(cfg.get("held", (0, n))),
            top_k=cfg["num_experts_per_tok"],
            routed_scale=float(cfg["moe_routed_scaling_factor"]),
            window=cfg["sliding_window"], windowed=win, period=period,
            full_theta=float(full["rope_theta"]),
            yarn_factor=float(full["factor"]),
            yarn_original=int(full["original_max_position_embeddings"]),
            yarn_beta_fast=float(full["beta_fast"]),
            yarn_beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"]),
            partial=float(full["partial_rotary_factor"]),
            window_theta=float(slide["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]),
            param_dtype=cfg.get("torch_dtype", "bfloat16"))

    def leaf_at(self, l: int) -> tuple:
        """``(name prefix, draw index)`` of layer ``l``'s leaves."""
        if l < self.dense_layers:
            return DENSE.format(l), 0
        j = l - self.dense_layers
        return PERIOD.format(j % self.period), j // self.period

    def layer_shapes(self, l: int) -> dict:
        """name -> (shape, stored dtype) of layer ``l``'s leaves."""
        E, D, dt = self.hidden, self.head_dim, self.param_dtype
        H = self.heads[l]
        out = {
            "attn_norm.weight": ((E,), dt),
            "attn.wq.weight": ((E, H * D), dt),
            "attn.wk.weight": ((E, self.kv_heads * D), dt),
            "attn.wv.weight": ((E, self.kv_heads * D), dt),
            "attn.wg.weight": ((E, H), dt),
            "attn.wo.weight": ((H * D, E), dt),
            "mlp_norm.weight": ((E,), dt),
        }
        if l < self.dense_layers:
            F_ = self.dense_ffn
            out.update({"mlp.gate.weight": ((E, F_), dt),
                        "mlp.up.weight": ((E, F_), dt),
                        "mlp.down.weight": ((F_, E), dt)})
            return out
        n, I, S = self.held[1], self.expert_ffn, self.shared_ffn
        out.update({
            "moe.router": ((E, self.experts), "float32"),
            "moe.w_gate": ((n, E, I), dt),
            "moe.w_up": ((n, E, I), dt),
            "moe.w_down": ((n, I, E), dt),
            "moe.shared_gate": ((E, S), dt),
            "moe.shared_up": ((E, S), dt),
            "moe.shared_down": ((S, E), dt),
        })
        return out

    def top_shapes(self) -> dict:
        dt = self.param_dtype
        return {"embed": ((self.vocab, self.hidden), dt),
                "norm": ((self.hidden,), dt),
                "lm_head": ((self.hidden, self.vocab), dt)}


# ---------------------------------------------------------------------------
# the mathematics (the control's float8, the matmuls at "highest", the
# norm and the head are SmallThinker's reference's)
# ---------------------------------------------------------------------------

def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """Hugging Face's YaRN frequencies of ``dim`` rotated dims:
    interpolated (over ``factor``) below the correction dim of
    ``beta_slow``, extrapolated above that of ``beta_fast``, a linear
    ramp between."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    return (1.0 / (factor * pos) * (1.0 - extrapolated)
            + 1.0 / pos * extrapolated)


def rotate(x, inv, factor: float):
    """``x`` [T, h, D] at positions 0..T-1: its first ``2 * len(inv)``
    dims rotated (pairs ``(i, i + len(inv))``), cos and sin times
    ``factor``; the other dims as they are."""
    T = x.shape[0]
    r = 2 * len(inv)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos = (jnp.cos(ang) * factor)[:, None]
    sin = (jnp.sin(ang) * factor)[:, None]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def rope_of(a: Arch, windowed: bool, fault=None):
    """``(inverse frequencies, factor)`` of a layer kind."""
    D = a.head_dim
    if windowed or fault == "plain_rope_full":
        theta = a.window_theta if windowed else a.full_theta
        return 1.0 / theta ** (np.arange(0, D, 2) / D), 1.0
    dim = int(D * a.partial)
    return (yarn_inv_freq(dim, a.full_theta, a.yarn_factor, a.yarn_original,
                          a.yarn_beta_fast, a.yarn_beta_slow),
            a.attention_factor)


def attend(q, k, v, window):
    """One row, one KV head: ``q`` [T, G, D] its G query heads, ``k``,
    ``v`` [T, D]. A block of queries at a time: a full layer's block
    scores [G, block, T] float32; a window layer's only the ``block +
    window`` keys that can reach it. ``window`` None: every j <= t."""
    T, G, D = q.shape
    qb = min(QUERY_BLOCK, T)
    nb = -(-T // qb)
    qp = jnp.pad(q, ((0, nb * qb - T), (0, 0), (0, 0)))
    if window is None:
        keys, back = (k, v), 0
    else:
        # keys t0 - window .. t0 + qb - 1 of a block at t0, padded on both
        # sides so that every block's slice is whole
        back = window
        keys = tuple(jnp.pad(x, ((window, nb * qb - T), (0, 0)))
                     for x in (k, v))

    def block(args):
        qs, t0 = args                                      # [qb, G, D]
        t = t0 + jnp.arange(qb)
        if window is None:
            ks, vs = keys
            at = jnp.arange(T)
        else:
            ks, vs = (jax.lax.dynamic_slice_in_dim(x, t0, qb + back)
                      for x in keys)
            at = t0 - back + jnp.arange(qb + back)
        s = jnp.einsum("tgd,sd->gts", qs, ks, precision=HI) * D ** -0.5
        seen = (at[None, :] <= t[:, None]) & (at[None, :] >= 0)
        if window is not None:
            seen = seen & (t[:, None] - at[None, :] < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, -1), vs,
                          precision=HI)

    out = jax.lax.map(block, (qp.reshape(nb, qb, G, D),
                              jnp.arange(nb) * qb))
    return out.reshape(nb * qb, G, D)[:T]


def attention(x, p, a: Arch, l: int, q=_ident, fault=None):
    """``x + Attn(RMSNorm(x))`` over whole rows [B, T, E]: layer ``l``'s
    kind and head count."""
    B, T, _ = x.shape
    H, Hkv, D = a.heads[l], a.kv_heads, a.head_dim
    windowed = bool(a.windowed[l])
    inv, factor = rope_of(a, windowed, fault)
    window = a.window if windowed and fault != "no_window" else None
    h = rms_norm(x, p["attn_norm.weight"], a.eps)
    qh = _mm("bte,ef->btf", h, p["attn.wq.weight"], q).reshape(B, T, H, D)
    kh = _mm("bte,ef->btf", h, p["attn.wk.weight"], q).reshape(B, T, Hkv, D)
    vh = _mm("bte,ef->btf", h, p["attn.wv.weight"], q).reshape(B, T, Hkv, D)
    gate = jax.nn.sigmoid(_mm("bte,eh->bth", h, p["attn.wg.weight"], q))

    def row(args):
        qr, kr, vr, gr = args
        qr, kr = rotate(qr, inv, factor), rotate(kr, inv, factor)
        # query head g * G + i reads KV head g
        qg = jnp.moveaxis(qr.reshape(T, Hkv, H // Hkv, D), 1, 0)
        out = jax.lax.map(
            lambda t: attend(t[0], t[1], t[2], window),
            (qg, jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        out = jnp.moveaxis(out, 0, 1).reshape(T, H, D)
        if fault != "no_head_gate":
            out = out * gr[..., None]
        return out.reshape(T, H * D)

    out = jax.lax.map(row, (qh, kh, vh, gate))
    return x + _mm("btf,fe->bte", out, p["attn.wo.weight"], q)


def swiglu(m, gate, up, down, q=_ident):
    act = (jax.nn.silu(_mm("...e,ef->...f", m, gate, q))
           * _mm("...e,ef->...f", m, up, q))
    return _mm("...f,fe->...e", act, down, q)


def route(m, router, a: Arch):
    """``(expert ids, gates)`` [..., top_k]: softmax over all experts,
    float32 at "highest" whatever the control's precision, the top_k
    largest, divided by their sum."""
    probs = jax.nn.softmax(
        jnp.einsum("...e,ex->...x", m, router, precision=HI), -1)
    gate, expert = jax.lax.top_k(probs, a.top_k)
    return expert, gate / jnp.sum(gate, -1, keepdims=True)


def routed_part(m, p, a: Arch, q=_ident, held=None):
    """What the held experts add for tokens ``m`` [T, E], before the
    routed scale: each held expert's output at exactly the tokens that
    picked it, times its gate. ``held`` ``(first, count)`` (default the
    Arch's) indexes the leaves' experts from ``first``."""
    first, count = a.held if held is None else held
    expert, gate = route(m, p["moe.router"], a)

    def one(total, args):
        e, wg, wu, wd = args
        g = jnp.sum(jnp.where(expert == first + e, gate, 0.0), -1)  # [T]
        return total + swiglu(m, wg, wu, wd, q) * g[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (jnp.arange(count), p["moe.w_gate"],
                         p["moe.w_up"], p["moe.w_down"]))[0]


def expert_mlp(m, p, a: Arch, q=_ident, fault=None):
    """The expert layer's output for tokens ``m`` [T, E]: the routed part
    scaled, and the shared expert."""
    s = 1.0 if fault == "no_routed_scale" else a.routed_scale
    return (s * routed_part(m, p, a, q)
            + swiglu(m, p["moe.shared_gate"], p["moe.shared_up"],
                     p["moe.shared_down"], q))


def layer(x, p, a: Arch, l: int, q=_ident, fault=None):
    h = attention(x, p, a, l, q, fault)
    m = rms_norm(h, p["mlp_norm.weight"], a.eps)
    if l < a.dense_layers:
        return h + swiglu(m, p["mlp.gate.weight"], p["mlp.up.weight"],
                          p["mlp.down.weight"], q)
    return h + jax.lax.map(lambda t: expert_mlp(t, p, a, q, fault), m)


# ---------------------------------------------------------------------------
# weights, by layer, from the seed
# ---------------------------------------------------------------------------

def layer_params(a: Arch, key, l: int, at=None) -> dict:
    """Layer ``l``'s leaves; ``at`` (traced or python int) overrides its
    draw index, for one program that serves every period."""
    prefix, index = a.leaf_at(l)
    index = index if at is None else at
    return {n: W.layer_leaf_f32(key, prefix + n, index, shape, dt)
            for n, (shape, dt) in a.layer_shapes(l).items()}


def top_param(a: Arch, key, which: str):
    shape, dt = a.top_shapes()[which]
    return W.layer_leaf_f32(key, TOP[which], 0, shape, dt)


def forward_logits(a: Arch, seed: int, ids, fault=None):
    """Float32 logits [B, T, V] of whole rows (the CPU tests' oracle)."""
    key = W.root_key(seed)
    x = top_param(a, key, "embed")[jnp.asarray(ids, jnp.int32)]
    for l in range(a.layers):
        x = layer(x, layer_params(a, key, l), a, l, fault=fault)
    norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")
    return jax.vmap(lambda r: head_logits(r, norm, head, a))(x)


# ---------------------------------------------------------------------------
# serving: logits of a padded sequence, layer by layer
# ---------------------------------------------------------------------------

def serve_logit_gaps(a: Arch, seed: int, seqs, spans,
                     precision: str = "float32"):
    """As ``reference.serve_logit_gaps``: ``seqs`` [R, S] int32, each
    row a prompt followed by the tokens served for it, zero-padded
    (causal, so the sequence never sees the padding); ``spans[r]`` is
    ``(prompt length, prompt + served length)``. Returns two lists, for
    each row a float32 array over served positions: the gap ``best
    logit - logit of the served token`` under the float32 forward, and
    the reference's own margin ``best - second best`` there. With
    ``precision="fp8"`` (the control) or one of :data:`FAULTS` the token
    is the one that other forward puts first at that position."""
    key = W.root_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    low = precision != "float32"
    quant = fp8 if precision == "fp8" else _ident
    fault = precision if precision in FAULTS else None

    def program_of(l):
        """The layer whose program serves layer ``l``: a dense layer's
        own, else the first of its place in the period."""
        if l < a.dense_layers:
            return l
        return a.dense_layers + (l - a.dense_layers) % a.period

    @functools.partial(jax.jit, static_argnames=("l", "other"))
    def run_layer(key, x, l, at, other):
        # one program a place in the period and side: the draw index is
        # traced
        return layer(x, layer_params(a, key, l, at), a, l,
                     quant if other else _ident, fault if other else None)

    @jax.jit
    def gaps(key, x, x_low, ids):
        norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")
        R, S, E = x.shape
        nb = -(-S // QUERY_BLOCK)
        pad = nb * QUERY_BLOCK - S

        def block(args):
            xr, xl, nxt = args
            lg = head_logits(xr, norm, head, a)
            tok = (jnp.argmax(head_logits(xl, norm, head, a, quant), -1)
                   if low else nxt)
            top2 = jax.lax.top_k(lg, 2)[0]
            return (top2[:, 0] - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], top2[:, 0] - top2[:, 1])

        def blocks(t):              # [R, S, ...] -> [R * nb, block, ...]
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape((R * nb, QUERY_BLOCK) + t.shape[2:])

        # the gap at position i is of the token at i + 1
        g, m = jax.lax.map(block, (blocks(x), blocks(x_low),
                                   blocks(jnp.roll(ids, -1, axis=1))))
        return (g.reshape(R, -1)[:, :S - 1], m.reshape(R, -1)[:, :S - 1])

    x = jax.jit(lambda key, ids: top_param(a, key, "embed")[ids])(key, seqs)
    x_low = x
    for l in range(a.layers):
        at = a.leaf_at(l)[1]
        x_low = (run_layer(key, x_low, program_of(l), at, True) if low
                 else x_low)
        x = run_layer(key, x, program_of(l), at, False)
    g, m = (np.asarray(t, np.float32)
            for t in gaps(key, x, x_low if low else x, seqs))
    return ([g[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)],
            [m[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)])
