"""Plain reference of the SmallThinker block (SmallThinker-21B-A3B,
4B-A0.6B): full attention layers with no position encoding and
sliding-window layers with RoPE mixed in one stack, every layer followed
by a ReGLU expert layer whose router reads the layer's input —
straightforward float32 ``jax.numpy`` at "highest" matmul precision, one
layer at a time, attention one row, one KV head's queries and one block
of queries at a time so that 13 824 positions fit. No kernels, no cache,
no batching. Imports nothing of the program and takes nothing it made:
the weights come from ``lib.weights`` by the program's leaf names, the
data from the seed.

One layer ``i`` for its input ``x`` [T, E]:

- router FIRST, from ``x`` itself (ahead of the norm and of attention):
  ``r = x W_r`` in float32; the ``top_k`` largest logits are the picks;
  gates = softmax over all experts at the picks, divided by their sum.
- ``a = RMSNorm(x)``; ``q = a W_q`` (H heads x D), ``k = a W_k``, ``v = a
  W_v`` (Hkv heads x D). ``rope_layout[i] == 1``: q and k rotated by
  theta, dims paired by halves, positions 0..T-1; ``0``: not rotated at
  all (NoPE).
- causal attention, scale D^-1/2, H / Hkv query heads a KV head;
  ``sliding_window_layout[i] == 1``: query ``t`` sees key ``j`` iff ``0 <=
  t - j < window``; ``0``: every ``j <= t``.
- ``h = x + o W_o``; ``m = RMSNorm(h)``; ``y = sum over picks of gate_e
  W_down,e (relu(W_gate,e m) * (W_up,e m))``; the layer gives ``h + y``.
  Every expert's output counts for exactly the tokens that picked it: no
  token is ever dropped.

Assumed (the config cannot tell; from the catalog's ``described_as``):
the router's input, and relu on the gate branch. Departures: the
secondary experts and the activation predictor of the family's runtime
are not in the config and are not built.

``quant`` is the control: every matmul operand except the router's and
the attention scores' passes through float8-e4m3 with a per-tensor
scale. ``no_window`` is the planted fault: every layer attends in full.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST

# leaf names as lib.weights knows them (the program's pytree paths):
# the stack is scanned over PERIODS of the layer pattern, so layer l is
# entry l % period of ``layers[...]`` drawn at index l // period
PERIOD = ".blocks.block.layers[{}]."
TOP = {"embed": ".embed.weight", "norm": ".norm.weight",
       "lm_head": ".lm_head.weight"}
QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_ffn: int
    vocab: int
    layers: int
    experts: int
    top_k: int
    window: int
    window_layout: tuple      # per layer: 1 = sliding window
    rope_layout: tuple        # per layer: 1 = rotated
    period: int               # layers of one repeat of the pattern
    rope_theta: float
    eps: float
    param_dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        L = cfg["num_hidden_layers"]
        win = tuple(int(v) for v in cfg["sliding_window_layout"][:L])
        rot = tuple(int(v) for v in cfg["rope_layout"][:L])
        period = next(n for n in range(1, L + 1)
                      if L % n == 0 and win == win[:n] * (L // n)
                      and rot == rot[:n] * (L // n))
        return cls(
            hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            expert_ffn=cfg["moe_ffn_hidden_size"], vocab=cfg["vocab_size"],
            layers=L, experts=cfg["moe_num_primary_experts"],
            top_k=cfg["moe_num_active_primary_experts"],
            window=cfg["sliding_window_size"], window_layout=win,
            rope_layout=rot, period=period,
            rope_theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]),
            param_dtype=cfg.get("torch_dtype", "bfloat16"))

    def layer_shapes(self) -> dict:
        """name -> (shape, stored dtype) of one layer's leaves."""
        E, D, dt = self.hidden, self.head_dim, self.param_dtype
        X, I = self.experts, self.expert_ffn
        return {
            "attn_norm.weight": ((E,), dt),
            "attn.wq.weight": ((E, self.heads * D), dt),
            "attn.wk.weight": ((E, self.kv_heads * D), dt),
            "attn.wv.weight": ((E, self.kv_heads * D), dt),
            "attn.wo.weight": ((self.heads * D, E), dt),
            "mlp_norm.weight": ((E,), dt),
            "moe.router": ((E, X), "float32"),
            "moe.w_gate": ((X, E, I), dt),
            "moe.w_up": ((X, E, I), dt),
            "moe.w_down": ((X, I, E), dt),
        }

    def top_shapes(self) -> dict:
        dt = self.param_dtype
        return {"embed": ((self.vocab, self.hidden), dt),
                "norm": ((self.hidden,), dt),
                "lm_head": ((self.hidden, self.vocab), dt)}


# ---------------------------------------------------------------------------
# the control's precision
# ---------------------------------------------------------------------------

def fp8(x):
    """float8-e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ident(x):
    return x


def _mm(spec, a, b, q=_ident):
    return jnp.einsum(spec, q(a), q(b), precision=HI)


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """Rotary embedding of [T, h, D] at positions 0..T-1, dims paired by
    halves (i with i + D/2)."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, window):
    """One row, one KV head: ``q`` [T, G, D] its G query heads, ``k``,
    ``v`` [T, D]. A block of queries at a time: the scores of one block
    are [G, block, T] float32. ``window`` None: every j <= t."""
    T, G, D = q.shape
    qb = min(QUERY_BLOCK, T)
    nb = -(-T // qb)
    qp = jnp.pad(q, ((0, nb * qb - T), (0, 0), (0, 0)))
    at = jnp.arange(T)

    def block(args):
        qs, t0 = args                                      # [qb, G, D]
        t = t0 + jnp.arange(qb)
        s = jnp.einsum("tgd,sd->gts", qs, k, precision=HI) * D ** -0.5
        seen = at[None, :] <= t[:, None]
        if window is not None:
            seen = seen & (t[:, None] - at[None, :] < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    out = jax.lax.map(block, (qp.reshape(nb, qb, G, D),
                              jnp.arange(nb) * qb))
    return out.reshape(nb * qb, G, D)[:T]


def attention(x, p, a: Arch, windowed: bool, rotated: bool, q=_ident):
    """``x + Attn(RMSNorm(x))`` over whole rows [B, T, E]."""
    B, T, _ = x.shape
    H, Hkv, D = a.heads, a.kv_heads, a.head_dim
    h = rms_norm(x, p["attn_norm.weight"], a.eps)
    qh = _mm("bte,ef->btf", h, p["attn.wq.weight"], q).reshape(B, T, H, D)
    kh = _mm("bte,ef->btf", h, p["attn.wk.weight"], q).reshape(B, T, Hkv, D)
    vh = _mm("bte,ef->btf", h, p["attn.wv.weight"], q).reshape(B, T, Hkv, D)
    window = a.window if windowed else None

    def row(args):
        qr, kr, vr = args
        if rotated:
            qr, kr = rope(qr, a.rope_theta), rope(kr, a.rope_theta)
        # query head g * (H / Hkv) + i reads KV head g
        qg = jnp.moveaxis(qr.reshape(T, Hkv, H // Hkv, D), 1, 0)
        out = jax.lax.map(
            lambda t: attend(t[0], t[1], t[2], window),
            (qg, jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(T, H * D)

    out = jax.lax.map(row, (qh, kh, vh))
    return x + _mm("btf,fe->bte", out, p["attn.wo.weight"], q)


def route(x, router, a: Arch):
    """``(expert ids, gates)`` [..., top_k] of every token from the
    layer's INPUT, float32 at "highest" whatever the control's
    precision."""
    logits = jnp.einsum("...e,ex->...x", x, router, precision=HI)
    expert = jax.lax.top_k(logits, a.top_k)[1]
    gate = jnp.take_along_axis(jax.nn.softmax(logits, -1), expert, -1)
    return expert, gate / jnp.sum(gate, -1, keepdims=True)


def reglu(h, gate, up, down, q=_ident):
    act = (jax.nn.relu(_mm("...e,ef->...f", h, gate, q))
           * _mm("...e,ef->...f", h, up, q))
    return _mm("...f,fe->...e", act, down, q)


def experts(m, expert, gate, p, a: Arch, q=_ident):
    """What the picked experts add for tokens ``m`` [T, E]: each
    expert's output at exactly the tokens that picked it, times its
    gate."""
    def one(total, args):
        e, wg, wu, wd = args
        g = jnp.sum(jnp.where(expert == e, gate, 0.0), -1)          # [T]
        return total + reglu(m, wg, wu, wd, q) * g[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (jnp.arange(a.experts), p["moe.w_gate"],
                         p["moe.w_up"], p["moe.w_down"]))[0]


def layer(x, p, a: Arch, windowed: bool, rotated: bool, q=_ident):
    expert, gate = route(x, p["moe.router"], a)         # the layer's input
    h = attention(x, p, a, windowed, rotated, q)
    m = rms_norm(h, p["mlp_norm.weight"], a.eps)
    y = jax.lax.map(lambda t: experts(t[0], t[1], t[2], p, a, q),
                    (m, expert, gate))
    return h + y


def head_logits(x, norm_w, head_w, a: Arch, q=_ident):
    return _mm("te,ev->tv", rms_norm(x, norm_w, a.eps), head_w, q)


# ---------------------------------------------------------------------------
# weights, by layer, from the seed
# ---------------------------------------------------------------------------

def layer_params(a: Arch, key, i: int, p) -> dict:
    """Entry ``i`` (a python int) of the scanned period, drawn at
    period index ``p`` (python int or traced): layer ``p * period +
    i``."""
    prefix = PERIOD.format(i)
    return {n: W.layer_leaf_f32(key, prefix + n, p, shape, dt)
            for n, (shape, dt) in a.layer_shapes().items()}


def top_param(a: Arch, key, which: str):
    shape, dt = a.top_shapes()[which]
    return W.layer_leaf_f32(key, TOP[which], 0, shape, dt)


def layer_kind(a: Arch, i: int, no_window: bool = False):
    """``(windowed, rotated)`` of entry ``i`` of the period."""
    return (bool(a.window_layout[i]) and not no_window,
            bool(a.rope_layout[i]))


def forward_logits(a: Arch, seed: int, ids, no_window: bool = False):
    """Float32 logits [B, T, V] of whole rows (the CPU tests' oracle)."""
    key = W.root_key(seed)
    x = top_param(a, key, "embed")[jnp.asarray(ids, jnp.int32)]
    for l in range(a.layers):
        i, p = l % a.period, l // a.period
        x = layer(x, layer_params(a, key, i, p), a,
                  *layer_kind(a, i, no_window))
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
    ``precision="fp8"`` (the control) or ``"no_window"`` (the fault:
    every layer full) the token is the one that other forward puts
    first at that position."""
    key = W.root_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    low = precision != "float32"
    quant = fp8 if precision == "fp8" else _ident

    @functools.partial(jax.jit, static_argnames=("i", "other"))
    def run_layer(key, x, i, p, other):
        # one program a layer kind and side: the period index is traced
        kind = layer_kind(a, i, other and precision == "no_window")
        return layer(x, layer_params(a, key, i, p), a, *kind,
                     quant if other else _ident)

    @jax.jit
    def gaps(key, x, x_low, ids):
        norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")
        R, S, E = x.shape
        nb = -(-S // QUERY_BLOCK)
        pad = nb * QUERY_BLOCK - S

        def block(args):
            # a block of positions at a time: a row's [S, V] logits at
            # the published vocabulary are 8 GB
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
        at = (l % a.period, l // a.period)
        x_low = run_layer(key, x_low, *at, True) if low else x_low
        x = run_layer(key, x, *at, False)
    g, m = (np.asarray(t, np.float32)
            for t in gaps(key, x, x_low if low else x, seqs))
    return ([g[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)],
            [m[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)])
