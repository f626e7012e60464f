"""Plain reference of the SDAR block-diffusion stack (SDAR-30B-A3B,
``model_type: sdar_moe``): straightforward float32 ``jax.numpy`` at
"highest" matmul precision, one layer at a time, attention one row and
one KV head's queries at a time. No kernels, no cache, no paging. Imports
nothing of the program and takes nothing it made: the weights come from
``lib.weights`` by the program's leaf names, the data from the seed, and
the attention mask from absolute positions in this file's own code.

One layer for its input ``x`` [T, E] at absolute positions ``pos``:

- ``a = RMSNorm(x)``; ``q = a W_q`` (H heads x D), ``k = a W_k``, ``v = a
  W_v`` (Hkv heads x D); every query head through one RMSNorm of D and
  every key head through another (head-wise q/k norm, eps of the config);
  q and k rotated by theta at ``pos``, dims paired by halves.
- attention, scale D^-1/2, H / Hkv query heads a KV head, under the
  block-causal mask ``M[t, j] = floor(pos_j / B) <= floor(pos_t / B)``:
  a position sees every earlier block and its whole own block.
- ``h = x + o W_o``; ``m = RMSNorm(h)``; softmax over all experts of ``m
  W_r`` (float32), the ``top_k`` largest picked, gates divided by their
  sum; ``y = sum over picks of gate_e W_down,e (SiLU(W_gate,e m) *
  (W_up,e m))`` — dropless: every expert counts for exactly the tokens
  that picked it. The layer gives ``h + y``.

The head is ``W_head RMSNorm(x_L)``, and the logits at a position are
that position's own token's (no shift).

:func:`replay` recomputes, for each compared block and each of its
denoising steps, the forward over everything before the block plus the
block as it stood then (the ids fixed by earlier steps, ``[MASK]``
elsewhere), and reads the logits at the block's positions. It computes
that forward in two parts, which is the same arithmetic: the served
sequence once under the mask (a position before the block sees only
positions before the block, so its keys and values do not depend on what
follows), then each block state's B rows against those keys and values
before the block's first position and against their own. Departures:
none in the mathematics; the weights are N(0, 0.02) from the seed with
norms 1, as the program's, but the head-wise q/k norms' weights, which
:func:`qk_norm_leaf_f32` draws U(1.25, 1.75) here and in the builder for the
program.

The control and the planted faults (``kind``): ``fp8`` — every matmul
operand except the router's and the attention scores' through
float8-e4m3 with a per-tensor scale; ``causal_in_block`` — a plain
causal mask in the block-causal one's place; ``no_qk_norm`` — the
head-wise norms left out. Each is a second forward beside the float32
one whose picks stand in the program's place.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST
PREFIX = ".blocks.block."
TOP = {"embed": ".embed.weight", "norm": ".norm.weight",
       "lm_head": ".lm_head.weight"}
QUERY_BLOCK = 512
QK_NORM = ("attn.q_norm.weight", "attn.k_norm.weight")
QK_NORM_RANGE = (1.25, 1.75)


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
    rope_theta: float
    eps: float
    block: int
    steps: int
    mask_id: int
    param_dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        return cls(
            hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            expert_ffn=cfg["moe_intermediate_size"],
            vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
            experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            rope_theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]), block=int(cfg["block_length"]),
            steps=int(cfg["denoising_steps"]),
            mask_id=int(cfg["mask_token_id"]),
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
            "attn.q_norm.weight": ((D,), dt),
            "attn.k_norm.weight": ((D,), dt),
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
# the mathematics
# ---------------------------------------------------------------------------

def fp8(x):
    """float8-e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ident(x):
    return x


def _mm(spec, a, b, q=_ident):
    return jnp.einsum(spec, q(a), q(b), precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta: float):
    """Rotary embedding of [T, h, D] at absolute positions ``pos`` [T],
    dims paired by halves (i with i + D/2)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(qpos, kpos, block: int, causal: bool = False):
    """``[T, S]``: query at absolute position ``qpos[t]`` sees the key at
    ``kpos[j]`` — block-causal (``floor(kpos / B) <= floor(qpos / B)``),
    or, as the ``causal_in_block`` fault, plain causal."""
    if causal:
        return kpos[None, :] <= qpos[:, None]
    return kpos[None, :] // block <= qpos[:, None] // block


def project(x, p, a: Arch, pos, q=_ident, qk_norm: bool = True):
    """``(q [T, H, D], k [T, Hkv, D], v [T, Hkv, D])`` of one row's
    normed stream, q and k normed a head and rotated."""
    T = x.shape[0]
    H, Hkv, D = a.heads, a.kv_heads, a.head_dim
    h = rms_norm(x, p["attn_norm.weight"], a.eps)
    qh = _mm("te,ef->tf", h, p["attn.wq.weight"], q).reshape(T, H, D)
    kh = _mm("te,ef->tf", h, p["attn.wk.weight"], q).reshape(T, Hkv, D)
    vh = _mm("te,ef->tf", h, p["attn.wv.weight"], q).reshape(T, Hkv, D)
    if qk_norm:
        qh = rms_norm(qh, p["attn.q_norm.weight"], a.eps)
        kh = rms_norm(kh, p["attn.k_norm.weight"], a.eps)
    return rope(qh, pos, a.rope_theta), rope(kh, pos, a.rope_theta), vh


def attend(qh, kh, vh, seen, a: Arch):
    """Queries [T, H, D] against keys / values [S, Hkv, D] where ``seen``
    [T, S]; query head g * (H / Hkv) + i reads KV head g. [T, H * D]."""
    T, H, D = qh.shape
    G = H // a.kv_heads
    qg = qh.reshape(T, a.kv_heads, G, D)
    s = jnp.einsum("tkgd,skd->kgts", qg, kh, precision=HI) * D ** -0.5
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), vh,
                   precision=HI)
    return o.reshape(T, H * D)


def moe(h, p, a: Arch, q=_ident):
    """``h + experts(RMSNorm(h))`` for tokens [N, E]: softmax routing
    over all experts in float32 at "highest" whatever the control's
    precision, top_k picks renormalised, SwiGLU experts, dropless."""
    m = rms_norm(h, p["mlp_norm.weight"], a.eps)
    probs = jax.nn.softmax(
        jnp.einsum("ne,ex->nx", m, p["moe.router"], precision=HI), -1)
    gate, expert = jax.lax.top_k(probs, a.top_k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)

    def one(total, args):
        e, wg, wu, wd = args
        g = jnp.sum(jnp.where(expert == e, gate, 0.0), -1)        # [N]
        act = (jax.nn.silu(_mm("ne,ef->nf", m, wg, q))
               * _mm("ne,ef->nf", m, wu, q))
        return total + _mm("nf,fe->ne", act, wd, q) * g[:, None], None

    return h + jax.lax.scan(one, jnp.zeros_like(m),
                            (jnp.arange(a.experts), p["moe.w_gate"],
                             p["moe.w_up"], p["moe.w_down"]))[0]


def qk_norm_leaf_f32(key, name: str, layer, shape, dtype):
    """The head-wise q/k norms' weights (``assumed`` in the
    configuration's file): ``U(1.25, 1.75)`` a dimension, rounded to the
    stored dtype, so that a head's scores spread by ~2.3 where weights of
    1 spread them by ~1 and leave attention over 1-2 k cached positions
    near uniform. Drawn as ``lib.weights`` draws: from ``(root key, leaf
    name, layer)``."""
    key = jax.random.fold_in(W.leaf_key(key, name), layer)
    return W.round_through(
        jax.random.uniform(key, shape, jnp.float32, *QK_NORM_RANGE), dtype)


def layer_params(a: Arch, key, l) -> dict:
    """Layer ``l`` (python int or traced) of the scanned stack."""
    return {n: (qk_norm_leaf_f32 if n in QK_NORM else W.layer_leaf_f32)(
                key, PREFIX + n, l, shape, dt)
            for n, (shape, dt) in a.layer_shapes().items()}


def top_param(a: Arch, key, which: str):
    shape, dt = a.top_shapes()[which]
    return W.layer_leaf_f32(key, TOP[which], 0, shape, dt)


def _kind(kind: str):
    """``(operand precision, causal mask, head-wise q/k norm)``."""
    return (fp8 if kind == "fp8" else _ident, kind == "causal_in_block",
            kind != "no_qk_norm")


def context_layer(x, p, a: Arch, kind: str = "float32"):
    """One layer over whole rows [R, S, E] at positions 0..S-1: the next
    stream, and the layer's keys and values [R, S, Hkv, D] (what a block
    later attends before its first position). Queries a block of
    ``QUERY_BLOCK`` at a time."""
    q, causal, qk_norm = _kind(kind)
    R, S, E = x.shape
    pos = jnp.arange(S)
    nb = -(-S // QUERY_BLOCK)

    def row(xr):
        qh, kh, vh = project(xr, p, a, pos, q, qk_norm)
        qp = jnp.pad(qh, ((0, nb * QUERY_BLOCK - S), (0, 0), (0, 0)))

        def blk(args):
            qs, t0 = args
            seen = visible(t0 + jnp.arange(QUERY_BLOCK), pos, a.block,
                           causal)
            return attend(qs, kh, vh, seen, a)

        out = jax.lax.map(blk, (qp.reshape(nb, QUERY_BLOCK, *qh.shape[1:]),
                                jnp.arange(nb) * QUERY_BLOCK))
        o = out.reshape(nb * QUERY_BLOCK, -1)[:S]
        return xr + _mm("tf,fe->te", o, p["attn.wo.weight"], q), kh, vh

    h, k, v = jax.lax.map(row, x)
    return moe(h.reshape(R * S, E), p, a, q).reshape(R, S, E), k, v


def block_layer(x, p0, k_ctx, v_ctx, p, a: Arch, kind: str = "float32"):
    """One layer over block states ``x`` [R, n, B, E] whose first
    positions are ``p0`` [R, n], against their row's context keys and
    values [R, S, Hkv, D]: a state's rows see the context before ``p0``
    and, by the mask, one another."""
    q, causal, qk_norm = _kind(kind)
    R, n, B, E = x.shape
    S = k_ctx.shape[1]

    def row(args):
        xr, pr, kc, vc = args

        def one(xb, start):
            pos = start + jnp.arange(B)
            qh, kh, vh = project(xb, p, a, pos, q, qk_norm)
            kpos = jnp.concatenate([jnp.arange(S), pos])
            before = jnp.concatenate([jnp.arange(S) < start,
                                      jnp.ones((B,), bool)])
            seen = before[None, :] & visible(pos, kpos, a.block, causal)
            o = attend(qh, jnp.concatenate([kc, kh]),
                       jnp.concatenate([vc, vh]), seen, a)
            return xb + _mm("tf,fe->te", o, p["attn.wo.weight"], q)

        return jax.vmap(one)(xr, pr)

    h = jax.lax.map(row, (x, p0, k_ctx, v_ctx))
    return moe(h.reshape(R * n * B, E), p, a, q).reshape(R, n, B, E)


def forward_logits(a: Arch, seed: int, ids, kind: str = "float32"):
    """Float32 logits [R, T, V] of whole rows at positions 0..T-1 under
    the mask (the CPU tests' oracle)."""
    key = W.root_key(seed)
    x = top_param(a, key, "embed")[jnp.asarray(ids, jnp.int32)]
    for l in range(a.layers):
        x, _, _ = context_layer(x, layer_params(a, key, l), a, kind)
    norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")
    return _mm("rte,ev->rtv", rms_norm(x, norm, a.eps), head)


# ---------------------------------------------------------------------------
# serving: the replay of block states
# ---------------------------------------------------------------------------

def replay(a: Arch, seed: int, seqs, p0, states, tokens,
           kind: str = "float32") -> dict:
    """The reference's logits at every position of every block state.

    ``seqs`` [R, S]: each row a prompt and the tokens served for it,
    zero-padded; ``p0`` [R, n]: the first position of each of a row's
    ``n`` block states (padding states may repeat one); ``states`` [R, n,
    B]: the block's ids as they stood (``[MASK]`` where not yet fixed);
    ``tokens`` [R, n, B]: the token the program fixed there, at those
    positions that step fixed (anything elsewhere). Returns float32
    arrays [R, n, B]: ``best``, ``second``, ``lse`` (the reference's best
    and second logit among the tokens other than ``[MASK]``, which no
    position is fixed to, and its log-sum-exp over all) and ``at_token``
    (its logit of
    ``tokens``); with ``kind`` other than float32 also ``other_token``,
    ``other_conf`` (the other forward's best token and its log
    probability) and ``at_other`` (the float32 logit of that token)."""
    key = W.root_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    p0 = jnp.asarray(p0, jnp.int32)
    states = jnp.asarray(states, jnp.int32)
    tokens = jnp.asarray(tokens, jnp.int32)
    kinds = ("float32",) if kind == "float32" else ("float32", kind)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def ctx_layer(key, x, l, kind):
        return context_layer(x, layer_params(a, key, l), a, kind)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def blk_layer(key, x, k, v, l, kind):
        return block_layer(x, p0, k, v, layer_params(a, key, l), a, kind)

    embed = jax.jit(lambda key, ids: top_param(a, key, "embed")[ids])
    outs = {}
    for k_ in kinds:
        x, xb = embed(key, seqs), embed(key, states)
        for l in range(a.layers):
            x, kc, vc = ctx_layer(key, x, l, kind=k_)
            xb = blk_layer(key, xb, kc, vc, l, kind=k_)
        outs[k_] = xb

    @jax.jit
    def stats(key, xb, xo):
        norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")
        q = fp8 if kind == "fp8" else _ident

        def tokens_of(lg):      # a position is never fixed to [MASK]
            return jnp.where(jnp.arange(a.vocab) == a.mask_id, -jnp.inf, lg)

        def one(args):          # one row's states: [n, B, V] logits
            xr, xl, tr = args
            lg = _mm("nbe,ev->nbv", rms_norm(xr, norm, a.eps), head)
            top2 = jax.lax.top_k(tokens_of(lg), 2)[0]
            got = {"best": top2[..., 0], "second": top2[..., 1],
                   "lse": jax.nn.logsumexp(lg, -1),
                   "at_token": jnp.take_along_axis(lg, tr[..., None],
                                                   -1)[..., 0]}
            if xl is not None:
                lo = _mm("nbe,ev->nbv", rms_norm(xl, norm, a.eps), head, q)
                tok = jnp.argmax(tokens_of(lo), -1)
                got.update(
                    other_token=tok.astype(jnp.int32),
                    other_conf=(jnp.max(tokens_of(lo), -1)
                                - jax.nn.logsumexp(lo, -1)),
                    at_other=jnp.take_along_axis(lg, tok[..., None],
                                                 -1)[..., 0])
            return got

        return jax.lax.map(one, (xb, xo, tokens))

    got = stats(key, outs["float32"],
                None if kind == "float32" else outs[kind])
    return {n: np.asarray(v) for n, v in got.items()}
