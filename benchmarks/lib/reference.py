"""Plain references: a decoder-only transformer (dense SwiGLU or
top-k-of-E routed experts, dropless) in straightforward float32
``jax.numpy`` at "highest" matmul precision — forward, loss, gradients
and AdamW, one layer at a time so that it fits beside nothing else on
the chip. Imports nothing of the program and takes nothing it made: the
weights come from ``lib.weights`` by leaf name, the data from the seed.

Departures from the published models, as the configurations state them:
no q/k norm in the OLMoE block (the program's ``LlamaAttention`` has
none), the Switch top-1 form of the load-balancing loss scaled by
``coef / layers`` per layer, no router z-loss. No token is ever dropped:
a program that drops one departs from this reference and fails.

``quant`` is the control: every matmul operand except the router's
passes through float8-e4m3 with a per-tensor scale — the nearest
precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import weights as W

HI = jax.lax.Precision.HIGHEST

# leaf names as lib.weights knows them (the program's pytree paths)
BLOCK = ".blocks.block."
TOP = {"embed": ".embed.weight", "norm": ".norm.weight",
       "lm_head": ".lm_head.weight"}


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden: int
    heads: int
    kv_heads: int
    ffn: int                 # dense intermediate or expert width
    vocab: int
    layers: int
    rope_theta: float
    eps: float
    experts: int = 0         # 0 = dense SwiGLU MLP
    top_k: int = 0
    aux_coef: float = 0.0
    param_dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        return cls(
            hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            ffn=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            layers=cfg["num_hidden_layers"],
            rope_theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]),
            experts=int(cfg.get("num_experts", 0)),
            top_k=int(cfg.get("num_experts_per_tok", 0)),
            aux_coef=float(cfg.get("router_aux_loss_coef", 0.0)),
            param_dtype=cfg.get("torch_dtype", "bfloat16"))

    def layer_shapes(self) -> dict:
        """name -> (shape, stored dtype) of one layer's leaves."""
        E, D, dt = self.hidden, self.head_dim, self.param_dtype
        out = {
            "attn_norm.weight": ((E,), dt),
            "attn.wq.weight": ((E, self.heads * D), dt),
            "attn.wk.weight": ((E, self.kv_heads * D), dt),
            "attn.wv.weight": ((E, self.kv_heads * D), dt),
            "attn.wo.weight": ((self.heads * D, E), dt),
            "mlp_norm.weight": ((E,), dt),
        }
        if self.experts:
            X, I = self.experts, self.ffn
            out.update({
                "moe.router": ((E, X), "float32"),
                "moe.w_gate": ((X, E, I), dt),
                "moe.w_up": ((X, E, I), dt),
                "moe.w_down": ((X, I, E), dt)})
        else:
            out.update({
                "mlp.gate.weight": ((E, self.ffn), dt),
                "mlp.up.weight": ((E, self.ffn), dt),
                "mlp.down.weight": ((self.ffn, E), dt)})
        return out

    def top_shapes(self) -> dict:
        dt = self.param_dtype
        return {"embed": ((self.vocab, self.hidden), dt),
                "norm": ((self.hidden,), dt),
                "lm_head": ((self.hidden, self.vocab), dt)}


# ---------------------------------------------------------------------------
# the control's precision
# ---------------------------------------------------------------------------

def fp8(x):
    """float8-e4m3 with a per-tensor scale, back in float32. Gradients
    pass straight through (the cotangents stay float32): the mildest
    fp8 step, so the control reads as low as such a step can."""
    x0 = jax.lax.stop_gradient(x)
    s = jnp.maximum(jnp.max(jnp.abs(x0)), 1e-30) / 448.0
    return x + ((x0 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
                - x0)


def _ident(x):
    return x


def _mm(spec, a, b, q):
    return jnp.einsum(spec, q(a), q(b), precision=HI)


# ---------------------------------------------------------------------------
# the mathematics, whole batch [B, T, E]
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Half-split rotary embedding of [B, T, H, D] at positions 0..T-1."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, a: Arch, q):
    B, T, _ = x.shape
    H, KV, D = a.heads, a.kv_heads, a.head_dim
    h = rms_norm(x, p["attn_norm.weight"], a.eps)
    qh = rope(_mm("bte,ef->btf", h, p["attn.wq.weight"], q)
              .reshape(B, T, H, D), a.rope_theta)
    kh = rope(_mm("bte,ef->btf", h, p["attn.wk.weight"], q)
              .reshape(B, T, KV, D), a.rope_theta)
    vh = _mm("bte,ef->btf", h, p["attn.wv.weight"], q).reshape(B, T, KV, D)
    g = H // KV
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def group(args):                     # one KV head and its g q-heads
        qg, kg, vg = args                # [B,T,g,D] [B,T,D] [B,T,D]
        s = _mm("btgd,bsd->bgts", qg, kg, q) / math.sqrt(D)
        s = jnp.where(causal, s, -jnp.inf)
        return _mm("bgts,bsd->btgd", jax.nn.softmax(s, -1), vg, q)

    out = jax.lax.map(group, (
        jnp.moveaxis(qh.reshape(B, T, KV, g, D), 2, 0),
        jnp.moveaxis(kh, 2, 0), jnp.moveaxis(vh, 2, 0)))   # [KV,B,T,g,D]
    out = jnp.moveaxis(out, 0, 2).reshape(B, T, H * D)
    return x + _mm("btf,fe->bte", out, p["attn.wo.weight"], q)


def dense_mlp(x, p, a: Arch, q):
    h = rms_norm(x, p["mlp_norm.weight"], a.eps)
    act = (jax.nn.silu(_mm("bte,ef->btf", h, p["mlp.gate.weight"], q))
           * _mm("bte,ef->btf", h, p["mlp.up.weight"], q))
    return x + _mm("btf,fe->bte", act, p["mlp.down.weight"], q), 0.0


def moe_mlp(x, p, a: Arch, q, constrain=_ident):
    """Every expert sees every token and the gate (softmax over all
    experts, top-k kept, NOT renormalised) zeroes the rest: dropless.
    One row at a time; ``constrain`` pins the expert axis of the
    intermediates to the devices that hold those experts."""
    X = a.experts
    h = rms_norm(x, p["mlp_norm.weight"], a.eps)
    probs = jax.nn.softmax(
        jnp.einsum("bte,ex->btx", h, p["moe.router"], precision=HI), -1)
    topv, topi = jax.lax.top_k(probs, a.top_k)
    gates = jnp.sum(jax.nn.one_hot(topi, X, dtype=probs.dtype)
                    * topv[..., None], -2)                     # [B,T,X]
    # Switch load-balancing loss over all B*T tokens
    frac = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, -1), X,
                                   dtype=probs.dtype), (0, 1))
    aux = X * jnp.sum(jax.lax.stop_gradient(frac) * jnp.mean(probs, (0, 1)))

    @jax.checkpoint
    def row(args):
        hr, gr = args                                          # [T,E] [T,X]
        act = constrain(
            jax.nn.silu(_mm("te,xei->xti", hr, p["moe.w_gate"], q))
            * _mm("te,xei->xti", hr, p["moe.w_up"], q))
        y = constrain(_mm("xti,xie->xte", act, p["moe.w_down"], q))
        return jnp.einsum("xte,tx->te", y, gr, precision=HI)

    return x + jax.lax.map(row, (h, gates)), aux


def layer(x, p, a: Arch, q=_ident, constrain=_ident):
    x = attention(x, p, a, q)
    if a.experts:
        return moe_mlp(x, p, a, q, constrain)
    return dense_mlp(x, p, a, q)


def head_logits(x, norm_w, head_w, a: Arch, q=_ident):
    return _mm("te,ev->tv", rms_norm(x, norm_w, a.eps), head_w, q)


def head_loss_sum(x, norm_w, head_w, ids, a: Arch, q=_ident):
    """Sum over rows and positions of the next-token cross entropy, one
    row's [T, V] logits at a time."""

    @jax.checkpoint
    def row(args):
        xr, idr = args
        lp = jax.nn.log_softmax(head_logits(xr, norm_w, head_w, a, q)[:-1])
        return -jnp.sum(jnp.take_along_axis(lp, idr[1:, None], -1))

    return jnp.sum(jax.lax.map(row, (x, ids)))


# ---------------------------------------------------------------------------
# weights, by layer, from the seed
# ---------------------------------------------------------------------------

def layer_params(a: Arch, key, l) -> dict:
    return {n: W.layer_leaf_f32(key, BLOCK + n, l, shape, dt)
            for n, (shape, dt) in a.layer_shapes().items()}


def top_param(a: Arch, key, which: str):
    shape, dt = a.top_shapes()[which]
    return W.layer_leaf_f32(key, TOP[which], 0, shape, dt)


class Placement:
    """Where the reference's arrays live: rows of activations and the
    expert axis of expert leaves spread over ``devices`` (one device:
    everything on it)."""

    def __init__(self, devices):
        self.mesh = Mesh(np.asarray(devices), ("d",))
        self.n = len(devices)

    def spec(self, *axes):
        return NamedSharding(self.mesh, P(*axes))

    def rows(self):
        return self.spec("d")

    def whole(self):
        return self.spec()

    def leaf(self, name: str, shape):
        if name.startswith("moe.w_") and shape[0] % self.n == 0:
            return self.spec("d")
        return self.spec()

    def top(self, which: str):
        """The vocabulary axis of the embedding and the head."""
        if self.n == 1:
            return self.spec()
        return {"embed": self.spec("d", None),
                "lm_head": self.spec(None, "d")}.get(which, self.spec())

    def constrain_experts(self, x):
        if self.n == 1 or x.shape[0] % self.n:
            return x
        return jax.lax.with_sharding_constraint(x, self.spec("d"))


# ---------------------------------------------------------------------------
# serving: logits of a padded sequence, layer by layer
# ---------------------------------------------------------------------------

def serve_logit_gaps(a: Arch, seed: int, seqs, spans,
                     precision: str = "float32"):
    """``seqs`` [R, S] int32: each row a prompt followed by the tokens
    served for it, zero-padded to S (causal, so the padding sees the
    sequence and the sequence never sees the padding); ``spans[r]`` is
    ``(prompt length, prompt + served length)``. Returns two lists, for
    each row a float32 array over served positions: the gap ``best logit
    - logit of the served token`` under the float32 forward, and the
    reference's own margin ``best - second best`` there. With
    ``precision="fp8"`` (the control) the token is not the served one
    but the one the fp8 forward puts first at that position."""
    key = W.root_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    low = precision == "fp8"

    @functools.partial(jax.jit, static_argnames=("quant",))
    def run_layer(key, x, l, quant):
        return layer(x, layer_params(a, key, l), a,
                     fp8 if quant else _ident)[0]

    @jax.jit
    def gaps(key, x, x_low, ids):
        norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")

        def row(args):
            xr, xl, idr = args
            lg = head_logits(xr, norm, head, a)[:-1]
            tok = (jnp.argmax(head_logits(xl, norm, head, a, fp8)[:-1], -1)
                   if low else idr[1:])
            top2 = jax.lax.top_k(lg, 2)[0]
            return (top2[:, 0] - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], top2[:, 0] - top2[:, 1])

        return jax.lax.map(row, (x, x_low, ids))   # [R, S-1] each: the gap
        # at i is of the token at i+1, the margin the reference's own

    x = jax.jit(lambda key, ids: top_param(a, key, "embed")[ids])(key, seqs)
    x_low = x
    for l in range(a.layers):
        x_low = run_layer(key, x_low, l, True) if low else x_low
        x = run_layer(key, x, l, False)
    g, m = (np.asarray(t, np.float32)
            for t in gaps(key, x, x_low if low else x, seqs))
    return ([g[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)],
            [m[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)])


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW over the first steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    clip_norm: float = 0.0     # gradients clipped to this global norm; 0: none


def adamw_leaf(opt: AdamW, count, p, m, v, g, dtype):
    """One leaf, float32 throughout; the new parameter is rounded
    through the dtype the configuration stores it in."""
    m = opt.beta1 * m + (1 - opt.beta1) * g
    v = opt.beta2 * v + (1 - opt.beta2) * g * g
    c1 = 1 - opt.beta1 ** count
    c2 = 1 - opt.beta2 ** count
    u = (m / c1) / (jnp.sqrt(v / c2) + opt.eps) + opt.weight_decay * p
    return W.round_through(p - opt.lr * u, dtype), m, v


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def _sumsq(tree):
    return sum(jnp.sum(jnp.square(x))
               for x in jax.tree_util.tree_leaves(tree))


class TrainPrograms:
    """The jitted pieces of a followed step, for one architecture,
    optimizer and placement: a layer forward, a layer backward with its
    AdamW update, the head's loss/backward/update, the embedding's.
    ``scale`` multiplies every gradient before the optimizer sees it
    (the clip's factor; 1 without clipping); the ``*_gsq`` pieces are
    the same backward passes giving the gradient's sum of squares alone,
    for the pass that finds that factor."""

    def __init__(self, a: Arch, opt: AdamW, place: "Placement",
                 precision: str = "float32"):
        q = fp8 if precision == "fp8" else _ident
        ce = place.constrain_experts
        shapes, tops = a.layer_shapes(), a.top_shapes()
        lsh = {n: place.leaf(n, s) for n, (s, _) in shapes.items()}
        rows, whole = place.rows(), place.whole()
        self.lsh, self.rows = lsh, rows

        self.make_layer = jax.jit(lambda key, l: layer_params(a, key, l),
                                  out_shardings=lsh)
        self.zeros_layer = jax.jit(
            lambda: {n: jnp.zeros(s, jnp.float32)
                     for n, (s, _) in shapes.items()}, out_shardings=lsh)
        self.fwd = jax.jit(lambda x, p: layer(x, p, a, q, ce),
                           in_shardings=(rows, lsh),
                           out_shardings=(rows, whole))

        def update(p, m, v, g, count, dtypes, scale):
            g = {n: x * scale for n, x in g.items()}
            new = {n: adamw_leaf(opt, count, p[n], m[n], v[n], g[n],
                                 dtypes[n][1]) for n in g}
            return ({n: t[0] for n, t in new.items()},
                    {n: t[1] for n, t in new.items()},
                    {n: t[2] for n, t in new.items()},
                    {n: jnp.sum(jnp.square(g[n])) for n in g})

        @functools.partial(
            jax.jit,
            in_shardings=(rows, lsh, lsh, lsh, rows, whole, whole, whole),
            out_shardings=(lsh, lsh, lsh, whole, rows),
            donate_argnums=(1, 2, 3, 4))
        def bwd_layer(x, p, m, v, dy, aux_cot, count, scale):
            _, vjp = jax.vjp(lambda p, x: layer(x, p, a, q, ce), p, x)
            g, dx = vjp((dy, aux_cot))
            return update(p, m, v, g, count, shapes, scale) + (dx,)

        @functools.partial(jax.jit, in_shardings=(rows, lsh, rows, whole),
                           out_shardings=(whole, rows))
        def layer_gsq(x, p, dy, aux_cot):
            _, vjp = jax.vjp(lambda p, x: layer(x, p, a, q, ce), p, x)
            g, dx = vjp((dy, aux_cot))
            return _sumsq(g), dx

        @functools.partial(
            jax.jit, donate_argnums=(2, 3, 4),
            out_shardings=(whole, rows, None, None, None, None))
        def head_step(x, ids, hp, hm, hv, count, denom, scale):
            loss, (dx, dn, dh) = head_grads(x, ids, hp, denom)
            return (loss, dx) + update(
                hp, hm, hv, {"norm": dn, "lm_head": dh}, count, tops, scale)

        def head_grads(x, ids, hp, denom):
            return jax.value_and_grad(
                lambda x, n, h: head_loss_sum(x, n, h, ids, a, q) / denom,
                argnums=(0, 1, 2))(x, hp["norm"], hp["lm_head"])

        @functools.partial(jax.jit, out_shardings=(whole, rows))
        def head_gsq(x, ids, hp, denom):
            _, (dx, dn, dh) = head_grads(x, ids, hp, denom)
            return _sumsq((dn, dh)), dx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def embed_step(e, m, v, ids, dx, count, scale):
            g = jnp.zeros_like(e).at[ids].add(dx) * scale
            return adamw_leaf(opt, count, e, m, v, g, tops["embed"][1]) + (
                jnp.sum(jnp.square(g)),)

        self.bwd_layer, self.head_step = bwd_layer, head_step
        self.embed_step = embed_step
        self.layer_gsq, self.head_gsq = layer_gsq, head_gsq
        self.embed_gsq = jax.jit(lambda e, ids, dx: jnp.sum(jnp.square(
            jnp.zeros_like(e).at[ids].add(dx))))
        self.embed = jax.jit(lambda e, ids: e[ids], out_shardings=rows)
        self.diff_layer = jax.jit(lambda key, p, l: {
            n: jnp.sum(jnp.square(p[n] - x))
            for n, x in layer_params(a, key, l).items()},
            in_shardings=(None, lsh, None))


def clip_scale(f: TrainPrograms, clip_norm: float, xs, ids, layers, hp,
               embed, aux_cot, denom):
    """The factor that clips the step's gradient to ``clip_norm`` by its
    global norm, ``min(1, clip_norm / norm)``: a backward pass of its own
    that keeps no gradient and updates nothing, so that clipping costs
    the reference time and not memory."""
    sq, dx = f.head_gsq(xs[-1], ids, hp, denom)
    for l in reversed(range(len(layers))):
        s, dx = f.layer_gsq(xs[l], layers[l], dx, aux_cot)
        sq = sq + s
    sq = sq + f.embed_gsq(embed, ids, dx)
    return jnp.minimum(1.0, clip_norm / jnp.maximum(jnp.sqrt(sq), 1e-12))


def train_steps(a: Arch, seed: int, batches, opt: AdamW, devices,
                precision: str = "float32"):
    """Follow ``len(batches)`` AdamW steps from the seed's weights.
    ``batches`` are [B, T] int32 arrays (labels = inputs, next-token
    loss, mean over B*(T-1), plus the load-balancing term). Returns
    ``{"loss": [..], "grad_norm": {leaf: ..} of step 1 as the optimizer
    gets it (clipped, where the optimizer clips),
    "change_norm": {leaf: ..} after the last step}`` with stacked leaves
    reduced over all layers, under the program's leaf names."""
    place = Placement(devices)
    f = TrainPrograms(a, opt, place, precision)
    tops = a.top_shapes()
    key = W.root_key(seed)
    P_ = [f.make_layer(key, l) for l in range(a.layers)]
    M_ = [f.zeros_layer() for _ in range(a.layers)]
    V_ = [f.zeros_layer() for _ in range(a.layers)]
    top = {k: jax.jit(lambda key, k=k: top_param(a, key, k),
                      out_shardings=place.top(k))(key) for k in tops}
    top_m = {k: jnp.zeros_like(x) for k, x in top.items()}
    top_v = {k: jnp.zeros_like(x) for k, x in top.items()}

    out = {"loss": [], "grad_norm": {}, "change_norm": {}}
    aux_cot = jnp.float32(a.aux_coef / a.layers)
    for step, ids in enumerate(batches, 1):
        ids = jax.device_put(jnp.asarray(ids, jnp.int32), f.rows)
        count = jnp.float32(step)
        xs = [f.embed(top["embed"], ids)]
        aux = 0.0
        for l in range(a.layers):
            x, ax = f.fwd(xs[-1], P_[l])
            xs.append(x)
            aux = aux + ax * aux_cot
        denom = jnp.float32(ids.shape[0] * (ids.shape[1] - 1))
        hp, hm, hv = ({n: t[n] for n in ("norm", "lm_head")}
                      for t in (top, top_m, top_v))
        scale = jnp.float32(1.0) if not opt.clip_norm else clip_scale(
            f, opt.clip_norm, xs, ids, P_, hp, top["embed"], aux_cot, denom)
        loss, dx, hp, hm, hv, gn_head = f.head_step(
            xs.pop(), ids, hp, hm, hv, count, denom, scale)
        for n in hp:
            top[n], top_m[n], top_v[n] = hp[n], hm[n], hv[n]
        out["loss"].append(float(loss + aux))
        gsq: dict = {}
        for l in reversed(range(a.layers)):
            P_[l], M_[l], V_[l], gn, dx = f.bwd_layer(
                xs.pop(), P_[l], M_[l], V_[l], dx, aux_cot, count, scale)
            for n, s in gn.items():
                gsq[n] = gsq.get(n, 0.0) + s
        top["embed"], top_m["embed"], top_v["embed"], gn_embed = f.embed_step(
            top["embed"], top_m["embed"], top_v["embed"], ids, dx, count,
            scale)
        if step == 1:
            out["grad_norm"] = {BLOCK + n: float(jnp.sqrt(s))
                                for n, s in gsq.items()}
            out["grad_norm"].update(
                {TOP[n]: float(jnp.sqrt(s)) for n, s in gn_head.items()})
            out["grad_norm"][TOP["embed"]] = float(jnp.sqrt(gn_embed))

    csq: dict = {}
    for l in range(a.layers):
        for n, s in f.diff_layer(key, P_[l], l).items():
            csq[n] = csq.get(n, 0.0) + s
    out["change_norm"] = {BLOCK + n: float(jnp.sqrt(s))
                          for n, s in csq.items()}
    for k in tops:
        out["change_norm"][TOP[k]] = float(jax.jit(
            lambda key, p, k=k: _norm(p - top_param(a, key, k)))(key, top[k]))
    return out
