"""Plain reference of the DeepSeek-V3 block (GigaChat3.1, DeepSeek-V3/R1):
latent attention (MLA) in its EXPANDED form at every position, leading
dense SwiGLU layers, then expert layers with sigmoid group-limited
routing, a shared expert and a held share of the routed experts —
straightforward float32 ``jax.numpy`` at "highest" matmul precision, one
layer at a time, attention one row and one group of heads at a time.
Imports nothing of the program and takes nothing it made: the weights
come from ``lib.weights`` by the program's leaf names, the data from the
seed.

The equations (h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h))):

- c_q = RMSNorm(x W_qa); per head [q_nope | q_rope] = c_q W_qb.
  [c_kv | k_rope] = x W_kva; c_kv <- RMSNorm(c_kv); k_rope <- RoPE(k_rope),
  one a token, shared by all heads; q_rope <- RoPE(q_rope). Per head
  [k_nope | v] = c_kv W_kvb. score_h(t, s) = (q_nope_h(t) k_nope_h(s) +
  q_rope_h(t) k_rope(s)) * scale, causal softmax, o = concat_h sum_s p v,
  out = o W_o. Never the absorbed form: that is the program's to prove.
- YaRN as DeepSeek-V3 applies it: inverse frequencies blend
  theta^(-2i/R) and the same / factor under the linear ramp between the
  correction dims of beta_fast / beta_slow; scale = (N + R)^(-1/2) * m^2
  with m = 0.1 * mscale_all_dim * ln(factor) + 1; cos/sin times
  mscale's ratio to mscale_all_dim's. Rope dims pair by halves (i with
  i + R/2).
- Expert layer: s = sigmoid(x W_g); s' = s + b; ``groups`` groups of
  consecutive experts, a group scores the sum of its two largest s', the
  ``groups_kept`` best stay, the ``top_k`` largest s' among their experts
  are picked; gates = s at the picks / their sum (+1e-20) * routed_scale;
  y = sum over picks that are HELD of gate_e Expert_e(x) + Shared(x).
  What the experts held elsewhere would add is left out, as in the
  program. Every held expert's output counts for exactly the tokens that
  picked it; no token is ever dropped.

Departures from the published model, as the configuration states them:
no multi-token-prediction module.

``quant`` is the control: every matmul operand except the router's and
the attention scores' passes through float8-e4m3 with a per-tensor scale.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST

# leaf names as lib.weights knows them (the program's pytree paths): the
# expert stack draws layer by layer, every other leaf whole from layer 0
EXPERT = ".blocks.block."
DENSE = ".dense_blocks.block."
TOP = {"embed": ".embed.weight", "norm": ".norm.weight",
       "lm_head": ".lm_head.weight"}


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    dense_ffn: int
    expert_ffn: int
    vocab: int
    layers: int
    dense_layers: int
    experts: int
    top_k: int
    groups: int
    groups_kept: int
    routed_scale: float
    shared: int
    held: tuple
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    eps: float
    param_dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        y = cfg["rope_scaling"]
        return cls(
            hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], dense_ffn=cfg["intermediate_size"],
            expert_ffn=cfg["moe_intermediate_size"],
            vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
            dense_layers=cfg["first_k_dense_replace"],
            experts=cfg["published"]["n_routed_experts"]
            if "held" in cfg else cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"], groups=cfg["n_group"],
            groups_kept=cfg["topk_group"],
            routed_scale=float(cfg["routed_scaling_factor"]),
            shared=int(cfg["n_shared_experts"]),
            held=tuple(cfg.get("held", (0, cfg["n_routed_experts"]))),
            rope_theta=float(cfg["rope_theta"]),
            yarn_factor=float(y["factor"]),
            yarn_original=int(y["original_max_position_embeddings"]),
            yarn_beta_fast=float(y["beta_fast"]),
            yarn_beta_slow=float(y["beta_slow"]),
            yarn_mscale=float(y["mscale"]),
            yarn_mscale_all_dim=float(y["mscale_all_dim"]),
            eps=float(cfg["rms_norm_eps"]),
            param_dtype=cfg.get("torch_dtype", "bfloat16"))

    def layer_shapes(self, expert: bool) -> dict:
        """name -> (shape, stored dtype) of one layer's leaves."""
        E, H, dt = self.hidden, self.heads, self.param_dtype
        out = {
            "attn_norm.weight": ((E,), dt),
            "attn.wq_a.weight": ((E, self.q_rank), dt),
            "attn.q_norm.weight": ((self.q_rank,), dt),
            "attn.wq_b.weight": ((self.q_rank, H * (self.nope + self.rope)),
                                 dt),
            "attn.wkv_a.weight": ((E, self.kv_rank + self.rope), dt),
            "attn.kv_norm.weight": ((self.kv_rank,), dt),
            "attn.wkv_b.weight": ((self.kv_rank,
                                   H * (self.nope + self.v_dim)), dt),
            "attn.wo.weight": ((H * self.v_dim, E), dt),
            "mlp_norm.weight": ((E,), dt),
        }
        if not expert:
            F_ = self.dense_ffn
            out.update({"mlp.gate.weight": ((E, F_), dt),
                        "mlp.up.weight": ((E, F_), dt),
                        "mlp.down.weight": ((F_, E), dt)})
            return out
        X, I, n = self.experts, self.expert_ffn, self.held[1]
        out.update({"moe.router": ((E, X), "float32"),
                    "moe.select_bias": ((X,), "float32"),
                    "moe.w_gate": ((n, E, I), dt),
                    "moe.w_up": ((n, E, I), dt),
                    "moe.w_down": ((n, I, E), dt)})
        if self.shared:
            S = self.shared * I
            out.update({"moe.shared_gate": ((E, S), dt),
                        "moe.shared_up": ((E, S), dt),
                        "moe.shared_down": ((S, E), dt)})
        return out

    def top_shapes(self) -> dict:
        dt = self.param_dtype
        return {"embed": ((self.vocab, self.hidden), dt),
                "norm": ((self.hidden,), dt),
                "lm_head": ((self.hidden, self.vocab), dt)}

    @property
    def mscale(self) -> float:
        if self.yarn_factor <= 1:
            return 1.0
        return 0.1 * self.yarn_mscale_all_dim * math.log(self.yarn_factor) + 1

    @property
    def softmax_scale(self) -> float:
        return (self.nope + self.rope) ** -0.5 * self.mscale ** 2


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


def yarn_inv_freq(a: Arch) -> np.ndarray:
    """The R/2 inverse frequencies, float64 arithmetic on the host."""
    R, base = a.rope, a.rope_theta
    plain = base ** (-np.arange(0, R, 2, dtype=np.float64) / R)
    if a.yarn_factor <= 1:
        return plain

    def correction_dim(rotations):
        return (R * math.log(a.yarn_original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(a.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(a.yarn_beta_slow)), R - 1)
    ramp = np.clip((np.arange(R // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / a.yarn_factor * ramp + plain * (1 - ramp)


def rope(x, a: Arch):
    """Rotary embedding of [..., T, h, R] at positions 0..T-1, dims
    paired by halves, YaRN frequencies."""
    T = x.shape[-3]
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_inv_freq(a), jnp.float32))
    ratio = 1.0
    if a.yarn_factor > 1:
        ratio = ((0.1 * a.yarn_mscale * math.log(a.yarn_factor) + 1)
                 / a.mscale)
    cos, sin = jnp.cos(ang)[:, None] * ratio, jnp.sin(ang)[:, None] * ratio
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, a: Arch, q=_ident, head_block: int = 8):
    """Expanded latent attention over whole rows [B, T, E]."""
    B, T, _ = x.shape
    H, N, R, V = a.heads, a.nope, a.rope, a.v_dim
    h = rms_norm(x, p["attn_norm.weight"], a.eps)
    c_q = rms_norm(_mm("bte,er->btr", h, p["attn.wq_a.weight"], q),
                   p["attn.q_norm.weight"], a.eps)
    qh = _mm("btr,rf->btf", c_q, p["attn.wq_b.weight"], q).reshape(
        B, T, H, N + R)
    q_nope, q_rope = qh[..., :N], rope(qh[..., N:], a)
    kv = _mm("bte,ef->btf", h, p["attn.wkv_a.weight"], q)
    c_kv = rms_norm(kv[..., :a.kv_rank], p["attn.kv_norm.weight"], a.eps)
    k_rope = rope(kv[..., None, a.kv_rank:], a)               # [B,T,1,R]
    kvh = _mm("btc,cf->btf", c_kv, p["attn.wkv_b.weight"], q).reshape(
        B, T, H, N + V)
    k_nope, v = kvh[..., :N], kvh[..., N:]
    causal = jnp.tril(jnp.ones((T, T), bool))
    g = math.gcd(H, head_block)

    def heads(args):                     # one row, g heads
        qn, qr, kn, vh, kr = args        # [T,g,N] [T,g,R] [T,g,N] [T,g,V] [T,R]
        s = (jnp.einsum("tgn,sgn->gts", qn, kn, precision=HI)
             + jnp.einsum("tgr,sr->gts", qr, kr, precision=HI))
        s = jnp.where(causal, s * a.softmax_scale, -jnp.inf)
        return jnp.einsum("gts,sgv->tgv", jax.nn.softmax(s, -1), vh,
                          precision=HI)

    def row(args):
        qn, qr, kn, vh, kr = args

        def split(t):                    # [T,H,d] -> [H/g,T,g,d]
            return jnp.moveaxis(t.reshape(T, H // g, g, t.shape[-1]), 1, 0)

        out = jax.lax.map(lambda t: heads(t + (kr,)),
                          (split(qn), split(qr), split(kn), split(vh)))
        return jnp.moveaxis(out, 0, 1).reshape(T, H * V)

    out = jax.lax.map(row, (q_nope, q_rope, k_nope, v, k_rope[:, :, 0]))
    return x + _mm("btf,fe->bte", out, p["attn.wo.weight"], q)


def swiglu(h, gate, up, down, q=_ident):
    act = (jax.nn.silu(_mm("...e,ef->...f", h, gate, q))
           * _mm("...e,ef->...f", h, up, q))
    return _mm("...f,fe->...e", act, down, q)


def dense_mlp(x, p, a: Arch, q=_ident):
    h = rms_norm(x, p["mlp_norm.weight"], a.eps)
    return x + swiglu(h, p["mlp.gate.weight"], p["mlp.up.weight"],
                      p["mlp.down.weight"], q)


def route(h, router, bias, a: Arch):
    """``(expert ids, gates)`` [..., top_k] of every token, float32 at
    "highest" whatever the control's precision."""
    s = jax.nn.sigmoid(jnp.einsum("...e,ex->...x", h, router, precision=HI))
    sel = s + bias
    per = sel.reshape(sel.shape[:-1] + (a.groups, a.experts // a.groups))
    group_score = jnp.sum(jax.lax.top_k(per, 2)[0], -1)
    best = jax.lax.top_k(group_score, a.groups_kept)[1]
    keep = jnp.any(jax.nn.one_hot(best, a.groups, dtype=bool), -2)
    sel = jnp.where(jnp.repeat(keep, a.experts // a.groups, -1), sel,
                    -jnp.inf)
    expert = jax.lax.top_k(sel, a.top_k)[1]
    gate = jnp.take_along_axis(s, expert, -1)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20) * a.routed_scale
    return expert, gate


def routed_part(h, p, a: Arch, q=_ident, held=None):
    """What the experts ``[first, first + count)`` add for tokens
    ``h`` [T, E]: each held expert's output at exactly the tokens that
    picked it, times its gate. ``p["moe.w_*"]`` hold those experts."""
    first, count = a.held if held is None else held
    expert, gate = route(h, p["moe.router"], p["moe.select_bias"], a)

    def one(args):
        e, wg, wu, wd = args
        g = jnp.sum(jnp.where(expert == first + e, gate, 0.0), -1)   # [T]
        return swiglu(h, wg, wu, wd, q) * g[:, None]

    parts = jax.lax.map(one, (jnp.arange(count), p["moe.w_gate"],
                              p["moe.w_up"], p["moe.w_down"]))
    return jnp.sum(parts, 0)


def shared_part(h, p, a: Arch, q=_ident):
    if not a.shared:
        return jnp.zeros_like(h)
    return swiglu(h, p["moe.shared_gate"], p["moe.shared_up"],
                  p["moe.shared_down"], q)


def moe_mlp(x, p, a: Arch, q=_ident):
    h = rms_norm(x, p["mlp_norm.weight"], a.eps)
    y = jax.lax.map(lambda hr: routed_part(hr, p, a, q)
                    + shared_part(hr, p, a, q), h)
    return x + y


def layer(x, p, a: Arch, expert: bool, q=_ident):
    x = attention(x, p, a, q)
    return moe_mlp(x, p, a, q) if expert else dense_mlp(x, p, a, q)


def head_logits(x, norm_w, head_w, a: Arch, q=_ident):
    return _mm("te,ev->tv", rms_norm(x, norm_w, a.eps), head_w, q)


# ---------------------------------------------------------------------------
# weights, by layer, from the seed
# ---------------------------------------------------------------------------

def layer_params(a: Arch, key, expert: bool, i) -> dict:
    """Layer ``i`` (python int or traced) of one of the two stacks: an
    expert layer's leaves are drawn for its index in the expert stack, a
    leading dense layer's are slices of the dense stack's whole draw."""
    if expert:
        return {n: W.layer_leaf_f32(key, EXPERT + n, i, shape, dt)
                for n, (shape, dt) in a.layer_shapes(True).items()}
    return {n: W.layer_leaf_f32(key, DENSE + n, 0,
                                (a.dense_layers,) + shape, dt)[i]
            for n, (shape, dt) in a.layer_shapes(False).items()}


def stack_index(a: Arch, l: int) -> tuple[bool, int]:
    """Layer ``l`` of the model -> (is an expert layer, index in its
    stack)."""
    return (l >= a.dense_layers,
            l - a.dense_layers if l >= a.dense_layers else l)


def top_param(a: Arch, key, which: str):
    shape, dt = a.top_shapes()[which]
    return W.layer_leaf_f32(key, TOP[which], 0, shape, dt)


def forward_logits(a: Arch, seed: int, ids):
    """Float32 logits [B, T, V] of whole rows (the CPU tests' oracle)."""
    key = W.root_key(seed)
    x = top_param(a, key, "embed")[jnp.asarray(ids, jnp.int32)]
    for l in range(a.layers):
        expert, i = stack_index(a, l)
        x = layer(x, layer_params(a, key, expert, i), a, expert)
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
    ``precision="fp8"`` (the control) the token is the one the fp8
    forward puts first at that position."""
    key = W.root_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    low = precision == "fp8"

    @functools.partial(jax.jit, static_argnames=("expert", "quant"))
    def run_layer(key, x, expert, i, quant):
        return layer(x, layer_params(a, key, expert, i), a, expert,
                     fp8 if quant else _ident)

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

        return jax.lax.map(row, (x, x_low, ids))

    x = jax.jit(lambda key, ids: top_param(a, key, "embed")[ids])(key, seqs)
    x_low = x
    for l in range(a.layers):
        at = stack_index(a, l)
        x_low = run_layer(key, x_low, *at, True) if low else x_low
        x = run_layer(key, x, *at, False)
    g, m = (np.asarray(t, np.float32)
            for t in gaps(key, x, x_low if low else x, seqs))
    return ([g[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)],
            [m[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)])
