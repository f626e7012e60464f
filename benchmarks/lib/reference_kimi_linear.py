"""Plain reference of the Kimi-Linear block (moonshotai
Kimi-Linear-48B-A3B): KDA layers — a gated delta-rule linear attention
with a per-channel decay, computed here as a ``lax.scan`` over SINGLE
tokens — beside latent-attention layers without position encoding in
their EXPANDED form under a full causal mask; one leading dense SwiGLU,
then expert layers with sigmoid routing over one group, a shared expert
and a held share of the routed experts. Straightforward float32
``jax.numpy`` at "highest" matmul precision, one layer at a time (a
latent layer one row at a time; a KDA layer's rows side by side, since a
scan over tokens is as many serial steps for six rows as for one). No
kernels, no cache, no chunked transform:
the program's chunked prefill, its one-token step, its snapshots and
its absorbed latent form are all the program's to prove. Imports
nothing of the program and takes nothing it made: the weights come from
``lib.weights`` by the program's leaf names (the decay's two leaves
from :func:`decay_leaf_f32` here, which the builder uses too), the data
from the seed. The expert layer, the dense MLP, the norms and the float8
control are ``lib.reference_latent``'s own lines (the same router
family: ``groups = groups_kept = 1``).

One KDA layer for the normed input ``x`` [T, E], per head ``h`` of
``H``, ``d_k = d_v = D``:

    q~, k~, v~ = x W_q, x W_k, x W_v
    q', k', v' = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~)),
        conv(u)_t = sum_j w[j] u_{t-K+1+j}   (causal depthwise, K taps, zeros ahead)
    q = q'_h / sqrt(|q'_h|^2 + 1e-6) * D^-1/2 ;  k = k'_h / sqrt(|k'_h|^2 + 1e-6) ;  v = v'_h
    g_t = -exp(A_log_h) * softplus((x_t W_fa) W_fb + dt_bias)_h  in R^D
    beta_t = sigmoid(x_t W_b)_h
    S~ = Diag(exp(g_t)) S_{t-1} ;  S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T ;  o_t = S_t^T q_t
    y_t = [ rmsnorm(o_t; w_o_norm) * sigmoid((x_t W_ga) W_gb)_h ] W_o

A latent layer: ``q = x W_q`` (H x (N + R)), ``[c_kv | k_r] = x W_kva``,
``c = rmsnorm(c_kv)``, ``[k_nope | v]_h = c W_kvb``, score ``(q_nope .
k_nope + q_r . k_r) (N + R)^-1/2``, NO rotation, causal softmax, ``W_o``.

Departures from the published model, as the configuration states them:
none in the block; the held share and the vocabulary slice are the
deployment's cut.

Controls and planted faults (``precision``): ``"fp8"`` — every
projection's operands through float8-e4m3 with a per-tensor scale (not
the router's, the scores' or the recurrence's own products);
``"state_bf16"`` — the state rounded to bfloat16 after every token (a
reading, not a limit's control); ``"no_state_restore"`` — every
recurrence and every convolution starts from nothing at position
``cut`` (the shared template's end): what a lost snapshot would serve.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from .reference_latent import (HI, _ident, _mm, dense_mlp, fp8, head_logits,
                               moe_mlp, rms_norm)

# leaf names as lib.weights knows them (the program's pytree paths): the
# first period and the leftover layers are blocks of their own, the whole
# periods between are scanned — layer i of the period drawn at the
# period's index in that stack
HEAD, TAIL = ".head[{}].", ".tail[{}]."
PERIOD = ".blocks.block.layers[{}]."
TOP = {"embed": ".embed.weight", "norm": ".norm.weight",
       "lm_head": ".lm_head.weight"}
DECAY = ("attn.A_log", "attn.dt_bias")


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden: int
    layers: int
    dense_layers: int
    full_layers: tuple        # 1-based, the latent layers
    # latent attention
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    # KDA
    kda_heads: int
    kda_dim: int
    conv: int
    kda_rank: int
    # MLPs (names as lib.reference_latent reads them)
    dense_ffn: int
    expert_ffn: int
    vocab: int
    experts: int
    top_k: int
    routed_scale: float
    shared: int
    held: tuple
    eps: float
    groups: int = 1
    groups_kept: int = 1
    param_dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        lin = cfg["linear_attn_config"]
        L = cfg["num_hidden_layers"]
        return cls(
            hidden=cfg["hidden_size"], layers=L,
            dense_layers=cfg["first_k_dense_replace"],
            full_layers=tuple(l for l in lin["full_attn_layers"] if l <= L),
            heads=cfg["num_attention_heads"], kv_rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], kda_heads=lin["num_heads"],
            kda_dim=lin["head_dim"], conv=lin["short_conv_kernel_size"],
            kda_rank=int(cfg.get("assumed", {}).get(
                "kda_rank", lin["head_dim"])),
            dense_ffn=cfg["intermediate_size"],
            expert_ffn=cfg["moe_intermediate_size"],
            vocab=cfg["vocab_size"],
            experts=cfg["published"]["num_experts"]
            if "held" in cfg else cfg["num_experts"],
            top_k=cfg["num_experts_per_token"],
            routed_scale=float(cfg["routed_scaling_factor"]),
            shared=int(cfg["num_shared_experts"]),
            held=tuple(cfg.get("held", (0, cfg["num_experts"]))),
            groups=int(cfg["num_expert_group"]),
            groups_kept=int(cfg["topk_group"]),
            eps=float(cfg["rms_norm_eps"]),
            param_dtype=cfg.get("torch_dtype", "bfloat16"))

    # -- the layout ----------------------------------------------------------
    @property
    def period(self) -> int:
        return self.full_layers[0]

    def kind(self, l: int) -> str:
        """Layer ``l`` (0-based): ``"kda"`` or ``"mla"``."""
        return "mla" if l + 1 in self.full_layers else "kda"

    @property
    def whole_periods(self) -> int:
        p, n = self.period, 0
        pattern = ["kda"] * (p - 1) + ["mla"]
        while [self.kind(l) for l in range((n + 1) * p, (n + 2) * p)
               if l < self.layers] == pattern:
            n += 1
        return n

    def where(self, l: int) -> tuple[str, int]:
        """Layer ``l`` -> (leaf-name prefix, index its stacked leaves are
        drawn at)."""
        p, n = self.period, self.whole_periods
        if l < p:
            return HEAD.format(l), 0
        if l < p * (1 + n):
            return PERIOD.format(l % p), l // p - 1
        return TAIL.format(l - p * (1 + n)), 0

    def scanned(self, l: int) -> bool:
        """Whether layer ``l``'s leaves are slices of stacked ones."""
        p = self.period
        return p <= l < p * (1 + self.whole_periods)

    # -- shapes --------------------------------------------------------------
    def layer_shapes(self, l: int) -> dict:
        """name -> (shape, stored dtype) of layer ``l``'s leaves."""
        E, dt = self.hidden, self.param_dtype
        out = {"attn_norm.weight": ((E,), dt), "mlp_norm.weight": ((E,), dt)}
        if self.kind(l) == "kda":
            H, D, R = self.kda_heads, self.kda_dim, self.kda_rank
            out.update({
                "attn.wq.weight": ((E, H * D), dt),
                "attn.wk.weight": ((E, H * D), dt),
                "attn.wv.weight": ((E, H * D), dt),
                "attn.conv": ((self.conv, 3 * H * D), dt),
                "attn.wf_a.weight": ((E, R), dt),
                "attn.wf_b.weight": ((R, H * D), dt),
                "attn.wb.weight": ((E, H), dt),
                "attn.wg_a.weight": ((E, R), dt),
                "attn.wg_b.weight": ((R, H * D), dt),
                "attn.wo.weight": ((H * D, E), dt),
                "attn.A_log": ((H,), "float32"),
                "attn.dt_bias": ((H * D,), "float32"),
                "attn.o_norm.weight": ((D,), dt)})
        else:
            H = self.heads
            out.update({
                "attn.wq.weight": ((E, H * (self.nope + self.rope)), dt),
                "attn.wkv_a.weight": ((E, self.kv_rank + self.rope), dt),
                "attn.kv_norm.weight": ((self.kv_rank,), dt),
                "attn.wkv_b.weight": ((self.kv_rank,
                                       H * (self.nope + self.v_dim)), dt),
                "attn.wo.weight": ((H * self.v_dim, E), dt)})
        if l < self.dense_layers:
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


# ---------------------------------------------------------------------------
# weights, by layer, from the seed
# ---------------------------------------------------------------------------

def decay_leaf_f32(key, name: str, layer, shape):
    """The decay's two leaves (``assumed`` in the configuration's file):
    ``A_log = log U(1, 16)`` and ``dt_bias`` the inverse softplus of ``dt
    ~ logU(1e-4, 1e-2)``, so that a step's decay ``exp(-A dt)`` spans
    ~0.85-0.9999 and the state still carries the template behind a
    512-token item (the family's own ``dt`` range, 1e-3..1e-1, forgets
    it: the configuration's file has the readings). Drawn as
    ``lib.weights`` draws: from ``(root key, leaf name, layer)``."""
    key = jax.random.fold_in(W.leaf_key(key, name), layer)
    if name.endswith("A_log"):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-4), math.log(1e-2)))
    return dt + jnp.log(-jnp.expm1(-dt))


def layer_params(a: Arch, key, l: int, at=None) -> dict:
    """Layer ``l``'s leaves in float32. ``at`` (python int or traced):
    the index a scanned layer's leaves are drawn at, where it differs
    from ``l``'s own (one jitted program serves a period's layer at
    every index)."""
    prefix, index = a.where(l)
    index = index if at is None else at
    out = {}
    for n, (shape, dt) in a.layer_shapes(l).items():
        if n in DECAY:
            out[n] = decay_leaf_f32(key, prefix + n, index, shape)
        else:
            out[n] = W.layer_leaf_f32(key, prefix + n, index, shape, dt)
    return out


def top_param(a: Arch, key, which: str):
    shape, dt = a.top_shapes()[which]
    return W.layer_leaf_f32(key, TOP[which], 0, shape, dt)


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------

def short_conv(u, w, cut=None):
    """``u`` [T, C], ``w`` [K, C]: ``y_t = sum_j w[j] u_{t-K+1+j}``,
    zeros ahead of the row. With ``cut``, outputs at ``t >= cut`` see
    nothing from before ``cut``."""
    T, K = u.shape[0], w.shape[0]
    t = jnp.arange(T)
    y = jnp.zeros_like(u)
    for j in range(K):
        back = K - 1 - j
        seen = t - back >= 0
        if cut is not None:
            seen &= (t < cut) | (t - back >= cut)
        y = y + jnp.where(seen[:, None], jnp.roll(u, back, axis=0), 0.0) * w[j]
    return y


def kda_row(h, p, a: Arch, q=_ident, mode: str = "", cut=None):
    """KDA attention of one row ``h`` [T, E] (normed input): [T, E]."""
    T = h.shape[0]
    H, D = a.kda_heads, a.kda_dim
    pre = jnp.concatenate(
        [_mm("te,ef->tf", h, p[f"attn.w{n}.weight"], q) for n in "qkv"], -1)
    act = jax.nn.silu(short_conv(pre, p["attn.conv"], cut))
    qh, kh, vh = (x.reshape(T, H, D) for x in jnp.split(act, 3, -1))
    qh = qh * jax.lax.rsqrt(jnp.sum(qh * qh, -1, keepdims=True) + 1e-6) \
        * D ** -0.5
    kh = kh * jax.lax.rsqrt(jnp.sum(kh * kh, -1, keepdims=True) + 1e-6)
    dt = jax.nn.softplus(
        _mm("tr,rf->tf", _mm("te,er->tr", h, p["attn.wf_a.weight"], q),
            p["attn.wf_b.weight"], q) + p["attn.dt_bias"])
    g = -jnp.exp(p["attn.A_log"])[:, None] * dt.reshape(T, H, D)
    beta = jax.nn.sigmoid(_mm("te,eh->th", h, p["attn.wb.weight"], q))
    reset = (jnp.arange(T) == (-1 if cut is None else cut))

    def token(S, x):
        qt, kt, vt, gt, bt, rs = x
        S = jnp.where(rs, 0.0, S) * jnp.exp(gt)[..., None]
        r = vt - jnp.einsum("hkv,hk->hv", S, kt, precision=HI)
        S = S + (bt[:, None] * kt)[..., None] * r[:, None, :]
        if mode == "state_bf16":
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)

    _, o = jax.lax.scan(token, jnp.zeros((H, D, D), jnp.float32),
                        (qh, kh, vh, g, beta, reset))
    gate = jax.nn.sigmoid(
        _mm("tr,rf->tf", _mm("te,er->tr", h, p["attn.wg_a.weight"], q),
            p["attn.wg_b.weight"], q)).reshape(T, H, D)
    o = rms_norm(o, p["attn.o_norm.weight"], a.eps) * gate
    return _mm("tf,fe->te", o.reshape(T, H * D), p["attn.wo.weight"], q)


def mla_row(h, p, a: Arch, q=_ident, head_block: int = 8):
    """Expanded latent attention of one row ``h`` [T, E], no rotation,
    a full causal mask, ``head_block`` heads at a time."""
    T = h.shape[0]
    H, N, R, V = a.heads, a.nope, a.rope, a.v_dim
    qh = _mm("te,ef->tf", h, p["attn.wq.weight"], q).reshape(T, H, N + R)
    kv = _mm("te,ef->tf", h, p["attn.wkv_a.weight"], q)
    c = rms_norm(kv[:, :a.kv_rank], p["attn.kv_norm.weight"], a.eps)
    k_r = kv[:, a.kv_rank:]                                    # [T, R]
    kvh = _mm("tc,cf->tf", c, p["attn.wkv_b.weight"], q).reshape(
        T, H, N + V)
    causal = jnp.tril(jnp.ones((T, T), bool))
    g = math.gcd(H, head_block)

    def heads(args):
        qb, kb = args                          # [T, g, N + R], [T, g, N + V]
        s = (jnp.einsum("tgn,sgn->gts", qb[..., :N], kb[..., :N],
                        precision=HI)
             + jnp.einsum("tgr,sr->gts", qb[..., N:], k_r, precision=HI))
        s = jnp.where(causal, s * (N + R) ** -0.5, -jnp.inf)
        return jnp.einsum("gts,sgv->tgv", jax.nn.softmax(s, -1),
                          kb[..., N:], precision=HI)

    def split(t):                          # [T, H, d] -> [H/g, T, g, d]
        return jnp.moveaxis(t.reshape(T, H // g, g, t.shape[-1]), 1, 0)

    out = jax.lax.map(heads, (split(qh), split(kvh)))
    out = jnp.moveaxis(out, 0, 1).reshape(T, H * V)
    return _mm("tf,fe->te", out, p["attn.wo.weight"], q)


def layer(x, p, a: Arch, l: int, q=_ident, mode: str = "", cut=None):
    """Layer ``l`` over whole rows ``x`` [B, T, E]."""
    h = rms_norm(x, p["attn_norm.weight"], a.eps)
    if a.kind(l) == "kda":
        # the rows side by side: a scan over tokens is as many serial
        # steps for six rows as for one
        x = x + jax.vmap(lambda r: kda_row(r, p, a, q, mode, cut))(h)
    else:
        x = x + jax.lax.map(lambda r: mla_row(r, p, a, q), h)
    return (dense_mlp(x, p, a, q) if l < a.dense_layers
            else moe_mlp(x, p, a, q))


def forward_logits(a: Arch, seed: int, ids, mode: str = "", cut=None):
    """Float32 logits [B, T, V] of whole rows (the CPU tests' oracle)."""
    key = W.root_key(seed)
    x = top_param(a, key, "embed")[jnp.asarray(ids, jnp.int32)]
    for l in range(a.layers):
        x = layer(x, layer_params(a, key, l), a, l, mode=mode,
                  cut=cut if mode == "no_state_restore" else None)
    norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")
    return jax.vmap(lambda r: head_logits(r, norm, head, a))(x)


# ---------------------------------------------------------------------------
# serving: logits of a padded sequence, layer by layer
# ---------------------------------------------------------------------------

def serve_logit_gaps(a: Arch, seed: int, seqs, spans,
                     precision: str = "float32", cut=None):
    """As ``reference_latent.serve_logit_gaps``: ``seqs`` [R, S] int32,
    each row a prompt followed by the tokens served for it, zero-padded
    (causal and recurrent, so a sequence never sees its padding);
    ``spans[r]`` is ``(prompt length, prompt + served length)``.
    Returns two lists, for each row a float32 array over served
    positions: the gap ``best logit - logit of the served token`` under
    the float32 forward, and the reference's own margin ``best - second
    best`` there. With ``precision`` ``"fp8"`` / ``"state_bf16"`` /
    ``"no_state_restore"`` the token is the one THAT forward puts first
    at the position (``cut``: where the last one forgets)."""
    key = W.root_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    low = precision != "float32"
    quant = precision == "fp8"
    mode = precision if precision in ("state_bf16",
                                      "no_state_restore") else ""
    cut = cut if mode == "no_state_restore" else None

    @functools.partial(jax.jit, static_argnames=("l", "alt"))
    def run_layer(key, x, at, l, alt):
        p = layer_params(a, key, l, at)
        if not alt:
            return layer(x, p, a, l)
        return layer(x, p, a, l, fp8 if quant else _ident, mode, cut)

    @jax.jit
    def gaps(key, x, x_low, ids):
        norm, head = top_param(a, key, "norm"), top_param(a, key, "lm_head")

        def row(args):
            xr, xl, idr = args
            lg = head_logits(xr, norm, head, a)[:-1]
            tok = (jnp.argmax(head_logits(xl, norm, head, a,
                                          fp8 if quant else _ident)[:-1], -1)
                   if low else idr[1:])
            top2 = jax.lax.top_k(lg, 2)[0]
            return (top2[:, 0] - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], top2[:, 0] - top2[:, 1])

        return jax.lax.map(row, (x, x_low, ids))

    x = jax.jit(lambda key, ids: top_param(a, key, "embed")[ids])(key, seqs)
    x_low = x
    p = a.period
    for l in range(a.layers):
        # a scanned layer compiles once for its place in the period
        proto = p + l % p if a.scanned(l) else l
        at = jnp.asarray(a.where(l)[1], jnp.int32)
        x_low = run_layer(key, x_low, at, proto, True) if low else x_low
        x = run_layer(key, x, at, proto, False)
    g, m = (np.asarray(t, np.float32)
            for t in gaps(key, x, x_low if low else x, seqs))
    return ([g[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)],
            [m[r, n0 - 1:total - 1] for r, (n0, total) in enumerate(spans)])
