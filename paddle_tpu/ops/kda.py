"""Kimi Delta Attention (KDA): a gated delta-rule linear attention with
a per-channel decay, in the three forms of ONE arithmetic that serving
needs. Per head, with a state ``S`` in R^{dk x dv} (float32):

    S~  = Diag(alpha_t) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

``alpha_t`` in (0, 1]^dk (given as its log ``g_t <= 0``), ``beta_t`` in
[0, 1], ``q_t``/``k_t`` in R^dk, ``v_t`` in R^dv.

- :func:`kda_sequential` — a ``lax.scan`` over single tokens: the oracle.
- :func:`kda_chunked` — a prefill chunk in chunks of 64 tokens, the WY /
  UT-transform matmul form, the state carried in and out (derivation at
  the function).
- :func:`kda_step` — one token against a stacked state ``[L, B, H, dk,
  dv]`` at layer ``layer``, the updated stack handed back: on one TPU
  chip the Pallas kernel ``ptpu_kda_step`` (``ops/pallas/kda_step.py``:
  the state read once and written once, in place), XLA's lines
  everywhere else — picked from shapes and backend, no flag; which arm
  a trace took is counted in :data:`step_arms`.

**Padding is the identity.** A position with ``alpha = 1`` (``g = 0``)
and ``beta = 0`` leaves the state as it was, bit for bit
(:func:`mask_padding`): a prefill bucket's padded tail, an idle slot of
the fused decode step and the chunked form's own fill to whole chunks
all go through it. :func:`short_conv` is the family's causal depthwise
convolution with its tail carried the same way (the tail is taken at the
true length).
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

__all__ = ["kda_sequential", "kda_chunked", "kda_step", "mask_padding",
           "short_conv", "step_arms", "CHUNK"]

CHUNK = 64          # tokens of one chunk of the chunked form
_SUB = 16           # sub-block of the pairwise decays (see kda_chunked)
_HI = jax.lax.Precision.HIGHEST

# which arm ``kda_step`` took, counted where a program is traced (as
# ``models._common.paged_attn_arms``): ``"kernel"`` — ``ptpu_kda_step``
# — or ``"xla"``. ``GenerationEngine.stats()["kda_step"]`` reads the
# difference around the trace of its step.
step_arms: collections.Counter = collections.Counter()


def mask_padding(g, beta, length):
    """``(g, beta)`` with every position at or past ``length`` made the
    identity (``g = 0``, ``beta = 0``). ``g`` [B, T, H, dk], ``beta``
    [B, T, H]; ``length`` None (no padding), a scalar or [B]."""
    if length is None:
        return g, beta
    T = g.shape[1]
    live = jnp.arange(T)[None, :] < jnp.reshape(
        jnp.asarray(length, jnp.int32), (-1, 1))             # [B|1, T]
    return (jnp.where(live[..., None, None], g, 0.0),
            jnp.where(live[..., None], beta, 0.0))


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def _one_token(S, q, k, v, g, beta):
    """The recurrence's one step on ``S`` [..., dk, dv]; q/k/g [..., dk],
    v [..., dv], beta [...]. float32, products at "highest"."""
    S = S * jnp.exp(g)[..., None]
    r = v - jnp.einsum("...kv,...k->...v", S, k, precision=_HI)
    S = S + (beta[..., None] * k)[..., None] * r[..., None, :]
    return S, jnp.einsum("...kv,...k->...v", S, q, precision=_HI)


def kda_sequential(q, k, v, g, beta, state=None, length=None):
    """Token by token. ``q``/``k``/``g`` [B, T, H, dk], ``v`` [B, T, H,
    dv], ``beta`` [B, T, H], ``state`` [B, H, dk, dv] float32 (None =
    zeros). Returns ``(o [B, T, H, dv] float32, state)``."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    g, beta = mask_padding(g, beta, length)
    B, T, H, dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)

    def step(S, x):
        S, o = _one_token(S, *x)
        return S, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _pairwise(x, y, G, inclusive: bool):
    """``M[r, i] = sum_d x_r[d] y_i[d] exp(G_r[d] - G_i[d])`` for ``i <
    r`` (``i <= r`` with ``inclusive``), else 0, inside one chunk: x, y,
    G [..., C, dk] with G the running sum of the log decays. Never
    forms ``exp(-G)`` on its own, which overflows once a channel has
    decayed by e^88 inside a chunk: a pair in different sub-blocks of
    16 meets at the start of the later one (both factors <= 1), a pair
    inside one sub-block takes its own exponent."""
    C, dk = x.shape[-2:]
    n, c = C // _SUB, _SUB
    lead = x.shape[:-2]
    xb = x.reshape(lead + (n, c, dk))
    Gb = G.reshape(lead + (n, c, dk))
    # G at the start of each sub-block: the sum through the token ahead
    G0 = jnp.concatenate(
        [jnp.zeros(lead + (1, dk), G.dtype), Gb[..., :-1, -1, :]], axis=-2)
    x_in = xb * jnp.exp(Gb - G0[..., None, :])            # <= |x|
    # y against every LATER sub-block's start: [.., n, C, dk]
    before = (jnp.arange(C)[None, :] < (jnp.arange(n) * c)[:, None])
    y_to = jnp.where(
        before[..., None],
        y[..., None, :, :] * jnp.exp(jnp.where(
            before[..., None], G0[..., :, None, :] - G[..., None, :, :],
            0.0)), 0.0)
    off = jnp.einsum("...ard,...aid->...ari", x_in, y_to, precision=_HI)
    # the diagonal sub-blocks, pair by pair
    yb = y.reshape(lead + (n, c, dk))
    tri = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :] if inclusive
           else jnp.arange(c)[:, None] > jnp.arange(c)[None, :])
    expo = jnp.where(tri[..., None],
                     Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf)
    diag = jnp.sum(xb[..., :, None, :] * yb[..., None, :, :]
                   * jnp.exp(expo), axis=-1)               # [.., n, c, c]
    at = jnp.arange(n)
    full = off.reshape(lead + (n, c, n, c))
    full = full.at[..., at, :, at, :].add(jnp.moveaxis(diag, -3, 0))
    return full.reshape(lead + (C, C))


def kda_chunked(q, k, v, g, beta, state=None, length=None):
    """A prefill chunk in the matmul form, chunks of ``CHUNK`` tokens.
    Arguments and result as :func:`kda_sequential`; ``T`` need not be a
    multiple of 64 (the fill is padding, i.e. the identity).

    Inside one chunk with incoming state ``S0``, ``G_r = sum_{i<=r}
    g_i`` and ``u_r = beta_r (v_r - S~_r^T k_r)`` (so ``S_r = Diag(a_r)
    S_{r-1} + k_r u_r^T``):

        A[r, i] = beta_r sum_d k_r k_i exp(G_r - G_i)      (i < r)
        (I + A) U = beta * (V - (K * exp(G)) S0)           (UT transform)
        O = (Q * exp(G)) S0 + M U,  M[r, i] = sum_d q_r k_i exp(G_r - G_i)  (i <= r)
        S_C = Diag(exp(G_C)) S0 + (K * exp(G_C - G))^T U

    ``T = (I + A)^-1`` (unit lower triangular) does not depend on the
    state, so it is made for all chunks at once; the scan over chunks
    carries the state through four small products."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    g, beta = mask_padding(g, beta, length)
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, dk, dv), jnp.float32)
    pad = -T % CHUNK
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    N = (T + pad) // CHUNK

    def chunks(x):          # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape((B, N, CHUNK, H) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                           # [N, B, H, C, dk]
    A = beta[..., None] * _pairwise(k, k, G, inclusive=False)
    M = _pairwise(q, k, G, inclusive=True)
    eye = jnp.eye(CHUNK, dtype=jnp.float32)
    Tm = jax.scipy.linalg.solve_triangular(
        eye + A, jnp.broadcast_to(eye, A.shape), lower=True,
        unit_diagonal=True)
    decay = jnp.exp(G)
    k_in, q_in = k * decay, q * decay
    k_out = k * jnp.exp(G[..., -1:, :] - G)
    last = decay[..., -1, :]                             # [N, B, H, dk]

    def chunk(S, x):
        Tm, M, k_in, q_in, k_out, last, v, beta = x
        rhs = beta[..., None] * (v - jnp.einsum(
            "bhck,bhkv->bhcv", k_in, S, precision=_HI))
        U = jnp.einsum("bhrc,bhcv->bhrv", Tm, rhs, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_in, S, precision=_HI)
             + jnp.einsum("bhrc,bhcv->bhrv", M, U, precision=_HI))
        S = S * last[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, U, precision=_HI)
        return S, o

    state, o = jax.lax.scan(chunk, state.astype(jnp.float32),
                            (Tm, M, k_in, q_in, k_out, last, v, beta))
    # [N, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
        B, N * CHUNK, H, dv)
    return o[:, :T], state


def kda_step(rows, layer, q, k, v, g, beta):
    """One token of every sequence against the stacked state ``rows``
    [L, B, H, dk, dv] (float32) at ``layer`` (python int or traced
    scalar). ``q``/``k``/``g`` [B, H, dk], ``v`` [B, H, dv], ``beta``
    [B, H] (a sequence to be left alone: ``g = 0, beta = 0``). Returns
    ``(o [B, H, dv] float32, rows)`` with the layer's state replaced."""
    from paddle_tpu.ops.pallas import kda_step as _ks

    q, k, v, g, beta = _f32(q, k, v, g, beta)
    if _ks.supported(rows, q, v):
        step_arms["kernel"] += 1
        return _ks.kda_step(rows, layer, q, k, v, g, beta)
    step_arms["xla"] += 1
    S = (rows[layer] if isinstance(layer, int) else
         jax.lax.dynamic_index_in_dim(rows, layer, 0, keepdims=False))
    S, o = _one_token(S, q, k, v, g, beta)
    return o, jax.lax.dynamic_update_index_in_dim(rows, S, layer, 0)


def short_conv(x, weight, tail=None, length=None):
    """Causal depthwise convolution of ``K`` taps, no bias: ``y_t =
    sum_j w[j] x_{t-K+1+j}``. ``x`` [B, T, D]; ``weight`` [K, D];
    ``tail`` [B, K-1, D] the inputs ahead of the chunk (None = zeros).
    Returns ``(y [B, T, D] in x's dtype, new tail)`` — the last ``K-1``
    inputs up to ``length`` (None = T; a scalar or [B]), so a padded
    chunk leaves the tail its true tokens give, and a chunk of length 0
    the tail it found."""
    B, T, D = x.shape
    K = weight.shape[0]
    if tail is None:
        tail = jnp.zeros((B, K - 1, D), x.dtype)
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)   # [B, T+K-1, D]
    w = weight.astype(jnp.float32)
    y = sum(seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
    if length is None:
        new = seq[:, T:]
    else:
        n = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
        new = jax.vmap(lambda s, at: jax.lax.dynamic_slice_in_dim(
            s, at, K - 1, 0))(seq, n)
    return y.astype(x.dtype), new.astype(tail.dtype)
