"""Mamba (selective state-space model) — BASELINE.json config
"Mamba-2 selective-scan".

TPU-native formulation: the selective recurrence
``h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t`` is a linear first-order
recurrence, so it runs as ``jax.lax.associative_scan`` (parallel prefix
scan, log-depth on TPU) instead of the reference-style sequential CUDA
kernel. A Pallas chunked-scan kernel can replace the inner scan for the
hot path; the math here is the specification it must match.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common import Embedding, Linear
from paddle_tpu.nn.initializer import Normal, Uniform
from paddle_tpu.nn.norm import RMSNorm
from paddle_tpu.nn.scan import ScannedBlocks
from paddle_tpu.ops import pallas as _pk

__all__ = ["MambaConfig", "MambaBlock", "MambaForCausalLM",
           "selective_scan"]


@dataclass(frozen=True)
class MambaConfig:
    vocab_size: int = 50277
    hidden_size: int = 768
    num_layers: int = 24
    state_size: int = 16
    conv_kernel: int = 4
    expand: int = 2
    dt_rank: int | None = None        # defaults to ceil(hidden/16)
    dtype: str = "float32"
    remat: bool = False
    # chunked scan: peak memory drops T/chunk (see selective_scan); None =
    # one-shot scan (fine for short T, OOMs for T in the thousands)
    scan_chunk_size: int | None = 128
    # LM-head loss path — see LlamaConfig.lm_head_mode (tied embeddings:
    # the fused kernel reads the transposed table)
    lm_head_mode: str = "dense"

    @property
    def inner_size(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.hidden_size // 16)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_layers=2,
                    state_size=8, dtype="float32")
        base.update(kw)
        return cls(**base)

    def num_params(self) -> int:
        """Exact parameter count (embeddings are TIED — counted once)."""
        E, Ei, N, R = (self.hidden_size, self.inner_size,
                       self.state_size, self.rank)
        per_layer = (E * 2 * Ei                     # in_proj
                     + Ei * self.conv_kernel + Ei   # conv w + b
                     + Ei * (R + 2 * N)             # x_proj
                     + R * Ei + Ei                  # dt_proj w + b
                     + Ei * N + Ei                  # A_log + D
                     + Ei * E                       # out_proj
                     + E)                           # norm
        return self.vocab_size * E + self.num_layers * per_layer + E


def selective_scan(u, delta, A, B, C, D, chunk_size: int | None = None,
                   return_state: bool = False, initial_state=None):
    """y = SSM(u) via parallel associative scan.

    u:[B,T,Ei] delta:[B,T,Ei] A:[Ei,N] B,C:[B,T,N] D:[Ei]

    ``chunk_size=None`` runs one associative scan over T — fastest, but
    it materializes the [B, T, Ei, N] discretized operands (the reason
    upstream Mamba needs a fused CUDA kernel). ``chunk_size=k`` instead
    runs a ``lax.scan`` over T/k chunks carrying only the [B, Ei, N]
    state, with the parallel scan inside each chunk: peak memory drops
    by T/k at one extra sequential dimension — the memory shape a long-
    context Mamba needs, kept XLA-fusible (no hand-written kernel; the
    within-chunk scan fuses into large elementwise blocks on the VPU).

    ``return_state=True`` additionally returns the final recurrent state
    ``h_T [B, Ei, N]``; ``initial_state`` seeds ``h_0`` (both = the
    decode/prefill handoff).
    """
    if chunk_size is None or chunk_size >= u.shape[1]:
        dA = jnp.exp(delta[..., None] * A)                   # [B,T,Ei,N]
        dBu = (delta * u)[..., None] * B[:, :, None, :]      # [B,T,Ei,N]

        def combine(left, right):
            a1, b1 = left
            a2, b2 = right
            return a1 * a2, a2 * b1 + b2

        cumA, h = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
        if initial_state is not None:
            # h_t += (prod_{<=t} dA) * h_0 — linearity of the recurrence
            h = h + cumA * initial_state[:, None]
        y = jnp.einsum("btin,btn->bti", h, C)
        y = y + u * D
        return (y, h[:, -1]) if return_state else y

    Bsz, T, Ei = u.shape
    k = int(chunk_size)
    if T % k:
        raise ValueError(f"T={T} not divisible by chunk_size={k}")

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h0, args):
        uc, dc, Bc, Cc = args                                # [B,k,...]
        dA = jnp.exp(dc[..., None] * A)                      # [B,k,Ei,N]
        dBu = (dc * uc)[..., None] * Bc[:, :, None, :]
        cumA, h = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
        # inject the carried state: h_t += (prod_{<=t} dA) * h0
        h = h + cumA * h0[:, None]
        yc = jnp.einsum("btin,btn->bti", h, Cc)
        return h[:, -1], yc

    def to_chunks(x):
        return jnp.moveaxis(
            x.reshape(Bsz, T // k, k, *x.shape[2:]), 1, 0)   # [nc,B,k,...]

    h0 = (initial_state if initial_state is not None
          else jnp.zeros((Bsz, Ei, A.shape[-1]), u.dtype))
    # per-chunk remat: without it the backward saves every chunk's scan
    # internals ([nc, B, k, Ei, N] — the full unchunked footprint again);
    # recomputing one chunk in backward keeps live memory at [B, k, Ei, N]
    h_last, ys = jax.lax.scan(jax.checkpoint(chunk_step, prevent_cse=False),
                              h0, (to_chunks(u), to_chunks(delta),
                                   to_chunks(B), to_chunks(C)))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, T, Ei) + u * D
    return (y, h_last) if return_state else y


class MambaBlock(Module):
    def __init__(self, cfg: MambaConfig, key=None):
        keys = rng.split_key(key, 5)
        E, Ei, N, R = (cfg.hidden_size, cfg.inner_size, cfg.state_size,
                       cfg.rank)
        dtype = jnp.dtype(cfg.dtype)
        self.in_proj = Linear(E, 2 * Ei, bias=False, key=keys[0], dtype=dtype)
        # depthwise causal conv weights [Ei, K]
        self.conv_weight = Uniform(-1, 1)(
            keys[1], (Ei, cfg.conv_kernel), dtype) / math.sqrt(cfg.conv_kernel)
        self.conv_bias = jnp.zeros((Ei,), dtype)
        self.x_proj = Linear(Ei, R + 2 * N, bias=False, key=keys[2],
                             dtype=dtype)
        self.dt_proj = Linear(R, Ei, key=keys[3], dtype=dtype)
        # S4D-real init: A_log so A = -exp(A_log) stays negative (stable)
        self.A_log = jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (Ei, N)).copy())
        self.D = jnp.ones((Ei,), jnp.float32)
        self.out_proj = Linear(Ei, E, bias=False, key=keys[4], dtype=dtype)
        self.norm = RMSNorm(E, dtype=dtype)
        self.state_size = N
        self.rank = R
        self.conv_kernel = cfg.conv_kernel
        self.scan_chunk_size = cfg.scan_chunk_size

    def _in_split(self, x):
        """norm + in_proj → (u_raw, z): the conv input and the gate."""
        xz = self.in_proj(self.norm(x))
        return jnp.split(xz, 2, axis=-1)

    def _ssm_coeffs(self, u):
        """u (post-conv activations, any leading dims) → (delta, B, C, A)
        in f32."""
        proj = self.x_proj(u)
        dt, Bc, Cc = jnp.split(proj, [self.rank,
                                      self.rank + self.state_size], axis=-1)
        delta = F.softplus(self.dt_proj(dt))
        A = -jnp.exp(self.A_log)                              # [Ei,N]
        return (delta.astype(jnp.float32), Bc.astype(jnp.float32),
                Cc.astype(jnp.float32), A)

    def _conv_seq(self, u_raw, left_ctx=None):
        """Causal depthwise conv over time for a [B, T, Ei] sequence.
        ``left_ctx`` [B, K-1, Ei] supplies the carried left context
        (decode prefill); None = K-1 zeros (sequence start). Returns
        ``(u, ctx)`` where ctx is the padded input the windows read —
        its last K-1 steps are the next carried tail."""
        K = self.conv_kernel
        if left_ctx is None:
            ctx = jnp.pad(u_raw, ((0, 0), (K - 1, 0), (0, 0)))
        else:
            ctx = jnp.concatenate([left_ctx.astype(u_raw.dtype), u_raw],
                                  axis=1)
        windows = jnp.stack(
            [ctx[:, i:i + u_raw.shape[1]] for i in range(K)],
            axis=-1)                                          # [B,T,Ei,K]
        u = jnp.einsum("btek,ek->bte", windows, self.conv_weight)
        return F.silu(u + self.conv_bias), ctx

    def __call__(self, x, training: bool = False):
        residual = x
        u_raw, z = self._in_split(x)                          # [B,T,Ei]
        u, _ = self._conv_seq(u_raw)
        delta, Bc, Cc, A = self._ssm_coeffs(u)
        T = u.shape[1]
        chunk = (self.scan_chunk_size
                 if self.scan_chunk_size and T % self.scan_chunk_size == 0
                 else None)
        uf = u.astype(jnp.float32)
        y = None
        mode = _pk.dispatch_mode()
        if mode != "off" and _pk.selective_scan_supported(
                uf, delta, A, Bc, Cc, self.D, chunk=chunk):
            y = _pk.selective_scan(
                uf, delta, A, Bc, Cc, self.D, chunk=chunk,
                partitioned=mode == "partitioned")
        if y is None:
            y = selective_scan(uf, delta, A, Bc, Cc, self.D,
                               chunk_size=chunk)
        y = y.astype(x.dtype) * F.silu(z)
        return residual + self.out_proj(y)

    # ---- stateful decode (the recurrent O(1)-per-token form) ----------

    def init_state(self, batch_size: int, dtype):
        """(conv tail [B, K-1, Ei], ssm state [B, Ei, N])."""
        Ei = self.conv_weight.shape[0]
        return (jnp.zeros((batch_size, self.conv_kernel - 1, Ei), dtype),
                jnp.zeros((batch_size, Ei, self.state_size), jnp.float32))

    def prefill(self, x, state):
        """Sequence forward that consumes AND returns decode state, so
        chunked prefill / continuation from a warm cache is exact: the
        carried conv tail replaces the causal zero-padding, and the
        carried SSM state seeds the scan (jnp path — runs once per
        generation; uses the same chunked-scan selection as __call__ so
        long prompts keep the chunked memory shape)."""
        conv_tail, h0 = state
        residual = x
        u_raw, z = self._in_split(x)
        K, T = self.conv_kernel, u_raw.shape[1]
        u, ctx = self._conv_seq(u_raw, left_ctx=conv_tail)
        delta, Bc, Cc, A = self._ssm_coeffs(u)
        chunk = (self.scan_chunk_size
                 if self.scan_chunk_size and T % self.scan_chunk_size == 0
                 else None)
        y, h_last = selective_scan(u.astype(jnp.float32), delta, A, Bc,
                                   Cc, self.D, chunk_size=chunk,
                                   return_state=True, initial_state=h0)
        y = y.astype(x.dtype) * F.silu(z)
        # explicit start index (NOT -(K-1): for K == 1 that is -0 and
        # would return the whole sequence instead of an empty tail)
        tail = ctx[:, ctx.shape[1] - (K - 1):]
        return residual + self.out_proj(y), (tail, h_last)

    def step(self, x, state):
        """One decode step: x [B, E], state from init_state/prefill."""
        conv_tail, h = state
        residual = x
        u_raw, z = self._in_split(x)                          # [B, Ei]
        window = jnp.concatenate([conv_tail, u_raw[:, None]], axis=1)
        u = jnp.einsum("bke,ek->be", window, self.conv_weight)
        u = F.silu(u + self.conv_bias)
        delta, Bc, Cc, A = self._ssm_coeffs(u)
        dA = jnp.exp(delta[..., None] * A)                    # [B,Ei,N]
        dBu = (delta * u.astype(jnp.float32))[..., None] * Bc[:, None, :]
        h = dA * h + dBu
        y = jnp.einsum("bin,bn->bi", h, Cc) + u.astype(jnp.float32) * self.D
        y = y.astype(x.dtype) * F.silu(z)
        return residual + self.out_proj(y), (window[:, 1:], h)


class MambaForCausalLM(Module):
    def __init__(self, cfg: MambaConfig, key=None):
        keys = rng.split_key(key, 2 + cfg.num_layers)
        dtype = jnp.dtype(cfg.dtype)
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_init=Normal(0.0, 0.02), dtype=dtype,
                               key=keys[0])
        self.blocks = ScannedBlocks(
            lambda i: MambaBlock(cfg, key=keys[2 + i]), cfg.num_layers,
            remat=cfg.remat)
        self.norm = RMSNorm(cfg.hidden_size, dtype=dtype)
        self.config = cfg

    def hidden_states(self, input_ids, training: bool = False):
        x = self.embed(input_ids)
        x = self.blocks(x, training=training)
        return self.norm(x)

    def __call__(self, input_ids, training: bool = False):
        x = self.hidden_states(input_ids, training=training)
        return x @ self.embed.weight.T       # tied embeddings

    def loss(self, input_ids, labels, ignore_index: int = -100,
             training: bool = True):
        from paddle_tpu.models._common import causal_lm_loss
        return causal_lm_loss(self, self.embed.weight.T, input_ids,
                              labels, ignore_index, training)

    # ---- decode interface (models/generation.py contract) -------------
    # Unlike attention models there is no positional KV cache: the
    # "cache" is the per-layer recurrent state (conv tail + SSM state),
    # O(1) in sequence length — Mamba's whole serving advantage. The
    # ``max_len``/``index`` arguments of the shared contract are
    # accepted and ignored (the state is positionless).

    def init_cache(self, batch_size: int, max_len: int | None = None,
                   dtype=None):
        cfg = self.config
        dtype = jnp.dtype(dtype or cfg.dtype)
        if dtype == jnp.int8:
            # the attention families' cache_dtype=int8 (quantized KV)
            # has no analogue here — the recurrent state is O(1) and
            # accumulates, so it stays in the model's float dtype
            dtype = jnp.dtype(cfg.dtype)
        elif not jnp.issubdtype(dtype, jnp.floating):
            raise ValueError(
                f"cache dtype {dtype} unsupported: use a float dtype "
                "(or jnp.int8, which Mamba maps back to its float "
                "state — the recurrent state accumulates)")
        L, Ei = cfg.num_layers, cfg.inner_size
        return (jnp.zeros((L, batch_size, cfg.conv_kernel - 1, Ei), dtype),
                jnp.zeros((L, batch_size, Ei, cfg.state_size),
                          jnp.float32))

    def forward_with_cache(self, input_ids, cache, index: int = 0):
        """Returns (logits [B, T, V], new cache). T > 1 = prefill (the
        parallel scan, consuming AND capturing each layer's state — so
        chunked prefill / warm-cache continuation is exact); T == 1 =
        one recurrent step. ``index`` is ignored (see class note)."""
        x = self.embed(input_ids)
        if input_ids.shape[1] == 1:
            h, new_cache = self.blocks.scan_with(
                x[:, 0], cache, fn=lambda blk, xc, st: blk.step(xc, st))
            h = h[:, None]
        else:
            h, new_cache = self.blocks.scan_with(
                x, cache, fn=lambda blk, xc, st: blk.prefill(xc, st))
        logits = self.norm(h) @ self.embed.weight.T
        return logits, new_cache
