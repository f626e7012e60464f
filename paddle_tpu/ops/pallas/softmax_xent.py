"""Fused softmax cross-entropy over [N, V] — the LM-head loss.

Reference CUDA equivalents: ``paddle/fluid/operators/
softmax_with_cross_entropy_op.cu`` and ``operators/math/softmax.cu``.
The fused formulation never stores the [N, V] probability matrix:

- forward: a Pallas kernel streams vocab blocks through VMEM computing
  the row log-sum-exp online; the label logit is a cheap gather outside.
- backward: ``softmax = exp(x - lse)`` is recomputed blockwise in a
  second kernel (saving only ``lse`` [N] as residual instead of the
  [N, V] probabilities jax.nn.log_softmax would keep), and the one-hot
  subtraction is a scatter-add outside.

Alignment: row blocks of 128 × vocab blocks of 256 → requires
``N % 128 == 0`` and ``V % 256 == 0`` (Llama's 32000 qualifies); callers
fall back to the jnp path otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _support

_BLOCK_N = 128
_BLOCK_V = 256
_NEG_INF = -1e30

# Auto-dispatch ceiling on the vocab width. Measured on a v5e chip
# (fwd+bwd, N=8192, bf16): the kernel wins below ~2k classes
# (V=1024: 4.8ms vs XLA 6.8ms) and loses above (V=4096: 6.7 vs 5.6;
# V=50304: 22.8 vs 11.6 — XLA fuses log_softmax into the surrounding
# graph and reads bf16, while this kernel re-reads the logits for lse
# and dx). LM-head losses must therefore stay on the XLA path; callers
# can still invoke the kernel explicitly for any supported shape.
DISPATCH_MAX_V = 2048


def supported(logits, labels) -> bool:
    if logits.ndim != 2 or labels.ndim != 1:
        return False
    n, v = logits.shape
    if labels.shape[0] != n:
        return False
    # n must tile by the row block (128, or n itself when n < 128 and a
    # multiple of 8); v must tile by the vocab block
    if n % _row_block(n) or n % 8 or v % _BLOCK_V:
        return False
    return logits.dtype in (jnp.float32, jnp.bfloat16)


def _row_block(n: int) -> int:
    return min(_BLOCK_N, n)


def _lse_kernel(x_ref, lse_ref, m_ref, l_ref, *, nv):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    x = x_ref[...].astype(jnp.float32)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    l_ref[:, :1] = (l_ref[:, :1] * jnp.exp(m_prev - m_new)
                    + jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True))
    m_ref[:, :1] = m_new

    @pl.when(iv == nv - 1)
    def _():
        lse = m_ref[:, :1] + jnp.log(l_ref[:, :1])
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _dx_kernel(x_ref, lse_ref, g_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    lse = lse_ref[:, :1]
    g = g_ref[:, :1]
    dx_ref[...] = (jnp.exp(x - lse) * g).astype(dx_ref.dtype)


def _lse_call(logits):
    """Raw kernel: lane-replicated [n, 128] log-sum-exp."""
    n, v = logits.shape
    br = _row_block(n)
    nb, nv = n // br, v // _BLOCK_V
    lse = pl.pallas_call(
        functools.partial(_lse_kernel, nv=nv),
        grid=(nb, nv),
        in_specs=[pl.BlockSpec((br, _BLOCK_V), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
        ],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_softmax_xent_lse",
    )(logits)
    return lse


def _dx_call(logits, lse_b, g_b):
    """Raw kernel: softmax(logits)·g from lane-replicated lse/g."""
    n, v = logits.shape
    br = _row_block(n)
    nb, nv = n // br, v // _BLOCK_V
    return pl.pallas_call(
        _dx_kernel,
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((br, _BLOCK_V), lambda i, j: (i, j)),
            pl.BlockSpec((br, 128), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 128), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, _BLOCK_V), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(logits.shape, logits.dtype),
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "parallel")),
        interpret=_support.interpret(),
        name="ptpu_softmax_xent_dx",
    )(logits, lse_b, g_b)


def _lse_dispatch(logits, part):
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.xent_lse()(logits)[:, 0]
    return _lse_call(logits)[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sce(part, logits, labels):
    lse = _lse_dispatch(logits, part)
    sel = jnp.take_along_axis(
        logits, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    return lse - sel.astype(jnp.float32)


def _sce_fwd(part, logits, labels):
    lse = _lse_dispatch(logits, part)
    sel = jnp.take_along_axis(
        logits, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    return lse - sel.astype(jnp.float32), (logits, labels, lse)


def _sce_bwd(part, res, g):
    logits, labels, lse = res
    n, v = logits.shape
    g = g.astype(jnp.float32)
    lse_b = jnp.broadcast_to(lse[:, None], (n, 128))
    g_b = jnp.broadcast_to(g[:, None], (n, 128))
    if part:
        from paddle_tpu.ops.pallas import _partition
        dx = _partition.xent_dx()(logits, lse_b, g_b)
    else:
        dx = _dx_call(logits, lse_b, g_b)
    # one-hot subtraction: dx[i, labels[i]] -= g[i]
    dx = dx.at[jnp.arange(n), labels].add((-g).astype(dx.dtype))
    return dx, jnp.zeros(labels.shape, dtype=jax.dtypes.float0)


_sce.defvjp(_sce_fwd, _sce_bwd)


def softmax_cross_entropy(logits, labels, *, partitioned: bool = False):
    """Per-row loss ``lse(logits) - logits[labels]`` for [N, V] logits and
    int [N] labels. ``supported(logits, labels)`` must hold.
    ``partitioned`` routes the kernels through the shard_map units so they
    run per-shard under a multi-device mesh (including a Megatron-style
    vocab-sharded lm head: local lse + log-sum-exp combine over the vocab
    axes)."""
    return _sce(bool(partitioned), logits, labels)
