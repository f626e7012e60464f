"""Fused LM-head ⊗ cross-entropy: loss(h @ W) with logits never stored.

Reference equivalent: ``paddle/fluid/operators/softmax_with_cross_entropy_op.cu``
fused with the preceding FC — the reference fuses softmax+xent at any
vocab size but still materializes the [N, V] logits the FC produced. At
real LM vocab (32k–50k) that tensor is the single largest activation in
the model (bench shape: 16384 × 32000 f32 = 2.1 GB forward + the same
again for dlogits in backward). This module fuses the hidden→vocab
matmul *into* the loss so neither ever exists in HBM:

- forward: grid (row blocks × vocab tiles). Each step computes one
  ``[bN, bV]`` logits tile on the MXU in VMEM (``h_blk @ W_tile``,
  f32 accumulation), folds it into an online max/log-sum-exp merge, and
  picks up the label logit by comparing an in-tile column iota against
  the (lane-replicated) labels. Outputs: lse [N] and the selected logit
  [N]; loss = lse − sel.
- backward dH: same grid; recomputes the tile, forms
  ``dlogits = (softmax − onehot)·g`` in registers, and accumulates
  ``dlogits @ W_tileᵀ`` into a VMEM [bN, E] scratch, emitted on the
  last vocab tile.
- backward dW: transposed grid (vocab outer, rows inner) so each
  ``[E, bV]`` output block stays resident in VMEM while all row blocks
  stream through, accumulating ``h_blkᵀ @ dlogits`` in f32 directly in
  the output ref.

Cost model: 10·N·E·V matmul FLOPs vs the unfused 6 (both backward
kernels recompute their logits tile), in exchange for O(N·V) → O(N)
loss-path HBM traffic and activation memory. At bench shapes the
lm-head is ~7% of model FLOPs, so the ~4% FLOP overhead buys back
gigabytes of HBM — the lever for larger batch/seq.

Alignment: E % 128 == 0, V divisible by one of the candidate vocab
tiles, rows divisible by the row block (callers pad rows or fall back).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _support

_NEG_INF = -1e30
_LANES = 128

# Vocab-tile candidates, largest first. The tile must divide V exactly
# (no masking path — a partial tile would poison the running max) and
# the per-tile VMEM working set must fit ~16 MB with double-buffering.
_BV_CANDIDATES = (1024, 896, 768, 640, 512, 384, 256, 128)
# bytes of VMEM per vocab-tile column the kernel holds, by kernel kind:
# fwd/dh hold the W tile (itemsize, double-buffered); dw additionally
# holds its f32 accumulator output block (double-buffered by the
# pipeline). At the headline E=2048, V=32000 bf16 these budgets pick
# bv=640 for fwd/dh and bv=256 for dw, and all three compile under
# libtpu 0.0.34's default scoped-VMEM limit (chip_smoke.py).
_BUDGET_FWD = 6 * 1024 * 1024
_BUDGET_DW = 10 * 1024 * 1024


def _pick_bv(e: int, v: int, itemsize: int, *, for_dw: bool = False):
    per_col = e * itemsize * 2 + (e * 4 * 2 if for_dw else 0)
    budget = _BUDGET_DW if for_dw else _BUDGET_FWD
    for bv in _BV_CANDIDATES:
        if v % bv == 0 and bv * per_col <= budget:
            return bv
    return None


def _pick_bn(n: int, e: int) -> int:
    bn = 256 if e <= 2048 else 128
    return min(bn, n)


def supported(hidden, weight, labels) -> bool:
    if hidden.ndim != 2 or weight.ndim != 2 or labels.ndim != 1:
        return False
    n, e = hidden.shape
    e2, v = weight.shape
    if e2 != e or labels.shape[0] != n:
        return False
    if e % _LANES or n < 8 or n % 8:
        return False
    bn = _pick_bn(n, e)
    if n % bn:
        return False
    itemsize = jnp.dtype(weight.dtype).itemsize
    if (_pick_bv(e, v, itemsize) is None
            or _pick_bv(e, v, itemsize, for_dw=True) is None):
        return False
    return (hidden.dtype in (jnp.float32, jnp.bfloat16)
            and weight.dtype == hidden.dtype
            and jnp.issubdtype(labels.dtype, jnp.integer))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, lab_ref, lse_ref, sel_ref, m_ref, l_ref,
                s_ref, *, nv, bv):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        s_ref[:] = jnp.zeros_like(s_ref)

    logits = jax.lax.dot(h_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32)
    bn = logits.shape[0]
    col = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    hit = col == lab_ref[:, :1]
    s_ref[:, :1] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1,
                            keepdims=True)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    l_ref[:, :1] = (l_ref[:, :1] * jnp.exp(m_prev - m_new)
                    + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
    m_ref[:, :1] = m_new

    @pl.when(iv == nv - 1)
    def _():
        lse = m_ref[:, :1] + jnp.log(l_ref[:, :1])
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        sel_ref[...] = jnp.broadcast_to(s_ref[:, :1], sel_ref.shape)


def _dh_kernel(h_ref, w_ref, lab_ref, lse_ref, g_ref, dh_ref, acc_ref,
               *, nv, bv):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    logits = jax.lax.dot(h_ref[...], w, preferred_element_type=jnp.float32)
    bn = logits.shape[0]
    p = jnp.exp(logits - lse_ref[:, :1])
    col = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    onehot = (col == lab_ref[:, :1]).astype(jnp.float32)
    dlog = ((p - onehot) * g_ref[:, :1]).astype(w.dtype)
    acc_ref[...] += jax.lax.dot_general(
        dlog, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(iv == nv - 1)
    def _():
        dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, lab_ref, lse_ref, g_ref, dw_ref, acc_ref,
               *, nb, bv):
    iv, ii = pl.program_id(0), pl.program_id(1)

    @pl.when(ii == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    h = h_ref[...]
    logits = jax.lax.dot(h, w_ref[...], preferred_element_type=jnp.float32)
    bn = logits.shape[0]
    p = jnp.exp(logits - lse_ref[:, :1])
    col = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    onehot = (col == lab_ref[:, :1]).astype(jnp.float32)
    dlog = ((p - onehot) * g_ref[:, :1]).astype(h.dtype)
    acc_ref[...] += jax.lax.dot_general(
        h, dlog, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ii == nb - 1)
    def _():
        # f32 accumulation in scratch, emit in the weight dtype — the
        # [E, V] f32 intermediate (262 MB at bench shape) never exists
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


# ---------------------------------------------------------------------------
# raw calls (local shapes; also the per-shard lowering for _partition)
# ---------------------------------------------------------------------------

def _fwd_call(hidden, weight, lab_b):
    """(lse [n, 128], sel [n, 128]) — lane-replicated row stats."""
    n, e = hidden.shape
    v = weight.shape[1]
    bn = _pick_bn(n, e)
    bv = _pick_bv(e, v, jnp.dtype(weight.dtype).itemsize)
    nb, nv = n // bn, v // bv
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nv=nv, bv=bv),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((bn, e), lambda i, j: (i, 0)),
            pl.BlockSpec((e, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
        ],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_linear_xent_fwd",
    )(hidden, weight, lab_b)


def _dh_call(hidden, weight, lab_b, lse_b, g_b):
    """dHidden [n, e] (hidden dtype)."""
    n, e = hidden.shape
    v = weight.shape[1]
    bn = _pick_bn(n, e)
    bv = _pick_bv(e, v, jnp.dtype(weight.dtype).itemsize)
    nb, nv = n // bn, v // bv
    return pl.pallas_call(
        functools.partial(_dh_kernel, nv=nv, bv=bv),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((bn, e), lambda i, j: (i, 0)),
            pl.BlockSpec((e, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, e), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, e), hidden.dtype),
        scratch_shapes=[pltpu.VMEM((bn, e), jnp.float32)],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_linear_xent_dh",
    )(hidden, weight, lab_b, lse_b, g_b)


def _dw_call(hidden, weight, lab_b, lse_b, g_b):
    """dW [e, v] in the weight dtype (f32-accumulated in VMEM)."""
    n, e = hidden.shape
    v = weight.shape[1]
    bn = _pick_bn(n, e)
    bv = _pick_bv(e, v, jnp.dtype(weight.dtype).itemsize, for_dw=True)
    nb, nv = n // bn, v // bv
    return pl.pallas_call(
        functools.partial(_dw_kernel, nb=nb, bv=bv),
        grid=(nv, nb),
        in_specs=[
            pl.BlockSpec((bn, e), lambda j, i: (i, 0)),
            pl.BlockSpec((e, bv), lambda j, i: (0, j)),
            pl.BlockSpec((bn, _LANES), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, _LANES), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, _LANES), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((e, bv), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((e, v), weight.dtype),
        scratch_shapes=[pltpu.VMEM((e, bv), jnp.float32)],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_linear_xent_dw",
    )(hidden, weight, lab_b, lse_b, g_b)


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------

def _lane(x, dtype=None):
    x = x if dtype is None else x.astype(dtype)
    return jnp.broadcast_to(x[:, None], (x.shape[0], _LANES))


def _fwd_dispatch(hidden, weight, lab_b, part):
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.flce_fwd()(hidden, weight, lab_b)
    return _fwd_call(hidden, weight, lab_b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flce(part, hidden, weight, labels):
    lse, sel = _fwd_dispatch(hidden, weight, _lane(labels, jnp.int32), part)
    return lse[:, 0] - sel[:, 0]


def _flce_fwd(part, hidden, weight, labels):
    lab_b = _lane(labels, jnp.int32)
    lse, sel = _fwd_dispatch(hidden, weight, lab_b, part)
    return lse[:, 0] - sel[:, 0], (hidden, weight, lab_b, lse[:, 0])


def _flce_bwd(part, res, g):
    hidden, weight, lab_b, lse = res
    lse_b = _lane(lse)
    g_b = _lane(g.astype(jnp.float32))
    if part:
        from paddle_tpu.ops.pallas import _partition
        dh = _partition.flce_dh()(hidden, weight, lab_b, lse_b, g_b)
        dw = _partition.flce_dw()(hidden, weight, lab_b, lse_b, g_b)
    else:
        dh = _dh_call(hidden, weight, lab_b, lse_b, g_b)
        dw = _dw_call(hidden, weight, lab_b, lse_b, g_b)
    # astype is a no-op for the raw kernel (it emits weight dtype); it
    # covers partitioned fallbacks that produce f32
    return (dh, dw.astype(weight.dtype),
            jnp.zeros((hidden.shape[0],), dtype=jax.dtypes.float0))


_flce.defvjp(_flce_fwd, _flce_bwd)


def fused_linear_cross_entropy(hidden, weight, labels, *,
                               partitioned: bool = False):
    """Per-row loss ``lse(h_i·W) − (h_i·W)[labels[i]]`` for [N, E] hidden,
    [E, V] weight and int [N] labels — the [N, V] logits are never
    materialized. ``supported(hidden, weight, labels)`` must hold.
    Out-of-range labels (e.g. an ignore_index of −100) select nothing:
    their row loss is the bare lse (callers mask it) and contributes no
    onehot term to the gradients — combined with a zero cotangent from
    the caller's mask, ignored rows produce exactly zero grad.

    ``partitioned`` routes the three kernels through the shard_map units
    (``_partition.flce_*``) so they run per shard on a multi-device mesh,
    including a Megatron vocab-sharded lm-head (local online lse + lse
    merge over the vocab axes, dW sharded over vocab, dH psum-reduced).
    """
    return _flce(bool(partitioned), hidden, weight, labels)


# ---------------------------------------------------------------------------
# chunked XLA reference (fallback + the honest competitor to microbench)
# ---------------------------------------------------------------------------

def chunked_linear_cross_entropy(hidden, weight, labels,
                                 block_v: int = 4096):
    """Pure-XLA vocab-chunked variant: lax.scan over V tiles with an
    online logsumexp carry, ``jax.checkpoint`` on the body so backward
    recomputes each tile instead of saving it. Same O(N) loss-path
    memory as the Pallas kernel; used as the dispatch fallback for
    unsupported shapes and as the microbench competitor that keeps the
    kernel honest."""
    n, e = hidden.shape
    v = weight.shape[1]
    block_v = min(block_v, v)
    nv, rem = divmod(v, block_v)
    lab = labels.astype(jnp.int32)

    @jax.checkpoint
    def merge(carry, w_c, off):
        m, l, s = carry
        logits = jnp.dot(hidden, w_c,
                         preferred_element_type=jnp.float32)  # [n, bv]
        col = off + jnp.arange(w_c.shape[1], dtype=jnp.int32)[None, :]
        s = s + jnp.sum(jnp.where(col == lab[:, None], logits, 0.0), axis=1)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1)
        return m_new, l, s

    carry = (jnp.full((n,), _NEG_INF, jnp.float32),
             jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    if nv:
        w_t = (weight[:, :nv * block_v]
               .reshape(e, nv, block_v).transpose(1, 0, 2))  # [nv, e, bv]
        offs = jnp.arange(nv, dtype=jnp.int32) * block_v
        carry, _ = jax.lax.scan(
            lambda c, xs: (merge(c, *xs), None), carry, (w_t, offs))
    if rem:
        # ragged tail chunk handled out-of-scan with the same online
        # merge — any V works without padding (a zero-pad would corrupt
        # the lse) or degrading to full-vocab tiles
        carry = merge(carry, weight[:, nv * block_v:],
                      jnp.int32(nv * block_v))
    m, l, s = carry
    return m + jnp.log(l) - s
