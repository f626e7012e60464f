"""Functional ops — the ``paddle.nn.functional`` equivalent.

The reference implements these as ~657 registered C++/CUDA operators
(reference ``paddle/fluid/operators/``, e.g. ``softmax_with_cross_entropy_op.cu``,
``layer_norm_op.cu``, ``dropout_op.cu``, ``lookup_table_v2_op.cu``). On TPU
the bulk is jax.numpy/lax — XLA fuses elementwise chains into matmul
epilogues on its own — and the hot set additionally has Pallas kernels in
``paddle_tpu.ops.pallas`` that these wrappers dispatch to on TPU.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import rng
from paddle_tpu.ops import pallas as _pk

__all__ = [
    "relu", "relu6", "gelu", "silu", "swish", "sigmoid", "tanh",
    "leaky_relu", "elu", "softplus", "hardswish", "hardsigmoid", "mish",
    "glu", "swiglu",
    "softmax", "log_softmax", "one_hot", "embedding", "linear",
    "dropout", "layer_norm", "rms_norm", "group_norm", "batch_norm",
    "cross_entropy", "softmax_with_cross_entropy", "linear_cross_entropy",
    "next_token_linear_loss",
    "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss",
    "smooth_l1_loss", "nll_loss", "kl_div", "label_smooth",
    "scaled_dot_product_attention", "rotary_embedding", "apply_rotary",
    "apply_partial_rotary",
    "avg_pool2d", "max_pool2d", "adaptive_avg_pool2d", "conv2d", "pad",
    "interpolate", "unfold", "clip", "normalize", "cosine_similarity",
    # extended surface (see sections below)
    "hardshrink", "hardtanh", "log_sigmoid", "maxout", "prelu", "selu",
    "softshrink", "softsign", "tanhshrink", "thresholded_relu",
    "dropout2d", "dropout3d", "alpha_dropout", "pixel_shuffle",
    "local_response_norm", "pairwise_distance", "ctc_loss",
    "margin_ranking_loss", "hsigmoid_loss",
    "max_pool1d", "avg_pool1d", "max_pool3d", "avg_pool3d",
    "adaptive_avg_pool1d", "adaptive_avg_pool3d", "adaptive_max_pool1d",
    "adaptive_max_pool2d", "adaptive_max_pool3d", "conv1d", "conv3d",
    "assign", "fc", "upsample", "square_error_cost", "log_loss",
    "affine_channel",
    "dice_loss", "sigmoid_focal_loss", "npair_loss", "diag_embed",
    "instance_norm", "data_norm", "bilinear", "bilinear_tensor_product",
    "row_conv", "spectral_norm", "conv1d_transpose", "conv2d_transpose",
    "conv3d_transpose", "affine_grid", "grid_sample", "nce",
]


# ---------------------------------------------------------------------------
# Activations (reference operators/activation_op.*)
# ---------------------------------------------------------------------------

def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def gelu(x, approximate: bool = False):
    return jax.nn.gelu(x, approximate=approximate)


def silu(x):
    return jax.nn.silu(x)


swish = silu


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def leaky_relu(x, negative_slope: float = 0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def elu(x, alpha: float = 1.0):
    return jax.nn.elu(x, alpha)


def softplus(x, beta: float = 1.0, threshold: float = 20.0):
    xb = x * beta
    return jnp.where(xb > threshold, x, jax.nn.softplus(xb) / beta)


def hardswish(x):
    return x * relu6(x + 3.0) / 6.0


def hardsigmoid(x):
    return jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def glu(x, axis: int = -1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * sigmoid(b)


def swiglu(x, gate):
    """SwiGLU combine used by Llama-style MLPs: silu(gate) * x."""
    return silu(gate) * x


# ---------------------------------------------------------------------------
# Normalization / softmax
# ---------------------------------------------------------------------------

def softmax(x, axis: int = -1):
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis: int = -1):
    return jax.nn.log_softmax(x, axis=axis)


def layer_norm(x, weight=None, bias=None, epsilon: float = 1e-5, axis=-1):
    """Row layer-norm (reference kernel ``operators/layer_norm_op.cu``,
    Welford rows). On TPU, supported shapes dispatch to the fused Pallas
    kernel (``paddle_tpu.ops.pallas.layer_norm``)."""
    if axis in (-1, x.ndim - 1):
        from paddle_tpu.ops.pallas import norm as _pn
        mode = _pk._support.dispatch_mode()
        if mode != "off" and _pn.supported(x, weight, bias):
            return _pk.layer_norm(x, weight, bias, epsilon,
                                  partitioned=mode == "partitioned")
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axis, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon)
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y


def rms_norm(x, weight=None, epsilon: float = 1e-6):
    """RMSNorm (no mean subtraction) — the Llama-family norm. Computed in
    fp32 and cast back, matching standard practice for bf16 training. On
    TPU, supported shapes dispatch to the fused Pallas kernel."""
    from paddle_tpu.ops.pallas import norm as _pn
    mode = _pk._support.dispatch_mode()
    if mode != "off" and _pn.supported(x, weight):
        return _pk.rms_norm(x, weight, epsilon,
                            partitioned=mode == "partitioned")
    dtype = x.dtype
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + epsilon)
    y = y.astype(dtype)
    if weight is not None:
        y = y * weight
    return y


def group_norm(x, num_groups: int, weight=None, bias=None,
               epsilon: float = 1e-5, data_format: str = "NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    g = x.reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=axes, keepdims=True)
    g = (g - mean) * lax.rsqrt(var + epsilon)
    y = g.reshape(n, c, *spatial)
    shape = (1, c) + (1,) * len(spatial)
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    if data_format == "NHWC":
        y = jnp.moveaxis(y, 1, -1)
    return y


def batch_norm(x, mean, var, weight=None, bias=None, epsilon: float = 1e-5,
               data_format: str = "NCHW"):
    """Inference-mode batch norm with given statistics (training-mode stat
    update lives in nn.BatchNorm; reference ``operators/batch_norm_op.cu``)."""
    c_axis = 1 if data_format == "NCHW" else -1
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    y = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


def normalize(x, p: float = 2.0, axis: int = -1, epsilon: float = 1e-12):
    norm = jnp.linalg.norm(x, ord=p, axis=axis, keepdims=True)
    return x / jnp.maximum(norm, epsilon)


def cosine_similarity(a, b, axis: int = -1, eps: float = 1e-8):
    a_n = jnp.linalg.norm(a, axis=axis)
    b_n = jnp.linalg.norm(b, axis=axis)
    dot = jnp.sum(a * b, axis=axis)
    return dot / jnp.maximum(a_n * b_n, eps)


# ---------------------------------------------------------------------------
# Core layers
# ---------------------------------------------------------------------------

def _amp_inputs(op: str, *tensors):
    """Dtype alignment for a white-listed op's floating inputs: inside an
    active ``amp.auto_cast`` scope cast them to the autocast dtype (the
    reference's AmpOperators allow-list cast, ``amp_auto_cast.cc``);
    outside, align mixed floating dtypes to their promoted type so bf16
    params compose with fp32 inputs (lax convs reject mixed dtypes)."""
    from paddle_tpu import amp as amp_mod

    dt = amp_mod.active_dtype(op)
    if dt is None:
        fdts = {t.dtype for t in tensors
                if t is not None and jnp.issubdtype(t.dtype, jnp.floating)}
        if len(fdts) <= 1:
            return tensors
        dt = jnp.result_type(*fdts)
    return tuple(
        t.astype(dt) if t is not None and jnp.issubdtype(
            t.dtype, jnp.floating) else t
        for t in tensors)


def linear(x, weight, bias=None):
    """y = x @ W (+ b). Weight layout [in, out] like the reference's fc
    (reference ``operators/math/fc.cc``) — feeds the MXU directly."""
    x, weight, bias = _amp_inputs("linear", x, weight, bias)
    y = jnp.matmul(x, weight)
    if bias is not None:
        y = y + bias
    return y


def embedding(ids, weight):
    """Lookup-table gather (reference ``operators/lookup_table_v2_op.cu``)."""
    return jnp.take(weight, ids, axis=0)


def one_hot(ids, num_classes: int, dtype=jnp.float32):
    return jax.nn.one_hot(ids, num_classes, dtype=dtype)


def dropout(x, p: float = 0.5, training: bool = True, key=None):
    """Inverted dropout (reference ``operators/dropout_op.cu``,
    upscale_in_train mode). Requires an RNG key while training — either
    explicit or from the ambient ``rng.stream`` opened by the trainer."""
    if not training or p == 0.0:
        return x
    if key is None:
        key = rng.stream_key()
    if key is None:
        raise ValueError(
            "dropout(training=True) needs an RNG key: pass key= or open a "
            "paddle_tpu.core.rng.stream(step_key) around the forward pass")
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def label_smooth(label, epsilon: float = 0.1):
    num = label.shape[-1]
    return label * (1.0 - epsilon) + epsilon / num


def clip(x, min=None, max=None):
    return jnp.clip(x, min, max)


def affine_channel(x, scale, bias=None, data_format: str = "NCHW"):
    """Per-channel affine y = scale_c · x + bias_c (reference
    ``operators/affine_channel_op.cc`` — the folded-BN inference form)."""
    c_axis = 1 if data_format == "NCHW" else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    y = x * scale.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


# ---------------------------------------------------------------------------
# Losses (reference operators/softmax_with_cross_entropy_op.cu etc.)
# ---------------------------------------------------------------------------

def softmax_with_cross_entropy(logits, label, soft_label: bool = False,
                               ignore_index: int = -100, axis: int = -1):
    """Fused softmax+xent — numerically stable log-softmax formulation.
    The reference fuses this in CUDA
    (``operators/softmax_with_cross_entropy_op.cu``); on TPU the [N, V]
    int-label hot case dispatches to the Pallas kernel, which saves only
    the [N] log-sum-exp for backward instead of the [N, V] probabilities."""
    if not soft_label and axis in (-1, logits.ndim - 1):
        from paddle_tpu.ops.pallas import softmax_xent as _px
        v = logits.shape[-1]
        flat = logits.reshape(-1, v)
        lab = label.reshape(-1)
        mode = _pk._support.dispatch_mode()
        # screen on everything but the row count before paying for the
        # padded copy (v alignment, dtypes)
        if mode != "off" and v % _px._BLOCK_V == 0 \
                and v <= _px.DISPATCH_MAX_V \
                and logits.dtype in (jnp.float32, jnp.bfloat16):
            # Row-pad to the kernel block so shifted-label LM losses
            # ([B, T-1, V] → B·(T-1) rows) still dispatch; padded rows are
            # ignore-masked so their loss (and hence grad) is zero.
            n = flat.shape[0]
            pad = (-n) % (_px._BLOCK_N if n >= _px._BLOCK_N else 8)
            if pad:
                flat_p = jnp.concatenate(
                    [flat, jnp.zeros((pad, v), flat.dtype)])
                lab_p = jnp.concatenate(
                    [lab, jnp.full((pad,), ignore_index, lab.dtype)])
            else:
                flat_p, lab_p = flat, lab
            if _px.supported(flat_p, lab_p):
                valid = lab_p != ignore_index
                safe = jnp.where(valid, lab_p, 0)
                loss = _pk.softmax_cross_entropy(
                    flat_p, safe, partitioned=mode == "partitioned")
                loss = jnp.where(valid, loss, 0.0).astype(logits.dtype)
                return loss[:n].reshape(label.shape)
    logp = jax.nn.log_softmax(logits, axis=axis)
    if soft_label:
        return -jnp.sum(label * logp, axis=axis)
    valid = label != ignore_index
    safe = jnp.where(valid, label, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=axis)[..., 0]
    return jnp.where(valid, nll, 0.0)


def cross_entropy(logits, label, soft_label: bool = False,
                  ignore_index: int = -100, reduction: str = "mean",
                  weight=None, axis: int = -1):
    if weight is not None and soft_label:
        # Per-class weights fold into the inner sum for soft labels:
        # loss = -sum_c label_c * w_c * logp_c, normalized by the
        # per-sample effective weight sum under "mean".
        logp = jax.nn.log_softmax(logits, axis=axis)
        loss = -jnp.sum(label * weight * logp, axis=axis)
        if reduction == "mean":
            wsum = jnp.sum(label * weight, axis=axis)
            return jnp.sum(loss) / jnp.maximum(jnp.sum(wsum), 1e-12)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss
    loss = softmax_with_cross_entropy(logits, label, soft_label,
                                      ignore_index, axis)
    if weight is not None and not soft_label:
        w = jnp.take(weight, jnp.where(label == ignore_index, 0, label))
        w = jnp.where(label == ignore_index, 0.0, w)
        loss = loss * w
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
    if reduction == "mean":
        if not soft_label:
            valid = (label != ignore_index).astype(loss.dtype)
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1.0)
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def linear_cross_entropy(hidden, weight, label, ignore_index: int = -100,
                         reduction: str = "mean", mode: str = "auto"):
    """LM-head projection fused with softmax cross-entropy:
    ``cross_entropy(hidden @ weight, label)`` without materializing the
    [..., V] logits (reference fuses only softmax+xent,
    ``operators/softmax_with_cross_entropy_op.cu``, and keeps the FC
    output of the preceding ``mul_op`` resident; at LM vocab sizes that
    logits tensor dominates activation memory).

    ``hidden`` [..., E], ``weight`` [E, V], int ``label`` [...].

    ``mode``:
      - ``"fused"``  — Pallas vocab-tiled kernel (``ops/pallas/linear_xent``):
        O(N) loss-path memory, ~10/6 the matmul FLOPs (both backward
        kernels recompute their logits tile). Measured on v5e at bench
        shape (N=16384, E=2048, V=32000, bf16): 66ms vs 41ms fwd+bwd —
        slower op-level, but removes the ~4 GB logits+dlogits peak.
      - ``"dense"``  — plain matmul + ``cross_entropy`` (XLA-fused lse).
      - ``"chunked"``— pure-XLA scan over vocab tiles (same O(N) memory,
        used off-TPU and as the honest competitor).
      - ``"auto"``   — fused when supported on TPU, else dense. Choose
        explicitly in memory-bound configs; dense is faster when the
        logits fit comfortably.
    """
    if mode not in ("auto", "fused", "chunked", "dense"):
        raise ValueError(
            f"linear_cross_entropy: unknown mode {mode!r} "
            "(expected 'auto', 'fused', 'chunked' or 'dense')")
    e = hidden.shape[-1]
    out_shape = label.shape
    flat = hidden.reshape(-1, e)
    lab = label.reshape(-1)
    n = flat.shape[0]

    loss = None
    if mode in ("auto", "fused", "chunked"):
        from paddle_tpu.ops.pallas import linear_xent as lmod
        if mode != "chunked":
            dmode = _pk._support.dispatch_mode()
            # row-pad to the kernel block (ignore-masked rows are free:
            # they select no label and carry a zero cotangent); below one
            # block the kernel only needs sublane (8) alignment
            bn = lmod._pick_bn(max(n, 1024), e)
            target = bn if n >= bn else 8
            pad = (-n) % target
            if dmode != "off":
                flat_p = (jnp.concatenate(
                    [flat, jnp.zeros((pad, e), flat.dtype)]) if pad else flat)
                lab_p = (jnp.concatenate(
                    [lab, jnp.full((pad,), ignore_index, lab.dtype)])
                    if pad else lab)
                if lmod.supported(flat_p, weight, lab_p):
                    loss = lmod.fused_linear_cross_entropy(
                        flat_p, weight, lab_p,
                        partitioned=dmode == "partitioned")[:n]
        if loss is None and mode in ("chunked", "fused"):
            loss = lmod.chunked_linear_cross_entropy(flat, weight, lab)
    if loss is None:
        logits = (flat @ weight).astype(jnp.float32)
        loss = softmax_with_cross_entropy(logits, lab,
                                          ignore_index=ignore_index)
    valid = lab != ignore_index
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(
            jnp.sum(valid.astype(loss.dtype)), 1.0)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss.reshape(out_shape)


def next_token_linear_loss(hidden, weight, labels, ignore_index: int = -100,
                           mode: str = "auto"):
    """Causal-LM head loss over ``hidden`` [B, T, E] with SAME-position
    ``labels`` [B, T]: shifts the labels left one step (position t
    predicts token t+1) and ignore-masks the final position, then runs
    :func:`linear_cross_entropy`. Running over all T rows with a shifted
    mask is mean-equivalent to the dense ``logits[:, :-1]`` slice while
    keeping the row count kernel-aligned — the shared head-loss path of
    the Llama/GPT families."""
    lab_shift = jnp.concatenate(
        [labels[:, 1:],
         jnp.full((labels.shape[0], 1), ignore_index, labels.dtype)],
        axis=1)
    return linear_cross_entropy(hidden, weight, lab_shift,
                                ignore_index=ignore_index, mode=mode)


def nll_loss(log_probs, label, reduction: str = "mean"):
    nll = -jnp.take_along_axis(log_probs, label[..., None], axis=-1)[..., 0]
    return _reduce(nll, reduction)


def binary_cross_entropy(probs, label, reduction: str = "mean",
                         epsilon: float = 1e-12):
    p = jnp.clip(probs, epsilon, 1.0 - epsilon)
    loss = -(label * jnp.log(p) + (1.0 - label) * jnp.log1p(-p))
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(logits, label, reduction: str = "mean",
                                     pos_weight=None):
    log_p = jax.nn.log_sigmoid(logits)
    log_not_p = jax.nn.log_sigmoid(-logits)
    if pos_weight is not None:
        loss = -(pos_weight * label * log_p + (1.0 - label) * log_not_p)
    else:
        loss = -(label * log_p + (1.0 - label) * log_not_p)
    return _reduce(loss, reduction)


def mse_loss(pred, target, reduction: str = "mean"):
    return _reduce(jnp.square(pred - target), reduction)


def l1_loss(pred, target, reduction: str = "mean"):
    return _reduce(jnp.abs(pred - target), reduction)


def smooth_l1_loss(pred, target, delta: float = 1.0, reduction: str = "mean"):
    d = jnp.abs(pred - target)
    loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    return _reduce(loss, reduction)


def kl_div(log_pred, target, reduction: str = "mean"):
    loss = target * (jnp.log(jnp.maximum(target, 1e-12)) - log_pred)
    return _reduce(loss, reduction)


def _reduce(loss, reduction: str):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


# ---------------------------------------------------------------------------
# Attention + RoPE
# ---------------------------------------------------------------------------

def scaled_dot_product_attention(q, k, v, mask=None, *, causal: bool = False,
                                 scale: float | None = None,
                                 dropout_p: float = 0.0, training: bool = False,
                                 use_pallas: str = "auto"):
    """Attention core, [B, T, H, D] layout.

    The reference fuses this as ``operators/fused/multihead_matmul_op.cu``
    (cuBLAS batched GEMM + softmax kernel). Here: einsum formulation that
    XLA maps onto the MXU; on TPU with supported shapes it dispatches to the
    Pallas flash-attention kernel (``paddle_tpu.ops.pallas.flash_attention``)
    which never materializes the [T, T] matrix.

    Supports grouped-query attention: k/v may have fewer heads than q as
    long as q_heads % kv_heads == 0.
    """
    B, Tq, Hq, D = q.shape
    Hkv = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    if use_pallas != "never" and dropout_p == 0.0 and mask is None:
        mode = _pk._support.dispatch_mode()
        if mode == "off" and use_pallas == "always":
            # Forced dispatch: inside any manual shard_map only the raw
            # kernel is safe (a nested shard_map unit cannot lower there).
            any_manual, _ = _pk._support._manual_axes()
            if any_manual or _pk._support.single_device():
                mode = "raw"
            else:
                mode = "partitioned"
        if _pk.flash_attention_supported(q, k, v, causal=causal) \
                and mode != "off":
            return _pk.flash_attention(q, k, v, causal=causal, scale=scale,
                                       partitioned=mode == "partitioned")
        if use_pallas == "always":
            raise RuntimeError(
                "use_pallas='always' but the flash kernel does not support "
                f"q{q.shape} k{k.shape} {q.dtype} (need seq divisible by the "
                "block size, head_dim in {64,128,256}, f32/bf16)")

    if Hkv != Hq:  # GQA: repeat kv heads
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    Tk = k.shape[1]
    if causal:
        i = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        j = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        causal_mask = (j <= i + (Tk - Tq))
        logits = jnp.where(causal_mask, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        probs = dropout(probs, dropout_p, training=training)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def rotary_embedding(positions, dim: int, base: float = 10000.0,
                     dtype=jnp.float32):
    """Compute RoPE cos/sin tables for integer positions, shape [..., dim/2]."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x, cos, sin):
    """Apply rotary embedding to [B, T, H, D] (cos/sin [B?, T, D/2]).
    On TPU, the [T, D/2]-table case dispatches to the fused Pallas
    kernel."""
    if x.ndim == 4 and cos.ndim == 2:
        from paddle_tpu.ops.pallas import rope as _pr
        mode = _pk._support.dispatch_mode()
        if mode != "off" and _pr.supported(x, cos, sin):
            return _pk.apply_rotary(x, cos, sin,
                                    partitioned=mode == "partitioned")
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == x.ndim - 2:          # [T, D/2] → broadcast over B, H
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    elif cos.ndim == x.ndim - 1:        # [B, T, D/2]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    rot1 = x1 * cos - x2 * sin
    rot2 = x2 * cos + x1 * sin
    return jnp.concatenate([rot1, rot2], axis=-1).astype(x.dtype)


def apply_partial_rotary(x, cos, sin, rotary_dim: int):
    """:func:`apply_rotary` on the first ``rotary_dim`` dims of each
    head (cos/sin ``[..., rotary_dim/2]``, pairs ``(i, i + rotary_dim/2)``);
    the other dims pass through unrotated."""
    if rotary_dim == x.shape[-1]:
        return apply_rotary(x, cos, sin)
    return jnp.concatenate(
        [apply_rotary(x[..., :rotary_dim], cos, sin), x[..., rotary_dim:]],
        axis=-1)


# ---------------------------------------------------------------------------
# Conv / pooling / image (reference operators/conv_cudnn_op.cu, pool_op.*)
# ---------------------------------------------------------------------------

def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups: int = 1, data_format: str = "NCHW"):
    """2D convolution. Weight layout [out_c, in_c/groups, kh, kw] (reference
    layout); lax.conv_general_dilated lets XLA pick the TPU-optimal internal
    layout regardless of the logical data_format."""
    x, weight, bias = _amp_inputs("conv2d", x, weight, bias)
    stride, dilation = _pair(stride), _pair(dilation)
    if isinstance(padding, str):
        pad = padding
    else:
        p = _pair(padding)
        pad = [(p[0], p[0]), (p[1], p[1])]
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW"
        else ("NHWC", "OIHW", "NHWC"))
    # no preferred_element_type=f32 for bf16: the XLA TPU conv already
    # accumulates bf16 operands in f32 internally, and an f32 *output*
    # type breaks the autodiff transpose (f32 cotangent vs bf16 operand)
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        shape = [1] * y.ndim
        shape[1 if data_format == "NCHW" else -1] = bias.shape[0]
        y = y + bias.reshape(shape)
    return y


def max_pool2d(x, kernel_size, stride=None, padding=0,
               data_format: str = "NCHW"):
    return _pool(x, kernel_size, stride, padding, data_format,
                 init=-jnp.inf, op=lax.max)


def avg_pool2d(x, kernel_size, stride=None, padding=0,
               data_format: str = "NCHW", exclusive: bool = True):
    """Average pooling. ``exclusive=True`` (reference default) divides each
    window by the count of *real* (non-padded) elements."""
    k = _pair(kernel_size)
    summed = _pool(x, kernel_size, stride, padding, data_format,
                   init=0.0, op=lax.add)
    p = _pair(padding)
    if exclusive and (p[0] or p[1]):
        ones = jnp.ones_like(x)
        counts = _pool(ones, kernel_size, stride, padding, data_format,
                       init=0.0, op=lax.add)
        return summed / counts
    return summed / (k[0] * k[1])


def _pool(x, kernel_size, stride, padding, data_format, init, op):
    k = _pair(kernel_size)
    s = _pair(stride if stride is not None else kernel_size)
    p = _pair(padding)
    if data_format == "NCHW":
        window = (1, 1, k[0], k[1])
        strides = (1, 1, s[0], s[1])
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    else:
        window = (1, k[0], k[1], 1)
        strides = (1, s[0], s[1], 1)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    return lax.reduce_window(x, init, op, window, strides, pads)


def _adaptive_windows(dim: int, out: int):
    """Static per-bin gather windows for torch/paddle adaptive pooling:
    bin i covers input [floor(i·D/O), ceil((i+1)·D/O)). Non-divisible
    sizes give uneven (possibly overlapping) bins — encoded as a fixed
    [out, W] index table + validity mask (W = widest bin), which keeps
    shapes static for XLA (the reference's adaptive attr,
    ``operators/pool_op.cc``, recomputes bounds per output element on
    the fly; here they are compile-time constants)."""
    import numpy as np

    i = np.arange(out)
    starts = (i * dim) // out
    ends = -((-(i + 1) * dim) // out)          # ceil((i+1)*dim/out)
    w = int((ends - starts).max())
    idx = starts[:, None] + np.arange(w)[None, :]
    mask = idx < ends[:, None]
    return (jnp.asarray(np.minimum(idx, dim - 1)),
            jnp.asarray(mask), w)


def _adaptive_pool_axis(x, axis: int, out: int, op: str):
    """General adaptive pool along one axis via the static window
    gather; reduces to the exact divisible case when bins are even."""
    dim = x.shape[axis]
    idx, mask, w = _adaptive_windows(dim, out)
    g = jnp.take(x, idx.reshape(-1), axis=axis)
    g = g.reshape(x.shape[:axis] + (out, w) + x.shape[axis + 1:])
    mshape = [1] * g.ndim
    mshape[axis], mshape[axis + 1] = out, w
    m = mask.reshape(mshape)
    if op == "max":
        return jnp.max(jnp.where(m, g, -jnp.inf), axis=axis + 1)
    s = jnp.sum(jnp.where(m, g, 0), axis=axis + 1)
    counts = jnp.sum(mask, axis=1).astype(x.dtype).reshape(
        [out if a == axis else 1 for a in range(s.ndim)])
    return s / counts


def adaptive_avg_pool2d(x, output_size, data_format: str = "NCHW"):
    out = _pair(output_size)
    if data_format == "NCHW":
        axes, (h, w) = (2, 3), (x.shape[2], x.shape[3])
    else:
        axes, (h, w) = (1, 2), (x.shape[1], x.shape[2])
    if h % out[0] == 0 and w % out[1] == 0:
        k = (h // out[0], w // out[1])
        return avg_pool2d(x, k, stride=k, padding=0,
                          data_format=data_format)
    y = _adaptive_pool_axis(x, axes[0], out[0], "avg")
    return _adaptive_pool_axis(y, axes[1], out[1], "avg")


def pad(x, paddings, mode: str = "constant", value: float = 0.0):
    if mode == "constant":
        return jnp.pad(x, paddings, constant_values=value)
    return jnp.pad(x, paddings, mode=mode)


def interpolate(x, scale_factor=None, size=None, mode: str = "nearest",
                data_format: str = "NCHW"):
    """Resize (reference ``operators/interpolate_op.*``)."""
    if data_format == "NCHW":
        n, c, h, w = x.shape
    else:
        n, h, w, c = x.shape
    if size is None:
        sf = _pair(scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    method = {"nearest": "nearest", "bilinear": "linear",
              "bicubic": "cubic"}[mode]
    if data_format == "NCHW":
        shape = (n, c, size[0], size[1])
    else:
        shape = (n, size[0], size[1], c)
    return jax.image.resize(x, shape, method=method)


def unfold(x, kernel_size, stride=1, padding=0, dilation=1):
    """im2col (reference ``operators/math/im2col.cu``) — rarely needed on
    TPU since XLA lowers conv directly, provided for API parity."""
    k, s, p, d = _pair(kernel_size), _pair(stride), _pair(padding), _pair(dilation)
    n, c, h, w = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])))
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=s, padding="VALID",
        rhs_dilation=d, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return patches.reshape(n, c * k[0] * k[1], -1)


# ---------------------------------------------------------------------------
# Extended activations (reference python/paddle/nn/functional/activation.py)
# ---------------------------------------------------------------------------

def hardshrink(x, threshold: float = 0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def hardtanh(x, min: float = -1.0, max: float = 1.0):
    return jnp.clip(x, min, max)


def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


def maxout(x, groups: int, axis: int = 1):
    """Max over ``groups`` channel groups (reference ``maxout_op``)."""
    shape = list(x.shape)
    if shape[axis] % groups:
        raise ValueError(f"channels {shape[axis]} % groups {groups} != 0")
    shape[axis:axis + 1] = [shape[axis] // groups, groups]
    return jnp.max(x.reshape(shape), axis=axis + 1)


def prelu(x, weight):
    """weight broadcasts per-channel ([C] against axis 1) or scalar."""
    w = weight
    if w.ndim == 1 and x.ndim > 2:
        w = w.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(x >= 0, x, w * x)


def selu(x, scale: float = 1.0507009873554805,
         alpha: float = 1.6732632423543772):
    return scale * jnp.where(x >= 0, x, alpha * (jnp.exp(x) - 1.0))


def softshrink(x, threshold: float = 0.5):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - threshold, 0.0)


def softsign(x):
    return x / (1.0 + jnp.abs(x))


def tanhshrink(x):
    return x - jnp.tanh(x)


def thresholded_relu(x, threshold: float = 1.0):
    return jnp.where(x > threshold, x, 0.0)


# ---------------------------------------------------------------------------
# Dropout variants (reference operators/dropout_op + nn/functional/common.py)
# ---------------------------------------------------------------------------

def dropout2d(x, p: float = 0.5, training: bool = True, key=None,
              data_format: str = "NCHW"):
    """Drop whole channels of [N, C, H, W]."""
    if not training or p == 0.0:
        return x
    if key is None:
        from paddle_tpu.core import rng as _rng
        key = _rng.next_key()
    c_axis = 1 if data_format == "NCHW" else -1
    shape = [x.shape[0], 1, 1, 1]
    shape[c_axis] = x.shape[c_axis]
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    return jnp.where(keep, x / (1.0 - p), 0.0)


def dropout3d(x, p: float = 0.5, training: bool = True, key=None):
    if not training or p == 0.0:
        return x
    if key is None:
        from paddle_tpu.core import rng as _rng
        key = _rng.next_key()
    keep = jax.random.bernoulli(key, 1.0 - p,
                                (x.shape[0], x.shape[1], 1, 1, 1))
    return jnp.where(keep, x / (1.0 - p), 0.0)


def alpha_dropout(x, p: float = 0.5, training: bool = True, key=None):
    """SELU-preserving dropout (reference alpha_dropout): dropped units
    take the negative saturation value; affine correction keeps
    mean/variance."""
    if not training or p == 0.0:
        return x
    if key is None:
        from paddle_tpu.core import rng as _rng
        key = _rng.next_key()
    alpha = 1.6732632423543772 * 1.0507009873554805
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    a = ((1.0 - p) * (1.0 + p * alpha ** 2)) ** -0.5
    b = a * alpha * p   # cancels the -alpha mass of the dropped units
    return a * jnp.where(keep, x, -alpha) + b


# ---------------------------------------------------------------------------
# Geometry / misc (pixel_shuffle_op, lrn_op, interpolate)
# ---------------------------------------------------------------------------

def pixel_shuffle(x, upscale_factor: int):
    """[N, C*r^2, H, W] → [N, C, H*r, W*r] (reference pixel_shuffle_op)."""
    r = int(upscale_factor)
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return x.reshape(n, c // (r * r), h * r, w * r)


def local_response_norm(x, size: int = 5, alpha: float = 1e-4,
                        beta: float = 0.75, k: float = 1.0):
    """AlexNet-style LRN over channels (reference ``lrn_op``)."""
    sq = jnp.square(x)
    half = size // 2
    pad = jnp.pad(sq, ((0, 0), (half, size - half - 1), (0, 0), (0, 0)))
    windows = jnp.stack([pad[:, i:i + x.shape[1]] for i in range(size)], 0)
    denom = k + alpha * jnp.sum(windows, axis=0)
    return x / denom ** beta


def pairwise_distance(a, b, p: float = 2.0, epsilon: float = 1e-6,
                      keepdim: bool = False):
    d = jnp.linalg.norm(jnp.abs(a - b) + epsilon, ord=p, axis=-1,
                        keepdims=keepdim)
    return d


# ---------------------------------------------------------------------------
# Extra losses (ctc, margin ranking, hierarchical sigmoid)
# ---------------------------------------------------------------------------

def ctc_loss(log_probs, labels, input_lengths, label_lengths,
             blank: int = 0, reduction: str = "mean"):
    """CTC (reference ``operators/warpctc_op``): forward-backward over
    [B, T, V] log-probs; optax's TPU-friendly implementation underneath.
    ``labels`` are padded [B, L]."""
    import optax

    B, T, V = log_probs.shape
    L = labels.shape[1]
    t_idx = jnp.arange(T)[None, :]
    logit_pad = (t_idx >= input_lengths[:, None]).astype(jnp.float32)
    l_idx = jnp.arange(L)[None, :]
    label_pad = (l_idx >= label_lengths[:, None]).astype(jnp.float32)
    loss = optax.ctc_loss(log_probs, logit_pad, labels, label_pad,
                          blank_id=blank)
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin: float = 0.0,
                        reduction: str = "mean"):
    """max(0, -label*(input-other) + margin) (reference
    margin_rank_loss_op)."""
    loss = jnp.maximum(0.0, -label * (input - other) + margin)
    return _reduce(loss, reduction)


def _hsigmoid_paths(num_classes: int):
    """Complete-binary-tree paths: for each class, the internal-node ids
    visited and the left/right codes (static, computed host-side)."""
    import numpy as np

    depth = max(int(np.ceil(np.log2(max(num_classes, 2)))), 1)
    nodes = np.zeros((num_classes, depth), np.int32)
    codes = np.zeros((num_classes, depth), np.float32)
    mask = np.zeros((num_classes, depth), np.float32)
    for c in range(num_classes):
        # leaf id in a heap-layout complete tree with num_classes leaves
        j = c + num_classes - 1
        path = []
        while j > 0:
            parent = (j - 1) // 2
            path.append((parent, float(j == 2 * parent + 2)))
            j = parent
        for d, (node, code) in enumerate(reversed(path)):
            if d < depth:
                nodes[c, d] = node
                codes[c, d] = code
                mask[c, d] = 1.0
    return nodes, codes, mask


def hsigmoid_loss(x, label, weight, bias=None, num_classes: int | None = None,
                  reduction: str = "mean"):
    """Hierarchical sigmoid (reference ``operators/hierarchical_sigmoid_op``):
    O(log V) classification over a complete binary tree. ``weight`` is
    [num_classes - 1, D] internal-node vectors."""
    num_classes = num_classes or (weight.shape[0] + 1)
    nodes, codes, mask = _hsigmoid_paths(num_classes)
    nodes_l = jnp.asarray(nodes)[label]          # [B, depth]
    codes_l = jnp.asarray(codes)[label]
    mask_l = jnp.asarray(mask)[label]
    w = weight[nodes_l]                          # [B, depth, D]
    logit = jnp.einsum("bd,bkd->bk", x, w)
    if bias is not None:
        logit = logit + bias[nodes_l]
    # BCE toward the path codes, masked to the real path length
    per_node = (jnp.maximum(logit, 0) - logit * codes_l
                + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    loss = jnp.sum(per_node * mask_l, axis=1)
    return _reduce(loss, reduction)


# ---------------------------------------------------------------------------
# N-d pooling + conv3d (generalize the 2D versions)
# ---------------------------------------------------------------------------

def _tuple_n(v, n):
    return tuple(v) if isinstance(v, (tuple, list)) else (v,) * n


def _pool_nd(x, nd, kernel_size, stride, padding, init, op, count_avg=False):
    k = _tuple_n(kernel_size, nd)
    s = _tuple_n(stride if stride is not None else kernel_size, nd)
    p = _tuple_n(padding, nd)
    window = (1, 1) + k
    strides = (1, 1) + s
    pads = ((0, 0), (0, 0)) + tuple((pi, pi) for pi in p)
    out = lax.reduce_window(x, init, op, window, strides, pads)
    if count_avg:
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return out / counts
    return out


def max_pool1d(x, kernel_size, stride=None, padding=0):
    return _pool_nd(x, 1, kernel_size, stride, padding, -jnp.inf, lax.max)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True):
    return _pool_nd(x, 1, kernel_size, stride, padding, 0.0, lax.add,
                    count_avg=True) if exclusive else _pool_nd(
        x, 1, kernel_size, stride, padding, 0.0, lax.add) / (
        _tuple_n(kernel_size, 1)[0])


def max_pool3d(x, kernel_size, stride=None, padding=0):
    return _pool_nd(x, 3, kernel_size, stride, padding, -jnp.inf, lax.max)


def avg_pool3d(x, kernel_size, stride=None, padding=0):
    return _pool_nd(x, 3, kernel_size, stride, padding, 0.0, lax.add,
                    count_avg=True)


def _adaptive_pool_nd(x, nd, output_size, op):
    out = _tuple_n(output_size, nd)
    spatial = x.shape[2:]
    if all(dim % size == 0 for size, dim in zip(out, spatial)):
        # even bins: one fused reduce_window
        k = tuple(dim // size for size, dim in zip(out, spatial))
        if op == "max":
            return _pool_nd(x, nd, k, k, 0, -jnp.inf, lax.max)
        return _pool_nd(x, nd, k, k, 0, 0.0, lax.add, count_avg=True)
    # uneven bins (any output size): per-axis static window gathers
    for d in range(nd):
        x = _adaptive_pool_axis(x, 2 + d, out[d], op)
    return x


def adaptive_avg_pool1d(x, output_size):
    return _adaptive_pool_nd(x, 1, output_size, "avg")


def adaptive_avg_pool3d(x, output_size):
    return _adaptive_pool_nd(x, 3, output_size, "avg")


def adaptive_max_pool1d(x, output_size):
    return _adaptive_pool_nd(x, 1, output_size, "max")


def adaptive_max_pool2d(x, output_size):
    return _adaptive_pool_nd(x, 2, output_size, "max")


def adaptive_max_pool3d(x, output_size):
    return _adaptive_pool_nd(x, 3, output_size, "max")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups: int = 1):
    """[N, C, D, H, W] conv (reference ``operators/conv_op`` 3D path)."""
    x, weight, bias = _amp_inputs("conv3d", x, weight, bias)
    s = _tuple_n(stride, 3)
    d = _tuple_n(dilation, 3)
    if isinstance(padding, str):
        pads = padding
    else:
        p = _tuple_n(padding, 3)
        pads = tuple((pi, pi) for pi in p)
    out = lax.conv_general_dilated(
        x, weight, window_strides=s, padding=pads, rhs_dilation=d,
        feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1, 1)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups: int = 1):
    """[N, C, L] conv via the general dilated conv."""
    x, weight, bias = _amp_inputs("conv1d", x, weight, bias)
    if isinstance(padding, str):
        pads = padding
    else:
        p = _tuple_n(padding, 1)
        pads = ((p[0], p[0]),)
    out = lax.conv_general_dilated(
        x, weight, window_strides=_tuple_n(stride, 1), padding=pads,
        rhs_dilation=_tuple_n(dilation, 1), feature_group_count=groups,
        dimension_numbers=("NCH", "OIH", "NCH"))
    if bias is not None:
        out = out + bias.reshape(1, -1, 1)
    return out


# ---------------------------------------------------------------------------
# Functional parity tail (reference python/paddle/nn/functional/*): aliases
# for ops that so far existed only as layers, plus the spatial-transformer
# pair and the remaining loss zoo.
# ---------------------------------------------------------------------------

def assign(x):
    """Copy (reference assign op)."""
    return jnp.array(x)


fc = linear            # reference fluid alias for the linear op
upsample = interpolate


def square_error_cost(input, label):
    return jnp.square(input - label)


def log_loss(input, label, epsilon: float = 1e-4):
    p = jnp.clip(input, epsilon, 1.0 - epsilon)
    return -label * jnp.log(p) - (1.0 - label) * jnp.log(1.0 - p)


def dice_loss(input, label, epsilon: float = 1e-5):
    """1 - 2|X∩Y| / (|X|+|Y|) over the trailing dims (reference
    dice_loss for segmentation; input probs, label one-hot/binary)."""
    reduce_dims = tuple(range(1, input.ndim))
    inter = jnp.sum(input * label, axis=reduce_dims)
    union = jnp.sum(input, axis=reduce_dims) + jnp.sum(label,
                                                      axis=reduce_dims)
    return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))


def sigmoid_focal_loss(logit, label, normalizer=None, alpha: float = 0.25,
                       gamma: float = 2.0, reduction: str = "sum"):
    """RetinaNet focal loss (reference sigmoid_focal_loss_op)."""
    p = jax.nn.sigmoid(logit)
    ce = (jnp.maximum(logit, 0) - logit * label
          + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    p_t = p * label + (1.0 - p) * (1.0 - label)
    a_t = alpha * label + (1.0 - alpha) * (1.0 - label)
    loss = a_t * jnp.power(1.0 - p_t, gamma) * ce
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


def npair_loss(anchor, positive, labels, l2_reg: float = 0.002):
    """N-pair metric-learning loss (reference npair_loss)."""
    sim = anchor @ positive.T                                 # [B, B]
    same = (labels[:, None] == labels[None, :]).astype(sim.dtype)
    targets = same / jnp.maximum(same.sum(axis=1, keepdims=True), 1.0)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.mean(jnp.sum(targets * logp, axis=1))
    reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(anchor), -1))
                    + jnp.mean(jnp.sum(jnp.square(positive), -1))) / 2
    return ce + reg


def diag_embed(x, offset: int = 0):
    """[..., N] → [..., N, N] diagonal matrices (reference diag_embed)."""
    n = x.shape[-1]
    base = jnp.eye(n, dtype=x.dtype)
    out = x[..., None] * base
    if offset:
        pad = abs(offset)
        z = jnp.zeros(x.shape[:-1] + (n + pad, n + pad), x.dtype)
        if offset > 0:
            out = z.at[..., :n, pad:].set(out)
        else:
            out = z.at[..., pad:, :n].set(out)
    return out


def instance_norm(x, weight=None, bias=None, epsilon: float = 1e-5):
    """Per-(sample, channel) normalization over spatial dims."""
    return group_norm(x, x.shape[1], weight, bias, epsilon, "NCHW")


def data_norm(x, batch_size, batch_sum, batch_square_sum,
              epsilon: float = 1e-4):
    """Normalization from accumulated global statistics (reference
    data_norm_op — the PS-era scale-invariant input norm: accumulators
    are updated asynchronously server-side)."""
    mean = batch_sum / batch_size
    var = batch_square_sum / batch_size - jnp.square(mean)
    return (x - mean) * lax.rsqrt(jnp.maximum(var, 0.0) + epsilon)


def bilinear(x1, x2, weight, bias=None):
    """out_k = x1 W_k x2 (reference bilinear/bilinear_tensor_product)."""
    out = jnp.einsum("...i,oij,...j->...o", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


bilinear_tensor_product = bilinear


def row_conv(x, weight):
    """Lookahead temporal conv (see nn.RowConv)."""
    ctx = weight.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, ctx - 1), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(ctx):
        out = out + xp[:, i:i + x.shape[1]] * weight[i]
    return out


def spectral_norm(weight, u, n_power_iterations: int = 1,
                  epsilon: float = 1e-12, dim: int = 0):
    """W / sigma_max(W) with power iteration; returns (normalized, u)."""
    w = jnp.moveaxis(weight, dim, 0)
    w2 = w.reshape(w.shape[0], -1)
    v = None
    for _ in range(max(n_power_iterations, 1)):
        v = w2.T @ u
        v = v / jnp.maximum(jnp.linalg.norm(v), epsilon)
        u = w2 @ v
        u = u / jnp.maximum(jnp.linalg.norm(u), epsilon)
    sigma = u @ w2 @ v
    return weight / jax.lax.stop_gradient(sigma), jax.lax.stop_gradient(u)


def conv1d_transpose(x, weight, bias=None, stride: int = 1,
                     padding: int = 0):
    """weight [in, out, k]; output length (L-1)*s - 2p + k."""
    x, weight, bias = _amp_inputs("conv1d_transpose", x, weight, bias)
    k = weight.shape[2]
    w = jnp.flip(weight, axis=(2,)).transpose(1, 0, 2)
    y = lax.conv_general_dilated(
        x, w, window_strides=(1,), padding=[(k - 1 - padding,) * 2],
        lhs_dilation=(stride,), dimension_numbers=("NCH", "OIH", "NCH"))
    if bias is not None:
        y = y + bias.reshape(1, -1, 1)
    return y


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0):
    x, weight, bias = _amp_inputs("conv2d_transpose", x, weight, bias)
    s = _pair(stride)
    p = _pair(padding)
    k = weight.shape[2:]
    w = jnp.flip(weight, axis=(2, 3)).transpose(1, 0, 2, 3)
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1),
        padding=[(k[0] - 1 - p[0],) * 2, (k[1] - 1 - p[1],) * 2],
        lhs_dilation=s, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if bias is not None:
        y = y + bias.reshape(1, -1, 1, 1)
    return y


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0):
    x, weight, bias = _amp_inputs("conv3d_transpose", x, weight, bias)
    s = _tuple_n(stride, 3)
    p = _tuple_n(padding, 3)
    k = weight.shape[2:]
    w = jnp.flip(weight, axis=(2, 3, 4)).transpose(1, 0, 2, 3, 4)
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1, 1),
        padding=[(ki - 1 - pi,) * 2 for ki, pi in zip(k, p)],
        lhs_dilation=s, dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    if bias is not None:
        y = y + bias.reshape(1, -1, 1, 1, 1)
    return y


def affine_grid(theta, out_shape, align_corners: bool = True):
    """Sampling grid from affine matrices theta [N, 2, 3] for
    ``grid_sample`` (reference affine_grid_op; spatial transformers)."""
    n, c, h, w = out_shape

    def coords(size):
        if align_corners:
            return jnp.linspace(-1.0, 1.0, size)
        step = 2.0 / size
        return jnp.linspace(-1.0 + step / 2, 1.0 - step / 2, size)

    ys = coords(h)
    xs = coords(w)
    gx, gy = jnp.meshgrid(xs, ys)                 # [H, W]
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)     # [H, W, 3]
    return jnp.einsum("hwk,nok->nhwo", base, theta)  # [N, H, W, 2]


def grid_sample(x, grid, mode: str = "bilinear",
                padding_mode: str = "zeros", align_corners: bool = True):
    """Sample [N, C, H, W] at normalized grid [N, Hg, Wg, 2] (reference
    grid_sample_op; bilinear or nearest, zero/border padding)."""
    n, c, h, w = x.shape

    def unnormalize(coord, size):
        if align_corners:
            return (coord + 1.0) / 2.0 * (size - 1)
        return ((coord + 1.0) * size - 1.0) / 2.0

    gx = unnormalize(grid[..., 0], w)              # [N, Hg, Wg]
    gy = unnormalize(grid[..., 1], h)

    def gather(yi, xi):
        inside = ((yi >= 0) & (yi < h) & (xi >= 0) & (xi < w))
        yc = jnp.clip(yi, 0, h - 1)
        xc = jnp.clip(xi, 0, w - 1)
        vals = x[jnp.arange(n)[:, None, None], :, yc, xc]  # [N,Hg,Wg,C]
        if padding_mode == "zeros":
            vals = vals * inside[..., None]
        return vals

    if mode == "nearest":
        out = gather(jnp.round(gy).astype(jnp.int32),
                     jnp.round(gx).astype(jnp.int32))
        return jnp.moveaxis(out, -1, 1)

    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx = gx - x0
    wy = gy - y0
    out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[..., None]
           + gather(y0, x1) * (wx * (1 - wy))[..., None]
           + gather(y1, x0) * ((1 - wx) * wy)[..., None]
           + gather(y1, x1) * (wx * wy)[..., None])
    return jnp.moveaxis(out, -1, 1)


def nce(x, labels, weight, bias=None, *, num_total_classes: int,
        num_neg_samples: int = 10, key=None):
    """Noise-contrastive estimation loss (reference nce_op): binary
    logistic discrimination of the true class against uniformly sampled
    noise classes."""
    if key is None:
        from paddle_tpu.core import rng as _rng
        key = _rng.next_key()
    b = x.shape[0]
    noise = jax.random.randint(key, (b, num_neg_samples), 0,
                               num_total_classes)
    all_ids = jnp.concatenate([labels[:, None], noise], axis=1)  # [B,1+S]
    w = weight[all_ids]                                          # [B,1+S,D]
    logits = jnp.einsum("bd,bkd->bk", x, w)
    if bias is not None:
        logits = logits + bias[all_ids]
    # log-odds correction for uniform noise: log(S * 1/V)
    logits = logits - jnp.log(num_neg_samples / num_total_classes)
    targets = jnp.zeros_like(logits).at[:, 0].set(1.0)
    per = (jnp.maximum(logits, 0) - logits * targets
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.mean(jnp.sum(per, axis=1))
