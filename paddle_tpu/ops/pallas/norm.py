"""Fused row-normalization kernels (RMSNorm / LayerNorm).

Reference CUDA equivalents: ``paddle/fluid/operators/layer_norm_op.cu``
(Welford row statistics) and ``fused/skip_layernorm_op.cu``. One VMEM
pass per row block computes statistics + normalized output; the row
statistics (rstd, and mean for LayerNorm) are saved for the backward
pass, which fuses dx with the dw/db cross-row reductions (dw/db
accumulate into a revisited output block across the sequential grid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _support

_BLOCK_ROWS = 256
_LANES = 128


def _shape2d(x):
    h = x.shape[-1]
    n = x.size // h
    return n, h


def supported(x, weight, bias=None) -> bool:
    n, h = _shape2d(x)
    if h % 128 or h > 16384:
        return False
    br = min(_BLOCK_ROWS, n)
    if n % br or br % 8:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if weight is not None and weight.shape != (h,):
        return False
    return bias is None or (bias.shape == (h,) and (
        weight is None or bias.dtype == weight.dtype))


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = x * rstd
    y_ref[...] = (xhat * w_ref[...].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dw_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    rstd = rstd_ref[:, :1]
    xhat = x * rstd
    wg = g * w
    c = jnp.mean(wg * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (wg - xhat * c)).astype(dx_ref.dtype)
    dw_ref[...] += jnp.sum(g * xhat, axis=0)


def _rms_fwd(x2d, w, eps):
    n, h = x2d.shape
    br = min(_BLOCK_ROWS, n)
    nb = n // br
    y, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
        ],
        interpret=_support.interpret(),
        name="ptpu_rms_norm_fwd",
    )(x2d, w)
    return y, rstd


def _rms_bwd_call(x2d, w, rstd, g):
    n, h = x2d.shape
    br = min(_BLOCK_ROWS, n)
    nb = n // br
    dx, dw = pl.pallas_call(
        _rms_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((h,), jnp.float32),
        ],
        compiler_params=_support.compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=_support.interpret(),
        name="ptpu_rms_norm_bwd",
    )(x2d, w, rstd, g)
    return dx, dw


def _rms_fwd_dispatch(x2d, w, eps, part):
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.rms_fwd(eps)(x2d, w)
    return _rms_fwd(x2d, w, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rms(eps, part, x2d, w):
    y, _ = _rms_fwd_dispatch(x2d, w, eps, part)
    return y


def _rms_vjp_fwd(eps, part, x2d, w):
    y, rstd = _rms_fwd_dispatch(x2d, w, eps, part)
    return y, (x2d, w, rstd)


def _rms_vjp_bwd(eps, part, res, g):
    x2d, w, rstd = res
    if part:
        from paddle_tpu.ops.pallas import _partition
        dx, dw = _partition.rms_bwd(eps)(x2d, w, rstd, g)
    else:
        dx, dw = _rms_bwd_call(x2d, w, rstd, g)
    return dx, dw.astype(w.dtype)


_rms.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


def rms_norm(x, weight, epsilon: float = 1e-6, *, partitioned: bool = False):
    """Fused RMSNorm over the last axis. ``supported(x, weight)`` must
    hold. Matches ``nn.functional.rms_norm`` numerics (fp32 statistics).
    ``partitioned`` routes through the shard_map unit so the kernel runs
    per-shard under a multi-device mesh."""
    n, h = _shape2d(x)
    w = weight if weight is not None else jnp.ones((h,), x.dtype)
    y = _rms(float(epsilon), bool(partitioned), x.reshape(n, h), w)
    return y.reshape(x.shape)


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xhat * w + b).astype(y_ref.dtype)
    mean_ref[...] = jnp.broadcast_to(mean, mean_ref.shape)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _ln_bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, g_ref,
                   dx_ref, dw_ref, db_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    mean = mean_ref[:, :1]
    rstd = rstd_ref[:, :1]
    xhat = (x - mean) * rstd
    wg = g * w
    c1 = jnp.mean(wg, axis=1, keepdims=True)
    c2 = jnp.mean(wg * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (wg - c1 - xhat * c2)).astype(dx_ref.dtype)
    dw_ref[...] += jnp.sum(g * xhat, axis=0)
    db_ref[...] += jnp.sum(g, axis=0)


def _ln_fwd(x2d, w, b, eps):
    n, h = x2d.shape
    br = min(_BLOCK_ROWS, n)
    nb = n // br
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
        ],
        interpret=_support.interpret(),
        name="ptpu_layer_norm_fwd",
    )(x2d, w, b)


def _ln_fwd_dispatch(x2d, w, b, eps, part):
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.ln_fwd(eps)(x2d, w, b)
    return _ln_fwd(x2d, w, b, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ln(eps, b_dtype, part, x2d, w, b):
    y, _, _ = _ln_fwd_dispatch(x2d, w, b, eps, part)
    return y


def _ln_vjp_fwd(eps, b_dtype, part, x2d, w, b):
    y, mean, rstd = _ln_fwd_dispatch(x2d, w, b, eps, part)
    return y, (x2d, w, mean, rstd)


def _ln_bwd_call(x2d, w, mean, rstd, g):
    n, h = x2d.shape
    br = min(_BLOCK_ROWS, n)
    nb = n // br
    dx, dw, db = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((h,), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32),
        ],
        compiler_params=_support.compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=_support.interpret(),
        name="ptpu_layer_norm_bwd",
    )(x2d, w, mean, rstd, g)
    return dx, dw, db


def _ln_vjp_bwd(eps, b_dtype, part, res, g):
    x2d, w, mean, rstd = res
    if part:
        from paddle_tpu.ops.pallas import _partition
        dx, dw, db = _partition.ln_bwd(eps)(x2d, w, mean, rstd, g)
    else:
        dx, dw, db = _ln_bwd_call(x2d, w, mean, rstd, g)
    return dx, dw.astype(w.dtype), db.astype(b_dtype)


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def layer_norm(x, weight, bias, epsilon: float = 1e-5, *,
               partitioned: bool = False):
    """Fused LayerNorm over the last axis (``supported`` must hold).
    ``partitioned`` routes through the shard_map unit so the kernel runs
    per-shard under a multi-device mesh."""
    n, h = _shape2d(x)
    w = weight if weight is not None else jnp.ones((h,), x.dtype)
    b = bias if bias is not None else jnp.zeros((h,), x.dtype)
    y = _ln(float(epsilon), jnp.dtype(b.dtype).name, bool(partitioned),
            x.reshape(n, h), w, b)
    return y.reshape(x.shape)
