"""Fused rotary position embedding (RoPE) application.

The rotation is pure VPU work; fusing it keeps q/k in VMEM for one pass
instead of the split/concat traffic of the jnp path. North-star item
(BASELINE.json: "rope"); no reference CUDA equivalent exists (the
reference predates RoPE models) — numerics match
``nn.functional.apply_rotary``.

Layout: x [B, T, H, D], cos/sin [T, D/2]. Backward rotates by the
negative angle (same kernel, sign flag); cos/sin receive zero gradients
(they are tables derived from integer positions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import _support

_BLOCK_T = 256


def supported(x, cos, sin) -> bool:
    if x.ndim != 4 or cos.ndim != 2:
        return False
    B, T, H, D = x.shape
    if D % 2 or cos.shape != (T, D // 2) or sin.shape != cos.shape:
        return False
    bt = min(_BLOCK_T, T)
    if T % bt or bt % 8:
        return False
    return x.dtype in (jnp.float32, jnp.bfloat16)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, d2, sign):
    x1 = x_ref[0, 0, :, :d2].astype(jnp.float32)
    x2 = x_ref[0, 0, :, d2:].astype(jnp.float32)
    cos = cos_ref[...]
    sin = sin_ref[...] * sign
    o_ref[0, 0, :, :d2] = (x1 * cos - x2 * sin).astype(o_ref.dtype)
    o_ref[0, 0, :, d2:] = (x2 * cos + x1 * sin).astype(o_ref.dtype)


def _rope_call(x, cos, sin, sign):
    B, T, H, D = x.shape
    d2 = D // 2
    bt = min(_BLOCK_T, T)
    xt = jnp.transpose(x, (0, 2, 1, 3))  # [B, H, T, D]: Mosaic-tileable
    ot = pl.pallas_call(
        functools.partial(_rope_kernel, d2=d2, sign=sign),
        grid=(B, H, T // bt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((bt, d2), lambda b, h, i: (i, 0)),
            pl.BlockSpec((bt, d2), lambda b, h, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bt, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        interpret=_support.interpret(),
        name="ptpu_rope",
    )(xt, cos.astype(jnp.float32), sin.astype(jnp.float32))
    return jnp.transpose(ot, (0, 2, 1, 3))


def _rope_dispatch(x, cos, sin, sign, part):
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.rope(sign)(x, cos, sin)
    return _rope_call(x, cos, sin, sign)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rope(part, x, cos, sin):
    return _rope_dispatch(x, cos, sin, 1.0, part)


def _rope_fwd(part, x, cos, sin):
    return _rope_dispatch(x, cos, sin, 1.0, part), (cos, sin)


def _rope_bwd(part, res, g):
    cos, sin = res
    dx = _rope_dispatch(g, cos, sin, -1.0, part)
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin)


_rope.defvjp(_rope_fwd, _rope_bwd)


def apply_rotary(x, cos, sin, *, partitioned: bool = False):
    """Fused RoPE for [B, T, H, D] x with [T, D/2] cos/sin tables.
    ``partitioned`` routes through the shard_map unit (batch/seq/head
    shardable; the tables shard with the sequence)."""
    return _rope(bool(partitioned), x, cos, sin)
