"""``ptpu_kda_step``: the one-token step of Kimi Delta Attention
(``ops/kda.py``) on one TPU chip, for all sequences of a decode step at
once.

The step is bound by the state it moves, not by its arithmetic: a
``[128, 128]`` float32 state a head — 64 KB read, 64 KB written — for
~100 k multiply-adds. So the kernel's whole job is to read each state
ONCE and write it ONCE, in place: the stacked state ``[N, L, H, dk,
dv]`` (sequence-major, as the serving engine keeps its slots) is an
aliased operand (``input_output_aliases``), the layer is a
scalar-prefetched index into it, and a grid step takes the 16 heads of
one sequence as one 1 MB block. XLA's lines (``ops.kda.kda_step``)
scale, reduce, update and reduce again in separate passes over the
state unless its fusion happens to join them, and slice the layer out of
the stack first.

Inside a block the dk-vectors (``q``, ``k``, the decay, ``beta``) have
to lie along sublanes to scale the state's rows. They arrive as rows of
one ``[128, 128]`` tile — 8 rows a head: q, k, alpha, beta and four of
zeros — and one transpose turns every row into a column. The products
run on the vector unit in float32; no matrix unit pass rounds them.

Under ``jax.vmap`` (the engine maps its decode step over slots, each a
batch of one) the mapped axis IS the sequence axis: the call's own
batching rule (:func:`_fold`) folds it into ``N``, so the step holds
one call a layer on the unmapped ``layer``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _support

__all__ = ["kda_step", "supported", "HEADS_PER_BLOCK"]

HEADS_PER_BLOCK = 16        # 16 heads x 8 rows = one 128-row tile
_ROWS = 8                   # rows a head in the packed tile


def supported(rows, q, v) -> bool:
    """Kernel gate; ``ops.kda.kda_step`` stays on XLA's lines when
    False. ``rows`` [L, 1, H, dk, dv] float32 (one sequence a call: the
    engine's slot; a mapped axis joins it), ``q`` [1, H, dk], ``v`` [1,
    H, dv]. Raw dispatch only (one TPU chip), 128-wide heads in blocks
    of 16."""
    if _support.dispatch_mode() != "raw":
        return False
    if rows.ndim != 5 or rows.dtype != jnp.float32 or rows.shape[1] != 1:
        return False
    _, _, H, dk, dv = rows.shape
    return (dk == 128 and dv == 128 and H % HEADS_PER_BLOCK == 0
            and q.shape == (1, H, dk) and v.shape == (1, H, dv))


def _kernel(layer_ref, s_ref, p_ref, v_ref, o_ref, s_out_ref):
    del layer_ref                       # read by the index maps
    cols = p_ref[0, 0].T                # [dk, 16 heads x 8]
    for h in range(HEADS_PER_BLOCK):
        at = _ROWS * h
        qc, kc = cols[:, at:at + 1], cols[:, at + 1:at + 2]
        ac, bc = cols[:, at + 2:at + 3], cols[:, at + 3:at + 4]
        S = s_ref[0, 0, h] * ac                                 # [dk, dv]
        r = v_ref[0, h:h + 1, :] - jnp.sum(S * kc, axis=0, keepdims=True)
        S = S + (kc * bc) * r
        o_ref[0, h:h + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)
        s_out_ref[0, 0, h] = S


def _raw(state, layer, q, k, v, alpha, beta):
    """``state`` [N, L, H, dk, dv]; ``layer`` int32 scalar; q/k/alpha
    [N, H, dk], v [N, H, dv], beta [N, H], all float32. Returns ``(o
    [N, H, dv], state)``."""
    N, L, H, dk, dv = state.shape
    Hb = HEADS_PER_BLOCK
    packed = jnp.stack(
        [q, k, alpha, jnp.broadcast_to(beta[..., None], q.shape)]
        + [jnp.zeros_like(q)] * (_ROWS - 4), axis=2)         # [N, H, 8, dk]
    packed = packed.reshape(N, H // Hb, Hb * _ROWS, dk)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, H // Hb),
        in_specs=[
            pl.BlockSpec((1, 1, Hb, dk, dv),
                         lambda n, hb, lay: (n, lay[0], hb, 0, 0)),
            pl.BlockSpec((1, 1, Hb * _ROWS, dk),
                         lambda n, hb, lay: (n, hb, 0, 0)),
            pl.BlockSpec((1, Hb, dv), lambda n, hb, lay: (n, hb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Hb, dv), lambda n, hb, lay: (n, hb, 0)),
            pl.BlockSpec((1, 1, Hb, dk, dv),
                         lambda n, hb, lay: (n, lay[0], hb, 0, 0)),
        ])
    o, state = pl.pallas_call(
        _kernel, grid_spec=spec,
        out_shape=[jax.ShapeDtypeStruct((N, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operands count the prefetched scalar: the state is the second
        input_output_aliases={1: 1},
        interpret=_support.interpret(),
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=6 * N * H * dk * dv, transcendentals=0,
            bytes_accessed=2 * 4 * N * H * dk * dv),
        name="ptpu_kda_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state, packed, v)
    return o, state


def _call(rows, layer, q, k, v, alpha, beta):
    """One sequence: ``rows`` [L, 1, H, dk, dv], the vectors [1, H, *]."""
    L = rows.shape[0]
    o, state = _raw(rows.reshape((1, L) + rows.shape[2:]), layer, q, k, v,
                    alpha, beta)
    return o, state.reshape(rows.shape)


_rows_call = jax.custom_batching.custom_vmap(_call)


@_rows_call.def_vmap
def _fold(axis_size, in_batched, rows, layer, *vecs):
    """The mapped axis joins the sequences: ``rows`` [S, L, 1, H, dk,
    dv] is the kernel's own layout with no copy."""
    rows_b, layer_b, *vecs_b = in_batched
    if layer_b or not rows_b:
        # a mapped layer, or one state under many queries: jax's own rule
        axes = [0 if b else None for b in in_batched]
        return jax.vmap(_call, in_axes=axes)(rows, layer, *vecs), (True, True)
    vecs = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip(vecs, vecs_b)]
    S, L = rows.shape[:2]
    o, state = _raw(rows.reshape((S, L) + rows.shape[3:]), layer,
                    *(x[:, 0] for x in vecs))
    return (o[:, None], state.reshape(rows.shape)), (True, True)


@functools.partial(jax.named_call, name="kda_step_kernel")
def kda_step(rows, layer, q, k, v, g, beta):
    """As ``ops.kda.kda_step`` (the caller has asked :func:`supported`):
    ``(o [1, H, dv], rows)``."""
    return _rows_call(rows, jnp.asarray(layer, jnp.int32), q, k, v,
                      jnp.exp(g), beta)
