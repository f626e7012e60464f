"""Flash attention for TPU — Pallas kernels, forward + backward.

Replaces the reference's fused attention CUDA path
(``paddle/fluid/operators/fused/multihead_matmul_op.cu``: cuBLAS batched
GEMM + softmax kernel, which materializes the [B, H, T, T] score matrix).
Here the online-softmax (flash) formulation streams K/V blocks through
VMEM so the score matrix never exists in HBM, q/k/v blocks feed the MXU
as [block, head_dim] tiles, and the [B,H,T] log-sum-exp is saved for the
backward pass (``jax.custom_vjp``).

The public entry takes the framework-wide [B, T, H, D] layout
(``paddle_tpu/nn/attention.py``) and transposes to [B, H, T, D] at the
kernel boundary (Mosaic requires the last two block dims to be the
tiled ones; XLA usually fuses the transpose into the producing
projection). Row statistics (lse, and the backward's delta) are stored
lane-replicated as [B, H, T, 128] — the Mosaic-aligned layout for
per-row scalars. Grouped-query attention maps q-head h to kv-head
``h // (Hq // Hkv)`` in the index maps; the backward pass computes
per-q-head dk/dv and sums over the group outside the kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _support

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
LANES = 128
NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() finite


def _blocks(Tq: int, Tk: int, block_q, block_k):
    bq = min(block_q or DEFAULT_BLOCK_Q, Tq)
    bk = min(block_k or DEFAULT_BLOCK_K, Tk)
    return bq, bk


def supported(q, k, v, *, causal: bool = False, block_q=None,
              block_k=None) -> bool:
    """Shape/dtype gate for the kernel; callers fall back to the einsum
    path when False."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return False
    B, Tq, Hq, D = q.shape
    _, Tk, Hkv, Dk = k.shape
    if v.shape != k.shape or Dk != D:
        return False
    if Hq % Hkv != 0:
        return False
    if D not in (64, 128, 256):
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    bq, bk = _blocks(Tq, Tk, block_q, block_k)
    if Tq % bq or Tk % bk:
        return False
    if bq % 8 or bk % 128:  # sublane/lane alignment of the [bq, bk] tile
        return False
    return True


def _causal_mask(s, iq, ik, bq, bk, delta_qk):
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + iq * bq + delta_qk
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
    return jnp.where(col <= row, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, bq, bk, nk, delta_qk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, ik, bq, bk, delta_qk)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, :, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv

    if causal:
        # skip blocks entirely above the diagonal
        @pl.when(ik * bk <= iq * bq + (bq - 1) + delta_qk)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l_safe)
        lse_ref[0, 0, :, :] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _fwd(qt, kt, vt, causal, scale, block_q, block_k):
    """qt/kt/vt in [B, H, T, D]; returns (o [B,H,Tq,D], lse [B,H,Tq,128])."""
    B, Hq, Tq, D = qt.shape
    _, Hkv, Tk, _ = kt.shape
    bq, bk = _blocks(Tq, Tk, block_q, block_k)
    nq, nk = Tq // bq, Tk // bk
    group = Hq // Hkv
    grid = (B, Hq, nq, nk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        delta_qk=Tk - Tq)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, qt.dtype),
            jax.ShapeDtypeStruct((B, Hq, Tq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_flash_fwd",
    )(qt, kt, vt)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref, dq_ref,
               dq_acc, *, scale, causal, bq, bk, nk, delta_qk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, ik, bq, bk, delta_qk)
        lse = lse_ref[0, 0, :, :1]               # (bq, 1)
        p = jnp.exp(s - lse)
        do = do_ref[0, 0, :, :]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0, :, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dta = dta_ref[0, 0, :, :1]               # rowsum(do * o)
        ds = p * (dp - dta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * bk <= iq * bq + (bq - 1) + delta_qk)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, bq, bk, nq, delta_qk):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, ik, bq, bk, delta_qk)
        lse = lse_ref[0, 0, :, :1]
        p = jnp.exp(s - lse)                     # (bq, bk)
        do = do_ref[0, 0, :, :]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0, :, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dta = dta_ref[0, 0, :, :1]
        ds = p * (dp - dta) * scale              # (bq, bk)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * bk <= iq * bq + (bq - 1) + delta_qk)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_impl(qt, kt, vt, ot, lse, do_t, causal, scale, block_q, block_k):
    B, Hq, Tq, D = qt.shape
    _, Hkv, Tk, _ = kt.shape
    bq, bk = _blocks(Tq, Tk, block_q, block_k)
    nq, nk = Tq // bq, Tk // bk
    group = Hq // Hkv

    # delta_i = rowsum(dO_i * O_i), lane-replicated to [B, H, Tq, 128]
    dta = jnp.einsum("bhtd,bhtd->bht", do_t.astype(jnp.float32),
                     ot.astype(jnp.float32))
    dta = jnp.broadcast_to(dta[..., None], (B, Hq, Tq, LANES))

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0))
    row_spec = pl.BlockSpec(
        (1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0))

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        delta_qk=Tk - Tq)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_flash_bwd_dq",
    )(qt, kt, vt, do_t, lse, dta)

    # dkv grid order: (b, h, ik, iq) — q blocks innermost
    q_spec_t = pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0))
    kv_spec_t = pl.BlockSpec(
        (1, 1, bk, D), lambda b, h, j, i, g=group: (b, h // g, j, 0))
    row_spec_t = pl.BlockSpec(
        (1, 1, bq, LANES), lambda b, h, j, i: (b, h, i, 0))
    dkv_out_spec = pl.BlockSpec(
        (1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0))

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nq=nq,
        delta_qk=Tk - Tq)
    # per-q-head dk/dv ([B, Hq, Tk, D]); GQA groups are reduced below
    dk_q, dv_q = pl.pallas_call(
        dkv_kernel,
        grid=(B, Hq, nk, nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Tk, D), kt.dtype),
            jax.ShapeDtypeStruct((B, Hq, Tk, D), vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_flash_bwd_dkv",
    )(qt, kt, vt, do_t, lse, dta)

    if group > 1:
        dk = dk_q.reshape(B, Hkv, group, Tk, D).sum(axis=2).astype(kt.dtype)
        dv = dv_q.reshape(B, Hkv, group, Tk, D).sum(axis=2).astype(vt.dtype)
    else:
        dk, dv = dk_q, dv_q
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring (operates in [B, H, T, D])
# ---------------------------------------------------------------------------

def _fwd_dispatch(qt, kt, vt, causal, scale, block_q, block_k, part):
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.flash_fwd(causal, scale, block_q, block_k)(
            qt, kt, vt)
    return _fwd(qt, kt, vt, causal, scale, block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash(causal, scale, block_q, block_k, part, qt, kt, vt):
    o, _ = _fwd_dispatch(qt, kt, vt, causal, scale, block_q, block_k, part)
    return o


def _flash_fwd(causal, scale, block_q, block_k, part, qt, kt, vt):
    o, lse = _fwd_dispatch(qt, kt, vt, causal, scale, block_q, block_k, part)
    return o, (qt, kt, vt, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, part, res, do):
    qt, kt, vt, o, lse = res
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.flash_bwd(causal, scale, block_q, block_k)(
            qt, kt, vt, o, lse, do)
    return _bwd_impl(qt, kt, vt, o, lse, do, causal, scale, block_q, block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False, scale=None,
                    block_q: int | None = None, block_k: int | None = None,
                    partitioned: bool = False):
    """Flash attention, [B, T, H, D] in/out. Differentiable (custom VJP).

    ``supported(q, k, v, causal=...)`` must hold; callers are expected to
    fall back to the dense path otherwise (``nn.functional.
    scaled_dot_product_attention`` does this automatically).
    ``partitioned`` routes both passes through the shard_map units so the
    kernels run per-shard (batch/head sharded, sequence replicated) under
    a multi-device mesh.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o = _flash(causal, float(scale), block_q, block_k, bool(partitioned),
               qt, kt, vt)
    return jnp.transpose(o, (0, 2, 1, 3))
