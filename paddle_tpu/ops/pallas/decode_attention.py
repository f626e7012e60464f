"""Fused single-token decode attention over the static KV cache.

The serving hot loop: every generated token attends its one query
against the filled prefix of the per-layer cache. The XLA einsum path
pays three taxes this kernel deletes (the timings below date from an
earlier build and are not measured on the current code):

- the per-layer ``lax.scan`` slice of the stacked cache materializes a
  full layer copy per layer per step (XLA cannot fuse a dynamic-slice
  producer into a custom call — measured 1.45 ms/step of pure copy on
  the bench geometry). This kernel takes the WHOLE stacked
  [L, B, Hkv, S, D] buffers and selects the layer in its index maps via
  a scalar-prefetched layer id — no slice ever exists;
- it reads the whole [S] buffer even when only ``index`` of ``S``
  positions are live — the index maps clamp the block id to the filled
  prefix (blocks past the fill repeat the previous block index and
  Mosaic elides the repeated DMA);
- the int8 cache dequant materializes full bf16 copies of k/v — here
  the int8 blocks go MXU-ready as ``convert(int8)`` and both scales fold
  into the [G, bk] logit/prob planes (column-wise multiplies), so the
  HBM traffic really is the int8 bytes.

The fresh token's k/v (raw dtype, exact) join the softmax as grid step
0; cache blocks stream as steps 1..nk with positions ``>= index``
masked. Layout contract matches ``models._common.init_kv_cache``
(stacked [L, B, Hkv, S, D], f32 scales [L, B, Hkv, S] for int8);
q [B, 1, Hq, D].

Reference role: the decode half of the reference's fused attention
serving path (``paddle/fluid/operators/fused/multihead_matmul_op.cu``
feeding ``inference/api/analysis_predictor.h``); inference-only, no VJP.

Batching: the GenerationEngine's fused decode step invokes this kernel
under ``jax.vmap`` (one mapped axis per engine slot, per-slot caches
and fill positions). The per-slot ``index`` is a scalar-prefetch
operand, and jax's pallas batching rule lowers a batched scalar-prefetch
operand as an explicit loop over the mapped axis (one kernel call per
slot on a dynamic slice of that slot's cache), not by growing the grid.
``tests/test_decode_attention.py`` pins the behavior (vmapped output
bit-equal to per-slot calls, interpret mode) along with the off-TPU
einsum fallback arm; ``chip_smoke.py`` compiles and checks it on the
chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _support

LANES = 128
NEG_INF = -1e30


def _block_k(S: int) -> int:
    for bk in (512, 256, 128):
        if S % bk == 0:
            return bk
    return 0


def supported(q, cache) -> bool:
    """Kernel gate; callers fall back to the einsum path when False.
    Decode chunks only (T == 1); prefill always takes the flash path.
    ``cache`` holds the STACKED buffers ([L, B, Hkv, S, D]). Under a
    multi-device mesh the shard_map unit (``_partition.decode_attn``)
    runs the kernel per batch/head shard — TP-sharded serving keeps the
    kernel path when tp divides num_kv_heads (the constraint
    ``shard_for_inference`` already validates); otherwise the kernel
    runs on replicated heads."""
    mode = _support.dispatch_mode()
    if mode not in ("raw", "partitioned"):
        return False
    if q.ndim != 4 or q.shape[1] != 1:
        return False
    B, T, Hq, D = q.shape
    k = cache[0]
    if k.ndim != 5:
        return False
    _, _, Hkv, S, Dk = k.shape
    if Dk != D or D not in (64, 128, 256) or Hq % Hkv:
        return False
    if _block_k(S) == 0:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    quantized = len(cache) == 4
    if quantized and k.dtype != jnp.int8:
        return False
    if not quantized and k.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return True


def _kernel(sp_ref, q_ref, kn_ref, vn_ref, kc_ref, vc_ref, *rest,
            scale, bk, nk, G, Hkv, quantized, out_dtype):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    j = pl.program_id(1)
    idx = sp_ref[1]

    @pl.when(j == 0)
    def _fresh():
        # the chunk's own token: p = exp(s - m) = 1, l = 1, acc = v_new
        q = q_ref[0].astype(jnp.float32)            # [Hq, D]
        kn = kn_ref[0].astype(jnp.float32)          # [Hkv, D]
        vn = vn_ref[0].astype(jnp.float32)
        for h in range(Hkv):
            rows = slice(h * G, (h + 1) * G)
            s_h = jnp.sum(q[rows] * kn[h:h + 1], axis=1,
                          keepdims=True) * scale    # [G, 1]
            m_ref[rows, :] = jnp.broadcast_to(s_h, (G, LANES))
            acc_ref[rows, :] = jnp.broadcast_to(vn[h:h + 1],
                                                (G, vn.shape[1]))
        l_ref[:, :] = jnp.ones_like(l_ref)

    last_block = jnp.maximum(idx - 1, 0) // bk

    @pl.when((j > 0) & (j - 1 <= last_block))
    def _cache_block():
        jb = j - 1
        # ONE block-diagonal dot for ALL heads instead of Hkv unrolled
        # [G, D]×[D, bk] matvecs: q [Hq, D] against the whole block
        # [Hkv·bk, D] computes every cross-head product and the
        # block-diagonal mask kills the wrong-head logits (exp(NEG) = 0,
        # so the p·V dot's cross-head sums vanish exactly). The waste
        # FLOPs are Hkv× the useful ones — irrelevant next to HBM (the
        # kernel is bandwidth-bound); the instruction-count drop is what
        # matters (the unrolled form measured ~56 µs per grid step,
        # ~16× its DMA bound, and scaled linearly with batch).
        # Operands stay in their stored dtype through the MXU (bf16, or
        # a bare int8 convert) with f32 accumulation.
        q = q_ref[0]                                # [Hq, D], model dtype
        Hq, D = q.shape
        cdt = q.dtype if kc_ref.dtype == jnp.int8 else kc_ref.dtype
        if q.dtype != cdt:
            q = q.astype(cdt)
        kb = kc_ref[0, 0]                           # [Hkv, bk, D]
        if kb.dtype != cdt:
            kb = kb.astype(cdt)
        kb = kb.reshape(Hkv * bk, D)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Hq, Hkv·bk]
        if quantized:
            # per-position scale folds into the logit plane (per column)
            s = s * ks_ref[0, 0].reshape(1, Hkv * bk)
        row_h = jax.lax.broadcasted_iota(
            jnp.int32, (Hq, Hkv * bk), 0) // G
        col = jax.lax.broadcasted_iota(jnp.int32, (Hq, Hkv * bk), 1)
        pos = jb * bk + col % bk
        valid = (row_h == col // bk) & (pos < idx)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                      # [Hq, Hkv·bk]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        if quantized:
            # v scale folds into the prob plane
            p = p * vs_ref[0, 0].reshape(1, Hkv * bk)
        vb = vc_ref[0, 0]
        if vb.dtype != cdt:
            vb = vb.astype(cdt)
        pv = jax.lax.dot_general(
            p.astype(cdt), vb.reshape(Hkv * bk, D),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [Hq, D]
        acc_ref[:, :] = acc_ref[:, :] * alpha + pv

    @pl.when(j == nk)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:, :] / jnp.where(l == 0.0, 1.0, l)).astype(
            out_dtype)


def decode_attention(q, k_new, v_new, cache, layer, index, *, scale: float):
    """q [B, 1, Hq, D]; k_new/v_new [B, Hkv, 1, D] (this step's raw k/v);
    ``cache`` the STACKED read-only buffers ([L, B, Hkv, S, D], int8
    layout adds [L, B, Hkv, S] scales); ``layer`` this block's layer id
    (traced under the layer scan); ``index`` traced int32 fill position
    (the layer's cache holds tokens [0, index)). Returns [B, 1, Hq, D]."""
    B, T, Hq, D = q.shape
    Hkv = k_new.shape[1]
    G = Hq // Hkv
    quantized = len(cache) == 4

    q2 = q.reshape(B, Hq, D)
    kn2 = k_new.reshape(B, Hkv, D)
    vn2 = v_new.reshape(B, Hkv, D)
    sp = jnp.stack([jnp.asarray(layer, jnp.int32),
                    jnp.asarray(index, jnp.int32)])

    if _support.dispatch_mode() == "partitioned":
        from paddle_tpu.ops.pallas import _partition
        out = _partition.decode_attn(float(scale), quantized)(
            sp, q2, kn2, vn2, *cache)
    else:
        out = raw_call(sp, q2, kn2, vn2, *cache, scale=scale)
    return out.reshape(B, 1, Hq, D)


def raw_call(sp, q2, kn2, vn2, *cache, scale: float):
    """The pallas_call on (per-shard) local shapes: sp = int32[2]
    (layer, index); q2 [B, Hq, D]; kn2/vn2 [B, Hkv, D]; cache the
    stacked buffers. Returns [B, Hq, D]."""
    B, Hq, D = q2.shape
    Hkv = kn2.shape[1]
    G = Hq // Hkv
    quantized = len(cache) == 4
    kc, vc = cache[0], cache[1]
    S = kc.shape[3]
    bk = _block_k(S)
    nk = S // bk

    def cache_map(b, j, sp_ref):
        last = jnp.maximum(sp_ref[1] - 1, 0) // bk
        return (sp_ref[0], b, 0,
                jnp.minimum(jnp.maximum(j - 1, 0), last), 0)

    def scale_map(b, j, sp_ref):
        last = jnp.maximum(sp_ref[1] - 1, 0) // bk
        return (sp_ref[0], b, 0, jnp.minimum(jnp.maximum(j - 1, 0), last))

    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, 1, Hkv, bk, D), cache_map),
        pl.BlockSpec((1, 1, Hkv, bk, D), cache_map),
    ]
    args = [q2, kn2, vn2, kc, vc]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, Hkv, bk), scale_map),
                     pl.BlockSpec((1, 1, Hkv, bk), scale_map)]
        args += [cache[2], cache[3]]

    kernel = functools.partial(
        _kernel, scale=scale, bk=bk, nk=nk, G=G, Hkv=Hkv,
        quantized=quantized, out_dtype=q2.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk + 1),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hq, D), lambda b, j, s: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hq, D), jnp.float32),
                pltpu.VMEM((Hq, LANES), jnp.float32),
                pltpu.VMEM((Hq, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q2.dtype),
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_decode_attn",
    )(sp, *args)
