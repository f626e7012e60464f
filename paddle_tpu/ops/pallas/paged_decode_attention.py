"""Page-table-aware single-token decode attention over the paged pool.

The paged serving engine's decode step reads K/V through the page table
one layer at a time (``models.generation.PagedCache.read_layer``): a
gather of every page of the slot's table row — capacity, not fill —
into a per-layer contiguous view, which the einsum arm of
``cached_attention`` then attends over. This kernel is the next step
down: it deletes that per-layer copy the same way ``decode_attention``
deleted the per-layer ``lax.scan`` slice — the page indirection moves
INTO the pallas index maps. The scalar-prefetch row carries
``[layer, index, table...]``, and the page-block index map

    page id = sp_ref[b, 2 + min(max(j - 1, 0), last_live_page)]

reads the slot's device-resident page table directly — grid step ``j``
DMAs physical page ``table[j - 1]`` of the pool, so the persistent HBM
(the pool) is the only cache the kernel ever touches, and only its live
pages. Blocks past the filled prefix repeat the last live page id and
Mosaic elides the repeated DMA, exactly the stacked-layer clamp trick.

Everything else is the ``decode_attention`` recipe on a page-shaped
block: the fresh token's raw k/v joins the streaming softmax as grid
step 0; pages stream as steps 1..M with positions ``>= index`` masked
(position ``p`` lives in page ``p // P`` at offset ``p % P``, matching
``paged_gather``'s view); one block-diagonal all-heads dot per page;
int8 pool scales fold into the logit/prob planes so HBM traffic stays
the int8 bytes.

Pool layout contract matches ``models.generation.init_paged_cache``:
k/v leaves ``[num_pages + 1, L, Hkv, P, D]`` (page id 0 = the reserved
null page), int8 layout adds f32 scale leaves
``[num_pages + 1, L, Hkv, P]``. ``table`` is one slot's int32 page-id
row — the same row the ``FLAGS_gen_device_pt`` engine keeps device-
resident, which is what makes "index maps read the page table" a
zero-upload statement end to end.

Status: interpreter-mode tests (``tests/test_paged_decode_attention.py``)
pin the kernel bit-exact to ``paged_gather`` + masked attention per
slot, under ``jax.vmap``, and for the int8 4-leaf layout — the
hardware-independent result. It is NOT on the engine's path: the paged
programs attend through ``PagedCache`` and the einsum arm (a joint f32
softmax; this kernel's online softmax orders every sum differently).
Whether a kernel that reads only live pages beats the per-layer gather
of capacity is ROADMAP C2, to be decided on the serving cell; note that
under ``jax.vmap`` a pallas call with a batched scalar-prefetch operand
becomes a loop over the mapped axis that slices every other operand per
iteration, so the engine has to call it over the slot axis itself
(``paged_decode_attention`` takes ``[B, ...]`` rows and tables), not
under its vmap. Off-TPU callers take the ``paged_reference`` einsum
fallback under the same ``supported()`` gate as the stacked kernel.
Multi-device meshes fall back too (no ``_partition`` unit yet — the
pool's KV-head shard would need a per-shard grid).
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


def supported(q, pool, table) -> bool:
    """Kernel gate; callers fall back to :func:`paged_reference` when
    False. ``q`` [B, 1, Hq, D] (decode chunks only); ``pool`` the paged
    leaves ([N, L, Hkv, P, D], int8 adds [N, L, Hkv, P] scales);
    ``table`` [B, M] int32 page rows. Raw dispatch only — a
    multi-device mesh has no partitioned wrapper for the paged layout
    yet, so it stays on the gather+einsum path."""
    if _support.dispatch_mode() != "raw":
        return False
    if q.ndim != 4 or q.shape[1] != 1:
        return False
    B, T, Hq, D = q.shape
    k = pool[0]
    if k.ndim != 5:
        return False
    _, _, Hkv, P, Dk = k.shape
    if Dk != D or D not in (64, 128, 256) or Hq % Hkv:
        return False
    if P % 8 or table.ndim != 2 or table.shape[0] != B:
        return False
    if _support.on_tpu() and not _support.interpret() and (Hkv * P) % LANES:
        return False                  # lane-aligned page blocks only
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    quantized = len(pool) == 4
    if quantized and k.dtype != jnp.int8:
        return False
    if not quantized and k.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return True


def _kernel(sp_ref, q_ref, kn_ref, vn_ref, kp_ref, vp_ref, *rest,
            scale, P, M, G, Hkv, quantized, out_dtype):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    idx = sp_ref[b, 1]

    @pl.when(j == 0)
    def _fresh():
        # the step's own token: p = exp(s - m) = 1, l = 1, acc = v_new
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

    last_page = jnp.maximum(idx - 1, 0) // P

    @pl.when((j > 0) & (j - 1 <= last_page))
    def _page_block():
        jb = j - 1
        # ONE block-diagonal dot for ALL heads over the page (the
        # decode_attention trick at page granularity): q [Hq, D]
        # against the whole [Hkv·P, D] page computes every cross-head
        # product, the mask kills the wrong-head logits exactly.
        q = q_ref[0]                                # [Hq, D], model dtype
        Hq, D = q.shape
        cdt = q.dtype if kp_ref.dtype == jnp.int8 else kp_ref.dtype
        if q.dtype != cdt:
            q = q.astype(cdt)
        kb = kp_ref[0, 0]                           # [Hkv, P, D]
        if kb.dtype != cdt:
            kb = kb.astype(cdt)
        kb = kb.reshape(Hkv * P, D)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Hq, Hkv·P]
        if quantized:
            # per-position scale folds into the logit plane (per column)
            s = s * ks_ref[0, 0].reshape(1, Hkv * P)
        row_h = jax.lax.broadcasted_iota(
            jnp.int32, (Hq, Hkv * P), 0) // G
        col = jax.lax.broadcasted_iota(jnp.int32, (Hq, Hkv * P), 1)
        pos = jb * P + col % P       # paged_gather's view coordinate
        valid = (row_h == col // P) & (pos < idx)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                      # [Hq, Hkv·P]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        if quantized:
            # v scale folds into the prob plane
            p = p * vs_ref[0, 0].reshape(1, Hkv * P)
        vb = vp_ref[0, 0]
        if vb.dtype != cdt:
            vb = vb.astype(cdt)
        pv = jax.lax.dot_general(
            p.astype(cdt), vb.reshape(Hkv * P, D),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [Hq, D]
        acc_ref[:, :] = acc_ref[:, :] * alpha + pv

    @pl.when(j == M)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:, :] / jnp.where(l == 0.0, 1.0, l)).astype(
            out_dtype)


def raw_call(sp, q2, kn2, vn2, *pool, scale: float):
    """The pallas_call on local shapes: sp int32 [B, 2 + M] rows of
    ``[layer, index, table...]``; q2 [B, Hq, D]; kn2/vn2 [B, Hkv, D];
    ``pool`` the paged leaves. Returns [B, Hq, D]."""
    B, Hq, D = q2.shape
    Hkv = kn2.shape[1]
    G = Hq // Hkv
    quantized = len(pool) == 4
    kp, vp = pool[0], pool[1]
    P = kp.shape[3]
    M = sp.shape[1] - 2

    def page_map(b, j, sp_ref):
        # THE point of this kernel: the block's pool coordinate is read
        # straight out of the slot's page-table row. Steps past the
        # filled prefix clamp to the last live page (repeated DMA
        # elided), mirroring the stacked kernel's fill clamp.
        last = jnp.maximum(sp_ref[b, 1] - 1, 0) // P
        jp = jnp.minimum(jnp.maximum(j - 1, 0), last)
        return (sp_ref[b, 2 + jp], sp_ref[b, 0], 0, 0, 0)

    def scale_map(b, j, sp_ref):
        last = jnp.maximum(sp_ref[b, 1] - 1, 0) // P
        jp = jnp.minimum(jnp.maximum(j - 1, 0), last)
        return (sp_ref[b, 2 + jp], sp_ref[b, 0], 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, 1, Hkv, P, D), page_map),
        pl.BlockSpec((1, 1, Hkv, P, D), page_map),
    ]
    args = [q2, kn2, vn2, kp, vp]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, Hkv, P), scale_map),
                     pl.BlockSpec((1, 1, Hkv, P), scale_map)]
        args += [pool[2], pool[3]]

    kernel = functools.partial(
        _kernel, scale=scale, P=P, M=M, G=G, Hkv=Hkv,
        quantized=quantized, out_dtype=q2.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, M + 1),
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
        name="ptpu_paged_decode_attn",
    )(sp, *args)


def paged_reference(q, k_new, v_new, pool, table, layer, index, *,
                    scale: float):
    """The gather+einsum semantics the kernel must match, and the
    off-TPU fallback arm: ``paged_gather`` the slot's pages at
    ``layer``, dequantize, mask positions ``>= index``, softmax over
    [cache, fresh] in f32, combine. Shapes as
    :func:`paged_decode_attention`."""
    from paddle_tpu.models.generation import PagedCache

    B, T, Hq, D = q.shape
    Hkv = k_new.shape[1]
    G = Hq // Hkv
    quantized = len(pool) == 4
    P = pool[0].shape[3]
    M = table.shape[1]

    def one(qb, knb, vnb, row, idx):
        # paged_gather, restricted to one layer: [Hkv, M·P, *rest]
        view = [v[0] for v in PagedCache(pool, row).read_layer(layer)]
        k_c, v_c = view[:2]
        if quantized:
            k_c = k_c.astype(qb.dtype) * view[2][..., None]
            v_c = v_c.astype(qb.dtype) * view[3][..., None]
        qh = qb.reshape(Hkv, G, D)                # [Hkv, G, D]
        s_c = jnp.einsum("hgd,hsd->hgs", qh, k_c) * scale
        mask = jnp.arange(M * P) < idx
        s_c = jnp.where(mask[None, None, :], s_c, NEG_INF)
        s_n = jnp.sum(qh * knb[:, None, :], axis=-1,
                      keepdims=True) * scale      # [Hkv, G, 1]
        s_all = jnp.concatenate([s_c, s_n], axis=-1).astype(jnp.float32)
        p = jax.nn.softmax(s_all, axis=-1).astype(qb.dtype)
        o = (jnp.einsum("hgs,hsd->hgd", p[..., :-1], v_c)
             + p[..., -1:] * vnb[:, None, :])
        return o.reshape(Hq, D)

    q2 = q.reshape(B, Hq, D)
    kn2 = k_new.reshape(B, Hkv, D)
    vn2 = v_new.reshape(B, Hkv, D)
    out = jax.vmap(one)(q2, kn2, vn2, table,
                        jnp.broadcast_to(jnp.asarray(index, jnp.int32),
                                         (B,)))
    return out.reshape(B, 1, Hq, D)


def paged_decode_attention(q, k_new, v_new, pool, table, layer, index, *,
                           scale: float):
    """q [B, 1, Hq, D]; k_new/v_new [B, Hkv, 1, D] (this step's raw
    k/v, not yet in the pool); ``pool`` the paged leaves; ``table``
    [B, M] int32 per-slot page rows (the engine's device-resident
    table); ``layer`` this block's layer id; ``index`` int32 fill
    position(s) — scalar or [B] (each slot's pool pages hold tokens
    [0, index)). Returns [B, 1, Hq, D]. Dispatches the kernel when
    :func:`supported`, else :func:`paged_reference`."""
    if not supported(q, pool, table):
        return paged_reference(q, k_new, v_new, pool, table, layer,
                               index, scale=scale)
    B, T, Hq, D = q.shape
    Hkv = k_new.shape[1]
    q2 = q.reshape(B, Hq, D)
    kn2 = k_new.reshape(B, Hkv, D)
    vn2 = v_new.reshape(B, Hkv, D)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    lay = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,))
    sp = jnp.concatenate([lay[:, None], idx[:, None],
                          jnp.asarray(table, jnp.int32)], axis=1)
    out = raw_call(sp, q2, kn2, vn2, *pool, scale=scale)
    return out.reshape(B, 1, Hq, D)
