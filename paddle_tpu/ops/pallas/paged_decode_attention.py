"""Page-table-aware single-token decode attention over the paged pool.

What the paged serving engine's decode step attends with on one TPU
chip (``models._common.cached_attention`` dispatches here for a
one-token chunk on a ``PagedCache`` when :func:`supported` holds,
``latent_attention`` when :func:`latent_supported` does: three kernel
bodies, one batching rule, told apart by the pool's leaves). The
other arm gathers every page of a slot's table row — capacity, not
fill — into a per-layer contiguous view
(``models.generation.PagedCache.read_layer``) for the einsum lines of
``cached_attention``. This kernel deletes that per-layer copy the same
way ``decode_attention`` deleted the per-layer ``lax.scan`` slice — the
page indirection moves INTO the kernel, which reads the slot's
device-resident page table from its scalar-prefetch row ``[layer,
index, (lo,) table...]``, so the persistent HBM (the pool) is the only
cache the kernel ever touches, and only its live pages. Which of the
two K/V forms a pool takes is a function of its leaves' shape
(:func:`copies_pages`), read where the program is traced.

The block-spec form (:func:`raw_call`; the int8 pool, and a 64-wide
head on pages of whole lane tiles, ``Hkv * P`` a multiple of 128, whose
copy Mosaic refuses). A page block's index map

    page id = sp_ref[b, 2 + min((j - 1) * K + i, last_live_page)]

— grid step ``j`` DMAs the K physical pages ``table[(j - 1) * K : j *
K]`` of the pool (every pool leaf is an operand K times over, operand
``i`` mapped to the step's ``i``-th page; K from
:func:`_pages_per_step`). Pages past the filled prefix repeat the last
live page id and Mosaic elides the repeated DMA, exactly the
stacked-layer clamp trick. A step's K pages are K independent dots on
either side of one softmax update, which is what lets the core's
matrix units work side by side: one page a step — the first form, a
chain of dot, update, dot — kept one unit busy at 0.55 µs a page where
the page's bytes take 0.16. A page costs two pipeline DMAs whatever its
size: ~0.28 µs on the v5e, which a 32 KB page of 4 heads does not hide
(36.7 ms a step of the window cell, 17 % of its roofline, whatever K)
and a 128 KB page of 16 heads half hides (3.22 ms a decode step of the
OLMoE cell, ~55 % of the chip's bandwidth).

The copy form (:func:`raw_copy_call`; every float pool whose copies
Mosaic takes — :func:`copies_pages`: pages narrower than a lane tile,
SmallThinker's 4 KV heads x 16 tokens = 64 rows, and pages of whole lane
tiles with a 128-wide head, OLMoE's 16 KV heads x 16 tokens = 256 rows).
The two leaves stay unblocked in HBM (``pl.ANY``) and the kernel copies
pages itself, as the latent body below does for its one leaf: a page of
one layer is a contiguous ``[Hkv, P, D]`` slab a leaf, ONE
``make_async_copy`` into its ``Hkv * P`` rows of a block's buffer — rows
in the order (page, head, offset) — :func:`_pages_per_block` pages a
block, contiguous in VMEM BEFORE the dot; a whole block is KP
straight-line starts and one wait a leaf on a byte-counting semaphore
(only a row's first and last live block loop over a count), started one
block ahead of its use across grid steps and across slots. Pages past the fill are not copied;
with a window, pages wholly before ``lo // P`` are not copied either
and blocks wholly before it take no grid step. Then one ``[Hq, D] x
[KP * Hkv * P, D]^T`` product, the head / fill / window mask, one
online-softmax update in float32, one ``[Hq, KP * Hkv * P] x [KP * Hkv
* P, D]`` product. Read on the v5e at the window cell's shapes (48 slots x
12.4-13.6 k positions on 2 full layers and 4 096 on 6 window layers,
152 k pages of 32 KB a step, ten steps chained in one program): 8.9 ms
a step at 64 pages a block where the block-spec form took 36.7 — 5.0 GB
at 563 GB/s, 69 % of the chip's bandwidth; 32 pages a block 10.1, 16
12.6, 128 8.5 (twice the VMEM). At OLMoE's shapes (16 slots x ~68 pages
of 64 KB a leaf, 16 pages a block, 8 layers) it read 1.70 ms a step
where the block-spec form took 3.22 — ~1.15 GB at ~680 GB/s, 83 % of
the chip's bandwidth — though at one query head a KV head (G = 1) the
one block-diagonal product computes 16 times the logits it keeps; 8
pages a block read 1.73, 32 read 1.74.

The block form (``ptpu_paged_block_attn``; :func:`block_supported`,
:func:`paged_block_attention`): the copy form at T > 1 query rows a
slot, for one block of a block-diffusion step (``models/sdar.py``),
whose rows see every cached position before the block and each other,
both ways. A slot's rows are its ``Hq x T`` queries in the order (head,
t), so the KV head of query row r is ``r // (G * T)``; grid step 0 is the
block's own T rows (:func:`_fresh_block`, the vector unit's T products a
row); then each live block of pages is copied once and multiplied with
all ``Hq x T`` rows. A slot's live pages are read once a layer for the
whole block, where T one-token calls would read them T times. The
one-token programs are text-identical with the form beside them.

Everything else is the ``decode_attention`` recipe on a page-shaped
block, in both forms: the fresh token's raw k/v joins the streaming
softmax as grid step 0; pages stream after it with positions ``>=
index`` masked (position ``p`` lives in page ``p // P`` at offset ``p %
P``, matching ``paged_gather``'s view); one block-diagonal all-heads
dot; int8 pool scales fold into the logit/prob planes so HBM traffic
stays the int8 bytes (block-spec form, interpreter only so far:
compiled for the TPU the gate sends the int8 pool to the gather arm,
see :func:`supported`).

Pool layout contract matches ``models.generation.init_paged_cache``:
k/v leaves ``[num_pages + 1, L, Hkv, P, D]`` (page id 0 = the reserved
null page), int8 layout adds f32 scale leaves
``[num_pages + 1, L, Hkv, P]``. ``table`` is one slot's int32 page-id
row. The kernel only reads the pool: the step's new k/v go in
afterwards by whole-page updates (``generation.paged_write``), so the
donated pool keeps its layout and is never copied.

The slot axis. The engine calls the model under ``jax.vmap`` over slots,
and jax's batching rule for a pallas call with a mapped scalar-prefetch
operand is a loop over the mapped axis that slices every other operand
per iteration. The call therefore carries a batching rule of its own
(:func:`_over_rows`, ``jax.custom_batching.custom_vmap``): a mapped axis
of S slots joins the rows, and the step holds ONE call a layer with grid
``(S, 1 + ceil(M / K))`` on the unmapped pool.

The latent arm (``ptpu_paged_latent_decode_attn``, behind
``models._common.latent_attention``). A latent (MLA) pool is ONE leaf
``[num_pages + 1, L, 1, P, W]`` — a token's compressed K/V row and its
shared rope key side by side, padded to whole lane tiles
(``init_latent_cache``) — and a page of one layer is ``[16, 640]`` =
20 KB where a K/V page is 64 KB a leaf. The K/V form would read every
page twice (the row is key AND value), take 8x the grid steps and hand
the matrix units 16-token stationary operands. So the third kernel
body leaves the pool unblocked in HBM (``pl.ANY``) and copies pages
itself: a block of :func:`_latent_pages_per_block` live pages, one
``make_async_copy`` a page into its 16-row place of a ``[KP * 16, W]``
VMEM buffer — contiguous BEFORE the dot — double-buffered one block
ahead across grid steps and across slots; then ONE ``[H, W] x [KP * 16,
W]^T`` product for the scores of all heads (the query is ``[q~ | q_rope
| 0]``, the pad zeros on both sides), one online-softmax update in
float32, one ``[H, KP * 16] x [KP * 16, C]`` product over the block's
first C columns, a lane-aligned slice of the same buffer. Pages past
the fill are not copied, positions ``>= index`` are masked, blocks
past the fill skipped; the step's own row is grid step 0. The slot axis
joins the rows through the same :func:`_over_rows`. Read on the v5e at
the latent cell's shapes (64 slots x 6.3-7.0 k rows, 5 layers): 5.4 ms
a decode step in the cell's trace where the gather and the einsum lines
took 22.7; chained alone in one program 6.0 — the copies alone 4.1
(2.7 GB at 650 GB/s, whatever the page placement), the products alone
3.3. (The K/V copy form keeps a body of its own: two leaves, a lower
edge and a row order the latent body has no use for, and the latent
step's program was to stay as it is.)

Status: interpreter-mode tests (``tests/test_paged_decode_attention.py``,
``tests/test_paged_window_attention.py``,
``tests/test_paged_latent_attention.py``) pin each body to its gather
arm per slot, under ``jax.vmap``, the block-spec one for the int8 4-leaf
layout too; ``tests/test_paged_kernel_step.py`` holds the engine's step
on this arm to the gather arm for both model families and both K/V
forms (tokens in float32, logits in bf16: the online softmax orders
every sum differently from the einsum arm's joint f32 softmax) and
compiles each for the v5e. Off-TPU callers take the gather arm
(``dispatch_mode()`` is ``"off"``). Multi-device meshes do too (no
``_partition`` unit yet — the pool's KV-head shard would need a
per-shard grid), as do prefill chunks and speculative verify windows
(``T > 1``, but for one block of a block-diffusion step on narrow
pages: the block form), a narrow page with a 64-wide head (Mosaic refuses the
copy's slice of half a lane tile), and an int8 latent leaf does not
exist (``init_latent_cache`` refuses it).
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


def _one_token_on_one_chip(q, table) -> bool:
    """What both gates ask first, from what a trace can see: a
    one-token chunk ``q`` [B, 1, H, *] of a float dtype with a page row
    a slot (``table`` [B, M]), dispatched raw — one TPU chip; a
    multi-device mesh has no partitioned wrapper for the paged layouts
    yet and stays on the gather + einsum lines."""
    return (_support.dispatch_mode() == "raw"
            and q.ndim == 4 and q.shape[1] == 1
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and table.ndim == 2 and table.shape[0] == q.shape[0])


def copies_pages(pool) -> bool:
    """Which K/V form a pool takes, from its leaves' shape alone: the
    kernel copies the pages of a float pool itself
    (:func:`raw_copy_call`) — pages narrower than a lane tile (``Hkv *
    P`` rows short of 128: SmallThinker's 4 KV heads x 16 tokens) and
    pages of whole lane tiles whose copies Mosaic takes, a head of
    whole lane tiles and a page of whole packed tiles (OLMoE's 16 KV
    heads x 16 tokens x 128). The int8 pool and a 64-wide head on pages
    of whole lane tiles arrive through block specs (:func:`raw_call`)."""
    if len(pool) != 2:
        return False
    k = pool[0]
    Hkv, P, D = k.shape[2:]
    if (Hkv * P) % LANES:
        return True
    return not (D % LANES or (P * k.dtype.itemsize) % 32)


def supported(q, pool, table) -> bool:
    """Kernel gate; callers fall back to :func:`paged_reference` when
    False. ``q`` [B, 1, Hq, D] (decode chunks only); ``pool`` the paged
    leaves ([N, L, Hkv, P, D], int8 adds [N, L, Hkv, P] scales);
    ``table`` [B, M] int32 page rows. Raw dispatch only
    (:func:`_one_token_on_one_chip`); the int8 pool stays on the gather
    arm where the kernel would be compiled (float leaves only there)."""
    if not _one_token_on_one_chip(q, table):
        return False
    B, T, Hq, D = q.shape
    k = pool[0]
    if k.ndim != 5:
        return False
    _, _, Hkv, P, Dk = k.shape
    if Dk != D or D not in (64, 128, 256) or Hq % Hkv or P % 8:
        return False
    quantized = len(pool) == 4
    if _support.on_tpu() and not _support.interpret():
        if quantized:
            # Mosaic refuses the scale planes' [Hkv, P] -> [1, Hkv * P]
            # reshape ("unsupported shape cast", v5e): the int8 pool is
            # the interpreter's only, compiled it takes the gather arm
            return False
        if copies_pages(pool):
            # a copy moves whole tiles of the leaf's dtype — a page's
            # [P, D] planes, 16 rows of bf16 by 128 lanes: Mosaic refuses
            # to slice a 64-wide row out of the pool ("must be aligned to
            # tiling (128)", v5e, found by AOT) — and a block's pages
            # together fill whole lane tiles of the score plane
            KP = _pages_per_block(table.shape[1],
                                  Hkv * P * D * k.dtype.itemsize)
            if (D % LANES or (P * k.dtype.itemsize) % 32
                    or (KP * Hkv * P) % LANES):
                return False
    if quantized and k.dtype != jnp.int8:
        return False
    if not quantized and k.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return True


def _fresh_token(q_ref, kn_ref, vn_ref, acc_ref, m_ref, l_ref, *,
                 scale, G, Hkv):
    """Grid step 0 of both K/V bodies — the step's own token: p =
    exp(s - m) = 1, l = 1, acc = v_new."""
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


def _fresh_block(q_ref, kn_ref, vn_ref, acc_ref, m_ref, l_ref, *,
                 scale, G, Hkv, T):
    """Grid step 0 of the block form — the step's own T rows, which every
    query row of the block sees (block-causal: one whole block both
    ways): rows in the order (KV head, query head of its group, t), keys
    (KV head, u); per KV head T products a row on the vector unit, their
    softmax started in float32."""
    q = q_ref[0].astype(jnp.float32)            # [Hkv * G * T, D]
    kn = kn_ref[0].astype(jnp.float32)          # [Hkv * T, D]
    vn = vn_ref[0].astype(jnp.float32)
    R = G * T
    for h in range(Hkv):
        rows = slice(h * R, (h + 1) * R)
        s = [jnp.sum(q[rows] * kn[h * T + u:h * T + u + 1], axis=1,
                     keepdims=True) * scale for u in range(T)]   # T x [R, 1]
        m = functools.reduce(jnp.maximum, s)
        p = [jnp.exp(x - m) for x in s]
        m_ref[rows, :] = jnp.broadcast_to(m, (R, LANES))
        l_ref[rows, :] = jnp.broadcast_to(sum(p), (R, LANES))
        acc_ref[rows, :] = sum(pu * vn[h * T + u:h * T + u + 1]
                               for u, pu in enumerate(p))


def _softmax_update(s, m_ref, l_ref):
    """One online-softmax update over a masked score plane ``s``
    [Hq, n] in float32: the running max and sum move, and the plane's
    probabilities come back with the factor the accumulator owes."""
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                      # [Hq, n]
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:, :1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_ref[:, :1] = m_new
    return p, alpha


def _kernel(sp_ref, q_ref, kn_ref, vn_ref, *rest,
            scale, P, K, steps, G, Hkv, quantized, out_dtype,
            windowed=False):
    # ``rest``: K page refs of k, K of v (int8: K of each scale plane
    # after them), then the output and the scratch
    n = (4 if quantized else 2) * K
    leaves = [rest[i * K:(i + 1) * K] for i in range(n // K)]
    o_ref, acc_ref, m_ref, l_ref = rest[n:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    idx = sp_ref[b, 1]

    @pl.when(j == 0)
    def _fresh():
        _fresh_token(q_ref, kn_ref, vn_ref, acc_ref, m_ref, l_ref,
                     scale=scale, G=G, Hkv=Hkv)

    last_page = jnp.maximum(idx - 1, 0) // P
    live = (j > 0) & ((j - 1) * K <= last_page)
    if windowed:
        # a window layer's row: positions before ``lo`` have slid out;
        # steps wholly before its page are skipped, the page itself is
        # masked from inside
        lo = sp_ref[b, 2]
        live = live & (j * K > lo // P)

    @pl.when(live)
    def _page_blocks():
        # ONE block-diagonal dot for ALL heads over each page (the
        # decode_attention trick at page granularity): q [Hq, D]
        # against the whole [Hkv·P, D] page computes every cross-head
        # product, the mask kills the wrong-head logits exactly. The
        # step's K pages are K independent dots on either side of ONE
        # softmax update over [Hq, K·Hkv·P] — nothing orders them among
        # themselves, so the matrix units take them side by side (a
        # page after a page, each through the scratch, kept one unit
        # busy: 0.55 µs a page where its bytes take 0.16). A page past
        # the fill (the last step's tail) is masked whole.
        q = q_ref[0]                                # [Hq, D], model dtype
        Hq, D = q.shape
        W = Hkv * P
        kp_refs, vp_refs = leaves[0], leaves[1]
        cdt = q.dtype if kp_refs[0].dtype == jnp.int8 else kp_refs[0].dtype
        q = q.astype(cdt)

        def page(ref):                              # [Hkv, P, D] -> [W, D]
            return ref[0, 0].astype(cdt).reshape(W, D)

        def plane(refs):                            # K x [Hkv, P] -> [1, K·W]
            return jnp.concatenate([r[0, 0].reshape(1, W) for r in refs], 1)

        s = jnp.concatenate([
            jax.lax.dot_general(q, page(r), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for r in kp_refs], axis=1) * scale      # [Hq, K·W]
        if quantized:
            # per-position scale folds into the logit plane (per column)
            s = s * plane(leaves[2])
        row_h = jax.lax.broadcasted_iota(jnp.int32, (Hq, K * W), 0) // G
        col = jax.lax.broadcasted_iota(jnp.int32, (Hq, K * W), 1)
        # paged_gather's view coordinate of column (page i, head, offset)
        pos = ((j - 1) * K + col // W) * P + col % P
        valid = (row_h == col % W // P) & (pos < idx)
        if windowed:
            valid = valid & (pos >= lo)
        s = jnp.where(valid, s, NEG_INF)
        p, alpha = _softmax_update(s, m_ref, l_ref)
        if quantized:
            # v scale folds into the prob plane
            p = p * plane(leaves[3])
        p = p.astype(cdt)
        pv = sum(
            jax.lax.dot_general(p[:, i * W:(i + 1) * W], page(r),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for i, r in enumerate(vp_refs))         # [Hq, D]
        acc_ref[:, :] = acc_ref[:, :] * alpha + pv

    @pl.when(j == steps)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:, :] / jnp.where(l == 0.0, 1.0, l)).astype(
            out_dtype)


def _pages_per_step(M: int, page_bytes: int) -> int:
    """Pages one grid step of the block-spec form streams: up to 8,
    within 1 MiB a leaf a step (double-buffered in VMEM). Several pages
    a step are what gives the kernel independent dots to run side by
    side (``_kernel``); on the v5e 16 pages read the same as 8, and one
    page a step — a page's two dots and its softmax update in a chain —
    took 5.9 ms a decode step of the OLMoE cell where 8 took 3.1. Only
    a 64-wide head on pages of whole lane tiles comes here compiled
    (:func:`copies_pages`; the int8 pool only interpreted): two DMAs a
    page, whatever their number, read 36.7 / 33.6 / 33.5 / 35.2 ms a
    step of the window cell at 8 / 16 / 32 / 64 pages a step, and 3.22
    ms a step of the OLMoE cell at 8, where the copy form
    (:func:`_pages_per_block`) reads 8.9 and 1.70."""
    return max(1, min(8, M, (1 << 20) // page_bytes))


def raw_call(sp, q2, kn2, vn2, *pool, scale: float, windowed: bool = False,
             name: str = "ptpu_paged_decode_attn"):
    """The pallas_call on local shapes: sp int32 [B, 2 + M] rows of
    ``[layer, index, table...]``; q2 [B, Hq, D]; kn2/vn2 [B, Hkv, D];
    ``pool`` the paged leaves. Returns [B, Hq, D]. Grid ``(B, 1 +
    ceil(M / K))``: step 0 the fresh token, then K pages a step — every
    pool leaf is passed K times, operand ``i`` of a step mapped to the
    step's ``i``-th page, so one step's K pages (anywhere in the pool)
    arrive by K block DMAs of the ordinary pipeline. ``windowed``: the
    rows are ``[layer, index, lo, table...]`` and positions before
    ``lo`` (the first a window layer's query still sees, in the row's
    own coordinates) are masked, the pages wholly before it skipped.
    ``name`` is the kernel's name in the device trace."""
    B, Hq, D = q2.shape
    Hkv = kn2.shape[1]
    G = Hq // Hkv
    quantized = len(pool) == 4
    kp = pool[0]
    P = kp.shape[3]
    HDR = 3 if windowed else 2
    M = sp.shape[1] - HDR
    K = _pages_per_step(M, Hkv * P * D * kp.dtype.itemsize)
    steps = -(-M // K)

    def page_map(i, ndim):
        # THE point of this kernel: the block's pool coordinate is read
        # straight out of the slot's page-table row. Pages past the
        # filled prefix clamp to the last live page (repeated DMA
        # elided), mirroring the stacked kernel's fill clamp.
        def index(b, j, sp_ref):
            last = jnp.maximum(sp_ref[b, 1] - 1, 0) // P
            jp = jnp.minimum(jnp.maximum(j - 1, 0) * K + i, last)
            if windowed:
                jp = jnp.maximum(jp, jnp.minimum(sp_ref[b, 2] // P, last))
            return (sp_ref[b, HDR + jp], sp_ref[b, 0]) + (0,) * (ndim - 2)
        return index

    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j, s: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j, s: (b, 0, 0)),
    ]
    args = [q2, kn2, vn2]
    for leaf in pool:
        block = (1, 1) + leaf.shape[2:]
        in_specs += [pl.BlockSpec(block, page_map(i, leaf.ndim))
                     for i in range(K)]
        args += [leaf] * K

    kernel = functools.partial(
        _kernel, scale=scale, P=P, K=K, steps=steps, G=G, Hkv=Hkv,
        quantized=quantized, out_dtype=q2.dtype,
        **({"windowed": True} if windowed else {}))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, steps + 1),
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
        name=name,
    )(sp, *args)


def _pages_per_block(M: int, page_bytes: int) -> int:
    """Pages one block of the copy form holds contiguous in VMEM, a
    leaf: up to 64, within 1 MiB (two blocks a leaf are resident, one in
    use and one in flight) and within the table rounded up to eight
    pages, so that a short table's block still fills whole lane tiles.
    The block is the stationary side of both products and the unit of
    one wait. On the v5e, at the window cell's shapes (pages of 16 KB a
    leaf), 64 pages a block read 8.9 ms a decode step, 32 read 10.1, 16
    read 12.6 (more grid steps and waits for the same copies) and 128
    read 8.5 at twice the VMEM and compile time."""
    return max(1, min(64, -(-M // 8) * 8, (1 << 20) // page_bytes))


def _copy_kernel(sp_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref,
                 kbuf, vbuf, sem, slot_ref, acc_ref, m_ref, l_ref, *,
                 scale, P, KP, M, G, Hkv, rows, out_dtype, windowed, T=1):
    # The K/V body for float pools (copies_pages). The pool stays
    # in HBM; a page of one layer is a contiguous [Hkv, P, D] slab a
    # leaf, copied into its Hkv·P rows of a block's buffer — rows in the
    # order (page, head, offset) — so the block is contiguous in VMEM
    # BEFORE its one dot a side. Grid step j >= 1 of a row attends the
    # row's (j - 1)-th LIVE block: blocks wholly behind a window row's
    # lower edge take no step at all. ``T`` > 1 (the block form): each
    # query head brings T rows, the KV head of query row r is r // (G·T),
    # and grid step 0 is the block's own T rows (:func:`_fresh_block`).
    HDR = 3 if windowed else 2
    b = pl.program_id(0)
    j = pl.program_id(1)
    idx = sp_ref[b, 1]
    W = Hkv * P
    steps = -(-M // KP)
    bufs = ((k_hbm, kbuf), (v_hbm, vbuf))

    def first_page(row):                  # the first page a row still sees
        return sp_ref[row, 2] // P if windowed else 0

    def end_page(row):
        # one past its last live page — and never past the table: an
        # idle slot's position may lie beyond what its row maps
        return jnp.minimum((jnp.maximum(sp_ref[row, 1], 0) + P - 1) // P, M)

    def first_block(row):
        return first_page(row) // KP

    def blocks(row):                      # live blocks of a row
        return jnp.maximum(
            (end_page(row) + KP - 1) // KP - first_block(row), 0)

    def page_copies(row, blk, slot, i, at):
        page = sp_ref[row, HDR + blk * KP + i]
        return [pltpu.make_async_copy(
            hbm.at[page, sp_ref[row, 0]], buf.at[slot, pl.ds(at, Hkv)],
            sem.at[leaf, slot]) for leaf, (hbm, buf) in enumerate(bufs)]

    def each_block(row, blk, whole, page):
        """A block's live pages, one [Hkv, P, D] copy a leaf each into
        its place of the buffer. Pages past the fill or wholly behind
        the window are not copied (what the buffer holds there is
        masked: an earlier block's rows, or the first step's zeros). A
        full block is KP copies a leaf in a straight line; only a row's
        first and last live block loop over a count."""
        i0 = jnp.maximum(first_page(row) - blk * KP, 0)
        i1 = jnp.minimum(end_page(row) - blk * KP, KP)
        full = (i0 == 0) & (i1 == KP)
        pl.when(full)(whole)

        @pl.when(jnp.logical_not(full))
        def _some():
            def body(i, carry):
                page(i, pl.multiple_of(i * Hkv, Hkv))
                return carry
            jax.lax.fori_loop(i0, i1, body, 0)

    def start(row, blk, slot):
        def page(i, at):
            for c in page_copies(row, blk, slot, i, at):
                c.start()

        def whole():
            for i in range(KP):
                page(i, i * Hkv)

        each_block(row, blk, whole, page)

    def wait(row, blk, slot):
        def page(i, at):
            for c in page_copies(row, blk, slot, i, at):
                c.wait()

        def whole():
            # the semaphores count bytes: one wait a leaf for a block
            for leaf, (_, buf) in enumerate(bufs):
                full = buf.at[slot]
                pltpu.make_async_copy(full, full, sem.at[leaf, slot]).wait()

        each_block(row, blk, whole, page)

    @pl.when((b == 0) & (j == 0))
    def _first():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0

    nb = blocks(b)

    @pl.when(j <= nb)
    def _prefetch():
        # the latent kernel's schedule: every live block is started
        # exactly once, one block ahead of its use and across slots — a
        # row's later blocks by the step before them, its first by the
        # last live step of the row before (row 0's by the call's first
        # step). A row with nothing cached starts and waits nothing.
        cur = slot_ref[0]
        using = j >= 1
        own = j < nb
        row = jnp.where(own, b, jnp.minimum(b + 1, rows - 1))
        go = jnp.where(own, using | (b == 0),
                       (b + 1 < rows) & (blocks(row) >= 1))

        @pl.when(go)
        def _start():
            start(row, first_block(row) + jnp.where(own, j, 0),
                  jnp.where(using, 1 - cur, cur))

    @pl.when(j == 0)
    def _fresh():
        if T > 1:
            _fresh_block(q_ref, kn_ref, vn_ref, acc_ref, m_ref, l_ref,
                         scale=scale, G=G, Hkv=Hkv, T=T)
        else:
            _fresh_token(q_ref, kn_ref, vn_ref, acc_ref, m_ref, l_ref,
                         scale=scale, G=G, Hkv=Hkv)

    @pl.when((j >= 1) & (j <= nb))
    def _block():
        cur = slot_ref[0]
        blk = first_block(b) + j - 1
        wait(b, blk, cur)
        slot_ref[0] = 1 - cur
        Hq, D = q_ref.shape[1:]
        q = q_ref[0].astype(kbuf.dtype)                    # [Hq, D]
        # ONE block-diagonal dot for all heads over the whole block: q
        # against [KP·Hkv·P, D] computes every cross-head product, the
        # mask kills the wrong-head logits exactly
        s = jax.lax.dot_general(
            q, kbuf[cur].reshape(KP * W, D), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [Hq, KP·W]
        # column (page i, head, offset) stands at paged_gather's view
        # position (blk·KP + i)·P + offset; one row of columns and one
        # column of rows, broadcast in the compare
        col = jax.lax.broadcasted_iota(jnp.int32, (1, KP * W), 1)
        pos = (blk * KP + col // W) * P + col % P
        seen = pos < jnp.minimum(idx, M * P)    # the fill, within the table
        if windowed:
            seen = seen & (pos >= sp_ref[b, 2])
        row_h = jax.lax.broadcasted_iota(jnp.int32, (Hq, 1), 0) // (G * T)
        s = jnp.where((row_h == col % W // P) & seen, s, NEG_INF)
        p, alpha = _softmax_update(s, m_ref, l_ref)
        pv = jax.lax.dot_general(
            p.astype(vbuf.dtype), vbuf[cur].reshape(KP * W, D),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_ref[:, :] = acc_ref[:, :] * alpha + pv

    @pl.when(j == steps)
    def _finalize():
        o_ref[0] = (acc_ref[:, :] / l_ref[:, :1]).astype(out_dtype)


def raw_copy_call(sp, q2, kn2, vn2, kp, vp, *, scale: float,
                  windowed: bool = False, T: int = 1, name: str | None = None):
    """The copy form's pallas_call on local shapes: operands as
    :func:`raw_call`'s, the two float leaves unblocked in HBM and only
    read. Grid ``(B, 1 + ceil(M / KP))``: step 0 the fresh token, then
    one live block of KP pages a step, double-buffered across steps and
    rows by the kernel's own copies. ``T`` > 1 is the block form
    (``ptpu_paged_block_attn``): q2 [B, Hq·T, D] in the order (head, t),
    kn2 / vn2 [B, Hkv·T, D] in the order (KV head, u). ``name`` (None:
    the form's own) is the kernel's name in the device trace."""
    B, Hq, D = q2.shape
    Hkv, P = kp.shape[2:4]
    M = sp.shape[1] - (3 if windowed else 2)
    KP = _pages_per_block(M, Hkv * P * D * kp.dtype.itemsize)
    steps = -(-M // KP)
    kernel = functools.partial(
        _copy_kernel, scale=scale, P=P, KP=KP, M=M, G=Hq // (Hkv * T),
        Hkv=Hkv, rows=B, out_dtype=q2.dtype, windowed=windowed,
        **({"T": T} if T > 1 else {}))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, steps + 1),
            in_specs=[
                pl.BlockSpec((1, Hq, D), lambda b, j, s: (b, 0, 0)),
                pl.BlockSpec((1, Hkv * T, D), lambda b, j, s: (b, 0, 0)),
                pl.BlockSpec((1, Hkv * T, D), lambda b, j, s: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, Hq, D), lambda b, j, s: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, KP * Hkv, P, D), kp.dtype),
                pltpu.VMEM((2, KP * Hkv, P, D), vp.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((Hq, D), jnp.float32),
                pltpu.VMEM((Hq, LANES), jnp.float32),
                pltpu.VMEM((Hq, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q2.dtype),
        # the copies of one step are waited in a later one: the grid
        # has to run in order
        compiler_params=_support.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_support.interpret(),
        name=name or ("ptpu_paged_block_attn" if T > 1
                      else "ptpu_paged_decode_attn"),
    )(sp, q2, kn2, vn2, kp, vp)


def latent_supported(q, pool, table, C: int) -> bool:
    """Gate of the latent kernel; ``latent_attention`` stays on its
    einsum lines when False. ``q`` [B, T, H, *] the chunk's queries;
    ``pool`` the latent layout — ONE float leaf [N, L, 1, P, W] with a
    lane-tiled row (``init_latent_cache`` pads it), which is what tells
    it from the K/V leaves; ``table`` [B, M]; ``C`` the row's leading
    value columns. Raw dispatch and one-token chunks only
    (:func:`_one_token_on_one_chip`). Compiled, a page must be whole
    tiles of the leaf's dtype (16 rows of bf16) and the value slice
    whole lane tiles."""
    if not _one_token_on_one_chip(q, table) or len(pool) != 1:
        return False
    leaf = pool[0]
    if leaf.ndim != 5 or leaf.shape[2] != 1:
        return False
    P, W = leaf.shape[3:]
    if W % LANES or P % 8 or not 0 < C <= W:
        return False
    if leaf.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if _support.on_tpu() and not _support.interpret():
        if C % LANES or (P * leaf.dtype.itemsize) % 32:
            return False
    return True


def _latent_pages_per_block(M: int, P: int) -> int:
    """Pages one block of the latent kernel holds contiguous in VMEM:
    1024 tokens (64 pages of 16), within the table. The block is the
    stationary side of both products, so it has to be many pages before
    the dot — a 16-token page alone fills 16 of a tile's 128 columns.
    On the v5e, at the latent cell's shapes, 64 pages a block read
    5.9-6.0 ms a decode step and 128 read 5.7-6.2 (twice the VMEM, and
    more masked columns computed on a short context); 32 read 6 % more
    than 64 did."""
    return max(1, min(M, 1024 // P))


def _latent_kernel(sp_ref, q_ref, new_ref, pool_ref, o_ref,
                   buf, sem, slot_ref, acc_ref, m_ref, l_ref, *,
                   scale, P, KP, steps, C, rows, out_dtype):
    # one latent row is key AND value: the score is q_full . row over
    # the whole W-wide row (the rope part rides along, the pad is zeros
    # on both sides), the value its first C columns — a lane-aligned
    # slice of the same VMEM block, so a page crosses HBM once.
    b = pl.program_id(0)
    j = pl.program_id(1)
    idx = sp_ref[b, 1]
    T = KP * P

    def blocks(row):                      # live blocks of a slot
        return (sp_ref[row, 1] + T - 1) // T

    def page_copy(row, blk, slot, i):
        return pltpu.make_async_copy(
            pool_ref.at[sp_ref[row, 2 + blk * KP + i], sp_ref[row, 0], 0],
            buf.at[slot, pl.ds(pl.multiple_of(i * P, P), P)], sem.at[slot])

    def each_block(row, blk, whole, page):
        """A block's live pages, one [P, W] copy each into its P-row
        place of the buffer: contiguous in VMEM before the dot. Pages
        past the fill are not copied (what the buffer holds there is
        masked: an earlier block's rows, or the first step's zeros).
        A full block is KP copies in a straight line — a loop over
        them costs 14 ns a page more, a branch a group of eight 0.4 µs
        a block — and only a slot's last block loops over its count."""
        n = jnp.minimum(KP, (sp_ref[row, 1] + P - 1) // P - blk * KP)
        pl.when(n == KP)(whole)

        @pl.when(n < KP)
        def _some():
            def body(i, carry):
                page(i)
                return carry
            jax.lax.fori_loop(0, n, body, 0)

    def start(row, blk, slot):
        def page(i):
            page_copy(row, blk, slot, i).start()

        def whole():
            for i in range(KP):
                page(i)

        each_block(row, blk, whole, page)

    def wait(row, blk, slot):
        def page(i):
            page_copy(row, blk, slot, i).wait()

        def whole():
            # the semaphore counts bytes: one wait for a whole block
            full = buf.at[slot]
            pltpu.make_async_copy(full, full, sem.at[slot]).wait()

        each_block(row, blk, whole, page)

    @pl.when((b == 0) & (j == 0))
    def _first():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

    nb = blocks(b)

    @pl.when(j <= nb)
    def _prefetch():
        # every live block is started exactly once, one block ahead of
        # its use and across slots: a slot's later blocks by the step
        # before them, its first by the last live step of the slot
        # before (slot 0's by the call's first step)
        cur = slot_ref[0]
        using = j >= 1
        own = j < nb
        row = jnp.where(own, b, jnp.minimum(b + 1, rows - 1))
        go = jnp.where(own, using | (b == 0),
                       (b + 1 < rows) & (blocks(row) >= 1))

        @pl.when(go)
        def _start():
            start(row, jnp.where(own, j, 0), jnp.where(using, 1 - cur, cur))

    @pl.when(j == 0)
    def _fresh():
        # the step's own row: p = exp(s - m) = 1, l = 1, acc = its value
        q = q_ref[0].astype(jnp.float32)                   # [H, W]
        new = new_ref[0].astype(jnp.float32)               # [1, W]
        s = jnp.sum(q * new, axis=1, keepdims=True) * scale
        m_ref[:, :] = jnp.broadcast_to(s, m_ref.shape)
        l_ref[:, :] = jnp.ones_like(l_ref)
        acc_ref[:, :] = jnp.broadcast_to(new[:, :C], acc_ref.shape)

    @pl.when((j >= 1) & (j <= nb))
    def _block():
        cur = slot_ref[0]
        wait(b, j - 1, cur)
        slot_ref[0] = 1 - cur
        q = q_ref[0]                                       # model dtype
        blk = buf[cur].astype(q.dtype)                     # [T, W]
        s = jax.lax.dot_general(q, blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = (j - 1) * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < idx, s, NEG_INF)               # [H, T]
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1,
                                                      keepdims=True)
        m_ref[:, :1] = m_new
        pv = jax.lax.dot_general(p.astype(q.dtype), blk[:, :C],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[:, :] = acc_ref[:, :] * alpha + pv

    @pl.when(j == steps)
    def _finalize():
        o_ref[0] = (acc_ref[:, :] / l_ref[:, :1]).astype(out_dtype)


def raw_latent_call(sp, q_full, new, leaf, *, scale: float, C: int):
    """The latent pallas_call on local shapes: sp int32 [B, 2 + M] rows
    of ``[layer, index, table...]``; q_full [B, H, W]; new [B, 1, W]
    (the step's own row); ``leaf`` the pool's one leaf [N, L, 1, P, W],
    unblocked in HBM and only read. Returns u [B, H, C]. Grid ``(B, 1 +
    ceil(M / KP))``: step 0 the fresh row, then one block of KP pages a
    step, double-buffered across steps and slots by the kernel's own
    copies."""
    B, H, W = q_full.shape
    P = leaf.shape[3]
    M = sp.shape[1] - 2
    KP = _latent_pages_per_block(M, P)
    steps = -(-M // KP)
    kernel = functools.partial(
        _latent_kernel, scale=scale, P=P, KP=KP, steps=steps, C=C, rows=B,
        out_dtype=q_full.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, steps + 1),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, j, s: (b, 0, 0)),
                pl.BlockSpec((1, 1, W), lambda b, j, s: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, C), lambda b, j, s: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, KP * P, W), leaf.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, C), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_full.dtype),
        # the copies of one step are waited in a later one: the grid
        # has to run in order
        compiler_params=_support.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_paged_latent_decode_attn",
    )(sp, q_full, new, leaf)


def _row_edges(index, window, base, P: int, B: int):
    """``(index, lo)`` [B] in a window row's own coordinates: the row's
    first entry is logical page ``base``, the query at absolute position
    ``index`` sees cached positions ``index - window < p < index``."""
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    off = jnp.broadcast_to(jnp.asarray(0 if base is None else base,
                                       jnp.int32), (B,)) * P
    lo = jnp.maximum(idx - (window - 1), 0)
    return idx - off, jnp.maximum(lo - off, 0)


def paged_reference(q, k_new, v_new, pool, table, layer, index, *,
                    scale: float, window: int | None = None, base=None):
    """The gather+einsum semantics the kernel must match, and the
    off-TPU fallback arm: ``paged_gather`` the slot's pages at
    ``layer``, dequantize, mask positions ``>= index`` (and, with a
    ``window``, those that have slid out), softmax over [cache, fresh]
    in f32, combine. Shapes as :func:`paged_decode_attention`."""
    from paddle_tpu.models.generation import PagedCache

    B, T, Hq, D = q.shape
    Hkv = k_new.shape[1]
    G = Hq // Hkv
    quantized = len(pool) == 4
    P = pool[0].shape[3]
    M = table.shape[1]

    def one(qb, knb, vnb, row, idx, lo):
        # paged_gather, restricted to one layer: [Hkv, M·P, *rest]
        view = [v[0] for v in PagedCache(pool, row).read_layer(layer)]
        k_c, v_c = view[:2]
        if quantized:
            k_c = k_c.astype(qb.dtype) * view[2][..., None]
            v_c = v_c.astype(qb.dtype) * view[3][..., None]
        qh = qb.reshape(Hkv, G, D)                # [Hkv, G, D]
        s_c = jnp.einsum("hgd,hsd->hgs", qh, k_c) * scale
        mask = jnp.arange(M * P) < idx
        if window is not None:
            mask = mask & (jnp.arange(M * P) >= lo)
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
    if window is None:
        idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
        lo = jnp.zeros((B,), jnp.int32)
    else:
        idx, lo = _row_edges(index, window, base, P, B)
    out = jax.vmap(one)(q2, kn2, vn2, table, idx, lo)
    return out.reshape(B, 1, Hq, D)


def _over_rows(raw, scale: float):
    """``raw`` (:func:`raw_call` or :func:`raw_latent_call`) on row
    operands — ``layer`` [B], ``index`` [B], ``table`` [B, M], then the
    call's per-row arrays (leading axis B), then the pool leaves — with
    a batching rule of its own. jax's rule for a pallas call whose
    scalar-prefetch operand is mapped is a ``while`` over the mapped
    axis that slices every operand per iteration; here a mapped axis is
    folded into the rows instead, so ``vmap`` over S slots of one row
    each is ONE ``raw`` call with S rows in its grid, on the unmapped
    pool."""

    def call(layer, index, table, *rest):
        *rows, pool = rest
        sp = jnp.concatenate([layer[:, None], index[:, None], table],
                             axis=1)
        return raw(sp, *rows, *pool, scale=scale)

    rows_call = jax.custom_batching.custom_vmap(call)

    @rows_call.def_vmap
    def _fold(axis_size, in_batched, *args):
        *rows, pool = args
        *rows_batched, pool_batched = in_batched
        if any(pool_batched):
            # a mapped pool has no row to fold into: jax's own rule
            axes = [0 if b else None for b in rows_batched]
            axes.append(tuple(0 if b else None for b in pool_batched))
            return jax.vmap(call, in_axes=axes)(*args), True
        rows = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, b in zip(rows, rows_batched)]
        out = rows_call(*(x.reshape((-1,) + x.shape[2:]) for x in rows),
                        pool)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return rows_call


def paged_decode_attention(q, k_new, v_new, pool, table, layer, index, *,
                           scale: float, window: int | None = None,
                           base=None, name: str | None = None):
    """q [B, 1, Hq, D]; k_new/v_new [B, Hkv, 1, D] (this step's raw
    k/v, not yet in the pool); ``pool`` the paged leaves; ``table``
    [B, M] int32 per-slot page rows (the engine's device-resident
    table); ``layer`` this block's layer id; ``index`` int32 fill
    position(s) — scalar or [B] (each slot's pool pages hold tokens
    [0, index)). Returns [B, 1, Hq, D]. Dispatches the kernel when
    :func:`supported`, else :func:`paged_reference`. Under ``jax.vmap``
    with the pool unmapped the mapped axis joins B (:func:`_over_rows`):
    still one kernel call.

    ``window`` (static; None = every cached position, today's program):
    a window layer's query at ``index`` sees cached positions ``index -
    window < p < index``. Its ``table`` rows then hold only the live
    logical pages, entry 0 being logical page ``base`` (scalar or [B];
    None = 0): the kernel works in the row's coordinates, reads its live
    pages from the first still seen, and masks what has slid out inside
    that page.

    ``name`` (None: ``ptpu_paged_decode_attn``) is the kernel's name in
    the device trace: a model whose window layers are to be read apart
    names them ``ptpu_paged_decode_attn_window``, which a reader that
    matches the default name still finds."""
    if not supported(q, pool, table):
        return paged_reference(q, k_new, v_new, pool, table, layer,
                               index, scale=scale, window=window, base=base)
    B, T, Hq, D = q.shape
    Hkv = k_new.shape[1]
    table = jnp.asarray(table, jnp.int32)
    raw = raw_copy_call if copies_pages(pool) else raw_call
    if window is None:
        idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    else:
        # ``lo`` rides as the first column of the rows' table
        idx, lo = _row_edges(index, window, base, pool[0].shape[3], B)
        table = jnp.concatenate([lo[:, None], table], axis=1)
        raw = functools.partial(raw, windowed=True)
    if name is not None:
        raw = functools.partial(raw, name=name)
    lay = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,))
    out = _over_rows(raw, scale)(
        lay, idx, table, q.reshape(B, Hq, D),
        k_new.reshape(B, Hkv, D), v_new.reshape(B, Hkv, D), tuple(pool))
    return out.reshape(B, 1, Hq, D)


def block_supported(q, pool, table) -> bool:
    """Gate of the block form (``ptpu_paged_block_attn``): ``q`` [B, T,
    Hq, D] with T > 1 — one block of a block-diffusion step, whose rows
    see each other both ways — of a float dtype, raw dispatch (one TPU
    chip), on a float pool the copy form takes (:func:`copies_pages`:
    pages narrower than a lane tile, or of whole lane tiles with a
    128-wide head), on the copy form's Mosaic terms where it is
    compiled. Everything else — a prefill chunk of several blocks is
    the caller's to keep off, a 64-wide head on pages of whole lane
    tiles, the int8 pool, a mesh, the CPU — stays on the gather arm and
    the einsum lines."""
    if not (_support.dispatch_mode() == "raw" and q.ndim == 4
            and q.shape[1] > 1 and q.dtype in (jnp.float32, jnp.bfloat16)
            and table.ndim == 2 and table.shape[0] == q.shape[0]):
        return False
    k = pool[0]
    if k.ndim != 5 or not copies_pages(pool) or k.dtype not in (
            jnp.float32, jnp.bfloat16):
        return False
    _, _, Hkv, P, Dk = k.shape
    D, Hq = q.shape[3], q.shape[2]
    if Dk != D or D not in (64, 128, 256) or Hq % Hkv or P % 8:
        return False
    if _support.on_tpu() and not _support.interpret():
        KP = _pages_per_block(table.shape[1],
                              Hkv * P * D * k.dtype.itemsize)
        if (D % LANES or (P * k.dtype.itemsize) % 32
                or (KP * Hkv * P) % LANES):
            return False
    return True


def paged_block_attention(q, k_new, v_new, pool, table, layer, index, *,
                          scale: float):
    """One block-diffusion step's attention through the page table: q
    [B, T, Hq, D] the block's T rows; k_new / v_new [B, Hkv, T, D] their
    raw k/v (the pool's copy of them is not read: the context ends at
    ``index``); ``pool``, ``table`` [B, M], ``layer``, ``index`` (the
    block's first position, scalar or [B]) as
    :func:`paged_decode_attention`. Every query row sees each cached
    position ``< index`` and all T rows of its block. Returns [B, T, Hq,
    D]. The caller has asked :func:`block_supported`. Under ``jax.vmap``
    with the pool unmapped the mapped axis joins B: one call a layer,
    each slot's live pages copied once for its T·Hq query rows."""
    B, T, Hq, D = q.shape
    Hkv = k_new.shape[1]
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    lay = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,))
    out = _over_rows(functools.partial(raw_copy_call, T=T), scale)(
        lay, idx, jnp.asarray(table, jnp.int32),
        q.transpose(0, 2, 1, 3).reshape(B, Hq * T, D),
        k_new.reshape(B, Hkv * T, D), v_new.reshape(B, Hkv * T, D),
        tuple(pool))
    return out.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)


def paged_latent_decode_attention(q_full, new, pool, table, layer, index, *,
                                  scale: float, C: int):
    """The absorbed latent decode step through the page table. q_full
    [B, 1, H, W] = ``[q_lat | q_rope | 0]``; ``new`` [B, W] the step's
    own row ``[c_kv | k_rope | 0]`` (not in the pool yet); ``pool`` the
    one-leaf latent pool; ``table`` [B, M]; ``layer``; ``index`` scalar
    or [B]. Returns u [B, 1, H, C] — the probabilities' sum of the
    rows' first ``C`` columns, what ``w_vc`` then expands. The caller
    has asked :func:`latent_supported`. Under ``jax.vmap`` with the pool
    unmapped the mapped axis joins B (:func:`_over_rows`)."""
    B, _, H, W = q_full.shape
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    lay = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,))
    out = _over_rows(functools.partial(raw_latent_call, C=C), scale)(
        lay, idx, jnp.asarray(table, jnp.int32), q_full.reshape(B, H, W),
        new.reshape(B, 1, W), tuple(pool))
    return out.reshape(B, 1, H, C)
