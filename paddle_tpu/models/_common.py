"""Shared model-zoo pieces."""

from __future__ import annotations

import collections

import jax.numpy as jnp

from paddle_tpu.models.generation import PagedCache
from paddle_tpu.nn import functional as F


def causal_lm_loss(model, head_weight, input_ids, labels,
                   ignore_index: int = -100, training: bool = True):
    """Next-token loss dispatch shared by the decoder-only families
    (Llama/GPT/Mamba). ``cfg.lm_head_mode != "dense"`` fuses the head
    projection into the loss (``F.next_token_linear_loss`` — the
    [B, T, V] logits never materialize); otherwise the model's dense
    ``__call__`` + sliced cross-entropy runs. ``head_weight`` is the
    [E, V] projection (tied models pass ``embed.weight.T`` — unused,
    hence DCE'd, on the dense path)."""
    mode = getattr(model.config, "lm_head_mode", "dense")
    if mode != "dense":
        x = model.hidden_states(input_ids, training=training)
        return F.next_token_linear_loss(x, head_weight, labels,
                                        ignore_index=ignore_index,
                                        mode=mode)
    logits = model(input_ids, training=training)
    return F.cross_entropy(
        logits[:, :-1].astype(jnp.float32), labels[:, 1:],
        ignore_index=ignore_index)


# How a paged program's attention read its cache, decided when the
# program is traced and counted there (as ``ops.pallas.partition_stats``
# counts its units), by ``cached_attention`` and ``latent_attention``
# alike: ``"paged_copy_kernel"`` — ``ptpu_paged_decode_attn`` in the form
# that copies the pages of a float K/V pool into VMEM itself;
# ``"paged_kernel"`` — the same kernel's block-spec form (the int8 pool,
# a 64-wide head on pages of whole lane tiles) or, over the
# latent leaf, ``ptpu_paged_latent_decode_attn``, through the page table;
# or ``"gather"`` — one layer's pages gathered and the einsum lines;
# ``"paged_block_kernel"`` — ``ptpu_paged_block_attn``, the copy form
# at B query rows a slot (a block-diffusion step).
# The engine reads the difference around the trace of its step
# (``GenerationEngine.stats()["decode_attn"]``).
paged_attn_arms: collections.Counter = collections.Counter()
# The same, a layer kind at a time: ``(window, Hq, Hkv, arm, kernel)``
# for each paged attention layer traced (``kernel`` the Pallas name in
# the device trace, None on the gather arm); the engine reads the
# difference around its step's trace (``stats()["attention"]``).
paged_attn_layers: collections.Counter = collections.Counter()


def attn_arm_since(before) -> str:
    """The arm a program traced since ``before`` (a copy of
    ``paged_attn_arms``) attends by: a kernel form if a layer took one,
    the copy forms first."""
    for arm in ("paged_block_kernel", "paged_copy_kernel", "paged_kernel"):
        if paged_attn_arms[arm] > before[arm]:
            return arm
    return "gather"


def attn_layers_since(before) -> dict:
    """``{window: {"Hq", "Hkv", "arm", "kernel"}}`` of the paged layer
    kinds a program traced since ``before`` (a copy of
    ``paged_attn_layers``); window None is the full layers'."""
    out = {}
    for key, n in paged_attn_layers.items():
        if n > before[key]:
            window, hq, hkv, arm, kernel = key
            out[window] = {"Hq": hq, "Hkv": hkv, "arm": arm,
                           "kernel": kernel}
    return out


def _quant_chunk(x):
    """Absmax-int8 quantize [B, Hkv, T, D] over D → (int8, f32 [B,Hkv,T])."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                  -127, 127).astype(jnp.int8)
    return xq, s


def window_mask(T: int, window):
    """``[T, T]`` mask of a chunk with nothing behind it under a sliding
    window (query ``t`` sees key ``j`` iff ``t - j < window``; causality
    is the caller's), or None where the window cannot bite: no window,
    or a chunk no longer than it."""
    if window is None or T <= window:
        return None
    return (jnp.arange(T)[:, None] - jnp.arange(T)[None, :]) < window


def block_mask(T: int, block: int):
    """``[T, T]`` block-causal mask of a chunk that starts on a block
    boundary: row ``t`` sees row ``u`` iff ``u // block <= t // block``
    — every earlier block and its whole own block, both ways."""
    at = jnp.arange(T) // block
    return at[None, :] <= at[:, None]


def cached_attention(q, k, v, cache, index, layer=0, window=None, block=1,
                     kernel_name=None):
    """Static-KV-cache attention core shared by every attention family
    (llama GQA, GPT fused-MHA, MoE). ``cache`` holds the FULL stacked
    read-only buffers ([L, B, Hkv, S, D] — see ``init_kv_cache``) and
    ``layer`` is this block's layer id (a traced scalar under the layer
    scan, a python int under a block loop). The new tokens are NOT
    written here — they are returned as a write payload and the model
    applies ONE stacked ``dynamic_update_slice`` per step
    (``apply_cache_writes``). Two measured-on-v5e design constraints
    shape this contract:

    - re-stacking the cache through ``lax.scan`` outputs cost a full
      cache copy per generated token (~2 ms/step on the bench geometry)
      → read/write split;
    - slicing the layer OUT of the stacked buffer costs a full layer
      copy per layer per step when the consumer is the Pallas kernel
      (XLA cannot fuse a dynamic-slice producer into a custom call;
      ~1.45 ms/step) → the kernel receives the stacked buffers and picks
      the layer inside its index maps via the scalar-prefetched ``layer``.

    The chunk's own k/v attend fresh (raw dtype, exact) while previous
    positions read from the buffer: key j < index from cache, chunk-local
    causal for [index, index+T) — the same visibility set as writing
    first and masking j <= index + t.

    Three cache forms, told apart by what ``cache`` is:
    - ``(k_buf, v_buf)`` [L, B, Hkv, S, D] — any float dtype.
    - ``(k_q, v_q, k_scale, v_scale)`` — int8 buffers + f32
      per-(head, position) scales [L, B, Hkv, S].
    - a ``generation.PagedCache`` — the page pool (either leaf set) and
      one sequence's page-table row, B = 1. A one-token chunk the paged
      kernel supports (``paged_decode_attention.supported``: one TPU
      chip) is attended by ``ptpu_paged_decode_attn`` through the row,
      live pages only; under the engine's ``vmap`` over slots that is
      one call for all slots (the kernel's own batching rule). The
      leaves' shape picks the kernel's form
      (``paged_decode_attention.copies_pages``): the pages of a float
      pool are copied into VMEM by the kernel itself, a block of them
      to one wait a leaf — SmallThinker's 4 KV heads x 16 tokens 64
      pages a block, 8.9 ms a step of the window cell where block specs
      took 36.7; OLMoE's 16 heads x 16 x 128 16 pages a block, 1.70 ms a
      step where block specs took 3.22 — and a 64-wide head on pages of
      whole lane tiles (whose copy Mosaic refuses) and the interpreter's
      int8 pool arrive through block specs. Everything else — a prefill chunk or verify window, the
      CPU, a multi-device mesh, other shapes — gathers this layer's
      pages through the row (``PagedCache.read_layer``) for the einsum
      lines below, which are also what the tests hold the kernel to.
      Either way a paged program never holds a sequence's all-layers
      view. Which of the three a trace took is counted in
      ``paged_attn_arms`` (``"paged_copy_kernel"``, ``"paged_kernel"``,
      ``"gather"``).

    The [..., Hkv, S, D] layout (heads ahead of sequence) matters on
    TPU: the decode attention contracts D and batches (B, Hkv), so S×D
    are the minor-most dims exactly as the MXU wants them — the previous
    [..., S, Hkv, D] layout made XLA physically transpose both buffers
    every step (measured ~0.9 ms/step extra on the bench geometry).

    ``window`` (static; None = every earlier position): a window layer's
    query at position ``t`` sees key ``j`` iff ``0 <= t - j < window``.
    The einsum lines take one more ``where`` on the cached and on the
    chunk-local scores, the paged kernel a lower edge; the contiguous
    decode kernel is not asked. A window layer's ``PagedCache`` row holds
    only the live pages from logical page ``cache.base`` on, and cached
    positions count from there.

    ``block`` (static; 1 = causal, every model but a block-diffusion
    one): block-causal attention over blocks of ``block`` positions. The
    chunk starts on a block boundary; cached positions ``< index`` are
    all seen and the chunk's rows see each other where ``t // block >=
    u // block`` (:func:`block_mask`; a one-block chunk — the engine's
    block step — all both ways). The cold first chunk takes the mask
    through the einsum attention too, never the flash kernel. A
    one-block chunk on a ``PagedCache`` the block kernel takes
    (``paged_decode_attention.block_supported``) is attended by
    ``ptpu_paged_block_attn``: each slot's live pages read once a layer
    for all ``block`` query rows (``"paged_block_kernel"``).

    ``kernel_name`` (None: ``ptpu_paged_decode_attn``): the one-token
    paged kernel's name in the device trace, for a model that reads a
    layer kind apart (Laguna's window layers:
    ``ptpu_paged_decode_attn_window``). Each paged layer's arm is also
    counted by kind in ``paged_attn_layers``.

    Returns ``(out [B, T, Hq, D], payload)`` where payload leaves are the
    chunk k/v in buffer layout ([B, Hkv, T, D], scales [B, Hkv, T]).
    """
    import jax

    paged = isinstance(cache, PagedCache)
    bufs = cache.pool if paged else cache
    quantized = len(bufs) == 4
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)

    kt = k.transpose(0, 2, 1, 3)                       # [B, Hkv, T, D]
    vt = v.transpose(0, 2, 1, 3)
    if quantized:
        kq, ks = _quant_chunk(kt)
        vq, vs = _quant_chunk(vt)
        payload = (kq, vq, ks, vs)
    else:
        payload = (kt.astype(bufs[0].dtype), vt.astype(bufs[1].dtype))

    if index is None or (isinstance(index, int) and index == 0):
        if block > 1:
            out = F.scaled_dot_product_attention(q, k, v, block_mask(T, block))
            return out, payload
        # prefill: nothing behind us — plain causal over the raw chunk
        # (flash-kernel eligible); a chunk no longer than the window
        # never meets its lower edge
        out = F.scaled_dot_product_attention(q, k, v, window_mask(T, window),
                                             causal=True)
        return out, payload

    idx = jnp.asarray(index, jnp.int32)
    if paged:
        from paddle_tpu.ops.pallas import paged_decode_attention as _pk
        if (block > 1 and T == block
                and _pk.block_supported(q, bufs, cache.table[None])):
            paged_attn_arms["paged_block_kernel"] += 1
            out = _pk.paged_block_attention(
                q, kt, vt, bufs, cache.table[None], layer, idx, scale=scale)
            return out, payload
        if block == 1 and _pk.supported(q, bufs, cache.table[None]):
            arm = ("paged_copy_kernel" if _pk.copies_pages(bufs)
                   else "paged_kernel")
            paged_attn_arms[arm] += 1
            paged_attn_layers[window, Hq, Hkv, arm,
                              kernel_name or "ptpu_paged_decode_attn"] += 1
            edge = ({} if window is None
                    else {"window": window, "base": cache.base})
            if kernel_name is not None:
                edge["name"] = kernel_name
            out = _pk.paged_decode_attention(
                q, kt, vt, bufs, cache.table[None], layer, idx, scale=scale,
                **edge)
            return out, payload
        paged_attn_arms["gather"] += 1
        paged_attn_layers[window, Hq, Hkv, "gather", None] += 1
        sl = cache.read_layer(layer)
    else:
        from paddle_tpu.ops.pallas import decode_attention as _dk
        if window is None and block == 1 and _dk.supported(q, cache):
            out = _dk.decode_attention(q, kt, vt, cache, layer, idx,
                                       scale=scale)
            return out, payload
        # einsum fallback (CPU / unsupported shapes): slice this layer
        sl = (tuple(c[layer] for c in cache) if isinstance(layer, int) else
              tuple(jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
                    for c in cache))

    # two-piece softmax — prefix logits against the buffer + fresh-chunk
    # causal logits, normalized jointly. GQA maps q-head (g, h) to
    # kv-head h with no repeat of the cache.
    if quantized:
        k_c, v_c, k_s, v_s = sl
        dt = q.dtype
        kc = k_c.astype(dt) * k_s.astype(dt)[..., None]
        vc = v_c.astype(dt) * v_s.astype(dt)[..., None]
    else:
        kc, vc = (c.astype(q.dtype) for c in sl)
    S = kc.shape[2]
    qh = q.transpose(0, 2, 1, 3).reshape(B, Hkv, G, T, D)
    neg = jnp.finfo(jnp.float32).min
    s_c = jnp.einsum("bkgtd,bksd->bkgts", qh, kc) * scale
    if window is None:
        seen = (jnp.arange(S) < idx)[None, None, None, None, :]
    else:
        # view position s is absolute position first + s; query t of the
        # chunk stands at idx + t and sees what lies under ``window`` back
        first = 0
        if paged and cache.base is not None:
            first = cache.base * bufs[0].shape[3]
        at = first + jnp.arange(S)[None, :]
        seen = ((at < idx) & (idx + jnp.arange(T)[:, None] - at < window)
                )[None, None, None]
    s_c = jnp.where(seen, s_c.astype(jnp.float32), neg)
    s_n = jnp.einsum("bkgtd,bkud->bkgtu", qh, kt) * scale
    chunk_causal = (block_mask(T, block) if block > 1 else
                    jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    if window is not None:
        chunk_causal = chunk_causal & (
            jnp.arange(T)[:, None] - jnp.arange(T)[None, :] < window)
    s_n = jnp.where(chunk_causal[None, None, None],
                    s_n.astype(jnp.float32), neg)
    probs = jax.nn.softmax(jnp.concatenate([s_c, s_n], axis=-1), axis=-1)
    p_c, p_n = probs[..., :S].astype(q.dtype), probs[..., S:].astype(q.dtype)
    out = (jnp.einsum("bkgts,bksd->bkgtd", p_c, vc)
           + jnp.einsum("bkgtu,bkud->bkgtd", p_n, vt))
    out = out.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)
    return out, payload


def init_latent_cache(num_layers, batch_size, max_len, width, dtype):
    """The cache of a latent-attention (MLA) model in the shared leaf
    layout: ONE leaf ``[L, B, 1, S, width]`` — a token's compressed
    K/V row and its shared rope key side by side, nothing per head. The
    ``1`` stands where the other families keep KV heads, so the page
    pool, the page table and every write go through the functions that
    serve them (``init_paged_cache``, ``PagedCache.read_layer``,
    ``paged_write``, ``apply_cache_writes``) unchanged. The row is
    padded with zeros to whole 128-lane tiles (576 -> 640), which is what
    the TPU's tiled layout allocates for it anyway: stated as it is
    allocated, a page update is whole tiles and the pool keeps its
    layout (``generation.paged_write``)."""
    dtype = jnp.dtype(dtype)
    width = -(-width // 128) * 128
    if not jnp.issubdtype(dtype, jnp.floating):
        raise ValueError(
            f"latent (MLA) cache in {dtype}: int8 latent cache leaves are "
            "not implemented (the quantized layout is per KV head); use a "
            "float dtype")
    return (jnp.zeros((num_layers, batch_size, 1, max_len, width), dtype),)


def latent_attention(q_nope, q_rope, c_kv, k_rope, w_kc, w_vc, scale,
                     cache=None, index=None, layer=0):
    """Attention core of a latent-attention (MLA, DeepSeek-V2/V3) layer,
    in the two arithmetic forms it needs.

    ``q_nope`` [B, T, H, N] and ``q_rope`` [B, T, H, R] (rotated);
    ``c_kv`` [B, T, C] the chunk's compressed K/V after its norm and
    ``k_rope`` [B, T, R] its one rope key a token (rotated), shared by
    all heads; ``w_kc`` [C, H, N] and ``w_vc`` [C, H, V] the two halves
    of the K/V up-projection. Returns ``(out [B, T, H, V], payload)``;
    the payload is the chunk's cache rows ``[B, 1, T, C + R + pad]``
    (absent with no cache).

    *Expanded* (nothing behind the chunk — no cache, or a static index
    0): per-head keys ``[c_kv·w_kc | k_rope]`` and values ``c_kv·w_vc``
    are made for the chunk and attended causally; per position that is
    C·H·(N+V) multiply-adds once.

    *Absorbed* (a cache behind the chunk: decode, and a prefill chunk
    after a cached prefix): the up-projection moves onto the query and
    the output — ``q~ = q_nope·w_kc^T`` [C], score ``= q~·c_kv(s) +
    q_rope·k_rope(s)``, ``u = sum_s p·c_kv(s)``, ``o = u·w_vc`` — so a
    cached position is read as its C + R numbers and never expanded
    (expanding costs C·H·(N+V) multiply-adds a cached position a
    layer for every call). The chunk's own rows attend in the same
    form, jointly normalised with the cached ones.

    The absorbed form has two arms, picked as ``cached_attention`` picks
    and counted in ``paged_attn_arms``. A one-token chunk on a
    ``PagedCache`` that the latent kernel's gate takes
    (``paged_decode_attention.latent_supported``: one TPU chip, a float
    leaf, a row of whole lane tiles) is attended by
    ``ptpu_paged_latent_decode_attn``: ``[q~ | q_rope | 0]`` against the
    slot's live pages read once through the table row, each cached row
    key and value at once, an online softmax with float32 state; under
    the engine's ``vmap`` over slots one call a layer. Everything else —
    a prefill chunk, the CPU, a mesh, the contiguous cache of
    ``generate()`` — reads the layer (``PagedCache.read_layer`` gathers
    a slot's pages, capacity not fill) for the einsum lines below: a
    two-piece softmax in float32, as ``cached_attention``. Both arms
    feed the products operands in the model's dtype, accumulate in
    float32 and cast the probabilities to the model's dtype before the
    value product."""
    import jax

    B, T, H, _ = q_nope.shape
    C, R_ = c_kv.shape[-1], k_rope.shape[-1]
    payload = None
    if cache is not None:
        paged = isinstance(cache, PagedCache)
        buf = (cache.pool if paged else cache)[0]
        pad = jnp.zeros(c_kv.shape[:-1] + (buf.shape[-1] - C - R_,),
                        c_kv.dtype)
        row = jnp.concatenate([c_kv, k_rope, pad], axis=-1)
        payload = (row[:, None].astype(buf.dtype),)

    if cache is None or index is None or (isinstance(index, int)
                                          and index == 0):
        with jax.named_scope("mla/attend"):
            k_nope = jnp.einsum("btc,chn->bthn", c_kv, w_kc)
            v = jnp.einsum("btc,chv->bthv", c_kv, w_vc)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                          (B, T, H, R_))], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
            out = F.scaled_dot_product_attention(q, k, v, causal=True,
                                                 scale=scale)
        return out, payload

    idx = jnp.asarray(index, jnp.int32)
    if paged:
        from paddle_tpu.ops.pallas import paged_decode_attention as _pk
        if _pk.latent_supported(q_nope, cache.pool, cache.table[None], C):
            paged_attn_arms["paged_kernel"] += 1
            with jax.named_scope("mla/absorb"):
                q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w_kc)
            with jax.named_scope("mla/attend"):
                q_full = jnp.concatenate(
                    [q_lat, q_rope.astype(q_lat.dtype),
                     jnp.zeros((B, T, H, pad.shape[-1]), q_lat.dtype)], -1)
                u = _pk.paged_latent_decode_attention(
                    q_full, row[:, 0].astype(q_lat.dtype), cache.pool,
                    cache.table[None], layer, idx, scale=scale, C=C)
            with jax.named_scope("mla/absorb"):
                out = jnp.einsum("bthc,chv->bthv", u, w_vc)
            return out, payload
        paged_attn_arms["gather"] += 1
        (lat,) = cache.read_layer(layer)                # [1, 1, S, C+R]
    else:
        lat = (buf[layer] if isinstance(layer, int) else
               jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False))
    lat = lat[:, 0].astype(q_nope.dtype)                # [B, S, C+R]
    c_c, r_c = lat[..., :C], lat[..., C:C + R_]
    S = lat.shape[1]
    neg = jnp.finfo(jnp.float32).min
    with jax.named_scope("mla/absorb"):
        q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w_kc)
    with jax.named_scope("mla/attend"):
        # scores come out of the products in float32 (they are cast for
        # the softmax anyway): a score of ~10 rounded to bf16 is off by
        # 0.03 in the exponent
        f32 = dict(preferred_element_type=jnp.float32)
        s_c = (jnp.einsum("bthc,bsc->bhts", q_lat, c_c, **f32)
               + jnp.einsum("bthr,bsr->bhts", q_rope, r_c, **f32)) * scale
        s_c = jnp.where((jnp.arange(S) < idx)[None, None, None, :], s_c, neg)
        s_n = (jnp.einsum("bthc,buc->bhtu", q_lat, c_kv, **f32)
               + jnp.einsum("bthr,bur->bhtu", q_rope, k_rope, **f32)) * scale
        chunk_causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s_n = jnp.where(chunk_causal[None, None], s_n, neg)
        probs = jax.nn.softmax(jnp.concatenate([s_c, s_n], -1), axis=-1)
        p_c = probs[..., :S].astype(q_nope.dtype)
        p_n = probs[..., S:].astype(q_nope.dtype)
        u = (jnp.einsum("bhts,bsc->bthc", p_c, c_c)
             + jnp.einsum("bhtu,buc->bthc", p_n, c_kv))
    with jax.named_scope("mla/absorb"):
        out = jnp.einsum("bthc,chv->bthv", u, w_vc)
    return out, payload


def apply_cache_writes(cache, payload, index):
    """Write the stacked per-layer chunk payloads ([L, B, Hkv, T, ...])
    into the static cache at position ``index`` — one
    ``dynamic_update_slice`` per buffer per step, in place under the
    decode loop's donation. A ``generation.PagedCache`` has no buffer of
    its own to write: the payload goes back as it is and the paged
    program puts it into the pool (``generation.paged_write``)."""
    import jax

    if isinstance(cache, PagedCache):
        return payload
    idx = jnp.asarray(0 if index is None else index, jnp.int32)

    def wr(buf, x):
        zeros = (jnp.zeros((), jnp.int32),) * 3
        start = zeros + (idx,) + (jnp.zeros((), jnp.int32),) * (buf.ndim - 4)
        return jax.lax.dynamic_update_slice(buf, x.astype(buf.dtype), start)

    with jax.named_scope("kv/write"):
        return tuple(wr(b, x) for b, x in zip(cache, payload))


def init_kv_cache(num_layers, batch_size, max_len, num_kv_heads, head_dim,
                  dtype):
    """The stacked static KV-cache layout every attention family shares:
    ``([L, B, Hkv, S, D], [L, B, Hkv, S, D])`` zeros. Batch MUST stay on
    axis 1 — beam search reorders cache leaves along it (generation.py).
    Heads sit AHEAD of sequence so the decode attention reads [S, D]
    minor-most (see ``cached_attention``).

    ``dtype=jnp.int8`` selects the quantized layout
    ``(k_q, v_q, k_scale, v_scale)`` with f32 per-(head, position)
    scales [L, B, Hkv, S]; request it with
    ``generate(..., cache_dtype=jnp.int8)``."""
    shape = (num_layers, batch_size, num_kv_heads, max_len, head_dim)
    dtype = jnp.dtype(dtype)
    if dtype == jnp.int8:
        sshape = shape[:-1]
        return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.zeros(sshape, jnp.float32),
                jnp.zeros(sshape, jnp.float32))
    if not jnp.issubdtype(dtype, jnp.floating):
        # any other integer dtype would silently truncate k/v on write
        raise ValueError(
            f"cache dtype {dtype} unsupported: use a float dtype or "
            "jnp.int8 (the quantized layout)")
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
