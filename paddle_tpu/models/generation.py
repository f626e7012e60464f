"""Autoregressive generation loop (greedy / temperature / top-k / top-p).

The reference's decode loop lives in graph ops (``paddle/fluid/operators/
beam_search_op.cc``, sampling ops) driven per-step from Python. The TPU
design instead compiles the WHOLE loop: prefill is one jitted forward
over the prompt, then ``lax.while_loop`` runs single-token steps against
a fixed-shape KV cache (``LlamaForCausalLM.init_cache``) — one compiled
step serves every position, no per-length recompilation — and exits as
soon as every row has emitted EOS, so short completions stop paying for
``max_new_tokens`` steps.

Works with any model exposing ``init_cache(B, S)`` and
``forward_with_cache(ids, cache, index)``.

Speculative decoding (:func:`speculative_generate`, Leviathan et al.
ICML '23): a cheap drafter — the model-free n-gram lookup of
:func:`ngram_propose`, or a small draft model with the same cache
contract — proposes k tokens, ONE multi-token target forward verifies
them all, and the longest matching prefix is accepted. Greedy output is
byte-identical to :func:`generate`; sampled output follows the same
one-split-per-emitted-token key schedule, so a fixed ``key`` replays
identically with speculation on or off. The serving engine
(``serving/engine.py``) carries the batched, flag-gated version of the
same algorithm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["generate", "sample_logits", "beam_search", "init_paged_cache",
           "PagedCache", "StateCache", "paged_gather", "paged_scatter",
           "paged_write", "paged_write_block", "transfer_schedule",
           "block_pick", "block_diffusion_generate",
           "advance_key", "ngram_propose",
           "speculative_generate", "serialize_page", "deserialize_page",
           "STACKED_KV_SPEC", "POOL_KV_SPEC", "PAGE_TABLE_SPEC"]

# --- sharded-KV spec map (the serving DeviceLayout contract) ----------
# Tensor-parallel serving shards the KV cache on the KV-head axis (Pope
# et al., "Efficiently Scaling Transformer Inference", 2022) — the axis
# the column-split wk/wv projections already produce sharded, so cache
# writes and attention reads need no resharding collective. Where that
# axis sits depends on the engine layout:
#   stacked contiguous leaves  [slots, L, 1, Hkv, S, *rest]  -> axis 3
#   paged pool leaves [num_pages + 1, L, Hkv, page_tokens, *rest] -> 2
# Both are PREFIX specs (shorter than the leaf rank), so the int8
# quantized layout's scale leaves — one trailing dim shorter than their
# data leaves — shard identically on the same Hkv axis.
from jax.sharding import PartitionSpec as _P

STACKED_KV_SPEC = _P(None, None, None, "tp")
POOL_KV_SPEC = _P(None, None, "tp")
# The page table itself is [slots, max_pages] int32 — tiny, and every
# shard of a tensor-parallel pool needs the full slot->page indirection
# to gather its own KV-head slice, so it is replicated across the mesh
# (the device-resident-page-table path keeps it living there between
# steps instead of re-uploading it each iteration).
PAGE_TABLE_SPEC = _P()


_advance_key_jit = None


def advance_key(key, steps):
    """Advance a PRNG key by ``steps`` split-and-keep-first operations —
    exactly the per-emitted-token key schedule of the serving
    ``GenerationEngine`` (each token consumes one
    ``key, sub = jax.random.split(key)``). A resumed sampled stream
    replays its RNG position by starting from
    ``advance_key(PRNGKey(seed), tokens_already_delivered)``: token
    ``k`` of the resumed stream then draws from the same subkey as
    token ``k`` of the uninterrupted one. ``steps`` may be traced (the
    loop is a ``lax.fori_loop``); 0 returns the key unchanged.

    The loop is jitted once per process: the engine calls this eagerly
    on every preemption resume and failover replay, and an un-jitted
    ``fori_loop`` re-traces on each call — tens of milliseconds on the
    hot resume path for what is microseconds of device work."""
    global _advance_key_jit
    if _advance_key_jit is None:
        _advance_key_jit = jax.jit(lambda k, n: jax.lax.fori_loop(
            0, n, lambda i, kk: jax.random.split(kk)[0], k))
    return _advance_key_jit(key, jnp.asarray(steps, jnp.int32))


def sample_logits(logits, key=None, *, temperature: float = 1.0,
                  top_k: int = 0, top_p: float = 1.0):
    """Pick next tokens from [B, V] logits. ``temperature == 0`` or
    ``key is None`` → greedy argmax; otherwise temperature / top-k /
    nucleus (top-p) sampling."""
    if key is None or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest set of tokens with cumulative prob >= top_p
        # (always keep the top-1)
        cutoff_mask = cum - probs < top_p
        threshold = jnp.min(
            jnp.where(cutoff_mask, sorted_logits, jnp.inf), axis=-1,
            keepdims=True)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM PagedAttention, SOSP '23): the pool/page-table
# layer of the cache contract. A model's ``init_cache`` proto defines the
# per-sequence leaf layout ([L, 1, Hkv, S, D] buffers — scales
# [L, 1, Hkv, S] in the int8 layout); these helpers re-express it as a
# pool of fixed-size pages plus a per-sequence page table. A paged
# program hands ``forward_with_cache`` a :class:`PagedCache` (the pool
# and ONE sequence's table row) in the cache's place: attention reads
# each layer's pages through the row — the paged decode kernel's index
# maps on one TPU chip, ``PagedCache.read_layer``'s gather everywhere
# else; no all-layers contiguous view ever materializes — the chunk's
# new k/v come back as the payload, and :func:`paged_write` puts them
# into the donated pool with in-place slice updates. ``paged_gather`` /
# ``paged_scatter`` are the whole-sequence translations between the two
# layouts — what the tests compare the per-layer path against, and the
# host-side tools' way to read a sequence out of a pool. Physical page 0
# is reserved as the null page: unmapped table entries and masked
# (padding, rejected-draft, inactive-slot) writes land there, never on a
# live page. Exactness contract: a read of pages holding positions
# [0, index) reproduces the contiguous buffer bit-for-bit over those
# positions, so paged decode logits equal contiguous decode logits.
# ---------------------------------------------------------------------------

def init_paged_cache(proto_cache, num_pages: int, page_tokens: int):
    """Allocate the page pool for a cache proto (``model.init_cache(1,
    S)`` leaves). Returns leaves ``[num_pages + 1, L, Hkv, page_tokens,
    *rest]`` — index 0 is the reserved null page, usable page ids are
    ``1 .. num_pages``."""
    pool = []
    for leaf in proto_cache:
        if leaf.ndim < 4 or leaf.shape[1] != 1:
            raise ValueError(
                f"cache leaf {leaf.shape} is not the [L, 1, Hkv, S, ...] "
                "layout init_kv_cache produces")
        L, _, Hkv = leaf.shape[:3]
        rest = leaf.shape[4:]
        pool.append(jnp.zeros((num_pages + 1, L, Hkv, page_tokens) + rest,
                              leaf.dtype))
    return tuple(pool)


@jax.tree_util.register_pytree_node_class
class PagedCache:
    """One sequence's cache as a paged program sees it: the pool leaves
    (``init_paged_cache`` layout, two or the int8 four) and the
    sequence's page-table row (``table`` [M] int32 physical page ids;
    entry 0 = null page). ``forward_with_cache`` takes it where it takes
    the contiguous tuple; ``_common.cached_attention`` tells the two
    apart by type, and reads the pool either through
    ``ops.pallas.paged_decode_attention`` (a one-token chunk on one TPU
    chip: the kernel takes ``pool`` and ``table`` as they are) or
    through :meth:`read_layer`. Under the engine's ``jax.vmap`` over
    slots the pool is unmapped and the row is the mapped operand; the
    kernel folds that axis into its grid.

    ``base`` (None everywhere but on a window layer group's row): the
    logical page the row's first entry stands for. Such a row holds
    only a stream's live pages — the window's and the chunk's — so
    ``table[i]`` is logical page ``base + i`` and view position ``v``
    of :meth:`read_layer` is absolute position ``base * page_tokens +
    v``."""

    def __init__(self, pool, table, base=None):
        self.pool, self.table, self.base = tuple(pool), table, base

    def tree_flatten(self):
        return (self.pool, self.table, self.base), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @jax.named_scope("kv/gather")
    def read_layer(self, layer):
        """Layer ``layer``'s (python int or traced scalar) contiguous
        view: leaves ``[1, Hkv, M * page_tokens, *rest]`` — what
        ``paged_gather(pool, table)`` holds at ``[layer]``, bit for bit,
        from one gather of this layer's pages. Unmapped (null) regions
        hold garbage; attention masks them (the fill position bounds
        every read)."""
        out = []
        for leaf in self.pool:
            g = leaf[self.table, layer]           # [M, Hkv, P, *rest]
            g = jnp.moveaxis(g, 0, 1)             # [Hkv, M, P, *rest]
            s = g.shape
            out.append(g.reshape(s[0], s[1] * s[2], *s[3:])[None])
        return tuple(out)


@jax.tree_util.register_pytree_node_class
class StateCache:
    """A recurrent layer group's cache (``models/kimi_linear.py``'s KDA
    layers): state of O(1) a sequence where the other groups keep
    per-token rows. ``rows`` are the group's leaves ``[L, B, ...]`` —
    the float32 state ``[L, B, H, dk, dv]`` and the convolution tail
    (``K-1`` inputs of ``D`` a sequence) — which a forward reads AND
    replaces, so they go
    through the layers as a carry and come back whole, in the cache's
    place. ``length`` tells the recurrence what attention learns from
    its mask: the chunk's true token count (None = all of it; a scalar
    or ``[B]``). Positions at or past it are padding and must be the
    identity on the rows — a prefill bucket's padded tail, and an idle
    slot of the fused decode step (length 0)."""

    def __init__(self, rows, length=None):
        self.rows, self.length = tuple(rows), length

    def tree_flatten(self):
        return (self.rows, self.length), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.named_scope("kv/gather")
def paged_gather(pool, table):
    """Materialize a sequence's contiguous cache view from its page
    table (``table`` [M] int32 physical page ids; entry 0 = null page).
    Returns leaves ``[L, 1, Hkv, M * page_tokens, *rest]`` — position
    ``p`` reads ``pool[table[p // page_tokens]][..., p % page_tokens]``.
    Unmapped (null) regions hold garbage. All layers at once: the
    compiled programs read per layer (``PagedCache.read_layer``), this is
    their oracle."""
    out = []
    for leaf in pool:
        g = leaf[table]                       # [M, L, Hkv, P, *rest]
        g = jnp.moveaxis(g, 0, 2)             # [L, Hkv, M, P, *rest]
        s = g.shape
        out.append(g.reshape(s[0], s[1], s[2] * s[3], *s[4:])[:, None])
    return tuple(out)


@jax.named_scope("kv/write")
def paged_write(pool, pages, offs, rows):
    """Put ``n`` single positions into the pool, in place: position
    ``i`` (``rows`` leaves ``[n, L, Hkv, *rest]``) goes to page
    ``pages[i]`` at in-page offset ``offs[i]``, one after the other:
    read the page, replace the row, write the whole page back
    (``dynamic_update_slice`` of ``[1, L, Hkv, P, *rest]``, dynamic on
    axis 0 only). Updating the row alone — by a scatter on axes 0 and 3
    or by a one-row slice update — touches half a packed ``(P, D)`` bf16
    tile, and XLA:TPU then gives the whole pool a position-major layout
    for the program: a copy of every leaf in and out per call. The
    caller sends what must not land (padding, rejected drafts, inactive
    slots) to the null page 0; later updates win where two positions
    name one target, which only happens there."""
    n = pages.shape[0]
    zero = jnp.zeros((), jnp.int32)

    def body(i, pool):
        out = []
        for leaf, r in zip(pool, rows):
            start = (pages[i],) + (zero,) * (leaf.ndim - 1)
            page = jax.lax.dynamic_slice(leaf, start, (1,) + leaf.shape[1:])
            x = jax.lax.dynamic_index_in_dim(r, i, 0)    # [1, L, Hkv, *rest]
            x = jnp.expand_dims(x, 3).astype(leaf.dtype)
            here = (jnp.arange(leaf.shape[3]) == offs[i]).reshape(
                (1, 1, 1, -1) + (1,) * (leaf.ndim - 4))
            out.append(jax.lax.dynamic_update_slice(
                leaf, jnp.where(here, x, page), start))
        return tuple(out)

    return jax.lax.fori_loop(0, n, body, tuple(pool))


@jax.named_scope("kv/write")
def paged_write_block(pool, pages, offs, rows):
    """:func:`paged_write` for ``n`` blocks of ``T`` positions: block
    ``i`` (``rows`` leaves ``[n, L, Hkv, T, *rest]``) goes to page
    ``pages[i]`` at in-page offsets ``offs[i] .. offs[i] + T`` — ``T``
    divides the page and ``offs[i]`` is a multiple of it, so a block
    never crosses a page. One whole-page update a block, as
    :func:`paged_write` makes one a position."""
    n = pages.shape[0]
    zero = jnp.zeros((), jnp.int32)

    def body(i, pool):
        out = []
        for leaf, r in zip(pool, rows):
            start = (pages[i],) + (zero,) * (leaf.ndim - 1)
            page = jax.lax.dynamic_slice(leaf, start, (1,) + leaf.shape[1:])
            x = jax.lax.dynamic_index_in_dim(r, i, 0)    # [1, L, Hkv, T, ...]
            P, T = leaf.shape[3], x.shape[3]
            # offset p of the page takes row p % T of the block
            x = jnp.concatenate([x] * (P // T), axis=3).astype(leaf.dtype)
            at = jnp.arange(P) - offs[i]
            here = ((at >= 0) & (at < T)).reshape(
                (1, 1, 1, -1) + (1,) * (leaf.ndim - 4))
            out.append(jax.lax.dynamic_update_slice(
                leaf, jnp.where(here, x, page), start))
        return tuple(out)

    return jax.lax.fori_loop(0, n, body, tuple(pool))


def paged_scatter(pool, table, chunk, index, page_tokens: int,
                  length=None):
    """Write a contiguous chunk (leaves ``[L, 1, Hkv, T, *rest]``,
    covering positions ``[index, index + T)``) into the pool through
    ``table`` (:func:`paged_write`, one position at a time). Positions
    at or past ``length`` (the chunk's true token count — padding) are
    redirected to the null page so a right-padded chunk can never
    clobber a live page."""
    T = chunk[0].shape[3]
    j = jnp.arange(T)
    pos = jnp.asarray(index, jnp.int32) + j
    pidx = jnp.clip(pos // page_tokens, 0, table.shape[0] - 1)
    pages = table[pidx]
    if length is not None:
        pages = jnp.where(j < length, pages, 0)
    rows = tuple(jnp.moveaxis(ch[:, 0], 2, 0) for ch in chunk)
    return paged_write(pool, pages, pos % page_tokens, rows)


_PAGE_MAGIC = b"KVPG1"


def serialize_page(leaves) -> bytes:
    """Encode ONE page's cache leaves (``[L, Hkv, page_tokens, *rest]``
    slices of the pool — any leaf count, so the int8 quantized layout's
    4-leaf data+scale variant serializes identically) into a
    self-describing wire frame: magic, a length-prefixed JSON header of
    per-leaf dtype/shape, then the raw leaf bytes concatenated. The
    byte image is exact — :func:`deserialize_page` rebuilds arrays that
    compare ``tobytes()``-equal, which is what makes a fetched page
    bit-identical to the page the publisher computed."""
    import json
    import struct
    specs = []
    blobs = []
    for leaf in leaves:
        a = np.ascontiguousarray(np.asarray(leaf))
        specs.append({"shape": list(a.shape), "dtype": a.dtype.name})
        blobs.append(a.tobytes())
    head = json.dumps(specs, separators=(",", ":")).encode()
    return b"".join([_PAGE_MAGIC, struct.pack("<I", len(head)), head]
                    + blobs)


def deserialize_page(buf: bytes):
    """Decode a :func:`serialize_page` frame back into a tuple of host
    numpy leaves. Raises ``ValueError`` on a foreign or truncated
    frame (a corrupt store entry must read as a miss, not as garbage
    KV)."""
    import json
    import struct
    m = len(_PAGE_MAGIC)
    if buf[:m] != _PAGE_MAGIC:
        raise ValueError("not a KV page frame")
    (hlen,) = struct.unpack_from("<I", buf, m)
    head = json.loads(buf[m + 4:m + 4 + hlen].decode())
    off = m + 4 + hlen
    out = []
    for spec in head:
        try:
            dt = np.dtype(spec["dtype"])
        except TypeError:
            import ml_dtypes  # jax's extension dtypes (bfloat16 etc.)
            dt = np.dtype(getattr(ml_dtypes, spec["dtype"]))
        n = int(np.prod(spec["shape"], dtype=np.int64)) * dt.itemsize
        if off + n > len(buf):
            raise ValueError("truncated KV page frame")
        out.append(np.frombuffer(buf, dt, count=n // dt.itemsize,
                                 offset=off).reshape(spec["shape"]))
        off += n
    if off != len(buf):
        raise ValueError("trailing bytes in KV page frame")
    return tuple(out)


def ngram_propose(context, k: int, *, max_ngram: int = 3,
                  min_ngram: int = 1) -> np.ndarray:
    """Model-free draft proposal by suffix n-gram lookup ("Prompt
    Lookup Decoding"): find a PRIOR occurrence of the stream's own
    trailing n-gram inside ``context`` (prompt + emitted tokens) and
    propose the up-to-``k`` tokens that followed it — the most recent
    occurrence with a full ``k``-token continuation, else the one with
    the longest continuation (a recent match truncated by the context
    edge drafts almost nothing exactly when the stream is looping and
    a full draft would be nearly free). Tries ``max_ngram`` down to
    ``min_ngram``; returns an int32 array of 0..k proposed tokens (0 =
    no match — the caller falls back to a plain decode step). Host-side
    numpy, O(len(context)) per n tried — zero extra weights, zero
    device work."""
    ctx = np.asarray(context, np.int64).reshape(-1)
    k = int(k)
    if k <= 0 or ctx.size < min_ngram + 1:
        return np.zeros((0,), np.int32)
    for n in range(min(max_ngram, ctx.size - 1), min_ngram - 1, -1):
        suffix = ctx[ctx.size - n:]
        # candidate starts 0 .. ctx.size-1-n: every window has at least
        # one continuation token, and the suffix occurrence itself
        # (start ctx.size-n) is excluded
        windows = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
        hits = np.nonzero((windows == suffix).all(axis=1))[0]
        if hits.size:
            full = hits[hits + n + k <= ctx.size]
            s = int(full[-1]) if full.size else int(hits[0])
            return ctx[s + n:s + n + k].astype(np.int32)
    return np.zeros((0,), np.int32)


def _draft_model_propose(draft_model, context, k: int,
                         cache_dtype=None) -> np.ndarray:
    """Greedy k-token lookahead from a small draft model sharing the
    ``init_cache``/``forward_with_cache`` contract: prefill the full
    context, then argmax-decode ``k`` tokens. Eager (re-prefills per
    call) — the jitted/bucketed variant lives in the serving engine."""
    ctx = np.asarray(context, np.int32).reshape(1, -1)
    T = ctx.shape[1]
    k = int(k)
    if k <= 0:
        return np.zeros((0,), np.int32)
    cache = draft_model.init_cache(1, T + k, dtype=cache_dtype)
    logits, cache = draft_model.forward_with_cache(
        jnp.asarray(ctx), cache, index=0)
    tok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
    out = [int(tok)]
    for i in range(k - 1):
        logits, cache = draft_model.forward_with_cache(
            tok[None, None], cache, index=T + i)
        tok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
        out.append(int(tok))
    return np.asarray(out, np.int32)


def speculative_generate(model, input_ids, max_new_tokens: int, *,
                         spec_k: int = 4, draft_model=None,
                         temperature: float = 0.0, top_k: int = 0,
                         top_p: float = 1.0, eos_token_id: int | None = None,
                         pad_token_id: int = 0, key=None, cache_dtype=None,
                         max_ngram: int = 3):
    """Speculative decode for ONE sequence — same output contract as
    :func:`generate` (shape [1, T0 + max_new_tokens], pad-filled past
    EOS) with fewer serial target-model forwards.

    Per round: the drafter (``draft_model`` if given, else
    :func:`ngram_propose` over the sequence's own prompt + emitted
    tokens) proposes up to ``spec_k`` tokens; ONE target forward over
    ``[pending, d_1..d_m]`` at the current position yields the target's
    pick at every proposed position; the longest prefix of drafts
    matching those picks is accepted, plus the target's own pick at the
    first mismatch — so each round emits 1..m+1 tokens and every
    emitted token is EXACTLY what non-speculative decode would have
    produced (greedy byte-identity; sampled picks are deterministic per
    key because each position's pick uses its scheduled subkey).

    RNG contract: one ``key, sub = jax.random.split(key)`` is consumed
    per EMITTED token regardless of acceptance pattern — the
    :func:`generate` /serving-engine schedule — so speculative and
    non-speculative runs replay identically and ``advance_key``-based
    stream resumption composes unchanged.

    Rollback: rejected drafts were written into cache positions at or
    past the new decode position; attention masks every position at or
    past the forward index (see ``models/_common.cached_attention``),
    and later writes overwrite them, so rollback is pure position-
    pointer arithmetic. The cache carries ``spec_k`` scratch positions
    past ``T0 + max_new_tokens`` so a full-width verify near the end of
    generation stays in bounds.

    Host-driven and eager (one device sync per round) — the reference
    implementation the tests pin the serving engine's compiled path
    against."""
    input_ids = jnp.asarray(input_ids, jnp.int32)
    B, T0 = input_ids.shape
    if B != 1:
        raise ValueError(
            f"speculative_generate handles one sequence (got batch {B}); "
            "per-row acceptance lengths desynchronize a shared cache "
            "index — use the serving engine for batched speculation")
    max_new_tokens = int(max_new_tokens)
    if max_new_tokens <= 0:
        return input_ids
    spec_k = max(int(spec_k), 0)
    S = T0 + max_new_tokens + spec_k          # spec_k scratch tail
    cache = model.init_cache(1, S, dtype=cache_dtype)
    logits, cache = model.forward_with_cache(input_ids, cache, index=0)
    if key is None:
        key = jax.random.PRNGKey(0)

    def pick(row_logits, key):
        return int(sample_logits(
            row_logits[None], None if temperature == 0.0 else key,
            temperature=temperature, top_k=top_k, top_p=top_p)[0])

    key, sub = jax.random.split(key)
    pending = pick(logits[0, T0 - 1], sub)
    emitted = [pending]
    finished = eos_token_id is not None and pending == eos_token_id
    prompt_np = np.asarray(input_ids[0])
    pos = T0                                  # pending not yet in cache

    while len(emitted) < max_new_tokens and not finished:
        remaining = max_new_tokens - len(emitted)
        budget = min(spec_k, remaining - 1)
        draft = np.zeros((0,), np.int32)
        if budget > 0:
            ctx = np.concatenate(
                [prompt_np, np.asarray(emitted, np.int32)])
            draft = (_draft_model_propose(draft_model, ctx, budget,
                                          cache_dtype=cache_dtype)
                     if draft_model is not None
                     else ngram_propose(ctx, budget, max_ngram=max_ngram))
        ids = np.concatenate(
            [np.asarray([pending], np.int32), draft])[None]
        logits, cache = model.forward_with_cache(
            jnp.asarray(ids), cache, index=pos)
        # prospective per-position picks: position i's pick uses the
        # subkey of the (i+1)-th split past the current key, but only
        # the splits of ACCEPTED (emitted) tokens are committed below
        chain, cur, picks = [], key, []
        for i in range(ids.shape[1]):
            cur, sub = jax.random.split(cur)
            chain.append(cur)
            picks.append(pick(logits[0, i], sub))
        accept = 0
        while accept < draft.size and picks[accept] == int(draft[accept]):
            accept += 1
        new_toks = [int(t) for t in draft[:accept]] + [picks[accept]]
        for t in new_toks:
            emitted.append(t)
            if eos_token_id is not None and t == eos_token_id:
                finished = True
                break
        pos += accept + 1
        pending = picks[accept]
        key = chain[accept]                  # one split per emitted token

    seq = np.full((1, T0 + max_new_tokens), pad_token_id, np.int32)
    seq[0, :T0] = prompt_np
    seq[0, T0:T0 + len(emitted)] = emitted
    return jnp.asarray(seq)


def generate(model, input_ids, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
             eos_token_id: int | None = None, pad_token_id: int = 0,
             key=None, cache_dtype=None):
    """Decode ``max_new_tokens`` tokens after the prompt.

    Returns [B, T0 + max_new_tokens] int32; positions after an emitted
    EOS are filled with ``pad_token_id``. Jit-compatible (wrap the call
    in ``jax.jit`` with ``static_argnums`` for the ints, or close over
    them) — the loop itself is a ``lax.while_loop`` that exits as soon
    as EVERY row has finished, so short completions don't pay for
    ``max_new_tokens`` steps (unwritten positions hold ``pad_token_id``
    from the initial fill — bit-identical to running the loop out, which
    only wrote pads past EOS).
    """
    input_ids = jnp.asarray(input_ids, jnp.int32)
    if max_new_tokens <= 0:
        return input_ids
    B, T0 = input_ids.shape
    S = T0 + int(max_new_tokens)
    cache = model.init_cache(B, S, dtype=cache_dtype)

    logits, cache = model.forward_with_cache(input_ids, cache, index=0)
    seq = jnp.concatenate(
        [input_ids, jnp.full((B, max_new_tokens), pad_token_id, jnp.int32)],
        axis=1)

    if key is None:
        key = jax.random.PRNGKey(0)

    def pick(logits, key):
        return sample_logits(logits, None if temperature == 0.0 else key,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p)

    key, sub = jax.random.split(key)
    next_tok = pick(logits[:, -1], sub)
    finished = jnp.zeros((B,), bool)
    if eos_token_id is not None:
        finished = next_tok == eos_token_id
    seq = jax.lax.dynamic_update_slice(seq, next_tok[:, None], (0, T0))

    def body(state):
        i, seq, cache, prev_tok, finished, key = state
        logits, cache = model.forward_with_cache(
            prev_tok[:, None], cache, index=T0 + i - 1)
        key, sub = jax.random.split(key)
        tok = pick(logits[:, -1], sub)
        if eos_token_id is not None:
            tok = jnp.where(finished, pad_token_id, tok)
            finished = finished | (tok == eos_token_id)
        seq = jax.lax.dynamic_update_slice(
            seq, tok[:, None], (0, T0 + i))
        return i + 1, seq, cache, tok, finished, key

    def cond(state):
        i, _, _, _, finished, _ = state
        # early exit once every row is done: the fori body only wrote
        # pad_token_id past EOS, and seq was initialized pad-filled, so
        # skipping those steps changes nothing but the step count
        return (i < max_new_tokens) & ~jnp.all(finished)

    if max_new_tokens > 1:
        _, seq, cache, next_tok, finished, key = jax.lax.while_loop(
            cond, body,
            (jnp.asarray(1, jnp.int32), seq, cache, next_tok, finished,
             key))
    return seq


def transfer_schedule(masked: int, steps: int) -> list[int]:
    """The linear transfer schedule of block diffusion: how many of a
    block's ``masked`` positions each of ``steps`` denoising steps fixes
    — ``masked // steps`` a step, the remainder one each to the first
    steps."""
    base, rem = divmod(int(masked), int(steps))
    return [base + (i < rem) for i in range(int(steps))]


def block_pick(logits, masked, n, mask_token_id: int):
    """One denoising step's pick, greedy with static low-confidence
    remasking: at every masked position (``masked`` [..., B]) the
    logits' [..., B, V] best token ``x0`` other than ``[MASK]`` itself
    and its probability ``c``; the ``n`` [...] masked positions of
    highest ``c`` are fixed to their ``x0`` (equal confidences: the lower
    position first). Returns ``(x0, the positions fixed)``. The serving
    engine's block step and :func:`block_diffusion_generate` both pick
    here."""
    lg = logits.astype(jnp.float32)
    x0 = jnp.argmax(jnp.where(jnp.arange(lg.shape[-1]) == mask_token_id,
                              -jnp.inf, lg), axis=-1).astype(jnp.int32)
    conf = jnp.exp(jnp.take_along_axis(lg, x0[..., None], -1)[..., 0]
                   - jax.nn.logsumexp(lg, axis=-1))
    _, order = jax.lax.top_k(jnp.where(masked, conf, -jnp.inf),
                             masked.shape[-1])
    rank = jnp.argsort(order, axis=-1)
    return x0, masked & (rank < jnp.asarray(n)[..., None])


def block_diffusion_generate(model, input_ids, max_new_tokens: int, *,
                             block_length: int, denoising_steps: int,
                             mask_token_id: int,
                             eos_token_id: int | None = None,
                             pad_token_id: int = 0, cache_dtype=None):
    """Greedy block-diffusion decode of ONE sequence on the contiguous
    cache of a model whose attention is block-causal over blocks of
    ``block_length`` (``models/sdar.py``): the oracle the serving
    engine's block step is held to.

    The prompt's first ``len // B`` blocks are prefilled; the first
    generated block holds the prompt's remainder and ``[MASK]`` in its
    other positions. Each denoising step forwards the block's B
    positions against the cache and fixes the masked positions of
    highest confidence (:func:`block_pick`), as many as
    :func:`transfer_schedule` gives that step for the block's masked
    count; once none is masked, a commit forwards the block's final
    tokens and writes their K/V, and the next block starts ``B`` on. The
    stream ends after the block that holds EOS or reaches
    ``max_new_tokens``; tokens past either are computed and not emitted.

    A position is masked until a step fixes it, whatever id the prompt
    put there, and no position is fixed to ``[MASK]``.

    Returns ``[1, T0 + max_new_tokens]`` int32 as :func:`generate`
    (``pad_token_id`` past the end). Host-driven; one jitted forward a
    chunk shape."""
    ids = np.asarray(input_ids, np.int32).reshape(-1)
    T0, B = ids.size, int(block_length)
    max_new_tokens = int(max_new_tokens)
    L0 = T0 // B * B
    end = L0 + -(-(T0 - L0 + max_new_tokens) // B) * B
    cache = model.init_cache(1, end, dtype=cache_dtype)
    fwd = jax.jit(lambda m, x, c, i: m.forward_with_cache(x, c, index=i))
    if L0:
        _, cache = fwd(model, jnp.asarray(ids[None, :L0]), cache,
                       jnp.int32(0))
    p0, first = L0, T0 - L0           # the prompt's share of block one
    blk = np.full((B,), mask_token_id, np.int32)
    blk[:first] = ids[L0:]
    emitted = []
    while True:
        sched = transfer_schedule(B - first, denoising_steps)
        fixed = np.where(np.arange(B) < first, -2, -1).astype(np.int32)
        step = 0
        while (fixed == -1).any():
            logits, _ = fwd(model, jnp.asarray(blk[None]), cache,
                            jnp.int32(p0))
            x0, fix = (np.asarray(t) for t in block_pick(
                logits[0], jnp.asarray(fixed == -1),
                sched[min(step, len(sched) - 1)], mask_token_id))
            blk = np.where(fix, x0, blk).astype(np.int32)
            fixed[fix] = step
            step += 1
        done = False
        for t in blk[first:]:
            emitted.append(int(t))
            if ((eos_token_id is not None and t == eos_token_id)
                    or len(emitted) >= max_new_tokens):
                done = True
                break
        if done:
            break
        _, cache = fwd(model, jnp.asarray(blk[None]), cache, jnp.int32(p0))
        p0, first = p0 + B, 0
        blk = np.full((B,), mask_token_id, np.int32)
    seq = np.full((1, T0 + max_new_tokens), pad_token_id, np.int32)
    seq[0, :T0] = ids
    seq[0, T0:T0 + len(emitted)] = emitted
    return jnp.asarray(seq)


def beam_search(model, input_ids, max_new_tokens: int, *,
                num_beams: int = 4, eos_token_id: int | None = None,
                pad_token_id: int = 0, length_penalty: float = 1.0,
                cache_dtype=None):
    """Beam-search decoding, fully compiled (reference:
    ``operators/beam_search_op.cc`` + ``beam_search_decode_op.cc`` and the
    BeamSearchDecoder of ``python/paddle/nn/layer/transformer.py`` —
    per-step graph ops driven from Python; here ONE ``lax.fori_loop``
    carries [B, beam] hypothesis state and the KV cache is gathered along
    its batch axis on every beam reorder).

    Returns [B, T0 + max_new_tokens] int32 — the best beam per batch item
    under ``score / gen_len**length_penalty``.
    """
    input_ids = jnp.asarray(input_ids, jnp.int32)
    B, T0 = input_ids.shape
    K = int(num_beams)
    S = T0 + int(max_new_tokens)
    NEG = jnp.asarray(-1e9, jnp.float32)

    flat_ids = jnp.repeat(input_ids, K, axis=0)           # [B*K, T0]
    cache = model.init_cache(B * K, S, dtype=cache_dtype)
    logits, cache = model.forward_with_cache(flat_ids, cache, index=0)
    V = logits.shape[-1]

    # step 0: all beams hold the same prompt — select K distinct first
    # tokens from beam 0's distribution
    logp0 = jax.nn.log_softmax(
        logits.reshape(B, K, -1, V)[:, 0, -1].astype(jnp.float32))
    scores, tok = jax.lax.top_k(logp0, K)                 # [B, K]

    seq = jnp.concatenate(
        [input_ids, jnp.full((B, max_new_tokens), pad_token_id, jnp.int32)],
        axis=1)
    seq = jnp.broadcast_to(seq[:, None], (B, K, S)).copy()
    seq = seq.at[:, :, T0].set(tok)
    finished = (tok == eos_token_id) if eos_token_id is not None else (
        jnp.zeros((B, K), bool))
    gen_lens = jnp.ones((B, K), jnp.float32)

    # token distribution for finished beams: pad with no score change
    pad_only = jnp.full((V,), NEG).at[pad_token_id].set(0.0)

    def body(i, state):
        seq, cache, scores, prev_tok, finished, gen_lens = state
        logits, cache = model.forward_with_cache(
            prev_tok.reshape(B * K, 1), cache, index=T0 + i - 1)
        logp = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32)).reshape(B, K, V)
        logp = jnp.where(finished[:, :, None], pad_only[None, None], logp)
        total = scores[:, :, None] + logp                 # [B, K, V]
        new_scores, idx = jax.lax.top_k(total.reshape(B, K * V), K)
        from_beam = idx // V                              # [B, K]
        tok = (idx % V).astype(jnp.int32)

        # reorder hypothesis state by source beam
        seq = jnp.take_along_axis(seq, from_beam[:, :, None], axis=1)
        finished = jnp.take_along_axis(finished, from_beam, axis=1)
        gen_lens = jnp.take_along_axis(gen_lens, from_beam, axis=1)
        gather = (jnp.arange(B)[:, None] * K + from_beam).reshape(-1)
        cache = jax.tree_util.tree_map(lambda c: c[:, gather], cache)

        seq = jax.lax.dynamic_update_slice(
            seq, tok[:, :, None], (0, 0, T0 + i))
        gen_lens = gen_lens + (~finished).astype(jnp.float32)
        if eos_token_id is not None:
            finished = finished | (tok == eos_token_id)
        return seq, cache, new_scores, tok, finished, gen_lens

    if max_new_tokens > 1:
        seq, cache, scores, tok, finished, gen_lens = jax.lax.fori_loop(
            1, max_new_tokens, body,
            (seq, cache, scores, tok, finished, gen_lens))

    final = scores / jnp.power(jnp.maximum(gen_lens, 1.0), length_penalty)
    best = jnp.argmax(final, axis=1)
    return seq[jnp.arange(B), best]
