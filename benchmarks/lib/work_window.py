"""Operations and bytes that a SmallThinker-family configuration REQUIRES
(full attention layers and sliding-window layers mixed, an expert layer
after each, every expert held here), from sizes alone — never from the
implementation: no padding, no capacity buffers, no gathered copies of
the cache, no expert run on a token that did not pick it. Every share
built on these is required work over measured time and cannot pass
100 %.

``a`` is a ``reference_smallthinker.Arch`` (only its sizes are read).
"""

from __future__ import annotations

ITEM = 2        # bytes of a bf16 weight or cache value


def attn_params(a) -> int:
    """wq, wk, wv, wo of one layer."""
    D = a.head_dim
    return 2 * a.hidden * a.heads * D + 2 * a.hidden * a.kv_heads * D


def expert_params(a) -> int:
    """One expert: gate, up, down."""
    return 3 * a.hidden * a.expert_ffn


def layer_fixed_params(a) -> int:
    """What every token of a layer multiplies with: attention and the
    router over all experts."""
    return attn_params(a) + a.hidden * a.experts


def params_held(a) -> int:
    """Every matrix of the model as it is cut: the layers' fixed parts,
    all their experts, the embedding and the head."""
    return (a.layers * (layer_fixed_params(a) + a.experts * expert_params(a))
            + 2 * a.vocab * a.hidden)


def weight_bytes(a) -> int:
    return ITEM * params_held(a)


def window_layers(a) -> int:
    return sum(a.window_layout)


def kv_bytes_per_token(a) -> int:
    """K and V of one position, every layer."""
    return ITEM * a.layers * 2 * a.kv_heads * a.head_dim


def token_fixed_params(a) -> int:
    """Weights EVERY token multiplies with: every layer's fixed part,
    its ``top_k`` picked experts, and the head (the embedding is a
    lookup)."""
    return (a.layers * (layer_fixed_params(a) + a.top_k * expert_params(a))
            + a.hidden * a.vocab)


def seen(a, context: int, windowed: bool) -> int:
    """Cached positions a query behind ``context`` cached positions
    attends, itself apart: all of them, or the last ``window - 1``."""
    return min(context, a.window - 1) if windowed else context


def attn_context_flops(a, full_ctx: float, window_ctx: float) -> float:
    """Scores and weighted values over ``full_ctx`` attended positions
    in each full layer and ``window_ctx`` in each window layer:
    2 * 2 * H * D a position a layer."""
    per = 4.0 * a.heads * a.head_dim
    w = window_layers(a)
    return per * ((a.layers - w) * full_ctx + w * window_ctx)


def serve_flops(a, start: int, n: int) -> float:
    """Forward of ``n`` tokens at positions ``start..start+n-1``: each
    attends itself and what it sees of the positions before it — all on
    a full layer, the window's on a window layer."""
    full = n * start + n * (n + 1) / 2.0
    W = a.window
    # sum over t of min(t + 1, W) for t in [start, start + n)
    below = max(min(start + n, W) - start, 0)       # t + 1 <= W
    win = below * start + below * (below + 1) / 2.0 + (n - below) * W
    return (2.0 * token_fixed_params(a) * n
            + attn_context_flops(a, full, win))


def kv_pages(fill: int, page: int, window: int | None = None) -> int:
    """Pages of ``page`` tokens holding the cached positions a decode
    step behind ``fill`` cached positions reads: all of them, or those
    from position ``fill - window + 1`` on."""
    if fill <= 0:
        return 0
    lo = 0 if window is None else max(fill - window + 1, 0)
    return (fill - 1) // page - lo // page + 1


def decode_kv_bytes(a, fill: int, page: int = 16) -> int:
    """Live K/V bytes one decode step reads for one slot behind ``fill``
    cached positions, each live page once a layer: whole pages, both
    leaves, every KV head."""
    w = window_layers(a)
    pages = ((a.layers - w) * kv_pages(fill, page)
             + w * kv_pages(fill, page, a.window))
    return pages * 2 * a.kv_heads * page * a.head_dim * ITEM


def decode_attn_flops(a, fill: int) -> float:
    """The paged decode kernel's required products for one slot: the
    cached positions it sees (the step's own token is not the
    kernel's pages)."""
    return attn_context_flops(a, seen(a, fill, False), seen(a, fill, True))


def experts_touched(a, live_tokens: float) -> float:
    """Experts a step of ``live_tokens`` tokens reads under even
    routing, per layer: X * (1 - (1 - k/X)^tokens)."""
    return a.experts * (1.0 - (1.0 - a.top_k / a.experts) ** live_tokens)


def decode_step_work(a, live_slots: float, kv_bytes: float,
                     attn_flops: float) -> dict:
    """One fused decode step of ``live_slots`` streams that reads
    ``kv_bytes`` of live K/V (:func:`decode_kv_bytes` summed over the
    slots): every weight the step must read once (fixed parts, head,
    the experts it touches under even routing), the live pages once a
    layer, and the FLOPs of ``live_slots`` tokens."""
    fixed = a.layers * layer_fixed_params(a) + a.hidden * a.vocab
    nbytes = ITEM * (fixed + a.layers * experts_touched(a, live_slots)
                     * expert_params(a)) + kv_bytes
    flops = 2.0 * token_fixed_params(a) * live_slots + attn_flops
    return {"flops": flops, "bytes": nbytes}
