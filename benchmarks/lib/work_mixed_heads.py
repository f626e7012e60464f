"""Operations and bytes that a Laguna-family configuration REQUIRES
(window and full attention layers with their own query-head counts over
the same KV heads, a leading dense layer, expert layers of which this
chip holds a share, a shared expert), from sizes alone — never from the
implementation: no padding, no capacity buffers, no gathered copies of
the cache, no masked products of other heads, no expert run on a token
that did not pick it. Every share built on these is required work over
measured time and cannot pass 100 %.

``a`` is a ``reference_laguna.Arch`` (only its sizes are read).
"""

from __future__ import annotations

from .work_latent import experts_touched
from .work_window import ITEM, expert_params, kv_pages, seen


def attn_params(a, l: int) -> int:
    """wq, wk, wv, the gate and wo of layer ``l`` (its own query heads)."""
    E, D, H = a.hidden, a.head_dim, a.heads[l]
    return 2 * E * H * D + 2 * E * a.kv_heads * D + E * H


def layer_fixed_params(a, l: int) -> int:
    """What every token of layer ``l`` multiplies with: attention, then
    the dense MLP, or the router over all experts and the shared
    expert."""
    if l < a.dense_layers:
        return attn_params(a, l) + 3 * a.hidden * a.dense_ffn
    return (attn_params(a, l) + a.hidden * a.experts
            + 3 * a.hidden * a.shared_ffn)


def expert_layers(a) -> int:
    return a.layers - a.dense_layers


def params_held(a) -> int:
    """Matrices this chip holds: every layer's fixed part, the held
    experts, the embedding and the head over the vocabulary slice."""
    return (sum(layer_fixed_params(a, l) for l in range(a.layers))
            + expert_layers(a) * a.held[1] * expert_params(a)
            + 2 * a.vocab * a.hidden)


def weight_bytes(a) -> int:
    return ITEM * params_held(a)


def kv_bytes_per_token(a) -> int:
    """K and V of one position, every layer: the same KV heads on both
    kinds."""
    return ITEM * a.layers * 2 * a.kv_heads * a.head_dim


def kv_page_layer_bytes(a, page: int = 16) -> int:
    """One page of one layer, both leaves."""
    return ITEM * 2 * a.kv_heads * page * a.head_dim


def token_fixed_params(a) -> int:
    """Weights EVERY token multiplies with: every layer's fixed part and
    the head (the embedding is a lookup; routed experts are counted by
    the picks that fall here)."""
    return (sum(layer_fixed_params(a, l) for l in range(a.layers))
            + a.hidden * a.vocab)


def kinds(a, windows: bool | None = None) -> list[int]:
    """Layers of the window kind (True), the full kind (False) or both
    (None)."""
    return [l for l in range(a.layers)
            if windows is None or bool(a.windowed[l]) == windows]


def attn_context_flops(a, full_ctx: float, window_ctx: float,
                       windows: bool | None = None) -> float:
    """Scores and weighted values over ``full_ctx`` attended positions
    in each full layer and ``window_ctx`` in each window layer: 2 * 2 *
    Hq * D a position a layer, at each layer's own Hq; ``windows``
    picks the kind (None: both)."""
    return sum(4.0 * a.heads[l] * a.head_dim
               * (window_ctx if a.windowed[l] else full_ctx)
               for l in kinds(a, windows))


def serve_flops(a, start: int, n: int, held_picks: float = 0.0) -> float:
    """Forward of ``n`` tokens at positions ``start..start+n-1``, each
    attending itself and what it sees before it — all on a full layer,
    the window's on a window layer — of which ``held_picks`` routed
    picks fell on experts held here."""
    full = n * start + n * (n + 1) / 2.0
    W = a.window
    below = max(min(start + n, W) - start, 0)       # t + 1 <= W
    win = below * start + below * (below + 1) / 2.0 + (n - below) * W
    return (2.0 * token_fixed_params(a) * n + attn_context_flops(a, full, win)
            + 2.0 * expert_params(a) * held_picks)


def decode_kv_bytes(a, fill: int, page: int = 16,
                    windows: bool | None = None) -> int:
    """Live K/V bytes one decode step reads for one slot behind ``fill``
    cached positions, each live page once a layer: whole pages, both
    leaves, every KV head; ``windows`` picks the kind (None: both)."""
    pages = sum(kv_pages(fill, page, a.window if a.windowed[l] else None)
                for l in kinds(a, windows))
    return pages * kv_page_layer_bytes(a, page)


def decode_attn_flops(a, fill: int, windows: bool | None = None) -> float:
    """The paged decode kernel's required products for one slot: the
    cached positions it sees at each layer's own query heads (the
    step's own token is not the kernel's pages)."""
    return attn_context_flops(a, seen(a, fill, False), seen(a, fill, True),
                              windows)


def decode_step_work(a, live_slots: float, kv_bytes: float,
                     attn_flops: float) -> dict:
    """One fused decode step of ``live_slots`` streams that reads
    ``kv_bytes`` of live K/V (:func:`decode_kv_bytes` summed over the
    slots) and needs ``attn_flops`` of products over it: every weight
    the step must read once (fixed parts, head, the held experts it
    touches under even routing), the live pages once a layer, and the
    FLOPs of ``live_slots`` tokens with their picks that fall here."""
    nbytes = ITEM * (token_fixed_params(a)
                     + expert_layers(a) * experts_touched(a, live_slots)
                     * expert_params(a)) + kv_bytes
    held_picks = (live_slots * a.top_k * a.held[1] / a.experts
                  * expert_layers(a))
    flops = (2.0 * token_fixed_params(a) * live_slots + attn_flops
             + 2.0 * expert_params(a) * held_picks)
    return {"flops": flops, "bytes": nbytes}
