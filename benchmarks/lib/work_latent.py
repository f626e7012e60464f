"""Operations and bytes that a DeepSeek-V3-family configuration REQUIRES
(latent attention, leading dense layers, expert layers of which this
chip holds a share), from sizes alone — never from the implementation:
no padding, no capacity buffers, no gathered copies of the cache, no
recomputation. Every share built on these is required work over measured
time and cannot pass 100 %.

``a`` is a ``reference_latent.Arch`` (only its sizes are read).
"""

from __future__ import annotations

ITEM = 2        # bytes of a bf16 weight or cache value


def attn_params(a) -> int:
    """wq_a, wq_b, wkv_a, wkv_b, wo of one layer."""
    H, E = a.heads, a.hidden
    return (E * a.q_rank + a.q_rank * H * (a.nope + a.rope)
            + E * (a.kv_rank + a.rope) + a.kv_rank * H * (a.nope + a.v_dim)
            + H * a.v_dim * E)


def expert_params(a) -> int:
    """One routed expert (a shared expert is the same SwiGLU)."""
    return 3 * a.hidden * a.expert_ffn


def dense_layer_params(a) -> int:
    return attn_params(a) + 3 * a.hidden * a.dense_ffn


def expert_layer_fixed_params(a) -> int:
    """What every token of an expert layer multiplies with: attention,
    the shared expert(s), the router over all experts."""
    return (attn_params(a) + a.shared * expert_params(a)
            + a.hidden * a.experts)


def expert_layers(a) -> int:
    return a.layers - a.dense_layers


def params_held(a) -> int:
    """Matrices this chip holds: every layer's fixed part, the held
    experts, the embedding and the head over the vocabulary slice."""
    return (a.dense_layers * dense_layer_params(a)
            + expert_layers(a) * (expert_layer_fixed_params(a)
                                  + a.held[1] * expert_params(a))
            + 2 * a.vocab * a.hidden)


def weight_bytes(a) -> int:
    return ITEM * params_held(a)


def kv_bytes_per_token(a) -> int:
    """The latent cache: c_kv and the shared rope key, every layer."""
    return ITEM * a.layers * (a.kv_rank + a.rope)


def token_fixed_params(a) -> int:
    """Weights EVERY token multiplies with: both stacks' fixed parts and
    the head (the embedding is a lookup)."""
    return (a.dense_layers * dense_layer_params(a)
            + expert_layers(a) * expert_layer_fixed_params(a)
            + a.hidden * a.vocab)


def attn_context_flops(a, context: float) -> float:
    """Scores and weighted values of one token over ``context``
    positions in every layer, in the expanded form (the least any form
    needs): 2 * 2 * H * (N + R) * context a layer; V is no wider than
    N + R in this family, so the sum is bounded by it."""
    return 2.0 * a.layers * a.heads * ((a.nope + a.rope) + a.v_dim) * context


def serve_flops(a, start: int, n: int, held_picks: float) -> float:
    """Forward of ``n`` tokens at positions ``start..start+n-1``, each
    attending the live context up to itself, of which ``held_picks``
    routed picks fell on experts held here."""
    ctx = n * start + n * (n + 1) / 2.0
    return (2.0 * token_fixed_params(a) * n + attn_context_flops(a, ctx)
            + 2.0 * expert_params(a) * held_picks)


def experts_touched(a, live_tokens: float) -> float:
    """Held experts a step of ``live_tokens`` tokens reads under even
    routing, per expert layer: held * (1 - (1 - k/X)^tokens)."""
    return a.held[1] * (1.0 - (1.0 - a.top_k / a.experts) ** live_tokens)


def decode_step_work(a, live_slots: float, context_rows: float) -> dict:
    """One fused decode step of ``live_slots`` streams whose live
    contexts sum to ``context_rows`` cached positions: every weight the
    step must read once (fixed parts, head, the held experts it touches
    under even routing), each live latent row once in every layer, and
    the FLOPs of ``live_slots`` tokens."""
    nbytes = ITEM * (token_fixed_params(a)
                     + expert_layers(a) * experts_touched(a, live_slots)
                     * expert_params(a)) \
        + context_rows * kv_bytes_per_token(a)
    held_picks = (live_slots * a.top_k * a.held[1] / a.experts
                  * expert_layers(a))
    flops = (2.0 * token_fixed_params(a) * live_slots
             + attn_context_flops(a, context_rows)
             + 2.0 * expert_params(a) * held_picks)
    return {"flops": flops, "bytes": nbytes}
