"""Operations and bytes that a Kimi-Linear configuration REQUIRES (KDA
layers with a recurrent state, latent layers without position encoding,
one leading dense MLP, expert layers of which this chip holds a share),
from sizes alone — never from the implementation: no padding, no
capacity buffers, no gathered copies of the cache, no recomputation, no
chunk transform. Every share built on these is required work over
measured time and cannot pass 100 %.

``a`` is a ``reference_kimi_linear.Arch`` (only its sizes are read).
The expert layer's reckoning — the held experts a step touches under
even routing — is ``work_latent``'s.
"""

from __future__ import annotations

from .work_latent import ITEM, expert_params, experts_touched

STATE_ITEM = 4      # bytes of a float32 state value


def kda_layers(a) -> int:
    return a.layers - len(a.full_layers)


def latent_layers(a) -> int:
    return len(a.full_layers)


def expert_layers(a) -> int:
    return a.layers - a.dense_layers


def kda_params(a) -> int:
    """wq, wk, wv, the three filters, the two low-rank pairs, wb, wo."""
    E, HD, R = a.hidden, a.kda_heads * a.kda_dim, a.kda_rank
    return (3 * E * HD + a.conv * 3 * HD + 2 * (E * R + R * HD)
            + E * a.kda_heads + HD * E)


def mla_params(a) -> int:
    """wq, wkv_a, wkv_b, wo."""
    H, E = a.heads, a.hidden
    return (E * H * (a.nope + a.rope) + E * (a.kv_rank + a.rope)
            + a.kv_rank * H * (a.nope + a.v_dim) + H * a.v_dim * E)


def token_fixed_params(a) -> int:
    """Weights EVERY token multiplies with: every layer's attention, the
    dense MLP, each expert layer's shared expert and router, the head
    (the embedding is a lookup)."""
    return (kda_layers(a) * kda_params(a) + latent_layers(a) * mla_params(a)
            + a.dense_layers * 3 * a.hidden * a.dense_ffn
            + expert_layers(a) * (a.shared * expert_params(a)
                                  + a.hidden * a.experts)
            + a.hidden * a.vocab)


def params_held(a) -> int:
    """Matrices this chip holds: the fixed parts, the held experts, the
    embedding over the vocabulary slice."""
    return (token_fixed_params(a) + a.hidden * a.vocab
            + expert_layers(a) * a.held[1] * expert_params(a))


def state_values(a) -> int:
    """One KDA layer's recurrent state of one stream."""
    return a.kda_heads * a.kda_dim * a.kda_dim


def state_bytes_per_slot(a) -> int:
    """Every KDA layer's float32 state and bf16 convolution tail of one
    stream: what a decode step reads and writes once a slot."""
    tail = (a.conv - 1) * 3 * a.kda_heads * a.kda_dim * ITEM
    return kda_layers(a) * (STATE_ITEM * state_values(a) + tail)


def kv_bytes_per_token(a) -> int:
    """The latent group's row a token: c_kv and the shared key part,
    every latent layer (unpadded)."""
    return ITEM * latent_layers(a) * (a.kv_rank + a.rope)


def state_flops(a, tokens: float) -> float:
    """The recurrence's three products a token a KDA layer: the decay's
    scaling and ``S^T k``, the rank-one update, ``S^T q`` — 6 D^2 a
    head."""
    return 6.0 * state_values(a) * kda_layers(a) * tokens


def attn_context_flops(a, context: float) -> float:
    """Scores and weighted values of one token over ``context``
    positions in every latent layer, in the expanded form."""
    return (2.0 * latent_layers(a) * a.heads
            * ((a.nope + a.rope) + a.v_dim) * context)


def serve_flops(a, start: int, n: int) -> float:
    """Forward of ``n`` tokens at positions ``start..start+n-1`` without
    the routed experts' part (the builder adds 2 x expert_params a held
    pick, from the engine's counters)."""
    ctx = n * start + n * (n + 1) / 2.0
    return (2.0 * token_fixed_params(a) * n + state_flops(a, n)
            + attn_context_flops(a, ctx))


def decode_step_work(a, live_slots: float, context_rows: float) -> dict:
    """One fused decode step of ``live_slots`` streams whose live
    contexts sum to ``context_rows`` cached positions: every weight the
    step must read once (fixed parts, head, the held experts it touches
    under even routing), every live slot's state read AND written, each
    live latent row once in every latent layer, and the FLOPs of
    ``live_slots`` tokens."""
    nbytes = (ITEM * (token_fixed_params(a)
                      + expert_layers(a) * experts_touched(a, live_slots)
                      * expert_params(a))
              + 2 * live_slots * state_bytes_per_slot(a)
              + context_rows * kv_bytes_per_token(a))
    held_picks = (live_slots * a.top_k * a.held[1] / a.experts
                  * expert_layers(a))
    flops = (2.0 * token_fixed_params(a) * live_slots
             + state_flops(a, live_slots)
             + attn_context_flops(a, context_rows)
             + 2.0 * expert_params(a) * held_picks)
    return {"flops": flops, "bytes": nbytes}


def kda_step_work(a, tokens: float) -> dict:
    """``ptpu_kda_step`` over ``tokens`` decode tokens (every KDA layer
    of each): the state read once and written once, 6 D^2 a head."""
    return {"flops": state_flops(a, tokens),
            "bytes": 2.0 * STATE_ITEM * state_values(a) * kda_layers(a)
            * tokens}
