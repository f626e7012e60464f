"""Operations and bytes that an SDAR-family configuration REQUIRES when
it is served by block diffusion (block-causal GQA with head-wise q/k
norm, an expert layer after each, every expert held here), from sizes
alone — never from the implementation: no padding, no gathered copies of
the cache, no expert run on a token that did not pick it, no pool page
read twice. Every share built on these is required work over measured
time and cannot pass 100 %.

A block of B positions costs ``steps`` denoising forwards of its B rows
and one commit forward, so a served token costs ``steps + 1`` forwards
of a position. ``a`` is a ``reference_sdar.Arch`` (only its sizes are
read).
"""

from __future__ import annotations

ITEM = 2        # bytes of a bf16 weight or cache value


def attn_params(a) -> int:
    """wq, wk, wv, wo of one layer (the q/k norms are vectors)."""
    D = a.head_dim
    return 2 * a.hidden * a.heads * D + 2 * a.hidden * a.kv_heads * D


def expert_params(a) -> int:
    """One expert: gate, up, down."""
    return 3 * a.hidden * a.expert_ffn


def layer_fixed_params(a) -> int:
    """What every position of a layer multiplies with: attention and
    the router over all experts."""
    return attn_params(a) + a.hidden * a.experts


def params_held(a) -> int:
    """Every matrix of the model as it is cut: the layers' fixed parts,
    all their experts, the embedding and the head."""
    return (a.layers * (layer_fixed_params(a) + a.experts * expert_params(a))
            + 2 * a.vocab * a.hidden)


def token_fixed_params(a) -> int:
    """Weights EVERY forwarded position multiplies with: every layer's
    fixed part, its ``top_k`` picked experts, and the head (the
    embedding is a lookup)."""
    return (a.layers * (layer_fixed_params(a) + a.top_k * expert_params(a))
            + a.hidden * a.vocab)


def kv_bytes_per_token(a) -> int:
    """K and V of one position, every layer."""
    return ITEM * a.layers * 2 * a.kv_heads * a.head_dim


def attn_flops(a, queries: float, keys: float) -> float:
    """Scores and weighted values of ``queries`` rows over ``keys``
    positions each, every layer: 2 * 2 * H * D a pair."""
    return 4.0 * a.layers * a.heads * a.head_dim * queries * keys


def serve_flops(a, start: int, n: int) -> float:
    """A prefill of ``n`` positions at ``start..start+n-1``: each
    position attends what lies before it and itself (the block's later
    rows it also sees are not counted: a lower bound)."""
    return (2.0 * token_fixed_params(a) * n
            + attn_flops(a, 1, n * start + n * (n + 1) / 2.0))


def output_token_flops(a, first: int) -> float:
    """One served token of a block that starts at ``first``: ``steps +
    1`` forwards of a position, each attending the context before the
    block and the block's B rows."""
    return (a.steps + 1) * (2.0 * token_fixed_params(a)
                            + attn_flops(a, 1, first + a.block))


def context_pages(first: int, page: int) -> int:
    """Pages holding the positions before a block at ``first``."""
    return -(-first // page)


def block_kv_bytes(a, first: int, page: int = 16) -> int:
    """What one slot's block step at ``first`` reads of K/V: the live
    pages before the block once a layer, both leaves, every KV head, and
    the block's own B rows."""
    rows = context_pages(first, page) * page + a.block
    return rows * kv_bytes_per_token(a)


def block_attn_flops(a, first: int) -> float:
    """The block kernel's required products for one slot: B query rows
    over the context and the block's own rows."""
    return attn_flops(a, a.block, first + a.block)


def experts_touched(a, positions: float) -> float:
    """Experts a step of ``positions`` forwarded positions reads under
    even routing, per layer: X * (1 - (1 - k/X)^positions)."""
    return a.experts * (1.0 - (1.0 - a.top_k / a.experts) ** positions)


def block_step_work(a, live_slots: float, kv_bytes: float,
                    attn: float) -> dict:
    """One block step of ``live_slots`` slots of B positions each, which
    reads ``kv_bytes`` of live K/V (:func:`block_kv_bytes` summed over
    the slots) and does ``attn`` attention products: every weight the
    step must read once (fixed parts, head, the experts its positions
    touch under even routing), the live K/V once, and the FLOPs of
    ``B * live_slots`` positions."""
    positions = a.block * live_slots
    fixed = a.layers * layer_fixed_params(a) + a.hidden * a.vocab
    nbytes = ITEM * (fixed + a.layers * experts_touched(a, positions)
                     * expert_params(a)) + kv_bytes
    return {"flops": 2.0 * token_fixed_params(a) * positions + attn,
            "bytes": nbytes}
