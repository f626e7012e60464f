"""Operations and bytes that the algorithm REQUIRES, from shapes alone —
never from the implementation, so recomputation (remat, flash's second
pass over the scores, the fused head's rebuilt logits) and padding
(capacity-padded expert buffers, padded caches, masked attention halves)
do not count. Every share built on these is required work over measured
time and cannot pass 100 %.

``a`` is a ``reference.Arch`` (only its sizes are read).
"""

from __future__ import annotations


def matmul_params_active(a) -> int:
    """Weights a token multiplies with: attention projections, the MLP
    (``top_k`` experts and the router for a routed layer) and the output
    head. The embedding is a lookup and the norms are vectors."""
    kv = a.kv_heads * a.head_dim
    attn = 2 * a.hidden * a.hidden + 2 * a.hidden * kv
    if a.experts:
        mlp = a.top_k * 3 * a.hidden * a.ffn + a.hidden * a.experts
    else:
        mlp = 3 * a.hidden * a.ffn
    return a.layers * (attn + mlp) + a.hidden * a.vocab


def train_flops_per_token(a, seq: int) -> float:
    """Forward + backward: 6 per active weight, and causal attention —
    per layer and token QK^T and PV over on average seq/2 keys, twice
    that again backward: 6 * L * E * T. (The usual 12*L*E*T counts the
    masked half too; it is not required work.)"""
    return 6.0 * matmul_params_active(a) + 6.0 * a.layers * a.hidden * seq


def serve_flops(a, start: int, n: int) -> float:
    """Forward of ``n`` tokens at positions ``start..start+n-1``, each
    attending the live context up to itself: 2 per active weight and
    4 * L * E * (position + 1)."""
    ctx = n * start + n * (n + 1) / 2.0
    return 2.0 * matmul_params_active(a) * n + 4.0 * a.layers * a.hidden * ctx


def flash_train_flops(a, rows: int, seq: int) -> float:
    """Causal attention forward (2 matmuls) and backward (4), every
    layer: 6 * T^2 * E per row and layer."""
    return 6.0 * seq * seq * a.hidden * a.layers * rows


def linear_xent_flops(a, tokens: int) -> float:
    """The head's logits forward and its two gradients: 3 matmuls of
    2 * tokens * E * V."""
    return 6.0 * tokens * a.hidden * a.vocab


def linear_xent_bytes(a, tokens: int, itemsize: int = 2) -> float:
    """Least traffic: the head read for each of the three passes and
    its gradient written once, the hidden states read and their gradient
    written."""
    return itemsize * (4.0 * a.hidden * a.vocab + 4.0 * tokens * a.hidden)


def flash_train_bytes(a, rows: int, seq: int, itemsize: int = 2) -> float:
    """q, k, v, o and their gradients, once each way."""
    kv = a.kv_heads * a.head_dim
    per = 2 * a.hidden + 2 * kv
    return itemsize * 3.0 * per * seq * rows * a.layers


def train_kernel_work(a, rows_per_chip: int, seq: int) -> dict:
    """Per step and chip, by kernel family."""
    tokens = rows_per_chip * seq
    return {
        "flash_attn": {"flops": flash_train_flops(a, rows_per_chip, seq),
                       "bytes": flash_train_bytes(a, rows_per_chip, seq)},
        "linear_xent": {"flops": linear_xent_flops(a, tokens),
                        "bytes": linear_xent_bytes(a, tokens)},
    }


def roofline_floor_s(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tf, tb = flops / peak_flops, nbytes / peak_bw
    return (tf, "compute") if tf >= tb else (tb, "memory")
