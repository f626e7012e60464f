"""Pallas TPU kernels for the hot-op set (reference: CUDA kernels under
``paddle/fluid/operators/fused/``, ``operators/math/``).

- ``flash_attention`` — fused attention, never materializes [T, T]
  (ref ``fused/multihead_matmul_op.cu``)
- ``rms_norm`` / ``layer_norm`` — fused row norms with saved statistics
  (ref ``layer_norm_op.cu``, ``fused/skip_layernorm_op.cu``)
- ``softmax_cross_entropy`` — fused [N, V] loss, probs never stored
  (ref ``softmax_with_cross_entropy_op.cu``, ``math/softmax.cu``)
- ``fused_linear_cross_entropy`` — LM-head matmul ⊗ xent, the [N, V]
  logits never stored (ref fuses only softmax+xent; this also folds the
  preceding FC — the memory lever at real vocab sizes)
- ``apply_rotary`` — fused RoPE rotation

All kernels run compiled on TPU and interpreted elsewhere
(``_support.interpret()``); all are differentiable via ``jax.custom_vjp``.
"""

from paddle_tpu.ops.pallas import _support
from paddle_tpu.ops.pallas import flash_attention as _fa
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.norm import layer_norm, rms_norm
from paddle_tpu.ops.pallas.rope import apply_rotary
from paddle_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
from paddle_tpu.ops.pallas.linear_xent import (
    chunked_linear_cross_entropy, fused_linear_cross_entropy,
)
from paddle_tpu.ops.pallas.selective_scan import (
    selective_scan, supported as selective_scan_supported,
)

force_interpret = _support.force_interpret
force_dispatch = _support.force_dispatch
on_tpu = _support.on_tpu
dispatch_mode = _support.dispatch_mode


def partition_stats() -> dict:
    """Lowering decisions taken by the multi-chip (shard_map) kernel
    units, keyed ``<unit>:<kernel|fallback>`` — recorded in the
    multichip driver artifact as proof the Pallas path executed under
    sharding."""
    from paddle_tpu.ops.pallas import _partition
    return dict(_partition.stats)


def reset_partition_stats() -> None:
    from paddle_tpu.ops.pallas import _partition
    _partition.reset_stats()


__all__ = [
    "flash_attention", "flash_attention_supported", "rms_norm", "layer_norm",
    "softmax_cross_entropy", "fused_linear_cross_entropy",
    "chunked_linear_cross_entropy", "apply_rotary",
    "selective_scan", "selective_scan_supported",
    "force_interpret", "force_dispatch", "on_tpu", "dispatch_mode",
    "partition_stats", "reset_partition_stats",
]


def flash_attention_supported(q, k, v, *, causal=False) -> bool:
    return _fa.supported(q, k, v, causal=causal)
