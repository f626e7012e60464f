"""FLOPs counting (reference ``python/paddle/hapi/dynamic_flops.py`` /
``static_flops.py``: per-layer hook-based multiply-add counters walking
the program).

TPU-native: XLA already computes an exact cost model for every compiled
executable — ``flops()`` compiles the forward and reads
``cost_analysis()['flops']``, which covers *every* op (fused, custom,
attention) rather than the hook-covered subset the reference counts.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

__all__ = ["flops"]


def flops(model_or_fn: Callable, *example_inputs: Any,
          per_sample: bool = False) -> int:
    """Analytical FLOPs of one forward pass at the example shapes."""
    fn = model_or_fn
    compiled = jax.jit(lambda *xs: fn(*xs)).lower(*example_inputs).compile()
    total = int(compiled.cost_analysis().get("flops", 0))
    if per_sample:
        batch = example_inputs[0].shape[0]
        return total // max(batch, 1)
    return total


def summary(model, example_inputs=None) -> str:
    """Parameter table by module path (reference ``paddle.summary`` /
    ``hapi/model_summary.py``); returns the printed string."""
    import numpy as np

    from paddle_tpu.core.module import named_parameters

    rows = []
    total = 0
    trainable = 0
    from paddle_tpu.core.module import trainable_mask
    import jax

    mask_leaves = jax.tree_util.tree_leaves(trainable_mask(model))
    for (name, p), is_train in zip(named_parameters(model), mask_leaves):
        n = int(np.prod(p.shape)) if hasattr(p, "shape") else 1
        total += n
        if is_train:
            trainable += n
        rows.append((name, tuple(getattr(p, "shape", ())),
                     str(getattr(p, "dtype", "-")), n))
    w = max((len(r[0]) for r in rows), default=10) + 2
    lines = [f"{'Param':<{w}}{'Shape':<20}{'Dtype':<10}{'Count':>12}",
             "-" * (w + 42)]
    for name, shape, dtype, n in rows:
        lines.append(f"{name:<{w}}{str(shape):<20}{dtype:<10}{n:>12,}")
    lines.append("-" * (w + 42))
    lines.append(f"Total params: {total:,}  "
                 f"(trainable {trainable:,}, buffers {total - trainable:,})")
    if example_inputs is not None:
        lines.append(f"Forward FLOPs: {flops(model, *example_inputs):,}")
    out = "\n".join(lines)
    print(out)
    return out
