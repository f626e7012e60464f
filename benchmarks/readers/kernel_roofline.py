"""A kernel family's share of its roofline: the least time the chip
could take for the work the algorithm requires (the larger of operations
over peak FLOP/s and bytes over peak HBM bytes/s, ``lib.flops``), over
the device time of the family's kernels in the trace. Work stated per
step is multiplied by the executions of the step program in the trace."""

import sys

from ..lib import flops, xplane
from .program_ms import durations


def read(ctx, kernels, work):
    w = ctx.get("kernel_work", {}).get(work)
    seconds = xplane.kernel_seconds(ctx["trace"], kernels)
    if not w or seconds <= 0:
        return None
    times = (len(durations(ctx, w["per_execution_of"]))
             if w.get("per_execution_of") else 1)
    if not times:
        return None
    floor, bound = flops.roofline_floor_s(
        w["flops"] * times, w["bytes"] * times, *ctx["peaks"])
    print(f"roofline {work}: {bound}-bound, floor {floor:.6g} s over "
          f"{seconds:.6g} s of {kernels}", file=sys.stderr)
    return 100.0 * floor / seconds
