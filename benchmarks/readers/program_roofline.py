"""A whole compiled program's share of its roofline: the least time the
chip could take for the work ONE execution requires (the larger of
operations over peak FLOP/s and bytes over peak HBM bytes/s), over the
median device time of the program's executions in the trace. The
builder states the work under ``kernel_work[work]``: a dict of ``flops``
and ``bytes`` an execution, or a function of the number of executions
in the trace that returns one (work that depends on what the traced
stretch held, averaged over its executions)."""

import statistics
import sys

from ..lib import flops
from .program_ms import durations


def read(ctx, programs, work):
    w = ctx.get("kernel_work", {}).get(work)
    found = durations(ctx, programs)
    if not w or not found:
        return None
    if callable(w):
        w = w(len(found))
    seconds = statistics.median(found)
    floor, bound = flops.roofline_floor_s(w["flops"], w["bytes"],
                                          *ctx["peaks"])
    print(f"roofline {work}: {bound}-bound, floor {floor:.6g} s over "
          f"{seconds:.6g} s median of {len(found)} {programs}",
          file=sys.stderr)
    return 100.0 * floor / seconds
