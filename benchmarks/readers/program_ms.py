"""Median device time of one execution of a program, by the name the
trace gives its executions (``XLA Modules`` line of chip 0)."""

import statistics


def durations(ctx, programs):
    return [d for name, ds in ctx["trace"].get("programs", {}).items()
            if name in programs for d in ds]


def read(ctx, programs):
    found = durations(ctx, programs)
    return 1e3 * statistics.median(found) if found else None
