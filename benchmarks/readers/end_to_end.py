"""An end-to-end quantity reported among the per-layer metrics (a tail
too unsteady at this cell's request count to carry a bound)."""


def read(ctx, key):
    return ctx["end_to_end"].get(key)
