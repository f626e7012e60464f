"""A number the builder counted itself (the program's ``stats()``, the
load generator's clock, ``CompileMeter``, ``memory_stats()``)."""


def read(ctx, key):
    return ctx["counters"].get(key)
