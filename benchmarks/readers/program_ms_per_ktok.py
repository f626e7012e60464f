"""Device time of a program per thousand tokens it processed: the mean
device time of its executions in the trace, times the executions the
whole window counted, over the tokens the whole window counted (the
trace has the times, the builder's counters the exact tokens)."""

from .program_ms import durations


def read(ctx, programs, executions, tokens):
    found = durations(ctx, programs)
    n, tok = ctx["counters"].get(executions), ctx["counters"].get(tokens)
    if not found or not n or not tok:
        return None
    return 1e3 * (sum(found) / len(found)) * n / (tok / 1e3)
