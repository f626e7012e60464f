"""The share of a span's wall time that its thread spent off the CPU:
100 x sum(dur - cpu) / sum(dur) over the recorded spans named ``span``
(``within``: only those under a recorded span of that name). A record's
``cpu`` is its thread's own CPU time between the span's two clock reads
(``paddle_tpu.core.trace``): what is left of ``dur`` the thread spent
waiting — for a lock, for the interpreter lock under other threads, or
inside a call that blocks — which a wall clock cannot tell from work.

``child`` names a span directly under ``span`` (the launch inside a
dispatch): stderr then gives the same share, and the median
milliseconds, for the children and for what is left of the parents (the
operand staging). Where the thread's CPU clock moves in coarse steps
(10 ms on the machines with the chip: a record's ``cpu`` is then 0 or a
whole step) only a sum over many spans means anything: stderr gives the
step and how many of them the sum holds, and a share of ``n`` steps is
good to about ``100 / sqrt(n)`` % of itself. Nothing is read, and the reason goes to stderr, where
the ring is empty, has evicted spans, or holds records without ``cpu``
(a program older than it).
"""

import statistics
import sys

from .span_ms import ring, under


def _off(durs, cpus):
    total = sum(durs)
    return 100.0 * (total - sum(cpus)) / total if total > 0 else None


def split(spans, span, child=None):
    """``{part: (n, off-CPU %, median ms)}`` for the spans named
    ``span``, and with ``child`` for the children and the remainder."""
    mine = [s for s in spans if s["name"] == span and "cpu" in s]
    if not mine:
        return None
    out = {span: (len(mine), _off([s["dur"] for s in mine],
                                  [s["cpu"] for s in mine]),
                  1e3 * statistics.median(s["dur"] for s in mine))}
    if child is None:
        return out
    ids = {s["span_id"] for s in mine}
    inner = {}
    for s in spans:
        if s["name"] == child and s.get("parent_id") in ids and "cpu" in s:
            d, c = inner.get(s["parent_id"], (0.0, 0.0))
            inner[s["parent_id"]] = d + s["dur"], c + s["cpu"]
    if inner:
        kid = list(inner.values())
        rest = [(s["dur"] - inner[s["span_id"]][0],
                 s["cpu"] - inner[s["span_id"]][1])
                for s in mine if s["span_id"] in inner]
        for name, pairs in ((child, kid), (f"{span} less {child}", rest)):
            durs, cpus = zip(*pairs)
            out[name] = (len(pairs), _off(durs, cpus),
                         1e3 * statistics.median(durs))
    return out


def read(ctx, span, within=None, child=None, metric="span_offcpu"):
    found = ring(metric)
    if found is None:
        return None
    found = under(found, within)
    parts = split(found, span, child)
    if parts is None:
        print(f"{metric}: no recorded {span} span carries cpu: not read",
              file=sys.stderr)
        return None
    line = "; ".join(
        f"{n} x {name} off the CPU {off:.4g} % of a median {ms:.4g} ms"
        for name, (n, off, ms) in parts.items() if off is not None)
    steps = [s["cpu"] for s in found if s.get("cpu")]
    if steps:
        total = sum(s["cpu"] for s in found if s["name"] == span)
        line += (f"; the CPU clock's smallest step under {within} is "
                 f"{1e3 * min(steps):.4g} ms, {total / min(steps):.0f} of "
                 f"them in the {span} spans")
    print(f"{metric}: {line}", file=sys.stderr)
    return parts[span][1]
