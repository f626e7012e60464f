"""Milliseconds of the program's own spans (``paddle_tpu.core.trace``:
recorded while the profiler's capture is live, kept in a process-wide
ring after it). Three forms, by the arguments given:

- ``per``: the summed time of the spans named in ``spans``, over the
  count of the span named ``per``;
- ``attr``: the mean of that attribute over the spans that carry it (the
  count goes to stderr);
- neither: the median over groups, a group being one occurrence of each
  name in ``spans``, in the order listed, on one thread (the time of one
  name is its plain median).

``within`` keeps only spans under a recorded span of that name: the
capture starts and ends in the middle of an iteration, and the pieces of
one that was not recorded whole are left out. Nothing is read, and the
reason goes to stderr, where the ring is empty or has evicted spans.
"""

import statistics
import sys


def ring(metric: str):
    """The program's spans, or None where they cannot carry a metric."""
    from paddle_tpu.core import trace

    snap = trace.snapshot()
    if snap.get("dropped"):
        print(f"{metric}: the span ring evicted {snap['dropped']} spans "
              f"(capacity {snap.get('capacity')}): not read",
              file=sys.stderr)
        return None
    if not snap["spans"]:
        print(f"{metric}: the program recorded no span during the "
              "capture: not read", file=sys.stderr)
        return None
    return snap["spans"]


def under(spans, within):
    """The spans that have a recorded ancestor named ``within`` (all of
    them where ``within`` is None)."""
    if within is None:
        return list(spans)
    by_id = {s["span_id"]: s for s in spans}

    def inside(s):
        while (s := by_id.get(s.get("parent_id"))) is not None:
            if s["name"] == within:
                return True
        return False

    return [s for s in spans if inside(s)]


def groups(spans, names):
    """Summed durations of one occurrence of each of ``names`` in the
    order listed, thread by thread in time order; a group that another
    name of the list interrupts is left out."""
    out = []
    threads = {}
    for s in sorted(spans, key=lambda s: s["ts"]):
        if s["name"] in names:
            threads.setdefault(s["tid"], []).append(s)
    for mine in threads.values():
        k, total = 0, 0.0
        for s in mine:
            if s["name"] != names[k]:
                k, total = 0, 0.0
            if s["name"] == names[k]:
                k, total = k + 1, total + s["dur"]
                if k == len(names):
                    out.append(total)
                    k, total = 0, 0.0
    return out


def read(ctx, spans, per=None, attr=None, within=None, metric="span_ms"):
    found = ring(metric)
    if found is None:
        return None
    found = under(found, within)
    mine = [s for s in found if s["name"] in spans]
    if attr is not None:
        values = [s["attrs"][attr] for s in mine if attr in s["attrs"]]
        print(f"{metric}: {len(values)} spans carry {attr}", file=sys.stderr)
        return statistics.fmean(values) if values else None
    if per is not None:
        n = sum(1 for s in found if s["name"] == per)
        return 1e3 * sum(s["dur"] for s in mine) / n if n else None
    sums = groups(mine, spans)
    return 1e3 * statistics.median(sums) if sums else None
