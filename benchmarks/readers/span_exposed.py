"""The time the chip's queue stands empty, as the engine loop itself can
see it, charged to what the host was doing meanwhile.

The loop numbers every compiled program it enqueues (``gen/launch``:
``seq``) and marks each readback with the number it waited for
(``landed``, on ``gen/step_wait``, ``gen/prefill_wait``, ``gen/draft``).
The device runs one stream in order, so a landing proves every launch up
to its number finished. On the loop's thread, on the records' monotonic
clock (``mono``): the queue is **empty** from the end of a landing that
leaves no launch outstanding until the start of the next ``gen/launch``.
That interval is *exposed*; each part of it is charged to the innermost
recorded span that covers it (a span with children is charged its self
time), and the parts inside an ``idle`` span are taken off exposed time
and loop time alike, as ``span_share`` does. Only whole ``loop`` spans
count: the capture starts and ends inside an iteration.

A launch number that was never recorded (enqueued before the capture
began) is still known: numbers are consecutive, so the launch that
follows a landing says how many were enqueued before it.

The exposed share is the lower bound of the device's idle share that the
host can see: the device starts somewhere inside the launch call (the
launches begun on an empty queue are the upper bound's other part), and
a **late landing** — a readback that returned within ``late_ms``, its
result ready before the host asked — says the chip had finished earlier,
by how long the host cannot know. With a later launch in flight behind
it (every drained step of a loop that runs one step ahead) the chip may
have gone idle before that launch got through, and no landing marks it:
where most landings are late the host sets the pace, and the exposed
share says little of the device's idle share.

One number by ``value``; the whole account goes to stderr with
``share``. Nothing is read, and the reason goes to stderr, where the
ring is empty, has evicted spans, or holds records without ``mono`` or
launch marks (a program older than they are).
"""

import bisect
import sys

from .span_ms import ring, under

VALUES = ("share", "launch_share", "ms_per_admission", "ms_per_step",
          "late_landing_share")
BETWEEN = "(between iterations)"


def charge(intervals, spans):
    """``{name: seconds}`` of ``intervals`` (``(start, end)`` pairs) by
    the innermost of ``spans`` (dicts with ``name``, ``mono``, ``dur``;
    properly nested, one thread) covering each part, ``BETWEEN`` where
    none does; and the same seconds as ``(start, end, span)`` pieces."""
    order = sorted(spans, key=lambda s: (s["mono"], -s["dur"]))
    starts = [s["mono"] for s in order]
    longest = max((s["dur"] for s in order), default=0.0)
    by_name, pieces = {}, []
    for a, b in intervals:
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_left(starts, b)
        near = [s for s in order[lo:hi] if s["mono"] + s["dur"] > a]
        cuts = sorted({a, b} | {t for s in near
                                for t in (s["mono"], s["mono"] + s["dur"])
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            inner = None
            for s in near:           # in start order: the last is innermost
                if s["mono"] <= x and s["mono"] + s["dur"] >= y:
                    inner = s
            name = BETWEEN if inner is None else inner["name"]
            by_name[name] = by_name.get(name, 0.0) + (y - x)
            pieces.append((x, y, inner))
    return by_name, pieces


def _thread_account(mine, loops, idle, launch, admit, admitted, step,
                    late_s):
    by_id = {s["span_id"]: s for s in mine}
    t_lo = min(s["mono"] for s in loops)
    t_hi = max(s["mono"] + s["dur"] for s in loops)
    kids = {}
    for s in mine:
        kids[s.get("parent_id")] = kids.get(s.get("parent_id"), 0.0) + s["dur"]

    def loop_of(s):
        while s is not None and s["name"] != loops[0]["name"]:
            s = by_id.get(s.get("parent_id"))
        return s

    events = [(s["mono"], 1, s) for s in mine
              if s["name"] == launch and "seq" in s["attrs"]]
    events += [(s["mono"] + s["dur"], 0, s) for s in mine
               if "landed" in s["attrs"]]
    events.sort(key=lambda e: e[:2])
    following = [None] * len(events)        # the launch after each event
    nxt = None
    for i in range(len(events) - 1, -1, -1):
        following[i] = nxt
        if events[i][1]:
            nxt = events[i][2]
    top, since = -1, None
    exposed, on_empty, landings, late, at_once_any = [], [], 0, 0, 0
    for (t, is_launch, s), after in zip(events, following):
        if is_launch:
            if since is not None:
                a, b = max(since, t_lo), min(t, t_hi)
                if b > a:
                    exposed.append((a, b))
                if t_lo <= t and t + s["dur"] <= t_hi:
                    on_empty.append(s)
                since = None
            continue
        top = max(top, s["attrs"]["landed"])
        # counted inside the whole iterations; what it says about the
        # queue holds wherever it lies (the interval is clipped)
        counted = t_lo <= t <= t_hi
        at_once = (counted and
                   s["dur"] - kids.get(s["span_id"], 0.0) < late_s)
        landings += counted
        at_once_any += at_once
        if after is None or top < after["attrs"]["seq"] - 1:
            continue                         # a later launch is in flight
        if since is None:
            since = t
        late += at_once
    by_name, pieces = charge(exposed, mine)
    idle_s = sum(by_name.pop(name, 0.0) for name in idle)
    per_loop = {}               # (iteration, span name) -> seconds
    for x, y, inner in pieces:
        if inner is not None and inner["name"] in idle:
            continue
        up = loop_of(inner)
        key = (up["span_id"] if up is not None else None,
               BETWEEN if inner is None else inner["name"])
        per_loop[key] = per_loop.get(key, 0.0) + (y - x)
    inside = under(mine, loops[0]["name"])      # in a whole iteration
    admits = [s for s in inside
              if s["name"] == admit and admitted in s["attrs"]]
    steps = sum(1 for s in inside if s["name"] == step)
    admitting = {loop_of(s)["span_id"] for s in admits}
    awake = sum(s["dur"] for s in loops) - sum(
        s["dur"] for s in inside if s["name"] in idle)
    admission_by = {}
    for (it, name), sec in per_loop.items():
        if it in admitting:
            admission_by[name] = admission_by.get(name, 0.0) + sec
    admission_s = sum(admission_by.values())
    return {"loops": len(loops), "awake_s": awake,
            "exposed_s": sum(by_name.values()), "idle_inside_s": idle_s,
            "by_span_s": by_name, "intervals": len(exposed),
            "launch_on_empty_s": sum(s["dur"] for s in on_empty),
            "launches_on_empty": len(on_empty),
            "launches": sum(1 for _, k, _ in events if k),
            "landings": landings, "late_landings": late,
            "at_once_landings": at_once_any,
            "admissions": len(admits), "steps": steps,
            "admission_exposed_s": admission_s,
            "admission_by_span_s": admission_by,
            "step_exposed_s": sum(per_loop.values()) - admission_s}


def account(spans, loop="gen/loop", idle=("gen/idle_wait",),
            launch="gen/launch", admit="gen/admit", admitted="waited_ms",
            step="gen/decode_step", late_ms=0.1):
    """The exposed-time account of every thread that recorded a whole
    ``loop`` span, summed; None where no record can carry it."""
    threads = {}
    for s in spans:
        if "mono" in s:
            threads.setdefault(s["tid"], []).append(s)
    total = None
    for mine in threads.values():
        loops = [s for s in mine if s["name"] == loop]
        if not loops or not any(s["name"] == launch for s in mine):
            continue
        one = _thread_account(mine, loops, tuple(idle), launch, admit,
                              admitted, step, late_ms * 1e-3)
        if total is None:
            total = one
            continue
        for key, v in one.items():
            if key in ("by_span_s", "admission_by_span_s"):
                for name, sec in v.items():
                    total[key][name] = total[key].get(name, 0.0) + sec
            else:
                total[key] += v
    return total


def value_of(acc, value):
    awake = acc["awake_s"]
    if value == "share":
        return 100.0 * acc["exposed_s"] / awake if awake > 0 else None
    if value == "launch_share":
        return (100.0 * acc["launch_on_empty_s"] / awake
                if awake > 0 else None)
    if value == "ms_per_admission":
        return (1e3 * acc["admission_exposed_s"] / acc["admissions"]
                if acc["admissions"] else None)
    if value == "ms_per_step":
        return 1e3 * acc["step_exposed_s"] / acc["steps"] if acc["steps"] \
            else None
    if value == "late_landing_share":
        return (100.0 * acc["at_once_landings"] / acc["landings"]
                if acc["landings"] else None)
    raise ValueError(f"span_exposed: value {value!r} is none of {VALUES}")


def read(ctx, value, metric="span_exposed", late_ms=0.1):
    if value not in VALUES:
        raise ValueError(f"span_exposed: value {value!r} is none of {VALUES}")
    found = ring(metric)
    if found is None:
        return None
    acc = account(found, late_ms=late_ms)
    if acc is None:
        print(f"{metric}: no thread recorded a whole loop span with launch "
              "marks on the monotonic clock: not read", file=sys.stderr)
        return None
    out = value_of(acc, value)
    if value == "share" and out is not None:
        low = out
        high = low + value_of(acc, "launch_share")
        def ms(by):
            return ", ".join(f"{name} {1e3 * sec:.4g}" for name, sec in
                             sorted(by.items(), key=lambda kv: -kv[1]))

        print(f"{metric}: {acc['loops']} loops awake {acc['awake_s']:.6g} s; "
              f"queue empty {acc['intervals']} times for "
              f"{acc['exposed_s']:.6g} s ({acc['idle_inside_s']:.6g} s of "
              f"idle wait taken off); the device's idle share lies between "
              f"{low:.4g} % and {high:.4g} % by the host's account "
              f"({acc['launches_on_empty']} of {acc['launches']} launches "
              f"began on an empty queue, {acc['launch_on_empty_s']:.6g} s); "
              f"{acc['landings']} landings, {acc['at_once_landings']} "
              f"returned at once, {acc['late_landings']} of them onto an "
              f"empty queue; {acc['admissions']} admissions "
              f"{1e3 * acc['admission_exposed_s']:.6g} ms, {acc['steps']} "
              f"steps {1e3 * acc['step_exposed_s']:.6g} ms; exposed ms by "
              f"span: {ms(acc['by_span_s'])}; of the admitting iterations: "
              f"{ms(acc['admission_by_span_s'])}", file=sys.stderr)
    return out
