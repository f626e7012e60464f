"""The share of a loop's time that the host spends neither waiting for
work nor blocked on the device: over the recorded spans named ``loop``,
100 x (loop - idle - device) / (loop - idle). ``idle`` and ``device``
name spans anywhere under a recorded loop span (a readback is a child of
the step's span, or of the loop itself when it drains a step dispatched
an iteration earlier); one inside another that is already counted is
not counted twice. The device's idle share measures the same thing from
the other side (``busy_s`` over the traced stretch). It differs by what
the device does while the host is still inside the call that started it.
"""

import sys

from .span_ms import ring


def read(ctx, loop, idle, device, metric="span_share"):
    found = ring(metric)
    if found is None:
        return None
    by_id = {s["span_id"]: s for s in found}
    kind = dict.fromkeys(idle, "idle") | dict.fromkeys(device, "device")
    loops = [s["dur"] for s in found if s["name"] == loop]
    waited = {"idle": 0.0, "device": 0.0}
    for s in found:
        if s["name"] not in kind:
            continue
        up = by_id.get(s.get("parent_id"))
        while up is not None and up["name"] != loop \
                and up["name"] not in kind:
            up = by_id.get(up.get("parent_id"))
        if up is not None and up["name"] == loop:
            waited[kind[s["name"]]] += s["dur"]
    total = sum(loops)
    awake = total - waited["idle"]
    if not loops or awake <= 0:
        print(f"{metric}: no whole {loop} span was recorded: not read",
              file=sys.stderr)
        return None
    print(f"{metric}: {len(loops)} x {loop} {total:.6g} s, idle "
          f"{waited['idle']:.6g} s, blocked on the device "
          f"{waited['device']:.6g} s", file=sys.stderr)
    return 100.0 * (awake - waited["device"]) / awake
