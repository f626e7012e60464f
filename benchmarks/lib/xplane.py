"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
device time by operation and by program, idle gaps named by the programs
around them, and the exposed time of collectives. Read with nothing but
``jax.profiler.ProfileData``.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` holds one event per executed HLO operation (a ``while`` and
other control flow hold their bodies' events inside them: self time is
what is counted) and whose line ``XLA Modules`` holds one event per
executed program. The host's threads are other planes and are not read
here.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all")


def load(path):
    import jax

    return jax.profiler.ProfileData.from_file(str(path))


def program_name(name: str) -> str:
    """``jit_step_fn(123456)`` -> ``jit_step_fn``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


_HLO = re.compile(r"^%?([^ ]+) = \(?(\w+\[[^\]]*\])")


def op_name(name: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction
    (``%fusion.2 = bf16[2,4096]{...} fusion(...)``): keep the
    instruction's name and the first shape it yields."""
    m = _HLO.match(name)
    return f"{m.group(1)}:{m.group(2)}" if m else name


def _events(line) -> list[tuple[float, float, str, object]]:
    return sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name, e)
                   for e in line.events), key=lambda t: (t[0], -t[1]))


def _label(event) -> str:
    """An operation's name with the strings the tracer attached to it
    (a Pallas kernel's own name rides in those, not in the HLO name)."""
    parts = [event.name]
    for key, value in event.stats:
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in intervals:
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _self_times(events):
    """``(start, end, name, event, self_ns)`` with the time of events
    nested inside taken off their holder."""
    out, stack = [], []
    for a, b, name, ev in events:
        while stack and stack[-1][1] <= a:
            stack.pop()
        rec = [a, b, name, ev, b - a]
        if stack:
            stack[-1][4] -= min(b, stack[-1][1]) - a
        stack.append(rec)
        out.append(rec)
    return out


def reduce_plane(plane) -> dict:
    lines = {ln.name: ln for ln in plane.lines}
    ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
    mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
    mod_starts = [m[0] for m in mods]

    def program_at(t: float) -> str:
        i = bisect.bisect_right(mod_starts, t) - 1
        if i >= 0 and t <= mods[i][1]:
            return program_name(mods[i][2])
        return "-"

    by_op: dict = {}          # "program/op" -> seconds of self time
    labels: dict = {}         # "program/op" -> label with tracer strings
    exposed = 0.0
    for a, b, name, ev, self_ns in _self_times(ops):
        key = f"{program_at(a)}/{op_name(name)}"
        by_op[key] = by_op.get(key, 0.0) + self_ns * 1e-9
        if key not in labels:
            labels[key] = _label(ev)
        if COLLECTIVE.search(name):
            exposed += self_ns * 1e-9
    programs: dict = {}
    for a, b, name, _ in mods:
        programs.setdefault(program_name(name), []).append((b - a) * 1e-9)

    # idle gaps of the device, named by the programs on either side
    gaps, end, prev = [], None, "-"
    for a, b, name, _ in ops:
        if end is not None and a > end:
            gaps.append((f"after_{prev}_/_before_{program_at(a)}",
                         (a - end) * 1e-9))
        if end is None or b > end:
            end, prev = b, program_at(a)
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": _union((a, b) for a, b, _, _ in ops) * 1e-9,
            "by_op": by_op, "labels": labels, "programs": programs,
            "gaps": gaps[:10], "collective_exposed_s": exposed,
            "n_ops": len(ops)}


def reduce(profile) -> dict:
    """All device planes. ``busy_s`` is averaged over the chips; the
    rest is chip 0's (every chip of an SPMD program runs the same)."""
    planes = sorted(((int(m.group(1)), p) for p in profile.planes
                     if (m := DEVICE_PLANE.match(p.name))),
                    key=lambda t: t[0])
    if not planes:                      # no chip in this trace
        return {"devices": 0, "busy_s": 0.0, "by_op": {}, "labels": {},
                "programs": {}, "gaps": [], "collective_exposed_s": 0.0,
                "n_ops": 0}
    per = [reduce_plane(p) for _, p in planes]
    first = per[0]
    return {"devices": len(per),
            "busy_s": sum(d["busy_s"] for d in per) / len(per),
            "by_op": first["by_op"], "labels": first["labels"],
            "programs": first["programs"], "gaps": first["gaps"],
            "collective_exposed_s": first["collective_exposed_s"],
            "n_ops": first["n_ops"]}


def kernel_seconds(reduced: dict, patterns) -> float:
    """Self time of the operations whose label holds any of
    ``patterns`` (a Pallas kernel's ``name=``)."""
    return sum(s for key, s in reduced["by_op"].items()
               if any(p in reduced["labels"][key] for p in patterns))


def top_ops(reduced: dict, n: int = 10) -> list:
    return [[k, s] for k, s in sorted(reduced["by_op"].items(),
                                      key=lambda kv: -kv[1])[:n]]
