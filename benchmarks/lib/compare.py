"""The comparison that decides ``correct``: each number beside its
limit. Training numbers follow the contract's measure — the gap between
the program's norm and the reference's (not the norm of their
difference), by the worst leaf, against the reference's norm of that
leaf or of the median leaf, whichever is larger."""

from __future__ import annotations

import statistics


def rel_gap(got: float, ref: float) -> float:
    return abs(got - ref) / max(abs(ref), 1e-30)


def worst_leaf(got: dict, ref: dict, leaves=None) -> tuple[float, str]:
    leaves = list(ref if leaves is None else leaves)
    if not leaves:
        return 0.0, "-"
    med = statistics.median(ref[n] for n in leaves)
    gaps = {n: abs(got[n] - ref[n]) / max(ref[n], med, 1e-30)
            for n in leaves}
    name = max(gaps, key=gaps.get)
    return gaps[name], name


def moved_leaves(ref_grad: dict) -> list[str]:
    """Leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's (a rule on the gradient,
    not on names)."""
    med = statistics.median(ref_grad.values())
    return [n for n, g in ref_grad.items() if g >= 1e-3 * med]


def train_numbers(got: dict, ref: dict, limits: dict) -> list[tuple]:
    """``got``/``ref``: ``{"loss": [...], "grad_norm": {leaf: x},
    "change_norm": {leaf: x}}``. Returns ``(name, value, limit, note)``
    rows."""
    rows = []
    if "loss_gap" in limits:     # compared only where it has a limit
        for i, (a, b) in enumerate(zip(got["loss"], ref["loss"]), 1):
            rows.append((f"loss_gap_step{i}", rel_gap(a, b),
                         limits["loss_gap"], f"{a:.6f} vs {b:.6f}"))
    g, leaf = worst_leaf(got["grad_norm"], ref["grad_norm"])
    rows.append(("grad_norm_gap", g, limits["grad_norm_gap"], leaf))
    c, leaf = worst_leaf(got["change_norm"], ref["change_norm"],
                         moved_leaves(ref["grad_norm"]))
    rows.append(("change_norm_gap", c, limits["change_norm_gap"], leaf))
    return rows


def verdict(rows) -> bool:
    return all(v == v and v <= lim for _, v, lim, _ in rows)
