"""State tape: jit-safe functional updates for stateful layers (BatchNorm).

The reference mutates running statistics in-place inside the CUDA batch-norm
kernel (reference ``operators/batch_norm_op.cu``, in/out MeanOut/VarianceOut
share buffers with the inputs). A functional framework can't mutate, so:
stateful layers carry a unique static ``_uid`` and, during a training-mode
forward, record their new statistics on an ambient *tape*; the trainer (all
inside the same jit trace) merges the tape back into the model pytree:

    with state_tape() as tape:
        y = model(x, training=True)
    model = merge_state(model, tape)
"""

from __future__ import annotations

import contextlib
import itertools
import threading
from contextvars import ContextVar

from paddle_tpu.core.module import Module

_uid_counter = itertools.count()
_uid_lock = threading.Lock()

_tape_var: ContextVar[dict | None] = ContextVar("ptpu_state_tape", default=None)


def new_uid() -> int:
    with _uid_lock:
        return next(_uid_counter)


@contextlib.contextmanager
def state_tape():
    tape: dict[int, dict] = {}
    token = _tape_var.set(tape)
    try:
        yield tape
    finally:
        _tape_var.reset(token)


def tape_call(fn, *args, **kwargs):
    """Run ``fn`` under a fresh tape and return ``(result, tape_dict)``
    — the shared per-layer step for scan-based executors (ScannedBlocks
    and both pipeline schedules): state updates ride out of the scan as
    outputs instead of leaking scan-body tracers onto an ambient tape."""
    with state_tape() as t:
        y = fn(*args, **kwargs)
    return y, dict(t)


def record_state(uid: int, **updates) -> bool:
    """Record new state arrays for the module with the given uid. Returns
    False if no tape is active (eval mode / user skipped the tape)."""
    tape = _tape_var.get()
    if tape is None:
        return False
    tape[uid] = updates
    return True


# Reserved tape entry name for per-layer auxiliary LOSS contributions
# (MoE load-balancing). Unlike BatchNorm statistics, these are
# differentiable loss terms: they ride the same per-layer tape through
# every scan-based executor (ScannedBlocks, GPipe ticks, 1F1B ticks —
# which seeds their cotangent in its manual backward), are summed by
# ``collect_aux`` into the training loss, and are NEVER merged back into
# module state (``merge_state`` skips them).
AUX_LOSS_KEY = "aux_loss"


def record_aux(uid: int, value) -> bool:
    """Record a pre-scaled auxiliary loss contribution: ``value`` must
    already carry its loss weight and 1/num_layers factor so that
    ``loss = main + collect_aux(tape)`` holds under every executor."""
    tape = _tape_var.get()
    if tape is None:
        return False
    tape.setdefault(uid, {})[AUX_LOSS_KEY] = value
    return True


def collect_aux(tape: dict):
    """Sum every ``AUX_LOSS_KEY`` entry on the tape (leaves may be
    layer-stacked [L, ...] — summed) into one scalar loss term."""
    import jax.numpy as jnp

    total = jnp.zeros((), jnp.float32)
    for updates in tape.values():
        if AUX_LOSS_KEY in updates:
            total = total + jnp.sum(
                updates[AUX_LOSS_KEY].astype(jnp.float32))
    return total


# Reserved tape entry name for per-position COUNTS a layer makes of its
# own live work (an expert layer's routed picks). Like the aux loss they
# ride the per-layer tape out of a scan and are never merged into module
# state; whoever knows which positions are live (the serving engine:
# active slots, unpadded prefill positions) sums them.
COUNT_KEY = "live_counts"


def record_count(uid: int, **counts) -> bool:
    """Record integer count arrays (one value a position, ``[B, T]``)
    under ``COUNT_KEY``. False with no tape active: nothing is kept and
    XLA drops the arithmetic."""
    tape = _tape_var.get()
    if tape is None:
        return False
    tape.setdefault(uid, {})[COUNT_KEY] = counts
    return True


def collect_counts(tape: dict) -> dict:
    """Sum every ``COUNT_KEY`` entry on the tape by name over modules
    and over leading layer axes (leaves may be ``[L, B, T]``): one
    ``[B, T]`` int32 array a name."""
    import jax.numpy as jnp

    out: dict = {}
    for updates in tape.values():
        for name, v in updates.get(COUNT_KEY, {}).items():
            v = jnp.sum(v.reshape((-1,) + v.shape[-2:]), axis=0)
            out[name] = out[name] + v if name in out else v
    return out


def map_modules(fn, tree):
    """Bottom-up map over every Module in a pytree (children first)."""

    def rec(obj):
        if isinstance(obj, Module):
            changes = {}
            for name, value in list(obj.__dict__.items()):
                new = rec(value)
                if new is not value:
                    changes[name] = new
            out = obj.replace(**changes) if changes else obj
            return fn(out)
        if isinstance(obj, (list, tuple)):
            vals = [rec(v) for v in obj]
            if all(a is b for a, b in zip(vals, obj)):
                return obj
            return type(obj)(vals)
        if isinstance(obj, dict):
            vals = {k: rec(v) for k, v in obj.items()}
            if all(vals[k] is obj[k] for k in obj):
                return obj
            return vals
        return obj

    return rec(tree)


def merge_state(model, tape: dict):
    """Return a copy of ``model`` with taped state merged in (matched by
    each stateful module's static ``_uid``). New state is cast to the
    dtype the module currently stores — under AMP the forward records
    compute-dtype (bf16) statistics, but the master buffers (and the
    TrainState layout jit donation depends on) stay in their storage
    dtype."""
    if not tape:
        return model

    def fn(m):
        uid = getattr(m, "_uid", None)
        if uid is not None and uid in tape:
            updates = {}
            for k, v in tape[uid].items():
                if k in (AUX_LOSS_KEY, COUNT_KEY):
                    # loss contribution / live counts, not module state
                    continue
                cur = getattr(m, k, None)
                if (hasattr(v, "astype") and hasattr(cur, "dtype")
                        and v.dtype != cur.dtype):
                    v = v.astype(cur.dtype)
                updates[k] = v
            return m.replace(**updates) if updates else m
        return m

    return map_modules(fn, model)
