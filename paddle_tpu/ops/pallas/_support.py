"""Shared dispatch helpers for the Pallas kernel set.

Kernels compile only for the TPU backend; on CPU they run through the
Pallas interpreter (bit-accurate, slow) — used by the OpTest-style unit
tests. The ``interpret()`` switch below decides per-call.
"""

from __future__ import annotations

import jax

_FORCE_INTERPRET = False
_FORCE_DISPATCH = False


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def single_device() -> bool:
    """True when no multi-device mesh is active."""
    from paddle_tpu.parallel import mesh as M

    mesh = M.current_mesh()
    return mesh is None or mesh.size <= 1


def _manual_axes():
    """(any_manual, all_manual) over the ambient abstract mesh axes."""
    am = jax.sharding.get_abstract_mesh()
    if not am.shape:
        return False, False
    manual = [t == jax.sharding.AxisType.Manual for t in am.axis_types]
    return any(manual), all(manual)


def dispatch_mode() -> str:
    """How the kernel set should dispatch at this trace point.

    - ``"off"`` — stay on the jnp path (not on TPU, or inside a
      partially-manual shard_map where neither raw local shapes nor a
      nested shard_map unit are safe).
    - ``"raw"`` — call pallas directly: single-device jit, or inside a
      fully-manual shard_map where shapes are already per-device (the
      Ulysses local-attention case).
    - ``"partitioned"`` — multi-device mesh under the automatic
      partitioner: route through the shard_map units
      (``ops/pallas/_partition.py``) so the kernel runs per shard. This
      is what the reference gets from launching its fused CUDA kernels
      per device under ``framework/parallel_executor.cc:504``.
    """
    if not (on_tpu() or _FORCE_DISPATCH):
        return "off"
    any_manual, all_manual = _manual_axes()
    if any_manual:
        return "raw" if all_manual else "off"
    return "raw" if single_device() else "partitioned"


def interpret() -> bool:
    """Whether pallas_call should run in interpreter mode."""
    return _FORCE_INTERPRET or not on_tpu()


def compiler_params(**kwargs):
    """TPU compiler params, or None off-TPU/interpret (ignored there)."""
    if interpret():
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


class force_interpret:
    """Context manager: run all paddle_tpu Pallas kernels interpreted."""

    def __enter__(self):
        global _FORCE_INTERPRET
        self._prev = _FORCE_INTERPRET
        _FORCE_INTERPRET = True

    def __exit__(self, *exc):
        global _FORCE_INTERPRET
        _FORCE_INTERPRET = self._prev
        return False


class force_dispatch:
    """Context manager: dispatch the kernel set even off-TPU (interpreted)
    — used by the virtual-mesh tests and the multichip dryrun to exercise
    the partitioned kernel path on CPU devices. Compilation of the jitted
    caller must happen inside the context (the interpret flag is read at
    lowering time)."""

    def __enter__(self):
        global _FORCE_DISPATCH, _FORCE_INTERPRET
        self._prev = (_FORCE_DISPATCH, _FORCE_INTERPRET)
        _FORCE_DISPATCH = True
        if not on_tpu():
            _FORCE_INTERPRET = True
        return self

    def __exit__(self, *exc):
        global _FORCE_DISPATCH, _FORCE_INTERPRET
        _FORCE_DISPATCH, _FORCE_INTERPRET = self._prev
        return False
