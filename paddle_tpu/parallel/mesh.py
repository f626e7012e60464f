"""Device mesh construction from a DistributedStrategy.

Replaces the reference's communicator bootstrap
(``c_gen_nccl_id``/``c_comm_init`` ops inserted by
``fleet/meta_optimizers/common.py:49-92`` and the ``ring_id`` attribute on
every collective op): one named mesh, axes = parallelism dimensions.

Axis order encodes ICI locality — the *last* (fastest-varying) axis maps to
physically adjacent chips, so the bandwidth-hungriest parallelism goes
last: ``("pp", "dp", "fsdp", "ep", "sp", "tp")``. Pipeline crosses the
slowest links (it only sends activations), tensor parallelism rides the
fastest; the expert all_to_all sits between the fsdp gather traffic and
the sp/tp ring traffic. See "How to Scale Your Model" for the mental
model.
"""

from __future__ import annotations

import math
import threading
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.strategy import DistributedStrategy

AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# data batch is sharded over every data-ish axis (dp + fsdp); fsdp sharding
# of the batch is what turns parameter sharding into ZeRO-3 semantics
BATCH_AXES = ("dp", "fsdp")

# process-wide default (``set_mesh`` / ``fleet.init``) and the per-thread
# ``MeshContext`` override: serving engines trace their entry points on
# their own loop threads, each under its own mesh, concurrently with the
# main thread
_current_mesh: Mesh | None = None
_scoped = threading.local()


def create_mesh(degrees: dict[str, int] | None = None,
                devices: Sequence | None = None) -> Mesh:
    """Build a Mesh with the canonical axis order.

    Missing axes get degree 1 (they still exist, so PartitionSpecs naming
    them are always valid). A single leftover factor is folded into "dp"
    when degrees are underspecified.
    """
    devices = list(devices) if devices is not None else jax.devices()
    degrees = dict(degrees or {})
    known = math.prod(degrees.get(a, 1) for a in AXIS_ORDER)
    n = len(devices)
    if n % known != 0:
        raise ValueError(
            f"device count {n} not divisible by parallel degrees {degrees}")
    if known < n:
        degrees["dp"] = degrees.get("dp", 1) * (n // known)
    shape = tuple(degrees.get(a, 1) for a in AXIS_ORDER)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def mesh_from_strategy(strategy: DistributedStrategy,
                       devices: Sequence | None = None) -> Mesh:
    return create_mesh(strategy.parallel_degrees(), devices)


def serving_mesh(tp: int, devices: Sequence | None = None) -> Mesh:
    """Inference-time tensor-parallel mesh: exactly the first ``tp``
    local devices on the canonical axis order, every non-tp axis degree
    1. ``create_mesh`` folds a leftover device factor into "dp" — right
    for training, wrong for a serving replica that wants exactly ``tp``
    chips and no data parallelism — so the device list is truncated
    here before the mesh is built."""
    if tp < 1:
        raise ValueError(f"serving mesh needs tp >= 1, got {tp}")
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < tp:
        raise ValueError(
            f"serving mesh needs {tp} devices, have {len(devices)} "
            "(on CPU, force more with XLA_FLAGS="
            "--xla_force_host_platform_device_count=N)")
    return create_mesh({"tp": tp}, devices=devices[:tp])


def create_hybrid_mesh(ici_degrees: dict[str, int],
                       dcn_degrees: dict[str, int] | None = None) -> Mesh:
    """Multi-slice mesh: ``dcn_degrees`` axes span slices over the data-
    center network, ``ici_degrees`` axes stay within a slice's ICI.

    The reference's hierarchical-allreduce intent
    (``graph_execution_optimizer.py:76-98``: intra-node ring then
    inter-node ring) expressed structurally: put dp (gradient
    reduction, latency-tolerant) on DCN and tp/sp/fsdp (bandwidth-
    hungry) on ICI, and XLA emits the two-level collectives. Built on
    ``jax.experimental.mesh_utils.create_hybrid_device_mesh``; requires
    a real multi-slice topology (falls back to ``create_mesh`` when
    there is a single slice, so launch scripts work unchanged on one
    host)."""
    dcn_degrees = dict(dcn_degrees or {})
    if not dcn_degrees or jax.process_count() == 1:
        merged = dict(ici_degrees)
        for ax, d in dcn_degrees.items():
            merged[ax] = merged.get(ax, 1) * d
        return create_mesh(merged)
    from jax.experimental import mesh_utils

    ici_shape = tuple(ici_degrees.get(a, 1) for a in AXIS_ORDER)
    dcn_shape = tuple(dcn_degrees.get(a, 1) for a in AXIS_ORDER)
    arr = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=jax.devices())
    return Mesh(arr, AXIS_ORDER)


def batch_spec(extra: tuple = ()) -> P:
    """PartitionSpec for a [batch, ...] input: batch over dp+fsdp."""
    return P(BATCH_AXES, *extra)


def set_mesh(mesh: Mesh) -> None:
    global _current_mesh
    _current_mesh = mesh


def get_mesh() -> Mesh:
    mesh = current_mesh()
    if mesh is None:
        raise RuntimeError(
            "no active mesh: call parallel.set_mesh / fleet.init first")
    return mesh


def current_mesh() -> Mesh | None:
    """The ambient mesh — this thread's innermost ``MeshContext``, else
    the ``set_mesh`` default — or None if none has been set."""
    mesh = getattr(_scoped, "mesh", None)
    return mesh if mesh is not None else _current_mesh


class MeshContext:
    """``with MeshContext(mesh):`` — sets the ambient mesh for the block
    on the calling thread."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_scoped, "mesh", None)
        _scoped.mesh = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        _scoped.mesh = self._prev
        return False


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def sharding_tree(mesh: Mesh, spec_tree):
    """Map a PartitionSpec tree to a NamedSharding tree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
