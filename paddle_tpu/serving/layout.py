"""Device layout for the serving engine: where engine state lives.

The :class:`~paddle_tpu.serving.engine.GenerationEngine` owns a pile of
device state (the batched KV cache or page pool, per-slot token/
position/key/sampling arrays) and a set of compiled entry points
(bucketed prefill, fused decode, speculative verify, draft lookahead)
that thread that state through donation. This module
puts ALL of that behind one object so the engine itself never touches
``jax.sharding``:

* ``DeviceLayout(0)`` — the default, from ``FLAGS_gen_mesh_tp=0`` — is
  the **identity layout**: no mesh is built, ``place_state`` returns
  its argument, and ``jit_entry`` is a plain ``jax.jit`` — the compiled
  surface is byte-identical to the pre-sharding build.
* ``DeviceLayout(tp)`` for ``tp >= 1`` builds a tensor-parallel mesh
  over the first ``tp`` local devices (``parallel.mesh.serving_mesh``),
  places model parameters with the per-module spec map (Megatron
  column/row split — ``models/llama.py``'s table), shards the KV
  cache/page pool on the KV-head axis (``models/generation.py``'s
  ``STACKED_KV_SPEC``/``POOL_KV_SPEC``), and gives every compiled entry
  point explicit in/out shardings so XLA's SPMD partitioner inserts the
  collectives. Page tables and the scheduler stay host-side and
  replicated — sharding is invisible above the compiled boundary.

A mesh-backed engine is ONE logical replica: one endpoint, one health
doc. The router/controller need no changes beyond reading the
``device`` stats block (:meth:`DeviceLayout.describe`), which carries
platform, device count, mesh axis sizes, and per-device KV bytes.

Byte-identity across layouts is a hard contract, not an aspiration:
matmul column/row splits concatenate/psum exact partial results, the
KV-head split never splits a reduction, and sampling runs on the
replicated logits — so greedy AND sampled token streams match the
unsharded engine bit-for-bit, and stream failover (``rng_skip``) can
resume a stream started on a tp=4 replica on an unsharded survivor.
Verified on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count``
in ``tests/test_sharded_gen.py`` (``pytest -m sharded``).
"""

from __future__ import annotations

import functools
from typing import Any

__all__ = ["DeviceLayout", "BoundEntry"]


class BoundEntry:
    """A jitted engine entry point with the model bound as its leading
    operand: callers pass (and lower with) everything after it."""

    def __init__(self, jitted, model):
        self._jitted = jitted
        self._model = model

    def __call__(self, *operands):
        return self._jitted(self._model, *operands)

    def lower(self, *operands):
        return self._jitted.lower(self._model, *operands)


class DeviceLayout:
    """Mesh-or-identity placement policy for engine device state.

    ``tp=0`` (the hard-off default): ``mesh is None`` and every method
    is a passthrough. ``tp>=1``: a ``serving_mesh(tp)`` over the first
    ``tp`` local devices; ``tp=1`` exercises the full sharded code path
    (explicit shardings, NamedSharding state) on a one-device mesh —
    useful for shaking out layout bugs without multi-device hardware.
    """

    def __init__(self, tp: int = 0, devices: Any = None):
        self.tp = int(tp)
        if self.tp <= 0:
            self.mesh = None
        else:
            from paddle_tpu.parallel.mesh import serving_mesh
            self.mesh = serving_mesh(self.tp, devices)

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    # -- placement ---------------------------------------------------------
    def shard_model(self, model):
        """Sharded params via the model's own ``shard_for_inference``
        (which validates head divisibility) when it has one, else the
        generic per-module spec map — any ``core.module.Module`` tree
        annotates ``_pspecs`` and unannotated leaves replicate."""
        if hasattr(model, "shard_for_inference"):
            return model.shard_for_inference(self.mesh)
        import jax

        from paddle_tpu.core.module import partition_specs
        from paddle_tpu.parallel.mesh import sharding_tree
        return jax.device_put(model,
                              sharding_tree(self.mesh,
                                            partition_specs(model)))

    @property
    def replicated(self):
        """NamedSharding replicating a leaf over the whole mesh (None
        for the identity layout — callers only use it under
        ``sharded``)."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    def place_pt(self, table):
        """Commit a device-resident page table to the layout: every
        mesh shard needs the full slot->page indirection to gather its
        own KV-head slice, so the table replicates
        (``PAGE_TABLE_SPEC``). Identity layout: ``jax.device_put`` with
        no sharding — a plain committed device array whose ``.at``
        dirty-row updates stay on device between steps."""
        import jax
        if self.mesh is None:
            return jax.device_put(table)
        from jax.sharding import NamedSharding

        from paddle_tpu.models.generation import PAGE_TABLE_SPEC
        return jax.device_put(
            table, NamedSharding(self.mesh, PAGE_TABLE_SPEC))

    def _kv_sharding(self, paged: bool):
        from jax.sharding import NamedSharding

        from paddle_tpu.models.generation import (
            POOL_KV_SPEC, STACKED_KV_SPEC,
        )
        return NamedSharding(self.mesh,
                             POOL_KV_SPEC if paged else STACKED_KV_SPEC)

    def state_sharding(self, state: dict, *, paged: bool):
        """Sharding tree matching the engine state dict: KV leaves on
        the KV-head axis (stacked contiguous layout or paged pool —
        prefix specs, so int8 scale leaves ride along), everything else
        (tokens, positions, keys, sampling params) replicated."""
        import jax
        kv = self._kv_sharding(paged)
        rep = self.replicated
        return {k: (jax.tree_util.tree_map(lambda _: kv, v)
                    if k == "cache" else rep)
                for k, v in state.items()}

    def place_state(self, state: dict, *, paged: bool) -> dict:
        """Commit freshly built engine state to the layout (identity
        when unsharded). Called at construction and on every
        self-healing rebuild — replacement state lands on the mesh,
        never half-placed."""
        if self.mesh is None:
            return state
        import jax
        return jax.device_put(state,
                              self.state_sharding(state, paged=paged))

    # -- compilation -------------------------------------------------------
    def jit_entry(self, fn, model, state: dict, *, paged: bool, n_in: int,
                  n_out: int):
        """Compile an engine entry point ``fn(model, state, *operands)
        -> (state, *results)``: the state is donated and threads
        through, with ``n_in`` extra operands and ``n_out`` extra
        results, all replicated. Identity layout: plain ``jax.jit``.
        Sharded: explicit in/out shardings pin the state to the KV-head
        split so the SPMD partitioner places the collectives inside the
        step instead of resharding at the call boundary (the model
        keeps the sharding ``shard_model`` committed it to).

        The model is an OPERAND, bound into the returned
        :class:`BoundEntry` — never a closed-over value: jit embeds
        closed-over arrays in the program as constants, so every bucket
        program would carry (and hold in device memory) its own copy of
        the weights."""
        import jax
        if self.mesh is None:
            return BoundEntry(jax.jit(fn, donate_argnums=(1,)), model)
        st = self.state_sharding(state, paged=paged)
        rep = self.replicated
        return BoundEntry(
            jax.jit(self._under_mesh(fn), donate_argnums=(1,),
                    in_shardings=(None, st) + (rep,) * n_in,
                    out_shardings=(st,) + (rep,) * n_out), model)

    def jit_aux(self, fn, model, *, n_in: int, n_out: int = 1):
        """Compile a stateless helper ``fn(model, *operands)`` (the
        draft-model lookahead): replicated in/out on the mesh, plain
        ``jax.jit`` otherwise; the model bound as in :meth:`jit_entry`."""
        import jax
        if self.mesh is None:
            return BoundEntry(jax.jit(fn), model)
        rep = self.replicated
        out = rep if n_out == 1 else (rep,) * n_out
        return BoundEntry(
            jax.jit(self._under_mesh(fn),
                    in_shardings=(None,) + (rep,) * n_in,
                    out_shardings=out), model)

    def _under_mesh(self, fn):
        """Trace ``fn`` with this layout's mesh ambient (the engine's
        loop thread traces on first call): the Pallas kernel set reads
        it to dispatch through its shard_map units instead
        of dropping a raw single-device kernel into an SPMD program."""
        from paddle_tpu.parallel.mesh import MeshContext
        mesh = self.mesh

        @functools.wraps(fn)
        def traced(*args):
            with MeshContext(mesh):
                return fn(*args)

        return traced

    # -- observability -----------------------------------------------------
    def describe(self, kv_bytes: int) -> dict:
        """The ``device`` block for engine ``stats()``/serving
        ``health``: platform, device count, mesh axis sizes (degree-1
        axes elided), total and per-device KV bytes — the topology a
        control plane needs for placement, next to the occupancy it
        already had."""
        import jax
        if self.mesh is None:
            return {"platform": jax.devices()[0].platform, "devices": 1,
                    "mesh": None, "kv_bytes": int(kv_bytes),
                    "kv_bytes_per_device": int(kv_bytes)}
        axes = {a: int(s) for a, s in dict(self.mesh.shape).items()
                if int(s) > 1}
        return {"platform": self.mesh.devices.flat[0].platform,
                "devices": int(self.mesh.size),
                "mesh": axes or {"tp": 1},
                "kv_bytes": int(kv_bytes),
                "kv_bytes_per_device": int(kv_bytes) // self.tp}
