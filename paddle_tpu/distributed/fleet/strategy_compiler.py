"""Strategy compiler: DistributedStrategy → one jitted sharded train step.

The reference's meta-optimizer stack (``fleet/base/fleet_base.py:1058-1108``
ranks AMP/Recompute/GradientMerge/Sharding/Pipeline meta-optimizers and
each rewrites the serialized program) becomes function composition over a
pure step:

  loss  =  amp_cast ∘ recompute(model blocks) ∘ user loss
  grads =  value_and_grad(loss)            (autodiff replaces append_backward)
  grads =  unscale/finite-check            (fp16 loss scaling only)
  grads =  merge(grads, k)                 (gradient merge / accumulation)
  new   =  optimizer.update                (clip inside the chain)
  state sharded by (dp, fsdp, tp) PartitionSpecs; XLA inserts all
  collectives (grad reduction = the DDP Reducer, param gather = ZeRO-3
  broadcast, etc.)

Everything is inside ONE ``jax.jit`` — the equivalent of the whole
ParallelExecutor SSA graph (reference ``framework/parallel_executor.cc``)
compiled ahead of time by XLA.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu import amp as amp_mod
from paddle_tpu.core import flags as flags_mod
from paddle_tpu.core import monitor
from paddle_tpu.core import rng
from paddle_tpu.core import trace as _trace
from paddle_tpu.core.module import apply_updates, trainable_mask
from paddle_tpu.core.strategy import DistributedStrategy
from paddle_tpu.nn.stateful import map_modules
from paddle_tpu.nn.scan import ScannedBlocks
from paddle_tpu.optimizer.transform import global_norm
from paddle_tpu.parallel.mesh import BATCH_AXES
from paddle_tpu.parallel.sharding import (
    opt_state_specs, param_specs_for_stage,
)

__all__ = ["TrainState", "CompiledTrainStep", "build_train_step"]


class TrainState(NamedTuple):
    model: Any
    opt_state: Any
    scaler: Any            # amp.ScalerState or ()
    merge_grads: Any       # fp32 grad accumulator pytree or ()
    step: jnp.ndarray


def _apply_pipeline_override(model, strategy: DistributedStrategy, mesh):
    """PipelineOptimizer analogue: swap ScannedBlocks for the GPipe
    executor over the ``pp`` axis (same stacked arrays, zero copy)."""
    if not strategy.pipeline.enable or strategy.pipeline.degree <= 1:
        return model
    from paddle_tpu.parallel.pipeline import pipeline_blocks

    S = strategy.pipeline.degree
    M = max(strategy.pipeline.num_microbatches, 1)
    sp = strategy.sequence_parallel
    seq_axis = "sp" if (sp.enable and sp.degree > 1) else None

    def fn(m):
        if isinstance(m, ScannedBlocks):
            return pipeline_blocks(m, S, M, mesh=mesh, seq_axis=seq_axis)
        return m

    return map_modules(fn, model)


def _apply_seq_parallel_override(model, strategy: DistributedStrategy):
    """Flip attention modules into ring/Ulysses mode (the long-context
    strategy — new capability, absent in the reference; SURVEY §2.3.8)."""
    sp = strategy.sequence_parallel
    if not sp.enable or sp.degree <= 1:
        return model

    def fn(m):
        if hasattr(m, "seq_mode"):
            return m.replace(seq_mode=sp.mode)
        return m

    return map_modules(fn, model)


def _apply_recompute_override(model, strategy: DistributedStrategy):
    """RecomputeOptimizer analogue: flip the remat flag on scanned blocks
    (static attr surgery — the model decides granularity, the strategy
    decides on/off + policy)."""
    if not strategy.recompute.enable:
        return model

    def fn(m):
        if isinstance(m, ScannedBlocks):
            policy = strategy.recompute.policy
            return m.replace(remat=True,
                             remat_policy=policy if policy != "none"
                             else m.remat_policy)
        return m

    return map_modules(fn, model)


def build_train_step(model, optimizer, loss_fn=None, *,
                     strategy: DistributedStrategy | None = None,
                     mesh=None, donate: bool = True) -> "CompiledTrainStep":
    """Compile the strategy against a model + optimizer.

    ``loss_fn(model, batch, training=True) -> scalar``; defaults to
    ``model.loss(**batch)``-style: a model with a ``.loss`` method gets
    ``model.loss(batch["input_ids"], batch["labels"])``.
    """
    strategy = strategy or DistributedStrategy()
    if mesh is None:
        from paddle_tpu.parallel.mesh import get_mesh
        mesh = get_mesh()
    if strategy.localsgd.enable and strategy.dgc.enable:
        raise ValueError(
            "localsgd and dgc are mutually exclusive comm-reduction "
            "strategies (pick one)")
    if strategy.localsgd.enable:
        from paddle_tpu.parallel.localsgd import build_localsgd_step
        return build_localsgd_step(model, optimizer, loss_fn,
                                   strategy=strategy, mesh=mesh,
                                   donate=donate)
    if strategy.dgc.enable:
        from paddle_tpu.parallel.dgc import build_dgc_step
        return build_dgc_step(model, optimizer, loss_fn,
                              strategy=strategy, mesh=mesh, donate=donate)

    far_cfg = strategy.fp16_allreduce
    use_fp16_ar = far_cfg.enable
    if use_fp16_ar:
        deg = strategy.parallel_degrees()
        # zero-1/2 compose (params replicated over the manual data axes;
        # only optimizer state is sharded — parity-tested). tp stays
        # rejected: with no axis_names the shard_map is manual over ALL
        # axes and would silently all-gather the Megatron shards
        # (replicated compute), and the correct partial-manual form
        # (axis_names={dp, fsdp}, tp automatic) is blocked upstream —
        # distilled to tests/repros/fp16_ar_partial_manual_tp.py (r4:
        # hard XLA-CPU abort; jax 0.9: ShardingTypeError — automatic-
        # axis contractions inside a partial-manual region demand
        # per-op out_sharding, which arbitrary layer code cannot
        # carry). test_fleet.py::test_fp16_allreduce_tp_gate_cites_
        # live_limitation re-probes every run and fails when upstream
        # unblocks. pp/sp nest their own manual schedules; zero-3
        # shards params over the very axes the reduction is manual
        # over.
        bad = [a for a in ("tp", "pp", "sp") if deg.get(a, 1) > 1]
        if bad or (strategy.sharding.enable and strategy.sharding.stage >= 3):
            raise ValueError(
                "fp16_allreduce compresses the data-parallel gradient "
                f"reduction only; incompatible with {bad or 'zero-3'} "
                "(those reductions are partitioned by XLA; zero-1/2 "
                "compose)")
        wire_dtype = jnp.dtype(far_cfg.dtype)

    pp_cfg = strategy.pipeline
    use_pp = pp_cfg.enable and pp_cfg.degree > 1
    if use_pp and pp_cfg.schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"pipeline.schedule={pp_cfg.schedule!r}: only 'gpipe' and "
            "'1f1b' are implemented")
    use_1f1b = use_pp and pp_cfg.schedule == "1f1b"
    # pp∘sp composition: the pipeline shard_maps run manual over
    # {pp, sp} and ring/Ulysses attention rides the already-manual sp
    # axis directly — r3's scoped-GSPMD fallback and the pp∘Ulysses gate
    # existed because the *nested* shard_map formulation crashes Shardy
    # ("axis already bound by a parent sdy.manual_computation",
    # tests/repros/shardy_nested_manual_sp.py) and, for Ulysses, aborted
    # XLA outright; the joint-manual formulation needs neither. (The r3
    # 1F1B∘AMP Shardy crash "Invalid binary instruction opcode copy" no
    # longer reproduces on jax 0.9.0 — its fallback is retired too.)
    pp_seq_axis = ("sp" if (use_pp and strategy.sequence_parallel.enable
                            and strategy.sequence_parallel.degree > 1)
                   else None)
    pipe_head_loss = pipe_loss_denom = None
    if (loss_fn is not None
            and getattr(loss_fn, "_pipeline_head_loss", False)
            and not use_1f1b):
        raise ValueError(
            "loss_fn is marked with pipeline_1f1b.head_loss (signature "
            "fn(head, h, labels)) — that contract only applies to "
            "pipeline.schedule='1f1b'; pass a generic "
            "loss_fn(model, batch) for other strategies")
    if use_1f1b:
        if loss_fn is not None:
            if getattr(loss_fn, "_pipeline_head_loss", False):
                # custom per-microbatch head loss (the arbitrary section
                # program of section_worker.cc:44): runs on the last
                # stage in place of pipeline_parts' default
                pipe_head_loss = loss_fn
                pipe_loss_denom = getattr(loss_fn, "_pipeline_denom",
                                          None)
                loss_fn = None
            else:
                raise ValueError(
                    "1f1b computes the loss per-microbatch on the last "
                    "stage; a generic loss_fn(model, batch) cannot be "
                    "scheduled. Mark a per-microbatch head loss with "
                    "paddle_tpu.parallel.pipeline_1f1b.head_loss("
                    "fn(head, h, labels) -> sum) or encode the loss in "
                    "model.pipeline_parts()")
        if not hasattr(model, "pipeline_parts"):
            raise ValueError(
                f"pipeline.schedule='1f1b' needs "
                f"{type(model).__name__}.pipeline_parts() (embed/blocks/"
                "head decomposition); implement it or use schedule='gpipe'")

    def _prepare(m):
        m = _apply_recompute_override(m, strategy)
        m = _apply_seq_parallel_override(m, strategy)
        return _apply_pipeline_override(m, strategy, mesh)

    model = _prepare(model)

    amp_cfg = strategy.amp
    amp_enabled = amp_cfg.enable
    amp_dtype = jnp.dtype(amp_cfg.dtype) if amp_enabled else None
    # bf16 has fp32 exponent range: loss scaling only matters for fp16
    use_scaler = (amp_enabled and amp_cfg.use_dynamic_loss_scaling
                  and amp_dtype == jnp.float16)
    scaler = amp_mod.GradScaler(
        init_loss_scaling=amp_cfg.init_loss_scaling,
        incr_ratio=amp_cfg.incr_ratio, decr_ratio=amp_cfg.decr_ratio,
        incr_every_n_steps=amp_cfg.incr_every_n_steps,
        decr_every_n_nan_or_inf=amp_cfg.decr_every_n_nan_or_inf,
        enable=use_scaler)

    gm_cfg = strategy.gradient_merge
    k_steps = gm_cfg.k_steps if gm_cfg.enable else 1

    # FLAGS_check_nan_inf is read at compile time: the sweep is part of the
    # jitted graph (flipping the flag after build_train_step has no effect,
    # matching the reference where it gates code inside the compiled op)
    check_nan = bool(flags_mod.flag("check_nan_inf"))

    stage = strategy.sharding.stage if strategy.sharding.enable else 0

    if loss_fn is None:
        def loss_fn(m, batch, training=True):
            return m.loss(batch["input_ids"], batch["labels"],
                          training=training)

    # ---- sharding layout -------------------------------------------------
    param_specs = param_specs_for_stage(model, mesh, stage)
    train_mask = trainable_mask(model)

    sp_enabled = (strategy.sequence_parallel.enable
                  and strategy.sequence_parallel.degree > 1)

    def _data_spec(leaf):
        if not leaf.ndim:
            return P()
        if sp_enabled and leaf.ndim >= 2:
            # [batch, seq, ...]: sequence dim sharded over sp
            return P(BATCH_AXES, "sp", *([None] * (leaf.ndim - 2)))
        return P(BATCH_AXES, *([None] * (leaf.ndim - 1)))

    def state_specs(state: TrainState) -> TrainState:
        return TrainState(
            model=param_specs,
            opt_state=opt_state_specs(state.opt_state, param_specs,
                                      state.model, mesh, stage),
            scaler=jax.tree_util.tree_map(lambda _: P(), state.scaler),
            merge_grads=(() if isinstance(state.merge_grads, tuple)
                         and state.merge_grads == () else param_specs),
            step=P(),
        )

    # ---- the step --------------------------------------------------------
    from paddle_tpu.parallel.mesh import MeshContext

    def step_fn(state: TrainState, batch, key):
        # ambient mesh available during tracing (ring attention / pipeline
        # shard_maps pick it up)
        with MeshContext(mesh):
            return _step_impl(state, batch, key)

    def _step_impl(state: TrainState, batch, key):
        model = state.model

        def compute_loss(m, b):
            if amp_enabled:
                m = amp_mod.cast_model(
                    m, amp_dtype,
                    keep_norms_fp32=amp_cfg.keep_norms_fp32)
            from paddle_tpu.nn.stateful import state_tape
            with rng.stream(key):
                with amp_mod.auto_cast(
                        enable=amp_enabled,
                        dtype=str(amp_dtype) if amp_enabled else "bfloat16",
                        custom_white_list=amp_cfg.custom_white_list,
                        custom_black_list=amp_cfg.custom_black_list):
                    with state_tape() as tape:
                        loss = loss_fn(m, b, training=True)
            # the tape (BatchNorm running stats etc.) rides has_aux out of
            # the grad trace and is merged into the updated model below
            if use_scaler:
                return scaler.scale(loss, state.scaler), (loss, dict(tape))
            return loss, (loss, dict(tape))

        if use_1f1b:
            # manual 1F1B schedule: loss computed per-microbatch on the
            # last stage, backward interleaved (pipeline_1f1b.py). The
            # schedule derives per-(stage, microbatch, layer) dropout
            # streams from `key` so the backward's recompute replays the
            # forward's masks; AMP rides a jax.vjp through cast_model
            # (grads land on the fp32 masters) and fp16 loss scaling
            # multiplies the backward seed. Stateful layers inside the
            # pipelined blocks ride the returned tape (per-microbatch
            # updates averaged inside the tick scan).
            from paddle_tpu.parallel import pipeline_1f1b

            cot_scale = (state.scaler.loss_scaling if use_scaler else None)

            def pipe_loss_grads(m):
                # fp32 grads whenever masters are fp32 (the amp path
                # re-casts onto them; a downcast round-trip would discard
                # the fp32 accumulation and could overflow scaled fp16)
                return pipeline_1f1b.loss_and_grads(
                    m, batch, mesh, key=key, cotangent_scale=cot_scale,
                    keep_fp32_grads=amp_enabled, seq_axis=pp_seq_axis,
                    head_loss_fn=pipe_head_loss,
                    loss_denom_fn=pipe_loss_denom)

            with jax.named_scope("forward_backward"):
                if amp_enabled:
                    # the VJP of cast_model is just the reverse cast
                    # (transpose of convert), applied by hand: grads land
                    # on the fp32 masters. (An actual jax.vjp over
                    # cast_model trips an XLA CPU crash inside the
                    # pipeline shard_map graph.)
                    with amp_mod.auto_cast(
                            enable=True, dtype=str(amp_dtype),
                            custom_white_list=amp_cfg.custom_white_list,
                            custom_black_list=amp_cfg.custom_black_list):
                        loss, grads_c, tape = pipe_loss_grads(
                            amp_mod.cast_model(
                                model, amp_dtype,
                                keep_norms_fp32=amp_cfg.keep_norms_fp32))
                    grads = jax.tree_util.tree_map(
                        lambda g, p: (g.astype(p.dtype)
                                      if hasattr(p, "dtype") else g),
                        grads_c, model)
                else:
                    loss, grads, tape = pipe_loss_grads(model)
            grads, all_finite = (scaler.unscale(grads, state.scaler)
                                 if use_scaler else
                                 (grads, jnp.asarray(True)))
        elif use_fp16_ar:
            # fp16/bf16-compressed gradient all-reduce: compute per-shard
            # grads inside a shard_map over the data axes and psum them in
            # the wire dtype (the c_allreduce-on-fp16 of the reference's
            # fp16_allreduce_optimizer), instead of XLA's implicit fp32
            # reduction in the backward.
            from jax import shard_map

            data_specs = jax.tree_util.tree_map(_data_spec, batch)

            def local_grads(m, b):
                (_, (loss, tape)), grads = jax.value_and_grad(
                    compute_loss, has_aux=True)(m, b)
                ndev = jax.lax.psum(1, BATCH_AXES)
                grads = jax.tree_util.tree_map(
                    lambda g: (jax.lax.psum(g.astype(wire_dtype), BATCH_AXES)
                               / ndev).astype(g.dtype), grads)
                loss = jax.lax.pmean(loss, BATCH_AXES)
                tape = {k: jax.lax.pmean(v, BATCH_AXES) for k, v in
                        tape.items()}
                return grads, loss, tape

            with jax.named_scope("forward_backward"):
                grads, loss, tape = shard_map(
                    local_grads, mesh=mesh, in_specs=(P(), data_specs),
                    out_specs=(P(), P(), P()), check_vma=False)(model, batch)
            grads, all_finite = (scaler.unscale(grads, state.scaler)
                                 if use_scaler else
                                 (grads, jnp.asarray(True)))
        else:
            grad_fn = jax.value_and_grad(
                lambda m: compute_loss(m, batch), has_aux=True)
            with jax.named_scope("forward_backward"):
                (_, (loss, tape)), grads = grad_fn(model)
            grads, all_finite = (scaler.unscale(grads, state.scaler)
                                 if use_scaler else
                                 (grads, jnp.asarray(True)))

        if k_steps > 1:
            # gradient merge: accumulate in fp32; apply every k-th step.
            # An overflow step (fp16 scaling) must NOT poison the window:
            # skip its contribution entirely (reference skips the whole
            # step on found_inf).
            acc = jax.tree_util.tree_map(
                lambda a, g: jnp.where(all_finite,
                                       a + g.astype(jnp.float32), a),
                state.merge_grads, grads)
            do_apply = (state.step + 1) % k_steps == 0
            eff = jax.tree_util.tree_map(
                lambda a, g: (a / k_steps if gm_cfg.avg else a).astype(
                    g.dtype), acc, grads)
        else:
            acc = state.merge_grads
            do_apply = jnp.asarray(True)
            eff = grads

        with jax.named_scope("optimizer_update"):
            updates, new_opt = optimizer.update(eff, state.opt_state, model)
            apply_gate = jnp.logical_and(do_apply, all_finite)
            updates = jax.tree_util.tree_map(
                lambda u: jnp.where(apply_gate, u, jnp.zeros_like(u)),
                updates)
            # buffers (BN running stats) never take optimizer updates —
            # they change only through the state tape merge below
            updates = jax.tree_util.tree_map(
                lambda u, t: u if t else jnp.zeros_like(u), updates,
                train_mask)
            new_opt = jax.tree_util.tree_map(
                lambda n, o: (jnp.where(apply_gate, n, o)
                              if hasattr(n, "shape") else n),
                new_opt, state.opt_state)
            new_model = apply_updates(model, updates)
        if tape:
            from paddle_tpu.nn.stateful import merge_state
            merged = merge_state(new_model, tape)
            # like the parameter update, state merges are gated on
            # finiteness: a skipped overflow step must not bake inf/nan
            # batch statistics into the running buffers forever
            new_model = jax.tree_util.tree_map(
                lambda n, o: (jnp.where(all_finite, n, o)
                              if hasattr(n, "dtype") else n),
                merged, new_model)
        if k_steps > 1:
            acc = jax.tree_util.tree_map(
                lambda a: jnp.where(do_apply, jnp.zeros_like(a), a), acc)

        new_scaler = (scaler.update(state.scaler,
                                    jnp.logical_not(all_finite))
                      if use_scaler else state.scaler)
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": global_norm(grads),
            "all_finite": all_finite,
        }
        if check_nan:
            # FLAGS_check_nan_inf sweep (reference checks every op output,
            # nan_inf_utils_detail.cc:301; one fused per-step sweep here —
            # the per-op boundary doesn't exist inside a single XLA graph)
            def _finite(tree):
                checks = [jnp.all(jnp.isfinite(l))
                          for l in jax.tree_util.tree_leaves(tree)
                          if hasattr(l, "dtype")
                          and jnp.issubdtype(l.dtype, jnp.floating)]
                return (jnp.all(jnp.stack(checks)) if checks
                        else jnp.asarray(True))

            metrics["check/loss_finite"] = jnp.all(jnp.isfinite(loss))
            metrics["check/grads_finite"] = _finite(grads)
            metrics["check/params_finite"] = _finite(new_model)
        return TrainState(new_model, new_opt, new_scaler, acc,
                          state.step + 1), metrics

    return CompiledTrainStep(step_fn, optimizer, scaler, mesh, param_specs,
                             state_specs, _data_spec, k_steps, donate,
                             _prepare)


class CompiledTrainStep:
    """The compiled, sharded training step + its state management."""

    def __init__(self, step_fn, optimizer, scaler, mesh, param_specs,
                 state_specs_fn, data_spec_fn, k_steps, donate,
                 prepare_model=lambda m: m):
        self._step_fn = step_fn
        self._optimizer = optimizer
        self._scaler = scaler
        self._mesh = mesh
        self.param_specs = param_specs
        self._state_specs_fn = state_specs_fn
        self._data_spec_fn = data_spec_fn
        self._k_steps = k_steps
        self._donate = donate
        self._prepare_model = prepare_model
        self._jitted = None

    @property
    def mesh(self):
        return self._mesh

    def init_state(self, model) -> TrainState:
        """Build + shard the full training state. Parameters are placed
        according to the strategy's specs (the ``startup program`` +
        ``c_broadcast``-params phase of the reference, done by device_put)."""
        model = self._prepare_model(model)
        opt_state = self._optimizer.init(model)
        scaler_state = (self._scaler.init() if self._scaler.enable else ())
        merge = (jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), model)
            if self._k_steps > 1 else ())
        state = TrainState(model, opt_state, scaler_state, merge,
                           jnp.zeros((), jnp.int32))
        specs = self._state_specs_fn(state)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(state, shardings)

    def shard_batch(self, batch):
        """Place a host batch onto the mesh (dp+fsdp over the batch dim) —
        the data-feed split of the reference's trainers."""
        with _trace.span("train/shard_batch"):
            shardings = jax.tree_util.tree_map(
                lambda x: NamedSharding(self._mesh, self._data_spec_fn(x)),
                batch)
            return jax.device_put(batch, shardings)

    def _build_jit(self, state, batch):
        """The production jit wiring (shardings + donation) — shared by
        ``__call__`` and ``compile_abstract`` so AOT artifacts measure
        exactly what training executes."""
        specs = self._state_specs_fn(state)
        state_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        data_shardings = jax.tree_util.tree_map(
            lambda x: NamedSharding(self._mesh, self._data_spec_fn(x)),
            batch)
        return jax.jit(
            self._step_fn,
            in_shardings=(state_shardings, data_shardings, None),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if self._donate else (),
        )

    def lower(self, state, batch, key=None):
        """Lower the train step over concrete or abstract
        (ShapeDtypeStruct) state/batch with the SAME jit wiring
        (shardings, donation) as ``__call__`` — nothing executes or is
        donated. ``.as_text()`` shows what the step dispatches (Pallas
        kernels by their ``name=``); ``.compile()`` is
        :meth:`compile_abstract`."""
        if key is None:
            key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return self._build_jit(state, batch).lower(state, batch, key)

    def compile_abstract(self, abstract_state, abstract_batch, key=None):
        """AOT-compile the train step over abstract (ShapeDtypeStruct)
        state/batch — full-size flagship configs compile and report XLA
        memory analysis without materializing any weights."""
        return self.lower(abstract_state, abstract_batch, key).compile()

    def __call__(self, state: TrainState, batch, key=None):
        if key is None:
            key = rng.next_key()
        if self._jitted is None:
            self._jitted = self._build_jit(state, batch)
        if not _trace.recording():
            new_state, metrics = self._jitted(state, batch, key)
        else:
            # the host side of one step: dispatch, and the compile when
            # jax built a program during the call (which step recompiled)
            with _trace.span("train/step") as sp:
                built = _trace.thread_compiles()
                new_state, metrics = self._jitted(state, batch, key)
                sp.set(compiled=int(_trace.thread_compiles() != built))
        if "check/grads_finite" in metrics:
            bad = [name for name in ("loss", "grads", "params")
                   if not bool(metrics[f"check/{name}_finite"])]
            if bad:
                raise FloatingPointError(
                    f"check_nan_inf: non-finite values in {', '.join(bad)} "
                    f"at step {int(new_state.step)} "
                    f"(loss={float(metrics['loss'])})")
        if flags_mod.flag("benchmark"):
            # FLAGS_benchmark: synchronize every step so host-side timing
            # brackets real device work (reference operator.cc:1123)
            jax.block_until_ready(new_state)
        monitor.stat_add("fleet/steps", 1)
        return new_state, metrics

    def eval_step(self, model, batch, eval_fn):
        """Jitted eval helper (no grad, eval mode). The jit wrapper is
        cached per eval_fn — keyed on the function object itself (a
        strong reference), never on ``id()``: an id can be reused by a
        new function after the old one is collected, which would silently
        serve the stale executable. Bounded LRU (a fresh closure per call
        would otherwise grow the cache for the step's lifetime)."""
        import collections

        if not hasattr(self, "_eval_cache"):
            self._eval_cache = collections.OrderedDict()
        jitted = self._eval_cache.get(eval_fn)
        if jitted is None:
            jitted = jax.jit(eval_fn)
            self._eval_cache[eval_fn] = jitted
            while len(self._eval_cache) > 8:
                self._eval_cache.popitem(last=False)
        else:
            self._eval_cache.move_to_end(eval_fn)
        return jitted(model, batch)
