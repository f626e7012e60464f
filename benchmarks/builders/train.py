"""A training cell: ``fleet.build_train_step`` over the configuration's
model and strategy, driven from the seed.

Set-up builds ONE ``CompiledTrainStep`` with its state, drives it through
its first three steps on the feed the window uses (the readings the
reference is compared with are taken there) and hands the same object to
the window. A window is whole steps: the clock stops at the fetch that
ends the last step begun before ``--seconds`` ran out.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import compare, flops, reference, traffic
from ..lib import weights as W
from . import common

REF_STEPS = 3


class Run:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, list(devices)
        self.cfg = cell.config
        self.args = self.cfg["train"]
        self.rows = int(self.args["batch_rows"])
        self.seq = int(cell.traffic["seq_len"])
        self.counters: dict = {}
        self.window_steps = 0
        self.window_s = 0.0

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        import paddle_tpu.distributed as dist
        from paddle_tpu import optimizer as optim
        from paddle_tpu.ops import pallas as pk
        from paddle_tpu.parallel import mesh as M

        strategy = dist.DistributedStrategy()
        sh = self.args.get("sharding")
        if sh:
            strategy.sharding.enable = True
            strategy.sharding.stage = int(sh["stage"])
            strategy.sharding.degree = int(sh["degree"])
        self.mesh = M.mesh_from_strategy(strategy, self.devices)
        self._ctx = M.MeshContext(self.mesh)
        self._ctx.__enter__()
        pk.reset_partition_stats()
        opt = self.args["adamw"]
        clip = opt.get("clip_norm")
        template = self.template = common.model_template(self.cfg)
        self.step = dist.fleet.build_train_step(
            template, optimizer=optim.AdamW(
                opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                epsilon=opt["eps"], weight_decay=opt["weight_decay"],
                grad_clip=optim.ClipGradByGlobalNorm(clip) if clip else None),
            strategy=strategy, mesh=self.mesh)
        key = W.root_key(self.seed)
        # every weight in one jitted call from the seed, born sharded as
        # the strategy lays it out; the optimizer's zeros follow it
        from jax.sharding import NamedSharding, PartitionSpec
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self.step.param_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        state = self.step.init_state(jax.jit(
            lambda k: common.seeded_model(template, k),
            out_shardings=shardings)(key))
        self.feed = traffic.packed_batches(
            self.cell.traffic, self.seed, self.rows,
            self.cfg["vocab_size"])
        self.first = {"loss": [], "batches": []}
        for i in range(REF_STEPS):
            ids = next(self.feed)
            self.first["batches"].append(ids)
            state, metrics = self._one(state, ids, i)
            self.first["loss"].append(float(metrics["loss"]))
            if i == 0:
                self.first["grad_norm"] = self._grad_norms(state, opt)
        self.first["change_norm"] = self._change_norms(state, key)
        self.state = state
        self.n_steps = REF_STEPS
        stats = pk.partition_stats()
        self.counters["partition_fallbacks"] = (
            sum(v for k, v in stats.items() if k.endswith(":fallback"))
            if len(self.devices) > 1 else None)

    def _one(self, state, ids, i: int):
        data = self.step.shard_batch({"input_ids": ids, "labels": ids})
        return self.step(state, data, jax.random.PRNGKey(i))

    @staticmethod
    def _grad_norms(state, opt) -> dict:
        """The first gradient as the optimizer got it, by leaf, from
        Adam's first moment after one step: m1 = (1 - beta1) g1."""
        adam = next(s for s in state.opt_state if hasattr(s, "mu"))
        norms = jax.jit(lambda mu: jax.tree_util.tree_map(
            lambda m: jnp.sqrt(jnp.sum(jnp.square(m))), mu))(adam.mu)
        return {jax.tree_util.keystr(p): float(v) / (1 - opt["beta1"])
                for p, v in jax.tree_util.tree_leaves_with_path(norms)}

    def _change_norms(self, state, key) -> dict:
        """Norm by leaf of (parameters now - parameters from the seed)."""
        def diff(model, k):
            start = common.seeded_model(self.template, k)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))),
                model, start)
        norms = jax.jit(diff)(state.model, key)
        return {jax.tree_util.keystr(p): float(v)
                for p, v in jax.tree_util.tree_leaves_with_path(norms)}

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, tracer=None) -> None:
        state, pending = self.state, collections.deque()
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            state, metrics = self._one(state, next(self.feed),
                                       self.n_steps + n)
            pending.append(metrics["loss"])
            n += 1
            if len(pending) > 2:        # at most two steps run ahead
                float(pending.popleft())
            if tracer is not None:
                tracer.tick()
        last = [float(x) for x in pending][-1]
        self.window_s = time.perf_counter() - t0
        self.window_steps, self.state = n, state
        self.last_loss = last
        self.n_steps += n

    def attempted(self) -> tuple[int, int]:
        return self.window_steps, 0 if np.isfinite(self.last_loss) else 1

    def end_to_end(self) -> dict:
        tokens = self.window_steps * self.rows * self.seq
        return {"train_tok_s_chip":
                tokens / self.window_s / len(self.devices)}

    def trace_context(self, traced=None) -> dict:
        """What the per-layer readers need beside the trace."""
        arch = reference.Arch.from_config(self.cfg)
        tokens = self.window_steps * self.rows * self.seq
        stats = [d.memory_stats() for d in self.devices]
        self.counters["peak_hbm_share"] = (
            100.0 * max(s["peak_bytes_in_use"] / s["bytes_limit"]
                        for s in stats) if all(stats) else None)
        return {
            "required_flops": tokens * flops.train_flops_per_token(
                arch, self.seq),
            "window_s": self.window_s, "steps": self.window_steps,
            "counters": self.counters,
            "kernel_work": {
                k: dict(w, per_execution_of=["jit_step_fn"])
                for k, w in flops.train_kernel_work(
                    arch, self.rows // len(self.devices), self.seq).items()},
        }

    # -- after the window ----------------------------------------------------
    def free(self) -> None:
        self._ctx.__exit__(None, None, None)
        del self.state, self.step
        jax.clear_caches()
        gc.collect()

    def _reference(self, precision: str = "float32", rows=None) -> dict:
        """``rows``: only the first ``rows`` rows of each batch count,
        repeated to the batch's size (the mean over the rest)."""
        opt = self.args["adamw"]
        rows = rows or self.rows
        return reference.train_steps(
            reference.Arch.from_config(self.cfg), self.seed,
            [np.concatenate([b[:rows]] * (self.rows // rows))
             for b in self.first["batches"]], reference.AdamW(
                lr=opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                eps=opt["eps"], weight_decay=opt["weight_decay"],
                clip_norm=opt.get("clip_norm") or 0.0),
            self.devices, precision)

    def compare(self) -> list[tuple]:
        self.ref = self._reference()
        return compare.train_numbers(self.first, self.ref,
                                     self.cfg["limits"])

    # -- readings that set the limits (benchmarks/readings.py) ---------------
    def control(self) -> list[tuple]:
        """The reference in the program's place, in float8."""
        return compare.train_numbers(self._reference("fp8"), self.ref,
                                     self.cfg["limits"])

    def fault(self, kind: str) -> list[tuple]:
        """The reference in the program's place with a fault planted:
        ``half_batch`` (half of the rows left out, the mean over the
        rest), ``no_exchange`` (one chip's rows only, as if gradients
        were never summed across chips)."""
        rows = {"half_batch": self.rows // 2,
                "no_exchange": self.rows // len(self.devices)}[kind]
        return compare.train_numbers(self._reference(rows=rows), self.ref,
                                     self.cfg["limits"])
