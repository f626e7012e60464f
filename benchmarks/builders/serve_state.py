"""A serving cell of a Kimi-Linear-family configuration (KDA layers with
a recurrent state beside latent layers, a slot-indexed state group next
to the latent page pool, prefix hits that restore a state snapshot):
``serve.Run`` with what is wired to ``lib.reference`` replaced — the
``Arch``, the required FLOPs, the logit gaps
(``lib.reference_kimi_linear``), the decode step's and the KDA step
kernel's required work (``lib.work_kda``) — the expert layer's pick
counters as ``serve_latent`` reads them, and the state group's books
from the engine's ``stats()``: bytes a slot as allocated, admissions and
those that restored a snapshot.

The decay's two leaves (``A_log``, ``dt_bias``) are not ``lib.weights``'s
N(0, 0.02): the builder replaces them in the seeded model with
``reference_kimi_linear.decay_leaf_f32``'s draw, which the reference
makes too (the configuration's ``assumed`` says why).

``logit_gap_per_tie`` divides by ``max(near-ties, compare_min_ties)``
(``serve_latent.per_tie``; PERF.md section 7, 0j). Readings beside the
float8 control: ``no_state_restore`` (the reference forgets everything at
the template's end — what a lost snapshot would serve) and
``state_bf16`` (the reference's state rounded to bfloat16 a token).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import loadgen, reference_kimi_linear, work_kda
from ..lib.work_latent import expert_params
from . import common, serve
from .serve_latent import HELD, PICKS, per_tie


def state_group(stats: dict) -> dict:
    """The state layer group's block of an engine's ``stats()``; empty
    for a program that has no such group."""
    return next((g for g in stats.get("groups", ())
                 if g.get("name") == "state"), {})


def with_decay(model, key):
    """``model`` with every ``A_log`` / ``dt_bias`` leaf drawn by the
    reference's ``decay_leaf_f32`` under its pytree path (a scanned
    leaf layer by layer, as ``lib.weights`` draws the others)."""
    def fix(path, leaf):
        name = jax.tree_util.keystr(path)
        if not name.endswith(reference_kimi_linear.DECAY):
            return leaf
        if not name.startswith(common.STACKED):
            return reference_kimi_linear.decay_leaf_f32(
                key, name, 0, leaf.shape).astype(leaf.dtype)
        return jax.vmap(lambda l: reference_kimi_linear.decay_leaf_f32(
            key, name, l, leaf.shape[1:]))(
                jnp.arange(leaf.shape[0])).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(fix, model)


class Run(serve.Run):
    def _arch(self):
        return reference_kimi_linear.Arch.from_config(self.cfg)

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        """``serve.Run.setup`` with the decay's leaves drawn as the
        reference draws them: its model comes from
        ``common.seeded_model``, which this wraps for the call."""
        plain = common.seeded_model
        common.seeded_model = lambda template, key: with_decay(
            plain(template, key), key)
        try:
            super().setup()
        finally:
            common.seeded_model = plain

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, tracer=None) -> None:
        """``serve.Run.window`` with the ``stats()`` it reads as the
        window opens and as it closes kept."""
        self._stats_at = []
        real = self.engine.stats

        def stats():
            self._stats_at.append(real())
            return self._stats_at[-1]

        self.engine.stats = stats
        try:
            super().window(seconds, tracer)
        finally:
            del self.engine.stats

    def trace_context(self, traced=None) -> dict:
        """As ``serve_window.Run.trace_context`` (``serve.Run``'s reads
        the sizes of ``lib.reference.Arch``): the window's prefills and
        their prefix hits, the load generator's lateness, the required
        FLOPs of every token forwarded, the pick counters and the state
        group's books."""
        a = self._arch()
        st0, st = self._stats_at[0], self._stats_at[-1]
        picks = st.get(PICKS, 0) - st0.get(PICKS, 0)
        held = st.get(HELD, 0) - st0.get(HELD, 0)
        prefills = [r for r in self.records if self._in_window(r.t_send)]
        prompt_tokens = sum(len(r.prompt) for r in prefills)
        # every hit is the template's whole pages (capped there: the
        # engine books a hit at admission, the window counts a prompt at
        # its send, serve_window's note)
        page = int(self.cfg["serve"]["engine"]["page_tokens"])
        hit = min(self.saved // max(len(prefills), 1),
                  int(self.mix["template_tokens"]) // page * page)
        prefilled = prompt_tokens - hit * len(prefills)
        decoded = [(len(r.prompt) + i, s) for r in self.records
                   for i, s in enumerate(r.stamps) if i > 0]
        in_window = [c for c, s in decoded if self._in_window(s)]
        late = loadgen.lateness(self.records, self.t0, self.t1)
        g0, g1 = state_group(st0), state_group(st)
        admitted = g1.get("admissions", 0) - g0.get("admissions", 0)
        restored = g1.get("restores", 0) - g0.get("restores", 0)
        slots = int(self.cfg["serve"]["engine"]["slots"])
        self.counters.update(
            prefill_calls=len(prefills), prefilled_tokens=prefilled,
            prefix_token_share=(100.0 * hit * len(prefills) / prompt_tokens
                                if prompt_tokens else None),
            gen_late_p99_ms=(1e3 * loadgen.percentile(late, 99)
                             if late else None),
            moe_held_pick_share=100.0 * held / picks if picks else None,
            # held picks an expert a decode step (serve_latent's
            # reckoning: a step is the window's decode tokens over the
            # slots)
            moe_tokens_per_held_expert=(
                held / (a.held[1] * work_kda.expert_layers(a)
                        * max(len(in_window) / slots, 1.0))
                if picks else None),
            kv_bytes_per_token=st.get("kv_bytes_per_token"),
            # the state group's books; a program without one reports
            # neither
            state_bytes_per_slot=g1.get("bytes_per_slot"),
            state_restore_share=(100.0 * restored / admitted
                                 if admitted else None))
        ctx = {"window_s": self.t1 - self.t0, "counters": self.counters,
               "kernel_work": {},
               "required_flops": (
                   sum(work_kda.serve_flops(a, hit, len(r.prompt) - hit)
                       for r in prefills)
                   + sum(work_kda.serve_flops(a, c, 1) for c in in_window)
                   + 2.0 * expert_params(a) * held)}
        if traced is not None and traced[0] is not None:
            t0, t1 = traced
            live = [c for c, s in decoded if t0 <= s <= t1]

            def step_work(executions: int) -> dict:
                """The mean traced step: the traced stretch's decode
                tokens and their contexts over its executions."""
                return work_kda.decode_step_work(
                    a, len(live) / executions, sum(live) / executions)

            ctx["kernel_work"]["decode_step"] = step_work
            # the KDA step kernel over the whole traced stretch: every
            # traced decode token's state, read and written once a layer
            ctx["kernel_work"]["kda_step"] = work_kda.kda_step_work(
                a, len(live))
        return ctx

    # -- after the window ----------------------------------------------------
    def _gaps(self, precision: str, seqs=None) -> list[tuple]:
        """``serve.Run._gaps`` through this family's reference, with the
        floor of near-ties under ``logit_gap_per_tie``."""
        lim = self.cfg["limits"]
        if self.sample is None:         # a control or fault asked for
            return [(n, float("nan"), lim[n],      # what compare() could not
                     "no request finished inside the window")
                    for n in ("logit_gap_per_tie", "logit_gap_max")]
        spans = self.sample[1]
        gaps, margins = (np.concatenate(t) for t in
                         reference_kimi_linear.serve_logit_gaps(
                             self._arch(), self.seed,
                             self.sample[0] if seqs is None else seqs,
                             spans, precision,
                             cut=int(self.mix["template_tokens"])))
        ties = int((margins < float(self.mix["compare_margin"])).sum())
        floor = int(self.mix.get("compare_min_ties", 1))
        at = int(gaps.argmax())
        return [("logit_gap_per_tie",
                 per_tie(float(gaps.sum()), ties, floor),
                 lim["logit_gap_per_tie"],
                 f"{gaps.size} served tokens of {len(spans)} requests, "
                 f"{ties} near-ties (floor {floor}), "
                 f"{int((gaps > 0).sum())} not the reference's choice, "
                 f"mean gap {float(gaps.mean()):.3g}"),
                ("logit_gap_max", float(gaps[at]), lim["logit_gap_max"],
                 f"at sampled token {at} of {gaps.size}")]

    def fault(self, kind: str) -> list[tuple]:
        """``no_state_restore`` / ``state_bf16``: at each sampled
        position the token that the reference so altered puts first.
        ``altered_token``: as ``serve.Run``."""
        if kind in ("no_state_restore", "state_bf16"):
            return self._gaps(kind)
        return super().fault(kind)
