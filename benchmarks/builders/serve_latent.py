"""A serving cell of a DeepSeek-V3-family configuration (latent
attention, a held share of the routed experts): ``serve.Run`` with what
is wired to ``lib.reference`` replaced — the ``Arch``, the required
FLOPs, the logit gaps (``lib.reference_latent``), the decode step's
required work (``lib.work_latent``) — and the expert layer's pick
counters read from the engine's ``stats()``.

One repair in its own comparison (PERF.md section 7, 0j):
``logit_gap_per_tie`` divides by ``max(near-ties, compare_min_ties)``
from the traffic file, not by ``max(near-ties, 1)``: a sample that holds
a handful of near-ties no longer reads one token's gap as the mean, and
a sample with plenty reads what it read before.
"""

from __future__ import annotations

import numpy as np

from ..lib import reference_latent, work_latent
from . import serve

PICKS, HELD = "moe_picks", "moe_picks_held"


def per_tie(gap_sum: float, ties: int, min_ties: int) -> float:
    """Sum of the served tokens' logit gaps over the near-ties of the
    sample, which count as at least ``min_ties``."""
    return gap_sum / max(ties, min_ties, 1)


class Run(serve.Run):
    def _arch(self):
        return reference_latent.Arch.from_config(self.cfg)

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, tracer=None) -> None:
        self._stats_at = []
        super().window(seconds, tracer)

    def _tokens_saved(self) -> int:
        # serve.Run.window reads this as the window opens and as it
        # closes: the engine's stats taken beside it bracket the same
        # stretch for the pick counters
        self._stats_at.append(self.engine.stats())
        return super()._tokens_saved()

    def trace_context(self, traced=None) -> dict:
        a = self._arch()
        ctx = super().trace_context(traced)
        st0, st = self._stats_at[0], self._stats_at[-1]
        picks = st.get(PICKS, 0) - st0.get(PICKS, 0)
        held = st.get(HELD, 0) - st0.get(HELD, 0)
        # required FLOPs of the window's work: the fixed weights and the
        # attention of every token forwarded, and an expert's SwiGLU for
        # every routed pick that fell on a held expert
        prefills = [r for r in self.records if self._in_window(r.t_send)]
        hit = self.saved // max(len(prefills), 1)
        need = sum(work_latent.serve_flops(a, hit, len(r.prompt) - hit, 0)
                   for r in prefills)
        decoded = [(len(r.prompt) + i, s) for r in self.records
                   for i, s in enumerate(r.stamps) if i > 0]
        need += sum(work_latent.serve_flops(a, c, 1, 0) for c, s in decoded
                    if self._in_window(s))
        ctx["required_flops"] = need + 2.0 * work_latent.expert_params(a) * held

        steps = sum(1 for _, s in decoded if self._in_window(s))
        slots = int(self.cfg["serve"]["engine"]["slots"])
        self.counters.update(
            moe_held_pick_share=100.0 * held / picks if picks else None,
            # held picks an expert a decode step: a step is the window's
            # decode tokens over the slots that made them (an upper bound
            # of the steps run only when slots idle), prefill picks are
            # in the numerator as they are in the deployment
            moe_tokens_per_held_expert=(
                held / (a.held[1] * work_latent.expert_layers(a)
                        * max(steps / slots, 1.0)) if picks else None),
            kv_bytes_per_token=st.get("kv_bytes_per_token"))
        if traced is not None and traced[0] is not None:
            t0, t1 = traced
            live = [c for c, s in decoded if t0 <= s <= t1]

            def step_work(executions: int) -> dict:
                """The mean traced step: the traced stretch's decode
                tokens and their contexts over its executions."""
                return work_latent.decode_step_work(
                    a, len(live) / executions, sum(live) / executions)

            ctx["kernel_work"]["decode_step"] = step_work
        return ctx

    # -- after the window ----------------------------------------------------
    def _gaps(self, precision: str, seqs=None) -> list[tuple]:
        """``serve.Run._gaps`` through the latent reference, with the
        floor of near-ties under ``logit_gap_per_tie``."""
        arch = self._arch()
        lim = self.cfg["limits"]
        spans = self.sample[1]
        gaps, margins = (np.concatenate(t) for t in
                         reference_latent.serve_logit_gaps(
                             arch, self.seed,
                             self.sample[0] if seqs is None else seqs,
                             spans, precision))
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
