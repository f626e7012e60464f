"""A serving cell of a SmallThinker-family configuration (window and
full attention layers mixed, a page pool of two layer groups):
``serve.Run`` with what is wired to ``lib.reference`` replaced — the
``Arch``, the required FLOPs, the logit gaps
(``lib.reference_smallthinker``), the decode step's and the paged
kernel's required work (``lib.work_window``) — the expert layer's pick
counters as ``serve_latent`` reads them, and the window group's books
from the engine's ``stats()``: the most pages one stream mapped at any
sample, the pages streams let go.

``logit_gap_per_tie`` divides by ``max(near-ties, compare_min_ties)``
(``serve_latent.per_tie``; PERF.md section 7, 0j). One more planted
fault, ``no_window``: the reference with every layer attending in full,
in the program's place.
"""

from __future__ import annotations

import numpy as np

from ..lib import loadgen, reference_smallthinker, work_window
from . import serve
from .serve_latent import HELD, PICKS, per_tie


def window_group(stats: dict) -> dict:
    """The window layer group's block of an engine's ``stats()``; empty
    for a program that has no such group."""
    return next((g for g in stats.get("groups", ())
                 if g.get("name") == "window"), {})


class Run(serve.Run):
    def _arch(self):
        return reference_smallthinker.Arch.from_config(self.cfg)

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, tracer=None) -> None:
        """``serve.Run.window`` with every ``stats()`` it reads kept:
        one as the window opens, one every 0.25 s, one as it closes."""
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

    def _decoded(self):
        """``(cached positions behind the token, stamp)`` of every
        decode step's token."""
        return [(len(r.prompt) + i, s) for r in self.records
                for i, s in enumerate(r.stamps) if i > 0]

    def trace_context(self, traced=None) -> dict:
        """As ``serve.Run.trace_context`` (which reads the sizes of
        ``lib.reference.Arch`` and cannot be asked): the window's
        prefills and their prefix hits, the load generator's lateness,
        the required FLOPs of every token forwarded."""
        a = self._arch()
        st0, st = self._stats_at[0], self._stats_at[-1]
        picks = st.get(PICKS, 0) - st0.get(PICKS, 0)
        held = st.get(HELD, 0) - st0.get(HELD, 0)
        prefills = [r for r in self.records if self._in_window(r.t_send)]
        prompt_tokens = sum(len(r.prompt) for r in prefills)
        # every hit is the template's whole pages. The engine's counter
        # books a hit at admission, the window counts a prompt at its
        # send: at ~55 requests a window one admission more or less than
        # sends moved the quotient by a whole template (12 524 "saved" a
        # request, my chip run, PR 33), so the count is capped at what a
        # prompt can share
        page = int(self.cfg["serve"]["engine"]["page_tokens"])
        hit = min(self.saved // max(len(prefills), 1),
                  int(self.mix["template_tokens"]) // page * page)
        prefilled = prompt_tokens - hit * len(prefills)
        decoded = self._decoded()
        in_window = [c for c, s in decoded if self._in_window(s)]
        late = loadgen.lateness(self.records, self.t0, self.t1)
        self.counters.update(
            prefill_calls=len(prefills), prefilled_tokens=prefilled,
            prefix_token_share=(100.0 * hit * len(prefills) / prompt_tokens
                                if prompt_tokens else None),
            gen_late_p99_ms=(1e3 * loadgen.percentile(late, 99)
                             if late else None))
        ctx = {"window_s": self.t1 - self.t0, "counters": self.counters,
               "kernel_work": {},
               "required_flops": (
                   sum(work_window.serve_flops(a, hit, len(r.prompt) - hit)
                       for r in prefills)
                   + sum(work_window.serve_flops(a, c, 1)
                         for c in in_window))}

        slots = int(self.cfg["serve"]["engine"]["slots"])
        groups = [window_group(s) for s in self._stats_at]
        slid = groups[-1].get("pages_slid", 0) - groups[0].get("pages_slid", 0)
        written = prefilled + len(in_window)
        self.counters.update(
            moe_held_pick_share=100.0 * held / picks if picks else None,
            # picks an expert a decode step (serve_latent's reckoning:
            # a step is the window's decode tokens over the slots)
            moe_tokens_per_held_expert=(
                held / (a.experts * a.layers
                        * max(len(in_window) / slots, 1.0))
                if picks else None),
            kv_bytes_per_token=st.get("kv_bytes_per_token"),
            # the window group's books; a program without one reports
            # neither
            kv_window_pages_per_stream_peak=(
                max(g["stream_pages_max"] for g in groups)
                if groups[-1] else None),
            # pages let go per 1 000 positions written in the window
            # (decode steps and uncached prompt tails): 1000 / page
            # tokens where every stream is past its window
            kv_slid_pages_per_ktok=(1e3 * slid / written
                                    if groups[-1] and written else None))
        if traced is not None and traced[0] is not None:
            t0, t1 = traced
            live = [c for c, s in decoded if t0 <= s <= t1]
            page = int(self.cfg["serve"]["engine"]["page_tokens"])
            kv = sum(work_window.decode_kv_bytes(a, c, page) for c in live)
            fl = sum(work_window.decode_attn_flops(a, c) for c in live)

            def step_work(executions: int) -> dict:
                """The mean traced step: the traced stretch's decode
                tokens and the K/V they read over its executions."""
                return work_window.decode_step_work(
                    a, len(live) / executions, kv / executions,
                    fl / executions)

            ctx["kernel_work"]["decode_step"] = step_work
            # the paged decode kernel, over the whole traced stretch:
            # the live K/V of every traced decode token, once a layer
            ctx["kernel_work"]["paged_window_attn"] = {"flops": fl,
                                                       "bytes": kv}
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
                         reference_smallthinker.serve_logit_gaps(
                             self._arch(), self.seed,
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

    def fault(self, kind: str) -> list[tuple]:
        """``no_window``: at each sampled position the token that the
        reference with every layer FULL puts first. ``altered_token``:
        as ``serve.Run``."""
        if kind == "no_window":
            return self._gaps("no_window")
        return super().fault(kind)
