"""A serving cell of a Laguna-family configuration (window and full
attention layers with their own query-head counts, a gate on every
head, a leading dense layer, a held share of the routed experts and a
shared expert; a page pool of two layer groups): ``serve_window.Run``
with what is wired to SmallThinker replaced — the ``Arch``, the
required FLOPs, the logit gaps (``lib.reference_laguna``), the decode
step's and the paged kernel's required work at each kind's head count
(``lib.work_mixed_heads``) — the expert layer's pick counters and the
window group's books read from the engine's ``stats()`` as
``serve_window`` reads them.

The paged kernel's work is stated twice: over both kinds
(``mixed_heads_attn``, read against ``ptpu_paged_decode_attn``, which
the window kind's ``ptpu_paged_decode_attn_window`` contains) and over
the window kind alone (``window_heads_attn``). Planted faults beyond
``altered_token``: the reference with every layer full
(``no_window``), with no head gate (``no_head_gate``), with its full
layers rotated by plain RoPE over the whole head (``plain_rope_full``)
and with no routed scale (``no_routed_scale``), each in the program's
place.
"""

from __future__ import annotations

import numpy as np

from ..lib import loadgen, reference_laguna, work_mixed_heads
from .serve_latent import HELD, PICKS, per_tie
from .serve_window import Run as WindowRun
from .serve_window import window_group


class Run(WindowRun):
    def _arch(self):
        return reference_laguna.Arch.from_config(self.cfg)

    def trace_context(self, traced=None) -> dict:
        """The window's prefills and their prefix hits, the load
        generator's lateness, the required FLOPs of every token forwarded
        and of every routed pick that fell on a held expert, the pick
        counters and the window group's books; with a trace, the work of
        the decode step and of the paged kernel over the traced
        stretch."""
        a = self._arch()
        w = work_mixed_heads
        st0, st = self._stats_at[0], self._stats_at[-1]
        picks = st.get(PICKS, 0) - st0.get(PICKS, 0)
        held = st.get(HELD, 0) - st0.get(HELD, 0)
        page = int(self.cfg["serve"]["engine"]["page_tokens"])
        slots = int(self.cfg["serve"]["engine"]["slots"])
        prefills = [r for r in self.records if self._in_window(r.t_send)]
        prompt_tokens = sum(len(r.prompt) for r in prefills)
        # every hit is the template's whole pages (serve_window's cap)
        hit = min(self.saved // max(len(prefills), 1),
                  int(self.mix["template_tokens"]) // page * page)
        prefilled = prompt_tokens - hit * len(prefills)
        decoded = self._decoded()
        in_window = [c for c, s in decoded if self._in_window(s)]
        late = loadgen.lateness(self.records, self.t0, self.t1)
        groups = [window_group(s) for s in self._stats_at]
        slid = groups[-1].get("pages_slid", 0) - groups[0].get("pages_slid", 0)
        written = prefilled + len(in_window)
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
                held / (a.held[1] * w.expert_layers(a)
                        * max(len(in_window) / slots, 1.0))
                if picks else None),
            kv_bytes_per_token=st.get("kv_bytes_per_token"),
            kv_window_pages_per_stream_peak=(
                max(g["stream_pages_max"] for g in groups)
                if groups[-1] else None),
            kv_slid_pages_per_ktok=(1e3 * slid / written
                                    if groups[-1] and written else None))
        ctx = {"window_s": self.t1 - self.t0, "counters": self.counters,
               "kernel_work": {},
               "required_flops": (
                   sum(w.serve_flops(a, hit, len(r.prompt) - hit)
                       for r in prefills)
                   + sum(w.serve_flops(a, c, 1) for c in in_window)
                   + 2.0 * w.expert_params(a) * held)}
        if traced is not None and traced[0] is not None:
            t0, t1 = traced
            live = [c for c, s in decoded if t0 <= s <= t1]
            kv = sum(w.decode_kv_bytes(a, c, page) for c in live)
            fl = sum(w.decode_attn_flops(a, c) for c in live)

            def step_work(executions: int) -> dict:
                """The mean traced step: the traced stretch's decode
                tokens and the K/V they read over its executions."""
                return w.decode_step_work(a, len(live) / executions,
                                          kv / executions, fl / executions)

            ctx["kernel_work"]["decode_step"] = step_work
            # the paged decode kernel over the whole traced stretch: the
            # live K/V of every traced decode token once a layer, both
            # kinds and the window kind alone
            ctx["kernel_work"]["mixed_heads_attn"] = {"flops": fl,
                                                      "bytes": kv}
            ctx["kernel_work"]["window_heads_attn"] = {
                "flops": sum(w.decode_attn_flops(a, c, windows=True)
                             for c in live),
                "bytes": sum(w.decode_kv_bytes(a, c, page, windows=True)
                             for c in live)}
        return ctx

    # -- after the window ----------------------------------------------------
    def _gaps(self, precision: str, seqs=None) -> list[tuple]:
        """``serve.Run._gaps`` through this family's reference, with the
        floor of near-ties under ``logit_gap_per_tie``."""
        lim = self.cfg["limits"]
        if self.sample is None:
            return [(n, float("nan"), lim[n],
                     "no request finished inside the window")
                    for n in ("logit_gap_per_tie", "logit_gap_max")]
        spans = self.sample[1]
        gaps, margins = (np.concatenate(t) for t in
                         reference_laguna.serve_logit_gaps(
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
        """One of ``reference_laguna.FAULTS``: at each sampled position
        the token that the faulted reference puts first.
        ``altered_token``: as ``serve.Run``."""
        if kind in reference_laguna.FAULTS:
            return self._gaps(kind)
        return super().fault(kind)
