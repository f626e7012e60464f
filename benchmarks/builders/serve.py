"""A serving cell: the configuration's model behind ``io.InferenceServer``
on a loopback port, a ``GenerationEngine`` from ``add_generator``, and
the traffic file's closed loop of ``InferenceClient.generate`` streams.

Set-up makes the weights on the device in one jitted call from the seed,
warms every shape the traffic uses (one request per prefill bucket, the
decode step) and starts the clients with staggered first requests; the
window opens once every client has finished one. The clients run on
through the window; it closes by the clock, each client then waits at
most for a first token and cancels the rest. ``correct`` compares a
sample of the requests finished inside the window with the plain
reference, once the engine is gone.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax
import numpy as np

from ..lib import flops, loadgen, reference, traffic
from ..lib import weights as W
from . import common

NAME = "bench"


class Run:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, list(devices)
        self.cfg, self.mix = cell.config, cell.traffic
        self.counters: dict = {}

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        from paddle_tpu import io

        template = common.model_template(self.cfg)
        model = jax.jit(lambda k: common.seeded_model(template, k))(
            W.root_key(self.seed))
        self.server = io.InferenceServer(port=0).start()
        self.engine = self.server.add_generator(
            NAME, model, **self.cfg["serve"]["engine"])
        del model
        endpoint = self.server.endpoint

        @contextlib.contextmanager
        def sender():
            with io.InferenceClient(endpoint) as client:
                yield lambda prompt, n: client.generate(NAME, prompt, n)

        self.source = traffic.requests(self.mix, self.seed,
                                       self.cfg["vocab_size"])
        # one request per prefill bucket the mix reaches (the first also
        # fills the template's pages), two tokens each: compiles or loads
        # every program of the window
        first, _ = next(self.source)
        tmpl = first[:int(self.mix["template_tokens"])]
        rng = traffic.rng(self.seed, 5)
        with sender() as send:
            for n in self.mix["warm_item_tokens"]:
                item = rng.integers(1, self.cfg["vocab_size"], n,
                                    dtype=np.int32)
                list(send(np.concatenate([tmpl, item]), 2))
        n = int(self.mix["clients"])
        self.loop = loadgen.ClosedLoop(
            sender, self.source, n,
            [int(self.mix["stagger_tokens"][0]
                 + c * self.mix["stagger_tokens"][1]) for c in range(n)])
        self.loop.start()
        while min(self.loop.completed()) < 1:
            time.sleep(0.05)

    # -- the measured window -------------------------------------------------
    @staticmethod
    def _tokens_saved() -> int:
        from paddle_tpu.core import monitor

        return monitor.get_stat("gen/prefix_tokens_saved") or 0

    def window(self, seconds: float, tracer=None) -> None:
        before = self._tokens_saved()
        pages_free_min = self.engine.stats().get("pages_free")
        self.t0 = time.perf_counter()
        while (now := time.perf_counter()) - self.t0 < seconds:
            time.sleep(min(0.25, max(self.t0 + seconds - now, 0.0)))
            if tracer is not None:
                tracer.tick()
            free = self.engine.stats().get("pages_free")
            if free is not None:
                pages_free_min = min(pages_free_min, free)
        self.t1 = self.t0 + seconds
        self.saved, st = self._tokens_saved() - before, self.engine.stats()
        self.closed = self.loop.close()
        self.records = list(self.loop.records)
        if st.get("pages"):
            self.counters["pages_used_peak_share"] = 100.0 * (
                1 - pages_free_min / st["pages"])
        self.broken = st.get("broken")

    def _in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def attempted(self) -> tuple[int, int]:
        sent = [r for r in self.records if self._in_window(r.t_send)]
        failed = sum(1 for r in sent if r.error is not None)
        return len(sent), failed + (0 if self.closed else 1)

    def end_to_end(self) -> dict:
        r, t0, t1 = self.records, self.t0, self.t1
        return {
            "serve_out_tok_s": loadgen.tokens_in_window(r, t0, t1)
            / (t1 - t0),
            "itl_p95_s": loadgen.percentile(
                loadgen.inter_token_gaps(r, t0, t1), 95),
            "ttft_p90_s": loadgen.percentile(
                loadgen.first_token_times(r, t0, t1), 90),
        }

    def trace_context(self, traced=None) -> dict:
        """What the per-layer readers need beside the trace. A prompt
        counts for the window when it was sent inside it: a closed loop
        always has a free slot, so that is when the engine admitted it
        and booked its prefix hit."""
        a = reference.Arch.from_config(self.cfg)
        prefills = [r for r in self.records if self._in_window(r.t_send)]
        prompt_tokens = sum(len(r.prompt) for r in prefills)
        prefilled = prompt_tokens - self.saved
        share = self.saved / max(prompt_tokens, 1)
        hit = self.saved // max(len(prefills), 1)   # every hit is the template
        need = sum(flops.serve_flops(a, hit, len(r.prompt) - hit)
                   for r in prefills)               # the uncached tails
        decoded = [(len(r.prompt) + i, s) for r in self.records
                   for i, s in enumerate(r.stamps) if i > 0]
        need += sum(flops.serve_flops(a, ctx, 1) for ctx, s in decoded
                    if self._in_window(s))
        late = loadgen.lateness(self.records, self.t0, self.t1)
        self.counters.update(
            prefill_calls=len(prefills), prefilled_tokens=prefilled,
            prefix_token_share=100.0 * share if prompt_tokens else None,
            gen_late_p99_ms=(1e3 * loadgen.percentile(late, 99)
                             if late else None))
        return {"required_flops": need, "window_s": self.t1 - self.t0,
                "counters": self.counters, "kernel_work": {}}

    # -- after the window ----------------------------------------------------
    def free(self) -> None:
        self.server.stop()
        self.engine.close()
        del self.engine, self.server, self.loop
        jax.clear_caches()
        gc.collect()

    def _sample(self):
        """The longest request finished in the window and a few more
        drawn from the seed: prompts with their served tokens, padded to
        one length."""
        done = [r for r in self.records if r.done and r.tokens
                and self._in_window(r.t_send)
                and self._in_window(r.stamps[-1])]
        if not done:
            return None
        done.sort(key=lambda r: -(len(r.prompt) + len(r.tokens)))
        k = min(int(self.mix["compare_requests"]), len(done)) - 1
        picks = traffic.rng(self.seed, 4).choice(
            np.arange(1, len(done)), size=k, replace=False) if k else []
        rows = [done[0]] + [done[int(i)] for i in picks]
        width = int(self.mix["compare_pad_tokens"])
        seqs = np.zeros((len(rows), width), np.int32)
        spans = []
        for i, r in enumerate(rows):
            n0, n = len(r.prompt), len(r.prompt) + len(r.tokens)
            seqs[i, :n0], seqs[i, n0:n] = r.prompt, r.tokens
            spans.append((n0, n))
        return seqs, spans

    def _gaps(self, precision: str, seqs=None) -> list[tuple]:
        """Two numbers from the gaps by which the sampled served tokens'
        logits lie below the reference's best.

        ``logit_gap_per_tie``: their mean, divided by the share of those
        positions at which the reference itself is nearly tied (its best
        two logits under ``compare_margin`` apart). A served token can
        leave the reference's choice only at a near-tie, so the mean gap
        grows with the density of near-ties, which varies twentyfold
        from seed to seed, times the square of the logit error; the
        quotient keeps the error alone, and is what a lower precision
        fails. ``logit_gap_max``: the widest gap, which swings too much
        to catch a precision but which one wrong token fails."""
        arch = reference.Arch.from_config(self.cfg)
        lim = self.cfg["limits"]
        spans = self.sample[1]
        gaps, margins = (np.concatenate(t) for t in
                         reference.serve_logit_gaps(
                             arch, self.seed,
                             self.sample[0] if seqs is None else seqs,
                             spans, precision))
        ties = int((margins < float(self.mix["compare_margin"])).sum())
        at = int(gaps.argmax())
        return [("logit_gap_per_tie", float(gaps.sum() / max(ties, 1)),
                 lim["logit_gap_per_tie"],
                 f"{gaps.size} served tokens of {len(spans)} requests, "
                 f"{ties} near-ties, {int((gaps > 0).sum())} not the "
                 f"reference's choice, mean gap {float(gaps.mean()):.3g}"),
                ("logit_gap_max", float(gaps[at]), lim["logit_gap_max"],
                 f"at sampled token {at} of {gaps.size}")]

    def compare(self) -> list[tuple]:
        errors = [r.error for r in self.records if r.error]
        rows = [("request_errors", float(len(errors)), 0.0,
                 errors[0][:120] if errors else "none"),
                ("engine_broken", 0.0 if not self.broken else 1.0, 0.0,
                 str(self.broken)[:120])]
        self.sample = self._sample()
        if self.sample is None:
            lim = self.cfg["limits"]
            return rows + [(n, float("nan"), lim[n],
                            "no request finished inside the window")
                           for n in ("logit_gap_per_tie", "logit_gap_max")]
        return rows + self._gaps("float32")

    # -- readings that set the limits (benchmarks/readings.py) ---------------
    def control(self) -> list[tuple]:
        """The reference in float8 in the program's place: at each
        sampled position the token that the float8 forward puts first."""
        return self._gaps("fp8")

    def fault(self, kind: str) -> list[tuple]:
        """``altered_token``: one served token of the sample, drawn from
        the seed, replaced by its successor in the vocabulary."""
        assert kind == "altered_token", kind
        seqs, spans = self.sample
        seqs = seqs.copy()
        rng = traffic.rng(self.seed, 6)
        row = int(rng.integers(len(spans)))
        pos = int(rng.integers(*spans[row]))
        seqs[row, pos] = (seqs[row, pos] + 1) % self.cfg["vocab_size"]
        return self._gaps("float32", seqs)
