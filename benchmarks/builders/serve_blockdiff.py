"""A serving cell of an SDAR-family configuration served by block
diffusion (block-causal attention with head-wise q/k norm, every expert
held; a step forwards a block of B positions a slot, fixes the masked
positions it is most confident of and commits whole blocks):
``serve.Run`` with what is wired to ``lib.reference`` replaced — the
``Arch``, the required FLOPs, the comparison (``lib.reference_sdar``),
the block step's and the block kernel's required work
(``lib.work_block``) — the expert layer's pick counters as
``serve_latent`` reads them, and the engine's block books
(``stats()["block_diffusion"]``): live slot-steps, positions fixed,
commits.

The configuration states its block keys (``block_length``,
``denoising_steps``, ``mask_token_id``) once, at its top level, where
the reference reads them; the builder hands them to the program's
configuration. The head-wise q/k norms' weights are not ``lib.weights``'s
1: the builder replaces them in the seeded model with
``reference_sdar.qk_norm_leaf_f32``'s draw, which the reference makes
too (the configuration's ``assumed`` says why).

The comparison replays what the timed path served. A finished stream's
final poll hands back each of its blocks as it ended and the denoising
step each position was fixed at (``GenerationEngine.poll``'s
``blocks``), which the builder keeps by prompt; for
``compare_requests`` finished requests (the longest and the rest drawn
from the seed) and ``compare_blocks`` of their blocks (the first, the
last, the rest drawn from the seed), the reference recomputes each
denoising step's forward with the block as it stood then, and three
numbers come out:

- ``logit_gap_per_tie``, ``logit_gap_max``: each fixed token's logit
  below the reference's best at its position, as the other cells have
  them (summed over the near-ties of the sample, floored at
  ``compare_min_ties``; the widest);
- ``confidence_gap_max``: at each step, the reference's confidence (log
  probability of its best token) that the step's ``n``-th most
  confident masked position reaches, less that of a position the
  program fixed — 0 where the program fixed the reference's ``n`` most
  confident positions; the widest. It sees the order of fixing, not only
  the tokens.

Readings beside the float8 control, each a forward in the program's
place at the program's block states: ``causal_in_block`` (the reference
with a plain causal mask: the comparison sees the in-block attention),
``no_qk_norm`` (the head-wise norms left out), ``left_to_right`` (the
float32 reference's own tokens, fixed at the leftmost masked positions,
the schedule's count a step: only the confidence gap sees the order);
and, on the program's own record, ``altered_token`` (one fixed token
replaced by its successor in the vocabulary).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import loadgen, reference_sdar, traffic, work_block
from . import common, serve
from .serve_latent import HELD, PICKS, per_tie

NAMES = ("logit_gap_per_tie", "logit_gap_max", "confidence_gap_max")
BLOCK_KEYS = ("block_length", "denoising_steps", "mask_token_id")
OTHER = ("fp8", "causal_in_block", "no_qk_norm")


def block_books(stats: dict) -> dict:
    """The engine's block-diffusion block of ``stats()``; empty for a
    program that has none."""
    return stats.get("block_diffusion") or {}


def fixing(masked: np.ndarray, n: int, conf=None) -> np.ndarray:
    """The ``n`` masked positions a step fixes: those of highest ``conf``
    (equal ones the lower first) or, with none, the leftmost."""
    order = (np.flatnonzero(masked) if conf is None else
             np.argsort(-np.where(masked, conf, -np.inf), kind="stable"))
    fix = np.zeros_like(masked)
    fix[order[:n]] = True
    return fix


def with_qk_norm(model, key):
    """``model`` with every head-wise q/k norm's weights drawn by the
    reference's ``qk_norm_leaf_f32`` under its pytree path, layer by
    layer (the stack's leaves are scanned)."""
    def fix(path, leaf):
        name = jax.tree_util.keystr(path)
        if not name.endswith(reference_sdar.QK_NORM):
            return leaf
        return jax.vmap(lambda l: reference_sdar.qk_norm_leaf_f32(
            key, name, l, leaf.shape[1:], leaf.dtype))(
                jnp.arange(leaf.shape[0])).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(fix, model)


class Run(serve.Run):
    def __init__(self, cell, seed: int, devices):
        super().__init__(cell, seed, devices)
        prog = dict(self.cfg["program"])
        prog["config_args"] = dict(prog["config_args"],
                                   **{k: self.cfg[k] for k in BLOCK_KEYS})
        self.cfg = dict(self.cfg, program=prog)

    def _arch(self):
        return reference_sdar.Arch.from_config(self.cfg)

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        """``serve.Run.setup`` with the q/k norms' weights drawn as the
        reference draws them (``common.seeded_model`` wrapped for the
        call, as ``serve_state`` draws the decay); then the engine's
        ``start`` and ``poll`` wrapped, so that each stream started from
        here on leaves its record, by prompt, at its final poll."""
        plain = common.seeded_model
        common.seeded_model = lambda template, key: with_qk_norm(
            plain(template, key), key)
        try:
            super().setup()
        finally:
            common.seeded_model = plain
        self._blocks, started = {}, {}
        start, poll = self.engine.start, self.engine.poll

        def start_(prompt, *args, **kw):
            gen_id = start(prompt, *args, **kw)
            started[gen_id] = np.asarray(prompt, np.int32).tobytes()
            return gen_id

        def poll_(gen_id, *args, **kw):
            doc = poll(gen_id, *args, **kw)
            if doc["done"] and gen_id in started:
                self._blocks[started.pop(gen_id)] = doc.get("blocks")
            return doc

        self.engine.start, self.engine.poll = start_, poll_

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, tracer=None) -> None:
        """``serve.Run.window`` with every ``stats()`` it reads kept,
        and the first one after the profiler stopped marked: the block
        books over the traced stretch."""
        self._stats_at, self._stats_traced = [], None
        real = self.engine.stats

        def stats():
            self._stats_at.append(real())
            if (self._stats_traced is None and tracer is not None
                    and tracer.t1 is not None):
                self._stats_traced = self._stats_at[-1]
            return self._stats_at[-1]

        self.engine.stats = stats
        try:
            super().window(seconds, tracer)
        finally:
            del self.engine.stats

    def trace_context(self, traced=None) -> dict:
        """The window's prefills and their prefix hits, the load
        generator's lateness, the required FLOPs of every prompt token
        prefilled and every token served (a served token costs ``steps
        + 1`` forwards of a position), the pick counters and the block
        books; over the traced stretch, the block step's and the block
        kernel's required work."""
        a = self._arch()
        B = a.block
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
        # every served token, by the first position of its block
        served = [((len(r.prompt) + i) // B * B, s) for r in self.records
                  for i, s in enumerate(r.stamps)]
        in_window = [f for f, s in served if self._in_window(s)]
        late = loadgen.lateness(self.records, self.t0, self.t1)
        b0, b1 = block_books(st0), block_books(st)
        slot_steps = b1.get("slot_steps", 0) - b0.get("slot_steps", 0)
        fixed = b1.get("tokens_fixed", 0) - b0.get("tokens_fixed", 0)
        slots = int(self.cfg["serve"]["engine"]["slots"])
        self.counters.update(
            prefill_calls=len(prefills), prefilled_tokens=prefilled,
            prefix_token_share=(100.0 * hit * len(prefills) / prompt_tokens
                                if prompt_tokens else None),
            gen_late_p99_ms=(1e3 * loadgen.percentile(late, 99)
                             if late else None),
            moe_held_pick_share=100.0 * held / picks if picks else None,
            # held picks an expert a step: a step is the window's live
            # slot-steps over the slots (prefill picks are in the
            # numerator, as in the other cells)
            moe_tokens_per_held_expert=(
                held / (a.experts * a.layers * max(slot_steps / slots, 1.0))
                if picks else None),
            kv_bytes_per_token=st.get("kv_bytes_per_token"),
            # positions fixed per live slot-step: B / (steps + 1) when
            # every block takes its steps and a commit of its own
            block_tokens_per_slot_step=(fixed / slot_steps
                                        if slot_steps else None))
        ctx = {"window_s": self.t1 - self.t0, "counters": self.counters,
               "kernel_work": {},
               "required_flops": (
                   sum(work_block.serve_flops(a, hit, len(r.prompt) - hit)
                       for r in prefills)
                   + sum(work_block.output_token_flops(a, f)
                         for f in in_window))}
        if traced is not None and traced[0] is not None:
            t0, t1 = traced
            # a served token stands for (steps + 1) / B slot-steps of
            # its block
            firsts = [f for f, s in served if t0 <= s <= t1]
            per = (a.steps + 1) / B
            kv = per * sum(work_block.block_kv_bytes(a, f, page)
                           for f in firsts)
            fl = per * sum(work_block.block_attn_flops(a, f) for f in firsts)
            bt = block_books(self._stats_traced or st)
            traced_steps = bt.get("slot_steps", 0) - b0.get("slot_steps", 0)

            def step_work(executions: int) -> dict:
                """The mean traced block step: the traced stretch's live
                slot-steps and the K/V they read over its executions."""
                return work_block.block_step_work(
                    a, traced_steps / executions, kv / executions,
                    fl / executions)

            ctx["kernel_work"]["decode_step"] = step_work
            # the block kernel over the whole traced stretch: each
            # slot-step's live pages once a layer and its B rows
            ctx["kernel_work"]["block_attn"] = {"flops": fl, "bytes": kv}
        return ctx

    # -- after the window ----------------------------------------------------
    def _picked(self):
        """The compared requests (``serve.Run._sample``'s) and, for
        each, its compared blocks from the engine's record: the first,
        the last and the rest drawn from the seed. None where a request
        has no record."""
        if self.sample is None:
            return None
        seqs, spans = self.sample
        want = int(self.mix["compare_blocks"])
        rng = traffic.rng(self.seed, 7)
        rows = []
        for i, (n0, _) in enumerate(spans):
            rec = self._blocks.get(seqs[i, :n0].tobytes())
            if not rec:
                return None
            mid = np.arange(1, len(rec) - 1)
            pick = rng.choice(mid, size=min(want - 2, mid.size),
                              replace=False) if mid.size else []
            keep = sorted({0, len(rec) - 1, *(int(j) for j in pick)})
            rows.append([(int(rec[j][0]), np.asarray(rec[j][1], np.int32),
                          np.asarray(rec[j][2], np.int32)) for j in keep])
        return rows

    def _gaps(self, kind: str, seqs=None, blocks=None) -> list[tuple]:
        """The three compared numbers of the program's record (or of the
        one given); for a ``kind`` of ``OTHER`` that forward's picks, and
        for ``left_to_right`` the float32 one's leftmost, in the
        program's place at its block states."""
        lim = self.cfg["limits"]
        blocks = self._picked() if blocks is None else blocks
        if self.sample is None or blocks is None:
            return [(n, float("nan"), lim[n],
                     "no request finished inside the window"
                     if self.sample is None else "a request has no record")
                    for n in NAMES]
        a = self._arch()
        seqs = self.sample[0] if seqs is None else seqs
        R, n = len(blocks), int(self.mix["compare_blocks"]) * a.steps
        p0 = np.zeros((R, n), np.int32)
        states = np.full((R, n, a.block), a.mask_id, np.int32)
        tokens = np.zeros((R, n, a.block), np.int32)
        where = []                       # (row, state, fixed_at, step)
        for r, rows in enumerate(blocks):
            j = 0
            for first, ids, fixed in rows:
                for s in range(int(fixed.max()) + 1):
                    p0[r, j] = first
                    states[r, j] = np.where(fixed < s, ids, a.mask_id)
                    tokens[r, j] = ids
                    where.append((r, j, fixed, s))
                    j += 1
        got = reference_sdar.replay(a, self.seed, seqs, p0, states, tokens,
                                    kind=kind if kind in OTHER
                                    else "float32")
        gaps, margins, conf = [], [], []
        for r, j, fixed, s in where:
            masked, n = fixed >= s, int((fixed == s).sum())
            c = (got["best"] - got["lse"])[r, j]
            if kind in OTHER:
                # the other forward's picks: its n most confident masked
                # positions, each its best token
                fix = fixing(masked, n, got["other_conf"][r, j])
                at = got["at_other"][r, j]
            elif kind == "left_to_right":
                fix, at = fixing(masked, n), got["best"][r, j]
            else:
                fix, at = fixed == s, got["at_token"][r, j]
            if not fix.any():
                continue
            nth = np.sort(c[masked])[::-1][int(fix.sum()) - 1]
            gaps.extend(got["best"][r, j][fix] - at[fix])
            margins.extend((got["best"] - got["second"])[r, j][fix])
            conf.extend(np.maximum(nth - c[fix], 0.0))
        gaps, margins, conf = (np.asarray(x, np.float64)
                               for x in (gaps, margins, conf))
        ties = int((margins < float(self.mix["compare_margin"])).sum())
        floor = int(self.mix.get("compare_min_ties", 1))
        at, cat = int(gaps.argmax()), int(conf.argmax())
        return [("logit_gap_per_tie",
                 per_tie(float(gaps.sum()), ties, floor),
                 lim["logit_gap_per_tie"],
                 f"{gaps.size} fixed tokens in {len(where)} denoising steps "
                 f"of {sum(map(len, blocks))} blocks of {R} requests, "
                 f"{ties} near-ties (floor {floor}), "
                 f"{int((gaps > 0).sum())} not the reference's choice, "
                 f"mean gap {float(gaps.mean()):.3g}"),
                ("logit_gap_max", float(gaps[at]), lim["logit_gap_max"],
                 f"at fixed token {at} of {gaps.size}"),
                ("confidence_gap_max", float(conf[cat]),
                 lim["confidence_gap_max"],
                 f"at fixed token {cat} of {conf.size}, "
                 f"{int((conf > 0).sum())} out of the reference's order")]

    def compare(self) -> list[tuple]:
        errors = [r.error for r in self.records if r.error]
        rows = [("request_errors", float(len(errors)), 0.0,
                 errors[0][:120] if errors else "none"),
                ("engine_broken", 0.0 if not self.broken else 1.0, 0.0,
                 str(self.broken)[:120])]
        self.sample = self._sample()
        return rows + self._gaps("float32")

    # -- readings that set the limits (benchmarks/readings.py) ---------------
    def control(self) -> list[tuple]:
        """The reference in float8 in the program's place: at each
        replayed step the positions and tokens the float8 forward puts
        first."""
        return self._gaps("fp8")

    def fault(self, kind: str) -> list[tuple]:
        """``causal_in_block`` / ``no_qk_norm``: the reference so
        altered in the program's place; ``left_to_right``: the float32
        reference fixing the leftmost masked positions there.
        ``altered_token``: one fixed token of a compared block, drawn
        from the seed, replaced by its successor in the vocabulary, in
        the record and in the served sequence."""
        if kind != "altered_token":
            return self._gaps(kind)
        blocks = self._picked()
        if self.sample is None or blocks is None:
            return self._gaps(kind)
        rng = traffic.rng(self.seed, 6)
        seqs = self.sample[0].copy()
        r = int(rng.integers(len(blocks)))
        b = int(rng.integers(len(blocks[r])))
        first, ids, fixed = blocks[r][b]
        i = int(rng.choice(np.flatnonzero(fixed >= 0)))
        ids = ids.copy()
        ids[i] = (ids[i] + 1) % self.cfg["vocab_size"]
        if ids[i] == self._arch().mask_id:
            ids[i] = (ids[i] + 1) % self.cfg["vocab_size"]
        if first + i < seqs.shape[1]:
            seqs[r, first + i] = ids[i]
        blocks = [list(rows) for rows in blocks]
        blocks[r][b] = (first, ids, fixed)
        return self._gaps(kind, seqs=seqs, blocks=blocks)
