"""Chip smoke: the train step and the generation server, once, on the TPU.

    python chip_smoke.py

Drives the two hot paths through the entry points users call, at the
full width of the 953M Llama-shaped decoder (:func:`headline_config`;
only step and request counts are small, weights are random from a
seed), in ONE process that holds the chip throughout:

1. train — ``fleet.build_train_step`` -> ``init_state`` -> ``shard_batch``
   -> a few AdamW steps on one repeated batch (finite loss/grad-norm,
   loss falls);
2. parity — logits of a training forward, a prefill and an engine-style
   vmapped cached decode step, compiled kernels vs the same code under
   ``ops.pallas.force_interpret()``;
3. serve — the trained weights behind ``io.InferenceServer`` on a
   loopback port, a contiguous-cache and a paged+prefix-cache generator,
   concurrent ``InferenceClient.generate`` streams from threads; then
   small models of the other serving families — latent attention, window
   and full layers, a recurrent state group, block diffusion
   (``block_phase``: the block step through ``ptpu_paged_block_attn``);
4. on a host with >= 4 chips, additionally ZeRO-3 x4 training and
   ``mesh_tp=4`` serving, with every Pallas unit asserted on the kernel
   arm of its per-shard (shard_map) dispatch and state spread over all
   devices.

Every program it runs is first lowered and checked for the Mosaic
kernels it must contain (by ``pallas_call`` name), so a silent jnp
fallback at the headline shapes fails the smoke. Exits non-zero, printing
no result line, unless ``jax.default_backend()`` is ``"tpu"`` and every
phase passed; otherwise the LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

The phase functions take the model config and sizes as arguments —
``tests/test_chip_smoke.py`` runs them on the CPU with
``LlamaConfig.tiny`` — and ``on_chip`` switches the assertions that only
mean something on the device (kernel names, dispatch mode, platform).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import threading
import time

import numpy as np

# Pallas kernels (``pallas_call(name=...)``) each lowered program must
# contain at the headline shapes. The paged prefill attends its chunk
# against the gathered page context through the einsum path (the flash
# kernel has no cache-offset form), so it carries no flash kernel; a
# decode step is T=1, below the norm/rope kernels' row blocks.
TRAIN_KERNELS = (
    "ptpu_flash_fwd", "ptpu_flash_bwd_dq", "ptpu_flash_bwd_dkv",
    "ptpu_rms_norm_fwd", "ptpu_rms_norm_bwd", "ptpu_rope",
    "ptpu_linear_xent_fwd", "ptpu_linear_xent_dh", "ptpu_linear_xent_dw")
PREFILL_KERNELS = {
    "contiguous": ("ptpu_flash_fwd", "ptpu_rms_norm_fwd", "ptpu_rope"),
    "paged": ("ptpu_rms_norm_fwd", "ptpu_rope"),
}
# the contiguous step runs the stacked-cache kernel under the engine's
# vmap; the paged step on one chip attends through the page table with
# the paged kernel, one call a layer for all slots. Under a mesh of
# several devices the paged layout has no per-shard unit: that step
# gathers a layer's pages for the einsum arm and holds no decode kernel
# (``serve_phase`` expects none there).
DECODE_KERNELS = {"contiguous": ("ptpu_decode_attn",),
                  "paged": ("ptpu_paged_decode_attn",)}
# the paged step of a latent-attention (MLA) model: the same dispatch,
# the latent arm of the same module
LATENT_DECODE_KERNEL = "ptpu_paged_latent_decode_attn"
# the one-token step of a KDA layer (a recurrent state read and written
# once, in place): the state group's step holds one call a KDA layer
KDA_STEP_KERNEL = "ptpu_kda_step"
# a block-diffusion step: the K/V kernel's copy form at B query rows a
# slot, each slot's live pages read once a layer for the whole block
BLOCK_ATTN_KERNEL = "ptpu_paged_block_attn"
# per-shard (shard_map) units the four-chip programs must take on the
# kernel arm (``ops.pallas.partition_stats()`` keys ``<unit>:kernel``)
TRAIN_UNITS = ("flash_fwd", "flash_bwd", "rms_fwd", "rms_bwd", "rope",
               "flce_fwd", "flce_dh", "flce_dw")
SERVE_UNITS = ("flash_fwd", "rms_fwd", "rope", "decode_attn")

# Logit agreement between two evaluations of the same mathematics
# (compiled vs interpreted kernels; chunked prefill or a cached decode
# step vs the one-shot prefill), as the rms difference over the rms
# logit and the largest difference over the largest logit. Both sides
# feed the MXU the same bf16 operands and
# accumulate in f32; they differ in accumulation order, in the exp/rsqrt
# implementations and in what XLA fuses around the kernels, hence in
# where each activation lands when it rounds to bf16 (eps = 2^-8 =
# 3.9e-3 per flipped rounding, compounding over 16 layers into
# near-uniform random-weight logits). Measured on the v5e at these
# widths: this bf16 pipeline sits rms 2.0e-2 from an f32
# precision-"highest" jnp reference, compiled and interpreted kernels
# 0.9-1.9e-2 apart, chunked and one-shot prefill 2.3-2.9e-2 apart, the
# vmapped decode step 2.0e-2 from the one-shot prefill. The bounds leave
# ~2x on that floor; an 8-bit intermediate (eps >= 6e-2 per rounding)
# or a dropped term lands far outside them.
LOGIT_RMS_RTOL = 5e-2
LOGIT_MAX_RTOL = 8e-2
# page size of the parity phase's paged decode: the engine's default
# (``FLAGS_gen_page_tokens``)
PARITY_PAGE_TOKENS = 16


class SmokeFailure(AssertionError):
    """A smoke check did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {message}", flush=True)


def headline_config():
    """The 953M Llama-shaped decoder the smoke trains and serves."""
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=16, num_heads=16, num_kv_heads=16, max_seq_len=2048,
        dtype="bfloat16", remat=True, remat_policy="save_mlp_dots_attn",
        lm_head_mode="fused")


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: np.ndarray
    new_tokens: int
    sampling: dict          # generate() kwargs; empty = greedy


def make_requests(vocab: int, prompt_lens, new_tokens, shared_prefix: int,
                  seed: int = 0) -> list[Request]:
    """One request per prompt length. The LAST TWO prompts share their
    first ``shared_prefix`` tokens (the prefix-cache case); request 1 is
    sampled, the rest greedy; request 0 is the greedy probe repeated
    after the others."""
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(1, vocab, n).astype(np.int32)
               for n in prompt_lens]
    prompts[-1][:shared_prefix] = prompts[-2][:shared_prefix]
    sampled = dict(temperature=0.8, top_k=40, top_p=0.95, seed=7)
    return [Request(p, n, sampled if i == 1 else {})
            for i, (p, n) in enumerate(zip(prompts, new_tokens))]


def headline_requests(vocab: int) -> list[Request]:
    """Prompts over 128-1024 tokens (prefill buckets 128/256/512/1024),
    32-64 new tokens, two 384-token prompts sharing a 256-token prefix."""
    return make_requests(vocab, (128, 200, 1024, 640, 384, 384),
                         (64, 32, 40, 32, 48, 48), shared_prefix=256)


class CompileMeter:
    """Where set-up time went, from jax's own monitoring events: seconds
    the backend spent compiling, seconds spent fetching and loading
    executables the persistent cache already held, the cache's hit/miss
    counts, and the jitted functions that cost the most (on a warm run:
    the ones the cache did not serve)."""

    def __init__(self):
        import jax

        self.backend_seconds = 0.0      # compile or cache load, per program
        self.retrieval_seconds = 0.0    # the cache-load part of it
        self.by_function: dict = {}     # jitted function name -> seconds
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, fun_name: str = "?",
                  **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_seconds += seconds
            self.programs += 1
            self.by_function[fun_name] = (
                self.by_function.get(fun_name, 0.0) + seconds)
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieval_seconds += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def report(self) -> dict:
        return {"compile_seconds": round(self.backend_seconds
                                         - self.retrieval_seconds, 1),
                "cache_load_seconds": round(self.retrieval_seconds, 1),
                "programs": self.programs, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "slowest": {name: round(secs, 1) for name, secs in sorted(
                    self.by_function.items(), key=lambda kv: -kv[1])[:5]}}


def _missing(text: str, names) -> list[str]:
    return [n for n in names if n not in text]


def _check_dispatch(chips: int) -> None:
    """The kernel set must be about to compile for the device, not
    interpret or stay on jnp — asserted where the steps are built."""
    from paddle_tpu.ops import pallas as pk

    check(pk._support.interpret() is False,
          "Pallas kernels would run interpreted on this backend")
    want = "raw" if chips == 1 else "partitioned"
    check(pk.dispatch_mode() == want,
          f"dispatch_mode() is {pk.dispatch_mode()!r}, expected {want!r} "
          f"on {chips} chip(s)")


def _check_spread(what: str, devices, tree=None) -> list[int]:
    """State really lives on every device: each large leaf has a shard
    on all of them, and no device's live bytes are out of line with
    device 0's (same order: within 2x either way)."""
    import jax

    if tree is not None:
        for leaf in jax.tree_util.tree_leaves(tree):
            if leaf.size >= 1 << 20:
                check(len(leaf.sharding.device_set) == len(devices)
                      and not leaf.sharding.is_fully_replicated,
                      f"{what}: a {leaf.shape} leaf is not sharded over "
                      f"all {len(devices)} devices ({leaf.sharding})")
    used = [int(d.memory_stats()["bytes_in_use"]) for d in devices]
    check(all(used[0] / 2 <= u <= used[0] * 2 for u in used),
          f"{what}: bytes_in_use per device {used} — not spread")
    return used


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch: int, seq: int, steps: int, chips: int = 1,
                on_chip: bool = True):
    """``steps`` AdamW steps through ``fleet.build_train_step`` on one
    repeated batch over ``chips`` devices (ZeRO-3 when > 1). Returns
    ``(trained model, report)``; the optimizer state is dropped."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as optim
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.optimizer import lr as lr_mod
    from paddle_tpu.parallel import mesh as M

    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    strategy = dist.DistributedStrategy()
    if chips > 1:
        strategy.sharding.enable = True
        strategy.sharding.stage = 3
        strategy.sharding.degree = chips
    devices = jax.devices()[:chips]
    mesh = M.mesh_from_strategy(strategy, devices)
    report = {"chips": chips, "params_m": round(cfg.num_params() / 1e6, 1),
              "batch": batch, "seq": seq}
    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            model,
            optimizer=optim.AdamW(
                lr_mod.warmup_cosine(3e-4, 100, 10000),
                grad_clip=optim.ClipGradByGlobalNorm(1.0)),
            strategy=strategy, mesh=mesh)
        state = step.init_state(model)
        del model                    # the state owns the weights now
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        data = step.shard_batch({"input_ids": jnp.asarray(ids),
                                 "labels": jnp.asarray(ids)})
        if on_chip:
            _check_dispatch(chips)
            pk.reset_partition_stats()
            absent = _missing(
                step.lower(state, data, jax.random.PRNGKey(0)).as_text(),
                TRAIN_KERNELS)
            check(not absent, f"train step lowered without {absent}")
            report["kernels"] = list(TRAIN_KERNELS)

        losses, norms = [], []
        for i in range(steps):
            state, metrics = step(state, data, jax.random.PRNGKey(i))
            losses.append(float(metrics["loss"]))
            norms.append(float(metrics["grad_norm"]))
            log(f"train chips={chips} step {i}: loss={losses[-1]:.4f} "
                f"grad_norm={norms[-1]:.4f}")
        if on_chip and chips > 1:
            report["bytes_in_use"] = _check_spread(
                "ZeRO-3 train state", devices, state.model)
    report.update(losses=losses, grad_norms=norms)
    check(all(np.isfinite(losses)) and all(np.isfinite(norms)),
          f"non-finite loss/grad-norm: {losses} {norms}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the repeated batch: {losses}")
    if on_chip and chips > 1:
        report["partition_stats"] = _check_units("train", TRAIN_UNITS)
    return state.model, report


def _check_units(what: str, units) -> dict:
    """Every per-shard unit took its kernel arm."""
    from paddle_tpu.ops import pallas as pk

    stats = pk.partition_stats()
    fallbacks = sorted(k for k in stats if k.endswith(":fallback"))
    check(not fallbacks, f"{what}: units lowered to their jnp fallback: "
                         f"{fallbacks} ({stats})")
    absent = [u for u in units if not stats.get(f"{u}:kernel")]
    check(not absent, f"{what}: units never partitioned: {absent} "
                      f"({stats})")
    return dict(sorted(stats.items()))


# ---------------------------------------------------------------------------
# phase 2: compiled kernels vs the interpreter
# ---------------------------------------------------------------------------

def _parity_programs(seq: int):
    """Fresh jits of the five compared programs (the interpret flag is
    read while tracing, so each mode traces its own)."""
    import jax
    import jax.numpy as jnp

    def prefill(m, x):
        cache = m.init_cache(1, 2 * seq)
        return m.forward_with_cache(x, cache, index=0)

    def decode(m, caches, toks, fills):
        def one(cache, tok, fill):
            logits, _ = m.forward_with_cache(tok[None, None], cache,
                                             index=fill)
            return logits[0, -1]
        return jax.vmap(one)(caches, toks, fills)

    def paged_decode(m, cache, toks, fills):
        # the same slots read through a page table: the cache scattered
        # over a pool's pages in reverse order, the pool unmapped under
        # the vmap as in the engine's paged step
        from paddle_tpu.models.generation import (
            PagedCache, init_paged_cache, paged_scatter,
        )
        page = PARITY_PAGE_TOKENS
        row = jnp.arange(2 * seq // page, 0, -1, dtype=jnp.int32)
        pool = paged_scatter(init_paged_cache(cache, row.size, page), row,
                             cache, 0, page)

        def one(tok, fill):
            logits, _ = m.forward_with_cache(
                tok[None, None], PagedCache(pool, row), index=fill)
            return logits[0, -1]
        return jax.vmap(one)(toks, fills)

    def chunked_prefill(m, x):
        # the prompt's tail forwarded against its already-cached head:
        # what a prefix-cache hit and chunked prefill run
        head = seq - seq // 8
        cache = m.init_cache(1, 2 * seq)
        _, cache = m.forward_with_cache(x[:, :head], cache, index=0)
        logits, _ = m.forward_with_cache(x[:, head:], cache, index=head)
        return logits

    return (jax.jit(lambda m, x: m(x)), jax.jit(prefill), jax.jit(decode),
            jax.jit(paged_decode), jax.jit(chunked_prefill))


def parity_phase(model, *, seq: int, rms_rtol: float = LOGIT_RMS_RTOL,
                 max_rtol: float = LOGIT_MAX_RTOL) -> dict:
    """Logits of one training forward, one prefill, one chunked prefill
    and one engine-style cached decode step (vmapped over two slots with
    different fill positions — the batched scalar-prefetch form the
    engine sends the decode kernel through) on the contiguous cache and
    on the same cache behind a page table (the paged kernel over the
    slot axis), compiled vs traced under ``force_interpret()`` (all
    decode variants read the SAME cache); and, across paths, the chunked
    prefill and both decode steps against the one-shot prefill's logits
    at the same positions."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas as pk

    vocab = model.config.vocab_size
    ids = jnp.asarray(np.random.RandomState(1).randint(
        0, vocab, (1, seq)).astype(np.int32))
    # each decode slot re-feeds a prompt token at its own position, with
    # the cache filled up to it: the logits the one-shot prefill also
    # produced at that position
    fills = jnp.asarray([seq - 1, seq - 1 - seq // 4], jnp.int32)

    def run(cache=None):
        forward, prefill, decode, paged, chunked = _parity_programs(seq)
        full = forward(model, ids)
        pre, own_cache = prefill(model, ids)
        cache = own_cache if cache is None else cache
        slots = jax.tree_util.tree_map(lambda c: jnp.stack([c, c]), cache)
        dec = decode(model, slots, ids[0, fills], fills)
        pag = paged(model, cache, ids[0, fills], fills)
        tail = chunked(model, ids)
        return {"train_forward": full, "prefill": pre, "decode": dec,
                "paged_decode": pag, "chunked_prefill": tail}, cache

    compiled, cache = run()
    with pk.force_interpret():
        interpreted, _ = run(cache)

    head = seq - seq // 8
    pairs = [(f"{name}: compiled vs interpreted", got, interpreted[name])
             for name, got in compiled.items()]
    # and across paths: the tail prefilled against its cached head must
    # reproduce the one-shot prefill's logits at the same positions
    pairs.append(("chunked vs one-shot prefill",
                  compiled["chunked_prefill"], compiled["prefill"][:, head:]))
    for name in ("decode", "paged_decode"):
        pairs.append((f"{name} vs one-shot prefill",
                      compiled[name], compiled["prefill"][0, fills]))
    report = {}
    for name, got, ref in pairs:
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        check(np.isfinite(got).all(), f"parity {name}: non-finite logits")
        diff = got - ref
        err = {"rms_rel": float(np.sqrt(np.mean(diff ** 2))
                                / np.sqrt(np.mean(ref ** 2))),
               "max_rel": float(np.abs(diff).max() / np.abs(ref).max()),
               "argmax_agree": float(np.mean(
                   got.argmax(-1) == ref.argmax(-1)))}
        report[name] = err
        log(f"parity {name}: rms {err['rms_rel']:.2e} (tolerance "
            f"{rms_rtol:.0e}), max {err['max_rel']:.2e} (tolerance "
            f"{max_rtol:.0e}), argmax agrees {err['argmax_agree']:.4f}")
        check(err["rms_rel"] <= rms_rtol and err["max_rel"] <= max_rtol,
              f"parity {name}: logits differ by rms {err['rms_rel']:.3e} "
              f"/ max {err['max_rel']:.3e}")
    return report


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------

def _agree(a, b) -> int:
    """Length of the common prefix of two token lists."""
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return n


def _solo(model, r: Request) -> list:
    """Solo ``generate()`` of one request, its sampling included."""
    import jax

    from paddle_tpu.models.generation import generate

    kw = dict(r.sampling)
    if kw:
        kw["key"] = jax.random.PRNGKey(kw.pop("seed"))
    out = jax.jit(lambda m, x: generate(m, x, r.new_tokens, **kw))(
        model, r.prompt[None])
    return np.asarray(out)[0, r.prompt.size:].tolist()


def _drive(endpoint: str, name: str, engine, requests, vocab: int):
    """All requests as concurrent client streams (one thread and one
    connection each), then the greedy probe again — which must return
    the same tokens whenever it runs the same programs. Through a prefix
    cache it does not: the repeat prefills only the tail past its cached
    pages, a differently shaped program that may round differently in
    bf16, so that repeat is reported and the gated one follows
    ``clear_prefix_cache()``. The step's sampler has to sort while the
    sampled request (top-k, top-p) is live — a decode step for each of
    its tokens past the prefill's — and not once for the greedy probe
    alone. Returns ``(token lists, repeat report)``."""
    from paddle_tpu import io

    results: list = [None] * len(requests)
    errors: list = []

    def stream(i: int, r: Request) -> None:
        try:
            with io.InferenceClient(endpoint) as client:
                results[i] = list(client.generate(
                    name, r.prompt, r.new_tokens, **r.sampling))
        except Exception as e:           # surfaced below, with the index
            errors.append(f"stream {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=stream, args=(i, r), daemon=True)
               for i, r in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    check(not any(t.is_alive() for t in threads),
          f"{name}: streams still running after 900 s")
    check(not errors, f"{name}: {errors}")
    for i, (r, toks) in enumerate(zip(requests, results)):
        check(len(toks) == r.new_tokens,
              f"{name}: stream {i} returned {len(toks)} tokens, declared "
              f"{r.new_tokens}")
        check(all(0 <= t < vocab for t in toks),
              f"{name}: stream {i} returned an out-of-vocab token")
    probe = requests[0]
    sorted_steps = engine.stats()["sample_sorted_steps"]
    longest = max(r.new_tokens for r in requests if r.sampling)
    check(sorted_steps >= longest - 1,
          f"{name}: the sampler sorted on {sorted_steps} steps, the "
          f"sampled stream took {longest - 1}")

    def repeat() -> list:
        with io.InferenceClient(endpoint) as client:
            return list(client.generate(name, probe.prompt,
                                        probe.new_tokens))

    info = {}
    if engine.stats().get("prefix_entries"):
        info["through_prefix_cache_agrees_for"] = (
            f"{_agree(repeat(), results[0])}/{probe.new_tokens} tokens")
        engine.clear_prefix_cache()
    again = repeat()
    info["same_programs_identical"] = again == results[0]
    alone = engine.stats()["sample_sorted_steps"] - sorted_steps
    info.update(sample_sorted_steps=sorted_steps,
                greedy_probe_sorted_steps=alone)
    check(alone == 0,
          f"{name}: the sampler sorted on {alone} steps of the greedy "
          "probe alone")
    check(again == results[0],
          f"{name}: the greedy probe repeated after the others returned "
          f"different tokens (first {_agree(again, results[0])} agree)")
    return results, info


def _check_generator(name: str, g: dict, *, platform: str,
                     devices: int) -> None:
    check(g["broken"] is None, f"{name}: engine broken: {g['broken']}")
    check(not g["stuck"] and g["rebuilds"] == 0 and g["quarantined"] == 0,
          f"{name}: stuck={g['stuck']} rebuilds={g['rebuilds']} "
          f"quarantined={g['quarantined']}")
    check(g["device"]["platform"] == platform
          and g["device"]["devices"] == devices,
          f"{name}: serving from {g['device']}, expected {devices} x "
          f"{platform}")
    check(g["active"] == 0 and g["queued"] == 0,
          f"{name}: not drained: active={g['active']} "
          f"queued={g['queued']}")
    if g["paged"]:
        check(g["pages_free"] + g["prefix_entries"] == g["pages"],
              f"{name}: page pool leaked: free={g['pages_free']} "
              f"prefix={g['prefix_entries']} pages={g['pages']}")


def serve_phase(model, requests, *, slots: int, max_len: int,
                mesh_tp: int = 0, on_chip: bool = True) -> dict:
    """``model`` behind an ``InferenceServer`` on a loopback port with a
    default (contiguous) and a paged+prefix-cache generator; every
    request streamed concurrently against each. Also reports — without
    gating on it on the chip — whether the engine's greedy probe and
    its sampled stream equal solo ``generate()`` (unsharded engines
    only; held off the chip, where the programs are float32)."""
    import jax

    from paddle_tpu import io
    from paddle_tpu.core import monitor
    from paddle_tpu.ops import pallas as pk

    vocab = model.config.vocab_size
    devices = jax.devices()[:max(mesh_tp, 1)]
    sharded = {"mesh_tp": mesh_tp} if mesh_tp else {}
    traps_before = monitor.get_stat("gen/traps")
    if on_chip and mesh_tp:
        pk.reset_partition_stats()
    elif on_chip:
        _check_dispatch(1)
    report: dict = {}
    server = io.InferenceServer(port=0).start()
    try:
        engines = {
            "contiguous": server.add_generator(
                "contiguous", model, slots=slots, max_len=max_len,
                **sharded),
            "paged": server.add_generator(
                "paged", model, slots=slots, max_len=max_len, paged=True,
                prefix_cache=True, **sharded),
        }
        if on_chip:
            longest = max(len(r.prompt) for r in requests)
            for name, engine in engines.items():
                text = engine.lowered_text(longest)
                decode = DECODE_KERNELS[name]
                if name == "paged":
                    # the headline model's pages, 16 KV heads x 16
                    # tokens x 128, are copied by the kernel itself
                    arm = "gather" if mesh_tp > 1 else "paged_copy_kernel"
                    got = engine.stats()["decode_attn"]
                    check(got == arm, f"paged step attends by {got}, "
                                      f"expected {arm}")
                    if arm == "gather":
                        decode = ()
                absent = (_missing(text["prefill"], PREFILL_KERNELS[name])
                          + _missing(text["decode"], decode))
                check(not absent, f"{name} engine lowered without {absent}")
                report[name] = {"kernels": {
                    "prefill": list(PREFILL_KERNELS[name]),
                    "decode": list(decode)}}
        tokens, repeats = {}, {}
        for name in engines:
            t0 = time.monotonic()
            tokens[name], repeats[name] = _drive(
                server.endpoint, name, engines[name], requests, vocab)
            log(f"serve {name} (mesh_tp={mesh_tp}): {len(requests)} "
                f"concurrent streams + probe in "
                f"{time.monotonic() - t0:.1f}s wall, compiles included")
        with io.InferenceClient(server.endpoint) as client:
            health = client.health()
        for name in engines:
            g = health["generators"][name]
            _check_generator(name, g, platform=devices[0].platform,
                             devices=len(devices))
            # a poll waits on its own stream's wake-up: the polls that
            # waited, the wake-ups that found tokens or an end, and those
            # that found nothing (a missed wake-up shows as a poll that
            # waited out its time instead)
            log(f"serve {name}: polls {g['poll']}")
            check(g["poll"]["wakes"] > 0,
                  f"{name}: no poll was woken: {g['poll']}")
            report.setdefault(name, {}).update(
                streams=len(requests), probe_repeat=repeats[name],
                compiles=g["compiles"], poll=g["poll"],
                device=g["device"],
                pages=({k: g[k] for k in ("pages", "pages_free",
                                          "prefix_entries")}
                       if g["paged"] else None))
        traps = monitor.get_stat("gen/traps") - traps_before
        check(traps == 0, f"{traps} engine trap(s) during serving")
        if on_chip and mesh_tp:
            report["bytes_in_use"] = _check_spread(
                f"mesh_tp={mesh_tp} engines", devices)
            report["partition_stats"] = _check_units("serve", SERVE_UNITS)
        report["paged_equals_contiguous"] = (
            tokens["paged"][0] == tokens["contiguous"][0])
        if not mesh_tp:
            for key, i in (("engine_agrees_with_solo_generate_for", 0),
                           ("sampled_agrees_with_solo_generate_for", 1)):
                solo = _solo(model, requests[i])
                agree = {name: _agree(solo, toks[i])
                         for name, toks in tokens.items()}
                check(on_chip or set(agree.values()) == {len(solo)},
                      f"{key}: {agree} of {len(solo)} tokens")
                report[key] = {name: f"{n}/{len(solo)} tokens"
                               for name, n in agree.items()}
    finally:
        server.stop()
    return report


def latent_phase(requests, *, slots: int, max_len: int,
                 on_chip: bool = True) -> dict:
    """A small latent-attention model (``DeepseekV3ForCausalLM``, a
    cache row of whole lane tiles: 128 + 64 -> 256) behind a paged,
    prefix-cached generator: every request streamed concurrently. On
    the chip its step has to attend through the latent paged kernel —
    ``stats()["decode_attn"]`` and the lowered step both say so — as
    :func:`serve_phase` holds the K/V step to its kernel."""
    import jax

    import paddle_tpu
    from paddle_tpu import io
    from paddle_tpu.models.deepseek_v3 import (
        DeepseekV3Config, DeepseekV3ForCausalLM,
    )

    paddle_tpu.seed(5)
    cfg = DeepseekV3Config.tiny(
        hidden_size=256, num_heads=8, kv_lora_rank=128, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=64, max_seq_len=max_len,
        dtype="bfloat16" if on_chip else "float32")
    model = DeepseekV3ForCausalLM(cfg)
    server = io.InferenceServer(port=0).start()
    try:
        engine = server.add_generator(
            "latent", model, slots=slots, max_len=max_len, paged=True,
            page_tokens=PARITY_PAGE_TOKENS, prefix_cache=True)
        text = engine.lowered_text(max(len(r.prompt) for r in requests))
        arm = engine.stats()["decode_attn"]
        if on_chip:
            check(arm == "paged_kernel",
                  f"latent paged step attends by {arm}, expected "
                  "paged_kernel")
            absent = _missing(text["decode"], (LATENT_DECODE_KERNEL,))
            check(not absent, f"latent engine lowered without {absent}")
        _, repeat = _drive(server.endpoint, "latent", engine, requests,
                           cfg.vocab_size)
        with io.InferenceClient(server.endpoint) as client:
            g = client.health()["generators"]["latent"]
        _check_generator("latent", g, platform=jax.devices()[0].platform,
                         devices=1)
        return {"decode_attn": arm, "streams": len(requests),
                "probe_repeat": repeat, "compiles": g["compiles"],
                "kernels": {"decode": [LATENT_DECODE_KERNEL]
                            if arm == "paged_kernel" else []}}
    finally:
        server.stop()


def window_phase(requests, *, slots: int, max_len: int, window: int,
                 chunk: int, on_chip: bool = True) -> dict:
    """A small two-period SmallThinker model (full and window layers
    mixed) behind a paged, prefix-cached generator whose pool has a
    group a layer kind: every request streamed concurrently, the last
    two through a shared prefix longer than the window. The window
    group has to slide (``gen/kv_pages_slid``), both pools have to come
    back whole, and on the chip every layer of the step attends through
    ``ptpu_paged_decode_attn`` in its copy form (pages of 2 KV heads x
    16 tokens x 128: narrower than a lane tile, whole-lane rows — the
    form refuses the 64-wide rows this phase had before PR 36, see
    ``paged_decode_attention.supported``). The probe's tokens are held to solo
    ``generate()`` off the chip (float32); in bf16 programs of other
    shapes round differently, so the chip reports the agreement."""
    import jax

    import paddle_tpu
    from paddle_tpu import io
    from paddle_tpu.core import monitor
    from paddle_tpu.models.smallthinker import (
        SmallThinkerConfig, SmallThinkerForCausalLM,
    )

    paddle_tpu.seed(6)
    cfg = SmallThinkerConfig.tiny(
        hidden_size=256, num_heads=4, num_kv_heads=2, head_dim=128,
        moe_intermediate_size=128, sliding_window=window,
        max_seq_len=max_len, dtype="bfloat16" if on_chip else "float32")
    model = SmallThinkerForCausalLM(cfg)
    slid0 = monitor.get_stat("gen/kv_pages_slid") or 0
    server = io.InferenceServer(port=0).start()
    try:
        engine = server.add_generator(
            "window", model, slots=slots, max_len=max_len, paged=True,
            page_tokens=PARITY_PAGE_TOKENS, prefill_chunk=chunk,
            prefix_cache=True)
        text = engine.lowered_text(max(len(r.prompt) for r in requests))
        arm = engine.stats()["decode_attn"]
        if on_chip:
            check(arm == "paged_copy_kernel",
                  f"window paged step attends by {arm}, expected "
                  "paged_copy_kernel")
            absent = _missing(text["decode"], DECODE_KERNELS["paged"])
            check(not absent, f"window engine lowered without {absent}")
        tokens, repeat = _drive(server.endpoint, "window", engine, requests,
                                cfg.vocab_size)
        probe = requests[-1]          # behind the shared prefix
        agree = _agree(tokens[-1], _solo(model, probe))
        check(on_chip or agree == probe.new_tokens,
              f"window: {agree}/{probe.new_tokens} tokens of the stream "
              "behind the shared prefix agree with solo generate()")
        slid = (monitor.get_stat("gen/kv_pages_slid") or 0) - slid0
        check(slid > 0, "window: no window-group page was let go")
        engine.clear_prefix_cache()
        st = engine.stats()
        check(all(g["pages_free"] == g["pages"] for g in st["groups"])
              and st["active"] == 0 and st["broken"] is None,
              f"window: a pool did not come back whole: {st['groups']}")
        check(st["groups"][1]["stream_pages_peak"]
              <= st["groups"][1]["row_pages"],
              f"window: a stream mapped more than its row: {st['groups']}")
        return {"decode_attn": arm, "streams": len(requests),
                "probe_repeat": repeat, "pages_slid": slid,
                "row_pages": st["groups"][1]["row_pages"],
                "stream_pages_peak": st["groups"][1]["stream_pages_peak"],
                "engine_agrees_with_solo_generate_for":
                    f"{agree}/{probe.new_tokens} tokens",
                "kernels": {"decode": list(DECODE_KERNELS["paged"])
                            if arm == "paged_copy_kernel" else []}}
    finally:
        server.stop()


def mixed_heads_phase(*, prompt_len: int, new_tokens: int, max_len: int,
                      window: int, chunk: int, on_chip: bool = True) -> dict:
    """A small Laguna model (a dense full layer, then window layers of
    24 query heads and a full one of 16 over 8 KV heads of 128, a gate on
    every head) behind a paged, prefix-cached engine with a group a layer
    kind: one greedy stream, held to solo ``generate()`` off the chip
    (float32) and reported on it (bf16 programs of other shapes round
    differently). ``stats()["attention"]`` names each group's head counts
    and arm; on the chip both groups take the K/V kernel's copy form
    (pages of 8 KV heads x 16 tokens x 128), the window layers under
    ``ptpu_paged_decode_attn_window``."""
    import jax

    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
    from paddle_tpu.serving.engine import GenerationEngine

    cfg = LagunaConfig.tiny(
        hidden_size=256, num_layers=5, num_heads=16, window_heads=24,
        num_kv_heads=8, head_dim=128, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, sliding_window=window,
        max_seq_len=max_len, dtype="bfloat16" if on_chip else "float32")
    model = LagunaForCausalLM(cfg, key=jax.random.PRNGKey(7))
    prompt = np.random.default_rng(7).integers(
        1, cfg.vocab_size, prompt_len, dtype=np.int32)
    with GenerationEngine(model, slots=2, max_len=max_len, paged=True,
                          page_tokens=PARITY_PAGE_TOKENS,
                          prefill_chunk=chunk, prefix_cache=True) as engine:
        text = engine.lowered_text(prompt_len)["decode"]
        gid, toks = engine.start(prompt, new_tokens), []
        while True:
            r = engine.poll(gid, len(toks), wait_s=300.0)
            check(r["error"] is None, f"mixed heads: {r['error']}")
            toks += r["tokens"]
            if r["done"]:
                break
        attention = engine.stats()["attention"]
    check([(a["Hq"], a["Hkv"]) for a in attention] == [(16, 8), (24, 8)],
          f"mixed heads: groups attend as {attention}")
    if on_chip:
        check(all(a["arm"] == "paged_copy_kernel" for a in attention),
              f"mixed heads: a group left the copy form: {attention}")
        check(attention[1]["kernel"] == "ptpu_paged_decode_attn_window"
              and "ptpu_paged_decode_attn_window" in text,
              f"mixed heads: the window layers' kernel is not named apart: "
              f"{attention}")
    agree = _agree(toks, _solo(model, Request(prompt, new_tokens, {})))
    check(on_chip or agree == new_tokens,
          f"mixed heads: {agree}/{new_tokens} tokens agree with solo "
          "generate()")
    return {"attention": attention,
            "engine_agrees_with_solo_generate_for":
                f"{agree}/{new_tokens} tokens"}


def state_phase(requests, *, slots: int, max_len: int, chunk: int,
                on_chip: bool = True) -> dict:
    """A small Kimi-Linear model (KDA layers with a recurrent state
    beside latent layers; 16 KDA heads of 128, what the step kernel's
    gate takes) behind a paged, prefix-cached generator with a state
    group: every request streamed concurrently, then the greedy probe
    again THROUGH the prefix cache — a stream that restores a state
    snapshot and prefills only its tail — and once more cold. On the
    chip the step's KDA layers have to take ``ptpu_kda_step`` and its
    latent layers the latent kernel; a snapshot has to be restored; the
    pages and the snapshots have to come back. The restored stream is
    held to the cold one off the chip (float32); in bf16 the two run
    programs of different shapes, so the chip reports the agreement."""
    import paddle_tpu
    from paddle_tpu import io
    from paddle_tpu.core import monitor
    from paddle_tpu.models.kimi_linear import (
        KimiLinearConfig, KimiLinearForCausalLM,
    )

    paddle_tpu.seed(7)
    cfg = KimiLinearConfig.tiny(
        hidden_size=256, kda_heads=16, kda_head_dim=128, kv_lora_rank=128,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64,
        moe_intermediate_size=128, intermediate_size=512,
        max_seq_len=max_len, dtype="bfloat16" if on_chip else "float32")
    model = KimiLinearForCausalLM(cfg)
    restores0 = monitor.get_stat("gen/state_restores") or 0
    server = io.InferenceServer(port=0).start()
    try:
        engine = server.add_generator(
            "state", model, slots=slots, max_len=max_len, paged=True,
            page_tokens=PARITY_PAGE_TOKENS, prefill_chunk=chunk,
            prefix_cache=True, state_snapshots=8)
        text = engine.lowered_text(max(len(r.prompt) for r in requests))
        st = engine.stats()
        arms = {"decode_attn": st["decode_attn"], "kda_step": st["kda_step"]}
        if on_chip:
            check(arms == {"decode_attn": "paged_kernel",
                           "kda_step": "kernel"},
                  f"state engine's step took {arms}, expected the paged "
                  "latent kernel and the KDA step kernel")
            absent = _missing(text["decode"],
                              (KDA_STEP_KERNEL, LATENT_DECODE_KERNEL))
            check(not absent, f"state engine lowered without {absent}")
        tokens, repeat = _drive(server.endpoint, "state", engine, requests,
                                cfg.vocab_size)
        restores = (monitor.get_stat("gen/state_restores") or 0) - restores0
        check(restores > 0, "state: no admission restored a snapshot")
        through = repeat.get("through_prefix_cache_agrees_for", "")
        probe = requests[0]
        check(on_chip or through == f"{probe.new_tokens}/"
              f"{probe.new_tokens} tokens",
              f"state: the restored stream agrees with the cold one for "
              f"{through}")
        engine.clear_prefix_cache()
        st = engine.stats()
        block = next(g for g in st["groups"] if g["name"] == "state")
        check(st["pages_free"] == st["pages"]
              and block["snapshots_free"] == block["snapshots"]
              and st["active"] == 0 and st["broken"] is None,
              f"state: pages or snapshots did not come back: {st['groups']}")
        return dict(arms, streams=len(requests), probe_repeat=repeat,
                    restores=restores,
                    state_bytes_per_slot=block["bytes_per_slot"],
                    kernels={"decode": [KDA_STEP_KERNEL,
                                        LATENT_DECODE_KERNEL]
                             if arms["kda_step"] == "kernel" else []})
    finally:
        server.stop()


def block_phase(requests, *, slots: int, max_len: int, chunk: int,
                on_chip: bool = True) -> dict:
    """A small SDAR model (block diffusion over blocks of 4, head-wise
    q/k norm, pages of 2 KV heads x 16 tokens x 128) in float32 behind a
    paged, prefix-cached engine: the greedy requests streamed
    concurrently (the engine refuses sampled ones), every stream held to
    the solo ``block_diffusion_generate`` byte for byte, on the chip too.
    Matmuls run at "highest" for the phase — the global setting, which
    the engine's loop thread reads, and which gives the kernel's products
    float32 contraction — so that the two, programs of other shapes,
    round alike to the last bits. On the chip the block step has to
    attend through ``ptpu_paged_block_attn``; the pool has to come back
    whole."""
    import jax

    import paddle_tpu
    from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
    from paddle_tpu.serving.engine import GenerationEngine

    paddle_tpu.seed(8)
    cfg = SDARConfig.tiny(
        hidden_size=256, num_heads=8, num_kv_heads=2, head_dim=128,
        moe_intermediate_size=128, max_seq_len=max_len, dtype="float32")
    model = SDARForCausalLM(cfg)
    greedy = [r for r in requests if not r.sampling]
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        with GenerationEngine(model, slots=slots, max_len=max_len,
                              paged=True, page_tokens=PARITY_PAGE_TOKENS,
                              prefill_chunk=chunk, prefix_cache=True,
                              async_depth=1) as engine:
            text = engine.lowered_text(max(len(r.prompt) for r in greedy))
            ids = [engine.start(r.prompt, r.new_tokens) for r in greedy]
            streams = []
            for gid in ids:
                toks = []
                while True:
                    doc = engine.poll(gid, len(toks), wait_s=30.0)
                    check(doc["error"] is None, f"block: {doc['error']}")
                    toks += doc["tokens"]
                    if doc["done"]:
                        break
                streams.append(toks)
            arm = engine.stats()["block_diffusion"]["attn"]
            if on_chip:
                check(arm == "paged_block_kernel",
                      f"block step attends by {arm}, expected "
                      "paged_block_kernel")
                absent = _missing(text["decode"], (BLOCK_ATTN_KERNEL,))
                check(not absent, f"block engine lowered without {absent}")
            agree = sum(_agree(toks, np.asarray(model.generate(
                r.prompt, r.new_tokens))[0, len(r.prompt):].tolist())
                for toks, r in zip(streams, greedy))
            total = sum(r.new_tokens for r in greedy)
            check(agree == total,
                  f"block: {agree}/{total} tokens agree with solo generation")
            engine.clear_prefix_cache()
            st = engine.stats()
            check(st["pages_free"] == st["pages"] and st["active"] == 0
                  and st["broken"] is None,
                  f"block: the pool did not come back whole: {st}")
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    return {"block_attn": arm, "streams": len(streams),
            "block_diffusion": st["block_diffusion"],
            "engine_agrees_with_solo_generation_for":
                f"{agree}/{total} tokens",
            "kernels": {"decode": [BLOCK_ATTN_KERNEL]
                        if arm == "paged_block_kernel" else []}}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main() -> int:
    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU — jax.default_backend() is {backend!r} "
              f"(devices: {jax.devices()}). The smoke measures nothing "
              "on a CPU; run it through the chip tool.", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={n_dev} compile_cache={cache_dir}", flush=True)
    meter = CompileMeter()
    t_start = time.monotonic()
    cfg = headline_config()
    requests = headline_requests(cfg.vocab_size)
    report: dict = {"device": device}

    model, report["train"] = train_phase(cfg, batch=4, seq=2048, steps=8)
    report["parity"] = parity_phase(model, seq=512)
    report["serve"] = serve_phase(model, requests, slots=8, max_len=2048)
    report["serve_latent"] = latent_phase(
        make_requests(256, (150, 140, 170, 130), (24, 16, 24, 16),
                      shared_prefix=96), slots=4, max_len=256)
    report["serve_window"] = window_phase(
        make_requests(256, (300, 280, 330, 310), (40, 24, 40, 24),
                      shared_prefix=256), slots=4, max_len=512, window=64,
        chunk=64)
    report["serve_mixed_heads"] = mixed_heads_phase(
        prompt_len=300, new_tokens=24, max_len=512, window=64, chunk=64)
    report["serve_state"] = state_phase(
        make_requests(256, (150, 140, 170, 160), (24, 16, 24, 16),
                      shared_prefix=128), slots=4, max_len=256, chunk=64)
    report["serve_block"] = block_phase(
        make_requests(256, (150, 140, 170, 160), (24, 18, 25, 16),
                      shared_prefix=128), slots=4, max_len=256, chunk=64)
    if n_dev >= 4:
        del model
        gc.collect()
        model, report["train_zero3_x4"] = train_phase(
            cfg, batch=4, seq=2048, steps=8, chips=4)
        report["serve_mesh_tp4"] = serve_phase(
            model, requests, slots=8, max_len=2048, mesh_tp=4)

    report["compile"] = meter.report()
    report["wall_seconds"] = round(time.monotonic() - t_start, 1)
    print("chip_smoke report: " + json.dumps(report), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
