"""Benchmark: Llama decoder training throughput on the available TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N}

``vs_baseline`` is measured MFU divided by 0.40 — the A100-class MFU the
north-star asks to match (BASELINE.json: "match A100 MFU on Llama-2";
the reference publishes no numbers). vs_baseline >= 1.0 means
A100-parity-or-better utilization on this chip.

Runs only on a TPU whose ``device_kind`` is in the peak tables below; an
unknown device raises instead of assuming a peak.

Usage: python bench.py [--smoke] [--steps N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

# per-chip peak bf16 FLOP/s by TPU generation, matched as a substring of
# ``device_kind`` (source: Google Cloud TPU documentation, the per-
# generation "System architecture" pages — v5e 197 TFLOP/s, v5p 459,
# v4 275, v6e 918)
PEAK_FLOPS = {
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6e": 918e12,
    "v6 lite": 918e12,   # v6e reports device_kind "TPU v6 lite"
}
A100_CLASS_MFU = 0.40


def _peak(table: dict, device, what: str) -> float:
    kind = device.device_kind.lower()
    for key, value in table.items():
        if key in kind:
            return value
    raise ValueError(
        f"no {what} on record for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); known: {sorted(table)}. A "
        "utilization figure needs the real peak — add the device to the "
        "table with its source instead of assuming one")


def detect_peak_flops(device) -> float:
    return _peak(PEAK_FLOPS, device, "peak bf16 FLOP/s")


# per-chip HBM bandwidth (bytes/s) by TPU generation — the decode
# roofline: tokens/s ≈ BW / bytes-per-token (same source as PEAK_FLOPS:
# v5e 819 GB/s, v5p 2765, v4 1228, v6e 1638)
PEAK_HBM_BW = {
    "v5e": 819e9,
    "v5 lite": 819e9,
    "v5p": 2765e9,
    "v4": 1228e9,
    "v6e": 1638e9,
    "v6 lite": 1638e9,   # v6e reports device_kind "TPU v6 lite"
}


def detect_peak_bandwidth(device) -> float:
    return _peak(PEAK_HBM_BW, device, "peak HBM bandwidth")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny fast config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--remat-policy", default=None,
                    help="override cfg.remat_policy (sweep tool)")
    ap.add_argument("--lm-head-mode", default=None,
                    choices=["dense", "fused", "chunked", "auto"],
                    help="override cfg.lm_head_mode (sweep tool)")
    ap.add_argument("--sustained", action="store_true",
                    help="one long window (>=50 steps, 5-step sync chunks)"
                         " reporting p50/p95 step time alongside the rate")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import paddle_tpu
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as optim
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import lr as lr_mod
    from paddle_tpu.parallel import mesh as M

    enable_compile_cache()
    dev = jax.devices()[0]
    n_chips = len(jax.devices())
    peak = detect_peak_flops(dev)

    if args.smoke:
        cfg = LlamaConfig.tiny(num_layers=2)
        batch, seq = 4, 128
    else:
        # ~1B-param Llama (the largest that fits one v5e chip in bf16 with
        # fp32 AdamW moments). Pallas kernels (flash attention, fused
        # rms_norm/rope, fused lm-head⊗xent) dispatch automatically on TPU.
        # The fused linear⊗xent head (logits never materialized) frees
        # the HBM that lets bs4 + save_mlp_dots_attn (skip recomputing the
        # mlp gate/up matmuls and the flash fwd) fit; which batch/policy
        # is fastest is not measured on the current code.
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=16, num_heads=16, num_kv_heads=16, max_seq_len=2048,
            dtype="bfloat16", remat=True, remat_policy="save_mlp_dots_attn",
            lm_head_mode="fused")
        batch, seq = 4, 2048
    if args.batch:
        batch = args.batch
    if args.seq:
        seq = args.seq
        cfg = dataclasses.replace(cfg, max_seq_len=max(cfg.max_seq_len, seq))
    if args.remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=args.remat_policy)
    if args.lm_head_mode:
        cfg = dataclasses.replace(cfg, lm_head_mode=args.lm_head_mode)

    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = cfg.num_params()

    strategy = dist.DistributedStrategy()
    if n_chips > 1:
        strategy.sharding.enable = True
        strategy.sharding.stage = 3
        strategy.sharding.degree = n_chips
    mesh = M.mesh_from_strategy(strategy, jax.devices())
    with M.MeshContext(mesh):
        sched = lr_mod.warmup_cosine(3e-4, 100, 10000)
        step = dist.fleet.build_train_step(
            model,
            optimizer=optim.AdamW(sched,
                                  grad_clip=optim.ClipGradByGlobalNorm(1.0)),
            strategy=strategy, mesh=mesh)
        state = step.init_state(model)
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        data = step.shard_batch({"input_ids": jnp.asarray(ids),
                                 "labels": jnp.asarray(ids)})

        for i in range(args.warmup):
            state, metrics = step(state, data, jax.random.PRNGKey(i))
        jax.block_until_ready(metrics["loss"])

        # sync once at the end of each window: each step's (donated) state
        # feeds the next, so the chain is a real device-side dependency
        # and the final float() drains it.
        # Best-of-3 windows: the fastest window estimates device
        # throughput (min-over-repetitions); the median rides along.
        p50_step = p95_step = None
        if args.sustained:
            # sustained mode: one long window of >=50 steps synced every
            # 5-step chunk — the long-window rate can't be flattered by a
            # lucky window, and the chunk quantiles expose stalls
            chunk = 5
            n_chunks = max(10, args.steps // chunk)
            chunk_dts = []
            k = 0
            for _ in range(n_chunks):
                t0 = time.perf_counter()
                for _ in range(chunk):
                    state, metrics = step(state, data,
                                          jax.random.PRNGKey(100 + k))
                    k += 1
                float(metrics["loss"])
                chunk_dts.append(time.perf_counter() - t0)
            dt = sum(chunk_dts)
            median_dt = dt
            args.steps = n_chunks * chunk
            steps_sorted = sorted(d / chunk for d in chunk_dts)
            p50_step = steps_sorted[len(steps_sorted) // 2]
            p95_step = steps_sorted[
                min(len(steps_sorted) - 1,
                    int(round(0.95 * (len(steps_sorted) - 1))))]
        else:
            n_windows = 1 if args.smoke else 3
            window_dts = []
            for w in range(n_windows):
                t0 = time.perf_counter()
                for i in range(args.steps):
                    state, metrics = step(state, data,
                                          jax.random.PRNGKey(100 + i))
                float(metrics["loss"])
                window_dts.append(time.perf_counter() - t0)
            dt = min(window_dts)
            # median alongside the min: guards against regressions the
            # min would mask
            median_dt = sorted(window_dts)[len(window_dts) // 2]

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * args.steps / dt
    tokens_per_sec_chip = tokens_per_sec / n_chips
    # training FLOPs/token: 6N weight flops + attention 12*L*E*T
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = tokens_per_sec_chip * flops_per_token / peak

    result = {
        "metric": (f"llama-{n_params/1e6:.0f}M bf16 train throughput "
                   f"({'sustained, ' if args.sustained else ''}seq={seq}, "
                   f"bs={batch}, "
                   f"{'zero3' if n_chips > 1 else 'single-chip'}, "
                   f"{dev.device_kind})"),
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / A100_CLASS_MFU, 4),
    }
    print(json.dumps(result))
    extra = ""
    if p50_step is not None:
        extra = (f"p50_step={p50_step*1e3:.1f}ms "
                 f"p95_step={p95_step*1e3:.1f}ms ")
    median_tps = tokens_per_step * args.steps / median_dt / n_chips
    print(f"# mfu={mfu:.3f} steps/sec={args.steps/dt:.3f} "
          f"median_tokens_per_sec_chip={median_tps:.1f} "
          f"median_mfu={mfu * dt / median_dt:.3f} {extra}"
          f"loss={float(metrics['loss']):.4f} params={n_params/1e6:.1f}M",
          file=sys.stderr)
    return result


if __name__ == "__main__":
    main()
