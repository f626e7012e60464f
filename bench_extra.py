"""Secondary benchmarks: per-family training throughput on one chip.

The "functional + throughput" rows beyond the headline Llama proxy
(`bench.py` stays the single-JSON-line entry). Prints one JSON line per
model family. Timing follows bench.py: chained donated state (each step
consumes the previous step's output, so the final fetch drains the whole
window) and best-of-3 windows.
"""

from __future__ import annotations

import json
import time

import numpy as np


def measure(step, state, data, steps=8, windows=3):
    import jax

    state, metrics = step(state, data, jax.random.PRNGKey(0))
    jax.block_until_ready(metrics["loss"])
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for i in range(steps):
            state, metrics = step(state, data, jax.random.PRNGKey(i))
        float(metrics["loss"])
        times.append(time.perf_counter() - t0)
    return min(times) / steps, float(metrics["loss"])


def lm_bench(name, model, vocab, batch, seq, n_params):
    import jax
    import jax.numpy as jnp

    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as optim
    from paddle_tpu.parallel import mesh as M

    mesh = M.create_mesh({"dp": 1}, jax.devices()[:1])
    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            model, optimizer=optim.AdamW(1e-4), mesh=mesh)
        state = step.init_state(model)
        ids = np.random.RandomState(0).randint(
            0, vocab, (batch, seq)).astype(np.int32)
        data = step.shard_batch({"input_ids": jnp.asarray(ids),
                                 "labels": jnp.asarray(ids)})
        sec_per_step, loss = measure(step, state, data)
    print(json.dumps({
        "model": name, "params_m": round(n_params / 1e6, 1),
        "tokens_per_sec": round(batch * seq / sec_per_step, 1),
        "loss": round(loss, 3)}), flush=True)


def main(only: str | None = None):
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.models import (
        GPTConfig, GPTForCausalLM, MambaConfig, MambaForCausalLM,
        MoEConfig, MoEForCausalLM, ErnieConfig, ErnieForPretraining,
    )

    enable_compile_cache()
    paddle_tpu.seed(0)
    want = lambda name: only is None or only in name

    if want("gpt"):
        # GPT (gpt3-1.3b geometry trimmed to fit the chip + Adam moments)
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=12,
                        num_heads=16, max_seq_len=2048, dtype="bfloat16",
                        remat=True)
        n = 50304 * 2048 * 2 + 12 * 12 * 2048 * 2048
        lm_bench("gpt-0.7B", GPTForCausalLM(cfg), 50304, 8, 2048, n)

    if want("mamba"):
        # Mamba (Pallas selective-scan kernel; per-layer remat)
        mcfg = MambaConfig(vocab_size=50304, hidden_size=1024,
                           num_layers=24, dtype="bfloat16", remat=True)
        # exact count (tied embedding once) — the old 405M estimate
        # double-counted the tied table; true size is ~212M
        lm_bench("mamba-0.2B", MambaForCausalLM(mcfg), 50304, 8, 2048,
                 mcfg.num_params())

    if want("moe"):
        # MoE (8 experts, ~4x active sparsity). r5: blocks are
        # scan-stacked (the pp×ep enabler); the unrolled no-remat graph
        # now exceeds the remote-compile helper's budget, and
        # dots_saveable per-layer remat is the measured optimum of the
        # policies that compile (47.0k vs full-recompute 40.5k vs the
        # r4 python-loop no-remat 49.7k — the scan conversion costs ~5%
        # on this single-chip leg in exchange for pipeline support)
        ecfg = MoEConfig(vocab_size=32000, hidden_size=1024,
                         intermediate_size=2816, num_layers=8, num_heads=16,
                         num_kv_heads=16, max_seq_len=1024,
                         dtype="bfloat16", num_experts=8, top_k=2,
                         remat=True, remat_policy="dots_saveable")
        lm_bench("moe-8x", MoEForCausalLM(ecfg), 32000, 8, 1024,
                 ecfg.num_params())

    if want("longctx"):
        # Long-context single-chip: seq 16384 through the Pallas flash
        # attention (O(T) memory) + per-layer remat — the on-hardware leg
        # of the long-context story (ring/Ulysses extend it across chips)
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        lcfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=8, num_heads=16, num_kv_heads=8,
            max_seq_len=16384, dtype="bfloat16", remat=True,
            remat_policy="nothing_saveable")
        n = lcfg.num_params()
        lm_bench("llama-longctx-16k", LlamaForCausalLM(lcfg), 32000, 1,
                 16384, n)

    if want("decode"):
        _decode_benches(only)

    # ERNIE base MLM (encoder side)
    import paddle_tpu.distributed as dist
    from paddle_tpu.parallel import mesh as M
    from paddle_tpu import optimizer as optim

    mesh = M.create_mesh({"dp": 1}, jax.devices()[:1])
    rs = np.random.RandomState(0)

    if want("ernie"):
        bcfg = ErnieConfig(vocab_size=40000, hidden_size=768, num_layers=12,
                           num_heads=12, intermediate_size=3072,
                           max_seq_len=512, dtype="bfloat16", dropout=0.0,
                           remat=True)
        model = ErnieForPretraining(bcfg)
        ids = rs.randint(5, 40000, (16, 512)).astype(np.int32)
        labels = np.where(rs.rand(16, 512) < 0.15, ids,
                          -100).astype(np.int32)

        def loss_fn(m, batch, training=True):
            return m.loss(batch["input_ids"], batch["labels"],
                          training=training)

        with M.MeshContext(mesh):
            step = dist.fleet.build_train_step(
                model, optimizer=optim.AdamW(1e-4), loss_fn=loss_fn,
                mesh=mesh)
            state = step.init_state(model)
            data = step.shard_batch({"input_ids": jnp.asarray(ids),
                                     "labels": jnp.asarray(labels)})
            sec, loss = measure(step, state, data)
        print(json.dumps({"model": "ernie-base", "params_m": 110.0,
                          "tokens_per_sec": round(16 * 512 / sec, 1),
                          "loss": round(loss, 3)}), flush=True)

    if want("vit"):
        _vit_bench(dist, M, optim, mesh, rs)

    if want("ppyoloe"):
        _det_bench(dist, M, optim, mesh, rs)


def _gen_time(model, ids, n_new, cache_dtype=None, reps=3):
    """Best-of-reps wall time of one jitted generate() call, timed
    through a host fetch of the tokens (the barrier that ends the
    device work)."""
    import jax

    from paddle_tpu.models.generation import generate

    gen = jax.jit(lambda m, i: generate(m, i, n_new,
                                        cache_dtype=cache_dtype))
    out = np.asarray(gen(model, ids))                 # compile + run
    assert out.shape == (ids.shape[0], ids.shape[1] + n_new)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(gen(model, ids))
        best = min(best, time.perf_counter() - t0)
    return best


def _decode_leg(name, model, ids, n_new, *, cache_dtype=None,
                weight_bytes=None, kv_bytes_per_tok=0.0, reps=3,
                extra=None):
    """One serving leg, reported the way serving systems report:
    prefill latency (the 16-token run ≈ TTFT) and steady-state decode
    rate (marginal tokens between the 16- and n_new-token runs — free
    of prefill amortization), plus roofline accounting: bytes/step =
    full weight read + average live KV-cache read, vs the chip's HBM
    peak. Decode is HBM-bandwidth-bound, so achieved/peak is the
    utilization number that matters."""
    import jax

    from bench import detect_peak_bandwidth

    B, T0 = ids.shape
    # warm run length keeps T0+warm a multiple of the decode kernel's
    # block size (128): a misaligned cache would push the warm run onto
    # the einsum fallback and skew the marginal-rate subtraction
    warm = 128
    t_small = _gen_time(model, ids, warm, cache_dtype=cache_dtype,
                        reps=reps)
    t_full = _gen_time(model, ids, n_new, cache_dtype=cache_dtype,
                       reps=reps)
    steady = B * (n_new - warm) / (t_full - t_small)
    total = B * n_new / t_full
    sec_per_step = (t_full - t_small) / (n_new - warm)

    rec = {"model": name, "batch": B, "new_tokens": n_new,
           "decode_tokens_per_sec": round(steady, 1),
           "tokens_per_sec_per_seq": round(steady / B, 1),
           "total_tokens_per_sec_incl_prefill": round(total, 1),
           "prefill_plus_warm_s": round(t_small, 3)}
    if weight_bytes is not None:
        avg_live = T0 + (warm + n_new) / 2
        step_bytes = weight_bytes + kv_bytes_per_tok * B * avg_live
        bw = detect_peak_bandwidth(jax.devices()[0])
        rec["achieved_gb_per_s"] = round(step_bytes / sec_per_step / 1e9,
                                         1)
        rec["hbm_roofline_frac"] = round(
            step_bytes / sec_per_step / bw, 3)
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)
    return steady


def _model_weight_bytes(model, exclude_embed_attrs=("embed", "pos_embed")):
    """Bytes of parameters a decode step actually re-reads: every leaf
    at its stored dtype (int8 weights count 1 byte + their scales),
    minus embedding tables (a gather reads one row per token)."""
    import jax

    total = sum(l.nbytes for l in jax.tree_util.tree_leaves(model)
                if hasattr(l, "nbytes"))
    for attr in exclude_embed_attrs:
        emb = getattr(model, attr, None)
        if emb is not None:
            total -= sum(l.nbytes for l in jax.tree_util.tree_leaves(emb)
                         if hasattr(l, "nbytes"))
    return total


def _decode_benches(only=None):
    """Serving-side decode legs: llama batch frontier (bf16 and
    int8-weights ∘ int8-KV-cache), GPT, long-context, MoE, Mamba —
    all through the shared cache contract + the fused decode-attention
    kernel (ops/pallas/decode_attention.py)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    import paddle_tpu as _pt
    from paddle_tpu.models import (
        GPTConfig, GPTForCausalLM, LlamaConfig, LlamaForCausalLM,
        MambaConfig, MambaForCausalLM, MoEConfig, MoEForCausalLM)
    from paddle_tpu.quant import quantize_weights_int8

    dcfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=16, num_heads=16, num_kv_heads=16,
        max_seq_len=1024, dtype="bfloat16", remat=False)
    _pt.seed(0)
    dmodel = LlamaForCausalLM(dcfg)
    qmodel = quantize_weights_int8(dmodel)
    prompt_len, new_toks = 128, 512
    kv_tok = 2 * dcfg.num_layers * dcfg.num_kv_heads * \
        (dcfg.hidden_size // dcfg.num_heads)          # elems per token

    def ids_for(B):
        return jnp.asarray(np.random.RandomState(0).randint(
            0, dcfg.vocab_size, (B, prompt_len)).astype(np.int32))

    wb, wq = _model_weight_bytes(dmodel), _model_weight_bytes(qmodel)
    # batch frontier: weights amortize across the batch until the live
    # KV cache fills HBM (bf16 tops out near bs96 on 16 GB; the int8
    # pair reaches bs128) — the aggregate-throughput lever
    for B in (8, 32, 96):
        _decode_leg("llama-953M-decode", dmodel, ids_for(B), new_toks,
                    weight_bytes=wb, kv_bytes_per_tok=kv_tok * 2,
                    extra={"params_m": round(dcfg.num_params() / 1e6, 1)})
    for B in (8, 32, 128):
        _decode_leg("llama-953M-decode-int8w-int8kv", qmodel,
                    ids_for(B), new_toks, cache_dtype=jnp.int8,
                    weight_bytes=wq,
                    kv_bytes_per_tok=kv_tok * 1 + 2 * 4 * dcfg.num_layers
                    * dcfg.num_kv_heads)
    del qmodel

    # GPT decode (learned positions, fused-QKV MHA), same contract
    gdcfg = GPTConfig(vocab_size=50304, hidden_size=2048,
                      num_layers=12, num_heads=16, max_seq_len=1024,
                      dropout=0.0, dtype="bfloat16", remat=False)
    _pt.seed(0)
    gmodel = GPTForCausalLM(gdcfg)
    gids = jnp.asarray(np.random.RandomState(0).randint(
        0, gdcfg.vocab_size, (8, prompt_len)).astype(np.int32))
    gkv = 2 * gdcfg.num_layers * gdcfg.num_heads * \
        (gdcfg.hidden_size // gdcfg.num_heads)
    _decode_leg("gpt-0.8B-decode", gmodel, gids, new_toks,
                weight_bytes=_model_weight_bytes(gmodel),
                kv_bytes_per_tok=gkv * 2,
                extra={"params_m": round(gdcfg.num_params() / 1e6, 1)})
    gq = quantize_weights_int8(gmodel)
    _decode_leg("gpt-0.8B-decode-int8w", gq, gids, new_toks,
                weight_bytes=_model_weight_bytes(gq),
                kv_bytes_per_tok=gkv * 2)
    del gmodel, gq

    # long-context: S=4096, live context ~3.8k — the int8-KV design
    # point (cache bytes dominate); prefill reported separately (its
    # cost includes quantizing the 3328-token prompt into the cache)
    lc_cfg = dataclasses.replace(dcfg, max_seq_len=4096)
    _pt.seed(0)
    lc_model = LlamaForCausalLM(lc_cfg)
    lc_ids = jnp.asarray(np.random.RandomState(0).randint(
        0, lc_cfg.vocab_size, (8, 3328)).astype(np.int32))
    _decode_leg("llama-953M-decode-longctx", lc_model, lc_ids, new_toks,
                weight_bytes=wb, kv_bytes_per_tok=kv_tok * 2, reps=2,
                extra={"live_context": 3328 + new_toks})
    _decode_leg("llama-953M-decode-longctx-int8kv", lc_model, lc_ids,
                new_toks, cache_dtype=jnp.int8,
                weight_bytes=wb,
                kv_bytes_per_tok=kv_tok * 1 + 2 * 4 * dcfg.num_layers
                * dcfg.num_kv_heads, reps=2,
                extra={"live_context": 3328 + new_toks})
    del lc_model

    # MoE decode: expert weights dominate the per-step read (every
    # expert is resident even though top-k route per token), so the
    # int8-weight win is the largest of any family
    ecfg = MoEConfig(vocab_size=32000, hidden_size=1024,
                     intermediate_size=2816, num_layers=8, num_heads=16,
                     num_kv_heads=16, max_seq_len=1024,
                     dtype="bfloat16", num_experts=8, top_k=2)
    _pt.seed(0)
    emodel = MoEForCausalLM(ecfg)
    eids = jnp.asarray(np.random.RandomState(0).randint(
        0, ecfg.vocab_size, (8, prompt_len)).astype(np.int32))
    ekv = 2 * ecfg.num_layers * ecfg.num_kv_heads * \
        (ecfg.hidden_size // ecfg.num_heads)
    _decode_leg("moe-8x-decode", emodel, eids, new_toks,
                weight_bytes=_model_weight_bytes(emodel),
                kv_bytes_per_tok=ekv * 2,
                extra={"params_m": round(ecfg.num_params() / 1e6, 1)})
    eq = quantize_weights_int8(emodel)
    _decode_leg("moe-8x-decode-int8w", eq, eids, new_toks,
                weight_bytes=_model_weight_bytes(eq),
                kv_bytes_per_tok=ekv * 2)
    del emodel, eq

    # Mamba stateful decode: the recurrent O(1)-per-token path — no KV
    # cache growth, constant state, per-token cost flat in context
    mdcfg = MambaConfig(vocab_size=50304, hidden_size=1024,
                        num_layers=24, dtype="bfloat16")
    _pt.seed(0)
    mmodel = MambaForCausalLM(mdcfg)
    mids = jnp.asarray(np.random.RandomState(0).randint(
        0, mdcfg.vocab_size, (8, prompt_len)).astype(np.int32))
    _decode_leg("mamba-0.2B-decode", mmodel, mids, new_toks,
                weight_bytes=_model_weight_bytes(mmodel),
                extra={"params_m": round(mdcfg.num_params() / 1e6, 1)})
    mq = quantize_weights_int8(mmodel)
    _decode_leg("mamba-0.2B-decode-int8w", mq, mids, new_toks,
                weight_bytes=_model_weight_bytes(mq))


def _vit_bench(dist, M, optim, mesh, rs):
    """ViT-L/16 image classification — bf16 AMP (autocast to bfloat16
    via the strategy compiler; fp32 master weights), with an MFU figure so
    the vision family has a hardware-utilization number like the LM
    rows."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.vision.models import vit_l_16

    rs = np.random.RandomState(11)   # own stream: results must not depend
    # on which earlier families ran (the `only` filter)
    vit = vit_l_16(num_classes=1000, remat=True)
    vb = 64   # per-layer remat frees activation memory; bs128 measured slower
    imgs = jnp.asarray(rs.randn(vb, 3, 224, 224).astype(np.float32))
    vlabels = jnp.asarray(rs.randint(0, 1000, (vb,)))

    def vit_loss(m, batch, training=True):
        import jax.numpy as jnp

        from paddle_tpu.nn import functional as F

        logits = m(batch["x"], training=training)
        return F.cross_entropy(logits.astype(jnp.float32), batch["y"])

    vs = dist.DistributedStrategy()
    vs.amp.enable = True
    vs.amp.dtype = "bfloat16"
    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            vit, optimizer=optim.AdamW(1e-4), loss_fn=vit_loss,
            strategy=vs, mesh=mesh)
        state = step.init_state(vit)
        data = step.shard_batch({"x": imgs, "y": vlabels})
        sec, loss = measure(step, state, data)
    # fwd FLOPs/img from dims (E=1024 L=24 T=197 mlp=4E): per block the
    # matmuls are qkv 6TE^2 + out-proj 2TE^2 + mlp 16TE^2 = 24TE^2, plus
    # attention 4T^2E; patch embed 2*T*E*(3*16*16); x3 for training
    E, L, T = 1024, 24, (224 // 16) ** 2 + 1
    fwd = L * (24 * T * E * E + 4 * T * T * E) + 2 * T * E * 3 * 16 * 16
    from bench import detect_peak_flops
    vit_mfu = (vb / sec) * 3 * fwd / detect_peak_flops(jax.devices()[0])
    print(json.dumps({"model": "vit-l-16", "params_m": 304.0,
                      "images_per_sec": round(vb / sec, 1),
                      "amp": "bfloat16", "mfu": round(vit_mfu, 4),
                      "loss": round(loss, 3)}), flush=True)


def _det_bench(dist, M, optim, mesh, rs):
    """PP-YOLOE-s detection training (TAL + VFL/DFL/GIoU), 640x640."""
    import jax.numpy as jnp

    from paddle_tpu.vision.models import ppyoloe_s

    rs = np.random.RandomState(12)   # own stream (see _vit_bench)
    det = ppyoloe_s(num_classes=80)
    db = 8
    dimgs = jnp.asarray(rs.randn(db, 3, 640, 640).astype(np.float32) * 0.1)
    gtb = np.zeros((db, 8, 4), np.float32)
    gtl = np.full((db, 8), -1, np.int32)
    for i in range(db):
        for g in range(rs.randint(1, 9)):
            cx, cy = rs.rand(2) * 560 + 40
            w, h = rs.rand(2) * 120 + 30
            gtb[i, g] = [max(cx - w, 0), max(cy - h, 0),
                         min(cx + w, 640), min(cy + h, 640)]
            gtl[i, g] = rs.randint(0, 80)

    def det_loss(m, batch, training=True):
        return m.loss(batch["x"], batch["boxes"], batch["labels"],
                      training=training)

    # scoped bf16 AMP (r4): backbone/neck/head convs autocast to bf16 and
    # BatchNorm emits its input dtype (f32 statistics math), while
    # model.loss pins decode/TAL/VFL/DFL/GIoU fp32 via amp.suspend —
    # measured 175.8 vs 136.4 img/s fp32 (1.29x) with step-1 loss parity
    # 0.4%; r3's whole-model autocast measured 9.3 img/s (15x SLOWER)
    ds = dist.DistributedStrategy()
    ds.amp.enable = True
    ds.amp.dtype = "bfloat16"
    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            det, optimizer=optim.AdamW(1e-4), loss_fn=det_loss,
            strategy=ds, mesh=mesh)
        state = step.init_state(det)
        data = step.shard_batch({"x": dimgs, "boxes": jnp.asarray(gtb),
                                 "labels": jnp.asarray(gtl)})
        sec, loss = measure(step, state, data)
    print(json.dumps({"model": "ppyoloe-s-640", "params_m": 6.7,
                      "images_per_sec": round(db / sec, 1),
                      "loss": round(loss, 3)}), flush=True)


if __name__ == "__main__":
    import sys

    main(sys.argv[1] if len(sys.argv) > 1 else None)
