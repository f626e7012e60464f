"""Cells made of nothing but new files in a temporary directory: tiny
configurations and traffic that the CPU can run, found by the same
loader and driven by the same ``run.drive`` as the real cells."""

from __future__ import annotations

import json
import pathlib
import shutil

HOME = pathlib.Path(__file__).resolve().parents[1]

TINY_DENSE = {
    "hidden_size": 64, "intermediate_size": 176, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
    "torch_dtype": "float32", "builder": "train",
    "program": {
        "model": "paddle_tpu.models:LlamaForCausalLM",
        "config": "paddle_tpu.models:LlamaConfig",
        "config_args": {
            "vocab_size": 256, "hidden_size": 64, "intermediate_size": 176,
            "num_layers": 2, "num_heads": 4, "num_kv_heads": 2,
            "max_seq_len": 64, "rope_base": 1000000.0, "rms_eps": 1e-05,
            "dtype": "float32", "remat": False}},
    "train": {"batch_rows": 4,
              "adamw": {"lr": 0.0003, "beta1": 0.9, "beta2": 0.95,
                        "eps": 1e-08, "weight_decay": 0.1}},
    "limits": {"loss_gap": 1e-5, "grad_norm_gap": 1e-4,
               "change_norm_gap": 1e-4},
}

TINY_MOE = {
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "num_experts": 8,
    "num_experts_per_tok": 2, "router_aux_loss_coef": 0.01,
    "torch_dtype": "float32",
    "program": {
        "model": "paddle_tpu.models.moe:MoEForCausalLM",
        "config": "paddle_tpu.models.moe:MoEConfig",
        "config_args": {
            "vocab_size": 256, "hidden_size": 64, "intermediate_size": 32,
            "num_layers": 2, "num_heads": 4, "num_kv_heads": 4,
            "max_seq_len": 64, "rope_base": 10000.0, "rms_eps": 1e-05,
            "dtype": "float32", "num_experts": 8, "top_k": 2,
            "capacity_factor": 4.0, "aux_loss_weight": 0.01}},
}

TINY_TRAIN_TRAFFIC = {"kind": "packed_tokens", "seq_len": 64,
                      "trace_seconds": 0.5}

TINY_SERVE_TRAFFIC = {
    "kind": "closed_loop", "clients": 4, "blocks": 4,
    "template_tokens": 24, "item_tokens": [4, 12],
    "output_tokens": [3, 9], "trace_seconds": 0.5, "compare_requests": 4}


# per-layer metrics whose files the benchmark keeps for a cell across
# chips, though BENCHMARK.json names none of them while no such cell is in
ACROSS_CHIPS = {
    name: {"name": name, "unit": unit, "better": "lower", "source": source,
           "layer": layer, "moves": "train_tok_s_chip"}
    for name, unit, source, layer in [
        ("collective_exposed_share", "%", "device_trace",
         "multi-chip (parallel, ZeRO-3)"),
        ("partition_fallbacks", "count", "program_counter",
         "multi-chip dispatch")]}


def make_cell(tmp: pathlib.Path, name: str, config: dict, traffic: dict,
              chips: int = 1, metrics=("train_mfu",)) -> pathlib.Path:
    """Write a whole benchmark of one cell under ``tmp`` — only data
    files and entries — and return the root to load it from."""
    real = json.loads((HOME.parent / "BENCHMARK.json").read_text())
    home = tmp / "benchmarks"
    for sub in ("configs", "traffic", "metrics"):
        (home / sub).mkdir(parents=True, exist_ok=True)
    (home / "configs" / "tiny.json").write_text(json.dumps(config))
    (home / "traffic" / "tiny-mix.json").write_text(json.dumps(traffic))
    known = dict(ACROSS_CHIPS, **{m["name"]: m for m in real["per_layer"]})
    per_layer = []
    for metric in metrics:
        shutil.copy(HOME / "metrics" / f"{metric}.json", home / "metrics")
        per_layer.append(dict(known[metric], workloads=[name]))
    bench = dict(
        real, paths=["benchmarks"],
        configs=[{"name": "tiny", "source": "test", "reduced": [],
                  "file": "benchmarks/configs/tiny.json", "why": "test"}],
        workloads=[{"name": name, "config": "tiny", "traffic": "tiny-mix",
                    "chips": chips, "why": "test"}],
        end_to_end=[{k: v for k, v in m.items() if k != "workloads"}
                    for m in real["end_to_end"]],
        per_layer=per_layer)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
