"""What PR 29 adds to the benchmark: the cell of the DeepSeek-V3-family
configuration loads through the loader, its metrics resolve to readers,
``work_latent`` matches hand arithmetic at the published widths, the
program's roofline reader and the floor of near-ties do what they say,
and a tiny cell of the same builder runs, agrees with its reference and
fails its control."""

import io
import json

import jax
import pytest

from benchmarks import run
from benchmarks.builders import serve_latent
from benchmarks.lib import cells, reference_latent, work_latent
from benchmarks.lib.meter import CompileMeter
from benchmarks.readers import program_roofline
from benchmarks.tests import util

CELL = "serve-gigachat3-ep16-docgen"
NEW_METRICS = ("moe_held_pick_share", "moe_tokens_per_held_expert",
               "kv_bytes_per_token", "decode_step_roofline")

TINY_LATENT = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 256,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "n_routed_experts": 8,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "n_shared_experts": 1,
    "rope_theta": 100000.0,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "rope_type": "yarn"},
    "rms_norm_eps": 1e-06, "torch_dtype": "float32", "held": [4, 8],
    "published": {"n_routed_experts": 16}, "builder": "serve_latent",
    "program": {
        "model": "paddle_tpu.models.deepseek_v3:DeepseekV3ForCausalLM",
        "config": "paddle_tpu.models.deepseek_v3:DeepseekV3Config",
        "config_args": {
            "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
            "moe_intermediate_size": 32, "num_layers": 3, "first_k_dense": 1,
            "num_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
            "max_seq_len": 64, "rope_base": 100000.0, "rope_factor": 4.0,
            "rope_original_max": 32, "rms_eps": 1e-06, "dtype": "float32",
            "n_routed_experts": 16, "num_experts_per_tok": 4, "n_group": 4,
            "topk_group": 2, "routed_scaling_factor": 2.5,
            "n_shared_experts": 1, "held": [4, 8]}},
    "serve": {"engine": {"slots": 4, "max_len": 64, "paged": True,
                         "prefix_cache": True, "pages": 64, "page_tokens": 8,
                         "prefill_chunk": 32, "queue_max": 16}},
    "limits": {"logit_gap_per_tie": 1e-5, "logit_gap_max": 1e-4}}
MIX = dict(util.TINY_SERVE_TRAFFIC, warm_item_tokens=[4, 12],
           stagger_tokens=[2, 1], compare_pad_tokens=64, compare_margin=0.1,
           compare_min_ties=5, output_tokens=[8, 16], compare_requests=12)


@pytest.fixture(scope="module")
def cell():
    return cells.load(util.HOME.parent, CELL)


def test_the_cell_loads_and_its_metrics_resolve(cell):
    assert cell.chips == 1 and cell.config["builder"] == "serve_latent"
    assert cells.builder("serve_latent") is serve_latent.Run
    names = [m["name"] for m in cell.per_layer()]
    assert set(NEW_METRICS) <= set(names) and "serve_mfu" in names
    for m in cell.per_layer():
        assert callable(cells.reader(m["reader"]))
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tok_s", "itl_p95_s", "setup_s"}


def test_the_traffic_is_the_issues_table(cell):
    want = {"kind": "closed_loop", "clients": 64, "template_tokens": 6144,
            "item_tokens": [128, 384], "output_tokens": [128, 512],
            "blocks": 16, "warm_item_tokens": [128, 128, 200, 300],
            "stagger_tokens": [8, 6], "compare_requests": 6,
            "compare_pad_tokens": 7168, "compare_margin": 0.05,
            "trace_seconds": 8}
    assert {k: cell.traffic[k] for k in want} == want
    assert cell.config["serve"]["engine"]["slots"] == want["clients"]


def test_the_file_holds_the_catalogs_numbers(cell):
    bench = cell.bench
    entry = next(c for c in bench["configs"]
                 if c["name"] == "gigachat3.1-702b-a36b-l5-ep16-serve")
    assert sorted(entry["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    c = cell.config
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 16, 16032)
    assert c["published"] == {"num_hidden_layers": 64,
                              "first_k_dense_replace": 3,
                              "n_routed_experts": 256, "vocab_size": 128256}
    args = c["program"]["config_args"]
    for key, arg in [("hidden_size", "hidden_size"),
                     ("q_lora_rank", "q_lora_rank"),
                     ("kv_lora_rank", "kv_lora_rank"),
                     ("qk_nope_head_dim", "qk_nope_head_dim"),
                     ("qk_rope_head_dim", "qk_rope_head_dim"),
                     ("v_head_dim", "v_head_dim"),
                     ("intermediate_size", "intermediate_size"),
                     ("moe_intermediate_size", "moe_intermediate_size"),
                     ("num_attention_heads", "num_heads"),
                     ("num_experts_per_tok", "num_experts_per_tok"),
                     ("n_group", "n_group"), ("topk_group", "topk_group"),
                     ("routed_scaling_factor", "routed_scaling_factor")]:
        assert c[key] == args[arg], key
    assert args["n_routed_experts"] == 256 and args["held"] == c["held"]


# -- required work at the published widths --------------------------------------

def test_work_latent_matches_hand_arithmetic(cell):
    a = reference_latent.Arch.from_config(cell.config)
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
            + 512 * 64 * (128 + 192) + 64 * 192 * 7168)
    assert attn == 132_579_328 == work_latent.attn_params(a)
    assert work_latent.expert_params(a) == 3 * 7168 * 2048 == 44_040_192
    assert work_latent.dense_layer_params(a) == attn + 3 * 7168 * 18432
    fixed = attn + 44_040_192 + 7168 * 256
    assert work_latent.expert_layer_fixed_params(a) == fixed
    held = (attn + 396_361_728 + 4 * (fixed + 16 * 44_040_192)
            + 2 * 16032 * 7168)
    assert work_latent.params_held(a) == held
    assert round(held / 1e9, 2) == 4.29
    assert round(work_latent.weight_bytes(a) / 1e9, 2) == 8.58
    assert work_latent.kv_bytes_per_token(a) == 5 * 576 * 2 == 5760
    # one decode token at context 6700 with half a held pick a layer
    one = work_latent.serve_flops(a, 6700, 1, 2.0)
    assert one == pytest.approx(
        2 * (held - 4 * 16 * 44_040_192 - 16032 * 7168)
        + 4 * 64 * 192 * 5 * 6701 + 2 * 44_040_192 * 2.0)


def test_decode_step_work_counts_each_byte_once(cell):
    a = reference_latent.Arch.from_config(cell.config)
    w = work_latent.decode_step_work(a, 64, 64 * 6700)
    touched = 16 * (1 - (1 - 8 / 256) ** 64)
    assert work_latent.experts_touched(a, 64) == pytest.approx(touched)
    fixed = work_latent.token_fixed_params(a)
    assert w["bytes"] == pytest.approx(
        2 * (fixed + 4 * touched * 44_040_192) + 64 * 6700 * 5760)
    assert 9.5e9 < w["bytes"] < 10.5e9      # ~12 ms at 819 GB/s
    assert w["flops"] == pytest.approx(
        64 * 2 * fixed + 4 * 64 * 192 * 5 * 64 * 6700
        + 2 * 44_040_192 * 64 * 8 * 16 / 256 * 4)
    # one stream reads fewer experts and fewer rows
    assert work_latent.decode_step_work(a, 1, 6700)["bytes"] < w["bytes"] / 2


def test_program_roofline_on_a_synthetic_context():
    ctx = {"trace": {"programs": {"jit_step": [0.020, 0.030, 0.025],
                                  "jit_prefill": [0.040]}},
           "peaks": (197e12, 819e9),
           "kernel_work": {"decode_step": lambda n: {
               "flops": 1e9 * n, "bytes": 819e9 * 0.010}}}
    got = program_roofline.read(ctx, ["jit_step"], "decode_step")
    assert got == pytest.approx(40.0)           # 10 ms floor over 25 ms
    ctx["kernel_work"]["decode_step"] = {"flops": 197e12 * 0.0125,
                                         "bytes": 1.0}
    assert program_roofline.read(ctx, ["jit_step"],
                                 "decode_step") == pytest.approx(50.0)
    assert program_roofline.read(ctx, ["jit_other"], "decode_step") is None
    assert program_roofline.read(ctx, ["jit_step"], "absent") is None
    assert program_roofline.read({"trace": {}, "peaks": (1, 1)},
                                 ["jit_step"], "decode_step") is None


def test_the_floor_of_near_ties(cell):
    assert cell.traffic["compare_min_ties"] == 50
    # PERF.md 0j: one near-tie, one token 0.0083 under the best
    assert serve_latent.per_tie(0.0083046, 1, 50) == pytest.approx(1.66e-4,
                                                                    rel=1e-2)
    assert serve_latent.per_tie(0.0083046, 1, 1) == 0.0083046
    assert serve_latent.per_tie(0.5, 200, 50) == 0.5 / 200
    assert serve_latent.per_tie(0.0, 0, 50) == 0.0


# -- a tiny cell of the same builder ----------------------------------------------

def test_a_tiny_latent_cell_runs_agrees_and_fails_its_control(tmp_path):
    metrics = ("serve_mfu", "decode_step_ms", "prefix_token_share",
               "compiles_in_window") + NEW_METRICS
    root = util.make_cell(tmp_path, "new-cell", TINY_LATENT, MIX, 1, [
        m for m in metrics if m not in NEW_METRICS])
    # the new metrics' entries, as BENCHMARK.json has them
    bench = json.loads((root / "BENCHMARK.json").read_text())
    real = json.loads((util.HOME.parent / "BENCHMARK.json").read_text())
    for m in real["per_layer"]:
        if m["name"] in NEW_METRICS:
            (root / "benchmarks" / "metrics" / f"{m['name']}.json"
             ).write_text((util.HOME / "metrics" / f"{m['name']}.json"
                           ).read_text())
            bench["per_layer"].append(dict(m, workloads=["new-cell"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load(root, "new-cell")
    out = io.StringIO()
    r = run.drive(cell, 2 ** 31 + 11, 3.0, True, jax.devices(),
                  CompileMeter(), out=out, err=io.StringIO(),
                  chip_peaks=(1e12, 1e11))
    assert r["correct"] and r["attempted"] > 4 and r["failed"] == 0
    got = r["metrics"]
    assert got["kv_bytes_per_token"]["value"] == 3 * 128 * 4
    assert 25 < got["moe_held_pick_share"]["value"] < 75   # 8 of 16 held
    assert got["moe_tokens_per_held_expert"]["value"] > 0
    assert got["compiles_in_window"]["value"] == 0
    assert 0 < got["serve_mfu"]["value"] < 100
    # no chip in a CPU trace: the roofline reader finds no program and
    # the metric is left out, never reported as 0
    assert "decode_step_roofline" not in got

    b = serve_latent.Run(cell, 5, jax.devices()[:1])
    b.setup()
    b.window(5.0)
    b.free()
    assert run.compare.verdict(b.compare())
    assert not run.compare.verdict(b.control())
    wrong = {n: v > lim for n, v, lim, _ in b.fault("altered_token")}
    assert wrong["logit_gap_max"]
