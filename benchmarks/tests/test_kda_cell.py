"""What PR 35 adds to the benchmark: the cell of the Kimi-Linear
configuration loads through the loader, its file holds the catalog
row's numbers (only ``num_experts`` and ``vocab_size`` reduced),
``work_kda`` matches hand arithmetic at the published widths, the
traffic offers every seed the same multiset, and a tiny cell of the same
builder runs, restores snapshots, agrees with its reference and fails
its control and its ``no_state_restore`` fault."""

import io
import json

import jax
import pytest

from benchmarks import run
from benchmarks.builders import serve_state
from benchmarks.lib import cells, reference_kimi_linear, traffic, work_kda
from benchmarks.lib.meter import CompileMeter
from benchmarks.tests import util

CELL = "serve-kimi-linear-ep16-longgen"
CONFIG = "kimi-linear-48b-a3b-ep16-serve"
GIGACHAT = "serve-gigachat3-ep16-docgen"
NEW_METRICS = ("kda_step_roofline", "state_bytes_per_slot",
               "state_restore_share")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_experts": 16, "vocab_size": 20480}

TINY_STATE = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 11, "first_k_dense_replace": 1,
    "linear_attn_config": {"full_attn_layers": [4, 8, 11], "head_dim": 16,
                           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10],
                           "num_heads": 2, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 256,
    "num_experts": 4, "published": {"num_experts": 16},
    "held": [4, 4], "num_experts_per_token": 4, "num_expert_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.446,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "torch_dtype": "float32", "builder": "serve_state",
    "program": {
        "model": "paddle_tpu.models.kimi_linear:KimiLinearForCausalLM",
        "config": "paddle_tpu.models.kimi_linear:KimiLinearConfig",
        "config_args": {
            "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
            "moe_intermediate_size": 32, "num_layers": 11,
            "full_attn_layers": [4, 8, 11], "num_heads": 4,
            "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "kda_heads": 2,
            "kda_head_dim": 16, "n_routed_experts": 16,
            "num_experts_per_tok": 4, "held": [4, 4], "max_seq_len": 160,
            "dtype": "float32"}},
    "serve": {"engine": {"slots": 4, "max_len": 160, "paged": True,
                         "prefix_cache": True, "pages": 96,
                         "page_tokens": 8, "prefill_chunk": 16,
                         "queue_max": 16, "async_depth": 1,
                         "state_snapshots": 6}},
    "limits": {"logit_gap_per_tie": 1e-5, "logit_gap_max": 1e-4}}
MIX = dict(util.TINY_SERVE_TRAFFIC, template_tokens=96, item_tokens=[4, 12],
           warm_item_tokens=[4, 12], stagger_tokens=[2, 1],
           compare_pad_tokens=160, compare_margin=0.1, compare_min_ties=5,
           output_tokens=[12, 24], compare_requests=12)


@pytest.fixture(scope="module")
def cell():
    return cells.load(util.HOME.parent, CELL)


def test_the_cell_loads_and_its_metrics_resolve(cell):
    assert cell.chips == 1 and cell.config["builder"] == "serve_state"
    assert cells.builder("serve_state") is serve_state.Run
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) <= names
    # every per-layer metric the latent cell lists, this cell lists too
    for m in cell.bench["per_layer"]:
        if GIGACHAT in m.get("workloads", ()):
            assert m["name"] in names, m["name"]
    for m in cell.per_layer():
        assert callable(cells.reader(m["reader"]))
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tok_s", "itl_p95_s", "setup_s"}
    for m in cell.bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_out_tok_s"


def test_the_traffic_is_the_issues_table(cell):
    want = {"kind": "closed_loop", "clients": 64, "template_tokens": 2048,
            "item_tokens": [128, 512], "output_tokens": [256, 768],
            "blocks": 16, "sampling": "greedy", "compare_requests": 6,
            "compare_pad_tokens": 3328, "compare_margin": 0.05,
            "compare_min_ties": 50, "trace_seconds": 8}
    assert {k: cell.traffic[k] for k in want} == want
    longdoc = json.loads((util.HOME / "traffic" / "longdoc-closed-48.json"
                          ).read_text())
    for key in ("stagger_tokens", "warm_item_tokens"):
        assert cell.traffic[key] == longdoc[key]
    eng = cell.config["serve"]["engine"]
    assert eng["slots"] == want["clients"] and eng["max_len"] == 4096
    assert (want["template_tokens"] + want["item_tokens"][1]
            + want["output_tokens"][1]) == want["compare_pad_tokens"]
    assert want["compare_pad_tokens"] <= eng["max_len"]
    # a chunk is whole KDA chunks and whole pages; the template's end is
    # a chunk's end, so a snapshot lies exactly there
    assert eng["prefill_chunk"] % 64 == 0
    assert eng["prefill_chunk"] % eng["page_tokens"] == 0
    assert want["template_tokens"] % eng["prefill_chunk"] == 0
    assert eng["state_snapshots"] == 16


def test_the_traffic_offers_every_seed_the_same_multiset(cell):
    one = sorted(traffic.schedule(cell.traffic, 1))
    assert one == sorted(traffic.schedule(cell.traffic, 2 ** 31 + 5))
    assert len(one) == 64 * 16
    assert {i for i, _ in one} >= {128, 512} and {o for _, o in one} >= {
        256, 768}


def test_the_file_holds_the_catalogs_numbers(cell):
    entry = next(c for c in cell.bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/moonshotai/"
                               "Kimi-Linear-48B-A3B-Instruct/blob/main/"
                               "config.json")
    c = cell.config
    for key, value in CATALOG.items():
        assert c[key] == REDUCED.get(key, value), key
    assert c["published"] == {"num_experts": 256, "vocab_size": 163840}
    assert c["held"] == [0, 16] and c["reduced"] == entry["reduced"]
    assert "16 chips" in c["deployment"]
    args = c["program"]["config_args"]
    lin = c["linear_attn_config"]
    for key, arg in [("hidden_size", "hidden_size"),
                     ("intermediate_size", "intermediate_size"),
                     ("moe_intermediate_size", "moe_intermediate_size"),
                     ("num_hidden_layers", "num_layers"),
                     ("first_k_dense_replace", "first_k_dense"),
                     ("num_attention_heads", "num_heads"),
                     ("kv_lora_rank", "kv_lora_rank"),
                     ("qk_nope_head_dim", "qk_nope_head_dim"),
                     ("qk_rope_head_dim", "qk_rope_head_dim"),
                     ("v_head_dim", "v_head_dim"),
                     ("vocab_size", "vocab_size"),
                     ("num_experts_per_token", "num_experts_per_tok"),
                     ("routed_scaling_factor", "routed_scaling_factor"),
                     ("num_shared_experts", "n_shared_experts"),
                     ("rms_norm_eps", "rms_eps"), ("held", "held")]:
        assert c[key] == args[arg], key
    # the router keeps its published width
    assert args["n_routed_experts"] == c["published"]["num_experts"]
    assert args["full_attn_layers"] == lin["full_attn_layers"]
    assert (args["kda_heads"], args["kda_head_dim"], args["conv_kernel"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert args["kda_rank"] == c["assumed"]["kda_rank"] == lin["head_dim"]
    for key in ("state precision", "decay init", "init", "snapshot policy",
                "prefill_chunk", "state_snapshots"):
        assert key in c["assumed"]
    for key in ("logit_gap_per_tie", "logit_gap_max"):
        assert c["limits"][key] > 0 and key in c["limits_why"]


# -- required work at the published widths --------------------------------------

@pytest.fixture(scope="module")
def arch(cell):
    return reference_kimi_linear.Arch.from_config(cell.config)


def test_work_kda_matches_hand_arithmetic(arch):
    a = arch
    assert (a.layers, work_kda.kda_layers(a), work_kda.latent_layers(a),
            work_kda.expert_layers(a)) == (27, 20, 7, 26)
    assert (a.period, a.whole_periods) == (4, 5)
    assert [a.kind(l) for l in (0, 3, 24, 25, 26)] == [
        "kda", "mla", "kda", "kda", "mla"]
    kda = (3 * 2304 * 4096 + 4 * 12288 + 2 * (2304 * 128 + 128 * 4096)
           + 2304 * 32 + 4096 * 2304)
    assert work_kda.kda_params(a) == kda == 39_510_016
    mla = (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304)
    assert work_kda.mla_params(a) == mla == 29_114_368
    expert = 3 * 2304 * 1024
    assert expert == 7_077_888
    fixed = (20 * kda + 7 * mla + 3 * 2304 * 9216
             + 26 * (expert + 2304 * 256) + 2304 * 20480)
    assert work_kda.token_fixed_params(a) == fixed
    held = fixed + 2304 * 20480 + 26 * 16 * expert
    assert work_kda.params_held(a) == held
    assert round(held / 1e9, 2) == 4.3 and round(2 * held / 1e9, 2) == 8.59
    # a slot's recurrent state: float32 state and bf16 convolution tail
    assert work_kda.state_bytes_per_slot(a) == 20 * (2_097_152 + 73_728) \
        == 43_417_600
    assert work_kda.kv_bytes_per_token(a) == 7 * 576 * 2 == 8064
    # one decode token behind 2 700 cached positions
    assert work_kda.serve_flops(a, 2700, 1) == pytest.approx(
        2 * fixed + 6 * 32 * 128 * 128 * 20
        + 2 * 7 * 32 * (192 + 128) * 2701)


def test_decode_step_work_counts_each_byte_once(arch):
    a = arch
    w = work_kda.decode_step_work(a, 64, 64 * 2700)
    touched = 16 * (1 - (1 - 8 / 256) ** 64)
    fixed = work_kda.token_fixed_params(a)
    assert w["bytes"] == pytest.approx(
        2 * (fixed + 26 * touched * 7_077_888)
        + 64 * 2 * 43_417_600 + 64 * 2700 * 8064)
    # ISSUE.md's forecast: weights 7.7, state 5.4 (5.56 with the tails),
    # latent 1.4 (unpadded rows): ~14.5 GB = ~17.7 ms at 819 GB/s
    assert 14.2e9 < w["bytes"] < 14.8e9
    assert w["flops"] == pytest.approx(
        64 * 2 * fixed + 64 * 20 * 6 * 32 * 128 * 128
        + 2 * 7 * 32 * 320 * 64 * 2700
        + 2 * 7_077_888 * 64 * 8 * 16 / 256 * 26)
    k = work_kda.kda_step_work(a, 64)
    assert k["bytes"] == 64 * 20 * 2 * 2_097_152
    assert k["flops"] == 64 * 20 * 32 * 6 * 128 * 128
    # the kernel is bound by the state it moves: 5.4 GB a step
    assert k["bytes"] / 819e9 > 50 * k["flops"] / 197e12


# -- a tiny cell of the same builder ----------------------------------------------

def test_a_tiny_state_cell_runs_restores_agrees_and_fails_its_faults(
        tmp_path):
    metrics = ("serve_mfu", "decode_step_ms", "prefix_token_share",
               "compiles_in_window", "pages_used_peak_share",
               "moe_held_pick_share", "moe_tokens_per_held_expert",
               "kv_bytes_per_token", "decode_step_roofline") + NEW_METRICS
    root = util.make_cell(tmp_path, "new-cell", TINY_STATE, MIX, 1, metrics)
    cell = cells.load(root, "new-cell")
    r = run.drive(cell, 2 ** 31 + 11, 3.0, True, jax.devices(),
                  CompileMeter(), out=io.StringIO(), err=io.StringIO(),
                  chip_peaks=(1e12, 1e11))
    assert r["correct"] and r["attempted"] > 4 and r["failed"] == 0
    got = r["metrics"]
    # 3 latent layers x (16 + 8 -> 128 lanes) x float32
    assert got["kv_bytes_per_token"]["value"] == 3 * 128 * 4
    # 8 KDA layers x (2 heads x 16 x 16 state + 3 x 96 tail), float32
    assert got["state_bytes_per_slot"]["value"] == 8 * (2048 + 1152)
    # every admission of the window restores the template's snapshot
    assert got["state_restore_share"]["value"] == 100
    assert 80 < got["prefix_token_share"]["value"] <= 100
    # 4 of 16 experts held: a quarter of the picks under even routing
    assert 5 < got["moe_held_pick_share"]["value"] < 60
    assert got["compiles_in_window"]["value"] == 0
    assert 0 < got["serve_mfu"]["value"] < 100
    # no chip in a CPU trace: the roofline readers find no program and no
    # kernel, and the metrics are left out, never reported as 0
    assert "decode_step_roofline" not in got
    assert "kda_step_roofline" not in got

    b = serve_state.Run(cell, 5, jax.devices()[:1])
    b.setup()
    b.window(5.0)
    b.free()
    assert run.compare.verdict(b.compare())
    assert not run.compare.verdict(b.control())
    wrong = {n: v > lim for n, v, lim, _ in b.fault("altered_token")}
    assert wrong["logit_gap_max"]
    # the comparison sees a lost snapshot, and at float32 a bf16 state
    assert not run.compare.verdict(b.fault("no_state_restore"))
    assert not run.compare.verdict(b.fault("state_bf16"))


def test_the_reference_cut_forgets_exactly_what_lies_before_it():
    """``no_state_restore`` at position ``cut`` is the forward of the
    row's tail alone in the KDA layers: a one-layer-kind check on the
    reference itself (the latent layers still see the whole row)."""
    import jax.numpy as jnp

    a = reference_kimi_linear.Arch.from_config(TINY_STATE)
    key = jax.random.PRNGKey(3)
    p = reference_kimi_linear.layer_params(a, key, 0)
    h = jax.random.normal(jax.random.PRNGKey(4), (40, 64))
    cut = reference_kimi_linear.kda_row(h, p, a, cut=24)
    whole = reference_kimi_linear.kda_row(h, p, a)
    tail = reference_kimi_linear.kda_row(h[24:], p, a)
    assert jnp.allclose(cut[:24], whole[:24], atol=1e-6)
    assert jnp.allclose(cut[24:], tail, atol=1e-6)
    assert not jnp.allclose(cut[24:], whole[24:], atol=1e-3)


def test_a_program_without_a_state_group_reports_neither_counter():
    assert serve_state.state_group({"pages": 4}) == {}
    assert serve_state.state_group(
        {"groups": [{"name": "full"}, {"name": "state", "snapshots": 2}]}
    ) == {"name": "state", "snapshots": 2}
