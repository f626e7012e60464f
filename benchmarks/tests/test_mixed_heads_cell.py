"""The cell of the Laguna configuration: it loads through the loader, its
file holds the catalog row's numbers but for the cuts it lists,
``work_mixed_heads`` matches hand arithmetic at the published widths
(1.991 B parameters held, 36 864 B a token, 64 KiB a page of a layer),
the traffic offers every seed the same multiset, and a tiny cell of the
same builder runs, slides, agrees with its reference and fails its
control and every planted fault."""

import io
import json

import jax
import pytest

from benchmarks import run
from benchmarks.builders import serve_mixed_heads
from benchmarks.lib import cells, reference_laguna, traffic, work_mixed_heads
from benchmarks.lib.meter import CompileMeter
from benchmarks.tests import util

CELL = "serve-laguna-ep16-codegen"
CONFIG = "laguna-s-2.1-l9-ep16-serve"
NEW_METRICS = ("mixed_heads_attn_roofline", "window_heads_attn_roofline")
REDUCED = {"num_hidden_layers": (48, 9), "num_experts": (256, 16),
           "vocab_size": (100352, 12544)}

WINDOW = 8
TINY = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "mlp_only_layers": [0],
    "sliding_window": WINDOW, "moe_routed_scaling_factor": 2.5,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "held": [0, 4], "published": {"num_experts": 8},
    "torch_dtype": "float32", "builder": "serve_mixed_heads",
    "program": {
        "model": "paddle_tpu.models.laguna:LagunaForCausalLM",
        "config": "paddle_tpu.models.laguna:LagunaConfig",
        "config_args": {
            "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
            "num_layers": 5, "num_heads": 4, "window_heads": 6,
            "num_kv_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
            "shared_expert_intermediate_size": 32, "num_experts": 8,
            "num_experts_per_tok": 3, "held": [0, 4],
            "sliding_window": WINDOW, "rope_original_max": 16,
            "max_seq_len": 96, "dtype": "float32"}},
    "serve": {"engine": {"slots": 4, "max_len": 96, "paged": True,
                         "prefix_cache": True, "pages": [64, 48],
                         "page_tokens": 8, "prefill_chunk": 16,
                         "queue_max": 16, "async_depth": 1}},
    "limits": {"logit_gap_per_tie": 1e-5, "logit_gap_max": 1e-4}}
# a template three windows long, items and outputs past a page
MIX = dict(util.TINY_SERVE_TRAFFIC, template_tokens=24, item_tokens=[4, 12],
           warm_item_tokens=[4, 12], stagger_tokens=[2, 1],
           compare_pad_tokens=96, compare_margin=0.1, compare_min_ties=5,
           output_tokens=[12, 24], compare_requests=12)


@pytest.fixture(scope="module")
def cell():
    return cells.load(util.HOME.parent, CELL)


def test_the_cell_loads_and_its_metrics_resolve(cell):
    assert cell.chips == 1 and cell.config["builder"] == "serve_mixed_heads"
    assert cells.builder("serve_mixed_heads") is serve_mixed_heads.Run
    names = [m["name"] for m in cell.per_layer()]
    assert set(NEW_METRICS) <= set(names)
    assert {"serve_mfu", "decode_step_roofline", "moe_held_pick_share",
            "kv_bytes_per_token", "kv_window_pages_per_stream_peak",
            "kv_slid_pages_per_ktok"} <= set(names)
    # SmallThinker's kernel share reads that family's work alone
    assert "paged_window_attn_roofline" not in names
    for m in cell.per_layer():
        assert callable(cells.reader(m["reader"]))
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tok_s", "itl_p95_s", "setup_s"}
    for m in cell.bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]


def test_the_traffic_is_the_issues_table(cell):
    want = {"kind": "closed_loop", "clients": 64, "template_tokens": 8192,
            "item_tokens": [128, 512], "output_tokens": [256, 1024],
            "blocks": 16, "sampling": "greedy", "compare_requests": 6,
            "compare_pad_tokens": 9728, "compare_margin": 0.05,
            "compare_min_ties": 50, "trace_seconds": 8}
    assert {k: cell.traffic[k] for k in want} == want
    eng = cell.config["serve"]["engine"]
    assert eng["slots"] == want["clients"] and eng["max_len"] == 9728
    assert (want["template_tokens"] + want["item_tokens"][1]
            + want["output_tokens"][1]) == eng["max_len"]
    # contexts cross YaRN's original 8192; a tail is one chunk as long
    # as the window
    assert want["template_tokens"] + want["item_tokens"][0] > 8192
    assert want["item_tokens"][1] == eng["prefill_chunk"] == 512
    one = sorted(traffic.schedule(cell.traffic, 1))
    assert one == sorted(traffic.schedule(cell.traffic, 2 ** 31 + 5))
    assert len(one) == 64 * 16


def test_the_file_holds_the_catalogs_numbers(cell):
    catalog = json.loads((util.HOME / "tests" / "data"
                          / "laguna-s-2.1-catalog.json").read_text())
    entry = next(c for c in cell.bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == catalog["source_url"]
    c = cell.config
    for key, value in catalog["config"].items():
        if key in REDUCED:
            assert (value, c[key]) == REDUCED[key], key
        else:
            assert c[key] == value, key
    assert c["published"] == {k: v[0] for k, v in REDUCED.items()}
    assert c["held"] == [0, 16]
    assert "16 chips share each layer" in c["deployment"]
    args = c["program"]["config_args"]
    assert args["num_experts"] == 256 and args["held"] == [0, 16]
    for key, arg in [("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
                     ("intermediate_size", "intermediate_size"),
                     ("num_attention_heads", "num_heads"),
                     ("num_key_value_heads", "num_kv_heads"),
                     ("moe_intermediate_size", "moe_intermediate_size"),
                     ("shared_expert_intermediate_size",
                      "shared_expert_intermediate_size"),
                     ("num_experts_per_tok", "num_experts_per_tok"),
                     ("moe_routed_scaling_factor", "routed_scale"),
                     ("sliding_window", "sliding_window"),
                     ("vocab_size", "vocab_size"),
                     ("num_hidden_layers", "num_layers"),
                     ("rms_norm_eps", "rms_eps")]:
        assert c[key] == args[arg], key
    full = c["rope_parameters"]["full_attention"]
    assert (full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"],
            full["partial_rotary_factor"]) == (
        args["rope_base"], args["rope_factor"], args["rope_original_max"],
        args["rope_beta_fast"], args["rope_beta_slow"],
        args["rope_attention_factor"], args["partial_rotary_factor"])
    assert (c["rope_parameters"]["sliding_attention"]["rope_theta"]
            == args["window_rope_base"])
    # the layers run are the published list's first nine, in periods
    kinds = ["sliding_attention" if w else "full_attention"
             for w in args["window_pattern"]]
    assert c["layer_types"][:9] == ["full_attention"] + kinds * 2
    assert c["num_attention_heads_per_layer"][:9] == [
        48] + [args["window_heads"] if w else args["num_heads"]
               for w in args["window_pattern"]] * 2
    for key in ("head gate", "routing", "shared expert", "q/k norm",
                "partial rotary", "init"):
        assert key in c["assumed"]


# -- required work at the published widths --------------------------------------

@pytest.fixture(scope="module")
def arch(cell):
    return reference_laguna.Arch.from_config(cell.config)


def test_work_matches_hand_arithmetic(arch):
    a, w = arch, work_mixed_heads
    assert (a.layers, a.period, a.dense_layers, sum(a.windowed)) == (
        9, 4, 1, 6)
    assert a.heads == (48, 72, 72, 72, 48, 72, 72, 72, 48)
    assert (a.experts, a.held, a.top_k) == (256, (0, 16), 10)
    full = 3072 * 6144 * 2 + 2 * 3072 * 1024 + 3072 * 48
    window = 3072 * 9216 * 2 + 2 * 3072 * 1024 + 3072 * 72
    assert w.attn_params(a, 0) == full == 44_187_648
    assert w.attn_params(a, 1) == window == 63_135_744
    expert = 3 * 3072 * 1024
    assert w.expert_params(a) == expert == 9_437_184
    assert w.layer_fixed_params(a, 0) == full + 3 * 3072 * 12288
    assert w.layer_fixed_params(a, 4) == full + 3072 * 256 + expert
    held = (full + 3 * 3072 * 12288
            + 2 * (full + 3072 * 256 + 17 * expert)
            + 6 * (window + 3072 * 256 + 17 * expert)
            + 2 * 12544 * 3072)
    assert w.params_held(a) == held == 1_991_442_432
    assert round(w.weight_bytes(a) / 1e9, 2) == 3.98
    assert w.kv_bytes_per_token(a) == 9 * 4096 == 36864
    assert w.kv_page_layer_bytes(a) == 64 * 1024


@pytest.mark.parametrize("fill,full,win", [
    (100, 7, 7),            # ceil(100 / 16) on both kinds
    (512, 32, 32),          # the window still starts in page 0 (at 1)
    (8800, 550, 32)])       # from 8 289 (page 518) to 8 799 (page 549)
def test_live_pages_and_products_of_a_slot(arch, fill, full, win):
    w = work_mixed_heads
    page = 65536
    assert w.decode_kv_bytes(arch, fill) == (3 * full + 6 * win) * page
    assert w.decode_kv_bytes(arch, fill, windows=True) == 6 * win * page
    assert w.decode_attn_flops(arch, fill) == (
        4 * 128 * (3 * 48 * fill + 6 * 72 * min(fill, 511)))
    assert w.decode_attn_flops(arch, fill, windows=True) == (
        4 * 128 * 6 * 72 * min(fill, 511))


def test_a_decode_step_at_the_forecast(arch):
    """64 slots at ~8.8 k: ~3.7 GB of weights, 6.9 GB of full-layer K/V
    and 0.81-0.83 GB of window K/V (32 or 33 pages a layer, as the
    window's edge falls), 11.4 GB in all — 14 ms at 819 GB/s."""
    w = work_mixed_heads
    kv = 64 * w.decode_kv_bytes(arch, 8800)
    assert 6.9e9 < 64 * w.decode_kv_bytes(arch, 8800, windows=False) < 7.0e9
    assert 0.80e9 < 64 * w.decode_kv_bytes(arch, 8800, windows=True) < 0.84e9
    step = w.decode_step_work(arch, 64, kv, 0.0)
    assert w.experts_touched(arch, 64) == pytest.approx(
        16 * (1 - (1 - 10 / 256) ** 64))
    assert 3.6e9 < step["bytes"] - kv < 3.8e9
    assert 11.3e9 < step["bytes"] < 11.5e9


def test_serve_flops_of_a_chunk_across_the_window_edge(arch):
    w = work_mixed_heads
    fixed = w.token_fixed_params(arch)
    n_full = sum(t + 1 for t in range(508, 520))
    n_win = sum(min(t + 1, 512) for t in range(508, 520))
    assert w.serve_flops(arch, 508, 12) == pytest.approx(
        2 * fixed * 12 + 4 * 128 * (3 * 48 * n_full + 6 * 72 * n_win))
    assert w.serve_flops(arch, 508, 12, held_picks=7) == pytest.approx(
        w.serve_flops(arch, 508, 12) + 14 * 3 * 3072 * 1024)


# -- a tiny cell of the same builder ----------------------------------------------

def test_a_tiny_cell_runs_agrees_and_fails_its_faults(tmp_path):
    metrics = ("serve_mfu", "decode_step_ms", "prefix_token_share",
               "compiles_in_window", "pages_used_peak_share",
               "moe_held_pick_share", "moe_tokens_per_held_expert",
               "kv_bytes_per_token", "decode_step_roofline",
               "kv_window_pages_per_stream_peak", "kv_slid_pages_per_ktok")
    root = util.make_cell(tmp_path, "new-cell", TINY, MIX, 1, metrics)
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
    r = run.drive(cell, 2 ** 31 + 13, 3.0, True, jax.devices(),
                  CompileMeter(), out=io.StringIO(), err=io.StringIO(),
                  chip_peaks=(1e12, 1e11))
    assert r["correct"] and r["attempted"] > 4 and r["failed"] == 0
    got = r["metrics"]
    assert got["kv_bytes_per_token"]["value"] == 5 * 2 * 2 * 16 * 4
    # 4 of 8 experts held: about half the picks fall here
    assert 20 < got["moe_held_pick_share"]["value"] < 80
    assert got["compiles_in_window"]["value"] == 0
    assert 0 < got["serve_mfu"]["value"] < 100
    # a row never holds more than window / page + 1 + chunk / page pages
    assert 0 < got["kv_window_pages_per_stream_peak"]["value"] <= 4
    assert got["kv_slid_pages_per_ktok"]["value"] > 0
    # no chip in a CPU trace: the roofline readers find no program and no
    # kernel, and the metrics are left out, never reported as 0
    for name in NEW_METRICS + ("decode_step_roofline",):
        assert name not in got

    b = serve_mixed_heads.Run(cell, 5, jax.devices()[:1])
    b.setup()
    b.window(5.0)
    b.free()
    assert run.compare.verdict(b.compare())
    assert not run.compare.verdict(b.control())
    wrong = {n: v > lim for n, v, lim, _ in b.fault("altered_token")}
    assert wrong["logit_gap_max"]
    for kind in reference_laguna.FAULTS:
        assert not run.compare.verdict(b.fault(kind)), kind
