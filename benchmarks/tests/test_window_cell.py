"""What PR 33 adds to the benchmark: the cell of the SmallThinker
configuration loads through the loader, its file holds the catalog
row's widths, ``work_window`` matches hand arithmetic at the published
widths (a slot at fill 100, 4 096, 4 097, 13 000), the traffic offers
every seed the same multiset, and a tiny cell of the same builder runs,
slides, agrees with its reference and fails its control and its
``no_window`` fault."""

import io
import json

import jax
import pytest

from benchmarks import run
from benchmarks.builders import serve_window
from benchmarks.lib import cells, reference_smallthinker, traffic, work_window
from benchmarks.lib.meter import CompileMeter
from benchmarks.tests import util

CELL = "serve-smallthinker-longdoc"
CONFIG = "smallthinker-21b-a3b-l8-serve"
NEW_METRICS = ("kv_window_pages_per_stream_peak", "kv_slid_pages_per_ktok",
               "paged_window_attn_roofline")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_theta": 1500000,
    "sliding_window_size": 4096, "vocab_size": 151936,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "tie_word_embeddings": False, "rope_scaling": None,
    "rope_layout": [0, 1, 1, 1] * 13,
    "sliding_window_layout": [0, 1, 1, 1] * 13}

TINY_WINDOW = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_ffn_hidden_size": 32, "vocab_size": 256,
    "num_hidden_layers": 8, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "sliding_window_size": 32,
    "sliding_window_layout": [0, 1, 1, 1] * 2,
    "rope_layout": [0, 1, 1, 1] * 2, "rope_theta": 1500000,
    "rms_norm_eps": 1e-06, "torch_dtype": "float32",
    "builder": "serve_window",
    "program": {
        "model": "paddle_tpu.models.smallthinker:SmallThinkerForCausalLM",
        "config": "paddle_tpu.models.smallthinker:SmallThinkerConfig",
        "config_args": {
            "vocab_size": 256, "hidden_size": 64, "num_layers": 8,
            "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
            "moe_intermediate_size": 32, "num_experts": 8,
            "num_experts_per_tok": 3, "sliding_window": 32,
            "max_seq_len": 160, "dtype": "float32"}},
    "serve": {"engine": {"slots": 4, "max_len": 160, "paged": True,
                         "prefix_cache": True, "pages": [96, 64],
                         "page_tokens": 8, "prefill_chunk": 16,
                         "queue_max": 16, "async_depth": 1}},
    "limits": {"logit_gap_per_tie": 1e-5, "logit_gap_max": 1e-4}}
# a template three windows long, items and outputs past a page
MIX = dict(util.TINY_SERVE_TRAFFIC, template_tokens=96, item_tokens=[4, 12],
           warm_item_tokens=[4, 12], stagger_tokens=[2, 1],
           compare_pad_tokens=160, compare_margin=0.1, compare_min_ties=5,
           output_tokens=[12, 24], compare_requests=12)


@pytest.fixture(scope="module")
def cell():
    return cells.load(util.HOME.parent, CELL)


def test_the_cell_loads_and_its_metrics_resolve(cell):
    assert cell.chips == 1 and cell.config["builder"] == "serve_window"
    assert cells.builder("serve_window") is serve_window.Run
    names = [m["name"] for m in cell.per_layer()]
    assert set(NEW_METRICS) <= set(names)
    assert {"serve_mfu", "decode_step_roofline", "moe_held_pick_share",
            "kv_bytes_per_token", "pages_used_peak_share"} <= set(names)
    for m in cell.per_layer():
        assert callable(cells.reader(m["reader"]))
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tok_s", "itl_p95_s", "setup_s"}
    # the new metrics are this cell's alone
    for m in cell.bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]


def test_the_traffic_is_the_issues_table(cell):
    want = {"kind": "closed_loop", "clients": 48, "template_tokens": 12288,
            "item_tokens": [128, 512], "output_tokens": [256, 768],
            "blocks": 16, "sampling": "greedy", "compare_requests": 6,
            "compare_pad_tokens": 13824, "compare_margin": 0.05,
            "compare_min_ties": 50, "trace_seconds": 8}
    assert {k: cell.traffic[k] for k in want} == want
    eng = cell.config["serve"]["engine"]
    assert eng["slots"] == want["clients"] and eng["max_len"] == 13824
    assert (want["template_tokens"] + want["item_tokens"][1]
            + want["output_tokens"][1]) <= eng["max_len"]
    # every stream lives three windows deep; every tail is one chunk
    assert want["template_tokens"] == 3 * cell.config["sliding_window_size"]
    assert want["item_tokens"][1] <= eng["prefill_chunk"]


def test_the_traffic_offers_every_seed_the_same_multiset(cell):
    one = sorted(traffic.schedule(cell.traffic, 1))
    assert one == sorted(traffic.schedule(cell.traffic, 2 ** 31 + 5))
    assert len(one) == 48 * 16
    assert {i for i, _ in one} >= {128, 512} and {o for _, o in one} >= {
        256, 768}


def test_the_file_holds_the_catalogs_numbers(cell):
    entry = next(c for c in cell.bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/PowerInfer/"
                               "SmallThinker-21BA3B-Instruct/blob/main/"
                               "config.json")
    c = cell.config
    for key, value in CATALOG.items():
        if key != "num_hidden_layers":
            assert c[key] == value, key
    assert c["num_hidden_layers"] == 8
    assert c["published"] == {"num_hidden_layers": 52}
    args = c["program"]["config_args"]
    for key, arg in [("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
                     ("num_attention_heads", "num_heads"),
                     ("num_key_value_heads", "num_kv_heads"),
                     ("moe_ffn_hidden_size", "moe_intermediate_size"),
                     ("moe_num_primary_experts", "num_experts"),
                     ("moe_num_active_primary_experts",
                      "num_experts_per_tok"),
                     ("sliding_window_size", "sliding_window"),
                     ("vocab_size", "vocab_size"),
                     ("num_hidden_layers", "num_layers"),
                     ("rope_theta", "rope_base"),
                     ("rms_norm_eps", "rms_eps")]:
        assert c[key] == args[arg], key
    # the period the program scans is the layouts' own
    n = len(args["window_pattern"])
    assert c["sliding_window_layout"] == args["window_pattern"] * (52 // n)
    assert c["rope_layout"] == args["rope_pattern"] * (52 // n)
    for key in ("router input", "expert activation", "init"):
        assert key in c["assumed"]
    assert any("secondary experts" in d for d in c["departures"])


# -- required work at the published widths --------------------------------------

@pytest.fixture(scope="module")
def arch(cell):
    return reference_smallthinker.Arch.from_config(cell.config)


def test_work_window_matches_hand_arithmetic(arch):
    a = arch
    assert (a.layers, a.period, sum(a.window_layout)) == (8, 4, 6)
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attn == 20_971_520 == work_window.attn_params(a)
    assert work_window.expert_params(a) == 3 * 2560 * 768 == 5_898_240
    layer = attn + 2560 * 64 + 64 * 5_898_240
    assert layer == 398_622_720
    held = 8 * layer + 2 * 151936 * 2560
    assert work_window.params_held(a) == held == 3_966_894_080
    assert round(held / 1e9, 2) == 3.97
    assert round(work_window.weight_bytes(a) / 1e9, 2) == 7.93
    assert work_window.kv_bytes_per_token(a) == 8 * 2048 == 16384
    # one decode token behind 13 000 cached positions: every position on
    # 2 layers, the window's 4 096 (itself among them) on 6
    active = 8 * (attn + 2560 * 64 + 6 * 5_898_240) + 2560 * 151936
    assert work_window.token_fixed_params(a) == active
    assert work_window.serve_flops(a, 13000, 1) == pytest.approx(
        2 * active + 4 * 28 * 128 * (2 * 13001 + 6 * 4096))
    # a chunk that crosses the window's edge: positions 4090..4099
    n_full = sum(t + 1 for t in range(4090, 4100))
    n_win = sum(min(t + 1, 4096) for t in range(4090, 4100))
    assert work_window.serve_flops(a, 4090, 10) == pytest.approx(
        2 * active * 10 + 4 * 28 * 128 * (2 * n_full + 6 * n_win))


@pytest.mark.parametrize("fill,full,win", [
    (100, 7, 7),            # ceil(100 / 16) on both kinds
    (4096, 256, 256),       # the window still starts in page 0 (at 1)
    (4097, 257, 257),       # from position 2: pages 0..256
    (13000, 813, 257)])     # from 8 905 (page 556) to 12 999 (page 812)
def test_live_pages_of_a_slot(arch, fill, full, win):
    assert work_window.kv_pages(fill, 16) == full
    assert work_window.kv_pages(fill, 16, 4096) == win
    page_bytes = 2 * 4 * 16 * 128 * 2               # both leaves, 4 heads
    assert work_window.decode_kv_bytes(arch, fill) == (
        2 * full + 6 * win) * page_bytes
    # the kernel's products: every cached position seen, a layer
    assert work_window.decode_attn_flops(arch, fill) == (
        4 * 28 * 128 * (2 * fill + 6 * min(fill, 4095)))


def test_decode_step_work_counts_each_byte_once(arch):
    a = arch
    kv = 48 * work_window.decode_kv_bytes(a, 13000)
    assert 4.9e9 < kv < 5.1e9                       # ~104 MB a slot
    w = work_window.decode_step_work(a, 48, kv, 0.0)
    touched = 64 * (1 - (1 - 6 / 64) ** 48)
    assert work_window.experts_touched(a, 48) == pytest.approx(touched)
    fixed = 8 * (20_971_520 + 2560 * 64) + 2560 * 151936
    assert w["bytes"] == pytest.approx(
        2 * (fixed + 8 * touched * 5_898_240) + kv)
    assert 11.9e9 < w["bytes"] < 12.3e9             # ~15 ms at 819 GB/s
    assert w["flops"] == pytest.approx(
        48 * 2 * work_window.token_fixed_params(a))
    # without the window the same step reads twice the K/V
    assert 48 * 8 * work_window.kv_pages(13000, 16) * 32768 > 2.0 * kv


# -- a tiny cell of the same builder ----------------------------------------------

def test_a_tiny_window_cell_runs_slides_agrees_and_fails_its_faults(tmp_path):
    metrics = ("serve_mfu", "decode_step_ms", "prefix_token_share",
               "compiles_in_window", "pages_used_peak_share",
               "moe_held_pick_share", "moe_tokens_per_held_expert",
               "kv_bytes_per_token", "decode_step_roofline")
    root = util.make_cell(tmp_path, "new-cell", TINY_WINDOW, MIX, 1, metrics)
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
    r = run.drive(cell, 2 ** 31 + 11, 3.0, True, jax.devices(),
                  CompileMeter(), out=io.StringIO(), err=io.StringIO(),
                  chip_peaks=(1e12, 1e11))
    assert r["correct"] and r["attempted"] > 4 and r["failed"] == 0
    got = r["metrics"]
    assert got["kv_bytes_per_token"]["value"] == 8 * 2 * 2 * 16 * 4
    assert got["moe_held_pick_share"]["value"] == 100     # all 8 held
    assert got["moe_tokens_per_held_expert"]["value"] > 0
    assert got["compiles_in_window"]["value"] == 0
    assert 0 < got["serve_mfu"]["value"] < 100
    assert 0 < got["pages_used_peak_share"]["value"] <= 100
    # a row never holds more than window / page + 1 + chunk / page pages
    assert 0 < got["kv_window_pages_per_stream_peak"]["value"] <= 7
    # every stream is past its window: a page goes for every 8 written
    assert 100 < got["kv_slid_pages_per_ktok"]["value"] <= 125.0
    # no chip in a CPU trace: the roofline readers find no program and no
    # kernel, and the metrics are left out, never reported as 0
    assert "decode_step_roofline" not in got
    assert "paged_window_attn_roofline" not in got

    b = serve_window.Run(cell, 5, jax.devices()[:1])
    b.setup()
    b.window(5.0)
    b.free()
    assert run.compare.verdict(b.compare())
    assert not run.compare.verdict(b.control())
    wrong = {n: v > lim for n, v, lim, _ in b.fault("altered_token")}
    assert wrong["logit_gap_max"]
    # at float32 and a window of 32 the comparison sees the window
    assert not run.compare.verdict(b.fault("no_window"))


def test_a_program_without_a_window_group_reports_neither_counter():
    assert serve_window.window_group({"pages": 4}) == {}
    assert serve_window.window_group(
        {"groups": [{"name": "full"}, {"name": "window", "pages": 2}]}
    ) == {"name": "window", "pages": 2}
