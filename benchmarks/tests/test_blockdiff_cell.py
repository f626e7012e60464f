"""The block-diffusion cell: it loads through the loader, its file holds
the catalog row's numbers (only ``num_hidden_layers`` reduced),
``work_block`` matches hand arithmetic at the published widths, the
traffic offers every seed the same multiset, and a tiny cell of the same
builder runs, agrees with its reference and fails its control and each
planted fault."""

import io
import json
import types

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.builders import common, serve_blockdiff
from benchmarks.lib import cells, reference_sdar, traffic, work_block
from benchmarks.lib import weights as W
from benchmarks.lib.meter import CompileMeter
from benchmarks.tests import util

CELL = "serve-sdar-blockdiff-chat"
CONFIG = "sdar-30b-a3b-l6-serve"
WINDOW = "serve-smallthinker-longdoc"
WINDOW_GROUP = ("kv_window_pages_per_stream_peak", "kv_slid_pages_per_ktok",
                "paged_window_attn_roofline")
NEW_METRICS = ("block_attn_roofline", "block_tokens_per_slot_step")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 6}

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "vocab_size": 256,
    "num_hidden_layers": 2, "num_experts": 8, "num_experts_per_tok": 3,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000, "block_length": 4,
    "denoising_steps": 4, "mask_token_id": 255, "torch_dtype": "float32",
    "builder": "serve_blockdiff",
    "program": {
        "model": "paddle_tpu.models.sdar:SDARForCausalLM",
        "config": "paddle_tpu.models.sdar:SDARConfig",
        "config_args": {
            "vocab_size": 256, "hidden_size": 64, "num_layers": 2,
            "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
            "moe_intermediate_size": 32, "num_experts": 8,
            "num_experts_per_tok": 3, "max_seq_len": 160,
            "rope_base": 1000000.0, "rms_eps": 1e-06,
            "dtype": "float32"}},
    "serve": {"engine": {"slots": 4, "max_len": 160, "paged": True,
                         "prefix_cache": True, "pages": 96,
                         "page_tokens": 8, "prefill_chunk": 16,
                         "queue_max": 16, "async_depth": 1}},
    "limits": {"logit_gap_per_tie": 1e-5, "logit_gap_max": 1e-4,
               "confidence_gap_max": 1e-4}}
MIX = dict(util.TINY_SERVE_TRAFFIC, template_tokens=48, item_tokens=[5, 14],
           warm_item_tokens=[5, 14], stagger_tokens=[2, 1],
           compare_pad_tokens=96, compare_margin=0.1, compare_min_ties=5,
           output_tokens=[6, 17], compare_requests=4, compare_blocks=3)


@pytest.fixture(scope="module")
def cell():
    return cells.load(util.HOME.parent, CELL)


def test_the_cell_loads_and_its_metrics_resolve(cell):
    assert cell.chips == 1 and cell.config["builder"] == "serve_blockdiff"
    assert cells.builder("serve_blockdiff") is serve_blockdiff.Run
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) <= names
    # every per-layer metric the window cell lists but its group's three
    for m in cell.bench["per_layer"]:
        if WINDOW in m.get("workloads", ()):
            assert (m["name"] in names) == (m["name"] not in WINDOW_GROUP)
    for m in cell.per_layer():
        assert callable(cells.reader(m["reader"]))
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tok_s", "itl_p95_s", "setup_s"}
    for m in cell.bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_out_tok_s"


def test_the_traffic_is_the_issues_table(cell):
    want = {"kind": "closed_loop", "clients": 64, "template_tokens": 1024,
            "item_tokens": [128, 512], "output_tokens": [256, 768],
            "blocks": 16, "sampling": "greedy", "compare_requests": 6,
            "compare_blocks": 8, "compare_pad_tokens": 2320,
            "compare_margin": 0.05, "compare_min_ties": 50,
            "trace_seconds": 8}
    assert {k: cell.traffic[k] for k in want} == want
    longgen = json.loads((util.HOME / "traffic" / "longgen-closed-64.json"
                          ).read_text())
    for key in ("stagger_tokens", "warm_item_tokens"):
        assert cell.traffic[key] == longgen[key]
    eng = cell.config["serve"]["engine"]
    assert eng["slots"] == want["clients"] and eng["max_len"] == 2320
    assert want["compare_pad_tokens"] == eng["max_len"] >= (
        want["template_tokens"] + want["item_tokens"][1]
        + want["output_tokens"][1])
    # whole blocks and whole pages: a chunk ends on a block, a block
    # never crosses a page, the template is whole pages
    B = cell.config["block_length"]
    assert eng["prefill_chunk"] % eng["page_tokens"] == 0
    assert eng["page_tokens"] % B == 0
    assert want["template_tokens"] % eng["page_tokens"] == 0
    assert cell.config["denoising_steps"] == 4


def test_the_traffic_offers_every_seed_the_same_multiset(cell):
    one = sorted(traffic.schedule(cell.traffic, 1))
    assert one == sorted(traffic.schedule(cell.traffic, 2 ** 31 + 5))
    assert len(one) == 64 * 16


def test_the_file_holds_the_catalogs_numbers(cell):
    entry = next(c for c in cell.bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    c = cell.config
    for key, value in CATALOG.items():
        assert c[key] == REDUCED.get(key, value), key
    assert c["published"] == {"num_hidden_layers": 48}
    assert c["reduced"] == entry["reduced"]
    assert "eight pipeline stages" in c["deployment"]
    args = c["program"]["config_args"]
    for key, arg in [("hidden_size", "hidden_size"),
                     ("moe_intermediate_size", "moe_intermediate_size"),
                     ("num_hidden_layers", "num_layers"),
                     ("num_attention_heads", "num_heads"),
                     ("num_key_value_heads", "num_kv_heads"),
                     ("head_dim", "head_dim"), ("vocab_size", "vocab_size"),
                     ("num_experts", "num_experts"),
                     ("num_experts_per_tok", "num_experts_per_tok"),
                     ("rms_norm_eps", "rms_eps")]:
        assert c[key] == args[arg], key
    assert args["rope_base"] == c["rope_theta"] and args["qk_norm"]
    # the block keys stand once, at the top level, where the reference
    # reads them; the builder hands them to the program
    keys = serve_blockdiff.BLOCK_KEYS
    assert not set(keys) & (set(args) | set(c["serve"]["engine"]))
    got = serve_blockdiff.Run(cell, 1, []).cfg["program"]["config_args"]
    assert {k: got[k] for k in keys} == {k: c[k] for k in keys}
    for key in ("block_length", "denoising_steps", "remasking",
                "mask_token_id", "logit shift", "qk norm", "init", "max_len",
                "slots", "pages", "prefill_chunk", "async_depth"):
        assert key in c["assumed"], key
    for key in ("logit_gap_per_tie", "logit_gap_max", "confidence_gap_max"):
        assert c["limits"][key] > 0 and key in c["limits_why"]


# -- required work at the published widths --------------------------------------

@pytest.fixture(scope="module")
def arch(cell):
    return reference_sdar.Arch.from_config(cell.config)


def test_work_block_matches_hand_arithmetic(arch):
    a = arch
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert work_block.attn_params(a) == attn == 18_874_368
    expert = 3 * 2048 * 768
    assert work_block.expert_params(a) == expert == 4_718_592
    layer = attn + 2048 * 128 + 128 * expert
    assert round(layer / 1e6, 2) == 623.12
    held = 6 * layer + 2 * 151936 * 2048
    assert work_block.params_held(a) == held
    assert round(held / 1e9, 3) == 4.361 and round(2 * held / 1e9, 2) == 8.72
    assert work_block.kv_bytes_per_token(a) == 12_288
    # one served token of a block at 2 000: 5 forwards of a position
    fixed = 6 * (attn + 2048 * 128 + 8 * expert) + 2048 * 151936
    assert work_block.output_token_flops(a, 2000) == pytest.approx(
        5 * (2 * fixed + 4 * 6 * 32 * 128 * 2004))


def test_block_step_work_counts_each_byte_once(arch):
    a = arch
    kv = 64 * work_block.block_kv_bytes(a, 1600)
    assert work_block.block_kv_bytes(a, 1600) == (100 * 16 + 4) * 12_288
    w = work_block.block_step_work(a, 64, kv, 0.0)
    # 256 positions touch every expert: the whole 8.10 GB of layers and
    # head (the embedding is a lookup) and ~1.3 GB of live K/V
    assert w["bytes"] == pytest.approx(
        2 * (6 * (18_874_368 + 2048 * 128) + 2048 * 151936
             + 6 * 128 * (1 - (1 - 8 / 128) ** 256) * 4_718_592) + kv)
    assert 9.2e9 < w["bytes"] < 9.5e9
    assert w["bytes"] / 819e9 > 3 * w["flops"] / 197e12


def test_a_step_fixes_by_confidence_or_from_the_left():
    masked = np.asarray([False, True, True, True])
    conf = np.asarray([9.0, 0.1, 0.5, 0.5])
    fixing = serve_blockdiff.fixing
    # the prompt's position never; equal confidences the lower first
    assert fixing(masked, 1, conf).tolist() == [0, 0, 1, 0]
    assert fixing(masked, 2, conf).tolist() == [0, 0, 1, 1]
    assert fixing(masked, 2).tolist() == [0, 1, 1, 0]
    assert serve_blockdiff.block_books({"pages": 1}) == {}


def test_a_window_with_no_finished_request_compares_nothing(cell):
    b = serve_blockdiff.Run(cell, 1, [])
    b.sample = None
    rows = b._gaps("float32")
    assert [r[0] for r in rows] == list(serve_blockdiff.NAMES)
    assert all(np.isnan(r[1]) for r in rows)


def test_the_program_draws_the_qk_norms_as_the_reference():
    b = serve_blockdiff.Run(types.SimpleNamespace(config=TINY, traffic=MIX),
                            3, [])
    key = W.root_key(2 ** 31 + 3)
    model = serve_blockdiff.with_qk_norm(common.seeded_model(
        common.model_template(b.cfg), key), key)
    a = reference_sdar.Arch.from_config(TINY)
    attn = model.blocks.block.attn
    for l in range(a.layers):
        ref = reference_sdar.layer_params(a, key, l)
        for name in reference_sdar.QK_NORM:
            got = getattr(attn, name.split(".")[1]).weight[l]
            assert np.array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref[name]))
            lo, hi = reference_sdar.QK_NORM_RANGE
            assert lo <= float(ref[name].min()) < float(
                ref[name].max()) <= hi


def test_the_reference_mask_is_block_causal():
    pos = np.arange(8)
    seen = np.asarray(reference_sdar.visible(pos, pos, 4))
    assert seen[1].tolist() == [1, 1, 1, 1, 0, 0, 0, 0]
    assert seen[4].tolist() == [1] * 8
    causal = np.asarray(reference_sdar.visible(pos, pos, 4, causal=True))
    assert causal[1].tolist() == [1, 1, 0, 0, 0, 0, 0, 0]


# -- a tiny cell of the same builder ----------------------------------------------

def test_a_tiny_block_cell_runs_agrees_and_fails_its_faults(tmp_path):
    metrics = ("serve_mfu", "decode_step_ms", "prefix_token_share",
               "compiles_in_window", "pages_used_peak_share",
               "moe_held_pick_share", "moe_tokens_per_held_expert",
               "kv_bytes_per_token", "decode_step_roofline") + NEW_METRICS
    root = util.make_cell(tmp_path, "new-cell", TINY, MIX, 1, metrics)
    cell = cells.load(root, "new-cell")
    r = run.drive(cell, 2 ** 31 + 11, 3.0, True, jax.devices(),
                  CompileMeter(), out=io.StringIO(), err=io.StringIO(),
                  chip_peaks=(1e12, 1e11))
    assert r["correct"] and r["attempted"] > 4 and r["failed"] == 0
    got = r["metrics"]
    # 2 layers x K and V x 2 heads x 16 x float32
    assert got["kv_bytes_per_token"]["value"] == 2 * 2 * 2 * 16 * 4
    assert got["moe_held_pick_share"]["value"] == 100
    assert got["compiles_in_window"]["value"] == 0
    # at most B / (steps + 1): a first block that the prompt's remainder
    # shares fixes fewer, a finished slot's last step fixes nothing
    assert 0.5 < got["block_tokens_per_slot_step"]["value"] <= 0.8
    assert 0 < got["serve_mfu"]["value"] < 100
    # no chip in a CPU trace: the roofline readers find no program and no
    # kernel, and the metrics are left out, never reported as 0
    assert "decode_step_roofline" not in got
    assert "block_attn_roofline" not in got

    b = serve_blockdiff.Run(cell, 5, jax.devices()[:1])
    b.setup()
    b.window(5.0)
    b.free()
    assert run.compare.verdict(b.compare())
    assert not run.compare.verdict(b.control())
    wrong = {n: v > lim for n, v, lim, _ in b.fault("altered_token")}
    assert wrong["logit_gap_max"]
    for kind in ("causal_in_block", "no_qk_norm"):
        assert not run.compare.verdict(b.fault(kind)), kind
    wrong = {n: v > lim for n, v, lim, _ in b.fault("left_to_right")}
    assert wrong["confidence_gap_max"]
