"""A cell made of nothing but new files resolves through the loader and
runs through ``run.drive``; the plain references agree with the program
at tiny presets; the control and every planted fault come out as not
correct."""

import contextlib
import io
import json
import re

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.builders import serve, train
from benchmarks.lib import cells
from benchmarks.lib.meter import CompileMeter
from benchmarks.tests import util

TRAIN_METRICS = ("train_mfu", "train_step_ms", "flash_attn_roofline",
                 "train_peak_hbm_share", "partition_fallbacks",
                 "collective_exposed_share")
SERVE_METRICS = ("serve_mfu", "ttft_p90_s", "decode_step_ms", "gen_late_p99_ms",
                 "prefix_token_share", "pages_used_peak_share",
                 "compiles_in_window")
SERVE_CFG = dict(
    util.TINY_MOE, builder="serve",
    limits={"logit_gap_per_tie": 1e-5, "logit_gap_max": 1e-4},
    serve={"engine": {"slots": 4, "max_len": 64, "paged": True,
                      "prefix_cache": True, "pages": 64, "page_tokens": 8,
                      "prefill_chunk": 32, "queue_max": 16}})
SERVE_MIX = dict(util.TINY_SERVE_TRAFFIC, warm_item_tokens=[4, 12],
                 stagger_tokens=[2, 1], compare_pad_tokens=64,
                 compare_margin=0.1)
MOE_TRAIN = dict(util.TINY_MOE, builder="train",
                 train=util.TINY_DENSE["train"],
                 limits=util.TINY_DENSE["limits"])
# capacity 1.0: the program drops picks, which no configuration states
MOE_DROPS = dict(MOE_TRAIN, program=dict(
    MOE_TRAIN["program"], config_args=dict(
        MOE_TRAIN["program"]["config_args"], capacity_factor=1.0)))
# a clip that bites at every step (the tiny model's gradient norm is ~1)
DENSE_CLIPPED = dict(util.TINY_DENSE, train=dict(
    util.TINY_DENSE["train"],
    adamw=dict(util.TINY_DENSE["train"]["adamw"], clip_norm=0.05)))


def drive(cell, trace=False, seed=2 ** 31 + 11, seconds=1.0):
    out = io.StringIO()
    result = run.drive(cell, seed, seconds, trace, jax.devices(),
                       CompileMeter(), out=out, err=io.StringIO(),
                       chip_peaks=(1e12, 1e11))
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    assert list(result)[-1] == "compared"
    return result


def cell_of(tmp_path, config, mix, chips=1, metrics=()):
    name = "new-cell"
    return cells.load(util.make_cell(tmp_path, name, config, mix, chips,
                                     metrics), name)


def test_run_py_branches_on_no_name():
    text = (util.HOME / "run.py").read_text()
    real = json.loads((util.HOME.parent / "BENCHMARK.json").read_text())
    for entry in real["workloads"] + real["configs"]:
        assert entry["name"] not in text
    assert not re.search(r"workload\s*==|config\s*==", text)


@pytest.mark.parametrize("config,chips", [
    (util.TINY_DENSE, 1), (DENSE_CLIPPED, 1), (MOE_TRAIN, 1), (MOE_TRAIN, 4)],
    ids=["internlm2-like-gqa", "internlm2-like-clipped",
         "olmoe-like-top2of8", "olmoe-like-zero3x4"])
def test_new_train_cell_runs_and_agrees_with_reference(tmp_path, config,
                                                       chips):
    cell = cell_of(tmp_path, config, util.TINY_TRAIN_TRAFFIC, chips,
                   TRAIN_METRICS)
    r = drive(cell)
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert set(r["compared"]) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "change_norm_gap"}


@pytest.mark.parametrize("chips,found", [
    (1, {"train_mfu"}), (4, {"train_mfu", "partition_fallbacks"})])
def test_traced_train_run_reports_per_layer_metrics(tmp_path, chips, found):
    cell = cell_of(tmp_path, util.TINY_DENSE, util.TINY_TRAIN_TRAFFIC, chips,
                   TRAIN_METRICS)
    r = drive(cell, trace=True)
    # no chip in a CPU trace: readers of the device trace find nothing
    # and are left out, never reported as 0
    assert set(r["metrics"]) == found
    assert r["correct"] and "breakdown" in r


def test_a_metric_that_lists_no_cells_goes_where_its_moves_is(tmp_path):
    root = util.make_cell(tmp_path, "new-cell", util.TINY_DENSE,
                          util.TINY_TRAIN_TRAFFIC, 1,
                          ("train_mfu", "serve_mfu"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        del m["workloads"]
    for m in bench["end_to_end"]:      # a train cell reports no serve rate
        if m["name"] in ("serve_out_tok_s", "itl_p95_s"):
            m["workloads"] = ["another-cell"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load(root, "new-cell")
    assert [m["name"] for m in cell.per_layer()] == ["train_mfu"]


def test_new_serve_cell_runs_and_agrees_with_reference(tmp_path):
    cell = cell_of(tmp_path, SERVE_CFG, SERVE_MIX, 1, SERVE_METRICS)
    r = drive(cell, seconds=2.0)
    assert r["correct"] and r["attempted"] > 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"serve_out_tok_s", "itl_p95_s", "setup_s"}
    t = drive(cell, trace=True, seconds=2.0)
    assert {"serve_mfu", "ttft_p90_s", "gen_late_p99_ms",
            "prefix_token_share", "compiles_in_window"} <= set(t["metrics"])
    assert t["metrics"]["compiles_in_window"]["value"] == 0
    assert t["metrics"]["prefix_token_share"]["value"] > 50


# -- the control and the faults have to fail -----------------------------------

@pytest.mark.parametrize("chips", [1, 4])
def test_a_program_that_drops_tokens_is_not_correct(tmp_path, chips):
    cell = cell_of(tmp_path, MOE_DROPS, util.TINY_TRAIN_TRAFFIC, chips)
    assert drive(cell)["correct"] is False


def test_a_clip_the_program_leaves_out_is_not_correct(tmp_path, monkeypatch):
    from paddle_tpu import optimizer as optim

    monkeypatch.setattr(optim, "ClipGradByGlobalNorm", lambda norm: None)
    cell = cell_of(tmp_path, DENSE_CLIPPED, util.TINY_TRAIN_TRAFFIC)
    assert drive(cell)["correct"] is False


def test_train_control_in_float8_fails(tmp_path):
    cell = cell_of(tmp_path, util.TINY_DENSE, util.TINY_TRAIN_TRAFFIC)
    r = train.Run(cell, 5, jax.devices()[:1])
    r.setup()
    r.free()
    assert run.compare.verdict(r.compare())
    assert not run.compare.verdict(r.control())
    assert not run.compare.verdict(r.fault("half_batch"))


def broken_train(monkeypatch, how):
    real = train.Run._one

    def one(self, state, ids, i):
        if how == "state_unchanged":
            _, metrics = real(self, jax.tree_util.tree_map(
                lambda x: x.copy(), state), ids, i)
            return state, metrics
        if how == "half_batch":          # the mean over half of the rows
            ids = np.concatenate([ids[: len(ids) // 2]] * 2)
        if how == "no_exchange":         # one chip's rows, on every chip
            n = len(self.devices)
            ids = np.concatenate([ids[: len(ids) // n]] * n)
        return real(self, state, ids, i)

    monkeypatch.setattr(train.Run, "_one", one)


@pytest.mark.parametrize("how,chips", [
    ("state_unchanged", 1), ("half_batch", 1), ("no_exchange", 4)])
def test_broken_train_step_is_not_correct(tmp_path, monkeypatch, how, chips):
    cell = cell_of(tmp_path, util.TINY_DENSE, util.TINY_TRAIN_TRAFFIC, chips)
    broken_train(monkeypatch, how)
    assert drive(cell)["correct"] is False


def test_serve_control_and_altered_token_fail(tmp_path, monkeypatch):
    # some hundreds of sampled tokens, so that float8 flips some whichever
    # requests the threads happen to finish inside the window
    long = dict(SERVE_MIX, output_tokens=[16, 24], compare_requests=16)
    r = serve.Run(cell_of(tmp_path / "long", SERVE_CFG, long), 5,
                  jax.devices()[:1])
    r.setup()
    r.window(5.0)
    r.free()
    assert run.compare.verdict(r.compare())
    assert not run.compare.verdict(r.control())
    wrong = {n: v > lim for n, v, lim, _ in r.fault("altered_token")}
    assert wrong["logit_gap_max"]

    # a token altered where it is produced: every stream's fifth token
    real = serve.loadgen.ClosedLoop._client

    def client(self, c, cap):
        make = self._make_sender

        @contextlib.contextmanager
        def altered():
            with make() as send:
                def send2(prompt, n):
                    for i, tok in enumerate(send(prompt, n)):
                        yield (tok + 1) % 256 if i == 4 else tok
                yield send2

        self._make_sender = altered
        return real(self, c, cap)

    monkeypatch.setattr(serve.loadgen.ClosedLoop, "_client", client)
    cell = cell_of(tmp_path / "short", SERVE_CFG, SERVE_MIX)
    assert drive(cell, seconds=2.0)["correct"] is False
