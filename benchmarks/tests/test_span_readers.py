"""The two readers of the program's own spans, on a hand-made ring
(exact arithmetic), and through ``run.drive`` on tiny cells."""

import pytest

from benchmarks.readers import span_ms, span_share
from paddle_tpu.core import trace

from . import test_cells, util

LOOP = dict(loop="gen/loop", idle=["gen/idle_wait"],
            device=["gen/step_wait", "gen/prefill", "gen/prefill_chunk"])


def ring(monkeypatch, spans, dropped=0):
    monkeypatch.setattr(trace, "snapshot", lambda: {
        "enabled": False, "capacity": 64, "dropped": dropped,
        "spans": spans})


def sp(name, ts, dur, sid, parent=None, tid=1, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid,
            "trace_id": "t", "span_id": sid, "parent_id": parent,
            "attrs": attrs}


def two_iterations():
    """Two whole loop iterations of 100 ms and 50 ms, the pieces of a
    third whose ``gen/loop`` was not recorded, and a client thread."""
    return [
        sp("gen/admit", 0.001, 0.004, "a1", "L1", gen="g1", waited_ms=30.0),
        sp("gen/dev_ops", 0.006, 0.002, "o1", "L1"),
        sp("gen/prefill_chunk", 0.010, 0.020, "p1", "L1"),
        sp("gen/step_dispatch", 0.031, 0.003, "d1", "s1"),
        sp("gen/step_wait", 0.034, 0.057, "w1", "s1"),
        sp("gen/decode_step", 0.031, 0.060, "s1", "L1"),
        sp("gen/emit", 0.092, 0.002, "e1", "L1"),
        sp("gen/loop", 0.000, 0.100, "L1"),
        sp("gen/idle_wait", 0.100, 0.010, "i2", "L2"),
        sp("gen/admit", 0.111, 0.001, "a2", "L2"),      # blocked: no attr
        sp("gen/step_dispatch", 0.112, 0.001, "d2", "s2"),
        sp("gen/decode_step", 0.112, 0.030, "s2", "L2"),
        sp("gen/emit", 0.143, 0.004, "e2", "L2"),
        sp("gen/loop", 0.100, 0.050, "L2"),
        # the capture ended inside this iteration: no gen/loop record
        sp("gen/admit", 0.151, 0.500, "a3", "L3", waited_ms=900.0),
        sp("gen/decode_step", 0.700, 0.900, "s3", "L3"),
        sp("gen/step_dispatch", 0.700, 0.800, "d3", "s3"),
        sp("gen/step_wait", 1.500, 0.100, "w3", "s3"),
        sp("gen/emit", 1.700, 0.700, "e3", "L3"),
        sp("wire/bench.generate_poll", 0.0, 0.5, "c1", tid=2),
    ]


def test_loop_host_share_is_the_loop_less_idle_less_blocked_on_device(
        monkeypatch):
    ring(monkeypatch, two_iterations())
    # loops 150 ms, idle 10 ms; blocked on the device: the prefill call
    # 20 ms and the first step's readback 57 ms (the second step was not
    # read back, and dispatch is the host's own time)
    assert span_share.read({}, **LOOP) == pytest.approx(
        100.0 * (140 - 77) / 140)


def test_loop_host_share_counts_a_readback_wherever_it_hangs(monkeypatch):
    """``gen_async_depth``: an iteration drains the step dispatched
    before it, so its ``gen/step_wait`` is the loop's own child; when
    speculating the readback lies two spans down. A span inside one
    already counted is not counted again."""
    ring(monkeypatch, [
        sp("gen/step_wait", 0.000, 0.040, "w0", "L1"),      # the drain
        sp("gen/emit", 0.040, 0.001, "e0", "L1"),
        sp("gen/step_dispatch", 0.042, 0.005, "d1", "s1"),
        sp("gen/decode_step", 0.042, 0.006, "s1", "L1"),
        sp("gen/loop", 0.000, 0.050, "L1"),
        sp("gen/step_dispatch", 0.051, 0.004, "d2", "v2"),
        sp("gen/step_wait", 0.055, 0.030, "w2", "v2"),
        sp("gen/spec_verify", 0.051, 0.035, "v2", "s2"),
        sp("gen/decode_step", 0.050, 0.037, "s2", "L2"),
        sp("gen/step_wait", 0.090, 0.002, "wp", "p2"),      # inside a
        sp("gen/prefill", 0.088, 0.010, "p2", "L2"),        # counted call
        sp("gen/loop", 0.050, 0.050, "L2"),
    ])
    # 100 ms of loop; blocked 40 + 30 + 10 ms
    assert span_share.read({}, **LOOP) == pytest.approx(20.0)


def test_span_ms_sum_per_occurrence_median_and_mean_attribute(monkeypatch):
    ring(monkeypatch, two_iterations())
    # (4 + 2 + 1) ms of admission over the two recorded decode steps
    assert span_ms.read({}, ["gen/admit", "gen/dev_ops"],
                        per="gen/decode_step", within="gen/loop") == \
        pytest.approx(3.5)
    # the median of 3 ms and 1 ms; the 800 ms one lies in no whole loop
    assert span_ms.read({}, ["gen/step_dispatch"], within="gen/loop") == \
        pytest.approx(2.0)
    assert span_ms.read({}, ["gen/emit"], within="gen/loop") == \
        pytest.approx(3.0)
    # one admission inside a whole loop carries the attribute
    assert span_ms.read({}, ["gen/admit"], attr="waited_ms",
                        within="gen/loop") == pytest.approx(30.0)
    # without the filter the orphans count
    assert span_ms.read({}, ["gen/admit"], attr="waited_ms") == \
        pytest.approx(465.0)


def test_span_ms_groups_one_of_each_name_per_step(monkeypatch):
    steps = []
    for i, (feed, step) in enumerate([(1, 3), (2, 4), (3, 11)]):
        t = 0.1 * i
        steps += [sp("train/shard_batch", t, feed * 1e-3, f"b{i}"),
                  sp("train/step", t + 0.01, step * 1e-3, f"s{i}",
                     compiled=0)]
    # the capture began between a batch and its step: a step alone
    ring(monkeypatch, [sp("train/step", -0.05, 0.5, "s-")] + steps)
    assert span_ms.read({}, ["train/shard_batch", "train/step"]) == \
        pytest.approx(6.0)                  # median of 4, 6, 14 ms


@pytest.mark.parametrize("spans,dropped", [([], 0), (two_iterations(), 3)],
                         ids=["empty", "overflowed"])
def test_readers_read_nothing_from_an_empty_or_overflowed_ring(
        monkeypatch, capsys, spans, dropped):
    ring(monkeypatch, spans, dropped)
    assert span_share.read({}, metric="loop_host_share", **LOOP) is None
    assert span_ms.read({}, ["gen/emit"], metric="loop_emit_ms") is None
    err = capsys.readouterr().err
    assert "loop_host_share" in err and "loop_emit_ms" in err
    assert ("evicted 3" if dropped else "no span") in err


def test_readers_find_nothing_where_the_names_are_absent(monkeypatch):
    """A program without these spans (the parent commit): no value, no
    error."""
    ring(monkeypatch, [sp("wire/x", 0.0, 0.1, "c1")])
    assert span_share.read({}, **LOOP) is None
    assert span_ms.read({}, ["gen/emit"], within="gen/loop") is None
    assert span_ms.read({}, ["gen/admit"], per="gen/decode_step") is None
    assert span_ms.read({}, ["gen/admit"], attr="waited_ms") is None


SERVE_SPAN_METRICS = ("loop_host_share", "loop_admit_ms_per_step",
                      "loop_dispatch_ms_per_step", "loop_emit_ms_per_step",
                      "admit_wait_ms")


def test_traced_tiny_cells_report_the_span_metrics(tmp_path):
    """Through ``run.drive``: the capture turns recording on, the
    readers find the engine's and the train step's spans in the ring
    after the run has freed its state, with nothing dropped."""
    trace.clear()
    cell = test_cells.cell_of(tmp_path / "serve", test_cells.SERVE_CFG,
                              test_cells.SERVE_MIX, 1, SERVE_SPAN_METRICS)
    r = test_cells.drive(cell, trace=True, seconds=2.0)
    assert set(SERVE_SPAN_METRICS) <= set(r["metrics"]), r["metrics"]
    assert 0 < r["metrics"]["loop_host_share"]["value"] < 100
    assert r["metrics"]["admit_wait_ms"]["value"] >= 0
    assert trace.snapshot()["dropped"] == 0
    trace.clear()
    cell = test_cells.cell_of(tmp_path / "train", util.TINY_DENSE,
                              util.TINY_TRAIN_TRAFFIC, 1,
                              ("train_host_ms_per_step",))
    r = test_cells.drive(cell, trace=True)
    assert r["metrics"]["train_host_ms_per_step"]["value"] > 0
    trace.clear()
