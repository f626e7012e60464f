"""The trace reduction: on synthetic lines (exact arithmetic) and on a
small trace recorded on the v5e and kept beside this file."""

import pathlib
import types

import pytest

from benchmarks.lib import xplane

DATA = pathlib.Path(__file__).parent / "data"


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def plane(ops, mods, name="/device:TPU:0"):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name="XLA Ops", events=ops),
        types.SimpleNamespace(name="XLA Modules", events=mods)])


def test_busy_self_time_gaps_and_kernel_labels():
    ops = [ev("while.1", 0, 100),                       # holds the next two
           ev("fusion.2", 10, 30), ev("custom-call.3", 50, 40,
                                      tf_op="ptpu_flash_fwd"),
           ev("all-gather-done.4", 150, 20),
           ev("fusion.2", 300, 50)]
    mods = [ev("jit_step_fn(77)", 0, 170), ev("jit_other(78)", 300, 50)]
    r = xplane.reduce(types.SimpleNamespace(planes=[
        plane(ops, mods), types.SimpleNamespace(name="/host:CPU", lines=[])]))
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx((100 + 20 + 50) * 1e-9)
    assert r["by_op"]["jit_step_fn/while.1"] == pytest.approx(30e-9)
    assert r["by_op"]["jit_step_fn/fusion.2"] == pytest.approx(30e-9)
    assert r["by_op"]["jit_other/fusion.2"] == pytest.approx(50e-9)
    assert xplane.kernel_seconds(r, ["ptpu_flash_fwd"]) == pytest.approx(
        40e-9)
    assert r["collective_exposed_s"] == pytest.approx(20e-9)
    assert r["programs"] == {"jit_step_fn": [pytest.approx(170e-9)],
                             "jit_other": [pytest.approx(50e-9)]}
    assert r["gaps"][0] == ("after_jit_step_fn_/_before_jit_other",
                            pytest.approx(130e-9))
    assert r["gaps"][1][1] == pytest.approx(50e-9)


def test_hlo_instruction_text_is_shortened_to_name_and_shape():
    text = ("%jvp_ptpu_linear_xent_dw_.1 = bf16[2048,92544]{1,0:T(8,128)} "
            "custom-call(bf16[8192,2048]{1,0} %copy-done.50), "
            "custom_call_target=\"tpu_custom_call\"")
    assert xplane.op_name(text) == "jvp_ptpu_linear_xent_dw_.1:bf16[2048,92544]"
    assert xplane.op_name("%t.1 = (f32[8,128]{1,0}, f32[8]{0}) fusion(x)") \
        == "t.1:f32[8,128]"
    assert xplane.op_name("fusion.2") == "fusion.2"
    r = xplane.reduce(types.SimpleNamespace(planes=[plane(
        [ev(text, 0, 10)], [ev("jit_step_fn(1)", 0, 10)])]))
    assert xplane.kernel_seconds(r, ["ptpu_linear_xent_dw"]) == \
        pytest.approx(10e-9)


def test_busy_is_averaged_over_chips():
    a = plane([ev("f", 0, 100)], [ev("jit_p(1)", 0, 100)])
    b = plane([ev("f", 0, 50)], [ev("jit_p(1)", 0, 50)], "/device:TPU:1")
    r = xplane.reduce(types.SimpleNamespace(planes=[b, a]))
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(75e-9)


def test_a_trace_without_a_chip_reads_nothing():
    r = xplane.reduce(types.SimpleNamespace(planes=[]))
    assert r["devices"] == 0 and xplane.top_ops(r) == []


def test_recorded_v5e_trace():
    path = DATA / "v5e_small.xplane.pb"
    if not path.exists():
        pytest.skip("no recorded trace beside the test")
    r = xplane.reduce(xplane.load(path))
    assert r["devices"] >= 1 and r["busy_s"] > 0 and r["n_ops"] > 0
    assert any(name.startswith("jit_") for name in r["programs"])
    total = sum(r["by_op"].values())
    assert total == pytest.approx(r["busy_s"], rel=0.05)
