"""The exposed-time account (``readers/span_exposed.py``) and the off-CPU
share (``readers/span_offcpu.py``) on hand-made rings (exact
arithmetic), and through ``run.drive`` on a tiny serving cell."""

import pytest

from benchmarks.readers import span_exposed, span_offcpu
from paddle_tpu.core import trace

from . import test_cells

MS = 1e-3
EXPOSED = ("loop_exposed_share", "loop_exposed_launch_share",
           "loop_exposed_ms_per_admission", "loop_exposed_ms_per_step",
           "loop_late_landing_share", "loop_dispatch_offcpu_share",
           "loop_launch_ms")


def ring(monkeypatch, spans, dropped=0):
    monkeypatch.setattr(trace, "snapshot", lambda: {
        "enabled": False, "capacity": 64, "dropped": dropped,
        "spans": spans})


def sp(name, at_ms, dur_ms, sid, parent=None, cpu_ms=None, tid=1, **attrs):
    """A record at ``at_ms`` on the monotonic clock; its realtime stamp
    is deliberately elsewhere (nothing may order by it)."""
    return {"name": name, "ts": 1e9 - at_ms * MS, "mono": at_ms * MS,
            "dur": dur_ms * MS, "cpu": (dur_ms if cpu_ms is None
                                        else cpu_ms) * MS,
            "tid": tid, "trace_id": "t", "span_id": sid, "parent_id": parent,
            "attrs": attrs}


def step(i, at, seq, landed=None, wait=8.0, admit=False):
    """One synchronous iteration of 20 ms from ``at``: [an admission
    with a whole prefill,] staging 1 ms, launch 2 ms, wait, emit 1 ms."""
    L, out, t = f"L{i}", [], at
    if admit:
        out += [sp("gen/admit", t + 0.5, 1, f"a{i}", L, gen="g",
                   waited_ms=3.0),
                sp("gen/dev_ops", t + 1.5, 2, f"o{i}", L)]
        t += 4
    out += [sp("gen/decode_step", t + 1, 3 + wait, f"s{i}", L),
            sp("gen/step_dispatch", t + 1, 3, f"d{i}", f"s{i}"),
            sp("gen/launch", t + 2, 2, f"l{i}", f"d{i}", seq=seq,
               entry="step")]
    if landed is not None:
        out += [sp("gen/step_wait", t + 4, wait, f"w{i}", f"s{i}",
                   landed=landed),
                sp("gen/emit", t + 4 + wait + 0.5, 1, f"e{i}", L)]
    out.append(sp("gen/loop", at, 20, L))
    return out


def test_a_synchronous_loop_is_exposed_from_each_landing_to_the_next_launch(
        monkeypatch, capsys):
    """Three iterations of 20 ms; each step lands 12 ms in (16 in the
    admitting one) and the next is launched 2 ms into the following
    iteration (6 ms where it admits): 10 ms, then 10 ms of exposed
    time, the last landing has no launch after it."""
    spans = step(1, 0, 7, 7) + step(2, 20, 8, 8, admit=True) \
        + step(3, 40, 9, 9)
    ring(monkeypatch, spans)
    read = lambda v: span_exposed.read({}, v, metric="m")   # noqa: E731
    # landing 1 at 12 -> launch 2 at 26 = 14 ms; landing 2 at 36 ->
    # launch 3 at 42 = 6 ms; of 60 ms awake
    assert read("share") == pytest.approx(100 * 20 / 60)
    # launches 8 and 9 began on an empty queue (7's past is unknown)
    assert read("launch_share") == pytest.approx(100 * 4 / 60)
    # iteration 2 holds the admission: 20..26 of the first interval and
    # 36..40 of the second = 10 ms for one admission; the rest, 10 ms,
    # over three decode steps
    assert read("ms_per_admission") == pytest.approx(10.0)
    assert read("ms_per_step") == pytest.approx(10.0 / 3)
    assert read("late_landing_share") == 0.0
    acc = span_exposed.account(spans)
    by = {k: round(v / MS, 6) for k, v in acc["by_span_s"].items()}
    # emit 1 + 1; admit 1; dev_ops 2; staging 1 + 1; decode_step self 0;
    # the loop's own time is the rest
    assert by == {"gen/emit": 2.0, "gen/admit": 1.0, "gen/dev_ops": 2.0,
                  "gen/step_dispatch": 2.0, "gen/loop": 13.0}
    assert acc["intervals"] == 2 and acc["landings"] == 3
    # of the admitting iteration: 20..26 and 36..40
    assert {k: round(v / MS, 6) for k, v in
            acc["admission_by_span_s"].items()} == {
        "gen/admit": 1.0, "gen/dev_ops": 2.0, "gen/step_dispatch": 1.0,
        "gen/emit": 1.0, "gen/loop": 5.0}
    err = capsys.readouterr().err
    assert "between 33.33 % and 40 %" in err and "gen/loop 13" in err


def depth_one(i, at, seq, admit_final=False):
    """One iteration at depth 1: launch ``seq`` (1..3 ms), then drain
    ``seq - 1`` (4..18 ms), emit. ``admit_final``: before them an
    admission whose only chunk is final, read back at 8 ms."""
    L, out, t = f"L{i}", [], at
    if admit_final:
        out += [sp("gen/admit", t + 0.5, 1, f"a{i}", L, waited_ms=1.0),
                sp("gen/prefill_chunk", t + 2, 6, f"p{i}", L, final=True),
                sp("gen/launch", t + 3, 1, f"pl{i}", f"p{i}", seq=seq,
                   entry="paged_prefill"),
                sp("gen/prefill_wait", t + 4, 4, f"pw{i}", f"p{i}",
                   landed=seq),
                sp("gen/emit", t + 8.5, 1, f"pe{i}", L, emitted=1)]
        t, seq = t + 10, seq + 1
    out += [sp("gen/decode_step", t + 0.5, 3, f"s{i}", L),
            sp("gen/step_dispatch", t + 0.5, 3, f"d{i}", f"s{i}"),
            sp("gen/launch", t + 1, 2, f"l{i}", f"d{i}", seq=seq,
               entry="paged_step"),
            sp("gen/step_wait", t + 4, 14 if not admit_final else 0.05,
               f"w{i}", L, landed=seq - (2 if admit_final else 1)),
            sp("gen/emit", t + 18.5, 1, f"e{i}", L)]
    out.append(sp("gen/loop", at, t - at + 20, L))
    return out


def test_depth_one_exposes_nothing_between_steps_and_all_of_an_admission(
        monkeypatch):
    """A step is drained only after the next is launched: the queue is
    never empty between steps. The readback of an admission's final
    chunk lands everything: from there to the step's launch is exposed,
    and the drain of the older step that follows returns at once onto a
    queue that holds the new one."""
    spans = depth_one(1, 0, 5) + depth_one(2, 20, 6) \
        + depth_one(3, 40, 7, admit_final=True) + depth_one(4, 70, 9)
    ring(monkeypatch, spans)
    acc = span_exposed.account(spans)
    # prefill_wait lands 7 at 48; launch 8 at 51: emit 1 ms, loop 1.5,
    # staging 0.5
    assert acc["intervals"] == 1
    assert acc["exposed_s"] == pytest.approx(3 * MS)
    assert {k: round(v / MS, 6) for k, v in acc["by_span_s"].items()} == {
        "gen/emit": 1.0, "gen/loop": 1.5, "gen/step_dispatch": 0.5}
    assert acc["admissions"] == 1
    assert span_exposed.read({}, "ms_per_admission") == pytest.approx(3.0)
    assert span_exposed.read({}, "ms_per_step") == 0.0
    assert span_exposed.read({}, "share") == pytest.approx(100 * 3 / 90)
    # five landings; the drain of 6 after 7 had landed returned at once:
    # late, though with 8 in flight it did not find the queue empty
    assert acc["landings"] == 5 and acc["at_once_landings"] == 1
    assert acc["late_landings"] == 0
    assert span_exposed.read({}, "late_landing_share") == pytest.approx(20.0)


def test_a_chunk_that_is_not_the_last_launches_and_lands_nothing(
        monkeypatch):
    """Its launch stays outstanding until a later landing names it or a
    later number: the queue is not empty behind it."""
    spans = step(1, 0, 3, 3) + [
        sp("gen/prefill_chunk", 21, 4, "p", "L2", final=False),
        sp("gen/launch", 22, 2, "pl", "p", seq=4, entry="paged_prefill"),
        # a landing of the OLDER step after the chunk's launch
        sp("gen/step_wait", 26, 0.01, "w", "L2", landed=3),
        sp("gen/decode_step", 30, 9, "s", "L2"),
        sp("gen/step_dispatch", 30, 2, "d", "s"),
        sp("gen/launch", 31, 1, "l", "d", seq=5, entry="paged_step"),
        sp("gen/step_wait", 32, 7, "w2", "s", landed=5),
        sp("gen/loop", 20, 20, "L2")] + step(3, 40, 6, 6)
    ring(monkeypatch, spans)
    acc = span_exposed.account(spans)
    # landing 3 at 12 -> the chunk's launch at 22; the landing of 3 at
    # 26 leaves 4 in flight; landing 5 at 39 -> launch 6 at 42
    assert acc["intervals"] == 2
    assert acc["exposed_s"] == pytest.approx(13 * MS)
    assert acc["launches_on_empty"] == 2
    assert acc["launch_on_empty_s"] == pytest.approx(4 * MS)


def test_a_landing_that_returns_at_once_is_late(monkeypatch, capsys):
    """The chip had finished before the host asked: the exposed time
    starts at the landing all the same (the host cannot know better),
    and the landing is counted, under the limit the metric's file
    gives."""
    spans = step(1, 0, 1, 1) + step(2, 20, 2, 2, wait=0.05) \
        + step(3, 40, 3, 3)
    ring(monkeypatch, spans)
    assert span_exposed.read({}, "late_landing_share") == \
        pytest.approx(100 / 3)
    assert span_exposed.read({}, "late_landing_share", late_ms=0.01) == 0.0
    assert span_exposed.read({}, "late_landing_share", late_ms=9.0) == 100.0
    acc = span_exposed.account(spans)
    assert acc["late_landings"] == 1 and acc["landings"] == 3
    span_exposed.read({}, "share", metric="m")
    assert "3 landings, 1 returned at once, 1 of them onto an empty " \
        "queue" in capsys.readouterr().err


def test_a_capture_that_starts_inside_an_iteration(monkeypatch):
    """The first iteration's ``gen/loop`` was not recorded and its
    launch was made before the capture began: the orphans count for
    nothing but what they say about the queue. An unseen launch is
    known by its number: the landing of 4 with 6 next leaves 5 in
    flight."""
    orphans = [sp("gen/step_wait", -6, 5, "w0", "s0", landed=4),
               sp("gen/emit", -0.8, 0.5, "e0", "L0")]
    late = [s for s in step(1, 0, 6, 6) + step(2, 20, 7, 7)]
    ring(monkeypatch, orphans + late)
    acc = span_exposed.account(orphans + late)
    # only landing 6 at 12 -> launch 7 at 22 counts
    assert acc["loops"] == 2 and acc["intervals"] == 1
    assert acc["exposed_s"] == pytest.approx(10 * MS)
    # had the next launch been 5, the orphan's landing opened an
    # interval, clipped to the first whole iteration
    for s in late:
        if "seq" in s["attrs"]:
            s["attrs"]["seq"] -= 1
        if "landed" in s["attrs"]:
            s["attrs"]["landed"] -= 1
    acc = span_exposed.account(orphans + late)
    assert acc["intervals"] == 2
    assert acc["exposed_s"] == pytest.approx((2 + 10) * MS)


def test_idle_waits_come_off_both_sides(monkeypatch):
    spans = step(1, 0, 1, 1) + [
        sp("gen/idle_wait", 21, 30, "i", "L2"),
        sp("gen/loop", 20, 32, "L2")] + step(3, 52, 2, 2)
    ring(monkeypatch, spans)
    acc = span_exposed.account(spans)
    # landing at 12 -> launch at 54: 42 ms, 30 of them waiting for work
    assert acc["exposed_s"] == pytest.approx(12 * MS)
    assert acc["idle_inside_s"] == pytest.approx(30 * MS)
    assert acc["awake_s"] == pytest.approx(42 * MS)
    assert "gen/idle_wait" not in acc["by_span_s"]


@pytest.mark.parametrize("spans,dropped,says", [
    ([], 0, "no span"),
    (step(1, 0, 1, 1), 3, "evicted 3"),
    ([{k: v for k, v in s.items() if k not in ("mono", "cpu")}
      for s in step(1, 0, 1, 1)], 0, "monotonic clock"),
    ([s for s in step(1, 0, 1, 1) if s["name"] != "gen/launch"], 0,
     "launch marks")],
    ids=["empty", "overflowed", "older-records", "no-launch-marks"])
def test_nothing_is_read_where_the_ring_cannot_carry_it(
        monkeypatch, capsys, spans, dropped, says):
    """An empty or evicting ring, and the parent commit's program (its
    records have no ``mono`` / ``cpu``, its loop no launch marks): no
    value, no error, the reason on stderr."""
    ring(monkeypatch, spans, dropped)
    for v in span_exposed.VALUES:
        assert span_exposed.read({}, v, metric="loop_exposed") is None
    assert span_offcpu.read({}, "gen/step_dispatch", within="gen/loop",
                            child="gen/launch", metric="loop_offcpu") \
        is None or says == "launch marks"
    err = capsys.readouterr().err
    assert says in err and "loop_exposed" in err
    with pytest.raises(ValueError):
        span_exposed.read({}, "nonsense")


def test_off_cpu_share_of_a_dispatch_and_of_its_launch(monkeypatch, capsys):
    """Two dispatches of 10 ms: the launches 8 ms of which 2 on the CPU,
    staging 2 ms all on the CPU. One outside any whole loop is left
    out."""
    spans = []
    for i, at in enumerate((0, 20)):
        spans += [sp("gen/step_dispatch", at + 1, 10, f"d{i}", f"L{i}",
                     cpu_ms=4),
                  sp("gen/launch", at + 3, 8, f"l{i}", f"d{i}", cpu_ms=2,
                     seq=i),
                  sp("gen/loop", at, 20, f"L{i}")]
    spans += [sp("gen/step_dispatch", 41, 100, "dx", "Lx", cpu_ms=100)]
    ring(monkeypatch, spans)
    assert span_offcpu.read({}, "gen/step_dispatch", within="gen/loop",
                            child="gen/launch", metric="m") == \
        pytest.approx(60.0)
    err = capsys.readouterr().err
    assert "2 x gen/launch off the CPU 75 % of a median 8 ms" in err
    assert "smallest step under gen/loop is 2 ms, 4 of them" in err
    assert "gen/step_dispatch less gen/launch off the CPU 0 % " \
        "of a median 2 ms" in err
    assert span_offcpu.read({}, "gen/step_dispatch") == pytest.approx(
        100 * 12 / 120)


def test_a_traced_tiny_cell_reports_the_account(tmp_path):
    """Through ``run.drive``: the engine's launch marks and the records'
    two clocks reach the readers after the run has freed its state."""
    trace.clear()
    cell = test_cells.cell_of(tmp_path, test_cells.SERVE_CFG,
                              test_cells.SERVE_MIX, 1, EXPOSED)
    r = test_cells.drive(cell, trace=True, seconds=2.0)
    assert set(EXPOSED) <= set(r["metrics"]), r["metrics"]
    got = {k: r["metrics"][k]["value"] for k in EXPOSED}
    assert 0 < got["loop_exposed_share"] < 100
    assert 0 < got["loop_exposed_launch_share"] < 100
    assert got["loop_exposed_ms_per_admission"] >= 0
    assert 0 <= got["loop_late_landing_share"] <= 100
    assert 0 <= got["loop_dispatch_offcpu_share"] <= 100
    assert got["loop_launch_ms"] > 0
    assert trace.snapshot()["dropped"] == 0
    trace.clear()
