"""Observability subsystem: span recorder (nesting, ring cap, disabled
no-op), cross-wire trace-id propagation, latency histograms + Prometheus
export, Chrome-trace JSON validity, structured JSON logging, health
stats-prefix filtering, spans inside a profiler capture and the engine
loop's launch / landing marks. All CPU-only and tier-1 fast."""

import json
import logging
import threading
import time

import pytest

from paddle_tpu.core import monitor, trace
from paddle_tpu.core.flags import get_flags, set_flags
from paddle_tpu.core.wire import FrameClient, FrameService, send_frame

pytestmark = pytest.mark.obs

_FLAGS = ["trace", "trace_buffer", "log_json"]


@pytest.fixture(autouse=True)
def _restore_obs_flags():
    """Tracing/logging must be back at production defaults (off) after
    each test — a leaked tracer would record every other suite."""
    saved = get_flags(_FLAGS)
    trace.clear()      # a capture in an earlier test leaves its spans
    yield
    set_flags(saved)
    trace.clear()


def _tracing_on(capacity=4096):
    set_flags({"trace_buffer": capacity, "trace": True})


class _Echo(FrameService):
    op_names = {1: "echo"}

    def _dispatch(self, sock, op, header, payload):
        send_frame(sock, 0, {"echo": header.get("x")})
        return True


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------

def test_disabled_mode_is_noop():
    """Production default: no flag and no profiler capture, so nothing
    records; span() returns one shared no-op object (no per-call
    allocation), nothing is recorded."""
    assert not trace.recording() and not trace.flag_on()
    s = trace.span("x", k=1)
    assert s is trace.span("y"), "disabled span must be a shared singleton"
    assert s is trace.server_span("z", "t", None)
    with s:
        assert trace.current() is None
    assert trace.get_spans() == []
    snap = trace.snapshot()
    assert snap["enabled"] is False and snap["spans"] == []
    assert snap["dropped"] == 0


def test_span_nesting_and_linkage():
    _tracing_on()
    with trace.span("outer", phase="a") as outer:
        assert trace.current() == (outer.trace_id, outer.span_id)
        with trace.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    assert trace.current() is None, "stack must unwind"
    names = [s["name"] for s in trace.get_spans()]
    assert names == ["inner", "outer"], "children record before parents"
    outer_rec = trace.get_spans()[1]
    assert outer_rec["attrs"] == {"phase": "a"}
    assert outer_rec["parent_id"] is None
    assert outer_rec["dur"] >= 0


def test_sibling_traces_get_distinct_ids():
    _tracing_on()
    with trace.span("a"):
        pass
    with trace.span("b"):
        pass
    a, b = trace.get_spans()
    assert a["trace_id"] != b["trace_id"]


def test_ring_buffer_caps_memory():
    _tracing_on(capacity=8)
    for n in range(30):
        with trace.span(f"s{n}"):
            pass
    spans = trace.get_spans()
    assert len(spans) == 8, "ring must evict oldest"
    assert [s["name"] for s in spans] == [f"s{n}" for n in range(22, 30)]


def test_live_resize_keeps_newest_spans():
    """Regression: resizing the buffer on a LIVE tracer used to swap in
    an empty ring, silently dropping every buffered span. A shrink must
    keep the newest spans that still fit; a grow must keep everything."""
    _tracing_on(capacity=16)
    for n in range(10):
        with trace.span(f"s{n}"):
            pass
    set_flags({"trace_buffer": 4})           # live shrink
    spans = trace.get_spans()
    assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"], \
        "shrink keeps the newest tail, not an empty ring"
    set_flags({"trace_buffer": 64})          # live grow
    assert [s["name"] for s in trace.get_spans()] == \
        ["s6", "s7", "s8", "s9"], "grow keeps every surviving span"
    with trace.span("after"):
        pass
    assert trace.get_spans()[-1]["name"] == "after"
    assert trace.snapshot()["capacity"] == 64


def test_span_records_exception_type():
    _tracing_on()
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    assert trace.get_spans()[-1]["attrs"]["error"] == "ValueError"


def test_record_event_emits_span():
    from paddle_tpu.core import profiler

    _tracing_on()
    with profiler.RecordEvent("annotated"):
        pass
    assert any(s["name"] == "annotated" for s in trace.get_spans())


# ---------------------------------------------------------------------------
# cross-wire propagation (acceptance)
# ---------------------------------------------------------------------------

def test_wire_round_trip_joins_one_trace():
    """Acceptance: a traced round-trip produces a client span and a
    server span sharing one trace id, the server's parent being the
    client span; both latency histograms fill; trace_dump scrapes it."""
    _tracing_on()
    monitor.reset_stats("wire/")
    srv = _Echo().start()
    c = FrameClient(srv.endpoint, {"echo": 1}, service="test", timeout=5.0)
    assert c._request("echo", {"x": 7})[0]["echo"] == 7

    # the server records its span after it has sent the reply, so the
    # client can be here first: wait for it (bounded)
    deadline = time.monotonic() + 5.0
    while True:
        spans = trace.get_spans()
        server = [s for s in spans if s["name"] == "wire/_Echo.echo"]
        if server or time.monotonic() > deadline:
            break
        time.sleep(0.005)
    client = [s for s in spans if s["name"] == "wire/test.echo"]
    assert len(client) == 1 and len(server) == 1
    assert client[0]["trace_id"] == server[0]["trace_id"]
    assert server[0]["parent_id"] == client[0]["span_id"]
    assert client[0]["tid"] != server[0]["tid"]

    hists = monitor.export_histograms("wire/")
    assert hists["wire/op_latency_s/test.echo"]["count"] == 1
    assert hists["wire/server_latency_s/_Echo.echo"]["count"] == 1

    # remote scrape returns the same spans (server shares the process
    # tracer here; the op itself is what obs_dump uses cross-process)
    dump = c.trace_dump()
    assert dump["enabled"] and dump["service"] == "_Echo"
    assert {s["span_id"] for s in dump["spans"]} >= {
        client[0]["span_id"], server[0]["span_id"]}
    c.close()
    srv.stop()


def test_untraced_client_headers_are_clean():
    """With FLAGS_trace off no trace keys ride the wire."""
    captured = {}

    class _Capture(FrameService):
        def _dispatch(self, sock, op, header, payload):
            captured.update(header)
            send_frame(sock, 0, {})
            return True

    srv = _Capture().start()
    c = FrameClient(srv.endpoint, {"go": 1}, timeout=5.0)
    c._request("go", {"x": 1})
    assert "tr" not in captured and "sp" not in captured
    c.close()
    srv.stop()


def test_trace_dump_clear_drains_server_buffer():
    _tracing_on()
    srv = _Echo().start()
    c = FrameClient(srv.endpoint, {"echo": 1}, service="t", timeout=5.0)
    c._request("echo", {})
    assert c.trace_dump(clear=True)["spans"]
    # buffer now holds only spans recorded after the drain (the dump
    # request itself lands post-snapshot)
    remaining = {s["name"] for s in c.trace_dump()["spans"]}
    assert "wire/t.echo" not in remaining
    c.close()
    srv.stop()


# ---------------------------------------------------------------------------
# histograms + exporters (acceptance)
# ---------------------------------------------------------------------------

def test_histogram_quantiles():
    monitor.reset_stats("t/")
    for v in [0.001] * 50 + [0.010] * 45 + [0.100] * 5:
        monitor.observe("t/lat_s", v)
    h = monitor.get_histogram("t/lat_s")
    assert h["count"] == 100
    assert h["sum"] == pytest.approx(1.0)
    assert h["min"] == pytest.approx(0.001)
    assert h["max"] == pytest.approx(0.100)
    assert h["p50"] <= h["p95"] <= h["p99"] <= h["max"]
    assert 0.0005 <= h["p50"] <= 0.002
    assert 0.005 <= h["p95"] <= 0.02
    assert monitor.get_histogram("t/never") is None
    monitor.reset_stats("t/")
    assert monitor.get_histogram("t/lat_s") is None, "reset clears hists"


def test_export_prometheus_emits_wire_quantiles():
    """Acceptance: export_prometheus() carries histogram quantiles for
    wire/* op latency after a traced round-trip."""
    _tracing_on()
    monitor.reset_stats("wire/")
    srv = _Echo().start()
    with FrameClient(srv.endpoint, {"echo": 1}, service="svc",
                     timeout=5.0) as c:
        c._request("echo", {})
    srv.stop()
    text = monitor.export_prometheus("wire/")
    assert 'wire_op_latency_s_svc_echo{quantile="0.5"}' in text
    assert 'wire_op_latency_s_svc_echo{quantile="0.99"}' in text
    assert "wire_op_latency_s_svc_echo_count 1" in text
    assert "# TYPE wire_op_latency_s_svc_echo summary" in text


def test_export_chrome_is_valid_json(tmp_path):
    """Acceptance: export_chrome output is valid JSON with well-formed
    Chrome trace events."""
    _tracing_on()
    with trace.span("parent", step=1):
        with trace.span("child"):
            pass
    path = str(tmp_path / "trace.json")
    trace.export_chrome(path)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert len(events) == 2
    for e in events:
        assert e["ph"] == "X"
        assert set(e) >= {"name", "ts", "dur", "pid", "tid", "args"}
        assert e["args"]["trace_id"]
    child = next(e for e in events if e["name"] == "child")
    parent = next(e for e in events if e["name"] == "parent")
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    assert parent["args"]["step"] == 1


def _load_obs_dump():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "obs_dump", os.path.join(os.path.dirname(__file__), "..", "tools",
                                 "obs_dump.py"))
    obs_dump = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_dump)
    return obs_dump


def test_obs_dump_merges_endpoints(tmp_path):
    """tools/obs_dump.py probes two live services and writes one merged
    Chrome trace with per-endpoint pids."""
    obs_dump = _load_obs_dump()
    _tracing_on()
    a, b = _Echo().start(), _Echo().start()
    with FrameClient(a.endpoint, {"echo": 1}, timeout=5.0) as c:
        c._request("echo", {})
    out = str(tmp_path / "fleet.json")
    rc = obs_dump.main([a.endpoint, b.endpoint, "-o", out,
                        "--stats-prefix", "wire/"])
    assert rc == 0
    with open(out) as f:
        doc = json.load(f)
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids >= {1, 2}, "each endpoint gets its own pid"
    names = {e["name"] for e in doc["traceEvents"]}
    assert "process_name" in names
    a.stop()
    b.stop()


# ---------------------------------------------------------------------------
# satellites: health stats prefix, JSON logs
# ---------------------------------------------------------------------------

def test_health_stats_prefix_filters_payload():
    monitor.reset_stats()
    monitor.stat_add("wire/x", 1)
    monitor.stat_add("ckpt/y", 2)
    srv = _Echo().start()
    with FrameClient(srv.endpoint, {}, timeout=5.0) as probe:
        full = probe.health()
        wire_only = probe.health(stats_prefix="wire/")
        none = probe.health(stats_prefix="no-such-prefix/")
    assert "ckpt/y" in full["stats"]
    assert "wire/x" in wire_only["stats"]
    assert not any(not k.startswith("wire/") for k in wire_only["stats"])
    assert none["stats"] == {}
    # the filtered probe still carries the load fields
    assert wire_only["status"] == "ok" and "inflight" in wire_only
    srv.stop()


def test_log_json_mode_correlates_with_trace(capsys):
    from paddle_tpu.core import logging as plog

    _tracing_on()
    records = []

    class _Sink(logging.Handler):
        def emit(self, record):
            records.append(self.format(record))

    sink = _Sink()
    logger = plog.get_logger()
    logger.addHandler(sink)
    try:
        set_flags({"log_json": True})
        with trace.span("op") as sp:
            plog.info("inside %s", "span")
        plog.warning("outside")
    finally:
        set_flags({"log_json": False})
        logger.removeHandler(sink)
    inside = json.loads(records[0])
    outside = json.loads(records[1])
    assert inside["msg"] == "inside span"
    assert inside["level"] == "INFO"
    assert inside["trace_id"] == sp.trace_id
    assert inside["span_id"] == sp.span_id
    assert isinstance(inside["ts"], float)
    assert outside["level"] == "WARNING" and "trace_id" not in outside


# ---------------------------------------------------------------------------
# satellites: histogram exposition, stream_traces under speculation +
# ledger failover joins
# ---------------------------------------------------------------------------

def test_export_prometheus_histogram_exposition():
    """Golden format: alongside the summary family, each histogram
    exports a real le-labeled cumulative ``_bucket`` family (sibling
    ``_hist`` name — one metric name cannot carry two TYPEs) that
    Prometheus' histogram_quantile() can consume: le values strictly
    increasing, counts cumulative, ``+Inf`` == ``_count``."""
    import re

    monitor.reset_stats("t/")
    monitor.observe("t/lat_s", 0.5)
    monitor.observe("t/lat_s", 0.5)
    monitor.observe("t/lat_s", 2.0)
    text = monitor.export_prometheus("t/")
    assert "# TYPE t_lat_s summary" in text
    assert "# TYPE t_lat_s_hist histogram" in text
    rows = re.findall(r't_lat_s_hist_bucket\{le="([^"]+)"\} (\d+)',
                      text)
    assert rows and rows[-1][0] == "+Inf"
    les = [float(le) for le, _ in rows[:-1]]
    counts = [int(c) for _, c in rows]
    assert les == sorted(les) and len(set(les)) == len(les)
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert counts[-1] == 3 and "t_lat_s_hist_count 3" in text
    m = re.search(r"t_lat_s_hist_sum ([0-9.e+-]+)", text)
    assert m and float(m.group(1)) == pytest.approx(3.0)
    # the two 0.5s are cumulative at the first bound >= 0.5; 2.0 only
    # joins at the first bound >= 2.0
    at = {float(le): int(c) for le, c in rows[:-1]}
    lo = min(b for b in les if b >= 0.5)
    hi = min(b for b in les if b >= 2.0)
    assert at[lo] == 2 and at[hi] == 3
    monitor.reset_stats("t/")


@pytest.fixture(scope="module")
def _gen_model():
    import paddle_tpu
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    return LlamaForCausalLM(cfg)


def _drain_gen(eng, gid):
    toks, n = [], 0
    while True:
        doc = eng.poll(gid, start=n, wait_s=0.5)
        toks += doc["tokens"]
        n = len(toks)
        if doc["done"]:
            return toks, doc["error"]


def test_stream_traces_spec_accept_under_stream_id(_gen_model):
    """A speculating engine's per-generation ``gen/spec_accept`` spans
    (emitted when drafts are accepted)
    group under the SAME stream trace id as the lifecycle spans, so
    stream_traces() shows speculation inside the request timeline."""
    import numpy as np

    from paddle_tpu.serving import GenerationEngine

    obs_dump = _load_obs_dump()
    _tracing_on(8192)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, 96, size=rs.randint(4, 10))
               .astype(np.int32) for _ in range(6)]
    with GenerationEngine(_gen_model, slots=3, max_len=40,
                          queue_max=8, spec_k=4, spec_mode="ngram",
                          spec_shed_occupancy=1.0) as eng:
        gids = [eng.start(p, 12, trace_id=f"t-spec-{i}")
                for i, p in enumerate(prompts)]
        for g in gids:
            _, err = _drain_gen(eng, g)
            assert err is None
        assert eng.stats()["spec"]["accepted"] > 0
    scrape = {"endpoint": "a", "service": "gen",
              "spans": trace.get_spans()}
    streams = obs_dump.stream_traces([scrape])
    accepted = [tid for tid, d in streams.items()
                if "gen/spec_accept" in d["names"]]
    assert accepted and all(tid.startswith("t-spec-") for tid in accepted)
    for tid in accepted:
        assert streams[tid]["retired"] == "complete"
        assert "gen/admitted" in streams[tid]["names"]


def test_stream_traces_ledger_spans_join_failover_resume(_gen_model):
    """The ``gen/ledger`` finalize events ride the stream's trace id, so
    a failed-over stream — cancelled on replica A, replayed with
    ``rng_skip`` on replica B — shows BOTH replicas' ledger finalizes in
    ONE stream_traces() entry, scraped at different times."""
    import numpy as np

    from paddle_tpu.serving import GenerationEngine

    obs_dump = _load_obs_dump()
    _tracing_on(8192)
    rs = np.random.RandomState(5)
    prompt = rs.randint(1, 96, size=(6,)).astype(np.int32)
    tid = "t-failover"
    # replica A: the stream dies mid-flight (cancel stands in for the
    # replica loss); its spans are scraped from its buffer
    with GenerationEngine(_gen_model, slots=2, max_len=32, queue_max=4,
                          step_wait_s=0.05, ledger=True) as a:
        gid = a.start(prompt, 12, trace_id=tid, tenant="acme")
        while len(a.poll(gid, wait_s=1.0)["tokens"]) < 2:
            pass
        a.cancel(gid)
        deadline_recs = None
        import time as _time
        t_end = _time.monotonic() + 5.0
        while _time.monotonic() < t_end:
            deadline_recs = a.ledger_dump()["records"]
            if deadline_recs:
                break
            _time.sleep(0.02)
        assert deadline_recs and deadline_recs[-1]["outcome"] == "cancelled"
    scrape_a = {"endpoint": "a", "service": "gen",
                "spans": trace.get_spans()}
    trace.clear()
    # replica B: the router's replay — same trace id, rng_skip past the
    # tokens already delivered
    with GenerationEngine(_gen_model, slots=2, max_len=32,
                          queue_max=4, ledger=True) as b:
        gid2 = b.start(prompt, 12, trace_id=tid, rng_skip=2,
                       tenant="acme")
        _, err = _drain_gen(b, gid2)
        assert err is None
        rec = b.ledger_dump()["records"][-1]
    assert rec["outcome"] == "complete" and rec["tenant"] == "acme"
    assert rec["resume"] == {"rng_skip": 2}
    scrape_b = {"endpoint": "b", "service": "gen",
                "spans": trace.get_spans()}
    streams = obs_dump.stream_traces([scrape_a, scrape_b])
    d = streams[tid]
    assert d["endpoints"] == ["a", "b"]
    assert "gen/ledger" in d["names"]
    assert d["retired"] == "complete"    # B's completion wins the join


# ---------------------------------------------------------------------------
# spans on the profiler's clock: recording follows a live capture
# ---------------------------------------------------------------------------

def _host_events(logdir):
    """``{name: [event, ...]}`` of the capture's ``/host:CPU`` plane."""
    import pathlib

    import jax

    path = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(e)
    return out


def _capture_at_most_twice(tmp_path, attempt):
    """``attempt(logdir)`` drives one capture, asserts what the program
    itself promises (its ring: every such assertion holds on every
    capture) and returns what it found wrong with the capture's
    ``/host:CPU`` plane. The profiler's host tracer is best effort: on a
    machine loaded by the whole suite it has dropped one event of some
    forty in one capture of 12–40, and a thread descheduled between an
    annotation and the span's own clock read stretches one against the
    other. So the plane is held to agreeing with the ring in one of two
    captures, not to never losing an event."""
    wrong = attempt(tmp_path / "first")
    if wrong:
        trace.clear()
        wrong = attempt(tmp_path / "second")
    assert not wrong, wrong


def test_capture_records_spans_on_the_host_plane(tmp_path):
    """Inside ``jax.profiler.start_trace`` — ``FLAGS_trace`` off — spans
    record, and the capture's ``/host:CPU`` plane holds an event of each
    span's name whose ``span_id`` stat is a ring record's and whose
    duration agrees with the record's to 0.2 ms."""
    import jax

    def attempt(logdir):
        assert not trace.recording()
        jax.profiler.start_trace(str(logdir))
        try:
            assert trace.recording() and not get_flags(["trace"])["trace"]
            with trace.span("t/outer", queue=3):
                time.sleep(0.01)
                with trace.span("t/inner"):
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        assert not trace.recording()
        recs = {s["name"]: s for s in trace.get_spans()}
        assert set(recs) == {"t/outer", "t/inner"}
        outer, inner = recs["t/outer"], recs["t/inner"]
        assert inner["parent_id"] == outer["span_id"]
        # the ring's own clock: the inner record lies inside the outer
        assert outer["mono"] <= inner["mono"]
        assert (inner["mono"] + inner["dur"]
                <= outer["mono"] + outer["dur"] + 1e-9)
        events = _host_events(logdir)
        wrong = []
        for name, rec in recs.items():
            found = events.get(name, [])
            if len(found) != 1:
                wrong.append(f"{len(found)} events named {name}")
                continue
            stats = dict(found[0].stats)
            if stats.get("span_id") != rec["span_id"]:
                wrong.append(f"{name}: span_id {stats.get('span_id')}")
            if abs(found[0].duration_ns * 1e-9 - rec["dur"]) >= 2e-4:
                wrong.append(f"{name}: event {found[0].duration_ns} ns, "
                             f"record {rec['dur']} s")
        if wrong:
            return wrong
        if dict(events["t/outer"][0].stats)["queue"] != 3:
            wrong.append("t/outer lost its attribute")
        # one clock: the inner event starts inside the outer one
        o, i = events["t/outer"][0], events["t/inner"][0]
        if not (o.start_ns <= i.start_ns and i.start_ns + i.duration_ns
                <= o.start_ns + o.duration_ns):
            wrong.append("the inner event is not inside the outer one")
        return wrong

    _capture_at_most_twice(tmp_path, attempt)


def test_capture_leaves_the_wire_untraced(tmp_path):
    """The per-message wire paths follow ``FLAGS_trace`` alone: inside a
    capture with the flag off a round trip records no ``wire/*`` span
    and carries no trace keys, while a plain span beside it records."""
    import jax

    captured = {}

    class _Capture(FrameService):
        def _dispatch(self, sock, op, header, payload):
            captured.update(header)
            send_frame(sock, 0, {})
            return True

    srv = _Capture().start()
    c = FrameClient(srv.endpoint, {"go": 1}, service="test", timeout=5.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.recording() and not trace.flag_on()
        with trace.span("t/around"):
            c._request("go", {"x": 1})
    finally:
        jax.profiler.stop_trace()
        c.close()
        srv.stop()
    assert captured["x"] == 1
    assert "tr" not in captured and "sp" not in captured
    assert [s["name"] for s in trace.get_spans()] == ["t/around"]


def test_ring_outlives_the_capture_and_counts_what_it_drops(tmp_path):
    """What a capture recorded is read after it has ended, until
    ``clear()``; a ring too small for it says how much it evicted."""
    import jax

    set_flags({"trace_buffer": 4})
    jax.profiler.start_trace(str(tmp_path))
    try:
        for n in range(10):
            with trace.span(f"s{n}"):
                pass
    finally:
        jax.profiler.stop_trace()
    with trace.span("after"):       # no capture, no flag: not recorded
        pass
    assert [s["name"] for s in trace.get_spans()] == ["s6", "s7", "s8", "s9"]
    snap = trace.snapshot()
    assert snap["enabled"] is False and snap["dropped"] == 6
    assert snap["capacity"] == 4 and len(snap["spans"]) == 4
    trace.clear()
    assert trace.get_spans() == [] and trace.snapshot()["dropped"] == 0
    set_flags({"trace_buffer": 16384})


def test_compiles_are_counted_from_jax_events():
    """The per-thread count moves when jax builds a program on this
    thread and only then, recording or not."""
    import threading

    import jax
    import jax.numpy as jnp

    def fresh_program(x):
        return jnp.cos(x) * 3 + 1

    x5, x7 = jnp.ones(5), jnp.ones(7)     # making them compiles too
    assert not trace.recording()
    mine = trace.thread_compiles()
    f = jax.jit(fresh_program)
    f(x5).block_until_ready()
    assert trace.thread_compiles() == mine + 1
    f(x5).block_until_ready()                     # jit cache hit
    assert trace.thread_compiles() == mine + 1
    other = threading.Thread(target=lambda: f(x7).block_until_ready())
    other.start()                                 # new shape, elsewhere
    other.join()
    assert trace.thread_compiles() == mine + 1
    assert trace.get_spans() == []


def _self_times(spans):
    """Span id → duration minus what its recorded children cover."""
    out = {s["span_id"]: s["dur"] for s in spans}
    for s in spans:
        if s["parent_id"] in out:
            out[s["parent_id"]] -= s["dur"]
    return out


def test_engine_loop_spans_nest_inside_a_capture(_gen_model, tmp_path):
    """A paged engine driven inside a capture, no flag set: every
    ``gen/loop`` iteration is the parent of its phases and holds them on
    the ring's monotonic clock (``ts``, the realtime stamp, is a second
    read and joins a record to its event, nothing more), no self time is
    negative, decode steps split into dispatch and wait, and each
    ``gen/admit`` says how long its request waited — no longer than the
    request took. The capture holds the iterations on the host plane."""
    import jax
    import numpy as np

    from paddle_tpu.serving import GenerationEngine

    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 96, size=n).astype(np.int32)
               for n in (5, 9, 12, 7)]

    def attempt(logdir):
        with GenerationEngine(_gen_model, slots=2, max_len=48, queue_max=8,
                              paged=True, page_tokens=8, pages=24) as eng:
            _drain_gen(eng, eng.start(prompts[0], 3))     # compile first
            jax.profiler.start_trace(str(logdir))
            try:
                t0 = time.monotonic()
                gids = [eng.start(p, 6) for p in prompts]
                latency = {}
                for g in gids:
                    _, err = _drain_gen(eng, g)
                    assert err is None
                    latency[g] = time.monotonic() - t0
            finally:
                jax.profiler.stop_trace()
        spans = trace.get_spans()
        assert trace.snapshot()["dropped"] == 0
        by_id = {s["span_id"]: s for s in spans}
        loops = [s for s in spans if s["name"] == "gen/loop"]
        assert loops and all("queue" in s["attrs"] and "active" in s["attrs"]
                             for s in loops)
        loop_thread = {s["tid"] for s in loops}
        assert len(loop_thread) == 1
        names = {s["name"] for s in spans if s["tid"] in loop_thread}
        assert names >= {"gen/loop", "gen/admit", "gen/dev_ops",
                         "gen/table_upload", "gen/prefill_chunk",
                         "gen/prefill_wait", "gen/decode_step",
                         "gen/step_dispatch", "gen/launch", "gen/step_wait",
                         "gen/emit"}, names

        def parent(s):
            return by_id.get(s["parent_id"], {}).get("name")

        for s in spans:
            if s["tid"] not in loop_thread or s["parent_id"] not in by_id:
                continue        # its iteration began before the capture
            if s["name"] in ("gen/step_dispatch", "gen/step_wait"):
                assert parent(s) == "gen/decode_step"
            elif s["name"] == "gen/launch":
                assert parent(s) in ("gen/step_dispatch",
                                     "gen/prefill_chunk")
            elif s["name"] == "gen/prefill_wait":
                assert parent(s) == "gen/prefill_chunk"
            elif s["name"] in ("gen/admit", "gen/prefill_chunk",
                               "gen/decode_step", "gen/idle_wait",
                               "gen/dev_ops", "gen/table_upload"):
                assert parent(s) == "gen/loop", (s["name"], parent(s))
            p = by_id[s["parent_id"]]
            assert p["mono"] <= s["mono"]
            assert s["mono"] + s["dur"] <= p["mono"] + p["dur"] + 1e-9
        assert min(_self_times(spans).values()) > -1e-6
        steps = [s for s in spans if s["name"] == "gen/decode_step"]
        assert all(s["attrs"]["compiled"] == 0 for s in steps)
        admits = [s for s in spans if s["name"] == "gen/admit"
                  and "waited_ms" in s["attrs"]]
        assert {s["attrs"]["gen"] for s in admits} == set(gids)
        for s in admits:
            assert 0 <= s["attrs"]["waited_ms"] * 1e-3 <= latency[
                s["attrs"]["gen"]]
            assert s["attrs"]["pages"] >= 1 and "prefix_tokens" in s["attrs"]
        # and the capture holds them on the host plane
        events = _host_events(logdir)
        ids = {dict(e.stats).get("span_id")
               for e in events.get("gen/loop", [])}
        lost = {s["span_id"] for s in loops} - ids
        return [f"{len(lost)} of {len(loops)} gen/loop records have no "
                f"event on /host:CPU"] if lost else []

    _capture_at_most_twice(tmp_path, attempt)


def test_phase_feeds_span_histogram_and_goodput_from_one_pair_of_reads(
        _gen_model):
    """``_phase`` reads the clock on entry and on exit and nowhere else:
    the span's duration, the histogram's sample and the goodput bucket's
    seconds are the same number."""
    from paddle_tpu.serving import GenerationEngine

    _tracing_on()
    with GenerationEngine(_gen_model, slots=1, max_len=32, queue_max=2,
                          ledger=True) as eng:
        monitor.reset_stats("t/")
        before = eng._goodput.snapshot()["buckets"]["kv_fetch"]
        with eng._phase("t/phase", "kv_fetch", "t/phase_s", k=1) as ph:
            time.sleep(0.003)
        after = eng._goodput.snapshot()["buckets"]["kv_fetch"]
        # an exception records the span and feeds nothing else
        with pytest.raises(ValueError):
            with eng._phase("t/phase", "kv_fetch", "t/phase_s"):
                raise ValueError("x")
    assert ph.dt == (ph.t1 - ph.t0) * 1e-9 and ph.dt >= 0.003
    recs = [s for s in trace.get_spans() if s["name"] == "t/phase"]
    assert len(recs) == 2 and recs[0]["attrs"] == {"k": 1}
    assert recs[0]["dur"] == ph.dt
    assert recs[1]["attrs"]["error"] == "ValueError"
    hist = monitor.get_histogram("t/phase_s")
    assert hist["count"] == 1 and hist["sum"] == pytest.approx(ph.dt, abs=0)
    assert after - before == pytest.approx(ph.dt, rel=1e-9)
    assert eng._goodput.snapshot()["buckets"]["kv_fetch"] == after
    monitor.reset_stats("t/")


def test_phase_without_a_recorder_still_times(_gen_model):
    """Nothing records: the section has no span, and histogram and
    ``dt`` still come from the two reads."""
    from paddle_tpu.serving import GenerationEngine

    with GenerationEngine(_gen_model, slots=1, max_len=32,
                          queue_max=2) as eng:
        monitor.reset_stats("t/")
        with eng._phase("t/quiet", hist="t/quiet_s") as ph:
            assert not ph.recording
        assert monitor.get_histogram("t/quiet_s")["sum"] == ph.dt
        # nothing to feed at all: the shared no-op, which reads no
        # clock, unless the caller wants the reads or the ledger a
        # bucket
        bare = eng._phase("t/bare", k=1)
        assert bare is eng._phase("t/other", "decode")
        with bare as inside:
            assert not inside.recording
            inside.set(k=2)
        assert not hasattr(bare, "t0")
        with eng._phase("t/clocked", clock=True) as ph:
            pass
        assert ph is not bare and ph.t1 >= ph.t0
    with GenerationEngine(_gen_model, slots=1, max_len=32, queue_max=2,
                          ledger=True) as eng:
        assert eng._phase("t/bare") is bare
        assert eng._phase("t/booked", "decode") is not bare
    assert trace.get_spans() == []
    monitor.reset_stats("t/")



# ---------------------------------------------------------------------------
# a span's two clocks, and the loop's launch / landing marks
# ---------------------------------------------------------------------------

_TICK = 2e-4        # what a CPU-clock reading may run ahead of the wall's


def test_every_record_carries_mono_and_cpu():
    """``mono`` is the span's start on the monotonic clock its duration
    is taken on — so a child lies inside its parent exactly — and
    ``cpu`` the thread's own CPU time inside it, never more than the
    wall time but for a tick; the Chrome export carries it."""
    _tracing_on()
    before = time.perf_counter()
    with trace.span("t/a"):
        with trace.span("t/b", k=1):
            sum(range(20000))
        with trace.span("t/c"):
            pass
    after = time.perf_counter()
    recs = {s["name"]: s for s in trace.get_spans()}
    assert set(recs) == {"t/a", "t/b", "t/c"}
    for s in recs.values():
        assert before <= s["mono"] <= s["mono"] + s["dur"] <= after
        assert 0.0 <= s["cpu"] <= s["dur"] + _TICK
        assert abs(s["ts"] - time.time()) < 60          # still realtime
    a, b, c = recs["t/a"], recs["t/b"], recs["t/c"]
    assert a["mono"] <= b["mono"] <= b["mono"] + b["dur"] <= c["mono"]
    assert c["mono"] + c["dur"] <= a["mono"] + a["dur"] + 1e-9
    assert a["cpu"] >= b["cpu"] + c["cpu"] - _TICK
    ev = {e["name"]: e for e in trace.to_chrome_events(trace.get_spans())}
    assert ev["t/b"]["args"]["cpu"] == b["cpu"] and ev["t/b"]["args"]["k"] == 1
    # a record of a peer that has no such field exports as before
    old = {k: v for k, v in b.items() if k not in ("mono", "cpu")}
    assert "cpu" not in trace.to_chrome_events([old])[0]["args"]


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_cpu_tells_a_span_that_waits_from_one_that_works(how):
    """A sleeping span reads ``cpu`` ~ 0 beside its wall time; one that
    computes reads the CPU time it burnt (on a loaded machine that may
    be well under its wall time, never over)."""
    _tracing_on()
    burn = 0.03
    with trace.span("t/x"):
        if how == "sleeps":
            time.sleep(0.05)
        else:
            c0 = time.thread_time()
            while time.thread_time() - c0 < burn:
                pass
    (rec,) = trace.get_spans()
    assert rec["cpu"] <= rec["dur"] + _TICK
    if how == "sleeps":
        assert rec["dur"] >= 0.05 and rec["cpu"] < 0.01
    else:
        assert rec["cpu"] >= burn - _TICK and rec["dur"] >= burn - _TICK


_LOOP_SHAPES = {
    "synchronous": dict(),
    "depth_1": dict(paged=True, page_tokens=8, pages=24, async_depth=1),
    "chunked": dict(paged=True, page_tokens=8, pages=24, prefill_chunk=4),
    "speculative": dict(spec_k=4, spec_mode="ngram",
                        spec_shed_occupancy=1.0),
}


@pytest.mark.parametrize("shape", list(_LOOP_SHAPES))
def test_loop_marks_what_it_launches_and_what_it_has_seen_land(
        _gen_model, shape):
    """Every call into a compiled engine program is a ``gen/launch``
    numbered in order under the span that staged its operands, and
    every readback names as ``landed`` a launch that was made: in
    order (but for a step left in flight behind a first token's
    readback), none twice. A prefill chunk that is not the last launches
    and lands nothing; at depth 1 a step lands after the next was
    launched; a speculative step is launched and landed as one."""
    import numpy as np

    from paddle_tpu.serving import GenerationEngine

    _tracing_on(16384)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, 96, size=n).astype(np.int32)
               for n in (5, 9, 12, 7)]
    if shape == "speculative":              # something an n-gram finds
        prompts = [np.tile(p[:3], 4) for p in prompts]
    with GenerationEngine(_gen_model, slots=2, max_len=48, queue_max=8,
                          **_LOOP_SHAPES[shape]) as eng:
        for g in [eng.start(p, 8) for p in prompts]:
            assert _drain_gen(eng, g)[1] is None
        launched = eng._launched
        spec_steps = eng.stats().get("spec", {}).get("verify_steps", 0)
    spans = sorted(trace.get_spans(), key=lambda s: s["mono"])
    assert trace.snapshot()["dropped"] == 0
    by_id = {s["span_id"]: s for s in spans}
    launches = [s for s in spans if s["name"] == "gen/launch"]
    seqs = [s["attrs"]["seq"] for s in launches]
    assert seqs == list(range(1, launched + 1))
    assert len({s["tid"] for s in launches}) == 1
    staged_by = {"step": "gen/step_dispatch", "paged_step":
                 "gen/step_dispatch", "spec_step": "gen/step_dispatch",
                 "prefill": "gen/prefill", "paged_prefill":
                 "gen/prefill_chunk", "draft": "gen/draft"}
    for s in launches:
        p = by_id[s["parent_id"]]
        assert p["name"] == staged_by[s["attrs"]["entry"]]
        assert p["mono"] <= s["mono"]
        assert s["mono"] + s["dur"] <= p["mono"] + p["dur"] + 1e-9
    landings = sorted((s for s in spans if "landed" in s["attrs"]),
                      key=lambda s: s["mono"] + s["dur"])
    landed = [s["attrs"]["landed"] for s in landings]
    assert len(set(landed)) == len(landed) and set(landed) <= set(seqs)
    for name in ("gen/step_wait", "gen/prefill_wait"):
        mine = [s["attrs"]["landed"] for s in landings if s["name"] == name]
        assert mine == sorted(mine)
    # only a step left in flight is read back behind a later launch's
    # landing (a first token's readback proves it finished too)
    assert landed == sorted(landed) or shape == "depth_1"
    assert {s["name"] for s in landings} <= {"gen/step_wait",
                                             "gen/prefill_wait"}
    start_of = {s["attrs"]["seq"]: s["mono"] for s in launches}
    for s in landings:                      # nothing lands before it starts
        assert start_of[s["attrs"]["landed"]] <= s["mono"]
    entry_of = {s["attrs"]["seq"]: s["attrs"]["entry"] for s in launches}
    waits = [s for s in landings if s["name"] == "gen/prefill_wait"]
    assert waits and all(entry_of[s["attrs"]["landed"]].endswith("prefill")
                         and by_id[s["parent_id"]]["name"] in
                         ("gen/prefill", "gen/prefill_chunk") for s in waits)
    chunks = [s for s in spans if s["name"] == "gen/prefill_chunk"]
    if shape == "chunked":
        inner = [s for s in chunks if not s["attrs"]["final"]]
        assert inner and len(waits) == len(chunks) - len(inner)
        held = {s["parent_id"] for s in waits}
        assert not held & {s["span_id"] for s in inner}
        assert set(landed) < set(seqs)          # they land with a later one
    elif shape == "depth_1":
        behind = [s for s in landings if s["name"] == "gen/step_wait"
                  and start_of.get(s["attrs"]["landed"] + 1, float("inf"))
                  < s["mono"]]
        assert behind, "no step was read back behind the next one's launch"
        assert all(by_id[s["parent_id"]]["name"] == "gen/loop"
                   for s in behind)
    elif shape == "speculative":
        spec = [q for q, e in entry_of.items() if e == "spec_step"]
        assert spec_steps > 0 and len(spec) == spec_steps
        assert set(spec) <= set(landed)
    else:
        assert landed == seqs                   # each launch, then its landing


def test_untraced_launches_read_no_clock_and_allocate_no_section(
        _gen_model, monkeypatch):
    """Nothing records: the launch, landing and table-upload sections
    are the shared no-op, the launch counter still counts, and the loop
    thread reads the clock exactly twice a compiled call — the reads of
    the section that holds it (``gen/decode_step``, ``gen/prefill``),
    which feed its histogram — and the thread's CPU clock never."""
    import numpy as np

    from paddle_tpu.serving import GenerationEngine, engine as engine_mod

    reads = {"perf_counter_ns": 0, "thread_time_ns": 0}

    class _Clock:
        def __getattr__(self, name):
            if name in reads:
                reads[name] += 1
            return getattr(time, name)

    assert not trace.recording()
    rs = np.random.RandomState(4)
    with GenerationEngine(_gen_model, slots=2, max_len=48,
                          queue_max=8) as eng:
        noop = engine_mod._NOOP_PHASE
        assert eng._phase("gen/launch", seq=1) is noop
        assert eng._phase("gen/step_wait", landed=1) is noop
        assert eng._phase("gen/prefill_wait", landed=1) is noop
        assert eng._phase("gen/table_upload") is noop
        n0 = eng._launched
        assert eng._launch("step") is noop and eng._launched == n0 + 1
        monkeypatch.setattr(engine_mod, "time", _Clock())
        n0 = eng._launched
        for g in [eng.start(rs.randint(1, 96, size=6).astype(np.int32), 5)
                  for _ in range(3)]:
            assert _drain_gen(eng, g)[1] is None
        calls = eng._launched - n0
        monkeypatch.undo()
    assert calls >= 3 + 4
    assert reads == {"perf_counter_ns": 2 * calls, "thread_time_ns": 0}
    assert trace.get_spans() == []


def test_train_step_span_marks_the_calls_that_compiled():
    """``train/step`` carries ``compiled=1`` on the first call and on a
    call with a new batch shape, 0 on every other; ``train/shard_batch``
    records beside it."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    import paddle_tpu.distributed as dist
    from paddle_tpu import nn, optimizer as optim
    from paddle_tpu.parallel import mesh as M

    paddle_tpu.seed(0)
    model = nn.Linear(4, 1)
    mesh = M.create_mesh({"dp": 1}, devices=jax.devices()[:1])

    def loss_fn(m, batch, training=True):
        return jnp.mean((m(batch["x"]) - batch["y"]) ** 2)

    _tracing_on()
    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            model, optimizer=optim.SGD(0.1), loss_fn=loss_fn, mesh=mesh)
        state = step.init_state(model)
        for rows in (2, 2, 2, 6, 6):
            batch = step.shard_batch({"x": jnp.ones((rows, 4)),
                                      "y": jnp.ones((rows, 1))})
            state, _ = step(state, batch, jax.random.PRNGKey(0))
    spans = trace.get_spans()
    steps = [s for s in spans if s["name"] == "train/step"]
    assert [s["attrs"]["compiled"] for s in steps] == [1, 0, 0, 1, 0]
    assert sum(s["name"] == "train/shard_batch" for s in spans) == 5
