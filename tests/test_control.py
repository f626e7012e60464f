"""Serving control plane: multi-model multiplexing, SLO-driven
autoscaling, sticky-drain scale-down — plus the wire/router primitives
it stands on (the ``unload_model`` op, per-model health stats, cordon)
and `RoutedClient` membership churn under live traffic.

The load-bearing properties: a clean scale-down loses ZERO in-flight
work (every session-pinned generation runs to completion on the replica
holding its KV state — no ``GenerationFailed``), and a replica serves
more registered models than its warm-tier capacity via LRU eviction.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core import monitor
from paddle_tpu.core.flags import get_flags, set_flags
from paddle_tpu.io import (
    InferenceClient, InferenceServer, ModelBusyError, Predictor,
    save_inference_model,
)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate
from paddle_tpu.serving import (
    GenerationEngine, InProcSpawner, RoutedClient, ServingController,
)
from paddle_tpu.serving.metrics import hist_delta

pytestmark = pytest.mark.control

VOCAB = 96


@pytest.fixture(scope="module")
def mlp_path(tmp_path_factory):
    """A dynamic-batch MLP artifact shared by the fleet tests."""
    paddle_tpu.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    path = str(tmp_path_factory.mktemp("ctl") / "mlp")
    save_inference_model(path, net, [np.zeros((2, 4), np.float32)],
                         dynamic_batch=True)
    return path


@pytest.fixture(scope="module")
def mlp_paths(tmp_path_factory):
    """Three distinct artifacts — the multi-model registry (distinct
    weights so responses identify which model answered)."""
    out = {}
    for i, name in enumerate(("a", "b", "c")):
        paddle_tpu.seed(i + 1)
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
        path = str(tmp_path_factory.mktemp("ctlm") / name)
        save_inference_model(path, net, [np.zeros((2, 4), np.float32)],
                             dynamic_batch=True)
        out[name] = path
    return out


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    return LlamaForCausalLM(cfg)


# ---------------------------------------------------------------------------
# unload_model wire op + per-model health stats
# ---------------------------------------------------------------------------

def test_unload_model_roundtrip(mlp_path):
    srv = InferenceServer({"m": mlp_path}).start()
    try:
        with InferenceClient(srv.endpoint) as c:
            (y,) = c.infer("m", np.ones((2, 4), np.float32))
            assert y.shape == (2, 3)
            assert c.unload_model("m") is True
            assert c.unload_model("m") is False      # idempotent
            with pytest.raises(RuntimeError, match="no model"):
                c.infer("m", np.ones((2, 4), np.float32))
            c.load_model("m", mlp_path)              # reload works
            (y2,) = c.infer("m", np.ones((2, 4), np.float32))
            np.testing.assert_allclose(y2, y, rtol=1e-6)
    finally:
        srv.stop()


def test_unload_model_admin_gated(mlp_path):
    srv = InferenceServer({"m": mlp_path}, admin_ops=False).start()
    try:
        with InferenceClient(srv.endpoint) as c:
            with pytest.raises(RuntimeError, match="admin"):
                c.unload_model("m")
            # data plane unaffected
            assert c.infer("m", np.ones((1, 4), np.float32))[0].shape \
                == (1, 3)
    finally:
        srv.stop()


def test_unload_busy_in_batcher_fails_typed(mlp_path):
    """A model with requests inside the dynamic batcher refuses the
    unload with the typed ModelBusyError — clean and retryable, never a
    hang or a predictor yanked from a forming batch."""

    class _SlowDyn:
        supports_batching = True
        input_specs = [{"shape": [None, 4], "dtype": "float32"}]
        output_specs = [{"shape": [None, 3], "dtype": "float32"}]

        def run(self, x):
            time.sleep(0.5)
            return np.zeros((x.shape[0], 3), np.float32)

    set_flags({"serving_batch_max": 8, "serving_batch_timeout_s": 0.05,
               "serving_batch_min_queue": 0})
    srv = InferenceServer()
    srv.add_model("slow", _SlowDyn())
    srv.start()
    try:
        done = []

        def worker():
            with InferenceClient(srv.endpoint, timeout=15.0) as c:
                done.append(c.infer("slow", np.ones((1, 4), np.float32)))

        t = threading.Thread(target=worker)
        t.start()
        time.sleep(0.15)                 # request is inside the batcher
        with pytest.raises(ModelBusyError, match="batcher"):
            srv.unload_model("slow")
        with InferenceClient(srv.endpoint, timeout=15.0) as c:
            with pytest.raises(ModelBusyError):   # typed over the wire
                c.unload_model("slow")
        t.join(timeout=30)
        assert len(done) == 1            # the batched request survived
        assert srv.unload_model("slow") is True   # drained: unload ok
    finally:
        set_flags({"serving_batch_max": 0, "serving_batch_timeout_s": 0.005,
                   "serving_batch_min_queue": 2})
        srv.stop()


def test_health_ships_per_model_stats(mlp_path):
    srv = InferenceServer({"m": mlp_path}).start()
    try:
        with InferenceClient(srv.endpoint) as c:
            h0 = c.health()
            assert h0["models"]["m"]["infers"] == 0
            assert h0["models"]["m"]["resident_bytes"] > 0
            for _ in range(3):
                c.infer("m", np.ones((1, 4), np.float32))
            h1 = c.health()
            st = h1["models"]["m"]
            assert st["infers"] == 3
            assert st["last_used_ts"] >= h0["models"]["m"]["last_used_ts"]
            assert st["idle_s"] < 5.0
            # stats_prefix still filters the monitor-stats snapshot;
            # the models/generators decision inputs always ship
            h2 = c.health(stats_prefix="\x00none")
            assert h2["stats"] == {}
            assert h2["models"]["m"]["infers"] == 3
    finally:
        srv.stop()


def test_router_unload_broadcast(mlp_path):
    servers = [InferenceServer({"m": mlp_path}).start() for _ in range(2)]
    rc = RoutedClient([s.endpoint for s in servers], probe_interval_s=0,
                      timeout=10.0)
    try:
        out = rc.unload_model("m")
        assert out == {s.endpoint: True for s in servers}
        with pytest.raises(RuntimeError, match="no model"):
            rc.infer("m", np.ones((1, 4), np.float32))
        rc.load_model("m", mlp_path)     # broadcast reload
        assert rc.infer("m", np.ones((1, 4), np.float32))[0].shape \
            == (1, 3)
    finally:
        rc.close()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# cordon (the sticky-drain routing primitive)
# ---------------------------------------------------------------------------

def test_cordon_excludes_new_picks_keeps_member(mlp_path):
    servers = [InferenceServer({"m": mlp_path}).start() for _ in range(2)]
    rc = RoutedClient([s.endpoint for s in servers], probe_interval_s=0,
                      timeout=10.0)
    try:
        rc.cordon(servers[0].endpoint)
        m = {r["endpoint"]: r for r in rc.members()}
        assert m[servers[0].endpoint]["cordoned"]
        assert m[servers[0].endpoint]["healthy"]     # cordon != down
        for _ in range(6):
            rc.infer("m", np.ones((1, 4), np.float32))
        # all traffic went to the uncordoned replica
        h = rc.health()
        # per-model infer counters prove placement (replica-local state)
        assert h[servers[0].endpoint]["models"]["m"]["infers"] == 0
        assert h[servers[1].endpoint]["models"]["m"]["infers"] == 6
        rc.uncordon(servers[0].endpoint)
        assert not rc.members()[0]["cordoned"]
        rc.infer("m", np.ones((1, 4), np.float32))   # eligible again
    finally:
        rc.close()
        for s in servers:
            s.stop()


def test_cordon_lets_pinned_generation_finish(model):
    """Cordon the replica holding a live generation: the stream keeps
    polling the SAME replica to completion (byte-identical), while new
    sessions pin elsewhere — the router half of sticky drain."""
    servers = []
    for _ in range(2):
        srv = InferenceServer().start()
        srv.add_generator("llm", model, slots=2, max_len=32,
                          step_wait_s=0.02)
        servers.append(srv)
    rc = RoutedClient([s.endpoint for s in servers], probe_interval_s=0,
                      timeout=10.0)
    try:
        rs = np.random.RandomState(11)
        prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 12))[0, 5:]
        sess = rc.session("drain-me")
        it = sess.generate("llm", prompt, 12, poll_wait_s=0.05)
        toks = [next(it)]
        pinned = sess.endpoint
        rc.cordon(pinned)
        toks += list(it)                  # stream survives the cordon
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        other = next(s.endpoint for s in servers if s.endpoint != pinned)
        sess2 = rc.session("new-after-cordon")
        sess2.health()
        assert sess2.endpoint == other    # new pins avoid the cordoned
    finally:
        rc.close()
        for s in servers:
            s.stop()


def test_membership_churn_under_concurrent_traffic(model, mlp_path):
    """Satellite: add/remove/cordon endpoints while infer AND streaming
    generations are in flight — zero lost requests, streams
    byte-identical, membership lands where the churn put it."""
    servers = []
    for _ in range(3):
        srv = InferenceServer({"m": mlp_path}).start()
        srv.add_generator("llm", model, slots=2, max_len=32,
                          step_wait_s=0.01)
        servers.append(srv)
    rc = RoutedClient([s.endpoint for s in servers[:2]],
                      probe_interval_s=0, timeout=10.0)
    ref_pred = Predictor(mlp_path)
    rs = np.random.RandomState(12)
    prompts = [rs.randint(0, VOCAB, (4 + i,)).astype(np.int32)
               for i in range(2)]
    refs = [np.asarray(generate(model, p[None], 10))[0, p.size:]
            for p in prompts]
    stop_at = time.perf_counter() + 2.0
    infer_results: dict = {}
    streams: dict = {}
    errors: list = []

    def infer_worker(i):
        try:
            j = 0
            while time.perf_counter() < stop_at:
                x = np.full((1, 4), float(i * 100 + j), np.float32)
                infer_results[(i, j)] = (x, rc.infer("m", x)[0])
                j += 1
                time.sleep(0.005)
        except Exception as e:
            errors.append(f"infer{i}: {type(e).__name__}: {e}")

    def stream_worker(i):
        try:
            sess = rc.session(f"churn-{i}")
            streams[i] = list(sess.generate("llm", prompts[i], 10,
                                            poll_wait_s=0.05))
        except Exception as e:
            errors.append(f"stream{i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=infer_worker, args=(i,))
               for i in range(3)]
    threads += [threading.Thread(target=stream_worker, args=(i,))
                for i in range(2)]
    for t in threads:
        t.start()
    # churn while traffic flows: grow, cordon/uncordon the one member
    # guaranteed stream-free (just added), then remove and re-add it
    time.sleep(0.2)
    rc.add_endpoint(servers[2].endpoint)
    time.sleep(0.2)
    rc.cordon(servers[2].endpoint)
    time.sleep(0.2)
    rc.uncordon(servers[2].endpoint)
    time.sleep(0.2)
    rc.remove_endpoint(servers[2].endpoint)
    time.sleep(0.2)
    rc.add_endpoint(servers[2].endpoint)
    for t in threads:
        t.join(timeout=60)
    try:
        assert not errors, errors
        assert len(infer_results) >= 20
        for (i, j), (x, y) in infer_results.items():
            np.testing.assert_allclose(y, np.asarray(ref_pred.run(x)),
                                       rtol=1e-5, atol=1e-6)
        for i in range(2):
            np.testing.assert_array_equal(
                np.asarray(streams[i], np.int32), refs[i])
        assert len(rc.endpoints()) == 3
    finally:
        rc.close()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# engine: undelivered (the drain-wait signal)
# ---------------------------------------------------------------------------

def test_engine_undelivered_tracks_final_poll(model):
    with GenerationEngine(model, slots=2, max_len=32) as eng:
        gid = eng.start(np.arange(1, 6, dtype=np.int32), 3)
        deadline = time.monotonic() + 10
        while not eng.poll(gid, start=0, wait_s=0.2)["done"]:
            assert time.monotonic() < deadline
        # done AND the done-carrying poll answered -> delivered
        assert eng.stats()["undelivered"] == 0
        gid2 = eng.start(np.arange(1, 6, dtype=np.int32), 3)
        deadline = time.monotonic() + 10
        while eng.stats()["active"] > 0 or eng.stats()["queued"] > 0:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        # finished computing, but no poll told the client: undelivered
        assert eng.stats()["undelivered"] == 1
        eng.poll(gid2, start=0, wait_s=0.2)
        assert eng.stats()["undelivered"] == 0


# ---------------------------------------------------------------------------
# ServingController
# ---------------------------------------------------------------------------

def _mlp_factory():
    return InferenceServer()


def test_controller_defaults_are_inert(mlp_path):
    """Flag defaults: autoscaling and eviction both off — ticks hold, no
    replica or model ever touched. (The data path reads no control_*
    flag at all; this pins the controller itself.)"""
    f = get_flags(["control_max_replicas", "control_warm_models"])
    assert f == {"control_max_replicas": 0, "control_warm_models": 0}
    ctl = ServingController(InProcSpawner(_mlp_factory), interval_s=0,
                            min_replicas=1)
    try:
        ctl.start()
        ctl.register_model("m", mlp_path)
        assert ctl.infer("m", np.ones((1, 4), np.float32))[0].shape \
            == (1, 3)
        for _ in range(8):
            d = ctl.tick()
        assert d.action == "hold" and "disabled" in d.reason
        assert len(ctl.router.endpoints()) == 1
        # no scale/evict decisions beyond the bootstrap + fault-in
        actions = {x["action"] for x in ctl.decisions()}
        assert actions <= {"scale_up", "fault_in"}   # bootstrap only
        assert sum(1 for x in ctl.decisions()
                   if x["action"] == "scale_up") == 1
    finally:
        ctl.close()


def test_controller_multiplexes_more_models_than_warm_tier(mlp_paths):
    """Warm capacity 1, three registered models: every model stays
    servable (cold ones fault in), residency never exceeds the cap
    after reconcile, and the LRU is the one evicted."""
    ctl = ServingController(InProcSpawner(_mlp_factory), interval_s=0,
                            min_replicas=1, warm_models=1)
    refs = {n: Predictor(p) for n, p in mlp_paths.items()}
    try:
        ctl.start()
        for n, p in mlp_paths.items():
            ctl.register_model(n, p)
        x = np.ones((1, 4), np.float32)
        for rnd in range(2):             # every model twice: re-fault-in
            for n in mlp_paths:
                np.testing.assert_allclose(
                    ctl.infer(n, x)[0], np.asarray(refs[n].run(x)),
                    rtol=1e-5, atol=1e-6)
        ctl.tick()
        for doc in ctl.router.health().values():
            assert len(doc["models"]) <= 1, doc["models"]
        evicts = [d for d in ctl.decisions() if d["action"] == "evict"]
        assert len(evicts) >= 3
        assert all("LRU" in d["reason"] for d in evicts)
    finally:
        ctl.close()


def test_controller_warm_pinned_model_survives_eviction(mlp_paths):
    ctl = ServingController(InProcSpawner(_mlp_factory), interval_s=0,
                            min_replicas=1, warm_models=1)
    try:
        ctl.start()
        ctl.register_model("a", mlp_paths["a"], warm=True)
        ctl.register_model("b", mlp_paths["b"])
        x = np.ones((1, 4), np.float32)
        ctl.infer("a", x)
        ctl.infer("b", x)                # over capacity: 2 resident > 1
        ctl.tick()
        for doc in ctl.router.health().values():
            assert "a" in doc["models"]  # pinned: never the LRU victim
    finally:
        ctl.close()


def _engine_factory(model, slots=1, step_wait_s=0.03):
    def factory():
        srv = InferenceServer().start()
        srv.add_generator("llm", model, slots=slots, max_len=32,
                          step_wait_s=step_wait_s)
        return srv
    return factory


def test_controller_scales_up_on_queue_pressure(model):
    """Sustained generation queueing (demand > slots) breaches for
    breach_ticks consecutive ticks -> one scale-up, with the queue
    signal named in the decision."""
    spawner = InProcSpawner(_engine_factory(model))
    ctl = ServingController(spawner, interval_s=0, min_replicas=1,
                            max_replicas=3, breach_ticks=2,
                            cooldown_s=0.0, queue_high=1.0)
    try:
        ctl.start()
        rs = np.random.RandomState(13)
        prompts = [rs.randint(0, VOCAB, (4,)).astype(np.int32)
                   for _ in range(3)]
        sessions = [ctl.router.session(f"load-{i}") for i in range(3)]
        its = [s.generate("llm", p, 20, poll_wait_s=0.02)
               for s, p in zip(sessions, prompts)]
        next(its[0])                      # slots=1: 2 of 3 queue behind
        d1 = ctl.tick()
        assert d1.action == "hold"        # hysteresis: 1 breach < 2
        assert d1.signals["queued"] >= 1
        d2 = ctl.tick()
        assert d2.action == "scale_up", (d2.action, d2.reason)
        assert "queued generations" in d2.reason
        assert len(ctl.router.endpoints()) == 2
        for it in its:                    # everything still completes
            list(it)
    finally:
        ctl.close()


def test_controller_cooldown_holds_second_scale_up(model):
    spawner = InProcSpawner(_engine_factory(model))
    ctl = ServingController(spawner, interval_s=0, min_replicas=1,
                            max_replicas=4, breach_ticks=1,
                            cooldown_s=60.0, queue_high=1.0)
    try:
        ctl.start()
        rs = np.random.RandomState(14)
        its = [ctl.router.session(f"cool-{i}").generate(
                   "llm", rs.randint(0, VOCAB, (4,)).astype(np.int32),
                   20, poll_wait_s=0.02) for i in range(3)]
        next(its[0])
        d1 = ctl.tick()
        assert d1.action == "scale_up"
        d2 = ctl.tick()                   # pressure persists; cooldown
        assert d2.action == "hold" and "cooldown" in d2.reason
        assert len(ctl.router.endpoints()) == 2     # no flap
        for it in its:
            list(it)
    finally:
        ctl.close()


def test_controller_sticky_drain_scale_down_is_lossless(model):
    """The tentpole acceptance: a scale-down victim with a LIVE pinned
    generation drains — the stream finishes byte-identical on the
    victim, no GenerationFailed, and only then is the replica stopped
    and removed."""
    monitor.reset_stats("control/")
    spawner = InProcSpawner(_engine_factory(model, slots=2))
    ctl = ServingController(spawner, interval_s=0, min_replicas=1,
                            max_replicas=2, drain_s=20.0)
    try:
        ctl.start()
        ctl.scale_to(2, reason="test setup")
        assert len(ctl.router.endpoints()) == 2
        rs = np.random.RandomState(15)
        prompt = rs.randint(0, VOCAB, (5,)).astype(np.int32)
        ref = np.asarray(generate(model, prompt[None], 15))[0, 5:]
        sess = ctl.router.session("pinned-on-victim")
        it = sess.generate("llm", prompt, 15, poll_wait_s=0.05)
        toks = [next(it)]
        victim = sess.endpoint
        got: dict = {}

        def drain():
            got["d"] = ctl.scale_down(victim=victim, reason="test drain")

        t = threading.Thread(target=drain)
        t.start()
        toks += list(it)                  # streams THROUGH the drain
        t.join(timeout=60)
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        d = got["d"]
        assert d.action == "scale_down" and d.endpoint == victim
        assert d.clean, d.reason          # inside the deadline, unforced
        assert monitor.get_stat("control/drain_forced") == 0
        assert victim not in ctl.router.endpoints()
        assert len(ctl.router.endpoints()) == 1
        assert victim not in spawner.servers        # actually stopped
        # the survivor still serves new generations
        toks2 = list(ctl.router.session("after").generate(
            "llm", prompt, 15, poll_wait_s=0.05))
        np.testing.assert_array_equal(np.asarray(toks2, np.int32), ref)
    finally:
        ctl.close()


def test_controller_scale_down_to_idle_fleet(model):
    """The automatic path: sustained idleness scales the fleet back to
    min_replicas (idle_ticks hysteresis), decision explains it."""
    spawner = InProcSpawner(_engine_factory(model))
    ctl = ServingController(spawner, interval_s=0, min_replicas=1,
                            max_replicas=3, idle_ticks=3, cooldown_s=0.0,
                            drain_s=10.0)
    try:
        ctl.start()
        ctl.scale_to(2, reason="test setup")
        d = None
        for _ in range(3):               # idle_ticks=3: fires on the 3rd
            d = ctl.tick()
        assert d.action == "scale_down", (d.action, d.reason)
        assert "idle" in d.reason and d.clean
        assert len(ctl.router.endpoints()) == 1
    finally:
        ctl.close()


def test_controller_replaces_dead_replica(mlp_path):
    spawner = InProcSpawner(_mlp_factory)
    ctl = ServingController(spawner, interval_s=0, min_replicas=2,
                            breach_ticks=1)
    try:
        ctl.start()
        ctl.register_model("m", mlp_path, warm=True)
        eps = ctl.router.endpoints()
        spawner.kill(eps[0])              # crash, no drain
        ctl.tick()                        # breach_ticks=1: replace now
        new_eps = ctl.router.endpoints()
        assert len(new_eps) == 2 and eps[0] not in new_eps
        replaced = [d for d in ctl.decisions()
                    if d["action"] == "replace"]
        assert replaced and "unreachable" in replaced[0]["reason"]
        # the substitute preloaded the warm model and serves it
        assert ctl.router.infer(
            "m", np.ones((1, 4), np.float32))[0].shape == (1, 3)
    finally:
        ctl.close()


def test_controller_spawn_preloads_registry(mlp_paths):
    ctl = ServingController(InProcSpawner(_mlp_factory), interval_s=0,
                            min_replicas=1)
    try:
        for n, p in mlp_paths.items():   # registry BEFORE any spawn
            ctl.register_model(n, p)
        ctl.start()
        ctl.scale_to(2, reason="grow")
        healths = ctl.router.health()
        assert len(healths) == 2
        for doc in healths.values():
            # warm_models=0 (no cap): every registered model preloads
            assert set(doc["models"]) == set(mlp_paths)
    finally:
        ctl.close()


def test_decisions_are_explainable():
    d = hist_delta(None, {"buckets": [1, 2], "count": 3, "sum": 1.0})
    assert d is None                      # no baseline yet
    assert hist_delta({"buckets": [1, 0]},
                      {"buckets": [1, 0], "count": 1}) is None  # empty
    d = hist_delta(
        {"buckets": [1, 2], "count": 3, "sum": 1.0},
        {"buckets": [2, 5], "count": 7, "sum": 4.0, "min": 0.1,
         "max": 0.9})
    assert d["buckets"] == [1, 3] and d["count"] == 4
    assert abs(d["sum"] - 3.0) < 1e-9


def _cum_hist(values):
    """A cumulative raw histogram snapshot, as ``health`` would ship."""
    h = monitor._Histogram()
    for v in values:
        h.observe(v)
    return h.summary(raw=True)


@pytest.mark.parametrize("max_replicas", [1, 2])
def test_controller_burn_rate_pressure_signals(max_replicas):
    """TTFT pressure is the multi-window burn rate, not a raw p99
    breach: the first scrape is a baseline (burn 0), a violating window
    trips BOTH windows past the threshold, and the resulting decision
    — a hold at ``max_replicas``, a scale-up below it — cites the burn
    and carries the evidence in its signals."""
    ctl = ServingController(InProcSpawner(_mlp_factory), interval_s=0,
                            max_replicas=max_replicas, breach_ticks=1,
                            cooldown_s=0.0, target_ttft_s=0.5,
                            slo_budget=0.1, burn_fast_ticks=2,
                            burn_slow_ticks=4, burn_threshold=1.0)
    try:
        def doc(values):
            return {"ep": {"status": "ok", "inflight": 0,
                           "generators": {}, "stats": {},
                           "histograms": {"gen/ttft_s":
                                          _cum_hist(values)}}}
        fast = [0.01] * 5
        s1 = ctl._signals(doc(fast))
        assert s1["ttft_burn_fast"] == 0.0      # baseline tick: no delta
        assert not ctl._pressure(s1)
        # window 2: five observations at 1.0s — 100% violating, budget
        # 0.1 -> burn 10x on both windows (one delta tick feeds both)
        s2 = ctl._signals(doc(fast + [2.0] * 5))
        assert s2["ttft_burn_fast"] == pytest.approx(10.0)
        assert s2["ttft_burn_slow"] == pytest.approx(10.0)
        assert s2["ttft_p99_s"] is not None and s2["ttft_p99_s"] > 0.5
        reasons = ctl._pressure(s2)
        assert any("burn rate" in r for r in reasons), reasons
        d = ctl._decide(s2)      # at max_replicas it holds, and the
        assert d.action == ("hold" if max_replicas == 1   # evidence is
                            else "scale_up")              # logged still
        assert "burn rate" in d.reason
        assert d.signals["ttft_burn_fast"] == pytest.approx(10.0)
        assert d.signals["ttft_burn_slow"] == pytest.approx(10.0)
        # two clean ticks push the violation out of the fast window: the
        # slow window still remembers it, but the PAGE condition needs
        # both — acute pressure released, no flapping on stale history
        s3 = ctl._signals(doc(fast + [2.0] * 5 + [0.01] * 20))
        s4 = ctl._signals(doc(fast + [2.0] * 5 + [0.01] * 40))
        assert s4["ttft_burn_fast"] == 0.0      # fast window is clean
        assert s4["ttft_burn_slow"] > 1.0       # slow window remembers
        assert not ctl._pressure(s4)
        assert s3["ttft_burn_fast"] < 10.0
    finally:
        ctl.close()


def test_controller_decision_log_schema(model):
    spawner = InProcSpawner(_engine_factory(model))
    ctl = ServingController(spawner, interval_s=0, min_replicas=1,
                            max_replicas=2, breach_ticks=1,
                            cooldown_s=0.0, drain_s=10.0)
    try:
        ctl.start()
        ctl.scale_to(2, reason="grow")
        ctl.scale_down(reason="shrink")
        docs = ctl.decisions()
        assert docs, "decisions must be recorded"
        for doc in docs:
            assert set(doc) == {"action", "reason", "endpoint", "clean",
                                "ts", "signals"}
            assert doc["reason"]
        acts = [d["action"] for d in docs]
        assert "scale_up" in acts and "scale_down" in acts
    finally:
        ctl.close()
