"""PS-backed sparse embedding serving (``FLAGS_serving_emb``, hard-off).

The load-bearing contracts: the hot-row LRU de-duplicates and batches
cache misses into ONE ``PSClient`` pull (with TTL expiry and capacity
eviction); the batched CTR endpoint's wire outputs match solo
predictions and stamp every response row with exactly one table
version; an online version rollover under concurrent load drops
nothing, restarts nothing, and never mixes two versions' rows inside
one response; PS outages degrade to counted stale serves rather than
errors for rows we still hold; and with the flag off (the default) the
server constructs no tier, ships no ``emb`` health block, and reads no
``serving_emb`` flags on the hot path (spy-pinned).  Satellite: live
tenant-quota reconfig (``GenScheduler.set_quotas`` + the
``sched_quotas`` wire op + the controller push, decision-logged).
"""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.core.flags import flag, set_flags
from paddle_tpu.distributed.ps import InProcClient, ParameterServer, PSClient
from paddle_tpu.io.serving import InferenceClient, InferenceServer
from paddle_tpu.serving import MetricsHub, RoutedClient, ServingController
from paddle_tpu.serving.control import InProcSpawner
from paddle_tpu.serving.scheduler import GenScheduler
from paddle_tpu.serving.sparse import EmbeddingServingTier, SparseCTRPredictor

pytestmark = pytest.mark.sparse

DIM = 8
SLOTS = 3


class _CountingPS:
    """Delegates to an InProcClient but counts versioned pulls."""

    def __init__(self, inner):
        self.inner = inner
        self.pulls = 0
        self.pulled_ids: list[np.ndarray] = []
        self.fail = False

    def pull_versioned(self, name, ids):
        if self.fail:
            raise ConnectionError("ps fleet unreachable (injected)")
        self.pulls += 1
        self.pulled_ids.append(np.asarray(ids, np.int64).copy())
        return self.inner.pull_versioned(name, ids)

    def versions(self):
        if self.fail:
            raise ConnectionError("ps fleet unreachable (injected)")
        return self.inner.versions()


def _mk_ps(seed=3):
    ps = InProcClient()
    ps.create_table("emb", DIM, optimizer="sgd", lr=0.5, seed=seed)
    return ps


@pytest.fixture
def emb_flags():
    """Enable the tier for a test; always restore the hard-off default."""
    def enable(cache_rows=256, ttl_s=0.0, batch_max=0):
        f = {"serving_emb": True, "serving_emb_cache_rows": cache_rows,
             "serving_emb_ttl_s": ttl_s}
        if batch_max:
            f.update({"serving_batch_max": batch_max,
                      "serving_batch_timeout_s": 0.02,
                      "serving_batch_min_queue": 0})
        set_flags(f)
    yield enable
    set_flags({"serving_emb": False, "serving_emb_cache_rows": 4096,
               "serving_emb_ttl_s": 0.0, "serving_batch_max": 0,
               "serving_batch_timeout_s": 0.005,
               "serving_batch_min_queue": 2})


# ---------------------------------------------------------------------------
# hot-row cache units
# ---------------------------------------------------------------------------

def test_cache_miss_dedup_then_hits():
    ps = _mk_ps()
    counting = _CountingPS(ps)
    tier = EmbeddingServingTier(counting, cache_rows=64, ttl_s=0.0)
    ids = np.array([5, 7, 5, 9, 7], np.int64)
    rows, ver = tier.lookup("emb", ids)
    assert rows.shape == (5, DIM) and ver == 0
    np.testing.assert_array_equal(rows, ps.pull("emb", ids))
    # duplicated ids were de-duplicated into ONE pull of the uniques
    assert counting.pulls == 1
    np.testing.assert_array_equal(counting.pulled_ids[0],
                                  np.array([5, 7, 9], np.int64))
    # second lookup: pure cache hits, zero pulls
    rows2, _ = tier.lookup("emb", ids)
    np.testing.assert_array_equal(rows2, rows)
    assert counting.pulls == 1
    s = tier.stats()["tables"]["emb"]
    assert s["misses"] == 3 and s["hits"] >= 3
    assert s["cached_rows"] == 3 and s["version"] == 0


def test_zipfian_stream_hit_rate_floor():
    """The CTR serving distribution (zipf a=1.3 over 50 000 ids: a
    small hot set dominates) through the default 4096-row cache, each
    caller's stream served three times over as a closed-loop load test
    does: the PS fleet is asked for every distinct row once, the
    replays pull nothing, and at least nine looked-up rows in ten are
    hot-row hits."""
    counting = _CountingPS(_mk_ps())
    tier = EmbeddingServingTier(counting, cache_rows=4096, ttl_s=0.0)
    rs = np.random.RandomState(100)
    stream = np.minimum(rs.zipf(1.3, size=(512, 8, 4)),
                        49_999).astype(np.int64)
    for rep in range(3):
        for q in stream:
            tier.lookup("emb", q)
        if rep == 0:
            pulled = sum(ids.size for ids in counting.pulled_ids)
            assert pulled == np.unique(stream).size
    assert sum(ids.size for ids in counting.pulled_ids) == pulled
    s = tier.stats()
    assert s["tables"]["emb"]["misses"] == pulled
    assert s["hit_rate"] >= 0.9, s


def test_lookup_preserves_id_shape():
    ps = _mk_ps()
    tier = EmbeddingServingTier(ps, cache_rows=64, ttl_s=0.0)
    ids = np.arange(6, dtype=np.int64).reshape(2, 3)
    rows, _ = tier.lookup("emb", ids)
    assert rows.shape == (2, 3, DIM)
    np.testing.assert_array_equal(rows.reshape(6, DIM),
                                  ps.pull("emb", ids.reshape(-1)))


def test_ttl_expiry_repulls():
    counting = _CountingPS(_mk_ps())
    tier = EmbeddingServingTier(counting, cache_rows=64, ttl_s=0.05)
    ids = np.array([1, 2], np.int64)
    tier.lookup("emb", ids)
    tier.lookup("emb", ids)                       # within TTL: hits
    assert counting.pulls == 1
    time.sleep(0.08)
    tier.lookup("emb", ids)                       # expired: re-pulled
    assert counting.pulls == 2
    assert tier.stats()["tables"]["emb"]["misses"] == 4


def test_lru_eviction_at_capacity():
    counting = _CountingPS(_mk_ps())
    tier = EmbeddingServingTier(counting, cache_rows=2, ttl_s=0.0)
    tier.lookup("emb", np.array([1], np.int64))
    tier.lookup("emb", np.array([2], np.int64))
    tier.lookup("emb", np.array([3], np.int64))   # evicts 1 (LRU)
    st = tier.stats()["tables"]["emb"]
    assert st["evictions"] == 1 and st["cached_rows"] == 2
    pulls = counting.pulls
    tier.lookup("emb", np.array([3], np.int64))   # still cached
    assert counting.pulls == pulls
    tier.lookup("emb", np.array([1], np.int64))   # evicted: re-pulled
    assert counting.pulls == pulls + 1


def test_ps_outage_serves_stale_counted_and_reraises_unknown():
    counting = _CountingPS(_mk_ps())
    tier = EmbeddingServingTier(counting, cache_rows=64, ttl_s=0.01)
    ids = np.array([4, 5], np.int64)
    warm, _ = tier.lookup("emb", ids)
    time.sleep(0.03)                              # rows now TTL-expired
    counting.fail = True
    rows, ver = tier.lookup("emb", ids)           # outage: stale fallback
    np.testing.assert_array_equal(rows, warm)
    st = tier.stats()["tables"]["emb"]
    assert st["stale_serves"] == 2 and ver == 0
    with pytest.raises(ConnectionError):          # uncached id: no fallback
        tier.lookup("emb", np.array([4, 99], np.int64))
    counting.fail = False
    tier.lookup("emb", ids)                       # recovery: pulls again
    assert tier.stats()["tables"]["emb"]["stale_serves"] == 2


# ---------------------------------------------------------------------------
# version rollover
# ---------------------------------------------------------------------------

def test_pull_reply_version_flips_generation():
    ps = _mk_ps()
    tier = EmbeddingServingTier(ps, cache_rows=64, ttl_s=0.0)
    _, v0 = tier.lookup("emb", np.array([1, 2], np.int64))
    assert v0 == 0
    assert ps.publish_version("emb") == 1
    # the next MISS pull comes back stamped v1 -> the whole response
    # (cached ids included) re-resolves at v1; nothing mixes versions
    rows, v1 = tier.lookup("emb", np.array([1, 2, 3], np.int64))
    assert v1 == 1 and rows.shape == (3, DIM)
    st = tier.stats()["tables"]["emb"]
    assert st["rollovers"] == 1 and st["version"] == 1


def test_maybe_rollover_polls_and_rate_limits():
    ps = _mk_ps()
    tier = EmbeddingServingTier(ps, cache_rows=64, ttl_s=0.0)
    tier.lookup("emb", np.array([1], np.int64))
    ps.publish_version("emb")
    assert tier.maybe_rollover() == {"emb": 1}
    assert tier.stats()["tables"]["emb"]["version"] == 1
    assert tier.maybe_rollover() is None          # rate-limited


def test_publish_version_writes_manifest_before_bump(tmp_path):
    ps = _mk_ps()
    ps.pull("emb", np.array([1, 2, 3], np.int64))
    root = str(tmp_path / "pub")
    v = ps.publish_version("emb", root=root)
    assert v == 1
    import json
    import os
    man = json.load(open(os.path.join(root, "v1", "MANIFEST.json")))
    assert man["table"] == "emb" and man["version"] == 1
    assert man["rows"] == 3 and man["shards"] == 1
    assert ps.table_version("emb") == 1


def test_tcp_publish_is_fleetwide_and_monotonic():
    s1, s2 = ParameterServer().start(), ParameterServer().start()
    try:
        c = PSClient([s1.endpoint, s2.endpoint])
        c.create_table("emb", 4, optimizer="sgd", lr=0.5, seed=9)
        ids = np.arange(8, dtype=np.int64)
        rows, ver = c.pull_versioned("emb", ids)
        assert rows.shape == (8, 4) and ver == 0
        assert c.publish_version("emb") == 1
        assert c.versions() == {"emb": 1}
        # every shard answers the new version inside pull replies too
        assert c.pull_versioned("emb", ids)[1] == 1
        # replayed publish of an older version never regresses
        for conn in c._conns:
            conn.request("publish", {"name": "emb", "version": 1})
        assert c.table_version("emb") == 1
        c.close()
    finally:
        s1.stop(), s2.stop()


# ---------------------------------------------------------------------------
# batched CTR endpoint over the wire
# ---------------------------------------------------------------------------

def _expected_scores(tier_client, pred, ids):
    """Solo reference: a fresh tier over the same PS state."""
    ref_tier = EmbeddingServingTier(tier_client, cache_rows=1024, ttl_s=0.0)
    ref = SparseCTRPredictor(ref_tier, "emb", SLOTS, emb_dim=DIM, seed=0)
    return ref.run(ids)


def test_batched_endpoint_matches_solo_and_stamps_version(emb_flags):
    emb_flags(batch_max=8)
    ps = _mk_ps()
    counting = _CountingPS(ps)
    srv = InferenceServer({})
    try:
        tier = srv.attach_embeddings(counting)
        assert tier is not None
        srv.add_model("ctr", SparseCTRPredictor(tier, "emb", SLOTS,
                                                emb_dim=DIM, seed=0))
        srv.start()
        rs = np.random.RandomState(0)
        queries = [rs.randint(0, 32, (2, SLOTS)).astype(np.int64)
                   for _ in range(6)]
        out, errs = {}, []
        gate = threading.Barrier(len(queries))

        def one(i):
            try:
                gate.wait()
                cli = InferenceClient(srv.endpoint)
                out[i] = cli.infer("ctr", queries[i])
                cli.close()
            except Exception as e:  # pragma: no cover - reporting
                errs.append((i, e))

        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(queries))]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert not errs, errs
        solo = InProcClient()
        solo.create_table("emb", DIM, optimizer="sgd", lr=0.5, seed=3)
        for i, q in enumerate(queries):
            scores, ver = out[i]
            ref_scores, _ = _expected_scores(solo, None, q)
            np.testing.assert_allclose(scores, ref_scores, rtol=1e-5,
                                       atol=1e-6)
            np.testing.assert_array_equal(
                ver, np.zeros((q.shape[0], 1), np.int64))
        # coalescing + dedup: far fewer PS pulls than requests
        assert counting.pulls <= len(queries)
        doc = srv.health()
        assert doc["emb"]["tables"]["emb"]["version"] == 0
        assert doc["emb"]["hit_rate"] >= 0.0
    finally:
        srv.stop()


def test_rollover_under_concurrent_load_single_version_per_response(
        emb_flags):
    """A trainer publish lands while the fleet serves: zero dropped
    requests, every response resolves entirely at ONE version (old
    in-flight requests finish on the old generation), and the version
    column tells which — scores always match that version's table."""
    emb_flags(batch_max=4)
    ps = _mk_ps()
    srv = InferenceServer({})
    try:
        tier = srv.attach_embeddings(ps)
        srv.add_model("ctr", SparseCTRPredictor(tier, "emb", SLOTS,
                                                emb_dim=DIM, seed=0))
        srv.start()
        q = np.arange(4 * SLOTS, dtype=np.int64).reshape(4, SLOTS)
        # warm every id at v0, then change the table AND publish: the
        # v0 cache keeps serving old values until the flip
        tier.lookup("emb", q)
        exp0, _ = _expected_scores(ps, None, q)
        g = np.random.RandomState(1).randn(
            q.size, DIM).astype(np.float32)
        ps.push_grad("emb", q.reshape(-1), g)
        fresh = InProcClient()
        fresh.create_table("emb", DIM, optimizer="sgd", lr=0.5, seed=3)
        fresh.push_grad("emb", q.reshape(-1), g)
        exp1, _ = _expected_scores(fresh, None, q)
        expected = {0: exp0, 1: exp1}

        stop, errs = threading.Event(), []
        seen = {0: 0, 1: 0}
        lock = threading.Lock()

        def hammer():
            cli = InferenceClient(srv.endpoint)
            try:
                while not stop.is_set():
                    scores, ver = cli.infer("ctr", q)
                    v = int(ver[0, 0])
                    assert (ver == v).all(), "mixed versions in response"
                    np.testing.assert_allclose(
                        scores, expected[v], rtol=1e-5, atol=1e-6)
                    with lock:
                        seen[v] += 1
            except Exception as e:  # pragma: no cover - reporting
                errs.append(e)
            finally:
                cli.close()

        ts = [threading.Thread(target=hammer) for _ in range(4)]
        [t.start() for t in ts]
        time.sleep(0.15)
        ps.publish_version("emb")                 # the trainer's push
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            doc = srv.health()                    # health tick = rollover
            if doc.get("emb", {}) \
                    .get("tables", {}).get("emb", {}) \
                    .get("version") == 1:
                break
            time.sleep(0.1)
        time.sleep(0.2)                           # serve a while on v1
        stop.set()
        [t.join() for t in ts]
        assert not errs, errs
        assert seen[0] > 0 and seen[1] > 0        # both sides observed
        st = srv.health()["emb"]
        assert st["rollovers"] == 1 and st["stale_serves"] == 0
    finally:
        srv.stop()


def test_fleet_emb_rollup_and_version_spread():
    hub = MetricsHub()
    emb_a = {"hits": 6, "misses": 2, "pulled_rows": 2, "pulled_bytes": 64,
             "stale_serves": 0, "rollovers": 1, "evictions": 0,
             "hit_rate": 0.75,
             "tables": {"emb": {"version": 1}}}
    emb_b = {"hits": 2, "misses": 2, "pulled_rows": 2, "pulled_bytes": 64,
             "stale_serves": 1, "rollovers": 0, "evictions": 0,
             "hit_rate": 0.5,
             "tables": {"emb": {"version": 0}}}
    base = {"status": "ok", "inflight": 0, "generators": {}, "stats": {}}
    hub.ingest({"a:1": dict(base, emb=emb_a),
                "b:1": dict(base, emb=emb_b),
                "c:1": dict(base)})               # no tier on c
    f = hub.fleet_emb()
    assert f["replicas"] == 2
    assert f["hit_rate"] == pytest.approx(8 / 12)
    assert f["pulled_rows"] == 4 and f["stale_serves"] == 1
    assert f["rollovers"] == 1
    # version spread > 1: a rollover is still propagating
    assert f["versions"] == {"emb": [0, 1]}
    assert MetricsHub().fleet_emb() is None       # flag off fleet-wide


# ---------------------------------------------------------------------------
# live tenant-quota reconfig (PR-18 residue satellite)
# ---------------------------------------------------------------------------

class _FakeEngine:
    def __init__(self, sched):
        self.sched = sched


def test_scheduler_set_quotas_live(monkeypatch):
    import paddle_tpu.serving.scheduler as sched_mod
    real = sched_mod.flag
    monkeypatch.setattr(
        sched_mod, "flag",
        lambda n: "a=1" if n == "gen_sched_quotas" else real(n))
    sched = GenScheduler()
    assert sched._quotas == {"a": 1.0}
    assert sched.set_quotas("a=2,b=1") == {"a": 2.0, "b": 1.0}
    assert sched._quotas == {"a": 2.0, "b": 1.0}
    # dict form; junk shares and blank names are skipped, never fatal
    assert sched.set_quotas({"x": "3", "y": "nope", "": 2, "z": -1}) \
        == {"x": 3.0}
    assert sched.set_quotas(None) == {}           # clear -> unweighted


def test_sched_quotas_wire_op(emb_flags):
    srv = InferenceServer({})
    sched = GenScheduler()
    with srv._lock:
        srv._generators["g"] = _FakeEngine(sched)
    try:
        srv.start()
        cli = InferenceClient(srv.endpoint)
        assert cli.sched_quotas({"t1": 3, "t2": 1}) == ["g"]
        assert sched._quotas == {"t1": 3.0, "t2": 1.0}
        cli.close()
    finally:
        with srv._lock:
            srv._generators.clear()
        srv.stop()
    # a scheduler-less replica answers [] rather than erroring
    bare = InferenceServer({})
    try:
        bare.start()
        cli = InferenceClient(bare.endpoint)
        assert cli.sched_quotas({"t1": 1}) == []
        cli.close()
    finally:
        bare.stop()


def test_controller_quota_push_is_decision_logged():
    srv = InferenceServer({})
    sched = GenScheduler()
    with srv._lock:
        srv._generators["g"] = _FakeEngine(sched)
    ctl = None
    try:
        srv.start()
        rc = RoutedClient([srv.endpoint], probe_interval_s=0)
        ctl = ServingController(InProcSpawner(lambda: InferenceServer({})),
                                router=rc, interval_s=0)
        applied = ctl.set_quotas({"gold": 4, "free": 1})
        assert applied == {srv.endpoint: ["g"]}
        assert sched._quotas == {"gold": 4.0, "free": 1.0}
        d = [d for d in ctl.decisions() if d["action"] == "set_quotas"][-1]
        assert d["clean"] is True
        assert d["signals"]["quotas"] == {"gold": 4.0, "free": 1.0}
        assert d["signals"]["updated"] == {srv.endpoint: ["g"]}
    finally:
        if ctl is not None:
            ctl.close(stop_replicas=False)
        with srv._lock:
            srv._generators.clear()
        srv.stop()


# ---------------------------------------------------------------------------
# hard-off defaults
# ---------------------------------------------------------------------------

def test_defaults_off_no_tier_no_hot_path_flag_reads(monkeypatch):
    """serving_emb defaults off: attach_embeddings is a None no-op, no
    tier is constructed, health ships no "emb" block, and serving reads
    no serving_emb flags past construction."""
    assert flag("serving_emb") is False
    import paddle_tpu.io.serving as io_mod
    import paddle_tpu.serving.sparse as sparse_mod

    reads: list[str] = []
    real_flag = io_mod.flag

    def spy(name):
        reads.append(name)
        return real_flag(name)

    monkeypatch.setattr(io_mod, "flag", spy)
    monkeypatch.setattr(sparse_mod, "flag", spy)

    srv = InferenceServer({})
    try:
        assert "serving_emb" in reads
        reads.clear()
        assert srv.attach_embeddings(_mk_ps()) is None
        assert srv._emb_tier is None
        srv.start()
        doc = srv.health()
        assert "emb" not in doc
        assert not [r for r in reads if r.startswith("serving_emb")]
    finally:
        srv.stop()
