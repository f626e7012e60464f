"""Fault-tolerance layer: wire deadlines/retry/reconnect, deterministic
fault injection, checkpoint integrity + rollback, guarded training,
preemption-safe epoch loops. All CPU-only and tier-1 fast."""

import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import io, nn
from paddle_tpu.core import fault, monitor
from paddle_tpu.core.wire import FrameClient, FrameService, send_frame

pytestmark = pytest.mark.fault


@pytest.fixture(autouse=True)
def _injection_off():
    """Injection must be hard-off around every test (the production
    default) — a leaked config would poison unrelated suites."""
    fault.reset()
    yield
    fault.reset()


# ---------------------------------------------------------------------------
# fault injection registry
# ---------------------------------------------------------------------------

def _fire_pattern(seed, n=32):
    fault.configure({"x": 0.5}, seed=seed)
    out = []
    for _ in range(n):
        try:
            fault.inject("x")
            out.append(0)
        except fault.InjectedFault:
            out.append(1)
    return out


def test_injection_deterministic_per_seed():
    a, b = _fire_pattern(7), _fire_pattern(7)
    assert a == b, "same seed must reproduce the same fire pattern"
    assert 0 < sum(a) < len(a)
    assert _fire_pattern(8) != a


def test_injection_cap_stats_and_default_off():
    monitor.reset_stats("fault/")
    fault.configure("y=1.0@2", seed=0)   # flag-style spec string
    fires = 0
    for _ in range(5):
        try:
            fault.inject("y")
        except fault.InjectedFault:
            fires += 1
    assert fires == 2, "@2 caps total fires"
    assert monitor.get_stat("fault/injected/y") == 2
    assert fault.site_counts()["y"] == (5, 2)
    fault.inject("unlisted.site")        # non-spec sites never fire
    fault.reset()
    assert not fault.enabled()
    fault.inject("y")                    # off == plain no-op


# ---------------------------------------------------------------------------
# wire: deadlines, retry, reconnect, context manager
# ---------------------------------------------------------------------------

class _Echo(FrameService):
    def _dispatch(self, sock, op, header, payload):
        send_frame(sock, 0, {"echo": header.get("x")})
        return True


class _Blackhole(FrameService):
    """Accepts requests and never replies — the dead-peer hang the old
    client waited on forever."""

    def _dispatch(self, sock, op, header, payload):
        time.sleep(2.0)
        return True


def test_request_deadline_and_retry_budget():
    srv = _Blackhole().start()
    monitor.reset_stats("wire/")
    c = FrameClient(srv.endpoint, {"ping": 1}, service="test",
                    timeout=0.2, retries=1, idempotent=("ping",))
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="after 2 attempt"):
        c._request("ping", {})
    assert time.monotonic() - t0 < 2.0, "deadline bounded the hang"
    assert monitor.get_stat("wire/timeouts") >= 2
    assert monitor.get_stat("wire/retries") == 1
    c.close()
    c.close()                            # double close is safe
    with pytest.raises(ConnectionError, match="closed"):
        c._request("ping", {})
    srv.stop()


def test_frame_client_context_manager():
    srv = _Echo().start()
    with FrameClient(srv.endpoint, {"e": 1}, timeout=5.0) as c:
        h, _ = c._request("e", {"x": 5})
        assert h["echo"] == 5
    with pytest.raises(ConnectionError, match="closed"):
        c._request("e", {})
    srv.stop()


def test_injected_wire_fault_recovered_by_retry():
    srv = _Echo().start()
    monitor.reset_stats("wire/")
    monitor.reset_stats("fault/")
    c = FrameClient(srv.endpoint, {"e": 1}, timeout=5.0, retries=2,
                    idempotent=("e",))
    with fault.inject_faults({"wire.send": (1.0, 2)}, seed=1):
        h, _ = c._request("e", {"x": 1})
    assert h["echo"] == 1
    assert monitor.get_stat("fault/injected/wire.send") == 2
    assert monitor.get_stat("wire/retries") == 2
    assert monitor.get_stat("wire/reconnects") >= 1
    c.close()
    srv.stop()


def test_non_idempotent_op_fails_fast():
    srv = _Echo().start()
    monitor.reset_stats("wire/")
    c = FrameClient(srv.endpoint, {"e": 1}, timeout=5.0, retries=3)
    with fault.inject_faults({"wire.send": (1.0, 1)}, seed=1):
        with pytest.raises(ConnectionError, match="after 1 attempt"):
            c._request("e", {"x": 1})    # not in the idempotent set
    assert monitor.get_stat("wire/retries") == 0
    c.close()
    srv.stop()


def test_inference_client_survives_server_restart(tmp_path):
    """The chaos scenario: kill the serving process mid-session, bring
    it back on the same port — the client's next request reconnects and
    succeeds instead of hanging or dying."""
    paddle_tpu.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    path = str(tmp_path / "mlp")
    io.save_inference_model(path, net, [np.zeros((2, 4), np.float32)])

    srv = io.InferenceServer({"m": path}).start()
    port = srv.port
    client = io.InferenceClient(srv.endpoint, timeout=10.0)
    x = np.ones((2, 4), np.float32)
    (y1,) = client.infer("m", x)
    srv.stop()                                   # kill

    monitor.reset_stats("wire/")
    srv2 = io.InferenceServer({"m": path}, port=port).start()  # restart
    (y2,) = client.infer("m", x)                 # same client object
    np.testing.assert_allclose(y2, y1)
    assert monitor.get_stat("wire/retries") >= 1
    assert monitor.get_stat("wire/reconnects") >= 1
    client.stop_server()
    client.stop_server()                         # safe to call twice
    client.close()
    srv2.stop()


def test_wirefs_and_ps_clients_take_timeouts(tmp_path):
    from paddle_tpu.distributed.ps import ParameterServer, PSClient

    fssrv = io.FSService(str(tmp_path / "root")).start()
    wfs = io.WireFS(fssrv.endpoint, timeout=5.0)
    wfs.mkdirs("a")
    assert wfs.is_dir("a")
    with fault.inject_faults({"fs.upload": 1.0}):
        with pytest.raises(fault.InjectedFault):
            wfs.upload(__file__, "a/f")
    wfs.upload(__file__, "a/f")                  # off again: works
    assert wfs.is_file("a/f")
    wfs.close()
    fssrv.stop()

    ps = ParameterServer().start()
    c = PSClient(ps.endpoint, timeout=5.0)
    c.create_table("t", 4)
    rows = c.pull("t", np.arange(3))
    assert rows.shape == (3, 4)
    c.stop_servers()
    c.close()


# ---------------------------------------------------------------------------
# checkpoint integrity + rollback
# ---------------------------------------------------------------------------

def _tpl(v=0.0, step=0):
    return {"w": jnp.full((8, 8), float(v)), "step": jnp.asarray(int(step))}


def _corrupt_tree(path):
    """Bit-flip + truncate every substantial file under a step dir."""
    for root, _, files in os.walk(path):
        for name in files:
            p = os.path.join(root, name)
            size = os.path.getsize(p)
            if size < 8:
                continue
            with open(p, "r+b") as f:
                f.seek(size // 2)
                b = f.read(1)
                f.seek(size // 2)
                f.write(bytes([(b[0] ^ 0xFF) if b else 0xFF]))
                f.truncate(max(size // 2, 8))


def test_corrupt_latest_step_falls_back(tmp_path):
    d = str(tmp_path / "ck")
    for s in (1, 2, 3):
        io.save_checkpoint(_tpl(s, s), d, step=s)
    io.checkpoint.wait_until_finished(d)
    assert io.latest_step(d) == 3

    _corrupt_tree(os.path.join(d, "3"))
    monitor.reset_stats("ckpt/")
    restored, used = io.load_checkpoint(_tpl(), d, return_step=True)
    assert used == 2
    assert float(restored["w"][0, 0]) == 2.0 and int(restored["step"]) == 2
    assert monitor.get_stat("ckpt/rollbacks") >= 1
    assert monitor.get_stat("ckpt/corrupt_steps") >= 1
    # strict mode surfaces the corruption instead
    with pytest.raises(Exception):
        io.load_checkpoint(_tpl(), d, step=3, fallback=False)


def test_bitflip_caught_by_manifest_checksum(tmp_path):
    """A single flipped byte that still *restores* cleanly must be caught
    by the manifest crc32, not returned as silently wrong weights."""
    d = str(tmp_path / "ck")
    for s in (1, 2):
        io.save_checkpoint(_tpl(s, s), d, step=s)
    io.checkpoint.wait_until_finished(d)
    # flip one payload byte in the largest file of step 2 (no truncation)
    biggest, bsize = None, -1
    for root, _, files in os.walk(os.path.join(d, "2")):
        for name in files:
            p = os.path.join(root, name)
            if os.path.getsize(p) > bsize:
                biggest, bsize = p, os.path.getsize(p)
    with open(biggest, "r+b") as f:
        f.seek(bsize // 2)
        b = f.read(1)
        f.seek(bsize // 2)
        f.write(bytes([b[0] ^ 0x01]))
    restored, used = io.load_checkpoint(_tpl(), d, return_step=True)
    assert used == 1 and float(restored["w"][0, 0]) == 1.0


def test_epoch_range_injected_save_crash_then_resume(tmp_path):
    """Acceptance scenario: a TrainEpochRange run crashes inside a
    checkpoint save (injected ``ckpt.save`` fault). The orbax step may
    exist on disk but carries no manifest — the relaunch must resume
    from the previous verifiable step, not crash, not trust it."""
    d = str(tmp_path / "run")
    monitor.reset_stats("ckpt/")
    monitor.reset_stats("fault/")
    r = io.TrainEpochRange(6, d, state=_tpl(-1, -1))
    seen = []
    with pytest.raises(fault.InjectedFault):
        for epoch in r:
            seen.append(epoch)
            r.state = _tpl(epoch, epoch)
            if epoch == 2:   # next epoch-end save will blow up
                fault.configure({"ckpt.save": 1.0}, seed=0)
    assert seen == [0, 1, 2]
    assert monitor.get_stat("fault/injected/ckpt.save") == 1
    fault.reset()
    io.checkpoint.wait_until_finished(d)   # let step 2's async data land

    r2 = io.TrainEpochRange(6, d, state=_tpl())
    assert r2.resumed
    assert r2.start_epoch == 2, "resumes AFTER the last verifiable step"
    assert int(r2.state["step"]) == 1
    assert io.verify_step(d, 1)
    assert not io.verify_step(d, 2)


def test_train_guard_nan_rollback_on_mlp(tmp_path):
    """Loss-spike sentinel on a tiny MLP: two poisoned epochs produce
    non-finite losses; the guard blocks checkpointing the poisoned state
    and rolls back to the last good step, and training continues."""
    d = str(tmp_path / "guard")
    paddle_tpu.seed(3)
    model = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 1))
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(16, 6).astype(np.float32))
    y = jnp.asarray(rs.randn(16, 1).astype(np.float32))

    def loss_fn(m, xb, yb):
        return jnp.mean((m(xb) - yb) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    monitor.reset_stats("ckpt/")
    monitor.reset_stats("train/")

    r = io.TrainEpochRange(8, d, state=model)
    guard = io.TrainGuard(r, patience=2, max_rollbacks=1)
    bad = {4, 5}
    losses = {}
    for epoch in r:
        xb = x * jnp.nan if epoch in bad else x
        loss, g = grad_fn(r.state, xb, y)
        new_m = jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg,
                                       r.state, g)
        r.state = guard.observe(new_m, loss)
        losses[epoch] = float(loss)

    assert guard.rollbacks == 1
    assert all(np.isnan(losses[e]) for e in bad)
    assert all(np.isfinite(losses[e]) for e in losses if e not in bad)
    # the post-rollback weights are finite (poison did not survive)
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree_util.tree_leaves(r.state))
    assert monitor.get_stat("train/steps_skipped_nonfinite") == 2
    assert monitor.get_stat("train/guard_rollbacks") == 1
    assert monitor.get_stat("ckpt/rollbacks") >= 1
    assert monitor.get_stat("ckpt/saves_skipped_unhealthy") >= 1


def test_train_guard_rollback_budget_exhausted(tmp_path):
    d = str(tmp_path / "budget")
    r = io.TrainEpochRange(10, d, state=_tpl())
    guard = io.TrainGuard(r, patience=1, max_rollbacks=0)
    with pytest.raises(io.RollbackBudgetExceeded):
        for epoch in r:
            r.state = guard.observe(_tpl(epoch, epoch), float("nan"))


def test_preemption_sigterm_saves_and_exits(tmp_path):
    """SIGTERM mid-epoch: the loop finishes the epoch, persists it (even
    off the save interval), flushes the async save, and exits; a
    relaunch resumes exactly there."""
    d = str(tmp_path / "pre")
    monitor.reset_stats("train/")
    r = io.TrainEpochRange(50, d, state=_tpl(), save_interval=10)
    seen = []
    with io.PreemptionHandler(r) as h:
        for epoch in r:
            seen.append(epoch)
            r.state = _tpl(epoch, epoch)
            if epoch == 3:
                os.kill(os.getpid(), signal.SIGTERM)
    assert h.installed and h.preempted and r.stopped
    assert seen == [0, 1, 2, 3], "stopped after the preempted epoch"
    assert io.latest_step(d) == 3
    assert monitor.get_stat("train/preemptions") == 1
    assert monitor.get_stat("train/preempted_exits") == 1

    r2 = io.TrainEpochRange(50, d, state=_tpl())
    assert r2.start_epoch == 4 and int(r2.state["step"]) == 3


# ---------------------------------------------------------------------------
# monitor satellites
# ---------------------------------------------------------------------------

def test_host_rss_current_vs_peak():
    cur, peak = monitor.host_rss_bytes(), monitor.host_peak_rss_bytes()
    assert isinstance(cur, int) and isinstance(peak, int)
    assert cur > 0 and peak > 0
    # current RSS can't meaningfully exceed the lifetime peak
    assert cur <= peak * 1.05
