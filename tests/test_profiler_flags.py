"""check_nan_inf / benchmark flag consumers + profiler timeline capture.

Reference behaviors: FLAGS_check_nan_inf per-op sweep
(``framework/details/nan_inf_utils_detail.cc:301``), FLAGS_benchmark
per-op sync (``framework/operator.cc:1123``), EnableProfiler/RecordEvent
(``platform/profiler.h:127,209``) + timeline export (``tools/timeline.py``).
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer as optim, profiler
from paddle_tpu.core import flags as flags_mod
from paddle_tpu.parallel import mesh as M


def _mlp_step(loss_fn=None):
    paddle_tpu.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
    mesh = M.create_mesh({"dp": 1}, devices=jax.devices()[:1])
    if loss_fn is None:
        def loss_fn(m, batch, training=True):
            return jnp.mean((m(batch["x"]) - batch["y"]) ** 2)
    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            model, optimizer=optim.SGD(0.1), loss_fn=loss_fn, mesh=mesh)
        state = step.init_state(model)
    batch = {"x": jnp.ones((4, 4)), "y": jnp.ones((4, 1))}
    return step, state, step.shard_batch(batch)


def test_check_nan_inf_raises_on_nonfinite():
    def bad_loss(m, batch, training=True):
        pred = m(batch["x"])
        # 0 * inf = nan enters the loss at step >= 1 via the updated params
        return jnp.mean((pred - batch["y"]) ** 2) + jnp.log(
            jnp.sum(pred) - jnp.sum(pred) - 1.0)  # log(-1) = nan

    paddle_tpu.set_flags({"check_nan_inf": True})
    try:
        step, state, batch = _mlp_step(bad_loss)
        with pytest.raises(FloatingPointError, match="check_nan_inf"):
            step(state, batch, jax.random.PRNGKey(0))
    finally:
        paddle_tpu.set_flags({"check_nan_inf": False})


def test_check_nan_inf_quiet_when_finite():
    paddle_tpu.set_flags({"check_nan_inf": True})
    try:
        step, state, batch = _mlp_step()
        state, metrics = step(state, batch, jax.random.PRNGKey(0))
        assert bool(metrics["check/grads_finite"])
        assert bool(metrics["check/params_finite"])
    finally:
        paddle_tpu.set_flags({"check_nan_inf": False})


def test_check_nan_inf_off_means_no_sweep():
    step, state, batch = _mlp_step()
    _, metrics = step(state, batch, jax.random.PRNGKey(0))
    assert not any(k.startswith("check/") for k in metrics)


def test_benchmark_flag_sync_path():
    paddle_tpu.set_flags({"benchmark": True})
    try:
        step, state, batch = _mlp_step()
        state, metrics = step(state, batch, jax.random.PRNGKey(0))
        assert np.isfinite(float(metrics["loss"]))
    finally:
        paddle_tpu.set_flags({"benchmark": False})


def test_profiler_captures_timeline(tmp_path):
    logdir = str(tmp_path / "prof")
    with profiler.profiler(logdir):
        f = jax.jit(lambda x: jnp.sin(x) @ x.T)
        jax.block_until_ready(f(jnp.ones((64, 64))))
    # a TensorBoard xplane artifact must exist (the timeline file)
    captured = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True)
    assert captured, f"no xplane capture under {logdir}"


def test_record_event_inside_and_outside_jit():
    with profiler.RecordEvent("host_span"):
        pass

    @profiler.record_function("fn_span")
    def g(x):
        with profiler.RecordEvent("inner"):
            return x * 2

    out = jax.jit(g)(jnp.ones(3))
    np.testing.assert_allclose(np.asarray(out), 2 * np.ones(3))
    # named_scope must land in the compiled HLO metadata
    hlo = jax.jit(g).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "fn_span" in hlo and "inner" in hlo


def test_named_scopes_in_train_step_hlo():
    """Phase annotations must appear in the compiled train step."""
    paddle_tpu.seed(0)
    model = nn.Linear(4, 1)
    mesh = M.create_mesh({"dp": 1}, devices=jax.devices()[:1])

    def loss_fn(m, batch, training=True):
        return jnp.mean((m(batch["x"]) - batch["y"]) ** 2)

    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            model, optimizer=optim.SGD(0.1), loss_fn=loss_fn, mesh=mesh)
        state = step.init_state(model)
        batch = {"x": jnp.ones((2, 4)), "y": jnp.ones((2, 1))}
        lowered = jax.jit(step._step_fn).lower(
            state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "forward_backward" in lowered
    assert "optimizer_update" in lowered


# ---------------------------------------------------------------------------
# stat registry / monitors (reference platform/monitor.h StatRegistry)
# ---------------------------------------------------------------------------

def test_stat_registry_counters():
    from paddle_tpu.core import monitor

    monitor.reset_stats("t/")
    monitor.stat_add("t/x", 3)
    monitor.stat_add("t/x", 2)
    monitor.stat_set("t/y", 7.5)
    assert monitor.get_stat("t/x") == 5
    exported = monitor.export_stats()
    assert exported["t/y"] == 7.5
    monitor.reset_stats("t/")
    assert monitor.get_stat("t/x") == 0


def test_train_step_increments_fleet_steps():
    from paddle_tpu.core import monitor

    monitor.reset_stats("fleet/")
    step, state, batch = _mlp_step()
    for i in range(3):
        state, _ = step(state, batch, jax.random.PRNGKey(i))
    assert monitor.get_stat("fleet/steps") == 3


def test_host_monitors():
    from paddle_tpu.core import monitor

    assert monitor.host_rss_bytes() > 10 * 1024 * 1024
    mem = monitor.device_memory_stats()
    assert isinstance(mem, dict)
