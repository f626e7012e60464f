"""Multi-process worker for launcher tests (the dist_mnist.py pattern:
reference ``tests/unittests/dist_mnist.py`` driven by test_dist_base.py).

Run under ``python -m paddle_tpu.distributed.launch --nproc N``; trains a
tiny model data-parallel across N *processes* (1 CPU device each) and
writes its loss curve to ``$TOY_OUT/losses.<rank>.json``.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # exactly one CPU device per process

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer as optim
from paddle_tpu.parallel import mesh as M


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "train"
    env = dist.init_parallel_env()

    if mode == "crash":
        # rank-1 dies; rank 0 would run forever — the launcher must tear
        # it down (watch_local_trainers behavior)
        if env.rank == 1:
            # hard exit: sys.exit would block in jax's atexit distributed-
            # shutdown barrier waiting for rank 0 (which is asleep) — a
            # real trainer crash doesn't run atexit either
            os._exit(3)
        import time
        time.sleep(300)
        return

    paddle_tpu.seed(0)
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 1))
    mesh = M.create_mesh({"dp": jax.device_count()})

    rs = np.random.RandomState(0)
    x = rs.randn(16, 8).astype(np.float32)
    w_true = rs.randn(8, 1).astype(np.float32)
    y = x @ w_true

    def loss_fn(m, batch, training=True):
        pred = m(batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    with M.MeshContext(mesh):
        step = dist.fleet.build_train_step(
            model, optimizer=optim.SGD(0.05), loss_fn=loss_fn, mesh=mesh)
        state = step.init_state(model)
        batch = step.shard_batch({"x": jnp.asarray(x), "y": jnp.asarray(y)})
        losses = []
        for i in range(8):
            state, metrics = step(state, batch, jax.random.PRNGKey(i))
            losses.append(float(metrics["loss"]))

    out_dir = os.environ.get("TOY_OUT", ".")
    with open(os.path.join(out_dir, f"losses.{env.rank}.json"), "w") as f:
        json.dump(losses, f)


if __name__ == "__main__":
    main()


def spawn_worker(out_dir):
    """Module-level worker for distributed.spawn tests."""
    env = dist.init_parallel_env()
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    f = jax.jit(shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                          in_specs=P(), out_specs=P()))
    out = f(jnp.asarray([1.0 * (env.rank + 1)]))
    # replicated psum: every rank sees sum over ranks
    with open(os.path.join(out_dir, f"spawn.{env.rank}.txt"), "w") as fh:
        fh.write(str(float(np.asarray(jax.device_get(out))[0])))
