"""Long-context training with ring-attention sequence parallelism.

Trains a tiny Llama with the sequence axis sharded over 4 devices
(ring attention: K/V blocks rotate around the ring while each device
holds only T/4 of the sequence) and verifies the losses match a plain
data-parallel run — the correctness contract that lets the same config
scale to sequences no single chip could hold.

On the CPU backend it self-bootstraps a virtual 8-device mesh when fewer
than 4 devices are present (the same recipe as tests/conftest.py):

    python examples/long_context_sp.py
"""

import argparse
import os
import subprocess
import sys


def _ensure_devices(n: int = 8) -> bool:
    """Re-exec on a virtual n-device CPU mesh if needed. Returns True in
    the child/ready process; the parent that delegated never returns —
    it raises SystemExit with the child's exit code. CPU backend only:
    a process that holds an accelerator with too few chips fails (a CPU
    child would hide that nothing ran on the device)."""
    import jax

    if len(jax.devices()) >= 4 or os.environ.get("_PTPU_SP_CHILD") == "1":
        return True
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"need 4 devices, the {jax.default_backend()} backend has "
            f"{len(jax.devices())}; run with JAX_PLATFORMS=cpu for the "
            "virtual mesh")
    env = dict(os.environ)
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["_PTPU_SP_CHILD"] = "1"
    code = ("import runpy, sys; sys.argv = [sys.argv[0]] + "
            f"{sys.argv[1:]!r}; "
            f"runpy.run_path({os.path.abspath(__file__)!r}, "
            "run_name='__main__')")
    raise SystemExit(subprocess.run(
        [sys.executable, "-c", code], env=env).returncode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seq", type=int, default=512)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as optim
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import mesh as M

    cfg = LlamaConfig.tiny(vocab_size=512, hidden_size=128, num_layers=2,
                           num_heads=4, num_kv_heads=4,
                           max_seq_len=args.seq)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (8, args.seq)).astype(np.int32)

    def run(strategy, tag):
        paddle_tpu.seed(7)
        model = LlamaForCausalLM(cfg)
        mesh = M.mesh_from_strategy(strategy)
        with M.MeshContext(mesh):
            step = dist.fleet.build_train_step(
                model, optimizer=optim.AdamW(1e-3), strategy=strategy,
                mesh=mesh)
            state = step.init_state(model)
            batch = step.shard_batch({"input_ids": jnp.asarray(ids),
                                      "labels": jnp.asarray(ids)})
            losses = []
            for i in range(args.steps):
                state, m = step(state, batch, jax.random.PRNGKey(i))
                losses.append(float(m["loss"]))
        print(f"{tag}: axes={dict(mesh.shape)} losses="
              f"{[round(l, 4) for l in losses]}")
        return losses

    sp = dist.DistributedStrategy()
    sp.sequence_parallel.enable = True
    sp.sequence_parallel.degree = 4
    sp.sequence_parallel.mode = "ring"
    ring = run(sp, "ring sp=4")
    ref = run(dist.DistributedStrategy(), "plain dp ")
    np.testing.assert_allclose(ring, ref, rtol=2e-4, atol=2e-5)
    print(f"OK: ring-attention losses match dense attention over "
          f"{args.steps} steps at seq {args.seq}")


if __name__ == "__main__":
    if _ensure_devices():
        main()
