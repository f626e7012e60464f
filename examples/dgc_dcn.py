"""Deep gradient compression over the data-parallel axis.

Trains a tiny Llama under DGC (reference: DGCMomentumOptimizer,
``fluid/optimizer.py:1183``): dense warmup steps, a sparsity ramp, then
99%-sparse top-k gradient exchange — the configuration aimed at
multi-host data parallelism over DCN, where cutting gradient bytes
~100x is the point. The script shows the executable schedule switching
(the ``dgc_sparsity`` metric), compares against a dense-DP run, and
prints the per-step wire-byte estimate the sparse exchange implies.

On the CPU backend it self-bootstraps a virtual 8-device mesh when fewer
than 8 devices are present (the same recipe as tests/conftest.py):

    python examples/dgc_dcn.py
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

    if len(jax.devices()) >= n or os.environ.get("_PTPU_DGC_CHILD") == "1":
        return True
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"need {n} devices, the {jax.default_backend()} backend has "
            f"{len(jax.devices())}; run with JAX_PLATFORMS=cpu for the "
            "virtual mesh")
    env = dict(os.environ)
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["_PTPU_DGC_CHILD"] = "1"
    code = ("import runpy, sys; sys.argv = [sys.argv[0]] + "
            f"{sys.argv[1:]!r}; "
            f"runpy.run_path({os.path.abspath(__file__)!r}, "
            "run_name='__main__')")
    raise SystemExit(subprocess.run(
        [sys.executable, "-c", code], env=env).returncode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--sparsity", type=float, default=0.99)
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as optim
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import mesh as M

    cfg = LlamaConfig.tiny(vocab_size=512, hidden_size=128, num_layers=2,
                           num_heads=4, num_kv_heads=4, max_seq_len=64)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (16, 64)).astype(np.int32)

    def run(strategy, tag, optimizer):
        paddle_tpu.seed(7)
        model = LlamaForCausalLM(cfg)
        mesh = M.mesh_from_strategy(strategy)
        with M.MeshContext(mesh):
            step = dist.fleet.build_train_step(
                model, optimizer=optimizer, strategy=strategy, mesh=mesh)
            state = step.init_state(model)
            batch = step.shard_batch({"input_ids": jnp.asarray(ids),
                                      "labels": jnp.asarray(ids)})
            for i in range(args.steps):
                state, m = step(state, batch, jax.random.PRNGKey(i))
                sp = float(m.get("dgc_sparsity", -1.0))
                phase = ("dense" if sp == 0.0 else
                         f"sparse@{sp:.4g}" if sp > 0 else "dp")
                print(f"[{tag}] step {i:2d} loss={float(m['loss']):.4f} "
                      f"({phase})")
        return float(m["loss"])

    # DGC: 2 dense warmup steps, ramp over 4, then 99% sparse. DGC owns
    # the momentum — pair it with a plain-SGD outer optimizer.
    s = dist.DistributedStrategy()
    s.dgc.enable = True
    s.dgc.momentum = 0.9
    s.dgc.sparsity = (0.75, 0.9375, args.sparsity)
    s.dgc.rampup_begin_step = 2
    s.dgc.rampup_step = 4
    s.dgc.dense_size_threshold = 1024
    dgc_loss = run(s, "dgc", optim.SGD(3e-2))

    # dense-DP baseline with the equivalent Momentum optimizer
    dp_loss = run(dist.DistributedStrategy(), "dp",
                  optim.Momentum(3e-2, momentum=0.9))

    # wire-byte estimate at the final sparsity: each worker ships
    # (value, index) pairs for its top-k of every compressed tensor
    # instead of the dense fp32 gradient
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: LlamaForCausalLM(cfg)))
        if hasattr(l, "shape") and l.size >= s.dgc.dense_size_threshold)
    dense_bytes = n_params * 4
    sparse_bytes = int(n_params * (1 - args.sparsity)) * 8
    print(f"\nfinal loss: dgc={dgc_loss:.4f} vs dense dp={dp_loss:.4f}")
    print(f"gradient wire bytes/step/worker (compressed tensors, "
          f"{n_params/1e3:.0f}k params): dense {dense_bytes/1e6:.2f} MB "
          f"-> dgc {sparse_bytes/1e6:.3f} MB "
          f"({dense_bytes / max(sparse_bytes, 1):.0f}x less)")


if __name__ == "__main__":
    if _ensure_devices():
        main()
