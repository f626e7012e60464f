"""Pallas selective-scan kernel (Mamba recurrence): numerics vs the XLA
formulation in ``models/mamba.py`` (the spec), finite-difference gradient
checks in interpret mode (the OpTest pattern,
reference ``tests/unittests/op_test.py:1324``), and the partitioned
multi-chip path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import importlib

from paddle_tpu.models.mamba import selective_scan as ref_scan
from paddle_tpu.ops.pallas import _partition, _support

SS = importlib.import_module("paddle_tpu.ops.pallas.selective_scan")


def make_inputs(Bsz=2, T=32, Ei=128, N=8, seed=0):
    rs = np.random.RandomState(seed)
    u = rs.randn(Bsz, T, Ei).astype(np.float32)
    delta = (np.abs(rs.randn(Bsz, T, Ei)) * 0.1).astype(np.float32)
    A = -np.abs(rs.randn(Ei, N)).astype(np.float32)
    B = rs.randn(Bsz, T, N).astype(np.float32)
    C = rs.randn(Bsz, T, N).astype(np.float32)
    D = rs.randn(Ei).astype(np.float32)
    return tuple(map(jnp.asarray, (u, delta, A, B, C, D)))


def test_forward_matches_reference():
    args = make_inputs()
    assert SS.supported(*args, chunk=8)
    with _support.force_interpret():
        y = SS.selective_scan(*args, chunk=8)
    yr = ref_scan(*args, chunk_size=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


def test_single_chunk_and_multi_chunk_agree():
    args = make_inputs(T=16)
    with _support.force_interpret():
        y1 = SS.selective_scan(*args, chunk=16)   # one chunk
        y2 = SS.selective_scan(*args, chunk=8)    # two chunks + carry
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_reference():
    """All six input gradients against jax.grad of the XLA spec. Ei=256
    (two lane blocks) so cross-channel-block reductions of dB/dC are
    exercised — Ei=128 hides an overwrite across the channel grid dim."""
    args = make_inputs(Ei=256)

    def loss_k(*a):
        return jnp.sum(SS.selective_scan(*a, chunk=8) ** 2)

    def loss_r(*a):
        return jnp.sum(ref_scan(*a, chunk_size=8) ** 2)

    with _support.force_interpret():
        gk = jax.grad(loss_k, argnums=tuple(range(6)))(*args)
    gr = jax.grad(loss_r, argnums=tuple(range(6)))(*args)
    for name, a, b in zip("u delta A B C D".split(), gk, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-8
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 1e-4, (name, err)


def test_finite_difference_gradients():
    """Direct FD check of the custom VJP (scoped x64 would change the
    kernel dtype gate, so FD runs in f32 with loose tolerance on a tiny
    problem)."""
    args = make_inputs(Bsz=1, T=8, Ei=128, N=8)

    def loss(*a):
        return jnp.sum(SS.selective_scan(*a, chunk=8) ** 2)

    with _support.force_interpret():
        grads = jax.grad(loss, argnums=(2, 5))(*args)  # A and D
        eps = 1e-2
        for argnum, g in zip((2, 5), grads):
            x = np.asarray(args[argnum])
            g = np.asarray(g)
            # probe where the gradient is largest so f32 FD can resolve it
            idx = np.unravel_index(np.argmax(np.abs(g)), g.shape)
            fd_vals = []
            for sign in (+1, -1):
                xp = x.copy()
                xp[idx] += sign * eps
                pert = list(args)
                pert[argnum] = jnp.asarray(xp)
                fd_vals.append(float(loss(*pert)))
            fd = (fd_vals[0] - fd_vals[1]) / (2 * eps)  # central difference
            an = float(g[idx])
            assert abs(fd - an) / (abs(an) + 1e-6) < 5e-2, (argnum, fd, an)


def test_mamba_block_dispatches_kernel(monkeypatch):
    """The model integration: MambaBlock must route through the kernel
    when the gate is open and reproduce the XLA-path output."""
    from paddle_tpu.models.mamba import MambaConfig, MambaForCausalLM
    import paddle_tpu

    cfg = MambaConfig.tiny(hidden_size=64, state_size=8, num_layers=2,
                           scan_chunk_size=8)
    paddle_tpu.seed(0)
    model = MambaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 16)),
                      jnp.int32)
    ref = model(ids)
    with _support.force_dispatch():
        _partition.reset_stats()
        out = model(ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_partitioned_selective_scan(devices8):
    """Batch over dp and channels over tp: the shard_map path must
    match the reference with grads."""
    from paddle_tpu.parallel.mesh import MeshContext
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
    args = make_inputs(Bsz=4, T=16, Ei=256, N=8)
    u = jax.device_put(args[0], NamedSharding(mesh, P("dp", None, "tp")))
    rest = args[1:]

    def loss_k(u, *a):
        return jnp.sum(SS.selective_scan(u, *a, chunk=8,
                                         partitioned=True) ** 2)

    grad_args = tuple(range(6))  # incl. dB/dC: channel-sharded partials
    with MeshContext(mesh), _support.force_dispatch():
        _partition.reset_stats()
        val, gs = jax.jit(jax.value_and_grad(
            loss_k, argnums=grad_args))(u, *rest)
        assert _partition.stats["selective_scan_fwd:kernel"] > 0
        assert _partition.stats["selective_scan_bwd:kernel"] > 0

    def loss_r(u, *a):
        return jnp.sum(ref_scan(u, *a, chunk_size=8) ** 2)

    rval, rgs = jax.value_and_grad(loss_r, argnums=grad_args)(*args)
    np.testing.assert_allclose(float(val), float(rval), rtol=1e-4)
    for name, got, ref in zip("u delta A B C D".split(), gs, rgs):
        scale = float(jnp.max(jnp.abs(ref))) + 1e-8
        err = float(jnp.max(jnp.abs(got - ref))) / scale
        assert err < 1e-3, (name, err)


def test_mamba_stateful_decode_matches_parallel_scan():
    """The recurrent O(1)-per-token decode path (init_cache /
    forward_with_cache) must reproduce the parallel-scan forward:
    prefill logits, teacher-forced stepwise logits, and the
    prefill→step state handoff all match."""
    import paddle_tpu
    from paddle_tpu.models import MambaConfig, MambaForCausalLM

    paddle_tpu.seed(0)
    cfg = MambaConfig.tiny(vocab_size=96, hidden_size=32, num_layers=2,
                           state_size=8)
    m = MambaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 96, (2, 12))
                      .astype(np.int32))
    full = np.asarray(m(ids))

    pre, cache_p = m.forward_with_cache(ids, m.init_cache(2))
    np.testing.assert_allclose(np.asarray(pre), full, rtol=2e-4,
                               atol=1e-5)

    cache = m.init_cache(2)
    steps = []
    for t in range(ids.shape[1]):
        lg, cache = m.forward_with_cache(ids[:, t:t + 1], cache)
        steps.append(np.asarray(lg[:, 0]))
    np.testing.assert_allclose(np.stack(steps, axis=1), full, rtol=2e-3,
                               atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(cache_p),
                    jax.tree_util.tree_leaves(cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-4)


def test_mamba_generate_runs_jitted():
    import paddle_tpu
    from paddle_tpu.models import MambaConfig, MambaForCausalLM
    from paddle_tpu.models.generation import generate

    paddle_tpu.seed(1)
    cfg = MambaConfig.tiny(vocab_size=64, hidden_size=32, num_layers=2,
                           state_size=8)
    m = MambaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 6))
                      .astype(np.int32))
    out = np.asarray(jax.jit(lambda mm, i: generate(mm, i, 8))(m, ids))
    assert out.shape == (2, 14)
    assert (out[:, :6] == np.asarray(ids)).all()


def test_mamba_prefill_short_prompt_pads_conv_tail():
    """Prompt shorter than the conv kernel: the conv tail zero-pads and
    continued stepping still matches the full parallel forward."""
    import paddle_tpu
    from paddle_tpu.models import MambaConfig, MambaForCausalLM

    paddle_tpu.seed(2)
    cfg = MambaConfig.tiny(vocab_size=64, hidden_size=32, num_layers=2,
                           state_size=8, conv_kernel=4)
    m = MambaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 64, (1, 5))
                      .astype(np.int32))
    # prefill only the first 2 tokens (< K-1), then step the rest
    _, cache = m.forward_with_cache(ids[:, :2], m.init_cache(1))
    outs = []
    for t in range(2, 5):
        lg, cache = m.forward_with_cache(ids[:, t:t + 1], cache)
        outs.append(np.asarray(lg[:, 0]))
    full = np.asarray(m(ids))
    np.testing.assert_allclose(np.stack(outs, axis=1), full[:, 2:],
                               rtol=2e-3, atol=1e-4)


def test_mamba_chunked_prefill_continuation_exact():
    """Warm-cache multi-token prefill (the Llama-contract pattern of
    appending T>1 chunks) must be exact: prefilling a prompt in two
    chunks equals one-shot prefill — logits AND carried state."""
    import paddle_tpu
    from paddle_tpu.models import MambaConfig, MambaForCausalLM

    paddle_tpu.seed(3)
    # scan_chunk_size=4 with T=16/9/7 chunks: the 16-token one-shot
    # prefill AND the 9/7 split both exercise selective_scan's CHUNKED
    # branch with initial_state/return_state (chunked when divisible,
    # unchunked otherwise) against each other
    cfg = MambaConfig.tiny(vocab_size=64, hidden_size=32, num_layers=2,
                           state_size=8, conv_kernel=4,
                           scan_chunk_size=4)
    m = MambaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 64, (2, 16))
                      .astype(np.int32))
    one_lg, one_cache = m.forward_with_cache(ids, m.init_cache(2))

    lg_a, cache = m.forward_with_cache(ids[:, :7], m.init_cache(2))
    lg_b, cache = m.forward_with_cache(ids[:, 7:], cache)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(lg_a), np.asarray(lg_b)], axis=1),
        np.asarray(one_lg), rtol=2e-3, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(one_cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-4)


def test_mamba_decode_conv_kernel_one():
    """conv_kernel=1 (no temporal conv): the carried tail is an empty
    [B, 0, Ei] slice — a -(K-1) slice bug would silently return the
    whole sequence and corrupt every subsequent step."""
    import paddle_tpu
    from paddle_tpu.models import MambaConfig, MambaForCausalLM

    paddle_tpu.seed(4)
    cfg = MambaConfig.tiny(vocab_size=64, hidden_size=32, num_layers=2,
                           state_size=8, conv_kernel=1)
    m = MambaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 64, (2, 6))
                      .astype(np.int32))
    full = np.asarray(m(ids))
    _, cache = m.forward_with_cache(ids[:, :4], m.init_cache(2))
    assert jax.tree_util.tree_leaves(cache)[0].shape[2] == 0
    outs = []
    for t in range(4, 6):
        lg, cache = m.forward_with_cache(ids[:, t:t + 1], cache)
        outs.append(np.asarray(lg[:, 0]))
    np.testing.assert_allclose(np.stack(outs, axis=1), full[:, 4:],
                               rtol=2e-3, atol=1e-4)
