"""Multi-chip Pallas dispatch: the shard_map units (``_partition``) must
run the kernels per-shard under the ambient multi-device mesh with
numerics matching the jnp reference — the analogue of the reference's
fused CUDA kernels running under the multi-device executor
(``fused/multihead_matmul_op.cu`` per device via
``framework/parallel_executor.cc:504``).

Everything runs interpreted on the virtual 8-device CPU mesh
(``_support.force_dispatch``), exactly the way the multichip dryrun
artifact exercises the path.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu
from paddle_tpu.ops.pallas import _partition, _support
from paddle_tpu.ops.pallas import norm as NORM
from paddle_tpu.ops.pallas import softmax_xent as SX
from paddle_tpu.ops.pallas import rope as RP
from paddle_tpu.parallel.mesh import MeshContext

FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture
def mesh222(devices8):
    """dp2 x fsdp2 x tp2, ambient for the test (the units plan their
    shardings from the ambient mesh)."""
    mesh = Mesh(np.array(devices8).reshape(2, 2, 2), ("dp", "fsdp", "tp"))
    with MeshContext(mesh):
        yield mesh


def put(mesh, x, *spec):
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(*spec)))


def test_partitioned_rms_and_ln(mesh222):
    rs = np.random.RandomState(0)
    x = rs.randn(512, 256).astype(np.float32)
    w = np.abs(rs.randn(256)).astype(np.float32)
    b = rs.randn(256).astype(np.float32)
    xs = put(mesh222, x, ("dp", "fsdp"), None)
    ws = put(mesh222, w, None)
    bs = put(mesh222, b, None)

    with _support.force_dispatch():
        _partition.reset_stats()

        def loss_rms(x, w):
            return jnp.sum(NORM.rms_norm(x, w, partitioned=True) ** 2)

        val, (gx, gw) = jax.jit(
            jax.value_and_grad(loss_rms, argnums=(0, 1)))(xs, ws)

        def loss_ln(x, w, b):
            return jnp.sum(NORM.layer_norm(x, w, b, partitioned=True) ** 2)

        lval, lgs = jax.jit(
            jax.value_and_grad(loss_ln, argnums=(0, 1, 2)))(xs, ws, bs)
        assert _partition.stats["rms_fwd:kernel"] > 0
        assert _partition.stats["ln_bwd:kernel"] > 0

    def ref_rms(x, w):
        rstd = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return jnp.sum((x * rstd * w) ** 2)

    rval, (rgx, rgw) = jax.value_and_grad(ref_rms, argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(float(val), float(rval), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rgx),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rgw),
                               rtol=1e-3, atol=1e-3)

    def ref_ln(x, w, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return jnp.sum(((x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b) ** 2)

    rlval, rlgs = jax.value_and_grad(ref_ln, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_allclose(float(lval), float(rlval), rtol=1e-5)
    for got, ref in zip(lgs, rlgs):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


def test_partitioned_flash_attention_gqa_head_sharded(mesh222):
    """Batch over dp, heads over tp, GQA group preserved per shard."""
    rs = np.random.RandomState(1)
    B, T, Hq, Hkv, D = 4, 128, 8, 4, 64
    q = rs.randn(B, T, Hq, D).astype(np.float32)
    k = rs.randn(B, T, Hkv, D).astype(np.float32)
    v = rs.randn(B, T, Hkv, D).astype(np.float32)
    qs = put(mesh222, q, "dp", None, "tp", None)
    ks = put(mesh222, k, "dp", None, "tp", None)
    vs = put(mesh222, v, "dp", None, "tp", None)

    with _support.force_dispatch():
        _partition.reset_stats()

        def loss(q, k, v):
            o = FA.flash_attention(q, k, v, causal=True, partitioned=True)
            return jnp.sum(o ** 2)

        val, gs = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2)))(qs, ks, vs)
        assert _partition.stats["flash_fwd:kernel"] > 0
        assert _partition.stats["flash_bwd:kernel"] > 0

    def ref(q, k, v):
        kk = jnp.repeat(k, Hq // Hkv, axis=2)
        vv = jnp.repeat(v, Hq // Hkv, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(D)
        i = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        logits = jnp.where(j <= i, logits, -1e30)
        p = jax.nn.softmax(logits, -1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, vv) ** 2)

    rval, rgs = jax.value_and_grad(ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(float(val), float(rval), rtol=1e-4)
    for got, refg in zip(gs, rgs):
        np.testing.assert_allclose(np.asarray(got), np.asarray(refg),
                                   rtol=1e-3, atol=1e-3)


def test_partitioned_xent_vocab_sharded(mesh222):
    """Megatron-style: rows over dp, vocab over tp — local lse + LSE
    combine across the vocab axes."""
    rs = np.random.RandomState(2)
    N, V = 256, 512
    logits = rs.randn(N, V).astype(np.float32)
    labels = rs.randint(0, V, (N,)).astype(np.int32)
    ls = put(mesh222, logits, "dp", "tp")
    ys = put(mesh222, labels, "dp")

    with _support.force_dispatch():
        _partition.reset_stats()

        def loss(lg, lb):
            return jnp.sum(SX.softmax_cross_entropy(lg, lb, partitioned=True))

        val, g = jax.jit(jax.value_and_grad(loss))(ls, ys)
        assert _partition.stats["xent_lse:kernel"] > 0
        assert _partition.stats["xent_dx:kernel"] > 0

    def ref(lg, lb):
        lp = jax.nn.log_softmax(lg, -1)
        return -jnp.sum(jnp.take_along_axis(lp, lb[:, None], 1))

    rval, rg = jax.value_and_grad(ref)(jnp.asarray(logits),
                                       jnp.asarray(labels))
    np.testing.assert_allclose(float(val), float(rval), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                               rtol=1e-4, atol=1e-5)


def test_partitioned_rope_seq_sharded(devices8):
    """Sequence sharded over sp: the cos/sin tables shard with it so
    every shard rotates by its own absolute positions."""
    mesh = Mesh(np.array(devices8).reshape(2, 2, 2), ("dp", "sp", "tp"))
    rs = np.random.RandomState(3)
    x = rs.randn(4, 256, 4, 64).astype(np.float32)
    ang = np.arange(256)[:, None] * (0.1 + np.arange(32)[None, :] / 32)
    cos = np.cos(ang).astype(np.float32)
    sin = np.sin(ang).astype(np.float32)
    xs = put(mesh, x, "dp", "sp", None, None)
    cs = put(mesh, cos, "sp", None)
    ss = put(mesh, sin, "sp", None)

    with MeshContext(mesh), _support.force_dispatch():
        _partition.reset_stats()

        def loss(x, c, s):
            return jnp.sum(RP.apply_rotary(x, c, s, partitioned=True) ** 2)

        val, g = jax.jit(jax.value_and_grad(loss))(xs, cs, ss)
        assert _partition.stats["rope:kernel"] > 0

    def ref(x, c, s):
        x1, x2 = x[..., :32], x[..., 32:]
        c = c[None, :, None, :]
        s = s[None, :, None, :]
        return jnp.sum(
            jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1) ** 2)

    rval, rg = jax.value_and_grad(ref)(jnp.asarray(x), jnp.asarray(cos),
                                       jnp.asarray(sin))
    np.testing.assert_allclose(float(val), float(rval), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                               rtol=1e-4, atol=1e-4)


def test_partitioned_misaligned_shard_falls_back(mesh222):
    """A shard whose row count breaks kernel block alignment must take the
    per-shard jnp fallback and stay correct (not crash, not gather)."""
    rs = np.random.RandomState(4)
    # 8-way row sharding of 72 rows -> 9 rows/shard: not sublane-aligned
    x = rs.randn(72, 256).astype(np.float32)
    w = np.abs(rs.randn(256)).astype(np.float32)
    mesh = Mesh(np.array(mesh222.devices).reshape(8), ("dp",))
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("dp", None)))
    ws = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P(None)))

    with MeshContext(mesh), _support.force_dispatch():
        _partition.reset_stats()
        y = jax.jit(lambda x, w: NORM.rms_norm(x, w, partitioned=True))(
            xs, ws)
        assert _partition.stats["rms_fwd:fallback"] > 0

    rstd = 1.0 / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(y), x * rstd * w,
                               rtol=1e-5, atol=1e-5)


def test_fleet_zero3_tp_kernels_match_jnp_losses(devices8):
    """VERDICT r2 'done when': under zero3×tp the Pallas kernel path must
    reproduce the jnp-path losses on the virtual mesh."""
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as optim
    from paddle_tpu.core.strategy import DistributedStrategy
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import mesh as M

    cfg = LlamaConfig.tiny(vocab_size=512, hidden_size=128, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=128)
    rs = np.random.RandomState(7)
    ids = rs.randint(0, 512, (8, 128)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)}

    def run(kernels: bool):
        paddle_tpu.seed(42)
        s = DistributedStrategy()
        s.sharding.enable = True
        s.sharding.stage = 3
        s.sharding.degree = 2
        s.tensor_parallel.enable = True
        s.tensor_parallel.degree = 2
        model = LlamaForCausalLM(cfg)
        mesh = M.mesh_from_strategy(s)
        losses = []
        with M.MeshContext(mesh):
            opt = optim.AdamW(1e-2)
            step = dist.fleet.build_train_step(model, optimizer=opt,
                                               strategy=s, mesh=mesh)
            state = step.init_state(model)
            sbatch = step.shard_batch(batch)
            if kernels:
                with _support.force_dispatch():
                    _partition.reset_stats()
                    for i in range(3):
                        state, metrics = step(state, sbatch,
                                              jax.random.PRNGKey(i))
                        losses.append(float(metrics["loss"]))
                    assert _partition.stats["flash_fwd:kernel"] > 0, \
                        dict(_partition.stats)
            else:
                for i in range(3):
                    state, metrics = step(state, sbatch,
                                          jax.random.PRNGKey(i))
                    losses.append(float(metrics["loss"]))
        return losses

    l_kernel = run(True)
    l_jnp = run(False)
    np.testing.assert_allclose(l_kernel, l_jnp, rtol=5e-4, atol=5e-5)


def test_ulysses_uses_raw_kernel_inside_shard_map(devices8):
    """Inside the fully-manual Ulysses shard_map the dispatch gate goes
    'raw' — flash runs on local head-sharded shapes — and the result still
    matches dense attention."""
    from paddle_tpu.parallel.ring_attention import ulysses_self_attention
    import paddle_tpu.nn.functional as F

    mesh = Mesh(np.array(devices8).reshape(8), ("sp",))
    rs = np.random.RandomState(5)
    q = rs.randn(2, 1024, 8, 64).astype(np.float32)
    k = rs.randn(2, 1024, 8, 64).astype(np.float32)
    v = rs.randn(2, 1024, 8, 64).astype(np.float32)
    qj, kj, vj = map(jnp.asarray, (q, k, v))

    with _support.force_dispatch():
        out = jax.jit(lambda q, k, v: ulysses_self_attention(
            q, k, v, mesh, axis="sp", causal=True))(qj, kj, vj)

    ref = F.scaled_dot_product_attention(qj, kj, vj, causal=True,
                                         use_pallas="never")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
