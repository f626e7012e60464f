"""Fused LM-head ⊗ cross-entropy kernel (ops/pallas/linear_xent).

OpTest-style (reference ``tests/unittests/op_test.py:226``): outputs and
custom_vjp gradients of the Pallas kernels (interpret mode on CPU) vs a
dense jnp reference; the chunked pure-XLA variant against the same
reference; the F.linear_cross_entropy dispatch surface (padding,
ignore_index, reductions); and the restructured llama loss path
(full-T rows with left-shifted labels) vs the sliced dense formulation.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F

LX = importlib.import_module("paddle_tpu.ops.pallas.linear_xent")


def dense_ref(h, w, labels):
    """Per-row lse − selected-logit; out-of-range labels select 0."""
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=1)
    v = w.shape[1]
    safe = jnp.clip(labels, 0, v - 1)
    sel = jnp.take_along_axis(logits, safe[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    sel = jnp.where((labels >= 0) & (labels < v), sel, 0.0)
    return lse - sel


@pytest.mark.parametrize("n,e,v", [
    (24, 128, 384),     # n < row block (sublane-aligned)
    (256, 128, 256),    # exactly one row block
    (512, 256, 1280),   # multiple row and vocab blocks
])
def test_fused_matches_dense(n, e, v):
    rs = np.random.RandomState(0)
    h = jnp.asarray(rs.randn(n, e).astype(np.float32))
    w = jnp.asarray(0.1 * rs.randn(e, v).astype(np.float32))
    labels = jnp.asarray(rs.randint(0, v, n).astype(np.int32))
    labels = labels.at[1].set(-100)   # ignore-style out-of-range row
    assert LX.supported(h, w, labels)

    out = LX.fused_linear_cross_entropy(h, w, labels)
    ref = dense_ref(h, w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    mask = (labels >= 0).astype(jnp.float32)

    def loss_fused(h, w):
        per = LX.fused_linear_cross_entropy(h, w, labels)
        return jnp.sum(per * mask) / jnp.sum(mask)

    def loss_dense(h, w):
        return jnp.sum(dense_ref(h, w, labels) * mask) / jnp.sum(mask)

    gf = jax.grad(loss_fused, (0, 1))(h, w)
    gd = jax.grad(loss_dense, (0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gd[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gd[1]),
                               rtol=1e-4, atol=1e-5)


def test_chunked_matches_dense():
    rs = np.random.RandomState(1)
    n, e, v = 40, 64, 640
    h = jnp.asarray(rs.randn(n, e).astype(np.float32))
    w = jnp.asarray(0.1 * rs.randn(e, v).astype(np.float32))
    labels = jnp.asarray(rs.randint(0, v, n).astype(np.int32))

    out = LX.chunked_linear_cross_entropy(h, w, labels, block_v=128)
    ref = dense_ref(h, w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def loss_c(h, w):
        return jnp.mean(LX.chunked_linear_cross_entropy(h, w, labels,
                                                        block_v=128))

    def loss_d(h, w):
        return jnp.mean(dense_ref(h, w, labels))

    gc = jax.grad(loss_c, (0, 1))(h, w)
    gd = jax.grad(loss_d, (0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gc[0]), np.asarray(gd[0]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gc[1]), np.asarray(gd[1]),
                               rtol=1e-4, atol=1e-6)


def test_ignored_rows_have_zero_grad():
    rs = np.random.RandomState(2)
    n, e, v = 32, 128, 256
    h = jnp.asarray(rs.randn(n, e).astype(np.float32))
    w = jnp.asarray(0.1 * rs.randn(e, v).astype(np.float32))
    labels = jnp.asarray(rs.randint(0, v, n).astype(np.int32))
    labels = labels.at[:8].set(-100)

    def loss(h):
        return F.linear_cross_entropy(h, w, labels, mode="fused")

    g = jax.grad(loss)(h)
    np.testing.assert_allclose(np.asarray(g[:8]), 0.0, atol=1e-12)
    assert float(jnp.max(jnp.abs(g[8:]))) > 0.0


def test_row_padding_path():
    """n = 44 is sublane-misaligned ((-44) % 8 == 4): the dispatch must
    pad rows, and gradients must flow correctly through the [:n] slice
    (padded rows are ignore-masked, so they contribute nothing)."""
    rs = np.random.RandomState(7)
    n, e, v = 44, 128, 256
    h = jnp.asarray(rs.randn(n, e).astype(np.float32))
    w = jnp.asarray(0.1 * rs.randn(e, v).astype(np.float32))
    labels = jnp.asarray(rs.randint(0, v, n).astype(np.int32))

    def loss_f(h, w):
        return F.linear_cross_entropy(h, w, labels, mode="fused")

    def loss_d(h, w):
        return F.cross_entropy((h @ w).astype(jnp.float32), labels)

    np.testing.assert_allclose(float(loss_f(h, w)), float(loss_d(h, w)),
                               rtol=1e-5)
    gf = jax.grad(loss_f, (0, 1))(h, w)
    gd = jax.grad(loss_d, (0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gd[0]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gd[1]),
                               rtol=1e-4, atol=1e-6)


def test_unknown_mode_raises():
    h = jnp.zeros((8, 128), jnp.float32)
    w = jnp.zeros((128, 256), jnp.float32)
    lab = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match="unknown mode"):
        F.linear_cross_entropy(h, w, lab, mode="Fused")


@pytest.mark.parametrize("mode", ["fused", "chunked", "dense"])
def test_functional_modes_agree(mode):
    rs = np.random.RandomState(3)
    b, t, e, v = 2, 20, 128, 256
    h = jnp.asarray(rs.randn(b, t, e).astype(np.float32))
    w = jnp.asarray(0.1 * rs.randn(e, v).astype(np.float32))
    labels = jnp.asarray(rs.randint(0, v, (b, t)).astype(np.int32))
    labels = labels.at[0, :3].set(-100)

    ref_logits = (h.reshape(-1, e) @ w).astype(jnp.float32)
    want = F.cross_entropy(ref_logits, labels.reshape(-1))
    got = F.linear_cross_entropy(h, w, labels, mode=mode)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    got_sum = F.linear_cross_entropy(h, w, labels, mode=mode,
                                     reduction="sum")
    want_sum = F.cross_entropy(ref_logits, labels.reshape(-1),
                               reduction="sum")
    np.testing.assert_allclose(float(got_sum), float(want_sum),
                               rtol=1e-5)

    got_none = F.linear_cross_entropy(h, w, labels, mode=mode,
                                      reduction="none")
    assert got_none.shape == labels.shape


def test_llama_loss_fused_path_matches_dense():
    """The restructured loss (full-T rows, left-shifted labels, final
    position ignore-masked) must equal the dense sliced formulation."""
    import dataclasses

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    import paddle_tpu
    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=64, num_layers=2)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(4)
    ids = jnp.asarray(rs.randint(0, 256, (2, 16)).astype(np.int32))

    dense = model.loss(ids, ids, training=False)
    model.config = dataclasses.replace(cfg, lm_head_mode="chunked")
    fused = model.loss(ids, ids, training=False)
    np.testing.assert_allclose(float(fused), float(dense), rtol=1e-5)


class TestPartitioned:
    """shard_map dispatch on the virtual 8-device mesh: rows
    sharded over (dp, fsdp), vocab sharded Megatron-style over tp —
    numerics must match the unsharded dense reference, and the kernel
    (not the fallback) must have lowered when shapes align."""

    @pytest.fixture
    def mesh(self, devices8):
        from jax.sharding import Mesh
        from paddle_tpu.parallel.mesh import MeshContext
        mesh = Mesh(np.array(devices8).reshape(2, 2, 2),
                    ("dp", "fsdp", "tp"))
        with MeshContext(mesh):       # the units plan from the ambient mesh
            yield mesh

    @pytest.mark.parametrize("aligned", [True, False])
    def test_vocab_sharded_matches_dense(self, mesh, aligned):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.pallas import _partition, _support

        rs = np.random.RandomState(0)
        # aligned: local shards stay kernel-tileable; misaligned (e=120)
        # must take the jnp fallback with identical numerics
        n, e, v = (512, 128, 512) if aligned else (512, 120, 512)
        h = rs.randn(n, e).astype(np.float32)
        w = (0.1 * rs.randn(e, v)).astype(np.float32)
        labels = rs.randint(0, v, n).astype(np.int32)
        labels[:5] = -100

        hs = jax.device_put(jnp.asarray(h),
                            NamedSharding(mesh, P(("dp", "fsdp"), None)))
        ws = jax.device_put(jnp.asarray(w),
                            NamedSharding(mesh, P(None, "tp")))
        lab = jnp.asarray(labels)

        with _support.force_dispatch():
            _partition.reset_stats()

            def loss(h, w):
                per = LX.fused_linear_cross_entropy(h, w, lab,
                                                    partitioned=True)
                mask = (lab >= 0).astype(jnp.float32)
                return jnp.sum(per * mask) / jnp.sum(mask)

            val, (gh, gw) = jax.jit(
                jax.value_and_grad(loss, (0, 1)))(hs, ws)
            key = "kernel" if aligned else "fallback"
            assert _partition.stats[f"flce_fwd:{key}"] > 0
            assert _partition.stats[f"flce_dh:{key}"] > 0
            assert _partition.stats[f"flce_dw:{key}"] > 0

        mask = (jnp.asarray(labels) >= 0).astype(jnp.float32)

        def ref(h, w):
            per = dense_ref(h, w, jnp.asarray(labels))
            return jnp.sum(per * mask) / jnp.sum(mask)

        rval, (rgh, rgw) = jax.value_and_grad(ref, (0, 1))(
            jnp.asarray(h), jnp.asarray(w))
        np.testing.assert_allclose(float(val), float(rval), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(rgh),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rgw),
                                   rtol=1e-4, atol=1e-5)


def test_gpt_loss_fused_path_matches_dense():
    import dataclasses

    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    import paddle_tpu
    paddle_tpu.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32, dtype="float32",
                    remat=False)
    model = GPTForCausalLM(cfg)
    rs = np.random.RandomState(5)
    ids = jnp.asarray(rs.randint(0, 256, (2, 16)).astype(np.int32))
    dense = model.loss(ids, ids, training=False)
    model.config = dataclasses.replace(cfg, lm_head_mode="chunked")
    fused = model.loss(ids, ids, training=False)
    np.testing.assert_allclose(float(fused), float(dense), rtol=1e-5)


def test_mamba_tied_loss_fused_path_matches_dense():
    """Tied-embedding models route the fused path through the
    transposed table."""
    import dataclasses

    from paddle_tpu.models import MambaConfig, MambaForCausalLM

    import paddle_tpu
    paddle_tpu.seed(0)
    cfg = MambaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      dtype="float32", scan_chunk_size=None)
    model = MambaForCausalLM(cfg)
    rs = np.random.RandomState(6)
    ids = jnp.asarray(rs.randint(0, 256, (2, 16)).astype(np.int32))
    dense = model.loss(ids, ids, training=False)
    model.config = dataclasses.replace(cfg, lm_head_mode="chunked")
    fused = model.loss(ids, ids, training=False)
    np.testing.assert_allclose(float(fused), float(dense), rtol=1e-5)


def test_supported_gates():
    h = jnp.zeros((24, 128), jnp.float32)
    w = jnp.zeros((128, 384), jnp.float32)
    lab = jnp.zeros((24,), jnp.int32)
    assert LX.supported(h, w, lab)
    # misaligned E
    assert not LX.supported(jnp.zeros((24, 100)), jnp.zeros((100, 384)), lab)
    # vocab with no 128-multiple divisor tile
    assert not LX.supported(h, jnp.zeros((128, 200)), lab)
    # row count not sublane-aligned
    assert not LX.supported(jnp.zeros((25, 128)), w,
                            jnp.zeros((25,), jnp.int32))
    # dtype mismatch
    assert not LX.supported(h.astype(jnp.bfloat16), w, lab)
