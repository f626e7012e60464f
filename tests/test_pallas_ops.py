"""OpTest-style checks for the Pallas kernel set (paddle_tpu.ops.pallas).

Strategy (reference ``tests/unittests/op_test.py:226`` pattern):
- outputs: kernel (interpret mode on CPU) vs the jnp reference
  implementation, elementwise;
- gradients: kernel's custom_vjp vs jax.grad of the jnp reference —
  the jnp references themselves are FD-checked (tests/test_nn.py via
  tests/op_test.py), so this chains to finite differences;
- plus one direct FD check on the cheapest kernel (rms_norm) to anchor
  the chain.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
from tests import op_test

FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
NORM = importlib.import_module("paddle_tpu.ops.pallas.norm")
SX = importlib.import_module("paddle_tpu.ops.pallas.softmax_xent")
ROPE = importlib.import_module("paddle_tpu.ops.pallas.rope")


def ref_attention(q, k, v, causal):
    return F.scaled_dot_product_attention(q, k, v, causal=causal,
                                          use_pallas="never")


@pytest.mark.parametrize("B,T,Hq,Hkv,D,causal", [
    (2, 256, 4, 4, 64, True),
    (1, 256, 4, 2, 128, True),    # GQA
    (2, 128, 2, 2, 64, False),
    (1, 512, 2, 1, 64, True),     # MQA, multiple q/k blocks
])
def test_flash_attention_matches_dense(B, T, Hq, Hkv, D, causal):
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, T, Hq, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, T, Hkv, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, T, Hkv, D).astype(np.float32))
    assert FA.supported(q, k, v, causal=causal)

    out = FA.flash_attention(q, k, v, causal=causal)
    ref = ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def loss_fa(q, k, v):
        return jnp.sum(jnp.sin(FA.flash_attention(q, k, v, causal=causal)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref_attention(q, k, v, causal)))

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_attention_decode_shape():
    """Tq < Tk (decode with cache): causal offset must align diagonals."""
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(1, 128, 2, 64).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 256, 2, 64).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 256, 2, 64).astype(np.float32))
    assert FA.supported(q, k, v, causal=True)
    out = FA.flash_attention(q, k, v, causal=True)
    ref = ref_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_unsupported_falls_back():
    q = jnp.zeros((1, 100, 2, 64))   # 100 not divisible by block
    assert not FA.supported(q, q, q, causal=True)
    q = jnp.zeros((1, 128, 2, 48))   # head_dim 48
    assert not FA.supported(q, q, q, causal=True)


def test_sdpa_use_pallas_always():
    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.randn(1, 128, 2, 64).astype(np.float32))
    out = F.scaled_dot_product_attention(q, q, q, causal=True,
                                         use_pallas="always")
    ref = F.scaled_dot_product_attention(q, q, q, causal=True,
                                         use_pallas="never")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    bad = jnp.zeros((1, 100, 2, 64))
    with pytest.raises(RuntimeError, match="use_pallas"):
        F.scaled_dot_product_attention(bad, bad, bad, causal=True,
                                       use_pallas="always")


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_rms_norm_kernel(dtype):
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(64, 256)).astype(dtype)
    w = jnp.asarray(rs.randn(256).astype(np.float32)).astype(dtype)
    assert NORM.supported(x, w)
    out = NORM.rms_norm(x, w)
    ref = F.rms_norm(x, w)
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    if dtype != np.float32:
        return
    g1 = jax.grad(lambda x, w: jnp.sum(jnp.sin(NORM.rms_norm(x, w))),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: jnp.sum(jnp.sin(F.rms_norm(x, w))),
                  argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_rms_norm_kernel_fd():
    """Direct finite-difference anchor on the kernel itself (f64 runs
    through the interpreter)."""
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(8, 128).astype(np.float32))
    w = jnp.asarray(rs.randn(128).astype(np.float32))
    op_test.check_grad(lambda x, w: NORM.rms_norm(x, w), [x, w],
                       wrt=(1,), rtol=1e-2, atol=1e-3)


def test_layer_norm_kernel():
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(64, 256).astype(np.float32))
    w = jnp.asarray(rs.randn(256).astype(np.float32))
    b = jnp.asarray(rs.randn(256).astype(np.float32))
    assert NORM.supported(x, w)
    np.testing.assert_allclose(
        np.asarray(NORM.layer_norm(x, w, b)),
        np.asarray(F.layer_norm(x, w, b)),  # on_tpu()=False → jnp path
        rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(NORM.layer_norm(*a))),
                  argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(F.layer_norm(*a))),
                  argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=2e-4)


def test_softmax_cross_entropy_kernel():
    rs = np.random.RandomState(6)
    logits = jnp.asarray(rs.randn(64, 512).astype(np.float32) * 3)
    labels = jnp.asarray(rs.randint(0, 512, (64,)).astype(np.int32))
    assert SX.supported(logits, labels)
    out = SX.softmax_cross_entropy(logits, labels)
    ref = F.softmax_with_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda l: jnp.mean(SX.softmax_cross_entropy(l, labels)))(
        logits)
    g2 = jax.grad(lambda l: jnp.mean(F.softmax_with_cross_entropy(
        l, labels)))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)


def test_rope_kernel():
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(2, 128, 4, 64).astype(np.float32))
    cos, sin = F.rotary_embedding(jnp.arange(128), 64)
    assert ROPE.supported(x, cos, sin)
    np.testing.assert_allclose(
        np.asarray(ROPE.apply_rotary(x, cos, sin)),
        np.asarray(F.apply_rotary(x, cos, sin)), rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda x: jnp.sum(jnp.sin(ROPE.apply_rotary(
        x, cos, sin))))(x)
    g2 = jax.grad(lambda x: jnp.sum(jnp.sin(F.apply_rotary(
        x, cos, sin))))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


def test_dispatch_wrappers_forced(monkeypatch):
    """Exercise the functional.py auto-dispatch wrappers on CPU by forcing
    the gate open (kernels run interpreted) — covers the reshape /
    ignore_index / fallback glue that on_tpu() normally hides from CI."""
    support = importlib.import_module("paddle_tpu.ops.pallas._support")
    monkeypatch.setattr(support, "dispatch_mode", lambda: "raw")
    rs = np.random.RandomState(11)

    # rms_norm + layer_norm via the wrapper (3D input → reshape round-trip)
    x = jnp.asarray(rs.randn(4, 16, 256).astype(np.float32))
    w = jnp.asarray(rs.randn(256).astype(np.float32))
    b = jnp.asarray(rs.randn(256).astype(np.float32))
    np.testing.assert_allclose(np.asarray(F.rms_norm(x, w)),
                               np.asarray(NORM.rms_norm(x, w)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(F.layer_norm(x, w, b)),
                               np.asarray(NORM.layer_norm(x, w, b)),
                               rtol=1e-6, atol=1e-6)
    # broadcastable-but-not-(h,) bias must fall back, not crash
    bad_bias = jnp.zeros((1,), jnp.float32)
    out = F.layer_norm(x, w, bad_bias)
    assert out.shape == x.shape

    # softmax_with_cross_entropy wrapper: [B, T, V] + ignore_index masking
    logits = jnp.asarray(rs.randn(2, 64, 512).astype(np.float32))
    labels = rs.randint(0, 512, (2, 64)).astype(np.int32)
    labels[0, :5] = -100
    labels = jnp.asarray(labels)
    got = F.softmax_with_cross_entropy(logits, labels)
    monkeypatch.setattr(support, "dispatch_mode", lambda: "off")
    ref = F.softmax_with_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[0, :5]))) == 0.0

    # apply_rotary wrapper
    monkeypatch.setattr(support, "dispatch_mode", lambda: "raw")
    x4 = jnp.asarray(rs.randn(2, 128, 4, 64).astype(np.float32))
    cos, sin = F.rotary_embedding(jnp.arange(128), 64)
    got = F.apply_rotary(x4, cos, sin)
    monkeypatch.setattr(support, "dispatch_mode", lambda: "off")
    ref = F.apply_rotary(x4, cos, sin)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_mode_under_multidevice_mesh(devices8):
    """Under a >1-device mesh the kernel set dispatches through the
    shard_map units (mode 'partitioned'); single device goes
    straight to pallas ('raw'); off-TPU without the force flag stays on
    the jnp path ('off')."""
    from paddle_tpu.parallel import mesh as M
    support = importlib.import_module("paddle_tpu.ops.pallas._support")
    mesh = M.create_mesh({"dp": 8}, devices8)
    assert support.single_device()
    assert support.dispatch_mode() == "off"  # CPU, no force
    with support.force_dispatch():
        assert support.dispatch_mode() == "raw"
        with M.MeshContext(mesh):
            assert not support.single_device()
            assert support.dispatch_mode() == "partitioned"
        assert support.dispatch_mode() == "raw"
    assert support.dispatch_mode() == "off"


def test_flash_attention_in_jit_and_remat():
    """Kernel must compose with jit + jax.checkpoint (the train step)."""
    rs = np.random.RandomState(9)
    q = jnp.asarray(rs.randn(1, 128, 2, 64).astype(np.float32))

    @jax.jit
    def step(q):
        def f(q):
            return jnp.sum(FA.flash_attention(q, q, q, causal=True) ** 2)
        return jax.grad(jax.checkpoint(f))(q)

    g = step(q)
    ref = jax.grad(lambda q: jnp.sum(
        ref_attention(q, q, q, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
