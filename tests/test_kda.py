"""The KDA operator (``ops/kda.py``): three forms of one arithmetic —
sequential (the oracle), chunked (the WY / UT-transform matmul form in
chunks of 64) and the one-token step — agree; the state is carried
across chunk and call boundaries; padding is the identity, state and
convolution tail both; the Pallas step kernel (``ptpu_kda_step``) in
interpret mode agrees with XLA's lines and holds ONE call under
``vmap``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import _support
from paddle_tpu.ops.pallas import kda_step as kernel

TOL = dict(atol=2e-6, rtol=1e-5)


def operands(B, T, H, dk, dv, seed=0, lo=-1.6):
    """Unit keys, scaled unit queries, decays from ``exp(lo)`` to ~1 a
    token a channel, a state to start from."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = jax.random.normal(ks[1], (B, T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = jax.random.uniform(ks[3], (B, T, H, dk), minval=lo, maxval=-1e-3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv))
    return (q, k, v, g, beta), S0


# jitted once a shape: op-by-op dispatch of the chunked form compiles a
# hundred small programs
sequential = jax.jit(kda.kda_sequential)
chunked = jax.jit(kda.kda_chunked)


def close(a, b, **tol):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               **(tol or TOL))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the three forms ---------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 63, 64, 65, 200])
def test_chunked_equals_sequential(T):
    x, S0 = operands(2, T, 3, 32, 16)
    o1, s1 = sequential(*x, S0)
    o2, s2 = chunked(*x, S0)
    close(o1, o2)
    close(s1, s2)
    # and from nothing
    close(sequential(*x)[0], chunked(*x)[0])


def test_token_by_token_steps_equal_sequential():
    x, S0 = operands(2, 9, 2, 16, 16)
    o, s = sequential(*x, S0)
    rows = jnp.stack([jnp.zeros_like(S0), S0])          # layer 1 of 2
    for t in range(9):
        ot, rows = kda.kda_step(rows, 1, *(a[:, t] for a in x))
        close(ot, o[:, t])
    close(rows[1], s)
    assert not np.asarray(rows[0]).any()                # layer 0 untouched


@pytest.mark.parametrize("cut", [1, 64, 70, 128])
def test_state_is_carried_across_call_boundaries(cut):
    x, S0 = operands(1, 150, 2, 16, 32, seed=1)
    o, s = sequential(*x, S0)
    oa, sa = chunked(*(a[:, :cut] for a in x), S0)
    ob, sb = chunked(*(a[:, cut:] for a in x), sa)
    close(jnp.concatenate([oa, ob], 1), o)
    close(sb, s)


def test_a_channel_that_decays_by_e200_inside_a_chunk_does_not_overflow():
    """exp(-G) of a chunk's running decay overflows float32 past e^88:
    the pairwise decays never form it."""
    x, S0 = operands(1, 128, 2, 16, 16, seed=2, lo=-4.0)
    assert float(jnp.sum(x[3], axis=1).min()) < -200
    o1, s1 = sequential(*x, S0)
    o2, s2 = chunked(*x, S0)
    assert np.isfinite(np.asarray(o2)).all()
    close(o1, o2)
    close(s1, s2)


# -- padding is the identity ---------------------------------------------------------

def test_a_padded_bucket_equals_the_unpadded_prompt():
    x, S0 = operands(2, 96, 2, 16, 16, seed=3)
    lens = jnp.array([70, 33])
    op, sp = chunked(*x, S0, length=lens)
    for b, n in enumerate((70, 33)):
        o, s = sequential(*(a[b:b + 1, :n] for a in x), S0[b:b + 1])
        close(op[b, :n], o[0])
        close(sp[b], s[0])
    # a scalar length, and the sequential form's own mask
    o3, s3 = sequential(*x, S0, length=jnp.int32(33))
    close(s3[1], sp[1])


def test_identity_positions_leave_the_state_bit_for_bit():
    (q, k, v, g, beta), S0 = operands(2, 5, 2, 16, 16, seed=4)
    g0, b0 = kda.mask_padding(g, beta, 0)
    assert not np.asarray(g0).any() and not np.asarray(b0).any()
    for form in (sequential, chunked):
        _, s = form(q, k, v, g0, b0, S0)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(S0))
    rows = S0[None]
    _, out = kda.kda_step(rows, 0, q[:, 0], k[:, 0], v[:, 0], g0[:, 0],
                          b0[:, 0])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(rows))


def test_short_conv_carries_its_tail_at_the_true_length():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 6))
    y, tail = kda.short_conv(x, w)
    want = sum(jnp.pad(x, ((0, 0), (3, 0), (0, 0)))[:, j:j + 10] * w[j]
               for j in range(4))
    close(y, want)
    close(tail, x[:, -3:])
    ya, ta = kda.short_conv(x[:, :4], w)
    yb, tb = kda.short_conv(x[:, 4:], w, ta)
    close(jnp.concatenate([ya, yb], 1), y)
    close(tb, tail)
    # a padded chunk: the tail of the true tokens, zeros ahead of a short
    # row, and a chunk of length 0 the tail it found
    _, tp = kda.short_conv(x, w, length=jnp.array([4, 2]))
    close(tp[0], x[0, 1:4])
    close(tp[1], jnp.concatenate([jnp.zeros((1, 6)), x[1, :2]]))
    _, t0 = kda.short_conv(x, w, ta, length=0)
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(ta))
    # token by token
    t = None
    for i in range(10):
        yi, t = kda.short_conv(x[:, i:i + 1], w, t)
        close(yi[:, 0], y[:, i])


# -- the kernel ----------------------------------------------------------------------

H, D = kernel.HEADS_PER_BLOCK, 128


def step_operands(N, seed):
    (q, k, v, g, beta), S0 = operands(N, 1, H, D, D, seed=seed)
    return tuple(a.reshape((N, 1) + a.shape[2:]) for a in (q, k, v, g, beta)
                 ), S0


def test_the_gate_asks_for_raw_dispatch_and_whole_blocks():
    rows = jnp.zeros((2, 1, H, D, D))
    q, v = jnp.zeros((1, H, D)), jnp.zeros((1, H, D))
    assert not kernel.supported(rows, q, v)             # the CPU: XLA's arm
    with _support.force_dispatch():
        assert kernel.supported(rows, q, v)
        assert not kernel.supported(rows[:, :, :8], q[:, :8], v[:, :8])
        assert not kernel.supported(jnp.zeros((2, 2, H, D, D)), q, v)
        assert not kernel.supported(rows.astype(jnp.bfloat16), q, v)
        assert not kernel.supported(jnp.zeros((2, 1, H, 64, 64)),
                                    q[..., :64], v[..., :64])


def test_kernel_agrees_with_xlas_lines_and_counts_its_arm():
    (q, k, v, g, beta), S0 = step_operands(1, 5)
    rows = jnp.stack([S0, 2 * S0])                      # [L=2, 1, H, D, D]
    args = tuple(a[:, 0] for a in (q, k, v, g, beta))
    before = dict(kda.step_arms)
    o_x, r_x = kda.kda_step(rows, 1, *args)
    assert kda.step_arms["xla"] == before.get("xla", 0) + 1
    with _support.force_dispatch():
        o_k, r_k = jax.jit(lambda r: kda.kda_step(r, jnp.int32(1), *args)
                           )(rows)
    assert kda.step_arms["kernel"] == before.get("kernel", 0) + 1
    close(o_k, o_x, atol=1e-6, rtol=1e-5)
    close(r_k, r_x, atol=2e-6, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(r_k[0]), np.asarray(rows[0]))


def test_under_vmap_the_slots_are_one_call():
    """The engine maps its step over slots, each a batch of one: the
    kernel's own batching rule folds the mapped axis into its grid."""
    S = 3
    (q, k, v, g, beta), S0 = step_operands(S, 6)
    # two slots live, one idle (length 0: the identity)
    g, beta = kda.mask_padding(g, beta, jnp.array([1, 0, 1]))
    rows = jnp.stack([S0, S0 * 0.5], 1)[:, :, None]     # [S, L=2, 1, ...]

    def one(r, *x):
        return kda.kda_step(r, jnp.int32(0), *x)

    o_x, r_x = jax.vmap(one)(rows, q, k, v, g, beta)
    with _support.force_dispatch():
        calls = str(jax.make_jaxpr(jax.vmap(one))(rows, q, k, v, g, beta)
                    ).count("pallas_call")
        o_k, r_k = jax.jit(jax.vmap(one))(rows, q, k, v, g, beta)
    assert calls == 1
    close(o_k, o_x, atol=1e-6, rtol=1e-5)
    close(r_k, r_x, atol=2e-6, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(r_k[1]), np.asarray(rows[1]))
