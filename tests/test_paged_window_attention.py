"""The paged decode kernel's lower edge (``window=`` / ``base=`` of
``ops/pallas/paged_decode_attention.py``): a window layer's row holds
only the live logical pages from ``base`` on, the query at position
``t`` sees cached positions ``t - W < p < t``. In interpret mode the
kernel has to reproduce an independent masked attention over the
absolute positions — at the edges ``t < W``, ``t == W - 1``, ``t ==
W``, a window that starts mid-page, a base that is not 0 — per slot,
under ``jax.vmap``, in the copy form a float pool takes (4 KV heads x 16
tokens, or a lane-aligned page of a 128-wide head: the kernel copies a
block of pages itself, and neither copies nor visits what lies wholly
behind the window) and in the block-spec form of a lane-aligned page of
a 64-wide head, and leave the program of every other model as it
was."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models._common import cached_attention
from paddle_tpu.models.generation import PagedCache
from paddle_tpu.ops.pallas import _support
from paddle_tpu.ops.pallas import paged_decode_attention as pdk
from test_paged_decode_attention import FORMS, set_form, walk_eqns

W, P = 24, 8


# 2 KV heads x 8 tokens x 64 are 16 rows (narrow: the copy form); with
# eight times the heads and a 128-wide head a page is one lane tile of
# rows (wide: the copy form too), with a 64-wide head the block-spec
# form's (wide64)
_heads, _dim = 1, 64


@pytest.fixture(params=FORMS)
def form(request, monkeypatch):
    """On narrow and on wide pages, at the kernel's own choice of pages
    a block / a grid step and at 3: steps wholly behind the window, a
    step the window starts inside."""
    global _heads, _dim
    _heads, _dim = set_form(request.param, monkeypatch)
    yield request.param
    _heads, _dim = 1, 64


def _mk(B=3, Hq=4, Hkv=2, M=6, D=None, L=2, N=24, seed=0,
        dtype=jnp.float32):
    Hq, Hkv, D = Hq * _heads, Hkv * _heads, D or _dim
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, 1, Hq, D), dtype)
    kn = jnp.asarray(rs.randn(B, Hkv, 1, D), dtype)
    vn = jnp.asarray(rs.randn(B, Hkv, 1, D), dtype)
    pool = (jnp.asarray(rs.randn(N + 1, L, Hkv, P, D), dtype),
            jnp.asarray(rs.randn(N + 1, L, Hkv, P, D), dtype))
    ids = rs.permutation(np.arange(1, N + 1))[: B * M]
    return q, kn, vn, pool, jnp.asarray(ids.reshape(B, M).astype(np.int32))


def _by_positions(q, kn, vn, pool, table, layer, idx, base, scale, window):
    """Independent of the kernel and of its reference: every row entry
    is placed at its ABSOLUTE positions ``(base + i) * P + offset`` and
    the mask is the window's definition, 0 <= t - j < W."""
    B, _, Hq, D = q.shape
    Hkv = kn.shape[1]
    G = Hq // Hkv
    outs = []
    for b in range(B):
        t = int(np.broadcast_to(np.asarray(idx), (B,))[b])
        first = int(np.broadcast_to(np.asarray(base), (B,))[b]) * P
        k = np.asarray(pool[0])[np.asarray(table[b]), layer]   # [M,Hkv,P,D]
        v = np.asarray(pool[1])[np.asarray(table[b]), layer]
        k = np.moveaxis(k, 0, 1).reshape(Hkv, -1, D)
        v = np.moveaxis(v, 0, 1).reshape(Hkv, -1, D)
        at = first + np.arange(k.shape[1])
        seen = (at < t) & (t - at < window)
        qh = np.asarray(q[b, 0], np.float64).reshape(Hkv, G, D)
        s = np.einsum("hgd,hsd->hgs", qh, k) * scale
        s = np.where(seen[None, None], s, -np.inf)
        s_new = np.einsum("hgd,hd->hg", qh, np.asarray(kn[b, :, 0]))[
            ..., None] * scale
        s = np.concatenate([s, s_new], -1)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = (np.einsum("hgs,hsd->hgd", p[..., :-1], v)
             + p[..., -1:] * np.asarray(vn[b, :, 0])[:, None])
        outs.append(o.reshape(Hq, D))
    return np.stack(outs).reshape(B, 1, Hq, D)


# (position of the query, logical page the row starts at): the row has 6
# pages = 48 positions, the window 24
EDGES = {
    "t<W": (10, 0), "t==W-1": (W - 1, 0), "t==W": (W, 0),
    "t==W+1": (W + 1, 0),
    "mid-page": (29, 0),             # sees 6..28: starts inside page 0
    "base-behind": (45, 1),          # sees 22..44; pages 1.. are mapped
    "base-at-edge": (45, 2),         # the row starts where the window does
    "base-not-0": (83, 7),           # sees 60..82 of a row from page 7
    "past-the-row": (70, 0),         # an idle slot's: sees 47 alone
    "row-full": (96 + 7, 7 + 6 - 6)}


@pytest.mark.parametrize("edge", list(EDGES), ids=list(EDGES))
def test_kernel_masks_what_has_slid_out(edge, form):
    t, base = EDGES[edge]
    if edge == "row-full":
        t, base = (base + 6) * P - 1, base       # the row's last position
    q, kn, vn, pool, table = _mk()
    want = _by_positions(q, kn, vn, pool, table, 1, t, base, 0.125, W)
    with _support.force_dispatch():
        assert pdk.supported(q, pool, table)
        got = pdk.paged_decode_attention(
            q, kn, vn, pool, table, jnp.int32(1), jnp.int32(t), scale=0.125,
            window=W, base=jnp.int32(base))
    ref = pdk.paged_reference(q, kn, vn, pool, table, jnp.int32(1),
                              jnp.int32(t), scale=0.125, window=W,
                              base=jnp.int32(base))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["wide64", "wide64-3"], indirect=True)
@pytest.mark.parametrize("edge", ["mid-page", "base-not-0", "past-the-row"])
def test_the_block_spec_form_masks_what_has_slid_out(edge, form):
    """A 64-wide head on lane-aligned pages stays on the block-spec form
    (its copy would slice half a lane tile out of the pool): the same
    lower edge, through the index map's clamp and the mask."""
    t, base = EDGES[edge]
    q, kn, vn, pool, table = _mk()
    assert not pdk.copies_pages(pool)
    want = _by_positions(q, kn, vn, pool, table, 1, t, base, 0.125, W)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(
            q, kn, vn, pool, table, jnp.int32(1), jnp.int32(t), scale=0.125,
            window=W, base=jnp.int32(base))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_a_slot_each_with_its_own_position_and_base(form):
    q, kn, vn, pool, table = _mk()
    idx = jnp.asarray([10, 45, 83], jnp.int32)
    base = jnp.asarray([0, 2, 7], jnp.int32)
    want = _by_positions(q, kn, vn, pool, table, 0, idx, base, 0.125, W)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(0), idx, scale=0.125,
                                         window=W, base=base)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_pages_behind_the_window_are_never_read(form):
    """Poison every position that has slid out, the null page and the
    pages of no slot: the output does not move."""
    q, kn, vn, pool, table = _mk()
    t, base = 45, 0                               # sees 22..44
    with _support.force_dispatch():
        clean = pdk.paged_decode_attention(
            q, kn, vn, pool, table, jnp.int32(1), jnp.int32(t), scale=0.125,
            window=W, base=jnp.int32(base))
        k, v = (np.array(x) for x in pool)
        live = np.zeros(k.shape[0], bool)
        for row in np.asarray(table):
            for i, pid in enumerate(row):
                for off in range(P):
                    if 22 <= i * P + off <= 44:
                        live[pid] = True
                    else:
                        k[pid, :, :, off] = 1e4
                        v[pid, :, :, off] = 1e4
        k[~live], v[~live] = 1e4, 1e4
        got = pdk.paged_decode_attention(
            q, kn, vn, (jnp.asarray(k), jnp.asarray(v)), table, jnp.int32(1),
            jnp.int32(t), scale=0.125, window=W, base=jnp.int32(base))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("form", ["narrow", "narrow-3"], indirect=True)
def test_the_row_joined_form_of_a_narrow_page(form):
    """4 KV heads x 8 tokens is a quarter of a lane tile, SmallThinker's
    28 query heads: the pages of a block stand joined along the rows in
    VMEM before one dot a side. Same numbers as the lane-aligned form's
    reference, with and without a window."""
    q, kn, vn, pool, table = _mk(Hq=28, Hkv=4, D=128, M=8, N=30, seed=3)
    assert (4 * P) % pdk.LANES and pdk.copies_pages(pool)
    for window, t, base in ((None, 50, 0), (W, 50, 3), (W, 20, 0)):
        kw = {} if window is None else dict(window=window,
                                            base=jnp.int32(base))
        want = _by_positions(q, kn, vn, pool, table, 1, t, base, 0.088,
                             window or 10 ** 6)
        with _support.force_dispatch():
            got = pdk.paged_decode_attention(
                q, kn, vn, pool, table, jnp.int32(1), jnp.int32(t),
                scale=0.088, **kw)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)


# a row of 12 pages, the window 24 positions: at 4 pages a block the
# fill and the lower edge each fall in the first, the middle and the
# last block, and whole blocks lie behind the window
BLOCK_EDGES = {
    "both_in_first": (20, 0),        # sees 0..19
    "lo_first_fill_middle": (45, 0),  # sees 22..44: pages 2..5
    "lo_middle_fill_last": (70, 0),  # sees 47..69: pages 5..8
    "both_in_last": (95, 0),         # sees 72..94: pages 9..11
    "block_edge": (64, 0),           # sees 41..63: the fill ends block 1
    "base_not_0": (8 * 5 + 70, 5)}   # the same rows from logical page 5


@pytest.mark.parametrize("form", ["narrow", "narrow-4"], indirect=True)
@pytest.mark.parametrize("edge", list(BLOCK_EDGES))
def test_copy_form_blocks_at_the_fill_and_at_the_window(edge, form):
    t, base = BLOCK_EDGES[edge]
    q, kn, vn, pool, table = _mk(M=12, N=40, seed=5)
    want = _by_positions(q, kn, vn, pool, table, 1, t, base, 0.125, W)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(
            q, kn, vn, pool, table, jnp.int32(1), jnp.int32(t), scale=0.125,
            window=W, base=jnp.int32(base))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["narrow", "narrow-4"], indirect=True)
def test_copy_form_a_slot_with_nothing_cached_between_two_live(form):
    """The cross-slot prefetch under a window: slot 1 has nothing
    cached (position 0), its neighbours stand in different blocks."""
    q, kn, vn, pool, table = _mk(M=12, N=40, seed=6)
    idx = jnp.asarray([70, 0, 8 * 3 + 95], jnp.int32)
    base = jnp.asarray([0, 0, 3], jnp.int32)
    want = _by_positions(q, kn, vn, pool, table, 0, idx, base, 0.125, W)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(0), idx, scale=0.125,
                                         window=W, base=base)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_under_vmap_it_is_one_call_over_the_slots(form):
    """The engine's shape: ``cached_attention`` under ``vmap`` over
    slots, each slot its row, base and position, the pool unmapped."""
    q, kn, vn, pool, table = _mk()
    idx = jnp.asarray([10, 45, 83], jnp.int32)
    base = jnp.asarray([0, 2, 7], jnp.int32)
    k = jnp.moveaxis(kn, 1, 2)                     # [B, 1, Hkv, D]
    v = jnp.moveaxis(vn, 1, 2)

    def one(qb, kb, vb, row, b0, t):
        out, _ = cached_attention(qb[None], kb[None], vb[None],
                                  PagedCache(pool, row, b0), t, layer=1,
                                  window=W)
        return out[0]

    with _support.force_dispatch():
        fn = jax.vmap(one)
        got = fn(q, k, v, table, base, idx)
        jaxpr = jax.make_jaxpr(fn)(q, k, v, table, base, idx)
    want = _by_positions(q, kn, vn, pool, table, 1, idx, base,
                         q.shape[-1] ** -0.5, W)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # one call, and no loop over the slots around it
    assert [path for e, path in walk_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"] == [("custom_vmap_call",)]


@pytest.mark.parametrize("T", [1, 5])
def test_the_gather_arm_masks_the_same_positions(T):
    """The einsum lines of ``cached_attention`` over a window row (the
    CPU's arm, and every prefill chunk's): a chunk of T queries behind a
    cached prefix, each with its own lower edge."""
    B, Hq, Hkv, D, M = 1, 4, 2, 16, 6
    rs = np.random.RandomState(1)
    pool = tuple(jnp.asarray(rs.randn(13, 2, Hkv, P, D), jnp.float32)
                 for _ in range(2))
    row = jnp.asarray([5, 2, 9, 1, 7, 3], jnp.int32)
    q = jnp.asarray(rs.randn(B, T, Hq, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, Hkv, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, T, Hkv, D), jnp.float32)
    base, t0 = 3, 3 * P + 30                      # the chunk starts at 54
    got, _ = cached_attention(q, k, v, PagedCache(pool, row, jnp.int32(base)),
                              jnp.int32(t0), layer=1, window=W)
    kc = np.moveaxis(np.asarray(pool[0])[np.asarray(row), 1], 0, 1
                     ).reshape(Hkv, -1, D)
    vc = np.moveaxis(np.asarray(pool[1])[np.asarray(row), 1], 0, 1
                     ).reshape(Hkv, -1, D)
    at = np.concatenate([base * P + np.arange(M * P), t0 + np.arange(T)])
    live = np.concatenate([base * P + np.arange(M * P) < t0,
                           np.ones(T, bool)])
    keys = np.concatenate([kc, np.moveaxis(np.asarray(k[0]), 0, 1)], 1)
    vals = np.concatenate([vc, np.moveaxis(np.asarray(v[0]), 0, 1)], 1)
    for i in range(T):
        t = t0 + i
        seen = live & (at <= t) & (t - at < W)
        qh = np.asarray(q[0, i], np.float64).reshape(Hkv, Hq // Hkv, D)
        s = np.where(seen, np.einsum("hgd,hsd->hgs", qh, keys) * D ** -0.5,
                     -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(got[0, i]),
            np.einsum("hgs,hsd->hgd", p, vals).reshape(Hq, D),
            rtol=2e-5, atol=2e-5)


def test_without_a_window_the_program_is_the_old_one():
    """``window=None`` adds nothing to what is traced: the rows keep
    their two header columns and the kernel its body."""
    q, kn, vn, pool, table = _mk()
    with _support.force_dispatch():
        plain = str(jax.make_jaxpr(lambda *a: pdk.paged_decode_attention(
            *a, pool, table, jnp.int32(1), jnp.int32(20), scale=0.125))(
                q, kn, vn))
        edged = str(jax.make_jaxpr(lambda *a: pdk.paged_decode_attention(
            *a, pool, table, jnp.int32(1), jnp.int32(20), scale=0.125,
            window=W, base=jnp.int32(0)))(q, kn, vn))
    assert "i32[3,8]" in plain and "i32[3,9]" not in plain   # [layer, idx, 6]
    assert "i32[3,9]" in edged                               # + lo


def test_the_compiled_gate_takes_the_narrow_page(monkeypatch):
    """Where the kernel would be compiled a float page of 4 heads x 16
    tokens (64 rows) takes the copy form when its copies move whole
    tiles (16 rows of bf16, 128 lanes) and a block's pages fill whole
    lane tiles — a short table's block is rounded up to eight pages —
    and the int8 pool stays refused."""
    monkeypatch.setattr(_support, "on_tpu", lambda: True)
    monkeypatch.setattr(_support, "interpret", lambda: False)
    monkeypatch.setattr(_support, "dispatch_mode", lambda: "raw")
    q = jnp.zeros((2, 1, 28, 128), jnp.bfloat16)
    table = jnp.zeros((2, 289), jnp.int32)

    def pool(hkv=4, p=16, d=128, dtype=jnp.bfloat16):
        return tuple(jnp.zeros((4, 6, hkv, p, d), dtype) for _ in range(2))

    assert pdk.copies_pages(pool()) and pdk.supported(q, pool(), table)
    assert pdk.supported(q, pool(), table[:, :1])           # a block of 8
    # half-lane rows (chip_smoke's window model before PR 36): Mosaic
    # refuses to slice them out of the pool
    assert not pdk.supported(q[..., :64], pool(d=64), table)
    # 8 rows of bf16 are half a packed tile
    assert not pdk.supported(q, pool(p=8), table)
    # one KV head x 8 float32 rows: a block of eight pages is half a tile
    assert not pdk.supported(q.astype(jnp.float32),
                             pool(hkv=1, p=8, dtype=jnp.float32),
                             table[:, :1])
    # lane-aligned pages of a 128-wide head are the copy form's too
    # (OLMoE's 16 KV heads x 16 tokens), of a 64-wide head the
    # block-spec form's
    assert pdk.copies_pages(pool(hkv=16))
    assert pdk.supported(q[:, :, :16], pool(hkv=16), table)
    assert not pdk.copies_pages(pool(hkv=16, d=64))
    assert pdk.supported(q[:, :, :16, :64], pool(hkv=16, d=64), table)
    quant = (jnp.zeros((4, 6, 4, 16, 128), jnp.int8),) * 2 + (
        jnp.zeros((4, 6, 4, 16), jnp.float32),) * 2
    assert not pdk.copies_pages(quant)
    assert not pdk.supported(q, quant, table)
