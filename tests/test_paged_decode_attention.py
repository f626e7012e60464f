"""Page-table-aware Pallas decode kernel (ops/pallas/paged_decode_attention.py).

OpTest discipline, same contract as ``test_decode_attention.py`` but
with the page indirection inside the kernel: in interpret mode it must
reproduce ``models.generation.paged_gather`` + masked attention
bit-for-bit per slot, honor the physical page permutation (same logical
sequence, different page placement → identical output), bound reads to
the filled prefix, fold int8 pool scales exactly, and survive
``jax.vmap`` over slots. Both K/V forms are held to it: the copy form
every float pool takes whose copies Mosaic accepts (the kernel copies a
block of pages into VMEM itself; narrow pages and OLMoE's 16 KV heads x
128) and the block-spec form of the int8 pool and of a 64-wide head on
pages of whole lane tiles, each at its own choice of pages a block / a
grid step and at 3. This is
the hardware-independent result; the TPU timing run is the stated caveat
in the module doc.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models.generation import paged_gather
from paddle_tpu.ops.pallas import _support
from paddle_tpu.ops.pallas import paged_decode_attention as pdk


# a page of 2 KV heads x 8 tokens is 16 rows (narrow: floats take the copy
# form, the int8 pool the block-spec form, which only the interpreter runs
# on such a page); with eight times the heads and a 128-wide head it is
# one lane tile of rows (wide: OLMoE's kind of page, floats the copy form
# too); the same page with a 64-wide head (wide64) is the block-spec
# form's, whose copy Mosaic refuses
WIDTHS = {"narrow": (1, 64), "wide": (8, 128), "wide64": (8, 64)}
FORMS = ["narrow", "narrow-3", "wide", "wide-3"]
_heads, _dim = 1, 64


def set_form(param, monkeypatch):
    """``"<width>[-<pages>]"``: both forms' pages a block / a grid step
    patched to ``pages`` where given; returns the width's multiple of
    the tests' heads and its head width."""
    width, _, pages = param.partition("-")
    if pages:
        for name in ("_pages_per_step", "_pages_per_block"):
            monkeypatch.setattr(pdk, name, lambda M, page_bytes: int(pages))
    return WIDTHS[width]


@pytest.fixture(autouse=True, params=FORMS)
def form(request, monkeypatch):
    """Every test on narrow and on wide pages, at the kernel's own
    choice of pages a block / a grid step (all of these tables' 4 in
    one) and at 3: two steps of pages, the second with a tail past the
    table (the block-spec form clamps it to the last live page, the
    copy form does not copy it)."""
    global _heads, _dim
    _heads, _dim = set_form(request.param, monkeypatch)
    yield request.param
    _heads, _dim = 1, 64


def _steps(M, pool):
    """Grid steps along a slot's pages: the fresh token's, then the
    form's pages each."""
    pages = (pdk._pages_per_block if pdk.copies_pages(pool)
             else pdk._pages_per_step)
    return 1 - (-M // pages(M, 1))


def _mk(B=2, Hq=4, Hkv=2, P=8, M=4, D=None, L=2, N=16, quant=False,
        dtype=jnp.float32, seed=0):
    Hq, Hkv, D = Hq * _heads, Hkv * _heads, D or _dim
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, 1, Hq, D), dtype)
    kn = jnp.asarray(rs.randn(B, Hkv, 1, D), dtype)
    vn = jnp.asarray(rs.randn(B, Hkv, 1, D), dtype)
    if quant:
        pool = (
            jnp.asarray(rs.randint(-127, 128, (N + 1, L, Hkv, P, D)),
                        jnp.int8),
            jnp.asarray(rs.randint(-127, 128, (N + 1, L, Hkv, P, D)),
                        jnp.int8),
            jnp.asarray(rs.rand(N + 1, L, Hkv, P) * 0.05 + 0.001,
                        jnp.float32),
            jnp.asarray(rs.rand(N + 1, L, Hkv, P) * 0.05 + 0.001,
                        jnp.float32),
        )
    else:
        pool = (jnp.asarray(rs.randn(N + 1, L, Hkv, P, D), dtype),
                jnp.asarray(rs.randn(N + 1, L, Hkv, P, D), dtype))
    # distinct live pages per slot, never the null page 0
    ids = rs.permutation(np.arange(1, N + 1))[: B * M]
    table = jnp.asarray(ids.reshape(B, M).astype(np.int32))
    return q, kn, vn, pool, table


def _via_paged_gather(q, kn, vn, pool, table, layer, idx, scale):
    """Independent reference built on the REAL ``paged_gather`` (the
    copy the kernel deletes): per slot, materialize the view, one-layer
    masked attention in the fallback's dtype discipline."""
    B, _, Hq, D = q.shape
    Hkv = kn.shape[1]
    G = Hq // Hkv
    M = table.shape[1]
    P = pool[0].shape[3]
    idx = np.broadcast_to(np.asarray(idx), (B,))
    outs = []
    for b in range(B):
        view = paged_gather(pool, table[b])       # [L, 1, Hkv, M*P, ...]
        if len(pool) == 4:
            k_c = (view[0][layer, 0].astype(q.dtype)
                   * view[2][layer, 0][..., None])
            v_c = (view[1][layer, 0].astype(q.dtype)
                   * view[3][layer, 0][..., None])
        else:
            k_c, v_c = view[0][layer, 0], view[1][layer, 0]
        qh = q[b, 0].reshape(Hkv, G, D)
        s_c = jnp.einsum("hgd,hsd->hgs", qh, k_c) * scale
        mask = jnp.arange(M * P) < idx[b]
        s_c = jnp.where(mask[None, None, :], s_c, pdk.NEG_INF)
        s_n = jnp.sum(qh * kn[b], axis=-1, keepdims=True) * scale
        s_all = jnp.concatenate([s_c, s_n], -1).astype(jnp.float32)
        p = jax.nn.softmax(s_all, -1).astype(q.dtype)
        o = (jnp.einsum("hgs,hsd->hgd", p[..., :-1], v_c)
             + p[..., -1:] * vn[b])
        outs.append(o.reshape(Hq, D))
    return jnp.stack(outs).reshape(B, 1, Hq, D)


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("idx", [1, 7, 17, 32])
def test_kernel_matches_paged_gather(quant, idx):
    q, kn, vn, pool, table = _mk(quant=quant)
    want = _via_paged_gather(q, kn, vn, pool, table, 1, idx, 0.125)
    with _support.force_dispatch():
        assert pdk.supported(q, pool, table)
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), jnp.int32(idx),
                                         scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the fallback arm is the same math
    ref = pdk.paged_reference(q, kn, vn, pool, table, 1, jnp.int32(idx),
                              scale=0.125)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True])
def test_kernel_selects_layer(quant):
    """sp_ref[b, 0] must pick layer l's plane out of the pool stack."""
    q, kn, vn, pool, table = _mk(L=3, quant=quant, seed=7)
    for l in range(3):
        with _support.force_dispatch():
            got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                             jnp.int32(l), jnp.int32(20),
                                             scale=0.125)
        want = _via_paged_gather(q, kn, vn, pool, table, l, 20, 0.125)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"l={l}")


def test_page_indirection_is_honored():
    """The same logical sequence under two different physical page
    placements must produce identical output — the proof that the index
    map reads the table rather than assuming contiguity."""
    q, kn, vn, pool, table = _mk(B=1, seed=3)
    perm = np.array([3, 1, 0, 2])                 # logical -> new slot order
    kp, vp = np.asarray(pool[0]).copy(), np.asarray(pool[1]).copy()
    old = np.asarray(table[0])
    new_ids = old[perm]                           # reuse the same pages...
    kp2, vp2 = kp.copy(), vp.copy()
    for lg in range(len(perm)):                   # ...but relocate content
        kp2[new_ids[lg]] = kp[old[lg]]
        vp2[new_ids[lg]] = vp[old[lg]]
    table2 = jnp.asarray(new_ids[None].astype(np.int32))
    with _support.force_dispatch():
        a = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                       jnp.int32(0), jnp.int32(25),
                                       scale=0.125)
        b = pdk.paged_decode_attention(
            q, kn, vn, (jnp.asarray(kp2), jnp.asarray(vp2)), table2,
            jnp.int32(0), jnp.int32(25), scale=0.125)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_ignores_stale_and_unmapped():
    """Positions >= index and the null page must not contribute:
    poisoning them with huge values changes nothing."""
    q, kn, vn, pool, table = _mk(seed=1)
    idx = 19                                       # mid page 3 of 4
    kp, vp = np.asarray(pool[0]).copy(), np.asarray(pool[1]).copy()
    P = kp.shape[3]
    for b in range(table.shape[0]):
        row = np.asarray(table[b])
        kp[row[idx // P], :, :, idx % P:] = 1e4    # stale tail of the page
        vp[row[idx // P], :, :, idx % P:] = -1e4
        kp[row[idx // P + 1:]] = 1e4               # wholly unfilled pages
        vp[row[idx // P + 1:]] = -1e4
    kp[0], vp[0] = 1e4, -1e4                       # the null page
    poisoned = (jnp.asarray(kp), jnp.asarray(vp))
    with _support.force_dispatch():
        a = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                       jnp.int32(0), jnp.int32(idx),
                                       scale=0.125)
        b = pdk.paged_decode_attention(q, kn, vn, poisoned, table,
                                       jnp.int32(0), jnp.int32(idx),
                                       scale=0.125)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("form", ["narrow", "narrow-3", "wide", "wide-3",
                                  "wide64"], indirect=True)
def test_a_fill_past_the_table_reads_the_table_alone(form):
    """An idle slot's position may lie beyond what its row maps (the
    engine steps every slot): the kernel attends the row's pages and
    reads no table entry past it. (The block-spec form — a 64-wide head
    on wide pages — is held where its pages a step divide the table, as
    they do in every engine that runs it: at 3 pages a step its last
    step's index map walks past the row — ROADMAP C2.)"""
    q, kn, vn, pool, table = _mk(seed=9)
    idx = table.shape[1] * 8 + 9
    want = _via_paged_gather(q, kn, vn, pool, table, 1, idx, 0.125)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), jnp.int32(idx),
                                         scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_per_slot_index_vector():
    """index may be [B] — each slot masks at its own fill position."""
    q, kn, vn, pool, table = _mk(seed=4)
    idxv = jnp.asarray([3, 30], jnp.int32)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(0), idxv, scale=0.125)
    want = _via_paged_gather(q, kn, vn, pool, table, 0,
                             np.asarray(idxv), 0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_gqa_group_mapping():
    """Hq=8, Hkv=2 (G=4): each q head reads ITS kv head's pages — the
    block-diagonal mask at page granularity."""
    q, kn, vn, pool, table = _mk(Hq=8, Hkv=2, seed=5)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), jnp.int32(28),
                                         scale=0.125)
    want = _via_paged_gather(q, kn, vn, pool, table, 1, 28, 0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "quant", [False, pytest.param(True, marks=pytest.mark.slow)])
def test_kernel_under_vmap_matches_per_slot(quant):
    """The engine's fused decode vmaps over the slot axis, so the
    kernel must survive jax's pallas batching rule: vmapped calls equal
    the per-slot calls exactly (pool closed over, tables/indices
    mapped)."""
    SLOTS = 3
    _, _, _, pool, _ = _mk(B=1, quant=quant, seed=20)
    qs, kns, vns, tabs = [], [], [], []
    idxs = [2, 15, 31]
    for s in range(SLOTS):
        q, kn, vn, _, table = _mk(B=1, quant=quant, seed=30 + s)
        qs.append(q), kns.append(kn), vns.append(vn), tabs.append(table)
    qv, knv, vnv = jnp.stack(qs), jnp.stack(kns), jnp.stack(vns)
    tabv = jnp.stack(tabs)
    idxv = jnp.asarray(idxs, jnp.int32)

    def one(q, kn, vn, tab, i):
        assert pdk.supported(q, pool, tab)     # gate holds under tracer
        return pdk.paged_decode_attention(q, kn, vn, pool, tab,
                                          jnp.int32(1), i, scale=0.125)

    with _support.force_dispatch():
        got = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, 0, 0)))(
            qv, knv, vnv, tabv, idxv)
        want = jnp.stack([
            pdk.paged_decode_attention(qs[s], kns[s], vns[s], pool,
                                       tabs[s], jnp.int32(1),
                                       jnp.int32(idxs[s]), scale=0.125)
            for s in range(SLOTS)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for s in range(SLOTS):
        np.testing.assert_allclose(
            np.asarray(got[s]),
            np.asarray(_via_paged_gather(qs[s], kns[s], vns[s], pool,
                                         tabs[s], 1, idxs[s], 0.125)),
            rtol=2e-5, atol=2e-5, err_msg=f"slot {s}")


# -- the copy form: blocks, fills, and the prefetch across slots ----------------

# 12 pages a slot: at 4 pages a block three blocks; at the kernel's own
# choice one block wider than the table
COPY_FILLS = {"nothing": 0, "one_row": 1, "first_block": 8 + 3,
              "block_edge": 4 * 8, "past_block_edge": 4 * 8 + 1,
              "middle_block": 6 * 8 + 5, "last_block": 11 * 8 + 2,
              "full_table": 12 * 8}
COPY_FORMS = pytest.mark.parametrize("form", ["narrow", "narrow-4"],
                                     indirect=True)


@COPY_FORMS
@pytest.mark.parametrize("fill", list(COPY_FILLS))
def test_copy_form_at_every_kind_of_fill(fill, form):
    q, kn, vn, pool, table = _mk(B=3, M=12, N=40, seed=11)
    assert pdk.copies_pages(pool)
    idx = COPY_FILLS[fill]
    want = _via_paged_gather(q, kn, vn, pool, table, 1, idx, 0.125)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), jnp.int32(idx),
                                         scale=0.125)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    ref = pdk.paged_reference(q, kn, vn, pool, table, 1, jnp.int32(idx),
                              scale=0.125)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@COPY_FORMS
@pytest.mark.parametrize("fills", [
    [5 * 8 + 1, 0, 12 * 8, 3], [0, 0, 6 * 8 + 5, 8], [9 * 8, 3, 0, 0],
    [0, 0, 0, 0]], ids=["empty_between", "empty_first", "empty_last",
                        "all_empty"])
def test_copy_form_prefetches_across_an_empty_slot(fills, form):
    """A block is started by the step before its use, a row's first by
    the row before: a slot with nothing cached between two live ones
    (or first, or last) starts and waits no copy of its own and hands
    the next live row's first block on. Each slot at its own fill."""
    q, kn, vn, pool, table = _mk(B=4, M=12, N=48, seed=12)
    idx = jnp.asarray(fills, jnp.int32)
    want = _via_paged_gather(q, kn, vn, pool, table, 0, fills, 0.125)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(0), idx, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the empty slot attends its own token alone
    for b, fill in enumerate(fills):
        if fill == 0:
            np.testing.assert_allclose(
                np.asarray(got[b, 0]).reshape(2, 2, -1),
                np.broadcast_to(np.asarray(vn[b])[:, :1], (2, 2, 64)),
                rtol=2e-6, atol=2e-6)


@COPY_FORMS
@pytest.mark.parametrize("fill", [1, 5, 8])
def test_copy_form_on_a_row_of_one_page(fill, form):
    q, kn, vn, pool, table = _mk(B=2, M=1, N=4, seed=13)
    want = _via_paged_gather(q, kn, vn, pool, table, 1, fill, 0.125)
    with _support.force_dispatch():
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), jnp.int32(fill),
                                         scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_form_follows_the_leaves(form):
    """Float pages take the copy form — narrow ones and wide ones of a
    128-wide head alike, the pool left unblocked in HBM, two operands —
    and the int8 pool the block-spec form, each leaf an operand a page
    of the step."""
    for quant in (False, True):
        q, kn, vn, pool, table = _mk(quant=quant)
        copies = not quant
        assert pdk.copies_pages(pool) == copies
        with _support.force_dispatch():
            jaxpr = jax.make_jaxpr(lambda *a: pdk.paged_decode_attention(
                *a, pool, table, jnp.int32(1), jnp.int32(20),
                scale=0.125))(q, kn, vn)
        (call,) = [e for e, _ in walk_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert call.params["name"] == "ptpu_paged_decode_attn"
        pages = 1 if copies else pdk._pages_per_step(table.shape[1], 1)
        assert len(call.invars) == 4 + len(pool) * pages


@pytest.mark.parametrize("form", ["wide64", "wide64-3"], indirect=True)
@pytest.mark.parametrize("idx", [7, 17, 32])
def test_a_64_wide_head_on_wide_pages_keeps_the_block_spec_form(idx, form):
    """16 KV heads x 8 tokens x 64: rows of whole lane tiles, but a copy
    of a page would slice half a lane tile out of the pool, so the float
    pool stays on the block-spec form — each leaf an operand a page of
    the step — and matches the gather arm."""
    q, kn, vn, pool, table = _mk(seed=17)
    assert not pdk.copies_pages(pool)
    want = _via_paged_gather(q, kn, vn, pool, table, 1, idx, 0.125)
    with _support.force_dispatch():
        assert pdk.supported(q, pool, table)
        jaxpr = jax.make_jaxpr(lambda *a: pdk.paged_decode_attention(
            *a, pool, table, jnp.int32(1), jnp.int32(idx),
            scale=0.125))(q, kn, vn)
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), jnp.int32(idx),
                                         scale=0.125)
    (call,) = [e for e, _ in walk_eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    pages = pdk._pages_per_step(table.shape[1], 1)
    assert len(call.invars) == 4 + len(pool) * pages
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# OLMoE's pages — 16 KV heads x 16 tokens x 128 — in float32: 8 pages a
# block (128 KB a page a leaf); each case three slots at their own fills
OLMOE_ROWS = {
    # fills off the page edges: a full block, then a part of one
    "off_page_edges": (12, [9 * 16 + 3, 5 * 16 + 7, 12 * 16 - 1]),
    # an idle slot's position past its row: the copies stop at the table
    "past_the_row": (12, [12 * 16 + 9, 40, 12 * 16 + 100]),
    # a row of three pages, shorter than one block
    "shorter_than_a_block": (3, [2 * 16 + 5, 3 * 16, 1]),
}


@pytest.mark.parametrize("form", ["wide"], indirect=True)
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("case", list(OLMOE_ROWS))
def test_copy_form_on_pages_of_sixteen_kv_heads(case, G, form):
    M, fills = OLMOE_ROWS[case]
    Hkv, P, D, N = 16, 16, 128, 40
    rs = np.random.RandomState(50 + G)
    q = jnp.asarray(rs.randn(3, 1, G * Hkv, D), jnp.float32)
    kn, vn = (jnp.asarray(rs.randn(3, Hkv, 1, D), jnp.float32)
              for _ in range(2))
    pool = tuple(jnp.asarray(rs.randn(N + 1, 2, Hkv, P, D), jnp.float32)
                 for _ in range(2))
    ids = rs.permutation(np.arange(1, N + 1))[:3 * M]
    table = jnp.asarray(ids.reshape(3, M).astype(np.int32))
    assert pdk.copies_pages(pool)
    assert pdk._pages_per_block(M, Hkv * P * D * 4) == 8
    idx = jnp.asarray(fills, jnp.int32)
    want = pdk.paged_reference(q, kn, vn, pool, table, 1, idx, scale=0.088)
    with _support.force_dispatch():
        assert pdk.supported(q, pool, table)
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), idx, scale=0.088)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# Laguna's pages — 8 KV heads x 16 tokens x 128, one lane tile of rows —
# under 6 and 9 query heads a KV head (its full and its window layers),
# in float32 (16 pages a block, more than the row): two slots at their
# own fills, off a page edge and past the row; the window rows start at
# their own logical page
EIGHT_HEADS = {"G6-full": (6, None, [0, 0]), "G9-window": (9, 40, [2, 1])}


@pytest.mark.parametrize("form", ["wide"], indirect=True)
@pytest.mark.parametrize("case", list(EIGHT_HEADS))
def test_copy_form_on_pages_of_eight_kv_heads(case, form):
    G, window, base = EIGHT_HEADS[case]
    Hkv, P, D, N, M = 8, 16, 128, 24, 6
    rs = np.random.RandomState(60 + G)
    q = jnp.asarray(rs.randn(2, 1, G * Hkv, D), jnp.float32)
    kn, vn = (jnp.asarray(rs.randn(2, Hkv, 1, D), jnp.float32)
              for _ in range(2))
    pool = tuple(jnp.asarray(rs.randn(N + 1, 2, Hkv, P, D), jnp.float32)
                 for _ in range(2))
    ids = rs.permutation(np.arange(1, N + 1))[:2 * M]
    table = jnp.asarray(ids.reshape(2, M).astype(np.int32))
    assert pdk.copies_pages(pool) and Hkv * P == pdk.LANES
    base = jnp.asarray(base, jnp.int32)
    idx = base * P + jnp.asarray([5 * P + 3, M * P + 9], jnp.int32)
    edge = {} if window is None else {"window": window, "base": base}
    want = pdk.paged_reference(q, kn, vn, pool, table, 1, idx, scale=0.088,
                               **edge)
    with _support.force_dispatch():
        assert pdk.supported(q, pool, table)
        got = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                         jnp.int32(1), idx, scale=0.088,
                                         **edge)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def walk_eqns(jaxpr, path=()):
    """``(eqn, names of the primitives enclosing it)`` for every
    equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk_eqns(sub, path + (eqn.primitive.name,))


def _pallas_calls(closed):
    return [(e.params["grid_mapping"].grid, path)
            for e, path in walk_eqns(closed.jaxpr)
            if e.primitive.name == "pallas_call"]


def test_vmap_over_slots_is_one_call_with_the_slots_in_its_grid():
    """The call's own batching rule: a mapped axis (and a second one
    around it) joins the rows of ONE pallas_call on the unmapped pool —
    jax's rule for a mapped scalar-prefetch operand would wrap the call
    in a ``while`` over the axis and slice every operand."""
    q, kn, vn, pool, table = _mk(B=3, seed=8)
    M = table.shape[1]
    idx = jnp.asarray([2, 15, 31], jnp.int32)

    def one(q, kn, vn, tab, i):
        return pdk.paged_decode_attention(q[None], kn[None], vn[None], pool,
                                          tab[None], jnp.int32(1), i,
                                          scale=0.125)

    def two(*a):
        return jax.vmap(one)(*a)

    twice = tuple(jnp.stack([x, x]) for x in (q, kn, vn, table, idx))
    with _support.force_dispatch():
        assert _pallas_calls(jax.make_jaxpr(jax.vmap(one))(
            q, kn, vn, table, idx)) == [
                ((3, _steps(M, pool)), ("custom_vmap_call",))]
        assert _pallas_calls(jax.make_jaxpr(jax.vmap(two))(*twice)) == [
            ((6, _steps(M, pool)), ("custom_vmap_call",))]
        got = jax.vmap(two)(*twice)
        want = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                          jnp.int32(1), idx, scale=0.125)
    for half in got:
        np.testing.assert_array_equal(np.asarray(half[:, 0]),
                                      np.asarray(want))


def test_vmap_with_a_mapped_pool_falls_back_to_jax_rule():
    """A pool that is itself mapped has no row to fold into: the rule
    hands that case to jax's own batching and stays correct."""
    a = _mk(B=2, seed=40)
    b = _mk(B=2, seed=41)
    stack = jax.tree_util.tree_map(lambda x, y: jnp.stack([x, y]), a, b)

    def call(q, kn, vn, pool, table):
        return pdk.paged_decode_attention(q, kn, vn, pool, table,
                                          jnp.int32(0), jnp.int32(20),
                                          scale=0.125)

    with _support.force_dispatch():
        got = jax.vmap(call)(*stack)
        want = jnp.stack([call(*a), call(*b)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_supported_gates():
    q, _, _, pool, table = _mk()
    with _support.force_dispatch():
        assert pdk.supported(q, pool, table)
        # prefill chunk (T > 1) is not this kernel's job
        assert not pdk.supported(jnp.zeros((2, 4) + q.shape[2:]), pool,
                                 table)
        # head_dim off the MXU grid
        assert not pdk.supported(
            jnp.zeros((2, 1, 4, 32)),
            (jnp.zeros((17, 2, 2, 8, 32)),) * 2, table)
        # page size not sublane-aligned
        assert not pdk.supported(
            jnp.zeros((2, 1, 4, 64)),
            (jnp.zeros((17, 2, 2, 6, 64)),) * 2, table)
        # table batch mismatch
        assert not pdk.supported(q, pool, table[:1])
        # int8 leaves without the 4-leaf scale layout
        assert not pdk.supported(
            q, (jnp.zeros((17, 2, 2, 8, 64), jnp.int8),) * 2, table)
    # no dispatch context off-TPU → fallback
    if not _support.on_tpu():
        assert not pdk.supported(q, pool, table)


def test_fallback_arm_dispatch(monkeypatch):
    """Off-TPU with no force_dispatch the public entry must take the
    einsum fallback (raw_call untouched); under force_dispatch it must
    route through the pallas_call."""
    q, kn, vn, pool, table = _mk(seed=6)
    calls = {}

    def spy(orig):
        def call(*a, **kw):
            calls["n"] = calls.get("n", 0) + 1
            return orig(*a, **kw)
        return call

    for raw in ("raw_call", "raw_copy_call"):
        monkeypatch.setattr(pdk, raw, spy(getattr(pdk, raw)))
    out_f = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                       jnp.int32(0), jnp.int32(10),
                                       scale=0.125)
    if not _support.on_tpu():
        assert calls.get("n", 0) == 0          # fallback arm
    with _support.force_dispatch():
        out_k = pdk.paged_decode_attention(q, kn, vn, pool, table,
                                           jnp.int32(0), jnp.int32(10),
                                           scale=0.125)
    assert calls.get("n", 0) >= 1              # kernel arm engaged
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_f),
                               rtol=2e-5, atol=2e-5)


# -- the block form (ptpu_paged_block_attn): T query rows a slot ----------

def _mk_block(B=3, T=4, M=12, N=40, seed=21):
    """A block step's operands on narrow pages: q [B, T, Hq, D], the
    block's own k/v [B, Hkv, T, D]."""
    _, _, _, pool, table = _mk(B=B, M=M, N=N, seed=seed)
    rs = np.random.RandomState(seed + 100)
    Hkv, D = pool[0].shape[2], pool[0].shape[4]
    q = jnp.asarray(rs.randn(B, T, 2 * Hkv, D), jnp.float32)
    kn = jnp.asarray(rs.randn(B, Hkv, T, D), jnp.float32)
    vn = jnp.asarray(rs.randn(B, Hkv, T, D), jnp.float32)
    return q, kn, vn, pool, table


def _block_via_gather(q, kn, vn, pool, table, layer, fills):
    """The gather arm a slot at a time: ``cached_attention`` on the
    slot's ``PagedCache`` with ``block`` = T, off the kernel."""
    from paddle_tpu.models._common import cached_attention
    from paddle_tpu.models.generation import PagedCache
    T = q.shape[1]
    outs = []
    for b, fill in enumerate(fills):
        out, _ = cached_attention(
            q[b:b + 1], kn[b:b + 1].transpose(0, 2, 1, 3),
            vn[b:b + 1].transpose(0, 2, 1, 3), PagedCache(pool, table[b]),
            jnp.int32(fill), layer=layer, block=T)
        outs.append(out[0])
    return jnp.stack(outs)


BLOCK_FILLS = [[0, 4, 12], [32, 52, 96], [8 * 8, 0, 11 * 8 + 4]]


@COPY_FORMS
@pytest.mark.parametrize("fills", BLOCK_FILLS,
                         ids=["short", "edges", "empty_between"])
def test_block_form_matches_the_gather_arm(fills, form):
    """T = 4 rows a slot, each slot at its own fill: every row sees the
    cached positions before its block's first and all four rows of the
    block, both ways; the kernel under ``vmap`` over slots (the engine's
    call) against the gather arm."""
    q, kn, vn, pool, table = _mk_block()
    want = _block_via_gather(q, kn, vn, pool, table, 1, fills)
    assert pdk.block_supported(q, pool, table) is False      # off the chip
    with _support.force_dispatch():
        assert pdk.block_supported(q, pool, table)
        got = jax.vmap(
            lambda qb, kb, vb, row, idx: pdk.paged_block_attention(
                qb[None], kb[None], vb[None], pool, row[None], 1, idx,
                scale=q.shape[-1] ** -0.5)[0])(
            q, kn, vn, table, jnp.asarray(fills, jnp.int32))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@COPY_FORMS
def test_block_form_is_its_own_kernel(form):
    """The block form lowers to ``ptpu_paged_block_attn``, one call for
    all slots under ``vmap``; a one-token chunk stays
    ``ptpu_paged_decode_attn``; the gate refuses T = 1, a 64-wide head
    on wide pages and the int8 pool."""
    q, kn, vn, pool, table = _mk_block()
    idx = jnp.asarray([4, 8, 12], jnp.int32)
    with _support.force_dispatch():
        closed = jax.make_jaxpr(jax.vmap(
            lambda qb, kb, vb, row, i: pdk.paged_block_attention(
                qb[None], kb[None], vb[None], pool, row[None], 0, i,
                scale=0.125)[0]))(q, kn, vn, table, idx)
        calls = [(e.params["name"], e.params["grid_mapping"].grid)
                 for e, _ in walk_eqns(closed.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert calls == [("ptpu_paged_block_attn",
                          (3, _steps(table.shape[1], pool)))]
        assert not pdk.block_supported(q[:, :1], pool, table)
        wide = tuple(jnp.tile(leaf, (1, 1, 8, 1, 1)) for leaf in pool)
        assert not pdk.block_supported(jnp.tile(q, (1, 1, 8, 1)), wide,
                                       table)
        _, _, _, qpool, _ = _mk(quant=True)
        assert not pdk.block_supported(q, qpool, table[:, :4])


@pytest.mark.parametrize("form", ["wide"], indirect=True)
def test_block_form_on_pages_of_sixteen_kv_heads(form):
    """Pages of 16 KV heads x 8 tokens x 128 (a lane tile of rows) take
    the block form too: T = 4 rows a slot at one query head a KV head,
    against the gather arm."""
    q, kn, vn, pool, table = _mk_block()
    q = q[:, :, :q.shape[2] // 2]                 # G = 1
    assert pool[0].shape[2:] == (16, 8, 128)
    fills = BLOCK_FILLS[2]
    want = _block_via_gather(q, kn, vn, pool, table, 1, fills)
    with _support.force_dispatch():
        assert pdk.block_supported(q, pool, table)
        got = jax.vmap(
            lambda qb, kb, vb, row, idx: pdk.paged_block_attention(
                qb[None], kb[None], vb[None], pool, row[None], 1, idx,
                scale=q.shape[-1] ** -0.5)[0])(
            q, kn, vn, table, jnp.asarray(fills, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
