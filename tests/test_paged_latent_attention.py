"""The latent arm of the paged decode kernel
(``ops/pallas/paged_decode_attention.py``: ``ptpu_paged_latent_decode_attn``).

In interpreter mode the kernel behind ``models._common.latent_attention``
must reproduce that function's gather arm — one layer's pages gathered
through the row, the absorbed einsum lines, a joint float32 softmax —
per slot and under ``jax.vmap``: at every kind of fill (nothing, one
row, a page edge, mid-page, a block edge, the full table), through
scattered page ids and through runs of ids (one strided copy a group),
past stale rows and the null page, at the layer its operand names; and
its gate must send everything else to the einsum lines.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import _common
from paddle_tpu.models.generation import PagedCache
from paddle_tpu.ops.pallas import _support
from paddle_tpu.ops.pallas import paged_decode_attention as pdk
from test_paged_decode_attention import walk_eqns

B, H, N_, R, C, V = 3, 4, 16, 8, 16, 24
P, M, L, PAGES, W = 8, 24, 3, 80, 128
SCALE = 0.2


@pytest.fixture(autouse=True, params=["default", 8])
def pages_per_block(request, monkeypatch):
    """Every test at the kernel's own block (the whole 24-page table in
    one) and at 8 pages: three blocks of one copy group each, so fills
    end inside a block, on a block edge and on the table's end."""
    if request.param != "default":
        monkeypatch.setattr(pdk, "_latent_pages_per_block",
                            lambda M, P: request.param)
    return request.param


def _mk(seed=0, dtype=jnp.float32, table="scattered", width=W):
    rs = np.random.RandomState(seed)

    def f(*shape):
        return jnp.asarray(rs.randn(*shape), dtype)

    chunk = (f(B, 1, H, N_), f(B, 1, H, R), f(B, 1, C), f(B, 1, R))
    w_kc, w_vc = f(C, H, N_) * 0.3, f(C, H, V) * 0.3
    leaf = np.zeros((PAGES + 1, L, 1, P, width), np.float32)
    leaf[..., :C + R] = rs.randn(PAGES + 1, L, 1, P, C + R)
    if table == "scattered":
        ids = rs.permutation(np.arange(1, PAGES + 1))[:B * M]
    else:                               # runs: each slot's pages in order
        ids = np.arange(1, B * M + 1)
    return (chunk, w_kc, w_vc, (jnp.asarray(leaf, dtype),),
            jnp.asarray(ids.reshape(B, M).astype(np.int32)))


def _attend(args, index, layer, kernel):
    """``latent_attention`` over the slots, as the engine's step calls
    it: one slot a call under ``vmap``."""
    chunk, w_kc, w_vc, pool, table = args

    def one(qn, qr, c, k, row, i):
        return _common.latent_attention(
            qn[None], qr[None], c[None], k[None], w_kc, w_vc, SCALE,
            cache=PagedCache(pool, row), index=i, layer=layer)[0][0]

    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    before = _common.paged_attn_arms["paged_kernel"]
    if kernel:
        with _support.force_dispatch():
            out = jax.jit(jax.vmap(one))(*chunk, table, idx)
        assert _common.paged_attn_arms["paged_kernel"] == before + 1
    else:
        out = jax.jit(jax.vmap(one))(*chunk, table, idx)
        assert _common.paged_attn_arms["paged_kernel"] == before
    return np.asarray(out)


FILLS = {"nothing": 0, "one_row": 1, "page_edge": 2 * P, "mid_page": 5 * P + 3,
         "block_edge": 8 * P, "past_block_edge": 8 * P + 1,
         "full_table": M * P}


@pytest.mark.parametrize("table", ["scattered", "runs"])
@pytest.mark.parametrize("fill", list(FILLS))
def test_kernel_matches_the_gather_arm(fill, table):
    args = _mk(seed=1, table=table)
    got = _attend(args, FILLS[fill], 1, kernel=True)
    want = _attend(args, FILLS[fill], 1, kernel=False)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_per_slot_index_vector():
    """Each slot masks at its own fill, an empty one beside a full one."""
    args = _mk(seed=2)
    idx = [0, 5 * P + 3, M * P]
    got = _attend(args, idx, 0, kernel=True)
    want = _attend(args, idx, 0, kernel=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the empty slot attends its own row alone: u is the row's value
    chunk, _, w_vc, _, _ = args
    np.testing.assert_allclose(
        got[0, 0], np.einsum("c,chv->hv", np.asarray(chunk[2][0, 0]),
                             np.asarray(w_vc)), rtol=2e-5, atol=2e-5)


def test_layer_operand_selects_the_layer():
    args = _mk(seed=3)
    outs = []
    for layer in range(L):
        got = _attend(args, 9 * P + 2, jnp.int32(layer), kernel=True)
        want = _attend(args, 9 * P + 2, layer, kernel=False)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=f"layer {layer}")
        outs.append(got)
    assert not np.allclose(outs[0], outs[1])


def test_stale_rows_and_the_null_page_do_not_contribute():
    """Rows at or past the fill — the tail of the last live page, the
    slot's reserved pages after it (copied with their group, masked),
    everything the table does not name — and the null page an unmapped
    slot reads: poisoned, they change nothing."""
    chunk, w_kc, w_vc, pool, table = _mk(seed=4)
    idx = [11 * P + 5, 3, 0]
    tab = np.asarray(table).copy()
    tab[2] = 0                                          # unmapped slot
    leaf = np.asarray(pool[0]).copy()
    dirty = leaf.copy()
    live = set()
    for b in range(B):
        pages = -(-idx[b] // P)
        live |= set(tab[b, :pages])
        if idx[b] % P:
            dirty[tab[b, pages - 1], :, :, idx[b] % P:] = 1e4
    for page in range(PAGES + 1):
        if page not in live:
            dirty[page] = -1e4                          # null page included
    clean = (chunk, w_kc, w_vc, (jnp.asarray(leaf),), jnp.asarray(tab))
    poisoned = (chunk, w_kc, w_vc, (jnp.asarray(dirty),), jnp.asarray(tab))
    a = _attend(clean, idx, 2, kernel=True)
    b = _attend(poisoned, idx, 2, kernel=True)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(a, _attend(clean, idx, 2, kernel=False),
                               rtol=2e-5, atol=2e-5)


def test_page_placement_does_not_matter():
    """One logical sequence a slot under two placements — scattered ids
    (a copy a page) and ids that run upward (one strided copy a group):
    equal to the last bit."""
    chunk, w_kc, w_vc, pool, table = _mk(seed=5, table="scattered")
    _, _, _, _, runs = _mk(seed=5, table="runs")
    leaf = np.asarray(pool[0])
    moved = np.zeros_like(leaf)
    moved[np.asarray(runs).ravel()] = leaf[np.asarray(table).ravel()]
    a = _attend((chunk, w_kc, w_vc, pool, table), 13 * P + 1, 1, kernel=True)
    b = _attend((chunk, w_kc, w_vc, (jnp.asarray(moved),), runs),
                13 * P + 1, 1, kernel=True)
    np.testing.assert_array_equal(a, b)


def test_bf16_operands_float32_state():
    """The stated precision: operands in the model's dtype into the
    products, float32 accumulation and softmax state — in bf16 the
    kernel is as near the float32 answer as the gather arm is."""
    exact = _attend(_mk(seed=6), 17 * P + 4, 1, kernel=False)
    args = _mk(seed=6, dtype=jnp.bfloat16)
    got = _attend(args, 17 * P + 4, 1, kernel=True).astype(np.float32)
    ref = _attend(args, 17 * P + 4, 1, kernel=False).astype(np.float32)

    def rms(x):
        return np.sqrt(np.mean((x - exact) ** 2) / np.mean(exact ** 2))

    assert rms(got) <= 1.5 * rms(ref) + 1e-3, (rms(got), rms(ref))
    assert rms(got) < 3e-2


def _calls(jaxpr):
    return [(e, path) for e, path in walk_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


def test_vmap_over_slots_is_one_call_with_the_slots_in_its_grid():
    """The batching rule shared with the K/V kernel: the mapped slot
    axis joins the rows of ONE call on the unmapped pool — no ``while``
    over slots, no gathered view of any slot."""
    chunk, w_kc, w_vc, pool, table = _mk(seed=7)

    def one(qn, qr, c, k, row, i):
        return _common.latent_attention(
            qn[None], qr[None], c[None], k[None], w_kc, w_vc, SCALE,
            cache=PagedCache(pool, row), index=i, layer=jnp.int32(1))[0]

    idx = jnp.asarray([3, 40, 100], jnp.int32)
    KP = pdk._latent_pages_per_block(M, P)
    views = {(B, M, 1, P, W), (B, 1, 1, M * P, W), (B, M * P, W)}

    def shapes(jaxpr):
        return {tuple(v.aval.shape) for e, _ in walk_eqns(jaxpr.jaxpr)
                for v in e.outvars if hasattr(v.aval, "shape")}

    with _support.force_dispatch():
        jaxpr = jax.make_jaxpr(jax.vmap(one))(*chunk, table, idx)
    (call, path), = _calls(jaxpr)
    assert call.params["name"] == "ptpu_paged_latent_decode_attn"
    assert call.params["grid_mapping"].grid == (B, 1 - (-M // KP))
    assert "while" not in path, path
    assert not views & shapes(jaxpr)
    gather = jax.make_jaxpr(jax.vmap(one))(*chunk, table, idx)
    assert not _calls(gather)
    assert views & shapes(gather)                   # the check sees them


def test_gate_sends_everything_else_to_the_einsum_lines():
    (q_nope, *_), _, _, pool, table = _mk()
    row = table[:1]
    q1 = q_nope[:1]
    with _support.force_dispatch():
        assert pdk.latent_supported(q1, pool, row, C)
        # a prefill chunk behind a cached prefix
        assert not pdk.latent_supported(
            jnp.zeros((1, 4, H, N_)), pool, row, C)
        # an integer leaf
        assert not pdk.latent_supported(
            q1, (pool[0].astype(jnp.int8),), row, C)
        # a row that is not whole lane tiles (the published 576)
        assert not pdk.latent_supported(
            q1, (jnp.zeros((PAGES + 1, L, 1, P, 576)),), row, C)
        # the K/V layouts: leaves per head, two or four of them
        assert not pdk.latent_supported(q1, pool * 2, row, C)
        assert not pdk.latent_supported(
            q1, (jnp.zeros((PAGES + 1, L, 2, P, W)),), row, C)
        # pages that are not whole sublane tiles; a table of other rows
        assert not pdk.latent_supported(
            q1, (jnp.zeros((PAGES + 1, L, 1, 6, W)),), row, C)
        assert not pdk.latent_supported(q1, pool, table, C)
        # a multi-device mesh: no partitioned unit for the paged layout
        from paddle_tpu.parallel import mesh as mesh_mod
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        with mesh_mod.MeshContext(mesh):
            assert _support.dispatch_mode() == "partitioned"
            assert not pdk.latent_supported(q1, pool, row, C)
    # the CPU, no force context
    if not _support.on_tpu():
        assert not pdk.latent_supported(q1, pool, row, C)


def test_compiled_gate_asks_for_whole_tiles(monkeypatch):
    """Where Mosaic compiles the kernel a page is whole tiles of the
    leaf's dtype (16 rows of bf16, 8 of float32) and the value slice
    whole lane tiles; the interpreter takes any."""
    monkeypatch.setattr(_support, "on_tpu", lambda: True)
    monkeypatch.setattr(_support, "single_device", lambda: True)
    q = jnp.zeros((1, 1, H, N_), jnp.bfloat16)
    row = jnp.zeros((1, M), jnp.int32)

    def pool(p, dtype):
        return (jnp.zeros((4, L, 1, p, 256), dtype),)

    assert pdk.latent_supported(q, pool(16, jnp.bfloat16), row, 128)
    assert pdk.latent_supported(q, pool(8, jnp.float32), row, 128)
    assert not pdk.latent_supported(q, pool(8, jnp.bfloat16), row, 128)
    assert not pdk.latent_supported(q, pool(16, jnp.bfloat16), row, 144)
