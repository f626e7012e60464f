"""The paged programs' cache contract: attention reads one layer's pages
through the slot's page-table row (``generation.PagedCache``), the new
k/v go into the donated pool in place (``generation.paged_write``).

Three things are pinned here. The per-layer read is ``paged_gather`` +
layer slice bit for bit. The compiled step and prefill hold no slot's
all-layers view and no copy of the pool (the mechanism is unconditional
in paged mode, so this is its "did it engage"). And the in-place writes
touch only what they own: shared prefix pages, an idle slot's pages and
every page outside a live slot's tail stay byte-identical.
"""

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.monitor import get_stat
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models._common import cached_attention
from paddle_tpu.models.generation import (
    PagedCache, init_paged_cache, paged_gather,
)
from paddle_tpu.serving import GenerationEngine

pytestmark = pytest.mark.gen

VOCAB = 96
L, HQ, HKV, D, P, M, N = 3, 4, 2, 8, 4, 6, 10


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(5)
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=VOCAB, hidden_size=HQ * D, num_layers=L, num_heads=HQ,
        num_kv_heads=HKV, max_seq_len=M * P))


def _random_pool(proto, pages, page_tokens, seed):
    """A pool whose every position holds something: an unwritten page
    that reads as zeros would hide a wrong page id."""
    rs = np.random.RandomState(seed)
    out = []
    for leaf in init_paged_cache(proto, pages, page_tokens):
        if leaf.dtype == jnp.int8:
            a = rs.randint(-127, 128, leaf.shape)
        elif leaf.ndim == 4:                            # int8 scales
            a = rs.uniform(0.01, 0.1, leaf.shape)
        else:
            a = rs.randn(*leaf.shape)
        out.append(jnp.asarray(a, leaf.dtype))
    return tuple(out)


def _same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# rows with null entries: past the end, in the middle, nearly all
ROWS = np.asarray([[3, 1, 5, 2, 0, 0], [7, 0, 4, 9, 6, 0],
                   [8, 10, 0, 0, 0, 0]], np.int32)


@pytest.mark.parametrize("layer", ["int", "scan"])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_per_layer_read_is_paged_gather(model, quant, T, layer):
    """``PagedCache`` in ``paged_gather``'s place changes no bit: the
    layer's view, the attention output, the payload and — through the
    model's layer scan, where ``layer`` is traced — the logits, under
    ``jax.vmap`` over table rows with the pool unmapped."""
    proto = model.init_cache(1, M * P,
                             dtype=jnp.int8 if quant else jnp.bfloat16)
    pool = _random_pool(proto, N, P, seed=T)
    rows = jnp.asarray(ROWS)
    # T = 5 starts inside a page and runs over a page edge
    idx = jnp.asarray([13, 9, 6] if T == 1 else [6, 9, 5], jnp.int32)
    rs = np.random.RandomState(1)

    view = jax.vmap(lambda r: PagedCache(pool, r).read_layer(1))(rows)
    want = jax.vmap(lambda r: tuple(c[1] for c in paged_gather(pool, r)))(
        rows)
    _same(view, want)

    if layer == "int":
        q = jnp.asarray(rs.randn(3, 1, T, HQ, D), jnp.float32)
        k, v = (jnp.asarray(rs.randn(3, 1, T, HKV, D), jnp.float32)
                for _ in range(2))

        def attend(cache_of):
            return jax.jit(jax.vmap(
                lambda r, i, q, k, v: cached_attention(
                    q, k, v, cache_of(r), i, layer=2)))(rows, idx, q, k, v)

        _same(attend(lambda r: PagedCache(pool, r)),
              attend(lambda r: paged_gather(pool, r)))
        return

    ids = jnp.asarray(rs.randint(1, VOCAB, (3, 1, T)), jnp.int32)

    def forward(cache_of, written):
        def one(r, i, x):
            logits, c = model.forward_with_cache(x, cache_of(r), index=i)
            return logits, tuple(written(leaf, i) for leaf in c)
        return jax.jit(jax.vmap(one))(rows, idx, ids)

    _same(forward(lambda r: PagedCache(pool, r), lambda leaf, i: leaf),
          forward(lambda r: paged_gather(pool, r),
                  lambda leaf, i: jax.lax.dynamic_slice_in_dim(
                      leaf, i, T, axis=3)))


# -- structure of the compiled programs --------------------------------------

SLOTS, DEEP, MAXLEN = 3, 8, 64


@pytest.fixture(scope="module")
def deep_model():
    """Eight layers: a per-layer read is an eighth of a slot's view, so
    the bounds below tell the two designs apart."""
    paddle_tpu.seed(6)
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=VOCAB, hidden_size=32, num_layers=DEEP, num_heads=2,
        num_kv_heads=2, max_seq_len=MAXLEN))


_SHAPE = re.compile(r"\b(?:pred|[su]\d+|bf16|f16|f32)\[([\d,]+)\]")


def _array_shapes(hlo: str) -> set:
    return {tuple(int(d) for d in m.group(1).split(","))
            for m in _SHAPE.finditer(hlo)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_program_holds_no_view_and_no_pool_copy(deep_model, program):
    """The optimized step (and the prefill's read): the donated pool is
    the result's pool, the temporaries are smaller than ONE pool leaf,
    and no cache-shaped buffer but the pool is as large as the mapped
    slots' all-layers view."""
    with GenerationEngine(deep_model, slots=SLOTS, max_len=MAXLEN,
                          paged=True, page_tokens=P, queue_max=4) as eng:
        pool = eng._state["cache"]
        compiled = eng.lowered(6)[program].compile()
    leaf = pool[0]
    maxp, (_, _, hkv, _, d) = MAXLEN // P, leaf.shape
    assert leaf.shape == (SLOTS * maxp + 1, DEEP, hkv, P, d)
    hlo = compiled.as_text()

    mem = compiled.memory_analysis()
    pool_bytes = sum(int(x.nbytes) for x in pool)
    assert mem.alias_size_in_bytes >= pool_bytes
    head = hlo[:hlo.index("\n")]
    aliased = {int(o) for o in re.findall(r"\{(\d+)\}: \(\d+, \{\}, \w+-alias",
                                          head)}
    assert set(range(len(pool))) <= aliased, head   # the state's first leaves
    temp, one_leaf = mem.temp_size_in_bytes, int(leaf.nbytes)
    assert temp < one_leaf

    shapes = _array_shapes(hlo)
    mapped = (SLOTS,) if program == "decode" else ()
    for gone in (mapped + (maxp, DEEP, hkv, P, d),       # leaf[table]
                 mapped + (DEEP, hkv, maxp, P, d),       # moved
                 mapped + (DEEP, 1, hkv, maxp * P, d),   # the view
                 mapped + (DEEP, hkv, maxp * P, d)):
        assert gone not in shapes, gone
    view = int(np.prod(mapped + (maxp, DEEP, hkv, P, d)))
    big = {s for s in shapes if len(s) > 3 and int(np.prod(s)) >= view}
    assert big <= {leaf.shape}, big


# -- in-place hazards ----------------------------------------------------------

def _changed(before, after):
    """``{(page, offset)}`` at which any leaf differs."""
    out = set()
    for b, a in zip(before, after):
        diff = np.asarray(b, np.float32) != np.asarray(a, np.float32)
        diff = diff.reshape(diff.shape[:4] + (-1,)).any(axis=(1, 2, 4))
        out |= {(int(p), int(o)) for p, o in zip(*np.nonzero(diff))}
    return out


def _hand_state(eng, pos, seed):
    state = eng._init_state()
    proto = eng._model.init_cache(1, eng.max_len, dtype=eng._cache_dtype)
    state["cache"] = _random_pool(proto, eng._pool.num_pages,
                                  eng._page_tokens, seed)
    state["pos"] = jnp.asarray(pos, jnp.int32)
    state["tok"] = jnp.asarray([5, 9, 2], jnp.int32)
    return state


# two generations on one template (pages 1, 2, as the prefix cache maps
# them) with tails of their own, and a slot that sits idle on its pages
TABLE = np.zeros((SLOTS, MAXLEN // P), np.int32)
TABLE[0, :7] = [1, 2, 3, 4, 5, 6, 20]
TABLE[1, :7] = [1, 2, 7, 8, 9, 10, 21]
TABLE[2, :3] = [11, 12, 13]


def _where(slot, positions):
    return {(int(TABLE[slot, p // P]), p % P) for p in positions}


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_decode_steps_write_only_own_tail_positions(deep_model, quant):
    steps = 3 * P
    pos = [2 * P + 1, 2 * P + 3, 5]
    with GenerationEngine(deep_model, slots=SLOTS, max_len=MAXLEN,
                          paged=True, page_tokens=P, pages=24,
                          cache_dtype=jnp.int8 if quant else None,
                          queue_max=4) as eng:
        state = _hand_state(eng, pos, seed=3)
        before = [np.asarray(x) for x in state["cache"]]
        pt, active = jnp.asarray(TABLE), jnp.asarray([True, True, False])
        for _ in range(steps):
            state, _ = eng._step(state, pt, active)
        after = [np.asarray(x) for x in state["cache"]]
    assert list(np.asarray(state["pos"])) == [pos[0] + steps,
                                              pos[1] + steps, 5]
    own = (_where(0, range(pos[0], pos[0] + steps))
           | _where(1, range(pos[1], pos[1] + steps)))
    live = {c for c in _changed(before, after) if c[0] != 0}
    assert live == own
    # the idle slot wrote somewhere: the null page took it
    assert (0, 5 % P) in _changed(before, after)


def test_spec_step_sends_rejected_drafts_to_null_page(deep_model):
    K = 3
    pos = [2 * P + 1, 2 * P + 2, 5]           # slot 1's window ends a page
    with GenerationEngine(deep_model, slots=SLOTS, max_len=MAXLEN,
                          paged=True, page_tokens=P, pages=24, spec_k=K,
                          spec_mode="ngram", queue_max=4) as eng:
        pt, active = jnp.asarray(TABLE), jnp.asarray([True, True, False])
        _, pick = eng._step(_hand_state(eng, pos, seed=4), pt, active)
        # slot 0: a first draft the target does not pick — all rejected;
        # slot 1: the target's own pick first — accepted, position + 1 lands
        drafts = np.full((SLOTS, K), 1, np.int32)
        drafts[0, 0] = (int(pick[0]) + 1) % VOCAB
        drafts[1, 0] = int(pick[1])
        state = _hand_state(eng, pos, seed=4)
        before = [np.asarray(x) for x in state["cache"]]
        state, _, emit = eng._spec_step(
            state, pt, active, jnp.asarray(drafts),
            jnp.asarray([K, K, 0], jnp.int32))
        after = [np.asarray(x) for x in state["cache"]]
    emit = [int(e) for e in emit]
    assert emit[0] == 1 and emit[1] >= 2 and emit[2] == 0
    own = (_where(0, range(pos[0], pos[0] + emit[0]))
           | _where(1, range(pos[1], pos[1] + emit[1])))
    changed = _changed(before, after)
    assert {c for c in changed if c[0] != 0} == own
    assert (0, (pos[0] + 1) % P) in changed     # a rejected draft's k/v


def test_shared_template_pages_survive_their_sharers(model):
    """Through the engine and its prefix cache: the pages a first
    request leaves cached are byte-identical after two more requests
    decoded on top of them side by side."""
    rs = np.random.RandomState(9)
    template = rs.randint(1, VOCAB, 2 * P + 1).astype(np.int32)

    def run(eng, item, n):
        gid = eng.start(np.concatenate([template, item]), n)
        while not eng.poll(gid, wait_s=0.5)["done"]:
            pass

    with GenerationEngine(model, slots=3, max_len=M * P, paged=True,
                          page_tokens=P, prefix_cache=True,
                          queue_max=4) as eng:
        run(eng, np.asarray([7], np.int32), 2)
        held = [p for p in range(1, eng._pool.num_pages + 1)
                if eng._pool.refcount(p)]
        assert len(held) == 2
        before = [np.asarray(x)[held] for x in eng._state["cache"]]
        hits = get_stat("gen/prefix_hits") or 0
        threads = [threading.Thread(target=run, args=(
            eng, np.asarray([11 + i, 3], np.int32), 3 * P - 3))
            for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert get_stat("gen/prefix_hits") - hits == 2
        after = [np.asarray(x)[held] for x in eng._state["cache"]]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)
