"""The engine's sampler follows what the live slots of a step ask for.

``serving.engine._sample`` picks for ``[N, V]`` logits in one of three
arms — ``argmax`` alone, ``categorical(key, logits / temperature)``, one
descending sort — chosen by a ``lax.switch`` on scalars reduced over
the live rows. The contract held here: every greedy and every
restricted row gets the token the former per-slot two-sort sampler
(kept below, verbatim, as the plain reference) gave for the same key;
an unrestricted sampling row gets ``categorical(key, logits /
temperature)``; a row's token never depends on the arm its co-tenants
pulled the call into; a retired slot's stale parameters arm nothing.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core import trace
from paddle_tpu.core.flags import get_flags, set_flags
from paddle_tpu.core.monitor import get_stat
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.engine import _sample

pytestmark = pytest.mark.gen

V = 64
GREEDY, PLAIN, SORTED = 0, 1, 2


def _reference_slot(logits, key, temperature, top_k, top_p):
    """The per-slot sampler as it stood before the arms (two full sorts
    whatever the request asks), verbatim: the plain reference."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        V = logits.shape[-1]
        lt = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
        # top-k via the kth-largest threshold, k traced (take clamps indices)
        asc = jnp.sort(lt, axis=-1)
        k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
        kth = jnp.take(asc, V - k_eff)
        lt = jnp.where(lt < kth, -jnp.inf, lt)
        # nucleus over what survived top-k (the sample_logits ordering)
        desc = jnp.sort(lt, axis=-1)[::-1]
        probs = jax.nn.softmax(desc)
        cum = jnp.cumsum(probs)
        keep = cum - probs < top_p              # always keeps the top-1
        thr = jnp.min(jnp.where(keep, desc, jnp.inf))
        lt = jnp.where(lt < thr, -jnp.inf, lt)
        sampled = jax.random.categorical(key, lt).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)


_reference = jax.jit(jax.vmap(_reference_slot))
_sampler = jax.jit(_sample)


@jax.jit
def _categorical(logits, keys, temperature):
    return jax.vmap(jax.random.categorical)(
        keys, logits.astype(jnp.float32) / temperature[:, None]).astype(
        jnp.int32)


def _logits(kind, n, seed=0):
    """``ties``: half-integer steps, so several entries share the k-th
    value exactly; ``steep``: a spread whose float32 cumulative sum
    reaches 1.0 long before the row ends; ``bf16``: a model's dtype."""
    rs = np.random.RandomState(seed)
    if kind == "ties":
        return jnp.asarray(rs.randint(-6, 7, (n, V)) * 0.5, jnp.float32)
    if kind == "steep":
        rows = np.stack([rs.permutation(np.linspace(0.0, -60.0, V))
                         for _ in range(n)])
        return jnp.asarray(rows, jnp.float32)
    return jnp.asarray(rs.randn(n, V) * 3.0, jnp.bfloat16)


def _keys(n, seed=11):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _params(rows):
    t, k, p = zip(*rows)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


def _arm_of(rows, live):
    arm = GREEDY
    for (t, k, p), on in zip(rows, live):
        if on and t > 0:
            arm = max(arm, SORTED if (k > 0 or p < 1.0) else PLAIN)
    return arm


def _expected(logits, keys, rows):
    """A row's token whatever the arm: the reference's for a greedy or
    a restricted row, the plain draw for an unrestricted sampling one."""
    t, k, p = _params(rows)
    ref = np.asarray(_reference(logits, keys, t, k, p))
    plain = np.asarray(_categorical(logits, keys, jnp.maximum(t, 1e-6)))
    want = ref.copy()
    for i, (ti, ki, pi) in enumerate(rows):
        if ti > 0 and ki <= 0 and pi >= 1.0:
            want[i] = plain[i]
    return want


@pytest.mark.parametrize("kind", ("ties", "steep", "bf16"))
@pytest.mark.parametrize("top_p", (0.3, 0.9, 1.0))
@pytest.mark.parametrize("top_k", (0, 1, 7, V))
@pytest.mark.parametrize("temperature", (0.0, 0.7, 1.3))
def test_uniform_batch_matches_the_two_sort_reference(temperature, top_k,
                                                      top_p, kind):
    n = 5
    rows = [(temperature, top_k, top_p)] * n
    logits, keys = _logits(kind, n, seed=top_k), _keys(n, seed=top_k + 3)
    toks, arm = _sampler(logits, keys, *_params(rows), jnp.ones((n,), bool))
    assert int(arm) == _arm_of(rows, [True] * n)
    np.testing.assert_array_equal(np.asarray(toks),
                                  _expected(logits, keys, rows))
    if temperature == 0.0:
        np.testing.assert_array_equal(
            np.asarray(toks), np.asarray(jnp.argmax(logits, axis=-1)))


MIX = [(0.0, 0, 1.0),        # greedy
       (0.9, 0, 1.0),        # samples, unrestricted
       (0.7, 7, 1.0),        # top-k alone
       (1.3, 0, 0.3),        # nucleus alone
       (0.8, 5, 0.9),        # both
       (0.0, 7, 0.5)]        # greedy with restricting params: argmax


@pytest.mark.parametrize("kind", ("ties", "steep", "bf16"))
@pytest.mark.parametrize("live,arm", [
    ((1, 1, 1, 1, 1, 1), SORTED),
    ((1, 1, 0, 0, 0, 1), PLAIN),     # restricted rows retired: no sort
    ((1, 0, 0, 0, 0, 1), GREEDY),    # sampling rows retired: argmax alone
    ((1, 0, 0, 1, 0, 0), SORTED),
    ((0, 0, 0, 0, 0, 0), GREEDY),
], ids=("all", "plain", "greedy", "one_nucleus", "none"))
def test_mixed_batch_rows_do_not_depend_on_the_arm(kind, live, arm):
    """The live rows decide the arm — a retired slot that still carries
    a restricted request's parameters arms nothing — and whatever the
    arm, every row that its own request could reach there gets the
    token it would get alone."""
    logits, keys = _logits(kind, len(MIX), seed=5), _keys(len(MIX))
    on = jnp.asarray(live, bool)
    toks, got = _sampler(logits, keys, *_params(MIX), on)
    assert int(got) == arm == _arm_of(MIX, live)
    want = _expected(logits, keys, MIX)
    toks = np.asarray(toks)
    for i, row in enumerate(MIX):
        if _arm_of([row], [True]) <= arm:      # dead rows included
            assert toks[i] == want[i], (i, row)
        if live[i]:
            solo, solo_arm = _sampler(
                logits[i:i + 1], keys[i:i + 1], *_params([row]),
                jnp.ones((1,), bool))
            assert int(solo_arm) == _arm_of([row], [True])
            assert int(solo[0]) == toks[i] == want[i], (i, row)


def _sorts(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "sort"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _sorts(sub)
    return n


def test_one_switch_and_one_sort_in_its_last_arm():
    """The program: one three-armed conditional on a scalar, no sort
    outside it, none in the greedy and plain arms, ONE in the third
    (the reference holds two)."""
    args = (_logits("ties", 4), _keys(4), *_params(MIX[:4]),
            jnp.ones((4,), bool))
    jaxpr = jax.make_jaxpr(_sample)(*args).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert conds[0].invars[0].aval.shape == ()
    assert [_sorts(b.jaxpr) for b in conds[0].params["branches"]] == [
        0, 0, 1]
    assert _sorts(jaxpr) == 1
    ref = jax.make_jaxpr(jax.vmap(_reference_slot))(*args[:-1]).jaxpr
    assert _sorts(ref) == 2


# -- through the engine ------------------------------------------------------

VOCAB = 96
RESTRICTED = dict(temperature=0.8, top_k=7, top_p=0.9, seed=42)
OTHER = dict(temperature=1.2, top_k=0, top_p=0.6, seed=5)
PLAIN_KW = dict(temperature=0.9, top_k=0, top_p=1.0, seed=9)
ENGINES = {"contiguous": dict(),
           "paged": dict(paged=True, page_tokens=8, pages=32,
                         prefill_chunk=4)}


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(7)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                           num_heads=2, num_kv_heads=2, max_seq_len=64)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module",
                params=[(layout, depth) for layout in sorted(ENGINES)
                        for depth in (0, 1)],
                ids=lambda p: f"{p[0]}-depth{p[1]}")
def eng(request, model):
    layout, depth = request.param
    with GenerationEngine(model, slots=3, max_len=48, queue_max=8,
                          async_depth=depth, **ENGINES[layout]) as e:
        yield e


@pytest.fixture
def tracing():
    saved = get_flags(["trace", "trace_buffer"])
    trace.clear()
    set_flags({"trace_buffer": 4096, "trace": True})
    yield
    set_flags(saved)
    trace.clear()


def _drain(engine, gen_id, wait_s=0.5):
    toks, n = [], 0
    while True:
        doc = engine.poll(gen_id, start=n, wait_s=wait_s)
        toks += doc["tokens"]
        n = len(toks)
        if doc["done"]:
            assert doc["error"] is None
            return toks


def _wait(engine, pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred(engine.stats()):
            return True
        time.sleep(0.01)
    return False


def _solo(model, prompt, n, kw):
    if kw is None:
        out = generate(model, prompt[None], n)
    else:
        out = generate(model, prompt[None], n,
                       temperature=kw["temperature"], top_k=kw["top_k"],
                       top_p=kw["top_p"],
                       key=jax.random.PRNGKey(kw["seed"]))
    return [int(t) for t in np.asarray(out)[0, prompt.size:]]


def _beside(eng, cotenants, prompt, n, kw):
    """``prompt``'s stream served while every co-tenant (started first,
    paced so that it outlives the stream) holds a slot."""
    eng.step_wait_s = 0.01
    try:
        gids = [eng.start(p, 40, **(k or {})) for p, k in cotenants]
        assert _wait(eng, lambda s: s["active"] == len(gids))
        toks = _drain(eng, eng.start(prompt, n, **kw))
        assert eng.stats()["active"] == len(gids)    # they were there
    finally:
        eng.step_wait_s = 0.0
    return toks, [_drain(eng, g) for g in gids]


def test_stream_does_not_depend_on_its_cotenants(model, eng):
    """The same (prompt, seed, temperature, top_k, top_p) yields the
    same stream alone, beside greedy co-tenants and beside another
    restricted stream — each of them its own solo ``generate()``."""
    rs = np.random.RandomState(31)
    p, a, b = (rs.randint(0, VOCAB, (n,)).astype(np.int32)
               for n in (6, 5, 7))
    for kw in (RESTRICTED, PLAIN_KW):
        want = _solo(model, p, 8, kw)
        assert _drain(eng, eng.start(p, 8, **kw)) == want
        for cotenants in ([(a, None), (b, None)],
                          [(a, OTHER), (b, None)],
                          [(a, OTHER), (b, PLAIN_KW)]):
            toks, others = _beside(eng, cotenants, p, 8, kw)
            assert toks == want, (kw, cotenants)
            for (q, k), got in zip(cotenants, others):
                assert got == _solo(model, q, 40, k), (kw, k)


def test_sorted_steps_follow_the_restricted_stream(model, eng, tracing):
    """``sort_slots`` on ``gen/decode_step`` and
    ``stats()["sample_sorted_steps"]`` read 0 while every live stream
    is greedy or unrestricted, grow while a restricted stream is live
    and stop when it retires (its co-tenant still running)."""
    rs = np.random.RandomState(32)
    p, a = (rs.randint(0, VOCAB, (n,)).astype(np.int32) for n in (6, 5))

    def steps():
        return [s["attrs"]["sort_slots"] for s in trace.get_spans()
                if s["name"] == "gen/decode_step"]

    base, stat0 = eng.stats()["sample_sorted_steps"], get_stat(
        "gen/sample_sorted_steps")
    _drain(eng, eng.start(a, 6))
    _drain(eng, eng.start(a, 6, **PLAIN_KW))
    assert _wait(eng, lambda s: s["pending_steps"] == 0)
    assert eng.stats()["sample_sorted_steps"] == base
    assert steps() and not any(steps())

    eng.step_wait_s = 0.01
    try:
        long = eng.start(a, 40)
        assert _wait(eng, lambda s: s["active"] == 1)
        _drain(eng, eng.start(p, 8, **RESTRICTED))
        assert _wait(eng, lambda s: s["active"] == 1)
        after = eng.stats()["sample_sorted_steps"]
        # 7 decode steps follow the prefill's token; a lagged one may
        # have been dispatched before the last token was read back
        assert 7 <= after - base <= 8
        _drain(eng, long)
    finally:
        eng.step_wait_s = 0.0
    assert _wait(eng, lambda s: s["pending_steps"] == 0)
    assert eng.stats()["sample_sorted_steps"] == after
    assert get_stat("gen/sample_sorted_steps") - stat0 == after - base
    seen = steps()
    assert set(seen) == {0, 1} and sum(seen) == after - base
    assert not any(seen[-5:])          # the co-tenant's last steps alone
