"""The paged decode step on its kernel arm: ``cached_attention`` hands a
one-token chunk on a ``PagedCache`` to ``ptpu_paged_decode_attn`` where
the kernel's gate holds — ``latent_attention`` to
``ptpu_paged_latent_decode_attn`` where the latent one's does — and the
engine's ``vmap`` over slots folds into ONE call a layer (the kernels'
shared batching rule). Everything here runs the kernels through the
interpreter (``_support.force_dispatch``) and holds them to the gather +
einsum arm — the same step with the gates shut, every other kernel
dispatched alike on both sides.

Pinned: tokens (float32, and over the int8 pool) and logits (bf16,
within ``chip_smoke``'s tolerance for two evaluations of one
mathematics) agree on slots at different fills, an idle slot on the
null page, a fill on a page edge and a template shared through the real
prefix cache; the step's jaxpr holds one ``pallas_call`` a layer body
whose grid holds the slots, no loop over slots and no gathered view;
the programs that must stay on the gather arm (prefill chunk, verify
window — of the latent model too) lower to the same text whether or not
the gates are open, and the K/V step does with the latent arm there or
not; and the step compiled for the v5e keeps the pool in place. The
latent model's step (``DeepseekV3ForCausalLM`` at the tiny preset) is
held the same way. The K/V model's pages of 2 KV heads x 8 tokens are
narrower than a lane tile, the ``wide`` model's (16 KV heads x 8 tokens
x 128) one lane tile of rows, OLMoE's kind of page: both float steps
take the kernel's copy form (``stats()["decode_attn"] ==
"paged_copy_kernel"``), the int8 step the block-spec form.
"""

import contextlib
import dataclasses
import functools
import hashlib
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.monitor import get_stat
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import PagedCache
from paddle_tpu.ops.pallas import _support
from paddle_tpu.ops.pallas import paged_decode_attention as pdk
from paddle_tpu.serving import GenerationEngine

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402
from test_paged_decode_attention import walk_eqns  # noqa: E402
from test_paged_view import _random_pool  # noqa: E402

pytestmark = pytest.mark.gen

VOCAB = 96
L, HQ, HKV, D, P = 2, 4, 2, 64, 8
SLOTS, MAXLEN = 4, 64
M = MAXLEN // P
STEPS = 34


def _llama(dtype, seed=11, intermediate_size=None, **kw):
    paddle_tpu.seed(seed)
    args = dict(vocab_size=VOCAB, hidden_size=HQ * D, num_layers=L,
                num_heads=HQ, num_kv_heads=HKV, max_seq_len=MAXLEN)
    args.update(kw)
    cfg = dataclasses.replace(LlamaConfig.tiny(**args), dtype=dtype)
    if intermediate_size:
        cfg = dataclasses.replace(cfg, intermediate_size=intermediate_size)
    return LlamaForCausalLM(cfg)


def _latent(dtype, seed=12, **kw):
    from paddle_tpu.models.deepseek_v3 import (
        DeepseekV3Config, DeepseekV3ForCausalLM,
    )
    paddle_tpu.seed(seed)
    args = dict(vocab_size=VOCAB, max_seq_len=MAXLEN, dtype=dtype)
    args.update(kw)
    return DeepseekV3ForCausalLM(DeepseekV3Config.tiny(**args))


def _wide(dtype, seed=14, **kw):
    """16 KV heads x 8 tokens x 128: a page of one lane tile of rows, the
    copy form's (OLMoE's heads and head width, at half its page); a
    narrow MLP keeps the model small."""
    return _llama(dtype, seed, hidden_size=16 * 128, num_heads=16,
                  num_kv_heads=16, intermediate_size=256, **kw)


FAMILIES = {"llama": _llama, "latent": _latent, "wide": _wide}


@pytest.fixture(scope="module")
def model():
    return _llama("float32")


@pytest.fixture(scope="module")
def models(model):
    return {"llama": model, "latent": _latent("float32"),
            "wide": _wide("float32")}


GATES = {"paged_kernel": (), "gather": ("supported", "latent_supported"),
         "kv_kernel_only": ("latent_supported",)}


@contextlib.contextmanager
def arm(name):
    """Trace under it: every kernel dispatched (interpreted), the paged
    ones refused for ``"gather"``, the latent one alone for
    ``"kv_kernel_only"``."""
    with _support.force_dispatch(), contextlib.ExitStack() as stack:
        for gate in GATES[name]:
            real = getattr(pdk, gate)
            setattr(pdk, gate, lambda *a, **k: False)
            stack.callback(setattr, pdk, gate, real)
        yield


# two slots on one template's pages (1, 2) with tails of their own, a
# slot whose fill sits on a page edge, and an idle slot mapped nowhere
# (the null page)
TABLE = np.zeros((SLOTS, M), np.int32)
TABLE[0] = [1, 2, 3, 4, 5, 6, 7, 8]
TABLE[1] = [1, 2, 9, 10, 11, 12, 13, 14]
TABLE[2] = [15, 16, 17, 18, 19, 20, 21, 22]
POS = [2 * P + 3, 2 * P + 5, 3 * P, 0]
ACTIVE = [True, True, True, False]
PAGES = 24


def _hand_state(eng, seed):
    state = eng._init_state()
    proto = eng._model.init_cache(1, MAXLEN, dtype=eng._cache_dtype)
    state["cache"] = _random_pool(proto, PAGES, P, seed)
    state["pos"] = jnp.asarray(POS, jnp.int32)
    state["tok"] = jnp.asarray([5, 9, 2, 7], jnp.int32)
    return state


def _stat(which, family, quant):
    """What ``stats()["decode_attn"]`` reads on arm ``which``: a float
    pool's kernel is the copy form, narrow pages and wide alike."""
    copies = which != "gather" and family != "latent" and not quant
    return "paged_copy_kernel" if copies else which


def _steps(model, which, quant, family="llama"):
    with arm(which), GenerationEngine(
            model, slots=SLOTS, max_len=MAXLEN, paged=True, page_tokens=P,
            pages=PAGES, cache_dtype=jnp.int8 if quant else None,
            queue_max=4) as eng:
        state = _hand_state(eng, seed=3)
        pt, active = jnp.asarray(TABLE), jnp.asarray(ACTIVE)
        toks = []
        for _ in range(STEPS):
            state, tok = eng._step(state, pt, active)
            toks.append(np.asarray(tok))
        assert eng.stats()["decode_attn"] == _stat(which, family, quant)
        return np.stack(toks), np.asarray(state["pos"])


@pytest.mark.parametrize("family,quant", [
    ("llama", False), ("llama", True), ("latent", False), ("wide", False)],
    ids=["f32", "int8", "latent-f32", "wide-f32"])
def test_step_tokens_equal_on_both_arms(models, family, quant):
    """34 steps from one hand-built pool: every live slot crosses page
    edges (and slot 2 starts on one), the idle slot reads the null page
    and keeps its token, and both arms pick the same tokens. (The
    latent pool's pad columns hold noise here: a zero pad on the query
    is what keeps them out of the score.)"""
    model = models[family]
    got, pos = _steps(model, "paged_kernel", quant, family)
    want, _ = _steps(model, "gather", quant, family)
    np.testing.assert_array_equal(got, want)
    assert list(pos) == [p + STEPS * a for p, a in zip(POS, ACTIVE)]
    assert (got[:, 3] == 7).all()
    assert len({tuple(t) for t in got[:, :3]}) > 8      # not one fixed point


@pytest.mark.parametrize("family,quant", [
    ("llama", False), ("llama", True), ("latent", False), ("wide", False)],
    ids=["bf16", "int8", "latent-bf16", "wide-bf16"])
def test_bf16_logits_within_chip_smoke_tolerance(family, quant):
    """The two arms are two evaluations of one mathematics (a joint
    float32 softmax against an online one): in bf16 their logits differ
    by what ``chip_smoke`` allows such a pair."""
    model = FAMILIES[family]("bfloat16")
    proto = model.init_cache(1, MAXLEN,
                             dtype=jnp.int8 if quant else jnp.bfloat16)
    pool = _random_pool(proto, PAGES, P, seed=4)
    rows = jnp.asarray(TABLE[:3])
    idx = jnp.asarray(POS[:3], jnp.int32) + jnp.asarray([0, P, 4 * P])
    ids = jnp.asarray([[[5]], [[9]], [[2]]], jnp.int32)

    def logits(which):
        def one(r, i, x):
            return model.forward_with_cache(x, PagedCache(pool, r),
                                            index=i)[0]
        with arm(which):
            return np.asarray(jax.jit(jax.vmap(one))(rows, idx, ids),
                              np.float32)

    got, ref = logits("paged_kernel"), logits("gather")
    assert np.isfinite(got).all()
    diff = got - ref
    assert diff.any()                       # the arms do differ in bf16
    rms = np.sqrt(np.mean(diff ** 2)) / np.sqrt(np.mean(ref ** 2))
    assert rms <= chip_smoke.LOGIT_RMS_RTOL, rms
    assert (np.abs(diff).max() / np.abs(ref).max()
            <= chip_smoke.LOGIT_MAX_RTOL)


def _serve_shared_template(model, which, family):
    """A first request leaves the template's pages in the prefix cache;
    two more then decode side by side on those pages."""
    rs = np.random.RandomState(9)
    template = rs.randint(1, VOCAB, 2 * P + 1).astype(np.int32)
    out = {}

    def run(eng, key, item, n):
        gid = eng.start(np.concatenate([template, item]), n)
        while True:
            r = eng.poll(gid, wait_s=0.5)
            if r["done"]:
                break
        assert r["error"] is None, r["error"]
        out[key] = r["tokens"]

    with arm(which), GenerationEngine(
            model, slots=3, max_len=MAXLEN, paged=True, page_tokens=P,
            prefix_cache=True, queue_max=4) as eng:
        run(eng, "first", np.asarray([7], np.int32), 2)
        hits = get_stat("gen/prefix_hits") or 0
        threads = [threading.Thread(target=run, args=(
            eng, i, np.asarray([11 + i, 3], np.int32), 4 * P))
            for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        assert get_stat("gen/prefix_hits") - hits == 2
        assert eng.stats()["decode_attn"] == _stat(which, family, False)
    return out


@pytest.mark.parametrize("family", list(FAMILIES))
def test_shared_template_through_prefix_cache(models, family):
    got = _serve_shared_template(models[family], "paged_kernel", family)
    want = _serve_shared_template(models[family], "gather", family)
    assert got == want and len(got[0]) == 4 * P


def test_cpu_engine_stays_on_gather_arm(model):
    """No force context: ``dispatch_mode()`` is ``"off"`` off the TPU,
    and the stat says so once the step has been traced."""
    with GenerationEngine(model, slots=2, max_len=MAXLEN, paged=True,
                          page_tokens=P, queue_max=4) as eng:
        assert eng.stats()["decode_attn"] is None
        eng.lowered(6)
        assert eng.stats()["decode_attn"] == "gather"
    with GenerationEngine(model, slots=2, max_len=MAXLEN,
                          queue_max=4) as eng:
        assert "decode_attn" not in eng.stats()


# -- structure of the traced step ----------------------------------------------

def _step_eqns(model, which):
    with arm(which), GenerationEngine(
            model, slots=SLOTS, max_len=MAXLEN, paged=True, page_tokens=P,
            pages=PAGES, queue_max=4) as eng:
        jaxpr = jax.make_jaxpr(eng._step._jitted)(
            model, eng._state, jnp.asarray(TABLE), jnp.asarray(ACTIVE))
    return list(walk_eqns(jaxpr.jaxpr))


def _attn_calls(eqns, name="ptpu_paged_decode_attn"):
    return [(e, path) for e, path in eqns
            if e.primitive.name == "pallas_call"
            and e.params["name"] == name]


def _shapes(eqns):
    return {tuple(v.aval.shape) for e, _ in eqns for v in e.outvars
            if hasattr(v.aval, "shape")}


@pytest.mark.parametrize("family", ["llama", "wide"])
def test_step_holds_one_kernel_call_a_layer_over_all_slots(models, family):
    model = models[family]
    hkv = model.config.num_kv_heads
    d = model.config.hidden_size // model.config.num_heads
    eqns = _step_eqns(model, "paged_kernel")
    calls = _attn_calls(eqns)
    assert len(calls) == 1                      # the layer scan's body
    call, path = calls[0]
    # the fresh token's step, then the row's pages: a block of them the
    # kernel copies itself, narrow pages and wide alike (the pool two
    # unblocked operands)
    pages = pdk._pages_per_block(M, hkv * P * d * 4)
    assert call.params["grid_mapping"].grid == (SLOTS, 1 - (-M // pages))
    assert len(call.invars) == 4 + 2
    assert "scan" in path and "while" not in path, path
    # the views a gather builds: pages through the row, and the
    # contiguous [Hkv, M * P, D] it reshapes them to
    pages, view = (SLOTS, M, hkv, P, d), (SLOTS, 1, hkv, M * P, d)
    assert not {pages, view} & _shapes(eqns)
    gather = _step_eqns(model, "gather")
    assert not _attn_calls(gather)
    assert {pages, view} <= _shapes(gather)     # the check sees them


def test_latent_step_holds_one_kernel_call_a_layer_body(models):
    """The latent model scans two stacks (dense layers, expert layers):
    one call in each body, all slots in its grid, no loop over slots
    and none of the views the gather arm builds of the latent leaf."""
    name = "ptpu_paged_latent_decode_attn"
    eqns = _step_eqns(models["latent"], "paged_kernel")
    calls = _attn_calls(eqns, name)
    assert len(calls) == 2 and not _attn_calls(eqns)
    for call, path in calls:
        assert call.params["grid_mapping"].grid == (
            SLOTS, 1 - (-M // pdk._latent_pages_per_block(M, P)))
        assert "scan" in path and "while" not in path, path
    width = models["latent"].init_cache(1, MAXLEN)[0].shape[-1]
    pages, view = (SLOTS, M, 1, P, width), (SLOTS, 1, 1, M * P, width)
    assert not {pages, view} & _shapes(eqns)
    gather = _step_eqns(models["latent"], "gather")
    assert not _attn_calls(gather, name)
    assert {pages, view} <= _shapes(gather)     # the check sees them


# -- what must not move --------------------------------------------------------

def _sha(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def _paged_programs(model, **kw):
    with GenerationEngine(model, slots=SLOTS, max_len=MAXLEN, paged=True,
                          page_tokens=P, pages=PAGES, queue_max=4,
                          **kw) as eng:
        low = eng.lowered(6)
        out = {"prefill": _sha(low["prefill"]), "step": _sha(low["decode"])}
        if eng._spec_step is not None:
            i32 = jnp.zeros((SLOTS,), jnp.int32)
            out["spec_step"] = _sha(eng._spec_step.lower(
                eng._state, jnp.asarray(eng._pt), jnp.zeros((SLOTS,), bool),
                jnp.zeros((SLOTS, eng._spec_k), jnp.int32), i32))
        return out


def test_only_the_plain_step_changes_with_the_gate(models):
    """With the gates open or shut the paged prefill chunk and the paged
    speculative verify lower to the same text (``T > 1`` never reaches a
    kernel), the latent model's prefill chunk too; the plain step of
    either family does change; and the K/V step lowers to the same text
    with the latent arm there or refused — the latent kernel shares the
    module and the batching rule, and moves nothing of the K/V arm."""
    sides = {}
    for which in GATES:
        with arm(which):
            sides[which] = {
                "llama": _paged_programs(models["llama"], spec_k=3,
                                         spec_mode="ngram"),
                "latent": _paged_programs(models["latent"])}
    a, b, c = (sides[k] for k in ("paged_kernel", "gather",
                                  "kv_kernel_only"))
    assert a["latent"]["prefill"] == b["latent"]["prefill"]
    assert a["latent"]["step"] != b["latent"]["step"]
    assert a["llama"]["prefill"] == b["llama"]["prefill"]
    assert a["llama"]["spec_step"] == b["llama"]["spec_step"]
    assert a["llama"]["step"] != b["llama"]["step"]
    assert c["llama"] == a["llama"]
    assert c["latent"] == b["latent"]


def test_olmoe_shaped_pages_keep_the_block_spec_call(monkeypatch):
    """16 KV heads x 16 tokens x 128 in bf16 (256 rows a page: OLMoE's
    pool) where the kernel would be compiled: the call is the copy
    form's — two pool operands, 16 pages a block, the grid in order,
    the double buffers, their semaphores and the slot word beside the
    three scratch buffers — as for a page of 4 KV heads (64 pages a
    block). The same page with a 64-wide head keeps the block-spec call:
    every leaf an operand a page of the step, the grid's slot axis
    parallel, three scratch buffers and no semaphore."""
    monkeypatch.setattr(_support, "on_tpu", lambda: True)
    monkeypatch.setattr(_support, "dispatch_mode", lambda: "raw")
    slots, m = 16, 128

    def call_of(hkv, d=128):
        q = jnp.zeros((slots, 1, 16, d), jnp.bfloat16)
        new = jnp.zeros((slots, hkv, 1, d), jnp.bfloat16)
        pool = (jnp.zeros((9, 8, hkv, 16, d), jnp.bfloat16),) * 2
        table = jnp.zeros((slots, m), jnp.int32)
        assert pdk.supported(q, pool, table)
        jaxpr = jax.make_jaxpr(lambda q, kn, vn, k, v: (
            pdk.paged_decode_attention(q, kn, vn, (k, v), table,
                                       jnp.int32(3), jnp.int32(70),
                                       scale=0.1)))(q, new, new, *pool)
        (call,) = [e for e, _ in walk_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert call.params["name"] == "ptpu_paged_decode_attn"
        mapping = call.params["grid_mapping"]
        return (mapping.grid, len(call.invars), mapping.num_scratch_operands,
                tuple(call.params["compiler_params"]["mosaic_tpu"]
                      .dimension_semantics))

    kp = pdk._pages_per_block(m, 16 * 16 * 128 * 2)
    assert kp == 16
    assert call_of(16) == ((slots, 1 + m // kp), 4 + 2, 7,
                           ("arbitrary", "arbitrary"))
    kp = pdk._pages_per_block(m, 4 * 16 * 128 * 2)
    assert call_of(4) == ((slots, 1 + m // kp), 4 + 2, 7,
                          ("arbitrary", "arbitrary"))
    k = pdk._pages_per_step(m, 16 * 16 * 64 * 2)
    assert k == 8
    assert call_of(16, d=64) == ((slots, 1 + m // k), 4 + 2 * k, 3,
                                 ("parallel", "arbitrary"))


# -- compiled for the chip (no chip needed: libtpu compiles for a described
# -- v5e); the topology is described inside the fixture, never at import ----

def _abstract(tree, sharding):
    """Every leaf as a shape on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("pool_dtype",
                         ["bf16", "int8", "latent-bf16", "bf16-narrow",
                          "bf16-olmoe"])
def test_step_compiled_for_v5e_keeps_the_pool_in_place(one_chip, monkeypatch,
                                                       pool_dtype):
    """Trap 2: the kernel only reads the pool, the write after the vmap
    updates whole pages, so Mosaic's operand is the donated pool as it
    lies — the temporaries stay under one pool leaf and no buffer but
    the pool is pool-sized, as ``test_paged_view`` bounds the gather arm.
    The int8 pool compiles too, on the gather arm: Mosaic refuses the
    kernel's reshape of the scale planes, and the gate knows. The latent
    step compiles with its own kernel (manual copies out of the pool
    left unblocked in HBM) under the same bounds, and so does a
    SmallThinker-shaped step — pages of 4 KV heads x 16 tokens, a full
    and a window layer group — with the K/V kernel's copy form in both
    groups. Pages of whole lane tiles take the copy form too: 8 KV heads
    x 16 tokens x 128, and OLMoE's 16 KV heads x 16 x 128 (16 pages a
    block)."""
    # hkv * p is one lane tile and a row a whole one; the pool is too
    # large for XLA to stage a copy of it in fast memory
    hkv, d, p, slots, maxlen = 8, 128, 16, 4, 512
    which = {"int8": "gather", "latent-bf16": "paged_kernel"}.get(
        pool_dtype, "paged_copy_kernel")
    kernel, kw, ffn = "ptpu_paged_decode_attn", {}, None
    if pool_dtype == "bf16-olmoe":
        hkv, ffn = 16, 256
    if pool_dtype == "latent-bf16":
        # a latent row of whole lane tiles (128 + 64 -> 256) whose value
        # part is one: what the compiled gate asks for
        model = _latent("bfloat16", seed=13, kv_lora_rank=128,
                        qk_rope_head_dim=64, max_seq_len=maxlen)
        kernel, kw = "ptpu_paged_latent_decode_attn", {"pages": 2048}
    elif pool_dtype == "bf16-narrow":
        from paddle_tpu.models.smallthinker import (
            SmallThinkerConfig, SmallThinkerForCausalLM,
        )
        paddle_tpu.seed(13)
        model = SmallThinkerForCausalLM(SmallThinkerConfig.tiny(
            vocab_size=VOCAB, hidden_size=256, num_heads=28, num_kv_heads=4,
            head_dim=128, sliding_window=128, max_seq_len=maxlen,
            dtype="bfloat16"))
        kw = {"pages": (512, 1024), "prefill_chunk": 64}
    else:
        model = _llama("bfloat16", seed=13, hidden_size=hkv * d,
                       num_layers=4, num_heads=hkv, num_kv_heads=hkv,
                       max_seq_len=maxlen, intermediate_size=ffn)
    monkeypatch.setattr(_support, "on_tpu", lambda: True)
    abstract = functools.partial(_abstract, sharding=one_chip)

    with GenerationEngine(
            model, slots=slots, max_len=maxlen, paged=True, page_tokens=p,
            cache_dtype=jnp.int8 if pool_dtype == "int8" else None,
            queue_max=4, **kw) as eng:
        pool = jax.tree_util.tree_leaves(eng._state["cache"])
        lowered = eng._step._jitted.trace(
            abstract(model), abstract(eng._state),
            abstract(eng._pt_upload(jnp)),
            abstract(jnp.zeros((slots,), bool))).lower(
                lowering_platforms=("tpu",))
        assert eng.stats()["decode_attn"] == which
    assert ((kernel in lowered.as_text()) == (which != "gather"))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(int(x.nbytes) for x in pool)
    rows = [x for x in pool if x.ndim == 5]      # without int8's scales
    assert mem.temp_size_in_bytes < min(int(x.nbytes) for x in rows)
    for leaf in {",".join(str(d) for d in x.shape) for x in rows}:
        for line in hlo.splitlines():
            if " copy(" in line:
                assert f"[{leaf}]" not in line, line
    # the sampler's arms stay arms on the chip: ONE sort over the
    # vocabulary in the whole step, inside the last branch of a
    # conditional (a select would run it for every greedy batch)
    wide = [line for line in hlo.splitlines()
            if " sort(" in line and f"[{slots},{VOCAB}]" in line]
    assert len(wide) == 1 and "sample/cond/branch_2" in wide[0], wide
    assert sum(" conditional(" in line and "sample/cond" in line
               for line in hlo.splitlines()) == 1


def test_a_64_wide_head_on_wide_pages_compiles_on_the_block_spec_form(
        one_chip, monkeypatch):
    """16 KV heads x 16 tokens x 64: rows of whole lane tiles, but the
    copy form would slice half a lane tile out of the pool, which Mosaic
    refuses — so the step keeps the block-spec form of the kernel, and
    it compiles for the v5e. (Such a step stages the pool through fast
    memory and back: ROADMAP C2.)"""
    monkeypatch.setattr(_support, "on_tpu", lambda: True)
    slots, maxlen = 4, 512
    model = _llama("bfloat16", seed=13, hidden_size=16 * 64, num_layers=4,
                   num_heads=16, num_kv_heads=16, max_seq_len=maxlen,
                   intermediate_size=256)
    abstract = functools.partial(_abstract, sharding=one_chip)

    with GenerationEngine(model, slots=slots, max_len=maxlen, paged=True,
                          page_tokens=16, queue_max=4) as eng:
        pool = eng._state["cache"]
        assert not pdk.copies_pages(pool)
        lowered = eng._step._jitted.trace(
            abstract(model), abstract(eng._state),
            abstract(eng._pt_upload(jnp)),
            abstract(jnp.zeros((slots,), bool))).lower(
                lowering_platforms=("tpu",))
        assert eng.stats()["decode_attn"] == "paged_kernel"
    assert "ptpu_paged_decode_attn" in lowered.as_text()
    lowered.compile()


def test_kda_step_kernel_compiled_for_v5e_keeps_the_state_in_place(
        one_chip, monkeypatch):
    """``ptpu_kda_step`` at Kimi-Linear's published widths (32 heads of
    128 x 128, float32) under the engine's ``vmap`` over slots, the
    layer a traced index into a stacked state: Mosaic takes the
    transposed tile of dk-vectors and the 16-head blocks, the call is
    ONE with the slots in its grid, and the donated state is aliased —
    no temporary and no copy of it."""
    from paddle_tpu.ops import kda

    monkeypatch.setattr(_support, "on_tpu", lambda: True)
    slots, layers, H, D = 8, 3, 32, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(rows, layer, *vecs):
        return jax.vmap(lambda r, *x: kda.kda_step(r, layer, *x))(
            rows, *vecs)

    state = sds((slots, layers, 1, H, D, D))
    arms = kda.step_arms["kernel"]
    lowered = jax.jit(step, donate_argnums=(0,)).trace(
        state, sds((), jnp.int32), *[sds((slots, 1, H, D))] * 4,
        sds((slots, 1, H))).lower(lowering_platforms=("tpu",))
    assert kda.step_arms["kernel"] == arms + 1
    assert lowered.as_text().count("ptpu_kda_step") >= 1
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    nbytes = slots * layers * H * D * D * 4
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // (slots * layers)
    leaf = f"[{slots},{layers},{H},{D},{D}]"
    for line in compiled.as_text().splitlines():
        if " copy(" in line:
            assert leaf not in line, line


def test_block_kernel_compiled_for_v5e_at_the_block_cells_widths(one_chip,
                                                                 monkeypatch):
    """``ptpu_paged_block_attn`` at the block-diffusion cell's widths —
    64 slots, blocks of 4 rows of 32 query heads x 128, pages of 4 KV
    heads x 16 tokens, 145 pages a row, a pool of 6 layers — under the
    engine's ``vmap`` over slots: Mosaic takes the block form in its VMEM
    budget, ONE call with the slots in its grid, and the pool is only
    read (no temporary of its size)."""
    from paddle_tpu.ops.pallas import paged_decode_attention as pdk

    monkeypatch.setattr(_support, "on_tpu", lambda: True)
    slots, T, Hq, Hkv, D, P, M, N, L = 64, 4, 32, 4, 128, 16, 145, 2048, 6

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (sds((N + 1, L, Hkv, P, D)), sds((N + 1, L, Hkv, P, D)))
    assert pdk.block_supported(jax.ShapeDtypeStruct((1, T, Hq, D),
                                                    jnp.bfloat16),
                               pool, jax.ShapeDtypeStruct((1, M), jnp.int32))

    def step(q, k, v, table, index, pool):
        return jax.vmap(lambda qb, kb, vb, row, i: pdk.paged_block_attention(
            qb[None], kb[None], vb[None], pool, row[None], 3, i,
            scale=D ** -0.5)[0])(q, k, v, table, index)

    lowered = jax.jit(step).trace(
        sds((slots, T, Hq, D)), sds((slots, Hkv, T, D)),
        sds((slots, Hkv, T, D)), sds((slots, M), jnp.int32),
        sds((slots,), jnp.int32), pool).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("ptpu_paged_block_attn") == 1
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < (N + 1) * L * Hkv * P * D * 2
