"""The paged engine's block step (``GenerationEngine`` of a model that
generates by block diffusion): greedy streams equal the solo
``block_diffusion_generate`` byte for byte — with co-tenants, every
prompt remainder a block can hold, a ``max_new_tokens`` that is not a
multiple of the block, EOS inside a block, the dispatch lookahead on and
off; a prefix hit serves what a cold prefill serves; the books, spans
and the record a finished stream's final poll hands back; and every combination the block
step does not carry refuses by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.builders import common
from benchmarks.lib import weights as W
from paddle_tpu.core import trace
from paddle_tpu.models.generation import block_diffusion_generate
from paddle_tpu.models.sdar import SDARForCausalLM
from paddle_tpu.serving.engine import GenerationEngine

B, P, MASK = 4, 8, 255
ARGS = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=3, max_seq_len=128,
            rope_base=1e6, rms_eps=1e-6, dtype="float32", block_length=B,
            denoising_steps=4, mask_token_id=MASK)
CFG = {"program": {"model": "paddle_tpu.models.sdar:SDARForCausalLM",
                   "config": "paddle_tpu.models.sdar:SDARConfig",
                   "config_args": ARGS}}
ENGINE = dict(slots=3, max_len=128, paged=True, page_tokens=P, pages=64,
              prefill_chunk=16, prefix_cache=True, queue_max=32)


@pytest.fixture(scope="module")
def model():
    # wider weights than N(0, 0.02), so that the seeded model's tokens
    # and confidences are far apart and its streams vary
    template = common.model_template(CFG)
    m = jax.jit(lambda k: common.seeded_model(template, k))(
        W.root_key(2 ** 31 + 23))
    return jax.tree_util.tree_map(
        lambda x: x * 10 if x.ndim >= 2 else x, m)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    template = rng.integers(1, 255, 16, dtype=np.int32)
    # remainders 1, 2, 3, 0 past whole blocks; one that shares the
    # template's two pages; one shorter than a block
    out = [np.concatenate([template, rng.integers(1, 255, n,
                                                  dtype=np.int32)])
           for n in (5, 10, 3, 8)]
    out.append(rng.integers(1, 255, 3, dtype=np.int32))
    return out


def solo(model, prompt, n, eos=None):
    """The oracle's stream: its tokens up to EOS (padding after it)."""
    out = np.asarray(block_diffusion_generate(
        model, prompt, n, block_length=B, denoising_steps=4,
        mask_token_id=MASK, eos_token_id=eos))[0, prompt.size:].tolist()
    return out[:out.index(eos) + 1] if eos in out else out


def drain(eng, gid, final=False):
    """A stream's tokens (and, with ``final``, its last poll's document)."""
    toks = []
    while True:
        r = eng.poll(gid, len(toks), wait_s=30.0)
        assert r["error"] is None, r["error"]
        toks += r["tokens"]
        if r["done"]:
            return (toks, r) if final else toks


@pytest.fixture(scope="module")
def wants(model, prompts):
    return [solo(model, p, n) for p, n in zip(prompts, (9, 13, 6, 11, 7))]


@pytest.mark.parametrize("depth", [0, 1])
def test_streams_equal_solo_generation(model, prompts, wants, depth):
    """Five streams on three slots (co-tenants; later ones admitted as
    earlier ones retire), each byte for byte the solo oracle's."""
    assert len({w[0] for w in wants} | {w[-1] for w in wants}) > 2
    with GenerationEngine(model, async_depth=depth, **ENGINE) as eng:
        ids = [eng.start(p, n) for p, n in zip(prompts, (9, 13, 6, 11, 7))]
        got = [drain(eng, g) for g in ids]
        st = eng.stats()
        eng.clear_prefix_cache()
        assert eng.stats()["pages_free"] == eng.stats()["pages"]
    assert got == wants
    bd = st["block_diffusion"]
    assert (bd["block_length"], bd["denoising_steps"]) == (B, 4)
    assert bd["attn"] == st["decode_attn"] == "gather"
    # every block's masked positions fixed once; a commit a whole block
    assert bd["tokens_fixed"] <= bd["slot_steps"] * B
    assert bd["commits"] >= sum(-(-len(w) // B) - 1 for w in wants)
    # a stream's last block ends it with no commit of its own
    assert 0 < bd["tokens_fixed"] / bd["slot_steps"] <= 1.0


def test_eos_inside_a_block_ends_the_stream_there(model, prompts, wants):
    eos = wants[1][5]                       # the second block's second
    want = solo(model, prompts[1], 13, eos=eos)
    assert want == wants[1][:wants[1].index(eos) + 1]
    with GenerationEngine(model, eos_token_id=eos, **ENGINE) as eng:
        assert drain(eng, eng.start(prompts[1], 13)) == want


def test_a_prefix_hit_serves_what_a_cold_prefill_serves(model, prompts,
                                                        wants):
    with GenerationEngine(model, **ENGINE) as eng:
        first = drain(eng, eng.start(prompts[0], 9))
        before = eng.stats()["prefix_entries"]
        assert before >= 2                  # the template's pages
        again, doc = drain(eng, eng.start(prompts[3], 11), final=True)
    assert first == wants[0] and again == wants[3]
    # the record the final poll hands back: every block whole, its first
    # position, and the step each position was fixed at (-2 the prompt's)
    rec = doc["blocks"]
    p0s = [r[0] for r in rec]
    assert p0s == list(range(24, 24 + B * len(rec), B))
    assert all(sorted(r[2]) == [0, 1, 2, 3] for r in rec)
    flat = [t for r in rec for t in r[1]]
    assert flat[:len(again)] == again


def test_the_block_step_is_one_program_with_its_spans(model, prompts):
    from paddle_tpu.core.flags import set_flags
    set_flags({"trace": True})
    try:
        trace.clear()
        with GenerationEngine(model, **ENGINE) as eng:
            drain(eng, eng.start(prompts[2], 6))
            st = eng.stats()
        spans = [s for s in trace.get_spans()
                 if s["name"] == "gen/decode_step"]
    finally:
        set_flags({"trace": False})
    assert spans and all("fixing" in s["attrs"] and "committing"
                         in s["attrs"] for s in spans)
    assert sum(s["attrs"]["fixing"] for s in spans) >= 2
    assert st["recompiles"] == 0


def test_a_prompt_holding_the_mask_id_is_a_prompt(model, prompts):
    p = prompts[0].copy()
    p[-1] = MASK                           # in the first generated block
    with GenerationEngine(model, **ENGINE) as eng:
        got = drain(eng, eng.start(p, 5))
    assert got == solo(model, p, 5) and MASK not in got


class _Windowed(SDARForCausalLM):
    cache_groups = ((1, None), (1, 32))


@pytest.mark.parametrize("kwargs,names", [
    ({"paged": False}, "contiguous engine"),
    ({"spec_k": 2}, "gen_spec_k"),
    ({"kv_store": True}, "gen_kv_store"),
    ({"role": "decode"}, "gen_role"),
    ({"sched": True}, "gen_sched"),
    ({"cache_dtype": jnp.int8}, "int8"),
    ({"page_tokens": 6}, "whole blocks"),
    ({"mesh_tp": 2}, "gen_mesh_tp"),
], ids=["contiguous", "spec", "kv_store", "role", "sched", "int8",
        "page", "mesh"])
def test_what_the_block_step_does_not_carry_refuses(model, kwargs, names):
    with pytest.raises(ValueError, match=names):
        GenerationEngine(model, **dict(ENGINE, **kwargs))


def test_a_layer_group_and_sampled_requests_refuse(model, prompts):
    windowed = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(model), jax.tree_util.tree_leaves(model))
    windowed.__class__ = _Windowed
    with pytest.raises(ValueError, match="layer group"):
        GenerationEngine(windowed, **dict(ENGINE, pages=(64, 64)))
    with GenerationEngine(model, **ENGINE) as eng:
        with pytest.raises(ValueError, match="temperature"):
            eng.start(prompts[0], 4, temperature=0.7)
