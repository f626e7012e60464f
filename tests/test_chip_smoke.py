"""``chip_smoke.py`` off the chip: its phase functions at
``LlamaConfig.tiny`` on the CPU (the device-only assertions — kernel
names, dispatch mode — parameterised off), its refusal to report
anything without a TPU, and the compile-cache placement rule its entry
point applies."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from paddle_tpu.core import compile_cache  # noqa: E402
from paddle_tpu.models import LlamaConfig  # noqa: E402


def _tiny_requests(vocab):
    # one prefill bucket (32) keeps the compile count down; the last two
    # prompts share their first page
    return cs.make_requests(vocab, (20, 18, 22, 17, 24, 24),
                            (4, 3, 4, 3, 4, 4), shared_prefix=16)


def test_train_and_serve_phases_tiny():
    cfg = LlamaConfig.tiny(max_seq_len=64, lm_head_mode="fused")
    model, train = cs.train_phase(cfg, batch=2, seq=32, steps=3,
                                  on_chip=False)
    assert train["losses"][-1] < train["losses"][0]
    serve = cs.serve_phase(model, _tiny_requests(cfg.vocab_size), slots=4,
                           max_len=64, on_chip=False)
    assert serve["contiguous"]["streams"] == serve["paged"]["streams"] == 6
    assert serve["paged"]["pages"]["prefix_entries"] >= 1
    assert serve["paged_equals_contiguous"]
    # on the CPU (f32) the repeat through the prefix cache and solo
    # generate() are byte-identical to the engine's greedy stream; on
    # the chip the smoke reports both without gating on them
    # request 1 (top-k, top-p) takes two decode steps past its prefill
    assert serve["paged"]["probe_repeat"] == {
        "through_prefix_cache_agrees_for": "4/4 tokens",
        "same_programs_identical": True,
        "sample_sorted_steps": 2, "greedy_probe_sorted_steps": 0}
    assert serve["contiguous"]["probe_repeat"]["sample_sorted_steps"] == 2
    assert serve["engine_agrees_with_solo_generate_for"] == {
        "contiguous": "4/4 tokens", "paged": "4/4 tokens"}
    assert serve["sampled_agrees_with_solo_generate_for"] == {
        "contiguous": "3/3 tokens", "paged": "3/3 tokens"}


def test_latent_phase_off_the_chip():
    """The latent engine's phase on the CPU (float32): the streams
    finish, the pool drains, and the step reports the gather arm — the
    kernel arm is the chip's."""
    latent = cs.latent_phase(
        cs.make_requests(256, (40, 36, 44, 34), (4, 3, 4, 3),
                         shared_prefix=32), slots=4, max_len=64,
        on_chip=False)
    assert latent["decode_attn"] == "gather" and latent["streams"] == 4
    assert latent["kernels"] == {"decode": []}
    assert latent["probe_repeat"]["same_programs_identical"]


def test_window_phase_off_the_chip():
    """The layer-group engine's phase on the CPU (float32): the streams
    finish, the window group slides and never outgrows its row, both
    pools come back whole, and the stream behind a shared prefix three
    windows long equals solo ``generate()``."""
    got = cs.window_phase(
        cs.make_requests(256, (60, 52, 70, 64), (12, 8, 12, 8),
                         shared_prefix=48), slots=4, max_len=128, window=16,
        chunk=16, on_chip=False)
    assert got["decode_attn"] == "gather" and got["streams"] == 4
    assert got["pages_slid"] > 0
    assert got["stream_pages_peak"] <= got["row_pages"] == 3
    assert got["engine_agrees_with_solo_generate_for"] == "8/8 tokens"
    assert got["probe_repeat"]["same_programs_identical"]


def test_state_phase_off_the_chip():
    """The state-group engine's phase on the CPU (float32): the streams
    finish, a snapshot is restored, the stream that restores one equals
    the cold one, pages and snapshots come back whole."""
    got = cs.state_phase(
        cs.make_requests(256, (44, 40, 52, 50), (10, 6, 10, 6),
                         shared_prefix=32), slots=4, max_len=96, chunk=16,
        on_chip=False)
    assert got["decode_attn"] == "gather" and got["kda_step"] == "xla"
    assert got["streams"] == 4 and got["restores"] > 0
    assert got["kernels"] == {"decode": []}
    repeat = got["probe_repeat"]
    assert repeat["through_prefix_cache_agrees_for"] == "10/10 tokens"
    assert repeat["same_programs_identical"]


@pytest.mark.slow
@pytest.mark.parametrize("kernels", ["off", "interpreted"])
def test_parity_phase_tiny(kernels):
    """The parity phase off the chip, float32: every compared pair — the
    contiguous and the paged decode step against the one-shot prefill
    among them — agrees to rounding, on the jnp arms and with the
    kernels (``ptpu_paged_decode_attn`` included: head size 64) forced
    through the interpreter."""
    import contextlib

    import paddle_tpu
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.ops import pallas as pk

    paddle_tpu.seed(3)
    model = LlamaForCausalLM(LlamaConfig.tiny(hidden_size=256))
    with (pk.force_dispatch() if kernels == "interpreted"
          else contextlib.nullcontext()):
        report = cs.parity_phase(model, seq=32, rms_rtol=1e-5, max_rtol=1e-5)
    assert {"paged_decode: compiled vs interpreted",
            "paged_decode vs one-shot prefill"} <= set(report)
    assert all(r["argmax_agree"] == 1.0 for r in report.values())


def test_four_device_phases_tiny():
    """The >= 4-chip branch (ZeRO-3 x4 train, mesh_tp=4 serve) on the
    virtual CPU mesh."""
    cfg = LlamaConfig.tiny(max_seq_len=64, num_heads=4, num_kv_heads=4,
                           lm_head_mode="fused")
    model, train = cs.train_phase(cfg, batch=4, seq=32, steps=3, chips=4,
                                  on_chip=False)
    assert train["losses"][-1] < train["losses"][0]
    serve = cs.serve_phase(model, _tiny_requests(cfg.vocab_size), slots=4,
                           max_len=64, mesh_tp=4, on_chip=False)
    assert serve["paged"]["device"]["mesh"] == {"tp": 4}


def test_smoke_check_failure_is_an_error():
    with pytest.raises(cs.SmokeFailure, match="page pool leaked"):
        cs._check_generator(
            "g", {"broken": None, "stuck": False, "rebuilds": 0,
                  "quarantined": 0, "active": 0, "queued": 0, "paged": True,
                  "pages": 8, "pages_free": 6, "prefix_entries": 1,
                  "device": {"platform": "cpu", "devices": 1}},
            platform="cpu", devices=1)


def test_main_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout == ""          # no result line off the chip


def test_compile_cache_placement(monkeypatch):
    dir_before = jax.config.jax_compilation_cache_dir
    secs_before = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # placed from outside: jax reads the variable, nothing set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == dir_before
        # not placed: the fixed in-checkout path
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
        # small bucket programs are admitted
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", dir_before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          secs_before)


def test_block_phase_off_the_chip():
    """The block-diffusion engine's phase on the CPU (float32): the
    greedy streams finish, each equals the solo block-diffusion
    generation, the pool comes back whole."""
    got = cs.block_phase(
        cs.make_requests(256, (30, 26, 35, 33), (6, 5, 7, 5),
                         shared_prefix=16), slots=3, max_len=64, chunk=16,
        on_chip=False)
    assert got["block_attn"] == "gather" and got["streams"] == 3
    assert got["engine_agrees_with_solo_generation_for"] == "18/18 tokens"
    assert got["kernels"] == {"decode": []}
    assert got["block_diffusion"]["commits"] > 0
