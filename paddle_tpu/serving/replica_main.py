"""Subprocess replica entry point for the serving control plane.

``python -m paddle_tpu.serving.replica_main name=/path/to/artifact ...``
starts an :class:`~paddle_tpu.io.serving.InferenceServer` on a free
port with the given saved-model artifacts, prints ``ENDPOINT host:port``
on stdout (the line :class:`~paddle_tpu.serving.control.
SubprocessSpawner` blocks on), and serves until the wire ``stop`` op or
SIGTERM — both drain gracefully (``FLAGS_wire_drain_s``). One replica =
one OS process: its own GIL and XLA runtime, killable with SIGKILL,
which is exactly what the chaos harness wants a dying replica to look
like.

``FLAGS_*`` environment variables apply as usual (the flag registry
reads them at import), so a spawner can configure batching, caps, and
timeouts per fleet through the child environment.

``--gen NAME`` additionally registers a continuous-batching generation
engine under ``NAME``, over a deterministically seeded tiny-Llama
(``--gen-seed``, fixed config): every replica spawned with the same
seed holds byte-identical weights, so greedy streams are comparable —
and resumable — ACROSS replicas without shipping an artifact. This is
the chaos/test path for killing a subprocess replica that holds a live
stream (``tools/chaos_check.py gen-resilience``); real deployments
register generators in their own entry point.

``--mesh-tp N`` builds that engine over an N-device tensor-parallel
mesh (``serving/layout.py``) while the replica stays one endpoint —
streams remain byte-identical to unsharded replicas, so a router can
fail a stream over between sharded and unsharded members freely
(``tools/chaos_check.py gen-sharded``).

``--kv-store --role prefill|decode|both`` joins the replica to the
disaggregated prefill/decode tier split (``serving/kvstore.py``):
with ``--kv-spill-dir`` pointing every member at one shared root, a
prefix prefilled on any replica is a KV fetch — not a recompute — on
every other, and a killed decode replica's streams resume elsewhere
with zero recomputed prefill tokens (``tools/chaos_check.py
gen-disagg``).
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import time


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("models", nargs="*", metavar="name=path",
                    help="model artifacts to serve (save_inference_model "
                         "layout)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (the default — the spawner "
                         "reads the ENDPOINT line)")
    ap.add_argument("--gen", default=None, metavar="NAME",
                    help="register a generation engine under NAME over a "
                         "deterministically seeded tiny-Llama (chaos/test "
                         "replicas; same --gen-seed => same weights on "
                         "every replica)")
    ap.add_argument("--gen-seed", type=int, default=7)
    ap.add_argument("--gen-slots", type=int, default=2)
    ap.add_argument("--gen-max-len", type=int, default=32)
    ap.add_argument("--gen-step-wait-s", type=float, default=0.0,
                    help="engine pacing knob (slows decode so chaos "
                         "harnesses can kill a replica mid-stream)")
    ap.add_argument("--gen-paged", action="store_true",
                    help="paged KV cache for the --gen engine")
    ap.add_argument("--gen-page-tokens", type=int, default=8)
    ap.add_argument("--gen-device-pt", action="store_true",
                    help="device-resident page table for the --gen "
                         "engine (FLAGS_gen_device_pt per replica); "
                         "inert unless --gen-paged")
    ap.add_argument("--gen-async-depth", type=int, default=0,
                    help="async double-buffered decode dispatch depth "
                         "for the --gen engine (FLAGS_gen_async_depth "
                         "per replica; 0 = synchronous loop, the "
                         "default). Token streams stay byte-identical")
    ap.add_argument("--gen-spec-k", type=int, default=0,
                    help="speculative decoding lookahead for the --gen "
                         "engine (0 = off, the default)")
    ap.add_argument("--gen-spec-mode", default="ngram",
                    choices=("ngram", "draft"),
                    help="drafter for --gen-spec-k>0; 'draft' builds a "
                         "1-layer draft Llama from the same --gen-seed")
    ap.add_argument("--mesh-tp", type=int, default=0,
                    help="tensor-parallel degree of the --gen engine's "
                         "device mesh (FLAGS_gen_mesh_tp per replica; "
                         "0 = unsharded). The replica stays ONE "
                         "endpoint; token streams are byte-identical "
                         "to unsharded replicas")
    ap.add_argument("--role", default=None,
                    choices=("prefill", "decode", "both"),
                    help="disaggregated serving tier of the --gen "
                         "engine (FLAGS_gen_role per replica; default "
                         "'both'). Inert unless the KV store is on")
    ap.add_argument("--kv-store", action="store_true",
                    help="enable the tiered KV page store for the "
                         "--gen engine (FLAGS_gen_kv_store per "
                         "replica); point --kv-spill-dir (or the "
                         "FLAGS_gen_kv_spill_dir environment) at a "
                         "shared root to make it fleet-wide")
    ap.add_argument("--kv-spill-dir", default=None,
                    help="KV store spill-tier root: a shared directory "
                         "or a ptfs:// WireFS endpoint")
    ap.add_argument("--kv-fetch-timeout-s", type=float, default=None,
                    help="per-page cold-fetch deadline for the KV "
                         "store (FLAGS_gen_kv_fetch_timeout_s per "
                         "replica); overruns degrade to recompute")
    ap.add_argument("--kv-hedge-ms", type=float, default=None,
                    help="hedged-fetch latency threshold "
                         "(FLAGS_gen_kv_hedge_ms per replica): a "
                         "pending spill read races a --kv-peers "
                         "replica after this many ms")
    ap.add_argument("--kv-breaker", type=int, default=None,
                    help="consecutive failures opening a KV tier "
                         "circuit breaker (FLAGS_gen_kv_breaker per "
                         "replica; 0 = no breakers)")
    ap.add_argument("--kv-breaker-backoff-s", type=float, default=None,
                    help="half-open probe backoff base for an open KV "
                         "tier breaker "
                         "(FLAGS_gen_kv_breaker_backoff_s per replica)")
    ap.add_argument("--kv-peers", default=None,
                    help="comma-separated peer replica endpoints for "
                         "the KV store's peer tier "
                         "(FLAGS_gen_kv_peers per replica)")
    ap.add_argument("--gen-sched", action="store_true",
                    help="enable the SLO-aware tenant-fair scheduler "
                         "for the --gen engine (FLAGS_gen_sched per "
                         "replica): priority classes on the 'pc' "
                         "header, weighted-fair queueing across "
                         "tenants, interactive-over-batch preemption "
                         "with byte-identical resume")
    ap.add_argument("--gen-sched-quotas", default=None,
                    help="per-tenant quota shares for the scheduler as "
                         "'tenant=share,...' (FLAGS_gen_sched_quotas "
                         "per replica)")
    ap.add_argument("--gen-sched-headroom", type=int, default=None,
                    help="interactive shed headroom past the queue/"
                         "inflight caps (FLAGS_gen_sched_headroom per "
                         "replica)")
    ap.add_argument("--emb-ps", default=None, metavar="ENDPOINTS",
                    help="comma-separated parameter-server endpoints: "
                         "attach the embedding serving tier "
                         "(FLAGS_serving_emb per replica) and register "
                         "a CTR model whose sparse tables live on the "
                         "PS fleet (tools/chaos_check.py sparse-serve)")
    ap.add_argument("--emb-table", default="emb:16:4",
                    metavar="NAME:DIM[:SLOTS]",
                    help="PS table the --emb-ps CTR model looks up "
                         "(default emb:16:4)")
    ap.add_argument("--emb-model", default="ctr",
                    help="model name the --emb-ps predictor serves "
                         "under (default ctr)")
    ap.add_argument("--emb-seed", type=int, default=0,
                    help="dense-tower seed for --emb-ps (same seed => "
                         "byte-identical tower on every replica)")
    ap.add_argument("--emb-cache-rows", type=int, default=None,
                    help="hot-row cache capacity per table "
                         "(FLAGS_serving_emb_cache_rows per replica)")
    ap.add_argument("--emb-ttl-s", type=float, default=None,
                    help="hot-row TTL within a table version "
                         "(FLAGS_serving_emb_ttl_s per replica; <=0 "
                         "never expires)")
    args = ap.parse_args(argv)

    if args.mesh_tp > 0:
        # a subprocess replica does not inherit a test harness's forced
        # host device count, and XLA reads the flag once at backend
        # init — set it BEFORE anything imports jax so a tp>1 mesh has
        # devices to stand on even on a plain CPU host. Respect an
        # explicit parent setting (real TPU fleets pass topology via
        # the environment).
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            n = max(args.mesh_tp, 8)
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={n}").strip()

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.core.flags import flag, set_flags
    from paddle_tpu.io.serving import InferenceServer

    enable_compile_cache()

    # running as ``python -m`` imports the paddle_tpu package (and
    # with it the flag registry) BEFORE main() runs, so an env export
    # here would be read too late — set the flags directly; the engine
    # reads them at construction
    kv_flags = {
        "gen_kv_spill_dir": args.kv_spill_dir,
        "gen_kv_fetch_timeout_s": args.kv_fetch_timeout_s,
        "gen_kv_hedge_ms": args.kv_hedge_ms,
        "gen_kv_breaker": args.kv_breaker,
        "gen_kv_breaker_backoff_s": args.kv_breaker_backoff_s,
        "gen_kv_peers": args.kv_peers,
    }
    kv_flags = {k: v for k, v in kv_flags.items() if v is not None}
    if args.gen_sched:
        kv_flags["gen_sched"] = True
    if args.gen_sched_quotas is not None:
        kv_flags["gen_sched_quotas"] = args.gen_sched_quotas
    if args.gen_sched_headroom is not None:
        kv_flags["gen_sched_headroom"] = args.gen_sched_headroom
    if args.emb_ps:
        kv_flags["serving_emb"] = True
        if args.emb_cache_rows is not None:
            kv_flags["serving_emb_cache_rows"] = args.emb_cache_rows
        if args.emb_ttl_s is not None:
            kv_flags["serving_emb_ttl_s"] = args.emb_ttl_s
    if kv_flags:
        set_flags(kv_flags)

    models: dict[str, str] = {}
    for spec in args.models:
        name, _, path = spec.partition("=")
        if not name or not path:
            ap.error(f"bad model spec {spec!r}; expected name=path")
        models[name] = path

    srv = InferenceServer(models, host=args.host, port=args.port)
    if args.gen:
        import paddle_tpu
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle_tpu.seed(args.gen_seed)
        cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32,
                               num_layers=2, num_heads=2, num_kv_heads=2,
                               max_seq_len=64)
        model = LlamaForCausalLM(cfg)
        draft = None
        if args.gen_spec_k > 0 and args.gen_spec_mode == "draft":
            # deterministically derived from the same seed stream, so
            # every replica drafts identically too
            dcfg = LlamaConfig.tiny(vocab_size=96, hidden_size=16,
                                    num_layers=1, num_heads=2,
                                    num_kv_heads=2, max_seq_len=64)
            draft = LlamaForCausalLM(dcfg)
        srv.add_generator(args.gen, model,
                          slots=args.gen_slots,
                          max_len=args.gen_max_len,
                          step_wait_s=args.gen_step_wait_s,
                          paged=args.gen_paged,
                          page_tokens=args.gen_page_tokens,
                          device_pt=args.gen_device_pt,
                          async_depth=args.gen_async_depth,
                          spec_k=args.gen_spec_k,
                          spec_mode=args.gen_spec_mode,
                          draft_model=draft,
                          mesh_tp=args.mesh_tp,
                          kv_store=(True if args.kv_store else None),
                          role=args.role)
    if args.emb_ps:
        from paddle_tpu.distributed.ps.client import PSClient
        from paddle_tpu.serving.sparse import SparseCTRPredictor

        spec = args.emb_table.split(":")
        tname = spec[0]
        dim = int(spec[1]) if len(spec) > 1 else 16
        slots = int(spec[2]) if len(spec) > 2 else 4
        ps = PSClient([e.strip() for e in args.emb_ps.split(",")
                       if e.strip()])
        tier = srv.attach_embeddings(ps)
        srv.add_model(args.emb_model,
                      SparseCTRPredictor(tier, tname, slots,
                                         emb_dim=dim, seed=args.emb_seed))
    srv.start()
    print(f"ENDPOINT {srv.endpoint}", flush=True)
    # after ENDPOINT (the line SubprocessSpawner blocks on): lets an
    # operator or HA journal record the pid of a replica started by
    # hand, so an adopting leader can escalate a stop past the wire
    print(f"PID {os.getpid()}", flush=True)

    def _term(signum, frame):        # scheduler preemption: drain, exit
        srv.stop(drain_s=float(flag("wire_drain_s")))

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    # serve until stopped (wire stop op, or the signal handler above);
    # _thread goes back to None once the accept loop is shut down
    while srv._thread is not None:
        time.sleep(0.2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
