"""Model serving: a TCP inference service over exported artifacts.

Reference role: the serving layer around the inference engine — the
C-API / AnalysisPredictor service wrapping
(``paddle/fluid/inference/api/analysis_predictor.h:82``,
``inference/capi/pd_predictor.cc``) that Paddle deploys behind
Paddle Serving. TPU-native formulation: an ``InferenceServer`` hosts
named :class:`~paddle_tpu.io.export.Predictor` instances (StableHLO
artifacts with baked-in weights, compiled once per model) and serves the
shared length-prefixed frame protocol (``core/wire.py`` — raw numpy
buffers, no pickling). Models can be registered at construction or
hot-loaded over the wire; requests run concurrently (jitted calls are
thread-safe; XLA serializes device execution).

Wire format for ``infer``: header ``{"model": name, "inputs":
[{"shape": [...], "dtype": "float32"}, ...], "nbytes": N}`` with the raw
input buffers concatenated in order; response mirrors it with output
specs + buffers.

Generation serving (``FLAGS_gen_slots``): ``add_generator`` registers a
continuous-batching :class:`~paddle_tpu.serving.engine.GenerationEngine`
over a live model, served through ``generate_start`` /
``generate_poll`` / ``generate_cancel`` (prompts/tokens ride the JSON
header — they are small) with :meth:`InferenceClient.generate` as the
streaming client iterator. A full engine sheds starts with the
retryable ``CODE_SHED`` status. With ``FLAGS_gen_paged`` the engine's
KV cache is a paged pool with prefix sharing and chunked prefill; the
``health`` op then ships page-pool occupancy (``pages_free``/``pages``)
and prefix-cache size per generator alongside slot occupancy, so
routers and autoscalers see real capacity (pages, not slots) without a
dedicated op.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any

import numpy as np

from paddle_tpu.core import trace as _trace
from paddle_tpu.core.flags import flag
from paddle_tpu.core.monitor import stat_add
from paddle_tpu.core.wire import (
    CODE_SHED, FrameClient, FrameService, send_frame,
)

__all__ = ["InferenceServer", "InferenceClient", "ModelBusyError"]

SERVING_OPS = {"infer": 1, "list_models": 2, "load_model": 3, "stop": 4,
               "generate_start": 5, "generate_poll": 6,
               "generate_cancel": 7, "unload_model": 8, "ledger_dump": 9,
               "kv_put": 10, "kv_get": 11, "kv_probe": 12,
               "sched_quotas": 13}
_OP_NAMES = {v: k for k, v in SERVING_OPS.items()}

# Marker prefix for the typed busy error as it crosses the wire (the
# frame protocol only carries an error string; the client re-raises the
# typed class when it sees the marker).
_BUSY_MARKER = "model busy:"


class ModelBusyError(RuntimeError):
    """``unload_model`` refused: the model still has requests inside the
    dynamic batcher (queued on the coalescing window or executing).
    Typed so controllers can distinguish "try again in a moment" from a
    real failure — the unload never ran and is safe to retry once the
    in-flight work drains."""


def _pack_arrays(arrays) -> tuple[list[dict], bytes]:
    specs, chunks = [], []
    for a in arrays:
        a = np.ascontiguousarray(a)
        specs.append({"shape": list(a.shape), "dtype": a.dtype.name})
        chunks.append(a.tobytes())
    return specs, b"".join(chunks)


def _unpack_arrays(specs: list[dict], payload: bytes) -> list[np.ndarray]:
    out, off = [], 0
    for spec in specs:
        dt = np.dtype(spec["dtype"])
        count = int(np.prod(spec["shape"]))
        n = count * dt.itemsize
        if off + n > len(payload):
            raise ValueError("payload shorter than declared input specs")
        # zero-copy view at offset (no bytes-slice duplicate of the buffer)
        out.append(np.frombuffer(payload, dt, count=count, offset=off)
                   .reshape(spec["shape"]))
        off += n
    if off != len(payload):
        raise ValueError("payload longer than declared input specs")
    return out


class InferenceServer(FrameService):
    """Serve named Predictors over TCP.

    ``models`` maps name -> saved-model directory (see
    ``io.save_inference_model``) or an already-constructed Predictor.

    ``admin_ops`` controls the mutating wire ops (``load_model`` — which
    reads an arbitrary server-side path — ``unload_model``, and
    ``stop``). Default: enabled
    only when bound to loopback; when exposing the server beyond
    localhost, the data-plane ``infer``/``list_models`` stay available
    and admin must be opted into explicitly.
    """

    op_names = _OP_NAMES           # span/histogram labels (core/wire.py)

    def __init__(self, models: dict[str, Any] | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 admin_ops: bool | None = None):
        from paddle_tpu.io.export import Predictor
        from paddle_tpu.serving.batcher import DynamicBatcher

        self._predictor_cls = Predictor
        self._models: dict[str, Any] = {}
        # per-model usage/footprint stats (shipped in ``health`` so a
        # control plane can make LRU/eviction decisions from data):
        # name -> {infers, last_used_ts, resident_bytes}
        self._model_stats: dict[str, dict[str, float]] = {}
        self._generators: dict[str, Any] = {}
        self._lock = threading.Lock()
        # per-tenant infer attribution (FLAGS_gen_ledger, read at
        # construction only — the hard-off default builds no book and
        # the infer path's only cost is one is-None check). Engine-side
        # generation attribution lives in each engine's RequestLedger.
        if flag("gen_ledger"):
            from paddle_tpu.serving.ledger import TenantBook
            self._ledger_infer = TenantBook()
        else:
            self._ledger_infer = None
        # per-server coalescer; consulted only when FLAGS_serving_batch_max
        # enables batching (one flag read per infer otherwise)
        self._batcher = DynamicBatcher(tenant_book=self._ledger_infer)
        # PS-backed embedding serving (FLAGS_serving_emb, read at
        # construction ONLY): hard-off leaves attach_embeddings a no-op
        # and every serving path byte-identical — the health tick's
        # rollover hook below is an is-None check, nothing more
        self._emb_enabled = bool(flag("serving_emb"))
        self._emb_tier = None
        for name, m in (models or {}).items():
            self.add_model(name, m)
        if admin_ops is None:
            admin_ops = host in ("127.0.0.1", "localhost", "::1")
        self._admin_ops = bool(admin_ops)
        super().__init__(host, port)

    def add_model(self, name: str, model) -> None:
        """Register a Predictor (or construct one from a saved-model
        path). A path is validated HERE — artifact + meta must exist and
        deserialize — so a bad ``load_model`` fails at registration with
        a wire error, not at some later caller's first ``infer``."""
        resident = 0
        if isinstance(model, str):
            from paddle_tpu.io.export import _ARTIFACT, _META

            for part in (_ARTIFACT, _META):
                if not os.path.isfile(os.path.join(model, part)):
                    raise ValueError(
                        f"{model!r} is not an inference-model directory "
                        f"(missing {part}); expected the layout written "
                        "by save_inference_model")
            # artifact size approximates resident bytes (weights are
            # baked into the StableHLO blob) — the LRU signal a control
            # plane weighs eviction candidates by
            resident = os.path.getsize(os.path.join(model, _ARTIFACT))
            try:
                pred = self._predictor_cls(model)
            except Exception as e:
                raise ValueError(
                    f"failed to load inference model from {model!r}: "
                    f"{type(e).__name__}: {e}") from e
        else:
            pred = model
            resident = int(getattr(model, "resident_bytes", 0) or 0)
        with self._lock:
            self._models[name] = pred
            self._model_stats[name] = {
                "infers": 0, "last_used_ts": time.time(),
                "resident_bytes": resident}

    def unload_model(self, name: str) -> bool:
        """Drop a registered model (the warm→cold transition of the
        serving control plane's multiplexing tier). Returns False for an
        unknown name (idempotent — a broadcast unload tolerates replicas
        that never loaded it). Raises :class:`ModelBusyError` while the
        model has requests inside the dynamic batcher: the unload never
        runs, the caller retries after the queue drains — never a hang,
        never a predictor yanked out from under a forming batch.
        Requests already past the registry lookup keep their predictor
        reference and complete normally."""
        n = self._batcher.pending(name)
        if n > 0:
            raise ModelBusyError(
                f"{_BUSY_MARKER} {name!r} has {n} request(s) in the "
                "batcher; retry after they drain")
        with self._lock:
            existed = self._models.pop(name, None) is not None
            self._model_stats.pop(name, None)
        if existed:
            stat_add("serving/models_unloaded")
        return existed

    def add_generator(self, name: str, model, **engine_kwargs):
        """Register a continuous-batching :class:`~paddle_tpu.serving.
        engine.GenerationEngine` for the ``generate_start`` /
        ``generate_poll`` / ``generate_cancel`` ops. ``model`` is a live
        model exposing ``init_cache``/``forward_with_cache`` (engines
        step the decode loop slot-by-slot — a baked StableHLO artifact
        cannot), or an already-constructed engine. Slot count comes from
        ``FLAGS_gen_slots`` unless ``slots=`` is passed; the flag's
        default of 0 keeps generation serving off entirely. Paged-cache
        mode (``FLAGS_gen_paged`` or ``paged=True`` in
        ``engine_kwargs``, plus ``page_tokens``/``pages``/
        ``prefill_chunk``/``prefix_cache``) changes only the engine's
        memory management — the wire surface is identical. Returns the
        engine now serving ``name``."""
        from paddle_tpu.serving.engine import GenerationEngine

        engine = (model if isinstance(model, GenerationEngine)
                  else GenerationEngine(model, **engine_kwargs))
        with self._lock:
            old = self._generators.get(name)
            self._generators[name] = engine
        if old is not None and old is not engine:
            old.close()
        sched = engine.sched
        if sched is not None:
            # one shed brain (FLAGS_gen_sched): FrameService's
            # would-shed path and the dynamic batcher's coalescing
            # bypass consult the engine's scheduler, so a request is
            # never double-shed and class headroom applies consistently
            self.set_shed_gate(sched.wire_gate)
            self._batcher.set_sched(sched)
        return engine

    def _generator(self, name: str):
        with self._lock:
            eng = self._generators.get(name)
        if eng is None:
            raise KeyError(f"no generator {name!r}; registered: "
                           f"{sorted(self._generators)} (use "
                           "add_generator; FLAGS_gen_slots enables)")
        return eng

    def attach_embeddings(self, ps_client):
        """Construct this replica's PS-backed embedding serving tier
        (``FLAGS_serving_emb``; ``serving/sparse.py``) over ``ps_client``
        and return it — callers then register
        :class:`~paddle_tpu.serving.sparse.SparseCTRPredictor` endpoints
        via :meth:`add_model`. With the flag off (the default) this is a
        no-op returning None: no tier, no version polling, the serving
        path stays byte-identical."""
        if not self._emb_enabled:
            return None
        from paddle_tpu.serving.sparse import EmbeddingServingTier

        tier = EmbeddingServingTier(ps_client)
        with self._lock:
            self._emb_tier = tier
        return tier

    def _kv_store(self):
        """This replica's KV page store: the first registered engine's
        (engines sharing a replica share its store), or None with
        ``FLAGS_gen_kv_store`` off — the kv ops then answer "not
        stored"/"not found"/"no match" rather than erroring, so fleet
        probes can sweep mixed fleets."""
        with self._lock:
            for eng in self._generators.values():
                kv = getattr(eng, "_kv", None)
                if kv is not None:
                    return kv
        return None

    def health(self, stats_prefix: str | None = None,
               histograms: bool = False, deep: bool = False,
               stats: bool = True) -> dict:
        """FrameService health + per-generator slot AND page-pool
        occupancy (paged engines report ``pages_free``/``pages`` +
        ``prefix_entries``) + per-model usage stats (infer count,
        last-used timestamp/idle seconds, approx resident bytes), so
        routers, probes, and the serving control plane see generation
        capacity and warm-tier residency without a dedicated op. Each
        generator also ships ``tokens_per_step`` (emitted tokens per
        fused decode iteration) and — on speculating engines
        (``FLAGS_gen_spec_k>0``) — a ``spec`` block with the
        proposed/accepted/rejected counts and ``accept_rate``, so the
        control plane can see speculation efficiency next to slot
        occupancy and tell a speculation win from a batching win.
        Every generator further ships a ``device`` block (platform,
        device count, mesh axis sizes, total + per-device KV bytes):
        a mesh-backed tensor-parallel engine (``FLAGS_gen_mesh_tp``)
        is ONE replica behind one endpoint, and this block is how its
        topology stays visible to placement decisions.
        ``stats_prefix`` keeps filtering the monitor-stats snapshot
        only — the ``models``/``generators`` sections always ship (they
        are the decision inputs a control loop polls for). ``deep``
        additionally runs a one-token canary decode per generation
        engine (``GenerationEngine.canary``) and ships the result under
        each generator's ``engine`` key: *engine* liveness — "device
        healthy" — as distinct from the *wire* liveness a shallow probe
        measures ("port open"), so a router prober or controller can
        tell a wedged device from a dead socket. Deep probes cost real
        decode work; the background router prober stays shallow."""
        doc = super().health(stats_prefix, histograms, deep, stats)
        now = time.time()
        with self._lock:
            engines = dict(self._generators)
            models = {n: dict(st, idle_s=max(now - st["last_used_ts"],
                                             0.0))
                      for n, st in self._model_stats.items()}
        gens = {n: e.stats() for n, e in engines.items()}
        if deep:
            for n, e in engines.items():
                gens[n]["engine"] = e.canary()
        if gens:
            doc["generators"] = gens
        doc["models"] = models
        if self._emb_tier is not None:
            # the health tick IS the rollover tick: every prober /
            # controller scrape gives the tier a (rate-limited) chance
            # to notice a newly published table version and flip
            self._emb_tier.maybe_rollover()
            doc["emb"] = self._emb_tier.stats()
        return doc

    def stop(self, drain_s: float | None = None) -> None:
        super().stop(drain_s)
        with self._lock:
            engines = list(self._generators.values())
        for engine in engines:
            engine.close()

    def _dispatch(self, sock, op: int, header: dict, payload: bytes) -> bool:
        name = _OP_NAMES.get(op)
        try:
            if (name in ("stop", "load_model", "unload_model",
                         "sched_quotas")
                    and not self._admin_ops):
                send_frame(sock, 1, {"error": f"admin op {name!r} disabled "
                                     "on this server (admin_ops=False)"})
                return True
            if name == "stop":
                send_frame(sock, 0, {})
                # graceful: other in-flight infers get wire_drain_s to
                # finish before their sockets are severed
                threading.Thread(
                    target=self.stop,
                    kwargs={"drain_s": float(flag("wire_drain_s"))},
                    daemon=True).start()
                return False
            if name == "list_models":
                with self._lock:
                    info = {n: {"inputs": p.input_specs,
                                "outputs": p.output_specs}
                            for n, p in self._models.items()}
                send_frame(sock, 0, {"models": info})
                return True
            if name == "load_model":
                self.add_model(header["name"], header["path"])
                send_frame(sock, 0, {})
                return True
            if name == "unload_model":
                send_frame(sock, 0,
                           {"unloaded": self.unload_model(header["name"])})
                return True
            if name == "generate_start":
                from paddle_tpu.serving.engine import EngineOverloaded

                engine = self._generator(header["model"])
                eos = header.get("eos_token_id")
                try:
                    gen_id = engine.start(
                        np.asarray(header["prompt"], np.int32),
                        int(header["max_new_tokens"]),
                        temperature=float(header.get("temperature", 0.0)),
                        top_k=int(header.get("top_k", 0)),
                        top_p=float(header.get("top_p", 1.0)),
                        eos_token_id=None if eos is None else int(eos),
                        seed=int(header.get("seed", 0)),
                        rng_skip=int(header.get("rng_skip", 0)),
                        # stream trace id ("st"): minted by the first
                        # generate_start of the logical stream, replayed
                        # by failover resume — joins this replica's slot
                        # events into the stream's fleet-wide trace
                        trace_id=header.get("st"),
                        # tenant ("tn"): the ledger's attribution
                        # identity, replayed by failover resume so
                        # per-tenant counters survive a replica death
                        tenant=header.get("tn"),
                        # original-stream crash fingerprint ("fp"):
                        # carried by failover resume so quarantine
                        # recognizes resumed poison even though the
                        # replay prompt grew by the delivered tokens
                        fingerprint=header.get("fp"),
                        # priority class ("pc"): the scheduler's
                        # admission/preemption input (FLAGS_gen_sched;
                        # ignored by default engines)
                        priority=header.get("pc"))
                except EngineOverloaded as e:
                    # full engine: shed, not error — the status is
                    # retryable for every client (the start never ran)
                    stat_add("gen/shed_wire")
                    send_frame(sock, CODE_SHED,
                               {"error": str(e),
                                "retry_after_s": e.retry_after_s})
                    return True
                send_frame(sock, 0, {"gen_id": gen_id})
                return True
            if name == "generate_poll":
                engine = self._generator(header["model"])
                doc = engine.poll(
                    header["gen_id"], start=int(header.get("start", 0)),
                    # bound the long-poll: a poll pins a handler thread
                    wait_s=min(float(header.get("wait_s", 0.0)), 2.0))
                send_frame(sock, 0, doc)
                return True
            if name == "generate_cancel":
                engine = self._generator(header["model"])
                send_frame(sock, 0,
                           {"cancelled": engine.cancel(header["gen_id"])})
                return True
            if name == "kv_put":
                store = self._kv_store()
                if store is None:
                    send_frame(sock, 0, {"stored": False})
                else:
                    send_frame(sock, 0, {"stored": store.put(
                        str(header["key"]), payload)})
                return True
            if name == "kv_get":
                store = self._kv_store()
                frame = (None if store is None
                         else store.get(str(header["key"])))
                send_frame(sock, 0,
                           {"found": frame is not None,
                            "nbytes": len(frame or b"")}, frame or b"")
                return True
            if name == "kv_probe":
                store = self._kv_store()
                keys = [str(k) for k in header.get("keys", ())]
                if store is not None and not store.placeable:
                    # cordoned or breaker-open: stop advertising KV
                    # locality — a no-match answer makes the router's
                    # _kv_place look elsewhere (match>0 is what pins)
                    send_frame(sock, 0, {"match": 0, "degraded": True})
                    return True
                send_frame(sock, 0, {"match": (0 if store is None
                                               else store.probe(keys))})
                return True
            if name == "sched_quotas":
                # live tenant-share reconfig (the controller's push over
                # the control channel): applied to every engine running
                # FLAGS_gen_sched; a replica with no scheduler answers
                # with an empty list rather than erroring, so a fleet
                # broadcast sweeps mixed fleets cleanly
                quotas = header.get("quotas") or {}
                updated = []
                with self._lock:
                    engines = dict(self._generators)
                for n, e in engines.items():
                    sched = getattr(e, "sched", None)
                    if sched is not None and hasattr(sched, "set_quotas"):
                        sched.set_quotas(quotas)
                        updated.append(n)
                send_frame(sock, 0, {"updated": sorted(updated)})
                return True
            if name == "ledger_dump":
                # performance-attribution dump (FLAGS_gen_ledger): each
                # engine's finalized phase records + tenant book +
                # goodput snapshot, plus the server-side infer tenant
                # book. Engines with the ledger off are omitted.
                limit = header.get("limit")
                with self._lock:
                    engines = dict(self._generators)
                gens = {}
                for n, e in engines.items():
                    d = e.ledger_dump(
                        None if limit is None else int(limit))
                    if d is not None:
                        gens[n] = d
                send_frame(sock, 0, {
                    "generators": gens,
                    "infer_tenants": (
                        None if self._ledger_infer is None
                        else self._ledger_infer.snapshot())})
                return True
            if name != "infer":
                send_frame(sock, 1, {"error": f"bad op {op}"})
                return True
            with self._lock:
                pred = self._models.get(header["model"])
                st = self._model_stats.get(header["model"])
                if st is not None:       # LRU signal for the control plane
                    st["infers"] += 1
                    st["last_used_ts"] = time.time()
            if pred is None:
                raise KeyError(f"no model {header['model']!r}; loaded: "
                               f"{sorted(self._models)}")
            inputs = _unpack_arrays(header["inputs"], payload)
            # Cross-request dynamic batching (FLAGS_serving_batch_max,
            # hard-off default — this flag read is all the unbatched
            # path pays): dynamic-batch models coalesce concurrent
            # requests into one bucketed Predictor.run.
            if (int(flag("serving_batch_max")) > 1
                    and self._batcher.can_batch(pred)):
                outs = self._batcher.submit(header["model"], pred, inputs,
                                            tenant=header.get("tn"))
            else:
                # nested under the wire server span: a traced request
                # shows model time separate from framing/dispatch time
                if self._ledger_infer is not None:
                    t0 = time.perf_counter()
                with _trace.span("serving/predict", model=header["model"]):
                    outs = pred.run(*inputs)
                if self._ledger_infer is not None:
                    self._ledger_infer.add(
                        header.get("tn"), requests=1,
                        chip_s=time.perf_counter() - t0)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            specs, body = _pack_arrays(np.asarray(o) for o in outs)
            send_frame(sock, 0, {"outputs": specs, "nbytes": len(body)},
                       body)
            return True
        except Exception as e:  # report, keep serving
            send_frame(sock, 1, {"error": f"{type(e).__name__}: {e}"})
            return True


class InferenceClient(FrameClient):
    """Client for :class:`InferenceServer`.

    ``infer``/``list_models``/``load_model`` are idempotent and retried
    across reconnects (flags ``wire_retries``/``wire_timeout_s``), so a
    client survives a server restart; ``stop`` fails fast.
    """

    def __init__(self, endpoint: str, *, timeout: float | None = None,
                 retries: int | None = None):
        # generate_poll (positional re-read) and generate_cancel are
        # idempotent; generate_start is NOT — a conn-level retry could
        # start the generation twice (CODE_SHED retries stay safe for
        # it: a shed start never executed)
        super().__init__(endpoint, SERVING_OPS, service="serving",
                         timeout=timeout, retries=retries,
                         idempotent=("infer", "list_models", "load_model",
                                     "unload_model", "generate_poll",
                                     "generate_cancel", "ledger_dump",
                                     "kv_put", "kv_get", "kv_probe",
                                     "sched_quotas"))

    def infer(self, model: str, *inputs,
              tenant: str | None = None) -> list[np.ndarray]:
        specs, payload = _pack_arrays(inputs)
        header = {"model": model, "inputs": specs, "nbytes": len(payload)}
        if tenant:
            # attribution identity (header "tn"): the server's ledger
            # books this request's chip-seconds under it when
            # FLAGS_gen_ledger is on; ignored otherwise
            header["tn"] = str(tenant)
        rheader, rpayload = self._request("infer", header, payload)
        # copy out of the frombuffer views: results a caller may mutate
        # must not be read-only aliases of the reply buffer (server-side
        # unpack stays zero-copy — Predictor only reads)
        return [np.array(a) for a in
                _unpack_arrays(rheader["outputs"], rpayload)]

    def list_models(self) -> dict:
        return self._request("list_models", {})[0]["models"]

    # -- streaming generation (continuous-batching engine) -----------------
    def generate_start(self, model: str, prompt, max_new_tokens: int, *,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, eos_token_id: int | None = None,
                       seed: int = 0, rng_skip: int = 0,
                       trace_id: str | None = None,
                       tenant: str | None = None,
                       fingerprint: str | None = None,
                       priority: str | None = None) -> str:
        """Admit a generation into ``model``'s engine; returns its id.
        A full engine surfaces as the retryable shed status (the client
        backs off per ``retry_after_s`` and retries within its budget,
        then raises :class:`~paddle_tpu.core.wire.WireShedError`); a
        quarantined crash fingerprint re-raises the typed
        :class:`~paddle_tpu.serving.engine.RequestQuarantined` — final,
        never retried. ``rng_skip`` fast-forwards the sampling-key
        schedule (stream resumption's RNG-position replay). ``trace_id``
        is the stream's fleet-unique trace id (header ``st``): with
        tracing on one is minted here when not given; a resuming caller
        passes the ORIGINAL stream's id so the replacement replica's
        slot events join the same trace. ``tenant`` (header ``tn``) is
        the attribution identity the engine's request ledger books this
        stream's tokens/chip-seconds under (``FLAGS_gen_ledger``).
        ``fingerprint`` (header ``fp``) is the ORIGINAL stream's crash
        fingerprint: a resuming caller passes it so the engine's
        quarantine matches the stream's history instead of hashing the
        grown replay prompt. ``priority`` (header ``pc``) is the
        stream's scheduling class (interactive / batch / best_effort)
        — consulted by replicas running ``FLAGS_gen_sched``; inert
        metadata elsewhere."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        header = {"model": model, "prompt": prompt.tolist(),
                  "max_new_tokens": int(max_new_tokens),
                  "temperature": float(temperature), "top_k": int(top_k),
                  "top_p": float(top_p), "seed": int(seed)}
        if eos_token_id is not None:
            header["eos_token_id"] = int(eos_token_id)
        if rng_skip:
            header["rng_skip"] = int(rng_skip)
        if trace_id is None and _trace.recording():
            trace_id = _trace.new_id()
        if trace_id:
            header["st"] = str(trace_id)
        if tenant:
            header["tn"] = str(tenant)
        if fingerprint:
            header["fp"] = str(fingerprint)
        if priority:
            header["pc"] = str(priority)
        try:
            return self._request("generate_start", header)[0]["gen_id"]
        except RuntimeError as e:
            from paddle_tpu.serving.engine import (
                QUARANTINE_MARKER, RequestQuarantined,
            )
            if QUARANTINE_MARKER in str(e):
                raise RequestQuarantined(str(e)) from e
            raise

    def generate_poll(self, model: str, gen_id: str, start: int = 0,
                      wait_s: float = 0.0) -> dict:
        """Tokens past ``start`` (long-polls up to ``wait_s`` server-side)
        → ``{"tokens", "done", "error", "queued"}``. A generation the
        server reaped via the poll TTL re-raises the typed
        :class:`~paddle_tpu.serving.engine.GenerationExpired` (distinct
        from plain unknown-id — the stream existed there and is gone)."""
        try:
            return self._request(
                "generate_poll", {"model": model, "gen_id": gen_id,
                                  "start": int(start),
                                  "wait_s": float(wait_s)})[0]
        except RuntimeError as e:
            from paddle_tpu.serving.engine import (
                EXPIRED_MARKER, GenerationExpired,
            )
            if EXPIRED_MARKER in str(e):
                raise GenerationExpired(str(e)) from e
            raise

    def generate_cancel(self, model: str, gen_id: str) -> bool:
        return self._request(
            "generate_cancel",
            {"model": model, "gen_id": gen_id})[0]["cancelled"]

    # -- KV page store (disaggregated serving, FLAGS_gen_kv_store) ---------
    def kv_put(self, key: str, frame: bytes) -> bool:
        """Push a serialized KV page frame into the replica's store
        under its radix chain key. Content-addressed and idempotent;
        False when the replica already held it (or runs no store)."""
        return self._request("kv_put", {"key": str(key),
                                        "nbytes": len(frame)},
                             bytes(frame))[0]["stored"]

    def kv_get(self, key: str) -> bytes | None:
        """Fetch a page frame from the replica's store, or None on a
        miss (including store-off replicas — a mixed fleet probes
        cleanly)."""
        header, payload = self._request("kv_get", {"key": str(key)})
        return payload if header["found"] else None

    def kv_probe(self, keys) -> int:
        """Longest prefix run of radix chain ``keys`` the replica's
        store holds — the KV-locality placement signal (0 on store-off
        replicas)."""
        return self._request("kv_probe",
                             {"keys": [str(k) for k in keys]})[0]["match"]

    def generate(self, model: str, prompt, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id: int | None = None,
                 seed: int = 0, poll_wait_s: float = 0.25,
                 tenant: str | None = None):
        """Streaming generation: admits the prompt (raises immediately on
        a full engine) and returns an iterator yielding token ids as the
        engine emits them. Closing the iterator early (``break`` /
        ``.close()``) cancels the generation server-side so its slot
        frees now instead of at the poll TTL."""
        gen_id = self.generate_start(
            model, prompt, max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
            seed=seed, tenant=tenant)

        def stream():
            n, finished = 0, False
            try:
                while True:
                    doc = self.generate_poll(model, gen_id, start=n,
                                             wait_s=poll_wait_s)
                    for tok in doc["tokens"]:
                        yield int(tok)
                    n += len(doc["tokens"])
                    if doc["done"]:
                        finished = True
                        if doc.get("error"):
                            raise RuntimeError(
                                f"generation {gen_id} failed: "
                                f"{doc['error']}")
                        return
            finally:
                if not finished:
                    try:
                        self.generate_cancel(model, gen_id)
                    except (RuntimeError, ConnectionError, OSError):
                        pass

        return stream()

    def ledger_dump(self, limit: int | None = None) -> dict:
        """Performance-attribution dump (``FLAGS_gen_ledger``):
        ``{"generators": {name: {records, tenants, goodput}},
        "infer_tenants": {...}|None}``. Engines (or servers) running
        with the ledger off simply contribute nothing — the op always
        succeeds. ``limit`` caps the per-engine record count."""
        header: dict[str, Any] = {}
        if limit is not None:
            header["limit"] = int(limit)
        return self._request("ledger_dump", header)[0]

    def sched_quotas(self, quotas: dict[str, float]) -> list[str]:
        """Push a live tenant-share map to the replica's schedulers
        (``FLAGS_gen_sched``; satellite of the controller's
        ``set_quotas`` broadcast). Returns the generator names whose
        scheduler applied it — empty on replicas running without the
        scheduler (idempotent: sets-to-value, safe to retry)."""
        q = {str(k): float(v) for k, v in (quotas or {}).items()}
        return self._request("sched_quotas",
                             {"quotas": q})[0]["updated"]

    def load_model(self, name: str, path: str) -> None:
        self._request("load_model", {"name": name, "path": path})

    def unload_model(self, name: str) -> bool:
        """Drop ``name`` from the server's registry (admin-gated like
        ``load_model``). False for a model that was never loaded
        (idempotent). A model with requests still inside the server's
        batcher surfaces as the typed :class:`ModelBusyError` — the
        unload never ran and is retryable once the queue drains."""
        try:
            return self._request(
                "unload_model", {"name": name})[0]["unloaded"]
        except RuntimeError as e:
            if _BUSY_MARKER in str(e):
                raise ModelBusyError(str(e)) from e
            raise

    def stop_server(self) -> None:
        try:
            self._request("stop", {})
        except (RuntimeError, ConnectionError, OSError):
            pass
