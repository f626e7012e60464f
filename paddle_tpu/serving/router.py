"""Health-aware client-side routing across inference-server replicas.

Reference role: the load-balancing tier in front of a Paddle Serving
fleet (N predictor-replica processes behind a router/BRPC channel
group). Here it is a *client library*: :class:`RoutedClient` holds one
:class:`~paddle_tpu.io.serving.InferenceClient` per replica endpoint and
spreads idempotent requests across them:

- **Least-inflight pick** — each wire client counts its submitted-but-
  unanswered requests (``FrameClient.inflight``, per-op via
  ``inflight_by_op()``); a request goes to the healthy replica with the
  fewest, ties broken round-robin. Slow replicas shed load automatically
  without any server cooperation.
- **Health-probe membership** — a daemon thread probes every replica's
  universal ``health`` op (never shed, answered even under overload)
  every ``FLAGS_serving_probe_interval_s``; unreachable or *draining*
  replicas stop receiving new requests and rejoin when the probe sees
  ``ok`` again. ``add_endpoint``/``remove_endpoint`` change membership
  live.
- **Failover** — a connect error/timeout marks the replica down and the
  request retries on the next pick; a :class:`~paddle_tpu.core.wire.
  WireShedError` (admission control turned the request away *before*
  execution) reroutes without marking the replica down. Both are safe
  for the idempotent serving ops this client routes (``infer``,
  ``list_models``, ``load_model``); the shed case is safe for any op.
  Each failing replica is tried at most once per request; when every
  member has failed, the last error surfaces.

Sticky drain (the control plane's scale-down primitive): ``cordon``
excludes a replica from every new pick — routed AND session — while its
pooled connections stay open, so in-flight streams finish on the replica
that holds their state; ``remove_endpoint`` then finalizes.

Stream resumption (``FLAGS_gen_resume_budget``, hard-off): with a
budget set, a generation stream that loses its replica mid-flight —
connection loss, replica death, or a server-side engine reset (the
``engine reset:`` error marker) — is transparently restarted on a
freshly picked replica by replaying ``prompt + tokens already
delivered`` as a prefill-from-prefix (cheap when the radix prefix cache
shares the replayed prefix) and continues emitting from where it broke:
byte-identical for greedy decode, RNG-position-replayed for sampled
streams (the engine's ``rng_skip``). Exhausting the budget surfaces the
typed :class:`StreamResumeExhausted`; a
:class:`~paddle_tpu.serving.engine.RequestQuarantined` rejection is
final and never resumed — a poisoned request must not be walked across
the fleet.

Speculative decoding (``FLAGS_gen_spec_k``) composes with resumption
unchanged: the engine consumes exactly one RNG split per EMITTED token
regardless of how many drafts each verify step accepted, so
``rng_skip = len(delivered)`` lands on the same key schedule whether
the original replica, the resuming replica, both, or neither were
speculating — speculative rollback is per-slot device state the wire
contract never sees (``tools/chaos_check.py`` gen-spec pins this).

Stats: ``serving/router/failovers``, ``serving/router/shed_rerouted``,
``serving/router/marked_down``, ``serving/router/recovered``,
``serving/router/cordoned``, ``serving/router/uncordoned``,
``serving/router/stream_resumes``, ``serving/router/resume_exhausted``.
"""

from __future__ import annotations

import random as _random_mod
import threading
import time
import uuid
import zlib
from typing import Callable

import numpy as np

from paddle_tpu.core import trace as _trace
from paddle_tpu.core.flags import flag
from paddle_tpu.core.monitor import stat_add
from paddle_tpu.core.wire import FrameClient, WireShedError
from paddle_tpu.io.serving import InferenceClient
from paddle_tpu.serving.engine import (
    EXPIRED_MARKER, RESET_MARKER, GenerationExpired, stream_fingerprint,
)

__all__ = ["RoutedClient", "ReplicaState", "StickySession",
           "GenerationFailed", "StreamResumeExhausted"]

_jitter_rng = _random_mod.Random()


def _jittered(base: float) -> float:
    """U[0.9, 1.1) x base — decorrelates N routers' (and standby
    controllers') probe cadence so they don't synchronize their health
    scrapes into a thundering herd on the fleet (the PR-8 shed-jitter
    idiom, tighter band: a cadence, not a backoff)."""
    return base * (0.9 + 0.2 * _jitter_rng.random())


class GenerationFailed(ConnectionError):
    """A non-idempotent generation op failed on its pinned replica.
    NEVER silently failed over — the generation's slot (KV cache + token
    stream) lives on exactly one replica, so rerouting a poll would
    return "unknown generation" and rerouting a start would leak a slot.
    ``endpoint`` names the replica so the caller can restart the
    generation elsewhere (or let stream resumption do it:
    ``FLAGS_gen_resume_budget``)."""

    def __init__(self, msg: str, endpoint: str):
        super().__init__(msg)
        self.endpoint = endpoint


class StreamResumeExhausted(GenerationFailed):
    """Stream resumption gave up: the generation lost its replica more
    times than ``FLAGS_gen_resume_budget`` allows. ``attempts`` counts
    the restarts tried; ``endpoint`` is the last replica that failed.
    Tokens already yielded to the caller remain valid — the stream is
    merely incomplete."""

    def __init__(self, msg: str, endpoint: str, attempts: int = 0):
        super().__init__(msg, endpoint)
        self.attempts = attempts


class ReplicaState:
    """One replica's routing view: endpoint, a small connection pool
    (lazy, rebuilt after failures), and probe-driven health.

    The pool matters: one ``FrameClient`` serializes its requests behind
    a connection lock, so a single shared connection could never present
    concurrent same-model requests to the replica — exactly what the
    server-side batcher coalesces. N pooled connections let one routed
    client keep N requests in flight per replica.

    ``cordoned`` is the sticky-drain state: a cordoned replica receives
    no NEW picks (routed or session) but keeps its pooled connections
    open, so in-flight work — a streaming generation's polls especially
    — runs to completion. Health probes keep running; ``cordon`` is
    orthogonal to ``healthy`` and survives recovery."""

    __slots__ = ("endpoint", "clients", "healthy", "last_error", "probes",
                 "failures", "cordoned")

    def __init__(self, endpoint: str):
        self.endpoint = endpoint
        self.clients: list[InferenceClient] = []
        self.healthy = True           # optimistic until a probe/request
        self.last_error: str | None = None
        self.probes = 0
        self.failures = 0
        self.cordoned = False

    @property
    def inflight(self) -> int:
        return sum(c.inflight for c in self.clients)


class RoutedClient:
    """Route idempotent serving requests across replica endpoints.

    ``endpoints`` may be empty at construction and grown later with
    :meth:`add_endpoint`. Per-replica connections are built by
    ``client_factory`` (default: ``InferenceClient(ep, timeout=timeout,
    retries=retries)`` with ``retries=0`` so failover happens at the
    router, not inside one replica's retry loop) and pooled up to
    ``pool_size`` per replica — grown on demand when every pooled
    connection is busy, so concurrent callers reach the replica
    concurrently (a prerequisite for server-side batching to coalesce
    them). ``probe_interval_s`` defaults to
    ``FLAGS_serving_probe_interval_s``; pass 0 to disable background
    probing (membership then only reacts to request errors).
    """

    def __init__(self, endpoints: list[str] | tuple[str, ...] = (), *,
                 timeout: float | None = None, retries: int = 0,
                 probe_interval_s: float | None = None,
                 pool_size: int = 8,
                 client_factory: Callable[[str], InferenceClient]
                 | None = None):
        self._factory = client_factory or (
            lambda ep: InferenceClient(ep, timeout=timeout,
                                       retries=retries))
        self._timeout = timeout
        self._pool_size = max(int(pool_size), 1)
        # KV-locality placement (FLAGS_gen_kv_store, read HERE only —
        # hard-off keeps session pinning byte-identical): with the
        # store on, an unpinned session's first generation probes the
        # healthy replicas' stores (kv_probe) and pins the one holding
        # the longest radix prefix of the prompt — the per-prefix
        # generalization of the load signals
        self._kv_locality = bool(flag("gen_kv_store"))
        self._kv_page_tokens = (int(flag("gen_page_tokens"))
                                if self._kv_locality else 0)
        self._lock = threading.Lock()
        self._replicas: list[ReplicaState] = []
        self._rr = 0                     # round-robin tie-breaker
        self._closed = False
        for ep in endpoints:
            self.add_endpoint(ep)
        if probe_interval_s is None:
            probe_interval_s = float(flag("serving_probe_interval_s"))
        self._probe_interval = float(probe_interval_s)
        self._probe_stop = threading.Event()
        self._prober: threading.Thread | None = None
        if self._probe_interval > 0:
            self._prober = threading.Thread(target=self._probe_loop,
                                            daemon=True)
            self._prober.start()

    # -- membership --------------------------------------------------------
    def add_endpoint(self, endpoint: str) -> None:
        with self._lock:
            if self._closed:
                raise ConnectionError("RoutedClient is closed")
            if any(r.endpoint == endpoint for r in self._replicas):
                return
            self._replicas.append(ReplicaState(endpoint))

    def remove_endpoint(self, endpoint: str) -> None:
        with self._lock:
            keep, drop = [], []
            for r in self._replicas:
                (drop if r.endpoint == endpoint else keep).append(r)
            self._replicas = keep
        for r in drop:
            self._close_clients(r)

    def cordon(self, endpoint: str) -> None:
        """Stop routing NEW requests to ``endpoint`` while keeping its
        pooled connections (and therefore all in-flight work, including
        streaming generations' polls) alive — the first half of a
        sticky-drain scale-down. Unknown endpoints are ignored. The
        replica remains a member (probed, visible in :meth:`members`)
        until :meth:`remove_endpoint` finalizes the removal."""
        with self._lock:
            for r in self._replicas:
                if r.endpoint == endpoint and not r.cordoned:
                    r.cordoned = True
                    stat_add("serving/router/cordoned")

    def uncordon(self, endpoint: str) -> None:
        """Re-admit a cordoned replica to routing (a cancelled drain)."""
        with self._lock:
            for r in self._replicas:
                if r.endpoint == endpoint and r.cordoned:
                    r.cordoned = False
                    stat_add("serving/router/uncordoned")

    def endpoints(self) -> list[str]:
        with self._lock:
            return [r.endpoint for r in self._replicas]

    def members(self) -> list[dict]:
        """Routing snapshot: one dict per replica (endpoint, healthy,
        cordoned, inflight, failures, last_error)."""
        with self._lock:
            return [{"endpoint": r.endpoint, "healthy": r.healthy,
                     "cordoned": r.cordoned,
                     "inflight": r.inflight, "failures": r.failures,
                     "last_error": r.last_error}
                    for r in self._replicas]

    # -- health probing ----------------------------------------------------
    def _probe_loop(self) -> None:
        while not self._probe_stop.wait(_jittered(self._probe_interval)):
            try:
                self.probe()
            except Exception:      # pragma: no cover - prober never dies
                pass

    def probe(self) -> list[dict]:
        """One probe round over current members (also runs on the
        background thread): each replica's ``health`` op decides its
        membership. Returns :meth:`members` afterwards."""
        with self._lock:
            replicas = list(self._replicas)
        for r in replicas:
            ok, err = self._probe_one(r.endpoint)
            with self._lock:
                if r not in self._replicas:    # removed mid-probe
                    continue
                r.probes += 1
                was = r.healthy
                r.healthy = ok
                r.last_error = err
                if ok and not was:
                    stat_add("serving/router/recovered")
        return self.members()

    def _probe_one(self, endpoint: str) -> tuple[bool, str | None]:
        """Probe via a short-lived dedicated connection: the data
        client's lock may be held by a long infer, and a probe must
        never queue behind the traffic it is assessing."""
        timeout = self._timeout if self._timeout is not None else 5.0
        try:
            with FrameClient(endpoint, {}, service="probe",
                             timeout=timeout, retries=0) as c:
                h = c.health(stats=False)    # liveness only, no stats
            if h.get("status") != "ok":
                return False, f"status={h.get('status')}"
            return True, None
        except (ConnectionError, RuntimeError, OSError) as e:
            return False, f"{type(e).__name__}: {e}"

    # -- routing core ------------------------------------------------------
    def _pick(self, exclude: set[str], any_health: bool = False
              ) -> ReplicaState | None:
        """Healthy replica with the fewest in-flight requests (ties:
        round-robin). ``any_health`` is the last resort — membership may
        be stale and a 'down' replica may be back. Cordoned replicas
        are NEVER picked, not even as the last resort: a drain that
        leaked new work would never converge."""
        with self._lock:
            pool = [r for r in self._replicas
                    if r.endpoint not in exclude and not r.cordoned
                    and (any_health or r.healthy)]
            if not pool:
                return None
            self._rr += 1
            lo = min(r.inflight for r in pool)
            ties = [r for r in pool if r.inflight == lo]
            return ties[self._rr % len(ties)]

    def _client(self, r: ReplicaState) -> InferenceClient:
        """An idle pooled connection if one exists; grow the pool while
        every connection is busy (up to ``pool_size``), then share the
        least-loaded one."""
        with self._lock:
            idle = [c for c in r.clients if c.inflight == 0]
            if idle:
                return idle[0]
            grow = len(r.clients) < self._pool_size
            if not grow and r.clients:
                return min(r.clients, key=lambda c: c.inflight)
        client = self._factory(r.endpoint)   # connects; may raise
        with self._lock:
            if len(r.clients) < self._pool_size:
                r.clients.append(client)
                return client
        client.close()                       # lost the race; pool full
        with self._lock:
            return min(r.clients, key=lambda c: c.inflight)

    def _mark_down(self, r: ReplicaState, err: BaseException) -> None:
        stat_add("serving/router/marked_down")
        with self._lock:
            r.healthy = False
            r.failures += 1
            r.last_error = f"{type(err).__name__}: {err}"
        self._close_clients(r)

    def _close_clients(self, r: ReplicaState) -> None:
        with self._lock:
            clients, r.clients = list(r.clients), []
        for client in clients:
            client.close()

    def _routed(self, fn: Callable[[InferenceClient], object]):
        """Run ``fn(client)`` on the best replica, failing over across
        members: connect errors mark the replica down, sheds just
        reroute. Only pass idempotent operations."""
        if self._closed:
            raise ConnectionError("RoutedClient is closed")
        tried: set[str] = set()
        last: BaseException | None = None
        for any_health in (False, True):
            while True:
                r = self._pick(tried, any_health)
                if r is None:
                    break
                tried.add(r.endpoint)
                try:
                    out = fn(self._client(r))
                    with self._lock:      # request-level health signal
                        if not r.healthy:
                            r.healthy = True
                            stat_add("serving/router/recovered")
                    return out
                except WireShedError as e:
                    # rejected BEFORE execution: replica is overloaded
                    # or draining, not dead — reroute, don't mark down
                    stat_add("serving/router/shed_rerouted")
                    last = e
                except (ConnectionError, TimeoutError, OSError) as e:
                    stat_add("serving/router/failovers")
                    self._mark_down(r, e)
                    last = e
        if last is not None:
            raise last
        raise ConnectionError("no replicas available "
                              f"(members: {self.endpoints()})")

    # -- session-sticky routing (generation affinity) ----------------------
    def session(self, session_id: str | None = None) -> "StickySession":
        """A sticky handle: hash ``session_id`` onto one healthy member
        and keep every op there (a generation's slot state is
        replica-local, so its start/poll/cancel MUST hit one replica).
        Re-picks only on member loss, and never while a generation is in
        flight — that surfaces as :class:`GenerationFailed` instead."""
        return StickySession(self, session_id or uuid.uuid4().hex)

    def generate(self, model: str, prompt, max_new_tokens: int, **kw):
        """Streaming generation through a fresh sticky session (see
        :meth:`session` for multi-op affinity). With
        ``FLAGS_gen_resume_budget`` (or ``resume_budget=``) set, the
        stream survives mid-flight replica loss by resuming on a fresh
        replica — byte-identical for greedy decode."""
        return self.session().generate(model, prompt, max_new_tokens,
                                       **kw)

    def _replica_for(self, endpoint: str) -> ReplicaState | None:
        with self._lock:
            for r in self._replicas:
                if r.endpoint == endpoint:
                    return r
        return None

    def _healthy_endpoints(self) -> list[str]:
        with self._lock:
            return sorted(r.endpoint for r in self._replicas
                          if r.healthy and not r.cordoned)

    # -- the routed serving surface ---------------------------------------
    def infer(self, model: str, *inputs,
              tenant: str | None = None) -> list[np.ndarray]:
        return self._routed(
            lambda c: c.infer(model, *inputs, tenant=tenant))

    def list_models(self) -> dict:
        return self._routed(lambda c: c.list_models())

    def load_model(self, name: str, path: str,
                   broadcast: bool = True) -> None:
        """Hot-load on every healthy non-cordoned replica
        (``broadcast=True``, default — replicas should serve the same
        model set) or on one (a draining replica's model set no longer
        matters)."""
        if not broadcast:
            self._routed(lambda c: c.load_model(name, path))
            return
        errors = []
        for r in list(self._replicas):
            if not r.healthy or r.cordoned:
                continue
            try:
                self._client(r).load_model(name, path)
            except (ConnectionError, RuntimeError, OSError) as e:
                errors.append(f"{r.endpoint}: {type(e).__name__}: {e}")
        if errors:
            raise RuntimeError("load_model failed on: " +
                               "; ".join(errors))

    def unload_model(self, name: str,
                     broadcast: bool = True) -> dict[str, bool]:
        """Drop ``name`` fleet-wide (the control plane's cold-tier
        transition). Returns endpoint -> unloaded (False where the model
        was never resident — unload is idempotent per replica). A
        replica refusing with the typed
        :class:`~paddle_tpu.io.serving.ModelBusyError` (requests still
        in its batcher) surfaces in the aggregate error — nothing hangs,
        the caller retries after the queue drains."""
        if not broadcast:
            return {"": bool(self._routed(
                lambda c: c.unload_model(name)))}
        out: dict[str, bool] = {}
        errors = []
        for r in list(self._replicas):
            if not r.healthy or r.cordoned:
                continue
            try:
                out[r.endpoint] = self._client(r).unload_model(name)
            except (ConnectionError, RuntimeError, OSError) as e:
                errors.append(f"{r.endpoint}: {type(e).__name__}: {e}")
        if errors:
            raise RuntimeError("unload_model failed on: " +
                               "; ".join(errors))
        return out

    def health(self, stats_prefix: str | None = None,
               histograms: bool = False,
               deep: bool = False,
               stats: bool = True) -> dict[str, dict]:
        """endpoint -> server health snapshot (unreachable replicas map
        to ``{"status": "unreachable", ...}``); covers cordoned members
        too — the control plane watches a draining victim's in-flight
        work through exactly this. ``stats_prefix``/``histograms`` pass
        through to each server's health op (raw-bucket histograms merge
        fleet-wide via ``monitor.merge_histograms``); ``deep`` asks each
        replica to run a one-token canary decode per generator — engine
        liveness ("device healthy") as distinct from the wire liveness
        ("port open") the shallow probe measures; ``stats=False`` asks
        for liveness-only docs (no stats payload at all)."""
        out = {}
        for r in list(self._replicas):
            ok, err = self._probe_one(r.endpoint)
            if ok:
                try:
                    out[r.endpoint] = self._client(r).health(
                        stats_prefix=stats_prefix, histograms=histograms,
                        deep=deep, stats=stats)
                    continue
                except (ConnectionError, RuntimeError, OSError) as e:
                    err = f"{type(e).__name__}: {e}"
            out[r.endpoint] = {"status": "unreachable", "error": err}
        return out

    def ledger_dump(self, limit: int | None = None) -> dict[str, dict]:
        """endpoint -> performance-attribution dump (the per-replica
        ``ledger_dump`` op: finalized phase records, per-tenant books,
        goodput snapshots — see ``serving/ledger.py``). Unreachable
        replicas map to ``{"status": "unreachable", ...}`` like
        :meth:`health`; replicas running with ``FLAGS_gen_ledger`` off
        contribute empty dumps. ``MetricsHub.fleet_goodput()`` /
        ``tenants()`` / ``fleet_kv()`` roll :meth:`health` up across
        replicas; this is the per-request half."""
        out: dict[str, dict] = {}
        for r in list(self._replicas):
            ok, err = self._probe_one(r.endpoint)
            if ok:
                try:
                    out[r.endpoint] = self._client(r).ledger_dump(limit)
                    continue
                except (ConnectionError, RuntimeError, OSError) as e:
                    err = f"{type(e).__name__}: {e}"
            out[r.endpoint] = {"status": "unreachable", "error": err}
        return out

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        self._probe_stop.set()
        with self._lock:
            self._closed = True
            replicas, self._replicas = list(self._replicas), []
        for r in replicas:
            for client in r.clients:
                client.close()
        if self._prober is not None:
            self._prober.join(timeout=2.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class StickySession:
    """Session-sticky view of a :class:`RoutedClient`: every op runs on
    ONE pinned replica (``crc32(session_id)`` over the sorted healthy
    membership, so the same session id re-pins to the same replica from
    any client while membership holds).

    Failure semantics differ from the routed path on purpose:

    - the pin is re-evaluated only between generations — member loss
      with no generation in flight re-picks quietly
      (``serving/router/session_repick``);
    - a connect error/timeout during an in-flight generation raises
      :class:`GenerationFailed` carrying the replica endpoint (and marks
      the replica down for the routed traffic) — NEVER a silent retry
      elsewhere: the slot state is gone, the caller must restart;
    - a shed ``generate_start`` (:class:`~paddle_tpu.core.wire.
      WireShedError`) propagates as-is: it never executed, so the caller
      may back off and retry — on this session or a fresh one.
    """

    def __init__(self, router: RoutedClient, session_id: str):
        self._router = router
        self.session_id = session_id
        self._endpoint: str | None = None
        self._active = 0               # generations currently streaming
        self._lock = threading.Lock()

    @property
    def endpoint(self) -> str | None:
        """The pinned replica (None until the first op pins one)."""
        return self._endpoint

    def _pin(self) -> ReplicaState:
        healthy = self._router._healthy_endpoints()
        with self._lock:
            if self._endpoint is not None and self._endpoint not in healthy:
                if self._active:
                    raise GenerationFailed(
                        f"replica {self._endpoint} lost with "
                        f"{self._active} generation(s) in flight on "
                        f"session {self.session_id}; restart them",
                        self._endpoint)
                stat_add("serving/router/session_repick")
                self._endpoint = None
            if self._endpoint is None:
                if not healthy:
                    raise ConnectionError(
                        "no healthy replicas to pin session "
                        f"{self.session_id} (members: "
                        f"{self._router.endpoints()})")
                idx = zlib.crc32(self.session_id.encode()) % len(healthy)
                self._endpoint = healthy[idx]
        r = self._router._replica_for(self._endpoint)
        if r is None:
            raise GenerationFailed(
                f"replica {self._endpoint} removed from membership",
                self._endpoint)
        return r

    def _client(self) -> InferenceClient:
        return self._router._client(self._pin())

    def _kv_place(self, prompt: np.ndarray) -> None:
        """KV-locality placement (FLAGS_gen_kv_store only): pin this
        not-yet-pinned session to the healthy replica whose store holds
        the longest radix-chain prefix of ``prompt`` — its admission
        serves those pages from RAM instead of fetching (or, store-off
        fleetwide, recomputing). Best-effort: probe errors and
        no-match fleets fall back to the crc32 pin; an existing pin is
        never moved (stickiness wins over locality)."""
        with self._lock:
            if self._endpoint is not None:
                return
        from paddle_tpu.serving.kvstore import page_chain_keys
        P = self._router._kv_page_tokens
        if P < 1:
            return
        keys = page_chain_keys(prompt, P,
                               limit=(int(prompt.size) - 1) // P)
        if not keys:
            return
        healthy = self._router._healthy_endpoints()
        if len(healthy) < 2:
            return
        best, best_n = None, 0
        for ep in healthy:
            r = self._router._replica_for(ep)
            if r is None:
                continue
            try:
                n = self._router._client(r).kv_probe(keys)
            except (ConnectionError, TimeoutError, OSError,
                    RuntimeError):
                continue
            if n > best_n:
                best, best_n = ep, n
        if best is not None:
            # revalidate at pin time: the probe loop is slow (network
            # round-trips), and a cordon/mark-down can land between the
            # healthy snapshot above and here — locality must never
            # override liveness
            r = self._router._replica_for(best)
            if r is None or not r.healthy or r.cordoned:
                stat_add("serving/router/kv_place_rejected")
                return
            with self._lock:
                if self._endpoint is None:
                    self._endpoint = best
                    stat_add("serving/router/kv_placements")

    def _wrap(self, fn, *, during_generation: bool):
        ep = self._endpoint
        try:
            return fn()
        except WireShedError:
            raise                     # never executed: safe anywhere
        except (ConnectionError, TimeoutError, OSError) as e:
            if isinstance(e, GenerationFailed):
                raise
            r = self._router._replica_for(ep) if ep else None
            if r is not None:
                self._router._mark_down(r, e)
            if during_generation:
                raise GenerationFailed(
                    f"generation op failed on replica {ep}: "
                    f"{type(e).__name__}: {e} — slot state lost, "
                    "restart the generation", ep or "?") from e
            raise

    def infer(self, model: str, *inputs,
              tenant: str | None = None) -> list[np.ndarray]:
        """Sticky infer (cache/session affinity). Errors surface; the
        next call re-pins if the member was lost."""
        client = self._client()
        return self._wrap(
            lambda: client.infer(model, *inputs, tenant=tenant),
            during_generation=False)

    def health(self) -> dict:
        client = self._client()
        return self._wrap(lambda: client.health(),
                          during_generation=False)

    def generate(self, model: str, prompt, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id: int | None = None,
                 seed: int = 0, poll_wait_s: float = 0.25,
                 resume_budget: int | None = None,
                 tenant: str | None = None,
                 priority: str | None = None):
        """Streaming generation pinned to the session's replica: start,
        every poll, and the close-time cancel all hit the replica
        holding the slot. Returns an iterator of token ids.

        ``resume_budget`` (default: ``FLAGS_gen_resume_budget``) turns
        on lossless stream resumption: when the stream breaks mid-flight
        — connection loss, replica death, or a server-side engine reset
        — the session re-pins to a fresh healthy replica and replays
        ``prompt + tokens already delivered`` as a prefill-from-prefix
        (``rng_skip`` replays the sampling-RNG position), continuing the
        stream from where it broke; greedy output is byte-identical to
        an uninterrupted run. More than ``resume_budget`` restarts
        surfaces the typed :class:`StreamResumeExhausted`. A
        :class:`~paddle_tpu.serving.engine.RequestQuarantined` rejection
        is never resumed. Budget 0 — the flag default — keeps the
        original fail-loud behavior byte-identically."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        budget = (int(flag("gen_resume_budget")) if resume_budget is None
                  else int(resume_budget))
        # One stream trace id per LOGICAL stream, minted here so every
        # resume attempt replays the same id onto its replacement
        # replica — obs_dump then merges the stream's whole life across
        # replicas into one trace. Only minted with tracing on.
        trace_id = _trace.new_id() if _trace.recording() else None
        # The tenant identity likewise rides every resume attempt, so
        # per-tenant ledger counters keep accruing to the same tenant
        # on whichever replica inherits the stream.
        kw = dict(temperature=temperature, top_k=top_k, top_p=top_p,
                  eos_token_id=eos_token_id, seed=seed,
                  poll_wait_s=poll_wait_s, trace_id=trace_id,
                  tenant=tenant, priority=priority)
        if self._router._kv_locality:
            self._kv_place(prompt)
        if budget <= 0:
            return self._stream_once(model, prompt, max_new_tokens, **kw)
        return self._resuming_stream(model, prompt, max_new_tokens,
                                     budget=budget, **kw)

    def _stream_once(self, model: str, prompt, max_new_tokens: int, *,
                     temperature: float, top_k: int, top_p: float,
                     eos_token_id: int | None, seed: int,
                     poll_wait_s: float, rng_skip: int = 0,
                     trace_id: str | None = None,
                     tenant: str | None = None,
                     fingerprint: str | None = None,
                     priority: str | None = None):
        """One pinned stream attempt (the pre-resumption ``generate``
        body). Server-side failures that lost the slot state but left
        the replica up — the ``engine reset:`` marker — surface as
        :class:`GenerationFailed` (resumable), a TTL reap as the typed
        :class:`~paddle_tpu.serving.engine.GenerationExpired`."""
        client = self._client()
        ep = self._endpoint
        gen_id = self._wrap(
            lambda: client.generate_start(
                model, prompt, max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
                seed=seed, rng_skip=rng_skip, trace_id=trace_id,
                tenant=tenant, fingerprint=fingerprint,
                priority=priority),
            during_generation=True)
        with self._lock:
            self._active += 1

        def stream():
            n, finished = 0, False
            try:
                while True:
                    doc = self._wrap(
                        lambda: client.generate_poll(
                            model, gen_id, start=n, wait_s=poll_wait_s),
                        during_generation=True)
                    for tok in doc["tokens"]:
                        yield int(tok)
                    n += len(doc["tokens"])
                    if doc["done"]:
                        finished = True
                        err = doc.get("error")
                        if err:
                            if RESET_MARKER in err:
                                # slot state lost to a self-healing
                                # engine reset; the replica is up —
                                # resumable, never silently retried
                                raise GenerationFailed(
                                    f"generation {gen_id} on {ep} "
                                    f"failed: {err}", ep or "?")
                            if EXPIRED_MARKER in err:
                                raise GenerationExpired(
                                    f"generation {gen_id} on {ep}: "
                                    f"{err}")
                            raise RuntimeError(
                                f"generation {gen_id} on {ep} failed: "
                                f"{err}")
                        return
            finally:
                with self._lock:
                    self._active -= 1
                if not finished:
                    try:
                        client.generate_cancel(model, gen_id)
                    except (RuntimeError, ConnectionError, OSError):
                        pass

        return stream()

    def _resuming_stream(self, model: str, prompt, max_new_tokens: int,
                         *, temperature: float, top_k: int, top_p: float,
                         eos_token_id: int | None, seed: int,
                         poll_wait_s: float, budget: int,
                         trace_id: str | None = None,
                         tenant: str | None = None,
                         priority: str | None = None):
        """Drive :meth:`_stream_once` attempts, replaying
        ``prompt + delivered`` onto a freshly pinned replica after each
        mid-flight loss, until the stream completes or the budget is
        exhausted (typed :class:`StreamResumeExhausted`). Delivered
        tokens are never re-yielded; greedy replays are byte-identical
        by the engine's prefill-from-prefix determinism contract, and
        sampled replays pass ``rng_skip=len(delivered)`` so the engine
        fast-forwards the per-(prompt, seed) key schedule to the break
        position. Every replay also carries the ORIGINAL stream's crash
        fingerprint (header ``fp``): the replay prompt grew by the
        delivered tokens and would hash fresh, so without the carry a
        poisoned stream dodges quarantine by failing over."""
        delivered: list[int] = []
        attempts = 0
        last: BaseException | None = None
        fp = stream_fingerprint(prompt, temperature, top_k, top_p, seed)
        while True:
            n0 = len(delivered)
            try:
                if n0 == 0:
                    inner = self._stream_once(
                        model, prompt, max_new_tokens,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, eos_token_id=eos_token_id,
                        seed=seed, poll_wait_s=poll_wait_s,
                        trace_id=trace_id, tenant=tenant,
                        priority=priority)
                else:
                    replay = np.concatenate(
                        [prompt, np.asarray(delivered, np.int32)])
                    if self._router._kv_locality:
                        # KV-native failover: land the resumed stream
                        # on the replica whose store already holds the
                        # longest prefix of the replay — its admission
                        # fetches instead of recomputing prefill
                        self._kv_place(replay)
                    inner = self._stream_once(
                        model, replay, max_new_tokens - n0,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, eos_token_id=eos_token_id,
                        seed=seed, poll_wait_s=poll_wait_s, rng_skip=n0,
                        trace_id=trace_id, tenant=tenant,
                        fingerprint=fp, priority=priority)
                for tok in inner:
                    delivered.append(int(tok))
                    yield int(tok)
                return
            except StreamResumeExhausted:
                raise
            except GenerationFailed as e:
                last = e
            except (ConnectionError, TimeoutError, OSError) as e:
                if attempts == 0 and n0 == 0:
                    raise            # initial start errors keep their type
                last = e             # restart-time failure: consume budget
            if len(delivered) >= max_new_tokens or (
                    eos_token_id is not None and delivered
                    and delivered[-1] == int(eos_token_id)):
                return               # broke after the final token: done
            attempts += 1
            if attempts > budget:
                stat_add("serving/router/resume_exhausted")
                raise StreamResumeExhausted(
                    f"generation stream lost its replica {attempts} "
                    f"time(s), past the resume budget "
                    f"({budget}; FLAGS_gen_resume_budget) — "
                    f"{len(delivered)}/{max_new_tokens} tokens were "
                    f"delivered; last: {type(last).__name__}: {last}",
                    getattr(last, "endpoint", None) or "?",
                    attempts=attempts) from last
            stat_add("serving/router/stream_resumes")
            if trace_id is not None and _trace.recording():
                # client-side marker in the SAME stream trace: the
                # merged dump shows exactly where the replica switch
                # happened between the dead engine's spans and the
                # survivor's
                with _trace.server_span("gen/stream_resume", trace_id,
                                        None, attempt=attempts,
                                        delivered=len(delivered)):
                    pass
            with self._lock:
                self._endpoint = None    # re-pin over current membership
            time.sleep(min(0.05 * attempts, 0.5))
