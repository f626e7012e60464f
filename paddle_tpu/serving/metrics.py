"""Fleet metrics hub: a windowed in-memory TSDB for control loops.

Reference role: the fleet half of ``paddle/fluid/platform/monitor.h`` —
the reference exported its global ``StatRegistry`` per process and left
cross-host aggregation to external scrapers; here the
:class:`ServingController` IS the scraper, so the aggregation layer
lives in-process. Each controller tick feeds every replica's ``health``
snapshot into the hub; the hub turns cumulative counters and histogram
totals into **per-tick deltas** (reset-aware: a restarted replica's
counters going backwards clamp to zero instead of producing a giant
negative spike) and answers windowed queries over them:

- ``window_histogram(name, ticks)`` — exact merged distribution of the
  last N ticks' observations across the whole fleet (possible because
  every process shares ``monitor._BUCKET_BOUNDS``),
- ``rate(name, ticks)`` — fleet-wide counter rate per second,
- ``burn_rates(name, threshold)`` — multi-window SLO **burn rate**: the
  fraction of windowed observations violating ``threshold``, divided by
  the error budget.  The violating fraction linearly interpolates the
  mass of the bucket the threshold lands in
  (:func:`~paddle_tpu.core.monitor.hist_fraction_above`), so an SLO
  threshold falling mid-bucket no longer hides up to that bucket's
  whole mass from the burn — the old all-below rounding is available as
  ``conservative=True``.  Burn 1.0 means the budget is being consumed
  exactly as fast as allowed; the controller requires BOTH a fast
  (acute) and a slow (sustained) window above
  ``FLAGS_control_burn_threshold`` before declaring TTFT pressure — the
  standard multi-window burn-rate alert, replacing the old single-tick
  raw-p99 breach check that chased noise.

With ``FLAGS_gen_ledger`` on, engine health docs additionally carry the
request-ledger signals (``serving/ledger.py``) and the hub rolls them
up fleet-wide: ``phase_percentiles()`` merges the per-phase latency
histograms every finalized generation observes (typed
:class:`PhasesNotReady` — not a bare ``{}`` — when nothing merged yet),
``tenants()`` sums the per-tenant consumption gauges, and
``fleet_goodput()`` combines the engines' loop-time taxonomies into one
fleet goodput fraction.  With ``FLAGS_gen_kv_store`` on, ``fleet_kv()``
likewise sums the engines' KV-store gauge blocks into the fleet hit
rate / fetch-bytes / demotion scoreboard.

Membership churn is survivable by construction: an endpoint's first
snapshot is a baseline (no delta), an endpoint that disappears simply
stops contributing new deltas, and its state is pruned after a full
slow window of absence.  An endpoint RE-ADDED after such an absence
(a replica cordoned away and readopted, an HA takeover) re-baselines
instead of differencing the whole gap's cumulative counters into one
bogus window delta.  Gauge-like per-model engine stats
(``health()["generators"]``) are kept as labeled (endpoint, model)
last-value series.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any

from paddle_tpu.core.monitor import hist_fraction_above, merge_histograms

__all__ = ["MetricsHub", "PhasesNotReady", "hist_delta"]


class PhasesNotReady(dict):
    """Typed empty result from :meth:`MetricsHub.phase_percentiles`:
    nothing merged this window.  A dict subclass so it JSON-serializes
    through health/report paths, and **falsy** (it holds no phase
    entries) so ``if pct:`` call sites behave exactly as with the old
    bare ``{}`` — but it carries the diagnosis the bare dict silently
    dropped: ``ticks_observed`` maps endpoint -> health ticks ingested.
    Cumulative histograms need two ticks to difference into a window
    delta, so an endpoint below 2 explains the emptiness ("not ready
    yet"); every endpoint at >= 2 with still nothing means the request
    ledger is off (or idle) fleet-wide."""

    __slots__ = ("ticks_observed",)

    def __init__(self, ticks_observed: dict[str, int]):
        super().__init__()
        self.ticks_observed = dict(ticks_observed)

    @property
    def not_ready(self) -> bool:
        return True

    @property
    def waiting(self) -> list[str]:
        """Endpoints that cannot contribute yet (fewer than two ticks)."""
        return sorted(ep for ep, n in self.ticks_observed.items() if n < 2)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"PhasesNotReady(ticks_observed={self.ticks_observed!r})"


def hist_delta(prev: dict | None, cur: dict | None) -> dict | None:
    """Per-window histogram delta from two cumulative raw snapshots
    (``export_histograms(raw=True)`` docs): what was observed *between*
    them.  None when there is nothing to diff — no current snapshot, no
    raw buckets, no previous snapshot (first sight is a baseline), or an
    empty window.  Negative bucket deltas (endpoint restarted, counters
    reset) clamp to zero, so a replica bounce reads as an empty window
    instead of poisoning the merge."""
    if not cur or not cur.get("buckets"):
        return None
    if not prev or not prev.get("buckets"):
        return None                      # first sight: baseline only
    buckets = [max(int(c) - int(p), 0)
               for c, p in zip(cur["buckets"], prev["buckets"])]
    count = sum(buckets)
    if count == 0:
        return None                      # nothing happened this window
    return {
        "buckets": buckets,
        "count": count,
        "sum": max(float(cur.get("sum", 0.0))
                   - float(prev.get("sum", 0.0)), 0.0),
        # min/max are cumulative (not diffable); the current snapshot's
        # values are the best available bounds for quantile clamping
        "min": float(cur.get("min", 0.0)),
        "max": float(cur.get("max", 0.0)),
    }


class _EndpointSeries:
    """Per-endpoint state: last cumulative snapshots (the delta
    baselines), a ring of per-tick deltas, and latest per-model gauges.
    Mutated only under the owning hub's lock."""

    __slots__ = ("prev_hists", "prev_stats", "ticks", "gauges", "emb",
                 "last_tick")

    def __init__(self, slow_ticks: int):
        self.prev_hists: dict[str, dict] = {}
        self.prev_stats: dict[str, float] = {}
        # (tick, ts, hist_deltas, stat_deltas) — slow window bounds it
        self.ticks: deque[tuple[int, float, dict, dict]] = deque(
            maxlen=max(slow_ticks, 1))
        self.gauges: dict[str, dict[str, Any]] = {}
        # latest embedding-tier gauge block (FLAGS_serving_emb replicas
        # ship it in health as "emb"); None on replicas without the tier
        self.emb: dict[str, Any] | None = None
        self.last_tick = 0

    def ingest(self, tick: int, ts: float, doc: dict) -> None:
        self.last_tick = tick
        hists = doc.get("histograms") or {}
        h_deltas: dict[str, dict] = {}
        for name, cur in hists.items():
            d = hist_delta(self.prev_hists.get(name), cur)
            if d is not None:
                h_deltas[name] = d
        self.prev_hists = {n: c for n, c in hists.items()
                           if isinstance(c, dict)}
        stats = doc.get("stats") or {}
        s_deltas: dict[str, float] = {}
        for name, cur in stats.items():
            if not isinstance(cur, (int, float)):
                continue
            prev = self.prev_stats.get(name)
            if prev is not None:         # first sight is a baseline
                s_deltas[name] = max(float(cur) - float(prev), 0.0)
        self.prev_stats = {n: float(v) for n, v in stats.items()
                           if isinstance(v, (int, float))}
        gens = doc.get("generators")
        if isinstance(gens, dict):
            self.gauges = {m: dict(g) for m, g in gens.items()
                           if isinstance(g, dict)}
        emb = doc.get("emb")
        if isinstance(emb, dict):
            self.emb = dict(emb)
        self.ticks.append((tick, ts, h_deltas, s_deltas))

    def window(self, tick: int, ticks: int):
        """Delta tuples within the last ``ticks`` hub ticks."""
        lo = tick - max(int(ticks), 1)
        return [t for t in self.ticks if t[0] > lo]


class MetricsHub:
    """Windowed fleet TSDB fed by controller health scrapes.

    ``fast_ticks``/``slow_ticks`` are the two burn-rate windows (in hub
    ingests, i.e. controller ticks).  Short histories are not an error:
    every windowed query uses however many ticks actually exist, so the
    hub gives sane answers from the second tick onward."""

    def __init__(self, fast_ticks: int = 5, slow_ticks: int = 60):
        self.fast_ticks = max(int(fast_ticks), 1)
        self.slow_ticks = max(int(slow_ticks), self.fast_ticks)
        self._lock = threading.Lock()
        self._tick = 0
        self._series: dict[str, _EndpointSeries] = {}

    # -- ingestion ---------------------------------------------------------
    def ingest(self, healths: dict[str, dict]) -> int:
        """One hub tick: feed ``{endpoint: health_doc}`` (unreachable or
        malformed docs are skipped — the endpoint just misses the tick),
        prune endpoints gone a full slow window, return the tick id."""
        ts = time.monotonic()
        with self._lock:
            self._tick += 1
            for ep, doc in healths.items():
                if (not isinstance(doc, dict)
                        or doc.get("status") == "unreachable"):
                    continue
                s = self._series.get(ep)
                if (s is not None
                        and self._tick - s.last_tick > self.slow_ticks):
                    # re-adoption after a full slow window of absence:
                    # ingestion (which refreshes last_tick) runs before
                    # the prune sweep below, so a returning endpoint
                    # would dodge its own prune and difference the
                    # WHOLE gap's cumulative counters against stale
                    # baselines — one giant bogus window delta. Treat
                    # it as brand new: first sight is a baseline.
                    s = None
                if s is None:
                    s = self._series[ep] = _EndpointSeries(
                        self.slow_ticks)
                s.ingest(self._tick, ts, doc)
            gone = [ep for ep, s in self._series.items()
                    if self._tick - s.last_tick > self.slow_ticks]
            for ep in gone:
                del self._series[ep]
            return self._tick

    # -- queries -----------------------------------------------------------
    def window_histogram(self, name: str,
                         ticks: int | None = None) -> dict | None:
        """Merged raw-bucket summary of ``name`` over the last N ticks
        across every endpoint, or None when nothing was observed."""
        with self._lock:
            docs = [d[2][name]
                    for s in self._series.values()
                    for d in s.window(self._tick, ticks or self.fast_ticks)
                    if name in d[2]]
        if not docs:
            return None
        return merge_histograms(docs, raw=True)

    def rate(self, name: str, ticks: int | None = None) -> float:
        """Fleet-wide counter rate (units/second) of ``name`` over the
        last N ticks; 0.0 without enough history to span time."""
        with self._lock:
            total = 0.0
            t_lo, t_hi = None, None
            for s in self._series.values():
                for tick, ts, _h, sd in s.window(self._tick,
                                                 ticks or self.fast_ticks):
                    total += sd.get(name, 0.0)
                    t_lo = ts if t_lo is None else min(t_lo, ts)
                    t_hi = ts if t_hi is None else max(t_hi, ts)
        if t_lo is None or t_hi is None or t_hi <= t_lo:
            return 0.0
        return total / (t_hi - t_lo)

    def burn_rates(self, name: str, threshold: float,
                   budget: float, tenant: str | None = None
                   ) -> tuple[float, float]:
        """(fast, slow) SLO burn rates for histogram ``name`` against
        ``threshold``: violating-fraction / ``budget`` per window.  No
        observations in a window → 0.0 (no traffic burns no budget).

        ``tenant=`` narrows to the per-tenant split of the histogram
        (``<name>/<tenant>`` — the engine observes e.g.
        ``gen/ttft_s/<tn>`` next to the fleet-wide series when a
        tenant header rode the request), so fairness decisions can
        cite per-tenant SLO burn rather than only fleet-wide."""
        if tenant:
            name = f"{name}/{tenant}"
        burns = []
        for w in (self.fast_ticks, self.slow_ticks):
            h = self.window_histogram(name, w)
            frac = hist_fraction_above(h, threshold) if h else 0.0
            burns.append(frac / budget if budget > 0 else 0.0)
        return burns[0], burns[1]

    def gauges(self) -> dict[str, dict[str, dict[str, Any]]]:
        """Latest (endpoint → model → engine-stats) gauge series."""
        with self._lock:
            return {ep: {m: dict(g) for m, g in s.gauges.items()}
                    for ep, s in self._series.items()}

    # -- request-ledger rollups (FLAGS_gen_ledger) -------------------------
    #: histograms the request ledger observes per finalized generation;
    #: windowed merges of these are the fleet latency decomposition
    PHASE_HISTOGRAMS = ("gen/e2e_s", "gen/phase/admit_wait_s",
                        "gen/phase/prefill_s", "gen/phase/decode_s",
                        "gen/phase/deliver_s")

    def ticks_observed(self) -> dict[str, int]:
        """Health ticks ingested per endpoint (windowed count). Cumulative
        histograms need TWO ticks to difference into a window delta, so
        an endpoint here with fewer than 2 cannot contribute to any
        windowed merge yet — the readiness signal
        :meth:`phase_percentiles` reports on an empty merge."""
        with self._lock:
            return {ep: len(s.ticks) for ep, s in self._series.items()}

    def phase_percentiles(self, ticks: int | None = None
                          ) -> dict[str, dict[str, float]]:
        """Fleet-merged per-phase latency percentiles over the last N
        ticks (default: slow window): the request ledger's phase
        histograms combined across every endpoint.  Phases nothing
        observed are omitted.  When NOTHING merged, returns the typed
        (and falsy — ``if pct:`` callers keep working)
        :class:`PhasesNotReady` instead of a bare ``{}``, carrying
        ``ticks_observed`` per endpoint: before an endpoint's second
        tick there is no delta to merge, and the caller can now tell
        "not ready yet" (some endpoint below 2 ticks) from "ledger off
        fleet-wide" (everyone ticking, still nothing) instead of
        guessing at an empty dict."""
        out: dict[str, dict[str, float]] = {}
        for name in self.PHASE_HISTOGRAMS:
            h = self.window_histogram(name, ticks or self.slow_ticks)
            if h is not None:
                out[name] = {k: h[k] for k in
                             ("count", "sum", "p50", "p95", "p99")}
        if not out:
            return PhasesNotReady(self.ticks_observed())
        return out

    def tenants(self) -> dict[str, dict[str, float]]:
        """Fleet-wide per-tenant consumption: every (endpoint, model)
        engine's latest ``tenants`` gauge block summed per tenant.  The
        gauges are cumulative over each engine's lifetime, so the sums
        are too — a replica restart zeroes that replica's contribution,
        like any gauge series."""
        out: dict[str, dict[str, float]] = {}
        with self._lock:
            for s in self._series.values():
                for g in s.gauges.values():
                    tens = g.get("tenants")
                    if not isinstance(tens, dict):
                        continue
                    for tenant, counters in tens.items():
                        if not isinstance(counters, dict):
                            continue
                        agg = out.setdefault(str(tenant), {})
                        for k, v in counters.items():
                            if isinstance(v, (int, float)):
                                agg[k] = agg.get(k, 0.0) + float(v)
        return out

    def fleet_goodput(self) -> dict[str, Any] | None:
        """Fleet goodput rollup: every (endpoint, model) engine's
        ``goodput`` gauge block merged by summing per-bucket seconds —
        equivalent to weighting each engine's fractions by the wall
        clock it accounted.  None when no engine reports one (ledger
        off fleet-wide)."""
        from paddle_tpu.serving.ledger import GOODPUT_USEFUL
        buckets: dict[str, float] = {}
        total = 0.0
        ticks = 0
        engines = 0
        with self._lock:
            for s in self._series.values():
                for g in s.gauges.values():
                    gp = g.get("goodput")
                    if not isinstance(gp, dict):
                        continue
                    engines += 1
                    total += float(gp.get("total_s", 0.0))
                    ticks += int(gp.get("ticks", 0))
                    for b, v in (gp.get("buckets") or {}).items():
                        if isinstance(v, (int, float)):
                            buckets[b] = buckets.get(b, 0.0) + float(v)
        if engines == 0:
            return None
        useful = sum(buckets.get(b, 0.0) for b in GOODPUT_USEFUL)
        return {
            "engines": engines, "total_s": total, "ticks": ticks,
            "buckets": buckets,
            "fractions": {b: (v / total if total > 0 else 0.0)
                          for b, v in buckets.items()},
            "goodput": useful / total if total > 0 else 0.0,
        }

    def fleet_kv(self) -> dict[str, Any] | None:
        """Fleet KV-store rollup: every (endpoint, model) engine's ``kv``
        gauge block (``serving/kvstore.py`` snapshot + engine counters)
        summed, with the derived fleet hit rate over all lookups — the
        disaggregated-serving scoreboard.  None when no engine reports
        one (store off fleet-wide)."""
        counters: dict[str, float] = {}
        roles: dict[str, int] = {}
        engines = 0
        degraded_engines = 0
        with self._lock:
            for s in self._series.values():
                for g in s.gauges.values():
                    kv = g.get("kv")
                    if not isinstance(kv, dict):
                        continue
                    engines += 1
                    if kv.get("degraded"):
                        degraded_engines += 1
                    role = kv.get("role")
                    if isinstance(role, str):
                        roles[role] = roles.get(role, 0) + 1
                    for k, v in kv.items():
                        if isinstance(v, (int, float)) and \
                                not isinstance(v, bool):
                            counters[k] = counters.get(k, 0.0) + float(v)
        if engines == 0:
            return None
        # kvstore counts spill_hits as a subset of hits (either tier)
        hits = counters.get("hits", 0.0)
        lookups = hits + counters.get("misses", 0.0)
        return {
            "engines": engines,
            "roles": roles,
            "counters": counters,
            "hit_rate": hits / lookups if lookups > 0 else 0.0,
            "fetch_bytes": counters.get("fetched_bytes", 0.0),
            "demotions": counters.get("demotions", 0.0),
            "prefill_recomputed": counters.get("prefill_recomputed", 0.0),
            # failure-domain visibility: stores reporting themselves
            # degraded (cordoned / breaker open), fetches that fell
            # back to recompute, deadline abandons, breaker trips
            "degraded_engines": degraded_engines,
            "fetch_degraded": counters.get("fetch_degraded", 0.0),
            "timeouts": counters.get("timeouts", 0.0),
            "breaker_opens": counters.get("breaker_opens", 0.0),
        }

    def fleet_emb(self) -> dict[str, Any] | None:
        """Fleet embedding-serving rollup (``FLAGS_serving_emb``): every
        replica's ``emb`` health block summed — cache hits/misses with
        the derived fleet hit rate, pulled rows/bytes, stale serves,
        rollovers — plus each served table's per-replica version spread
        (``versions``: table -> sorted unique versions; more than one
        entry means a rollover is still propagating).  None when no
        replica reports the tier (flag off fleet-wide)."""
        counters: dict[str, float] = {}
        versions: dict[str, set] = {}
        replicas = 0
        with self._lock:
            for s in self._series.values():
                emb = s.emb
                if not isinstance(emb, dict):
                    continue
                replicas += 1
                for k, v in emb.items():
                    if isinstance(v, (int, float)) and \
                            not isinstance(v, bool):
                        counters[k] = counters.get(k, 0.0) + float(v)
                tables = emb.get("tables")
                if isinstance(tables, dict):
                    for name, t in tables.items():
                        if isinstance(t, dict) and "version" in t:
                            versions.setdefault(str(name), set()).add(
                                int(t["version"]))
        if replicas == 0:
            return None
        hits = counters.get("hits", 0.0)
        lookups = hits + counters.get("misses", 0.0)
        return {
            "replicas": replicas,
            "counters": counters,
            "hit_rate": hits / lookups if lookups > 0 else 0.0,
            "pulled_rows": counters.get("pulled_rows", 0.0),
            "pulled_bytes": counters.get("pulled_bytes", 0.0),
            "stale_serves": counters.get("stale_serves", 0.0),
            "rollovers": counters.get("rollovers", 0.0),
            "versions": {n: sorted(vs) for n, vs in versions.items()},
        }

    def endpoints(self) -> list[str]:
        with self._lock:
            return sorted(self._series)

    def snapshot(self) -> dict[str, Any]:
        """JSON-safe introspection doc (tests, chaos checks, dumps)."""
        with self._lock:
            return {
                "tick": self._tick,
                "fast_ticks": self.fast_ticks,
                "slow_ticks": self.slow_ticks,
                "endpoints": {
                    ep: {"last_tick": s.last_tick,
                         "ticks": len(s.ticks),
                         "models": sorted(s.gauges)}
                    for ep, s in self._series.items()},
            }
