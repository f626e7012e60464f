"""Runtime stat registry + host monitors.

Reference: ``paddle/fluid/platform/monitor.h:77,130`` — a global
``StatRegistry`` of named int64 stats updated through ``STAT_ADD`` macros
scattered in hot paths (GPU memory stats etc.), exported to Python for
observability; plus the scope-buffered monitor
(``framework/details/scope_buffered_monitor.cc``) tracking per-step
resource deltas.

TPU mapping: device memory is XLA's (``jax.local_devices()[0]
.memory_stats()`` is the authoritative source, surfaced here); the
registry tracks host-side counters — steps, tokens, data-pipeline stalls,
checkpoint writes.
"""

from __future__ import annotations

import bisect
import math
import re
import threading
from typing import Any

__all__ = ["StatRegistry", "stats", "stat_add", "stat_set", "get_stat",
           "observe", "get_histogram", "export_stats", "export_histograms",
           "export_prometheus", "merge_histograms", "hist_fraction_above",
           "reset_stats", "device_memory_stats",
           "host_rss_bytes", "host_peak_rss_bytes"]


# Fixed log-spaced histogram buckets: 3 per decade from 1e-7 to 1e+3
# (100 ns .. ~17 min when observing seconds) + one overflow bucket. Fixed
# bounds keep observe() O(log n) with zero allocation and make histograms
# mergeable across processes.
_BUCKET_BOUNDS = tuple(10.0 ** (-7 + i / 3.0) for i in range(31))


class _Histogram:
    """Fixed-bucket latency/size histogram (quantiles via log
    interpolation inside the landing bucket, clamped to observed
    min/max). Mutated only under the owning registry's lock."""

    __slots__ = ("counts", "sum", "count", "min", "max")

    def __init__(self):
        self.counts = [0] * (len(_BUCKET_BOUNDS) + 1)
        self.sum = 0.0
        self.count = 0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, value: float) -> None:
        self.counts[bisect.bisect_left(_BUCKET_BOUNDS, value)] += 1
        self.sum += value
        self.count += 1
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def quantile(self, q: float) -> float:
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= target and c:
                lo = _BUCKET_BOUNDS[i - 1] if i > 0 else self.min
                hi = (_BUCKET_BOUNDS[i] if i < len(_BUCKET_BOUNDS)
                      else self.max)
                lo = max(lo, self.min)
                hi = min(hi, self.max)
                if lo <= 0 or hi <= lo:
                    return hi
                # log interpolation: fraction of this bucket's mass below
                # the target maps onto the bucket's log-spaced width
                frac = (target - (cum - c)) / c
                return lo * (hi / lo) ** frac
        return self.max

    def summary(self, raw: bool = False) -> dict[str, Any]:
        doc: dict[str, Any] = {
            "count": self.count, "sum": self.sum,
            "min": self.min if self.count else 0.0,
            "max": self.max if self.count else 0.0,
            "p50": self.quantile(0.50), "p95": self.quantile(0.95),
            "p99": self.quantile(0.99)}
        if raw:
            # bucket counts ride along so histograms from different
            # processes can be MERGED (fixed bounds make counts addable)
            # instead of having their quantiles averaged, which is wrong
            doc["buckets"] = list(self.counts)
        return doc

    @classmethod
    def from_raw(cls, doc: dict[str, Any]) -> "_Histogram":
        h = cls()
        buckets = list(doc.get("buckets") or ())
        if len(buckets) == len(h.counts):
            h.counts = [int(c) for c in buckets]
        h.sum = float(doc.get("sum", 0.0))
        h.count = int(doc.get("count", 0))
        if h.count:
            h.min = float(doc.get("min", 0.0))
            h.max = float(doc.get("max", 0.0))
        return h

    def merge(self, other: "_Histogram") -> None:
        self.counts = [a + b for a, b in zip(self.counts, other.counts)]
        self.sum += other.sum
        self.count += other.count
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)


class StatRegistry:
    """Thread-safe named counters (int or float) + observation
    histograms (``observe()``, fixed log-spaced buckets)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: dict[str, float] = {}
        self._hists: dict[str, _Histogram] = {}

    def add(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._stats[name] = self._stats.get(name, 0) + value

    def set(self, name: str, value: float) -> None:
        with self._lock:
            self._stats[name] = value

    def get(self, name: str, default: float = 0) -> float:
        with self._lock:
            return self._stats.get(name, default)

    def observe(self, name: str, value: float) -> None:
        """Record one observation (latency, size, wait) into the named
        histogram — the p50/p95/p99 companion to ``add()`` counters."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = _Histogram()
            h.observe(float(value))

    def histogram(self, name: str) -> dict[str, float] | None:
        """count/sum/min/max/p50/p95/p99 summary, or None if never
        observed."""
        with self._lock:
            h = self._hists.get(name)
            return h.summary() if h is not None else None

    def export(self, prefix: str | None = None) -> dict[str, float]:
        with self._lock:
            if prefix is None:
                return dict(self._stats)
            return {k: v for k, v in self._stats.items()
                    if k.startswith(prefix)}

    def export_histograms(self, prefix: str | None = None,
                          raw: bool = False
                          ) -> dict[str, dict[str, Any]]:
        with self._lock:
            return {k: h.summary(raw) for k, h in self._hists.items()
                    if prefix is None or k.startswith(prefix)}

    def reset(self, prefix: str | None = None) -> None:
        with self._lock:
            if prefix is None:
                self._stats.clear()
                self._hists.clear()
            else:
                for k in [k for k in self._stats if k.startswith(prefix)]:
                    del self._stats[k]
                for k in [k for k in self._hists if k.startswith(prefix)]:
                    del self._hists[k]


stats = StatRegistry()          # the global registry (monitor.h pattern)


def stat_add(name: str, value: float = 1) -> None:
    """STAT_ADD macro analogue."""
    stats.add(name, value)


def stat_set(name: str, value: float) -> None:
    stats.set(name, value)


def get_stat(name: str, default: float = 0) -> float:
    return stats.get(name, default)


def observe(name: str, value: float) -> None:
    """Record a histogram observation in the global registry."""
    stats.observe(name, value)


def get_histogram(name: str) -> dict[str, float] | None:
    return stats.histogram(name)


def export_stats(prefix: str | None = None) -> dict[str, float]:
    return stats.export(prefix)


def export_histograms(prefix: str | None = None, raw: bool = False
                      ) -> dict[str, dict[str, Any]]:
    """Histogram summaries from the global registry. ``raw=True`` adds
    each histogram's fixed-bound bucket counts so snapshots from
    different processes can be combined with :func:`merge_histograms`
    (the wire ``health`` op ships these to fleet scrapers)."""
    return stats.export_histograms(prefix, raw)


def merge_histograms(docs: list[dict[str, Any]],
                     raw: bool = False) -> dict[str, Any]:
    """Merge raw histogram snapshots (``export_histograms(raw=True)``
    entries, e.g. one per fleet endpoint) into a single summary with
    exact combined quantiles — possible because every process shares the
    same fixed log-spaced bucket bounds."""
    merged = _Histogram()
    for doc in docs:
        merged.merge(_Histogram.from_raw(doc))
    return merged.summary(raw)


def hist_fraction_above(doc: dict[str, Any], threshold: float,
                        conservative: bool = False) -> float:
    """Fraction of a raw histogram snapshot's observations at or above
    ``threshold`` — the SLO-violation numerator for burn-rate math
    (``serving/metrics.py``). Buckets whose lower bound is >= threshold
    count in full; the bucket the threshold itself lands in contributes
    the linearly interpolated share of its mass above the threshold
    (individual observations inside a bucket are unrecoverable, so the
    uniform-spread assumption of Prometheus' ``histogram_quantile`` is
    applied). ``conservative=True`` restores the pre-interpolation
    behavior — the whole boundary bucket counts as below — which
    systematically under-counts violations whenever the threshold falls
    inside a populated bucket: with these 3-per-decade bounds a bucket
    spans ~2.15x in value, so an SLO threshold mid-bucket could hide up
    to that bucket's entire mass from the burn rate. 0.0 when the
    snapshot is empty or carries no buckets."""
    buckets = doc.get("buckets") if doc else None
    total = int(doc.get("count", 0)) if doc else 0
    if not buckets or total <= 0:
        return 0.0
    # bucket j holds values v with bisect_left(bounds, v) == j, i.e.
    # (bounds[j-1], bounds[j]]; every bucket past j is all-violating
    j = bisect.bisect_left(_BUCKET_BOUNDS, threshold)
    violating = float(sum(int(c) for c in buckets[j + 1:]))
    boundary = int(buckets[j]) if j < len(buckets) else 0
    if boundary and not conservative:
        lo = _BUCKET_BOUNDS[j - 1] if j > 0 else 0.0
        # the overflow bucket has no upper bound; the snapshot's
        # observed max is the best available one
        hi = (_BUCKET_BOUNDS[j] if j < len(_BUCKET_BOUNDS)
              else float(doc.get("max", lo)))
        if hi > lo:
            frac = min(max((hi - threshold) / (hi - lo), 0.0), 1.0)
            violating += boundary * frac
    return min(violating / total, 1.0)


def reset_stats(prefix: str | None = None) -> None:
    stats.reset(prefix)


_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    n = _PROM_BAD.sub("_", name)
    return "_" + n if n[:1].isdigit() else n


def export_prometheus(prefix: str | None = None) -> str:
    """Prometheus text exposition of the registry: counters/gauges as
    ``gauge`` lines, histograms as ``summary`` families (p50/p95/p99
    ``quantile`` labels + ``_sum``/``_count``) plus a sibling
    ``<name>_hist`` **histogram** family carrying the real cumulative
    le-labeled bucket counts — what ``histogram_quantile()`` and
    recording rules consume; the pre-computed quantiles in the summary
    can't be re-aggregated across instances, the buckets can. (Two
    families because one metric name can't carry two TYPEs.)
    Scrape-ready for the fleet-wide dashboards the reference exported
    through monitor.h's Python bindings."""
    lines: list[str] = []
    for name, value in sorted(stats.export(prefix).items()):
        pn = _prom_name(name)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {value:g}")
    for name, h in sorted(stats.export_histograms(prefix,
                                                  raw=True).items()):
        pn = _prom_name(name)
        lines.append(f"# TYPE {pn} summary")
        for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            lines.append(f'{pn}{{quantile="{q}"}} {h[key]:g}')
        lines.append(f"{pn}_sum {h['sum']:g}")
        lines.append(f"{pn}_count {h['count']:g}")
        hn = pn + "_hist"
        lines.append(f"# TYPE {hn} histogram")
        cum = 0
        for bound, c in zip(_BUCKET_BOUNDS, h["buckets"]):
            cum += int(c)
            lines.append(f'{hn}_bucket{{le="{bound:g}"}} {cum}')
        cum += int(h["buckets"][-1])     # overflow bucket
        lines.append(f'{hn}_bucket{{le="+Inf"}} {cum}')
        lines.append(f"{hn}_sum {h['sum']:g}")
        lines.append(f"{hn}_count {h['count']:g}")
    return "\n".join(lines) + ("\n" if lines else "")


def device_memory_stats(device=None) -> dict[str, Any]:
    """XLA's per-device memory stats (bytes_in_use, peak_bytes_in_use, …)
    — the STAT_GPU_MEM role, owned by the runtime not the framework."""
    import jax

    dev = device or jax.local_devices()[0]
    return dict(dev.memory_stats() or {})


def host_rss_bytes() -> int:
    """CURRENT resident set size of this process, from /proc/self/status
    VmRSS (ru_maxrss is the lifetime *peak*, not current — see
    :func:`host_peak_rss_bytes`); falls back to the peak where /proc is
    unavailable (macOS)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024     # value is kB
    except (OSError, ValueError, IndexError):
        pass
    return host_peak_rss_bytes()


def host_peak_rss_bytes() -> int:
    """Peak resident set size over the process lifetime (ru_maxrss)."""
    import resource

    # ru_maxrss is KiB on Linux
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
