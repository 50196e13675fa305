"""Metrics time series — the retention half of the observability plane.

``/metrics`` is a point-in-time snapshot: between two scrapes the registry's
history is gone, so nothing in-process can answer "what was commit p99 over
the last five minutes" — the exact question the SLO burn-rate monitors
(`obs/slo`) ask. This module retains it: a ``delta-obs-scraper`` daemon
snapshots the telemetry registry every ``delta.tpu.obs.scrape.intervalMs``
into bounded in-memory rings (``delta.tpu.obs.scrape.keep`` samples per
series, default 400 — at the 10s default interval the rings span ~67min,
comfortably past the SLO slow window):

* **counters** — the cumulative value per scrape (windowed rates are a
  subtraction, :func:`counter_window`);
* **gauges** — the value per scrape;
* **histograms** — the cumulative bucket counts per scrape, so a windowed
  quantile is the bucket-quantile of ``counts[now] - counts[window_start]``
  (:func:`quantile_window`, sharing ``telemetry.bucket_quantile``).

Window queries are Prometheus-shaped: a window needs two samples — the
baseline is the newest sample at or before ``now - window``, else the
OLDEST retained sample; with a single sample the window is empty. Deltas
therefore never reach before the first scrape: counters and histograms
that predate the scraper (all-time process history) contribute nothing,
and a ring that evicted history under-covers its window instead of
silently widening to all-time (which would let an hour-old incident keep
the "slow" burn hot forever, or fire ratio alerts off lifetime counts the
moment an operator starts the scraper).

Memory is strictly bounded: (series ⨯ keep) samples, each a small tuple;
rings resize in place when ``keep`` changes, and the series map itself is
capped at ``delta.tpu.obs.scrape.maxSeries`` — past it, the series whose
value went stale longest ago are evicted (under table churn the per-table
labeled series would otherwise accumulate for the life of the process). Everything is pull-by-call
except the daemon tick, and the whole module is blackout-inert: with
``delta.tpu.telemetry.enabled=false`` :func:`scrape_once` returns before
touching the registry — zero series entries, zero ring growth, zero SLO
evaluation.

Each scrape ends by driving the SLO monitors (``delta.tpu.obs.slo.enabled``)
so a served process needs exactly one daemon for the whole plane. Queryable
via ``GET /slo``/``/fleet`` (`obs/server`) and ``tools/fleet_dump.py``.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["Scraper", "start_scraper", "stop_scraper", "scrape_once",
           "scrape_count", "counter_window", "quantile_window",
           "histogram_labels", "series_snapshot", "reset"]

LabelKey = Tuple[str, Tuple[Tuple[str, str], ...]]

_LOCK = threading.Lock()
#: counter name -> ring of (ts_ms, cumulative value)
_COUNTERS: Dict[str, Deque[Tuple[int, float]]] = {}
#: (gauge name, labels) -> ring of (ts_ms, value)
_GAUGES: Dict[LabelKey, Deque[Tuple[int, float]]] = {}
#: (hist name, labels) -> ring of (ts_ms, bucket_counts, sum, count)
_HISTS: Dict[LabelKey, Deque[Tuple[int, Tuple[int, ...], float, int]]] = {}
_SCRAPES = 0
#: series key -> ts of the last scrape where its VALUE changed (the
#: eviction clock for the maxSeries cap); keys are ("c", name) /
#: ("g", label_key) / ("h", label_key)
_LAST_CHANGE: Dict[tuple, int] = {}
#: evicted series -> the comparator value they were evicted at. The
#: telemetry registry never forgets a series, so an evicted ring would be
#: recreated on the very next scrape; the tombstone (one number, not a
#: ring) keeps it out until its value MOVES again — a dead table's series
#: stays evicted, a quiet-but-live one comes back on its next change.
_EVICTED: Dict[tuple, float] = {}


def _keep() -> int:
    n = conf.get_int("delta.tpu.obs.scrape.keep", 400)
    return n if n > 0 else 400


def _max_series() -> int:
    n = conf.get_int("delta.tpu.obs.scrape.maxSeries", 8192)
    return n if n > 0 else 8192


def _ring(store, key, keep):
    """The ring for ``key`` at maxlen ``keep``; callers hold ``_LOCK``."""
    ring = store.get(key)
    if ring is None:
        ring = store[key] = deque(maxlen=keep)
    elif ring.maxlen != keep:
        ring = store[key] = deque(ring, maxlen=keep)
    return ring


def scrape_once(now_ms: Optional[int] = None,
                evaluate_slo: Optional[bool] = None) -> int:
    """Snapshot the whole telemetry registry into the rings; returns the
    number of series touched (0 under a telemetry blackout — the scrape
    does no registry work at all then). ``now_ms`` is injectable so tests
    can pin window math; ``evaluate_slo`` overrides the
    ``delta.tpu.obs.slo.enabled`` gate."""
    global _SCRAPES
    if not conf.get_bool("delta.tpu.telemetry.enabled", True):
        return 0
    now = int(now_ms if now_ms is not None else time.time() * 1000)
    keep = _keep()
    # registry reads copy under the telemetry lock — each snapshot is
    # internally consistent (never torn mid-bump)
    ctrs = telemetry.counters()
    gags = telemetry.gauges()
    hists = telemetry.histogram_rows()
    with _LOCK:
        for name, value in ctrs.items():
            if _EVICTED.get(("c", name)) == float(value):
                continue  # tombstoned and still not moving
            _EVICTED.pop(("c", name), None)
            ring = _ring(_COUNTERS, name, keep)
            if not ring or ring[-1][1] != float(value):
                _LAST_CHANGE[("c", name)] = now
            else:
                _LAST_CHANGE.setdefault(("c", name), now)
            ring.append((now, float(value)))
        for key, value in gags.items():
            if _EVICTED.get(("g", key)) == float(value):
                continue
            _EVICTED.pop(("g", key), None)
            ring = _ring(_GAUGES, key, keep)
            if not ring or ring[-1][1] != float(value):
                _LAST_CHANGE[("g", key)] = now
            else:
                _LAST_CHANGE.setdefault(("g", key), now)
            ring.append((now, float(value)))
        for name, labels, counts, total, count in hists:
            if _EVICTED.get(("h", (name, labels))) == float(count):
                continue
            _EVICTED.pop(("h", (name, labels)), None)
            ring = _ring(_HISTS, (name, labels), keep)
            if not ring or ring[-1][3] != int(count):
                _LAST_CHANGE[("h", (name, labels))] = now
            else:
                _LAST_CHANGE.setdefault(("h", (name, labels)), now)
            ring.append((now, tuple(counts), float(total), int(count)))
        _evict_stale_series_locked()
        _SCRAPES += 1
        touched = len(ctrs) + len(gags) + len(hists)
        series = len(_COUNTERS) + len(_GAUGES) + len(_HISTS)
    telemetry.bump_counter("obs.scrape.ticks")
    telemetry.set_gauge("obs.scrape.series", series)
    run_slo = (evaluate_slo if evaluate_slo is not None
               else conf.get_bool("delta.tpu.obs.slo.enabled", True))
    if run_slo:
        from delta_tpu.obs import slo

        slo.evaluate(now_ms=now)
    return touched


def _evict_stale_series_locked() -> None:
    """Cap the series map at ``maxSeries`` by dropping the series whose
    value went stale longest ago (dead tables' labeled series stop moving;
    live-but-quiet series outrank them only by recency, which is the best
    signal available without a registry of table lifetimes). Callers hold
    ``_LOCK``."""
    stores = {"c": _COUNTERS, "g": _GAUGES, "h": _HISTS}
    total = sum(len(s) for s in stores.values())
    cap = _max_series()
    if total <= cap:
        return
    by_staleness = sorted(
        _LAST_CHANGE.items(), key=lambda kv: kv[1])  # stalest first
    for (kind, key), _ts in by_staleness[:total - cap]:
        ring = stores[kind].pop(key, None)
        _LAST_CHANGE.pop((kind, key), None)
        if ring:
            # tombstone at the evicted value: the registry still holds the
            # series, so without this the ring is recreated next scrape
            last = ring[-1]
            _EVICTED[(kind, key)] = float(
                last[3] if kind == "h" else last[1])
    if len(_EVICTED) > 4 * cap:
        # the tombstone map must not become its own leak under extreme
        # churn; dropping the oldest costs one re-scrape+re-evict cycle
        for k in list(_EVICTED)[:len(_EVICTED) - 2 * cap]:
            _EVICTED.pop(k, None)


def scrape_count() -> int:
    with _LOCK:
        return _SCRAPES


# ---------------------------------------------------------------------------
# Window queries
# ---------------------------------------------------------------------------


def _window_ends(ring, window_ms: int, now_ms: int):
    """(baseline, latest) samples bracketing the trailing window: latest =
    newest sample, baseline = newest sample at or before ``now - window``,
    else the oldest retained sample. Windows never reach before the first
    scrape — cumulative values that predate the scraper are history, not
    signal (counting them from zero would page on all-time counts the
    moment the scraper starts). baseline None (single sample) = empty
    window."""
    latest = None
    baseline = None
    cutoff = now_ms - window_ms
    for sample in ring:  # rings are small (keep <= a few hundred)
        if sample[0] <= cutoff:
            baseline = sample
        if latest is None or sample[0] >= latest[0]:
            latest = sample
    if baseline is None and len(ring) > 1 and latest is not ring[0]:
        baseline = ring[0]
    if baseline is latest:
        baseline = None  # single usable sample: the window is empty
    return baseline, latest


def counter_window(name: str, window_ms: int,
                   now_ms: Optional[int] = None) -> Dict[str, float]:
    """Counter delta + per-second rate over the trailing window."""
    now = int(now_ms if now_ms is not None else time.time() * 1000)
    with _LOCK:
        ring = _COUNTERS.get(name)
        samples = list(ring) if ring else []
    if not samples:
        return {"delta": 0.0, "ratePerSec": 0.0, "samples": 0}
    baseline, latest = _window_ends(samples, window_ms, now)
    if baseline is None:  # single sample: no delta is computable yet
        return {"delta": 0.0, "ratePerSec": 0.0, "samples": len(samples)}
    delta = max(0.0, latest[1] - baseline[1])
    dt_s = max((latest[0] - baseline[0]) / 1000.0, 1e-9)
    return {"delta": delta, "ratePerSec": delta / dt_s,
            "samples": len(samples)}


def quantile_window(name: str, labels: Tuple[Tuple[str, str], ...],
                    q: float, window_ms: int,
                    now_ms: Optional[int] = None
                    ) -> Tuple[Optional[float], int]:
    """(approximate q-quantile, observation count) of a labeled histogram
    over the trailing window, from cumulative-bucket-count deltas. The
    quantile is None when the window holds no observations; a crossing
    past the last bucket bound reports twice the last bound (conservative
    — "worse than the histogram can resolve" must still compare > any
    threshold)."""
    now = int(now_ms if now_ms is not None else time.time() * 1000)
    with _LOCK:
        ring = _HISTS.get((name, labels))
        samples = list(ring) if ring else []
    if not samples:
        return None, 0
    baseline, latest = _window_ends(samples, window_ms, now)
    if baseline is None:  # single sample: no delta is computable yet
        return None, 0
    _ts, counts_l, _sum_l, count_l = latest
    _bt, counts_b, _sum_b, count_b = baseline
    dcounts = [a - b for a, b in zip(counts_l, counts_b)]
    dcount = count_l - count_b
    if dcount <= 0:
        return None, 0
    value = telemetry.bucket_quantile(dcounts, dcount, q)
    if value is None:  # +Inf bucket crossing
        value = telemetry.HISTOGRAM_BUCKETS[-1] * 2.0
    return value, dcount


def histogram_labels(name: str) -> List[Tuple[Tuple[str, str], ...]]:
    """Every label set the rings hold for histogram ``name``."""
    with _LOCK:
        return [lb for (n, lb) in _HISTS if n == name]


def series_snapshot(prefix: str = "",
                    limit: Optional[int] = None) -> Dict[str, Any]:
    """JSON-able dump of the rings (``/fleet``/``tools/fleet_dump``):
    counters and gauges as ``[[ts, value], ...]``, histograms as
    ``[[ts, count, sum], ...]`` (bucket vectors stay internal — window
    quantiles are served by :func:`quantile_window`). ``limit`` tails each
    series."""
    def _tail(seq):
        # limit <= 0 degrades to "no limit": seq[-(-5):] would DROP the
        # oldest samples while looking like a valid tail, and /fleet feeds
        # the user-controlled ?samples= straight here
        return seq[-limit:] if limit is not None and limit > 0 else seq

    with _LOCK:
        ctrs = {n: _tail([[t, v] for t, v in ring])
                for n, ring in sorted(_COUNTERS.items())
                if not prefix or telemetry._prefix_match(n, prefix)}
        gags = {f"{n}{telemetry._labels_suffix(lb)}":
                _tail([[t, v] for t, v in ring])
                for (n, lb), ring in sorted(_GAUGES.items())
                if not prefix or telemetry._prefix_match(n, prefix)}
        hists = {f"{n}{telemetry._labels_suffix(lb)}":
                 _tail([[t, c, round(s, 3)] for t, _b, s, c in ring])
                 for (n, lb), ring in sorted(_HISTS.items())
                 if not prefix or telemetry._prefix_match(n, prefix)}
        scrapes = _SCRAPES
    return {"scrapes": scrapes, "counters": ctrs, "gauges": gags,
            "histograms": hists}


# ---------------------------------------------------------------------------
# Daemon
# ---------------------------------------------------------------------------


class Scraper:
    """Daemon thread ticking :func:`scrape_once` every
    ``delta.tpu.obs.scrape.intervalMs``. Under a telemetry blackout the
    tick returns immediately — the thread does no registry work."""

    def __init__(self):
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "Scraper":
        if self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="delta-obs-scraper")
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)

    def tick(self) -> None:
        """Wake the daemon for an immediate scrape (tests, operators)."""
        self._wake.set()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                scrape_once()
            except Exception:  # noqa: BLE001 — a bad scrape must not kill
                # the daemon; the next tick retries with fresh state
                telemetry.logger.warning("obs scrape failed", exc_info=True)
            interval = conf.get_int("delta.tpu.obs.scrape.intervalMs", 10_000)
            if interval <= 0:
                interval = 10_000  # a zero/negative conf must not busy-spin
            self._wake.wait(timeout=interval / 1000.0)
            self._wake.clear()


_SCRAPER: Optional[Scraper] = None
_SCRAPER_LOCK = threading.Lock()


def start_scraper() -> Scraper:
    """Start (or return) the process-wide scraper daemon."""
    global _SCRAPER
    with _SCRAPER_LOCK:
        if _SCRAPER is None:
            _SCRAPER = Scraper()
        _SCRAPER.start()
        return _SCRAPER


def stop_scraper() -> None:
    global _SCRAPER
    with _SCRAPER_LOCK:
        if _SCRAPER is not None:
            _SCRAPER.stop()
            _SCRAPER = None


def reset() -> None:
    """Stop the daemon and drop every ring (tests)."""
    global _SCRAPES
    stop_scraper()
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()
        _LAST_CHANGE.clear()
        _EVICTED.clear()
        _SCRAPES = 0
