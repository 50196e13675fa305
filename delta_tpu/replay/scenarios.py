"""Capacity testing + synthetic scenario traces.

:func:`capacity_replay` replays a :class:`~delta_tpu.replay.trace.WorkloadTrace`
time-compressed (10x / 100x) against the LIVE scraper/SLO plane: every scan
event's measured planning latency feeds the real
``delta.scan.planning.duration_ms`` histogram under the table's hashed
fleet label, and the time-series scraper snapshots + evaluates the SLO
objectives at the compressed timestamps — a burn that would take an hour of
real traffic pre-fires in seconds, BEFORE the traffic arrives. The replay
deliberately writes into the live metric rings (that is the point); run it
against a staging process or follow with ``timeseries.reset()`` +
``slo.reset()`` when the rings must stay pristine.

The synthetic generators (:func:`zipf_hot_key_storm`, :func:`cdc_burst`,
:func:`contention_flood`) emit deterministic (seeded) traces in the SAME
serialized format `replay/trace` produces from the journal, so shadow runs,
capacity replays and torture all draw from one scenario library.
"""
from __future__ import annotations

import random
import time
from typing import Any, Dict, List, Optional

from delta_tpu.utils import telemetry

from delta_tpu.replay.trace import TraceEvent, WorkloadTrace

__all__ = ["SCENARIOS", "capacity_replay", "cdc_burst", "contention_flood",
           "zipf_hot_key_storm"]


# ---------------------------------------------------------------------------
# Capacity replay
# ---------------------------------------------------------------------------


def capacity_replay(trace: WorkloadTrace, speed: float = 10.0,
                    scrape_every: int = 8,
                    now_ms: Optional[int] = None) -> Dict[str, Any]:
    """Replay ``trace``'s scan latencies at ``speed``x against the live
    scraper/SLO plane. Event N lands at simulated time
    ``now + (ts_N - ts_0) / speed``; every ``scrape_every`` events the
    scraper snapshots and the SLO objectives evaluate at that simulated
    clock. Returns the fired objectives + alerts attributed to the trace's
    table."""
    from delta_tpu.obs import fleet, slo, timeseries

    speed = max(float(speed), 1e-6)
    label = fleet.table_label(trace.path) if trace.path else ""
    scans = [e for e in trace.events if e.kind == "scan"]
    start = int(now_ms if now_ms is not None else time.time() * 1000)
    scrapes = 0
    if scans:
        t0 = scans[0].ts
        # baseline snapshot BEFORE any observation: window queries diff the
        # latest sample against the oldest retained one, so observations
        # recorded before the first scrape would vanish into the baseline
        timeseries.scrape_once(now_ms=start - 1, evaluate_slo=False)
        scrapes += 1
        sim = start
        for i, ev in enumerate(scans):
            sim = start + int((ev.ts - t0) / speed)
            telemetry.observe("delta.scan.planning.duration_ms",
                              float(ev.planning_ms), table=label)
            if (i + 1) % max(1, int(scrape_every)) == 0:
                timeseries.scrape_once(now_ms=sim, evaluate_slo=True)
                scrapes += 1
        timeseries.scrape_once(now_ms=sim + 1, evaluate_slo=True)
        scrapes += 1
    alerts = [a for a in slo.active_alerts()
              if not label or a.get("table") in (label, None)]
    telemetry.bump_counter("replay.capacity.runs")
    return {
        "path": trace.path,
        "source": trace.source,
        "speed": speed,
        "events": len(scans),
        "scrapes": scrapes,
        "simulatedMs": (int((scans[-1].ts - scans[0].ts) / speed)
                        if scans else 0),
        "originalMs": (scans[-1].ts - scans[0].ts) if scans else 0,
        "alerts": alerts,
        "objectives": sorted({a["objective"] for a in alerts}),
    }


# ---------------------------------------------------------------------------
# Synthetic scenario library
# ---------------------------------------------------------------------------


def _zipf_index(rng: random.Random, n: int, skew: float = 1.2) -> int:
    """Cheap zipf-ish draw over [0, n): inverse-power transform of a
    uniform sample — no scipy, deterministic under the seed."""
    u = rng.random()
    return min(n - 1, int(n * (u ** skew) * u))


def zipf_hot_key_storm(path: str = "synthetic://zipf", scans: int = 120,
                       keys: int = 50, seed: int = 7,
                       interval_ms: int = 30_000,
                       hot_planning_ms: float = 900.0) -> WorkloadTrace:
    """A skewed point-lookup storm: zipf-distributed ``k = <key>`` scans
    where the hottest keys also carry pathological planning latency — the
    shape that burns the ``scanPlanningP99`` objective under load."""
    rng = random.Random(seed)
    events: List[TraceEvent] = []
    for i in range(scans):
        key = _zipf_index(rng, keys)
        hot = key < max(1, keys // 10)
        events.append(TraceEvent(
            ts=i * interval_ms, kind="scan", predicate=f"k = {key}",
            fingerprint="eq(k,?)",
            planning_ms=(hot_planning_ms * (0.8 + 0.4 * rng.random())
                         if hot else 5.0 + 10.0 * rng.random()),
            payload={"hotKey": hot},
        ))
    return WorkloadTrace(path=path, built_at_ms=0, events=events,
                         source="synthetic:zipfHotKeyStorm")


def cdc_burst(path: str = "synthetic://cdc", bursts: int = 4,
              writes_per_burst: int = 25, seed: int = 11,
              interval_ms: int = 60_000) -> WorkloadTrace:
    """Change-data-capture apply bursts: trains of MERGE-shaped dml +
    commit events with trailing verification scans — the workload the
    merge-on-read delta store (ROADMAP item 3) will be sized against."""
    rng = random.Random(seed)
    events: List[TraceEvent] = []
    ts = 0
    for b in range(bursts):
        ts = b * bursts * interval_ms
        for w in range(writes_per_burst):
            ts += int(interval_ms / writes_per_burst)
            events.append(TraceEvent(
                ts=ts, kind="dml",
                payload={"op": "MERGE", "rows": 1 + _zipf_index(rng, 500)}))
            events.append(TraceEvent(
                ts=ts + 1, kind="commit",
                payload={"outcome": "committed", "attempts": 1}))
        events.append(TraceEvent(
            ts=ts + 2, kind="scan", predicate=f"v >= {rng.randrange(1000)}",
            fingerprint="ge(v,?)",
            planning_ms=20.0 + 30.0 * rng.random()))
    return WorkloadTrace(path=path, built_at_ms=0, events=events,
                         source="synthetic:cdcBurst")


def contention_flood(path: str = "synthetic://contention", writers: int = 8,
                     rounds: int = 12, seed: int = 13,
                     interval_ms: int = 10_000) -> WorkloadTrace:
    """Concurrent-writer pile-up: every round, ``writers`` commits race and
    most retry or lose — the trace the commit-retry-rate SLO and the group
    commit coordinator are torture-tested against."""
    rng = random.Random(seed)
    events: List[TraceEvent] = []
    for r in range(rounds):
        base = r * interval_ms
        for w in range(writers):
            won = w == r % writers
            attempts = 1 if won else 1 + _zipf_index(rng, 4)
            events.append(TraceEvent(
                ts=base + w, kind="commit",
                payload={"outcome": ("committed" if won or attempts < 4
                                     else "conflict"),
                         "attempts": attempts, "writer": w}))
        events.append(TraceEvent(
            ts=base + writers, kind="scan", predicate=None,
            planning_ms=15.0 + 20.0 * rng.random()))
    return WorkloadTrace(path=path, built_at_ms=0, events=events,
                         source="synthetic:contentionFlood")


#: name → generator; torture resolves scenarios through this
SCENARIOS = {
    "zipfHotKeyStorm": zipf_hot_key_storm,
    "cdcBurst": cdc_burst,
    "contentionFlood": contention_flood,
}
