"""Single catalog of every observability metric name and public entry point.

The AST lint in ``tests/test_telemetry.py`` enforces that (a) every string
constant passed to ``set_gauge`` anywhere in ``delta_tpu/`` appears in
:data:`GAUGES`, (b) every counter bumped from ``delta_tpu/obs/`` (and the
maintenance/conflict counters wired for the doctor) appears in
:data:`COUNTERS`, (c) the INVERSE pass — every constant-string
``bump_counter`` / ``observe`` call site engine-wide resolves to
:data:`COUNTERS` ∪ :data:`ENGINE_COUNTERS` / :data:`HISTOGRAMS` — so no
metric can ship un-cataloged, and (d) each ``obs/`` module's ``__all__``
matches :data:`PUBLIC_API` — so dashboards and the doctor never chase
stringly-typed drift: a renamed gauge fails the suite, not a Grafana panel.

``table.health.*`` gauges are emitted by :func:`delta_tpu.obs.doctor.doctor`
(labeled by table path) and validated against this catalog at publish time.
"""
from __future__ import annotations

__all__ = ["GAUGES", "COUNTERS", "ENGINE_COUNTERS", "HISTOGRAMS",
           "PUBLIC_API", "DESCRIPTIONS", "health_gauge"]

#: Every labeled gauge the engine publishes.
GAUGES = frozenset({
    # -- doctor: table-health gauges (obs/doctor.py, label: path) --------
    "table.health.severity",
    "table.health.files.count",
    "table.health.files.bytes",
    "table.health.checkpoint.commitsSince",
    "table.health.checkpoint.tailBytes",
    "table.health.checkpoint.tailFiles",
    "table.health.smallFiles.count",
    "table.health.smallFiles.bytes",
    "table.health.smallFiles.estReduction",
    "table.health.dv.files",
    "table.health.dv.deletedRows",
    "table.health.dv.deletedPct",
    "table.health.dv.filesPastPurge",
    "table.health.stats.coveragePct",
    "table.health.stats.parsedPct",
    "table.health.partition.count",
    "table.health.partition.gini",
    "table.health.tombstones.count",
    "table.health.tombstones.bytes",
    "table.health.protocol.minReader",
    "table.health.protocol.minWriter",
    # -- doctor: distributed-execution supervision (obs/doctor
    #    ._dim_distributed, process-wide counters) ----------------------
    "table.health.distributed.itemsRetried",
    "table.health.distributed.itemsQuarantined",
    "table.health.distributed.itemsSpeculated",
    "table.health.distributed.speculationWins",
    "table.health.distributed.slicesRecovered",
    "table.health.distributed.degraded",
    # -- doctor: device residency pressure (obs/doctor._dim_device) ------
    "table.health.device.hbmBytes",
    "table.health.device.keyCacheBytes",
    "table.health.device.stateCacheBytes",
    "table.health.device.scratchBytes",
    "table.health.device.budgetBytes",
    "table.health.device.pressure",
    "table.health.device.worstDevice",
    "table.health.device.worstDeviceBytes",
    "table.health.device.worstDevicePressure",
    # -- device-memory ledger (obs/hbm_ledger, process-wide) -------------
    "device.hbm.keyCacheBytes",
    "device.hbm.stateCacheBytes",
    "device.hbm.scratchBytes",
    "device.hbm.columnCacheBytes",
    # -- router audit + calibration (obs/router_audit, obs/calibration) --
    "router.missRate",
    "router.calibration",        # label: constant
    # -- streaming consumer lag (streaming/source.py, label: path) -------
    "streaming.source.backlogFiles",
    "streaming.source.backlogBytes",
    "streaming.source.lastBatchVersionLag",
    # -- maintenance recency (commands/optimize.py, vacuum.py) -----------
    "table.maintenance.lastOptimizeVersion",
    "table.maintenance.lastVacuumTimestamp",
    # -- static analysis (analysis/__init__.publish_metrics, label: rule) -
    "analysis.findings",
    # -- autopilot maintenance scheduler (delta_tpu/autopilot, label: path)
    "autopilot.lastRunTimestamp",
    # -- fleet observability plane (obs/fleet, obs/timeseries, obs/slo) ---
    "fleet.tables",               # live registered DeltaLogs
    "obs.scrape.series",          # series held in the scrape rings
    "slo.burnRate",               # labels: objective, table, window
    "slo.alerts",                 # alerts currently firing
    # -- shadow optimizer (delta_tpu/replay, label: path) -----------------
    "shadow.topScore",            # best candidate score of the last run
    # -- resident key cache per-table residency (ops/key_cache, label: table)
    "keyCache.residentBytes",
    # -- scan column cache per-table residency (ops/column_cache, label: table)
    "columnCache.residentBytes",
})

#: Counters introduced by the obs layer and its doctor feeds.
COUNTERS = frozenset({
    "obs.incidents.written",
    "obs.server.requests",
    # -- distributed-trace spool (obs/trace_store) ------------------------
    "trace.spansSpooled",         # spans appended to the JSONL spool
    "trace.spansDropped",         # spans dropped by the byte cap / IO error
    "commit.conflicts",
    "maintenance.optimize.filesCompacted",
    "maintenance.optimize.filesWritten",
    "maintenance.vacuum.filesDeleted",
    "maintenance.vacuum.bytesReclaimed",
    # -- robustness layer (utils/retries, storage/faults, txn) -----------
    "storage.retry.attempts",     # one per backoff sleep, any store
    "storage.retry.exhausted",    # gave up: surfaced to the caller
    "faults.injected",            # deterministic fault injector fired
    "commit.reconciled",          # ambiguous commit resolved via txnId
    # -- predicate pushdown synthesis (obs/scan_report.record_rewrite_fired)
    "scan.rewrites.fired",        # synthesized rewrite excluded data in a scan
    # -- device MERGE router + resident key cache (commands/merge.py,
    #    ops/key_cache.py) — `auto_used_device` made observable on
    #    production tables via /metrics and flight-recorder incidents
    "merge.device.engaged",       # a device join produced this merge's pairs
    "merge.device.declined",      # link cost model chose the host
    "merge.device.fallback",      # device path raised; host join took over
    "merge.device.cacheHit",      # engaged from an HBM-resident key lane
    "merge.clause.insertOnly",    # MERGEs with no WHEN MATCHED clause
    "merge.clause.delete",        # MERGEs with a WHEN MATCHED THEN DELETE clause
    "merge.resident.pairsOnly",   # the resident probe's pairs were the join
    "merge.resident.pairsOnly.declined",  # engaged, then decoded after all
    "merge.resident.probe.overflow",  # candidate rows past the pair scratch
    "merge.dv.overlapped",        # vectors written beside the data file
    "merge.device.compiles",      # XLA compiles inside a MERGE's root span
    "merge.keyCache.builds",      # cold key-lane builds (inline or bg)
    "merge.keyCache.advances",    # incremental log-tail applications
    "merge.keyCache.invalidations",  # entries dropped by a rewrite epoch bump
    "merge.keyCache.flipSearches",  # flips mirrored in a live sorted view
    "merge.keyCache.flipResorts",  # flips that dropped the view instead
    "merge.keyCache.tailSorts",   # sorts of a slab's tail run alone
    "merge.keyCache.folds",       # whole sorts that emptied a full tail
    # -- router audit ledger + calibrator (obs/router_audit, obs/calibration)
    "router.audits",              # one per routed decision recorded
    "router.misses",              # hindsight: rejected route predicted faster
    "router.calibration.updates",  # EWMA samples folded into the state
    # -- workload journal + layout advisor (obs/journal, obs/advisor) -----
    "journal.entries",            # entries written to journal segments
    "journal.bytes.written",      # JSONL bytes appended
    "journal.segments.written",   # segment files opened
    "journal.segments.swept",     # segments deleted by the size/age sweep
    "journal.entriesDropped",     # buffer cap hit or unwritable directory
    "journal.literalSamples",     # reservoir-sampled concrete predicates
    "advisor.runs",               # advise() invocations
    "advisor.recommendations",    # recommendations emitted across runs
    # -- autopilot maintenance scheduler (delta_tpu/autopilot) ------------
    "autopilot.runs",             # run_once passes (daemon ticks + manual)
    "autopilot.actions.planned",  # actions surviving cooldown into a plan
    "autopilot.actions.executed",  # actions that ran to completion
    "autopilot.actions.skipped",  # cost cap / run budget aborts
    "autopilot.actions.deferred",  # not-quiet / backoff / busy deferrals
    "autopilot.actions.failed",   # genuine execution failures
    "autopilot.contentionAborts",  # maintenance commits that lost to
                                   # foreground writers and backed off
    # -- fleet observability plane (obs/fleet, obs/timeseries, obs/slo) ---
    "obs.server.clientAborts",    # responses cut short by a client hangup
    "obs.scrape.ticks",           # scraper passes over the registry
    "fleet.sweeps",               # fleet_doctor/fleet_advise sweeps run
    "slo.evaluations",            # SLO evaluation passes
    "slo.alerts.fired",           # alerts that crossed both burn windows
    "slo.alerts.cleared",         # alerts cleared by fast-window recovery
    # -- workload replay + shadow optimizer (delta_tpu/replay) ------------
    "replay.traces.built",        # WorkloadTraces reconstructed from journals
    "replay.scans.replayed",      # trace scans re-executed in replays
    "replay.literals.synthesized",  # predicates rebuilt from file stats
    "replay.capacity.runs",       # time-compressed SLO capacity replays
    "shadow.runs",                # shadow_run scorecards produced
    "shadow.candidates",          # candidate configurations scored
})

#: Every OTHER counter the engine bumps by constant name — the inverse lint
#: (tests/test_telemetry.py) fails on any ``bump_counter`` call site whose
#: name is in neither this set nor :data:`COUNTERS`. Dynamic families
#: (``logstore.{op}.calls``/``.bytes``) are f-strings and out of lint scope.
ENGINE_COUNTERS = frozenset({
    "checkpoint.parts",
    "checkpoint.actions",
    "checkpoint.written",
    "checkpoint.incremental.built",
    "checkpoint.incremental.fallback",
    "commit.total",
    "commit.retries",
    "convert.stats.fromFooter",
    "convert.stats.fromDecode",
    "footerCache.hits",
    "footerCache.misses",
    "footerCache.evictions",
    "log.update.coalesced",
    "log.update.installed",
    "log.update.unchanged",
    "parquet.files.written",
    "parquet.bytes.written",
    "parquet.rows.written",
    "write.stats.footer",         # files whose statistics the footer gave
    "write.stats.decoded",        # files walked again for their statistics
    "scan.files.read",
    "scan.bytes.read",
    "scan.bytes.skipped",
    "scan.bytes.deviceSkipped",
    "scan.bytes.deviceSurvivor",
    "scan.rowgroups.total",
    "scan.rowgroups.pruned",
    "scan.rowgroups.lateSkipped",
    "scan.rowgroups.deviceSkipped",
    "scan.device.engaged",
    "scan.device.declined",
    "scan.device.fallback",
    "scan.device.compiles",
    "scan.aggregate.device",
    "scan.aggregate.grouped",
    "scan.aggregate.grouped.tiled",
    "scan.aggregate.declined",
    "scan.prune.deviceFallback",
    "columnCache.hits",
    "columnCache.misses",
    "columnCache.keep.hits",      # a file's keep mask found resident
    "columnCache.keep.misses",    # built from the file's vector and uploaded
    "columnCache.packs",          # packs of small files' lanes built
    "scan.aggregate.packedLaunches",  # aggregate launches over a pack
    "columnCache.evictions",
    "columnCache.invalidations",
    "scan.rewrites.synthesized",
    "scan.rewrites.unknown",
    "stateCache.builds",
    "stateCache.plan.resident",
    "stateCache.plan.fallback.lowering",
    "stateCache.plan.fallback.noentry",
    "stateCache.plan.fallback.version",
    "stateCache.scan.resident",
    "stateCache.scan.fallback.lowering",
    "stateCache.scan.fallback.noentry",
    "stateCache.scan.fallback.version",
    # -- the host-device link and the compiler, counted where they are
    #    used (parallel/link.to_device / to_host, utils/jaxcache) ---------
    "link.h2d.bytes",
    "link.h2d.count",
    "link.d2h.bytes",
    "link.d2h.count",
    "link.d2h.waitUs",
    # -- the interpreter's collections (utils/telemetry._on_gc) ------------
    "host.gc.collections",
    "host.gc.pauseUs",
    "device.compiles",
    "device.compileUs",
    "device.cacheFetches",
    "stateExport.statsLanes.struct",
    "stateExport.statsLanes.json",
    "stateExport.statsLanes.mixed",
    "stateExport.statsLanes.us",
    "streaming.sink.batches",
    # -- distributed executor + sharded planning (parallel/executor,
    #    parallel/distributed, ops/state_cache sharded plan) --------------
    "dist.jobs",                  # sharded jobs launched (run_sharded calls)
    "dist.items",                 # work items executed across all jobs
    "dist.steals",                # items stolen from another worker's deque
    "dist.plan.sharded",          # plan batches served by the shard_map kernel
    "dist.optimize.groups",       # OPTIMIZE bin-pack groups rewritten by
                                  # sharded workers
    "dist.commit.fanin",          # distributed-job commits funneled through
                                  # the group-commit coordinator
    # -- distributed-execution supervision (parallel/executor item retry +
    #    quarantine, heartbeat speculation; parallel/leases slice recovery;
    #    the graceful-degradation ladder) -------------------------------
    "dist.items.retried",         # transient item attempts retried in place
    "dist.items.quarantined",     # poison items quarantined off a job
    "dist.items.speculated",      # stuck items speculatively re-dispatched
    "dist.speculation.wins",      # speculative attempts that won the race
    "dist.slice.recovered",       # orphaned host slices re-executed by the
                                  # coordinator after lease expiry
    "dist.lease.swept",           # expired _dist/ lease files swept
    "dist.degraded.pool",         # sharded jobs degraded to inline execution
    "dist.degraded.plan",         # shard_map plans degraded to the host pass
    "dist.degraded.lease",        # slices run uncovered after lease-write
                                  # failure
})

#: Every histogram observed by constant name (``telemetry.observe``).
HISTOGRAMS = frozenset({
    "commit.group.batchSize",
    "commit.queueWaitMs",
    "delta.checkpoint.duration_ms",
    "delta.commit.duration_ms",
    "delta.scan.planning.duration_ms",
    "delta.streaming.sink.batch_ms",
    "delta.streaming.source.batch_ms",
    "dist.item.duration_ms",
    "journal.flushKb",
    "router.predicted_ms",
    "router.actual_ms",
})

#: Public surface of each obs module, lint-matched against its ``__all__``.
PUBLIC_API = {
    "doctor": ("HealthDimension", "TableHealthReport", "doctor",
               "SEVERITY_RANK"),
    "scan_report": ("ScanReport", "last_scan_report", "clear_last_report",
                    "start_report", "current_report", "contribute",
                    "record_phase", "record_rewrite_fired", "finish_report"),
    "server": ("ObsServer", "start_server", "stop_server"),
    "flight_recorder": ("install", "uninstall", "record_incident",
                        "incident_files"),
    "metric_names": ("GAUGES", "COUNTERS", "ENGINE_COUNTERS", "HISTOGRAMS",
                     "PUBLIC_API", "DESCRIPTIONS", "health_gauge"),
    "router_audit": ("RouterAudit", "record_audit", "recent_audits",
                     "clear_audits", "audit_stats", "last_audit"),
    "calibration": ("enabled", "ingest", "state_path", "load_state",
                    "save_state", "apply_state", "current_state", "reset"),
    "hbm_ledger": ("Account", "adjust", "totals", "budget_bytes",
                   "device_totals", "worst_device",
                   "key_cache_allowance", "column_cache_allowance",
                   "over_budget", "maybe_relieve", "reset"),
    "journal": ("enabled", "journal_dir", "predicate_fingerprint",
                "record_scan", "record_commit", "record_dml",
                "record_router", "record_autopilot", "record_shadow",
                "record_dist", "attempt_state", "record_attempt", "flush",
                "read_entries", "sweep", "live_writer_spared", "reset"),
    "advisor": ("Recommendation", "AdvisorReport", "advise"),
    "actions": ("ActionSpec", "MaintenanceAction", "CATALOG", "CATALOG_REF",
                "RECOMMENDATION_ACTIONS", "COOLDOWN_PHASES", "spec",
                "remedy_name", "executable_kinds", "action_key",
                "attempts_in_cooldown"),
    "fleet": ("enabled", "register", "unregister", "live_tables",
              "table_label", "label_path", "fleet_doctor", "fleet_advise",
              "fleet_status", "FleetEntry", "FleetReport", "reset"),
    "timeseries": ("Scraper", "start_scraper", "stop_scraper", "scrape_once",
                   "scrape_count", "counter_window", "quantile_window",
                   "histogram_labels", "series_snapshot", "reset"),
    "slo": ("SloObjective", "SloAlert", "SloBreach", "objectives",
            "evaluate", "active_alerts", "priority_boost", "firing_count",
            "status", "reset"),
    "trace_store": ("install", "uninstall", "read_spools", "recent_traces",
                    "stitch_trace", "analyze_trace", "reset"),
}


#: One-line description per catalog entry, emitted as ``# HELP`` lines in
#: the Prometheus exposition (``telemetry.prometheus_text``) so scrapers
#: classify and document every series. The lint in ``tests/test_telemetry``
#: requires a non-empty description for EVERY catalog name — a new metric
#: cannot ship undocumented.
DESCRIPTIONS = {
    # gauges — doctor
    "table.health.severity": "Worst doctor dimension severity (0 ok, 1 warn, 2 critical).",
    "table.health.files.count": "Live data files in the current snapshot.",
    "table.health.files.bytes": "Live data bytes in the current snapshot.",
    "table.health.checkpoint.commitsSince": "Commits replayed after the last checkpoint on a cold build.",
    "table.health.checkpoint.tailBytes": "Log-tail bytes re-read per snapshot update.",
    "table.health.checkpoint.tailFiles": "Log-tail commit files after the last checkpoint.",
    "table.health.smallFiles.count": "Files below the OPTIMIZE compaction floor.",
    "table.health.smallFiles.bytes": "Bytes held in small files.",
    "table.health.smallFiles.estReduction": "Estimated file-count reduction OPTIMIZE would achieve.",
    "table.health.dv.files": "Files carrying deletion vectors.",
    "table.health.dv.deletedRows": "Rows soft-deleted via deletion vectors.",
    "table.health.dv.deletedPct": "Soft-deleted fraction of the table's physical rows.",
    "table.health.dv.filesPastPurge": "Files past the per-file PURGE threshold.",
    "table.health.stats.coveragePct": "Fraction of files carrying min/max stats.",
    "table.health.stats.parsedPct": "Fraction of files whose stats parse cleanly.",
    "table.health.partition.count": "Distinct partitions in the snapshot.",
    "table.health.partition.gini": "Byte-skew Gini coefficient across partitions.",
    "table.health.tombstones.count": "Removed files awaiting retention expiry.",
    "table.health.tombstones.bytes": "Bytes held by tombstoned files.",
    "table.health.protocol.minReader": "Table protocol minimum reader version.",
    "table.health.protocol.minWriter": "Table protocol minimum writer version.",
    "table.health.device.hbmBytes": "Device-resident bytes attributed while diagnosing this table.",
    "table.health.device.keyCacheBytes": "Key-cache slab bytes resident on device.",
    "table.health.device.stateCacheBytes": "State-cache lane bytes resident on device.",
    "table.health.device.scratchBytes": "Transient probe-scratch bytes resident on device.",
    "table.health.device.budgetBytes": "Configured soft HBM budget (0 = unlimited).",
    "table.health.device.pressure": "Resident bytes over the soft budget (fraction).",
    "table.health.device.worstDevice": "Index of the most-loaded device in the per-device HBM breakdown.",
    "table.health.device.worstDeviceBytes": "Resident bytes on the most-loaded device.",
    "table.health.device.worstDevicePressure": "Worst device's bytes over its fair share of the soft budget.",
    # gauges — device ledger / router / streaming / maintenance
    "device.hbm.keyCacheBytes": "Process-wide key-cache bytes resident on device.",
    "device.hbm.stateCacheBytes": "Process-wide state-cache bytes resident on device.",
    "device.hbm.scratchBytes": "Process-wide transient scratch bytes resident on device.",
    "device.hbm.columnCacheBytes": "Process-wide scan column-cache lane bytes resident on device.",
    "columnCache.residentBytes": "HBM-resident scan column-lane bytes per table.",
    "router.missRate": "Fraction of routed decisions where a rejected route predicted faster.",
    "router.calibration": "Installed calibrated value per link constant.",
    "streaming.source.backlogFiles": "Committed files not yet served to the streaming consumer.",
    "streaming.source.backlogBytes": "Committed bytes not yet served to the streaming consumer.",
    "streaming.source.lastBatchVersionLag": "Table versions between the last served batch and the head.",
    "table.maintenance.lastOptimizeVersion": "Table version written by the last OPTIMIZE.",
    "table.maintenance.lastVacuumTimestamp": "Wall-clock ms of the last VACUUM.",
    "analysis.findings": "Non-baselined static-analysis findings per rule (tools/analyze.py).",
    "fleet.tables": "DeltaLog handles registered in the process-wide fleet registry.",
    "obs.scrape.series": "Distinct series retained in the obs scraper's in-memory rings.",
    "slo.burnRate": "Observed-over-objective burn rate per objective/table/window.",
    "slo.alerts": "SLO alerts currently firing.",
    "shadow.topScore": "Best candidate score of the table's latest shadow run.",
    "keyCache.residentBytes": "HBM-resident key-cache slab bytes per table.",
    # counters — obs layer
    "obs.incidents.written": "Flight-recorder incident files written.",
    "obs.server.requests": "HTTP requests served by the obs endpoint.",
    "trace.spansSpooled": "Sampled spans appended to the distributed-trace JSONL spool.",
    "trace.spansDropped": "Sampled spans dropped by the spool byte cap or an IO error.",
    "commit.conflicts": "Commits aborted on a genuine logical conflict.",
    "maintenance.optimize.filesCompacted": "Files removed by OPTIMIZE compaction.",
    "maintenance.optimize.filesWritten": "Files written by OPTIMIZE compaction.",
    "maintenance.vacuum.filesDeleted": "Unreferenced files deleted by VACUUM.",
    "maintenance.vacuum.bytesReclaimed": "Bytes reclaimed by VACUUM.",
    "storage.retry.attempts": "Transient-failure retry sleeps across all stores.",
    "storage.retry.exhausted": "Retry policies that gave up and surfaced the error.",
    "faults.injected": "Deterministic fault-injector activations.",
    "commit.reconciled": "Ambiguous commit outcomes resolved via the txnId token.",
    "merge.device.engaged": "MERGEs whose join pairs came from a device join.",
    "merge.device.declined": "MERGEs where the cost model chose the host join.",
    "merge.device.fallback": "MERGEs (mode=auto) whose device path raised and fell back to the host join.",
    "merge.device.cacheHit": "Device MERGEs served from an HBM-resident key lane.",
    "merge.clause.insertOnly": "MERGE statements with no WHEN MATCHED clause (the de-duplicating insert; the join fetches no pair).",
    "merge.clause.delete": "MERGE statements with a WHEN MATCHED THEN DELETE clause.",
    "merge.dv.overlapped": "MERGE statements that made both deletion vectors and rows to write, and wrote the vectors on worker threads beside the data file (overlapped on the delta.dml.merge.deletionVectors span); a statement that made one of the two runs inline and does not count.",
    "merge.resident.pairsOnly": "Resident MERGEs that took the pairs-only route: the probe's pairs are the join, no decode of the target.",
    "merge.resident.pairsOnly.declined": "Pairs-only MERGEs that decoded the target after all (probe overflow, a slab that disagrees with the snapshot).",
    "merge.resident.probe.overflow": "Resident probes declined to the host join because the matched keys' candidate slab rows (dead versions, duplicate target keys) pass the pair kernel's scratch bound.",
    "merge.device.compiles": "XLA compiles that ran with a delta.dml.merge span open on the compiling thread.",
    "merge.keyCache.builds": "Cold resident key-lane builds.",
    "merge.keyCache.advances": "Incremental log-tail applications to a key lane.",
    "merge.keyCache.invalidations": "Key-cache entries dropped by a rewrite epoch bump.",
    "merge.keyCache.flipSearches": "Validity flips of rows of a key slab's live big sorted run that were mirrored in sorted space, the rows' sorted positions found by a search of the run (span delta.keyCache.locate). A flip of the tail run's rows alone, or on a dropped view, counts in neither.",
    "merge.keyCache.flipResorts": "Validity flips of more rows of a live big sorted run than are worth searching for: the flip stayed in row space and dropped both sorted runs, and the next probe sorted the whole slab (its delta.keyCache.sort span says tier=all, cause=flips).",
    "merge.keyCache.tailSorts": "Sorts of a key slab's tail run alone (delta.keyCache.sort with tier=tail): the rows appended after the big sorted run's, or flipped among them, sorted at the tail's fixed capacity while the big run stayed as it was.",
    "merge.keyCache.folds": "Sorts of a whole key slab because an append found no room in the tail run (delta.keyCache.sort with tier=all, cause=fold): the big run takes every row in and the tail is empty again.",
    "router.audits": "Routed decisions recorded in the audit ledger.",
    "router.misses": "Audits where a rejected route's prediction beat the actual.",
    "router.calibration.updates": "EWMA samples folded into the calibration state.",
    "journal.entries": "Workload-journal entries written to segments.",
    "journal.bytes.written": "JSONL bytes appended to journal segments.",
    "journal.segments.written": "Journal segment files opened.",
    "journal.segments.swept": "Journal segments deleted by the size/age sweep.",
    "journal.entriesDropped": "Journal entries dropped (buffer cap or unwritable dir).",
    "journal.literalSamples": "Concrete predicate SQLs persisted by the literal-sample reservoir.",
    "advisor.runs": "Layout-advisor invocations.",
    "advisor.recommendations": "Recommendations emitted by the advisor.",
    "autopilot.lastRunTimestamp": "Wall-clock ms of the last autopilot pass over the table.",
    "autopilot.runs": "Autopilot maintenance passes (daemon ticks + manual run_once).",
    "autopilot.actions.planned": "Maintenance actions planned past the cooldown filter.",
    "autopilot.actions.executed": "Maintenance actions executed to completion.",
    "autopilot.actions.skipped": "Maintenance actions aborted by a cost cap or run budget.",
    "autopilot.actions.deferred": "Maintenance actions deferred (window not quiet, backoff, or busy).",
    "autopilot.actions.failed": "Maintenance actions that failed outright.",
    "autopilot.contentionAborts": "Maintenance commits that lost to foreground writers and backed off.",
    "obs.server.clientAborts": "HTTP responses cut short by a client disconnect (BrokenPipe/ConnectionReset).",
    "obs.scrape.ticks": "Scraper passes snapshotting the metrics registry into rings.",
    "fleet.sweeps": "Fleet-wide doctor/advisor sweeps over the table registry.",
    "slo.evaluations": "SLO burn-rate evaluation passes.",
    "slo.alerts.fired": "SLO alerts fired (both burn windows crossed 1.0).",
    "slo.alerts.cleared": "SLO alerts cleared by fast-window recovery below the hysteresis ratio.",
    "replay.traces.built": "WorkloadTraces reconstructed from table journals.",
    "replay.scans.replayed": "Trace scan events re-executed through the real scan path.",
    "replay.literals.synthesized": "Scan predicates rehydrated via stats-guided literal synthesis.",
    "replay.capacity.runs": "Time-compressed capacity replays against the SLO plane.",
    "shadow.runs": "Shadow-optimizer what-if runs completed.",
    "shadow.candidates": "Candidate configurations scored across shadow runs.",
    # counters — engine
    "checkpoint.parts": "Checkpoint part files written.",
    "checkpoint.actions": "Actions serialized into checkpoints.",
    "checkpoint.written": "Checkpoints completed.",
    "checkpoint.incremental.built": "Checkpoints built incrementally from a cached base plus tail.",
    "checkpoint.incremental.fallback": "Incremental checkpoint builds that fell back to full reconstruction.",
    "commit.total": "Commits attempted through the transaction pipeline.",
    "commit.retries": "Extra commit attempts after lost races.",
    "convert.stats.fromFooter": "CONVERT stats derived from Parquet footers.",
    "convert.stats.fromDecode": "CONVERT stats derived via full decode fallback.",
    "footerCache.hits": "Parquet footer cache hits.",
    "footerCache.misses": "Parquet footer cache misses (footer parsed).",
    "footerCache.evictions": "Parquet footers evicted by the LRU bound.",
    "log.update.coalesced": "Log updates served by a concurrent racer's just-completed listing.",
    "log.update.installed": "Log updates that installed a newer snapshot.",
    "log.update.unchanged": "Log updates that found no new commits.",
    "parquet.files.written": "Parquet data files written.",
    "parquet.bytes.written": "Parquet bytes written.",
    "parquet.rows.written": "Rows written to Parquet files.",
    "write.stats.footer": "Data files whose AddFile statistics came from the footer their encoder had just made (source=footer on delta.write.stats): no second pass over the rows.",
    "write.stats.decoded": "Data files whose footer could not give the statistics (a nested column, bounds withheld for an oversized value, NaN or zero float bounds) and whose rows were walked for them (source=decode).",
    "scan.files.read": "Data files decoded by scans.",
    "scan.bytes.read": "Compressed bytes of files decoded by scans.",
    "scan.bytes.skipped": "Uncompressed bytes skipped by row-group pruning.",
    "scan.bytes.deviceSkipped": "Uncompressed bytes skipped by all-False device residual masks.",
    "scan.bytes.deviceSurvivor": "Survivor row-group bytes host-decoded on the device residual path.",
    "scan.rowgroups.total": "Row groups considered by the second pruning tier.",
    "scan.rowgroups.pruned": "Row groups skipped via footer stats.",
    "scan.rowgroups.lateSkipped": "Row groups skipped by late materialization.",
    "scan.rowgroups.deviceSkipped": "Row groups skipped by all-False device residual masks.",
    "scan.device.engaged": "Scans whose residual mask was computed on device.",
    "scan.device.declined": "Scans where the cost model kept the residual on host.",
    "scan.device.fallback": "Device residual attempts that fell back to the host path.",
    "scan.device.compiles": "XLA compiles that ran with a delta.scan span open on the compiling thread (a new literal or lane shape).",
    "scan.aggregate.device": "Aggregate SELECTs, ungrouped or grouped, answered by the fused filter-and-sum kernels over resident lanes.",
    "scan.aggregate.grouped": "Of scan.aggregate.device, the GROUP BY queries: answered by the grouped kernel, the files' partials merged by value on the host (span delta.scan.deviceAggregate.groups).",
    "scan.aggregate.grouped.tiled": "Of scan.aggregate.grouped, the queries whose launches ran the tile kernel (one pass over a file in on-chip memory, 32-bit integers only); the others ran the wide formulation, which carries an int64 product. The delta.columnCache.aggregate span's program says which; the lanes' extremes choose.",
    "scan.aggregate.declined": "Aggregate SELECTs, ungrouped or grouped, the device route declined (the delta.scan.deviceAggregate span's route says why: host:shape, type, predicate, budget, overflow, groups, off); the host scan answered.",
    "device.compiles": "XLA compiles in this process (persistent-cache fetches not counted).",
    "device.compileUs": "Microseconds spent in those XLA compiles.",
    "device.cacheFetches": "Executables fetched from the persistent compilation cache instead of compiled.",
    "link.h2d.bytes": "Bytes uploaded host to device by the caches and kernels (exact, from nbytes).",
    "link.h2d.count": "Host-to-device uploads (asynchronous: bytes and count only, no time).",
    "link.d2h.bytes": "Bytes fetched device to host (exact, from nbytes).",
    "link.d2h.count": "Blocking device-to-host fetches.",
    "link.d2h.waitUs": "Wall microseconds in blocking fetches: the wait for the kernel that makes the array, then the copy.",
    "host.gc.collections": "Collections of the interpreter's garbage collector, every generation (a gc.callbacks entry; a pause of a millisecond or more is also an event host.gc on the spans' clock).",
    "host.gc.pauseUs": "Wall microseconds inside those collections: every thread of the process stands still for them.",
    "scan.prune.deviceFallback": "Device file prunes that raised and fell back to the host evaluator.",
    "columnCache.hits": "Scan column-cache lane hits (file, column resident).",
    "columnCache.misses": "Scan column-cache lane misses (cold decode).",
    "columnCache.keep.hits": "Launches of a device aggregate over a file with a deletion vector that found the file's keep mask resident (built from the same vector by a query before).",
    "columnCache.keep.misses": "Keep masks built from a file's deletion vector and uploaded (delta.columnCache.keepMask with cached=false): once a vector, the first query after the commit that wrote it.",
    "columnCache.packs": "Packs built: small files' lanes laid end to end in one buffer a column (ops/column_cache.Pack), from at least 16 files of at most 65,536 rows that a query missed together (span delta.columnCache.pack with new=true).",
    "scan.aggregate.packedLaunches": "Launches of a device aggregate over a pack: one reads every file of the pack that planning kept, as runs of rows (the .launch stage's files and ranges).",
    "columnCache.evictions": "Scan column-cache lanes evicted by the LRU bound.",
    "columnCache.invalidations": "Scan column-cache lanes dropped by a rewrite epoch bump.",
    "scan.rewrites.synthesized": "Conjuncts lowered to stats bounds only via predicate synthesis.",
    "scan.rewrites.fired": "Synthesized rewrites that excluded files or row groups in a scan.",
    "scan.rewrites.unknown": "Conjuncts predicate synthesis still could not lower (kept residual).",
    "stateCache.builds": "Device state-cache lane builds.",
    "stateCache.plan.resident": "Scan plans served from resident lanes.",
    "stateCache.plan.fallback.lowering": "Scan plans that could not lower to ranges.",
    "stateCache.plan.fallback.noentry": "Scan plans with no resident entry.",
    "stateCache.plan.fallback.version": "Scan plans whose entry advanced past the snapshot.",
    "stateCache.scan.resident": "File prunes served from resident lanes.",
    "stateCache.scan.fallback.lowering": "File prunes that could not lower to ranges.",
    "stateCache.scan.fallback.noentry": "File prunes with no resident entry.",
    "stateCache.scan.fallback.version": "File prunes whose entry advanced past the snapshot.",
    "stateExport.statsLanes.struct": "Checkpoint rows decoded from typed struct stats.",
    "stateExport.statsLanes.json": "Checkpoint rows decoded via per-row JSON stats.",
    "stateExport.statsLanes.mixed": "Checkpoint segments mixing struct and JSON stats.",
    "stateExport.statsLanes.us": "Checkpoint stats decoded with microsecond timestamps.",
    "streaming.sink.batches": "Micro-batches written by the streaming sink.",
    # histograms
    "delta.scan.planning.duration_ms": "Scan-planning (file pruning) latency per table (ms).",
    "journal.flushKb": "JSONL KiB per journal flush batch, labeled per table.",
    "commit.group.batchSize": "Transactions written per group-commit batch.",
    "commit.queueWaitMs": "Time a grouped commit waited in the coordinator queue (ms).",
    "delta.checkpoint.duration_ms": "Checkpoint write latency (ms).",
    "delta.commit.duration_ms": "Commit pipeline latency (ms).",
    "delta.streaming.sink.batch_ms": "Streaming sink addBatch latency (ms).",
    "delta.streaming.source.batch_ms": "Streaming source getBatch latency (ms).",
    "router.predicted_ms": "Router-predicted cost of the chosen route (ms).",
    "router.actual_ms": "Measured cost of the chosen route (ms).",
    # distributed executor + sharded planning
    "dist.jobs": "Sharded work-item jobs launched by the distributed executor.",
    "dist.items": "Work items executed across all sharded jobs.",
    "dist.steals": "Work items stolen from another worker's deque (skew relief).",
    "dist.plan.sharded": "Scan-plan batches served by the shard_map pruning kernel.",
    "dist.optimize.groups": "OPTIMIZE bin-pack groups rewritten by sharded workers.",
    "dist.commit.fanin": "Distributed-job commits funneled through the group-commit coordinator.",
    "dist.items.retried": "Transient work-item attempts retried in place by the executor.",
    "dist.items.quarantined": "Poison work items quarantined off a sharded job.",
    "dist.items.speculated": "Stuck work items speculatively re-dispatched by the supervisor.",
    "dist.speculation.wins": "Speculative re-dispatches that beat the original attempt.",
    "dist.slice.recovered": "Orphaned host slices re-executed after lease expiry.",
    "dist.lease.swept": "Expired distributed-lease files swept from _delta_log/_dist.",
    "dist.degraded.pool": "Sharded jobs that degraded to inline execution after pool failure.",
    "dist.degraded.plan": "shard_map scan plans that degraded to the host fine pass.",
    "dist.degraded.lease": "Distributed slices run uncovered after a lease-write failure.",
    "dist.item.duration_ms": "Per-work-item wall clock inside the distributed executor (ms).",
    # doctor distributed-supervision dimension (process-wide)
    "table.health.distributed.itemsRetried": "Transient item retries seen by this process's sharded jobs.",
    "table.health.distributed.itemsQuarantined": "Poison items quarantined by this process's sharded jobs.",
    "table.health.distributed.itemsSpeculated": "Stuck items speculatively re-dispatched in this process.",
    "table.health.distributed.speculationWins": "Speculative re-dispatches that won in this process.",
    "table.health.distributed.slicesRecovered": "Orphaned distributed slices recovered by this process.",
    "table.health.distributed.degraded": "Degradation-ladder rungs taken (pool+plan+lease) in this process.",
}


def health_gauge(dimension: str, metric: str) -> str:
    """The catalog-checked gauge name for a doctor metric — raises on a name
    that is not registered, so a new metric cannot ship un-cataloged."""
    name = f"table.health.{dimension}.{metric}"
    if name not in GAUGES:
        raise ValueError(f"gauge {name!r} is not registered in "
                         "delta_tpu/obs/metric_names.py")
    return name
