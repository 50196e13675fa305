"""Configuration system.

Three tiers, mirroring the reference (SURVEY §5 "Config / flag system"):

1. **Session confs** (:class:`SqlConf`) ≈ ``sources/DeltaSQLConf.scala`` —
   process-wide engine knobs under ``delta.tpu.*``.
2. **Table properties** (:class:`DeltaConfigs`) ≈ ``DeltaConfig.scala:114-433``
   — typed, validated ``delta.*`` keys persisted in ``Metadata.configuration``,
   with session-level defaults via ``delta.tpu.properties.defaults.*``.
3. Per-operation reader/writer options (≈ ``DeltaOptions.scala``) are keyword
   arguments on the command constructors (e.g. ``merge_schema`` /
   ``replace_where`` on ``delta_tpu.commands.write.WriteIntoDelta``).
"""
from __future__ import annotations

import re
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Generic, Optional, TypeVar

from delta_tpu.utils.errors import DeltaIllegalArgumentError

T = TypeVar("T")

__all__ = ["SqlConf", "conf", "DeltaConfig", "DeltaConfigs", "parse_interval_ms"]


# ---------------------------------------------------------------------------
# Session conf
# ---------------------------------------------------------------------------

class SqlConf:
    """Process-wide conf with defaults; thread-safe; supports ``with
    conf.set_temporarily(...)`` for tests (≈ SQLConf + withSQLConf)."""

    _DEFAULTS: Dict[str, Any] = {
        # ≈ DELTA_MAX_RETRY_COMMIT_ATTEMPTS (DeltaSQLConf.scala:182)
        "delta.tpu.maxCommitAttempts": 10_000_000,
        # Group commit (txn/group_commit): concurrent commit() calls on one
        # DeltaLog enqueue; a leader drains the queue, reads the log tail
        # ONCE, conflict-checks the batch (against the tail AND each other)
        # and writes members as consecutive versions — amortizing the
        # per-writer list/read-tail/CAS cycle under contention. Default OFF:
        # with it off the commit path is byte-identical to the ungrouped
        # engine (regression-tested).
        "delta.tpu.commit.group.enabled": False,
        # Max transactions one leader writes per batch drain.
        "delta.tpu.commit.group.maxBatch": 32,
        # How long a new leader lingers for the queue to fill before
        # draining (the classic group-commit accumulation window).
        "delta.tpu.commit.group.maxWaitMs": 2,
        # Asynchronous interval checkpointing (log/checkpointer): the
        # every-Nth-commit checkpoint (`delta.checkpointInterval`) is
        # enqueued to a background daemon instead of stalling the
        # committing writer on an O(table) synchronous write. Default OFF.
        "delta.tpu.checkpoint.async": False,
        # Incremental checkpoint builds (log/checkpointer): checkpoint N is
        # built from the cached reconciled columns of checkpoint M plus a
        # decode of ONLY the tail commits M+1..N, instead of re-decoding
        # the whole base checkpoint. Falls back to full reconstruction (and
        # re-seeds the cache) on any gap/overflow. Default OFF.
        "delta.tpu.checkpoint.incremental": False,
        # Cached incremental bases kept across tables (LRU).
        "delta.tpu.checkpoint.incremental.maxTables": 8,
        # ≈ DELTA_CHECKPOINT_PART_SIZE — actions per checkpoint part
        "delta.tpu.checkpointPartSize": 1_000_000,
        # Run the MERGE equi-join on device (ops/join_kernel) when the
        # condition is 1-2 integer equi-keys with no residual conjuncts
        # (composite keys pack into one int64 lane).
        "delta.tpu.merge.devicePath.enabled": True,
        # Executor routing for the MERGE join: "auto" prices the device leg
        # against the measured link profile (parallel/link.py) — separately
        # for the resident-cache-hit and the cold slab-upload cases — and
        # declines the device when the host hash join is cheaper; "force"
        # always engages the device; "off" never does.
        "delta.tpu.merge.devicePath.mode": "auto",
        # On a multichip mesh, prefer the all-gather sharded sort-merge
        # kernel (ops/join_kernel) over the single-device resident-slab
        # pipeline. Off by default: the resident pipeline wins on link
        # economics until the multichip executor (ROADMAP item 2) is real.
        "delta.tpu.merge.devicePath.preferMesh": False,
        # Cross-MERGE resident key cache (ops/key_cache): keep packed target
        # join keys HBM-resident keyed by snapshot version + rewrite epoch,
        # so repeated MERGEs against a hot table skip both the key decode
        # and the upload. False disables caching AND the background build
        # (the fused device path then rebuilds a transient slab per merge).
        "delta.tpu.merge.keyCache.enabled": True,
        # Minimum estimated table rows before the post-commit background
        # key-lane build kicks in (small tables never win on device).
        "delta.tpu.merge.residentKeys.minRows": 1 << 20,
        # Resident key-cache budgets (ops/key_cache.KeyCache._evict).
        "delta.tpu.keyCache.maxBytes": 1 << 30,
        "delta.tpu.keyCache.maxEntries": 8,
        # Device residual-filter path (ops/column_cache): "auto" prices
        # device vs host per scan through parallel/link, "force" always
        # engages (the benchmark's `force` pins), "off" disables the path and
        # the cache.
        "delta.tpu.read.deviceResidual.mode": "auto",
        # Scan column-cache budgets (ops/column_cache.ColumnCache._evict);
        # entries are per-(file, column) lanes, hence the larger count.
        "delta.tpu.columnCache.maxBytes": 1 << 30,
        "delta.tpu.columnCache.maxEntries": 4096,
        # Process-wide soft budget over EVERY device-resident byte the
        # engine holds (key-cache slabs + state-cache lanes + join scratch
        # + scan column lanes, obs/hbm_ledger). When set, each LRU cache
        # prices itself against budget minus everyone else, so growth
        # anywhere becomes eviction pressure instead of OOM. None =
        # unlimited.
        "delta.tpu.device.hbmBudgetBytes": None,
        # Router audit ledger (obs/router_audit): last N routed decisions
        # kept for the HTTP /router route.
        "delta.tpu.router.auditKeep": 256,
        # Self-calibrating cost model (obs/calibration): EWMA re-fit of the
        # parallel/link.py throughput constants from the audit ledger's
        # measured samples. Off by default — routing then runs on the
        # shipped constants.
        "delta.tpu.router.calibration.enabled": False,
        # Where calibration state persists. None = next to the log of the
        # table that produced the samples (<log dir>/.router_calibration
        # .json, local paths only); set for object-store tables or to share
        # one state file across tables on the same hardware.
        "delta.tpu.router.calibration.statePath": None,
        # EWMA blend weight of each new sample (0.01..1.0].
        "delta.tpu.router.calibration.alpha": 0.2,
        # Samples a constant needs before its calibrated value overrides
        # the shipped default (guards against one noisy first merge).
        "delta.tpu.router.calibration.minSamples": 3,
        # Hot-path (scan planner) ingests throttle the state-file write to
        # at most one per this interval; merges always flush.
        "delta.tpu.router.calibration.flushIntervalMs": 2000,
        # Link profile overrides (MB/s). Unset = probe once per process.
        "delta.tpu.link.uploadMBps": None,
        "delta.tpu.link.downloadMBps": None,
        # Non-equi MERGE pair-streaming tile budget: peak candidate pairs
        # materialized per tile of the target x source grid.
        "delta.tpu.merge.nonEquiPairBudget": 8_000_000,
        # Device-resident state cache (ops/state_cache): keep decoded
        # snapshot stat lanes HBM-resident across queries.
        "delta.tpu.stateCache.enabled": True,
        "delta.tpu.stateCache.maxBytes": 2 << 30,
        "delta.tpu.stateCache.maxEntries": 16,
        # Serve file-tier prunes from resident lanes (ops/pruning).
        "delta.tpu.stateCache.serveScans": True,
        # Plan scans on device from resident lanes; "auto" prices the
        # device leg against the link profile, "force"/"off" override.
        "delta.tpu.stateCache.devicePlan.enabled": True,
        "delta.tpu.stateCache.devicePlan.mode": "auto",
        # ≈ DELTA_VACUUM_RETENTION_CHECK_ENABLED
        "delta.tpu.retentionDurationCheck.enabled": True,
        # ≈ DELTA_STATE_CORRUPTION_IS_FATAL
        "delta.tpu.state.corruptionIsFatal": True,
        # ≈ DELTA_ASYNC_UPDATE_STALENESS_TIME_LIMIT (DeltaSQLConf.scala:262)
        "delta.tpu.stalenessLimitMs": 0,
        # Preferred spelling of the staleness bound (log/deltalog.update
        # stale_ok path); None falls back to delta.tpu.stalenessLimitMs.
        "delta.tpu.snapshot.stalenessLimitMs": None,
        # ≈ DELTA_SCHEMA_AUTO_MIGRATE (merge schema on write by default off)
        "delta.tpu.schema.autoMerge.enabled": False,
        # ≈ DELTA_HISTORY_METRICS_ENABLED
        "delta.tpu.history.metricsEnabled": True,
        # Usage-event/span recording (utils/telemetry). False = no events or
        # spans are buffered (zero-overhead blackout); counters stay live.
        "delta.tpu.telemetry.enabled": True,
        # Telemetry ring-buffer capacity (events + spans).
        "delta.tpu.telemetry.bufferSize": 4096,
        # Distributed-trace plane (utils/telemetry + obs/trace_store).
        # Head-sampling probability for NEW root traces; errors and
        # SLO-burn windows force-sample regardless.
        "delta.tpu.trace.sampleRate": 1.0,
        # Directory receiving per-process JSONL span spools (and the
        # collector's stitch source for /traces). None = no spooling —
        # spans stay in the in-process ring only.
        "delta.tpu.trace.dir": None,
        # Per-process spool byte cap; past it spans drop (counted in
        # trace.spansDropped) instead of filling the disk.
        "delta.tpu.trace.maxBytes": 32 * 1024 * 1024,
        # Operator HTTP endpoint (obs/server): serve /metrics, /healthz,
        # /events, /trace, /doctor on this port. None = no server; 0 = an
        # ephemeral port (tests). Opt-in only — nothing listens by default.
        "delta.tpu.obs.port": None,
        # Failure flight recorder (obs/flight_recorder): directory receiving
        # incident JSON files when an instrumented operation raises. None =
        # recorder off (the default; span-error hooks cost nothing then).
        "delta.tpu.obs.incidentDir": None,
        # Max incident files kept in incidentDir (oldest deleted first).
        "delta.tpu.obs.incidentKeep": 20,
        # Last N ring-buffer events snapshotted into each incident file.
        "delta.tpu.obs.incidentEvents": 64,
        # Persistent per-table workload journal (obs/journal): one JSONL
        # entry per scan/commit/DML/router decision, batched into segment
        # files under <table>/_delta_log/_journal/ for the layout advisor
        # (obs/advisor). Inert under a telemetry blackout either way;
        # object-store (scheme://) tables never journal.
        "delta.tpu.journal.enabled": True,
        # Active segment rotates past this many bytes.
        "delta.tpu.journal.segmentBytes": 1 << 20,
        # Total on-disk bound per table; oldest segments swept first.
        "delta.tpu.journal.maxBytes": 16 << 20,
        # Segments older than this are swept regardless of the size bound.
        "delta.tpu.journal.retentionMs": 7 * 86_400_000,
        # Buffered entries flush to disk at this count or age, whichever
        # comes first — the IO runs on the journal writer thread, never on
        # the operation's thread.
        "delta.tpu.journal.flushEntries": 64,
        "delta.tpu.journal.flushIntervalMs": 2000,
        # Literal-sample reservoir: the first K scans per predicate
        # fingerprint persist their concrete SQL (deterministic first-K,
        # replay-stable); past the bound the report predicate is redacted,
        # so K bounds how many concrete literals ever hit disk. 0 redacts
        # everything (fingerprints only — workload replay then falls back
        # to stats-guided literal synthesis).
        "delta.tpu.journal.literalSamples": 3,
        # -- workload replay + shadow optimizer (delta_tpu/replay) -----------
        # Scans replayed per trace (newest kept) — bounds a shadow run's
        # cost on a long-journaled table.
        "delta.tpu.replay.maxScans": 256,
        # Sandbox root for shadow clones; None = a fresh tempfile.mkdtemp
        # per run. Always removed afterwards, BaseException included.
        "delta.tpu.replay.sandboxDir": None,
        # Score weight for scans whose literal was synthesized from file
        # stats instead of sampled from the journal — measured-on-real-
        # literals evidence counts full, synthesized counts this fraction.
        "delta.tpu.replay.literalDiscount": 0.5,
        # Candidate clones are prepared concurrently on the
        # delta-replay-prep pool (replays themselves run sequentially: the
        # per-scan flight recorder is process-global).
        "delta.tpu.replay.prepWorkers": 2,
        # -- fleet observability plane (obs/fleet, obs/timeseries, obs/slo) --
        # Process-wide table registry: every DeltaLog auto-registers on
        # construction (weakref'd) so fleet_doctor()/fleet_advise() can
        # sweep all live tables. Inert under a telemetry blackout either
        # way; this switch turns just the registry off.
        "delta.tpu.obs.fleet.enabled": True,
        # Metrics scraper daemon (obs/timeseries): snapshot the telemetry
        # registry every intervalMs into bounded in-memory rings of
        # `keep` samples per series (counter cumulatives, gauge values,
        # histogram bucket counts). 10s x 400 ~= 67min of history —
        # deliberately PAST the 1h SLO slow window, so the slow-window
        # baseline is a real sample, not the counts-from-zero fallback.
        "delta.tpu.obs.scrape.intervalMs": 10_000,
        "delta.tpu.obs.scrape.keep": 400,
        # Hard cap on distinct series tracked across the rings; past it
        # the series whose value went stale longest ago are evicted
        # (bounds memory under table churn — dead tables' labeled series
        # stop changing and age out first).
        "delta.tpu.obs.scrape.maxSeries": 8192,
        # SLO burn-rate monitors (obs/slo) over the scraped series,
        # evaluated after each scrape: an objective fires only when BOTH
        # the fast and the slow window burn past 1.0 (multi-window rule),
        # and clears with hysteresis once the fast window drops below
        # clearRatio. Firing alerts write a flight-recorder incident
        # (when incidentDir is set) and boost the autopilot's priority
        # for the offending table's actions by priorityBoost.
        "delta.tpu.obs.slo.enabled": True,
        "delta.tpu.obs.slo.fastWindowMs": 300_000,
        "delta.tpu.obs.slo.slowWindowMs": 3_600_000,
        "delta.tpu.obs.slo.clearRatio": 0.8,
        # Observation floor per window before an alert may fire: right
        # after scraper start both windows see the same counts-from-zero
        # delta, so one cold-start outlier must not page.
        "delta.tpu.obs.slo.minObservations": 10,
        "delta.tpu.obs.slo.priorityBoost": 25.0,
        # Default objectives (obs/slo.objectives): per-table latency
        # quantiles and process-wide failure-rate ceilings.
        "delta.tpu.obs.slo.commitLatencyP99Ms": 2_000.0,
        "delta.tpu.obs.slo.scanPlanningP99Ms": 500.0,
        "delta.tpu.obs.slo.commitConflictRate": 0.05,
        "delta.tpu.obs.slo.retryExhaustionRate": 0.02,
        "delta.tpu.obs.slo.journalDropRate": 0.01,
        # Streaming backlog gauges walk at most this many pending files past
        # each batch end (a deeply lagging consumer must not re-read its
        # whole remaining log tail per micro-batch; the published count is a
        # floor when the cap is hit). <= 0 publishes only the version lag.
        "delta.tpu.obs.streamingBacklogMaxFiles": 1024,
        # Materialize parsed per-file stats as typed Parquet struct columns
        # (`add.stats_parsed` / `add.partitionValues_parsed`) in checkpoints
        # when the table does not set delta.checkpoint.writeStatsAsStruct
        # itself. Default ON: the cold state-cache build then reads typed
        # columns instead of re-parsing per-file stats JSON (the dominant
        # cost of a 1M-file cold build).
        "delta.tpu.checkpoint.writeStatsAsStruct": True,
        # ≈ DELTA_WRITE_CHECKSUM_ENABLED
        "delta.tpu.writeChecksum.enabled": True,
        # Target max rows per written data file (write-path sharding unit).
        "delta.tpu.write.targetFileRows": 4_000_000,
        # BYTE_STREAM_SPLIT encoding for float columns: much faster decode,
        # equal size. Disable for parquet-mr < 1.12 readers (Spark <= 3.1).
        "delta.tpu.write.byteStreamSplit": True,
        # "auto" = snappy only on string/float columns, high-entropy ints
        # uncompressed (snappy on random int64 is 14x slower to decode for
        # ~10% size); or a codec name applied to all columns.
        "delta.tpu.write.compression": "auto",
        # Predicate pushdown synthesis (expr/synthesis): arithmetic /
        # string / temporal predicates the base skipping rules can't lower
        # (`price * qty > 1000`, `substr(id,1,4) = 'us-w'`, `year(d) =
        # 2026`) rewrite into sound can-match predicates over the same
        # min/max stats lanes, at BOTH pruning tiers (file + row group).
        # False disables the synthesis fallback: such shapes keep every
        # file/row group and run as residual filters only. The NOT
        # comparison pushdown (`Not(Lt)` ≡ `Ge`, type-gated) is a
        # base-rule fix and stays on either way.
        "delta.tpu.read.predicateSynthesis": True,
        # Second pruning tier inside the Parquet decode (exec/rowgroups):
        # footer row-group stats skip non-matching row groups, and predicate
        # columns decode first so remaining columns decode only for row
        # groups with possible matches (late materialization). False = every
        # surviving file decodes in full (the pre-tier behavior).
        "delta.tpu.read.rowGroupSkipping": True,
        # Bounded LRU of parsed Parquet footers keyed by path and validated
        # by (size, mtime): hot-table queries stop re-parsing footers per
        # open. 0 disables caching (footers parse on every open).
        "delta.tpu.read.footerCacheEntries": 1024,
        # Max rows per row group written by the engine (the skipping granule
        # of the read tier above). Arrow's 1Mi default would leave most
        # files as a single group with nothing to skip. <= 0 = Arrow default.
        "delta.tpu.write.rowGroupRows": 131_072,
        # Below this many candidate files, stats skipping runs on the host
        # (one device round-trip costs more than the whole numpy pass).
        "delta.tpu.device.pruning.minFiles": 4096,
        # Deterministic fault injection (storage/faults.py): a FaultPlan
        # object or a spec string like "seed=42,rate=0.05,kinds=transient".
        # None (the default) installs NO wrapper — zero overhead, asserted
        # by tests/test_faults.py.
        "delta.tpu.faults.plan": None,
        # Transient-retry layer over every table's LogStore (storage/
        # retrying.py): idempotent ops (reads, listings, overwrite-PUTs)
        # retry under utils/retries.RetryPolicy; the commit create-if-
        # absent is NEVER retried (ambiguity is reconciled in the txn
        # layer via commitInfo.txnId instead).
        "delta.tpu.storage.retry.enabled": True,
        "delta.tpu.storage.retry.maxAttempts": 5,
        "delta.tpu.storage.retry.baseDelayMs": 20,
        "delta.tpu.storage.retry.maxDelayMs": 1000,
        # Total wall-clock bound across attempts+sleeps of one op: a
        # flapping store fails in bounded time.
        "delta.tpu.storage.retry.deadlineMs": 15_000,
        # Metadata cleanup also sweeps aged .{name}.{uuid}.tmp staging
        # orphans (crashed writers) from _delta_log; younger files may be
        # in-flight writes and are kept.
        "delta.tpu.cleanup.tmpOrphanTtlMs": 3_600_000,
        # Named-table catalog (catalog/catalog.py): persistence path (None
        # = in-memory only) and how long an in-flight foreign-host CREATE
        # claim stays live before the name is forfeited.
        "delta.tpu.catalog.path": None,
        "delta.tpu.catalog.claimTimeoutMs": 600_000,
        # Multi-host barrier/gather timeout (parallel/distributed).
        "delta.tpu.distributed.timeoutMs": 600_000,
        # Sharded work-item executor (parallel/executor): worker count
        # (None = min(8, cpu count)) and deque work stealing for the
        # zipf hot-shard case.
        "delta.tpu.distributed.workers": None,
        "delta.tpu.distributed.workStealing.enabled": True,
        # shard_map scan planning (ops/state_cache sharded lanes): "auto"
        # prices sharded-vs-single with the per-shard link constants,
        # "force"/"off" pin the choice.
        "delta.tpu.distributed.plan.enabled": True,
        "delta.tpu.distributed.plan.mode": "auto",
        # Distributed OPTIMIZE: rewrite bin-pack groups on executor
        # workers (None = delta.tpu.distributed.workers).
        "delta.tpu.distributed.optimize.workers": None,
        # Distributed MERGE: probe candidate files for touched ones on
        # executor workers before the join (Spark's findTouchedFiles job);
        # minFiles gates the fan-out below which inline always wins.
        "delta.tpu.distributed.merge.probe.enabled": True,
        "delta.tpu.distributed.merge.probe.minFiles": 8,
        # Funnel distributed-job commits through the group-commit
        # coordinator (txn/group_commit) as the single-writer fan-in.
        "delta.tpu.distributed.singleWriterFanIn": True,
        # Per-item transient retry inside the sharded executor
        # (parallel/executor): bounded attempts + a total per-item
        # deadline via the shared utils/retries.RetryPolicy. Only
        # Exceptions classified transient retry; permanent failures
        # quarantine or abort per the job's on_failure policy.
        "delta.tpu.distributed.retry.maxAttempts": 3,
        "delta.tpu.distributed.retry.baseDelayMs": 10,
        "delta.tpu.distributed.retry.maxDelayMs": 200,
        "delta.tpu.distributed.retry.deadlineMs": 10_000,
        # Stuck-item supervision: the delta-dist-supervisor thread marks
        # items whose heartbeat age exceeds max(itemTimeoutMs, measured
        # ms/byte x LPT byte estimate x slackFactor) — the floor is a
        # conf, the effective timeout is priced per item — and
        # speculatively re-dispatches them to an idle worker,
        # first-completion-wins. itemTimeoutMs <= 0 disables supervision.
        "delta.tpu.distributed.itemTimeoutMs": 120_000,
        "delta.tpu.distributed.speculation.enabled": True,
        "delta.tpu.distributed.speculation.slackFactor": 4.0,
        "delta.tpu.distributed.supervisor.intervalMs": 25,
        # Multihost orphaned-slice recovery (parallel/leases): hosts in a
        # distributed OPTIMIZE write heartbeat lease files under
        # _delta_log/_dist/; after fan-in the coordinator re-executes
        # slices whose lease expired (ttlMs past the last heartbeat)
        # without being cleared. Leases are local-file IO like the
        # journal; object-store tables skip them.
        "delta.tpu.distributed.lease.enabled": True,
        "delta.tpu.distributed.lease.ttlMs": 60_000,
        # How long the coordinator lingers after its own commit waiting
        # for peer leases to APPEAR before concluding there are none — a
        # peer that dies pre-lease lost no committed data, so the wait is
        # deliberately short; once a lease is seen, it is tracked to
        # clear/expiry regardless of this window.
        "delta.tpu.distributed.lease.settleMs": 250,
        # DML writes per-file deletion vectors instead of rewriting files
        # when the table enables them (commands/dml_common).
        "delta.tpu.deletionVectors.enabled": True,
        # Network object stores (storage/logstore): the HTTP endpoint for
        # s3/gs schemes (required — no silent local fallback) and the
        # conditional-PUT dialect (None = auto by scheme).
        "delta.tpu.storage.objectStore.endpoint": None,
        "delta.tpu.storage.objectStore.dialect": None,
        # Autopilot maintenance scheduler (delta_tpu/autopilot): closes the
        # observe→decide→act→audit loop over the doctor's remedies and the
        # advisor's recommendations. Strictly opt-in: the daemon only runs
        # when enabled=true AND start() is called, and even then dryRun
        # (default ON) journals the plan without executing anything.
        "delta.tpu.autopilot.enabled": False,
        "delta.tpu.autopilot.dryRun": True,
        # Daemon tick interval between maintenance passes over the
        # registered tables.
        "delta.tpu.autopilot.intervalMs": 60_000,
        # Per-run cost caps: total bytes an OPTIMIZE/ZORDER/PURGE may
        # select for rewrite (over-budget jobs abort pre-IO with a
        # journaled SKIPPED outcome), wall-clock budget across a run's
        # actions, and how many actions one run may execute.
        "delta.tpu.autopilot.maxBytesPerRun": 2 << 30,
        "delta.tpu.autopilot.budgetMs": 300_000,
        "delta.tpu.autopilot.maxActionsPerRun": 4,
        # Per-action cooldown: an ATTEMPTED action (started / executed /
        # failed / interrupted) is not re-planned for this long — also the
        # crash-loop guard, since "started" ledger entries are flushed to
        # disk before execution.
        "delta.tpu.autopilot.cooldownMs": 6 * 3_600_000,
        # After a maintenance commit loses to a foreground writer, the
        # whole table backs off for this long.
        "delta.tpu.autopilot.contentionBackoffMs": 300_000,
        # Quiet-window pick: execute only when the journal shows at most
        # quietMaxCommits foreground commits inside the last quietWindowMs
        # (the same 60s bucketing the advisor's contention analysis uses).
        "delta.tpu.autopilot.quietWindowMs": 60_000,
        "delta.tpu.autopilot.quietMaxCommits": 0,
        # Maintenance commits lose gracefully: attempts are capped at this
        # (txn.transaction.commit_attempts_cap) instead of retry-storming
        # through delta.tpu.maxCommitAttempts against foreground writers.
        "delta.tpu.autopilot.maxCommitAttempts": 3,
        # Shadow-validation guardrail: when on, rewrite-class actions
        # (OPTIMIZE/ZORDER/PURGE) whose selection exceeds
        # requireShadowMinBytes only execute once a journaled shadow run
        # CONFIRMED them — refuted candidates are suppressed with the
        # measured deltas cited, untested ones deferred until a shadow run
        # exists. 0 gates every rewrite; unknown sizes are treated as over
        # the threshold (fail closed).
        "delta.tpu.autopilot.requireShadow": False,
        "delta.tpu.autopilot.requireShadowMinBytes": 0,
        # After an executed ZORDER, audit the realized effect by replaying
        # the shadow run's trace against the live table (replay/shadow.
        # realized_audit) instead of reporting a pending longitudinal
        # verdict.
        "delta.tpu.autopilot.shadowAudit": True,
    }

    def __init__(self):
        self._values: Dict[str, Any] = {}
        self._lock = threading.RLock()
        self._generation = 0

    def generation(self) -> int:
        """Monotonic mutation counter, bumped on every set/unset (including
        ``set_temporarily`` enter/exit). Hot paths cache conf-derived values
        keyed on this instead of paying a locked lookup per call."""
        return self._generation

    def get(self, key: str, default: Any = None) -> Any:
        with self._lock:
            if key in self._values:
                return self._values[key]
        if key in self._DEFAULTS:
            return self._DEFAULTS[key]
        return default

    def get_bool(self, key: str, default: bool = False) -> bool:
        """Boolean conf with string coercion: "false"/"0"/"off" (any case)
        are False — a raw ``bool(conf.get(...))`` treats "false" as truthy."""
        v = self.get(key, default)
        if isinstance(v, str):
            return v.strip().lower() not in ("false", "0", "off", "no", "")
        return bool(v)

    def get_int(self, key: str, default: int = 0) -> int:
        """Integer conf with coercion; malformed user-set values fall back
        to ``default`` (for registered keys the registry default makes
        None impossible). One helper so numeric-guardrail readers don't
        each re-implement the try/int dance."""
        v = self.get(key, default)
        try:
            return int(v)
        except (TypeError, ValueError):
            return int(default)

    def set(self, key: str, value: Any) -> None:
        with self._lock:
            self._values[key] = value
            self._generation += 1

    def unset(self, key: str) -> None:
        with self._lock:
            self._values.pop(key, None)
            self._generation += 1

    def set_temporarily(self, **kv: Any):
        """Context manager: ``with conf.set_temporarily(**{'k': v}): ...``"""
        outer = self

        class _Ctx:
            def __enter__(self):
                self._saved = {}
                for k, v in kv.items():
                    key = k.replace("__", ".")
                    with outer._lock:
                        self._saved[key] = outer._values.get(key, _MISSING)
                        outer._values[key] = v
                        outer._generation += 1
                return outer

            def __exit__(self, *exc):
                for key, old in self._saved.items():
                    with outer._lock:
                        if old is _MISSING:
                            outer._values.pop(key, None)
                        else:
                            outer._values[key] = old
                        outer._generation += 1
                return False

        return _Ctx()


_MISSING = object()
conf = SqlConf()


# ---------------------------------------------------------------------------
# Interval parsing (CalendarInterval subset: "interval N unit [N unit ...]")
# ---------------------------------------------------------------------------

_UNIT_MS = {
    "millisecond": 1,
    "second": 1000,
    "minute": 60_000,
    "hour": 3_600_000,
    "day": 86_400_000,
    "week": 7 * 86_400_000,
}

_INTERVAL_RE = re.compile(r"(-?\d+)\s+(millisecond|second|minute|hour|day|week)s?", re.IGNORECASE)


def parse_interval_ms(s: str) -> int:
    """Parse ``"interval 30 days"``-style durations to millis. Months/years are
    rejected, matching ``DeltaConfigs.isValidIntervalConfigValue`` which bans
    non-fixed durations."""
    text = s.strip()
    if text.lower().startswith("interval"):
        text = text[len("interval"):]
    ms = 0
    matched = False
    for m in _INTERVAL_RE.finditer(text):
        matched = True
        ms += int(m.group(1)) * _UNIT_MS[m.group(2).lower()]
    if not matched:
        raise DeltaIllegalArgumentError(f"Invalid interval: {s!r}")
    if ms < 0:
        raise DeltaIllegalArgumentError(f"Interval must be non-negative: {s!r}")
    return ms


# ---------------------------------------------------------------------------
# Table properties
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DeltaConfig(Generic[T]):
    key: str  # full key incl. "delta." prefix
    default: str
    from_string: Callable[[str], T]
    validate: Optional[Callable[[T], bool]] = None
    help: str = ""

    @property
    def _session_default_key(self) -> str:
        return f"delta.tpu.properties.defaults.{self.key[len('delta.'):]}"

    def is_explicit(self, metadata) -> bool:
        """True when the table (or the session defaults tier) sets this
        property, i.e. :meth:`from_metadata` would NOT fall back to the
        built-in default."""
        return ((metadata.configuration or {}).get(self.key) is not None
                or conf.get(self._session_default_key) is not None)

    def from_metadata(self, metadata) -> T:
        raw = (metadata.configuration or {}).get(self.key)
        if raw is None:
            raw = conf.get(self._session_default_key)
        if raw is None:
            raw = self.default
        try:
            value = self.from_string(str(raw))
        except DeltaIllegalArgumentError:
            raise
        except (ValueError, TypeError) as e:
            raise DeltaIllegalArgumentError(
                f"Invalid value {raw!r} for table property {self.key}: {e}"
            )
        if self.validate and not self.validate(value):
            raise DeltaIllegalArgumentError(
                f"Invalid value {raw!r} for table property {self.key}"
            )
        return value


def _bool(s: str) -> bool:
    if s.lower() in ("true", "1"):
        return True
    if s.lower() in ("false", "0"):
        return False
    raise ValueError(f"not a boolean: {s!r}")


class DeltaConfigs:
    """Registry of table properties (``DeltaConfig.scala:227-433``)."""

    LOG_RETENTION = DeltaConfig(
        "delta.logRetentionDuration", "interval 30 days", parse_interval_ms,
        help="How long commit/checkpoint files are kept before cleanup.",
    )
    TOMBSTONE_RETENTION = DeltaConfig(
        "delta.deletedFileRetentionDuration", "interval 1 week", parse_interval_ms,
        help="How long RemoveFile tombstones (and their data files) are kept.",
    )
    CHECKPOINT_INTERVAL = DeltaConfig(
        "delta.checkpointInterval", "10", int, lambda v: v > 0,
        help="Checkpoint every N commits.",
    )
    ENABLE_EXPIRED_LOG_CLEANUP = DeltaConfig(
        "delta.enableExpiredLogCleanup", "true", _bool,
    )
    IS_APPEND_ONLY = DeltaConfig(
        "delta.appendOnly", "false", _bool,
        help="When true, deletes/updates are rejected (protocol writer v2 feature).",
    )
    ISOLATION_LEVEL = DeltaConfig(
        "delta.isolationLevel", "WriteSerializable", str,
        lambda v: v in ("Serializable", "WriteSerializable"),
        help="Write isolation for data-changing commits "
             "(isolationLevels.scala:27-91).",
    )
    ENABLE_DELETION_VECTORS = DeltaConfig(
        "delta.tpu.enableDeletionVectors", "false", _bool,
        help="DML marks deleted rows in per-file deletion vectors instead of "
             "rewriting whole files (beyond-reference feature; bumps the "
             "table protocol to (3, 7)).",
    )
    CHECKPOINT_WRITE_STATS_AS_JSON = DeltaConfig(
        "delta.checkpoint.writeStatsAsJson", "true", _bool,
    )
    CHECKPOINT_WRITE_STATS_AS_STRUCT = DeltaConfig(
        "delta.checkpoint.writeStatsAsStruct", "false", _bool,
    )
    DATA_SKIPPING_NUM_INDEXED_COLS = DeltaConfig(
        "delta.dataSkippingNumIndexedCols", "32", int, lambda v: v >= -1,
        help="First N schema columns get min/max/nullCount stats (-1 = all).",
    )
    SYMLINK_FORMAT_MANIFEST_ENABLED = DeltaConfig(
        "delta.compatibility.symlinkFormatManifest.enabled", "false", _bool,
    )
    RANDOMIZE_FILE_PREFIXES = DeltaConfig(
        "delta.randomizeFilePrefixes", "false", _bool,
    )
    RANDOM_PREFIX_LENGTH = DeltaConfig(
        "delta.randomPrefixLength", "2", int, lambda v: v > 0,
    )
    CHANGE_DATA_FEED = DeltaConfig(
        "delta.enableChangeDataFeed", "false", _bool,
        help="Write change-data files for UPDATE/DELETE/MERGE.",
    )
    MIN_READER_VERSION = DeltaConfig(
        "delta.minReaderVersion", "1", int, lambda v: v > 0,
    )
    MIN_WRITER_VERSION = DeltaConfig(
        "delta.minWriterVersion", "2", int, lambda v: v > 0,
    )

    _ALL: Dict[str, DeltaConfig] = {}

    @classmethod
    def all_configs(cls) -> Dict[str, DeltaConfig]:
        if not cls._ALL:
            for name in dir(cls):
                v = getattr(cls, name)
                if isinstance(v, DeltaConfig):
                    cls._ALL[v.key.lower()] = v
        return cls._ALL

    @classmethod
    def validate_configuration(cls, configuration: Dict[str, str]) -> Dict[str, str]:
        """Type-check user-provided ``delta.*`` keys; unknown ``delta.`` keys
        are rejected (``DeltaConfig.scala verifyTableProperties``)."""
        registry = cls.all_configs()
        out = {}
        for k, v in configuration.items():
            lk = k.lower()
            if lk.startswith("delta."):
                cfg = registry.get(lk)
                if cfg is None:
                    # The reference allows unknown keys through when they match
                    # no validator only for forward-compat "delta.constraints.*"
                    # and arbitrary user keys are kept; constraints use this.
                    if lk.startswith("delta.constraints."):
                        out[k] = v
                        continue
                    raise DeltaIllegalArgumentError(f"Unknown configuration was specified: {k}")
                # run the parser for validation, store canonical key
                probe = Metadata_probe(configuration={cfg.key: v})
                cfg.from_metadata(probe)
                out[cfg.key] = v
            else:
                out[k] = v
        return out

    @classmethod
    def merge_global_configs(cls, configuration: Dict[str, str]) -> Dict[str, str]:
        """Apply session-level defaults ``delta.tpu.properties.defaults.*``
        for keys the user didn't set (``DeltaConfig.mergeGlobalConfigs``)."""
        out = dict(configuration)
        for cfg in cls.all_configs().values():
            if cfg.key in out:
                continue
            default = conf.get(f"delta.tpu.properties.defaults.{cfg.key[len('delta.'):]}" )
            if default is not None:
                out[cfg.key] = str(default)
        return out


class Metadata_probe:
    """Minimal object exposing .configuration for DeltaConfig.from_metadata."""

    def __init__(self, configuration: Dict[str, str]):
        self.configuration = configuration
