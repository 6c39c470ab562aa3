"""Environment-knob registry + typed parsers (ISSUE 12).

Every ``PIO_*`` environment variable the framework reads is declared
here ONCE — name, type, default, one-line doc — and read ONLY through
the typed parsers below. The `pio lint` env-knob checker
(analysis/check_env.py) fails any raw ``os.environ`` read of a
``PIO_*`` key elsewhere in the package, and any parser call against an
undeclared name raises at call time, so the registry can never go
stale in either direction. ``pio lint --knobs`` renders this registry
as the README "Configuration knobs" table (CI diffs it for freshness).

Parsers accept an optional ``env`` mapping so call sites that operate
on captured child/config environments (rollout config, fault specs,
fleet coords) parse through the same single grammar: missing/empty →
default; malformed → default with a warning (a typo'd knob must not
silently change behavior — PR-6 round 6 discipline, now universal).
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Any, Mapping, Optional

log = logging.getLogger(__name__)

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    type: str  # str|path|int|float|bool|flag|enum|json|spec|prefix
    default: Any
    doc: str
    prefix: bool = False  # name is a family prefix (dynamic suffixes)


KNOBS: dict[str, Knob] = {}


def _k(name: str, type_: str, default: Any, doc: str) -> None:
    KNOBS[name] = Knob(name, type_, default, doc, prefix=type_ == "prefix")


# -- storage / data plane ----------------------------------------------------
_k("PIO_FS_BASEDIR", "path", "~/.pio_store",
   "Base directory for sqlite/localfs/docfs storage and pickled models.")
_k("PIO_STORAGE_SOURCES_", "prefix", None,
   "Storage source family: PIO_STORAGE_SOURCES_<NAME>_TYPE plus "
   "per-source keys (PATH, HOSTS, PORTS, ...) — reference pio-env.sh.")
_k("PIO_STORAGE_REPOSITORIES_", "prefix", None,
   "Repository bindings: PIO_STORAGE_REPOSITORIES_<REPO>_SOURCE for "
   "METADATA / EVENTDATA / MODELDATA.")
_k("PIO_STORAGE_RETRY_ATTEMPTS", "int", 3,
   "Storage RPC retry attempts (per-source RETRY_ATTEMPTS overrides).")
_k("PIO_STORAGE_RETRY_BASE_DELAY", "float", 0.05,
   "Base delay (s) of the storage RPC exponential backoff.")
_k("PIO_BREAKER_THRESHOLD", "int", 5,
   "Consecutive storage failures before the circuit breaker opens.")
_k("PIO_BREAKER_COOLDOWN", "float", 10.0,
   "Seconds an open storage breaker waits before its recovery probe.")
_k("PIO_WAL_DIR", "path", "~/.predictionio_tpu/event-wal",
   "Event-server WAL spill directory for storage-outage ingestion.")
_k("PIO_TEST_POSTGRES_DSN", "str", "",
   "DSN enabling the live-postgres storage contract tests.")

# -- serving / rollout -------------------------------------------------------
_k("PIO_ROLLOUT_FRACTION", "float", 0.1,
   "Sticky fraction of traffic routed to a canary candidate.")
_k("PIO_ROLLOUT_WINDOW_S", "float", 30.0,
   "Sliding stats window (s) the rollout verdict compares over.")
_k("PIO_ROLLOUT_INTERVAL_S", "float", 1.0,
   "Seconds between rollout verdict ticks.")
_k("PIO_ROLLOUT_MIN_REQUESTS", "int", 20,
   "Candidate samples required before the verdict engages.")
_k("PIO_ROLLOUT_MAX_ERROR_DELTA", "float", 0.05,
   "Candidate-minus-live error-rate delta that forces rollback.")
_k("PIO_ROLLOUT_MAX_P99_RATIO", "float", 3.0,
   "Candidate/live p99 latency ratio that forces rollback.")
_k("PIO_ROLLOUT_BAKE_S", "float", 60.0,
   "Healthy bake time (s) before a canary auto-promotes.")
_k("PIO_ROLLOUT_SHADOW", "bool", False,
   "Shadow mode: mirror live traffic to the candidate and compare.")
_k("PIO_ROLLOUT_MIN_AGREEMENT", "float", 0.9,
   "Minimum shadow result-agreement fraction (rollback below).")
_k("PIO_ROLLOUT_PROXY", "flag", "",
   "Set 1 to enable the admin server's /rollout proxy endpoints (the "
   "target query-server URL rides each request body).")
_k("PIO_SERVE_HBM_BYTES", "float", None,
   "Per-device HBM budget (bytes) gating sharded serving residency.")

# -- tenancy -----------------------------------------------------------------
_k("PIO_TENANT_CACHE_SIZE", "int", 4,
   "Resident model-cache entries per query server (LRU beyond).")
_k("PIO_TENANT_CACHE_HBM_BYTES", "float", 0,
   "Model-cache budget in measured device bytes (0 = count-based).")
_k("PIO_TENANT_REFRESH_S", "float", 5.0,
   "TTL (s) of the admission path's cached tenant records.")
_k("PIO_TENANT_SYNC_S", "float", 10.0,
   "Period (s) of the mux background sync (refresh/rollouts/prefetch).")
_k("PIO_TENANT_METRIC_MAX", "int", 50,
   "Distinct tenant label values before metrics collapse to (other).")

# -- gateway / replicated serving (ISSUE 15) ---------------------------------
_k("PIO_GATEWAY_SYNC_S", "float", 0.5,
   "Seconds between gateway discovery/health sync passes.")
_k("PIO_GATEWAY_STALE_S", "float", 3.0,
   "Replica heartbeat age (s) past which the gateway stops routing "
   "to it.")
_k("PIO_GATEWAY_HEDGE", "bool", True,
   "Hedged queries: speculate to the next replica at the p95 mark.")
_k("PIO_GATEWAY_HEDGE_MIN_MS", "float", 25.0,
   "Floor (ms) on the hedge delay while a replica's latency window "
   "is cold.")
_k("PIO_GATEWAY_LOAD_FACTOR", "float", 1.5,
   "Bounded-load consistent hashing: skip replicas over factor x the "
   "mean in-flight load.")
_k("PIO_GATEWAY_VNODES", "int", 64,
   "Virtual nodes per replica on the consistent-hash ring.")
_k("PIO_REPLICA_HEARTBEAT_S", "float", 1.0,
   "Seconds between a replica's registry heartbeats.")

# -- online learning ---------------------------------------------------------
_k("PIO_ONLINE_TICK_S", "float", 0.5,
   "Seconds between online fold-in consumer ticks.")
_k("PIO_ONLINE_DRIFT_THRESHOLD", "float", 1.0,
   "Score-drift score that pauses fold-in and raises the alert.")
_k("PIO_ONLINE_DRIFT_COOLDOWN_S", "float", 0.0,
   "Cool-down (s) after a completed retrain before a drift-paused "
   "consumer re-probes drift once and auto-resumes if clean; 0 keeps "
   "the immediate-resume-on-retrain behaviour.")

# -- event-store replication -------------------------------------------------
_k("PIO_REPL_FOLLOWERS", "str", "",
   "Comma-separated host:port follower storage daemons the primary's "
   "SegmentShipper streams segments and the WAL tail to. Empty "
   "disables replication.")
_k("PIO_REPL_MIN_ACKS", "int", 0,
   "Synchronous-replication floor: insert_batch acks only after this "
   "many followers applied the WAL frame (0 = async shipping only).")
_k("PIO_REPL_SHIP_INTERVAL_S", "float", 0.25,
   "Seconds between background SegmentShipper passes (segment sync + "
   "WAL-tail catch-up + tombstone sync).")
_k("PIO_REPL_WAL_BATCH", "int", 512,
   "Max live-tail rows per replication WAL frame on catch-up passes.")
_k("PIO_REPL_MAX_LAG_REVISIONS", "int", 1000,
   "Replication-lag budget (revisions) used by the replication_lag "
   "SLO preset.")
_k("PIO_REPL_EPOCH", "int", 1,
   "Replication epoch a primary storage daemon stamps into shipped "
   "frames at boot. Normally 1 for the original primary; a promoted "
   "follower's epoch comes from the election generation instead.")

# -- fleet -------------------------------------------------------------------
_k("PIO_FLEET_COORDINATOR", "str", "",
   "host:port of process 0 for jax.distributed multi-host init.")
_k("PIO_FLEET_NUM_PROCESSES", "int", 1,
   "Total process count of the fleet's jax.distributed job.")
_k("PIO_FLEET_PROCESS_ID", "int", 0,
   "This process's index within the jax.distributed job.")

# -- observability: tracing / metrics / profiling ----------------------------
_k("PIO_TRACE_SAMPLE", "float", 0.1,
   "Tail-sampling keep probability for non-error, non-slow traces.")
_k("PIO_TRACE_MAX", "int", 256,
   "Retained-trace cap of the in-process span recorder.")
_k("PIO_TRACE_SLOW_MS", "float", 250.0,
   "Root-span duration (ms) above which a trace is always kept.")
_k("PIO_DEVPROF", "flag", "1",
   "Device profiling layer; 0 disables every instrument() wrapper.")
_k("PIO_DEVPROF_MEMORY", "flag", "",
   "Force memory_analysis on (1) / off (0) for all instrumented jits.")
_k("PIO_PEAK_FLOPS", "float", None,
   "Peak device FLOP/s override pinning every dtype column (MFU).")
_k("PIO_PEAK_FLOPS_INT8", "float", None,
   "Peak int8 FLOP/s override for dtype-aware MFU.")
_k("PIO_PEAK_FLOPS_F32", "float", None,
   "Peak f32 FLOP/s override for dtype-aware MFU.")
_k("PIO_PEAK_HBM_BPS", "float", None,
   "Peak HBM bandwidth (bytes/s) override for %-of-roof.")
_k("PIO_PROFILE_CAPTURE_DIR", "path", "",
   "Directory enabling POST /debug/profile/capture jax.profiler dumps.")

# -- fleet observability (ISSUE 16) -----------------------------------------
_k("PIO_TRACE_COLLECT", "flag", "1",
   "Fleet trace collector; 0 disables /debug/traces polling even when "
   "scrape targets exist.")
_k("PIO_TRACE_COLLECT_INTERVAL_S", "float", 2.0,
   "Seconds between trace-collector /debug/traces polls.")
_k("PIO_TRACE_COLLECT_HOLD_S", "float", 15.0,
   "Seconds an orphan span fragment (no root seen yet) is held for "
   "late stitching before it expires.")
_k("PIO_TRACE_COLLECT_MAX", "int", 256,
   "Assembled cross-process traces retained by the collector.")
_k("PIO_TRACE_EXEMPLARS", "int", 4,
   "Slowest (trace-id, value) exemplars retained per histogram family "
   "(0 disables exemplar capture).")
_k("PIO_RECORDING_RULES", "json", "",
   "Recording rules: JSON array of rule objects, or @/path/to/rules "
   "(auto-derived per-SLO rules are added on top).")
_k("PIO_TENANT_SLO_PRESETS", "flag", "",
   "Set 1 to auto-derive per-tenant availability/latency SLO presets "
   "from tenant records at mux attach.")
_k("PIO_WORKER_METRICS_URL", "str", "",
   "Metrics URL a fleet worker advertises on its registry record so "
   "`pio fleet status` can scrape per-worker device gauges.")

# -- push telemetry (ISSUE 17) ----------------------------------------------
_k("PIO_PUSH_URL", "str", "",
   "Base URL of a push-telemetry ingest (POST /telemetry/push); set in "
   "ephemeral processes (train workers, fleet workers) to ship spooled "
   "metrics/spans/devprof. Empty disables shipping.")
_k("PIO_PUSH_SPOOL", "path", "",
   "Local fsync'd spool directory for the telemetry shipper; the train "
   "scheduler defaults each child to <log_dir>/<job>.spool so orphaned "
   "spools of killed workers are shipped by the supervisor.")
_k("PIO_PUSH_INGEST", "flag", "",
   "Set 1 to enable the guarded POST /telemetry/push ingest endpoint "
   "on this server (dashboard/monitor).")
_k("PIO_PUSH_INTERVAL_S", "float", 10.0,
   "Seconds between telemetry-shipper spool+ship passes.")
_k("PIO_PUSH_DEADLINE_S", "float", 5.0,
   "Wall-clock budget (s) one telemetry ship pass may spend retrying.")
_k("PIO_PUSH_SPOOL_MAX_BYTES", "int", 8 * 1024 * 1024,
   "Telemetry spool directory size bound; oldest spool files drop "
   "first.")
_k("PIO_SCRAPE_BACKOFF_MAX_S", "float", 60.0,
   "Cap (s) on the fleet scraper's exponential backoff for down "
   "targets (up{instance}=0 still records every tick).")
_k("PIO_PUSH_TOKEN", "str", "",
   "Shared secret for per-instance push-ingest auth: shippers send "
   "X-PIO-Push-Token = HMAC-SHA256(secret, instance) and the ingest "
   "rejects payloads whose token does not match their instance label. "
   "Empty disables auth.")
_k("PIO_PUSH_SPAN_RATE", "float", 50.0,
   "Per-instance pushed-span admission budget (spans/s token bucket) "
   "at the telemetry ingest; overflow is dropped and counted in "
   "telemetry_push_dropped_total{kind=span}.")
_k("PIO_PUSH_SPAN_BURST", "float", 200.0,
   "Burst capacity (spans) of the per-instance pushed-span bucket.")

# -- monitoring plane --------------------------------------------------------
_k("PIO_TSDB", "flag", "1",
   "In-process monitoring plane; 0 disables sampler/TSDB/SLO engine.")
_k("PIO_TSDB_POINTS", "int", 720,
   "Ring-buffer points retained per TSDB series.")
_k("PIO_TSDB_MAX_SERIES", "int", 4096,
   "TSDB series-cardinality cap (adds beyond are dropped+counted).")
_k("PIO_TSDB_INTERVAL_S", "float", 5.0,
   "Seconds between metrics-sampler snapshots into the TSDB.")
_k("PIO_SLO_INTERVAL_S", "float", 15.0,
   "Seconds between SLO burn-rate evaluation passes.")
_k("PIO_SLOS", "json", "",
   "SLO specs: JSON array of spec objects, or @/path/to/slos.json.")
_k("PIO_MONITOR_TARGETS", "str", "",
   "Comma-separated name=url /metrics scrape targets for the fleet "
   "scraper (pio monitor, dashboard).")
_k("PIO_SCRAPE_INTERVAL_S", "float", 10.0,
   "Seconds between fleet-scraper /metrics polls.")
_k("PIO_TSDB_SNAPSHOT", "path", "",
   "Path persisting the TSDB rings across restarts (empty = off).")
_k("PIO_TSDB_SNAPSHOT_INTERVAL_S", "float", 60.0,
   "Seconds between TSDB snapshot writes.")
_k("PIO_TSDB_DIR", "path", "",
   "Directory of the durable on-disk TSDB tier (fsync'd WAL + sealed "
   "columnar blocks + 5m/1h downsampled tiers). Empty keeps history "
   "memory-only; set, it supersedes PIO_TSDB_SNAPSHOT.")
_k("PIO_TSDB_FLUSH_S", "float", 2.0,
   "Seconds between durable-TSDB WAL flush+fsync passes.")
_k("PIO_TSDB_SEAL_POINTS", "int", 50000,
   "Points in the active WAL segment that trigger sealing it into an "
   "immutable columnar block.")
_k("PIO_TSDB_SEAL_AGE_S", "float", 300.0,
   "Age (s) of a non-empty active WAL segment that triggers sealing.")
_k("PIO_TSDB_COMPACT_S", "float", 30.0,
   "Seconds between durable-TSDB compactor passes (downsampling + "
   "per-tier retention).")
_k("PIO_TSDB_CKPT_POINTS", "int", 50000,
   "Flushed WAL points between durable-TSDB replay-checkpoint writes; "
   "attach replays only WAL bytes past the checkpoint (0 disables "
   "checkpointing).")
_k("PIO_TSDB_RETENTION_RAW", "float", 6 * 3600.0,
   "Retention (s) of raw-resolution durable blocks.")
_k("PIO_TSDB_RETENTION_5M", "float", 3 * 86400.0,
   "Retention (s) of the 5-minute downsampled tier.")
_k("PIO_TSDB_RETENTION_1H", "float", 14 * 86400.0,
   "Retention (s) of the 1-hour downsampled tier.")
_k("PIO_ALERT_WEBHOOK", "str", "",
   "URL POSTed one JSON alert per SLO/external alert transition.")
_k("PIO_ALERT_EXEC", "str", "",
   "Command run per alert transition (JSON on stdin + $PIO_ALERT_JSON).")
_k("PIO_ALERT_JSON", "str", "",
   "Set BY the exec alert sink for its child: the alert payload.")

# -- kernels / numerics ------------------------------------------------------
_k("PIO_DENSE_ALS", "flag", "",
   "Dense ALS solver: 1 forces on, 0 forces off, empty = auto.")
_k("PIO_DENSE_ALS_BYTES", "int", 9_000_000_000,
   "Densified-matrix byte budget the dense-ALS auto mode respects.")
_k("PIO_PALLAS_DENSE", "enum", "",
   "Dense-pass Pallas kernel mode: tpu | interpret | 0 (XLA).")
_k("PIO_PALLAS_WINDOWED", "enum", "",
   "Windowed-pass Pallas kernel mode: tpu | interpret | 0 (XLA).")
_k("PIO_PALLAS_RECOMMEND", "enum", "",
   "Fused recommend+top-k kernel mode: tpu | interpret | empty (XLA).")

# -- resilience / fault injection -------------------------------------------
_k("PIO_FAULTS", "spec", "",
   "Deterministic fault specs: point:mode:prob[:param][,...].")
_k("PIO_FAULTS_SEED", "int", None,
   "Seed pinning every fault point's RNG across processes.")
_k("PIO_FAULTS_ADMIN", "flag", "",
   "Set 1 to enable the guarded POST /debug/faults admin endpoint.")

# -- analysis / sanitizer (ISSUE 12) ----------------------------------------
_k("PIO_TSAN", "flag", "",
   "Set 1 to patch threading locks with the lock-order sanitizer.")
_k("PIO_TSAN_REPORT", "path", "",
   "Path the sanitizer writes its JSON findings report to at exit.")

# -- fleet evaluation & auto-tuning (ISSUE 20) -------------------------------
_k("PIO_EVAL_POLL_S", "float", 0.5,
   "Eval-driver poll cadence (s): partial-result folds + re-dispatch.")
_k("PIO_EVAL_SHARD_TIMEOUT_S", "float", 600.0,
   "Wall-clock timeout (s) for one fleet eval shard job.")
_k("PIO_EVAL_MAX_ATTEMPTS", "int", 3,
   "Queue retry budget per eval shard job (infra failures).")
_k("PIO_EVAL_REDISPATCH", "int", 2,
   "Extra driver re-submissions per exhausted eval shard before the "
   "run fails (straggler/poison insurance on top of queue retries).")
_k("PIO_EVAL_RETENTION", "int", 20,
   "Terminal EvalRun records (with results) the eval GC keeps.")
_k("PIO_TUNE_PRIOR", "flag", "1",
   "Set 0 to disable the canary offline prior from eval records.")
_k("PIO_TUNE_STRICT_BAKE", "float", 2.0,
   "Bake-window multiplier when the candidate's linked offline eval "
   "score is worse than live's (<=1 disables).")
_k("PIO_CAS_SETTLE_S", "str", "",
   "Operator-pinned CAS claim settle window (s); empty = adapt from "
   "measured storage write-visibility skew at fleet-member start.")
_k("PIO_CAS_SETTLE_MIN_S", "float", 0.02,
   "Floor (s) of the adaptive CAS claim settle window.")
_k("PIO_CAS_SETTLE_MAX_S", "float", 2.0,
   "Ceiling (s) of the adaptive CAS claim settle window.")

def knob_registry() -> list[Knob]:
    """Declared knobs, sorted by name (the `pio lint --knobs` view)."""
    return [KNOBS[n] for n in sorted(KNOBS)]


def _require(name: str) -> Knob:
    knob = KNOBS.get(name)
    if knob is None:
        for k in KNOBS.values():
            if k.prefix and name.startswith(k.name):
                return k
        raise ValueError(
            f"env knob {name!r} is not declared in the registry "
            "(predictionio_tpu/utils/env.py) — declare it with a type, "
            "default, and doc line before reading it"
        )
    return knob


def _get(name: str, env: Optional[Mapping[str, str]]) -> Optional[str]:
    _require(name)
    mapping = os.environ if env is None else env
    raw = mapping.get(name)
    if raw is None or raw == "":
        return None
    return raw


def env_raw(name: str, env: Optional[Mapping[str, str]] = None
            ) -> Optional[str]:
    """Raw registered read: the value as set, or None when missing/empty.
    For save/restore sites and grammars with their own parser (faults,
    SLO specs) — everything else should use a typed parser."""
    return _get(name, env)


def env_str(name: str, default: Optional[str] = None,
            env: Optional[Mapping[str, str]] = None) -> str:
    raw = _get(name, env)
    if raw is not None:
        return raw
    if default is not None:
        return default
    knob_default = _require(name).default
    return "" if knob_default is None else str(knob_default)


def env_path(name: str, default: Optional[str] = None,
             env: Optional[Mapping[str, str]] = None) -> str:
    """Like env_str but expands ~ in both the value and the default."""
    return os.path.expanduser(env_str(name, default, env))


def env_float(name: str, default: Optional[float] = None,
              env: Optional[Mapping[str, str]] = None) -> float:
    if default is None:
        d = _require(name).default
        default = 0.0 if d is None else float(d)
    raw = _get(name, env)
    if raw is None:
        return float(default)
    try:
        return float(raw)
    except ValueError:
        log.warning("ignoring malformed %s=%r", name, raw)
        return float(default)


def env_opt_float(name: str, env: Optional[Mapping[str, str]] = None
                  ) -> Optional[float]:
    """Float or None when unset/malformed (peak-override semantics)."""
    raw = _get(name, env)
    if raw is None:
        return None
    try:
        return float(raw)
    except ValueError:
        log.warning("ignoring malformed %s=%r", name, raw)
        return None


def env_int(name: str, default: Optional[int] = None,
            env: Optional[Mapping[str, str]] = None) -> int:
    if default is None:
        d = _require(name).default
        default = 0 if d is None else int(d)
    raw = _get(name, env)
    if raw is None:
        return int(default)
    try:
        return int(float(raw))
    except (ValueError, OverflowError):  # OverflowError: "inf"
        log.warning("ignoring malformed %s=%r", name, raw)
        return int(default)


def env_bool(name: str, default: Optional[bool] = None,
             env: Optional[Mapping[str, str]] = None) -> bool:
    if default is None:
        default = bool(_require(name).default)
    raw = _get(name, env)
    if raw is None:
        return bool(default)
    lowered = raw.strip().lower()
    if lowered in _TRUTHY:
        return True
    if lowered in _FALSY:
        return False
    log.warning("ignoring malformed %s=%r", name, raw)
    return bool(default)


def env_flag(name: str, env: Optional[Mapping[str, str]] = None) -> bool:
    """Presence-style gate: set to anything but ''/0/false/no/off."""
    raw = _get(name, env)
    if raw is None:
        return False
    return raw.strip().lower() not in _FALSY


def knobs_markdown() -> str:
    """The registry as a markdown table — `pio lint --knobs` output and
    the README "Configuration knobs" section (CI keeps them in sync)."""
    lines = [
        "| Knob | Type | Default | Description |",
        "| --- | --- | --- | --- |",
    ]
    for knob in knob_registry():
        if knob.prefix:
            name = f"`{knob.name}*`"
            default = ""
        else:
            name = f"`{knob.name}`"
            default = "" if knob.default in (None, "") else f"`{knob.default}`"
        doc = " ".join(knob.doc.split())
        lines.append(f"| {name} | {knob.type} | {default} | {doc} |")
    return "\n".join(lines) + "\n"
