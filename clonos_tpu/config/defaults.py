"""The framework's configuration surface.

Covers the reference's Clonos-specific keys (SURVEY §2.3 config row:
flink-runtime .../configuration/JobManagerOptions.java:111-135, NettyConfig
.java:82-98, ExecutionConfig.java:297-310, InFlightLogConfig.java:42-71) plus
the TPU-native knobs this framework adds (log capacities, spill budgets).
"""

from __future__ import annotations

from clonos_tpu.config.options import ConfigOption

# --- failover / standby (reference: JobManagerOptions.java:111-135) ---------

FAILOVER_STRATEGY = ConfigOption(
    "jobmanager.execution.failover-strategy", "standbytask",
    description="Failover strategy: 'standbytask' (Clonos local recovery) or "
                "'full' (global restart).")

NUM_STANDBY_TASKS = ConfigOption(
    "jobmanager.execution.num-standby-tasks", 1,
    description="Passive standby replicas per subtask, state-synced via "
                "checkpoint pushes.")

CHECKPOINT_BACKOFF_MULTIPLIER = ConfigOption(
    "jobmanager.execution.checkpoint-backoff-multiplier", 2.0,
    description="Multiplier on the checkpoint interval during recovery.")

# --- determinant sharing (reference: ExecutionConfig.java:297-310) ----------

DETERMINANT_SHARING_DEPTH = ConfigOption(
    "causal.determinant-sharing-depth", -1,
    description="How many hops downstream determinants are replicated. "
                "-1 = full sharing (survive any number of connected "
                "failures); k = survive up to k connected failures.")

# --- determinant log memory (reference: NettyConfig.java:82-98) -------------

DETERMINANT_LOG_CAPACITY = ConfigOption(
    "causal.log.capacity", 1 << 16,
    description="Slots per thread causal log ring buffer (device HBM). "
                "Must be a power of two.",
    validator=lambda v: v > 0 and (v & (v - 1)) == 0)

DETERMINANT_MAX_EPOCHS = ConfigOption(
    "causal.log.max-epochs", 64,
    description="Maximum concurrently-retained (un-truncated) epochs per log.",
    validator=lambda v: v > 0)

# --- in-flight log (reference: InFlightLogConfig.java:42-71) ----------------

INFLIGHT_TYPE = ConfigOption(
    "taskmanager.inflight.type", "inmemory",
    validator=lambda v: v in ("spillable", "inmemory", "disabled"),
    description="In-flight log implementation.")

INFLIGHT_SPILL_POLICY = ConfigOption(
    "taskmanager.inflight.spill.policy", "eager",
    validator=lambda v: v in ("eager", "availability", "epoch"),
    description="When to spill epochs from HBM to host memory/disk.")

INFLIGHT_HOST_BUDGET_EPOCHS = ConfigOption(
    "taskmanager.inflight.spill.host-budget-epochs", 2,
    description="Sealed epochs each spill owner keeps resident in the host "
                "staging tier once their segments are durable; older "
                "epochs demote to disk-only (storage/tiered.py).")

INFLIGHT_CAPACITY_BATCHES = ConfigOption(
    "taskmanager.inflight.capacity-batches", 256,
    description="Batches retained per edge in the device-resident in-flight "
                "ring.")

# --- checkpointing ----------------------------------------------------------

CHECKPOINT_INTERVAL_STEPS = ConfigOption(
    "checkpoint.interval-steps", 16,
    description="Supersteps per epoch (checkpoint barrier cadence).")

CHECKPOINT_DIR = ConfigOption(
    "checkpoint.dir", "/tmp/clonos_tpu/checkpoints",
    description="Durable storage root for snapshots and spilled epochs.")

# --- heartbeats --------------------------------------------------------------

HEARTBEAT_TIMEOUT_MS = ConfigOption(
    "heartbeat.timeout", 5000,
    description="Missed-heartbeat window before a task executor is declared "
                "failed.")

# --- observability (clonos_tpu/obs) -----------------------------------------

AUDIT_ENABLED = ConfigOption(
    "observability.audit.enabled", False,
    description="Seal a per-epoch audit digest at every checkpoint barrier, "
                "persist the epoch ledger next to the checkpoints, and "
                "validate replayed epochs against it during recovery. Off = "
                "the NullAuditor: no digest reads, no ledger writes, no "
                "wire fields.")

AUDIT_ON_DIVERGENCE = ConfigOption(
    "observability.audit.on-divergence", "warn",
    validator=lambda v: v in ("warn", "abort"),
    description="What a replay-divergence audit finding does: 'warn' emits "
                "the recovery.audit.divergence instant and counts it; "
                "'abort' additionally fails the recovery "
                "(AuditDivergenceError) before the job resumes on "
                "non-reproduced state.")

PROFILE_ENABLED = ConfigOption(
    "observability.profile.enabled", False,
    description="Attribute per-section fault-tolerance overhead "
                "(overhead.<section>-ms histograms + the "
                "overhead.ft-fraction gauge) with device-fenced section "
                "timers in the hot paths. Off = the NullProfiler: no "
                "fencing, no per-step host work.")
