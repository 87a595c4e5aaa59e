"""The CYCLONUS_* environment vocabulary as a single declarative
registry: every flag's name, type, parsed default, owning subsystem,
and one-line meaning, plus never-raise accessors that parse through
the registry.

Two drifts motivated centralizing this:

  * CYCLONUS_SLAB_MAX_BYTES had four parse sites; serve/incremental.py
    and engine/cidrspace.py degraded a malformed value to the 6 GiB
    default while engine/api.py's two sites parsed with a bare int()
    and raised at evaluate time.  One flag, two failure modes.
  * CYCLONUS_AUTOTUNE_TIMEOUT_S was parsed independently at both
    autotune search sites in engine/api.py — same default today, but
    nothing pinned them together.

Accessors here never raise on a malformed value: they degrade to the
registered default (the serve/incremental.py discipline, now uniform).
Flags whose resolvers validate-and-raise on purpose (CYCLONUS_PACK,
CYCLONUS_MESH_SCHEDULE, CYCLONUS_PALLAS_DTYPE reject unknown modes at
entry-point resolution) keep their validating parse at the resolver;
the registry still declares them so the vocabulary — and the README
table generated from it — is complete.  tests/test_envflags.py greps
the tree and fails on any CYCLONUS_* token missing from this registry.

Bool semantics are encoded by the default: default False means the
flag is opt-in (`== "1"`), default True means opt-out (`!= "0"`).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class Flag:
    name: str
    kind: str  # "bool" | "int" | "float" | "enum" | "str" | "path"
    default: object
    owner: str  # "engine" | "serve" | "worker" | "chaos" | "telemetry" | "probe" | "harness" | "cli" | "slo" | "audit"
    description: str
    choices: Tuple[str, ...] = field(default=())


_FLAGS = [
    # --- engine: evaluation plans and budgets -------------------------
    Flag("CYCLONUS_SLAB_MAX_BYTES", "int", 6 * 2**30, "engine",
         "HBM byte budget shared by counts slabs, CIDR staging, and "
         "serve's staged patches (default 6 GiB)."),
    Flag("CYCLONUS_PACK", "enum", "auto", "engine",
         "Packed dtype plan kill switch; resolved eagerly at entry "
         "points (encoding.resolve_pack).", choices=("auto", "0", "1")),
    Flag("CYCLONUS_COMPACT", "enum", "", "engine",
         "Rule-compaction opt-out: '0' disables, '1' forces past the "
         "host-work budget, '' (default) auto.", choices=("", "0", "1")),
    Flag("CYCLONUS_PRE_CACHE", "bool", True, "engine",
         "Device-resident precompute of the dense counts route: the "
         "pin of a repeated case set and the case-independent half "
         "kept for every other request; off keeps nothing on the "
         "device (the fused program runs)."),
    Flag("CYCLONUS_CLASS_COMPRESS", "enum", "auto", "engine",
         "Pod-class compression: 'auto' (size floor), '1' (force), "
         "'0' (off).", choices=("auto", "0", "1")),
    Flag("CYCLONUS_CLASS_MIN_PODS", "int", 2048, "engine",
         "Pod-count floor below which auto class compression stays "
         "off."),
    Flag("CYCLONUS_FULL_LOCATIONS", "bool", False, "engine",
         "Keep full jaxpr source locations (debug; bigger traces)."),
    Flag("CYCLONUS_JAX_CACHE", "path", "", "engine",
         "JAX persistent compilation cache dir; '0' disables, unset "
         "picks the compile-cache root ($JAX_COMPILATION_CACHE_DIR, "
         "else .cache/jax in the checkout)."),
    # --- engine: kernels and autotune ---------------------------------
    Flag("CYCLONUS_PALLAS_DTYPE", "enum", "int8", "engine",
         "Pallas counts-kernel operand dtype.",
         choices=("int8", "bf16")),
    Flag("CYCLONUS_PALLAS_SLAB", "enum", "auto", "engine",
         "Pallas slab materialization: 'auto' (TPU only), '1', '0'.",
         choices=("auto", "0", "1")),
    Flag("CYCLONUS_MESH_SCHEDULE", "enum", "ring", "engine",
         "Sharded grid peer exchange (sharded.mesh_schedule).",
         choices=("ring", "allgather")),
    Flag("CYCLONUS_AUTOTUNE", "enum", "auto", "engine",
         "Steady-state kernel autotune: 'auto' (TPU only), '1' "
         "(force, interpret ok), '0' (off).",
         choices=("auto", "0", "1")),
    Flag("CYCLONUS_AUTOTUNE_REPS", "int", 4, "engine",
         "Timed reps per autotune round."),
    Flag("CYCLONUS_AUTOTUNE_ROUNDS", "int", 3, "engine",
         "Autotune rounds per candidate."),
    Flag("CYCLONUS_AUTOTUNE_TIMEOUT_S", "float", 240.0, "engine",
         "Wall-clock bound on one autotune search (both the packed "
         "candidate search and the dense search share it)."),
    Flag("CYCLONUS_AUTOTUNE_DRAIN_S", "float", 5.0, "engine",
         "Grace period for an orphaned autotune candidate thread."),
    Flag("CYCLONUS_AUTOTUNE_CACHE", "path", "", "engine",
         "Autotune result cache file; '0' disables, unset picks "
         "autotune.json under the compile-cache root."),
    Flag("CYCLONUS_AOT_CACHE", "path", "", "engine",
         "Persistent AOT executable cache dir; '0' disables, unset "
         "picks aot/ under the compile-cache root."),
    # --- engine: CIDR pre-classification ------------------------------
    Flag("CYCLONUS_CIDR_TSS", "enum", "auto", "engine",
         "TSS/LPM CIDR pre-classification: 'auto' (spec floor), '1', "
         "'0'.", choices=("auto", "0", "1")),
    Flag("CYCLONUS_CIDR_TSS_MIN", "int", 256, "engine",
         "CIDR spec-count floor for auto TSS."),
    Flag("CYCLONUS_CIDR_TSS_DEVICE", "enum", "auto", "engine",
         "Device-side TSS classify: 'auto' (cell floor), '1', '0'.",
         choices=("auto", "0", "1")),
    Flag("CYCLONUS_CIDR_DEVICE_MIN", "int", 1 << 24, "engine",
         "Cell-count floor for auto device-side TSS classify."),
    # --- serve ---------------------------------------------------------
    Flag("CYCLONUS_SERVE_HEADROOM", "int", 1, "serve",
         "Spare compiled-shape buckets kept warm past the live "
         "snapshot's need."),
    Flag("CYCLONUS_SERVE_PREWARM", "bool", True, "serve",
         "Prewarm compiled programs at serve start."),
    Flag("CYCLONUS_SERVE_PREWARM_PAIRS", "int", 64, "serve",
         "Pair-batch bucket size prewarmed for query()."),
    Flag("CYCLONUS_SERVE_CHURN_ROWS", "int", 64, "serve",
         "Row-growth slack per incremental patch flush."),
    Flag("CYCLONUS_SERVE_CHURN_FRAC", "float", 0.25, "serve",
         "Fraction of snapshot rows tolerated as staged churn before "
         "rebuild."),
    # --- worker / fleet -------------------------------------------------
    Flag("CYCLONUS_WORKER_TIMEOUT_S", "float", 120.0, "worker",
         "Per-request worker RPC timeout."),
    Flag("CYCLONUS_WORKER_RETRIES", "int", 2, "worker",
         "Worker RPC retry attempts."),
    Flag("CYCLONUS_WORKER_BACKOFF_S", "float", 0.5, "worker",
         "Base backoff between worker RPC retries."),
    Flag("CYCLONUS_WORKER_IMAGE", "str", "cyclonus-tpu-worker:latest",
         "worker", "Worker container image."),
    Flag("CYCLONUS_AGNHOST_IMAGE", "str", "", "worker",
         "Agnhost probe image override."),
    Flag("CYCLONUS_CONNECT_NATIVE", "bool", False, "worker",
         "Probe with native sockets instead of agnhost exec."),
    Flag("CYCLONUS_SOURCE_IP", "str", "", "worker",
         "Source IP override for native probes."),
    # --- chaos ----------------------------------------------------------
    Flag("CYCLONUS_CHAOS", "str", "", "chaos",
         "Fault-injection spec armed for the chaos harness."),
    Flag("CYCLONUS_CHAOS_TTFV_S", "float", 150.0, "chaos",
         "Time-to-first-verdict bound asserted by the chaos harness."),
    # --- telemetry ------------------------------------------------------
    Flag("CYCLONUS_TELEMETRY", "bool", True, "telemetry",
         "Telemetry counters/gauges master switch."),
    Flag("CYCLONUS_TRACE_EVENTS", "bool", False, "telemetry",
         "Structured event trace emission."),
    Flag("CYCLONUS_TRACE_EVENTS_N", "int", 32768, "telemetry",
         "Event trace ring capacity."),
    Flag("CYCLONUS_TRACE_ID", "str", "", "telemetry",
         "Trace correlation id attached to emitted events."),
    Flag("CYCLONUS_TRACE_VERDICTS", "bool", False, "telemetry",
         "Per-verdict trace logging in the probe runner."),
    Flag("CYCLONUS_FLIGHT_RECORDER_PATH", "path", "", "telemetry",
         "Flight-recorder dump path ('' picks the default)."),
    Flag("CYCLONUS_FLIGHT_RECORDER_N", "int", 64, "telemetry",
         "Flight-recorder ring capacity."),
    # --- slo: objectives, windows, and enforcement ----------------------
    Flag("CYCLONUS_SLO_QUERY_P99_S", "float", 0.25, "slo",
         "query_p99 objective target: per-flow query latency bound."),
    Flag("CYCLONUS_SLO_FRESHNESS_S", "float", 5.0, "slo",
         "freshness objective target: oldest pending delta's tolerated "
         "wait age."),
    Flag("CYCLONUS_SLO_TTFV_S", "float", 150.0, "slo",
         "ttfv objective target: time-to-first-verdict after restart."),
    Flag("CYCLONUS_SLO_BUDGET", "float", 0.01, "slo",
         "Error budget shared by the declared objectives (tolerated "
         "bad-event fraction)."),
    Flag("CYCLONUS_SLO_FAST_S", "float", 300.0, "slo",
         "Fast burn-rate window (seconds)."),
    Flag("CYCLONUS_SLO_SLOW_S", "float", 3600.0, "slo",
         "Slow burn-rate window (seconds)."),
    Flag("CYCLONUS_SLO_ENFORCE", "bool", False, "slo",
         "Arm SLO enforcement (admission control, shed, degraded-path "
         "governance); accounting and /slo run regardless."),
    Flag("CYCLONUS_SLO_QUEUE_CAP", "int", 1024, "slo",
         "Pending-delta queue cap applied while the freshness budget "
         "is burning."),
    Flag("CYCLONUS_SLO_ENTER_BURN", "float", 2.0, "slo",
         "Fast-window burn rate at which an objective enters "
         "'burning'."),
    Flag("CYCLONUS_SLO_EXIT_BURN", "float", 1.0, "slo",
         "Burn rate both windows must stay below to start the exit "
         "hold."),
    Flag("CYCLONUS_SLO_HOLD_S", "float", 60.0, "slo",
         "Continuous below-exit-threshold time required to leave an "
         "enforcement state."),
    # --- audit: shadow-oracle sampling + epoch digests ------------------
    Flag("CYCLONUS_AUDIT", "bool", False, "audit",
         "Arm the verdict audit plane (shadow-oracle sampler, epoch "
         "digests, /audit route); off strips the query path to one "
         "attribute check."),
    Flag("CYCLONUS_AUDIT_RATE", "float", 0.05, "audit",
         "Fraction of answered flow queries the shadow-oracle sampler "
         "re-checks (seeded Bernoulli per verdict)."),
    Flag("CYCLONUS_AUDIT_QUEUE", "int", 1024, "audit",
         "Audit check-queue cap; overflow drops are counted, never "
         "block the query path."),
    Flag("CYCLONUS_AUDIT_SEED", "int", 0, "audit",
         "Sampler RNG seed (deterministic sampling decisions for a "
         "fixed query order)."),
    Flag("CYCLONUS_AUDIT_DIGEST_ROWS", "int", 8, "audit",
         "Truth-table rows sampled into each epoch digest (seeded off "
         "the state digest, so replicas sample identical rows)."),
    Flag("CYCLONUS_AUDIT_EPOCHS", "int", 8, "audit",
         "Epoch snapshot ring depth: checks older than this many "
         "committed epochs are dropped as epoch_evicted."),
    # --- harnesses (strip contracts: read ONCE at import) ---------------
    Flag("CYCLONUS_SHAPE_CHECK", "bool", False, "harness",
         "Arm runtime shape-contract checks (utils/contracts.py)."),
    Flag("CYCLONUS_GUARD_CHECK", "bool", False, "harness",
         "Arm runtime lock-guard checks (utils/guards.py)."),
    Flag("CYCLONUS_KEYHARNESS", "bool", False, "harness",
         "Arm the cache-key mutation recorder (utils/cachekeys.py)."),
    Flag("CYCLONUS_PLANHARNESS", "bool", False, "harness",
         "Arm the dispatch-route recorder (engine/planspec.py)."),
    Flag("CYCLONUS_STATEHARNESS", "bool", False, "harness",
         "Arm the state-surface registry call recorder "
         "(serve/stateregistry.py)."),
    Flag("CYCLONUS_SKEWHARNESS", "bool", False, "harness",
         "Arm the wire skew-view recorder (worker/wireregistry.py)."),
]

REGISTRY: Dict[str, Flag] = {f.name: f for f in _FLAGS}


def get_raw(name: str) -> Optional[str]:
    """The unparsed environment value, or None when unset.  `name` must
    be registered — an unregistered read is a programming error and
    raises KeyError (at import/test time, not in degraded parsing)."""
    flag = REGISTRY[name]
    return os.environ.get(flag.name)


def get_int(name: str) -> int:  # never-raises (registered names)
    flag = REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return int(flag.default)
    try:
        return int(raw)
    except (ValueError, TypeError):
        return int(flag.default)


def get_float(name: str) -> float:  # never-raises (registered names)
    flag = REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return float(flag.default)
    try:
        return float(raw)
    except (ValueError, TypeError):
        return float(flag.default)


def get_bool(name: str) -> bool:  # never-raises (registered names)
    """Default-False flags are opt-in (== '1'); default-True flags are
    opt-out (!= '0') — the two bool conventions the tree already uses,
    selected by the registered default."""
    flag = REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return bool(flag.default)
    return raw != "0" if flag.default else raw == "1"


def get_enum(name: str) -> str:  # never-raises (registered names)
    """Lower-cased value, degrading to the registered default when the
    value is not a registered choice.  Resolvers that must REJECT an
    unknown mode (resolve_pack, mesh_schedule) keep their own
    validating parse; this accessor is for callers that want the
    degrade-to-default discipline."""
    flag = REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return str(flag.default)
    val = raw.lower()
    return val if val in flag.choices else str(flag.default)


def get_str(name: str) -> str:  # never-raises (registered names)
    flag = REGISTRY[name]
    raw = os.environ.get(name)
    return str(flag.default) if raw is None else raw


def _render_default(flag: Flag) -> str:
    if flag.kind == "bool":
        return "on" if flag.default else "off"
    if flag.name == "CYCLONUS_SLAB_MAX_BYTES":
        return "6 GiB"
    if flag.default == "":
        return "(unset)"
    return str(flag.default)


def markdown_table(owner: Optional[str] = None) -> str:
    """The README env-var table, generated so it cannot drift from the
    registry (tests/test_envflags.py diffs README against this)."""
    rows = [f for f in _FLAGS if owner is None or f.owner == owner]
    out = ["| Variable | Type | Default | Subsystem | Meaning |",
           "| --- | --- | --- | --- | --- |"]
    for f in rows:
        out.append(
            f"| `{f.name}` | {f.kind} | {_render_default(f)} | "
            f"{f.owner} | {f.description} |"
        )
    return "\n".join(out)
