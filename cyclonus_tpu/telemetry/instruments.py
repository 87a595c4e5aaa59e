"""The named instruments of the TPU verdict engine.

One place declares every `cyclonus_tpu_*` metric (naming scheme:
docs/DESIGN.md "Telemetry") so the exposition schema is stable and the
engine call sites stay one-liners.  Unlabeled gauges/counters exist from
import, so a scrape of a fresh process already shows the full schema.

`eval_flight` is the per-evaluation wrapper the engine hot paths use: it
numbers the evaluation, opens its `engine.eval` span (every span inside
carries the number as `eval_id`), times it, feeds the latency histogram
/ throughput gauges, and appends a flight-recorder entry (including on
crash, with the exception as the outcome).  Cost per eval when enabled:
one span, a handful of locked dict updates, one ring append — host-side
only, never a device sync (pinned by the jaxlint test).
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Dict, Iterable, Iterator, Optional

from . import events, recorder, spans, state
from .metrics import REGISTRY

# --- evaluation throughput / latency ------------------------------------

EVAL_CELLS_PER_SEC = REGISTRY.gauge(
    "cyclonus_tpu_eval_cells_per_sec",
    "Most recent synchronous evaluation rate (grid cells per second).",
)
EVAL_PIPELINED_CELLS_PER_SEC = REGISTRY.gauge(
    "cyclonus_tpu_eval_pipelined_cells_per_sec",
    "Device-side steady-state rate with the per-dispatch round trip "
    "amortized over in-flight evaluations (counts_pipelined_eval_s).",
)
EVAL_LATENCY = REGISTRY.histogram(
    "cyclonus_tpu_eval_latency_seconds",
    "Wall-clock per engine evaluation, by kernel path.",
    labelnames=("path",),
)
EVAL_DISPATCHES = REGISTRY.counter(
    "cyclonus_tpu_eval_dispatches_total",
    "Engine evaluations dispatched, by kernel path.",
    labelnames=("path",),
)

# --- HBM watermarks ------------------------------------------------------

SLAB_HBM_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_slab_hbm_bytes",
    "Slab-kernel HBM bytes: planned at slab-plan time (q=2 budget "
    "point), updated to the actual pinned operand bytes when cached.",
)
SLAB_HBM_BUDGET_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_slab_hbm_budget_bytes",
    "CYCLONUS_SLAB_MAX_BYTES budget the slab plan is gated against.",
)
PRE_CACHE_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_pre_cache_bytes",
    "Device-resident precompute bytes currently pinned (0 = no pin).",
)
PRE_CACHE_BUDGET_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_pre_cache_budget_bytes",
    "Precompute pin ceiling (engine/api.py _PRE_CACHE_MAX_BYTES).",
)
STATIC_PRE_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_static_pre_bytes",
    "Bytes of the case-independent half of the dense counts precompute "
    "kept on the device (tiled._precompute_static; 0 = none resident).",
)
MESH_PEER_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_mesh_peer_buffer_bytes",
    "Per-device peer-side working-set bytes of the last sharded grid "
    "eval, by exchange schedule (ring = resident shard bundle + one "
    "in-flight ppermute block; allgather = the full replicated peer "
    "copy).  The scale-out acceptance asserts ring < allgather at 8 "
    "devices (engine/sharded.py peer_buffer_bytes).",
    labelnames=("schedule",),
)
MESH_DISPATCH_BYTES = REGISTRY.counter(
    "cyclonus_tpu_mesh_dispatch_bytes_total",
    "Host bytes the sharded grid program's launches have sent to the "
    "mesh, by route (classes / ring / allgather): every host array "
    "among a call's operands, an array sharded over the mesh once and a "
    "replicated one times the chips (the `host_bytes` of the "
    "`engine.dispatch_sharded` span, summed).",
    labelnames=("route",),
)
TIER_EVALS = REGISTRY.counter(
    "cyclonus_tpu_tier_evals_total",
    "Evaluations of a tiered engine (AdminNetworkPolicy / "
    "BaselineAdminNetworkPolicy over NetworkPolicy), by the epilogue that "
    "resolved the tiers (engine/tiled.py tier_attrs): fused (the packed "
    "Pallas kernel's) or xla (the min-reduction over packed rule words "
    "every other route runs).  "
    "The `tier_kernel` of the evaluation's `engine.eval` span, counted.",
    labelnames=("kernel",),
)
COUNTS_TILE = REGISTRY.counter(
    "cyclonus_tpu_counts_tile_total",
    "Counts evaluations that ran the XLA counts tile body, by its form "
    "(engine/tiled.py counts_tile_form): bqn (both directions in one "
    "[block, Q, pods] layout) or bnq (a tiered set: the tables body "
    "with its tier epilogue).  The `tile` of the evaluation's "
    "`engine.eval` span, counted.",
    labelnames=("form",),
)
CLASS_CONTRACT = REGISTRY.counter(
    "cyclonus_tpu_class_contract_total",
    "Class counts evaluations by the form of their tile contraction "
    "(engine/tiled.py class_counts_pack): packed (the target words on the "
    "VPU, in the fused Pallas kernel or the XLA tile over a tiered set) or "
    "mxu (the XLA tile's unpacked target axis on the MXU).  The "
    "`class_contract` of the evaluation's `engine.eval` span, counted.",
    labelnames=("form",),
)
TARGET_ALLOWS = REGISTRY.counter(
    "cyclonus_tpu_target_allows_total",
    "Target-allows contractions (kernel.target_allows_q) of evaluations "
    "whose program traces it, one a direction, by form "
    "(engine/kernel.py tallow_attrs): band (each tile of targets against "
    "its own peer rows, where the direction carries band_rows) or dense "
    "(the one-hot over every peer row).  The `tallow` of the "
    "evaluation's `engine.eval` span, counted.",
    labelnames=("form",),
)
GRID_HOST_BUFFER = REGISTRY.counter(
    "cyclonus_tpu_grid_host_buffer_total",
    "Host buffers handed to sharded tables' readbacks "
    "(engine/api.py _HostBuffers), one a table: recycled (the memory of "
    "a table nothing views any more, its pages mapped already) or fresh "
    "(np.empty: every page is faulted in while the shards are laid).",
    labelnames=("outcome",),
)

DEVICE_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_device_bytes",
    "Device memory of the fullest local device as the runtime reports it "
    "(memory_stats: stat=in_use now, stat=peak since process start), "
    "refreshed at scrape time.  Absent until the process has started a "
    "backend, and on backends that report no memory_stats (the CPU).",
    labelnames=("stat",),
)


class _DeviceMemory:
    """Scrape-time refresher of DEVICE_BYTES (the collector registry
    holds bound methods by weakref, hence an object).  It never starts a
    backend: a scrape of a process that has not touched the device must
    not be the thing that claims the chip."""

    def refresh(self) -> None:
        jax = sys.modules.get("jax")
        if jax is None:
            return
        from jax._src import xla_bridge

        started = getattr(xla_bridge, "backends_are_initialized", None)
        if started is None or not started():
            return
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        for stat, key in (("in_use", "bytes_in_use"), ("peak", "peak_bytes_in_use")):
            values = [s[key] for s in stats if key in s]
            if values:
                DEVICE_BYTES.set(max(values), stat=stat)


_DEVICE_MEMORY = _DeviceMemory()
REGISTRY.register_collector(_DEVICE_MEMORY.refresh)

# --- equivalence-class grid compression ----------------------------------

CLASS_PODS = REGISTRY.gauge(
    "cyclonus_tpu_class_pods",
    "Grid compression: real pod count of the engine whose classes were "
    "last computed.",
)
CLASS_COUNT = REGISTRY.gauge(
    "cyclonus_tpu_class_count",
    "Grid compression: label-equivalence class count (the compressed "
    "pod-axis length).",
)
CLASS_RATIO = REGISTRY.gauge(
    "cyclonus_tpu_class_compression_ratio",
    "Grid compression: pods / classes (1.0 = no reduction; the grid "
    "work shrinks by ratio^2).",
)
CLASS_AUX_BYTES = REGISTRY.gauge(
    "cyclonus_tpu_class_aux_bytes",
    "Grid compression: device bytes of the gather/index tensors (class "
    "map, weights, compressed tensor buffer) counted against the "
    "CYCLONUS_SLAB_MAX_BYTES budget.",
)
CLASS_ROUTE = REGISTRY.counter(
    "cyclonus_tpu_class_route_total",
    "Grid compression: every engine's decision at construction, by "
    "outcome: kept (a class state was built and kept), no_reduction "
    "(auto: classes > 0.9 x pods, the classes and their CIDR space are "
    "dropped), below_floor (auto under CYCLONUS_CLASS_MIN_PODS, or no "
    "pods), no_selector_pass (auto: compaction's budget skipped the "
    "host selector pass), off (CYCLONUS_CLASS_COMPRESS=0, or past the "
    "2^24 pods exact counts allow).",
    labelnames=("outcome",),
)
SELECTOR_PASS = REGISTRY.counter(
    "cyclonus_tpu_selector_pass_total",
    "The host selector pass over the distinct pod label rows "
    "(engine/selectors.py), one an engine: ran (for dead-target compaction "
    "or a forced class state; span engine.selector_pass says its form and "
    "rows) or skipped (compaction off or past the pass's work budget, and "
    "no class state forced).",
    labelnames=("outcome",),
)
CLASS_EVALS = REGISTRY.counter(
    "cyclonus_tpu_class_evals_total",
    "Evaluations served by the compressed class path, by path "
    "(grid/counts/sharded).",
    labelnames=("path",),
)

# --- cache hit/miss counters --------------------------------------------

PRE_CACHE_HITS = REGISTRY.counter(
    "cyclonus_tpu_pre_cache_hits_total",
    "Counts evaluations served from the pinned device-resident "
    "precompute (steady state: only the counts kernel runs).",
)
PRE_CACHE_MISSES = REGISTRY.counter(
    "cyclonus_tpu_pre_cache_misses_total",
    "Counts evaluations that could not use a pinned precompute (cold "
    "call, case-set change, or cache declined/evicted).",
)
STATIC_PRE = REGISTRY.counter(
    "cyclonus_tpu_static_pre_total",
    "Dense counts requests that no pinned precompute serves, by what "
    "the resident static half did for them: built (computed and kept), "
    "hit (the request ran only what its port cases decide), declined "
    "(over the pins' byte ceiling or CYCLONUS_PRE_CACHE=0: the fused "
    "program ran).",
    labelnames=("outcome",),
)
SLAB_OPS_CACHE_HITS = REGISTRY.counter(
    "cyclonus_tpu_slab_ops_cache_hits_total",
    "Slab dispatches served from cached gathered operands "
    "(engine/api.py _slab_ops_for).",
)
SLAB_OPS_CACHE_MISSES = REGISTRY.counter(
    "cyclonus_tpu_slab_ops_cache_misses_total",
    "Slab operand builds (cache cold or evicted with the precompute).",
)
KERNEL_TRACES = REGISTRY.counter(
    "cyclonus_tpu_kernel_traces_total",
    "jit traces of the verdict kernels, by kernel: each trace is a "
    "compile-cache miss at the program level (dispatches - traces = "
    "hits); the persistent XLA cache may still serve the binary.",
    labelnames=("kernel",),
)

# --- autotune ------------------------------------------------------------

AUTOTUNE_OUTCOMES = REGISTRY.counter(
    "cyclonus_tpu_autotune_outcomes_total",
    "Slab-vs-default autotune outcomes: winner (slab/default) or "
    "candidate containment (error/timeout).",
    labelnames=("outcome",),
)
AUTOTUNE_SEARCHES = REGISTRY.counter(
    "cyclonus_tpu_autotune_searches_total",
    "Full candidate searches actually TIMED (compile + min-of-N "
    "rounds).  A process that adopts a persisted winner never "
    "increments this — the restart-adoption gate asserts exactly that.",
)
AUTOTUNE_CACHE = REGISTRY.counter(
    "cyclonus_tpu_autotune_cache_total",
    "Persisted autotune-cache lookups by outcome: hit (winner "
    "adopted), miss (no/invalid entry -> fresh search), store "
    "(winner persisted), disabled.",
    labelnames=("outcome",),
)

# --- persistent AOT executable cache -------------------------------------

AOT_CACHE = REGISTRY.counter(
    "cyclonus_tpu_aot_cache_total",
    "Persistent AOT executable-cache events by outcome: hit (serialized "
    "executable adopted from disk — zero trace, zero compile), shared "
    "(the process had loaded it already: no file read), miss (no entry "
    "-> fresh lower+compile), store (executable persisted), corrupt/stale "
    "(entry rejected -> fresh compile), unserializable (store refused "
    "by the runtime), fallback (wrapper pinned to plain jit).",
    labelnames=("outcome",),
)
AOT_COMPILES = REGISTRY.counter(
    "cyclonus_tpu_aot_compiles_total",
    "Fresh lower+compile passes paid by AOT-wrapped programs.  A "
    "restarted process adopting a warm cache keeps this flat — the "
    "zero-recompile restart contract tests/test_aot_cache.py asserts.",
)

# --- start-up: what a process did before its first verdict ---------------

JAX_COMPILE_SECONDS = REGISTRY.counter(
    "cyclonus_tpu_jax_compile_seconds_total",
    "Seconds JAX spent making programs, by stage as jax.monitoring "
    "reports it: trace (jaxpr), lower (to MLIR), backend_compile (XLA, "
    "or the read of its persistent cache).  Every jit of the process, "
    "AOT-wrapped or not.",
    labelnames=("stage",),
)
JAX_COMPILES = REGISTRY.counter(
    "cyclonus_tpu_jax_compiles_total",
    "Backend compiles by what JAX's persistent compilation cache did: "
    "hit (the executable was read from it), miss (compiled and written), "
    "uncached (compiled; too quick to keep, or no cache configured).",
    labelnames=("cache",),
)
STARTUP_SECONDS = REGISTRY.gauge(
    "cyclonus_tpu_startup_seconds",
    "The main thread's time between process start and the close of the "
    "start-up record (telemetry/events.py), by phase: import (JAX and "
    "Pallas), backend (the runtime's start), matcher (matcher.build), "
    "engine (the engine's constructor), program (compiles, loads, the "
    "autotune), first_eval (the first evaluation).  An instant counts "
    "once, for the innermost of these that was open.",
    labelnames=("phase",),
)
TIME_TO_FIRST_VERDICT = REGISTRY.gauge(
    "cyclonus_tpu_time_to_first_verdict_seconds",
    "Process start to the end of the first engine evaluation that "
    "handed its caller a result (0: none yet).",
)

WORKER_RETRIES = REGISTRY.counter(
    "cyclonus_tpu_worker_retries_total",
    "Driver-side worker batch retries (worker/client.py): each one is "
    "a batch re-issued after a timeout or exec failure, with jittered "
    "backoff — a worker that dies mid-batch costs retries, never a "
    "wedged driver.",
)
CHAOS_INJECTIONS = REGISTRY.counter(
    "cyclonus_tpu_chaos_injections_total",
    "Faults injected by the chaos layer (cyclonus_tpu/chaos), by "
    "injection point.  Nonzero only when CYCLONUS_CHAOS is armed.",
    labelnames=("point",),
)

# --- verdict service (cyclonus_tpu/serve) --------------------------------

SERVE_EPOCH = REGISTRY.gauge(
    "cyclonus_tpu_serve_epoch",
    "Verdict service: applied delta-batch generation of the live engine.",
)
SERVE_PENDING = REGISTRY.gauge(
    "cyclonus_tpu_serve_pending_deltas",
    "Verdict service: deltas submitted but not yet applied.",
)
SERVE_STALENESS = REGISTRY.gauge(
    "cyclonus_tpu_serve_staleness_seconds",
    "Verdict service: age of the oldest pending delta (0 = engine is "
    "current).",
)
SERVE_DELTAS = REGISTRY.counter(
    "cyclonus_tpu_serve_deltas_total",
    "Verdict service: deltas submitted.",
)
SERVE_APPLIES = REGISTRY.counter(
    "cyclonus_tpu_serve_applies_total",
    "Verdict service: apply batches, by mode (incremental = row/slab "
    "patch of the live buffer; class_rebuild = patch + class-state "
    "rebuild; full = re-encode + re-device_put; noop = state already "
    "current).",
    labelnames=("mode",),
)
SERVE_FALLBACKS = REGISTRY.counter(
    "cyclonus_tpu_serve_fallbacks_total",
    "Verdict service: incremental applies that fell back to a full "
    "rebuild, by reason.",
    labelnames=("reason",),
)
SERVE_REJECTED = REGISTRY.counter(
    "cyclonus_tpu_serve_rejected_deltas_total",
    "Verdict service: malformed deltas rejected at validation (reported "
    "back on the wire, never applied) — distinct from fallbacks, which "
    "count rebuilds of VALID batches.",
)
SERVE_PATCH_BYTES = REGISTRY.counter(
    "cyclonus_tpu_serve_patch_bytes_total",
    "Verdict service: bytes scatter-patched into live device buffers "
    "(the incremental path's entire host->device traffic).",
)
SERVE_HEADROOM_SAVES = REGISTRY.counter(
    "cyclonus_tpu_serve_headroom_saves_total",
    "Verdict service: policy patches that crossed a rule-slab bucket "
    "boundary but stayed on the incremental path because the serve "
    "engine pre-reserved slab headroom (CYCLONUS_SERVE_HEADROOM) — "
    "each one is a full rebuild avoided.",
)
SERVE_QUERIES = REGISTRY.counter(
    "cyclonus_tpu_serve_queries_total",
    "Verdict service: flow queries answered.",
)
SERVE_DEGRADED = REGISTRY.counter(
    "cyclonus_tpu_serve_degraded_queries_total",
    "Verdict service: queries answered from the scalar-oracle "
    "authoritative-state fallback while the engine was still warming "
    "(graceful degradation — correct verdicts at host speed, counted "
    "so a fleet can see which replicas served degraded and for how "
    "many flows).",
)
SERVE_QUERY_LATENCY = REGISTRY.histogram(
    "cyclonus_tpu_serve_query_latency_seconds",
    "Verdict service: per-flow query latency, batch-amortized (the "
    "p50/p99 surfaced by /state).",
)
SERVE_APPLY_SECONDS = REGISTRY.histogram(
    "cyclonus_tpu_serve_apply_seconds",
    "Verdict service: delta-apply spans, by mode.",
    labelnames=("mode",),
)
SERVE_GAUGE_REFRESH_SKIPPED = REGISTRY.counter(
    "cyclonus_tpu_serve_gauge_refresh_skipped_total",
    "Verdict service: scrape-time gauge refreshes skipped because the "
    "service lock was contended past the try-lock timeout — nonzero "
    "means /metrics pending/staleness values are themselves stale.",
)

# --- SLO engine (cyclonus_tpu/slo) ----------------------------------------

SLO_BURN_RATE = REGISTRY.gauge(
    "cyclonus_tpu_slo_burn_rate",
    "SLO engine: error-budget burn rate per objective and window "
    "(1.0 = budget spent exactly as fast as it accrues).",
    labelnames=("objective", "window"),
)
SLO_BUDGET_REMAINING = REGISTRY.gauge(
    "cyclonus_tpu_slo_budget_remaining",
    "SLO engine: fraction of the slow-window error budget left per "
    "objective, in [0, 1] (0 = exhausted).",
    labelnames=("objective",),
)
SLO_STATE = REGISTRY.gauge(
    "cyclonus_tpu_slo_enforcement_state",
    "SLO engine: enforcement state per objective (0 ok / 1 burning / "
    "2 exhausted).",
    labelnames=("objective",),
)
SLO_BREACHES = REGISTRY.counter(
    "cyclonus_tpu_slo_breaches_total",
    "SLO engine: budget-exhaustion transitions (each one dumps the "
    "flight recorder with reason slo-breach:<objective>).",
    labelnames=("objective",),
)
SLO_SHED = REGISTRY.counter(
    "cyclonus_tpu_slo_shed_queries_total",
    "SLO engine: flow queries refused with a typed Shed verdict while "
    "the query_p99 budget was exhausted (never a wrong verdict — a "
    "shed is distinguishable from allow/deny).",
)
SLO_ADMISSION_REJECTS = REGISTRY.counter(
    "cyclonus_tpu_slo_admission_rejects_total",
    "SLO engine: delta batches refused at submit() by freshness-budget "
    "admission control.",
)

# --- audit plane (cyclonus_tpu/audit) ------------------------------------

AUDIT_CHECKED = REGISTRY.counter(
    "cyclonus_tpu_audit_checked_total",
    "Audit plane: sampled verdicts re-evaluated against the scalar "
    "TieredPolicy oracle on the query-epoch snapshot.",
)
AUDIT_DIVERGED = REGISTRY.counter(
    "cyclonus_tpu_audit_diverged_total",
    "Audit plane: shadow-oracle checks whose allow bits disagreed with "
    "the served verdict (each one dumps an audit-divergence bundle and "
    "burns verdict_integrity).",
)
AUDIT_CHECK_LATENCY = REGISTRY.histogram(
    "cyclonus_tpu_audit_check_latency_seconds",
    "Audit plane: per-check shadow-oracle evaluation latency (host-"
    "side, off the query path).",
)
AUDIT_QUEUE_DEPTH = REGISTRY.gauge(
    "cyclonus_tpu_audit_queue_depth",
    "Audit plane: sampled checks waiting in the bounded audit queue.",
)
AUDIT_DROPPED = REGISTRY.counter(
    "cyclonus_tpu_audit_dropped_total",
    "Audit plane: sampled checks dropped without evaluation (reason="
    "overflow: queue at CYCLONUS_AUDIT_QUEUE; reason=epoch_evicted: "
    "the query's epoch snapshot aged out of the ring).",
    labelnames=("reason",),
)
AUDIT_DIGEST_SECONDS = REGISTRY.gauge(
    "cyclonus_tpu_audit_digest_seconds",
    "Audit plane: wall-clock seconds the latest epoch state digest "
    "took to compute (background thread, never the query path).",
)
AUDIT_DIGEST_EPOCH = REGISTRY.gauge(
    "cyclonus_tpu_audit_digest_epoch",
    "Audit plane: newest epoch with a committed state digest.",
)

# --- real-probe latency --------------------------------------------------

PROBE_LATENCY = REGISTRY.histogram(
    "cyclonus_tpu_probe_latency_seconds",
    "Per-probe real-connection latency (worker/model.py Result."
    "latency_ms), observed in the worker and driver-side from batch "
    "results.  outcome=error samples include retry+timeout time — keep "
    "them out of connection-latency percentiles.",
    labelnames=("source", "outcome"),
)

# --- verdict volume ------------------------------------------------------

VERDICTS = REGISTRY.counter(
    "cyclonus_tpu_verdicts_total",
    "Simulated job verdicts scattered to callers, by engine.",
    labelnames=("engine",),
)


# start-up phase -> the spans it is made of (docs/DESIGN.md "Start-up
# record"); of engine.eval only the process's first counts.  The
# benchmark's eight `setup.*` phases (benchmarks/startup_spans.py PHASES)
# cut the same record finer, by the same exclusive_seconds: `engine` here
# is its setup.engine_s + setup.classes_s (the class spans lie inside
# engine.new), `first_eval` the first evaluation of its setup.warmup_s
# (every warm-up request, with its fetches and waits), and what is
# outside every span is no phase here.
STARTUP_PHASES = {
    "import": ("startup.import",),
    "backend": ("startup.backend",),
    "matcher": ("matcher.build",),
    "engine": ("engine.new",),
    "program": (
        "engine.program", "engine.static_pre", "engine.autotune",
        "jax.compile",
    ),
    "first_eval": ("engine.eval",),
}


def exclusive_seconds(
    spans_of_thread: Iterable[Dict[str, Any]],
    phase_of: Dict[str, str],
    lo: float = float("-inf"),
    hi: float = float("inf"),
) -> Dict[str, float]:
    """One thread's time line between `lo` and `hi` shared out among
    phases: an instant goes to the innermost open span that `phase_of`
    (span name -> phase) names, to no phase where none is open, and is
    counted once however many spans cover it.  A phase whose spans all
    lie outside [lo, hi], or have no length, is there with 0.0.  The
    one attribution of the start-up record: the gauges below and the
    benchmark's `setup.*` readers (benchmarks/startup_spans.py, with a
    phase map of its own) both go through it."""
    marks = sorted(
        (
            (
                max(sp["start_s"], lo),
                min(sp["start_s"] + sp["dur_s"], hi),
                phase_of[sp["name"]],
            )
            for sp in spans_of_thread if sp["name"] in phase_of
        ),
        # of two that start together the longer is the outer one
        key=lambda m: (m[0], -m[1]),
    )
    out: Dict[str, float] = {}
    open_: list = []  # (end, phase), innermost last
    cursor = lo  # the time line is shared out up to here

    def run_to(t: float) -> None:
        nonlocal cursor
        while open_ and open_[-1][0] <= t:
            end, phase = open_.pop()
            if end > cursor:
                out[phase] = out.get(phase, 0.0) + end - cursor
                cursor = end
        if open_ and t > cursor:
            phase = open_[-1][1]
            out[phase] = out.get(phase, 0.0) + t - cursor
        cursor = max(cursor, t)

    for start, end, phase in marks:
        if end <= start:
            out.setdefault(phase, 0.0)
            continue
        run_to(start)
        open_.append((end, phase))
    while open_:
        run_to(open_[-1][0])
    return out


def startup_phases() -> Optional[Dict[str, float]]:
    """STARTUP_PHASES' seconds as the start-up record has them now, every
    phase present; None where the ring no longer holds the whole record."""
    found = events.startup_spans()
    if found["wrapped"]:
        return None
    main = threading.main_thread().ident
    mine, seen_eval = [], False
    for sp in found["spans"]:
        if sp["thread"] != main:
            continue
        if sp["name"] == "engine.eval":
            if seen_eval:
                continue
            seen_eval = True
        mine.append(sp)
    phase_of = {n: ph for ph, names in STARTUP_PHASES.items() for n in names}
    got = exclusive_seconds(mine, phase_of)
    return {phase: got.get(phase, 0.0) for phase in STARTUP_PHASES}


def render_startup() -> str:
    """The six phases and the time to the first verdict, a line each
    (`generate --phase-stats` prints them above its table)."""
    phases = startup_phases() or {}
    rows = [f"startup.{ph:<16}{s:>10.4f}s" for ph, s in phases.items()]
    rows.append(
        f"{'time_to_first_verdict':<24}{TIME_TO_FIRST_VERDICT.value():>10.4f}s"
    )
    return "\n".join(rows)


class _Startup:
    """Keeps STARTUP_SECONDS true to the record: refreshed at every
    scrape while the record is open, set for good when it closes
    (events.close_startup calls refresh_startup; the ring may drop the
    record later)."""

    def __init__(self) -> None:
        self.final = False

    def refresh(self) -> None:
        if self.final:
            return
        self.final = not events.STARTUP
        for phase, seconds in (startup_phases() or {}).items():
            STARTUP_SECONDS.set(seconds, phase=phase)


_STARTUP = _Startup()
REGISTRY.register_collector(_STARTUP.refresh)
refresh_startup = _STARTUP.refresh

_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class _JaxCompiles:
    """The one listener on jax.monitoring: each stage of each compile
    feeds JAX_COMPILE_SECONDS and becomes a completed `jax.compile` span
    (attrs `stage`, `fun`; `cache` on a backend compile), so that a
    compile which goes through a plain `jit`, and so through no
    `engine.program` span, still lies on the timeline.

    JAX says when a stage starts (a scalar) and when it has ended (a
    duration).  Stages nest: tracing one jit traces every jit it calls,
    hundreds of them a program.  A trace or a lowering INSIDE another
    stage is in that stage's seconds already and is neither counted nor
    recorded again; a backend compile always is.  JAX reports the
    persistent cache's hit or miss as an event of its own inside the
    backend compile it belongs to: kept per thread until that ends."""

    def __init__(self) -> None:
        self.watching = False
        self._tls = threading.local()

    def on_start(self, event: str, value: float, **kw: Any) -> None:
        if event in _JAX_STAGES:
            self._tls.depth = getattr(self._tls, "depth", 0) + 1

    def on_event(self, event: str, **kw: Any) -> None:
        outcome = _JAX_CACHE_EVENTS.get(event)
        if outcome is not None:
            self._tls.cache = outcome

    def on_duration(self, event: str, duration: float, **kw: Any) -> None:
        stage = _JAX_STAGES.get(event)
        if stage is None:
            return
        depth = getattr(self._tls, "depth", 1)
        self._tls.depth = max(depth - 1, 0)
        attrs = {"stage": stage, "fun": str(kw.get("fun_name", ""))[:64]}
        if stage == "backend_compile":
            attrs["cache"] = getattr(self._tls, "cache", None) or "uncached"
            self._tls.cache = None
            JAX_COMPILES.inc(cache=attrs["cache"])
        elif depth > 1:
            return
        JAX_COMPILE_SECONDS.inc(max(duration, 0.0), stage=stage)
        spans.completed("jax.compile", duration, **attrs)


_JAX_COMPILES = _JaxCompiles()


def watch_jax_compiles() -> None:
    """Register the compile listener, once a process (engine.first_import
    calls this wherever the program may be first to import JAX)."""
    if _JAX_COMPILES.watching:
        return
    _JAX_COMPILES.watching = True
    from jax import monitoring

    monitoring.register_scalar_listener(_JAX_COMPILES.on_start)
    monitoring.register_event_listener(_JAX_COMPILES.on_event)
    monitoring.register_event_duration_secs_listener(_JAX_COMPILES.on_duration)


_first_verdict_at: Optional[float] = None


def _first_verdict() -> None:
    global _first_verdict_at
    _first_verdict_at = time.time()
    TIME_TO_FIRST_VERDICT.set(_first_verdict_at - events.T0_EPOCH)


class _NullFlight:
    __slots__ = ()
    eval_id = None

    def set(self, **kw: Any) -> "_NullFlight":
        return self


_NULL_FLIGHT = _NullFlight()


class Flight:
    """Mutable per-evaluation record; `set(cells=..., **attrs)` enriches
    the flight entry (and, when cells is set, the throughput gauge).
    `eval_id` is the evaluation's number: the entry's `seq` and the
    `eval_id` of every span recorded inside the flight."""

    __slots__ = ("data", "eval_id")

    def __init__(self, data: Dict[str, Any], eval_id: int):
        self.data = data
        self.eval_id = eval_id

    def set(self, **kw: Any) -> "Flight":
        self.data.update(kw)
        return self


@contextlib.contextmanager
def eval_flight(path: str, n_pods: int, q: int, **attrs: Any) -> Iterator[Flight]:
    """Wrap one engine evaluation: its number, its `engine.eval` span
    (attr `route` = the PathSpec name; `mode` where the route's dispatch
    says which of its programs ran; `schedule` and `classes` on the mesh
    grid route), histogram + dispatch counter +
    flight record, outcome 'ok' or the exception repr."""
    if not state.ENABLED:
        yield _NULL_FLIGHT  # type: ignore[misc]
        return
    eval_id = recorder.next_seq()
    flight = Flight(
        {"path": path, "n_pods": n_pods, "q": q, "seq": eval_id, **attrs},
        eval_id,
    )
    outcome = "ok"
    t0 = time.perf_counter()
    try:
        with spans.evaluation(eval_id), spans.span(
            "engine.eval", route=path
        ) as sp:
            yield flight
            # which program of the route ran (resident / fused / split / steady)
            # shows on the span as it does in the flight entry
            if "mode" in flight.data:
                sp.set(mode=flight.data["mode"])
            # and which leaf of the mesh grid route: its exchange, and
            # whether it ran over the class axis
            if "schedule" in flight.data:
                sp.set(
                    schedule=flight.data["schedule"],
                    classes=bool(flight.data.get("classes")),
                )
            # and the layout of the XLA counts tile body it ran
            # (tiled.counts_tile_form), counted a form
            if "tile" in flight.data:
                sp.set(tile=flight.data["tile"])
                COUNTS_TILE.inc(form=flight.data["tile"])
            # and, on the class counts route, the classes, the row-sum
            # program that ran over them, where their selector matches
            # came from and the packed target words
            if "class_kernel" in flight.data:
                sp.set(
                    classes=flight.data.get("classes"),
                    class_kernel=flight.data["class_kernel"],
                    class_sel=flight.data["class_sel"],
                    target_words=flight.data["target_words"],
                )
            # and the form its tile contraction took (tiled.class_counts_pack),
            # counted a form
            if "class_contract" in flight.data:
                sp.set(class_contract=flight.data["class_contract"])
                CLASS_CONTRACT.inc(form=flight.data["class_contract"])
            # and the form of its target-allows contraction a direction
            # and the peer rows a target reads (kernel.tallow_attrs),
            # counted a direction
            if "tallow" in flight.data:
                sp.set(
                    tallow=flight.data["tallow"],
                    tallow_span=flight.data["tallow_span"],
                )
                for form in flight.data["tallow"].split(","):
                    TARGET_ALLOWS.inc(form=form)
            # and, over a tiered set, its tier rule rows and the epilogue
            # that resolved them (tiled.tier_attrs), counted a kernel
            if "tier_kernel" in flight.data:
                sp.set(
                    tiered=1,
                    tier_rows=flight.data["tier_rows"],
                    tier_kernel=flight.data["tier_kernel"],
                )
                if "tier_words" in flight.data:
                    sp.set(tier_words=flight.data["tier_words"])
                TIER_EVALS.inc(kernel=flight.data["tier_kernel"])
            # and what the mesh counts entry decided its route from: one
            # chip's bytes of the replicated precompute and their ceiling
            if "replicated_bytes" in flight.data:
                sp.set(
                    devices=flight.data["devices"],
                    replicated_bytes=flight.data["replicated_bytes"],
                    ceiling_bytes=flight.data["ceiling_bytes"],
                )
    except BaseException as e:
        outcome = f"{type(e).__name__}: {e}"[:300]
        raise
    finally:
        dt = time.perf_counter() - t0
        EVAL_LATENCY.observe(dt, path=path)
        EVAL_DISPATCHES.inc(path=path)
        cells = flight.data.get("cells")
        if outcome == "ok" and cells and dt > 0:
            EVAL_CELLS_PER_SEC.set(cells / dt)
        if outcome == "ok" and _first_verdict_at is None:
            _first_verdict()
        flight.data["seconds"] = round(dt, 6)
        flight.data["outcome"] = outcome
        recorder.record(**flight.data)
