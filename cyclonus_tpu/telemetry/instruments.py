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
import time
from typing import Any, Dict, Iterator, Optional

from . import recorder, spans, state
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
MESH_RING_STEP_SECONDS = REGISTRY.gauge(
    "cyclonus_tpu_mesh_ring_step_seconds",
    "Per-hop seconds of the last pipelined ring-counts eval "
    "(pipelined eval seconds / device count): the overlapped ICI-hop "
    "budget.",
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
ENGINE_PROGRAMS_BUILT = REGISTRY.counter(
    "cyclonus_tpu_engine_programs_built_total",
    "Per-engine counts-program families built (api._build_counts_jits).",
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
        flight.data["seconds"] = round(dt, 6)
        flight.data["outcome"] = outcome
        recorder.record(**flight.data)
