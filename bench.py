#!/usr/bin/env python
"""Benchmark: simulated connectivity cells/sec on a synthetic service-mesh
cluster.  Default = the BASELINE.md north-star: 100k pods x 10k policies,
full 2e10-cell matrix, tiled fused-pallas path, single chip.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "cells/sec", "vs_baseline": N}

vs_baseline is measured against the north-star rate from BASELINE.json
(100k-pod x 10k-policy full matrix in <10s => 1e9 cells/sec).

The reference publishes no numbers (BASELINE.md); its simulated engine is a
sequential Go loop (jobrunner.go:68-74).  A scalar-oracle spot check on a
random sample of cells guards against benchmarking a wrong kernel.

Env overrides: BENCH_PODS, BENCH_POLICIES, BENCH_SAMPLE (oracle spot-check
size), BENCH_TRACE_DIR (= the `--trace-dir` option: wrap the eval phase
in jax.profiler.trace and write the TensorBoard/XProf capture there; the
JSON line's detail.trace block records whether an artifact was written),
BENCH_TILED (default 1: tiled counts mode, scales past HBM;
0 = full-grid tables mode, needs BENCH_PODS <~ 25000 on one chip),
BENCH_COUNTS_BACKEND (pallas | xla | sharded — mesh-parallel tile loop),
BENCH_BLOCK (xla tile height), BENCH_SHARDED=1 (full-grid mode over a
device mesh), BENCH_DEADLINE_S (total watchdog backstop, default 1500,
0=off), BENCH_STALL_S (per-phase stall bound, default 300 — trips fast
on a wedged compile; set 0 for huge cold one-phase compiles like the 2M
envelope), BENCH_MESH_PODS / BENCH_MESH_POLICIES (the detail.mesh leg's
problem size; BENCH_MESH=0 skips — the leg runs the OVERLAPPED ring
path at 1/2/4/8 devices of the default backend, as many as it has,
recording cells_per_sec_per_chip + ring_step_s + overlap_efficiency per
row plus the ring-vs-allgather grid parity and peer-buffer watermarks),
BENCH_MEGA (auto: the 1M-pod equivalence-class compression case runs on
TPU only; 1/0 force/skip), BENCH_MEGA_PODS / BENCH_MEGA_POLICIES /
BENCH_MEGA_NS (its problem shape — few namespaces by design: the case
models the "thousands of pods, a handful of label shapes" regime the
compression exists for; detail.mega_class.class_compression records
pods/classes/ratio/gather_s).  Every line also records the HEADLINE
engine's detail.class_compression (CYCLONUS_CLASS_COMPRESS governs the
engine-side path selection), and detail.tiers — the precedence-tier leg
(BENCH_TIERS=0 skips, BENCH_TIERS_PODS / BENCH_TIERS_POLICIES /
BENCH_TIERS_SAMPLE size it): a deterministic ANP/BANP lattice over a
synthetic cluster, recording {active, anp_count, rule_rows, banp,
resolve_s} plus leg timings, with tiered-oracle spot parity enforced.

The bench attaches to the default backend and exits nonzero when that
is not a TPU, unless the caller pinned the CPU (JAX_PLATFORMS=cpu or
jax.config jax_platforms) as the mechanics tests do: a CPU line is never
a fallback.  On any failure — watchdog expiry, backend attach error, or
crash — the bench still prints one parseable JSON line with an "error"
field, a "failure_class" (ok | backend_init | watchdog_stall | engine)
and the per-phase wall-clock history, then exits nonzero.  Successful
lines carry failure_class "ok", the same phase history, and
detail.device (platform, device_kind, count as JAX reports them).
"""

import json
import os
import random
import sys
import time

import numpy as np

BASELINE_CELLS_PER_SEC = 1e9


def last_json_line(text: str):
    """The bench's output contract is ONE JSON line (possibly preceded
    by table/log lines); return the last parseable one, or None.  The
    single parser for every consumer, so a framing change lands in one
    place."""
    lines = [l for l in text.splitlines() if l.startswith("{")]
    if not lines:
        return None
    return json.loads(lines[-1])

# --- bounded-time failure path -------------------------------------------
# A bench that hangs until its driver kills it leaves no JSON line at
# all, so every hazard has a bound:
#   - a global watchdog (BENCH_DEADLINE_S, 0 disables) that prints an
#     error JSON line with the per-phase wall-clock history and exits 2;
#   - a top-level try/except that converts any crash into an error JSON
#     line before re-raising, so rc != 0 still carries a diagnosis.
_WD = {"phase": "startup", "t0": time.time(), "history": []}


def _enter_phase(name: str) -> None:
    now = time.time()
    _WD["history"].append((_WD["phase"], round(now - _WD["t0"], 3)))
    _WD["phase"] = name
    _WD["t0"] = now


def _phase_history() -> list:
    """The per-phase wall-clock history including the in-flight phase —
    carried by EVERY JSON line (success and failure)."""
    history = _WD["history"] + [
        (_WD["phase"], round(time.time() - _WD["t0"], 3))
    ]
    return [list(h) for h in history]


def _pack_detail(engine=None) -> dict:
    """detail.pack — on EVERY bench line, success and failure (the
    guard tests read it unconditionally).  With
    a live engine: the full pack_stats (dtype plan, packed word depths,
    tuned tile winner, autotune search forensics).  Before an engine
    exists (init failures, watchdog lines): the env-resolved plan alone,
    with winner/autotune null."""
    if engine is not None:
        try:
            return engine.pack_stats()
        except Exception:  # noqa: BLE001 — a reporting helper never kills a line
            pass
    try:
        from cyclonus_tpu.engine.encoding import pack_enabled

        active = pack_enabled()
    except Exception:  # noqa: BLE001
        active = None
    return {
        "active": active,
        "dtype": "packed32" if active else os.environ.get(
            "CYCLONUS_PALLAS_DTYPE", "int8"
        ),
        "words": None,
        "winner": None,
        "autotune": None,
        "cache_path": None,
    }


def _error_json(
    msg: str,
    extra_detail: dict = None,
    failure_class: str = "engine",
) -> str:
    """failure_class says whether this run died attaching the backend
    (backend_init) or inside the measured pipeline (engine /
    watchdog_stall).  Call sites pass what they KNOW; 'engine' is the
    conservative default for an unattributed crash."""
    detail = {"phase_history_s": _phase_history(), "pack": _pack_detail()}
    if extra_detail:
        detail.update(extra_detail)
    return json.dumps(
        {
            "metric": "simulated connectivity cells/sec (FAILED)",
            "value": 0,
            "unit": "cells/sec",
            "vs_baseline": 0.0,
            "error": msg,
            "failure_class": failure_class,
            "detail": detail,
        }
    )


def _trace_detail(trace_dir: str) -> dict:
    """The detail.trace block: did this run capture a device profile,
    and did the profiler actually leave an artifact on disk?  Asserted
    present by tests/test_bench_guard.py so every BENCH line records
    its trace provenance."""
    written = False
    if trace_dir and os.path.isdir(trace_dir):
        written = any(files for _, _, files in os.walk(trace_dir))
    return {"dir": trace_dir or None, "written": written}


def _aot_snapshot() -> dict:
    """AOT executable-cache counters for detail.aot_cache.  The SUCCESS
    path snapshots this at END OF WARMUP, not end of run: the later
    legs (serve churn, parity, mega) build fresh engines that adopt
    entries THIS process just stored, and counting those would mark a
    genuinely cold run cache-bearing.  adopted > 0 is the
    zero-recompile restart proof."""
    from cyclonus_tpu.engine import aot_cache

    return {
        k: v
        for k, v in aot_cache.counters().items()
        if k in ("hits", "misses", "adopted", "stores", "compiles", "dir")
    }


def _key_audit() -> dict:
    """detail.key_audit — the cache-key registry census
    (utils/cachekeys.py): how many cache families registered their key
    components this process.  0 outside the key-mutation harness env —
    the registry strips to a no-op (tests/test_bench_guard.py asserts
    the cyclonus_tpu_cachekey_* instruments are absent too)."""
    from cyclonus_tpu.utils import cachekeys

    return {
        "active": cachekeys.ACTIVE,
        "registered": cachekeys.registered_count(),
    }


def _attach_backend() -> dict:
    """Plain attach to the default backend: detail.device as JAX reports
    it.  A backend that cannot initialise, or one that is not a TPU
    when the caller did not pin the CPU, prints the error line and
    exits 4 — there is no CPU leg to fall back to."""
    import jax

    from cyclonus_tpu.engine import device_identity

    try:
        device = device_identity()
    except Exception as e:  # noqa: BLE001 — boundary: report JAX's own message
        print(
            _error_json(
                f"backend attach failed: {type(e).__name__}: {e}",
                failure_class="backend_init",
            ),
            flush=True,
        )
        sys.exit(4)
    if device["platform"] != "tpu" and jax.config.jax_platforms != "cpu":
        print(
            _error_json(
                f"default backend is {device['platform']!r}, not tpu, and "
                "the caller did not pin JAX_PLATFORMS=cpu",
                extra_detail={"device": device},
                failure_class="backend_init",
            ),
            flush=True,
        )
        sys.exit(4)
    return device


def _start_watchdog(done: "threading.Event", deadline_s: float, stall_s: float):
    """Two triggers: a PER-PHASE stall bound (stall_s — a healthy bench
    advances phases every few seconds to a few minutes, so 300s inside
    one phase means a wedged compile) and a generous total backstop
    (deadline_s), deliberately high so a legitimately cold compile
    cache (the parity compiles + the main program) is never killed by
    its own guard."""
    import threading

    t_start = time.time()
    active = [b / 4 for b in (deadline_s, stall_s) if b > 0]
    poll = max(0.25, min([5.0] + active))

    def run():
        while not done.wait(poll):
            now = time.time()
            phase_age = now - _WD["t0"]
            total = now - t_start
            if stall_s > 0 and phase_age > stall_s:
                msg = (
                    f"watchdog: stalled {phase_age:.0f}s in phase "
                    f"'{_WD['phase']}' (BENCH_STALL_S={stall_s:g})"
                )
            elif deadline_s > 0 and total > deadline_s:
                msg = (
                    f"watchdog: exceeded BENCH_DEADLINE_S={deadline_s:g}s "
                    f"in phase '{_WD['phase']}'"
                )
            else:
                continue
            print(
                _error_json(msg, failure_class="watchdog_stall"), flush=True
            )
            os._exit(2)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def build_synthetic(
    n_pods: int, n_policies: int, rng: random.Random, n_ns: int = None
):
    from cyclonus_tpu.kube.netpol import (
        IntOrString,
        LabelSelector,
        NetworkPolicy,
        NetworkPolicyEgressRule,
        NetworkPolicyIngressRule,
        NetworkPolicyPeer,
        NetworkPolicyPort,
        NetworkPolicySpec,
        IPBlock,
    )

    n_ns = n_ns or max(2, n_pods // 250)
    namespaces = {
        f"ns{i}": {"ns": f"ns{i}", "team": f"team{i % 7}"} for i in range(n_ns)
    }
    pods = []
    for i in range(n_pods):
        ns = f"ns{i % n_ns}"
        labels = {
            "pod": f"p{i % 100}",
            "app": f"app{i % 20}",
            "tier": f"tier{i % 5}",
        }
        ip = f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"
        pods.append((ns, f"pod-{i}", labels, ip))

    policies = []
    for i in range(n_policies):
        ns = f"ns{rng.randrange(n_ns)}"
        target = LabelSelector.make(match_labels={"app": f"app{rng.randrange(20)}"})
        peers = []
        r = rng.random()
        if r < 0.2:
            peers.append(
                NetworkPolicyPeer(
                    ip_block=IPBlock.make(
                        f"10.{rng.randrange(4)}.0.0/16",
                        [f"10.{rng.randrange(4)}.{rng.randrange(8)}.0/24"],
                    )
                )
            )
        else:
            peers.append(
                NetworkPolicyPeer(
                    pod_selector=LabelSelector.make(
                        match_labels={"tier": f"tier{rng.randrange(5)}"}
                    ),
                    namespace_selector=LabelSelector.make(
                        match_labels={"team": f"team{rng.randrange(7)}"}
                    )
                    if rng.random() < 0.5
                    else None,
                )
            )
        ports = [NetworkPolicyPort(protocol="TCP", port=IntOrString(80))]
        if rng.random() < 0.3:
            ports.append(
                NetworkPolicyPort(
                    protocol="UDP", port=IntOrString("serve-81-udp")
                )
            )
        rule_i = NetworkPolicyIngressRule(ports=ports, from_=peers)
        rule_e = NetworkPolicyEgressRule(ports=ports, to=peers)
        types = ["Ingress"] if rng.random() < 0.6 else ["Ingress", "Egress"]
        policies.append(
            NetworkPolicy(
                name=f"bench-{i}",
                namespace=ns,
                spec=NetworkPolicySpec(
                    pod_selector=target,
                    policy_types=types,
                    ingress=[rule_i],
                    egress=[rule_e] if "Egress" in types else [],
                ),
            )
        )
    return pods, namespaces, policies


def spot_check(policy, pods, namespaces, cases, grid, n_samples, rng):
    from cyclonus_tpu.matcher import InternalPeer, Traffic, TrafficPeer

    n = len(pods)
    triples = [
        (rng.randrange(len(cases)), rng.randrange(n), rng.randrange(n))
        for _ in range(n_samples)
    ]
    got = grid.gather(triples)  # one device gather, one tiny transfer
    for (qi, si, di), got_row in zip(triples, got):
        case = cases[qi]
        sns, _, slabels, sip = pods[si]
        dns, _, dlabels, dip = pods[di]
        t = Traffic(
            source=TrafficPeer(
                internal=InternalPeer(slabels, namespaces.get(sns, {}), sns), ip=sip
            ),
            destination=TrafficPeer(
                internal=InternalPeer(dlabels, namespaces.get(dns, {}), dns), ip=dip
            ),
            resolved_port=case.port,
            resolved_port_name=case.port_name,
            protocol=case.protocol,
        )
        r = policy.is_traffic_allowed(t)
        expected = (r.ingress.is_allowed, r.egress.is_allowed, r.is_allowed)
        if tuple(bool(x) for x in got_row) != expected:
            raise AssertionError(
                f"PARITY FAILURE at q={case} s={si} d={di}: "
                f"oracle={expected} engine={tuple(got_row)}"
            )


def spot_check_pairs(engine, policy, pods, namespaces, cases, n_samples, rng):
    """Scale-path parity: point verdicts via the pairs kernel (no N x N
    grid) vs the scalar oracle."""
    from cyclonus_tpu.matcher import InternalPeer, Traffic, TrafficPeer

    n = len(pods)
    pairs = [(rng.randrange(n), rng.randrange(n)) for _ in range(n_samples)]
    got = engine.evaluate_pairs(cases, pairs)  # [K, Q, 3]
    for k, (si, di) in enumerate(pairs):
        for qi, case in enumerate(cases):
            sns, _, slabels, sip = pods[si]
            dns, _, dlabels, dip = pods[di]
            t = Traffic(
                source=TrafficPeer(
                    internal=InternalPeer(slabels, namespaces.get(sns, {}), sns),
                    ip=sip,
                ),
                destination=TrafficPeer(
                    internal=InternalPeer(dlabels, namespaces.get(dns, {}), dns),
                    ip=dip,
                ),
                resolved_port=case.port,
                resolved_port_name=case.port_name,
                protocol=case.protocol,
            )
            r = policy.is_traffic_allowed(t)
            expected = (r.ingress.is_allowed, r.egress.is_allowed, r.is_allowed)
            if tuple(bool(x) for x in got[k, qi]) != expected:
                raise AssertionError(
                    f"PARITY FAILURE at q={case} s={si} d={di}: "
                    f"oracle={expected} engine={tuple(got[k, qi])}"
                )


def run_compiled_parity(rng):
    """Mosaic-compiled pallas parity across bucketed shapes (VERDICT r2
    item 6): the CI suites check the pallas kernels' SEMANTICS in
    interpret mode; only a real-TPU run checks what Mosaic actually
    compiles.  Each case evaluates counts via the compiled pallas path
    and diffs against the independent XLA tile-loop path.  Cases cover
    the single-chunk fast kernel and the general (multi-chunk, nz-skip)
    kernel — via CYCLONUS_COMPACT=0, which leaves thousands of dead
    targets — in both int8 and bf16 operand modes.  Every case uses a
    distinct pod-count BUCKET (_bucket_pods granule, not just a distinct
    count) so each gets a fresh trace even if the counts jit were ever
    shared across engines (the operand dtype env var is read at trace
    time).

    Returns {"cases": N, "ok": bool, "failures": [...]}: a verdict
    mismatch, a compile or run failure of ANY case (the forced-slab one
    included), or a forced-slab case whose plan came out ineligible all
    make ok=False, and the bench raises."""
    import jax

    from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies

    if jax.default_backend() != "tpu":
        return {"cases": 0, "ok": None, "skipped": "not on tpu"}
    cases_spec = [
        # (pods, policies, compact, dtype, slab, pack) — compact=False
        # forces the multi-chunk general kernel (dead targets stay,
        # T > 1024); slab=True forces the per-tile target-slab kernel
        # (eligible at >= 2*SLAB_BS bucketed pods); pack=True compiles
        # the bit-packed word kernel (the production default plan —
        # dense cases pin the CYCLONUS_PACK=0 fallback kernels).  Pod
        # counts use distinct buckets per dtype plan.
        (2048, 300, True, "int8", False, False),
        (2304, 300, True, "bf16", False, False),  # odd count: bucketing pads
        (4096, 1500, False, "int8", False, False),
        (4104, 1500, False, "bf16", False, False),  # -> 5120 bucket
        (6144, 600, True, "int8", False, False),
        (8192, 800, True, "int8", True, False),  # Mosaic-compiles the slab
        (3072, 400, True, "int8", False, True),  # packed word kernel
        (10240, 1500, False, "int8", False, True),  # packed, deep target axis
    ]
    port_cases = [
        PortCase(80, "serve-80-tcp", "TCP"),
        PortCase(81, "serve-81-udp", "UDP"),
    ]
    failures = []
    for pods_n, pols_n, compact, dtype, slab, pack in cases_spec:
        saved = {
            k: os.environ.get(k)
            for k in (
                "CYCLONUS_COMPACT",
                "CYCLONUS_PALLAS_DTYPE",
                "CYCLONUS_PALLAS_SLAB",
                "CYCLONUS_PACK",
            )
        }
        try:
            _enter_phase(f"compiled_parity:{pods_n}x{pols_n}:{dtype}")
            os.environ["CYCLONUS_COMPACT"] = "1" if compact else "0"
            os.environ["CYCLONUS_PALLAS_DTYPE"] = dtype
            os.environ["CYCLONUS_PALLAS_SLAB"] = "1" if slab else "0"
            os.environ["CYCLONUS_PACK"] = "1" if pack else "0"
            pods, namespaces, policies = build_synthetic(
                pods_n, pols_n, random.Random(rng.randrange(1 << 30))
            )
            policy = build_network_policies(True, policies)
            engine = TpuPolicyEngine(policy, pods, namespaces)
            try:
                got = engine.evaluate_grid_counts(port_cases, backend="pallas")
            except Exception as e:  # noqa: BLE001 — recorded, then fatal
                failures.append(
                    {"case": [pods_n, pols_n, compact, dtype, slab, pack],
                     "error": f"{type(e).__name__}: {e}"[:300]}
                )
                continue
            want = engine.evaluate_grid_counts(port_cases, backend="xla")
            if got != want:
                failures.append(
                    {"case": [pods_n, pols_n, compact, dtype, slab, pack],
                     "pallas": got, "xla": want}
                )
            if slab and engine._slab_plan_state is None:
                failures.append(
                    {"case": [pods_n, pols_n, compact, dtype, slab, pack],
                     "error": "slab case fell back (plan ineligible)"}
                )
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    return {
        "cases": len(cases_spec),
        "ok": not failures,
        "failures": failures,
    }


#: per-chip peaks the roofline model divides by, keyed by the
#: device_kind JAX reports.  HBM bandwidth and int8 rate: Google Cloud
#: documentation, "TPU v5e"; vpu_ops: ~8x128 lanes * 4 ALUs * ~1 GHz
#: (approximate, no published figure).  A kind that is not here is an
#: error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bps": 819e9, "mxu_int8": 394.7e12, "vpu_ops": 4e12},
}


def roofline_model(engine, q: int, eval_s: float, device_kind: str) -> dict:
    """Analytic roofline for the measured counts eval: which hardware
    limit the kernel is near, from the ACTUAL post-compaction shapes the
    kernel ran with, against DEVICE_PEAKS[device_kind] (ValueError for a
    device it has no peaks for).  Three components (the kernel overlaps
    them; the bound is the max):
      - hbm_s: operand DMA traffic / 819 GB/s HBM.  b_e/a_i blocks are
        refetched once per src tile (the dominant term); a_e/b_i once
        per (q, src tile).
      - mxu_s_dense: 2*q*Ns'*Nd'*(kt_e+kt_i) int8 MACs at 394.7 TOPS
        peak.  DENSE upper bound — the nz block skip removes most of it
        in the ns-sorted regime, so the true MXU time is lower.
      - vpu_s: the per-cell epilogue (2 compares, 1 and, ~3 reduce ops
        per cell amortized) at ~4e12 int ops/s — the floor that fusing
        exists to expose.
    Under the PACKED dtype plan (detail.pack) the contraction leaves the
    MXU entirely: the word AND/OR steps are VPU work over ceil(T/32)
    int32 words per direction — vpu_s absorbs the contraction term,
    mxu_s_dense drops out, and operand bytes shrink to the packed words.
    efficiency = roofline_s / eval_s (1.0 = at the modeled limit)."""
    from cyclonus_tpu.engine.encoding import packed_words
    from cyclonus_tpu.engine.pallas_kernel import (
        PACKED_BD,
        PACKED_BS,
        _kt_for,
        _tiles_for,
        lane_round_up,
    )

    if device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no roofline peaks for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)})"
        )
    peaks = DEVICE_PEAKS[device_kind]
    hbm_bps = peaks["hbm_bps"]
    mxu_int8 = peaks["mxu_int8"]  # peak int8 MACs*2/s
    vpu_ops = peaks["vpu_ops"]

    # the dense kernels append one pseudo-target row per direction; the
    # packed kernel does NOT (flags ride a separate word), so the raw
    # target counts feed the packed branch and +1 only the dense one —
    # keeping detail.roofline.kt consistent with detail.pack.words
    t_e_raw = int(engine._tensors["egress"]["target_ns"].shape[0])
    t_i_raw = int(engine._tensors["ingress"]["target_ns"].shape[0])
    t_e, t_i = t_e_raw + 1, t_i_raw + 1
    n_b = int(engine._tensors["pod_ns_id"].shape[0])

    if engine._pack:
        choice = engine.pack_stats().get("winner") or {}
        bs = int(choice.get("bs", PACKED_BS))
        bd = int(choice.get("bd", PACKED_BD))
        w_e, w_i = packed_words(t_e_raw), packed_words(t_i_raw)
        kt_e, kt_i = w_e, w_i
        ns_pad = -(-n_b // bs) * bs
        nd_pad = -(-n_b // bd) * bd
        n_i = ns_pad // bs
        # int32 words: a_e/b_i per (q, src tile), b_e/a_i per src tile
        hbm_bytes = 4 * q * n_i * (
            bs * (lane_round_up(w_e + 1) + lane_round_up(w_i))
            + nd_pad * (w_e + w_i + 2)
        )
        # contraction (1 AND + 1 OR per word pair) + the fused epilogue
        vpu_cell_ops = q * ns_pad * nd_pad * (2 * (w_e + w_i) + 6)
        comp = {
            "hbm_s": hbm_bytes / hbm_bps,
            "vpu_s": vpu_cell_ops / vpu_ops,
        }
        dtype = "packed32"
    else:
        dtype = os.environ.get("CYCLONUS_PALLAS_DTYPE", "int8")
        kt_e, kt_i = _kt_for(t_e), _kt_for(t_i)
        single = kt_e >= t_e and kt_i >= t_i
        bs, bd = _tiles_for(
            kt_e, kt_i, n_b,
            single_chunk_int8=single and dtype == "int8",
            n_dst=n_b,
        )
        ns_pad = -(-n_b // bs) * bs
        nd_pad = -(-n_b // bd) * bd
        n_i, n_j = ns_pad // bs, nd_pad // bd
        opb = 2 if dtype == "bf16" else 1  # bytes per operand element
        hbm_bytes = opb * q * n_i * (
            bs * (kt_e + kt_i) + n_j * bd * (kt_e + kt_i)
        )
        mxu_ops = 2 * q * ns_pad * nd_pad * (kt_e + kt_i)
        vpu_cell_ops = 6 * q * ns_pad * nd_pad
        comp = {
            "hbm_s": hbm_bytes / hbm_bps,
            "mxu_s_dense": mxu_ops
            / (mxu_int8 if dtype == "int8" else mxu_int8 / 2),
            "vpu_s": vpu_cell_ops / vpu_ops,
        }
    bound = max(comp, key=comp.get)
    roofline_s = comp[bound]
    return {
        "device_kind": device_kind,
        "tile": [bs, bd],
        "kt": [kt_e, kt_i],
        "dtype": dtype,
        "hbm_gb": round(hbm_bytes / 1e9, 3),
        **{k: round(v, 6) for k, v in comp.items()},
        "bound": bound,
        "roofline_s": round(roofline_s, 6),
        "efficiency_vs_roofline": round(roofline_s / eval_s, 3)
        if eval_s > 0
        else None,
    }


def mesh_case(pods, namespaces, policies, cases) -> dict:
    """The first-class mesh leg (detail.mesh): the OVERLAPPED ring path
    as the benchmarked scale-out headline.

    Runs ring counts (sync + the double-buffered pipelined twin,
    engine.mesh_counts_pipelined_eval_s) at 1/2/4/8 devices over one
    fixed BENCH_MESH_PODS problem, on the devices of the DEFAULT
    backend and as many of them as it has: a one-chip TPU records the
    1-device row only, never rows from a virtual CPU mesh (virtual:
    true marks a run whose caller pinned the CPU).  Plus a grid leg at
    the max device count pinning the overlapped schedule bit-identical
    to the all-gather schedule and the single-device kernel, and the
    peer-buffer watermark comparison (ring < allgather).

    Every row carries the stable fields: cells_per_sec, cells_per_sec_per_chip, ring_step_s (pipelined
    per-hop seconds), overlap_efficiency (ideal n-dev eval = 1-dev
    pipelined / n_dev, over the measured pipelined eval; ~1 on a real
    mesh with full compute/transfer overlap, ~1/n on a virtual mesh
    that timeshares one core), counts_ok, virtual."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from cyclonus_tpu.engine import TpuPolicyEngine, sharded as sharded_mod
    from cyclonus_tpu.matcher import build_network_policies

    devices = list(jax.devices())
    virtual = devices[0].platform != "tpu"
    policy = build_network_policies(True, policies)
    engine = TpuPolicyEngine(policy, pods, namespaces)
    n = len(pods)
    cells = len(cases) * n * n
    want = engine.evaluate_grid_counts(cases, block=512)
    rows = []
    pipe_1dev = None
    max_mesh = None
    for n_dev in (1, 2, 4, 8):
        if len(devices) < n_dev:
            break
        _enter_phase(f"mesh:{n_dev}dev")
        mesh = Mesh(np.array(devices[:n_dev]), ("x",))
        max_mesh = (n_dev, mesh)

        def run(m=mesh):
            return engine.evaluate_grid_counts_ring(cases, block=512, mesh=m)

        counts = run()  # warmup/compile
        times = []
        for _ in range(2):
            t0 = time.time()
            counts = run()
            times.append(time.time() - t0)
        sync_s = min(times)
        ok = counts == want
        if not ok:
            raise AssertionError(
                f"MESH LEG: ring counts @{n_dev}dev {counts} != {want}"
            )
        pipe_s, pipe_counts = engine.mesh_counts_pipelined_eval_s(
            cases, reps=5, block=512, mesh=mesh
        )
        if pipe_counts != want:
            raise AssertionError(
                f"MESH LEG: pipelined counts @{n_dev}dev "
                f"{pipe_counts} != {want}"
            )
        if n_dev == 1:
            pipe_1dev = pipe_s
        overlap = (
            round((pipe_1dev / n_dev) / pipe_s, 4)
            if pipe_1dev and pipe_s > 0
            else None
        )
        rows.append(
            {
                "path": "ring",
                "devices": n_dev,
                "eval_s": round(sync_s, 4),
                "pipelined_eval_s": round(pipe_s, 4),
                # on a VIRTUAL mesh these are shape evidence only (one
                # core timeshared), flagged by `virtual`
                "cells_per_sec": round(cells / pipe_s) if pipe_s > 0 else None,
                "cells_per_sec_per_chip": round(cells / pipe_s / n_dev)
                if pipe_s > 0
                else None,
                "ring_step_s": round(pipe_s / n_dev, 5) if pipe_s > 0 else None,
                "overlap_efficiency": overlap,
                "counts_ok": ok,
                "virtual": virtual,
            }
        )
    grid_parity = None
    peer_bytes = None
    if max_mesh is not None:
        n_dev, mesh = max_mesh
        _enter_phase(f"mesh:grid{n_dev}dev")
        ref = engine.evaluate_grid(cases)
        t0 = time.time()
        ring_grid = engine.evaluate_grid_sharded(
            cases, mesh=mesh, schedule="ring"
        ).block_until_ready()
        grid_s = time.time() - t0
        ag_grid = engine.evaluate_grid_sharded(
            cases, mesh=mesh, schedule="allgather"
        )
        for name in ("ingress", "egress", "combined"):
            a = np.asarray(getattr(ring_grid, name))
            if not np.array_equal(a, np.asarray(getattr(ref, name))):
                raise AssertionError(
                    f"MESH LEG: overlapped grid != single-device on {name}"
                )
            if not np.array_equal(a, np.asarray(getattr(ag_grid, name))):
                raise AssertionError(
                    f"MESH LEG: overlapped grid != all-gather on {name}"
                )
        grid_parity = {
            "devices": n_dev,
            "eval_s": round(grid_s, 4),
            "bit_identical": True,  # vs all-gather AND single-device
        }
        # the HBM watermark acceptance: the overlapped schedule's peak
        # per-device peer-buffer bytes must undercut the all-gather
        # schedule's replicated peer copy once the mesh is real (>1 dev)
        from cyclonus_tpu.engine.encoding import pack_enabled

        t = engine._tensors_with_cases(cases)
        t_padded, _ = sharded_mod._pad_pod_arrays(t, n, n_dev)
        rb = sharded_mod.peer_buffer_bytes(
            t_padded, n_dev, "ring", pack=pack_enabled()
        )
        ab = sharded_mod.peer_buffer_bytes(t_padded, n_dev, "allgather")
        # the watermark acceptance holds from 8 devices up: the ring's
        # double-buffered bf16 bundle is ~4x(allgather bool bytes)/D, so
        # it crosses below the replicated copy past D=4 — a 2-device
        # mesh legitimately measures larger, and only reports (ok: null)
        asserted = n_dev >= 8
        peer_bytes = {
            "ring": rb,
            "allgather": ab,
            "ok": (rb < ab) if asserted else None,
        }
        if asserted and rb >= ab:
            raise AssertionError(
                f"MESH LEG: overlapped peer-buffer bytes {rb} not below "
                f"all-gather's replicated {ab} at {n_dev} devices"
            )
    return {
        "pods": n,
        "policies": len(policies),
        "schedule": "ring",
        # a real-mesh bench records virtual: false
        "virtual": virtual,
        "note": (
            "virtual CPU mesh, one physical core: flat wall-clock = "
            "conserved work; overlap_efficiency ~1/n by construction"
            if virtual
            else "real device mesh"
        ),
        "rows": rows,
        "grid_parity": grid_parity,
        "peer_buffer_bytes": peer_bytes,
    }


def _mesh_leg(cases) -> dict:
    """Bounded wrapper for the mesh leg: detail.mesh appears on EVERY
    bench line (rows empty when skipped), correctness failures re-raise
    loudly, and a wedged compile costs only this detail block."""
    if os.environ.get("BENCH_MESH", "1") != "1":
        return {
            "rows": [],
            "virtual": None,
            "schedule": "ring",
            "skipped": "BENCH_MESH=0",
        }
    import random as _random

    from cyclonus_tpu.utils.bounded import run_bounded

    # BENCH_MESH_PODS/POLICIES: the guard tests shrink the mesh problem
    # to keep the CI subprocess cheap
    m_pods, m_ns, m_pols = build_synthetic(
        int(os.environ.get("BENCH_MESH_PODS", "2048")),
        int(os.environ.get("BENCH_MESH_POLICIES", "200")),
        _random.Random(77),
    )
    _stall_env = float(os.environ.get("BENCH_STALL_S", "300"))
    _bound = min(300.0, _stall_env * 0.8) if _stall_env > 0 else 600.0
    status, value = run_bounded(
        lambda: mesh_case(m_pods, m_ns, m_pols, cases), _bound
    )
    if status == "ok":
        return value
    if status == "error" and isinstance(value, AssertionError):
        raise value
    return {
        "rows": [],
        "virtual": None,
        "schedule": "ring",
        "status": status,
        "error": None if status == "timeout" else repr(value),
    }


def serve_churn_case(cases, headline_pods: int, headline_policies: int) -> dict:
    """BENCH serve leg (detail.serve): a VerdictService on a
    BENCH_SERVE_PODS-pod synthetic cluster, a seeded stream of
    BENCH_SERVE_DELTAS single-pod deltas applied one at a time with
    BENCH_SERVE_QUERIES flow queries interleaved — incremental_apply_s
    vs full_rebuild_s, queries/s under churn, and the differential
    parity gate.

    The acceptance assertions are hard failures: every delta must take
    the INCREMENTAL path (no full re-encode, no re-device_put of
    untouched slabs — pinned via the engine.encode / engine.device_put
    span counters), and the patched engine must stay bit-identical to a
    fresh rebuild with oracle spot checks (VerdictService.verify_parity)."""
    import random as _random

    from cyclonus_tpu import telemetry
    from cyclonus_tpu.serve import VerdictService
    from cyclonus_tpu.telemetry import instruments as ti
    from cyclonus_tpu.telemetry.metrics import histogram_quantile
    from cyclonus_tpu.worker.model import Delta, FlowQuery

    n_pods = int(
        os.environ.get("BENCH_SERVE_PODS", "0")
    ) or min(1024, headline_pods)
    n_policies = int(
        os.environ.get("BENCH_SERVE_POLICIES", "0")
    ) or min(128, max(headline_policies, 8))
    k_deltas = int(os.environ.get("BENCH_SERVE_DELTAS", "32"))
    q_per_step = int(os.environ.get("BENCH_SERVE_QUERIES", "8"))
    rng = _random.Random(123)
    pods, namespaces, pol_objs = build_synthetic(n_pods, n_policies, rng)
    # audit plane rides the churn leg: a seeded shadow-oracle sampler
    # re-checks a fraction of the answered queries against the scalar
    # oracle and digests every committed epoch — detail.audit
    # (checked/diverged/digest_s) rides every line
    from cyclonus_tpu.audit import AuditController

    aud = AuditController(
        rate=float(os.environ.get("BENCH_AUDIT_RATE", "0.25")), seed=42
    )
    t0 = time.perf_counter()
    svc = VerdictService(pods, namespaces, pol_objs, audit=aud)
    build_s = time.perf_counter() - t0
    full_rebuild_s = svc.state()["last_full_rebuild_s"]
    # warm the device state + the query program before timing churn
    keys = list(svc.pods)
    warm_q = FlowQuery(
        src=keys[0], dst=keys[1], port=80, protocol="TCP",
        port_name="serve-80-tcp",
    )
    svc.query([warm_q])
    svc.apply([Delta(
        kind="pod_labels", namespace=pods[0][0], name=pods[0][1],
        labels={**pods[0][2], "tier": "tier1"},
    )])  # warm the scatter program too
    spans = telemetry.SPANS.stats()
    encodes0 = spans.get("engine.encode", {}).get("count", 0)
    device_puts0 = spans.get("engine.device_put", {}).get("count", 0)
    patch_bytes0 = ti.SERVE_PATCH_BYTES.value()
    headroom_saves0 = ti.SERVE_HEADROOM_SAVES.value()
    shed0 = ti.SLO_SHED.value()
    apply_times, query_times, n_queries = [], [], 0
    for step in range(k_deltas):
        key = keys[rng.randrange(len(keys))]
        ns, name = key.split("/", 1)
        if step % 5 == 4:
            # delete-then-recreate: the remove frees the row the add
            # re-claims, so the pair stays within the bucketed capacity
            pod = svc.pods[key]
            batch = [
                Delta(kind="pod_remove", namespace=ns, name=name),
                Delta(kind="pod_add", namespace=ns, name=name,
                      labels=dict(pod[2]), ip=pod[3]),
            ]
        else:
            batch = [Delta(
                kind="pod_labels", namespace=ns, name=name,
                labels={
                    "pod": f"p{rng.randrange(100)}",
                    "app": f"app{rng.randrange(20)}",
                    "tier": f"tier{rng.randrange(5)}",
                },
            )]
        report = svc.apply(batch)
        # class_rebuild is still a patch path (only the class buffer
        # re-uploads; the main buffer and compiled programs survive) —
        # it appears under CYCLONUS_CLASS_COMPRESS=1 only: serve engines
        # build compact=False, which skips the selector pass auto mode
        # reuses, so auto compression never activates here regardless of
        # BENCH_SERVE_PODS.  Only "full" (re-encode + re-device_put)
        # fails.
        if report["mode"] not in ("incremental", "class_rebuild"):
            raise AssertionError(
                f"SERVE CHURN: delta step {step} took mode "
                f"{report['mode']!r}, expected an incremental patch "
                f"({batch})"
            )
        apply_times.append(report["seconds"])
        queries = []
        for _ in range(q_per_step):
            a, b = rng.choice(keys), rng.choice(keys)
            if rng.random() < 0.5:
                queries.append(FlowQuery(
                    src=a, dst=b, port=80, protocol="TCP",
                    port_name="serve-80-tcp",
                ))
            else:
                queries.append(FlowQuery(
                    src=a, dst=b, port=81, protocol="UDP",
                    port_name="serve-81-udp",
                ))
        tq = time.perf_counter()
        svc.query(queries)
        query_times.append(time.perf_counter() - tq)
        n_queries += len(queries)
    spans = telemetry.SPANS.stats()
    encodes = spans.get("engine.encode", {}).get("count", 0)
    device_puts = spans.get("engine.device_put", {}).get("count", 0)
    if encodes != encodes0 or device_puts != device_puts0:
        raise AssertionError(
            "SERVE CHURN: incremental applies re-encoded or re-"
            f"device_put ({encodes - encodes0} encodes, "
            f"{device_puts - device_puts0} device_puts)"
        )
    patch_bytes = ti.SERVE_PATCH_BYTES.value() - patch_bytes0
    parity = svc.verify_parity(oracle_samples=32)
    incr_mean = sum(apply_times) / max(len(apply_times), 1)
    qps = n_queries / max(sum(query_times), 1e-9)
    hist = ti.SERVE_QUERY_LATENCY.snapshot()
    st = svc.state()
    return {
        "pods": n_pods,
        "policies": n_policies,
        "deltas": k_deltas,
        "build_s": round(build_s, 3),
        "full_rebuild_s": round(full_rebuild_s, 4),
        "incremental_apply_s": round(incr_mean, 5),
        "incremental_apply_max_s": round(max(apply_times), 5),
        "speedup_vs_rebuild": round(full_rebuild_s / max(incr_mean, 1e-9), 1),
        "queries": n_queries,
        "queries_per_sec": round(qps, 1),
        "query_p50_ms": (
            round(histogram_quantile(hist, 0.50) * 1e3, 3)
            if histogram_quantile(hist, 0.50) is not None
            else None
        ),
        "query_p99_ms": (
            round(histogram_quantile(hist, 0.99) * 1e3, 3)
            if histogram_quantile(hist, 0.99) is not None
            else None
        ),
        "patch_bytes": int(patch_bytes),
        # bucket-crossing policy churn absorbed by the pre-reserved slab
        # headroom (cyclonus_tpu_serve_headroom_saves_total delta)
        "headroom_saves": int(
            ti.SERVE_HEADROOM_SAVES.value() - headroom_saves0
        ),
        "no_reencode": True,
        "applies": st["applies"],
        "parity": parity,
        # SLO accounting (enforcement stays disarmed in the bench):
        # shed_rate should be 0.0 and the query_p99 budget healthy
        "shed_rate": round(
            (ti.SLO_SHED.value() - shed0) / max(n_queries, 1), 4
        ),
        "slo_budget_remaining": st["slo"]["objectives"]["query_p99"][
            "budget_remaining"
        ],
        "audit": _audit_leg_detail(aud),
    }


def _audit_leg_detail(aud) -> dict:
    """Drain the churn leg's audit controller and reduce its snapshot
    to the detail.audit block."""
    aud.flush(timeout=30.0)
    snap = aud.snapshot()
    aud.close()
    latest = snap.get("latest") or {}
    return {
        "checked": int(snap["checked"]),
        "diverged": int(snap["diverged"]),
        "digest_s": latest.get("seconds"),
        "digest": latest.get("digest"),
        "dropped": dict(snap["dropped"]),
    }


def _serve_churn_leg(cases, n_pods: int, n_policies: int):
    """Bounded wrapper for the serve leg (BENCH_SERVE=0 skips): like the
    mega/sharded legs, a wedged compile must cost only this detail
    block, but correctness failures (the incremental-path assertion or
    the differential gate) re-raise loudly."""
    if os.environ.get("BENCH_SERVE", "1") != "1":
        return None
    from cyclonus_tpu.utils.bounded import run_bounded

    _stall_env = float(os.environ.get("BENCH_STALL_S", "300"))
    _bound = min(240.0, _stall_env * 0.8) if _stall_env > 0 else 600.0
    status, value = run_bounded(
        lambda: serve_churn_case(cases, n_pods, n_policies), _bound
    )
    if status == "ok":
        return value
    if status == "error" and isinstance(value, AssertionError):
        raise value
    return {
        "status": status,
        "error": None if status == "timeout" else repr(value),
    }


def _audit_detail(serve_detail):
    """The top-level detail.audit block (on every line): lifted out of the serve leg's report — None when the leg was
    skipped, timed out, or predates the audit plane."""
    if isinstance(serve_detail, dict):
        a = serve_detail.get("audit")
        if isinstance(a, dict):
            return a
    return None


def _wire_detail():
    """The top-level detail.wire block (on every line): the wire-protocol generation this run spoke plus a live
    skew sweep — every registered message round-tripped through its
    real codec under both skew directions (older-peer legacy views,
    newer-peer unknown-key injection; worker/wireregistry.py).  The
    sweep is pure host-side dict shuffling (milliseconds), so it rides
    every bench line; a non-empty problems list is a bench failure —
    it means the committed protocol cannot survive a mixed-version
    fleet.  (This must NOT import tests.skewharness: the harness
    module arms env flags at import time.)"""
    from cyclonus_tpu.worker import model, wireregistry

    sweep = wireregistry.skew_sweep(model.CODECS)
    problems = sweep["problems"]
    assert not problems, f"wire skew sweep failed: {problems[:5]}"
    return {
        "schema_version": sweep["schema_version"],
        "keys": sweep["keys"],
        "skew_pairs_checked": sweep["skew_pairs_checked"],
    }


def tiers_lattice():
    """The deterministic ANP/BANP lattice of the tiers leg (and of
    chip_smoke.py's tier-epilogue phase) over build_synthetic's label
    scheme: overlapping priorities (two at 5), a Pass-chain into the NP
    tier, an endPort range, SCTP, and a BANP default-deny for one app."""
    from cyclonus_tpu.kube.netpol import IntOrString, LabelSelector
    from cyclonus_tpu.tiers.model import (
        AdminNetworkPolicy,
        BaselineAdminNetworkPolicy,
        TierPort,
        TierRule,
        TierScope,
        TierSet,
    )

    return TierSet(
        anps=[
            AdminNetworkPolicy(
                name="bench-deny-tier0", priority=5,
                subject=TierScope(
                    pod_selector=LabelSelector.make({"tier": "tier0"})
                ),
                ingress=[TierRule(
                    action="Deny",
                    peers=[TierScope(
                        pod_selector=LabelSelector.make({"app": "app1"})
                    )],
                    ports=[TierPort(
                        protocol="TCP", port=IntOrString(80), end_port=81
                    )],
                )],
            ),
            AdminNetworkPolicy(
                name="bench-pass-tier1", priority=5,
                subject=TierScope(
                    pod_selector=LabelSelector.make({"tier": "tier1"})
                ),
                ingress=[TierRule(
                    action="Pass", peers=[TierScope()],
                )],
            ),
            AdminNetworkPolicy(
                name="bench-allow-sctp", priority=9,
                subject=TierScope(),
                ingress=[TierRule(
                    action="Allow",
                    peers=[TierScope(
                        namespace_selector=LabelSelector.make(
                            {"team": "team0"}
                        )
                    )],
                    ports=[TierPort(
                        protocol="SCTP", port=IntOrString(82)
                    )],
                )],
            ),
        ],
        banp=BaselineAdminNetworkPolicy(
            subject=TierScope(
                pod_selector=LabelSelector.make({"app": "app2"})
            ),
            ingress=[TierRule(action="Deny", peers=[TierScope()])],
        ),
    )


def tiers_case(cases, headline_pods: int, headline_policies: int) -> dict:
    """BENCH tiers leg (detail.tiers): the precedence-tier lattice on a
    BENCH_TIERS_PODS-pod synthetic cluster under a deterministic
    ANP/BANP set layered over BENCH_TIERS_POLICIES NetworkPolicies —
    resolve_s is the tiered grid dispatch (engine.tier_stats), with a
    scalar-oracle spot check on sampled cells so a wrong tier epilogue
    can never publish a rate (docs/DESIGN.md "Precedence tiers")."""
    import random as _random

    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies
    from cyclonus_tpu.matcher.tiered import TieredPolicy

    n_pods = int(
        os.environ.get("BENCH_TIERS_PODS", "0")
    ) or min(1024, headline_pods)
    n_policies = int(
        os.environ.get("BENCH_TIERS_POLICIES", "0")
    ) or min(32, max(headline_policies, 8))
    rng = _random.Random(777)
    pods, namespaces, pol_objs = build_synthetic(n_pods, n_policies, rng)
    tiers = tiers_lattice()
    t0 = time.perf_counter()
    policy = build_network_policies(True, pol_objs)
    engine = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    grid = engine.evaluate_grid(cases)
    warmup_s = time.perf_counter() - t0
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        grid = engine.evaluate_grid(cases)
        times.append(time.perf_counter() - t0)
    combined = np.asarray(grid.combined)
    # spot differential: sampled cells against the tiered scalar oracle
    from cyclonus_tpu.analysis.oracle import traffic_for_cell

    oracle = TieredPolicy(policy, tiers)
    n_samples = int(os.environ.get("BENCH_TIERS_SAMPLE", "16"))
    for _ in range(n_samples):
        qi = rng.randrange(len(cases))
        si, di = rng.randrange(n_pods), rng.randrange(n_pods)
        t = traffic_for_cell(pods, namespaces, cases[qi], si, di)
        _ing, _eg, want = oracle.is_traffic_allowed(t)
        got = bool(combined[qi, si, di])
        if got != want:
            raise AssertionError(
                f"BENCH TIERS: kernel diverges from the tiered oracle "
                f"at case={cases[qi]} src={pods[si][:2]} "
                f"dst={pods[di][:2]}: kernel={got} oracle={want}"
            )
    stats = engine.tier_stats()
    stats.update({
        "pods": n_pods,
        "policies": n_policies,
        "build_s": round(build_s, 3),
        "warmup_s": round(warmup_s, 3),
        "eval_s": round(min(times), 4),
        "parity_spot_checks": n_samples,
    })
    return stats


def _tiers_leg(cases, n_pods: int, n_policies: int):
    """Bounded wrapper for the tiers leg (BENCH_TIERS=0 skips; skipped
    legs still record {active: False} so detail.tiers appears on every
    line).  Oracle-parity failures re-raise loudly like the serve leg's."""
    if os.environ.get("BENCH_TIERS", "1") != "1":
        return {"active": False, "skipped": "BENCH_TIERS=0"}
    from cyclonus_tpu.utils.bounded import run_bounded

    _stall_env = float(os.environ.get("BENCH_STALL_S", "300"))
    _bound = min(240.0, _stall_env * 0.8) if _stall_env > 0 else 600.0
    status, value = run_bounded(
        lambda: tiers_case(cases, n_pods, n_policies), _bound
    )
    if status == "ok":
        return value
    if status == "error" and isinstance(value, AssertionError):
        raise value
    return {
        "active": False,
        "status": status,
        "error": None if status == "timeout" else repr(value),
    }


def cidr_cluster(n_pods: int, distinct: int, pool: int):
    """(pods, namespaces, netpols, rng) of the cidr leg (and of
    chip_smoke.py's CIDR phase): an ipBlock-heavy synthetic cluster —
    `distinct` distinct (base, mask, excepts) rows over `n_pods` pods
    drawn from a bounded pool of `pool` IPs, the regime where IP
    structure, not labels, carries the signature entropy.  The rng is
    returned mid-stream so the leg's later draws are unchanged."""
    import random as _random

    from cyclonus_tpu.kube.netpol import (
        IPBlock,
        LabelSelector,
        NetworkPolicy,
        NetworkPolicyEgressRule,
        NetworkPolicyIngressRule,
        NetworkPolicyPeer,
        NetworkPolicySpec,
    )

    rng = _random.Random(424242)
    namespaces = {"cidr": {"ns": "cidr"}}
    ip_pool = sorted(
        {
            f"10.{rng.randrange(64)}.{rng.randrange(256)}"
            f".{rng.randrange(1, 255)}"
            for _ in range(pool)
        }
    )
    # two label shapes on purpose: the signature entropy must come from
    # the CIDR structure, which is exactly what the TSS stage compresses
    pods = [
        ("cidr", f"p{i}", {"app": f"app{i % 2}"}, ip_pool[i % len(ip_pool)])
        for i in range(n_pods)
    ]
    # the distinct-CIDR corpus: /32 splinters on the pod pool's /24s
    # (membership actually varies) plus an UNBOUNDED /32 family over
    # 10.0.0.0/10 (~4.2M candidates — what lets BENCH_CIDR_DISTINCT
    # reach the 100k acceptance shape; pool-only families cap at ~49k
    # and the rejection loop would spin forever), /24 and /16 ladders,
    # excepts.  The attempts bound keeps a pathological request (past
    # the family capacity) from hanging the leg: it runs with what it
    # got, and requested_distinct vs distinct_cidrs records the gap.
    cidrs: list = []
    seen = set()
    attempts = 0
    while len(cidrs) < distinct and attempts < 64 * distinct:
        attempts += 1
        roll = rng.random()
        if roll < 0.30:
            ip = rng.choice(ip_pool)
            a, b, c, _d = ip.split(".")
            cand = (f"{a}.{b}.{c}.{rng.randrange(256)}/32", ())
        elif roll < 0.55:
            cand = (
                f"10.{rng.randrange(64)}.{rng.randrange(256)}"
                f".{rng.randrange(256)}/32",
                (),
            )
        elif roll < 0.80:
            cand = (
                f"10.{rng.randrange(64)}.{rng.randrange(256)}.0/24",
                (),
            )
        elif roll < 0.92:
            b2 = rng.randrange(64)
            cand = (f"10.{b2}.0.0/16", (f"10.{b2}.{rng.randrange(256)}.0/24",))
        else:
            cand = (f"10.{rng.randrange(64)}.0.0/{rng.choice((12, 14, 15))}", ())
        if cand not in seen:
            seen.add(cand)
            cidrs.append(cand)
    per_rule = 64
    netpols = []
    for i in range(0, len(cidrs), per_rule):
        chunk = cidrs[i : i + per_rule]
        peers = [
            NetworkPolicyPeer(ip_block=IPBlock.make(c, list(ex)))
            for c, ex in chunk
        ]
        netpols.append(
            NetworkPolicy(
                name=f"cidr-{i // per_rule}",
                namespace="cidr",
                spec=NetworkPolicySpec(
                    pod_selector=LabelSelector.make(),
                    policy_types=["Ingress", "Egress"],
                    ingress=[NetworkPolicyIngressRule(ports=[], from_=peers)],
                    egress=[NetworkPolicyEgressRule(ports=[], to=peers)],
                ),
            )
        )
    return pods, namespaces, netpols, rng


def cidr_case(cases, headline_pods: int, headline_policies: int) -> dict:
    """BENCH cidr leg (detail.cidr): the TSS/LPM CIDR pre-classification
    stage (docs/DESIGN.md "CIDR tuple-space pre-classification") on a
    synthetic ipBlock-heavy cluster — BENCH_CIDR_DISTINCT distinct
    (base, mask, excepts) rows over BENCH_CIDR_PODS pods drawn from a
    bounded IP pool (the regime where IP structure, not labels, carries
    the signature entropy).  Records {active, distinct_cidrs,
    partitions, classes, ratio, lpm_s} plus the measured dense-vs-TSS
    throughput comparison: the TSS-compressed engine must beat the
    dense engine's rate (asserted at >= 512 pods; smaller guard shapes
    record without asserting), with counts cross-checked bit-identical
    on a shared sub-cluster and scalar-oracle pair spot checks."""
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies

    n_pods = int(
        os.environ.get("BENCH_CIDR_PODS", "0")
    ) or min(1024, headline_pods)
    distinct = int(
        os.environ.get("BENCH_CIDR_DISTINCT", "0")
    ) or min(512, max(64, headline_policies))
    pool = int(os.environ.get("BENCH_CIDR_IP_POOL", "0")) or 64
    pods, namespaces, netpols, rng = cidr_cluster(n_pods, distinct, pool)
    policy = build_network_policies(True, netpols)
    t0 = time.perf_counter()
    engine = TpuPolicyEngine(
        policy, pods, namespaces, class_compress="1", cidr_tss="1"
    )
    build_s = time.perf_counter() - t0
    out = {
        "pods": n_pods,
        "requested_distinct": distinct,
        "build_s": round(build_s, 3),
    }
    out.update(engine.cidr_stats())
    cc = engine.class_compression_stats()
    out["classes"] = cc.get("classes")
    out["ratio"] = cc.get("ratio")
    out["hbm_budget_ok"] = engine._class_counts_eligible(len(cases))
    # steady-state TSS-compressed counts rate
    counts = engine.evaluate_grid_counts(cases)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        counts = engine.evaluate_grid_counts(cases)
        times.append(time.perf_counter() - t0)
    out["eval_s"] = round(min(times), 4)
    out["cells_per_sec"] = round(counts["cells"] / min(times))
    # oracle spot parity through the pairs kernel (raises on divergence)
    n_samples = int(os.environ.get("BENCH_CIDR_SAMPLE", "6"))
    spot_check_pairs(engine, policy, pods, namespaces, cases, n_samples, rng)
    out["parity_spot_checks"] = n_samples
    # dense twin on a bounded sub-cluster: the measured comparison plus
    # a bit-identity cross-check of the two paths' counts
    n_dense = min(n_pods, int(os.environ.get("BENCH_CIDR_DENSE_PODS", "512")))
    sub_pods = pods[:n_dense]
    dense_engine = TpuPolicyEngine(
        policy, sub_pods, namespaces, class_compress="0", cidr_tss="0"
    )
    dense_counts = dense_engine.evaluate_grid_counts(cases)
    d_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        dense_counts = dense_engine.evaluate_grid_counts(cases)
        d_times.append(time.perf_counter() - t0)
    dense_rate = dense_counts["cells"] / min(d_times)
    out["dense"] = {
        "pods": n_dense,
        "eval_s": round(min(d_times), 4),
        "cells_per_sec": round(dense_rate),
    }
    # the SHAPE-MATCHED twin: the same sub-cluster through a TSS engine,
    # both the bit-identity cross-check AND the timed side of the
    # throughput gate — comparing the full-shape TSS rate against a
    # smaller dense grid would let fixed dispatch overhead amortize
    # differently and mask a real TSS regression
    sub_tss = TpuPolicyEngine(
        policy, sub_pods, namespaces, class_compress="1", cidr_tss="1"
    )
    sub_counts = sub_tss.evaluate_grid_counts(cases)
    if sub_counts != dense_counts:
        raise AssertionError(
            f"BENCH CIDR: TSS-compressed counts diverge from dense on "
            f"the shared sub-cluster: {sub_counts} != {dense_counts}"
        )
    s_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        sub_tss.evaluate_grid_counts(cases)
        s_times.append(time.perf_counter() - t0)
    sub_tss_rate = sub_counts["cells"] / min(s_times)
    out["tss_at_dense_shape"] = {
        "pods": n_dense,
        "eval_s": round(min(s_times), 4),
        "cells_per_sec": round(sub_tss_rate),
    }
    out["speedup_vs_dense"] = round(sub_tss_rate / max(dense_rate, 1e-9), 2)
    # the dense-vs-TSS throughput gate, same pods on both sides: at real
    # shapes the compressed grid must beat the dense one
    # (BENCH_CIDR_MIN_SPEEDUP scales the bound); tiny guard shapes
    # record the ratio without asserting
    min_speedup = float(os.environ.get("BENCH_CIDR_MIN_SPEEDUP", "1.0"))
    if n_dense >= 512 and out["speedup_vs_dense"] < min_speedup:
        raise AssertionError(
            f"BENCH CIDR: TSS throughput {round(sub_tss_rate)} cells/s "
            f"did not beat dense {round(dense_rate)} cells/s at "
            f"{n_dense} pods (speedup {out['speedup_vs_dense']} < "
            f"{min_speedup})"
        )
    return out


def _cidr_leg(cases, n_pods: int, n_policies: int):
    """Bounded wrapper for the cidr leg (BENCH_CIDR=0 skips; skipped
    legs still record {active: False} so detail.cidr appears on every
    line).  Correctness failures re-raise loudly like the tiers leg's."""
    if os.environ.get("BENCH_CIDR", "1") != "1":
        return {"active": False, "skipped": "BENCH_CIDR=0"}
    from cyclonus_tpu.utils.bounded import run_bounded

    _stall_env = float(os.environ.get("BENCH_STALL_S", "300"))
    _bound = min(240.0, _stall_env * 0.8) if _stall_env > 0 else 600.0
    status, value = run_bounded(
        lambda: cidr_case(cases, n_pods, n_policies), _bound
    )
    if status == "ok":
        return value
    if status == "error" and isinstance(value, AssertionError):
        raise value
    return {
        "active": False,
        "status": status,
        "error": None if status == "timeout" else repr(value),
    }


def mega_class_case(cases) -> dict:
    """The 1M-pod synthetic-cluster case (ROADMAP item 2): a cluster an
    order of magnitude past the headline shape, evaluable on one chip
    ONLY because equivalence-class compression collapses the pod axis —
    the dense 2e12-cell grid would blow both the HBM budget and the
    bench deadline.  The cluster models the regime the compression
    exists for (many pods, few distinct label shapes: BENCH_MEGA_NS
    namespaces over BENCH_MEGA_PODS pods), and the case records
    detail.mega_class.class_compression = {pods, classes, ratio,
    gather_s} plus the three safety legs: the HBM-budget eligibility
    check, a scalar-oracle pairs spot check, and the oracle-backed
    class-reduction audit (analysis.audit_class_reduction)."""
    from cyclonus_tpu import analysis
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies

    n_pods = int(os.environ.get("BENCH_MEGA_PODS", "1000000"))
    n_pols = int(os.environ.get("BENCH_MEGA_POLICIES", "2000"))
    n_ns = int(os.environ.get("BENCH_MEGA_NS", "512"))
    rng = random.Random(20260803)
    pods, namespaces, policies = build_synthetic(
        n_pods, n_pols, rng, n_ns=n_ns
    )
    t0 = time.time()
    policy = build_network_policies(True, policies)
    t_build = time.time() - t0
    t0 = time.time()
    engine = TpuPolicyEngine(policy, pods, namespaces)
    t_encode = time.time() - t0
    out = {
        "pods": n_pods,
        "policies": n_pols,
        "namespaces": n_ns,
        "build_s": round(t_build, 3),
        "encode_s": round(t_encode, 3),
        "class_compression": engine.class_compression_stats(),
    }
    if not out["class_compression"]["active"]:
        out["skipped"] = "class compression inactive for this shape"
        return out
    # the acceptance gate: the compressed path's whole device footprint
    # (aux/index tensors + class precompute + row sums) must fit the
    # CYCLONUS_SLAB_MAX_BYTES HBM budget
    out["hbm_budget_ok"] = engine._class_counts_eligible(len(cases))
    if not out["hbm_budget_ok"]:
        # do NOT fall through: evaluate_grid_counts would route to the
        # dense kernels, whose [T, N, Q] precompute at this shape is the
        # exact HBM blow-up the compression exists to avoid — a clean
        # skip beats an infra-looking timeout/OOM
        out["skipped"] = (
            "compressed counts exceed CYCLONUS_SLAB_MAX_BYTES; dense "
            "fallback is not viable at this shape"
        )
        return out
    t0 = time.time()
    counts = engine.evaluate_grid_counts(cases)
    out["warmup_s"] = round(time.time() - t0, 3)
    times = []
    for _ in range(3):
        t0 = time.time()
        counts = engine.evaluate_grid_counts(cases)
        times.append(time.time() - t0)
    out["eval_s"] = round(min(times), 4)
    out["cells"] = counts["cells"]
    out["cells_per_sec"] = round(counts["cells"] / min(times))
    out["allow_rate"] = round(counts["combined"] / max(counts["cells"], 1), 4)
    # refresh: the evals above recorded the broadcast-back epilogue
    out["class_compression"] = engine.class_compression_stats()
    # scalar-oracle spot check through the pairs kernel (no N x N grid)
    n_samples = int(os.environ.get("BENCH_MEGA_SAMPLE", "10"))
    spot_check_pairs(engine, policy, pods, namespaces, cases, n_samples, rng)
    out["parity_spot_checks"] = n_samples
    # the class reduction itself, oracle-verified on sampled co-classed
    # pods (a violation raises out of the bench as a correctness failure)
    audit = analysis.audit_class_reduction(
        policy, pods, namespaces, cases, engine.pod_classes(),
        max_classes=int(os.environ.get("BENCH_MEGA_AUDIT_CLASSES", "4")),
        peers_per_class=4, rng=rng,
    )
    out["audit"] = {
        "checked_classes": audit["checked_classes"],
        "checked_cells": audit["checked_cells"],
        "ok": audit["ok"],
    }
    if not audit["ok"]:
        raise AssertionError(
            f"CLASS REDUCTION AUDIT FAILURE: {audit['violations'][:3]}"
        )
    return out


def main():
    import threading

    done = threading.Event()
    deadline_s = float(os.environ.get("BENCH_DEADLINE_S", "1500"))
    stall_s = float(os.environ.get("BENCH_STALL_S", "300"))
    # the two bounds are independent knobs: either alone arms the watchdog
    if deadline_s > 0 or stall_s > 0:
        _start_watchdog(done, deadline_s, stall_s)
    try:
        rc = _bench(done)
    except SystemExit:
        raise
    except BaseException as e:
        done.set()
        print(
            _error_json(
                f"{type(e).__name__}: {e}", failure_class="engine"
            ),
            flush=True,
        )
        raise
    done.set()
    return rc


def _bench(done):
    _enter_phase("backend_attach")
    t0 = time.time()
    device = _attach_backend()
    t_init = time.time() - t0

    # the slab autotune (engine api) may compile a second program inside
    # the eval phase; keep its bound comfortably under BENCH_STALL_S so
    # a wedged candidate compile self-rejects before the phase watchdog
    # could kill the whole bench (explicit env wins).  Derived from the
    # actual stall bound so tightening BENCH_STALL_S keeps the invariant.
    _stall = float(os.environ.get("BENCH_STALL_S", "300"))
    _autotune_cap = min(150.0, _stall / 2) if _stall > 0 else 150.0
    os.environ.setdefault("CYCLONUS_AUTOTUNE_TIMEOUT_S", f"{_autotune_cap:g}")
    sharded = os.environ.get("BENCH_SHARDED", "") == "1"
    # BENCH_SHARDED selects the full-grid mesh path, which the tiled
    # default would otherwise shadow
    tiled = os.environ.get("BENCH_TILED", "1") == "1" and not sharded
    # default = the BASELINE.md north-star configuration (100k pods x 10k
    # policies, full matrix) on the tiled fused-pallas path — the only
    # mode that fits a single chip at this scale; full-grid modes default
    # to a size whose verdict tables actually fit in memory
    default_pods, default_pols = ("100000", "10000") if tiled else ("10000", "1000")
    n_pods = int(os.environ.get("BENCH_PODS", default_pods))
    n_policies = int(os.environ.get("BENCH_POLICIES", default_pols))
    counts_backend = os.environ.get("BENCH_COUNTS_BACKEND", "pallas")
    block = int(os.environ.get("BENCH_BLOCK", "1024"))
    n_samples = int(os.environ.get("BENCH_SAMPLE", "25"))
    trace_dir = os.environ.get("BENCH_TRACE_DIR", "")
    rng = random.Random(20260729)

    from cyclonus_tpu.utils.tracing import jax_profile

    from cyclonus_tpu import telemetry
    from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies

    _enter_phase("synthetic_build")
    pods, namespaces, policies = build_synthetic(n_pods, n_policies, rng)
    _enter_phase("matcher_build")
    t0 = time.time()
    policy = build_network_policies(True, policies)
    t_build = time.time() - t0

    _enter_phase("encode")
    t0 = time.time()
    engine = TpuPolicyEngine(policy, pods, namespaces)
    t_encode = time.time() - t0

    cases = [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "serve-81-udp", "UDP")]

    if tiled:
        # counts mode: the whole tile loop runs device-side in one jit; the
        # [n_tiles, 3] readback is the execution barrier
        def run_tiled():
            if counts_backend == "sharded":
                return engine.evaluate_grid_counts_sharded(cases, block=block)
            return engine.evaluate_grid_counts(
                cases, block=block, backend=counts_backend
            )

        _enter_phase("warmup")
        telemetry.reset()
        t0 = time.time()
        counts = run_tiled()
        t_warm = time.time() - t0
        # what warmup is made of: single-buffer transfer vs trace+compile
        # +first-execution (the engine.dispatch phase) — from the
        # telemetry span registry (the old ad-hoc phase dict, upgraded)
        warm_phases = {
            k: round(v["total_s"], 3)
            for k, v in telemetry.SPANS.stats().items()
        }
        # AOT forensics frozen HERE: later legs adopt this process's own
        # stores, which must not mark a cold run cache-bearing
        aot_warmup = _aot_snapshot()
        _enter_phase("eval")
        times = []
        # BENCH_TRACE_DIR / --trace-dir: profile exactly the steady-state
        # eval reps (warmup's compile noise would drown the kernels)
        with jax_profile(trace_dir or None):
            for _ in range(5):
                t0 = time.time()
                counts = run_tiled()
                times.append(time.time() - t0)
        t_eval = min(times)
        cells = counts["cells"]
        cells_per_sec = cells / t_eval
        # device-side throughput, separated from the per-dispatch host
        # round trip (not measured on the current machine): 10 async
        # dispatches, one readback.  The HEADLINE stays the sync number;
        # this detail is what a batched caller sustains.
        _enter_phase("pipelined")
        pipelined = None
        if counts_backend == "pallas":
            piped = engine.counts_pipelined_eval_s(cases)
            if piped is not None:
                dt, piped_counts = piped
                if piped_counts != counts:
                    raise AssertionError(
                        f"PIPELINED COUNTS MISMATCH: {piped_counts} != {counts}"
                    )
                pipelined = {
                    "eval_s": round(dt, 4),
                    "cells_per_sec": round(cells / dt),
                    "dispatch_overhead_s": round(t_eval - dt, 4),
                }
        _enter_phase("spot_check")
        spot_check_pairs(
            engine, policy, pods, namespaces, cases, n_samples, rng
        )
        # cross-check the MEASURED path (_counts_kernel masking/padding)
        # against the oracle-checked single-device kernel: verdicts are
        # pairwise-independent, so a random sub-cluster must yield
        # identical counts from both.
        _enter_phase("sub_parity")
        sub_n = min(n_pods, 384)
        sub_pods = [pods[i] for i in sorted(rng.sample(range(n_pods), sub_n))]
        sub_engine = TpuPolicyEngine(policy, sub_pods, namespaces)
        if counts_backend == "sharded":
            sub_counts = sub_engine.evaluate_grid_counts_sharded(
                cases, block=100
            )
        else:
            sub_counts = sub_engine.evaluate_grid_counts(
                cases, block=100, backend=counts_backend
            )
        sub_grid = sub_engine.evaluate_grid(cases)
        expected = {
            "ingress": int(np.asarray(sub_grid.ingress).sum()),
            "egress": int(np.asarray(sub_grid.egress).sum()),
            "combined": int(np.asarray(sub_grid.combined).sum()),
        }
        for k, v in expected.items():
            if sub_counts[k] != v:
                raise AssertionError(
                    f"TILED COUNTS MISMATCH on sub-cluster {k}: "
                    f"counts={sub_counts[k]} kernel={v}"
                )
        # packed-vs-unpacked parity: the same sub-cluster through an
        # engine with the CYCLONUS_PACK kill switch thrown must count
        # identically — the in-bench leg of the packed differential
        # gate (raises, never warns: wrong counts are never publishable)
        if engine._pack:
            _enter_phase("pack_parity")
            saved_pack = os.environ.get("CYCLONUS_PACK")
            os.environ["CYCLONUS_PACK"] = "0"
            try:
                unpacked_engine = TpuPolicyEngine(
                    policy, sub_pods, namespaces
                )
                unpacked = unpacked_engine.evaluate_grid_counts(
                    cases, block=100, backend="xla"
                )
            finally:
                if saved_pack is None:
                    os.environ.pop("CYCLONUS_PACK", None)
                else:
                    os.environ["CYCLONUS_PACK"] = saved_pack
            for k, v in expected.items():
                if unpacked[k] != v:
                    raise AssertionError(
                        f"PACKED PARITY MISMATCH on sub-cluster {k}: "
                        f"packed={v} unpacked={unpacked[k]}"
                    )
        allow_rate = counts["combined"] / max(cells, 1)
        # the production multi-chip fast path (tiled.py sharded +
        # kernel="pallas") Mosaic-compiles through shard_map here on a
        # 1-device Mesh over the REAL chip — the only way to validate
        # that compile path without multi-chip hardware.  Counts must
        # match the single-device kernel.
        _enter_phase("sharded_1dev")
        sharded_1dev = None
        if (
            os.environ.get("BENCH_SHARDED_1DEV", "1") == "1"
            and counts_backend == "pallas"
        ):
            import jax

            if jax.default_backend() == "tpu":
                from jax.sharding import Mesh

                from cyclonus_tpu.utils.bounded import run_bounded

                mesh_1 = Mesh(np.array(jax.devices()[:1]), ("x",))

                def _sharded_1dev_leg():
                    # first call Mosaic-compiles the shard_map+pallas
                    # program; second is the timed steady state
                    sub_engine.evaluate_grid_counts_sharded(
                        cases, mesh=mesh_1, kernel="pallas"
                    )
                    t0 = time.time()
                    c = sub_engine.evaluate_grid_counts_sharded(
                        cases, mesh=mesh_1, kernel="pallas"
                    )
                    return c, time.time() - t0

                # BOUNDED: this leg compiles a fresh program AFTER the
                # headline eval is already measured.  A wedged compile
                # must cost only this detail block, never the artifact
                # (the stall watchdog would otherwise rc=2 the whole
                # bench).
                _stall_env = float(os.environ.get("BENCH_STALL_S", "300"))
                _bound = (
                    min(150.0, _stall_env / 2) if _stall_env > 0 else 150.0
                )
                status, value = run_bounded(_sharded_1dev_leg, _bound)
                if status == "ok":
                    sp_counts, dt = value
                    sharded_1dev = {
                        "pods": sub_n,
                        "eval_s": round(dt, 4),
                        "counts_ok": all(
                            sp_counts[k] == expected[k] for k in expected
                        ),
                        "compiled": True,  # tpu backend => interpret=False
                    }
                    # a count MISMATCH is a correctness failure and must
                    # fail the bench loudly (a hang above is containable;
                    # wrong numbers are not)
                    if not sharded_1dev["counts_ok"]:
                        raise AssertionError(
                            f"SHARDED-PALLAS 1-DEV MISMATCH: {sp_counts} "
                            f"!= {expected}"
                        )
                else:
                    sharded_1dev = {
                        "pods": sub_n,
                        "status": status,
                        "error": None if status == "timeout" else repr(value),
                    }
        _enter_phase("compiled_parity")
        compiled_parity = (
            run_compiled_parity(rng)
            if os.environ.get("BENCH_PARITY", "1") == "1"
            else None
        )
        if compiled_parity and compiled_parity.get("ok") is False:
            raise AssertionError(
                f"COMPILED PALLAS PARITY FAILURE: {compiled_parity['failures']}"
            )
        _enter_phase("roofline")
        # a pinned-CPU mechanics run has no device to model; on a TPU
        # an unknown device_kind raises rather than borrow v5e peaks
        roofline = (
            roofline_model(engine, len(cases), t_eval, device["kind"])
            if counts_backend == "pallas" and device["platform"] == "tpu"
            else None
        )
        _enter_phase("mega_class")
        mega_detail = None
        mega_mode = os.environ.get("BENCH_MEGA", "auto")
        if mega_mode == "auto":
            import jax

            mega_on = jax.default_backend() == "tpu"
        else:
            mega_on = mega_mode == "1"
        if mega_on:
            from cyclonus_tpu.utils.bounded import run_bounded

            # BOUNDED like the sharded_1dev leg: the mega case compiles
            # fresh programs after the headline is measured — a wedged
            # compile must cost only this detail block.  Correctness
            # failures (oracle parity / class audit) re-raise loudly.
            _stall_env = float(os.environ.get("BENCH_STALL_S", "300"))
            _bound = (
                min(240.0, _stall_env * 0.8) if _stall_env > 0 else 600.0
            )
            status, value = run_bounded(lambda: mega_class_case(cases), _bound)
            if status == "ok":
                mega_detail = value
            elif status == "error" and isinstance(value, AssertionError):
                raise value
            else:
                mega_detail = {
                    "status": status,
                    "error": None if status == "timeout" else repr(value),
                }
        _enter_phase("mesh")
        mesh_detail = _mesh_leg(cases)
        # snapshot the telemetry block BEFORE the serve leg: its delta/
        # query churn floods the 64-entry flight-recorder ring with
        # pairs evaluations, and the BENCH telemetry block must keep
        # recording the HEADLINE engine's state (detail.serve carries
        # the serve leg's own numbers)
        tel_snapshot = telemetry.snapshot()
        _enter_phase("tiers")
        tiers_detail = _tiers_leg(cases, n_pods, n_policies)
        _enter_phase("cidr")
        cidr_detail = _cidr_leg(cases, n_pods, n_policies)
        _enter_phase("serve_churn")
        serve_detail = _serve_churn_leg(cases, n_pods, n_policies)
        done.set()
        print(
            json.dumps(
                {
                    "metric": f"simulated connectivity cells/sec ({n_pods} pods"
                    f" x {n_policies} policies, {len(cases)} port cases, "
                    f"tiled {counts_backend})",
                    "value": round(cells_per_sec),
                    "unit": "cells/sec",
                    "vs_baseline": round(
                        cells_per_sec / BASELINE_CELLS_PER_SEC, 4
                    ),
                    # a healthy run says so explicitly: "ok" is never
                    # inferred from the absence of an error
                    "failure_class": "ok",
                    "detail": {
                        "device": device,
                        "build_s": round(t_build, 3),
                        "encode_s": round(t_encode, 3),
                        "backend_init_s": round(t_init, 3),
                        # the full per-phase wall-clock (the _WD
                        # watchdog history)
                        "phase_history_s": _phase_history(),
                        # AOT executable-cache counters as of the end
                        # of warmup, and the cache-key registry census
                        "aot_cache": aot_warmup,
                        "key_audit": _key_audit(),
                        "warmup_s": round(t_warm, 3),
                        "warmup_phases": warm_phases,
                        "eval_s": round(t_eval, 4),
                        # per-rep times for transparency: rep 1 runs the
                        # fused program, rep 2 builds the split/pre-cache
                        # path, reps 3+ are the cached steady state
                        "eval_reps": [round(t, 4) for t in times],
                        # device-side rate with the per-dispatch round
                        # trip amortized over 10 in-flight evals; the
                        # headline above is the conservative sync number
                        "pipelined": pipelined,
                        "allow_rate": round(allow_rate, 4),
                        "parity_spot_checks": n_samples,
                        # host->device payload: the ENTIRE tensor transfer
                        # is this one buffer (engine/api.py _pack_tensors)
                        "packed_mb": round(engine._packed_buf.nbytes / 1e6, 2)
                        if engine._packed_buf is not None
                        else None,
                        # Mosaic-compiled kernel vs XLA path across
                        # bucketed shapes/dtypes/kernels (BENCH_PARITY=0
                        # to skip); a mismatch raises above
                        "compiled_parity": compiled_parity,
                        # which counts kernel the engine's on-device
                        # autotune picked (auto mode: slab vs default
                        # timed at the first steady-state eval), with
                        # the measured legs — None if never tuned
                        "slab": {
                            "plan": isinstance(
                                engine._slab_plan_state, dict
                            ),
                            "choice": engine._slab_choice,
                            "autotune": engine._slab_autotune,
                        },
                        # analytic limit for THIS eval's shapes on THIS
                        # device_kind: which of HBM / MXU(dense) /
                        # VPU-epilogue binds, and how close the measured
                        # eval is to it
                        "roofline": roofline,
                        # the bit-packed dtype plan: active flag, packed
                        # word depths, tuned tile winner + autotune
                        # search forensics
                        "pack": _pack_detail(engine),
                        # the multi-chip sharded-pallas program Mosaic-
                        # compiled on a 1-device Mesh over the real chip
                        # (the compile path multi-chip would use), counts
                        # pinned to the single-device kernel
                        "sharded_pallas_1dev": sharded_1dev,
                        # equivalence-class grid compression of the
                        # HEADLINE engine: pods/classes/ratio + the
                        # broadcast-back epilogue seconds
                        "class_compression": engine.class_compression_stats(),
                        # the verdict-service churn leg (BENCH_SERVE=0
                        # to skip): incremental_apply_s vs
                        # full_rebuild_s and queries/s under a seeded
                        # delta stream, with the incremental-path and
                        # differential-parity assertions enforced
                        "serve": serve_detail,
                        # the audit plane's churn-leg accounting
                        "audit": _audit_detail(serve_detail),
                        # the wire-protocol generation + live skew sweep
                        "wire": _wire_detail(),
                        # the precedence-tier leg (BENCH_TIERS=0 skips,
                        # still recording {active: False}): ANP/BANP
                        # lattice resolve_s with oracle spot parity
                        "tiers": tiers_detail,
                        # the TSS/LPM CIDR pre-classification leg
                        # (BENCH_CIDR=0 skips, still recording
                        # {active: False}): distinct CIDRs/partitions/
                        # classes/lpm_s with the dense-vs-TSS throughput
                        # comparison asserted and counts cross-checked
                        "cidr": cidr_detail,
                        # the 1M-pod synthetic case (BENCH_MEGA): the
                        # compression-only shape, with its own
                        # class_compression block, HBM-budget check,
                        # oracle spot parity, and class-reduction audit
                        "mega_class": mega_detail,
                        # the first-class mesh leg (BENCH_MESH=0 skips,
                        # rows stay [] so detail.mesh rides every line):
                        # overlapped ring counts at 1/2/4/8 devices —
                        # cells_per_sec_per_chip + ring_step_s +
                        # overlap_efficiency per row, virtual flagged —
                        # plus the ring-vs-allgather grid parity and
                        # peer-buffer watermark
                        "mesh": mesh_detail,
                        # full telemetry snapshot (metrics incl. cache
                        # hit/miss + HBM watermarks, span aggregates,
                        # flight-recorder window), captured before the
                        # serve leg — see above
                        "telemetry": tel_snapshot,
                        # device-profile provenance: the --trace-dir /
                        # BENCH_TRACE_DIR capture, and whether the
                        # profiler actually wrote an artifact
                        "trace": _trace_detail(trace_dir),
                    },
                }
            )
        )
        return

    def run():
        if sharded:
            g = engine.evaluate_grid_sharded(cases)
        else:
            g = engine.evaluate_grid(cases)
        # a scalar readback as the execution barrier
        g.allow_stats()
        return g

    # warmup (jit compile)
    _enter_phase("warmup")
    t0 = time.time()
    grid = run()
    t_warm = time.time() - t0
    # AOT forensics frozen at end of warmup (same rationale as tiled)
    aot_warmup = _aot_snapshot()

    _enter_phase("eval")
    times = []
    with jax_profile(trace_dir or None):
        for _ in range(3):
            t0 = time.time()
            grid = run()
            times.append(time.time() - t0)
    t_eval = min(times)

    cells = len(cases) * n_pods * n_pods
    cells_per_sec = cells / t_eval

    _enter_phase("spot_check")
    spot_check(policy, pods, namespaces, cases, grid, n_samples, rng)

    allow_rate = grid.allow_stats()["combined"]
    _enter_phase("mesh")
    mesh_detail = _mesh_leg(cases)
    # snapshot before the serve leg floods the flight-recorder ring
    # (same rationale as the tiled branch)
    tel_snapshot = telemetry.snapshot()
    _enter_phase("tiers")
    tiers_detail = _tiers_leg(cases, n_pods, n_policies)
    _enter_phase("serve_churn")
    serve_detail = _serve_churn_leg(cases, n_pods, n_policies)
    done.set()
    print(
        json.dumps(
            {
                "metric": f"simulated connectivity cells/sec ({n_pods} pods x "
                f"{n_policies} policies, {len(cases)} port cases, "
                f"{'sharded' if sharded else 'single-device'})",
                "value": round(cells_per_sec),
                "unit": "cells/sec",
                "vs_baseline": round(cells_per_sec / BASELINE_CELLS_PER_SEC, 4),
                "failure_class": "ok",
                "detail": {
                    "device": device,
                    "build_s": round(t_build, 3),
                    "encode_s": round(t_encode, 3),
                    "backend_init_s": round(t_init, 3),
                    "phase_history_s": _phase_history(),
                    "aot_cache": aot_warmup,
                    "key_audit": _key_audit(),
                    "warmup_s": round(t_warm, 3),
                    "eval_s": round(t_eval, 4),
                    "allow_rate": round(allow_rate, 4),
                    "parity_spot_checks": n_samples,
                    "pack": _pack_detail(engine),
                    "class_compression": engine.class_compression_stats(),
                    "mesh": mesh_detail,
                    "serve": serve_detail,
                    "audit": _audit_detail(serve_detail),
                    "wire": _wire_detail(),
                    "tiers": tiers_detail,
                    "telemetry": tel_snapshot,
                    "trace": _trace_detail(trace_dir),
                },
            }
        )
    )


if __name__ == "__main__":
    # the one command-line option; everything else stays env-driven
    # (BENCH_*) because the guard tests drive main() in-process where
    # argv belongs to the embedding interpreter
    import argparse

    _p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    _p.add_argument(
        "--trace-dir",
        default="",
        metavar="DIR",
        help="wrap the eval phase in jax.profiler.trace and write the "
        "TensorBoard/XProf capture to DIR (same as BENCH_TRACE_DIR)",
    )
    _a = _p.parse_args()
    if _a.trace_dir:
        os.environ["BENCH_TRACE_DIR"] = _a.trace_dir
    sys.exit(main())
