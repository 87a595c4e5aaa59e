#!/usr/bin/env python
"""Attribute the fused counts kernel's time: matmul depth vs grid-step
overhead (VERDICT r3 item 4 groundwork).

The eval floor at the 100k x 10k bench config is ~0.14-0.15 s against a
~0.13 s dense-MXU model (2*q*N^2*(kt_e+kt_i) int8 MACs at 394.7 TOPS).
Two competing explanations for where the next 2x lives:

  A. depth-bound: the contraction (kt_e + kt_i = ~640) dominates; then
     per-src-tile target slabs (depth -> ~256) are worth ~2x.  (An r3
     windowed-slab attempt measured only 10-15%, evidence against.)
  B. step-bound: ~9.6k grid steps x fixed per-step cost (DMA setup,
     epilogue flush) dominate; then depth cuts buy nothing and the acc
     VMEM wall (16 MiB -> >= ~5k steps) is the real ceiling.

This probe separates them on hardware: it runs the SAME pod axis and
grid with the real target depth and with the depth truncated to one
128-lane chunk per direction.  If B, both times are close; if A, the
truncated run is ~(128+128)/(kt_e+kt_i) of the full one.

Usage (needs the TPU; CPU interpret mode would measure nothing real):
    python tools/kernel_probe.py [pods] [policies]
Prints one JSON line per case.
"""

import json
import random
import sys
import time

import numpy as np


def main() -> int:
    n_pods = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    n_pols = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000

    sys.path.insert(0, ".")
    from bench import build_synthetic

    from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
    from cyclonus_tpu.engine.pallas_kernel import (
        sum_partials,
        verdict_counts_pallas_rect,
    )
    from cyclonus_tpu.engine.tiled import _precompute_jit
    from cyclonus_tpu.matcher import build_network_policies

    import os

    import jax

    if jax.default_backend() != "tpu" and os.environ.get("PROBE_ALLOW_CPU") != "1":
        print(json.dumps({"error": "needs TPU (interpret mode measures nothing)"}))
        return 1

    rng = random.Random(20260729)
    pods, namespaces, policies = build_synthetic(n_pods, n_pols, rng)
    policy = build_network_policies(True, policies)
    engine = TpuPolicyEngine(policy, pods, namespaces)
    cases = [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "serve-81-udp", "UDP")]
    q = len(cases)

    # the REAL precompute the fast path runs on (compacted, ns-sorted)
    pre = _precompute_jit(engine._tensors_with_cases(cases))
    e, ig = pre["egress"], pre["ingress"]
    args_full = (
        e["tmatch"], e["has_target"], e["tallow_bf"],
        ig["tmatch"], ig["has_target"], ig["tallow_bf"],
    )
    # depth-truncated twin: one 128-lane chunk per direction, same pod
    # axis, same tile grid -> same step count, ~1/5 the MACs
    args_thin = (
        e["tmatch"][:127], e["has_target"], e["tallow_bf"][:127],
        ig["tmatch"][:127], ig["has_target"], ig["tallow_bf"][:127],
    )

    interpret = jax.default_backend() != "tpu"  # CPU smoke only

    def run(args, label):
        out = verdict_counts_pallas_rect(*args, interpret=interpret)
        np.asarray(out)  # readback barrier
        times = []
        for _ in range(5):
            t0 = time.time()
            out = verdict_counts_pallas_rect(*args, interpret=interpret)
            np.asarray(out)
            times.append(time.time() - t0)
        counts = sum_partials(out, q, 0)
        print(
            json.dumps(
                {
                    "case": label,
                    "t_e": int(args[0].shape[0]),
                    "t_i": int(args[3].shape[0]),
                    "eval_s": round(min(times), 4),
                    "reps": [round(t, 4) for t in times],
                    "combined": counts["combined"],
                }
            ),
            flush=True,
        )
        return min(times)

    full = run(args_full, "full-depth")
    thin = run(args_thin, "thin-depth-128")
    depth_full = int(args_full[0].shape[0]) + int(args_full[3].shape[0])
    print(
        json.dumps(
            {
                "case": "attribution",
                "thin_over_full": round(thin / full, 3),
                "depth_ratio": round(256 / max(depth_full, 1), 3),
                "verdict": "depth-bound (slabs worth it)"
                if thin / full < 0.6
                else "step-bound (cut grid steps, not depth)",
            }
        ),
        flush=True,
    )

    # the actual candidate: per-tile slab kernel, measured via the full
    # engine path (CYCLONUS_PALLAS_SLAB=1) so gather overhead is included.
    # The parity reference MUST be pinned before the env flips — the
    # first engine's slab plan is still unset, and a later counts call
    # would engage the slab path there too, making the check slab-vs-slab.
    os.environ["CYCLONUS_PALLAS_SLAB"] = "0"
    want = engine.evaluate_grid_counts(cases, backend="pallas")
    # apples-to-apples baseline: the DEFAULT kernel through the same
    # engine path (dispatch + pre-cache + host sum included), so the
    # flip decision isn't skewed by engine overhead absent from `full`
    base_times = []
    for _ in range(5):
        t0 = time.time()
        want = engine.evaluate_grid_counts(cases, backend="pallas")
        base_times.append(time.time() - t0)
    base = min(base_times)
    print(
        json.dumps(
            {
                "case": "default-engine-path",
                "eval_s": round(base, 4),
                "reps": [round(t, 4) for t in base_times],
            }
        ),
        flush=True,
    )
    os.environ["CYCLONUS_PALLAS_SLAB"] = "1"
    slab_engine = TpuPolicyEngine(policy, pods, namespaces)
    counts = slab_engine.evaluate_grid_counts(cases, backend="pallas")
    if slab_engine._slab_plan_state is None:
        print(json.dumps({"case": "slab", "skipped": "plan ineligible"}))
        return 0
    times = []
    for _ in range(5):
        t0 = time.time()
        counts = slab_engine.evaluate_grid_counts(cases, backend="pallas")
        times.append(time.time() - t0)
    print(
        json.dumps(
            {
                "case": "slab-engine-path",
                "eval_s": round(min(times), 4),
                "reps": [round(t, 4) for t in times],
                "speedup_vs_default_path": round(base / min(times), 2),
                "counts_match_default": counts == want,
            }
        )
    )
    if counts != want:
        print(json.dumps({"error": "SLAB COUNTS MISMATCH", "slab": counts, "want": want}))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
