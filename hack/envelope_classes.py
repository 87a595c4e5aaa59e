#!/usr/bin/env python3
"""Build the engine of `k8s-envelope-150k` on the host at its full size (150,004
pods, 16,650 policies; `--rehearsal` for the configuration's rehearsal) and
print what its constructor decided: the selector pass (form, label rows,
operations, seconds), the class route's outcome, the class count, the targets
and packed target words a direction, the peer rows, and the class route's HBM
estimate.  `--requests N` then times N `evaluate_grid_counts` requests over
port-sweep's pairs (that part wants a TPU).

    python3 hack/envelope_classes.py [--rehearsal] [--seed S] [--requests N]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import generators, generators_envelope, program  # noqa: E402

CONFIG = os.path.join(REPO, "benchmarks", "configs", "k8s-envelope-150k.json")
TRAFFIC = os.path.join(REPO, "benchmarks", "traffic", "port-sweep-envelope.json")
OUTCOMES = ("kept", "no_reduction", "below_floor", "no_selector_pass", "off")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--requests", type=int, default=0)
    args = ap.parse_args()
    with open(CONFIG) as f:
        cfg = json.load(f)
    sizes = cfg["rehearsal" if args.rehearsal else "sizes"]
    t0 = time.perf_counter()
    pods, namespaces, policies = generators_envelope.build(sizes, cfg["generator"], args.seed)
    policy = program.build_policy(program.parse_policies(policies))
    t1 = time.perf_counter()

    from cyclonus_tpu.engine import api
    from cyclonus_tpu.engine.encoding import packed_words
    from cyclonus_tpu.telemetry import events
    from cyclonus_tpu.telemetry import instruments as ti

    engine = api.TpuPolicyEngine(policy, pods, namespaces)
    t2 = time.perf_counter()
    print(f"pods {len(pods)}, policies {len(policies)}: generator and matcher "
          f"{t1 - t0:.2f} s, engine {t2 - t1:.2f} s")
    for sp in events.startup_spans()["spans"]:
        if sp["name"] in ("engine.selector_pass", "engine.compact", "engine.classify",
                          "engine.partition", "engine.class_tensors"):
            print(f"  {sp['name']}: {sp['dur_s']:.3f} s {sp['attrs']}")
    print("  class route: " + ", ".join(
        f"{o} {ti.CLASS_ROUTE.value(outcome=o):.0f}" for o in OUTCOMES
        if ti.CLASS_ROUTE.value(outcome=o)))
    stats = engine.class_compression_stats()
    print(f"  classes {stats['classes']} of {stats['pods']} pods, signature "
          f"{stats.get('signature_bytes')} bytes, partitions {stats['partitions']}")
    st = engine._class_state
    if st is not None:
        ct = st["ctensors"]
        for d in ("ingress", "egress"):
            t = int(ct[d]["target_ns"].shape[0])
            print(f"  {d}: targets {t} (bucketed), words {packed_words(t)}, "
                  f"peer rows {int(ct[d]['peer_kind'].shape[0])} (bucketed)")
        print(f"  selectors {int(ct['sel_req_kv'].shape[0])} (bucketed), class axis "
              f"{int(ct['pod_ns_id'].shape[0])}, HBM estimate at Q = 2: "
              f"{engine._class_counts_bytes(2)} bytes")
    if args.requests:
        with open(TRAFFIC) as f:
            sets = generators.case_sets(json.load(f)["case_sets"])
        for i in range(args.requests):
            cases = program.port_cases(sets[i % len(sets)])
            t = time.perf_counter()
            got = engine.evaluate_grid_counts(cases)
            print(f"  request {i}: {time.perf_counter() - t:.3f} s "
                  f"{ {k: int(got[k]) for k in ('ingress', 'egress', 'combined')} }")
    return 0


if __name__ == "__main__":
    sys.exit(main())
