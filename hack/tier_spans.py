#!/usr/bin/env python3
"""Run one benchmark cell traced and list what its window's TIERED requests say
of themselves (PR 40): a line a request with the evaluation's route, `tiered`,
`tier_rows`, `tier_kernel` and `tier_words`, and its readback barrier
(`engine.execute`);
then set-up's `engine.tier_encode` (anps, rules, rows) and `engine.classify`
(classes, pods) from the start-up record, and the counter
`cyclonus_tpu_tier_evals_total` by kernel beside the process's evaluations.

    python3 hack/tier_spans.py --workload anp-100k-10k.port-sweep --seed 1 --seconds 30 --trace 1

(the arguments are benchmarks/run.py's; BENCH_REHEARSE=1 rehearses on the CPU).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402

SETUP = ("engine.tier_encode", "engine.classify", "engine.new")


def say(msg: str) -> None:
    print(msg, file=sys.stderr)


def main() -> int:
    rc = run.main()
    from cyclonus_tpu.engine import tiled
    from cyclonus_tpu.telemetry import events
    from cyclonus_tpu.telemetry import instruments as ti

    by_eval = {}
    for sp in events.capture_spans()["spans"]:
        by_eval.setdefault(sp["eval_id"], {}).setdefault(sp["name"], []).append(sp)
    for eval_id, found in sorted(by_eval.items(), key=lambda kv: str(kv[0])):
        if "engine.eval" in found:
            root = found["engine.eval"][0]
            wait = sum(sp["dur_s"] for sp in found.get("engine.execute", ()))
            say(f"  eval {eval_id}: {root['dur_s'] * 1e3:.1f} ms {root['attrs']}; "
                f"execute {wait * 1e3:.1f} ms")
    for sp in events.startup_spans()["spans"]:
        if sp["name"] in SETUP:
            say(f"  set-up: {sp['name']} {sp['dur_s']:.3f} s {sp['attrs']}")
    evals = sum(v for _, v in ti.EVAL_DISPATCHES.samples())
    say("  cyclonus_tpu_tier_evals_total: " + ", ".join(
        f"{k} {ti.TIER_EVALS.value(kernel=k):.0f}" for k in (tiled.TIER_XLA, tiled.TIER_FUSED)
    ) + f"; evaluations {evals:.0f}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
