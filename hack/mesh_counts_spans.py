#!/usr/bin/env python3
"""Run one benchmark cell traced and list what its window's MESH COUNTS
requests say of themselves (PR 38): a line a request with the evaluation's
route, mode, devices and the bytes its route was decided from, what its launch
sent (`engine.dispatch_sharded`) and how long its readback barrier waited
(`engine.execute`); then every `jax.compile`, `engine.program` and
`engine.static_pre` span that lies INSIDE the window (there should be none:
the pair is built and the static placed in set-up), the start-up record's
`engine.static_pre` and the counter `cyclonus_tpu_static_pre_total`.

    python3 hack/mesh_counts_spans.py --workload <cell> --seed 1 --seconds 30 --trace 1

(the arguments are benchmarks/run.py's; BENCH_REHEARSE=1 rehearses on the CPU).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402

SETUP_ONLY = ("jax.compile", "engine.program", "engine.static_pre")


def main() -> int:
    rc = run.main()
    from cyclonus_tpu.telemetry import events
    from cyclonus_tpu.telemetry import instruments as ti

    spans = events.capture_spans()["spans"]
    by_eval = {}
    for sp in spans:
        by_eval.setdefault(sp["eval_id"], {}).setdefault(sp["name"], []).append(sp)
    for eval_id, found in sorted(by_eval.items(), key=lambda kv: str(kv[0])):
        if "engine.eval" not in found:
            continue
        root = found["engine.eval"][0]
        sent = found.get("engine.dispatch_sharded", [{"attrs": {}, "dur_s": 0.0}])[0]
        wait = sum(sp["dur_s"] for sp in found.get("engine.execute", ()))
        print(f"  eval {eval_id}: {root['dur_s'] * 1e3:.1f} ms {root['attrs']}; launch "
              f"{sent['dur_s'] * 1e3:.2f} ms {sent['attrs']}; execute "
              f"{wait * 1e3:.1f} ms", file=sys.stderr)
    inside = [sp for sp in spans if sp["name"] in SETUP_ONLY]
    print(f"  spans of set-up's kind inside the window: {len(inside)} "
          f"{[(sp['name'], sp['attrs']) for sp in inside]}", file=sys.stderr)
    for sp in events.startup_spans()["spans"]:
        if sp["name"] == "engine.static_pre":
            print(f"  set-up: engine.static_pre {sp['dur_s']:.3f} s {sp['attrs']}",
                  file=sys.stderr)
    print("  cyclonus_tpu_static_pre_total: " + ", ".join(
        f"{o} {ti.STATIC_PRE.value(outcome=o):.0f}"
        for o in ("built", "hit", "declined")), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
