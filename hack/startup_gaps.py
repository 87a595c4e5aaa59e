#!/usr/bin/env python3
"""Run one benchmark cell and list, from the program's start-up record, the
main thread's top-level spans and the stretches between them that no span
covers (where set-up time still has no span), what closed the record and how
many events and evaluations it holds, and the nine `setup.*` readings.

    python3 hack/startup_gaps.py --workload <cell> --seed 1 --seconds 2 --trace 1

(the arguments are benchmarks/run.py's; BENCH_REHEARSE=1 rehearses on the CPU).
"""

import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402


def main() -> int:
    rc = run.main()
    from cyclonus_tpu.telemetry import events

    found = events.startup_spans()
    lo = time.time() - (time.perf_counter() - run.T_START)
    main_thread = threading.main_thread().ident
    top = [
        sp for sp in found["spans"]
        if sp["thread"] == main_thread and "/" not in sp["path"]
    ]
    evals = sum(1 for sp in found["spans"] if sp["name"] == "engine.eval")
    print(
        f"record: {found['events']} events, closed by {found['closed_by']}, "
        f"wrapped {found['wrapped']}, {evals} evaluations in it (warm-up's "
        f"and, in an untraced run, the window's up to the cap)", file=sys.stderr,
    )
    cursor = lo
    for sp in top:
        if sp["start_s"] - cursor > 0.05:
            print(f"  {cursor - lo:9.3f}  +{sp['start_s'] - cursor:8.3f} s  (no span)",
                  file=sys.stderr)
        if sp["dur_s"] > 0.05:
            print(f"  {sp['start_s'] - lo:9.3f}   {sp['dur_s']:8.3f} s  {sp['name']} "
                  f"{sp['attrs']}", file=sys.stderr)
        cursor = max(cursor, sp["start_s"] + sp["dur_s"])
    from benchmarks import startup_spans

    for metric, value in (startup_spans.LAST[1] or {}).items():
        print(f"  {metric:<18}{value:10.3f}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
