#!/usr/bin/env python3
"""Run one benchmark cell traced and list what its window's readbacks say of
themselves (PR 37): a line a request with each `grid.copy`'s `recycled` and
milliseconds, then, over the window, the `grid.shard_copy` spans' time split
into the wait for the runtime's transfer and `lay_ms`, and the counter
`cyclonus_tpu_grid_host_buffer_total` over the whole process.

    python3 hack/readback_spans.py --workload <cell> --seed 1 --seconds 30 --trace 1

(the arguments are benchmarks/run.py's; BENCH_REHEARSE=1 rehearses on the CPU).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402


def main() -> int:
    rc = run.main()
    from cyclonus_tpu.telemetry import events
    from cyclonus_tpu.telemetry import instruments as ti

    spans = events.capture_spans()["spans"]
    requests = {}
    for sp in spans:
        if sp["name"] == "grid.copy":
            requests.setdefault(sp["eval_id"], []).append(sp)
    for eval_id, copies in sorted(requests.items()):
        print(f"  eval {eval_id}: recycled "
              f"{[sp['attrs'].get('recycled') for sp in copies]} grid.copy ms "
              f"{[round(sp['dur_s'] * 1e3, 1) for sp in copies]}", file=sys.stderr)
    shard = [sp for sp in spans if sp["name"] == "grid.shard_copy"]
    if shard and requests:
        total = sum(sp["dur_s"] for sp in shard) * 1e3 / len(requests)
        lay = sum(sp["attrs"].get("lay_ms", 0.0) for sp in shard) / len(requests)
        print(f"  grid.shard_copy a request: {total:.1f} ms = wait "
              f"{total - lay:.1f} + lay_ms {lay:.1f}", file=sys.stderr)
    print("  cyclonus_tpu_grid_host_buffer_total: " + ", ".join(
        f"{o} {ti.GRID_HOST_BUFFER.value(outcome=o):.0f}"
        for o in ("fresh", "recycled")), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
