#!/usr/bin/env python3
"""Find the knee of a `wire` cell once, by hand, on the chip:

    python3 benchmarks/knee_sweep.py --config <configuration> --traffic <mix> \
        --seed <n> --rates 20,30,40,50,60,80 --seconds 15

One serve child, one window per offered rate, the mix of the cell's traffic
file at each.  Prints, per rate: lines offered and completed per second, the
backlog when the last line was due, `query_p95_ms`, `visible_mean_ms`, and
how late the generator itself ran (`late_p99_ms`).  The knee is the highest
rate with no backlog growing through the window; the cell's traffic file then
fixes its rate at about three fifths of it, as a number.  Not part of a run.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness, run  # noqa: E402
from benchmarks.kinds import wire  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    cell = run.make_cell(
        f"{args.config}.{args.traffic}", f"benchmarks/configs/{args.config}.json",
        args.traffic, 1, args.seed, args.seconds, False)
    served, pods, namespaces, policies = wire.start(cell)
    rows = []
    try:
        script = wire.Script(cell, pods)
        done = wire.warm(served, script)
        for rate in [float(r) for r in args.rates.split(",")]:
            script.window(int(rate * args.seconds))
            lines = script.lines[done:]
            done = len(script.lines)
            t0, due, sent, received, replies = served.paced([l[1] for l in lines], 1.0 / rate)
            rows.append(wire.window_stats(lines, rate, t0, due, sent, received))
            harness.say(json.dumps(rows[-1]))
    finally:
        device = served.close()
    print(json.dumps({"device": device, "rehearsal": cell.rehearse, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
