#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds everything by name, so a later PR adds files and entries and edits
nothing that exists:

  the cell           BENCHMARK.json `workloads` (config, traffic, chips)
  the configuration  the `file` of that entry of `configs`
  the traffic mix    benchmarks/traffic/<traffic>.json, whose `kind` names
  the kind           benchmarks/kinds/<kind>.py with `run(cell) -> Outcome`
  a per-layer metric benchmarks/layer_metrics/<metric>.py with
                     `read(layers) -> number or None` (None: nothing to read
                     in this cell; the metric is then left out of the line)

With --trace 0 the last line of stdout carries the cell's end-to-end metrics,
with --trace 1 its per-layer metrics and the device's busy time.  Without a
TPU (or with fewer chips than the cell asks for) it exits non-zero and prints
no result.  BENCH_REHEARSE=1 rehearses a cell on the CPU at the sizes of the
configuration's `rehearsal` block: its last line names the cpu and carries no
metric at all.  BENCH_CONTROL=<guarantee> puts the reference with that one
guarantee broken in the program's place after the window, which has to come
out as `correct: false` (the driver sets neither).
"""

import time

T_START = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def load_json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool):
    """The cell as BENCHMARK.json and the files it names describe it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    workload = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[workload["config"]]
    return make_cell(name, config["file"], workload["traffic"], workload["chips"],
                     seed, seconds, trace)


def make_cell(name, config_file, traffic, chips, seed, seconds, trace):
    """A configuration file under a traffic mix, as the environment says to
    run it (rehearsal, control)."""
    from benchmarks import harness

    rehearse = os.environ.get("BENCH_REHEARSE") == "1"
    if rehearse:
        harness.rehearsal_env()
    return harness.Cell(
        name=name, config=load_json(config_file),
        traffic=load_json("benchmarks", "traffic", traffic + ".json"),
        chips=chips, seed=seed, seconds=seconds, trace=trace,
        rehearse=rehearse, t_start=T_START,
        control=os.environ.get("BENCH_CONTROL", ""),
    )


def metrics_of(bench: dict, section: str, cell_name: str, reported: set = None):
    """The section's metrics that this cell reports."""
    out = []
    for m in bench[section]:
        cells = m.get("workloads")
        if cells is None and reported is not None:
            if m["name"] in reported or m.get("moves") in reported:
                out.append(m)
        elif cells is None or cell_name in cells:
            out.append(m)
    return out


def read_layer_metric(name: str, layers):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(layers)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmarks import harness

    if args.trace:
        # every traced run prints the PathSpec routes its requests took
        os.environ["CYCLONUS_PLANHARNESS"] = "1"
    bench = load_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace))
    kind = importlib.import_module("benchmarks.kinds." + cell.traffic["kind"])
    try:
        outcome = kind.run(cell)
    except harness.NoAccelerator as e:
        harness.say(f"benchmark: {e}")
        return 3

    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    values = {}
    if args.trace:
        for m in metrics_of(bench, "per_layer", cell.name, set(outcome.end_to_end)):
            value = read_layer_metric(m["name"], outcome.layers)
            if value is not None:
                values[m["name"]] = value
    else:
        for m in metrics_of(bench, "end_to_end", cell.name, set(outcome.end_to_end)):
            values[m["name"]] = outcome.end_to_end[m["name"]]

    device = dict(outcome.device)
    result = {
        "correct": all(value <= limit for _, value, limit in outcome.checks),
        "attempted": outcome.attempted,
        "failed": outcome.failed,
    }
    if args.trace and outcome.layers.trace:
        reduced = outcome.layers.trace
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
    if cell.rehearse:
        # a CPU run names no device metric: counts of what ran, nothing timed
        result["rehearsal"] = True
        result["metrics"] = {}
        result["would_report"] = sorted(values)
        for key in ("busy_s", "window_s"):
            device.pop(key, None)
        result.pop("breakdown", None)
    else:
        result["metrics"] = {
            name: {"value": value, "unit": units[name]} for name, value in values.items()
        }
    result["device"] = device
    result["checks"] = {
        name: {"value": value, "limit": limit} for name, value, limit in outcome.checks
    }
    for name, value, limit in outcome.checks:
        harness.say(f"check {name}: {value} (limit {limit})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
