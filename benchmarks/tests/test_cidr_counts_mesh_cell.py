"""Run by hand: `python3 -m pytest benchmarks/tests/test_cidr_counts_mesh_cell.py -q`.

The four-chip cell `cidr-100k-10k-x4.port-sweep` (kind
`sweep_mesh_counts_generated`): its entries and files resolve; a rehearsal on
four CPU devices runs the cell's files end to end, comes out correct and
takes the pod-sharded counts route; what the cell lists per layer is a SUBSET
of what a traced line reports (not an equality: a later PR may name this cell
under a metric of its own); the control comes out not correct; a program that
does not name the held mesh counts pair is refused with exit code 4 before
anything is built; and the reader this cell brings, on hand-made layers.
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks.run import metrics_of, read_layer_metric  # noqa: E402

CELL = "cidr-100k-10k-x4.port-sweep"
# a CPU has no device trace: what a traced REHEARSAL can list
ON_ANY_BACKEND = {
    "api.result_wait_ms", "mesh.launch_ms", "mesh.launch_bytes", "setup.import_s",
    "setup.backend_s", "setup.matcher_s", "setup.engine_s", "setup.classes_s",
    "setup.program_s", "setup.warmup_s", "setup.outside_s", "setup.compiles",
}
DEVICE_TRACE = {
    "api.dispatch_ms", "kernel.device_ms", "device.peak_bytes", "mesh.collective_ms",
    "mesh.collective_wait_ms", "mesh.busy_skew", "mesh.counts_roofline",
}


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def rehearse(trace: int, **env):
    env = dict(os.environ, BENCH_REHEARSE="1", **env)
    env.pop("CYCLONUS_AOT_CACHE", None)   # as on the chip: the default cache
    env.pop("XLA_FLAGS", None)            # the kind asks for its devices itself
    env.pop("CYCLONUS_CLASS_MIN_PODS", None)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_the_cell_as_benchmark_json_describes_it():
    b = load("BENCHMARK.json")
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "port-sweep-mesh-counts"
    (config,) = [c for c in b["configs"] if c["name"] == cell["config"]]
    cfg = load(config["file"])
    assert cfg["reduced"] == config["reduced"] == ["chips"]
    assert cfg["source"] == config["source"] and len(cfg["source"]) <= 200
    assert cfg["entry"] == "evaluate_grid_counts_sharded"
    # configs[4]'s own size, as mesh-100k-10k reads it, on one four-chip host
    mesh = load("benchmarks", "configs", "mesh-100k-10k.json")
    assert cfg["sizes"] == dict(mesh["sizes"], chips=4)
    assert cfg["published"]["configs[4]"]["chips"] == 8
    assert cfg["rehearsal"] == {"pods": 660, "policies": 66, "namespaces": 4, "chips": 4}
    # the shapes are cidr-10k-5k's, key for key; only the scale moves
    assert cfg["generator"] == load("benchmarks", "configs", "cidr-10k-5k.json")["generator"]
    # the mix is the one-chip counts cells': three pairs, Q = 2
    traffic = load("benchmarks", "traffic", cell["traffic"] + ".json")
    assert traffic["kind"] == "sweep_mesh_counts_generated"
    assert traffic["case_sets"] == load("benchmarks", "traffic", "port-sweep-cidr.json")["case_sets"]
    listed = {m["name"] for m in metrics_of(b, "per_layer", CELL)}
    assert ON_ANY_BACKEND | DEVICE_TRACE <= listed
    (roofline,) = [m for m in b["per_layer"] if m["name"] == "mesh.counts_roofline"]
    assert roofline["workloads"] == [CELL] and roofline["moves"] == "sweep_cells_per_s"
    assert [m["name"] for m in metrics_of(b, "end_to_end", CELL)] == ["sweep_cells_per_s", "setup_s"]
    # at most half of the cells, rounded down, ask for four chips
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= len(b["workloads"]) // 2


def test_a_rehearsal_on_four_cpu_devices_is_correct_and_lists_what_it_reports():
    line, _ = rehearse(0)
    assert line["correct"] is True and line["rehearsal"] and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert line["would_report"] == ["setup_s", "sweep_cells_per_s"]
    assert line["checks"]["count_requests_wrong"] == {"value": 0, "limit": 0}
    assert line["attempted"] >= 3


def test_a_traced_rehearsal_takes_the_pod_sharded_route_and_reports_a_subset():
    line, err = rehearse(1)
    assert line["correct"] is True
    assert "routes: ['counts.ring']" in err
    listed = {m["name"] for m in metrics_of(load("BENCHMARK.json"), "per_layer", CELL)}
    reported = set(line["would_report"])
    # every metric a CPU can read is reported, and nothing is reported that
    # the cell does not list; the device-trace metrics need the chip
    assert ON_ANY_BACKEND <= reported <= listed
    assert not reported & DEVICE_TRACE


def test_the_control_is_not_correct():
    line, _ = rehearse(0, BENCH_CONTROL="drop_except")
    assert line["correct"] is False
    assert line["checks"]["count_requests_wrong"]["value"] > 0


def test_a_program_without_the_held_pair_is_refused_before_anything_is_built(monkeypatch):
    """The parent's mesh counts entry replicates the precompute on every chip
    and rebuilds it a request: at the cell's size a run would end two minutes
    into its set-up, out of device memory."""
    from benchmarks.kinds import sweep_generated, sweep_mesh_counts_generated as kind
    from benchmarks import program
    from cyclonus_tpu.engine import tiled

    assert program.ENTRIES["evaluate_grid_counts_sharded"] is kind.MeshCountsEntry
    assert kind.MeshCountsEntry.result == "counts"
    assert tiled.MESH_COUNTS_HELD == kind.HELD
    built = []
    monkeypatch.setattr(sweep_generated, "run", lambda cell: built.append(cell) or "ran")
    cell = types.SimpleNamespace(rehearse=False, sizes={"chips": 4})
    assert kind.run(cell) == "ran" and built == [cell]
    for before_38 in ("mesh-counts=per-call", None):
        if before_38 is None:
            monkeypatch.delattr(tiled, "MESH_COUNTS_HELD")
        else:
            monkeypatch.setattr(tiled, "MESH_COUNTS_HELD", before_38)
        with pytest.raises(SystemExit) as refused:
            kind.run(cell)
        assert refused.value.code == 4 and built == [cell]


def test_the_kinds_refusal_is_the_runs_exit_code(tmp_path):
    """run.py on a program without the constant ends with exit code 4, at
    once: a sitecustomize of the child takes the constant out of the module."""
    shim = tmp_path / "sitecustomize.py"
    shim.write_text(
        "import cyclonus_tpu.engine.tiled as t\n"
        "del t.MESH_COUNTS_HELD\n"
    )
    env = dict(os.environ, BENCH_REHEARSE="1",
               PYTHONPATH=os.pathsep.join([str(tmp_path), REPO]))
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "7",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 4, done.stderr[-2000:]
    assert "not the held pair" in done.stderr


# -- the reader, on hand-made layers ------------------------------------------

def layers(busy_s, requests):
    from benchmarks import harness

    cell = types.SimpleNamespace(
        sizes={"pods": 100000, "policies": 10000}, chips=4,
        traffic=load("benchmarks", "traffic", "port-sweep-mesh-counts.json"),
    )
    return harness.LayerContext(
        cell=cell, spans={}, counters={}, requests=requests,
        trace={"busy_s": busy_s} if busy_s is not None else None,
        device={"kind": "TPU v5 lite"},
    )


def test_counts_roofline_is_the_shapes_bytes_over_the_meshs_peak():
    from benchmarks import peaks

    # 2,960,032 bytes over four chips at 819 GB/s = 0.9035 us; 1.5 s a request
    least = peaks.grid_min_bytes(pods=100000, policies=10000, port_cases=2, result="counts")
    assert least == 100000 * 20 + 2 * 10000 * 48 + 32
    got = read_layer_metric("mesh.counts_roofline", layers(busy_s=15.0, requests=10))
    assert got == pytest.approx(100.0 * least / (4 * 819e9) / 1.5)
    assert 0.0 < got < 1e-3          # small by construction, and never 0
    for nothing in (layers(None, 10), layers(0.0, 10), layers(15.0, 0)):
        assert read_layer_metric("mesh.counts_roofline", nothing) is None
