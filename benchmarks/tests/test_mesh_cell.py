"""Run by hand: `python3 -m pytest benchmarks/tests/test_mesh_cell.py -q`.

The four-chip cell `tables-40k-4k-x4.port-sweep` (kind `sweep_mesh`): a
rehearsal on four CPU devices that comes out correct and lists what the cell
reports; the control, which comes out not correct; a mesh result that is not
in word form, which the kind refuses before any fetch; and the five mesh
readers on hand-made events, where the right answer is plain (and nothing to
read is None, never 0).
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

from benchmarks import mesh_trace, peaks  # noqa: E402
from benchmarks.run import metrics_of, read_layer_metric  # noqa: E402

CELL = "tables-40k-4k-x4.port-sweep"
NEW = ["mesh.launch_ms", "mesh.collective_ms", "mesh.busy_skew",
       "mesh.grid_roofline", "readback.shard_copy_ms"]


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(trace: int, **env):
    env = dict(os.environ, BENCH_REHEARSE="1", **env)
    env.pop("CYCLONUS_AOT_CACHE", None)   # as on the chip: the default cache
    env.pop("XLA_FLAGS", None)            # the kind asks for its devices itself
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_the_cell_as_benchmark_json_describes_it():
    b = bench()
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "port-sweep-mesh"
    (config,) = [c for c in b["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(REPO, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == config["reduced"] and cfg["source"] == config["source"]
    assert cfg["sizes"]["chips"] == cfg["rehearsal"]["chips"] == 4
    # the cell's own metrics read in this cell and in no other
    for m in b["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "sweep_cells_per_s"
    assert sorted(m["name"] for m in b["per_layer"] if m["name"] in NEW) == sorted(NEW)
    reported = {m["name"] for m in metrics_of(b, "per_layer", CELL)}
    assert "kernel.grid_roofline" not in reported and "api.launch_ms" not in reported
    # at most half of the cells, rounded down, ask for four chips
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= len(b["workloads"]) // 2


def test_a_rehearsal_on_four_cpu_devices_is_correct_and_lists_what_it_reports():
    line, _ = rehearse(0)
    assert line["correct"] is True and line["rehearsal"] and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert line["would_report"] == ["setup_s", "sweep_cells_per_s"]
    assert line["checks"]["table_cells_wrong"] == {"value": 0, "limit": 0}
    assert line["checks"]["sampled_cells_wrong"] == {"value": 0, "limit": 0}


def test_a_traced_rehearsal_takes_the_mesh_route_and_lists_the_span_metrics():
    line, err = rehearse(1)
    assert line["correct"] is True
    assert "routes: ['grid.sharded.classes']" in err
    assert {"mesh.launch_ms", "readback.shard_copy_ms", "readback.copy_ms",
            "readback.wait_ms", "readback.fetch_ms"} <= set(line["would_report"])


def test_the_control_is_not_correct():
    line, _ = rehearse(0, BENCH_CONTROL="drop_named_ports")
    assert line["correct"] is False
    assert line["checks"]["table_cells_wrong"]["value"] > 0


def test_a_mesh_result_that_is_not_words_is_refused_before_any_fetch(monkeypatch):
    from benchmarks import harness, program
    from benchmarks.kinds import sweep_mesh

    assert program.ENTRIES["evaluate_grid_sharded"] is sweep_mesh.MeshTablesEntry
    assert sweep_mesh.MeshTablesEntry.fetch is program.TablesEntry.fetch
    fetched = []

    class Before28:
        """An engine whose mesh entry hands back what the parent's does."""
        @staticmethod
        def evaluate_grid_sharded(cases):
            return types.SimpleNamespace(ingress_dev=types.SimpleNamespace(dtype="bool"))

    monkeypatch.setattr(program.TablesEntry, "fetch",
                        staticmethod(lambda out: fetched.append(out)))
    monkeypatch.setattr(program, "new_engine", lambda *a: Before28)
    monkeypatch.setattr(harness, "require_device", lambda cell: {})
    monkeypatch.setenv("BENCH_REHEARSE", "1")
    from benchmarks.run import make_cell

    cell = make_cell(CELL, "benchmarks/configs/tables-40k-4k-x4.json",
                     "port-sweep-mesh", 4, 5, 1.0, False)
    with pytest.raises(SystemExit) as refused:
        sweep_mesh.run(cell)
    assert refused.value.code == 4 and fetched == []


# -- the readers, on hand-made events ---------------------------------------

def layers(device_events, requests=2, chips=2, spans=(("bench.window", 0.0, 10.0),)):
    from benchmarks import harness, trace_reduce

    cell = types.SimpleNamespace(
        chips=chips, sizes={"pods": 40000, "policies": 4000},
        traffic={"case_sets": [[[80, "TCP"], [81, "UDP"]]]},
    )
    window = [(s, e) for n, s, e in spans if n == "bench.window"]
    return harness.LayerContext(
        cell=cell, spans={}, counters={}, requests=requests,
        device_events=device_events, host_spans=list(spans),
        device={"kind": "TPU v5 lite"},
        trace=trace_reduce.reduce_events(
            device_events, list(spans), window=window[0] if window else None
        ) if device_events else None,
    )


# two chips with unequal busy time; a collective-permute and an all-gather
# among fusions; one operation runs over the window's end
UNEVEN = {
    "/device:TPU:0": [
        ("fusion.1_u32_2_10000_10112_", 1.0, 1.4),
        ("collective-permute-start.2_pred_128_512_", 1.4, 1.5),
        ("collective-permute-done.2", 1.6, 1.7),
        ("copy.3_u32_2_10000_10112_", 2.0, 2.4),
        ("all-gather.4_pred_512_512_2_", 9.9, 10.5),     # 0.1 inside
    ],
    "/device:TPU:1": [
        ("fusion.1_u32_2_10000_10112_", 1.0, 1.2),
        ("all-gather.4_pred_512_512_2_", 1.2, 1.3),
        ("fusion.9_all_gathers_nothing", 3.0, 3.2),       # a fusion, by its name
    ],
}


def test_per_device_clips_to_the_window_and_busy_is_a_union():
    found = mesh_trace.per_device(layers(UNEVEN))
    assert found["/device:TPU:0"][-1] == ("all-gather.4_pred_512_512_2_", 9.9, 10.0)
    assert mesh_trace.busy_seconds(found["/device:TPU:0"]) == pytest.approx(1.1)
    assert mesh_trace.busy_seconds(found["/device:TPU:1"]) == pytest.approx(0.5)
    assert mesh_trace.busy_seconds([("a", 0.0, 2.0), ("b", 1.0, 3.0)]) == pytest.approx(3.0)
    assert mesh_trace.is_collective("all-to-all.1") and mesh_trace.is_collective("all-reduce-start.7")
    assert not mesh_trace.is_collective("fusion.9_all_gathers_nothing")


def test_collective_ms_is_the_mean_over_chips_a_request():
    # chip 0: 0.1 + 0.1 + 0.1 (clipped) = 0.3 s; chip 1: 0.1 s; mean 0.2 s; 2 requests
    assert read_layer_metric("mesh.collective_ms", layers(UNEVEN)) == pytest.approx(100.0)


def test_busy_skew_is_the_busiest_chip_over_the_mean():
    assert read_layer_metric("mesh.busy_skew", layers(UNEVEN)) == pytest.approx(1.1 / 0.8)
    even = {d: [("fusion.1", 1.0, 2.0)] for d in ("/device:TPU:0", "/device:TPU:1")}
    assert read_layer_metric("mesh.busy_skew", layers(even)) == pytest.approx(1.0)
    one = {"/device:TPU:0": [("fusion.1", 1.0, 2.0)], "/device:TPU:1": []}
    assert read_layer_metric("mesh.busy_skew", layers(one)) == pytest.approx(2.0)


def test_grid_roofline_spreads_the_bytes_over_the_cells_chips():
    least = peaks.grid_min_bytes(40000, 4000, 2, "tables") / (2 * 819e9)
    # mean busy 0.8 s over 2 requests: 0.4 s a request on each chip
    assert read_layer_metric("mesh.grid_roofline", layers(UNEVEN)) == pytest.approx(
        100.0 * least / 0.4)
    # the same bytes whatever implements them, over every chip: a device time
    # at the least time reads 100 %, and four chips need a quarter of one's
    at_peak = {f"/device:TPU:{k}": [("fusion.1", 0.0, 2 * least / 2)] for k in range(4)}
    assert read_layer_metric(
        "mesh.grid_roofline", layers(at_peak, chips=4)) == pytest.approx(100.0)


def test_no_events_read_as_nothing_never_as_0():
    for empty in ({}, None):
        ctx = layers(empty)
        for name in ("mesh.collective_ms", "mesh.busy_skew", "mesh.grid_roofline"):
            assert read_layer_metric(name, ctx) is None, name
    # fusions alone: the chips were busy, and there is no collective to read
    alone = {"/device:TPU:0": [("fusion.1", 1.0, 2.0)]}
    assert read_layer_metric("mesh.collective_ms", layers(alone)) is None
    assert read_layer_metric("mesh.busy_skew", layers(alone)) == pytest.approx(1.0)
    # no requests: nothing to divide by
    assert read_layer_metric("mesh.collective_ms", layers(UNEVEN, requests=0)) is None


@pytest.mark.parametrize("name,span", [
    ("mesh.launch_ms", "engine.dispatch_sharded"),
    ("readback.shard_copy_ms", "grid.shard_copy"),
])
def test_the_span_readers(name, span, monkeypatch):
    from cyclonus_tpu.telemetry import events

    ctx = types.SimpleNamespace(requests=2)
    made = {"capture": 1, "wrapped": False, "spans": [
        {"name": span, "path": span, "start_s": 0.0, "dur_s": d, "eval_id": 1, "attrs": {}}
        for d in (0.2, 0.3, 0.1)
    ] + [{"name": "grid.copy", "path": "grid.copy", "start_s": 0.0, "dur_s": 9.0,
          "eval_id": 1, "attrs": {}}]}
    monkeypatch.setattr(events, "capture_spans", lambda: made)
    assert read_layer_metric(name, ctx) == pytest.approx(300.0)
    # a capture without the span (the parent's program): nothing, never 0
    monkeypatch.setattr(events, "capture_spans", lambda: dict(made, spans=made["spans"][-1:]))
    assert read_layer_metric(name, ctx) is None
    monkeypatch.delattr(events, "capture_spans")   # a program with no such list
    assert read_layer_metric(name, ctx) is None
