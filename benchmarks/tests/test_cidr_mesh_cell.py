"""Run by hand: `python3 -m pytest benchmarks/tests/test_cidr_mesh_cell.py -q`.

The four-chip cell `cidr-40k-20k-x4.port-sweep` (kind `sweep_mesh_generated`):
its entries and files resolve; a rehearsal on four CPU devices comes out
correct, lists what the cell reports and takes the DENSE mesh route; the
control comes out not correct; a program that does not name the exchange of
words is refused before anything is built; and the two readers this cell
brings, on hand-made events and spans, where the right answer is plain (and
nothing to read is None, never 0).
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

CELL = "cidr-40k-20k-x4.port-sweep"
SIBLING = "tables-40k-4k-x4.port-sweep"
NEW = ["mesh.collective_wait_ms", "mesh.launch_bytes"]
SHARED = ["api.dispatch_ms", "kernel.device_ms", "readback.fetch_ms", "readback.wait_ms",
          "readback.copy_ms", "readback.shard_copy_ms", "device.peak_bytes", "mesh.launch_ms",
          "mesh.collective_ms", "mesh.busy_skew", "mesh.grid_roofline"]


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


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
    b = bench()
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "port-sweep-mesh-cidr"
    (config,) = [c for c in b["configs"] if c["name"] == cell["config"]]
    cfg = load(config["file"])
    assert cfg["reduced"] == config["reduced"] and cfg["source"] == config["source"]
    assert len(cfg["source"]) <= 200
    assert cfg["entry"] == "evaluate_grid_sharded"
    assert cfg["sizes"] == {"pods": 40000, "policies": 20000, "namespaces": 160, "chips": 4}
    assert cfg["rehearsal"]["chips"] == 4
    # the shapes are cidr-10k-5k's, key for key; only the scale moves
    small = load("benchmarks", "configs", "cidr-10k-5k.json")
    assert cfg["generator"] == small["generator"]
    assert cfg["sizes"]["pods"] // cfg["sizes"]["policies"] == 2
    assert cfg["sizes"]["pods"] // cfg["sizes"]["namespaces"] == 250
    # the mix is the sibling's, so that the two four-chip cells differ by the cluster
    traffic = load("benchmarks", "traffic", cell["traffic"] + ".json")
    assert traffic["kind"] == "sweep_mesh_generated"
    assert traffic["case_sets"] == load("benchmarks", "traffic", "port-sweep-mesh.json")["case_sets"]
    # the two metrics it brings read here alone; the shared ones in both four-chip cells
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "sweep_cells_per_s"
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL and SIBLING in by_name[name]["workloads"]
    assert sorted(m["name"] for m in metrics_of(b, "per_layer", CELL)) == sorted(NEW + SHARED)
    assert [m["name"] for m in metrics_of(b, "end_to_end", CELL)] == ["sweep_cells_per_s", "setup_s"]
    # at most half of the cells, rounded down, ask for four chips
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= len(b["workloads"]) // 2


def test_a_rehearsal_on_four_cpu_devices_is_correct_and_lists_what_it_reports():
    line, _ = rehearse(0)
    assert line["correct"] is True and line["rehearsal"] and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert line["would_report"] == ["setup_s", "sweep_cells_per_s"]
    assert line["checks"]["table_cells_wrong"] == {"value": 0, "limit": 0}
    assert line["checks"]["sampled_cells_wrong"] == {"value": 0, "limit": 0}


def test_a_traced_rehearsal_takes_the_dense_mesh_route_and_lists_the_span_metrics():
    line, err = rehearse(1)
    assert line["correct"] is True
    assert "routes: ['grid.sharded.ring']" in err
    assert {"mesh.launch_bytes", "mesh.launch_ms", "readback.shard_copy_ms",
            "readback.copy_ms", "readback.wait_ms", "readback.fetch_ms"} <= set(line["would_report"])


def test_the_control_is_not_correct():
    line, _ = rehearse(0, BENCH_CONTROL="drop_except")
    assert line["correct"] is False
    assert line["checks"]["table_cells_wrong"]["value"] > 0


def test_a_program_that_does_not_name_the_exchange_of_words_is_refused(monkeypatch):
    """Before anything is built: the parent's dense epilogue exchanges
    booleans, which the TPU compiler takes nine minutes over at the cell's
    size, and a run would sit that out as set-up."""
    from benchmarks.kinds import sweep_generated, sweep_mesh, sweep_mesh_generated
    from benchmarks import program
    from cyclonus_tpu.engine import sharded

    assert program.ENTRIES["evaluate_grid_sharded"] is sweep_mesh.MeshTablesEntry
    assert sharded.DENSE_EXCHANGE == sweep_mesh_generated.EXCHANGE
    built = []
    monkeypatch.setattr(sweep_generated, "run", lambda cell: built.append(cell) or "ran")
    cell = types.SimpleNamespace(rehearse=False, sizes={"chips": 4})
    assert sweep_mesh_generated.run(cell) == "ran" and built == [cell]
    for before_34 in ("xchg=bools", None):
        if before_34 is None:
            monkeypatch.delattr(sharded, "DENSE_EXCHANGE")
        else:
            monkeypatch.setattr(sharded, "DENSE_EXCHANGE", before_34)
        with pytest.raises(SystemExit) as refused:
            sweep_mesh_generated.run(cell)
        assert refused.value.code == 4 and built == [cell]


# -- the readers, on hand-made events and spans ------------------------------

def layers(device_events, requests=2, spans=(("bench.window", 0.0, 10.0),)):
    from benchmarks import harness

    return harness.LayerContext(
        cell=None, spans={}, counters={}, requests=requests,
        device_events=device_events, host_spans=list(spans),
        device={"kind": "TPU v5 lite"},
    )


# two chips; the ring's hop as a start / done pair, the all-to-all with no
# start half, an all-gather pair that runs over the window's end
RING = {
    "/device:TPU:0": [
        ("fusion.1_s32_10240_10240_", 1.0, 1.4),
        ("collective-permute-start.2_s32_100_10240_", 1.4, 1.5),
        ("collective-permute-done.2_s32_100_10240_", 1.6, 1.9),
        ("all-to-all.1_u32_4_1_10240_2560_", 2.0, 2.4),
        ("all-gather-start.4_pred_512_", 9.0, 9.1),
        ("all-gather-done.4_pred_512_", 9.9, 10.5),      # 0.1 inside
    ],
    "/device:TPU:1": [
        ("fusion.1_s32_10240_10240_", 1.0, 1.2),
        ("collective-permute-start.2_s32_100_10240_", 1.2, 1.3),
        ("collective-permute-done.2_s32_100_10240_", 1.3, 1.4),
        ("all_to_all.3_u32_4_1_10240_2560_", 2.0, 2.1),  # named after the JAX primitive
        ("fusion.9_all_to_all_done", 3.0, 3.2),          # a fusion, by its name
    ],
}


def test_collective_wait_counts_the_done_halves_and_the_synchronous_collectives():
    # chip 0: 0.3 + 0.4 + 0.1 (clipped) = 0.8 s; chip 1: 0.1 + 0.1 = 0.2 s; mean 0.5 s; 2 requests
    assert read_layer_metric("mesh.collective_wait_ms", layers(RING)) == pytest.approx(250.0)
    # `mesh.collective_ms` adds the starts (0.2 + 0.1) and, asking by opcode alone,
    # leaves out the exchange the compiler names `all_to_all` (0.1 on chip 1)
    assert read_layer_metric("mesh.collective_ms", layers(RING)) == pytest.approx(300.0)


def test_collective_wait_is_0_where_every_collective_only_issues_and_none_where_there_is_none():
    issued = {"/device:TPU:0": [("collective-permute-start.2", 1.0, 1.1), ("fusion.1", 1.1, 2.0)]}
    assert read_layer_metric("mesh.collective_wait_ms", layers(issued)) == 0.0
    # the all-gather schedule's gathers, as the TPU compiler names them
    gathers = {"/device:TPU:0": [("async-collective-start.1_pred_3199_10240_", 1.0, 1.2),
                                 ("async-collective-done.1_pred_3199_40960_", 1.5, 1.9)]}
    assert read_layer_metric("mesh.collective_wait_ms", layers(gathers)) == pytest.approx(200.0)
    alone = {"/device:TPU:0": [("fusion.1", 1.0, 2.0)]}
    assert read_layer_metric("mesh.collective_wait_ms", layers(alone)) is None
    for empty in ({}, None):
        assert read_layer_metric("mesh.collective_wait_ms", layers(empty)) is None
    assert read_layer_metric("mesh.collective_wait_ms", layers(RING, requests=0)) is None


def test_launch_bytes_sums_the_dispatch_spans_host_bytes(monkeypatch):
    from cyclonus_tpu.telemetry import events

    def span(name, **attrs):
        return {"name": name, "path": name, "start_s": 0.0, "dur_s": 0.2, "eval_id": 1,
                "attrs": attrs}

    ctx = types.SimpleNamespace(requests=2)
    made = {"capture": 1, "wrapped": False, "spans": [
        span("engine.dispatch_sharded", route="ring", host_bytes=100, host_operands=60),
        span("engine.dispatch_sharded", route="ring", host_bytes=140, host_operands=60),
        span("engine.dispatch", host_bytes=999),       # the one-chip span: not this layer's
    ]}
    monkeypatch.setattr(events, "capture_spans", lambda: made)
    assert read_layer_metric("mesh.launch_bytes", ctx) == pytest.approx(120.0)
    # the parent's program: the span is there, the attribute is not: nothing, never 0
    parent = dict(made, spans=[span("engine.dispatch_sharded", route="ring", devices=4)])
    monkeypatch.setattr(events, "capture_spans", lambda: parent)
    assert read_layer_metric("mesh.launch_bytes", ctx) is None
    # a window the program's ring dropped part of: a sum would be short
    monkeypatch.setattr(events, "capture_spans", lambda: dict(made, wrapped=True))
    assert read_layer_metric("mesh.launch_bytes", ctx) is None
    monkeypatch.delattr(events, "capture_spans")   # a program with no such list
    assert read_layer_metric("mesh.launch_bytes", ctx) is None
