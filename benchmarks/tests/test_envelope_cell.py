"""Run by hand: `python3 -m pytest benchmarks/tests/test_envelope_cell.py -q`.

The cell `k8s-envelope-150k.port-sweep` (kind `sweep_envelope`): its entries
and files resolve; a rehearsal on the CPU runs the cell's files end to end,
comes out correct and takes the class route; what the cell lists per layer is
a SUBSET of what a traced line reports (a later PR may name this cell under a
metric of its own); each control (`drop_end_port`, `drop_named_ports`,
`drop_except`) comes out not correct; an engine that kept no class state is
refused before the warm-up; and the two readers this cell brings, on
hand-made start-up records.
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

CELL = "k8s-envelope-150k.port-sweep"
TWIN = "mesh-100k-10k.port-sweep"
TIERED = "anp-100k-10k.port-sweep"
NEW = ("classes.count", "classes.selector_pass_s")
# a CPU has no device trace: what a traced REHEARSAL can list
ON_ANY_BACKEND = {
    "api.launch_ms", "api.result_wait_ms", "api.host_finish_ms",
    "setup.import_s", "setup.backend_s", "setup.matcher_s", "setup.engine_s",
    "setup.classes_s", "setup.program_s", "setup.warmup_s", "setup.outside_s",
    "setup.compiles",
} | set(NEW)
DEVICE_TRACE = {"api.dispatch_ms", "kernel.device_ms", "kernel.grid_roofline", "device.peak_bytes"}


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def rehearse(trace: int, seconds: float = 1, **env):
    env = dict(os.environ, BENCH_REHEARSE="1", **env)
    env.pop("CYCLONUS_AOT_CACHE", None)   # as on the chip: the default cache
    env.pop("CYCLONUS_CLASS_MIN_PODS", None)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", str(seconds), "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    return done


def last_line(done):
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_the_cell_as_benchmark_json_describes_it():
    b = load("BENCHMARK.json")
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "port-sweep-envelope"
    assert len(cell["why"]) <= 200
    (config,) = [c for c in b["configs"] if c["name"] == cell["config"]]
    cfg = load(config["file"])
    assert cfg["reduced"] == config["reduced"] == []
    assert cfg["source"] == config["source"] and len(cfg["source"]) <= 200
    assert cfg["entry"] == "evaluate_grid_counts"
    assert cfg["generator"]["module"] == "generators_envelope"
    traffic = load("benchmarks", "traffic", cell["traffic"] + ".json")
    assert traffic["kind"] == "sweep_envelope"
    assert traffic["case_sets"] == load("benchmarks", "traffic", "port-sweep.json")["case_sets"]
    listed = {m["name"] for m in metrics_of(b, "per_layer", CELL)}
    assert ON_ANY_BACKEND | DEVICE_TRACE <= listed
    for name in NEW:
        (m,) = [m for m in b["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL, TWIN, TIERED] and m["layer"] == "engine/encoding"
    assert [m["name"] for m in metrics_of(b, "end_to_end", CELL)] == ["sweep_cells_per_s", "setup_s"]


def test_a_rehearsal_is_correct():
    line, _ = last_line(rehearse(0, seconds=10))
    assert line["correct"] is True and line["rehearsal"] and line["metrics"] == {}
    assert line["would_report"] == ["setup_s", "sweep_cells_per_s"]
    assert line["checks"]["count_requests_wrong"] == {"value": 0, "limit": 0}
    assert line["attempted"] >= 3


def test_a_traced_rehearsal_takes_the_class_route_and_reports_a_subset():
    line, err = last_line(rehearse(1))
    assert line["correct"] is True
    assert "routes: ['counts.classes']" in err
    listed = {m["name"] for m in metrics_of(load("BENCHMARK.json"), "per_layer", CELL)}
    reported = set(line["would_report"])
    assert ON_ANY_BACKEND <= reported <= listed
    assert not reported & DEVICE_TRACE


@pytest.mark.parametrize("control", ["drop_end_port", "drop_named_ports", "drop_except"])
def test_each_control_is_not_correct(control):
    line, _ = last_line(rehearse(0, seconds=10, BENCH_CONTROL=control))
    assert line["attempted"] >= 3
    assert line["correct"] is False
    assert line["checks"]["count_requests_wrong"]["value"] > 0


def test_an_engine_without_classes_is_refused():
    done = rehearse(0, CYCLONUS_CLASS_COMPRESS="0")
    assert done.returncode == 4
    assert "kept no class state" in done.stderr and "class_route off" in done.stderr


# -- the readers, on hand-made start-up records --------------------------------

def layers():
    from benchmarks import harness

    return harness.LayerContext(
        cell=types.SimpleNamespace(t_start=0.0), spans={}, counters={}, requests=3,
    )


def record(*spans, wrapped=False):
    return {"closed_by": "capture", "wrapped": wrapped, "events": 2 * len(spans), "spans": [
        {"name": name, "start_s": start, "dur_s": dur, "attrs": attrs}
        for name, start, dur, attrs in spans
    ]}


@pytest.fixture
def window(monkeypatch):
    from benchmarks import program_spans

    monkeypatch.setattr(program_spans, "capture", lambda: {
        "capture": 1, "wrapped": False,
        "spans": [{"name": "engine.eval", "start_s": 100.0, "dur_s": 1.0, "attrs": {}}],
    })


def test_the_readers_read_the_set_ups_spans(monkeypatch, window):
    from benchmarks import startup_spans

    monkeypatch.setattr(startup_spans, "record", lambda: record(
        ("engine.selector_pass", 10.0, 0.5, {"rows": 16402, "how": "host"}),
        ("engine.classify", 11.0, 0.2, {"classes": 16402, "pods": 150004, "kept": True}),
        ("engine.selector_pass", 12.0, 0.25, {"rows": 3, "how": "host"}),
        # after the window's first span: not set-up's
        ("engine.classify", 101.0, 0.2, {"classes": 7, "pods": 9, "kept": True}),
    ))
    assert read_layer_metric("classes.count", layers()) == 16402
    assert read_layer_metric("classes.selector_pass_s", layers()) == pytest.approx(0.75)


@pytest.mark.parametrize("found", [
    None,                                            # no start-up record
    record(("engine.new", 1.0, 2.0, {})),            # no pass, no classify
    record(("engine.classify", 1.0, 2.0, {"classes": 5}), wrapped=True),
])
def test_the_readers_read_nothing_never_0(monkeypatch, window, found):
    from benchmarks import startup_spans

    monkeypatch.setattr(startup_spans, "record", lambda: found)
    for name in NEW:
        assert read_layer_metric(name, layers()) is None
