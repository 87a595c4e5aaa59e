"""Run by hand: `python3 -m pytest benchmarks/tests/test_program_spans.py -q`.

The door to the program's own spans and the nine readers behind it: on a
hand-made capture, where the right answer is plain; against a program that
keeps no such list (any commit before PR 26), which reads as nothing and never
raises; and in a CPU rehearsal of each cell, whose line has to list the cell's
new metrics under `would_report`.
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

from benchmarks import program_spans  # noqa: E402
from benchmarks.run import metrics_of, read_layer_metric  # noqa: E402

# span name -> the lengths (s) a hand-made window of 4 requests holds of it
LENGTHS = {
    "grid.wait": [0.010, 0.030], "grid.copy": [0.300, 0.500],
    "engine.case_tensors": [0.002, 0.002],   # read by no metric
    "engine.dispatch": [0.001] * 4, "engine.execute": [0.006, 0.002],
    "engine.finish": [0.0004] * 4,
    "engine.encode_policy": [0.040], "engine.build_tensors": [0.008],
    "engine.compact": [0.010], "engine.partition": [0.002],
    "engine.cidrspace": [0.001], "engine.classify": [0.006],
    "engine.class_tensors": [0.003, 0.002],
    "engine.program": [0.050, 0.030], "engine.device_put": [0.012, 0.004],
    "engine.eval": [0.1] * 4,   # read by no metric
}
# metric -> its value in ms a request: 1e3 x the summed lengths over 4
EXPECTED = {
    "readback.wait_ms": 10.0, "readback.copy_ms": 200.0,
    "api.launch_ms": 1.0,
    "api.result_wait_ms": 2.0, "api.host_finish_ms": 0.4,
    "encoding.policy_ms": 12.0, "encoding.classes_ms": 6.0,
    "api.program_load_ms": 20.0, "api.device_put_ms": 4.0,
}


def hand_made(wrapped=False):
    spans = [
        {"name": name, "path": name, "start_s": 0.0, "dur_s": dur,
         "eval_id": 1, "attrs": {}}
        for name, lengths in LENGTHS.items() for dur in lengths
    ]
    return {"capture": 1, "wrapped": wrapped, "spans": spans}


@pytest.fixture
def layers():
    return types.SimpleNamespace(requests=4)


def put_in_the_programs_place(monkeypatch, capture_spans):
    from cyclonus_tpu.telemetry import events

    if capture_spans is None:
        monkeypatch.delattr(events, "capture_spans")
    else:
        monkeypatch.setattr(events, "capture_spans", capture_spans)


def test_the_door_on_a_hand_made_capture(layers, monkeypatch):
    put_in_the_programs_place(monkeypatch, hand_made)
    assert program_spans.capture()["capture"] == 1
    assert program_spans.per_request_ms(layers, "grid.copy") == pytest.approx(200.0)
    assert program_spans.per_request_ms(
        layers, "engine.encode_policy", "engine.build_tensors"
    ) == pytest.approx(12.0)
    # nothing recorded is None, never 0; so is a window of no requests
    assert program_spans.per_request_ms(layers, "serve.query") is None
    layers.requests = 0
    assert program_spans.per_request_ms(layers, "grid.copy") is None


def test_a_wrapped_ring_or_a_program_without_the_list_reads_as_nothing(
    layers, monkeypatch
):
    put_in_the_programs_place(monkeypatch, lambda: hand_made(wrapped=True))
    assert program_spans.per_request_ms(layers, "grid.copy") is None
    put_in_the_programs_place(monkeypatch, None)   # the parent of PR 26
    assert program_spans.capture() is None
    for name in EXPECTED:
        assert read_layer_metric(name, layers) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_on_the_hand_made_capture(name, layers, monkeypatch):
    put_in_the_programs_place(monkeypatch, hand_made)
    assert read_layer_metric(name, layers) == pytest.approx(EXPECTED[name])


def test_every_new_entry_has_its_reader_and_reads_program_spans():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"] if m["name"] in EXPECTED]
    assert sorted(m["name"] for m in new) == sorted(EXPECTED)
    for m in new:
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert os.path.exists(
            os.path.join(REPO, "benchmarks", "layer_metrics", m["name"] + ".py")
        )


@pytest.mark.parametrize("cell", [
    "tables-10k-1k.port-sweep", "mesh-100k-10k.port-sweep",
    "tables-10k-1k.whatif-oneshot",
])
def test_a_rehearsal_lists_the_cells_new_metrics(cell):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {
        m["name"] for m in metrics_of(bench, "per_layer", cell)
        if m["name"] in EXPECTED
    }
    assert want
    env = dict(os.environ, BENCH_REHEARSE="1")
    env.pop("CYCLONUS_AOT_CACHE", None)   # as on the chip: the default cache
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    assert want <= set(line["would_report"]), line["would_report"]
