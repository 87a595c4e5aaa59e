"""Run by hand: `python3 -m pytest benchmarks/tests/test_startup_spans.py -q`.

The door to the program's start-up record and the nine `setup.*` readers
behind it: on a hand-made record, where the right answer is plain (the eight
timed ones add up to the window's start less process start, a nested span is
counted once, a phase with no span is left out); against a program that keeps
no such record (any commit before PR 36) or whose ring has wrapped, which read
as nothing and never raise; and in a CPU rehearsal of a cell, whose line has
to list the nine under `would_report`.
"""

import json
import os
import subprocess
import sys
import threading
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import startup_spans  # noqa: E402
from benchmarks.run import metrics_of, read_layer_metric  # noqa: E402

MAIN = threading.main_thread().ident
AGE = 100.0   # the hand-made process started this long before the test
WINDOW = 60.0  # and its window opened this long after its start

# (name, start after process start, length, thread, attrs): a set-up of 60 s
RECORD = [
    ("engine.new", 5.0, 30.0, MAIN, {}),
    ("startup.import", 5.0, 3.0, MAIN, {"module": "jax"}),
    ("startup.backend", 8.0, 4.0, MAIN, {}),
    ("engine.encode", 12.0, 22.0, MAIN, {}),             # named by no metric
    ("engine.classify", 14.0, 10.0, MAIN, {}),
    ("jax.compile", 15.0, 1.0, MAIN, {"stage": "backend_compile", "cache": "miss"}),
    ("engine.class_tensors", 24.0, 2.0, MAIN, {}),
    ("matcher.build", 36.0, 2.0, MAIN, {}),
    ("engine.eval", 40.0, 10.0, MAIN, {}),
    ("engine.program", 41.0, 6.0, MAIN, {}),
    ("jax.compile", 41.5, 2.0, MAIN, {"stage": "trace"}),   # inside engine.program:
    ("jax.compile", 43.5, 3.0, MAIN, {"stage": "backend_compile", "cache": "hit"}),
    ("grid.fetch", 50.0, 4.0, MAIN, {}),
    ("grid.wait", 50.0, 1.0, MAIN, {}),                     # inside the fetch
    ("jax.compile", 55.0, 1.0, MAIN, {"stage": "backend_compile", "cache": "uncached"}),
    ("engine.eval", 20.0, 30.0, MAIN + 1, {}),              # another thread's
    ("jax.compile", 56.0, 1.0, MAIN + 1, {"stage": "backend_compile", "cache": "miss"}),
    ("engine.eval", 58.0, 5.0, MAIN, {}),                   # ends inside the window
]
EXPECTED = {
    "setup.import_s": 3.0,
    "setup.backend_s": 4.0,
    "setup.matcher_s": 2.0,
    "setup.engine_s": 30.0 - 3 - 4 - 10 - 2,    # engine.new less what is named inside it
    "setup.classes_s": 10.0 - 1 + 2,            # the compile inside classify is program_s's
    "setup.program_s": 1.0 + 6.0 + 1.0,         # 15-16, engine.program whole, 55-56
    "setup.warmup_s": (10.0 - 6.0) + 4.0,
    "setup.outside_s": WINDOW - (30.0 + 2.0 + 10.0 + 4.0 + 1.0),
    "setup.compiles": 3,                        # hits apart; every thread's
}


def hand_made(wrapped=False, names=None):
    t0 = time.time() - AGE
    return {
        "t0_epoch": t0, "closed_by": "capture", "wrapped": wrapped, "events": 36,
        "spans": [
            {"name": name, "path": name, "start_s": t0 + start, "dur_s": dur,
             "eval_id": None, "attrs": attrs, "thread": thread}
            for name, start, dur, thread, attrs in RECORD
            if names is None or name in names
        ],
    }


@pytest.fixture
def layers():
    """A run whose process started AGE seconds ago (each test gets its own:
    the door remembers what it read of a LayerContext)."""
    cell = types.SimpleNamespace(t_start=time.perf_counter() - AGE)
    return types.SimpleNamespace(cell=cell, requests=4)


def put_in_the_programs_place(monkeypatch, record, window_after=WINDOW):
    from cyclonus_tpu.telemetry import events

    if record is None:
        monkeypatch.delattr(events, "startup_spans")
    else:
        monkeypatch.setattr(events, "startup_spans", record)
    first = time.time() - AGE + window_after
    monkeypatch.setattr(events, "capture_spans", lambda: {
        "capture": 1, "wrapped": False,
        "spans": [{"name": "engine.eval", "path": "engine.eval", "start_s": first,
                   "dur_s": 1.0, "eval_id": 9, "attrs": {}}],
    })


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_on_the_hand_made_record(name, layers, monkeypatch):
    put_in_the_programs_place(monkeypatch, hand_made)
    assert read_layer_metric(name, layers) == pytest.approx(EXPECTED[name], abs=1e-3)


def test_the_eight_timed_ones_add_up_to_the_set_up(layers, monkeypatch):
    put_in_the_programs_place(monkeypatch, hand_made)
    found = startup_spans.phases(layers)
    timed = {k: v for k, v in found.items() if k != startup_spans.COMPILES}
    assert sorted(timed) == sorted(set(EXPECTED) - {startup_spans.COMPILES})
    # window start less process start, exactly: the last is the remainder
    assert sum(timed.values()) == pytest.approx(WINDOW, abs=1e-3)
    assert all(v >= 0 for v in timed.values())


def test_a_nested_span_is_counted_once():
    def sp(name, start, dur):
        return {"name": name, "start_s": start, "dur_s": dur}

    got = startup_spans.share_out(
        [sp("engine.new", 1, 8), sp("engine.classify", 2, 4),
         sp("jax.compile", 3, 2), sp("jax.compile", 3.5, 1),   # nested traces
         sp("engine.eval", 9.5, 2)],                            # runs past hi
        0.0, 10.0,
    )
    assert got == pytest.approx({
        "setup.engine_s": 4.0, "setup.classes_s": 2.0, "setup.program_s": 2.0,
        "setup.warmup_s": 0.5, "setup.outside_s": 1.5,
    })
    assert sum(got.values()) == pytest.approx(10.0)


def test_a_phase_with_no_span_is_left_out_never_0(layers, monkeypatch):
    put_in_the_programs_place(
        monkeypatch, lambda: hand_made(names=("matcher.build", "engine.eval"))
    )
    assert read_layer_metric("setup.classes_s", layers) is None
    assert read_layer_metric("setup.import_s", layers) is None
    assert read_layer_metric("setup.matcher_s", layers) == pytest.approx(2.0, abs=1e-3)
    assert read_layer_metric("setup.compiles", layers) == 0   # this one may read 0
    assert read_layer_metric("setup.outside_s", layers) == pytest.approx(
        WINDOW - 2.0 - 10.0, abs=1e-3
    )


def test_an_old_program_or_a_wrapped_record_reads_as_nothing(monkeypatch):
    def fresh():
        cell = types.SimpleNamespace(t_start=time.perf_counter() - AGE)
        return types.SimpleNamespace(cell=cell, requests=4)

    put_in_the_programs_place(monkeypatch, lambda: hand_made(wrapped=True))
    layers = fresh()
    assert all(read_layer_metric(name, layers) is None for name in EXPECTED)
    put_in_the_programs_place(monkeypatch, None)   # the parent of PR 36
    layers = fresh()
    assert startup_spans.record() is None
    assert all(read_layer_metric(name, layers) is None for name in EXPECTED)


def test_the_nine_entries_name_their_cells_and_have_their_readers():
    """All six cells, but for `setup.backend_s` in the one-shot cell: there the
    harness's `require_device` starts the backend before the program is asked to,
    so the program records no `startup.backend`, and a listed metric has to be read."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    six = [w["name"] for w in bench["workloads"]][:6]
    new = [m for m in bench["per_layer"] if m["name"].startswith("setup.")]
    assert [m["name"] for m in new] == [m["name"] for m in bench["per_layer"][-9:]]
    assert sorted(m["name"] for m in new) == sorted(EXPECTED)
    for m in new:
        cells = [
            c for c in six
            if (m["name"], c) != ("setup.backend_s", "tables-10k-1k.whatif-oneshot")
        ]
        assert m["moves"] == "setup_s" and m["workloads"] == cells
        assert (m["unit"], m["source"]) == (
            ("count", "program_counter") if m["name"] == "setup.compiles"
            else ("s", "program_span")
        )
        assert os.path.exists(
            os.path.join(REPO, "benchmarks", "layer_metrics", m["name"] + ".py")
        )


def test_a_rehearsal_lists_the_nine():
    cell = "mesh-100k-10k.port-sweep"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {
        m["name"] for m in metrics_of(bench, "per_layer", cell)
        if m["name"].startswith("setup.")
    }
    assert len(want) == 9
    env = dict(os.environ, BENCH_REHEARSE="1")
    env.pop("CYCLONUS_AOT_CACHE", None)   # as on the chip: the default cache
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "3600000019", "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    assert want <= set(line["would_report"]), line["would_report"]
    assert "startup record:" in done.stderr
