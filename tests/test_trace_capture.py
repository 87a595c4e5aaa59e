"""The program's spans under a real JAX profiler capture (CPU backend):

  * inside a capture a span is a `cyclonus.<name>` annotation in the
    capture's host plane, on the clock of every other annotation;
  * `events.capture_spans()` is exactly the spans opened inside the
    capture, numbered per capture, with the registry's own durations;
  * the spans of one evaluation and its fetches share one `eval_id`;
  * the span tree of the three routes the benchmark's cells run;
  * with no capture nothing is recorded and no annotation is built, and
    a `detail` span does not exist;
  * the START-UP RECORD: spans are kept from the import on, capture or
    none, until the first capture, `close_startup()` or the cap; the
    ring's other readers never see them; JAX's compiles lie in it;
  * importing telemetry, and scraping it, leave JAX alone.
"""

import contextlib
import glob
import io
import json
import os
import random
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cyclonus_tpu import telemetry  # noqa: E402
from cyclonus_tpu.telemetry import events, spans  # noqa: E402
from cyclonus_tpu.telemetry.spans import span  # noqa: E402
from cyclonus_tpu.utils.bounded import BoundedRing  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_events():
    # every test starts as a process long past its start-up: the record
    # closed (TestStartupRecord opens one of its own)
    events.disable()
    events.close_startup()
    events.reset()
    yield
    events.disable()
    events.close_startup()
    events.reset()


@contextlib.contextmanager
def capture(trace_dir):
    """A profiler capture as the benchmark's traced window makes it."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(trace_dir):
    """{event name: [(start_ns, end_ns, stats)]} of the capture's host planes."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True
    )
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                found.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                )
    return found


def by_name(found, name):
    return [sp for sp in found["spans"] if sp["name"] == name]


@pytest.fixture(scope="module")
def cluster():
    from cyclonus_tpu.synthetic import build_synthetic

    return build_synthetic(256, 24, random.Random(11))


@pytest.fixture(scope="module")
def engine(cluster):
    """A class-compressed engine, warm on both entries the cells use."""
    from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies

    pods, namespaces, policies = cluster
    eng = TpuPolicyEngine(
        build_network_policies(True, policies), pods, namespaces,
        class_compress="1",
    )
    cases = [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "", "UDP")]
    eng.evaluate_grid(cases).combined
    eng.evaluate_grid_counts(cases)
    return eng, cases


class TestAnnotation:
    def test_span_lies_in_the_host_plane_inside_an_outer_annotation(
        self, tmp_path
    ):
        with capture(tmp_path):
            with jax.profiler.TraceAnnotation("test.outer"):
                with span("cap.inner", pods=4) as s:
                    s.set(targets=3, note="x" * 200)
        found = host_events(tmp_path)
        (outer,) = found["test.outer"]
        (inner,) = found["cyclonus.cap.inner"]
        # one clock: the program's span nests inside the test's annotation
        assert outer[0] <= inner[0] <= inner[1] <= outer[1]
        # small attributes ride along, those set inside the block too; a
        # long value stays out of the trace (and in the span's own attrs)
        assert inner[2]["pods"] == 4 and inner[2]["targets"] == 3
        assert "note" not in inner[2]
        assert "cap.inner" not in found  # only the prefixed name is written

    def test_no_capture_records_nothing_and_builds_no_annotation(
        self, monkeypatch
    ):
        class Off:
            @staticmethod
            def is_enabled():
                return False

            def __init__(self, *a, **kw):
                raise AssertionError("TraceAnnotation built with no capture")

        monkeypatch.setattr(spans, "_ANNOTATION", Off)
        with span("cap.off"):
            pass
        assert events.entries() == [] and events.CAPTURE == 0
        assert events.capture_spans()["spans"] == []
        # events.ACTIVE behaves as before: B/E pairs, no capture number
        events.enable("t")
        with span("cap.active", x=1):
            pass
        b, e = events.entries()
        assert (b["ph"], e["ph"]) == ("B", "E") and b["trace_id"] == "t"
        assert "capture" not in b and "capture" not in e
        assert e["dur_s"] > 0 and e["args"] == {"x": 1}

    def test_a_detail_span_exists_only_while_something_records(self, tmp_path):
        telemetry.SPANS.reset()
        with span("cap.outer"):
            with spans.detail("cap.detail", x=1) as sp:
                assert sp is spans._NULL_SPAN
                with span("cap.leaf"):
                    pass
        # unobserved: not in the registry, and its child hangs from its parent
        assert set(telemetry.SPANS.tree()) == {"cap.outer", "cap.outer/cap.leaf"}
        assert events.entries() == []
        with capture(tmp_path):
            with span("cap.outer"):
                with spans.detail("cap.detail", x=1):
                    with span("cap.leaf"):
                        pass
        assert [sp["path"] for sp in events.capture_spans()["spans"]] == [
            "cap.outer", "cap.outer/cap.detail",
            "cap.outer/cap.detail/cap.leaf",
        ]
        assert "cyclonus.cap.detail" in host_events(tmp_path)
        events.reset()
        events.enable("t")   # an ACTIVE trace keeps a timeline too
        with spans.detail("cap.detail"):
            pass
        assert [e["name"] for e in events.entries()] == ["cap.detail"] * 2


class TestCaptureSpans:
    def test_exactly_the_spans_inside_and_the_next_capture_gets_the_next_number(
        self, tmp_path
    ):
        with span("cap.before"):
            pass
        with capture(tmp_path / "a"):
            with span("cap.outer", n=1):
                with span("cap.leaf"):
                    pass
        with span("cap.between"):
            pass
        first = events.capture_spans()
        assert [sp["path"] for sp in first["spans"]] == [
            "cap.outer", "cap.outer/cap.leaf",
        ]
        assert first["wrapped"] is False
        assert first["spans"][0]["attrs"] == {"n": 1}
        with capture(tmp_path / "b"):
            with span("cap.second"):
                pass
        second = events.capture_spans()
        assert second["capture"] == first["capture"] + 1
        assert [sp["name"] for sp in second["spans"]] == ["cap.second"]
        # an older capture is still there under its number
        assert events.capture_spans(first["capture"])["spans"] == first["spans"]

    def test_dur_s_is_what_the_registry_recorded(self, tmp_path):
        telemetry.SPANS.reset()
        with capture(tmp_path):
            with span("cap.timed"):
                sum(range(1000))
        (sp,) = events.capture_spans()["spans"]
        stats = telemetry.SPANS.stats()["cap.timed"]
        assert sp["dur_s"] == stats["total_s"] == stats["max_s"] > 0

    def test_a_wrapped_ring_is_reported(self, tmp_path, monkeypatch):
        monkeypatch.setattr(events, "RING", BoundedRing(8))
        with capture(tmp_path / "fits"):
            for _ in range(4):
                with span("cap.fits"):
                    pass
        found = events.capture_spans()
        assert found["wrapped"] is False and len(found["spans"]) == 4
        with span("cap.between"):  # the span that sees the capture gone
            pass
        with capture(tmp_path / "wraps"):
            for _ in range(10):
                with span("cap.wraps"):
                    pass
        assert events.capture_spans()["capture"] == found["capture"] + 1
        found = events.capture_spans()
        assert found["wrapped"] is True
        assert 0 < len(found["spans"]) <= 4
        assert all(sp["dur_s"] > 0 for sp in found["spans"])


@pytest.fixture
def record():
    """An open start-up record on an empty ring, as a fresh process has."""
    events._open_startup()
    return events.startup_spans


def _event(ph, name, path, ts, tid=1, **more):
    return {"ph": ph, "name": name, "path": path, "ts": ts, "pid": 7,
            "tid": tid, **more}


class TestStartupRecord:
    def test_it_holds_a_span_run_before_any_capture(self, record):
        with span("boot.outer", pods=3) as s:
            with span("boot.inner"):
                pass
            s.set(targets=2)
        found = record()
        assert found["closed_by"] is None and found["wrapped"] is False
        assert found["events"] == 4
        assert [sp["path"] for sp in found["spans"]] == [
            "boot.outer", "boot.outer/boot.inner",
        ]
        outer, inner = found["spans"]
        assert outer["attrs"] == {"pods": 3, "targets": 2}
        assert outer["thread"] == inner["thread"] != None  # noqa: E711
        assert outer["start_s"] <= inner["start_s"]
        assert inner["dur_s"] <= outer["dur_s"]
        # the process's start, on the spans' clock, is behind us
        assert 0 < found["t0_epoch"] <= outer["start_s"]
        # nothing else that reads the ring sees any of it
        assert events.entries() == [] and events.since(0) == []
        assert events.capture_spans()["spans"] == []

    @pytest.mark.parametrize("by", ["capture", "call", "cap"])
    def test_it_closes_for_good_and_records_nothing_after(
        self, record, tmp_path, monkeypatch, by
    ):
        monkeypatch.setattr(events, "STARTUP_CAP", 6)
        with span("boot.kept"):
            pass
        assert events.STARTUP is True
        if by == "capture":
            with capture(tmp_path):
                with span("cap.first"):
                    pass
            # the record is what preceded the capture's first span,
            # and the capture holds that span
            assert [
                sp["name"] for sp in events.capture_spans()["spans"]
            ] == ["cap.first"]
        elif by == "call":
            events.close_startup()
        else:
            for _ in range(2):
                with span("boot.kept"):
                    pass
            assert record()["events"] == 6
        assert events.STARTUP is False
        with span("boot.late"):
            pass
        found = record()
        assert found["closed_by"] == by
        assert {sp["name"] for sp in found["spans"]} == {"boot.kept"}
        assert len(found["spans"]) == (3 if by == "cap" else 1)
        events.close_startup("call")  # a closed record stays as it closed
        assert record()["closed_by"] == by

    def test_both_doors_pair_one_event_list_identically(self, monkeypatch):
        """startup_spans() and capture_spans() are one pairing function:
        the same hand-made events, tagged for the one and for the other,
        come back as the same spans."""
        plain = [
            _event("B", "a", "a", 10.0, args={"x": 1}),
            _event("B", "b", "a/b", 10.5, eval_id=4),
            _event("B", "t", "t", 10.6, tid=2),            # another thread
            _event("E", "b", "a/b", 11.0, eval_id=4, dur_s=0.5),
            _event("E", "c", "a/c", 12.0, dur_s=0.25),     # its B was dropped
            _event("E", "a", "a", 13.0, args={"x": 1, "y": 2}, dur_s=3.0),
            _event("B", "open", "open", 14.0),             # never closed
            _event("E", "t", "t", 15.0, tid=2, dur_s=4.4),
        ]
        want = [
            ("a", 10.0, 3.0, {"x": 1, "y": 2}, None, 1),
            ("a/b", 10.5, 0.5, {}, 4, 1),
            ("t", 10.6, 4.4, {}, None, 2),
            ("a/c", 11.75, 0.25, {}, None, 1),
        ]

        def through(tag):
            monkeypatch.setattr(events, "RING", BoundedRing(64))
            for e in plain:
                events.RING.append({**e, **tag})

        def shape(spans):
            return [
                (sp["path"], sp["start_s"], sp["dur_s"], sp["attrs"],
                 sp["eval_id"], sp["thread"])
                for sp in spans
            ]

        through({"startup": "only"})
        events._open_startup()
        events._STARTUP["first"] = 0
        from_record = events.startup_spans()["spans"]
        through({"capture": 1})
        monkeypatch.setattr(events, "_capture_seen", 1)
        monkeypatch.setattr(events, "_CAPTURE_STARTS", {1: 0})
        from_capture = events.capture_spans()["spans"]
        assert shape(from_record) == shape(from_capture) == want
        assert from_record == from_capture == events.pair_spans(plain)

    def test_wrapped_when_the_ring_has_dropped_the_first_event(
        self, monkeypatch
    ):
        monkeypatch.setattr(events, "RING", BoundedRing(8))
        events._open_startup()
        for _ in range(4):
            with span("boot.fits"):
                pass
        assert events.startup_spans()["wrapped"] is False
        with span("boot.one_more"):
            pass
        found = events.startup_spans()
        assert found["wrapped"] is True and found["events"] == 8
        assert all(sp["dur_s"] > 0 for sp in found["spans"])

    def test_a_trace_shares_the_events_and_a_worker_ships_none_of_ours(
        self, record
    ):
        with span("boot.untraced"):
            pass
        marker = events.mark()
        events.enable("t")
        with span("boot.traced"):
            pass
        events.disable()
        # one event a B or E, wanted twice: the record has both spans,
        # the trace's readers the traced one alone
        assert events.RING.appended == 4
        assert [sp["name"] for sp in record()["spans"]] == [
            "boot.untraced", "boot.traced",
        ]
        assert [e["name"] for e in events.entries()] == ["boot.traced"] * 2
        shipped = events.since(marker)
        assert [e["name"] for e in shipped] == ["boot.traced"] * 2
        # a worker's events come in without its record's tag
        foreign = [{**e, "pid": e["pid"] + 1} for e in shipped]
        assert events.ingest(foreign) == 2
        assert len(record()["spans"]) == 2
        assert len(events.entries()) == 4

    def test_with_telemetry_off_nothing_is_recorded(self):
        code = (
            "from cyclonus_tpu.telemetry import events\n"
            "from cyclonus_tpu.telemetry.spans import span, completed\n"
            "assert events.STARTUP is False\n"
            "with span('boot.x'):\n"
            "    pass\n"
            "completed('jax.compile', 0.5, stage='trace')\n"
            "found = events.startup_spans()\n"
            "assert found['spans'] == [] and found['events'] == 0, found\n"
            "assert events.RING.appended == 0\n"
        )
        subprocess.run(
            [sys.executable, "-c", code], check=True, cwd=REPO, timeout=120,
            env={**os.environ, "CYCLONUS_TELEMETRY": "0"},
        )

    def test_one_compile_for_one_new_jit_and_none_for_its_second_call(
        self, record
    ):
        from cyclonus_tpu.engine import first_import
        from cyclonus_tpu.telemetry import instruments as ti

        first_import()  # JAX is in: this registers the listener, once
        first_import()

        def compiles():
            return sum(
                ti.JAX_COMPILES.value(cache=c)
                for c in ("hit", "miss", "uncached")
            )

        def stages():
            return [
                sp["attrs"]["stage"] for sp in record()["spans"]
                if sp["name"] == "jax.compile"
            ]

        @jax.jit
        def fresh(x):  # nested jits: their traces are the outer one's
            return jax.numpy.sum(jax.numpy.sort(x * 3 + 1))

        before, seconds = compiles(), ti.JAX_COMPILE_SECONDS.value(
            stage="backend_compile"
        )
        with span("boot.program"):
            fresh(np.arange(7.0)).block_until_ready()
        assert compiles() == before + 1
        assert ti.JAX_COMPILE_SECONDS.value(stage="backend_compile") > seconds
        assert stages() == ["trace", "lower", "backend_compile"]
        compiled = [
            sp for sp in record()["spans"] if sp["name"] == "jax.compile"
        ]
        (outer,) = by_name(record(), "boot.program")
        for sp in compiled:
            assert sp["path"] == "boot.program/jax.compile"
            assert outer["start_s"] <= sp["start_s"]
            assert sp["dur_s"] > 0 and "fresh" in sp["attrs"]["fun"]
        assert compiled[-1]["attrs"]["cache"] in ("hit", "miss", "uncached")
        fresh(np.arange(7.0)).block_until_ready()
        assert compiles() == before + 1 and len(stages()) == 3

    def test_the_gauges_are_set_when_the_record_closes(self, record):
        from cyclonus_tpu.telemetry import instruments as ti

        ti._STARTUP.final = False
        with span("engine.new", pods=1):
            with span("startup.import", module="jax"):
                time.sleep(0.02)
            with span("startup.backend"):
                time.sleep(0.01)
            time.sleep(0.01)
        with span("engine.eval"):
            with span("engine.program"):
                time.sleep(0.01)
        with span("engine.eval"):   # only the first evaluation counts
            time.sleep(0.05)
        open_ = ti.startup_phases()
        assert open_["import"] >= 0.02 and open_["backend"] >= 0.01
        assert 0.01 <= open_["engine"] < 0.02 + 0.01 + 0.01
        assert open_["program"] >= 0.01 and open_["first_eval"] < 0.01
        assert open_["matcher"] == 0.0
        # a scrape refreshes them while the record is open ...
        assert "cyclonus_tpu_startup_seconds" in telemetry.render_prometheus()
        assert ti.STARTUP_SECONDS.value(phase="import") == open_["import"]
        with span("startup.import", module="jax.experimental.pallas"):
            time.sleep(0.01)
        events.close_startup()
        # ... and the close sets them for good
        closed = ti.STARTUP_SECONDS.value(phase="import")
        assert closed >= open_["import"] + 0.01
        events.reset()
        telemetry.render_prometheus()
        assert ti.STARTUP_SECONDS.value(phase="import") == closed
        lines = ti.render_startup().splitlines()
        assert lines[-1].startswith("time_to_first_verdict")

    def test_instants_are_shared_out_once_to_the_innermost_named_span(self):
        from cyclonus_tpu.telemetry.instruments import exclusive_seconds

        def sp(name, start, dur):
            return {"name": name, "start_s": start, "dur_s": dur}

        got = exclusive_seconds(
            [sp("a", 0, 10), sp("b", 1, 2), sp("c", 1.5, 1), sp("b", 5, 1),
             sp("unnamed", 3, 1), sp("a", 20, 1),
             # a compile inside a compile: counted once
             sp("b", 30, 4), sp("b", 31, 2),
             # two that start together: the longer is the outer one
             sp("c", 40, 1), sp("a", 40, 3)],
            {"a": "A", "b": "B", "c": "C"},
        )
        assert got == pytest.approx({"A": 10.0, "B": 6.0, "C": 2.0})

    def test_the_sharing_out_is_clipped_to_its_window(self):
        from cyclonus_tpu.telemetry.instruments import exclusive_seconds

        def sp(name, start, dur):
            return {"name": name, "start_s": start, "dur_s": dur}

        got = exclusive_seconds(
            [sp("a", -2, 5),      # began before lo: counted from lo
             sp("b", 4, 2), sp("b", 5, 0),
             sp("a", 9, 4),       # runs past hi: counted up to hi
             sp("c", 12, 1)],     # outside the window: there, with 0.0
            {"a": "A", "b": "B", "c": "C"}, 0.0, 10.0,
        )
        assert got == pytest.approx({"A": 4.0, "B": 2.0, "C": 0.0})

    def test_a_fresh_process_records_its_imports_backend_and_engine(self):
        code = (
            "import random, sys, threading\n"
            "from cyclonus_tpu.engine import PortCase, TpuPolicyEngine\n"
            "assert 'jax' not in sys.modules\n"
            "from cyclonus_tpu.matcher import build_network_policies\n"
            "from cyclonus_tpu.synthetic import build_synthetic\n"
            "from cyclonus_tpu.telemetry import events, instruments as ti\n"
            "pods, namespaces, policies = build_synthetic(\n"
            "    64, 8, random.Random(3))\n"
            "eng = TpuPolicyEngine(\n"
            "    build_network_policies(True, policies), pods, namespaces)\n"
            "TpuPolicyEngine(\n"
            "    build_network_policies(True, policies), pods, namespaces)\n"
            "assert ti.TIME_TO_FIRST_VERDICT.value() == 0\n"
            "eng.evaluate_grid([PortCase(80, '', 'TCP')]).combined\n"
            "found = events.startup_spans()\n"
            "paths = [sp['path'] for sp in found['spans']]\n"
            "assert paths.count('engine.new/startup.import') >= 1, paths\n"
            "assert paths.count('engine.new/startup.backend') == 1, paths\n"
            "assert paths.count('engine.new') == 2, paths\n"
            "assert 'engine.new/engine.encode' in paths\n"
            "modules = [sp['attrs']['module'] for sp in found['spans']\n"
            "           if sp['name'] == 'startup.import']\n"
            "assert modules[0] == 'jax' and len(set(modules)) == len(modules)\n"
            "(backend,) = [sp for sp in found['spans']\n"
            "              if sp['name'] == 'startup.backend']\n"
            "assert backend['attrs'] == {'platform': 'cpu', 'devices': 1}\n"
            "assert any(sp['name'] == 'jax.compile' for sp in found['spans'])\n"
            "phases = ti.startup_phases()\n"
            "assert phases['import'] > 0 and phases['engine'] > 0, phases\n"
            "age = ti.TIME_TO_FIRST_VERDICT.value()\n"
            "assert sum(phases.values()) < age < 600, (phases, age)\n"
        )
        subprocess.run(
            [sys.executable, "-c", code], check=True, cwd=REPO, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                 "CYCLONUS_AOT_CACHE": "0", "CYCLONUS_JAX_CACHE": "0"},
        )


class TestEvaluationSpans:
    def test_one_eval_id_an_evaluation_with_its_fetches(self, engine, tmp_path):
        eng, cases = engine
        telemetry.recorder.reset()
        with capture(tmp_path):
            for _ in range(2):
                out = eng.evaluate_grid(cases)
                out.ingress, out.egress, out.combined
        found = events.capture_spans()
        evals = by_name(found, "engine.eval")
        assert [sp["attrs"]["route"] for sp in evals] == ["grid.classes"] * 2
        ids = [sp["eval_id"] for sp in evals]
        assert None not in ids and ids[0] != ids[1]
        # the flight recorder's entry and the spans name each other
        assert ids == [e["seq"] for e in telemetry.recorder.entries()]
        for eval_id in ids:
            names = [
                sp["name"] for sp in found["spans"] if sp["eval_id"] == eval_id
            ]
            assert names == [
                "engine.eval", "engine.case_tensors", "engine.dispatch",
            ] + ["grid.fetch", "grid.wait", "grid.copy"] * 3
        assert all(sp["eval_id"] in ids for sp in found["spans"])
        # the counter that says the word format engaged
        copies = by_name(found, "grid.copy")
        assert [sp["attrs"]["dtype"] for sp in copies] == ["uint32"] * 6

    def test_wait_and_copy_are_the_children_of_fetch(self, engine, tmp_path):
        eng, cases = engine
        with capture(tmp_path):
            out = eng.evaluate_grid(cases)
            table = out.combined
        found = events.capture_spans()
        (fetch,) = by_name(found, "grid.fetch")
        (wait,) = by_name(found, "grid.wait")
        (copy,) = by_name(found, "grid.copy")
        assert wait["path"] == "grid.fetch/grid.wait"
        assert copy["path"] == "grid.fetch/grid.copy"
        # the single-device routes hand over 32-bit words (`form` says so
        # in any trace); the host's boolean table is a view of them
        words = np.asarray(out.combined_dev)
        assert fetch["attrs"] == {"table": "combined", "form": "words"}
        assert copy["attrs"] == {
            "bytes": words.nbytes, "dtype": "uint32", "shards": 1,
            "recycled": 0,
        }
        # a table on one device is copied whole: no shard copies, and its
        # destination is the runtime's, not a buffer of the holder's
        assert by_name(found, "grid.shard_copy") == []
        assert table.dtype == np.bool_ and np.shares_memory(table, words)
        assert wait["dur_s"] + copy["dur_s"] <= fetch["dur_s"]
        assert fetch["start_s"] <= wait["start_s"] <= copy["start_s"]
        # block_until_ready is the same wait, outside any fetch
        events.end_capture()  # as utils.tracing.jax_profile says it
        with capture(tmp_path / "ready"):
            ready = eng.evaluate_grid(cases).block_until_ready()
        (root,) = by_name(events.capture_spans(), "engine.eval")
        (wait,) = by_name(events.capture_spans(), "grid.wait")
        assert wait["path"] == "grid.wait"
        assert wait["eval_id"] == root["eval_id"] == ready.eval_id

    def test_counts_route_tree(self, engine, tmp_path):
        eng, cases = engine
        with capture(tmp_path):
            eng.evaluate_grid_counts(cases)
        found = events.capture_spans()
        assert [sp["path"] for sp in found["spans"]] == [
            "engine.eval",
            "engine.eval/engine.case_tensors",
            "engine.eval/engine.plan",
            "engine.eval/engine.dispatch",
            "engine.eval/engine.execute",
            "engine.eval/engine.finish",
        ]
        (root,) = by_name(found, "engine.eval")
        assert root["attrs"]["route"] == "counts.classes"
        children = sum(sp["dur_s"] for sp in found["spans"][1:])
        assert children <= root["dur_s"]

    @pytest.mark.parametrize("class_compress", ["1", "0"])
    def test_constructor_and_first_evaluation_tree(
        self, cluster, tmp_path, class_compress
    ):
        from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
        from cyclonus_tpu.matcher import build_network_policies

        pods, namespaces, policies = cluster
        with capture(tmp_path):
            policy = build_network_policies(True, policies)
            eng = TpuPolicyEngine(
                policy, pods, namespaces, class_compress=class_compress
            )
            eng.evaluate_grid([PortCase(80, "serve-80-tcp", "TCP")]).ingress
        found = events.capture_spans()
        (build,) = by_name(found, "matcher.build")
        assert build["attrs"]["policies"] == len(policies)
        assert build["attrs"]["targets"] > 0
        inside_encode = [
            sp["name"] for sp in found["spans"]
            if sp["path"].startswith("engine.new/engine.encode/")
        ]
        want = ["engine.encode_policy", "engine.build_tensors", "engine.compact"]
        if class_compress == "1":
            want += [
                "engine.partition", "engine.cidrspace", "engine.classify",
                "engine.class_tensors",
            ]
        assert inside_encode == want + ["engine.class_tensors"]
        # the constructor is one span, the parent of all of that
        (new,) = by_name(found, "engine.new")
        assert new["path"] == "engine.new"
        assert new["attrs"]["pods"] == len(pods) and new["attrs"]["targets"] > 0
        (root,) = by_name(found, "engine.eval")
        route = "grid.classes" if class_compress == "1" else "grid"
        assert root["attrs"]["route"] == route
        # the first evaluation sends the tensors: device_put inside
        # case_tensors; the dispatch span stays a sibling of both
        paths = {sp["path"] for sp in found["spans"]}
        assert "engine.eval/engine.case_tensors/engine.device_put" in paths
        assert "engine.eval/engine.case_tensors/engine.unpack" in paths
        assert "engine.eval/engine.dispatch" in paths
        # either single-device route hands the host 32-bit words
        (copy,) = by_name(found, "grid.copy")
        assert copy["attrs"]["dtype"] == "uint32"


class TestMeshSpans:
    """The mesh routes (`evaluate_grid_sharded`): the dispatch span names
    the route, the mesh and the schedule, the evaluation's number rides
    into the fetches as on the one-chip routes, and a row-sharded table
    comes to the host in one `grid.shard_copy` a shard."""

    @pytest.mark.parametrize(
        "route,class_compress,schedule",
        [("classes", "1", None), ("ring", "0", "ring"),
         ("allgather", "0", "allgather")],
    )
    @pytest.mark.parametrize("n_dev", [2, 4])
    def test_one_shard_copy_a_shard_under_one_eval_id(
        self, cluster, tmp_path, route, class_compress, schedule, n_dev
    ):
        from jax.sharding import Mesh

        from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
        from cyclonus_tpu.matcher import build_network_policies

        pods, namespaces, policies = cluster
        eng = TpuPolicyEngine(
            build_network_policies(True, policies), pods, namespaces,
            class_compress=class_compress,
        )
        cases = [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "", "UDP")]
        mesh = Mesh(np.array(jax.devices("cpu")[:n_dev]), ("x",))
        eng.evaluate_grid_sharded(cases, mesh=mesh, schedule=schedule)  # warm
        with capture(tmp_path):
            out = eng.evaluate_grid_sharded(cases, mesh=mesh, schedule=schedule)
            tables = out.ingress, out.egress, out.combined
        found = events.capture_spans()
        (root,) = by_name(found, "engine.eval")
        assert root["attrs"]["route"] == "grid.sharded"
        assert out.eval_id == root["eval_id"] is not None
        (dispatch,) = by_name(found, "engine.dispatch_sharded")
        assert dispatch["path"] == "engine.eval/engine.dispatch_sharded"
        assert {
            k: dispatch["attrs"][k] for k in ("route", "devices", "schedule")
        } == {
            "route": route, "devices": n_dev,
            "schedule": schedule or "ring",
        }
        copies = by_name(found, "grid.copy")
        assert len(copies) == 3
        words = np.asarray(out.combined_dev)
        for copy in copies:
            # recycled: 1 where an earlier test's tables of this shape
            # are gone (the holder is the process's)
            assert copy["attrs"] == {
                "bytes": words.nbytes, "dtype": "uint32", "shards": n_dev,
                "recycled": copy["attrs"]["recycled"],
            }
            assert copy["attrs"]["recycled"] in (0, 1)
        shard_copies = by_name(found, "grid.shard_copy")
        assert len(shard_copies) == 3 * n_dev
        assert {sp["path"] for sp in shard_copies} == {
            "grid.fetch/grid.copy/grid.shard_copy"
        }
        devices = sorted(d.id for d in mesh.devices.flat)
        for k in range(3):
            of_table = shard_copies[k * n_dev:(k + 1) * n_dev]
            assert sorted(sp["attrs"]["device"] for sp in of_table) == devices
            assert sum(sp["attrs"]["bytes"] for sp in of_table) == words.nbytes
            assert {sp["attrs"]["dtype"] for sp in of_table} == {"uint32"}
            assert sum(sp["dur_s"] for sp in of_table) <= copies[k]["dur_s"]
            for sp in of_table:
                assert 0 <= sp["attrs"]["lay_ms"] <= sp["dur_s"] * 1e3
        # the evaluation and its fetches carry the evaluation's number
        # (the case tensors are made before the sharded evaluation opens)
        for sp in found["spans"]:
            if sp["path"].startswith(("engine.eval", "grid.")):
                assert sp["eval_id"] == root["eval_id"], sp["path"]
        # one buffer of the final shape a table, viewed and not copied
        for table in tables:
            assert table.dtype == np.bool_ and table.base is not None

    def test_the_copy_says_recycled_and_a_shard_copy_its_lay_ms(
        self, cluster, tmp_path, monkeypatch
    ):
        """`grid.copy` carries `recycled` (1 where the table's buffer was
        the memory of a table nothing views any more, the counter beside
        it) and every `grid.shard_copy` `lay_ms`, the part of the span
        that writes the shard into the buffer."""
        from jax.sharding import Mesh

        from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, api
        from cyclonus_tpu.matcher import build_network_policies
        from cyclonus_tpu.telemetry import instruments as ti

        monkeypatch.setattr(api, "_host_buffers", api._HostBuffers())
        pods, namespaces, policies = cluster
        eng = TpuPolicyEngine(
            build_network_policies(True, policies), pods, namespaces,
            class_compress="1",
        )
        cases = [PortCase(80, "serve-80-tcp", "TCP")]
        mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("x",))
        before = {
            o: ti.GRID_HOST_BUFFER.value(outcome=o) for o in ("fresh", "recycled")
        }
        for turn, outcome in enumerate(["fresh", "recycled"]):
            with capture(tmp_path / outcome):
                out = eng.evaluate_grid_sharded(cases, mesh=mesh)
                tables = out.ingress, out.egress, out.combined
            # two captures with no span between them read as one
            found = {"spans": [
                sp for sp in events.capture_spans()["spans"]
                if sp["eval_id"] == out.eval_id
            ]}
            copies = by_name(found, "grid.copy")
            assert [sp["attrs"]["recycled"] for sp in copies] == [turn] * 3
            shard_copies = by_name(found, "grid.shard_copy")
            assert len(shard_copies) == 3 * 4
            for sp in shard_copies:
                assert 0 <= sp["attrs"]["lay_ms"] <= sp["dur_s"] * 1e3
            assert (
                ti.GRID_HOST_BUFFER.value(outcome=outcome) == before[outcome] + 3
            )
            del out, tables
        body = telemetry.render_prometheus()
        assert 'cyclonus_tpu_grid_host_buffer_total{outcome="recycled"}' in body
        assert 'cyclonus_tpu_grid_host_buffer_total{outcome="fresh"}' in body

    @pytest.mark.parametrize(
        "route,class_compress,schedule",
        [("classes", "1", None), ("ring", "0", "ring"),
         ("allgather", "0", "allgather")],
    )
    @pytest.mark.parametrize("n_dev", [2, 4])
    def test_the_dispatch_says_what_it_sent_and_the_eval_which_leaf_ran(
        self, cluster, tmp_path, route, class_compress, schedule, n_dev
    ):
        """`engine.dispatch_sharded` counts the host arrays among the
        program's operands and their bytes times the chips each goes to
        (an array sharded over the pods once, a replicated one to every
        chip), and says the rows a chip holds and the peer working set;
        the counter sums the bytes by route; `engine.eval` says the
        schedule and whether the class axis was evaluated."""
        from jax.sharding import Mesh

        from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, sharded
        from cyclonus_tpu.matcher import build_network_policies
        from cyclonus_tpu.telemetry import instruments as ti

        pods, namespaces, policies = cluster
        eng = TpuPolicyEngine(
            build_network_policies(True, policies), pods, namespaces,
            class_compress=class_compress,
        )
        cases = [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "", "UDP")]
        mesh = Mesh(np.array(jax.devices("cpu")[:n_dev]), ("x",))
        eng.evaluate_grid_sharded(cases, mesh=mesh, schedule=schedule)  # warm
        before = ti.MESH_DISPATCH_BYTES.value(route=route)
        with capture(tmp_path):
            eng.evaluate_grid_sharded(cases, mesh=mesh, schedule=schedule)
        found = events.capture_spans()
        # what the call hands the program, worked out here from the
        # engine's own tensors: per-pod arrays are cut over the chips
        if route == "classes":
            n_eval = eng.pod_classes().n_classes
            tensors, padded = sharded._pad_pod_arrays(
                eng._ctensors_with_cases(cases), n_eval, n_dev
            )
            step = n_dev * 8
            rows = -(-len(pods) // step) * step  # the pod -> class map, cut
            extra = [(4 * rows, 1), (4 * len(pods), n_dev)]  # and whole
        else:
            tensors, padded = sharded._pad_pod_arrays(
                eng._tensors_with_cases(cases), len(pods), n_dev * 8
            )
            extra = []
        sent = [
            (np.asarray(leaf).nbytes, 1 if path[0].key in sharded._POD_KEYS else n_dev)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tensors)[0]
        ] + extra
        (dispatch,) = by_name(found, "engine.dispatch_sharded")
        attrs = dispatch["attrs"]
        assert attrs["host_operands"] == len(sent)
        assert attrs["host_bytes"] == sum(b * chips for b, chips in sent)
        assert attrs["shard"] == padded // n_dev
        assert attrs["peer_bytes"] == ti.MESH_PEER_BYTES.value(
            schedule=schedule or "ring"
        ) > 0
        assert (
            ti.MESH_DISPATCH_BYTES.value(route=route) - before
            == attrs["host_bytes"]
        )
        (root,) = by_name(found, "engine.eval")
        assert root["attrs"] == {
            "route": "grid.sharded", "schedule": schedule or "ring",
            "classes": route == "classes",
        }

    def test_outside_a_capture_a_shard_copy_is_no_span(self, cluster):
        """`grid.shard_copy` is a detail span: it exists only while
        something keeps a timeline."""
        from jax.sharding import Mesh

        from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
        from cyclonus_tpu.matcher import build_network_policies
        from cyclonus_tpu.utils import tracing

        pods, namespaces, policies = cluster
        eng = TpuPolicyEngine(
            build_network_policies(True, policies), pods, namespaces,
            class_compress="1",
        )
        mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("x",))
        tracing.reset()
        out = eng.evaluate_grid_sharded([PortCase(80, "", "TCP")], mesh=mesh)
        assert out.combined.shape == (1, len(pods), len(pods))
        stats = tracing.stats()
        assert stats["grid.copy"]["count"] == 1
        assert "grid.shard_copy" not in stats


class TestServeSpans:
    def test_lock_wait_and_codec_round_a_line(self, tmp_path):
        from cyclonus_tpu.kube.yaml_io import parse_policy_dict
        from cyclonus_tpu.serve import VerdictService, run_stdio
        from cyclonus_tpu.worker.model import Batch, Delta, FlowQuery

        namespaces = {ns: {"ns": ns} for ns in ("x", "y")}
        pods = [
            ("xy"[i % 2], f"p{i}", {"app": f"a{i % 3}"}, f"10.0.0.{i + 1}")
            for i in range(8)
        ]
        policy = parse_policy_dict({
            "apiVersion": "networking.k8s.io/v1", "kind": "NetworkPolicy",
            "metadata": {"name": "pol0", "namespace": "x"},
            "spec": {
                "podSelector": {"matchLabels": {"app": "a0"}},
                "policyTypes": ["Ingress"],
                "ingress": [{"from": [
                    {"podSelector": {"matchLabels": {"app": "a1"}}}
                ]}],
            },
        })
        svc = VerdictService(pods, namespaces, [policy])
        line = Batch(
            namespace="", pod="", container="",
            deltas=[Delta(kind="pod_labels", namespace="x", name="p0",
                          labels={"app": "a1"})],
            queries=[FlowQuery(src="x/p0", dst="x/p2", port=80,
                               protocol="TCP", port_name="")],
        ).to_json()
        out = io.StringIO()
        with capture(tmp_path):
            assert run_stdio(svc, io.StringIO(line + "\n"), out) == 1
        assert json.loads(out.getvalue())["Applied"] == 1
        found = events.capture_spans()
        paths = [sp["path"] for sp in found["spans"]]
        assert [sp["attrs"]["side"] for sp in by_name(found, "serve.codec")] == [
            "decode", "encode",
        ]
        assert "serve.query/serve.lock_wait" in paths
        assert paths.count("serve.lock_wait") == 1  # the apply's own
        assert "serve.apply" in paths


    def test_an_interrupted_lock_acquisition_restores_the_span_path(self):
        from cyclonus_tpu.serve.service import _lock_wait

        class Interrupted:
            def __enter__(self):
                raise KeyboardInterrupt

            def __exit__(self, *exc):
                raise AssertionError("never held")

        telemetry.SPANS.reset()
        with span("cap.query"):
            with pytest.raises(KeyboardInterrupt):
                with _lock_wait() as waited, Interrupted():
                    waited.held()
            assert spans.current_path() == "cap.query"
        assert spans.current_path() == ""
        assert telemetry.SPANS.stats()["serve.lock_wait"]["count"] == 1


class TestImports:
    def test_importing_and_scraping_telemetry_leaves_jax_out(self):
        code = (
            "import sys\n"
            "import cyclonus_tpu.telemetry as t\n"
            "from cyclonus_tpu.telemetry.spans import span\n"
            "with span('x'):\n"
            "    pass\n"
            "text = t.render_prometheus()\n"
            "assert 'cyclonus_tpu_device_bytes' in text\n"
            "assert 'jax' not in sys.modules, sorted(\n"
            "    m for m in sys.modules if m.startswith('jax'))\n"
        )
        subprocess.run(
            [sys.executable, "-c", code], check=True, cwd=REPO, timeout=120
        )

    def test_device_bytes_gauge_reads_the_fullest_device(self, monkeypatch):
        from cyclonus_tpu.telemetry import instruments as ti

        class Dev:
            def __init__(self, stats):
                self._stats = stats

            def memory_stats(self):
                return self._stats

        jax.devices()  # a backend is up: the refresher may look
        monkeypatch.setattr(jax, "local_devices", lambda: [
            Dev({"bytes_in_use": 10, "peak_bytes_in_use": 70}),
            Dev({"bytes_in_use": 30, "peak_bytes_in_use": 50}),
            Dev(None),
        ])
        text = telemetry.render_prometheus()
        assert 'cyclonus_tpu_device_bytes{stat="in_use"} 30' in text
        assert 'cyclonus_tpu_device_bytes{stat="peak"} 70' in text
        assert ti.DEVICE_BYTES.value(stat="peak") == 70
