"""The program's spans under a real JAX profiler capture (CPU backend):

  * inside a capture a span is a `cyclonus.<name>` annotation in the
    capture's host plane, on the clock of every other annotation;
  * `events.capture_spans()` is exactly the spans opened inside the
    capture, numbered per capture, with the registry's own durations;
  * the spans of one evaluation and its fetches share one `eval_id`;
  * the span tree of the three routes the benchmark's cells run;
  * with no capture nothing is recorded and no annotation is built, and
    a `detail` span does not exist;
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
    events.disable()
    events.reset()
    yield
    events.disable()
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
        }
        # a table on one device is copied whole: no shard copies
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
            if sp["path"].startswith("engine.encode/")
        ]
        want = ["engine.encode_policy", "engine.build_tensors", "engine.compact"]
        if class_compress == "1":
            want += [
                "engine.partition", "engine.cidrspace", "engine.classify",
                "engine.class_tensors",
            ]
        assert inside_encode == want + ["engine.class_tensors"]
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
            assert copy["attrs"] == {
                "bytes": words.nbytes, "dtype": "uint32", "shards": n_dev,
            }
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
        # the evaluation and its fetches carry the evaluation's number
        # (the case tensors are made before the sharded evaluation opens)
        for sp in found["spans"]:
            if sp["path"].startswith(("engine.eval", "grid.")):
                assert sp["eval_id"] == root["eval_id"], sp["path"]
        # one buffer of the final shape a table, viewed and not copied
        for table in tables:
            assert table.dtype == np.bool_ and table.base is not None

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
