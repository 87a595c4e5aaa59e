"""Run by hand: `python3 -m pytest benchmarks/tests/test_cidr_cell.py -q`.

The cell `cidr-10k-5k.port-sweep` (kind `sweep_generated`, generator
`generators_cidr`): the cell as BENCHMARK.json describes it; a rehearsal on the
CPU that comes out correct, on the dense counts route, after the program has
computed this cluster's classes and REFUSED them; the two controls, which come
out not correct; `reference.py` against the program's scalar oracle on this
generator's shapes; and `kernel.precompute_ms` on hand-made events, where the
right answer is plain (and nothing to read is None, never 0).
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

from benchmarks import generators, generators_cidr, reference  # noqa: E402
from benchmarks.run import metrics_of, read_layer_metric  # noqa: E402

CELL = "cidr-10k-5k.port-sweep"
REPORTED = ["api.dispatch_ms", "api.launch_ms", "api.result_wait_ms", "device.peak_bytes",
            "kernel.device_ms", "kernel.grid_roofline", "kernel.precompute_ms"]


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def config():
    with open(os.path.join(REPO, "benchmarks", "configs", "cidr-10k-5k.json")) as f:
        return json.load(f)


def rehearse(trace: int, **env):
    env = dict(os.environ, BENCH_REHEARSE="1", **env)
    env.pop("CYCLONUS_AOT_CACHE", None)        # as on the chip: the default cache
    env.pop("CYCLONUS_CLASS_MIN_PODS", None)   # the kind lowers it itself
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
    assert cell["chips"] == 1 and cell["traffic"] == "port-sweep-cidr"
    (entry,) = [c for c in b["configs"] if c["name"] == cell["config"]]
    cfg = config()
    assert cfg["reduced"] == entry["reduced"] == [] and cfg["source"] == entry["source"]
    assert len(entry["source"]) <= 200 and "configs[3]" in entry["source"]
    assert cfg["sizes"] == {"pods": 10000, "policies": 5000, "namespaces": 40}
    assert cfg["entry"] == "evaluate_grid_counts"
    assert sorted(m["name"] for m in metrics_of(b, "per_layer", CELL)) == REPORTED
    assert [m["name"] for m in metrics_of(b, "end_to_end", CELL)] == [
        "sweep_cells_per_s", "setup_s"]
    (new,) = [m for m in b["per_layer"] if m["name"] == "kernel.precompute_ms"]
    assert new["workloads"] == [CELL] and new["moves"] == "sweep_cells_per_s"
    # the mix is port-sweep's three pairs, letter for letter, under the new kind
    with open(os.path.join(REPO, "benchmarks", "traffic", "port-sweep.json")) as f:
        pairs = json.load(f)["case_sets"]
    with open(os.path.join(REPO, "benchmarks", "traffic", "port-sweep-cidr.json")) as f:
        mix = json.load(f)
    assert mix["case_sets"] == pairs and mix["kind"] == "sweep_generated"


def test_a_rehearsal_is_correct_and_lists_what_it_reports():
    line, _ = rehearse(0)
    assert line["correct"] is True and line["rehearsal"] and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert line["would_report"] == ["setup_s", "sweep_cells_per_s"]
    assert line["checks"]["count_requests_wrong"] == {"value": 0, "limit": 0}
    assert line["attempted"] >= 3 and line["failed"] == 0


def test_a_traced_rehearsal_refuses_the_classes_and_takes_the_dense_route():
    line, err = rehearse(1)
    assert line["correct"] is True
    assert "routes: ['counts.pallas']" in err
    assert {"api.launch_ms", "api.result_wait_ms"} <= set(line["would_report"])


@pytest.mark.parametrize("control", ["drop_except", "drop_named_ports"])
def test_the_controls_are_not_correct(control):
    line, _ = rehearse(0, BENCH_CONTROL=control)
    assert line["correct"] is False
    assert line["checks"]["count_requests_wrong"]["value"] > 0


def test_the_rehearsal_walks_the_refusal(monkeypatch):
    """In process, at the rehearsal's sizes: the kind's engine is built at
    the program's defaults with the pod floor at the cluster's own size, so
    the TSS stage and the refusal both happen, as at 10,000 pods."""
    from benchmarks import program
    from benchmarks.kinds import sweep_generated
    from cyclonus_tpu.telemetry import instruments, spans

    monkeypatch.delenv("CYCLONUS_CLASS_MIN_PODS", raising=False)
    cfg = config()
    pods, namespaces, policies = generators_cidr.build(cfg["rehearsal"], cfg["generator"], 7)
    spans.REGISTRY.reset()
    before = instruments.CLASS_ROUTE.value(outcome="no_reduction")
    try:
        engine = sweep_generated.new_engine(
            program.build_policy(program.parse_policies(policies)), pods, namespaces, True
        )
    finally:
        os.environ.pop("CYCLONUS_CLASS_MIN_PODS", None)
    assert engine.pod_classes() is None
    assert instruments.CLASS_ROUTE.value(outcome="no_reduction") == before + 1
    attrs = {p.rsplit("/", 1)[-1]: r["attrs"] for p, r in spans.REGISTRY.tree().items()}
    assert attrs["engine.classify"]["kept"] is False
    assert attrs["engine.classify"]["classes"] > 0.9 * len(pods)
    assert attrs["engine.cidrspace"]["active"] and attrs["engine.cidrspace"]["specs"] >= 256


# -- reference.py against the program's scalar oracle, on these shapes --------

def test_the_reference_agrees_with_the_scalar_oracle():
    """Several peers a rule, egress-only policies, /32 and /8, excepts as long
    as /32: every cell of the grid form, and a sample of the scalar walk."""
    from benchmarks import program
    from cyclonus_tpu.analysis.oracle import oracle_verdicts, traffic_for_cell

    gen = dict(config()["generator"], pods_per_node=16)
    sizes = {"pods": 64, "policies": 48, "namespaces": 3}
    pods, namespaces, policies = generators_cidr.build(sizes, gen, 11)
    policy = program.build_policy(program.parse_policies(policies))
    sets = generators.case_sets([[[80, "TCP"], [81, "UDP"]]])
    cases = program.port_cases(sets[0])
    ref = reference.GridReference(pods, namespaces, policies)
    ingress, egress, combined = ref.tables(sets[0])
    by_ns = reference.policies_by_namespace(policies)
    n, wrong, allowed = len(pods), 0, 0
    for q, case in enumerate(cases):
        for s in range(n):
            for d in range(n):
                want = oracle_verdicts(policy, traffic_for_cell(pods, namespaces, case, s, d))
                got = (ingress[q, d, s], egress[q, s, d], combined[q, s, d])
                wrong += tuple(bool(x) for x in got) != tuple(want)
                allowed += want[2]
                if (s + d) % 7 == 0:
                    assert reference.flow_verdict(
                        by_ns, namespaces, pods[s], pods[d], sets[0][q]) == tuple(want)
    assert wrong == 0 and 0 < allowed < len(cases) * n * n
    counts = ref.counts(sets[0])
    assert counts["combined"] == allowed and counts["cells"] == len(cases) * n * n
    # the control reads another grid on the same cluster
    broken = reference.GridReference(pods, namespaces, policies, "drop_except")
    assert broken.counts(sets[0]) != counts


# -- the reader, on hand-made events ------------------------------------------

def layers(device_events, requests=2, spans=(("bench.window", 0.0, 10.0),)):
    from benchmarks import harness

    return harness.LayerContext(
        cell=types.SimpleNamespace(chips=1), spans={}, counters={}, requests=requests,
        device_events=device_events, host_spans=list(spans), device={"kind": "TPU v5 lite"},
    )


FUSED = {"/device:TPU:0": [
    ("fusion.3_pred_16384_10240_", 1.0, 1.3),
    ("sort.1_s32_10240_", 1.3, 1.4),
    ("_verdict_counts_pallas_packed.1_f32_2_20_128_", 1.4, 2.0),
    ("fusion.3_pred_16384_10240_", 4.0, 4.3),
    ("copy.2_s32_10240_", 4.2, 4.4),                       # overlaps: counted once
    ("_verdict_counts_pallas_packed.1_f32_2_20_128_", 4.4, 5.0),
    ("fusion.9_f32_2_20_3_", 9.9, 10.4),                   # 0.1 inside the window
]}


def test_precompute_ms_is_the_device_time_outside_the_counts_kernel():
    # 0.3 + 0.1 + 0.4 (a union) + 0.1 (clipped) = 0.9 s over 2 requests
    assert read_layer_metric("kernel.precompute_ms", layers(FUSED)) == pytest.approx(450.0)


def test_precompute_ms_reads_nothing_where_there_is_nothing_to_read():
    for empty in ({}, None):
        assert read_layer_metric("kernel.precompute_ms", layers(empty)) is None
    # a route with no Pallas counts operation (the class route's fused kernel,
    # the XLA tile loop): not the route this is about, so nothing, never 0
    other = {"/device:TPU:0": [("fusion.1_pred_512_5120_", 1.0, 2.0),
                               ("_class_rowsums_fused_kernel.1", 2.0, 3.0)]}
    assert read_layer_metric("kernel.precompute_ms", layers(other)) is None
    assert read_layer_metric("kernel.precompute_ms", layers(FUSED, requests=0)) is None
    # the kernel alone (the steady state, where no precompute runs): nothing
    alone = {"/device:TPU:0": [("_verdict_counts_pallas.2_f32_2_20_128_", 1.0, 2.0)]}
    assert read_layer_metric("kernel.precompute_ms", layers(alone)) is None
