"""The ipBlock-heavy cluster on the MESH COUNTS entry: what the four-chip
cell `cidr-100k-10k-x4.port-sweep` runs, held here on four virtual CPU
devices at the cell's rehearsal size (660 pods, 66 policies, 4
namespaces), on the sweep's three port pairs.

  * PARITY: `evaluate_grid_counts_sharded` at its defaults == the one-chip
    `evaluate_grid_counts` == `benchmarks/reference.py`'s counts, on BOTH
    sides of the route decision (the replicated source-row route and the
    pod-sharded ring; the ceiling is the constant, lowered by
    monkeypatching it, and no environment variable);
  * ROUTE: the decision, its bytes and its ceiling are on `engine.eval`,
    the recorded PathSpec is the route's and nothing else;
  * HELD: the pair is built once per engine state; the second and third
    request trace, lower and compile nothing and send their port cases
    alone (`engine.dispatch_sharded`: `host_bytes` = 12 x Q); no
    `jax.compile` span lies inside a later request;
  * a patched buffer, another policy set and another pod set each build
    the static again (`cyclonus_tpu_static_pre_total{outcome="built"}`).
"""

import importlib.util
import os

import pytest

import cyclonus_tpu.engine.api as api
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, planspec
from cyclonus_tpu.engine import sharded as sharded_mod
from cyclonus_tpu.engine import tiled
from cyclonus_tpu.kube.yaml_io import policy_to_dict
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.synthetic import CIDR_ALLOWLISTS, cidr_allowlists
from cyclonus_tpu.telemetry import instruments as ti
from cyclonus_tpu.telemetry import spans

from test_engine_sharded import cpu_mesh

# benchmarks/traffic/port-sweep-mesh-counts.json: three pairs, Q = 2
PAIRS = [
    [PortCase(p, f"serve-{p}-{proto.lower()}", proto) for p, proto in pair]
    for pair in (
        ((80, "TCP"), (81, "UDP")),
        ((80, "UDP"), (81, "SCTP")),
        ((80, "SCTP"), (81, "TCP")),
    )
]
IDS = ["A", "B", "C"]
SIZES = (660, 66, 4)  # the configuration's rehearsal sizes
ROUTES = {"rows": "counts.sharded.xla", "ring": "counts.ring"}
KINDS = ("ingress", "egress", "combined", "cells")
STAGES = ("trace", "lower", "backend_compile")


def new_engine(pods, namespaces, policies):
    """The program's defaults, with the pod floor lowered so that `auto`
    decides as it does at 100,000 pods (it refuses class compression)."""
    floor = os.environ.get("CYCLONUS_CLASS_MIN_PODS")
    os.environ["CYCLONUS_CLASS_MIN_PODS"] = "32"
    try:
        eng = TpuPolicyEngine(
            build_network_policies(True, policies), pods, namespaces
        )
    finally:
        if floor is None:
            del os.environ["CYCLONUS_CLASS_MIN_PODS"]
        else:
            os.environ["CYCLONUS_CLASS_MIN_PODS"] = floor
    assert eng.pod_classes() is None
    return eng


@pytest.fixture(scope="module")
def cluster():
    return cidr_allowlists(*SIZES, dict(CIDR_ALLOWLISTS))


@pytest.fixture(scope="module")
def engine(cluster):
    return new_engine(*cluster)


@pytest.fixture(scope="module")
def reference(cluster):
    """`benchmarks/reference.py`, read only here: what decides the cell's
    `correct`."""
    pods, namespaces, policies = cluster
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference", os.path.join(repo, "benchmarks", "reference.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.GridReference(
        pods, namespaces, [policy_to_dict(p) for p in policies], ""
    )


@pytest.fixture
def route(request, monkeypatch):
    """Either side of the route decision, by the constant alone."""
    if request.param == "ring":
        monkeypatch.setattr(api, "_MESH_REPLICATED_MAX_BYTES", 0)
    return request.param


def both_routes(fn):
    return pytest.mark.parametrize("route", list(ROUTES), indirect=True)(fn)


def compile_state():
    """Everything that moves when JAX traces, lowers or compiles."""
    return (
        [s for _, s in ti.KERNEL_TRACES.samples()],
        [s for _, s in ti.JAX_COMPILES.samples()],
        [ti.JAX_COMPILE_SECONDS.value(stage=s) for s in STAGES],
        ti.AOT_COMPILES.value(),
    )


def spans_named(name):
    return [
        rec["attrs"] for path, rec in spans.REGISTRY.tree().items()
        if path.rsplit("/", 1)[-1] == name
    ]


def test_the_cluster_is_the_cells_rehearsal(cluster, reference):
    pods, namespaces, policies = cluster
    assert (len(pods), len(policies), len(namespaces)) == SIZES
    said = [reference.counts([(c.port, c.port_name, c.protocol) for c in pair])
            for pair in PAIRS]
    assert 0 < said[0]["combined"] < said[0]["cells"]  # the allowlists decide
    assert said[0] != said[1]


@both_routes
@pytest.mark.parametrize("pair", range(3), ids=IDS)
def test_the_mesh_entry_one_chip_and_the_reference_count_the_same(
    engine, reference, route, pair
):
    cases = PAIRS[pair]
    got = engine.evaluate_grid_counts_sharded(cases, mesh=cpu_mesh(4))
    one_chip = engine.evaluate_grid_counts(cases)
    said = reference.counts([(c.port, c.port_name, c.protocol) for c in cases])
    assert {k: got[k] for k in KINDS} == {k: one_chip[k] for k in KINDS} == said
    assert all(isinstance(got[k], int) for k in KINDS)


@pytest.mark.parametrize("pair", range(3), ids=IDS)
def test_at_the_defaults_the_mesh_is_every_device(engine, pair, monkeypatch):
    """No mesh given: `sharded.default_mesh`, all the default backend's
    devices (eight here), and the per-call ring the tests pin counts the
    same."""
    monkeypatch.setattr(api, "_MESH_REPLICATED_MAX_BYTES", 0)
    spans.REGISTRY.reset()
    got = engine.evaluate_grid_counts_sharded(PAIRS[pair])
    (root,) = spans_named("engine.eval")
    assert root["devices"] == sharded_mod.default_mesh().devices.size
    assert got == engine.evaluate_grid_counts_ring(PAIRS[pair], mesh=cpu_mesh(4))


@both_routes
def test_the_route_its_bytes_and_its_ceiling_are_on_the_evaluation(
    engine, route, monkeypatch
):
    monkeypatch.setattr(planspec, "ACTIVE", True)  # arm the recorder
    planspec.drain()
    spans.REGISTRY.reset()
    engine.evaluate_grid_counts_sharded(PAIRS[0], mesh=cpu_mesh(4))
    assert planspec.drain() == [ROUTES[route]]
    (root,) = spans_named("engine.eval")
    n_padded = -(-engine._tensors["pod_ns_id"].shape[0] // 660) * 660
    need = engine._mesh_replicated_bytes(2, n_padded)
    assert root == {
        "route": ROUTES[route], "mode": "held", "devices": 4,
        "replicated_bytes": need,
        "ceiling_bytes": api._MESH_REPLICATED_MAX_BYTES,
    }
    # from the shapes: the static half and a direction's peer_allow
    assert need > engine._static_pre_bytes() > 0
    assert (need <= root["ceiling_bytes"]) == (route == "rows")
    (sent,) = spans_named("engine.dispatch_sharded")
    assert sent["route"] == route and sent["devices"] == 4
    assert sent["shard"] == n_padded // 4
    assert len(spans_named("engine.execute")) == 1


@both_routes
def test_the_declared_plan_predicts_the_route(route):
    said = planspec.predict(
        "counts_sharded",
        {"platform": "cpu", "replicated_fits": route == "rows"},
    )
    assert said == ROUTES[route]
    assert planspec.predict(
        "counts_sharded", {"classes": True, "replicated_fits": False}
    ) == "counts.sharded.classes"


@both_routes
def test_a_later_request_compiles_nothing_and_sends_its_cases(engine, route):
    mesh = cpu_mesh(4)
    engine.evaluate_grid_counts_sharded(PAIRS[0], mesh=mesh)  # warm
    before = compile_state()
    built = ti.STATIC_PRE.value(outcome="built")
    hits = ti.STATIC_PRE.value(outcome="hit")
    sent = ti.MESH_DISPATCH_BYTES.value(route=route)
    for k, pair in enumerate((PAIRS[1], PAIRS[2]), start=1):
        spans.REGISTRY.reset()
        engine.evaluate_grid_counts_sharded(pair, mesh=mesh)
        assert compile_state() == before
        (attrs,) = spans_named("engine.dispatch_sharded")
        assert attrs["host_operands"] == 1
        assert attrs["host_bytes"] == 12 * len(pair)
        assert not spans_named("jax.compile")
        assert not spans_named("engine.static_pre")
        assert ti.STATIC_PRE.value(outcome="hit") == hits + k
        assert ti.MESH_DISPATCH_BYTES.value(route=route) == sent + 24 * k
    assert ti.STATIC_PRE.value(outcome="built") == built


@both_routes
def test_the_pair_names_itself_in_its_persistent_key(engine, route):
    engine.evaluate_grid_counts_sharded(PAIRS[0], mesh=cpu_mesh(4))
    assert tiled.MESH_COUNTS_HELD == "mesh-counts=held"
    plans = {
        fn._plan
        for key, pair in engine._mesh_counts_jits.items()
        if key[2] == route and len(key[0]) == 4
        for fn in pair
    }
    (plan,) = plans
    assert f";{tiled.MESH_COUNTS_HELD};route={route};" in plan
    assert plan.startswith(engine._aot_plan() + ";")


@both_routes
def test_a_patched_buffer_builds_the_static_again(engine, route):
    mesh = cpu_mesh(4)
    want = engine.evaluate_grid_counts_sharded(PAIRS[0], mesh=mesh)
    built = ti.STATIC_PRE.value(outcome="built")
    engine.invalidate_after_patch()
    spans.REGISTRY.reset()
    assert engine.evaluate_grid_counts_sharded(PAIRS[0], mesh=mesh) == want
    assert ti.STATIC_PRE.value(outcome="built") == built + 1
    (static,) = spans_named("engine.static_pre")
    assert static["devices"] == 4 and static["bytes"] > 0
    assert ti.STATIC_PRE_BYTES.value() == static["bytes"]


@both_routes
@pytest.mark.parametrize("change", ["policies", "pods"])
def test_another_policy_or_pod_set_builds_its_own_static(
    cluster, engine, reference, route, change
):
    """A new engine state never answers from another's static: the counts
    are the changed cluster's, by the reference."""
    pods, namespaces, policies = cluster
    if change == "policies":
        policies = policies[: len(policies) // 2]
    else:
        pods = pods[:-60]
    mesh = cpu_mesh(4)
    engine.evaluate_grid_counts_sharded(PAIRS[0], mesh=mesh)
    built = ti.STATIC_PRE.value(outcome="built")
    other = new_engine(pods, namespaces, policies)
    got = other.evaluate_grid_counts_sharded(PAIRS[0], mesh=mesh)
    assert ti.STATIC_PRE.value(outcome="built") == built + 1
    ref = type(reference)(
        pods, namespaces, [policy_to_dict(p) for p in policies], ""
    )
    assert got == ref.counts(
        [(c.port, c.port_name, c.protocol) for c in PAIRS[0]]
    )
    assert got != engine.evaluate_grid_counts_sharded(PAIRS[0], mesh=mesh)
