"""synthetic.cidr_allowlists: the cluster whose IP structure cuts across
its labels, and what the engine does with it.

  * PARITY: a small such cluster through TpuPolicyEngine, full tables
    and counts, against the scalar oracle, on every side of the two
    hand-offs it stands at: class_compress auto / 0 / 1 x cidr_tss
    auto / 0 / 1 (with the pod floor lowered, so `auto` decides);
  * REFUSAL: above the pod floor `auto` computes the classes, finds no
    reduction and keeps no class state: the dense routes take every
    call, cyclonus_tpu_class_route_total{outcome="no_reduction"}
    counts it, `engine.classify` says kept=False and `engine.cidrspace`
    what was dropped; the other outcomes count too;
  * the dense counts evaluation's `engine.eval` span names the program
    that ran (`mode`: resident / fused / split / steady), as its flight
    entry does;
  * the benchmark's plain reference (`benchmarks/reference.py`, read-only
    here) reads what this generator emits as the scalar oracle does.
"""

import importlib.util
import os

import numpy as np
import pytest

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, planspec
from cyclonus_tpu.kube.yaml_io import policy_to_dict
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.synthetic import CIDR_ALLOWLISTS, cidr_allowlists
from cyclonus_tpu.telemetry import instruments as ti
from cyclonus_tpu.telemetry import recorder, spans
from cyclonus_tpu.tiers.fuzz import _oracle_table, _table_from_grid

CASES = [
    PortCase(80, "serve-80-tcp", "TCP"),
    PortCase(81, "serve-81-udp", "UDP"),
]
KINDS = ("ingress", "egress", "combined")
# four nodes of 16 pods, so that /24s, /26s and /28s all cut the cluster
SMALL = dict(CIDR_ALLOWLISTS, pods_per_node=16)


@pytest.fixture(scope="module")
def small():
    pods, namespaces, policies = cidr_allowlists(64, 48, 3, SMALL)
    policy = build_network_policies(True, policies)
    want = _oracle_table(policy, None, pods, namespaces, CASES)
    return policy, pods, namespaces, want


@pytest.fixture
def low_floor(monkeypatch):
    monkeypatch.setenv("CYCLONUS_CLASS_MIN_PODS", "32")
    monkeypatch.delenv("CYCLONUS_CLASS_COMPRESS", raising=False)
    monkeypatch.delenv("CYCLONUS_CIDR_TSS", raising=False)


def outcomes():
    return {
        o: ti.CLASS_ROUTE.value(outcome=o)
        for o in ("kept", "no_reduction", "below_floor", "no_selector_pass", "off")
    }


def span_attrs(name):
    (attrs,) = [
        rec["attrs"] for path, rec in spans.REGISTRY.tree().items()
        if path.rsplit("/", 1)[-1] == name
    ]
    return attrs


def test_the_small_cluster_is_neither_all_allow_nor_all_deny(small):
    *_, want = small
    share = want[0, :, :, 2].mean()
    assert 0.02 < share < 0.9
    # the except lists decide cells: without them the grid differs
    assert want[0].any() and not want[0].all()


@pytest.mark.parametrize("cidr_tss", ["auto", "0", "1"])
@pytest.mark.parametrize("class_compress", ["auto", "0", "1"])
def test_tables_and_counts_against_the_scalar_oracle(
    small, low_floor, class_compress, cidr_tss
):
    policy, pods, namespaces, want = small
    engine = TpuPolicyEngine(
        policy, pods, namespaces, class_compress=class_compress, cidr_tss=cidr_tss
    )
    # 64 pods fall into 64 classes: only forcing keeps a class state
    assert (engine.pod_classes() is not None) == (class_compress == "1")
    if class_compress == "1":
        assert engine.cidr_stats()["active"] == (cidr_tss == "1")
    got = _table_from_grid(engine.evaluate_grid(CASES))
    assert np.array_equal(got, want)
    counts = engine.evaluate_grid_counts(CASES)
    assert {k: counts[k] for k in KINDS} == {
        k: int(want[..., i].sum()) for i, k in enumerate(KINDS)
    }
    assert counts["cells"] == want[..., 0].size


class TestTheRefusal:
    def test_auto_above_the_floor_keeps_no_class_state(self, small, low_floor):
        policy, pods, namespaces, want = small
        spans.REGISTRY.reset()
        before = outcomes()
        engine = TpuPolicyEngine(policy, pods, namespaces, cidr_tss="1")
        assert engine.pod_classes() is None
        assert not engine.class_compression_stats()["active"]
        # the CIDR space went with the class state ...
        assert not engine.cidr_stats()["active"]
        after = outcomes()
        assert after["no_reduction"] == before["no_reduction"] + 1
        assert {k: after[k] for k in after if k != "no_reduction"} == {
            k: before[k] for k in before if k != "no_reduction"
        }
        classify = span_attrs("engine.classify")
        assert classify == {"classes": 64, "pods": 64, "kept": False}
        # ... and its span says what it held
        space = span_attrs("engine.cidrspace")
        assert space["active"] is True and space["device"] is False
        assert space["specs"] > 64 and space["atoms"] >= space["specs"] // 2
        assert 1 <= space["partitions"] <= 16  # distinct prefix lengths

    def test_the_recorded_route_is_a_dense_one(self, small, low_floor, monkeypatch):
        policy, pods, namespaces, want = small
        monkeypatch.setattr(planspec, "ACTIVE", True)  # arm the recorder
        engine = TpuPolicyEngine(policy, pods, namespaces)
        planspec.drain()
        engine.evaluate_grid(CASES).block_until_ready()
        engine.evaluate_grid_counts(CASES)
        engine.evaluate_grid_counts(CASES, backend="pallas")
        routes = planspec.drain()
        assert routes and not any("classes" in r for r in routes), routes
        assert "counts.xla" in routes and "counts.pallas" in routes

    @pytest.mark.parametrize("class_compress, floor, outcome", [
        ("1", "32", "kept"),
        ("auto", "4096", "below_floor"),
        ("0", "32", "off"),
    ])
    def test_every_engine_counts_its_decision(
        self, small, monkeypatch, class_compress, floor, outcome
    ):
        policy, pods, namespaces, _ = small
        monkeypatch.setenv("CYCLONUS_CLASS_MIN_PODS", floor)
        before = outcomes()
        TpuPolicyEngine(policy, pods, namespaces, class_compress=class_compress)
        after = outcomes()
        assert after[outcome] == before[outcome] + 1
        assert sum(after.values()) == sum(before.values()) + 1

    def test_no_selector_pass_is_counted(self, small, low_floor):
        policy, pods, namespaces, _ = small
        before = outcomes()
        # without compaction's host selector pass auto does not pay for one
        engine = TpuPolicyEngine(policy, pods, namespaces, compact=False)
        assert engine.pod_classes() is None
        assert outcomes()["no_selector_pass"] == before["no_selector_pass"] + 1

    def test_kept_shows_on_the_classify_span(self, small, low_floor):
        policy, pods, namespaces, _ = small
        spans.REGISTRY.reset()
        TpuPolicyEngine(policy, pods, namespaces, class_compress="1", cidr_tss="0")
        assert span_attrs("engine.classify") == {
            "classes": 64, "pods": 64, "kept": True
        }
        assert span_attrs("engine.cidrspace") == {"active": False}


def test_the_dense_counts_eval_span_names_its_program(small, low_floor):
    """resident (since PR 33 the first call runs from the static half of
    the precompute it builds; `fused` before), then split on the repeat,
    then steady: the progression the flight recorder has always shown,
    on the `engine.eval` span too."""
    policy, pods, namespaces, _ = small
    engine = TpuPolicyEngine(policy, pods, namespaces)
    seen = []
    for _ in range(3):
        spans.REGISTRY.reset()
        engine.evaluate_grid_counts(CASES, backend="pallas")
        attrs = span_attrs("engine.eval")
        assert attrs["route"] == "counts.pallas"
        assert attrs["mode"] == recorder.entries()[-1]["mode"]
        seen.append(attrs["mode"])
    assert seen == ["resident", "split", "steady"]


@pytest.mark.parametrize("broken", ["", "drop_except", "drop_named_ports"])
def test_the_benchmarks_reference_reads_these_shapes_as_the_oracle_does(small, broken):
    """Several peers a rule, egress-only policies, /32 and /8, an except
    as long as /32: `GridReference` is this configuration's reference as
    it stands; each control breaks a guarantee this cluster leans on."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference", os.path.join(repo, "benchmarks", "reference.py")
    )
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    _, pods, namespaces, want = small
    _, _, policies = cidr_allowlists(64, 48, 3, SMALL)
    cases = [(c.port, c.port_name, c.protocol) for c in CASES]
    ref = reference.GridReference(
        pods, namespaces, [policy_to_dict(p) for p in policies], broken
    )
    ingress, egress, combined = ref.tables(cases)
    got = np.stack([np.swapaxes(ingress, 1, 2), egress, combined], axis=-1)
    counts = ref.counts(cases)
    if broken:
        assert not np.array_equal(got, want)
        assert counts["combined"] != int(want[..., 2].sum())
    else:
        assert np.array_equal(got, want)
        assert {k: counts[k] for k in KINDS} == {
            k: int(want[..., i].sum()) for i, k in enumerate(KINDS)
        }
