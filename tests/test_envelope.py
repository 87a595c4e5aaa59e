"""The deployment `k8s-envelope-150k` on the CPU, and the selector pass
over distinct label rows that lets its class route exist.

At the configuration's rehearsal (one load namespace: Deployments of all three
sizes, every selector operator and port form, the two system namespaces):

  * the engine's counts on `counts.classes` (the program's own selector pass
    over the class axis, and the resident matches that replace it past
    `api._CLASS_SELPOD_DEVICE_MAX_BYTES`) and on the dense `counts.xla` equal
    `benchmarks/reference_envelope.py`, what decides the cell's `correct`, and
    each of its controls changes the answer;
  * the constructor's pass runs over the distinct label rows (`rows`, not
    pods), in a span `engine.selector_pass` of its own, counted by
    `cyclonus_tpu_selector_pass_total{outcome}`; a `counts.classes`
    evaluation says its classes, row-sum program, selector source and target
    words.

On the cyclic, `cidr`, tier and envelope generators at their rehearsal sizes
the pass over label rows (the inverted index) gives the [S, N] pass's
matches (the numpy twin of kernel.selector_match over every pod), and
`compute_pod_classes` and dead-target compaction over them give what the
[S, N] pass gives (`pod_signatures` and `classes_from_signatures`, the
serve path's form): the same classes, ids, representatives, sizes and
signature width, and the same live targets.
"""

import json
import os
import random
import sys

import numpy as np
import pytest

import cyclonus_tpu.engine.api as api
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, selectors, tiled
from cyclonus_tpu.engine import pallas_kernel
from cyclonus_tpu.engine.encoding import (
    classes_from_signatures,
    compute_pod_classes,
    packed_words,
    pod_signatures,
)
from cyclonus_tpu.kube.yaml_io import parse_policy_dict
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.synthetic import (
    anp_tenancy,
    build_synthetic,
    cidr_allowlists,
    k8s_envelope_objects,
)
from cyclonus_tpu.telemetry import instruments as ti
from cyclonus_tpu.telemetry import recorder, spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference_envelope import BROKEN, EnvelopeReference  # noqa: E402

# benchmarks/traffic/port-sweep-envelope.json: three pairs, Q = 2
PAIRS = [
    [(80, "TCP"), (81, "UDP")],
    [(80, "UDP"), (81, "SCTP")],
    [(80, "SCTP"), (81, "TCP")],
]


def cases_of(pair):
    return [(p, f"serve-{p}-{proto.lower()}", proto) for p, proto in pair]


def port_cases(pair):
    return [PortCase(*c) for c in cases_of(pair)]


def spans_named(name):
    return [
        rec["attrs"] for path, rec in spans.REGISTRY.tree().items()
        if path.rsplit("/", 1)[-1] == name
    ]


@pytest.fixture(scope="module")
def envelope():
    """The rehearsal's deployment in a seed's order: (pods, namespaces,
    policy dicts, the compiled policy)."""
    pods, namespaces, policies = k8s_envelope_objects(1, 100)
    random.Random("7/pods").shuffle(pods)
    random.Random("7/policies").shuffle(policies)
    policy = build_network_policies(True, [parse_policy_dict(d) for d in policies])
    return pods, namespaces, policies, policy


@pytest.fixture(scope="module")
def reference(envelope):
    pods, namespaces, policies, _ = envelope
    return EnvelopeReference(pods, namespaces, policies)


ROUTES = ["classes-device", "classes-host", "dense"]


@pytest.fixture(scope="module")
def engines(envelope):
    pods, namespaces, _, policy = envelope
    out = {
        "classes-device": TpuPolicyEngine(policy, pods, namespaces, class_compress="1"),
        "dense": TpuPolicyEngine(policy, pods, namespaces, class_compress="0"),
    }
    held = api._CLASS_SELPOD_DEVICE_MAX_BYTES
    api._CLASS_SELPOD_DEVICE_MAX_BYTES = 0
    try:
        out["classes-host"] = TpuPolicyEngine(policy, pods, namespaces, class_compress="1")
    finally:
        api._CLASS_SELPOD_DEVICE_MAX_BYTES = held
    return out


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("pair", range(3), ids=["A", "B", "C"])
def test_counts_match_the_reference(engines, reference, route, pair):
    got = engines[route].evaluate_grid_counts(port_cases(PAIRS[pair]))
    want = reference.counts(cases_of(PAIRS[pair]))
    assert {k: int(got[k]) for k in want} == want
    assert 0 < want["combined"] < want["cells"]


@pytest.mark.parametrize("broken", [b for b in BROKEN if b])
def test_each_control_changes_the_answer(envelope, reference, broken):
    pods, namespaces, policies, _ = envelope
    control = EnvelopeReference(pods, namespaces, policies, broken)
    assert any(
        control.counts(cases_of(pair)) != reference.counts(cases_of(pair))
        for pair in PAIRS
    )


def test_the_classes_track_the_deployments(engines):
    """328 Deployments and the two system ones: one class each, and the
    resident matches exist only past the ceiling."""
    eng = engines["classes-device"]
    assert eng.class_compression_stats()["classes"] == 330
    assert eng._class_state["selpod"] is None
    held = engines["classes-host"]._class_state
    assert held["selpod"] is not None
    ct = held["ctensors"]
    assert held["selpod"].shape == (ct["sel_req_kv"].shape[0], ct["pod_ns_id"].shape[0])
    assert engines["dense"].class_compression_stats()["active"] is False


def test_the_pass_is_a_span_over_label_rows(envelope, monkeypatch):
    pods, namespaces, _, policy = envelope
    monkeypatch.setenv("CYCLONUS_CLASS_MIN_PODS", str(len(pods)))
    spans.REGISTRY.reset()
    ran = ti.SELECTOR_PASS.value(outcome="ran")
    eng = TpuPolicyEngine(policy, pods, namespaces)
    (sp,) = spans_named("engine.selector_pass")
    rows = {json.dumps(p[2], sort_keys=True) for p in pods}
    assert sp["rows"] == len(rows) == 330 < sp["pods"] == len(pods)
    assert sp["selectors"] == eng._selpod_prebucket.match.shape[0]
    assert sp["how"] == "host" and sp["ops"] > 0
    assert ti.SELECTOR_PASS.value(outcome="ran") == ran + 1
    assert eng.class_compression_stats()["active"]
    skipped = ti.SELECTOR_PASS.value(outcome="skipped")
    TpuPolicyEngine(policy, pods, namespaces, compact=False)
    assert ti.SELECTOR_PASS.value(outcome="skipped") == skipped + 1


@pytest.mark.parametrize("route, sel", [("classes-device", "device"), ("classes-host", "host")])
def test_a_class_evaluation_says_what_it_ran(engines, route, sel):
    eng = engines[route]
    spans.REGISTRY.reset()
    eng.evaluate_grid_counts(port_cases(PAIRS[0]))
    (root,) = spans_named("engine.eval")
    ct = eng._class_state["ctensors"]
    words = sum(packed_words(int(ct[d]["target_ns"].shape[0])) for d in ("ingress", "egress"))
    assert root["route"] == "counts.classes"
    assert (root["classes"], root["class_kernel"], root["class_sel"], root["target_words"]) == (
        330, "xla", sel, words
    )
    entry = recorder.entries()[-1]
    assert (entry["classes"], entry["class_kernel"], entry["class_sel"], entry["target_words"]) == (
        330, "xla", sel, words
    )


def test_the_class_kernel_leaves_a_deep_word_axis_to_the_xla_tile(engines, monkeypatch):
    ct = engines["classes-device"]._class_state["ctensors"]
    assert pallas_kernel.packed_words_eligible(ct)
    assert tiled._class_counts_kernel_choice(ct, True, "pallas") == "pallas"
    monkeypatch.setattr(pallas_kernel, "PACKED_MAX_WORDS", 1)
    assert not pallas_kernel.packed_words_eligible(ct)
    monkeypatch.setattr(tiled.jax, "default_backend", lambda: "tpu")
    assert tiled._class_counts_kernel_choice(ct, True, None) == "xla"
    with pytest.raises(ValueError, match="PACKED_MAX_WORDS"):
        tiled._class_counts_kernel_choice(ct, True, "pallas")


@pytest.fixture(scope="module")
def tiered_engines():
    """A tiered set on the class route, and its dense twin (`counts.xla`)
    for the counts."""
    pods, namespaces, policy, tiers = _tiers()
    return {
        compress: TpuPolicyEngine(
            policy, pods, namespaces, class_compress=compress, tiers=tiers
        )
        for compress in ("1", "0")
    }


@pytest.mark.parametrize(
    "deployment, pack, contract",
    [
        ("envelope", True, tiled.CONTRACT_MXU),
        ("envelope", False, tiled.CONTRACT_MXU),
        ("tiered", True, tiled.CONTRACT_PACKED),
        ("tiered", False, tiled.CONTRACT_MXU),
    ],
    ids=["envelope-mxu", "envelope-unpacked", "tiered-packed", "tiered-unpacked"],
)
def test_the_class_tile_contracts_in_the_form_of_its_words(
    engines, tiered_engines, reference, monkeypatch, deployment, pack, contract
):
    """On the XLA tile an untiered set contracts the unpacked target axis
    on the MXU whether or not packing is on, and its counts are the
    reference's; a tiered set keeps the packed form where packing is on
    and the tables' unpacked bf16 body where it is off, and its counts are
    the dense route's.  The flight entry, the `engine.eval` span and
    cyclonus_tpu_class_contract_total say which."""
    if deployment == "envelope":
        eng = engines["classes-host"]
        want = {p: reference.counts(cases_of(PAIRS[p])) for p in range(3)}
    else:
        eng = tiered_engines["1"]
        want = {
            p: tiered_engines["0"].evaluate_grid_counts(port_cases(PAIRS[p]))
            for p in range(3)
        }
    monkeypatch.setattr(eng, "_pack", pack)
    before = ti.CLASS_CONTRACT.value(form=contract)
    for p in range(3):
        spans.REGISTRY.reset()
        got = eng.evaluate_grid_counts(port_cases(PAIRS[p]))
        assert {k: int(got[k]) for k in want[p]} == want[p]
        (root,) = spans_named("engine.eval")
        assert (root["route"], root["class_contract"]) == ("counts.classes", contract)
        assert recorder.entries()[-1]["class_contract"] == contract
    assert ti.CLASS_CONTRACT.value(form=contract) == before + 3


def test_the_estimate_counts_the_form_of_the_target_axis(engines, monkeypatch):
    """The class route's HBM estimate holds the MXU form's tallow
    [Q, T, Cb] in tiled.MXU_DTYPE a direction where the XLA tile runs an
    untiered set, its contraction's result counted once, and the packed
    words [W, Cb, Q] and [W, Cb] int32 a direction beside that result
    where the fused kernel runs, T in whole words either way."""
    eng = engines["classes-host"]
    ct = eng._class_state["ctensors"]
    cb, q = int(ct["pod_ns_id"].shape[0]), 2
    dims = {
        d: (32 * packed_words(int(ct[d]["target_ns"].shape[0])), int(ct[d]["peer_kind"].shape[0]))
        for d in ("ingress", "egress")
    }
    mxu = eng._class_counts_bytes(q)
    monkeypatch.setattr(tiled, "_class_counts_kernel_choice", lambda *a: "pallas")
    packed = eng._class_counts_bytes(q)
    size = np.dtype(tiled.MXU_DTYPE).itemsize
    words = sum(4 * t // 32 * cb * (q + 1) for t, _ in dims.values())
    unpacked = sum(size * q * t * cb for t, _ in dims.values())
    stage = {
        form: max(q * t * (p + cb * (form == "packed")) for t, p in dims.values())
        for form in ("packed", "mxu")
    }
    assert packed - mxu == (words + stage["packed"]) - (unpacked + stage["mxu"])


def test_the_estimate_counts_the_selector_stage_and_the_one_hot(engines):
    """The program's own pass over the class axis holds two [S, Cb, E]
    boolean planes; the resident matches hold [S, Cb] instead, in the
    engine's aux bytes; a direction's port-folded one-hot and its result
    are a stage of their own."""
    device, host = engines["classes-device"], engines["classes-host"]
    ct = device._class_state["ctensors"]
    s, e = ct["sel_exp_op"].shape
    cb = ct["pod_ns_id"].shape[0]
    est = device._class_counts_bytes(2)
    assert est >= device._class_state["aux_bytes"] + s * cb + 2 * s * cb * e
    onehot = max(
        2 * packed_words(int(ct[d]["target_ns"].shape[0])) * 32
        * (int(ct[d]["peer_kind"].shape[0]) + cb)
        for d in ("ingress", "egress")
    )
    assert host._class_counts_bytes(2) >= host._class_state["aux_bytes"] + onehot
    assert host._class_state["aux_bytes"] - device._class_state["aux_bytes"] == s * cb


# --- the pass over label rows against the pass over pods ------------------


def _cyclic():
    pods, namespaces, policies = build_synthetic(600, 60, random.Random(31))
    return pods, namespaces, build_network_policies(True, policies), None


def _cidr():
    pods, namespaces, policies = cidr_allowlists(660, 330, 4)
    return pods, namespaces, build_network_policies(True, policies), None


def _tiers():
    pods, namespaces, policies = build_synthetic(600, 60, random.Random(31), n_ns=4)
    return pods, namespaces, build_network_policies(True, policies), anp_tenancy(4)


def _envelope():
    pods, namespaces, policies = k8s_envelope_objects(1, 100)
    policy = build_network_policies(True, [parse_policy_dict(d) for d in policies])
    return pods, namespaces, policy, None


GENERATORS = {"cyclic": _cyclic, "cidr": _cidr, "tiers": _tiers, "envelope": _envelope}


@pytest.fixture(scope="module", params=sorted(GENERATORS))
def raw_tensors(request):
    pods, namespaces, policy, tiers = GENERATORS[request.param]()
    eng = TpuPolicyEngine(
        policy, pods, namespaces, compact=False, class_compress="0", tiers=tiers
    )
    return request.param, eng._build_tensors()


def _pods_pass_live(tensors, selpod, direction):
    """Dead-target compaction's verdict from the [S, N] pass, spelled out:
    a target lives where its selector matches a pod of its namespace."""
    d = tensors[direction]
    pod_ns = tensors["pod_ns_id"]
    return np.array([
        ns >= 0 and bool(np.any(selpod[sel] & (pod_ns == ns)))
        for ns, sel in zip(d["target_ns"].tolist(), d["target_sel"].tolist())
    ], dtype=bool)


def test_the_rows_pass_is_the_pods_pass(raw_tensors):
    name, t = raw_tensors
    rows = selectors.run(t)
    full = api._selector_pod_matches_host(t)
    assert np.array_equal(rows.pods(), full)
    distinct = {tuple(r) for r in np.concatenate([t["pod_kv"], t["pod_key"]], axis=1).tolist()}
    assert rows.n_rows == len(distinct) < t["pod_ns_id"].shape[0]
    # the classes: the same ids, representatives, sizes and width
    a = compute_pod_classes(t, rows)
    b = classes_from_signatures(pod_signatures(t, full))
    assert (a.n_classes, a.signature_bytes) == (b.n_classes, b.signature_bytes)
    for k in ("class_of_pod", "class_rep", "class_size"):
        assert np.array_equal(getattr(a, k), getattr(b, k)), k
    # dead-target compaction: the same live targets, the same tensors
    compacted = api._compact_dead_targets(t, rows)
    for direction in ("ingress", "egress"):
        d = t[direction]
        live = _pods_pass_live(t, full, direction)
        assert np.array_equal(
            rows.live_targets(t["pod_ns_id"], d["target_ns"], d["target_sel"]), live
        )
        assert np.array_equal(compacted[direction]["target_sel"], d["target_sel"][live])
        assert np.array_equal(compacted[direction]["target_ns"], d["target_ns"][live])


def test_the_class_matches_are_the_device_pass(raw_tensors):
    """The resident matches of a bucketed class set equal the device's own
    pass over it, pad class rows and pad selector rows included."""
    from cyclonus_tpu.engine.encoding import gather_class_pod_rows
    from cyclonus_tpu.engine.kernel import selector_match

    name, t = raw_tensors
    rows = selectors.run(t)
    pc = compute_pod_classes(t, rows)
    ct = api._bucket_tensors(gather_class_pod_rows(t, pc.class_rep))
    got = selectors.class_matches(rows, ct, pc.class_rep)
    want = np.asarray(selector_match(
        ct["sel_req_kv"], ct["sel_exp_op"], ct["sel_exp_key"], ct["sel_exp_vals"],
        ct["pod_kv"], ct["pod_key"],
    ))
    assert got.shape == want.shape and np.array_equal(got, want)
