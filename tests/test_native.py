"""Native C++ grid evaluator: build, parity vs the Python oracle and the
TPU kernel, graceful fallback."""

import random

import numpy as np
import pytest

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.matcher import (
    InternalPeer,
    Traffic,
    TrafficPeer,
    build_network_policies,
)
from cyclonus_tpu.native import (
    NativeUnsupported,
    evaluate_grid_native,
    native_available,
)

pytestmark = pytest.mark.skipif(
    not native_available(), reason="g++ toolchain unavailable"
)


def synthetic(n_pods, n_policies, seed):
    from cyclonus_tpu.synthetic import build_synthetic

    return build_synthetic(n_pods, n_policies, random.Random(seed))


CASES = [
    PortCase(80, "serve-80-tcp", "TCP"),
    PortCase(81, "serve-81-udp", "UDP"),
    PortCase(9999, "", "SCTP"),
]


def oracle_verdict(policy, pods, namespaces, case, si, di):
    sns, _, sl, sip = pods[si]
    dns, _, dl, dip = pods[di]
    t = Traffic(
        source=TrafficPeer(internal=InternalPeer(sl, namespaces[sns], sns), ip=sip),
        destination=TrafficPeer(
            internal=InternalPeer(dl, namespaces[dns], dns), ip=dip
        ),
        resolved_port=case.port,
        resolved_port_name=case.port_name,
        protocol=case.protocol,
    )
    r = policy.is_traffic_allowed(t)
    return (r.ingress.is_allowed, r.egress.is_allowed, r.is_allowed)


def test_native_matches_oracle_sampled():
    pods, namespaces, policies = synthetic(80, 60, seed=3)
    policy = build_network_policies(True, policies)
    grid = evaluate_grid_native(policy, pods, namespaces, CASES)
    rng = random.Random(5)
    for _ in range(400):
        qi = rng.randrange(len(CASES))
        si, di = rng.randrange(80), rng.randrange(80)
        assert grid.job_verdict(qi, si, di) == oracle_verdict(
            policy, pods, namespaces, CASES[qi], si, di
        )


def test_native_matches_tpu_full_grid():
    pods, namespaces, policies = synthetic(50, 40, seed=9)
    policy = build_network_policies(True, policies)
    native = evaluate_grid_native(policy, pods, namespaces, CASES)
    tpu = TpuPolicyEngine(policy, pods, namespaces).evaluate_grid(CASES)
    assert np.array_equal(native.ingress, tpu.ingress)
    assert np.array_equal(native.egress, tpu.egress)
    assert np.array_equal(native.combined, tpu.combined)


def test_native_match_expressions():
    from cyclonus_tpu.kube.netpol import (
        LabelSelector,
        LabelSelectorRequirement,
        NetworkPolicy,
        NetworkPolicyIngressRule,
        NetworkPolicyPeer,
        NetworkPolicySpec,
    )

    sel = LabelSelector.make(
        match_expressions=[
            LabelSelectorRequirement(key="tier", operator="NotIn", values=["web"]),
            LabelSelectorRequirement(key="app", operator="Exists"),
        ]
    )
    pol = NetworkPolicy(
        name="exp",
        namespace="n1",
        spec=NetworkPolicySpec(
            pod_selector=LabelSelector.make(match_labels={"role": "db"}),
            policy_types=["Ingress"],
            ingress=[NetworkPolicyIngressRule(
                ports=[], from_=[NetworkPolicyPeer(pod_selector=sel)]
            )],
        ),
    )
    namespaces = {"n1": {"ns": "n1"}}
    pods = [
        ("n1", "db", {"role": "db"}, "10.0.0.1"),
        ("n1", "api", {"app": "x", "tier": "api"}, "10.0.0.2"),
        ("n1", "web", {"app": "x", "tier": "web"}, "10.0.0.3"),
        ("n1", "bare", {"tier": "api"}, "10.0.0.4"),  # NotIn ok, Exists fails
        ("n1", "nokey", {"app": "y"}, "10.0.0.5"),  # NotIn absent-key => match? NO
    ]
    policy = build_network_policies(True, [pol])
    cases = [PortCase(80, "", "TCP")]
    grid = evaluate_grid_native(policy, pods, namespaces, cases)
    for si in range(len(pods)):
        for di in range(len(pods)):
            assert grid.job_verdict(0, si, di) == oracle_verdict(
                policy, pods, namespaces, cases[0], si, di
            ), (si, di)


def test_native_rejects_ipv6():
    pods, namespaces, policies = synthetic(10, 5, seed=1)
    pods[0] = (pods[0][0], pods[0][1], pods[0][2], "fd00::1")
    policy = build_network_policies(True, policies)
    with pytest.raises(NativeUnsupported):
        evaluate_grid_native(policy, pods, namespaces, CASES[:1])


def test_runner_native_engine_matches_oracle():
    from cyclonus_tpu.recipes import ALL_RECIPES

    for r in ALL_RECIPES[:4]:
        oracle = r.run_probe(engine="oracle")
        native = r.run_probe(engine="native")
        assert oracle.render_table() == native.render_table(), r.name
