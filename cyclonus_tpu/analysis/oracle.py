"""Scalar-oracle cross-checks for the analysis layer.

Every audit/diff claim the tensor path produces is re-derived here with
the matcher's line-by-line evaluation (matcher/core.py — the same oracle
the engine parity suites pin against) on a sampled subset of grid cells.
A mismatch is an internal-consistency failure (an engine or analysis
bug), never a report row: callers raise on it.
"""

from __future__ import annotations

import random
from typing import Dict, List, Sequence, Tuple

from ..engine.api import PortCase
from ..matcher.core import (
    InternalPeer,
    Policy,
    Target,
    Traffic,
    TrafficPeer,
)

# (namespace, name, labels, ip) — the engine's pod tuple format
PodTuple = Tuple[str, str, Dict[str, str], str]
# (case index, src pod index, dst pod index)
Cell = Tuple[int, int, int]


def traffic_for_cell(
    pods: Sequence[PodTuple],
    namespaces: Dict[str, Dict[str, str]],
    case: PortCase,
    src_idx: int,
    dst_idx: int,
) -> Traffic:
    """The oracle Traffic for grid cell (case, src pod, dst pod) — the
    same construction the engine parity tests use."""
    sns, _, slabels, sip = pods[src_idx]
    dns, _, dlabels, dip = pods[dst_idx]
    return Traffic(
        source=TrafficPeer(
            internal=InternalPeer(
                pod_labels=slabels,
                namespace_labels=namespaces.get(sns, {}),
                namespace=sns,
            ),
            ip=sip,
        ),
        destination=TrafficPeer(
            internal=InternalPeer(
                pod_labels=dlabels,
                namespace_labels=namespaces.get(dns, {}),
                namespace=dns,
            ),
            ip=dip,
        ),
        resolved_port=case.port,
        resolved_port_name=case.port_name,
        protocol=case.protocol,
    )


def oracle_verdicts(policy: Policy, traffic: Traffic) -> Tuple[bool, bool, bool]:
    """(ingress, egress, combined) allowed per the scalar matcher."""
    r = policy.is_traffic_allowed(traffic)
    return (r.ingress.is_allowed, r.egress.is_allowed, r.is_allowed)


def policy_without_rule(
    policy: Policy, direction: str, target_idx: int, peer_idx: int
) -> Policy:
    """A copy of the policy set with ONE resolved rule removed: peer
    `peer_idx` of target `target_idx` in the sorted_targets() order of
    `direction`.  The target itself stays (a peer-less target still
    denies), exactly matching the audit's removal semantics."""
    ingress, egress = policy.sorted_targets()
    lists = {"ingress": list(ingress), "egress": list(egress)}
    targets = lists[direction]
    t = targets[target_idx]
    peers = [pm for j, pm in enumerate(t.peers) if j != peer_idx]
    targets[target_idx] = Target(
        namespace=t.namespace,
        pod_selector=t.pod_selector,
        peers=peers,
        source_rules=t.source_rules,
    )
    return Policy.from_targets(lists["ingress"], lists["egress"])


def check_rule_removal(
    policy: Policy,
    modified: Policy,
    direction: str,
    pods: Sequence[PodTuple],
    namespaces: Dict[str, Dict[str, str]],
    cases: Sequence[PortCase],
    cells: Sequence[Cell],
) -> List[Tuple[Cell, bool, bool]]:
    """Oracle-evaluate `cells` under the original and the rule-stripped
    policy set; returns the cells whose DIRECTION verdict changed (empty
    = the dead-rule claim holds on this sample)."""
    is_ingress = direction == "ingress"
    bad = []
    for cell in cells:
        qi, si, di = cell
        t = traffic_for_cell(pods, namespaces, cases[qi], si, di)
        before = policy.is_ingress_or_egress_allowed(t, is_ingress).is_allowed
        after = modified.is_ingress_or_egress_allowed(t, is_ingress).is_allowed
        if before != after:
            bad.append((cell, before, after))
    return bad


def sample_cells(
    n_pods: int, n_cases: int, k: int, rng: random.Random
) -> List[Cell]:
    """k uniformly random grid cells."""
    if n_pods == 0 or n_cases == 0:
        return []
    return [
        (
            rng.randrange(n_cases),
            rng.randrange(n_pods),
            rng.randrange(n_pods),
        )
        for _ in range(k)
    ]


def _hold_to_oracle(policy, pods, namespaces, case, si, di, got) -> None:
    expected = oracle_verdicts(
        policy, traffic_for_cell(pods, namespaces, case, si, di)
    )
    if tuple(bool(x) for x in got) != expected:
        raise AssertionError(
            f"PARITY FAILURE at q={case} s={si} d={di}: "
            f"oracle={expected} engine={tuple(got)}"
        )


def spot_check(
    policy: Policy,
    pods: Sequence[PodTuple],
    namespaces: Dict[str, Dict[str, str]],
    cases: Sequence[PortCase],
    grid,
    n_samples: int,
    rng: random.Random,
) -> None:
    """Hold `n_samples` random cells of a GridVerdict to the scalar
    oracle (one device gather, one tiny transfer); raises AssertionError
    naming the first cell that differs."""
    cells = sample_cells(len(pods), len(cases), n_samples, rng)
    for (qi, si, di), got in zip(cells, grid.gather(cells)):
        _hold_to_oracle(policy, pods, namespaces, cases[qi], si, di, got)


def spot_check_pairs(
    engine,
    policy: Policy,
    pods: Sequence[PodTuple],
    namespaces: Dict[str, Dict[str, str]],
    cases: Sequence[PortCase],
    n_samples: int,
    rng: random.Random,
) -> None:
    """Scale-path parity: `n_samples` random (src, dst) pairs through the
    engine's pairs kernel (no N x N grid), every case of each held to the
    scalar oracle."""
    n = len(pods)
    pairs = [(rng.randrange(n), rng.randrange(n)) for _ in range(n_samples)]
    got = engine.evaluate_pairs(cases, pairs)  # [K, Q, 3]
    for k, (si, di) in enumerate(pairs):
        for qi, case in enumerate(cases):
            _hold_to_oracle(policy, pods, namespaces, case, si, di, got[k, qi])
