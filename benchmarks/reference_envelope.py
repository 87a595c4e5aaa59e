"""The plain reference of `k8s-envelope-150k`: Kubernetes NetworkPolicy
semantics on plain data, for a cluster too large for a pod x pod grid and
policies whose selectors use matchExpressions and whose ports use endPort.

It imports nothing of the program (reference.py, whose `labels_match` and
address helpers it reads, imports nothing either) and shares no code with the
program's class compression.  Its inputs are the generator's pods, namespaces
and policy dicts; its answer the allow counts the counts entry reports.

Semantics (networking.k8s.io/v1): a policy applies to a pod in a direction
when the direction is in its policyTypes, the pod is in the policy's namespace
and its podSelector matches (matchLabels and matchExpressions: In, NotIn,
Exists, DoesNotExist); a pod no policy applies to is open in that direction;
otherwise the flow is allowed when some applying policy has a rule whose ports
match the port case (no ports: all) and one of whose peers matches the other
end (no peers: all).  A peer is an ipBlock (the other end's address inside
`cidr` and outside every `except`) or a namespace / pod selector pair (no
namespaceSelector: the policy's own namespace).  A port term matches on
protocol (default TCP) and on the number, on the range port .. endPort where
it has an endPort, or on the resolved port NAME where it names the port.
Combined is ingress AND egress.

The grouping.  A NetworkPolicy sees a pod through three things only: its
namespace, its label set and, for each distinct ipBlock of the policies,
whether its address lies in it.  Pods equal in all three are one GROUP, and a
group's verdicts are computed once: per direction and port case an allow
matrix over (target group, other end's group), [K, K], spread by the groups'
sizes into exact int64 counts.  Exact by the semantics, not sampled.

`broken` names ONE stated guarantee to break, for the control that has to come
out as not correct: reference.py's "drop_named_ports" (a rule that names its
port matches nothing) and "drop_except" (every ipBlock `except` ignored), and
"drop_end_port": a port range matches its first port only.
"""

import json

import numpy as np

from benchmarks.reference import _ip, _net, labels_match

BROKEN = ("", "drop_named_ports", "drop_except", "drop_end_port")


def ports_match(ports, case, broken="") -> bool:
    port, name, protocol = case
    if not ports:
        return True
    for p in ports:
        if (p.get("protocol") or "TCP") != protocol:
            continue
        want = p.get("port")
        if want is None:
            return True
        if isinstance(want, str):
            if broken != "drop_named_ports" and want == name:
                return True
            continue
        end = p.get("endPort")
        if end is None or broken == "drop_end_port":
            end = want
        if want <= port <= end:
            return True
    return False


def _blocks(policies) -> list:
    """Every distinct ipBlock of the policies, in the order met."""
    seen = {}
    for p in policies:
        spec = p["spec"]
        for rules, key in ((spec.get("ingress"), "from"), (spec.get("egress"), "to")):
            for rule in rules or []:
                for peer in rule.get(key) or []:
                    if "ipBlock" in peer:
                        seen.setdefault(json.dumps(peer["ipBlock"], sort_keys=True), None)
    return [json.loads(b) for b in seen]


def _in_block(addr: int, block: dict, broken: str) -> bool:
    base, mask = _net(block["cidr"])
    if addr & mask != base:
        return False
    if broken != "drop_except":
        for ex in block.get("except") or []:
            ebase, emask = _net(ex)
            if addr & emask == ebase:
                return False
    return True


class EnvelopeReference:
    """Allow counts of one cluster under one policy set, exactly, over the
    pod groups (module docstring)."""

    def __init__(self, pods, namespaces, policies, broken=""):
        if broken not in BROKEN:
            raise ValueError(f"unknown guarantee to break: {broken!r}")
        self.broken = broken
        self.n = len(pods)
        self.namespaces = namespaces
        self.policies = policies
        self.blocks = _blocks(policies)
        group_of, keys = {}, []
        of_pod = np.empty(self.n, dtype=np.int64)
        for i, (ns, _, labels, ip) in enumerate(pods):
            addr = _ip(ip)
            key = (
                ns,
                json.dumps(labels, sort_keys=True),
                tuple(_in_block(addr, b, broken) for b in self.blocks),
            )
            g = group_of.get(key)
            if g is None:
                g = group_of[key] = len(keys)
                keys.append(key)
            of_pod[i] = g
        self.group_ns = [k[0] for k in keys]
        self.group_labels = [json.loads(k[1]) for k in keys]
        self.group_blocks = np.array([k[2] for k in keys], dtype=bool).reshape(
            len(keys), len(self.blocks)
        )
        self.size = np.bincount(of_pod, minlength=len(keys)).astype(np.int64)
        self.by_ns = {}
        for g, ns in enumerate(self.group_ns):
            self.by_ns.setdefault(ns, []).append(g)
        self.block_id = {
            json.dumps(b, sort_keys=True): j for j, b in enumerate(self.blocks)
        }
        self._memo = {}

    @property
    def k(self) -> int:
        return len(self.group_ns)

    def _groups_matching(self, selector, namespaces) -> np.ndarray:
        """The groups of those namespaces whose labels `selector` matches, as
        indices (a mask a selector would hold gigabytes at this size)."""
        key = (json.dumps(selector, sort_keys=True), tuple(namespaces))
        if key not in self._memo:
            self._memo[key] = np.array([
                g for ns in namespaces for g in self.by_ns.get(ns, ())
                if labels_match(selector, self.group_labels[g])
            ], dtype=np.int64)
        return self._memo[key]

    def _peer_groups(self, peer, policy_ns: str) -> np.ndarray:
        if "ipBlock" in peer:
            j = self.block_id[json.dumps(peer["ipBlock"], sort_keys=True)]
            return np.flatnonzero(self.group_blocks[:, j])
        ns_sel = peer.get("namespaceSelector")
        if ns_sel is None:
            chosen = [policy_ns]
        else:
            chosen = [
                ns for ns in sorted(self.by_ns)
                if labels_match(ns_sel, self.namespaces.get(ns, {}))
            ]
        return self._groups_matching(peer.get("podSelector"), chosen)

    def direction(self, direction: str, case) -> np.ndarray:
        """[K, K] bool: allow[t, o] says whether a pod of group t accepts the
        other end in group o in this direction on this port case."""
        rules_key, peers_key = (
            ("ingress", "from") if direction == "Ingress" else ("egress", "to")
        )
        allow = np.zeros((self.k, self.k), dtype=bool)
        applies = np.zeros(self.k, dtype=bool)
        for p in self.policies:
            spec = p["spec"]
            if direction not in (spec.get("policyTypes") or ["Ingress"]):
                continue
            ns = p["metadata"].get("namespace", "")
            targets = self._groups_matching(spec.get("podSelector"), [ns])
            if not targets.size:
                continue
            applies[targets] = True
            opened = np.zeros(self.k, dtype=bool)
            for rule in spec.get(rules_key) or []:
                if not ports_match(rule.get("ports"), case, self.broken):
                    continue
                peers = rule.get(peers_key)
                if not peers:
                    opened[:] = True
                for peer in peers or []:
                    opened[self._peer_groups(peer, ns)] = True
            if opened.any():
                allow[targets] |= opened
        allow[~applies] = True
        return allow

    def counts(self, cases) -> dict:
        """Allowed flows summed over the cases, as the counts entry reports."""
        out = {"ingress": 0, "egress": 0, "combined": 0, "cells": 0}
        for case in cases:
            case = tuple(case)
            ing = self.direction("Ingress", case)  # [dst group, src group]
            eg = self.direction("Egress", case)  # [src group, dst group]
            out["ingress"] += self._weighted(ing)
            out["egress"] += self._weighted(eg)
            out["combined"] += self._weighted(eg & ing.T)
            out["cells"] += self.n * self.n
        return out

    def _weighted(self, allow: np.ndarray, chunk: int = 1024) -> int:
        """sum over (t, o) of allow[t, o] x size[t] x size[o], exact: a row's
        weighted sum is an integer of at most the pod count, and float64
        holds every such sum exactly; the products are taken in int64."""
        w = self.size.astype(np.float64)
        total = 0
        for lo in range(0, self.k, chunk):
            rows = np.rint(allow[lo : lo + chunk].astype(np.float64) @ w)
            total += int(rows.astype(np.int64) @ self.size[lo : lo + chunk])
        return total
