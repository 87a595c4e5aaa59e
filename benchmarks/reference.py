"""The plain reference: Kubernetes NetworkPolicy semantics on plain data.

It imports nothing of the program and takes nothing the program has made: its
inputs are the generators' pods, namespaces and policy dicts, its answers the
three verdicts of a flow (ingress, egress, combined).  `correct` in every cell
is a comparison of what the timed path returned with what this file says.

Semantics (networking.k8s.io/v1, as upstream cyclonus's matcher reads them):
a policy applies to a pod in a direction when the direction is in its
policyTypes, the pod is in the policy's namespace and its podSelector matches;
a pod no policy applies to is open in that direction; otherwise the flow is
allowed when some applying policy has a rule whose ports match the port case
(no ports: all) and one of whose peers matches the other end (no peers: all).
A peer is an ipBlock (the other end's IP inside `cidr` and outside every
`except`) or a pod/namespace selector pair (no namespaceSelector: the policy's
own namespace).  A port matches on protocol (default TCP) and on the number,
or on the resolved port NAME where the policy names the port.  Combined is
ingress AND egress.

Two forms of the same semantics: `flow_verdict` answers one flow by a scalar
walk (the served cell checks every reply with it; the grid form is tested
against it), and `GridReference` answers whole grids with numpy by grouping
the pods that the same policies apply to - exact, not sampled.

`broken` names ONE stated guarantee to break, for the control that has to come
out as not correct (see PERF.md).  Both answer approximately where exactness
is promised: "drop_named_ports" never resolves a port NAME (a rule that names
its port matches nothing), "drop_except" ignores every ipBlock `except` list.
"""

import ipaddress
import json

import numpy as np

BROKEN = ("", "drop_named_ports", "drop_except")


def _net(cidr: str):
    n = ipaddress.ip_network(cidr, strict=False)
    return int(n.network_address), int(n.netmask)


def _ip(ip: str) -> int:
    return int(ipaddress.ip_address(ip))


def labels_match(selector, labels: dict) -> bool:
    """A LabelSelector dict against a label dict; None or {} matches all."""
    if not selector:
        return True
    for k, v in (selector.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for e in selector.get("matchExpressions") or []:
        op, key, vals = e["operator"], e["key"], e.get("values") or []
        if op == "In" and labels.get(key) not in vals:
            return False
        if op == "NotIn" and key in labels and labels[key] in vals:
            return False
        if op == "Exists" and key not in labels:
            return False
        if op == "DoesNotExist" and key in labels:
            return False
    return True


def ports_match(ports, case, broken="") -> bool:
    port, name, protocol = case
    if not ports:
        return True
    for p in ports:
        if (p.get("protocol") or "TCP") != protocol:
            continue
        want = p.get("port")
        if isinstance(want, str) and broken == "drop_named_ports":
            continue
        if want is None or want == (name if isinstance(want, str) else port):
            return True
    return False


def _peer_matches(peer, policy_ns, other, namespaces, broken) -> bool:
    ons, _, olabels, oip = other
    block = peer.get("ipBlock")
    if block:
        addr = _ip(oip)
        base, mask = _net(block["cidr"])
        if addr & mask != base:
            return False
        if broken != "drop_except":
            for ex in block.get("except") or []:
                ebase, emask = _net(ex)
                if addr & emask == ebase:
                    return False
        return True
    ns_sel = peer.get("namespaceSelector")
    if ns_sel is None:
        if ons != policy_ns:
            return False
    elif not labels_match(ns_sel, namespaces.get(ons, {})):
        return False
    return labels_match(peer.get("podSelector"), olabels)


def policies_by_namespace(policies) -> dict:
    out = {}
    for p in policies:
        out.setdefault(p["metadata"].get("namespace", ""), []).append(p["spec"])
    return out


def _direction_allowed(by_ns, namespaces, target, other, case, direction, broken):
    tns, _, tlabels, _ = target
    rules_key, peers_key = (
        ("ingress", "from") if direction == "Ingress" else ("egress", "to")
    )
    applies = False
    for spec in by_ns.get(tns, ()):
        types = spec.get("policyTypes") or ["Ingress"]
        if direction not in types or not labels_match(spec.get("podSelector"), tlabels):
            continue
        applies = True
        for rule in spec.get(rules_key) or []:
            if not ports_match(rule.get("ports"), case, broken):
                continue
            peers = rule.get(peers_key)
            if not peers or any(
                _peer_matches(p, tns, other, namespaces, broken) for p in peers
            ):
                return True
    return not applies


def flow_verdict(by_ns, namespaces, src, dst, case, broken="") -> tuple:
    """(ingress, egress, combined) of one flow src -> dst on one port case."""
    ingress = _direction_allowed(by_ns, namespaces, dst, src, case, "Ingress", broken)
    egress = _direction_allowed(by_ns, namespaces, src, dst, case, "Egress", broken)
    return ingress, egress, ingress and egress


class GridReference:
    """Whole verdict grids of one cluster and one policy set, exactly.

    Per direction the pods fall into groups to which the same set of policies
    applies; a group's allowed peers are one boolean row over all pods.  A
    table is those rows gathered by group; a count is row sums times group
    sizes, and for `combined` the sum over (ingress group g, egress group h) of
    (sources of h that g lets in) x (destinations of g that h lets out).
    """

    def __init__(self, pods, namespaces, policies, broken=""):
        if broken not in BROKEN:
            raise ValueError(f"unknown guarantee to break: {broken!r}")
        self.broken = broken
        self.n = len(pods)
        self.namespaces = namespaces
        self.policies = policies
        ns_names = sorted({p[0] for p in pods} | set(namespaces))
        self.ns_id = {name: i for i, name in enumerate(ns_names)}
        self.pod_ns = np.array([self.ns_id[p[0]] for p in pods], dtype=np.int32)
        self.pod_ip = np.array([_ip(p[3]) for p in pods], dtype=np.uint32)
        keys = sorted({k for p in pods for k in p[2]})
        self.value_id = {k: {} for k in keys}
        self.pod_label = {}
        for k in keys:
            ids = self.value_id[k]
            self.pod_label[k] = np.array(
                [ids.setdefault(p[2][k], len(ids)) if k in p[2] else -1 for p in pods],
                dtype=np.int32,
            )
        self._memo = {}
        self._dir = {}

    # -- selectors over all pods, memoised by what they say ---------------

    def _pods_matching(self, selector) -> np.ndarray:
        key = ("pods", json.dumps(selector, sort_keys=True))
        if key not in self._memo:
            if selector and selector.get("matchExpressions"):
                raise ValueError("the grid reference reads matchLabels only")
            mask = np.ones(self.n, dtype=bool)
            for k, v in ((selector or {}).get("matchLabels") or {}).items():
                if k in self.pod_label:
                    mask &= self.pod_label[k] == self.value_id[k].get(v, -2)
                else:
                    mask[:] = False
            self._memo[key] = mask
        return self._memo[key]

    def _peer_mask(self, peer, policy_ns: str) -> np.ndarray:
        own_ns = "ipBlock" not in peer and peer.get("namespaceSelector") is None
        key = ("peer", json.dumps(peer, sort_keys=True), policy_ns if own_ns else "")
        if key in self._memo:
            return self._memo[key]
        block = peer.get("ipBlock")
        if block:
            base, netmask = _net(block["cidr"])
            mask = (self.pod_ip & np.uint32(netmask)) == np.uint32(base)
            if self.broken != "drop_except":
                for ex in block.get("except") or []:
                    ebase, emask = _net(ex)
                    mask &= (self.pod_ip & np.uint32(emask)) != np.uint32(ebase)
        else:
            if own_ns:
                ns_ok = np.zeros(len(self.ns_id), dtype=bool)
                if policy_ns in self.ns_id:
                    ns_ok[self.ns_id[policy_ns]] = True
            else:
                ns_ok = np.array([
                    labels_match(peer["namespaceSelector"], self.namespaces.get(n, {}))
                    for n in self.ns_id
                ], dtype=bool)
            mask = ns_ok[self.pod_ns] & self._pods_matching(peer.get("podSelector"))
        self._memo[key] = mask
        return mask

    # -- one direction under one port case --------------------------------

    def _groups(self, direction: str):
        """(group of each pod, each group's tuple of policy indices); the
        pods no policy applies to share the last group, whose tuple is ()."""
        if direction not in self._dir:
            applies = [[] for _ in range(self.n)]
            for i, p in enumerate(self.policies):
                spec = p["spec"]
                if direction not in (spec.get("policyTypes") or ["Ingress"]):
                    continue
                ns = self.ns_id.get(p["metadata"].get("namespace", ""))
                targets = np.flatnonzero(
                    (self.pod_ns == ns) & self._pods_matching(spec.get("podSelector"))
                )
                for t in targets.tolist():
                    applies[t].append(i)
            ids, group_of = {}, np.empty(self.n, dtype=np.int32)
            for t, a in enumerate(applies):
                if a:
                    group_of[t] = ids.setdefault(tuple(a), len(ids))
            sets = list(ids) + [()]
            for t, a in enumerate(applies):
                if not a:
                    group_of[t] = len(sets) - 1
            self._dir[direction] = (group_of, sets)
        return self._dir[direction]

    def _policy_allows(self, i: int, direction: str, case) -> np.ndarray:
        key = ("allow", i, direction, case)
        if key not in self._memo:
            p = self.policies[i]
            rules_key, peers_key = (
                ("ingress", "from") if direction == "Ingress" else ("egress", "to")
            )
            mask = np.zeros(self.n, dtype=bool)
            for rule in p["spec"].get(rules_key) or []:
                if not ports_match(rule.get("ports"), case, self.broken):
                    continue
                peers = rule.get(peers_key)
                if not peers:
                    mask[:] = True
                for peer in peers or []:
                    mask |= self._peer_mask(peer, p["metadata"].get("namespace", ""))
            self._memo[key] = mask
        return self._memo[key]

    def direction(self, direction: str, case):
        """(group_of[N], allow[G, N]): allow[group_of[t], o] says whether pod
        t accepts the other end o in this direction on this port case."""
        group_of, sets = self._groups(direction)
        allow = np.zeros((len(sets), self.n), dtype=bool)
        for g, policy_ids in enumerate(sets):
            if not policy_ids:
                allow[g] = True
            for i in policy_ids:
                allow[g] |= self._policy_allows(i, direction, tuple(case))
        return group_of, allow

    # -- the two forms of answer the entries give --------------------------

    def tables(self, cases, out=None):
        """ingress [Q, dst, src], egress and combined [Q, src, dst]; written
        into the three arrays of `out` where given (fresh pages cost more
        than the arithmetic, so a caller that compares one answer at a time
        hands the same three back)."""
        ingress, egress, combined = out or (
            np.empty((len(cases), self.n, self.n), dtype=bool) for _ in range(3)
        )
        for k, case in enumerate(cases):
            g_in, allow_in = self.direction("Ingress", case)
            g_eg, allow_eg = self.direction("Egress", case)
            np.take(allow_in, g_in, axis=0, out=ingress[k], mode="clip")
            np.take(allow_eg, g_eg, axis=0, out=egress[k], mode="clip")
            # combined[s, d] = ingress[d, s] & egress[s, d], without
            # transposing a whole table: gather the transposed rows instead
            np.take(np.ascontiguousarray(allow_in.T), g_in, axis=1, out=combined[k],
                    mode="clip")
            combined[k] &= egress[k]
        return ingress, egress, combined

    def counts(self, cases) -> dict:
        """Allowed flows summed over the cases, as the counts entry reports."""
        out = {"ingress": 0, "egress": 0, "combined": 0, "cells": 0}
        for case in cases:
            g_in, allow_in = self.direction("Ingress", case)
            g_eg, allow_eg = self.direction("Egress", case)
            size_in = np.bincount(g_in, minlength=allow_in.shape[0]).astype(np.int64)
            size_eg = np.bincount(g_eg, minlength=allow_eg.shape[0]).astype(np.int64)
            out["ingress"] += int(allow_in.sum(axis=1, dtype=np.int64) @ size_in)
            out["egress"] += int(allow_eg.sum(axis=1, dtype=np.int64) @ size_eg)
            # a[g, h]: sources of egress group h that ingress group g lets in
            # b[h, g]: destinations of ingress group g that h lets out
            a = _sum_columns_by_group(allow_in, g_eg, allow_eg.shape[0])
            b = _sum_columns_by_group(allow_eg, g_in, allow_in.shape[0])
            out["combined"] += int((a * b.T).sum(dtype=np.int64))
            out["cells"] += self.n * self.n
        return out


def _sum_columns_by_group(rows: np.ndarray, group_of: np.ndarray, n_groups: int):
    """[R, N] bool -> [R, n_groups] int64: per row, how many set columns fall
    in each group of columns."""
    order = np.argsort(group_of, kind="stable")
    sizes = np.bincount(group_of, minlength=n_groups)
    out = np.zeros((rows.shape[0], n_groups), dtype=np.int64)
    present = np.flatnonzero(sizes)
    starts = (np.cumsum(sizes) - sizes)[present]
    out[:, present] = np.add.reduceat(
        rows[:, order].astype(np.int32), starts, axis=1
    )
    return out
