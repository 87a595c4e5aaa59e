"""Seeded synthetic clusters: what the tests, chip_smoke.py, the chaos
scenarios and `serve --synthetic-pods` evaluate when no real cluster is
at hand.

One pod scheme (`_pod`): pod i is `pod-<i>` with the three cyclic labels
pod=p<i%100>, app=app<i%20>, tier=tier<i%5> and the address 10.x.y.z of
its index; namespace i is `ns<i>` with team=team<i%7>.  The two cluster
functions differ ONLY in how a pod gets its namespace:

  build_synthetic    pod i lives in namespace i % n_ns (cyclic), and a
                     NetworkPolicy set is drawn from the caller's rng
  synthetic_cluster  pod i lives in a namespace drawn from
                     random.Random(seed); pods and namespaces only

Every draw is in a fixed order, so a seeded cluster is the same in every
process (tests/test_synthetic.py pins digests): `serve --synthetic-pods N
--seed S` and whoever drives it build the same pods without asking each
other.  `benchmarks/generators.py` holds the benchmark's OWN copy of both
functions by contract (PERF.md section 6, PR 25: the benchmark imports
nothing of the program); it is not a third place to edit, and
tests/test_synthetic.py holds the two equal where they are meant to be.

cidr_cluster and tiers_lattice are the two special-purpose shapes over
the same label scheme: an ipBlock-heavy cluster for the TSS/LPM CIDR
stage, and a fixed ANP/BANP lattice for the precedence tiers.

k8s_envelope is the Kubernetes scale envelope (5,000 nodes, 150,000 pods)
as ClusterLoader2's load test lays it out, under one NetworkPolicy a
Deployment: a cluster whose classes track its workloads, with
matchExpressions, endPort ranges and SCTP in its policies.
`benchmarks/generators_envelope.py` is the benchmark's own copy
(configuration `k8s-envelope-150k`), held equal to it in
tests/test_synthetic.py.

cidr_allowlists is the cluster whose IP structure cuts across its labels
(node /24s, labels drawn a pod, allowlists kept by CIDR with except
lists): the one that auto class compression REFUSES, so the dense routes
run.  `benchmarks/generators_cidr.py` is the benchmark's own copy (its
configuration `cidr-10k-5k` holds the shape parameters repeated in
CIDR_ALLOWLISTS below), held equal to it in tests/test_synthetic.py.
"""

from __future__ import annotations

import bisect
import random
from ipaddress import IPv4Address
from typing import Optional

from .kube.netpol import (
    IntOrString,
    IPBlock,
    LabelSelector,
    NetworkPolicy,
    NetworkPolicyEgressRule,
    NetworkPolicyIngressRule,
    NetworkPolicyPeer,
    NetworkPolicyPort,
    NetworkPolicySpec,
)
from .tiers.model import (
    AdminNetworkPolicy,
    BaselineAdminNetworkPolicy,
    TierPort,
    TierRule,
    TierScope,
    TierSet,
)


def _namespaces(n_ns: int) -> dict:
    return {
        f"ns{i}": {"ns": f"ns{i}", "team": f"team{i % 7}"} for i in range(n_ns)
    }


def _pod(i: int, ns: str) -> tuple:
    """(namespace, name, labels, ip) of pod i: the engine's pod tuple."""
    labels = {
        "pod": f"p{i % 100}",
        "app": f"app{i % 20}",
        "tier": f"tier{i % 5}",
    }
    ip = f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"
    return (ns, f"pod-{i}", labels, ip)


def build_synthetic(
    n_pods: int, n_policies: int, rng: random.Random, n_ns: Optional[int] = None
):
    """(pods, namespaces, policies): pods cyclic over `n_ns` namespaces
    (default one per 250 pods, at least 2) and `n_policies` NetworkPolicies
    drawn from `rng`: an app-label target; 20 % ipBlock peers with one
    except, else a tier podSelector with a team namespaceSelector half the
    time; TCP 80, 30 % also the named UDP port; 60 % ingress-only."""
    n_ns = n_ns or max(2, n_pods // 250)
    namespaces = _namespaces(n_ns)
    pods = [_pod(i, f"ns{i % n_ns}") for i in range(n_pods)]

    policies = []
    for i in range(n_policies):
        ns = f"ns{rng.randrange(n_ns)}"
        target = LabelSelector.make(match_labels={"app": f"app{rng.randrange(20)}"})
        peers = []
        r = rng.random()
        if r < 0.2:
            peers.append(
                NetworkPolicyPeer(
                    ip_block=IPBlock.make(
                        f"10.{rng.randrange(4)}.0.0/16",
                        [f"10.{rng.randrange(4)}.{rng.randrange(8)}.0/24"],
                    )
                )
            )
        else:
            peers.append(
                NetworkPolicyPeer(
                    pod_selector=LabelSelector.make(
                        match_labels={"tier": f"tier{rng.randrange(5)}"}
                    ),
                    namespace_selector=LabelSelector.make(
                        match_labels={"team": f"team{rng.randrange(7)}"}
                    )
                    if rng.random() < 0.5
                    else None,
                )
            )
        ports = [NetworkPolicyPort(protocol="TCP", port=IntOrString(80))]
        if rng.random() < 0.3:
            ports.append(
                NetworkPolicyPort(
                    protocol="UDP", port=IntOrString("serve-81-udp")
                )
            )
        rule_i = NetworkPolicyIngressRule(ports=ports, from_=peers)
        rule_e = NetworkPolicyEgressRule(ports=ports, to=peers)
        types = ["Ingress"] if rng.random() < 0.6 else ["Ingress", "Egress"]
        policies.append(
            NetworkPolicy(
                name=f"bench-{i}",
                namespace=ns,
                spec=NetworkPolicySpec(
                    pod_selector=target,
                    policy_types=types,
                    ingress=[rule_i],
                    egress=[rule_e] if "Egress" in types else [],
                ),
            )
        )
    return pods, namespaces, policies


def synthetic_cluster(n_pods: int, n_ns: int, seed: int):
    """(pods, namespaces) as `serve --synthetic-pods` makes them: pod i
    lives in a namespace drawn from random.Random(seed)."""
    rng = random.Random(seed)
    n_ns = max(1, n_ns)
    pods = [_pod(i, f"ns{rng.randrange(n_ns)}") for i in range(n_pods)]
    return pods, _namespaces(n_ns)


def cidr_cluster(n_pods: int, distinct: int, pool: int):
    """(pods, namespaces, netpols, rng): an ipBlock-heavy cluster —
    `distinct` distinct (base, mask, excepts) rows over `n_pods` pods
    drawn from a bounded pool of `pool` IPs, the regime where IP
    structure, not labels, carries the signature entropy.  The rng is
    returned mid-stream so a caller's later draws follow the cluster's."""
    rng = random.Random(424242)
    namespaces = {"cidr": {"ns": "cidr"}}
    ip_pool = sorted(
        {
            f"10.{rng.randrange(64)}.{rng.randrange(256)}"
            f".{rng.randrange(1, 255)}"
            for _ in range(pool)
        }
    )
    # two label shapes on purpose: the signature entropy must come from
    # the CIDR structure, which is exactly what the TSS stage compresses
    pods = [
        ("cidr", f"p{i}", {"app": f"app{i % 2}"}, ip_pool[i % len(ip_pool)])
        for i in range(n_pods)
    ]
    # the distinct-CIDR corpus: /32 splinters on the pod pool's /24s
    # (membership actually varies) plus an UNBOUNDED /32 family over
    # 10.0.0.0/10 (~4.2M candidates, so `distinct` can reach 100k;
    # pool-only families cap at ~49k and the rejection loop would spin
    # forever), /24 and /16 ladders, excepts.  The attempts bound keeps
    # a request past the family capacity from hanging: the cluster then
    # holds what it got.
    cidrs: list = []
    seen = set()
    attempts = 0
    while len(cidrs) < distinct and attempts < 64 * distinct:
        attempts += 1
        roll = rng.random()
        if roll < 0.30:
            ip = rng.choice(ip_pool)
            a, b, c, _d = ip.split(".")
            cand = (f"{a}.{b}.{c}.{rng.randrange(256)}/32", ())
        elif roll < 0.55:
            cand = (
                f"10.{rng.randrange(64)}.{rng.randrange(256)}"
                f".{rng.randrange(256)}/32",
                (),
            )
        elif roll < 0.80:
            cand = (
                f"10.{rng.randrange(64)}.{rng.randrange(256)}.0/24",
                (),
            )
        elif roll < 0.92:
            b2 = rng.randrange(64)
            cand = (f"10.{b2}.0.0/16", (f"10.{b2}.{rng.randrange(256)}.0/24",))
        else:
            cand = (f"10.{rng.randrange(64)}.0.0/{rng.choice((12, 14, 15))}", ())
        if cand not in seen:
            seen.add(cand)
            cidrs.append(cand)
    per_rule = 64
    netpols = []
    for i in range(0, len(cidrs), per_rule):
        chunk = cidrs[i : i + per_rule]
        peers = [
            NetworkPolicyPeer(ip_block=IPBlock.make(c, list(ex)))
            for c, ex in chunk
        ]
        netpols.append(
            NetworkPolicy(
                name=f"cidr-{i // per_rule}",
                namespace="cidr",
                spec=NetworkPolicySpec(
                    pod_selector=LabelSelector.make(),
                    policy_types=["Ingress", "Egress"],
                    ingress=[NetworkPolicyIngressRule(ports=[], from_=peers)],
                    egress=[NetworkPolicyEgressRule(ports=[], to=peers)],
                ),
            )
        )
    return pods, namespaces, netpols, rng


#: the shapes of cidr_allowlists; benchmarks/configs/cidr-10k-5k.json's
#: `generator` block holds the same values (tests/test_synthetic.py)
CIDR_ALLOWLISTS = {
    "structure_seed": 20261002,
    "pods_per_node": 110,  # kubelet --max-pods; a node owns a /24
    "vocab": {"app": 20, "tier": 5},
    "peers_per_rule": [1, 2, 2, 4, 4, 8],
    "tier_peer_share": 0.3,
    "ingress_only_share": 0.4,
    "egress_only_share": 0.3,
    "named_udp_share": 0.3,
    # [prefix length, weight]: ClassBench ACL-like, as recalled
    "prefix_lengths": [
        [32, 35], [24, 25], [16, 8], [28, 7], [27, 5], [26, 5], [20, 5],
        [22, 4], [30, 2], [12, 2], [8, 2],
    ],
    "inside_share": 0.5,
    "outside_first_octet": [11, 223],
    "except_max_prefix": 28,
    "except_counts": [0, 0, 1, 1, 2, 4],
    "except_extra_bits": [4, 8],
}


def _node_pod_addr(i: int, per_node: int) -> int:
    """Pod i's address: node i // per_node owns
    10.(64 + (node >> 8)).(node & 255).0/24, host byte 1 + i % per_node."""
    node = i // per_node
    return (
        (10 << 24) | ((64 + (node >> 8)) << 16) | ((node & 255) << 8)
        | (1 + i % per_node)
    )


def _allowlist_block(n_pods: int, gen: dict, rng: random.Random) -> IPBlock:
    lengths, weights = zip(*gen["prefix_lengths"])
    (length,) = rng.choices(lengths, weights)
    if rng.random() < gen["inside_share"]:
        addr = _node_pod_addr(rng.randrange(n_pods), gen["pods_per_node"])
    else:
        lo, hi = gen["outside_first_octet"]
        addr = (rng.randrange(lo, hi + 1) << 24) | rng.getrandbits(24)
    base = addr & ~((1 << (32 - length)) - 1)
    excepts: list = []
    if length <= gen["except_max_prefix"]:
        for _ in range(rng.choice(gen["except_counts"])):
            longer = length + rng.choice(gen["except_extra_bits"])
            if longer > 32:
                longer = length + min(gen["except_extra_bits"])
            inside = base | (rng.getrandbits(longer - length) << (32 - longer))
            entry = f"{IPv4Address(inside)}/{longer}"
            if entry not in excepts:
                excepts.append(entry)
    return IPBlock.make(f"{IPv4Address(base)}/{length}", excepts)


def _allowlist_peers(n_pods: int, gen: dict, rng: random.Random) -> list:
    peers = [
        NetworkPolicyPeer(ip_block=_allowlist_block(n_pods, gen, rng))
        for _ in range(rng.choice(gen["peers_per_rule"]))
    ]
    if rng.random() < gen["tier_peer_share"]:
        tier = f"tier{rng.randrange(gen['vocab']['tier'])}"
        peers.append(
            NetworkPolicyPeer(
                pod_selector=LabelSelector.make(match_labels={"tier": tier})
            )
        )
    return peers


def cidr_allowlists(
    n_pods: int, n_policies: int, n_ns: int, gen: Optional[dict] = None
):
    """(pods, namespaces, policies): pod i in namespace i % n_ns on node
    i // 110 (a /24 a node), `app` and `tier` drawn a pod; `n_policies`
    NetworkPolicies that target one app of one namespace with one rule a
    direction of 1 - 8 ipBlock peers (half inside the pod range, half
    external; prefix lengths by CIDR_ALLOWLISTS; except lists on /28 and
    shorter) and sometimes a tier podSelector beside them; 40 % ingress
    only, 30 % egress only, 30 % both.  Every draw comes from
    gen["structure_seed"], pods and policies from separate streams."""
    gen = gen or CIDR_ALLOWLISTS
    vocab, per_node = gen["vocab"], gen["pods_per_node"]
    rng = random.Random(f"{gen['structure_seed']}/pods")
    pods = []
    for i in range(n_pods):
        labels = {
            "app": f"app{rng.randrange(vocab['app'])}",
            "tier": f"tier{rng.randrange(vocab['tier'])}",
        }
        pods.append(
            (f"ns{i % n_ns}", f"pod-{i}", labels,
             str(IPv4Address(_node_pod_addr(i, per_node))))
        )
    namespaces = {f"ns{i}": {"ns": f"ns{i}"} for i in range(n_ns)}

    rng = random.Random(f"{gen['structure_seed']}/policy-set/0")
    policies = []
    for i in range(n_policies):
        ns = f"ns{rng.randrange(n_ns)}"
        target = LabelSelector.make(
            match_labels={"app": f"app{rng.randrange(vocab['app'])}"}
        )
        ports = [NetworkPolicyPort(protocol="TCP", port=IntOrString(80))]
        if rng.random() < gen["named_udp_share"]:
            ports.append(
                NetworkPolicyPort(protocol="UDP", port=IntOrString("serve-81-udp"))
            )
        roll = rng.random()
        if roll < gen["ingress_only_share"]:
            types = ["Ingress"]
        elif roll < gen["ingress_only_share"] + gen["egress_only_share"]:
            types = ["Egress"]
        else:
            types = ["Ingress", "Egress"]
        ingress, egress = [], []
        if "Ingress" in types:
            ingress = [NetworkPolicyIngressRule(
                ports=ports, from_=_allowlist_peers(n_pods, gen, rng)
            )]
        if "Egress" in types:
            egress = [NetworkPolicyEgressRule(
                ports=ports, to=_allowlist_peers(n_pods, gen, rng)
            )]
        policies.append(
            NetworkPolicy(
                name=f"cidr-{i}",
                namespace=ns,
                spec=NetworkPolicySpec(
                    pod_selector=target,
                    policy_types=types,
                    ingress=ingress,
                    egress=egress,
                ),
            )
        )
    return pods, namespaces, policies


def tiers_lattice() -> TierSet:
    """A fixed ANP/BANP lattice over the pod scheme's labels: overlapping
    priorities (two at 5), a Pass-chain into the NP tier, an endPort
    range, SCTP, and a BANP default-deny for one app."""
    return TierSet(
        anps=[
            AdminNetworkPolicy(
                name="bench-deny-tier0", priority=5,
                subject=TierScope(
                    pod_selector=LabelSelector.make({"tier": "tier0"})
                ),
                ingress=[TierRule(
                    action="Deny",
                    peers=[TierScope(
                        pod_selector=LabelSelector.make({"app": "app1"})
                    )],
                    ports=[TierPort(
                        protocol="TCP", port=IntOrString(80), end_port=81
                    )],
                )],
            ),
            AdminNetworkPolicy(
                name="bench-pass-tier1", priority=5,
                subject=TierScope(
                    pod_selector=LabelSelector.make({"tier": "tier1"})
                ),
                ingress=[TierRule(
                    action="Pass", peers=[TierScope()],
                )],
            ),
            AdminNetworkPolicy(
                name="bench-allow-sctp", priority=9,
                subject=TierScope(),
                ingress=[TierRule(
                    action="Allow",
                    peers=[TierScope(
                        namespace_selector=LabelSelector.make(
                            {"team": "team0"}
                        )
                    )],
                    ports=[TierPort(
                        protocol="SCTP", port=IntOrString(82)
                    )],
                )],
            ),
        ],
        banp=BaselineAdminNetworkPolicy(
            subject=TierScope(
                pod_selector=LabelSelector.make({"app": "app2"})
            ),
            ingress=[TierRule(action="Deny", peers=[TierScope()])],
        ),
    )


#: the shapes of anp_tenancy; benchmarks/configs/anp-100k-10k.json's `tiers`
#: block holds the same values (tests/test_synthetic.py).  The CRD caps
#: (100 rules a direction, 100 peers and ports a rule) are recalled from
#: network-policy-api v1alpha1, and the shares are assumed
ANP_TENANCY = {
    "tier_seed": 20261015,
    "apps": 10,
    "rules": 100,  # a direction of every app ANP: the CRD's cap
    "app_priority": 10,  # app ANP K at app_priority + K // 2: ties in pairs
    "tenants": 7,
    "tenant_priority": 50,
    "monitoring_ns": "ns0",
    "dns_ns": "ns1",
    "vocab": {"pod": 100, "tier": 5, "team": 7},
    "actions": [["Allow", 45], ["Deny", 35], ["Pass", 20]],
    "peers_per_rule": [1, 2],
    "ports_per_rule": [0, 1, 2],
    "port_terms": [
        {"portNumber": {"protocol": "TCP", "port": 80}},
        {"portRange": {"protocol": "TCP", "start": 80, "end": 81}},
        {"namedPort": "serve-81-udp"},
        {"portNumber": {"protocol": "UDP", "port": 80}},
        {"portNumber": {"protocol": "SCTP", "port": 81}},
    ],
}


def _ns_scope(labels: dict) -> dict:
    return {"namespaces": {"matchLabels": labels} if labels else {}}


def _anp_peer(n_ns: int, gen: dict, rng: random.Random) -> dict:
    vocab = gen["vocab"]
    kind = rng.randrange(4)
    if kind == 0:
        return _ns_scope({"team": f"team{rng.randrange(vocab['team'])}"})
    if kind == 1:
        return _ns_scope({"ns": f"ns{rng.randrange(n_ns)}"})
    if kind == 2:
        return {"pods": {
            "namespaceSelector": {
                "matchLabels": {"team": f"team{rng.randrange(vocab['team'])}"}
            },
            "podSelector": {
                "matchLabels": {"tier": f"tier{rng.randrange(vocab['tier'])}"}
            },
        }}
    return {"pods": {
        "namespaceSelector": {},
        "podSelector": {"matchLabels": {"pod": f"p{rng.randrange(vocab['pod'])}"}},
    }}


def _anp_rule(n_ns: int, gen: dict, rng: random.Random, peers_key: str) -> dict:
    actions, weights = zip(*gen["actions"])
    (action,) = rng.choices(actions, weights)
    rule = {
        "action": action,
        peers_key: [
            _anp_peer(n_ns, gen, rng) for _ in range(rng.choice(gen["peers_per_rule"]))
        ],
    }
    n_ports = rng.choice(gen["ports_per_rule"])
    if n_ports:
        rule["ports"] = [dict(t) for t in rng.sample(gen["port_terms"], n_ports)]
    return rule


def anp_tenancy_objects(n_ns: int, gen: Optional[dict] = None) -> tuple:
    """(ANP dicts, the BANP dict) of anp_tenancy, as a user's YAML holds
    them, in the order they are drawn."""
    gen = gen or ANP_TENANCY
    rng = random.Random(f"{gen['tier_seed']}/anps")
    meta = {"apiVersion": "policy.networking.k8s.io/v1alpha1"}
    monitoring = [_ns_scope({"ns": gen["monitoring_ns"]})]
    anps = [dict(meta, kind="AdminNetworkPolicy", metadata={"name": "platform-allow"},
                 spec={
        "priority": 0,
        "subject": _ns_scope({}),
        "ingress": [{"action": "Allow", "from": monitoring}],
        "egress": [{"action": "Allow", "to": [_ns_scope({"ns": gen["dns_ns"]})],
                    "ports": [{"portNumber": {"protocol": "UDP", "port": 53}},
                              {"portNumber": {"protocol": "TCP", "port": 53}}]}],
    })]
    for k in range(gen["apps"]):
        anps.append(dict(meta, kind="AdminNetworkPolicy",
                         metadata={"name": f"app-app{k}"}, spec={
            "priority": gen["app_priority"] + k // 2,
            "subject": {"pods": {
                "namespaceSelector": {},
                "podSelector": {"matchLabels": {"app": f"app{k}"}},
            }},
            "ingress": [_anp_rule(n_ns, gen, rng, "from") for _ in range(gen["rules"])],
            "egress": [_anp_rule(n_ns, gen, rng, "to") for _ in range(gen["rules"])],
        }))
    for k in range(gen["tenants"]):
        team = {"team": f"team{k}"}
        anps.append(dict(meta, kind="AdminNetworkPolicy",
                         metadata={"name": f"tenant-team{k}"}, spec={
            "priority": gen["tenant_priority"] + k,
            "subject": _ns_scope(team),
            "ingress": [{"action": "Pass", "from": [_ns_scope(team)]},
                        {"action": "Deny", "from": [_ns_scope({})]}],
            "egress": [{"action": "Pass", "to": [_ns_scope(team)]},
                       {"action": "Deny", "to": [_ns_scope({})]}],
        }))
    banp = dict(meta, kind="BaselineAdminNetworkPolicy",
                metadata={"name": "default"}, spec={
        "subject": _ns_scope({}),
        "ingress": [{"action": "Allow", "from": monitoring},
                    {"action": "Deny", "from": [_ns_scope({})]}],
    })
    return anps, banp


def anp_tenancy(n_ns: int, gen: Optional[dict] = None) -> TierSet:
    """A platform team's admin tiers over a cluster of `n_ns` namespaces
    (`_namespaces`' labels) and the pod scheme's labels: `platform-allow`
    (priority 0: monitoring in from ns0, DNS out to ns1), one guardrail ANP
    an app for the first `apps` apps at the CRD's cap of `rules` rules a
    direction (Allow / Deny / Pass drawn by `actions`, 1 - 2 peers over
    team, ns, team x tier and pod labels, 0 - 2 of `port_terms`), one
    tenant ANP a team (Pass within the team, then Deny), and the BANP
    `default` (monitoring in, then Deny).  Drawn as plain dicts from
    gen["tier_seed"] (anp_tenancy_objects, of which
    benchmarks/generators_tiers.py is the benchmark's copy) and parsed by
    the model's own from_dict."""
    anps, banp = anp_tenancy_objects(n_ns, gen)
    tiers = TierSet(
        anps=[AdminNetworkPolicy.from_dict(d) for d in anps],
        banp=BaselineAdminNetworkPolicy.from_dict(banp),
    )
    tiers.validate()
    return tiers


#: the shapes of k8s_envelope; benchmarks/configs/k8s-envelope-150k.json's
#: `generator` block holds the same values (tests/test_synthetic.py).  The
#: ClusterLoader2 defaults and the shares are recalled or assumed (the
#: configuration's `assumed` says which)
K8S_ENVELOPE = {
    "structure_seed": 20261018,
    "pods_per_node": 30,  # ClusterLoader2's PODS_PER_NODE
    "host_first": 2,
    # [replicas, Deployments a namespace]: big / medium / small
    "deployments": [[250, 3], [30, 25], [5, 300]],
    "tiers": ["frontend", "backend", "cache", "db"],
    "teams": 7,
    "callers": [1, 4],
    "zipf": 1.1,
    "cross_namespace_share": 0.1,
    "expression_share": 0.05,
    "ports": [
        [{"protocol": "TCP", "port": 80}, 60],
        [{"protocol": "TCP", "port": 80, "endPort": 81}, 15],
        [{"protocol": "UDP", "port": "serve-81-udp"}, 15],
        [{"protocol": "SCTP", "port": 81}, 10],
    ],
}
_HASH_CHARS = "bcdfghjklmnpqrstvwxz2456789"
_ENVELOPE_SYSTEM = (
    ("kube-system", "coredns", {"k8s-app": "kube-dns"}, 2),
    ("monitoring", "prometheus", {"app": "prometheus"}, 2),
)
_NOT_A_DB = {"matchExpressions": [
    {"key": "tier", "operator": "NotIn", "values": ["db"]},
    {"key": "version", "operator": "Exists"},
    {"key": "k8s-app", "operator": "DoesNotExist"},
]}


def _node_addr(node: int, host: int) -> str:
    return str(IPv4Address(
        (10 << 24) | ((node // 256) << 16) | ((node % 256) << 8) | host
    ))


def _template_hash(rng: random.Random, seen: set) -> str:
    while True:
        h = "".join(rng.choice(_HASH_CHARS) for _ in range(10))
        if h not in seen:
            seen.add(h)
            return h


def _envelope_deployments(n_ns: int, gen: dict) -> list:
    rng = random.Random(f"{gen['structure_seed']}/deployments")
    seen, out = set(), []
    for i in range(n_ns):
        ns, k = f"load-{i}", 0
        for replicas, count in gen["deployments"]:
            for _ in range(count):
                app = f"{ns}-d{k}"
                labels = {
                    "app": app,
                    "pod-template-hash": _template_hash(rng, seen),
                    "tier": rng.choice(gen["tiers"]),
                    "version": "v1",
                }
                out.append((ns, app, replicas, labels))
                k += 1
    return out


def _envelope_callers(apps: list, gen: dict, rng: random.Random) -> dict:
    ranked = list(apps)
    rng.shuffle(ranked)
    cum, total = [], 0.0
    for r in range(len(ranked)):
        total += (r + 1) ** -gen["zipf"]
        cum.append(total)
    lo, hi = gen["callers"]
    callers = {}
    for app in apps:
        want = min(rng.randint(lo, hi), len(apps) - 1)
        chosen: list = []
        while len(chosen) < want:
            pick = ranked[bisect.bisect_right(cum, rng.random() * total)]
            if pick != app and pick not in chosen:
                chosen.append(pick)
        callers[app] = chosen
    return callers


def _netpol(ns: str, name: str, spec: dict) -> dict:
    return {
        "apiVersion": "networking.k8s.io/v1",
        "kind": "NetworkPolicy",
        "metadata": {"name": name, "namespace": ns},
        "spec": spec,
    }


def _envelope_namespace_policies(ns: str) -> list:
    def sel(name: str, value: str) -> dict:
        return {"matchLabels": {name: value}}

    return [
        _netpol(ns, "default-deny-ingress", {"podSelector": {}, "policyTypes": ["Ingress"]}),
        _netpol(ns, "default-deny-egress", {"podSelector": {}, "policyTypes": ["Egress"]}),
        _netpol(ns, "allow-dns", {
            "podSelector": {}, "policyTypes": ["Egress"],
            "egress": [{
                "to": [{
                    "namespaceSelector": sel("kubernetes.io/metadata.name", "kube-system"),
                    "podSelector": sel("k8s-app", "kube-dns"),
                }],
                "ports": [{"protocol": "UDP", "port": 53}, {"protocol": "TCP", "port": 53}],
            }],
        }),
        _netpol(ns, "allow-monitoring", {
            "podSelector": {}, "policyTypes": ["Ingress"],
            "ingress": [{
                "from": [{
                    "namespaceSelector": sel("kubernetes.io/metadata.name", "monitoring"),
                    "podSelector": sel("app", "prometheus"),
                }],
                "ports": [{"protocol": "TCP", "port": 80}],
            }],
        }),
        _netpol(ns, "allow-external-egress", {
            "podSelector": sel("tier", "frontend"), "policyTypes": ["Egress"],
            "egress": [{
                "to": [{"ipBlock": {"cidr": "0.0.0.0/0", "except": ["10.0.0.0/8"]}}],
                "ports": [{"protocol": "TCP", "port": 80}, {"protocol": "TCP", "port": 443}],
            }],
        }),
    ]


def _apps_in(apps: list) -> dict:
    return {"matchExpressions": [{"key": "app", "operator": "In", "values": list(apps)}]}


def k8s_envelope_objects(
    n_ns: int, n_nodes: int, gen: Optional[dict] = None
) -> tuple:
    """(pods, namespaces, NetworkPolicy dicts) of the scale envelope, in the
    order they are drawn: `n_ns` load namespaces `load-<i>` of the
    Deployments `gen["deployments"]` lists ([replicas, how many]), each
    replica labelled app / pod-template-hash / tier / version, spread round
    robin over `n_nodes` nodes (node k owns 10.(k // 256).(k % 256).0/24),
    then kube-system's CoreDNS and monitoring's Prometheus (two pods each).
    Per load namespace five policies (default deny in and out, DNS out,
    monitoring in, the frontends' external egress), and per Deployment one:
    ingress from its 1 - 4 Zipf-drawn callers as `app In (...)`, egress to
    its callees as `app In (...)`, one rule a serving port (TCP 80, TCP
    80 - 81, UDP serve-81-udp, SCTP 81); a share admits its team's
    frontends across namespaces, a share every non-db pod of its namespace
    (NotIn / Exists / DoesNotExist).  Every draw comes from
    gen["structure_seed"]."""
    gen = gen or K8S_ENVELOPE
    rng = random.Random(f"{gen['structure_seed']}/system")
    deployments = _envelope_deployments(n_ns, gen)
    seen: set = set()
    pods, g = [], 0
    for ns, app, replicas, labels in deployments:
        for j in range(replicas):
            ip = _node_addr(g % n_nodes, gen["host_first"] + g // n_nodes)
            pods.append((ns, f"{app}-{labels['pod-template-hash']}-{j}", labels, ip))
            g += 1
    node = 0
    for ns, name, base, replicas in _ENVELOPE_SYSTEM:
        labels = dict(base, **{"pod-template-hash": _template_hash(rng, seen)})
        for j in range(replicas):
            ip = _node_addr(node, gen["host_first"] + gen["pods_per_node"])
            pods.append((ns, f"{name}-{labels['pod-template-hash']}-{j}", labels, ip))
            node += 1
    namespaces = {
        f"load-{i}": {
            "kubernetes.io/metadata.name": f"load-{i}",
            "team": f"team{i % gen['teams']}",
            "env": "prod" if i % 2 == 0 else "staging",
        }
        for i in range(n_ns)
    }
    for ns, _, _, _ in _ENVELOPE_SYSTEM:
        namespaces[ns] = {"kubernetes.io/metadata.name": ns}

    rng = random.Random(f"{gen['structure_seed']}/policies")
    terms, weights = zip(*gen["ports"])
    by_ns: dict = {}
    for ns, app, _, _ in deployments:
        by_ns.setdefault(ns, []).append(app)
    policies = []
    for i, (ns, apps) in enumerate(by_ns.items()):
        policies.extend(_envelope_namespace_policies(ns))
        callers = _envelope_callers(apps, gen, rng)
        serves = {app: rng.choices(range(len(terms)), weights)[0] for app in apps}
        callees: dict = {app: [] for app in apps}
        for app in apps:
            for caller in callers[app]:
                callees[caller].append(app)
        team = f"team{i % gen['teams']}"
        for app in apps:
            if rng.random() < gen["expression_share"]:
                peers = [{"podSelector": _NOT_A_DB}]
            else:
                peers = [{"podSelector": _apps_in(callers[app])}]
            if rng.random() < gen["cross_namespace_share"]:
                peers.append({
                    "namespaceSelector": {"matchLabels": {"team": team}},
                    "podSelector": {"matchLabels": {"tier": "frontend"}},
                })
            spec = {
                "podSelector": {"matchLabels": {"app": app}},
                "policyTypes": ["Ingress", "Egress"],
                "ingress": [{"from": peers, "ports": [dict(terms[serves[app]])]}],
            }
            egress = []
            for term in range(len(terms)):
                to = [c for c in callees[app] if serves[c] == term]
                if to:
                    egress.append({
                        "to": [{"podSelector": _apps_in(to)}],
                        "ports": [dict(terms[term])],
                    })
            if egress:
                spec["egress"] = egress
            policies.append(_netpol(ns, f"allow-{app}", spec))
    return pods, namespaces, policies


def k8s_envelope(n_ns: int, n_nodes: int, gen: Optional[dict] = None) -> tuple:
    """(pods, namespaces, NetworkPolicies) of k8s_envelope_objects, the
    policies parsed by the program's own parser."""
    from .kube.yaml_io import parse_policy_dict

    pods, namespaces, policies = k8s_envelope_objects(n_ns, n_nodes, gen)
    return pods, namespaces, [parse_policy_dict(d) for d in policies]
