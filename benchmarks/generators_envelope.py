"""Seeded inputs of the deployment `k8s-envelope-150k`: the Kubernetes scale
envelope (5,000 nodes, 150,000 pods) as ClusterLoader2's load test lays it
out, under one NetworkPolicy a Deployment, so that classes track workloads.

The cluster.  `namespaces` load namespaces `load-<i>`, each of the
`deployments` Deployments ([replicas, how many] pairs: 3 of 250, 25 of 30 and
300 of 5 replicas, 3,000 pods), and two system namespaces: `kube-system` with
CoreDNS (`k8s-app: kube-dns`) and `monitoring` with Prometheus
(`app: prometheus`).  Every replica of a Deployment carries the same labels:
`app: <ns>-d<k>` (unique to the Deployment), `pod-template-hash` (10
characters, unique), `tier` (drawn from `tiers`) and `version: v1`.  A
namespace carries `kubernetes.io/metadata.name` (as the API server sets it),
`team: team<i % teams>` and `env` (prod for even i, staging for odd).

Addresses follow Kubernetes' defaults: node k owns 10.(k // 256).(k % 256).0/24
(--node-cidr-mask-size 24); the load pods are spread over the nodes round robin
(load pod g on node g % nodes, host byte `host_first` + g // nodes), and the
four system pods sit at host byte `host_first` + `pods_per_node` of nodes 0 - 3.

The policies, shaped by the kubernetes-network-policy-recipes collection.  Per
load namespace: `default-deny-ingress` (recipe 03), `default-deny-egress`
(recipe 12), `allow-dns` (recipe 11a: egress to kube-system's kube-dns on UDP
and TCP 53), `allow-monitoring` (recipe 07: ingress from monitoring's
Prometheus on TCP 80) and `allow-external-egress` (recipe 08's egress form:
`tier: frontend` to 0.0.0.0/0 except 10.0.0.0/8 on TCP 80 and 443).  Per
Deployment, `allow-<app>` (recipe 02): the Deployment's pods, ingress from its
callers as `app In (...)`, egress to its callees as `app In (...)`, one rule a
serving port.  The call graph: every Deployment has 1 - 4 callers drawn from
its namespace by a Zipf(`zipf`) popularity over a seeded ranking of the
namespace's Deployments, so that a few gateways call many services; a
Deployment serves on ONE port term drawn from `ports` (TCP 80, TCP 80 with
endPort 81, the named UDP port serve-81-udp, SCTP 81), its ingress rule opens
that term, and a caller's egress holds one rule a serving port of its callees.
`cross_namespace_share` of the Deployments also admit their team's frontends
from every namespace of the team (recipe 07's namespace x pod peer), and
`expression_share` admit, in place of their callers, every pod of the
namespace that is not a db (`tier NotIn [db]`, `version Exists`,
`k8s-app DoesNotExist`), so that every operator is present.

Everything is drawn from the configuration's `structure_seed`, so every
`--seed` meets the same cluster and the same counts; `--seed` orders the pods
and the policies.

Plain data: pods are (namespace, name, labels, ip) tuples, namespaces a dict of
label dicts, policies Kubernetes-shaped dicts.  Nothing of the program is
imported.  The program's copy is cyclonus_tpu/synthetic.py `k8s_envelope`;
tests/test_synthetic.py holds the two equal.
"""

import bisect
import random
from ipaddress import IPv4Address

HASH_CHARS = "bcdfghjklmnpqrstvwxz2456789"
SYSTEM = (
    ("kube-system", "coredns", {"k8s-app": "kube-dns"}, 2),
    ("monitoring", "prometheus", {"app": "prometheus"}, 2),
)


def node_addr(node: int, host: int) -> str:
    return str(IPv4Address((10 << 24) | ((node // 256) << 16) | ((node % 256) << 8) | host))


def template_hash(rng, seen: set) -> str:
    while True:
        h = "".join(rng.choice(HASH_CHARS) for _ in range(10))
        if h not in seen:
            seen.add(h)
            return h


def deployments(n_ns: int, gen: dict):
    """[(namespace, app, replicas, labels)] of the load namespaces, in order."""
    rng = random.Random(f"{gen['structure_seed']}/deployments")
    seen, out = set(), []
    for i in range(n_ns):
        ns, k = f"load-{i}", 0
        for replicas, count in gen["deployments"]:
            for _ in range(count):
                app = f"{ns}-d{k}"
                labels = {
                    "app": app,
                    "pod-template-hash": template_hash(rng, seen),
                    "tier": rng.choice(gen["tiers"]),
                    "version": "v1",
                }
                out.append((ns, app, replicas, labels))
                k += 1
    return out


def cluster(sizes: dict, gen: dict):
    """(pods, namespaces) in index order: the load pods, then the system pods."""
    n_ns, nodes = sizes["namespaces"], sizes["nodes"]
    rng = random.Random(f"{gen['structure_seed']}/system")
    seen, pods, g = set(), [], 0
    for ns, app, replicas, labels in deployments(n_ns, gen):
        for j in range(replicas):
            ip = node_addr(g % nodes, gen["host_first"] + g // nodes)
            pods.append((ns, f"{app}-{labels['pod-template-hash']}-{j}", labels, ip))
            g += 1
    node = 0
    for ns, name, base, replicas in SYSTEM:
        labels = dict(base, **{"pod-template-hash": template_hash(rng, seen)})
        for j in range(replicas):
            ip = node_addr(node, gen["host_first"] + gen["pods_per_node"])
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
    for ns, _, _, _ in SYSTEM:
        namespaces[ns] = {"kubernetes.io/metadata.name": ns}
    return pods, namespaces


def call_graph(apps: list, gen: dict, rng):
    """{app: [its callers]}: 1 - 4 callers a Deployment, drawn without
    repeats from the other Deployments of its namespace by Zipf popularity
    over a seeded ranking of them."""
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
        chosen = []
        while len(chosen) < want:
            pick = ranked[bisect.bisect_right(cum, rng.random() * total)]
            if pick != app and pick not in chosen:
                chosen.append(pick)
        callers[app] = chosen
    return callers


def _policy(ns: str, name: str, spec: dict) -> dict:
    return {
        "apiVersion": "networking.k8s.io/v1",
        "kind": "NetworkPolicy",
        "metadata": {"name": name, "namespace": ns},
        "spec": spec,
    }


def _selector(name: str, value: str) -> dict:
    return {"matchLabels": {name: value}}


def namespace_policies(ns: str) -> list:
    """The five policies every load namespace holds."""
    every = {}
    return [
        _policy(ns, "default-deny-ingress", {"podSelector": every, "policyTypes": ["Ingress"]}),
        _policy(ns, "default-deny-egress", {"podSelector": every, "policyTypes": ["Egress"]}),
        _policy(ns, "allow-dns", {
            "podSelector": every, "policyTypes": ["Egress"],
            "egress": [{
                "to": [{
                    "namespaceSelector": _selector("kubernetes.io/metadata.name", "kube-system"),
                    "podSelector": _selector("k8s-app", "kube-dns"),
                }],
                "ports": [{"protocol": "UDP", "port": 53}, {"protocol": "TCP", "port": 53}],
            }],
        }),
        _policy(ns, "allow-monitoring", {
            "podSelector": every, "policyTypes": ["Ingress"],
            "ingress": [{
                "from": [{
                    "namespaceSelector": _selector("kubernetes.io/metadata.name", "monitoring"),
                    "podSelector": _selector("app", "prometheus"),
                }],
                "ports": [{"protocol": "TCP", "port": 80}],
            }],
        }),
        _policy(ns, "allow-external-egress", {
            "podSelector": _selector("tier", "frontend"), "policyTypes": ["Egress"],
            "egress": [{
                "to": [{"ipBlock": {"cidr": "0.0.0.0/0", "except": ["10.0.0.0/8"]}}],
                "ports": [{"protocol": "TCP", "port": 80}, {"protocol": "TCP", "port": 443}],
            }],
        }),
    ]


def _apps_in(apps: list) -> dict:
    return {"matchExpressions": [{"key": "app", "operator": "In", "values": list(apps)}]}


NOT_A_DB = {"matchExpressions": [
    {"key": "tier", "operator": "NotIn", "values": ["db"]},
    {"key": "version", "operator": "Exists"},
    {"key": "k8s-app", "operator": "DoesNotExist"},
]}


def policies(sizes: dict, gen: dict) -> list:
    """Every policy of the deployment, in the order it is drawn."""
    rng = random.Random(f"{gen['structure_seed']}/policies")
    terms, weights = zip(*gen["ports"])
    by_ns = {}
    for ns, app, _, _ in deployments(sizes["namespaces"], gen):
        by_ns.setdefault(ns, []).append(app)
    out = []
    for i, (ns, apps) in enumerate(by_ns.items()):
        out.extend(namespace_policies(ns))
        callers = call_graph(apps, gen, rng)
        serves = {app: rng.choices(range(len(terms)), weights)[0] for app in apps}
        callees = {app: [] for app in apps}
        for app in apps:
            for caller in callers[app]:
                callees[caller].append(app)
        team = f"team{i % gen['teams']}"
        for app in apps:
            if rng.random() < gen["expression_share"]:
                peers = [{"podSelector": NOT_A_DB}]
            else:
                peers = [{"podSelector": _apps_in(callers[app])}]
            if rng.random() < gen["cross_namespace_share"]:
                peers.append({
                    "namespaceSelector": _selector("team", team),
                    "podSelector": _selector("tier", "frontend"),
                })
            spec = {
                "podSelector": _selector("app", app),
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
            out.append(_policy(ns, f"allow-{app}", spec))
    return out


def build(sizes: dict, gen: dict, seed: int):
    """(pods, namespaces, policies) of the configuration's ONE deployment, in
    the order `seed` gives it."""
    pods, namespaces = cluster(sizes, gen)
    drawn = policies(sizes, gen)
    if (len(pods), len(drawn)) != (sizes["pods"], sizes["policies"]):
        raise ValueError(
            f"the configuration says {sizes['pods']} pods and {sizes['policies']} "
            f"policies; its generator draws {len(pods)} and {len(drawn)}"
        )
    random.Random(f"{seed}/pod-order").shuffle(pods)
    random.Random(f"{seed}/policy-order").shuffle(drawn)
    return pods, namespaces, drawn
