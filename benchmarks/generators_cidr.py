"""Seeded inputs of the ipBlock-heavy deployment `cidr-10k-5k`: a cluster whose
IP structure cuts across its labels, and allowlists kept by CIDR.

The cluster follows Kubernetes' documented defaults: every node owns a /24 of
the pod range (kube-controller-manager --node-cidr-mask-size 24) and holds at
most `pods_per_node` pods (kubelet --max-pods 110).  Node k owns
10.(64 + (k >> 8)).(k & 255).0/24; pod i lives on node i // pods_per_node at
host byte 1 + i % pods_per_node, in namespace i % n_ns.  Its `app` and `tier`
labels are drawn a pod, independent of namespace and node (a scheduler spreads
replicas over nodes), so every namespace holds every app and every policy
selects pods.

A policy targets one `app` of one namespace and has one rule a direction, with
several ipBlock peers (and sometimes a `tier` podSelector beside them).  An
ipBlock's prefix length comes from the configuration's table; half of the
blocks lie inside the pod range (a pod's address masked to the length), half
outside it (external allowlists: they match no pod and still cost rows); a
block of /`except_max_prefix` or shorter carries `except` entries 4 or 8 bits
longer, inside the block.

Everything is drawn from the configuration's `structure_seed`, so every
`--seed` meets the same shapes and the same counts; `--seed` orders the pods
and the policies (as generators.build_synthetic does, and for its reason).

Plain data, as generators.py: pods are (namespace, name, labels, ip) tuples,
namespaces a dict of label dicts, policies Kubernetes-shaped dicts.  Nothing
of the program is imported.  A kind needs `build` and `policy_set`; port cases
come from generators.case_sets.  The program's copy is
cyclonus_tpu/synthetic.py `cidr_allowlists`; tests/test_synthetic.py holds the
two equal.
"""

import random
from ipaddress import IPv4Address

POD_RANGE_SECOND_OCTET = 64


def pod_addr(i: int, per_node: int) -> int:
    node = i // per_node
    return (
        (10 << 24) | ((POD_RANGE_SECOND_OCTET + (node >> 8)) << 16)
        | ((node & 255) << 8) | (1 + i % per_node)
    )


def cluster(n_pods: int, n_ns: int, gen: dict):
    """(pods, namespaces) in index order."""
    rng = random.Random(f"{gen['structure_seed']}/pods")
    vocab, per_node = gen["vocab"], gen["pods_per_node"]
    pods = []
    for i in range(n_pods):
        labels = {
            "app": f"app{rng.randrange(vocab['app'])}",
            "tier": f"tier{rng.randrange(vocab['tier'])}",
        }
        ip = str(IPv4Address(pod_addr(i, per_node)))
        pods.append((f"ns{i % n_ns}", f"pod-{i}", labels, ip))
    return pods, {f"ns{i}": {"ns": f"ns{i}"} for i in range(n_ns)}


def ip_block(n_pods: int, gen: dict, rng) -> dict:
    lengths, weights = zip(*gen["prefix_lengths"])
    (length,) = rng.choices(lengths, weights)
    if rng.random() < gen["inside_share"]:
        addr = pod_addr(rng.randrange(n_pods), gen["pods_per_node"])
    else:
        lo, hi = gen["outside_first_octet"]
        addr = (rng.randrange(lo, hi + 1) << 24) | rng.getrandbits(24)
    base = addr & ~((1 << (32 - length)) - 1)
    block = {"cidr": f"{IPv4Address(base)}/{length}"}
    if length <= gen["except_max_prefix"]:
        excepts = []
        for _ in range(rng.choice(gen["except_counts"])):
            longer = length + rng.choice(gen["except_extra_bits"])
            if longer > 32:
                longer = length + min(gen["except_extra_bits"])
            inside = base | (rng.getrandbits(longer - length) << (32 - longer))
            entry = f"{IPv4Address(inside)}/{longer}"
            if entry not in excepts:
                excepts.append(entry)
        if excepts:
            block["except"] = excepts
    return block


def rule_peers(n_pods: int, gen: dict, rng) -> list:
    peers = [
        {"ipBlock": ip_block(n_pods, gen, rng)}
        for _ in range(rng.choice(gen["peers_per_rule"]))
    ]
    if rng.random() < gen["tier_peer_share"]:
        tier = f"tier{rng.randrange(gen['vocab']['tier'])}"
        peers.append({"podSelector": {"matchLabels": {"tier": tier}}})
    return peers


def allowlist_policies(n_policies: int, n_pods: int, n_ns: int, gen: dict, rng) -> list:
    policies = []
    for i in range(n_policies):
        ns = f"ns{rng.randrange(n_ns)}"
        target = {"matchLabels": {"app": f"app{rng.randrange(gen['vocab']['app'])}"}}
        ports = [{"protocol": "TCP", "port": 80}]
        if rng.random() < gen["named_udp_share"]:
            ports.append({"protocol": "UDP", "port": "serve-81-udp"})
        roll = rng.random()
        if roll < gen["ingress_only_share"]:
            types = ["Ingress"]
        elif roll < gen["ingress_only_share"] + gen["egress_only_share"]:
            types = ["Egress"]
        else:
            types = ["Ingress", "Egress"]
        spec = {"podSelector": target, "policyTypes": types}
        if "Ingress" in types:
            spec["ingress"] = [{"ports": ports, "from": rule_peers(n_pods, gen, rng)}]
        if "Egress" in types:
            spec["egress"] = [{"ports": ports, "to": rule_peers(n_pods, gen, rng)}]
        policies.append({
            "apiVersion": "networking.k8s.io/v1",
            "kind": "NetworkPolicy",
            "metadata": {"name": f"cidr-{i}", "namespace": ns},
            "spec": spec,
        })
    return policies


def policy_set(sizes: dict, gen: dict, seed: int, j: int = 0) -> list:
    """The configuration's policy set j, in the order `seed` gives it."""
    policies = allowlist_policies(
        sizes["policies"], sizes["pods"], sizes["namespaces"], gen,
        random.Random(f"{gen['structure_seed']}/policy-set/{j}"),
    )
    random.Random(f"{seed}/policy-order/{j}").shuffle(policies)
    return policies


def build(sizes: dict, gen: dict, seed: int):
    """(pods, namespaces, policies) of the configuration's ONE deployment, in
    the order `seed` gives it."""
    pods, namespaces = cluster(sizes["pods"], sizes["namespaces"], gen)
    random.Random(f"{seed}/pod-order").shuffle(pods)
    return pods, namespaces, policy_set(sizes, gen, seed)
