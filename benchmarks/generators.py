"""Seeded inputs of the benchmark: clusters, policy sets, port cases.

Copies of the program's two sound generators (the originals fix
`random.Random(20260729)` / seed 7):

  build_synthetic    <- bench.build_synthetic (cyclic labels, cyclic namespaces);
                        the configuration fixes the draw, the seed the order
  synthetic_cluster  <- cyclonus_tpu.cli.serve_cmd.synthetic_cluster (the pods
                        `serve --synthetic-pods N --seed S` makes for itself;
                        this copy is how the load generator and the reference
                        know them without asking the child)

Everything here is plain data: pods are (namespace, name, labels, ip) tuples,
namespaces a dict of label dicts, policies Kubernetes-shaped dicts (what a
user's YAML holds).  Nothing of the program is imported; the kinds hand the
dicts to the program's own parser, and reference.py reads the same dicts.
"""

import random


def port_case(port: int, protocol: str) -> tuple:
    """(port, port name, protocol) as upstream's probe names a served port."""
    return (port, f"serve-{port}-{protocol.lower()}", protocol)


def case_sets(spec) -> list:
    """A traffic file's `case_sets`: lists of [port, protocol] pairs."""
    return [[port_case(p, proto) for p, proto in one] for one in spec]


def pod_ip(i: int) -> str:
    return f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"


def pod_labels(i: int, vocab: dict) -> dict:
    return {
        "pod": f"p{i % vocab['pod']}",
        "app": f"app{i % vocab['app']}",
        "tier": f"tier{i % vocab['tier']}",
    }


def namespaces_of(n_ns: int, vocab: dict) -> dict:
    return {
        f"ns{i}": {"ns": f"ns{i}", "team": f"team{i % vocab['team']}"}
        for i in range(n_ns)
    }


def synthetic_policies(n_policies: int, n_ns: int, gen: dict, rng) -> list:
    """The policy half of bench.build_synthetic, draw for draw."""
    vocab = gen["vocab"]
    policies = []
    for i in range(n_policies):
        ns = f"ns{rng.randrange(n_ns)}"
        target = {"matchLabels": {"app": f"app{rng.randrange(vocab['app'])}"}}
        if rng.random() < gen["ipblock_share"]:
            peer = {
                "ipBlock": {
                    "cidr": f"10.{rng.randrange(4)}.0.0/16",
                    "except": [f"10.{rng.randrange(4)}.{rng.randrange(8)}.0/24"],
                }
            }
        else:
            peer = {
                "podSelector": {
                    "matchLabels": {"tier": f"tier{rng.randrange(vocab['tier'])}"}
                }
            }
            if rng.random() < 0.5:
                team = f"team{rng.randrange(vocab['team'])}"
                peer["namespaceSelector"] = {"matchLabels": {"team": team}}
        ports = [{"protocol": "TCP", "port": 80}]
        if rng.random() < gen["named_udp_share"]:
            ports.append({"protocol": "UDP", "port": "serve-81-udp"})
        ingress_only = rng.random() < gen["ingress_only_share"]
        spec = {
            "podSelector": target,
            "policyTypes": ["Ingress"] if ingress_only else ["Ingress", "Egress"],
            "ingress": [{"ports": ports, "from": [peer]}],
        }
        if not ingress_only:
            spec["egress"] = [{"ports": ports, "to": [peer]}]
        policies.append({
            "apiVersion": "networking.k8s.io/v1",
            "kind": "NetworkPolicy",
            "metadata": {"name": f"bench-{i}", "namespace": ns},
            "spec": spec,
        })
    return policies


def policy_set(sizes: dict, gen: dict, seed: int, j: int = 0) -> list:
    """The configuration's policy set j, in the order `seed` gives it.  The
    draw comes from the configuration's `structure_seed`, not from `seed`:
    see build_synthetic."""
    policies = synthetic_policies(
        sizes["policies"], sizes["namespaces"], gen,
        random.Random(f"{gen['structure_seed']}/policy-set/{j}"),
    )
    random.Random(f"{seed}/policy-order/{j}").shuffle(policies)
    return policies


def build_synthetic(sizes: dict, gen: dict, seed: int):
    """(pods, namespaces, policies) of the configuration's ONE deployment, in
    the order `seed` gives it.

    Pod i lives in namespace i % n_ns and the policies are policy set 0 of
    the configuration's `structure_seed`.  A draw per seed moved the counts
    cell between 2,592 and 4,384 pod classes, so between padded kernel
    shapes, and its rate by 28 % (PERF.md, Findings): so every seed meets the
    same sizes, classes and shapes, and `seed` shuffles the order of the pods
    and of the policies, which moves every row of every table and changes no
    count."""
    n_ns, vocab = sizes["namespaces"], gen["vocab"]
    pods = [
        (f"ns{i % n_ns}", f"pod-{i}", pod_labels(i, vocab), pod_ip(i))
        for i in range(sizes["pods"])
    ]
    random.Random(f"{seed}/pod-order").shuffle(pods)
    return pods, namespaces_of(n_ns, vocab), policy_set(sizes, gen, seed)


def synthetic_cluster(sizes: dict, gen: dict, seed: int):
    """(pods, namespaces) as `serve --synthetic-pods` draws them: pod i lives
    in a namespace drawn from random.Random(seed)."""
    n_ns, vocab = max(1, sizes["namespaces"]), gen["vocab"]
    rng = random.Random(seed)
    pods = [
        (f"ns{rng.randrange(n_ns)}", f"pod-{i}", pod_labels(i, vocab), pod_ip(i))
        for i in range(sizes["pods"])
    ]
    return pods, namespaces_of(n_ns, vocab)
