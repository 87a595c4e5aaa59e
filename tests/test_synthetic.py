"""cyclonus_tpu/synthetic.py and the oracle's spot checks.

  * every seeded cluster is byte-identical to the one the parent of PR 30
    built: the digests below were computed at commit 2ee4f57 with
    `bench.build_synthetic`, `bench.cidr_cluster`, `bench.tiers_lattice`
    and `cli.serve_cmd.synthetic_cluster`, before those were moved;
  * the served child's pods equal the benchmark's own copy
    (`benchmarks/generators.py`, read-only here): the wire kind's load
    generator and reference know the pods without asking the child, so
    the two must draw alike;
  * `spot_check` / `spot_check_pairs` pass on a sound engine, sample the
    cells the parent's sampled, and raise naming the cell on one flipped
    verdict (a check that cannot fail guards nothing).
"""

import hashlib
import importlib.util
import ipaddress
import json
import os
import random

import numpy as np
import pytest

from cyclonus_tpu.analysis.oracle import spot_check, spot_check_pairs
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.kube.yaml_io import parse_policy_dict, policy_to_dict
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.synthetic import (
    ANP_TENANCY,
    CIDR_ALLOWLISTS,
    K8S_ENVELOPE,
    anp_tenancy,
    build_synthetic,
    cidr_allowlists,
    cidr_cluster,
    k8s_envelope,
    k8s_envelope_objects,
    synthetic_cluster,
    tiers_lattice,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "serve-81-udp", "UDP")]


def digest(*parts) -> str:
    return hashlib.sha256(json.dumps(parts, sort_keys=True).encode()).hexdigest()


def policy_dicts(policies):
    return [policy_to_dict(p) for p in policies]


@pytest.fixture(scope="module")
def generators():
    """benchmarks/generators.py by path: the benchmark is not a package
    and imports nothing of the program, and nothing here edits it."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_generators", os.path.join(REPO, "benchmarks", "generators.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served_config():
    """The configuration whose `serve` cells use --synthetic-pods."""
    with open(os.path.join(REPO, "benchmarks", "configs", "mesh-100k-10k.json")) as f:
        return json.load(f)


class TestPinnedToTheParent:
    @pytest.mark.parametrize(
        "n_pods, n_policies, seed, n_ns, want",
        [
            (600, 60, 31, None,
             "b8089c7a8efd4eaea6c6743de608338ae4b643f7ae2a2f6919fafcd222a09028"),
            (2000, 100, 77, None,
             "fed2399e22e842be93ae7aee1609e702419b9cb60ee7538dd16dc8303c1c185f"),
            (16, 8, 7, None,
             "5379a546b888e2c27e3de4a3dbfe7cc8ae8d6af43129a6bf4e9c1b98441db8e6"),
            (640, 24, 5, 64,
             "085ca2c7de7a389c7e2be3cd6fe4dd9e381b7963393e4a2d219641814b9f69a7"),
        ],
    )
    def test_build_synthetic(self, n_pods, n_policies, seed, n_ns, want):
        pods, namespaces, policies = build_synthetic(
            n_pods, n_policies, random.Random(seed), n_ns=n_ns
        )
        assert digest(pods, namespaces, policy_dicts(policies)) == want

    @pytest.mark.parametrize(
        "n_pods, n_ns, seed, want",
        [
            (64, 4, 7,
             "bd150193d3058ef275c8bd48f7e8dd5749dc678d85cd3ca3aed922df09f0f53d"),
            (1000, 40, 20260729,
             "7270e83ec812c2435258e80a64c750220d703827e1a301664e5a3f636004716c"),
            # no namespace asked for is one namespace
            (48, 0, 3,
             "b17c350066bc5acf30c79e90be67bcf6045a0f58057dbbb3b0122fc2af2793d6"),
        ],
    )
    def test_synthetic_cluster(self, n_pods, n_ns, seed, want):
        assert digest(*synthetic_cluster(n_pods, n_ns, seed)) == want

    def test_cidr_cluster(self):
        pods, namespaces, netpols, rng = cidr_cluster(256, 64, 64)
        # the rng comes back mid-stream: its next draw is part of the pin
        assert digest(pods, namespaces, policy_dicts(netpols), rng.random()) == (
            "0eb4db15f496057d0e573271b7b743644cd7ad71ec27c94c2a03d82354dabf5f"
        )

    def test_tiers_lattice(self):
        t = tiers_lattice()
        assert digest([a.to_dict() for a in t.anps], t.banp.to_dict()) == (
            "27beb505ddf3e06e2ee283a243af591e47743a0a5cf7df8fd29a1c09af55052a"
        )


class TestTheBenchmarksCopy:
    @pytest.mark.parametrize("n_pods, n_ns, seed", [(600, 4, 7), (1000, 40, 20260729)])
    def test_synthetic_cluster_pod_for_pod(
        self, generators, served_config, n_pods, n_ns, seed
    ):
        sizes = {"pods": n_pods, "namespaces": n_ns}
        theirs = generators.synthetic_cluster(sizes, served_config["generator"], seed)
        pods, namespaces = synthetic_cluster(n_pods, n_ns, seed)
        assert (pods, namespaces) == theirs

    def test_serve_hands_its_service_the_predicted_pods(
        self, generators, served_config, monkeypatch
    ):
        """`serve --synthetic-pods N --synthetic-namespaces M --seed S`,
        up to the service's constructor."""
        import argparse

        import cyclonus_tpu.serve
        from cyclonus_tpu.cli.serve_cmd import setup_serve

        class Handed(Exception):
            pass

        def service(pods, namespaces, policies, **kw):
            raise Handed(pods, namespaces)

        monkeypatch.setattr(cyclonus_tpu.serve, "VerdictService", service)
        parser = argparse.ArgumentParser()
        setup_serve(parser.add_subparsers())
        args = parser.parse_args(
            ["serve", "--synthetic-pods", "64", "--synthetic-namespaces", "4",
             "--seed", "11"]
        )
        with pytest.raises(Handed) as handed:
            args.func(args)
        assert handed.value.args == generators.synthetic_cluster(
            {"pods": 64, "namespaces": 4}, served_config["generator"], 11
        )

    def test_build_synthetic_pods_differ_only_in_order(self, generators, served_config):
        """By design the benchmark shuffles its pods by --seed (PERF.md,
        Findings, PR 25); in index order they are the program's."""
        sizes = {"pods": 600, "policies": 60, "namespaces": 4}
        theirs, their_ns, _ = generators.build_synthetic(
            sizes, served_config["generator"], 3
        )
        pods, namespaces, _ = build_synthetic(600, 60, random.Random(0), n_ns=4)
        assert theirs != pods
        assert sorted(theirs, key=lambda p: int(p[1][len("pod-"):])) == pods
        assert their_ns == namespaces

    def test_policy_draw_is_the_same_draw(self, generators, served_config):
        """By design the benchmark seeds its draw from a string and
        shuffles the set by --seed; from one rng the draw itself is the
        program's, policy for policy."""
        theirs = generators.synthetic_policies(
            60, 4, served_config["generator"], random.Random(31)
        )
        _, _, policies = build_synthetic(600, 60, random.Random(31), n_ns=4)
        assert policy_dicts(parse_policy_dict(d) for d in theirs) == policy_dicts(
            policies
        )


@pytest.fixture(scope="module")
def generators_cidr():
    """benchmarks/generators_cidr.py by path, as `generators` above."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_generators_cidr",
        os.path.join(REPO, "benchmarks", "generators_cidr.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cidr_config():
    with open(os.path.join(REPO, "benchmarks", "configs", "cidr-10k-5k.json")) as f:
        return json.load(f)


class TestCidrAllowlists:
    """PR 32's generator: pinned, and the benchmark's copy
    (`benchmarks/generators_cidr.py`, configuration `cidr-10k-5k`) held to
    the program's."""

    SIZES = {"pods": 660, "policies": 330, "namespaces": 4}

    @pytest.mark.parametrize(
        "n_pods, n_policies, n_ns, want",
        [
            (660, 330, 4,
             "90fb9eff6cb4a635792de4a9954da638d40deccbfd376005c36275f53bc6f634"),
            (64, 48, 3,
             "86b5378f89878333aeb6d1ee4b731d8497ef2f67c9f26448d628986c8efd038c"),
        ],
    )
    def test_pinned(self, n_pods, n_policies, n_ns, want):
        pods, namespaces, policies = cidr_allowlists(n_pods, n_policies, n_ns)
        assert digest(pods, namespaces, policy_dicts(policies)) == want

    def test_the_configuration_holds_the_programs_shapes(self, cidr_config):
        gen = dict(cidr_config["generator"])
        assert gen.pop("module") == "generators_cidr"
        assert gen == CIDR_ALLOWLISTS

    def test_the_benchmarks_copy_draws_the_same_cluster(
        self, generators_cidr, cidr_config
    ):
        gen = cidr_config["generator"]
        pods, namespaces, policies = cidr_allowlists(660, 330, 4)
        assert generators_cidr.cluster(660, 4, gen) == (pods, namespaces)
        theirs = generators_cidr.allowlist_policies(
            330, 660, 4, gen, random.Random(f"{gen['structure_seed']}/policy-set/0")
        )
        assert policy_dicts(parse_policy_dict(d) for d in theirs) == policy_dicts(
            policies
        )

    def test_every_shape_the_issue_names_is_drawn(self, generators_cidr, cidr_config):
        """Several peers a rule, egress-only policies, /32 and /8, an
        except as long as /32, a pod's address inside its node's /24."""
        _, _, policies = generators_cidr.build(
            self.SIZES, cidr_config["generator"], 1
        )
        types = {tuple(p["spec"]["policyTypes"]) for p in policies}
        assert types == {("Ingress",), ("Egress",), ("Ingress", "Egress")}
        blocks = [
            peer["ipBlock"]
            for p in policies
            for rules, key in ((p["spec"].get("ingress", []), "from"),
                               (p["spec"].get("egress", []), "to"))
            for rule in rules for peer in rule[key] if "ipBlock" in peer
        ]
        lengths = {int(b["cidr"].split("/")[1]) for b in blocks}
        assert lengths == {32, 30, 28, 27, 26, 24, 22, 20, 16, 12, 8}
        excepts = [e for b in blocks for e in b.get("except", [])]
        assert any(e.endswith("/32") for e in excepts)
        assert max(len(b.get("except", [])) for b in blocks) == 4
        assert max(
            len(rule[key])
            for p in policies
            for rules, key in ((p["spec"].get("ingress", []), "from"),
                               (p["spec"].get("egress", []), "to"))
            for rule in rules
        ) == 9  # eight blocks and the tier selector beside them
        # every except lies inside its block, and is longer
        for b in blocks:
            net = ipaddress.ip_network(b["cidr"])  # strict: no host bits set
            for e in b.get("except", []):
                inner = ipaddress.ip_network(e)
                assert inner.subnet_of(net) and inner.prefixlen > net.prefixlen

    def test_the_seed_reorders_and_changes_no_count(
        self, generators_cidr, cidr_config
    ):
        gen = cidr_config["generator"]
        a = generators_cidr.build(self.SIZES, gen, 1)
        b = generators_cidr.build(self.SIZES, gen, 3000000019)
        assert a[0] != b[0] and a[2] != b[2] and a[1] == b[1]
        assert sorted(a[0]) == sorted(b[0])
        by_name = lambda ps: sorted(ps, key=lambda p: p["metadata"]["name"])
        assert by_name(a[2]) == by_name(b[2])
        counts = []
        for pods, namespaces, policies in (a, b):
            policy = build_network_policies(
                True, [parse_policy_dict(d) for d in policies]
            )
            got = TpuPolicyEngine(policy, pods, namespaces).evaluate_grid_counts(CASES)
            counts.append({k: int(got[k]) for k in ("ingress", "egress", "combined")})
        assert counts[0] == counts[1]
        assert 0 < counts[0]["combined"] < len(CASES) * 660 * 660


@pytest.fixture(scope="module")
def generators_tiers():
    """benchmarks/generators_tiers.py by path, as `generators` above."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_generators_tiers",
        os.path.join(REPO, "benchmarks", "generators_tiers.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def anp_config():
    with open(os.path.join(REPO, "benchmarks", "configs", "anp-100k-10k.json")) as f:
        return json.load(f)


class TestAnpTenancy:
    """PR 40's generator: pinned, and the benchmark's copy
    (`benchmarks/generators_tiers.py`, configuration `anp-100k-10k`) held to
    the program's."""

    @pytest.mark.parametrize(
        "n_ns, want",
        [
            (400, "49889caf2ca3f5b6bd4d2b35e76c7673c74b2051dbb07e83feabf62198f17387"),
            (4, "e4f7261383347cdbbbaada708b0b8f0b4a971f6c848e0416579271f019d9aaa0"),
        ],
    )
    def test_pinned(self, n_ns, want):
        t = anp_tenancy(n_ns)
        assert digest([a.to_dict() for a in t.anps], t.banp.to_dict()) == want

    def test_the_configuration_holds_the_programs_shapes(self, anp_config, served_config):
        assert anp_config["tiers"] == ANP_TENANCY
        # the cluster and its NetworkPolicies are the twin's, key for key
        assert anp_config["generator"] == served_config["generator"]
        assert anp_config["sizes"] == served_config["sizes"]

    @pytest.mark.parametrize("n_ns", [400, 4])
    def test_the_benchmarks_copy_draws_the_same_tiers(
        self, generators_tiers, anp_config, n_ns
    ):
        from cyclonus_tpu.tiers.model import parse_tier_object

        anps, banp = generators_tiers.build(
            {"namespaces": n_ns}, anp_config["tiers"], 3000000019
        )
        t = anp_tenancy(n_ns)
        # the seed orders the ANPs and nothing else
        assert [a["metadata"]["name"] for a in anps] != [a.name for a in t.anps]
        by_name = sorted((parse_tier_object(d) for d in anps), key=lambda a: a.name)
        assert [a.to_dict() for a in by_name] == [
            a.to_dict() for a in sorted(t.anps, key=lambda a: a.name)
        ]
        assert parse_tier_object(banp).to_dict() == t.banp.to_dict()
        assert generators_tiers.rule_count(anp_config["tiers"]) == sum(
            t.rule_count().values()
        )

    def test_the_shapes_the_issue_names(self):
        t = anp_tenancy(400)
        apps = [a for a in t.anps if a.name.startswith("app-")]
        assert len(t.anps) == 18 and len(apps) == 10
        assert all(len(a.ingress) == len(a.egress) == 100 for a in apps)
        assert sorted(a.priority for a in apps) == [10, 10, 11, 11, 12, 12, 13, 13, 14, 14]
        rules = [r for a in apps for r in a.ingress + a.egress]
        assert {r.action for r in rules} == {"Allow", "Deny", "Pass"}
        assert {len(r.peers) for r in rules} == {1, 2}
        assert {len(r.ports or ()) for r in rules} == {0, 1, 2}
        # past the fused kernel's ceiling twice over, once bucketed
        rows = [sum(len(o.rule.peers) for o in t.ordered_rules(ing, tier))
                for ing in (True, False) for tier in ("anp", "banp")]
        assert sum(rows) > 2 * 1024


@pytest.fixture(scope="module")
def generators_envelope():
    """benchmarks/generators_envelope.py by path, as `generators` above."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_generators_envelope",
        os.path.join(REPO, "benchmarks", "generators_envelope.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def envelope_config():
    with open(os.path.join(REPO, "benchmarks", "configs", "k8s-envelope-150k.json")) as f:
        return json.load(f)


class TestK8sEnvelope:
    """The envelope's generator: pinned, and the benchmark's copy
    (`benchmarks/generators_envelope.py`, configuration `k8s-envelope-150k`)
    held to the program's."""

    @pytest.mark.parametrize(
        "n_ns, n_nodes, want",
        [
            (1, 100, "0f5248158c1a5d637666d44309c4984cc40051ec44caeccef4533ce3cd827e69"),
            (2, 40, "6aa7a1c762d24edd7a920e4a757b15910108edf6ef9db2e69ee345f7ced227ec"),
        ],
    )
    def test_pinned(self, n_ns, n_nodes, want):
        assert digest(*k8s_envelope_objects(n_ns, n_nodes)) == want

    def test_the_configuration_holds_the_programs_shapes(self, envelope_config):
        gen = dict(envelope_config["generator"])
        assert gen.pop("module") == "generators_envelope"
        assert gen == K8S_ENVELOPE
        assert envelope_config["reduced"] == []
        sizes, rehearsal = envelope_config["sizes"], envelope_config["rehearsal"]
        assert (sizes["pods"], sizes["policies"]) == (150_004, 16_650)
        assert (rehearsal["pods"], rehearsal["policies"]) == (3_004, 333)

    @pytest.mark.parametrize("n_ns, n_nodes", [(1, 100), (2, 40)])
    def test_the_benchmarks_copy_draws_the_same_deployment(
        self, generators_envelope, envelope_config, n_ns, n_nodes
    ):
        gen = envelope_config["generator"]
        sizes = {"namespaces": n_ns, "nodes": n_nodes}
        pods, namespaces, policies = k8s_envelope_objects(n_ns, n_nodes)
        assert generators_envelope.cluster(sizes, gen) == (pods, namespaces)
        assert generators_envelope.policies(sizes, gen) == policies
        # and the program's parser reads them back to the same policies
        _, _, parsed = k8s_envelope(n_ns, n_nodes)
        assert policy_dicts(parsed) == policy_dicts(
            parse_policy_dict(d) for d in policies
        )

    def test_every_shape_the_configuration_names_is_drawn(
        self, generators_envelope, envelope_config
    ):
        """All three Deployment sizes, one label set a Deployment, every
        selector operator, every port form, the ipBlock with its except, a
        cross-namespace peer, and the counts the configuration states."""
        pods, namespaces, policies = generators_envelope.build(
            envelope_config["rehearsal"], envelope_config["generator"], 1
        )
        replicas = {}
        for ns, _, labels, _ in pods:
            replicas.setdefault(json.dumps(labels, sort_keys=True), []).append(ns)
        assert sorted({len(v) for v in replicas.values()}) == [2, 5, 30, 250]
        assert len(replicas) == 330  # 328 Deployments + CoreDNS + Prometheus
        assert {"kube-system", "monitoring", "load-0"} == set(namespaces)
        ops, ports, peers = set(), set(), []
        for p in policies:
            for rules, key in ((p["spec"].get("ingress", []), "from"),
                               (p["spec"].get("egress", []), "to")):
                for rule in rules:
                    ports |= {json.dumps(t, sort_keys=True) for t in rule.get("ports", [])}
                    for peer in rule[key]:
                        peers.append(peer)
                        for e in (peer.get("podSelector") or {}).get("matchExpressions", []):
                            ops.add(e["operator"])
        assert ops == {"In", "NotIn", "Exists", "DoesNotExist"}
        assert {json.dumps(t, sort_keys=True) for t, _ in K8S_ENVELOPE["ports"]} <= ports
        assert {"ipBlock": {"cidr": "0.0.0.0/0", "except": ["10.0.0.0/8"]}} in peers
        assert any("team" in (peer.get("namespaceSelector") or {}).get("matchLabels", {})
                   for peer in peers)
        # every load pod lies on a node's /24 at .2 - .31, inside the except
        hosts = {int(ip.rsplit(".", 1)[1]) for ns, _, _, ip in pods if ns == "load-0"}
        assert hosts == set(range(2, 32))
        assert all(ipaddress.ip_address(ip) in ipaddress.ip_network("10.0.0.0/8")
                   for *_, ip in pods)

    def test_the_seed_reorders_and_changes_no_count(
        self, generators_envelope, envelope_config
    ):
        gen, sizes = envelope_config["generator"], envelope_config["rehearsal"]
        a = generators_envelope.build(sizes, gen, 1)
        b = generators_envelope.build(sizes, gen, 3000000019)
        assert a[0] != b[0] and a[2] != b[2] and a[1] == b[1]
        assert sorted(a[0]) == sorted(b[0])
        by_name = lambda ps: sorted(ps, key=lambda p: p["metadata"]["name"])
        assert by_name(a[2]) == by_name(b[2])


class Flipped:
    """A grid or an engine whose k-th sampled answer has its combined
    verdict flipped."""

    def __init__(self, inner, k: int):
        self.inner, self.k = inner, k

    def gather(self, triples):
        got = np.array(self.inner.gather(triples))
        got[self.k, 2] = not got[self.k, 2]
        return got

    def evaluate_pairs(self, cases, pairs):
        got = np.array(self.inner.evaluate_pairs(cases, pairs))
        got[self.k, 0, 2] = not got[self.k, 0, 2]
        return got


class TestSpotChecks:
    @pytest.fixture(scope="class")
    def sound(self):
        pods, namespaces, policies = build_synthetic(48, 12, random.Random(9))
        policy = build_network_policies(True, policies)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        return policy, pods, namespaces, engine, engine.evaluate_grid(CASES)

    def test_spot_check_passes_on_a_sound_grid(self, sound):
        policy, pods, namespaces, _, grid = sound
        spot_check(policy, pods, namespaces, CASES, grid, 32, random.Random(1))

    def test_spot_check_names_the_flipped_cell(self, sound):
        policy, pods, namespaces, _, grid = sound
        rng = random.Random(1)
        # the parent's draw: (case, src, dst) for each sample, in this order
        cells = [
            (rng.randrange(len(CASES)), rng.randrange(48), rng.randrange(48))
            for _ in range(32)
        ]
        qi, si, di = cells[5]
        with pytest.raises(AssertionError, match=f"s={si} d={di}") as err:
            spot_check(
                policy, pods, namespaces, CASES, Flipped(grid, 5), 32, random.Random(1)
            )
        assert str(CASES[qi]) in str(err.value)

    def test_spot_check_pairs_passes_on_a_sound_engine(self, sound):
        policy, pods, namespaces, engine, _ = sound
        spot_check_pairs(engine, policy, pods, namespaces, CASES, 16, random.Random(2))

    def test_spot_check_pairs_names_the_flipped_pair(self, sound):
        policy, pods, namespaces, engine, _ = sound
        rng = random.Random(2)
        # the parent's draw: (src, dst) for each sample, no case drawn
        pairs = [(rng.randrange(48), rng.randrange(48)) for _ in range(16)]
        si, di = pairs[3]
        with pytest.raises(AssertionError, match=f"s={si} d={di}"):
            spot_check_pairs(
                Flipped(engine, 3), policy, pods, namespaces, CASES, 16,
                random.Random(2),
            )

    def test_spot_checks_leave_the_rng_where_the_parent_left_it(self, sound):
        """Callers go on drawing from the rng they pass (chip_smoke.py's
        CIDR phase passes cidr_cluster's): 3 draws a cell, 2 a pair."""
        policy, pods, namespaces, engine, grid = sound
        a, b = random.Random(4), random.Random(4)
        spot_check(policy, pods, namespaces, CASES, grid, 8, a)
        spot_check_pairs(engine, policy, pods, namespaces, CASES, 8, a)
        for _ in range(8):
            b.randrange(len(CASES)), b.randrange(48), b.randrange(48)
        for _ in range(8):
            b.randrange(48), b.randrange(48)
        assert a.random() == b.random()
