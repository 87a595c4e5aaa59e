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
    CIDR_ALLOWLISTS,
    build_synthetic,
    cidr_allowlists,
    cidr_cluster,
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
