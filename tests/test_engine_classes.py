"""Equivalence-class grid compression (docs/DESIGN.md "Grid compression").

Four layers of proof, mirroring the tentpole's safety story:

  * PROPERTY: every pod's selector-visible signature (its class) implies
    an identical scalar-oracle verdict row — seeded random clusters with
    replica pods, plus the adversarial designed cases (empty selectors,
    overlapping CIDR excepts, pods differing only in a label no policy
    selects) where co-membership must also HOLD (the <=> direction).
  * PARTITIONS: the tuple-space rule-axis compression (duplicate
    targets/rules merge) is exact and actually fires.
  * AUDIT: analysis.audit_class_reduction passes on real classes and
    FIRES on a deliberately corrupted class map.
  * BUDGET: the gather/index tensors count toward CYCLONUS_SLAB_MAX_BYTES
    (slab plan + compressed-counts eligibility), and the bypass falls
    back to the dense path with identical counts.

The compressed-vs-dense-vs-oracle truth-table parity lives in
tests/test_engine_parity.py (TestCompressedParity).
"""

import json
import random

import numpy as np
import pytest

from cyclonus_tpu.analysis import audit_class_reduction
from cyclonus_tpu.analysis.oracle import oracle_verdicts, traffic_for_cell
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.engine.encoding import compress_rule_axes, compute_pod_classes
from cyclonus_tpu.kube.netpol import (
    IPBlock,
    LabelSelector,
    NetworkPolicyIngressRule,
    NetworkPolicyPeer,
)
from cyclonus_tpu.matcher import build_network_policies

from test_engine_parity import mkpol, random_policy

CASES = [
    PortCase(80, "serve-80-tcp", "TCP"),
    PortCase(81, "serve-81-udp", "UDP"),
]


def oracle_row(policy, pods, namespaces, cases, a):
    """Pod a's full oracle verdict row: (a -> p) and (p -> a) for every
    peer p and case — the object class co-membership must preserve."""
    row = []
    for case in cases:
        for p in range(len(pods)):
            row.append(
                oracle_verdicts(
                    policy, traffic_for_cell(pods, namespaces, case, a, p)
                )
            )
            row.append(
                oracle_verdicts(
                    policy, traffic_for_cell(pods, namespaces, case, p, a)
                )
            )
    return tuple(row)


def compressed_engine(policy, pods, namespaces, monkeypatch):
    monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", "1")
    engine = TpuPolicyEngine(policy, pods, namespaces)
    assert engine.pod_classes() is not None
    return engine


def assert_classes_sound(engine, policy, pods, namespaces, cases):
    """Soundness: class co-membership => identical oracle verdict rows."""
    pc = engine.pod_classes()
    rows = {
        a: oracle_row(policy, pods, namespaces, cases, a)
        for a in range(len(pods))
    }
    by_class = {}
    for a in range(len(pods)):
        by_class.setdefault(int(pc.class_of_pod[a]), []).append(a)
    for c, members in sorted(by_class.items()):
        head = rows[members[0]]
        for m in members[1:]:
            assert rows[m] == head, (
                f"class {c}: pods {members[0]} and {m} share a class but "
                f"their oracle verdict rows differ"
            )
    return pc, rows


class TestSignatureProperty:
    """Satellite: hash every pod's selector-visible signature, assert
    class co-membership <=> identical scalar-oracle verdict rows."""

    @pytest.mark.parametrize("seed", range(8))
    def test_seeded_random_clusters(self, seed, monkeypatch):
        rng = random.Random(seed)
        nss = ["x", "y", "z"]
        keys = ["pod", "app", "tier"]
        values = ["a", "b", "c", "web", "db"]
        namespaces = {ns: {"ns": ns} for ns in nss}
        # replica templates: several pods share each (ns, labels) shape,
        # the regime the compression targets
        pods = []
        ip = 1
        for i in range(rng.randrange(4, 7)):
            ns = rng.choice(nss)
            labels = {
                rng.choice(keys): rng.choice(values)
                for _ in range(rng.randrange(0, 3))
            }
            for r in range(rng.randrange(1, 4)):
                pods.append(
                    (
                        ns,
                        f"p{i}-{r}",
                        dict(labels),
                        f"192.168.{rng.randrange(4)}.{ip}",
                    )
                )
                ip += 1
        policies = [
            random_policy(rng, i, nss, keys, values)
            for i in range(rng.randrange(1, 5))
        ]
        policy = build_network_policies(True, policies)
        engine = compressed_engine(policy, pods, namespaces, monkeypatch)
        assert_classes_sound(engine, policy, pods, namespaces, CASES)

    def test_unselected_label_merges_pods(self, monkeypatch):
        """Pods differing ONLY in a label no policy selects must land in
        one class (the <= direction, by construction) and share rows."""
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", "a", {"app": "web", "junk": "1"}, "10.0.0.1"),
            ("x", "b", {"app": "web", "junk": "2"}, "10.0.0.2"),
            ("x", "c", {"app": "db"}, "10.0.0.3"),
        ]
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "sel-app",
                    "x",
                    LabelSelector.make(match_labels={"app": "web"}),
                    ["Ingress"],
                    ingress=[
                        NetworkPolicyIngressRule(
                            from_=[
                                NetworkPolicyPeer(
                                    pod_selector=LabelSelector.make(
                                        match_labels={"app": "db"}
                                    )
                                )
                            ]
                        )
                    ],
                )
            ],
        )
        engine = compressed_engine(policy, pods, namespaces, monkeypatch)
        pc, rows = assert_classes_sound(engine, policy, pods, namespaces, CASES)
        assert pc.class_of_pod[0] == pc.class_of_pod[1]
        assert pc.class_of_pod[0] != pc.class_of_pod[2]
        # the <=> on this designed case: identical rows exactly where
        # classes agree
        assert rows[0] == rows[1]
        assert rows[0] != rows[2]

    def test_empty_selector_merges_whole_namespace(self, monkeypatch):
        """An empty pod selector observes nothing about labels, so pods
        of one namespace with arbitrary distinct labels share a class."""
        namespaces = {"x": {"ns": "x"}, "y": {"ns": "y"}}
        pods = [
            ("x", "a", {"r": "1"}, "10.0.0.1"),
            ("x", "b", {"s": "2"}, "10.0.0.2"),
            ("y", "c", {"r": "1"}, "10.0.0.3"),
        ]
        policy = build_network_policies(
            True,
            [mkpol("deny-x", "x", LabelSelector.make(), ["Ingress", "Egress"])],
        )
        engine = compressed_engine(policy, pods, namespaces, monkeypatch)
        pc, rows = assert_classes_sound(engine, policy, pods, namespaces, CASES)
        assert pc.class_of_pod[0] == pc.class_of_pod[1]
        assert pc.class_of_pod[0] != pc.class_of_pod[2]
        assert rows[0] == rows[1]

    def test_overlapping_cidrs_split_pods(self, monkeypatch):
        """Overlapping CIDR excepts are part of the signature: pods with
        identical labels but different membership in an except block
        must SPLIT; pods on the same side must merge."""
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", "in-a", {"app": "w"}, "192.168.1.10"),
            ("x", "in-b", {"app": "w"}, "192.168.1.11"),  # same /28 side
            ("x", "exc", {"app": "w"}, "192.168.1.129"),  # inside except
            ("x", "out", {"app": "w"}, "192.168.2.10"),  # outside base
        ]
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "ipb",
                    "x",
                    LabelSelector.make(),
                    ["Ingress"],
                    ingress=[
                        NetworkPolicyIngressRule(
                            from_=[
                                NetworkPolicyPeer(
                                    ip_block=IPBlock.make(
                                        "192.168.1.0/24",
                                        ["192.168.1.128/25"],
                                    )
                                ),
                                NetworkPolicyPeer(
                                    ip_block=IPBlock.make("192.168.1.0/25")
                                ),
                            ]
                        )
                    ],
                )
            ],
        )
        engine = compressed_engine(policy, pods, namespaces, monkeypatch)
        pc, rows = assert_classes_sound(engine, policy, pods, namespaces, CASES)
        assert pc.class_of_pod[0] == pc.class_of_pod[1]
        assert pc.class_of_pod[0] != pc.class_of_pod[2]
        assert rows[0] == rows[1]
        assert rows[0] != rows[2]
        # "inside the except" and "outside the base" are OBSERVABLY
        # equivalent (neither matches any block): the signature must
        # merge them, not split on raw IP bytes
        assert pc.class_of_pod[2] == pc.class_of_pod[3]
        assert rows[2] == rows[3]


class TestRulePartitions:
    """Tuple-space partition compression of the rule axes is exact and
    actually collapses duplicated rules.  The matcher's simplify pass
    (build_network_policies(True, ...)) dedups most of this upstream —
    the engine-side compression is the defense for UNSIMPLIFIED policy
    sets (simplify=False is a supported reference mode) and for
    duplicates the simplifier's peer-kind buckets don't cover."""

    def _dup_policy_engine(self, monkeypatch, mode, k=4):
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", f"p{i}", {"app": "web" if i % 2 else "db"}, f"10.0.0.{i + 1}")
            for i in range(6)
        ]
        # k byte-identical policies: same target selector, same rule.
        # Built UNSIMPLIFIED so the duplicate peers reach the encoder.
        pol = lambda i: mkpol(  # noqa: E731
            f"dup-{i}",
            "x",
            LabelSelector.make(match_labels={"app": "web"}),
            ["Ingress"],
            ingress=[
                NetworkPolicyIngressRule(
                    from_=[
                        NetworkPolicyPeer(
                            pod_selector=LabelSelector.make(
                                match_labels={"app": "db"}
                            )
                        )
                    ]
                )
            ],
        )
        policy = build_network_policies(False, [pol(i) for i in range(k)])
        monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", mode)
        return TpuPolicyEngine(policy, pods, namespaces), policy, pods, namespaces

    def test_duplicate_rules_collapse(self, monkeypatch):
        engine, policy, pods, namespaces = self._dup_policy_engine(
            monkeypatch, "1", k=4
        )
        st = engine.class_compression_stats()
        p = st["partitions"]["ingress"]
        # the builder combines same-(ns, selector) targets; the k
        # duplicated PEER rows survive unsimplified and must collapse
        assert p["peers_before"] >= 4 and p["peers_after"] == 1
        assert p["partitions"] == 1
        compressed = engine.evaluate_grid_counts(CASES, backend="xla")
        monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", "0")
        dense = TpuPolicyEngine(policy, pods, namespaces)
        assert compressed == dense.evaluate_grid_counts(CASES, backend="xla")
        g_c = engine.evaluate_grid(CASES)
        g_d = dense.evaluate_grid(CASES)
        for name in ("ingress", "egress", "combined"):
            assert np.array_equal(
                np.asarray(getattr(g_c, name)), np.asarray(getattr(g_d, name))
            )

    def test_duplicate_targets_merge_unit(self):
        """Targets with identical (ns, selector) merge.  Every Policy
        constructor combines same-primary-key targets upstream, so this
        is the below-the-matcher safety net — exercised on a tensor
        dict with the duplication applied directly."""
        namespaces = {"x": {"ns": "x"}}
        pods = [("x", "p", {"app": "web"}, "10.0.0.1")]
        pol = mkpol(
            "p",
            "x",
            LabelSelector.make(match_labels={"app": "web"}),
            ["Ingress"],
            ingress=[NetworkPolicyIngressRule()],
        )
        import os

        os.environ["CYCLONUS_CLASS_COMPRESS"] = "0"
        try:
            engine = TpuPolicyEngine(
                build_network_policies(True, [pol]), pods, namespaces
            )
        finally:
            os.environ.pop("CYCLONUS_CLASS_COMPRESS", None)
        raw = engine._build_tensors()["ingress"]
        assert raw["target_ns"].shape[0] == 1
        dup = dict(raw)
        for k in ("target_ns", "target_sel"):
            dup[k] = np.concatenate([raw[k], raw[k]])
        p = raw["peer_target"].shape[0]
        dup["peer_target"] = np.concatenate(
            [raw["peer_target"], raw["peer_target"] + 1]
        )
        for k in (
            "peer_kind", "peer_ns_kind", "peer_ns_id", "peer_ns_sel",
            "peer_pod_kind", "peer_pod_sel", "ip_base", "ip_mask",
            "ip_is_v4", "ex_base", "ex_mask", "ex_valid",
        ):
            dup[k] = np.concatenate([raw[k], raw[k]])
        dup["port_spec"] = {
            k: np.concatenate([v, v]) for k, v in raw["port_spec"].items()
        }
        nd, stats = compress_rule_axes(dup)
        assert stats["targets_before"] == 2 and stats["targets_after"] == 1
        assert stats["peers_before"] == 2 * p and stats["peers_after"] == p
        assert nd["peer_target"].tolist() == [0] * p

    def test_compress_rule_axes_unit(self):
        """Triplicated identical rules within one policy, built
        unsimplified, collapse to one flat peer row."""
        namespaces = {"x": {"ns": "x"}}
        pods = [("x", "p", {"a": "b"}, "10.0.0.1")]
        pol = mkpol(
            "p",
            "x",
            LabelSelector.make(),
            ["Ingress"],
            ingress=[
                NetworkPolicyIngressRule(
                    from_=[
                        NetworkPolicyPeer(
                            pod_selector=LabelSelector.make(
                                match_labels={"a": "b"}
                            )
                        )
                    ]
                    * 3  # triplicated identical rule within one target
                )
            ],
        )
        import os

        os.environ["CYCLONUS_CLASS_COMPRESS"] = "0"
        try:
            engine = TpuPolicyEngine(
                build_network_policies(False, [pol]), pods, namespaces
            )
        finally:
            os.environ.pop("CYCLONUS_CLASS_COMPRESS", None)
        raw = engine._build_tensors()["ingress"]
        nd, stats = compress_rule_axes(raw)
        assert stats["peers_before"] == 3 and stats["peers_after"] == 1
        assert nd["peer_target"].shape[0] == 1
        assert nd["port_spec"]["spec_all"].shape[0] == 1


class TestClassAudit:
    def _cluster(self, monkeypatch):
        namespaces = {"x": {"ns": "x"}, "y": {"ns": "y"}}
        pods = []
        for i in range(12):
            ns = "x" if i % 3 else "y"
            app = "web" if i % 2 else "db"
            pods.append((ns, f"p{i}", {"app": app}, f"10.0.0.{i + 1}"))
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "w",
                    "x",
                    LabelSelector.make(match_labels={"app": "web"}),
                    ["Ingress"],
                    ingress=[
                        NetworkPolicyIngressRule(
                            from_=[
                                NetworkPolicyPeer(
                                    pod_selector=LabelSelector.make(
                                        match_labels={"app": "web"}
                                    )
                                )
                            ]
                        )
                    ],
                )
            ],
        )
        engine = compressed_engine(policy, pods, namespaces, monkeypatch)
        return engine, policy, pods, namespaces

    def test_audit_passes_on_real_classes(self, monkeypatch):
        engine, policy, pods, namespaces = self._cluster(monkeypatch)
        report = audit_class_reduction(
            policy, pods, namespaces, CASES, engine.pod_classes(),
            max_classes=16, peers_per_class=16,
        )
        assert report["ok"], report["violations"][:3]
        assert report["checked_classes"] >= 1
        assert report["checked_cells"] > 0

    def test_audit_fires_on_corrupted_classes(self, monkeypatch):
        """Merging two genuinely-different pods into one class must
        surface as violations — the audit's reason to exist."""
        from cyclonus_tpu.engine.encoding import PodClasses

        engine, policy, pods, namespaces = self._cluster(monkeypatch)
        pc = engine.pod_classes()
        rows = {
            a: oracle_row(policy, pods, namespaces, CASES, a)
            for a in range(len(pods))
        }
        # find two pods with different oracle rows and force-merge them
        a, b = next(
            (i, j)
            for i in range(len(pods))
            for j in range(i + 1, len(pods))
            if rows[i] != rows[j]
        )
        corrupt_of = np.asarray(pc.class_of_pod).copy()
        corrupt_of[b] = corrupt_of[a]
        sizes = np.bincount(corrupt_of, minlength=pc.n_classes).astype(np.int32)
        corrupted = PodClasses(
            n_pods=pc.n_pods,
            n_classes=pc.n_classes,
            class_of_pod=corrupt_of,
            class_rep=pc.class_rep,
            class_size=sizes,
        )
        report = audit_class_reduction(
            policy, pods, namespaces, CASES, corrupted,
            max_classes=32, peers_per_class=len(pods),
        )
        assert not report["ok"]
        assert report["violations"]


class TestBudgetAccounting:
    """Satellite: the gather/index tensors count toward
    CYCLONUS_SLAB_MAX_BYTES — in the slab plan and in the compressed
    counts eligibility — with a dense fallback that stays correct."""

    def _engine(self, monkeypatch, n=64):
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", f"p{i}", {"app": f"a{i % 4}"}, f"10.0.0.{i + 1}")
            for i in range(n)
        ]
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "w",
                    "x",
                    LabelSelector.make(match_labels={"app": "a0"}),
                    ["Ingress"],
                    ingress=[NetworkPolicyIngressRule()],
                )
            ],
        )
        return compressed_engine(policy, pods, namespaces, monkeypatch)

    def test_aux_bytes_counted_and_bypass_stays_correct(self, monkeypatch):
        engine = self._engine(monkeypatch)
        assert engine._class_aux_bytes() > 0
        assert engine._class_counts_eligible(len(CASES))
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        # a budget smaller than the aux tensors alone: the compressed
        # route must BYPASS (not over-commit), and the dense fallback
        # must produce identical counts
        monkeypatch.setenv("CYCLONUS_SLAB_MAX_BYTES", "1")
        assert not engine._class_counts_eligible(len(CASES))
        assert engine.evaluate_grid_counts(CASES, backend="xla") == want

    def test_slab_plan_charges_class_aux(self, monkeypatch):
        """A budget that admits the slab exactly must REJECT once the
        class aux bytes share it, and re-admit when the budget grows by
        exactly that amount."""
        from cyclonus_tpu.engine.pallas_kernel import SLAB_BD, SLAB_BS, slab_w_aug

        # the slab plan is a legacy-dtype-plan feature: the packed plan
        # (CYCLONUS_PACK default) retires it, so pin the kill switch
        monkeypatch.setenv("CYCLONUS_PACK", "0")
        monkeypatch.setenv("CYCLONUS_PALLAS_SLAB", "1")
        monkeypatch.setenv("CYCLONUS_PALLAS_DTYPE", "int8")
        n = 4 * SLAB_BS
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", f"p{i}", {"pod": "a"}, f"10.0.{i // 250}.{i % 250}")
            for i in range(n)
        ]
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "allow", "x", LabelSelector.make(), ["Ingress"],
                    ingress=[NetworkPolicyIngressRule()],
                )
            ],
        )
        engine = compressed_engine(policy, pods, namespaces, monkeypatch)
        aux = engine._class_aux_bytes()
        assert aux > 0
        n_b = int(engine._tensors["pod_ns_id"].shape[0])
        n_tiles = -(-n_b // SLAB_BS) + -(-n_b // SLAB_BD)
        slab_bytes = 2 * n_tiles * slab_w_aug("int8") * n_b
        ns = engine._tensors["pod_ns_id"]
        key = np.where(ns < 0, np.iinfo(np.int32).max, ns)
        perm = np.argsort(key, kind="stable").astype(np.int32)
        monkeypatch.setenv("CYCLONUS_SLAB_MAX_BYTES", str(slab_bytes))
        assert engine._slab_plan(perm) is None
        monkeypatch.setenv("CYCLONUS_SLAB_MAX_BYTES", str(slab_bytes + aux))
        assert engine._slab_plan(perm) is not None


class TestModeSelection:
    def _tiny(self, monkeypatch, mode=None, min_pods=None):
        if mode is not None:
            monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", mode)
        else:
            monkeypatch.delenv("CYCLONUS_CLASS_COMPRESS", raising=False)
        if min_pods is not None:
            monkeypatch.setenv("CYCLONUS_CLASS_MIN_PODS", str(min_pods))
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", f"p{i}", {"app": "web"}, f"10.0.0.{i + 1}") for i in range(8)
        ]
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "w", "x", LabelSelector.make(), ["Ingress"],
                    ingress=[NetworkPolicyIngressRule()],
                )
            ],
        )
        return TpuPolicyEngine(policy, pods, namespaces)

    def test_auto_skips_small_clusters(self, monkeypatch):
        engine = self._tiny(monkeypatch)
        assert engine.pod_classes() is None
        assert not engine.class_compression_stats()["active"]
        # ...but the partition stats still record (rule compression is on)
        assert engine.class_compression_stats()["partitions"] is not None

    def test_auto_engages_above_floor(self, monkeypatch):
        engine = self._tiny(monkeypatch, min_pods=4)
        pc = engine.pod_classes()
        assert pc is not None and pc.n_classes == 1  # identical pods
        assert engine.class_compression_stats()["ratio"] == 8.0

    def test_off_disables_everything(self, monkeypatch):
        engine = self._tiny(monkeypatch, mode="0")
        assert engine.pod_classes() is None
        assert engine.class_compression_stats()["partitions"] is None

    def test_gauges_published(self, monkeypatch):
        from cyclonus_tpu.telemetry import instruments as ti

        engine = self._tiny(monkeypatch, mode="1")
        assert engine.pod_classes() is not None
        engine.evaluate_grid_counts(CASES, backend="xla")
        snap = ti.REGISTRY.snapshot()
        assert snap["cyclonus_tpu_class_count"]["samples"][0]["value"] == 1
        assert snap["cyclonus_tpu_class_compression_ratio"]["samples"][0][
            "value"
        ] == 8.0
        assert snap["cyclonus_tpu_class_aux_bytes"]["samples"][0]["value"] > 0
        evals = {
            tuple(sorted(s["labels"].items())): s["value"]
            for s in snap["cyclonus_tpu_class_evals_total"]["samples"]
        }
        assert evals.get((("path", "counts"),), 0) >= 1


class TestCompressedEvaluatorCoverage:
    """The sharded grid/counts compressed routes agree with dense (the
    xla parity lives in TestCompressedParity; this pins the mesh legs +
    the pipelined twin)."""

    def _cluster(self):
        namespaces = {ns: {"ns": ns} for ns in ("x", "y")}
        pods = []
        for i in range(20):
            ns = "x" if i % 2 else "y"
            pods.append(
                (ns, f"p{i}", {"app": f"a{i % 3}"}, f"192.168.0.{i + 1}")
            )
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "w",
                    "x",
                    LabelSelector.make(match_labels={"app": "a0"}),
                    ["Ingress", "Egress"],
                    ingress=[
                        NetworkPolicyIngressRule(
                            from_=[
                                NetworkPolicyPeer(
                                    ip_block=IPBlock.make(
                                        "192.168.0.0/28", []
                                    )
                                )
                            ]
                        )
                    ],
                )
            ],
        )
        return policy, pods, namespaces

    def test_sharded_routes_match_dense(self, monkeypatch):
        policy, pods, namespaces = self._cluster()
        monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", "0")
        dense = TpuPolicyEngine(policy, pods, namespaces)
        want_counts = dense.evaluate_grid_counts(CASES, backend="xla")
        want_grid = np.asarray(dense.evaluate_grid(CASES).combined)
        engine = compressed_engine(policy, pods, namespaces, monkeypatch)
        assert engine.evaluate_grid_counts_sharded(CASES, block=4) == want_counts
        got = engine.evaluate_grid_sharded(CASES)
        assert np.array_equal(np.asarray(got.combined), want_grid)
        piped = engine.counts_pipelined_eval_s(CASES, reps=2)
        assert piped is not None
        assert {k: piped[1][k] for k in want_counts} == want_counts
        stats = engine.class_compression_stats()
        assert stats["active"] and stats["gather_s"] is not None
