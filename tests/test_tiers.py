"""Precedence-tier subsystem tests (cyclonus_tpu/tiers + the lattice
plumbing through matcher/engine/serve/analysis):

  * model round-trips and validation (dict/YAML, action vocabularies,
    priority bounds, port-range sanity);
  * lattice unit tests on the scalar oracle (matcher/tiered.py): verdict
    precedence, Pass-fallthrough, BANP-never-after-NP, equal-priority
    name tiebreak, external-peer passthrough;
  * property tests: priority-order invariance under ANP list shuffle,
    all-Pass transparency, and the zero-tier byte-identity acceptance
    criterion (empty TierSet == tiers=None == the networkingv1-only
    tensor set);
  * the differential gate on fixtures + >= 8 fuzz seeds (dense AND
    class-compressed engine tables bit-identical to the tiered oracle);
  * endPort ranges and SCTP through matcher -> encoding -> kernel;
  * the serve layer: tier deltas patch like rule slabs (incremental on
    shape-preserving changes, full-rebuild fallback on tier-structure
    changes), plus the shared-selector-table regression the lattice
    exposed in IncrementalEngine.patch_policy;
  * the audit layer: audit_class_reduction under `tiers` fires on a
    merge only the ADMIN tiers distinguish (the plain-oracle
    under-assertion regression), and audit_policy_set stays sound on a
    tiered engine (the tier-composition note in analysis/audit.py).
"""

import random

import numpy as np
import pytest

from cyclonus_tpu.analysis.classes import audit_class_reduction
from cyclonus_tpu.engine.api import PortCase, TpuPolicyEngine
from cyclonus_tpu.kube.netpol import (
    IntOrString,
    LabelSelector,
    NetworkPolicy,
    NetworkPolicyIngressRule,
    NetworkPolicyPeer,
    NetworkPolicyPort,
    NetworkPolicySpec,
)
from cyclonus_tpu.matcher.builder import build_network_policies
from cyclonus_tpu.matcher.core import InternalPeer, Traffic, TrafficPeer
from cyclonus_tpu.matcher.tiered import TieredPolicy, tiered_oracle_verdicts
from cyclonus_tpu.serve import VerdictService
from cyclonus_tpu.tiers import fuzz
from cyclonus_tpu.tiers.model import (
    AdminNetworkPolicy,
    BaselineAdminNetworkPolicy,
    TierPort,
    TierRule,
    TierScope,
    TierSet,
    load_tier_set_from_yaml,
    parse_tier_object,
)
from cyclonus_tpu.worker.model import Delta, FlowQuery

CASES = [
    PortCase(80, "serve-80-tcp", "TCP"),
    PortCase(81, "serve-81-udp", "UDP"),
    PortCase(82, "serve-82-sctp", "SCTP"),
]


def mk_cluster():
    """Three namespaces, labeled pods — small enough for full oracle
    truth tables, labeled richly enough that tier scopes can split it."""
    namespaces = {
        "x": {"ns": "x", "team": "red"},
        "y": {"ns": "y", "team": "blue"},
        "z": {"ns": "z"},
    }
    pods = []
    i = 0
    for ns in namespaces:
        for name, labels in (
            ("a", {"pod": "a", "app": "web"}),
            ("b", {"pod": "b", "app": "db"}),
            ("c", {"pod": "c"}),
        ):
            pods.append((ns, name, dict(labels), f"10.0.0.{i + 1}"))
            i += 1
    return pods, namespaces


def traffic_between(pods, namespaces, case, si, di):
    sns, _sn, sl, sip = pods[si]
    dns, _dn, dl, dip = pods[di]
    return Traffic(
        source=TrafficPeer(
            internal=InternalPeer(
                pod_labels=sl, namespace_labels=namespaces[sns], namespace=sns
            ),
            ip=sip,
        ),
        destination=TrafficPeer(
            internal=InternalPeer(
                pod_labels=dl, namespace_labels=namespaces[dns], namespace=dns
            ),
            ip=dip,
        ),
        resolved_port=case.port,
        resolved_port_name=case.port_name,
        protocol=case.protocol,
    )


def pod_sel(**labels):
    return LabelSelector.make(match_labels=dict(labels))


def anp(name, priority, subject, ingress=(), egress=()):
    return AdminNetworkPolicy(
        name=name,
        priority=priority,
        subject=subject,
        ingress=list(ingress),
        egress=list(egress),
    )


def rule(action, peers=None, ports=None):
    return TierRule(
        action=action,
        peers=list(peers) if peers is not None else [TierScope()],
        ports=ports,
    )


def oracle_table(policy, tiers, pods, namespaces, cases=CASES):
    return fuzz._oracle_table(policy, tiers, pods, namespaces, cases)


def engine_table(policy, tiers, pods, namespaces, cases=CASES, mode="0"):
    engine = TpuPolicyEngine(
        policy, pods, namespaces, tiers=tiers, class_compress=mode
    )
    return fuzz._engine_table(engine, cases)


# --- model -----------------------------------------------------------------


class TestModel:
    def test_anp_dict_round_trip(self):
        a = anp(
            "a1",
            7,
            TierScope(
                namespace_selector=pod_sel(ns="x"),
                pod_selector=pod_sel(app="web"),
            ),
            ingress=[
                rule(
                    "Deny",
                    peers=[TierScope(namespace_selector=pod_sel(team="red"))],
                    ports=[
                        TierPort(protocol="TCP", port=IntOrString(80)),
                        TierPort(
                            protocol="SCTP",
                            port=IntOrString(80),
                            end_port=90,
                        ),
                        TierPort(protocol="TCP", port=IntOrString("http")),
                    ],
                )
            ],
            egress=[rule("Pass")],
        )
        assert AdminNetworkPolicy.from_dict(a.to_dict()) == a
        assert parse_tier_object(a.to_dict()) == a

    def test_banp_dict_round_trip(self):
        b = BaselineAdminNetworkPolicy(
            subject=TierScope(namespace_selector=pod_sel(ns="x")),
            ingress=[rule("Deny")],
        )
        assert BaselineAdminNetworkPolicy.from_dict(b.to_dict()) == b

    def test_nil_vs_empty_scope_survives_round_trip(self):
        # namespaces variant (pod_selector None = every pod of matching
        # namespaces) must not collapse into the pods variant with an
        # empty selector — both match everything, but the distinction
        # is API-visible
        ns_variant = TierScope(namespace_selector=pod_sel(ns="x"))
        rt = TierScope.from_dict(ns_variant.to_dict())
        assert rt.pod_selector is None
        pods_variant = TierScope(
            namespace_selector=pod_sel(ns="x"),
            pod_selector=LabelSelector.make(),
        )
        rt = TierScope.from_dict(pods_variant.to_dict())
        assert rt.pod_selector is not None

    def test_validation_rejects_bad_objects(self):
        with pytest.raises(ValueError, match="priority"):
            anp("p", 1001, TierScope()).validate()
        with pytest.raises(ValueError, match="invalid action"):
            anp("a", 1, TierScope(), ingress=[rule("Accept")]).validate()
        with pytest.raises(ValueError, match="invalid action"):
            # Pass is an ANP-only verb: BANP has nothing below to pass to
            BaselineAdminNetworkPolicy(ingress=[rule("Pass")]).validate()
        with pytest.raises(ValueError, match="end 79 < start"):
            TierPort(
                protocol="TCP", port=IntOrString(80), end_port=79
            ).validate()
        with pytest.raises(ValueError, match="must be numeric"):
            TierPort(
                protocol="TCP", port=IntOrString("http"), end_port=90
            ).validate()
        with pytest.raises(ValueError, match="duplicate"):
            TierSet(
                anps=[anp("dup", 1, TierScope()), anp("dup", 2, TierScope())]
            ).validate()
        # spec.priority is REQUIRED upstream: a payload without it must
        # be rejected at parse, never silently become priority 0 (the
        # cluster's highest) — the serve layer's pre-mutation validation
        # rides on this
        with pytest.raises(ValueError, match="priority is required"):
            AdminNetworkPolicy.from_dict(
                {
                    "kind": "AdminNetworkPolicy",
                    "metadata": {"name": "no-prio"},
                    "spec": {"ingress": [{"action": "Deny", "from": []}]},
                }
            )

    def test_yaml_loading(self):
        text = """
apiVersion: policy.networking.k8s.io/v1alpha1
kind: AdminNetworkPolicy
metadata: {name: deny-web}
spec:
  priority: 3
  subject: {pods: {namespaceSelector: {}, podSelector: {matchLabels: {app: web}}}}
  ingress:
    - action: Deny
      from:
        - namespaces: {matchLabels: {team: red}}
---
apiVersion: policy.networking.k8s.io/v1alpha1
kind: BaselineAdminNetworkPolicy
metadata: {name: default}
spec:
  subject: {namespaces: {}}
  ingress:
    - action: Allow
      from:
        - namespaces: {}
"""
        ts = load_tier_set_from_yaml(text)
        assert [a.name for a in ts.anps] == ["deny-web"]
        assert ts.banp is not None
        banp_only = text[text.index("---") :]
        with pytest.raises(ValueError, match="singleton"):
            load_tier_set_from_yaml(banp_only + banp_only)
        with pytest.raises(ValueError, match="unknown tier object kind"):
            load_tier_set_from_yaml("kind: NetworkPolicy\nmetadata: {name: x}")

    def test_ordered_rules_totalizes_priority_ties(self):
        ts = TierSet(
            anps=[
                anp("bbb", 5, TierScope(), ingress=[rule("Deny")]),
                anp("aaa", 5, TierScope(), ingress=[rule("Allow")]),
                anp("zzz", 1, TierScope(), ingress=[rule("Pass")]),
            ]
        )
        ordered = ts.ordered_rules(True, "anp")
        assert [o.policy.name for o in ordered] == ["zzz", "aaa", "bbb"]
        assert [o.rank for o in ordered] == [0, 1, 2]


# --- scalar lattice --------------------------------------------------------


class TestLatticeOracle:
    def _pods(self):
        return mk_cluster()

    def _idx(self, pods, ns, name):
        return next(
            i for i, p in enumerate(pods) if p[0] == ns and p[1] == name
        )

    def test_anp_deny_beats_default_allow(self):
        pods, namespaces = self._pods()
        ts = TierSet(
            anps=[
                anp(
                    "deny-web",
                    1,
                    TierScope(pod_selector=pod_sel(app="web")),
                    ingress=[rule("Deny")],
                )
            ]
        )
        oracle = TieredPolicy(build_network_policies(True, []), ts)
        web = self._idx(pods, "x", "a")
        db = self._idx(pods, "x", "b")
        t = traffic_between(pods, namespaces, CASES[0], db, web)
        assert oracle.is_traffic_allowed(t) == (False, True, False)
        assert oracle.explain(t) == {"ingress": "anp", "egress": "default"}
        # non-subject pods untouched
        t = traffic_between(pods, namespaces, CASES[0], web, db)
        assert oracle.is_traffic_allowed(t) == (True, True, True)

    def test_priority_orders_conflicting_anps(self):
        pods, namespaces = self._pods()
        deny = anp(
            "deny", 2, TierScope(), ingress=[rule("Deny")]
        )
        allow = anp(
            "allow", 1, TierScope(), ingress=[rule("Allow")]
        )
        policy = build_network_policies(True, [])
        t = traffic_between(pods, namespaces, CASES[0], 0, 4)
        assert TieredPolicy(policy, TierSet(anps=[deny, allow])).is_traffic_allowed(t)[0] is True
        # flip the priorities: deny now wins
        deny.priority, allow.priority = 1, 2
        assert TieredPolicy(policy, TierSet(anps=[deny, allow])).is_traffic_allowed(t)[0] is False

    def test_equal_priority_resolves_by_name(self):
        pods, namespaces = self._pods()
        policy = build_network_policies(True, [])
        t = traffic_between(pods, namespaces, CASES[0], 0, 4)
        ts = TierSet(
            anps=[
                anp("a-allow", 5, TierScope(), ingress=[rule("Allow")]),
                anp("b-deny", 5, TierScope(), ingress=[rule("Deny")]),
            ]
        )
        assert TieredPolicy(policy, ts).is_traffic_allowed(t)[0] is True
        ts = TierSet(
            anps=[
                anp("a-deny", 5, TierScope(), ingress=[rule("Deny")]),
                anp("b-allow", 5, TierScope(), ingress=[rule("Allow")]),
            ]
        )
        assert TieredPolicy(policy, ts).is_traffic_allowed(t)[0] is False

    def test_pass_falls_through_np_then_banp_then_default(self):
        pods, namespaces = self._pods()
        # ANP Pass over everything; NP denies x/a's non-80 ingress;
        # BANP denies db pods; everything else default-allows
        ts = TierSet(
            anps=[anp("pass-all", 0, TierScope(), ingress=[rule("Pass")])],
            banp=BaselineAdminNetworkPolicy(
                subject=TierScope(pod_selector=pod_sel(app="db")),
                ingress=[rule("Deny")],
            ),
        )
        np_pol = NetworkPolicy(
            name="allow-80",
            namespace="x",
            spec=NetworkPolicySpec(
                pod_selector=pod_sel(pod="a"),
                policy_types=["Ingress"],
                ingress=[
                    NetworkPolicyIngressRule(
                        ports=[
                            NetworkPolicyPort(
                                protocol="TCP", port=IntOrString(80)
                            )
                        ],
                        from_=[],
                    )
                ],
            ),
        )
        oracle = TieredPolicy(build_network_policies(True, [np_pol]), ts)
        xa = self._idx(pods, "x", "a")
        xb = self._idx(pods, "x", "b")  # app=db -> BANP subject
        zc = self._idx(pods, "z", "c")
        # NP tier decides for x/a: TCP 80 allowed, UDP 81 denied
        t80 = traffic_between(pods, namespaces, CASES[0], zc, xa)
        t81 = traffic_between(pods, namespaces, CASES[1], zc, xa)
        assert oracle.is_traffic_allowed(t80)[0] is True
        assert oracle.is_traffic_allowed(t81)[0] is False
        assert oracle.explain(t81)["ingress"] == "np"
        # no NP target for x/b -> falls to BANP deny
        t = traffic_between(pods, namespaces, CASES[0], zc, xb)
        assert oracle.is_traffic_allowed(t)[0] is False
        assert oracle.explain(t)["ingress"] == "banp"
        # no NP, no BANP match -> default allow
        t = traffic_between(pods, namespaces, CASES[0], xa, zc)
        assert oracle.is_traffic_allowed(t)[0] is True
        assert oracle.explain(t)["ingress"] == "default"

    def test_banp_never_fires_for_np_selected_pods(self):
        pods, namespaces = self._pods()
        # NP allows everything into x/a; BANP would deny it — NP is final
        np_pol = NetworkPolicy(
            name="allow-all",
            namespace="x",
            spec=NetworkPolicySpec(
                pod_selector=pod_sel(pod="a"),
                policy_types=["Ingress"],
                ingress=[NetworkPolicyIngressRule(ports=[], from_=[])],
            ),
        )
        ts = TierSet(
            banp=BaselineAdminNetworkPolicy(
                subject=TierScope(), ingress=[rule("Deny")]
            )
        )
        oracle = TieredPolicy(build_network_policies(True, [np_pol]), ts)
        xa = self._idx(pods, "x", "a")
        zc = self._idx(pods, "z", "c")
        t = traffic_between(pods, namespaces, CASES[0], zc, xa)
        assert oracle.is_traffic_allowed(t)[0] is True
        assert oracle.explain(t)["ingress"] == "np"
        # the unselected pod gets the BANP deny
        t = traffic_between(pods, namespaces, CASES[0], xa, zc)
        assert oracle.is_traffic_allowed(t)[0] is False
        assert oracle.explain(t)["ingress"] == "banp"

    def test_external_peer_passes_admin_tiers(self):
        pods, namespaces = self._pods()
        ts = TierSet(
            anps=[anp("deny-all", 0, TierScope(), ingress=[rule("Deny")])]
        )
        oracle = TieredPolicy(build_network_policies(True, []), ts)
        # external destination: ingress verdict is "external" allow
        t = Traffic(
            source=TrafficPeer(
                internal=InternalPeer(
                    pod_labels={"pod": "a"},
                    namespace_labels=namespaces["x"],
                    namespace="x",
                ),
                ip="10.0.0.1",
            ),
            destination=TrafficPeer(internal=None, ip="8.8.8.8"),
            resolved_port=80,
            resolved_port_name="",
            protocol="TCP",
        )
        assert oracle.direction_allowed(t, True) == (True, "external")
        # external SOURCE against an internal target: admin scopes are
        # cluster-internal, the deny-all never matches the peer -> the
        # verdict falls through to default
        t2 = Traffic(
            source=TrafficPeer(internal=None, ip="8.8.8.8"),
            destination=t.source,
            resolved_port=80,
            resolved_port_name="",
            protocol="TCP",
        )
        assert oracle.direction_allowed(t2, True) == (True, "default")

    def test_tiered_oracle_verdicts_defers_to_plain_without_tiers(self):
        pods, namespaces = self._pods()
        policy = build_network_policies(True, [])
        t = traffic_between(pods, namespaces, CASES[0], 0, 1)
        assert tiered_oracle_verdicts(policy, None, t) == (True, True, True)
        assert tiered_oracle_verdicts(policy, TierSet(), t) == (
            True,
            True,
            True,
        )


# --- properties ------------------------------------------------------------


class TestProperties:
    def test_priority_order_invariant_under_anp_shuffle(self):
        """The verdict lattice depends on (priority, name), never on the
        declaration order of the ANP list."""
        checked = 0
        for seed in range(12):
            fc = fuzz.build_fuzz_case(seed)
            if fc.tiers is None or len(fc.tiers.anps) < 2:
                continue
            policy = build_network_policies(fc.simplify, fc.netpols)
            want = oracle_table(
                policy, fc.tiers, fc.pods, fc.namespaces, fc.cases
            )
            shuffled = fc.tiers.copy()
            random.Random(seed ^ 0xFACE).shuffle(shuffled.anps)
            got = oracle_table(
                policy, shuffled, fc.pods, fc.namespaces, fc.cases
            )
            assert np.array_equal(got, want), f"seed {seed}"
            checked += 1
        assert checked >= 2
        # engine-side twin on one seed: the slab rank order is also
        # declaration-order independent
        fc = fuzz.build_fuzz_case(5)
        assert fc.tiers is not None and len(fc.tiers.anps) >= 2
        policy = build_network_policies(fc.simplify, fc.netpols)
        shuffled = fc.tiers.copy()
        random.Random(0xFACE).shuffle(shuffled.anps)
        want = engine_table(policy, fc.tiers, fc.pods, fc.namespaces, fc.cases)
        got = engine_table(policy, shuffled, fc.pods, fc.namespaces, fc.cases)
        assert np.array_equal(got, want)

    def test_all_pass_anps_are_transparent(self):
        """An ANP tier of only Pass rules (and no BANP) must leave every
        verdict exactly as the plain networkingv1 oracle computes it."""
        checked = 0
        for seed in range(10):
            fc = fuzz.build_fuzz_case(seed)
            if fc.tiers is None or not fc.tiers.anps:
                continue
            passthrough = fc.tiers.copy()
            passthrough.banp = None
            for a in passthrough.anps:
                for r in a.ingress + a.egress:
                    r.action = "Pass"
            policy = build_network_policies(fc.simplify, fc.netpols)
            want = oracle_table(
                policy, None, fc.pods, fc.namespaces, fc.cases
            )
            got = oracle_table(
                policy, passthrough, fc.pods, fc.namespaces, fc.cases
            )
            assert np.array_equal(got, want), f"seed {seed}"
            checked += 1
        assert checked >= 2

    def test_zero_tier_encoding_byte_identical(self):
        """The acceptance criterion: zero ANP/BANP objects keep the
        networkingv1-only fast path — the tensor set (and therefore
        every compiled program) is byte-identical, tiers=None and an
        empty TierSet included."""
        pods, namespaces = mk_cluster()
        netpols = [
            NetworkPolicy(
                name="np0",
                namespace="x",
                spec=NetworkPolicySpec(
                    pod_selector=pod_sel(app="web"),
                    policy_types=["Ingress"],
                    ingress=[
                        NetworkPolicyIngressRule(
                            ports=[],
                            from_=[
                                NetworkPolicyPeer(
                                    pod_selector=pod_sel(pod="b")
                                )
                            ],
                        )
                    ],
                ),
            )
        ]
        policy = build_network_policies(True, netpols)
        plain = TpuPolicyEngine(policy, pods, namespaces)
        empty = TpuPolicyEngine(policy, pods, namespaces, tiers=TierSet())
        assert empty.tiers is None
        assert plain.encoding.tiers is None and empty.encoding.tiers is None
        assert "tiers" not in plain._tensors
        assert "tiers" not in empty._tensors

        def flatten(prefix, tree, out):
            for k in sorted(tree):
                v = tree[k]
                if isinstance(v, dict):
                    flatten(f"{prefix}{k}.", v, out)
                else:
                    out[f"{prefix}{k}"] = v
            return out

        a = flatten("", plain._tensors, {})
        b = flatten("", empty._tensors, {})
        assert list(a) == list(b)
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
        assert plain.tier_stats() == {
            "active": False,
            "anp_count": 0,
            "rule_rows": 0,
            "banp": False,
            "resolve_s": None,
        }

    def test_tier_stats_reports_active_lattice(self):
        pods, namespaces = mk_cluster()
        ts = TierSet(
            anps=[
                anp(
                    "a",
                    1,
                    TierScope(),
                    ingress=[
                        rule(
                            "Deny",
                            peers=[
                                TierScope(),
                                TierScope(pod_selector=pod_sel(app="db")),
                            ],
                        )
                    ],
                )
            ],
            banp=BaselineAdminNetworkPolicy(ingress=[rule("Allow")]),
        )
        engine = TpuPolicyEngine(
            build_network_policies(True, []), pods, namespaces, tiers=ts
        )
        st = engine.tier_stats()
        assert st["active"] is True and st["anp_count"] == 1
        assert st["banp"] is True
        # flat rows: 2 peer rows (ANP ingress) + 1 (BANP ingress), both
        # directions counted — egress contributes none here
        assert st["rule_rows"] == 3
        assert st["resolve_s"] is None
        engine.evaluate_grid(CASES)
        assert engine.tier_stats()["resolve_s"] > 0


# --- the differential gate -------------------------------------------------


class TestDifferentialGate:
    def test_conformance_fixtures_dense_and_compressed(self):
        """The generator's ANP/BANP family through the same
        kernel-vs-oracle gate `cyclonus-tpu fuzz --conformance` runs."""
        assert fuzz.run_conformance() >= 8

    @pytest.mark.parametrize("seed", range(8))
    def test_fuzz_seed(self, seed):
        """>= 8 seeded adversarial scenarios, each checked dense AND
        class-compressed against the tiered scalar oracle (truth tables
        bit-identical, counts equal to oracle sums, pair spot checks).
        A failure reproduces with `cyclonus-tpu fuzz --seed N --seeds
        1`."""
        fuzz.run_seed(seed, pair_samples=8)

    def test_fuzz_runs_reference_linter_and_reports_warnings(self):
        """Every seed's generated NetworkPolicy set runs the ported
        reference linter (cyclonus_tpu/linter/checks.py) non-crashing,
        and the warning census rides the fuzz report — the pkg/linter
        parity pass exercised at generator scale.  The per-seed stats
        and the aggregated report must agree."""
        report = fuzz.run(
            seeds=4, check_counts=False, check_mesh=False, pair_samples=0
        )
        d = report.to_dict()
        assert "lint_warnings" in d and "lint_warnings_by_check" in d
        assert d["lint_warnings"] == sum(
            d["lint_warnings_by_check"].values()
        )
        # the adversarial generator reliably produces lintable shapes
        # (protocol-less ports, all-allowed/blocked targets) across a
        # few seeds — an always-zero census would mean the leg is dead
        assert d["lint_warnings"] > 0, d
        per_seed = [
            fuzz.run_seed(
                s, check_counts=False, check_mesh=False, pair_samples=0
            )["lint_warnings"]
            for s in range(4)
        ]
        assert sum(per_seed) == d["lint_warnings"]


# --- endPort + SCTP --------------------------------------------------------


class TestEndPortSctp:
    def _netpol_endport(self, proto="TCP"):
        return NetworkPolicy(
            name="range",
            namespace="x",
            spec=NetworkPolicySpec(
                pod_selector=pod_sel(app="web"),
                policy_types=["Ingress"],
                ingress=[
                    NetworkPolicyIngressRule(
                        ports=[
                            NetworkPolicyPort(
                                protocol=proto,
                                port=IntOrString(80),
                                end_port=85,
                            )
                        ],
                        from_=[],
                    )
                ],
            ),
        )

    def test_np_endport_range_engine_vs_oracle(self):
        pods, namespaces = mk_cluster()
        cases = [
            PortCase(79, "", "TCP"),
            PortCase(80, "", "TCP"),
            PortCase(85, "", "TCP"),
            PortCase(86, "", "TCP"),
            PortCase(80, "", "UDP"),  # protocol axis respected
        ]
        policy = build_network_policies(True, [self._netpol_endport()])
        want = oracle_table(policy, None, pods, namespaces, cases)
        for mode in ("0", "1"):
            got = engine_table(
                policy, None, pods, namespaces, cases, mode=mode
            )
            assert np.array_equal(got, want), f"mode {mode}"
        # boundary semantics, pinned explicitly: [80, 85] inclusive
        web = next(
            i for i, p in enumerate(pods) if p[0] == "x" and p[1] == "a"
        )
        other = (web + 1) % len(pods)
        assert want[0, other, web, 0] == False  # 79  # noqa: E712
        assert want[1, other, web, 0] == True  # 80  # noqa: E712
        assert want[2, other, web, 0] == True  # 85  # noqa: E712
        assert want[3, other, web, 0] == False  # 86  # noqa: E712
        assert want[4, other, web, 0] == False  # UDP  # noqa: E712

    def test_tier_port_range_and_sctp_engine_vs_oracle(self):
        pods, namespaces = mk_cluster()
        cases = [
            PortCase(79, "", "SCTP"),
            PortCase(80, "", "SCTP"),
            PortCase(81, "", "SCTP"),
            PortCase(82, "", "SCTP"),
            PortCase(80, "", "TCP"),
        ]
        ts = TierSet(
            anps=[
                anp(
                    "deny-sctp-window",
                    1,
                    TierScope(),
                    ingress=[
                        rule(
                            "Deny",
                            ports=[
                                TierPort(
                                    protocol="SCTP",
                                    port=IntOrString(80),
                                    end_port=81,
                                )
                            ],
                        )
                    ],
                )
            ]
        )
        policy = build_network_policies(True, [])
        want = oracle_table(policy, ts, pods, namespaces, cases)
        for mode in ("0", "1"):
            got = engine_table(policy, ts, pods, namespaces, cases, mode=mode)
            assert np.array_equal(got, want), f"mode {mode}"
        # SCTP [80, 81] denied; 79/82 and TCP 80 untouched
        assert not want[1, 0, 4, 0] and not want[2, 0, 4, 0]
        assert want[0, 0, 4, 0] and want[3, 0, 4, 0] and want[4, 0, 4, 0]


# --- serve layer -----------------------------------------------------------


def _tiny_serve(tiers=None, netpols=()):
    namespaces = {"x": {"ns": "x"}, "y": {"ns": "y"}}
    pods = []
    for i in range(8):
        ns = "x" if i % 2 == 0 else "y"
        pods.append(
            (
                ns,
                f"p{i}",
                {"app": "web" if i % 4 < 2 else "db"},
                f"10.0.0.{i + 1}",
            )
        )
    return VerdictService(pods, namespaces, list(netpols), tiers=tiers), pods


def _q(svc, src, dst, port=80, proto="TCP", name="serve-80-tcp"):
    [v] = svc.query(
        [FlowQuery(src=src, dst=dst, port=port, protocol=proto,
                   port_name=name)]
    )
    assert not v.error, v.error
    return v.combined


class TestServeTiers:
    def test_anp_upsert_same_shape_patches_incrementally(self):
        """Tier slabs patch like rule slabs: an action flip keeps every
        bucketed shape, so the delta takes the incremental path — and
        the patched engine stays bit-identical to a fresh rebuild."""
        ts = TierSet(
            anps=[
                anp(
                    "flip",
                    1,
                    TierScope(pod_selector=pod_sel(app="web")),
                    ingress=[rule("Deny")],
                )
            ]
        )
        svc, pods = _tiny_serve(tiers=ts)
        web = f"{pods[0][0]}/{pods[0][1]}"
        db = f"{pods[2][0]}/{pods[2][1]}"
        assert _q(svc, db, web) is False
        flipped = ts.anps[0].to_dict()
        flipped["spec"]["ingress"][0]["action"] = "Allow"
        report = svc.apply(
            [Delta(kind="anp_upsert", name="flip", policy=flipped)]
        )
        assert report["mode"] in ("incremental", "class_rebuild"), report
        assert _q(svc, db, web) is True
        svc.verify_parity(CASES[:2], oracle_samples=16)

    def test_np_delta_on_tiered_service_re_encodes_shared_table(self):
        """The shared-selector-table regression: a PURE NetworkPolicy
        delta on a tiered engine must re-encode the tier slabs too
        (their selector ids index the table the NP re-encode rebuilds).
        Before the fix, patch_policy dropped the tier slabs' table —
        verify_parity catches any drift bit-exactly."""
        ts = TierSet(
            anps=[
                anp(
                    "deny-db",
                    1,
                    TierScope(pod_selector=pod_sel(app="db")),
                    ingress=[rule("Deny")],
                )
            ]
        )
        netpol = NetworkPolicy(
            name="allow-80",
            namespace="x",
            spec=NetworkPolicySpec(
                pod_selector=pod_sel(app="web"),
                policy_types=["Ingress"],
                ingress=[
                    NetworkPolicyIngressRule(
                        ports=[
                            NetworkPolicyPort(
                                protocol="TCP", port=IntOrString(80)
                            )
                        ],
                        from_=[
                            NetworkPolicyPeer(pod_selector=pod_sel(app="web"))
                        ],
                    )
                ],
            ),
        )
        svc, pods = _tiny_serve(tiers=ts, netpols=[netpol])
        from cyclonus_tpu.kube.yaml_io import policy_to_dict

        changed = netpol
        changed.spec.ingress[0].from_ = [
            NetworkPolicyPeer(pod_selector=pod_sel(app="db"))
        ]
        report = svc.apply(
            [
                Delta(
                    kind="policy_upsert",
                    namespace="x",
                    name="allow-80",
                    policy=policy_to_dict(changed),
                )
            ]
        )
        # mode may be incremental or full depending on bucketed shapes;
        # correctness is the invariant — incremental engine == fresh
        # rebuild == tiered oracle
        svc.verify_parity(CASES[:2], oracle_samples=16)
        # the ANP deny must still be live after the NP-only delta
        db = f"{pods[2][0]}/{pods[2][1]}"
        web = f"{pods[0][0]}/{pods[0][1]}"
        assert _q(svc, web, db) is False, report

    def test_tier_structure_change_falls_back_to_full_rebuild(self):
        """ANP objects appearing on a tier-less engine (or the tier
        slabs vanishing) is a tensor-structure change only the full
        rebuild can make — and the rebuilt engine is correct."""
        svc, pods = _tiny_serve()  # no tiers
        web = f"{pods[0][0]}/{pods[0][1]}"
        db = f"{pods[2][0]}/{pods[2][1]}"
        assert _q(svc, db, web) is True
        new_anp = anp(
            "deny-web",
            1,
            TierScope(pod_selector=pod_sel(app="web")),
            ingress=[rule("Deny")],
        )
        report = svc.apply(
            [Delta(kind="anp_upsert", name="deny-web",
                   policy=new_anp.to_dict())]
        )
        assert report["mode"] == "full", report
        assert _q(svc, db, web) is False
        svc.verify_parity(CASES[:2], oracle_samples=16)
        # ... and vanishing again is also structural
        report = svc.apply([Delta(kind="anp_delete", name="deny-web")])
        assert report["mode"] == "full", report
        assert _q(svc, db, web) is True

    def test_banp_upsert_delete_round_trip(self):
        svc, pods = _tiny_serve()
        web = f"{pods[0][0]}/{pods[0][1]}"
        db = f"{pods[2][0]}/{pods[2][1]}"
        banp = BaselineAdminNetworkPolicy(
            subject=TierScope(pod_selector=pod_sel(app="web")),
            ingress=[rule("Deny")],
        )
        svc.apply([Delta(kind="banp_upsert", policy=banp.to_dict())])
        assert _q(svc, db, web) is False
        assert svc.state()["tiers"]["banp"] is True
        svc.apply([Delta(kind="banp_delete")])
        assert _q(svc, db, web) is True
        assert svc.state()["tiers"]["active"] is False

    def test_malformed_tier_delta_rejected_before_state_mutates(self):
        svc, _pods = _tiny_serve()
        bad = {
            "kind": "AdminNetworkPolicy",
            "metadata": {"name": "bad"},
            "spec": {"priority": 9999, "ingress": [{"action": "Deny"}]},
        }
        report = svc.apply([Delta(kind="anp_upsert", name="bad",
                                  policy=bad)])
        assert report["rejected"] and not report["applied"]
        assert "bad" not in svc.anps
        assert svc.state()["tiers"]["active"] is False
        # spec.priority is required — a payload without it must never
        # silently install at priority 0
        no_prio = {
            "kind": "AdminNetworkPolicy",
            "metadata": {"name": "sneaky"},
            "spec": {"ingress": [{"action": "Deny", "from": []}]},
        }
        report = svc.apply([Delta(kind="anp_upsert", name="sneaky",
                                  policy=no_prio)])
        assert report["rejected"] and "priority is required" in \
            report["rejected"][0]
        assert "sneaky" not in svc.anps

    def test_misrouted_tier_payload_rejected_by_kind(self):
        """from_dict ignores `kind`, so the wire path checks it like
        the YAML path's parse_tier_object: an ANP sent as banp_upsert
        (or junk) must be rejected, never installed as the baseline."""
        ts = TierSet(
            banp=BaselineAdminNetworkPolicy(
                subject=TierScope(pod_selector=pod_sel(app="web")),
                ingress=[rule("Deny")],
            )
        )
        svc, pods = _tiny_serve(tiers=ts)
        web = f"{pods[0][0]}/{pods[0][1]}"
        db = f"{pods[2][0]}/{pods[2][1]}"
        assert _q(svc, db, web) is False  # the real baseline deny
        mis = anp(
            "mis", 1, TierScope(), ingress=[rule("Allow")]
        ).to_dict()  # kind: AdminNetworkPolicy
        report = svc.apply([Delta(kind="banp_upsert", policy=mis)])
        assert report["rejected"], report
        assert "kind" in report["rejected"][0]
        report = svc.apply(
            [Delta(kind="banp_upsert", policy={"kind": "x"})]
        )
        assert report["rejected"], report
        # the real baseline survived both
        assert svc.banp == ts.banp
        assert _q(svc, db, web) is False


class TestMeshTieredCounts:
    """The mesh-parallel counts routes (replicated source rows, ring
    ppermute rotation of the dst-side tier arrays) carry the same
    resolution epilogue — differentially gated here against the tiered
    oracle on the CPU 8-virtual-device mesh."""

    def test_sharded_and_ring_counts_match_oracle_under_tiers(
        self, monkeypatch
    ):
        import cyclonus_tpu.engine.api as api

        pods, namespaces = mk_cluster()
        netpol = NetworkPolicy(
            name="allow-80",
            namespace="x",
            spec=NetworkPolicySpec(
                pod_selector=pod_sel(app="web"),
                policy_types=["Ingress"],
                ingress=[
                    NetworkPolicyIngressRule(
                        ports=[
                            NetworkPolicyPort(
                                protocol="TCP", port=IntOrString(80)
                            )
                        ],
                        from_=[],
                    )
                ],
            ),
        )
        ts = TierSet(
            anps=[
                anp(
                    "deny-db",
                    1,
                    TierScope(pod_selector=pod_sel(app="db")),
                    ingress=[
                        rule(
                            "Deny",
                            peers=[
                                TierScope(
                                    namespace_selector=pod_sel(team="red")
                                )
                            ],
                        )
                    ],
                ),
                anp("pass-web", 2,
                    TierScope(pod_selector=pod_sel(app="web")),
                    ingress=[rule("Pass")]),
            ],
            banp=BaselineAdminNetworkPolicy(
                subject=TierScope(namespace_selector=pod_sel(ns="z")),
                ingress=[rule("Deny")],
                egress=[rule("Allow")],
            ),
        )
        policy = build_network_policies(True, [netpol])
        want = oracle_table(policy, ts, pods, namespaces, CASES)
        sums = {
            "ingress": int(want[..., 0].sum()),
            "egress": int(want[..., 1].sum()),
            "combined": int(want[..., 2].sum()),
        }
        engine = TpuPolicyEngine(policy, pods, namespaces, tiers=ts)
        # the entry's default route, then the ring forced by the ceiling
        for name in ("default", "ring"):
            if name == "ring":
                monkeypatch.setattr(api, "_MESH_REPLICATED_MAX_BYTES", 0)
            counts = engine.evaluate_grid_counts_sharded(CASES, block=4)
            assert {k: counts[k] for k in sums} == sums, name
        # the mesh-sharded GRID path too (shard_map tier all-gathers):
        # full truth table bit-identical to the tiered oracle
        grid = engine.evaluate_grid_sharded(CASES)
        got = np.stack(
            [
                np.swapaxes(np.asarray(grid.ingress), 1, 2),
                np.asarray(grid.egress),
                np.asarray(grid.combined),
            ],
            axis=-1,
        )
        assert np.array_equal(got, want)

    def test_explicit_pallas_counts_request_fails_loudly(self, monkeypatch):
        """Under the legacy (CYCLONUS_PACK=0) dtype plan the dense
        pallas kernel cannot express the lattice: the auto default
        routes tiered counts to the XLA tile body and an EXPLICIT
        pallas request must raise, not silently publish the XLA rate
        under the pallas label.  Under the PACKED plan the fused tier
        epilogue serves pallas counts directly — and stays bit-identical
        to the oracle."""
        pods, namespaces = mk_cluster()
        ts = TierSet(
            anps=[anp("d", 1, TierScope(), ingress=[rule("Deny")])]
        )
        monkeypatch.setenv("CYCLONUS_PACK", "0")
        engine = TpuPolicyEngine(
            build_network_policies(True, []), pods, namespaces, tiers=ts
        )
        with pytest.raises(ValueError, match="precedence-tier"):
            engine.evaluate_grid_counts(CASES, backend="pallas")
        with pytest.raises(ValueError, match="precedence-tier"):
            engine.evaluate_grid_counts_sharded(CASES, kernel="pallas")
        # auto stays routed and correct
        want = oracle_table(
            build_network_policies(True, []), ts, pods, namespaces, CASES
        )
        counts = engine.evaluate_grid_counts(CASES, block=8)
        assert counts["combined"] == int(want[..., 2].sum())
        # packed plan: the fused tier epilogue serves an explicit
        # pallas request, counts pinned to the oracle; the sharded
        # per-device kernel keeps the loud failure (no fused tier there)
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        packed = TpuPolicyEngine(
            build_network_policies(True, []), pods, namespaces, tiers=ts
        )
        pcounts = packed.evaluate_grid_counts(CASES, backend="pallas")
        assert pcounts["combined"] == int(want[..., 2].sum())
        assert pcounts == counts
        with pytest.raises(ValueError, match="precedence-tier"):
            packed.evaluate_grid_counts_sharded(CASES, kernel="pallas")


# --- audit layer -----------------------------------------------------------


class TestAuditTierComposition:
    def test_class_audit_plain_oracle_under_asserts_without_tiers(self):
        """The bool-OR regression the lattice exposed: merge two pods
        only the ADMIN tiers distinguish — the plain-oracle audit passes
        (no NetworkPolicy separates them) while the tiered audit fires.
        audit_class_reduction(tiers=...) is the fix."""
        from cyclonus_tpu.engine.encoding import PodClasses

        pods, namespaces = mk_cluster()
        ts = TierSet(
            anps=[
                anp(
                    "deny-web",
                    1,
                    TierScope(pod_selector=pod_sel(app="web")),
                    ingress=[rule("Deny")],
                )
            ]
        )
        policy = build_network_policies(True, [])
        engine = TpuPolicyEngine(
            policy, pods, namespaces, tiers=ts, class_compress="1"
        )
        pc = engine.pod_classes()
        assert pc is not None
        # x/a (app=web, ANP-denied ingress) vs x/c (no app label): no
        # NetworkPolicy exists, so the plain oracle sees them identical
        a = next(
            i for i, p in enumerate(pods) if p[0] == "x" and p[1] == "a"
        )
        c = next(
            i for i, p in enumerate(pods) if p[0] == "x" and p[1] == "c"
        )
        of = np.asarray(pc.class_of_pod)
        # the REAL classifier must already keep them apart (tier
        # selectors ride the shared selector table the signature packs)
        assert of[a] != of[c]
        corrupt_of = of.copy()
        corrupt_of[c] = corrupt_of[a]
        sizes = np.bincount(corrupt_of, minlength=pc.n_classes).astype(
            np.int32
        )
        corrupted = PodClasses(
            n_pods=pc.n_pods,
            n_classes=pc.n_classes,
            class_of_pod=corrupt_of,
            class_rep=pc.class_rep,
            class_size=sizes,
        )
        plain = audit_class_reduction(
            policy, pods, namespaces, CASES[:1], corrupted,
            max_classes=32, peers_per_class=len(pods),
        )
        assert plain["ok"], "plain oracle cannot see the tier split"
        tiered = audit_class_reduction(
            policy, pods, namespaces, CASES[:1], corrupted,
            max_classes=32, peers_per_class=len(pods), tiers=ts,
        )
        assert not tiered["ok"]
        assert tiered["violations"]

    def test_class_audit_passes_on_real_tiered_classes(self):
        pods, namespaces = mk_cluster()
        ts = TierSet(
            anps=[
                anp(
                    "deny-web",
                    1,
                    TierScope(pod_selector=pod_sel(app="web")),
                    ingress=[rule("Deny")],
                )
            ],
            banp=BaselineAdminNetworkPolicy(ingress=[rule("Allow")]),
        )
        policy = build_network_policies(True, [])
        engine = TpuPolicyEngine(
            policy, pods, namespaces, tiers=ts, class_compress="1"
        )
        pc = engine.pod_classes()
        assert pc is not None
        report = audit_class_reduction(
            policy, pods, namespaces, CASES, pc,
            max_classes=32, peers_per_class=len(pods), tiers=ts,
        )
        assert report["ok"], report["violations"][:3]

    def test_np_audit_stays_sound_on_tiered_engine(self):
        """The tier-composition note in analysis/audit.py: firing masks
        are an NP-tier concept; on a tiered engine the audit's findings
        must match the tier-less engine's exactly (firing_components
        excludes the tier slabs)."""
        from cyclonus_tpu.analysis.audit import audit_policy_set

        pods, namespaces = mk_cluster()
        shadowing = NetworkPolicy(
            name="wide",
            namespace="x",
            spec=NetworkPolicySpec(
                pod_selector=pod_sel(app="web"),
                policy_types=["Ingress"],
                ingress=[
                    NetworkPolicyIngressRule(ports=[], from_=[]),
                    NetworkPolicyIngressRule(
                        ports=[],
                        from_=[
                            NetworkPolicyPeer(pod_selector=pod_sel(pod="b"))
                        ],
                    ),
                ],
            ),
        )
        policy = build_network_policies(False, [shadowing])
        ts = TierSet(
            anps=[anp("pass", 1, TierScope(), ingress=[rule("Pass")])]
        )
        plain_engine = TpuPolicyEngine(policy, pods, namespaces)
        tiered_engine = TpuPolicyEngine(policy, pods, namespaces, tiers=ts)
        plain = audit_policy_set(
            policy, pods, namespaces, CASES[:2], engine=plain_engine
        )
        tiered = audit_policy_set(
            policy, pods, namespaces, CASES[:2], engine=tiered_engine
        )

        def key(f):
            return (f.kind, f.rule.label, f.fire_cells, f.oracle)

        assert [key(f) for f in plain.findings] == [
            key(f) for f in tiered.findings
        ]
        assert plain.findings  # the shadowed rule IS found
