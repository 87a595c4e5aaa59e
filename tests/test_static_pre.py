"""The cut of `tiled._precompute` where the port cases enter, and the
dense counts route's holder of the first half (engine/api.py
`_static_pre`, programs `counts.static` and `counts.cases`):

  * `_precompute_cases(_precompute_static(t))`, the two halves as two
    programs with the static crossing between them as device arrays,
    equals `_precompute(t)` and the function as it stood before the cut,
    leaf by leaf, packed and not, with IPv6 host rows and with tiers;
  * an engine answers a case set that no pin serves from the resident
    static (`mode=resident`), builds it once, and counts what the fused
    program and the scalar oracle count;
  * a patched buffer is never answered from the static of the buffer
    before it;
  * over the pins' byte ceiling, or with CYCLONUS_PRE_CACHE=0, the route
    answers from the fused program as it always has.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cyclonus_tpu.engine.api as api
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.engine import tiled
from cyclonus_tpu.engine.kernel import (
    direction_precompute,
    m_tp_onehot,
    pack_bool_words_jnp,
    port_spec_allows,
    selector_match,
    tier_direction_arrays,
)
from cyclonus_tpu.kube.netpol import (
    IPBlock,
    LabelSelector,
    NetworkPolicyEgressRule,
    NetworkPolicyIngressRule,
    NetworkPolicyPeer,
)
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.serve import VerdictService
from cyclonus_tpu.telemetry import instruments as ti
from cyclonus_tpu.telemetry import recorder, spans
from cyclonus_tpu.tiers.fuzz import _oracle_table, build_fuzz_case
from cyclonus_tpu.worker.model import Delta

from test_engine_parity import default_cluster, mkpol
from test_engine_tiled import fuzz_problem
from test_serve import APPS, mk_policy

A = [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "serve-81-udp", "UDP")]
B = [PortCase(81, "", "UDP")]
C = [PortCase(9999, "", "TCP"), PortCase(80, "serve-80-tcp", "SCTP")]
KINDS = ("ingress", "egress", "combined")


def v6_problem():
    """Every other pod on an IPv6 address and an ipBlock peer over them
    in each direction: the encoder resolves those rows on the host
    (`host_ip_match`), and `_apply_host_ip` lays them over `peer_match`."""
    pods, namespaces = default_cluster()
    pods = [
        (ns, name, labels, ip if i % 2 else f"2001:db8::{i + 1}")
        for i, (ns, name, labels, ip) in enumerate(pods)
    ]
    peer = NetworkPolicyPeer(ip_block=IPBlock.make("2001:db8::/112", []))
    pol_i = mkpol(
        "v6-in", "x", LabelSelector.make(), ["Ingress"],
        ingress=[NetworkPolicyIngressRule(ports=[], from_=[peer])],
    )
    pol_e = mkpol(
        "v6-eg", "y", LabelSelector.make(), ["Egress"],
        egress=[NetworkPolicyEgressRule(ports=[], to=[peer])],
    )
    return build_network_policies(True, [pol_i, pol_e]), None, pods, namespaces, A


def problem(which):
    """(policy, tiers, pods, namespaces, cases) of a parametrised case."""
    if which == "host_ip":
        return v6_problem()
    if which == "tiered":
        for seed in range(32):
            fc = build_fuzz_case(seed)
            if fc.tiers is not None:
                policy = build_network_policies(fc.simplify, fc.netpols)
                return policy, fc.tiers, fc.pods, fc.namespaces, fc.cases
        raise AssertionError("generator produced no tiered case in 32 seeds")
    policy, pods, namespaces = fuzz_problem(31, n_extra_pods=9)
    return policy, None, pods, namespaces, A


def oracle_counts(policy, tiers, pods, namespaces, cases):
    table = _oracle_table(policy, tiers, list(pods), namespaces, list(cases))
    return {k: int(table[..., i].sum()) for i, k in enumerate(KINDS)}


def counts_of(got):
    return {k: got[k] for k in KINDS}


def precompute_before_the_cut(tensors, pack):
    """`tiled._precompute` as it stood before PR 33 cut it in two."""
    selpod = selector_match(
        tensors["sel_req_kv"], tensors["sel_exp_op"], tensors["sel_exp_key"],
        tensors["sel_exp_vals"], tensors["pod_kv"], tensors["pod_key"],
    )
    selns = selector_match(
        tensors["sel_req_kv"], tensors["sel_exp_op"], tensors["sel_exp_key"],
        tensors["sel_exp_vals"], tensors["ns_kv"], tensors["ns_key"],
    )
    out = {}
    q = tensors["q_port"].shape[0]
    for direction in ("ingress", "egress"):
        enc = tensors[direction]
        pre = direction_precompute(
            enc, selpod, selns, tensors["pod_ns_id"], tensors["pod_ip"],
            tensors["pod_ip_valid"],
        )
        pre = tiled._apply_host_ip(enc, pre)
        pport = port_spec_allows(
            enc["port_spec"], tensors["q_port"], tensors["q_name"],
            tensors["q_proto"],
        )
        n_p, n = pre["peer_match"].shape
        peer_allow = (
            pre["peer_match"][:, :, None] & pport[:, None, :]
        ).reshape(n_p, n * q)
        tallow = jnp.matmul(
            m_tp_onehot(enc).astype(jnp.bfloat16),
            peer_allow.astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16,
        )
        t = tallow.shape[0]
        out[direction] = {
            "tmatch": pre["tmatch"], "has_target": pre["has_target"],
        }
        if pack:
            out[direction]["tallow_pk"] = pack_bool_words_jnp(
                (tallow > 0).reshape(t, n, q)
            )
            out[direction]["tmatch_pk"] = pack_bool_words_jnp(pre["tmatch"])
        else:
            out[direction]["tallow_bf"] = (
                (tallow > 0).astype(jnp.bfloat16).reshape(t, n, q)
            )
        if "tiers" in tensors:
            out[direction]["tier"] = tier_direction_arrays(
                tensors["tiers"][direction], selpod, selns,
                tensors["pod_ns_id"], tensors["q_port"], tensors["q_name"],
                tensors["q_proto"],
            )
    return out


def assert_same_tree(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestTheCut:
    @pytest.mark.parametrize("pack", [False, True], ids=["bf16", "packed"])
    @pytest.mark.parametrize("which", ["plain", "host_ip", "tiered"])
    def test_the_halves_make_the_whole_leaf_by_leaf(self, which, pack):
        policy, tiers, pods, namespaces, cases = problem(which)
        engine = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
        tensors = engine._tensors_with_cases(cases)
        if which == "host_ip":
            assert "host_ip_match" in tensors["ingress"]
            assert "host_ip_match" in tensors["egress"]
        assert ("tiers" in tensors) == (which == "tiered")
        static = jax.jit(tiled._precompute_static, static_argnames="pack")(
            {k: v for k, v in tensors.items() if not k.startswith("q_")},
            pack=pack,
        )
        # the static holds nothing of the cases, and is what a holder keeps
        assert all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(static))
        halves = jax.jit(tiled._precompute_cases, static_argnames="pack")(
            static, tensors["q_port"], tensors["q_name"], tensors["q_proto"],
            pack=pack,
        )
        whole = jax.jit(tiled._precompute, static_argnames="pack")(
            tensors, pack=pack
        )
        before = jax.jit(precompute_before_the_cut, static_argnames="pack")(
            tensors, pack=pack
        )
        assert_same_tree(halves, whole)
        assert_same_tree(whole, before)
        assert ("tier" in whole["egress"]) == (which == "tiered")
        assert ("tallow_pk" in whole["egress"]) == pack

    def test_other_cases_reuse_one_static(self):
        policy, _, pods, namespaces, _ = problem("plain")
        engine = TpuPolicyEngine(policy, pods, namespaces)
        tensors = engine._tensors_with_cases(A)
        static = tiled._precompute_static(tensors, True)
        for cases in (B, C):
            t = engine._tensors_with_cases(cases)
            assert_same_tree(
                tiled._precompute_cases(
                    static, t["q_port"], t["q_name"], t["q_proto"], True
                ),
                tiled._precompute(t, True),
            )


def modes_and_counts(engine, sequence):
    seen, got = [], []
    for cases in sequence:
        got.append(counts_of(engine.evaluate_grid_counts(cases, backend="pallas")))
        seen.append(recorder.entries()[-1]["mode"])
    return seen, got


def static_outcomes():
    return {
        o: ti.STATIC_PRE.value(outcome=o) for o in ("built", "hit", "declined")
    }


class TestTheHolder:
    @pytest.mark.parametrize(
        "which,pack",
        [
            ("plain", "1"), ("plain", "0"), ("host_ip", "1"), ("host_ip", "0"),
            # the Pallas counts kernel takes tiers under the packed plan only
            ("tiered", "1"),
        ],
    )
    def test_progression_counts_and_one_build(self, which, pack, monkeypatch):
        """A, B, C, A, A, A: four requests that no pin serves run from
        the static, built once; the repeat pins A's precompute as ever."""
        monkeypatch.setenv("CYCLONUS_PACK", pack)
        policy, tiers, pods, namespaces, a = problem(which)
        sets = {"A": list(a), "B": B, "C": C}
        want = {
            k: oracle_counts(policy, tiers, pods, namespaces, v)
            for k, v in sets.items()
        }
        engine = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
        assert engine._static_pre is None  # built when first needed
        before = static_outcomes()
        seen, got = modes_and_counts(engine, [sets[k] for k in "ABCAAA"])
        assert seen == ["resident"] * 4 + ["split", "steady"]
        assert got == [want[k] for k in "ABCAAA"]
        after = static_outcomes()
        assert after["built"] == before["built"] + 1
        assert after["hit"] == before["hit"] + 3
        assert after["declined"] == before["declined"]
        # the fused program, as an engine that may keep nothing runs it
        monkeypatch.setenv("CYCLONUS_PRE_CACHE", "0")
        fused = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
        seen, got = modes_and_counts(fused, [sets[k] for k in "ABC"])
        assert seen == ["fused"] * 3
        assert got == [want[k] for k in "ABC"]

    def test_the_build_is_a_span_and_a_gauge_of_the_real_bytes(self):
        policy, _, pods, namespaces, _ = problem("plain")
        engine = TpuPolicyEngine(policy, pods, namespaces)
        spans.REGISTRY.reset()
        engine.evaluate_grid_counts(A, backend="pallas")
        actual = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(engine._static_pre)
        )
        # what the gate adds up from shapes before anything runs
        assert engine._static_pre_bytes() == actual
        assert ti.STATIC_PRE_BYTES.value() == actual
        # the build is enqueued inside the request's one dispatch span
        built = spans.REGISTRY.tree()[
            "engine.eval/engine.dispatch/engine.static_pre"
        ]
        assert built["count"] == 1 and built["attrs"] == {"bytes": actual}
        kept = engine._static_pre
        spans.REGISTRY.reset()
        engine.evaluate_grid_counts(B, backend="pallas")
        assert engine._static_pre is kept
        tree = spans.REGISTRY.tree()
        assert not [path for path in tree if path.endswith("engine.static_pre")]
        mode = tree["engine.eval"]["attrs"]["mode"]
        assert mode == "resident" == recorder.entries()[-1]["mode"]

    def test_tiered_static_bytes_are_counted_from_shapes(self):
        policy, tiers, pods, namespaces, cases = problem("tiered")
        engine = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
        engine.evaluate_grid_counts(cases, backend="pallas")
        assert engine._static_pre_bytes() == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(engine._static_pre)
        )

    @pytest.mark.parametrize("how", ["ceiling", "opt_out"])
    def test_declined_runs_the_fused_program(self, how, monkeypatch):
        policy, _, pods, namespaces, _ = problem("plain")
        sets = {"A": A, "B": B}
        want = {
            k: oracle_counts(policy, None, pods, namespaces, v)
            for k, v in sets.items()
        }
        engine = TpuPolicyEngine(policy, pods, namespaces)
        if how == "ceiling":
            # one byte under what the static alone would hold
            monkeypatch.setattr(
                api, "_PRE_CACHE_MAX_BYTES", engine._static_pre_bytes() - 1
            )
        else:
            monkeypatch.setenv("CYCLONUS_PRE_CACHE", "0")
        # a repeat under the ceiling would still pin its precompute, as
        # ever (the ceiling holds less than the static, not nothing)
        order = "ABAB" if how == "ceiling" else "ABAA"
        before = static_outcomes()
        seen, got = modes_and_counts(engine, [sets[k] for k in order])
        assert seen == ["fused"] * 4
        assert got == [want[k] for k in order]
        assert engine._static_pre is None and engine._pre_cache is None
        after = static_outcomes()
        assert after["declined"] == before["declined"] + 4
        assert after["built"] == before["built"]
        assert after["hit"] == before["hit"]

    def test_a_patched_buffer_gets_a_static_of_its_own(self):
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", f"p{i}", {"app": APPS[i % 2]}, f"10.0.0.{i + 1}")
            for i in range(8)
        ]
        svc = VerdictService(
            pods, namespaces, [mk_policy("pol0", "x", random.Random(3))],
            class_compress="0",
        )

        def oracle(cases):
            return oracle_counts(
                svc._policy, None, svc.pods.values(), dict(svc.namespaces),
                cases,
            )

        def counts(cases):
            got = svc.engine.evaluate_grid_counts(cases, backend="pallas")
            return counts_of(got), recorder.entries()[-1]["mode"]

        before = oracle(A)
        assert counts(A) == (before, "resident")
        stale = svc.engine._static_pre
        assert stale is not None
        built = ti.STATIC_PRE.value(outcome="built")
        r = svc.apply([
            Delta(kind="pod_labels", namespace="x", name="p4",
                  labels={"app": "a1"}),
        ])
        assert r["mode"] == "incremental", r
        assert svc.engine._static_pre is None
        assert ti.STATIC_PRE_BYTES.value() == 0
        after = oracle(A)
        assert after != before  # or a stale static would go unnoticed
        assert counts(A) == (after, "resident")
        assert svc.engine._static_pre is not stale
        assert ti.STATIC_PRE.value(outcome="built") == built + 1
        assert counts(B) == (oracle(B), "resident")
