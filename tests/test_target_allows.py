"""The target-allows contraction with the port cases folded into the
one-hot (kernel.target_allows_q, traced by tiled._precompute_cases):

  * `_precompute_cases` hands back the tree of the unfolded form, which
    built `peer_allow [P, N, Q]` and contracted `[T, P] x [P, N * Q]`,
    leaf for leaf and bit for bit: Q of 1, 2 and 3, packed and not, both
    tile forms, tiered and untiered, with peer rows whose port spec
    allows some cases and not others; and `_precompute` still equals
    the function as it stood before the cut (test_static_pre.py);
  * `target_allows_q` equals the elementwise definition.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cyclonus_tpu.engine.api as api
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, kernel, tiled
from cyclonus_tpu.engine.encoding import PACK_BITS, packed_words
from cyclonus_tpu.engine.kernel import (
    m_tp_onehot,
    pack_bool_words_jnp,
    port_spec_allows,
    target_allows_q,
    tier_direction_arrays,
)
from cyclonus_tpu.kube.netpol import (
    IntOrString,
    IPBlock,
    LabelSelector,
    NetworkPolicyEgressRule,
    NetworkPolicyIngressRule,
    NetworkPolicyPeer,
    NetworkPolicyPort,
)
from cyclonus_tpu.matcher import build_network_policies

from test_engine_parity import default_cluster, mkpol
from test_engine_tiled import fuzz_problem
from test_static_pre import assert_same_tree, precompute_before_the_cut, problem

CASES = {
    1: [PortCase(80, "serve-80-tcp", "TCP")],
    2: [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "serve-81-udp", "UDP")],
    3: [
        PortCase(81, "serve-81-udp", "UDP"),
        PortCase(9999, "", "TCP"),
        PortCase(80, "serve-80-tcp", "SCTP"),
    ],
}


def precompute_cases_unfolded(static, q_port, q_name, q_proto, pack, tile):
    """`tiled._precompute_cases` as it stood before the port cases were
    folded into the one-hot: peer_allow [P, N * Q] (or [P, Q * N] for
    the counts tile's egress), one matmul, the result relaid."""
    out = {}
    q = q_port.shape[0]
    qn = tile == tiled.TILE_BQN and tiled.counts_tile_form(static) == tiled.TILE_BQN
    for direction in ("ingress", "egress"):
        st = static[direction]
        pport = port_spec_allows(st["port_spec"], q_port, q_name, q_proto)
        n_p, n = st["peer_match"].shape
        if qn and direction == "egress":
            peer_allow = (
                st["peer_match"][:, None, :] & pport[:, :, None]
            ).reshape(n_p, q * n)
        else:
            peer_allow = (
                st["peer_match"][:, :, None] & pport[:, None, :]
            ).reshape(n_p, n * q)
        tallow = jnp.matmul(
            m_tp_onehot(st).astype(jnp.bfloat16),
            peer_allow.astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16,
        )
        t = tallow.shape[0]
        lay = (t, q, n) if qn and direction == "egress" else (t, n, q)
        out[direction] = {"tmatch": st["tmatch"], "has_target": st["has_target"]}
        if pack:
            out[direction]["tallow_pk"] = pack_bool_words_jnp(
                (tallow > 0).reshape(lay)
            )
            out[direction]["tmatch_pk"] = st["tmatch_pk"]
        else:
            out[direction]["tallow_bf"] = (
                (tallow > 0).astype(jnp.bfloat16).reshape(lay)
            )
        if "tiers" in static:
            tiers = static["tiers"]
            out[direction]["tier"] = tier_direction_arrays(
                tiers["enc"][direction], tiers["selpod"], tiers["selns"],
                tiers["pod_ns_id"], q_port, q_name, q_proto,
            )
    return out


def port(protocol, value):
    return NetworkPolicyPort(protocol=protocol, port=IntOrString(value))


def ports_problem():
    """Rules whose port specs allow one case of a set and not the next:
    80/TCP alone, the named UDP port alone, an 80 - 90 TCP range, beside
    a rule with no ports, over pod and ipBlock peers."""
    pods, namespaces = default_cluster()
    pod_peer = NetworkPolicyPeer(pod_selector=LabelSelector.make({"pod": "a"}))
    ip_peer = NetworkPolicyPeer(ip_block=IPBlock.make("192.168.1.0/28", []))
    ingress = mkpol(
        "in", "x", LabelSelector.make(), ["Ingress"],
        ingress=[
            NetworkPolicyIngressRule(ports=[port("TCP", 80)], from_=[pod_peer]),
            NetworkPolicyIngressRule(
                ports=[port("UDP", "serve-81-udp")], from_=[ip_peer]
            ),
        ],
    )
    egress = mkpol(
        "eg", "y", LabelSelector.make({"pod": "b"}), ["Egress"],
        egress=[
            NetworkPolicyEgressRule(
                ports=[
                    NetworkPolicyPort(
                        protocol="TCP", port=IntOrString(80), end_port=90
                    )
                ],
                to=[ip_peer],
            ),
            NetworkPolicyEgressRule(ports=[], to=[pod_peer]),
        ],
    )
    return build_network_policies(True, [ingress, egress]), None, pods, namespaces


def tensors_of(which, q):
    """A fuzzed set with peer rows in both directions and port specs
    that differ by case (seed 29), test_static_pre's tiered and IPv6
    sets, or ports_problem."""
    if which == "ports":
        policy, tiers, pods, namespaces = ports_problem()
    elif which == "fuzz":
        tiers = None
        policy, pods, namespaces = fuzz_problem(29, n_extra_pods=9)
    else:
        policy, tiers, pods, namespaces, _ = problem(which)
    engine = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
    return engine._tensors_with_cases(CASES[q])


def static_of(tensors, pack):
    return jax.jit(tiled._precompute_static, static_argnames="pack")(
        {k: v for k, v in tensors.items() if not k.startswith("q_")}, pack=pack
    )


def mixed_rows(tensors, static):
    """Peer rows, over both directions, whose port spec allows some of
    the cases and not all."""
    n = 0
    for direction in ("ingress", "egress"):
        pport = np.asarray(
            port_spec_allows(
                static[direction]["port_spec"], tensors["q_port"],
                tensors["q_name"], tensors["q_proto"],
            )
        )
        n += int((pport.any(axis=1) & ~pport.all(axis=1)).sum())
    return n


@pytest.mark.parametrize("which", ["fuzz", "tiered", "ports"])
@pytest.mark.parametrize("tile", [tiled.TILE_BNQ, tiled.TILE_BQN])
@pytest.mark.parametrize("pack", [False, True], ids=["bf16", "packed"])
@pytest.mark.parametrize("q", [1, 2, 3])
def test_folded_cases_make_the_unfolded_tree(q, pack, tile, which):
    tensors = tensors_of(which, q)
    assert ("tiers" in tensors) == (which == "tiered")
    static = static_of(tensors, pack)
    if which in ("fuzz", "ports") and q > 1:
        # a row whose port spec is false for some case only: the fold
        # masks the one-hot per case, and must not leak across cases
        assert mixed_rows(tensors, static) > 0
    args = (static, tensors["q_port"], tensors["q_name"], tensors["q_proto"])
    got = jax.jit(tiled._precompute_cases, static_argnames=("pack", "tile"))(
        *args, pack=pack, tile=tile
    )
    want = jax.jit(precompute_cases_unfolded, static_argnames=("pack", "tile"))(
        *args, pack=pack, tile=tile
    )
    assert_same_tree(got, want)
    if tile == tiled.TILE_BNQ:
        before = jax.jit(precompute_before_the_cut, static_argnames="pack")(
            tensors, pack=pack
        )
        assert_same_tree(got, before)


@pytest.mark.parametrize("which", ["fuzz", "tiered", "host_ip", "ports"])
@pytest.mark.parametrize("q", [1, 2, 3])
def test_target_allows_q_is_the_elementwise_definition(q, which):
    tensors = tensors_of(which, q)
    static = static_of(tensors, False)
    allowed = 0
    for direction in ("ingress", "egress"):
        st = static[direction]
        pport = port_spec_allows(
            st["port_spec"], tensors["q_port"], tensors["q_name"],
            tensors["q_proto"],
        )
        target = np.asarray(st["peer_target"])
        peer = np.asarray(st["peer_match"])
        ports = np.asarray(pport)
        t = st["target_ns"].shape[0]
        want = np.zeros((q, t, peer.shape[1]), bool)
        for p in range(peer.shape[0]):
            if 0 <= target[p] < t:
                want[:, target[p]] |= ports[p][:, None] & peer[p][None, :]
        got = np.asarray(jax.jit(target_allows_q)(st, st["peer_match"], pport))
        assert got.dtype == np.bool_ and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
        # rows past T (a packer's 32-row words) match no peer, the -1
        # pad rows included
        assert (target < 0).any()
        rows = packed_words(t) * PACK_BITS + PACK_BITS
        padded = np.asarray(
            jax.jit(target_allows_q, static_argnums=3)(
                st, st["peer_match"], pport, rows
            )
        )
        assert padded.shape == (q, rows, peer.shape[1])
        np.testing.assert_array_equal(padded[:, :t], want)
        assert not padded[:, t:].any()
        allowed += int(want.sum())
    assert allowed  # the cluster allows something


# --- the banded form: each tile of kernel.TARGET_BAND targets against its
# own peer rows (kernel.band_rows, laid by api._band_tensors) ------------


def shuffled_peers(tensors, seed):
    """`tensors` with each direction's peer rows in a random order (a
    verdict reads no order of them): rows out of target order, as
    compress_rule_axes may leave them."""
    out = dict(tensors)
    for direction in ("ingress", "egress"):
        enc = dict(tensors[direction])
        perm = np.random.default_rng(seed).permutation(enc["peer_target"].shape[0])
        for k in api._DIRECTION_PADS:
            if k in enc and not k.startswith("target_"):
                enc[k] = np.asarray(enc[k])[perm]
        enc["port_spec"] = {
            k: np.asarray(v)[perm] for k, v in enc["port_spec"].items()
        }
        out[direction] = enc
    return out


def banded(tensors):
    """`tensors` with each direction's band plan laid, whatever the rule
    (kernel.band_engages) would say of these shapes."""
    out = dict(tensors)
    for direction in ("ingress", "egress"):
        enc = dict(tensors[direction])
        enc["band_rows"] = kernel.band_rows(
            enc["peer_target"], enc["target_ns"].shape[0]
        )
        out[direction] = enc
    return out


#: (tile, pack): the counts tile's two layouts packed and not, and the
#: class tile's MXU form, which is unpacked alone
FORMS = [
    (tiled.TILE_BNQ, False), (tiled.TILE_BNQ, True),
    (tiled.TILE_BQN, False), (tiled.TILE_BQN, True),
    (tiled.TILE_QTN, False),
]


def cases_tree(static, tensors, pack, tile):
    args = (static, tensors["q_port"], tensors["q_name"], tensors["q_proto"])
    return jax.jit(tiled._precompute_cases, static_argnames=("pack", "tile"))(
        *args, pack=pack, tile=tile
    )


@pytest.mark.parametrize("which", ["fuzz", "tiered", "ports"])
@pytest.mark.parametrize("tile,pack", FORMS, ids=[f"{t}-{p}" for t, p in FORMS])
@pytest.mark.parametrize("q", [1, 2, 3])
def test_band_form_makes_the_dense_tree(q, tile, pack, which):
    tensors = tensors_of(which, q)
    if which == "fuzz":
        tensors = shuffled_peers(tensors, q)
        pt = tensors["ingress"]["peer_target"]
        assert (np.diff(pt[pt >= 0]) < 0).any()
    static = static_of(banded(tensors), pack)
    for direction in ("ingress", "egress"):
        band = static[direction]["band_rows"]
        assert static[direction]["peer_match"].shape[:2] == band.shape
    assert_same_tree(
        cases_tree(static, tensors, pack, tile),
        cases_tree(static_of(tensors, pack), tensors, pack, tile),
    )


def wide_static(seed, t=200, n=96, pack=False):
    """A direction's _precompute_static result, laid on the host: 260 rows
    over the targets, 300 rows of ONE target (a span far past the rest),
    40 pad rows (-1), in random order; T = 200, two tiles, not a multiple
    of TARGET_BAND.  Returns (dense static, banded static)."""
    from cyclonus_tpu.engine.encoding import PORT_INT

    rng = np.random.default_rng(seed)
    pt = np.concatenate([
        rng.integers(0, t, 260), np.full(300, 77), np.full(40, -1)
    ]).astype(np.int32)
    rng.shuffle(pt)
    p = pt.size
    tmatch = rng.random((t, n)) < 0.05
    enc = {
        "peer_match": jnp.asarray(rng.random((p, n)) < 0.2),
        "tmatch": jnp.asarray(tmatch),
        "has_target": jnp.asarray(tmatch.any(axis=0)),
        "peer_target": jnp.asarray(pt),
        "target_ns": jnp.zeros((t,), jnp.int32),
        "port_spec": {
            "spec_all": jnp.asarray(rng.random(p) < 0.25),
            "item_kind": jnp.full((p, 1), PORT_INT, jnp.int32),
            "item_port": jnp.asarray(rng.integers(80, 82, (p, 1)), jnp.int32),
            "item_name": jnp.full((p, 1), -1, jnp.int32),
            "item_proto": jnp.asarray(rng.integers(0, 2, (p, 1)), jnp.int32),
            "rng_from": jnp.zeros((p, 1), jnp.int32),
            "rng_to": jnp.full((p, 1), -1, jnp.int32),
            "rng_proto": jnp.zeros((p, 1), jnp.int32),
        },
    }
    if pack:
        enc["tmatch_pk"] = pack_bool_words_jnp(enc["tmatch"])
    band = kernel.band_rows(pt, t)
    wide = dict(enc)
    wide["band_rows"] = jnp.asarray(band)
    wide["peer_match"] = kernel.band_peer_match(wide["band_rows"], enc["peer_match"])
    return {"ingress": enc, "egress": enc}, {"ingress": wide, "egress": wide}


WIDE_CASES = {
    q: {
        "q_port": jnp.array([80, 81, 80][:q], jnp.int32),
        "q_name": jnp.array([-1, -1, -1][:q], jnp.int32),
        "q_proto": jnp.array([0, 1, 1][:q], jnp.int32),
    }
    for q in (1, 2, 3)
}


@pytest.mark.parametrize("tile,pack", FORMS, ids=[f"{t}-{p}" for t, p in FORMS])
@pytest.mark.parametrize("q", [1, 2, 3])
def test_band_form_over_a_wide_span_makes_the_dense_tree(q, tile, pack):
    dense, band = wide_static(q, pack=pack)
    b = band["ingress"]["band_rows"]
    # two tiles; the 300-row target's tile sets the span
    assert b.shape[0] == 2 and b.shape[1] >= 384
    assert (np.asarray(b) < 0).any()
    assert_same_tree(
        cases_tree(band, WIDE_CASES[q], pack, tile),
        cases_tree(dense, WIDE_CASES[q], pack, tile),
    )


@pytest.mark.parametrize("q", [1, 2, 3])
def test_band_form_is_the_elementwise_definition(q):
    dense, band = wide_static(10 + q)
    st, wide = dense["ingress"], band["ingress"]
    c = WIDE_CASES[q]
    pport = port_spec_allows(st["port_spec"], c["q_port"], c["q_name"], c["q_proto"])
    target = np.asarray(st["peer_target"])
    peer, ports = np.asarray(st["peer_match"]), np.asarray(pport)
    t = st["target_ns"].shape[0]
    want = np.zeros((q, t, peer.shape[1]), bool)
    for p in range(peer.shape[0]):
        if target[p] >= 0:
            want[:, target[p]] |= ports[p][:, None] & peer[p][None, :]
    fn = jax.jit(target_allows_q, static_argnums=3)
    np.testing.assert_array_equal(np.asarray(fn(wide, wide["peer_match"], pport)), want)
    # rows to whole words, and past the tiles: false rows
    for rows in (packed_words(t) * PACK_BITS, 2 * kernel.TARGET_BAND + PACK_BITS):
        got = np.asarray(fn(wide, wide["peer_match"], pport, rows))
        assert got.shape == (q, rows, peer.shape[1])
        np.testing.assert_array_equal(got[:, :t], want)
        assert not got[:, t:].any()
    assert want.any()


def draw_targets(seed, t, p, most):
    """peer_target [p]: each target's rows drawn Poisson about 0.9 p / t,
    held to 1 - `most`, shuffled, the rest -1 pads (bucketing's)."""
    rng = np.random.default_rng(seed)
    counts = np.clip(rng.poisson(0.9 * p / t, t), 1, most)
    rows = np.repeat(np.arange(t), counts)[:p]
    pt = np.full(p, -1, np.int32)
    pt[: rows.size] = rows
    rng.shuffle(pt)
    return pt


@pytest.mark.parametrize(
    "t,p,most,engages",
    [
        (16_511, 18_048, 2, True),  # the envelope's ingress
        (16_511, 14_848, 4, True),  # its egress
        (895, 13_056, 57, True),  # cidr-10k-5k
        (383, 512, 7, False),  # mesh-100k-10k
        (31, 64, 3, False),  # tables-10k-1k
    ],
)
def test_band_rule_reads_the_shapes(t, p, most, engages):
    pt = draw_targets(t, t, p, most)
    band = kernel.band_rows(pt, t)
    tiles, span = band.shape
    assert tiles == -(-t // kernel.TARGET_BAND) and span % 128 == 0
    # every real row lies once, in its target's tile
    real = band[band >= 0]
    assert sorted(real) == sorted(np.flatnonzero(pt >= 0))
    for k in range(tiles):
        rows = band[k][band[k] >= 0]
        assert (pt[rows] // kernel.TARGET_BAND == k).all()
        assert (np.diff(pt[rows]) >= 0).all()
    assert kernel.band_engages(band, t, p) is engages
    enc = {"target_ns": np.zeros(t, np.int32), "peer_target": pt}
    laid = api._band_tensors({"ingress": enc, "egress": enc})
    assert ("band_rows" in laid["ingress"]) is engages
    assert kernel.target_allows_form(laid) == (
        kernel.TARGET_ALLOWS_BAND if engages else kernel.TARGET_ALLOWS
    )
    # the serve reservation steps the span a granule a step
    assert kernel.band_rows(pt, t, headroom=1).shape == (tiles, span + 128)


def many_targets_problem(n=600):
    """n pods in one namespace and n ingress policies, policy i over pod i
    (app i) admitting pod i (tier i) on 80/TCP: n targets of one peer row
    each.  At 600: T' 639, P 640, five tiles of span 128, so the band
    engages in ingress (egress has no targets: dense)."""
    from test_serve_headroom import mkpol as one_target_policy

    pods = [
        ("x", f"pod-{i}", {"app": f"app{i}", "tier": f"tier{i}"},
         f"10.0.{i // 250}.{i % 250 + 1}")
        for i in range(n)
    ]
    policies = [one_target_policy(i) for i in range(n)]
    return build_network_policies(True, policies), pods, {"x": {"ns": "x"}}


class TestBandEngine:
    def test_counts_plan_and_flight_where_the_rule_engages(self, monkeypatch):
        from cyclonus_tpu.telemetry import instruments as ti

        from cyclonus_tpu.telemetry import spans

        policy, pods, namespaces = many_targets_problem()
        spans.REGISTRY.reset()
        engine = TpuPolicyEngine(policy, pods, namespaces)
        band = engine._tensors["ingress"]["band_rows"]
        assert band.shape == (5, 128) and "band_rows" not in engine._tensors["egress"]
        (plan,) = [
            rec["attrs"] for path, rec in spans.REGISTRY.tree().items()
            if path.endswith("engine.class_tensors")
        ]
        # the constructor's span says what engine.eval will (tallow_attrs)
        assert plan == kernel.tallow_attrs(engine._tensors)
        assert plan == {
            "tallow": "band,dense",
            "tallow_span": 128 + engine._tensors["egress"]["peer_target"].shape[0],
        }
        assert engine._aot_plan().endswith(f";{kernel.TARGET_ALLOWS_BAND}")
        before = {f: ti.TARGET_ALLOWS.value(form=f) for f in ("band", "dense")}
        counts = engine.evaluate_grid_counts(CASES[2])
        # pod i admits pod i alone, on 80/TCP alone; nothing limits egress
        n = len(pods)
        assert counts == {
            "ingress": n, "egress": 2 * n * n, "combined": n, "cells": 2 * n * n,
        }
        assert {
            f: ti.TARGET_ALLOWS.value(form=f) - before[f] for f in before
        } == {"band": 1, "dense": 1}
        monkeypatch.setattr(kernel, "band_engages", lambda *a: False)
        dense = TpuPolicyEngine(policy, pods, namespaces)
        assert "band_rows" not in dense._tensors["ingress"]
        assert dense._aot_plan().endswith(f";{kernel.TARGET_ALLOWS}")
        assert dense.evaluate_grid_counts(CASES[2]) == counts

    def test_serve_delta_that_adds_peer_rows_lays_a_new_plan(self, monkeypatch):
        """A policy upsert that gives one target two more peer rows patches
        the live engine on the incremental path with a band plan laid
        anew (forced here: the rule is the shapes' at this size), and the
        patched engine counts what a fresh one counts."""
        from test_serve_headroom import boundary_cluster, mkpol as one_target_policy

        from cyclonus_tpu.kube.netpol import NetworkPolicyPeer as Peer
        from cyclonus_tpu.kube.yaml_io import policy_to_dict
        from cyclonus_tpu.serve import VerdictService
        from cyclonus_tpu.worker.model import Delta

        monkeypatch.delenv("CYCLONUS_SERVE_HEADROOM", raising=False)
        monkeypatch.setattr(kernel, "band_engages", lambda *a: True)
        pods, namespaces, policies = boundary_cluster()
        svc = VerdictService(pods, namespaces, policies)
        before = np.array(svc.engine._tensors["ingress"]["band_rows"])
        changed = one_target_policy(3)
        changed.spec.ingress[0].from_ += [
            Peer(pod_selector=LabelSelector.make({"tier": f"tier{j}"}))
            for j in (5, 7)
        ]
        report = svc.apply([
            Delta(kind="policy_upsert", namespace="x", name="p3",
                  policy=policy_to_dict(changed)),
        ])
        assert report["mode"] in ("incremental", "class_rebuild"), report
        after = np.array(svc.engine._tensors["ingress"]["band_rows"])
        assert after.shape == before.shape
        assert (after >= 0).sum() == (before >= 0).sum() + 2
        fresh = TpuPolicyEngine(
            svc._policy, list(svc.pods.values()), dict(svc.namespaces)
        )
        pt = svc.engine._tensors["ingress"]["peer_target"]
        assert sorted(after[after >= 0]) == sorted(np.flatnonzero(pt >= 0))
        for q in (1, 2):
            assert svc.engine.evaluate_grid_counts(CASES[q]) == (
                fresh.evaluate_grid_counts(CASES[q])
            )
        svc.verify_parity(oracle_samples=8)

    def test_the_resident_static_holds_the_band(self):
        """The dense counts route's resident static (`counts.static`, the
        Pallas route `cidr-10k-5k` runs) keeps peer_match laid to the band,
        its bytes counted from the shapes before it exists, and the
        requests it serves count what the dense form counts."""
        import jax

        policy, pods, namespaces = many_targets_problem()
        engine = TpuPolicyEngine(policy, pods, namespaces)
        n = len(pods)
        want = {
            1: {"ingress": n, "egress": n * n, "combined": n, "cells": n * n},
            2: {"ingress": n, "egress": 2 * n * n, "combined": n, "cells": 2 * n * n},
            3: {"ingress": 0, "egress": 3 * n * n, "combined": 0, "cells": 3 * n * n},
        }
        for q in (2, 1, 3):
            assert engine.evaluate_grid_counts(CASES[q], backend="pallas") == want[q]
        static = engine._static_pre
        assert static["ingress"]["peer_match"].shape[:2] == (5, 128)
        assert static["egress"]["peer_match"].ndim == 2
        assert engine._static_pre_bytes() == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(static)
        )
