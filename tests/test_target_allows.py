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

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, tiled
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
