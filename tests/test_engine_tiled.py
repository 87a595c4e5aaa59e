"""Parity gate for the tiled/streaming evaluation paths (engine/tiled.py):
counts, streamed blocks, and point-pair verdicts must agree exactly with
the single-device kernel (itself oracle-checked by test_engine_parity.py),
across fuzzed policy sets, odd block sizes (pad rows in play), and the
IPv6 host-fallback path.
"""

import random

import numpy as np
import pytest

import cyclonus_tpu.engine.api as api
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.matcher import build_network_policies

from test_engine_parity import (
    default_cluster,
    mkpol,
    oracle_grid,
    random_policy,
)
from cyclonus_tpu.kube.netpol import (
    IPBlock,
    LabelSelector,
    NetworkPolicyIngressRule,
    NetworkPolicyPeer,
)

CASES = [
    PortCase(80, "serve-80-tcp", "TCP"),
    PortCase(81, "serve-81-udp", "UDP"),
]


def fuzz_problem(seed, n_extra_pods=0):
    rng = random.Random(seed)
    nss = ["x", "y", "z"]
    keys = ["pod", "app", "ns", "team"]
    values = ["a", "b", "c", "x", "y", "z", "blue", "red"]
    pods, namespaces = default_cluster()
    # fuzzed team labels layer ON TOP of the defaults so namespace-selector
    # peers on "team" genuinely discriminate
    for ns in nss:
        namespaces[ns] = {"ns": ns, "team": rng.choice(["blue", "red"])}
    for i in range(n_extra_pods):
        ns = rng.choice(nss)
        pods.append(
            (ns, f"extra-{i}", {"app": rng.choice(values)}, f"192.168.2.{i + 1}")
        )
    policies = [
        random_policy(rng, i, nss, keys, values)
        for i in range(rng.randrange(2, 6))
    ]
    return build_network_policies(True, policies), pods, namespaces


def full_grids(engine, cases):
    g = engine.evaluate_grid(cases)
    return (
        np.asarray(g.ingress),
        np.asarray(g.egress),
        np.asarray(g.combined),
    )


class TestTiledCounts:
    @pytest.mark.parametrize("seed,block", [(0, 4), (1, 5), (2, 16), (3, 64)])
    def test_counts_match_kernel(self, seed, block):
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=7)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)
        counts = engine.evaluate_grid_counts(CASES, block=block, backend="xla")
        assert counts["ingress"] == int(ing.sum())
        assert counts["egress"] == int(egr.sum())
        assert counts["combined"] == int(comb.sum())
        assert counts["cells"] == ing.size

    def test_counts_empty(self):
        policy, pods, namespaces = fuzz_problem(0)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        assert engine.evaluate_grid_counts([]) == {
            "ingress": 0,
            "egress": 0,
            "combined": 0,
            "cells": 0,
        }

    @pytest.mark.parametrize(
        "seed,block", [(20, 2), (21, 8), (22, 2), (23, 8)]
    )
    def test_counts_ring_match_kernel(self, seed, block, monkeypatch):
        """Ring-rotation counts (both axes sharded, ppermute per step):
        the mesh counts entry on its ring route must equal the
        single-device kernel's sums."""
        monkeypatch.setattr(api, "_MESH_REPLICATED_MAX_BYTES", 0)
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=13)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)
        counts = engine.evaluate_grid_counts_sharded(CASES, block=block)
        assert counts["ingress"] == int(ing.sum())
        assert counts["egress"] == int(egr.sum())
        assert counts["combined"] == int(comb.sum())

    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_ring_per_device_shard_shapes(self, n_dev, monkeypatch):
        """The ring path's O(N / n_dev) per-device memory claim, asserted
        structurally: inside the shard_map'd per-device function, the pod
        arrays must arrive with exactly n_padded / n_dev rows.  Recorded
        by intercepting the per-device _precompute_static while the held
        ring's `counts.mesh.static` traces (the AOT cache off, so that no
        adopted executable skips the trace)."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        import cyclonus_tpu.engine.tiled as tiled

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", "0")
        monkeypatch.setattr(api, "_MESH_REPLICATED_MAX_BYTES", 0)
        policy, pods, namespaces = fuzz_problem(25, n_extra_pods=13)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        cpu = jax.devices("cpu")
        if len(cpu) < n_dev:
            pytest.skip(f"needs {n_dev} CPU devices, have {len(cpu)}")
        mesh = Mesh(np.array(cpu[:n_dev]), ("x",))

        seen = []
        real_precompute = tiled._precompute_static

        def recording_precompute(tensors, *args, **kwargs):
            seen.append(int(tensors["pod_kv"].shape[0]))
            return real_precompute(tensors, *args, **kwargs)

        monkeypatch.setattr(tiled, "_precompute_static", recording_precompute)
        block = 4
        want = engine.evaluate_grid_counts(CASES, block=block, backend="xla")
        seen.clear()  # only the ring's trace is asserted
        got = engine.evaluate_grid_counts_sharded(
            CASES, block=block, mesh=mesh
        )
        assert got == want
        # ring path: per-device pod rows = n_padded / n_dev exactly.
        # The engine's tensors arrive shape-BUCKETED (api._bucket_pods),
        # so mesh padding starts from the bucketed axis, not n_pods.
        n_bucketed = engine._tensors["pod_ns_id"].shape[0]
        # mirror _counts_mesh's block clamp: the engine shrinks the tile
        # height so every device gets at least one tile
        n = engine.encoding.cluster.n_pods
        block_eff = min(block, max(n // n_dev, 1))
        granule = n_dev * block_eff
        n_padded = -(-n_bucketed // granule) * granule
        assert seen, "ring path never traced (unexpected jit cache hit)"
        assert seen[-1] == n_padded // n_dev
        assert seen[-1] < n_bucketed  # strictly smaller than the full axis

    def test_counts_ring_ipv6_host_rows(self, monkeypatch):
        """host_ip_match rows are pod-axis sharded in the ring path — on
        BOTH sides: the ingress policy patches the local (peer) view, the
        egress policy's patched rows are baked into the tallow bundle
        that rotates around the ring."""
        from cyclonus_tpu.kube.netpol import (
            IPBlock,
            LabelSelector,
            NetworkPolicyEgressRule,
            NetworkPolicyIngressRule,
            NetworkPolicyPeer,
        )
        from cyclonus_tpu.matcher import build_network_policies
        from test_engine_parity import default_cluster, mkpol

        monkeypatch.setattr(api, "_MESH_REPLICATED_MAX_BYTES", 0)
        pods, namespaces = default_cluster()
        pods = [
            (ns, name, labels, ip if i % 2 else f"2001:db8::{i + 1}")
            for i, (ns, name, labels, ip) in enumerate(pods)
        ]
        v6_peer = NetworkPolicyPeer(ip_block=IPBlock.make("2001:db8::/112", []))
        pol_i = mkpol(
            "v6-in",
            "x",
            LabelSelector.make(),
            ["Ingress"],
            ingress=[NetworkPolicyIngressRule(ports=[], from_=[v6_peer])],
        )
        pol_e = mkpol(
            "v6-eg",
            "y",
            LabelSelector.make(),
            ["Egress"],
            egress=[NetworkPolicyEgressRule(ports=[], to=[v6_peer])],
        )
        policy = build_network_policies(True, [pol_i, pol_e])
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)
        counts = engine.evaluate_grid_counts_sharded(CASES, block=2)
        assert counts["combined"] == int(comb.sum())
        assert counts["ingress"] == int(ing.sum())

    @pytest.mark.parametrize("seed,block", [(7, 2), (8, 16)])
    def test_counts_sharded_match_kernel(self, seed, block):
        """Mesh-parallel counts over the virtual multi-device mesh must
        equal the single-device kernel's sums (pad rows per device)."""
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=11)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)
        counts = engine.evaluate_grid_counts_sharded(CASES, block=block)
        assert counts["ingress"] == int(ing.sum())
        assert counts["egress"] == int(egr.sum())
        assert counts["combined"] == int(comb.sum())

    @pytest.mark.parametrize("seed,block", [(9, 2), (10, 8)])
    def test_counts_sharded_pallas_kernel(self, seed, block):
        """The production multi-chip FAST path: kernel="pallas" forces
        the fused rectangular verdict+count kernel per device (interpret
        mode on the CPU mesh, Mosaic-compiled on TPU) — pinned against
        the single-device kernel exactly like the xla tile loop, and
        against the xla mesh path's full result dict."""
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=11)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)
        counts = engine.evaluate_grid_counts_sharded(
            CASES, block=block, kernel="pallas"
        )
        assert counts["ingress"] == int(ing.sum())
        assert counts["egress"] == int(egr.sum())
        assert counts["combined"] == int(comb.sum())
        assert counts == engine.evaluate_grid_counts_sharded(
            CASES, block=block, kernel="xla"
        )


class TestTiledBlocks:
    # (7, 3): 14 pods bucket to a 16-row pod axis — a block size that
    # doesn't divide it used to yield pad rows mislabeled as real rows
    @pytest.mark.parametrize("seed,block", [(4, 4), (5, 7), (6, 32), (7, 3)])
    def test_blocks_match_kernel(self, seed, block):
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=5)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)  # [Q, N, N]
        n = len(pods)
        seen = 0
        for start, b_ing, b_egr, b_comb in engine.iter_grid_blocks(
            CASES, block=block
        ):
            b = b_egr.shape[0]
            # block layout: [b, N, Q]; full-grid: ingress [Q, dst, src],
            # egress/combined [Q, src, dst]
            np.testing.assert_array_equal(
                b_egr, np.moveaxis(egr[:, start : start + b, :], 0, -1)
            )
            np.testing.assert_array_equal(
                b_comb, np.moveaxis(comb[:, start : start + b, :], 0, -1)
            )
            np.testing.assert_array_equal(
                b_ing,
                np.moveaxis(ing[:, :, start : start + b], 0, -1).transpose(
                    1, 0, 2
                ),
            )
            seen += b
        assert seen == n


class TestPairs:
    @pytest.mark.parametrize("seed", range(4))
    def test_pairs_match_oracle(self, seed):
        policy, pods, namespaces = fuzz_problem(100 + seed, n_extra_pods=3)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        rng = random.Random(seed)
        n = len(pods)
        pairs = [(rng.randrange(n), rng.randrange(n)) for _ in range(40)]
        got = engine.evaluate_pairs(CASES, pairs)  # [K, Q, 3]
        expected = oracle_grid(policy, pods, namespaces, CASES)
        for k, (s, d) in enumerate(pairs):
            for qi in range(len(CASES)):
                exp = expected[(qi, s, d)]
                assert tuple(bool(x) for x in got[k, qi]) == exp, (
                    f"pair ({s},{d}) case {qi}: engine="
                    f"{tuple(got[k, qi])} oracle={exp}"
                )

    def test_pairs_ipv6_host_fallback(self):
        # IPv6 IPBlock forces host-evaluated peer rows; the pairs kernel
        # must re-index them by original pod row
        pods, namespaces = default_cluster()
        pods = [
            (ns, name, labels, ip if i % 2 else f"2001:db8::{i + 1}")
            for i, (ns, name, labels, ip) in enumerate(pods)
        ]
        pol = mkpol(
            "v6",
            "x",
            LabelSelector.make(),
            ["Ingress"],
            ingress=[
                NetworkPolicyIngressRule(
                    ports=[],
                    from_=[
                        NetworkPolicyPeer(
                            ip_block=IPBlock.make("2001:db8::/112", [])
                        )
                    ],
                )
            ],
        )
        policy = build_network_policies(True, [pol])
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)
        n = len(pods)
        pairs = [(s, d) for s in range(n) for d in range(n)]
        got = engine.evaluate_pairs(CASES, pairs)
        for k, (s, d) in enumerate(pairs):
            for qi in range(len(CASES)):
                assert bool(got[k, qi, 0]) == bool(ing[qi, d, s])
                assert bool(got[k, qi, 1]) == bool(egr[qi, s, d])
                assert bool(got[k, qi, 2]) == bool(comb[qi, s, d])
