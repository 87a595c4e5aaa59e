"""The overlapped ring-exchange mesh path (engine/sharded.py): parity
against the single-device kernel and the all-gather reference schedule
at 1/2/4/8 virtual devices (uneven pod/device divisions included), the
tiered and class-compressed routes, the peer-buffer HBM watermark claim
(ring < allgather), the mesh counts entry's ring route on a tiered
engine, and the zero-recompile elastic-resize contract (same-bucket
cluster resizes reuse every compiled sharded program)."""

import random

import numpy as np
import pytest
from jax.sharding import Mesh

from cyclonus_tpu.engine import TpuPolicyEngine
from cyclonus_tpu.engine import sharded as sharded_mod
from cyclonus_tpu.engine.api import _bucket_down, _bucket_pods, _bucket_up
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.telemetry import instruments as ti

from test_engine_tiled import CASES, fuzz_problem


def cpu_mesh(n_dev):
    import jax

    cpu = jax.devices("cpu")
    if len(cpu) < n_dev:
        pytest.skip(f"needs {n_dev} CPU devices, have {len(cpu)}")
    return Mesh(np.array(cpu[:n_dev]), ("x",))


def grids_equal(a, b):
    return all(
        np.array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        )
        for name in ("ingress", "egress", "combined")
    )


def synthetic_engine(n_pods, n_pols=6, seed=3, **kw):
    from cyclonus_tpu.synthetic import build_synthetic

    pods, namespaces, policies = build_synthetic(
        n_pods, n_pols, random.Random(seed)
    )
    policy = build_network_policies(True, policies)
    return TpuPolicyEngine(policy, pods, namespaces, **kw), policy, pods


class TestDefaultMesh:
    def test_never_cpu_devices_when_the_default_backend_is_not_the_cpu(
        self, monkeypatch
    ):
        """A one-chip accelerator gets a one-device mesh of ITS device:
        the virtual CPU mesh this suite runs on is never substituted, or
        `--engine tpu-sharded` would compute on the host and say
        nothing."""
        import jax

        class Chip:
            platform, device_kind, id, process_index = "tpu", "fake", 0, 0

        chip = Chip()
        cpu_devices = jax.devices("cpu")
        assert len(cpu_devices) > 1  # the substitution was on offer

        def devices(backend=None):
            return cpu_devices if backend == "cpu" else [chip]

        monkeypatch.setattr(sharded_mod.jax, "devices", devices)
        monkeypatch.setattr(
            sharded_mod, "Mesh", lambda devs, axes: (list(devs.flat), axes)
        )
        assert sharded_mod.default_mesh() == ([chip], ("x",))

    def test_all_devices_of_the_default_backend(self):
        import jax

        mesh = sharded_mod.default_mesh()
        assert list(mesh.devices.flat) == list(jax.devices())


class TestRingParity:
    @pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
    @pytest.mark.parametrize("seed", [0, 3])
    def test_ring_matches_single_device_uneven(self, seed, n_dev):
        """Overlapped ring grid == single-device kernel at every mesh
        width, with pod counts that do NOT divide the device count
        (padded rows must stay inert)."""
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=4)
        assert len(pods) % 8 != 0  # 13 pods: uneven over every mesh
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ref = engine.evaluate_grid(CASES)
        ring = engine.evaluate_grid_sharded(
            CASES, mesh=cpu_mesh(n_dev), schedule="ring"
        )
        assert grids_equal(ring, ref)
        # pad rows stripped: the grid is exactly n x n
        n = len(pods)
        assert np.asarray(ring.combined).shape == (len(CASES), n, n)

    @pytest.mark.parametrize("seed", [1, 4])
    def test_ring_bit_identical_to_allgather(self, seed):
        """The overlapped schedule and the all-gather reference must
        produce the SAME truth tables bit for bit."""
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=2)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        mesh = cpu_mesh(8)
        ring = engine.evaluate_grid_sharded(CASES, mesh=mesh, schedule="ring")
        ag = engine.evaluate_grid_sharded(
            CASES, mesh=mesh, schedule="allgather"
        )
        assert grids_equal(ring, ag)

    def test_ring_tiered_engine(self):
        """The precedence-tier epilogue resolves INSIDE the ring step:
        a tiered engine's overlapped grid must equal the single-device
        tiered kernel."""
        from cyclonus_tpu.kube.netpol import IntOrString, LabelSelector
        from cyclonus_tpu.tiers.model import (
            AdminNetworkPolicy,
            BaselineAdminNetworkPolicy,
            TierPort,
            TierRule,
            TierScope,
            TierSet,
        )

        policy, pods, namespaces = fuzz_problem(7, n_extra_pods=4)
        tiers = TierSet(
            anps=[
                AdminNetworkPolicy(
                    name="deny-a",
                    priority=5,
                    subject=TierScope(
                        pod_selector=LabelSelector.make({"pod": "a"})
                    ),
                    ingress=[
                        TierRule(
                            action="Deny",
                            peers=[TierScope(
                                pod_selector=LabelSelector.make({"pod": "b"})
                            )],
                            ports=[TierPort(
                                protocol="TCP", port=IntOrString(80)
                            )],
                        )
                    ],
                )
            ],
            banp=BaselineAdminNetworkPolicy(
                subject=TierScope(
                    pod_selector=LabelSelector.make({"pod": "c"})
                ),
                ingress=[TierRule(action="Deny", peers=[TierScope()])],
            ),
        )
        engine = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
        ref = engine.evaluate_grid(CASES)
        ring = engine.evaluate_grid_sharded(
            CASES, mesh=cpu_mesh(8), schedule="ring"
        )
        assert grids_equal(ring, ref)

    def test_ring_class_compressed_engine(self):
        """The compressed route is a C x C ring over class
        representatives + the gather epilogue; still bit-identical to
        the dense single-device grid."""
        policy, pods, namespaces = fuzz_problem(2, n_extra_pods=6)
        engine = TpuPolicyEngine(
            policy, pods, namespaces, class_compress="1"
        )
        assert engine.pod_classes() is not None
        ref_engine = TpuPolicyEngine(
            policy, pods, namespaces, class_compress="0"
        )
        ref = ref_engine.evaluate_grid(CASES)
        ring = engine.evaluate_grid_sharded(
            CASES, mesh=cpu_mesh(8), schedule="ring"
        )
        assert grids_equal(ring, ref)

    def test_ring_ipv6_host_rows(self):
        """Host-evaluated (IPv6) peer rows ride the pod-sharded
        host_ip_match columns through the ring like every other per-pod
        array."""
        from cyclonus_tpu.kube.netpol import (
            IPBlock,
            LabelSelector,
            NetworkPolicyIngressRule,
            NetworkPolicyPeer,
        )
        from test_engine_parity import default_cluster, mkpol

        pods, namespaces = default_cluster()
        pods = [
            (ns, name, labels, f"2001:db8::{i + 1}")
            for i, (ns, name, labels, _ip) in enumerate(pods)
        ]
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "v6",
                    "x",
                    LabelSelector.make(),
                    ["Ingress"],
                    ingress=[
                        NetworkPolicyIngressRule(
                            ports=[],
                            from_=[
                                NetworkPolicyPeer(
                                    ip_block=IPBlock.make(
                                        "2001:db8::/112",
                                        ["2001:db8::4/126"],
                                    )
                                )
                            ],
                        )
                    ],
                )
            ],
        )
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ref = engine.evaluate_grid(CASES)
        ring = engine.evaluate_grid_sharded(
            CASES, mesh=cpu_mesh(8), schedule="ring"
        )
        assert grids_equal(ring, ref)


class TestMeshCounts:
    def test_ring_route_tiered_matches_counts(self, monkeypatch):
        """Tier slabs rotate with the bundle: the mesh counts entry on its
        ring route, on a tiered engine, equals the tiered one-chip
        counts."""
        import cyclonus_tpu.engine.api as api
        from cyclonus_tpu.kube.netpol import LabelSelector
        from cyclonus_tpu.tiers.model import (
            AdminNetworkPolicy,
            TierRule,
            TierScope,
            TierSet,
        )

        monkeypatch.setattr(api, "_MESH_REPLICATED_MAX_BYTES", 0)
        policy, pods, namespaces = fuzz_problem(9, n_extra_pods=4)
        tiers = TierSet(
            anps=[
                AdminNetworkPolicy(
                    name="deny-b",
                    priority=3,
                    subject=TierScope(),
                    egress=[
                        TierRule(
                            action="Deny",
                            peers=[TierScope(
                                pod_selector=LabelSelector.make({"pod": "b"})
                            )],
                        )
                    ],
                )
            ]
        )
        engine = TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)
        want = engine.evaluate_grid_counts(CASES, block=4)
        counts = engine.evaluate_grid_counts_sharded(
            CASES, block=4, mesh=cpu_mesh(4)
        )
        assert counts == want


class TestPeerBufferWatermark:
    def test_ring_under_allgather_at_8_devices(self):
        """The scale-out acceptance: the overlapped schedule's peak
        per-device peer-buffer bytes (resident shard bundle + one
        in-flight block) must undercut the all-gather schedule's
        replicated peer copy at 8 devices — asserted through the SAME
        gauge the bench records."""
        engine, _policy, pods = synthetic_engine(64, n_pols=10)
        mesh = cpu_mesh(8)
        engine.evaluate_grid_sharded(CASES, mesh=mesh, schedule="ring")
        ring_bytes = ti.MESH_PEER_BYTES.value(schedule="ring")
        engine.evaluate_grid_sharded(CASES, mesh=mesh, schedule="allgather")
        ag_bytes = ti.MESH_PEER_BYTES.value(schedule="allgather")
        assert 0 < ring_bytes < ag_bytes
        # the host-side estimator agrees with what the gauges recorded
        t = engine._tensors_with_cases(CASES)
        t, _ = sharded_mod._pad_pod_arrays(t, len(pods), 8)
        from cyclonus_tpu.engine.encoding import pack_enabled

        assert ring_bytes == sharded_mod.peer_buffer_bytes(
            t, 8, "ring", pack=pack_enabled()
        )
        assert ag_bytes == sharded_mod.peer_buffer_bytes(t, 8, "allgather")


class TestElasticResize:
    def test_bucket_step_helpers_invert(self):
        for b in (4, 8, 16, 64, 128, 256, 384, 512, 1024):
            assert _bucket_down(_bucket_up(b, 1), 1) == b
            assert _bucket_down(_bucket_up(b, 2), 2) == b
        assert _bucket_down(4, 3) == 4  # floored at the smallest bucket

    def test_same_bucket_resize_zero_retrace(self):
        """The zero-recompile elastic-resize contract: a +-10% pod
        resize within one _bucket_pods bucket must not add a single
        trace to the shared grid kernel or the cached sharded (ring)
        program — the bucketing makes the shapes identical, so the jit
        caches hit."""
        from cyclonus_tpu.synthetic import build_synthetic
        from cyclonus_tpu.engine.kernel import evaluate_grid_kernel

        n_a, n_b = 900, 990  # +10%: both bucket to 1024
        assert _bucket_pods(n_a) == _bucket_pods(int(n_a * 1.1))
        pods, namespaces, policies = build_synthetic(
            n_b, 8, random.Random(11)
        )
        policy = build_network_policies(True, policies)
        mesh = cpu_mesh(8)
        eng_a = TpuPolicyEngine(policy, pods[:n_a], namespaces)
        eng_a.evaluate_grid(CASES)
        eng_a.evaluate_grid_sharded(CASES, mesh=mesh, schedule="ring")
        kernel_traces = evaluate_grid_kernel._cache_size()
        ring_fns = {
            id(fn): fn._cache_size()
            for fn in sharded_mod._SHARDED_PROGRAMS.values()
        }
        eng_b = TpuPolicyEngine(policy, pods, namespaces)
        eng_b.evaluate_grid(CASES)
        eng_b.evaluate_grid_sharded(CASES, mesh=mesh, schedule="ring")
        assert evaluate_grid_kernel._cache_size() == kernel_traces
        for fn in sharded_mod._SHARDED_PROGRAMS.values():
            assert fn._cache_size() == ring_fns.get(id(fn), 0), (
                "same-bucket resize retraced a sharded program"
            )


class TestMeshWordPrograms:
    """The sharded programs emit their tables as row-sharded words
    (kernel.cell_words) from inside the shard_map: what the lowered
    program holds, and what its persistent key names."""

    @staticmethod
    def _lowered(monkeypatch, engine, mesh, schedule=None, cases=CASES):
        """The StableHLO text of the sharded program one evaluation
        runs, read where evaluate_grid_sharded resolves it."""
        texts = []
        real = sharded_mod._sharded_program

        def spy(*a, **kw):
            fn = real(*a, **kw)

            class Spy:
                def resolve(self, *args):
                    texts.append(fn._jitted.lower(*args).as_text())

                def __call__(self, *args):
                    return fn(*args)

            return Spy()

        monkeypatch.setattr(sharded_mod, "_sharded_program", spy)
        grid = engine.evaluate_grid_sharded(cases, mesh=mesh, schedule=schedule)
        (text,) = texts
        return text, grid

    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_class_route_holds_no_pod_grid_boolean(self, monkeypatch, n_dev):
        """The class route broadcasts back to pods INSIDE the sharded
        program, each device its own rows, as words: no boolean value of
        the lowered program is as large as an [N, N] table (the parent
        built three [N, N, Q] booleans with eager gathers outside)."""
        import re

        engine, _policy, pods = synthetic_engine(
            600, n_pols=12, class_compress="1"
        )
        n = len(pods)
        classes = engine.pod_classes().n_classes
        assert classes * classes * len(CASES) < n * n  # a boolean class grid fits
        text, grid = self._lowered(monkeypatch, engine, cpu_mesh(n_dev))
        booleans = re.findall(r"tensor<((?:\d+x)+)i1>", text)
        assert booleans  # the class grid is there
        largest = max(
            int(np.prod([int(d) for d in dims.split("x") if d]))
            for dims in booleans
        )
        assert largest < n * n
        # the words are there instead, a device's rows of them
        rows = grid.combined_dev.shape[1] // n_dev
        width = grid.combined_dev.shape[2]
        assert f"tensor<{len(CASES)}x{rows}x{width}xui32>" in text
        assert grids_equal(grid, engine.evaluate_grid(CASES))

    @pytest.mark.parametrize("schedule", ["ring", "allgather"])
    def test_dense_routes_turn_ingress_by_one_all_to_all_of_blocks(
        self, monkeypatch, schedule
    ):
        """Ingress is indexed [dst, src]: its blocks change hands inside
        the program, and no boolean value spans all rows of a table."""
        import re

        engine, _policy, pods = synthetic_engine(100, class_compress="0")
        text, grid = self._lowered(
            monkeypatch, engine, cpu_mesh(4), schedule=schedule
        )
        assert len(re.findall(r"stablehlo\.all_to_all", text)) == 1
        # and as WORDS (PR 34; tests/test_cidr_mesh.py holds the shape)
        (exchange,) = re.findall(r"stablehlo\.all_to_all.*", text)
        assert "ui32>" in exchange and "xi1>" not in exchange
        n_pad = grid.ingress_dev.shape[1]
        assert n_pad % (4 * 8) == 0
        # no boolean table over all rows, in either order of its axes
        q = len(CASES)
        assert f"tensor<{n_pad // 4}x{n_pad}x{q}xi1>" in text  # a device's block
        for whole in (f"{n_pad}x{n_pad}x{q}", f"{q}x{n_pad}x{n_pad}"):
            assert f"tensor<{whole}xi1>" not in text
        assert grids_equal(grid, engine.evaluate_grid(CASES))

    @pytest.mark.parametrize("class_compress", ["0", "1"])
    def test_aot_key_of_sharded_grid_changes_with_the_word_format(
        self, monkeypatch, class_compress
    ):
        """aot_cache.make_key sees nothing of a program's result, so the
        plan of `sharded.grid` names the form (kernel.WORD_FORMAT): an
        executable that returns another form lies under another key."""
        from cyclonus_tpu.engine import aot_cache
        from cyclonus_tpu.engine.kernel import WORD_FORMAT

        engine, _policy, _pods = synthetic_engine(
            40, class_compress=class_compress
        )
        mesh = cpu_mesh(2)

        def program():
            sharded_mod._SHARDED_PROGRAMS.clear()
            engine.evaluate_grid_sharded(CASES, mesh=mesh)
            (fn,) = sharded_mod._SHARDED_PROGRAMS.values()
            return fn

        now = program()
        assert now._name == "sharded.grid"
        assert now._plan.endswith(";" + WORD_FORMAT)
        assert f"classes={class_compress == '1'}" in now._plan
        monkeypatch.setattr(sharded_mod, "WORD_FORMAT", "out=bits.1x1")
        other = program()
        assert other._plan == now._plan.replace(WORD_FORMAT, "out=bits.1x1")
        keys = {
            aot_cache.make_key(
                "sharded.grid", "sig", schedule=fn._schedule, plan=fn._plan
            )
            for fn in (now, other)
        }
        assert len(keys) == 2
        sharded_mod._SHARDED_PROGRAMS.clear()

    @pytest.mark.parametrize("class_compress", ["0", "1"])
    @pytest.mark.parametrize("pack", ["1", "0"])
    def test_aot_key_of_sharded_grid_names_the_packed_contraction(
        self, monkeypatch, pack, class_compress
    ):
        """Nor does make_key see a program's code: with pack on, both
        schedules can trace kernel.packed_any, so the plan names the
        contraction's form (kernel.PACKED_CONTRACTION) and the scan's
        executable (PR 34 and before) lies under another key; with
        CYCLONUS_PACK=0 nothing packed is traced and nothing is named."""
        from cyclonus_tpu.engine import aot_cache
        from cyclonus_tpu.engine.kernel import PACKED_CONTRACTION

        monkeypatch.setenv("CYCLONUS_PACK", pack)
        engine, _policy, _pods = synthetic_engine(
            40, class_compress=class_compress
        )
        mesh = cpu_mesh(2)

        def program():
            sharded_mod._SHARDED_PROGRAMS.clear()
            engine.evaluate_grid_sharded(CASES, mesh=mesh)
            (fn,) = sharded_mod._SHARDED_PROGRAMS.values()
            sharded_mod._SHARDED_PROGRAMS.clear()
            return fn

        now = program()
        if pack == "0":
            assert "pack=False;classes=" in now._plan and "any=" not in now._plan
            return
        assert f"pack=True;{PACKED_CONTRACTION};classes=" in now._plan
        monkeypatch.setattr(sharded_mod, "PACKED_CONTRACTION", "any=scan")
        other = program()
        assert other._plan == now._plan.replace(PACKED_CONTRACTION, "any=scan")
        keys = {
            aot_cache.make_key(
                "sharded.grid", "sig", schedule=fn._schedule, plan=fn._plan
            )
            for fn in (now, other)
        }
        assert len(keys) == 2
