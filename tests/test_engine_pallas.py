"""Parity gate for the fused Pallas verdict+count kernel
(engine/pallas_kernel.py): counts must equal the oracle-checked
single-device kernel's sums exactly.  On CPU the kernel runs in Pallas
interpret mode; on TPU it compiles via Mosaic — same program either way.
"""

import numpy as np
import pytest

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.telemetry import recorder

from test_engine_tiled import CASES, fuzz_problem, full_grids


class TestPallasCounts:
    @pytest.mark.parametrize("seed", range(4))
    def test_counts_match_kernel(self, seed):
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=6)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        ing, egr, comb = full_grids(engine, CASES)
        counts = engine.evaluate_grid_counts(CASES, backend="pallas")
        assert counts["ingress"] == int(ing.sum())
        assert counts["egress"] == int(egr.sum())
        assert counts["combined"] == int(comb.sum())
        assert counts["cells"] == ing.size

    def test_single_port_case(self):
        policy, pods, namespaces = fuzz_problem(11)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        cases = [PortCase(80, "serve-80-tcp", "TCP")]
        ing, egr, comb = full_grids(engine, cases)
        counts = engine.evaluate_grid_counts(cases, backend="pallas")
        assert counts["combined"] == int(comb.sum())

    def test_matches_xla_backend(self):
        policy, pods, namespaces = fuzz_problem(12, n_extra_pods=9)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        a = engine.evaluate_grid_counts(CASES, block=8, backend="xla")
        b = engine.evaluate_grid_counts(CASES, backend="pallas")
        assert a == b

    def test_pre_cache_state_machine(self, monkeypatch):
        """The device-resident precompute cache: populated on the second
        consecutive evaluation of one case set, hit thereafter, evicted
        after two consecutive other-set evaluations — with identical
        counts on every path, and a byte estimate that matches the real
        pytree."""
        import cyclonus_tpu.engine.api as api

        policy, pods, namespaces = fuzz_problem(14, n_extra_pods=8)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        A = CASES
        B = [PortCase(81, "", "UDP")]
        C = [PortCase(9999, "", "TCP")]
        want_a = engine.evaluate_grid_counts(A, backend="xla")
        # 1st A: the resident path (the static half built and kept, the
        # request's program from where its cases enter; `fused` before
        # PR 33), no pin; 2nd A: split path populates it
        assert engine.evaluate_grid_counts(A, backend="pallas") == want_a
        assert recorder.entries()[-1]["mode"] == "resident"
        assert engine._static_pre is not None
        assert engine._pre_cache is None
        assert engine.evaluate_grid_counts(A, backend="pallas") == want_a
        assert engine._pre_cache is not None
        # estimate matches the cached pytree (has_target [N] x2 is the
        # only leaf it ignores)
        import jax

        actual = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(engine._pre_cache[1])
        )
        n = engine._tensors["pod_ns_id"].shape[0]
        assert engine._pre_bytes_estimate(len(A)) == actual - 2 * n
        # cache hit
        assert engine.evaluate_grid_counts(A, backend="pallas") == want_a
        assert engine._pre_cache_misses == 0
        # one other-set call must NOT evict (A/B alternation)
        want_b = engine.evaluate_grid_counts(B, backend="xla")
        assert engine.evaluate_grid_counts(B, backend="pallas") == want_b
        assert engine._pre_cache is not None
        assert engine.evaluate_grid_counts(A, backend="pallas") == want_a
        # B seen again: the split path REPLACES the cached set with B's
        # (alternating sets each get cached when re-seen, never thrash)
        assert engine.evaluate_grid_counts(B, backend="pallas") == want_b
        assert engine._pre_cache is not None
        tallow_key = "tallow_pk" if engine._pack else "tallow_bf"
        assert engine._pre_cache[1]["egress"][tallow_key].shape[-1] == len(B)
        # two consecutive distinct foreign sets evict outright
        want_c = engine.evaluate_grid_counts(C, backend="xla")
        assert engine.evaluate_grid_counts(A, backend="pallas") == want_a
        assert engine.evaluate_grid_counts(C, backend="pallas") == want_c
        assert engine._pre_cache is None

    def test_pre_cache_size_gate_and_opt_out(self, monkeypatch):
        """An over-cap estimate keeps the engine on the fused path (no
        split compile, no pin); CYCLONUS_PRE_CACHE=0 disables caching."""
        import cyclonus_tpu.engine.api as api

        policy, pods, namespaces = fuzz_problem(15, n_extra_pods=8)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        monkeypatch.setattr(api, "_PRE_CACHE_MAX_BYTES", 0)
        for _ in range(3):
            assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
            assert recorder.entries()[-1]["mode"] == "fused"
        assert engine._pre_cache is None and engine._static_pre is None

        monkeypatch.undo()
        monkeypatch.setenv("CYCLONUS_PRE_CACHE", "0")
        engine2 = TpuPolicyEngine(policy, pods, namespaces)
        for _ in range(3):
            assert engine2.evaluate_grid_counts(CASES, backend="pallas") == want
            assert recorder.entries()[-1]["mode"] == "fused"
        assert engine2._pre_cache is None and engine2._static_pre is None

    def test_bf16_operand_mode(self, monkeypatch):
        """The CYCLONUS_PALLAS_DTYPE=bf16 fallback (f32 accumulators)
        must count identically to the default int8 path.  The env var is
        read at trace time, so clear jit caches around the flip."""
        import jax

        policy, pods, namespaces = fuzz_problem(13, n_extra_pods=7)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="pallas")
        monkeypatch.setenv("CYCLONUS_PALLAS_DTYPE", "bf16")
        jax.clear_caches()
        try:
            engine2 = TpuPolicyEngine(policy, pods, namespaces)
            got = engine2.evaluate_grid_counts(CASES, backend="pallas")
        finally:
            monkeypatch.undo()
            jax.clear_caches()
        assert got == want

    def test_unequal_direction_chunks(self, monkeypatch):
        """Regression: with different target-axis chunk counts per
        direction (n_k_e != n_k_i), the clamped index maps refetch the
        shorter direction's last chunk and the per-direction guards must
        skip accumulating it.  Shrinking KT forces multiple chunks from a
        small fixture; an ingress-heavy and an egress-heavy policy set
        exercise both orderings."""
        import jax

        import cyclonus_tpu.engine.pallas_kernel as pk
        from cyclonus_tpu.kube.netpol import (
            IntOrString,
            LabelSelector,
            NetworkPolicyEgressRule,
            NetworkPolicyIngressRule,
            NetworkPolicyPeer,
            NetworkPolicyPort,
        )
        from cyclonus_tpu.matcher import build_network_policies
        from test_engine_parity import default_cluster, mkpol

        pods, namespaces = default_cluster()

        def mk_dir_policies(n_ing, n_eg):
            out = []
            for i in range(n_ing):
                out.append(mkpol(
                    f"in{i}", "x",
                    LabelSelector.make(match_labels={"pod": "abc"[i % 3], "i": str(i)}),
                    ["Ingress"],
                    ingress=[NetworkPolicyIngressRule(
                        ports=[NetworkPolicyPort(protocol="TCP", port=IntOrString(80))],
                        from_=[NetworkPolicyPeer(pod_selector=LabelSelector.make())],
                    )],
                ))
            for i in range(n_eg):
                out.append(mkpol(
                    f"eg{i}", "y",
                    LabelSelector.make(match_labels={"pod": "abc"[i % 3], "e": str(i)}),
                    ["Egress"],
                    egress=[NetworkPolicyEgressRule(
                        ports=[],
                        to=[NetworkPolicyPeer(pod_selector=LabelSelector.make())],
                    )],
                ))
            return out

        # these fixtures' targets mostly match no pod; dead-target
        # compaction would collapse them to a single chunk and make the
        # multi-chunk path untested
        monkeypatch.setenv("CYCLONUS_COMPACT", "0")
        # KT is a lane dimension (min 128); >128 targets on one side
        # yields n_k 2 vs 1
        monkeypatch.setattr(pk, "KT", 128)
        try:
            for n_ing, n_eg in [(150, 3), (3, 150)]:
                policy = build_network_policies(True, mk_dir_policies(n_ing, n_eg))
                engine = TpuPolicyEngine(policy, pods, namespaces)
                want = engine.evaluate_grid_counts(CASES, block=8, backend="xla")
                jax.clear_caches()  # KT is read at trace time, not cached on
                got = engine.evaluate_grid_counts(CASES, backend="pallas")
                assert got == want, (n_ing, n_eg, got, want)
        finally:
            jax.clear_caches()

    def test_unequal_src_dst_tiles(self, monkeypatch):
        """Regression: with BS != BD the pod axis must pad to a COMMON
        multiple — independent rounding silently dropped trailing dst
        rows (caught as a count mismatch in a 100k tile-size sweep)."""
        import cyclonus_tpu.engine.pallas_kernel as pk

        policy, pods, namespaces = fuzz_problem(13, n_extra_pods=10)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, block=8, backend="xla")
        import jax

        try:
            for bs, bd in [(256, 512), (512, 256)]:
                monkeypatch.setattr(pk, "BS", bs)
                monkeypatch.setattr(pk, "BD", bd)
                # BS/BD are read at trace time but are NOT part of the jit
                # cache key; identical input shapes would silently reuse
                # the previous configuration's executable
                jax.clear_caches()
                got = engine.evaluate_grid_counts(CASES, backend="pallas")
                assert got == want, (bs, bd, got, want)
        finally:
            # don't leave a non-default-tiling executable in the global
            # cache for later tests with identical input shapes
            jax.clear_caches()

    def test_doubled_src_tile_path(self):
        """A >512-pod cluster with small T-chunks takes the bs=1024
        doubled-src-tile configuration (_tiles_for) — the asymmetric
        bs != bd index maps, nz reshapes, and epilogue flush must still
        count exactly (every other test cluster is far below one tile)."""
        import random

        from cyclonus_tpu.engine.pallas_kernel import _tiles_for
        from cyclonus_tpu.matcher import build_network_policies
        from cyclonus_tpu.synthetic import build_synthetic

        rng = random.Random(31)
        pods, namespaces, policies = build_synthetic(600, 60, rng)
        policy = build_network_policies(True, policies)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        for d in ("ingress", "egress"):
            assert engine._tensors[d]["target_ns"].shape[0] + 1 <= 128
        assert _tiles_for(128, 128, 600) == (1024, 512)  # the tested config
        want = engine.evaluate_grid_counts(CASES, block=64, backend="xla")
        got = engine.evaluate_grid_counts(CASES, backend="pallas")
        assert got == want

    def test_rect_non_prefix_masks(self):
        """The RECTANGULAR kernel (verdict_counts_pallas_rect) — the
        per-device program of the mesh fast path: Ns != Nd and validity
        as arbitrary per-side masks (a shard's rows are a window of the
        global pod axis, not a prefix, and dead pods can sit anywhere).
        Pinned against the oracle-checked single-device grids restricted
        to the same window/masks."""
        import numpy as np

        from cyclonus_tpu.engine.pallas_kernel import (
            sum_partials,
            verdict_counts_pallas_rect,
        )
        from cyclonus_tpu.engine.tiled import _precompute_jit

        policy, pods, namespaces = fuzz_problem(16, n_extra_pods=10)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        n = len(pods)
        n_b = engine._tensors["pod_ns_id"].shape[0]  # bucketed axis
        assert n_b > n  # pad rows in play
        pre = _precompute_jit(engine._tensors_with_cases(CASES))
        e, ig = pre["egress"], pre["ingress"]
        ing, egr, comb = full_grids(engine, CASES)  # [Q, N, N] real pods

        base = np.arange(n_b) < n
        q = len(CASES)

        for src0, holes_src, holes_dst in [
            (3, [4, 7], [0, 5]),  # src window into the axis, holes both sides
            (0, [], [1, 2, 9]),  # full src, dst holes only
            (n - 2, [n - 1], []),  # window straddling the real/pad boundary
        ]:
            src_ok = base.copy()
            src_ok[holes_src] = False
            dst_ok = base.copy()
            dst_ok[holes_dst] = False
            partials = verdict_counts_pallas_rect(
                e["tmatch"][:, src0:],
                e["has_target"][src0:],
                e["tallow_bf"],
                ig["tmatch"],
                ig["has_target"],
                ig["tallow_bf"][:, src0:],
                valid_src=src_ok[src0:],
                valid_dst=dst_ok,
                interpret=True,
            )
            got = sum_partials(partials, q, 0)
            srcsel = [s for s in range(src0, n) if src_ok[s]]
            dstsel = [d for d in range(n) if dst_ok[d]]
            sel = np.ix_(range(q), srcsel, dstsel)
            sel_t = np.ix_(range(q), dstsel, srcsel)  # ingress is [Q, dst, src]
            assert got["ingress"] == int(ing[sel_t].sum()), (src0, holes_src, holes_dst)
            assert got["egress"] == int(egr[sel].sum()), (src0, holes_src, holes_dst)
            assert got["combined"] == int(comb[sel].sum()), (src0, holes_src, holes_dst)

    def test_rect_dst_window(self):
        """Rect with the DST side windowed/masked instead (Ns > Nd): the
        opposite orientation of the mesh path's slicing."""
        import numpy as np

        from cyclonus_tpu.engine.pallas_kernel import (
            sum_partials,
            verdict_counts_pallas_rect,
        )
        from cyclonus_tpu.engine.tiled import _precompute_jit

        policy, pods, namespaces = fuzz_problem(17, n_extra_pods=9)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        n = len(pods)
        n_b = engine._tensors["pod_ns_id"].shape[0]
        pre = _precompute_jit(engine._tensors_with_cases(CASES))
        e, ig = pre["egress"], pre["ingress"]
        ing, egr, comb = full_grids(engine, CASES)

        dst0 = 2
        base = np.arange(n_b) < n
        src_ok = base.copy()
        src_ok[[6]] = False
        dst_ok = base.copy()
        dst_ok[[3, 8]] = False
        q = len(CASES)
        partials = verdict_counts_pallas_rect(
            e["tmatch"],
            e["has_target"],
            e["tallow_bf"][:, dst0:],
            ig["tmatch"][:, dst0:],
            ig["has_target"][dst0:],
            ig["tallow_bf"],
            valid_src=src_ok,
            valid_dst=dst_ok[dst0:],
            interpret=True,
        )
        got = sum_partials(partials, q, 0)
        srcsel = [s for s in range(n) if src_ok[s]]
        dstsel = [d for d in range(dst0, n) if dst_ok[d]]
        sel = np.ix_(range(q), srcsel, dstsel)
        sel_t = np.ix_(range(q), dstsel, srcsel)
        assert got["ingress"] == int(ing[sel_t].sum())
        assert got["egress"] == int(egr[sel].sum())
        assert got["combined"] == int(comb[sel].sum())

    def test_dtype_flip_without_cache_clear(self):
        """CYCLONUS_PALLAS_DTYPE is now resolved OUTSIDE the jit and
        passed as a static argument: flipping it mid-process retraces
        instead of silently reusing the previous dtype's executable — no
        jax.clear_caches() around this test, which is the point."""
        from cyclonus_tpu.engine.pallas_kernel import (
            sum_partials,
            verdict_counts_pallas_rect,
        )
        from cyclonus_tpu.engine.tiled import _precompute_jit

        policy, pods, namespaces = fuzz_problem(18, n_extra_pods=5)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        pre = _precompute_jit(engine._tensors_with_cases(CASES))
        e, ig = pre["egress"], pre["ingress"]
        args = (
            e["tmatch"], e["has_target"], e["tallow_bf"],
            ig["tmatch"], ig["has_target"], ig["tallow_bf"],
        )
        q = len(CASES)
        got = {
            od: sum_partials(
                verdict_counts_pallas_rect(
                    *args, interpret=True, operand_dtype=od
                ),
                q,
                0,
            )
            for od in ("int8", "bf16", "int8")
        }
        assert got["int8"] == got["bf16"]

    def _slab_case(self, policy, pods, namespaces, bs, bd, w, n_pods=None):
        """Run the slab kernel (interpret) on an engine's precompute and
        pin its counts against the oracle-checked full grids."""
        import numpy as np

        from cyclonus_tpu.engine.pallas_kernel import (
            slab_windows,
            sum_partials,
            verdict_counts_pallas_slab,
        )
        from cyclonus_tpu.engine.tiled import _precompute_jit

        engine = TpuPolicyEngine(policy, pods, namespaces)
        n = len(pods) if n_pods is None else n_pods
        pre = _precompute_jit(engine._tensors_with_cases(CASES))
        e, ig = pre["egress"], pre["ingress"]
        n_b = engine._tensors["pod_ns_id"].shape[0]
        valid = np.arange(n_b) < n
        tm_e = np.asarray(e["tmatch"]) & valid[None, :]
        tm_i = np.asarray(ig["tmatch"]) & valid[None, :]
        t0_e, ok_e = slab_windows(tm_e, bs, w)
        t0_i, ok_i = slab_windows(tm_i, bd, w)
        assert ok_e and ok_i, "fixture must be slab-eligible"
        partials = verdict_counts_pallas_slab(
            e["tmatch"], e["has_target"], e["tallow_bf"],
            ig["tmatch"], ig["has_target"], ig["tallow_bf"],
            t0_e, t0_i, n,
            interpret=True, bs=bs, bd=bd, w=w,
        )
        got = sum_partials(partials, len(CASES), 0)
        ing, egr, comb = full_grids(engine, CASES)
        sel = [s for s in range(min(n, len(pods)))]
        q = len(CASES)
        ix = np.ix_(range(q), sel, sel)
        assert got["ingress"] == int(ing[ix].sum())
        assert got["egress"] == int(egr[ix].sum())
        assert got["combined"] == int(comb[ix].sum())

    @pytest.mark.parametrize("seed", [30, 31, 32])
    def test_slab_counts_match_kernel(self, seed):
        """Per-tile target-slab kernel parity on fuzzed problems: tiny
        tiles force multiple slabs, windows land mid-axis."""
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=9)
        self._slab_case(policy, pods, namespaces, bs=8, bd=4, w=8)

    def test_slab_validity_prefix(self):
        """Validity cut below the real pod count: trailing pods must
        contribute nothing on either axis (epilogue OR-terms included)."""
        policy, pods, namespaces = fuzz_problem(33, n_extra_pods=10)
        self._slab_case(policy, pods, namespaces, bs=8, bd=8, w=8, n_pods=len(pods) - 3)

    def test_slab_multi_namespace_sorted(self):
        """An ns-SORTED multi-namespace cluster — the production regime:
        narrow per-tile windows over a longer target axis, windows
        differing per tile, plus the bs != bd asymmetric layout."""
        import random

        from cyclonus_tpu.matcher import build_network_policies
        from cyclonus_tpu.synthetic import build_synthetic

        rng = random.Random(77)
        pods, namespaces, policies = build_synthetic(2000, 100, rng)
        pods = sorted(pods, key=lambda p: p[0])  # ns-sort, like the packed path
        policy = build_network_policies(True, policies)
        self._slab_case(policy, pods, namespaces, bs=256, bd=128, w=64)

    def test_slab_api_path(self, monkeypatch):
        """CYCLONUS_PALLAS_SLAB=1 routes the packed counts path through
        the slab kernel (tiny tile overrides so a fuzz cluster spans
        multiple tiles), identical counts on cold, split/pre-cache, and
        cached evaluations; an ineligible width gate falls back to the
        chunked kernels with counts unchanged."""
        import cyclonus_tpu.engine.pallas_kernel as pk

        monkeypatch.setenv("CYCLONUS_PACK", "0")
        monkeypatch.setenv("CYCLONUS_PALLAS_SLAB", "1")
        monkeypatch.setattr(pk, "SLAB_BS", 8)
        monkeypatch.setattr(pk, "SLAB_BD", 8)
        monkeypatch.setattr(pk, "SLAB_W", 8)
        policy, pods, namespaces = fuzz_problem(34, n_extra_pods=10)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        got = engine.evaluate_grid_counts(CASES, backend="pallas")
        assert isinstance(engine._slab_plan_state, dict)  # plan engaged
        assert got == want
        # 2nd/3rd evaluations take the split + pre-cache paths
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want

        # deterministic width-gate fallback: two same-namespace targets
        # that both match pods occupy two rows of one tile's window, so
        # W=1 is ALWAYS ineligible — the plan must come back None and
        # the chunked kernels must produce identical counts
        from cyclonus_tpu.kube.netpol import LabelSelector
        from cyclonus_tpu.matcher import build_network_policies

        from test_engine_parity import default_cluster, mkpol

        monkeypatch.setattr(pk, "SLAB_W", 1)
        d_pods, d_ns = default_cluster()
        policy2 = build_network_policies(
            True,
            [
                mkpol("p1", "x", LabelSelector.make(match_labels={"pod": "a"}),
                      ["Ingress"], ingress=[]),
                mkpol("p2", "x", LabelSelector.make(match_labels={"pod": "b"}),
                      ["Ingress"], ingress=[]),
            ],
        )
        engine2 = TpuPolicyEngine(policy2, d_pods, d_ns)
        want2 = engine2.evaluate_grid_counts(CASES, backend="xla")
        assert engine2.evaluate_grid_counts(CASES, backend="pallas") == want2
        assert engine2._slab_plan_state is None  # gate rejected W=1

    def test_slab_autotune_mechanics(self, monkeypatch):
        """_autotune_slab times both steady-state programs from the
        pinned precompute, records a boolean choice, and returns
        partials identical to either path (the perf decision itself is
        TPU-side; this pins the mechanics)."""
        import numpy as np

        import cyclonus_tpu.engine.pallas_kernel as pk
        from cyclonus_tpu.engine.pallas_kernel import sum_partials

        monkeypatch.setenv("CYCLONUS_PACK", "0")
        monkeypatch.setenv("CYCLONUS_PALLAS_SLAB", "1")
        monkeypatch.setattr(pk, "SLAB_BS", 8)
        monkeypatch.setattr(pk, "SLAB_BD", 8)
        monkeypatch.setattr(pk, "SLAB_W", 8)
        policy, pods, namespaces = fuzz_problem(35, n_extra_pods=10)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        for _ in range(3):  # reach the pinned-precompute steady state
            assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine._pre_cache is not None
        engine._slab_choice = None
        key = engine._steady_state_args(CASES)[0]
        partials = engine._autotune_slab(np.int32(len(pods)), key)
        assert engine._slab_choice in (True, False)
        # the candidate leg built and cached the gathered slab operands
        assert engine._slab_ops_cache is not None
        assert engine._slab_ops_cache[0] == key
        got = sum_partials(np.asarray(partials), len(CASES), len(pods))
        for k in ("ingress", "egress", "combined"):
            assert got[k] == want[k]
        # later calls run the recorded winner
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want

    def test_slab_autotune_candidate_failure_rejects(self, monkeypatch):
        """A slab program that fails to compile/run must reject ITSELF
        in the autotune — choice False, default result returned, no
        exception — because the autotune is where an unproven kernel
        runs unforced and must never take down the proven path."""
        import numpy as np

        import cyclonus_tpu.engine.pallas_kernel as pk
        from cyclonus_tpu.engine.pallas_kernel import sum_partials

        monkeypatch.setenv("CYCLONUS_PACK", "0")
        monkeypatch.setenv("CYCLONUS_PALLAS_SLAB", "1")
        monkeypatch.setattr(pk, "SLAB_BS", 8)
        monkeypatch.setattr(pk, "SLAB_BD", 8)
        monkeypatch.setattr(pk, "SLAB_W", 8)
        policy, pods, namespaces = fuzz_problem(37, n_extra_pods=9)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        for _ in range(3):
            assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine._pre_cache is not None
        engine._slab_choice = None
        key = engine._steady_state_args(CASES)[0]

        def failing_slab(ops):
            raise RuntimeError("mosaic compile failure (simulated)")

        monkeypatch.setattr(
            engine, "_counts_from_slab_ops_jit", failing_slab
        )
        partials = engine._autotune_slab(np.int32(len(pods)), key)
        assert engine._slab_choice is False
        # a rejected candidate must not leave its operands pinned
        assert engine._slab_ops_cache is None
        got = sum_partials(np.asarray(partials), len(CASES), len(pods))
        for k in ("ingress", "egress", "combined"):
            assert got[k] == want[k]
        # the rejection sticks: later calls run the default path without
        # touching the failing slab leg
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want

        # a HANGING candidate (wedged remote compile) must also reject
        # via the bounded leg, not stall the caller
        import time as _t

        def hanging_slab(ops):
            _t.sleep(30)

        monkeypatch.setattr(
            engine, "_counts_from_slab_ops_jit", hanging_slab
        )
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_TIMEOUT_S", "0.5")
        engine._slab_choice = None
        t0 = _t.time()
        partials = engine._autotune_slab(np.int32(len(pods)), key)
        assert _t.time() - t0 < 10
        assert engine._slab_choice is False
        got = sum_partials(np.asarray(partials), len(CASES), len(pods))
        assert got["combined"] == want["combined"]

    def test_slab_autotune_rejection_telemetry_and_orphan_gating(
        self, monkeypatch
    ):
        """A rejected candidate must leave telemetry (WHY there are no
        timed legs), and after a TIMEOUT the next dispatch must gate on
        the abandoned thread: wait briefly for it, count the overlap if
        it is still in flight, and never let its stray execution race a
        real dispatch unrecorded."""
        import threading
        import time as _t

        import cyclonus_tpu.engine.pallas_kernel as pk

        monkeypatch.setenv("CYCLONUS_PACK", "0")
        monkeypatch.setenv("CYCLONUS_PALLAS_SLAB", "1")
        monkeypatch.setattr(pk, "SLAB_BS", 8)
        monkeypatch.setattr(pk, "SLAB_BD", 8)
        monkeypatch.setattr(pk, "SLAB_W", 8)
        policy, pods, namespaces = fuzz_problem(38, n_extra_pods=9)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        for _ in range(3):
            assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine._pre_cache is not None
        real_slab = engine._counts_from_slab_ops_jit
        key = engine._steady_state_args(CASES)[0]

        # --- error branch: telemetry, no orphan ---
        def failing(ops):
            raise RuntimeError("mosaic compile failure (simulated)")

        monkeypatch.setattr(engine, "_counts_from_slab_ops_jit", failing)
        engine._slab_choice = None
        engine._autotune_slab(np.int32(len(pods)), key)
        tel = engine._slab_autotune
        assert tel["candidate"] == "error"
        assert "mosaic compile failure" in tel["candidate_error"]
        assert "default_s" in tel
        assert engine._autotune_orphan is None

        # --- timeout branch: orphan gates the next dispatch ---
        release = threading.Event()

        def hanging(ops):
            release.wait(30)
            return real_slab(ops)

        monkeypatch.setattr(engine, "_counts_from_slab_ops_jit", hanging)
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_TIMEOUT_S", "0.3")
        engine._slab_choice = None
        engine._autotune_slab(np.int32(len(pods)), key)
        assert engine._slab_autotune["candidate"] == "timeout"
        assert engine._autotune_orphan is not None

        # a dispatch while the orphan is live: brief wait times out,
        # overlap counted, orphan kept for the non-blocking next check
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_DRAIN_S", "0.2")
        monkeypatch.setattr(
            engine, "_counts_from_slab_ops_jit", real_slab
        )
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine._slab_autotune["orphan_overlap_dispatches"] == 1
        assert engine._autotune_orphan is not None

        # once the orphan finishes, the next dispatch clears it without
        # further counting
        release.set()
        deadline = _t.time() + 10
        while not engine._autotune_orphan["event"].is_set():
            assert _t.time() < deadline
            _t.sleep(0.02)
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine._autotune_orphan is None
        assert engine._slab_autotune["orphan_overlap_dispatches"] == 1

    def test_slab_ops_cache_lifecycle(self, monkeypatch):
        """The gathered slab operands are built once per pinned case set
        (forced mode dispatches from the cache), reused by identity on
        repeat dispatches, and evicted WITH the pre-cache."""
        import cyclonus_tpu.engine.pallas_kernel as pk

        monkeypatch.setenv("CYCLONUS_PACK", "0")
        monkeypatch.setenv("CYCLONUS_PALLAS_SLAB", "1")
        monkeypatch.setattr(pk, "SLAB_BS", 8)
        monkeypatch.setattr(pk, "SLAB_BD", 8)
        monkeypatch.setattr(pk, "SLAB_W", 8)
        policy, pods, namespaces = fuzz_problem(41, n_extra_pods=8)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        for _ in range(3):
            assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        # steady state + forced choice => the dispatch rides the cache
        assert engine._slab_choice is True
        assert engine._slab_ops_cache is not None
        ops_first = engine._slab_ops_cache[1]
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine._slab_ops_cache[1] is ops_first  # reused, not rebuilt
        # two consecutive other-set evaluations evict pre AND slab ops
        other = [PortCase(9999, "", "TCP")]
        want_other = engine.evaluate_grid_counts(other, backend="xla")
        assert engine.evaluate_grid_counts(other, backend="pallas") == want_other
        assert engine.evaluate_grid_counts(other, backend="pallas") == want_other
        assert engine._slab_ops_cache is None or (
            engine._slab_ops_cache[0] != engine._steady_state_args(CASES)[0]
        )

    def test_counts_pipelined_eval(self):
        """counts_pipelined_eval_s: None before the pinned-precompute
        steady state, then (seconds, counts) with counts identical to
        the sync path — the device-throughput leg the bench records."""
        policy, pods, namespaces = fuzz_problem(40, n_extra_pods=7)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        assert engine.counts_pipelined_eval_s(CASES) is None  # cold
        for _ in range(3):
            assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        got = engine.counts_pipelined_eval_s(CASES, reps=3)
        assert got is not None
        dt, counts = got
        assert dt > 0
        assert counts == want
        # a different case set is not at steady state
        other = [PortCase(9999, "", "TCP")]
        assert engine.counts_pipelined_eval_s(other) is None

    def test_slab_auto_mode_needs_tpu(self, monkeypatch):
        """The default 'auto' mode never engages off TPU (interpret-mode
        timing is meaningless): no plan, default kernels, counts
        unchanged."""
        import jax

        import cyclonus_tpu.engine.pallas_kernel as pk

        if jax.default_backend() == "tpu":
            pytest.skip("off-TPU behavior; suite running on real TPU")
        monkeypatch.delenv("CYCLONUS_PALLAS_SLAB", raising=False)
        monkeypatch.setattr(pk, "SLAB_BS", 8)
        monkeypatch.setattr(pk, "SLAB_BD", 8)
        policy, pods, namespaces = fuzz_problem(36, n_extra_pods=8)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, backend="xla")
        assert engine.evaluate_grid_counts(CASES, backend="pallas") == want
        assert engine._slab_plan_state is None
        assert engine._slab_choice is None

    def test_slab_windows_eligibility(self):
        """slab_windows: window starts and the ineligibility verdict for
        scattered (non-local) target structure."""
        import numpy as np

        from cyclonus_tpu.engine.pallas_kernel import slab_windows

        tm = np.zeros((40, 8), dtype=bool)
        tm[3, 0] = tm[5, 1] = True  # tile 0 (cols 0-3): rows 3..5
        tm[20, 4] = tm[24, 7] = True  # tile 1: rows 20..24
        t0, ok = slab_windows(tm, tile=4, w=8)
        assert ok
        assert list(t0) == [3, 20]
        # scatter one tile's matches past the window
        tm[35, 2] = True  # tile 0 now spans 3..35 > 8
        _t0, ok = slab_windows(tm, tile=4, w=8)
        assert not ok
        # empty tmatch: trivially eligible
        t0, ok = slab_windows(np.zeros((0, 8), dtype=bool), tile=4, w=8)
        assert ok

    def test_selector_match_np_twin(self):
        """The numpy selector evaluator that drives dead-target compaction
        must agree with the device kernel op for op — fuzzed over random
        selector tables (incl. matchExpressions) and label sets."""
        import numpy as np

        from cyclonus_tpu.engine.api import _selector_match_np
        from cyclonus_tpu.engine.kernel import selector_match

        rng = np.random.default_rng(7)
        for _ in range(20):
            s, r, e, v, n, l = (
                rng.integers(1, 6),
                rng.integers(1, 4),
                rng.integers(1, 4),
                rng.integers(1, 4),
                rng.integers(1, 12),
                rng.integers(1, 5),
            )
            args = (
                rng.integers(-1, 6, size=(s, r)).astype(np.int32),
                rng.integers(0, 5, size=(s, e)).astype(np.int32),
                rng.integers(-1, 5, size=(s, e)).astype(np.int32),
                rng.integers(-1, 6, size=(s, e, v)).astype(np.int32),
                rng.integers(-1, 6, size=(n, l)).astype(np.int32),
                rng.integers(-1, 5, size=(n, l)).astype(np.int32),
            )
            got = _selector_match_np(*args)
            want = np.asarray(selector_match(*args))
            assert np.array_equal(got, want)

    def test_no_policies_all_allow(self):
        """With zero policies every pod is target-free: the pseudo-target
        fold must produce all-allow counts for exactly the valid pods
        (pads contribute nothing)."""
        from cyclonus_tpu.matcher import build_network_policies

        from test_engine_parity import default_cluster

        pods, namespaces = default_cluster()
        policy = build_network_policies(True, [])
        engine = TpuPolicyEngine(policy, pods, namespaces)
        counts = engine.evaluate_grid_counts(CASES, backend="pallas")
        n = len(pods)
        full = n * n * len(CASES)
        assert counts == {
            "ingress": full,
            "egress": full,
            "combined": full,
            "cells": full,
        }

    def test_one_empty_direction(self):
        """Ingress-only policies leave the egress target axis empty
        (T_e = 0): its padded pseudo-row chunk must still produce the
        all-allow egress verdicts for valid pods."""
        from cyclonus_tpu.kube.netpol import LabelSelector
        from cyclonus_tpu.matcher import build_network_policies

        from test_engine_parity import default_cluster, mkpol

        pods, namespaces = default_cluster()
        policy = build_network_policies(
            True,
            [
                mkpol(
                    "deny-in",
                    "x",
                    LabelSelector.make(match_labels={"pod": "a"}),
                    ["Ingress"],
                    ingress=[],
                )
            ],
        )
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, block=8, backend="xla")
        got = engine.evaluate_grid_counts(CASES, backend="pallas")
        assert got == want
        n = len(pods)
        assert got["egress"] == n * n * len(CASES)  # no egress targets


class TestSlabLayout:
    def test_slab_w_aug_alignment_arbitrary_w(self):
        """slab_w_aug must land on the dtype sublane tile for ANY w
        override (not just tile-aligned ones), with room for the window
        plus the OR-term row."""
        from cyclonus_tpu.engine.pallas_kernel import slab_w_aug

        for od, tile in (("int8", 32), ("bf16", 16)):
            for w in (1, 17, 32, 100, 128, 129, 257):
                aug = slab_w_aug(od, w)
                assert aug % tile == 0, (od, w, aug)
                assert aug >= w + 1, (od, w, aug)
                # minimal: no more than one extra tile of padding
                assert aug < w + 1 + tile, (od, w, aug)

    def test_slab_w_aug_default_unchanged(self):
        """Tile-aligned defaults keep the historical layout (the
        persistent compile cache keys on these shapes)."""
        from cyclonus_tpu.engine.pallas_kernel import SLAB_W, slab_w_aug

        assert SLAB_W % 32 == 0
        assert slab_w_aug("int8") == SLAB_W + 32
        assert slab_w_aug("bf16") == SLAB_W + 16

    def test_slab_budget_counts_bytes_not_elements(self, monkeypatch):
        """api._slab_plan must scale its HBM estimate by the operand
        itemsize: with bf16 operands the same element count is twice
        the bytes, so a budget that admits an int8 plan at the edge
        must reject the bf16 one."""
        from cyclonus_tpu.engine.pallas_kernel import (
            SLAB_BD,
            SLAB_BS,
            slab_w_aug,
        )
        from cyclonus_tpu.matcher import build_network_policies
        from test_engine_parity import mkpol
        from cyclonus_tpu.kube.netpol import (
            LabelSelector,
            NetworkPolicyIngressRule,
        )

        n = 4 * SLAB_BS  # spans >= 2 src tiles so the plan engages
        pods = [("x", f"p{i}", {"pod": "a"}, f"10.0.{i // 250}.{i % 250}")
                for i in range(n)]
        namespaces = {"x": {"ns": "x"}}
        policy = build_network_policies(
            True,
            [mkpol("allow", "x", LabelSelector.make(), ["Ingress"],
                   ingress=[NetworkPolicyIngressRule()])],
        )
        monkeypatch.setenv("CYCLONUS_PACK", "0")
        monkeypatch.setenv("CYCLONUS_PALLAS_SLAB", "1")
        # this test pins the slab BYTE accounting with an exact budget;
        # class compression would add its aux/index bytes to the same
        # budget (its own test: test_engine_classes.py) and skew the
        # equality below
        monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", "0")

        monkeypatch.setenv("CYCLONUS_PALLAS_DTYPE", "int8")
        engine = TpuPolicyEngine(policy, pods, namespaces)
        n_b = int(engine._tensors["pod_ns_id"].shape[0])
        n_tiles = -(-n_b // SLAB_BS) + -(-n_b // SLAB_BD)
        elems = n_tiles * slab_w_aug("int8") * n_b
        ns = engine._tensors["pod_ns_id"]
        key = np.where(ns < 0, np.iinfo(np.int32).max, ns)
        perm = np.argsort(key, kind="stable").astype(np.int32)

        # budget admitting 2 cases of int8 exactly
        budget = 2 * elems
        monkeypatch.setenv("CYCLONUS_SLAB_MAX_BYTES", str(budget))
        assert engine._slab_plan(perm) is not None

        # same ELEMENT budget under bf16 must be rejected (2x the bytes)
        monkeypatch.setenv("CYCLONUS_PALLAS_DTYPE", "bf16")
        bf16_elems = n_tiles * slab_w_aug("bf16") * n_b
        monkeypatch.setenv("CYCLONUS_SLAB_MAX_BYTES", str(2 * bf16_elems))
        engine2 = TpuPolicyEngine(policy, pods, namespaces)
        assert engine2._slab_plan(perm) is None
