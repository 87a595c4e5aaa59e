"""The bit-packed kernel's differential gate (docs/DESIGN.md "Bit-packed
kernel").

Everything the packed dtype plan touches must stay BIT-IDENTICAL to the
scalar oracle and to the CYCLONUS_PACK=0 legacy plan: the packing
primitives (numpy/jnp twins), the XLA tile bodies, the packed Pallas
kernel with its fused tier and class-gather epilogues, every route
(dense / compressed / tiered / sharded ring), and the persisted tile
autotuner's adopt-on-restart contract.
"""

import json
import os

import numpy as np
import pytest

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.engine.encoding import (
    PACK_BITS,
    pack_bool_words,
    pack_enabled,
    packed_words,
)
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.telemetry.instruments import eval_flight

from test_engine_tiled import CASES, fuzz_problem, full_grids

#: the fuzz seeds every route must hold bit-identity on (the same
#: generator `make fuzz` drives: dense + tiered + CIDR-heavy cases)
FUZZ_SEEDS = range(8)


def _engines_packed_unpacked(monkeypatch, policy, pods, namespaces, **kw):
    """(packed, unpacked) engines over one problem — the kill-switch
    pair every parity test diffs."""
    monkeypatch.setenv("CYCLONUS_PACK", "1")
    packed = TpuPolicyEngine(policy, pods, namespaces, **kw)
    monkeypatch.setenv("CYCLONUS_PACK", "0")
    unpacked = TpuPolicyEngine(policy, pods, namespaces, **kw)
    monkeypatch.setenv("CYCLONUS_PACK", "1")
    return packed, unpacked


class TestPackPrimitives:
    @pytest.mark.parametrize("t", [1, 5, 31, 32, 33, 64, 70, 257])
    def test_numpy_jnp_twins_bit_identical(self, t):
        import jax.numpy as jnp

        from cyclonus_tpu.engine.kernel import pack_bool_words_jnp

        rng = np.random.default_rng(t)
        a = rng.random((t, 6, 3)) > 0.5
        for axis in (0, 1, 2):
            want = pack_bool_words(a, axis=axis)
            got = np.asarray(pack_bool_words_jnp(jnp.asarray(a), axis=axis))
            assert want.dtype == np.int32
            assert np.array_equal(want, got)

    def test_pack_round_trips_every_bit(self):
        rng = np.random.default_rng(7)
        a = rng.random((70, 9)) > 0.3
        words = pack_bool_words(a)  # [W, 9]
        assert words.shape == (packed_words(70), 9)
        # unpack by hand: bit b of word w is element w * 32 + b
        back = np.zeros_like(a)
        uw = words.view(np.uint32)
        for i in range(70):
            back[i] = (uw[i // PACK_BITS] >> np.uint32(i % PACK_BITS)) & 1
        assert np.array_equal(back, a)

    @pytest.mark.parametrize(
        "t,a,b",
        [
            (1, 3, 4),
            (31, 5, 7),
            (32, 5, 7),
            (33, 5, 7),
            (67, 12, 20),
            (3199, 9, 11),  # the cidr-40k-20k-x4 ring's depth: 100 words
            (70, 1, 20),
            (70, 12, 1),
            (64, 1, 1),
        ],
    )
    def test_packed_any_equals_bool_contraction(self, t, a, b):
        import jax.numpy as jnp

        from cyclonus_tpu.engine.kernel import packed_any, pack_bool_words_jnp

        rng = np.random.default_rng(3 + t)
        # thin enough that some cells of the deep cases stay False
        x = rng.random((t, a)) > 1 - 0.4 / np.sqrt(t)  # [T, A]
        y = rng.random((t, b)) > 1 - 0.4 / np.sqrt(t)  # [T, B]
        want = (x.astype(np.int64).T @ y.astype(np.int64)) > 0
        assert want.size < 30 or (want.any() and not want.all())
        got = np.asarray(
            packed_any(
                pack_bool_words_jnp(jnp.asarray(x)),
                pack_bool_words_jnp(jnp.asarray(y)),
            )
        )
        assert got.dtype == np.bool_ and got.shape == (a, b)
        assert np.array_equal(want, got)

    @pytest.mark.parametrize("bit", [0, 30, 31])
    def test_packed_any_sees_one_shared_bit_alone(self, bit):
        """One bit of one word set on both sides and nothing else: bit
        31 is the int32 sign, and the contraction must see it as any
        other (`!= 0`, never `> 0`)."""
        import jax.numpy as jnp

        from cyclonus_tpu.engine.kernel import packed_any

        word = np.int32(-(2**31)) if bit == 31 else np.int32(1 << bit)
        a_pk = np.zeros((3, 4), np.int32)
        b_pk = np.zeros((3, 5), np.int32)
        a_pk[1, 2] = word
        b_pk[1, 3] = word
        b_pk[2, 0] = word  # another word: shares nothing with a_pk
        got = np.asarray(packed_any(jnp.asarray(a_pk), jnp.asarray(b_pk)))
        want = np.zeros((4, 5), bool)
        want[2, 3] = True
        assert np.array_equal(got, want)

    @pytest.mark.parametrize("zero", ["a", "b", "both"])
    def test_packed_any_of_all_zero_operands_is_false(self, zero):
        import jax.numpy as jnp

        from cyclonus_tpu.engine.kernel import packed_any

        ones = np.full((2, 6), -1, np.int32)
        a_pk = np.zeros_like(ones) if zero in ("a", "both") else ones
        b_pk = np.zeros_like(ones) if zero in ("b", "both") else ones
        got = np.asarray(packed_any(jnp.asarray(a_pk), jnp.asarray(b_pk)))
        assert got.shape == (6, 6) and not got.any()

    def test_packed_any_is_one_reduction_and_no_loop(self):
        """The contraction is ONE reduce over the words of the broadcast
        AND (kernel.PACKED_CONTRACTION): a scan or a while would carry
        an [A, B] accumulator through HBM once a word again (PERF.md
        section 6, PR 35), and must not come back unnoticed."""
        import jax
        import jax.numpy as jnp

        from cyclonus_tpu.engine.kernel import PACKED_CONTRACTION, packed_any

        assert PACKED_CONTRACTION == "any=reduce"
        jaxpr = jax.make_jaxpr(packed_any)(
            jax.ShapeDtypeStruct((100, 64), jnp.int32),
            jax.ShapeDtypeStruct((100, 48), jnp.int32),
        )

        def primitives(j):
            for eqn in j.eqns:
                yield eqn.primitive.name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from primitives(sub)

        names = list(primitives(jaxpr.jaxpr))
        assert not {"scan", "while", "cond"} & set(names), names
        assert sum(n.startswith("reduce") for n in names) == 1, names

    def test_pack_enabled_resolution(self, monkeypatch):
        monkeypatch.delenv("CYCLONUS_PACK", raising=False)
        assert pack_enabled() is True  # auto default: on
        monkeypatch.setenv("CYCLONUS_PACK", "0")
        assert pack_enabled() is False
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        assert pack_enabled() is True
        monkeypatch.setenv("CYCLONUS_PACK", "bogus")
        with pytest.raises(ValueError, match="CYCLONUS_PACK"):
            pack_enabled()


class TestPackedFuzzParity:
    """packed == unpacked == scalar oracle, across the same seeded
    generator `make fuzz` gates — dense, class-compressed, tiered, and
    the 8-virtual-device overlapped mesh route."""

    @pytest.mark.parametrize("seed", FUZZ_SEEDS)
    def test_grid_and_counts_routes(self, seed, monkeypatch):
        from cyclonus_tpu.tiers.fuzz import (
            _engine_table,
            _oracle_table,
            _table_from_grid,
            build_fuzz_case,
        )

        fc = build_fuzz_case(seed)
        policy = build_network_policies(fc.simplify, fc.netpols)
        want = _oracle_table(policy, fc.tiers, fc.pods, fc.namespaces, fc.cases)
        packed, unpacked = _engines_packed_unpacked(
            monkeypatch, policy, fc.pods, fc.namespaces, tiers=fc.tiers
        )
        got = _engine_table(packed, fc.cases)
        assert np.array_equal(got, want), f"seed {seed}: packed grid != oracle"
        assert np.array_equal(
            _engine_table(unpacked, fc.cases), got
        ), f"seed {seed}: packed != unpacked grid"

        # counts: XLA tile loop (packed contraction) vs oracle sums
        sums = {
            "ingress": int(want[..., 0].sum()),
            "egress": int(want[..., 1].sum()),
            "combined": int(want[..., 2].sum()),
        }
        counts = packed.evaluate_grid_counts(fc.cases, block=8, backend="xla")
        assert {k: counts[k] for k in sums} == sums, f"seed {seed}: xla counts"
        # pallas counts (the packed kernel; fused tier epilogue when the
        # case is tiered) — explicit backend, so a tiered case that
        # cannot ride the fused kernel would raise rather than reroute
        pcounts = packed.evaluate_grid_counts(fc.cases, backend="pallas")
        assert {k: pcounts[k] for k in sums} == sums, (
            f"seed {seed}: pallas packed counts"
        )

        # sharded route: the packed bundle rides the ppermute ring
        ring = _table_from_grid(
            packed.evaluate_grid_sharded(fc.cases, schedule="ring")
        )
        assert np.array_equal(ring, want), f"seed {seed}: packed ring grid"

    @pytest.mark.parametrize("seed", [0, 3, 5])
    def test_compressed_route(self, seed, monkeypatch):
        from cyclonus_tpu.tiers.fuzz import (
            _engine_table,
            _oracle_table,
            build_fuzz_case,
        )

        fc = build_fuzz_case(seed)
        policy = build_network_policies(fc.simplify, fc.netpols)
        want = _oracle_table(policy, fc.tiers, fc.pods, fc.namespaces, fc.cases)
        monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", "1")
        packed, unpacked = _engines_packed_unpacked(
            monkeypatch, policy, fc.pods, fc.namespaces, tiers=fc.tiers
        )
        assert packed._class_state is not None
        got = _engine_table(packed, fc.cases)
        assert np.array_equal(got, want), f"seed {seed}: packed compressed grid"
        assert np.array_equal(_engine_table(unpacked, fc.cases), want)
        sums = {
            "ingress": int(want[..., 0].sum()),
            "egress": int(want[..., 1].sum()),
            "combined": int(want[..., 2].sum()),
        }
        counts = packed.evaluate_grid_counts(fc.cases, block=8)
        assert {k: counts[k] for k in sums} == sums


class TestPackedFixtureParity:
    """Bundled example fixtures + the feature fixtures through the
    packed/unpacked pair (the same clusters the main parity gate
    uses)."""

    def test_feature_fixture_grids(self, monkeypatch):
        from test_engine_parity import default_cluster, oracle_grid

        for seed in (2, 9, 17):
            policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=7)
            packed, unpacked = _engines_packed_unpacked(
                monkeypatch, policy, pods, namespaces
            )
            want = oracle_grid(policy, pods, namespaces, CASES)
            for engine in (packed, unpacked):
                grid = engine.evaluate_grid(CASES)
                for qi, case in enumerate(CASES):
                    for si in range(len(pods)):
                        for di in range(len(pods)):
                            got = grid.job_verdict(qi, si, di)
                            assert got == want[(qi, si, di)], (
                                f"{case} {si}->{di}: {got} != "
                                f"{want[(qi, si, di)]}"
                            )
        # the feature cluster itself exercises ip/selector variety;
        # default_cluster is the shared base those fixtures extend
        assert len(default_cluster()[0]) > 0

    def test_bundled_example_fixtures(self, monkeypatch):
        """The bundled example-policy library (all 21 reference canned
        policies at once) + the pathological set through both plans:
        packed and unpacked grids and counts must agree exactly."""
        from cyclonus_tpu.kube import pathological as pa
        from cyclonus_tpu.kube.examples import all_examples
        from test_engine_parity import default_cluster

        pods, namespaces = default_cluster()
        namespaces["other"] = dict(pa.LABELS_AB)
        pods = pods + [
            (pa.NAMESPACE, "pp-a", dict(pa.LABELS_AB), "10.0.0.1"),
            ("other", "pp-c", dict(pa.LABELS_EF), "192.168.242.1"),
        ]
        namespaces.setdefault(pa.NAMESPACE, {"ns": pa.NAMESPACE})
        for netpols in (
            all_examples(),
            list(pa.ALL_PATHOLOGICAL_POLICIES),
        ):
            policy = build_network_policies(True, netpols)
            packed, unpacked = _engines_packed_unpacked(
                monkeypatch, policy, pods, namespaces
            )
            a = packed.evaluate_grid_counts(CASES, block=8, backend="xla")
            b = unpacked.evaluate_grid_counts(CASES, block=8, backend="xla")
            assert a == b
            ga = packed.evaluate_grid(CASES)
            gb = unpacked.evaluate_grid(CASES)
            for name in ("ingress", "egress", "combined"):
                assert np.array_equal(
                    np.asarray(getattr(ga, name)),
                    np.asarray(getattr(gb, name)),
                )


class TestFusedEpilogues:
    """Fused-epilogue vs split-epilogue bit-identity: the Pallas kernel
    that resolves the tier lattice / applies the class-gather weighting
    in VMEM must reproduce the split XLA programs exactly."""

    def test_fused_tier_counts_equal_split(self, monkeypatch):
        from cyclonus_tpu.tiers.fuzz import build_fuzz_case

        tiered_seeds = []
        for seed in range(32):
            fc = build_fuzz_case(seed)
            if fc.tiers is not None:
                tiered_seeds.append(fc)
            if len(tiered_seeds) >= 3:
                break
        assert tiered_seeds, "generator produced no tiered case in 32 seeds"
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        for fc in tiered_seeds:
            policy = build_network_policies(fc.simplify, fc.netpols)
            engine = TpuPolicyEngine(
                policy, fc.pods, fc.namespaces, tiers=fc.tiers
            )
            split = engine.evaluate_grid_counts(
                fc.cases, block=8, backend="xla"
            )
            fused = engine.evaluate_grid_counts(fc.cases, backend="pallas")
            assert fused == split, f"seed {fc.seed}"

    def test_fused_class_rowsums_equal_split(self, monkeypatch):
        from cyclonus_tpu.engine.tiled import (
            class_rowsums_plan,
            evaluate_grid_counts_classes,
        )

        monkeypatch.setenv("CYCLONUS_PACK", "1")
        monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", "1")
        policy, pods, namespaces = fuzz_problem(21, n_extra_pods=12)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        assert engine._class_state is not None
        pc = engine._class_state["classes"]
        tensors = engine._class_state["ctensors"]
        w, _, _ = class_rowsums_plan(tensors, pc.n_classes, pc.class_size)
        q_cases = np.stack(engine._port_case_arrays(CASES))
        n = len(pods)
        with eval_flight("counts.classes", n, len(CASES)) as fl:
            split, _ = evaluate_grid_counts_classes(
                fl, tensors, w, q_cases, pc.n_classes, pc.class_size, n,
                kernel="xla",
            )
        with eval_flight("counts.classes", n, len(CASES)) as fl:
            fused, _ = evaluate_grid_counts_classes(
                fl, tensors, w, q_cases, pc.n_classes, pc.class_size, n,
                kernel="pallas",
            )
        assert fused == split
        # and both equal the dense truth
        ing, egr, comb = full_grids(engine, CASES)
        assert split["combined"] == int(comb.sum())

    def test_fused_class_route_respects_tier_ceiling(self, monkeypatch):
        """The class-counts route shares the SAME static-unroll ceiling
        as the dense route (one packed_tier_eligible implementation):
        an oversized tier rule axis must refuse the fused kernel."""
        import cyclonus_tpu.engine.pallas_kernel as pk

        from cyclonus_tpu.engine.tiled import (
            class_rowsums_plan,
            evaluate_grid_counts_classes,
        )
        from cyclonus_tpu.tiers.fuzz import build_fuzz_case

        fc = None
        for seed in range(32):
            c = build_fuzz_case(seed)
            if c.tiers is not None:
                fc = c
                break
        assert fc is not None
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        monkeypatch.setenv("CYCLONUS_CLASS_COMPRESS", "1")
        policy = build_network_policies(fc.simplify, fc.netpols)
        engine = TpuPolicyEngine(policy, fc.pods, fc.namespaces, tiers=fc.tiers)
        if engine._class_state is None:
            pytest.skip("fuzz case compressed to nothing")
        pc = engine._class_state["classes"]
        tensors = engine._class_state["ctensors"]
        w, _, _ = class_rowsums_plan(tensors, pc.n_classes, pc.class_size)
        q_cases = np.stack(engine._port_case_arrays(fc.cases))
        monkeypatch.setattr(pk, "PACKED_TIER_MAX_ROWS", 1)
        n, q = len(fc.pods), len(fc.cases)
        with pytest.raises(ValueError, match="static-unroll ceiling"):
            with eval_flight("counts.classes", n, q) as fl:
                evaluate_grid_counts_classes(
                    fl, tensors, w, q_cases, pc.n_classes, pc.class_size, n,
                    kernel="pallas",
                )
        # auto routes to the XLA body and stays correct
        with eval_flight("counts.classes", n, q) as fl:
            counts, _ = evaluate_grid_counts_classes(
                fl, tensors, w, q_cases, pc.n_classes, pc.class_size, n
            )
        want = engine.evaluate_grid_counts(fc.cases, block=8, backend="xla")
        assert counts["combined"] == want["combined"]

    def test_fused_tier_rejects_oversized_rule_axis(self, monkeypatch):
        """Past the static-unroll ceiling the fused kernel must NOT
        engage: auto reroutes to XLA, explicit pallas fails loudly."""
        import cyclonus_tpu.engine.pallas_kernel as pk

        from cyclonus_tpu.tiers.fuzz import build_fuzz_case

        fc = None
        for seed in range(32):
            c = build_fuzz_case(seed)
            if c.tiers is not None:
                fc = c
                break
        assert fc is not None
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        monkeypatch.setattr(pk, "PACKED_TIER_MAX_ROWS", 1)
        policy = build_network_policies(fc.simplify, fc.netpols)
        engine = TpuPolicyEngine(policy, fc.pods, fc.namespaces, tiers=fc.tiers)
        with pytest.raises(ValueError, match="precedence-tier"):
            engine.evaluate_grid_counts(fc.cases, backend="pallas")
        auto = engine.evaluate_grid_counts(fc.cases, block=8)
        xla = engine.evaluate_grid_counts(fc.cases, block=8, backend="xla")
        assert auto == xla


class TestKillSwitch:
    """The CYCLONUS_PACK=0 regression: the legacy representation comes
    back exactly — no packed twins anywhere, identical verdicts."""

    def test_unpacked_engine_has_no_packed_twins(self, monkeypatch):
        from cyclonus_tpu.engine.tiled import _precompute

        policy, pods, namespaces = fuzz_problem(4, n_extra_pods=5)
        monkeypatch.setenv("CYCLONUS_PACK", "0")
        engine = TpuPolicyEngine(policy, pods, namespaces)
        assert engine._pack is False
        pre = _precompute(engine._tensors_with_cases(CASES), False)
        assert "tallow_pk" not in pre["egress"]
        assert "tallow_bf" in pre["egress"]
        pre_packed = _precompute(engine._tensors_with_cases(CASES), True)
        assert "tallow_pk" in pre_packed["egress"]
        assert "tallow_bf" not in pre_packed["egress"]

    def test_kill_switch_counts_identical(self, monkeypatch):
        policy, pods, namespaces = fuzz_problem(13, n_extra_pods=9)
        packed, unpacked = _engines_packed_unpacked(
            monkeypatch, policy, pods, namespaces
        )
        for backend in ("xla", "pallas"):
            a = packed.evaluate_grid_counts(CASES, block=8, backend=backend)
            b = unpacked.evaluate_grid_counts(CASES, block=8, backend=backend)
            assert a == b, backend
        # pack detail reflects the plan either way
        assert packed.pack_stats()["active"] is True
        assert unpacked.pack_stats()["active"] is False
        assert packed.pack_stats()["dtype"] == "packed32"


class TestPersistedAutotune:
    """The tile autotuner's persistence contract: the first process
    searches (min-of-N, noise-floored) and persists the winner keyed by
    (shape bucket, mesh, dtype plan); a second process ADOPTS it with
    ZERO candidate searches; a corrupt or stale cache file degrades to
    a fresh search, never an error."""

    def _tuned_engine(self, monkeypatch, tmp_path, seed=35):
        import cyclonus_tpu.engine.pallas_kernel as pk

        cache = tmp_path / "autotune.json"
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_CACHE", str(cache))
        monkeypatch.setenv("CYCLONUS_AUTOTUNE", "1")
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_REPS", "1")
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_ROUNDS", "2")
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        # tiny tile candidates so a test-sized cluster has a real
        # 2-candidate search space
        monkeypatch.setattr(pk, "PACKED_TILE_CANDIDATES", ((8, 8), (16, 8)))
        policy, pods, namespaces = fuzz_problem(seed, n_extra_pods=10)
        return cache, policy, pods, namespaces

    def _reach_steady(self, engine):
        out = None
        for _ in range(4):
            out = engine.evaluate_grid_counts(CASES, backend="pallas")
        return out

    def test_search_persists_and_restart_adopts(self, monkeypatch, tmp_path):
        from cyclonus_tpu.telemetry.instruments import (
            AUTOTUNE_CACHE,
            AUTOTUNE_SEARCHES,
        )

        cache, policy, pods, namespaces = self._tuned_engine(
            monkeypatch, tmp_path
        )
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, block=8, backend="xla")
        searches0 = AUTOTUNE_SEARCHES.value()
        assert self._reach_steady(engine) == want
        assert AUTOTUNE_SEARCHES.value() == searches0 + 1
        choice = engine.pack_stats()["winner"]
        assert choice is not None and choice["kernel"] == "packed"
        assert engine._autotune_stats["source"] == "search"
        assert engine._autotune_stats["search_s"] >= 0
        assert len(engine._autotune_stats["candidates"]) == 2
        # the winner landed on disk under the versioned schema
        doc = json.loads(cache.read_text())
        assert doc["v"] >= 1
        (entry,) = doc["entries"].values()
        assert entry["winner"]["kernel"] == "packed"
        assert entry["winner"]["bs"] == choice["bs"]

        # "second process": a fresh engine over the same problem adopts
        # the persisted winner with NO candidate search
        hits0 = AUTOTUNE_CACHE.value(outcome="hit")
        engine2 = TpuPolicyEngine(policy, pods, namespaces)
        assert self._reach_steady(engine2) == want
        assert AUTOTUNE_SEARCHES.value() == searches0 + 1  # zero new searches
        assert AUTOTUNE_CACHE.value(outcome="hit") == hits0 + 1
        assert engine2.pack_stats()["winner"] == choice
        assert engine2._autotune_stats["source"] == "cache"

    def test_corrupt_cache_degrades_to_fresh_search(
        self, monkeypatch, tmp_path
    ):
        from cyclonus_tpu.telemetry.instruments import AUTOTUNE_SEARCHES

        cache, policy, pods, namespaces = self._tuned_engine(
            monkeypatch, tmp_path, seed=36
        )
        # truncated JSON: degrade, do not die
        cache.write_text('{"v": 1, "entries": {"x": {"winn')
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, block=8, backend="xla")
        s0 = AUTOTUNE_SEARCHES.value()
        assert self._reach_steady(engine) == want
        assert AUTOTUNE_SEARCHES.value() == s0 + 1  # fresh search ran
        # and the search REPLACED the corrupt file with a valid one
        doc = json.loads(cache.read_text())
        assert doc["v"] >= 1 and doc["entries"]

    def test_stale_version_and_malformed_winner_ignored(
        self, monkeypatch, tmp_path
    ):
        from cyclonus_tpu.engine import autotune as at

        cache = tmp_path / "autotune.json"
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_CACHE", str(cache))
        key = at.make_key({"n": 1}, "cpu", "packed32")
        # stale version
        cache.write_text(json.dumps({"v": 9999, "entries": {key: {
            "winner": {"kernel": "packed", "bs": 8, "bd": 8}}}}))
        assert at.load_winner(key) is None
        # right version, unknown kernel
        cache.write_text(json.dumps({"v": at.CACHE_VERSION, "entries": {key: {
            "winner": {"kernel": "warp-drive"}}}}))
        assert at.load_winner(key) is None
        # right version, malformed tile
        cache.write_text(json.dumps({"v": at.CACHE_VERSION, "entries": {key: {
            "winner": {"kernel": "packed", "bs": "big"}}}}))
        assert at.load_winner(key) is None
        # valid entry round-trips
        assert at.store_winner(key, {"kernel": "packed", "bs": 8, "bd": 8})
        assert at.load_winner(key) == {"kernel": "packed", "bs": 8, "bd": 8}
        # disabled path: no reads, no writes
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_CACHE", "0")
        assert at.cache_path() is None
        assert at.load_winner(key) is None
        assert at.store_winner(key, {"kernel": "default"}) is False

    def test_tuned_tile_dispatch_matches_default(self, monkeypatch, tmp_path):
        """The tuned-tile steady-state program produces the same counts
        as the default tile (the autotune can only change SPEED)."""
        cache, policy, pods, namespaces = self._tuned_engine(
            monkeypatch, tmp_path, seed=37
        )
        engine = TpuPolicyEngine(policy, pods, namespaces)
        want = engine.evaluate_grid_counts(CASES, block=8, backend="xla")
        assert self._reach_steady(engine) == want
        # post-tune steady dispatches run the winner and stay identical
        for _ in range(2):
            assert (
                engine.evaluate_grid_counts(CASES, backend="pallas") == want
            )
        piped = engine.counts_pipelined_eval_s(CASES, reps=2)
        assert piped is not None
        _dt, counts = piped
        assert {k: counts[k] for k in ("ingress", "egress", "combined")} == {
            k: want[k] for k in ("ingress", "egress", "combined")
        }
