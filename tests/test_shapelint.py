"""tools/shapelint.py + utils/contracts.py tests: seeded-violation gates
for SC001-SC004 (each defect class must be caught, each suppression
honored), the clean-run + annotation-count acceptance gate over the
engine/analysis/worker-model paths, the runtime contract twin
(CYCLONUS_SHAPE_CHECK=1 catches a deliberately mis-shaped encoding in a
subprocess; zero overhead when off, pinned by the paired-median
differential), the ip-except mask-guard regression, and the wire-drift
static check."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import shapelint

PRELUDE = """
    import numpy as np
    from dataclasses import dataclass
    from cyclonus_tpu.utils import contracts


    @contracts.checked
    @dataclass
    class Enc:
        ids: np.ndarray = contracts.tensor("(N, L) int32", sentinel="-1=pad")
        ips: np.ndarray = contracts.tensor(
            "(N,) uint32", sentinel="0=invalid", mask="ip_valid"
        )
        ip_valid: np.ndarray = contracts.tensor("(N,) bool")
"""


def _lint_source(tmp_path, source: str, prelude: str = PRELUDE):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(prelude).lstrip() + textwrap.dedent(source))
    findings, _stats = shapelint.lint_paths([str(p)])
    return findings


def _codes(findings):
    return [f.code for f in findings]


class TestSC001ShapeContract:
    def test_wrong_rank_at_constructor(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def build(n):
                return Enc(
                    ids=np.zeros((n,), dtype=np.int32),
                    ips=np.zeros((n,), np.uint32),
                    ip_valid=np.ones(n, dtype=bool),
                )
            """,
        )
        assert _codes(findings) == ["SC001"]
        assert "rank" in findings[0].message

    def test_wrong_dtype_through_call_site_inference(self, tmp_path):
        """One level of return inference: the helper's dtype travels to
        the constructor check."""
        findings = _lint_source(
            tmp_path,
            """
            def helper(n):
                return np.full((n, 4), -1, dtype=np.float32)

            def build(n):
                return Enc(
                    ids=helper(n),
                    ips=np.zeros((n,), np.uint32),
                    ip_valid=np.ones(n, dtype=bool),
                )
            """,
        )
        assert _codes(findings) == ["SC001"]
        assert "float32" in findings[0].message

    def test_consistent_build_is_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def helper(n):
                return np.full((n, 4), -1, dtype=np.int32)

            def build(n):
                return Enc(
                    ids=helper(n),
                    ips=np.zeros((n,), np.uint32),
                    ip_valid=np.ones(n, dtype=bool),
                )
            """,
        )
        assert findings == []

    def test_rank_changing_implicit_broadcast(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(
                a,  # shape: (N,) int32
                b,  # shape: (N, L) int32
            ):
                return a == b
            """,
        )
        assert _codes(findings) == ["SC001"]
        assert "broadcast" in findings[0].message

    def test_explicit_index_marks_intent(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(
                a,  # shape: (N,) int32
                b,  # shape: (N, L) int32
            ):
                return a[:, None] == b
            """,
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def build(n):
                return Enc(
                    ids=np.zeros((n,), dtype=np.int32),  # shapelint: ignore[SC001]
                    ips=np.zeros((n,), np.uint32),
                    ip_valid=np.ones(n, dtype=bool),
                )
            """,
        )
        assert findings == []


class TestSC002DtypePromotion:
    def test_cross_signedness_compare(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(n):
                a = np.zeros((n,), dtype=np.uint32)
                b = np.zeros((n,), dtype=np.int32)
                return a == b
            """,
        )
        assert _codes(findings) == ["SC002"]
        assert "uint32 vs int32" in findings[0].message

    def test_explicit_cast_is_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(n):
                a = np.zeros((n,), dtype=np.uint32)
                b = np.zeros((n,), dtype=np.int32)
                return a == b.astype(np.uint32)
            """,
        )
        assert findings == []

    def test_declared_dtypes_cross_module_fields(self, tmp_path):
        """The contract registry feeds the dtype check: dict-key access
        to a declared field carries its declared dtype."""
        findings = _lint_source(
            tmp_path,
            """
            def f(enc, raw):
                ids = np.zeros((4,), dtype=np.int32)
                return enc["ips"] & ids
            """,
        )
        assert _codes(findings) == ["SC002"]

    def test_bool_arithmetic_upcast(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(n):
                a = np.zeros((n,), dtype=bool)
                b = np.ones((n,), dtype=bool)
                return a + b
            """,
        )
        assert _codes(findings) == ["SC002"]
        assert "bool" in findings[0].message

    def test_bare_float_literal(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f():
                return np.array([0.5, 1.5])
            """,
        )
        assert _codes(findings) == ["SC002"]
        assert "float" in findings[0].message

    def test_pinned_dtype_is_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f():
                return np.array([0.5, 1.5], dtype=np.float32)
            """,
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(n):
                a = np.zeros((n,), dtype=np.uint32)
                b = np.zeros((n,), dtype=np.int32)
                return a == b  # shapelint: ignore[SC002]
            """,
        )
        assert findings == []


class TestSC003Sentinel:
    def test_masked_compare_without_mask(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(enc, raw):
                return enc.ips == raw
            """,
        )
        assert _codes(findings) == ["SC003"]
        assert "ip_valid" in findings[0].message

    def test_mask_in_same_statement_is_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(enc, raw):
                return (enc.ips == raw) & enc.ip_valid
            """,
        )
        assert findings == []

    def test_wrong_sentinel_fill(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def build(n):
                return Enc(
                    ids=np.full((n, 4), -2, dtype=np.int32),
                    ips=np.zeros((n,), np.uint32),
                    ip_valid=np.ones(n, dtype=bool),
                )
            """,
        )
        assert _codes(findings) == ["SC003"]
        assert "-2" in findings[0].message

    def test_suppression(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(enc, raw):
                return enc.ips == raw  # shapelint: ignore[SC003]
            """,
        )
        assert findings == []


class TestSC004TileAlignment:
    def test_misaligned_literal_lane_dim(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def make(pl):
                return pl.BlockSpec((8, 100), lambda i: (i, 0))
            """,
            prelude="",
        )
        assert _codes(findings) == ["SC004"]
        assert "100" in findings[0].message

    def test_unprovable_round_math_lane_dim(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def make(pl, n):
                g = -(-n // 96) * 96
                return pl.BlockSpec((8, g), lambda i: (i, 0))
            """,
            prelude="",
        )
        assert _codes(findings) == ["SC004"]

    def test_correct_round_up_is_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def make(pl, n):
                g = -(-n // 128) * 128
                return pl.BlockSpec((8, g), lambda i: (i, 0))
            """,
            prelude="",
        )
        assert findings == []

    def test_round_up_through_helper_and_unpack(self, tmp_path):
        """The prover follows one level of call returns, including
        tuple unpacking and `x *= 2` augmentation (the _tiles_for
        shape)."""
        findings = _lint_source(
            tmp_path,
            """
            BS = 512

            def tiles(n):
                bs = BS
                if n > bs:
                    bs *= 2
                return bs, 128

            def make(pl, n):
                bs, kt = tiles(n)
                return pl.BlockSpec((kt, bs), lambda i: (i, 0))
            """,
            prelude="",
        )
        assert findings == []

    def test_tile_comment_assertion(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(n):
                w = n + 128 - n % 128  # tile: 128
                return w
            """,
            prelude="",
        )
        assert _codes(findings) == ["SC004"]
        assert "tile: 128" in findings[0].message

    def test_tile_comment_discharged(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(n):
                w = ((n + 127) // 128) * 128  # tile: 128
                return w
            """,
            prelude="",
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def make(pl):
                return pl.BlockSpec((8, 100), lambda i: (i, 0))  # shapelint: ignore[SC004]
            """,
            prelude="",
        )
        assert findings == []


class TestSC004PackedLaneArithmetic:
    """The packed-word (32-per-word) round-ups of the bit-packed kernel
    must be prover-discharged like the 128-lane lane_round_up — both
    locally and THROUGH IMPORTS (the cross-file registry resolution)."""

    def _lint_two(self, tmp_path, a_src, b_src):
        import textwrap

        (tmp_path / "enc.py").write_text(textwrap.dedent(a_src))
        (tmp_path / "use.py").write_text(textwrap.dedent(b_src))
        findings, _stats = shapelint.lint_paths(
            [str(tmp_path / "enc.py"), str(tmp_path / "use.py")]
        )
        return findings

    def test_packed_round_up_discharges_locally(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            PACK_BITS = 32

            def packed_words(n):
                return -(-max(int(n), 1) // PACK_BITS)

            def f(t):
                total = packed_words(t) * PACK_BITS  # tile: 32
                return total
            """,
            prelude="",
        )
        assert findings == []

    def test_packed_round_up_discharges_through_import(self, tmp_path):
        findings = self._lint_two(
            tmp_path,
            """
            PACK_BITS = 32

            def packed_words(n):
                return -(-max(int(n), 1) // PACK_BITS)
            """,
            """
            from enc import PACK_BITS, packed_words

            def f(t):
                total = packed_words(t) * PACK_BITS  # tile: 32
                return total
            """,
        )
        assert findings == []

    def test_imported_helper_proves_lane_dim(self, tmp_path):
        # a BlockSpec lane dim built from an IMPORTED round-up helper
        # (the pallas_kernel.lane_round_up pattern used cross-module)
        findings = self._lint_two(
            tmp_path,
            """
            def lane_round_up(n):
                return -(-max(int(n), 1) // 128) * 128
            """,
            """
            from enc import lane_round_up

            def make(pl, w):
                lanes = lane_round_up(w + 1)  # tile: 128
                return pl.BlockSpec((8, lanes), lambda i: (i, 0))
            """,
        )
        assert findings == []

    def test_hand_rolled_packed_round_up_flags(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def f(t):
                total = t + 32 - t % 32  # tile: 32
                return total
            """,
            prelude="",
        )
        assert _codes(findings) == ["SC004"]
        assert "tile: 32" in findings[0].message

    def test_imported_const_wrong_multiple_flags(self, tmp_path):
        # cross-file constants must prove the RIGHT divisibility, not
        # rubber-stamp: words * 32 is not a multiple of 128
        findings = self._lint_two(
            tmp_path,
            """
            PACK_BITS = 32
            """,
            """
            from enc import PACK_BITS

            def f(w):
                bits = w * PACK_BITS  # tile: 128
                return bits
            """,
        )
        assert _codes(findings) == ["SC004"]

    def test_live_packed_annotations_discharge(self):
        # the real engine modules: the packed helpers' own `# tile: 32`
        # assertions must hold with zero SC004 findings
        findings, stats = shapelint.lint_paths(
            [
                os.path.join(REPO, "cyclonus_tpu", "engine", f)
                for f in ("encoding.py", "kernel.py", "pallas_kernel.py")
            ]
        )
        assert [f for f in findings if f.code == "SC004"] == []


class TestWireDrift:
    WIRE_PRELUDE = """
        from typing import ClassVar, Dict
        from cyclonus_tpu.utils import contracts
    """

    def test_unconditional_optional_and_missing_required(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            class Msg:
                WIRE: ClassVar[Dict[str, contracts.WireField]] = {
                    "A": contracts.wire(str),
                    "B": contracts.wire(float, optional=True),
                    "C": contracts.wire(str),
                }

                def to_dict(self):
                    return {"A": self.a, "B": self.b, "X": 1}
            """,
            prelude=self.WIRE_PRELUDE,
        )
        assert _codes(findings) == ["SC001", "SC001", "SC001"]
        msgs = " ".join(f.message for f in findings)
        assert "'X'" in msgs and "'B'" in msgs and "'C'" in msgs

    def test_compliant_emit_is_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            class Msg:
                WIRE: ClassVar[Dict[str, contracts.WireField]] = {
                    "A": contracts.wire(str),
                    "B": contracts.wire(float, optional=True),
                }

                def to_dict(self):
                    d = {"A": self.a}
                    if self.b is not None:
                        d["B"] = self.b
                    return d
            """,
            prelude=self.WIRE_PRELUDE,
        )
        assert findings == []

    def test_worker_model_optional_field_drift_is_caught(self, tmp_path):
        """The compat gate the wire suite relies on: emitting
        Result.LatencyMs unconditionally (an optional-field contract
        change) must be flagged when worker/model.py drifts.  The WIRE
        tables are registry projections now (worker/wireregistry.py),
        not literals shapelint can extract — so the gate on the REAL
        model moved to wirelint's WR001; this test pins it against a
        drifted copy of the real tree (model + registry + golden)."""
        import sys as _sys

        _sys.path.insert(0, os.path.join(REPO, "tools"))
        import wirelint

        worker = os.path.join(REPO, "cyclonus_tpu", "worker")
        src = open(os.path.join(worker, "model.py")).read()
        drifted = src.replace(
            "        if self.latency_ms is not None:\n"
            "            d[\"LatencyMs\"] = self.latency_ms\n",
            "        d[\"LatencyMs\"] = self.latency_ms\n",
        )
        assert drifted != src, "model.py emit site moved; update this test"
        pkg = tmp_path / "worker_drifted"
        pkg.mkdir()
        (pkg / "model.py").write_text(drifted)
        for name in ("wireregistry.py", "wire_schema.json"):
            (pkg / name).write_text(open(os.path.join(worker, name)).read())
        findings, _ = wirelint.lint_paths([str(pkg)])
        assert any(
            f.code == "WR001" and "LatencyMs" in f.message
            and "unconditionally" in f.message for f in findings
        ), findings


class TestCleanRun:
    PATHS = [
        os.path.join(REPO, "cyclonus_tpu", "engine"),
        os.path.join(REPO, "cyclonus_tpu", "analysis"),
        os.path.join(REPO, "cyclonus_tpu", "worker", "model.py"),
    ]

    def test_pipeline_is_clean_with_live_annotations(self):
        """The acceptance gate: shapelint exits clean over the encoding
        -> kernel pipeline + wire model with >= 20 live contract
        annotations (ISSUE 5 floor; the codebase carries far more)."""
        findings, stats = shapelint.lint_paths(self.PATHS)
        assert findings == [], "\n".join(f.render() for f in findings)
        assert stats["contracts"] >= 20, stats

    def test_cli_exit_status(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "shapelint.py"),
             "cyclonus_tpu/engine", "cyclonus_tpu/analysis",
             "cyclonus_tpu/worker/model.py"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "contract annotation(s)" in proc.stderr


class TestRuntimeContracts:
    def test_violation_fires_in_checked_subprocess(self):
        """CYCLONUS_SHAPE_CHECK=1: a deliberately mis-shaped encoding
        raises ContractViolation naming the field path and the observed
        shape/dtype; a real encode stays clean and the contract-check
        counter registers."""
        code = textwrap.dedent(
            """
            import numpy as np
            from cyclonus_tpu.engine.encoding import (
                ClusterEncoding, _Vocab, encode_policy,
            )
            from cyclonus_tpu.matcher.core import Policy
            from cyclonus_tpu.utils.contracts import ContractViolation

            enc = encode_policy(
                Policy(),
                [("ns", "a", {"app": "x"}, "10.0.0.1"), ("ns", "b", {}, "zz")],
                {"ns": {"team": "t"}},
            )
            assert enc.cluster.pod_ip_valid.tolist() == [True, False]
            from cyclonus_tpu.telemetry.metrics import REGISTRY
            text = REGISTRY.render_prometheus() if hasattr(
                REGISTRY, "render_prometheus") else ""
            try:
                ClusterEncoding(
                    vocab=_Vocab(), pod_keys=["ns/a"],
                    pod_ns_id=np.zeros((1, 2), np.int32),  # rank 2, declared (N,)
                    pod_kv=np.full((1, 1), -1, np.int32),
                    pod_key=np.full((1, 1), -1, np.int32),
                    pod_ip=np.zeros(1, np.uint32),
                    pod_ip_valid=np.zeros(1, bool),
                    pod_ips=["10.0.0.1"],
                    ns_kv=np.full((1, 1), -1, np.int32),
                    ns_key=np.full((1, 1), -1, np.int32),
                )
            except ContractViolation as e:
                assert "ClusterEncoding.pod_ns_id" in str(e), e
                assert "(1, 2)" in str(e), e
                print("VIOLATION-OK")
            else:
                raise SystemExit("mis-shaped encoding did not raise")
            """
        )
        env = dict(os.environ, CYCLONUS_SHAPE_CHECK="1", JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "VIOLATION-OK" in proc.stdout

    def test_wire_check_fires_in_checked_subprocess(self):
        code = textwrap.dedent(
            """
            from cyclonus_tpu.worker.model import Request
            from cyclonus_tpu.utils.contracts import ContractViolation
            try:
                Request.from_dict(
                    {"Key": "k", "Protocol": "tcp", "Host": "h", "Port": "80"}
                )
            except ContractViolation as e:
                assert "Request.Port" in str(e), e
                print("WIRE-VIOLATION-OK")
            else:
                raise SystemExit("wrong wire type did not raise")
            """
        )
        env = dict(os.environ, CYCLONUS_SHAPE_CHECK="1", JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "WIRE-VIOLATION-OK" in proc.stdout

    def test_check_off_returns_classes_untouched(self):
        from cyclonus_tpu.engine import encoding
        from cyclonus_tpu.utils import contracts

        assert not contracts.CHECK  # the test process never sets the var
        # checked() returned the classes untouched: the dataclass
        # __init__ is not wrapped (functools.wraps would leave
        # __wrapped__ behind), and args() returned original functions
        from cyclonus_tpu.engine import kernel

        for cls in (
            encoding.ClusterEncoding,
            encoding._DirectionEncoding,
            encoding.PolicyEncoding,
        ):
            assert not hasattr(cls.__init__, "__wrapped__"), cls
        for fn in (
            kernel.selector_match,
            kernel.direction_precompute,
            kernel.port_spec_allows,
        ):
            assert not hasattr(fn, "__wrapped__"), fn
            assert hasattr(fn, "__tensor_contracts__")  # lint metadata rides

    @pytest.mark.parametrize("shape_check, exists", [("", False), ("1", True)])
    def test_check_counter_exists_only_when_checking(self, shape_check, exists):
        """The strip proof: the contract-check counter is created on the
        first check, so a process without CYCLONUS_SHAPE_CHECK that
        encodes and evaluates never has it in its metric registry.  The
        armed arm is the control: the same script registers it."""
        code = textwrap.dedent(
            """
            import random
            from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
            from cyclonus_tpu.matcher import build_network_policies
            from cyclonus_tpu.synthetic import build_synthetic
            from cyclonus_tpu.telemetry.metrics import REGISTRY

            pods, namespaces, policies = build_synthetic(16, 8, random.Random(7))
            engine = TpuPolicyEngine(
                build_network_policies(True, policies), pods, namespaces
            )
            engine.evaluate_grid_counts([PortCase(80, "serve-80-tcp", "TCP")])
            print("COUNTER", "cyclonus_tpu_contract_checks_total" in REGISTRY.snapshot())
            """
        )
        env = dict(os.environ, CYCLONUS_SHAPE_CHECK=shape_check, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert f"COUNTER {exists}" in proc.stdout, proc.stdout

    def test_zero_overhead_when_off(self):
        """<2% on dataclass construction: the contracts-annotated class
        vs a structurally identical plain dataclass.  With checking off
        `checked` returns the class untouched, so both loops run the
        same bytecode — pinned with the same paired-median differential
        as the guards overhead test (budget 2% or the measurement's own
        noise floor, whichever is larger)."""
        import statistics
        from dataclasses import dataclass

        import numpy as np

        from cyclonus_tpu.utils import contracts

        @contracts.checked
        @dataclass
        class Annotated:
            a: np.ndarray = contracts.tensor("(N, L) int32", sentinel="-1=pad")
            b: np.ndarray = contracts.tensor("(N,) uint32")
            c: np.ndarray = contracts.tensor("(N,) bool")

        @dataclass
        class Plain:
            a: np.ndarray
            b: np.ndarray
            c: np.ndarray

        a = np.full((8, 4), -1, np.int32)
        b = np.zeros(8, np.uint32)
        c = np.zeros(8, bool)
        reps = 20000

        def timed(cls):
            t0 = time.perf_counter()
            for _ in range(reps):
                cls(a=a, b=b, c=c)
            return (time.perf_counter() - t0) / reps

        timed(Annotated), timed(Plain)  # warm both code paths
        diffs, plains = [], []
        for i in range(21):
            if i % 2 == 0:
                tg = timed(Annotated)
                tp = timed(Plain)
            else:
                tp = timed(Plain)
                tg = timed(Annotated)
            diffs.append(tg - tp)
            plains.append(tp)
        med = statistics.median(diffs)
        overhead = max(med, 0.0)
        t_plain = statistics.median(plains)
        mad = statistics.median(abs(d - med) for d in diffs)
        noise_floor = 4 * mad / (len(diffs) ** 0.5)
        budget = max(0.02 * t_plain, noise_floor) + 5e-9
        assert overhead < budget, (
            f"contracts cost {overhead * 1e9:.1f} ns/init "
            f"({100 * overhead / t_plain:.2f}% of {t_plain * 1e9:.0f} ns; "
            f"budget {budget * 1e9:.1f} ns)"
        )


class TestIpExceptMaskRegression:
    def test_invalid_pod_never_matches_ip_peer(self):
        """Regression for the in_except mask-guard (the SC003 finding
        the pod_ip contract surfaced in kernel.direction_precompute):
        an ip peer with an except block must (a) block excepted valid
        pods, (b) allow non-excepted valid pods, and (c) never match a
        pod whose IP failed to parse — including via the except term,
        whose old form compared the 0-sentinel as a real address."""
        import jax.numpy as jnp
        import numpy as np

        from cyclonus_tpu.engine.encoding import PEER_IP
        from cyclonus_tpu.engine.kernel import direction_precompute

        # peer 0: 10.0.0.0/8 except 10.1.0.0/16 (and an adversarial
        # peer 1: 0.0.0.0/0 except 0.0.0.0/0, whose except row would
        # "match" the 0-sentinel of an invalid pod)
        enc = {
            "target_ns": jnp.array([0], jnp.int32),
            "target_sel": jnp.array([0], jnp.int32),
            "peer_target": jnp.array([0, 0], jnp.int32),
            "peer_kind": jnp.array([PEER_IP, PEER_IP], jnp.int32),
            "peer_ns_kind": jnp.array([2, 2], jnp.int32),
            "peer_ns_id": jnp.array([-1, -1], jnp.int32),
            "peer_ns_sel": jnp.array([-1, -1], jnp.int32),
            "peer_pod_kind": jnp.array([0, 0], jnp.int32),
            "peer_pod_sel": jnp.array([-1, -1], jnp.int32),
            "ip_base": jnp.array([0x0A000000, 0], jnp.uint32),
            "ip_mask": jnp.array([0xFF000000, 0], jnp.uint32),
            "ip_is_v4": jnp.array([True, True]),
            "ex_base": jnp.array([[0x0A010000], [0]], jnp.uint32),
            "ex_mask": jnp.array([[0xFFFF0000], [0]], jnp.uint32),
            "ex_valid": jnp.array([[True], [True]]),
        }
        pods = ["10.1.2.3", "10.2.2.2", "<unparseable>"]
        pod_ip = np.array([0x0A010203, 0x0A020202, 0], np.uint32)
        pod_ip_valid = np.array([True, True, False])
        pre = direction_precompute(
            enc,
            jnp.ones((1, 3), bool),
            jnp.ones((1, 1), bool),
            jnp.zeros(3, jnp.int32),
            jnp.asarray(pod_ip),
            jnp.asarray(pod_ip_valid),
        )
        got = np.asarray(pre["peer_match"])
        # peer 0: excepted / allowed / invalid
        assert got[0].tolist() == [False, True, False], (pods, got)
        # peer 1: everything in-cidr is excepted; the invalid pod must
        # be False through BOTH terms, not "in cidr but also in except"
        assert got[1].tolist() == [False, False, False], (pods, got)


class TestMakefileWiring:
    def test_make_lint_runs_shapelint(self):
        mk = open(os.path.join(REPO, "Makefile")).read()
        assert "shapelint:" in mk
        lint_rule = mk.split("\nlint:", 1)[1].split("\n\n", 1)[0]
        body = mk.split("\nshapelint:", 1)[1].split("\n\n", 1)[0]
        assert "shapelint" in mk.split("\nlint:", 1)[1].splitlines()[0], (
            "make lint must depend on shapelint"
        )
        assert "tools/shapelint.py" in body
        for target in ("cyclonus_tpu/engine", "cyclonus_tpu/analysis",
                       "cyclonus_tpu/worker/model.py"):
            assert target in body
        assert lint_rule is not None


class TestReviewRegressions:
    def test_bool_matmul_is_sc002(self, tmp_path):
        """bool @ bool stays bool in numpy (every nonzero sum collapses
        to True) — the exact hazard audit.py's astype-before-matmul
        comment names."""
        findings = _lint_source(
            tmp_path,
            """
            def f(n):
                a = np.zeros((n, n), dtype=bool)
                b = np.ones((n, n), dtype=bool)
                return a @ b
            """,
        )
        assert _codes(findings) == ["SC002"]
        assert "matmul" in findings[0].message

    def test_parse_spec_rejects_comma_typo(self):
        """'(N L)' must raise at declaration time, not become a wrong
        rank-1 contract the runtime twin then enforces spuriously."""
        import pytest

        from cyclonus_tpu.utils import contracts

        with pytest.raises(ValueError, match="N L"):
            contracts.parse_spec("(N L) int32")

    def test_result_parse_side_type_drift_is_caught(self):
        """Result.from_dict type-checks PRESENT wire keys under
        CYCLONUS_SHAPE_CHECK=1 (tolerating absent ones, per the compat
        rules), symmetric with Request.from_dict."""
        code = textwrap.dedent(
            """
            from cyclonus_tpu.worker.model import Result
            from cyclonus_tpu.utils.contracts import ContractViolation
            # absent optional keys tolerated
            Result.from_dict({
                "Request": {"Key": "k", "Protocol": "tcp", "Host": "h",
                            "Port": 1},
                "Output": "", "Error": "",
            })
            try:
                Result.from_dict({
                    "Request": {"Key": "k", "Protocol": "tcp", "Host": "h",
                                "Port": 1},
                    "Output": 5, "Error": "",
                })
            except ContractViolation as e:
                assert "Result.Output" in str(e), e
                print("RESULT-DRIFT-OK")
            else:
                raise SystemExit("drifted Output type did not raise")
            """
        )
        env = dict(os.environ, CYCLONUS_SHAPE_CHECK="1", JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "RESULT-DRIFT-OK" in proc.stdout
