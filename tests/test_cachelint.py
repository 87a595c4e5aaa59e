"""tools/cachelint.py tests: seeded-violation gates for CC001–CC005
(each defect class must fire, each suppression must be honored), the
clean-run + annotation-count acceptance gate over the cache-bearing
packages, the runtime cachekeys registry strip/overhead contract, the
tier-1 slice of the key-mutation harness (tests/keyharness.py), the
regression tests for the real never-raise gaps the pass surfaced in
engine/autotune.py, and the combined four-leg lint wall-clock budget."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import cachelint


def _lint_source(tmp_path, source: str, name: str = "mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    findings, _stats = cachelint.lint_paths([str(p)])
    return findings


def _codes(findings):
    return [f.code for f in findings]


class TestCC001TraceBakedKeys:
    def test_uncovered_closure_capture_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram

            def build(scale):
                return AotProgram("p", jax.jit(lambda x: x * scale))
            """,
        )
        assert _codes(findings) == ["CC001"]
        assert "'scale'" in findings[0].message

    def test_plan_expression_covers(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram

            def build(scale):
                return AotProgram(
                    "p", jax.jit(lambda x: x * scale), plan=f"s={scale}"
                )
            """,
        )
        assert findings == []

    def test_trailing_comment_covers(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram

            def build(scale):
                return AotProgram(  # cache-key: scale (caller-bucketed)
                    "p", jax.jit(lambda x: x * scale)
                )
            """,
        )
        assert findings == []

    def test_cachekeys_descriptor_covers(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram
            from cyclonus_tpu.utils import cachekeys

            def build(scale):
                comps = cachekeys.program("scale")
                return AotProgram("p", jax.jit(lambda x: x * scale))
            """,
        )
        assert findings == []

    def test_forward_derivation_covers(self, tmp_path):
        """n_dev = mesh.devices.size: baked n_dev is covered because it
        derives from a name the key expression carries."""
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram

            def build(mesh):
                n_dev = mesh.devices.size
                return AotProgram(
                    "p", jax.jit(lambda x: x * n_dev), plan=f"m={mesh}"
                )
            """,
        )
        assert findings == []

    def test_backward_derivation_covers(self, tmp_path):
        """The key embeds a digest OF the baked value: covered."""
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram, digest

            def build(specs):
                spec_digest = digest(specs)
                return AotProgram(
                    "p", jax.jit(lambda x: x + len(specs)),
                    plan=f"d={spec_digest}",
                )
            """,
        )
        assert findings == []

    def test_self_attr_covered_via_method_expansion(self, tmp_path):
        """plan=self._plan() one level in: the self attrs the method
        body reads are key components."""
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram

            class Engine:
                def _plan(self):
                    return f"pack={self._pack}"

                def build(self):
                    pack = self._pack
                    return AotProgram(
                        "p", jax.jit(lambda x: x * pack), plan=self._plan()
                    )
            """,
        )
        assert findings == []

    def test_self_attr_uncovered_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram

            class Engine:
                def build(self):
                    pack = self._pack
                    return AotProgram("p", jax.jit(lambda x: x * pack))
            """,
        )
        assert _codes(findings) == ["CC001"]

    def test_undeclared_program_dict_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax

            _PROGRAMS = {}

            def get(mesh, shard):
                key = (shard,)
                fn = jax.jit(lambda t: t + shard)
                _PROGRAMS[key] = fn
                return fn
            """,
        )
        assert "CC001" in _codes(findings)
        assert any("no `# cache-key:` declaration" in f.message for f in findings)

    def test_declared_dict_with_incomplete_key_fires(self, tmp_path):
        """mesh is baked into the program but the key tuple only
        carries shard: the same key would serve a program compiled for
        a different mesh."""
        findings = _lint_source(
            tmp_path,
            """
            import jax

            _PROGRAMS = {}  # cache-key: shard

            def get(mesh, shard):
                key = (shard,)
                fn = jax.jit(lambda t: t + mesh.size + shard)
                _PROGRAMS[key] = fn
                return fn
            """,
        )
        assert _codes(findings) == ["CC001"]
        assert "'mesh'" in findings[0].message

    def test_declared_dict_complete_key_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax

            _PROGRAMS = {}  # cache-key: mesh, shard

            def get(mesh, shard):
                key = (tuple(mesh.devices.flat), shard)
                fn = jax.jit(lambda t: t + mesh.size + shard)
                _PROGRAMS[key] = fn
                return fn
            """,
        )
        assert findings == []

    def test_module_global_jit_with_bake_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax

            _JIT = None

            def get(width):
                global _JIT
                if _JIT is None:
                    _JIT = jax.jit(lambda b: b * width)
                return _JIT
            """,
        )
        assert _codes(findings) == ["CC001"]
        assert "process-lifetime staleness" in findings[0].message

    def test_module_global_jit_without_bake_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax

            _JIT = None

            def get():
                global _JIT
                if _JIT is None:
                    _JIT = jax.jit(lambda b, i, v: b.at[i].set(v))
                return _JIT
            """,
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import jax
            from cyclonus_tpu.engine.aot_cache import AotProgram

            def build(scale):
                return AotProgram("p", jax.jit(lambda x: x * scale))  # cachelint: ignore[CC001]
            """,
        )
        assert findings == []


class TestCC002DerivedInvalidation:
    BASE = """
    class Engine:
        def __init__(self):
            self._pre_cache = None  # derived-from: buffer
            self._grid_jit = None  # derived-from: shapes
            self._packed_buf = None  # derived-from: patched

        def invalidate_after_patch(self):
            {body}
    """

    def test_value_derived_not_reset_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path, self.BASE.format(body="pass")
        )
        assert _codes(findings) == ["CC002"]
        assert "_pre_cache" in findings[0].message

    def test_value_derived_reset_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path, self.BASE.format(body="self._pre_cache = None")
        )
        assert findings == []

    def test_undeclared_cache_attr_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            class Engine:
                def __init__(self):
                    self._foo_cache = None

                def invalidate_after_patch(self):
                    pass
            """,
        )
        assert _codes(findings) == ["CC002"]
        assert "no `# derived-from:` declaration" in findings[0].message

    def test_class_without_invalidate_is_out_of_scope(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            class Widget:
                def __init__(self):
                    self._foo_cache = None
            """,
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            class Engine:
                def __init__(self):
                    self._foo_cache = None  # cachelint: ignore[CC002]

                def invalidate_after_patch(self):
                    pass
            """,
        )
        assert findings == []


class TestCC003EnvOnCachedPath:
    def test_env_read_in_jit_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import os
            import jax

            @jax.jit
            def body(x):
                if os.environ.get("MODE") == "1":
                    return x
                return x + 1
            """,
        )
        assert _codes(findings) == ["CC003"]

    def test_env_read_one_level_helper_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import os
            import jax

            def mode():
                return os.getenv("MODE", "0")

            @jax.jit
            def body(x):
                return x + int(mode())
            """,
        )
        assert _codes(findings) == ["CC003"]
        assert "reached from jit-traced" in findings[0].message

    def test_eager_resolution_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import os
            import jax

            def build():
                mode = os.environ.get("MODE", "0") == "1"
                return jax.jit(lambda x: x + 1 if mode else x)
            """,
        )
        assert findings == []

    def test_subscript_env_read_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import os
            import jax

            @jax.jit
            def body(x):
                return x + len(os.environ["MODE"])
            """,
        )
        assert _codes(findings) == ["CC003"]


class TestCC004PersistDiscipline:
    def test_direct_write_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            CACHE_VERSION = 1

            def store(key, value, path):
                with open(path, "w") as f:
                    json.dump({"v": CACHE_VERSION, "key": key}, f)
            """,
        )
        assert _codes(findings) == ["CC004"]
        assert "tmp+os.replace" in findings[0].message

    ATOMIC = """
    import json, logging, os, tempfile

    CACHE_VERSION = 1
    log = logging.getLogger(__name__)

    def load(path):  # never-raises
        try:
            with open(path) as f:
                return json.load(f)
        except Exception as e:
            log.info("corrupt: %s", e)
            return None

    def store({params}, path):
        fd, tmp = tempfile.mkstemp(dir=".")
        with os.fdopen(fd, "w") as f:
            json.dump({entry}, f)
        os.replace(tmp, path)
    """

    def test_atomic_versioned_keyed_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            self.ATOMIC.format(
                params="key, value",
                entry='{"v": CACHE_VERSION, "key": key, "value": value}',
            ),
        )
        assert findings == []

    def test_missing_version_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            self.ATOMIC.format(
                params="key, value", entry='{"key": key, "value": value}'
            ),
        )
        assert _codes(findings) == ["CC004"]
        assert "CACHE_VERSION" in findings[0].message

    def test_missing_key_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            self.ATOMIC.format(
                params="value", entry='{"v": CACHE_VERSION, "value": value}'
            ),
        )
        assert _codes(findings) == ["CC004"]
        assert "cache key" in findings[0].message

    def test_missing_mkstemp_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json, os

            CACHE_VERSION = 1

            def load(path):  # never-raises
                try:
                    with open(path) as f:
                        return json.load(f)
                except Exception:
                    raise

            def store(key, value, path):
                with open(path + ".tmp", "w") as f:
                    json.dump({"v": CACHE_VERSION, "key": key}, f)
                os.replace(path + ".tmp", path)
            """,
        )
        assert _codes(findings) == ["CC004"]
        assert "mkstemp" in findings[0].message

    def test_missing_never_raise_read_twin_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json, os, tempfile

            CACHE_VERSION = 1

            def store(key, value, path):
                fd, tmp = tempfile.mkstemp(dir=".")
                with os.fdopen(fd, "w") as f:
                    json.dump({"v": CACHE_VERSION, "key": key}, f)
                os.replace(tmp, path)
            """,
        )
        assert _codes(findings) == ["CC004"]
        assert "read twin" in findings[0].message

    def test_non_cache_module_untouched(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            def save(path, data):
                with open(path, "w") as f:
                    json.dump(data, f)
            """,
        )
        assert findings == []


class TestCC005NeverRaise:
    def test_unshielded_call_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            def load(path):  # never-raises
                with open(path) as f:
                    return json.load(f)
            """,
        )
        assert all(c == "CC005" for c in _codes(findings))
        assert findings

    def test_broad_handler_with_counter_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            def load(path, metric):  # never-raises
                try:
                    with open(path) as f:
                        return json.load(f)
                except Exception:
                    metric.inc()
                    return None
            """,
        )
        assert findings == []

    def test_narrow_handler_does_not_shield(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            def load(path):  # never-raises
                try:
                    with open(path) as f:
                        return json.load(f)
                except FileNotFoundError:
                    return None
            """,
        )
        assert all(c == "CC005" for c in _codes(findings))
        assert findings

    def test_swallow_without_evidence_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            def load(path):  # never-raises
                try:
                    with open(path) as f:
                        return json.load(f)
                except Exception:
                    return None
            """,
        )
        assert _codes(findings) == ["CC005"]
        assert "evidence" in findings[0].message

    def test_never_raise_callee_chain_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import os

            def resolve():  # never-raises
                raw = os.environ.get("X")
                if raw is None:
                    return None
                return os.path.expanduser(raw.strip())

            def outer(key):  # never-raises
                base = resolve()
                if base is None:
                    return None
                return os.path.join(base, key)
            """,
        )
        assert findings == []

    def test_plain_index_subscript_fires_slice_clean(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def head(items):  # never-raises
                return items[0]

            def tail(items):  # never-raises
                return items[1:]
            """,
        )
        assert _codes(findings) == ["CC005"]
        assert "subscript" in findings[0].message

    def test_raise_fires(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            def load(path):  # never-raises
                raise ValueError(path)
            """,
        )
        assert _codes(findings) == ["CC005"]

    def test_unannotated_function_untouched(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            def load(path):
                with open(path) as f:
                    return json.load(f)
            """,
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = _lint_source(
            tmp_path,
            """
            import json

            def load(path):  # never-raises
                with open(path) as f:  # cachelint: ignore[CC005]
                    return json.load(f)  # cachelint: ignore[CC005]
            """,
        )
        assert findings == []


CACHE_PACKAGES = [
    os.path.join(REPO, "cyclonus_tpu", p)
    for p in ("engine", "serve", "chaos")
]


class TestCleanRun:
    def test_packages_clean_with_live_annotations(self):
        """THE acceptance gate: 0 findings over the cache-bearing
        packages with >= 25 live cache-key / derived-from /
        never-raises annotations."""
        findings, stats = cachelint.lint_paths(CACHE_PACKAGES)
        assert findings == [], "\n".join(f.render() for f in findings)
        assert stats["annotations"] >= 25, stats

    @pytest.mark.parametrize(
        "attr,twin,derived_from",
        [
            ("_class_w_dev", "_class_of_dev", ["classes"]),
            ("_static_pre", "_pre_cache", ["buffer"]),
        ],
    )
    def test_resident_operands_declared_and_cleared(
        self, attr, twin, derived_from
    ):
        """What the engine keeps on the device from call to call derives
        from state the serve layer mutates in place: the class weights
        (`_class_w_dev`, PR 31) from the class state, the static half of
        the dense precompute (`_static_pre`, PR 33) from the packed
        buffer.  Each is declared as its older twin is, and
        invalidate_after_patch resets it."""
        import ast

        path = os.path.join(REPO, "cyclonus_tpu", "engine", "api.py")
        src = open(path).read()
        model = cachelint.ModuleModel(path, ast.parse(src), src.splitlines())
        decls, invalidate, reset = cachelint.derived_model(
            model, model.classes["TpuPolicyEngine"]
        )
        assert invalidate is not None
        tokens, _line = decls[attr]
        assert tokens == decls[twin][0] == derived_from
        assert attr in reset and twin in reset

    def test_cli_exit_status(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "cachelint.py")],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "never-raises annotation(s)" in proc.stderr

    def test_makefile_wires_cachelint_into_lint(self):
        mk = open(os.path.join(REPO, "Makefile")).read()
        assert "cachelint" in mk
        lint_block = mk.split("lint:", 1)[1]
        assert "cachelint" in lint_block.split("\n\n")[0] or (
            "cachelint" in mk.split("lint:")[0]
        )
        assert "keyharness" in mk


class TestSurfacedGaps:
    """Regression tests for the REAL contract violations the pass
    surfaced (ISSUE 13's fix-with-regression-test requirement)."""

    def test_store_winner_unserializable_degrades(self, tmp_path, monkeypatch):
        """json.dump's TypeError on a non-serializable timing value
        used to ESCAPE store_winner's documented never-raise contract
        (`except OSError` only).  Now it logs and returns False."""
        from cyclonus_tpu.engine import autotune as at

        monkeypatch.setenv(
            "CYCLONUS_AUTOTUNE_CACHE", str(tmp_path / "autotune.json")
        )
        key = at.make_key({"n": 8}, "cpu", "packed32")
        ok = at.store_winner(
            key, {"kernel": "packed", "bs": 8, "bd": 8},
            {"weird": object()},  # not JSON-serializable -> TypeError
        )
        assert ok is False  # degraded, did not raise
        # the file is untouched/absent, and a later good write works
        assert at.load_winner(key) is None
        assert at.store_winner(key, {"kernel": "default"}) is True
        assert at.load_winner(key) == {"kernel": "default"}

    def test_read_all_survives_arbitrary_reader_error(
        self, tmp_path, monkeypatch
    ):
        """_read_all's documented '{} on ANY problem' now holds for
        exceptions outside the old (OSError, ValueError) pair."""
        import json as _json

        from cyclonus_tpu.engine import autotune as at

        path = tmp_path / "autotune.json"
        path.write_text("{}")
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_CACHE", str(path))

        def boom(*a, **k):
            raise RuntimeError("pathological entry")

        monkeypatch.setattr(_json, "load", boom)
        assert at._read_all(str(path)) == {}
        assert at.load_winner("anything") is None

    def test_load_winner_malformed_dims(self, tmp_path, monkeypatch):
        from cyclonus_tpu.engine import autotune as at

        monkeypatch.setenv(
            "CYCLONUS_AUTOTUNE_CACHE", str(tmp_path / "a.json")
        )
        key = at.make_key({"n": 8}, "cpu", "packed32")
        assert at.store_winner(key, {"kernel": "packed", "bs": "wide"})
        assert at.load_winner(key) is None  # malformed dim -> fresh search


class TestCachekeysRegistry:
    def test_inactive_registry_is_inert(self):
        """The suite never sets CYCLONUS_KEYHARNESS: registration is a
        no-op, the registry stays empty, and the cachekey instruments
        never enter the metric registry (the strip proof)."""
        from cyclonus_tpu.telemetry.metrics import REGISTRY
        from cyclonus_tpu.utils import cachekeys

        assert cachekeys.ACTIVE is False
        assert (
            cachekeys.register(
                "t", kind="program", components=("a",), fingerprint="f"
            )
            is None
        )
        assert cachekeys.registered_count() == 0
        assert cachekeys.registered() == {}
        names = set(REGISTRY.snapshot())
        assert not any(n.startswith("cyclonus_tpu_cachekey") for n in names), (
            names
        )

    def test_program_descriptor_passthrough(self):
        from cyclonus_tpu.utils import cachekeys

        assert cachekeys.program("a", "b") == ("a", "b")

    def test_zero_overhead_when_off(self):
        """< 2% (or the measurement's own noise floor) for the inactive
        register() no-op against a plain no-op call — the paired-median
        differential method of test_locklint/test_shapelint."""
        import statistics

        from cyclonus_tpu.utils import cachekeys

        def noop():
            return None

        reps = 20000

        def timed_reg():
            t0 = time.perf_counter()
            for _ in range(reps):
                cachekeys.register(
                    "cache", kind="program", components=("a", "b")
                )
            return (time.perf_counter() - t0) / reps

        def timed_noop():
            t0 = time.perf_counter()
            for _ in range(reps):
                noop()
            return (time.perf_counter() - t0) / reps

        timed_reg(), timed_noop()  # warm
        diffs, bases = [], []
        for i in range(15):
            if i % 2 == 0:
                tr, tn = timed_reg(), timed_noop()
            else:
                tn, tr = timed_noop(), timed_reg()
            diffs.append(tr - tn)
            bases.append(tn)
        med = max(statistics.median(diffs), 0.0)
        base = statistics.median(bases)
        mad = statistics.median([abs(d - statistics.median(diffs)) for d in diffs])
        floor = 3 * mad / max(len(diffs) ** 0.5, 1)
        # the no-op path is one module-attr read + return: it must cost
        # no more than a comparable plain call, within noise.  A 500ns
        # absolute ceiling guards the property even if the baseline
        # no-op is optimized away.
        assert med <= max(0.02 * base + floor, 5e-7), (med, base, floor)
        assert cachekeys.registered_count() == 0  # still inert


class TestKeyharnessTier1:
    def test_quick_slice(self, tmp_path):
        """The bounded tier-1 slice of the key-mutation harness: AOT +
        autotune key fields, the AOT memory tier, the invalidate
        contract, and the pair program (the full sweep incl. subprocess
        restart legs is `make keyharness` / -m slow below)."""
        from tests import keyharness

        results = keyharness.run(str(tmp_path), quick=True)
        assert set(results) == {
            "aot_key_fields",
            "shared_tier_key",
            "autotune_key_fields",
            "invalidate_derived_contract",
            "pairs_program_key",
        }
        assert results["invalidate_derived_contract"]["value_attrs"] >= 10


@pytest.mark.slow
class TestKeyharnessFull:
    def test_full_sweep(self, tmp_path):
        from tests import keyharness

        results = keyharness.run(str(tmp_path), quick=False)
        assert "aot_restart_subprocess" in results
        assert "registry_census" in results
        assert "sharded_program_key" in results


class TestLintBudget:
    def test_seven_legs_stay_under_wall_clock_budget(self):
        """The combined `make lint` static legs (jaxlint + locklint +
        shapelint + cachelint + planlint + statelint + wirelint,
        in-process over their Makefile paths) must stay inside one
        minute — the seven-leg lint is part of `make check`'s inner
        loop and a slow linter stops being run."""
        import importlib

        t0 = time.perf_counter()
        jaxlint = importlib.import_module("jaxlint")
        locklint = importlib.import_module("locklint")
        shapelint = importlib.import_module("shapelint")
        planlint = importlib.import_module("planlint")
        statelint = importlib.import_module("statelint")
        wirelint = importlib.import_module("wirelint")
        jax_paths = [
            os.path.join(REPO, "cyclonus_tpu", p)
            for p in (
                "engine", "telemetry", "worker", "analysis", "probe",
                "serve", "tiers", "chaos", "linter", "recipes",
            )
        ]
        for f in jaxlint.iter_py_files(jax_paths):
            jaxlint.lint_file(f)
        locklint.lint_paths([os.path.join(REPO, "cyclonus_tpu")])
        shapelint.lint_paths(
            [
                os.path.join(REPO, "cyclonus_tpu", p)
                for p in (
                    "engine", "analysis", os.path.join("worker", "model.py"),
                    "serve", "tiers", "chaos", "linter", "recipes",
                )
            ]
        )
        cachelint.lint_paths(CACHE_PACKAGES)
        planlint.lint_paths(
            [
                os.path.join(REPO, "cyclonus_tpu", p)
                for p in ("engine", "serve", "tiers")
            ]
        )
        statelint.lint_paths(
            [
                os.path.join(REPO, "cyclonus_tpu", p)
                for p in ("serve", "audit")
            ]
        )
        wirelint.lint_paths(
            [
                os.path.join(REPO, "cyclonus_tpu", p)
                for p in ("worker", "serve")
            ]
        )
        elapsed = time.perf_counter() - t0
        assert elapsed < 60.0, f"seven lint legs took {elapsed:.1f}s"
