"""utils/envflags.py tests: the CYCLONUS_* registry is complete over
every token the tree actually reads (grep-backed, so a new env var
cannot ship undeclared), the never-raise accessor semantics (malformed
degrades to the registered default; the two bool conventions are
selected by the default), the SLAB_MAX_BYTES / AUTOTUNE_TIMEOUT_S
parse-drift regressions (engine paths used to raise on a malformed
value that serve degraded), and the README env-var table staying
generated-not-handwritten."""

import os
import re
from contextlib import contextmanager

from cyclonus_tpu.utils import envflags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextmanager
def _env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    try:
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class TestRegistryCompleteness:
    def test_every_env_read_in_tree_is_registered(self):
        """Grep cyclonus_tpu/ for CYCLONUS_* tokens; every one must be a
        registered Flag.  (Docstrings mentioning a var count too — a
        documented flag that is not declared is exactly the drift this
        registry exists to prevent.)"""
        pat = re.compile(r"CYCLONUS_[A-Z0-9_]+")
        seen = set()
        pkg = os.path.join(REPO, "cyclonus_tpu")
        for root, _dirs, files in os.walk(pkg):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                with open(os.path.join(root, fn)) as f:
                    seen.update(pat.findall(f.read()))
        missing = sorted(seen - set(envflags.REGISTRY))
        assert not missing, f"undeclared env vars: {missing}"

    def test_registry_is_nonempty_and_typed(self):
        assert len(envflags.REGISTRY) >= 40
        for flag in envflags.REGISTRY.values():
            assert flag.kind in ("bool", "int", "float", "enum", "str", "path")
            assert flag.owner in (
                "engine", "serve", "worker", "chaos", "telemetry",
                "probe", "harness", "cli", "slo", "audit",
            )
            assert flag.description
            if flag.kind == "enum":
                assert flag.choices, flag.name

    def test_unregistered_name_is_a_programming_error(self):
        import pytest

        with pytest.raises(KeyError):
            envflags.get_int("CYCLONUS_NO_SUCH_FLAG")


class TestAccessorSemantics:
    def test_int_malformed_degrades_to_default(self):
        with _env(CYCLONUS_SERVE_PREWARM_PAIRS="not-a-number"):
            assert envflags.get_int("CYCLONUS_SERVE_PREWARM_PAIRS") == 64
        with _env(CYCLONUS_SERVE_PREWARM_PAIRS="128"):
            assert envflags.get_int("CYCLONUS_SERVE_PREWARM_PAIRS") == 128
        with _env(CYCLONUS_SERVE_PREWARM_PAIRS=None):
            assert envflags.get_int("CYCLONUS_SERVE_PREWARM_PAIRS") == 64

    def test_float_malformed_degrades_to_default(self):
        with _env(CYCLONUS_CHAOS_TTFV_S="soon"):
            assert envflags.get_float("CYCLONUS_CHAOS_TTFV_S") == 150.0
        with _env(CYCLONUS_CHAOS_TTFV_S="2.5"):
            assert envflags.get_float("CYCLONUS_CHAOS_TTFV_S") == 2.5

    def test_bool_opt_in_convention(self):
        # default False => armed only by exactly "1"
        with _env(CYCLONUS_TRACE_EVENTS="1"):
            assert envflags.get_bool("CYCLONUS_TRACE_EVENTS") is True
        with _env(CYCLONUS_TRACE_EVENTS="yes"):
            assert envflags.get_bool("CYCLONUS_TRACE_EVENTS") is False
        with _env(CYCLONUS_TRACE_EVENTS=None):
            assert envflags.get_bool("CYCLONUS_TRACE_EVENTS") is False

    def test_bool_opt_out_convention(self):
        # default True => disarmed only by exactly "0"
        with _env(CYCLONUS_TELEMETRY="0"):
            assert envflags.get_bool("CYCLONUS_TELEMETRY") is False
        with _env(CYCLONUS_TELEMETRY="anything"):
            assert envflags.get_bool("CYCLONUS_TELEMETRY") is True
        with _env(CYCLONUS_TELEMETRY=None):
            assert envflags.get_bool("CYCLONUS_TELEMETRY") is True

    def test_enum_degrades_to_default_on_unknown(self):
        with _env(CYCLONUS_CIDR_TSS="bogus"):
            assert envflags.get_enum("CYCLONUS_CIDR_TSS") == "auto"
        with _env(CYCLONUS_CIDR_TSS="1"):
            assert envflags.get_enum("CYCLONUS_CIDR_TSS") == "1"


class TestSlabBudgetDriftRegression:
    """engine/api.py and engine/cidrspace.py used to parse
    CYCLONUS_SLAB_MAX_BYTES with a bare int() — a malformed value
    raised at evaluate time on engine paths while serve degraded it to
    the 6 GiB default.  All four sites now share envflags.get_int."""

    def test_malformed_budget_degrades_everywhere(self):
        with _env(CYCLONUS_SLAB_MAX_BYTES="6GiB"):
            assert envflags.get_int("CYCLONUS_SLAB_MAX_BYTES") == 6 * 2**30
            from cyclonus_tpu.serve.incremental import patch_byte_budget

            assert patch_byte_budget() == 6 * 2**30

    def test_malformed_budget_does_not_raise_on_cidr_gate(self):
        import random

        from cyclonus_tpu.synthetic import build_synthetic
        from cyclonus_tpu.engine import TpuPolicyEngine, cidrspace
        from cyclonus_tpu.matcher import build_network_policies

        pods, namespaces, policies = build_synthetic(12, 3, random.Random(7))
        policy = build_network_policies(True, policies)
        eng = TpuPolicyEngine(policy, pods, namespaces)
        with _env(CYCLONUS_SLAB_MAX_BYTES="6GiB"):
            # resolve()'s HBM gate used to carry its own try/except copy
            # of the parse; through envflags it must degrade, not raise,
            # whether or not the synthetic set has IPv4 atoms.
            cidrspace.resolve(eng._tensors, mode="1")

    def test_malformed_budget_does_not_raise_on_class_counts_gate(self):
        import random

        from cyclonus_tpu.synthetic import build_synthetic
        from cyclonus_tpu.engine import TpuPolicyEngine
        from cyclonus_tpu.matcher import build_network_policies

        pods, namespaces, policies = build_synthetic(12, 3, random.Random(7))
        policy = build_network_policies(True, policies)
        with _env(CYCLONUS_SLAB_MAX_BYTES="oops", CYCLONUS_CLASS_COMPRESS="1"):
            eng = TpuPolicyEngine(policy, pods, namespaces)
            # the eligibility gate consults the budget; a malformed
            # value must degrade to the default, not raise at dispatch
            assert eng._class_counts_eligible(2) in (True, False)

    def test_autotune_timeout_shared_parse(self):
        with _env(CYCLONUS_AUTOTUNE_TIMEOUT_S="oops"):
            assert envflags.get_float("CYCLONUS_AUTOTUNE_TIMEOUT_S") == 240.0
        with _env(CYCLONUS_AUTOTUNE_TIMEOUT_S="17.5"):
            assert envflags.get_float("CYCLONUS_AUTOTUNE_TIMEOUT_S") == 17.5


class TestReadmeTable:
    def test_markdown_table_covers_registry(self):
        table = envflags.markdown_table()
        for name in envflags.REGISTRY:
            assert f"`{name}`" in table

    def test_readme_env_table_is_generated(self):
        """README's env-var table is the generator's output verbatim —
        regenerate with
        python -c 'from cyclonus_tpu.utils import envflags; print(envflags.markdown_table())'
        when the registry changes."""
        with open(os.path.join(REPO, "README.md")) as f:
            readme = f.read()
        assert envflags.markdown_table() in readme
