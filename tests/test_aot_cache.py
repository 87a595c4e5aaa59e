"""Persistent AOT executable cache (cyclonus_tpu/engine/aot_cache.py):
the zero-recompile restart contract, and the corrupt/stale/concurrent
degradation discipline (docs/DESIGN.md "Cold start & chaos")."""

import json
import os
import pickle
import subprocess
import sys

import pytest

from cyclonus_tpu.engine import aot_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a small engine driven end to end in a FRESH interpreter: build,
# evaluate grid + pairs + counts, print the verdict digest + the AOT
# counters + the engine span counts as one JSON line
_DRIVER = """
import json, os, random, sys
import numpy as np
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from bench import build_synthetic
from cyclonus_tpu import telemetry
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, aot_cache
from cyclonus_tpu.matcher import build_network_policies

pods, namespaces, policies = build_synthetic(40, 10, random.Random(3))
policy = build_network_policies(True, policies)
engine = TpuPolicyEngine(policy, pods, namespaces)
cases = [PortCase(80, "serve-80-tcp", "TCP")]
grid = np.asarray(engine.evaluate_grid(cases).combined)
counts = engine.evaluate_grid_counts(cases, backend="pallas")
pairs = engine.evaluate_pairs(cases, [(0, 1), (2, 3)])
spans = telemetry.SPANS.stats()
from cyclonus_tpu.telemetry import instruments as ti
kernel_traces = sum(
    s0.get("value", 0)
    for s0 in ti.KERNEL_TRACES.snapshot().get("samples", [])
)
print(json.dumps({{
    "digest": int(grid.sum()),
    "counts": counts,
    "pairs": int(pairs.sum()),
    "aot": aot_cache.counters(),
    "dispatch_spans": spans.get("engine.dispatch", {{}}).get("count", 0),
    "kernel_traces": kernel_traces,
}}))
"""


def _run_driver(cache_dir, extra_env=None):
    env = dict(os.environ)
    env["CYCLONUS_AOT_CACHE"] = str(cache_dir)
    env["CYCLONUS_AUTOTUNE_CACHE"] = "0"
    # isolate from any developer-level JAX compilation cache so the
    # measured compile counts are the AOT layer's alone
    env["CYCLONUS_JAX_CACHE"] = "0"
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER.format(repo=REPO)],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestRestartContract:
    def test_restart_adopts_executables_with_zero_compiles(self, tmp_path):
        """THE cold-start acceptance gate: a fresh process against a
        warm cache adopts every covered executable — hits > 0, fresh
        compiles == 0 — and produces bit-identical results."""
        cache = tmp_path / "aot"
        first = _run_driver(cache)
        assert first["aot"]["compiles"] > 0  # cold: paid real compiles
        assert first["aot"]["hits"] == 0
        assert first["aot"]["stores"] > 0
        second = _run_driver(cache)
        # zero-recompile adoption: every program the first process
        # persisted is adopted, nothing compiles fresh
        assert second["aot"]["compiles"] == 0, second["aot"]
        assert second["aot"]["misses"] == 0, second["aot"]
        assert second["aot"]["adopted"] >= first["aot"]["stores"]
        # identical verdicts through the adopted executables
        assert second["digest"] == first["digest"]
        assert second["counts"] == first["counts"]
        assert second["pairs"] == first["pairs"]
        # the engine still dispatched the same evaluations (the spans
        # prove the warm path ran, it didn't skip work)
        assert second["dispatch_spans"] == first["dispatch_spans"]
        # and the kernel trace counters stay FLAT: adopted executables
        # never re-enter the python kernel builders
        assert first["kernel_traces"] > 0
        assert second["kernel_traces"] == 0, second

    def test_poisoned_entries_degrade_to_fresh_compile(self, tmp_path):
        """Corrupt bytes, truncation, and version skew each degrade to
        a fresh compile — never a raise, never a wrong verdict."""
        cache = tmp_path / "aot"
        first = _run_driver(cache)
        entries = sorted(p for p in cache.iterdir() if p.suffix == ".aotx")
        assert entries, "no cache entries written"
        for i, path in enumerate(entries):
            if i % 3 == 0:
                path.write_bytes(b"\xffgarbage" * 100)
            elif i % 3 == 1:
                path.write_bytes(path.read_bytes()[: max(1, path.stat().st_size // 2)])
            else:
                path.write_bytes(
                    pickle.dumps({"v": 999, "key": "nope", "payload": b""})
                )
        third = _run_driver(cache)
        assert third["digest"] == first["digest"]
        assert third["counts"] == first["counts"]
        # every poisoned entry was rejected and recompiled fresh
        assert third["aot"]["compiles"] > 0
        assert third["aot"]["hits"] == 0

    def test_concurrently_written_cache_stays_loadable(self, tmp_path):
        """Two processes warming the same cache dir concurrently must
        both finish and leave a cache a third process fully adopts
        (per-entry atomic replace: same-key racers both wrote a valid
        executable)."""
        cache = tmp_path / "aot"
        env = dict(os.environ)
        env["CYCLONUS_AOT_CACHE"] = str(cache)
        env["CYCLONUS_AUTOTUNE_CACHE"] = "0"
        env["CYCLONUS_JAX_CACHE"] = "0"
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _DRIVER.format(repo=REPO)],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                cwd=REPO,
                env=env,
            )
            for _ in range(2)
        ]
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, out[-800:] + err[-800:]
            outs.append(json.loads(out.strip().splitlines()[-1]))
        assert outs[0]["digest"] == outs[1]["digest"]
        adopter = _run_driver(cache)
        assert adopter["aot"]["compiles"] == 0, adopter["aot"]
        assert adopter["digest"] == outs[0]["digest"]


class TestCacheModule:
    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", "0")
        assert aot_cache.cache_dir() is None
        assert aot_cache.load("anything") is None
        assert aot_cache.store("anything", object()) is False

    def test_load_never_raises_on_garbage(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        key = aot_cache.make_key("t", "sig")
        path = aot_cache._entry_path(str(tmp_path), key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(b"not a pickle at all")
        assert aot_cache.load(key) is None

    def test_key_collision_rejected_by_embedded_key(self, tmp_path, monkeypatch):
        """An entry whose embedded key differs from the requested key
        (digest collision / copied file) is stale, not loadable."""
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        key = aot_cache.make_key("t", "sig")
        path = aot_cache._entry_path(str(tmp_path), key)
        with open(path, "wb") as f:
            pickle.dump(
                {
                    "v": aot_cache.CACHE_VERSION,
                    "key": "some-other-key",
                    "payload": b"",
                    "in_tree": None,
                    "out_tree": None,
                },
                f,
            )
        assert aot_cache.load(key) is None

    def test_store_unserializable_returns_false(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))

        class NotCompiled:
            pass

        assert aot_cache.store(aot_cache.make_key("t", "s"), NotCompiled()) is False

    def test_make_key_varies_by_all_dimensions(self):
        base = aot_cache.make_key("a", "s", schedule="single", plan="p")
        assert aot_cache.make_key("b", "s", schedule="single", plan="p") != base
        assert aot_cache.make_key("a", "t", schedule="single", plan="p") != base
        assert aot_cache.make_key("a", "s", schedule="ring", plan="p") != base
        assert aot_cache.make_key("a", "s", schedule="single", plan="q") != base

    def test_platform_stamp_covers_jaxlib_independently(self):
        """Key-omission regression (tools/cachelint.py audit): the
        serialized payload is a JAXLIB binary, and jaxlib can be pinned
        independently of jax — a jaxlib-only upgrade must invalidate,
        not adopt."""
        import jax
        import jaxlib

        stamp = aot_cache.platform_stamp()
        assert f"jax={jax.__version__}" in stamp
        assert "jaxlib=" in stamp
        base_key = aot_cache.make_key("a", "s")
        orig = jaxlib.__version__
        try:
            jaxlib.__version__ = orig + ".post1"
            assert aot_cache.platform_stamp() != stamp
            # and the full key follows the stamp: a jaxlib-only bump
            # must miss every persisted executable
            assert aot_cache.make_key("a", "s") != base_key
        finally:
            jaxlib.__version__ = orig
        assert aot_cache.platform_stamp() == stamp  # revert hits
        assert aot_cache.make_key("a", "s") == base_key

    def test_aot_program_round_trip_in_process(self, tmp_path, monkeypatch):
        """AotProgram stores on first call and a FRESH wrapper adopts
        from disk (load path exercised without a subprocess)."""
        import jax
        import jax.numpy as jnp

        from cyclonus_tpu.telemetry import instruments as ti

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        jitted = jax.jit(lambda x: x * 3 + 1)
        x = jnp.arange(8, dtype=jnp.int32)
        p1 = aot_cache.AotProgram("t.roundtrip", jitted, plan="unit")
        out1 = p1(x)
        hits0 = ti.AOT_CACHE.value(outcome="hit")
        p2 = aot_cache.AotProgram("t.roundtrip", jitted, plan="unit")
        out2 = p2(x)
        assert ti.AOT_CACHE.value(outcome="hit") == hits0 + 1
        assert (out1 == out2).all()

    def test_aot_program_falls_back_on_unlowerable(self, tmp_path, monkeypatch):
        """A wrapped callable without .lower (or whose lowering fails)
        pins the fallback and still answers."""
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))

        def plain(x):
            return x + 1

        p = aot_cache.AotProgram("t.fallback", plain, plan="unit")
        assert p(1) == 2
        assert p(2) == 3  # fallback pinned, still works

    def test_counters_schema(self):
        c = aot_cache.counters()
        for k in ("hits", "misses", "adopted", "stores", "compiles", "dir"):
            assert k in c
        assert c["adopted"] == c["hits"]


class TestGridResultFormat:
    """make_key sees nothing of a program's code or result, so the grid
    programs name their result format in the plan: the executable of a
    build that returned boolean tables lies under another key and is
    never adopted by one that returns words (kernel.cell_words)."""

    @pytest.mark.parametrize(
        "name,class_compress", [("grid", "0"), ("grid.classes", "1")]
    )
    def test_boolean_era_executable_is_not_adopted(
        self, tmp_path, monkeypatch, name, class_compress
    ):
        import random

        import jax
        import numpy as np

        from bench import build_synthetic
        from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
        from cyclonus_tpu.engine.kernel import evaluate_grid_kernel
        from cyclonus_tpu.matcher import build_network_policies

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        pods, namespaces, policies = build_synthetic(40, 10, random.Random(3))
        policy = build_network_policies(True, policies)
        cases = [PortCase(80, "serve-80-tcp", "TCP")]

        def engine():
            return TpuPolicyEngine(
                policy, pods, namespaces, class_compress=class_compress
            )

        def entries():
            """key -> file of this program's entries in the cache"""
            found = {}
            for f in tmp_path.glob("*.aotx"):
                key = pickle.loads(f.read_bytes())["key"]
                if json.loads(key)["name"] == name:
                    found[key] = f
            return found

        first = engine()
        want = first.evaluate_grid(cases).combined
        ((key, path),) = entries().items()
        parts = json.loads(key)
        # the parent's key for the same program, shapes, platform, plan
        assert parts["plan"] == first._aot_plan() + ";out=w32.8x128"
        old_key = aot_cache.make_key(
            name, parts["sig"], schedule=parts["schedule"],
            plan=first._aot_plan(),
        )
        assert old_key != key
        # an executable of that era, same arguments, boolean tables back
        if name == "grid":
            args = (first._tensors_with_cases(cases, device=True),)
            old = evaluate_grid_kernel.lower(*args, pack=first._pack)
        else:
            args = (
                first._ctensors_with_cases(cases, device=True),
                first._class_of_dev,
            )
            pack = first._pack
            old = jax.jit(
                lambda t, co: evaluate_grid_kernel(t, pack=pack)
            ).lower(*args)
        assert aot_cache.store(old_key, old.compile())
        assert aot_cache.load(old_key) is not None  # it WOULD load
        path.unlink()
        # a new process' engine: finds only the old entry, builds its own
        grid = engine().evaluate_grid(cases)
        assert grid.combined_dev.dtype == np.uint32
        assert np.array_equal(grid.combined, want)
        assert set(entries()) == {key, old_key}


@pytest.mark.slow
class TestRestartContractSharded:
    def test_sharded_program_adopts_on_restart(self, tmp_path):
        """The cached ring shard_map program rides the same cache."""
        driver = """
import json, os, random, sys
import numpy as np
sys.path.insert(0, {repo!r})
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=8").strip()
import jax
jax.config.update("jax_platforms", "cpu")
from bench import build_synthetic
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, aot_cache
from cyclonus_tpu.matcher import build_network_policies

pods, namespaces, policies = build_synthetic(40, 10, random.Random(3))
policy = build_network_policies(True, policies)
engine = TpuPolicyEngine(policy, pods, namespaces)
cases = [PortCase(80, "serve-80-tcp", "TCP")]
g = np.asarray(engine.evaluate_grid_sharded(cases, schedule="ring").combined)
print(json.dumps({{"digest": int(g.sum()), "aot": aot_cache.counters()}}))
"""
        env_common = {
            "CYCLONUS_AOT_CACHE": str(tmp_path / "aot"),
            "CYCLONUS_AUTOTUNE_CACHE": "0",
            "CYCLONUS_JAX_CACHE": "0",
        }

        def run():
            env = dict(os.environ)
            env.update(env_common)
            proc = subprocess.run(
                [sys.executable, "-c", driver.format(repo=REPO)],
                capture_output=True, text=True, timeout=300, cwd=REPO,
                env=env,
            )
            assert proc.returncode == 0, proc.stdout[-800:] + proc.stderr[-800:]
            return json.loads(proc.stdout.strip().splitlines()[-1])

        first = run()
        assert first["aot"]["stores"] > 0
        second = run()
        assert second["aot"]["compiles"] == 0, second["aot"]
        assert second["digest"] == first["digest"]
