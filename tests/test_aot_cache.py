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
from cyclonus_tpu.synthetic import build_synthetic
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
        from disk (load path exercised without a subprocess: forget()
        stands for the new process, whose memory tier is empty)."""
        import jax
        import jax.numpy as jnp

        from cyclonus_tpu.telemetry import instruments as ti

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        jitted = jax.jit(lambda x: x * 3 + 1)
        x = jnp.arange(8, dtype=jnp.int32)
        p1 = aot_cache.AotProgram("t.roundtrip", jitted, plan="unit")
        out1 = p1(x)
        aot_cache.forget()
        hits0 = ti.AOT_CACHE.value(outcome="hit")
        shared0 = ti.AOT_CACHE.value(outcome="shared")
        p2 = aot_cache.AotProgram("t.roundtrip", jitted, plan="unit")
        out2 = p2(x)
        assert ti.AOT_CACHE.value(outcome="hit") == hits0 + 1
        assert ti.AOT_CACHE.value(outcome="shared") == shared0
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
        for k in (
            "hits", "shared", "misses", "adopted", "stores", "compiles", "dir"
        ):
            assert k in c
        assert c["adopted"] == c["hits"]  # `hit` still means: from disk


def _small_problem():
    """(policy, pods, namespaces, cases): the driver's cluster."""
    import random

    from cyclonus_tpu.synthetic import build_synthetic
    from cyclonus_tpu.engine import PortCase
    from cyclonus_tpu.matcher import build_network_policies

    pods, namespaces, policies = build_synthetic(40, 10, random.Random(3))
    policy = build_network_policies(True, policies)
    return policy, pods, namespaces, [PortCase(80, "serve-80-tcp", "TCP")]


def _entries(cache_dir, name):
    """key -> file of program `name`'s entries in the cache directory"""
    found = {}
    for f in cache_dir.glob("*.aotx"):
        key = pickle.loads(f.read_bytes())["key"]
        if json.loads(key)["name"] == name:
            found[key] = f
    return found


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
        import jax
        import numpy as np

        from cyclonus_tpu.engine import TpuPolicyEngine
        from cyclonus_tpu.engine.kernel import evaluate_grid_kernel

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        policy, pods, namespaces, cases = _small_problem()

        def engine():
            return TpuPolicyEngine(
                policy, pods, namespaces, class_compress=class_compress
            )

        def entries():
            return _entries(tmp_path, name)

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
        aot_cache.forget()
        grid = engine().evaluate_grid(cases)
        assert grid.combined_dev.dtype == np.uint32
        assert np.array_equal(grid.combined, want)
        assert set(entries()) == {key, old_key}


class TestPackedContractionInKey:
    """make_key sees nothing of a program's code, so every program that
    can trace kernel.packed_any names the contraction's form in its plan
    (kernel.PACKED_CONTRACTION): the executable of a build whose
    packed_any was a lax.scan (PR 34 and before) lies under another key
    and is never adopted by one whose packed_any is one reduction."""

    @pytest.mark.parametrize("pack", ["1", "0"])
    def test_engine_plan_names_the_form_only_when_packed(
        self, monkeypatch, pack
    ):
        from cyclonus_tpu.engine import TpuPolicyEngine, kernel

        monkeypatch.setenv("CYCLONUS_PACK", pack)
        policy, pods, namespaces, _cases = _small_problem()
        engine = TpuPolicyEngine(policy, pods, namespaces)
        plan = engine._aot_plan()
        if pack == "0":
            assert "any=" not in plan and "packed32" not in plan
            return
        # the dtype word itself is the autotuner's and pack_stats()'s
        assert plan.startswith(f"packed32;{kernel.PACKED_CONTRACTION};")
        assert engine.pack_stats()["dtype"] == "packed32"
        form = kernel.PACKED_CONTRACTION
        monkeypatch.setattr(kernel, "PACKED_CONTRACTION", "any=scan")
        other = engine._aot_plan()
        assert other == plan.replace(form, "any=scan") != plan
        keys = {aot_cache.make_key("grid", "sig", plan=p) for p in (plan, other)}
        assert len(keys) == 2

    @pytest.mark.parametrize(
        "name,class_compress", [("grid", "0"), ("grid.classes", "1")]
    )
    def test_scan_era_executable_is_not_adopted(
        self, tmp_path, monkeypatch, name, class_compress
    ):
        """A cache directory that holds the PARENT's executable of the
        same program, shapes and platform: the engine misses, builds its
        own and stores it beside the old one."""
        import numpy as np

        from cyclonus_tpu.engine import TpuPolicyEngine, kernel

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        policy, pods, namespaces, cases = _small_problem()

        def engine():
            return TpuPolicyEngine(
                policy, pods, namespaces, class_compress=class_compress
            )

        def entries():
            return _entries(tmp_path, name)

        aot_cache.forget()
        first = engine()
        want = first.evaluate_grid(cases).combined
        ((key, path),) = entries().items()
        parts = json.loads(key)
        assert f";{kernel.PACKED_CONTRACTION};" in parts["plan"]
        # the parent's key: the same plan without the contraction's form
        old_key = aot_cache.make_key(
            name, parts["sig"], schedule=parts["schedule"],
            plan=parts["plan"].replace(f";{kernel.PACKED_CONTRACTION}", ""),
        )
        assert old_key != key
        # what lies under it would load (any executable of this
        # signature stands for the scan's: adopting it is the fault)
        assert aot_cache.store(old_key, aot_cache.load(key))
        assert aot_cache.load(old_key) is not None
        path.unlink()
        aot_cache.forget()
        before = _outcomes()
        grid = engine().evaluate_grid(cases)
        assert np.array_equal(grid.combined, want)
        assert set(entries()) == {key, old_key}
        moved = _moved(before)
        assert moved.get("miss", 0) >= 1 and moved.get("compiles", 0) >= 1


def _outcomes():
    from cyclonus_tpu.telemetry import instruments as ti

    return {
        "shared": ti.AOT_CACHE.value(outcome="shared"),
        "hit": ti.AOT_CACHE.value(outcome="hit"),
        "miss": ti.AOT_CACHE.value(outcome="miss"),
        "compiles": ti.AOT_COMPILES.value(),
    }


def _moved(before):
    """The outcomes that changed since `before`, and by how much."""
    return {
        k: v - before[k] for k, v in _outcomes().items() if v != before[k]
    }


class TestMemoryTier:
    """The process-wide tier in front of the files: a wrapper of a key
    the process has already loaded shares the loaded executable.  Where
    a wrapper would get its executable from the DISK it is resolved and
    not called: on the suite's eight virtual devices a deserialized
    one-device executable is loaded for all eight and rejected at call
    time (ROADMAP D12).  That each key component keeps wrappers apart
    is tests/keyharness.py's `shared_tier_key`."""

    @pytest.fixture(autouse=True)
    def _fresh_tier(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path / "aot"))
        aot_cache.forget()
        yield
        aot_cache.forget()

    @staticmethod
    def _wrapper(name="t.shared", plan="unit"):
        import jax

        return aot_cache.AotProgram(name, jax.jit(lambda x: x * 3 + 1), plan=plan)

    @staticmethod
    def _x(n=8):
        import jax.numpy as jnp

        return jnp.arange(n, dtype=jnp.int32)

    def test_second_wrapper_shares_even_without_the_file(self, tmp_path):
        x = self._x()
        want = self._wrapper()(x)
        (entry,) = (tmp_path / "aot").glob("*.aotx")
        entry.unlink()
        before = _outcomes()
        assert (self._wrapper()(x) == want).all()
        assert _moved(before) == {"shared": 1}
        assert not list((tmp_path / "aot").glob("*.aotx"))  # nothing rewritten

    def test_forget_sends_the_next_wrapper_back_to_the_disk(self):
        x = self._x()
        want = self._wrapper()(x)  # built here: callable on any host
        aot_cache.forget()
        before = _outcomes()
        self._wrapper().resolve(x)
        assert _moved(before) == {"hit": 1}
        # and what the disk gave is in the tier again
        before = _outcomes()
        self._wrapper().resolve(x)
        assert _moved(before) == {"shared": 1}
        # forget() leaves a wrapper what it has resolved
        first = self._wrapper()
        first(x)
        aot_cache.forget()
        before = _outcomes()
        assert (first(x) == want).all()
        assert _moved(before) == {}

    def test_another_cache_directory_does_not_share(self, tmp_path, monkeypatch):
        x = self._x()
        self._wrapper()(x)
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path / "other"))
        before = _outcomes()
        self._wrapper()(x)
        assert _moved(before) == {"miss": 1, "compiles": 1}
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path / "aot"))
        before = _outcomes()
        self._wrapper()(x)
        assert _moved(before) == {"shared": 1}

    def test_lru_never_holds_more_than_its_bound(self, monkeypatch):
        monkeypatch.setattr(aot_cache, "SHARED_MAX", 3)
        x = self._x()
        for i in range(5):
            self._wrapper(plan=f"p{i}")(x)
            assert len(aot_cache._SHARED) <= 3
        assert len(aot_cache._SHARED) == 3
        # p2 is now the oldest: resolving it makes it the newest, and
        # the next new key pushes p3 out, not p2
        before = _outcomes()
        self._wrapper(plan="p2")(x)
        self._wrapper(plan="p5")(x)
        self._wrapper(plan="p2")(x)
        assert _moved(before) == {"shared": 2, "miss": 1, "compiles": 1}
        before = _outcomes()
        self._wrapper(plan="p3").resolve(x)  # evicted: back to its file
        assert _moved(before) == {"hit": 1}
        assert len(aot_cache._SHARED) == 3

    def test_an_evicted_executable_stays_with_the_wrapper_that_holds_it(
        self, monkeypatch
    ):
        monkeypatch.setattr(aot_cache, "SHARED_MAX", 1)
        x = self._x()
        first = self._wrapper(plan="p0")
        want = first(x)
        self._wrapper(plan="p1")(x)
        before = _outcomes()
        assert (first(x) == want).all()
        assert _moved(before) == {}

    def test_a_call_time_rejection_evicts(self):
        x = self._x()
        want = self._wrapper()(x)
        (slot,) = aot_cache._SHARED

        def rejected(*args, **kwargs):
            raise RuntimeError("the runtime rejects this executable")

        aot_cache._SHARED[slot] = rejected
        before = _outcomes()
        second = self._wrapper()
        assert (second(x) == want).all()  # through the plain jit
        assert (second(x) == want).all()  # pinned, still answers
        assert _moved(before) == {"shared": 1}
        assert slot not in aot_cache._SHARED
        before = _outcomes()
        self._wrapper().resolve(x)
        assert _moved(before) == {"hit": 1}  # the next one asks the disk
        assert aot_cache._SHARED[slot] is not rejected

    def test_a_built_program_is_shared_where_store_fails(self, monkeypatch):
        monkeypatch.setattr(aot_cache, "store", lambda key, compiled: False)
        x = self._x()
        want = self._wrapper()(x)
        before = _outcomes()
        assert (self._wrapper()(x) == want).all()
        assert _moved(before) == {"shared": 1}

    def test_threads_keep_the_bound_and_never_cross_keys(self, monkeypatch):
        """More threads than cores put, get, drop and forget for one
        second: the tier never passes its bound, and a key
        never yields another key's executable."""
        import sys
        import threading
        import time

        monkeypatch.setattr(aot_cache, "SHARED_MAX", 8)
        n_threads = 4 * (os.cpu_count() or 4)
        deadline = time.monotonic() + 1.0
        faults = []

        def work(seed):
            i = seed
            while time.monotonic() < deadline and not faults:
                i = (i * 1103515245 + 12345) % (1 << 31)
                key = f"k{i % 24}"
                step = i % 7
                if step < 3:
                    aot_cache._shared_put("d", key, ("exe", key))
                elif step < 5:
                    got = aot_cache._shared_get("d", key)
                    if got is not None and got != ("exe", key):
                        faults.append((key, got))
                elif step == 5:
                    aot_cache._shared_drop("d", key)
                elif i % 97 == 0:
                    aot_cache.forget()
                if len(aot_cache._SHARED) > 8:
                    faults.append(("bound", len(aot_cache._SHARED)))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [
                threading.Thread(target=work, args=(t + 1,), daemon=True)
                for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not faults, faults[:5]
        assert len(aot_cache._SHARED) <= 8

    def test_disabled_cache_never_enters_the_tier(self, monkeypatch):
        monkeypatch.setenv("CYCLONUS_AOT_CACHE", "0")
        before = _outcomes()
        p = self._wrapper()
        p.resolve(self._x())
        p(self._x())
        assert _moved(before) == {}
        assert not aot_cache._SHARED


class TestEnginesShare:
    def test_a_new_engine_of_loaded_shapes_shares_every_program(
        self, tmp_path, monkeypatch
    ):
        """Two engines of the same shapes and different policy sets in
        one process (the what-if user): the second obtains every
        program from the memory tier and answers as the scalar oracle
        does; an engine of another shape bucket misses."""
        import random

        import numpy as np

        from cyclonus_tpu.synthetic import build_synthetic
        from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
        from cyclonus_tpu.matcher import build_network_policies
        from cyclonus_tpu.telemetry import events
        from tests.test_engine_parity import oracle_grid

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", str(tmp_path))
        monkeypatch.setattr(events, "ACTIVE", True)
        aot_cache.forget()
        cases = [PortCase(80, "serve-80-tcp", "TCP")]

        def what_if(n_pods, policy_seed):
            """(how each engine.program span obtained its program, the
            policy, the cluster, the evaluated grid)"""
            pods, namespaces, _ = build_synthetic(n_pods, 10, random.Random(3))
            _, _, policies = build_synthetic(
                n_pods, 10, random.Random(policy_seed)
            )
            policy = build_network_policies(True, policies)
            mark = events.mark()
            grid = TpuPolicyEngine(policy, pods, namespaces).evaluate_grid(cases)
            grid.block_until_ready()
            how = [
                e["args"]["how"] for e in events.since(mark)
                if e["ph"] == "E" and e["name"] == "engine.program"
            ]
            return how, policy, pods, namespaces, grid

        how1, *_rest, grid1 = what_if(40, 3)
        assert how1 and set(how1) == {"built"}
        before = _outcomes()
        how2, policy, pods, namespaces, grid2 = what_if(40, 5)
        # seeds 3 and 5 draw policy sets that encode to the same shape
        # bucket; if the generator changes, pick another pair
        assert how2 == ["shared"] * len(how1), how2
        assert _moved(before) == {"shared": len(how1)}
        assert not np.array_equal(grid1.combined, grid2.combined)
        want = oracle_grid(policy, pods, namespaces, cases)
        wrong = [
            cell for cell, verdicts in want.items()
            if grid2.job_verdict(*cell) != verdicts
        ]
        assert len(want) == 40 * 40 and not wrong, wrong[:5]
        before = _outcomes()
        how3, *_rest = what_if(300, 3)
        assert set(how3) == {"built"}
        assert _moved(before) == {"miss": len(how3), "compiles": len(how3)}


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
from cyclonus_tpu.synthetic import build_synthetic
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
