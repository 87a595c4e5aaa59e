"""Key-mutation harness: the dynamic proof behind tools/cachelint.py
(docs/DESIGN.md "Cache discipline"), mirroring tests/raceharness.py's
role for the lock lint.

The static pass proves every trace-baked value APPEARS in its declared
cache key; this harness proves the keys actually DISCRIMINATE: for
every registered key component it perturbs that one component, asserts
the cache misses (a new key string, a new program entry, a fresh
compile), then reverts and asserts a hit.  A component that can be
mutated without a miss is an incomplete key — the engine would serve a
program compiled for a different value: the stale-verdict failure
mode, strictly worse than a crash.

Covered cache families (the acceptance list in ISSUE 13):

  * the persistent AOT executable cache (engine/aot_cache.py) — key
    fields in-process, plus a SUBPROCESS restart leg: a warm cache is
    adopted with zero fresh compiles, and a mutated dtype-plan
    component (CYCLONUS_PACK) misses every entry while the verdicts
    stay bit-identical;
  * the memory tier in front of those files (aot_cache._SHARED, the
    executables this process has loaded) — each of its six components
    sends a new wrapper past the tier, the revert shares again;
  * the persisted autotune winner cache (engine/autotune.py) — every
    shape-bucket field, the mesh signature, and the dtype plan;
  * the in-process sharded-program cache (engine/sharded.py
    _SHARDED_PROGRAMS) — schedule / pack / mesh;
  * the serve pair program (engine/api.py _pairs_aot) and the grid
    program — per-signature dispatch entries.

Run modes: `python -m tests.keyharness` (quick slice, the tier-1 gate
via tests/test_cachelint.py), `--full` adds the engine-behavior,
sharded, restart-subprocess, and registry-census legs (`make
keyharness`, slow).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import subprocess
import sys
from typing import Callable, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# multi-device CPU mesh + CPU pin BEFORE any jax import, for standalone
# `python -m tests.keyharness` runs (pytest runs get this from
# tests/conftest.py; setting it twice is harmless)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")


class HarnessFailure(AssertionError):
    """A key component failed its miss-on-mutate / hit-on-revert proof;
    the message names the cache and the component."""


def _check(cond: bool, cache: str, component: str, detail: str) -> None:
    if not cond:
        raise HarnessFailure(
            f"{cache}: key component {component!r} failed — {detail}"
        )


@contextlib.contextmanager
def _env(**kv: Optional[str]):
    """Set/unset env vars, restoring exactly on exit (mutate/revert is
    the harness's whole contract — it must apply to its own state)."""
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


class Ctx:
    """Shared scenario context: tmp dir, rng, one lazily built small
    engine (24 pods — enough to exercise every program family, small
    enough for the tier-1 budget)."""

    def __init__(self, tmp: str, seed: int):
        self.tmp = tmp
        self.rng = random.Random(seed)
        self._engine = None
        self._cases = None

    def engine(self):
        if self._engine is None:
            from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
            from cyclonus_tpu.matcher import build_network_policies
            from cyclonus_tpu.synthetic import build_synthetic

            pods, namespaces, policies = build_synthetic(
                24, 6, random.Random(7)
            )
            policy = build_network_policies(True, policies)
            self._engine = TpuPolicyEngine(policy, pods, namespaces)
            self._cases = [PortCase(80, "serve-80-tcp", "TCP")]
        return self._engine

    def cases(self, q: int = 1):
        from cyclonus_tpu.engine import PortCase

        base = [
            PortCase(80, "serve-80-tcp", "TCP"),
            PortCase(81, "serve-81-udp", "UDP"),
            PortCase(8080, "", "TCP"),
        ]
        return base[:q]


# --- scenarios -------------------------------------------------------------


def scenario_aot_key_fields(ctx: Ctx) -> Dict:
    """Every field of the persisted AOT key discriminates: name,
    signature, schedule, plan, and the platform stamp (including the
    jaxlib leg the cachelint audit added)."""
    from cyclonus_tpu.engine import aot_cache

    base = aot_cache.make_key("grid", "sig0", schedule="single", plan="p0")
    muts = 0

    def prove(component: str, **kw) -> None:
        nonlocal muts
        name = kw.pop("_name", "grid")
        sig = kw.pop("_sig", "sig0")
        args = {"schedule": "single", "plan": "p0"}
        args.update(kw)
        mutated = aot_cache.make_key(name, sig, **args)
        _check(mutated != base, "aot", component, "mutation did not miss")
        muts += 1

    prove("name", _name="grid2")
    prove("signature", _sig="sig1")
    prove("schedule", schedule="ring")
    prove("plan", plan="p1")
    # revert: identical inputs produce the identical key (hit)
    again = aot_cache.make_key("grid", "sig0", schedule="single", plan="p0")
    _check(again == base, "aot", "revert", "revert did not hit")
    # platform stamp: jax and jaxlib versions each discriminate
    import jax

    stamp0 = aot_cache.platform_stamp()
    orig = jax.__version__
    try:
        jax.__version__ = orig + ".mut"
        _check(
            aot_cache.platform_stamp() != stamp0,
            "aot", "platform.jax", "jax version mutation did not miss",
        )
        muts += 1
    finally:
        jax.__version__ = orig
    _check(
        aot_cache.platform_stamp() == stamp0,
        "aot", "platform.revert", "platform revert did not hit",
    )
    try:
        import jaxlib

        jorig = jaxlib.__version__
        try:
            jaxlib.__version__ = jorig + ".mut"
            _check(
                aot_cache.platform_stamp() != stamp0,
                "aot", "platform.jaxlib",
                "jaxlib version mutation did not miss (the PR-13 key "
                "omission fix)",
            )
            muts += 1
        finally:
            jaxlib.__version__ = jorig
    except ImportError:  # pragma: no cover - jaxlib always rides jax here
        pass
    return {"mutations": muts}


#: what `aot_cache` registers for its memory tier (the census leg
#: compares); the scenario below mutates exactly these
SHARED_TIER_COMPONENTS = (
    "cache_dir", "name", "signature", "platform", "schedule", "plan"
)


def scenario_shared_tier_key(ctx: Ctx) -> Dict:
    """The memory tier in front of the AOT files (aot_cache._SHARED): a
    new wrapper of a key the process has loaded shares the executable
    (outcome `shared`); with any one component mutated it does not, and
    with the component back it shares again."""
    import jax
    import jax.numpy as jnp

    from cyclonus_tpu.engine import aot_cache
    from cyclonus_tpu.telemetry import instruments as ti

    x8 = jnp.arange(8, dtype=jnp.int32)
    x16 = jnp.arange(16, dtype=jnp.int32)

    def shares(name="kh.shared", plan="p0", schedule="single", x=x8) -> bool:
        """Whether a new wrapper got its executable from the tier."""
        before = ti.AOT_CACHE.value(outcome="shared")
        aot_cache.AotProgram(
            name, jax.jit(lambda v: v + 1), plan=plan, schedule=schedule
        ).resolve(x)
        return ti.AOT_CACHE.value(outcome="shared") == before + 1

    def other_dir() -> bool:
        with _env(CYCLONUS_AOT_CACHE=os.path.join(ctx.tmp, "aot-shared-2")):
            return shares()

    def other_platform() -> bool:
        orig = jax.__version__
        try:
            jax.__version__ = orig + ".mut"
            return shares()
        finally:
            jax.__version__ = orig

    mutations = {
        "cache_dir": other_dir,
        "name": lambda: shares(name="kh.shared2"),
        "signature": lambda: shares(x=x16),
        "platform": other_platform,
        "schedule": lambda: shares(schedule="ring"),
        "plan": lambda: shares(plan="p1"),
    }
    _check(
        tuple(mutations) == SHARED_TIER_COMPONENTS,
        "aot.shared", "components", "a component has no mutation",
    )
    with _env(CYCLONUS_AOT_CACHE=os.path.join(ctx.tmp, "aot-shared")):
        aot_cache.forget()
        _check(not shares(), "aot.shared", "cold", "an empty tier shared")
        _check(shares(), "aot.shared", "steady", "same key did not share")
        for component, mutated_shares in mutations.items():
            _check(
                not mutated_shares(),
                "aot.shared", component, "mutation did not miss",
            )
            _check(shares(), "aot.shared", component, "revert did not hit")
        aot_cache.forget()
        _check(not shares(), "aot.shared", "forget", "shared after forget()")
    return {"mutations": len(mutations)}


def scenario_autotune_key_fields(ctx: Ctx) -> Dict:
    """Persisted autotune winner: every shape-bucket field, the mesh,
    and the dtype plan each miss when mutated and hit on revert —
    through the real store/load path against a real cache file."""
    from cyclonus_tpu.engine import autotune as at

    path = os.path.join(ctx.tmp, "autotune.json")
    shape = {
        "n": 256, "te": 16, "ti": 16, "q": 2,
        "tiered": False, "classes": False,
    }
    with _env(CYCLONUS_AUTOTUNE_CACHE=path):
        key = at.make_key(shape, "cpu:host:8", "packed32")
        winner = {"kernel": "packed", "bs": 256, "bd": 512}
        assert at.store_winner(key, winner, {"default_s": 0.1})
        got = at.load_winner(key)
        _check(got == winner, "autotune", "baseline", f"store/load broke: {got}")
        muts = 0
        for field, mutated in [
            ("shape.n", dict(shape, n=512)),
            ("shape.te", dict(shape, te=32)),
            ("shape.ti", dict(shape, ti=32)),
            ("shape.q", dict(shape, q=3)),
            ("shape.tiered", dict(shape, tiered=True)),
            ("shape.classes", dict(shape, classes=True)),
        ]:
            miss = at.load_winner(at.make_key(mutated, "cpu:host:8", "packed32"))
            _check(miss is None, "autotune", field, "mutation did not miss")
            muts += 1
        miss = at.load_winner(at.make_key(shape, "tpu:v5e:4", "packed32"))
        _check(miss is None, "autotune", "mesh", "mutation did not miss")
        muts += 1
        miss = at.load_winner(at.make_key(shape, "cpu:host:8", "int8"))
        _check(miss is None, "autotune", "dtype_plan", "mutation did not miss")
        muts += 1
        # revert → hit
        _check(
            at.load_winner(at.make_key(shape, "cpu:host:8", "packed32"))
            == winner,
            "autotune", "revert", "revert did not hit",
        )
    return {"mutations": muts}


def scenario_invalidate_derived_contract(ctx: Ctx) -> Dict:
    """Runtime cross-check of the CC002 contract: every attribute
    api.py declares value-derived (`# derived-from:` with a value
    token) is actually overwritten by invalidate_after_patch — the
    static declaration list drives the runtime assertion, so the two
    sides can never drift."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import ast

    import cachelint

    path = os.path.join(REPO, "cyclonus_tpu", "engine", "api.py")
    src = open(path).read()
    tree = ast.parse(src)
    model = cachelint.ModuleModel(path, tree, src.splitlines())
    cls = model.classes["TpuPolicyEngine"]
    decls, invalidate, reset = cachelint.derived_model(model, cls)
    assert invalidate is not None
    value_attrs = sorted(
        attr
        for attr, (tokens, _ln) in decls.items()
        if any(t not in cachelint.DERIVED_EXEMPT_TOKENS for t in tokens)
    )
    _check(
        len(value_attrs) >= 10,
        "invalidate", "census",
        f"expected >=10 declared value-derived attrs, found {value_attrs}",
    )
    eng = ctx.engine()
    sentinels = {}
    for attr in value_attrs:
        # a sentinel the reset must overwrite; _kernel_choice keeps a
        # tuned PACKED tile by design, so plant a non-packed choice
        sentinel = (
            {"kernel": "slab"} if attr == "_kernel_choice" else object()
        )
        setattr(eng, attr, sentinel)
        sentinels[attr] = sentinel
    eng.invalidate_after_patch()
    stale = [
        attr
        for attr, sentinel in sentinels.items()
        if getattr(eng, attr, None) is sentinel
    ]
    _check(
        not stale, "invalidate", ",".join(stale) or "-",
        "declared value-derived attr(s) survived invalidate_after_patch",
    )
    return {"value_attrs": len(value_attrs)}


def scenario_pairs_program_key(ctx: Ctx) -> Dict:
    """The serve pair program dispatches per argument signature: a
    changed pair-batch bucket misses (new entry), the original batch
    reverts to a hit (no growth)."""
    eng = ctx.engine()
    cases = ctx.cases(1)
    with _env(CYCLONUS_AOT_CACHE=os.path.join(ctx.tmp, "aot-pairs")):
        eng._pairs_aot = None  # fresh wrapper under the tmp cache
        eng.evaluate_pairs(cases, [(0, 1)] * 4)
        progs = eng._pairs_aot._programs
        n1 = len(progs)
        eng.evaluate_pairs(cases, [(1, 2)] * 4)
        _check(
            len(progs) == n1, "pairs", "values-not-keys",
            "same-shape batch with different VALUES must hit (values are "
            "arguments, not key components)",
        )
        eng.evaluate_pairs(cases, [(0, 1)] * 12)  # new pair-count bucket
        _check(len(progs) == n1 + 1, "pairs", "k", "mutation did not miss")
        eng.evaluate_pairs(cases, [(0, 1)] * 4)  # revert
        _check(len(progs) == n1 + 1, "pairs", "revert", "revert did not hit")
        q2 = ctx.cases(2)
        eng.evaluate_pairs(q2, [(0, 1)] * 4)  # case-count component
        _check(len(progs) == n1 + 2, "pairs", "q", "mutation did not miss")
    return {"programs": len(progs)}


def scenario_grid_program_key(ctx: Ctx) -> Dict:
    """The grid AOT program: same case set hits, a different case count
    misses, revert hits."""
    import numpy as np

    eng = ctx.engine()
    with _env(CYCLONUS_AOT_CACHE=os.path.join(ctx.tmp, "aot-grid")):
        eng._grid_aot = None
        g1 = np.asarray(eng.evaluate_grid(ctx.cases(1)).combined)
        progs = eng._grid_aot._programs
        n1 = len(progs)
        g2 = np.asarray(eng.evaluate_grid(ctx.cases(1)).combined)
        _check(len(progs) == n1, "grid", "steady", "repeat did not hit")
        _check((g1 == g2).all(), "grid", "determinism", "repeat changed verdicts")
        eng.evaluate_grid(ctx.cases(2))
        _check(len(progs) == n1 + 1, "grid", "q", "mutation did not miss")
        eng.evaluate_grid(ctx.cases(1))
        _check(len(progs) == n1 + 1, "grid", "revert", "revert did not hit")
    return {"programs": len(progs)}


def scenario_sharded_program_key(ctx: Ctx) -> Dict:
    """_SHARDED_PROGRAMS (the compiled ring/allgather shard_map pair):
    schedule, pack, and mesh each miss when mutated; reverting each
    reuses the existing entry (no growth — the zero-recompile elastic
    contract's cache)."""
    import jax
    import numpy as np

    from cyclonus_tpu.engine import sharded

    eng = ctx.engine()
    cases = ctx.cases(1)
    sharded._SHARDED_PROGRAMS.clear()
    base = np.asarray(eng.evaluate_grid_sharded(cases, schedule="ring").combined)
    n1 = len(sharded._SHARDED_PROGRAMS)
    _check(n1 >= 1, "sharded", "baseline", "no program cached")
    eng.evaluate_grid_sharded(cases, schedule="ring")
    _check(
        len(sharded._SHARDED_PROGRAMS) == n1,
        "sharded", "steady", "repeat did not hit",
    )
    got = np.asarray(
        eng.evaluate_grid_sharded(cases, schedule="allgather").combined
    )
    _check(
        len(sharded._SHARDED_PROGRAMS) == n1 + 1,
        "sharded", "schedule", "mutation did not miss",
    )
    _check(
        (got == base).all(), "sharded", "schedule",
        "ring and allgather diverged (parity, not key, is broken)",
    )
    eng.evaluate_grid_sharded(cases, schedule="ring")
    _check(
        len(sharded._SHARDED_PROGRAMS) == n1 + 1,
        "sharded", "schedule-revert", "revert did not hit",
    )
    # pack flip: evaluate_grid_sharded resolves pack_enabled() per call
    pack_now = os.environ.get("CYCLONUS_PACK", "")
    flipped = "0" if pack_now != "0" else "1"
    with _env(CYCLONUS_PACK=flipped):
        got = np.asarray(
            eng.evaluate_grid_sharded(cases, schedule="ring").combined
        )
        _check(
            len(sharded._SHARDED_PROGRAMS) == n1 + 2,
            "sharded", "pack", "mutation did not miss",
        )
        _check((got == base).all(), "sharded", "pack", "pack flip changed verdicts")
    eng.evaluate_grid_sharded(cases, schedule="ring")
    _check(
        len(sharded._SHARDED_PROGRAMS) == n1 + 2,
        "sharded", "pack-revert", "revert did not hit",
    )
    # mesh: a smaller device subset is a different key
    cpus = jax.devices("cpu")
    if len(cpus) >= 4:
        from jax.sharding import Mesh

        small = Mesh(np.array(cpus[:4]), ("x",))
        got = np.asarray(
            eng.evaluate_grid_sharded(cases, mesh=small, schedule="ring").combined
        )
        _check(
            len(sharded._SHARDED_PROGRAMS) == n1 + 3,
            "sharded", "mesh", "mutation did not miss",
        )
        _check((got == base).all(), "sharded", "mesh", "mesh change broke parity")
    return {"programs": len(sharded._SHARDED_PROGRAMS)}


_RESTART_DRIVER = """
import json, os, random, sys
sys.path.insert(0, {repo!r})
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from cyclonus_tpu.synthetic import build_synthetic
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, aot_cache
from cyclonus_tpu.matcher import build_network_policies

pods, namespaces, policies = build_synthetic(24, 6, random.Random(7))
policy = build_network_policies(True, policies)
engine = TpuPolicyEngine(policy, pods, namespaces)
cases = [PortCase(80, "serve-80-tcp", "TCP")]
grid = np.asarray(engine.evaluate_grid(cases).combined)
pairs = engine.evaluate_pairs(cases, [(0, 1), (2, 3)])
print(json.dumps({{
    "digest": int(grid.sum()),
    "pairs": int(pairs.sum()),
    "aot": aot_cache.counters(),
}}))
"""


def _run_restart_child(cache_dir: str, extra_env: Dict[str, str]) -> Dict:
    env = dict(os.environ)
    env["CYCLONUS_AOT_CACHE"] = cache_dir
    env["CYCLONUS_AUTOTUNE_CACHE"] = "0"
    env["CYCLONUS_JAX_CACHE"] = "0"
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, "-c", _RESTART_DRIVER.format(repo=REPO)],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    if proc.returncode != 0:
        raise HarnessFailure(
            "restart child failed: "
            + proc.stdout[-600:] + proc.stderr[-600:]
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def scenario_aot_restart_subprocess(ctx: Ctx) -> Dict:
    """The restart leg: a fresh process adopts the warm AOT cache with
    ZERO fresh compiles (hit on every component unchanged); a third
    process with ONE key component mutated (the dtype plan, via
    CYCLONUS_PACK) misses every entry — and still produces bit-identical
    verdicts, because the key discriminates programs, not answers."""
    cache = os.path.join(ctx.tmp, "aot-restart")
    pack_now = os.environ.get("CYCLONUS_PACK", "")
    flipped = "0" if pack_now != "0" else "1"
    cold = _run_restart_child(cache, {})
    if cold["aot"]["compiles"] == 0 or cold["aot"]["stores"] == 0:
        raise HarnessFailure(f"cold child did not populate: {cold['aot']}")
    warm = _run_restart_child(cache, {})
    _check(
        warm["aot"]["compiles"] == 0 and warm["aot"]["misses"] == 0,
        "aot-restart", "hit-on-revert",
        f"warm restart recompiled: {warm['aot']}",
    )
    _check(
        warm["digest"] == cold["digest"] and warm["pairs"] == cold["pairs"],
        "aot-restart", "verdicts", "adopted executables changed verdicts",
    )
    mutated = _run_restart_child(cache, {"CYCLONUS_PACK": flipped})
    _check(
        mutated["aot"]["hits"] == 0 and mutated["aot"]["compiles"] > 0,
        "aot-restart", "plan(pack)",
        f"mutated dtype plan still adopted: {mutated['aot']}",
    )
    _check(
        mutated["digest"] == cold["digest"],
        "aot-restart", "pack-parity", "pack flip changed verdicts",
    )
    return {"cold_compiles": cold["aot"]["compiles"]}


def scenario_registry_census(ctx: Ctx) -> Dict:
    """Under CYCLONUS_KEYHARNESS=1 every cache family the acceptance
    list names registers its key components (subprocess: ACTIVE is
    read at import)."""
    code = """
import json, os, random, sys
sys.path.insert(0, {repo!r})
os.environ["CYCLONUS_KEYHARNESS"] = "1"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=8").strip()
import jax
jax.config.update("jax_platforms", "cpu")
from cyclonus_tpu.synthetic import build_synthetic
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, autotune
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.utils import cachekeys

pods, namespaces, policies = build_synthetic(24, 6, random.Random(7))
policy = build_network_policies(True, policies)
engine = TpuPolicyEngine(policy, pods, namespaces)
cases = [PortCase(80, "serve-80-tcp", "TCP")]
engine.evaluate_grid(cases)
engine.evaluate_pairs(cases, [(0, 1)])
engine.evaluate_grid_sharded(cases, schedule="ring")
autotune.make_key({{"n": 1}}, "cpu", "packed32")
reg = cachekeys.registered()
print(json.dumps({{
    "names": sorted(reg),
    "components": {{k: list(v.components) for k, v in reg.items()}},
    "fingerprints": {{k: v.fingerprint for k, v in reg.items()}},
    "packed": engine._pack,
    "count": cachekeys.registered_count(),
}}))
"""
    env = dict(os.environ)
    env["CYCLONUS_AOT_CACHE"] = os.path.join(ctx.tmp, "aot-census")
    env["CYCLONUS_AUTOTUNE_CACHE"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", code.format(repo=REPO)],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    if proc.returncode != 0:
        raise HarnessFailure(
            "census child failed: " + proc.stdout[-600:] + proc.stderr[-600:]
        )
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    names = out["names"]
    _check(
        any(n.startswith("aot:") for n in names),
        "registry", "aot", f"no AOT families registered: {names}",
    )
    for family in ("autotune", "sharded.programs", "aot.shared"):
        _check(family in names, "registry", family, f"not registered: {names}")
    _check(
        tuple(out["components"]["aot.shared"]) == SHARED_TIER_COMPONENTS,
        "registry", "aot.shared",
        f"components differ: {out['components']['aot.shared']}",
    )
    _check(
        "aot:pairs" in names, "registry", "aot:pairs",
        f"serve pair program not registered: {names}",
    )
    if out["packed"]:
        # every program that can trace kernel.packed_any names the
        # contraction's form in its persisted key (PR 35)
        from cyclonus_tpu.engine.kernel import PACKED_CONTRACTION

        for family in ("aot:sharded.grid", "aot:pairs") + tuple(
            n for n in names if n in ("aot:grid", "aot:grid.classes")
        ):
            _check(
                PACKED_CONTRACTION in (out["fingerprints"].get(family) or ""),
                "registry", family,
                f"key does not name the packed contraction: "
                f"{out['fingerprints'].get(family)}",
            )
    _check(out["count"] == len(names), "registry", "count", "census mismatch")
    for name, comps in out["components"].items():
        _check(bool(comps), "registry", name, "registered with no components")
    return {"registered": out["count"]}


#: (name, fn, in_quick_slice)
SCENARIOS: List[Tuple[str, Callable[[Ctx], Dict], bool]] = [
    ("aot_key_fields", scenario_aot_key_fields, True),
    ("shared_tier_key", scenario_shared_tier_key, True),
    ("autotune_key_fields", scenario_autotune_key_fields, True),
    ("invalidate_derived_contract", scenario_invalidate_derived_contract, True),
    ("pairs_program_key", scenario_pairs_program_key, True),
    ("grid_program_key", scenario_grid_program_key, False),
    ("sharded_program_key", scenario_sharded_program_key, False),
    ("aot_restart_subprocess", scenario_aot_restart_subprocess, False),
    ("registry_census", scenario_registry_census, False),
]


def run(
    tmp: str,
    *,
    quick: bool = True,
    only: Optional[List[str]] = None,
    seed: int = 0,
    log: Optional[Callable[[str], None]] = None,
) -> Dict[str, Dict]:
    """Run the scenario set; raises HarnessFailure on the first
    violation.  Returns per-scenario stats."""
    ctx = Ctx(tmp, seed)
    results: Dict[str, Dict] = {}
    for name, fn, in_quick in SCENARIOS:
        if only is not None:
            if name not in only:
                continue
        elif quick and not in_quick:
            continue
        stats = fn(ctx)
        results[name] = stats
        if log is not None:
            log(f"keyharness {name}: OK {stats}")
    return results


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true", help="all scenarios")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--scenarios", nargs="*", default=None,
        help=f"subset (choices: {[n for n, _f, _q in SCENARIOS]})",
    )
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    import tempfile

    with tempfile.TemporaryDirectory(prefix="keyharness-") as tmp:
        results = run(
            tmp,
            quick=not args.full,
            only=args.scenarios,
            seed=args.seed,
            log=print if args.verbose else None,
        )
    print(
        f"keyharness: {len(results)} scenario(s) passed "
        f"({', '.join(sorted(results))})",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
