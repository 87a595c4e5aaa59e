"""Dispatch-route harness: the dynamic proof behind tools/planlint.py
(docs/DESIGN.md "Plan surface"), mirroring tests/keyharness.py's role
for the cache lint.

The static pass proves every dispatch leaf records a DECLARED path and
every reachable feature interaction has a matrix cell; this harness
proves the declarations PREDICT: it arms the route recorder
(CYCLONUS_PLANHARNESS=1, read once at import — the strip contract),
sweeps the governing flag/argument matrix through the real public
entry points, and asserts the drained routes equal what
``planspec.predict`` derives from the PathSpec registry alone.  Where
the compatibility matrix says "raise", the harness asserts the live
dispatch raises the cell's EXACT declared message.  A route the
declarations cannot predict is a silent dispatch change — the planlint
failure mode planlint itself cannot see.

The quick slice (tier-1, via tests/test_planlint.py) must exercise
every PathSpec whose coverage is "tier1" — that census is asserted
here, not in the test, so `python -m tests.planharness` fails the same
way.  `--full` adds the slow ring-pipeline leg (`make planharness`).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import random
import sys
from typing import Callable, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the recorder is armed at planspec IMPORT (strip contract) — set the
# flag before any cyclonus_tpu import, plus the standalone-run env the
# pytest path gets from tests/conftest.py
os.environ["CYCLONUS_PLANHARNESS"] = "1"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("CYCLONUS_AUTOTUNE_CACHE", "0")
os.environ.setdefault("CYCLONUS_AOT_CACHE", "0")


class HarnessFailure(AssertionError):
    """A recorded route diverged from the registry's prediction; the
    message names the scenario and both routes."""


def _check(cond: bool, scenario: str, detail: str) -> None:
    if not cond:
        raise HarnessFailure(f"{scenario}: {detail}")


def _expect(scenario: str, actual: List[str], expected: List[str]) -> None:
    _check(
        actual == expected, scenario,
        f"recorded routes {actual} != predicted {expected}",
    )


@contextlib.contextmanager
def _env(**kv: Optional[str]):
    """Set/unset env vars, restoring exactly on exit."""
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
    """Shared scenario context: one lazily built engine per flag
    configuration (24 pods — every program family, inside the tier-1
    budget), plus the recorded-route union for the coverage census."""

    def __init__(self, seed: int):
        self.rng = random.Random(seed)
        self._engines: Dict[Tuple, object] = {}
        self.covered: set = set()

    def _fixture(self):
        from cyclonus_tpu.matcher import build_network_policies
        from cyclonus_tpu.synthetic import build_synthetic

        pods, namespaces, policies = build_synthetic(24, 6, random.Random(7))
        return build_network_policies(True, policies), pods, namespaces

    def engine(self, *, class_compress=None, tiers=False, env=()):
        key = (class_compress, tiers, tuple(env))
        if key not in self._engines:
            from cyclonus_tpu.engine import TpuPolicyEngine

            policy, pods, namespaces = self._fixture()
            kwargs = {}
            if class_compress is not None:
                kwargs["class_compress"] = class_compress
            if tiers:
                kwargs["tiers"] = self._tierset()
            with _env(**dict(env)):
                self._engines[key] = TpuPolicyEngine(
                    policy, pods, namespaces, **kwargs
                )
        return self._engines[key]

    def _tierset(self):
        from cyclonus_tpu.tiers.model import (
            AdminNetworkPolicy,
            TierRule,
            TierScope,
            TierSet,
        )

        return TierSet(anps=[
            AdminNetworkPolicy(
                name="harness-tier", priority=1, subject=TierScope(),
                ingress=[TierRule(action="Allow", peers=[TierScope()])],
            )
        ])

    def cases(self, q: int = 1):
        from cyclonus_tpu.engine import PortCase

        base = [
            PortCase(80, "serve-80-tcp", "TCP"),
            PortCase(81, "serve-81-udp", "UDP"),
        ]
        return base[:q]

    def drain(self) -> List[str]:
        from cyclonus_tpu.engine import planspec

        routes = planspec.drain()
        self.covered.update(routes)
        return routes


# --- scenarios -------------------------------------------------------------


def scenario_grid_routes(ctx: Ctx) -> Dict:
    """evaluate_grid routes on the dense engine and the class-compressed
    engine exactly as the `classes` feature predicts."""
    from cyclonus_tpu.engine import planspec

    eng = ctx.engine()
    ctx.drain()
    eng.evaluate_grid(ctx.cases(1))
    _expect("grid.dense", ctx.drain(), [planspec.predict("grid", {})])

    ceng = ctx.engine(class_compress="1")
    _check(
        ceng.class_compression_stats()["active"],
        "grid.classes", "forced class compression did not activate",
    )
    ctx.drain()
    ceng.evaluate_grid(ctx.cases(1))
    _expect(
        "grid.classes", ctx.drain(),
        [planspec.predict("grid", {"classes": True})],
    )
    return {"routes": 2}


def scenario_sharded_grid_routes(ctx: Ctx) -> Dict:
    """evaluate_grid_sharded: explicit ring / allgather, the default
    (auto) schedule, and the class-compressed route."""
    from cyclonus_tpu.engine import planspec

    eng = ctx.engine()
    cases = ctx.cases(1)
    ctx.drain()
    for schedule in ("ring", "allgather", None):
        kw = {} if schedule is None else {"schedule": schedule}
        eng.evaluate_grid_sharded(cases, **kw)
        feats = {} if schedule is None else {"schedule": schedule}
        _expect(
            f"grid.sharded[{schedule}]", ctx.drain(),
            [planspec.predict("grid_sharded", feats)],
        )
    ceng = ctx.engine(class_compress="1")
    ctx.drain()
    ceng.evaluate_grid_sharded(cases)
    _expect(
        "grid.sharded.classes", ctx.drain(),
        [planspec.predict("grid_sharded", {"classes": True})],
    )
    return {"routes": 4}


def scenario_counts_routes(ctx: Ctx) -> Dict:
    """evaluate_grid_counts backend routing: explicit xla, auto on a
    CPU host, the compressed route — and the tiers x pallas matrix
    cell: auto-fallback silently, explicit request raises the cell's
    exact declared message (live AND predicted)."""
    from cyclonus_tpu.engine import planspec

    eng = ctx.engine()
    cases = ctx.cases(1)
    ctx.drain()
    eng.evaluate_grid_counts(cases, backend="xla")
    _expect(
        "counts.xla", ctx.drain(),
        [planspec.predict("counts", {"backend": "xla"})],
    )
    eng.evaluate_grid_counts(cases)  # auto on CPU resolves to xla
    _expect(
        "counts.auto", ctx.drain(),
        [planspec.predict("counts", {"platform": "cpu"})],
    )
    ceng = ctx.engine(class_compress="1")
    ctx.drain()
    ceng.evaluate_grid_counts(cases)
    _expect(
        "counts.classes", ctx.drain(),
        [planspec.predict("counts", {"classes": True, "platform": "cpu"})],
    )
    # tiers x backend=pallas, explicit: both sides raise the SAME text
    teng = ctx.engine(tiers=True, env=(("CYCLONUS_PACK", "0"),))
    ctx.drain()
    live_msg = pred_msg = None
    try:
        teng.evaluate_grid_counts(cases, backend="pallas")
    except ValueError as e:
        live_msg = str(e)
    try:
        planspec.predict(
            "counts", {"backend": "pallas", "tiers": True, "pack": False}
        )
    except planspec.PlanError as e:
        pred_msg = str(e)
    _check(live_msg is not None, "counts.tiers-pallas", "live did not raise")
    _check(pred_msg is not None, "counts.tiers-pallas", "predict did not raise")
    _check(
        live_msg == pred_msg == planspec.interaction(
            "tiers", "backend=pallas"
        ).message,
        "counts.tiers-pallas",
        f"raise text diverged from the declared cell: live={live_msg!r} "
        f"predicted={pred_msg!r}",
    )
    ctx.drain()  # the raise recorded no route
    # auto on the tiered engine falls back to the xla tile body
    teng.evaluate_grid_counts(cases)
    _expect(
        "counts.tiers-auto", ctx.drain(),
        [planspec.predict(
            "counts", {"platform": "cpu", "tiers": True, "pack": False}
        )],
    )
    return {"routes": 5}


def scenario_counts_steady_routes(ctx: Ctx) -> Dict:
    """The pallas counts path and its steady-state sub-dispatch: the
    cold call (resident) and the split call record only counts.pallas; the
    third (pinned-precompute) call adds the counts.steady.* leaf —
    default, tuned-packed (via a planted kernel choice), and the slab
    kernel on a CYCLONUS_PACK=0 engine (the pack x slab matrix cell
    retires slab under the packed plan)."""
    from cyclonus_tpu.engine import planspec

    cases = ctx.cases(1)
    with _env(CYCLONUS_AUTOTUNE="0"):
        eng = ctx.engine(env=(("CYCLONUS_AUTOTUNE", "0"),))
        ctx.drain()
        cp = planspec.predict("counts", {"backend": "pallas", "pack": True})
        for _ in range(2):  # cold (resident), then split
            eng.evaluate_grid_counts(cases, backend="pallas")
        _expect("counts.pallas.warmup", ctx.drain(), [cp, cp])
        eng.evaluate_grid_counts(cases, backend="pallas")  # steady
        _expect(
            "counts.steady.default", ctx.drain(),
            [cp, planspec.predict("counts_steady", {"pack": True})],
        )
        # a tuned packed choice routes the steady dispatch to the tuned
        # tile (what the autotune's winner adoption sets)
        with eng._slab_lock:
            eng._kernel_choice = {"kernel": "packed", "bs": 8, "bd": 128}
        eng.evaluate_grid_counts(cases, backend="pallas")
        _expect(
            "counts.steady.packed_tuned", ctx.drain(),
            [cp, planspec.predict(
                "counts_steady", {"pack": True, "tuned": True}
            )],
        )
        with eng._slab_lock:
            eng._kernel_choice = None
    # slab kernel: only reachable with the packed plan OFF
    slab_env = (
        ("CYCLONUS_PACK", "0"),
        ("CYCLONUS_PALLAS_SLAB", "1"),
        ("CYCLONUS_AUTOTUNE", "0"),
    )
    import cyclonus_tpu.engine.pallas_kernel as pk

    tiles = {"SLAB_BS": pk.SLAB_BS, "SLAB_BD": pk.SLAB_BD, "SLAB_W": pk.SLAB_W}
    try:
        # tiny tile overrides so the 24-pod cluster spans multiple src
        # tiles (the same trick tests/test_engine_pallas.py uses)
        pk.SLAB_BS = pk.SLAB_BD = pk.SLAB_W = 8
        with _env(**dict(slab_env)):
            seng = ctx.engine(env=slab_env)
            ctx.drain()
            cp0 = planspec.predict(
                "counts", {"backend": "pallas", "pack": False}
            )
            for _ in range(2):
                seng.evaluate_grid_counts(cases, backend="pallas")
            _expect("counts.slab.warmup", ctx.drain(), [cp0, cp0])
            _check(
                isinstance(seng._slab_plan_state, dict),
                "counts.steady.slab",
                f"slab plan did not engage: {seng._slab_plan_state!r}",
            )
            with seng._slab_lock:
                seng._kernel_choice = {"kernel": "slab"}
            seng.evaluate_grid_counts(cases, backend="pallas")
            _expect(
                "counts.steady.slab", ctx.drain(),
                [cp0, planspec.predict(
                    "counts_steady", {"pack": False, "slab": True}
                )],
            )
    finally:
        for k, v in tiles.items():
            setattr(pk, k, v)
    return {"routes": 3}


def scenario_counts_sharded_routes(ctx: Ctx) -> Dict:
    """evaluate_grid_counts_sharded kernel routing: explicit xla, auto
    on CPU, the compressed route, and the tiers x kernel=pallas cell's
    exact raise."""
    from cyclonus_tpu.engine import planspec

    eng = ctx.engine()
    cases = ctx.cases(1)
    ctx.drain()
    eng.evaluate_grid_counts_sharded(cases, kernel="xla")
    _expect(
        "counts.sharded.xla", ctx.drain(),
        [planspec.predict("counts_sharded", {"kernel": "xla"})],
    )
    eng.evaluate_grid_counts_sharded(cases)
    _expect(
        "counts.sharded.auto", ctx.drain(),
        [planspec.predict("counts_sharded", {"platform": "cpu"})],
    )
    ceng = ctx.engine(class_compress="1")
    ctx.drain()
    ceng.evaluate_grid_counts_sharded(cases)
    _expect(
        "counts.sharded.classes", ctx.drain(),
        [planspec.predict("counts_sharded", {"classes": True})],
    )
    teng = ctx.engine(tiers=True, env=(("CYCLONUS_PACK", "0"),))
    ctx.drain()
    live_msg = pred_msg = None
    try:
        teng.evaluate_grid_counts_sharded(cases, kernel="pallas")
    except ValueError as e:
        live_msg = str(e)
    try:
        planspec.predict(
            "counts_sharded", {"kernel": "pallas", "tiers": True}
        )
    except planspec.PlanError as e:
        pred_msg = str(e)
    _check(
        live_msg is not None and live_msg == pred_msg,
        "counts.sharded.tiers-pallas",
        f"raise text diverged: live={live_msg!r} predicted={pred_msg!r}",
    )
    ctx.drain()
    # auto under tiers resolves to the XLA tile body (fallback cell)
    teng.evaluate_grid_counts_sharded(cases)
    _expect(
        "counts.sharded.tiers-auto", ctx.drain(),
        [planspec.predict("counts_sharded", {"tiers": True})],
    )
    return {"routes": 4}


def scenario_ring_family_routes(ctx: Ctx) -> Dict:
    """The mesh counts entry's ring route: both pod axes sharded, taken
    where no chip holds the replicated precompute (the ceiling is
    lowered to 0 for the scenario)."""
    from cyclonus_tpu.engine import api, planspec

    eng = ctx.engine()
    cases = ctx.cases(1)
    ceiling = api._MESH_REPLICATED_MAX_BYTES
    api._MESH_REPLICATED_MAX_BYTES = 0
    try:
        ctx.drain()
        eng.evaluate_grid_counts_sharded(cases)
    finally:
        api._MESH_REPLICATED_MAX_BYTES = ceiling
    _expect(
        "counts.ring", ctx.drain(),
        [planspec.predict("counts_sharded", {"replicated_fits": False})],
    )
    return {"routes": 1}


def scenario_analysis_routes(ctx: Ctx) -> Dict:
    """The point / streaming / analysis entries: blocked grid stream,
    the serve pair program, and the raw firing components."""
    from cyclonus_tpu.engine import planspec

    eng = ctx.engine()
    cases = ctx.cases(1)
    ctx.drain()
    for _ in eng.iter_grid_blocks(cases, block=8):
        pass
    _expect(
        "grid.blocks", ctx.drain(), [planspec.predict("grid_blocks", {})]
    )
    eng.evaluate_pairs(cases, [(0, 1), (2, 3)])
    _expect("pairs.aot", ctx.drain(), [planspec.predict("pairs", {})])
    eng.firing_components(cases)
    _expect("firing.raw", ctx.drain(), [planspec.predict("firing", {})])
    return {"routes": 3}


def scenario_serve_routes(ctx: Ctx) -> Dict:
    """serve's query routing: a deferred-readiness replica answers from
    the degraded scalar oracle; after mark_ready the live engine path
    (which itself dispatches the pair program) takes over — the
    warming x query matrix cell."""
    from cyclonus_tpu.engine import planspec
    from cyclonus_tpu.serve import VerdictService
    from cyclonus_tpu.worker.model import FlowQuery

    namespaces = {ns: {"ns": ns} for ns in ("x", "y")}
    pods = [
        ("x", "p0", {"app": "a0"}, "10.0.0.1"),
        ("y", "p1", {"app": "a1"}, "10.0.0.2"),
    ]
    svc = VerdictService(pods, namespaces, [], defer_ready=True)
    queries = [FlowQuery(src="x/p0", dst="y/p1", port=80, protocol="TCP")]
    ctx.drain()
    svc.query(queries)
    degraded = ctx.drain()
    _check(
        degraded[:1] == [planspec.predict("serve_query", {"warming": True})],
        "serve.query.degraded",
        f"warming query routed {degraded}",
    )
    svc.mark_ready()
    svc.query(queries)
    live = ctx.drain()
    _check(
        live[:1] == [planspec.predict("serve_query", {})],
        "serve.query.live",
        f"live query routed {live}",
    )
    # shed: pin the query_p99 objective exhausted on an armed controller
    # and the same query comes back as a typed refusal route
    from cyclonus_tpu.slo import EXHAUSTED, SloController

    svc2 = VerdictService(pods, namespaces, [], slo=SloController(enforce=True))
    svc2.slo.force_state("query_p99", EXHAUSTED)
    ctx.drain()
    out = svc2.query(queries)
    shed = ctx.drain()
    _check(
        all(v.shed for v in out)
        and shed[:1] == [planspec.predict("serve_query", {"shed": True})],
        "serve.query.shed",
        f"exhausted query routed {shed}",
    )
    return {"routes": 3}


def scenario_audit_routes(ctx: Ctx) -> Dict:
    """The audit plane's shadow-oracle check: a sampled verdict from a
    live service drains through the scalar re-evaluation route."""
    from cyclonus_tpu.audit import AuditController
    from cyclonus_tpu.engine import planspec
    from cyclonus_tpu.serve import VerdictService
    from cyclonus_tpu.worker.model import FlowQuery

    namespaces = {ns: {"ns": ns} for ns in ("x", "y")}
    pods = [
        ("x", "p0", {"app": "a0"}, "10.0.0.1"),
        ("y", "p1", {"app": "a1"}, "10.0.0.2"),
    ]
    svc = VerdictService(
        pods, namespaces, [],
        audit=AuditController(rate=1.0, seed=7, start_worker=False),
    )
    svc.query([FlowQuery(src="x/p0", dst="y/p1", port=80, protocol="TCP")])
    ctx.drain()
    checked = svc.audit.drain()
    routes = ctx.drain()
    _check(
        checked == 1
        and routes[:1] == [planspec.predict("serve_audit", {})],
        "serve.audit.check",
        f"audit check routed {routes} ({checked} checked)",
    )
    return {"routes": 1}


#: (name, fn, in_quick_slice)
SCENARIOS: List[Tuple[str, Callable[[Ctx], Dict], bool]] = [
    ("grid_routes", scenario_grid_routes, True),
    ("sharded_grid_routes", scenario_sharded_grid_routes, True),
    ("counts_routes", scenario_counts_routes, True),
    ("counts_steady_routes", scenario_counts_steady_routes, True),
    ("counts_sharded_routes", scenario_counts_sharded_routes, True),
    ("ring_family_routes", scenario_ring_family_routes, True),
    ("analysis_routes", scenario_analysis_routes, True),
    ("serve_routes", scenario_serve_routes, True),
    ("audit_routes", scenario_audit_routes, True),
]


def coverage_census(ctx: Ctx, *, quick: bool) -> Dict:
    """Every PathSpec whose coverage tier the run claims must have been
    recorded — the tier-1 route-coverage acceptance gate.  device_only
    paths are exempt everywhere (no TPU in this harness)."""
    from cyclonus_tpu.engine import planspec

    want_tiers = {"tier1"} if quick else {"tier1", "slow"}
    missing = sorted(
        p.name for p in planspec.PATHS
        if p.coverage in want_tiers and p.name not in ctx.covered
    )
    _check(
        not missing, "coverage",
        f"declared {sorted(want_tiers)} path(s) never recorded: {missing}",
    )
    return {"covered": len(ctx.covered)}


def run(
    *,
    quick: bool = True,
    only: Optional[List[str]] = None,
    seed: int = 0,
    log: Optional[Callable[[str], None]] = None,
) -> Dict[str, Dict]:
    """Run the scenario set; raises HarnessFailure on the first route
    divergence.  Returns per-scenario stats."""
    ctx = Ctx(seed)
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
            log(f"planharness {name}: OK {stats}")
    if only is None:
        results["coverage_census"] = coverage_census(ctx, quick=quick)
        if log is not None:
            log(f"planharness coverage_census: OK {results['coverage_census']}")
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
    results = run(
        quick=not args.full,
        only=args.scenarios,
        seed=args.seed,
        log=print if args.verbose else None,
    )
    print(
        f"planharness: {len(results)} scenario(s) passed "
        f"({', '.join(sorted(results))})",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
