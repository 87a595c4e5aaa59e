#!/usr/bin/env python
"""`make slo`: the SLO enforcement drill — shed under REAL overload,
non-shed parity, and budget recovery, in one process.

Shrinks the objective knobs (tiny query_p99 target so every real query
is a bad event; 2s/4s burn windows; 1s exit hold) so the full
ok -> exhausted -> ok arc runs in seconds, then:

  1. drives query batches through a VerdictService with enforcement
     armed, scraping the registry between batches (the scrape IS the
     accounting cadence in production — the drill uses the same path),
     until the query_p99 budget exhausts and queries come back SHED;
  2. asserts the shed answers are typed refusals (shed=True + error,
     HTTP-mapped 429 elsewhere) and — the differential gate extended to
     the shed path — that every NON-shed answer stayed bit-identical to
     an unloaded twin service with enforcement off;
  3. stops the load, keeps scraping, and asserts the budget RECOVERS:
     the bad events age out of the slow window, the hysteresis hold
     expires, the route returns to live, budget_remaining returns to
     1.0, and a fresh query answers (twin-identical) again.

Wired into `make check` via the `slo` target next to the unit legs in
tests/test_slo.py."""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# objective knobs BEFORE any cyclonus_tpu import: declared_objectives()
# resolves them when a controller is constructed
os.environ["CYCLONUS_SLO_QUERY_P99_S"] = "0.000001"  # every query is bad
os.environ["CYCLONUS_SLO_FAST_S"] = "2"
os.environ["CYCLONUS_SLO_SLOW_S"] = "4"
os.environ["CYCLONUS_SLO_HOLD_S"] = "1"
os.environ["CYCLONUS_SLO_ENFORCE"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from cyclonus_tpu.synthetic import synthetic_cluster  # noqa: E402
from cyclonus_tpu.slo.engine import SloController  # noqa: E402
from cyclonus_tpu.serve.service import VerdictService  # noqa: E402
from cyclonus_tpu.telemetry import instruments as ti  # noqa: E402
from cyclonus_tpu.worker.model import FlowQuery  # noqa: E402

N_PODS, N_NS, SEED = 16, 2, 11


def bits(v):
    """The answer bits parity compares (latency/epoch excluded: timing
    and apply history may differ between the twins by construction)."""
    return (v.ingress, v.egress, v.combined, v.error)


def scrape() -> None:
    """One registry scrape: runs every registered collector, which is
    what advances the SLO accounting in production."""
    ti.REGISTRY.snapshot()


def main() -> int:
    import random

    pods, namespaces = synthetic_cluster(N_PODS, N_NS, SEED)
    keys = [f"{p[0]}/{p[1]}" for p in pods]
    rng = random.Random(SEED)
    queries = [
        FlowQuery(src=rng.choice(keys), dst=rng.choice(keys), port=80,
                  protocol="TCP", port_name="serve-80-tcp")
        for _ in range(8)
    ]

    svc = VerdictService(pods, namespaces, [])
    twin = VerdictService(
        pods, namespaces, [], slo=SloController(enforce=False)
    )
    assert svc.slo.enforce, "drill requires CYCLONUS_SLO_ENFORCE armed"
    baseline = [bits(v) for v in twin.query(queries)]

    # phase 1: overload until shed.  Every query is a bad event under
    # the shrunk target, so the budget exhausts within a few scrapes.
    shed_seen = 0
    non_shed_checked = 0
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        out = svc.query(queries)
        if all(v.shed for v in out):
            shed_seen += len(out)
            break
        for v, want in zip(out, baseline):
            assert not v.shed, "partial shed inside one batch"
            assert bits(v) == want, (
                f"PARITY under load: {v.query.src}->{v.query.dst}: "
                f"{bits(v)} != {want}"
            )
            non_shed_checked += 1
        scrape()
        time.sleep(0.05)
    assert shed_seen, "overload never shed (budget did not exhaust)"
    snap = svc.slo_snapshot()
    q = snap["objectives"]["query_p99"]
    assert q["state"] == "exhausted", snap
    assert q["budget_remaining"] == 0.0, snap
    assert snap["shed_queries"] >= shed_seen, snap
    shed_verdict = svc.query(queries[:1])[0]
    assert shed_verdict.shed and shed_verdict.error, shed_verdict
    shed_seen += 1

    # phase 2: load stops; bad events age out of the 4s slow window and
    # the 1s hold expires — the budget must RECOVER, not latch.
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        scrape()
        if svc.slo.query_route() == "live":
            break
        time.sleep(0.2)
    snap = svc.slo_snapshot()
    q = snap["objectives"]["query_p99"]
    assert q["state"] == "ok", f"budget never recovered: {snap}"
    assert q["budget_remaining"] == 1.0, snap

    out = [bits(v) for v in svc.query(queries)]
    assert out == baseline, "post-recovery answers drifted from the twin"
    print(
        f"slo-drill: OK — {non_shed_checked} parity-checked answers "
        f"under load, {shed_seen} shed refusals at exhaustion, budget "
        f"recovered to 1.0 and answers twin-identical again"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
