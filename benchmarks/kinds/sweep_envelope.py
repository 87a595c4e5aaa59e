"""Traffic kind `sweep_envelope`: the `sweep_generated` kind's one client and
closed loop over the Kubernetes scale envelope (`k8s-envelope-150k`), with the
reference that reads what its policies hold: matchExpressions, endPort ranges,
named ports and SCTP (`reference_envelope.EnvelopeReference`, exact int64
counts over the pods' groups; `reference.GridReference` reads matchLabels only
and holds a pod x pod row a group).

The cluster and its policies come from the configuration's `generator` block
(`generators_envelope.build`).  A request is the configuration's entry (the
counts entry) on a case set, at the program's defaults; `sweep_cells_per_s`
and `correct` (`count_requests_wrong`) are computed as in `sweep_generated`.

One refusal of its own, before the warm-up: an engine that kept no class
state.  The cell measures the class route at a realistic ratio; a program
whose constructor could not classify this cluster (one whose selector
pass runs over PODS, past its work budget here: class route
`no_selector_pass`) would fall to the dense counts route over 150,004 pods,
whose precompute holds gigabytes a direction and runs for minutes.  Such a
run ends here at once, with exit code 4 and the class route's outcome.

The engine is built at the program's defaults; a rehearsal lowers the class
floor to its own pod count, as `sweep_generated` does, and asks for the
Pallas kernels (interpret mode) through the counts entry as every rehearsal
does.
"""

import os

from benchmarks import closed_loop, generators, generators_envelope, harness, program
from benchmarks.reference_envelope import EnvelopeReference


def new_engine(policy, pods, namespaces, rehearse: bool):
    from cyclonus_tpu.engine.api import TpuPolicyEngine

    if rehearse:
        os.environ.setdefault("CYCLONUS_CLASS_MIN_PODS", str(len(pods)))
    return TpuPolicyEngine(policy, pods, namespaces)


OUTCOMES = ("kept", "no_reduction", "below_floor", "no_selector_pass", "off")


def refuse_without_classes(engine) -> None:
    stats = engine.class_compression_stats()
    if stats.get("active"):
        return
    from cyclonus_tpu.telemetry import instruments

    route = [o for o in OUTCOMES if instruments.CLASS_ROUTE.value(outcome=o)]
    harness.say(
        f"benchmark: the engine kept no class state over {stats.get('pods')} "
        f"pods (class_route {'/'.join(route) or 'not counted'}): the dense "
        "counts route at this size is not what the cell measures; refused"
    )
    raise SystemExit(4)


def run(cell):
    cfg = cell.config
    spans = harness.Spans()
    entry = program.ENTRIES[cfg["entry"]]
    pods, namespaces, policies = generators_envelope.build(
        cell.sizes, cfg["generator"], cell.seed
    )
    sets = generators.case_sets(cell.traffic["case_sets"])
    port_sets = [program.port_cases(s) for s in sets]
    state = {"engine": new_engine(
        program.build_policy(program.parse_policies(policies)), pods, namespaces,
        cell.rehearse,
    )}
    refuse_without_classes(state["engine"])

    def request(key):
        with spans.span("bench.request"):
            with spans.span("bench.evaluate"):
                out = entry.evaluate(state["engine"], port_sets[key])
            with spans.span("bench.fetch"):
                return entry.fetch(out)

    references = {}

    def expected_of(key, broken):
        if broken not in references:
            references[broken] = EnvelopeReference(pods, namespaces, policies, broken)
        return closed_loop.answer(references[broken], entry.result, sets[key], broken)

    n = len(pods)

    def finish(keys, elapsed, setup_s):
        cells = sum(len(sets[k]) * n * n for k in keys)
        return {"sweep_cells_per_s": cells / elapsed, "setup_s": setup_s}

    return closed_loop.run(
        cell, spans=spans, n_keys=len(sets), n_pods=n, result=entry.result,
        request=request, cases_of=lambda key: len(sets[key]),
        release=state.clear, expected_of=expected_of, finish=finish,
    )
