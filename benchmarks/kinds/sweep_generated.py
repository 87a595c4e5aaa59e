"""Traffic kind `sweep_generated`: the `sweep` kind's one client and closed
loop over a cluster that the CONFIGURATION's own generator makes.

`kinds/sweep.py` calls `generators.build_synthetic` by name; here the
configuration's `generator` block names its module under `benchmarks/`
(`"module": "generators_cidr"`), which has `build(sizes, gen, seed) -> (pods,
namespaces, policies)` as plain data, so the next configuration with a
generator of its own adds a generator file and no kind.  Everything else is
the sweep: the traffic file gives `case_sets`, the configuration the sizes and
the `entry`; a request is entry(case set) with the result on the host;
`sweep_cells_per_s` and `correct` are computed as there.

The engine is built at the program's defaults, on the chip and in a rehearsal
alike (`program.new_engine` forces class compression in a rehearsal, which is
the route such a cluster may be there to refuse).  A rehearsal's cluster is
too small for the program to consider class compression at all
(`CYCLONUS_CLASS_MIN_PODS`), so a rehearsal lowers that floor to its own pod
count, unless the caller has set it: the decision the chip run makes at the
real size is then made here too, by the same rule.
"""

import importlib
import os

from benchmarks import closed_loop, generators, harness, program
from benchmarks.reference import GridReference


def new_engine(policy, pods, namespaces, rehearse: bool):
    from cyclonus_tpu.engine.api import TpuPolicyEngine

    if rehearse:
        os.environ.setdefault("CYCLONUS_CLASS_MIN_PODS", str(len(pods)))
    return TpuPolicyEngine(policy, pods, namespaces)


def run(cell):
    cfg = cell.config
    spans = harness.Spans()
    entry = program.ENTRIES[cfg["entry"]]
    generator = importlib.import_module("benchmarks." + cfg["generator"]["module"])
    pods, namespaces, policies = generator.build(cell.sizes, cfg["generator"], cell.seed)
    sets = generators.case_sets(cell.traffic["case_sets"])
    port_sets = [program.port_cases(s) for s in sets]
    state = {"engine": new_engine(
        program.build_policy(program.parse_policies(policies)), pods, namespaces,
        cell.rehearse,
    )}

    def request(key):
        with spans.span("bench.request"):
            with spans.span("bench.evaluate"):
                out = entry.evaluate(state["engine"], port_sets[key])
            with spans.span("bench.fetch"):
                return entry.fetch(out)

    references = {}

    def expected_of(key, broken):
        if broken not in references:
            references[broken] = GridReference(pods, namespaces, policies, broken)
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
