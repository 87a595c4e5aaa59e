"""Traffic kind `sweep`: one client sweeping port-case sets over a fixed
cluster, closed loop - what one `probe` process does.

The traffic file gives `case_sets` (lists of [port, protocol]); the
configuration gives the sizes, the generator's parameters and the `entry`
(the tables or the counts entry).  A request is entry(case set) with the
result on the host.  Reports `sweep_cells_per_s`: the verdict cells (port
cases x pods x pods) of all completed requests over the whole window.
"""

from benchmarks import closed_loop, generators, harness, program
from benchmarks.reference import GridReference


def run(cell):
    cfg = cell.config
    spans = harness.Spans()
    entry = program.ENTRIES[cfg["entry"]]
    pods, namespaces, policies = generators.build_synthetic(
        cell.sizes, cfg["generator"], cell.seed
    )
    sets = generators.case_sets(cell.traffic["case_sets"])
    port_sets = [program.port_cases(s) for s in sets]
    state = {"engine": program.new_engine(
        program.build_policy(program.parse_policies(policies)), pods, namespaces
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
