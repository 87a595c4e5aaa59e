"""Traffic kind `oneshot`: one client asking what-if questions, closed loop -
what a user of `analyze --mode probe`, or one step of `generate`, waits for.

Set-up takes the configuration's first `policy_sets` policy sets over its one
cluster, in the order the seed gives them.
A request takes the next set's policy objects through `build_network_policies`
-> a NEW `TpuPolicyEngine` -> the tables entry on the mix's one case set ->
tables on the host.  Reports `whatif_mean_s`: the whole window over the
what-ifs it completed.
"""

from benchmarks import closed_loop, generators, harness, program
from benchmarks.reference import GridReference


def run(cell):
    cfg, sizes = cell.config, cell.sizes
    spans = harness.Spans()
    entry = program.ENTRIES[cfg["entry"]]
    pods, namespaces, _ = generators.build_synthetic(
        dict(sizes, policies=0), cfg["generator"], cell.seed
    )
    policy_sets = [
        generators.policy_set(sizes, cfg["generator"], cell.seed, j)
        for j in range(cell.traffic["policy_sets"])
    ]
    parsed = [program.parse_policies(p) for p in policy_sets]
    (cases,) = generators.case_sets(cell.traffic["case_sets"])
    port_cases = program.port_cases(cases)
    state = {}

    def request(key):
        with spans.span("bench.request"):
            with spans.span("bench.matcher.build"):
                policy = program.build_policy(parsed[key])
            with spans.span("bench.engine.new"):
                state["engine"] = program.new_engine(policy, pods, namespaces)
            with spans.span("bench.first_dispatch"):
                out = entry.evaluate(state["engine"], port_cases)
                entry.ready(out)
            with spans.span("bench.fetch"):
                return entry.fetch(out)

    def expected_of(key, broken):
        ref = GridReference(pods, namespaces, policy_sets[key], broken)
        return closed_loop.answer(ref, entry.result, cases, broken)

    def finish(keys, elapsed, setup_s):
        return {"whatif_mean_s": elapsed / len(keys), "setup_s": setup_s}

    return closed_loop.run(
        cell, spans=spans, n_keys=len(policy_sets), n_pods=len(pods),
        result=entry.result, request=request, cases_of=lambda key: len(cases),
        release=state.clear, expected_of=expected_of, finish=finish,
    )
