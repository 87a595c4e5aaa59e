"""The in-process system under test: the one file through which the `sweep`
and `oneshot` kinds reach the program.  It takes the generators' plain data in
by the program's own parser and returns what the entries return; it computes
no verdict itself.

An entry is named by the configuration (`"entry"`): the tables entry
`evaluate_grid` (what probe.runner.SimulatedRunner drives) or the counts entry
`evaluate_grid_counts`.

In a rehearsal (BENCH_REHEARSE=1, the CPU, tiny sizes) it asks by name for the
routes the chip takes by default at the real sizes - class compression and the
Pallas kernels, here in interpret mode - so that a rehearsal walks the code
the chip run will.  On the chip every option stays at its default.
"""

import os


def rehearsing() -> bool:
    return os.environ.get("BENCH_REHEARSE") == "1"


def parse_policies(policy_dicts):
    from cyclonus_tpu.kube.yaml_io import parse_policy_dict

    return [parse_policy_dict(d) for d in policy_dicts]


def build_policy(parsed):
    from cyclonus_tpu.matcher.builder import build_network_policies

    return build_network_policies(True, parsed)


def new_engine(policy, pods, namespaces):
    from cyclonus_tpu.engine.api import TpuPolicyEngine

    return TpuPolicyEngine(
        policy, pods, namespaces, class_compress="1" if rehearsing() else None
    )


def port_cases(cases):
    from cyclonus_tpu.engine.api import PortCase

    return [PortCase(*c) for c in cases]


def routes() -> list:
    """The PathSpec names recorded since the last call (traced runs arm the
    recorder with CYCLONUS_PLANHARNESS=1), without repeats, in order."""
    from cyclonus_tpu.engine import planspec

    seen = []
    for r in planspec.drain():
        if r not in seen:
            seen.append(r)
    return seen


class TablesEntry:
    """evaluate_grid: dispatch returns at once; the fetch waits and copies."""
    result = "tables"

    @staticmethod
    def evaluate(engine, cases):
        return engine.evaluate_grid(cases)

    @staticmethod
    def ready(out):
        out.block_until_ready()

    @staticmethod
    def fetch(out):
        return out.ingress, out.egress, out.combined


class CountsEntry:
    """evaluate_grid_counts: one call, the integers come back inside it."""
    result = "counts"

    @staticmethod
    def evaluate(engine, cases):
        return engine.evaluate_grid_counts(
            cases, backend="pallas" if rehearsing() else None
        )

    @staticmethod
    def ready(out):
        pass

    @staticmethod
    def fetch(out):
        return {k: int(out[k]) for k in ("ingress", "egress", "combined", "cells")}


ENTRIES = {"evaluate_grid": TablesEntry, "evaluate_grid_counts": CountsEntry}
