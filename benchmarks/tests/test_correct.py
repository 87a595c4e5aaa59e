"""Run by hand: `python3 -m pytest benchmarks/tests -q` (CPU; about a minute).

Three things the contract asks to be kept as tests beside the benchmark:

  * the grid form of the reference equals its scalar form, cell for cell;
  * every CONTROL - the reference with one stated guarantee broken, put in the
    program's place - comes out as not correct, at a size a test run can hold;
  * a run driven end to end in rehearsal (the look for a chip skipped) with
    the timed path BROKEN underneath - an answer altered where it is produced -
    comes out as `correct: false`, once for every cell, and once for the
    served mix `serve-steady`, which is kept for the cell that PERF.md's first
    open question describes and is in no cell yet.
"""

import contextlib
import io
import json
import os
import random
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from benchmarks import generators, program, reference  # noqa: E402
from benchmarks.grid_check import GridChecker  # noqa: E402
from benchmarks.kinds import wire  # noqa: E402

GEN = {"structure_seed": 1, "vocab": {"pod": 100, "app": 20, "tier": 5, "team": 7},
       "ipblock_share": 0.2, "named_udp_share": 0.3, "ingress_only_share": 0.6}
CASES = [generators.port_case(80, "TCP"), generators.port_case(81, "UDP")]
CELLS = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_grid_reference_equals_the_scalar_walk(seed):
    sizes = {"pods": 120, "namespaces": 3, "policies": 40}
    pods, namespaces, policies = generators.build_synthetic(
        sizes, dict(GEN, structure_seed=seed), seed)
    ref = reference.GridReference(pods, namespaces, policies)
    ingress, egress, combined = ref.tables(CASES)
    by_ns = reference.policies_by_namespace(policies)
    for q, case in enumerate(CASES):
        for s in range(len(pods)):
            for d in range(len(pods)):
                want = reference.flow_verdict(by_ns, namespaces, pods[s], pods[d], case)
                assert (ingress[q, d, s], egress[q, s, d], combined[q, s, d]) == want
    counts = ref.counts(CASES)
    assert counts == {"ingress": int(ingress.sum()), "egress": int(egress.sum()),
                      "combined": int(combined.sum()), "cells": combined.size}


# a size at which each guarantee decides some cell on every seed tried (the
# cells' own sizes: PERF.md)
CONTROL_SIZES = {"pods": 2000, "namespaces": 8, "policies": 400}


@pytest.mark.parametrize("result", ["tables", "counts"])
@pytest.mark.parametrize("guarantee", ["drop_named_ports", "drop_except"])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_with_a_guarantee_broken_is_not_correct(result, guarantee, seed):
    pods, namespaces, policies = generators.build_synthetic(
        CONTROL_SIZES, dict(GEN, structure_seed=seed), seed)
    exact = reference.GridReference(pods, namespaces, policies)
    broken = reference.GridReference(pods, namespaces, policies, guarantee)
    checker = GridChecker(result, len(pods), random.Random(seed))
    checker.record(0, len(CASES), getattr(exact, result)(CASES))
    assert all(v <= limit for _, v, limit in checker.compare(
        lambda key: getattr(exact, result)(CASES)))
    checker.record(0, len(CASES), getattr(exact, result)(CASES))
    checker.substitute(lambda key: getattr(broken, result)(CASES))
    checks = checker.compare(lambda key: getattr(exact, result)(CASES))
    assert any(v > limit for _, v, limit in checks), checks


@pytest.mark.parametrize("seed", [6, 7, 8])
def test_control_stale_reads_is_not_correct(seed):
    cell = _served_cell(seed, 1.0)
    policies = generators.policy_set(cell.sizes, cell.config["generator"], seed)
    pods, namespaces = generators.synthetic_cluster(cell.sizes, cell.config["generator"], seed)
    script = wire.Script(cell, pods)
    script.window(90)
    exact = wire.expected_replies(script.lines, pods, namespaces, policies)
    stale = wire.expected_replies(script.lines, pods, namespaces, policies, stale=True)
    assert all(v == 0 for _, v, _ in wire.compare([e + ("incremental",) for e in exact], exact))
    checks = wire.compare([s + ("incremental",) for s in stale], exact)
    assert dict((n, v) for n, v, _ in checks)["verdicts_wrong"] > 0, checks


def _served_cell(seed, seconds):
    """`mesh-100k-10k` under `serve-steady`, in rehearsal, as run.py would
    build it once BENCHMARK.json lists the cell."""
    import run

    os.environ["BENCH_REHEARSE"] = "1"
    return run.make_cell(
        "mesh-100k-10k.serve-steady", "benchmarks/configs/mesh-100k-10k.json",
        "serve-steady", 1, seed, seconds, False)


def _rehearse(name, monkeypatch, seconds="1"):
    """One whole run of run.py in this process, in rehearsal; its result."""
    import run

    monkeypatch.setenv("BENCH_REHEARSE", "1")
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", name, "--seed", str(2**31 + 5),
        "--seconds", seconds, "--trace", "0"])
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main() == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _altered_tables(fetch):
    def altered(out):
        ingress, egress, combined = fetch(out)
        combined = np.array(combined)
        combined[0, 0, 0] ^= True      # one verdict of every answer
        return ingress, egress, combined
    return altered


def _altered_counts(fetch):
    def altered(out):
        return dict(fetch(out), ingress=fetch(out)["ingress"] + 1)
    return altered


@pytest.mark.parametrize("name", [w["name"] for w in CELLS])
def test_an_altered_answer_is_not_correct(name, monkeypatch):
    assert _rehearse(name, monkeypatch)["correct"] is True
    monkeypatch.setattr(program.TablesEntry, "fetch",
                        staticmethod(_altered_tables(program.TablesEntry.fetch)))
    monkeypatch.setattr(program.CountsEntry, "fetch",
                        staticmethod(_altered_counts(program.CountsEntry.fetch)))
    result = _rehearse(name, monkeypatch)
    assert result["correct"] is False, result


def test_an_altered_served_verdict_is_not_correct(monkeypatch):
    checks = lambda: {n: v for n, v, _ in wire.run(_served_cell(2**31 + 5, 2.0)).checks}
    assert set(checks().values()) == {0}
    monkeypatch.setattr(wire, "CHILD", os.path.join(
        REPO, "benchmarks", "tests", "faulty_child.py"))
    assert checks()["verdicts_wrong"] > 0
