"""The operands of the `counts.classes` route, split into what the engine
owns and what a call brings (engine/api.py `_class_counts_operands`,
engine/tiled.py `evaluate_grid_counts_classes`):

  * the counts are the scalar oracle's, and equal what the row-sum
    programs give when fed a freshly built host `w` (the form before the
    split), on the XLA tile loop and the fused Pallas kernel;
  * the class weights stay on the device from call to call and the one
    host array a call hands the program is its port cases, which the
    `engine.dispatch` span says in `host_operands` / `host_bytes`;
  * the serve layer changes class sizes in place, so a kept `w` that
    outlives a class move or a class rebuild is a wrong count;
  * nothing crosses the link implicitly.
"""

import random

import jax
import numpy as np
import pytest

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.engine.tiled import (
    class_rowsums_plan,
    evaluate_grid_counts_classes,
)
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.serve import VerdictService
from cyclonus_tpu.telemetry import events
from cyclonus_tpu.telemetry.instruments import eval_flight
from cyclonus_tpu.tiers.fuzz import _oracle_table, build_fuzz_case
from cyclonus_tpu.worker.model import Delta

from test_engine_tiled import fuzz_problem
from test_serve import APPS, mk_policy

CASES = [
    PortCase(80, "serve-80-tcp", "TCP"),
    PortCase(81, "serve-81-udp", "UDP"),
    PortCase(80, "serve-80-tcp", "SCTP"),
]
KINDS = ("ingress", "egress", "combined")


def oracle_counts(policy, tiers, pods, namespaces, cases):
    table = _oracle_table(policy, tiers, list(pods), namespaces, list(cases))
    return {k: int(table[..., i].sum()) for i, k in enumerate(KINDS)}


def counts_of(got):
    return {k: got[k] for k in KINDS}


def tiered_case():
    for seed in range(32):
        fc = build_fuzz_case(seed)
        if fc.tiers is not None:
            return fc
    raise AssertionError("generator produced no tiered case in 32 seeds")


def problem(which):
    """(policy, tiers, pods, namespaces, cases) of a parametrised case."""
    if which == "tiered":
        fc = tiered_case()
        policy = build_network_policies(fc.simplify, fc.netpols)
        return policy, fc.tiers, fc.pods, fc.namespaces, fc.cases
    policy, pods, namespaces = fuzz_problem(21, n_extra_pods=12)
    return policy, None, pods, namespaces, CASES[:which]


def fresh_operands(engine, cases):
    """(host class tensors, a `w` built for the call, the case rows): the
    form every call had before the engine kept its own on the device."""
    st = engine._class_state
    pc = st["classes"]
    w, _, _ = class_rowsums_plan(st["ctensors"], pc.n_classes, pc.class_size)
    return st["ctensors"], w, np.stack(engine._port_case_arrays(cases))


def dispatch_attrs(marker):
    return [
        e["args"] for e in events.since(marker)
        if e["ph"] == "E" and e["name"] == "engine.dispatch"
    ]


@pytest.fixture
def traced():
    events.enable()
    try:
        yield
    finally:
        events.disable()
        events.reset()


class TestCountsOperands:
    @pytest.mark.parametrize("which", [1, 2, 3, "tiered"])
    def test_counts_equal_oracle_and_fresh_w(self, which, monkeypatch):
        monkeypatch.setenv("CYCLONUS_PACK", "1")
        policy, tiers, pods, namespaces, cases = problem(which)
        engine = TpuPolicyEngine(
            policy, pods, namespaces, tiers=tiers, class_compress="1"
        )
        if engine._class_state is None:
            pytest.skip("the case compressed to nothing")
        want = oracle_counts(policy, tiers, pods, namespaces, cases)
        got = engine.evaluate_grid_counts(cases)
        assert counts_of(got) == want
        assert got["cells"] == len(cases) * len(pods) ** 2
        pc = engine._class_state["classes"]
        for kernel in ("xla", "pallas"):
            with eval_flight("counts.classes", len(pods), len(cases)) as fl:
                fresh, _ = evaluate_grid_counts_classes(
                    fl, *fresh_operands(engine, cases), pc.n_classes,
                    pc.class_size, len(pods), kernel=kernel,
                )
            assert fresh == got, kernel

    def test_weights_stay_resident_and_one_host_operand(self, traced):
        policy, pods, namespaces = fuzz_problem(21, n_extra_pods=12)
        engine = TpuPolicyEngine(policy, pods, namespaces, class_compress="1")
        assert engine._class_w_dev is None  # built when first needed
        resident, tensors = [], []
        for case_set in (CASES[:1], CASES[1:], CASES):
            marker = events.mark()
            got = engine.evaluate_grid_counts(case_set)
            assert counts_of(got) == oracle_counts(
                policy, None, pods, namespaces, case_set
            )
            (attrs,) = dispatch_attrs(marker)
            assert attrs["host_operands"] == 1
            assert attrs["host_bytes"] == 12 * len(case_set)
            resident.append(engine._class_w_dev)
            tensors.append(engine._class_device_tensors)
        assert isinstance(resident[0], jax.Array)
        assert resident[1] is resident[0] and resident[2] is resident[0]
        # the resident tensor dict is passed as it is: no port cases in it
        assert tensors[1] is tensors[0] and tensors[2] is tensors[0]
        assert not {"q_port", "q_name", "q_proto"} & set(tensors[0])

    def test_host_w_is_counted_as_a_second_operand(self, traced):
        """The counter counts what crosses, whatever a caller passes: a
        `w` handed over from the host, as before the split, shows."""
        policy, pods, namespaces = fuzz_problem(21, n_extra_pods=12)
        engine = TpuPolicyEngine(policy, pods, namespaces, class_compress="1")
        engine.evaluate_grid_counts(CASES[:2])
        pc = engine._class_state["classes"]
        _, w, q_cases = fresh_operands(engine, CASES[:2])
        marker = events.mark()
        with eval_flight("counts.classes", len(pods), 2) as fl:
            evaluate_grid_counts_classes(
                fl, engine._class_device_tensors, w, q_cases, pc.n_classes,
                pc.class_size, len(pods),
            )
        (attrs,) = dispatch_attrs(marker)
        assert attrs["host_operands"] == 2
        assert attrs["host_bytes"] == w.nbytes + q_cases.nbytes

    @pytest.mark.parametrize(
        "pod,action,mode",
        [("p4", "moved", "incremental"), ("p0", "rebuild", "class_rebuild")],
    )
    def test_counts_after_class_sizes_change(self, pod, action, mode):
        """The stale-`w` case.  p4 is a member of a0's class and moves to
        a1's in place; p0 is a0's representative, whose departure
        rebuilds the class state.  Either way the class sizes the kept
        `w` was built from are gone."""
        namespaces = {"x": {"ns": "x"}}
        pods = [
            ("x", f"p{i}", {"app": APPS[i % 2]}, f"10.0.0.{i + 1}")
            for i in range(8)
        ]
        svc = VerdictService(
            pods, namespaces, [mk_policy("pol0", "x", random.Random(3))],
            class_compress="1",
        )
        cases = CASES[:2]

        def oracle():
            return oracle_counts(
                svc._policy, None, svc.pods.values(), dict(svc.namespaces),
                cases,
            )

        before = oracle()
        assert counts_of(svc.engine.evaluate_grid_counts(cases)) == before
        stale = svc.engine._class_w_dev
        assert stale is not None
        actions = []
        inner = svc._inc.update_pod_signature
        svc._inc.update_pod_signature = lambda i: (
            actions.append(inner(i)) or actions[-1]
        )
        r = svc.apply([
            Delta(kind="pod_labels", namespace="x", name=pod,
                  labels={"app": "a1"}),
        ])
        assert r["mode"] == mode, r
        assert actions == [action]
        after = oracle()
        assert after != before  # or a stale `w` would go unnoticed
        assert counts_of(svc.engine.evaluate_grid_counts(cases)) == after
        pc = svc.engine._class_state["classes"]
        assert svc.engine._class_w_dev is not stale
        fresh = np.asarray(svc.engine._class_w_dev)
        np.testing.assert_array_equal(fresh[: pc.n_classes], pc.class_size)
        assert not fresh[pc.n_classes:].any()

    def test_second_call_makes_no_implicit_transfer(self):
        implicit = jax.jit(lambda x: x + 1)
        implicit(np.arange(3))
        try:
            with jax.transfer_guard_host_to_device("disallow"):
                implicit(np.arange(3))
        except Exception:  # the guard's error type is the runtime's own
            pass
        else:
            pytest.skip(
                f"the {jax.default_backend()} backend's transfer guard "
                f"lets a host argument of a jitted call through"
            )
        policy, pods, namespaces = fuzz_problem(21, n_extra_pods=12)
        engine = TpuPolicyEngine(policy, pods, namespaces, class_compress="1")
        first = engine.evaluate_grid_counts(CASES[:2])
        with jax.transfer_guard_host_to_device("disallow"):
            second = engine.evaluate_grid_counts(CASES[1:])
            again = engine.evaluate_grid_counts(CASES[:2])
        assert again == first
        assert counts_of(second) == oracle_counts(
            policy, None, pods, namespaces, CASES[1:]
        )
