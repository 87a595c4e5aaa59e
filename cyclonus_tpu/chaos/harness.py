"""The chaos suite: seeded, bounded fault-injection scenarios
(docs/DESIGN.md "Cold start & chaos"; `make chaos` runs them all,
`cyclonus-tpu chaos` is the CLI).

Each scenario injects ONE fault class and asserts the designed
degradation — retry, rollback, fresh compile, bounded restart — plus
the differential invariant that matters after the fault: verdicts stay
oracle-exact.  Scenarios are pure functions returning a report dict
with an "ok" flag; run_all wraps each in the bounded-run discipline so
a wedged scenario costs its bound, never the suite.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time
from typing import Dict, List, Optional

from . import ChaosError, disarm, injected, reset
from ..synthetic import synthetic_cluster

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: default wall-clock bound on a restarted replica's time-to-first-
#: verdict (CYCLONUS_CHAOS_TTFV_S overrides; generous because a CPU CI
#: restart pays the full jax import, not just the engine build)
DEFAULT_TTFV_BOUND_S = 150.0


def _ttfv_bound_s() -> float:
    try:
        return float(os.environ.get("CYCLONUS_CHAOS_TTFV_S", str(DEFAULT_TTFV_BOUND_S)))
    except ValueError:
        return DEFAULT_TTFV_BOUND_S


def _held_accelerator() -> Optional[str]:
    """The non-CPU platform this process has already initialised, or
    None — asked without importing or initialising JAX."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


class _Serve:
    """A real `cyclonus-tpu serve` subprocess on the JSON-lines wire
    (stderr to a file so a chatty child can never deadlock the pipe)."""

    def __init__(self, n_pods: int, n_ns: int, seed: int, workdir: str,
                 tag: str, env: Optional[Dict[str, str]] = None):
        self.stderr_path = os.path.join(workdir, f"serve-{tag}.stderr")
        # children INHERIT the caller's platform choice (`make chaos`
        # and the test suite export JAX_PLATFORMS=cpu themselves), so a
        # parent that already holds an accelerator would start children
        # that cannot attach to it: a chip belongs to one process
        held = _held_accelerator()
        if held is not None:
            raise RuntimeError(
                f"this process holds the {held} backend; a serve child "
                "could not attach to it.  Drive serve children from a "
                "process that has not initialised JAX, or pin "
                "JAX_PLATFORMS=cpu"
            )
        full_env = dict(os.environ)
        full_env.update(env or {})
        self._stderr = open(self.stderr_path, "w")
        self.started_at = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cyclonus_tpu", "serve",
             "--synthetic-pods", str(n_pods),
             "--synthetic-namespaces", str(n_ns),
             "--seed", str(seed)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._stderr, text=True, bufsize=1,
            env=full_env, cwd=REPO,
        )

    def round_trip(self, line: str) -> dict:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        reply = self.proc.stdout.readline()
        if not reply:
            raise RuntimeError(
                f"serve died mid-reply (rc={self.proc.poll()}); stderr "
                f"tail: {open(self.stderr_path).read()[-500:]}"
            )
        return json.loads(reply)

    def kill(self) -> None:
        self.proc.kill()
        self.proc.wait(timeout=30)
        self._stderr.close()

    def close(self) -> int:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        rc = self.proc.wait(timeout=60)
        self._stderr.close()
        return rc


def _oracle_check(pods_state, namespaces, netpols, queries, verdicts) -> int:
    """Every wire verdict must equal the scalar oracle over the SAME
    post-delta state the harness mirrored — the restarted replica is a
    rebuild, so this IS the incremental==rebuild==oracle parity leg."""
    from ..analysis.oracle import oracle_verdicts, traffic_for_cell
    from ..engine.api import PortCase
    from ..matcher.builder import build_network_policies

    policy = build_network_policies(True, list(netpols))
    plist = list(pods_state.values())
    idx = {f"{p[0]}/{p[1]}": i for i, p in enumerate(plist)}
    checked = 0
    for q, v in zip(queries, verdicts):
        if v.get("Error"):
            raise AssertionError(f"query errored after fault: {v}")
        case = PortCase(q.port, q.port_name, q.protocol)
        want = oracle_verdicts(
            policy,
            traffic_for_cell(plist, namespaces, case, idx[q.src], idx[q.dst]),
        )
        got = (v["Ingress"], v["Egress"], v["Combined"])
        if got != want:
            raise AssertionError(
                f"CHAOS PARITY: {q.src}->{q.dst}: service={got} "
                f"oracle={want}"
            )
        checked += 1
    return checked


def scenario_serve_kill_restart(
    seed: int = 0,
    workdir: Optional[str] = None,
    n_pods: int = 24,
    churn_steps: int = 6,
    ttfv_bound_s: Optional[float] = None,
) -> Dict:
    """SIGKILL a serve replica mid-churn, restart it against the same
    (persistent) caches, and bound its time-to-first-verdict; verdicts
    after the restart — including after a fresh delta batch — must be
    oracle-exact."""
    import tempfile

    from ..worker.model import Batch, Delta, FlowQuery

    bound = ttfv_bound_s if ttfv_bound_s is not None else _ttfv_bound_s()
    workdir = workdir or tempfile.mkdtemp(prefix="cyclonus-chaos-")
    n_ns = 3
    rng = random.Random(seed)
    pods, namespaces = synthetic_cluster(n_pods, n_ns, seed)
    state = {f"{p[0]}/{p[1]}": p for p in pods}
    keys = list(state)

    def churn_line(step: int) -> tuple:
        key = keys[rng.randrange(len(keys))]
        ns, name = key.split("/", 1)
        labels = {"pod": f"p{step}", "app": f"app{rng.randrange(20)}",
                  "tier": f"tier{rng.randrange(5)}"}
        return key, labels, Batch(
            namespace="", pod="", container="",
            deltas=[Delta(kind="pod_labels", namespace=ns, name=name,
                          labels=dict(labels))],
        ).to_json()

    # phase 1: a replica under churn, killed without warning mid-stream
    srv = _Serve(n_pods, n_ns, seed, workdir, "victim")
    applied_before_kill = 0
    for step in range(churn_steps):
        _key, _labels, line = churn_line(step)
        reply = srv.round_trip(line)
        if reply.get("Error"):
            raise AssertionError(f"churn delta rejected: {reply}")
        applied_before_kill += 1
    srv.kill()  # mid-churn: no shutdown, no flush — the crash case

    # phase 2: the restarted replica rebuilds from its source of truth
    # (the deltas above died with the victim — by design: authoritative
    # state is upstream, the replica is a cache of it), adopting the
    # persistent AOT/autotune caches.  TTFV = process start -> first
    # verdict reply on the wire, prewarm included.
    rng2 = random.Random(seed + 1)
    queries = [
        FlowQuery(src=rng2.choice(keys), dst=rng2.choice(keys), port=80,
                  protocol="TCP", port_name="serve-80-tcp")
        for _ in range(8)
    ]
    srv2 = _Serve(n_pods, n_ns, seed, workdir, "restarted")
    reply = srv2.round_trip(Batch(
        namespace="", pod="", container="", queries=queries,
    ).to_json())
    ttfv_s = time.perf_counter() - srv2.started_at
    checked = _oracle_check(
        {f"{p[0]}/{p[1]}": p for p in pods}, namespaces, [],
        queries, reply.get("Verdicts") or [],
    )
    # post-restart churn: the incremental path must survive the fault
    key, labels, line = churn_line(999)
    delta_reply = srv2.round_trip(line)
    if delta_reply.get("Mode") not in ("incremental", "class_rebuild"):
        raise AssertionError(
            f"post-restart delta fell off the incremental path: "
            f"{delta_reply}"
        )
    p = state[key]
    post_state = dict({f"{q[0]}/{q[1]}": q for q in pods})
    post_state[key] = (p[0], p[1], labels, p[3])
    reply2 = srv2.round_trip(Batch(
        namespace="", pod="", container="", queries=queries,
    ).to_json())
    checked += _oracle_check(
        post_state, namespaces, [], queries, reply2.get("Verdicts") or []
    )
    rc = srv2.close()
    if rc != 0:
        raise AssertionError(f"restarted serve exited rc={rc}")
    if ttfv_s > bound:
        raise AssertionError(
            f"time-to-first-verdict {ttfv_s:.1f}s exceeds the "
            f"{bound:g}s bound (CYCLONUS_CHAOS_TTFV_S)"
        )
    return {
        "ok": True,
        "applied_before_kill": applied_before_kill,
        "ttfv_s": round(ttfv_s, 3),
        "ttfv_bound_s": bound,
        "oracle_checked": checked,
    }


def _poison_file(path: str, mode: str) -> None:
    if mode == "truncate":
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            head = f.read(max(1, size // 2))
        with open(path, "wb") as f:
            f.write(head)
    elif mode == "garbage":
        with open(path, "wb") as f:
            f.write(b"\x00not a pickle\xff" * 64)
    elif mode == "version_skew":
        import pickle

        with open(path, "wb") as f:
            pickle.dump({"v": 9999, "key": "?", "payload": b""}, f)
    else:
        raise ValueError(mode)


def scenario_poisoned_caches(
    seed: int = 0, workdir: Optional[str] = None, n_pods: int = 24
) -> Dict:
    """Poison/truncate/version-skew every persisted cache — AOT
    executables AND the autotune winners — then build a fresh engine:
    it must degrade to fresh compiles (never raise) and stay
    bit-identical to the pre-poison engine."""
    import tempfile

    import numpy as np

    workdir = workdir or tempfile.mkdtemp(prefix="cyclonus-chaos-")
    aot_dir = os.path.join(workdir, "aot")
    tune_path = os.path.join(workdir, "autotune.json")
    saved = {
        k: os.environ.get(k)
        for k in ("CYCLONUS_AOT_CACHE", "CYCLONUS_AUTOTUNE_CACHE")
    }
    os.environ["CYCLONUS_AOT_CACHE"] = aot_dir
    os.environ["CYCLONUS_AUTOTUNE_CACHE"] = tune_path
    try:
        from ..engine import PortCase, TpuPolicyEngine
        from ..engine import aot_cache
        from ..matcher.builder import build_network_policies
        from ..telemetry import instruments as ti

        pods, namespaces = synthetic_cluster(n_pods, 3, seed)
        policy = build_network_policies(True, [])
        cases = [PortCase(80, "chaos-80-tcp", "TCP")]
        eng_a = TpuPolicyEngine(policy, pods, namespaces)
        grid_a = np.asarray(eng_a.evaluate_grid(cases).combined)
        pairs_a = eng_a.evaluate_pairs(cases, [(0, 1), (1, 0)])
        entries = sorted(
            os.path.join(aot_dir, f)
            for f in os.listdir(aot_dir)
            if f.endswith(".aotx")
        )
        if not entries:
            raise AssertionError("no AOT entries written to poison")
        modes = ["truncate", "garbage", "version_skew"]
        for i, path in enumerate(entries):
            _poison_file(path, modes[i % len(modes)])
        with open(tune_path, "w") as f:
            f.write('{"v": 1, "entries": {truncated')
        # the directory's contents were replaced under a live process:
        # without this the fresh engine shares eng_a's executables and
        # never opens a poisoned file
        aot_cache.forget()
        corrupt0 = ti.AOT_CACHE.value(outcome="corrupt") + ti.AOT_CACHE.value(
            outcome="stale"
        )
        eng_b = TpuPolicyEngine(policy, pods, namespaces)
        grid_b = np.asarray(eng_b.evaluate_grid(cases).combined)
        pairs_b = eng_b.evaluate_pairs(cases, [(0, 1), (1, 0)])
        if not np.array_equal(grid_a, grid_b):
            raise AssertionError("grid diverged after cache poisoning")
        if not np.array_equal(pairs_a, pairs_b):
            raise AssertionError("pairs diverged after cache poisoning")
        rejected = (
            ti.AOT_CACHE.value(outcome="corrupt")
            + ti.AOT_CACHE.value(outcome="stale")
            - corrupt0
        )
        if rejected <= 0:
            raise AssertionError(
                "poisoned AOT entries were not detected (no corrupt/"
                "stale outcomes counted)"
            )
        return {
            "ok": True,
            "entries_poisoned": len(entries),
            "rejected": int(rejected),
            "aot": aot_cache.counters(),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def scenario_backend_init_flake(seed: int = 0, failures: int = 2) -> Dict:
    """Arm the `backend_init` point for N failures and drive a retry
    envelope over it (the utils/retry jittered backoff helper): the
    call must recover on attempt N+1 with the structured last-error
    retained."""
    from ..utils.retry import full_jitter_pause
    from . import fire

    tok = reset(f"backend_init:{failures}")
    try:
        rng = random.Random(seed)
        state: Dict = {"attempts": 0, "last_error": None}
        recovered_at = None
        for attempt in range(1, failures + 2):
            state["attempts"] = attempt
            try:
                fire("backend_init")
                recovered_at = attempt
                break
            except ChaosError as e:
                state["last_error"] = {
                    "type": type(e).__name__,
                    "message": str(e)[:200],
                }
            time.sleep(min(0.05, full_jitter_pause(0.01, attempt, rng)))
        if recovered_at != failures + 1:
            raise AssertionError(
                f"retry loop recovered at attempt {recovered_at}, "
                f"expected {failures + 1}"
            )
        if (state["last_error"] or {}).get("type") != "ChaosError":
            raise AssertionError(
                f"structured last_error missing: {state['last_error']}"
            )
        return {
            "ok": True,
            "attempts": state["attempts"],
            "last_error": state["last_error"],
            "injected": injected(),
        }
    finally:
        disarm(tok)


class _InProcessKube:
    """The minimal IKubernetes a worker Client needs: run the in-pod
    worker in-process (same JSON contract as kubectl exec)."""

    def execute_remote_command(self, namespace, pod, container, command):
        from ..worker.worker import run_worker

        return run_worker(command[2]), "", None


def scenario_worker_wire(seed: int = 0, failures: int = 2) -> Dict:
    """Kill the worker wire N times mid-batch: the driver-side client
    must retry with backoff (cyclonus_tpu_worker_retries_total moves)
    and the batch must complete — a dead worker wedges nothing."""
    from ..telemetry import instruments as ti
    from ..worker.client import Client
    from ..worker.model import Batch

    tok = reset(f"worker_wire:{failures}")
    saved = {
        k: os.environ.get(k)
        for k in ("CYCLONUS_WORKER_BACKOFF_S", "CYCLONUS_WORKER_TIMEOUT_S")
    }
    os.environ["CYCLONUS_WORKER_BACKOFF_S"] = "0.01"
    try:
        retries0 = ti.WORKER_RETRIES.value()
        client = Client(_InProcessKube())
        results = client.batch(
            Batch(namespace="x", pod="a", container="c", requests=[])
        )
        retried = int(ti.WORKER_RETRIES.value() - retries0)
        if retried != failures:
            raise AssertionError(
                f"expected {failures} retries, counted {retried}"
            )
        return {
            "ok": True,
            "retries": retried,
            "results": len(results),
            "injected": injected(),
        }
    finally:
        disarm(tok)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def scenario_delta_drop(seed: int = 0, n_pods: int = 16) -> Dict:
    """Drop a delta batch mid-apply (after the authoritative dicts
    mutated): the service must roll the batch back wholesale, stay
    incremental==rebuild==oracle consistent, and accept the next batch
    cleanly."""
    from ..serve import VerdictService
    from ..worker.model import Delta

    pods, namespaces = synthetic_cluster(n_pods, 2, seed)
    svc = VerdictService(pods, namespaces, [])
    epoch0 = svc.epoch
    key = next(iter(svc.pods))
    ns, name = key.split("/", 1)
    delta = Delta(kind="pod_labels", namespace=ns, name=name,
                  labels={"app": "chaos", "pod": "p0", "tier": "t0"})
    tok = reset("delta_apply:1")
    try:
        raised = False
        try:
            svc.apply([delta])
        except ChaosError:
            raised = True
        if not raised:
            raise AssertionError("injected delta_apply fault did not fire")
        if svc.epoch != epoch0:
            raise AssertionError("epoch advanced through a dropped batch")
        if svc.pods[key][2].get("app") == "chaos":
            raise AssertionError("rollback left the mutated pod labels")
        parity1 = svc.verify_parity(oracle_samples=8)
        report = svc.apply([delta])
        if report["epoch"] != epoch0 + 1:
            raise AssertionError(f"post-fault apply failed: {report}")
        parity2 = svc.verify_parity(oracle_samples=8)
        return {
            "ok": True,
            "rolled_back": True,
            "parity": [parity1, parity2],
            "injected": injected(),
        }
    finally:
        disarm(tok)


def scenario_slo_ttfv(
    seed: int = 0,
    workdir: Optional[str] = None,
) -> Dict:
    """The SLO leg, both halves of the ttfv objective's contract:

    (a) kill/restart mid-churn must stay inside the DECLARED
        time-to-first-verdict error budget — CYCLONUS_SLO_TTFV_S, the
        same target the in-service controller enforces, not the looser
        harness bound — so the chaos suite and the SLO engine cannot
        drift apart on what a tolerable restart is;
    (b) the breach path: an over-budget first verdict (forced with a
        tiny target) must dump the flight recorder with the triggering
        objective in its reason, because a breach nobody can diagnose
        afterwards is just an outage with a counter."""
    import dataclasses
    import tempfile

    from ..slo.engine import SloController
    from ..slo.objectives import declared_objectives
    from ..utils import envflags

    workdir = workdir or tempfile.mkdtemp(prefix="cyclonus-chaos-slo-")

    # (a) restart bounded by the declared objective (smaller cluster
    # than serve_kill_restart: this leg asserts the budget, not churn
    # breadth, and the suite pays both scenarios)
    ttfv_target = envflags.get_float("CYCLONUS_SLO_TTFV_S")
    restart = scenario_serve_kill_restart(
        seed=seed, workdir=workdir, n_pods=12, churn_steps=3,
        ttfv_bound_s=ttfv_target,
    )

    # (b) forced breach -> black-box dump naming the objective
    dump_file = os.path.join(workdir, "slo-breach.json")
    ttfv_obj = next(o for o in declared_objectives() if o.name == "ttfv")
    ctl = SloController(
        [dataclasses.replace(ttfv_obj, target_s=0.001)], enforce=True
    )
    prev = os.environ.get("CYCLONUS_FLIGHT_RECORDER_PATH")
    os.environ["CYCLONUS_FLIGHT_RECORDER_PATH"] = dump_file
    try:
        ctl.observe_ttfv(5.0)  # 5s against a 1ms target: exhaustion
    finally:
        if prev is None:
            os.environ.pop("CYCLONUS_FLIGHT_RECORDER_PATH", None)
        else:
            os.environ["CYCLONUS_FLIGHT_RECORDER_PATH"] = prev
    if ctl.state_of("ttfv") != "exhausted":
        raise AssertionError(
            f"over-budget ttfv left state {ctl.state_of('ttfv')!r}, "
            "expected 'exhausted'"
        )
    if not os.path.exists(dump_file):
        raise AssertionError("slo breach produced no flight-recorder dump")
    with open(dump_file) as f:
        dumped = json.load(f)
    if dumped.get("reason") != "slo-breach:ttfv":
        raise AssertionError(
            f"breach dump reason {dumped.get('reason')!r} does not name "
            "the objective (want 'slo-breach:ttfv')"
        )
    breach_entries = [
        e for e in dumped.get("entries") or []
        if e.get("path") == "slo.breach"
    ]
    if not breach_entries:
        raise AssertionError("breach dump carries no slo.breach entry")
    return {
        "ok": True,
        "restart": restart,
        "ttfv_budget_s": ttfv_target,
        "breach_dump": dump_file,
        "breach_reason": dumped["reason"],
    }


def scenario_audit_divergence(
    seed: int = 0,
    workdir: Optional[str] = None,
    n_pods: int = 12,
    check_budget: int = 32,
) -> Dict:
    """The audit plane's end-to-end detection contract, on a REAL serve
    under churn: arm `verdict_corrupt` (one flipped sampled verdict)
    and the shadow-oracle sampler must detect it within the check
    budget, leaving an `audit-divergence` flight-recorder bundle on
    disk; then the SAME churn with the point disarmed must finish with
    no divergence dump at all."""
    import tempfile

    from ..worker.model import Batch, Delta, FlowQuery

    workdir = workdir or tempfile.mkdtemp(prefix="cyclonus-chaos-audit-")
    n_ns = 2
    rng = random.Random(seed)

    def churn(srv, keys, dump_file, budget) -> Optional[int]:
        """Deltas + query batches until the divergence dump appears (the
        audit worker is async — poll between batches); returns the
        number of audited-eligible queries sent before detection, or
        None when the budget ran out without a dump."""
        sent = 0
        for step in range(budget):
            key = keys[rng.randrange(len(keys))]
            ns, name = key.split("/", 1)
            line = Batch(
                namespace="", pod="", container="",
                deltas=[Delta(
                    kind="pod_labels", namespace=ns, name=name,
                    labels={"pod": f"p{step}", "app": f"a{step % 7}"},
                )],
                queries=[FlowQuery(
                    src=keys[rng.randrange(len(keys))],
                    dst=keys[rng.randrange(len(keys))],
                    port=80, protocol="TCP", port_name="serve-80-tcp",
                )],
            ).to_json()
            reply = srv.round_trip(line)
            if reply.get("Error"):
                raise AssertionError(f"churn line rejected: {reply}")
            sent += 1
            deadline = time.perf_counter() + 0.5
            while time.perf_counter() < deadline:
                if os.path.exists(dump_file):
                    return sent
                time.sleep(0.05)
        return None

    pods, _namespaces = synthetic_cluster(n_pods, n_ns, seed)
    keys = [f"{p[0]}/{p[1]}" for p in pods]

    # phase 1: armed — every query sampled (rate 1.0), one corruption
    armed_dump = os.path.join(workdir, "audit-armed.json")
    srv = _Serve(n_pods, n_ns, seed, workdir, "audit-armed", env={
        "CYCLONUS_AUDIT": "1",
        "CYCLONUS_AUDIT_RATE": "1.0",
        "CYCLONUS_CHAOS": "verdict_corrupt:1",
        "CYCLONUS_FLIGHT_RECORDER_PATH": armed_dump,
    })
    try:
        detected_after = churn(srv, keys, armed_dump, check_budget)
    finally:
        srv.kill()
    if detected_after is None:
        raise AssertionError(
            f"armed verdict_corrupt went undetected through "
            f"{check_budget} checks (no audit-divergence dump)"
        )
    with open(armed_dump) as f:
        dumped = json.load(f)
    if dumped.get("reason") != "audit-divergence":
        raise AssertionError(
            f"divergence dump reason {dumped.get('reason')!r} "
            "(want 'audit-divergence')"
        )
    div_entries = [
        e for e in dumped.get("entries") or []
        if e.get("path") == "audit.divergence"
    ]
    if not div_entries:
        raise AssertionError("divergence dump carries no repro bundle")
    bundle = div_entries[-1]
    for field in ("query", "served", "oracle", "route", "epoch", "config"):
        if field not in bundle:
            raise AssertionError(f"repro bundle missing {field!r}")

    # phase 2: disarmed — the same churn must audit clean (no dump)
    clean_dump = os.path.join(workdir, "audit-clean.json")
    srv2 = _Serve(n_pods, n_ns, seed, workdir, "audit-clean", env={
        "CYCLONUS_AUDIT": "1",
        "CYCLONUS_AUDIT_RATE": "1.0",
        "CYCLONUS_CHAOS": "",
        "CYCLONUS_FLIGHT_RECORDER_PATH": clean_dump,
    })
    try:
        clean = churn(srv2, keys, clean_dump, min(check_budget, 8))
        rc = srv2.close()
    except Exception:
        srv2.kill()
        raise
    if rc != 0:
        raise AssertionError(f"disarmed serve exited rc={rc}")
    if clean is not None or os.path.exists(clean_dump):
        raise AssertionError(
            "disarmed run produced an audit-divergence dump — the "
            "sampler diverged with no injected fault"
        )
    return {
        "ok": True,
        "detected_after_checks": detected_after,
        "check_budget": check_budget,
        "bundle_route": bundle.get("route"),
        "bundle_epoch": bundle.get("epoch"),
        "dump": armed_dump,
    }


SCENARIOS = {
    "serve_kill_restart": scenario_serve_kill_restart,
    "audit_divergence": scenario_audit_divergence,
    "slo_ttfv": scenario_slo_ttfv,
    "poisoned_caches": scenario_poisoned_caches,
    "backend_init_flake": scenario_backend_init_flake,
    "worker_wire": scenario_worker_wire,
    "delta_drop": scenario_delta_drop,
}


def run_all(
    seed: int = 0,
    only: Optional[List[str]] = None,
    bound_s: float = 420.0,
) -> Dict:
    """Run the (selected) scenarios, each bounded; returns the suite
    report with per-scenario results and the overall ok flag."""
    from ..utils.bounded import run_bounded

    names = only or list(SCENARIOS)
    out: Dict = {"seed": seed, "scenarios": {}, "ok": True}
    for name in names:
        fn = SCENARIOS[name]
        t0 = time.perf_counter()
        status, value = run_bounded(lambda f=fn: f(seed=seed), bound_s)
        if status == "ok":
            report = value
        else:
            report = {
                "ok": False,
                "error": (
                    f"scenario exceeded the {bound_s:g}s bound"
                    if status == "timeout"
                    else f"{type(value).__name__}: {value}"
                ),
            }
        report["seconds"] = round(time.perf_counter() - t0, 3)
        out["scenarios"][name] = report
        out["ok"] = out["ok"] and bool(report.get("ok"))
    return out
