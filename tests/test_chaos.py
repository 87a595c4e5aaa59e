"""Chaos layer (cyclonus_tpu/chaos): injection-point semantics, the
seeded harness scenarios, and the serve warmup/degraded-query surface
(docs/DESIGN.md "Cold start & chaos")."""

import os

import pytest

from cyclonus_tpu import chaos
from cyclonus_tpu.chaos import harness
from cyclonus_tpu.telemetry import instruments as ti


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends disarmed — chaos state is process-
    global by design (the env var IS the control surface)."""
    chaos.reset("")
    yield
    chaos.reset("")


class TestInjection:
    def test_disarmed_hooks_are_noops(self):
        chaos.fire("backend_init")  # must not raise
        assert chaos.stall("worker_wire_stall") == 0.0
        assert chaos.injected() == {}

    def test_fire_respects_budget(self):
        chaos.reset("backend_init:2")
        for _ in range(2):
            with pytest.raises(chaos.ChaosError):
                chaos.fire("backend_init")
        chaos.fire("backend_init")  # budget spent: disarmed
        assert chaos.injected() == {"backend_init": 2}

    def test_spec_parses_count_and_arg(self):
        chaos.reset("worker_wire_stall:1:0.01,delta_apply:3")
        assert chaos.armed("worker_wire_stall")
        slept = chaos.stall("worker_wire_stall")
        assert slept == pytest.approx(0.01)
        assert not chaos.armed("worker_wire_stall")
        assert chaos.armed("delta_apply")

    def test_env_change_rearms(self, monkeypatch):
        monkeypatch.setenv("CYCLONUS_CHAOS", "delta_apply:1")
        assert chaos.armed("delta_apply")
        monkeypatch.setenv("CYCLONUS_CHAOS", "")
        assert not chaos.armed("delta_apply")

    def test_injections_counted_in_telemetry(self):
        before = ti.CHAOS_INJECTIONS.value(point="worker_wire")
        chaos.reset("worker_wire:1")
        with pytest.raises(chaos.ChaosError):
            chaos.fire("worker_wire")
        assert ti.CHAOS_INJECTIONS.value(point="worker_wire") == before + 1

    def test_malformed_spec_degrades(self):
        chaos.reset("::,bad:notanint:x,,ok:1")
        # malformed parts never raise; the parseable point arms
        assert chaos.armed("ok")


class TestScenarios:
    def test_backend_init_flake_recovers_with_structured_error(self):
        report = harness.scenario_backend_init_flake(seed=1, failures=2)
        assert report["ok"]
        assert report["attempts"] == 3
        assert report["last_error"]["type"] == "ChaosError"
        assert "backend_init" in report["last_error"]["message"]

    def test_worker_wire_retries_and_counts(self):
        report = harness.scenario_worker_wire(seed=1, failures=2)
        assert report["ok"] and report["retries"] == 2

    def test_delta_drop_rolls_back_and_recovers(self):
        report = harness.scenario_delta_drop(seed=1, n_pods=12)
        assert report["ok"] and report["rolled_back"]
        assert all(p["pods"] == 12 for p in report["parity"])

    def test_poisoned_caches_degrade_to_fresh_compile(self, tmp_path):
        report = harness.scenario_poisoned_caches(
            seed=1, workdir=str(tmp_path), n_pods=16
        )
        assert report["ok"]
        assert report["entries_poisoned"] >= 1
        assert report["rejected"] >= 1

    @pytest.mark.slow
    def test_serve_kill_restart_bounds_ttfv(self, tmp_path):
        report = harness.scenario_serve_kill_restart(
            seed=1, workdir=str(tmp_path), n_pods=16, churn_steps=3
        )
        assert report["ok"]
        assert report["ttfv_s"] <= report["ttfv_bound_s"]
        assert report["oracle_checked"] >= 16

    def test_run_all_reports_per_scenario(self):
        report = harness.run_all(
            seed=2, only=["backend_init_flake", "worker_wire"], bound_s=60.0
        )
        assert report["ok"]
        assert set(report["scenarios"]) == {
            "backend_init_flake", "worker_wire",
        }
        for r in report["scenarios"].values():
            assert r["ok"] and r["seconds"] >= 0


class TestServeWarmup:
    def _cluster(self, n=16):
        from cyclonus_tpu.synthetic import synthetic_cluster

        return synthetic_cluster(n, 2, 5)

    def test_defer_ready_serves_degraded_then_live_parity(self):
        from cyclonus_tpu.serve import VerdictService
        from cyclonus_tpu.worker.model import FlowQuery

        pods, namespaces = self._cluster()
        svc = VerdictService(pods, namespaces, [], defer_ready=True)
        assert not svc.ready
        ready, detail = svc.readiness()
        assert not ready and "prewarming" in detail
        keys = list(svc.pods)
        queries = [
            FlowQuery(src=keys[i], dst=keys[-1 - i], port=80,
                      protocol="TCP", port_name="serve-80-tcp")
            for i in range(4)
        ]
        degraded0 = ti.SERVE_DEGRADED.value()
        deg = svc.query(queries)
        assert ti.SERVE_DEGRADED.value() == degraded0 + len(queries)
        pw = svc.prewarm(pair_buckets=[1, 4])
        assert svc.ready and pw["programs"] == 2
        live = svc.query(queries)
        assert ti.SERVE_DEGRADED.value() == degraded0 + len(queries)
        # graceful degradation must be EXACT degradation: the oracle
        # fallback and the engine agree verdict for verdict
        for a, b in zip(deg, live):
            assert (a.ingress, a.egress, a.combined) == (
                b.ingress, b.egress, b.combined
            )

    def test_degraded_unknown_pod_answers_error(self):
        from cyclonus_tpu.serve import VerdictService
        from cyclonus_tpu.worker.model import FlowQuery

        pods, namespaces = self._cluster()
        svc = VerdictService(pods, namespaces, [], defer_ready=True)
        v = svc.query([FlowQuery(src="no/such", dst=list(svc.pods)[0],
                                 port=80, protocol="TCP")])[0]
        assert v.error and "no/such" in v.error

    def test_default_construction_is_ready(self):
        from cyclonus_tpu.serve import VerdictService

        pods, namespaces = self._cluster(8)
        svc = VerdictService(pods, namespaces, [])
        assert svc.ready
        assert svc.state()["ready"] is True

    def test_prewarm_failure_still_marks_ready(self, monkeypatch):
        from cyclonus_tpu.serve import VerdictService

        pods, namespaces = self._cluster(8)
        svc = VerdictService(pods, namespaces, [], defer_ready=True)

        def boom(*a, **k):
            raise RuntimeError("compile exploded")

        monkeypatch.setattr(svc.engine, "evaluate_pairs", boom)
        pw = svc.prewarm(pair_buckets=[1])
        assert svc.ready
        assert "compile exploded" in (pw["error"] or "")

    def test_serve_cli_exits_nonzero_when_prewarm_fails(
        self, monkeypatch, capsys
    ):
        """A replica whose query programs cannot initialise, compile or
        run is dead, not degraded: `cyclonus-tpu serve` reports the
        prewarm error and exits 1 before it reads a single batch."""
        import cyclonus_tpu.cli

        def boom(self, *a, **k):
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(
            "cyclonus_tpu.engine.TpuPolicyEngine.evaluate_pairs", boom
        )
        rc = cyclonus_tpu.cli.main(["serve", "--synthetic-pods", "8"])
        err = capsys.readouterr().err
        assert rc == 1
        assert "serve: prewarm failed: RuntimeError: Unable to initialize" in err
        assert "engine ready" not in err

    def test_state_counts_degraded_queries(self):
        from cyclonus_tpu.serve import VerdictService
        from cyclonus_tpu.worker.model import FlowQuery

        pods, namespaces = self._cluster(8)
        svc = VerdictService(pods, namespaces, [], defer_ready=True)
        keys = list(svc.pods)
        svc.query([FlowQuery(src=keys[0], dst=keys[1], port=80,
                             protocol="TCP")])
        st = svc.state()
        assert st["ready"] is False
        assert st["degraded_queries"] >= 1


class TestWorkerRetry:
    """Satellite: worker/client.py per-batch timeout + jittered-backoff
    retry over the one canonical backoff helper."""

    def _batch(self):
        from cyclonus_tpu.worker.model import Batch

        return Batch(namespace="x", pod="a", container="c", requests=[])

    def test_flaky_exec_retries_then_succeeds(self, monkeypatch):
        from cyclonus_tpu.kube.ikubernetes import KubeError
        from cyclonus_tpu.worker.client import Client

        monkeypatch.setenv("CYCLONUS_WORKER_BACKOFF_S", "0.01")
        calls = {"n": 0}

        class FlakyKube:
            def execute_remote_command(self, ns, pod, container, command):
                calls["n"] += 1
                if calls["n"] <= 2:
                    return "", "", KubeError("wire died")
                return "[]", "", None

        retries0 = ti.WORKER_RETRIES.value()
        results = Client(FlakyKube()).batch(self._batch())
        assert results == [] and calls["n"] == 3
        assert ti.WORKER_RETRIES.value() == retries0 + 2

    def test_exhausted_retries_raise_with_last_error(self, monkeypatch):
        from cyclonus_tpu.kube.ikubernetes import KubeError
        from cyclonus_tpu.worker.client import Client

        monkeypatch.setenv("CYCLONUS_WORKER_BACKOFF_S", "0.01")
        monkeypatch.setenv("CYCLONUS_WORKER_RETRIES", "1")

        class DeadKube:
            def execute_remote_command(self, ns, pod, container, command):
                return "", "", KubeError("wire dead")

        with pytest.raises(KubeError) as ei:
            Client(DeadKube()).batch(self._batch())
        assert "after 2 attempt(s)" in str(ei.value)
        assert "wire dead" in str(ei.value)

    def test_timeout_bounds_a_wedged_worker(self, monkeypatch):
        import time as _time

        from cyclonus_tpu.kube.ikubernetes import KubeError
        from cyclonus_tpu.worker.client import Client

        monkeypatch.setenv("CYCLONUS_WORKER_TIMEOUT_S", "0.2")
        monkeypatch.setenv("CYCLONUS_WORKER_RETRIES", "0")
        monkeypatch.setenv("CYCLONUS_WORKER_BACKOFF_S", "0.01")

        class WedgedKube:
            def execute_remote_command(self, ns, pod, container, command):
                _time.sleep(30)

        t0 = _time.perf_counter()
        with pytest.raises(KubeError) as ei:
            Client(WedgedKube()).batch(self._batch())
        assert _time.perf_counter() - t0 < 10
        assert "timed out" in str(ei.value)

    def test_stall_injection_trips_timeout_then_recovers(self, monkeypatch):
        """The chaos worker_wire_stall point + the per-batch timeout +
        the retry compose: one stalled attempt, then success."""
        from cyclonus_tpu.worker.client import Client

        monkeypatch.setenv("CYCLONUS_WORKER_TIMEOUT_S", "0.3")
        monkeypatch.setenv("CYCLONUS_WORKER_BACKOFF_S", "0.01")
        chaos.reset("worker_wire_stall:1:5")

        class OkKube:
            def execute_remote_command(self, ns, pod, container, command):
                return "[]", "", None

        retries0 = ti.WORKER_RETRIES.value()
        results = Client(OkKube()).batch(self._batch())
        assert results == []
        assert ti.WORKER_RETRIES.value() == retries0 + 1


class TestCli:
    def test_chaos_cli_runs_selected_scenarios(self):
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["CYCLONUS_AOT_CACHE"] = "0"
        proc = subprocess.run(
            [sys.executable, "-m", "cyclonus_tpu", "chaos",
             "--scenario", "backend_init_flake",
             "--scenario", "worker_wire", "--json"],
            capture_output=True, text=True, timeout=240, cwd=repo, env=env,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        import json as _json

        report = _json.loads(proc.stdout[proc.stdout.index("{"):])
        assert report["ok"]

    def test_chaos_cli_rejects_unknown_scenario(self):
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-m", "cyclonus_tpu", "chaos",
             "--scenario", "nope"],
            capture_output=True, text=True, timeout=120, cwd=repo,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 2
        assert "unknown scenario" in proc.stderr
