"""Job runners (reference: probe/jobrunner.go).

The simulated runner is THE TPU hot path: instead of the reference's
sequential per-job loop (jobrunner.go:68-74), engine='tpu' compiles the
(policy, resources) pair once and evaluates the whole verdict grid on
device, then scatters per-job results out of the grid.  engine='oracle'
keeps the scalar per-job evaluation for parity checking.

Kube runners remain host-side concurrency (they are I/O bound cluster exec
calls): a thread pool replaces the reference's 15-goroutine pool.
"""

from __future__ import annotations

import logging
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from ..kube.ikubernetes import IKubernetes, KubeError
from ..matcher.core import Policy
from ..telemetry import instruments as ti
from ..telemetry import spans
from ..telemetry.spans import span
from .connectivity import (
    CONNECTIVITY_ALLOWED,
    CONNECTIVITY_BLOCKED,
    CONNECTIVITY_CHECK_FAILED,
    CONNECTIVITY_INVALID_NAMED_PORT,
    CONNECTIVITY_INVALID_PORT_PROTOCOL,
    CONNECTIVITY_UNKNOWN,
)
from .job import Job, JobResult, Jobs
from .probeconfig import ProbeConfig
from .resources import Resources
from .table import Table

DEFAULT_ENGINE = "tpu"
# the CLI --engine vocabulary (tpu-sharded = tpu over the device mesh)
ENGINE_CHOICES = ["oracle", "tpu", "tpu-sharded", "native"]

# parity with the reference's logrus trace level (jobrunner.go:80 logs
# every simulated verdict): CYCLONUS_TRACE_VERDICTS=1 logs each verdict
# as it is scattered out of the grid.  Checked per probe (not cached) so
# tests can flip it; the per-verdict work is guarded so the off path
# costs one boolean.
_verdict_logger = logging.getLogger("cyclonus.trace.verdicts")


def _trace_verdicts() -> bool:
    on = os.environ.get("CYCLONUS_TRACE_VERDICTS", "") == "1"
    if on and _verdict_logger.level == logging.NOTSET:
        # the flag is an explicit opt-in: without this, the logger would
        # inherit the CLI's default INFO root level and the DEBUG-level
        # verdict lines would silently vanish (the root handler's own
        # level is NOTSET, so lowering just this logger is enough)
        _verdict_logger.setLevel(logging.DEBUG)
    return on


def _log_verdict(engine: str, job, ingress: str, egress: str, combined: str) -> None:
    _verdict_logger.debug(
        "verdict [%s] %s -> %s %s/%s: ingress=%s egress=%s combined=%s",
        engine,
        job.from_key,
        job.to_key,
        job.resolved_port,
        job.protocol,
        ingress,
        egress,
        combined,
    )


class JobRunner:
    def run_jobs(self, jobs: List[Job]) -> List[JobResult]:
        raise NotImplementedError

    def run_jobs_with_resources(
        self, jobs: List[Job], resources: Optional[Resources]
    ) -> List[JobResult]:
        """Runners that can exploit whole-cluster context (the TPU grid
        path) override this; the default ignores resources.  Wrappers
        delegating both methods compose transparently."""
        return self.run_jobs(jobs)


class Runner:
    """jobrunner.go:13-58."""

    def __init__(self, job_runner: JobRunner):
        self.job_runner = job_runner

    def run_probe_for_config(
        self, probe_config: ProbeConfig, resources: Resources
    ) -> Table:
        return Table.from_job_results(
            resources, self._run_probe(resources.get_jobs_for_probe_config(probe_config), resources)
        )

    def _run_probe(self, jobs: Jobs, resources: Resources) -> List[JobResult]:
        results = self.job_runner.run_jobs_with_resources(jobs.valid, resources)

        # invalid buckets (jobrunner.go:36-57)
        for j in jobs.bad_port_protocol:
            results.append(
                JobResult(
                    job=j,
                    ingress=CONNECTIVITY_INVALID_PORT_PROTOCOL,
                    egress=CONNECTIVITY_UNKNOWN,
                    combined=CONNECTIVITY_INVALID_PORT_PROTOCOL,
                )
            )
        for j in jobs.bad_named_port:
            results.append(
                JobResult(
                    job=j,
                    ingress=CONNECTIVITY_INVALID_NAMED_PORT,
                    egress=CONNECTIVITY_UNKNOWN,
                    combined=CONNECTIVITY_INVALID_NAMED_PORT,
                )
            )
        return results


class SimulatedJobRunner(JobRunner):
    """engine='oracle': per-job scalar evaluation (reference behavior).
    engine='tpu': grid evaluation on device, optionally mesh-sharded.
    engine='native': C++ grid evaluation on host (falls back to oracle
    when the native library is unavailable or the shape unsupported)."""

    def __init__(self, policies: Policy, engine: str = DEFAULT_ENGINE, sharded: bool = False):
        if engine == "tpu-sharded":  # CLI alias for engine=tpu + mesh
            engine, sharded = "tpu", True
        if engine not in set(ENGINE_CHOICES) - {"tpu-sharded"}:
            raise ValueError(f"invalid simulated engine {engine!r}")
        self.policies = policies
        self.engine = engine
        self.sharded = sharded

    # --- oracle path (jobrunner.go:68-94) ---

    def run_jobs(self, jobs: List[Job]) -> List[JobResult]:
        return [self.run_job(j) for j in jobs]

    def run_job(self, job: Job) -> JobResult:
        allowed = self.policies.is_traffic_allowed(job.traffic())
        result = JobResult(
            job=job,
            ingress=CONNECTIVITY_ALLOWED
            if allowed.ingress.is_allowed
            else CONNECTIVITY_BLOCKED,
            egress=CONNECTIVITY_ALLOWED
            if allowed.egress.is_allowed
            else CONNECTIVITY_BLOCKED,
            combined=CONNECTIVITY_ALLOWED
            if allowed.is_allowed
            else CONNECTIVITY_BLOCKED,
        )
        ti.VERDICTS.inc(engine="oracle")
        if _trace_verdicts():
            _log_verdict(
                "oracle", job, result.ingress, result.egress, result.combined
            )
        return result

    # --- TPU path ---

    def run_jobs_with_resources(
        self, jobs: List[Job], resources: Optional[Resources]
    ) -> List[JobResult]:
        if self.engine == "oracle" or resources is None or not jobs:
            return self.run_jobs(jobs)
        from ..engine import PortCase

        pods = [
            (p.namespace, p.name, p.labels, p.ip) for p in resources.pods
        ]
        cases: List[PortCase] = []
        case_index: Dict[PortCase, int] = {}
        for job in jobs:
            case = PortCase(job.resolved_port, job.resolved_port_name, job.protocol)
            if case not in case_index:
                case_index[case] = len(cases)
                cases.append(case)

        if self.engine == "native":
            from ..native import (
                NativeUnavailable,
                NativeUnsupported,
                evaluate_grid_native,
            )

            try:
                grid = evaluate_grid_native(
                    self.policies, pods, resources.namespaces, cases
                )
            except (NativeUnavailable, NativeUnsupported):
                return self.run_jobs(jobs)
            pod_index = {k: i for i, k in enumerate(grid.pod_keys)}
        else:
            # a backend that cannot initialise raises here with JAX's
            # own message; the engine name is never rewritten
            from ..engine import TpuPolicyEngine

            with span(
                "probe.simulated",
                engine=self.engine,
                sharded=self.sharded,
                pods=len(pods),
                cases=len(cases),
                jobs=len(jobs),
            ):
                engine = TpuPolicyEngine(
                    self.policies, pods, resources.namespaces
                )
                pod_index = engine.pod_index()
                if self.sharded:
                    grid = engine.evaluate_grid_sharded(cases)
                else:
                    grid = engine.evaluate_grid(cases)

        trace = _trace_verdicts()
        results = []
        for job in jobs:
            qi = case_index[
                PortCase(job.resolved_port, job.resolved_port_name, job.protocol)
            ]
            ingress, egress, combined = grid.job_verdict(
                qi, pod_index[job.from_key], pod_index[job.to_key]
            )
            results.append(
                JobResult(
                    job=job,
                    ingress=CONNECTIVITY_ALLOWED if ingress else CONNECTIVITY_BLOCKED,
                    egress=CONNECTIVITY_ALLOWED if egress else CONNECTIVITY_BLOCKED,
                    combined=CONNECTIVITY_ALLOWED if combined else CONNECTIVITY_BLOCKED,
                )
            )
            if trace:
                r = results[-1]
                _log_verdict(self.engine, job, r.ingress, r.egress, r.combined)
        ti.VERDICTS.inc(len(jobs), engine=self.engine)
        return results


class KubeJobRunner(JobRunner):
    """Thread-pool exec of agnhost connect in every source pod
    (jobrunner.go:96-147)."""

    def __init__(self, kubernetes: IKubernetes, workers: int = 15):
        self.kubernetes = kubernetes
        self.workers = workers

    def run_jobs(self, jobs: List[Job]) -> List[JobResult]:
        if not jobs:
            return []
        with ThreadPoolExecutor(max_workers=self.workers) as pool:
            return list(pool.map(self._run_one, jobs))

    def _run_one(self, job: Job) -> JobResult:
        connectivity = self._probe_connectivity(job)
        return JobResult(job=job, combined=connectivity)

    def _probe_connectivity(self, job: Job) -> str:
        """jobrunner.go:134-147: setup failure => checkfailed; command
        failure => blocked; success => allowed."""
        try:
            _stdout, _stderr, command_err = self.kubernetes.execute_remote_command(
                job.from_namespace, job.from_pod, job.from_container, job.client_command()
            )
        except KubeError:
            return CONNECTIVITY_CHECK_FAILED
        if command_err is not None:
            return CONNECTIVITY_BLOCKED
        return CONNECTIVITY_ALLOWED


class KubeBatchJobRunner(JobRunner):
    """One in-pod worker batch per source pod (jobrunner.go:149-227)."""

    def __init__(self, kubernetes: IKubernetes, workers: int = 9):
        from ..worker.client import Client

        self.client = Client(kubernetes)
        self.workers = workers

    def run_jobs(self, jobs: List[Job]) -> List[JobResult]:
        from ..telemetry import events
        from ..worker.model import Batch, Request

        job_map: Dict[str, Job] = {}
        batches: Dict[str, Batch] = {}
        for job in jobs:
            if job.from_key not in batches:
                batches[job.from_key] = Batch(
                    namespace=job.from_namespace,
                    pod=job.from_pod,
                    container=job.from_container,
                )
            batches[job.from_key].requests.append(
                Request(
                    key=job.key(),
                    protocol=job.protocol,
                    host=job.to_host,
                    port=job.resolved_port,
                )
            )
            job_map[job.key()] = job

        if events.enabled():
            # trace context crosses the wire on the batch: the parent
            # path is captured HERE (the issuing step's thread) because
            # the pool threads below have no span state of their own
            parent = spans.current_path()
            for batch in batches.values():
                batch.trace_id = events.trace_id() or ""
                batch.parent_span = parent

        results: List[JobResult] = []
        if not batches:
            return results
        with ThreadPoolExecutor(max_workers=self.workers) as pool:
            for batch_results in pool.map(self._run_batch, batches.values()):
                for key, connectivity in batch_results:
                    results.append(JobResult(job=job_map[key], combined=connectivity))
        return results

    def _run_batch(self, batch):
        try:
            # re-adopt the issuing step's path on this pool thread so
            # the driver-side exec span — and, through the refreshed
            # parent_span, the remote worker's spans — nest under it
            with spans.adopt(batch.parent_span):
                with span(
                    "probe.kube_batch",
                    pod=batch.key(),
                    requests=len(batch.requests),
                ):
                    if batch.trace_id:
                        batch.parent_span = spans.current_path()
                    results = self.client.batch(batch)
        except KubeError:
            return [(r.key, CONNECTIVITY_CHECK_FAILED) for r in batch.requests]
        for r in results:
            # workers report per-probe latency (worker/model.py
            # latency_ms, optional for old workers): the driver-side
            # histogram is the real-probe latency data source.  Blocked/
            # failed probes carry retry+timeout time, so they land in a
            # separate outcome series and never distort the ok-latency
            # percentiles.
            if r.latency_ms is not None:
                ti.PROBE_LATENCY.observe(
                    r.latency_ms / 1000.0,
                    source="batch",
                    outcome="ok" if r.is_success() else "error",
                )
        return [
            (
                r.request.key,
                CONNECTIVITY_ALLOWED if r.is_success() else CONNECTIVITY_BLOCKED,
            )
            for r in results
        ]


def new_simulated_runner(
    policies: Policy, engine: str = DEFAULT_ENGINE, sharded: bool = False
) -> Runner:
    return Runner(SimulatedJobRunner(policies, engine=engine, sharded=sharded))


def new_kube_runner(kubernetes: IKubernetes, workers: int = 15) -> Runner:
    return Runner(KubeJobRunner(kubernetes, workers))


def new_kube_batch_runner(kubernetes: IKubernetes, workers: int = 9) -> Runner:
    return Runner(KubeBatchJobRunner(kubernetes, workers))
