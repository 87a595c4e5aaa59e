"""L3 tests: resources immutability (ported from resources_test.go), job
fan-out, bad-port buckets, truth tables, and simulated runner engine parity
(oracle vs tpu) at the probe-table level."""

import pytest

from cyclonus_tpu.kube import MockKubernetes
from cyclonus_tpu.kube.netpol import IntOrString
from cyclonus_tpu.kube.yaml_io import load_policies_from_yaml
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.probe import (
    CONNECTIVITY_INVALID_NAMED_PORT,
    CONNECTIVITY_INVALID_PORT_PROTOCOL,
    Pod,
    ProbeConfig,
    Resources,
    new_simulated_runner,
)
from cyclonus_tpu.probe.probeconfig import PROBE_MODE_SERVICE_NAME


def make_resources() -> Resources:
    kube = MockKubernetes(1.0)
    return Resources.new_default(
        kube,
        ["x", "y", "z"],
        ["a", "b", "c"],
        [80, 81],
        ["TCP", "UDP", "SCTP"],
        pod_creation_timeout_seconds=1,
    )


class TestResources:
    def test_default_creation(self):
        r = make_resources()
        assert len(r.pods) == 9
        assert len(r.namespaces) == 3
        assert all(p.ip.startswith("192.168.") for p in r.pods)
        # 2 ports x 3 protocols = 6 containers per pod
        assert all(len(p.containers) == 6 for p in r.pods)
        # the mock allocates ClusterIPs like a real apiserver so the
        # service-ip probe destination mode works clusterless
        assert r.pods[0].service_ip.startswith("10.96.")

    def test_immutable_updates(self):
        # resources_test.go:immutability specs
        r = make_resources()
        r2 = r.create_namespace("w", {"ns": "w"})
        assert "w" not in r.namespaces and "w" in r2.namespaces

        r3 = r.update_namespace_labels("x", {"ns": "x", "extra": "1"})
        assert r.namespaces["x"] == {"ns": "x"}
        assert r3.namespaces["x"]["extra"] == "1"

        r4 = r.delete_namespace("x")
        assert len(r4.pods) == 6 and len(r.pods) == 9

        r5 = r.set_pod_labels("x", "a", {"pod": "a", "new": "1"})
        assert r.get_pod("x", "a").labels == {"pod": "a"}
        assert r5.get_pod("x", "a").labels["new"] == "1"

        r6 = r.delete_pod("x", "a")
        assert len(r6.pods) == 8
        with pytest.raises(Exception):
            r6.get_pod("x", "a")

        r7 = r.create_pod("x", "d", {"pod": "d"})
        assert len(r7.pods) == 10
        # new pods copy the first pod's containers (reference TODO preserved)
        assert r7.get_pod("x", "d").containers == r.pods[0].containers

    def test_error_cases(self):
        r = make_resources()
        with pytest.raises(Exception):
            r.create_namespace("x", {})
        with pytest.raises(Exception):
            r.delete_namespace("nope")
        with pytest.raises(Exception):
            r.set_pod_labels("x", "nope", {})
        with pytest.raises(Exception):
            r.create_pod("nope", "d", {})


class TestJobFanOut:
    def test_all_available(self):
        r = make_resources()
        jobs = r.get_jobs_all_available_servers(PROBE_MODE_SERVICE_NAME)
        # 9 x 9 pairs x 6 containers
        assert len(jobs.valid) == 9 * 9 * 6
        assert not jobs.bad_named_port and not jobs.bad_port_protocol
        j = jobs.valid[0]
        assert j.to_host.endswith(".svc.cluster.local")
        assert j.resolved_port in (80, 81)
        assert j.resolved_port_name.startswith("serve-")

    def test_numbered_port(self):
        r = make_resources()
        jobs = r.get_jobs_for_named_port_protocol(
            IntOrString(80), "TCP", PROBE_MODE_SERVICE_NAME
        )
        assert len(jobs.valid) == 81
        assert jobs.valid[0].resolved_port_name == "serve-80-tcp"

    def test_unserved_numbered_port(self):
        r = make_resources()
        jobs = r.get_jobs_for_named_port_protocol(
            IntOrString(7777), "TCP", PROBE_MODE_SERVICE_NAME
        )
        assert len(jobs.valid) == 0
        assert len(jobs.bad_port_protocol) == 81

    def test_named_port(self):
        r = make_resources()
        jobs = r.get_jobs_for_named_port_protocol(
            IntOrString("serve-81-udp"), "UDP", PROBE_MODE_SERVICE_NAME
        )
        assert len(jobs.valid) == 81
        assert jobs.valid[0].resolved_port == 81

    def test_bad_named_port(self):
        r = make_resources()
        jobs = r.get_jobs_for_named_port_protocol(
            IntOrString("no-such-port"), "TCP", PROBE_MODE_SERVICE_NAME
        )
        assert len(jobs.bad_named_port) == 81


DENY_ALL_Y = """
apiVersion: networking.k8s.io/v1
kind: NetworkPolicy
metadata:
  name: deny-all
  namespace: y
spec:
  podSelector: {}
  policyTypes:
  - Ingress
"""


class TestSimulatedRunner:
    @pytest.mark.parametrize("engine", ["oracle", "tpu"])
    def test_deny_all_y_table(self, engine):
        r = make_resources()
        policy = build_network_policies(True, load_policies_from_yaml(DENY_ALL_Y))
        runner = new_simulated_runner(policy, engine=engine)
        table = runner.run_probe_for_config(
            ProbeConfig.port_protocol_config(IntOrString(80), "TCP"), r
        )
        for fr, to in table.wrapped.keys():
            item = table.get(fr, to)
            result = list(item.job_results.values())[0]
            expected = "blocked" if to.startswith("y/") else "allowed"
            assert result.combined == expected, (fr, to)

    def test_engines_agree_all_available(self):
        r = make_resources()
        policy = build_network_policies(True, load_policies_from_yaml(DENY_ALL_Y))
        t_oracle = new_simulated_runner(policy, engine="oracle").run_probe_for_config(
            ProbeConfig.all_available_config(), r
        )
        t_tpu = new_simulated_runner(policy, engine="tpu").run_probe_for_config(
            ProbeConfig.all_available_config(), r
        )
        for fr, to in t_oracle.wrapped.keys():
            a = t_oracle.get(fr, to).job_results
            b = t_tpu.get(fr, to).job_results
            assert set(a) == set(b)
            for k in a:
                assert (a[k].ingress, a[k].egress, a[k].combined) == (
                    b[k].ingress,
                    b[k].egress,
                    b[k].combined,
                ), (fr, to, k)

    def test_tpu_engine_raises_on_dead_backend(self, monkeypatch):
        """A backend that fails to initialise raises with its own
        message, and the engine name is not rewritten: `--engine tpu`
        never computes on a host engine behind the caller's back."""
        import cyclonus_tpu.engine as engine_mod

        def dead_backend(*_a, **_k):
            raise RuntimeError(
                "Unable to initialize backend 'tpu': the TPU is already "
                "in use by another process"
            )

        r = make_resources()
        policy = build_network_policies(True, load_policies_from_yaml(DENY_ALL_Y))
        monkeypatch.setattr(engine_mod, "TpuPolicyEngine", dead_backend)
        runner = new_simulated_runner(policy, engine="tpu")
        with pytest.raises(RuntimeError, match="already in use"):
            runner.run_probe_for_config(
                ProbeConfig.port_protocol_config(IntOrString(80), "TCP"), r
            )
        assert runner.job_runner.engine == "tpu"

    def test_bad_buckets_in_table(self):
        r = make_resources()
        policy = build_network_policies(True, [])
        runner = new_simulated_runner(policy, engine="tpu")
        table = runner.run_probe_for_config(
            ProbeConfig.port_protocol_config(IntOrString("no-such"), "TCP"), r
        )
        result = list(table.get("x/a", "x/b").job_results.values())[0]
        assert result.combined == CONNECTIVITY_INVALID_NAMED_PORT

        table2 = runner.run_probe_for_config(
            ProbeConfig.port_protocol_config(IntOrString(9999), "TCP"), r
        )
        result2 = list(table2.get("x/a", "x/b").job_results.values())[0]
        assert result2.combined == CONNECTIVITY_INVALID_PORT_PROTOCOL

    def test_table_rendering(self):
        r = make_resources()
        policy = build_network_policies(True, load_policies_from_yaml(DENY_ALL_Y))
        runner = new_simulated_runner(policy, engine="tpu")
        table = runner.run_probe_for_config(
            ProbeConfig.port_protocol_config(IntOrString(80), "TCP"), r
        )
        rendered = table.render_table()
        assert "x/a" in rendered and "z/c" in rendered
        assert "X" in rendered and "." in rendered
        # multi-port render path
        table_multi = runner.run_probe_for_config(
            ProbeConfig.all_available_config(), r
        )
        rendered_multi = table_multi.render_table()
        assert "TCP/80" in rendered_multi


class TestKubeRunner:
    def test_mock_exec_all_pass(self):
        from cyclonus_tpu.probe import new_kube_runner

        kube = MockKubernetes(1.0)
        r = Resources.new_default(
            kube, ["x"], ["a", "b"], [80], ["TCP"], pod_creation_timeout_seconds=1
        )
        runner = new_kube_runner(kube)
        table = runner.run_probe_for_config(
            ProbeConfig.port_protocol_config(IntOrString(80), "TCP"), r
        )
        for fr, to in table.wrapped.keys():
            result = list(table.get(fr, to).job_results.values())[0]
            assert result.combined == "allowed"
            assert result.ingress is None  # kube probes only see combined

    def test_mock_exec_policy_aware(self):
        # exec_verdict_fn lets the mock emulate a CNI
        from cyclonus_tpu.probe import new_kube_runner

        kube = MockKubernetes(1.0)
        r = Resources.new_default(
            kube, ["x"], ["a", "b"], [80], ["TCP"], pod_creation_timeout_seconds=1
        )
        kube.exec_verdict_fn = lambda ns, pod, cont, cmd: pod != "a"
        runner = new_kube_runner(kube)
        table = runner.run_probe_for_config(
            ProbeConfig.port_protocol_config(IntOrString(80), "TCP"), r
        )
        assert (
            list(table.get("x/a", "x/b").job_results.values())[0].combined
            == "blocked"
        )
        assert (
            list(table.get("x/b", "x/a").job_results.values())[0].combined
            == "allowed"
        )
