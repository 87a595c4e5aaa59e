#!/usr/bin/env python
"""`make serve-smoke`: end-to-end smoke of the verdict service.

Starts a REAL `cyclonus-tpu serve` subprocess on a seeded synthetic
cluster, then over its stdin/stdout wire:

  1. applies a policy_upsert delta batch (rule-slab path),
  2. applies a single-pod label flip and asserts the INCREMENTAL path
     took it (reply Mode),
  3. queries a seeded set of flows and asserts every verdict against
     the scalar oracle evaluated over the same post-delta state
     (the driver mirrors the delta stream onto its own copy),
  4. closes stdin and asserts a clean rc=0 shutdown.

Wired into `make check` so the serve wire loop, the incremental encode
path, and the oracle stay pinned together in CI."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cyclonus_tpu.analysis.oracle import (  # noqa: E402
    oracle_verdicts,
    traffic_for_cell,
)
from cyclonus_tpu.synthetic import synthetic_cluster  # noqa: E402
from cyclonus_tpu.kube.yaml_io import parse_policy_dict  # noqa: E402
from cyclonus_tpu.matcher.builder import build_network_policies  # noqa: E402
from cyclonus_tpu.worker.model import Batch, Delta, FlowQuery  # noqa: E402

N_PODS, N_NS, SEED = 24, 2, 7

POLICY = {
    "apiVersion": "networking.k8s.io/v1",
    "kind": "NetworkPolicy",
    "metadata": {"name": "smoke-allow-app1", "namespace": "ns0"},
    "spec": {
        "podSelector": {"matchLabels": {"app": "app0"}},
        "policyTypes": ["Ingress"],
        "ingress": [
            {
                "from": [{"podSelector": {"matchLabels": {"app": "app1"}}}],
                "ports": [{"protocol": "TCP", "port": 80}],
            }
        ],
    },
}


def main() -> int:
    import random

    pods, namespaces = synthetic_cluster(N_PODS, N_NS, SEED)
    state = {f"{p[0]}/{p[1]}": p for p in pods}
    flip_key = next(iter(state))
    flip_ns, flip_name = flip_key.split("/", 1)
    new_labels = {"pod": "p0", "app": "app1", "tier": "tier0"}

    line1 = Batch(
        namespace="", pod="", container="",
        deltas=[Delta(kind="policy_upsert", namespace="ns0",
                      name="smoke-allow-app1", policy=POLICY)],
    ).to_json()
    line2 = Batch(
        namespace="", pod="", container="",
        deltas=[Delta(kind="pod_labels", namespace=flip_ns,
                      name=flip_name, labels=dict(new_labels))],
    ).to_json()
    rng = random.Random(99)
    keys = list(state)
    queries = [
        FlowQuery(src=rng.choice(keys), dst=rng.choice(keys), port=80,
                  protocol="TCP", port_name="serve-80-tcp")
        for _ in range(12)
    ]
    line3 = Batch(
        namespace="", pod="", container="", queries=queries
    ).to_json()

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "cyclonus_tpu", "serve",
         "--synthetic-pods", str(N_PODS),
         "--synthetic-namespaces", str(N_NS),
         "--seed", str(SEED), "--max-lines", "3"],
        input="\n".join([line1, line2, line3]) + "\n",
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    if proc.returncode != 0:
        print(proc.stderr[-3000:], file=sys.stderr)
        print(f"serve-smoke: FAIL (rc={proc.returncode})")
        return 1
    replies = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    assert len(replies) == 3, replies
    assert replies[0]["Applied"] == 1 and replies[0]["Epoch"] == 1, replies[0]
    assert replies[1]["Applied"] == 1 and replies[1]["Epoch"] == 2, replies[1]
    assert replies[1]["Mode"] == "incremental", (
        f"single-pod delta must take the incremental path: {replies[1]}"
    )

    # mirror the deltas onto the driver's copy and oracle-check verdicts
    p = state[flip_key]
    state[flip_key] = (p[0], p[1], new_labels, p[3])
    policy = build_network_policies(True, [parse_policy_dict(POLICY)])
    plist = list(state.values())
    idx = {f"{p[0]}/{p[1]}": i for i, p in enumerate(plist)}
    verdicts = replies[2]["Verdicts"]
    assert len(verdicts) == len(queries)
    from cyclonus_tpu.engine.api import PortCase

    checked = 0
    for q, v in zip(queries, verdicts):
        assert not v.get("Error"), v
        case = PortCase(q.port, q.port_name, q.protocol)
        want = oracle_verdicts(
            policy,
            traffic_for_cell(
                plist, namespaces, case, idx[q.src], idx[q.dst]
            ),
        )
        got = (v["Ingress"], v["Egress"], v["Combined"])
        assert got == want, (
            f"PARITY: {q.src}->{q.dst}: service={got} oracle={want}"
        )
        assert v["Epoch"] == 2
        checked += 1
    print(
        f"serve-smoke: OK — policy upsert + incremental pod patch + "
        f"{checked} oracle-checked verdicts, clean shutdown"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
