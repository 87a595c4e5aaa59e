#!/usr/bin/env python
"""`make audit`: the audit-plane drill against a REAL serve subprocess.

Boots `cyclonus-tpu serve` with the audit plane armed at rate 1.0 and a
metrics port, drives deltas + queries over the stdio wire, and asserts
the whole observable surface from the OUTSIDE — the way a fleet
operator would:

  1. /audit answers 200 with checked > 0, diverged == 0, and a state
     digest for every committed epoch;
  2. /state carries the same audit block, and /metrics exports the
     cyclonus_tpu_audit_* family (checked counter > 0, diverged == 0);
  3. a second replica booted from the SAME synthetic cluster at the
     same churn point reports the SAME epoch digest — the replica-vs-
     replica string equality the digests exist for;
  4. an armed `verdict_corrupt` on a third replica produces a nonzero
     diverged count on /audit within the check budget (detection is
     observable from the outside, not just in the flight recorder).

Wired into `make check` via the `audit` target next to the unit legs in
tests/test_audit.py."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_PODS, N_NS, SEED = 12, 2, 19
CHECK_BUDGET = 24


class Serve:
    """A serve subprocess with the audit plane armed and a metrics
    port; stderr to a file so a chatty child can never deadlock."""

    def __init__(self, tag: str, workdir: str, extra_env=None):
        self.stderr_path = os.path.join(workdir, f"serve-{tag}.stderr")
        self._stderr = open(self.stderr_path, "w")
        env = dict(os.environ)
        env.update({
            "CYCLONUS_AUDIT": "1",
            "CYCLONUS_AUDIT_RATE": "1.0",
            "CYCLONUS_AUDIT_SEED": "5",
            "CYCLONUS_FLIGHT_RECORDER_PATH": os.path.join(
                workdir, f"dump-{tag}.json"
            ),
        })
        env.update(extra_env or {})
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cyclonus_tpu", "serve",
             "--synthetic-pods", str(N_PODS),
             "--synthetic-namespaces", str(N_NS),
             "--seed", str(SEED),
             "--metrics-port", "0"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._stderr, text=True, bufsize=1, env=env, cwd=REPO,
        )
        self.url = self._discover_url()

    def _discover_url(self) -> str:
        """The banner prints the ephemeral port; poll stderr for it."""
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with open(self.stderr_path) as f:
                for line in f:
                    if "metrics on " in line:
                        return line.split("metrics on ", 1)[1].split(
                            "/metrics", 1
                        )[0]
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"serve died before banner (rc={self.proc.poll()}): "
                    f"{open(self.stderr_path).read()[-500:]}"
                )
            time.sleep(0.05)
        raise RuntimeError("serve never printed its metrics banner")

    def round_trip(self, line: str) -> dict:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        reply = self.proc.stdout.readline()
        if not reply:
            raise RuntimeError(
                f"serve died mid-reply (rc={self.proc.poll()}); stderr: "
                f"{open(self.stderr_path).read()[-500:]}"
            )
        return json.loads(reply)

    def get(self, path: str):
        with urllib.request.urlopen(self.url + path, timeout=10) as r:
            return r.status, json.loads(r.read().decode())

    def get_text(self, path: str) -> str:
        with urllib.request.urlopen(self.url + path, timeout=10) as r:
            return r.read().decode()

    def close(self) -> int:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        rc = self.proc.wait(timeout=60)
        self._stderr.close()
        return rc

    def kill(self) -> None:
        self.proc.kill()
        self.proc.wait(timeout=30)
        self._stderr.close()


def churn_lines(keys, steps: int, seed: int):
    import random

    from cyclonus_tpu.worker.model import Batch, Delta, FlowQuery

    rng = random.Random(seed)
    for step in range(steps):
        key = keys[rng.randrange(len(keys))]
        ns, name = key.split("/", 1)
        yield Batch(
            namespace="", pod="", container="",
            deltas=[Delta(
                kind="pod_labels", namespace=ns, name=name,
                labels={"pod": f"p{step}", "app": f"a{step % 5}"},
            )],
            queries=[FlowQuery(
                src=keys[rng.randrange(len(keys))],
                dst=keys[rng.randrange(len(keys))],
                port=80, protocol="TCP", port_name="serve-80-tcp",
            )],
        ).to_json()


def wait_audit(srv: Serve, pred, timeout: float = 20.0):
    """Poll /audit until pred(payload) (the worker is async)."""
    deadline = time.monotonic() + timeout
    payload = None
    while time.monotonic() < deadline:
        status, payload = srv.get("/audit")
        assert status == 200, payload
        if pred(payload):
            return payload
        time.sleep(0.1)
    raise AssertionError(f"/audit never satisfied predicate: {payload}")


def main() -> int:
    import tempfile

    from cyclonus_tpu.synthetic import synthetic_cluster

    workdir = tempfile.mkdtemp(prefix="cyclonus-audit-drill-")
    pods, _ns = synthetic_cluster(N_PODS, N_NS, SEED)
    keys = [f"{p[0]}/{p[1]}" for p in pods]
    steps = 6

    # 1+2: a clean replica under churn — /audit, /state, /metrics agree
    a = Serve("a", workdir)
    for line in churn_lines(keys, steps, 1):
        reply = a.round_trip(line)
        assert not reply.get("Error"), reply
    snap = wait_audit(a, lambda p: (
        p["checked"] > 0
        and p["queue_depth"] == 0
        and p["pending_digests"] == 0
        and str(steps) in p["digests"]
    ))
    assert snap["enabled"] is True and snap["diverged"] == 0, snap
    assert set(snap["digests"]) == {str(e) for e in range(steps + 1)}, (
        snap["digests"]
    )
    status, st = a.get("/state")
    assert status == 200 and st["audit"]["enabled"] is True, st
    assert st["audit"]["diverged"] == 0, st
    prom = a.get_text("/metrics")
    assert "cyclonus_tpu_audit_checked_total" in prom
    assert "cyclonus_tpu_audit_diverged_total 0" in prom

    # 3: a second replica, same cluster + same churn -> equal digest
    b = Serve("b", workdir)
    for line in churn_lines(keys, steps, 1):
        reply = b.round_trip(line)
        assert not reply.get("Error"), reply
    snap_b = wait_audit(b, lambda p: str(steps) in p["digests"])
    assert snap_b["digests"][str(steps)] == snap["digests"][str(steps)], (
        "replica digests diverged at the same epoch:\n"
        f"  a: {snap['digests'][str(steps)]}\n"
        f"  b: {snap_b['digests'][str(steps)]}"
    )
    rc_a, rc_b = a.close(), b.close()
    assert rc_a == 0 and rc_b == 0, (rc_a, rc_b)

    # 4: armed corruption is detected, observable on /audit
    c = Serve("c", workdir, extra_env={"CYCLONUS_CHAOS": "verdict_corrupt:1"})
    detected = None
    for i, line in enumerate(churn_lines(keys, CHECK_BUDGET, 2)):
        reply = c.round_trip(line)
        assert not reply.get("Error"), reply
        status, payload = c.get("/audit")
        if payload.get("diverged", 0) > 0:
            detected = i + 1
            break
        time.sleep(0.05)
    if detected is None:
        payload = wait_audit(c, lambda p: p["diverged"] > 0, timeout=10.0)
        detected = CHECK_BUDGET
    last = c.get("/audit")[1]["last_divergence"]
    assert last and last["route"].startswith("serve.query."), last
    assert os.path.exists(os.path.join(workdir, "dump-c.json")), (
        "no audit-divergence dump on disk"
    )
    c.kill()

    print(
        f"audit-drill: OK — {int(snap['checked'])} shadow checks clean "
        f"across {steps + 1} epochs, replica digests equal at epoch "
        f"{steps}, injected corruption detected within {detected} "
        f"churn steps (budget {CHECK_BUDGET})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
