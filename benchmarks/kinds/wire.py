"""Traffic kind `wire`: an open loop over the real wire of one `serve` replica.

Set-up writes the policy set to a directory, starts the replica as a CHILD on
the stdio JSON-lines wire (benchmarks/serve_child.py; this process never
touches JAX, the child holds the chip), waits for its prewarm, and warms every
kind of line.  The window writes lines on an evenly paced schedule fixed by
the traffic file (`lines_per_s`), never waiting for a reply before the next
line is due; one thread writes and another reads, so a full pipe never stops
the schedule.  Each line is timed from when it was DUE to when its reply was
read.  The count of lines of each kind is the same for every seed; the seed
chooses pods and labels.

Per `cycle` lines one is a delta line, the others query lines of
`queries_per_line` flows (source and destination Zipf over the pods with a
seeded hot set, the mix's port cases alternating).  A delta line also carries
two flows, to and from the changed pod, so that its reply holds the first
verdicts at the new epoch; of every `replace_every` delta lines the last is a
`pod_remove` + `pod_add` pair (a rolling update replaces a pod), the others
one `pod_labels` edit of the `tier` label.

Reports `query_p95_ms` (95th percentile over ALL query lines) and
`visible_mean_ms` (mean over ALL delta lines).

After the window every reply is held to reference.py on a state mirrored
here: each verdict against `flow_verdict` on the state as of its line
(read-your-writes per line), each delta line's Applied and epoch.  The control
`stale_reads` answers from the state BEFORE each line's own deltas.
"""

import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from benchmarks import generators, harness, reference, serve_child, trace_reduce

CHILD = os.path.join(harness.REPO, "benchmarks", "serve_child.py")
READY_TIMEOUT_S = 1100.0   # the first start in a checkout compiles
DRAIN_TIMEOUT_S = 60.0     # a reply may come this long after the window closed
PROFILE_WRITE_S = 200.0    # the child may take this long to write a capture
APPLY_HISTOGRAM = "cyclonus_tpu_serve_apply_seconds"


class Served:
    """One `serve` child and its wire."""

    def __init__(self, cell, policies_dir: str, cluster_seed: int):
        sizes = cell.sizes
        self.stderr_path = os.path.join(cell.scratch("serve"), "child.stderr")
        self._stderr = open(self.stderr_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, CHILD,
             "--synthetic-pods", str(sizes["pods"]),
             "--synthetic-namespaces", str(sizes["namespaces"]),
             "--seed", str(cluster_seed), "--policies", policies_dir,
             "--metrics-port", "0"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self._stderr,
            text=True, bufsize=1, cwd=harness.REPO,
        )
        self.port = None

    def stderr(self) -> str:
        with open(self.stderr_path) as f:
            return f.read()

    def wait_ready(self) -> dict:
        """Blocks until the replica reads batches; the device as it says."""
        deadline = time.monotonic() + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            text = self.stderr()
            m = re.search(r"serve: engine ready on (\w+) \((.+) x(\d+)\)", text)
            if m:
                self.port = int(re.search(r"\(port (\d+)\)", text).group(1))
                return {"platform": m[1], "kind": m[2], "count": int(m[3])}
            if self.proc.poll() is not None:
                break
            time.sleep(0.05)
        raise RuntimeError(f"serve child not ready (rc={self.proc.poll()}):\n{text[-2000:]}")

    def get(self, path: str, timeout: float = 120.0) -> dict:
        with urllib.request.urlopen(f"http://127.0.0.1:{self.port}{path}", timeout=timeout) as r:
            return json.load(r)

    def round_trip(self, line: str) -> str:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        reply = self.proc.stdout.readline()
        if not reply:
            raise RuntimeError(f"serve child gave no reply:\n{self.stderr()[-2000:]}")
        return reply

    def paced(self, lines, interval: float):
        """Write `lines` one every `interval` seconds from now; returns (due,
        sent, received, raw replies), each per line (None where no reply)."""
        n = len(lines)
        due, sent = [0.0] * n, [0.0] * n
        received, replies = [None] * n, [None] * n
        t0 = time.perf_counter() + 0.01

        def write():
            for i, line in enumerate(lines):
                due[i] = t0 + i * interval
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.proc.stdin.write(line + "\n")
                self.proc.stdin.flush()
                sent[i] = time.perf_counter()

        def read():
            for i in range(n):
                reply = self.proc.stdout.readline()
                if not reply:
                    return
                received[i] = time.perf_counter()
                replies[i] = reply

        writer = threading.Thread(target=write, daemon=True)
        reader = threading.Thread(target=read, daemon=True)
        reader.start()
        writer.start()
        writer.join()
        reader.join(timeout=DRAIN_TIMEOUT_S)
        return t0, due, sent, received, replies

    def close(self) -> dict:
        """EOF is the clean shutdown; the child never outlives this.  Returns
        what the child said of its device at exit."""
        try:
            if self.proc.poll() is None:
                self.proc.stdin.close()
                self.proc.wait(timeout=120)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()
            self._stderr.close()
        said = [l for l in self.stderr().splitlines() if l.startswith(serve_child.MARK)]
        return json.loads(said[-1][len(serve_child.MARK):]) if said else {}


class Script:
    """The lines of a run, made from the seed before the window, with the
    state they leave mirrored line by line for the reference."""

    def __init__(self, cell, pods):
        mix = cell.traffic
        self.mix = mix
        self.cases = generators.case_sets([mix["port_cases"]])[0]
        self.slots = [f"{p[0]}/{p[1]}" for p in pods]     # slot -> its pod's key now
        self.state = {k: p for k, p in zip(self.slots, pods)}
        self.n_tiers = cell.config["generator"]["vocab"]["tier"]
        self.rng = np.random.default_rng(cell.rng("script").getrandbits(63))
        order = self.rng.permutation(len(pods))           # the seeded hot set
        weights = 1.0 / np.arange(1, len(pods) + 1) ** mix["zipf"]
        self.hot_order, self.cdf = order, np.cumsum(weights) / weights.sum()
        self.lines = []     # (kind, json line, deltas, flows)
        self.replaced = 0
        self.deltas_made = 0
        self.turn = 0

    def _zipf(self, k: int):
        return self.hot_order[np.searchsorted(self.cdf, self.rng.random(k))]

    def _flow(self, src_key, dst_key):
        case = self.cases[self.turn % len(self.cases)]
        self.turn += 1
        return (src_key, dst_key, case)

    def query_line(self):
        k = self.mix["queries_per_line"]
        flows = [self._flow(self.slots[s], self.slots[d])
                 for s, d in zip(self._zipf(k), self._zipf(k))]
        self._add("query", [], flows)

    def delta_line(self, replace: bool):
        slot = int(self.rng.integers(0, len(self.slots)))
        ns, name, labels, ip = self.state[self.slots[slot]]
        if replace:
            self.replaced += 1
            new = (ns, f"{name.split('.')[0]}.r{self.replaced}", dict(labels), ip)
            deltas = [
                {"Kind": "pod_remove", "Namespace": ns, "Name": name},
                {"Kind": "pod_add", "Namespace": ns, "Name": new[1],
                 "Labels": new[2], "Ip": ip},
            ]
            del self.state[self.slots[slot]]
            self.slots[slot] = f"{ns}/{new[1]}"
        else:
            tier = int(labels["tier"][4:])
            tier = (tier + 1 + int(self.rng.integers(0, self.n_tiers - 1))) % self.n_tiers
            new = (ns, name, dict(labels, tier=f"tier{tier}"), ip)
            deltas = [{"Kind": "pod_labels", "Namespace": ns, "Name": name,
                       "Labels": new[2]}]
        self.state[self.slots[slot]] = new
        other = self.slots[int(self._zipf(1)[0])]
        flows = [self._flow(self.slots[slot], other), self._flow(other, self.slots[slot])]
        self._add("replace" if replace else "labels", deltas, flows)

    def _add(self, kind, deltas, flows):
        batch = {"Namespace": "", "Pod": "", "Container": "", "Requests": []}
        if deltas:
            batch["Deltas"] = deltas
        batch["Queries"] = [
            {"Src": s, "Dst": d, "Port": c[0], "PortName": c[1], "Protocol": c[2]}
            for s, d, c in flows
        ]
        self.lines.append((kind, json.dumps(batch), deltas, flows))

    def window(self, n_lines: int):
        """`n_lines` lines on the mix's pattern: the cycle's middle line is
        the delta line, every `replace_every`-th delta a replacement."""
        cycle, every = self.mix["cycle"], self.mix["replace_every"]
        for i in range(n_lines):
            if i % cycle == cycle // 2:
                self.deltas_made += 1
                self.delta_line(replace=self.deltas_made % every == 0)
            else:
                self.query_line()


def expected_replies(script_lines, pods, namespaces, policies, stale=False):
    """What reference.py says each line's reply holds: (applied, epoch rank,
    [verdict or None]).  The epoch rank counts the delta lines applied.
    `stale` breaks read-your-writes: a line is answered from the state before
    its own deltas (the control)."""
    by_ns = reference.policies_by_namespace(policies)
    state = {f"{p[0]}/{p[1]}": p for p in pods}
    rank, out = 0, []
    for kind, _, deltas, flows in script_lines:
        before = dict(state) if stale and deltas else None
        for d in deltas:
            key = f"{d['Namespace']}/{d['Name']}"
            if d["Kind"] == "pod_remove":
                del state[key]
            elif d["Kind"] == "pod_add":
                state[key] = (d["Namespace"], d["Name"], d["Labels"], d["Ip"])
            else:
                ns, name, _, ip = state[key]
                state[key] = (ns, name, d["Labels"], ip)
        answered_from = before if before is not None else state
        rank_said = rank if before is not None else rank + bool(deltas)
        rank += bool(deltas)
        verdicts = [
            reference.flow_verdict(by_ns, namespaces, answered_from[s], answered_from[d], c)
            if s in answered_from and d in answered_from else None
            for s, d, c in flows
        ]
        out.append((len(deltas) if deltas else None, rank_said, verdicts))
    return out


def said_replies(script_lines, raw_replies):
    """The child's replies in the form of `expected_replies`, with the mode of
    the apply added.  The epoch rank of a delta line is how many distinct,
    rising epochs the delta replies have shown up to it (-1 where its epoch
    did not rise); a query line has the rank of the newest of them if it
    carries that epoch (-1 otherwise).  A verdict that carries an error, a
    shed mark or another epoch than its line counts as none."""
    out, epochs = [], []
    for (kind, _, deltas, flows), raw in zip(script_lines, raw_replies):
        if raw is None:
            out.append(None)
            continue
        reply = json.loads(raw)
        epoch = reply.get("Epoch")
        if deltas:
            risen = isinstance(epoch, int) and (not epochs or epoch > epochs[-1])
            if risen:
                epochs.append(epoch)
            rank = len(epochs) if risen else -1
        else:
            rank = len(epochs) if not epochs or epoch == epochs[-1] else -1
        verdicts = [
            None if v.get("Error") or v.get("Shed") or v.get("Epoch") != epoch
            else (v["Ingress"], v["Egress"], v["Combined"])
            for v in reply.get("Verdicts") or []
        ]
        verdicts += [None] * (len(flows) - len(verdicts))
        out.append((reply.get("Applied") if deltas else None, rank, verdicts,
                    reply.get("Mode")))
    return out


def compare(said, expected):
    """[(name, value, limit)], every comparison exact."""
    unanswered = verdicts_wrong = epochs_wrong = 0
    for got, want in zip(said, expected):
        if got is None:
            unanswered += 1
            continue
        if got[0] != want[0] or got[1] != want[1]:
            epochs_wrong += 1
        verdicts_wrong += sum(1 for g, w in zip(got[2], want[2]) if g is None or g != w)
    return [("lines_unanswered", unanswered, 0),
            ("verdicts_wrong", verdicts_wrong, 0),
            ("epochs_wrong", epochs_wrong, 0)]


def child_counters(served) -> dict:
    """Every counter and histogram of the child's /metrics.json as
    {name: (sum, count)}, labels summed (a counter's count is 0)."""
    out = {}
    for name, family in served.get("/metrics.json").items():
        samples = family.get("samples") or []
        if family.get("type") == "histogram":
            out[name] = (sum(s.get("sum", 0.0) for s in samples),
                         sum(s.get("count", 0) for s in samples))
        elif family.get("type") == "counter":
            out[name] = (sum(s.get("value", 0.0) for s in samples), 0)
    return out


def write_policies(cell, policies) -> str:
    import yaml

    path = cell.scratch("serve", "policies")
    dumper = getattr(yaml, "CSafeDumper", yaml.SafeDumper)
    with open(os.path.join(path, "policies.yaml"), "w") as f:
        yaml.dump_all(policies, f, Dumper=dumper, sort_keys=False)
    return path


def start(cell):
    """(served child ready and checked, pods, namespaces, policies)."""
    cfg = cell.config
    # a mix may hold the child's own cluster draw fixed (`data_seed`), so that
    # --seed orders the traffic and does not change the work; null: from --seed
    data_seed = cell.traffic["data_seed"]
    data_seed = cell.seed if data_seed is None else data_seed
    policies = generators.policy_set(cell.sizes, cfg["generator"], data_seed)
    served = Served(cell, write_policies(cell, policies), data_seed)
    try:
        pods, namespaces = generators.synthetic_cluster(
            cell.sizes, cfg["generator"], data_seed
        )
        harness.check_device(cell, served.wait_ready())
    except BaseException:
        served.close()
        raise
    return served, pods, namespaces, policies


def warm(served, script) -> int:
    """Every kind of line once more than once, in lock step; returns how many
    script lines it used."""
    for _ in range(2):
        script.query_line()
        script.delta_line(replace=False)
        script.delta_line(replace=True)
    for _, line, _, _ in script.lines:
        served.round_trip(line)
    return len(script.lines)


def window_stats(lines, rate: float, t0, due, sent, received) -> dict:
    """What the generator's clocks say of one paced window: each line timed
    from when it was due to when its reply was read."""
    def latencies_ms(kinds):
        return [1e3 * (r - d) for (k, *_), d, r in zip(lines, due, received)
                if k in kinds and r is not None]

    query, delta = latencies_ms(("query",)), latencies_ms(("labels", "replace"))
    answered = [r for r in received if r is not None]
    close = t0 + len(lines) / rate
    return {
        "lines": len(lines),
        "offered_lines_per_s": rate,
        "completed_lines_per_s": len(answered) / (max(answered) - t0),
        "backlog_at_close": sum(1 for r in received if r is None or r > close),
        "query_p50_ms": harness.percentile(query, 50),
        "query_p95_ms": harness.percentile(query, 95),
        "visible_mean_ms": sum(delta) / len(delta),
        "late_p99_ms": harness.percentile(
            [1e3 * (s - d) for s, d in zip(sent, due)], 99),
    }


def run(cell):
    mix = cell.traffic
    served, pods, namespaces, policies = start(cell)
    try:
        script = Script(cell, pods)
        warmed = warm(served, script)
        script.window(max(mix["cycle"], int(mix["lines_per_s"] * cell.seconds)))
        lines = script.lines[warmed:]
        profile = {}
        if cell.trace:
            # the child's own profiler, over the first seconds of the window:
            # its Python-level trace of a whole window takes minutes to write
            capture_s = min(cell.seconds, harness.TRACE_SECONDS)

            def capture():
                profile.update(served.get(
                    f"/profile?seconds={capture_s:g}", timeout=PROFILE_WRITE_S))
            profiler = threading.Thread(target=capture, daemon=True)
            profiler.start()
            time.sleep(0.2)  # the capture is armed before the first line is due
        before = child_counters(served)
        t0, due, sent, received, replies = served.paced(
            [l[1] for l in lines], 1.0 / mix["lines_per_s"]
        )
        after = child_counters(served)
        if cell.trace:
            profiler.join(timeout=PROFILE_WRITE_S)
            if not profile.get("artifact"):
                raise RuntimeError(f"the child's /profile gave no trace: {profile}")
    finally:
        device = served.close()
    if not device:
        raise RuntimeError(f"the serve child said nothing of its device:\n{served.stderr()[-2000:]}")

    # epoch ranks count the window's delta lines alone, on both sides
    base = sum(1 for l in script.lines[:warmed] if l[2])
    answer = lambda stale: [
        (a, r - base, v) for a, r, v in expected_replies(
            script.lines, pods, namespaces, policies, stale=stale)[warmed:]
    ]
    expected = answer(False)
    if cell.control == "stale_reads":
        said = [(a, r, v, "incremental") for a, r, v in answer(True)]
    elif cell.control:
        raise ValueError(f"the wire kind has no control {cell.control!r}")
    else:
        said = said_replies(lines, replies)
    checks = compare(said, expected)

    stats = window_stats(lines, mix["lines_per_s"], t0, due, sent, received)
    answered = [s for s in said if s is not None]
    failed = sum(1 for s in said if s is None or any(v is None for v in s[2]))
    deltas = [s for (k, *_), s in zip(lines, said) if k != "query" and s is not None]
    layers = harness.LayerContext(
        cell=cell, requests=len(answered), device=device,
        spans={"wire.query_service": _service_s(lines, sent, received)},
        counters={
            "apply_seconds_sum": after[APPLY_HISTOGRAM][0] - before[APPLY_HISTOGRAM][0],
            "apply_count": after[APPLY_HISTOGRAM][1] - before[APPLY_HISTOGRAM][1],
            "delta_lines": len(deltas),
            "incremental_lines": sum(1 for s in deltas if s[3] == "incremental"),
            "late_p99_ms": stats["late_p99_ms"],
        },
    )
    # what the child counted inside the window, and its longest silence: a
    # stall shows here as a program built, a fallback, or one long gap
    moved = {k: after[k][0] - before.get(k, (0, 0))[0] for k in after
             if not after[k][1] and after[k][0] != before.get(k, (0, 0))[0]}
    times = [t0] + [r for r in received if r is not None]
    gap, at = max((b - a, i) for i, (a, b) in enumerate(zip(times, times[1:])))
    harness.say(f"wire: child counters moved in the window: {moved}")
    harness.say(f"wire: longest silence {1e3 * gap:.1f} ms before the reply to line {at} ({lines[at][0]})")
    harness.say(f"wire: {json.dumps(stats)}")
    if cell.trace:
        try:
            layers.device_events, layers.host_spans = trace_reduce.read_xplane(
                trace_reduce.find_xplane(profile["artifact"]))
            layers.trace = trace_reduce.reduce_events(layers.device_events, layers.host_spans)
        finally:
            shutil.rmtree(profile["artifact"], ignore_errors=True)
    return harness.Outcome(
        attempted=len(lines), failed=failed, checks=checks, device=device, layers=layers,
        end_to_end={
            "query_p95_ms": stats["query_p95_ms"],
            "visible_mean_ms": stats["visible_mean_ms"],
            "setup_s": t0 - cell.t_start,
        },
    )


def _service_s(lines, sent, received):
    """Per query line, reply time less the wait behind the line before it:
    received - max(sent, the previous line's reply), in seconds."""
    out, prev = [], None
    for (kind, *_), s, r in zip(lines, sent, received):
        if r is None:
            break
        if kind == "query":
            out.append(r - max(s, prev or s))
        prev = r
    return out
