#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that cyclonus-tpu still starts on the chip.

Drives the three main paths once, through the entry points a user calls, at
the sizes users call real, and checks what comes out against the repo's own
references (the scalar oracle, the XLA tile loop, the single-device kernel):

  batch   evaluate_grid_counts at 100,000 pods x 10,000 policies x 2 port
          cases (2e10 cells) on the default route, on class_compress="0" and
          on backend="xla"; the pairs kernel against the scalar oracle; the
          full [Q, N, N] tables at 10,000 x 1,000 with their sums tied to the
          counts kernels; every Pallas variant the default shapes do not
          reach; `analyze --mode probe` and a slice of `generate --mock`
  mesh    on more than one device, in the same JAX process: the mesh counts
          entry at 100,000 x 10,000 and both grid schedules at 10,000 x
          1,000 against the single-device results, and the CLI slice under
          `--engine tpu-sharded`
  serve   `python -m cyclonus_tpu serve` over 100,000 pods and the 10,000
          policies, driven over its stdio wire: policy and pod deltas, two
          64-query batches, every verdict checked against the scalar oracle
          on a mirrored state; then a second start against the caches the
          first one left

ONE PROCESS FOR EACH CHIP.  This file run without arguments is the PARENT: it
never initialises a JAX backend.  It starts, one after the other, (1)
`chip_smoke.py batch` - ONE JAX process that runs every batch and mesh phase,
(2) the serve command, cold, (3) the serve command again, warm.  No two of
them are alive at the same time.

JAX_PLATFORMS=tpu is set for every child before anything imports JAX, so a
missing chip is JAX's own error and never a CPU run: without an accelerator
the script exits non-zero and prints no result.  Sizes are fixed below and all
data comes from SEED.  The last line of stdout is one JSON object with the
device as JAX reports it.  Seconds printed here are smoke timings of one run,
not measurements of any metric.

CHIP_SMOKE_REHEARSE=1 rehearses the same phases and assertions on the CPU at
tiny sizes (Pallas in interpret mode, a 4-device virtual mesh); its last line
says "rehearsal": true and names the cpu.  CHIP_SMOKE_OUT moves the scratch
directory (default: chiprun_out/chip_smoke next to this file).
"""

import contextlib
import io
import json
import os
import random
import select
import subprocess
import sys
import re
import time
import urllib.request
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260729
REHEARSE = os.environ.get("CHIP_SMOKE_REHEARSE") == "1"
PLATFORM = "cpu" if REHEARSE else "tpu"
OUT = os.environ.get("CHIP_SMOKE_OUT") or os.path.join(
    HERE, "chiprun_out", "chip_smoke"
)

# Fixed sizes: BASELINE config 5 cut to one chip (counts), config 3 (tables).
REAL = {
    "pods": 100_000, "policies": 10_000,
    "table_pods": 10_000, "table_policies": 1_000,
    "serve_ns": 400,
    "tier_pods": 1_024, "tier_policies": 32,
    # CYCLONUS_COMPACT=0 over 128 namespaces leaves T > 1024: multi-chunk
    "dense_pods": 4_096, "dense_policies": 4_000, "dense_ns": 128,
    "slab_pods": 8_192, "slab_policies": 800,
    # pods x atoms >= 2^24 routes the LPM stage to the device by itself
    "cidr_pods": 20_000, "cidr_distinct": 1_024,
    "generate_cases": 6,
}
TINY = {
    "pods": 600, "policies": 60,
    "table_pods": 200, "table_policies": 30,
    "serve_ns": 4,
    "tier_pods": 96, "tier_policies": 8,
    "dense_pods": 640, "dense_policies": 2_400, "dense_ns": 64,
    "slab_pods": 64, "slab_policies": 16,
    "cidr_pods": 256, "cidr_distinct": 64,
    "generate_cases": 2,
}
SIZES = TINY if REHEARSE else REAL
ORACLE_PAIRS, ORACLE_CELLS, SERVE_QUERIES, LABEL_DELTAS = 64, 256, 64, 8

CHILD_ENV = {
    "JAX_PLATFORMS": PLATFORM,
    # every phase prints the PathSpec names it actually recorded
    "CYCLONUS_PLANHARNESS": "1",
}
if REHEARSE:
    CHILD_ENV["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    # interpret-mode timings mean nothing, but the search machinery runs
    CHILD_ENV["CYCLONUS_AUTOTUNE"] = "1"
    # what is rehearsed is mechanics: skip XLA's CPU optimisation passes
    CHILD_ENV["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"


def say(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def cache_census() -> dict:
    """Entries under the compile-cache root, by cache."""
    from cyclonus_tpu.engine import aot_cache, autotune, cache_root

    root = cache_root()
    jax_n = 0
    if os.path.isdir(root):
        jax_n = sum(
            1 for f in os.listdir(root)
            if os.path.isfile(os.path.join(root, f)) and f != "autotune.json"
        )
    aot_dir, tune = aot_cache.cache_dir(), autotune.cache_path()
    aot_n = (
        sum(1 for f in os.listdir(aot_dir) if f.endswith(".aotx"))
        if aot_dir and os.path.isdir(aot_dir) else 0
    )
    tune_n = len(autotune._read_all(tune)) if tune else 0
    return {"root": root, "jax": jax_n, "aot": aot_n, "autotune": tune_n}


# ==========================================================================
# The batch child: ONE JAX process for every batch and mesh phase.
# ==========================================================================


@contextlib.contextmanager
def phase(name: str):
    """Print the phase, the PathSpec names it drained and its seconds; a
    phase that raises names itself and ends the run."""
    from cyclonus_tpu.engine import planspec

    planspec.drain()
    say(f"== {name}")
    t0 = time.perf_counter()
    try:
        yield
    except BaseException as e:
        say(f"   routes: {planspec.drain()}")
        say(f"SMOKE_FAIL phase={name!r}: {type(e).__name__}: {e}")
        raise
    say(f"   routes: {planspec.drain()}")
    say(f"   ok ({time.perf_counter() - t0:.1f}s)")


def routes_of(fn):
    """(result, PathSpec names recorded while fn ran)."""
    from cyclonus_tpu.engine import planspec

    planspec.drain()
    out = fn()
    return out, planspec.drain()


def env(**kv):
    """Environment overrides for the lifetime of the engines built inside
    (the dtype-plan flags are read at construction and at trace time)."""
    return mock.patch.dict(os.environ, kv)


def device_peaks() -> list:
    """peak_bytes_in_use of every device (None where the backend has no
    memory stats)."""
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


def port_cases():
    from cyclonus_tpu.engine import PortCase

    return [
        PortCase(80, "serve-80-tcp", "TCP"),
        PortCase(81, "serve-81-udp", "UDP"),
    ]


def counts3(c: dict) -> tuple:
    return (c["ingress"], c["egress"], c["combined"])


def device_phase() -> dict:
    import importlib.metadata as md

    from cyclonus_tpu.engine import device_identity

    device = device_identity()
    check(
        device["platform"] == PLATFORM,
        f"JAX found platform {device['platform']!r} ({device['kind']}), "
        f"not {PLATFORM!r}",
    )
    versions = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            versions[dist] = md.version(dist)
        except md.PackageNotFoundError:
            versions[dist] = None
    say(
        "SMOKE_DEVICE " + json.dumps(
            {**device, **versions, "cache_dir": cache_census()["root"]}
        )
    )
    return device


def batch_counts_phase(state: dict) -> None:
    """Counts at the headline shape on the default route, the dense packed
    route (cold fused call, split call, steady call with the tile autotune)
    and the XLA tile loop: all equal."""
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies
    from cyclonus_tpu.synthetic import build_synthetic

    n, p = SIZES["pods"], SIZES["policies"]
    pods, namespaces, policies = build_synthetic(
        n, p, random.Random(SEED)
    )
    policy = build_network_policies(True, policies)
    cases = port_cases()
    # on the chip the default backend IS pallas; the rehearsal has to ask
    backend = "pallas" if REHEARSE else None
    default = TpuPolicyEngine(
        policy, pods, namespaces, class_compress="1" if REHEARSE else None
    )
    cc = default.class_compression_stats()
    say(f"   default engine: classes={cc['classes']} ratio={cc['ratio']}")
    got_default, r = routes_of(
        lambda: default.evaluate_grid_counts(cases, backend=backend)
    )
    say(f"   default route {r}: {got_default}")
    check(r == ["counts.classes"], f"default route recorded {r}")

    dense = TpuPolicyEngine(policy, pods, namespaces, class_compress="0")
    calls = []
    # the engine reaches its steady dispatch in four calls: the cold one
    # (the fused program where the static half of the precompute passes
    # the pins' byte ceiling, else the resident pair), the split pair
    # that pins the precompute, the first steady call (which runs the
    # tile autotune instead), then the tuned kernel
    for label in ("cold fused", "split", "autotune", "steady"):
        got, r = routes_of(
            lambda: dense.evaluate_grid_counts(cases, backend=backend)
        )
        say(f"   dense {label} {r}: {got}")
        check("counts.xla" not in r, f"{label}: counts.xla where pallas asked")
        check(r[:1] == ["counts.pallas"], f"{label} recorded {r}")
        calls.append((got, r))
    check(calls[0][1] == ["counts.pallas"], f"cold call recorded {calls[0][1]}")
    check(
        calls[3][1][1:] in (
            ["counts.steady.packed_tuned"], ["counts.steady.default"]
        ),
        f"steady call recorded {calls[3][1]}",
    )
    tune = dense.pack_stats()["autotune"]
    say(f"   autotune: {json.dumps(tune)}")
    for cand in (tune or {}).get("candidates", []):
        check(
            "status" not in cand,
            f"autotune rejected tile ({cand.get('bs')}, {cand.get('bd')}): "
            f"{cand.get('status')}: {cand.get('error')}",
        )
    got_xla, r = routes_of(
        lambda: dense.evaluate_grid_counts(cases, backend="xla")
    )
    say(f"   dense xla {r}: {got_xla}")
    check(r == ["counts.xla"], f"xla call recorded {r}")
    for label, got in [("default", got_default), ("xla", got_xla)] + [
        (f"dense call {i}", c[0]) for i, c in enumerate(calls)
    ]:
        check(got == got_xla, f"counts differ: {label} {got} != xla {got_xla}")
    check(got_xla["cells"] == len(cases) * n * n, "cell count")
    state.update(
        pods=pods, namespaces=namespaces, policy=policy, cases=cases,
        default=default, dense=dense, counts=got_xla,
    )


def packed_tiles_phase(state: dict) -> None:
    """Every PACKED_TILE_CANDIDATES entry compiled explicitly at the
    headline shape, from the precompute the steady state pinned."""
    import numpy as np

    from cyclonus_tpu.engine.pallas_kernel import (
        PACKED_TILE_CANDIDATES,
        sum_partials,
    )

    dense, cases, want = state["dense"], state["cases"], state["counts"]
    n = len(state["pods"])
    check(dense._pre_cache is not None, "dense engine is not at steady state")
    for bs, bd in PACKED_TILE_CANDIDATES:
        t0 = time.perf_counter()
        partials = dense._counts_from_pre_packed_jit(
            dense._pre_cache[1], np.int32(n), bs=bs, bd=bd
        )
        got = sum_partials(np.asarray(partials), len(cases), n)
        say(f"   tile ({bs}, {bd}): {time.perf_counter() - t0:.1f}s")
        check(got == want, f"tile ({bs}, {bd}) counts {got} != {want}")


def oracle_phase(state: dict) -> None:
    """Seeded pairs through evaluate_pairs against the scalar oracle (the
    per-pair device->host syncs stay outside anything timed)."""
    from cyclonus_tpu.analysis.oracle import spot_check_pairs

    for name in ("default", "dense"):
        spot_check_pairs(
            state[name], state["policy"], state["pods"], state["namespaces"],
            state["cases"], ORACLE_PAIRS, random.Random(SEED + 1),
        )
    say(f"   {ORACLE_PAIRS} pairs x {len(state['cases'])} cases x 2 engines")


def tables_phase(state: dict) -> None:
    """The full [Q, N, N] grid: sampled cells against the scalar oracle,
    and its sums against the counts kernels on the same engines."""
    from cyclonus_tpu.analysis.oracle import spot_check
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies
    from cyclonus_tpu.synthetic import build_synthetic

    n, p = SIZES["table_pods"], SIZES["table_policies"]
    pods, namespaces, policies = build_synthetic(
        n, p, random.Random(SEED + 2)
    )
    policy = build_network_policies(True, policies)
    cases = port_cases()
    backend = "pallas" if REHEARSE else None
    grids = {}
    for name, cc in (("default", "1" if REHEARSE else None), ("dense", "0")):
        engine = TpuPolicyEngine(policy, pods, namespaces, class_compress=cc)
        grid, r = routes_of(lambda: engine.evaluate_grid(cases))
        spot_check(
            policy, pods, namespaces, cases, grid, ORACLE_CELLS,
            random.Random(SEED + 3),
        )
        sums = grid.allow_counts()
        counts, rc = routes_of(
            lambda: engine.evaluate_grid_counts(cases, backend=backend)
        )
        say(f"   {name} {r}: sums {sums}; {rc}: counts {counts3(counts)}")
        check(sums == counts3(counts), f"{name}: grid sums != counts")
        grids[name] = (engine, grid)
    check(
        grids["default"][1].allow_counts() == grids["dense"][1].allow_counts(),
        "default and dense grids differ",
    )
    state.update(table_cases=cases, table_engines=grids)


def tier_phase() -> None:
    """The fused tier epilogue of the packed kernel (dense counts and the
    class-weighted variant) against the XLA tile loop and the tiered
    scalar oracle."""
    import numpy as np

    from cyclonus_tpu.analysis.oracle import traffic_for_cell
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies
    from cyclonus_tpu.matcher.tiered import TieredPolicy
    from cyclonus_tpu.synthetic import build_synthetic, tiers_lattice

    n, p = SIZES["tier_pods"], SIZES["tier_policies"]
    pods, namespaces, policies = build_synthetic(
        n, p, random.Random(777)
    )
    policy = build_network_policies(True, policies)
    tiers = tiers_lattice()
    cases = port_cases()
    oracle = TieredPolicy(policy, tiers)
    rng = random.Random(SEED + 4)
    for cc in ("0", "1"):
        engine = TpuPolicyEngine(
            policy, pods, namespaces, tiers=tiers, class_compress=cc
        )
        got, r = routes_of(
            lambda: engine.evaluate_grid_counts(cases, backend="pallas")
        )
        want = engine.evaluate_grid_counts(cases, backend="xla")
        say(f"   class_compress={cc} {r}: {got}")
        check(got == want, f"tiered pallas {got} != xla {want}")
        grid = engine.evaluate_grid(cases)
        check(grid.allow_counts() == counts3(got), "tiered grid sums != counts")
        combined = np.asarray(grid.combined)
        for _ in range(16):
            qi, si, di = rng.randrange(2), rng.randrange(n), rng.randrange(n)
            t = traffic_for_cell(pods, namespaces, cases[qi], si, di)
            want_cell = oracle.is_traffic_allowed(t)[2]
            check(
                bool(combined[qi, si, di]) == want_cell,
                f"tiered oracle differs at q={qi} s={si} d={di}",
            )


def dense_plan_phase() -> None:
    """The CYCLONUS_PACK=0 kernels: the multi-chunk general kernel in int8
    and bf16, and the slab kernel."""
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies
    from cyclonus_tpu.synthetic import build_synthetic
    from cyclonus_tpu.telemetry import instruments as ti

    cases = port_cases()

    def run(label, n, p, n_ns, **engine_kw):
        pods, namespaces, policies = build_synthetic(
            n, p, random.Random(SEED + 5), n_ns=n_ns
        )
        policy = build_network_policies(True, policies)
        engine = TpuPolicyEngine(
            policy, pods, namespaces, class_compress="0", **engine_kw
        )
        got, r = routes_of(
            lambda: engine.evaluate_grid_counts(cases, backend="pallas")
        )
        want = engine.evaluate_grid_counts(cases, backend="xla")
        say(f"   {label} {r}: {got}")
        check(got == want, f"{label}: pallas {got} != xla {want}")
        return engine

    n, p, n_ns = (SIZES[k] for k in ("dense_pods", "dense_policies", "dense_ns"))
    # which kernel a program holds shows only while it is traced, and an
    # executable adopted from a warm AOT cache is never traced: these
    # engines build theirs (JAX's own compile cache still serves them)
    traced = {"CYCLONUS_AOT_CACHE": "0"}
    for dtype, extra in (("int8", 0), ("bf16", 8)):  # +8: a fresh pod bucket
        chunked0 = ti.KERNEL_TRACES.value(kernel="counts_chunked")
        with env(CYCLONUS_PACK="0", CYCLONUS_PALLAS_DTYPE=dtype,
                 CYCLONUS_PALLAS_SLAB="0", **traced):
            engine = run(f"dense {dtype}", n + extra, p, n_ns, compact=False)
        t = engine._tensors
        depth = [int(t[d]["target_ns"].shape[0]) for d in ("egress", "ingress")]
        check(
            ti.KERNEL_TRACES.value(kernel="counts_chunked") > chunked0,
            f"dense {dtype}: target depth {depth} did not reach the "
            "multi-chunk kernel",
        )
    with env(CYCLONUS_PACK="0", CYCLONUS_PALLAS_DTYPE="int8",
             CYCLONUS_PALLAS_SLAB="1", **traced):
        slab0 = ti.KERNEL_TRACES.value(kernel="counts_slab")
        engine = run(
            "slab int8", SIZES["slab_pods"], SIZES["slab_policies"], None
        )
        if not REHEARSE:  # the slab tile constants need >= 2 x 2048 pods
            check(
                isinstance(engine._slab_plan_state, dict)
                and ti.KERNEL_TRACES.value(kernel="counts_slab") > slab0,
                "the forced slab plan did not run the slab kernel",
            )


def cidr_phase() -> None:
    """The TSS/LPM CIDR stage on the device over an ipBlock-heavy set:
    counts equal to the dense per-spec path, pairs equal to the oracle."""
    from cyclonus_tpu.analysis.oracle import spot_check_pairs
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.matcher import build_network_policies
    from cyclonus_tpu.synthetic import cidr_cluster

    pods, namespaces, netpols, rng = cidr_cluster(
        SIZES["cidr_pods"], SIZES["cidr_distinct"], 64
    )
    policy = build_network_policies(True, netpols)
    cases = port_cases()
    # the rehearsal is far below the work floor that routes it by itself
    with env(**({"CYCLONUS_CIDR_TSS_DEVICE": "1"} if REHEARSE else {})):
        engine = TpuPolicyEngine(
            policy, pods, namespaces, class_compress="1", cidr_tss="1"
        )
    stats = engine.cidr_stats()
    say(f"   {json.dumps(stats)}")
    check(stats["active"] and stats["device"], "LPM stage did not run on device")
    got, r = routes_of(lambda: engine.evaluate_grid_counts(cases))
    dense = TpuPolicyEngine(
        policy, pods, namespaces, class_compress="0", cidr_tss="0"
    )
    want = dense.evaluate_grid_counts(cases, backend="xla")
    say(f"   tss {r}: {got}")
    check(got == want, f"TSS counts {got} != dense {want}")
    spot_check_pairs(engine, policy, pods, namespaces, cases, 16, rng)


def cli_phase(engine_flag: str) -> None:
    """The CLI in this process (it holds the chip): every verdict must come
    from the tpu engine, none from a host engine."""
    import cyclonus_tpu.cli
    from cyclonus_tpu.telemetry import instruments as ti

    commands = [
        ["analyze", "--mode", "probe", "--engine", engine_flag,
         "--policy-path", os.path.join(
             HERE, "examples", "networkpolicies", "simple-example"),
         "--probe-path", os.path.join(HERE, "examples", "probe.json")],
        ["generate", "--mock", "--perfect-cni", "--engine", engine_flag,
         "--retries", "0", "--max-cases", str(SIZES["generate_cases"])],
    ]
    for argv in commands:
        before = ti.VERDICTS.value(engine="tpu")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cyclonus_tpu.cli.main(argv)
        made = ti.VERDICTS.value(engine="tpu") - before
        say(f"   {' '.join(argv[:3])} --engine {engine_flag}: rc={rc}, "
            f"{int(made)} tpu verdicts")
        if rc != 0:
            say(buf.getvalue()[-2000:])
        check(rc == 0, f"{argv[0]} exited {rc} (differing cells)")
        check(made > 0, f"{argv[0]} produced no tpu-engine verdict")
        check(
            ti.VERDICTS.value(engine="native") == 0,
            f"{argv[0]} answered from the native engine",
        )
    check(
        "cyclonus_tpu.native.bridge" not in sys.modules,
        "the smoke path loaded the native library",
    )


def mesh_phase(state: dict) -> None:
    """All devices of the default backend: mesh counts and grids equal the
    single-device results, and the work is spread over every device."""
    import jax
    import numpy as np

    devices = jax.devices()
    cases, want = state["cases"], state["counts"]
    for label, fn in (
        ("sharded default", lambda: state["default"].evaluate_grid_counts_sharded(cases)),
        ("sharded dense", lambda: state["dense"].evaluate_grid_counts_sharded(cases)),
    ):
        got, r = routes_of(fn)
        say(f"   counts {label} {r}: {got}")
        check(got == want, f"{label} counts {got} != single-device {want}")
    tcases = state["table_cases"]
    for name, (engine, ref) in state["table_engines"].items():
        ref_np = [np.asarray(getattr(ref, k)) for k in ("ingress", "egress", "combined")]
        for schedule in ("ring", "allgather"):
            grid, r = routes_of(
                lambda: engine.evaluate_grid_sharded(tcases, schedule=schedule)
            )
            on = {s.device for s in grid.combined_dev.addressable_shards}
            say(f"   grid {name} {schedule} {r}: shards on {len(on)} devices")
            check(
                len(on) == len(devices),
                f"{name} {schedule}: output shards on {len(on)} of "
                f"{len(devices)} devices",
            )
            for k, want_np in zip(("ingress", "egress", "combined"), ref_np):
                check(
                    np.array_equal(np.asarray(getattr(grid, k)), want_np),
                    f"{name} {schedule}: {k} differs from single-device",
                )
    now = device_peaks()
    say(f"   peak bytes per device: start {state['peaks0']} now {now}")
    if None in now:
        say("   (this backend reports no memory stats: spread not asserted)")
    else:
        for d, a, b in zip(devices, state["peaks0"], now):
            check(b > (a or 0), f"{d}: peak memory did not rise ({a} -> {b})")
    cli_phase("tpu-sharded")


def batch_main() -> int:
    sys.path.insert(0, HERE)
    with phase("device"):
        device = device_phase()
    state = {"peaks0": device_peaks()}
    census0 = cache_census()
    with phase(f"batch counts {SIZES['pods']} x {SIZES['policies']}"):
        batch_counts_phase(state)
    with phase("packed tile candidates"):
        packed_tiles_phase(state)
    with phase("oracle pairs"):
        oracle_phase(state)
    with phase(f"tables {SIZES['table_pods']} x {SIZES['table_policies']}"):
        tables_phase(state)
    with phase("variant: tiered packed epilogue"):
        tier_phase()
    with phase("variant: dense int8/bf16 multi-chunk + slab (CYCLONUS_PACK=0)"):
        dense_plan_phase()
    with phase("variant: CIDR TSS on device"):
        cidr_phase()
    with phase("cli --engine tpu"):
        cli_phase("tpu")
    if device["count"] > 1:
        with phase(f"mesh over {device['count']} devices"):
            mesh_phase(state)
    else:
        say("== mesh\n   skipped: 1 device")
    say(f"   compile cache: before {census0} after {cache_census()}")
    say("SMOKE_BATCH_OK")
    return 0


# ==========================================================================
# The parent: never initialises a JAX backend; drives children in turn.
# ==========================================================================


def run_batch_child() -> dict:
    """Run `chip_smoke.py batch`, relaying its output; its device line."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "batch"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        bufsize=1, cwd=HERE,
    )
    device, ok = None, False
    try:
        with open(os.path.join(OUT, "batch.log"), "w") as log:
            for line in proc.stdout:
                log.write(line)
                if line.startswith("SMOKE_DEVICE "):
                    device = json.loads(line[len("SMOKE_DEVICE "):])
                ok = ok or line.startswith("SMOKE_BATCH_OK")
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0 or not ok or device is None:
        tail = open(os.path.join(OUT, "batch.log")).read()[-3000:]
        raise SmokeFailure(f"batch child exited {rc}; log tail:\n{tail}")
    return device


class ServeChild:
    """One `python -m cyclonus_tpu serve` on the JSON-lines wire."""

    def __init__(self, tag: str, policies_dir: str):
        self.stderr_path = os.path.join(OUT, f"serve-{tag}.stderr")
        self._stderr = open(self.stderr_path, "w")
        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cyclonus_tpu", "serve",
             "--synthetic-pods", str(SIZES["pods"]),
             "--synthetic-namespaces", str(SIZES["serve_ns"]),
             "--seed", "7", "--policies", policies_dir,
             "--metrics-port", "0"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._stderr, text=True, bufsize=1, cwd=HERE,
        )

    def stderr(self) -> str:
        self._stderr.flush()
        with open(self.stderr_path) as f:
            return f.read()

    def round_trip(self, line: str, timeout_s: float = 600.0) -> dict:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        ready, _, _ = select.select([self.proc.stdout], [], [], timeout_s)
        reply = self.proc.stdout.readline() if ready else ""
        if not reply:
            raise SmokeFailure(
                f"serve gave no reply within {timeout_s:g}s "
                f"(rc={self.proc.poll()}); stderr tail:\n{self.stderr()[-2000:]}"
            )
        out = json.loads(reply)
        check("Error" not in out, f"serve answered an error: {out}")
        return out

    def state(self) -> dict:
        port = self.stderr().split("(port ", 1)[1].split(")", 1)[0]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/state", timeout=60
        ) as r:
            return json.load(r)

    def banner(self, prefix: str) -> str:
        lines = [l for l in self.stderr().splitlines() if l.startswith(prefix)]
        check(lines, f"serve stderr has no {prefix!r} line")
        return lines[-1]

    def close(self) -> int:
        """EOF is the clean shutdown; the child never outlives this."""
        try:
            if self.proc.poll() is None:
                self.proc.stdin.close()
                self.proc.wait(timeout=120)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()
            self._stderr.close()
        return self.proc.returncode


def prewarm_facts(child: ServeChild) -> dict:
    """{'seconds', 'adopted', 'compiles'} from the child's prewarm banner."""
    line = child.banner("serve: prewarmed ")
    check("prewarm failed" not in child.stderr(), "serve reported a prewarm error")
    m = re.search(r"in ([\d.]+)s \(aot adopted=(\d+) compiles=(\d+)\)", line)
    check(m, f"unreadable prewarm banner: {line!r}")
    return {
        "seconds": float(m[1]), "adopted": int(m[2]), "compiles": int(m[3])
    }


def serve_phase(policies) -> dict:
    """Cold start, the delta and query script with every verdict checked
    against the scalar oracle on a mirrored state, then a warm start."""
    from cyclonus_tpu.analysis.oracle import oracle_verdicts, traffic_for_cell
    from cyclonus_tpu.engine.api import PortCase
    from cyclonus_tpu.kube.yaml_io import parse_policy_dict, policies_to_yaml
    from cyclonus_tpu.matcher.builder import build_network_policies
    from cyclonus_tpu.synthetic import synthetic_cluster
    from cyclonus_tpu.worker.model import Batch, Delta, FlowQuery

    pol_dir = os.path.join(OUT, "policies")
    os.makedirs(pol_dir, exist_ok=True)
    with open(os.path.join(pol_dir, "policies.yaml"), "w") as f:
        f.write(policies_to_yaml(policies))

    def batch(deltas=(), queries=()) -> str:
        return Batch(namespace="", pod="", container="",
                     deltas=list(deltas), queries=list(queries)).to_json()

    pods, namespaces = synthetic_cluster(SIZES["pods"], SIZES["serve_ns"], 7)
    state = {f"{p[0]}/{p[1]}": p for p in pods}
    mirror = list(policies)
    rng = random.Random(SEED + 6)
    upsert = {
        "apiVersion": "networking.k8s.io/v1",
        "kind": "NetworkPolicy",
        "metadata": {"name": "smoke-allow-app1", "namespace": "ns0"},
        "spec": {
            "podSelector": {"matchLabels": {"app": "app0"}},
            "policyTypes": ["Ingress"],
            "ingress": [{
                "from": [{"podSelector": {"matchLabels": {"app": "app1"}}}],
                "ports": [{"protocol": "TCP", "port": 80}],
            }],
        },
    }
    gone = mirror[7]

    say("== serve (cold start)")
    cold = ServeChild("cold", pol_dir)
    try:
        reply = cold.round_trip(batch(deltas=[Delta(
            kind="policy_upsert", namespace="ns0", name="smoke-allow-app1",
            policy=upsert)]))
        start_s = time.perf_counter() - cold.t0
        say(f"   {cold.banner('serve: engine ready')}")
        check(f"engine ready on {PLATFORM}" in cold.stderr(),
              f"serve did not start on {PLATFORM}")
        cold_facts = prewarm_facts(cold)
        say(f"   first reply after {start_s:.1f}s; prewarm {cold_facts}")
        say(f"   policy_upsert: {reply}")
        check(reply["Applied"] == 1, f"policy_upsert not applied: {reply}")
        mirror.append(parse_policy_dict(upsert))
        reply = cold.round_trip(batch(deltas=[Delta(
            kind="policy_delete", namespace=gone.namespace, name=gone.name)]))
        say(f"   policy_delete {gone.namespace}/{gone.name}: {reply}")
        check(reply["Applied"] == 1, f"policy_delete not applied: {reply}")
        mirror.remove(gone)

        modes = []
        for key in rng.sample(sorted(state), LABEL_DELTAS):
            ns, name, labels, ip = state[key]
            labels = dict(labels, tier=f"tier{(int(labels['tier'][4:]) + 1) % 5}")
            reply = cold.round_trip(batch(deltas=[Delta(
                kind="pod_labels", namespace=ns, name=name, labels=labels)]))
            modes.append(reply.get("Mode"))
            check(
                reply["Applied"] == 1 and reply["Mode"] == "incremental",
                f"pod_labels on {key} did not take the incremental path: {reply}",
            )
            state[key] = (ns, name, labels, ip)
        say(f"   {LABEL_DELTAS} pod_labels deltas: {modes}")

        leaver = rng.choice(sorted(state))
        lns, lname, llabels, lip = state[leaver]
        reply = cold.round_trip(batch(deltas=[
            Delta(kind="pod_remove", namespace=lns, name=lname),
            Delta(kind="pod_add", namespace=lns, name="smoke-newcomer",
                  labels=dict(llabels), ip=lip),
        ]))
        say(f"   pod_remove + pod_add: {reply}")
        check(reply["Applied"] == 2, f"pod_remove + pod_add: {reply}")
        del state[leaver]
        state[f"{lns}/smoke-newcomer"] = (lns, "smoke-newcomer", llabels, lip)

        policy = build_network_policies(True, mirror)
        plist = list(state.values())
        idx = {f"{p[0]}/{p[1]}": i for i, p in enumerate(plist)}
        keys = sorted(state)
        ports = [(80, "serve-80-tcp", "TCP"), (81, "serve-81-udp", "UDP")]
        checked = 0
        for _ in range(2):
            queries = [
                FlowQuery(src=rng.choice(keys), dst=rng.choice(keys),
                          port=ports[i % 2][0], port_name=ports[i % 2][1],
                          protocol=ports[i % 2][2])
                for i in range(SERVE_QUERIES)
            ]
            verdicts = cold.round_trip(batch(queries=queries))["Verdicts"]
            check(len(verdicts) == len(queries), "verdict count")
            for q, v in zip(queries, verdicts):
                check(not v.get("Error"), f"verdict error: {v}")
                want = oracle_verdicts(policy, traffic_for_cell(
                    plist, namespaces, PortCase(q.port, q.port_name, q.protocol),
                    idx[q.src], idx[q.dst]))
                got = (v["Ingress"], v["Egress"], v["Combined"])
                check(got == want,
                      f"{q.src}->{q.dst}:{q.port}: serve {got} oracle {want}")
                checked += 1
        st = cold.state()
        live = st["query_latency"]["count"]
        say(f"   {checked} verdicts equal the oracle; state: ready={st['ready']} "
            f"serve.query.live={live} "
            f"serve.query.degraded={st['degraded_queries']} epoch={st['epoch']}")
        check(st["ready"] is True, "serve state is not ready")
        check(st["degraded_queries"] == 0, "serve answered degraded")
        check(live == checked, f"{live} of {checked} queries took the live route")
    finally:
        rc = cold.close()
    check(rc == 0, f"serve child exited {rc}")

    say("== serve (warm start: the caches the cold start left)")
    warm = ServeChild("warm", pol_dir)
    try:
        q = FlowQuery(src=pods[0][0] + "/" + pods[0][1],
                      dst=pods[1][0] + "/" + pods[1][1],
                      port=80, port_name="serve-80-tcp", protocol="TCP")
        warm.round_trip(batch(queries=[q]))
        start_s = time.perf_counter() - warm.t0
        warm_facts = prewarm_facts(warm)
        say(f"   first reply after {start_s:.1f}s; prewarm {warm_facts}")
    finally:
        rc = warm.close()
    check(rc == 0, f"warm serve child exited {rc}")
    # on a machine that came with this repository's caches the first
    # start is a warm one too: then neither compiles anything
    check(
        warm_facts["compiles"] < max(cold_facts["compiles"], 1),
        f"the warm start compiled as much as the cold one: cold {cold_facts} "
        f"warm {warm_facts}",
    )
    return {"cold": cold_facts, "warm": warm_facts}


def parent_main() -> int:
    sys.path.insert(0, HERE)
    os.makedirs(OUT, exist_ok=True)
    t0 = time.perf_counter()
    census0 = cache_census()
    device = run_batch_child()
    check(device["platform"] == PLATFORM, f"batch child ran on {device}")

    from cyclonus_tpu.synthetic import build_synthetic

    _, _, policies = build_synthetic(
        SIZES["pods"], SIZES["policies"], random.Random(SEED)
    )
    starts = serve_phase(policies)
    census = cache_census()
    say(f"== compile caches under {census['root']}")
    say(f"   entries before {census0} after {census}")
    say(f"   serve prewarm (one smoke run): cold {starts['cold']} "
        f"warm {starts['warm']}")
    check(census["jax"] > census0["jax"] or census0["jax"] > 0,
          "no JAX compile-cache entry under the cache root")
    check(census["aot"] > 0, "no AOT executable under the cache root")

    from jax._src import xla_bridge

    check(not xla_bridge.backends_are_initialized(),
          "the parent initialised a JAX backend")
    say(f"   all phases ok in {time.perf_counter() - t0:.0f}s")
    result = {"ok": True, "device": {
        k: device[k] for k in ("platform", "kind", "count")}}
    if REHEARSE:
        result["rehearsal"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    os.environ.update(CHILD_ENV)  # before anything can import JAX
    if sys.argv[1:] == ["batch"]:
        sys.exit(batch_main())
    try:
        sys.exit(parent_main())
    except SmokeFailure as e:
        say(f"chip_smoke: FAILED: {e}")
        sys.exit(1)
