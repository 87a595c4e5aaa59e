#!/usr/bin/env python3
"""The evidence for `chips: 4` of `cidr-100k-10k-x4` (ISSUE 38, step 1), and
the parent's ring beside the held pair on the chips.

    python3 hack/mesh_counts_evidence.py compile     # here or there: no chip needed
    chiprun --chips 4 -- python3 hack/mesh_counts_evidence.py run

`compile` builds the engine at the configuration's size (host work: the
generator, the matcher, the encoding) and COMPILES, for a described v5e:2x2
and without running anything, the programs a counts request of two port cases
could take, and prints each one's `memory_analysis()` a chip:

  one-chip   what `evaluate_grid_counts` runs on one chip at this size: the
             fused program (the static half alone is over the pins' ceiling),
             and its precompute alone (`counts.pre`)
  rows       `evaluate_grid_counts_sharded` as it was until PR 38: the
             precompute REPLICATED on every chip, the source rows split
  ring       `evaluate_grid_counts_ring` as it was: both pod axes sharded,
             everything recomputed and re-sent a request
  held       PR 38's pair on the ring route: `counts.mesh.static` (once per
             engine state) and `counts.mesh.cases` (a request)

A program the TPU compiler refuses (it does not fit 16 GB) prints the
compiler's message, which names the bytes it wanted.  `run` (four chips)
calls the per-call ring twice and the mesh counts entry four times, and
prints what each took and whether a request compiled.  BENCH_REHEARSE=1 runs
either at the rehearsal sizes on four CPU devices.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIG = "benchmarks/configs/cidr-100k-10k-x4.json"
PAIRS = (((80, "TCP"), (81, "UDP")), ((80, "UDP"), (81, "SCTP")),
         ((80, "SCTP"), (81, "TCP")))


def build_engine(rehearse: bool):
    from benchmarks import program
    from benchmarks.kinds import sweep_generated

    import importlib

    with open(os.path.join(REPO, CONFIG)) as f:
        cfg = json.load(f)
    sizes = cfg["rehearsal" if rehearse else "sizes"]
    gen = importlib.import_module("benchmarks." + cfg["generator"]["module"])
    pods, namespaces, policies = gen.build(sizes, cfg["generator"], 1)
    policy = program.build_policy(program.parse_policies(policies))
    return sweep_generated.new_engine(policy, pods, namespaces, rehearse), sizes


def cases_of(pair):
    from cyclonus_tpu.engine.api import PortCase

    return [PortCase(p, f"serve-{p}-{proto.lower()}", proto) for p, proto in pair]


def report(name, lower):
    t0 = time.perf_counter()
    try:
        m = lower().compile().memory_analysis()
    except Exception as e:  # the compiler's refusal is the reading
        print(f"{name}: REFUSED after {time.perf_counter() - t0:.0f} s: "
              f"{str(e)[:1500]}", flush=True)
        return
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: compiled in {time.perf_counter() - t0:.0f} s; a chip: "
          f"arguments {m.argument_size_in_bytes:,} + outputs "
          f"{m.output_size_in_bytes:,} + temporaries {m.temp_size_in_bytes:,} "
          f"- aliased {m.alias_size_in_bytes:,} = {total:,} bytes; code "
          f"{m.generated_code_size_in_bytes:,}", flush=True)


def compile_only(rehearse: bool):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from cyclonus_tpu.engine import pallas_kernel, tiled
    from cyclonus_tpu.engine.sharded import (
        _pad_pod_arrays, pod_sharded_in_specs, shard_map_no_check,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    # the program's Pallas calls ask the default backend, which is the CPU
    # here: the compile is for the chip
    pallas_kernel._should_interpret = lambda: False
    eng, sizes = build_engine(rehearse)
    n, q, n_dev = sizes["pods"], 2, 4
    t = eng._tensors
    print(f"pods {n} (axis {t['pod_ns_id'].shape[0]}), peer rows "
          f"{[int(t[d]['peer_target'].shape[0]) for d in ('ingress', 'egress')]}, "
          f"targets {[int(t[d]['target_ns'].shape[0]) for d in ('ingress', 'egress')]}, "
          f"class state {eng.pod_classes() is not None}; static half "
          f"{eng._static_pre_bytes():,} bytes on one chip", flush=True)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("x",))
    block = tiled._int32_safe_block(min(1024, max(n // n_dev, 1)), n, q)
    padded, n_padded = _pad_pod_arrays(t, n, n_dev * block)
    from cyclonus_tpu.engine import api

    print(f"route decision's bytes: replicated {eng._mesh_replicated_bytes(q, n_padded):,} "
          f"a chip against a ceiling of {api._MESH_REPLICATED_MAX_BYTES:,}", flush=True)

    def shaped(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    # one chip: the fused program of the dense counts route
    eng._ensure_packed()
    eng._pod_perm_dev = np.zeros((t["pod_ns_id"].shape[0],), np.int32)
    eng._build_counts_jits()
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                         sharding=one)
    qp, qn, qr = eng._port_case_arrays(cases_of(PAIRS[0]))
    report("one-chip (counts.fused)", lambda: eng._counts_packed_jit._jitted.lower(
        sds(eng._packed_buf), sds(eng._pod_perm_dev), sds(qp), sds(qn), sds(qr),
        sds(np.int32(n))))

    # and its precompute alone (counts.pre: what the fused program holds in
    # HBM before its Pallas kernel starts)
    report("one-chip, the precompute alone (counts.pre)", lambda: eng._pre_jit._jitted.lower(
        sds(eng._packed_buf), sds(eng._pod_perm_dev), sds(qp), sds(qn), sds(qr)))

    with_cases = dict(padded, q_port=qp, q_name=qn, q_proto=qr)
    pack, shard = eng._pack, n_padded // n_dev
    for name, specs, body in (
        ("rows (counts.sharded.pallas, per call)",
         jax.tree_util.tree_map(lambda _: P(), with_cases),
         lambda x: tiled._row_counts(tiled._precompute(x, pack), n, n_dev,
                                     n_padded, block, "pallas", pack)),
        ("ring (counts.ring, per call)", pod_sharded_in_specs(with_cases),
         lambda x: tiled._ring_counts(tiled._precompute(x, pack), n, n_dev,
                                      shard, block)),
    ):
        fn = jax.jit(shard_map_no_check(body, mesh=mesh, in_specs=(specs,),
                                        out_specs=P()))
        report(name, lambda: fn.lower(shaped(with_cases, specs)))

    static_fn, cases_fn = tiled.mesh_counts_programs(
        mesh, padded, block, "ring", "pallas", pack, eng._aot_plan())
    static_specs = tiled._mesh_static_specs(padded, pack, True)
    lowered = static_fn._jitted.lower(shaped(padded, pod_sharded_in_specs(padded)))
    report("held: counts.mesh.static (ring, once)", lambda: lowered)
    static = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        lowered.out_info, static_specs)
    rep = NamedSharding(mesh, P())
    report("held: counts.mesh.cases (ring, a request)", lambda: cases_fn._jitted.lower(
        static, jax.ShapeDtypeStruct((3, q), np.int32, sharding=rep),
        jax.ShapeDtypeStruct((), np.int32, sharding=rep)))


def run(rehearse: bool):
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    from cyclonus_tpu.engine import api
    from cyclonus_tpu.telemetry import instruments as ti

    if rehearse:  # 660 pods walk the ring as 100,000 do
        api._MESH_REPLICATED_MAX_BYTES = 0
    eng, sizes = build_engine(rehearse)
    import jax

    print(f"devices: {jax.devices()}", flush=True)

    def compiles():
        return sum(ti.JAX_COMPILES.value(cache=c) for c in ("miss", "hit", "uncached"))

    def timed(name, call, pair):
        before = compiles()
        t0 = time.perf_counter()
        try:
            got = call(cases_of(pair))
        except Exception as e:
            print(f"{name} {pair}: FAILED after {time.perf_counter() - t0:.1f} s: "
                  f"{str(e)[:800]}", flush=True)
            return None
        print(f"{name} {pair}: {time.perf_counter() - t0:.2f} s, backend compiles "
              f"{compiles() - before:.0f}, {got}", flush=True)
        return got

    want = [timed("held entry", eng.evaluate_grid_counts_sharded, p)
            for p in PAIRS + PAIRS[:1]]
    stats = [d.memory_stats() or {} for d in jax.devices()]
    print("peak bytes a chip after the held entry:",
          [s.get("peak_bytes_in_use") for s in stats], flush=True)
    eng._mesh_static = None  # the per-call ring needs the chips' memory
    got = [timed("per-call ring", eng.evaluate_grid_counts_ring, p)
           for p in PAIRS[:2]]
    print("agree:", got == want[:2], flush=True)


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "compile"
    rehearse = os.environ.get("BENCH_REHEARSE") == "1"
    {"compile": compile_only, "run": run}[mode](rehearse)
