#!/usr/bin/env python3
"""Time the host's side of a sharded table's readback by where the shards
are laid, on the machine it runs on (PERF.md section 6, PR 37):

  fresh   np.empty a table, as `api._copy_shards` did until PR 37: every
          page of the destination is first touched by the lay threads, and
          unmapped when the tables are dropped (timed apart: `drop`)
  reused  three buffers allocated and touched once before the first timed
          request and written again by every request
  none    no destination: the shards' own host arrays (the runtime's
          transfer and un-tiling alone, the floor)

    chiprun --chips 4 -- python3 hack/readback_probe.py [--rounds 3]

builds `tables-40k-4k-x4` as its cell does (BENCH_REHEARSE=1: four CPU
devices, the rehearsal sizes), and for every reading makes a NEW evaluation
(JAX keeps a shard's host copy with the shard: a second fetch of one result
transfers nothing).  A request is Q = 1, the cell's first case: three tables.
Prints a line a reading (seconds: the three tables' copy, of which waiting
for the shards and laying them) and the medians; a chip run leaves the same
as JSON in chiprun_out/readback_probe.json.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import generators, program  # noqa: E402
from benchmarks.kinds import sweep_mesh  # noqa: E402

CONFIG = "benchmarks/configs/tables-40k-4k-x4.json"
TABLES = ("ingress", "egress", "combined")


def copy_table(dev, out):
    """_copy_shards' loop into `out` (None: nowhere); (wait_s, lay_s)."""
    from cyclonus_tpu.engine import api

    shards = api._shards_of(dev)
    for sh in shards:
        sh.data.copy_to_host_async()
    wait_s = lay_s = 0.0
    for sh in shards:
        t0 = time.perf_counter()
        piece = np.asarray(sh.data)
        t1 = time.perf_counter()
        if out is not None:
            api._lay(out, sh.index, piece)
        wait_s += t1 - t0
        lay_s += time.perf_counter() - t1
    return wait_s, lay_s


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    with open(os.path.join(REPO, CONFIG)) as f:
        cfg = json.load(f)
    sizes = cfg["rehearsal"] if program.rehearsing() else cfg["sizes"]
    if program.rehearsing():
        sweep_mesh.rehearsal_devices(sizes["chips"])
    from cyclonus_tpu.engine import api  # after the devices are asked for

    pods, namespaces, policies = generators.build_synthetic(
        sizes, cfg["generator"], args.seed
    )
    engine = program.new_engine(
        program.build_policy(program.parse_policies(policies)), pods, namespaces
    )
    cases = program.port_cases(generators.case_sets([[[80, "TCP"]]])[0])

    def evaluate():
        grid = engine.evaluate_grid_sharded(cases)
        grid.block_until_ready()
        return [getattr(grid, name + "_dev") for name in TABLES]

    devs = evaluate()  # warm: compiles or adopts the program
    shape, dtype = devs[0].shape, devs[0].dtype
    t0 = time.perf_counter()
    reused = [np.empty(shape, dtype) for _ in TABLES]
    for buf in reused:
        buf.fill(0)
    touch_s = time.perf_counter() - t0
    nbytes = sum(b.nbytes for b in reused)
    print(f"probe: {len(pods)} pods, table {shape} {dtype}, {nbytes} bytes a "
          f"request, {len(api._shards_of(devs[0]))} shards a table; touching "
          f"three new buffers once {touch_s:.3f} s", file=sys.stderr)
    del devs

    readings = {"fresh": [], "reused": [], "none": []}
    for r in range(args.rounds):
        for how in readings:
            devs = evaluate()
            t0 = time.perf_counter()
            if how == "fresh":
                outs = [np.empty(shape, dtype) for _ in TABLES]
            else:
                outs = reused if how == "reused" else [None] * len(TABLES)
            parts = [copy_table(d, o) for d, o in zip(devs, outs)]
            copy_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            del outs, devs
            drop_s = time.perf_counter() - t0
            one = {
                "copy_s": copy_s, "wait_s": sum(p[0] for p in parts),
                "lay_s": sum(p[1] for p in parts), "drop_s": drop_s,
            }
            readings[how].append(one)
            print(f"probe: round {r} {how:<7}" + "".join(
                f" {k} {v:.3f}" for k, v in one.items()), file=sys.stderr)
    medians = {
        how: {k: statistics.median(o[k] for o in ones) for k in ones[0]}
        for how, ones in readings.items()
    }
    import jax

    result = {
        "device": f"{jax.devices()[0].device_kind} x {len(jax.devices())}",
        "pods": len(pods), "bytes_a_request": nbytes, "touch_s": touch_s,
        "readings": readings, "medians": medians,
        "fresh_over_reused": medians["fresh"]["copy_s"] / medians["reused"]["copy_s"],
    }
    if not program.rehearsing():  # a CPU rehearsal leaves a chip run's file
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "readback_probe.json"), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({k: result[k] for k in (
        "device", "pods", "bytes_a_request", "medians", "fresh_over_reused")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
