"""Traffic kind `sweep_mesh`: the `sweep` kind's one client and closed loop on
the program's MESH entry - what one `probe --engine tpu-sharded` process does
over a cluster whose tables no single chip holds.

A request is `engine.evaluate_grid_sharded(case set)` on the default mesh (all
the chips JAX reports) and the default schedule, then the three tables on the
host.  Everything else is `kinds/sweep.py`, which this kind runs: the
configuration gives the sizes (with `chips`), the generator's parameters and
the entry's name; the traffic file the `case_sets`; `sweep_cells_per_s` and
`correct` are computed as there.

The entry lives here because `program.ENTRIES` is a file of the accepted
benchmark: it is put beside the others when this kind is imported, under the
name the configuration gives, so that `sweep.run` and the readers that look a
cell's entry up (`readback.fetch_ms`) find it.  Its fetch is the tables
entry's own.

A mesh result that is not in the word form (`kernel.cell_words`, uint32) is
refused after the first evaluation and before any fetch: a program from
before PR 28 hands back boolean `[Q, N, N]` tables, 1.6 GB a port case each at
this size, whose placement the compiler chose and whose copy to the host
takes a quarter of a minute a request.  Such a run ends at once with exit
code 4 (on four v5e chips that program runs out of device memory before it
gets that far, and exits 1: my chip run, PR 28).

In a rehearsal the CPU is asked for as many devices as the cell has chips,
before anything imports JAX (in a process where JAX is already up, the mesh is
what devices there are).
"""

import os
import sys

from benchmarks import harness, program
from benchmarks.kinds import sweep

ENTRY = "evaluate_grid_sharded"
DEVICES_FLAG = "--xla_force_host_platform_device_count"


class MeshTablesEntry(program.TablesEntry):
    """evaluate_grid_sharded: dispatch returns at once; the tables entry's
    fetch waits and copies, shard by shard."""

    @staticmethod
    def evaluate(engine, cases):
        out = engine.evaluate_grid_sharded(cases)
        if str(out.ingress_dev.dtype) != "uint32":
            harness.say(
                f"benchmark: the mesh entry handed back {out.ingress_dev.dtype} "
                "tables, not uint32 words (a program from before PR 28): refused"
            )
            raise SystemExit(4)
        return out


program.ENTRIES.setdefault(ENTRY, MeshTablesEntry)


def rehearsal_devices(chips: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "jax" not in sys.modules and DEVICES_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {DEVICES_FLAG}={chips}".strip()


def run(cell):
    if cell.rehearse:
        rehearsal_devices(cell.sizes["chips"])
    return sweep.run(cell)
