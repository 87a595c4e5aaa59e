"""Traffic kind `sweep_mesh_counts_generated`: the `sweep_generated` kind's
closed loop on the program's MESH COUNTS entry - what a security or platform
team's audit does over a cluster whose dense precompute no single chip holds:
allow counts of the whole grid a port pair, never the grid.

A request is `engine.evaluate_grid_counts_sharded(case set)` at the program's
defaults (the default mesh: all the chips JAX reports; the route, the kernel
and class compression as the program decides them), four integers back inside
the call.  Everything else is `kinds/sweep_generated.py`, which this kind
runs: the configuration gives the sizes (with `chips`), the generator's module
and the entry's name; the traffic file the `case_sets`; `sweep_cells_per_s`
and `correct` (`count_requests_wrong` against `reference.GridReference`) are
computed as there.

The entry lives here because `program.ENTRIES` is a file of the accepted
benchmark: it is put beside the others when this kind is imported, under the
name the configuration gives.  Its fetch is the counts entry's own.

One refusal of its own, before anything is built: a program whose mesh counts
entry is not the HELD pair (every commit before PR 38).  There the default
entry replicates the whole precompute on every chip and builds it again on
every request, from a program traced again on every request; at this cell's
size that is 10 GB of `peer_allow` a direction beside 6 GB of static on a
16 GB chip, and a run would end two minutes into its set-up, out of device
memory.  The program names the held pair in
`cyclonus_tpu.engine.tiled.MESH_COUNTS_HELD`, which is part of both programs'
persistent key; a program that does not name it, or names another form, ends
here at once with exit code 4.

In a rehearsal the CPU is asked for as many devices as the cell has chips,
before anything imports JAX (`sweep_mesh.rehearsal_devices`), and the
program's ceiling for a replicated precompute (`api._MESH_REPLICATED_MAX_BYTES`,
a constant: the program reads no option for it) is set to nothing, so that a
rehearsal's 660 pods walk the ring as the chip run's 100,000 do.
"""

from benchmarks import harness, program
from benchmarks.kinds import sweep_generated, sweep_mesh

ENTRY = "evaluate_grid_counts_sharded"
HELD = "mesh-counts=held"


class MeshCountsEntry(program.CountsEntry):
    """evaluate_grid_counts_sharded: one call, the integers come back inside
    it; every option at the program's default, in a rehearsal too."""

    @staticmethod
    def evaluate(engine, cases):
        return engine.evaluate_grid_counts_sharded(cases)


program.ENTRIES.setdefault(ENTRY, MeshCountsEntry)


def refuse_per_call_program() -> None:
    from cyclonus_tpu.engine import tiled

    found = getattr(tiled, "MESH_COUNTS_HELD", None)
    if found != HELD:
        harness.say(
            "benchmark: the program's mesh counts entry is not the held pair "
            f"(tiled.MESH_COUNTS_HELD is {found!r}, not {HELD!r}: a program "
            "from before PR 38, whose entry replicates the precompute on every "
            "chip and rebuilds it a request, past a chip's memory at this "
            "size): refused"
        )
        raise SystemExit(4)


def run(cell):
    if cell.rehearse:
        sweep_mesh.rehearsal_devices(cell.sizes["chips"])
    refuse_per_call_program()
    if cell.rehearse:
        from cyclonus_tpu.engine import api

        api._MESH_REPLICATED_MAX_BYTES = 0
    return sweep_generated.run(cell)
