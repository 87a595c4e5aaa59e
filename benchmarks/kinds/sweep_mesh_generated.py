"""Traffic kind `sweep_mesh_generated`: the `sweep_mesh` kind's closed loop on
the program's MESH entry over a cluster that the CONFIGURATION's own generator
makes - what one `probe --engine tpu-sharded` process does over a cluster
whose tables no single chip holds and whose rule set class compression
refuses.

It is two kinds that exist, joined: importing `kinds/sweep_mesh` puts its
`MeshTablesEntry` (evaluate_grid_sharded on the default mesh and schedule,
with its refusal of a result that is not uint32 words, exit code 4) into
`program.ENTRIES`, and its `rehearsal_devices` asks a rehearsal's CPU for as
many devices as the cell has chips; `kinds/sweep_generated.run` does the rest
(the generator the configuration names, the engine at the program's defaults,
the window, `sweep_cells_per_s` and `correct`).

One refusal of its own, before anything is built: a program whose dense mesh
epilogue exchanges ingress as BOOLEANS (every commit before PR 34).  At this
cell's size the TPU compiler takes nine minutes and 327 MB of code over that
one all_to_all (PERF.md, PR 34: 555 s for a described v5e:2x2), which a run
would sit out as set-up.  The program names its exchange in
`cyclonus_tpu.engine.sharded.DENSE_EXCHANGE`, which is part of the program's
persistent key; a program that does not name it, or names another form, ends
here at once with exit code 4.
"""

from benchmarks import harness
from benchmarks.kinds import sweep_generated, sweep_mesh

EXCHANGE = "xchg=words"


def refuse_boolean_exchange() -> None:
    from cyclonus_tpu.engine import sharded

    found = getattr(sharded, "DENSE_EXCHANGE", None)
    if found != EXCHANGE:
        harness.say(
            "benchmark: the program's dense mesh epilogue does not exchange "
            f"words (sharded.DENSE_EXCHANGE is {found!r}, not {EXCHANGE!r}: a "
            "program from before PR 34, whose exchange of booleans takes the "
            "TPU compiler nine minutes at this size): refused"
        )
        raise SystemExit(4)


def run(cell):
    if cell.rehearse:
        sweep_mesh.rehearsal_devices(cell.sizes["chips"])
    refuse_boolean_exchange()
    return sweep_generated.run(cell)
