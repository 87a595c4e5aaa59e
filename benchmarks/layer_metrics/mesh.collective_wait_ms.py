"""Device time a request spends WAITING in collectives: the `-done` halves of
asynchronous collectives and the collectives that have no `-start` half (a
synchronous all-to-all, all-gather, ...), summed on each chip and averaged
over the chips.  `mesh.collective_ms` counts the `-start` halves too, which
only issue a transfer; the difference is what hides behind other work (the
ring's hop behind its step's contraction, as engine/sharded.py claims).

An instruction is named after its opcode (`collective-permute-done.2`,
`all-gather.14`) or after the JAX primitive that made it (`all_to_all.3`, with
underscores: the dense epilogue's exchange, as the TPU compiler names it), so
a name is asked about with its underscores read as hyphens; an all-gather the
compiler made asynchronous is `async-collective-start.1` / `-done.1`.
`mesh_trace.is_collective` alone does not, so `mesh.collective_ms` leaves the
`all_to_all` out and this metric can read above it (PERF.md section 7).
Nothing (never 0) where the window holds no collective at all."""

from benchmarks import mesh_trace

# what the TPU compiler calls the all-gather schedule's gathers (my chip run, PR 34)
ASYNC = "async-collective"


def kind(name: str):
    """The collective an operation is (`all-to-all`, ...), or None."""
    name = name.replace("_", "-")
    for opcode in mesh_trace.COLLECTIVES + (ASYNC,):
        if name.startswith(opcode):
            return opcode
    return None


def waits(name: str) -> bool:
    """`collective-permute-done.2_...`, `all-to-all.1_...` and
    `all_to_all.3_...` do, `collective-permute-start.2_...` does not."""
    opcode = kind(name)
    return opcode is not None and not name[len(opcode):].startswith("-start")


def read(layers):
    devices = mesh_trace.per_device(layers)
    if not devices or not layers.requests:
        return None
    if not any(kind(n) for evs in devices.values() for n, _, _ in evs):
        return None  # no collective in the trace: nothing to read, not 0
    spent = [sum(e - s for n, s, e in evs if waits(n)) for evs in devices.values()]
    return 1e3 * sum(spent) / len(spent) / layers.requests
