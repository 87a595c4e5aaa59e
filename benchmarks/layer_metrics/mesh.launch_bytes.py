"""Host bytes a request's launch sends to the mesh: the `host_bytes` of the
window's `engine.dispatch_sharded` spans (every host array among the sharded
program's operands, an array sharded over the pods once, a replicated one
times the chips), summed, over the requests.  Nothing (never 0) where no span
carries the attribute (a program from before PR 34), or where the program's
ring dropped part of the window."""

from benchmarks import program_spans


def read(layers):
    found = program_spans.capture()
    if not found or found["wrapped"] or not layers.requests:
        return None
    sent = [
        sp["attrs"]["host_bytes"] for sp in found["spans"]
        if sp["name"] == "engine.dispatch_sharded" and "host_bytes" in sp["attrs"]
    ]
    return sum(sent) / layers.requests if sent else None
