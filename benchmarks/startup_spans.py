"""The program's start-up record: the door through which the `setup.*` readers
reach `cyclonus_tpu.telemetry.events.startup_spans`, beside `program_spans.py`.

From its import on the program keeps the spans it completes, until its first
profiler capture: in a traced run the record is what the process did before
the window.  `phases(layers)` lays the MAIN thread's time between process
start (run.py's T_START) and the window's first span at the door of eight
phases: an instant belongs to the innermost open span that a phase names, to
`setup.outside_s` where none is open, and is counted once, so the eight add up
to the whole of it.  A program that keeps no such record (any commit before
PR 36), or whose ring has dropped part of it, reads as nothing, never as 0.
"""

import threading
import time

from benchmarks import harness, program_spans

# metric -> the spans whose own time it is
PHASES = {
    "setup.import_s": ("startup.import",),
    "setup.backend_s": ("startup.backend",),
    "setup.matcher_s": ("matcher.build",),
    "setup.engine_s": ("engine.new",),
    "setup.classes_s": (
        "engine.cidrspace", "engine.classify", "engine.class_tensors",
        "engine.compact", "engine.partition",
    ),
    "setup.program_s": (
        "engine.program", "engine.static_pre", "engine.autotune", "jax.compile",
    ),
    # grid.wait: the tables entry's block_until_ready, outside any fetch
    "setup.warmup_s": ("engine.eval", "grid.fetch", "grid.wait"),
}
OUTSIDE = "setup.outside_s"
COMPILES = "setup.compiles"

# the LayerContext last read and what was read of it: nine readers ask in turn,
# and hack/startup_gaps.py prints it after a run
LAST = [None, None]


def record():
    """{"t0_epoch", "closed_by", "wrapped", "spans": [...]}, or None where
    the program keeps no such record."""
    from cyclonus_tpu.telemetry import events

    read = getattr(events, "startup_spans", None)
    return read() if read is not None else None


def share_out(spans, lo, hi):
    """{phase: seconds} of the time line [lo, hi]: each instant to the
    innermost open span that PHASES names, the rest to OUTSIDE.  The
    sharing out is the program's own (`instruments.exclusive_seconds`, the
    one its start-up gauges use), under the benchmark's phase map; None
    where the program has no such function."""
    from cyclonus_tpu.telemetry import instruments

    exclusive = getattr(instruments, "exclusive_seconds", None)
    if exclusive is None:
        return None
    phase_of = {name: phase for phase, names in PHASES.items() for name in names}
    out = exclusive(spans, phase_of, lo, hi)
    out[OUTSIDE] = (hi - lo) - sum(out.values())
    return out


def phases(layers):
    """{metric: value} of the nine `setup.*` metrics, a timed phase left out
    where the record holds none of its spans; None where there is no record,
    no window, or a record the ring has wrapped."""
    if LAST[0] is not layers:
        LAST[:] = layers, _read(layers)
    return LAST[1]


def _read(layers):
    found, window = record(), program_spans.capture()
    if not found or found["wrapped"] or not window or not window["spans"]:
        return None
    hi = window["spans"][0]["start_s"]
    lo = time.time() - (time.perf_counter() - layers.cell.t_start)
    main = threading.main_thread().ident
    before = [
        sp for sp in found["spans"] if sp["start_s"] + sp["dur_s"] <= hi
    ]
    out = share_out([sp for sp in before if sp.get("thread") == main], lo, hi)
    if out is None:
        return None
    out[COMPILES] = sum(
        1 for sp in before
        if sp["name"] == "jax.compile"
        and sp["attrs"].get("stage") == "backend_compile"
        and sp["attrs"].get("cache") != "hit"
    )
    harness.say(
        f"startup record: {found.get('events')} events, {len(before)} spans "
        f"before the window, closed by {found['closed_by']}"
    )
    return out


def read(layers, metric):
    found = phases(layers)
    return found.get(metric) if found else None
