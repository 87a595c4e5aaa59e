"""The program's own spans of the traced window: the one file through which
per-layer readers reach `cyclonus_tpu.telemetry.events.capture_spans`.

While a profiler capture runs, and only then, the program keeps the spans it
completes (`engine.eval`, `engine.dispatch`, `grid.copy`, ...; the same spans
lie in the capture's own trace as `cyclonus.<name>` annotations).  A traced
run's window is one capture, so the newest capture is the window: no more and
no less.  A program that keeps no such list (any commit before PR 26) reads as
nothing recorded, never as 0.
"""


def capture():
    """{"capture", "wrapped", "spans": [{name, path, start_s, dur_s, eval_id,
    attrs}]} of the newest capture, or None where the program has no such
    list."""
    from cyclonus_tpu.telemetry import events

    read = getattr(events, "capture_spans", None)
    return read() if read is not None else None


def per_request_ms(layers, *names):
    """1e3 x the summed length of the window's spans of those names over the
    requests it completed; None where none was recorded, or where the
    program's ring dropped part of the window (a sum would be short)."""
    found = capture()
    if not found or found["wrapped"] or not layers.requests:
        return None
    lengths = [sp["dur_s"] for sp in found["spans"] if sp["name"] in names]
    return 1e3 * sum(lengths) / layers.requests if lengths else None
