"""One client, one request at a time: the window that the `sweep` and
`oneshot` kinds share.  A kind builds its system under test and says what a
request is; this file warms it, measures the window, reads the device, frees
the program's state, and only then runs the reference and compares.

Requests go round robin over `n_keys` distinct requests, from a start the seed
chooses and without a break between warm-up and window, so that no request
repeats the one before it.  The window ends when the request in flight at
`--seconds` completes; rates are over the time that really passed.
"""

import contextlib
import gc
import time

import numpy as np

from benchmarks import harness, program
from benchmarks.grid_check import GridChecker

_TABLES = {}  # shape -> the three arrays the exact reference's tables go into


def answer(ref, result: str, cases, broken: str):
    """The reference's answer in the entry's form.  The exact reference is
    compared one answer at a time, so its tables reuse one set of arrays; the
    control's answers are kept side by side and get their own."""
    if result == "counts":
        return ref.counts(cases)
    if broken:
        return ref.tables(cases)
    shape = (len(cases), ref.n, ref.n)
    if shape not in _TABLES:
        _TABLES[shape] = tuple(np.empty(shape, dtype=bool) for _ in range(3))
    return ref.tables(cases, out=_TABLES[shape])


def run(cell, *, spans, n_keys, n_pods, result, request, cases_of,
        release, expected_of, finish):
    """request(key) -> the answer on the host; cases_of(key) -> how many port
    cases it holds; release() drops the program's device state;
    expected_of(key, broken) -> the reference's answer in the same form;
    finish(keys, elapsed, setup_s) -> the end-to-end metrics, from the keys of
    the requests the window completed."""
    device = harness.require_device(cell)
    handed_over = time.perf_counter()
    checker = GridChecker(result, n_pods, cell.rng("cells"))
    turn = cell.rng("start").randrange(n_keys)
    # the distinct requests share their shapes: the first compiles (or loads
    # the cache), the others confirm that nothing else does
    for _ in range(n_keys):
        request(turn % n_keys)
        turn += 1
    harness.say(
        f"setup: {handed_over - cell.t_start:.1f} s of imports, data and engine, "
        f"{time.perf_counter() - handed_over:.1f} s of warm-up"
    )
    compiles = harness.CompileCounter()
    seconds, at_most = cell.seconds, float("inf")
    if cell.trace:
        seconds, at_most = min(seconds, harness.TRACE_SECONDS), harness.TRACE_REQUESTS
    tracer = harness.TraceWindow(cell, spans) if cell.trace else None
    done = []
    with tracer or contextlib.nullcontext():
        with spans.span("bench.window"):
            t0 = time.perf_counter()
            while True:
                key = turn % n_keys
                checker.record(key, cases_of(key), request(key))
                turn += 1
                done.append(key)
                if time.perf_counter() - t0 >= seconds or len(done) >= at_most:
                    break
            t1 = time.perf_counter()
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    layers = harness.LayerContext(
        cell=cell, spans=spans.inside(t0, t1), requests=len(done), device=device,
        counters={"compiles_in_window": compiles.count},
    )
    if cell.trace:
        harness.say(f"routes: {program.routes()}")
        layers.device_events, layers.host_spans = tracer.read()
        layers.trace = harness.reduce_window(
            layers.device_events, layers.host_spans, "bench.window"
        )
    release()
    gc.collect()
    if cell.control:
        checker.substitute(lambda key: expected_of(key, cell.control))
    checks = checker.compare(lambda key: expected_of(key, ""))
    return harness.Outcome(
        attempted=len(done), failed=0, checks=checks, device=device, layers=layers,
        end_to_end=finish(done, t1 - t0, t0 - cell.t_start),
    )
