"""Trace-event recorder: the timeline half of the telemetry layer.

The span registry (spans.py) aggregates — count/total/max per path —
which answers "how expensive", never "when".  This module, when a trace
is active, additionally captures every span enter/exit as a timestamped
EVENT into a bounded ring (utils/bounded.BoundedRing), so a run can be
rendered as a wall-clock timeline (trace_export.py writes Chrome
trace-event JSON loadable in Perfetto / chrome://tracing).

Event shape (one dict per enter/exit):

    {"ph": "B"|"E", "name": ..., "path": "a/b/c", "ts": <epoch seconds>,
     "pid": ..., "tid": ..., "role": "driver"|"worker", "trace_id": ...,
     "args": {span attrs}}

Timestamps are epoch seconds (time.time), NOT perf_counter: a trace is
merged across PROCESSES (the driver and its in-pod workers), and the
epoch clock is the only one they share.  pid/tid keep the processes and
threads on separate timeline rows.

Trace context — (trace_id, parent span path) — crosses the driver→worker
wire as optional fields on the worker Batch (worker/model.py): the
worker adopts the driver's path as its span parent (spans.adopt), records
its own events under the same trace_id, and ships them back attached to
its Results.  `ingest` merges them into the driver's ring; events from
this process's own pid are skipped, because an in-process worker (tests,
--mock) already recorded into the same ring.

Recording is OFF by default — aggregates are always cheap, events are
per-occurrence — and costs one module-attribute read per span when off.
Enable with `enable()` (the --trace-out flags do this) or
CYCLONUS_TRACE_EVENTS=1 at process start; the ring holds the newest
CYCLONUS_TRACE_EVENTS_N events (default 32768), so an unbounded run keeps
a bounded, newest-wins window.

A JAX profiler CAPTURE turns recording on by itself, for as long as it
runs and only then: spans.span sees the capture, numbers it
(`begin_capture`) and records its B/E pair tagged with that `capture`
number and the evaluation's number as `eval_id`, the E event carrying
`dur_s` (the perf_counter difference the span registry got).
`capture_spans()` pairs one capture's events back into completed spans —
the list the benchmark's per-layer readers read, since the same spans
lie in the capture's own trace as `cyclonus.<name>` annotations.  The
default capacity holds the benchmark's largest traced window (500
requests of the counts route at 12 events each) five times over.

The START-UP RECORD is the third reason an event is kept.  From the
import of this module on, every span's B/E pair goes into the same ring
tagged `startup`, though no trace is ACTIVE and no capture records, so
that what a process did between its start and its first request can be
read back (`startup_spans()`: the same pairing as `capture_spans`).  The
record closes for good at the first of: the first profiler capture seen
(`begin_capture`; in a traced benchmark run the record is then exactly
what preceded the window), `close_startup()` (serve calls it when
/readyz turns ready), or `STARTUP_CAP` events.  Closed, it costs a span
one module-attribute read (`STARTUP`), as `ACTIVE` does.  The ring's
other readers (`entries`, `since`, trace_export) never see an event that
was kept for the record alone.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from typing import Any, Dict, Iterable, List, Optional

from ..utils.bounded import BoundedRing
from . import state


_DEFAULT_CAPACITY = 32768


def _default_capacity() -> int:
    try:
        return max(
            1,
            int(os.environ.get("CYCLONUS_TRACE_EVENTS_N", _DEFAULT_CAPACITY)),
        )
    except ValueError:
        return _DEFAULT_CAPACITY


RING = BoundedRing(_default_capacity())

# os.getpid() is a real syscall on every call (CPython does not cache
# it) and costs ~15 us under gVisor-style sandboxes — per EVENT that
# would dwarf the span itself.  Workers are fresh interpreters (never
# os.fork without exec), so the import-time value is always right.
_PID = os.getpid()

# Module attribute, read by the span() hot path: the disabled cost is
# this one read.  Flipped only by enable()/disable().
ACTIVE: bool = False

_TRACE: Dict[str, Optional[str]] = {"id": None, "role": "driver"}

# Profiler captures seen so far.  CAPTURE is the number of the capture
# that is recording NOW (0: none), read and written by the span() hot
# path through begin_capture/end_capture; _CAPTURE_STARTS keeps, for the
# newest few captures, the ring's lifetime append count when each was
# first seen, which is how capture_spans tells a wrapped ring.
CAPTURE: int = 0
_CAPTURES_KEPT = 8
_capture_lock = threading.Lock()
_capture_seen = 0  # guarded-by: _capture_lock
_CAPTURE_STARTS: Dict[int, int] = {}  # guarded-by: _capture_lock

# The start-up record.  STARTUP is read by the span() hot path, like
# ACTIVE, and only ever goes from True to False (close_startup).  The cap
# counts every event the ring took since the record opened.  It is four
# times the largest set-up of the benchmark's six cells (PERF.md section
# 5 has the six counts), and few enough that the counts cell's 3 ms
# requests, six events each, fill it in the first half second of their
# window: a process that never captures and never calls close_startup
# stops paying for the record after some 160 requests.
STARTUP: bool = state.ENABLED
STARTUP_CAP = 1024
# `first`: the ring's lifetime append count when the record opened (the
# hot path reads it bare: one dict read of an int)
_STARTUP: Dict[str, Any] = {"first": 0, "closed_by": None}


def _process_start_epoch() -> float:
    """When this process started, on the epoch clock: its age is now on
    CLOCK_BOOTTIME less field 22 of /proc/self/stat (the start, in clock
    ticks since boot).  /proc/stat's `btime` would do for the boot, but
    it is in whole seconds, which put the start up to a second off.
    Anywhere that cannot be read, the start is this import."""
    now = time.time()
    try:
        with open("/proc/self/stat") as f:
            after_comm = f.read().rsplit(")", 1)[1].split()
        started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - age if age >= 0 else now


T0_EPOCH = _process_start_epoch()


def enable(trace_id: Optional[str] = None, role: str = "driver") -> str:
    """Start (or join) a trace.  Returns the trace id — generated when
    not given (the driver's case), passed through when joining one (the
    worker adopting the driver's id off the wire)."""
    global ACTIVE
    tid = trace_id or uuid.uuid4().hex[:16]
    _TRACE["id"] = tid
    _TRACE["role"] = role
    ACTIVE = True
    return tid


def disable() -> None:
    global ACTIVE
    ACTIVE = False


def enabled() -> bool:
    return ACTIVE and state.ENABLED


def trace_id() -> Optional[str]:
    return _TRACE["id"]


def begin_capture() -> int:
    """A span found a profiler capture recording and CAPTURE at 0: this
    is a capture not seen before, and it gets the next number.  (Only
    spans look, so two captures with no span and no end_capture between
    them count as one.)"""
    global CAPTURE, _capture_seen
    with _capture_lock:
        if not CAPTURE:
            _capture_seen += 1
            _CAPTURE_STARTS[_capture_seen] = RING.appended
            for old in sorted(_CAPTURE_STARTS)[:-_CAPTURES_KEPT]:
                del _CAPTURE_STARTS[old]
            CAPTURE = _capture_seen
        number = CAPTURE
    if STARTUP:
        # from here capture_spans takes over: the record is what
        # preceded the capture's first span
        close_startup("capture")
    return number


def end_capture() -> None:
    """No capture is recording any more (a span saw none, or the code
    that stopped one says so): the next one seen is a new capture."""
    global CAPTURE
    CAPTURE = 0


def record(
    ph: str,
    name: str,
    path: str,
    attrs: Optional[Dict[str, Any]] = None,
    *,
    capture: int = 0,
    eval_id: Optional[int] = None,
    dur_s: Optional[float] = None,
    ts: Optional[float] = None,
) -> None:
    """Append one B/E event (called by spans.span on enter/exit).  With
    `capture` (the number of the profiler capture the span runs in), or
    while the start-up record is open, the event is kept even while no
    trace is ACTIVE; `startup` then says whether the record alone wanted
    it ("only": hidden from the ring's other readers) or not ("shared").
    `ts` is the event's epoch time where that is not now (the B of a
    span recorded when it was over, spans.completed)."""
    startup = STARTUP
    if not ((ACTIVE or capture or startup) and state.ENABLED):
        return
    event: Dict[str, Any] = {
        "ph": ph,
        "name": name,
        "path": path,
        "ts": time.time() if ts is None else ts,
        "pid": _PID,
        "tid": threading.get_ident(),
        "role": _TRACE["role"],
        "trace_id": _TRACE["id"],
    }
    if attrs:
        event["args"] = dict(attrs)
    if capture:
        event["capture"] = capture
    if eval_id is not None:
        event["eval_id"] = eval_id
    if dur_s is not None:
        event["dur_s"] = dur_s
    if startup:
        event["startup"] = "shared" if ACTIVE or capture else "only"
    taken = RING.append(event)
    if startup and taken - _STARTUP["first"] >= STARTUP_CAP:
        close_startup("cap")


def ingest(foreign: List[Dict[str, Any]]) -> int:
    """Merge events recorded by ANOTHER process (a worker's, shipped back
    on its Results) into this ring; returns how many were taken.  Events
    stamped with this process's own pid are skipped — an in-process
    worker (tests, --mock) already recorded them here, and ingesting
    again would double every span on the timeline."""
    taken = 0
    for e in foreign:
        if not isinstance(e, dict) or e.get("pid") == _PID:
            continue
        if not all(k in e for k in ("ph", "name", "path", "ts")):
            continue
        e = dict(e)
        e.pop("startup", None)  # the sender's start-up record, not ours
        RING.append(e)
        taken += 1
    return taken


def _shown(window: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The window without the events kept for the start-up record alone."""
    return [e for e in window if e.get("startup") != "only"]


def entries() -> List[Dict[str, Any]]:
    """Oldest-to-newest copy of the current event window."""
    return _shown(RING.snapshot())


def mark() -> int:
    """Position token for `since`: the lifetime append count."""
    return RING.appended


def since(marker: int) -> List[Dict[str, Any]]:
    """Events appended after `mark()` that are still in the window (the
    worker uses this to slice out exactly its batch's events).  The
    window and the append count come from ONE lock hold
    (snapshot_with_count): with the old separate snapshot()/.appended
    reads, appends landing between them inflated the count and the
    slice returned PRE-marker events — another thread's spans leaked
    into the worker's batch."""
    snap, appended = RING.snapshot_with_count()
    new = appended - marker
    if new <= 0:
        return []
    return _shown(snap[-min(new, len(snap)):])


def pair_spans(window: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """B/E events, oldest first, paired back into completed spans, by
    start: {"name", "path", "start_s", "dur_s", "eval_id", "attrs",
    "thread"}.  `start_s` is the B event's epoch time, `dur_s` the E
    event's perf_counter length, `attrs` the span's final attributes,
    `thread` the recording thread's ident.  A span still open at the
    window's end is left out; an E whose B the ring has dropped is a span
    all the same, its start reckoned from its end."""
    spans: List[Dict[str, Any]] = []
    open_by_thread: Dict[Any, List[Dict[str, Any]]] = {}
    for e in window:
        stack = open_by_thread.setdefault((e.get("pid"), e.get("tid")), [])
        if e["ph"] == "B":
            stack.append(e)
            continue
        # span() records its E in a `finally`, so a B is missing only
        # where the ring dropped it: reckon that start from the end
        begin = (
            stack.pop() if stack and stack[-1]["path"] == e["path"] else None
        )
        dur = e.get("dur_s", 0.0)
        spans.append({
            "name": e["name"],
            "path": e["path"],
            "start_s": begin["ts"] if begin is not None else e["ts"] - dur,
            "dur_s": dur,
            "eval_id": e.get("eval_id"),
            "attrs": dict(e.get("args") or {}),
            "thread": e.get("tid"),
        })
    spans.sort(key=lambda sp: sp["start_s"])
    return spans


def capture_spans(capture: Optional[int] = None) -> Dict[str, Any]:
    """The completed spans of one profiler capture (default: the newest
    one seen), oldest first:

        {"capture": n, "wrapped": bool,
         "spans": [{"name", "path", "start_s", "dur_s", "eval_id",
                    "attrs", "thread"}, ...]}

    as `pair_spans` makes them.  `wrapped` says that the ring dropped
    events of this capture (its first event is no longer in the window),
    so sums over `spans` would be short.  Capture 0 (none seen yet) is an
    empty list."""
    snap, appended = RING.snapshot_with_count()
    with _capture_lock:
        if capture is None:
            capture = _capture_seen
        started = _CAPTURE_STARTS.get(capture)
    if not capture:
        return {"capture": 0, "wrapped": False, "spans": []}
    wrapped = started is None or started < appended - len(snap)
    spans = pair_spans(e for e in snap if e.get("capture") == capture)
    return {"capture": capture, "wrapped": wrapped, "spans": spans}


def startup_spans() -> Dict[str, Any]:
    """The start-up record, as far as the ring still holds it:

        {"t0_epoch": ..., "closed_by": None|"capture"|"call"|"cap",
         "wrapped": bool, "events": n, "spans": [...]}

    `spans` are the record's completed spans as `pair_spans` makes them
    (the shape of `capture_spans`), `t0_epoch` the process's start on
    their clock, `closed_by` what closed the record (None: still open),
    `events` how many of its events the ring holds.  `wrapped` says the
    ring has dropped the record's first event, so sums over `spans`
    would be short."""
    snap, appended = RING.snapshot_with_count()
    first, closed_by = _STARTUP["first"], _STARTUP["closed_by"]
    kept = [e for e in snap if "startup" in e]
    return {
        "t0_epoch": T0_EPOCH,
        "closed_by": closed_by,
        "wrapped": first < appended - len(snap),
        "events": len(kept),
        "spans": pair_spans(kept),
    }


def close_startup(by: str = "call") -> None:
    """Close the start-up record for good (a no-op on a closed one) and
    set the cyclonus_tpu_startup_seconds gauges from it, while the ring
    still holds it."""
    global STARTUP
    with _capture_lock:
        if not STARTUP:
            return
        STARTUP = False
        _STARTUP["closed_by"] = by
    from . import instruments  # imports this module: not at the top

    instruments.refresh_startup()


def _open_startup() -> None:
    """An open record from the ring as it stands, as a fresh process has
    it from its import (a test's way to get one back)."""
    global STARTUP
    with _capture_lock:
        _STARTUP.update(first=RING.appended, closed_by=None)
        STARTUP = state.ENABLED


def reset() -> None:
    """Clear the window (the active/trace-id state survives — a reset
    mid-trace starts an empty timeline, not an untraced one).  A capture
    that is recording is counted as a new one by its next span, so its
    `wrapped` is reckoned from the cleared ring; an open start-up record
    goes on from the cleared ring too."""
    RING.clear()
    end_capture()
    _STARTUP["first"] = 0


if os.environ.get("CYCLONUS_TRACE_EVENTS", "") == "1":
    enable(os.environ.get("CYCLONUS_TRACE_ID") or None)
