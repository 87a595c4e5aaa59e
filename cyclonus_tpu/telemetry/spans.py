"""Hierarchical structured spans.

Upgrades the flat phase timers of `utils/tracing.py` (which now delegates
here) into parent/child-nested spans with attributes, while keeping the
old flat view intact for existing consumers:

    with span("engine.encode", pods=n) as s:
        ...
        s.set(targets=t)

Nesting is tracked per thread (a thread-local path stack), so concurrent
evaluations never see each other's parents.  The registry aggregates two
views under one lock:

  * flat, by span NAME — exactly the shape `utils.tracing.stats()` has
    always returned ({"count", "total_s", "max_s"} per name);
  * hierarchical, by span PATH ("a/b/c"), each node additionally carrying
    the most recent attributes — rendered as a tree by `render_tree`.

Span names are static strings (phase names, kernel paths), so the
registry is bounded by the instrumentation sites, not by traffic.  The
hot-path cost when telemetry is disabled is one module-attribute read;
when enabled, two perf_counter calls plus one locked dict update.

While a JAX profiler capture is recording (`--jax-profile`,
`/profile?seconds=N`, the benchmark's `--trace 1` window) a span is
ALSO a `jax.profiler.TraceAnnotation("cyclonus." + name)`: it lies in
the capture's `/host:CPU` plane, on the clock the device operations are
on, so an idle stretch of the device can be laid at the program's own
span.  For that time, and only then, its B/E events are recorded too
(events.py `capture_spans`).  With no capture the added cost is one
`TraceAnnotation.is_enabled()` call.  JAX is never imported from here:
no capture can run in a process that has not imported it.

From the import of telemetry until the start-up record closes (its
first capture, `events.close_startup()`, or its cap) a span's B/E events
are recorded as well, capture or none: `events.startup_spans()` is what
the process did before its first request.  Closed, the record costs one
more module-attribute read.

`detail(name)` is a span that exists ONLY while a capture or a trace
records (the start-up record does not count): the steps of a request that take less than a span costs to
aggregate are visible on a timeline and free otherwise.
"""

from __future__ import annotations

import contextlib
import logging
import sys
import threading
import time
from typing import Any, Dict, Iterator, Optional

from ..utils import guards
from . import events, state

logger = logging.getLogger("cyclonus.trace")

_EMPTY: Dict[str, Any] = {}


def _trace_annotation():
    """`jax.profiler.TraceAnnotation` once the process has imported JAX,
    else None; looked up through sys.modules so that importing telemetry
    never starts importing JAX."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _ANNOTATION = getattr(profiler, "TraceAnnotation", None)
    return _ANNOTATION


_ANNOTATION = None
# what a trace annotation carries of a span's attributes: the viewer
# shows them as the event's stats, and a long repr would bloat every event
_SMALL = (bool, int, float, str)


def _capture_number() -> int:
    """The number of the profiler capture that records now, 0 where none
    does: the first span (or completed span) to see a capture counts it,
    the first to see it gone ends it."""
    annotation = _ANNOTATION or _trace_annotation()
    if annotation is not None and annotation.is_enabled():
        return events.CAPTURE or events.begin_capture()
    if events.CAPTURE:
        events.end_capture()
    return 0


def _small(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {
        k: v for k, v in attrs.items()
        if isinstance(v, _SMALL) and (not isinstance(v, str) or len(v) <= 64)
    }


class Span:
    """One timed block, the context manager `span()` returns:

        with span("engine.encode", pods=n) as s:
            ...
            s.set(targets=t)

    Entering makes it the current thread's active span (its children
    nest under its path); leaving records it."""

    __slots__ = (
        "name", "path", "attrs", "_parent", "_t0", "_capture", "_eval_id",
        "_annotation", "_shown",
    )

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.path = name
        self.attrs = attrs

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        name = self.name
        parent = self._parent = getattr(_tls, "path", "")
        if parent:
            self.path = f"{parent}/{name}"
        _tls.path = self.path
        capture = self._capture = _capture_number()
        self._annotation = self._eval_id = None
        if capture or events.ACTIVE or events.STARTUP:
            eval_id = self._eval_id = getattr(_tls, "eval_id", None)
            events.record(
                "B", name, self.path, self.attrs,
                capture=capture, eval_id=eval_id,
            )
            if capture:
                shown = _small(self.attrs)
                if eval_id is not None:
                    shown["eval_id"] = eval_id
                self._shown = tuple(shown)
                # a capture records, so _ANNOTATION is the class by now
                self._annotation = _ANNOTATION("cyclonus." + name, **shown)
                self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = time.perf_counter() - self._t0
        if self._annotation is not None:
            late = _small(self.attrs)  # what s.set() added inside the block
            for k in self._shown:
                late.pop(k, None)
            if late:
                self._annotation.set_metadata(**late)
            self._annotation.__exit__(*exc)
        _tls.path = self._parent
        REGISTRY.record(self.path, self.name, dt, self.attrs)
        if self._capture or events.ACTIVE or events.STARTUP:
            # exit carries the FINAL attrs (s.set() calls inside the block)
            events.record(
                "E", self.name, self.path, self.attrs,
                capture=self._capture,
                eval_id=self._eval_id,
                dur_s=dt,
            )
        logger.debug("phase %s: %.4fs", self.path, dt)


class _NullSpan:
    """Shared no-op handle for the disabled path (no allocation)."""

    __slots__ = ()
    name = ""
    path = ""
    attrs = _EMPTY

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None


_NULL_SPAN = _NullSpan()


@guards.checked
class SpanRegistry:
    """Thread-safe per-process aggregation of completed spans."""

    # runtime twins of the guarded-by contract (tools/locklint.py LK001)
    _flat = guards.Guarded("_lock")
    _tree = guards.Guarded("_lock")

    def __init__(self) -> None:
        self._lock = guards.lock()
        self._flat: Dict[str, Dict[str, float]] = {}  # guarded-by: self._lock
        self._tree: Dict[str, Dict[str, Any]] = {}  # guarded-by: self._lock

    def record(
        self, path: str, name: str, dt: float, attrs: Dict[str, Any]
    ) -> None:
        with self._lock:
            rec = self._flat.get(name)
            if rec is None:
                rec = self._flat[name] = {
                    "count": 0, "total_s": 0.0, "max_s": 0.0
                }
            rec["count"] += 1
            rec["total_s"] += dt
            if dt > rec["max_s"]:
                rec["max_s"] = dt
            node = self._tree.get(path)
            if node is None:
                node = self._tree[path] = {
                    "count": 0, "total_s": 0.0, "max_s": 0.0, "attrs": {}
                }
            node["count"] += 1
            node["total_s"] += dt
            if dt > node["max_s"]:
                node["max_s"] = dt
            if attrs:
                node["attrs"].update(attrs)

    def stats(self) -> Dict[str, Dict[str, float]]:
        """Flat per-name aggregates (the historical tracing.stats shape)."""
        with self._lock:
            return {k: dict(v) for k, v in self._flat.items()}

    def tree(self) -> Dict[str, Dict[str, Any]]:
        """Per-path aggregates with attributes; keys are 'a/b/c' paths."""
        with self._lock:
            return {
                k: {**{x: v[x] for x in ("count", "total_s", "max_s")},
                    "attrs": dict(v["attrs"])}
                for k, v in self._tree.items()
            }

    def reset(self) -> None:
        with self._lock:
            self._flat.clear()
            self._tree.clear()

    def render_tree(self) -> str:
        """Indented tree view, children under parents, sorted by path."""
        rows = sorted(self.tree().items())
        if not rows:
            return "(no spans recorded)"
        out = [f"{'span':<44}{'count':>8}{'total_s':>12}{'max_s':>10}"]
        for path, rec in rows:
            depth = path.count("/")
            label = ("  " * depth) + path.rsplit("/", 1)[-1]
            attrs = (
                " " + ",".join(f"{k}={v}" for k, v in sorted(rec["attrs"].items()))
                if rec["attrs"]
                else ""
            )
            out.append(
                f"{label:<44}{int(rec['count']):>8}{rec['total_s']:>12.4f}"
                f"{rec['max_s']:>10.4f}{attrs}"
            )
        return "\n".join(out)


REGISTRY = SpanRegistry()

_tls = threading.local()


def current_path() -> str:
    """The active span path on this thread ('' at top level)."""
    return getattr(_tls, "path", "")


@contextlib.contextmanager
def adopt(path: str) -> Iterator[None]:
    """Adopt a foreign span path as this thread's parent, so subsequent
    spans nest under it.  Two users: worker threads inheriting the
    issuing thread's path (pool.map drops thread-locals), and the remote
    worker adopting the DRIVER's path off the wire (worker/model.py
    Batch.parent_span) so a merged trace renders as one tree."""
    prev = getattr(_tls, "path", "")
    _tls.path = path or ""
    try:
        yield
    finally:
        _tls.path = prev


def span(name: str, **attrs: Any):
    """Time a block as a child of the current thread's active span."""
    if not state.ENABLED:
        return _NULL_SPAN
    return Span(name, attrs)


def detail(name: str, **attrs: Any):
    """A span for a step of tens of microseconds inside a request
    (`engine.case_tensors`, `engine.plan`, `engine.unpack`,
    `engine.finish`): it exists only while something keeps a timeline —
    a profiler capture or an ACTIVE trace — and is the shared no-op
    handle otherwise, so an unobserved request pays one poll for it and
    the registry's aggregates since process start never hold it."""
    if state.ENABLED:
        if events.ACTIVE:
            return Span(name, attrs)
        annotation = _ANNOTATION or _trace_annotation()
        if annotation is not None and annotation.is_enabled():
            return Span(name, attrs)
    return _NULL_SPAN


def completed(name: str, dur_s: float, **attrs: Any) -> None:
    """Record a span that is over already and ended now, as a child of
    the current thread's active span: what a callback hears of only when
    it is done (a JAX compile stage, instruments.watch_jax_compiles).
    The registry gets it as it gets any span; a timeline that is being
    kept (the start-up record, a capture, an ACTIVE trace) gets its B/E
    pair, the B dated back by the span's length."""
    if not state.ENABLED:
        return
    parent = getattr(_tls, "path", "")
    path = f"{parent}/{name}" if parent else name
    REGISTRY.record(path, name, dur_s, attrs)
    capture = _capture_number()
    if capture or events.ACTIVE or events.STARTUP:
        eval_id = getattr(_tls, "eval_id", None)
        events.record(
            "B", name, path, attrs, capture=capture, eval_id=eval_id,
            ts=time.time() - dur_s,
        )
        events.record(
            "E", name, path, attrs, capture=capture, eval_id=eval_id,
            dur_s=dur_s,
        )


class evaluation:
    """Everything this thread's spans record inside the block belongs to
    evaluation `eval_id`: `instruments.eval_flight` opens one per
    evaluation, and `GridVerdict` re-opens its evaluation's round the
    fetches that run after `evaluate_grid` has returned.  The id lands on
    the recorded events and the trace annotations, so the spans of one
    request share an identifier."""

    __slots__ = ("eval_id", "_prev")

    def __init__(self, eval_id: Optional[int]):
        self.eval_id = eval_id

    def __enter__(self) -> "evaluation":
        self._prev = getattr(_tls, "eval_id", None)
        _tls.eval_id = self.eval_id
        return self

    def __exit__(self, *exc: Any) -> None:
        _tls.eval_id = self._prev
