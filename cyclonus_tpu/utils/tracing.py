"""Phase timers + JAX profiler hooks — now a thin facade over
`cyclonus_tpu.telemetry.spans`.

The reference has no tracing/profiling at all (SURVEY.md section 5); its
closest analog is logrus trace-level logging of each simulated verdict
(jobrunner.go:80 — mirrored by CYCLONUS_TRACE_VERDICTS in
probe/runner.py).  Tracing here is first-class: `phase` is a structured
span (hierarchical, thread-safe, attribute-carrying), and this module
keeps the historical flat API so existing consumers (the generate
--phase-stats flag, tests) are unchanged:

    with phase("encode"):
        ...
    stats()        -> {"encode": {"count": 3, "total_s": ..., "max_s": ...}}
    reset()

    with jax_profile("/tmp/trace"):   # no-op when dir is falsy
        engine.evaluate_grid(cases)

For the hierarchical view, attributes, metrics, and the flight recorder,
use `cyclonus_tpu.telemetry` directly.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Dict, Iterator, Optional

from ..telemetry.spans import (  # noqa: F401 (re-exports)
    REGISTRY,
    detail,
    span as phase,
)

logger = logging.getLogger("cyclonus.trace")


def stats() -> Dict[str, Dict[str, float]]:
    """Flat per-name aggregates (the pre-telemetry shape, preserved)."""
    return REGISTRY.stats()


def reset() -> None:
    REGISTRY.reset()


def render_stats() -> str:
    rows = sorted(stats().items())
    if not rows:
        from ..telemetry import state

        if not state.ENABLED:
            return "(no phases recorded: telemetry disabled, CYCLONUS_TELEMETRY=0)"
        return "(no phases recorded)"
    out = [f"{'phase':<24}{'count':>8}{'total_s':>12}{'max_s':>10}"]
    for name, rec in rows:
        out.append(
            f"{name:<24}{int(rec['count']):>8}{rec['total_s']:>12.4f}"
            f"{rec['max_s']:>10.4f}"
        )
    return "\n".join(out)


@contextlib.contextmanager
def jax_profile(trace_dir: Optional[str]) -> Iterator[None]:
    """Wrap a block in jax.profiler.trace(trace_dir); no-op when falsy.
    While it runs every phase/span is also a `cyclonus.<name>` trace
    annotation in the capture (telemetry/spans.py)."""
    if not trace_dir:
        yield
        return
    import jax

    from ..telemetry import events

    try:
        with jax.profiler.trace(trace_dir):
            yield
    finally:
        # spans notice a capture's end only at the next span: say so now,
        # so that a capture started right after this one is a new one
        events.end_capture()
    logger.info("jax profiler trace written to %s", trace_dir)
