"""From a profiler trace (.xplane.pb) to numbers, the same way in every PR.

  busy_s       union of the intervals in which an operation ran on a device
               (the device plane's "XLA Ops" line), averaged over the devices
  window_s     the length of the traced window, as given or as the trace spans
  device_ops   device seconds by operation, largest first
  idle_gaps    idle device time by the innermost `bench.<layer>` host span that
               covered it, a gap that runs through several spans being cut at
               their edges ("host:_no_benchmark_annotation" where none did),
               largest first

`reduce_events` is the arithmetic on plain tuples (tested on hand-made
events); `read_xplane` turns a trace file into those tuples with JAX's own
reader and nothing else.  benchmarks/tests/test_trace_reduce.py pins both on
the small recorded trace beside it.
"""

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
NO_SPAN = "host:_no_benchmark_annotation"
SPAN_PREFIX = "bench."


def op_name(raw: str) -> str:
    """`%fusion.9 = pred[2,64]{1,0} fusion(...)` -> `fusion.9_pred_2_64_`:
    the instruction and its result shape, in the characters a name may have."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*\(?([\w]+\[[\d,]*\])?", raw)
    short = (m.group(1) + ("_" + m.group(2) if m.group(2) else "")) if m else raw
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", short)[:96]


def _union(intervals):
    """Sorted, merged copies of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce_events(device_events: dict, host_spans, window=None, top: int = 10) -> dict:
    """device_events: {device name: [(op name, start_s, end_s), ...]};
    host_spans: [(span name, start_s, end_s), ...] on the same clock;
    window: (start_s, end_s) to clip to, default what the device events span."""
    every = [ev for evs in device_events.values() for ev in evs]
    if not every:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": [],
                "devices": 0}
    lo, hi = window or (min(e[1] for e in every), max(e[2] for e in every))
    busy, by_op, gaps = [], {}, {}
    cover = _innermost(host_spans)
    ends = [piece[2] for piece in cover]
    for evs in device_events.values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]
        for n, s, e in clipped:
            by_op[n] = by_op.get(n, 0.0) + (e - s)
        merged = _union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            for name, s, e in _cut(cover, ends, a, b):
                gaps[name] = gaps.get(name, 0.0) + (e - s)
    n_dev = len(device_events)
    rank = lambda d: sorted(([k, v / n_dev] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / n_dev, "window_s": hi - lo,
            "device_ops": rank(by_op), "idle_gaps": rank(gaps), "devices": n_dev}


def _innermost(host_spans):
    """The time line as [(name, start, end)] pieces in order, each under the
    innermost (latest opened, still open) span; stretches no span covers are
    left out."""
    edges = sorted(
        [(s, 1, -e, name) for name, s, e in host_spans]      # longer opens first
        + [(e, 0, -s, name) for name, s, e in host_spans]    # closes before opens
    )
    pieces, open_spans, since = [], [], None
    for t, opening, _, name in edges:
        if open_spans and t > since:
            pieces.append((open_spans[-1], since, t))
        if opening:
            open_spans.append(name)
        else:
            # spans nest, so the one that closes is the last of its name
            del open_spans[len(open_spans) - 1 - open_spans[::-1].index(name)]
        since = t
    return pieces


def _cut(pieces, ends, a: float, b: float):
    """The interval (a, b) cut at the pieces' edges: [(name, start, end)],
    with NO_SPAN for what no piece covers; `ends` are the pieces' ends."""
    out, at = [], a
    i = bisect.bisect_right(ends, a)
    while at < b and i < len(pieces):
        name, s, e = pieces[i]
        if s >= b:
            break
        if s > at:
            out.append((NO_SPAN, at, s))
            at = s
        out.append((name, at, min(e, b)))
        at = min(e, b)
        i += 1
    if at < b:
        out.append((NO_SPAN, at, b))
    return out


def busy_inside(device_events: dict, spans) -> float:
    """Device-busy seconds (mean over devices) inside the union of `spans`."""
    cover = _union([(s, e) for _, s, e in spans])
    total = 0.0
    for evs in device_events.values():
        merged = _union([(s, e) for _, s, e in evs])
        for lo, hi in cover:
            total += sum(e - s for s, e in _clip(merged, lo, hi))
    return total / max(1, len(device_events))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str):
    """(device_events, host_spans) of a trace file, times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events[plane.name] = [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append(
                            (ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                        )
    return device_events, host_spans
