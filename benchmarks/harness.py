"""What every kind of traffic shares: the cell as run.py found it, spans, the
device check, the profiler window, compile counting and the result's pieces.

Nothing here knows a configuration, a mix or a metric by name.
"""

import contextlib
import dataclasses
import os
import random
import shutil
import sys
import time

from benchmarks import trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# scratch of a run: traces and the served child's files; under the checkout,
# in a directory .gitignore lists, at a path that never moves
SCRATCH = os.path.join(REPO, ".cache", "bench")
# an in-process traced run measures at most this long and this many requests:
# the profiler's file grows with the requests, and reading 4,000 requests of
# the counts cell took over five minutes (my chip run, PR 25)
TRACE_SECONDS = 10.0
TRACE_REQUESTS = 500


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict      # benchmarks/configs/<config>.json
    traffic: dict     # benchmarks/traffic/<mix>.json
    chips: int
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float    # perf_counter at process start: setup_s counts from here
    control: str = ""  # a guarantee to break in the reference put in the
    #                    program's place (BENCH_CONTROL; the driver never sets it)

    @property
    def sizes(self) -> dict:
        return self.config["rehearsal" if self.rehearse else "sizes"]

    def rng(self, salt: str) -> random.Random:
        return random.Random(f"{self.seed}/{self.name}/{salt}")

    def scratch(self, *parts) -> str:
        path = os.path.join(SCRATCH, self.name, *parts)
        os.makedirs(path, exist_ok=True)
        return path


@dataclasses.dataclass
class Outcome:
    """What a kind hands back to run.py."""
    attempted: int
    failed: int
    end_to_end: dict            # metric name -> value
    checks: list                # [(name, value, limit)]: correct = all value <= limit
    device: dict                # platform, kind, count, memory_peak_bytes
    layers: "LayerContext" = None


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read (benchmarks/layer_metrics/*.py)."""
    cell: Cell
    spans: dict                 # span name -> [seconds] inside the measured window
    counters: dict              # counter name -> number
    requests: int               # requests the measured window completed
    trace: dict = None          # trace_reduce.reduce_events(...) of the window
    device_events: dict = None  # raw tuples, for readers that clip themselves
    host_spans: list = None
    device: dict = None

    def span_mean_ms(self, name: str):
        """Mean length of the window's spans of that name; None where none."""
        spans = self.spans.get(name)
        return 1e3 * sum(spans) / len(spans) if spans else None


class Spans:
    """Host spans `bench.<layer>` around the calls into each layer: kept in
    memory on the host clock, and written into the profiler's trace (so that
    idle device time can be laid at a span's door) while one is running."""

    def __init__(self):
        self.records = []  # (name, start, end) on time.perf_counter
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def inside(self, lo: float, hi: float) -> dict:
        out = {}
        for name, s, e in self.records:
            if s >= lo and e <= hi:
                out.setdefault(name, []).append(e - s)
        return out


def rehearsal_env() -> None:
    """Before anything imports JAX: the CPU, and mechanics over speed."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")


def require_device(cell: Cell) -> dict:
    """The devices as JAX reports them; raises NoAccelerator off a TPU."""
    import jax

    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    check_device(cell, found)
    return found


def check_device(cell: Cell, found: dict) -> None:
    if cell.rehearse:
        return
    if found["platform"] != "tpu" or found["count"] < cell.chips:
        raise NoAccelerator(
            f"{cell.name} needs {cell.chips} TPU chip(s); JAX reports {found}"
        )


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


class CompileCounter:
    """Programs JAX compiled or fetched from its cache since `reset`."""

    def __init__(self):
        from jax import monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.count = 0
        self._event = BACKEND_COMPILE_EVENT
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self._event:
            self.count += 1

    def reset(self):
        self.count = 0


class TraceWindow:
    """JAX's profiler around a measured window of this process."""

    def __init__(self, cell: Cell, spans: Spans):
        self.dir = cell.scratch("trace")
        self.spans = spans

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.spans.annotate = True
        return self

    def __exit__(self, *exc):
        import jax

        self.spans.annotate = False
        jax.profiler.stop_trace()

    def read(self):
        """(device_events, host_spans), and the trace's files are removed."""
        try:
            return trace_reduce.read_xplane(trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def reduce_window(device_events, host_spans, window_name: str):
    """The trace clipped to the host span that wraps the measured window."""
    window = [(s, e) for n, s, e in host_spans if n == window_name]
    return trace_reduce.reduce_events(
        device_events, host_spans, window=window[0] if window else None
    )


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
