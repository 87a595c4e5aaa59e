"""The TPU engine: compiles a resolved matcher Policy + cluster model into
dense tensors and evaluates the full ingress+egress verdict grid as JAX
kernels (reference counterpart: the sequential loop in
pkg/connectivity/probe/jobrunner.go:68-94 + pkg/matcher/policy.go:131-174).

Pipeline:
  encoding.py  - host-side tensor compiler (numpy): vocab-encode labels,
                 selectors, targets, peers, port specs
  kernel.py    - jit/vmap verdict kernels (single device)
  sharded.py   - Mesh + shard_map source-axis-sharded evaluation
  TpuPolicyEngine - the user-facing facade
"""

import importlib as _importlib
import logging as _logging
import os as _os
import sys as _sys

from ..telemetry import instruments as _ti
from ..telemetry.spans import span as _span

_cache_configured = False
_backend_started = False


def first_import(module: str = "jax") -> None:
    """Import `module` here and now if the process has not yet, in a
    `startup.import` span (attr `module`): JAX with libtpu takes seconds
    to import, `jax.experimental.pallas` a further one, and whoever
    happens to be first would otherwise carry that in its own span, or
    in none.  Every place of the engine that may be first calls this
    ahead of its own import statement.  With JAX in, the compile
    listener is registered (instruments.watch_jax_compiles)."""
    if module not in _sys.modules:
        with _span("startup.import", module=module):
            _importlib.import_module(module)
    if module == "jax":
        _ti.watch_jax_compiles()


def start_backend() -> None:
    """Start the default backend if nothing has yet, in a
    `startup.backend` span (attrs `platform`, `devices`): the process's
    first `jax.devices()` is the TPU runtime's start, which otherwise
    hides in whichever span first touches the device (or in none)."""
    global _backend_started
    if _backend_started:
        return
    _backend_started = True
    first_import()
    import jax

    # JAX says whether something else started the backend already (a
    # private name: where a JAX has moved it, the first call here is
    # taken for the start, and a started backend reads as a short span)
    bridge = getattr(getattr(jax, "_src", None), "xla_bridge", None)
    started = getattr(bridge, "backends_are_initialized", None)
    if started is None or not started():
        with _span("startup.backend") as sp:
            found = jax.devices()
            sp.set(platform=found[0].platform, devices=len(found))


def devices() -> list:
    """`jax.devices()` of the default backend, started by start_backend."""
    start_backend()
    import jax

    return jax.devices()


def cache_root() -> str:  # never-raises
    """The one directory every compile artefact lives under: JAX's
    persistent compilation cache directly in it, the AOT executable
    cache in `aot/` (aot_cache.py) and the autotune winners in
    `autotune.json` (autotune.py).  $JAX_COMPILATION_CACHE_DIR when set
    — a cache placed from outside the process — else the fixed
    `.cache/jax` of the checkout this package was imported from.  The
    path is part of JAX's cache key, so it is a function of the
    package location alone: the same from every process and working
    directory."""
    placed = _os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if placed:
        return placed
    checkout = _os.path.dirname(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    )
    return _os.path.join(checkout, ".cache", "jax")


def device_identity() -> dict:
    """The devices of the default backend as JAX reports them —
    {"platform", "kind", "count"} — for every line, banner and result
    that has to say what answered.  Initialises the backend."""
    found = devices()
    return {
        "platform": found[0].platform,
        "kind": found[0].device_kind,
        "count": len(found),
    }


def ensure_persistent_compile_cache() -> None:
    """Cache compiled XLA executables across processes: a CLI invocation
    pays seconds of TPU compile for the verdict kernels; with the cache
    a repeat run with the same tensor shapes skips it.  The directory is
    cache_root(); CYCLONUS_JAX_CACHE=0 opts out and CYCLONUS_JAX_CACHE=
    <dir> redirects (neither applies when JAX_COMPILATION_CACHE_DIR
    already placed the cache: the code then sets no directory at all).

    Called lazily from the first jax-using engine path (NOT at import
    time - the oracle/native engines never pay the jax import).  A
    cache that cannot be configured is a warning, never an error and
    never silent."""
    global _cache_configured
    if _cache_configured:
        return
    _cache_configured = True
    first_import()
    import jax

    # Full-traceback locations leak CALLER line numbers into the
    # Mosaic custom-call payload, where the cache key's
    # strip-debuginfo pass cannot reach (the payload is an opaque
    # serialized module): editing ANY file on the pallas call stack
    # — even a benchmark script — minted a fresh key for an
    # unchanged kernel and re-paid the TPU compile.  Frame-free
    # locations keep the key a function of the program alone.  It is
    # key hygiene, not cache placement, so it applies wherever the
    # cache lives; CYCLONUS_FULL_LOCATIONS=1 restores the
    # debug-friendly full frames.
    if _os.environ.get("CYCLONUS_FULL_LOCATIONS", "") != "1":
        jax.config.update("jax_include_full_tracebacks_in_locations", False)

    setting = _os.environ.get("CYCLONUS_JAX_CACHE", "")
    if not jax.config.jax_compilation_cache_dir:
        # nothing placed the cache from outside (the env var and a
        # caller's own jax.config.update both land in this option)
        if setting == "0":
            return
        path = setting or cache_root()
        try:
            _os.makedirs(path, exist_ok=True)
        except OSError as e:
            _logging.getLogger(__name__).warning(
                "persistent compile cache disabled: cannot create %s (%s)",
                path,
                e,
            )
            return
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in _os.environ:
        # the verdict kernels at CLI-typical cluster sizes compile in
        # ~0.2-1s each; the default 1s floor would cache none of them
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


from .encoding import ClusterEncoding, PolicyEncoding, encode_cluster, encode_policy
from .api import TpuPolicyEngine, PortCase

__all__ = [
    "ClusterEncoding",
    "PolicyEncoding",
    "encode_cluster",
    "encode_policy",
    "TpuPolicyEngine",
    "PortCase",
]
