"""Persistent AOT executable cache (docs/DESIGN.md "Cold start & chaos").

The JAX persistent compilation cache (engine/__init__.py) already skips
the XLA *compile* on a warm restart, but a fresh process still pays the
full Python *trace* of every program plus the cache's own lookup
machinery, and it recurs for every compiled program family.  This
module goes the rest of the way: compiled executables are SERIALIZED
(jax.experimental.serialize_executable — the loaded binary, not the
StableHLO) keyed by

    (program name, arg shape/dtype signature = the shape bucket,
     mesh signature, schedule, dtype plan / pack)

so a restarted process ADOPTS the executable with zero traces and zero
compiles — the AOT_COMPILES counter stays flat, which is exactly what
tests/test_aot_cache.py's subprocess restart gate asserts.

Robustness contract (the engine/autotune.py discipline): the cache is
advisory.  A corrupt, truncated, version-skewed, wrong-key, or
concurrently-replaced entry degrades to a fresh trace+compile — load
NEVER raises — and a failed write is a logged warning.  Every entry is
its own file written atomically (tmp + os.replace), so concurrent
processes warming different programs can never clobber each other and a
reader can never observe a half-written entry; same-key racers both
wrote a valid executable and the last one wins.  Entries embed the full
key plus CACHE_VERSION and the jax/backend stamp: a jaxlib upgrade or a
different device kind silently invalidates instead of loading an
executable the runtime cannot run.

Security note: entries are pickles (the serialize_executable payload
format), loaded only from the operator's own cache directory — the same
trust boundary as the autotune cache and JAX's own compilation cache.

In front of the files stands a MEMORY TIER of the process (`_SHARED`):
the executables this process has already adopted or built, under the
same key string and the directory they belong to.  A new engine of
shapes the process has loaded resolves its programs there (counter
outcome `shared`, span attr how="shared"): no file, no unpickle, no
`deserialize_and_load`.  A LIVE process therefore does nothing with a
file that changes under it, be it replaced, poisoned or deleted, until
`forget()` or a restart: it goes on running the executable it holds,
which is a valid one for that key.  The restart contract and the
poisoned-entry contract above are about NEW processes, which start with
an empty memory tier, and are unchanged.

CYCLONUS_AOT_CACHE: cache directory; "0"/"" disables entirely (the test
suite default — tests/conftest.py — so suites never share executables
through the checkout's cache); unset -> `aot/` under the engine's
cache_root() ($JAX_COMPILATION_CACHE_DIR, else the checkout's fixed
.cache/jax).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import tempfile
import threading
from typing import Any, Dict, Optional, Tuple

from ..telemetry.spans import span
from ..utils import cachekeys

log = logging.getLogger(__name__)

#: bump when the entry layout changes: stale versions are ignored
#: (fresh compile), never migrated
CACHE_VERSION = 1

#: the memory tier holds at most this many executables, least recently
#: resolved out first.  An engine resolves two to six (grid + unpack,
#: counts, pairs, the sharded grid), a `serve` replica a dozen over its
#: pair-batch buckets, `generate`'s suite a few shape buckets of each.
SHARED_MAX = 64

#: (cache directory, persisted key string) -> loaded executable, oldest
#: first.  The lock covers the mapping's own bookkeeping alone, never a
#: load or a compile: the worst interleaving loads one key twice, both
#: valid, and the last is kept.
_SHARED_LOCK = threading.Lock()
_SHARED: Dict[Tuple[str, str], Any] = {}  # guarded-by: _SHARED_LOCK  # cache-key: cache_dir, name, signature, platform, schedule, plan

if cachekeys.ACTIVE:
    # the entry's identity is the file's (make_key) and its directory
    cachekeys.register(
        "aot.shared",
        kind="program",
        components=cachekeys.program(
            "cache_dir", "name", "signature", "platform", "schedule", "plan"
        ),
    )


def cache_dir() -> Optional[str]:  # never-raises
    """Resolved cache directory, or None when persistence is disabled."""
    raw = os.environ.get("CYCLONUS_AOT_CACHE")
    if raw is None:
        from . import cache_root

        # cache_root is checked as never-raising in its own module
        return os.path.join(cache_root(), "aot")  # cachelint: ignore[CC005]
    raw = raw.strip()
    if raw in ("", "0"):
        return None
    return raw


def platform_stamp() -> str:
    """The (jax + jaxlib version, backend, device kind, device count)
    stamp an entry must match to load: a serialized executable is a
    binary for one runtime on one device topology — skew means
    recompile, never a load attempt that the runtime rejects (or worse,
    misruns).  jaxlib rides the stamp SEPARATELY from jax: the payload
    bytes are jaxlib's, and the two versions can be pinned
    independently — a jaxlib-only upgrade used to slip past the key
    (found by the tools/cachelint.py key-surface audit; pinned by
    tests/test_aot_cache.py)."""
    import jax

    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", "?")
    except Exception:  # no separate jaxlib dist: jax's version rules
        jaxlib_v = "?"
    devs = jax.devices()
    return (
        f"jax={jax.__version__};jaxlib={jaxlib_v};"
        f"backend={jax.default_backend()};"
        f"kind={devs[0].device_kind};n={len(devs)}"
    )


def make_key(
    name: str,
    signature: str,
    *,
    schedule: str = "single",
    plan: str = "",
) -> str:
    """Stable string key for one executable: the program NAME, the arg
    shape/dtype SIGNATURE (the shape bucket — bucketing is what makes
    two processes lower byte-identical programs), the mesh/platform
    stamp, the exchange SCHEDULE (single / ring / allgather), and the
    dtype PLAN (packed32 / int8 / bf16 + any per-engine extras)."""
    return json.dumps(
        {
            "name": name,
            "sig": signature,
            "platform": platform_stamp(),
            "schedule": schedule,
            "plan": plan,
        },
        sort_keys=True,
        separators=(",", ":"),
    )


def _entry_path(base: str, key: str) -> str:  # never-raises
    d = hashlib.sha256(key.encode("utf-8")).hexdigest()[:32]
    return os.path.join(base, f"{d}.aotx")


def digest(obj) -> str:  # never-raises
    """Stable short digest of `repr(obj)` — THE helper for folding
    program identity the arg shapes can't see (unpack leaf metas,
    partition-spec structures) into a cache key's plan.  One
    implementation on purpose: the digest width/encoding is part of
    the key, so changing it is a cache-invalidation event that must
    happen in exactly one place."""
    return hashlib.sha256(repr(obj).encode("utf-8")).hexdigest()[:16]


def load(key: str):  # never-raises
    """The deserialized, loaded executable for `key`, or None (disabled
    / missing / corrupt / version-skewed / key-collided / any
    deserialization failure).  Never raises."""
    base = cache_dir()
    if base is None:
        return None
    path = _entry_path(base, key)
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
    except FileNotFoundError:
        return None
    except Exception:
        # truncated pickle, chmod surprise, poisoned bytes: all degrade
        # to a fresh compile (the chaos harness injects exactly this)
        _count("corrupt")
        return None
    try:
        if (
            not isinstance(entry, dict)
            or entry.get("v") != CACHE_VERSION
            or entry.get("key") != key  # digest collision or stale stamp
        ):
            _count("stale")
            return None
        from jax.experimental import serialize_executable as se

        return se.deserialize_and_load(
            entry["payload"], entry["in_tree"], entry["out_tree"]
        )
    except Exception as e:
        # e.g. jaxlib CPU "Symbols not found" for some fusion patterns
        # when an executable crosses processes: degrade to a fresh
        # compile.  Truncated message — the full symbol list is noise.
        _count("corrupt")
        log.info(
            "aot cache entry unloadable (%s): %s", path, str(e)[:160]
        )
        return None


def store(key: str, compiled) -> bool:  # never-raises
    """Serialize `compiled` under `key` (atomic tmp + os.replace).
    Returns True when written; any failure — an executable kind the
    backend cannot serialize (pallas custom calls on some runtimes),
    a full disk — logs and returns False, never raising into the
    evaluation that just compiled a perfectly good program."""
    base = cache_dir()
    if base is None:
        return False
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        entry = {
            "v": CACHE_VERSION,
            "key": key,
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
        }
        os.makedirs(base, exist_ok=True)
        path = _entry_path(base, key)
        fd, tmp = tempfile.mkstemp(dir=base, prefix=".aot-")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(entry, f)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        _count("store")
        return True
    except Exception as e:
        _count("unserializable")
        log.info("aot cache store failed for %s: %s", key[:120], e)
        return False


def _shared_get(base: str, key: str):
    """The memory tier's executable for `key` under directory `base`,
    made its most recent entry, or None."""
    with _SHARED_LOCK:
        compiled = _SHARED.pop((base, key), None)
        if compiled is not None:
            _SHARED[(base, key)] = compiled
    return compiled


def _shared_put(base: str, key: str, compiled) -> None:
    """Keep `compiled` as the most recent entry and drop the oldest
    beyond SHARED_MAX (an AotProgram that still holds a dropped
    executable in its own `_programs` keeps it alive)."""
    with _SHARED_LOCK:
        _SHARED.pop((base, key), None)
        while len(_SHARED) >= SHARED_MAX:
            del _SHARED[next(iter(_SHARED))]
        _SHARED[(base, key)] = compiled


def _shared_drop(base: str, key: str) -> None:
    """A loaded executable the runtime rejected is not handed on."""
    with _SHARED_LOCK:
        _SHARED.pop((base, key), None)


def forget() -> None:
    """Empty the memory tier: the next resolve of every key goes back
    to the directory.  For tests that stand for a new process, and for
    a caller that replaced the directory's contents under a live one.
    Executables that an AotProgram has resolved stay with it."""
    with _SHARED_LOCK:
        _SHARED.clear()


def _count(outcome: str) -> None:
    from ..telemetry import instruments as ti

    ti.AOT_CACHE.inc(outcome=outcome)


def counters() -> Dict[str, Any]:
    """The per-process AOT cache forensics (serve's prewarm report and
    the restart tests read them): hits (executables adopted from disk —
    `adopted` aliases it for the acceptance schema), shared (resolved
    in the memory tier: loaded earlier by this process), misses,
    stores, and fresh compiles actually paid (the restart gate's flat
    line)."""
    from ..telemetry import instruments as ti

    return {
        "hits": int(ti.AOT_CACHE.value(outcome="hit")),
        "shared": int(ti.AOT_CACHE.value(outcome="shared")),
        "misses": int(ti.AOT_CACHE.value(outcome="miss")),
        "adopted": int(ti.AOT_CACHE.value(outcome="hit")),
        "stores": int(ti.AOT_CACHE.value(outcome="store")),
        "corrupt": int(ti.AOT_CACHE.value(outcome="corrupt")),
        "compiles": int(ti.AOT_COMPILES.value()),
        "dir": cache_dir(),
    }


def _leaf_sig(leaf) -> Tuple:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(int(d) for d in shape), str(dtype))
    # non-array leaf (None never reaches here — it is a pytree node):
    # a python scalar lowers as a weak-typed literal, so its TYPE is
    # part of the program identity but its value is not
    return ("p", type(leaf).__name__)


def call_key(args: tuple, kwargs: dict):
    """Hashable shape/dtype key of a call's argument pytree — the
    per-dispatch fast path (a treedef + leaf-sig tuple; no string
    building on the hot path).  `signature_string` renders it for the
    persisted key only when a call actually needs resolving."""
    from jax import tree_util as jtu

    leaves, treedef = jtu.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_sig(x) for x in leaves))


def signature_string(key) -> str:
    """The stable string form of a call_key — the shape-bucket half of
    the persisted cache key."""
    treedef, leaf_sigs = key
    return json.dumps(
        [str(treedef)] + [list(s) for s in leaf_sigs],
        separators=(",", ":"),
    )


class AotProgram:
    """Wrap a jitted callable with the persistent executable cache.

    On the first call per argument signature: take the executable the
    PROCESS already holds under the same key (the memory tier: another
    wrapper, usually an earlier engine's, adopted or built it), else
    try to ADOPT a serialized executable (zero trace, zero compile),
    else lower+compile via the wrapped jit (counted in AOT_COMPILES)
    and persist the result; what the last two yield goes into the
    memory tier.  Later calls with the same signature dispatch the
    resolved executable directly.  A file that changes under a live
    process is not looked at again for a key the process holds, until
    `forget()`.  Any failure anywhere — an unserializable
    program, a runtime that rejects the AOT path, statics the lowering
    chokes on — pins a per-signature FALLBACK to the plain jitted
    callable, so the wrapper can never be less robust than the jit it
    wraps.

    Not thread-safe by design: engines issue evaluations from one
    thread at a time (api.py threading model), and the abandoned-
    autotune orphan only ever calls through programs resolved earlier
    on the issuing thread (dict reads are atomic under the GIL; the
    worst interleaving resolves the same signature twice, both valid).
    The memory tier is shared by every thread's engines under its own
    lock, held for the bookkeeping alone: one key may be loaded twice,
    and the last is kept.
    """

    def __init__(
        self,
        name: str,
        jitted,
        *,
        plan: str = "",
        schedule: str = "single",
        static_argnames: Tuple[str, ...] = (),
    ):
        self._name = name
        self._jitted = jitted
        self._plan = plan
        self._schedule = schedule
        self._static_argnames = tuple(static_argnames)
        if cachekeys.ACTIVE:
            # the key-mutation harness (tests/keyharness.py) proves
            # each component miss-on-mutate; the fingerprint is the
            # persisted key with the per-call signature left symbolic
            cachekeys.register(
                f"aot:{name}",
                kind="persisted",
                components=cachekeys.program(
                    "name", "signature", "platform", "schedule", "plan"
                ),
                fingerprint=make_key(
                    name, "<signature>", schedule=schedule, plan=plan
                ),
            )
        # (call_key, statics) -> compiled | None(=fallback); keyed by
        # the hashable tuple so steady-state dispatches never build a
        # signature string
        self._programs: Dict[Any, Any] = {}

    def _cache_size(self) -> int:
        """Trace-cache size of the wrapped jit — the zero-recompile
        elastic-resize gates read this through the program caches.
        Adopted executables never trace, so they never count."""
        return self._jitted._cache_size()

    def _program(self, args, kwargs):
        """(key, dynamic kwargs, the executable or None=fallback) for
        this call's signature, obtaining the executable first where the
        signature is new: span `engine.program`, attr `how` = shared
        (from the memory tier), adopted (from the persistent cache),
        built (lowered and compiled here) or fallback."""
        statics = tuple(
            (k, kwargs[k]) for k in self._static_argnames if k in kwargs
        )
        dyn_kwargs = {
            k: v for k, v in kwargs.items() if k not in self._static_argnames
        }
        key = (call_key(args, dyn_kwargs), statics)
        if key not in self._programs:
            with span("engine.program", program=self._name) as sp:
                self._programs[key] = self._resolve(
                    self._persisted_key(key), args, kwargs, sp
                )
        return key, dyn_kwargs, self._programs[key]

    def resolve(self, *args, **kwargs) -> None:
        """Obtain the executable for these arguments without running it,
        so that a caller can time getting the program apart from the
        dispatch that follows.  With the persistent cache off there is
        nothing to obtain: the plain jit traces at its first call."""
        if cache_dir() is not None:
            self._program(args, kwargs)

    def __call__(self, *args, **kwargs):
        if cache_dir() is None:
            return self._jitted(*args, **kwargs)
        key, dyn_kwargs, compiled = self._program(args, kwargs)
        if compiled is None:
            return self._jitted(*args, **kwargs)
        try:
            return compiled(*args, **dyn_kwargs)
        except Exception:
            # a loaded executable the runtime rejects at CALL time
            # (device moved, donation mismatch): fall back for good,
            # and do not hand it to the next engine either
            _count("call_fallback")
            self._programs[key] = None
            _shared_drop(cache_dir(), self._persisted_key(key))
            return self._jitted(*args, **kwargs)

    def _persisted_key(self, key) -> str:
        """The key string of the file, and of the memory tier's entry,
        for one `_programs` key."""
        sig = signature_string(key[0]) + "|" + repr(key[1])
        return make_key(
            self._name, sig, schedule=self._schedule, plan=self._plan
        )

    def _resolve(self, key: str, args, kwargs, sp):
        from ..telemetry import instruments as ti

        base = cache_dir()
        compiled = _shared_get(base, key)
        if compiled is not None:
            ti.AOT_CACHE.inc(outcome="shared")
            sp.set(how="shared")
            return compiled
        try:
            compiled = load(key)
        except Exception:  # belt and braces: load already never raises
            compiled = None
        if compiled is not None:
            ti.AOT_CACHE.inc(outcome="hit")
            sp.set(how="adopted")
            _shared_put(base, key, compiled)
            return compiled
        ti.AOT_CACHE.inc(outcome="miss")
        sp.set(how="built")
        try:
            compiled = self._jitted.lower(*args, **kwargs).compile()
            ti.AOT_COMPILES.inc()
        except Exception as e:
            # lowering surprises (unsupported statics, tracer leaks in
            # exotic paths) must not break evaluation: plain jit from
            # here on for this signature
            log.info("aot lower/compile fallback for %s: %s", self._name, e)
            ti.AOT_CACHE.inc(outcome="fallback")
            sp.set(how="fallback")
            return None
        store(key, compiled)
        # whether or not it could be serialised: the next engine need
        # not read back what this one just wrote
        _shared_put(base, key, compiled)
        return compiled
