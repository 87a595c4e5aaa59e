"""Persisted counts-kernel autotune cache (docs/DESIGN.md "Bit-packed
kernel").

The engine's on-device autotune (api._autotune_slab / _autotune_packed)
times candidate kernels from the pinned precompute and keeps the winner
for the engine's life.  That search costs real wall-clock on every fresh
process — candidate compiles plus min-of-N timed rounds — for an answer
that is a pure function of (shape bucket, mesh, dtype plan).  This
module persists the winner to disk under exactly that key, so a
restarted process ADOPTS the tuned configuration with zero candidate
search (asserted via the AUTOTUNE_SEARCHES counter in
tests/test_engine_packed.py).

Robustness contract: the cache is advisory.  A corrupt, truncated,
version-skewed, or otherwise surprising file degrades to a fresh search
— load_winner never raises — and a failed write is a logged warning,
never an error.  Writes are atomic (tmp + os.replace) and
read-merge-write so concurrent processes tuning different buckets don't
clobber each other (last writer wins per key, which is fine: both wrote
a measured winner).

CYCLONUS_AUTOTUNE_CACHE: cache file path; "0"/"" disables persistence
entirely (the test suite default — tests/conftest.py — so suites never
share state through the checkout's cache); unset -> `autotune.json`
under the engine's cache_root() ($JAX_COMPILATION_CACHE_DIR, else the
checkout's fixed .cache/jax).
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
from typing import Any, Dict, Optional

from ..utils import cachekeys

log = logging.getLogger(__name__)

#: bump when the entry layout or the meaning of a winner changes: stale
#: versions are ignored (fresh search), never migrated
CACHE_VERSION = 1

#: winner kernels a persisted entry may name; anything else is treated
#: as corrupt (a newer writer's kernel kinds must not crash an older
#: reader — it re-searches instead)
KNOWN_KERNELS = ("default", "slab", "packed")

def cache_path() -> Optional[str]:  # never-raises
    """Resolved cache file path, or None when persistence is disabled."""
    raw = os.environ.get("CYCLONUS_AUTOTUNE_CACHE")
    if raw is None:
        from . import cache_root

        # cache_root is checked as never-raising in its own module
        return os.path.join(cache_root(), "autotune.json")  # cachelint: ignore[CC005]
    raw = raw.strip()
    if raw in ("", "0"):
        return None
    return raw


def make_key(
    shape_bucket: Dict[str, Any], mesh: str, dtype_plan: str
) -> str:
    """Stable string key for one tuned configuration: the SHAPE BUCKET
    (the bucketed dims that select compiled programs — pod axis, target
    axes, case count, tiered/compressed flags), the MESH signature
    (backend + device kind + count), and the DTYPE PLAN (packed32 /
    int8 / bf16).  Two processes with equal keys run byte-identical
    candidate programs, which is what makes the winner transferable."""
    key = json.dumps(
        {"shape": shape_bucket, "mesh": mesh, "dtype": dtype_plan},
        sort_keys=True,
        separators=(",", ":"),
    )
    if cachekeys.ACTIVE:
        cachekeys.register(
            "autotune",
            kind="persisted",
            components=cachekeys.program(
                "shape_bucket", "mesh", "dtype_plan"
            ),
            fingerprint=key,
        )
    return key


def _read_all(path: str) -> Dict[str, Any]:  # never-raises
    """The whole cache file as a dict — {} on ANY problem (missing,
    truncated JSON, wrong top-level type, version skew).  The handler
    is deliberately BROAD: the old (OSError, ValueError) pair let a
    pathological entry escape the documented any-problem contract
    (e.g. RecursionError from absurd nesting) — found by
    tools/cachelint.py CC005."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}
    except Exception as e:
        log.debug("autotune cache unreadable (%s): %s", path, e)
        return {}
    if not isinstance(data, dict) or data.get("v") != CACHE_VERSION:
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def load_winner(key: str) -> Optional[Dict[str, Any]]:  # never-raises
    """The persisted winner for `key`, or None (disabled / missing /
    corrupt / stale / malformed entry).  Returns the winner dict
    ({"kernel": ..., optional "bs"/"bd", ...}); timings ride along under
    "timings" for forensics but are not re-validated."""
    path = cache_path()
    if path is None:
        return None
    entry = _read_all(path).get(key)
    if not isinstance(entry, dict):
        return None
    winner = entry.get("winner")
    if not isinstance(winner, dict) or winner.get("kernel") not in KNOWN_KERNELS:
        return None
    for dim in ("bs", "bd"):
        v = winner.get(dim)
        if v is not None and not isinstance(v, int):
            return None
    return winner


def store_winner(  # never-raises
    key: str, winner: Dict[str, Any], timings: Optional[Dict[str, Any]] = None
) -> bool:
    """Persist `winner` under `key` (read-merge-atomic-replace).
    Returns True when written; failures log and return False — a broken
    cache disk must never take down the engine that just finished a
    perfectly good search.  The handler is BROAD on purpose: the old
    `except OSError` let json.dump's TypeError on a non-serializable
    winner/timing value escape into the evaluation that just finished a
    perfectly good search, violating this very docstring (REAL bug
    surfaced by tools/cachelint.py CC005; regression-pinned in
    tests/test_cachelint.py)."""
    path = cache_path()
    if path is None:
        return False
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        entries = _read_all(path)
        entries[key] = {"winner": dict(winner), "timings": dict(timings or {})}
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path) or ".", prefix=".autotune-"
        )
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump({"v": CACHE_VERSION, "entries": entries}, f)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return True
    except Exception as e:
        log.warning("autotune cache write failed (%s): %s", path, e)
        return False
