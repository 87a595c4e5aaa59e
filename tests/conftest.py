"""Test configuration: force JAX onto a virtual 8-device CPU mesh so the
multi-chip sharding paths are exercised without TPU hardware.

Must run before the first backend initialization anywhere in the test
session.  The pin is double: JAX_PLATFORMS=cpu for the subprocesses the
suite spawns (they inherit the environment), and the config-level
update below for this process, which holds whatever the environment
says.  Set CYCLONUS_TEST_TPU=1 to deliberately run the suite against
the real default backend instead."""

import atexit
import os
import shutil
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the persisted autotune cache (engine/autotune.py) defaults to a file
# under the checkout's compile-cache root; the suite must never share
# tuned winners across tests or with the developer's real cache — tests
# that exercise persistence point this at a tmp_path explicitly
os.environ.setdefault("CYCLONUS_AUTOTUNE_CACHE", "0")
# same discipline for the persistent AOT executable cache
# (engine/aot_cache.py): unrelated tests must never adopt executables
# from — or leak them into — the checkout's cache; the restart-contract
# tests point it at a tmp_path explicitly
os.environ.setdefault("CYCLONUS_AOT_CACHE", "0")
# and for JAX's own persistent cache (engine.ensure_persistent_compile_
# cache), which defaults to the checkout's .cache/jax: a test session
# (the xdist workers inherit the controller's environment, and the
# children the tests spawn theirs) compiles into a directory of its own
# that goes when the session does.  Nothing the suite compiles may land
# in the checkout: test_chip_smoke.py holds the checkout's cache to
# "unchanged" while the other workers run
if not (
    os.environ.get("CYCLONUS_JAX_CACHE")
    or os.environ.get("JAX_COMPILATION_CACHE_DIR")
):
    _jax_cache = tempfile.mkdtemp(prefix="cyclonus-test-jax-")
    os.environ["CYCLONUS_JAX_CACHE"] = _jax_cache
    atexit.register(shutil.rmtree, _jax_cache, ignore_errors=True)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

if os.environ.get("CYCLONUS_TEST_TPU", "") != "1":
    import jax

    jax.config.update("jax_platforms", "cpu")
