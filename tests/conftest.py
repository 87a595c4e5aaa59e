"""Test configuration: force JAX onto a virtual 8-device CPU mesh so the
multi-chip sharding paths are exercised without TPU hardware.

Must run before the first backend initialization anywhere in the test
session.  The pin is double: JAX_PLATFORMS=cpu for the subprocesses the
suite spawns (they inherit the environment), and the config-level
update below for this process, which holds whatever the environment
says.  Set CYCLONUS_TEST_TPU=1 to deliberately run the suite against
the real default backend instead."""

import os

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
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

if os.environ.get("CYCLONUS_TEST_TPU", "") != "1":
    import jax

    jax.config.update("jax_platforms", "cpu")
