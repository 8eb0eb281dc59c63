"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's DistributedQueryRunner trick (N workers in one JVM,
testing/trino-testing/.../DistributedQueryRunner.java:84): N logical TPU
workers are N XLA host devices in one process.  The chip is driven by
chip_smoke.py and benchmark/run.py, one process per chip — never by the
tests.
"""

import os

# Must be set before jax initializes its backends.  FORCE cpu: on a machine
# with a chip the default backend is the TPU, which tests must never take.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# Persistent compile cache: the suite's dominant cost is cold XLA compiles
# repeated per pytest process.  Placement is the program's one rule
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).  Disable
# with TRINO_TPU_NO_TEST_CACHE=1 (e.g. when bisecting compiler issues).
if os.environ.get("TRINO_TPU_NO_TEST_CACHE") != "1":
    from trino_tpu.parallel.spmd import configure_persistent_cache

    configure_persistent_cache()

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bounded_compile_caches():
    """Free live XLA executables at every module boundary.  Hundreds of
    accumulated executables have produced allocator-level segfaults late in
    the suite (first seen in test_tpcds, now guarded suite-wide); with the
    persistent disk cache above, re-entering a cleared program is a cheap
    reload, not a recompile."""
    yield
    jax.clear_caches()
    try:
        from trino_tpu.runtime.buffer_pool import POOL

        POOL.clear()
    except Exception:
        pass


@pytest.fixture(scope="session")
def devices():
    return jax.devices()
