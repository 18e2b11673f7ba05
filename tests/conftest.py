"""Test bootstrap: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's mock-K8s tier (SURVEY §4): multi-chip behavior is
validated on virtual devices; the chip path is proven by chip_smoke.py on a
TPU, and tests/test_tpu_compile.py asks the TPU compiler from here.
"""

import os

# The tests run on the CPU backend whatever the shell exports.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Lock-order recording (chaos CI step: LSTPU_LOCKORDER=1) must be armed
# BEFORE any langstream_tpu import so module-level locks (lifecycle,
# observability) are created through the tracking factory.
if os.environ.get("LSTPU_LOCKORDER") == "1":
    from langstream_tpu.analysis import lockorder as _lockorder

    _lockorder.activate()
else:
    _lockorder = None

import asyncio  # noqa: E402
import jax  # noqa: E402
import pytest  # noqa: E402

# CPU XLA's default matmul precision is bf16-level; correctness tests compare
# fp32 paths, so force true fp32 matmuls (TPU perf paths use bf16 on purpose).
jax.config.update("jax_default_matmul_precision", "highest")

from langstream_tpu.messaging.memory import MemoryBroker  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` under a hard 870 s timeout (ROADMAP.md);
    # slow-marked suites (2-process SPMD, engine-pair-heavy parity tests)
    # run in the chaos CI step and on demand instead
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (runs in the chaos CI step)"
    )


def pytest_sessionfinish(session, exitstatus):
    # the whole suite is ONE lock-order experiment: every inter-lock
    # acquisition edge observed across every test aggregates into a
    # single graph, and any cycle fails the session even when each
    # individual test passed (two tests can each exercise one half of
    # an inversion)
    if _lockorder is None:
        return
    rec = _lockorder.deactivate()
    if rec is None:
        return
    report = rec.report()
    if report:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        if tr is not None:
            tr.write_line("")
            tr.write_line(report, red=True)
        session.exitstatus = 1


def _memory_maps() -> tuple[int, int]:
    """(this process's memory mappings, the kernel's limit a process)."""
    try:
        with open("/proc/self/maps") as maps:
            held = sum(1 for _ in maps)
        with open("/proc/sys/vm/max_map_count") as limit:
            return held, int(limit.read())
    except (OSError, ValueError):  # no procfs: nothing to watch
        return 0, 1


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Every compiled program a worker keeps in JAX's caches holds a few
    memory mappings (its JIT code), a worker runs many test files, and the
    kernel caps a process's mappings (`vm.max_map_count`, 65,530 by default):
    past it `mmap` fails inside XLA's compile and the worker dies with a
    segmentation fault or an abort, in whatever test compiles next (seen at
    some 1,770 tests, two whole runs out of two; `jax.clear_caches()` gives
    the mappings back). After a test file, a worker that holds over a third
    of the limit drops its caches: the next file compiles what it needs."""
    yield
    held, limit = _memory_maps()
    if 3 * held > limit:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """Building an engine through tpu-serving turns JAX's persistent compile
    cache on for the whole process (engine.enable_persistent_compile_cache).
    Put the setting back after each test, so that one test's cache directory
    never serves — or collects — the next test's compiles."""
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
    )
    before = [getattr(jax.config, n) for n in names]
    yield
    if [getattr(jax.config, n) for n in names] != before:
        from jax.experimental.compilation_cache import compilation_cache as cc

        for name, value in zip(names, before):
            jax.config.update(name, value)
        cc.reset_cache()


@pytest.fixture(autouse=True)
def _reset_memory_broker():
    MemoryBroker.reset()
    yield
    MemoryBroker.reset()


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop."""

    def _run(coro):
        return asyncio.run(coro)

    return _run
