"""Test harness: run JAX on a virtual 8-device CPU mesh.

Real-TPU runs are exercised separately by the driver; tests must be
hermetic and exercise the multi-device sharding paths, so force the CPU
backend with 8 virtual devices BEFORE jax initializes.
"""

import os

# Force CPU even when the session environment preselects a TPU
# platform: tests must be hermetic and multi-device, and a chip
# belongs to one process at a time.
# hermetic telemetry: a driver-level LGBM_TPU_TELEMETRY must not make
# every training test append to a shared trace file
os.environ.pop("LGBM_TPU_TELEMETRY", None)
# hermetic fault injection: an ambient LGBM_TPU_FAULTS spec would fire
# inside arbitrary training tests (robustness tests install their own
# plans programmatically)
os.environ.pop("LGBM_TPU_FAULTS", None)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# cap CPU codegen at AVX2: XLA's host-feature detection in this VM
# reports ISA extensions (AVX512/AMX families) the host cannot actually
# execute, and the generated code then dies with SIGILL/SIGSEGV inside
# backend_compile_and_load on big programs. AVX2 is universally safe.
if "xla_cpu_max_isa" not in flags:
    flags = (flags + " --xla_cpu_max_isa=AVX2").strip()
os.environ["XLA_FLAGS"] = flags
os.environ.setdefault("JAX_ENABLE_X64", "0")
# NO persistent compile cache for the CPU suite: XLA:CPU AOT cache
# entries embed a target-machine feature set that does not reliably
# match the execution host in this sandbox, and LOADING such an entry
# can segfault outright (observed: SIGSEGV inside
# compilation_cache.get_executable_and_time after cpu_aot_loader
# "machine type ... doesn't match" warnings). Slower reruns beat a
# flaky suite. The TPU path keeps its own cache (.jax_cache_tpu) — a
# different backend, unaffected. With the variable gone before jax is
# imported, utils/compile_cache.py resolves no directory on the CPU
# backend, so nothing here has to touch jax's cache config.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

_last_module = [None]


@pytest.fixture(autouse=True)
def _clear_jax_caches_between_modules(request):
    """Full-suite runs accumulate hundreds of compiled XLA:CPU
    executables in-process; on this sandbox's jaxlib the NEXT large
    compile can then segfault inside backend_compile_and_load
    (reproducible at tests/test_training.py after ~200 tests; the same
    file passes solo). Dropping compiled programs at module boundaries
    keeps the live-executable footprint bounded."""
    mod = request.module.__name__
    if _last_module[0] is not None and _last_module[0] != mod:
        jax.clear_caches()
    _last_module[0] = mod
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 budgeted run (-m 'not slow')")


@pytest.fixture(scope="session", autouse=True)
def _no_session_thread_leaks():
    """No non-daemon thread born during the suite may outlive it: an
    engine whose stop()/shutdown() forgets a join shows up here as a
    hard failure naming the thread, instead of as a hanging pytest
    process (graftsync GS301; docs/StaticAnalysis.md)."""
    import threading
    import time
    before = set(threading.enumerate())
    yield
    deadline = time.monotonic() + 5.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive() and not t.daemon]
        if not leaked or time.monotonic() >= deadline:
            break
        time.sleep(0.05)
    assert not leaked, (
        "non-daemon thread(s) outlived the test session: "
        + ", ".join(t.name for t in leaked)
        + " — some stop()/shutdown() is missing a join "
          "(graftsync GS301)")
