"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh so multi-chip
sharding logic is exercised hermetically (the driver does the same for
dryrun_multichip). The overrides run before first backend use, so a plain
``pytest`` on a machine that holds a TPU still tests on the CPU.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# Tests validate numerics: use exact f32 matmuls. Production keeps the
# platform default (bf16 passes on the MXU), which is what we want on TPU.
jax.config.update("jax_default_matmul_precision", "float32")
# Persistent compile cache: the mmap-guard fixture below drops
# executables at module boundaries, so identical programs recompile
# across modules (and across repeated suite runs); the disk cache turns
# those into loads. Keyed by backend+topology+program, so the virtual
# 8-device CPU mesh caches independently of TPU runs.
from gofr_tpu.compile_cache import configure as _configure_compile_cache

_configure_compile_cache()

assert jax.devices()[0].platform == "cpu", "tests must run on the CPU backend"
assert len(jax.devices()) == 8, "tests expect a virtual 8-device CPU mesh"


import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--lockwatch", action="store_true", default=False,
        help="instrument threading.Lock/RLock with the lock-order "
             "watchdog (gofr_tpu.testutil.lockwatch) and fail the "
             "session on any observed order inversion — this repo's "
             "`go test -race`")
    parser.addoption(
        "--hbmwatch", action="store_true", default=False,
        help="snapshot live device bytes around every test "
             "(gofr_tpu.testutil.hbmwatch over jax.live_arrays + the "
             "hbm accounting registry); print per-test leak deltas "
             "and fail the session on retained growth — the memory "
             "sibling of --lockwatch")
    parser.addoption(
        "--chaoswatch", action="store_true", default=False,
        help="count ChaosSchedule.fire traffic per declared chaos "
             "seam (gofr_tpu.testutil.chaoswatch); print the per-seam "
             "fire/injection table and fail the session if any "
             "chaos.SEAMS entry never fired — the fault-injection "
             "sibling of --lockwatch/--hbmwatch")


def pytest_configure(config):
    if config.getoption("--lockwatch"):
        from gofr_tpu.testutil.lockwatch import LockWatch

        watch = LockWatch(name="pytest-session")
        watch.install()
        config._lockwatch = watch
    from gofr_tpu.testutil import chaoswatch as chaoswatch_mod
    from gofr_tpu.testutil import hbmwatch as hbmwatch_mod

    hbmwatch_mod.install_session_watch(config)
    chaoswatch_mod.install_session_watch(config)


@pytest.fixture
def hbmwatch():
    """A fresh HBMWatch for steady-state leak assertions
    (assert_flat: N warmups, then live device bytes must stay flat).
    Independent of --hbmwatch: regression tests always assert."""
    from gofr_tpu.testutil.hbmwatch import HBMWatch

    return HBMWatch("fixture")


def pytest_unconfigure(config):
    watch = getattr(config, "_lockwatch", None)
    if watch is not None:
        watch.uninstall()


# the longest tier-1 test takes 75 s of a loaded run (PERF.md, Findings PR 41)
TEST_TIME_LIMIT_S = 300


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    """Every test's time limit, set-up and teardown included: past it
    the interpreter writes every thread's stack to the run's stderr and
    ends the process (``faulthandler``'s own thread, which needs no
    GIL). Under xdist that is a crashed worker: the test is reported
    failed by name, a new worker takes the rest of its batch and the
    run ends. Without it a test that waits for ever (a consumer on a
    queue whose producer is stuck in a device dispatch) holds its
    worker's whole batch until the run's own limit cuts it, and the run
    then names nothing."""
    import faulthandler

    from _pytest.faulthandler import fault_handler_stderr_fd_key

    # pytest's copy of the real stderr: fd 2 is the capture's file here
    fd = item.config.stash.get(fault_handler_stderr_fd_key, None)
    faulthandler.dump_traceback_later(
        TEST_TIME_LIMIT_S, exit=True,
        file=fd if fd is not None else sys.__stderr__)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


def _memory_maps() -> tuple[int, int]:
    """(memory maps this process holds, the most it may)."""
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
        with open("/proc/sys/vm/max_map_count") as f:
            return n_maps, int(f.read())
    except OSError:
        return 0, 1 << 31


def _engine_threads_alive() -> bool:
    import threading

    return any(t.name == "gofr-tpu-gen" and t.is_alive()
               for t in threading.enumerate())


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables_between_modules():
    """Cap the process's memory-map count. Every compiled XLA executable
    holds mmap'd code; the suite compiles thousands of programs and the
    map count grows ~1.5k/min toward vm.max_map_count (65530 here) —
    past it, mmap fails inside the compiler and the process SEGFAULTS
    (observed twice at ~90% of the full suite, always inside
    backend_compile_and_load, never reproducible solo). Dropping the
    jit caches at module boundaries frees executables whose owners
    (closed engines, module-scoped models) are gone; the cost is
    cross-module recompiles, which are rare since shapes differ per
    module anyway."""
    yield
    import gc
    import time

    # Clear ONLY under real map-count pressure: on boxes with an
    # effectively unlimited vm.max_map_count the guard buys nothing,
    # while jax.clear_caches() itself is the hazard — on jaxlib 0.4.x
    # it segfaults nondeterministically inside weakref-cache clearing
    # after engine-heavy modules (observed reliably after test_paged,
    # test_examples). Where the cap is real (the 65530 box this guard
    # was written for) a third of it leaves an engine module (35,000
    # maps a worker at the worst: PR 47 lost a worker twice in
    # test_laguna with the threshold at half) room to its end.
    n_maps, cap = _memory_maps()
    if n_maps < cap // 3:
        return

    # A gofr-tpu-gen loop thread may still be winding down INSIDE a
    # device dispatch (engine close() joins with a 10 s timeout; a chunk
    # compile can exceed it). clear_caches() would free the executable
    # out from under that running dispatch — drain those threads first,
    # compile-sized bound, like pytest_sessionfinish below.
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline and _engine_threads_alive():
        time.sleep(0.2)

    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _release_compiled_executables_inside_a_module():
    """The same guard after every test, at half the limit, for a module
    whose own programs outgrow what the module boundary left it: only
    while no engine's loop thread lives (a module-scoped engine holds
    its programs until the module ends, and may be inside a dispatch)."""
    yield
    n_maps, cap = _memory_maps()
    if n_maps >= cap // 2 and not _engine_threads_alive():
        import gc

        jax.clear_caches()
        gc.collect()


def pytest_sessionfinish(session, exitstatus):
    """Fail loudly on (a) lock-order inversions observed by a
    --lockwatch run and (b) leaked worker threads (VERDICT r3 weak #6: a
    circuit breaker outlived its server and health-probed a dead port
    every 5 s after `314 passed`). Every framework thread — engine
    loops, breaker probes, JWKS refreshers, pollers — is named and must
    be stopped by its owner's close()/stop(); grace period covers
    threads mid-teardown."""
    import time

    failures = []
    watch = getattr(session.config, "_lockwatch", None)
    if watch is not None:
        s = watch.summary()
        print(f"\nlockwatch: {s['acquisitions']} acquisitions, "  # noqa: T201
              f"{s['sites']} lock sites, {s['edges']} order edges, "
              f"{len(s['violations'])} inversion(s)")
        # collect, don't raise yet: an inversion must not mask the
        # leaked-thread gate below — both checks always run
        try:
            watch.check()
        except AssertionError as exc:
            failures.append(str(exc))

    from gofr_tpu.testutil import framework_threads as suspects

    def drained() -> bool:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if not suspects():
                return True
            time.sleep(0.2)
        # A gofr-tpu-gen loop thread can legitimately outlive close()'s
        # join: it may be BLOCKED inside a device dispatch (a
        # chunk-program compile takes 30-60 s on the virtual CPU mesh)
        # and exits as soon as the dispatch returns — that is
        # winding-down, not a leak. Give only those threads a
        # compile-sized drain before failing.
        if all(t.name == "gofr-tpu-gen" for t in suspects()):
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if not suspects():
                    return True
                time.sleep(1.0)
        return False

    if not drained():
        names = sorted(t.name for t in suspects())
        failures.append(
            f"leaked framework threads after test session: {names}")
    if failures:
        raise RuntimeError("\n\n".join(failures))
