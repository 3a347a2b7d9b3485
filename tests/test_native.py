"""Native runtime tests: C queue/histogram, ctypes seam, Python fallback.

The native library mirrors the reference's graceful-degradation stance
(container/container.go:55-126): every consumer must behave identically
with GOFR_NATIVE=0, so each behavior is asserted against both backends.
"""

import threading
import time

import pytest

from gofr_tpu import native
from gofr_tpu.tpu.batcher import BatcherClosed, CoalescingBatcher


def test_native_builds_and_loads():
    assert native.available(), "toolchain present in CI image — must build"


def test_native_queue_flush_on_full_batch():
    q = native.NativeBatchQueue(4, max_delay=5.0)  # long deadline: size-triggered
    for i in range(4):
        q.push(i)
    t0 = time.monotonic()
    ids, wait = q.pop_batch()
    assert ids == [0, 1, 2, 3]
    assert time.monotonic() - t0 < 1.0  # did not wait for the deadline
    q.close()


def test_native_queue_flush_on_deadline():
    q = native.NativeBatchQueue(64, max_delay=0.02)
    q.push(7)
    t0 = time.monotonic()
    ids, wait = q.pop_batch()
    took = time.monotonic() - t0
    assert ids == [7]
    assert wait >= 0.015 and took < 1.0
    q.close()


def test_native_queue_close_drains_then_returns_empty():
    q = native.NativeBatchQueue(8, 0.5)
    for i in range(3):
        q.push(i)
    q.close()
    assert q.pop_batch()[0] == [0, 1, 2]
    assert q.pop_batch()[0] == []
    assert q.push(9) is False


def test_native_queue_mpmc_under_contention():
    q = native.NativeBatchQueue(16, 0.001)
    got, lock = [], threading.Lock()

    def popper():
        while True:
            ids, _ = q.pop_batch()
            if not ids:
                return
            with lock:
                got.extend(ids)

    popper_t = threading.Thread(target=popper)
    popper_t.start()
    pushers = [threading.Thread(target=lambda lo=lo: [q.push(lo * 250 + i)
                                                      for i in range(250)])
               for lo in range(4)]
    for t in pushers:
        t.start()
    for t in pushers:
        t.join()
    deadline = time.monotonic() + 5.0
    while len(q) and time.monotonic() < deadline:
        time.sleep(0.005)
    q.close()
    popper_t.join(timeout=5.0)
    assert sorted(got) == list(range(1000))


def test_native_histogram_counts_and_sum():
    h = native.NativeHistogram((0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0, 0.5):
        h.record(v)
    counts, total, count = h.snapshot()
    assert counts == [1, 2, 1, 1]  # per-bucket incl. +inf
    assert count == 5
    assert abs(total - 56.05) < 1e-9


def test_native_histogram_concurrent_records():
    h = native.NativeHistogram((0.5,))
    def worker():
        for _ in range(10_000):
            h.record(0.25)
    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    counts, total, count = h.snapshot()
    assert count == 40_000 and counts[0] == 40_000
    assert abs(total - 10_000.0) < 1e-6


def test_native_histogram_record_keeps_the_interpreter_lock():
    """The wait-free record call is bound through PyDLL: a CDLL call
    would hand the interpreter lock to any thread the recording loop
    had just woken, once a sample. The calls that can block or copy
    (snapshot, the queue) still let go of it."""
    import ctypes

    lib = native.load()
    holds_lock = ctypes.PyDLL._func_flags_ & ~ctypes.CDLL._func_flags_
    assert type(lib.hist_record)._flags_ & holds_lock
    assert not type(lib.hist_snapshot)._flags_ & holds_lock
    assert not type(lib.gq_pop_batch)._flags_ & holds_lock


@pytest.mark.parametrize("use_native", [True, False])
def test_batcher_backends_equivalent(use_native):
    seen = []

    def runner(items):
        seen.append(len(items))
        return [x + 100 for x in items]

    b = CoalescingBatcher(runner, max_batch=8, max_delay=0.01,
                          use_native=use_native)
    if use_native:
        assert b._native is not None
    results = [None] * 24
    def worker(i):
        results[i] = b.submit(i)
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(24)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results == [i + 100 for i in range(24)]
    assert all(s <= 8 for s in seen)
    b.close()
    with pytest.raises(BatcherClosed):
        b.submit(1)


def test_metrics_native_histogram_renders_cumulative():
    from gofr_tpu.metrics import Manager

    m = Manager()
    m.new_histogram("t_hist", "test", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        m.record_histogram("t_hist", v, route="/x")
    text = m.render_prometheus()
    assert 't_hist_bucket{route="/x",le="0.1"} 1' in text
    assert 't_hist_bucket{route="/x",le="1"} 2' in text
    assert 't_hist_bucket{route="/x",le="+Inf"} 3' in text
    assert 't_hist_count{route="/x"} 3' in text
    assert "t_hist_sum" in text
