"""Behavioral regressions for the GL001/GL101 findings fixed in this
PR. tests/test_gofrlint.py::test_fixed_module_stays_clean keeps each
module analyzer-clean; these pin the RUNTIME contract the fixes bought:

  - batcher: the failed-native-push reap holds the batcher lock (the
    close() iteration over _items must never see a concurrent pop);
  - wire: SocketWriter.deferred is only ever written under _blk, on
    both nonblocking park paths;
  - grpcx client: close() flips _closed under _lock, like every other
    writer (_teardown);
  - kvcache: model_fingerprint syncs the host ONCE (a batched
    device_get over all sampled leaves), not once per leaf.
"""

import socket
import threading
from types import SimpleNamespace

import pytest


# -- batcher: reap-on-failed-push under the lock -----------------------------

def test_batcher_failed_native_push_reaps_under_lock():
    from gofr_tpu.tpu.batcher import BatcherClosed, CoalescingBatcher

    b = CoalescingBatcher(lambda items: items, max_batch=2,
                          max_delay=0.001, name="reg-batcher",
                          use_native=False)
    try:
        class RejectingNative:
            """Native queue already closed: every push bounces."""

            def __len__(self):
                return 0

            def push(self, item_id):
                return False

            def close(self):
                pass

        lock = b._lock

        class AssertingItems(dict):
            def pop(self, *a):
                assert lock.locked(), \
                    "reap of a failed push must hold the batcher lock"
                return dict.pop(self, *a)

        b._native = RejectingNative()
        b._items = AssertingItems()
        with pytest.raises(BatcherClosed):
            b.submit("x")
        assert not b._items, "failed push left its item in _items"
    finally:
        b._native = None
        b.close()


# -- wire: deferred counter writes stay under _blk ---------------------------

def _asserting_writer(sock):
    from gofr_tpu.wire import SocketWriter

    class W(SocketWriter):
        def __setattr__(self, name, value):
            if name == "deferred" and getattr(self, "_ctor_done", False):
                assert self._blk.locked(), \
                    "deferred must only be written under _blk"
            object.__setattr__(self, name, value)

    w = W(sock)
    w._ctor_done = True
    return w


def test_socketwriter_wouldblock_park_counts_deferred_under_blk():
    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
        w = _asserting_writer(a)
        # nothing read from peer: a large nonblocking write must park a
        # tail and count exactly one deferral (under _blk, asserted)
        ok = w.write(b"x" * 1_000_000, block=False)
        assert ok is False
        assert w.deferred == 1
        assert len(w._backlog) > 0
    finally:
        a.close()
        b.close()


def test_socketwriter_contended_park_counts_deferred_under_blk():
    a, b = socket.socketpair()
    try:
        w = _asserting_writer(a)
        w._lock.acquire()  # simulate another thread mid-send
        try:
            got = []
            t = threading.Thread(
                target=lambda: got.append(w.write(b"parked", block=False)),
                name="parker")
            t.start()
            t.join(5)
            assert not t.is_alive()
            # the writer that holds the socket sweeps the backlog when
            # it lets go (tests/test_transport_fast.py holds that), so
            # the parked write asks nothing of its caller
            assert got == [True]
            assert w.deferred == 1
            assert bytes(w._backlog) == b"parked"
        finally:
            w._lock.release()
        assert w.write(b"", block=True)  # drains the backlog
        assert b.recv(64) == b"parked"
    finally:
        a.close()
        b.close()


# -- grpcx client: close() flips _closed under _lock -------------------------

def test_grpc_channel_close_flips_closed_under_lock():
    from gofr_tpu.grpcx.client import GRPCChannel

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    conns = []

    def accept():
        try:
            conn, _ = srv.accept()
            conns.append(conn)
            while conn.recv(65536):
                pass
        except OSError:
            pass

    t = threading.Thread(target=accept, name="dumb-server", daemon=True)
    t.start()
    ch = GRPCChannel("127.0.0.1", srv.getsockname()[1], connect_timeout=2)
    try:
        flips = []
        inner = ch._lock

        class Snoop:
            def __enter__(self):
                inner.acquire()
                self._entry = ch._closed
                return self

            def __exit__(self, *exc):
                if ch._closed != self._entry:
                    flips.append(True)
                inner.release()

            def acquire(self, *a, **k):
                return self.__enter__() and True

            def release(self):
                self.__exit__()

        ch._lock = Snoop()
        ch.close()
        assert ch._closed is True
        assert flips, "_closed was flipped without holding _lock"
    finally:
        ch._lock = inner
        srv.close()
        for c in conns:
            c.close()
        t.join(5)


# -- kvcache: model_fingerprint is one batched transfer ----------------------

def _tiny_cfg():
    return SimpleNamespace(name="reg", vocab_size=32, dim=8, n_layers=2,
                           n_heads=2, n_kv_heads=2, head_dim=4,
                           rope_theta=10000.0)


def test_model_fingerprint_single_batched_device_get(monkeypatch):
    import jax
    import jax.numpy as jnp

    from gofr_tpu.tpu.kvcache import model_fingerprint

    params = {f"layer{i}": jnp.full((4, 4), float(i)) for i in range(6)}
    calls = []
    real = jax.device_get

    def counting(tree):
        calls.append(tree)
        return real(tree)

    monkeypatch.setattr(jax, "device_get", counting)
    fp = model_fingerprint(_tiny_cfg(), params)
    assert len(calls) == 1, \
        f"{len(calls)} host syncs for one fingerprint (want 1, batched)"
    assert isinstance(calls[0], list) and len(calls[0]) >= 2

    # and the batching must not have changed what is hashed: weights
    # still differentiate, config still differentiates
    assert fp == model_fingerprint(_tiny_cfg(), params)
    other = dict(params, layer0=jnp.full((4, 4), 99.0))
    assert fp != model_fingerprint(_tiny_cfg(), other)
    assert fp != model_fingerprint(_tiny_cfg(), None)
