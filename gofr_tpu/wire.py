"""Transport fast-path primitives shared by grpcx and the HTTP streamer.

Three building blocks behind the own-wire TTFT fix (ISSUE 2; the pure-
Python transport added ~142 ms on top of the engine path in the last
hardware capture):

  SocketWriter — vectored (``sendmsg``) frame writes with an ordered
      backlog, so a producer thread can hand bytes to the wire WITHOUT
      ever blocking on the socket or on another writer. One syscall
      carries many frames; a contended write parks in the backlog and
      leaves with the writer that holds the socket, a partial one rides
      out with the next write.

  Outbox — an ordered multi-producer send queue drained by whichever
      thread is available (thread-combining), never by a dedicated
      flusher thread. This is the write scheduler: bursts (a fused
      decode block delivering K tokens back-to-back) coalesce into one
      vectored write instead of K wakeups and K syscalls.

  burst / defer — a producer that pushes a run of items to many streams
      on one thread (a fused decode block: K tokens to every slot) says
      so with ``burst()``; a sink called inside may ``defer`` its send to
      the end of the run, so that a stream's K items leave in one write
      and not in K.

  PushStream / MappedStream — a queue-backed item stream with an
      optional zero-handoff *sink*: when a consumer registers one, the
      producing thread delivers items straight into the consumer's send
      path instead of waking a reader thread. GenStream (tpu/generator)
      extends PushStream, which is how first-token bytes go from the
      engine loop's ``_deliver`` to the socket without an intermediate
      thread.

Everything here is stdlib-only and transport-agnostic; grpcx frames and
HTTP chunked encoding both sit on top.
"""

from __future__ import annotations

import collections
import contextlib
import queue
import socket
import threading
import time

from .errors import ConnectionLost

# sendmsg buffer-list cap per syscall — far below any platform IOV_MAX
# (Linux: 1024) while keeping per-call bookkeeping bounded
_IOV_CAP = 64


class SocketWriter:
    """Vectored, backlog-capable socket writer.

    Guarantees:
      - wire byte order equals commit order: a write's bytes are
        committed (to the socket or the backlog) under the internal
        locks before the call returns;
      - ``write(..., block=False)`` NEVER blocks on the socket or on a
        concurrent writer — bytes that cannot leave immediately park in
        the backlog;
      - every blocking write drains the backlog ahead of its own bytes,
        so any stream that *ends* with a blocking write (gRPC trailers,
        the terminal HTTP chunk) leaves the wire fully flushed;
      - bytes parked behind a writer that holds the socket leave with
        that writer: it sweeps the backlog when it lets the socket go
        (``_sweep``). Only a FULL socket leaves bytes parked with no one
        to send them, and only then does a nonblocking write say False.
        (Until PR 43 a contended write said False too, and the gRPC
        sender that heard it sent the rest of its stream through its
        worker thread, whose blocking writes the engine's next writes
        then met: at 6,000 tokens a second five streams in six ended up
        there.)
    """

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self._lock = threading.Lock()   # serializes actual socket sends
        self._blk = threading.Lock()    # guards _backlog and _closed
        self._backlog = bytearray()
        self._closed = False
        self._flusher = False  # a _flush_parked thread is alive
        self.syscalls = 0     # sendmsg calls issued (incl. EAGAIN probes)
        self.bytes_sent = 0
        self.deferred = 0     # nonblocking writes parked without a syscall

    # -- internals -----------------------------------------------------------
    def _take(self, bufs) -> list[memoryview]:
        """Swap out the backlog and append ``bufs`` — the commit point."""
        with self._blk:
            if self._closed:
                raise ConnectionLost("connection closed")
            views: list[memoryview] = []
            if self._backlog:
                views.append(memoryview(bytes(self._backlog)))
                self._backlog.clear()
            views.extend(memoryview(b) for b in bufs if len(b))
            return views

    def _send_vec(self, views: list[memoryview], flags: int) -> int:
        """One bounded sendmsg; returns bytes sent (0 on would-block)."""
        self.syscalls += 1
        try:
            n = self.sock.sendmsg(views[:_IOV_CAP], [], flags)
        except (BlockingIOError, InterruptedError):
            return 0
        self.bytes_sent += n
        return n

    def _drain(self, views: list[memoryview], flags: int) -> int:
        """Send as much of ``views`` as the socket takes; returns bytes
        sent. With ``flags=0`` this blocks until everything is out."""
        total = sum(len(v) for v in views)
        sent = 0
        while sent < total:
            # advance past fully-sent buffers; slice the partial one
            while views and len(views[0]) == 0:
                views.pop(0)
            n = self._send_vec(views, flags)
            if n == 0 and flags:
                return sent
            sent += n
            while n and views:
                if n >= len(views[0]):
                    n -= len(views.pop(0))
                else:
                    views[0] = views[0][n:]
                    n = 0
        return sent

    # -- API -----------------------------------------------------------------
    def write(self, bufs, block: bool = True) -> bool:
        """Write ``bufs`` (an iterable of bytes-likes, or one bytes-like)
        in order. ``block=False`` returns immediately: contended or
        would-block bytes park in the backlog. Contended ones leave
        with the writer that holds the socket; would-block ones are
        flushed by the next write on this connection.

        Returns True when everything (backlog included) reached the
        socket or a writer that holds it will send it, False when the
        socket would block with bytes parked — a nonblocking caller
        that gets False must arrange for SOME later write/flush on the
        connection, or the parked bytes sit until the next traffic."""
        if isinstance(bufs, (bytes, bytearray, memoryview)):
            bufs = [bufs]
        if block:
            with self._lock:
                self._send_taken(bufs, 0)
            return self._sweep(0)
        if defer(self, self._burst_end):
            # a producer's burst on this thread (a decode block's tokens
            # to every stream): the bytes wait in the backlog for its
            # end and leave in one syscall a connection, not one a stream
            self._park(bufs)
            return True
        if not self._lock.acquire(blocking=False):
            # a writer holds the socket: it already swapped the backlog
            # out, so parking here lands AFTER its bytes — commit order
            # is preserved. The holder sweeps the backlog when it lets
            # the socket go; it may have let go since the try above, so
            # this thread sweeps too.
            self._park(bufs)
            return self._sweep(socket.MSG_DONTWAIT)
        try:
            if not self._send_taken(bufs, socket.MSG_DONTWAIT):
                return False
        finally:
            self._lock.release()
        return self._sweep(socket.MSG_DONTWAIT)

    def _park(self, bufs) -> None:
        with self._blk:
            if self._closed:
                raise ConnectionLost("connection closed")
            for b in bufs:
                self._backlog += b
            self.deferred += 1

    def _burst_end(self) -> None:
        """What a burst parked here, in one send. Its writers heard True,
        so where the socket is full a thread of this writer's own waits
        for room (a client that reads slower than the engine writes)."""
        try:
            if self.write((), block=False):
                return
        except (OSError, ConnectionLost):
            return  # a dead connection: its streams' workers hear of it
        with self._blk:
            if self._flusher or self._closed:
                return
            self._flusher = True
        threading.Thread(target=self._flush_parked, name="gofr-wire-flush",
                         daemon=True).start()

    def _flush_parked(self) -> None:
        try:
            while True:
                self.flush()
                with self._blk:
                    if not self._backlog:
                        self._flusher = False  # with the look, not after
                        return
        except (OSError, ConnectionLost):
            return  # a dead connection: its streams' workers hear of it
        finally:
            with self._blk:
                self._flusher = False

    def _send_taken(self, bufs, flags: int) -> bool:
        """Holding ``_lock``: the backlog and ``bufs`` to the socket. False
        if the socket took only part (nonblocking): the rest is parked."""
        views = self._take(bufs)
        total = sum(len(v) for v in views)
        sent = self._drain(views, flags)
        if sent < total:
            # _drain advanced ``views`` in place: what remains is
            # exactly the unsent tail
            rest = b"".join(views)
            with self._blk:
                # unsent tail goes back to the FRONT: bytes parked by
                # other threads during this send came later
                self._backlog[:0] = rest
                self.deferred += 1
            return False
        return True

    def _sweep(self, flags: int) -> bool:
        """After a write let the socket go: send what other threads
        parked while it held it, so that a contended nonblocking write
        needs no later flush of its caller's. Whoever parks tries the
        lock AFTER parking and whoever holds it looks at the backlog
        AFTER releasing, so one of the two sees the bytes. False only
        if the socket would block with bytes still parked."""
        while True:
            with self._blk:
                if not self._backlog:
                    return True
            if not self._lock.acquire(blocking=False):
                return True  # that writer sweeps when it lets go
            try:
                if not self._send_taken((), flags):
                    return False
            finally:
                self._lock.release()

    def flush(self) -> None:
        """Blocking drain of any backlog left by nonblocking writes."""
        self.write([], block=True)

    @property
    def backlog_bytes(self) -> int:
        """Bytes parked by nonblocking writes and not yet on the wire —
        the flow-control signal windowed producers (the PD KV-ship
        path) bound themselves against instead of letting the backlog
        grow without limit on a stalled peer."""
        with self._blk:
            return len(self._backlog)

    def close(self) -> None:
        with self._blk:
            self._closed = True
            self._backlog.clear()
        try:
            # shutdown BEFORE close: it wakes a writer blocked in sendmsg
            # (close alone would deadlock behind the in-progress syscall)
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()


def observe_backlog(metrics, backlog_bytes: int, **labels) -> None:
    """Export one outbox-backlog sample (``app_tpu_wire_backlog_bytes``,
    labeled by caller role): the flow-control signal ``backlog_bytes``
    already tracks, made scrapeable so a stalled peer shows up on a
    dashboard before it shows up as a deadline storm. Swallows every
    failure — telemetry must never take a send path down."""
    if metrics is None:
        return
    try:
        metrics.set_gauge("app_tpu_wire_backlog_bytes",
                          float(backlog_bytes), **labels)
    except Exception:
        pass


class Outbox:
    """Ordered send queue with thread-combining flush.

    Producers ``append()`` then ``pump(block=False)`` — which never
    blocks the producer; whichever thread wins the flusher role drains
    everything pending (its own items plus anything other threads
    appended meanwhile) in FIFO order. The owning worker thread calls
    ``pump(block=True)`` to clear stalls and at end-of-stream.

    ``drain(batch, block)`` is the send callback: it consumes a PREFIX
    of ``batch`` and returns how many items it consumed. A blocking
    drain must consume the whole batch; a nonblocking drain may stop
    early (no flow-control credit), which sets ``stalled`` so the
    producer can stop fast-pathing.
    """

    def __init__(self, drain):
        self._drain_cb = drain
        self._items: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._flushing = False
        self.stalled = False

    def append(self, item) -> None:
        with self._lock:
            self._items.append(item)

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)

    def pump(self, block: bool = False) -> None:
        while True:
            with self._lock:
                if self._flushing:
                    if not block:
                        return   # the active flusher will see our items
                    busy = True
                else:
                    if not self._items:
                        return
                    self._flushing = True
                    busy = False
            if busy:
                # a nonblocking flusher is mid-drain; it is brief — yield
                # once and retake (only blocking pumps ever spin here)
                time.sleep(0)
                continue
            try:
                while True:
                    with self._lock:
                        batch = list(self._items)
                    if not batch:
                        break
                    n = self._drain_cb(batch, block)
                    with self._lock:
                        for _ in range(n):
                            self._items.popleft()
                    if n < len(batch):
                        self.stalled = True
                        return
                    self.stalled = False
            finally:
                with self._lock:
                    self._flushing = False
            # items appended between the final emptiness check and the
            # flag clear are picked up by looping (no lost wakeup)


class _Burst(threading.local):
    pending: "dict | None" = None  # inside a burst: {key: flush}, in order


_burst = _Burst()


@contextlib.contextmanager
def burst():
    """A run of pushes on this thread, to many streams: sinks called inside
    may put their sends off to its end (``defer``). The engine's reap
    wraps a decode block's deliveries in one: K tokens a stream then cost
    one pump at the end of the block and a connection one syscall, where a
    pump and a syscall a token made delivery the longest phase of the loop
    once every token went through the sink (PERF.md, PR 43). Nested bursts
    flush with the outermost."""
    if _burst.pending is not None:
        yield
        return
    _burst.pending = pending = {}
    try:
        yield
    finally:
        # the sinks' flushes first, still inside the burst: what they
        # write parks with its SocketWriter, whose one send a connection
        # runs last, outside it
        for last in (False, True):
            flushes = list(pending.values())
            pending.clear()
            if last:
                _burst.pending = None
            for flush in flushes:
                try:
                    flush()
                except Exception:
                    pass  # a flush answers for its own failures, as a sink


def defer(key, flush) -> bool:
    """Inside a burst of this thread: ``flush()`` runs once at its end
    (one call a ``key``, in the order of first deferral); says True.
    Outside a burst: does nothing and says False."""
    pending = _burst.pending
    if pending is None:
        return False
    pending.setdefault(key, flush)
    return True


# sentinel a producer-side sink can enqueue (PushStream.wake) to rouse
# the consuming worker without delivering an item — e.g. "the outbox
# stalled with your bytes in it, come flush". Iterating consumers that
# never call wake() never see it.
WAKE = object()


class PushStream:
    """Queue-backed item stream with an optional zero-handoff sink.

    Producer side calls ``_push(item)``; ``None`` ends the stream and a
    queued ``BaseException`` re-raises in the consumer. When a consumer
    registers a sink, items are handed to it ON THE PRODUCING THREAD;
    the sink returns True to consume or False to fall back to the queue
    (the consumer's iterator). Terminal items always go to the queue so
    the consuming thread observes the end.

    A decline is PERMANENT: the first False detaches the sink and every
    later item rides the queue. This is what makes the ordering
    guarantee structural — if a sink could decline item N and accept
    item N+1, the producing thread would write N+1 to the wire while N
    waited for the consumer thread. (In-tree sinks downgrade themselves
    on any obstacle anyway; the detach enforces it for everyone.)

    The sink MUST be non-blocking and exception-free in spirit: a sink
    that raises is dropped (the stream falls back to queue delivery)
    rather than killing the producer.
    """

    def __init__(self):
        self._q: queue.Queue = queue.Queue()
        self._sink = None
        self._sink_lock = threading.Lock()

    def _sink_try(self, sink, item) -> bool:
        try:
            return bool(sink(item))
        except Exception:
            self._sink = None
            return False

    def _push(self, item) -> None:
        with self._sink_lock:
            sink = self._sink
            if (sink is not None and item is not None
                    and not isinstance(item, BaseException)):
                if self._sink_try(sink, item):
                    return
                self._sink = None  # declines are permanent (see class doc)
            self._q.put(item)

    def set_sink(self, sink) -> None:
        """Register ``sink`` and drain already-queued items through it
        under the delivery lock, so delivery order is preserved across
        the registration boundary. Terminal items (and everything after
        a declined item) stay queued for the iterator."""
        with self._sink_lock:
            pending = []
            while True:
                try:
                    pending.append(self._q.get_nowait())
                except queue.Empty:
                    break
            self._sink = sink
            for idx, item in enumerate(pending):
                if (item is None or isinstance(item, BaseException)
                        or not self._sink_try(sink, item)):
                    if item is not None and not isinstance(item,
                                                           BaseException):
                        self._sink = None  # declined: permanent fallback
                    for rest in pending[idx:]:
                        self._q.put(rest)
                    break

    def clear_sink(self) -> None:
        with self._sink_lock:
            self._sink = None

    def wake(self) -> None:
        """Rouse the consuming thread with a WAKE marker. Safe from
        inside a sink callback (no locks taken)."""
        self._q.put(WAKE)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def map(self, fn) -> "MappedStream":
        return MappedStream(self, fn)


class MappedStream:
    """A PushStream view with a per-item transform — lets one source
    serve different consumers (gRPC messages, HTTP ndjson chunks)
    while keeping the zero-handoff sink protocol intact."""

    def __init__(self, source, fn):
        self._source = source
        self._fn = fn

    def set_sink(self, sink) -> None:
        fn = self._fn
        self._source.set_sink(lambda item: sink(fn(item)))

    def clear_sink(self) -> None:
        cs = getattr(self._source, "clear_sink", None)
        if cs is not None:
            cs()

    def __iter__(self):
        for item in self._source:
            yield item if item is WAKE else self._fn(item)

    def map(self, fn) -> "MappedStream":
        return MappedStream(self, fn)

    def wake(self) -> None:
        w = getattr(self._source, "wake", None)
        if w is not None:
            w()

    def cancel(self) -> None:
        c = getattr(self._source, "cancel", None)
        if c is not None:
            c()

    @property
    def trace(self):
        """TTFT decomposition stamps of the underlying source (GenStream
        sets ``first_put``), for the transport's grpc.handoff span."""
        return getattr(self._source, "trace", None)
