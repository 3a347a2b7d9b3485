"""Transport fast path (ISSUE 2): HPACK encode caching, stateless
blocks, the vectored/backlog socket writer, the outbox write scheduler,
and zero-handoff server streaming end-to-end on loopback.

Correctness bar: the caches must be BYTE-IDENTICAL to the uncached
encoder under any dynamic-table state (including evictions and
mid-stream resizes), the writer must preserve commit order across
blocking/nonblocking mixes and EAGAIN backpressure, and a pushed stream
must arrive complete and in order whether tokens ride the sink fast
path or the worker fallback. Liveness/ordering only — no timing
assertions (tools/transport_bench.py owns the numbers).
"""

import random
import socket
import string
import threading
import time

import pytest

from gofr_tpu.grpcx import (GRPCServer, GRPCService, ServerStream,
                            TransportOptions, dial)
from gofr_tpu.grpcx import http2 as h2
from gofr_tpu.grpcx.hpack import Decoder, Encoder, encode_stateless
from gofr_tpu import wire
from gofr_tpu.wire import Outbox, PushStream, SocketWriter

NAME_CHARS = string.ascii_lowercase + string.digits + "-"


def _rand_headers(rng):
    out = []
    for _ in range(rng.randint(0, 10)):
        if rng.random() < 0.4:  # repeats exercise the dynamic table
            name = rng.choice([":status", "content-type", "grpc-status",
                               "x-request-id", "grpc-message"])
            value = rng.choice(["200", "application/grpc", "0", "abc", ""])
        else:
            name = "".join(rng.choice(NAME_CHARS)
                           for _ in range(rng.randint(1, 16)))
            value = "".join(rng.choice(string.printable.strip())
                            for _ in range(rng.randint(0, 40)))
        out.append((name, value))
    return out


# -- HPACK encode caching -----------------------------------------------------

def test_encoder_memo_is_byte_identical_under_eviction():
    """Cached vs uncached encoders fed the same header sequence — with a
    SMALL table so entries evict constantly, plus mid-stream resizes —
    must emit byte-identical blocks, and a decoder must round-trip."""
    rng = random.Random(0xFA57)
    memo = Encoder(max_table_size=256)
    plain = Encoder(max_table_size=256, memo=False)
    dec = Decoder(max_table_size=256)
    dec.table.resize(256)
    for i in range(300):
        if i % 23 == 11:
            size = rng.choice([0, 64, 128, 256])
            memo.set_max_table_size(size)
            plain.set_max_table_size(size)
        headers = _rand_headers(rng)
        a = memo.encode(headers)
        b = plain.encode(headers)
        assert a == b, f"case {i}: memo diverged for {headers!r}"
        got = dec.decode(a)
        assert got == [(n.lower().encode(), v.encode()) for n, v in headers]
    # the memo encoder actually indexed things (the fast path ran)
    assert memo._str_cache


def test_encoder_memo_matches_across_huffman_and_indexing_modes():
    rng = random.Random(0x5EED)
    memo, plain = Encoder(), Encoder(memo=False)
    for i in range(150):
        memo.huffman = plain.huffman = rng.random() < 0.7
        memo.indexing = plain.indexing = rng.random() < 0.8
        headers = _rand_headers(rng)
        assert memo.encode(headers) == plain.encode(headers), f"case {i}"


def test_encode_stateless_blocks_leave_decoder_state_untouched():
    """Stateless blocks (the pre-encoded per-server response/trailer
    templates) must decode correctly at ANY point in a connection's
    life and never touch the decoder's dynamic table."""
    resp = [(":status", "200"), ("content-type", "application/grpc")]
    trailer = [("grpc-status", "0")]
    block_resp = encode_stateless(resp)
    # deterministic: pre-encoding once per server is sound
    assert block_resp == encode_stateless(resp)

    enc, dec = Encoder(), Decoder()
    # interleave stateful traffic with stateless blocks
    stateful = [("x-request-id", "abc-123"), ("content-type", "text/html")]
    dec.decode(enc.encode(stateful))
    entries_before = list(dec.table.entries)
    assert dec.decode(block_resp) == [(b":status", b"200"),
                                      (b"content-type", b"application/grpc")]
    assert dec.decode(encode_stateless(trailer)) == [(b"grpc-status", b"0")]
    assert dec.table.entries == entries_before  # untouched
    # stateful traffic still consistent afterwards
    got = dec.decode(enc.encode(stateful))
    assert got == [(b"x-request-id", b"abc-123"),
                   (b"content-type", b"text/html")]


def test_dynamic_table_duplicate_entries_index_newest():
    """The O(1) reverse index must match the linear scan's preference
    for the most recent duplicate (smallest index)."""
    enc = Encoder()
    dec = Decoder()
    headers = [("x-dup", "v"), ("x-other", "a"), ("x-dup", "v")]
    for _ in range(3):  # re-encoding keeps hitting the dynamic entries
        assert dec.decode(enc.encode(headers)) == [
            (b"x-dup", b"v"), (b"x-other", b"a"), (b"x-dup", b"v")]


# -- SocketWriter -------------------------------------------------------------

def _writer_pair():
    a, b = socket.socketpair()
    # tiny buffers force the EAGAIN/backlog path deterministically
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
    return SocketWriter(a), a, b


def test_socket_writer_preserves_order_across_modes_and_backpressure():
    wr, a, b = _writer_pair()
    rng = random.Random(0xB10B)
    sent = bytearray()
    received = bytearray()

    def reader():
        while True:
            chunk = b.recv(65536)
            if not chunk:
                return  # EOF after the writer's shutdown — drained all
            received.extend(chunk)

    t = threading.Thread(target=reader)
    try:
        # phase 1: NO reader — nonblocking writes must fill the socket
        # buffer and start parking in the backlog without ever blocking
        for i in range(200):
            payload = bytes([i % 251]) * rng.randint(200, 2000)
            sent.extend(payload)
            wr.write([payload], block=False)
        assert wr.deferred > 0, "test never exercised the backlog path"
        # phase 2: reader drains while mixed blocking/nonblocking writes
        # land on top of the backlog — order must survive
        t.start()
        for i in range(200):
            payload = bytes([(100 + i) % 251]) * rng.randint(1, 2000)
            sent.extend(payload)
            wr.write([payload], block=rng.random() < 0.5)
        wr.flush()
        # EOF, not a flag, ends the reader: any done-flag protocol races
        # a reader that drained the final chunk before the flag was set
        a.shutdown(socket.SHUT_WR)
        t.join(timeout=20)
        assert not t.is_alive()
        assert bytes(received) == bytes(sent)
    finally:
        a.close()
        b.close()


def test_socket_writer_vectored_single_syscall():
    wr, a, b = _writer_pair()
    try:
        bufs = [b"h" * 9, b"x" * 100, b"h" * 9, b"y" * 100]
        wr.write(bufs, block=True)
        assert wr.syscalls == 1  # one sendmsg carried all four buffers
        got = b.recv(65536)
        assert got == b"".join(bufs)
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("holder_blocks", [True, False],
                         ids=["blocking-holder", "nonblocking-holder"])
def test_socket_writer_holder_sweeps_what_was_parked_behind_it(
        holder_blocks):
    """A nonblocking write that meets a writer on the socket parks, says
    True and asks nothing more of its caller: the writer that holds the
    socket sends the parked bytes when it lets go, after its own and with
    no further call on the connection. (The engine's loop writes tokens
    this way while a stream's worker writes trailers; when such a write
    said False, its stream went to its worker thread for good.)"""
    a, b = socket.socketpair()
    wr = SocketWriter(a)
    inside, parked = threading.Event(), threading.Event()
    real = wr._drain

    def held(views, flags):
        # the holder is in its send, the socket's lock taken, until the
        # other thread has parked
        inside.set()
        assert parked.wait(5)
        return real(views, flags)

    wr._drain = held
    try:
        t = threading.Thread(
            target=lambda: wr.write([b"held|"], block=holder_blocks))
        t.start()
        assert inside.wait(5)
        wr._drain = real
        assert wr.write([b"parked"], block=False) is True
        assert wr.deferred == 1 and wr.backlog_bytes == len(b"parked")
        parked.set()
        t.join(5)
        assert not t.is_alive()
        assert wr.backlog_bytes == 0
        b.settimeout(5)
        got = b""
        while len(got) < len(b"held|parked"):
            got += b.recv(64)
        assert got == b"held|parked"
    finally:
        a.close()
        b.close()


def test_socket_writer_full_socket_still_says_false():
    """What is left of False: the socket would block and nobody holds it,
    so the parked bytes have no one to send them but a later write."""
    wr, a, b = _writer_pair()
    try:
        assert wr.write([b"x" * 1_000_000], block=False) is False
        assert wr.backlog_bytes > 0
        # and a contended write behind such bytes: the holder's sweep
        # meets the full socket too, and its caller hears False
        assert wr.write([b"y"], block=False) is False
    finally:
        a.close()
        b.close()


# -- Outbox -------------------------------------------------------------------

def test_outbox_drains_in_order_across_threads():
    drained = []

    def drain(batch, block):
        drained.extend(batch)
        return len(batch)

    box = Outbox(drain)
    items = list(range(500))

    def produce(chunk):
        for i in chunk:
            box.append(i)
            box.pump(block=False)

    ts = [threading.Thread(target=produce, args=(items[i::2],))
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    box.pump(block=True)
    assert sorted(drained) == items
    assert len(drained) == len(items)  # exactly once each
    # per-producer order preserved (FIFO outbox)
    for lane in (items[0::2], items[1::2]):
        seen = [i for i in drained if i in set(lane)]
        assert seen == lane


def test_outbox_stall_then_blocking_pump_completes():
    state = {"accept": 1}
    drained = []

    def drain(batch, block):
        if block:
            drained.extend(batch)
            return len(batch)
        n = min(state["accept"], len(batch))
        drained.extend(batch[:n])
        return n

    box = Outbox(drain)
    for i in range(5):
        box.append(i)
    box.pump(block=False)
    assert box.stalled and drained == [0]
    box.pump(block=True)  # the worker path clears the stall
    assert drained == [0, 1, 2, 3, 4]


# -- PushStream ---------------------------------------------------------------

# -- burst / defer ------------------------------------------------------------

def test_burst_runs_each_deferred_flush_once_at_its_end_in_order():
    ran = []
    assert wire.defer("a", lambda: ran.append("outside")) is False
    with wire.burst():
        for key in ("a", "b", "a", "c", "b"):
            assert wire.defer(key, lambda key=key: ran.append(key)) is True
        with wire.burst():  # nested: the outermost flushes
            assert wire.defer("d", lambda: ran.append("d")) is True
        assert ran == []
    assert ran == ["a", "b", "c", "d"]
    assert wire.defer("a", lambda: ran.append("outside")) is False


def test_burst_is_this_threads_alone():
    seen = []
    with wire.burst():
        t = threading.Thread(
            target=lambda: seen.append(wire.defer("k", lambda: None)))
        t.start()
        t.join(5)
    assert seen == [False]


def test_socket_writer_in_a_burst_sends_once_a_connection():
    """Inside a producer's burst nonblocking writes wait in the backlog
    and leave in one syscall at its end, in order."""
    a, b = socket.socketpair()
    wr = SocketWriter(a)
    try:
        with wire.burst():
            for i in range(10):
                assert wr.write([b"%d|" % i], block=False) is True
            assert wr.syscalls == 0 and wr.backlog_bytes == 20
        assert wr.syscalls == 1 and wr.backlog_bytes == 0
        assert b.recv(64) == b"0|1|2|3|4|5|6|7|8|9|"
        # a blocking write inside a burst does not wait for its end
        with wire.burst():
            wr.write([b"parked|"], block=False)
            wr.write([b"now"], block=True)
            assert b.recv(64) == b"parked|now"
    finally:
        a.close()
        b.close()


def test_socket_writer_burst_on_a_full_socket_flushes_by_itself():
    """The burst's writers heard True, so where the socket is full at its
    end nobody is left to flush: a thread of the writer's own waits for
    room, and ends when the backlog is out."""
    wr, a, b = _writer_pair()
    payload = b"".join(bytes([i % 251]) * 1000 for i in range(400))
    try:
        with wire.burst():
            assert wr.write([payload], block=False) is True
        assert wr._flusher  # the socket took a part: nobody reads yet
        got = bytearray()
        b.settimeout(10)
        while len(got) < len(payload):
            got.extend(b.recv(65536))
        assert bytes(got) == payload
        deadline = time.monotonic() + 5
        while wr._flusher and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not wr._flusher and wr.backlog_bytes == 0
    finally:
        a.close()
        b.close()


def test_push_stream_sink_registration_drains_in_order():
    src = PushStream()
    for i in range(3):
        src._push(i)  # queued before any sink exists
    got = []
    src.set_sink(lambda item: (got.append(item), True)[1])
    for i in range(3, 6):
        src._push(i)
    src._push(None)
    assert got == [0, 1, 2, 3, 4, 5]  # pre-registration items came first
    assert list(src) == []            # terminal reached the iterator


def test_push_stream_declined_items_fall_back_to_queue_in_order():
    src = PushStream()
    got = []

    def sink(item):
        if item >= 2:
            return False  # downgrade mid-stream
        got.append(item)
        return True

    src.set_sink(sink)
    for i in range(5):
        src._push(i)
    src._push(None)
    assert got == [0, 1]
    assert list(src) == [2, 3, 4]


def test_push_stream_raising_sink_is_dropped_not_fatal():
    src = PushStream()

    def sink(item):
        raise RuntimeError("broken sink")

    src.set_sink(sink)
    src._push(1)
    src._push(None)
    assert list(src) == [1]  # fell back to the queue, producer survived


def test_push_stream_queued_error_reraises():
    src = PushStream()
    src._push(7)
    src._push(ValueError("boom"))
    it = iter(src)
    assert next(it) == 7
    with pytest.raises(ValueError, match="boom"):
        next(it)


def test_mapped_stream_sink_and_iter():
    src = PushStream()
    mapped = src.map(lambda t: t * 10)
    got = []
    mapped.set_sink(lambda item: (got.append(item), True)[1])
    src._push(1)
    src._push(2)
    src._push(None)
    assert got == [10, 20]


# -- loopback streaming smoke (ordering/liveness, never timing) ---------------

def _token_server(options, gap_s=0.0):
    svc = GRPCService("t.Stream")

    @svc.unary("Echo")
    def echo(ctx, req):
        return req

    @svc.server_stream("Tokens")
    def tokens(ctx, req):
        src = PushStream()

        def produce():
            for i in range(req["n"]):
                src._push({"t": i, "pad": "x" * req.get("pad", 0)})
                if gap_s:
                    time.sleep(gap_s)
            src._push(None)

        threading.Thread(target=produce, daemon=True).start()
        return ServerStream(src)

    srv = GRPCServer([svc], port=0, options=options)
    srv.start()
    return srv


@pytest.mark.parametrize("options", [TransportOptions(),
                                     TransportOptions.legacy()],
                         ids=["fast", "legacy"])
def test_stream_tokens_arrive_complete_and_ordered(options):
    srv = _token_server(options, gap_s=0.001)
    ch = dial(f"127.0.0.1:{srv.port}", options=options)
    try:
        got = [m["t"] for m in ch.server_stream("/t.Stream/Tokens",
                                                {"n": 40})]
        assert got == list(range(40))
        # and the connection still serves unary RPCs afterwards
        assert ch.unary("/t.Stream/Echo", {"ok": 1}) == {"ok": 1}
    finally:
        ch.close()
        srv.stop()


def test_fast_path_coalesces_headers_with_first_data():
    srv = _token_server(TransportOptions())
    ch = dial(f"127.0.0.1:{srv.port}")
    try:
        list(ch.server_stream("/t.Stream/Tokens", {"n": 4}))
        conn = next(iter(srv._conns))
        assert conn.io.coalesced_header_data >= 1
    finally:
        ch.close()
        srv.stop()


def test_oversized_messages_downgrade_to_worker_path():
    """Messages above the peer's max frame size can't ride the sink fast
    path; they must fall back to the worker's multi-frame send without
    loss or reordering."""
    srv = _token_server(TransportOptions())
    ch = dial(f"127.0.0.1:{srv.port}")
    try:
        pad = h2.DEFAULT_MAX_FRAME  # each message > one frame
        got = list(ch.server_stream("/t.Stream/Tokens",
                                    {"n": 6, "pad": pad}, timeout=60.0))
        assert [m["t"] for m in got] == list(range(6))
        assert all(len(m["pad"]) == pad for m in got)
    finally:
        ch.close()
        srv.stop()


def test_lazy_window_replenish_sustains_long_streams():
    """Total streamed bytes far beyond the 64 KiB initial windows: the
    batched WINDOW_UPDATE policy must keep credit flowing."""
    srv = _token_server(TransportOptions())
    ch = dial(f"127.0.0.1:{srv.port}")
    try:
        got = list(ch.server_stream("/t.Stream/Tokens",
                                    {"n": 300, "pad": 1024}, timeout=60.0))
        assert [m["t"] for m in got] == list(range(300))
    finally:
        ch.close()
        srv.stop()


def test_zero_handoff_cancel_mid_stream_releases_cleanly():
    srv = _token_server(TransportOptions(), gap_s=0.002)
    ch = dial(f"127.0.0.1:{srv.port}")
    try:
        it = ch.server_stream("/t.Stream/Tokens", {"n": 100000})
        first = [next(it) for _ in range(3)]
        assert [m["t"] for m in first] == [0, 1, 2]
        it.close()  # RST_STREAM
        assert not ch._calls
        assert ch.unary("/t.Stream/Echo", {"after": 1}) == {"after": 1}
    finally:
        ch.close()
        srv.stop()


def _burst_server(bursts, k, cancel_after=None):
    """Streams ``bursts`` x ``k`` tokens, each run of ``k`` pushed inside
    one ``wire.burst()``, as the engine's reap delivers a decode block."""
    svc = GRPCService("t.Burst")
    state = {}

    @svc.server_stream("Tokens")
    def tokens(ctx, req):
        src = PushStream()

        def produce():
            i = 0
            for _ in range(bursts):
                with wire.burst():
                    for _ in range(k):
                        src._push({"t": i})
                        i += 1
                time.sleep(0.002)
            src._push(None)

        state["thread"] = t = threading.Thread(target=produce, daemon=True)
        t.start()
        return ServerStream(src)

    srv = GRPCServer([svc], port=0)
    srv.start()
    return srv, state


def test_burst_tokens_arrive_complete_ordered_and_coalesced():
    srv, _ = _burst_server(bursts=25, k=4)
    ch = dial(f"127.0.0.1:{srv.port}")
    try:
        got = [m["t"] for m in ch.server_stream("/t.Burst/Tokens", {})]
        assert got == list(range(100))
        conn = next(iter(srv._conns))
        # a run of four left in one write (and HEADERS, trailers and the
        # settings exchange in a few more): far fewer than a token each
        assert conn.io.writer.syscalls < 60
    finally:
        ch.close()
        srv.stop()


def test_burst_that_ends_after_a_cancel_writes_nothing_more():
    """A pump put off to a burst's end may come after the RPC is over:
    it finds the sender closed and the connection stays usable."""
    srv, state = _burst_server(bursts=2000, k=4)
    ch = dial(f"127.0.0.1:{srv.port}")
    try:
        it = ch.server_stream("/t.Burst/Tokens", {})
        assert [next(it)["t"] for _ in range(3)] == [0, 1, 2]
        it.close()  # RST_STREAM mid-stream, the producer still bursting
        for _ in range(20):
            assert ch.unary("/grpc.health.v1.Health/Check", {}) == {
                "status": "SERVING"}
            time.sleep(0.005)
    finally:
        ch.close()
        srv.stop()


def test_first_send_spans_exported():
    """The TTFT decomposition spans (grpc.hpack, grpc.frame-write,
    grpc.handoff) must export once per stream when a tracer is wired."""
    from gofr_tpu.tracing import InMemoryExporter, Tracer

    class Shim:
        logger = None
        exporter = InMemoryExporter()
        tracer = Tracer(service_name="t", exporter=exporter)

    svc = GRPCService("t.Spans")

    @svc.server_stream("Tokens")
    def tokens(ctx, req):
        src = PushStream()
        src.trace = {}

        def produce():
            for i in range(5):
                src.trace.setdefault("first_put", time.monotonic())
                src._push({"t": i})
            src._push(None)

        threading.Thread(target=produce, daemon=True).start()
        return ServerStream(src)

    srv = GRPCServer([svc], port=0, container=Shim())
    srv.start()
    ch = dial(f"127.0.0.1:{srv.port}")
    try:
        assert len(list(ch.server_stream("/t.Spans/Tokens", {}))) == 5
        deadline = time.monotonic() + 5
        names = set()
        while time.monotonic() < deadline:
            names = {s.name for s in Shim.exporter.spans}
            if {"grpc.hpack", "grpc.frame-write", "grpc.handoff"} <= names:
                break
            time.sleep(0.01)
        assert {"grpc.hpack", "grpc.frame-write", "grpc.handoff"} <= names
        # once per stream, not per token
        assert sum(1 for s in Shim.exporter.spans
                   if s.name == "grpc.hpack") == 1
    finally:
        ch.close()
        srv.stop()
