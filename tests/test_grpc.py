"""grpcx tests: HPACK codec, HTTP/2 transport, end-to-end RPC semantics.

Mirrors the reference's seam strategy (SURVEY §4): the client in
grpcx.client plays the role grpc.Dial plays in the reference's example
tests (examples/grpc-server/main_test.go:15-50) — real sockets on
localhost, no mocks in the wire path.
"""

import threading
import time

import pytest

from gofr_tpu.grpcx import (GRPCError, GRPCService, GRPCServer,
                            dial, INVALID_ARGUMENT, INTERNAL,
                            DEADLINE_EXCEEDED, UNIMPLEMENTED)
from gofr_tpu.grpcx.hpack import (Decoder, Encoder, HPACKError,
                                  decode_int, encode_int,
                                  huffman_decode, huffman_encode)


# -- hpack --------------------------------------------------------------------

def test_hpack_integer_roundtrip():
    for prefix in (4, 5, 6, 7):
        for val in (0, 1, 9, 30, 31, 127, 128, 255, 1337, 1 << 20):
            data = bytes(encode_int(val, prefix))
            got, pos = decode_int(data, 0, prefix)
            assert got == val and pos == len(data)


def test_huffman_roundtrip():
    for s in (b"", b"a", b"www.example.com", b"no-cache",
              b"custom-value", bytes(range(256))):
        assert huffman_decode(huffman_encode(s)) == s


def test_huffman_rfc_vectors():
    # RFC 7541 C.4.1: "www.example.com" huffman-encodes to these bytes
    assert huffman_encode(b"www.example.com") == bytes.fromhex(
        "f1e3c2e5f23a6ba0ab90f4ff")
    assert huffman_encode(b"no-cache") == bytes.fromhex("a8eb10649cbf")


def test_huffman_rejects_bad_padding():
    with pytest.raises(HPACKError):
        huffman_decode(b"\x00")  # 0-bits are '0' * 8 -> invalid padding


def test_hpack_header_roundtrip_with_dynamic_table():
    enc, dec = Encoder(), Decoder()
    rounds = [
        [(":method", "POST"), (":path", "/pkg.Svc/M"), (":scheme", "http"),
         ("content-type", "application/grpc"), ("te", "trailers"),
         ("x-request-id", "abc-123")],
        [(":method", "POST"), (":path", "/pkg.Svc/M"), (":scheme", "http"),
         ("content-type", "application/grpc"), ("x-request-id", "abc-124")],
    ]
    for headers in rounds:
        block = enc.encode(headers)
        got = [(n.decode(), v.decode()) for n, v in dec.decode(block)]
        assert got == [(n.lower(), v) for n, v in headers]
    # second round should be far smaller thanks to the dynamic table
    assert len(enc.encode(rounds[1])) < 30


def test_hpack_decoder_handles_plain_literals_and_size_update():
    dec = Decoder()
    # literal w/o indexing, new name, no huffman: "x-a: b"
    block = b"\x00" + bytes([3]) + b"x-a" + bytes([1]) + b"b"
    assert dec.decode(block) == [(b"x-a", b"b")]
    # dynamic table size update within bounds then an indexed static header
    block = b"\x3f\xe1\x1f" + b"\x82"  # resize to 4064, then :method GET
    assert dec.decode(block) == [(b":method", b"GET")]
    with pytest.raises(HPACKError):
        dec.decode(b"\x80")  # index 0 invalid
    with pytest.raises(HPACKError):
        dec.decode(b"\xff\xff\xff")  # truncated integer


def test_hpack_no_indexing_mode():
    enc, dec = Encoder(), Decoder()
    enc.indexing = False
    headers = [("x-custom", "v1"), (":path", "/x")]
    for _ in range(2):
        got = dec.decode(enc.encode(headers))
        assert got == [(b"x-custom", b"v1"), (b":path", b"/x")]
    assert not enc.table.entries  # nothing was indexed
    assert not dec.table.entries


def test_hpack_table_size_downgrade_emits_update():
    """RFC 7541 §4.2: when the peer shrinks SETTINGS_HEADER_TABLE_SIZE the
    encoder must evict beyond the new size and open the next header block
    with a dynamic-table-size update — stale indexed refs would otherwise
    point into entries the peer's shrunken table already dropped."""
    enc, dec = Encoder(), Decoder()
    headers = [("x-custom", "v1"), ("x-other", "v2")]
    dec.decode(enc.encode(headers))  # both now in the dynamic tables
    assert len(enc.table.entries) == 2

    enc.set_max_table_size(0)  # peer shrank its table to nothing
    assert not enc.table.entries  # evicted immediately
    block = enc.encode(headers)
    assert block[0] & 0xE0 == 0x20 and block[0] & 0x1F == 0  # §6.3 update
    dec2 = Decoder()  # a fresh peer with a 0-size table decodes cleanly
    dec2.table.resize(0)
    assert dec2.decode(block) == [(b"x-custom", b"v1"), (b"x-other", b"v2")]
    assert not dec2.table.entries

    enc.set_max_table_size(4096)  # grow back: update emitted, indexing resumes
    block = enc.encode(headers)
    assert dec.decode(block) == [(b"x-custom", b"v1"), (b"x-other", b"v2")]


def test_hpack_shrink_then_grow_signals_minimum():
    """RFC 7541 §4.2: size drops to 0 then back up BETWEEN header blocks
    must still signal the intermediate minimum so the peer flushes."""
    enc, dec = Encoder(), Decoder()
    headers = [("x-a", "1")]
    dec.decode(enc.encode(headers))
    assert dec.table.entries
    enc.set_max_table_size(0)
    enc.set_max_table_size(4096)
    block = enc.encode(headers)
    # two §6.3 updates open the block: 0, then 4096
    assert block[0] == 0x20
    got = dec.decode(block)
    assert got == [(b"x-a", b"1")]
    assert dec.table.max_size == 4096
    # the 0-update flushed, then the literal was re-added
    assert len(dec.table.entries) == 1


def test_server_stream_abandoned_iterator_sends_rst(channel, server):
    """Dropping a server-stream iterator mid-stream must RST the stream so
    the server stops generating and the call entry is released."""
    it = channel.server_stream("/test.Echo/Count", {"n": 50000})
    got = [next(it) for _ in range(3)]
    assert got == [{"i": 0}, {"i": 1}, {"i": 2}]
    it.close()  # abandon -> GeneratorExit -> RST_STREAM(CANCEL)
    assert not channel._calls  # local entry released
    # channel still healthy for new calls on the same connection
    assert channel.unary("/test.Echo/Say", {"msg": "after"})["msg"] == "after"


# -- end-to-end RPC -----------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    echo = GRPCService("test.Echo")

    @echo.unary("Say")
    def say(ctx, req):
        return {"msg": req["msg"], "peer_set": bool(ctx.peer)}

    @echo.unary("Fail")
    def fail(ctx, req):
        raise GRPCError(INVALID_ARGUMENT, "bad thing")

    @echo.unary("Panic")
    def panic(ctx, req):
        raise RuntimeError("boom")

    @echo.unary("Meta")
    def meta(ctx, req):
        return {"got": ctx.metadata.get("x-api-key", "")}

    @echo.server_stream("Count")
    def count(ctx, req):
        for i in range(req["n"]):
            yield {"i": i}

    @echo.unary("Slow")
    def slow(ctx, req):
        time.sleep(req.get("sleep", 0.5))
        return {"ok": True}

    srv = GRPCServer([echo], port=0)
    srv.start()
    yield srv
    srv.stop()


@pytest.fixture()
def channel(server):
    ch = dial(f"127.0.0.1:{server.port}")
    yield ch
    ch.close()


def test_unary_roundtrip(channel):
    out = channel.unary("/test.Echo/Say", {"msg": "hello"})
    assert out == {"msg": "hello", "peer_set": True}


def test_unary_many_sequential_calls_one_connection(channel):
    for i in range(20):
        assert channel.unary("/test.Echo/Say", {"msg": str(i)})["msg"] == str(i)


def test_concurrent_calls_multiplex(channel):
    out = [None] * 10
    def worker(i):
        out[i] = channel.unary("/test.Echo/Say", {"msg": f"m{i}"})["msg"]
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(10)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert out == [f"m{i}" for i in range(10)]


def test_server_streaming(channel):
    got = list(channel.server_stream("/test.Echo/Count", {"n": 25}))
    assert got == [{"i": i} for i in range(25)]


def test_error_statuses(channel):
    with pytest.raises(GRPCError) as e:
        channel.unary("/test.Echo/Fail", {})
    assert e.value.code == INVALID_ARGUMENT and "bad thing" in e.value.message

    with pytest.raises(GRPCError) as e:
        channel.unary("/test.Echo/Panic", {})
    assert e.value.code == INTERNAL  # recovery interceptor, no leak
    assert "boom" not in e.value.message

    with pytest.raises(GRPCError) as e:
        channel.unary("/test.Echo/Nope", {})
    assert e.value.code == UNIMPLEMENTED
    with pytest.raises(GRPCError) as e:
        channel.unary("/test.Nothing/X", {})
    assert e.value.code == UNIMPLEMENTED


def test_metadata_passthrough(channel):
    out = channel.unary("/test.Echo/Meta", {}, metadata={"X-API-Key": "k1"})
    assert out == {"got": "k1"}


def test_deadline_exceeded(channel):
    with pytest.raises(GRPCError) as e:
        channel.unary("/test.Echo/Slow", {"sleep": 0.5}, timeout=0.1)
    assert e.value.code == DEADLINE_EXCEEDED


def test_large_message_flow_control(channel):
    # 1 MiB payload forces multi-frame DATA + window refills both ways
    big = "x" * (1 << 20)
    out = channel.unary("/test.Echo/Say", {"msg": big}, timeout=30.0)
    assert out["msg"] == big


def test_protobuf_codec_roundtrip():
    """ProtoCodec against a hand-built descriptor (no protoc needed)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    pool = descriptor_pool.DescriptorPool()
    fd = descriptor_pb2.FileDescriptorProto()
    fd.name = "t.proto"
    fd.package = "t"
    m = fd.message_type.add()
    m.name = "Ping"
    f = m.field.add()
    f.name = "text"
    f.number = 1
    f.type = f.TYPE_STRING
    f.label = f.LABEL_OPTIONAL
    pool.Add(fd)
    Ping = message_factory.GetMessageClass(pool.FindMessageTypeByName("t.Ping"))

    svc_obj = GRPCService("t.P")

    @svc_obj.unary("Ping", request_type=Ping, response_type=Ping)
    def ping(ctx, req):
        out = Ping()
        out.text = req.text + "!"
        return out

    srv = GRPCServer([svc_obj], port=0)
    srv.start()
    try:
        ch = dial(f"127.0.0.1:{srv.port}")
        from gofr_tpu.grpcx import ProtoCodec

        req = Ping()
        req.text = "hi"
        out = ch.unary("/t.P/Ping", req, codec=ProtoCodec(Ping))
        assert out.text == "hi!"
        ch.close()
    finally:
        srv.stop()


# -- client / bidi streaming --------------------------------------------------

@pytest.fixture(scope="module")
def stream_server():
    agg = GRPCService("test.Stream")

    @agg.client_stream("Sum")
    def sum_(ctx, requests):
        return {"total": sum(r["n"] for r in requests)}

    @agg.bidi_stream("EchoUpper")
    def echo_upper(ctx, requests):
        for r in requests:
            yield {"msg": r["msg"].upper()}

    @agg.bidi_stream("Forever")
    def forever(ctx, requests):
        next(iter(requests))  # one request, then stream until cancelled
        i = 0
        while not ctx.is_cancelled():
            yield {"i": i}
            i += 1

    srv = GRPCServer([agg], port=0)
    srv.start()
    yield srv
    srv.stop()


def test_client_streaming_aggregates(stream_server):
    ch = dial(f"127.0.0.1:{stream_server.port}")
    try:
        out = ch.client_stream("/test.Stream/Sum",
                               ({"n": i} for i in range(10)))
        assert out == {"total": 45}
    finally:
        ch.close()


def test_bidi_streaming_interleaves(stream_server):
    ch = dial(f"127.0.0.1:{stream_server.port}")
    try:
        call = ch.bidi_stream("/test.Stream/EchoUpper")
        it = iter(call)
        # request/response strictly interleaved: each reply arrives before
        # the next request is sent — a genuinely bidirectional exchange
        for word in ("alpha", "beta", "gamma"):
            call.send({"msg": word})
            assert next(it)["msg"] == word.upper()
        call.close_send()
        assert list(it) == []  # server generator ends at half-close
    finally:
        ch.close()


def test_bidi_mid_stream_cancel(stream_server):
    ch = dial(f"127.0.0.1:{stream_server.port}")
    try:
        call = ch.bidi_stream("/test.Stream/Forever")
        call.send({"go": True})
        it = iter(call)
        got = [next(it)["i"] for _ in range(3)]
        assert got == [0, 1, 2]
        call.cancel()  # RST_STREAM: server's ctx.is_cancelled() goes true
        assert not ch._calls
        # channel unharmed: a fresh RPC on the same connection works
        assert ch.client_stream("/test.Stream/Sum",
                                [{"n": 2}, {"n": 3}]) == {"total": 5}
    finally:
        ch.close()


# -- app integration: token streaming over gRPC -------------------------------

def test_app_grpc_token_streaming():
    from gofr_tpu import App
    from gofr_tpu.config import MapConfig

    app = App(MapConfig({"GRPC_PORT": "0", "METRICS_PORT": "0",
                         "TPU_MODEL": "tiny", "TPU_MAX_SEQ": "64",
                         "TPU_SLOTS": "2", "TPU_SEQ_BUCKETS": "8,16"}))
    llm = GRPCService("llm.Generation")

    @llm.server_stream("Generate")
    def generate(ctx, req):
        stream = ctx.tpu.generate(req["tokens"],
                                  max_new_tokens=req.get("max_new_tokens", 8))
        for tok in stream:
            yield {"token": tok}

    app.register_grpc_service(llm)
    app.run(block=False)
    try:
        ch = dial(f"127.0.0.1:{app.grpc_port}")
        # generous deadline: the first request compiles the engine's
        # bucket programs, and loaded CI boxes have stretched the default
        # 60 s past breaking (observed under a concurrent full-suite run)
        toks = [m["token"] for m in ch.server_stream(
            "/llm.Generation/Generate",
            {"tokens": [5, 17, 42], "max_new_tokens": 6}, timeout=240.0)]
        assert len(toks) == 6
        assert all(isinstance(t, int) for t in toks)
        ch.close()
    finally:
        app.stop()


def test_app_grpc_bidi_generation_cancel_releases_slot():
    """The cancellable generation RPC (SURVEY §7 step 5): prompts stream
    in, tokens stream out on the same call, and a mid-stream client cancel
    frees the engine slot for the next request."""
    from gofr_tpu import App
    from gofr_tpu.config import MapConfig

    app = App(MapConfig({"GRPC_PORT": "0", "METRICS_PORT": "0",
                         "TPU_MODEL": "tiny", "TPU_MAX_SEQ": "64",
                         "TPU_SLOTS": "1", "TPU_SEQ_BUCKETS": "8,16"}))
    llm = GRPCService("llm.Generation")

    @llm.bidi_stream("Chat")
    def chat(ctx, requests):
        for req in requests:  # each request = one prompt turn
            stream = ctx.tpu.generate(req["tokens"],
                                      max_new_tokens=req.get("max_new", 8))
            try:
                for tok in stream:
                    yield {"token": tok}
            finally:
                stream.cancel()  # client RST mid-turn frees the slot
            yield {"turn_done": True}

    app.register_grpc_service(llm)
    app.run(block=False)
    gen = app.container.tpu.generator
    try:
        ch = dial(f"127.0.0.1:{app.grpc_port}")
        call = ch.bidi_stream("/llm.Generation/Chat", timeout=240.0)
        it = iter(call)
        # turn 1: full generation, then the turn marker
        call.send({"tokens": [5, 17, 42], "max_new": 4})
        msgs = [next(it) for _ in range(5)]
        assert [m for m in msgs if "token" in m] and msgs[-1] == {"turn_done": True}
        # turn 2: cancel mid-generation — with ONE slot, the engine can
        # only serve the follow-up if the cancel released it
        call.send({"tokens": [1, 2, 3], "max_new": 1000})
        assert "token" in next(it)
        call.cancel()
        # generous deadline for the same loaded-CI reason as the call
        # timeouts above: RST propagation + handler teardown + slot
        # release can stretch well past a couple of seconds under load
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if gen.stats()["active"] == 0 and gen._pending.qsize() == 0:
                break
            time.sleep(0.02)
        assert gen.stats()["active"] == 0
        # a fresh turn on a NEW call must get the (only) slot
        call2 = ch.bidi_stream("/llm.Generation/Chat", timeout=240.0)
        call2.send({"tokens": [9, 9], "max_new": 3})
        call2.close_send()
        toks = [m["token"] for m in call2 if "token" in m]
        assert len(toks) == 3
        ch.close()
    finally:
        app.stop()


# -- the first message's path, once, on the serving timeline ------------------

@pytest.mark.parametrize("path", ["iterator", "push"])
def test_one_first_event_a_stream_with_ordered_stamps(path):
    """Where a stream's first message reaches the socket the transport
    writes ONE timeline ``first`` event with the stamps the code already
    takes, in order: request HEADERS received, (engine submit and
    first_put, which only the push path can see: it holds the GenStream),
    transport got it, header encode, the coalesced write."""
    from gofr_tpu import App
    from gofr_tpu.config import MapConfig
    from gofr_tpu.grpcx import ServerStream

    app = App(MapConfig({"GRPC_PORT": "0", "METRICS_PORT": "0",
                         "TPU_MODEL": "tiny", "TPU_MAX_SEQ": "64",
                         "TPU_SLOTS": "2", "TPU_SEQ_BUCKETS": "8,16"}))
    llm = GRPCService("llm.Generation")

    @llm.server_stream("Generate")
    def generate(ctx, req):
        stream = ctx.tpu.generate(req["tokens"], max_new_tokens=5)
        if path == "push":
            return ServerStream(stream, lambda tok: {"token": tok})
        return ({"token": tok} for tok in stream)

    app.register_grpc_service(llm)
    app.run(block=False)
    try:
        ch = dial(f"127.0.0.1:{app.grpc_port}")
        for prompt in ([5, 17, 42], [7, 8, 9, 10]):
            toks = list(ch.server_stream("/llm.Generation/Generate",
                                         {"tokens": prompt}, timeout=240.0))
            assert len(toks) == 5
        ch.close()
        firsts = [e for e in app.container.observe.timeline.events()
                  if e[3] == "first"]
    finally:
        app.stop()
    assert len(firsts) == 2  # one a stream, not one a token
    for _, ts, dur, _, rid, trace_id, (headers, submit, first_put, got), \
            (enc0, enc1, write0, write1) in firsts:
        assert dur is None and ts == write1
        assert len(trace_id) == 32  # the RPC span's: joins logs and spans
        if path == "push":
            assert isinstance(rid, int)
            order = [headers, submit, first_put, got, enc0, enc1, write0,
                     write1]
        else:
            assert rid is None and submit is None and first_put is None
            order = [headers, got, enc0, enc1, write0, write1]
        assert all(isinstance(t, float) for t in order)
        assert order == sorted(order)
    assert firsts[0][4] != firsts[1][4] or path == "iterator"
