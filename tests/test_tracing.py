import time

from gofr_tpu.tracing import (InMemoryExporter, Span, TailSampler, Tracer,
                              ZipkinExporter, current_span,
                              parse_traceparent)


def test_traceparent_parse():
    assert parse_traceparent("00-" + "a" * 32 + "-" + "b" * 16 + "-01") == ("a" * 32, "b" * 16)
    assert parse_traceparent("garbage") is None
    assert parse_traceparent(None) is None
    assert parse_traceparent("00-short-bad-01") is None


def test_traceparent_rejects_all_zero_ids():
    # W3C Trace Context: all-zero trace-id / parent-id are defined
    # invalid — a malformed inbound header must start a FRESH trace, not
    # stitch every such request into "trace 000..0"
    assert parse_traceparent("00-" + "0" * 32 + "-" + "b" * 16 + "-01") is None
    assert parse_traceparent("00-" + "a" * 32 + "-" + "0" * 16 + "-01") is None
    t = Tracer("svc")
    s = t.start_span("inbound", traceparent="00-" + "0" * 32 + "-" + "0" * 16 + "-01")
    try:
        assert s.trace_id != "0" * 32 and s.parent_id is None
    finally:
        s.end()


def test_span_nesting_and_export():
    exp = InMemoryExporter()
    t = Tracer("svc", exporter=exp)
    with t.span("outer") as outer:
        assert current_span() is outer
        with t.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert current_span() is inner
        assert current_span() is outer
    assert current_span() is None
    assert [s.name for s in exp.spans] == ["inner", "outer"]
    assert exp.spans[0].duration_us >= 0


def test_remote_parent_via_traceparent():
    t = Tracer("svc")
    s = t.start_span("inbound", traceparent="00-" + "1" * 32 + "-" + "2" * 16 + "-01")
    assert s.trace_id == "1" * 32
    assert s.parent_id == "2" * 16
    s.end()


def test_record_span_exports_interval_without_context_stack():
    # the serving loop measures stages itself (one thread multiplexes
    # every request) — record_span must export the interval as-is and
    # never touch the current-span contextvar
    exp = InMemoryExporter()
    t = Tracer("svc", exporter=exp)
    parent = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
    s = t.record_span("tpu.prefill", 10.0, 10.25, traceparent=parent,
                      attributes={"slot": 3})
    assert current_span() is None
    assert s.trace_id == "a" * 32 and s.parent_id == "b" * 16
    assert abs(s.duration_us - 250_000) < 1000
    assert exp.spans == [s]
    assert s.attributes == {"slot": 3}


# -- tail-based sampling -----------------------------------------------------

def _span(name, trace_id, *, root=False, dur_us=1000, **attrs):
    s = Span(name=name, trace_id=trace_id, span_id="b" * 16, root=root,
             attributes=dict(attrs))
    s.end_ns = s.start_ns + dur_us * 1000
    return s


def test_tail_sampler_keeps_error_shed_and_expired_traces():
    # rate 0: NOTHING healthy survives, so anything exported must have
    # been kept by the must-keep rules — the deterministic form of the
    # "100% of shed/expired/error" acceptance criterion
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=0.0)
    cases = {
        "e1" * 16: _span("tpu.shed", "e1" * 16),                # shed marker
        "e2" * 16: _span("GET /x", "e2" * 16, root=True,
                         **{"http.status_code": 500}),          # 5xx error
        "e3" * 16: _span("GET /y", "e3" * 16, root=True,
                         **{"http.status_code": 429}),          # shed
        "e4" * 16: _span("grpc/p", "e4" * 16, root=True,
                         **{"rpc.grpc.status_code": 4}),        # deadline
        "e5" * 16: _span("tpu.decode", "e5" * 16,
                         error="device lost"),                  # error attr
    }
    for s in cases.values():
        ts.export(s, "svc")
    ts.flush_pending()  # settle rootless traces
    kept = {s.trace_id for s in exp.spans}
    assert kept == set(cases)

    # healthy traces at rate 0: buffered, then dropped at the verdict
    healthy = _span("GET /ok", "a0" * 16, root=True,
                    **{"http.status_code": 200})
    ts.export(healthy, "svc")
    assert all(s.trace_id != "a0" * 16 for s in exp.spans)
    assert ts.stats()["dropped_traces"] == 1


def test_tail_sampler_buffers_whole_trace_until_root_and_keeps_order():
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=1.0)
    tid = "ab" * 16
    ts.export(_span("tpu.prefill", tid), "svc")
    ts.export(_span("tpu.decode", tid), "svc")
    assert exp.spans == []  # buffered: no root yet
    ts.export(_span("GET /gen", tid, root=True), "svc")
    assert [s.name for s in exp.spans] == ["tpu.prefill", "tpu.decode",
                                           "GET /gen"]
    # late span of a decided trace follows the verdict immediately
    ts.export(_span("tpu.late", tid), "svc")
    assert exp.spans[-1].name == "tpu.late"


def test_tail_sampler_rate_is_deterministic_in_the_trace_id():
    # hash-fraction sampling: the FIRST 13 hex chars decide, so these
    # two ids straddle any 0.5 rate deterministically
    low = "0" * 32   # fraction 0.0 -> kept at rate 0.5
    high = "f" * 32  # fraction ~1.0 -> dropped at rate 0.5
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=0.5)
    ts.export(_span("a", low, root=True), "svc")
    ts.export(_span("b", high, root=True), "svc")
    kept = {s.trace_id for s in exp.spans}
    assert low in kept and high not in kept


def test_tail_sampler_keeps_slow_tail_above_rolling_p99():
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=0.0, min_samples=20)
    # warm the latency estimator with healthy fast roots (all dropped
    # at rate 0) ...
    for i in range(30):
        tid = f"{i:02d}" * 16
        ts.export(_span("GET /fast", tid, root=True, dur_us=1000), "svc")
    assert exp.spans == []
    # ... then a root far above the rolling p99 must be kept
    slow = _span("GET /slow", "ee" * 16, root=True, dur_us=500_000)
    ts.export(slow, "svc")
    assert [s.trace_id for s in exp.spans] == ["ee" * 16]


def test_tail_sampler_late_root_overrides_a_premature_drop_verdict():
    """A request longer than linger_s gets its stage spans swept and
    judged before the root finishes. When the root then arrives
    carrying an error (or slow-tail) signal, the verdict must FLIP:
    the root span — status, duration, slo_class — exports instead of
    being silently discarded against the stale drop."""
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=0.0, linger_s=0.0)
    tid = "dd" * 16
    ts.export(_span("tpu.prefill", tid), "svc")       # healthy stage span
    time.sleep(0.01)
    ts.export(_span("other", "11" * 16), "svc")       # triggers the sweep
    assert ts.stats()["dropped_traces"] >= 1          # judged prematurely
    root = _span("GET /gen", tid, root=True, **{"http.status_code": 504})
    ts.export(root, "svc")
    assert any(s is root for s in exp.spans)          # late root kept
    # and later spans of the flipped trace follow the kept verdict
    ts.export(_span("tpu.decode", tid), "svc")
    assert exp.spans[-1].name == "tpu.decode"
    # a healthy late root stays dropped
    ts.export(_span("GET /ok", "11" * 16, root=True,
                    **{"http.status_code": 200}), "svc")
    assert all(s.trace_id != "11" * 16 for s in exp.spans)


def test_tail_sampler_span_cap_never_drops_the_root_and_is_visible():
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=1.0, max_spans_per_trace=4)
    tid = "cc" * 16
    for i in range(10):
        ts.export(_span(f"stage{i}", tid), "svc")
    ts.export(_span("GET /gen", tid, root=True), "svc")
    names = [s.name for s in exp.spans]
    assert "GET /gen" in names          # root survived the full buffer
    assert len(names) == 5              # 4 buffered stages + the root
    assert ts.stats()["spans_truncated"] == 6


def test_tail_sampler_activity_refreshes_the_linger_window():
    # linger measures IDLE time: a trace still emitting spans is a live
    # request, not an orphan — it must not be swept mid-flight
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=0.0, linger_s=0.05)
    tid = "ab" * 16
    ts.export(_span("s0", tid), "svc")
    for _ in range(4):
        time.sleep(0.02)  # each gap < linger_s, total age > linger_s
        ts.export(_span("sN", tid), "svc")
    assert ts.stats()["pending_traces"] >= 1  # still buffered, not judged


def test_tail_sampler_judges_rootless_traces_after_linger():
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=0.0, linger_s=0.0)
    ts.export(_span("tpu.decode", "aa" * 16, error="x"), "svc")
    # a later export sweeps the lingered trace: interesting -> kept
    # even though no root ever arrived
    time.sleep(0.01)
    ts.export(_span("other", "bb" * 16), "svc")
    assert any(s.trace_id == "aa" * 16 for s in exp.spans)


def test_tail_sampler_flushes_idle_traces_without_further_traffic():
    """The idle sweeper: a rootless error trace buffered right before
    traffic STOPS must still reach the collector — no later export()
    call is ever coming to run the sweep for it."""
    exp = InMemoryExporter()
    ts = TailSampler(exp, sample_rate=0.0, linger_s=0.05)
    try:
        ts.export(_span("tpu.decode", "aa" * 16, error="device lost"),
                  "svc")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if any(s.trace_id == "aa" * 16 for s in exp.spans):
                break
            time.sleep(0.05)
        assert any(s.trace_id == "aa" * 16 for s in exp.spans), \
            "idle trace never flushed by the sweeper thread"
    finally:
        ts.shutdown()
    assert ts._thread is not None and not ts._thread.is_alive()


def test_start_span_marks_process_local_roots():
    t = Tracer("svc")
    root = t.start_span("inbound", traceparent="00-" + "1" * 32 + "-"
                        + "2" * 16 + "-01")
    child = t.start_span("inner")
    assert root.root is True       # no ambient parent -> local root
    assert child.root is False     # ambient parent -> not a root
    child.end()
    root.end()
    # record_span intervals never root (the serving loop's stage spans)
    exp = InMemoryExporter()
    t2 = Tracer("svc", exporter=exp)
    s = t2.record_span("tpu.prefill", 1.0, 2.0)
    assert s.root is False


# -- bounded export buffer ---------------------------------------------------

def test_zipkin_pending_buffer_is_bounded_when_collector_stalls(monkeypatch):
    import urllib.request

    from gofr_tpu.metrics import Manager, register_framework_metrics

    def down_collector(req, timeout=None):
        raise OSError("connection refused")

    monkeypatch.setattr(urllib.request, "urlopen", down_collector)
    m = Manager()
    register_framework_metrics(m)
    # flush interval long enough that the test controls every flush
    exp = ZipkinExporter("tracer.invalid", batch_size=10_000,
                         flush_interval=3600.0, max_pending=64, metrics=m)
    try:
        t = Tracer("svc", exporter=exp)
        for i in range(200):
            with t.span(f"s{i}"):
                pass
        with exp._lock:
            assert len(exp._buf) == 64          # bounded
            names = [z["name"] for z in exp._buf]
        assert names[0] == "s136" and names[-1] == "s199"  # newest kept
        assert exp.dropped == 136
        text = m.render_prometheus()
        assert "app_tpu_spans_dropped_total 136.0" in text
        # fail-open: a flush against the dead collector must not raise
        exp._flush()
        with exp._lock:
            assert len(exp._buf) == 0  # handed to the (failed) POST
    finally:
        exp.shutdown()


def test_zipkin_shutdown_joins_thread_and_flushes(monkeypatch):
    import urllib.request

    posted = []

    def fake_urlopen(req, timeout=None):
        import io
        import json

        posted.extend(json.loads(req.data))
        return io.BytesIO(b"")

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    # huge batch + interval: nothing flushes until shutdown
    exp = ZipkinExporter("tracer.invalid", batch_size=1000,
                         flush_interval=3600.0)
    t = Tracer("svc", exporter=exp)
    with t.span("buffered"):
        pass
    assert posted == []  # still buffered
    exp.shutdown()
    assert [z["name"] for z in posted] == ["buffered"]
    assert not exp._thread.is_alive()  # clean exits must not strand it


# -- nothing is built when nobody exports -------------------------------------

def test_ids_are_drawn_without_a_system_call(monkeypatch):
    """start_span gives every request a trace id (logs, exemplars and the
    timeline use it) from a generator seeded once per process: neither
    ``secrets`` nor ``os.urandom`` is called per span."""
    import os
    import secrets

    from gofr_tpu import tracing

    def forbidden(*a, **k):
        raise AssertionError("a per-span call into the entropy pool")

    monkeypatch.setattr(os, "urandom", forbidden)
    for name in ("token_hex", "token_bytes", "randbits"):
        monkeypatch.setattr(secrets, name, forbidden)
    assert not hasattr(tracing, "secrets")
    for exporter in (None, InMemoryExporter()):
        t = Tracer("svc", exporter=exporter)
        seen = set()
        for _ in range(200):
            s = t.start_span("inbound")
            s.end()
            assert len(s.trace_id) == 32 and len(s.span_id) == 16
            assert int(s.trace_id, 16) and int(s.span_id, 16)
            seen.add(s.trace_id)
            seen.add(s.span_id)
            t.record_span("tpu.prefill", 1.0, 2.0, trace_id=s.trace_id)
        assert len(seen) == 400  # unique, if not secret


def test_record_span_without_an_exporter_builds_nothing(monkeypatch):
    from gofr_tpu import tracing

    built = []
    real = tracing.Span

    class Counted(real):
        def __init__(self, *a, **k):
            built.append(1)
            super().__init__(*a, **k)

    monkeypatch.setattr(tracing, "Span", Counted)
    t = Tracer("svc")  # TRACER_HOST unset: no exporter
    assert t.record_span("tpu.decode", 1.0, 2.0, trace_id="ab" * 16,
                         attributes={"slot": 0}) is None
    assert built == []
    t.exporter = InMemoryExporter()
    s = t.record_span("tpu.decode", 1.0, 2.0, trace_id="ab" * 16,
                      attributes={"slot": 0})
    assert built == [1] and t.exporter.spans == [s]
    assert (s.name, s.trace_id, s.parent_id) == ("tpu.decode", "ab" * 16, None)
    assert s.duration_us == 1_000_000 and s.attributes == {"slot": 0}


def test_a_forked_child_seeds_the_ids_again(monkeypatch):
    """Two workers forked from one parent must not hand out the same
    trace ids: the hook registered for the child draws a new seed (run
    here by hand: forking a process that holds JAX's threads is unsafe)."""
    import os

    from gofr_tpu import tracing

    tracing._ids.seed(1234)
    parent_next = tracing._new_trace_id()
    tracing._ids.seed(1234)  # the state a fork would copy
    monkeypatch.setattr(os, "urandom", lambda n: b"\x07" * n)
    tracing._seed_ids()      # what os.register_at_fork runs in the child
    assert tracing._new_trace_id() != parent_next
    monkeypatch.undo()
    tracing._seed_ids()


# -- the serving loop's spans: as before with an exporter, none without -------

def _serve_three(observe):
    import jax

    from gofr_tpu.models import LLAMA_CONFIGS, llama
    from gofr_tpu.tpu import GenerationEngine

    cfg = LLAMA_CONFIGS["tiny"]
    eng = GenerationEngine(cfg, llama.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(8, 16),
                           observe=observe)
    try:
        streams = [eng.generate([3, 1, 4, 1 + i], max_new_tokens=6)
                   for i in range(3)]
        assert all(len(s.tokens()) == 6 for s in streams)
        # the terminal (and the spans made there) follows the last token
        deadline = time.monotonic() + 5.0
        while observe.requests.snapshot() and time.monotonic() < deadline:
            time.sleep(0.01)
        return streams
    finally:
        eng.close()


def test_stage_spans_with_an_exporter_are_as_before():
    """tpu.admit-wait, tpu.prefill and tpu.decode: one each a request, in
    the request's trace, back to back, with the attributes they always
    had. They are made at the request's terminal from the stamps in
    stream.trace, which is also what the wide event reports."""
    from gofr_tpu.observe import Observe

    exp = InMemoryExporter()
    streams = _serve_three(Observe(tracer=Tracer("svc", exporter=exp)))
    for s in streams:
        mine = {sp.name: sp for sp in exp.spans if sp.trace_id == s.trace_id}
        assert set(mine) == {"tpu.admit-wait", "tpu.prefill", "tpu.decode"}
        wait, prefill, decode = (mine["tpu.admit-wait"], mine["tpu.prefill"],
                                 mine["tpu.decode"])
        assert set(wait.attributes) == {"slot", "slo_class"}
        assert set(prefill.attributes) == {"slot", "prompt_len", "slo_class"}
        assert set(decode.attributes) == {"slot", "tokens", "slo_class"}
        assert prefill.attributes["prompt_len"] == 4
        assert decode.attributes["tokens"] == 6
        assert decode.attributes["slot"] in (0, 1)
        assert wait.attributes["slo_class"] == "latency"
        t = s.trace
        assert wait.start_ns == int(t["submit"] * 1e9)
        assert wait.end_ns == prefill.start_ns == int(t["admit"] * 1e9)
        assert prefill.end_ns == int(t["prefill_done"] * 1e9)
        assert decode.start_ns == int(t["first_put"] * 1e9)
        assert all(sp.parent_id is None and not sp.root
                   for sp in mine.values())


def test_no_tracing_work_on_the_generation_thread_without_an_exporter(
        monkeypatch):
    """TRACER_HOST unset: the tracer exists (requests still get trace
    ids) but exports to nobody, and the generation thread never enters
    tracing.py: no Span, no id, no record_span."""
    import threading

    from gofr_tpu import tracing
    from gofr_tpu.observe import Observe

    on_loop = []

    def watched(fn):
        def inner(*a, **k):
            if threading.current_thread().name == "gofr-tpu-gen":
                on_loop.append(fn.__name__)
            return fn(*a, **k)
        return inner

    for name in ("_new_trace_id", "_new_span_id"):
        monkeypatch.setattr(tracing, name, watched(getattr(tracing, name)))
    for name in ("record_span", "start_span", "_on_end"):
        monkeypatch.setattr(Tracer, name, watched(getattr(Tracer, name)))
    monkeypatch.setattr(tracing.Span, "__init__",
                        watched(tracing.Span.__init__))
    streams = _serve_three(Observe(tracer=Tracer("svc")))
    assert on_loop == []
    # every request still has a trace id of its own
    assert len({s.trace_id for s in streams}) == 3
    assert all(len(s.trace_id) == 32 for s in streams)
