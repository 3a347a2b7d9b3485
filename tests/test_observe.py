"""Tests for gofr_tpu/observe — the inference flight recorder and the
/debug introspection pages, unit-level and through the full App
(HTTP -> batcher -> generator) on the CPU backend."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from gofr_tpu import App
from gofr_tpu.config import MapConfig
from gofr_tpu.observe import FlightRecorder, RequestRegistry
from gofr_tpu.observe.profiler import collect_profile, render_collapsed


def _get(port, path, timeout=10):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


# -- registry ---------------------------------------------------------------

def test_registry_add_update_remove():
    reg = RequestRegistry()
    a = reg.add("http", "GET /x", "ab" * 16, stage="handler")
    b = reg.add("generate", "generate", stage="queued",
                detail={"prompt_len": 7})
    assert len(reg) == 2 and reg.total_started == 2
    b.stage = "decode"
    b.tokens = 5
    snap = reg.snapshot()
    assert [e["name"] for e in snap] == ["GET /x", "generate"]  # oldest first
    gen = snap[1]
    assert gen["stage"] == "decode" and gen["tokens"] == 5
    assert gen["detail"] == {"prompt_len": 7}
    assert gen["age_s"] >= 0
    assert snap[0]["trace_id"] == "ab" * 16
    reg.remove(a)
    reg.remove(a)  # idempotent
    reg.remove(None)  # tolerated
    assert len(reg) == 1
    reg.remove(b)
    assert reg.snapshot() == []


# -- flight recorder --------------------------------------------------------

def test_recorder_ring_buffer_and_filters():
    rec = FlightRecorder(capacity=4)
    for i in range(6):
        rec.record("submitted", request_id=i, prompt_len=i * 10)
    rec.record("finished", request_id=5, tokens=3)
    events = rec.events()
    assert len(events) == 4  # bounded: oldest fell off
    assert rec.stats() == {"capacity": 4, "buffered": 4,
                           "total_recorded": 7, "dropped": 3}
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    assert rec.events(event="finished")[0]["tokens"] == 3
    assert all(e["request_id"] == 5 for e in rec.events(request_id=5))
    assert len(rec.events(limit=2)) == 2
    assert rec.events(since_seq=events[-1]["seq"]) == []


def test_recorder_rejects_bad_capacity():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# -- profiler ---------------------------------------------------------------

def test_profiler_collapsed_stacks_capture_a_named_thread():
    marker = threading.Event()

    def parked_in_wait_for_profiler():
        marker.wait(10.0)

    t = threading.Thread(target=parked_in_wait_for_profiler,
                         name="observe-test-parked")
    t.start()
    try:
        counts = collect_profile(seconds=0.25, hz=200)
    finally:
        marker.set()
        t.join()
    text = render_collapsed(counts)
    assert "observe-test-parked;" in text
    assert "parked_in_wait_for_profiler" in text
    line = next(l for l in text.splitlines()
                if l.startswith("observe-test-parked;"))
    stack, count = line.rsplit(" ", 1)
    assert int(count) >= 1
    # root-first: the thread entry point precedes the leaf wait frame
    assert stack.index("parked_in_wait_for_profiler") < stack.index("wait")


# -- /debug pages on a plain app (no TPU) -----------------------------------

@pytest.fixture
def app():
    a = App(MapConfig({"HTTP_PORT": "0", "METRICS_PORT": "0",
                       "APP_NAME": "observe-test",
                       "API_SECRET_TOKEN": "hush"}))
    yield a
    if a._running.is_set():
        a.stop()


def test_debug_requests_shows_inflight_http_request(app):
    release = threading.Event()

    @app.get("/slow")
    def slow(ctx):
        release.wait(30.0)
        return "done"

    app.run(block=False)
    t = threading.Thread(target=lambda: _get(app.http_port, "/slow", 60))
    t.start()
    try:
        entry = None
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and entry is None:
            _, body, _ = _get(app.metrics_port, "/debug/requests?format=json")
            active = json.loads(body)["active"]
            entry = next((e for e in active if e["name"] == "GET /slow"), None)
            time.sleep(0.02)
        assert entry is not None, "in-flight request never appeared"
        assert entry["kind"] == "http" and entry["stage"] == "handler"
        assert len(entry["trace_id"]) == 32  # stitched from the tracer span
        assert entry["age_s"] >= 0
        # the HTML rendering carries the same rows
        _, html_body, headers = _get(app.metrics_port, "/debug/requests")
        assert "text/html" in headers["Content-Type"]
        assert b"GET /slow" in html_body
    finally:
        release.set()
        t.join(timeout=30)
    # after completion the table drains
    _, body, _ = _get(app.metrics_port, "/debug/requests?format=json")
    assert all(e["name"] != "GET /slow"
               for e in json.loads(body)["active"])


def test_debug_vars_redacts_secrets_and_reports_topology(app):
    app.run(block=False)
    _, body, _ = _get(app.metrics_port, "/debug/vars")
    payload = json.loads(body)
    assert payload["app"]["name"] == "observe-test"
    assert payload["config"]["API_SECRET_TOKEN"] == "<redacted>"
    assert payload["devices"]["platform"] == "cpu"
    assert payload["devices"]["devices"] == 8
    assert payload["recorder"]["capacity"] == 2048


def test_debug_index_and_pprof_profile(app):
    app.run(block=False)
    status, body, _ = _get(app.metrics_port, "/debug")
    assert status == 200 and b"/debug/pprof/profile" in body
    status, body, headers = _get(app.metrics_port,
                                 "/debug/pprof/profile?seconds=0.2&hz=200")
    assert status == 200
    assert "text/plain" in headers["Content-Type"]
    assert int(headers["X-Profile-Samples"]) > 0
    # collapsed-stack lines: "frame;frame;... count"
    first = body.decode().splitlines()[0]
    stack, count = first.rsplit(" ", 1)
    assert ";" in stack and int(count) >= 1
    # guard rails on the knobs
    status, _, _ = _get(app.metrics_port, "/debug/pprof/profile?seconds=9999")
    assert status == 400
    status, _, _ = _get(app.metrics_port, "/debug/pprof/profile?seconds=nan2")
    assert status == 400
    # an unbounded sample rate would busy-spin the GIL for the window
    status, _, _ = _get(app.metrics_port,
                        "/debug/pprof/profile?seconds=1&hz=1000000000")
    assert status == 400


def test_debug_events_bad_request_id_is_400(app):
    app.run(block=False)
    status, _, _ = _get(app.metrics_port, "/debug/events?request_id=xyz")
    assert status == 400


def test_debug_cache_without_engine_reports_disabled(app):
    """/debug/cache on an app with no TPU generator: valid JSON, not a
    500 — the page must degrade like the rest of the debug surface."""
    app.run(block=False)
    status, body, _ = _get(app.metrics_port, "/debug/cache")
    assert status == 200
    payload = json.loads(body)
    assert payload == {"enabled": False, "cache": None}


# -- OpenMetrics exposition conformance -------------------------------------

def _manager_with_samples(with_exemplars):
    from gofr_tpu.metrics import Manager

    m = Manager()
    m.new_histogram("lat", "latency", buckets=[0.1, 1.0, 10.0])
    m.new_counter("hits_total", "hits")
    m.new_gauge("depth", "queue depth")
    tid = "ab" * 16
    m.record_histogram("lat", 0.05, exemplar=tid if with_exemplars else None,
                       route="/a")
    m.record_histogram("lat", 50.0, exemplar=tid if with_exemplars else None,
                       route="/a")
    m.increment_counter("hits_total",
                        exemplar=tid if with_exemplars else None)
    m.set_gauge("depth", 3.0)
    return m


def test_openmetrics_exemplars_only_on_bucket_and_total_lines():
    m = _manager_with_samples(with_exemplars=True)
    text = m.render_openmetrics()
    with_ex = [l for l in text.splitlines() if " # {" in l]
    # exemplars land exactly where the spec allows: histogram bucket
    # lines and the counter _total sample — never _sum/_count/gauges
    assert with_ex, "no exemplar rendered"
    for line in with_ex:
        assert line.startswith("lat_bucket") or line.startswith("hits_total")
    assert not any(l.startswith(("lat_sum", "lat_count", "depth")) and "#" in l
                   for l in text.splitlines() if not l.startswith("# "))
    # the exemplar carries the trace id, value, and a timestamp
    bucket_line = next(l for l in with_ex if l.startswith('lat_bucket'))
    assert '# {trace_id="' + "ab" * 16 + '"}' in bucket_line
    # the 0.05 exemplar sits on the le="0.1" bucket, the 50.0 one on +Inf
    assert any('le="0.1"' in l and "0.05" in l for l in with_ex)
    assert any('le="+Inf"' in l and "50" in l for l in with_ex)


def test_openmetrics_terminates_with_eof_and_names_counter_family():
    m = _manager_with_samples(with_exemplars=False)
    text = m.render_openmetrics()
    assert text.endswith("# EOF\n")
    assert text.count("# EOF") == 1
    # counter family drops _total on TYPE/HELP; samples keep it
    assert "# TYPE hits counter" in text
    assert "hits_total 1.0" in text
    assert "# TYPE lat histogram" in text
    assert "# TYPE depth gauge" in text


def test_openmetrics_label_escaping_roundtrip():
    import re

    from gofr_tpu.metrics import Manager

    m = Manager()
    m.new_counter("esc_total")
    tricky = 'a\\b"c\\\\d'
    m.increment_counter("esc_total", path=tricky, exemplar='t"\\id')
    text = m.render_openmetrics()
    line = next(l for l in text.splitlines() if l.startswith("esc_total{"))
    sample = line.split(" # ")[0]
    match = re.fullmatch(r'esc_total\{path="((?:[^"\\]|\\.)*)"\} 1\.0',
                         sample)
    assert match, f"malformed exposition line: {line!r}"
    assert re.sub(r"\\(.)", r"\1", match.group(1)) == tricky
    # the exemplar labelset escapes the same way
    ex = line.split(" # ", 1)[1]
    ex_match = re.fullmatch(r'\{trace_id="((?:[^"\\]|\\.)*)"\} 1 [0-9.]+', ex)
    assert ex_match, f"malformed exemplar: {ex!r}"
    assert re.sub(r"\\(.)", r"\1", ex_match.group(1)) == 't"\\id'


def test_prometheus_text_is_byte_identical_with_and_without_exemplars():
    # recording exemplars must not perturb the 0.0.4 exposition AT ALL:
    # scrapers that never opted into OpenMetrics see identical bytes
    a = _manager_with_samples(with_exemplars=True)
    b = _manager_with_samples(with_exemplars=False)
    assert a.render_prometheus() == b.render_prometheus()
    assert " # {" not in a.render_prometheus()
    assert "# EOF" not in a.render_prometheus()


def test_metrics_endpoint_content_negotiation(app):
    app.run(block=False)
    # default: Prometheus 0.0.4, no EOF, no exemplar syntax
    _, body, headers = _get(app.metrics_port, "/metrics")
    assert "text/plain" in headers["Content-Type"]
    assert "0.0.4" in headers["Content-Type"]
    assert b"# EOF" not in body
    # explicit Accept: OpenMetrics with the versioned content type
    req = urllib.request.Request(
        f"http://127.0.0.1:{app.metrics_port}/metrics",
        headers={"Accept": "application/openmetrics-text"})
    with urllib.request.urlopen(req, timeout=10) as r:
        om_headers = dict(r.headers)
        om_body = r.read()
    assert "application/openmetrics-text" in om_headers["Content-Type"]
    assert om_body.endswith(b"# EOF\n")


def test_debug_events_html_renders_seq_and_trace_id(app):
    app.run(block=False)
    app.container.observe.recorder.record(
        "submitted", request_id=1, trace_id="cd" * 16, prompt_len=3)
    status, body, headers = _get(app.metrics_port,
                                 "/debug/events?format=html")
    assert status == 200 and "text/html" in headers["Content-Type"]
    assert b"<th>seq</th>" in body and b"<th>trace_id</th>" in body
    assert ("cd" * 16).encode() in body


def test_debug_timeline_page_serves_chrome_trace(app):
    app.run(block=False)
    tl = app.container.observe.timeline
    tl.decode_block(time.monotonic() - 0.01, time.monotonic(), (0,), 4)
    status, body, _ = _get(app.metrics_port, "/debug/timeline")
    assert status == 200
    payload = json.loads(body)
    assert "traceEvents" in payload
    assert any(e.get("cat") == "decode" for e in payload["traceEvents"])
    # the trailing-window filter drops events older than last_ms
    status, body, _ = _get(app.metrics_port,
                           "/debug/timeline?last_ms=0.001")
    assert not any(e.get("cat") == "decode"
                   for e in json.loads(body)["traceEvents"])
    status, body, _ = _get(app.metrics_port,
                           "/debug/timeline?format=stats")
    assert json.loads(body)["enabled"] is True
    status, _, _ = _get(app.metrics_port, "/debug/timeline?last_ms=zzz")
    assert status == 400
    # float() parses nan/inf happily; they must still 400, not return
    # a silently empty trace
    for bad in ("nan", "inf", "-5"):
        status, _, _ = _get(app.metrics_port,
                            f"/debug/timeline?last_ms={bad}")
        assert status == 400, f"last_ms={bad} accepted"


# -- acceptance: the full serving path on the CPU backend -------------------

def test_full_app_generation_flight_recorder_and_telemetry():
    """Drive HTTP -> batcher -> generator end to end: /debug/requests
    must show the in-flight generation (stage + age + trace id) WHILE it
    runs, and /metrics must expose non-empty TTFT and inter-token
    histograms after it completes (ISSUE acceptance criteria)."""
    from gofr_tpu.tracing import InMemoryExporter

    app = App(MapConfig({"HTTP_PORT": "0", "METRICS_PORT": "0",
                         "TPU_MODEL": "tiny", "TPU_MAX_SEQ": "128",
                         "TPU_SLOTS": "2", "TPU_SEQ_BUCKETS": "8,16"}))
    # capture exported spans so the TTFT exemplar's trace id can be
    # resolved against them (trace<->metric correlation acceptance)
    span_sink = InMemoryExporter()
    app.container.tracer.exporter = span_sink

    @app.get("/gen")
    def gen(ctx):
        return {"tokens": ctx.tpu.generate(
            [1, 2, 3], max_new_tokens=100).tokens()}

    app.run(block=False)
    try:
        results = []

        def client():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{app.http_port}/gen",
                    timeout=300) as r:
                results.append(json.loads(r.read()))

        t = threading.Thread(target=client)
        t.start()
        gen_entry = http_entry = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and gen_entry is None:
            _, body, _ = _get(app.metrics_port, "/debug/requests?format=json")
            active = json.loads(body)["active"]
            gen_entry = next((e for e in active if e["kind"] == "generate"),
                             None)
            http_entry = next((e for e in active if e["kind"] == "http"),
                              http_entry)
            time.sleep(0.02)
        assert gen_entry is not None, "generation never showed in-flight"
        assert gen_entry["stage"] in ("queued", "prefill", "decode")
        assert gen_entry["age_s"] >= 0
        assert len(gen_entry["trace_id"]) == 32
        # generate() inherited the HTTP request's trace context
        assert http_entry is not None and http_entry["name"] == "GET /gen"
        assert gen_entry["trace_id"] == http_entry["trace_id"]
        t.join(timeout=300)
        assert not t.is_alive()
        assert len(results[0]["data"]["tokens"]) == 100

        # -- /metrics: non-empty serving histograms --------------------------
        _, body, _ = _get(app.metrics_port, "/metrics")
        text = body.decode()

        def series_count(name):
            # TTFT carries the scheduler's slo_class label (untagged
            # traffic is latency-class — serving-scheduler.md); the
            # inter-token series stays program-only
            line = next(l for l in text.splitlines()
                        if l.startswith(f'{name}_count{{program="generate"'))
            return int(float(line.split()[-1]))

        assert series_count("app_tpu_ttft_duration") >= 1
        assert 'slo_class="latency"' in next(
            l for l in text.splitlines()
            if l.startswith('app_tpu_ttft_duration_count{'))
        assert series_count("app_tpu_inter_token_duration") >= 99
        assert 'app_tpu_active_sequences 0.0' in text  # drained
        assert 'app_tpu_queue_depth{program="generate"} 0.0' in text
        tps = next(l for l in text.splitlines()
                   if l.startswith("app_tpu_tokens_per_second"))
        assert float(tps.split()[-1]) > 0

        # -- /debug/events: the request's full lifecycle ----------------------
        rid = gen_entry["id"]
        _, body, _ = _get(app.metrics_port, "/debug/events")
        events = json.loads(body)["events"]
        mine = [e for e in events
                if e.get("trace_id") == gen_entry["trace_id"]]
        kinds = [e["event"] for e in mine]
        for expected in ("submitted", "finished", "request"):
            assert expected in kinds, f"missing {expected} in {kinds}"
        finished = next(e for e in mine if e["event"] == "finished")
        assert finished["tokens"] == 100
        assert finished["duration_s"] > 0
        # admission and the first token are stamped once and reported by
        # the request's one wide row (the recorder's own "admitted" and
        # "first_token" rows repeated the same intervals and are gone)
        row = next(e for e in mine if e["event"] == "request")
        assert row["ttft_s"] > 0
        assert row["queue_wait_s"] >= 0
        assert set(row["breakdown"]) == {"queue_wait_s", "prefill_s",
                                         "handoff_s", "decode_s"}
        assert "admitted" not in kinds and "first_token" not in kinds
        del rid

        # -- wide event: one canonical row reconstructs the request -----------
        wides = [e for e in mine if e["event"] == "request"]
        assert len(wides) == 1
        wide = wides[0]
        assert wide["outcome"] == "finished" and wide["tokens"] == 100
        assert wide["slo_class"] == "latency"
        assert wide["queue_wait_s"] >= 0 and wide["chunks"] == 0

        # -- exemplars: the TTFT bucket's trace id resolves to spans ----------
        req = urllib.request.Request(
            f"http://127.0.0.1:{app.metrics_port}/metrics",
            headers={"Accept": "application/openmetrics-text"})
        with urllib.request.urlopen(req, timeout=10) as r:
            om = r.read().decode()
        assert om.endswith("# EOF\n")
        ex_line = next(l for l in om.splitlines()
                       if l.startswith("app_tpu_ttft_duration_bucket")
                       and " # {" in l)
        ex_tid = ex_line.split('trace_id="', 1)[1].split('"', 1)[0]
        assert ex_tid == gen_entry["trace_id"]
        exported = {s.trace_id for s in span_sink.spans}
        assert ex_tid in exported  # the bucket links to real spans
        assert any(s.name == "tpu.prefill" and s.trace_id == ex_tid
                   for s in span_sink.spans)

        # -- timeline: the serving window exported the schedule ---------------
        _, body, _ = _get(app.metrics_port, "/debug/timeline")
        trace = json.loads(body)
        cats = {e.get("cat") for e in trace["traceEvents"]}
        assert "decode" in cats and "prefill" in cats

        # -- /debug/vars: engine + generator state ----------------------------
        _, body, _ = _get(app.metrics_port, "/debug/vars")
        payload = json.loads(body)
        assert payload["tpu"]["model"] == "tiny"
        assert payload["tpu"]["generator"]["total_requests"] >= 1
        assert "score" in payload["tpu"]["batchers"]
        assert payload["timeline"]["enabled"] is True
    finally:
        app.stop()
