import pytest

from gofr_tpu.metrics import (
    Manager,
    MetricAlreadyRegistered,
    MetricNotRegistered,
    register_framework_metrics,
    update_system_metrics,
)


def test_counter_lifecycle():
    m = Manager()
    m.new_counter("reqs", "total")
    m.increment_counter("reqs", path="/a")
    m.increment_counter("reqs", path="/a")
    m.increment_counter("reqs", path="/b")
    text = m.render_prometheus()
    assert '# TYPE reqs counter' in text
    assert 'reqs{path="/a"} 2.0' in text
    assert 'reqs{path="/b"} 1.0' in text


def test_duplicate_and_missing_registration():
    m = Manager()
    m.new_gauge("g")
    with pytest.raises(MetricAlreadyRegistered):
        m.new_gauge("g")
    with pytest.raises(MetricNotRegistered):
        m.increment_counter("nope")
    with pytest.raises(MetricNotRegistered):
        m.increment_counter("g")  # wrong kind


def test_updown_and_gauge():
    m = Manager()
    m.new_updown_counter("inflight")
    m.delta_updown_counter("inflight", 3)
    m.delta_updown_counter("inflight", -1)
    m.new_gauge("temp")
    m.set_gauge("temp", 42.5, zone="a")
    text = m.render_prometheus()
    assert "inflight 2.0" in text
    assert 'temp{zone="a"} 42.5' in text


def test_histogram_buckets_cumulative():
    m = Manager()
    m.new_histogram("lat", "latency", buckets=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 5.0, 50.0):
        m.record_histogram("lat", v)
    text = m.render_prometheus()
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1"} 2' in text
    assert 'lat_bucket{le="10"} 3' in text
    assert 'lat_bucket{le="+Inf"} 4' in text
    assert "lat_count 4" in text
    assert "lat_sum 55.55" in text


def test_label_value_escaping_roundtrip():
    # exposition format 0.0.4: label values escape backslash and quote;
    # a conformant scraper unescaping the rendered line must recover the
    # exact recorded value
    import re

    m = Manager()
    m.new_counter("esc")
    tricky = 'a\\b"c\\\\d'
    m.increment_counter("esc", path=tricky)
    text = m.render_prometheus()
    line = next(l for l in text.splitlines() if l.startswith("esc{"))
    match = re.fullmatch(r'esc\{path="((?:[^"\\]|\\.)*)"\} 1\.0', line)
    assert match, f"malformed exposition line: {line!r}"
    unescaped = re.sub(r"\\(.)", r"\1", match.group(1))
    assert unescaped == tricky


def _assert_histogram_monotone(text: str, name: str):
    import re

    buckets = []
    inf = count = None
    for line in text.splitlines():
        m_b = re.match(rf'{name}_bucket\{{le="([^"]+)"\}} (\d+)', line)
        if m_b:
            if m_b.group(1) == "+Inf":
                inf = int(m_b.group(2))
            else:
                buckets.append((float(m_b.group(1)), int(m_b.group(2))))
        elif line.startswith(f"{name}_count"):
            count = int(line.split()[-1])
    assert buckets and inf is not None and count is not None
    for (_, a), (_, b) in zip(buckets, buckets[1:]):
        assert a <= b, f"bucket counts not monotone in: {text}"
    assert buckets[-1][1] <= inf == count


def _hammer_histogram_while_scraping(m: Manager):
    import threading

    stop = threading.Event()

    def writer():
        while not stop.is_set():
            for v in (0.05, 0.5, 5.0, 50.0):
                m.record_histogram("lat", v)

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            _assert_histogram_monotone(m.render_prometheus(), "lat")
    finally:
        stop.set()
        for t in threads:
            t.join()
    # quiesced: every write fully applied, totals self-consistent
    _assert_histogram_monotone(m.render_prometheus(), "lat")


def test_histogram_concurrent_scrape_monotone_native():
    from gofr_tpu.native import available

    if not available():
        import pytest as _pytest

        _pytest.skip("native runtime unavailable")
    m = Manager()
    m.new_histogram("lat", "latency", buckets=[0.1, 1.0, 10.0])
    _hammer_histogram_while_scraping(m)


def test_histogram_concurrent_scrape_monotone_pure_python(monkeypatch):
    import gofr_tpu.native as native

    monkeypatch.setattr(native, "available", lambda: False)
    m = Manager()
    m.new_histogram("lat", "latency", buckets=[0.1, 1.0, 10.0])
    _hammer_histogram_while_scraping(m)
    # the fallback representation really was the locked python list
    assert all(type(v) is list for v in m._metrics["lat"].series.values())


def test_trace_ids_stitched_into_structured_log_lines():
    # every structured (JSON) log line emitted inside a span must carry
    # the span's trace/span ids — the log<->trace correlation the whole
    # observability story hangs on
    import io
    import json as _json

    from gofr_tpu.glog import Logger, LogLevel
    from gofr_tpu.tracing import Tracer

    buf = io.StringIO()
    log = Logger(level=LogLevel.INFO, out=buf, err=buf, pretty=False)
    t = Tracer("svc")
    with t.span("unit-of-work") as span:
        log.info({"event": "inside"})
    log.info({"event": "outside"})
    inside, outside = [_json.loads(l) for l in buf.getvalue().splitlines()]
    assert inside["trace_id"] == span.trace_id
    assert inside["span_id"] == span.span_id
    assert "trace_id" not in outside


def test_framework_metrics_register_and_system_update():
    m = Manager()
    register_framework_metrics(m)
    update_system_metrics(m)
    text = m.render_prometheus()
    assert "app_go_routines" in text
    assert "app_http_response" in text
    assert "app_tpu_predict_duration" in text
    # system gauges got real values
    assert "app_sys_memory_alloc 0.0" not in text


def test_parse_prometheus_sums_label_sets_of_the_rendered_text():
    from gofr_tpu.metrics import parse_prometheus

    m = Manager()
    m.new_counter("reqs", "requests")
    m.new_histogram("lat", "latency", buckets=(0.1, 1.0))
    m.increment_counter("reqs", path="/a")
    m.increment_counter("reqs", path="/a")
    m.increment_counter("reqs", path="/b")
    m.record_histogram("lat", 0.05, program="x")
    m.record_histogram("lat", 0.5, program="y")
    totals = parse_prometheus(m.render_prometheus())
    assert totals["reqs"] == 3.0
    assert totals["lat_count"] == 2.0
    assert totals["lat_sum"] == pytest.approx(0.55)
    assert parse_prometheus("# HELP x\nbroken line\n\n") == {}
