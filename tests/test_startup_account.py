"""The engine's account of its own start-up (observe/startup.py): phases
back to back from the configuration to ready, one record a warm-up call
from the compile clock's snapshots, the persistent cache's misses by
name, and every place the account is read: ``stats()["startup"]``, the
timeline's start-up track, the gauges, the ready line, the health check."""

import time

import jax
import pytest

from gofr_tpu import compile_cache
from gofr_tpu import metrics as gm
from gofr_tpu.config import MapConfig
from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.observe import Observe, StartupAccount, Timeline
from gofr_tpu.tpu import GenerationEngine, TPUEngine, new_engine_from_config
from gofr_tpu.tpu import programs

TINY = LLAMA_CONFIGS["tiny"]
PHASES = {"configure", "weights", "allocate", "programs", "warmup", "ready"}


class _Log:
    def __init__(self):
        self.lines = []

    def info(self, msg):
        self.lines.append(msg)

    debug = warn = error = info


@pytest.fixture(scope="module")
def started():
    """An engine from configuration, warmed by itself (TPU_WARMUP), with
    its logger, metrics and observability bundle."""
    m = gm.Manager()
    gm.register_framework_metrics(m)
    obs, log = Observe(metrics=m), _Log()
    eng = new_engine_from_config(MapConfig({
        "TPU_MODEL": "tiny", "TPU_SLOTS": "2", "TPU_MAX_SEQ": "64",
        "TPU_SEQ_BUCKETS": "16", "TPU_BATCH_BUCKETS": "1",
        "TPU_PREFIX_CACHE": "2", "TPU_WARMUP": "true"}), log, m, observe=obs)
    yield eng, obs, m, log
    eng.close()


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init(TINY, jax.random.PRNGKey(1))


def _engine(params, **kw):
    return GenerationEngine(TINY, params, slots=2, max_seq=64,
                            prompt_buckets=(16,), **kw)


def test_phases_are_back_to_back_and_sum_to_ready(started):
    st = started[0].generator.stats()["startup"]
    phases = [p for p in st["phases"] if p["t0"] < st["t_ready"]]
    assert {p["name"] for p in phases} == PHASES
    assert phases[0]["t0"] == st["t_start"] and phases[-1]["name"] == "ready"
    for a, b in zip(phases, phases[1:]):
        assert a["t0"] + a["seconds"] == pytest.approx(b["t0"], abs=1e-6)
    assert sum(p["seconds"] for p in phases) == pytest.approx(
        st["t_ready"] - st["t_start"], abs=1e-3)
    # the engine warmed itself: the first warm-up ends as it gets ready
    assert st["t_start"] < st["t_warm"] <= st["t_ready"]
    # every phase's end reads the device's memory (a CPU reports none)
    assert all("bytes_in_use" in p and "peak_bytes" in p for p in phases)
    assert started[0].stats()["startup"]["t_ready"] == st["t_ready"]


def test_phases_say_what_was_placed_and_allocated(started):
    st = started[0].generator.stats()["startup"]
    weights = next(p for p in st["phases"] if p["name"] == "weights")
    assert weights["leaves"] > 0 and weights["bytes"] > 0
    made = {p["tag"]: p["bytes"] for p in st["phases"]
            if p["name"] == "allocate"}
    assert set(made) == {"cache", "pool"} and all(made.values())


def test_the_ready_line_says_how_long_and_what_missed(started):
    st = started[0].generator.stats()["startup"]
    ready = next(x for x in started[3].lines
                 if x.get("event") == "tpu engine ready")
    assert ready["seconds"] == pytest.approx(st["t_ready"] - st["t_start"],
                                             abs=1e-3)
    assert len(ready["phases"]) == 3 and set(ready["phases"]) <= PHASES
    assert ready["cache_misses"] == len(st["missed"])
    assert ready["missed"] == st["missed"][:8]


def test_the_gauges_are_set_at_ready(started):
    st = started[0].generator.stats()["startup"]
    text = started[2].render_prometheus()
    warm = next(p for p in st["phases"] if p["name"] == "warmup")
    line = next(x for x in text.splitlines()
                if x.startswith('app_tpu_startup_seconds{phase="warmup"}'))
    assert float(line.rsplit(" ", 1)[1]) == pytest.approx(warm["seconds"])
    line = next(x for x in text.splitlines()
                if x.startswith("app_tpu_startup_cache_misses "))
    assert float(line.rsplit(" ", 1)[1]) == st["cache"]["misses"]


def test_health_check_drops_startup_once_ready(started):
    details = started[0].health_check().details
    assert "startup" not in details
    # the whole account is stats()' and /debug/vars', not every poll's
    assert "startup" not in details["generator"]


def test_health_check_shows_startup_until_ready():
    eng = TPUEngine(observe=Observe())
    try:
        assert "startup" not in eng.health_check().details  # not begun
        eng.startup.phase("configure")
        with eng.startup.warming() as acct:
            acct.expect(3)
            with acct.call("score", (1, 16)):
                pass
            said = eng.health_check().details["startup"]
            assert said["phase"] == "warmup"
            assert (said["programs_done"], said["programs_total"]) == (1, 3)
            assert said["seconds"] >= 0
        assert eng.health_check().details["startup"]["phase"] == "configure"
        eng.startup.finish()
        assert "startup" not in eng.health_check().details
    finally:
        eng.close()


def test_the_batchers_warmup_is_recorded_with_the_generators(started):
    eng = started[0]
    st = eng.generator.stats()["startup"]
    names = {p.attr for p in programs.TABLE} | set(eng._programs)
    assert [r["program"] for r in st["warmup"]][:1] == ["score"]
    assert {r["program"] for r in st["warmup"]} <= names
    assert "_step_jit" in {r["program"] for r in st["warmup"]}
    # one warm-up phase for both (engine.warmup calls the generator's)
    assert sum(p["name"] == "warmup" for p in st["phases"]) == 1
    assert all(r["pass"] == 0 for r in st["warmup"])


def test_warmup_records_sum_to_the_compile_clocks_deltas(tiny_params):
    eng = _engine(tiny_params)
    try:
        clock = compile_cache.clock()
        before = clock.snapshot()
        eng.warmup()
        after = clock.snapshot()
        st = eng.stats()["startup"]
        assert st["t_ready"] is None and st["t_warm"] is not None
        records = st["warmup"]
        # prefill and final chunk of the one bucket, two step signatures
        assert [(r["program"], tuple(r["shape"])) for r in records] == [
            ("_prefill_jit", (1, 16)), ("_chunk_final_jit", (1, 16)),
            ("_chunk_mid_jit", (1, 16)),
            ("_step_jit", (2, 4, "host carry")),
            ("_step_jit", (2, 4, "device carry"))]
        assert {r["program"] for r in records} <= {
            p.attr for p in programs.TABLE}
        assert sum(r["compile_seconds"] for r in records) == pytest.approx(
            after["seconds"] - before["seconds"])
        for k in ("hits", "misses"):
            assert sum(r[k] for r in records) == after[k] - before[k]
        warm = next(p for p in st["phases"] if p["name"] == "warmup")
        assert sum(r["seconds"] for r in records) <= warm["seconds"]
        # the second signature is the first one's program with its own
        # outputs fed back: warmed, not compiled again
        assert records[-1]["compile_seconds"] == 0.0
    finally:
        eng.close()


def test_a_second_engine_in_the_process_misses_nothing(tiny_params):
    first = _engine(tiny_params)
    try:
        first.warmup()
    finally:
        first.close()
    second = _engine(tiny_params)
    try:
        second.warmup()
        st = second.stats()["startup"]
        # every program of the first engine's is found in the cache
        assert sum(r["misses"] for r in st["warmup"]) == 0
        assert sum(r["hits"] for r in st["warmup"]) >= 4
        assert st["cache"]["misses"] == 0 and st["missed"] == []
    finally:
        second.close()


def test_a_warmup_while_serving_appends_and_rewrites_nothing(tiny_params):
    eng = _engine(tiny_params)
    try:
        eng.warmup()
        st = eng.stats()["startup"]
        assert len(eng.generate([5, 17, 42], max_new_tokens=6).tokens()) == 6
        eng.warmup()
        again = eng.stats()["startup"]
        n = len(st["warmup"])
        assert again["warmup"][:n] == st["warmup"]
        assert [r["pass"] for r in again["warmup"][n:]] == [1] * n
        assert again["phases"][:len(st["phases"])] == st["phases"]
        assert [(p["name"], p["pass"]) for p in again["phases"]
                if p["name"] == "warmup"] == [("warmup", 0), ("warmup", 1)]
        # what set-up's metrics read stays set-up's
        for k in ("t_warm", "cache", "missed"):
            assert again[k] == st[k]
    finally:
        eng.close()


def test_a_miss_is_named_by_the_label_and_the_jitted_function():
    tl = Timeline(capacity=64)
    acct = StartupAccount(tl)
    clock = compile_cache.clock()
    keep, clock.timeline = clock.timeline, tl
    # JAX counts a miss where it writes the program to the cache, which
    # it does not for one that compiled in less than this
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        acct.phase("configure")
        n = len(clock.missed)

        def _never_seen_before(x):
            return x * 3 + time.monotonic_ns() % 1_000_003  # new a trace

        with acct.warming() as w, w.call("_step_jit", (2, 4)):
            assert clock.label == "_step_jit(2, 4)"
            jax.block_until_ready(jax.jit(_never_seen_before)(1.0))
        assert clock.label == "configure"
        assert clock.missed[n:] == ["_step_jit(2, 4) jit(_never_seen_before)"]
        assert acct.stats()["missed"] == clock.missed[n:]
        assert acct.stats()["cache"]["misses"] == 1
        marks = [e for e in tl.events() if e[3] == "compile"]
        assert marks[-1][5] == "_step_jit(2, 4) jit(_never_seen_before)"
        acct.finish()
        assert clock.label == compile_cache.SERVING
        # what misses after the first warm-up is named apart from set-up's
        jax.block_until_ready(jax.jit(_never_seen_before)(2))
        said = acct.stats()
        assert said["missed"] == clock.missed[n:n + 1]
        assert said["missed_later"] == ["serving jit(_never_seen_before)"]
        assert said["cache"] == {"hits": 0, "misses": 1, "programs": 1}
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
        clock.timeline = keep
        clock.label = compile_cache.SERVING


def test_chrome_trace_carries_the_startup_track(started):
    trace = started[1].timeline.chrome_trace()["traceEvents"]
    track = next(e for e in trace if e.get("name") == "thread_name"
                 and e["args"]["name"] == "start-up")
    mine = [e for e in trace if e.get("cat") == "startup"]
    assert mine and all(e["ph"] == "X" and e["tid"] == track["tid"]
                        for e in mine)
    names = [e["name"] for e in mine]
    assert {"configure", "weights", "allocate cache", "allocate pool",
            "programs", "warmup", "ready"} <= set(names)
    # a warm-up call lies inside the warm-up phase, under its program
    warm = next(e for e in mine if e["name"] == "warmup")
    step = next(e for e in mine if e["name"].startswith("warmup _step_jit"))
    assert warm["ts"] <= step["ts"]
    assert step["ts"] + step["dur"] <= warm["ts"] + warm["dur"] + 1.0
    assert step["args"] == {"phase": "warmup", "detail": step["name"][7:],
                            "seq": step["args"]["seq"]}
    # the clock marks compiles from the first phase on, the weights' too
    assert any(e.get("cat") == "compile"
               and e["args"]["what"].startswith("weights ") for e in trace)


def test_spans_go_out_only_with_an_exporter():
    from gofr_tpu.tracing import InMemoryExporter, Tracer

    exporter = InMemoryExporter()
    for tracer, want in ((Tracer("t"), 0), (Tracer("t", exporter), 4)):
        acct = StartupAccount(tracer=tracer)
        acct.phase("configure")
        acct.phase("weights")
        acct.finish()
        assert len(exporter.spans) == want
    root = next(s for s in exporter.spans if s.name == "tpu.startup")
    kids = [s for s in exporter.spans if s is not root]
    assert [s.name for s in kids] == [
        "tpu.startup.configure", "tpu.startup.weights", "tpu.startup.ready"]
    assert all(s.parent_id == root.span_id and s.trace_id == root.trace_id
               for s in kids)
