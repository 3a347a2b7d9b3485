"""Tests for the serving timeline profiler (gofr_tpu/observe/timeline.py):
ring semantics, Chrome-trace/Perfetto export shape, hot-path emission
from a real serving window on the CPU backend, and the canonical wide
events that ride the same terminal paths."""

import io
import json
import threading
import time

import jax
import numpy as np
import pytest

from gofr_tpu.glog import Logger, LogLevel
from gofr_tpu.metrics import Manager, register_framework_metrics
from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.observe import Observe, Timeline
from gofr_tpu.observe.timeline import timeline_from_config
from gofr_tpu.resilience import AdmissionGate
from gofr_tpu.tpu import GenerationEngine
from gofr_tpu.errors import TooManyRequests


# -- ring semantics ----------------------------------------------------------

def test_ring_bounded_ordered_and_drop_accounting():
    tl = Timeline(capacity=8)
    for i in range(20):
        tl.append("k", float(i), None, i)
    ev = tl.events()
    assert len(ev) == 8  # bounded: oldest fell off
    seqs = [e[0] for e in ev]
    assert seqs == sorted(seqs) and seqs[-1] == 19
    st = tl.stats()
    assert st["capacity"] == 8 and st["buffered"] == 8
    assert st["total_recorded"] == 20 and st["dropped"] == 12


def test_ring_capacity_rounds_up_to_power_of_two():
    assert Timeline(capacity=100).capacity == 128
    with pytest.raises(ValueError):
        Timeline(capacity=1)


def test_disabled_timeline_records_nothing():
    tl = Timeline(capacity=8, enabled=False)
    tl.append("k", 0.0, None)
    tl.decode_block(0.0, 1.0, (0,), 4)
    tl.hbm("engine", 1.0)
    assert tl.events() == []
    assert tl.stats()["total_recorded"] == 0
    assert tl.chrome_trace()["otherData"]["enabled"] is False


def test_disabled_timeline_does_not_preallocate_the_ring():
    tl = Timeline(capacity=65536, enabled=False)
    assert len(tl._buf) == 2          # stub, not 64k dead pointers
    assert tl.stats()["capacity"] == 65536  # configured size still reported


def test_last_ms_window_filter():
    tl = Timeline(capacity=64)
    now = time.monotonic()
    tl.append("old", now - 10.0, None)
    tl.append("new", now, None)
    kinds = [e[3] for e in tl.events(last_ms=1000.0)]
    assert kinds == ["new"]
    assert [e[3] for e in tl.events()] == ["old", "new"]


def test_concurrent_append_stays_consistent():
    tl = Timeline(capacity=256)

    def hammer(base):
        for i in range(2000):
            tl.append("k", time.monotonic(), None, base + i)

    threads = [threading.Thread(target=hammer, args=(t * 10000,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ev = tl.events()
    assert 0 < len(ev) <= 256
    seqs = [e[0] for e in ev]
    assert seqs == sorted(seqs)
    json.dumps(tl.chrome_trace())  # always serializable


def test_timeline_from_config_knobs():
    from gofr_tpu.config import MapConfig

    tl = timeline_from_config(MapConfig({"TPU_TIMELINE": "0"}))
    assert tl.enabled is False
    tl = timeline_from_config(MapConfig({"TPU_TIMELINE_EVENTS": "100"}))
    assert tl.enabled is True and tl.capacity == 128
    tl = timeline_from_config(MapConfig({"TPU_TIMELINE_EVENTS": "junk"}))
    assert tl.capacity == 65536


# -- Chrome-trace export against a KNOWN synthetic schedule ------------------

def test_chrome_trace_shape_and_ordering_from_known_schedule():
    """Feed a hand-built serving window and assert the exported JSON is
    exactly the Perfetto view of it: per-slot tracks, named slices in
    schedule order, instants on the scheduler track, an HBM counter
    track."""
    tl = Timeline(capacity=256)
    t = 100.0
    tl.hbm("engine", 1024.0)
    tl.admit(0, "latency", 0.001, 7, "ab" * 16)
    tl.prefill(t, t + 0.010, 0, 48, 7, "ab" * 16)
    tl.chunk(t + 0.010, t + 0.012, 1, 0, 16, 8)
    tl.chunk(t + 0.014, t + 0.016, 1, 1, 16, 8)
    tl.decode_block(t + 0.020, t + 0.030, (0, 1), 4)
    tl.shed("generate", "throughput", "cd" * 16)
    tl.expired("queue", 9)
    tl.kvcache("t1", 32, 0)
    tr = tl.chrome_trace()
    ev = tr["traceEvents"]
    json.dumps(tr)

    names = {e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"scheduler", "slot 0", "slot 1"} <= names

    # per-slot decode slices: the one block expands to BOTH slot tracks
    decodes = [e for e in ev if e.get("cat") == "decode"]
    assert len(decodes) == 2
    assert {e["tid"] for e in decodes} == {10, 11}
    for e in decodes:
        assert e["ph"] == "X" and e["name"] == "decode x4"
        assert e["dur"] == pytest.approx(0.010 * 1e6)

    # chunk slices in schedule order on slot 1's track
    chunks = [e for e in ev if e.get("cat") == "chunk"]
    assert [c["args"]["chunk_index"] for c in chunks] == [0, 1]
    assert all(c["tid"] == 11 for c in chunks)

    prefill = next(e for e in ev if e.get("cat") == "prefill")
    assert prefill["tid"] == 10 and prefill["args"]["prompt_len"] == 48
    assert prefill["args"]["trace_id"] == "ab" * 16

    # instants: admit on the slot track, shed/expired on the scheduler
    admit = next(e for e in ev if e.get("name") == "admit")
    assert admit["ph"] == "i" and admit["tid"] == 10
    assert admit["args"]["request_id"] == 7
    shed = next(e for e in ev if e.get("name") == "shed generate")
    assert shed["tid"] == 1 and shed["args"]["slo_class"] == "throughput"
    assert any(e.get("name") == "expired queue" for e in ev)
    kv = next(e for e in ev if e.get("name") == "kvcache t1")
    assert kv["args"] == {"tier": "t1", "tokens": 32,
                          "seq": kv["args"]["seq"]}

    # counter track
    ctr = next(e for e in ev if e.get("ph") == "C")
    assert ctr["name"] == "hbm:engine" and ctr["args"]["bytes"] == 1024.0

    # body is globally ts-ordered (metadata rows lead)
    body = [e for e in ev if e.get("ph") != "M"]
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)


# -- a real serving window on the CPU backend --------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = LLAMA_CONFIGS["tiny"]
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(tiny, **kw):
    cfg, params = tiny
    defaults = dict(slots=2, max_seq=128, prompt_buckets=(8, 16, 32),
                    decode_block=4)
    defaults.update(kw)
    return GenerationEngine(cfg, params, **defaults)


def test_serving_window_exports_known_schedule(tiny):
    """Acceptance: a recorded window with chunked prefill + decode
    exports Chrome-trace JSON whose tracks and ordering match the run's
    known schedule — chunk slices with increasing index inside the long
    admission's prefill slice, decode slices on the active slots, HBM
    counter samples present."""
    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=8192))
    eng = _engine(tiny, metrics=m, observe=obs, prefill_chunk=16)
    try:
        rng = np.random.default_rng(1)
        cfgv = eng.cfg.vocab_size
        long_prompt = rng.integers(1, cfgv, 60).tolist()
        s_long = eng.generate(long_prompt, max_new_tokens=8)
        long_toks = s_long.tokens()
        s_short = eng.generate([1, 2, 3], max_new_tokens=8)
        short_toks = s_short.tokens()
        assert len(long_toks) == 8 and len(short_toks) == 8
        # known schedule: 60 tokens at a 16-token chunk budget = 3 mid
        # chunks (the final chunk samples inside the prefill dispatch)
        assert s_long.chunks == 3

        tr = obs.timeline.chrome_trace()
        ev = tr["traceEvents"]
        json.dumps(tr)

        chunks = [e for e in ev if e.get("cat") == "chunk"]
        assert [c["args"]["chunk_index"] for c in chunks] == [0, 1, 2]
        assert all(c["args"]["chunk_len"] == 16 for c in chunks)

        prefills = [e for e in ev if e.get("cat") == "prefill"]
        assert len(prefills) == 2
        long_pf = next(p for p in prefills if p["args"]["prompt_len"] == 60)
        # the chunk slices sit INSIDE the long admission's prefill span
        for c in chunks:
            assert long_pf["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= long_pf["ts"] + long_pf["dur"] + 1

        decodes = [e for e in ev if e.get("cat") == "decode"]
        assert decodes and all(d["name"] == "decode x4" for d in decodes)
        assert {d["tid"] for d in decodes} <= {10, 11}

        admits = [e for e in ev if e.get("name") == "admit"]
        assert len(admits) == 2
        assert all(a["args"]["slo_class"] == "latency" for a in admits)

        # hbm accounting fan-out produced at least the engine cache sample
        counters = [e for e in ev if e.get("ph") == "C"]
        assert any(e["name"] == "hbm:engine" for e in counters)

        # per-track ordering: every track's slices are ts-ordered
        by_tid = {}
        for e in ev:
            if e.get("ph") == "X":
                by_tid.setdefault(e["tid"], []).append(e["ts"])
        for tids in by_tid.values():
            assert tids == sorted(tids)
    finally:
        eng.close()


def test_timeline_off_emits_nothing_from_the_hot_path(tiny):
    obs = Observe(timeline=Timeline(capacity=256, enabled=False))
    eng = _engine(tiny, observe=obs)
    try:
        assert eng._tl is None  # hot paths hold None, not a dead ring
        assert eng.generate([1, 2, 3], max_new_tokens=4).tokens()
        assert obs.timeline.events() == []
    finally:
        eng.close()


# -- canonical wide events ---------------------------------------------------

def _wide_log_lines(buf):
    out = []
    for line in buf.getvalue().splitlines():
        try:
            entry = json.loads(line)
        except ValueError:
            continue
        msg = entry.get("message")
        if isinstance(msg, dict) and msg.get("event") == "request":
            out.append(msg)
    return out


def test_wide_event_on_finish_carries_canonical_fields(tiny):
    m = Manager()
    register_framework_metrics(m)
    buf = io.StringIO()
    log = Logger(level=LogLevel.INFO, out=buf, err=buf, pretty=False)
    obs = Observe(metrics=m, timeline=Timeline(capacity=1024))
    eng = _engine(tiny, metrics=m, observe=obs, logger=log,
                  prefill_chunk=16, prefix_cache_slots=0)
    try:
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, eng.cfg.vocab_size, 40).tolist()
        s = eng.generate(prompt, max_new_tokens=6)
        assert len(s.tokens()) == 6
        # recorder: one "request" row joinable by trace_id/request_id
        reqs = obs.recorder.events(event="request")
        assert len(reqs) == 1
        r = reqs[0]
        assert r["outcome"] == "finished" and r["tokens"] == 6
        assert r["slo_class"] == "latency"
        assert r["chunks"] == 2          # 40 tokens / 16-chunk budget
        assert r["request_id"] == s.request_id
        assert r["queue_wait_s"] >= 0 and r["duration_s"] > 0
        assert r["cache_tier"] is None and r["cache_tokens"] == 0
        # glog: the same dict on one greppable line
        wide = _wide_log_lines(buf)
        assert len(wide) == 1 and wide[0]["outcome"] == "finished"
        assert wide[0]["trace_id"] == s.trace_id
        assert wide[0]["chunks"] == 2
    finally:
        eng.close()


def test_wide_event_on_shed_and_expiry(tiny):
    m = Manager()
    register_framework_metrics(m)
    buf = io.StringIO()
    log = Logger(level=LogLevel.INFO, out=buf, err=buf, pretty=False)
    obs = Observe(metrics=m, timeline=Timeline(capacity=1024))
    gate = AdmissionGate(max_queue_depth=1, name="generate", metrics=m)
    eng = _engine(tiny, metrics=m, observe=obs, logger=log, gate=gate)
    try:
        # force a deterministic shed: make the gate see an over-depth
        # queue for exactly one generate() call
        orig = eng._pending.qsize
        eng._pending.qsize = lambda: 10
        try:
            with pytest.raises(TooManyRequests):
                eng.generate([1, 2, 3], max_new_tokens=4)
        finally:
            eng._pending.qsize = orig
        sheds = [r for r in obs.recorder.events(event="request")
                 if r["outcome"] == "shed"]
        assert len(sheds) == 1 and sheds[0]["sheds"] == 1
        shed_lines = [w for w in _wide_log_lines(buf)
                      if w["outcome"] == "shed"]
        assert len(shed_lines) == 1
        # timeline carries the shed marker too
        assert any(e[3] == "shed" for e in obs.timeline.events())

        # expiry: a request whose deadline lapses while it queues
        # behind a full slot pool emits a failed wide event naming the
        # expiry. Both slots are held by live streams when the doomed
        # request arrives, so it MUST wait past its tiny deadline.
        from gofr_tpu.resilience import Deadline
        from gofr_tpu.errors import DeadlineExceeded

        eng.gate = None
        blockers = [eng.generate([1, 2, 3], max_new_tokens=64)
                    for _ in range(2)]
        doomed = eng.generate([4, 5, 6], max_new_tokens=4,
                              deadline=Deadline.after(0.003))
        with pytest.raises(DeadlineExceeded):
            doomed.tokens()
        for b in blockers:
            b.tokens()
        fails = [r for r in obs.recorder.events(event="request")
                 if r["outcome"] == "failed"]
        assert fails and "expired" in fails[0]["error"]
        assert fails[0]["slo_class"] == "latency"
    finally:
        eng.close()


def test_wide_log_line_survives_a_raised_log_level():
    """The wide event is the per-request log contract: a deployment
    running at WARN to cut diagnostic noise must still get one line
    per request (glog.Logger.wide bypasses the level gate)."""
    buf = io.StringIO()
    log = Logger(level=LogLevel.WARN, out=buf, err=buf, pretty=False)
    log.info({"event": "diagnostic"})       # filtered as usual
    log.wide({"event": "request", "outcome": "finished"})
    lines = buf.getvalue().splitlines()
    assert len(lines) == 1
    entry = json.loads(lines[0])
    assert entry["message"]["event"] == "request"
    assert entry["level"] == "INFO"         # honestly labeled


# -- hot-path overhead guard -------------------------------------------------

def test_append_cost_is_sub_microsecond_scale():
    """The emission budget: one append must stay cheap enough for
    per-decode-block emission (<1µs target; the CI bound is generous
    for noisy shared runners)."""
    tl = Timeline(capacity=65536)
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        tl.append("decode", 0.0, 0.001, (0, 1), 4)
    per_event_us = (time.perf_counter() - t0) / n * 1e6
    assert per_event_us < 25.0, f"append cost {per_event_us:.2f}µs"

    off = Timeline(capacity=65536, enabled=False)
    t0 = time.perf_counter()
    for i in range(n):
        off.append("decode", 0.0, 0.001, (0, 1), 4)
    off_us = (time.perf_counter() - t0) / n * 1e6
    assert off_us < 5.0, f"disabled append cost {off_us:.2f}µs"


# -- the generation thread's own account of its time --------------------------

@pytest.mark.parametrize("kind", ["loop", "store", "first", "compile", "gap",
                                  "decode", "stall", "host"])
def test_loop_account_events_round_trip(kind):
    """loop, store, first and compile events (and the gap's slack, the
    decode block's live tokens, the watchdog's stall record and its
    once-a-second host sample) come back from events() as written and
    render on their tracks in chrome_trace()."""
    tl = Timeline(capacity=64)
    t = 200.0
    tl.loop(t, t + 0.004, "admit", 2)
    tl.store(t + 0.001, t + 0.003, 1, 600, "t0+host")
    tl.first(7, "ab" * 16, (t, t + 0.001, t + 0.2, t + 0.2001),
             (t + 0.2001, t + 0.2002, t + 0.2002, t + 0.2005))
    tl.compile(1.25)
    tl.dispatch_gap(t + 0.010, t + 0.012, 0.0005)
    tl.decode_block(t + 0.020, t + 0.030, (0, 1), 4, 1234)
    record = {"id": 3, "phase": "fetch", "cause": "fetch_late",
              "queue": [{"kind": "decode", "ready_after": 0.2}]}
    tl.stall(t + 0.040, t + 2.040, "fetch", "generator.py:_first_token",
             "fetch_late", 3, record)
    tl.host(t + 1.0, 0.25, 0.001)
    tl.host(t + 2.0, None, 0.0)
    assert tl.last("gap", before=t + 1.0)[1] == t + 0.010
    assert tl.last("gap", before=t) is None
    ev = next(e for e in tl.events() if e[3] == kind)
    tr = tl.chrome_trace()
    json.dumps(tr)
    rows = tr["traceEvents"]
    tracks = {e["tid"]: e["args"]["name"] for e in rows
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    if kind == "loop":
        assert ev[1:3] == (t, pytest.approx(0.004))
        assert ev[4:6] == ("admit", 2)
        row = next(e for e in rows if e.get("cat") == "loop")
        assert row["name"] == "loop:admit" and row["ph"] == "X"
        assert tracks[row["tid"]] == "host loop"
        assert row["args"]["n"] == 2
        assert row["dur"] == pytest.approx(4000.0)
    elif kind == "store":
        assert ev[4:7] == (1, 600, "t0+host")
        row = next(e for e in rows if e.get("cat") == "store")
        assert tracks[row["tid"]] == "slot 1"
        assert row["args"]["tier"] == "t0+host"
        assert row["args"]["tokens"] == 600
    elif kind == "first":
        assert ev[1] == t + 0.2005 and ev[2] is None  # instant at write1
        assert ev[4:6] == (7, "ab" * 16)
        assert ev[6][2] == t + 0.2 and ev[7][3] == t + 0.2005
        row = next(e for e in rows if e.get("cat") == "transport")
        assert tracks[row["tid"]] == "transport" and row["ph"] == "i"
        a = row["args"]
        assert a["request_id"] == 7 and a["trace_id"] == "ab" * 16
        assert a["headers"] <= a["submit"] <= a["first_put"] <= a["got"] \
            <= a["enc0"] <= a["enc1"] <= a["write0"] <= a["write1"]
    elif kind == "compile":
        assert ev[2] is None and ev[4] == 1.25
        row = next(e for e in rows if e.get("cat") == "compile")
        assert tracks[row["tid"]] == "host loop" and row["ph"] == "i"
        assert row["args"]["seconds"] == 1.25
    elif kind == "stall":
        assert ev[1:3] == (t + 0.040, pytest.approx(2.0))
        assert ev[4:8] == ("fetch", "generator.py:_first_token",
                           "fetch_late", 3)
        assert ev[8] is record
        row = next(e for e in rows if e.get("cat") == "stall")
        assert row["ph"] == "X" and tracks[row["tid"]] == "host loop"
        assert row["name"] == "stall:fetch fetch_late"
        assert row["dur"] == pytest.approx(2.0e6)
        assert row["args"]["record"] == record
        assert row["args"]["site"] == "generator.py:_first_token"
    elif kind == "host":
        assert ev[2] is None and ev[4:6] == (0.25, 0.001)
        first, second = (e for e in rows if e.get("name") == "host")
        assert first["ph"] == "C"   # a counter track; what /proc did not
        # say is left out, not drawn at zero
        assert first["args"] == {"loop_cpu": 0.25, "late": 0.001}
        assert second["args"] == {"late": 0.0}
    elif kind == "gap":
        assert ev[4] == 0.0005
        row = next(e for e in rows if e.get("cat") == "gap")
        assert tracks[row["tid"]] == "device stream"
        assert row["args"]["slack_s"] == 0.0005
    else:
        assert ev[4:7] == ((0, 1), 4, 1234)
        row = next(e for e in rows if e.get("cat") == "decode")
        assert row["args"]["live_tokens"] == 1234


def _burst(eng, n=6, new=12):
    rng = np.random.default_rng(5)
    streams = [eng.generate(rng.integers(1, eng.cfg.vocab_size, k).tolist(),
                            max_new_tokens=new)
               for k in (5, 12, 60, 7, 30, 3)[:n]]
    return [s.tokens() for s in streams]


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_loop_events_cover_the_generation_threads_time(tiny, paged):
    """Over a served burst (bucket prefills, a chunked prompt, pipelined
    decode) the loop events are written back to back: no two overlap,
    they leave out under 2% of the thread's time, and every phase name
    is one of the documented seven."""
    obs = Observe(timeline=Timeline(capacity=16384))
    kw = dict(paged_blocks=64) if paged else {}
    eng = _engine(tiny, observe=obs, slots=3, prefill_chunk=16,
                  decode_pipeline=2, **kw)
    try:
        _burst(eng)  # warm: compiles land in this one
        t0 = time.monotonic()
        outs = _burst(eng)
        t1 = time.monotonic()
        assert all(len(o) == 12 for o in outs)
        # let the thread park, which closes its last phase: the phase that
        # was open at t1 is written when the next one begins (0.12 s as a
        # rule; on a machine six test workers share it has taken longer,
        # and the uncovered tail then read as 4% of the burst)
        waited = time.monotonic() + 5.0
        time.sleep(0.12)
        while time.monotonic() < waited and not any(
                e[3] == "loop" and e[1] + e[2] >= t1
                for e in obs.timeline.events()):
            time.sleep(0.02)
    finally:
        eng.close()
    loops = sorted((e for e in obs.timeline.events() if e[3] == "loop"),
                   key=lambda e: e[1])
    assert {e[4] for e in loops} <= {"admit", "dispatch", "wait", "fetch",
                                     "deliver", "park", "other"}
    assert {"admit", "dispatch", "fetch", "deliver"} <= {e[4] for e in loops}
    for a, b in zip(loops, loops[1:]):
        assert a[1] + a[2] <= b[1] + 1e-9, (a, b)  # never nested
    covered = sum(max(0.0, min(e[1] + e[2], t1) - max(e[1], t0))
                  for e in loops)
    assert covered >= 0.98 * (t1 - t0)
    # admit phases carry the requests they started: six in the burst
    # (an admission that ran a chunk lattice splits into several slices)
    assert sum(e[5] for e in loops
               if e[4] == "admit" and t0 <= e[1] < t1) == 6
    # the decode events carry the live tokens of the block as dispatched
    decodes = [e for e in obs.timeline.events() if e[3] == "decode"]
    assert decodes and all(isinstance(e[6], int) and e[6] > 0
                           for e in decodes)
    assert max(e[6] for e in decodes) <= 3 * 128


def test_a_gap_closes_at_a_prefill_dispatch(tiny):
    """Device-dry intervals end at the next dispatch of ANY program. A
    request that arrives at an idle engine finds the stream dry since
    the last block's reap, and its prefill, not a decode block, is the
    dispatch that closes the gap: the gap event ends inside that
    request's prefill slice, before any decode block starts."""
    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=8192))
    eng = _engine(tiny, observe=obs, metrics=m)
    try:
        eng.generate([1, 2, 3], max_new_tokens=6).tokens()
        time.sleep(0.15)  # idle: the stream is dry, a gap is open
        t_second = time.monotonic()
        eng.generate([4, 5, 6, 7], max_new_tokens=6).tokens()
    finally:
        eng.close()
    ev = obs.timeline.events()
    prefill = next(e for e in ev if e[3] == "prefill" and e[1] >= t_second)
    decode = next(e for e in ev if e[3] == "decode" and e[1] >= t_second)
    gaps = [e for e in ev if e[3] == "gap"
            and e[1] < t_second <= e[1] + e[2]]
    assert len(gaps) == 1, gaps  # the idle interval, as one event
    end = gaps[0][1] + gaps[0][2]
    assert prefill[1] <= end <= prefill[1] + prefill[2]
    assert end < decode[1]
    assert gaps[0][2] >= 0.1 and gaps[0][4] >= 0.0  # duration, slack
    # and the histogram holds the same interval (no zero "by construction")
    text = m.render_prometheus()
    assert 'app_tpu_dispatch_gap_duration_count{program="generate"}' in text
    assert all(g > 0.0 for g in eng._acct.gap_samples)


def test_prefix_store_writes_one_store_event(tiny):
    obs = Observe(timeline=Timeline(capacity=4096))
    eng = _engine(tiny, observe=obs, prefix_cache_slots=2,
                  prefix_store_min=8)
    try:
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, eng.cfg.vocab_size, 24).tolist()
        eng.generate(prompt, max_new_tokens=4).tokens()
    finally:
        eng.close()
    ev = obs.timeline.events()
    stores = [e for e in ev if e[3] == "store"]
    assert len(stores) == 1
    _, t0, dur, _, slot, tokens, tier, _ = stores[0]
    assert tokens == 24 and tier == "t0" and dur >= 0.0
    # it lies inside an admit phase of the loop
    assert any(e[3] == "loop" and e[4] == "admit"
               and e[1] <= t0 and t0 + dur <= e[1] + e[2] + 1e-9 for e in ev)


def test_chunk_programs_are_named_in_the_device_trace(tiny):
    """A jitted functools.partial is jit__unknown in a device trace; the
    chunk programs carry the names the benchmark's readers look for."""
    eng = _engine(tiny)
    try:
        assert eng._chunk_mid_jit.__name__ == "_chunk_mid"
        assert eng._chunk_final_jit.__name__ == "_chunk_final"
        assert eng._step_jit.__name__ == "_step_fn"
        assert eng._prefill_jit.__name__ == "_prefill_fn"
    finally:
        eng.close()
