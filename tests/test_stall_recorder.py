"""The stall recorder (observe/stall.py): a phase of the generation loop
that outlasts TPU_STALL_MS leaves one record of what the queue, the
threads and the machine did meanwhile, written by a watchdog thread and
never by the loop's own.

An induced stall (a chaos latency rule of 1.5 s on the step seam, the
threshold at 200 ms) through a whole App; the watchdog alone against a
stand-in for the loop's account, where /proc is taken away and where
arrays are queued; the ``cause`` rule on hand-made records."""

import gc
import io
import json
import sys
import threading
import time
import urllib.request
import weakref
from collections import deque
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu import App, chaos
from gofr_tpu.config import MapConfig
from gofr_tpu.metrics import (Manager, parse_prometheus,
                              register_framework_metrics)
from gofr_tpu.models import llama
from gofr_tpu.models.common import LLAMA_CONFIGS
from gofr_tpu.observe import Observe
from gofr_tpu.observe import stall as stall_mod
from gofr_tpu.observe.stall import Proc, StallWatch, classify
from gofr_tpu.observe.timeline import Timeline
from gofr_tpu.tpu import GenerationEngine

TINY = LLAMA_CONFIGS["tiny"]
RULE_S = 1.5


def _until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return json.loads(r.read())


# -- an induced stall through a whole App --------------------------------------

@pytest.fixture(scope="module")
def stalled():
    app = App(MapConfig({"HTTP_PORT": "0", "METRICS_PORT": "0",
                         "TPU_MODEL": "tiny", "TPU_MAX_SEQ": "64",
                         "TPU_SLOTS": "2", "TPU_SEQ_BUCKETS": "8,16",
                         "TPU_STALL_MS": "200"}))
    log = io.StringIO()
    app.container.logger.out = log
    gen = app.container.tpu.generator
    app.run(block=False)
    try:
        # every program compiled (a compile of 200 ms is a stall too)
        gen.generate([1, 2, 3], max_new_tokens=8).tokens()
        assert _until(lambda: gen._acct.ph == "park")
        time.sleep(3 * stall_mod.TICK)
        before = gen.stats()["scheduler"]["stalls"]["count"]
        prom0 = parse_prometheus(app.container.metrics.render_prometheus())
        schedule = chaos.install(chaos.ChaosSchedule().on(
            chaos.GENERATOR_STEP, latency=RULE_S))
        try:
            stream = gen.generate([4, 5, 6, 7], max_new_tokens=6)
            assert _until(lambda: schedule.stats()["calls"].get(
                chaos.GENERATOR_STEP))
        finally:
            chaos.uninstall()  # the one call that fired is sleeping
        tokens = stream.tokens()
        assert _until(lambda: gen.stats()["scheduler"]["stalls"]["count"]
                      > before)
        time.sleep(3 * stall_mod.TICK)
        stats = gen.stats()["scheduler"]["stalls"]
        yield SimpleNamespace(
            app=app, gen=gen, tokens=tokens, before=before, stats=stats,
            records=gen.stall_watch.records()[before - stats["count"]:],
            prom0=prom0, log=log.getvalue(),
            prom=parse_prometheus(
                app.container.metrics.render_prometheus()),
            events=app.container.observe.timeline.events(),
            page=_get(app.metrics_port, "/debug/stalls"),
            trace=_get(app.metrics_port, "/debug/timeline"))
    finally:
        chaos.uninstall()
        app.stop()


@pytest.mark.parametrize("what", [
    "one_record", "phase_and_site", "dur", "counters", "event", "slice",
    "page", "log_line", "os_side", "cause", "stacks"])
def test_an_induced_stall_leaves_one_whole_record(stalled, what):
    s = stalled
    rec = s.records[-1]
    if what == "one_record":
        assert len(s.tokens) == 6
        assert s.stats["count"] == s.before + 1 and len(s.records) == 1
        assert s.stats["last"] == rec
        assert s.stats["threshold_ms"] == 200.0
    elif what == "phase_and_site":
        # the seam fires where the loop tops up the pipe: behind an
        # admission's prefill (phase admit) or in its own pass (other)
        assert rec["phase"] in ("admit", "other")
        assert rec["site"] == "chaos.py:fire"
    elif what == "dur":
        assert abs(rec["dur"] - RULE_S) <= 2 * stall_mod.TICK + 0.05
    elif what == "counters":
        for name, by in (("app_tpu_loop_stall_total", 1.0),
                         ("app_tpu_loop_stall_seconds_total", rec["dur"])):
            assert s.prom[name] - s.prom0[name] == pytest.approx(by)
        assert s.stats["seconds"] >= rec["dur"]
    elif what == "event":
        mine = [e for e in s.events
                if e[3] == "stall" and e[7] == rec["id"]]
        assert len(mine) == 1
        e = mine[0]
        assert e[1] == pytest.approx(rec["t0"]) and e[2] == \
            pytest.approx(rec["dur"])
        assert e[4:7] == (rec["phase"], rec["site"], rec["cause"])
        assert e[8] == rec
        # the loop's own event of the same interval
        loop = [x for x in s.events if x[3] == "loop"
                and x[1] == pytest.approx(rec["t0"], abs=1e-5)]
        assert loop and loop[0][2] == pytest.approx(rec["dur"], abs=0.11)
        # and what the counters say is what the events sum to
        assert sum(x[2] for x in s.events if x[3] == "stall") == \
            pytest.approx(s.prom["app_tpu_loop_stall_seconds_total"])
    elif what == "slice":
        rows = s.trace["traceEvents"]
        tracks = {e["tid"]: e["args"]["name"] for e in rows
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
        row = [e for e in rows if e.get("cat") == "stall"
               and e["args"]["id"] == rec["id"]]
        assert len(row) == 1 and row[0]["ph"] == "X"
        assert tracks[row[0]["tid"]] == "host loop"
        assert row[0]["args"]["record"]["site"] == rec["site"]
        assert row[0]["dur"] == pytest.approx(rec["dur"] * 1e6)
    elif what == "page":
        assert s.page["enabled"] is True
        assert s.page["count"] == s.stats["count"]
        assert s.page["records"][-1]["id"] == rec["id"]
        assert s.page["records"][-1]["stacks"] == rec["stacks"]
    elif what == "log_line":
        lines = [json.loads(ln) for ln in s.log.splitlines()
                 if "generation loop stalled" in ln]
        mine = [ln for ln in lines if ln["message"]["id"] == rec["id"]]
        assert len(mine) == 1 and mine[0]["level"] == "WARN"
        assert mine[0]["message"]["dur"] == rec["dur"]
        assert mine[0]["message"]["cause"] == rec["cause"]
    elif what == "os_side":
        os_side = rec["os"]
        lo, hi = os_side["interval"]
        # from the scan made when the phase was first watched (a fifth
        # of the threshold in, and a tick or two) to the end
        assert 0.04 <= lo <= 0.04 + 4 * stall_mod.TICK
        assert hi >= rec["dur"]
        loop = [t for t in os_side["threads"] if t.get("loop")]
        assert len(loop) == 1 and loop[0]["name"] == "gofr-tpu-gen"
        assert loop[0]["state"] == "S"      # asleep in the rule
        assert loop[0]["cpu_s"] <= 0.1      # and all through it
        assert set(loop[0]) == {"tid", "name", "loop", "state", "cpu_s"}
        assert rec["watchdog"]["ticks"] >= 20   # 1.5 s of 50 ms ticks
        assert rec["watchdog"]["late_s"] < 0.75
        assert os_side["process"]["threads"] >= len(os_side["threads"])
        assert os_side["process"]["cpu_s"] == pytest.approx(
            sum(t["cpu_s"] for t in os_side["threads"]), abs=0.05)
        assert set(os_side) == {"interval", "threads", "process"}
        assert s.prom["app_tpu_loop_cpu_seconds_total"] > 0.0
        assert s.stats["scan"]["threads"] == os_side["process"]["threads"]
    elif what == "cause":
        assert rec["cause"] == "host_work" == classify(rec)
    else:
        first = rec["stacks"][0]
        assert first["t"] >= 0.2
        loop = next(x for x in first["stacks"]
                    if x.startswith("gofr-tpu-gen;"))
        assert "fire (gofr_tpu/chaos.py" in loop and "_loop" in loop
        assert 2 <= len(rec["stacks"]) <= stall_mod.MAX_STACKS


# -- engines without a stall, and without a watchdog ---------------------------

@pytest.fixture(scope="module")
def tiny_params():
    return llama.init(TINY, jax.random.PRNGKey(1))


def _engine(params, **kw):
    return GenerationEngine(TINY, params, slots=2, max_seq=64,
                            prompt_buckets=(8, 16), **kw)


def test_a_run_without_the_rule_leaves_none(tiny_params):
    m = Manager()
    register_framework_metrics(m)
    eng = _engine(tiny_params, observe=Observe(metrics=m), metrics=m)
    try:
        eng.generate([1, 2, 3], max_new_tokens=8).tokens()  # compiles
        before = eng.stats()["scheduler"]["stalls"]["count"]
        for _ in range(3):
            eng.generate([1, 2, 3, 4], max_new_tokens=8).tokens()
        time.sleep(3 * stall_mod.TICK)
        assert eng.stats()["scheduler"]["stalls"]["count"] == before
        prom = parse_prometheus(m.render_prometheus())
        # a sample of 0 from the start, not an absent name
        assert prom["app_tpu_loop_stall_seconds_total"] == \
            pytest.approx(eng.stats()["scheduler"]["stalls"]["seconds"])
        assert 'app_tpu_loop_stall_total{phase="fetch"} 0.0' \
            in m.render_prometheus()
        # and once a second the host's side as a counter sample: the
        # loop's CPU and the watchdog's lateness, seconds a second
        assert _until(lambda: any(e[3] == "host"
                                  for e in eng._tl.events()), 3.0)
        host = next(e for e in eng._tl.events() if e[3] == "host")
        assert 0.0 <= host[4] <= 1.5 and 0.0 <= host[5] < 1.0
    finally:
        eng.close()
    assert not eng.stall_watch.is_alive()


def test_no_timeline_no_watchdog(tiny_params):
    n = sum(t.name == "gofr-tpu-stall" for t in threading.enumerate())
    eng = _engine(tiny_params,
                  observe=Observe(timeline=Timeline(enabled=False)))
    try:
        assert eng.stall_watch is None
        assert sum(t.name == "gofr-tpu-stall"
                   for t in threading.enumerate()) == n
        eng.generate([1, 2, 3], max_new_tokens=4).tokens()
        assert "stalls" not in eng.stats()["scheduler"]
    finally:
        eng.close()


# -- the watchdog alone, against a stand-in for the loop -----------------------

class _Loop:
    """What the watchdog reads of an engine: an account whose phase the
    test moves, a pipe, and a parked thread for the native id and the
    stack."""

    def __init__(self, threshold_s=0.2, proc=None, metrics=None):
        self.acct = SimpleNamespace(ph="park", ph_t0=time.monotonic(),
                                    busy_seen=0.0, last_out=None)
        self.pipe = deque()
        self._done = threading.Event()
        self.thread = threading.Thread(target=self._done.wait, args=(60.0,),
                                       name="stand-in-loop", daemon=True)
        self.thread.start()
        self.tl = Timeline(capacity=256)
        self.watch = StallWatch(self.acct, self.pipe, self.thread, self.tl,
                                metrics=metrics, threshold_s=threshold_s,
                                proc=proc)
        self.watch.start()

    def phase(self, name):
        now = time.monotonic()
        self.tl.loop(self.acct.ph_t0, now, self.acct.ph)  # as the loop does
        self.acct.ph, self.acct.ph_t0 = name, now

    def stall(self, name, seconds):
        self.phase(name)
        time.sleep(seconds)
        self.phase("park")
        assert _until(lambda: self.watch._open is None, 2.0)
        time.sleep(2 * stall_mod.TICK)

    def close(self):
        self.watch.stop()
        self._done.set()
        self.thread.join(5.0)


@pytest.mark.parametrize("what", ["record", "series"])
def test_an_unreadable_proc_leaves_fields_out_not_zero(what, tmp_path):
    m = Manager()
    register_framework_metrics(m)
    loop = _Loop(proc=Proc(root=str(tmp_path / "no-proc")), metrics=m)
    try:
        loop.stall("fetch", 0.5)
        (rec,) = loop.watch.records()
    finally:
        loop.close()
    if what == "record":
        assert rec["phase"] == "fetch" and "os" not in rec
        assert rec["site"] == "outside gofr_tpu"
        assert rec["cause"] == "unknown"   # nothing queued, nothing read
        assert rec["dur"] == pytest.approx(0.5, abs=0.11)
    else:
        prom = parse_prometheus(m.render_prometheus())
        assert "app_tpu_loop_cpu_seconds_total" not in prom
        assert prom["app_tpu_loop_stall_total"] == 1.0
        # the host sample says how late the ticks woke, and no CPU
        assert all(e[4] is None for e in loop.tl.events()
                   if e[3] == "host")


def test_proc_reads_state_and_cpu_from_stat_in_the_kernels_ticks(tmp_path):
    """What the chip machine's sandboxed kernel keeps: a thread's and
    the process's ``stat`` (state, utime and stime in ticks; a comm may
    hold spaces and brackets) and ``comm``."""
    root = tmp_path / "proc"
    task = root / "self" / "task"
    line = "{} (py thon) 3) {} 1 1 1 0 0 0 0 0 0 0 {} {} 0 0 20 0 2 0 5 " \
        "100 7 0\n"
    for tid, (state, utime, stime) in {11: ("S", 250, 50),
                                       12: ("R", 1000, 0)}.items():
        (task / str(tid)).mkdir(parents=True)
        (task / str(tid) / "stat").write_text(
            line.format(tid, state, utime, stime))
        (task / str(tid) / "comm").write_text("py thon) 3\n")
    (root / "self" / "stat").write_text(line.format(11, "S", 1400, 100))
    proc = Proc(root=str(root))
    tick = proc._tick
    assert proc.thread(11) == ("S", 300 / tick)
    assert proc.threads() == {11: ("S", 300 / tick), 12: ("R", 1000 / tick)}
    assert proc.process_cpu() == 1500 / tick
    assert proc.name(12) == "py thon) 3"
    assert proc.thread(13) is None and proc.name(13) == ""
    # against this process's own: a thread that burns a quarter second
    # reads a quarter second, in the unit the counter is written in
    real, tid = Proc(), threading.get_native_id()
    before, all_before = real.thread(tid)[1], real.process_cpu()
    end = time.thread_time() + 0.25    # this thread's own CPU
    while time.thread_time() < end:
        pass
    assert real.thread(tid)[1] - before == pytest.approx(0.25, abs=0.08)
    assert real.process_cpu() - all_before >= 0.17


def test_a_stall_the_watchdog_slept_through_gets_its_record_afterwards():
    """One thread keeps the interpreter lock for 0.8 s: the loop's phase
    lasts that long and the watchdog does not run either. When it wakes
    it finds its own tick late, looks the phase up in the ring, and
    writes the record after the fact: no site, no stacks, no queue, but
    that the whole process stood, and that it burned a core meanwhile."""
    loop = _Loop()
    try:
        time.sleep(3 * stall_mod.TICK)       # a tick lies before the stall
        interval = sys.getswitchinterval()
        sys.setswitchinterval(5.0)
        try:
            loop.phase("wait")
            end = time.monotonic() + 0.8
            while time.monotonic() < end:
                pass
            loop.phase("park")
        finally:
            sys.setswitchinterval(interval)
        assert _until(lambda: loop.watch.stats()["count"] == 1, 2.0)
        (rec,) = loop.watch.records()
        assert rec["phase"] == "wait" and rec["site"] == stall_mod.UNSEEN
        assert rec["dur"] == pytest.approx(0.8, abs=0.02)  # the ring's own
        assert rec["stacks"] == [] and rec["queue"] == []
        assert rec["watchdog"]["late_max_s"] >= 0.5
        assert rec["cause"] == "process_stood"
        # no thread by name, but the process burned a core while it
        # stood (a held lock, not a paused sandbox) and the loop's
        # thread, asleep in its stand-in, none of it
        os_side = rec["os"]
        assert os_side["process"] == {"cpu_s": pytest.approx(0.8, abs=0.2)}
        (mine,) = os_side["threads"]
        assert mine["loop"] and mine["name"] == "stand-in-loop"
        assert mine["cpu_s"] <= 0.05
        lo, hi = os_side["interval"]
        assert -2 * stall_mod.TICK - 0.05 <= lo <= 0.04
        assert hi >= rec["dur"] - 0.001     # the tick that woke late
        events = [e for e in loop.tl.events() if e[3] == "stall"]
        assert len(events) == 1 and events[0][2] == pytest.approx(rec["dur"])
        # looked at again on the next late tick, it is not written twice
        loop.watch._look_back(time.monotonic(), 5.0)
        assert loop.watch.stats()["count"] == 1
    finally:
        loop.close()


def test_a_phase_under_the_threshold_leaves_nothing():
    loop = _Loop(threshold_s=0.4)
    try:
        loop.stall("fetch", 0.03)    # never watched: no thread is scanned,
        assert loop.watch.stats()["scan"]["threads"] == 0  # nothing paid
        loop.stall("fetch", 0.2)     # watched from 80 ms, never armed
        assert loop.watch.stats()["scan"]["threads"] >= 3
        loop.stall("park", 0.6)      # the idle wait is no stall
        assert loop.watch.records() == []
        assert loop.watch.stats()["count"] == 0
        assert not any(e[3] == "stall" for e in loop.tl.events())
    finally:
        loop.close()


def test_the_queue_is_seen_from_outside_and_no_array_is_kept():
    loop = _Loop()
    try:
        done = jnp.arange(4) + 1
        done.block_until_ready()
        last = (jnp.ones(3), {"cache": jnp.zeros(2)})
        t_disp = time.monotonic()
        loop.pipe.append(SimpleNamespace(arrays=(done, done), kind="decode",
                                         t0=t_disp))
        loop.acct.last_out = last
        refs = [weakref.ref(done), weakref.ref(last[0])]
        loop.stall("fetch", 0.5)
        (rec,) = loop.watch.records()
        assert [q["kind"] for q in rec["queue"]] == ["decode", "last"]
        first, second = rec["queue"]
        assert first["dispatched"] == pytest.approx(t_disp - rec["t0"],
                                                    abs=1e-5)
        assert second["dispatched"] is None
        # both were done long before the phase ended, and were seen so
        # by the watchdog's own poll, a tick or two after it began to look
        for q in rec["queue"]:
            assert q["ready_after"] is not None
            assert q["ready_after"] <= 0.2 * 0.2 + 3 * stall_mod.TICK
        # ready 0.1 s into a stall of 0.5 s: the device was done early
        assert rec["cause"] == "fetch_late"
        # after the close the watchdog holds no array: ours were the last
        loop.pipe.clear()
        loop.acct.last_out = None
        del done, last, first, second
        gc.collect()
        assert [r() for r in refs] == [None, None]
    finally:
        loop.close()


# -- the cause, one word a rule -------------------------------------------------

def _rec(**kw):
    base = {"dur": 2.0, "phase": "fetch", "queue": [], "os": {
        "interval": [0.2, 2.1],
        "threads": [{"tid": 1, "loop": True, "cpu_s": 0.0},
                    {"tid": 2, "cpu_s": 0.01}],
        "process": {"threads": 2, "cpu_s": 0.02}}}
    os_side = dict(base["os"], **kw.pop("os", {}))
    return dict(base, os=os_side, **kw)


_QUEUE_LATE = [{"kind": "decode", "dispatched": -0.05, "ready_after": None},
               {"kind": "last", "dispatched": None, "ready_after": None}]
_QUEUE_DONE = [{"kind": "decode", "dispatched": -0.05, "ready_after": 0.21},
               {"kind": "last", "dispatched": None, "ready_after": 1.4}]


@pytest.mark.parametrize("word,rec", [
    ("process_stood", _rec(queue=_QUEUE_LATE, watchdog={
        "ticks": 12, "late_s": 1.3, "late_max_s": 0.9})),
    # after the fact: the watchdog woke 2.04 s late, nothing else known
    ("process_stood", _rec(phase="wait", dur=2.081, watchdog={
        "ticks": 0, "late_s": 2.04, "late_max_s": 2.04}, os={
            "interval": [-0.03, 2.09], "process": {"cpu_s": 0.0},
            "threads": [{"tid": 1, "loop": True, "cpu_s": 0.0}]})),
    ("process_stood", _rec(phase="deliver", watchdog={
        "ticks": 3, "late_s": 1.0, "late_max_s": 1.0})),
    ("device_late", _rec(queue=_QUEUE_LATE, watchdog={
        "ticks": 40, "late_s": 0.02, "late_max_s": 0.004}, os={
            "threads": [{"tid": 1, "loop": True, "cpu_s": 0.0, "state": "S"},
                        {"tid": 2, "cpu_s": 0.03, "state": "S"}]})),
    ("fetch_late", _rec(queue=_QUEUE_DONE)),
    ("fetch_late", _rec(phase="wait", queue=_QUEUE_DONE[:1])),
    ("device_late", _rec(queue=_QUEUE_LATE)),
    ("host_work", _rec(phase="deliver", queue=_QUEUE_DONE)),
    ("host_work", _rec(phase="dispatch", queue=_QUEUE_LATE)),
    # one ready early and one never: neither rule
    ("unknown", _rec(queue=[_QUEUE_DONE[0], _QUEUE_LATE[1]])),
    ("unknown", _rec(queue=_QUEUE_LATE,
                     os={"process": {"threads": 2, "cpu_s": 1.5}})),
    # the induced device stall on the chip: a 3 s program queued from
    # another thread, 0.63 CPU seconds over 3.5 s, both blocks late
    ("device_late", _rec(dur=2.529, queue=_QUEUE_LATE, watchdog={
        "ticks": 40, "late_s": 0.1055, "late_max_s": 0.0811}, os={
            "interval": [-1.001, 2.532],
            "process": {"threads": 278, "cpu_s": 0.63}})),
    # the same again (call F): the watchdog's tick fell between the first
    # block's end and the fetch's return, 6 ms before the phase ended
    ("device_late", _rec(dur=2.203575, queue=[
        {"kind": "decode", "dispatched": -0.059233, "ready_after": 2.197717},
        {"kind": "decode", "dispatched": -0.00202, "ready_after": None}],
        watchdog={"ticks": 37, "late_s": 0.024515, "late_max_s": 0.001166},
        os={"interval": [0.235, 2.253],
            "process": {"threads": 276, "cpu_s": 0.15}})),
    ("unknown", {"dur": 2.0, "phase": "fetch", "queue": _QUEUE_LATE}),
    ("unknown", _rec()),           # nothing queued: nothing to go by
])
def test_the_cause_is_one_word_by_the_written_rule(word, rec):
    assert classify(rec) == word
