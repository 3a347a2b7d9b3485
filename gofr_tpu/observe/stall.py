"""A record of every silence: what the device queue, the process's
threads and the machine were doing while the generation loop stood in
one phase for a second or more, and the operating system's side of the
loop's account while it does not.

The loop's own account (``tpu/generator._LoopAccount``) is written and
read by the loop's thread alone, so while that thread is blocked nobody
looks. ``StallWatch`` is the one other reader: a daemon thread
(``gofr-tpu-stall``) started and stopped with the generation thread,
absent when the engine holds no timeline (``TPU_TIMELINE=0``). Every
``TICK`` (50 ms) it reads the account's ``(ph, ph_t0)`` (two attribute
reads, read again to reject a torn pair: the loop's thread gains no
call, no lock and no branch), the loop thread's
``/proc/self/task/<tid>/stat`` and the process's ``/proc/self/stat``
(CPU seconds, in the kernel's ticks); once a second it writes what the
loop's thread used, and how late its own ticks woke, to the timeline as
one ``host`` sample. Events go to the one timeline ring on
``time.monotonic()``, stacks come from ``profiler.sample_once``.

A phase other than ``park`` older than a fifth of the threshold
(``TPU_STALL_MS``, default 1000) is WATCHED: the watchdog scans every
thread of the process once (``stat``: state and CPU; the one thing here
that costs, a read a thread, and a window without such a phase pays
none), takes weak references to an output of every program in flight
(the pipe's blocks, the account's ``last_out``) and polls ``is_ready()``
once a tick, from outside the blocked thread. Older than the threshold
it is ARMED: stacks, the loop thread's site, the last ``gap`` before it,
a scan of the threads' states. The first tick that sees the phase gone
closes the record; a phase that ends under the threshold leaves
nothing. The references are weak and dropped at the close: the watchdog
never keeps a ``jax.Array`` alive.

The watchdog can stand still with the loop: the machine or its sandbox
pauses the process, every core is taken, or one thread keeps the
interpreter lock (a long collection of the cyclic garbage collector, a
native call that does not release it). It then wakes to find its own
tick late and the phase gone. A tick late by a fifth of the threshold
makes it LOOK BACK: the loop's own ``loop`` event of that phase is in
the ring, and a phase in it that outlasted the threshold gets its record
after the fact, with what can still be known: no site (``unseen``), no
stacks, no queue and no thread by name, but the CPU the loop's thread
and the whole process used between the watchdog's last tick before it
and its first after (a process that burned a core while it stood kept
the interpreter lock in one of its threads; one that used nothing was
not run) and that the watchdog did not run either.

A record (``/debug/stalls``, ``stats()["scheduler"]["stalls"]``, one
WARN log line, one ``stall`` timeline event, counters
``app_tpu_loop_stall_total{phase}`` and
``app_tpu_loop_stall_seconds_total{phase}``):

  id, t0 (monotonic), wall (epoch seconds), dur, phase, site
  gap      {dur, before_s, slack}: the last dry interval of the device
           stream that began before t0, and how long before t0 it ended
           (negative: the phase's own first dispatch ended it)
  stream_busy_s  how long before t0 the loop last knew the stream busy
           (negative: inside the phase)
  queue    [{kind, dispatched, ready_after}] oldest first; seconds
           relative to t0; ready_after None: not seen done before the end
  watchdog {ticks, late_s, late_max_s}: the watchdog's own 50 ms ticks
           while it watched the phase, and how late they woke (a thread
           of this process that the host did or did not run on time)
  os       {interval: [from, to] relative to t0 (from: the scan made
           when the phase was first watched, a fifth of the threshold
           in), threads: [{tid, name, loop, cpu_s, state}] (the loop's,
           every one in state D at arming, the eight that used most
           CPU), process: {threads, cpu_s}}; after the fact: interval
           from the watchdog's last tick before it stood to its first
           after, threads: the loop's alone, process: {cpu_s}. CPU is
           stat's utime + stime (10 ms ticks on the chip machine's
           sandboxed kernel). What /proc could not give is absent, never
           zero. A kernel's schedstat (run-queue time), the machine's
           steal and its pressure file are NOT read: the one machine
           that measures has none of them (PERF.md section 7)
  stacks   up to four snapshots {t, stacks}: at arming, then every half
           second (later ones hold only the threads whose stack changed)
  cause    one word, by ``classify``

``cause`` is decided from the record's numbers and nothing else, first
match wins (the thresholds are this module's constants; PERF.md section
7 says which caught silences set them):

  process_stood  the watchdog's own ticks came late by half the stall or
               more in sum: not the loop's thread alone, the whole
               process was not run (``os.process.cpu_s`` says whether one
               of its threads burned the time)
  host_work    a phase other than wait and fetch: the loop's own work
               (a compile or a blocked transfer in dispatch, a socket in
               deliver)
  fetch_late   the phase is wait or fetch and every queued program was
               seen ready at least half a second before the phase ended:
               the device was done, the transfer or the host was not
  device_late  the phase is wait or fetch, no queued program was seen
               ready earlier than that half second before the end (the
               queue drains in order once the device moves again, and a
               tick can fall between its first program and the fetch),
               and the process used under half a core: the device, or
               the thread that feeds it, stood still
  unknown      none of these
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
import weakref
from collections import deque

from . import profiler

__all__ = ["Proc", "StallWatch", "classify"]

TICK = 0.05             # seconds between two looks at the loop's account
HOST_EVERY = 1.0        # seconds between two ``host`` samples
WATCH_SHARE = 0.2       # of the threshold: from here the queue is polled
STACK_EVERY = 0.5       # seconds between two stack snapshots of a stall
MAX_STACKS = 4
STACK_FRAMES = 10       # innermost frames kept of a stack
TOP_THREADS = 8
KEEP = 32               # records behind /debug/stalls

UNSEEN = "unseen"       # the site of a stall the watchdog slept through

LATE_SHARE = 0.5        # of the stall, the watchdog's own ticks late
READY_EARLY_S = 0.5     # seen ready this long before the end: fetch_late;
                        # none seen ready before that: device_late
IDLE_CORE_SHARE = 0.5   # the process's CPU under this: device_late

PHASES = ("admit", "dispatch", "wait", "fetch", "deliver", "other")


def read_bytes(path: str) -> bytes:
    fd = os.open(path, os.O_RDONLY)
    try:
        return os.read(fd, 65536)
    finally:
        os.close(fd)


class Proc:
    """The operating system's side, read from ``/proc`` (``root``: a test
    hands in a directory that is not there): a ``stat`` file's state and
    CPU seconds, of one thread, of every thread, of the process. Every
    method returns None where its file cannot be read; nothing here
    raises for that."""

    def __init__(self, root: str = "/proc"):
        self.root = root
        try:
            self._tick = float(os.sysconf("SC_CLK_TCK"))
        except (ValueError, OSError):
            self._tick = 100.0

    def _stat(self, path: str) -> "tuple[str, float] | None":
        """(state, CPU seconds) from a ``stat``: "pid (comm) S ..." with
        utime and stime the 14th and 15th fields (comm may hold spaces)."""
        try:
            rest = read_bytes(f"{self.root}/self/{path}").rpartition(
                b") ")[2].split()
            return rest[0].decode(), (int(rest[11]) + int(rest[12])) \
                / self._tick
        except (OSError, ValueError, IndexError):
            return None

    def thread(self, tid: int) -> "tuple[str, float] | None":
        """(state, CPU seconds) of one thread."""
        return self._stat(f"task/{tid}/stat")

    def process_cpu(self) -> "float | None":
        """CPU seconds of the whole process, every thread it has and had."""
        got = self._stat("stat")
        return None if got is None else got[1]

    def threads(self) -> "dict[int, tuple[str, float]] | None":
        """Every thread of the process: {tid: (state, CPU seconds)} (a
        thread that ended between the listing and the read is left
        out)."""
        try:
            tids = [int(t) for t in os.listdir(f"{self.root}/self/task")]
        except (OSError, ValueError):
            return None
        out = {}
        for tid in tids:
            got = self.thread(tid)
            if got is not None:
                out[tid] = got
        return out or None

    def name(self, tid: int) -> str:
        """The kernel's name of a thread (a runtime's threads name
        themselves; a Python thread carries the process's)."""
        try:
            return read_bytes(f"{self.root}/self/task/{tid}/comm").decode(
                "utf-8", "replace").strip()
        except OSError:
            return ""


def classify(rec: dict) -> str:
    """The record's ``cause``: one word by the rule in this module's
    docstring, from the record's numbers and nothing else."""
    dur = float(rec.get("dur") or 0.0)
    late = (rec.get("watchdog") or {}).get("late_s", 0.0)
    if dur > 0 and late >= LATE_SHARE * dur:
        return "process_stood"
    if rec.get("phase") not in ("wait", "fetch"):
        return "host_work"
    queue = rec.get("queue") or []
    seen = [q.get("ready_after") for q in queue]
    # half a second, or half of a stall shorter than a second (a
    # threshold set under the default)
    early = dur - min(READY_EARLY_S, dur / 2)
    if queue and all(r is not None and r <= early for r in seen):
        return "fetch_late"
    os_side = rec.get("os") or {}
    process = os_side.get("process") or {}
    span = os_side.get("interval")
    if queue and all(r is None or r > early for r in seen) \
            and span and "cpu_s" in process \
            and process["cpu_s"] < IDLE_CORE_SHARE * (span[1] - span[0]):
        return "device_late"
    return "unknown"


def _site(frame) -> str:
    """The innermost frame inside gofr_tpu, as ``file.py:function``."""
    marker = os.sep + "gofr_tpu" + os.sep
    while frame is not None:
        fname = frame.f_code.co_filename
        if marker in fname:
            return f"{os.path.basename(fname)}:{frame.f_code.co_name}"
        frame = frame.f_back
    return "outside gofr_tpu"


def _first_leaf(out):
    """One array of a program's outputs (they become ready together)."""
    if out is None or hasattr(out, "is_ready"):
        return out
    import jax

    leaves = jax.tree_util.tree_leaves(out)
    return leaves[0] if leaves else None


class _Open:
    """A phase being watched: what the watchdog holds until it ends."""
    __slots__ = ("key", "queue", "base", "armed", "rec", "stacks_at",
                 "last_stacks", "ticks", "late", "late_max")

    def __init__(self, key, queue, base):
        self.key = key          # (phase, ph_t0): the phase's identity
        self.queue = queue      # [[kind, dispatched, weakref, ready_after]]
        # what the deltas of the record's ``os`` run from: the scan of
        # every thread made when the phase was first watched, or, for a
        # phase the watchdog slept through, its last tick's reading
        self.base = base
        self.armed = False
        self.rec: dict = {}
        self.stacks_at = 0.0
        self.last_stacks: dict[str, str] = {}
        # the watchdog's own ticks while it watched, and how late they
        # came: a thread of this process that asked for 50 ms
        self.ticks = 0
        self.late = 0.0
        self.late_max = 0.0


class StallWatch(threading.Thread):
    """The watchdog (this module's docstring). ``account``: the loop's
    ``_LoopAccount``; ``pipe``: the engine's deque of in-flight blocks
    (each with ``arrays``, ``kind`` and ``t0``); ``loop_thread``: the
    generation thread, for its native id and its stack."""

    def __init__(self, account, pipe, loop_thread, timeline, *,
                 metrics=None, logger=None, threshold_s: float = 1.0,
                 proc: "Proc | None" = None):
        super().__init__(name="gofr-tpu-stall", daemon=True)
        self._acct = account
        self._pipe = pipe
        self._loop_thread = loop_thread
        self._tl = timeline
        self._metrics = metrics
        self._logger = logger
        self.threshold_s = max(TICK, float(threshold_s))
        self._proc = proc if proc is not None else Proc()
        self._halt = threading.Event()
        self._ids = itertools.count(1)
        self._lock = threading.Lock()  # the records: one writer, any reader
        self._records: "deque[dict]" = deque(maxlen=KEEP)
        self._count = 0
        self._seconds = 0.0
        self._open: "_Open | None" = None
        self._seen: "deque[float]" = deque(maxlen=2 * KEEP)  # t0 of each
        # the operating system's side at the last tick and at the one
        # before it: {t, loop, process} CPU seconds, None what /proc
        # did not give
        self._os_now: dict = {"t": time.monotonic(), "loop": None,
                              "process": None}
        self._os_before = self._os_now
        # what the next ``host`` sample is made of: when the last one was
        # written, the loop's CPU seconds and the ticks' lateness since
        self._host = [self._os_now["t"], 0.0, 0.0]
        self._scan_cost = (0, 0.0)  # threads read, seconds it took
        if metrics is not None:
            # a sample of 0 from the start: an absent name is how a
            # reader tells a program without the recorder from a clean run
            for ph in PHASES:
                self._inc("app_tpu_loop_stall_total", 0.0, phase=ph)
                self._inc("app_tpu_loop_stall_seconds_total", 0.0, phase=ph)

    # -- reading -------------------------------------------------------------
    def records(self) -> list[dict]:
        with self._lock:
            return list(self._records)

    def stats(self) -> dict:
        with self._lock:
            return {"count": self._count,
                    "seconds": round(self._seconds, 6),
                    "threshold_ms": round(self.threshold_s * 1e3, 3),
                    # what the last scan of every thread cost (one when
                    # a phase is first watched, at arming, at the close)
                    "scan": {"threads": self._scan_cost[0],
                             "seconds": round(self._scan_cost[1], 6)},
                    "last": self._records[-1] if self._records else None}

    def stop(self) -> None:
        self._halt.set()
        if self.is_alive() and threading.current_thread() is not self:
            self.join(timeout=5.0)

    # -- the thread ----------------------------------------------------------
    def run(self) -> None:
        try:
            due = time.monotonic() + TICK
            while not self._halt.wait(TICK):
                now = time.monotonic()
                self._tick(now, max(0.0, now - due))
                due = time.monotonic() + TICK
        except Exception as e:  # noqa: BLE001 - the watchdog must not take
            # the process down, and a broken watchdog must say so once
            if self._logger is not None:
                self._logger.error({"event": "stall watchdog stopped",
                                    "error": repr(e)})
        finally:
            self._open = None

    def _inc(self, name: str, by: float, **labels) -> None:
        if self._metrics is not None:
            self._metrics.increment_counter(name, by=by, **labels)

    def _phase(self) -> "tuple[str, float]":
        acct = self._acct
        while True:
            ph, t0 = acct.ph, acct.ph_t0
            if ph == acct.ph and t0 == acct.ph_t0:
                return ph, t0

    def _tick(self, now: float, late: float = 0.0) -> None:
        ph, t0 = self._phase()
        self._os_tick(now, late, parked=ph == "park"
                      and now - t0 > HOST_EVERY)
        cur = self._open
        if cur is not None and cur.key != (ph, t0):
            if t0 <= cur.key[1]:
                return  # a new name beside the old start: look again
            self._open = None
            # the phase that follows began when this one ended; where
            # more than one has passed since the last tick, its start
            # still lies between the true end and now
            end = min(now, t0)
            if cur.armed or end - cur.key[1] >= self.threshold_s:
                cur.late += late
                cur.late_max = max(cur.late_max, late)
                self._close(cur, end)
            cur = None
        if late >= WATCH_SHARE * self.threshold_s:
            self._look_back(now, late)
        age = now - t0
        if ph == "park" or age < WATCH_SHARE * self.threshold_s:
            return
        if cur is None:
            # armed from the next tick on: a pair torn between the two
            # reads does not live that long
            self._open = _Open((ph, t0), self._queue(), self._scan(now))
            return
        cur.ticks += 1
        cur.late += late
        cur.late_max = max(cur.late_max, late)
        self._poll(cur, now)
        if not cur.armed and age >= self.threshold_s:
            self._arm(cur, now)
        elif cur.armed and len(cur.rec["stacks"]) < MAX_STACKS \
                and now - cur.stacks_at >= STACK_EVERY:
            self._stacks(cur, now)

    def _look_back(self, now: float, late: float) -> None:
        """The watchdog itself stood still for ``late`` seconds: whatever
        phase of the loop outlasted the threshold meanwhile was never
        watched. Its ``loop`` event is in the ring; it gets its record
        after the fact, with what can still be known: no queue, no
        stacks, but the CPU the loop's thread and the process used since
        the watchdog's last tick, and that the watchdog did not run
        either."""
        for e in self._tl.since("loop", now - late - 2 * TICK):
            if e[4] == "park" or e[2] < self.threshold_s \
                    or e[1] in self._seen:
                continue
            cur = _Open((e[4], e[1]), [], self._os_before)
            cur.late = cur.late_max = late
            self._close(cur, e[1] + e[2])

    # -- the operating system's side, continuously ---------------------------
    def _os_tick(self, now: float, late: float, parked: bool) -> None:
        """The loop thread's and the process's CPU seconds, once a tick
        (two reads); once a second what the loop's thread used and how
        late the ticks woke, as a ``host`` sample (none while the loop
        has been parked for over a second: an idle server)."""
        tid = self._loop_thread.native_id
        got = self._proc.thread(tid) if tid is not None else None
        before = self._os_before = self._os_now
        self._os_now = {"t": now, "loop": None if got is None else got[1],
                        "process": self._proc.process_cpu()}
        host = self._host
        host[2] += late
        if got is not None and before["loop"] is not None:
            used = max(0.0, got[1] - before["loop"])
            self._inc("app_tpu_loop_cpu_seconds_total", used)
            host[1] += used
        elif got is not None:
            # the first reading: a sample of 0, so that the name is there
            self._inc("app_tpu_loop_cpu_seconds_total", 0.0)
        span = now - host[0]
        if span < HOST_EVERY:
            return
        if not parked:
            self._tl.host(now, None if got is None
                          else round(host[1] / span, 6),
                          round(host[2] / span, 6))
        host[:] = [now, 0.0, 0.0]

    def _scan(self, now: float) -> dict:
        """Every thread of the process, once: a read a thread (15-50 ms
        at the 270-460 threads of a benchmark process on the chip
        machine), so only for a phase that lasts."""
        c0 = time.perf_counter()
        threads = self._proc.threads()
        self._scan_cost = (len(threads or ()), time.perf_counter() - c0)
        return {"t": now, "threads": threads}

    # -- a phase that lasts --------------------------------------------------
    def _queue(self) -> list:
        """Weak references to one output of every program in flight,
        oldest first: the pipe's blocks, then the last program queued
        where that is none of them (a prefill, a chunk, a store)."""
        try:
            blocks = list(self._pipe)
        except RuntimeError:  # the loop moved on while we copied
            return []
        queue, seen = [], set()
        for b in blocks:
            arr = _first_leaf(b.arrays)
            if arr is not None:
                seen.add(id(arr))
                queue.append([b.kind, b.t0, weakref.ref(arr), None])
        last = None
        try:
            last = _first_leaf(self._acct.last_out)
            if last is not None and id(last) not in seen:
                queue.append(["last", None, weakref.ref(last), None])
        except TypeError:  # no weak reference to this kind of output
            pass
        del blocks, last
        return queue

    def _poll(self, cur: _Open, now: float) -> None:
        for item in cur.queue:
            if item[3] is not None:
                continue
            arr = item[2]()
            if arr is None:     # reaped: the loop has moved on
                continue
            try:
                if arr.is_ready():
                    item[3] = now
            except Exception:  # noqa: BLE001 - donated, or no probe
                pass
            del arr

    def _arm(self, cur: _Open, now: float) -> None:
        ph, t0 = cur.key
        cur.armed = True
        frames = sys._current_frames()
        site = _site(frames.get(self._loop_thread.ident))
        del frames
        rec = cur.rec = {"id": next(self._ids), "site": site, "stacks": []}
        busy = self._acct.busy_seen
        if busy:
            rec["stream_busy_s"] = round(t0 - busy, 6)
        threads = self._scan(now)["threads"]
        if threads is not None:
            rec["states"] = {tid: t[0] for tid, t in threads.items()}
        self._stacks(cur, now)

    def _stacks(self, cur: _Open, now: float) -> None:
        cur.stacks_at = now
        own = {threading.get_ident()}
        changed = []
        for stack in profiler.sample_once(own):
            name, _, frames = stack.partition(";")
            frames = ";".join(frames.split(";")[-STACK_FRAMES:])
            if cur.last_stacks.get(name) != frames:
                cur.last_stacks[name] = frames
                changed.append(f"{name};{frames}")
        cur.rec["stacks"].append(
            {"t": round(now - cur.key[1], 3), "stacks": changed})

    def _close(self, cur: _Open, end: float) -> None:
        """Write the record of a phase that has ended. One that was never
        armed (the watchdog stood still with the loop, and woke to find
        the phase gone) has no site and no stacks, and says so."""
        ph, t0 = cur.key
        if t0 in self._seen:
            return
        self._seen.append(t0)
        # the loop's own event of this phase, if it is in the ring yet,
        # has the length to the microsecond
        own = self._tl.last("loop", before=t0 + 1e-9)
        if own is not None and own[1] == t0 and own[4] == ph:
            end = t0 + own[2]
        armed = cur.rec
        dur = round(end - t0, 6)
        rec = {"id": armed.get("id") or next(self._ids), "t0": round(t0, 6),
               "wall": round(self._tl.wall_time(t0), 3), "dur": dur,
               "phase": ph, "site": armed.get("site", UNSEEN), "cause": ""}
        gap = self._tl.last("gap", before=t0)
        if gap is not None:
            rec["gap"] = {"dur": round(gap[2], 6),
                          "before_s": round(t0 - (gap[1] + gap[2]), 6),
                          "slack": round(gap[4] or 0.0, 6)}
        if "stream_busy_s" in armed:
            rec["stream_busy_s"] = armed["stream_busy_s"]
        rec["queue"] = [
            {"kind": kind,
             "dispatched": None if at is None else round(at - t0, 6),
             "ready_after": None if ready is None or ready > end
             else round(ready - t0, 6)}
            for kind, at, _ref, ready in cur.queue]
        rec["watchdog"] = {"ticks": cur.ticks, "late_s": round(cur.late, 6),
                           "late_max_s": round(cur.late_max, 6)}
        os_side = self._os_side(cur.base, t0, armed.get("states"))
        if os_side:
            rec["os"] = os_side
        rec["stacks"] = armed.get("stacks", [])
        rec["cause"] = classify(rec)
        with self._lock:
            self._count += 1
            self._seconds += dur
            self._records.append(rec)
        self._inc("app_tpu_loop_stall_total", 1.0, phase=ph)
        self._inc("app_tpu_loop_stall_seconds_total", dur, phase=ph)
        self._tl.stall(t0, end, ph, rec["site"], rec["cause"], rec["id"],
                       rec)
        if self._logger is not None:
            self._logger.warn({"event": "generation loop stalled", **rec})

    def _os_side(self, base: dict, t0: float, states: "dict | None") -> dict:
        """CPU seconds from ``base`` to now: a thread from a scan of
        every thread (a phase that was watched), the loop's thread and
        the process from a tick's reading (one that was not)."""
        now = time.monotonic()
        tid = self._loop_thread.native_id
        out: dict = {}
        if "threads" in base:
            old, threads = base["threads"], self._scan(now)["threads"]
            if old is None or threads is None:
                return out
            rows = []
            for k, (_state, cpu) in threads.items():
                row = {"tid": k,
                       "cpu_s": round(cpu - old.get(k, ("", 0.0))[1], 6)}
                if k == tid:
                    row["loop"] = True
                if (states or {}).get(k):
                    row["state"] = states[k]
                rows.append(row)
            rows.sort(key=lambda r: -r["cpu_s"])
            out["threads"] = [r for i, r in enumerate(rows)
                              if i < TOP_THREADS or r.get("loop")
                              or r.get("state") == "D"]
            out["process"] = {
                "threads": len(rows),
                "cpu_s": round(sum(r["cpu_s"] for r in rows), 6)}
        else:
            last = self._os_now
            if base["loop"] is not None and last["loop"] is not None:
                out["threads"] = [{
                    "tid": tid, "loop": True,
                    "cpu_s": round(last["loop"] - base["loop"], 6)}]
            if base["process"] is not None and last["process"] is not None:
                out["process"] = {
                    "cpu_s": round(last["process"] - base["process"], 6)}
            now = last["t"]
        python = {t.native_id: t.name for t in threading.enumerate()}
        for r in out.get("threads", ()):
            r["name"] = python.get(r["tid"]) or self._proc.name(r["tid"])
        if out:
            out["interval"] = [round(base["t"] - t0, 3), round(now - t0, 3)]
        return out
