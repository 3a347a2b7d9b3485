"""Fault-injection harness: seeded chaos at the serving stack's seams.

No reference equivalent. Resilience claims (deadlines drop expired work,
the admission gate sheds instead of queueing, the generation loop
recovers from device loss, the breaker+retry client survives flapping
backends) are only true if something keeps proving them — this module
is that something, wired into CI (``tools/chaos_bench.py --smoke`` and
the ``chaos`` pytest marker).

Model: a ``ChaosSchedule`` holds per-seam rules (latency, injected
errors, or both); production code calls ``chaos.fire(SEAM)`` at a fixed
set of seams, which is a single ``None`` check when no schedule is
installed — the hot path pays one attribute read. Decisions are
DETERMINISTIC: every firing is derived from ``(seed, seam, call_index)``
only, so the same schedule driven by the same call counts makes the
same injections — the property the CI smoke asserts by digesting the
decision stream twice (two consecutive runs must agree).

Seams (grep for ``chaos.fire``):

  ==================  =====================================================
  BATCHER_DISPATCH    tpu/batcher._run_one, before the runner executes —
                      models slow/failing device dispatch for ``predict``
  GENERATOR_PREFILL   tpu/generator._start, before the prefill dispatch —
                      a raised error fails ONE stream (admission error path)
  GENERATOR_CHUNK     tpu/generator._chunk_lattice, before EACH mid-chunk
                      dispatch of a chunked prefill — indexing by chunk
                      lets a schedule kill chunk N of a long admission
                      specifically (mid-chunk DeviceLost recovery)
  GENERATOR_STEP      tpu/generator._loop, before a decode tick — a raised
                      ``DeviceLost`` exercises the full loop-recovery path
                      (cache reallocation, waiter fail-fast)
  GATEWAY_PICK        gateway/router.pick, before each replica-pick
                      decision — injected latency widens the
                      pick/drain race deterministically; an injected
                      error fails THAT pick (typed 503 to the client,
                      never a gateway crash)
  GATEWAY_RELAY       gateway/relay, before EACH forward attempt —
                      an injected error is treated as that attempt's
                      transport loss, driving the pre-first-token
                      failover path on attempt N exactly (``every=N``)
  GATEWAY_MIDSTREAM   gateway relay loop, before EACH relayed token
                      line AFTER the first — an injected error is that
                      line's transport loss, driving the POST-commit
                      auto-resume path after exactly N relayed tokens
                      (``every=N, limit=1``)
  GENERATOR_MIDKILL   tpu/generator._deliver, after EACH delivered
                      token — an injected error kills THAT stream
                      after exactly N emitted tokens (``every=N,
                      limit=1``), the in-process stand-in for a
                      replica SIGKILL mid-stream; the typed error line
                      carries a resume token
  GRPC_STREAM         grpcx/server._handle_stream, before dispatch —
                      transport-level latency/errors per RPC
  HBM_ALLOC           tpu/hbm lease points (lease/alloc/check) — an
                      injected ``ResourceExhausted`` models a device
                      allocation failure that survived reclaim+retry:
                      the arbiter sheds that request (429/
                      RESOURCE_EXHAUSTED + Retry-After) and the
                      process keeps serving. ``every=N`` kills
                      allocation N deterministically
  HTTP_REQUEST        http/server._handle, before routing
  PD_INGEST           pd/ingest._on_kv, before each received KV frame
                      is validated/assembled — an injected error is
                      THAT transfer's fault: the ingest server rejects
                      the one request typed (502 KVTransferError over
                      the wire) and the reader loop keeps serving
                      every other stream on the connection
  SERVICE_REQUEST     service/client._do, before the network hop —
                      feeds the retry/breaker composition tests
  ==================  =====================================================

Socket-level faults don't need a seam: ``slow_loris`` (dribble an
incomplete HTTP request) and ``slow_h2_preface`` (dribble a partial
HTTP/2 client preface) attack a live listener from the outside, proving
one stuck peer can't wedge the accept path.
"""

from __future__ import annotations

import contextlib
import hashlib
import random
import socket
import threading
import time

__all__ = [
    "BATCHER_DISPATCH", "GATEWAY_MIDSTREAM", "GATEWAY_PICK",
    "GATEWAY_RELAY", "GENERATOR_CHUNK", "GENERATOR_MIDKILL",
    "GENERATOR_PREFILL", "GENERATOR_STEP",
    "GRPC_STREAM", "HBM_ALLOC", "HTTP_REQUEST", "PD_INGEST",
    "SERVICE_REQUEST", "SEAMS",
    "ChaosSchedule", "DeviceLost", "ResourceExhausted", "Rule",
    "active", "fire", "install", "scope", "slow_h2_preface", "slow_loris",
    "uninstall",
]

BATCHER_DISPATCH = "batcher.dispatch"
GATEWAY_MIDSTREAM = "gateway.midstream"
GATEWAY_PICK = "gateway.pick"
GATEWAY_RELAY = "gateway.relay"
GENERATOR_CHUNK = "generator.chunk"
GENERATOR_MIDKILL = "generator.midkill"
GENERATOR_PREFILL = "generator.prefill"
GENERATOR_STEP = "generator.step"
GRPC_STREAM = "grpc.stream"
HBM_ALLOC = "hbm.alloc"
HTTP_REQUEST = "http.request"
PD_INGEST = "pd.ingest"
SERVICE_REQUEST = "service.request"

SEAMS = (BATCHER_DISPATCH, GATEWAY_MIDSTREAM, GATEWAY_PICK, GATEWAY_RELAY,
         GENERATOR_CHUNK, GENERATOR_MIDKILL, GENERATOR_PREFILL,
         GENERATOR_STEP, GRPC_STREAM, HBM_ALLOC,
         HTTP_REQUEST, PD_INGEST, SERVICE_REQUEST)


class DeviceLost(RuntimeError):
    """Injected stand-in for an accelerator runtime failure (the class
    of error a real XLA dispatch surfaces when a chip drops off the
    host). Raised at GENERATOR_STEP / BATCHER_DISPATCH it takes the
    same except-paths real device loss takes."""


class ResourceExhausted(RuntimeError):
    """Injected stand-in for a device allocation failure (the
    RESOURCE_EXHAUSTED ``XlaRuntimeError`` a real OOM surfaces).
    Raised at HBM_ALLOC it takes the arbiter's shed path; raised at
    BATCHER_DISPATCH it exercises the batcher's reclaim-then-retry.
    The message carries the marker ``tpu/hbm.is_oom_error`` keys on,
    so the classifier treats injected and real OOMs identically."""

    def __init__(self, msg: str = "injected RESOURCE_EXHAUSTED: device "
                                  "memory exhausted (chaos)"):
        super().__init__(msg)


class Rule:
    """One seam's injection policy.

    latency/jitter: every call sleeps ``latency + U[0, jitter)`` seconds
      (the uniform draw is deterministic per call index).
    error: exception INSTANCE, class, or zero-arg factory raised on
      firing calls.
    every: fire on every Nth call (deterministic cadence), OR
    p: fire with probability ``p`` per call (deterministic per-index
      Bernoulli draw from the schedule's seed).
    limit: stop firing errors after this many (0 = unlimited); latency
      keeps applying.
    """

    __slots__ = ("latency", "jitter", "error", "every", "p", "limit")

    def __init__(self, latency: float = 0.0, jitter: float = 0.0,
                 error=None, every: int = 0, p: float = 0.0,
                 limit: int = 0):
        if every and p:
            raise ValueError("rule takes every= OR p=, not both")
        self.latency = float(latency)
        self.jitter = float(jitter)
        self.error = error
        self.every = int(every)
        self.p = float(p)
        self.limit = int(limit)

    def _make_error(self) -> BaseException:
        err = self.error
        if isinstance(err, BaseException):
            return err
        return err()  # class or factory

    def decide(self, seed: int, seam: str, idx: int) -> tuple[bool, float]:
        """(fire_error, sleep_s) for call ``idx`` — a pure function of
        (seed, seam, idx), which is what makes schedules replayable."""
        rng = random.Random(f"{seed}:{seam}:{idx}")
        sleep_s = self.latency + (rng.random() * self.jitter
                                  if self.jitter > 0 else 0.0)
        fire = False
        if self.error is not None:
            if self.every > 0:
                fire = (idx % self.every) == self.every - 1
            elif self.p > 0:
                fire = rng.random() < self.p
        return fire, sleep_s


class ChaosSchedule:
    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._rules: dict[str, Rule] = {}
        self._lock = threading.Lock()
        self._calls: dict[str, int] = {}
        self._fired: dict[str, int] = {}
        self._slept: dict[str, float] = {}

    def on(self, seam: str, *, latency: float = 0.0, jitter: float = 0.0,
           error=None, every: int = 0, p: float = 0.0,
           limit: int = 0) -> "ChaosSchedule":
        """Attach a rule to a seam; chainable. Unknown seam names are
        allowed (tests may define private seams) but the canonical set
        is ``SEAMS``."""
        self._rules[seam] = Rule(latency=latency, jitter=jitter, error=error,
                                 every=every, p=p, limit=limit)
        return self

    # -- the injection point --------------------------------------------------
    def fire(self, seam: str) -> None:
        rule = self._rules.get(seam)
        if rule is None:
            return
        with self._lock:
            idx = self._calls.get(seam, 0)
            self._calls[seam] = idx + 1
        fire_error, sleep_s = rule.decide(self.seed, seam, idx)
        if sleep_s > 0:
            with self._lock:
                self._slept[seam] = self._slept.get(seam, 0.0) + sleep_s
            time.sleep(sleep_s)
        if fire_error:
            with self._lock:
                fired = self._fired.get(seam, 0)
                if rule.limit and fired >= rule.limit:
                    return
                self._fired[seam] = fired + 1
            raise rule._make_error()

    # -- introspection --------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {"seed": self.seed,
                    "calls": dict(self._calls),
                    "errors_fired": dict(self._fired),
                    "injected_sleep_s": {k: round(v, 6)
                                         for k, v in self._slept.items()}}

    def decisions(self, seam: str, n: int) -> list[tuple[bool, float]]:
        """The first ``n`` decisions a seam WILL make — pure replay, no
        state touched. The determinism oracle for tests and the smoke
        digest."""
        rule = self._rules.get(seam)
        if rule is None:
            return [(False, 0.0)] * n
        return [rule.decide(self.seed, seam, i) for i in range(n)]

    def digest(self, calls_per_seam: int = 256) -> str:
        """Hex digest of the full decision stream over every configured
        seam: two runs of the same seeded schedule MUST produce the
        same digest (the CI determinism gate diffs exactly this)."""
        h = hashlib.sha256()
        for seam in sorted(self._rules):
            for fire, sleep_s in self.decisions(seam, calls_per_seam):
                h.update(f"{seam}|{int(fire)}|{sleep_s:.9f};".encode())
        return h.hexdigest()


# -- module-level installation (what the seams consult) -----------------------
_ACTIVE: ChaosSchedule | None = None


def install(schedule: ChaosSchedule) -> ChaosSchedule:
    global _ACTIVE
    _ACTIVE = schedule
    return schedule


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> ChaosSchedule | None:
    return _ACTIVE


def fire(seam: str) -> None:
    """Called by production code at each seam. One None-check when no
    chaos is installed — safe on hot paths."""
    s = _ACTIVE
    if s is not None:
        s.fire(seam)


@contextlib.contextmanager
def scope(schedule: ChaosSchedule):
    """Install for the duration of a with-block (tests/bench phases)."""
    install(schedule)
    try:
        yield schedule
    finally:
        uninstall()


# -- socket-level faults (no seam needed: they attack a live listener) --------
def slow_loris(host: str, port: int, *, path: str = "/",
               duration: float = 1.0, interval: float = 0.05) -> int:
    """Hold a connection open dribbling an incomplete HTTP request one
    byte per ``interval`` for ``duration`` seconds, then drop it without
    ever finishing the headers. Returns bytes sent. A healthy threaded
    server serves other clients throughout (one handler thread is tied
    up, nothing else)."""
    payload = (f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
               "X-Slow: loris\r\n").encode()
    sent = 0
    deadline = time.monotonic() + duration
    with socket.create_connection((host, port), timeout=5.0) as s:
        for b in payload:
            if time.monotonic() >= deadline:
                break
            try:
                s.send(bytes([b]))
                sent += 1
            except OSError:
                break  # server gave up on us first — also a pass
            time.sleep(interval)
    return sent


def slow_h2_preface(host: str, port: int, *, duration: float = 1.0,
                    interval: float = 0.05) -> int:
    """The gRPC flavor: dribble a PARTIAL HTTP/2 client preface, then
    hang up. The connection thread must stay parked in its preface read
    without consuming a stream or blocking the accept loop."""
    preface = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"[:-4]  # never completes
    sent = 0
    deadline = time.monotonic() + duration
    with socket.create_connection((host, port), timeout=5.0) as s:
        for b in preface:
            if time.monotonic() >= deadline:
                break
            try:
                s.send(bytes([b]))
                sent += 1
            except OSError:
                break
            time.sleep(interval)
    return sent
