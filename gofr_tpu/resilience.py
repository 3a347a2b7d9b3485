"""Overload-safe serving primitives: deadlines, admission control, brownout.

No reference equivalent (the reference's resilience surface is the
client-side circuit breaker, pkg/gofr/service/circuit_breaker.go; nothing
server-side sheds load). This module is the serving-side discipline of
Dean & Barroso's "The Tail at Scale" applied to GoFr's one-Context
handler model:

  - ``Deadline``: one absolute-monotonic expiry threaded from the wire
    (gRPC ``grpc-timeout`` / HTTP ``X-Request-Timeout``) to the chip
    (batcher items, generation requests) and back. The transport parses
    it once and opens a ``deadline_scope``; everything downstream —
    handler, ``ctx.tpu.predict``, ``generate`` — reads the ambient
    deadline without per-call plumbing, and the dispatcher DROPS
    already-expired items before burning device time on a caller that
    is gone.
  - ``AdmissionGate``: a bounded gate in front of the batcher queue and
    the generation slot queue. Under overload every queued request gets
    slower; the gate instead fails the excess FAST
    (``TooManyRequests`` -> 429 / ``RESOURCE_EXHAUSTED``) with a
    ``Retry-After`` estimate, keeping admitted-request latency flat and
    goodput at capacity (proved by ``tools/chaos_bench.py``).
  - Brownout: between "healthy" and "shedding" there is a window where
    the gate caps ``max_new_tokens`` so each admitted stream costs
    fewer decode iterations — degrading answer length before
    availability.
  - SLO classes: every request carries a serving class —
    ``latency`` (interactive, the default) or ``throughput`` (batch/
    offline, tagged via the ``X-SLO-Class`` header / ``slo-class``
    gRPC metadata). The class rides the same ambient-threading-local
    channel as the deadline, and overload degrades CLASSES IN ORDER:
    the gate sheds and brownouts throughput-class at a fraction of the
    latency-class bounds, so batch traffic absorbs pressure before an
    interactive request feels it (docs/advanced-guide/
    serving-scheduler.md).

Thread model: the ambient deadline and SLO class are
``threading.local`` (handlers run one-per-thread on both transports,
like ``tracing.current_span``); the gate's EWMA state is guarded by
one small lock and is touched only at admission/dispatch, never per
token.
"""

from __future__ import annotations

import contextlib
import threading
import time

from .errors import DeadlineExceeded, TooManyRequests

__all__ = [
    "AdmissionGate",
    "Deadline",
    "DeadlineExceeded",
    "DecodePipelinePolicy",
    "SLO_CLASSES",
    "SLO_LATENCY",
    "SLO_THROUGHPUT",
    "TooManyRequests",
    "current_deadline",
    "current_slo_class",
    "deadline_scope",
    "parse_http_timeout",
    "parse_slo_class",
    "slo_scope",
]


class Deadline:
    """An absolute expiry on the monotonic clock.

    Built once at the transport edge and carried by reference; every
    layer asks the same object ``remaining()``/``expired()`` so clock
    reads stay consistent and the budget shrinks as work progresses
    (the grpc-timeout contract: the deadline covers the WHOLE request,
    not each hop)."""

    __slots__ = ("at",)

    def __init__(self, at: float):
        self.at = float(at)

    @classmethod
    def after(cls, seconds: float) -> "Deadline":
        return cls(time.monotonic() + float(seconds))

    def remaining(self) -> float:
        """Seconds left; <= 0 once expired."""
        return self.at - time.monotonic()

    def expired(self) -> bool:
        return time.monotonic() >= self.at

    def budget(self, timeout: float | None) -> float:
        """Tighten a layer's own timeout to what the deadline allows."""
        rem = self.remaining()
        return rem if timeout is None else min(timeout, rem)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"Deadline(in {self.remaining() * 1e3:.1f}ms)"


_scope = threading.local()


def current_deadline() -> Deadline | None:
    """The ambient deadline opened by the transport for this handler
    thread (None outside any scope)."""
    return getattr(_scope, "deadline", None)


@contextlib.contextmanager
def deadline_scope(deadline: Deadline | None):
    """Make ``deadline`` ambient for the calling thread. Nested scopes
    keep the TIGHTER deadline (a handler-set sub-deadline may shrink
    the budget, never extend the caller's)."""
    prev = getattr(_scope, "deadline", None)
    if deadline is not None and prev is not None and prev.at < deadline.at:
        deadline = prev
    _scope.deadline = deadline if deadline is not None else prev
    try:
        yield deadline
    finally:
        _scope.deadline = prev


class DecodePipelinePolicy:
    """Depth policy for the generator's decode dispatch pipeline.

    ``depth`` is the ceiling (the engine's ``decode_pipeline``, 2): how many
    fused decode blocks may be in flight on the device stream at once.
    Depth 2 is the steady-state win — the host reaps block N while
    block N+1 computes, so the device never idles between blocks — but
    a deeper queue also means anything dispatched NEXT (a latency-class
    admission's prefill, a chunk-lattice slice) waits behind more queued
    compute. ``target()`` is consulted before every pipeline top-up and
    collapses to 1 exactly when that wait would cost an SLO:

      - a latency-class request is waiting AND a slot is free for it
        (``latency_admittable``: its prefill, dispatched now, must queue
        behind at most ONE in-flight block). A waiter alone does not
        collapse the depth: with every slot busy there is no prefill to
        queue, and untagged traffic is latency class, so any standing
        queue on a full batch would pin the loop at depth 1 and leave
        the device dry behind every reap. What the rule costs at depth
        2: a slot that finishes inside block N is seen free at N's
        reap, when N+1 is already queued, so the waiter's prefill runs
        one block later than it would at depth 1 (and its first token
        comes that block later); the bound above still holds, because
        the reap that frees the slot is the moment the policy answers 1;
      - a chunk-lattice admission was deferred by the in-flight pass
        (the lattice needs a fully reaped loop — its interleaved decode
        blocks re-decode from host token state);
      - speculative decoding is active (verify windows are built from
        host-delivered history, which only exists after a reap).

    Pure and lock-free: callers pass the facts, the policy returns a
    depth — the generator owns WHEN to ask, this owns the answer (and
    stats()/tests read the same answer, so the decision is observable
    and deterministic)."""

    __slots__ = ("depth",)

    def __init__(self, depth: int = 2):
        self.depth = max(1, int(depth))

    def target(self, *, latency_admittable: bool = False,
               lattice_deferred: bool = False,
               spec_decode: bool = False) -> int:
        if latency_admittable or lattice_deferred or spec_decode:
            return 1
        return self.depth


# -- SLO classes ------------------------------------------------------------
# Two classes, not N priorities: the scheduler's contract is a latency
# SLO for interactive traffic and a drain guarantee for batch traffic.
# More levels would just be a priority queue with extra starvation
# surface; everything downstream (batcher pickup, gate degradation,
# metric labels) keys on these two strings.
SLO_LATENCY = "latency"
SLO_THROUGHPUT = "throughput"
SLO_CLASSES = (SLO_LATENCY, SLO_THROUGHPUT)

_THROUGHPUT_ALIASES = frozenset({"throughput", "batch", "bulk", "offline",
                                 "best-effort", "besteffort"})


def parse_slo_class(val: str | None) -> str:
    """``X-SLO-Class`` header / ``slo-class`` gRPC metadata -> class.
    Unknown or absent values are LATENCY: untagged traffic keeps the
    full SLO (opting INTO deprioritization must be explicit — a typo in
    a batch job's header costs capacity, never an interactive user's
    latency)."""
    if not val:
        return SLO_LATENCY
    return (SLO_THROUGHPUT if val.strip().lower() in _THROUGHPUT_ALIASES
            else SLO_LATENCY)


def current_slo_class() -> str:
    """The ambient SLO class opened by the transport for this handler
    thread (latency outside any scope)."""
    return getattr(_scope, "slo_class", None) or SLO_LATENCY


@contextlib.contextmanager
def slo_scope(slo_class: str | None):
    """Make ``slo_class`` ambient for the calling thread. None keeps
    the enclosing scope's class (transports call this unconditionally);
    a nested explicit class WINS — a handler may re-class its own
    downstream work, e.g. fan-out prefetches as throughput."""
    prev = getattr(_scope, "slo_class", None)
    _scope.slo_class = slo_class if slo_class is not None \
        else (prev or SLO_LATENCY)
    try:
        yield _scope.slo_class
    finally:
        _scope.slo_class = prev


_HTTP_TIMEOUT_UNITS = (("ms", 1e-3), ("us", 1e-6), ("s", 1.0), ("m", 60.0))


def parse_http_timeout(val: str | None) -> float | None:
    """``X-Request-Timeout`` header -> seconds. Accepts a bare float
    (seconds) or a unit suffix: ``50ms``, ``2s``, ``250us``, ``1m``.
    Malformed/non-positive values are ignored (None) — a bad client
    header must never fail the request itself."""
    if not val:
        return None
    val = val.strip().lower()
    scale = 1.0
    for suffix, s in _HTTP_TIMEOUT_UNITS:
        if val.endswith(suffix):
            val, scale = val[: -len(suffix)], s
            break
    try:
        seconds = float(val) * scale
    except ValueError:
        return None
    return seconds if seconds > 0 else None


class AdmissionGate:
    """Bounded admission with early shedding and a brownout band.

    One gate fronts one queue (a program's coalescing batcher, or the
    generation engine's pending queue). ``admit(depth)`` raises
    ``TooManyRequests`` when either bound is crossed:

      - ``max_queue_depth``: more than this many waiters queued;
      - ``max_queue_delay``: the EWMA of observed queue wait exceeds
        this — the "every request is already slow" signal that depth
        alone misses when service time varies.

    The wait EWMA is fed by the dispatcher (``note_wait``) with each
    batch's oldest-item wait / each admission's queue wait, so the gate
    tracks the latency a NEW arrival would actually experience. The
    shed's ``Retry-After`` is that same estimate — honest backpressure
    a client-side retry policy (service/retry.py) can obey.

    Brownout: with ``brownout_delay`` configured, ``cap_tokens`` caps
    ``max_new_tokens`` while the wait EWMA sits above the threshold —
    shorter answers per admitted stream instead of shed streams.

    SLO-class degradation order: throughput-class requests see every
    bound scaled by ``throughput_factor`` (default 0.5) — half the
    queue depth, half the delay budget, brownout at half the wait
    threshold. Under rising load the gate therefore sheds and
    brownouts BATCH traffic first, and latency-class requests keep the
    full bounds until throughput is fully squeezed out. Factor 1.0
    restores class-blind gating.

    Both bounds disabled (0) -> the gate admits everything and costs
    one attribute read per request.
    """

    # EWMA smoothing for the observed-wait estimate: heavy enough to
    # ride out one odd batch, light enough to track a load swing within
    # a few dispatches.
    ALPHA = 0.3

    def __init__(self, max_queue_depth: int = 0, max_queue_delay: float = 0.0,
                 brownout_delay: float = 0.0, brownout_max_new: int = 32,
                 throughput_factor: float = 0.5,
                 name: str = "", metrics=None, tracer=None, logger=None):
        self.max_queue_depth = int(max_queue_depth)
        self.max_queue_delay = float(max_queue_delay)
        self.brownout_delay = float(brownout_delay)
        self.brownout_max_new = int(brownout_max_new)
        # clamp to (0, 1]: 0 would shed ALL throughput traffic even at
        # idle, and > 1 would invert the degradation order
        self.throughput_factor = min(1.0, max(0.01, float(throughput_factor)))
        self.name = name
        self.metrics = metrics
        self.tracer = tracer
        self.logger = logger
        self.enabled = self.max_queue_depth > 0 or self.max_queue_delay > 0
        self._lock = threading.Lock()
        self._wait_ewma = 0.0
        # per-class brownout band state (edge-logged, gauge-backed):
        # throughput's band engages earlier under class degradation
        self._brownout_on = {c: False for c in SLO_CLASSES}
        self.sheds = 0
        self.sheds_by_class = {c: 0 for c in SLO_CLASSES}
        self.brownout_capped = 0

    def clone(self, name: str) -> "AdmissionGate":
        """A fresh gate with the same bounds and telemetry plumbing but
        its OWN state — one gate must front one queue, so a multi-program
        engine clones its configured gate per program (a shared wait
        EWMA would let a backlogged program shed a healthy one's
        traffic)."""
        return AdmissionGate(
            max_queue_depth=self.max_queue_depth,
            max_queue_delay=self.max_queue_delay,
            brownout_delay=self.brownout_delay,
            brownout_max_new=self.brownout_max_new,
            throughput_factor=self.throughput_factor,
            name=name, metrics=self.metrics, tracer=self.tracer,
            logger=self.logger)

    # -- dispatcher side ------------------------------------------------------
    def note_wait(self, wait_s: float) -> None:
        """Feed one observed queue wait (seconds) into the estimate."""
        with self._lock:
            self._wait_ewma += self.ALPHA * (wait_s - self._wait_ewma)

    @property
    def estimated_wait(self) -> float:
        return self._wait_ewma

    # -- admission side -------------------------------------------------------
    def admit(self, depth: int, program: str = "",
              slo_class: str = SLO_LATENCY, tenant: str = "") -> None:
        """Admit or raise ``TooManyRequests``. ``depth`` is the queue's
        CURRENT depth (the caller reads it lock-free; an off-by-a-few
        race only moves the shed boundary by that much).
        Throughput-class requests are judged against bounds scaled by
        ``throughput_factor`` — they shed FIRST as load rises.
        ``tenant`` only labels the shed telemetry (pass it when a
        tenancy plane is installed); global pressure bounds stay
        tenant-blind."""
        if not self.enabled:
            return
        f = (self.throughput_factor if slo_class == SLO_THROUGHPUT else 1.0)
        wait = self._wait_ewma
        over_depth = (self.max_queue_depth > 0
                      and depth >= max(1, int(self.max_queue_depth * f)))
        over_delay = (self.max_queue_delay > 0 and depth > 0
                      and wait > self.max_queue_delay * f)
        if not (over_depth or over_delay):
            return
        self._shed(depth, wait, program, slo_class, tenant=tenant)

    def admit_tenant(self, spec, quotas, program: str = "",
                     slo_class: str = SLO_LATENCY) -> None:
        """Per-tenant quota admission (rps token bucket + concurrency),
        routed through the gate's one shed-bookkeeping path. Over-quota
        raises ``TooManyRequests`` with ``reason=tenant_quota`` — a 429
        scoped to THIS tenant while everyone else keeps flowing, which
        is the opposite failure shape from a global queue shed. On
        success the quota is CONSUMED; the caller must release the
        concurrency slot at the request's terminal
        (``quotas.release(tenant_id)``)."""
        why, retry_after = quotas.check(spec)
        if why is None:
            return
        tid = spec.tenant_id
        self._record_shed(program, slo_class,
                          {"reason": "tenant_quota", "quota": why},
                          tenant=tid)
        raise TooManyRequests(
            f"{self.name or 'admission'}: tenant {tid!r} over {why} "
            f"quota — shed ({slo_class})",
            retry_after=max(0.05, retry_after), reason="tenant_quota")

    def _record_shed(self, program: str, slo_class: str,
                     attributes: dict, trace_id: str = "",
                     tenant: str = "") -> None:
        """The one shed-bookkeeping path (queue pressure AND memory
        pressure): counters, the ``app_tpu_shed_total`` increment
        exemplar'd by the request's trace, and the zero-length
        ``tpu.shed`` marker span — so the two pressure kinds can never
        drift apart in what they record. ``trace_id`` overrides the
        ambient-span lookup for callers off the handler thread (the
        generation loop)."""
        self.sheds += 1
        if slo_class in self.sheds_by_class:
            self.sheds_by_class[slo_class] += 1
        now = time.monotonic()
        if not trace_id and (self.metrics is not None
                             or self.tracer is not None):
            from . import tracing

            span = tracing.current_span()  # the shed caller's request
            trace_id = span.trace_id if span is not None else ""
        if self.metrics is not None:
            try:
                # the tenant label exists only on tenancy-enabled
                # deployments — without a plane the series names stay
                # bit-identical to pre-tenancy builds
                labels = {"program": program or self.name,
                          "slo_class": slo_class}
                if tenant:
                    labels["tenant"] = tenant
                self.metrics.increment_counter(
                    "app_tpu_shed_total", exemplar=trace_id or None,
                    **labels)
            except Exception:
                pass
        if self.tracer is not None:
            try:
                # zero-length marker span: the request's trace shows
                # WHERE it died and WHY (queue state or memory reason)
                attrs = {**attributes,
                         "program": program or self.name,
                         "slo_class": slo_class}
                if tenant:
                    attrs.setdefault("tenant", tenant)
                self.tracer.record_span(
                    "tpu.shed", now, now, trace_id=trace_id or None,
                    attributes=attrs)
            except Exception:
                pass

    def _shed(self, depth: int, wait: float, program: str,
              slo_class: str = SLO_LATENCY, tenant: str = "") -> None:
        # honest Retry-After: the current wait estimate, floored so a
        # zero-estimate early shed doesn't invite an instant retry storm
        self._record_shed(program, slo_class,
                          {"queue_depth": depth,
                           "wait_ewma_ms": round(wait * 1e3, 3)},
                          tenant=tenant)
        raise TooManyRequests(
            f"{self.name or 'admission'}: queue depth {depth}, "
            f"estimated wait {wait * 1e3:.0f}ms — shed ({slo_class})",
            retry_after=max(0.05, wait))

    def shed_memory(self, program: str = "",
                    slo_class: str = SLO_LATENCY,
                    retry_after: float = 1.0,
                    trace_id: str = "") -> TooManyRequests:
        """Route an HBM-arbiter allocation failure through the gate's
        shed surface: same counters (``sheds``/``sheds_by_class``/
        ``app_tpu_shed_total``), same ``tpu.shed`` marker span, same
        429 + ``Retry-After`` contract as a queue shed — with
        ``reason: hbm`` attached so dashboards can split memory
        pressure from queue pressure. RETURNS the error (the caller
        decides whether to raise it or deliver it into a stream that
        already exists); the arbiter's own ``app_tpu_hbm_shed_total``
        is counted by ``hbm.note_shed`` at the raise site, not here.
        ``trace_id``: the request's trace when the caller is off the
        handler thread (the generation loop), else the ambient span
        is used."""
        self._record_shed(program, slo_class, {"reason": "hbm"},
                          trace_id=trace_id)
        return TooManyRequests(
            f"{self.name or 'admission'}: device memory exhausted — "
            f"shed ({slo_class})", retry_after=max(0.05, retry_after),
            reason="hbm")

    def cap_tokens(self, max_new_tokens: int,
                   slo_class: str = SLO_LATENCY) -> int:
        """Brownout: cap a generation request's token budget while the
        queue-wait estimate sits above ``brownout_delay``. Throughput-
        class requests brown out at ``brownout_delay *
        throughput_factor`` — answer length degrades for batch traffic
        a full band before interactive traffic is touched."""
        if self.brownout_delay <= 0:
            return max_new_tokens
        wait = self._wait_ewma
        active = self._refresh_brownout(wait)[slo_class]
        if not active or max_new_tokens <= self.brownout_max_new:
            return max_new_tokens
        self.brownout_capped += 1
        if self.metrics is not None:
            try:
                self.metrics.increment_counter("app_tpu_brownout_capped_total",
                                               slo_class=slo_class)
            except Exception:
                pass
        return self.brownout_max_new

    def _band_threshold(self, slo_class: str) -> float:
        return self.brownout_delay * (
            self.throughput_factor if slo_class == SLO_THROUGHPUT else 1.0)

    def _refresh_brownout(self, wait: float) -> dict:
        """Recompute EVERY class's band state from the current wait
        estimate (band state is PER CLASS — throughput engages a full
        factor earlier, and keying one flag on mixed traffic would flap
        the gauge/log). Refreshing all classes on any observation is
        what lets a class whose traffic vanished — e.g. throughput
        fully shed by admit() and never reaching here — still CLEAR
        once the estimate recovers. Emits the per-class gauge AND the
        pre-existing unlabeled any-class series on each edge."""
        states = {c: wait > self._band_threshold(c) for c in SLO_CLASSES}
        if states != self._brownout_on:
            with self._lock:
                changed = {c: a for c, a in states.items()
                           if a != self._brownout_on.get(c, False)}
                if changed:
                    self._brownout_on = states
                    for cls, active in changed.items():
                        if self.metrics is not None:
                            try:
                                self.metrics.set_gauge(
                                    "app_tpu_brownout_active",
                                    1.0 if active else 0.0, slo_class=cls)
                            except Exception:
                                pass
                        if self.logger is not None:
                            self.logger.warn({
                                "event": "brownout " + ("entered" if active
                                                        else "cleared"),
                                "gate": self.name,
                                "slo_class": cls,
                                "wait_ewma_ms": round(wait * 1e3, 1)})
                    if self.metrics is not None:
                        try:  # the unlabeled series dashboards pinned
                            # before the per-class split keeps flowing
                            self.metrics.set_gauge(
                                "app_tpu_brownout_active",
                                1.0 if any(states.values()) else 0.0)
                        except Exception:
                            pass
        return states

    def stats(self) -> dict:
        # brownout_active derives LIVE from the estimate (not the
        # event-driven flags): it must read False after recovery even
        # if no request has touched cap_tokens since
        wait = self._wait_ewma
        active = (self.brownout_delay > 0
                  and any(wait > self._band_threshold(c)
                          for c in SLO_CLASSES))
        return {
            "enabled": self.enabled,
            "max_queue_depth": self.max_queue_depth,
            "max_queue_delay": self.max_queue_delay,
            "throughput_factor": self.throughput_factor,
            "wait_ewma_ms": round(wait * 1e3, 3),
            "sheds": self.sheds,
            "sheds_by_class": dict(self.sheds_by_class),
            "brownout_active": active,
            "brownout_capped": self.brownout_capped,
        }


def gate_from_config(cfg, name: str, metrics=None, tracer=None,
                     logger=None) -> AdmissionGate | None:
    """Build a gate from ``TPU_MAX_QUEUE_DEPTH`` / ``TPU_MAX_QUEUE_DELAY``
    / ``TPU_BROWNOUT_DELAY`` / ``TPU_BROWNOUT_MAX_NEW`` /
    ``TPU_SLO_THROUGHPUT_FACTOR`` (all bounds default off: enabling
    load shedding is a capacity-planning decision, not a framework
    default). Returns None when fully disabled."""
    depth = cfg.get_int("TPU_MAX_QUEUE_DEPTH", 0)
    delay = cfg.get_float("TPU_MAX_QUEUE_DELAY", 0.0)
    b_delay = cfg.get_float("TPU_BROWNOUT_DELAY", 0.0)
    if depth <= 0 and delay <= 0 and b_delay <= 0:
        return None
    return AdmissionGate(
        max_queue_depth=depth, max_queue_delay=delay,
        brownout_delay=b_delay,
        brownout_max_new=cfg.get_int("TPU_BROWNOUT_MAX_NEW", 32),
        throughput_factor=cfg.get_float("TPU_SLO_THROUGHPUT_FACTOR", 0.5),
        name=name, metrics=metrics, tracer=tracer, logger=logger)
