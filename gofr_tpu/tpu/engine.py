"""TPU inference engine: the framework's flagship datasource.

No reference equivalent (SURVEY §2 last rows): GoFr's container carries
Redis/SQL/PubSub clients (pkg/gofr/container/container.go:26-38); here the
accelerator is wired the same way — constructed from config (a model that
cannot be built fails startup: it is what the server is for),
health-checked into ``/.well-known/health``, observable through
``app_tpu_*`` metrics, reachable from handlers as ``ctx.tpu``.

TPU-first design:
  - Programs are jitted callables compiled AOT per (batch, seq) BUCKET.
    XLA traces once per static shape; serving arbitrary request shapes
    means padding to a small lattice of precompiled shapes, never
    recompiling on the hot path.
  - A single dispatcher (``CoalescingBatcher``) coalesces concurrent
    handler threads into one device dispatch, so MXU utilization scales
    with offered load.
  - Results transfer device->host once per batch (one ``jax.device_get``),
    and inputs are stacked host-side then transferred once.
  - Weights live on device permanently (params are device arrays, possibly
    sharded over a mesh by the config wiring; the engine is layout-agnostic).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..datasource import Health, STATUS_DEGRADED, STATUS_DOWN, STATUS_UP
from ..errors import DeadlineExceeded, ProgramNotFound, ServiceUnavailable
from ..observe.startup import StartupAccount
from ..resilience import current_deadline, current_slo_class
from . import hbm
from .batcher import ClassPolicy, CoalescingBatcher, pad_bucket

DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8)
DEFAULT_SEQ_BUCKETS = (32, 64, 128, 256, 512)


@dataclass
class Program:
    """One servable compiled function.

    kind="tokens": items are 1-D int32 token arrays of varying length;
      the runner pads to (Bb, Sb) buckets and calls
      ``fn(params, tokens[B,S], lengths[B])``.
    kind="fixed": items are pytrees of fixed-shape arrays; the runner
      stacks them on a new leading axis and calls ``fn(params, batch)``.

    ``fn`` must return an array (or pytree) with leading batch axis.
    """

    name: str
    fn: Callable
    params: Any
    kind: str = "tokens"
    batch_buckets: tuple[int, ...] = DEFAULT_BATCH_BUCKETS
    seq_buckets: tuple[int, ...] = DEFAULT_SEQ_BUCKETS
    example_item: Any = None  # fixed-kind: per-item input struct for warmup
    _jitted: Callable = field(init=False, default=None)
    _compiled_shapes: set = field(init=False, default_factory=set)

    def __post_init__(self):
        self.batch_buckets = tuple(sorted(self.batch_buckets))
        self.seq_buckets = tuple(sorted(self.seq_buckets))
        self._jitted = jax.jit(self.fn)

    @property
    def max_batch(self) -> int:
        return self.batch_buckets[-1]


class TPUEngine:
    """Registry of compiled programs + coalescing dispatch + health.

    Thread-safe: any number of handler threads may call ``predict``
    concurrently; per-program batchers serialize device dispatch.
    """

    def __init__(self, logger=None, metrics=None, max_delay: float = 0.004,
                 mesh=None, model_name: str = "", observe=None, gate=None,
                 class_policy: ClassPolicy | None = None):
        self.logger = logger
        self.metrics = metrics
        self.observe = observe  # Observe bundle (registry + flight recorder)
        # serving timeline (observe/timeline.py): None when emission is
        # off so hot paths pay one attribute test (see generator)
        tl = getattr(observe, "timeline", None) if observe is not None \
            else None
        self._tl = tl if (tl is not None and tl.enabled) else None
        # the account of this engine's start-up (observe/startup.py):
        # the container's, which the generator is handed the same way
        self.startup = observe.startup if observe is not None \
            else StartupAccount()
        # resilience.AdmissionGate TEMPLATE (None = admit everything):
        # each program gets its own clone (one gate per queue — a shared
        # wait EWMA would let a backlogged program shed a healthy one's
        # traffic), fed with that program's batch waits at dispatch
        self.gate = gate
        self._gates: dict[str, Any] = {}
        # SLO-class batching policy (None = classic FIFO): per-class
        # wait lines in every program's batcher — latency first,
        # throughput on a longer delay with a reserved pickup share.
        # Opt-in (TPU_SLO_BATCH_SHARE): the class-aware line runs the
        # Python dispatcher, giving up the native scheduler's
        # GIL-released wait.
        self.class_policy = class_policy
        self.max_delay = max_delay
        self.mesh = mesh
        self.model_name = model_name
        self.devices = jax.devices()
        self.platform = self.devices[0].platform
        self.device_kind = self.devices[0].device_kind
        self._programs: dict[str, Program] = {}
        self._batchers: dict[str, CoalescingBatcher] = {}
        self._lock = threading.Lock()
        self.generator = None  # set by config wiring for decoder models
        # disaggregated serving (gofr_tpu/pd/): the config wiring sets
        # exactly one of these for non-fused roles — a prefill worker's
        # coordinator (generate() routes through it) or a decode
        # worker's KV-ingest listener
        self.serving_role = "fused"
        self.pd_prefill = None
        self.pd_ingest = None
        # tenancy.TenantPlane, set by the config wiring when TPU_TENANTS
        # is configured; None = anonymous single-tenant serving
        self.tenancy = None
        self._closed = False
        if metrics is not None:
            # device-byte + arbiter gauges/counters (app_tpu_device_
            # bytes, app_tpu_hbm_*): attach even for engines without a
            # generator — the batcher's OOM-shed path counts through
            # the same registry
            hbm.set_metrics(metrics)
            try:
                metrics.set_gauge("app_tpu_devices", len(self.devices))
            except Exception:
                pass

    # -- registration --------------------------------------------------------
    def register(self, name: str, fn: Callable, params: Any, *,
                 kind: str = "tokens",
                 batch_buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS,
                 seq_buckets: Sequence[int] = DEFAULT_SEQ_BUCKETS,
                 example_item: Any = None) -> Program:
        prog = Program(name=name, fn=fn, params=params, kind=kind,
                       batch_buckets=tuple(batch_buckets),
                       seq_buckets=tuple(seq_buckets),
                       example_item=example_item)
        with self.startup.within("programs", program=name), self._lock:
            self._programs[name] = prog
            if self.gate is not None:
                self._gates[name] = self.gate.clone(name)
            self._batchers[name] = CoalescingBatcher(
                runner=lambda items, p=prog: self._run_batch(p, items),
                max_batch=prog.max_batch, max_delay=self.max_delay,
                name=f"tpu-{name}", on_dispatch=self._dispatch_metrics(prog),
                on_queue_depth=self._depth_gauge(name),
                on_expired=self._expired_counter(name),
                class_policy=self.class_policy, timeline=self._tl)
        if self.logger is not None:
            self.logger.info({"event": "tpu program registered", "program": name,
                              "kind": kind, "batch_buckets": list(prog.batch_buckets)})
        return prog

    def _depth_gauge(self, program: str):
        if self.metrics is None:
            return None

        def hook(depth: int) -> None:
            self.metrics.set_gauge("app_tpu_queue_depth", float(depth),
                                   program=program)
        return hook

    def _gate_for(self, program: str):
        """The program's own gate clone; created lazily for gates
        installed after registration (tests, dynamic reconfiguration)."""
        g = self._gates.get(program)
        if g is None and self.gate is not None:
            with self._lock:
                g = self._gates.get(program)
                if g is None:
                    # (GL203 suppressed: keyed by program NAME —
                    # bounded by register() calls, not by requests)
                    g = self.gate.clone(program)
                    self._gates[program] = g  # noqa: GL203
        return g

    def _dispatch_metrics(self, prog: Program):
        def hook(batch_size: int, oldest_wait: float) -> None:
            gate = self._gates.get(prog.name)
            if gate is not None:
                # the gate's shed decision tracks what a new arrival
                # would wait — exactly this program's oldest-item wait
                gate.note_wait(oldest_wait)
            if self.metrics is None:
                return
            bucket = pad_bucket(batch_size, prog.batch_buckets)
            self.metrics.record_histogram("app_tpu_batch_wait_duration",
                                          oldest_wait, program=prog.name)
            self.metrics.set_gauge("app_tpu_batch_fill", batch_size / bucket,
                                   program=prog.name)
        return hook

    def _expired_counter(self, program: str):
        def hook(n: int) -> None:
            if self.metrics is None:
                return
            for _ in range(n):
                self.metrics.increment_counter(
                    "app_tpu_expired_dropped_total", program=program)
        return hook

    # -- the batched device dispatch ----------------------------------------
    def _run_batch(self, prog: Program, items: list) -> list:
        t0 = time.monotonic()
        if prog.kind == "tokens":
            out = self._run_tokens(prog, items)
        else:
            out = self._run_fixed(prog, items)
        if self._tl is not None:
            self._tl.predict(t0, time.monotonic(), prog.name, len(items))
        if self.metrics is not None:
            self.metrics.record_histogram("app_tpu_device_execute_duration",
                                          time.monotonic() - t0, program=prog.name)
        return out

    def _run_tokens(self, prog: Program, items: list) -> list:
        lengths = [int(np.asarray(it).shape[0]) for it in items]
        Sb = pad_bucket(max(lengths), prog.seq_buckets)
        Bb = pad_bucket(len(items), prog.batch_buckets)
        tokens = np.zeros((Bb, Sb), np.int32)
        for i, it in enumerate(items):
            tokens[i, : lengths[i]] = np.asarray(it, np.int32)
        lens = np.zeros((Bb,), np.int32)
        lens[: len(items)] = lengths
        self._note_shape(prog, (Bb, Sb))
        out = prog._jitted(prog.params, jnp.asarray(tokens), jnp.asarray(lens))
        out = jax.device_get(out)
        return [jax.tree.map(lambda a: a[i], out) for i in range(len(items))]

    def _run_fixed(self, prog: Program, items: list) -> list:
        Bb = pad_bucket(len(items), prog.batch_buckets)
        pad = [items[-1]] * (Bb - len(items))
        batch = jax.tree.map(lambda *xs: np.stack(xs), *(list(items) + pad))
        self._note_shape(prog, (Bb,))
        out = prog._jitted(prog.params, batch)
        out = jax.device_get(out)
        return [jax.tree.map(lambda a: a[i], out) for i in range(len(items))]

    def _note_shape(self, prog: Program, shape: tuple) -> None:
        if shape not in prog._compiled_shapes:
            prog._compiled_shapes.add(shape)
            if self.logger is not None:
                self.logger.debug({"event": "tpu compile", "program": prog.name,
                                   "shape": list(shape)})

    # -- public API (ctx.tpu.predict) ---------------------------------------
    def predict(self, program: str, item: Any, timeout: float | None = 60.0,
                deadline=None, slo_class: str | None = None) -> Any:
        """Run one item through a registered program, coalescing with any
        concurrent callers. Returns the un-batched result (numpy).

        ``deadline`` (resilience.Deadline) defaults to the AMBIENT one
        the transport opened from the request's wire deadline
        (grpc-timeout / X-Request-Timeout): the wait is capped to the
        remaining budget and the item is dropped unexecuted if it
        expires while queued. An admission gate, when configured, sheds
        with ``TooManyRequests`` before the item ever joins the line.
        ``slo_class`` defaults to the transport's ambient class; the
        gate degrades throughput-class first, and with a class policy
        configured the batcher schedules the classes separately."""
        if self._closed:
            raise ServiceUnavailable("TPU engine is closed")
        batcher = self._batchers.get(program)
        if batcher is None:
            raise ProgramNotFound(program, list(self._programs))
        if deadline is None:
            deadline = current_deadline()
        if slo_class is None:
            slo_class = current_slo_class()
        if deadline is not None and deadline.expired():
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_tpu_expired_dropped_total", program=program)
            raise DeadlineExceeded(
                f"deadline expired before predict({program!r}) was queued")
        gate = self._gate_for(program)
        from .. import tracing

        span = tracing.current_span()
        trace_id = span.trace_id if span else ""
        tenant_spec = None
        if self.tenancy is not None:
            # same edge contract as generate(): resolve the ambient
            # tenant, apply its class default, consume its quota for
            # the duration of the call
            from ..tenancy.registry import current_tenant

            tenant_spec = self.tenancy.resolve(current_tenant())
            slo_class = self.tenancy.effective_class(tenant_spec, slo_class)
            try:
                self.tenancy.admit(tenant_spec, program=program,
                                   slo_class=slo_class, gate=gate)
            except BaseException:
                if self._tl is not None:
                    self._tl.shed(program, slo_class, trace_id)
                raise
        try:
            if gate is not None:
                try:
                    gate.admit(batcher.queue_depth(), program=program,
                               slo_class=slo_class,
                               tenant=tenant_spec.tenant_id
                               if tenant_spec is not None else "")
                except BaseException:
                    if self._tl is not None:
                        self._tl.shed(program, slo_class, trace_id)
                    raise
            self._validate_item(self._programs[program], item)
        except BaseException:
            if tenant_spec is not None:
                self.tenancy.release(tenant_spec.tenant_id)
            raise
        t0 = time.monotonic()
        entry = None
        if self.observe is not None:
            entry = self.observe.requests.add(
                "predict", program, trace_id, stage="batch-wait")
        failed = None
        try:
            return batcher.submit(item, timeout=timeout, deadline=deadline,
                                  slo_class=slo_class)
        except BaseException as e:
            failed = e
            raise
        finally:
            if tenant_spec is not None:
                self.tenancy.release(tenant_spec.tenant_id)
            dur = time.monotonic() - t0
            if self.observe is not None:
                self.observe.requests.remove(entry)
                if failed is not None:
                    # no request_id: that field is the generation-stream
                    # counter's namespace; a registry-entry id here would
                    # collide with it on /debug/events filters
                    self.observe.recorder.record(
                        "predict_failed",
                        trace_id=entry.trace_id, program=program,
                        duration_s=round(dur, 6), error=repr(failed))
            if self.metrics is not None:
                self.metrics.increment_counter("app_tpu_requests_total",
                                               program=program)
                self.metrics.record_histogram("app_tpu_predict_duration",
                                              dur, exemplar=trace_id or None,
                                              program=program)

    def predict_batch(self, program: str, items: list) -> list:
        """Direct batched execution, bypassing the coalescing queue (for
        subscribers that already hold a natural batch)."""
        prog = self._programs.get(program)
        if prog is None:
            raise ProgramNotFound(program)
        for it in items:
            self._validate_item(prog, it)
        out = []
        for i in range(0, len(items), prog.max_batch):
            out.extend(self._run_batch(prog, items[i : i + prog.max_batch]))
        if self.metrics is not None:
            for _ in items:  # one request per ITEM (the unit predict counts)
                self.metrics.increment_counter("app_tpu_requests_total",
                                               program=program)
        return out

    def _validate_item(self, prog: Program, item: Any) -> None:
        """Reject oversized inputs BEFORE they join a coalesced batch — a
        bad item inside the runner would fail every innocent request
        dispatched with it."""
        if prog.kind == "tokens":
            n = int(np.asarray(item).shape[0])
            limit = prog.seq_buckets[-1]
            if n == 0 or n > limit:
                raise ValueError(
                    f"program {prog.name!r}: item length {n} outside (0, {limit}]")
        elif prog.example_item is not None:
            want = jax.tree.map(lambda a: np.shape(a), prog.example_item)
            got = jax.tree.map(lambda a: np.shape(a), item)
            if want != got:
                raise ValueError(
                    f"program {prog.name!r}: item shapes {got} != expected {want}")

    def generate(self, *args, **kw):
        """Streaming token generation (decoder models). See
        ``generator.GenerationEngine.generate``. On a prefill-role
        worker (``TPU_SERVING_ROLE=prefill``) this routes through the
        P/D coordinator: local prefill-only compute, KV shipped to the
        decode pool, tokens relayed back — same signature, same
        ambient deadline/SLO pickup, the handler never knows. The
        durable-streams params (``seed``, ``continue_from``) pass
        through on both paths, so a resumed continuation admits
        identically on fused, prefill and decode workers."""
        if self.pd_prefill is not None:
            return self.pd_prefill.generate(*args, **kw)
        if self.generator is None:
            raise ServiceUnavailable(
                "no decoder model configured (TPU_MODEL must be a "
                "llama-family model for generate)")
        return self.generator.generate(*args, **kw)

    # -- warmup (compile-cache priming; BASELINE TTFT target needs this) -----
    def _warm_shapes(self, prog: Program) -> list[tuple]:
        if prog.kind == "tokens":
            return [(Bb, Sb) for Bb in prog.batch_buckets
                    for Sb in prog.seq_buckets]
        if prog.example_item is not None:
            return [(Bb,) for Bb in prog.batch_buckets]
        if self.logger is not None:
            self.logger.warn({"event": "tpu warmup skipped",
                              "program": prog.name,
                              "reason": "fixed-kind program registered "
                                        "without example_item"})
        return []

    def warmup(self, program: str | None = None) -> None:
        """Compile every bucket of the batcher programs, then the
        generator's: one record a call in the start-up account."""
        names = [program] if program else list(self._programs)
        plan = [(self._programs[n], shape) for n in names
                for shape in self._warm_shapes(self._programs[n])]
        with self.startup.warming() as acct:
            acct.expect(len(plan))
            for prog, shape in plan:
                with acct.call(prog.name, shape):
                    if prog.kind == "tokens":
                        Bb, Sb = shape
                        args = (jnp.zeros((Bb, Sb), jnp.int32),
                                jnp.full((Bb,), Sb, jnp.int32))
                    else:
                        args = (jax.tree.map(
                            lambda a: jnp.broadcast_to(
                                jnp.asarray(a)[None], shape + np.shape(a)),
                            prog.example_item),)
                    jax.block_until_ready(prog._jitted(prog.params, *args))
                self._note_shape(prog, shape)
            if self.generator is not None:
                self.generator.warmup()

    def stats(self) -> dict:
        return {"startup": self.startup.stats()}

    # -- health (reference container/health.go:5-25 shape) -------------------
    def health_check(self) -> Health:
        details: dict[str, Any] = {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "devices": len(self.devices),
            "model": self.model_name,
            "programs": {
                n: {"kind": p.kind,
                    "batch_buckets": list(p.batch_buckets),
                    "compiled_shapes": sorted(map(list, p._compiled_shapes))}
                for n, p in self._programs.items()
            },
        }
        if self._gates:
            details["admission"] = {n: g.stats()
                                    for n, g in sorted(self._gates.items())}
        if self.mesh is not None:
            details["mesh"] = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        try:
            stats = self.devices[0].memory_stats()
            if stats:
                details["hbm_bytes_in_use"] = stats.get("bytes_in_use")
                details["hbm_bytes_limit"] = stats.get("bytes_limit")
        except Exception:
            pass
        # per-subsystem declared bytes (the hbm accounting registry —
        # what the backend's opaque bytes_in_use decomposes into)
        acct = hbm.live_bytes()
        if acct:
            details["device_memory"] = acct
        # the arbiter's budget/lease/reclaim summary (full lease table
        # on /debug/vars and tools/hbm_report.py)
        arb = hbm.arbiter_stats()
        if arb["budget_bytes"] or arb["leases"]:
            details["hbm_arbiter"] = {
                k: arb[k] for k in ("budget_bytes", "in_use_bytes",
                                    "headroom_bytes", "reclaims",
                                    "sheds", "oom_retries")}
            # per-shard break-out (mesh engines settle one lease entry
            # per device): in-use + headroom per chip, so a balancer
            # can see ONE hot shard before it becomes a shed storm
            for k in ("device_budget_bytes", "devices"):
                if k in arb:
                    details["hbm_arbiter"][k] = arb[k]
        if self.generator is not None:
            details["generator"] = self.generator.stats()
            # the whole account is /debug/vars'; health says how far
            details["generator"].pop("startup", None)
        starting = self.startup.progress()
        if starting is not None:
            # until ready: which phase, and how far the warm-up is
            details["startup"] = starting
        if self.tenancy is not None:
            details["tenancy"] = self.tenancy.stats()
        if self.serving_role != "fused":
            # role-aware health (disaggregated-serving.md): a decode
            # worker reports its ingest listener, a prefill worker its
            # peer path — load balancers and the gateway read THIS to
            # know which pool a replica serves and whether the
            # cross-pool path is up
            details["serving_role"] = self.serving_role
            if self.pd_ingest is not None:
                details["pd"] = self.pd_ingest.stats()
            elif self.pd_prefill is not None:
                details["pd"] = self.pd_prefill.stats()
        if self._closed:
            return Health(STATUS_DOWN, details)
        if self.generator is not None and self.generator.down is not None:
            # device loop bricked (donated cache lost and unrecoverable)
            return Health(STATUS_DOWN, details)
        if self.pd_ingest is not None and not self.pd_ingest.stats()["listening"]:
            # a decode worker that cannot accept KV is not serving its
            # role, whatever its local engine thinks
            return Health(STATUS_DOWN, details)
        # A live engine with no programs can't serve yet.
        status = STATUS_UP if (self._programs or self.generator) else STATUS_DEGRADED
        if self.pd_prefill is not None and not self.pd_prefill.connected:
            # prefill worker with no decode path: still alive (it can
            # prefill, reconnect is armed) but degraded — readiness
            # surfaces let the balancer prefer connected replicas
            status = STATUS_DEGRADED
        return Health(status, details)

    def close(self) -> None:
        self._closed = True
        # PD halves first: the ingest listener stops accepting and the
        # coordinator fails its relays typed BEFORE the generator they
        # feed shuts down
        if self.pd_ingest is not None:
            self.pd_ingest.close()
        if self.pd_prefill is not None:
            self.pd_prefill.close()
        for b in self._batchers.values():
            b.close(drain=False)
        if self.generator is not None:
            self.generator.close()
