"""Continuous-batching token generation: the streaming-decode serving loop.

No reference equivalent (SURVEY §5 "checkpoint/resume": the reference is a
stateless microservice framework; token streaming is the BASELINE.json
Llama target). Design:

  - A FIXED pool of B batch slots shares one preallocated KV cache
    [L, B, KV, Smax, hd]. Slots are admitted/retired independently via a
    per-slot ``lengths`` cursor — XLA shapes never change, so the decode
    step compiles exactly once.
  - ADMISSION runs a per-sequence prefill jitted at a small lattice of
    prompt buckets, writing KV straight into the slot with
    ``dynamic_update_slice`` (slot index is traced — no per-slot
    recompile) and emitting the first token, so TTFT = one prefill
    dispatch, never waiting for a decode round.
  - DECODE is one jitted step over all B slots per iteration — inactive
    slots compute but their cursors are frozen, so occupancy only affects
    useful-token throughput, never shape or compile state.
  - The KV cache is DONATED through both jits: the cache buffer is
    updated in place in HBM, zero copies per token.
  - Sampling (greedy + temperature) is fused into the jitted step; the
    host sees only B int32s per iteration.

Consumers call ``generate()`` from any thread and read tokens off a
stream; one background thread owns the device loop.
"""

from __future__ import annotations

import functools
import itertools
import queue
import threading
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import chaos, compile_cache
from ..errors import DeadlineExceeded, UnsupportedOptions
from ..models import family, llama
from ..models.common import ModelConfig
from ..observe.stall import StallWatch
from ..observe.startup import StartupAccount
from ..resilience import (SLO_LATENCY, SLO_THROUGHPUT, DecodePipelinePolicy,
                          current_deadline, current_slo_class)
from ..tenancy.fair import WeightedFairLine
from ..tenancy.registry import current_tenant
from ..wire import PushStream, burst
from . import hbm, prefill_plan, programs
from .batcher import pad_bucket
from .kvcache import HostKV, ShardedHostKV, clamp_restore_len, dense_hostkv

_REQ_IDS = itertools.count(1)


class _ClassPending:
    """SLO-class-aware pending line for the serving loop: latency-class
    requests are picked first; a weighted anti-starvation counter hands
    every Nth pick to the throughput line while it has waiters, so
    saturating interactive traffic can never starve batch streams out
    of the slot pool entirely (the generator-side mirror of the
    batcher's ClassPolicy reserve).

    Thread model: any thread puts (``generate()``); ONLY the serving
    loop pops — the same single-consumer contract the old queue.Queue
    carried, which is what makes pop-then-push-front requeues exact.

    Each class line is a ``WeightedFairLine``: inside a class, tenants
    are served deficit-round-robin over their registry queue weight
    (2:1:1 weights pop A,A,B,C under saturation). Requests without a
    tenant all ride the default line, which collapses each class back
    to the plain FIFO this started as — the latency/throughput split
    and anti-starvation streak above are unchanged."""

    def __init__(self, throughput_share: float = 0.25):
        share = min(max(float(throughput_share), 0.0), 1.0)
        # share -> latency picks per throughput pick, FLOORED so the
        # realized contended fraction 1/(weight+1) is always >= the
        # configured share (0.25 -> 3:1, 0.5 -> 1:1, >= 0.5 rounds
        # toward throughput-first). None disables the guarantee
        # (throughput then drains only when the latency line is empty).
        self._weight = (int((1.0 - share) / share) if share > 0 else None)
        self._lat = WeightedFairLine()
        self._thr = WeightedFairLine()
        self._lock = threading.Lock()
        self._lat_streak = 0
        self._prev_streak = 0  # streak before the most recent pop

    def put(self, req: "_Request") -> None:
        with self._lock:
            (self._thr if req.slo_class == SLO_THROUGHPUT
             else self._lat).append(req)

    def put_front(self, req: "_Request") -> None:
        """UNDO the most recent pop: return the request to the head of
        its class line AND restore the anti-starvation streak to its
        pre-pop value (the in-flight lattice deferral). Without the
        restore, a throughput request whose streak-earned turn lands
        in a deferred pass would burn its credit with nothing served —
        under latency saturation its admission could slip far past the
        configured share. Valid because pops and push-fronts come from
        the single consumer thread, back-to-back."""
        with self._lock:
            (self._thr if req.slo_class == SLO_THROUGHPUT
             else self._lat).appendleft(req)
            self._lat_streak = self._prev_streak

    def get_nowait(self, allow_throughput: bool = True) -> "_Request":
        """Pop the next admissible request. ``allow_throughput=False``
        is the slot-reservation path: the caller is filling one of the
        latency-reserved slots, so only the latency line may serve it
        (raises queue.Empty when only throughput waits)."""
        with self._lock:
            use_thr = allow_throughput and bool(self._thr) and (
                not self._lat
                or (self._weight is not None
                    and self._lat_streak >= self._weight))
            line = self._thr if use_thr else self._lat
            if not line:
                raise queue.Empty
            self._prev_streak = self._lat_streak
            if use_thr:
                self._lat_streak = 0
            else:
                self._lat_streak += 1
            return line.popleft()

    def qsize(self) -> int:
        return len(self._lat) + len(self._thr)

    def qsize_class(self, slo_class: str) -> int:
        return len(self._thr if slo_class == SLO_THROUGHPUT else self._lat)

    def qsize_by_tenant(self) -> dict[str, int]:
        """Queued requests per tenant across both class lines (the
        per-tenant queue-depth gauge; snapshot under the put lock so a
        concurrent put can't double-count a request mid-move)."""
        with self._lock:
            out = dict(self._lat.by_tenant())
            for tid, n in self._thr.by_tenant().items():
                out[tid] = out.get(tid, 0) + n
            return out

    def empty(self) -> bool:
        return not (self._lat or self._thr)


class GenerationError(RuntimeError):
    pass


class GenStream(PushStream):
    """Iterator over generated token ids; ``cancel()`` releases the slot.

    A PushStream: transports may register a zero-handoff sink
    (``set_sink``) so the serving loop's ``_deliver`` hands each token
    straight to the connection writer instead of waking a consumer
    thread — the first-token fast path of the gRPC/HTTP streamers.
    ``stream.map(fn)`` adapts tokens to messages/chunks for either."""

    def __init__(self, request_id: int, engine: "GenerationEngine",
                 logprobs: bool = False):
        super().__init__()  # _q + sink state (wire.PushStream)
        self.request_id = request_id
        self._engine = engine
        self.cancelled = threading.Event()
        self.prompt_len = 0
        self.logprobs = logprobs  # items are (token, logprob) tuples
        # TTFT decomposition (time.monotonic seconds): "submit" set by
        # generate(), "admit" when the serving loop pops the request,
        # "prefill_done" when the first token hits this queue. Lets a
        # client attribute its observed TTFT to admission wait vs
        # prefill vs delivery wake-up (tools/ttft_probe.py).
        self.trace: dict[str, float] = {}
        # flight-recorder state (set by generate() when the engine has an
        # Observe bundle): the request's W3C trace context — inherited
        # from the submitting thread's span or minted fresh — and its
        # in-flight registry entry
        self.traceparent: str | None = None
        self.trace_id: str = ""
        self.obs_entry = None
        self.failed: str | None = None  # set by the loop's error handler
        # canonical-wide-event state (docs/advanced-guide/
        # observability.md "wide events"): accumulated by the serving
        # loop, emitted once at the stream's terminal outcome
        self.slo_class: str = SLO_LATENCY
        self.chunks = 0             # mid-chunk dispatches of this prefill
        self.cache_tier: str | None = None  # kvcache tier that served it
        self.cache_tokens = 0       # prompt positions the tier covered
        # deadline-expiry site for the wide event ("queue"/"mid-prefill"/
        # "mid-decode"; "post-handoff" for ingested P/D requests — the
        # decode-side record that a request died AFTER the pool boundary)
        self.where: str | None = None
        # durable-stream resume state (docs/advanced-guide/resilience.md
        # "stream resume contract"): ``cursor_base`` is the absolute
        # generated-token index this stream CONTINUES from (0 for a
        # fresh request) — token i of this stream sits at absolute
        # cursor ``cursor_base + i``; ``seed`` is the per-request
        # sampling seed the resume token must carry so a continuation
        # re-keys the PRNG identically (None for greedy requests)
        self.cursor_base = 0
        self.seed: int | None = None
        # tenancy: the resolved (canonical) tenant id for wide events
        # and per-tenant metric labels; ``_tenant_held`` marks a live
        # concurrency-quota slot that must be released exactly once at
        # the stream's terminal (whatever that terminal is)
        self.tenant: str = "default"
        self._tenant_held = False

    def tokens(self) -> list[int]:
        """Drain the whole stream (blocking) into a list of ids
        (logprobs, when enabled, are dropped here — iterate for them)."""
        return [t[0] if isinstance(t, tuple) else t for t in self]

    def cancel(self) -> None:
        self.cancelled.set()


class _Request:
    __slots__ = ("stream", "prompt", "given", "max_new", "temperature",
                 "top_k",
                 "eos_id", "adapter", "enqueued_at", "lattice_peek",
                 "kv_match", "deadline", "slo_class", "kv_sink",
                 "kv_shipped", "ingest", "seed", "pos_base", "tenant",
                 "tenant_weight")

    @property
    def logprobs(self) -> bool:
        return self.stream.logprobs

    def __init__(self, stream: GenStream, prompt: np.ndarray, max_new: int,
                 temperature: float, top_k: int, eos_id: int | None,
                 adapter: int = 0, deadline=None,
                 slo_class: str = SLO_LATENCY, block: int = 0):
        self.stream = stream
        # a family that generates a block of ``block`` positions at a
        # time prefills a prompt's whole blocks; the tokens left over
        # open its first generated block as given positions
        # (``given``; empty for every other family). Everything that
        # runs, stores or restores a prompt sees the whole blocks alone
        # and so stays on block boundaries
        cut = len(prompt) - len(prompt) % block if block else len(prompt)
        self.prompt, self.given = prompt[:cut], prompt[cut:]
        self.max_new = max_new
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.adapter = adapter
        self.enqueued_at = time.monotonic()
        self.lattice_peek: tuple[int, bool] | None = None
        # memoized CacheManager.match verdict, keyed by the manager's
        # version counter (see GenerationEngine._kv_match)
        self.kv_match: tuple[int, Any] | None = None
        # resilience.Deadline: expired requests are dropped at admission
        # (no prefill dispatch for a caller that already gave up)
        self.deadline = deadline
        # resilience SLO class: selects the pending line, the gate's
        # degradation band, and the per-class telemetry labels
        self.slo_class = slo_class
        # disaggregated serving (gofr_tpu/pd/): ``kv_sink`` marks a
        # PREFILL-ONLY request — prefill runs normally, the slot's KV
        # streams out through the sink per chunk, the single delivered
        # token is the sampled first token, and the slot retires
        # without decoding. ``ingest`` is the DECODE-side mirror:
        # (HostKV, first_token, first_lp) shipped by a prefill worker —
        # admission installs the rows instead of dispatching a prefill.
        self.kv_sink = None
        self.kv_shipped = 0
        self.ingest: "tuple | None" = None
        # per-request sampling seed (int32; 0 for greedy) and the
        # absolute generated-token index this request resumes from —
        # together they re-key every sample on ABSOLUTE position
        # (fold_in(PRNGKey(seed), pos)), which is what makes a
        # mid-stream continuation sample-exact: token P of a resumed
        # stream consumes the key token P of the original would have
        self.seed = 0
        self.pos_base = 0
        # tenancy: the fair line's scheduling key and DRR quantum (the
        # registry queue weight, snapshotted at admission)
        self.tenant = "default"
        self.tenant_weight = 1


class _Inflight:
    """A dispatched-but-unreaped device tick. ``arrays``: the dispatch's
    output futures (readiness probe); ``reap``: fetch results and
    deliver tokens — must run under the engine's device lock (through
    ``GenerationEngine._reap``, which accounts the loop's time).
    ``kind`` and ``t0`` (what was dispatched, and when) are for the stall
    watchdog, which looks at the pipe from outside the loop's thread."""
    __slots__ = ("arrays", "reap", "kind", "t0")

    def __init__(self, arrays, reap, kind: str, t0: float):
        self.arrays = arrays
        self.reap = reap
        self.kind = kind
        self.t0 = t0


class _LoopAccount:
    """The generation thread's account of its own time and of the device
    stream, written to the serving timeline. Its state belongs to that
    one thread: no other thread calls in, so nothing here locks.

    Phases (``phase``): the thread is always in exactly one of admit /
    dispatch / wait / fetch / deliver / park / other. One call ends a
    phase and begins the next, so phases never nest and leave no time
    out. The phase that ends is written twice by the one helper: as the
    timeline's ``loop`` event on the monotonic clock, and as a profiler
    annotation ``gofr.<phase>``, so that any device profile of the
    process shows the host phases beside the device's operations on the
    profiler's own clock.

    Dry intervals (``dispatch`` / ``probe``): ``last_out`` is an output
    of the last program the thread queued. When a probe finds it ready
    nothing is queued behind it, so the stream is dry: ``idle_from``
    opens, and the next dispatch of ANY program closes the interval
    into the histogram and the timeline's ``gap`` track. ``busy_seen``
    is when the thread last knew the stream busy (a dispatch, or a
    probe that found the output not ready): the stream may have run
    dry that much before ``idle_from``, which the gap event carries as
    its slack."""
    __slots__ = ("tl", "metrics", "ph", "ph_t0", "ph_n", "ph_mark",
                 "idle_from", "idle_slack", "busy_seen", "last_out",
                 "gap_samples", "dry_total")

    MARK = {p: "gofr." + p for p in (
        "admit", "dispatch", "wait", "fetch", "deliver", "park", "other")}

    def __init__(self, timeline, metrics):
        self.tl = timeline  # None with TPU_TIMELINE=0: no event, no mark
        self.metrics = metrics
        self.ph = "other"
        self.ph_t0 = time.monotonic()
        self.ph_n = 0           # the phase's count (requests admitted)
        self.ph_mark: Any = None
        self.idle_from: float | None = None
        self.idle_slack = 0.0
        self.busy_seen = 0.0
        self.last_out: Any = None
        self.gap_samples: "deque[float]" = deque(maxlen=2048)
        self.dry_total = 0.0    # seconds in all dry intervals so far

    def phase(self, name: str) -> str:
        """Move to phase ``name``; returns the phase left, so that a
        region entered from inside another (admission between polls, a
        decode block between chunks) can put it back. Every boundary
        also asks whether the device ran dry."""
        prev = self.ph
        if prev == name:
            return prev
        now = time.monotonic()
        if prev == "fetch":
            # the thread was blocked on the device until now: if the
            # stream is dry, it has only just become so
            self.busy_seen = now
        self.probe(now)
        if self.tl is not None:
            self.tl.loop(self.ph_t0, now, prev, self.ph_n)
            if self.ph_mark is not None:
                self.ph_mark.__exit__(None, None, None)
            # a TraceAnnotation starts when it is made
            self.ph_mark = jax.profiler.TraceAnnotation(self.MARK[name])
        self.ph, self.ph_t0, self.ph_n = name, now, 0
        return prev

    def dispatch(self, now: float, out) -> None:
        """A program was queued (its jitted call returned at ``now``
        with ``out``): the stream is busy from here, and an open dry
        interval ends. An interval that the probe just before the call
        opened was dry for a time the thread did not see: that is a
        ``gap`` event of next to no length whose slack says how long it
        may have been."""
        self.busy_seen = now
        self.last_out = out
        if self.idle_from is None:
            return
        gap, self.idle_from = max(0.0, now - self.idle_from), None
        if gap > 0.0:
            self.gap_samples.append(gap)
            self.dry_total += gap
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_tpu_dispatch_gap_duration", gap, program="generate")
        if self.tl is not None:
            self.tl.dispatch_gap(now - gap, now, self.idle_slack)

    def probe(self, now: float) -> None:
        """Has the stream run dry? It has once the last program queued
        is done."""
        out = self.last_out
        if out is None or self.idle_from is not None:
            return
        try:
            if not isinstance(out, jax.Array):
                # the outputs of one program become ready together
                out = self.last_out = jax.tree_util.tree_leaves(out)[0]
            ready = out.is_ready()
        except Exception:  # donated elsewhere, or no probe: cannot know
            self.last_out = None
            return
        if ready:
            self.idle_from, self.last_out = now, None
            self.idle_slack = now - self.busy_seen
        else:
            self.busy_seen = now

    def reset(self) -> None:
        """After a failed dispatch nothing is known about the stream."""
        self.idle_from = self.last_out = None


class _Slot:
    __slots__ = ("request", "remaining", "generated", "last_token_t")

    def __init__(self):
        self.request: _Request | None = None
        self.remaining = 0
        self.generated = 0
        self.last_token_t = 0.0  # monotonic time of the last delivery

    @property
    def free(self) -> bool:
        return self.request is None


class GenerationEngine:
    def __init__(self, cfg: ModelConfig, params: Any, *, slots: int = 8,
                 max_seq: int | None = None,
                 prompt_buckets: tuple[int, ...] = (32, 64, 128, 256, 512),
                 logger=None, metrics=None, observe=None, seed: int = 0,
                 mesh=None, gate=None,
                 kv_dtype=None, decode_block: int = 4,
                 decode_pipeline: int = 2,
                 admit_window_ms: float = 2.0,
                 prefix_cache_slots: int = 0,
                 prefix_store_min: int | None = None,
                 kvcache=None,
                 spec_decode_k: int = 0,
                 lora_adapters: int = 0, lora_rank: int = 16,
                 paged_blocks: int = 0, paged_block_size: int = 128,
                 prefill_chunk: int | None = None,
                 slo_throughput_share: float = 0.25,
                 slo_latency_slots: int = 1,
                 serving_role: str | None = None,
                 stall_ms: float = 1000.0):
        self.cfg = cfg
        # the model family: its programs and its cache row layout. This
        # is the one place the engine learns it; every call below goes
        # through ``self._fam`` and every cache helper maps over the
        # cache's arrays whatever they are
        self._fam = family(cfg)
        refused = self._fam.unsupported_options(
            mesh=mesh, paged_blocks=paged_blocks, kvcache=kvcache,
            spec_decode_k=spec_decode_k, lora_adapters=lora_adapters,
            kv_dtype=kv_dtype, serving_role=serving_role)
        if refused:
            raise UnsupportedOptions(
                refused, f"the model family of {cfg.name!r}")
        self.params = params
        self.n_slots = slots
        # serializes device-state mutation (the loop thread vs warmup/
        # close). Created BEFORE the first hbm.alloc below: the
        # arbiter's reclaim callbacks registered on those leases take
        # this lock, and another engine's construction may invoke them
        # while ours is still mid-__init__. REENTRANT because the
        # serving loop itself can trigger reclaim (admission check ->
        # budget overshoot -> our own pool shrink) while already
        # holding the lock.
        self._device_lock = threading.RLock()
        # guards the _closed check-then-enqueue in generate() against close()
        self._admission_lock = threading.Lock()
        # Multi-LoRA serving: n adapter slots of rank-r deltas on the
        # attention projections, stacked inside params["layers"] so the
        # layer scan slices them with the base weights; each request
        # picks its adapter (generate(adapter=i)) and every program
        # gathers per-row — multi-tenant fine-tunes over ONE shared
        # weight stream. Adapter 0 is the base no-op (B initialized
        # zero); fill others via load_adapter()/checkpoints.
        self._n_adapters = max(0, int(lora_adapters))
        if self._n_adapters:
            if "lora_a_wq" not in params["layers"]:
                def _build_lora():
                    built = llama.init_lora(cfg, self._n_adapters,
                                            int(lora_rank),
                                            jax.random.PRNGKey(seed + 1))
                    if mesh is not None:
                        # stacks shard like any stacked leaf (layer dim
                        # over pp, rank-r matrices replicated — they're
                        # tiny next to the weight stream); the per-row
                        # adapter gather reads a replicated table with
                        # batch-sharded indices, which GSPMD partitions
                        # cleanly
                        from ..parallel import shardings_for

                        built = jax.device_put(built,
                                               shardings_for(built, mesh))
                    return built

                stacks = hbm.alloc("lora", _build_lora, owner=self,
                                   priority=hbm.PRI_CACHE)
                self.params = {**params, "layers": {
                    **params["layers"], **stacks}}
            else:
                # a checkpoint brought its own stacks: their width is
                # the truth. A silent mismatch would CLAMP the device
                # gather (tenant 4 served tenant 2's fine-tune) and
                # DROP out-of-bounds load_adapter scatters.
                n_stack = int(params["layers"]["lora_a_wq"].shape[1])
                if n_stack != self._n_adapters:
                    raise ValueError(
                        f"params carry {n_stack} LoRA adapter slots but "
                        f"lora_adapters={self._n_adapters}; they must "
                        "match (gather clamping would silently serve "
                        "the wrong tenant)")
        self._slot_adapter = np.zeros((slots,), np.int32)
        # K decode steps fused into one dispatch (lax.scan on device): the
        # host sees K tokens per roundtrip instead of one, amortizing
        # dispatch latency K-fold. Cost: a finished stream wastes at
        # most K-1 slot-steps, and admission waits at most one block.
        self.decode_block = max(1, int(decode_block))
        # Decode dispatch pipeline: how many fused blocks may be in
        # flight on the device stream at once. No setting reaches this
        # argument: serving runs at 2, and depth 1 stays as the
        # reference the token-exactness tests compare against. At depth
        # 2 the loop dispatches block N+1 BEFORE reaping block N — all
        # of N+1's inputs (cache, PRNG key, slot-state carry) are device
        # futures chained from N's outputs, so the dispatch queues with
        # zero host feedback and the host overlaps N's reap/delivery/
        # admission with N+1's compute. The policy collapses to 1 when
        # queueing a second block would cost an SLO (a latency-class
        # waiter that a free slot can take now, chunk lattice deferred,
        # spec decode) — see resilience.DecodePipelinePolicy.
        self._pipeline = DecodePipelinePolicy(decode_pipeline)
        # the dispatched, un-reaped blocks, oldest first. Only the loop
        # thread touches it, under the device lock
        self._pipe: "deque[_Inflight]" = deque()
        self._lattice_deferred = False
        # inside the admission pass a chunk lattice runs between chunks
        self._in_lattice = False
        self._depth_now = 0
        # overlapped reaps (a block still queued at reap) are counted;
        # the stream's dry intervals are the loop account's (_LoopAccount)
        self._reaps = 0
        self._overlapped_reaps = 0
        # an expert layer's decode account (families whose step counts
        # its assignments): assignments made, (step, layer, expert) cells
        # that got one, cells in all
        self._moe_assigned = self._moe_touched = self._moe_cells = 0
        # the sampler's account (_sampling_flag): decode blocks
        # dispatched, those with an active slot that draws, those with
        # one that draws from its top-k
        self._sample_blocks = self._sample_drawn = self._sample_topk = 0
        # bytes of recurrent state a slot holds whatever its length (0:
        # the family keeps rows alone), for app_tpu_state_live_bytes
        said = self._fam.serving_stats(cfg, slots)
        self._state_bytes = said.get("state_bytes_per_slot", 0)
        # rows of a window layer's ring (0: the family keeps whole rows
        # alone) and the bytes one ring row takes over all such layers,
        # for the decode events' ring rows and
        # app_tpu_kv_window_live_bytes
        self._ring_rows = said.get("window_rows", 0)
        self._ring_row_bytes = said.get("window_bytes_per_slot", 0) \
            // max(self._ring_rows, 1)
        self._ring_live = 0
        # a family that runs its stack several times a token says so
        # (0: once, and stats() says nothing): the tokens its decode
        # blocks emitted, each through every pass, for stats()["loop"];
        # and the bytes a cached token takes where a family says them,
        # for app_tpu_kv_live_bytes
        self._loop_steps = said.get("loop_steps", 0)
        self._loop_tokens = 0
        # a family whose decode step is a PASS over a block of W
        # positions a slot says so (0: a step is a token): a prefill
        # yields no token (_first_token), a dispatch is ``decode_block``
        # passes that deliver up to W tokens a commit, the cursor moves
        # by whole blocks at the reap. Its account: slot-passes that
        # denoised and that committed, positions committed, tokens
        # emitted (the device's counters, _decode_reap)
        self._diffusion = said.get("diffusion") or {}
        self._dblock = self._diffusion.get("block_length", 0)
        self._diff_n = dict.fromkeys(
            ("denoise_passes", "commit_passes", "committed", "emitted"), 0)
        self._kv_token_bytes = said.get("kv_bytes_per_token", 0)
        # In-flight admission poll cadence (seconds). While a decode
        # block runs on device, the serving loop waits on the submit
        # event in slices of this length and admits new arrivals
        # immediately (their prefill queues behind the block on the
        # device stream) — see _admit_inflight. Historically this was a
        # post-block GIL-yield sleep ("admit window"); the env knob
        # TPU_ADMIT_WINDOW_MS keeps the name. 0 falls back to 1 ms.
        self._admit_window = max(0.0, float(admit_window_ms)) / 1e3
        self.max_seq = min(max_seq or cfg.max_seq, cfg.max_seq)
        self.prompt_buckets = tuple(sorted(b for b in prompt_buckets
                                           if b <= self.max_seq)) or (self.max_seq,)
        # Chunked-prefill interleave budget (TPU_PREFILL_CHUNK): a
        # prompt longer than the budget is admitted as a SEQUENCE of
        # bounded chunk dispatches, and between chunks the admission
        # loop runs one decode block for the live batch AND an
        # admission pass for new arrivals — a 4k-token prefill can no
        # longer stall every active stream's next token, and a newly
        # arrived short request gets its first dispatch within one
        # chunk budget (docs/advanced-guide/serving-scheduler.md).
        #   None -> budget = largest prompt bucket (interleave on);
        #   <= 0 -> interleave OFF: the lattice's chunks dispatch
        #           back-to-back (the head-of-line A/B arm);
        #   else -> snapped UP to the nearest prompt bucket (chunk
        #           shapes are compile keys — off-lattice sizes would
        #           recompile mid-serving).
        C_max = self.prompt_buckets[-1]
        if prefill_chunk is None:
            self._chunk, self._chunk_interleave = C_max, True
        elif prefill_chunk <= 0:
            self._chunk, self._chunk_interleave = C_max, False
        else:
            self._chunk = pad_bucket(min(int(prefill_chunk), C_max),
                                     self.prompt_buckets)
            self._chunk_interleave = True
        # can a cached position be computed again? Rows can (the lattice
        # overlaps its last chunk, a prefix hit resumes anywhere); a
        # recurrent state cannot: its lattice runs left-aligned chunks,
        # the last one padded, and a prefix hit resumes only where the
        # pool holds the state (_chunk_lattice, _resume_at)
        self._rewind = self._fam.RECOMPUTABLE
        if self._dblock and any(n % self._dblock for n in (
                self.max_seq, *self.prompt_buckets)):
            raise ValueError(
                f"max_seq {self.max_seq} and the prompt buckets "
                f"{self.prompt_buckets} must be whole blocks of "
                f"{self._dblock} for the model family of {cfg.name!r}: a "
                "block cut by a chunk's edge would attend rows that are "
                "not computed yet")
        if not self._rewind and self.max_seq % self._chunk:
            raise ValueError(
                f"max_seq {self.max_seq} must be whole prefill chunks of "
                f"{self._chunk} for the model family of {cfg.name!r}: its "
                "last chunk is padded, not overlapped")
        # a prompt within the chunk budget may run as two dispatches
        # instead of one padded bucket (_admit_prefill): the seconds a
        # prompt program takes, measured at the end of a warm-up, and
        # for each prompt length the bucket its first dispatch runs (0:
        # one bucket). An engine that was not warmed has neither and
        # pads as before. Beside them, what the prompt programs ran,
        # and of the rows a slot reserves those a chunk program's
        # attention fetched: the blocks under its start, in the block
        # the family says its walk has (0: it walks under no cursor)
        self._prefill_costs: dict | None = None
        self._split_first: list[int] | None = None
        self._prefill_n = dict.fromkeys(
            ("admissions", "split", "prompt_tokens", "positions",
             "routed_positions", "chunks", "chunks_on_walk_kernel",
             "cache_rows_walked", "cache_rows_reserved"), 0)
        self._walk_block = self._fam.chunk_block(cfg, self.max_seq)
        # a latent family says whether a chunk program of a size walks
        # its cached rows in the kernel (mla.chunk_walk_latent)
        self._walk_kernel = getattr(self._fam, "chunk_walk_kernel", None)
        # positions from which a prompt program of this family routes
        # its experts (None: it has none, or no such rule)
        self._routed_from = said.get("moe_prompt_dispatch", {}).get(
            "routed_from_tokens")

        # Paged (block-pool) KV cache: slots share a pool of fixed
        # T-token blocks via a host-owned block table instead of owning
        # [max_seq] rows — HBM sized to expected LIVE tokens, so decode
        # batch scales past what contiguous rows fit (the road past
        # batch 96 on 8B/v5e; models/paged_llama.py). On a MESH the
        # pool shards KV-heads over tp (parallel.paged_cache_specs —
        # the block axis stays whole so the host-owned table remains
        # global dispatch data) and attention runs the dense-gather
        # reference instead of the Pallas kernel (a pallas_call is
        # opaque to the GSPMD partitioner) — mesh-aware paged serving
        # is a tensor-parallel configuration, token-exact vs the
        # contiguous mesh path (docs/advanced-guide/
        # multichip-serving.md).
        self._paged = paged_blocks > 0
        if self._paged:
            self._block_t = int(paged_block_size)
            self._mb = -(-self.max_seq // self._block_t)
            min_blocks = 2 + (self.prompt_buckets[-1] // self._block_t)
            if paged_blocks < min_blocks:
                raise ValueError(f"paged_blocks={paged_blocks} too small: "
                                 f"need >= {min_blocks} (trash block + "
                                 "one prompt's worth)")
            from ..models.paged_llama import (BlockAllocator,
                                              SharedPrefixIndex)

            self._alloc = BlockAllocator(paged_blocks)
            self._table = np.zeros((slots, self._mb), np.int32)
            self._slot_blocks: list[list[int]] = [[] for _ in range(slots)]
            self._cursors = np.zeros((slots,), np.int64)  # device cursor
            # the cursor each slot's on-device stop mask freezes at
            # (budget/capacity; 0 = none): the host advances _cursors
            # eagerly at dispatch, and under the depth-2 pipeline a
            # stream can have finished on device one whole un-reaped
            # block ago — without this bound _ensure_blocks would
            # demand pool blocks the stream will never write and could
            # starvation-retire it (or a neighbor) for them
            self._stop_cursors = np.zeros((slots,), np.int64)
            self._paged_evictions = 0
            self._prefix_idx = None
            if prefix_cache_slots > 0:
                # ZERO-COPY prefix cache over the pool itself: entries
                # hold refcounted references to a stored prompt's full
                # blocks (no KV moves to store); a hit refs the shared
                # blocks into the new slot's table and prefill resumes
                # at the match point via the scratch row. Evictable
                # under pool pressure.
                self._prefix_idx = SharedPrefixIndex(prefix_cache_slots,
                                                     self._alloc,
                                                     self._block_t)
                self._store_min = int(prefix_store_min
                                      or self.prompt_buckets[-1])
        else:
            # contiguous engine: the host's view of each slot's KV
            # positions (prompt at admission, + a block's steps at each
            # dispatch), for the decode event's live-token count
            self._cursors = np.zeros((slots,), np.int64)
        self.logger = logger
        self.metrics = metrics
        if metrics is not None:
            # device-byte attribution gauges (app_tpu_device_bytes):
            # the hbm registry pushes every accounting change
            hbm.set_metrics(metrics)
        # resilience.AdmissionGate fronting the pending queue (None =
        # admit everything): sheds with TooManyRequests under overload
        # and caps max_new_tokens in its brownout band; fed with each
        # admission's observed queue wait at _start
        self.gate = gate
        # flight recorder + in-flight registry + stage spans (observe/)
        self._observe = observe
        # serving timeline (observe/timeline.py): hot paths hold None
        # when emission is off (TPU_TIMELINE=0) so the disabled cost is
        # one attribute test, not a method call into a dead ring
        tl = getattr(observe, "timeline", None) if observe is not None \
            else None
        self._tl = tl if (tl is not None and tl.enabled) else None
        if self._tl is not None:
            # device-byte accounting changes land HBM counter samples
            # on the exported Perfetto trace (one track per subsystem)
            hbm.set_timeline(self._tl)
            # and every backend compile leaves a mark on the loop's track
            compile_cache.clock().timeline = self._tl
        # the loop thread's account of its own time and of the stream
        self._acct = _LoopAccount(self._tl, metrics)
        # the account of start-up (observe/startup.py): the container's,
        # as the timeline is; buffers, programs and warm-ups write to it
        self._startup = observe.startup if observe is not None \
            else StartupAccount()
        self.mesh = mesh
        self.down: str | None = None  # set when the device loop is bricked
        self._replacements = 0  # warm mesh re-placements survived
        self._seed = int(seed)  # recovery reseeds the chained key
        self._recoveries = 0
        if mesh is not None:
            tp = mesh.shape.get("tp", 1)
            data = mesh.devices.size // max(tp * mesh.shape.get("sp", 1)
                                            * mesh.shape.get("pp", 1), 1)
            if tp > 1 and cfg.n_kv_heads % tp and data > 1:
                # VERIFIED numerics hazard (tools/multichip_bench.py
                # bring-up, CPU GSPMD): a tp that splits a KV head
                # (n_kv_heads % tp != 0) combined with dp/fsdp > 1
                # produced logits off by O(1) — not reduction noise —
                # while the same tp with data axes = 1, and any
                # head-aligned tp, stayed exact. Until root-caused in
                # the partitioner this config is REFUSED at startup
                # (it served wrong answers silently when it was only a
                # warning); tp alone (data axes = 1) falls back to the
                # jnp reference instead (docs/advanced-guide/
                # multichip-serving.md "known limits").
                from ..errors import ShardingConfigError

                row = ",".join(
                    f"{ax}={n}" for ax, n in
                    zip(mesh.axis_names, mesh.devices.shape) if n > 1)
                raise ShardingConfigError(
                    f"TPU_SHARDING='{row}': tp={tp} splits a KV head "
                    f"(n_kv_heads={cfg.n_kv_heads}) on a multi-axis mesh "
                    f"(data axes product {data}) — a verified "
                    f"wrong-logits configuration. Use a tp that divides "
                    f"n_kv_heads, or drop the data axes (dp/fsdp=1) to "
                    f"serve tp-only on the jnp fallback.",
                    sharding_row=row)
        # The device side (programs.py): what the engine holds in HBM,
        # how it is sharded and which programs run over it. kv_dtype=
        # jnp.int8 halves decode's cache HBM stream (quantize on write,
        # dequant fused into attention) — the default for serving big
        # models; None keeps the model dtype (exact numerics). Buffers
        # are allocated in the order cache, pool, scratch: the arbiter's
        # reclaim order depends on it.
        self._prog = programs.EnginePrograms(
            cfg, self._fam, self, max_seq=self.max_seq, kv_dtype=kv_dtype,
            decode_block=self.decode_block, n_adapters=self._n_adapters,
            spec_k=max(0, int(spec_decode_k)), mesh=mesh,
            paged=(paged_blocks, self._block_t) if self._paged else None,
            startup=self._startup)
        self._prog.describe(
            "cache", slots,
            self._hbm_paged_reclaim if self._paged else None)
        self._key = self._prog.key(self._seed)
        self.cache = self._prog.allocate("cache")
        # what a decode step's attention fetches of a slot at cursor c: c
        # rounded up to this block where the flash-decode kernel takes
        # these shapes, every reserved position (None) on the reference
        # path; the paged pool has its own account
        self._kv_block = None if self._paged else self._fam.decode_kv_block(
            cfg, self.cache, mesh)
        self._slots = [_Slot() for _ in range(slots)]
        self._last_tokens = np.zeros((slots,), np.int32)
        self._active = np.zeros((slots,), bool)
        self._temps = np.zeros((slots,), np.float32)
        self._top_ks = np.zeros((slots,), np.int32)
        # on-device stop-mask state: each slot's remaining token budget
        # (the device carry of _Slot.remaining) and its EOS stop set,
        # EOS_PAD-padded to a fixed width (sets wider than EOS_MAX keep
        # the host check as the only stop — correct, just K-step lazier)
        self._budgets = np.zeros((slots,), np.int32)
        self._eos_mat = np.full((slots, self.EOS_MAX), llama.EOS_PAD,
                                np.int32)
        # durable-streams sampling state: each slot's request seed and
        # the absolute generated-token position of its next sample
        # (pos_base + delivered count) — see _resume_keys
        self._slot_seed = np.zeros((slots,), np.int32)
        self._pos_abs = np.zeros((slots,), np.int32)
        # a block family's first block: the tokens the prompt gives it
        # and how many (the pack's last columns, beside the cursor)
        self._given = np.zeros((slots, self._dblock), np.int32)
        self._given_n = np.zeros((slots,), np.int32)
        # auto-seed counter for sampled requests submitted without an
        # explicit seed: deterministic per engine (same engine seed +
        # same request order -> same streams), and surfaced on the
        # stream so resume tokens can replay it
        self._auto_seed = itertools.count(1)
        # the coalesced dispatch pack: every host-owned per-slot decode
        # input (last token, active, budget, temp, top-k, adapter,
        # host-wins, seed, position, EOS set, block table) rides to the
        # device as ONE
        # [B, W] int32 h2d transfer, rebuilt only when a mirror is
        # dirty — in steady-state decode the dispatch is all-device
        # (cache/key/carry chain from the previous block's outputs)
        self._pack = None
        self._pack_dirty = True
        # device mirrors of host-owned dispatch arrays (see _dev)
        self._mirror: dict[str, Any] = {}
        self._dirty: set[str] = set()
        self._last_dev = None
        self._host_wins = np.ones((slots,), bool)

        # Hierarchical prefix KV cache (tpu/kvcache/): a P-row HBM pool
        # (T0) indexed by a block-hash radix tree, spilling LRU-evicted
        # rows into host DRAM (T1) and sharing int8 blocks through the
        # framework Redis client (T2), behind one CacheManager facade.
        # A hit replaces MXU prefill work for the matched positions
        # with one HBM row copy (T0) or a host->device upload + row
        # copy (T1/T2 promotion); the remainder (always >= 1 token, so
        # the first sample recomputes) prefills from the match point.
        # On mesh engines the pool shards like the serving cache (its
        # row programs are programs.py's *_masked forms) and the
        # OFFLOAD tiers run PER-SHARD: T1 spills read each tp shard's
        # head range straight off its own device shard (ShardedHostKV —
        # no cross-device assembly on the spill path), T2 frames each
        # shard through the unchanged int8 block codec under a
        # fingerprint carrying the mesh shape, and promotion lands the
        # assembled dense row.
        # (Paged engines built their zero-copy SharedPrefixIndex above
        # instead — no side pool, entries reference pool blocks.)
        self._pool = None
        self._kvc = None
        self._host_write_jit = None
        # P/D ingest row-install program (pd/ingest.py): compiled on
        # first shipped-KV admission — decode-role engines pay one
        # compile there instead of every engine paying it at startup
        self._ingest_write_jit = None
        if not self._paged:
            self._prefix_idx = None
            if prefix_cache_slots > 0:
                from .kvcache import (CacheManager, KVCacheOptions,
                                      KVLayout, model_fingerprint)

                opts = kvcache or KVCacheOptions()
                if (mesh is not None and jax.process_count() > 1
                        and (opts.host_mb > 0 or opts.redis is not None)):
                    # Multi-PROCESS meshes: _kv_row_get snapshots only
                    # the process-LOCAL shards (addressable_shards),
                    # so a T1/T2 row would silently hold a fraction of
                    # the KV heads and every restore would degrade to
                    # a shape-drift miss. Keep the T0 radix index;
                    # disable the offload tiers loudly until the
                    # snapshot is process-aware.
                    import dataclasses

                    if logger is not None:
                        logger.warn({
                            "event": "kvcache offload tiers disabled on "
                            "multi-process mesh (per-shard snapshots are "
                            "process-local; T0 radix index stays on)"})
                    if opts.redis is not None:
                        try:  # don't leak the discarded connection
                            opts.redis.close()
                        except Exception:
                            pass
                    opts = dataclasses.replace(opts, host_mb=0, redis=None)

                # Mesh pools settle per-shard lease keys; the pool
                # shards like the serving cache (batch rows over the
                # data axes when they divide, KV heads over tp).
                self._prog.describe("pool", prefix_cache_slots,
                                    self._hbm_pool_reclaim)
                self._pool = self._prog.allocate("pool")
                layout = KVLayout(self._fam.kv_tables(cfg),
                                  *self._fam.kv_layout(cfg),
                                  self._pool.quantized,
                                  np.dtype(str(self._pool[0].dtype)),
                                  self.max_seq)
                # (a part of start-up with a name of its own: the
                # fingerprint reads samples of the weights on the device)
                with self._startup.within("configure", tag="kvcache"):
                    self._kvc = CacheManager(
                        prefix_cache_slots, layout, block=opts.block,
                        host_bytes=opts.host_mb << 20, redis=opts.redis,
                        redis_ttl_s=opts.redis_ttl_s,
                        epoch_refresh_s=opts.epoch_refresh_s,
                        fingerprint=model_fingerprint(
                            cfg, params,
                            extra=str(layout.np_dtype) + self._mesh_extra()),
                        metrics=metrics, logger=logger,
                        shards=self._kv_shards)
                self._store_min = int(prefix_store_min
                                      or self.prompt_buckets[-1])
        if (self._kvc is None and kvcache is not None
                and kvcache.redis is not None):
            # KVCacheOptions promises the engine owns the client; a
            # paged or prefix_cache_slots=0 engine never builds the
            # manager, so honor the contract here instead of leaking
            # the socket for the process lifetime
            if logger is not None:
                logger.warn({"event": "kvcache redis client discarded "
                             "(engine has no prefix cache: paged or "
                             "prefix_cache_slots=0)"})
            try:
                kvcache.redis.close()
            except Exception:
                pass

        # Prompt-lookup speculative decoding (greedy slots only): each
        # tick proposes K draft tokens per slot by matching the trailing
        # n-gram of the slot's history against its own earlier tokens
        # (repetitive text, code, JSON); ONE verify dispatch streams the
        # weights once and emits 1..K+1 tokens per slot. Misses cost a
        # normal decode tick (the engine falls back when no slot drafts,
        # any active slot samples, or a slot is within a window of
        # capacity). Drafting is host-side numpy either way; on mesh
        # engines the verify dispatch shards exactly like the decode
        # step (batch over data axes, KV heads over tp).
        self._spec_k = max(0, int(spec_decode_k))
        if self._spec_k:
            self._spec_windows = 0
            self._spec_emitted = 0
            # per-slot token history as preallocated buffers: _draft
            # slices VIEWS (no list boxing on the decode loop's
            # GIL-held critical path); append is one index write
            self._hist_buf = np.zeros((slots, self.max_seq), np.int32)
            self._hist_n = np.zeros((slots,), np.int64)

        self._pending = _ClassPending(slo_throughput_share)
        # Latency slot reservation: throughput-class admissions may
        # never take the LAST ``slo_latency_slots`` free slots, so a
        # latency arrival under batch-driven saturation finds a slot
        # at its uncontended wait instead of queueing behind admitted
        # batch streams (the gate bounds the LINE; this bounds the
        # SLOTS). Clamped so throughput can always run somewhere; costs
        # nothing when traffic is untagged (all-latency).
        self._lat_reserve = max(0, min(int(slo_latency_slots), slots - 1))
        self._work = threading.Event()
        self._closed = False
        self._draining = False
        # requests popped off _pending but not yet visible in _active —
        # the admission window (prefill can compile for seconds on a
        # first-shape request); drain() must count them as in-flight
        self._admitting = 0
        self.total_tokens = 0
        self.total_requests = 0
        # tenancy plane (gofr_tpu/tenancy/): installed post-construction
        # by install_tenancy(); None means every request is the
        # anonymous default tenant and nothing tenant-shaped runs
        self.tenancy = None
        self._tenant_leased: set[str] = set()   # live tenant:{id} leases
        self._gauge_tenants: set[str] = set()   # tenants ever gauged

        self._build_jits()
        if self._paged and (self.max_seq - 1 > self._chunk
                            or self._prefix_idx is not None):
            # Long-prompt admission AND prefix-hit resume both run the
            # chunk lattice against a dense single-slot SCRATCH row
            # (identical programs to the contiguous engine's, B=1),
            # then one dispatch lands the row in the pool
            # (paged_llama.write_row_to_blocks). The scratch costs one
            # slot-row of HBM (~67 MB at 8B/1024).
            self._ensure_scratch()
        self._thread = threading.Thread(target=self._loop, name="gofr-tpu-gen",
                                        daemon=True)
        self._thread.start()
        # the one reader of the loop's account besides the loop: what
        # the queue, the threads and the machine did while a phase
        # lasted (observe/stall.py); with the timeline, or not at all
        self.stall_watch = None
        if self._tl is not None:
            self.stall_watch = StallWatch(
                self._acct, self._pipe, self._thread, self._tl,
                metrics=metrics, logger=logger,
                threshold_s=stall_ms / 1e3)
            self.stall_watch.start()

    def install_tenancy(self, plane) -> None:
        """Attach the multi-tenant serving plane (tenancy.TenantPlane).
        From here on generate() resolves the ambient tenant against the
        registry: quota admission, weighted fair queueing, per-tenant
        cache budgets, and tenant-labeled telemetry all switch on."""
        self.tenancy = plane
        if plane is not None and self._kvc is not None:
            row_bytes = 0
            if self._pool is not None and self._kvc.slots > 0:
                row_bytes = hbm.tree_nbytes(self._pool) // self._kvc.slots
            self._kvc.set_tenancy(plane.cache_shares, row_bytes=row_bytes)

    def _build_jits(self, needs=None) -> None:
        """Bind every compiled program the table (programs.TABLE) gives
        this engine, or only those that need one of ``needs``."""
        kvc = self._kvc
        offload = kvc is not None and (kvc.wants_offload or kvc.shares)
        for attr, prog in self._prog.build(needs, offload=offload).items():
            setattr(self, attr, prog)
        if needs is None:
            # other programs: what was measured of the old ones is void
            self._prefill_costs = self._split_first = None

    @property
    def _kv_shards(self) -> int:
        return self._prog.placed.kv_shards  # tp shards of the KV heads

    def _mesh_extra(self) -> str:
        """Fingerprint suffix carrying the KV shard layout: the T2
        tier frames blocks PER SHARD, so replicas sharded differently
        must occupy disjoint namespaces — a tp=4 frame must never
        half-decode on a tp=2 reader."""
        return f":tp{self._kv_shards}" if self._kv_shards > 1 else ""

    @staticmethod
    def _device_alive(dev) -> bool:
        """Can this device still take work? A tiny placed transfer is
        the probe — a lost mesh device fails it, a healthy one costs
        microseconds (recovery path only, never per token)."""
        try:
            jax.block_until_ready(
                jax.device_put(jnp.zeros((1,), jnp.int32), dev))
            return True
        except Exception:
            return False

    def _replace_mesh(self) -> None:
        """Warm device-loss re-placement: after a mesh engine's loop
        failure, rebuild the mesh over the devices still alive (the
        same shape when all answer — the chaos-simulated case and a
        hot-spare rejoin — or a shrunk plan, dp-first/tp-last, when
        chips are gone), re-place params, recompute every sharding
        from the buffers' descriptions, and rebuild the compiled
        surface. The recovery code that runs next re-settles the same
        hbm lease keys per shard (account's group SET semantics — no
        double count even across a shape change) and rewarms T0 from
        the T1/T2 tiers exactly like single-device recovery, so
        serving resumes token-exact instead of the process dying with
        the device. Runs under the device lock on the loop thread.

        LIMIT: the params re-place below reads the OLD placement. A
        device that answers the probe again (transient loss, the
        chaos-simulated case) or whose param shards are replicated
        elsewhere recovers warm; a chip that is physically gone while
        holding the only copy of a tp param shard makes that
        device_put raise, and the outer recovery marks the engine
        down — restart-and-reload is the path for that case until
        params can re-place from a host/checkpoint copy
        (docs/advanced-guide/multichip-serving.md, known limits)."""
        from ..parallel import remesh, shardings_for

        live = [d for d in self.mesh.devices.flat if self._device_alive(d)]
        lost = self.mesh.devices.size - len(live)
        new_mesh = remesh(self.mesh, live)
        self.mesh = new_mesh
        old_shards = self._kv_shards
        # shardings recompute from the buffers' descriptions, so the
        # reallocations that follow land placed on the new mesh
        self._prog.place(new_mesh)
        # params re-place (a no-op data move when the mesh is
        # unchanged); the LoRA stacks ride along and re-settle their
        # lease via account's SET semantics right below. (GL202
        # suppressed: params are placed and owned by the config
        # wiring, not the engine — the engine accounts only the
        # subtree it allocated, exactly like construction does.)
        self.params = jax.device_put(  # noqa: GL202 — see note above
            self.params, shardings_for(self.params, new_mesh))
        if self._n_adapters:
            stacks = {k: v for k, v in self.params["layers"].items()
                      if k.startswith("lora_")}
            if stacks:
                hbm.account("lora", stacks, owner=self)
        if self._kvc is not None and self._kv_shards != old_shards:
            # the shard layout changed (degraded tp): T1 survives
            # (payloads assemble dense at promotion), T2 re-namespaces
            from .kvcache import model_fingerprint

            self._kvc.rekey(
                model_fingerprint(self.cfg, self.params,
                                  extra=str(self._kvc.layout.np_dtype)
                                  + self._mesh_extra()),
                self._kv_shards)
        self._build_jits()
        self._replacements += 1
        if self.logger is not None:
            self.logger.warn({
                "event": "mesh re-placed after device failure",
                "lost_devices": lost,
                "devices": int(new_mesh.devices.size),
                "axes": {k: int(v) for k, v in
                         zip(new_mesh.axis_names, new_mesh.devices.shape)
                         if v > 1}})

    # the widths and the dispatch-pack layout the host side shares with
    # the traced functions (programs.py)
    TOP_K_MAX = programs.TOP_K_MAX
    EOS_MAX = programs.EOS_MAX
    _PACK_EXTRA = programs.PACK_EXTRA
    # the attribute that holds each described buffer
    _BUFFER_ATTR = {"cache": "cache", "pool": "_pool", "scratch": "_scratch"}

    def _hist_set(self, idx: int, tokens) -> None:
        n = min(len(tokens), self._hist_buf.shape[1])
        self._hist_buf[idx, :n] = tokens[:n]
        self._hist_n[idx] = n

    def _hist_append(self, idx: int, token: int) -> None:
        n = self._hist_n[idx]
        if n < self._hist_buf.shape[1]:
            self._hist_buf[idx, n] = token
            self._hist_n[idx] = n + 1

    def _draft(self, idx: int) -> list[int] | None:
        """Prompt-lookup draft: the K tokens that followed the most
        recent earlier occurrence of the history's trailing 2-gram.
        None = no match (this slot proposes nothing). Pure numpy over
        buffer views — no per-tick list boxing on the decode loop's
        GIL-held critical path."""
        n = int(self._hist_n[idx])
        K = self._spec_k
        if n < 3:
            return None
        h = self._hist_buf[idx, :n]  # view, no copy
        a, b = h[-2], h[-1]
        # positions j <= n-3 with h[j] == a and h[j+1] == b
        hits = np.flatnonzero((h[:-2] == a) & (h[1:-1] == b))
        if len(hits) == 0:
            return None
        j = int(hits[-1])  # most recent earlier occurrence
        cont = h[j + 2:j + 2 + K]
        if cont.size == 0:
            return None
        return cont.tolist() + [0] * (K - cont.size)

    # -- public API ----------------------------------------------------------
    def generate(self, prompt, max_new_tokens: int = 128,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id=None, adapter: int = 0,
                 logprobs: bool = False, deadline=None,
                 slo_class: str | None = None,
                 kv_sink=None, ingest=None,
                 traceparent: str | None = None,
                 seed: int | None = None,
                 continue_from=None) -> GenStream:
        """Enqueue a prompt (sequence of token ids); returns a GenStream
        yielding generated ids as the device produces them.

        ``temperature=0`` (default) is greedy. ``top_k > 0`` truncates
        sampling to the k most likely tokens; k is CAPPED at
        TOP_K_MAX (64) — the compiled step extracts a fixed top set
        once and masks within it, so larger requested k silently
        saturates to 64 rather than widening the distribution.

        ``eos_id``: a single stop token id, or any iterable of them
        (OpenAI-style ``stop`` sets) — the stream ends at (and includes)
        the first generated token in the set. Checked host-side per
        delivered token; never a compile key.

        ``deadline`` (resilience.Deadline) defaults to the ambient one
        the transport opened from the wire deadline; an expired request
        raises here, and one that expires while queued is dropped at
        admission without a prefill dispatch. With an admission gate
        configured, overload sheds with ``TooManyRequests`` (fast 429/
        RESOURCE_EXHAUSTED) and the brownout band caps
        ``max_new_tokens``.

        ``slo_class`` (resilience.SLO_LATENCY/SLO_THROUGHPUT) defaults
        to the transport's ambient class (``X-SLO-Class`` header /
        ``slo-class`` gRPC metadata): latency-class requests pick up
        slots first; throughput-class tolerates longer queueing, is
        shed/browned-out FIRST under pressure, and still drains via the
        pending line's weighted anti-starvation pickup.

        Disaggregated serving (gofr_tpu/pd/, docs/advanced-guide/
        disaggregated-serving.md): ``kv_sink`` runs the request
        PREFILL-ONLY — the stream delivers exactly the sampled first
        token while the slot's KV ships out through the sink
        ``(HostKV, start, total)`` per prefill chunk (single-device
        contiguous engines only). ``ingest=(HostKV, first_token,
        first_lp)`` is the decode-side mirror: admission installs the
        shipped rows under an ``hbm`` stage lease instead of running a
        prefill, then decodes normally. ``traceparent`` overrides the
        ambient trace context — the cross-process propagation seam, so
        both pools' spans join ONE distributed trace and the tail
        sampler's deterministic trace-id verdict keeps or drops the
        whole handoff together.

        Durable streams (docs/advanced-guide/resilience.md): ``seed``
        fixes the request's sampling PRNG; every sample is keyed on
        ``fold_in(PRNGKey(seed), absolute_position)``, so the stream is
        replayable token-exact from any position. Sampled requests
        without a seed get a deterministic per-engine one (surfaced as
        ``stream.seed`` for resume tokens). ``continue_from=(prompt,
        emitted)`` admits a CONTINUATION of an interrupted stream: the
        prompt + already-emitted tokens prefill as one prompt (the
        emitted tokens extend the same block-chain hashes the radix
        index and T2 keys use, so a warm resume prefills only the
        un-cached tail), ``max_new_tokens`` still counts from the
        ORIGINAL request (the continuation yields at most
        ``max_new_tokens - len(emitted)`` more), and sampling resumes
        at absolute position ``len(emitted)`` — greedy continuations
        are bit-exact by construction, seeded-sampled ones by the
        position re-keying."""
        if self._closed:
            raise GenerationError("generation engine is closed")
        if self._draining:
            raise GenerationError("generation engine is draining")
        if self.down is not None:
            raise GenerationError(f"generation engine is down: {self.down}")
        pos_base = 0
        if continue_from is not None:
            base, emitted = continue_from
            base = np.asarray(base, np.int32).reshape(-1)
            emitted = np.asarray(emitted, np.int32).reshape(-1)
            # the continuation's prefill IS prompt + emitted: one
            # prompt whose block-chain hashes extend the original's, so
            # the radix index / T1 / T2 tiers cover everything a warm
            # replica already computed and only the tail re-prefills
            prompt = np.concatenate([base, emitted])
            pos_base = int(emitted.size)
            max_new_tokens = int(max_new_tokens) - pos_base
            if max_new_tokens <= 0:
                raise GenerationError(
                    f"continue_from carries {pos_base} emitted tokens "
                    "but the request budget allows no more — nothing "
                    "to resume")
        if kv_sink is not None and ingest is not None:
            raise GenerationError("kv_sink and ingest are exclusive "
                                  "(a request is prefill-only OR "
                                  "decode-only, never both)")
        if kv_sink is not None and (self._paged or self.mesh is not None):
            raise GenerationError("kv_sink (prefill-only serving) "
                                  "requires a single-device contiguous "
                                  "engine")
        if ingest is not None:
            self._validate_ingest(ingest, np.asarray(prompt,
                                                     np.int32).reshape(-1))
        if deadline is None:
            deadline = current_deadline()
        if slo_class is None:
            slo_class = current_slo_class()
        elif slo_class not in (SLO_LATENCY, SLO_THROUGHPUT):
            raise GenerationError(f"unknown slo_class {slo_class!r}")
        tenant_spec = None
        tenant = None
        if self.tenancy is not None:
            # resolve the ambient tenant (stamped by the transport's
            # tenant_scope) against the registry: canonical id, class
            # default for untagged traffic, registry-routed LoRA
            tenant_spec = self.tenancy.resolve(current_tenant())
            tenant = tenant_spec.tenant_id
            slo_class = self.tenancy.effective_class(tenant_spec, slo_class)
            adapter = self.tenancy.effective_adapter(tenant_spec,
                                                     int(adapter))
        if deadline is not None and deadline.expired():
            self._count_expired(where="post-handoff" if ingest is not None
                                else "pre-queue")
            raise DeadlineExceeded("deadline expired before generate() "
                                   "was queued")
        if tenant_spec is not None:
            try:
                # per-tenant quota FIRST: an over-quota tenant sheds on
                # its own 429 (reason=tenant_quota) without consuming
                # the shared gate's judgment of global pressure
                self.tenancy.admit(tenant_spec, program="generate",
                                   slo_class=slo_class, gate=self.gate)
            except BaseException:
                self._wide_shed(slo_class, tenant=tenant)
                raise
        try:
            # from here to enqueue, the tenant holds a live concurrency
            # slot: EVERY early raise must give it back (the stream's
            # terminal releases it otherwise)
            if self.gate is not None:
                try:
                    self.gate.admit(self._pending.qsize(),
                                    program="generate",
                                    slo_class=slo_class,
                                    tenant=tenant or "")
                except BaseException:
                    # shed: the request dies HERE, before a stream
                    # exists — its canonical wide event and timeline
                    # marker are the only record that it ever arrived
                    self._wide_shed(slo_class, tenant=tenant)
                    raise
                max_new_tokens = self.gate.cap_tokens(max_new_tokens,
                                                      slo_class=slo_class)
            if eos_id is not None and not isinstance(eos_id,
                                                     (int, np.integer)):
                eos_id = frozenset(int(t) for t in eos_id) or None
            elif isinstance(eos_id, np.integer):
                eos_id = int(eos_id)
            if adapter and not 0 <= adapter < max(self._n_adapters, 1):
                raise GenerationError(
                    f"adapter {adapter} out of range (engine has "
                    f"{self._n_adapters} LoRA adapter slots)")
            if seed is not None:
                seed = int(seed) & 0x7FFFFFFF
            elif temperature > 0:
                # deterministic per-engine auto-seed: same engine seed +
                # same submission order -> same streams, and the value
                # is surfaced on the stream so a resume token can
                # replay it
                seed = (self._seed * 1000003 + next(self._auto_seed)) \
                    & 0x7FFFFFFF
        except BaseException:
            if tenant_spec is not None:
                self.tenancy.release(tenant)
            raise
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        stream = GenStream(next(_REQ_IDS), self, logprobs=logprobs)
        stream.trace["submit"] = time.monotonic()
        stream.trace["request_id"] = stream.request_id  # for the transport
        stream.prompt_len = len(prompt)
        stream.slo_class = slo_class
        stream.cursor_base = pos_base
        stream.seed = seed
        stream.tenant = tenant or "default"
        stream._tenant_held = tenant_spec is not None
        if len(prompt) == 0:
            stream._q.put(GenerationError("empty prompt"))
            stream._q.put(None)
            self._release_tenant(stream)
            return stream
        # Prompts longer than the largest bucket run through chunked
        # prefill at admission (see _start; paged engines chunk into a
        # dense scratch row, then land it in the pool); the only hard
        # limit is cache capacity minus one position for the first
        # generated token.
        # (a block family delivers no token from its prefill: its first
        # one has to lie under the capacity stop too)
        limit = self.max_seq - 1 - bool(self._dblock)
        if len(prompt) > limit:
            stream._q.put(GenerationError(
                f"prompt length {len(prompt)} exceeds serving limit {limit}"))
            stream._q.put(None)
            self._release_tenant(stream)
            return stream
        if self._paged:
            # fail-fast when the POOL can never hold this prompt — a
            # transient shortage requeues at admission, but a structural
            # one would requeue forever (livelock, caller blocked)
            need = -(-len(prompt) // self._block_t)
            usable = self._alloc.n_blocks - 1
            if need > usable:
                stream._q.put(GenerationError(
                    f"prompt needs {need} pool blocks but the pool has "
                    f"{usable} (raise TPU_PAGED_BLOCKS or "
                    "TPU_PAGED_BLOCK)"))
                stream._q.put(None)
                self._release_tenant(stream)
                return stream
        if traceparent:
            # explicit cross-process context (the P/D ingest path): the
            # shipped request's spans must join the PREFILL worker's
            # trace, not a fresh local one — that one shared trace id
            # is also what makes both processes' tail samplers agree
            from .. import tracing

            ids = tracing.parse_traceparent(traceparent)
            if ids is not None:
                stream.traceparent = traceparent
                stream.trace_id = ids[0]
        if self._observe is not None:
            from .. import tracing

            if not stream.trace_id:
                span = tracing.current_span()
                if span is not None:  # inherit the submitter's context
                    stream.traceparent = span.traceparent()
                    stream.trace_id = span.trace_id
                else:  # mint a trace id so the stage spans still
                    # correlate; no traceparent — they export as roots
                    # of that trace rather than children of a span
                    # nobody ever emits
                    stream.trace_id = tracing._new_trace_id()
            # detail.request_id is the FLIGHT-RECORDER key: registry
            # entry ids and stream request ids are separate counters, so
            # /debug/requests must surface the one /debug/events filters
            # by, or cross-referencing the two pages silently lies
            stream.obs_entry = self._observe.requests.add(
                "generate", "generate", stream.trace_id, stage="queued",
                detail={"request_id": stream.request_id,
                        "prompt_len": len(prompt),
                        "max_new": max_new_tokens,
                        "slo_class": slo_class})
            self._observe.recorder.record(
                "submitted", request_id=stream.request_id,
                trace_id=stream.trace_id, prompt_len=len(prompt),
                max_new=max_new_tokens)
        try:
            with self._admission_lock:
                if self._closed:
                    raise GenerationError("generation engine is closed")
                if self._draining:
                    # drain() sets the flag under this lock; without this
                    # re-check a racing generate() could slip a request in
                    # after the drain snapshot and silently extend the window
                    raise GenerationError("generation engine is draining")
                req = _Request(stream, prompt, max_new_tokens,
                               temperature, top_k, eos_id,
                               adapter=int(adapter), deadline=deadline,
                               slo_class=slo_class, block=self._dblock)
                req.kv_sink = kv_sink
                req.ingest = ingest
                req.seed = 0 if seed is None else seed
                req.pos_base = pos_base
                if tenant_spec is not None:
                    req.tenant = tenant
                    req.tenant_weight = tenant_spec.weight
                self._pending.put(req)
        except BaseException:
            self._obs_end(stream, "failed", error="rejected at admission")
            raise
        self._obs_gauges()
        self._work.set()
        return stream

    def stats(self) -> dict:
        if self.down is not None:
            return {"down": self.down, "slots": self.n_slots}
        out = {
            "slots": self.n_slots,
            "active": int(self._active.sum()),
            "queued": self._pending.qsize(),
            "draining": self._draining,
            "max_seq": self.max_seq,
            # cache positions a flash-decode work item covers, None on
            # the reference path (the family's decode_kv_block)
            "decode_kv_block": self._kv_block,
            "prompt_buckets": list(self.prompt_buckets),
            "total_requests": self.total_requests,
            "total_tokens": self.total_tokens,
            "scheduler": {
                "prefill_chunk": self._chunk,
                "chunk_interleave": self._chunk_interleave,
                "latency_reserved_slots": self._lat_reserve,
                "queued_latency": self._pending.qsize_class(SLO_LATENCY),
                "queued_throughput":
                    self._pending.qsize_class(SLO_THROUGHPUT),
                "pipeline": self._pipeline_stats(),
                "prefill": self._prefill_stats(),
                # phases that outlasted TPU_STALL_MS (observe/stall.py)
                **({} if self.stall_watch is None
                   else {"stalls": self.stall_watch.stats()}),
            },
            **self._fam.serving_stats(self.cfg, self.n_slots),
            # which of the sampler's branches the decode blocks asked
            # for (_sampling_flag)
            "sampling": {"blocks": self._sample_blocks,
                         "drawn_blocks": self._sample_drawn,
                         "topk_blocks": self._sample_topk},
            # phases, warm-up records, cache misses (observe/startup.py)
            "startup": self._startup.stats(),
        }
        if self._dblock:
            # the slot-passes the decode dispatches ran, by what a slot
            # did in them, and what they committed and delivered
            n = self._diff_n
            ran = n["denoise_passes"] + n["commit_passes"]
            out["diffusion"] = {
                **self._diffusion, **n, "passes": ran,
                "rows_written": n["commit_passes"] * self._dblock,
                "tokens_per_pass": round(n["emitted"] / ran, 4)
                if ran else None}
        if self._loop_steps:
            # tokens the decode blocks emitted and the passes over the
            # stack they took (every token runs every pass: ModelConfig
            # refuses an exit threshold under 1)
            out["loop"] = {"tokens": self._loop_tokens,
                           "passes": self._loop_tokens * self._loop_steps}
        if self._ring_rows:
            # rows the active slots hold: of the full layers (a layer),
            # and of a window layer's rings at the last decode block
            out["kv_live_rows"] = {
                "full": int(self._cursors[self._active].sum()),
                "window": self._ring_live}
        if self.tenancy is not None:
            out["scheduler"]["queued_by_tenant"] = \
                self._pending.qsize_by_tenant()
            out["tenancy"] = self.tenancy.stats()
        if self.mesh is not None:
            out["mesh"] = {
                "devices": int(self.mesh.devices.size),
                "axes": {k: int(v) for k, v in
                         zip(self.mesh.axis_names, self.mesh.devices.shape)
                         if v > 1},
                "kv_shards": self._kv_shards,
                "replacements": self._replacements,
            }
        if self.gate is not None:
            out["admission"] = self.gate.stats()
        if self._kvc is not None:
            out["prefix_cache"] = self._kvc.stats()
        elif self._prefix_idx is not None:
            out["prefix_cache"] = self._prefix_idx.stats()
        if self._paged:
            n_usable = self._alloc.n_blocks - 1
            out["paged"] = {
                "block_size": self._block_t,
                "blocks": n_usable,
                "free": self._alloc.free_blocks,
                "utilization": round(1 - self._alloc.free_blocks
                                     / max(1, n_usable), 3),
                "evictions": self._paged_evictions,
            }
        if self._moe_cells:
            out["moe"] = {
                "expert_tokens": self._moe_assigned,
                "tokens_per_expert": round(
                    self._moe_assigned / self._moe_cells, 4),
                "experts_idle_ratio": round(
                    1.0 - self._moe_touched / self._moe_cells, 4)}
        if self._n_adapters:
            out["lora"] = {"adapters": self._n_adapters,
                           "rank": int(self.params["layers"]
                                       ["lora_a_wq"].shape[-1])}
        if self._spec_k:
            out["spec_decode"] = {
                "k": self._spec_k,
                "windows": self._spec_windows,
                "emitted": self._spec_emitted,
                "tokens_per_window": (
                    round(self._spec_emitted / self._spec_windows, 3)
                    if self._spec_windows else None),
            }
        return out

    def _prefill_stats(self) -> dict:
        """What the prompt programs ran and how a prompt is cut to them:
        the measured table (ms, both timings of each program; None on an
        engine that was not warmed), the plan made from it (for each
        bucket, the lengths that leave it for two dispatches and into
        what: prefill_plan.ranges), the admissions that ran a prompt
        program and those of them that split, the share of the
        positions run that held no prompt token, the share that ran in a
        program whose experts route (_count_program; None where the
        family has no such rule), the chunk dispatches and those of
        them whose walk over cached latent rows ran in the kernel, and
        of the rows the chunk programs' slots reserve the share their
        attention walked (_count_chunk)."""
        n = dict(self._prefill_n)
        costs = self._prefill_costs
        return {
            "costs_ms": costs and {
                prog: {b: [round(t * 1e3, 3) for t in ts]
                       for b, ts in table.items()}
                for prog, table in costs.items()},
            "plan": prefill_plan.ranges(self._split_first or (),
                                        self.prompt_buckets),
            **n,
            "padded_pct": (round(100 * (1 - n["prompt_tokens"]
                                        / n["positions"]), 2)
                           if n["positions"] else None),
            "routed_pct": (round(100 * n["routed_positions"]
                                 / n["positions"], 2)
                           if n["positions"] and self._routed_from else None),
            "walked_pct": (round(100 * n["cache_rows_walked"]
                                 / n["cache_rows_reserved"], 2)
                           if n["cache_rows_reserved"] else None),
        }

    def _pipeline_stats(self) -> dict:
        """Decode-pipeline observability (also the deterministic probe
        the depth tests poll): the configured ceiling, the depth the
        NEXT top-up would target (computed from the same facts the loop
        reads), the depth currently in flight, and the measured
        inter-block host-gap distribution — overlapped reaps are the
        blocks whose successor was already queued on-device."""
        # lock-free snapshot: the serving loop appends concurrently and
        # CPython raises if an append lands mid-iteration — retry a few
        # times rather than taking the device lock on a stats poll
        samples: list = []
        for _ in range(4):
            try:
                samples = list(self._acct.gap_samples)
                break
            except RuntimeError:
                continue
        return {
            "depth": self._pipeline.depth,
            "target_depth": self._target_depth(),
            "latency_admittable": self._latency_admittable(),
            "depth_now": self._depth_now,
            "reaps": self._reaps,
            "overlapped_reaps": self._overlapped_reaps,
            "gap_p50_ms": (round(float(np.median(samples)) * 1e3, 4)
                           if samples else None),
            "gap_samples": len(samples),
            # what pipelining is for: the stream's dry time a reap
            "dry_ms_per_reap": (
                round(self._acct.dry_total / self._reaps * 1e3, 4)
                if self._reaps else None),
        }

    def warmup(self) -> None:
        """Prime every compiled shape (prefill per bucket + the step).

        Safe while serving: the device lock excludes the loop thread for
        the duration (both jits donate the cache buffer); dummy prefills
        go into a FREE slot only (they overwrite that slot's KV), and the
        cursor snapshot restores the lengths afterwards. With every slot
        busy the prefill warmup is skipped — an all-busy engine has those
        shapes compiled already or will compile them on admission.

        Each call is a record of the start-up account (its seconds, its
        compiles, the cache's hits and misses, the memory peak after it),
        under a ``warmup`` phase of its own when called again later."""
        with self._device_lock:
            free = next((i for i, s in enumerate(self._slots) if s.free), None)
            with self._startup.warming() as acct:
                plan = self._warm_plan(free)
                acct.expect(len(plan))
                cursors = np.asarray(jax.device_get(self.cache.lengths))
                for attr, shape, run in plan:
                    with acct.call(attr, shape):
                        run()
                if free is not None and not self._paged \
                        and self._prefill_costs is None:
                    self._time_prefills(free, plan)
                # restore cursors dirtied by the dummy dispatches
                self.cache = self.cache._replace(
                    lengths=jnp.asarray(cursors))

    def _time_prefills(self, free: int, plan: list[tuple]) -> None:
        """Measure the seconds each prompt program the warm-up just
        compiled takes, twice, and make the split plan from them
        (prefill_plan.first_buckets; _admit_prefill follows it). The
        plan's own calls pass zeros and a length of 1: every position
        of a routed model would go to the same experts, and a kernel
        may skip what lies past the length. These pass varied tokens at
        the full length, the final chunk behind as many rows as it
        holds, into the free slot the plan wrote. A final chunk's
        attention walks the blocks of cached rows under its start
        (ops.attention.chunk_attention), so its cost depends on where
        it runs: a split's rest runs behind its first bucket, at most
        half the chunk budget, and the chunk timed for it behind its
        own bucket; both walk ONE block of the family's 256 rows or
        more (chunk_block), so the table prices what a split runs."""
        i32 = jnp.int32
        names = {"_prefill_jit": "prefill", "_chunk_final_jit": "chunk_final"}
        costs: dict[str, dict[int, list[float]]] = {
            name: {} for name in names.values()}
        rng = np.random.default_rng(0)
        for attr, shape, _ in plan:
            if attr not in names:
                continue
            b = shape[1]
            start = max(0, min(b, self.max_seq - b))
            tokens = jnp.asarray(
                rng.integers(1, self.cfg.vocab_size, (1, b)), i32)
            tail = (jnp.float32(0.0), i32(0), self._key, i32(0), i32(0),
                    self._adapter1(None))
            where = ((i32(b), i32(free)) if attr == "_prefill_jit" else
                     (i32(start), i32(free), i32(start + b), i32(b - 1)))
            for _ in range(2):
                t0 = time.perf_counter()
                # the sync is the measurement: a call's seconds, in set-up
                *_, self._key, self.cache = jax.block_until_ready(  # noqa: GL101
                    getattr(self, attr)(self.cache, self.params, tokens,
                                        *where, *tail))
                costs[names[attr]].setdefault(b, []).append(
                    time.perf_counter() - t0)
        self._prefill_costs = costs
        self._split_first = prefill_plan.first_buckets(
            self.prompt_buckets, self._chunk, costs["prefill"],
            costs["chunk_final"], overlapped=self._rewind,
            max_seq=self.max_seq)

    def _warm_plan(self, free: int | None) -> list[tuple]:
        """What a warm-up calls, in order: (the engine attribute of the
        ``programs.TABLE`` row, the shape that keys the compile, the
        call, which blocks until its result is ready). Each call rebinds
        the buffer its program donates."""
        plan: list[tuple] = []
        i32, zero = jnp.int32, jnp.int32(0)

        def tail():
            # (temp, top_k, key, seed, pos, adapter): the key as the last
            # call left it, as serving passes it
            return (jnp.float32(0.0), zero, self._key, zero, zero,
                    self._adapter1(None))

        if free is not None:
            # chunk programs run for prompts past the chunk budget (the
            # largest bucket unless TPU_PREFILL_CHUNK bounds it) — and,
            # with a prefix pool, for ANY hit (prefill resumes mid-prompt
            # through the chunk lattice), so they must be warm whenever
            # the pool exists. They write the serving cache's free slot,
            # or a paged engine's scratch row
            C = self._chunk
            if self._paged:
                row, slot = "_scratch", zero
                chunked = hasattr(self, "_scratch")
            else:
                row, slot = "cache", i32(free)
                chunked = self.max_seq - 1 > C or self._kvc is not None

            def prefill(b):
                # paged: dummy KV lands in the trash block (blocks all
                # 0); the cursor restore undoes lengths
                blocks = (jnp.zeros((-(-b // self._block_t),), i32),) \
                    if self._paged else ()
                _, _, self._key, self.cache = jax.block_until_ready(
                    self._prefill_jit(
                        self.cache, self.params, jnp.zeros((1, b), i32),
                        i32(1), *blocks, i32(free), *tail()))

            def chunk_final(b):
                _, _, self._key, buf = jax.block_until_ready(
                    self._chunk_final_jit(
                        getattr(self, row), self.params,
                        jnp.zeros((1, b), i32), zero, slot, i32(1), zero,
                        *tail()))
                setattr(self, row, buf)

            def chunk_mid():
                setattr(self, row, jax.block_until_ready(
                    self._chunk_mid_jit(
                        getattr(self, row), self.params,
                        jnp.zeros((1, C), i32), zero, slot, zero, zero,
                        *tail())))

            def row_to_blocks():
                self.cache = jax.block_until_ready(self._row_to_blocks_jit(
                    self.cache, self._scratch, jnp.zeros((self._mb,), i32)))

            def blocks_to_row():
                # prefix-hit restore program (trash-block gather)
                self._scratch = jax.block_until_ready(
                    self._blocks_to_row_jit(
                        self._scratch, self.cache,
                        jnp.zeros((self._mb,), i32)))

            for b in self.prompt_buckets:
                if b > C:
                    # single-dispatch prefills and final chunks are both
                    # bounded by the chunk budget — wider buckets never
                    # dispatch
                    continue
                plan.append(("_prefill_jit", (1, b), functools.partial(prefill, b)))
                if chunked:
                    # chunked-admission lattice: the final chunk compiles
                    # per bucket, mid chunks only at C
                    plan.append(("_chunk_final_jit", (1, b),
                                 functools.partial(chunk_final, b)))
            if chunked:
                plan.append(("_chunk_mid_jit", (1, C), chunk_mid))
            if chunked and self._paged:
                plan.append(("_row_to_blocks_jit", (self._mb,),
                             row_to_blocks))
                plan.append(("_blocks_to_row_jit", (self._mb,),
                             blocks_to_row))
        elif self.logger is not None:
            self.logger.debug({"event": "generator warmup skipped prefill",
                               "reason": "no free slot"})

        def host_write():
            # an IDENTITY rewrite of pool row 0 (a zero-filled dummy
            # would corrupt a live entry's stored KV); mesh snapshots
            # assemble dense first, like the promote path
            kv = dense_hostkv(self._kv_row_get(self._pool, 0, self.max_seq))
            quant = self._pool.quantized
            self._pool = jax.block_until_ready(self._host_write_jit(
                self._pool, jnp.asarray(kv.k[:, None]),
                jnp.asarray(kv.v[:, None]),
                jnp.asarray(kv.k_scale[:, None]) if quant else None,
                jnp.asarray(kv.v_scale[:, None]) if quant else None,
                jnp.int32(0)))

        if self._host_write_jit is not None:
            # the T1/T2 promote program
            plan.append(("_host_write_jit", (self.max_seq,), host_write))

        # All-inactive warm pack (host_wins set, active clear, EOS padded,
        # paged table ZEROED — not the live one: an active slot whose
        # cursor sits at an unallocated block boundary would have its
        # clamped row redirect the dummy write INTO its last live block;
        # with zeros every garbage write lands in the trash block). Two
        # calls: the first covers the host-built carry signature (first
        # live block, _last_dev=None); the second feeds the returned
        # carry + chained key back — the STEADY-STATE signature, whose
        # inputs are jit-output-committed (mesh: rep-sharded). Warming
        # only one would re-lower the big fused scan mid-serving.
        warm_pack, carry = self._warm_pack(), {}

        def step(first: bool):
            _, _, _, carry["dev"], self._key, self.cache, _ = \
                jax.block_until_ready(self._step_jit(
                    self.cache, self.params, warm_pack,
                    self._host_carry() if first else carry["dev"],
                    self._key))

        shape = (self.n_slots, self.decode_block)
        plan.append(("_step_jit", (*shape, "host carry"),
                     functools.partial(step, True)))
        plan.append(("_step_jit", (*shape, "device carry"),
                     functools.partial(step, False)))

        def verify():
            # All-inactive dispatch: emit 0, cursors frozen, garbage KV
            # lands beyond cursors (paged: in the trash block via a
            # zeroed table) like the step warmup's
            window = jnp.zeros((self.n_slots, self._spec_k + 1), jnp.int32)
            table = (jnp.zeros_like(jnp.asarray(self._table)),) \
                if self._paged else ()
            _, _, _, cache_w = self._verify_jit(
                self.cache, self.params, window,
                jnp.zeros((self.n_slots,), bool), self._key, *table,
                self._adapters())
            self.cache = jax.block_until_ready(cache_w)

        if self._spec_k:
            # the verify program too — its first real tick would
            # otherwise compile mid-serving under the device lock,
            # freezing every live stream
            plan.append(("_verify_jit", (self.n_slots, self._spec_k + 1),
                         verify))
        return plan

    def kvcache_stats(self) -> dict | None:
        """Tiered prefix-cache stats for /debug/cache; None when no
        prefix cache is configured."""
        if self._kvc is not None:
            return {"kind": "hierarchical", **self._kvc.stats()}
        if self._prefix_idx is not None:
            return {"kind": "paged-shared", **self._prefix_idx.stats()}
        return None

    def load_adapter(self, idx: int, tree: dict) -> None:
        """Install adapter weights into slot ``idx``: ``tree`` maps a
        projection name ('wq'/'wk'/'wv'/'wo') to its (A [L, in, r],
        B [L, r, out]) pair — the layout LoRA training produces per
        layer. Safe while serving: the swap happens under the device
        lock between iterations; params are never donated, so in-flight
        dispatches keep their snapshot."""
        if not self._n_adapters:
            raise GenerationError("engine built without lora_adapters")
        if not 0 < idx < self._n_adapters:
            raise GenerationError(
                f"adapter slot {idx} invalid (1..{self._n_adapters - 1}; "
                "slot 0 is the base no-op)")
        for name in tree:
            if f"lora_a_{name}" not in self.params["layers"]:
                raise GenerationError(f"unknown LoRA target {name!r}")
        with self._device_lock:
            layers = dict(self.params["layers"])
            for name, (a, b) in tree.items():
                ka, kb = f"lora_a_{name}", f"lora_b_{name}"
                layers[ka] = layers[ka].at[:, idx].set(
                    jnp.asarray(a, layers[ka].dtype))
                layers[kb] = layers[kb].at[:, idx].set(
                    jnp.asarray(b, layers[kb].dtype))
            self.params = {**self.params, "layers": layers}
            if self._prefix_idx is not None:
                # Stored prefix KV was computed through the OLD adapter
                # weights — restoring it after the swap would serve
                # wrong attention keys (same hazard as cross-adapter
                # reuse). Invalidating inside the device lock, AFTER the
                # swap, serializes against the loop's match/store: no
                # old-weight entry can be stored after we invalidate,
                # and the index is only ever mutated under this lock.
                self._prefix_idx.invalidate_adapter(idx)
            if self._kvc is not None:
                # ALL tiers (same hazard as above): T0/T1 drop locally;
                # T2 bumps the adapter's Redis epoch, which renames the
                # shared namespace for every replica at once
                self._kvc.invalidate_adapter(idx)

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful shutdown, phase 1: refuse NEW requests (generate()
        raises), keep serving everything already accepted — active slots
        and the admission queue — until idle or ``timeout``. Returns
        True when fully drained; either way the caller still owns the
        final close(). The k8s-style stop sequence is
        ``app.stop(grace_s=...)``: listeners stay up through the drain
        so in-flight streams complete over their live connections."""
        with self._admission_lock:
            self._draining = True
        def idle() -> bool:
            return (not self._active.any() and self._pending.empty()
                    and self._admitting == 0)

        deadline = time.monotonic() + max(0.0, timeout)
        while time.monotonic() < deadline:
            if idle():
                return True
            time.sleep(0.05)
        return idle()

    def close(self) -> None:
        with self._admission_lock:
            self._closed = True
        self._work.set()
        self._thread.join(timeout=10.0)
        if self.stall_watch is not None:
            self.stall_watch.stop()
        # the registry must not keep claiming bytes for a closed engine
        # (hbmwatch reconciles accounted vs live bytes; the buffers
        # themselves die with this instance's last reference)
        hbm.release(owner=self)
        if self._kvc is not None and self._kvc.redis is not None:
            try:  # the engine owns the T2 client (KVCacheOptions.redis)
                self._kvc.redis.client.close()
            except Exception:
                pass
        for slot in self._slots:
            if slot.request is not None:
                slot.request.stream._q.put(GenerationError("engine closed"))
                slot.request.stream._q.put(None)
                self._obs_end(slot.request.stream, "failed",
                              error="engine closed")
                slot.request = None
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            req.stream._q.put(GenerationError("engine closed"))
            req.stream._q.put(None)
            self._obs_end(req.stream, "failed", error="engine closed")

    # -- the serving loop ----------------------------------------------------
    def _pack_width(self) -> int:
        return (self._PACK_EXTRA + self.EOS_MAX
                + (self._mb if self._paged else 0)
                + (self._dblock + 2 if self._dblock else 0))

    def _warm_pack(self):
        """All-inactive dispatch pack for warmup: host_wins set so the
        carry is ignored, active clear so no cursor moves, EOS rows
        padded, (paged) table zeroed so garbage lands in the trash
        block."""
        p = np.zeros((self.n_slots, self._pack_width()), np.int32)
        p[:, 6] = 1
        p[:, self._PACK_EXTRA:self._PACK_EXTRA + self.EOS_MAX] = \
            llama.EOS_PAD
        return jnp.asarray(p)

    def _host_carry(self):
        """Host-built device slot-state carry — the first block's (and
        post-recovery's) stand-in for the previous dispatch's outputs.
        np.array copies before conversion: see _dev's aliasing note."""
        return (jnp.asarray(np.array(self._last_tokens)),
                jnp.asarray(np.array(self._active)),
                jnp.asarray(np.array(self._budgets)),
                jnp.asarray(np.array(self._pos_abs)),
                *self._prog.carry_tail(self.n_slots))

    def _sampling_flag(self) -> int:
        """What the block about to be dispatched asks of the sampler
        (programs._sample), counted into ``stats()["sampling"]``: bit 0
        an active slot has ``temperature > 0`` (the step takes the drawn
        branch), bit 1 such a slot also has ``top_k > 0`` (the top-64
        runs). The host's own arrays, no device read. The device's
        predicate follows the carried ``active`` mask: a slot that stops
        inside a block already dispatched still counts here for the
        block behind it, where the device skips it."""
        drawing = self._active & (self._temps > 0)
        flag = 0
        if drawing.any():
            flag = 3 if (self._top_ks[drawing] > 0).any() else 1
        self._sample_blocks += 1
        self._sample_drawn += flag & 1
        self._sample_topk += flag >> 1
        return flag

    def _dispatch_pack(self):
        """The decode dispatch's ONE host input: every host-owned
        per-slot array packed into a [B, W] int32 matrix (temps ride as
        f32 bit patterns; the scan prologue bitcasts them back). These
        arrays change only at admission/retirement — re-uploading them
        as a handful of separate h2d transfers per block costs dispatch
        time on every block, so the pack re-uploads as a single transfer and
        ONLY when a mutation site marked it dirty (_touch); in steady
        state the cached device copy is reused and the dispatch carries
        zero host payload. The np staging buffer is fresh per build and
        never mutated after conversion, so CPU-backend zero-copy
        aliasing (the r4 token-carry flake) cannot bite."""
        if self._pack is None or self._pack_dirty:
            E = self.EOS_MAX
            p = np.empty((self.n_slots, self._pack_width()), np.int32)
            p[:, 0] = self._last_tokens
            p[:, 1] = self._active
            p[:, 2] = self._budgets
            p[:, 3] = self._temps.view(np.int32)
            p[:, 4] = self._top_ks
            p[:, 5] = self._slot_adapter
            p[:, 6] = self._host_wins
            p[:, 7] = self._slot_seed
            p[:, 8] = self._pos_abs
            p[:, self._PACK_EXTRA:self._PACK_EXTRA + E] = self._eos_mat
            if self._paged:
                p[:, self._PACK_EXTRA + E:] = self._table
            if self._dblock:
                p[:, self._PACK_EXTRA + E] = self._cursors
                p[:, self._PACK_EXTRA + E + 1] = self._given_n
                p[:, self._PACK_EXTRA + E + 2:] = self._given
            self._pack = jnp.asarray(p)
            self._pack_dirty = False
        return self._pack

    def _dev(self, name: str, host):
        """Device mirror of a host-owned dispatch array. These arrays
        (active mask, temps, top-ks, adapters, block table) change only
        at admission/retirement; re-uploading them every block cost a
        handful of h2d transfers per dispatch. Mutation sites mark
        them dirty (_touch).

        The np source is COPIED before device conversion: on the CPU
        backend jnp.asarray ALIASES numpy memory zero-copy, and
        dispatches are async — a host mutation (in-flight admission,
        post-dispatch bookkeeping) would otherwise be read by the
        still-executing block. That aliasing was the r4 token-carry
        flake's root cause."""
        if name in self._dirty or name not in self._mirror:
            self._mirror[name] = jnp.asarray(np.array(host))
            self._dirty.discard(name)
        return self._mirror[name]

    def _touch(self, *names: str) -> None:
        # one call dirties both representations: the legacy per-name
        # mirrors (_dev — verify/predict paths) and the coalesced
        # decode dispatch pack
        self._dirty.update(names)
        self._pack_dirty = True

    def _adapters(self):
        """[B] adapter ids for batch dispatches, or None when LoRA is
        off (None is an empty pytree: the jit signature stays stable
        and the model paths skip the gather entirely)."""
        if not self._n_adapters:
            return None
        return self._dev("adapters", self._slot_adapter)

    def _adapter1(self, req: "_Request | None"):
        if not self._n_adapters:
            return None
        return jnp.asarray([0 if req is None else req.adapter], jnp.int32)

    def _admit(self) -> int:
        """One admission pass, accounted as the loop's ``admit`` phase
        (with the requests it started as the phase's count). A pass
        that could start nothing — no free slot, or nobody waiting —
        returns before the phase changes: in-flight admission polls
        every millisecond behind a full batch."""
        if self._pending.empty() or not any(s.free for s in self._slots):
            return 0
        prev = self._acct.phase("admit")
        try:
            started = self._admit_pass()
            self._acct.ph_n += started
            return started
        finally:
            self._acct.phase(prev)

    def _admit_pass(self) -> int:
        """Admit pending requests into free slots; returns the number
        started. A pass under an un-reaped block (in-flight admission,
        see _admit_inflight, or a synchronous pass once an earlier
        admission of its own has queued a block behind its prefill, see
        _first_token) must NOT start a chunk-lattice admission — the
        lattice interleaves its own decode blocks, which would
        double-decode every active slot from the un-reaped outer
        block's stale _last_tokens — so lattice-path requests stay
        queued until the outer reap and the next synchronous pass. Nor
        may the pass a lattice runs between its own chunks: one chunk
        stream at a time."""
        started = 0
        for idx, slot in enumerate(self._slots):
            if not slot.free:
                continue
            # _admitting goes up BEFORE the pop: between get_nowait and
            # any later increment a request would be invisible to all of
            # drain()'s idle conditions (not pending, not active, not
            # admitting) and a graceful shutdown could kill an accepted
            # stream. Only this thread mutates the counter.
            self._admitting += 1
            try:
                # slot reservation: this pick may only go to a
                # throughput-class request if filling it still leaves
                # the reserved latency slots free
                free_now = sum(1 for s in self._slots if s.free)
                try:
                    req = self._pending.get_nowait(
                        allow_throughput=free_now > self._lat_reserve)
                except queue.Empty:
                    return started
                if (self._pipe or self._in_lattice) \
                        and self._needs_lattice(req):
                    # a lattice admission cannot start under an
                    # un-reaped block (its interleaved decode ticks
                    # would re-decode stale tokens): return the
                    # request to the HEAD of its class line for the
                    # next synchronous pass. Pop-then-push-front
                    # instead of peek: with per-class lines a
                    # concurrent put() could otherwise change which
                    # head the verdict applied to. The flag drops the
                    # pipeline to depth 1 so that synchronous pass
                    # arrives within one reap instead of never (a full
                    # pipeline would otherwise re-dispatch forever).
                    self._lattice_deferred = True
                    self._pending.put_front(req)
                    return started
                if req.stream.cancelled.is_set():
                    req.stream._q.put(None)
                    self._obs_end(req.stream, "cancelled", tokens=0)
                    continue
                if req.deadline is not None and req.deadline.expired():
                    # the caller's wire deadline ran out while queued:
                    # fail fast, never dispatch its prefill. Ingested
                    # (P/D-shipped) requests record where=post-handoff:
                    # the budget burned AFTER the pool boundary, and
                    # the wide event on THIS worker is the record
                    where = self._expiry_where(req, "queue")
                    self._count_expired(where=where,
                                        request_id=req.stream.request_id)
                    req.stream.where = where
                    wait_s = time.monotonic() - req.enqueued_at
                    req.stream._q.put(DeadlineExceeded(
                        f"deadline expired after {wait_s:.3f}s in the "
                        "admission queue"))
                    req.stream._q.put(None)
                    self._obs_end(req.stream, "failed",
                                  error="deadline expired in queue",
                                  wait_s=round(wait_s, 6))
                    continue
                try:
                    # arbiter checkpoint: one zero-byte lease per
                    # admission. The seeded HBM_ALLOC chaos seam and
                    # the budget-overshoot reclaim both live behind
                    # it, and a failure sheds THIS request (429 +
                    # Retry-After through the gate's shed surface)
                    # instead of raising into the loop's device-loss
                    # recovery — memory pressure degrades the
                    # request, never the engine
                    hbm.check("engine")
                except hbm.HBMExhausted as e:
                    self._shed_oom(req, e)
                    continue
                blocks = None
                if self._paged:
                    blocks = (self._ingest_blocks(req)
                              if req.ingest is not None
                              else self._paged_admission_blocks(req))
                    if blocks is None:
                        # transient pool pressure: requeue and let active
                        # slots retire blocks. (FIFO order is not
                        # preserved across the requeue — pool-pressure
                        # reordering is documented engine behavior.)
                        self._pending.put(req)
                        return started
                self._start(idx, slot, req, blocks)
                started += 1
            finally:
                self._admitting -= 1
        return started

    def _needs_lattice(self, req: _Request) -> bool:
        """Would admitting ``req`` run the chunk-prefill lattice?
        True for prompts past the largest bucket, and for paged prefix
        hits (a hit resumes the lattice from the match point).
        SharedPrefixIndex.match is pure — hit/miss accounting happens
        in accept()/reject() at real admission — so peeking here costs
        one LCP scan and perturbs nothing. The verdict is memoized on
        the request, keyed by the index's version counter: the in-flight
        admission path re-peeks the queue head every ~2 ms poll, and an
        O(entries x prompt) LCP rescan of an unchanged index on the
        serving-loop thread is pure waste."""
        if req.ingest is not None:
            # shipped-KV admission: the install is one row write, no
            # prefill dispatch and no chunk lattice regardless of
            # prompt length — always safe under an un-reaped block
            return False
        L = len(req.prompt)
        if L > self._chunk:
            # past the chunk budget (== the largest bucket by default;
            # smaller when TPU_PREFILL_CHUNK bounds per-dispatch
            # prefill work) the prompt admits through the lattice
            return True
        if not self._paged and self._kvc is not None:
            # contiguous engines: a usable tier hit ALSO resumes the
            # chunk lattice mid-prompt, so in-flight admission must
            # defer it exactly like the paged path (starting the
            # lattice under an un-reaped outer block double-decodes
            # active slots). The memoized _kv_match keeps the verdict
            # consistent with the real admission's — a T2 consult does
            # network I/O, and peeking a DIFFERENT answer than the
            # restore would re-open the hazard this guard closes.
            return self._resume_at(self._kv_match(req), L) > 0
        if self._paged and self._prefix_idx is not None:
            ver = self._prefix_idx.version
            if req.lattice_peek is not None and req.lattice_peek[0] == ver:
                return req.lattice_peek[1]
            _, m = self._prefix_idx.match(
                np.asarray(req.prompt, np.int32), req.adapter)
            verdict = bool(m) and self._lattice_resume_valid(L, m)
            req.lattice_peek = (ver, verdict)
            return verdict
        return False

    def _paged_admission_blocks(self, req: _Request
                                ) -> "tuple[list, int, list] | None":
        """Blocks for one paged admission: consult the prefix index,
        take the slot's hold on any shared blocks, allocate the fresh
        remainder (evicting LRU prefix entries under pressure). Returns
        (shared, matched_tokens, fresh) with one reference per block
        held for the slot — or None (nothing held) when the pool cannot
        cover the request right now."""
        shared, m = [], 0
        if self._prefix_idx is not None:
            shared, m = self._prefix_idx.match(
                np.asarray(req.prompt, np.int32), req.adapter)
            if m and not self._lattice_resume_valid(len(req.prompt), m):
                shared, m = [], 0  # off-lattice window: full recompute
            if shared:
                # take the slot's hold NOW: the evict-retry below could
                # otherwise free the matched entry's blocks out from
                # under us
                self._alloc.ref(shared)
        need = -(-len(req.prompt) // self._block_t) - len(shared)
        fresh = self._alloc.alloc(need)
        while fresh is None and self._prefix_idx is not None \
                and self._prefix_idx.evict_one():
            fresh = self._alloc.alloc(need)
        if fresh is None:
            if shared:
                self._alloc.free(shared)
            return None
        if self._prefix_idx is not None:
            if m:
                self._prefix_idx.accept(shared)
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_tpu_prefix_cache_hits_total")
            else:
                self._prefix_idx.reject()
        return shared, m, fresh

    def _admit_prefill(self, idx: int, req: _Request) -> tuple[int, float]:
        """Run the request's prompt through prefill into slot ``idx`` and
        return (first sampled token, its logprob).

        Prompts within the bucket lattice go through one padded prefill
        dispatch, or through two (_split_prefill) where the plan made
        from the engine's measured table says so. Longer prompts run
        CHUNKED: full chunks of the largest
        bucket size C from position 0, then a final chunk of bucket size
        Sb that ENDS exactly at the prompt end — it may overlap the tail
        of the last full chunk (those positions recompute to identical
        KV: same tokens, same positions, same prefix visibility), which
        keeps every dispatch on the compiled lattice with zero padding
        waste in the cache: capacity used == prompt length."""
        L = len(req.prompt)
        C = self.prompt_buckets[-1]
        self._slot_adapter[idx] = req.adapter
        self._touch("adapters")
        if not L:
            # a block family's prompt of less than a block: all of it
            # is given to the first block, and no program runs
            return None, 0.0
        pos = self._prefix_restore(idx, req, L, C)
        if pos or L > self._chunk:
            return self._chunk_lattice("cache", idx, req, pos)
        b1 = self._split_first[L] if self._split_first else 0
        if b1:
            return self._split_prefill(idx, req, b1)
        Sb = pad_bucket(L, self.prompt_buckets)
        padded = np.zeros((1, Sb), np.int32)
        padded[0, :L] = req.prompt
        tok, lp, self._key, self.cache = self._run(
            self._prefill_jit,
            self.cache, self.params, jnp.asarray(padded), jnp.int32(L),
            jnp.int32(idx), jnp.float32(req.temperature),
            jnp.int32(req.top_k), self._key, jnp.int32(req.seed),
            jnp.int32(req.pos_base), self._adapter1(req))
        self._count_program(Sb)
        self._count_prefill(L, Sb)
        return self._first_token(tok, lp)

    def _split_prefill(self, idx: int, req: _Request,
                       b1: int) -> tuple[int, float]:
        """A prompt within the chunk budget as two dispatches, where the
        measured table says they are cheaper than the one padded bucket
        (prefill_plan): the whole bucket ``b1`` through the prefill
        program, whose sampled token is dropped, then the rest through
        the final-chunk program in the form the lattice's last chunk
        has (_final_chunk). Back to back: no decode block, admission
        pass or fetch between them, so this is one admission to
        everything around it (an in-flight one stays in flight, and
        cancellation and expiry were looked at before the first)."""
        tl = self._tl
        _, _, self._key, self.cache = self._run(
            self._prefill_jit, self.cache, self.params,
            jnp.asarray(req.prompt[None, :b1]), jnp.int32(b1),
            jnp.int32(idx), jnp.float32(0.0), jnp.int32(0), self._key,
            jnp.int32(0), jnp.int32(0), self._adapter1(req))
        self._count_program(b1)
        t0c = time.monotonic() if tl is not None else 0.0
        tok, lp, Sr = self._final_chunk("cache", idx, req, b1)
        if tl is not None:
            tl.chunk(t0c, time.monotonic(), idx, 0, Sr,
                     req.stream.request_id)
        self._count_prefill(len(req.prompt), b1 + Sr, split=True)
        return self._first_token(tok, lp)

    def _count_prefill(self, tokens: int, positions: int,
                       split: bool = False) -> None:
        """One admission's prompt programs: the prompt tokens they
        computed and the positions they ran (padding and an overlap
        counted), stats()["scheduler"]["prefill"] and three counters."""
        n = self._prefill_n
        n["admissions"] += 1
        n["split"] += split
        n["prompt_tokens"] += tokens
        n["positions"] += positions
        if self.metrics is not None:
            inc = self.metrics.increment_counter
            inc("app_tpu_prefill_prompt_tokens_total", by=tokens)
            inc("app_tpu_prefill_positions_total", by=positions)
            if split:
                inc("app_tpu_prefill_split_total")

    def _count_program(self, positions: int) -> None:
        """One dispatch of a prompt program of ``positions`` positions:
        where the family says its programs of that size route their
        experts (stats()["moe_prompt_dispatch"]), they are routed
        positions, stats()["scheduler"]["prefill"] and a counter."""
        if self._routed_from and positions >= self._routed_from:
            self._prefill_n["routed_positions"] += positions
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_tpu_moe_routed_positions_total", by=positions)

    def _count_chunk(self, start: int, positions: int) -> None:
        """One dispatch of a chunk program of ``positions`` positions at
        ``start`` (_count_program): the cached rows its attention
        fetched (the blocks under its start) beside the rows the slot
        reserves, stats()["scheduler"]["prefill"] and two counters. A
        family whose chunk program walks under no cursor counts
        neither. Before them, whether the program's walk over cached
        latent rows is the kernel's: of ``chunks`` dispatches,
        ``chunks_on_walk_kernel`` and a counter."""
        self._count_program(positions)
        n = self._prefill_n
        n["chunks"] += 1
        if self._walk_kernel and self._walk_kernel(self.cfg, self.max_seq,
                                                   positions):
            n["chunks_on_walk_kernel"] += 1
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_tpu_chunk_walk_kernel_total")
        block = self._walk_block
        if not block:
            return
        walked = -(-start // block) * block
        n["cache_rows_walked"] += walked
        n["cache_rows_reserved"] += self.max_seq
        if self.metrics is not None:
            inc = self.metrics.increment_counter
            inc("app_tpu_chunk_rows_walked_total", by=walked)
            inc("app_tpu_chunk_rows_reserved_total", by=self.max_seq)

    def _first_token(self, tok, lp) -> tuple[int, float]:
        """Fetch the token an admission's last program sampled. The
        copy blocks until every program queued before it is done (the
        decode block in flight, then the prefill): the thread is
        blocked on the device, which is the loop's ``fetch`` phase, not
        host work of admission.

        Before it blocks, the pipe is topped up with ONE decode block
        for the slots already decoding, queued behind the prefill
        (_trail): the token comes back when the prefill is done, as
        before, and the admission's host work after it (prefix store,
        delivery, _start's bookkeeping, the reap of the older block,
        the next dispatch pack) runs while that block computes instead
        of with the stream dry. The admitted slot is not in that block
        (it joins the next through host_wins), so its second token
        comes one block later than it would from a dry stream.

        The one place that asks whether the family's prefill yields a
        token: one whose step is a pass over a block does not (its
        programs sample from zeros), and (None, 0.0) tells _start to
        deliver nothing and hand the slot to its first pass. The fetch
        stays: it is what makes an admission wait for its prompt."""
        self._trail()
        prev = self._acct.phase("fetch")
        try:
            tok, lp = jax.device_get((tok, lp))  # one round trip, not two
            if self._dblock:
                return None, 0.0
            return int(tok), float(lp)
        finally:
            self._acct.phase(prev)

    def _trail(self) -> None:
        """Queue one decode block behind the admission prefill just
        dispatched, where the depth policy has room for it: not under
        the pass a chunk lattice runs between its chunks (it reaps its
        own block synchronously, and a block queued here would be
        older and un-reaped), not while another latency-class waiter
        can be admitted in this pass (its prefill goes first), not past
        the configured depth. In that block the admitted slot is
        inactive and its cursor is the one the prefill set (the prompt
        length), so the step's frozen-cursor scatter lands at the first
        position after the prompt, which the slot's own first decode
        step overwrites; on the paged engine the slot's table row is
        still the trash block's (_paged_admit_prefill installs it after
        the fetch). Fires the GENERATOR_STEP chaos seam like every
        other top-up: a failure here is a device loss between a
        prefill and the block behind it."""
        pipe = self._pipe
        if self._in_lattice or len(pipe) >= self._target_depth():
            return
        chaos.fire(chaos.GENERATOR_STEP)
        inflight = self._tick(decode_only=bool(pipe))
        if inflight is not None:
            pipe.append(inflight)
            self._note_depth(len(pipe))

    def _lattice_resume_valid(self, L: int, m: int) -> bool:
        """Can the chunk lattice resume at position ``m`` of an L-token
        prompt? The final chunk's bucket must not pad wider than the
        prompt (a negative window start would slice off the compiled
        lattice) — the shared reject-to-miss guard for prefix hits on
        both engine kinds. Mirrors ``_chunk_lattice``'s loop: mid
        chunks advance by the configured chunk budget."""
        T = self._chunk
        rem = L - m
        while rem > T:
            rem -= T
        return L - pad_bucket(rem, self.prompt_buckets) >= 0

    def _resume_at(self, mt, L: int) -> int:
        """The position an L-token prompt's prefill resumes from on
        match ``mt`` (0: the match is of no use). Rows restore to any
        matched length on the lattice (at most L - 1: the last position
        is always prefilled, the pool stores KV, not logits). A family
        whose memory cannot be rewound stores a row under the tokens
        before the position its state was taken at (_lattice_snapshot),
        so its hit is whole or nothing: every token of the entry, which
        is a chunk boundary; never fewer, never from rows alone. A
        prompt that ends at that boundary is a miss: the clamp to L - 1
        would resume one token before the position the state holds."""
        if mt is None:
            return 0
        if not self._rewind:
            m = mt.matched_len
            if mt.entry is None or m != len(mt.entry.key) or m >= L \
                    or m < self.prompt_buckets[0]:
                return 0
            return m
        m = clamp_restore_len(mt.matched_len, L)
        if self._dblock:
            # a block's rows hold all of its tokens: whole matched
            # blocks alone are the matched tokens' own
            m -= m % self._dblock
        if m < self.prompt_buckets[0] \
                or not self._lattice_resume_valid(L, m):
            # less than the smallest bucket: the copy would not remove a
            # dispatch's worth of work; and the final chunk needs
            # [L - Sb, L) to be a valid window
            return 0
        return m

    def _chunk_lattice(self, attr: str, slot: int, req: _Request,
                       pos: int = 0,
                       track_slot: int | None = None) -> tuple[int, float]:
        """Run the chunked-prefill lattice for ``req.prompt[pos:]``
        against the cache at ``getattr(self, attr)`` ("cache" for the
        contiguous engine, "_scratch" for paged long-prompt admission),
        writing into batch row ``slot``. Between mid chunks (interleave
        on) the loop yields the device: one admission pass for NEW
        arrivals — a bucket-lattice request reaching the pending line
        mid-prefill gets its own prefill dispatched within one chunk
        budget instead of waiting out this whole prompt — then one
        decode block for the live batch, so long admissions never
        stall active decode streams. With ``prefill_chunk <= 0`` the
        chunks dispatch back-to-back (the head-of-line contrast arm
        tools/slo_bench.py measures against). Returns the final
        chunk's sampled (token, logprob) — or (0, 0.0) when the
        request was cancelled or deadline-expired mid-lattice (the
        token is discarded anyway: _deliver retires cancelled slots
        before use). ``track_slot``: the serving slot the timeline
        renders these chunk slices on (paged admissions dispatch
        against scratch row 0 but serve slot ``idx``)."""
        L = len(req.prompt)
        T = self._chunk
        pos0 = pos
        tslot = slot if track_slot is None else track_slot
        ship_cap = L
        if req.kv_sink is not None:
            # prefill-only: the FINAL chunk re-computes its window
            # [L - Sb, L) reading already-quantized cache for the
            # earlier positions, so on int8 caches the overlap's
            # layer>0 KV differs from the mid-chunk version by one
            # int8 round trip — and the slot row keeps the FINAL
            # version. Mid-chunk shipping stops at the final window's
            # start; the overlap ships from the settled row in _start,
            # keeping the shipped stream bit-identical to the row (the
            # decode pool must replicate THIS engine's cache exactly).
            rem = L - pos
            while rem > T:
                rem -= T
            ship_cap = L - pad_bucket(rem, self.prompt_buckets)
        while L - pos > T:
            if req.stream.cancelled.is_set():
                return 0, 0.0
            if self._expire_mid_lattice(req, pos):
                return 0, 0.0
            chaos.fire(chaos.GENERATOR_CHUNK)
            chunk = req.prompt[pos:pos + T]
            t0c = time.monotonic() if self._tl is not None else 0.0
            setattr(self, attr, self._run(
                self._chunk_mid_jit, getattr(self, attr), self.params,
                jnp.asarray(chunk[None, :]), jnp.int32(pos),
                jnp.int32(slot), jnp.int32(0), jnp.int32(0),
                jnp.float32(0.0), jnp.int32(0), self._key,
                jnp.int32(0), jnp.int32(0), self._adapter1(req)))
            self._count_chunk(pos, T)
            pos += T
            req.stream.chunks += 1
            if self._tl is not None:
                # host dispatch slice (the device work runs async
                # behind it); index + length make the lattice's shape
                # readable on the slot's track
                self._tl.chunk(t0c, time.monotonic(), tslot,
                               req.stream.chunks - 1, T,
                               req.stream.request_id)
            if self.metrics is not None:
                self.metrics.increment_counter("app_tpu_prefill_chunks_total")
            if req.kv_sink is not None and attr == "cache":
                # prefill-only: stream the chunk's KV out NOW — the
                # decode peer's host-side assembly (and the wire
                # transfer) overlaps the remaining chunks' compute, so
                # the handoff costs one tail ship, not a whole-prompt
                # serialization (capped before the final window — see
                # ship_cap above). The row read blocks on this chunk's
                # dispatch; a ship failure cancels the request (never
                # the loop).
                if not self._ship_range(attr, slot, req,
                                        min(pos, ship_cap)):
                    return 0, 0.0
            if not self._chunk_interleave:
                continue
            # Yield between chunks — everything below already runs
            # under the device lock (the lattice is only entered from
            # the loop thread's admission pass):
            #   1. admit new arrivals into OTHER free slots (this
            #      slot is claimed by _start); lattice-path arrivals
            #      stay queued — one chunk stream at a time;
            #   2. one decode block for the live batch, reaped
            #      synchronously so its tokens deliver before the
            #      next chunk occupies the device (so an admission of
            #      step 1 queues no block behind its prefill: that
            #      block would be reaped after this one).
            self._in_lattice = True
            try:
                self._admit()
            finally:
                self._in_lattice = False
            inflight = self._tick(decode_only=True)
            if inflight is not None:
                self._reap(inflight)
        if req.stream.cancelled.is_set():
            return 0, 0.0
        if self._expire_mid_lattice(req, pos):
            return 0, 0.0
        if not self._rewind:
            # the memory as it stands here, at a chunk boundary, is
            # what a prefix hit can use
            self._lattice_snapshot(slot, req, pos)
        tok, lp, Sb = self._final_chunk(attr, slot, req, pos)
        self._count_prefill(L - pos0, pos - pos0 + Sb)
        return self._first_token(tok, lp)

    def _final_chunk(self, attr: str, slot: int, req: _Request, pos: int):
        """Dispatch the final-chunk program for ``req.prompt[pos:]``
        into row ``slot`` of the cache at ``attr``: the device's
        (token, logprob) and the bucket it ran."""
        L = len(req.prompt)
        rem = L - pos
        Sb = pad_bucket(rem, self.prompt_buckets)
        if self._rewind:
            # the final chunk ends at the prompt's end and overlaps the
            # one before: those positions recompute to identical KV
            begin, final = L - Sb, req.prompt[L - Sb:]
        else:
            # a state cannot be rewound: the final chunk starts where
            # the last one ended and is padded (the family masks what
            # lies past the sampled position)
            begin, final = pos, np.zeros((Sb,), np.int32)
            final[:rem] = req.prompt[pos:]
        tok, lp, self._key, new_cache = self._run(
            self._chunk_final_jit,
            getattr(self, attr), self.params, jnp.asarray(final[None, :]),
            jnp.int32(begin), jnp.int32(slot), jnp.int32(L),
            jnp.int32(L - begin - 1), jnp.float32(req.temperature),
            jnp.int32(req.top_k), self._key, jnp.int32(req.seed),
            jnp.int32(req.pos_base), self._adapter1(req))
        setattr(self, attr, new_cache)
        self._count_chunk(begin, Sb)
        return tok, lp, Sb

    def _expire_mid_lattice(self, req: _Request, pos: int) -> bool:
        """Deadline check between chunk dispatches: a half-prefilled
        request whose caller already gave up must stop burning device
        time NOW — its remaining chunks, its decode slot, all of it.
        Fails the stream with DeadlineExceeded and flips the cancelled
        flag so the existing cancel-retire path (parked cursor, block
        release at _deliver/_retire) cleans the slot up."""
        if req.deadline is None or not req.deadline.expired():
            return False
        self._count_expired(where="mid-prefill",
                            request_id=req.stream.request_id)
        req.stream.where = "mid-prefill"
        req.stream.failed = "deadline expired mid-prefill"
        req.stream._q.put(DeadlineExceeded(
            f"deadline expired after {pos}/{len(req.prompt)} prompt "
            "tokens were prefilled"))
        req.stream.cancel()
        if self._observe is not None:
            self._observe.recorder.record(
                "expired_mid_prefill", request_id=req.stream.request_id,
                trace_id=req.stream.trace_id, prefilled=pos,
                prompt_len=len(req.prompt))
        return True

    def _expire_decoding(self, idx: int, slot: _Slot) -> bool:
        """Deadline check at the reap, once per slot per block: a
        decoding stream whose caller's wire deadline ran out stops
        consuming its slot NOW — even with further blocks already in
        flight (the pipelined dispatches' tokens for this slot are
        dropped by the snapshot/emitted guards, and _retire's host_wins
        deactivates it for every dispatch after those). Fails the
        stream with DeadlineExceeded and retires the slot."""
        req = slot.request
        if req is None or req.deadline is None or not req.deadline.expired():
            return False
        where = self._expiry_where(req, "mid-decode")
        self._count_expired(where=where,
                            request_id=req.stream.request_id)
        req.stream.where = where
        req.stream.failed = "deadline expired mid-decode"
        req.stream._q.put(DeadlineExceeded(
            f"deadline expired after {slot.generated} generated tokens"))
        req.stream.cancel()
        if self._observe is not None:
            self._observe.recorder.record(
                "expired_mid_decode", request_id=req.stream.request_id,
                trace_id=req.stream.trace_id, tokens=slot.generated)
        self._retire(idx, slot)
        return True

    # -- paged-mode host side ------------------------------------------------
    def _paged_admit_prefill(self, idx: int, req: _Request,
                             shared: list[int], m: int,
                             fresh: list[int]) -> tuple[int, float]:
        """Paged admission. ``shared``/``m``: prefix-cache hit — m
        tokens of KV already live in ``shared`` pool blocks (the slot
        holds a reference, taken at _admit); ``fresh``: newly allocated
        blocks for the rest. Bucket-lattice prompts without a hit go
        through one padded prefill dispatch; everything else (long
        prompts, any hit) resumes the chunk lattice on the dense
        scratch row — for hits, the shared blocks gather into the
        scratch first and only the FRESH region writes back, so shared
        blocks are never rewritten."""
        L = len(req.prompt)
        T = self._block_t
        blocks = shared + fresh
        self._slot_adapter[idx] = req.adapter
        self._touch("adapters")
        # Register the blocks as the slot's FIRST — every exit path
        # (cancel mid-lattice included) then frees them through the
        # normal _retire, instead of leaking pool blocks the allocator
        # handed _admit (_start's exception path clears this state
        # itself before freeing). The TABLE row, however, stays zeroed
        # (trash-routed) until admission completes: the device cursor is
        # still the slot's STALE previous length, and the decode ticks
        # interleaved into the chunk lattice write garbage KV for
        # inactive slots at that cursor — through an installed row that
        # garbage would land inside the new blocks (for a prefix hit,
        # inside SHARED blocks, permanently corrupting every other
        # holder; the write-back only repairs the fresh region).
        self._slot_blocks[idx] = blocks
        self._cursors[idx] = L
        if m == 0 and L <= self._chunk:
            Sb = pad_bucket(L, self.prompt_buckets)
            n_wr = -(-Sb // T)
            write_blocks = blocks + [0] * (n_wr - len(blocks))
            padded = np.zeros((1, Sb), np.int32)
            padded[0, :L] = req.prompt
            tok, lp, self._key, self.cache = self._run(
                self._prefill_jit,
                self.cache, self.params, jnp.asarray(padded), jnp.int32(L),
                jnp.asarray(write_blocks, jnp.int32), jnp.int32(idx),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                self._key, jnp.int32(req.seed), jnp.int32(req.pos_base),
                self._adapter1(req))
            self._count_program(Sb)
            self._count_prefill(L, Sb)
            # the row goes in AFTER the fetch: the block _first_token
            # queues behind the prefill holds this slot inactive at
            # cursor L, and through an installed row its garbage write
            # would land in the prompt's last block when L fills it
            # (the clamped row repeats that block)
            first = self._first_token(tok, lp)
            self._write_table_row(idx)
            return first
        if m > 0:
            # restore: shared blocks -> scratch positions [0, m)
            read_blocks = shared + [0] * (self._mb - len(shared))
            self._scratch = self._run(
                self._blocks_to_row_jit, self._scratch, self.cache,
                jnp.asarray(read_blocks, jnp.int32))
            # zero-copy block-share hit: the wide event and timeline
            # call it tier "paged" (the paged engine has no t0/t1/t2)
            req.stream.cache_tier = "paged"
            req.stream.cache_tokens = m
            if self._tl is not None:
                self._tl.kvcache("paged", m, idx)
        tok, lp = self._chunk_lattice("_scratch", 0, req, pos=m,
                                      track_slot=idx)
        if req.stream.cancelled.is_set():
            return tok, lp  # slot retires at _deliver; blocks free there
        # write back only the FRESH region: scratch rows for the shared
        # blocks (identical data) route to the trash block
        write_blocks = [0] * len(shared) + fresh \
            + [0] * (self._mb - len(blocks))
        self.cache = self._run(
            self._row_to_blocks_jit, self.cache, self._scratch,
            jnp.asarray(write_blocks, jnp.int32))
        self.cache = self.cache._replace(
            lengths=self.cache.lengths.at[idx].set(L))
        self._write_table_row(idx)
        return tok, lp

    def _write_table_row(self, idx: int) -> None:
        """Clamped table row: entries past the slot's live blocks repeat
        the last one (the kernel's DMA-skip); empty slots stay on the
        trash block. Slice-assigned — this runs on the GIL-held serving
        loop."""
        blocks = self._slot_blocks[idx]
        self._touch("table")
        if not blocks:
            self._table[idx, :] = 0
            return
        n = min(len(blocks), self._mb)
        self._table[idx, :n] = blocks[:n]
        self._table[idx, n:] = blocks[n - 1]

    def _ensure_blocks(self, horizon: int | None = None) -> None:
        """Pre-dispatch invariant: every active slot owns blocks covering
        its next ``horizon`` positions (default: one decode block; verify
        ticks pass their window width). On pool exhaustion the slot that
        cannot grow is retired early (its stream ends as if at capacity)
        — freeing its blocks for the rest of the batch; the eviction is
        logged and counted."""
        K = horizon or self.decode_block
        T = self._block_t
        for idx, slot in enumerate(self._slots):
            if not self._active[idx]:
                continue
            cur = int(self._cursors[idx])
            hi = cur + K  # highest write is at position hi - 1
            stop = int(self._stop_cursors[idx])
            if horizon is None and stop > 0:
                # decode writes freeze at the device stop cursor: never
                # demand (or starvation-retire for) blocks a finished
                # stream will not touch. Verify windows keep the full
                # horizon — their junk rows past acceptance are the
                # clamped-table contract.
                hi = min(hi, stop)
                if hi <= cur:
                    continue  # device-stopped; awaiting the reap
            need = min((hi - 1) // T + 1, self._mb)
            if len(self._slot_blocks[idx]) >= need:
                continue  # row already written at admission/last growth
            starved = False
            while len(self._slot_blocks[idx]) < need:
                got = self._alloc.alloc(1)
                if got is None:
                    # prefix entries are the pressure valve: evict LRU
                    # stored prefixes before truncating a live stream
                    if self._prefix_idx is not None and \
                            self._prefix_idx.evict_one():
                        continue
                    starved = True
                    break
                self._slot_blocks[idx].extend(got)
            if starved:
                self._paged_evictions += 1
                if self.logger is not None:
                    self.logger.warn({
                        "event": "paged pool exhausted: stream truncated",
                        "slot": idx,
                        "generated": slot.generated,
                        "free_blocks": self._alloc.free_blocks})
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_tpu_paged_evictions_total")
                self._retire(idx, slot)
                continue
            self._write_table_row(idx)

    def _kv_match(self, req: _Request, prompt: np.ndarray | None = None):
        """Request-memoized ``CacheManager.match``, keyed by the
        manager's version counter. The in-flight admission peek
        (_needs_lattice) and the real admission must see ONE verdict —
        a disagreement would start a chunk lattice inside an in-flight
        admission — and a T2 consult does network I/O the ~2 ms peek
        poll must not repeat. Only the serving-loop thread calls this,
        and it cannot store between peek and admit, so a memo keyed by
        version is exact."""
        ver = self._kvc.version
        if req.kv_match is not None and req.kv_match[0] == ver:
            return req.kv_match[1]
        if prompt is None:
            prompt = np.asarray(req.prompt, np.int32)
        mt = self._kvc.match(prompt, req.adapter)
        req.kv_match = (ver, mt)
        return mt

    def _kv_row_get(self, store, row: int, plen: int,
                    start: int = 0) -> "HostKV | ShardedHostKV":
        """Fetch positions ``[start, plen)`` of one pool/cache row to
        host numpy — the spill half of T1 offload, the read half of
        T2 write-through, and (``start > 0``) the incremental KV-ship
        reads of a prefill worker. On a MESH the snapshot is
        PER-SHARD: each tp shard's head range reads straight off its
        own device shard (no cross-device gather on the spill path) —
        a ShardedHostKV whose parts the offload tiers store and frame
        verbatim; the restore side assembles the canonical dense row
        (dense_hostkv) before the placed write. The device holds a KV
        head's positions together ([L, B, KV, Smax, hd]); what the host
        keeps, stores and ships is [L, plen, KV, hd] as it always was:
        the transposition happens here."""
        quant = store.k_scale is not None
        if self.mesh is None:
            def get(a):
                return self._host_order(
                    np.asarray(a[:, row, :, start:plen]))

            return HostKV(get(store.k), get(store.v),
                          get(store.k_scale) if quant else None,
                          get(store.v_scale) if quant else None)
        k_p = self._row_shard_parts(store.k, row, start, plen)
        v_p = self._row_shard_parts(store.v, row, start, plen)
        ks_p = (self._row_shard_parts(store.k_scale, row, start, plen)
                if quant else None)
        vs_p = (self._row_shard_parts(store.v_scale, row, start, plen)
                if quant else None)
        parts = tuple(HostKV(k_p[i], v_p[i],
                             ks_p[i] if quant else None,
                             vs_p[i] if quant else None)
                      for i in range(len(k_p)))
        return parts[0] if len(parts) == 1 else ShardedHostKV(parts)

    @staticmethod
    def _host_order(a: np.ndarray) -> np.ndarray:
        """A fetched row [L, KV, plen(, hd)] as the host holds it,
        [L, plen, KV(, hd)], contiguous (HostKV)."""
        return np.ascontiguousarray(np.swapaxes(a, 1, 2))

    @classmethod
    def _row_shard_parts(cls, arr, row: int, start: int,
                         stop: int) -> list:
        """One batch row's positions ``[start, stop)`` read per tp
        shard of a [L, B, KV, Smax(, hd)] cache leaf, each in the
        host's order: walk the leaf's addressable shards, keep the
        shard covering ``row`` for each distinct KV-head offset
        (replicated axes repeat the same heads — first wins), and
        return the pieces in head order.
        Each read is a single-device ``device_get`` of that shard's
        slab — the mesh never assembles the row to spill it."""
        parts: dict[int, np.ndarray] = {}
        B = arr.shape[1]
        for sh in arr.addressable_shards:
            idx = sh.index
            bsl = idx[1]
            b0 = bsl.start or 0
            b1 = B if bsl.stop is None else bsl.stop
            if not (b0 <= row < b1):
                continue
            h0 = idx[2].start or 0
            if h0 in parts:
                continue
            parts[h0] = cls._host_order(
                np.asarray(sh.data)[:, row - b0, :, start:stop])
        return [parts[h] for h in sorted(parts)]

    def _offload_victim(self, victim) -> None:
        """Spill a T0-evicted entry's pool row to the host tier. MUST
        run before the dispatch that overwrites the row (store/promote
        call it between claiming the row and copying into it)."""
        if victim is None or not self._kvc.wants_offload:
            return
        plen = min(len(victim.key), self.max_seq)
        self._kvc.offload(victim, self._kv_row_get(self._pool,
                                                   victim.row, plen))

    def _promote_hostkv(self, mt) -> int | None:
        """Land a T1/T2 match's host KV in a T0 pool row (device_put +
        one compiled row write) and register it under the entry's full
        key — the next hit on this prefix is a T0 row copy. Returns the
        row, or None when the payload cannot serve this engine (shape/
        quantization drift: treat as a miss, never an error). Sharded
        snapshots assemble to the canonical dense row first — which is
        what lets T1 entries survive even a mesh-SHAPE change across
        device-loss re-placement."""
        kv = dense_hostkv(mt.hostkv) if mt.hostkv is not None else None
        quant = self._pool.quantized
        if (kv is None or kv.plen > self.max_seq or len(mt.key) < kv.plen
                or (quant and kv.k_scale is None)
                or kv.k.shape[0] != self._pool.k.shape[0]
                or kv.k.shape[2:] != self._fam.kv_layout(self.cfg)):
            return None
        row, victim = self._kvc.store(mt.key[:kv.plen], mt.adapter)
        self._offload_victim(victim)

        def pad(a, like):
            out = np.zeros((a.shape[0], 1, self.max_seq) + a.shape[2:],
                           like.dtype)
            out[:, 0, :kv.plen] = a
            return jnp.asarray(out)

        self._pool = self._run(
            self._host_write_jit,
            self._pool, pad(kv.k, self._pool.k), pad(kv.v, self._pool.v),
            pad(kv.k_scale, self._pool.k_scale) if quant else None,
            pad(kv.v_scale, self._pool.v_scale) if quant else None,
            jnp.int32(row))
        return row

    # -- disaggregated serving (gofr_tpu/pd/) --------------------------------
    @staticmethod
    def _expiry_where(req: _Request, default: str) -> str:
        """Expiry-site label for telemetry: ingested (P/D-shipped)
        requests died AFTER the pool handoff — the decode worker's
        wide event says so, whatever stage the local default names."""
        return "post-handoff" if req.ingest is not None else default

    def _ship_range(self, attr: str, row: int, req: _Request,
                    upto: int) -> bool:
        """Prefill-only KV ship: snapshot prompt positions
        ``[req.kv_shipped, upto)`` of the slot row and hand them to the
        request's sink (the PD shipper frames + sends them). A sink
        failure — peer gone, ship window stalled past its deadline —
        fails THIS request (cancel-retire path) and returns False; it
        must never surface into the loop's device-loss recovery, the
        engine is healthy."""
        if req.kv_sink is None or upto <= req.kv_shipped:
            return True
        if req.stream.cancelled.is_set():
            # a dead request (client cancel, or an earlier ship failure
            # that already cancelled it) must not re-block the serving
            # loop for another window deadline shipping KV nobody will
            # ingest — _start's tail ship hits this after a mid-lattice
            # failure
            return False
        try:
            kv = self._kv_row_get(getattr(self, attr), row, upto,
                                  start=req.kv_shipped)
            req.kv_sink(kv, req.kv_shipped, len(req.prompt))
            req.kv_shipped = upto
            return True
        except BaseException as e:  # noqa: BLE001 — per-request failure
            req.stream.failed = f"kv ship failed: {e!r}"
            req.stream._q.put(GenerationError(f"kv ship failed: {e!r}"))
            req.stream.cancel()
            if self._observe is not None:
                self._observe.recorder.record(
                    "kv_ship_failed", request_id=req.stream.request_id,
                    trace_id=req.stream.trace_id,
                    shipped=req.kv_shipped, prompt_len=len(req.prompt),
                    error=repr(e))
            if self.logger is not None:
                self.logger.warn({"event": "pd kv ship failed",
                                  "request_id": req.stream.request_id,
                                  "shipped": req.kv_shipped,
                                  "error": repr(e)})
            return False

    def _validate_ingest(self, ingest, prompt: np.ndarray) -> None:
        """Reject a shipped-KV payload that cannot land in THIS
        engine's cache before it is ever queued: the ingest server
        relays the raised error typed; nothing here touches the
        device. (Frame-level integrity — checksum, truncation — was
        already enforced per frame by quant.decode_block at the
        transfer boundary.)"""
        kv, _, _ = ingest
        if self.mesh is not None:
            raise GenerationError("KV ingest requires a single-device "
                                  "decode engine (sharded install does "
                                  "not partition)")
        if kv.plen != len(prompt):
            raise GenerationError(
                f"ingest KV covers {kv.plen} tokens but the prompt has "
                f"{len(prompt)} — the transfer is incomplete")
        cfg = self.cfg
        tables = self._fam.kv_tables(cfg)
        if (kv.k.shape[0] != tables
                or kv.k.shape[2:] != (cfg.n_kv_heads, cfg.head_dim)):
            raise GenerationError(
                f"ingest KV layout {kv.k.shape} does not match this "
                f"engine ({tables} row tables, {cfg.n_kv_heads} KV "
                f"heads, head_dim {cfg.head_dim})")
        quant = self.cache.k_scale is not None
        if quant and kv.k_scale is None:
            raise GenerationError("ingest KV lacks scale planes but the "
                                  "serving cache is int8-quantized")
        if str(kv.k.dtype) != str(self.cache.k.dtype):
            raise GenerationError(
                f"ingest KV dtype {kv.k.dtype} != serving cache dtype "
                f"{self.cache.k.dtype}")

    def _ingest_blocks(self, req: _Request) -> "tuple[list, int, list] | None":
        """Paged-pool blocks for one shipped-KV admission: all fresh
        (the shipped rows are installed, not prefix-matched), evicting
        LRU stored prefixes under pressure exactly like a local
        admission. None = transient shortage, requeue."""
        need = -(-len(req.prompt) // self._block_t)
        fresh = self._alloc.alloc(need)
        while fresh is None and self._prefix_idx is not None \
                and self._prefix_idx.evict_one():
            fresh = self._alloc.alloc(need)
        if fresh is None:
            return None
        return [], 0, fresh

    def _ingest_install(self, idx: int, req: _Request,
                        fresh: "list | None") -> tuple[int, float]:
        """Land a prefill worker's shipped KV in slot ``idx`` with ZERO
        prefill FLOPs: pad the host rows to the compiled row shape and
        install them — contiguous engines write the serving row
        directly; paged engines stage through the dense scratch row
        and land it in their ``fresh`` pool blocks (the same two
        programs the T1/T2 promote and long-prompt admission paths
        compile). The transient padded upload is leased from the HBM
        arbiter first (``pd-ingest`` stage, PRI_SCRATCH): under memory
        pressure the request SHEDS 429 at the boundary instead of
        OOMing the decode pool. T0 promotion then rides the normal
        ``_prefix_store`` in _start — an ingested prompt's KV lands in
        a pool row / shared-block entry exactly like a locally
        prefilled one, so repeat traffic hits locally next time."""
        kv, first, first_lp = req.ingest
        L = kv.plen
        self._slot_adapter[idx] = req.adapter
        self._touch("adapters")
        if self._paged:
            self._ensure_scratch()
            target_attr = "_scratch"
            row = 0
        else:
            target_attr = "cache"
            row = idx
        target = getattr(self, target_attr)
        quant = target.k_scale is not None

        def pad(a, like):
            out = np.zeros((a.shape[0], 1, self.max_seq) + a.shape[2:],
                           np.dtype(str(like.dtype)))
            out[:, 0, :L] = a
            return out

        k_p, v_p = pad(kv.k, target.k), pad(kv.v, target.v)
        ks_p = pad(kv.k_scale, target.k_scale) if quant else None
        vs_p = pad(kv.v_scale, target.v_scale) if quant else None
        stage = k_p.nbytes + v_p.nbytes \
            + (ks_p.nbytes + vs_p.nbytes if quant else 0)
        # the stage lease is the admission's honest memory claim: the
        # padded device upload lives until the row write consumes it
        hbm.lease("pd-ingest", stage, owner=self, tag="stage",
                  priority=hbm.PRI_SCRATCH)
        try:
            if self._ingest_write_jit is None:
                self._ingest_write_jit = jax.jit(
                    programs._write_row_from_host, donate_argnums=(0,))
            installed = self._run(
                self._ingest_write_jit,
                target, jnp.asarray(k_p), jnp.asarray(v_p),
                jnp.asarray(ks_p) if quant else None,
                jnp.asarray(vs_p) if quant else None, jnp.int32(row))
            setattr(self, target_attr, installed)
            if self._paged:
                self._slot_blocks[idx] = list(fresh)
                self._cursors[idx] = L
                write_blocks = list(fresh) + [0] * (self._mb - len(fresh))
                self.cache = self._run(
                    self._row_to_blocks_jit, self.cache, self._scratch,
                    jnp.asarray(write_blocks, jnp.int32))
                self._write_table_row(idx)
            self.cache = self.cache._replace(
                lengths=self.cache.lengths.at[idx].set(L))
        finally:
            hbm.release("pd-ingest", owner=self, tag="stage")
        req.stream.cache_tier = "pd-ship"
        req.stream.cache_tokens = L
        if self._tl is not None:
            self._tl.kvcache("pd", L, idx)
        if self.metrics is not None:
            try:
                self.metrics.increment_counter(
                    "app_tpu_pd_ingests_total")
            except Exception:
                pass
        return int(first), float(first_lp)

    def _ensure_scratch(self) -> None:
        """Paged decode workers built without a chunk scratch (short
        max_seq, no prefix index) grow one lazily at the first ingest:
        the dense staging row and the row->blocks program are the same
        machinery long-prompt admission compiles."""
        if hasattr(self, "_scratch"):
            return
        self._prog.describe("scratch", 1)
        self._scratch = self._prog.allocate("scratch")
        self._build_jits(("scratch",))

    def _prefix_restore(self, idx: int, req: _Request, L: int,
                        C: int) -> int:
        """Consult the cache hierarchy; on a useful hit land the prefix
        KV in slot ``idx`` and return the position prefill resumes from
        (0 = no hit). T0 hits are one pool-row copy; T1/T2 hits promote
        through a pool row first (_promote_hostkv). The returned
        position keeps every later dispatch on the compiled lattice:
        chunk STARTS are traced values, only chunk LENGTHS are compile
        keys, so resuming mid-prompt compiles nothing new. At least one
        prompt position is always recomputed — the final chunk ends at
        the prompt end and samples there."""
        if self._kvc is None:
            return 0
        prompt = np.asarray(req.prompt, np.int32)
        t_start = time.monotonic()
        mt = self._kv_match(req, prompt)
        # the memo's job (one verdict for peek AND restore) is done the
        # moment the restore reads it — drop it now, or a T2 match's
        # decoded HostKV (tens of MB at real model dims) stays pinned
        # on the request for the stream's whole lifetime
        req.kv_match = None
        if mt is None:
            self._kvc.reject(prompt=prompt)
            return 0
        # Full-prompt-hit clamp: match() may cover the ENTIRE prompt
        # (exact repeat); restore at most L-1 positions so the final
        # chunk prefills >= 1 token — the dispatch needs logits at the
        # prompt end to sample the first generated token (the pool
        # stores KV, not logits).
        m_eff = self._resume_at(mt, L)
        assert m_eff < L, "kvcache restore clamp violated"
        if not m_eff:
            self._kvc.reject(mt)
            return 0
        if mt.tier == "t0":
            row = mt.row
        else:
            row = self._promote_hostkv(mt)
            if row is None:
                self._kvc.reject(mt)
                return 0
        self.cache = self._run(self._pool_load_jit, self.cache, self._pool,
                               jnp.int32(idx), jnp.int32(row))
        restore_s = time.monotonic() - t_start
        self._kvc.accept(mt, restore_s,
                         tenant=req.tenant if self.tenancy is not None
                         else None)
        req.stream.cache_tier = mt.tier
        req.stream.cache_tokens = m_eff
        if self._tl is not None:
            self._tl.kvcache(mt.tier, m_eff, idx)
        self._obs_span("tpu.prefix-restore", t_start, t_start + restore_s,
                       req.stream, {"tier": mt.tier, "tokens": m_eff,
                                    "slot": idx})
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_tpu_prefix_cache_hits_total")
        return m_eff

    def _prefix_store(self, idx: int, req: _Request) -> None:
        """After a completed admission, remember this prompt's KV row
        (skipped for short prompts and already-covered ones). Must run
        BEFORE the slot's first decode tick — decode writes position L
        into the same row. A T0 victim spills its row to the host tier
        before being overwritten; with the Redis tier on, the fresh
        KV's full blocks write through so sibling replicas skip the
        prefill too."""
        if req.stream.cancelled.is_set() or not self._rewind:
            return  # a state is stored where it was taken: the lattice
        prompt = np.asarray(req.prompt, np.int32)
        t0 = time.monotonic()
        if self._paged:
            if self._prefix_idx is None or len(prompt) < self._store_min \
                    or self._prefix_idx.covered(prompt, req.adapter):
                return
            # zero-copy: reference the slot's full prompt blocks as a
            # SharedPrefixIndex entry — they are immutable from here on
            # (decode only writes the cursor's block). _start calls this
            # AFTER the admit dispatch materialized, so a device-failed
            # prefill can never store an entry over garbage KV.
            self._prefix_idx.store(prompt, self._slot_blocks[idx],
                                   req.adapter)
            if self._tl is not None:
                self._tl.store(t0, time.monotonic(), idx, len(prompt),
                               "paged")
            return
        if self._kvc is None or len(prompt) < self._store_min \
                or self._kvc.covered(prompt, req.adapter):
            return
        row, victim = self._kvc.store(prompt, req.adapter,
                                      tenant=req.tenant
                                      if self.tenancy is not None else None)
        tier = "t0"
        if victim is not None and self._kvc.wants_offload:
            tier += "+host"  # the victim's row is fetched to the host first
        self._offload_victim(victim)
        self._pool = self._run(self._pool_store_jit, self._pool, self.cache,
                               jnp.int32(row), jnp.int32(idx))
        if self.tenancy is not None:
            self._tenant_cache_sync()
        if self._kvc.shares:
            # write-through: a device_get of the slot's fresh KV is the
            # price of warming every replica — but only through the
            # last full block this replica hasn't already shared (an
            # already-written prefix costs no transfer; the trailing
            # partial block has no chain hash and never transfers)
            want = self._kvc.redis.pending_put_len(prompt, req.adapter)
            if want > 0:
                tier += "+shared"
                self._kvc.store_shared(prompt, req.adapter,
                                       self._kv_row_get(self.cache, idx,
                                                        want))
        if self._tl is not None:
            self._tl.store(t0, time.monotonic(), idx, len(prompt), tier)

    def _lattice_snapshot(self, idx: int, req: _Request, pos: int) -> None:
        """Remember slot ``idx``'s memory as it stands at chunk boundary
        ``pos`` of its prompt, before the final chunk runs: the rows of
        the ``pos`` tokens so far and the state taken at exactly that
        position, one pool row under the key ``prompt[:pos]``. The slot
        is parked (no decode step writes it) and the copy queues behind
        the chunks that built it. A prompt of one chunk or less has no
        boundary and is not stored."""
        if self._kvc is None or self._paged or pos <= 0 \
                or req.stream.cancelled.is_set() \
                or len(req.prompt) < self._store_min:
            return
        key = np.asarray(req.prompt[:pos], np.int32)
        if self._kvc.covered(key, req.adapter):
            return
        t0 = time.monotonic()
        row, _ = self._kvc.store(key, req.adapter,
                                 tenant=req.tenant
                                 if self.tenancy is not None else None)
        self._pool = self._run(self._pool_store_jit, self._pool, self.cache,
                               jnp.int32(row), jnp.int32(idx))
        if self.tenancy is not None:
            self._tenant_cache_sync()
        if self._tl is not None:
            self._tl.store(t0, time.monotonic(), idx, pos, "t0")

    def _shed_oom(self, req: _Request, e: "hbm.HBMExhausted") -> None:
        """OOM-shed a popped admission: the arbiter could not cover a
        lease (seeded HBM_ALLOC fault, or a real budget overshoot that
        survived reclaim), so THIS request degrades to a served
        429/RESOURCE_EXHAUSTED with the arbiter's Retry-After while
        the engine keeps serving everything else — the memory-pressure
        mirror of the gate's queue-pressure shed. The arbiter counted
        app_tpu_hbm_shed_total at its raise site; here the failure
        routes through the gate's shed surface (counters + tpu.shed
        span with reason=hbm) and the stream's terminal wide event."""
        retry_after = getattr(e, "retry_after", None) or 1.0
        err: BaseException = e
        if self.gate is not None:
            err = self.gate.shed_memory(
                program="generate", slo_class=req.slo_class,
                retry_after=retry_after, trace_id=req.stream.trace_id)
        else:
            now = time.monotonic()
            self._obs_span("tpu.shed", now, now, req.stream,
                           {"reason": "hbm", "slo_class": req.slo_class})
        if self._tl is not None:
            self._tl.shed("generate", req.slo_class, req.stream.trace_id)
        req.stream.failed = "hbm exhausted: shed"
        req.stream._q.put(err)
        req.stream._q.put(None)
        self._obs_end(req.stream, "shed", tokens=0, error=str(e))

    # -- arbiter reclaim callbacks (registered on the hbm leases) ------------
    def _hbm_pool_reclaim(self, need: int) -> int:
        """Shrink the T0 prefix pool toward the host tier: spill every
        live entry's row to T1 (when configured), drop enough rows to
        cover ``need`` bytes (always keeping one), and reallocate the
        pool at the smaller size. Future hits promote back from T1/T2
        exactly like post-recovery rewarming — the cache gets slower,
        the process survives. Runs under the device lock (reentrant:
        the serving loop may trigger its own shrink via the admission
        checkpoint). Mesh pools shrink the same way — spills are
        per-shard snapshots, the smaller pool re-places onto a FITTED
        sharding (fewer rows may stop dividing the data axes) and the
        pool programs rebuild against it. Returns bytes freed
        (global; the arbiter's per-device pass scales by this lease's
        shard fraction)."""
        with self._device_lock:
            kvc = getattr(self, "_kvc", None)
            pool = getattr(self, "_pool", None)
            if kvc is None or pool is None:
                return 0
            slots = kvc.slots
            if slots <= 1:
                return 0
            total = hbm.tree_nbytes(pool)
            row_b = max(1, total // slots)
            drop = min(slots - 1, -(-max(int(need), 1) // row_b))
            new_slots = slots - drop
            for entry in kvc.t0.entries():
                # the same spill path T0's LRU eviction uses (host-tier
                # guard included) — one convention for moving a pool
                # row down a tier
                self._offload_victim(entry)
            kvc.shrink(new_slots)
            # drop the old buffer BEFORE allocating the replacement:
            # holding both would spike usage past the very budget this
            # reclaim is trying to satisfy
            self._pool = None
            del pool
            try:
                # the pool's description at the new row count, FITTED
                # fresh: the shrunk row count may stop dividing the data
                # axes (replicate instead), and the pool programs must
                # rebuild against whatever the new placement actually is
                self._prog.describe("pool", new_slots,
                                    self._hbm_pool_reclaim)
                self._pool = self._prog.allocate("pool", lease=False)
                self._build_jits(("pool", "offload"))
            except BaseException:
                # even the SMALLER pool failed to allocate (we are, by
                # definition, under memory pressure here). A None pool
                # behind a live CacheManager would AttributeError every
                # later store/promote, so disable the prefix tiers
                # outright — serving continues cache-less, the whole
                # old pool's bytes count as freed, and the arbiter's
                # caller gets the maximum this lease could give
                self._disable_prefix_tiers()
                hbm.release("kvcache-t0", owner=self, tag="pool")
                if self.logger is not None:
                    self.logger.error({
                        "event": "kvcache t0 disabled: arbiter shrink "
                                 "could not reallocate the smaller pool",
                        "slots_attempted": new_slots})
                return total
            if self.logger is not None:
                self.logger.warn({
                    "event": "kvcache t0 shrunk by hbm arbiter reclaim",
                    "slots": new_slots, "dropped_rows": drop,
                    "freed_bytes": drop * row_b})
            return drop * row_b

    def _disable_prefix_tiers(self) -> None:
        """Last-resort degradation: drop the hierarchical prefix cache
        entirely (pool gone, manager detached, its Redis client closed)
        so every cache path sees the same None it sees on engines built
        without one — requests keep serving, they just prefill fully."""
        kvc, self._kvc = self._kvc, None
        self._pool = None
        self._prog.forget("pool")
        self._host_write_jit = None
        if kvc is not None and kvc.redis is not None:
            try:  # the engine owns the T2 client (KVCacheOptions.redis)
                kvc.redis.client.close()
            except Exception:
                pass

    def _hbm_paged_reclaim(self, need: int) -> int:
        """Release ONE cold shared-prefix entry's blocks back to the
        paged pool — the same one-at-a-time valve the in-pool pressure
        paths use (_paged_admission_blocks/_ensure_blocks): flushing
        the whole index would trade every future hit for a reclaim
        that may have needed a single eviction. The pool tensor itself
        is one preallocated buffer, so this frees BLOCK capacity (room
        for live streams to grow / new admissions) rather than HBM
        bytes — it reports 0 toward a byte deficit but still runs
        under pressure so the next block-level allocation finds
        room."""
        del need
        if not self._paged or self._prefix_idx is None:
            return 0
        with self._device_lock:
            if self._prefix_idx.evict_one() and self.logger is not None:
                self.logger.warn({"event": "paged prefix entry evicted "
                                  "by hbm arbiter reclaim"})
        return 0

    def _tenant_cache_sync(self) -> None:
        """Reconcile per-tenant arbiter leases with the cache ledger.
        A tenant holding more T0 rows than its cache-share budget gets
        a zero-byte ``tenant:{id}`` lease at PRI_SCRATCH whose reclaim
        callback evicts THAT tenant's rows — so arbiter pressure asks
        the over-budget tenant to give back its own blocks before the
        PRI_CACHE pool shrink flushes everyone's. Back under budget,
        the lease releases. Zero-byte because the pool's own lease
        already accounts the bytes (the paged-index precedent); this
        lease exists purely for its reclaim ordering."""
        kvc = self._kvc
        if kvc is None or self.tenancy is None:
            return
        try:
            over = set()
            for tid, rows in kvc.tenant_rows().items():
                budget = kvc.tenant_budget(tid)
                if budget is not None and rows > budget:
                    over.add(tid)
            for tid in over - self._tenant_leased:
                hbm.tenant_lease(
                    "kvcache-t0", 0, tenant=tid, owner=self,
                    priority=hbm.PRI_SCRATCH,
                    reclaim=lambda ask, t=tid: self._tenant_cache_evict(t))
                self._tenant_leased.add(tid)
            for tid in self._tenant_leased - over:
                hbm.release("kvcache-t0", owner=self, tag=f"tenant:{tid}")
                self._tenant_leased.discard(tid)
        except Exception:
            pass  # quota leases are best-effort; serving must not stall

    def _tenant_cache_evict(self, tenant: str) -> int:
        """Arbiter reclaim callback for a tenant's cache-quota lease:
        evict the over-budget tenant's own T0 rows (LRU-first, down to
        its budget), spilling each to the host tier exactly like a
        store-path victim — warm state degrades to T1, other tenants'
        rows are untouched. Reports 0 toward a byte deficit (the pool
        lease accounts the bytes) but still frees the contended rows."""
        if self._kvc is None:
            return 0
        with self._device_lock:
            for victim in self._kvc.evict_tenant(tenant):
                self._offload_victim(victim)
        self._tenant_cache_sync()
        return 0

    def _count_expired(self, where: str = "queue",
                       request_id=None) -> None:
        if self._tl is not None:
            self._tl.expired(where, request_id)
        if self.metrics is not None:
            try:
                self.metrics.increment_counter(
                    "app_tpu_expired_dropped_total", program="generate")
            except Exception:
                pass

    def _release_tenant(self, stream: GenStream) -> None:
        """Give back the stream's tenant concurrency-quota slot, exactly
        once, at whatever terminal the stream reaches (finish, failure,
        cancel, early-return error stream)."""
        if not stream._tenant_held:
            return
        stream._tenant_held = False
        if self.tenancy is not None:
            try:
                self.tenancy.release(stream.tenant)
            except Exception:
                pass  # quota bookkeeping must never take the loop down

    # -- flight-recorder plumbing (all no-ops without an Observe bundle) -----
    def _obs_end(self, stream: GenStream, event: str, **fields) -> None:
        """Remove the request's registry entry, record its terminal
        lifecycle event (finished/failed/cancelled), and emit the
        request's canonical WIDE event. Every stream's one terminal
        passes through here, which is what makes it the tenant
        quota-release point."""
        self._release_tenant(stream)
        if self._observe is not None:
            self._observe.requests.remove(stream.obs_entry)
            self._observe.recorder.record(event, request_id=stream.request_id,
                                          trace_id=stream.trace_id, **fields)
            self._stage_spans(stream, fields.get("tokens", 0))
        self._wide_event(stream, event, fields)

    def _stage_spans(self, stream: GenStream, tokens: int) -> None:
        """The request's stage spans (tpu.admit-wait / tpu.prefill /
        tpu.decode), made at its terminal from the stamps the loop took
        once in ``stream.trace`` — and only when somebody exports."""
        tracer = self._observe.tracer
        if tracer is None or tracer.exporter is None:
            return
        t = stream.trace
        now = time.monotonic()
        slot, cls = t.get("slot"), stream.slo_class
        for name, a, b, attrs in (
                ("tpu.admit-wait", t.get("submit"), t.get("admit"),
                 {"slot": slot, "slo_class": cls}),
                ("tpu.prefill", t.get("admit"), t.get("prefill_done"),
                 {"slot": slot, "prompt_len": stream.prompt_len,
                  "slo_class": cls}),
                ("tpu.decode", t.get("first_put") if tokens else None, now,
                 {"slot": slot, "tokens": tokens, "slo_class": cls})):
            if a is not None and b is not None:
                self._obs_span(name, a, b, stream, attrs)

    def _wide_fields(self, outcome: str, trace_id: str,
                     slo_class: str, tenant: str | None = None) -> dict:
        """The canonical wide-event skeleton: key order is part of the
        contract (one grep on ``"event": "request"`` reconstructs any
        request; dashboards and scripts rely on stable field names).
        ``tenant`` appears only on tenancy-enabled engines — events from
        planeless deployments are byte-stable against older tooling."""
        out = {"event": "request", "outcome": outcome,
               "trace_id": trace_id, "slo_class": slo_class}
        if tenant is not None:
            out["tenant"] = tenant
        return out

    def _wide_event(self, stream: GenStream, outcome: str,
                    fields: dict) -> None:
        """One structured event per request at its terminal outcome —
        slo class, queue wait, chunk count, cache tier, tokens, trace
        id — through glog (grep the logs) AND the flight recorder
        (/debug/events survives log rotation)."""
        trace = stream.trace
        submit = trace.get("submit")
        admit = trace.get("admit")
        now = time.monotonic()
        if stream.cursor_base and outcome == "finished":
            # a continuation that ran to completion IS the resumed tail
            # of an interrupted stream — surface it as its own outcome
            # so dashboards can count resumes without joining on fields
            outcome = "resumed"
        wide = self._wide_fields(
            outcome, stream.trace_id, stream.slo_class,
            tenant=stream.tenant if self.tenancy is not None else None)
        wide.update({
            "request_id": stream.request_id,
            "prompt_len": stream.prompt_len,
            "tokens": fields.get("tokens", 0),
            "queue_wait_s": (round(admit - submit, 6)
                             if admit is not None and submit is not None
                             else None),
            "duration_s": fields.get(
                "duration_s",
                round(now - submit, 6) if submit is not None else None),
            "chunks": stream.chunks,
            "cache_tier": stream.cache_tier,
            "cache_tokens": stream.cache_tokens,
        })
        if trace.get("first_put") is not None and submit is not None:
            wide["ttft_s"] = round(trace["first_put"] - submit, 6)
        if stream.cursor_base:
            # durable-streams resume: where the continuation picked up
            # and how much prefix it actually had to recompute (a warm
            # resume covers most of prompt+emitted from T1/T2 and
            # recomputes only the tail)
            wide["resumed_at_cursor"] = stream.cursor_base
            wide["recompute_tokens"] = max(
                0, stream.prompt_len - stream.cache_tokens)
        # critical-path breakdown: the request's life as named segments
        # that SUM to duration_s (each bounded by consecutive trace
        # stamps, so the invariant holds by construction). On a decode
        # worker "prefill" is the ingest install of shipped KV.
        breakdown: dict = {}
        prefill_done = trace.get("prefill_done")
        first_put = trace.get("first_put")
        cuts = [("queue_wait", submit, admit),
                ("prefill", admit, prefill_done),
                ("handoff", prefill_done, first_put),
                ("decode", first_put, now)]
        for seg, a, b in cuts:
            if a is not None and b is not None:
                breakdown[seg + "_s"] = round(max(0.0, b - a), 6)
        if breakdown:
            wide["breakdown"] = breakdown
        # wall-clock anchor for cross-process placement: emission wall
        # time minus the monotonic elapsed puts submit on the wall axis
        # without a second stamp in the hot path
        if submit is not None:
            wide["submit_wall_s"] = round(time.time() - (now - submit), 6)
        if trace.get("kv_transfer_s") is not None:
            # the P/D wire segment — it PRECEDES submit on the decode
            # worker (the assembly exists before generate() is called),
            # so it rides beside the breakdown, not inside it
            wide["kv_transfer_s"] = trace["kv_transfer_s"]
        if self.metrics is not None and breakdown:
            tid = stream.trace_id or None
            for i, (seg_s, v) in enumerate(sorted(breakdown.items())):
                try:
                    self.metrics.record_histogram(
                        "app_tpu_request_segment_duration", v,
                        exemplar=tid if i == 0 else None,
                        segment=seg_s[:-2], program="generate")
                except Exception:
                    pass  # telemetry must never take the serving loop down
        if "error" in fields:
            wide["error"] = fields["error"]
        if stream.where is not None:
            # the deadline-expiry site — "post-handoff" on a decode
            # worker says the budget died AFTER the P/D pool boundary
            wide["where"] = stream.where
        if self._observe is not None:
            self._observe.recorder.record(
                "request", request_id=stream.request_id,
                trace_id=stream.trace_id,
                **{k: v for k, v in wide.items()
                   if k not in ("event", "request_id", "trace_id")})
        if self.logger is not None:
            try:
                self.logger.wide(wide)
            except Exception:
                pass  # telemetry must never take the serving loop down

    def _wide_shed(self, slo_class: str, tenant: str | None = None) -> None:
        """Wide event + timeline marker for a request shed at the gate
        (no stream exists yet; the ambient span is the only trace
        context the request ever had)."""
        trace_id = ""
        if self._observe is not None:
            from .. import tracing

            span = tracing.current_span()
            if span is not None:
                trace_id = span.trace_id
        if self._tl is not None:
            self._tl.shed("generate", slo_class, trace_id)
        wide = self._wide_fields("shed", trace_id, slo_class, tenant=tenant)
        wide["sheds"] = 1
        if self._observe is not None:
            self._observe.recorder.record(
                "request", trace_id=trace_id,
                **{k: v for k, v in wide.items()
                   if k not in ("event", "trace_id")})
        if self.logger is not None:
            try:
                self.logger.wide(wide)
            except Exception:
                pass

    def _obs_stage(self, stream: GenStream, stage: str) -> None:
        if stream.obs_entry is not None:
            stream.obs_entry.stage = stage

    def _obs_span(self, name: str, start_s: float, end_s: float,
                  stream: GenStream, attrs: dict | None = None) -> None:
        """Export one serving span, parented by the request's inbound
        trace context. Without an exporter nothing is built."""
        obs = self._observe
        if obs is None or obs.tracer is None or obs.tracer.exporter is None:
            return
        try:
            obs.tracer.record_span(name, start_s, end_s,
                                   traceparent=stream.traceparent,
                                   trace_id=stream.trace_id or None,
                                   attributes=attrs)
        except Exception:
            pass  # telemetry must never take the serving loop down

    def _record_itl(self, slot: _Slot, n: int) -> None:
        """Record ``n`` inter-token-latency samples for a slot about to
        receive ``n`` tokens from one reaped dispatch: the block interval
        (time since the slot's previous delivery) amortized per token.
        This is the DEVICE cadence a steady-state client observes, not
        the microsecond host-loop gaps within one burst delivery."""
        if self.metrics is None or n <= 0 or slot.last_token_t == 0.0:
            return
        gap = (time.monotonic() - slot.last_token_t) / n
        # one exemplar per reap (first sample): n identical samples
        # land in one bucket, and the OpenMetrics join only needs one
        # trace id per bucket update
        tid = slot.request.stream.trace_id or None if slot.request else None
        for i in range(n):
            self.metrics.record_histogram("app_tpu_inter_token_duration",
                                          gap, exemplar=tid if i == 0 else None,
                                          program="generate")

    def _obs_gauges(self) -> None:
        """Refresh the live-load gauges after admission/retirement."""
        if self.metrics is None:
            return
        self.metrics.set_gauge("app_tpu_active_sequences",
                               float(self._active.sum()))
        self.metrics.set_gauge("app_tpu_queue_depth",
                               float(self._pending.qsize()),
                               program="generate")
        for cls in (SLO_LATENCY, SLO_THROUGHPUT):
            # per-class wait lines alongside the total (distinct label
            # sets are distinct series; dashboards on the unlabeled
            # total keep working)
            self.metrics.set_gauge("app_tpu_queue_depth",
                                   float(self._pending.qsize_class(cls)),
                                   program="generate", slo_class=cls)
        if self.tenancy is not None:
            # per-tenant wait lines; a tenant that drained must zero
            # (not freeze) its gauge, so remember everyone ever seen
            by_tenant = self._pending.qsize_by_tenant()
            self._gauge_tenants.update(by_tenant)
            for tid in self._gauge_tenants:
                self.metrics.set_gauge("app_tpu_queue_depth",
                                       float(by_tenant.get(tid, 0)),
                                       program="generate", tenant=tid)

    def _start(self, idx: int, slot: _Slot, req: _Request,
               blocks: "tuple | None" = None) -> None:
        t0 = time.monotonic()
        # the stage stamps are taken once, here and in _deliver; the wide
        # event, the segment histogram, the flight recorder's request
        # row and the stage spans are all written from them in _obs_end
        req.stream.trace["admit"] = t0
        req.stream.trace["slot"] = idx
        if self.gate is not None:
            self.gate.note_wait(t0 - req.enqueued_at)
        if self._tl is not None:
            self._tl.admit(idx, req.slo_class, t0 - req.enqueued_at,
                           req.stream.request_id, req.stream.trace_id)
        self._obs_stage(req.stream, "prefill")
        # CLAIM the slot before any dispatch: a chunk-lattice admission
        # runs nested admission passes between chunks, and an unclaimed
        # slot (request still None until the old post-prefill
        # assignment) would be handed to a second request mid-lattice
        slot.request = req
        try:
            chaos.fire(chaos.GENERATOR_PREFILL)
            if req.ingest is not None:
                first, first_lp = self._ingest_install(
                    idx, req, blocks[2] if blocks else None)
            elif self._paged:
                shared, m, fresh = blocks
                first, first_lp = self._paged_admit_prefill(
                    idx, req, shared, m, fresh)
            else:
                first, first_lp = self._admit_prefill(idx, req)
        except hbm.HBMExhausted as e:
            # the ingest stage lease (or any admission-path lease)
            # could not be covered: this is MEMORY pressure, served as
            # a 429 shed of THIS request — never loop recovery. The
            # typed error rides the stream back (for P/D requests: over
            # the wire through the prefill worker to the client).
            if self._paged and blocks:
                shared, _, fresh = blocks
                self._slot_blocks[idx] = []
                self._table[idx, :] = 0
                self._cursors[idx] = 0
                self._touch("table")
                self._alloc.free(shared + fresh)
            slot.request = None
            self._shed_oom(req, e)
            self._obs_gauges()
            return
        except BaseException as e:  # noqa: BLE001 — the request is already
            # off the pending queue and owns no slot: fail ITS stream here,
            # then let _loop's handler deal with engine-level fallout.
            if self._paged and blocks:
                # the failed admission may have already installed the
                # slot's blocks/table/cursor (_paged_admit_prefill writes
                # them before the device error surfaces at int(tok)) —
                # clear them BEFORE freeing, or the stale table row would
                # direct this slot's frozen-cursor garbage writes into
                # blocks re-issued to another live stream. The slot holds
                # one reference on shared + fresh alike (taken in _admit
                # / alloc); freeing drops exactly that hold.
                shared, m, fresh = blocks
                self._slot_blocks[idx] = []
                self._table[idx, :] = 0
                self._cursors[idx] = 0
                self._touch("table")
                self._alloc.free(shared + fresh)
            # un-claim BEFORE re-raising: the loop's recovery handler
            # retires every slot holding a request, and this stream is
            # already failed right here — leaving the claim would
            # deliver it a second error and end its registry entry twice
            slot.request = None
            req.stream._q.put(GenerationError(f"prefill failed: {e!r}"))
            req.stream._q.put(None)
            self._obs_end(req.stream, "failed", stage="prefill",
                          error=repr(e))
            raise
        prefill_done = time.monotonic()
        req.stream.trace["prefill_done"] = prefill_done
        if self._tl is not None:
            self._tl.prefill(t0, prefill_done, idx, len(req.prompt),
                             req.stream.request_id, req.stream.trace_id)
        if not self._paged:
            self._cursors[idx] = len(req.prompt)
        if req.kv_sink is not None:
            # prefill-only: ship the tail the chunk hooks haven't sent
            # (the whole row for bucket prompts) BEFORE the first-token
            # delivery — frame order on the wire is the ingest
            # contract. A ship failure cancelled the stream; _deliver
            # retires the slot on that flag below.
            self._ship_range("cache", idx, req, len(req.prompt))
        self._prefix_store(idx, req)
        if self._spec_k:
            self._hist_set(idx, req.prompt)
        if self.metrics is not None:
            self.metrics.record_histogram("app_tpu_batch_wait_duration",
                                          t0 - req.enqueued_at, program="generate")
        slot.generated = 0
        # prefill-only requests deliver exactly the sampled first token
        # and retire — the DECODE pool owns the rest of the budget
        slot.remaining = 1 if req.kv_sink is not None else req.max_new
        self.total_requests += 1
        self._temps[idx] = req.temperature
        self._top_ks[idx] = req.top_k
        self._slot_seed[idx] = req.seed
        self._touch("temps", "top_ks", "seeds")
        if self._spec_k:
            self._hist_append(idx, int(first))
        if first is None:
            # the prefill yielded no token (_first_token): the slot's
            # first block opens with the prompt's last tokens given
            n = len(req.given)
            self._given[idx] = 0
            self._given[idx, :n] = req.given
            self._given_n[idx] = n
            first = 0
            if req.stream.cancelled.is_set():
                self._retire(idx, slot)
        else:
            self._deliver(idx, slot, first, first_lp)
        if slot.request is not None:  # not finished by the first token
            self._last_tokens[idx] = first
            self._active[idx] = True
            # device-side stop state: the budget mirrors slot.remaining
            # (tokens still allowed after the prefill's first one); the
            # EOS row arms the in-scan stop set. host_wins forces all
            # of it over whatever the device carry held for this slot.
            self._budgets[idx] = slot.remaining
            self._eos_row(idx, req.eos_id)
            if self._paged:
                # where the device's budget/capacity stop masks will
                # freeze this slot's cursor (EOS may stop earlier —
                # the over-advance is bounded by one reap)
                self._stop_cursors[idx] = min(
                    req.stream.prompt_len + slot.remaining,
                    self.max_seq - 2)
            # the slot's next sample sits at absolute position
            # pos_base + delivered-so-far (the prefill's first token
            # consumed pos_base itself)
            self._pos_abs[idx] = req.pos_base + slot.generated
            self._host_wins[idx] = True
            self._touch("active", "last_tokens", "host_wins", "budgets",
                        "eos", "pos")
        self._obs_gauges()

    def _eos_row(self, idx: int, eos_id) -> None:
        """Arm slot ``idx``'s on-device EOS stop set. Sets wider than
        EOS_MAX fall back to host-only retirement (the extra ids simply
        never match on device; the stream stays exact, the slot just
        burns junk steps until the reap notices)."""
        row = self._eos_mat[idx]
        row[:] = llama.EOS_PAD
        if eos_id is None:
            return
        ids = (eos_id,) if isinstance(eos_id, int) else tuple(eos_id)
        for j, t in zip(range(self.EOS_MAX), ids):
            row[j] = t

    def _deliver(self, idx: int, slot: _Slot, token: int,
                 lp: float | None = None) -> None:
        """Push one token to the consumer; retire the slot when finished."""
        req = slot.request
        if req.stream.cancelled.is_set():
            self._retire(idx, slot)
            return
        now = time.monotonic()
        if slot.generated == 0:  # first token: prefill_done -> first_put
            # is the prefix-store cost (a device row copy when an entry
            # is stored) — attributed separately from delivery wake-up
            req.stream.trace["first_put"] = now
            ttft = now - req.stream.trace["submit"]
            if self.metrics is not None:
                # the exemplar makes a dashboard's p99 TTFT bucket
                # resolve to the exact trace that populated it; the
                # label-key set is ONE set whether or not tenancy is
                # on (tenant="" = untenanted) so the series never
                # splits on deployment mode
                self.metrics.record_histogram(
                    "app_tpu_ttft_duration", ttft,
                    exemplar=req.stream.trace_id or None,
                    program="generate", slo_class=req.slo_class,
                    tenant=(req.tenant or ""
                            if self.tenancy is not None else ""))
            self._obs_stage(req.stream, "decode")
        # inter-token latency is recorded at the REAP level (_record_itl),
        # not here: a fused decode block delivers its K tokens back-to-back
        # in one host loop, and per-delivery gaps would report microsecond
        # burst artifacts instead of device cadence
        slot.last_token_t = now
        # _push: straight into a registered transport sink (zero-handoff
        # delivery — bytes leave on THIS thread, nonblocking) or the
        # stream queue for iterator consumers
        req.stream._push((token, lp) if req.logprobs else token)
        slot.generated += 1
        slot.remaining -= 1
        self.total_tokens += 1
        try:
            # durable-streams chaos seam: a seeded GENERATOR_MIDKILL
            # (every=N, limit=1) kills THIS stream after exactly N
            # delivered tokens — the in-process stand-in for a replica
            # SIGKILL mid-stream, replayable by digest. Only the one
            # stream dies (typed error + retire); the engine keeps
            # serving, exactly like a per-request failure.
            chaos.fire(chaos.GENERATOR_MIDKILL)
        except BaseException as e:  # noqa: BLE001 — per-request failure
            req.stream.failed = (f"chaos mid-stream kill after "
                                 f"{slot.generated} tokens")
            req.stream._q.put(GenerationError(
                f"mid-stream kill after {slot.generated} tokens: {e!r}"))
            self._retire(idx, slot)
            return
        if req.stream.obs_entry is not None:
            req.stream.obs_entry.tokens = slot.generated
        if self.metrics is not None:
            self.metrics.increment_counter("app_tpu_tokens_generated_total")
        at_eos = req.eos_id is not None and (
            token in req.eos_id if isinstance(req.eos_id, frozenset)
            else token == req.eos_id)
        # cursor positions used so far: prompt_len + generated
        at_capacity = req.stream.prompt_len + slot.generated >= self.max_seq - 1
        if at_eos or slot.remaining <= 0 or at_capacity:
            self._retire(idx, slot)

    def _retire(self, idx: int, slot: _Slot) -> None:
        stream = slot.request.stream
        now = time.monotonic()
        first = stream.trace.get("first_put")
        decode_s = (now - first) if first is not None else 0.0
        tps = slot.generated / decode_s if decode_s > 0 else 0.0
        if self.metrics is not None and slot.generated > 1:
            # throughput needs at least one inter-token interval
            self.metrics.set_gauge("app_tpu_tokens_per_second", tps,
                                   program="generate")
        event = ("failed" if stream.failed is not None
                 else "cancelled" if stream.cancelled.is_set()
                 else "finished")
        fields = {"slot": idx, "tokens": slot.generated,
                  "duration_s": round(now - stream.trace["submit"], 6),
                  # throughput needs at least one inter-token interval —
                  # a 1-token stream's first_put->retire gap is
                  # microseconds and would report ~1e6 tok/s
                  "tokens_per_s": (round(tps, 3)
                                   if slot.generated > 1 else None)}
        if stream.failed is not None:
            fields["error"] = stream.failed
        self._obs_end(stream, event, **fields)
        slot.request.stream._push(None)
        slot.request = None
        self._active[idx] = False
        self._temps[idx] = 0.0
        self._top_ks[idx] = 0
        self._slot_adapter[idx] = 0
        self._budgets[idx] = 0
        self._slot_seed[idx] = 0
        self._pos_abs[idx] = 0
        self._eos_mat[idx, :] = llama.EOS_PAD
        # host wins the next dispatch's merge for this slot: a host-only
        # retirement (cancel, deadline, paged starvation) deactivates a
        # slot the device carry still believes is live — without this
        # an already-pipelined block would be the LAST junk it emits,
        # but the carry would keep it running forever
        self._host_wins[idx] = True
        self._touch("active", "temps", "top_ks", "adapters", "budgets",
                    "eos", "host_wins")
        if self._paged:
            # freed blocks may be re-issued immediately; the retired
            # slot's frozen-cursor garbage writes go to the trash block
            # because its table row zeroes BEFORE the next dispatch
            if self._slot_blocks[idx]:
                self._alloc.free(self._slot_blocks[idx])
                self._slot_blocks[idx] = []
            self._table[idx, :] = 0
            self._stop_cursors[idx] = 0
            self._touch("table")
        self._cursors[idx] = 0
        self._obs_gauges()

    def _loop(self) -> None:
        # the decode dispatch pipeline: oldest-first deque of in-flight
        # fused blocks. Depth 1 reproduces the old dispatch->overlap->
        # reap loop exactly; at depth 2 the loop keeps a SECOND block
        # queued on the device stream while reaping the first, so the
        # host-side reap/delivery/admission work overlaps device compute
        # instead of idling it (PERF_LEDGER.jsonl, PR 29: a full batch
        # kept at depth 2 gave 7.8 to 23.0% more out_tok_s than at 1).
        # The invariant: this thread never does host work, and never
        # blocks on the device, with nothing queued behind the program
        # it waits for, unless the depth policy says a waiting
        # request's first token needs it AND that request can be
        # started (a free slot). A full batch with a standing queue
        # therefore runs at depth 2.
        pipe = self._pipe
        while not self._closed:
            try:
                if pipe or self._active.any() or not self._pending.empty():
                    # whatever is not inside one of the loop's named
                    # phases — this glue, a wait for the device lock —
                    # is "other", and has to stay near zero
                    self._acct.phase("other")
                    with self._device_lock:
                        if not pipe:
                            # synchronous admission pass — the only one
                            # allowed to run a chunk lattice (its
                            # interleaved decode blocks need a fully
                            # reaped loop)
                            self._lattice_deferred = False
                            self._admit()
                        chaos.fire(chaos.GENERATOR_STEP)
                        depth = self._target_depth()
                        while len(pipe) < depth:
                            inflight = self._tick(decode_only=bool(pipe))
                            if inflight is None:
                                break
                            pipe.append(inflight)
                        self._note_depth(len(pipe))
                    if not pipe:
                        continue
                    # serve admissions WHILE the oldest block runs on
                    # device, then fetch its results — see
                    # _admit_inflight for why this is the TTFT fix
                    self._admit_inflight(pipe[0])
                    with self._device_lock:
                        inflight = pipe.popleft()
                        self._reap(inflight)
                else:
                    self._acct.phase("park")
                    self._work.wait(timeout=0.05)
                    self._work.clear()
            except BaseException as e:  # noqa: BLE001 — waiters must not hang
                if not self._recover(e):
                    return

    def _recover(self, e: BaseException) -> bool:
        """The loop failed with ``e``: fail the live streams, reallocate
        what the device held, and say whether the loop may go on (False:
        the engine is closed, or down).

        A failed prefill/step may have consumed the DONATED cache
        buffer; continuing would serve every later request an opaque
        "donated buffer" error. Recovery runs in three phases, ordered
        so consumers neither observe stale state NOR hang behind device
        work:
          1. host-side invariants (mirrors, PRNG epoch, prefix index) —
             pure Python, cannot hang;
          2. error delivery — waiters fail fast with every
             host-observable invariant already consistent;
          3. device reallocation — may block indefinitely on a WEDGED
             device, which is exactly why it runs after delivery. No
             admission can race it: only this loop thread admits, and
             it is here."""
        # unwind EVERY in-flight dispatch first: their output futures
        # (and the donated cache chained through them) died with the
        # failure — reaping one would only re-raise the same error;
        # recovery below reseeds ONCE for however many dispatches were
        # in flight
        self._pipe.clear()
        self._acct.phase("other")  # the recovery path
        if self._closed:
            return False
        if self.logger is not None:
            self.logger.error({"event": "generation loop failed",
                               "error": repr(e)})
        err = GenerationError(f"generation failed: {e!r}")
        with self._device_lock:
            # device-mirror buffers may have died with the failed
            # dispatch — rebuild them all on next use
            self._mirror.clear()
            self._pack = None
            self._pack_dirty = True
            self._last_dev = None
            self._acct.reset()
            self._host_wins[:] = True
            self._recoveries += 1
            if self._prefix_idx is not None:
                # paged entries reference blocks of the OLD pool and
                # would restore all-zero KV on a hit
                self._prefix_idx.clear()
            if self._kvc is not None:
                # tiered recovery: T0 entries die with the pool (they'd
                # match prompts against fresh zeroed rows), but T1 host
                # snapshots and T2 shared blocks are device-independent
                # and SURVIVE — the next admission rewarns the new pool
                # from them instead of paying a full prefill
                self._kvc.clear_device()
        # under the device lock: _retire mutates _active/_table/
        # _cursors, and warmup()/swap_adapter() on OTHER threads hold
        # the lock while reading slot state — an unlocked retire here
        # could free a slot mid-warmup-prefill
        with self._device_lock:
            for idx, slot in enumerate(self._slots):
                if slot.request is not None:
                    slot.request.stream.failed = repr(e)
                    slot.request.stream._q.put(err)
                    self._retire(idx, slot)
        try:
            with self._device_lock:
                if self.mesh is not None:
                    # warm device-loss re-placement: rebuild the mesh
                    # over live devices, re-place params, recompute
                    # shardings, rebuild the compiled surface — the
                    # reallocations below then land placed on the NEW
                    # mesh and re-settle the same per-shard lease keys
                    self._replace_mesh()
                # the PRNG key chains THROUGH dispatches now: an async
                # failure leaves self._key bound to the failed
                # computation's error-state output, and every later
                # program would consume it and re-raise forever —
                # reseed from the host, salted so recoveries don't
                # replay the stream
                self._key = self._prog.key(self._seed + self._recoveries)
                # every live buffer again, not the serving cache alone:
                # the pool's store program donates the pool (a failed
                # store leaves it consumed/poisoned), and the chunk
                # programs donate the scratch row (a failed chunk
                # dispatch would brick every later long-prompt
                # admission). Each re-leases and re-accounts under its
                # own key, and the arbiter's reclaim-then-retry covers a
                # recovery that lands while HBM is contended
                for tag in self._prog.buffers:
                    setattr(self, self._BUFFER_ATTR[tag],
                            self._prog.allocate(tag))
            if self.logger is not None:
                self.logger.warn({"event": "generation cache "
                                  "reallocated after device failure"})
        except BaseException as e2:  # noqa: BLE001
            self.down = f"cache reallocation failed: {e2!r} " \
                        f"(after: {e!r})"
            if self.logger is not None:
                self.logger.error({"event": "generation engine down",
                                   "error": self.down})
        if self.down is None:
            return True
        # fail queued requests too — their consumers block on the
        # stream and no later iteration will admit them
        down_err = GenerationError(
            f"generation engine is down: {self.down}")
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            req.stream._q.put(down_err)
            req.stream._q.put(None)
            self._obs_end(req.stream, "failed", error=self.down)
        return False

    def _admit_inflight(self, inflight: _Inflight) -> None:
        """Admit new arrivals while a dispatched tick executes on device.

        Dispatches are async: until the tick's outputs are ready, the
        old loop sat in device_get, which serialized
        (delivery + admission + prefill dispatch) AFTER the block, so a
        request arriving mid-block paid up to a whole extra block of
        TTFT (the r3 gRPC gap). Here the loop thread instead waits on
        the submit event and runs admissions NOW: the new request's
        prefill queues on the device stream right behind the in-flight
        block, making its first token cost (remaining block + prefill)
        — the hardware floor. Behind a full batch at depth 2 that
        block is the SECOND one queued: the slot a waiter gets was seen
        free at the reap of the block before it (the depth policy then
        answers 1 and nothing more is queued), so the refill comes one
        block later than at depth 1, and with the block _first_token
        queues behind the prefill the refilled slot decodes from the
        block after that. Readiness is polled via jax.Array
        .is_ready(); if the probe is unsupported the reap just blocks
        like the old loop. The deadline bounds the poll so a wedged
        device surfaces its error through the blocking reap rather than
        a silent spin."""
        deadline = time.monotonic() + 60.0
        poll = self._admit_window or 1e-3
        prev = self._acct.phase("wait")
        try:
            while not self._closed:
                now = time.monotonic()
                if now >= deadline:
                    return
                try:
                    # the last program queued may be a prefill behind
                    # this block: the stream is dry when THAT is done
                    self._acct.probe(now)
                    if all(a.is_ready() for a in inflight.arrays):
                        return
                except Exception:  # no readiness probe on this backend
                    return
                started = 0
                if not self._pending.empty():
                    with self._device_lock:
                        started = self._admit()
                if started:
                    continue  # more may be queued behind the ones admitted
                # nothing admitted (queue empty, no free slot, pool
                # pressure, or a lattice request deferred to the reap):
                # WAIT — looping straight back would busy-spin on the GIL
                # and the device lock for the whole block, starving the
                # very submitter/consumer threads this loop exists to serve
                self._work.clear()
                self._work.wait(poll)
        finally:
            self._acct.phase(prev)

    def _reap(self, inflight: _Inflight) -> None:
        """Fetch a dispatched tick's results and deliver them: the
        loop's ``fetch`` phase up to the device->host copy's return,
        ``deliver`` from there (the reap itself moves the phase on).
        ``inflight`` is off the pipe already: a block still on it is
        queued behind this one, so the stream cannot run dry behind
        this reap (the chunk lattice's synchronous reaps have none)."""
        self._reaps += 1
        if self._pipe:
            self._overlapped_reaps += 1
        prev = self._acct.phase("fetch")
        try:
            inflight.reap()
        finally:
            self._acct.phase(prev)

    def _run(self, fn, *args):
        """Dispatch one compiled program from the loop thread. Every
        program goes through here, so that a dry interval ends at the
        next dispatch of any kind and the dry probe always asks about
        the last program queued."""
        acct = self._acct
        if acct.idle_from is None:
            acct.probe(time.monotonic())  # dry already, and nobody saw?
        out = fn(*args)
        # the device cannot start before the call has queued the program
        acct.dispatch(time.monotonic(), out)
        return out

    def _target_depth(self) -> int:
        """Pipeline depth for the next top-up — the engine-side facts
        feeding resilience.DecodePipelinePolicy. Also surfaced by
        stats() so tests and dashboards see the same verdict the loop
        acts on."""
        return self._pipeline.target(
            latency_admittable=self._latency_admittable(),
            lattice_deferred=self._lattice_deferred,
            spec_decode=bool(self._spec_k))

    def _latency_admittable(self) -> bool:
        """A latency-class request is waiting and a slot is free for it
        (the two conditions _admit tests before it does anything): its
        prefill can be dispatched now, so a second queued block would
        stand in front of its first token."""
        return (self._pending.qsize_class(SLO_LATENCY) > 0
                and any(s.free for s in self._slots))

    def _note_depth(self, depth: int) -> None:
        if depth == self._depth_now:
            return
        self._depth_now = depth
        if self.metrics is not None:
            self.metrics.set_gauge("app_tpu_pipeline_depth", float(depth),
                                   program="generate")

    def _tick(self, decode_only: bool = False) -> "_Inflight | None":
        """Dispatch one serving tick: a speculative verify pass when the
        engine can use one (spec enabled, every active slot greedy and
        clear of capacity, at least one slot has a draft), else a decode
        block. Returns the in-flight handle (reap delivers) or None.
        ``decode_only``: a pipeline top-up behind an un-reaped block —
        verify windows are built from host-delivered history, which
        does not exist yet (the depth policy already pins spec engines
        to depth 1; this is the structural guard) — or the chunk
        lattice's decode block between two chunks. The loop's
        ``dispatch`` phase, from entry to the jitted call's return."""
        prev = self._acct.phase("dispatch")
        try:
            if not decode_only and self._spec_k and self._spec_eligible():
                drafts = {idx: self._draft(idx)
                          for idx in range(self.n_slots) if self._active[idx]}
                drafted = sum(d is not None for d in drafts.values())
                # Coverage gate: slots WITHOUT drafts emit 1 token per
                # verify pass vs decode_block per decode dispatch — one
                # repetitive stream must not drag a batch of
                # non-repetitive ones into K-times-slower cadence. Verify
                # only when at least half the active slots would
                # actually speculate.
                if drafted > 0 and 2 * drafted >= len(drafts):
                    return self._verify_tick(drafts)
            return self._decode_tick()
        finally:
            self._acct.phase(prev)

    def _spec_eligible(self) -> bool:
        W = self._spec_k + 1
        saw_active = False
        for idx, slot in enumerate(self._slots):
            if not self._active[idx]:
                continue
            req = slot.request
            if req is None or req.temperature > 0:
                return False  # sampling slots need the decode sampler
            if req.stream.prompt_len + slot.generated + W > self.max_seq:
                return False  # would scatter past capacity (llama.
                # verify_step's capacity contract) — the slot retires soon
            saw_active = True
        return saw_active

    def _verify_tick(self, drafts: dict) -> "_Inflight | None":
        """Dispatch one verify pass: window = [last_token, K drafts] per
        slot (zero drafts for slots with no lookup match — they still
        emit their 1 guaranteed token). The reap mirrors _decode_tick's:
        emitted tokens stream in order, retirement mid-window discards
        the rest."""
        W = self._spec_k + 1
        window = np.zeros((self.n_slots, W), np.int32)
        window[:, 0] = self._last_tokens
        for idx, d in drafts.items():
            if d is not None:
                window[idx, 1:] = d
        # the verify pass is greedy-only: the key argument is unused, so
        # pass the live key as-is — no split dispatch, no chain needed
        if self._paged:
            self._ensure_blocks(W)  # window rows span up to W positions
            if not self._active.any():
                return None
            toks, lps, emit, self.cache = self._run(
                self._verify_jit,
                self.cache, self.params, jnp.asarray(window),
                self._dev("active", self._active), self._key,
                self._dev("table", self._table), self._adapters())
        else:
            toks, lps, emit, self.cache = self._run(
                self._verify_jit,
                self.cache, self.params, jnp.asarray(window),
                self._dev("active", self._active), self._key,
                self._adapters())
        # Dispatch-time snapshots: in-flight admissions mutate _active /
        # slot.request before the reap runs, and this window's tokens
        # belong to the slots AS DISPATCHED — a slot that retired and
        # was re-admitted mid-flight must not receive them.
        snap_active = self._active.copy()
        snap_reqs = [s.request for s in self._slots]
        t_dispatch = time.monotonic()
        return _Inflight((toks, lps, emit), functools.partial(
            self._verify_reap, toks, lps, emit, snap_active, snap_reqs,
            t_dispatch), "verify", t_dispatch)

    # invoked through _Inflight.reap, always under the engine's device
    # lock (see _loop) — the partial hides that from static call-graph
    # inference  # gl: holds self._device_lock
    def _verify_reap(self, toks, lps, emit, snap_active, snap_reqs,
                     t0: float = 0.0) -> None:
        toks_np, lps_np, emit_np = jax.device_get((toks, lps, emit))
        self._acct.phase("deliver")
        if self._tl is not None:
            self._tl.verify_block(
                t0, time.monotonic(),
                tuple(int(i) for i in np.flatnonzero(snap_active)),
                self._spec_k + 1)
        self._spec_windows += int(snap_active.sum())
        self._spec_emitted += int(emit_np.sum())
        emit_l = emit_np.tolist()
        # device cursors advanced by emit (accepted tokens only; zero
        # for slots outside the dispatch mask, so in-flight admissions
        # — cursor set by their own prefill — are safe)
        for idx in range(self.n_slots):
            self._cursors[idx] += emit_l[idx]
        toks_l, lps_l = toks_np.tolist(), lps_np.tolist()
        with burst():  # a stream's accepted tokens leave in one write
            for idx, slot in enumerate(self._slots):
                if not snap_active[idx] \
                        or slot.request is not snap_reqs[idx]:
                    continue
                if self._expire_decoding(idx, slot):
                    continue
                self._record_itl(slot, emit_l[idx])
                for k in range(emit_l[idx]):
                    if not self._active[idx]:
                        break  # retired mid-window (EOS/budget/cancel)
                    t = toks_l[idx][k]
                    self._last_tokens[idx] = t
                    self._hist_append(idx, t)
                    self._deliver(idx, slot, t, lps_l[idx][k])
        # a verify pass advanced host state outside the decode carry
        # chain: host wins the next decode dispatch's merge, so sync
        # the budget mirror to what the deliveries left behind
        for idx in np.flatnonzero(snap_active):
            s = self._slots[idx]
            self._budgets[idx] = s.remaining if s.request is not None else 0
            # absolute sampling position mirrors the delivered count
            # (verify passes are greedy, but the mirror must stay true
            # for the next decode dispatch's host_wins merge)
            self._pos_abs[idx] = (s.request.pos_base + s.generated
                                  if s.request is not None else 0)
            if self._paged:
                self._stop_cursors[idx] = (
                    min(int(self._cursors[idx]) + s.remaining,
                        self.max_seq - 2)
                    if s.request is not None else 0)
        self._host_wins |= snap_active
        self._touch("last_tokens", "host_wins", "budgets", "pos")

    def _decode_tick(self) -> "_Inflight | None":
        """Dispatch one fused decode block; the reap fetches [K, B]
        tokens + the emitted mask and delivers in step order. A slot
        that finishes (EOS/budget/capacity) at step k self-deactivates
        ON DEVICE (llama.decode_stop_mask in the scan carry), so the
        waste of an already-finished stream is bounded within ONE block
        even when a second block was dispatched before this one's
        tokens reached the host (pipeline depth 2)."""
        if not self._active.any():
            return None
        if self._paged:
            self._ensure_blocks()  # may retire starving slots
            if not self._active.any():
                return None
        if self._last_dev is None:  # first block / post-recovery:
            # no previous dispatch to chain from — build the slot-state
            # carry from the host arrays
            self._last_dev = self._host_carry()
        # KV positions the block's attention has to read, as dispatched,
        # and those it fetches: each active cursor rounded up to the
        # kernel's block, or all that the slots reserve
        cursors = self._cursors[self._active]
        live = int(cursors.sum())
        # of a window layer's rings: each cursor cut to the window
        ring = int(np.minimum(cursors, self._ring_rows).sum()) \
            if self._ring_rows else None
        bs = self._kv_block
        fetched = (None if self._paged
                   else int((-(-cursors // bs) * bs).sum()) if bs
                   else self.n_slots * self.max_seq)
        t_dispatch = time.monotonic()
        sampled = self._sampling_flag()
        pack = self._dispatch_pack()
        toks, lps, emitted, self._last_dev, self._key, self.cache, \
            counters = self._run(self._step_jit, self.cache, self.params,
                                 pack, self._last_dev, self._key)
        if not self._paged:
            # (a block family's cursors move at the reap, by as many
            # whole blocks as it finds committed)
            if not self._dblock:
                self._cursors[self._active] += self.decode_block
        else:
            # advance bounded by each slot's device stop cursor: the
            # scan freezes a slot there (budget/capacity), so the host
            # view must not run past it while un-reaped blocks pile up
            # behind the pipeline. EOS stops land wherever they land —
            # that over-advance is bounded by one reap.
            adv = np.minimum(
                self.decode_block,
                np.maximum(self._stop_cursors - self._cursors, 0))
            adv = np.where(self._stop_cursors > 0, adv, self.decode_block)
            self._cursors[self._active] += adv[self._active]
        if self._host_wins.any():
            self._host_wins[:] = False
            self._touch("host_wins")
        # snapshots: see _verify_tick — this block's tokens belong to
        # the slots as dispatched, not as mutated by in-flight admissions
        snap_active = self._active.copy()
        snap_reqs = [s.request for s in self._slots]
        return _Inflight((toks, lps, emitted), functools.partial(
            self._decode_reap, toks, lps, emitted, snap_active, snap_reqs,
            t_dispatch, live, fetched, counters, ring, sampled),
            "decode", t_dispatch)

    # invoked through _Inflight.reap, always under the engine's device
    # lock (see _loop)  # gl: holds self._device_lock
    def _decode_reap(self, toks, lps, emitted, snap_active, snap_reqs,
                     t0: float = 0.0, live: int | None = None,
                     fetched: int | None = None, counters=(),
                     ring: int | None = None,
                     sampled: int | None = None) -> None:
        # one fetch: what the family's step counted rides with the tokens
        toks_np, lps_np, emit_np, counters = jax.device_get(
            (toks, lps, emitted, counters))
        self._acct.phase("deliver")
        # an expert layer's assignments [steps, routed layers, held
        # experts]: how many the block made, and how many (step, layer,
        # expert) cells got at least one (each is an expert's weights read)
        moe = counters[0] if counters else None
        assigned = None if moe is None else int(moe.sum())
        touched = None if moe is None else int(np.count_nonzero(moe))
        # a family with recurrent layers: the (layer, slot) states the
        # block's steps updated in place (each is read and written once)
        states = int(counters[1].sum()) \
            if len(counters) > 1 and counters[1] is not None else None
        # a family whose full layers select the rows they read: the rows
        # the block's steps kept and the rows they chose among, over
        # those layers and the active slots
        kept = tuple(int(n) for n in counters[2].reshape(-1, 2).sum(0)) \
            if len(counters) > 2 and counters[2] is not None else None
        # a family whose step is a pass over a block: the slot-passes
        # that denoised and that committed, the positions committed and
        # the tokens emitted, over the dispatch's passes
        passes = tuple(int(n) for n in counters[3].sum(0)) \
            if len(counters) > 3 else None
        if self._tl is not None:
            # one ring event per fused block, fanned out to per-slot
            # slices only at export time — the hot path pays one append
            self._tl.decode_block(
                t0, time.monotonic(),
                tuple(int(i) for i in np.flatnonzero(snap_active)),
                self.decode_block, live, fetched, assigned, touched, states,
                ring, sampled or None, kept,
                None if passes is None else (
                    passes[0] + passes[1], passes[3],
                    passes[1] * self._dblock))
        if passes is not None:
            for name, n in zip(self._diff_n, passes):
                self._diff_n[name] += n
            if self.metrics is not None:
                inc = self.metrics.increment_counter
                inc("app_tpu_diffusion_passes_total",
                    by=passes[0] + passes[1])
                inc("app_tpu_diffusion_tokens_total", by=passes[3])
        if self._loop_steps:
            self._loop_tokens += int(emit_np.sum())
        if self._kv_token_bytes and live is not None \
                and self.metrics is not None:
            self.metrics.set_gauge("app_tpu_kv_live_bytes",
                                   float(live * self._kv_token_bytes))
        if ring is not None:
            self._ring_live = ring
            if self.metrics is not None:
                self.metrics.set_gauge("app_tpu_kv_window_live_bytes",
                                       float(ring * self._ring_row_bytes))
        if states is not None and self.metrics is not None:
            self.metrics.set_gauge(
                "app_tpu_state_live_bytes",
                float(snap_active.sum()) * self._state_bytes)
        if moe is not None:
            self._moe_assigned += assigned
            self._moe_touched += touched
            self._moe_cells += moe.size
            if self.metrics is not None:
                self.metrics.delta_updown_counter(
                    "app_tpu_moe_expert_tokens", float(assigned))
                self.metrics.set_gauge("app_tpu_moe_experts_idle_ratio",
                                       1.0 - touched / moe.size)
        if self.metrics is not None:
            self.metrics.set_gauge("app_tpu_batch_fill",
                                   float(self._active.sum()) / self.n_slots,
                                   program="generate")
            if fetched is not None:
                self.metrics.set_gauge(
                    "app_tpu_decode_kv_read_ratio",
                    fetched / (self.n_slots * self.max_seq))
        # bulk-convert once: per-element int()/float() on numpy scalars
        # costs real milliseconds per reap at high slot counts
        toks_l, lps_l = toks_np.tolist(), lps_np.tolist()
        emit_l = emit_np.tolist()
        counts = emit_np.sum(axis=0)  # real tokens per slot this block
        if self._dblock:
            # a pass that emitted for a slot committed its block: the
            # slot's cursor moved by one
            W = self._dblock
            commits = emit_np.reshape(-1, W, self.n_slots).any(1).sum(0)
        for idx, slot in enumerate(self._slots):
            if snap_active[idx] and self._active[idx] \
                    and slot.request is snap_reqs[idx]:
                if self._dblock:
                    self._cursors[idx] += W * int(commits[idx])
                if self._expire_decoding(idx, slot):
                    continue
                if counts[idx]:
                    self._record_itl(slot, int(counts[idx]))
        # one burst a block: a stream's K tokens leave in one write at
        # its end, not in K (wire.burst)
        with burst():
            for k in range(len(toks_l)):
                trow, lrow, erow = toks_l[k], lps_l[k], emit_l[k]
                for idx, slot in enumerate(self._slots):
                    if not snap_active[idx] or not self._active[idx] \
                            or slot.request is not snap_reqs[idx] \
                            or not erow[idx]:
                        # the emitted mask replays the device stop masks:
                        # tokens a self-deactivated slot carried (frozen
                        # repeats) are never delivered, keeping the stream
                        # identical to host-side retirement
                        continue
                    self._last_tokens[idx] = trow[idx]
                    if self._spec_k:
                        self._hist_append(idx, trow[idx])
                    self._deliver(idx, slot, trow[idx], lrow[idx])
