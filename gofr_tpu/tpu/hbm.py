"""Device-memory arbiter: one HBM budget every subsystem leases from.

Why this exists: the flat decode path ran healthy on a chip while the
prefix-cache, engine and speculative configurations died with
RESOURCE_EXHAUSTED and the paged engine ran out of memory at every
batch size — each subsystem allocated HBM assuming it owned the whole
device, and the first one to be wrong killed the process.
This module is the arbitration point that makes the subsystems
coexist: ONE budget, leased out per subsystem, with demand-driven
reclaim and a shed path so an allocation failure degrades the
*request* instead of killing the *process*.

Three layers, lowest first:

  **Accounting** (the PR-6 substrate, unchanged contract): every
  persistent device buffer is declared via :func:`account`, keyed
  ``(subsystem, owner, tag)`` with SET semantics (recovery realloc /
  mesh re-placement replace instead of double-count); gofrlint GL202
  enforces the discipline statically and ``pytest --hbmwatch``
  reconciles declared bytes against ``jax.live_arrays()`` ground
  truth. ``release(owner=self)`` in ``close()``; a weakref finalizer
  backstops owners that die without it (it only notes the owner: the
  next call drops the entries, ``_Registry._reap``).

  **Leases** (the arbiter): :func:`lease` reserves bytes against the
  budget BEFORE an allocation, tagged with a priority class —
  ``PRI_SERVING`` (live serving state, never auto-reclaimed),
  ``PRI_CACHE`` (performance caches that shrink toward lower tiers),
  ``PRI_SCRATCH`` (workspace, dropped first) — and an optional
  **reclaim callback** ``(need_bytes) -> freed_bytes``. When a lease
  would exceed the budget the arbiter runs reclaim over the registered
  callbacks (highest priority class first: scratch, then caches), then
  re-checks; if the deficit survives it raises :class:`HBMExhausted`.
  :func:`alloc` is the one-call form serving code uses: lease (sized
  by ``jax.eval_shape`` when a budget is set), run the allocation
  thunk, catch a REAL device OOM (``XlaRuntimeError`` /
  RESOURCE_EXHAUSTED — :func:`is_oom_error`), reclaim, retry ONCE,
  and account the result. :func:`check` is the zero-byte request-path
  checkpoint the generation admission loop calls per admission.

  **Shed** (the degradation contract): :class:`HBMExhausted` IS a
  ``TooManyRequests`` — it carries ``Retry-After`` and maps to
  429/RESOURCE_EXHAUSTED at both transports, so an uncoverable lease
  routes through the existing AdmissionGate shed surface
  (``AdmissionGate.shed_memory``, ``tpu.shed`` span,
  ``app_tpu_hbm_shed_total``) and the process keeps serving. The
  seeded chaos seam ``HBM_ALLOC`` fires at every lease point, so
  fault schedules can kill allocation N deterministically
  (tests/test_hbm_arbiter.py, tools/hbm_report.py --pressure).

The budget: ``TPU_HBM_BUDGET_MB`` (config; 0/unset = resolve from the
device) minus nothing, else on accelerator backends the device's
reported ``bytes_limit`` minus the ``TPU_HBM_HEADROOM`` fraction
(default 0.1 — XLA needs workspace the registry can't see). On the
CPU backend the budget stays OFF unless set explicitly
(``set_budget``) — tests opt in with a tiny synthetic budget.

**Per-shard leases** (multi-chip tensor-parallel serving,
docs/advanced-guide/multichip-serving.md): lease keys carry a DEVICE
axis — ``(subsystem, owner, tag, device)`` — so a mesh engine's
sharded buffers settle one entry per device. :func:`account` splits a
sharded tree automatically (per-device figures amortize each leaf's
LOGICAL bytes over its shards, so global totals are bit-identical to
the unsharded accounting and a replicated leaf never double-counts);
:func:`alloc_sharded` is the budgeted persist-point form for sharded
thunks (pre-leases an even per-device share, allocates, accounts the
real shard figures — gofrlint GL202 blesses it like ``hbm.alloc``).
With a per-device budget set (``set_device_budget`` /
``TPU_HBM_DEVICE_BUDGET_MB``, auto-resolved per device on accelerator
backends) the arbiter checks each shard's device against ITS budget
and reclaim runs PER-DEVICE: a hot shard's deficit asks only the
leases on that device to spill, never flushing the whole mesh.

Observability: ``app_tpu_device_bytes{subsystem=}`` gauges on every
accounting change, ``app_tpu_hbm_budget_bytes``,
``app_tpu_hbm_device_in_use_bytes{device=}`` /
``app_tpu_hbm_device_budget_bytes`` per-shard gauges,
``app_tpu_hbm_reclaims_total{subsystem=}`` /
``app_tpu_hbm_shed_total{subsystem=}`` counters, ``hbm:*`` counter
tracks plus reclaim/shed instants on the serving timeline, the
``hbm_arbiter`` section of ``/debug/vars`` and
``TPUEngine.health_check`` (both break out per-device in-use and
headroom), and ``tools/hbm_report.py``'s lease table. Subsystem
vocabulary: ``engine`` (serving KV cache + chunk scratch),
``kvcache-t0`` (prefix-pool rows), ``lora`` (adapter stacks),
``spec-decode``/``batcher`` (when they grow device state).
"""

from __future__ import annotations

import collections
import threading
import weakref
from typing import Any, Callable

from .. import chaos
from ..errors import TooManyRequests

__all__ = ["HBMExhausted", "PRI_CACHE", "PRI_SCRATCH", "PRI_SERVING",
           "account", "alloc", "alloc_sharded", "arbiter_stats", "budget",
           "check", "configure", "device_budget", "device_bytes",
           "is_oom_error", "lease", "live_bytes", "note_shed", "reclaim",
           "release", "reset", "set_budget", "set_device_budget",
           "set_metrics", "set_timeline", "shard_breakdown", "snapshot",
           "tree_nbytes"]

GAUGE = "app_tpu_device_bytes"
BUDGET_GAUGE = "app_tpu_hbm_budget_bytes"
DEVICE_GAUGE = "app_tpu_hbm_device_in_use_bytes"
DEVICE_BUDGET_GAUGE = "app_tpu_hbm_device_budget_bytes"
RECLAIMS_COUNTER = "app_tpu_hbm_reclaims_total"
SHED_COUNTER = "app_tpu_hbm_shed_total"

# Lease priority classes — the RECLAIM order, highest value first:
# scratch/workspace is dropped before caches shrink, and live serving
# state is never auto-reclaimed (a lease may still attach a callback
# at PRI_SERVING — e.g. the paged engine's cold-block release — but it
# runs last).
PRI_SERVING = 0
PRI_CACHE = 1
PRI_SCRATCH = 2


class HBMExhausted(TooManyRequests):
    """A lease the budget cannot cover even after reclaim (or a real
    device OOM that survived the reclaim-then-retry pass). A
    ``TooManyRequests`` subclass on purpose: the failure is SERVED —
    429 with ``Retry-After`` on HTTP, RESOURCE_EXHAUSTED with the
    retry trailer on gRPC — through the same shed surface queue
    overload uses (resilience.AdmissionGate), instead of killing the
    process the way an unhandled allocation failure does."""

    def __init__(self, subsystem: str, nbytes: int, *,
                 budget: int | None = None, in_use: int | None = None,
                 retry_after: float = 1.0):
        detail = ""
        if budget is not None:
            detail = (f" (budget {budget >> 20} MiB, "
                      f"in use {(in_use or 0) >> 20} MiB)")
        super().__init__(
            f"hbm arbiter: cannot cover {subsystem!r} lease of "
            f"{int(nbytes)} bytes after reclaim{detail}",
            retry_after=retry_after, reason="hbm")
        self.subsystem = subsystem
        self.nbytes = int(nbytes)


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OutOfMemory")


def is_oom_error(e: BaseException) -> bool:
    """Is this the allocation-failure class the arbiter owns? Covers
    our own :class:`HBMExhausted`, the chaos harness's injected
    ``ResourceExhausted``, and the runtime's ``XlaRuntimeError`` (or
    any RuntimeError) whose message carries the RESOURCE_EXHAUSTED /
    out-of-memory markers — jaxlib raises different concrete types
    across versions, so the classifier is name+message based rather
    than an isinstance check against a moving target."""
    if isinstance(e, HBMExhausted):
        return True
    name = type(e).__name__
    if "ResourceExhausted" in name or "OutOfMemory" in name:
        return True
    if "XlaRuntimeError" in name or isinstance(e, (RuntimeError,
                                                   MemoryError)):
        msg = str(e)
        return any(m in msg for m in _OOM_MARKERS)
    return False


def tree_nbytes(tree: Any) -> int:
    """Total bytes of the array leaves of ``tree`` (jax or numpy —
    anything with ``nbytes``). None leaves (e.g. absent scale planes)
    contribute nothing."""
    import jax

    return sum(int(getattr(leaf, "nbytes", 0) or 0)
               for leaf in jax.tree.leaves(tree))


def _estimate_nbytes(fn: Callable[[], Any]) -> int:
    """Size an allocation thunk WITHOUT allocating: ``jax.eval_shape``
    traces it abstractly and the ShapeDtypeStruct leaves give exact
    byte figures. 0 when the thunk resists tracing (device_put of
    host data, side effects) — the lease then reserves nothing and
    enforcement falls to the post-hoc OOM retry."""
    try:
        import jax
        import numpy as np

        spec = jax.eval_shape(fn)
        total = 0
        for leaf in jax.tree.leaves(spec):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            total += int(np.prod(shape, dtype=np.int64)) \
                * np.dtype(dtype).itemsize
        return total
    except Exception:
        return 0


def shard_breakdown(tree: Any) -> dict[str, int]:
    """Per-device byte breakdown of ``tree``'s multi-device leaves,
    keyed by device id (str). Each leaf's LOGICAL ``nbytes`` is
    amortized over its shards proportionally to the per-shard physical
    bytes, so the breakdown's total equals :func:`tree_nbytes` of the
    sharded leaves exactly: a fully partitioned leaf attributes each
    shard's own bytes, a replicated leaf attributes 1/N per device
    instead of N full copies — global accounting invariants (hbmwatch
    reconciliation, leak gates) see the same totals whether a buffer
    is sharded or not. Single-device leaves contribute nothing (they
    stay on the device-less axis)."""
    import jax

    out: dict[str, int] = {}
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        if not shards or len(shards) <= 1 or nbytes <= 0:
            continue
        raw: dict[str, int] = {}
        try:
            for sh in shards:
                d = str(sh.device.id)
                raw[d] = raw.get(d, 0) + int(sh.data.nbytes)
        except Exception:
            continue  # exotic backend: leaf stays device-less
        total = sum(raw.values())
        if total <= 0:
            continue
        for d, b in raw.items():
            out[d] = out.get(d, 0) + (b * nbytes) // total
    return out


class _Registry:
    def __init__(self) -> None:
        self._mu = threading.Lock()
        # (subsystem, owner_id, tag, device) -> bytes. The DEVICE axis
        # ("" = device-less / whole-process) is what per-shard leases
        # settle on: a mesh engine's cache is one entry per device, so
        # per-device budgets, reclaim and headroom all see real
        # figures. SET semantics hold per (subsystem, owner, tag)
        # GROUP: re-accounting replaces every device's entry for the
        # group at once (recovery/re-placement re-settles, never
        # double-counts — even across a mesh-shape change).
        self._entries: dict[tuple[str, int, str, str], int] = {}
        # lease metadata per key: (priority, reclaim-callable-or-ref).
        # Bound-method callbacks are held via weakref.WeakMethod so a
        # registered reclaimer never pins its engine alive; account()
        # preserves the lease group's meta across re-accounts (moving
        # it to the new device keys), so a recovery re-account keeps
        # the lease's class and callback.
        self._meta: dict[tuple[str, int, str, str], tuple[int, Any]] = {}
        self._budget: int | None = None
        # per-device budget (bytes each device's leases may hold): the
        # multi-chip half of the arbiter. None = per-device checks off
        # (single-device processes never key entries by device anyway).
        self._dev_budget: int | None = None
        # single-flight reclaim: one pass at a time process-wide.
        # Concurrent requesters return 0 and judge the budget as-is —
        # which also breaks any cross-engine lock cycle a nested
        # reclaim chain could otherwise build (engine A's callback
        # holds A's device lock while B's callback wants B's).
        self._reclaim_mu = threading.Lock()
        self._reclaims: dict[str, int] = {}
        # device labels with a live app_tpu_hbm_device_in_use_bytes
        # series: vanished devices push an explicit 0 at the next
        # _push instead of leaving a stale last value; _push_mu
        # serializes snapshot+export so a stale snapshot can never
        # land after fresher zeros
        self._pushed_devs: set[str] = set()
        self._push_mu = threading.Lock()
        self._reclaimed_bytes = 0
        self._sheds: dict[str, int] = {}
        self._oom_retries: dict[str, int] = {}
        # gauge sinks, weakly held: the registry outlives any Manager
        # and must neither pin one alive nor stop pushing to A because
        # B registered later (two engines, two Managers — both see the
        # same process-truth figures)
        self._sinks: "weakref.WeakSet[Any]" = weakref.WeakSet()
        # serving timelines (observe/timeline.py), same weak fan-out:
        # every accounting change lands a counter sample so the
        # exported Perfetto trace carries an HBM track per subsystem
        self._timelines: "weakref.WeakSet[Any]" = weakref.WeakSet()
        # ids of owners the collector has finalized since the last _reap
        self._dead_owners: "collections.deque[int]" = collections.deque()

    # -- accounting (PR-6 contract; sharded trees split per device) ----------
    def account(self, subsystem: str, tree: Any, *, owner: Any = None,
                tag: str = "") -> Any:
        base = (subsystem, id(owner) if owner is not None else 0, tag)
        n = tree_nbytes(tree)
        dev = shard_breakdown(tree)
        self._reap()
        with self._mu:
            # SET semantics over the whole lease GROUP: drop every
            # device's entry for (subsystem, owner, tag) before writing
            # the new figures — a re-placement onto a DIFFERENT mesh
            # shape must not strand stale per-device entries. The
            # group's lease meta (priority, reclaim cb) survives onto
            # the new keys.
            meta = None
            for key in [k for k in self._entries if k[:3] == base]:
                self._entries.pop(key)
                m = self._meta.pop(key, None)
                if m is not None:
                    meta = m
            for key in [k for k in self._meta if k[:3] == base]:
                meta = self._meta.pop(key)
            if dev:
                rem = n - sum(dev.values())
                for d, b in sorted(dev.items()):
                    self._entries[base + (d,)] = b
                    if meta is not None:
                        self._meta[base + (d,)] = meta
                if rem > 0:  # single-device leaves riding a sharded tree
                    self._entries[base + ("",)] = rem
                    if meta is not None:
                        self._meta[base + ("",)] = meta
            else:
                self._entries[base + ("",)] = n
                if meta is not None:
                    self._meta[base + ("",)] = meta
        if owner is not None:
            # safety net for owners that die WITHOUT close() — an
            # __init__ that OOMs after its first account() (exactly
            # the regime this registry exists for) must not leave
            # phantom bytes behind, and a reused id() must not alias a
            # dead owner's entries. Idempotent with close()'s explicit
            # release; runs at the owner's collection.
            try:
                weakref.finalize(owner, self._release_owner_id,
                                 id(owner))
            except TypeError:
                pass  # non-weakrefable owner: explicit release only
        self._push(subsystem)
        return tree

    def _release_owner_id(self, oid: int) -> None:
        """An owner's finalizer. The collector runs it on whichever
        thread crossed its threshold, between two bytecodes of whatever
        that thread was doing: inside a ``with self._mu`` of this class
        or a metrics sink's ``with m.lock``, neither re-entrant, and
        waiting for one of those here is waiting for oneself (a tier-1
        worker stood in ``arbiter_stats`` so until the run's limit cut
        it, PR 41). So it takes no lock: it notes the id, and the next
        call from outside drops the entries (_reap)."""
        self._dead_owners.append(oid)  # noqa: GL001 — a deque's append

    def _reap(self) -> None:
        """Drop what collected owners left, leases and reclaim callbacks
        with the entries, and push their subsystems' gauges. First thing
        in every call that reads or writes the table: a reader sees no
        dead owner's bytes, and a new owner whose id() is a dead one's
        (queued before its memory could be reused) starts clean."""
        if not self._dead_owners:
            return
        touched: set[str] = set()
        with self._mu:
            while self._dead_owners:
                oid = self._dead_owners.popleft()
                for key in [k for k in self._entries if k[1] == oid]:
                    self._entries.pop(key)
                    touched.add(key[0])
                for key in [k for k in self._meta if k[1] == oid]:
                    self._meta.pop(key)
        for sub in touched:
            self._push(sub)

    def release(self, subsystem: str | None = None, *,
                owner: Any = None, tag: str | None = None) -> int:
        """Drop entries by subsystem and/or owner (and optionally an
        exact tag; all devices of each matched lease group); returns
        the bytes released. ``release(owner=self)`` in ``close()``
        drops every subsystem the instance accounted — leases and
        their reclaim callbacks die with the entries."""
        oid = None if owner is None else id(owner)
        dropped = 0
        touched: set[str] = set()
        self._reap()
        with self._mu:
            for key in list(self._entries):
                sub, key_oid, key_tag, _ = key
                if subsystem is not None and sub != subsystem:
                    continue
                if oid is not None and key_oid != oid:
                    continue
                if tag is not None and key_tag != tag:
                    continue
                dropped += self._entries.pop(key)
                self._meta.pop(key, None)
                touched.add(sub)
        for sub in touched:
            self._push(sub)
        return dropped

    def live_bytes(self) -> dict[str, int]:
        """Accounted bytes aggregated by subsystem (zero-byte
        subsystems with live keys included — a released-to-zero
        subsystem disappears)."""
        out: dict[str, int] = {}
        self._reap()
        with self._mu:
            for (sub, _, _, _), n in self._entries.items():
                out[sub] = out.get(sub, 0) + n
        return dict(sorted(out.items()))

    def device_bytes(self) -> dict[str, int]:
        """Accounted bytes aggregated by device id ("" = device-less
        entries: single-device processes and unsharded leaves)."""
        self._reap()
        with self._mu:
            out = self._device_bytes_locked()
        return dict(sorted(out.items()))

    def _device_in_use_locked(self, dev: str) -> int:
        return sum(n for (_, _, _, d), n in self._entries.items()
                   if d == dev)

    def snapshot(self) -> dict[tuple[str, int, str, str], int]:
        self._reap()
        with self._mu:
            return dict(self._entries)

    # -- the arbiter ---------------------------------------------------------
    def set_budget(self, nbytes: int | None) -> None:
        """Install (or clear, with None/0) the process HBM budget in
        bytes. Tests use this directly with tiny synthetic budgets;
        production resolves it via :func:`configure`."""
        self._budget = int(nbytes) if nbytes else None
        for m in list(self._sinks):
            try:
                m.set_gauge(BUDGET_GAUGE, float(self._budget or 0))
            except Exception:
                pass

    def budget(self) -> int | None:
        return self._budget

    def set_device_budget(self, nbytes: int | None) -> None:
        """Install (or clear) the PER-DEVICE budget: bytes each
        device's leases may hold. Sharded mesh buffers key by their
        device; device-less entries (single-device processes — their
        whole footprint sits on the default chip) are checked as one
        "" group, so on a multi-chip host a non-mesh engine is still
        bounded by its one chip's budget rather than the process-wide
        per_dev * n_local figure."""
        self._dev_budget = int(nbytes) if nbytes else None
        for m in list(self._sinks):
            try:
                m.set_gauge(DEVICE_BUDGET_GAUGE,
                            float(self._dev_budget or 0))
            except Exception:
                pass

    def device_budget(self) -> int | None:
        return self._dev_budget

    def configure(self, budget_mb: int | None = None,
                  headroom: float = 0.1,
                  device_budget_mb: int | None = None,
                  n_devices: int | None = None) -> int | None:
        """Resolve and install the budgets. An explicit ``budget_mb``
        / ``device_budget_mb`` wins its OWN axis; any axis left unset
        resolves, on accelerator backends, from the local device's
        reported ``bytes_limit`` minus the ``headroom`` fraction (XLA
        keeps workspace the registry can't see): that figure is the
        PER-DEVICE budget and the process budget is it times
        ``n_devices`` — the chips the engine being built will occupy
        (its mesh's local devices, or one), defaulting to every LOCAL
        device: a mesh process honestly owns its own chips' HBM, not
        the pod's, and a single-device engine on a four-chip host owns
        one chip's. Setting TPU_HBM_BUDGET_MB alone therefore still
        arms per-device arbitration. The CPU backend leaves unset axes
        off — there is no meaningful device limit to enforce, and every
        existing test would suddenly arbitrate against host RAM. An
        accelerator that reports no ``bytes_limit`` is an error: the
        arbiter silently off is how a 16 GB chip OOMs mid-serving.
        Returns the active budget."""
        if device_budget_mb:
            self.set_device_budget(int(device_budget_mb) << 20)
        if budget_mb:
            self.set_budget(int(budget_mb) << 20)
        if budget_mb and device_budget_mb:
            return self._budget
        import jax

        # LOCAL devices: under the distributed runtime jax.devices() is
        # the global pod list, but this process only owns (and only
        # accounts) its local chips' HBM — a pod-wide budget would
        # never bind.
        devices = jax.local_devices()
        dev = devices[0]
        if dev.platform == "cpu":
            return self._budget
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        if not limit:
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                "memory_stats()['bytes_limit']: the HBM budget cannot be "
                "resolved — set TPU_HBM_BUDGET_MB and "
                "TPU_HBM_DEVICE_BUDGET_MB explicitly")
        frac = min(max(float(headroom), 0.0), 0.9)
        per_dev = int(limit * (1.0 - frac))
        # an explicit knob wins its own axis, but never disables the
        # OTHER one: TPU_HBM_BUDGET_MB alone still resolves the
        # per-device bound (and vice versa) — per-device arbitration
        # must not turn off because the global knob predates it
        if not device_budget_mb:
            self.set_device_budget(per_dev)
        if not budget_mb:
            self.set_budget(per_dev * (n_devices or len(devices)))
        return self._budget

    def _in_use_locked(self) -> int:
        return sum(self._entries.values())

    def tenant_lease(self, subsystem: str, nbytes: int, *, tenant: str,
                     owner: Any = None, priority: int = PRI_SCRATCH,
                     reclaim: Callable[[int], int] | None = None,
                     device: str = "") -> int:
        """A per-tenant cache-quota lease: :func:`lease` with the tag
        fixed to ``tenant:{id}`` so the tenant's footprint is visible
        in ``snapshot()``/``check()`` under its own key. Registered at
        PRI_SCRATCH (most-reclaimable) with a reclaim callback that
        evicts THAT tenant's cache blocks — under memory pressure the
        arbiter asks the over-budget tenant to give back its own rows
        BEFORE the PRI_CACHE pool shrink flushes everyone's. Usually
        zero-byte: the pool's own lease already accounts the bytes;
        this one exists for its reclaim ordering (the same convention
        as the paged pool's zero-byte reclaim hooks)."""
        return self.lease(subsystem, int(nbytes), owner=owner,
                          tag=f"tenant:{tenant}", priority=priority,
                          reclaim=reclaim, device=device)

    def lease(self, subsystem: str, nbytes: int, *, owner: Any = None,
              tag: str = "", priority: int = PRI_CACHE,
              reclaim: Callable[[int], int] | None = None,
              device: str = "", _seam: bool = True) -> int:
        """Reserve ``nbytes`` against the budget BEFORE allocating.
        Fires the seeded ``HBM_ALLOC`` chaos seam (an injected
        ResourceExhausted sheds deterministically), runs reclaim when
        the budget can't cover the request, and raises
        :class:`HBMExhausted` on a surviving deficit. On success the
        reservation is recorded under ``(subsystem, owner, tag,
        device)`` — the later :func:`account` of the real tree
        replaces the figure (SET semantics over the lease group),
        while the priority class and ``reclaim`` callback stay
        attached to the lease. ``device`` is the per-shard axis: a
        device-keyed lease is additionally checked against the
        per-device budget, and ITS deficit reclaims only that
        device's leases. Returns ``nbytes``."""
        if _seam:  # _alloc_impl fires once for its whole share split
            self._fire_seam(subsystem, int(nbytes))
        self._reap()
        need = int(nbytes)
        dev = str(device or "")
        key = (subsystem, id(owner) if owner is not None else 0, tag, dev)
        wrapped = self._wrap_reclaim(reclaim)

        def shortfalls() -> "tuple[int, int]":
            # (global deficit, this device's deficit), net of any bytes
            # the key itself already holds (SET semantics)
            with self._mu:
                held = self._entries.get(key, 0)
                g = 0
                if self._budget:
                    g = self._in_use_locked() - held + need - self._budget
                d = 0
                if self._dev_budget:
                    # "" is a real group: a single-device process's
                    # whole footprint sits on its default chip, so the
                    # per-device bound applies to it exactly as to a
                    # shard — without this a multi-chip host's auto
                    # budget (per_dev * n_local) would never bind a
                    # non-mesh engine
                    d = self._device_in_use_locked(dev) - held + need \
                        - self._dev_budget
                return g, d

        def try_reserve() -> bool:
            # budget check and reservation insert under ONE lock hold:
            # two concurrent leases (e.g. two engines constructing in
            # one process) must not both pass a check neither has
            # reserved against yet — that would jointly over-commit
            # the budget with no reclaim and no shed
            with self._mu:
                held = self._entries.get(key, 0)
                b = self._budget
                if b and self._in_use_locked() - held + need > b:
                    return False
                db = self._dev_budget
                if db and \
                        self._device_in_use_locked(dev) - held + need > db:
                    return False
                self._entries[key] = need
                self._meta[key] = (int(priority), wrapped)
                return True

        if not try_reserve():
            g, d = shortfalls()
            if g > 0:
                self._reclaim(g, requester=subsystem)
                # the global pass may have spilled bytes on this very
                # device (a pool shrink touches every shard) — recompute
                # so the per-device pass doesn't over-reclaim a deficit
                # that is already covered
                g, d = shortfalls()
            if d > 0:
                # the hot shard's deficit: ask only ITS device's leases
                # to spill — one overcommitted device must not flush
                # every shard's caches across the mesh
                self._reclaim(d, requester=subsystem, device=dev)
            if not try_reserve():
                g, d = shortfalls()
                self.note_shed(subsystem)
                if d > 0 and g <= 0:
                    # only the per-device bound failed: attribute the
                    # shed to THAT device with ITS figures (check()'s
                    # "sub@devN" convention) — the global budget may
                    # be unset or healthy, and a 429 naming it would
                    # hide which shard overflowed
                    with self._mu:
                        dev_use = self._device_in_use_locked(dev) \
                            - self._entries.get(key, 0)
                    raise HBMExhausted(
                        f"{subsystem}@dev{dev}" if dev else subsystem,
                        need, budget=self._dev_budget, in_use=dev_use)
                with self._mu:
                    in_use = self._in_use_locked() \
                        - self._entries.get(key, 0)
                raise HBMExhausted(subsystem, need, budget=self._budget,
                                   in_use=in_use)
        if owner is not None:
            try:
                weakref.finalize(owner, self._release_owner_id, id(owner))
            except TypeError:
                pass
        self._push(subsystem)
        return need

    def alloc(self, subsystem: str, fn: Callable[[], Any], *,
              owner: Any = None, tag: str = "",
              priority: int = PRI_CACHE,
              reclaim: Callable[[int], int] | None = None) -> Any:
        """Reclaim-then-retry allocation: the one call serving code
        wraps its persist-point allocations in (gofrlint GL202 accepts
        it as the accounting API). Leases the thunk's ``eval_shape``
        size when a budget is set, runs the thunk, and on a REAL
        device OOM (:func:`is_oom_error`) runs demand-driven reclaim
        and retries ONCE; a second failure raises
        :class:`HBMExhausted` (ruling the 429/RESOURCE_EXHAUSTED shed
        path) instead of letting the raw runtime error escape. The
        result is accounted under ``(subsystem, owner, tag)``. A
        failed allocation rolls the reservation back to the lease
        group's pre-lease state — no phantom bytes stay registered
        eating headroom for a buffer that never materialized."""
        return self._alloc_impl(subsystem, fn, owner=owner, tag=tag,
                                priority=priority, reclaim=reclaim,
                                devices=None)

    def alloc_sharded(self, subsystem: str, fn: Callable[[], Any], *,
                      owner: Any = None, tag: str = "",
                      priority: int = PRI_CACHE,
                      reclaim: Callable[[int], int] | None = None,
                      devices=()) -> Any:
        """:func:`alloc` for SHARDED persist points (gofrlint GL202
        blesses this form too): ``fn`` returns a tree placed across
        ``devices`` (mesh device ids), the pre-allocation lease splits
        an even share per device — each checked against the per-device
        budget, each reclaiming per-device on a deficit — and the
        account records the REAL per-shard figures (replacing the even
        estimate; SET semantics over the lease group). The one-call
        form a mesh engine's cache/pool/scratch persist points use."""
        labels = [str(getattr(d, "id", d)) for d in devices]
        return self._alloc_impl(subsystem, fn, owner=owner, tag=tag,
                                priority=priority, reclaim=reclaim,
                                devices=labels or None)

    def _alloc_impl(self, subsystem: str, fn: Callable[[], Any], *,
                    owner: Any, tag: str, priority: int,
                    reclaim: Callable[[int], int] | None,
                    devices: "list[str] | None") -> Any:
        base = (subsystem, id(owner) if owner is not None else 0, tag)
        self._reap()
        with self._mu:
            prior = {k: self._entries[k] for k in self._entries
                     if k[:3] == base}
            prior_meta = {k: self._meta[k] for k in self._meta
                          if k[:3] == base}

        def rollback() -> None:
            with self._mu:
                for k in [k for k in self._entries if k[:3] == base]:
                    self._entries.pop(k)
                for k in [k for k in self._meta if k[:3] == base]:
                    self._meta.pop(k)
                self._entries.update(prior)
                self._meta.update(prior_meta)
            self._push(subsystem)

        # device-less allocs are bounded too (the "" group vs the
        # per-device budget), so the estimate must be real whenever
        # EITHER budget is armed — not only for sharded thunks
        gated = bool(self._budget or self._dev_budget)
        need = _estimate_nbytes(fn) if gated else 0
        # ONE chaos-seam firing per allocation, however many per-device
        # shares the lease splits into — schedules stay comparable
        # between single-device and mesh engines
        self._fire_seam(subsystem, need)
        try:
            if devices:
                share = -(-need // len(devices))
                for d in devices:
                    self.lease(subsystem, share, owner=owner, tag=tag,
                               priority=priority, reclaim=reclaim,
                               device=d, _seam=False)
            else:
                self.lease(subsystem, need, owner=owner, tag=tag,
                           priority=priority, reclaim=reclaim,
                           _seam=False)
        except BaseException:
            rollback()
            raise
        try:
            tree = fn()
        except BaseException as e:
            if not is_oom_error(e):
                rollback()
                raise
            with self._mu:
                self._oom_retries[subsystem] = \
                    self._oom_retries.get(subsystem, 0) + 1
            # with no budget configured the lease skipped the size
            # estimate — compute it NOW so the reclaim pass frees the
            # allocation's worth, not one token byte (a real OOM is
            # exactly the no-budget regime's enforcement path). NB
            # eval_shape traces the thunk abstractly: pure allocation
            # thunks (the contract) are side-effect free under it
            self._reclaim(max(need or _estimate_nbytes(fn), 1),
                          requester=subsystem)
            try:
                tree = fn()
            except BaseException as e2:
                rollback()
                if is_oom_error(e2):
                    self.note_shed(subsystem)
                    raise HBMExhausted(subsystem, need,
                                       budget=self._budget) from e2
                raise
        return self.account(subsystem, tree, owner=owner, tag=tag)

    def check(self, subsystem: str) -> None:
        """Zero-byte lease for request-path admission points (the
        generation loop calls it once per admission): fires the
        ``HBM_ALLOC`` seam and, when the process sits OVER its budget
        (budget lowered at runtime, or actuals outgrew estimates),
        runs reclaim and raises :class:`HBMExhausted` if the overshoot
        survives — the caller sheds THAT request and keeps serving.
        With a per-device budget set, each overcommitted device runs
        its OWN reclaim pass (one hot shard spills without flushing
        the mesh) and a surviving per-device overshoot sheds too."""
        self._fire_seam(subsystem, 0)
        self._reap()
        b = self._budget
        if b:
            with self._mu:
                in_use = self._in_use_locked()
            if in_use > b:
                self._reclaim(in_use - b, requester=subsystem)
                with self._mu:
                    in_use = self._in_use_locked()
                if in_use > b:
                    self.note_shed(subsystem)
                    raise HBMExhausted(subsystem, 0, budget=b,
                                       in_use=in_use)
        db = self._dev_budget
        if db:
            with self._mu:
                # "" included: device-less entries are one group too
                # (a single-device process's default chip)
                over = [d for d, n in
                        self._device_bytes_locked().items() if n > db]
            for d in over:
                # re-read THIS device's deficit: an earlier device's
                # pass may have spilled on EVERY shard (a sharded pool
                # shrink), already covering this one — reclaiming the
                # stale figure would cascade pool shrinks and flush
                # the mesh-wide T0 the per-device design protects
                with self._mu:
                    deficit = self._device_in_use_locked(d) - db
                if deficit <= 0:
                    continue
                self._reclaim(deficit, requester=subsystem, device=d)
                with self._mu:
                    in_use = self._device_in_use_locked(d)
                if in_use > db:
                    self.note_shed(subsystem)
                    raise HBMExhausted(
                        f"{subsystem}@dev{d}" if d else subsystem, 0,
                        budget=db, in_use=in_use)

    def _device_bytes_locked(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for (_, _, _, dev), n in self._entries.items():
            out[dev] = out.get(dev, 0) + n
        return out

    def _fire_seam(self, subsystem: str, nbytes: int) -> None:
        try:
            chaos.fire(chaos.HBM_ALLOC)
        except BaseException as e:
            if is_oom_error(e):
                # an injected allocation failure models one that
                # survived retry: exercise the reclaim machinery (the
                # recovery coverage the schedule is reproducing), then
                # shed deterministically
                self._reclaim(max(int(nbytes), 1), requester=subsystem)
                self.note_shed(subsystem)
                raise HBMExhausted(subsystem, nbytes,
                                   budget=self._budget) from e
            raise

    def reclaim(self, nbytes: int | None = None) -> int:
        """Manually run one demand-driven reclaim pass for ``nbytes``.
        ``None`` asks EVERY registered reclaimer (need = the sum of
        all reclaimable leases): the last-ditch form the batcher's OOM
        retry uses, where the transient deficit is unknowable and the
        alternative is shedding the whole batch. Returns bytes freed;
        lease/alloc/check run sized passes implicitly."""
        self._reap()
        if nbytes is None:
            with self._mu:
                need = sum(self._entries.get(k, 0)
                           for k in self._meta
                           if self._meta[k][1] is not None) or 1
        else:
            need = max(int(nbytes), 1)
        return self._reclaim(need, requester="manual")

    def _wrap_reclaim(self, cb):
        if cb is None:
            return None
        try:
            return weakref.WeakMethod(cb)
        except TypeError:
            return cb  # plain function/lambda: held strongly

    def _deref_reclaim(self, wrapped):
        if isinstance(wrapped, weakref.WeakMethod):
            return wrapped()
        return wrapped

    def _reclaim(self, need: int, requester: str = "",
                 device: str | None = None) -> int:
        """Run registered reclaim callbacks, highest priority class
        first (PRI_SCRATCH before PRI_CACHE before PRI_SERVING), until
        ``need`` bytes are freed or the candidates run out.
        ``device``: a per-shard pass — only leases holding bytes ON
        that device are asked, and each callback's (global) freed
        figure counts toward the deficit scaled by the lease group's
        share on that device, so one overcommitted shard never flushes
        the whole mesh. Single-flight: a pass already in progress
        makes this a no-op returning 0 (the concurrent requester
        re-checks the budget as-is)."""
        if not self._reclaim_mu.acquire(blocking=False):
            return 0
        try:
            with self._mu:
                # one candidate per lease GROUP (a sharded lease holds
                # N device keys sharing one callback — calling it once
                # per shard would over-reclaim N-fold); per-device
                # passes keep only groups with bytes on that device
                groups: dict[tuple, dict] = {}
                for key, meta in self._meta.items():
                    if meta[1] is None:
                        continue
                    g = groups.setdefault(key[:3], {
                        "meta": meta, "bytes": 0, "dev_bytes": 0,
                        "keys": []})
                    n = self._entries.get(key, 0)
                    g["bytes"] += n
                    g["keys"].append(key)
                    if device is not None and key[3] == device:
                        g["dev_bytes"] += n
                candidates = sorted(
                    (g for g in groups.values()
                     if device is None or g["dev_bytes"] > 0),
                    key=lambda g: (-g["meta"][0],
                                   -(g["dev_bytes"] if device is not None
                                     else g["bytes"])))
            freed = 0
            for g in candidates:
                if freed >= need:
                    break
                cb = self._deref_reclaim(g["meta"][1])
                if cb is None:
                    with self._mu:  # owner died: drop the dead callback
                        for key in g["keys"]:
                            self._meta.pop(key, None)
                    continue
                # ask for the GLOBAL equivalent of the remaining
                # per-device deficit: a lease whose bytes spread over
                # nd devices frees ~1/nd of each reclaimed row here
                frac = (g["dev_bytes"] / g["bytes"]
                        if device is not None and g["bytes"] else 1.0)
                ask = need - freed
                if device is not None and frac > 0:
                    ask = int(ask / frac) + 1
                try:
                    got = int(cb(ask) or 0)
                except Exception:
                    got = 0  # a failing reclaimer must never take the
                    # requesting allocation down with it
                if got > 0:
                    freed += max(int(got * frac), 1) \
                        if device is not None else got
                    sub = g["keys"][0][0]
                    with self._mu:
                        self._reclaims[sub] = self._reclaims.get(sub, 0) + 1
                        self._reclaimed_bytes += got
                    self._count_metric(RECLAIMS_COUNTER, subsystem=sub)
                    self._event_timeline(sub, "reclaim", got)
            return freed
        finally:
            self._reclaim_mu.release()

    def note_shed(self, subsystem: str) -> None:
        """Record one request degraded (429/RESOURCE_EXHAUSTED) because
        the arbiter could not cover an allocation:
        ``app_tpu_hbm_shed_total{subsystem=}`` plus a timeline instant
        on the subsystem's ``hbm:*`` track. Every :class:`HBMExhausted`
        raise site in this module counts itself — a caller that merely
        catches and re-routes one must NOT count it again (the
        batcher's own persistent-OOM shed, which raises a plain
        TooManyRequests, is the one external caller)."""
        with self._mu:
            self._sheds[subsystem] = self._sheds.get(subsystem, 0) + 1
        self._count_metric(SHED_COUNTER, subsystem=subsystem)
        self._event_timeline(subsystem, "shed", 0)

    def arbiter_stats(self) -> dict:
        """The lease/reclaim table: budget, in-use, per-lease rows
        (subsystem/tag/bytes/priority/reclaimable), and the reclaim/
        shed/retry counters — what /debug/vars, health_check and
        tools/hbm_report.py render."""
        self._reap()
        with self._mu:
            entries = dict(self._entries)
            meta = dict(self._meta)
            reclaims = dict(self._reclaims)
            sheds = dict(self._sheds)
            retries = dict(self._oom_retries)
            reclaimed = self._reclaimed_bytes
        in_use = sum(entries.values())
        pri_names = {PRI_SERVING: "serving", PRI_CACHE: "cache",
                     PRI_SCRATCH: "scratch"}
        leases = []
        per_dev: dict[str, int] = {}
        for (sub, oid, tag, dev), n in sorted(entries.items()):
            pri, cb = meta.get((sub, oid, tag, dev), (PRI_CACHE, None))
            row = {
                "subsystem": sub, "owner": oid, "tag": tag, "bytes": n,
                "priority": pri_names.get(pri, str(pri)),
                "reclaimable": self._deref_reclaim(cb) is not None,
            }
            if dev:
                row["device"] = dev
                per_dev[dev] = per_dev.get(dev, 0) + n
            leases.append(row)
        out = {
            "budget_bytes": self._budget,
            "in_use_bytes": in_use,
            "headroom_bytes": (self._budget - in_use
                               if self._budget else None),
            "leases": leases,
            "reclaims": reclaims,
            "reclaimed_bytes": reclaimed,
            "sheds": sheds,
            "oom_retries": retries,
        }
        if per_dev or self._dev_budget:
            db = self._dev_budget
            out["device_budget_bytes"] = db
            out["devices"] = {
                d: {"in_use_bytes": n,
                    "headroom_bytes": (db - n) if db else None}
                for d, n in sorted(per_dev.items())}
        return out

    # -- fan-out sinks -------------------------------------------------------
    def set_metrics(self, metrics: Any) -> None:
        """Attach a metrics Manager (weakly held; every attached
        Manager receives every later change as
        ``app_tpu_device_bytes{subsystem=...}``). ``None`` detaches
        all sinks."""
        if metrics is None:
            self._sinks.clear()
            return
        self._sinks.add(metrics)
        for sub in self.live_bytes():
            self._push(sub)
        try:
            metrics.set_gauge(BUDGET_GAUGE, float(self._budget or 0))
            metrics.set_gauge(DEVICE_BUDGET_GAUGE,
                              float(self._dev_budget or 0))
        except Exception:
            pass

    def reset(self) -> None:
        """Test hook: forget everything — entries, leases, budget,
        counters (and zero pushed gauges)."""
        with self._mu:
            subs = {sub for (sub, _, _, _) in self._entries}
            self._dead_owners.clear()
            self._entries.clear()
            self._meta.clear()
            self._reclaims.clear()
            self._sheds.clear()
            self._oom_retries.clear()
            self._reclaimed_bytes = 0
        self.set_budget(None)
        self.set_device_budget(None)
        for sub in subs:
            self._push(sub)

    def set_timeline(self, timeline: Any) -> None:
        """Attach a serving timeline (weakly held) that receives an
        ``hbm`` counter sample on every accounting change plus
        reclaim/shed instants. ``None`` detaches all timelines."""
        if timeline is None:
            self._timelines.clear()
            return
        self._timelines.add(timeline)
        for sub, n in self.live_bytes().items():
            try:
                timeline.hbm(sub, float(n))
            except Exception:
                pass

    def _count_metric(self, name: str, **labels) -> None:
        for m in list(self._sinks):
            try:
                m.increment_counter(name, **labels)
            except Exception:
                pass  # accounting must never take the serving path down

    def _event_timeline(self, subsystem: str, what: str,
                        nbytes: int) -> None:
        for tl in list(self._timelines):
            try:
                fn = getattr(tl, "hbm_event", None)
                if fn is not None:
                    fn(subsystem, what, float(nbytes))
            except Exception:
                pass

    def _push(self, subsystem: str) -> None:
        sinks = list(self._sinks)
        timelines = list(self._timelines)
        if not sinks and not timelines:
            return
        # _push_mu serializes whole pushes: without it, a thread
        # holding a pre-release snapshot could write its stale nonzero
        # per-device values AFTER another thread's explicit zeros —
        # re-creating exactly the phantom-in-use the zeros prevent
        with self._push_mu:
            # devices whose entries vanished (engine closed, mesh
            # shrank) must push an explicit 0 — a gauge series that
            # just stops updating reads as phantom in-use forever (the
            # subsystem gauge's zero-on-release contract, per device)
            with self._mu:
                # not live_bytes(): that reaps, and a reap pushes
                value = float(sum(n for (sub, _, _, _), n
                                  in self._entries.items()
                                  if sub == subsystem))
                per_dev = {d: n for d, n in
                           self._device_bytes_locked().items() if d}
                gone = self._pushed_devs - set(per_dev)
                self._pushed_devs = set(per_dev)
            for m in sinks:
                try:
                    m.set_gauge(GAUGE, value, subsystem=subsystem)
                    # per-shard in-use (only when entries carry a
                    # device axis — single-device processes export no
                    # series)
                    for d, n in per_dev.items():
                        m.set_gauge(DEVICE_GAUGE, float(n), device=d)
                    for d in gone:
                        m.set_gauge(DEVICE_GAUGE, 0.0, device=d)
                except Exception:
                    pass  # accounting must never take the serving
                    # path down
        for tl in timelines:
            try:
                tl.hbm(subsystem, value)
            except Exception:
                pass


_registry = _Registry()

account = _registry.account
alloc = _registry.alloc
alloc_sharded = _registry.alloc_sharded
arbiter_stats = _registry.arbiter_stats
budget = _registry.budget
check = _registry.check
configure = _registry.configure
device_budget = _registry.device_budget
device_bytes = _registry.device_bytes
lease = _registry.lease
live_bytes = _registry.live_bytes
note_shed = _registry.note_shed
reclaim = _registry.reclaim
release = _registry.release
reset = _registry.reset
set_budget = _registry.set_budget
set_device_budget = _registry.set_device_budget
set_metrics = _registry.set_metrics
set_timeline = _registry.set_timeline
snapshot = _registry.snapshot
tenant_lease = _registry.tenant_lease
