"""The unified HBM arbiter (ISSUE 10): budget leases, reclaim-then-
retry allocation, and OOM-shed serving.

What these tests pin, in order of altitude:

  - arbiter units: budget enforcement at lease time, SET-semantics
    settle via account(), reclaim priority order (scratch before
    caches, serving never auto-reclaimed), dead-owner callback purge,
    the OOM classifier, counters/gauges on the metrics face and
    reclaim/shed instants on the timeline export;
  - the seeded ``HBM_ALLOC`` chaos seam: deterministic per-index
    injection (kill allocation N), replayable via the schedule digest;
  - subsystem COEXISTENCE — the acceptance criterion: one process
    running a contiguous engine with a prefix cache (T0 + host T1)
    plus a paged engine with spec decode under a deliberately tiny
    synthetic budget. Constructing the second engine forces the
    arbiter to shrink the first engine's T0 pool toward the host tier
    (leases rebalance), both engines then serve TOKEN-EXACT against
    unconstrained references, and entries spilled by the shrink are
    served back from T1;
  - OOM-shed serving: a seeded ``HBM_ALLOC`` storm over a live engine
    yields only 429/RESOURCE_EXHAUSTED responses with ``Retry-After``
    — never an unhandled exception, never a dead engine — and
    post-storm serving returns to token-exact, leak-flat steady state
    (HBMWatch.assert_flat);
  - the batcher's reclaim-then-retry: a transient dispatch OOM is
    retried once after reclaim and DELIVERS; a persistent OOM sheds
    the batch as 429 instead of a raw runtime error; non-OOM errors
    still propagate untouched.
"""

import gc

import jax
import numpy as np
import pytest

from gofr_tpu import chaos
from gofr_tpu.errors import TooManyRequests
from gofr_tpu.metrics import Manager, register_framework_metrics
from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.testutil.hbmwatch import HBMWatch
from gofr_tpu.tpu import GenerationEngine, hbm
from gofr_tpu.tpu.batcher import CoalescingBatcher
from gofr_tpu.tpu.kvcache import KVCacheOptions

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(autouse=True)
def _clean_arbiter():
    hbm.reset()
    yield
    chaos.uninstall()
    hbm.reset()
    # engines are cyclic (slots -> requests -> streams -> engine);
    # collect the cycles NOW so their device buffers don't free at an
    # arbitrary automatic-gc point inside a LATER test's two
    # live_device_bytes() reads (an order-dependent flake)
    gc.collect()


def tiny_engine(**kw):
    params = kw.pop("params", None)
    if params is None:
        params = llama.init(TINY, jax.random.PRNGKey(0))
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prompt_buckets", (16, 32))
    return GenerationEngine(TINY, params, **kw)


def prompts(seed=0, n=24):
    rng = np.random.default_rng(seed)
    return lambda: rng.integers(1, TINY.vocab_size, size=n)


# -- arbiter units ------------------------------------------------------------

def test_lease_enforces_budget_and_sheds_429():
    o = object()
    hbm.set_budget(100)
    hbm.lease("engine", 80, owner=o, tag="cache")
    with pytest.raises(hbm.HBMExhausted) as ei:
        hbm.lease("kvcache-t0", 40, owner=o, tag="pool")
    e = ei.value
    # the shed contract: a SERVED degradation, not a crash — 429 with
    # an honest Retry-After (grpc maps 429 -> RESOURCE_EXHAUSTED)
    assert isinstance(e, TooManyRequests)
    assert e.status_code == 429
    assert "Retry-After" in e.headers
    st = hbm.arbiter_stats()
    assert st["sheds"] == {"kvcache-t0": 1}
    assert st["in_use_bytes"] == 80  # the failed lease reserved nothing


def test_lease_settles_via_account_set_semantics():
    o = object()
    hbm.set_budget(1 << 20)
    hbm.lease("engine", 512, owner=o, tag="cache")
    assert hbm.live_bytes() == {"engine": 512}
    # the real allocation replaces the reservation (same key)
    hbm.account("engine", np.zeros((16,), np.float32), owner=o, tag="cache")
    assert hbm.live_bytes() == {"engine": 64}
    # re-leasing the SAME key replaces, never double-counts
    hbm.lease("engine", 128, owner=o, tag="cache")
    assert hbm.live_bytes() == {"engine": 128}


def test_reclaim_priority_order_scratch_before_cache():
    o = object()
    order = []

    def make_cb(name, freed, key_tag):
        def cb(need):
            order.append(name)
            hbm.release(owner=o, tag=key_tag)
            return freed
        return cb

    hbm.set_budget(300)
    hbm.lease("engine", 100, owner=o, tag="serving",
              priority=hbm.PRI_SERVING)
    hbm.lease("kvcache-t0", 100, owner=o, tag="pool",
              priority=hbm.PRI_CACHE,
              reclaim=make_cb("cache", 100, "pool"))
    hbm.lease("engine", 100, owner=o, tag="scratch",
              priority=hbm.PRI_SCRATCH,
              reclaim=make_cb("scratch", 100, "scratch"))
    # needs 100: scratch must be asked first and cover it alone
    hbm.lease("lora", 100, owner=o, tag="l")
    assert order == ["scratch"]
    # needs 100 more: only the cache remains reclaimable
    hbm.lease("lora", 100, owner=o, tag="l2")
    assert order == ["scratch", "cache"]
    st = hbm.arbiter_stats()
    assert st["reclaims"] == {"engine": 1, "kvcache-t0": 1}
    assert st["reclaimed_bytes"] == 200


def test_dead_owner_reclaim_callback_is_purged():
    class Owner:
        def cb(self, need):  # pragma: no cover — must never run
            raise AssertionError("dead owner's reclaimer invoked")

    o = Owner()
    hbm.set_budget(200)
    hbm.lease("engine", 150, owner=o, tag="x", reclaim=o.cb)
    del o
    gc.collect()  # finalizer drops the entries AND the WeakMethod dies
    assert hbm.live_bytes() == {}
    hbm.lease("engine", 180, owner=object(), tag="y")  # no dead cb fires


def test_is_oom_error_classification():
    assert hbm.is_oom_error(chaos.ResourceExhausted())
    assert hbm.is_oom_error(hbm.HBMExhausted("engine", 4))
    assert hbm.is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: alloc"))
    assert hbm.is_oom_error(RuntimeError("Out of memory while trying"))
    assert not hbm.is_oom_error(RuntimeError("device link dropped"))
    assert not hbm.is_oom_error(ValueError("RESOURCE_EXHAUSTED"))
    assert not hbm.is_oom_error(chaos.DeviceLost("gone"))


def test_check_reclaims_budget_overshoot_then_sheds():
    o = object()
    calls = []

    def cb(need):
        calls.append(need)
        return 0  # cannot actually free anything

    hbm.lease("engine", 100, owner=o, tag="c", reclaim=cb)
    hbm.check("engine")  # no budget: free pass
    hbm.set_budget(60)   # budget lowered under the live lease
    with pytest.raises(hbm.HBMExhausted):
        hbm.check("engine")
    assert calls == [40]  # asked for exactly the overshoot


def test_alloc_retries_once_after_real_oom_then_sheds():
    o = object()
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return np.zeros((4,), np.float32)

    out = hbm.alloc("engine", flaky, owner=o, tag="c")
    # one real attempt + the OOM branch's eval_shape sizing trace (it
    # executes a numpy thunk concretely) + one retry — the contract is
    # the retry happened once and the result landed
    assert out.nbytes == 16 and attempts["n"] >= 2
    assert hbm.arbiter_stats()["oom_retries"] == {"engine": 1}

    def dead():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with pytest.raises(hbm.HBMExhausted):
        hbm.alloc("engine", dead, owner=o, tag="d")
    # a non-OOM failure propagates untouched (no silent conversion)
    with pytest.raises(ValueError):
        hbm.alloc("engine", lambda: (_ for _ in ()).throw(ValueError("x")),
                  owner=o, tag="e")


def test_alloc_failure_rolls_back_the_reservation():
    o = object()
    hbm.set_budget(1000)

    # fresh key, non-OOM failure: reservation fully removed
    with pytest.raises(ValueError):
        hbm.alloc("engine",
                  lambda: (_ for _ in ()).throw(ValueError("x")),
                  owner=o, tag="a")
    assert hbm.live_bytes() == {}

    # fresh key, persistent OOM: no phantom bytes eat headroom either
    def dead():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with pytest.raises(hbm.HBMExhausted):
        hbm.alloc("engine", dead, owner=o, tag="b")
    assert hbm.live_bytes() == {}
    # the headroom is genuinely intact: a full-budget lease still fits
    hbm.lease("engine", 1000, owner=o, tag="c")


def test_alloc_failure_restores_prior_figure_on_existing_key():
    # recovery-realloc shape: the key already holds a settled figure;
    # a failed re-alloc must restore IT, not zero it or keep the
    # estimate
    o = object()
    hbm.set_budget(1 << 20)
    hbm.alloc("engine", lambda: np.zeros((8,), np.float32),
              owner=o, tag="cache", priority=hbm.PRI_SERVING)
    assert hbm.live_bytes() == {"engine": 32}
    with pytest.raises(ValueError):
        hbm.alloc("engine",
                  lambda: (_ for _ in ()).throw(ValueError("x")),
                  owner=o, tag="cache")
    assert hbm.live_bytes() == {"engine": 32}
    # the lease meta survived too: still marked serving-class
    rows = {r["tag"]: r for r in hbm.arbiter_stats()["leases"]}
    assert rows["cache"]["priority"] == "serving"


def test_concurrent_leases_never_jointly_overcommit():
    import threading

    hbm.set_budget(1000)
    results = []
    barrier = threading.Barrier(4)

    def one(i):
        o = object()
        barrier.wait()
        try:
            hbm.lease("engine", 400, owner=o, tag=f"t{i}")
            results.append(("ok", o))  # hold the owner: entries live
        except hbm.HBMExhausted:
            results.append(("shed", None))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # check-and-reserve is atomic: whatever subset won, the SUM of
    # reservations respects the budget (4x400 admitted would be the
    # over-commit race)
    assert sum(hbm.live_bytes().values()) <= 1000
    assert sum(1 for kind, _ in results if kind == "ok") <= 2


def test_pool_shrink_realloc_failure_disables_tiers_not_crashes(
        monkeypatch):
    # the reclaim callback runs under the memory pressure that
    # triggered it: if even the SMALLER pool fails to allocate, the
    # prefix tiers must disable cleanly (engine serves cache-less)
    # instead of leaving a None pool behind a live CacheManager
    eng = tiny_engine(prefix_cache_slots=4, prefix_store_min=16)
    next_p = prompts(seed=8)
    try:
        ref = eng.generate(next_p(), max_new_tokens=4).tokens()
        from gofr_tpu.models import llama as llama_mod

        real_init = llama_mod.init_cache

        def failing_init(cfg, slots, *a, **kw):
            if slots < 4:  # only the shrink's smaller realloc fails
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return real_init(cfg, slots, *a, **kw)

        monkeypatch.setattr(llama_mod, "init_cache", failing_init)
        freed = eng._hbm_pool_reclaim(1)
        assert freed > 0  # the whole old pool counts as freed
        assert eng._kvc is None and eng._pool is None
        assert "kvcache-t0" not in hbm.live_bytes()
        monkeypatch.setattr(llama_mod, "init_cache", real_init)
        # cache-less serving continues, token-exact
        out = eng.generate(next_p(), max_new_tokens=4).tokens()
        assert len(out) == 4
        assert len(ref) == 4
    finally:
        eng.close()


def test_metrics_face_and_timeline_instants():
    m = Manager()
    register_framework_metrics(m)
    hbm.set_metrics(m)

    from gofr_tpu.observe.timeline import Timeline

    tl = Timeline(enabled=True, capacity=256)
    hbm.set_timeline(tl)

    o = object()
    hbm.set_budget(100)
    hbm.lease("engine", 60, owner=o, tag="c",
              reclaim=lambda need: hbm.release("engine", owner=o,
                                               tag="c") and 60 or 60)
    with pytest.raises(hbm.HBMExhausted):
        hbm.lease("kvcache-t0", 200, owner=o, tag="p")  # reclaim + shed
    text = m.render_prometheus()
    assert 'app_tpu_hbm_budget_bytes 100' in text
    assert 'app_tpu_hbm_reclaims_total{subsystem="engine"} 1' in text
    assert 'app_tpu_hbm_shed_total{subsystem="kvcache-t0"} 1' in text
    kinds = {e["name"] for e in tl.chrome_trace()["traceEvents"]
             if e.get("cat") == "hbm"}
    assert "hbm:engine reclaim" in kinds
    assert "hbm:kvcache-t0 shed" in kinds
    hbm.set_metrics(None)
    hbm.set_timeline(None)


def test_chaos_seam_kills_allocation_n_deterministically():
    sched = chaos.ChaosSchedule(seed=11).on(
        chaos.HBM_ALLOC, error=chaos.ResourceExhausted, every=3)

    def run():
        out = []
        with chaos.scope(chaos.ChaosSchedule(seed=11).on(
                chaos.HBM_ALLOC, error=chaos.ResourceExhausted, every=3)):
            for _ in range(9):
                try:
                    hbm.check("engine")
                    out.append(True)
                except hbm.HBMExhausted:
                    out.append(False)
        return out

    a, b = run(), run()
    assert a == b == [True, True, False] * 3  # kill allocation 3, 6, 9
    # the replay digest is the reproducibility oracle CI relies on
    assert sched.digest() == chaos.ChaosSchedule(seed=11).on(
        chaos.HBM_ALLOC, error=chaos.ResourceExhausted, every=3).digest()


# -- subsystem coexistence (the acceptance criterion) -------------------------

@pytest.mark.parametrize("spec_k", [2])
def test_coexistence_t0_shrinks_paged_proceeds_tokens_exact(spec_k):
    params = llama.init(TINY, jax.random.PRNGKey(0))
    next_a, next_b = prompts(seed=1), prompts(seed=2, n=20)
    p_a, p_b = next_a(), next_b()

    # unconstrained references FIRST (budget off): the tokens the
    # constrained run must reproduce exactly
    ref_a_eng = tiny_engine(params=params, prefix_cache_slots=4,
                            prefix_store_min=16,
                            kvcache=KVCacheOptions(host_mb=8))
    ref_a = ref_a_eng.generate(p_a, max_new_tokens=6).tokens()
    bytes_a = sum(hbm.live_bytes().values())
    pool_bytes = hbm.live_bytes()["kvcache-t0"]
    ref_b_eng = tiny_engine(params=params, paged_blocks=12,
                            paged_block_size=16, spec_decode_k=spec_k)
    ref_b = ref_b_eng.generate(p_b, max_new_tokens=6).tokens()
    bytes_b = sum(hbm.live_bytes().values()) - bytes_a
    ref_b_eng.close()
    gc.collect()

    # deliberately tiny synthetic budget: A fits, but A + B only fits
    # if A's 4-row T0 pool gives up ~2 rows
    row_bytes = pool_bytes // 4
    hbm.set_budget(bytes_a + bytes_b - 2 * row_bytes + row_bytes // 2)
    a = ref_a_eng  # the live engine IS the constrained one
    assert a.generate(p_a, max_new_tokens=6).tokens() == ref_a  # warm T0
    slots_before = a._kvc.slots
    b = tiny_engine(params=params, paged_blocks=12, paged_block_size=16,
                    spec_decode_k=spec_k)
    try:
        # leases rebalanced: T0 shrank, the paged lease proceeded
        assert a._kvc.slots < slots_before
        st = hbm.arbiter_stats()
        assert st["reclaims"].get("kvcache-t0", 0) >= 1
        assert st["in_use_bytes"] <= st["budget_bytes"]
        # both engines serve token-exact vs the unconstrained runs
        sa = a.generate(p_a, max_new_tokens=6)
        assert sa.tokens() == ref_a
        assert b.generate(p_b, max_new_tokens=6).tokens() == ref_b
        # the shrink SPILLED, it didn't drop: the prompt cached in T0
        # before the shrink now serves from the host tier (and the
        # host tier counted the spills)
        assert sa.cache_tier == "t1"
        assert a._kvc.host.spills >= 1
        # several more admissions on both engines: still exact, alive
        for _ in range(3):
            pa, pb = next_a(), next_b()
            r1 = a.generate(pa, max_new_tokens=4).tokens()
            r2 = b.generate(pb, max_new_tokens=4).tokens()
            assert len(r1) == 4 and len(r2) == 4
    finally:
        b.close()
        a.close()


# -- OOM-shed serving under a seeded storm ------------------------------------

def test_hbm_storm_sheds_429_only_and_recovers_token_exact():
    eng = tiny_engine(prefix_cache_slots=2, prefix_store_min=16)
    next_p = prompts(seed=3)
    p0 = next_p()
    try:
        ref = eng.generate(p0, max_new_tokens=6).tokens()
        sched = chaos.ChaosSchedule(seed=5).on(
            chaos.HBM_ALLOC, error=chaos.ResourceExhausted, every=2)
        outcomes = []
        with chaos.scope(sched):
            for _ in range(8):
                s = eng.generate(next_p(), max_new_tokens=4)
                try:
                    s.tokens()
                    outcomes.append("ok")
                except TooManyRequests as e:
                    # the ONLY acceptable failure: a served 429 with
                    # Retry-After (RESOURCE_EXHAUSTED on gRPC)
                    assert e.status_code == 429
                    assert "Retry-After" in e.headers
                    outcomes.append("shed")
        # every=2 on sequential admissions: deterministic alternation
        assert outcomes == ["ok", "shed"] * 4
        assert eng.down is None  # the ENGINE survived the whole storm
        st = hbm.arbiter_stats()
        assert st["sheds"] == {"engine": 4}
        # post-storm: token-exact steady state
        assert eng.generate(p0, max_new_tokens=6).tokens() == ref
    finally:
        eng.close()


def test_post_storm_serving_is_leak_flat():
    eng = tiny_engine()
    next_p = prompts(seed=4)
    try:
        with chaos.scope(chaos.ChaosSchedule(seed=9).on(
                chaos.HBM_ALLOC, error=chaos.ResourceExhausted, every=2)):
            for _ in range(6):
                try:
                    eng.generate(next_p(), max_new_tokens=4).tokens()
                except TooManyRequests:
                    pass

        def serve():
            eng.generate(next_p(), max_new_tokens=4).tokens()

        # the acceptance criterion's hbmwatch clause: after the storm,
        # steady-state serving grows live device bytes by ZERO
        HBMWatch("post-storm").assert_flat(serve, warmup=2, iters=3)
    finally:
        eng.close()


def test_shed_routes_through_admission_gate_surface():
    from gofr_tpu.resilience import AdmissionGate

    m = Manager()
    register_framework_metrics(m)
    gate = AdmissionGate(max_queue_depth=64, name="generate", metrics=m)
    # metrics= attaches the Manager to the hbm registry too (the
    # generator calls hbm.set_metrics), so the arbiter's shed counter
    # exports alongside the gate's
    eng = tiny_engine(gate=gate, metrics=m)
    next_p = prompts(seed=6)
    try:
        with chaos.scope(chaos.ChaosSchedule(seed=1).on(
                chaos.HBM_ALLOC, error=chaos.ResourceExhausted, every=1)):
            with pytest.raises(TooManyRequests):
                eng.generate(next_p(), max_new_tokens=4).tokens()
        # the gate's shed surface counted it (same counters a queue
        # shed lands in), alongside the arbiter's own subsystem counter
        assert gate.stats()["sheds"] == 1
        text = m.render_prometheus()
        assert 'app_tpu_shed_total' in text
        assert 'app_tpu_hbm_shed_total{subsystem="engine"} 1' in text
    finally:
        eng.close()


def test_storm_during_recovery_keeps_deviceloss_contract():
    # DeviceLost recovery reallocates through hbm.alloc now; with no
    # storm active the realloc must settle the SAME lease keys (set
    # semantics — no double count) and serving resumes
    eng = tiny_engine(prefix_cache_slots=2, prefix_store_min=16)
    next_p = prompts(seed=7)
    try:
        before = hbm.live_bytes()
        with chaos.scope(chaos.ChaosSchedule(seed=2).on(
                chaos.GENERATOR_STEP, error=chaos.DeviceLost, every=1,
                limit=1)):
            with pytest.raises(Exception):
                eng.generate(next_p(), max_new_tokens=4).tokens()
        # recovered: same accounting figures, engine serves again
        deadline = 50
        while eng.down is None and deadline:
            out = eng.generate(next_p(), max_new_tokens=4)
            try:
                toks = out.tokens()
                assert len(toks) == 4
                break
            except Exception:
                deadline -= 1
        assert eng.down is None
        assert hbm.live_bytes() == before
    finally:
        eng.close()


# -- batcher: reclaim-then-retry + shed ---------------------------------------

def test_batcher_transient_oom_reclaims_and_retries():
    reclaimed = []
    o = object()
    hbm.lease("kvcache-t0", 64, owner=o, tag="p", priority=hbm.PRI_CACHE,
              reclaim=lambda need: reclaimed.append(need) or 64)
    calls = {"n": 0}

    def runner(items):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return [x * 2 for x in items]

    with CoalescingBatcher(runner, max_batch=2, max_delay=0.001,
                           use_native=False) as b:
        assert b.submit(3, timeout=5) == 6
    assert calls["n"] == 2
    assert reclaimed  # the retry ran an arbiter reclaim pass first


def test_batcher_persistent_oom_sheds_429():
    def runner(items):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with CoalescingBatcher(runner, max_batch=2, max_delay=0.001,
                           use_native=False) as b:
        with pytest.raises(TooManyRequests) as ei:
            b.submit(1, timeout=5)
    assert ei.value.status_code == 429
    assert ei.value.retry_after is not None
    assert hbm.arbiter_stats()["sheds"] == {"batcher": 1}


def test_batcher_chaos_injection_recovers_via_retry():
    with CoalescingBatcher(lambda items: [x + 1 for x in items],
                           max_batch=2, max_delay=0.001,
                           use_native=False) as b:
        with chaos.scope(chaos.ChaosSchedule(seed=1).on(
                chaos.BATCHER_DISPATCH, error=chaos.ResourceExhausted,
                every=1)):
            # injected at the seam, retried WITHOUT re-injection: the
            # reclaim-then-retry contract absorbs a transient fault
            assert b.submit(5, timeout=5) == 6


def test_batcher_non_oom_errors_propagate_untouched():
    def runner(items):
        raise ValueError("boom")

    with CoalescingBatcher(runner, max_batch=2, max_delay=0.001,
                           use_native=False) as b:
        with pytest.raises(ValueError):
            b.submit(1, timeout=5)


# -- config + surfaces --------------------------------------------------------

def test_configure_budget_mb_and_health_surface():
    hbm.configure(budget_mb=64)
    assert hbm.budget() == 64 << 20
    eng = tiny_engine()
    try:
        from gofr_tpu.tpu import TPUEngine

        t = TPUEngine()
        t.generator = eng
        details = t.health_check().details
        arb = details["hbm_arbiter"]
        assert arb["budget_bytes"] == 64 << 20
        assert arb["in_use_bytes"] > 0
        t.generator = None
        t.close()
    finally:
        eng.close()


def test_configure_explicit_global_still_resolves_per_device(monkeypatch):
    # TPU_HBM_BUDGET_MB predates the per-device budget: setting it
    # alone must NOT leave per-device arbitration off on accelerator
    # backends (the early-return regression), and resolution must read
    # LOCAL devices — under the distributed runtime jax.devices() is
    # the pod list while this process only owns its own chips' HBM.
    class _Dev:
        platform = "tpu"

        @staticmethod
        def memory_stats():
            return {"bytes_limit": 100 << 20}

    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(), _Dev()])
    got = hbm.configure(budget_mb=64, headroom=0.1)
    assert got == hbm.budget() == 64 << 20  # explicit global wins
    assert hbm.device_budget() == int((100 << 20) * 0.9)
    hbm.reset()
    # and the mirror: explicit per-device alone resolves the global
    # from per_dev * local device count
    hbm.configure(device_budget_mb=32, headroom=0.1)
    assert hbm.device_budget() == 32 << 20
    assert hbm.budget() == int((100 << 20) * 0.9) * 2


def test_per_device_lease_failure_names_the_device():
    # no global budget at all: only the per-device bound can fail, and
    # the 429 must carry the device and ITS figures (check()'s
    # "sub@devN" convention), not budget=None/global in-use
    hbm.set_device_budget(8 << 20)
    o = object()
    hbm.lease("engine", 6 << 20, owner=o, tag="cache", device="3")
    with pytest.raises(hbm.HBMExhausted) as ei:
        hbm.lease("engine", 4 << 20, owner=o, tag="scratch", device="3")
    msg = str(ei.value)
    assert "@dev3" in msg
    # the DEVICE's budget and in-use, not the (unset) global ones —
    # with budget=None the old path rendered no figures at all
    assert "budget 8 MiB" in msg and "in use 6 MiB" in msg


def test_device_gauge_zeroes_when_device_entries_vanish():
    # a series that just STOPS updating reads as phantom in-use on a
    # dead/idle chip forever — release must push an explicit 0 per
    # device (the subsystem gauge's zero-on-release contract)
    m = Manager()
    register_framework_metrics(m)
    hbm.set_metrics(m)
    try:
        o = object()
        hbm.lease("engine", 10, owner=o, tag="c", device="0")
        hbm.lease("engine", 20, owner=o, tag="c", device="1")
        text = m.render_prometheus()
        assert 'app_tpu_hbm_device_in_use_bytes{device="1"} 20' in text
        hbm.release("engine", owner=o)
        text = m.render_prometheus()
        assert 'app_tpu_hbm_device_in_use_bytes{device="0"} 0' in text
        assert 'app_tpu_hbm_device_in_use_bytes{device="1"} 0' in text
    finally:
        hbm.set_metrics(None)


def test_device_budget_bounds_deviceless_group():
    # device-less entries are ONE implicit group (a single-device
    # process's default chip): on a multi-chip host the auto budget is
    # per_dev * n_local, so without this check a non-mesh engine could
    # overcommit its one chip n_local-fold before anything bound it
    hbm.set_device_budget(8 << 20)
    o = object()
    hbm.lease("engine", 6 << 20, owner=o, tag="cache")
    with pytest.raises(hbm.HBMExhausted) as ei:
        hbm.lease("engine", 4 << 20, owner=o, tag="scratch")
    msg = str(ei.value)
    assert "@dev" not in msg  # device-less failure names the plain sub
    assert "budget 8 MiB" in msg and "in use 6 MiB" in msg
    # and a device-keyed lease is NOT charged against the "" group
    hbm.lease("engine", 7 << 20, owner=o, tag="shard", device="2")


def test_arbiter_stats_lease_table_shape():
    o = object()
    hbm.lease("engine", 10, owner=o, tag="cache",
              priority=hbm.PRI_SERVING)
    hbm.lease("engine", 20, owner=o, tag="scratch",
              priority=hbm.PRI_SCRATCH, reclaim=lambda n: 0)
    rows = hbm.arbiter_stats()["leases"]
    by_tag = {r["tag"]: r for r in rows}
    assert by_tag["cache"]["priority"] == "serving"
    assert by_tag["cache"]["reclaimable"] is False
    assert by_tag["scratch"]["priority"] == "scratch"
    assert by_tag["scratch"]["reclaimable"] is True
    assert by_tag["scratch"]["bytes"] == 20
