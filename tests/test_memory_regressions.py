"""Behavioral regression tests for the GL2xx fixes (ISSUE 6).

Every true finding the resource-lifetime pass surfaced was fixed by
threading the hbm accounting API through the serving modules; these
tests pin the BEHAVIOR those fixes bought:

  - the registry attributes the engine's persistent buffers (serving
    cache, prefix pool, scratch row, LoRA stacks) by subsystem, with
    figures matching the actual tree bytes;
  - close() releases the instance's accounting (the hbmwatch session
    gate relies on it);
  - recovery re-accounts the reallocated buffers instead of double-
    counting (set semantics per (subsystem, owner, tag));
  - steady-state serving is leak-flat: repeated requests through the
    contiguous engine, the prefix-cache store/restore path, and the
    paged engine grow live device bytes by ZERO after warmup — the
    exact regime whose violation killed the flat prefix cache;
  - the Prometheus gauge face: app_tpu_device_bytes{subsystem=...}
    tracks accounting changes and lands on the metrics text format.
"""

import jax
import numpy as np
import pytest

from gofr_tpu.metrics import Manager, register_framework_metrics
from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.testutil.hbmwatch import attribution
from gofr_tpu.tpu import GenerationEngine, hbm

TINY = LLAMA_CONFIGS["tiny"]


def tiny_engine(**kw):
    cfg = kw.pop("cfg", TINY)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prompt_buckets", (16, 32))
    return GenerationEngine(cfg, params, **kw)


def prompt(rng, n=24):
    return rng.integers(1, TINY.vocab_size, size=n)


# -- registry unit behavior ---------------------------------------------------

def test_account_set_semantics_and_release():
    hbm.reset()
    owner = object()
    a = np.zeros((4, 4), np.float32)
    hbm.account("engine", a, owner=owner, tag="cache")
    assert hbm.live_bytes() == {"engine": 64}
    # re-account the same key (recovery/replacement): REPLACES
    hbm.account("engine", np.zeros((8, 4), np.float32),
                owner=owner, tag="cache")
    assert hbm.live_bytes() == {"engine": 128}
    # distinct tag adds
    hbm.account("engine", a, owner=owner, tag="scratch")
    assert hbm.live_bytes() == {"engine": 192}
    hbm.account("kvcache-t0", a, owner=owner, tag="pool")
    assert hbm.live_bytes()["kvcache-t0"] == 64
    # owner-scoped release drops everything the instance accounted
    released = hbm.release(owner=owner)
    assert released == 256
    assert hbm.live_bytes() == {}


def test_account_two_owners_attribute_independently():
    hbm.reset()
    o1, o2 = object(), object()
    a = np.zeros((4,), np.float32)
    hbm.account("engine", a, owner=o1, tag="cache")
    hbm.account("engine", a, owner=o2, tag="cache")
    assert hbm.live_bytes() == {"engine": 32}
    hbm.release(owner=o1)
    assert hbm.live_bytes() == {"engine": 16}
    hbm.release(owner=o2)
    assert hbm.live_bytes() == {}


def test_dead_owner_without_close_releases_on_gc():
    # an __init__ that OOMs after its first account() never reaches
    # close(); the finalizer safety net must clear the phantom bytes
    # when the half-built owner is collected (and a later reused id()
    # can therefore never alias a dead owner's entries)
    import gc

    hbm.reset()

    class Owner:
        pass

    o = Owner()
    hbm.account("engine", np.zeros((8,), np.float32), owner=o)
    assert hbm.live_bytes() == {"engine": 32}
    del o
    gc.collect()
    assert hbm.live_bytes() == {}


@pytest.mark.parametrize("held", ["table", "push", "sink"])
def test_owner_collected_under_a_lock_of_its_own_thread_does_not_wait(held):
    # the collector runs an owner's finalizer on whichever thread it
    # interrupts: one inside the registry's critical sections, or a
    # sink's. None of those locks is re-entrant, so a finalizer that
    # takes one waits for its own thread: a tier-1 worker stood so in
    # arbiter_stats (health_check) until the run's limit cut it (PR 41)
    import threading

    hbm.reset()
    m = Manager()
    register_framework_metrics(m)
    hbm.set_metrics(m)

    class Owner:
        pass

    alive = [Owner()]
    hbm.lease("engine", 32, owner=alive[0], reclaim=lambda need: 0)
    hbm.account("engine", np.zeros((8,), np.float32), owner=alive[0])
    lock = {"table": hbm._registry._mu, "push": hbm._registry._push_mu,
            "sink": m._get(hbm.GAUGE, "gauge").lock}[held]
    out = threading.Event()

    def inside():
        with lock:
            alive.clear()       # the last reference: finalized here
        out.set()

    t = threading.Thread(target=inside, daemon=True)
    t.start()
    t.join(10.0)
    try:
        assert out.is_set(), f"the finalizer waited for the {held} lock"
        # the next call from outside drops the entries, the lease with
        # them, and pushes the subsystem's gauge
        assert hbm.live_bytes() == {}
        assert hbm.arbiter_stats()["leases"] == []
        assert 'app_tpu_device_bytes{subsystem="engine"} 0' \
            in m.render_prometheus()
    finally:
        hbm.set_metrics(None)
        hbm.reset()


def test_two_metrics_sinks_both_receive_pushes():
    # two engines with two Managers (A/B serving, tests): registering
    # B must not stop A's exporter from seeing later changes
    hbm.reset()
    ma, mb = Manager(), Manager()
    register_framework_metrics(ma)
    register_framework_metrics(mb)
    hbm.set_metrics(ma)
    hbm.set_metrics(mb)
    try:
        owner = object()
        hbm.account("engine", np.zeros((16,), np.float32), owner=owner)
        for m in (ma, mb):
            assert 'app_tpu_device_bytes{subsystem="engine"} 64' \
                in m.render_prometheus()
        hbm.release(owner=owner)
        for m in (ma, mb):
            assert 'app_tpu_device_bytes{subsystem="engine"} 0' \
                in m.render_prometheus()
    finally:
        hbm.set_metrics(None)


def test_tree_nbytes_counts_leaves_and_skips_none():
    tree = {"k": np.zeros((2, 2), np.float32),
            "scale": None,
            "nested": [np.zeros((4,), np.int8)]}
    assert hbm.tree_nbytes(tree) == 16 + 4


# -- engine accounting (the GL202 fixes) --------------------------------------

def test_engine_accounts_cache_and_pool_and_releases_on_close():
    hbm.reset()
    eng = tiny_engine(prefix_cache_slots=2, prefix_store_min=16)
    try:
        live = hbm.live_bytes()
        assert live["engine"] == hbm.tree_nbytes(eng.cache)
        assert live["kvcache-t0"] == hbm.tree_nbytes(eng._pool)
        assert live["engine"] > 0 and live["kvcache-t0"] > 0
    finally:
        eng.close()
    assert hbm.live_bytes() == {}, \
        "close() must release the instance's accounting"


def test_paged_engine_accounts_pool_cache():
    hbm.reset()
    eng = tiny_engine(paged_blocks=10, paged_block_size=16)
    try:
        # "engine" = the block pool + the dense chunk scratch row
        # (long-prompt admission path allocates it alongside)
        want = hbm.tree_nbytes(eng.cache) + hbm.tree_nbytes(eng._scratch)
        assert hbm.live_bytes()["engine"] == want
    finally:
        eng.close()
    assert hbm.live_bytes() == {}


def test_lora_stacks_accounted():
    hbm.reset()
    eng = tiny_engine(lora_adapters=2, lora_rank=4)
    try:
        live = hbm.live_bytes()
        assert live.get("lora", 0) > 0
    finally:
        eng.close()
    assert hbm.live_bytes() == {}


def test_recovery_reaccounts_instead_of_double_counting():
    hbm.reset()
    eng = tiny_engine(prefix_cache_slots=2, prefix_store_min=16)
    try:
        before = hbm.live_bytes()
        rng = np.random.default_rng(0)
        eng.generate(prompt(rng), max_new_tokens=4).tokens()
        # force the loop's recovery path: poison the device cache so
        # the next dispatch fails (the handler reallocates + reaccounts)
        eng.cache = None
        try:
            eng.generate(prompt(rng), max_new_tokens=4).tokens()
        except Exception:
            pass  # this request fails; recovery runs in the loop

        def alive_again():
            s = eng.generate(prompt(rng), max_new_tokens=4)
            return len(s.tokens())

        assert alive_again() > 0, "engine must recover"
        after = hbm.live_bytes()
        assert after == before, \
            f"recovery must re-account, not double-count: {after}"
    finally:
        eng.close()


# -- steady-state leak flatness (the GL203 regime) ----------------------------

def test_serving_steady_state_is_leak_flat(hbmwatch):
    hbm.reset()
    eng = tiny_engine()
    rng = np.random.default_rng(1)
    try:
        def one_request():
            eng.generate(prompt(rng), max_new_tokens=4).tokens()

        hbmwatch.assert_flat(one_request, warmup=3, iters=3,
                             label="contiguous serving")
    finally:
        eng.close()


def test_prefix_cache_steady_state_is_leak_flat(hbmwatch):
    # the EXACT shape that killed the flat prefix cache: repeated
    # store/restore traffic must not grow device bytes once the pool
    # is at capacity (LRU eviction reuses rows)
    hbm.reset()
    eng = tiny_engine(prefix_cache_slots=2, prefix_store_min=16)
    rng = np.random.default_rng(2)
    shared = prompt(rng, 32)
    try:
        def one_request():
            tail = prompt(rng, 8)
            eng.generate(np.concatenate([shared, tail]),
                         max_new_tokens=4).tokens()

        hbmwatch.assert_flat(one_request, warmup=4, iters=3,
                             label="prefix store/restore")
    finally:
        eng.close()


def test_paged_steady_state_is_leak_flat(hbmwatch):
    hbm.reset()
    eng = tiny_engine(paged_blocks=12, paged_block_size=16)
    rng = np.random.default_rng(3)
    try:
        def one_request():
            eng.generate(prompt(rng), max_new_tokens=4).tokens()

        hbmwatch.assert_flat(one_request, warmup=3, iters=3,
                             label="paged serving")
    finally:
        eng.close()


# -- metric + attribution faces ----------------------------------------------

def test_device_bytes_gauge_tracks_registry():
    hbm.reset()
    m = Manager()
    register_framework_metrics(m)
    hbm.set_metrics(m)
    try:
        owner = object()
        hbm.account("engine", np.zeros((16,), np.float32), owner=owner)
        text = m.render_prometheus()
        assert 'app_tpu_device_bytes{subsystem="engine"} 64' in text
        hbm.release(owner=owner)
        text = m.render_prometheus()
        assert 'app_tpu_device_bytes{subsystem="engine"} 0' in text
    finally:
        hbm.set_metrics(None)


def test_attribution_reconciles_accounted_against_live():
    hbm.reset()
    eng = tiny_engine()
    try:
        att = attribution()
        assert att["accounted"].get("engine") == \
            hbm.tree_nbytes(eng.cache)
        assert att["live_bytes"] >= sum(att["accounted"].values())
        assert att["unattributed"] == \
            att["live_bytes"] - sum(att["accounted"].values())
    finally:
        eng.close()


def test_engine_health_reports_device_memory():
    from gofr_tpu.tpu import TPUEngine

    hbm.reset()
    gen = tiny_engine()
    eng = TPUEngine()
    eng.generator = gen
    try:
        details = eng.health_check().details
        assert details["device_memory"].get("engine", 0) > 0
    finally:
        eng.close()


def test_hbmwatch_detects_seeded_device_leak(hbmwatch):
    # the harness itself must fire on the leak shape GL203 describes:
    # a per-request container holding device arrays with no eviction
    import jax.numpy as jnp

    held = []

    def leaky_request():
        held.append(jnp.zeros((256,), jnp.float32))

    with pytest.raises(Exception) as ei:
        hbmwatch.assert_flat(leaky_request, warmup=1, iters=2,
                             label="seeded leak")
    assert "growth" in str(ei.value)

    def fixed_request():
        held.append(jnp.zeros((256,), jnp.float32))
        while len(held) > 2:
            held.pop(0)

    hbmwatch.assert_flat(fixed_request, warmup=3, iters=3,
                         label="fixed")
