"""A prompt within the chunk budget as two dispatches instead of one
padded bucket: the plan made from a table of seconds a program
(gofr_tpu/tpu/prefill_plan.py), the table an engine measures at the end
of its warm-up, and the llama family's split admission against its
one-bucket admission (the other families run the same case in their own
modules: tests/_prefill_split.py)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split

from gofr_tpu import compile_cache
from gofr_tpu.metrics import Manager, register_framework_metrics
from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.observe import Observe
from gofr_tpu.observe.timeline import Timeline
from gofr_tpu.tpu import GenerationEngine
from gofr_tpu.tpu.prefill_plan import first_buckets, ranges

TINY = LLAMA_CONFIGS["tiny"]
BUCKETS = (32, 64, 128, 256, 512)
# the mix's eight prompt lengths (benchmarks/traffic/batch-sat.json)
MIX = (87, 138, 182, 229, 286, 360, 476, 749)


@pytest.fixture(scope="module")
def params():
    return llama.init(TINY, jax.random.PRNGKey(0))


# -- the plan from synthetic tables -------------------------------------------

def _table(seconds, jitter=0.0):
    """Both programs' table from seconds a bucket; the second timing of
    each is ``jitter`` longer."""
    t = {b: [s, s + jitter] for b, s in zip(BUCKETS, seconds)}
    return t, t


def _plan(seconds, jitter=0.0, **kw):
    kw = {"overlapped": True, "max_seq": 2048, **kw}
    return first_buckets(BUCKETS, 512, *_table(seconds, jitter), **kw)


# a pass over the weights is 10 ms and a position 0.116 ms: the two
# small buckets cost the pass, the rest their positions
COMPUTE_BOUND = (0.010, 0.010, 0.015, 0.030, 0.060)
# a pass over the weights outweighs every bucket's positions
WEIGHT_BOUND = (0.030, 0.030, 0.030, 0.031, 0.036)


@pytest.mark.parametrize("length,first,rest", [
    (87, 0, 0), (138, 128, 32), (182, 128, 64), (229, 0, 0),
    (286, 256, 32), (360, 256, 128), (476, 0, 0)])
def test_a_compute_bound_table_splits_what_pads_most(length, first, rest):
    plan = _plan(COMPUTE_BOUND)
    assert plan[length] == first
    if first:
        held = [r for r in ranges(plan, BUCKETS)[BUCKETS[
            np.searchsorted(BUCKETS, length)]] if r["from"] <= length <= r["to"]]
        assert [(r["first"], r["rest"]) for r in held] == [(first, rest)]


def test_the_mix_runs_a_sixth_fewer_positions_under_that_plan():
    plan = _plan(COMPUTE_BOUND)
    one = sum(next(b for b in BUCKETS if b >= n) for n in MIX[:-1])
    ran = sum(plan[n] + next(b for b in BUCKETS if b >= n - plan[n])
              for n in MIX[:-1])
    # 749 runs 512 + 256 either way
    assert (one + 768, ran + 768) == (3200, 2688)


def test_a_weight_bound_table_splits_nothing():
    assert not any(_plan(WEIGHT_BOUND))
    assert ranges(_plan(WEIGHT_BOUND), BUCKETS) == {}


def test_a_tie_stays_one_bucket():
    # 256 + 256 costs what 512 costs
    plan = _plan((0.005, 0.010, 0.015, 0.030, 0.060))
    assert plan[500] == 0
    # and so does a gain the bucket's own two timings cannot tell apart:
    # 286 in 256 + 32 saves 20 ms of 60, the timings of 512 differ by 25
    assert _plan(COMPUTE_BOUND)[286] == 256
    assert _plan(COMPUTE_BOUND, jitter=0.025)[286] == 0


def test_no_table_is_no_plan():
    assert not any(first_buckets(BUCKETS, 512, {}, {}, overlapped=True,
                                 max_seq=2048))
    # the final-chunk programs were not compiled (no chunked admission)
    prefill, _ = _table(COMPUTE_BOUND)
    assert not any(first_buckets(BUCKETS, 512, prefill, {}, overlapped=True,
                                 max_seq=2048))


def test_the_rest_has_to_fit_its_form():
    # a first part of 32 is free and the widest rest nearly so: 100 =
    # 32 + 68 in 512 would begin before the prompt if it overlapped, and
    # end past a cache of 512 rows if it were padded
    buckets = (32, 512)
    table = {32: [0.0, 0.0], 512: [0.5, 0.5]}
    final = {32: [0.0, 0.0], 512: [0.001, 0.001]}
    assert first_buckets(buckets, 512, table, final, overlapped=True,
                         max_seq=2048)[100] == 0
    assert first_buckets(buckets, 512, table, final, overlapped=False,
                         max_seq=512)[100] == 0
    assert first_buckets(buckets, 512, table, final, overlapped=False,
                         max_seq=1024)[100] == 32


# -- the llama family: both forms of row ----------------------------------------

@pytest.mark.parametrize("kv_dtype,tol", [(None, 2e-4), (jnp.int8, 0.05)])
def test_a_split_admission_is_the_one_bucket_admission(params, kv_dtype, tol):
    """Overlapped (this family's last chunk), model-type and int8 rows."""
    _prefill_split.check(TINY, params, tol=tol, kv_dtype=kv_dtype)


# -- the engine's table, counters and timeline ----------------------------------

def _observed_engine(params):
    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=512))
    eng = GenerationEngine(TINY, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), observe=obs, metrics=m)
    return eng, m, obs


@pytest.fixture(scope="module")
def observed(params):
    """A warmed engine; the tests force its plan and count in differences."""
    eng, m, obs = _observed_engine(params)
    eng.warmup()
    yield eng, m, obs
    eng.close()


def _counter(m, name):
    for line in m.render_prometheus().splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def test_the_warm_up_measures_each_prompt_program_twice(params):
    eng, m, _ = _observed_engine(params)
    try:
        # not warmed: no table, no plan, one padded bucket as before
        pre = eng.stats()["scheduler"]["prefill"]
        assert pre["costs_ms"] is None and pre["plan"] == {}
        assert pre["padded_pct"] is None
        eng.generate(list(range(1, 21)), max_new_tokens=2).tokens()
        pre = eng.stats()["scheduler"]["prefill"]
        assert (pre["admissions"], pre["split"]) == (1, 0)
        assert (pre["prompt_tokens"], pre["positions"]) == (20, 32)
        assert pre["padded_pct"] == 37.5
        assert _counter(m, "app_tpu_prefill_positions_total") == 32
        assert _counter(m, "app_tpu_prefill_prompt_tokens_total") == 20
        assert _counter(m, "app_tpu_prefill_split_total") == 0
        eng.warmup()
        records = eng.stats()["startup"]["warmup"]
        costs = eng.stats()["scheduler"]["prefill"]["costs_ms"]
        # max_seq 128 is past the chunk budget: the final chunks are compiled
        assert sorted(costs) == ["chunk_final", "prefill"]
        for table in costs.values():
            assert sorted(table) == [16, 32]
            assert all(len(ts) == 2 and min(ts) > 0 for ts in table.values())
        assert len(eng._split_first) == 33
        # the timed calls are no records of the account: one a plan's call
        assert sum(r["program"] == "_prefill_jit" for r in records) == 2
        # a later warm-up keeps the table it has
        kept = eng._prefill_costs
        eng.warmup()
        assert eng._prefill_costs is kept
        # and timing compiles nothing: the plan's calls compiled every shape
        programs = compile_cache.clock().snapshot()["programs"]
        with eng._device_lock:
            eng._time_prefills(0, eng._warm_plan(0))
        assert compile_cache.clock().snapshot()["programs"] == programs
        assert eng._prefill_costs is not kept
    finally:
        eng.close()


def test_a_split_counts_its_positions_and_draws_its_chunk(observed):
    eng, m, obs = observed
    before = {k: _counter(m, f"app_tpu_prefill_{k}_total")
              for k in ("split", "positions", "prompt_tokens")}
    n0 = dict(eng._prefill_n)
    eng._split_first = [0] * 17 + [16] * 16
    programs = compile_cache.clock().snapshot()["programs"]
    t0 = time.monotonic()
    stream = eng.generate(list(range(1, 21)), max_new_tokens=2)
    stream.tokens()
    # two dispatches of programs the warm-up compiled: nothing new
    assert compile_cache.clock().snapshot()["programs"] == programs
    after = {k: _counter(m, f"app_tpu_prefill_{k}_total") for k in before}
    assert after["split"] == before["split"] + 1
    assert after["positions"] == before["positions"] + 16 + 16
    assert after["prompt_tokens"] == before["prompt_tokens"] + 20
    pre = eng.stats()["scheduler"]["prefill"]
    assert pre["split"] == n0["split"] + 1
    assert pre["plan"] == {32: [{"from": 17, "to": 32, "first": 16,
                                 "rest": 16}]}
    # the prefill event as it was, the second dispatch a chunk slice of
    # the rest's bucket on the slot's track; no mid-chunk was counted
    events = [e for e in obs.timeline.events() if e[1] >= t0]
    prefill = [e for e in events if e[3] == "prefill"]
    chunk = [e for e in events if e[3] == "chunk"]
    assert len(prefill) == 1 and prefill[0][5] == 20
    assert len(chunk) == 1
    assert chunk[0][4:7] == (stream.trace["slot"], 0, 16)
    assert prefill[0][1] <= chunk[0][1] <= prefill[0][1] + prefill[0][2]
    assert stream.chunks == 0


def test_a_request_cancelled_before_admission_runs_neither_dispatch(observed):
    eng, _, _ = observed
    eng._split_first = [0] * 17 + [16] * 16
    before = dict(eng._prefill_n)
    with eng._device_lock:  # the loop cannot admit meanwhile
        stream = eng.generate(list(range(1, 21)), max_new_tokens=4)
        stream.cancel()
    assert stream.tokens() == []
    # a later admission is the next the counters see
    eng.generate(list(range(1, 9)), max_new_tokens=1).tokens()
    after = eng._prefill_n
    assert after["admissions"] == before["admissions"] + 1
    assert after["split"] == before["split"]
    assert after["positions"] == before["positions"] + 16


def test_the_lattice_counts_its_chunks_and_its_overlap(observed):
    eng, _, _ = observed
    before = dict(eng._prefill_n)
    eng.generate(list(range(1, 71)), max_new_tokens=1).tokens()
    after = eng._prefill_n
    # 70 = 32 + 32 + 6 in 16, overlapped
    assert after["positions"] == before["positions"] + 80
    assert after["prompt_tokens"] == before["prompt_tokens"] + 70
    assert after["split"] == before["split"]


# -- a chunk's attention walks the blocks of cached rows under its start --------

def _walking_engine(params, monkeypatch, block, **kw):
    """An engine whose chunk programs walk a slot's 128 rows ``block`` at
    a time (the one constant, read when a program is traced and when the
    engine asks its family what a dispatch fetched)."""
    from gofr_tpu.ops import attention

    monkeypatch.setattr(attention, "_CHUNK_BLOCK", block)
    m = Manager()
    register_framework_metrics(m)
    eng = GenerationEngine(TINY, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), metrics=m, **kw)
    assert eng._walk_block == min(block, 128)
    return eng, m


def test_chunk_dispatches_count_the_rows_they_walked(params, monkeypatch):
    eng, m = _walking_engine(params, monkeypatch, 16)
    rows = ("cache_rows_walked", "cache_rows_reserved")

    def counted():
        pre = eng.stats()["scheduler"]["prefill"]
        n = tuple(pre[k] for k in rows)
        assert n == (_counter(m, "app_tpu_chunk_rows_walked_total"),
                     _counter(m, "app_tpu_chunk_rows_reserved_total"))
        return n + (pre["walked_pct"],)

    try:
        assert counted() == (0, 0, None)
        # one bucket: the prefill program, no chunk program, no row
        eng.generate(list(range(1, 21)), max_new_tokens=2).tokens()
        assert counted() == (0, 0, None)
        # a split, 20 = 16 + the last 16: the rest starts at 4, one block
        eng._split_first = [0] * 17 + [16] * 16
        eng.generate(list(range(1, 21)), max_new_tokens=2).tokens()
        assert counted() == (16, 128, 12.5)
        # a lattice, 70 = 32 at 0, 32 at 32, the last 16 at 54: no block,
        # two, four of the slot's eight
        eng.generate(list(range(1, 71)), max_new_tokens=2).tokens()
        assert counted() == (16 + 0 + 32 + 64, 128 * 4, 21.88)
    finally:
        eng.close()


@pytest.mark.parametrize("kv_dtype,tol", [(None, 2e-4), (jnp.int8, 0.05)])
def test_the_walk_serves_the_same_whatever_its_block(params, monkeypatch,
                                                     kv_dtype, tol):
    """A lattice, a split and a prefix hit that resumes the lattice past
    position 0, on an engine whose one block spans the slot (every
    reserved row scored and masked, as the one softmax did) and on one
    that walks blocks of 16: the same greedy tokens, logprobs to the
    tolerance the chunked and split tests hold."""
    rng = np.random.default_rng(52)
    long = rng.integers(1, TINY.vocab_size, 70).tolist()
    hit = long[:40] + rng.integers(1, TINY.vocab_size, 45).tolist()
    short = rng.integers(1, TINY.vocab_size, 20).tolist()
    served = {}
    for block in (128, 16):
        eng, _ = _walking_engine(params, monkeypatch, block, kv_dtype=kv_dtype,
                                 prefix_cache_slots=2, prefix_store_min=16)
        try:
            eng._split_first = [0] * 17 + [16] * 16
            served[block] = [
                list(eng.generate(p, max_new_tokens=8, logprobs=True))
                for p in (long, hit, short)]
            assert eng.stats()["prefix_cache"]["hits"] >= 1
            pre = eng.stats()["scheduler"]["prefill"]
            assert pre["split"] == 1 and pre["cache_rows_reserved"] >= 128 * 5
            if block == 128:      # a chunk past position 0 fetches the slot
                assert pre["walked_pct"] > 60
        finally:
            eng.close()
    for one, walked in zip(served[128], served[16]):
        assert [int(t) for t, _ in one] == [int(t) for t, _ in walked]
        assert max(abs(float(a[1]) - float(b[1]))
                   for a, b in zip(one, walked)) < tol
