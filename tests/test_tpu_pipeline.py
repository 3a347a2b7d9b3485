"""Pipelined decode dispatch (depth-2 double-buffered blocks).

The serving loop may keep TWO fused decode blocks in flight on the
device stream: block N+1 is dispatched before block N is reaped, all of
its inputs (cache, PRNG key, slot-state carry) chained on device. These
tests pin the contracts that make that legal:

  - depth-2 streams are token-exact vs depth-1 (contiguous AND paged,
    chunk-lattice admissions interleaving);
  - on-device stop masks (EOS set / budget / capacity in the scan
    carry) retire streams at exactly the position host retirement
    would, and a stream finishing at depth 2 emits no post-EOS tokens;
  - a deadline expiring mid-decode fails the stream and frees its slot
    even with blocks still in flight;
  - device failure mid-pipeline unwinds every in-flight dispatch,
    reseeds once, and the next admission is token-exact;
  - the depth policy (resilience.DecodePipelinePolicy) collapses to 1
    while a latency-class request waits that a free slot can take, or
    spec decode is on (a waiter on a full batch leaves it at 2), and
    stats() exposes the same verdict the loop acts on.
"""

import importlib.util
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import chaos
from gofr_tpu.errors import DeadlineExceeded
from gofr_tpu.models import llama
from gofr_tpu.models.common import LLAMA_CONFIGS
from gofr_tpu.observe import Observe
from gofr_tpu.observe.timeline import Timeline
from gofr_tpu.resilience import Deadline, DecodePipelinePolicy
from gofr_tpu.tpu import GenerationEngine
from gofr_tpu.tpu.generator import GenerationError

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init(TINY, jax.random.PRNGKey(1))


def _engine(params, depth, paged=False, **kw):
    kwargs = dict(slots=4, max_seq=64, prompt_buckets=(8, 16),
                  decode_pipeline=depth)
    if paged:
        kwargs.update(paged_blocks=40, paged_block_size=8)
    kwargs.update(kw)
    return GenerationEngine(TINY, params, **kwargs)


def _reference_greedy(params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, TINY, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


# -- the policy itself --------------------------------------------------------

def test_pipeline_policy_verdicts():
    p = DecodePipelinePolicy(2)
    assert p.target() == 2
    assert p.target(latency_admittable=True) == 1
    assert p.target(lattice_deferred=True) == 1
    assert p.target(spec_decode=True) == 1
    assert DecodePipelinePolicy(1).target() == 1
    assert DecodePipelinePolicy(0).depth == 1  # clamped, never 0


def test_decode_stop_mask_unit():
    """The on-device stop verdict in isolation: EOS-set membership,
    budget exhaustion, capacity — and the EOS_PAD sentinel never
    matching a real token id."""
    toks = jnp.asarray([7, 9, 11, 13], jnp.int32)
    lengths = jnp.asarray([10, 10, 10, 62], jnp.int32)
    budget = jnp.asarray([5, 0, 5, 5], jnp.int32)
    eos = jnp.full((4, 4), llama.EOS_PAD, jnp.int32)
    eos = eos.at[0, 1].set(7)      # slot 0: token IS in its stop set
    eos = eos.at[2, 0].set(99)     # slot 2: stop set misses
    stop = llama.decode_stop_mask(toks, lengths, budget, eos,
                                  jnp.int32(62))
    assert stop.tolist() == [True, True, False, True]


# -- token exactness ----------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True])
def test_depth2_token_exact_vs_depth1(tiny_params, paged):
    """Same seeded workload — short prompts, bucket-lattice prompts, and
    prompts past the largest bucket (chunk interleave ON) — must stream
    identical greedy tokens at depth 1 and depth 2, on both engines."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, TINY.vocab_size, n).tolist()
               for n in (40, 4, 7, 12, 26, 5)]
    outs = {}
    for depth in (1, 2):
        eng = _engine(tiny_params, depth, paged=paged)
        try:
            streams = [eng.generate(p, max_new_tokens=10) for p in prompts]
            outs[depth] = [s.tokens() for s in streams]
        finally:
            eng.close()
    assert outs[1] == outs[2]
    # one oracle spot-check (depth-1 correctness itself is pinned by
    # test_tpu.py; per-prompt full-forward oracles here would only
    # re-buy that coverage at real wall-clock cost)
    assert outs[2][1] == _reference_greedy(tiny_params, prompts[1], 10)


def test_steady_decode_overlaps_reaps(tiny_params):
    """During steady decode (no admissions pending) the depth-2 loop
    must keep a second block queued on-device: reaps observe a
    non-empty pipe and the inter-block gap records 0."""
    eng = _engine(tiny_params, 2)
    try:
        streams = [eng.generate([3, 1, 4, 1 + i], max_new_tokens=32)
                   for i in range(2)]
        for s in streams:
            s.tokens()
        st = eng.stats()["scheduler"]["pipeline"]
        assert st["depth"] == 2
        assert st["overlapped_reaps"] > 0
        assert st["gap_p50_ms"] is not None
    finally:
        eng.close()


def test_depth2_sampling_stays_bounded(tiny_params):
    """Sampled streams (temperature/top-k) at depth 2: lengths honored,
    tokens in range. (No cross-depth exactness claim — the PRNG chain
    advances per dispatched block, and the two depths dispatch
    different block counts.)"""
    eng = _engine(tiny_params, 2)
    try:
        streams = [eng.generate([2, 7, 1], max_new_tokens=9,
                                temperature=0.8, top_k=8)
                   for _ in range(3)]
        for s in streams:
            toks = s.tokens()
            assert len(toks) == 9
            assert all(0 <= t < TINY.vocab_size for t in toks)
    finally:
        eng.close()


# -- on-device stop masks -----------------------------------------------------

@pytest.mark.parametrize("paged", [False, True])
def test_stop_masks_match_host_retirement(tiny_params, paged):
    """A stream hitting EOS at pipeline depth 2 ends at exactly the
    first stop token — no post-EOS tokens from the block that was
    already in flight — for small stop sets (on-device), stop SETS, and
    sets wider than EOS_MAX (host-side fallback)."""
    base_eng = _engine(tiny_params, 1, paged=paged)
    try:
        base = base_eng.generate([5, 17, 42, 7], max_new_tokens=12).tokens()
    finally:
        base_eng.close()
    stop = base[2]
    want = base[:base.index(stop) + 1]
    unused = [t for t in range(TINY.vocab_size) if t not in base]
    eng = _engine(tiny_params, 2, paged=paged)
    try:
        for eos in (stop,                                   # single id
                    {stop, unused[0]},                      # on-device set
                    set(unused[:9]) | {stop}):              # > EOS_MAX
            got = eng.generate([5, 17, 42, 7], max_new_tokens=50,
                               eos_id=eos).tokens()
            assert got == want, f"eos={eos!r}"
        # budget stop mid-block at depth 2
        got = eng.generate([5, 17, 42, 7], max_new_tokens=5).tokens()
        assert got == base[:5]
        # the stop-masked slots freed: the engine drains fully
        deadline = time.monotonic() + 5.0
        while eng.stats()["active"] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.stats()["active"] == 0
    finally:
        eng.close()


def test_capacity_stop_on_device(tiny_params):
    """max_seq-bound retirement is part of the on-device stop mask: a
    depth-2 stream asked for more tokens than the cache can hold stops
    at the same position as depth 1."""
    outs = {}
    for depth in (1, 2):
        eng = _engine(tiny_params, depth, max_seq=32)
        try:
            outs[depth] = eng.generate([5, 17, 42, 7],
                                       max_new_tokens=500).tokens()
        finally:
            eng.close()
    assert outs[1] == outs[2]
    assert len(outs[2]) > 0


# -- deadlines mid-pipeline ---------------------------------------------------

def test_deadline_expiry_with_blocks_in_flight(tiny_params):
    """A stream whose wire deadline runs out mid-decode fails with
    DeadlineExceeded at the next reap — with pipelined blocks still in
    flight — and its slot serves the next request."""
    eng = _engine(tiny_params, 2, max_seq=128, prompt_buckets=(8,),
                  decode_block=2)
    try:
        want = eng.generate([5, 17, 42, 7], max_new_tokens=6).tokens()
        d = Deadline.after(3600.0)
        s = eng.generate([3, 1, 4], max_new_tokens=4000, deadline=d)
        it = iter(s)
        next(it)  # admitted and decoding, pipelined blocks in flight
        next(it)
        d.at = 0.0  # the wire deadline just ran out mid-decode
        with pytest.raises(DeadlineExceeded):
            for _ in it:
                pass
        # slot freed and the engine keeps serving, token-exact
        got = eng.generate([5, 17, 42, 7], max_new_tokens=6).tokens()
        assert got == want
        assert eng.stats()["active"] == 0
    finally:
        eng.close()


# -- recovery mid-pipeline ----------------------------------------------------

def test_chaos_step_mid_pipeline_recovers_token_exact(tiny_params):
    """A seeded GENERATOR_STEP DeviceLost raised while a block is in
    flight (the pipeline keeps one queued between iterations): recovery
    unwinds the in-flight dispatches, reseeds ONCE, and the next
    admission streams the exact greedy tokens."""
    eng = _engine(tiny_params, 2)
    try:
        want = eng.generate([5, 17, 42, 7], max_new_tokens=12).tokens()
        # the third GENERATOR_STEP firing lands with an un-reaped block
        # queued (iterations after the first top up an existing pipe)
        sched = chaos.ChaosSchedule(seed=0).on(
            chaos.GENERATOR_STEP, error=chaos.DeviceLost, every=3, limit=1)
        with chaos.scope(sched):
            with pytest.raises(GenerationError):
                eng.generate([5, 17, 42, 7], max_new_tokens=12).tokens()
        got = eng.generate([5, 17, 42, 7], max_new_tokens=12).tokens()
        assert got == want
        assert eng.down is None
        assert eng._recoveries == 1  # one reseed for the whole pipe
    finally:
        eng.close()


def test_dispatch_failure_mid_topup_unwinds_pipe(tiny_params):
    """A device failure surfacing from the SECOND dispatch of a top-up
    (one block already in flight, the failing one mid-dispatch having
    consumed the donated cache) must unwind both and recover."""
    eng = _engine(tiny_params, 2)
    try:
        want = eng.generate([5, 17, 42, 7], max_new_tokens=12).tokens()
        calls = {"n": 0}
        orig = eng._step_jit

        def flaky(*a, **k):
            calls["n"] += 1
            if calls["n"] == 4:  # a top-up call with one block in flight
                raise RuntimeError("injected mid-pipeline device loss")
            return orig(*a, **k)

        eng._step_jit = flaky
        with pytest.raises(GenerationError):
            eng.generate([5, 17, 42, 7], max_new_tokens=16).tokens()
        eng._step_jit = orig
        got = eng.generate([5, 17, 42, 7], max_new_tokens=12).tokens()
        assert got == want
        assert eng.down is None
        assert eng._recoveries == 1
    finally:
        eng.close()


# -- the depth policy in the live loop ---------------------------------------

def _pipeline(eng) -> dict:
    return eng.stats()["scheduler"]["pipeline"]


def _poll(cond, timeout: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.002)
    return cond()


@pytest.mark.parametrize("slot_free", [False, True])
def test_depth_drops_only_for_an_admittable_waiter(tiny_params, slot_free):
    """Stats-polled. Every slot busy and a latency-class request
    queued: nothing can be admitted, so the top-ups keep targeting
    depth 2 and reaps go on overlapping while it waits. A slot free
    and a latency-class request queued (the loop held off admission by
    the device lock): depth 1 until it is admitted, then 2 again."""
    eng = _engine(tiny_params, 2, slots=2)
    try:
        n_bg = 1 if slot_free else 2
        bg = [eng.generate([2, 3 + i], max_new_tokens=48)
              for i in range(n_bg)]
        for it in [iter(s) for s in bg]:
            next(it)  # admitted and decoding
        if slot_free:
            with eng._device_lock:  # the loop cannot admit meanwhile
                waiter = eng.generate([9, 9], max_new_tokens=4)
                st = _pipeline(eng)
                assert st["latency_admittable"]
                assert st["depth"] == 2 and st["target_depth"] == 1
            assert waiter.tokens()
            assert _poll(lambda: _pipeline(eng)["target_depth"] == 2)
            assert not _pipeline(eng)["latency_admittable"]
        else:
            before = _pipeline(eng)["overlapped_reaps"]
            waiter = eng.generate([9, 9], max_new_tokens=4)  # latency
            st = _pipeline(eng)
            assert not st["latency_admittable"]
            assert st["target_depth"] == 2
            # the queue stands and the batch is full: block N+1 is
            # queued while block N is reaped
            assert _poll(
                lambda: _pipeline(eng)["overlapped_reaps"] >= before + 3
                or not eng.stats()["active"])
            assert _pipeline(eng)["overlapped_reaps"] >= before + 3
            assert waiter.tokens()  # served once a slot freed
            assert _pipeline(eng)["target_depth"] == 2
        for s in bg:
            s.tokens()
    finally:
        eng.close()


def test_spec_decode_pins_depth_one(tiny_params):
    """Verify windows are built from host-delivered history: a spec
    engine never pipelines, and says so in stats()."""
    eng = _engine(tiny_params, 2, spec_decode_k=3)
    try:
        st = eng.stats()["scheduler"]["pipeline"]
        assert st["depth"] == 2 and st["target_depth"] == 1
        # and the serving path stays exact through the forced depth
        got = eng.generate([5, 17, 42, 7], max_new_tokens=8).tokens()
        assert got == _reference_greedy(tiny_params, [5, 17, 42, 7], 8)
    finally:
        eng.close()


# -- a standing latency-class queue, and the block behind a prefill ----------

# 12 untagged (latency-class) requests on 4 slots: 8 stand in line from
# the start. Short, bucket (8, 16) and chunked (> 16) prompts; budgets
# that end inside a block of 4 as well as on its edge.
_QUEUE_LENS = (40, 4, 7, 12, 26, 5, 16, 8, 33, 3, 14, 9)
_QUEUE_BUDGETS = (10, 5, 7, 13, 6, 9, 11, 3, 8, 14, 2, 6)


def standing_queue_outputs(make_engine, vocab):
    """{depth: tokens of every request} for the workload above;
    ``make_engine(depth)`` builds a 4-slot engine. Shared with the
    latent family's test."""
    rng = np.random.default_rng(29)
    prompts = [rng.integers(1, vocab, n).tolist() for n in _QUEUE_LENS]
    outs = {}
    for depth in (1, 2):
        eng = make_engine(depth)
        _hold_blocks(eng)  # admissions find blocks in flight, as on a chip
        try:
            streams = [eng.generate(p, max_new_tokens=n)
                       for p, n in zip(prompts, _QUEUE_BUDGETS)]
            outs[depth] = [s.tokens() for s in streams]
            st = eng.stats()["scheduler"]["pipeline"]
            if depth == 2:
                # the queue did not pin the loop at depth 1
                assert st["overlapped_reaps"] > 0
            else:
                assert st["overlapped_reaps"] == 0
        finally:
            eng.close()
    assert [len(o) for o in outs[1]] == list(_QUEUE_BUDGETS)
    return prompts, outs


@pytest.mark.parametrize("paged", [False, True])
def test_standing_latency_queue_token_exact_across_depths(tiny_params, paged):
    prompts, outs = standing_queue_outputs(
        lambda depth: _engine(tiny_params, depth, paged=paged),
        TINY.vocab_size)
    assert outs[1] == outs[2]
    for i in (1, 6, 9):  # a short, a bucket-edge and a late-admitted one
        assert outs[2][i] == _reference_greedy(tiny_params, prompts[i],
                                               _QUEUE_BUDGETS[i])


class _NotYet:
    """A readiness probe that answers False to its first polls."""

    def __init__(self, polls: int):
        self.polls = polls

    def is_ready(self) -> bool:
        self.polls -= 1
        return self.polls < 0


def _hold_blocks(eng, polls: int = 2) -> None:
    """Every dispatched block reads not-ready to the loop's first
    polls, as a block does on a chip where it takes tens of
    milliseconds: the tiny model's blocks are done before the loop
    looks, and an admission would otherwise always find the pipe
    reaped (the synchronous pass)."""
    orig = eng._tick

    def tick(**kw):
        inflight = orig(**kw)
        if inflight is not None:
            inflight.arrays = (*inflight.arrays, _NotYet(polls))
        return inflight

    eng._tick = tick


def _spy_trail(eng) -> list:
    """Record (blocks on the pipe before, after) of every _trail call;
    a call made while a latency-class waiter could be admitted (its
    prefill goes first) must queue nothing."""
    seen = []
    orig = eng._trail

    def trail():
        before, waiter = len(eng._pipe), eng._latency_admittable()
        orig()
        assert not (waiter and len(eng._pipe) > max(before, 1))
        seen.append((before, len(eng._pipe)))

    eng._trail = trail
    return seen


@pytest.mark.parametrize("paged", [False, True])
def test_refilled_slot_behind_trailing_block_is_exact(tiny_params, paged):
    """A slot freed and refilled while the other decodes: the refill's
    prefill gets a decode block queued behind it before its first token
    is fetched. In that block the new slot is inactive and its frozen
    cursor is the prompt length: the garbage write must land outside
    the prompt just written (paged: prompts of exactly one and two
    blocks of 8, where a clamped table row would route it INTO the
    last prompt block). Old and new requests return the reference's
    greedy tokens."""
    eng = _engine(tiny_params, 2, paged=paged, slots=2)
    _hold_blocks(eng)
    seen = _spy_trail(eng)
    try:
        rng = np.random.default_rng(31)
        work = [(rng.integers(1, TINY.vocab_size, n).tolist(), new)
                for n, new in ((6, 40), (5, 5), (8, 9), (16, 7), (11, 6))]
        streams = [eng.generate(p, max_new_tokens=new) for p, new in work]
        for (p, new), s in zip(work, streams):
            assert s.tokens() == _reference_greedy(tiny_params, p, new)
        # it engaged behind an un-reaped block (in-flight admission)
        assert any(before >= 1 and after == before + 1
                   for before, after in seen), seen
        assert max(after for _, after in seen) <= 2  # never past the depth
    finally:
        eng.close()


def test_trailing_block_waits_for_the_reserved_slots_waiter(tiny_params):
    """Three slots, one reserved for the latency class. A throughput
    and a latency request arrive together behind one decoding stream
    (submitted under the device lock, so one in-flight pass sees
    both): whichever prefill goes first, no block is queued behind it
    while the latency request can still be admitted (_spy_trail holds
    that), and none past the configured depth. Everybody's tokens are
    the reference's."""
    from gofr_tpu.resilience import SLO_THROUGHPUT

    eng = _engine(tiny_params, 2, slots=3, slo_latency_slots=1)
    _hold_blocks(eng, polls=4)
    seen = _spy_trail(eng)
    try:
        rng = np.random.default_rng(41)
        work = [(rng.integers(1, TINY.vocab_size, n).tolist(), new)
                for n, new in ((6, 28), (7, 6), (8, 9))]
        streams = [eng.generate(*work[0][:1], max_new_tokens=work[0][1])]
        next(iter(streams[0]))  # decoding: blocks are in flight
        with eng._device_lock:
            streams.append(eng.generate(work[1][0], max_new_tokens=6,
                                        slo_class=SLO_THROUGHPUT))
            streams.append(eng.generate(work[2][0], max_new_tokens=9))
        got = [streams[0].tokens()] + [s.tokens() for s in streams[1:]]
        first = _reference_greedy(tiny_params, *work[0])
        assert got[0] == first[1:]  # its first token went to next()
        for (p, new), toks in zip(work[1:], got[1:]):
            assert toks == _reference_greedy(tiny_params, p, new)
        assert len(seen) == 3 and max(a for _, a in seen) <= 2, seen
    finally:
        eng.close()


def test_chaos_step_between_prefill_and_trailing_block_recovers(tiny_params):
    """A seeded GENERATOR_STEP DeviceLost fired by the top-up that
    queues a block behind an admission's prefill: the admission under
    way and the stream already decoding fail, the pipe is unwound, the
    waiter still in line is served token-exact after recovery, and
    nobody hangs."""
    eng = _engine(tiny_params, 2, slots=2)
    sched = chaos.ChaosSchedule(seed=0).on(
        chaos.GENERATOR_STEP, error=chaos.DeviceLost, every=1, limit=1)
    _hold_blocks(eng)
    orig = eng._trail

    def trail():
        if not eng._pipe or sched.stats()["errors_fired"]:
            return orig()
        with chaos.scope(sched):  # only the loop thread fires this seam
            orig()

    try:
        rng = np.random.default_rng(37)
        work = [(rng.integers(1, TINY.vocab_size, n).tolist(), new)
                for n, new in ((6, 40), (5, 5), (8, 9), (7, 6))]
        want = _reference_greedy(tiny_params, *work[3])
        eng._trail = trail
        streams = [eng.generate(p, max_new_tokens=new) for p, new in work]
        failed = 0
        for s in streams[:3]:
            try:
                s.tokens()
            except GenerationError:
                failed += 1
        assert sched.stats()["errors_fired"] == {chaos.GENERATOR_STEP: 1}
        assert failed >= 2  # the long stream and the admission under way
        assert streams[3].tokens() == want
        assert eng.down is None and eng._recoveries == 1
        assert not eng._pipe or eng.stats()["active"] == 0
    finally:
        eng.close()


def test_overlapped_reap_reader_matches_the_programs_count(tiny_params):
    """benchmarks/metrics/sched.overlapped_reap_pct.py reads, from the
    decode events alone, the share the program counts itself
    (overlapped_reaps / reaps): a block was queued behind a reap
    exactly when the next decode event starts before this one ends."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "metrics", "sched.overlapped_reap_pct.py")
    spec = importlib.util.spec_from_file_location("overlapped_reap", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def ev(t0, t1):
        return (0, t0, t1 - t0, "decode", (0,), 4, 0, 0)

    # dispatch 0..1, 0.5..2 (queued behind the first), 2.1..3 (alone)
    ctx = SimpleNamespace(trace={"span": (0.0, 10.0)},
                          timeline=[ev(0.0, 1.0), ev(0.5, 2.0),
                                    ev(2.1, 3.0)])
    assert reader.read(ctx) == pytest.approx(100.0 / 3)
    # only reaps inside the traced span count
    ctx.trace = {"span": (1.5, 10.0)}
    assert reader.read(ctx) == pytest.approx(0.0)
    assert reader.read(SimpleNamespace(trace=None, timeline=[])) is None
    assert reader.read(SimpleNamespace(trace={"span": (0.0, 1.0)},
                                       timeline=[])) is None

    obs = Observe(timeline=Timeline(capacity=4096))
    eng = _engine(tiny_params, 2, observe=obs)
    try:
        t0 = time.monotonic()
        streams = [eng.generate([3, 1, 4, 1 + i], max_new_tokens=20 + 3 * i)
                   for i in range(7)]
        for s in streams:
            s.tokens()
        assert _poll(lambda: not eng._pipe)
        st = eng.stats()["scheduler"]["pipeline"]
        live = SimpleNamespace(trace={"span": (t0, time.monotonic())},
                               timeline=obs.timeline.events())
        assert 0 < st["overlapped_reaps"] < st["reaps"]
        assert reader.read(live) == pytest.approx(
            100.0 * st["overlapped_reaps"] / st["reaps"])
    finally:
        eng.close()


# -- observability ------------------------------------------------------------

def test_timeline_gap_track_exports():
    from gofr_tpu.observe.timeline import Timeline

    tl = Timeline(capacity=64)
    t = time.monotonic()
    tl.dispatch_gap(t, t + 0.004)
    events = tl.chrome_trace()["traceEvents"]
    gap = next(e for e in events if e.get("name") == "dispatch gap")
    assert gap["ph"] == "X" and gap["tid"] == 2
    assert abs(gap["dur"] - 4000.0) < 100.0
    # the device-stream track is named in the metadata header
    assert any(e.get("name") == "thread_name" and e.get("tid") == 2
               and e["args"]["name"] == "device stream" for e in events)


def test_dispatch_gap_metrics_registered_and_recorded(tiny_params):
    from gofr_tpu import metrics as gm

    m = gm.Manager()
    gm.register_framework_metrics(m)
    eng = GenerationEngine(TINY, tiny_params, slots=2, max_seq=64,
                           prompt_buckets=(8,), metrics=m,
                           decode_pipeline=2)
    try:
        eng.generate([5, 17, 42, 7], max_new_tokens=9).tokens()
        text = m.render_openmetrics()
        assert "app_tpu_dispatch_gap_duration" in text
        assert "app_tpu_pipeline_depth" in text
    finally:
        eng.close()
