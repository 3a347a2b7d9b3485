"""The window family (models/laguna.py) at the ``tiny-swa-moe`` preset (two
periods of one full layer to three window layers, a window of 8, 6 and 8
query heads on 2 KV heads, half the head rotated on the full layers, one
dense layer before seven routed ones), held at the logit level against
the benchmark's plain float32 reference (benchmarks/references/laguna.py),
which imports nothing of the program, keeps no cache and no ring, and is
the file the chip's ``correct`` is decided by."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split

from gofr_tpu.models import (LLAMA_CONFIGS, blocks, deepseek_v3 as ds, family,
                             laguna as lg, llama, moe, solar_open2 as so)
from gofr_tpu.ops import attention, flash_decode
from gofr_tpu.tpu import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-swa-moe"]
W = CFG.window_size
# |log-probability - reference|, float32 both sides: eight layers of
# float32 sums in another order (experts in blocks, a ring's rows out of
# position order)
F32_TOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_laguna", os.path.join(
            REPO, "benchmarks", "references", "laguna.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return lg.init(CFG, jax.random.PRNGKey(0))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n) \
        .astype(np.int32)


def _ref(params, toks, rows, **kw):
    return np.asarray(REF.forward_logprobs(params, CFG, np.asarray(toks),
                                           list(rows), **kw)[0])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_family_is_chosen_by_fields_not_by_name():
    assert family(CFG) is lg
    assert family(LLAMA_CONFIGS["tiny"]) is llama
    assert family(LLAMA_CONFIGS["tiny-mla-moe"]) is ds
    assert family(LLAMA_CONFIGS["tiny-kda-moe"]) is so
    renamed = LLAMA_CONFIGS["tiny"].with_(
        layer_pattern=["full", "window"], window_size=4)
    assert family(renamed) is lg
    assert lg.counts(CFG) == {"full": 2, "window": 6}
    assert (lg.heads(CFG, "full"), lg.heads(CFG, "window")) == (6, 8)
    with pytest.raises(ValueError, match="does not tile"):
        lg.counts(CFG.with_(window_size=0))


def test_rope_tables_a_kind(params):
    rope = lg.get_rope_tables(CFG, 64)
    # the full layers rotate rotary_dim = 8 of 16 values, under YaRN's
    # factor; the window layers all 16, plainly
    assert rope["full"][0].shape == (64, 4)
    assert rope["window"][0].shape == (64, 8)
    assert float(rope["full"][0][0, 0]) == pytest.approx(1.1386)
    assert float(rope["window"][0][0, 0]) == 1.0
    for kind in lg.KINDS:
        cos, sin = REF.rope_tables(CFG, kind, 64)
        np.testing.assert_allclose(rope[kind][0], cos, atol=1e-6)
        np.testing.assert_allclose(rope[kind][1], sin, atol=1e-6)


def _serve(params, toks, L, bucket, n_new, slots=3, slot=1):
    """Whole-prompt prefill of toks[:L] into ``slot``, then ``n_new``
    decode steps teacher-forced on toks[L:]: the log-probabilities after
    positions L - 1 .. L + n_new - 1."""
    cache = lg.init_cache(CFG, slots, 64)
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :L] = toks[:L]
    logits, *kv, _ = lg.prefill_kv(params, CFG, jnp.asarray(pad),
                                   jnp.asarray([L]), rope_max=64,
                                   logit_pos=jnp.asarray([L - 1]))
    cache = lg.write_kv(cache, *kv, (0, slot, 0, 0, 0),
                        cache.lengths.at[slot].set(L))
    out = [_logprobs(logits[0, 0])]
    act = jnp.arange(slots) == slot
    step = jax.jit(lambda t, c: lg.decode_step(params, CFG, t, c, active=act))
    for n in range(n_new):
        t = jnp.zeros((slots,), jnp.int32).at[slot].set(int(toks[L + n]))
        logits, new, _ = step(t, cache)
        cache = new._replace(
            lengths=jnp.where(act, new.lengths, cache.lengths))
        out.append(_logprobs(logits[slot]))
    return np.stack(out), cache


@pytest.mark.parametrize("L,bucket", [(5, 8), (8, 8), (20, 32), (32, 32)])
def test_prefill_then_decode_through_the_ring(params, L, bucket):
    """A prompt under the window, one that fills it and two that wrap it
    (the bucket's padding must not reach the ring), then 3 x W decoded
    tokens: the ring wraps three times more."""
    toks = _tokens(L, L + 3 * W)
    got, cache = _serve(params, toks, L, bucket, 3 * W)
    want = _ref(params, toks, range(L - 1, L + 3 * W))
    assert np.abs(got - want).max() < F32_TOL
    assert int(cache.lengths[1]) == L + 3 * W


@pytest.mark.parametrize("delta", [-1, 1])
def test_a_window_one_off_fails(params, delta):
    """The reference with the window one narrower or one wider is another
    model: the edge (p - W < j) is held, in prefill and in decode."""
    L = 12
    toks = _tokens(3, L + W)
    got, _ = _serve(params, toks, L, 16, W)
    rows = range(L - 1, L + W)
    assert np.abs(got - _ref(params, toks, rows)).max() < F32_TOL
    off = np.abs(got - _ref(params, toks, rows, window_delta=delta))
    assert off[0].max() > 50 * F32_TOL          # the prefill's position
    assert off[1:].min(axis=0).max() > 0 and off[1:].max() > 50 * F32_TOL


@pytest.mark.parametrize("chunk,L", [(8, 21), (16, 40), (16, 48), (32, 50)])
def test_left_aligned_chunks_across_the_wrap(params, chunk, L):
    """Chunks as long as the ring and longer, the last one padded: a
    chunk reads the ring before it overwrites it, and padding does not
    reach it. Then decode goes on from the rings the chunks left."""
    toks = _tokens(chunk + L, L + W)
    cache = lg.init_cache(CFG, 1, 64)
    pos = 0
    while L - pos > chunk:
        _, cache = lg.prefill_chunk(
            params, CFG, jnp.asarray(toks[None, pos:pos + chunk]), cache,
            jnp.int32(pos), compute_logits=False)
        pos += chunk
    final = np.zeros((1, chunk), np.int32)
    final[0, :L - pos] = toks[pos:L]
    logits, cache = lg.prefill_chunk(
        params, CFG, jnp.asarray(final), cache, jnp.int32(pos),
        logit_pos=jnp.asarray([L - pos - 1]))
    got = [_logprobs(logits[0, 0])]
    cache = cache._replace(lengths=jnp.asarray([L], jnp.int32))
    for n in range(W):
        logits, cache, _ = lg.decode_step(
            params, CFG, jnp.asarray(toks[L + n:L + n + 1]), cache)
        got.append(_logprobs(logits[0]))
    want = _ref(params, toks, range(L - 1, L + W))
    assert np.abs(np.stack(got) - want).max() < F32_TOL


def test_slots_under_and_over_the_window_in_one_batch(params):
    """Three slots in one decode batch: 3 positions (under the window),
    20 (the ring has wrapped) and an idle one whose ring must stay as it
    is; then all rings against the positions they should hold."""
    lens = (3, 20)
    seqs = [_tokens(40 + n, n + W) for n in lens]
    cache = lg.init_cache(CFG, 3, 64)
    for slot, (n, toks) in enumerate(zip(lens, seqs)):
        pad = np.zeros((1, 32), np.int32)
        pad[0, :n] = toks[:n]
        _, *kv, _ = lg.prefill_kv(params, CFG, jnp.asarray(pad),
                                  jnp.asarray([n]), rope_max=64)
        cache = lg.write_kv(cache, *kv, (0, slot, 0, 0, 0),
                            cache.lengths.at[slot].set(n))
    idle = np.asarray(cache.wk[:, 2])
    act = jnp.asarray([True, True, False])
    got = [[], []]
    for n in range(W):
        t = jnp.asarray([seqs[0][lens[0] + n], seqs[1][lens[1] + n], 7])
        logits, new, counts = lg.decode_step(params, CFG, t, cache,
                                             active=act)
        cache = new._replace(
            lengths=jnp.where(act, new.lengths, cache.lengths))
        for slot in (0, 1):
            got[slot].append(_logprobs(logits[slot]))
    for slot, (n, toks) in enumerate(zip(lens, seqs)):
        want = _ref(params, toks, range(n, n + W))
        assert np.abs(np.stack(got[slot]) - want).max() < F32_TOL
    # two tokens a step, top-2 of 8 over seven routed layers
    assert counts.shape == (7, 8) and int(counts.sum()) == 7 * 2 * 2
    # an idle slot's garbage row lands on its own ring, at its frozen
    # cursor (0); every other row is untouched
    assert np.array_equal(np.asarray(cache.wk[:, 2, :, 1:]), idle[:, :, 1:])
    # a cursor parked at capacity writes neither rows nor ring
    parked = cache._replace(lengths=cache.lengths.at[2].set(64))
    _, after, _ = lg.decode_step(params, CFG, jnp.asarray([1, 2, 3]), parked)
    assert np.array_equal(np.asarray(after.wk[:, 2]),
                          np.asarray(parked.wk[:, 2]))
    assert np.array_equal(np.asarray(after.k[:, 2]), np.asarray(parked.k[:, 2]))


@pytest.mark.parametrize("lengths", [(0, 3, 7), (8, 9, 15), (16, 17, 40)])
def test_the_ring_kernel_equals_its_jnp_form(lengths):
    """ops.flash_decode's kernel, interpreted, on a ring: lengths under,
    at and over the window and across a wrap, a group of 3 and of 4."""
    rng = np.random.default_rng(sum(lengths))
    B, KV, D, L = len(lengths), 2, 16, 3
    ring_k, ring_v = (jnp.asarray(rng.normal(size=(L, B, KV, W, D)),
                                  jnp.float32) for _ in range(2))
    live, skip = flash_decode.ring_rows(jnp.asarray(lengths), W)
    assert [int(x) for x in live] == [min(n, W) for n in lengths]
    for H in (6, 8):
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        kn, vn = (jnp.asarray(rng.normal(size=(B, 1, KV, D)), jnp.float32)
                  for _ in range(2))
        for layer in (0, 2):
            want = attention.decode_attention_appended(
                q, ring_k[layer], ring_v[layer], kn, vn, live, exclude=skip)
            got = flash_decode.flash_decode_ring(
                q, ring_k, ring_v, kn, vn, jnp.asarray(lengths),
                jnp.int32(layer), block_s=W, interpret=True)
            np.testing.assert_allclose(got, want, atol=2e-6)
    # and the excluded row is the oldest: a full ring without it is the
    # W - 1 positions before the new token
    held = np.asarray(attention.ring_held(W, jnp.asarray(lengths)))
    for b, n in enumerate(lengths):
        seen = sorted(int(p) for r, p in enumerate(held[b])
                      if r < int(live[b]) and r != int(skip[b]))
        assert seen == list(range(max(n - W + 1, 0), n))


def test_the_model_on_the_interpreted_kernels(params, monkeypatch):
    """Banded flash prefill, the ring and the full layers' decode kernel
    and the row append, interpreted, against the reference."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    toks = _tokens(9, 20 + 2 * W)
    got, _ = _serve(params, toks, 20, 32, 2 * W)
    want = _ref(params, toks, range(19, 20 + 2 * W))
    assert np.abs(got - want).max() < F32_TOL


@pytest.mark.parametrize("what", ["scores", "router"])
def test_a_bfloat16_shortcut_fails_the_tolerance(params, monkeypatch, what):
    """The float32 program passes F32_TOL (every test above); with the
    attention scores, or the router's scores, taken in bfloat16 it does
    not: the tolerance holds both."""
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(x.dtype)  # noqa: E731
    if what == "scores":
        real = blocks.causal_attention
        monkeypatch.setattr(
            blocks, "causal_attention",
            lambda q, k, v, **kw: real(bf16(q), bf16(k), v, **kw))
    else:
        real = moe.route
        monkeypatch.setattr(
            moe, "route", lambda hf, router, bias, cfg: real(
                bf16(hf), bf16(router), bias, cfg))
    toks = _tokens(11, 24)
    logits = lg.forward(params, CFG, jnp.asarray(toks[None]))
    err = np.abs(_logprobs(logits[0]) - _ref(params, toks, range(24))).max()
    assert err > 5 * F32_TOL


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params):
    eng = GenerationEngine(CFG, params, slots=3, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=2,
                           prefix_store_min=16)
    yield eng
    eng.close()


def _held_to_the_reference(params, prompt, served):
    """Each served token's log-probability against the reference's,
    teacher-forced on prompt + the tokens served (the chip's check)."""
    seq = list(prompt) + [t for t, _ in served[:-1]]
    ref = _ref(params, seq, range(len(prompt) - 1, len(seq)))
    return max(abs(lp - ref[j, tok]) for j, (tok, lp) in enumerate(served))


def _generate(engine, prompt, n):
    return [(int(t), float(lp)) for t, lp in
            engine.generate(prompt, max_new_tokens=n, logprobs=True)]


@pytest.mark.parametrize("length", [5, 20, 32, 33, 70, 100])
def test_engine_against_the_reference(engine, params, length):
    """Under the window, a bucket that wraps it, a whole bucket, one
    token past it (two chunks, the last all padding but one), three
    chunks, four; 3 x W tokens decoded after each."""
    prompt = _tokens(length, length).tolist()
    served = _generate(engine, prompt, 3 * W)
    assert _held_to_the_reference(params, prompt, served) < F32_TOL


def test_engine_lattice_interleaved_with_other_slots_decode(engine, params):
    """Long prompts admitted while other slots decode: the decode blocks
    between their chunks write no row on a half-built ring (the slot is
    parked at capacity), and the chunks leave the decoding slots' alone."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (9, 100, 14, 90, 11)]
    streams = [engine.generate(p, max_new_tokens=20, logprobs=True)
               for p in prompts]
    for p, s in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in s]
        assert len(served) == 20
        assert _held_to_the_reference(params, p, served) < F32_TOL


def test_engine_prefix_hit_restores_rows_and_rings(params):
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=4,
                           prefix_store_min=16)
    try:
        prompt = _tokens(5, 70).tolist()
        miss = _generate(eng, prompt, 2 * W)
        assert eng.stats()["prefix_cache"]["hits"] == 0
        # stored under the tokens before the last chunk boundary, with
        # the rings as they stood there
        assert [len(e.key) for e in eng._kvc.t0.entries()] == [64]
        hit = _generate(eng, prompt, 2 * W)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        assert [t for t, _ in hit] == [t for t, _ in miss]
        assert max(abs(a[1] - b[1]) for a, b in zip(hit, miss)) < 1e-5
        assert _held_to_the_reference(params, prompt, hit) < F32_TOL
        # a longer prompt over the same 64 tokens resumes at 64 too,
        # into another slot's rings
        longer = prompt[:64] + _tokens(9, 40).tolist()
        served = _generate(eng, longer, W)
        assert eng.stats()["prefix_cache"]["hits"] == 2
        assert _held_to_the_reference(params, longer, served) < F32_TOL
    finally:
        eng.close()


def test_engine_says_its_rings_and_counts_their_rows(params):
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=256))
    # one block in flight: each block's stop is reaped before the next
    # dispatch, so 13 tokens are the prefill's and exactly three blocks
    # (at depth 2 a fourth can be queued before the third's stop is seen:
    # what is counted here is rings and rows, not depth)
    eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                           prompt_buckets=(16,), observe=obs, metrics=m,
                           decode_block=4, decode_pipeline=1)
    try:
        eng.generate([3, 4, 5], max_new_tokens=13).tokens()
        stats = eng.stats()
        events = [e for e in obs.timeline.events() if e[3] == "decode"]
        cache = eng.cache
    finally:
        eng.close()
    # what serving_stats says a slot takes is what the arrays take
    ring = (cache.wk.nbytes + cache.wv.nbytes) // 2
    assert stats["window_bytes_per_slot"] == ring == 6 * 8 * 2 * 2 * 16 * 4
    assert stats["window_rows"] == W
    assert stats["kv_bytes_per_token"] * 64 \
        == (cache.k.nbytes + cache.v.nbytes) // 2
    assert stats["moe_decode_dispatch"]["block_rows"] == 16
    assert stats["moe"]["expert_tokens"] > 0
    assert set(stats["kv_live_rows"]) == {"full", "window"}
    # decode events: the expert layer's two counts, no states, then the
    # ring rows at dispatch: 3 positions, 7, then the window's 8
    assert events and all(len(e) == 12 and e[10] is None for e in events)
    assert [e[11] for e in events] == [3, 7, 8]
    assert [e[6] for e in events] == [3, 7, 11]
    assert f"app_tpu_kv_window_live_bytes {float(8 * ring // W)}" \
        in m.render_prometheus()
    args = [e["args"] for e in obs.timeline.chrome_trace()["traceEvents"]
            if e.get("cat") == "decode"]
    assert args and args[-1]["ring_rows"] == 8 \
        and "states_updated" not in args[-1]


@pytest.mark.parametrize("counted,tail", [
    ({"ring": 9}, (None, None, None, 9)),
    ({"assigned": 5, "touched": 3, "ring": 9}, (5, 3, None, 9)),
    ({"assigned": 5, "touched": 3, "states": 2, "ring": 9}, (5, 3, 2, 9))])
def test_ring_rows_keep_their_place_in_a_decode_event(counted, tail):
    from gofr_tpu.observe.timeline import Timeline

    tl = Timeline(capacity=8)
    tl.decode_block(0.0, 1.0, (0,), 4, 7, 8, **counted)
    (event,) = tl.events()
    assert tuple(event[8:]) == tail


class _Tiers:
    host_mb, redis = 64, None


@pytest.mark.parametrize("option", [
    {"paged_blocks": 8}, {"spec_decode_k": 2}, {"lora_adapters": 2},
    {"kvcache": _Tiers()}, {"mesh": object()}, {"kv_dtype": jnp.int8},
    {"serving_role": "prefill"}, {"serving_role": "decode"},
])
def test_the_engine_refuses_what_takes_whole_rows(params, option):
    from gofr_tpu.errors import UnsupportedOptions

    (name,) = option
    with pytest.raises(UnsupportedOptions, match=name) as e:
        GenerationEngine(CFG, params, slots=2, max_seq=64, **option)
    assert [opt for opt, _ in e.value.refused] == [name]
    assert lg.unsupported_options(serving_role="fused",
                                  kv_dtype=jnp.bfloat16) == []


def test_the_engine_refuses_a_capacity_that_is_not_whole_chunks(params):
    with pytest.raises(ValueError, match="whole prefill chunks"):
        GenerationEngine(CFG, params, slots=2, max_seq=72,
                         prompt_buckets=(16, 32))


def test_start_up_from_config_refuses_by_name():
    from gofr_tpu.config import MapConfig
    from gofr_tpu.tpu import new_engine_from_config

    base = {"TPU_MODEL": "tiny-swa-moe", "TPU_SLOTS": "2",
            "TPU_MAX_SEQ": "64", "TPU_SEQ_BUCKETS": "16",
            "TPU_KV_DTYPE": "model",
            "TPU_PREFIX_CACHE": "2"}  # the host tier hangs off the pool
    for key, value in (("TPU_SPEC_DECODE", "4"),
                       ("TPU_KVCACHE_HOST_MB", "64"),
                       ("TPU_KV_DTYPE", "int8"),
                       ("TPU_SERVING_ROLE", "decode")):
        with pytest.raises(ValueError, match=key):
            new_engine_from_config(MapConfig({**base, key: value}))
    eng = new_engine_from_config(MapConfig(base))
    try:
        assert eng.generator.generate([1, 2, 3], max_new_tokens=3).tokens()
        assert eng.predict("score", [1, 2, 3]).shape == (CFG.vocab_size,)
    finally:
        eng.close()


# -- a prompt as two dispatches -------------------------------------------------

@pytest.mark.parametrize("kv_dtype,tol", [(None, F32_TOL)])
def test_a_split_admission_is_the_one_bucket_admission(params, kv_dtype, tol):
    """A prompt admitted as a whole bucket and the rest (left-aligned: this
    family's last chunk) against the same prompt in one padded bucket:
    the same greedy tokens, logprobs and cache arrays to the chunked
    tests' tolerance, and the positions counted (tests/_prefill_split.py)."""
    _prefill_split.check(CFG, params, tol=tol, kv_dtype=kv_dtype)
