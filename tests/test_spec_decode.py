"""Prompt-lookup speculative decoding: the verify pass must be an
EXECUTION optimization, never a semantics change — greedy streams equal
the plain-decode engine's token for token, whether drafts hit, miss, or
the engine falls back entirely."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.ops.attention import (decode_attention_appended,
                                    window_attention_appended)
from gofr_tpu.tpu import GenerationEngine

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return llama.init(TINY, jax.random.PRNGKey(1))


# -- op level -----------------------------------------------------------------

def test_window_attention_w1_equals_appended_decode():
    rng = jax.random.split(jax.random.PRNGKey(0), 5)
    B, S, H, KV, D = 2, 16, 4, 2, 8
    q = jax.random.normal(rng[0], (B, 1, H, D), jnp.float32)
    kc = jax.random.normal(rng[1], (B, KV, S, D), jnp.float32)
    vc = jax.random.normal(rng[2], (B, KV, S, D), jnp.float32)
    kn = jax.random.normal(rng[3], (B, 1, KV, D), jnp.float32)
    vn = jax.random.normal(rng[4], (B, 1, KV, D), jnp.float32)
    lens = jnp.asarray([7, 0], jnp.int32)
    got = window_attention_appended(q, kc, vc, kn, vn, lens)
    want = decode_attention_appended(q, kc, vc, kn, vn, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_verify_step_reproduces_sequential_decode(params):
    """With the TRUE greedy continuation as drafts, verify_step's argmax
    chain equals sequential decode_step's, the full window accepts, and
    the advanced cache continues identically (dense cache: exact)."""
    cache = llama.init_cache(TINY, 3, 32)
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 256, (3, 8)),
                       jnp.int32)
    lens = jnp.asarray([8, 5, 3], jnp.int32)
    logits, cache = llama.prefill(params, TINY, toks, cache, lens)
    last = jnp.asarray([int(jnp.argmax(logits[b, lens[b] - 1]))
                        for b in range(3)], jnp.int32)
    c_seq, t, seq = cache, last, [last]
    for _ in range(5):
        lg, c_seq = llama.decode_step(params, TINY, t, c_seq)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        seq.append(t)
    seq = jnp.stack(seq, 1)                                  # [3, 6]

    vlogits, c_ver = llama.verify_step(params, TINY, seq[:, :5], cache)
    greedy = jnp.argmax(vlogits, -1)
    np.testing.assert_array_equal(np.asarray(greedy[:, :5]),
                                  np.asarray(seq[:, 1:6]))
    # caches agree: one more decode step from both produces equal logits
    adv = c_ver._replace(lengths=cache.lengths + 5)
    lg_a, _ = llama.decode_step(params, TINY, seq[:, 5], c_seq)
    lg_b, _ = llama.decode_step(params, TINY, seq[:, 5], adv)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                               rtol=1e-5, atol=1e-5)


def test_verify_step_partial_accept_prefix(params):
    """Wrong drafts: the agreement prefix is exactly where the first
    draft diverges from the model's argmax."""
    cache = llama.init_cache(TINY, 1, 32)
    logits, cache = llama.prefill(
        params, TINY, jnp.asarray([[5, 17, 42]], jnp.int32), cache,
        jnp.asarray([3], jnp.int32))
    last = int(jnp.argmax(logits[0, 2]))
    # true continuation for 2 steps, then a wrong third draft
    c, t, true = cache, jnp.asarray([last], jnp.int32), []
    for _ in range(2):
        lg, c = llama.decode_step(params, TINY, t, c)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        true.append(int(t[0]))
    wrong = (true[-1] + 1) % TINY.vocab_size
    window = jnp.asarray([[last, true[0], true[1], wrong]], jnp.int32)
    vlogits, _ = llama.verify_step(params, TINY, window, cache)
    greedy = np.asarray(jnp.argmax(vlogits, -1))[0]
    agree = (greedy[:-1] == np.asarray(window)[0, 1:]).astype(int)
    accept = int(np.cumprod(agree).sum())
    assert accept == 2  # both true drafts accepted, the wrong one not


# -- engine level -------------------------------------------------------------

def _ref_stream(params, prompt, n, **kw):
    kw.setdefault("slots", 2)
    eng = GenerationEngine(TINY, params, max_seq=64,
                           prompt_buckets=(8, 16), **kw)
    try:
        return eng.generate(prompt, max_new_tokens=n).tokens()
    finally:
        eng.close()


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_spec_engine_matches_plain_engine(params, kv_dtype):
    """Repetitive AND random prompts stream identical greedy tokens with
    spec decode on vs off. (int8 note: in-window neighbors are attended
    in bf16 — the same contract chunked prefill already has — so int8
    equality is seed-dependent in principle; these fixed seeds pin it.)"""
    rep = [7, 9, 7, 9, 7, 9, 7, 9, 7, 9]           # lookup hits
    rnd = np.random.default_rng(2).integers(1, 256, 12).tolist()
    for prompt in (rep, rnd):
        want = _ref_stream(params, prompt, 24, kv_dtype=kv_dtype)
        eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                               prompt_buckets=(8, 16), kv_dtype=kv_dtype,
                               spec_decode_k=3)
        try:
            got = eng.generate(prompt, max_new_tokens=24).tokens()
            assert got == want, f"prompt {prompt[:4]}..."
            st = eng.stats()["spec_decode"]
            assert st["emitted"] >= st["windows"] > 0
        finally:
            eng.close()


def test_spec_concurrent_slots_and_eos(params):
    """Two slots decoding concurrently under spec, one hitting EOS
    mid-window: streams match the plain engine; post-EOS window tokens
    are discarded."""
    p1 = [3, 1, 4, 3, 1, 4, 3, 1, 4]
    p2 = [2, 7, 2, 7, 2, 7]
    plain = {tuple(p): _ref_stream(params, p, 16) for p in (p1, p2)}
    eos = plain[tuple(p1)][4]  # stop p1 at its 5th token
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), spec_decode_k=4)
    try:
        s1 = eng.generate(p1, max_new_tokens=16, eos_id=eos)
        s2 = eng.generate(p2, max_new_tokens=16)
        got1, got2 = s1.tokens(), s2.tokens()
        want1 = plain[tuple(p1)][:plain[tuple(p1)].index(eos) + 1]
        assert got1 == want1
        assert got2 == plain[tuple(p2)]
    finally:
        eng.close()


def test_spec_falls_back_for_sampling_slots(params):
    """A temperature>0 slot forces the decode path (verify is greedy-
    only); greedy streams stay correct alongside it."""
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), spec_decode_k=3,
                           seed=9)
    try:
        hot = eng.generate([1, 2, 3], max_new_tokens=20, temperature=0.9)
        cold = eng.generate([7, 9, 7, 9, 7, 9], max_new_tokens=12)
        got = cold.tokens()
        assert got == _ref_stream(params, [7, 9, 7, 9, 7, 9], 12)
        assert len(hot.tokens()) == 20
    finally:
        eng.close()


def test_spec_respects_capacity(params):
    """A stream running to the cache edge retires exactly like the
    plain engine (verify windows never scatter past capacity)."""
    prompt = [5, 17, 42, 5, 17, 42]
    want = _ref_stream(params, prompt, 200)  # capacity-limited
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), spec_decode_k=4)
    try:
        assert eng.generate(prompt, max_new_tokens=200).tokens() == want
    finally:
        eng.close()


def test_spec_coverage_gate_mixed_workload(params):
    """One repetitive stream among several non-repetitive ones: the
    coverage gate keeps the batch on decode blocks until enough slots
    can speculate, and every stream still matches the plain engine."""
    prompts = [[7, 9, 7, 9, 7, 9, 7, 9],
               np.random.default_rng(11).integers(1, 256, 10).tolist(),
               np.random.default_rng(12).integers(1, 256, 9).tolist(),
               np.random.default_rng(13).integers(1, 256, 11).tolist()]
    plain = {tuple(p): _ref_stream(params, p, 12, slots=4)
             for p in prompts}
    eng = GenerationEngine(TINY, params, slots=4, max_seq=64,
                           prompt_buckets=(8, 16), spec_decode_k=3)
    try:
        streams = [eng.generate(p, max_new_tokens=12) for p in prompts]
        for p, s in zip(prompts, streams):
            assert s.tokens() == plain[tuple(p)], f"prompt {p[:4]}..."
    finally:
        eng.close()


@pytest.mark.parametrize("axes", [{"dp": 2, "fsdp": 2, "tp": 2},
                                  {"tp": 8}])
def test_spec_mesh_engine_matches_plain(params, axes):
    """Sharded engines support speculative decoding (VERDICT r3 #4):
    drafting stays host-side numpy, the verify dispatch shards exactly
    like the decode step (batch over data axes, KV heads over tp,
    out_shardings pinned so cache donation aliases). Streams must equal
    the unsharded plain engine's token for token and the verify pass
    must actually run (windows > 0)."""
    from gofr_tpu import parallel

    rep = [7, 9, 7, 9, 7, 9, 7, 9, 7, 9]           # lookup hits
    want = _ref_stream(params, rep, 24)
    mesh = parallel.make_mesh(**axes)
    eng = GenerationEngine(TINY, parallel.shard_params(params, mesh),
                           slots=2, max_seq=64, prompt_buckets=(8, 16),
                           mesh=mesh, spec_decode_k=3)
    try:
        got = eng.generate(rep, max_new_tokens=24).tokens()
        assert got == want
        st = eng.stats()["spec_decode"]
        assert st["emitted"] >= st["windows"] > 0
    finally:
        eng.close()
