"""The sampler's branches (tpu/programs.py:_sample): an all-greedy batch
takes an argmax and a logprob, the draws run under ``lax.cond`` only where
an active slot asks for them, and every slot's token and logprob are the
bits the unconditional body gave. That body is kept here, as it stood
before the branches, as the reference."""

import dataclasses
import importlib.util
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, family, llama
from gofr_tpu.tpu.checkpoint import maybe_quantize
from gofr_tpu.tpu import GenerationEngine, programs

TINY = LLAMA_CONFIGS["tiny"]
SLOTS, STEPS = 8, 4


def _unconditional(self, logits, temps, seeds, pos, top_ks, active=None):
    """``_sample`` before its branches: every draw for every slot, and a
    ``where`` at the end."""
    keys = self._resume_keys(seeds, pos)
    V = logits.shape[-1]
    safe_t = jnp.maximum(temps, 1e-6)[:, None]
    scaled = logits / safe_t
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    kmax = min(self.TOP_K_MAX, V)
    vals, idx = jax.lax.top_k(scaled, kmax)
    kk = jnp.minimum(jnp.where(top_ks > 0, top_ks, kmax), kmax)
    vals = jnp.where(jnp.arange(kmax)[None, :] < kk[:, None], vals, -jnp.inf)
    in_k = jax.vmap(jax.random.categorical)(keys, vals)
    topk_tok = jnp.take_along_axis(idx, in_k[:, None], axis=1)[:, 0]
    sampled = jnp.where(top_ks > 0, topk_tok, sampled)
    greedy = jnp.argmax(logits, axis=-1)
    tok = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lp = jnp.take_along_axis(logp, tok[:, None], axis=1)[:, 0]
    return tok, lp


class _Unconditional(programs.EnginePrograms):
    _sample = _unconditional


@pytest.fixture(scope="module")
def tiny_llama():
    return llama.init(TINY, jax.random.PRNGKey(1))


@pytest.fixture()
def gen_engine(tiny_llama):
    eng = GenerationEngine(TINY, tiny_llama, slots=SLOTS, max_seq=64,
                           prompt_buckets=(8, 16))
    yield eng
    eng.close()


def _programs(cls=programs.EnginePrograms, cfg=TINY):
    prog = cls(cfg, family(cfg), object(), max_seq=64, kv_dtype=jnp.int8,
               decode_block=STEPS, n_adapters=0, spec_k=0, mesh=None,
               paged=None)
    prog.describe("cache", SLOTS)
    return prog


# (temperature, top_k, active) a slot; the expected host flag
_G, _T, _K = (0.0, 0, True), (0.9, 0, True), (1.3, 5, True)
_RETIRED = (0.7, 40, False)   # stopped, its settings still in the pack
CASES = {
    "all-greedy": ([_G] * SLOTS, 0),
    "all-temperature": ([_T] * SLOTS, 1),
    "all-top-k": ([_K, (0.6, 64, True), (2.0, 1, True), (0.8, 200, True)] * 2,
                  3),
    "mixed": ([_G, _T, _K, _G, _K, _T, _G, (1.0, 3, True)], 3),
    "mixed-with-a-retired-sampler": ([_G, _RETIRED, _K, _T, _G, _G, _T, _G],
                                     3),
    "greedy-beside-a-retired-sampler": ([_G, _RETIRED, _G, _G, (0.5, 0, False),
                                         _G, _G, _G], 0),
    # a greedy slot's top_k is a setting nobody reads
    "greedy-with-top-k": ([(0.0, 40, True)] * SLOTS, 0),
}


def _settings(case):
    rows, _ = CASES[case]
    return (jnp.asarray([r[0] for r in rows], jnp.float32),
            jnp.asarray([r[1] for r in rows], jnp.int32),
            jnp.asarray([r[2] for r in rows], bool))


@pytest.mark.parametrize("case", CASES)
def test_sample_gives_the_unconditional_bits(case):
    temps, top_ks, active = _settings(case)
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(11),
                                     (SLOTS, TINY.vocab_size))
    seeds = jnp.arange(SLOTS, dtype=jnp.int32) * 7919 + 3
    pos = jnp.arange(SLOTS, dtype=jnp.int32) + 17
    prog = _programs()
    got = jax.jit(prog._sample)(logits, temps, seeds, pos, top_ks, active)
    want = jax.jit(lambda *a: _unconditional(prog, *a))(
        logits, temps, seeds, pos, top_ks)
    on = np.asarray(active)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g)[on], np.asarray(w)[on])
    if on.all():
        # the prefill callers' form: no mask means every row
        again = jax.jit(prog._sample)(logits, temps, seeds, pos, top_ks)
        for g, w in zip(again, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("head", ["plain", "int8", "tied"])
def test_logits_are_exact_in_the_type_the_branches_take(head):
    """``llama.logits_dtype`` is a promise about ``llama.logits``, which
    every family projects through: narrowing the float32 logits to it
    loses nothing, so ``_sample`` may hand its branches the narrow
    array."""
    cfg = dataclasses.replace(TINY, dtype="bfloat16",
                              tie_embeddings=head == "tied")
    params = llama.init(cfg, jax.random.PRNGKey(2))
    if head == "int8":
        params = maybe_quantize(params, True)
    x = jax.random.normal(jax.random.PRNGKey(3), (SLOTS, cfg.dim),
                          jnp.bfloat16)
    logits = llama.logits(params, cfg, x)
    dtype = llama.logits_dtype(cfg)
    assert dtype == (jnp.float32 if head == "tied" else jnp.bfloat16)
    assert logits.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(logits.astype(dtype).astype(jnp.float32)),
        np.asarray(logits))


def _primitives(jaxpr, in_cond=False):
    """(name, inside a ``cond``'s branch) of every equation, those of
    nested jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, in_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(
                sub, in_cond or eqn.primitive.name == "cond")


DRAWS = {"top_k", "sort", "random_bits", "threefry2x32", "random_wrap",
         "random_fold_in", "random_seed"}


def test_the_draws_are_traced_under_cond_alone(tiny_llama):
    """The step as traced: the top-k and every random bit are inside a
    ``cond``'s branches; the scan's body outside them holds none."""
    prog = _programs()
    pack, carry = _dispatch("mixed")
    jaxpr = jax.make_jaxpr(prog._step_fn)(
        prog._rows(SLOTS), tiny_llama, pack, carry,
        jax.random.PRNGKey(0)).jaxpr
    seen = [(name, inside) for name, inside in _primitives(jaxpr)
            if name in DRAWS]
    assert {"top_k", "random_bits", "random_fold_in"} <= {n for n, _ in seen}
    assert [name for name, inside in seen if not inside] == []


def _dispatch(case):
    """The pack and the carry of a dispatch whose slots hold the case's
    settings: the host wins every slot."""
    temps, top_ks, active = _settings(case)
    p = np.zeros((SLOTS, programs.PACK_EXTRA + programs.EOS_MAX), np.int32)
    p[:, 0] = np.arange(SLOTS) * 5 + 1            # last tokens
    p[:, 1] = np.asarray(active)
    p[:, 2] = 32                                  # budgets
    p[:, 3] = np.asarray(temps).view(np.int32)
    p[:, 4] = np.asarray(top_ks)
    p[:, 6] = 1                                   # host wins
    p[:, 7] = np.arange(SLOTS) * 104729 + 11      # seeds
    p[:, 8] = np.arange(SLOTS) + 2                # absolute positions
    p[:, programs.PACK_EXTRA:] = llama.EOS_PAD
    carry = (jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), bool),
             jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), jnp.int32))
    return jnp.asarray(p), carry


@pytest.fixture(scope="module")
def tiny_bf16():
    """The tiny model in bfloat16 with an int8 head, as the cells run
    theirs: the branches then take the logits narrowed to bfloat16."""
    cfg = dataclasses.replace(TINY, dtype="bfloat16")
    return cfg, maybe_quantize(llama.init(cfg, jax.random.PRNGKey(1)), True)


@pytest.mark.parametrize("model", ["float32", "bfloat16-int8"])
@pytest.mark.parametrize("case", CASES)
def test_four_fused_steps_give_the_unconditional_bits(case, model, tiny_llama,
                                                      tiny_bf16):
    """Through ``_fused_decode_scan`` on the tiny model: each step's
    token feeds the next, so one differing bit would show in every later
    step."""
    cfg, params = (TINY, tiny_llama) if model == "float32" else tiny_bf16
    pack, carry = _dispatch(case)
    outs = []
    for cls in (programs.EnginePrograms, _Unconditional):
        prog = _programs(cls, cfg)
        cache = prog._rows(SLOTS)
        cache = cache._replace(
            lengths=jnp.arange(SLOTS, dtype=jnp.int32) + 3)
        toks, lps, emitted, last, _, cache, _ = jax.jit(prog._step_fn)(
            cache, params, pack, carry, jax.random.PRNGKey(0))
        outs.append((np.asarray(toks), np.asarray(lps), np.asarray(emitted),
                     [np.asarray(x) for x in last],
                     np.asarray(cache.lengths)))
    (toks, lps, emitted, last, lengths), (wtoks, wlps, wemitted, wlast,
                                          wlengths) = outs
    assert toks.shape == (STEPS, SLOTS)
    np.testing.assert_array_equal(emitted, wemitted)
    assert emitted.any()
    # an inactive slot carries its token through and emits nothing: its
    # logprob is nobody's (the reap delivers emitted entries only)
    np.testing.assert_array_equal(toks, wtoks)
    np.testing.assert_array_equal(lps[emitted], wlps[wemitted])
    for g, w in zip(last, wlast):
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(lengths, wlengths)


@pytest.mark.parametrize("case", CASES)
def test_host_flag_follows_the_active_settings(case, gen_engine):
    """``stats()["sampling"]`` from the host's own arrays: one drawn
    block where an active slot draws, none where the only slots with a
    temperature are not active."""
    rows, flag = CASES[case]
    gen_engine._temps[:] = [r[0] for r in rows]
    gen_engine._top_ks[:] = [r[1] for r in rows]
    gen_engine._active[:] = [r[2] for r in rows]
    try:
        before = gen_engine.stats()["sampling"]
        assert gen_engine._sampling_flag() == flag
        after = gen_engine.stats()["sampling"]
    finally:
        gen_engine._temps[:] = 0.0
        gen_engine._top_ks[:] = 0
        gen_engine._active[:] = False
    assert after == {"blocks": before["blocks"] + 1,
                     "drawn_blocks": before["drawn_blocks"] + (flag & 1),
                     "topk_blocks": before["topk_blocks"] + (flag >> 1)}


def test_stats_count_the_blocks_that_drew(tiny_llama):
    """Through the engine: a greedy stream's blocks draw nothing, a
    sampled stream's all draw, and the ``decode`` events carry the flag."""
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    obs = Observe(timeline=Timeline(capacity=1024))
    eng = GenerationEngine(TINY, tiny_llama, slots=2, max_seq=64,
                           prompt_buckets=(8,), observe=obs)
    try:
        assert len(eng.generate([5, 17, 42], max_new_tokens=9).tokens()) == 9
        greedy = eng.stats()["sampling"]
        assert greedy["blocks"] >= 2
        assert greedy["drawn_blocks"] == greedy["topk_blocks"] == 0
        eng.generate([5, 17, 42], max_new_tokens=9, temperature=0.8,
                     seed=3).tokens()
        drawn = eng.stats()["sampling"]
        n = drawn["blocks"] - greedy["blocks"]
        assert n >= 2
        assert drawn["drawn_blocks"] == n and drawn["topk_blocks"] == 0
        eng.generate([5, 17, 42], max_new_tokens=9, temperature=0.8,
                     top_k=4, seed=3).tokens()
        cut = eng.stats()["sampling"]
        m = cut["blocks"] - drawn["blocks"]
        assert cut["drawn_blocks"] == n + m and cut["topk_blocks"] == m
    finally:
        eng.close()
    events = obs.timeline.events()
    # an all-greedy block's event says nothing of the sampler
    flags = [e[12] if len(e) > 12 else 0 for e in events if e[3] == "decode"]
    assert flags == [0] * greedy["blocks"] + [1] * n + [3] * m
    # the benchmark's reader over the same events is the program's share
    assert _reader().read(SimpleNamespace(timeline=events, engine_stats={
        "sampling": cut})) == pytest.approx(
            100.0 * cut["drawn_blocks"] / cut["blocks"])


def _reader():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "metrics", "sample.drawn_blocks_pct.py")
    spec = importlib.util.spec_from_file_location("drawn_blocks", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


@pytest.mark.parametrize("flags,counter,want", [
    ((0, 0, 0, 0), True, 0.0), ((0, 1, 3, 0), True, 50.0), ((3,), True, 100.0),
    ((), True, None),            # no decode block in the window
    ((0, 0), False, None),       # a program without the counter
])
def test_reader_gives_the_share_of_blocks_that_drew(flags, counter, want):
    from gofr_tpu.observe.timeline import Timeline

    tl = Timeline(capacity=16)
    for i, flag in enumerate(flags):
        tl.decode_block(float(i), i + 0.5, (0,), 4, 7, 8,
                        sampled=flag or None)
    tl.prefill(9.0, 9.1, 0, 12, 1, "t")
    stats = {"sampling": {"blocks": len(flags)}} if counter else {"slots": 1}
    got = _reader().read(SimpleNamespace(timeline=tl.events(),
                                         engine_stats=stats))
    assert got == want if want is None else got == pytest.approx(want)
