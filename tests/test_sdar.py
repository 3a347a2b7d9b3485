"""The block-diffusion family (models/sdar.py) at the
``tiny-diffusion-moe`` preset (blocks of four positions committed two a
pass, three layers, 6 query heads of 16 values on 2 KV heads, a q/k norm
a head, a softmax router over eight experts, an untied head, float32),
served by the engine itself (prefill under the block-causal mask, the
chunk lattice, denoise and commit passes through the dispatch pack and
the device carry) and held at the log-probability level against the
benchmark's plain float32 reference (benchmarks/references/sdar.py),
which imports nothing of the program, keeps no cache and no state, and is
the file the chip's ``correct`` is decided by."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.errors import UnsupportedOptions
from gofr_tpu.models import LLAMA_CONFIGS, family, llama, sdar
from gofr_tpu.ops import attention, flash, flash_decode
from gofr_tpu.tpu import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-diffusion-moe"]
W = CFG.block_length
# |log-probability - reference|, float32 both sides: three layers of
# float32 sums in another order (experts in blocks, a running softmax)
F32_TOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_sdar", os.path.join(
            REPO, "benchmarks", "references", "sdar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return sdar.init(CFG, jax.random.PRNGKey(0))


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prompt_buckets", (16, 32))
    return GenerationEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def engine(params):
    gen = _engine(params)
    yield gen
    gen.close()


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n)]


def _errors(params, prompt, served, cfg=CFG, **kw):
    """|served log-probability - reference| a generated token, as
    benchmarks/reference.py's ``compare`` asks the reference: the prompt
    and the tokens but the last, zero padding, rows n - 1 .. n - 2 +
    new."""
    n, new = len(prompt), len(served)
    seq = prompt + [t for t, _ in served[:-1]] + [0] * 7
    ref = np.asarray(REF.forward_logprobs(
        params, cfg, seq, range(n - 1, n - 1 + new), **kw)[0])
    return np.array([abs(lp - ref[j, t]) for j, (t, lp) in enumerate(served)])


def _serve(gen, prompt, new, **kw):
    return [(int(t), float(lp)) for t, lp in gen.generate(
        prompt, max_new_tokens=new, logprobs=True, **kw)]


def test_the_family_is_chosen_by_fields_not_by_name():
    assert family(CFG) is sdar
    assert family(LLAMA_CONFIGS["tiny"]) is llama
    assert family(LLAMA_CONFIGS["tiny"].with_(block_length=4)) is sdar
    assert sdar.passes(CFG) == 2 and sdar.per_pass(CFG) == 2
    assert "router_bias" not in sdar.init(
        CFG, jax.random.PRNGKey(0))["layers"]
    with pytest.raises(ValueError, match="denoise_passes"):
        CFG.with_(denoise_passes=3)
    with pytest.raises(ValueError, match="commit_order"):
        CFG.with_(commit_order="random")
    with pytest.raises(ValueError, match="mask_token_id"):
        CFG.with_(mask_token_id=256)


def test_refuses_what_has_not_carried_a_block(params):
    for option, kw in (("spec_decode_k", {"spec_decode_k": 2}),
                       ("paged_blocks", {"paged_blocks": 8}),
                       ("lora_adapters", {"lora_adapters": 1})):
        with pytest.raises(UnsupportedOptions, match=option):
            _engine(params, **kw)
    with pytest.raises(ValueError, match="whole blocks"):
        _engine(params, max_seq=126)


# -- the engine against the plain reference ------------------------------------

@pytest.mark.parametrize("n,new", [
    (24, 16),    # n mod 4 = 0: a bucket less a block, nothing given
    (25, 16),    # 1 given: the first block takes two denoise passes
    (26, 16),    # 2 given: one
    (27, 16),    # 3 given: one, of one position
    (32, 12),    # a whole bucket
    (16, 7),     # max_new_tokens no multiple of 4: the last block is cut
    (13, 5),
    (3, 9),      # less than a block: no prefill program runs at all
    (4, 1),
    (1, 2),
    (42, 16),    # past the largest bucket: the chunk lattice
    (75, 10),    # two mid chunks and an overlapping final one, 3 given
    (100, 13),
])
def test_served_logprobs_equal_the_reference(params, engine, n, new):
    prompt = _prompt(1000 * n + new, n)
    served = _serve(engine, prompt, new)
    assert len(served) == new
    err = _errors(params, prompt, served)
    assert err.max() < F32_TOL, err


@pytest.mark.parametrize("control", ["causal_block", "no_commit", "shifted"])
def test_the_reference_with_one_departure_is_far(params, engine, control):
    """The tolerance tells the engine from a causal block, from rows kept
    from the last denoise pass and from a distribution read a row
    early."""
    prompt = _prompt(7, 26)
    served = _serve(engine, prompt, 16)
    assert _errors(params, prompt, served).max() < F32_TOL
    assert np.median(_errors(params, prompt, served, control=control)) \
        > 100 * F32_TOL


def test_two_slots_at_different_passes_of_their_blocks(params, engine):
    """Streams admitted together whose first blocks take one and two
    denoise passes (2 and 0 given) run out of step from then on, one
    committing in the pass in which the other denoises: the same program
    does both, and each stream is the reference's."""
    prompts = [_prompt(50 + i, n) for i, n in enumerate((26, 24, 27, 40))]
    streams = [engine.generate(p, max_new_tokens=18, logprobs=True)
               for p in prompts]
    for prompt, stream in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in stream]
        assert len(served) == 18
        assert _errors(params, prompt, served).max() < F32_TOL
    n = engine.stats()["diffusion"]
    assert n["denoise_passes"] and n["commit_passes"]
    assert n["rows_written"] == W * n["commit_passes"]


def test_the_mask_id_is_a_token_like_any_other(params, engine):
    """Masked-ness is state. A prompt full of the mask token's id, and a
    stream that EMITS that id (the random model's favourite after this
    prompt), are served as the reference serves them: a known position
    holding id 255 carries that row of the embedding as a token, and
    stays known."""
    prompt = _prompt(39, 8)
    served = _serve(engine, prompt, 12)
    assert CFG.mask_token_id in [t for t, _ in served][:-4], served
    assert _errors(params, prompt, served).max() < F32_TOL
    prompt = [CFG.mask_token_id] * 26
    served = _serve(engine, prompt, 8)
    assert _errors(params, prompt, served).max() < F32_TOL


def test_a_stream_stops_at_its_eos_inside_a_block(params, engine):
    prompt = _prompt(3, 24)
    served = _serve(engine, prompt, 16)
    eos = served[5][0]
    first = [t for t, _ in served].index(eos)
    cut = _serve(engine, prompt, 16, eos_id=eos)
    assert cut == served[:first + 1]


def test_capacity_stops_a_stream_where_every_family_stops(params):
    gen = _engine(params, max_seq=64, slots=2)
    try:
        for n in (50, 57, 62):
            served = _serve(gen, _prompt(n, n), 40)
            # the last position delivered is max_seq - 2
            assert len(served) == 63 - n
            assert _errors(params, _prompt(n, n), served).max() < F32_TOL
        stream = gen.generate(_prompt(1, 63), max_new_tokens=4)
        with pytest.raises(Exception, match="exceeds serving limit"):
            list(stream)
    finally:
        gen.close()


def test_a_prefix_pool_hit_equals_its_miss(params):
    """A hit restores whole blocks (the match cut to a block boundary)
    and runs the rest; its tokens and log-probabilities are the miss's,
    for a repeated prompt and for one that shares 41 tokens: 40 are
    restored, not 41."""
    gen = _engine(params, prefix_cache_slots=2)
    try:
        prompt = _prompt(11, 90)
        miss = _serve(gen, prompt, 12)
        hit = _serve(gen, prompt, 12)
        assert gen.stats()["prefix_cache"]["hits"] == 1
        assert [t for t, _ in hit] == [t for t, _ in miss]
        np.testing.assert_allclose([lp for _, lp in hit],
                                   [lp for _, lp in miss], atol=F32_TOL)
        assert _errors(params, prompt, hit).max() < F32_TOL
        other = prompt[:41] + _prompt(12, 40)
        shared = _serve(gen, other, 8)
        assert gen.stats()["prefix_cache"]["hits"] == 2
        assert _errors(params, other, shared).max() < F32_TOL
    finally:
        gen.close()


def test_sampled_streams_repeat_by_seed(params, engine):
    prompt = _prompt(21, 30)
    a = _serve(engine, prompt, 12, temperature=0.9, top_k=8, seed=5)
    b = _serve(engine, prompt, 12, temperature=0.9, top_k=8, seed=5)
    c = _serve(engine, prompt, 12, temperature=0.9, top_k=8, seed=6)
    assert a == b and a != c


# -- the three orders against a plain sampler ----------------------------------

def _plain_generate(params, cfg, prompt, new):
    """The sampler as the family's description has it, block by block and
    pass by pass on the reference's own stack: the tokens a greedy stream
    commits, in position order."""
    k = cfg.block_length // (cfg.denoise_passes or cfg.block_length)
    seq, n = list(prompt), len(prompt)
    while len(seq) < n + new:
        start = len(seq) // W * W
        block = seq[start:] + [0] * (W - len(seq) + start)
        known = [i < len(seq) - start for i in range(W)]
        while not all(known):
            ids = np.array(seq[:start] + block, np.int32)
            is_mask = np.array([False] * start + [not x for x in known])
            pos = np.arange(start + W)
            seen = (pos[None, :] // W) <= (pos[:, None] // W)
            with jax.default_matmul_precision("highest"):
                lp, _ = REF._forward(
                    params, cfg, jnp.asarray(ids), jnp.asarray(is_mask),
                    jnp.asarray(pos, jnp.int32), jnp.asarray(seen),
                    jnp.arange(start, start + W), "", 8)
            lp = np.asarray(lp)
            tok, conf = lp.argmax(-1), np.exp(lp.max(-1))
            masked = [i for i in range(W) if not known[i]]
            if cfg.commit_order == "sequential":
                take = masked[:k]
            elif cfg.commit_order == "low_confidence_static":
                take = sorted(masked, key=lambda i: -conf[i])[:k]
            else:
                take = [i for i in masked
                        if conf[i] > cfg.confidence_threshold] \
                    or [max(masked, key=lambda i: conf[i])]
            for i in take:
                block[i], known[i] = int(tok[i]), True
        seq = seq[:start] + block
    return seq[n:n + new]


@pytest.mark.parametrize("order,threshold", [
    ("sequential", 0.9), ("low_confidence_static", 0.9),
    ("low_confidence_dynamic", 0.9), ("low_confidence_dynamic", 0.02)])
def test_each_order_commits_what_a_plain_sampler_commits(params, order,
                                                         threshold):
    """At the published threshold nothing passes on random weights and
    ``low_confidence_dynamic`` commits one position a pass; at 0.02 most
    do, some passes committing several."""
    cfg = CFG.with_(commit_order=order, confidence_threshold=threshold)
    gen = _engine(params, cfg=cfg, slots=2)
    try:
        for n in (26, 9):
            prompt = _prompt(300 + n, n)
            served = [t for t, _ in _serve(gen, prompt, 14)]
            assert served == _plain_generate(params, cfg, prompt, 14)
        said = gen.stats()["diffusion"]
        assert said["order"] == order
        if order == "low_confidence_dynamic":
            per_pass = said["committed"] / said["denoise_passes"]
            assert per_pass == 1.0 if threshold == 0.9 else per_pass > 1.2
    finally:
        gen.close()


def test_the_orders_pick_by_rule():
    conf = jnp.asarray([[0.3, 0.95, 0.5, 0.5], [0.1, 0.2, 0.05, 0.93]])
    masked = jnp.asarray([[True, True, True, True],
                          [True, True, True, False]])
    static = sdar.commits(CFG.with_(commit_order="low_confidence_static"),
                          conf, masked)
    assert static.tolist() == [[False, True, True, False],
                               [True, True, False, False]]
    dynamic = sdar.commits(CFG.with_(commit_order="low_confidence_dynamic"),
                           conf, masked)
    assert dynamic.tolist() == [[False, True, False, False],
                                [False, True, False, False]]
    known = jnp.asarray([[True, False, False, False],
                         [True, True, True, False],
                         [False, False, False, False]])
    at, found = sdar.candidates(CFG, known)
    assert at.tolist() == [[1, 2], [3, 3], [0, 1]]
    assert found.tolist() == [[True, True], [True, False], [True, True]]
    assert sdar.commits(CFG, conf[:, :2], found[:2]).tolist() \
        == found[:2].tolist()


# -- the operations ------------------------------------------------------------

def _qkv(seed, b, s, h, kv, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), dtype),
            jax.random.normal(ks[1], (b, s, kv, d), dtype),
            jax.random.normal(ks[2], (b, s, kv, d), dtype))


def _dense(q, k, v, seen):
    """softmax(q k^T / sqrt(d)) v under ``seen`` [S, T], grouped heads."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
    return jnp.einsum("bhst,bthd->bshd", p, v)


def test_block_causal_prefill_kernel_and_reference():
    q, k, v = _qkv(0, 2, 32, 4, 2, 128)
    lengths = jnp.asarray([32, 20])
    want = _dense(q, k, v, attention.block_causal(32, 4))
    got = attention.causal_attention(q, k, v, block=4)
    np.testing.assert_allclose(got, want, atol=2e-5)
    kern = flash.flash_causal_prefill(q, k, v, lengths, block_q=16,
                                      block_k=16, interpret=True, block=4)
    np.testing.assert_allclose(kern[0], want[0], atol=2e-5)
    short = _dense(q[1:, :20], k[1:, :20], v[1:, :20],
                   attention.block_causal(20, 4))
    np.testing.assert_allclose(kern[1, :20], short[0], atol=2e-5)
    with pytest.raises(ValueError, match="do not tile"):
        flash.flash_causal_prefill(q, k, v, lengths, block_q=16,
                                   block_k=16, interpret=True, block=3)
    # without a block both are what they were
    np.testing.assert_array_equal(
        attention.causal_attention(q, k, v, block=0),
        attention.causal_attention(q, k, v))


def test_chunk_attention_block_causal_within_the_chunk():
    q, k, v = _qkv(1, 1, 24, 4, 2, 16)
    want = _dense(q, k, v, attention.block_causal(24, 4))[:, 8:]
    cache_k = jnp.zeros((1, 2, 32, 16)).at[:, :, :8].set(
        jnp.swapaxes(k[:, :8], 1, 2))
    cache_v = jnp.zeros((1, 2, 32, 16)).at[:, :, :8].set(
        jnp.swapaxes(v[:, :8], 1, 2))
    got = attention.chunk_attention(q[:, 8:], cache_k, cache_v, k[:, 8:],
                                    v[:, 8:], jnp.int32(8), block=4)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True])
def test_block_decode_kernel_against_the_window_reference(quant):
    """W query positions a slot over the live cache and over themselves,
    both ways: the kernel's branch (interpreted) against
    ``window_attention_appended`` with every window position seen, at
    slots of length 0, inside a block and over several."""
    from gofr_tpu.ops.quant import quantize_kv

    L, B, KV, S, D, H = 2, 4, 2, 64, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    ck = jax.random.normal(ks[0], (L, B, KV, S, D), jnp.float32)
    cv = jax.random.normal(ks[1], (L, B, KV, S, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, W, H, D), jnp.float32)
    kn = jax.random.normal(ks[3], (B, W, KV, D), jnp.float32)
    vn = jax.random.normal(ks[4], (B, W, KV, D), jnp.float32)
    lengths = jnp.asarray([0, 5, 32, 64])
    scales = (None, None)
    if quant:
        ck, sk = quantize_kv(ck)
        cv, sv = quantize_kv(cv)
        scales = (sk, sv)
    for layer in (0, 1):
        want = attention.window_attention_appended(
            q, ck[layer], cv[layer], kn, vn, lengths,
            *(None if s is None else s[layer] for s in scales),
            within=attention.block_causal(W, W))
        got = flash_decode.flash_decode_block(
            q, ck, cv, kn, vn, lengths, jnp.int32(layer), *scales,
            block_s=16, interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_window_reference_keeps_its_causal_default():
    q, k, v = _qkv(3, 2, 3, 4, 2, 16)
    ck = jnp.zeros((2, 2, 8, 16))
    lengths = jnp.asarray([0, 0])
    causal = attention.window_attention_appended(q, ck, ck, k, v, lengths)
    np.testing.assert_allclose(
        causal, _dense(q, k, v, jnp.tril(jnp.ones((3, 3), bool))), atol=2e-5)
    both = attention.window_attention_appended(
        q, ck, ck, k, v, lengths, within=attention.block_causal(3, 3))
    np.testing.assert_allclose(
        both, _dense(q, k, v, jnp.ones((3, 3), bool)), atol=2e-5)


def test_softmax_router_renormalises_the_chosen(params):
    from gofr_tpu.models import moe

    h = jax.random.normal(jax.random.PRNGKey(4), (5, CFG.dim))
    router = params["layers"]["router"][0]
    topi, w = moe.route(h, router, None, CFG)
    p = jax.nn.softmax(h @ router, -1)
    want_v, want_i = jax.lax.top_k(p, CFG.experts_per_token)
    assert topi.tolist() == want_i.tolist()
    np.testing.assert_allclose(
        w, want_v / want_v.sum(-1, keepdims=True), atol=1e-6)


def test_the_engine_runs_the_kernels_interpreted(params, monkeypatch):
    """The same streams with every kernel on the path interpreted (the
    block-decode branch, the row append four times a commit pass, the
    block-causal flash prefill): heads of 128 so that they take the
    shapes."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    cfg = CFG.with_(attn_head_dim=128, n_layers=2)
    wide = sdar.init(cfg, jax.random.PRNGKey(1))
    gen = _engine(wide, cfg=cfg, slots=2)
    try:
        assert gen.stats()["decode_kv_block"]
        for n in (26, 40):
            prompt = _prompt(400 + n, n)
            served = _serve(gen, prompt, 10)
            assert _errors(wide, prompt, served, cfg=cfg).max() < F32_TOL
    finally:
        gen.close()


def test_an_int8_cache_serves_near_the_reference(params):
    gen = _engine(params, kv_dtype=jnp.int8, slots=2)
    try:
        prompt = _prompt(31, 42)
        served = _serve(gen, prompt, 12)
        assert np.median(_errors(params, prompt, served)) < 0.05
    finally:
        gen.close()


def test_stats_timeline_and_counters(params):
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    metrics = Manager()
    register_framework_metrics(metrics)
    observe = Observe(metrics=metrics, timeline=Timeline(capacity=256))
    gen = _engine(params, metrics=metrics, observe=observe, slots=2)
    try:
        served = _serve(gen, _prompt(5, 24), 8)
        assert len(served) == 8
        said = gen.stats()["diffusion"]
        assert said["block_length"] == W and said["passes_per_block"] == 2
        assert said["order"] == "sequential"
        assert said["emitted"] == 8 and said["commit_passes"] == 2
        assert said["denoise_passes"] == 4 and said["committed"] == 8
        assert said["tokens_per_pass"] == round(8 / 6, 4)
        events = [e for e in observe.timeline.events() if e[3] == "decode"]
        ran, delivered, rows = map(sum, zip(*(e[-1] for e in events)))
        assert (ran, delivered, rows) == (6, 8, 8)
        text = metrics.render_prometheus()
        assert "app_tpu_diffusion_passes_total 6" in text
        assert "app_tpu_diffusion_tokens_total 8" in text
    finally:
        gen.close()
