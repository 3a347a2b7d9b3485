"""The hybrid family (models/solar_open2.py, ops/kda.py) at the
``tiny-kda-moe`` preset (two periods of one full layer to three linear
ones, 4 of 16 experts held), held against the benchmark's plain float32
reference (benchmarks/references/solar_open2.py), which imports nothing
of the program and is the file the chip's ``correct`` is decided by."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split

from gofr_tpu.models import (LLAMA_CONFIGS, deepseek_v3 as ds, family, llama,
                             moe, solar_open2 as so)
from gofr_tpu.ops import kda
from gofr_tpu.tpu import GenerationEngine
from gofr_tpu.tpu.checkpoint import maybe_quantize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-kda-moe"]
# |log-probability - reference|, float32 both sides: eight layers of
# float32 sums in another order (a scan a period, experts in blocks)
F32_TOL = 2e-4
# int8 weights both sides: the per-channel scale is applied after the
# matmul in the program and before it in the reference
INT8_TOL = 2e-3
# the kernels against the jnp recurrence, float32 both sides: a sum down
# the sublanes against an einsum, a few ulp a token
KERNEL_TOL = 2e-5


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_solar_open2", os.path.join(
            REPO, "benchmarks", "references", "solar_open2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return so.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    # seed 2: no router of the eight layers sits nearer than 7e-5 to a
    # tie at any of these positions, with float32 or int8 weights (of
    # seeds 1-11 the widest; seed 1 has a gap of 1e-6 at position 32,
    # where two float32 sums in another order pick another expert)
    return jax.random.randint(jax.random.PRNGKey(2), (2, 40), 1,
                              CFG.vocab_size)


def _ref_logprobs(params, cfg, toks):
    return np.stack([np.asarray(REF.forward_logprobs(
        params, cfg, np.asarray(row), range(len(row)))[0]) for row in toks])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_family_is_chosen_by_fields_not_by_name():
    assert family(CFG) is so
    assert family(LLAMA_CONFIGS["tiny"]) is llama
    assert family(LLAMA_CONFIGS["tiny-mla-moe"]) is ds
    renamed = LLAMA_CONFIGS["tiny"].with_(
        layer_pattern=["full", "linear"])      # a list, as a file gives it
    assert family(renamed) is so and renamed.layer_pattern == ("full",
                                                               "linear")
    assert so.counts(CFG) == (2, 1, 3)
    # the full layers' head size is stated, not dim // n_heads, and is
    # not the linear layers'
    assert CFG.head_dim == 24 != CFG.dim // CFG.n_heads
    assert CFG.head_dim != CFG.linear_head_dim
    assert LLAMA_CONFIGS["tiny"].head_dim == 16    # the default is what was
    assert not so.RECOMPUTABLE and llama.RECOMPUTABLE and ds.RECOMPUTABLE
    with pytest.raises(ValueError, match="layer_pattern"):
        so.counts(CFG.with_(n_layers=6))


def test_full_forward_against_the_reference(params, tokens):
    logits = so.forward(params, CFG, tokens)
    err = np.abs(_logprobs(logits) - _ref_logprobs(params, CFG, tokens))
    assert err.max() < F32_TOL


def _serve(params, cfg, row, L, bucket, slots=3, slot=1):
    """Prefill ``row[:L]`` padded to ``bucket`` into one slot of a cache
    whose other slots idle, then decode the rest a token a step:
    log-probabilities [len(row), V]."""
    pad = jnp.zeros((1, bucket), jnp.int32).at[0, :L].set(row[:L])
    logits, *kv, _ = so.prefill_kv(params, cfg, pad, jnp.asarray([L]))
    cache = so.init_cache(cfg, slots, 64)
    cache = so.write_kv(cache, *kv, (0, slot, 0, 0, 0),
                        cache.lengths.at[slot].set(L))
    active = jnp.arange(slots) == slot
    out = [logits[0, :L]]
    for t in range(L, len(row)):
        step, cache, _, _ = so.decode_step(
            params, cfg, jnp.zeros((slots,), jnp.int32).at[slot].set(row[t]),
            cache, active=active)
        out.append(step[slot][None])
    return _logprobs(jnp.concatenate(out)), cache


def test_prefill_then_decode_through_the_cache(params, tokens):
    """A padded bucket writes rows, state and tail; decode steps read and
    rewrite them: the reference's full forward."""
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    got, cache = _serve(params, CFG, tokens[0], 24, 32)
    assert np.abs(got - want).max() < F32_TOL
    # the idle slots' state and tail are bit for bit what they were
    assert not np.asarray(cache.state[:, [0, 2]]).any()
    assert not np.asarray(cache.conv[:, [0, 2]]).any()


@pytest.mark.parametrize("L,bucket", [(24, 32), (32, 32), (1, 8), (2, 8)])
def test_a_padded_bucket_leaves_state_and_tail_as_at_the_last_token(
        params, tokens, L, bucket):
    pad = jnp.zeros((1, bucket), jnp.int32).at[0, :L].set(tokens[0, :L])
    _, _, _, state, conv, _ = so.prefill_kv(params, CFG, pad,
                                            jnp.asarray([L]))
    _, _, _, state_l, conv_l, _ = so.prefill_kv(params, CFG, tokens[:1, :L])
    # float32 sums over a bucket and over L tokens, fused differently
    assert np.abs(np.asarray(state - state_l)).max() < 1e-5
    # the same inputs from a matmul of another height
    assert np.abs(np.asarray(conv - conv_l)).max() < 1e-5


@pytest.mark.parametrize("chunk", [8, 16])
def test_left_aligned_chunks_against_the_reference(params, tokens, chunk):
    """Chunks from position 0, the last one padded; the slot's stale
    state and tail are not read at position 0."""
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    L = 36                                   # 36 = 2 x 16 + 4 = 4 x 8 + 4
    cache = so.init_cache(CFG, 1, 64)
    cache = cache._replace(state=cache.state + 3.0, conv=cache.conv + 2.0)
    pos = 0
    while L - pos > chunk:
        _, cache = so.prefill_chunk(params, CFG, tokens[:1, pos:pos + chunk],
                                    cache, jnp.int32(pos),
                                    compute_logits=False)
        pos += chunk
    last = jnp.zeros((1, chunk), jnp.int32).at[0, :L - pos].set(
        tokens[0, pos:L])
    logits, cache = so.prefill_chunk(params, CFG, last, cache, jnp.int32(pos),
                                     logit_pos=jnp.asarray([L - pos - 1]))
    assert np.abs(_logprobs(logits[0, 0]) - want[L - 1]).max() < F32_TOL
    _, _, _, state, conv, _ = so.prefill_kv(params, CFG, tokens[:1, :L])
    # the same float32 recurrence through three or five programs
    assert np.abs(np.asarray(cache.state - state)).max() < 1e-4
    assert np.abs(np.asarray(cache.conv - conv)).max() < 1e-4


# -- the kernels, interpreted ---------------------------------------------------

def _recurrence_inputs(B, T, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    return (unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (B, T, H, dk))),
            jax.random.normal(ks[2], (B, T, H, dv)),
            jax.nn.sigmoid(2 * jax.random.normal(ks[3], (B, T, H, dk))),
            2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))),
            jax.random.normal(ks[5], (B, H, dk, dv)))


@pytest.mark.parametrize("T", [8, 40])
def test_the_prefill_kernel_equals_the_token_recurrence(T):
    """The kernel on T + 8 tokens (whole sub-blocks of 16: one chunk of
    16, three of 16) and ``prefill_auto`` on T (no whole sub-blocks: the
    jnp recurrence must still take them), from ``g = log alpha``."""
    q, k, v, a, b, s0 = _recurrence_inputs(2, T + 8, 4, 16, 24)
    g = jnp.log(a)
    o_ref, s_ref = kda.recurrent_ref(q, k, v, a, b, s0)
    o, s = kda.kda_prefill(q, k, v, g, b, s0, interpret=True)
    assert np.abs(np.asarray(o - o_ref)).max() < KERNEL_TOL
    assert np.abs(np.asarray(s - s_ref)).max() < KERNEL_TOL
    # masked positions are the identity: g 0, beta 0
    live = jnp.arange(T + 8) < T + 5
    g_m = jnp.where(live[None, :, None, None], g, 0.0)
    b_m = jnp.where(live[None, :, None], b, 0.0)
    _, s_m = kda.kda_prefill(q, k, v, g_m, b_m, s0, interpret=True)
    _, s_cut = kda.recurrent_ref(q[:, :T + 5], k[:, :T + 5], v[:, :T + 5],
                                 a[:, :T + 5], b[:, :T + 5], s0)
    assert np.abs(np.asarray(s_m - s_cut)).max() < KERNEL_TOL
    assert kda.prefill_path(16, 24, 4, T) == "recurrence"
    o_t, s_t = kda.prefill_auto(q[:, :T], k[:, :T], v[:, :T], g[:, :T],
                                b[:, :T], s0)
    o_r, s_r = kda.recurrent_ref(q[:, :T], k[:, :T], v[:, :T], a[:, :T],
                                 b[:, :T], s0)
    assert np.abs(np.asarray(o_t - o_r)).max() < KERNEL_TOL
    assert np.abs(np.asarray(s_t - s_r)).max() < KERNEL_TOL


def _recurrence_f64(q, k, v, g, beta, s0):
    """The recurrence in float64 numpy: one row's tokens [T, H, ...]."""
    q, k, v, g, beta, S = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, s0))
    o = np.zeros(v.shape)
    for t in range(q.shape[0]):
        S = S * np.exp(g[t])[..., None]
        u = v[t] - np.einsum("hk,hkv->hv", k[t], S)
        S = S + (k[t] * beta[t][:, None])[..., None] * u[:, None, :]
        o[t] = np.einsum("hk,hkv->hv", q[t], S)
    return o, S


def _stress_inputs(T, decay, step, H=2, dk=16, dv=24):
    """Two rows of T - 3 and T // 2 + 1 tokens in a bucket of T, the
    padding masked as the model masks it; a state that is not empty."""
    q, k, v, _, _, s0 = _recurrence_inputs(2, T, H, dk, dv, seed=T)
    ks = jax.random.split(jax.random.PRNGKey(T + 1), 4)
    u = jax.random.uniform(ks[0], (2, T, H, dk))
    if decay == "mild":
        g = -0.02 * u
    elif decay == "strong":
        # down to e^-30 a token: a channel is past float32's smallest
        # number three tokens into a sub-block
        g = -30.0 * u
    else:
        # a channel: strong for every token, or mild but for a token in
        # three, so that short sums stand beside long ones
        always = jax.random.bernoulli(ks[1], 0.5, (1, 1, H, dk))
        now = jax.random.bernoulli(ks[2], 0.3, (2, T, H, 1))
        g = jnp.where(always | now, -30.0, -0.02) * u
    beta = {"zero": jnp.zeros((2, T, H)),
            "near2": 2.0 - 1e-3 * jax.random.uniform(ks[3], (2, T, H)),
            "random": 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (2, T, H)))
            }[step]
    lengths = np.asarray([T - 3, T // 2 + 1])
    live = jnp.arange(T)[None, :] < lengths[:, None]
    return (q, k, v, jnp.where(live[..., None, None], g, 0.0),
            jnp.where(live[..., None], beta, 0.0), s0), lengths


@pytest.mark.parametrize("step", ["zero", "near2", "random"])
@pytest.mark.parametrize("decay", ["mild", "strong", "mixed"])
@pytest.mark.parametrize("T", [32, 64, 256, 512])
def test_the_chunkwise_kernel_is_the_recurrence(T, decay, step):
    """The kernel, interpreted, against the recurrence in float64: no
    farther from it than four times what the float32 jnp recurrence is
    (a few ulp of the largest value where that one is exact), nothing
    NaN or inf, a padded row's state the recurrence cut at its length;
    and 512 tokens in one call are 256 + 256 through the state (what
    ``_chunk_mid`` then ``_chunk_final`` do)."""
    args, lengths = _stress_inputs(T, decay, step)
    q, k, v, g, beta, s0 = args
    o, s = (np.asarray(x) for x in kda.kda_prefill(*args, interpret=True))
    assert np.isfinite(o).all() and np.isfinite(s).all()
    o32, s32 = (np.asarray(x) for x in kda.recurrent_ref(
        q, k, v, jnp.exp(g), beta, s0))
    ulp = np.finfo(np.float32).eps
    for row, n in enumerate(lengths):
        o64, s64 = _recurrence_f64(*(x[row, :n] for x in args[:5]), s0[row])
        for got, ref, want in ((o[row, :n], o32[row, :n], o64),
                               (s[row], s32[row], s64)):
            # a state that has all but decayed away (1e-18 of inputs of
            # order 1) is held to 1e-12, not to its own last digits
            floor = 4 * ulp * np.abs(want).max()
            assert np.abs(got - want).max() \
                <= 4 * max(np.abs(ref - want).max(), floor) + 1e-12
    if T == 512:
        half = [x[:, :256] for x in args[:5]], [x[:, 256:] for x in args[:5]]
        o_a, s_a = kda.kda_prefill(*half[0], s0, interpret=True)
        o_b, s_b = kda.kda_prefill(*half[1], s_a, interpret=True)
        assert np.abs(np.concatenate([o_a, o_b], axis=1) - o).max() < 1e-6
        assert np.abs(np.asarray(s_b) - s).max() < 1e-6


@pytest.mark.parametrize("active", [
    [1, 0, 1, 1, 0], [0, 0, 0, 0, 0], [1, 1, 1, 1, 1], [0, 0, 0, 0, 1]])
def test_the_decode_kernel_updates_the_active_states_alone(active):
    q, k, v, a, b, _ = _recurrence_inputs(5, 1, 4, 16, 24, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 4, 16, 24))
    act = jnp.asarray(active, bool)
    args = (jnp.int32(1), q[:, 0], k[:, 0], v[:, 0], a[:, 0], b[:, 0], act)
    o_ref, s_ref = kda.decode_ref(state, *args)
    o, s = kda.kda_decode(state, *args, interpret=True)
    assert np.abs(np.asarray(o - o_ref)).max() < KERNEL_TOL
    assert np.abs(np.asarray(s - s_ref)).max() < KERNEL_TOL
    idle = ~np.asarray(act)
    assert np.array_equal(np.asarray(s)[1][idle], np.asarray(state)[1][idle])
    assert np.array_equal(np.asarray(s)[[0, 2]], np.asarray(state)[[0, 2]])
    assert not np.asarray(o)[idle].any()


def test_the_model_on_the_interpreted_kernels(params, tokens, monkeypatch):
    """The serving path with both kernels in it, as on the chip."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    want = _ref_logprobs(params, CFG, tokens[:1, :20])[0]
    got, _ = _serve(params, CFG, tokens[0, :20], 12, 16)
    assert np.abs(got - want).max() < F32_TOL


def test_the_short_convolution_against_its_definition():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 5))
    tail = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 5))
    w = jax.random.normal(jax.random.PRNGKey(5), (4, 5))
    y, new = kda.short_conv(x, tail, w, jnp.asarray([6, 2]))
    xs = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    want = sum(xs[:, j:j + 6] * np.asarray(w)[j] for j in range(4))
    assert np.abs(np.asarray(y) - want / (1 + np.exp(-want))).max() < 1e-6
    assert np.array_equal(np.asarray(new[0]), xs[0, 6:9])
    assert np.array_equal(np.asarray(new[1]), xs[1, 2:5])   # after input 1


# -- the chip's share -----------------------------------------------------------

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """16 experts, a chip a quarter: each share's routed part, and the
    shared expert counted once, sum to the uncut reference's layer. The
    program computes share j from the parameters of a chip that holds
    experts 4j..4j+3 (its router renumbered so that the held experts are
    ids 0..3, as the program's share always is)."""
    whole_cfg = CFG.with_(n_experts_held=CFG.n_experts)
    layers = so.init(whole_cfg, jax.random.PRNGKey(9))["linear"]
    h = jax.random.normal(jax.random.PRNGKey(10), (12, CFG.dim))
    every = [(e, e) for e in range(CFG.n_experts)]
    with jax.default_matmul_precision("highest"):
        uncut, _ = REF.layer_share(layers, whole_cfg, 1, h, every)
        shared, _ = REF.layer_share(layers, whole_cfg, 1, h, [])
    total = np.asarray(shared)
    for j in range(4):
        mine = [(4 * j + k, 4 * j + k) for k in range(4)]
        with jax.default_matmul_precision("highest"):
            ref_share, _ = REF.layer_share(layers, whole_cfg, 1, h, mine,
                                           shared=False)
        perm = np.arange(CFG.n_experts)
        perm[0:4], perm[4 * j:4 * j + 4] = np.arange(4 * j, 4 * j + 4), \
            np.arange(4)
        lw = {k: v[1] for k, v in layers.items()
              if k not in moe.EXPERT_STACKS}
        lw.update(router=lw["router"][:, perm],
                  router_bias=lw["router_bias"][perm],
                  experts=({k: layers[k][:, 4 * j:4 * j + 4]
                            for k in moe.EXPERT_STACKS}, jnp.int32(1)))
        got, _ = moe.moe_ffn(h[None], lw, CFG)
        assert np.abs(np.asarray(got[0]) - np.asarray(ref_share + shared)) \
            .max() < 1e-4
        total = total + np.asarray(ref_share)
    assert np.abs(total - np.asarray(uncut)).max() < 1e-4


def test_the_int8_path_and_what_a_bfloat16_state_does_to_it(params, tokens):
    """int8 weights both sides agree; the control the chip's tolerance is
    set against (the state rounded to bfloat16 every token) does not."""
    q = maybe_quantize(params, True)
    assert hasattr(q["full"]["w_attn_gate"], "scale")
    assert hasattr(q["linear"]["wq"], "scale")
    want = _ref_logprobs(q, CFG, tokens[:1])[0]
    got, _ = _serve(q, CFG, tokens[0], 24, 32)
    assert np.abs(got - want).max() < INT8_TOL
    # the control: serve with the state rounded to bfloat16 after every step
    pad = jnp.zeros((1, 32), jnp.int32).at[0, :24].set(tokens[0, :24])
    _, *kv, _ = so.prefill_kv(q, CFG, pad, jnp.asarray([24]))
    cache = so.write_kv(so.init_cache(CFG, 1, 64), *kv, (0, 0, 0, 0, 0),
                        jnp.asarray([24]))
    worst = 0.0
    for t in range(24, 40):
        cache = cache._replace(state=cache.state.astype(jnp.bfloat16)
                               .astype(jnp.float32))
        step, cache, _, _ = so.decode_step(q, CFG, tokens[0, t][None], cache)
        worst = max(worst, np.abs(_logprobs(step[0]) - want[t]).max())
    assert worst > 2 * INT8_TOL


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
    ref = np.asarray(REF.forward_logprobs(
        params, CFG, np.asarray(seq),
        range(len(prompt) - 1, len(seq)))[0])
    return max(abs(lp - ref[j, tok]) for j, (tok, lp) in enumerate(served))


def _generate(engine, prompt, n):
    return [(int(t), float(lp)) for t, lp in
            engine.generate(prompt, max_new_tokens=n, logprobs=True)]


@pytest.mark.parametrize("length", [10, 20, 32, 33, 70, 100])
def test_engine_against_the_reference(engine, params, length):
    """A bucket, the next, a whole bucket, one token past it (two
    chunks, the last all padding but one), three chunks, four."""
    prompt = np.random.default_rng(length).integers(1, 256, length).tolist()
    served = _generate(engine, prompt, 8)
    assert _held_to_the_reference(params, prompt, served) < F32_TOL


def test_engine_lattice_interleaved_with_other_slots_decode(engine, params):
    """Two 3- and 4-chunk prompts admitted while other slots decode: the
    decode blocks between their chunks leave a half-built state alone,
    and their chunks leave the decoding slots' states alone."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (9, 100, 14, 90, 11)]
    streams = [engine.generate(p, max_new_tokens=20, logprobs=True)
               for p in prompts]
    for p, s in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in s]
        assert len(served) == 20
        assert _held_to_the_reference(params, p, served) < F32_TOL


def test_engine_prefix_hit_is_cut_to_a_chunk_boundary(params):
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    obs = Observe(timeline=Timeline(capacity=512))
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=4,
                           prefix_store_min=16, observe=obs)
    try:
        prompt = np.random.default_rng(5).integers(1, 256, 70).tolist()
        miss = _generate(eng, prompt, 8)
        assert eng.stats()["prefix_cache"]["hits"] == 0
        hit = _generate(eng, prompt, 8)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        # stored under the tokens before the last boundary, 64 of 70
        assert [len(e.key) for e in eng._kvc.t0.entries()] == [64]
        assert [t for t, _ in hit] == [t for t, _ in miss]
        assert max(abs(a[1] - b[1]) for a, b in zip(hit, miss)) < 1e-5
        assert _held_to_the_reference(params, prompt, hit) < F32_TOL
        cut = [e for e in obs.timeline.events() if e[3] == "kvcache"]
        assert [(e[4], e[5]) for e in cut] == [("t0", 64)]
        # shares 40 tokens with the entry: below its boundary, a miss
        other = prompt[:40] + np.random.default_rng(7).integers(
            1, 256, 30).tolist()
        served = _generate(eng, other, 4)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        assert _held_to_the_reference(params, other, served) < F32_TOL
        # one chunk or less has no boundary: never stored
        short = np.random.default_rng(8).integers(1, 256, 32).tolist()
        _generate(eng, short, 2)
        assert sorted(len(e.key) for e in eng._kvc.t0.entries()) == [64, 64]
        # a longer prompt over the same 64 tokens resumes at 64 too
        longer = prompt[:64] + np.random.default_rng(9).integers(
            1, 256, 40).tolist()
        served = _generate(eng, longer, 4)
        assert eng.stats()["prefix_cache"]["hits"] == 2
        assert _held_to_the_reference(params, longer, served) < F32_TOL
    finally:
        eng.close()


@pytest.mark.parametrize("extra", [0, 1])
def test_engine_prompt_that_ends_at_a_stored_boundary(params, extra):
    """The stored key's own tokens (64 of 64) are a miss: the state the
    row holds was taken AT 64, and a resume has to prefill at least one
    token after it. One token more is a hit at 64. Either way the pool
    keeps the one row it had, and what it serves afterwards is right."""
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=4,
                           prefix_store_min=16)
    try:
        prompt = np.random.default_rng(5).integers(1, 256, 70).tolist()
        _generate(eng, prompt, 2)
        assert [len(e.key) for e in eng._kvc.t0.entries()] == [64]
        served = _generate(eng, prompt[:64 + extra], 8)
        assert eng.stats()["prefix_cache"]["hits"] == extra
        assert _held_to_the_reference(
            params, prompt[:64 + extra], served) < F32_TOL
        assert [len(e.key) for e in eng._kvc.t0.entries()] == [64]
        again = _generate(eng, prompt, 8)
        assert eng.stats()["prefix_cache"]["hits"] == extra + 1
        assert _held_to_the_reference(params, prompt, again) < F32_TOL
    finally:
        eng.close()


def test_engine_counts_states_and_says_their_bytes(params):
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=256))
    eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                           prompt_buckets=(16,), observe=obs, metrics=m)
    try:
        eng.generate([3, 4, 5], max_new_tokens=9).tokens()
        stats = eng.stats()
        events = [e for e in obs.timeline.events() if e[3] == "decode"]
    finally:
        eng.close()
    per_slot = 6 * (4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4)
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 24 * 4
    assert stats["moe_decode_dispatch"]["block_rows"] == 16
    assert stats["moe"]["expert_tokens"] > 0
    # decode events: the expert layer's two counts, then the states
    assert events and all(len(e) == 11 for e in events)
    # one slot, six linear layers, a state a step while it decodes
    assert sum(e[10] for e in events) == 6 * 8
    assert f"app_tpu_state_live_bytes {float(per_slot)}" \
        in m.render_prometheus()
    args = [e["args"] for e in obs.timeline.chrome_trace()["traceEvents"]
            if e.get("cat") == "decode"]
    assert args and "states_updated" in args[0]


@pytest.mark.parametrize("counted,tail,args", [
    ({}, (), {}),
    ({"assigned": 5, "touched": 3}, (5, 3),
     {"moe_assigned": 5, "moe_touched": 3}),
    ({"states": 12}, (None, None, 12), {"states_updated": 12}),
    ({"assigned": 5, "touched": 3, "states": 12}, (5, 3, 12),
     {"moe_assigned": 5, "moe_touched": 3, "states_updated": 12})])
def test_a_decode_events_counts_keep_their_places(counted, tail, args):
    """States without an expert layer must not read as its assignments:
    the readers take the fields by position."""
    from gofr_tpu.observe.timeline import Timeline

    tl = Timeline(capacity=8)
    tl.decode_block(0.0, 1.0, (0,), 4, 7, 8, **counted)
    (event,) = tl.events()
    assert tuple(event[8:]) == tail
    (shown,) = [e["args"] for e in tl.chrome_trace()["traceEvents"]
                if e.get("cat") == "decode"]
    assert {k: v for k, v in shown.items() if "moe" in k or "states" in k} \
        == args


class _Tiers:
    host_mb, redis = 64, None


@pytest.mark.parametrize("option", [
    {"paged_blocks": 8}, {"spec_decode_k": 2}, {"lora_adapters": 2},
    {"kvcache": _Tiers()}, {"mesh": object()},
    {"serving_role": "prefill"}, {"serving_role": "decode"},
])
def test_the_engine_refuses_what_would_restore_from_rows_alone(params,
                                                               option):
    (name,) = option
    with pytest.raises(ValueError, match=name) as e:
        GenerationEngine(CFG, params, slots=2, max_seq=64, **option)
    assert [opt for opt, _ in e.value.refused] == [name]
    assert so.unsupported_options(serving_role="fused",
                                  kv_dtype=jnp.int8) == []


def test_the_engine_refuses_a_capacity_that_is_not_whole_chunks(params):
    with pytest.raises(ValueError, match="whole prefill chunks"):
        GenerationEngine(CFG, params, slots=2, max_seq=72,
                         prompt_buckets=(16, 32))


def test_an_int8_cache_holds_the_full_layers_rows_alone(params):
    """``kv_dtype`` int8 quantises K and V of the full layers as llama's
    cache does; state and tail keep their types. The rows are held to
    the float cache's (0.4% of the largest value a vector: one int8
    step); the logits are not held to the reference here, because a row
    0.4% off moves a 16-way router across its near-ties (tiny-moe's
    int8 test says the same of llama's expert layer)."""
    prompt = list(range(1, 40))
    rows = {}
    for dtype in (None, jnp.int8):
        eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                               prompt_buckets=(16,), kv_dtype=dtype)
        try:
            served = _generate(eng, prompt, 6)
            cache = eng.cache
        finally:
            eng.close()
        assert len(served) == 6 and all(
            0 <= t < CFG.vocab_size and np.isfinite(lp) for t, lp in served)
        assert cache.state.dtype == jnp.float32
        k = np.asarray(cache.k[:, :, :, :32], np.float32)
        if dtype is not None:
            assert cache.quantized and cache.k.dtype == jnp.int8
            k = k * np.asarray(cache.k_scale[:, :, :, :32])[..., None]
        rows[dtype] = k
    slot = np.abs(rows[None]).reshape(2, 2, -1).max(-1).argmax(-1)[0]
    exact, quant = rows[None][:, slot], rows[jnp.int8]
    quant = quant[:, np.abs(quant).reshape(2, 2, -1).max(-1).argmax(-1)[0]]
    # the first chunk's rows are computed from tokens alone
    assert np.abs(quant[0, :, :16] - exact[0, :, :16]).max() \
        < 0.005 * np.abs(exact[0]).max()


def test_start_up_from_config_refuses_by_name():
    from gofr_tpu.config import MapConfig
    from gofr_tpu.tpu import new_engine_from_config

    base = {"TPU_MODEL": "tiny-kda-moe", "TPU_SLOTS": "2",
            "TPU_MAX_SEQ": "64", "TPU_SEQ_BUCKETS": "16",
            "TPU_PREFIX_CACHE": "2"}  # the host tier hangs off the pool
    for key, value in (("TPU_SPEC_DECODE", "4"),
                       ("TPU_KVCACHE_HOST_MB", "64"),
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
    tests' tolerance, and the positions counted (tests/_prefill_split.py).
    Model-type rows only: at this preset's 24-wide heads an int8 row
    puts either form 0.2 nats from the float32 engine and the greedy
    tokens of both part from it within a few steps."""
    _prefill_split.check(CFG, params, tol=tol, kv_dtype=kv_dtype)
