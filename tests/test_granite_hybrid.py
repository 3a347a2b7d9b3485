"""The state-space family's layer of two halves (``tiny-ssm-dense``: a
mixer and a gated feed-forward a layer, ONE group, heads of 64 paired in
the cache, four multipliers, tied head) against its plain float32
reference, ``benchmarks/references/granite_hybrid.py``: logits, not
tokens, through prefill, the chunk lattice, decode, the pool and the
kernels (interpreted)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, family, hybrid_cache
from gofr_tpu.models import nemotron_h as nh
from gofr_tpu.ops import ssd
from gofr_tpu.tpu import GenerationEngine, random_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-ssm-dense"]
# |log-probability - reference|, float32 both sides: six layers of
# float32 sums in another order (the chunk form, paired rows)
F32_TOL = 2e-4
# int8 weights both sides: the per-channel scale is applied after the
# matmul in the program and before it in the reference
INT8_TOL = 2e-3
KERNEL_TOL = 2e-5


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_granite_hybrid", os.path.join(
            REPO, "benchmarks", "references", "granite_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return nh.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(2), (2, 40), 1,
                              CFG.vocab_size)


def _ref_logprobs(params, cfg, toks, **kw):
    return np.stack([np.asarray(REF.forward_logprobs(
        params, cfg, np.asarray(row), range(len(row)), **kw)[0])
        for row in toks])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_preset_is_the_family_with_a_layer_of_two_halves(params):
    assert family(CFG) is nh
    assert nh.counts(CFG) == (4, 0, 2)
    assert CFG.layer_ffn and CFG.ssm_groups == 1 and CFG.tie_embeddings
    # heads of 64 where dim // n_heads is 16: two KV heads a cache row
    assert CFG.head_dim == 64 != CFG.dim // CFG.n_heads
    assert hybrid_cache.paired(CFG) and nh.kv_layout(CFG) == (1, 128)
    assert nh.init_cache(CFG, 3, 64).k.shape == (2, 3, 1, 64, 128)
    # the older presets' rows stay a KV head each: narrower than half a
    # lane row, and their int8 cache keeps a scale a row
    for name in ("tiny-ssm-moe", "tiny-kda-moe"):
        cfg = LLAMA_CONFIGS[name]
        assert not hybrid_cache.paired(cfg)
        assert hybrid_cache.kv_layout(cfg) == (cfg.n_kv_heads, cfg.head_dim)
    assert all(m != 1.0 for m in (
        CFG.embedding_multiplier, CFG.residual_multiplier,
        CFG.logits_scaling, CFG.attention_multiplier * CFG.head_dim ** 0.5))
    # a feed-forward a layer, no routed layer, no head of its own
    assert set(params) == {"embedding", "norm", "mamba", "attn", "ffn",
                           "final_norm"}
    assert params["ffn"]["w_ffn_in"].shape == (6, 64, 2 * 96)
    with pytest.raises(ValueError, match="second half"):
        nh.counts(CFG.with_(layer_pattern=("mamba", "moe") * 3))
    with pytest.raises(ValueError, match="int8"):
        nh.init_cache(CFG, 2, 64, jnp.int8)


def test_full_forward_against_the_reference(params, tokens):
    logits = jax.jit(lambda p, t: nh.forward(p, CFG, t))(params, tokens)
    err = np.abs(_logprobs(logits) - _ref_logprobs(params, CFG, tokens))
    assert err.max() < F32_TOL


def test_an_in_projection_that_is_not_whole_lane_rows_is_padded(tokens):
    """At a model width of whole lane rows a ``w_ssm_in`` of 296 columns
    is stored 384 wide (the chip would keep it input-axis-minor and copy
    it every dispatch); the columns past dt are read by nothing, in the
    program and the reference alike. The preset's own width (64) is not
    padded, nor is the older preset's."""
    assert nh.in_width(CFG) == 128 + 160 + 8
    assert nh.in_width(LLAMA_CONFIGS["tiny-ssm-moe"]) == 64 + 128 + 8
    wide = CFG.with_(dim=128)
    assert nh.in_width(wide) == 384
    params = nh.init(wide, jax.random.PRNGKey(1))
    assert params["mamba"]["w_ssm_in"].shape == (4, 128, 384)
    logits = jax.jit(lambda p, t: nh.forward(p, wide, t))(params, tokens[:1])
    err = np.abs(_logprobs(logits) - _ref_logprobs(params, wide, tokens[:1]))
    assert err.max() < F32_TOL
    # what lies past dt moves nothing
    moved = dict(params, mamba=dict(
        params["mamba"], w_ssm_in=params["mamba"]["w_ssm_in"]
        .at[:, :, 296:].set(7.0)))
    again = jax.jit(lambda p, t: nh.forward(p, wide, t))(moved, tokens[:1])
    assert np.array_equal(np.asarray(again), np.asarray(logits))


def _serve(params, cfg, row, L, bucket, slots=3, slot=1):
    """Prefill ``row[:L]`` padded to ``bucket`` into one slot of a cache
    whose other slots idle, then decode the rest a token a step:
    log-probabilities [len(row), V]."""
    pad = jnp.zeros((1, bucket), jnp.int32).at[0, :L].set(row[:L])
    logits, *kv, _ = jax.jit(lambda p, t, n: nh.prefill_kv(p, cfg, t, n))(
        params, pad, jnp.asarray([L]))
    cache = nh.init_cache(cfg, slots, 64)
    cache = nh.write_kv(cache, *kv, (0, slot, 0, 0, 0),
                        cache.lengths.at[slot].set(L))
    active = jnp.arange(slots) == slot
    decode = jax.jit(lambda p, t, c: nh.decode_step(p, cfg, t, c,
                                                    active=active))
    out = [logits[0, :L]]
    for t in range(L, len(row)):
        step, cache, n, _ = decode(
            params, jnp.zeros((slots,), jnp.int32).at[slot].set(row[t]),
            cache)
        assert n is None     # no routed layer: nothing to count
        out.append(step[slot][None])
    return _logprobs(jnp.concatenate(out)), cache


def test_prefill_in_a_padded_bucket_then_decode_with_idle_slots(params,
                                                                tokens):
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    got, cache = _serve(params, CFG, tokens[0], 24, 32)
    assert np.abs(got - want).max() < F32_TOL
    # the idle slots' state and tail are bit for bit what they were
    assert not np.asarray(cache.state[:, [0, 2]]).any()
    assert not np.asarray(cache.conv[:, [0, 2]]).any()


def test_the_model_on_the_interpreted_kernels(params, tokens, monkeypatch):
    """The in-place decode kernel, the chunk kernel at one group, the
    decode kernel over paired rows and the row append."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    got, _ = _serve(params, CFG, tokens[0], 24, 32)
    assert np.abs(got - want).max() < F32_TOL


@pytest.mark.parametrize("chunk,L", [(16, 40), (8, 20), (16, 36)])
def test_a_lattice_of_three_chunks_that_crosses_a_state(params, tokens,
                                                         chunk, L):
    """Left-aligned chunks, the last padded: each goes on from the state,
    the tail and the paired rows the one before it left; the slot's
    stale state and tail are not read at position 0."""
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    cache = nh.init_cache(CFG, 1, 64)
    cache = cache._replace(state=cache.state + 3.0, conv=cache.conv + 2.0)
    mid = jax.jit(lambda p, t, c, s: nh.prefill_chunk(
        p, CFG, t, c, s, compute_logits=False)[1])
    pos, n = 0, 0
    while L - pos > chunk:
        cache = mid(params, tokens[:1, pos:pos + chunk], cache,
                    jnp.int32(pos))
        pos, n = pos + chunk, n + 1
    assert n == 2
    last = jnp.zeros((1, chunk), jnp.int32).at[0, :L - pos].set(
        tokens[0, pos:L])
    logits, cache = jax.jit(lambda p, t, c, s, at: nh.prefill_chunk(
        p, CFG, t, c, s, logit_pos=at))(
        params, last, cache, jnp.int32(pos), jnp.asarray([L - pos - 1]))
    assert np.abs(_logprobs(logits[0, 0]) - want[L - 1]).max() < F32_TOL
    _, k, _, state, conv, _ = jax.jit(
        lambda p, t: nh.prefill_kv(p, CFG, t))(params, tokens[:1, :L])
    assert np.abs(np.asarray(cache.state - state)).max() < 1e-4
    assert np.abs(np.asarray(cache.conv - conv)).max() < 1e-4
    # the rows the chunks wrote are the whole prompt's, paired
    got = np.moveaxis(np.asarray(cache.k[:, :, :, :L]), 3, 2)
    assert np.abs(got - np.asarray(k)).max() < 1e-4


# -- each multiplier matters ----------------------------------------------------

@pytest.mark.parametrize("field,without", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.0), ("logits_scaling", 1.0)])
def test_the_comparison_fails_with_a_multiplier_left_out(params, tokens,
                                                          field, without):
    """The program without one multiplier (the attention's: at
    head_dim^-1/2) against the reference with all four: each fails the
    tolerance the whole model passes, by ten times or more."""
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    cfg = CFG.with_(**{field: without})
    got, _ = _serve(params, cfg, tokens[0], 24, 32)
    assert np.abs(got - want).max() > 10 * F32_TOL
    # and the reference reads the same fields: without it too, they agree
    assert np.abs(got - _ref_logprobs(params, cfg, tokens[:1])[0]).max() \
        < F32_TOL


def test_a_bfloat16_state_fails_the_comparison(params, tokens):
    """The control the cell's check names: the reference with its state
    rounded to bfloat16 after every token is not the program."""
    got, _ = _serve(params, CFG, tokens[0], 24, 32)
    rounded = _ref_logprobs(params, CFG, tokens[:1],
                            state_dtype=jnp.bfloat16)[0]
    assert np.abs(got - rounded).max() > 5 * F32_TOL


def test_the_int8_path_and_its_draw():
    """``tpu.random_params``: the projections int8, the feed-forward's
    pair among them, wq and wk drawn at the fan-in the family says; the
    served logits against the reference on the same leaves."""
    q = random_params(nh.init, CFG, quant=True, seed=3)
    for stack, names in (("ffn", ("w_ffn_in", "w_ffn_out")),
                         ("mamba", ("w_ssm_in", "w_ssm_out")),
                         ("attn", ("wq", "wk", "wv", "wo"))):
        for name in names:
            assert q[stack][name].w.dtype == jnp.int8, name
    assert q["embedding"].dtype == jnp.float32 and "lm_head" not in q
    fan = nh.init.fan_in(CFG, "wq")
    assert fan == CFG.dim * CFG.attention_multiplier * 8 == 16
    assert nh.init.fan_in(CFG, "wv") is None
    scale = float(q["attn"]["wq"].scale[0, 0])
    assert scale == pytest.approx(fan ** -0.5 * 3 ** 0.5 / 127)
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 30), 1, 256)
    want = _ref_logprobs(q, CFG, toks)[0]
    got, _ = _serve(q, CFG, toks[0], 20, 32)
    assert np.abs(got - want).max() < INT8_TOL


def test_an_idle_slot_and_a_reused_slot(params, tokens):
    _, cache = _serve(params, CFG, tokens[0], 24, 32)
    dirty = cache._replace(state=cache.state.at[:, 0].set(7.0),
                           conv=cache.conv.at[:, 0].set(5.0))
    step, after, _, updated = jax.jit(
        lambda p, t, c, a: nh.decode_step(p, CFG, t, c, active=a))(
        params, jnp.asarray([9, 9, 9]), dirty,
        jnp.asarray([False, True, False]))
    assert int(updated) == 4
    for a, b in ((after.state, dirty.state), (after.conv, dirty.conv)):
        assert np.array_equal(np.asarray(a[:, [0, 2]]),
                              np.asarray(b[:, [0, 2]]))
        assert not np.array_equal(np.asarray(a[:, 1]), np.asarray(b[:, 1]))
    # slot 0 is taken by a new prompt, chunked from position 0
    want = _ref_logprobs(params, CFG, tokens[1:2])[0]
    small = jax.tree_util.tree_map(lambda a: a[:, :1],
                                   dirty._replace(lengths=None))
    small = small._replace(lengths=jnp.zeros((1,), jnp.int32))
    logits, _ = jax.jit(lambda p, t, c: nh.prefill_chunk(
        p, CFG, t, c, jnp.int32(0), logit_pos=jnp.asarray([15])))(
        params, tokens[1:2, :16], small)
    assert np.abs(_logprobs(logits[0, 0]) - want[15]).max() < F32_TOL


# -- the kernels at one group, interpreted ----------------------------------------

def _recurrence_inputs(B, T, H, P, G, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    R = H // G * P
    delta = jax.nn.softplus(jax.random.normal(ks[0], (B, T, H)) - 1)
    la = -jnp.exp(jax.random.uniform(ks[1], (H,), maxval=2.5)) * delta
    dx = jax.random.normal(ks[2], (B, T, G, R)) * ssd._rows(delta, G, R)
    bm, cm = (jax.random.normal(k, (B, T, G, N)) for k in ks[3:5])
    return dx, la, bm, cm, jax.random.normal(ks[5], (B, G, N, R))


def _close(got, want):
    return float(jnp.abs(got - want).max()
                 / jnp.maximum(jnp.abs(want).max(), 1.0))


@pytest.fixture
def cut_at(monkeypatch):
    """Set the lanes of a group a prefill program holds; the kernel reads
    them as it is traced, so what was traced at another cut is dropped,
    before and after."""
    def set_lanes(lanes):
        monkeypatch.setattr(ssd, "_PREFILL_LANES", lanes)
        ssd.ssd_prefill.clear_cache()
    yield set_lanes
    ssd.ssd_prefill.clear_cache()


@pytest.mark.parametrize("T,chunk,dims,lanes,cuts", [
    (32, 16, (16, 32, 1, 16), 128, 4),     # ONE group of 512 lanes in four
    (24, 8, (8, 64, 1, 16), 256, 2),       # heads of 64, two a lane row
    (32, 16, (16, 32, 1, 16), 1024, 1),    # the group whole
    (24, 8, (16, 8, 8, 16), 1024, 1),      # eight groups: never cut
    (32, 16, (16, 32, 2, 16), 128, 2)])    # two groups, each in two
def test_the_chunk_kernel_with_a_lane_cut(cut_at, T, chunk, dims, lanes,
                                          cuts):
    dx, la, bm, cm, s0 = _recurrence_inputs(2, T, *dims)
    H, P, G, _ = dims
    cut_at(lanes)
    assert ssd.prefill_cuts(H // G * P, P) == cuts
    want_y, want_s = ssd.recurrent_ref(dx, la, bm, cm, s0)
    y, s1 = ssd.ssd_prefill(dx, la, bm, cm, s0, chunk=chunk, interpret=True)
    assert _close(y, want_y) < KERNEL_TOL and _close(s1, want_s) < KERNEL_TOL


def test_the_published_group_is_cut_in_four_and_the_older_one_is_not():
    assert ssd.prefill_cuts(4096, 64) == 4      # 64 heads of 64, one group
    assert ssd.prefill_cuts(1024, 64) == 1      # 16 heads of 64 a group


@pytest.mark.parametrize("dims", [(16, 32, 1, 16), (16, 8, 8, 16)])
@pytest.mark.parametrize("T", [8, 16])
def test_a_bucket_shorter_than_a_chunk_equals_its_padded_form(cut_at, T,
                                                              dims):
    """One chunk of the bucket's own length against the same tokens
    padded with identity positions to a whole chunk of 32."""
    dx, la, bm, cm, s0 = _recurrence_inputs(2, T, *dims)
    cut_at(256)
    y, s1 = ssd.ssd_prefill(dx, la, bm, cm, s0, chunk=32, interpret=True)
    padded = ssd._pad_tokens((dx, la, bm, cm), 32 - T)
    yp, sp = ssd.ssd_prefill(*padded, s0, chunk=32, interpret=True)
    assert _close(y, yp[:, :T]) < KERNEL_TOL and _close(s1, sp) < KERNEL_TOL
    want_y, want_s = ssd.recurrent_ref(dx, la, bm, cm, s0)
    assert _close(y, want_y) < KERNEL_TOL and _close(s1, want_s) < KERNEL_TOL


@pytest.mark.parametrize("dims", [(16, 32, 1, 16), (16, 8, 8, 16)])
@pytest.mark.parametrize("active", [[True, False, True, True, False],
                                    [False] * 5])
def test_the_decode_kernel_at_one_group_and_at_eight(active, dims):
    dx, la, bm, cm, s0 = _recurrence_inputs(5, 1, *dims, seed=1)
    state = jnp.stack([s0 + 1, s0, s0 - 1])
    act = jnp.asarray(active)
    args = (dx[:, 0], la[:, 0], bm[:, 0], cm[:, 0], act)
    want_y, want_s = ssd.decode_ref(state, 1, *args)
    y, got = ssd.ssd_decode(state, jnp.int32(1), *args, interpret=True)
    assert _close(y, want_y) < KERNEL_TOL and _close(got, want_s) < KERNEL_TOL
    idle = np.flatnonzero(~np.asarray(act))
    assert np.array_equal(np.asarray(got)[:, idle],
                          np.asarray(state)[:, idle])


# -- the engine -------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params):
    eng = GenerationEngine(CFG, params, slots=3, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=2,
                           prefix_store_min=16)
    yield eng
    eng.close()


def _held_to_the_reference(params, prompt, served):
    seq = list(prompt) + [t for t, _ in served[:-1]]
    ref = np.asarray(REF.forward_logprobs(
        params, CFG, np.asarray(seq),
        range(len(prompt) - 1, len(seq)))[0])
    return max(abs(lp - ref[j, tok]) for j, (tok, lp) in enumerate(served))


def _generate(engine, prompt, n):
    return [(int(t), float(lp)) for t, lp in
            engine.generate(prompt, max_new_tokens=n, logprobs=True)]


@pytest.mark.parametrize("length", [10, 32, 33, 70, 100])
def test_engine_against_the_reference(engine, params, length):
    """A bucket, a whole bucket, one token past it (two chunks), three
    chunks, four."""
    prompt = np.random.default_rng(length).integers(1, 256, length).tolist()
    served = _generate(engine, prompt, 8)
    assert _held_to_the_reference(params, prompt, served) < F32_TOL


def test_engine_lattice_interleaved_with_other_slots_decode(engine, params):
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (9, 100, 14, 90)]
    streams = [engine.generate(p, max_new_tokens=16, logprobs=True)
               for p in prompts]
    for p, s in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in s]
        assert len(served) == 16
        assert _held_to_the_reference(params, p, served) < F32_TOL


def test_engine_pool_hit_restores_every_layers_state(params):
    """A prefix-pool row carries the four states, the tails and the
    paired rows at the chunk boundary; a hit resumes there and equals
    the miss."""
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=4,
                           prefix_store_min=16)
    try:
        prompt = np.random.default_rng(5).integers(1, 256, 70).tolist()
        miss = _generate(eng, prompt, 8)
        assert eng.stats()["prefix_cache"]["hits"] == 0
        hit = _generate(eng, prompt, 8)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        assert [len(e.key) for e in eng._kvc.t0.entries()] == [64]
        assert [t for t, _ in hit] == [t for t, _ in miss]
        assert max(abs(a[1] - b[1]) for a, b in zip(hit, miss)) < 1e-5
        assert _held_to_the_reference(params, prompt, hit) < F32_TOL
    finally:
        eng.close()


def test_the_state_is_float32_under_a_bfloat16_model():
    """The state keeps float32 whatever the model's type: the engine's
    cache, and what its decode step hands back."""
    cfg = CFG.with_(dtype="bfloat16")
    eng = GenerationEngine(cfg, nh.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(16,))
    try:
        assert eng.cache.state.dtype == jnp.float32
        assert eng.cache.conv.dtype == eng.cache.k.dtype == jnp.bfloat16
        eng.generate([3, 4, 5], max_new_tokens=5).tokens()
        assert eng.cache.state.dtype == jnp.float32
        assert float(jnp.abs(eng.cache.state).max()) > 0
    finally:
        eng.close()


def test_engine_says_state_bytes_token_bytes_and_paired_rows(params):
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
    # four mamba layers: 8 x 16 x 16 float32 and 3 tail inputs of
    # 128 + 2 x 16 channels
    per_slot = 4 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 64 * 4
    assert stats["kv_heads_per_row"] == 2
    assert "moe_decode_dispatch" not in stats
    # decode events: no expert counts, the states updated: one slot,
    # four mamba layers, a state a step while it decodes
    assert events and all(e[8] is None for e in events)
    assert sum(e[10] for e in events) == 4 * 8
    assert f"app_tpu_state_live_bytes {float(per_slot)}" \
        in m.render_prometheus()
