"""The looped family (models/ouro.py) at the ``tiny-loop`` preset (two
layers run three times: six row tables a token, four norms a layer, a KV
head a query head, an untied head), held at the logit level against the
benchmark's plain float32 reference (benchmarks/references/ouro.py),
which imports nothing of the program, keeps no cache, writes the loop as
two Python ``for``s, and is the file the chip's ``correct`` is decided
by."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split

from gofr_tpu.models import LLAMA_CONFIGS, ModelConfig, family, llama, ouro
from gofr_tpu.ops.quant import QuantizedLinear
from gofr_tpu.tpu import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-loop"]
N_NEW = 12
# |log-probability - reference|, float32 both sides: six layer passes of
# float32 sums in another order
F32_TOL = 2e-4
# what a forward that loops wrongly is apart by, at the least
APART = 1e-2


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_ouro", os.path.join(
            REPO, "benchmarks", "references", "ouro.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return ouro.init(CFG, jax.random.PRNGKey(0))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n) \
        .astype(np.int32)


def _ref(params, toks, rows, cfg=CFG, **kw):
    return np.asarray(REF.forward_logprobs(params, cfg, np.asarray(toks),
                                           list(rows), **kw)[0])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_family_is_chosen_by_fields_not_by_name():
    assert family(CFG) is ouro
    assert family(LLAMA_CONFIGS["tiny"]) is llama
    # one pass without the sandwich is llama's block; either field alone
    # is this family's
    assert family(CFG.with_(loop_steps=1, sandwich_norm=False)) is llama
    assert family(CFG.with_(loop_steps=1)) is ouro
    assert family(CFG.with_(sandwich_norm=False)) is ouro
    assert family(LLAMA_CONFIGS["tiny"].with_(loop_steps=2)) is ouro
    assert ouro.kv_tables(CFG) == 6 and llama.kv_tables(CFG) == 2


@pytest.mark.parametrize("preset", sorted(
    n for n in LLAMA_CONFIGS if n.startswith("tiny")))
def test_every_family_says_its_row_tables(preset):
    """The name the engine asks for the cache's table count: the depth
    wherever a token passes a layer once."""
    cfg = LLAMA_CONFIGS[preset]
    want = 6 if preset == "tiny-loop" else cfg.n_layers
    assert family(cfg).kv_tables(cfg) == want


def test_a_threshold_under_one_is_refused_by_name():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        CFG.with_(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ModelConfig(loop_steps=4, early_exit_threshold=0.5)
    assert CFG.with_(early_exit_threshold=1.0).loop_steps == 3


def test_the_cache_has_a_table_a_pass_a_layer_and_a_step_writes_each(params):
    cache = ouro.init_cache(CFG, 3, 64, dtype=jnp.int8)
    assert cache.k.shape == (6, 3, 4, 64, 16)
    assert cache.k_scale.shape == (6, 3, 4, 64)
    cache = cache._replace(lengths=jnp.asarray([5, 0, 9], jnp.int32))
    _, new = jax.jit(lambda t, c: ouro.decode_step(params, CFG, t, c))(
        jnp.asarray([7, 8, 9], jnp.int32), cache)
    assert new.lengths.tolist() == [6, 1, 10]
    wrote = np.asarray(new.k_scale) != 0            # [6, 3, 4, 64]
    for slot, pos in enumerate((5, 0, 9)):
        # one row into every table, every KV head, at the cursor alone
        assert wrote[:, slot, :, pos].all()
        assert wrote[:, slot].sum() == 6 * 4
    # the six rows differ: a pass does not write another pass's row
    rows = np.asarray(new.k[:, 0, :, 5].astype(jnp.float32)
                      * new.k_scale[:, 0, :, 5, None])
    assert min(np.abs(rows[a] - rows[b]).max()
               for a in range(6) for b in range(a)) > 1e-3


def _serve(params, toks, L, bucket, n_new, slots=3, slot=1, cfg=CFG):
    """Whole-prompt prefill of toks[:L] into ``slot``, then ``n_new``
    decode steps teacher-forced on toks[L:]: the log-probabilities after
    positions L - 1 .. L + n_new - 1."""
    cache = ouro.init_cache(cfg, slots, 64)
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :L] = toks[:L]
    logits, k, v, _ = jax.jit(lambda t, n: ouro.prefill_kv(
        params, cfg, t, n, rope_max=64, logit_pos=n - 1))(
        jnp.asarray(pad), jnp.asarray([L]))
    cache = ouro.write_kv(cache, k, v, (0, slot, 0, 0, 0),
                          cache.lengths.at[slot].set(L))
    out = [_logprobs(logits[0, 0])]
    act = jnp.arange(slots) == slot
    step = jax.jit(lambda t, c: ouro.decode_step(params, cfg, t, c,
                                                 active=act))
    for n in range(n_new):
        t = jnp.zeros((slots,), jnp.int32).at[slot].set(int(toks[L + n]))
        logits, new = step(t, cache)
        cache = new._replace(
            lengths=jnp.where(act, new.lengths, cache.lengths))
        out.append(_logprobs(logits[slot]))
    return np.stack(out), cache


@pytest.mark.parametrize("L,bucket", [(16, 16), (15, 16), (17, 32), (1, 16)])
def test_prefill_then_decode_through_the_cache(params, L, bucket):
    toks = _tokens(100 * L + bucket, L + N_NEW)
    got, cache = _serve(params, toks, L, bucket, N_NEW)
    want = _ref(params, toks, range(L - 1, L + N_NEW))
    assert np.abs(got - want).max() < F32_TOL
    assert int(cache.lengths[1]) == L + N_NEW


@pytest.mark.parametrize("chunk,L", [(16, 40), (8, 24)])
def test_a_chunked_prompt_then_decode(params, chunk, L):
    """Chunks against the growing cache, each (pass, layer) reading its
    own table's rows before the chunk; then decode goes on from there."""
    toks = _tokens(chunk + L, L + N_NEW)
    cache = ouro.init_cache(CFG, 1, 64)
    run = jax.jit(lambda t, c, s, at, final: ouro.prefill_chunk(
        params, CFG, t, c, s, compute_logits=final,
        logit_pos=at if final else None), static_argnums=4)
    for pos in range(0, L, chunk):
        logits, cache = run(jnp.asarray(toks[None, pos:pos + chunk]), cache,
                            jnp.int32(pos), jnp.asarray([L - 1 - pos]),
                            pos + chunk >= L)
    got = [_logprobs(logits[0, 0])]
    cache = cache._replace(lengths=jnp.asarray([L], jnp.int32))
    step = jax.jit(lambda t, c: ouro.decode_step(params, CFG, t, c))
    for n in range(N_NEW):
        logits, cache = step(jnp.asarray(toks[L + n:L + n + 1]), cache)
        got.append(_logprobs(logits[0]))
    want = _ref(params, toks, range(L - 1, L + N_NEW))
    assert np.abs(np.stack(got) - want).max() < F32_TOL


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_the_reference_with_one_departure_is_another_model(params, control):
    """One pass fewer, or every pass on pass 0's keys and values: the
    check must see the loop and the tables, so each control is apart from
    the reference (and so from the engine, which agrees with it)."""
    toks = _tokens(3, 40)
    rows = range(8, 40)
    want = _ref(params, toks, rows)
    other = _ref(params, toks, rows, control=control)
    assert np.abs(other - want).max() > APART
    with pytest.raises(ValueError, match="unknown control"):
        _ref(params, toks, rows, control="no_such")


@pytest.mark.parametrize("leaf", ouro.NORMS + ("final_norm",))
def test_each_of_the_norms_shows(params, leaf):
    """The weights are drawn around 1, so a forward without one of the
    four norms' weights (or the final norm's) is another model."""
    toks = _tokens(4, 24)
    flat = dict(params, layers=dict(params["layers"]))
    where = flat if leaf == "final_norm" else flat["layers"]
    where[leaf] = jnp.ones_like(where[leaf])
    want = _logprobs(ouro.forward(params, CFG, jnp.asarray(toks[None]))[0])
    got = _logprobs(ouro.forward(flat, CFG, jnp.asarray(toks[None]))[0])
    assert np.abs(got - want).max() > APART


def test_the_model_on_the_interpreted_kernels(params, monkeypatch):
    """The decode kernel at a group of one over six tables and the
    step's write, interpreted, against the reference."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    toks = _tokens(9, 20 + 4)
    got, _ = _serve(params, toks, 20, 32, 4)
    want = _ref(params, toks, range(19, 20 + 4))
    assert np.abs(got - want).max() < F32_TOL


def test_a_checkpoint_loads_by_the_sources_names(params, tmp_path):
    """The two further norms and the exit gate keep the source's names,
    go through a checkpoint file and quantise-on-load as they are (no
    int8), and the loaded tree serves the same logits."""
    from gofr_tpu.tpu.checkpoint import load_npz, maybe_quantize, save_npz

    path = str(tmp_path / "ouro.npz")
    save_npz(path, params)
    loaded = maybe_quantize(load_npz(path), True)
    layers = loaded["layers"]
    for name in ("input_layernorm_2", "post_attention_layernorm_2"):
        assert layers[name].shape == (2, 64)
        np.testing.assert_array_equal(layers[name], params["layers"][name])
    assert set(loaded["early_exit_gate"]) == {"w", "b"}
    assert not isinstance(loaded["early_exit_gate"]["w"], QuantizedLinear)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert isinstance(layers[name], QuantizedLinear)
    assert isinstance(loaded["lm_head"], QuantizedLinear)
    # served from the quantised tree, against the reference on the same
    toks = _tokens(6, 30)
    got = _logprobs(ouro.forward(loaded, CFG, jnp.asarray(toks[None]))[0])
    want = _ref(loaded, toks, range(30))
    assert np.abs(got - want).max() < 5e-4


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params):
    eng = GenerationEngine(CFG, params, slots=3, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=2,
                           prefix_store_min=16, kv_dtype=jnp.float32)
    yield eng
    eng.close()


def _held_to(params, prompt, served, **kw):
    """Each served token's log-probability against the reference's,
    teacher-forced on prompt + the tokens served (the chip's check)."""
    seq = list(prompt) + [t for t, _ in served[:-1]]
    ref = _ref(params, seq, range(len(prompt) - 1, len(seq)), **kw)
    return max(abs(lp - ref[j, tok]) for j, (tok, lp) in enumerate(served))


def _generate(engine, prompt, n):
    return [(int(t), float(lp)) for t, lp in
            engine.generate(prompt, max_new_tokens=n, logprobs=True)]


@pytest.mark.parametrize("length", [1, 15, 16, 17, 32, 33, 70, 100])
def test_engine_against_the_reference(engine, params, length):
    """Prompts around each bucket, one token past the largest (the
    chunked path), three chunks, four; then decode through the cache.
    Both controls are apart from what the engine served."""
    prompt = _tokens(length, length).tolist()
    served = _generate(engine, prompt, N_NEW)
    assert _held_to(params, prompt, served) < F32_TOL
    if length >= 16:
        for control in REF.CONTROLS:
            assert _held_to(params, prompt, served, control=control) > APART


def test_engine_interleaves_long_prompts_with_decode(engine, params):
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (9, 100, 2, 90, 11)]
    streams = [engine.generate(p, max_new_tokens=20, logprobs=True)
               for p in prompts]
    for p, s in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in s]
        assert len(served) == 20
        assert _held_to(params, p, served) < F32_TOL


def test_engine_prefix_hit_restores_every_table(params):
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=2,
                           prefix_store_min=16, kv_dtype=jnp.float32)
    try:
        # the pool's layout counts the family's tables, not the depth
        assert eng._kvc.layout.layers == 6
        assert eng._pool.k.shape[0] == 6
        prompt = _tokens(5, 70).tolist()
        miss = _generate(eng, prompt, N_NEW)
        assert eng.stats()["prefix_cache"]["hits"] == 0
        hit = _generate(eng, prompt, N_NEW)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        assert [t for t, _ in hit] == [t for t, _ in miss]
        assert max(abs(a[1] - b[1]) for a, b in zip(hit, miss)) < 1e-5
        assert _held_to(params, prompt, hit) < F32_TOL
        # every table of the restored slot holds the row, not the first
        # n_layers alone: a hit with tables missing would read zeros in
        # passes 1 and 2 and land on the ``pass0_rows`` side of APART
        assert _held_to(params, prompt, hit, control="pass0_rows") > APART
    finally:
        eng.close()


def test_engine_with_an_int8_cache_stays_near_the_reference(params):
    eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                           prompt_buckets=(16, 32), kv_dtype=jnp.int8)
    try:
        assert eng.cache.k.dtype == jnp.int8 and eng.cache.k.shape[0] == 6
        prompt = _tokens(8, 40).tolist()
        served = _generate(eng, prompt, N_NEW)
        assert _held_to(params, prompt, served) < 0.05
    finally:
        eng.close()


def test_engine_says_its_loop_and_counts_it(params):
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=256))
    eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                           prompt_buckets=(16,), observe=obs, metrics=m,
                           decode_block=4, kv_dtype=jnp.int8)
    try:
        eng.generate([3, 4, 5], max_new_tokens=13).tokens()
        stats = eng.stats()
        cache = eng.cache
    finally:
        eng.close()
    assert stats["loop_steps"] == 3 and stats["kv_tables"] == 6
    # an int8 cache's bytes a token are what its arrays take a position
    assert cache.k.dtype == jnp.int8
    assert stats["kv_bytes_per_token"] * 64 * 2 == sum(
        a.nbytes for a in (cache.k, cache.v, cache.k_scale, cache.v_scale))
    # the seven projections of two layers, three times, and the head
    per_layer = 4 * 64 * 64 + 3 * 64 * 96
    assert stats["weight_bytes_per_step"] == 3 * 2 * per_layer + 64 * 256
    # twelve tokens came out of decode steps (the first of the thirteen
    # out of the prefill), each through every pass
    assert stats["loop"] == {"tokens": 12, "passes": 36}
    # the last decode block that held the stream: its live positions at
    # this family's bytes a token
    text = m.render_prometheus()
    assert "app_tpu_kv_live_bytes" in text
    live = [float(line.split()[-1]) for line in text.splitlines()
            if line.startswith("app_tpu_kv_live_bytes")]
    assert live and live[0] % stats["kv_bytes_per_token"] == 0


class _Tiers:
    host_mb, redis = 64, None


@pytest.mark.parametrize("option", [
    {"paged_blocks": 8}, {"spec_decode_k": 2}, {"lora_adapters": 2},
    {"kvcache": _Tiers()}, {"mesh": object()},
    {"serving_role": "prefill"}, {"serving_role": "decode"},
])
def test_the_engine_refuses_what_counts_tables_by_the_depth(params, option):
    from gofr_tpu.errors import UnsupportedOptions

    (name,) = option
    with pytest.raises(UnsupportedOptions, match=name) as e:
        GenerationEngine(CFG, params, slots=2, max_seq=64, **option)
    assert [opt for opt, _ in e.value.refused] == [name]
    # an int8 row is this family's deployment; the fused role is no role
    assert ouro.unsupported_options(serving_role="fused",
                                    kv_dtype=jnp.int8) == []


def test_start_up_from_config_refuses_by_name():
    from gofr_tpu.config import MapConfig
    from gofr_tpu.tpu import new_engine_from_config

    base = {"TPU_MODEL": "tiny-loop", "TPU_SLOTS": "2", "TPU_MAX_SEQ": "64",
            "TPU_SEQ_BUCKETS": "16", "TPU_QUANT": "int8",
            "TPU_PREFIX_CACHE": "1"}  # the host tier hangs off the pool
    for key, value in (("TPU_SPEC_DECODE", "4"),
                       ("TPU_KVCACHE_HOST_MB", "64"),
                       ("TPU_SERVING_ROLE", "decode")):
        with pytest.raises(ValueError, match=key):
            new_engine_from_config(MapConfig({**base, key: value}))
    eng = new_engine_from_config(MapConfig(base))
    try:
        gen = eng.generator
        assert gen.cache.k.dtype == jnp.int8 and gen.cache.k.shape[0] == 6
        # random_params: int8 projections, the norms and the gate as
        # ``init`` draws them (around 1, not quantised)
        layers = gen.params["layers"]
        assert isinstance(layers["wq"], QuantizedLinear)
        norm = np.asarray(layers["input_layernorm_2"], np.float32)
        assert norm.shape == (2, 64)
        # within a tenth of its mean: 1/8 inside a residual branch
        assert abs(norm.mean() / ouro.BRANCH_GAIN - 1) < 0.05
        assert 0.05 < norm.std() / ouro.BRANCH_GAIN < 0.2
        first = np.asarray(layers["attn_norm"], np.float32)
        assert abs(first.mean() - 1) < 0.05 and 0.05 < first.std() < 0.2
        assert gen.generate([1, 2, 3], max_new_tokens=3).tokens()
    finally:
        eng.close()


def test_the_fingerprint_tells_a_looped_model_from_a_one_pass_one():
    """A stored row of a one-pass model is never restored into a looped
    one of the same name and widths: the fingerprint hashes the tables."""
    from gofr_tpu.tpu.kvcache import model_fingerprint

    once = CFG.with_(loop_steps=1, sandwich_norm=False)
    assert model_fingerprint(CFG) != model_fingerprint(once)
    assert model_fingerprint(CFG) != model_fingerprint(
        CFG.with_(loop_steps=2))
    # the same number wherever a token passes a layer once
    tiny = LLAMA_CONFIGS["tiny"]
    assert model_fingerprint(tiny) == model_fingerprint(
        tiny.with_(norm_eps=1e-6))


# -- a prompt as two dispatches -------------------------------------------------

@pytest.mark.parametrize("kv_dtype,tol", [(None, F32_TOL), (jnp.int8, 0.05)])
def test_a_split_admission_is_the_one_bucket_admission(params, kv_dtype, tol):
    """A prompt admitted as a whole bucket and the rest (overlapped: this
    family's last chunk) against the same prompt in one padded bucket:
    the same greedy tokens, logprobs and cache arrays to the chunked
    tests' tolerance, and the positions counted (tests/_prefill_split.py).
    With int8 rows the rest attends over the first part's rows as the
    cache holds them, quantized, which is what decode reads: the bound
    is the int8 engine test's."""
    _prefill_split.check(CFG, params, tol=tol, kv_dtype=kv_dtype)
