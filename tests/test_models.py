import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, BERT_CONFIGS, VIT_CONFIGS, bert, llama, vit
from gofr_tpu.models.common import sample_logits
from gofr_tpu.ops.quant import maybe_quantize_tree

TINY = LLAMA_CONFIGS["tiny"]


def test_llama_prefill_shapes_and_cache():
    params = llama.init(TINY, jax.random.PRNGKey(0))
    cache = llama.init_cache(TINY, batch=2, max_seq=32)
    tokens = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    logits, cache = llama.prefill(params, TINY, tokens, cache)
    assert logits.shape == (2, 4, TINY.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache.k.shape == (TINY.n_layers, 2, TINY.n_kv_heads, 32, TINY.head_dim)
    assert list(cache.lengths) == [4, 4]


def test_llama_decode_matches_prefill():
    """Token-by-token decode must reproduce the teacher-forced prefill logits."""
    params = llama.init(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, TINY.vocab_size)

    cache_full = llama.init_cache(TINY, batch=2, max_seq=16)
    logits_full, _ = llama.prefill(params, TINY, tokens, cache_full)

    # prefill only the first token, then decode the rest one at a time
    cache = llama.init_cache(TINY, batch=2, max_seq=16)
    logits, cache = llama.prefill(params, TINY, tokens[:, :1], cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(logits_full[:, 0]),
                               rtol=1e-4, atol=1e-4)
    for t in range(1, 8):
        step_logits, cache = llama.decode_step(params, TINY, tokens[:, t], cache)
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(logits_full[:, t]),
            rtol=2e-3, atol=2e-3,
        )
    assert list(cache.lengths) == [8, 8]


def test_prefill_kv_logit_pos_matches_full():
    """The sample-one-position serving path (logit_pos gathers the hidden
    state BEFORE lm_head) must equal gathering the full [B, S, V] logits
    at the same positions — for prefill_kv and prefill_chunk alike."""
    params = llama.init(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0,
                                TINY.vocab_size)
    lengths = jnp.array([6, 4], jnp.int32)
    full, k_full, v_full, _ = llama.prefill_kv(params, TINY, tokens, lengths)
    pos = lengths - 1
    sel, k_sel, v_sel, _ = llama.prefill_kv(params, TINY, tokens, lengths,
                                            logit_pos=pos)
    assert sel.shape == (2, 1, TINY.vocab_size)
    want = jnp.take_along_axis(full, pos[:, None, None], axis=1)
    np.testing.assert_allclose(np.asarray(sel), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(k_full), np.asarray(k_sel))
    np.testing.assert_array_equal(np.asarray(v_full), np.asarray(v_sel))

    cache = llama.init_cache(TINY, batch=2, max_seq=16)
    cache = cache._replace(lengths=jnp.array([6, 6], jnp.int32))
    cfull, _ = llama.prefill_chunk(params, TINY, tokens, cache, 0)
    csel, _ = llama.prefill_chunk(params, TINY, tokens, cache, 0,
                                  logit_pos=pos)
    np.testing.assert_allclose(
        np.asarray(csel),
        np.asarray(jnp.take_along_axis(cfull, pos[:, None, None], axis=1)),
        rtol=1e-5, atol=1e-5)


def test_llama_prefill_respects_padding():
    """Padding tokens after the true length must not change earlier logits."""
    params = llama.init(TINY, jax.random.PRNGKey(0))
    tokens = jnp.array([[1, 2, 3, 0, 0, 0]], jnp.int32)
    lengths = jnp.array([3], jnp.int32)
    cache = llama.init_cache(TINY, batch=1, max_seq=16)
    logits_padded, _ = llama.prefill(params, TINY, tokens, cache, lengths=lengths)

    cache2 = llama.init_cache(TINY, batch=1, max_seq=16)
    logits_exact, _ = llama.prefill(params, TINY, tokens[:, :3], cache2)
    np.testing.assert_allclose(np.asarray(logits_padded[:, :3]),
                               np.asarray(logits_exact), rtol=2e-3, atol=2e-3)


def test_llama_quantized_decode_is_close():
    cfg = TINY.with_(dtype="float32")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    qparams = maybe_quantize_tree(params, True, min_size=0)
    tokens = jnp.array([[1, 2, 3]], jnp.int32)
    cache = llama.init_cache(cfg, 1, 16)
    qcache = llama.init_cache(cfg, 1, 16)
    logits, _ = llama.prefill(params, cfg, tokens, cache)
    qlogits, _ = llama.prefill(qparams, cfg, tokens, qcache)
    # int8 weight-only: logits correlate strongly with dense
    a, b = np.asarray(logits).ravel(), np.asarray(qlogits).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.99


def test_int8_kv_cache_decode_matches_bf16():
    """The quantized KV cache (quantize-on-write, dequant fused into
    attention) must track the dense cache: greedy tokens equal, logits
    within int8 tolerance, and the cursor/scale planes maintained."""
    params = llama.init(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                TINY.vocab_size)
    dense = llama.init_cache(TINY, 2, 16)
    quant = llama.init_cache(TINY, 2, 16, dtype=jnp.int8)
    assert quant.quantized and quant.k.dtype == jnp.int8
    assert quant.k_scale.shape == quant.k.shape[:-1]

    ld, dense = llama.prefill(params, TINY, tokens, dense)
    lq, quant = llama.prefill(params, TINY, tokens, quant)
    # prefill logits come from activations, not the cache: exact match
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lq),
                               rtol=1e-5, atol=1e-5)
    for t in [3, 1, 4]:
        step = jnp.full((2,), t, jnp.int32)
        dd, dense = llama.decode_step(params, TINY, step, dense)
        dq, quant = llama.decode_step(params, TINY, step, quant)
        assert np.array_equal(np.argmax(dd, -1), np.argmax(dq, -1))
        assert float(np.abs(np.asarray(dd) - np.asarray(dq)).max()) < 0.15
    assert list(quant.lengths) == [11, 11]


def test_int8_kv_cache_chunked_prefill():
    """Chunked prefill through an int8 cache matches whole-prompt prefill
    (the long-prompt admission path with the production cache dtype)."""
    params = llama.init(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                TINY.vocab_size)
    whole = llama.init_cache(TINY, 1, 16, dtype=jnp.int8)
    lw, whole = llama.prefill(params, TINY, tokens, whole)

    chunked = llama.init_cache(TINY, 1, 16, dtype=jnp.int8)
    _, chunked = llama.prefill_chunk(params, TINY, tokens[:, :4], chunked,
                                     0, compute_logits=False)
    lg, chunked = llama.prefill_chunk(params, TINY, tokens[:, 4:], chunked, 4)
    assert float(np.abs(np.asarray(lg) - np.asarray(lw[:, 4:])).max()) < 0.15
    # stored K must match between the two admission paths (dequantized —
    # float summation order may flip an odd int8 bucket by one)
    from gofr_tpu.ops.quant import dequantize_kv
    dq_chunk = np.asarray(dequantize_kv(chunked.k, chunked.k_scale,
                                        jnp.float32))[:, :, :8]
    dq_whole = np.asarray(dequantize_kv(whole.k, whole.k_scale,
                                        jnp.float32))[:, :, :8]
    np.testing.assert_allclose(dq_chunk, dq_whole, atol=5e-2)


def _parent_qkv(x, layer_w, cfg, cos, sin, positions, adapter):
    """``_layer``'s q, k and v as they were written before its barrier:
    each projection reshaped to heads, and roped, in one expression."""
    from gofr_tpu.ops.norms import rms_norm
    from gofr_tpu.ops.quant import qmatmul
    from gofr_tpu.ops.rope import apply_rope

    B, S = x.shape[:2]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, layer_w["attn_norm"], cfg.norm_eps)
    q = (qmatmul(h, layer_w["wq"])
         + llama._lora(h, layer_w, "wq", adapter)).reshape(B, S, H, hd)
    k = (qmatmul(h, layer_w["wk"])
         + llama._lora(h, layer_w, "wk", adapter)).reshape(B, S, KV, hd)
    v = (qmatmul(h, layer_w["wv"])
         + llama._lora(h, layer_w, "wv", adapter)).reshape(B, S, KV, hd)
    return (apply_rope(q, cos, sin, positions),
            apply_rope(k, cos, sin, positions), v)


@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_qkv_barrier_changes_no_value(dtype, quant, lora):
    """The barrier between the three projections and the reshape to
    heads is about layout alone: q, k and v come out bit for bit as the
    parent's formulation gives them, with an adapter and without."""
    cfg = dataclasses.replace(TINY, dtype=dtype)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    layers = params["layers"]
    adapter = None
    if lora:
        layers = {**layers,
                  **llama.init_lora(cfg, 3, 4, jax.random.PRNGKey(2))}
        for i, name in enumerate(("wq", "wk", "wv")):  # B is made zero
            b = layers[f"lora_b_{name}"]
            layers[f"lora_b_{name}"] = (jax.random.normal(
                jax.random.PRNGKey(10 + i), b.shape) * 0.05).astype(b.dtype)
        adapter = jnp.asarray([2, 1], jnp.int32)
    layers = maybe_quantize_tree(layers, quant, min_size=1)
    layer_w = jax.tree_util.tree_map(lambda a: a[1], layers)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, cfg.dim),
                          cfg.jdtype)
    cos, sin = llama.get_rope_tables(cfg, 32)
    positions = jnp.asarray([[3, 4, 5, 6, 7], [0, 1, 2, 3, 4]], jnp.int32)

    def through_layer(x, layer_w):
        seen = []

        def attend(q, k, v):
            seen.extend((q, k, v))
            return q

        llama.layer(x, layer_w, cfg, cos, sin, positions,
                    kv_write=lambda k, v: (k, v), attend=attend,
                    adapter=adapter)
        return tuple(seen)

    got = jax.jit(through_layer)(x, layer_w)
    want = jax.jit(lambda x, lw: _parent_qkv(x, lw, cfg, cos, sin, positions,
                                             adapter))(x, layer_w)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8], ids=["float", "int8"])
def test_decode_step_logits_equal_without_the_barrier(monkeypatch, kv_dtype):
    """A whole jitted ``decode_step`` on ``tiny``: the logits and the
    rows it writes are the same bits with the barrier traced as the
    identity, which is the parent's program."""
    params = llama.init(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                TINY.vocab_size)
    cache = llama.init_cache(TINY, batch=2, max_seq=16, dtype=kv_dtype)
    _, cache = llama.prefill(params, TINY, tokens, cache)
    nxt = jnp.asarray([7, 9], jnp.int32)

    def step(tokens, cache):
        return llama.decode_step(params, TINY, tokens, cache)

    got = jax.jit(step)(nxt, cache)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    want = jax.jit(lambda t, c: step(t, c))(nxt, cache)  # traced afresh
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_llama_jit_decode_no_retrace():
    params = llama.init(TINY, jax.random.PRNGKey(0))
    cache = llama.init_cache(TINY, 2, 16)
    tokens = jnp.array([[1, 2], [3, 4]], jnp.int32)
    _, cache = llama.prefill(params, TINY, tokens, cache)

    traces = []

    @jax.jit
    def step(params, tokens, cache):
        traces.append(1)
        return llama.decode_step(params, TINY, tokens, cache)

    t = jnp.array([5, 6], jnp.int32)
    for _ in range(3):
        logits, cache = step(params, t, cache)
    assert len(traces) == 1  # compiled once, reused
    assert logits.shape == (2, TINY.vocab_size)


def test_sample_logits_greedy_and_topk():
    logits = jnp.array([[0.0, 5.0, 1.0], [2.0, 0.1, 9.0]])
    assert list(sample_logits(logits, None, temperature=0.0)) == [1, 2]
    key = jax.random.PRNGKey(0)
    s = sample_logits(logits, key, temperature=0.5, top_k=1)
    assert list(s) == [1, 2]  # top-1 sampling == greedy


def test_bert_embeddings():
    cfg = BERT_CONFIGS["tiny"]
    params = bert.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    mask = jnp.array([[1, 1, 1, 0], [1, 1, 0, 0]], bool)
    emb = bert.embed(params, cfg, tokens, mask)
    assert emb.shape == (2, cfg.dim)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(emb), axis=-1), 1.0, rtol=1e-5)
    # padding must not affect the embedding
    emb2 = bert.embed(params, cfg, jnp.array([[1, 2, 3, 9], [4, 5, 9, 9]], jnp.int32), mask)
    np.testing.assert_allclose(np.asarray(emb), np.asarray(emb2), atol=1e-5)


def test_vit_classification():
    cfg = VIT_CONFIGS["tiny"]
    params = vit.init(cfg, jax.random.PRNGKey(0))
    images = jax.random.uniform(jax.random.PRNGKey(1), (2, 28, 28, 3))
    logits = vit.forward(params, cfg, images)
    assert logits.shape == (2, cfg.n_classes)
    assert logits.dtype == jnp.float32
    # patchify roundtrip sanity
    patches = vit.patchify(images, 14)
    assert patches.shape == (2, 4, 14 * 14 * 3)
