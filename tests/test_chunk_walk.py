"""A chunk's attention over the cache walks the blocks under its start.

``ops.attention.chunk_attention`` and ``ops.mla.chunk_attention`` run one
softmax over the cached rows below ``start`` and the chunk's own tokens
as a running softmax over blocks of rows. The one-softmax formula they
replaced is kept here as the reference. A cached row (and an int8
cache's scale) past the last block the walk may fetch is NaN: a row
fetched that should not be would show in every output.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import attention, mla
from gofr_tpu.ops.quant import quantize_kv

BLOCK, SMAX, C, D = 16, 64, 8, 32
STARTS = [0, 1, 21, 32, SMAX]    # none, one row, mid-block, an edge, all


def _one_softmax(q, k_cache, v_cache, k_new, v_new, start, k_scale=None,
                 v_scale=None, scale=None):
    """``chunk_attention`` as it stood before the walk: every reserved
    row scored, masked by the cursor, one softmax."""
    b, c, h, d = q.shape
    n_kv, smax = k_cache.shape[1], k_cache.shape[2]
    scale = scale or d ** -0.5
    qg = (q * scale).reshape(b, c, n_kv, h // n_kv, d)
    scores_c = jnp.einsum("bskgd,bktd->bkgst", qg, k_cache.astype(qg.dtype),
                          preferred_element_type=jnp.float32)
    if k_scale is not None:
        scores_c = scores_c * k_scale[:, :, None, None, :]
    in_prefix = jnp.arange(smax)[None, :] < start
    scores_c = jnp.where(in_prefix[None, None, None], scores_c,
                         attention.NEG_INF)
    scores_n = jnp.einsum("bskgd,btkd->bkgst", qg, k_new,
                          preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((c, c), dtype=bool))
    scores_n = jnp.where(causal[None, None, None], scores_n,
                         attention.NEG_INF)
    probs = jax.nn.softmax(
        jnp.concatenate([scores_c, scores_n], axis=-1), axis=-1)
    probs_c = probs[..., :smax]
    if v_scale is not None:
        probs_c = probs_c * v_scale[:, :, None, None, :]
    vdt = q.dtype if v_scale is not None else v_cache.dtype
    out = (jnp.einsum("bkgst,bktd->bskgd",
                      probs_c.astype(vdt), v_cache.astype(vdt))
           + jnp.einsum("bkgst,btkd->bskgd",
                        probs[..., smax:].astype(v_new.dtype), v_new))
    return out.reshape(b, c, h, d)


def _poison(x, start, axis):
    """NaN (or, in an int8 array, the value that scores highest) in
    every row past the last block a walk to ``start`` may fetch."""
    fetched = -(-start // BLOCK) * BLOCK
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    past = jnp.arange(x.shape[axis]).reshape(shape) >= fetched
    bad = 127 if x.dtype == jnp.int8 else jnp.nan
    return jnp.where(past, jnp.asarray(bad, x.dtype), x)


def _case(start, int8, group, dtype=jnp.float32, b=2, kv=2):
    ks = jax.random.split(jax.random.PRNGKey(7 * start + group), 5)
    h = kv * group
    q = jax.random.normal(ks[0], (b, C, h, D), dtype)
    k_new = jax.random.normal(ks[1], (b, C, kv, D), dtype)
    v_new = jax.random.normal(ks[2], (b, C, kv, D), dtype)
    # the cache in its own order [B, KV, Smax, D]
    k = jax.random.normal(ks[3], (b, kv, SMAX, D), dtype)
    v = jax.random.normal(ks[4], (b, kv, SMAX, D), dtype)
    scales = (None, None)
    if int8:
        (k, ks_), (v, vs_) = quantize_kv(k), quantize_kv(v)
        scales = (ks_, vs_)
    return q, k, v, k_new, v_new, scales


@pytest.mark.parametrize("scaled", [False, True],
                         ids=["scale_default", "scale_handed_in"])
@pytest.mark.parametrize("group", [1, 4], ids=["group1", "group4"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16_cache",
                                                     "int8_cache"])
@pytest.mark.parametrize("start", STARTS)
def test_the_walk_equals_one_softmax_over_every_row(monkeypatch, start, int8,
                                                    group, scaled):
    dtype = jnp.bfloat16
    q, k, v, k_new, v_new, (k_s, v_s) = _case(start, int8, group, dtype)
    scale = 0.11 if scaled else None
    want = _one_softmax(q, k, v, k_new, v_new, start, k_s, v_s, scale)
    assert want.dtype == dtype

    monkeypatch.setattr(attention, "_CHUNK_BLOCK", BLOCK)
    bad = [_poison(k, start, 2), _poison(v, start, 2)]
    bad_s = [None if s is None else _poison(s, start, 2)
             for s in (k_s, v_s)]
    got = jax.jit(lambda n: attention.chunk_attention(
        q, bad[0], bad[1], k_new, v_new, n, *bad_s, scale=scale))(
            jnp.int32(start))
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # bfloat16 operands either way; what differs is where the
    # probabilities are divided (after the value matmul, in float32)
    np.testing.assert_allclose(got, want, atol=0.03)


@pytest.mark.parametrize("int8", [False, True], ids=["f32_cache",
                                                     "int8_cache"])
@pytest.mark.parametrize("start", STARTS)
def test_the_walk_is_the_softmax_exactly_in_float32(monkeypatch, start, int8):
    """The same at float32, where the two forms differ by rounding
    alone: the walk is the formula, not near it."""
    q, k, v, k_new, v_new, (k_s, v_s) = _case(start, int8, 2)
    want = _one_softmax(q, k, v, k_new, v_new, start, k_s, v_s)
    monkeypatch.setattr(attention, "_CHUNK_BLOCK", BLOCK)
    got = jax.jit(lambda n: attention.chunk_attention(
        q, _poison(k, start, 2), _poison(v, start, 2), k_new, v_new, n,
        None if k_s is None else _poison(k_s, start, 2),
        None if v_s is None else _poison(v_s, start, 2)))(jnp.int32(start))
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("start", [0, 21, 40])
@pytest.mark.parametrize("valid", [1, 5, C])
def test_padding_inside_a_final_chunk_leaves_the_valid_rows_alone(
        monkeypatch, start, valid):
    """A final chunk is padded past its sampled position: whatever the
    padded tokens hold, the valid rows' output is the same."""
    q, k, v, k_new, v_new, _ = _case(start, False, 2)
    monkeypatch.setattr(attention, "_CHUNK_BLOCK", BLOCK)
    run = jax.jit(attention.chunk_attention)
    want = run(q, k, v, k_new, v_new, jnp.int32(start))
    pad = (jnp.arange(C) >= valid)[None, :, None, None]
    noise = jax.random.normal(jax.random.PRNGKey(99), q.shape) * 30
    got = run(jnp.where(pad, noise, q), k, v,
              jnp.where(pad, noise[:, :, :2], k_new),
              jnp.where(pad, -noise[:, :, :2], v_new), jnp.int32(start))
    np.testing.assert_array_equal(np.asarray(got[:, :valid]),
                                  np.asarray(want[:, :valid]))


def test_a_small_slot_fits_the_block_to_itself(monkeypatch):
    """A slot shorter than the block, or not a whole number of them,
    walks blocks that divide it (``flash.fit_block``)."""
    q, k, v, k_new, v_new, _ = _case(40, False, 2)
    k, v = k[:, :, :48], v[:, :, :48]
    want = _one_softmax(q, k, v, k_new, v_new, 40)
    for block in (512, 32):       # 48 rows: one block of 48, three of 16
        monkeypatch.setattr(attention, "_CHUNK_BLOCK", block)
        got = jax.jit(attention.chunk_attention)(q, k, v, k_new, v_new,
                                                 jnp.int32(40))
        np.testing.assert_allclose(got, want, atol=2e-5)


def _latent_one_softmax(q_cat, q, rows, start, k_nope, k_pe, v, rank):
    """``mla.chunk_attention`` as it stood before the walk."""
    c, smax, dn = q.shape[1], rows.shape[1], k_nope.shape[-1]
    rows = rows.astype(q_cat.dtype)
    s_cache = jnp.einsum("bqhw,btw->bhqt", q_cat, rows,
                         preferred_element_type=jnp.float32)
    s_cache = jnp.where((jnp.arange(smax) < start)[None, None, None],
                        s_cache, attention.NEG_INF)
    s_new = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], k_pe,
                          preferred_element_type=jnp.float32))
    s_new = jnp.where(jnp.tril(jnp.ones((c, c), bool))[None, None], s_new,
                      attention.NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([s_cache, s_new], -1), axis=-1)
    o_lat = jnp.einsum("bhqt,btr->bqhr", probs[..., :smax].astype(rows.dtype),
                       rows[..., :rank], preferred_element_type=jnp.float32)
    o_new = jnp.einsum("bhqk,bkhd->bqhd", probs[..., smax:].astype(v.dtype),
                       v)
    return o_lat, o_new


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("start", STARTS)
def test_the_latent_walk_equals_one_softmax_over_every_row(monkeypatch, start,
                                                           dtype):
    b, h, rank, rope, dn, dv = 2, 3, 16, 4, 6, 5
    ks = jax.random.split(jax.random.PRNGKey(start), 6)
    q_cat = jax.random.normal(ks[0], (b, C, h, rank + rope), dtype)
    q = jax.random.normal(ks[1], (b, C, h, dn + rope), dtype)
    rows = jax.random.normal(ks[2], (b, SMAX, rank + rope), dtype)
    k_nope = jax.random.normal(ks[3], (b, C, h, dn), dtype)
    k_pe = jax.random.normal(ks[4], (b, C, rope), dtype)
    v = jax.random.normal(ks[5], (b, C, h, dv), dtype)
    # the CPU has no bfloat16 x bfloat16 = float32 batched dot for the
    # formula's value product: the reference runs on the same values in
    # float32
    want = _latent_one_softmax(*(x.astype(jnp.float32) for x in (q_cat, q, rows)),
                               start, *(x.astype(jnp.float32)
                                        for x in (k_nope, k_pe, v)), rank)

    monkeypatch.setattr(mla, "_CHUNK_BLOCK", BLOCK)
    got = jax.jit(lambda n: mla.chunk_attention(
        q_cat, q, _poison(rows, start, 1), n, k_nope, k_pe, v, rank))(
            jnp.int32(start))
    atol = 2e-5 if dtype == jnp.float32 else 0.03
    assert got[0].dtype == jnp.float32 and got[1].dtype == dtype
    for g, w in zip(got, want):
        assert g.shape == w.shape
        g = np.asarray(g, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, np.asarray(w), atol=atol)
