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


def _latent_kept_one_softmax(q_cat, q, rows, k_nope, k_pe, v, rank,
                             keep_cache, keep_new):
    """``mla.chunk_attention_kept`` as one softmax over every cached row
    and the chunk's own tokens, a mask a query over each."""
    t, dn = rows.shape[1], k_nope.shape[-1]
    rows = rows.astype(q_cat.dtype)
    s_cache = jnp.einsum("bqhw,btw->bhqt", q_cat, rows,
                         preferred_element_type=jnp.float32)
    s_cache = jnp.where(keep_cache[:, None], s_cache, attention.NEG_INF)
    s_new = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], k_pe,
                          preferred_element_type=jnp.float32))
    s_new = jnp.where(keep_new[:, None], s_new, attention.NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([s_cache, s_new], -1), axis=-1)
    o_lat = jnp.einsum("bhqt,btr->bqhr", probs[..., :t].astype(rows.dtype),
                       rows[..., :rank], preferred_element_type=jnp.float32)
    o_new = jnp.einsum("bhqk,bkhd->bqhd", probs[..., t:].astype(v.dtype), v)
    return o_lat, o_new


def _latent_one_softmax(q_cat, q, rows, start, k_nope, k_pe, v, rank):
    """``mla.chunk_attention`` as it stood before the walk."""
    c = q.shape[1]
    return _latent_kept_one_softmax(
        q_cat, q, rows, k_nope, k_pe, v, rank,
        (jnp.arange(rows.shape[1]) < start)[None, None],
        jnp.tril(jnp.ones((c, c), bool))[None])


def _on(monkeypatch, path):
    """The walk's path: the jnp loop a CPU takes, or the kernel
    interpreted at its smallest tile, 8 queries, so that the stream of
    row tiles runs from one tile into the next and from one slot into
    the next."""
    monkeypatch.setattr(mla, "_CHUNK_BLOCK", BLOCK)
    if path == "kernel":
        monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
        monkeypatch.setattr(mla, "_CHUNK_TILE_ROWS", 64)
    else:
        monkeypatch.delenv("GOFR_FLASH_INTERPRET", raising=False)


@pytest.mark.parametrize("path", ["jnp", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("start", STARTS)
def test_the_latent_walk_equals_one_softmax_over_every_row(monkeypatch, start,
                                                           dtype, path):
    b, h, rank, rope, dn, dv = 2, 8, 128, 128, 6, 5
    ks = jax.random.split(jax.random.PRNGKey(start), 6)
    q_cat = jax.random.normal(ks[0], (b, C, h, rank + rope), dtype) * 0.3
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

    _on(monkeypatch, path)
    assert bool(mla.chunk_tile(C, h, SMAX, rank + rope, rank, dtype)) \
        == (path == "kernel")
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


# (stored width, rank) in whole lanes: the full layers' 640 / 512 and the
# window layers' 1,152 / 1,024, reduced
WIDTHS = [(256, 128), (384, 256)]
CL = 2 * C       # a chunk of two of the kernel's smallest tiles


def _masks(form, start, key, b):
    """(keep_cache [b or 1, CL or 1, SMAX], keep_new, live) of the three
    callers: the cursor, a learned selection, a ring's rows by the
    positions they hold (``dots3_note.prefill_chunk``'s ``seen`` and
    ``band``, a window of SMAX + 1)."""
    causal = jnp.tril(jnp.ones((CL, CL), bool))[None]
    before = jnp.arange(SMAX) < start
    if form == "cursor":
        return before[None, None], causal, start
    if form == "ring":
        held = attention.ring_held(SMAX, start)
        positions = start + jnp.arange(CL)
        seen = (held >= 0) & (held[None, :] >= positions[:, None] - SMAX)
        band = causal & ~jnp.tril(jnp.ones((CL, CL), bool), -SMAX - 1)[None]
        return seen[None], band, min(start, SMAX)
    k1, k2 = jax.random.split(key)
    keep = jax.random.bernoulli(k1, 0.4, (b, CL, SMAX)) & before
    keep_new = (jax.random.bernoulli(k2, 0.7, (b, CL, CL)) & causal
                | jnp.eye(CL, dtype=bool))
    # query 1 keeps no cached row; query 0, where there is a cached row,
    # keeps it and none of the chunk's own tokens
    keep = keep.at[:, 1].set(False)
    if start:
        keep = keep.at[:, 0, 0].set(True)
        keep_new = keep_new.at[:, 0].set(False)
    return keep, keep_new, start


@pytest.mark.parametrize("path", ["jnp", "kernel"])
@pytest.mark.parametrize("width,rank", WIDTHS, ids=["w256r128", "w384r256"])
@pytest.mark.parametrize("form,start", [
    (form, start) for form in ("cursor", "selection", "ring")
    for start in STARTS] + [("ring", SMAX + 24)])
def test_the_kept_walk_takes_every_caller_s_mask_at_both_widths(
        monkeypatch, start, form, width, rank, path):
    """``chunk_attention_kept`` under the three masks its callers hand
    it, on the jnp loop and in the kernel: a query that keeps no cached
    row, one that keeps none of the chunk's own tokens, a ring that has
    wrapped (``live`` is all of it), NaN in every row past the last
    block the walk may fetch."""
    b, h, dn, rope, dv = 2, 8, 6, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(start + width), 7)
    q_cat = jax.random.normal(ks[0], (b, CL, h, width)) * 0.3
    q = jax.random.normal(ks[1], (b, CL, h, dn + rope))
    rows = jax.random.normal(ks[2], (b, SMAX, width))
    k_nope = jax.random.normal(ks[3], (b, CL, h, dn))
    k_pe = jax.random.normal(ks[4], (b, CL, rope))
    v = jax.random.normal(ks[5], (b, CL, h, dv))
    keep_cache, keep_new, live = _masks(form, start, ks[6], b)
    want = _latent_kept_one_softmax(q_cat, q, rows, k_nope, k_pe, v, rank,
                                    keep_cache, keep_new)

    _on(monkeypatch, path)
    got = jax.jit(lambda n: mla.chunk_attention_kept(
        q_cat, q, _poison(rows, live, 1), k_nope, k_pe, v, rank, keep_cache,
        keep_new, n))(jnp.int32(live))
    for g, w in zip(got, want):
        assert g.shape == w.shape and np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.mark.parametrize("chunk,heads,table,width,rank,dtype,why", [
    (512, 128, 16384, 640, 512, jnp.float32, "float32 operands"),
    (512, 128, 16384, 576, 512, jnp.bfloat16, "a width of 4.5 lane tiles"),
    (512, 128, 16384, 640, 448, jnp.bfloat16, "a rank of 3.5 lane tiles"),
    (512, 12, 16384, 640, 512, jnp.bfloat16, "heads of 1.5 sublane tiles"),
    (20, 128, 16384, 640, 512, jnp.bfloat16, "a chunk of 2.5 tiles"),
    (512, 128, 320, 640, 512, jnp.bfloat16, "blocks of 64 rows"),
    (512, 128, 16384, 640, 512, jnp.bfloat16, "a CPU")])
def test_the_dispatcher_answers_jnp_for_what_the_kernel_refuses(
        monkeypatch, chunk, heads, table, width, rank, dtype, why):
    """``chunk_tile`` on a TPU: None for each shape the kernel does not
    take, and for every shape on a CPU; the served shapes get a tile of
    1,024 score rows."""
    from gofr_tpu.ops import flash

    monkeypatch.delenv("GOFR_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(flash, "tpu_backend_ok", lambda: why != "a CPU")
    assert mla.chunk_tile(chunk, heads, table, width, rank, dtype) is None
    monkeypatch.setattr(flash, "tpu_backend_ok", lambda: True)
    served = [(512, 128, 16384, 640, 512), (512, 64, 512, 1152, 1024),
              (64, 64, 2048, 640, 512)]
    assert [mla.chunk_tile(*s, jnp.bfloat16) for s in served] == [8, 16, 16]
