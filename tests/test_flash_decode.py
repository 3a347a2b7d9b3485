"""Flash-decode kernel numerics vs the jnp reference (interpret mode on
the CPU backend; that Mosaic compiles it and how fast it runs is the
chip's to say, chip_smoke.py and the benchmark, never this file)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.observe import Observe
from gofr_tpu.observe.timeline import Timeline
from gofr_tpu.ops import flash as flash_mod
from gofr_tpu.ops import flash_decode as fd
from gofr_tpu.ops.attention import decode_attention_appended
from gofr_tpu.ops.quant import quantize_kv
from gofr_tpu.tpu import GenerationEngine

L, S, H, KV, D = 3, 256, 8, 2, 128      # GQA 4:1
BS = 64
# the cursors the block geometry can get wrong: an empty slot, one
# position, either side of a block's edge, the last position a slot
# may hold before the engine retires it
CURSORS = [0, 1, BS - 1, BS, BS + 1, S - 2]
B = len(CURSORS)
CACHES = ["int8", "bfloat16", "float32"]


def _mk(key, cache: str, b: int = B, h: int = H, kv: int = KV):
    """q, stacked k, stacked v, k_new, v_new, k_scale, v_scale."""
    ks = jax.random.split(key, 5)
    dt = jnp.bfloat16 if cache == "bfloat16" else jnp.float32
    q = jax.random.normal(ks[0], (b, 1, h, D), dt)
    k = jax.random.normal(ks[1], (L, b, kv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (L, b, kv, S, D), jnp.float32)
    k_new = jax.random.normal(ks[3], (b, 1, kv, D), dt)
    v_new = jax.random.normal(ks[4], (b, 1, kv, D), dt)
    if cache != "int8":
        return q, k.astype(dt), v.astype(dt), k_new, v_new, None, None
    qk, sk = quantize_kv(k)
    qv, sv = quantize_kv(v)
    return q, qk, qv, k_new, v_new, sk, sv


def _kernel(args, lens, layer, block_s=BS):
    q, k, v, k_new, v_new, sk, sv = args
    return np.asarray(fd.flash_decode_stacked(
        q, k, v, k_new, v_new, jnp.asarray(lens, jnp.int32),
        jnp.int32(layer), sk, sv, block_s=block_s,
        interpret=True).astype(jnp.float32))


def _reference(args, lens, layer):
    q, k, v, k_new, v_new, sk, sv = args
    return np.asarray(decode_attention_appended(
        q, k[layer], v[layer], k_new, v_new, jnp.asarray(lens, jnp.int32),
        None if sk is None else sk[layer],
        None if sv is None else sv[layer]).astype(jnp.float32))


def _tol(cache):
    # bfloat16: the reference rounds normalised probabilities, the
    # kernel unnormalised ones
    return dict(rtol=3e-2, atol=3e-2) if cache == "bfloat16" else dict(
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cache", CACHES)
@pytest.mark.parametrize("layer", [0, 1, L - 1])
@pytest.mark.parametrize("slot", range(B))
def test_stacked_matches_reference(cache, layer, slot):
    """Read through the stacked cache at the first, a middle and the last
    layer, every cursor in every slot position (the work list's order
    depends on which slot is short)."""
    args = _mk(jax.random.PRNGKey(0), cache)
    lens = np.roll(CURSORS, slot)
    np.testing.assert_allclose(_kernel(args, lens, layer),
                               _reference(args, lens, layer), **_tol(cache))


@pytest.mark.parametrize("cache", CACHES)
@pytest.mark.parametrize("h,kv", [(16, 4), (32, 8), (8, 8), (32, 16),
                                  (4, 1), (8, 2)])
def test_head_geometries(cache, h, kv):
    """A KV head's [block, hd] tile is taken whole from the
    [KV, block, hd] item, a group of q heads padded to whole sublanes:
    groups of four, one and two over four, eight and sixteen KV heads,
    and the one and two KV heads a tp shard can be left with. Poisoned
    past the cursors, so a row read for the wrong head or position
    shows."""
    args = _mk(jax.random.PRNGKey(4), cache, h=h, kv=kv)
    lens = [200, 0, 33, S - 2, 128, 31]
    np.testing.assert_allclose(_kernel(_poison(args, lens), lens, 1),
                               _reference(args, lens, 1), **_tol(cache))


@pytest.mark.parametrize("cache", CACHES)
@pytest.mark.parametrize("block_s", [32, 128, 256])
def test_block_sizes_agree(cache, block_s):
    """One item a slot (block = Smax) down to eight."""
    args = _mk(jax.random.PRNGKey(1), cache)
    lens = [200, 0, 33, 256 - 2, 128, 31]
    np.testing.assert_allclose(_kernel(args, lens, 1, block_s),
                               _reference(args, lens, 1), **_tol(cache))


@pytest.mark.parametrize("cache", CACHES)
def test_empty_slots_return_the_new_token(cache):
    """Length 0 everywhere: no item, no DMA, and the output is exactly
    the appended token's value vector (a softmax of one element), not
    whatever the output buffer held."""
    args = _mk(jax.random.PRNGKey(2), cache)
    got = _kernel(args, [0] * B, 1)
    want = np.repeat(np.asarray(args[4][:, 0].astype(jnp.float32)),
                     H // KV, axis=1)[:, None]
    np.testing.assert_array_equal(got, want)


def _poison(args, lens):
    """Everything a slot's length says is not its to read, made as loud
    as the dtype allows: +-127 (or 1e30) in K and V, 1e30 scales."""
    q, k, v, k_new, v_new, sk, sv = args
    dead = (np.arange(S)[None, :] >= np.asarray(lens)[:, None])  # [B,S]
    dead5 = jnp.asarray(dead)[None, :, None, :, None]
    sign = jnp.where(jnp.arange(D) % 2 == 0, 1, -1)
    loud = 127 if k.dtype == jnp.int8 else 1e30
    k = jnp.where(dead5, (sign * loud).astype(k.dtype), k)
    v = jnp.where(dead5, (-sign * loud).astype(v.dtype), v)
    if sk is not None:
        sk = jnp.where(dead5[..., 0], 1e30, sk)
        sv = jnp.where(dead5[..., 0], 1e30, sv)
    return q, k, v, k_new, v_new, sk, sv


@pytest.mark.parametrize("cache", CACHES)
@pytest.mark.parametrize("layer", [0, L - 1])
def test_nothing_past_the_cursor_is_read(cache, layer):
    """Positions past each cursor and whole dead slots poisoned: the
    output is bit-identical to the clean cache's."""
    args = _mk(jax.random.PRNGKey(3), cache)
    lens = [0, 1, BS - 1, BS + 1, 0, S - 2]
    clean = _kernel(args, lens, layer)
    dirty = _kernel(_poison(args, lens), lens, layer)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(clean, dirty)


@pytest.mark.parametrize("lens,n,slots,blocks", [
    ([0, 0, 0], 0, [], []),
    ([1, 0, 64], 2, [0, 2], [0, 0]),
    ([65, 0, 128], 4, [0, 0, 2, 2], [0, 1, 0, 1]),
    ([256, 256, 256], 12, [0] * 4 + [1] * 4 + [2] * 4, [0, 1, 2, 3] * 3),
])
def test_work_list(lens, n, slots, blocks):
    got_n, slot, blk = fd._work_list(jnp.asarray(lens, jnp.int32), S, BS)
    assert int(got_n[0]) == n
    assert slot.shape == (len(lens) * S // BS,)
    assert slot[:n].tolist() == slots and blk[:n].tolist() == blocks
    # the tail is never walked, but it is prefetched as scalars: in range
    assert 0 <= int(slot.min()) and int(slot.max()) < len(lens)


# -- the step's write -----------------------------------------------------------

@pytest.mark.parametrize("cache", CACHES)
@pytest.mark.parametrize("kv", [1, 2, 8])
def test_append_rows_writes_the_row_and_nothing_else(cache, kv):
    """Every layer's and KV head's row lands at its slot's position,
    first and last of a 32-bit word, of a tile and of the cache; a
    position at capacity is dropped; every other byte of both caches is
    what it was (the scatter's result, bit for bit)."""
    dt = {"int8": jnp.int8, "bfloat16": jnp.bfloat16,
          "float32": jnp.float32}[cache]
    ks = jax.random.split(jax.random.PRNGKey(kv), 4)

    def rand(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 50).astype(dt)

    k, v = rand(ks[0], (L, B, kv, S, D)), rand(ks[1], (L, B, kv, S, D))
    k_rows, v_rows = rand(ks[2], (L, B, kv, D)), rand(ks[3], (L, B, kv, D))
    pos = jnp.asarray([0, 3, 31, 32, S - 1, S], jnp.int32)
    got = fd.append_rows_stacked(k, v, k_rows, v_rows, pos, interpret=True)
    slots = jnp.arange(B)
    for new, old, rows in zip(got, (k, v), (k_rows, v_rows)):
        want = old.at[:, slots, :, pos].set(jnp.moveaxis(rows, 1, 0),
                                            mode="drop")
        np.testing.assert_array_equal(np.asarray(new.astype(jnp.float32)),
                                      np.asarray(want.astype(jnp.float32)))
        # the slot at capacity: nothing of it moved
        np.testing.assert_array_equal(
            np.asarray(new[:, -1].astype(jnp.float32)),
            np.asarray(old[:, -1].astype(jnp.float32)))


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
@pytest.mark.parametrize("w", [1, 3])
def test_write_rows_kernel_arm_is_the_scatter(kv_dtype, w, monkeypatch):
    """llama.write_rows, a decode step's row and a verify window's
    three: the kernel arm (interpreted) leaves the cache the reference
    arm's scatter leaves, scales included, a parked slot untouched."""
    b, smax = 4, 64
    cache = llama.init_cache(TINY, b, smax, kv_dtype)
    ks = jax.random.split(jax.random.PRNGKey(w), 2)
    shape = (TINY.n_layers, b, w, TINY.n_kv_heads, TINY.head_dim)
    k_rows = jax.random.normal(ks[0], shape, jnp.float32)
    v_rows = jax.random.normal(ks[1], shape, jnp.float32)
    lengths = jnp.asarray([0, 17, smax - w, smax], jnp.int32)
    positions = lengths[:, None] + jnp.arange(w)[None, :]
    want = llama.write_rows(cache, k_rows, v_rows, positions, lengths + w,
                             TINY.n_heads)
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    got = llama.write_rows(cache, k_rows, v_rows, positions, lengths + w,
                            TINY.n_heads)
    for a, e in zip(got, want):
        assert (a is None) == (e is None)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(e))
    assert np.asarray(got.k[:, 1, :, 17]).any()
    assert not np.asarray(got.k[:, 3]).any()


@pytest.mark.parametrize("kv,smax,cursors,w", [
    # a slot of length 0, a tile's last lane and the next tile's first,
    # two slots on one tile (128, 130), the last position, at capacity
    # and past it
    (8, 256, [0, 127, 128, 130, 255, 256, 300], 1),
    (2, 256, [0, 127, 128, 130, 255, 256, 300], 1),
    # a verify window of two: astride a tile's edge, astride capacity
    (8, 256, [0, 127, 128, 254, 255, 256], 2),
    (2, 256, [0, 127, 128, 254, 255, 256], 2),
    # more slots than a tile has lanes: the step's scales in two groups
    (2, 32, [(7 * i) % 34 for i in range(40)], 1),
])
def test_append_rows_writes_the_scales_the_select_writes(kv, smax, cursors,
                                                         w, monkeypatch):
    """The scale tables of an int8 cache after ``append_rows``
    (interpreted: the lane tile around each cursor read, the step's scale
    put on the cursor's lane, written back) are the tables
    ``write_rows``' select leaves on the path without kernels, bit for
    bit: every other value of both tables is what it was, and a
    position at or past capacity drops its scales with its row."""
    b, n_l = len(cursors), 2
    ks = jax.random.split(jax.random.PRNGKey(kv + w), 6)
    rand = jax.random.normal
    cache = llama.KVCache(
        k=(rand(ks[0], (n_l, b, kv, smax, D)) * 40).astype(jnp.int8),
        v=(rand(ks[1], (n_l, b, kv, smax, D)) * 40).astype(jnp.int8),
        lengths=jnp.asarray(cursors, jnp.int32),
        k_scale=jnp.abs(rand(ks[2], (n_l, b, kv, smax))) + 1.0,
        v_scale=jnp.abs(rand(ks[3], (n_l, b, kv, smax))) + 1.0)
    k_rows = rand(ks[4], (n_l, b, w, kv, D))
    v_rows = rand(ks[5], (n_l, b, w, kv, D))
    positions = cache.lengths[:, None] + jnp.arange(w)[None, :]
    args = (cache, k_rows, v_rows, positions, cache.lengths + w, 4 * kv)
    want = llama.write_rows(*args)
    appends, real = [], fd.append_rows

    def counted(*a, **kw):
        appends.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(fd, "append_rows", counted)
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    got = llama.write_rows(*args)
    assert len(appends) == w and all(len(a) == 9 for a in appends)
    for a, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e))
    # the select's tables are the old ones but at the cursors under Smax
    old, new = np.asarray(cache.k_scale), np.asarray(got.k_scale)
    moved = {(slot, int(p)) for _, slot, _, p in zip(*np.nonzero(old != new))}
    assert moved == {(i, c + j) for i, c in enumerate(cursors)
                     for j in range(w) if c + j < smax}


# -- selection: what the code can observe, no setting -------------------------

def _cache_shape(kv=8, d=128, smax=2048, dtype=jnp.int8):
    return jax.ShapeDtypeStruct((2, 4, kv, smax, d), dtype)


def test_reference_off_tpu():
    """A CPU process and no interpret flag: decode stays on the
    reference."""
    assert fd.kernel_block(32, _cache_shape()) is None


@pytest.mark.parametrize("kw,want", [
    (dict(), 256),
    (dict(dtype=jnp.bfloat16), 256),
    (dict(smax=128), 128),
    (dict(smax=1152), 128),               # the largest block that divides
    (dict(d=64), None),                   # head_dim not whole lanes
    (dict(smax=200), None),               # no lane-aligned block divides
    (dict(kv=2), 256),                    # a tp=4 shard of 8 int8 heads
    (dict(kv=1), 256),
    (dict(kv=2, dtype=jnp.bfloat16), 256),
    (dict(kv=3), None),                   # 32 heads over 3 KV heads
])
def test_kernel_block_follows_the_shapes(monkeypatch, kw, want):
    monkeypatch.setattr(flash_mod, "tpu_backend_ok", lambda: True)
    assert fd.kernel_block(32, _cache_shape(**kw)) == want


def test_interpret_flag_takes_any_shape(monkeypatch):
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    assert fd.kernel_block(4, _cache_shape(kv=2, d=16, smax=64)) == 64


# -- the model and the engine --------------------------------------------------

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init(TINY, jax.random.PRNGKey(1))


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_decode_step_ignores_inactive_slots(kv_dtype, tiny_params,
                                            monkeypatch):
    """An inactive slot keeps a stale, non-zero cursor (the engine
    freezes it) over a row of poison: the active slots' logits are those
    of the reference path, and finite."""
    b, smax = 4, 64
    cache = llama.init_cache(TINY, b, smax, kv_dtype)
    tokens = jnp.asarray([[5, 17, 42, 7, 9, 11]] * b, jnp.int32)
    _, cache = llama.prefill(tiny_params, TINY, tokens, cache, flash=False)
    active = jnp.asarray([True, False, True, False])
    stale = jnp.where(active, cache.lengths, smax - 3)
    loud = 127 if kv_dtype is not None else 1e30
    dead = (~active)[None, :, None, None, None]
    cache = cache._replace(
        lengths=stale,
        k=jnp.where(dead, jnp.asarray(loud, cache.k.dtype), cache.k),
        v=jnp.where(dead, jnp.asarray(loud, cache.v.dtype), cache.v))
    step = jnp.asarray([3, 1, 4, 1], jnp.int32)
    want, _ = llama.decode_step(tiny_params, TINY, step, cache)
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    got, new = llama.decode_step(tiny_params, TINY, step, cache,
                                 active=active)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[::2], np.asarray(want)[::2],
                               rtol=2e-4, atol=2e-4)
    # the cursors in the cache keep their semantics: every slot steps
    assert new.lengths.tolist() == (stale + 1).tolist()


def _engine(params, observe=None, **kw):
    return GenerationEngine(TINY, params, slots=4, max_seq=64,
                            prompt_buckets=(8, 16), observe=observe, **kw)


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_engine_token_exact_and_counts_what_it_fetches(kv_dtype, tiny_params,
                                                       monkeypatch):
    """Fused decode blocks through the kernel, token for token against
    the reference path, and the decode event says how much of what the
    slots reserve each path fetched."""
    prompts = [[5, 17, 42, 7], [3, 1, 4, 1, 5, 9, 2, 6]]

    def run():
        tl = Timeline(capacity=4096)
        eng = _engine(tiny_params, Observe(timeline=tl), kv_dtype=kv_dtype)
        try:
            toks = [eng.generate(p, max_new_tokens=20).tokens()
                    for p in prompts]
        finally:
            eng.close()
        return toks, [e for e in tl.events() if e[3] == "decode"]

    want, ref_events = run()
    assert ref_events and all(e[7] == 4 * 64 for e in ref_events)

    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    calls = []
    inner = fd.decode_attention_auto
    monkeypatch.setattr(fd, "decode_attention_auto",
                        lambda *a, **k: calls.append(1) or inner(*a, **k))
    got, events = run()
    assert got == want
    assert calls                      # the kernel, not a silent fallback
    # one slot active at a cursor under 64 = one block of 64
    assert events and all(e[7] == 64 and e[6] <= e[7] for e in events)
