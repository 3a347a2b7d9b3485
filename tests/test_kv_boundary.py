"""Where KV crosses between the device and everything else.

The device holds a KV head's positions together, [L, B, KV, Smax, hd]
(models.llama.KVCache). The host tier, the Redis tier's stored entries
and the P/D wire all hold and frame [L, plen, KV, hd]
(tpu.kvcache.HostKV), with stored data behind them, and that did not
move: the transposition happens in ``GenerationEngine._kv_row_get`` /
``_row_shard_parts`` (device -> host) and in
``programs._write_row_from_host[_masked]`` (host -> device).
``tests/fixtures/kv_frames_parent.json`` holds the frames commit
48df26c, the last with [L, B, Smax, KV, hd] on the device, made for one
prompt (``encode_block`` of what a prefill-only request ships), and the
tokens it went on to generate.
"""

import base64
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.ops.quant import quantize_kv
from gofr_tpu.parallel import make_mesh, shard_params
from gofr_tpu.tpu import GenerationEngine, programs
from gofr_tpu.tpu.kvcache import HostKV, KVCacheOptions, dense_hostkv
from gofr_tpu.tpu.kvcache.quant import (KVLayout, ShardedHostKV,
                                        concat_blocks, decode_block,
                                        encode_block)

TINY = LLAMA_CONFIGS["tiny"]
FIXTURE = json.loads((pathlib.Path(__file__).parent / "fixtures"
                      / "kv_frames_parent.json").read_text())
PROMPT = FIXTURE["prompt"]
DTYPES = {"int8": jnp.int8, "float32": None}
L, KV, HD = TINY.n_layers, TINY.n_kv_heads, TINY.head_dim


@pytest.fixture(scope="module")
def params():
    return llama.init(TINY, jax.random.PRNGKey(1))


def _engine(params, name, **kw):
    return GenerationEngine(TINY, params, slots=2, max_seq=64,
                            prompt_buckets=(8, 16, 32),
                            kv_dtype=DTYPES[name], **kw)


def _stacks(params, name, plen=len(PROMPT)):
    """The prompt's K and V as the layers make them, [L, plen, KV, hd],
    in the cache's stored form: what a host slab has always held."""
    _, k, v, _ = llama.prefill_kv(params, TINY,
                                  jnp.asarray([PROMPT[:plen]], jnp.int32))
    if name == "int8":
        (k, sk), (v, sv) = quantize_kv(k), quantize_kv(v)
        return HostKV(*(np.asarray(a[:, 0]) for a in (k, v, sk, sv)))
    return HostKV(np.asarray(k[:, 0]), np.asarray(v[:, 0]), None, None)


def _same(a: HostKV, b: HostKV):
    for x, y in zip(a, b):
        assert (x is None) == (y is None)
        if x is not None:
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(x, y)


def _close(a: HostKV, b: HostKV):
    """The same K/V up to what two programs' float32 differ by: a last
    place, and at int8 one step of a vector's scale."""
    for (x, xs), (y, ys) in (((a.k, a.k_scale), (b.k, b.k_scale)),
                             ((a.v, a.v_scale), (b.v, b.v_scale))):
        assert x.shape == y.shape and x.dtype == y.dtype
        if xs is None:
            np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)
            continue
        np.testing.assert_allclose(xs, ys, rtol=1e-4)
        np.testing.assert_allclose(x * xs[..., None], y * ys[..., None],
                                   atol=1.01 * float(ys.max()))


# -- device -> host ------------------------------------------------------------

@pytest.mark.parametrize("name", DTYPES)
def test_row_fetched_from_one_device_is_the_host_slab(params, name):
    """A row written by prefill and fetched with _kv_row_get is
    [L, plen, KV, hd] (scales [L, plen, KV]), contiguous, and holds the
    layers' K/V at every position; a range fetch is that slab's slice."""
    eng = _engine(params, name, prefix_cache_slots=2, prefix_store_min=8)
    try:
        eng.generate(PROMPT, max_new_tokens=2).tokens()
        assert eng.cache.k.shape == (L, 2, KV, 64, HD)
        plen = len(PROMPT) - 1          # the pool stores whole blocks
        row = eng._kvc.match(np.asarray(PROMPT, np.int32), 0).row
        kv = eng._kv_row_get(eng._pool, row, plen)
        assert isinstance(kv, HostKV)
        assert kv.k.shape == (L, plen, KV, HD)
        assert all(a.flags["C_CONTIGUOUS"] for a in kv if a is not None)
        _close(kv, _stacks(params, name, plen))
        part = eng._kv_row_get(eng._pool, row, 17, start=5)
        _same(part, kv.slice_tokens(5, 17))
    finally:
        eng.close()


@pytest.mark.parametrize("name", DTYPES)
def test_row_fetched_per_shard_is_the_host_slab(params, name):
    """On the CPU mesh each tp shard's part is [L, plen, KV/tp, hd],
    read off its own device, and the parts assemble to the dense slab
    the cache holds."""
    mesh = make_mesh(tp=2, dp=4)
    eng = GenerationEngine(TINY, shard_params(params, mesh), slots=4,
                           max_seq=64, prompt_buckets=(8, 16, 32),
                           kv_dtype=DTYPES[name], mesh=mesh,
                           prefix_cache_slots=4, prefix_store_min=8)
    try:
        eng.generate(PROMPT, max_new_tokens=2).tokens()
        plen = len(PROMPT) - 1
        row = eng._kvc.match(np.asarray(PROMPT, np.int32), 0).row
        kv = eng._kv_row_get(eng._pool, row, plen)
        assert isinstance(kv, ShardedHostKV) and kv.shards == 2
        assert all(p.k.shape == (L, plen, KV // 2, HD) for p in kv.parts)
        dense = dense_hostkv(kv)
        pool = eng._pool
        want = HostKV(*(None if a is None else np.swapaxes(
            np.asarray(a)[:, row, :, :plen], 1, 2)
            for a in (pool.k, pool.v, pool.k_scale, pool.v_scale)))
        _same(dense, want)
        _close(dense, _stacks(params, name, plen))
    finally:
        eng.close()


# -- host -> device ------------------------------------------------------------

@pytest.mark.parametrize("write", [programs._write_row_from_host,
                                   programs._write_row_from_host_masked],
                         ids=["slice", "masked"])
@pytest.mark.parametrize("name", DTYPES)
def test_host_slab_written_back_gives_the_same_bytes(params, name, write):
    """Fetched, padded as the engine pads it and written to another row:
    that row holds the bytes the first did, and no other row moved."""
    quant = name == "int8"
    cache = llama.init_cache(TINY, 3, 32, DTYPES[name])
    tokens = jnp.asarray([PROMPT[:20]] * 3, jnp.int32) + jnp.arange(3)[:, None]
    _, cache = llama.prefill(params, TINY, tokens, cache)
    leaves = [a for a in (cache.k, cache.v, cache.k_scale, cache.v_scale)
              if a is not None]
    slab = [np.swapaxes(np.asarray(a)[:, 1, :, :20], 1, 2) for a in leaves]

    def pad(a):
        out = np.zeros((a.shape[0], 1, 32) + a.shape[2:], a.dtype)
        out[:, 0, :20] = a
        return jnp.asarray(out)

    padded = [pad(a) for a in slab] + [None] * (4 - len(slab))
    new = write(cache, *padded, jnp.int32(2))
    for before, after in zip(leaves, (new.k, new.v, new.k_scale,
                                      new.v_scale)):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(after[:, 2, :, :20],
                                      before[:, 1, :, :20])
        assert not after[:, 2, :, 20:].any()
        np.testing.assert_array_equal(after[:, :2], before[:, :2])
    assert (new.k_scale is not None) == quant


# -- the stored and shipped bytes ----------------------------------------------

def _frame(name):
    return base64.b64decode(FIXTURE[name]["frame"])


@pytest.mark.parametrize("name", DTYPES)
def test_pd_frame_is_the_parents_byte_for_byte(params, name):
    """What a prefill-only request ships for the prompt, framed as the
    P/D shipper frames it, is the parent commit's frame."""
    eng = _engine(params, name)
    try:
        shipped = []
        s = eng.generate(PROMPT, max_new_tokens=FIXTURE["new_tokens"],
                         logprobs=True,
                         kv_sink=lambda kv, st, tot: shipped.append(kv))
        first, lp = list(s)[0]
    finally:
        eng.close()
    assert encode_block(concat_blocks(shipped)) == _frame(name)
    assert first == FIXTURE[name]["first"]
    assert lp == pytest.approx(FIXTURE[name]["lp"], abs=1e-5)


@pytest.mark.parametrize("name", DTYPES)
def test_redis_entry_is_the_parents_byte_for_byte(params, name):
    """What the pool row spills and the Redis tier stores (the same
    codec over _kv_row_get's slab) is the parent's frame, block by
    block."""
    eng = _engine(params, name, prefix_cache_slots=2, prefix_store_min=8)
    try:
        eng.generate(PROMPT, max_new_tokens=2).tokens()
        row = eng._kvc.match(np.asarray(PROMPT, np.int32), 0).row
        kv = eng._kv_row_get(eng._pool, row, 16)
    finally:
        eng.close()
    layout = KVLayout(L, KV, HD, name == "int8",
                      np.dtype(np.int8 if name == "int8" else np.float32),
                      64)
    parent = decode_block(_frame(name), layout)
    for lo in (0, 8):
        assert (encode_block(kv.slice_tokens(lo, lo + 8))
                == encode_block(parent.slice_tokens(lo, lo + 8)))


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
@pytest.mark.parametrize("name", DTYPES)
def test_parents_frame_ingests_token_exact(params, name, paged):
    """The parent's frame, decoded by today's codec and installed by the
    P/D ingest path (a padded upload, _write_row_from_host, and on a
    paged engine the scratch row into blocks), continues with the
    tokens the parent generated."""
    kw = {"paged_blocks": 24, "paged_block_size": 16} if paged else {}
    dec = _engine(params, name, **kw)
    layout = KVLayout(L, KV, HD, name == "int8",
                      np.dtype(str(dec.cache.k.dtype)), 64)
    kv = decode_block(_frame(name), layout)
    assert kv is not None and kv.k.shape == (L, len(PROMPT), KV, HD)
    try:
        out = dec.generate(
            PROMPT, max_new_tokens=FIXTURE["new_tokens"],
            ingest=(kv, FIXTURE[name]["first"], FIXTURE[name]["lp"])).tokens()
    finally:
        dec.close()
    assert out == FIXTURE[name]["tokens"]


@pytest.mark.parametrize("name", DTYPES)
def test_parents_frame_promotes_from_the_host_tier_token_exact(params, name):
    """The same blob held by the host tier: the next request for the
    prompt is a T1 hit, promoted to a pool row by the host-write program
    and restored, and generates the parent's tokens."""
    eng = _engine(params, name, prefix_cache_slots=2, prefix_store_min=8,
                  kvcache=KVCacheOptions(block=8, host_mb=8,
                                         epoch_refresh_s=0.0))
    layout = KVLayout(L, KV, HD, name == "int8",
                      np.dtype(str(eng.cache.k.dtype)), 64)
    kv = decode_block(_frame(name), layout)
    try:
        assert eng._kvc.host.put(np.asarray(PROMPT, np.int32), 0, kv)
        out = eng.generate(PROMPT,
                           max_new_tokens=FIXTURE["new_tokens"]).tokens()
        assert eng.stats()["prefix_cache"]["tiers"]["t1"]["hits"] == 1
    finally:
        eng.close()
    assert out == FIXTURE[name]["tokens"]
