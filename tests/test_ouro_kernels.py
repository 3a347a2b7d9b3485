"""The cache's two kernels at the looped family's table count (192 row
tables of 16 KV heads, a group of ONE query head a KV head), interpreted
on the CPU: the step's write cut over the table axis against XLA's
scatter and the select over the scale tables, and the decode kernel
against the jnp form. That Mosaic takes both at the cell's shapes is
tests/test_kernels_compile_v5e.py's to say."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import llama
from gofr_tpu.ops import flash_decode as fd
from gofr_tpu.ops.attention import decode_attention_appended
from gofr_tpu.ops.quant import quantize_kv

TABLES, KV, D = 192, 16, 128


@pytest.mark.parametrize("n_l,b,n_kv,rows,d,item,lanes,want", [
    # the cell: 192 x 16 tables, 7 slots, int8 with scales: a pass a call
    (192, 7, 16, 32, 128, 1, 128, 48),
    # Mistral's and a tp=4 shard of Mixtral's: all 32 tables in one visit
    (32, 40, 8, 32, 128, 1, 128, 32),
    (32, 40, 2, 32, 128, 1, 128, 32),
    # the conv family's five full layers at 96 slots, bfloat16, no scales
    (5, 96, 4, 16, 128, 2, 0, 5),
    # a table count with no divisor that fits but 1
    (193, 7, 16, 32, 128, 1, 128, 1),
])
def test_the_tables_a_visit_holds(n_l, b, n_kv, rows, d, item, lanes, want):
    assert fd.append_tables(n_l, b, n_kv, rows, d, item, lanes) == want


def test_the_write_at_192_tables_is_the_scatter_rows_and_scales(monkeypatch):
    """``llama.write_rows`` at 192 x 16 tables, the kernel arm
    (interpreted; three calls of 64 tables at these three slots x 32
    positions) against the arm without kernels: rows and both scale
    tables bit for bit, a slot at capacity untouched."""
    b, smax = 3, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    rand = jax.random.normal
    cache = llama.KVCache(
        k=(rand(ks[0], (TABLES, b, KV, smax, D)) * 40).astype(jnp.int8),
        v=(rand(ks[1], (TABLES, b, KV, smax, D)) * 40).astype(jnp.int8),
        lengths=jnp.asarray([0, 17, smax], jnp.int32),
        k_scale=jnp.abs(rand(ks[2], (TABLES, b, KV, smax))) + 1.0,
        v_scale=jnp.abs(rand(ks[3], (TABLES, b, KV, smax))) + 1.0)
    k_rows = rand(ks[4], (TABLES, b, 1, KV, D))
    v_rows = rand(ks[5], (TABLES, b, 1, KV, D))
    args = (cache, k_rows, v_rows, cache.lengths[:, None], cache.lengths + 1,
            KV)
    want = llama.write_rows(*args)
    assert fd.append_tables(TABLES, b, KV, 32, D, 1, 32) == 64
    calls, real = [], fd.pl.pallas_call

    def counted(*a, **kw):
        calls.append(kw.get("out_shape"))
        return real(*a, **kw)

    monkeypatch.setattr(fd.pl, "pallas_call", counted)
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    # (the jitted kernel wrapper must trace again under the counter)
    (qk, sk), (qv, sv) = (quantize_kv(r[:, :, 0]) for r in (k_rows, v_rows))
    got = fd.append_rows_stacked.__wrapped__(
        cache.k, cache.v, qk, qv, cache.lengths, cache.k_scale,
        cache.v_scale, sk, sv, interpret=True)
    assert len(calls) == 3
    for a, e in zip(got, (want.k, want.v, want.k_scale, want.v_scale)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e))
    # every table took its row: the last of the third call too
    assert (np.asarray(got[2])[:, 1, :, 17]
            != np.asarray(cache.k_scale)[:, 1, :, 17]).all()
    np.testing.assert_array_equal(np.asarray(got[0])[:, 2],
                                  np.asarray(cache.k)[:, 2])


@pytest.mark.parametrize("table", [0, 5, TABLES - 1])
def test_the_decode_kernel_at_a_group_of_one(table):
    """16 query heads on 16 KV heads (a group of one, padded to a sublane
    tile of eight in the kernel), an int8 cache, the table index where
    the layer index was: against ``decode_attention_appended`` over that
    table's slice."""
    b, smax, bs = 4, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(table), 5)
    q = jax.random.normal(ks[0], (b, 1, KV, D))
    k, sk = quantize_kv(jax.random.normal(ks[1], (TABLES, b, KV, smax, D)))
    v, sv = quantize_kv(jax.random.normal(ks[2], (TABLES, b, KV, smax, D)))
    k_new = jax.random.normal(ks[3], (b, 1, KV, D))
    v_new = jax.random.normal(ks[4], (b, 1, KV, D))
    lens = jnp.asarray([0, 1, bs + 1, smax - 2], jnp.int32)
    got = fd.flash_decode_stacked(q, k, v, k_new, v_new, lens,
                                  jnp.int32(table), sk, sv, block_s=bs,
                                  interpret=True)
    want = decode_attention_appended(q, k[table], v[table], k_new, v_new,
                                     lens, sk[table], sv[table])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
