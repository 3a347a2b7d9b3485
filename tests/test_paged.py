"""Paged KV cache: block-pool attention and decode must be numerically
invisible — the kernel (interpret mode) matches the dense-gather
reference, and paged_decode_step streams the exact tokens
llama.decode_step does from an identically-seeded contiguous cache.
Hardware existence is proven by chip_smoke.py (TPU_PAGED_BLOCKS), never here
(the r2 flash-kernel lesson)."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.models.paged_llama import (BlockAllocator,
                                         init_paged_cache,
                                         paged_decode_step,
                                         write_prompt_blocks)
from gofr_tpu.ops.attention import decode_attention_appended
from gofr_tpu.ops.paged_attention import (gather_heads,
                                          paged_attention_reference,
                                          paged_decode_attention)
from gofr_tpu.ops.quant import quantize_kv

TINY = LLAMA_CONFIGS["tiny"]

B, H, KV, D = 3, 8, 4, 128
T = 128           # block size
MB = 2            # max blocks per slot
N = B * MB + 1    # pool incl. trash block 0


def _mk(key, quant: bool, lengths):
    """Pool + clamped table + the dense cache it represents."""
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    k_pool = jax.random.normal(ks[1], (N, T, KV, D), jnp.float32)
    v_pool = jax.random.normal(ks[2], (N, T, KV, D), jnp.float32)
    k_new = jax.random.normal(ks[3], (B, 1, KV, D), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, 1, KV, D), jnp.float32)
    # each slot owns MB distinct blocks, clamped at its live range
    table = np.zeros((B, MB), np.int32)
    for b in range(B):
        live = max(1, -(-int(lengths[b]) // T))
        for j in range(MB):
            table[b, j] = 1 + b * MB + min(j, live - 1)
    table = jnp.asarray(table)
    sk = sv = None
    if quant:
        k_pool, sk = quantize_kv(k_pool)
        v_pool, sv = quantize_kv(v_pool)
    return q, k_pool, v_pool, k_new, v_new, table, sk, sv


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("lengths", [[256, 100, 1], [37, 128, 255],
                                     [0, 5, 256]])
def test_paged_kernel_matches_dense_reference(quant, lengths):
    """The paged kernel == dense decode attention over the gathered
    view == the paged reference, on ragged lengths incl. empty slots."""
    lens = jnp.asarray(lengths, jnp.int32)
    q, kp, vp, k_new, v_new, table, sk, sv = _mk(
        jax.random.PRNGKey(0), quant, lengths)
    got = paged_decode_attention(q, kp, vp, k_new, v_new, table, lens,
                                 sk, sv, interpret=True)
    want = paged_attention_reference(q, kp, vp, k_new, v_new, table,
                                     lens, sk, sv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # and the reference really equals dense attention on the gathered view
    dense = decode_attention_appended(
        q, gather_heads(kp, table), gather_heads(vp, table), k_new,
        v_new, lens,
        gather_heads(sk, table) if quant else None,
        gather_heads(sv, table) if quant else None)
    np.testing.assert_allclose(np.asarray(want), np.asarray(dense),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("w", [1, 3, 5])
@pytest.mark.parametrize("lengths", [[256, 100, 1], [0, 37, 255]])
def test_paged_window_kernel_matches_dense_reference(quant, w, lengths):
    """The verify-pass window kernel (decode kernel + exact in-window
    fold) == window_attention_appended over the gathered dense view —
    ragged cursors, empty slots, W=1 reduces to appended decode."""
    from gofr_tpu.ops.attention import window_attention_appended
    from gofr_tpu.ops.paged_attention import paged_window_attention

    lens = jnp.asarray(lengths, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, w, H, D), jnp.float32)
    k_new = jax.random.normal(ks[1], (B, w, KV, D), jnp.float32)
    v_new = jax.random.normal(ks[2], (B, w, KV, D), jnp.float32)
    _, kp, vp, _, _, table, sk, sv = _mk(ks[3], quant, lengths)
    got = paged_window_attention(q, kp, vp, k_new, v_new, table, lens,
                                 sk, sv, interpret=True)
    want = window_attention_appended(
        q, gather_heads(kp, table), gather_heads(vp, table), k_new,
        v_new, lens,
        gather_heads(sk, table) if quant else None,
        gather_heads(sv, table) if quant else None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_paged_decode_step_matches_contiguous(kv_dtype):
    """Seed a contiguous cache and a paged pool with the same prompt KV,
    then decode 2*T+8 greedy steps through both paths (crossing a block
    boundary) — logits argmax and cursor behavior must match exactly."""
    cfg = TINY
    params = llama.init(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (9, 4, 13)]
    slots, t, mb = 3, 16, 4
    max_seq = t * mb

    dense = llama.init_cache(cfg, slots, max_seq, dtype=kv_dtype)
    paged = init_paged_cache(cfg, slots, n_blocks=slots * mb + 1,
                             block_size=t, dtype=kv_dtype)
    alloc = BlockAllocator(paged.n_blocks)
    table = np.zeros((slots, mb), np.int32)
    rope = llama.get_rope_tables(cfg, max_seq)

    slot_blocks = []
    for b, prompt in enumerate(prompts):
        toks = jnp.asarray([prompt], jnp.int32)
        logits, k_stack, v_stack, _ = llama.prefill_kv(
            params, cfg, toks, rope_max=max_seq, rope_tables=rope)
        L = len(prompt)
        dense = llama.write_kv(dense, k_stack, v_stack, (0, b, 0, 0, 0),
                               dense.lengths.at[b].set(L))
        blocks = alloc.alloc(-(-L // t))
        slot_blocks.append(blocks)
        paged = write_prompt_blocks(paged, k_stack, v_stack,
                                    jnp.asarray(blocks), L)
        paged = paged._replace(lengths=paged.lengths.at[b].set(L))
        for j in range(mb):
            table[b, j] = blocks[min(j, len(blocks) - 1)]

    last = jnp.asarray([p[-1] for p in prompts], jnp.int32)
    # re-derive the first generated token from the prefill logits of each
    # prompt end: simpler — step both caches from the last prompt token
    d_tokens, p_tokens = last, last
    for step in range(2 * t + 8):
        # grow tables host-side exactly like the engine: ensure the
        # block for position `lengths` exists before stepping
        for b in range(slots):
            need = int(paged.lengths[b]) // t + 1
            while len(slot_blocks[b]) < need:
                nb = alloc.alloc(1)
                assert nb is not None
                slot_blocks[b].extend(nb)
            for j in range(mb):
                table[b, j] = slot_blocks[b][min(j, len(slot_blocks[b]) - 1)]
        d_logits, dense = llama.decode_step(params, cfg, d_tokens, dense,
                                            rope_tables=rope)
        p_logits, paged = paged_decode_step(params, cfg, p_tokens, paged,
                                            jnp.asarray(table),
                                            rope_tables=rope, flash=False)
        d_tok = jnp.argmax(d_logits, axis=-1).astype(jnp.int32)
        p_tok = jnp.argmax(p_logits, axis=-1).astype(jnp.int32)
        assert np.array_equal(np.asarray(d_tok), np.asarray(p_tok)), \
            f"diverged at step {step}"
        assert np.array_equal(np.asarray(dense.lengths),
                              np.asarray(paged.lengths))
        d_tokens, p_tokens = d_tok, p_tok


def test_write_prompt_blocks_partial_final_block():
    """Prompt KV lands in the right pool coordinates, incl. a partial
    final block; positions past the prompt stay untouched pool data."""
    cfg = TINY
    params = llama.init(cfg, jax.random.PRNGKey(2))
    t = 16
    S = 24  # 1.5 blocks
    toks = jnp.asarray([list(range(1, S + 1))], jnp.int32)
    _, k_stack, v_stack, _ = llama.prefill_kv(params, cfg, toks,
                                              rope_max=64)
    paged = init_paged_cache(cfg, 1, n_blocks=4, block_size=t)
    paged = write_prompt_blocks(paged, k_stack, v_stack,
                                jnp.asarray([2, 3]), S)
    got0 = np.asarray(paged.k[:, 2])            # block 2: rows 0..16
    got1 = np.asarray(paged.k[:, 3, :S - t])    # block 3: rows 16..24
    want = np.asarray(k_stack[:, 0].astype(paged.k.dtype))
    np.testing.assert_array_equal(got0, want[:, :t])
    np.testing.assert_array_equal(got1, want[:, t:S])
    assert not np.asarray(paged.k[:, 1]).any()  # unallocated untouched


def test_block_allocator():
    a = BlockAllocator(6)           # blocks 1..5 usable
    assert a.free_blocks == 5
    x = a.alloc(3)
    assert len(set(x)) == 3 and 0 not in x
    assert a.alloc(3) is None       # only 2 left: all-or-nothing
    assert a.free_blocks == 2
    a.free(x)
    assert a.free_blocks == 5
    with pytest.raises(ValueError):
        BlockAllocator(1)


# -- engine level -------------------------------------------------------------

from gofr_tpu.tpu import GenerationEngine, GenerationError  # noqa: E402


@pytest.fixture(scope="module")
def params():
    return llama.init(TINY, jax.random.PRNGKey(1))


def _streams(engine, prompts, n):
    streams = [engine.generate(p, max_new_tokens=n) for p in prompts]
    return [s.tokens() for s in streams]


def test_needs_lattice_peek(params):
    """The in-flight admission peek must flag exactly the requests that
    would run the chunk lattice: prompts past the largest bucket, and
    paged prefix HITS (which resume the lattice) — misses and short
    prompts stay admittable mid-flight."""
    from gofr_tpu.tpu.generator import _Request, GenStream

    def req(eng, prompt):
        return _Request(GenStream(0, eng),
                        np.asarray(prompt, np.int32), 4, 0.0, 0, None)

    rng = np.random.default_rng(11)
    prefix = rng.integers(1, TINY.vocab_size, 36).tolist()
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16),
                           paged_blocks=13, paged_block_size=16,
                           prefix_cache_slots=2, prefix_store_min=16)
    try:
        gen = eng
        short = rng.integers(1, TINY.vocab_size, 6).tolist()
        assert not gen._needs_lattice(req(eng, short))
        assert gen._needs_lattice(req(eng, rng.integers(
            1, TINY.vocab_size, 20).tolist()))  # > largest bucket
        # a stored prefix turns a continuation into a lattice resume
        assert not gen._needs_lattice(req(eng, prefix[:12] + [7, 7]))
        eng.generate(prefix, max_new_tokens=2).tokens()
        hits = req(eng, prefix + [5, 6])
        assert gen._needs_lattice(hits)
    finally:
        eng.close()


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_paged_engine_matches_contiguous_engine(params, kv_dtype):
    """The paged engine streams the exact tokens the contiguous engine
    does — concurrent slots, block-boundary crossings, slot reuse."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, TINY.vocab_size, n).tolist()
               for n in (9, 14, 5, 11)]
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8, 16), kv_dtype=kv_dtype)
    try:
        want = _streams(dense, prompts, 40)  # crosses the 16-block twice
    finally:
        dense.close()
    paged = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8, 16), kv_dtype=kv_dtype,
                             paged_blocks=2 * 4 + 1, paged_block_size=16)
    try:
        got = _streams(paged, prompts, 40)
        assert got == want
        st = paged.stats()["paged"]
        assert st["blocks"] == 8 and st["evictions"] == 0
        assert st["free"] == 8  # all retired -> all freed
    finally:
        paged.close()


def test_paged_pool_exhaustion_truncates_not_corrupts(params):
    """An undersized pool truncates the starving stream (counted as an
    eviction) instead of corrupting others: the surviving stream still
    matches the contiguous engine's tokens."""
    rng = np.random.default_rng(8)
    p1 = rng.integers(1, TINY.vocab_size, 8).tolist()
    p2 = rng.integers(1, TINY.vocab_size, 8).tolist()
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8,))
    try:
        w1 = dense.generate(p1, max_new_tokens=40).tokens()
        w2 = dense.generate(p2, max_new_tokens=40).tokens()
    finally:
        dense.close()
    # pool: trash + 3 blocks of 16 — two 8-token prompts admit (1 block
    # each), but both cannot grow to 48 tokens (needs 3 blocks each)
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8,), paged_blocks=4,
                           paged_block_size=16)
    try:
        s1 = eng.generate(p1, max_new_tokens=40)
        s2 = eng.generate(p2, max_new_tokens=40)
        g1, g2 = s1.tokens(), s2.tokens()
        st = eng.stats()["paged"]
        assert st["evictions"] >= 1
        # every delivered token is correct — truncated streams are a
        # PREFIX of the contiguous engine's output, never divergent
        assert g1 == w1[:len(g1)] and g2 == w2[:len(g2)]
        assert len(g1) == 40 or len(g2) == 40  # one stream ran to budget
        assert st["free"] == 3
    finally:
        eng.close()


def test_paged_engine_rejects_unsupported_combos(params):
    from gofr_tpu import parallel

    # paged + mesh is a SUPPORTED composition now (the pool shards
    # KV-heads over tp, attention runs the dense-gather reference —
    # docs/advanced-guide/multichip-serving.md); the old refusal would
    # be a regression. Deeper exactness coverage lives in
    # tests/test_multichip_serving.py — here just prove construction
    # and a served stream.
    mesh = parallel.make_mesh(dp=8)
    eng = GenerationEngine(TINY, parallel.shard_params(params, mesh),
                           slots=2, max_seq=64, prompt_buckets=(8,),
                           mesh=mesh, paged_blocks=8)
    try:
        assert len(eng.generate([3, 1, 4], max_new_tokens=3).tokens()) == 3
    finally:
        eng.close()
    with pytest.raises(ValueError, match="too small"):
        GenerationEngine(TINY, params, slots=2, max_seq=64,
                         prompt_buckets=(16,), paged_blocks=2,
                         paged_block_size=16)
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), paged_blocks=9,
                           paged_block_size=16)
    try:
        s = eng.generate(list(range(1, 65)), max_new_tokens=2)
        with pytest.raises(Exception, match="serving limit"):
            s.tokens()
    finally:
        eng.close()


def test_paged_long_prompt_chunked_admission_matches_contiguous(params):
    """Prompts past the largest bucket chunk-prefill into the dense
    scratch row and land in the pool via write_row_to_blocks — tokens
    must match the contiguous engine's chunked path exactly, including
    while another slot decodes (the interleaved-decode admission)."""
    rng = np.random.default_rng(11)
    long_p = rng.integers(1, TINY.vocab_size, 41).tolist()  # > bucket 16
    short_p = rng.integers(1, TINY.vocab_size, 7).tolist()
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8, 16), kv_dtype=jnp.int8)
    try:
        want_long = dense.generate(long_p, max_new_tokens=8).tokens()
        want_short = dense.generate(short_p, max_new_tokens=12).tokens()
    finally:
        dense.close()
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), kv_dtype=jnp.int8,
                           paged_blocks=9, paged_block_size=16)
    try:
        eng.warmup()  # compiles the scratch chunk lattice too
        s_short = eng.generate(short_p, max_new_tokens=12)
        s_long = eng.generate(long_p, max_new_tokens=8)
        assert s_long.tokens() == want_long
        assert s_short.tokens() == want_short
        assert eng.stats()["paged"]["free"] == 8
    finally:
        eng.close()


def test_paged_cancel_mid_long_admission_frees_blocks(params):
    """Cancelling a long prompt during chunked admission must return its
    pool blocks (the blocks are registered to the slot BEFORE the
    lattice runs, so the normal retire path frees them)."""
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), paged_blocks=9,
                           paged_block_size=16)
    rng = np.random.default_rng(13)
    try:
        total = eng.stats()["paged"]["free"]
        for _ in range(4):  # repeated cancels must not drain the pool
            s = eng.generate(rng.integers(1, TINY.vocab_size, 41).tolist(),
                             max_new_tokens=8)
            s.cancel()
            list(s)
        deadline = 50
        while eng.stats()["paged"]["free"] != total and deadline:
            import time
            time.sleep(0.1)
            deadline -= 1
        assert eng.stats()["paged"]["free"] == total
        # and the engine still serves
        got = eng.generate([1, 2, 3], max_new_tokens=3).tokens()
        assert len(got) == 3
    finally:
        eng.close()


def test_paged_structurally_oversized_prompt_fails_fast(params):
    """A prompt needing more blocks than the pool HAS must error, not
    requeue forever (the admission-livelock fix)."""
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), paged_blocks=4,
                           paged_block_size=16)  # 3 usable blocks
    try:
        s = eng.generate(list(range(1, 51)), max_new_tokens=2)  # needs 4
        with pytest.raises(Exception, match="pool blocks"):
            s.tokens()
    finally:
        eng.close()


def test_refcounted_allocator():
    a = BlockAllocator(5)            # blocks 1..4 usable
    x = a.alloc(2)
    a.ref(x)                         # second holder (a prefix entry)
    a.free(x)                        # first holder retires
    assert a.free_blocks == 2        # still held by the entry
    a.free(x)                        # entry evicted
    assert a.free_blocks == 4


def test_shared_prefix_index_zero_copy_semantics():
    from gofr_tpu.models.paged_llama import SharedPrefixIndex

    a = BlockAllocator(10)
    idx = SharedPrefixIndex(2, a, block_size=4)
    p1 = np.arange(1, 11, dtype=np.int32)          # 10 tokens = 2.5 blocks
    b1 = a.alloc(3)
    idx.store(p1, b1, adapter=0)                   # refs the 2 FULL blocks
    a.free(b1)                                      # the slot retires
    assert a.free_blocks == 10 - 1 - 2              # entry still holds 2
    # exact-prefix continuation: both full blocks reusable
    blocks, m = idx.match(np.concatenate([p1, [99, 98]]), 0)
    assert m == 8 and blocks == b1[:2]
    # partial overlap: only the first block's tokens agree
    p2 = np.concatenate([p1[:6], [77, 77, 77, 77]]).astype(np.int32)
    blocks, m = idx.match(p2, 0)
    assert m == 4 and blocks == b1[:1]
    # never consumes the whole prompt (>= 1 token recomputes)
    blocks, m = idx.match(p1[:8], 0)
    assert m == 4
    # adapters never cross
    assert idx.match(p1, adapter=1) == ([], 0)
    # eviction returns the blocks
    assert idx.evict_one()
    assert a.free_blocks == 10 - 1


def test_prefix_eviction_prefers_reclaimable_entries():
    """Pool-pressure eviction must pick an entry whose blocks ACTUALLY
    free (no live slot sharing them) over the LRU one — evicting a
    share-held entry reclaims nothing and would flush the index for no
    memory. clear() (engine recovery) drops everything."""
    from gofr_tpu.models.paged_llama import SharedPrefixIndex

    a = BlockAllocator(10)
    idx = SharedPrefixIndex(4, a, block_size=4)
    old = np.arange(1, 10, dtype=np.int32)          # 2 full blocks
    b_old = a.alloc(3)
    idx.store(old, b_old, adapter=0)                # LRU-oldest entry
    # a live slot still shares the old entry's full blocks
    slot_hold = b_old[:2]
    a.ref(slot_hold)
    a.free(b_old)                                    # storing slot retires
    new = np.arange(50, 59, dtype=np.int32)
    b_new = a.alloc(3)
    idx.store(new, b_new, adapter=0)                # newer, sole-held
    a.free(b_new)
    free_before = a.free_blocks
    assert idx.evict_one()
    # the NEWER (reclaimable) entry went, and its 2 full blocks freed
    assert a.free_blocks == free_before + 2
    blocks, m = idx.match(np.concatenate([old, [99]]), 0)
    assert m == 8, "the share-held LRU entry must survive"
    idx.reject()
    # nothing reclaimable left: the share-held entry is still evictable
    # (finite retry loops), it just frees no blocks yet
    free_before = a.free_blocks
    assert idx.evict_one()
    assert a.free_blocks == free_before
    assert not idx.evict_one()
    a.free(slot_hold)                                # slot retires later
    assert a.free_blocks == 9                        # everything back

    b = a.alloc(2)
    idx.store(np.arange(1, 10, dtype=np.int32), b, adapter=0)
    a.free(b)
    assert idx.clear() == 1
    assert a.free_blocks == 9


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_paged_prefix_hits_stream_exact_tokens(params, kv_dtype):
    """The zero-copy prefix cache: a stored prompt's blocks are SHARED
    into later slots (no KV copied to store) and hit streams equal the
    prefix-less contiguous engine's exactly — incl. a partial-overlap
    hit and an exact repeat."""
    rng = np.random.default_rng(17)
    prefix = rng.integers(1, TINY.vocab_size, 36).tolist()  # 2 full 16-blocks
    cont = prefix + rng.integers(1, TINY.vocab_size, 6).tolist()
    part = prefix[:20] + rng.integers(1, TINY.vocab_size, 8).tolist()
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8, 16), kv_dtype=kv_dtype)
    try:
        oracle = {tuple(p): dense.generate(p, max_new_tokens=6).tokens()
                  for p in (prefix, cont, part)}
    finally:
        dense.close()
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), kv_dtype=kv_dtype,
                           paged_blocks=13, paged_block_size=16,
                           prefix_cache_slots=2, prefix_store_min=16)
    try:
        assert eng.generate(prefix, max_new_tokens=6).tokens() == \
            oracle[tuple(prefix)]
        st = eng.stats()["prefix_cache"]
        assert st["entries"] == 1 and st["blocks_held"] == 2
        for p in (cont, part, prefix):  # full hit, partial hit, repeat
            assert eng.generate(p, max_new_tokens=6).tokens() == \
                oracle[tuple(p)], f"prompt len {len(p)}"
        assert eng.stats()["prefix_cache"]["hits"] >= 3
        # all slots retired: only the entries hold blocks
        free = eng.stats()["paged"]["free"]
        held = eng.stats()["prefix_cache"]["blocks_held"]
        assert free + held == eng.stats()["paged"]["blocks"]
    finally:
        eng.close()


def test_paged_prefix_off_lattice_window_degrades_to_miss(params):
    """A hit whose resumed final-chunk window would pad wider than the
    prompt (negative start — off the compiled lattice) must downgrade to
    a miss and still stream the exact reference tokens (the same
    reject-to-miss guard the contiguous _prefix_restore has)."""
    rng = np.random.default_rng(23)
    base = rng.integers(1, TINY.vocab_size, 16).tolist()
    short = base[:8] + rng.integers(1, TINY.vocab_size, 2).tolist()  # L=10
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(16,))
    try:
        want = dense.generate(short, max_new_tokens=6).tokens()
    finally:
        dense.close()
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(16,), paged_blocks=9,
                           paged_block_size=8, prefix_cache_slots=2,
                           prefix_store_min=16)
    try:
        eng.generate(base, max_new_tokens=2).tokens()   # stores 2 blocks
        got = eng.generate(short, max_new_tokens=6).tokens()
        assert got == want
        # the 8-token match existed but the window was invalid: no hit
        assert eng.stats()["prefix_cache"]["hits"] == 0
    finally:
        eng.close()


def test_paged_prefix_hit_with_interleaved_decode_never_corrupts_shared(
        params):
    """A prefix hit whose remainder needs MID chunks interleaves decode
    ticks into its admission; the admitted slot's stale device cursor
    must not let those ticks scatter garbage into SHARED blocks (the
    write-back only repairs the fresh region). After the storm, a THIRD
    request hitting the same shared blocks must still stream the exact
    reference tokens."""
    rng = np.random.default_rng(29)
    prefix = rng.integers(1, TINY.vocab_size, 33).tolist()   # 2 full blocks
    long_hit = prefix + rng.integers(1, TINY.vocab_size, 20).tolist()  # 53
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8, 16))
    try:
        want_long = dense.generate(long_hit, max_new_tokens=4).tokens()
        want_pfx = dense.generate(prefix, max_new_tokens=4).tokens()
    finally:
        dense.close()
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), paged_blocks=11,
                           paged_block_size=16, prefix_cache_slots=2,
                           prefix_store_min=16)
    try:
        # seed the entry, then keep slot 0 decoding while the hit admits
        assert eng.generate(prefix, max_new_tokens=4).tokens() == want_pfx
        busy = eng.generate(rng.integers(1, TINY.vocab_size, 5).tolist(),
                            max_new_tokens=48)
        got = eng.generate(long_hit, max_new_tokens=4).tokens()
        assert got == want_long
        assert eng.stats()["prefix_cache"]["hits"] >= 1
        busy.cancel()
        list(busy)
        # the shared blocks survived the interleaved garbage writes
        again = eng.generate(prefix, max_new_tokens=4).tokens()
        assert again == want_pfx
    finally:
        eng.close()


def test_paged_prefix_entries_evict_under_pool_pressure(params):
    """Stored entries are the pool's pressure valve: when a live stream
    needs a block and none are free, LRU entries evict (no stream
    truncation) and their blocks recycle."""
    rng = np.random.default_rng(19)
    p1 = rng.integers(1, TINY.vocab_size, 16).tolist()
    p2 = rng.integers(1, TINY.vocab_size, 16).tolist()
    eng = GenerationEngine(TINY, params, slots=1, max_seq=64,
                           prompt_buckets=(8, 16), paged_blocks=5,
                           paged_block_size=16, prefix_cache_slots=2,
                           prefix_store_min=16)
    try:
        # p1 stores a 1-block entry and retires (entry keeps the block);
        # p2's long decode then needs all 4 usable blocks — the entry
        # must evict mid-decode, the stream must NOT truncate
        eng.generate(p1, max_new_tokens=2).tokens()
        assert eng.stats()["prefix_cache"]["entries"] == 1
        got = eng.generate(p2, max_new_tokens=40).tokens()
        assert len(got) == 40
        st = eng.stats()
        assert st["paged"]["evictions"] == 0          # no truncation
        assert st["prefix_cache"]["entries"] <= 1     # p1's entry evicted
        # (p2's own entry may have been stored after the eviction)
    finally:
        eng.close()


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_paged_spec_decode_matches_plain_engine(params, kv_dtype):
    """Speculative decoding over the paged pool: repetitive greedy
    streams equal the plain (contiguous, spec-less) engine's token for
    token, the verify pass actually runs, and window writes cross block
    boundaries without corruption."""
    rep = [7, 9, 7, 9, 7, 9, 7, 9, 7, 9]
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8, 16), kv_dtype=kv_dtype)
    try:
        want = dense.generate(rep, max_new_tokens=30).tokens()
    finally:
        dense.close()
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), kv_dtype=kv_dtype,
                           paged_blocks=9, paged_block_size=16,
                           spec_decode_k=3)
    try:
        got = eng.generate(rep, max_new_tokens=30).tokens()
        assert got == want
        st = eng.stats()["spec_decode"]
        assert st["emitted"] >= st["windows"] > 0
        assert eng.stats()["paged"]["free"] == 8  # retired -> freed
    finally:
        eng.close()


def test_paged_multi_lora_streams_match_merged_reference():
    """Multi-LoRA composes with the paged pool (adapters are params-side,
    orthogonal to cache layout): per-request adapters over paged blocks
    stream the merged-weights reference exactly."""
    params = llama.init(TINY, jax.random.PRNGKey(1))
    layers = {**params["layers"],
              **llama.init_lora(TINY, 2, 4, jax.random.PRNGKey(2))}
    for name in llama.LORA_TARGETS:
        b = layers[f"lora_b_{name}"]
        # crc32, not salted hash(): weights must be reproducible
        fill = jax.random.normal(
            jax.random.PRNGKey(zlib.crc32(name.encode()) % 997),
                                 b.shape[:1] + b.shape[2:]) * 0.05
        layers[f"lora_b_{name}"] = b.at[:, 1].set(fill.astype(b.dtype))
    lp = {**params, "layers": layers}

    def ref(prompt, n, adapter):
        merged = llama.merge_lora(lp, TINY, adapter)
        toks = list(prompt)
        for _ in range(n):
            logits = llama.forward(merged, TINY,
                                   jnp.asarray([toks], jnp.int32))
            toks.append(int(jnp.argmax(logits[0, -1])))
        return toks[len(prompt):]

    eng = GenerationEngine(TINY, lp, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), lora_adapters=2,
                           paged_blocks=9, paged_block_size=16)
    rng = np.random.default_rng(31)
    p = rng.integers(1, TINY.vocab_size, 6).tolist()
    try:
        s0 = eng.generate(p, max_new_tokens=8, adapter=0)
        s1 = eng.generate(p, max_new_tokens=8, adapter=1)
        assert s0.tokens() == ref(p, 8, 0)
        assert s1.tokens() == ref(p, 8, 1)
    finally:
        eng.close()


def test_paged_engine_warmup_and_drain(params):
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16), paged_blocks=9,
                           paged_block_size=16)
    try:
        eng.warmup()
        s = eng.generate([3, 1, 4, 1, 5], max_new_tokens=4)
        assert len(s.tokens()) == 4
        assert eng.drain(timeout=5.0)
    finally:
        eng.close()


def test_paged_recovery_cycles_clear_shared_prefix_and_keep_serving(params):
    """Device-failure recovery on a PAGED engine with the zero-copy
    prefix cache, cycled: each recovery must reallocate the pool and
    clear the shared-prefix index (stored entries reference blocks of
    the OLD pool — a hit through the fresh pool would restore all-zero
    KV), with every invariant already consistent the instant the error
    unblocks the consumer, exact tokens on the next serve, and the
    allocator's free-block accounting balanced across recoveries (no
    reference leaks)."""
    rng = np.random.default_rng(23)
    prefix = rng.integers(1, TINY.vocab_size, 36).tolist()  # 2 full blocks
    dense = GenerationEngine(TINY, params, slots=2, max_seq=64,
                             prompt_buckets=(8, 16))
    try:
        want = dense.generate(prefix, max_new_tokens=6).tokens()
    finally:
        dense.close()
    eng = GenerationEngine(TINY, params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16),
                           paged_blocks=13, paged_block_size=16,
                           prefix_cache_slots=2, prefix_store_min=16)
    try:
        idle_free = eng.stats()["paged"]["free"]
        for cycle in range(4):
            got = eng.generate(prefix, max_new_tokens=6).tokens()
            assert got == want, f"cycle {cycle}"
            assert eng.stats()["prefix_cache"]["entries"] == 1
            assert eng.stats()["paged"]["free"] < idle_free  # entry holds
            real = eng._step_jit
            state = {"fired": False}

            def flaky(*a, **k):
                if not state["fired"]:
                    state["fired"] = True
                    raise RuntimeError(f"paged injected failure #{cycle}")
                return real(*a, **k)

            eng._step_jit = flaky
            with pytest.raises(GenerationError):
                eng.generate([1, 2, 3], max_new_tokens=4).tokens()
            eng._step_jit = real
            # observer-consistency at the instant the error unblocked us
            assert eng.down is None, f"cycle {cycle}"
            assert len(eng._prefix_idx) == 0, f"cycle {cycle}"
            # refcount balance: entries cleared + failed slot retired
            # returns EVERY block to the free list — leaks here would
            # shrink the pool a little every recovery until admissions
            # stall under phantom pressure
            assert eng.stats()["paged"]["free"] == idle_free, \
                f"cycle {cycle}"
    finally:
        eng.close()
