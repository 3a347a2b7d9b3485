"""Latent (MLA) attention over a cache of one row a token a layer.

The cache row is ``[c_kv | k_pe]``: the ``rank`` values of the normed
latent and the rotated key that all heads share. Keys and values a head
are never stored: the query is *absorbed* (``q_nope . W_UK^T``, done by
the caller) so that a head's score against a cached row is one dot
product over the row, ``[q_abs | q_pe] . [c_kv | k_pe]``, and its output
is the probability-weighted sum of the rows' first ``rank`` values, which
the caller takes through ``W_UV``. Every head reads the same row, so the
step streams ``width`` values a token a layer whatever the head count.

Three shapes of it, all float32 scores and softmax:

``prefill_attention``  expanded, causal, a whole prompt (keys and values
    a head materialised over the prompt only): the reference form.
``chunk_attention``    a chunk attends absorbed to the rows cached
    before it and expanded to its own tokens, one softmax over both,
    run from the chunk's tokens over the blocks of rows under its
    start (``chunk_attention_kept`` is the walk; a mask a query there).
``decode_attention``   one token a slot, absorbed, the appended row
    riding beside the cache. On a TPU, for shapes the kernel takes
    (``decode_block``), the Pallas kernel below is handed the whole
    stacked cache ``[L, B, Smax, width]`` and the layer index and
    fetches each slot's rows from 0 to its cursor rounded up to the
    block, once and in place: nothing past it, nothing for a slot of
    length 0, no layer copy (ops/flash_decode.py's work list and block;
    one row tile serves scores and values).

The kernel's schedule (PERF.md, Findings PR 44; TPU v5e, nine layers a
call at 128 slots x 2,048 rows x 640 lanes with 36% of the pool live and
42% fetched, ms a call of which 0.21 are the work lists and selects
around the kernels): an item is a [256, 640] bfloat16 tile, 0.40 us of
DMA at the chip's bandwidth, and two matmuls that 64 query rows run
through a 128-wide matrix unit in about as long. One item a trip of the
loop with one more in flight, as the kernel was written, paid the two
one after the other: 3.31. Two in flight: 2.88; three: 2.87. Two items
a trip, as two chains one after the other in one straight line of code
so that the second's score matmul is issued while the first's softmax
runs: 2.40 with two more in flight, 2.12 with four, 2.10 with six.
Three a trip and six in flight 1.96; FOUR A TRIP AND EIGHT IN FLIGHT
1.91 (``_CHAINS``, ``_N_BUF``); twelve in flight, six or eight a trip
the same. With every slot full the same call reads 7.32 -> 4.20, 92% of
the bandwidth. Read no better and left out: a pair of one slot as ONE
softmax over 512 positions (2.17 against 2.12; folded alone where the
pair straddles two slots 2.60), the last division as a reciprocal and a
product (2.16 against 2.12), the running max and sum kept one lane wide
(2.11 against 2.12).

Query scale: the caller multiplies the queries by the softmax scale,
``(nope + rope)^-1/2`` times YaRN's factor; nothing here knows it.

Stored width: the HBM tile is 128 lanes wide, so XLA stores a minor
dimension of 576 in 640 lanes whatever the program says, and Mosaic
cannot slice a tile-unaligned minor dimension of an HBM array at all.
The caller therefore stores rows ``width`` wide with ``width`` a whole
number of lanes (576 values, then zeros to 640) and pads ``q_cat`` and
the appended row with zeros to match; a zero lane adds nothing to a
score, and the values are the row's first ``rank`` lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .flash_decode import _LANES, _SUBLANES, _work_list, block_size


@jax.named_scope("mla/prefill_attn")
def prefill_attention(q, k_nope, k_pe, v, mask=None, keep=None):
    """Expanded causal attention. q [B, S, H, dn + dr] (scaled);
    k_nope [B, S, H, dn]; k_pe [B, S, dr], one for all heads;
    v [B, S, H, dv]; mask [B, S] valid tokens; keep [B or 1, S, S]: the
    positions each query may see beside the causal rule (a window's
    band, a learned selection). Returns [B, S, H, dv]."""
    s = q.shape[1]
    dn = k_nope.shape[-1]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], k_pe,
                           preferred_element_type=jnp.float32))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    keep = causal if keep is None else causal & keep[:, None]
    if mask is not None:
        keep = keep & mask[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(keep, scores, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


# cached rows a trip of ``chunk_attention_kept``'s walk scores
_CHUNK_BLOCK = 512
# score rows (queries x heads) a tile of ``chunk_walk_latent`` holds
_CHUNK_TILE_ROWS = 1024


def chunk_block(rows: int) -> int:
    """The block ``chunk_attention_kept`` walks ``rows`` cached rows in:
    ``_CHUNK_BLOCK``, fitted to a table it does not divide."""
    from .flash import fit_block

    return fit_block(rows, _CHUNK_BLOCK)


def chunk_tile(chunk: int, heads: int, table: int, width: int, rank: int,
               dtype) -> int | None:
    """The queries a tile of ``chunk_walk_latent`` scores (with all their
    heads) where backend and shapes allow the kernel, None where the
    walk stays the jnp loop: not a TPU, operands that are not bfloat16,
    a rank, a stored width or a block of rows that is not whole lanes,
    heads that are not whole sublanes, a chunk the tile does not divide.
    ``GOFR_FLASH_INTERPRET=1`` runs the kernel interpreted on any
    backend, at whatever tile divides the chunk."""
    from .flash import fit_block, interpret_env, tpu_backend_ok

    tile = max(_SUBLANES, _CHUNK_TILE_ROWS // heads // _SUBLANES * _SUBLANES)
    if interpret_env():
        return fit_block(chunk, tile)
    if (chunk % tile or heads % _SUBLANES or rank % _LANES or width % _LANES
            or chunk_block(table) % _LANES
            or jnp.dtype(dtype) != jnp.bfloat16 or not tpu_backend_ok()):
        return None
    return tile


def _chunk_walk_kernel(n_ref, q_ref, keep_hbm, rows_hbm, o_ref, m_out, l_out,
                       rows_buf, keep_buf, m_ref, l_ref, acc_ref, sem, *,
                       block: int, rank: int, heads: int):
    """One tile of queries (all heads of each, folded into the matmuls'
    rows: the keys are one for all heads) over the first ``n_ref[0]``
    blocks of a slot's cached rows, under a running softmax whose scores
    stay in VMEM. The blocks every tile walks are the same, so the tiles
    of rows and of the mask are ONE stream over the whole grid, the next
    item in flight while this one is folded: a tile's first block was
    started by the tile before it."""
    b, i = pl.program_id(0), pl.program_id(1)
    nb, nq = pl.num_programs(0), pl.num_programs(1)
    n = n_ref[0]
    tq = keep_buf.shape[1]
    first = (b * nq + i) * n

    def copies(b, i, j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(rows_hbm.at[b, at], rows_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(
                    keep_hbm.at[b, pl.ds(pl.multiple_of(i * tq, tq), tq), at],
                    keep_buf.at[slot], sem.at[1, slot]))

    @pl.when((first == 0) & (n > 0))
    def _open():
        for c in copies(b, i, 0, 0):
            c.start()

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(j, _):
        slot = (first + j) % 2
        for c in copies(b, i, j, slot):
            c.wait()

        @pl.when(j + 1 < n)
        def _next_block():
            for c in copies(b, i, j + 1, 1 - slot):
                c.start()

        @pl.when((j + 1 == n) & ((i + 1 < nq) | (b + 1 < nb)))
        def _next_tile():
            wrap = i + 1 == nq
            for c in copies(jnp.where(wrap, b + 1, b),
                            jnp.where(wrap, 0, i + 1), 0, 1 - slot):
                c.start()

        tile = rows_buf[slot]                                # [block, width]
        s = jax.lax.dot_general(q_ref[0], tile, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        keep = keep_buf[slot] != 0                           # [tq, block]
        # one row of the mask serves a query's heads
        s = jnp.concatenate(
            [jnp.where(keep[a:a + 1], s[a * heads:(a + 1) * heads], NEG_INF)
             for a in range(tq)], 0)
        m = m_ref[:, :1]
        m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p, scale = jnp.exp(s - m_next), jnp.exp(m - m_next)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * scale + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * scale + jax.lax.dot_general(
            p.astype(tile.dtype), tile[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)

    jax.lax.fori_loop(0, n, fold, None)
    o_ref[0] = acc_ref[...]
    # a query's [heads, 1] column as the [1, heads] row the caller reads:
    # the diagonal of its broadcast, summed over sublanes
    eye = (jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 1))
    for ref, out in ((m_ref, m_out), (l_ref, l_out)):
        for a in range(tq):
            col = ref[a * heads:(a + 1) * heads, :1]
            out[0, a:a + 1, :] = jnp.sum(jnp.where(eye, col, 0.0), axis=0,
                                         keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("rank", "block", "tile", "interpret"))
def chunk_walk_latent(q_cat, rows, keep_cache, live, *, rank: int,
                      block: int, tile: int, interpret: bool = False):
    """The cached part of ``chunk_attention_kept`` as one kernel: for
    every query and head the running maximum ``m``, the sum ``l`` and the
    unnormalised latent output of a softmax over the rows ``keep_cache``
    keeps among the first ``live``, fetched a block of ``block`` rows at
    a time and no block past ``live``'s. q_cat [B, C, H, width];
    rows [B, T, width]; keep_cache [B or 1, C or 1, T]. Returns
    (o [B, C, H, rank] float32, m [B, C, H], l [B, C, H]); a query that
    keeps nothing, and every query at ``live`` 0, answers NEG_INF for
    ``m`` with an ``o`` that is finite, which its weight in the caller's
    merge brings to nothing."""
    b, c, h, width = q_cat.shape
    t = rows.shape[1]
    n = jnp.minimum((live + block - 1) // block, t // block)
    stat = jax.ShapeDtypeStruct((b, c, h), jnp.float32)
    stats = pl.BlockSpec((1, tile, h), lambda b, i, n: (b, i, 0))
    o, m, l = pl.pallas_call(
        functools.partial(_chunk_walk_kernel, block=block, rank=rank,
                          heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, c // tile),
            in_specs=[pl.BlockSpec((1, tile * h, width),
                                   lambda b, i, n: (b, i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, tile * h, rank),
                                    lambda b, i, n: (b, i, 0)),
                       stats, stats],
            scratch_shapes=[
                pltpu.VMEM((2, block, width), rows.dtype),
                pltpu.VMEM((2, tile, block), jnp.int32),
                pltpu.VMEM((tile * h, _LANES), jnp.float32),
                pltpu.VMEM((tile * h, _LANES), jnp.float32),
                pltpu.VMEM((tile * h, rank), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=[jax.ShapeDtypeStruct((b, c * h, rank), jnp.float32),
                   stat, stat],
        interpret=interpret,
        # the stream of row tiles runs from one grid step into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        name="chunk_walk_latent",
    )(jnp.reshape(n, (1,)).astype(jnp.int32),
      q_cat.reshape(b, c * h, width).astype(rows.dtype),
      jnp.broadcast_to(keep_cache, (b, c, t)).astype(jnp.int32), rows)
    return o.reshape(b, c, h, rank), m, l


@jax.named_scope("mla/chunk_attn_kept")
def chunk_attention_kept(q_cat, q, rows, k_nope, k_pe, v, rank: int,
                         keep_cache, keep_new, live):
    """``chunk_attention`` with a mask a query in place of the cursor:
    keep_cache [B or 1, C or 1, T] over the cached rows ``rows``
    [B, T, width] (a ring's rows in the order they lie, or a slot's
    rows from 0),
    keep_new [B or 1, C, C] over the chunk's own tokens (the causal rule
    included). ``live`` (a traced scalar): the leading rows of ``rows``
    that ``keep_cache`` can keep, the chunk's start or what of it a ring
    holds. The cached rows are walked a block of ``_CHUNK_BLOCK`` at a
    time up to ``live`` under a running softmax, so a reserved row costs
    nothing and no score leaves the chip's VMEM where ``chunk_tile``
    hands the walk to ``chunk_walk_latent`` (the chunk's own tokens then
    meet its answer by their maxima and sums); elsewhere the walk is a
    jnp loop that starts from the chunk's own tokens and holds heads x C
    x block float32 scores at once (all T of them are 1 GB a layer at
    128 x 512 x 4,096). Returns (o_lat [B, C, H, rank] float32,
    o_new [B, C, H, dv]) as ``chunk_attention`` does."""
    t, dn = rows.shape[1], k_nope.shape[-1]
    block = chunk_block(t)
    rows = rows.astype(q_cat.dtype)
    s_new = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], k_pe,
                          preferred_element_type=jnp.float32))
    s_new = jnp.where(keep_new[:, None], s_new, NEG_INF)
    # a query whose selection kept none of the chunk's tokens starts from
    # NEG_INF and weights of 1: the first cached row it does keep scales
    # them to nothing
    m = jnp.max(s_new, -1, keepdims=True)                    # [B, H, C, 1]
    p = jnp.exp(s_new - m)
    l = jnp.sum(p, -1, keepdims=True)
    o_new = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    queries = chunk_tile(q.shape[1], q.shape[2], t, rows.shape[-1], rank,
                         rows.dtype)
    if queries:
        from .flash import interpret_env

        o_lat, m_c, l_c = chunk_walk_latent(
            q_cat, rows, keep_cache, live, rank=rank, block=block,
            tile=queries, interpret=interpret_env())
        m_c, l_c = (jnp.swapaxes(a, 1, 2)[..., None] for a in (m_c, l_c))
        m_all = jnp.maximum(m, m_c)
        w_new, w_c = jnp.exp(m - m_all), jnp.exp(m_c - m_all)
        l = l * w_new + l_c * w_c
        return (o_lat * jnp.swapaxes(w_c / l, 1, 2),
                jnp.swapaxes(o_new * (w_new / l), 1, 2).astype(v.dtype))

    def fold(j, carry):
        m, l, o_lat, o_new = carry
        tile = jax.lax.dynamic_slice_in_dim(rows, j * block, block, 1)
        keep = jax.lax.dynamic_slice_in_dim(keep_cache, j * block, block, 2)
        s = jnp.einsum("bqhw,btw->bhqt", q_cat, tile,
                       preferred_element_type=jnp.float32)
        s = jnp.where(keep[:, None], s, NEG_INF)
        m_next = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        scale, p = jnp.exp(m - m_next), jnp.exp(s - m_next)
        o_lat = o_lat * scale + jnp.einsum(
            "bhqt,btr->bhqr", p.astype(rows.dtype), tile[..., :rank],
            preferred_element_type=jnp.float32)
        return (m_next, l * scale + jnp.sum(p, -1, keepdims=True), o_lat,
                o_new * scale)

    _, l, o_lat, o_new = jax.lax.fori_loop(
        0, (live + block - 1) // block, fold,
        (m, l, jnp.zeros(m.shape[:3] + (rank,), jnp.float32), o_new))
    return (jnp.swapaxes(o_lat / l, 1, 2),
            jnp.swapaxes(o_new / l, 1, 2).astype(v.dtype))


@jax.named_scope("mla/chunk_attn")
def chunk_attention(q_cat, q, rows, start, k_nope, k_pe, v, rank: int):
    """A chunk of C tokens at [start, start + C): absorbed over the rows
    cached before it, expanded and causal within itself; one softmax
    over both, by ``chunk_attention_kept``'s walk with the cursor as its
    mask, so that the blocks under ``start`` are fetched and no others.

    q_cat [B, C, H, width]: [q_abs | q_pe], scaled; q [B, C, H, dn + dr],
    scaled; rows [B, Smax, width]; k_nope/k_pe/v: the chunk's own, as in
    ``prefill_attention``. Returns (o_lat [B, C, H, rank] float32: the
    cached rows' part, still latent; o_new [B, C, H, dv]: the chunk's
    part). The caller adds ``o_lat . W_UV`` and ``o_new``."""
    c = q.shape[1]
    return chunk_attention_kept(
        q_cat, q, rows, k_nope, k_pe, v, rank,
        (jnp.arange(rows.shape[1]) < start)[None, None],
        jnp.tril(jnp.ones((c, c), bool))[None], start)


def decode_attention_reference(q_cat, rows, row_new, lengths, rank: int,
                               keep=None, keep_new=None):
    """One token a slot over every reserved position, masked by the
    cursor. q_cat [B, H, width] scaled; rows [B, Smax, width];
    row_new [B, width]: this token's row, not in the cache yet;
    lengths [B] excluding it; keep [B, Smax] and keep_new [B]: the
    cached rows below the cursor, and whether the token's own row, that
    a learned selection kept (None: all). Returns o_lat [B, H, rank] in
    q's dtype."""
    smax = rows.shape[1]
    rows = rows.astype(q_cat.dtype)
    s_cache = jnp.einsum("bhw,btw->bht", q_cat, rows,
                         preferred_element_type=jnp.float32)
    live = jnp.arange(smax)[None, None] < lengths[:, None, None]
    if keep is not None:
        live = live & keep[:, None]
    s_cache = jnp.where(live, s_cache, NEG_INF)
    s_new = jnp.einsum("bhw,bw->bh", q_cat, row_new,
                       preferred_element_type=jnp.float32)[..., None]
    if keep_new is not None:
        s_new = jnp.where(keep_new[:, None, None], s_new, NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([s_cache, s_new], -1), axis=-1)
    o = (jnp.einsum("bht,btr->bhr", probs[..., :smax].astype(rows.dtype),
                    rows[..., :rank], preferred_element_type=jnp.float32)
         + probs[..., smax:] * row_new[:, None, :rank].astype(jnp.float32))
    return o.astype(q_cat.dtype)


_CHAINS = 4             # items a trip of the kernel's loop folds, a chain each
_N_BUF = 3 * _CHAINS    # tiles in VMEM: a trip's, two more trips' in flight


def _decode_kernel(layer_ref, n_ref, slot_ref, blk_ref, len_ref, *refs,
                   block_s: int, rank: int, masked: bool = False):
    """One layer: walk the (slot, block) work list ``_CHAINS`` items a
    trip, the next two trips' tiles in flight. A row tile serves the
    score matmul whole and the value matmul by its first ``rank`` lanes.

    ``masked``: a learned selection goes with the cursor. ``own_ref``
    [B] (scalars) says whether a slot's appended row is kept,
    ``keep_ref`` [B * blocks a slot, block_s] which of a block's rows
    are; a row left out scores NEG_INF, as one past the cursor does.

    A trip's items are one straight line of code whatever their slots:
    every score matmul first, then each item's softmax update starting
    from the item before it, or afresh (a select) where it opens a
    slot, so that one item's matmuls are issued while another's softmax
    runs. What needs a branch stands before the line (a slot's first
    score) or after it (a slot's answer)."""
    if masked:
        own_ref, q_ref, new_ref, keep_ref, rows_hbm, o_ref, buf, m_ref, \
            l_ref, acc_ref, first_ref, sem = refs
        per_slot = keep_ref.shape[0] // len_ref.shape[0]
    else:
        q_ref, new_ref, rows_hbm, o_ref, buf, m_ref, l_ref, acc_ref, \
            first_ref, sem = refs
    layer = layer_ref[0]
    n = n_ref[0]

    def copy(w):
        start = pl.multiple_of(blk_ref[w] * block_s, block_s)
        return pltpu.make_async_copy(
            rows_hbm.at[layer, slot_ref[w], pl.ds(start, block_s)],
            buf.at[w % _N_BUF], sem.at[w % _N_BUF])

    def first_score(slot):
        # the appended row is the recurrence's first element
        new = new_ref[slot].astype(jnp.float32)              # [1, width]
        s = jnp.sum(q_ref[slot].astype(jnp.float32) * new, axis=-1,
                    keepdims=True)                           # [H, 1]
        return jnp.where(own_ref[slot] != 0, s, NEG_INF) if masked else s

    def first_acc(slot):
        return jnp.broadcast_to(
            new_ref[slot].astype(jnp.float32)[:, :rank], acc_ref.shape)

    def fold(a, count: int):
        """Items a .. a + count - 1 (their tiles have landed)."""
        slots = [slot_ref[a + j] for j in range(count)]
        blks = [blk_ref[a + j] for j in range(count)]
        lens = [len_ref[slot] for slot in slots]

        for j in range(count):
            @pl.when(blks[j] == 0)
            def _first():
                first_ref[j] = jnp.broadcast_to(first_score(slots[j]),
                                                first_ref.shape[1:])

        scored = []
        for j in range(count):
            tile = buf[(a + j) % _N_BUF]                     # [BS, width]
            s = jax.lax.dot_general(
                q_ref[slots[j]], tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            pos = blks[j] * block_s + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_s), 1)
            seen = pos < lens[j]
            if masked:
                seen = seen & (keep_ref[pl.ds(
                    slots[j] * per_slot + blks[j], 1), :] != 0)
            scored.append((tile, jnp.where(seen, s, NEG_INF)))
        m, l, acc = m_ref[:, :1], l_ref[:, :1], acc_ref[...]
        answers = []
        for j, (tile, s) in enumerate(scored):               # s [H, BS]
            fresh = blks[j] == 0
            m = jnp.where(fresh, first_ref[j][:, :1], m)
            l = jnp.where(fresh, 1.0, l)
            acc = jnp.where(fresh, first_acc(slots[j]), acc)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(tile.dtype), tile[:, :rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m = m_new
            answers.append((l, acc))
        m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l, l_ref.shape)
        acc_ref[...] = acc
        for j, (l, acc) in enumerate(answers):
            @pl.when((blks[j] + 1) * block_s >= lens[j])
            def _done():
                o_ref[slots[j]] = (acc / l).astype(o_ref.dtype)

    ahead = _N_BUF - _CHAINS
    for w in range(ahead):
        @pl.when(w < n)
        def _start():
            copy(w).start()

    def trip(t, _):
        a = t * _CHAINS
        for j in range(_CHAINS):       # into the buffers the last trip left
            @pl.when(a + ahead + j < n)
            def _next():
                copy(a + ahead + j).start()

        for j in range(_CHAINS):
            copy(a + j).wait()
        fold(a, _CHAINS)

    def alone(w, _):
        copy(w).wait()
        fold(w, 1)

    jax.lax.fori_loop(0, n // _CHAINS, trip, None)
    jax.lax.fori_loop(n - n % _CHAINS, n, alone, None)


@functools.partial(jax.jit, static_argnames=("rank", "block_s", "interpret"))
def decode_attention_stacked(q_cat, rows, row_new, lengths, layer, *,
                             rank: int, block_s: int,
                             interpret: bool = False, keep=None,
                             keep_new=None):
    """``decode_attention_reference`` over layer ``layer`` of the stacked
    cache ``rows`` [L, B, Smax, width], reading only what ``lengths``
    (0 for a slot whose cache must not be read) says is live. With
    ``keep`` [B, Smax] and ``keep_new`` [B] the blocks up to the cursor
    are fetched all the same and the rows left out are masked."""
    b, h, width = q_cat.shape
    smax = rows.shape[2]
    h_pad = -(-h // _SUBLANES) * _SUBLANES
    lengths = lengths.astype(jnp.int32)
    n, slot, blk = _work_list(lengths, smax, block_s)
    qp = jnp.pad(q_cat, ((0, 0), (0, h_pad - h), (0, 0)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    masked = keep is not None
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32), n, slot, blk,
               lengths)
    operands = (qp.astype(rows.dtype),
                row_new[:, None, :].astype(rows.dtype))
    if masked:
        scalars += (keep_new.astype(jnp.int32),)
        operands += (keep.astype(jnp.int32).reshape(-1, block_s),)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, rank=rank,
                          masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(1,),
            in_specs=[vmem] * len(operands)
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((_N_BUF, block_s, width), rows.dtype),
                pltpu.VMEM((h_pad, _LANES), jnp.float32),
                pltpu.VMEM((h_pad, _LANES), jnp.float32),
                pltpu.VMEM((h_pad, rank), jnp.float32),
                pltpu.VMEM((_CHAINS, h_pad, _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((_N_BUF,))]),
        out_shape=jax.ShapeDtypeStruct((b, h_pad, rank), q_cat.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024),
    )(*scalars, *operands, rows)
    # a slot with no item never reached the kernel's write: its answer is
    # the softmax of one element, the appended row's latent
    alone = jnp.broadcast_to(row_new[:, None, :rank], (b, h, rank))
    return jnp.where((lengths > 0)[:, None, None], out[:, :h],
                     alone.astype(out.dtype)).astype(q_cat.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "block_s", "interpret"))
def decode_attention_kept(q_cat, rows, row_new, lengths, layer, keep,
                          keep_new, *, rank: int, block_s: int,
                          interpret: bool = False):
    """``decode_attention_stacked`` over the rows a learned selection
    kept (``keep`` [B, Smax], ``keep_new`` [B]: ops/dsa.py). A program of
    its own name, so that a device trace tells a selecting layer's
    kernel from one that reads every row."""
    return decode_attention_stacked.__wrapped__(
        q_cat, rows, row_new, lengths, layer, rank=rank, block_s=block_s,
        interpret=interpret, keep=keep, keep_new=keep_new)


@functools.partial(jax.jit, static_argnames=("rank", "block_s", "interpret"))
def decode_attention_ring(q_cat, rings, row_new, lengths, layer, *,
                          rank: int, block_s: int, interpret: bool = False):
    """``decode_attention_stacked`` over layer ``layer`` of stacked RINGS
    [L, B, W, width], position p at row p % W, for slots that have
    ``lengths`` [B] positions cached (0: a slot that must not be read):
    each attends to its new token and the W positions before it, which
    are the rows the ring holds, min(length, W) of them from row 0, in
    whatever order they lie (a softmax does not ask). The new token's
    row overwrites the oldest after the step. A program of its own
    name, as ``decode_attention_kept``."""
    return decode_attention_stacked.__wrapped__(
        q_cat, rings, row_new, jnp.minimum(lengths, rings.shape[2]), layer,
        rank=rank, block_s=block_s, interpret=interpret)


def decode_block(rows, rank: int) -> int | None:
    """The kernel's block where backend and shapes allow it, None where
    decode attention stays on the reference: not a TPU, a latent that is
    not whole lanes, a cache shorter than a block.
    ``GOFR_FLASH_INTERPRET=1`` runs the kernel interpreted anywhere."""
    from .flash import interpret_env, tpu_backend_ok

    smax = rows.shape[2]
    block_s = block_size(smax)
    if interpret_env():
        return block_s
    if rank % _LANES or smax % _LANES or not tpu_backend_ok():
        return None
    return block_s


@jax.named_scope("mla/decode_attn")
def decode_attention(q_cat, rows, row_new, lengths, layer, *, rank: int,
                     block_s: int | None):
    """Absorbed decode attention over layer ``layer`` of the stacked
    cache: the kernel where ``block_s`` (``decode_block``'s answer) says
    so, the reference over the layer's slice otherwise."""
    if block_s:
        from .flash import interpret_env

        return decode_attention_stacked(q_cat, rows, row_new, lengths, layer,
                                        rank=rank, block_s=block_s,
                                        interpret=interpret_env())
    layer_rows = jax.lax.dynamic_index_in_dim(rows, layer, 0, keepdims=False)
    return decode_attention_reference(q_cat, layer_rows, row_new, lengths,
                                      rank)


@jax.named_scope("mla/sparse_decode_attn")
def sparse_decode_attention(q_cat, rows, row_new, lengths, layer, keep,
                            keep_new, *, rank: int, block_s: int | None):
    """``decode_attention`` over the rows a learned selection kept."""
    if block_s:
        from .flash import interpret_env

        return decode_attention_kept(q_cat, rows, row_new, lengths, layer,
                                     keep, keep_new, rank=rank,
                                     block_s=block_s,
                                     interpret=interpret_env())
    layer_rows = jax.lax.dynamic_index_in_dim(rows, layer, 0, keepdims=False)
    return decode_attention_reference(q_cat, layer_rows, row_new, lengths,
                                      rank, keep, keep_new)


@jax.named_scope("mla/window_decode_attn")
def ring_decode_attention(q_cat, rings, row_new, lengths, layer, *,
                          rank: int, block_s: int | None):
    """``decode_attention`` over layer ``layer`` of stacked rings
    [L, B, W, width] (``decode_attention_ring`` says what a step reads)."""
    if block_s:
        from .flash import interpret_env

        return decode_attention_ring(q_cat, rings, row_new, lengths, layer,
                                     rank=rank, block_s=block_s,
                                     interpret=interpret_env())
    ring = jax.lax.dynamic_index_in_dim(rings, layer, 0, keepdims=False)
    return decode_attention_reference(
        q_cat, ring, row_new, jnp.minimum(lengths, ring.shape[1]), rank)
