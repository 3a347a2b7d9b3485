"""Pallas TPU flash-decode attention: read the live part of the KV cache,
once and in place, and write the step's row where the cache already is.

The decode step is bound by HBM traffic, and what the plain path
(ops.attention.decode_attention_appended) streams is mostly padding: it
attends over all ``Smax`` positions of every slot, and inside the layer
loop XLA first copies each layer's K and V out of the stacked cache
(PERF.md, Findings PR 25: 13 of a 27 ms step where 23% of the pool was
live). This kernel is handed the whole stacked cache
``[L, B, KV, Smax, hd]`` and the layer index, and fetches, per layer and
step, each slot's blocks from position 0 to its length rounded up to
``block_s`` — nothing past it, nothing for a slot of length 0, no copy.

One program a layer walks a work list of (slot, block) items that the
caller's lengths define (``_work_list``; scalar-prefetched), with a
double-buffered DMA straight from HBM: item w+1 is in flight while item
w is folded, across slot boundaries too, so a layer pays one DMA latency
and not one a slot. Same mathematics as the reference: int8 K/V with the
per-vector scales applied on the score and probability side, float32
scores and softmax statistics. The current token's k/v, not yet in the
cache (llama.decode_step defers the write to one post-loop
``append_rows``), is the recurrence's starting state (m = its score,
l = 1, acc = its value), which is the exact flash combination; a slot
of length 0 therefore returns its own value vector.

GQA: the cache is laid out a KV head at a time, so an item arrives as
[KV, block_s, hd] and a KV head's [block_s, hd] tile is contiguous, whole
(32, 128) int8 tiles high whatever the KV count. Each goes int8 ->
compute dtype in registers and into a [G, hd] x [hd, block_s] score
matmul and a [G, block_s] x [block_s, hd] value matmul, with its scale
row [1, block_s] from the scales' own [.., KV, Smax] order.

W query positions a slot (``flash_decode_block``; a family that denoises
a block of positions as a whole, models/sdar.py): the same kernel with
the KV head's tile W x G query rows high, W times the arithmetic a
fetched byte. The W new rows, which every one of them sees, are folded
in jnp (W scores a query row) and handed over as the recurrence's first
element, their log-sum-exp as m, l = 1 and their softmax-weighed values
as acc, which is what the one new row is to W = 1; W = 1 itself is
``flash_decode_stacked``, whose program this branch leaves as it was.

History, for whoever wants another A/B: v1 looped KV heads over 4-row
matmuls on sub-tile slices and lost 1.8x to XLA; v2/v3 expanded q
block-diagonally to one dense [H, KV*hd] matmul a tile on a
(slot x 128-block) grid with a clamped index map, and lost its one chip
A/B to the XLA path (a capture from before PERF_LEDGER.jsonl) for
three reasons none of which was the tile geometry: it was handed the
scan's per-layer slice, so the layer copy stayed in front of it; its
grid was 640 steps a layer, most of them skipped blocks that still paid
the step; and it was given frozen cursors, so it streamed dead slots.
Until PR 31 the cache was [L, B, Smax, KV, hd], positions and KV heads
interleaved row by row in a tile: the kernel converted the whole int8
tile to float32, stored it to a staging buffer and read each KV head
back with a stride of KV, about 1,000 vector stores and 1,000 strided
loads an item where the item's DMA needs 0.64 us (3.7 ms a step at
batch-sat's lengths where the bytes need 2.0; taking the tile apart by
byte instead, a shift pair on 32-bit words, was exact and 3x slower
still, 13.3 against 4.7 ms, PERF.md Findings PR 25). The staging buffer,
the strided read and the refusal of fewer than four int8 KV heads a
chip all went with the layout (PERF.md, Findings PR 31).

The step's write (``append_rows``, below the attention): the row every
layer and KV head made goes into the cache in place, a slot's tiles
around its cursor fetched, merged and written back, and with it, since
PR 48, an int8 cache's two scales: the slot's [L, KV, 128] lane tile of
each float32 table [L, B, KV, Smax] rides the same three buffers, the
step's scale put on the cursor's lane. Two readings led there: XLA's
scatter into [.., KV, Smax] costs two layout copies of a table, 0.6 ms
each a step at 32 x 40 x 8 x 2,048, and the select over both whole
tables that replaced it 0.5 ms (336 MB to change 20,480 values; PERF.md,
Findings PR 25 and PR 48). The select is still the write of the scales
where the kernels do not run (llama.write_rows).

Sharding: a pallas_call is opaque to the GSPMD partitioner, so on a mesh
the kernels run under ``shard_map`` over the tp (and data) axes: every
device walks its local [KV/tp] head shard of the stacked cache, no
collective inside attention. The reference stays the path for shapes
and backends the kernel cannot take (``decode_attention_auto``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF

_LANES = 128
_SUBLANES = 8


def block_size(smax: int) -> int:
    """Cache positions a work item covers: 256 where the cache allows.
    Larger blocks amortise an item's fixed cost (one chain of convert,
    matmul, softmax, matmul that nothing overlaps), smaller ones fetch
    less past a slot's length (half a block a live slot on average). On
    the v5e, 32 layers of Mistral-7B's attention at 40 slots x 2,048
    took 4.0 / 2.7 / 3.1 ms at 128 / 256 / 512 with a quarter of the
    pool live, 1.1 / 0.9 / 1.0 ms with 14 slots busy, and 12.4 / 7.6 /
    7.6 ms with all of it live, 5.4 GB that the chip streams in 6.6
    (PERF.md, Findings PR 31; before the cache was laid out a KV head
    at a time: 4.2 / 1.2 / 11.8 at 256 on the same lengths). With two
    items in flight and not three (``_KV_BUF``) the same three read
    3.4 / 1.0 / 9.8 at 256: an item's DMA takes 0.64 us and its latency
    is not hidden behind one item's fold; a fourth buffer adds nothing.
    ``ops.mla``'s kernel fetches by this block too (its item is one
    [256, 640] bfloat16 tile, 0.40 us of DMA) but keeps buffers and a
    loop of its own: four items a trip and eight more in flight, 3.31
    -> 1.91 ms for nine layers at 128 slots x 2,048 with 36% of the
    pool live, where three buffers alone read 2.88 (ops/mla.py; PERF.md,
    Findings PR 44)."""
    from .flash import fit_block

    return fit_block(smax, 256)


def _work_list(lengths, smax: int, block_s: int):
    """(item count [1], slot of each item [W], block of each item [W]):
    slot b contributes ceil(lengths[b] / block_s) items, in slot order.
    W = B * Smax / block_s is the static bound."""
    b = lengths.shape[0]
    per_slot = smax // block_s
    nblk = (lengths + block_s - 1) // block_s                # [B]
    ends = jnp.cumsum(nblk)
    w = jnp.arange(b * per_slot, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(ends[None, :] <= w[:, None], axis=1),
                       b - 1).astype(jnp.int32)
    blk = w - (ends - nblk)[slot]
    return ends[-1:].astype(jnp.int32), slot, blk.astype(jnp.int32)


_KV_BUF = 3    # items w+1 and w+2 in flight while item w is folded: see
#                block_size (ops.mla has a buffer count of its own)


def _decode_kernel(*refs, block_s: int, n_kv: int, quant: bool,
                   ring: bool = False, window: bool = False):
    """The whole layer: walk the work list, fold each item. ``window``:
    a slot brings W query positions (its group's rows W times over) and
    W new rows they all see; what those give among themselves arrives
    folded, as the recurrence's first element (``flash_decode_block``),
    in the places of the one new row's k and v."""
    layer_ref, n_ref, slot_ref, blk_ref, len_ref = refs[:5]
    if ring:      # one more scalar a slot: the row it must not read
        skip_ref, refs = refs[5], refs[:5] + refs[6:]
    q_ref, kn_ref, vn_ref, k_hbm, v_hbm = refs[5:10]
    if quant:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf = refs[10:17]
        m_ref, l_ref, acc_ref, sem = refs[17:]
    else:
        o_ref, kbuf, vbuf = refs[10:13]
        m_ref, l_ref, acc_ref, sem = refs[13:]
    layer = layer_ref[0]
    n = n_ref[0]
    cdt = q_ref.dtype

    def copies(w, buf):
        slot = slot_ref[w]
        here = pl.ds(pl.multiple_of(blk_ref[w] * block_s, block_s), block_s)
        cs = [pltpu.make_async_copy(k_hbm.at[layer, slot, :, here],
                                    kbuf.at[buf], sem.at[0, buf]),
              pltpu.make_async_copy(v_hbm.at[layer, slot, :, here],
                                    vbuf.at[buf], sem.at[1, buf])]
        if quant:
            cs += [pltpu.make_async_copy(ks_hbm.at[layer, slot, :, here],
                                         ksbuf.at[buf], sem.at[2, buf]),
                   pltpu.make_async_copy(vs_hbm.at[layer, slot, :, here],
                                         vsbuf.at[buf], sem.at[3, buf])]
        return cs

    for ahead in range(_KV_BUF - 1):
        @pl.when(n > ahead)
        def _first():
            for c in copies(ahead, ahead):
                c.start()

    def item(w, _):
        buf = w % _KV_BUF
        ahead = w + _KV_BUF - 1         # into the buffer item w-1 left

        @pl.when(ahead < n)
        def _next():
            for c in copies(ahead, ahead % _KV_BUF):
                c.start()

        for c in copies(w, buf):
            c.wait()
        slot = slot_ref[w]
        blk = blk_ref[w]
        length = len_ref[slot]

        @pl.when(blk == 0)
        def _init():
            if window:
                # the block within itself: its scores' log-sum-exp on
                # every lane, and its values weighed by their softmax
                m_ref[...] = kn_ref[slot]
                l_ref[...] = jnp.ones_like(l_ref)
                acc_ref[...] = vn_ref[slot]
                return
            # the appended token is the recurrence's first element
            s_new = jnp.sum(q_ref[slot].astype(jnp.float32)
                            * kn_ref[slot].astype(jnp.float32),
                            axis=-1, keepdims=True)         # [KV, Gp, 1]
            m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
            acc_ref[...] = vn_ref[slot].astype(jnp.float32)

        pos = blk * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        live = pos < length                                  # [1, BS]
        if ring:
            live &= pos != skip_ref[slot]
        # the KV heads' chains are independent: all score matmuls, one
        # softmax update over [KV, Gp, BS], all value matmuls
        q_all = q_ref[slot]                                  # [KV, Gp, D]
        scores = []
        for kv in range(n_kv):
            s = jax.lax.dot_general(
                q_all[kv], kbuf[buf, kv].astype(cdt),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [Gp, BS]
            if quant:
                s = s * ksbuf[buf, kv:kv + 1, :]
            scores.append(jnp.where(live, s, NEG_INF))
        s = jnp.stack(scores)                                # [KV, Gp, BS]
        m_prev = m_ref[:, :, :1]                             # [KV, Gp, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = []
        for kv in range(n_kv):
            p_kv = p[kv]
            if quant:
                # where, not a product alone: what lies past the length
                # in a block is not the slot's to read, whatever it holds
                p_kv = jnp.where(live, p_kv * vsbuf[buf, kv:kv + 1, :], 0.0)
            pv.append(jax.lax.dot_general(
                p_kv.astype(cdt), vbuf[buf, kv].astype(cdt),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))         # [Gp, D]
        acc_ref[...] = acc_ref[...] * corr + jnp.stack(pv)

        @pl.when((blk + 1) * block_s >= length)
        def _done():
            o_ref[slot] = acc_ref[...] / l_ref[:, :, :1]

    jax.lax.fori_loop(0, n, item, None)


def _walk(q_rows, first_m, first_acc, cache_k, cache_v, work, lengths,
          layer, k_scale, v_scale, skip=(), *, block_s: int,
          interpret: bool, **kernel):
    """The kernel's call over layer ``layer``: ``q_rows`` [B, KV, Gp, D]
    the scaled query rows of each KV head, ``first_m`` / ``first_acc`` the
    operands of the recurrence's first element (one new row's k and v a
    query row, or a block's folded own rows: ``_decode_kernel``),
    ``work`` the slots' work list (``_work_list``), ``lengths`` [B]
    int32, ``skip`` () or (the ring's row not to read,).
    Returns the attention [B, KV, Gp, D] float32; a slot of length 0 is
    not written."""
    b, n_kv, g_pad, d = q_rows.shape
    quant = k_scale is not None
    n, slot, blk = work
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q_rows, first_m, first_acc, cache_k, cache_v]
    in_specs = [vmem, vmem, vmem, hbm, hbm]
    scratch = [pltpu.VMEM((_KV_BUF, n_kv, block_s, d), cache_k.dtype),
               pltpu.VMEM((_KV_BUF, n_kv, block_s, d), cache_v.dtype)]
    if quant:
        # a [KV, BS] tile has a KV head's scales in one row, positions
        # along lanes like its scores
        operands += [k_scale, v_scale]
        in_specs += [hbm, hbm]
        scratch += [pltpu.VMEM((_KV_BUF, n_kv, block_s), jnp.float32),
                    pltpu.VMEM((_KV_BUF, n_kv, block_s), jnp.float32)]
    scratch += [pltpu.VMEM((n_kv, g_pad, _LANES), jnp.float32),
                pltpu.VMEM((n_kv, g_pad, _LANES), jnp.float32),
                pltpu.VMEM((n_kv, g_pad, d), jnp.float32),
                pltpu.SemaphoreType.DMA((4, _KV_BUF))]
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, n_kv=n_kv,
                          quant=quant, **kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(skip), grid=(1,), in_specs=in_specs,
            out_specs=vmem, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g_pad, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), n, slot, blk, lengths,
      *skip, *operands)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "scale"))
def flash_decode_stacked(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                         k_scale=None, v_scale=None, *, block_s: int,
                         interpret: bool = False, exclude=None,
                         scale: float | None = None) -> jnp.ndarray:
    """decode_attention_appended over layer ``layer`` of the stacked
    cache, reading only what ``lengths`` says is live.

    q: [B, 1, H, D]; cache_k/cache_v: [L, B, KV, Smax, D] (int8 with
    scales [L, B, KV, Smax], or dense); k_new/v_new: [B, 1, KV, D];
    lengths [B] EXCLUDING the current token, 0 for a slot whose cache
    must not be read; layer: int32 scalar; exclude: [B] int32 or None,
    a row of each slot that is not read though it lies below its length
    (``ring_rows``); scale: the softmax scale where it is not D^-1/2.
    Returns [B, 1, H, D] in q.dtype.

    A head of 64 values comes here PAIRED (ops.attention.pair_rows): the
    cache [L, B, KV/2, Smax, 128] two KV heads a row, q with zeros in the
    half that is not its KV head's, ``scale`` 64^-1/2; a KV head's group
    of four is then a pair's group of eight, one float32 sublane tile,
    and the kernel below is the one that 128-wide heads run."""
    b, _, h, d = q.shape
    n_kv, smax = cache_k.shape[2], cache_k.shape[3]
    g = h // n_kv
    g_pad = -(-g // _SUBLANES) * _SUBLANES
    lengths = lengths.astype(jnp.int32)
    skip = () if exclude is None else (exclude.astype(jnp.int32),)
    work = _work_list(lengths, smax, block_s)
    qg = (q[:, 0] * (scale or d ** -0.5)).reshape(b, n_kv, g, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))

    def per_group(x):  # [B, 1, KV, D] -> [B, KV, Gp, D], a row a q head
        return jnp.broadcast_to(x[:, 0, :, None, :], (b, n_kv, g_pad, d))

    out = _walk(qg, per_group(k_new), per_group(v_new), cache_k, cache_v,
                work, lengths, layer, k_scale, v_scale, skip,
                block_s=block_s, interpret=interpret,
                **({"ring": True} if skip else {}))
    out = out[:, :, :g].reshape(b, h, d)
    # a slot with no item never reached the kernel's write: its answer is
    # the softmax of one element, the appended token's value
    v_rep = jnp.repeat(v_new[:, 0], g, axis=1).astype(jnp.float32)
    out = jnp.where((lengths > 0)[:, None, None], out, v_rep)
    return out.astype(q.dtype).reshape(b, 1, h, d)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "scale"))
def flash_decode_block(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                       k_scale=None, v_scale=None, *, block_s: int,
                       interpret: bool = False,
                       scale: float | None = None) -> jnp.ndarray:
    """``flash_decode_stacked`` for W query positions a slot that are not
    in the cache yet and see each other BOTH ways (a block that is
    denoised as a whole): each attends the slot's ``lengths`` cached rows
    and all W new ones.

    q: [B, W, H, D]; k_new/v_new: [B, W, KV, D]; the rest as
    ``flash_decode_stacked``. Returns [B, W, H, D] in q.dtype.

    The same kernel: a KV head's tile is its group's rows W times over
    ([W G, D] against each fetched block of K and V: W times the
    arithmetic a fetched byte), and what the W new rows give among
    themselves, W scores a query row, is folded here in jnp and handed
    over as the recurrence's first element: m = their log-sum-exp, l = 1,
    acc = their values weighed by their softmax. A slot of length 0
    never reaches the kernel's write and keeps that."""
    b, w, h, d = q.shape
    n_kv, smax = cache_k.shape[2], cache_k.shape[3]
    g = h // n_kv
    rows = w * g
    g_pad = -(-rows // _SUBLANES) * _SUBLANES
    lengths = lengths.astype(jnp.int32)
    work = _work_list(lengths, smax, block_s)
    # [B, W, KV, G, D] -> [B, KV, W G, D]: a KV head's rows together
    qg = (q * (scale or d ** -0.5)).reshape(b, w, n_kv, g, d)
    qg = jnp.moveaxis(qg, 1, 2).reshape(b, n_kv, rows, d)
    s_new = jnp.einsum("bkrd,btkd->bkrt", qg, k_new,
                       preferred_element_type=jnp.float32)  # [B,KV,WG,W]
    m0 = jax.nn.logsumexp(s_new, axis=-1, keepdims=True)
    acc0 = jnp.einsum("bkrt,btkd->bkrd", jnp.exp(s_new - m0),
                      v_new.astype(jnp.float32))
    pad = ((0, 0), (0, 0), (0, g_pad - rows), (0, 0))
    qg, acc0 = jnp.pad(qg, pad), jnp.pad(acc0, pad)
    m0 = jnp.broadcast_to(jnp.pad(m0, pad), (b, n_kv, g_pad, _LANES))
    out = _walk(qg, m0, acc0, cache_k, cache_v, work, lengths, layer,
                k_scale, v_scale, block_s=block_s, interpret=interpret,
                window=True)
    out = jnp.where((lengths > 0)[:, None, None, None], out, acc0)
    out = out[:, :, :rows].reshape(b, n_kv, w, g, d)
    return jnp.moveaxis(out, 2, 1).reshape(b, w, h, d).astype(q.dtype)


def ring_rows(lengths, rows: int):
    """What a decode step reads of a ring of ``rows`` rows, position p at
    row p % rows, where a slot has ``lengths`` [B] positions cached and
    attends to the last ``rows`` positions, its new token among them:
    (rows live [B], the one row among them it must not read [B]). A ring
    that has wrapped holds the last ``rows`` positions, one more than
    the window has room for beside the new token: the oldest, at the row
    the new token's position falls on and this step overwrites."""
    lengths = lengths.astype(jnp.int32)
    return jnp.minimum(lengths, rows), lengths % rows


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode_ring(q, ring_k, ring_v, k_new, v_new, lengths, layer, *,
                      block_s: int, interpret: bool = False) -> jnp.ndarray:
    """``flash_decode_stacked`` over layer ``layer`` of stacked RINGS
    [L, B, KV, W, D] for slots that have ``lengths`` [B] positions cached
    (0: a slot that must not be read): each attends to its new token and
    the W - 1 positions before it (``ring_rows``). A program of its own
    name, so that a device trace tells a window layer's kernel from a
    full layer's."""
    live, skip = ring_rows(lengths, ring_k.shape[3])
    return flash_decode_stacked.__wrapped__(
        q, ring_k, ring_v, k_new, v_new, live, layer, block_s=block_s,
        interpret=interpret, exclude=skip)


def flash_decode_sharded(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                         k_scale=None, v_scale=None, *, mesh,
                         batch_axes=(), head_axis=None, block_s: int,
                         interpret: bool = False,
                         scale: float | None = None) -> jnp.ndarray:
    """shard_map'd flash_decode_stacked: each device walks its local
    [KV/tp] head shard of the stacked cache (and its local batch shard on
    data-parallel meshes). The specs mirror parallel.kv_cache_specs, so
    GSPMD never gathers the cache at the shard_map boundary; no
    collective inside attention (the o-proj psum downstream is
    unchanged). check_vma off: pallas_call has no replication rule."""
    from jax.sharding import PartitionSpec as P

    qspec, cspec, sspec, lspec = _shard_specs(batch_axes, head_axis)
    specs = (qspec, cspec, cspec, qspec, qspec, lspec, P())
    args = (q, cache_k, cache_v, k_new, v_new, lengths, layer)
    if k_scale is not None:
        specs += (sspec, sspec)
        args += (k_scale, v_scale)
    run = functools.partial(flash_decode_stacked, block_s=block_s,
                            interpret=interpret, scale=scale)
    return jax.shard_map(run, mesh=mesh, in_specs=specs, out_specs=qspec,
                         check_vma=False)(*args)


def _shard_specs(batch_axes, head_axis):
    """PartitionSpecs of the kernels' shard_map: q/k_new/v_new
    [B, 1, heads, D]; the caches [L, B, KV, Smax, D]; the scales
    [L, B, KV, Smax] and a step's rows [L, B, KV, D]; lengths [B]. They
    mirror parallel.kv_cache_specs, so GSPMD never gathers the cache at
    the shard_map boundary."""
    from jax.sharding import PartitionSpec as P

    bax = tuple(batch_axes) or None
    return (P(bax, None, head_axis, None),
            P(None, bax, head_axis, None, None),
            P(None, bax, head_axis, None), P(bax))


def kernel_block(n_heads: int, cache_k, mesh=None) -> int | None:
    """The kernels' block size where backend and shapes allow them, None
    where decode attention and the step's write stay on the reference:
    not a TPU, a cache row that is not whole lanes (a head of 64 is
    cached two KV heads a row and is: ops.attention.pair_rows), a cache no
    lane-aligned block divides, or a tp that would split a KV head. The
    local KV-head count does not matter: a head's (Smax, hd) tiles are
    whole at any count. ``GOFR_FLASH_INTERPRET=1`` runs the kernels
    interpreted on any backend and shape."""
    from .flash import interpret_env, tpu_backend_ok

    _, b, n_kv, smax, d = cache_k.shape
    if mesh is not None:
        from ..parallel.sharding import attention_shard_axes

        batch_axes, head_axis = attention_shard_axes(mesh, b, n_heads, n_kv)
        if head_axis is None and not batch_axes:
            return None
    block_s = block_size(smax)
    if interpret_env():
        return block_s
    if d % _LANES or smax % _LANES or n_heads % n_kv or not tpu_backend_ok():
        return None
    return block_s


@jax.named_scope("flash_decode_block")
def block_attention_auto(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                         k_scale=None, v_scale=None, *, block_s: int,
                         scale: float | None = None) -> jnp.ndarray:
    """``flash_decode_block`` over layer ``layer`` of the stacked cache
    (one device: the family that denoises blocks refuses a mesh).
    ``block_s``: ``kernel_block``'s answer for these shapes; the caller
    takes ``ops.attention.window_attention_appended`` where it is None."""
    from .flash import interpret_env

    return flash_decode_block(q, cache_k, cache_v, k_new, v_new, lengths,
                              layer, k_scale, v_scale, block_s=block_s,
                              interpret=interpret_env(), scale=scale)


@jax.named_scope("flash_decode")
def decode_attention_auto(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                          k_scale=None, v_scale=None, *, block_s: int,
                          mesh=None, scale: float | None = None) -> jnp.ndarray:
    """The kernel over layer ``layer`` of the stacked cache, under
    shard_map where ``mesh`` shards heads or batch. ``block_s`` is
    ``kernel_block``'s answer for these shapes: the caller asks first,
    and takes ops.attention.decode_attention_appended where it is None.
    ``scale``: the softmax scale where it is not D^-1/2 (paired heads)."""
    from .flash import interpret_env

    interpret = interpret_env()
    if mesh is not None:
        from ..parallel.sharding import attention_shard_axes

        batch_axes, head_axis = attention_shard_axes(
            mesh, q.shape[0], q.shape[2], cache_k.shape[2])
        return flash_decode_sharded(
            q, cache_k, cache_v, k_new, v_new, lengths, layer, k_scale,
            v_scale, mesh=mesh, batch_axes=batch_axes, head_axis=head_axis,
            block_s=block_s, interpret=interpret, scale=scale)
    return flash_decode_stacked(q, cache_k, cache_v, k_new, v_new, lengths,
                                layer, k_scale, v_scale, block_s=block_s,
                                interpret=interpret, scale=scale)


# -- the step's write ---------------------------------------------------------

_APPEND_BUF = 3    # slot b+1 read and slot b-1 written while slot b merges
# what one visit's buffers and the step's rows may take of VMEM, under
# the 64 MiB the kernel is given: Mistral's 32 tables x 8 KV heads x 40
# slots take 17.8 MB whole; 192 x 16 (a stack run four times) would take
# 110 MB and go a share of 48 at a time, 27.5 MB (``append_tables``)
_APPEND_VMEM = 32 * 1024 * 1024


def append_tables(n_l: int, b: int, n_kv: int, rows: int, d: int,
                  item: int, lanes: int) -> int:
    """Tables one call of the append kernel visits: all ``n_l`` where
    their tiles fit ``_APPEND_VMEM``, else the largest divisor of
    ``n_l`` that does, the calls one after another over the table axis,
    each in place on what the one before it left. A table costs its K
    and V tiles a buffer ([KV, rows, d]), the step's rows as 32-bit
    words ([B, KV, d]) and, where the cache has scales (``lanes`` > 0),
    their lane tiles a buffer and the step's a slot a lane."""
    a_table = 2 * n_kv * (_APPEND_BUF * rows * d * item + b * d * 4)
    if lanes:
        a_table += 2 * n_kv * lanes * 4 * (_APPEND_BUF + -(-b // lanes))
    fit = max(_APPEND_VMEM // a_table, 1)
    return max(n for n in range(1, n_l + 1) if n_l % n == 0 and n <= fit)


def _append_kernel(pos_ref, keep_ref, *refs, rows: int, per: int,
                   tables=slice(None)):
    """Every slot: fetch the ``rows`` positions around its cursor, all
    layers (or the share of them ``tables`` names: ``append_tables``)
    and KV heads, put the new row's bits into its 32-bit words,
    write the tiles back. ``refs``: what the step made, the tables it
    goes into, the same as outputs, a buffer each, the semaphores; the
    tables are K and V or, for a quantized cache, their two scale
    tables too, whose tile, the lanes around the cursor, makes the same
    trip: the step's scale goes to the cursor's lane."""
    *refs, sem = refs
    n = len(refs) // 4                   # tables: 2, or 4 with the scales'
    news, _, hbm, bufs = (refs[i * n:(i + 1) * n] for i in range(4))
    kbuf, vbuf, *sbufs = bufs            # (the outputs alias the inputs)
    nb = pos_ref.shape[0]
    n_kv, d = kbuf.shape[2], kbuf.shape[4]
    lanes = sbufs[0].shape[3] if sbufs else 0
    sub = jax.lax.broadcasted_iota(jnp.int32, (1, rows // per, d), 1)
    if sbufs:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, lanes), 2)

    def dmas(b, read: bool):
        buf = b % _APPEND_BUF
        here = pl.ds(pl.multiple_of(pos_ref[b] // rows * rows, rows), rows)
        tile = pl.ds(pl.multiple_of(pos_ref[b] // lanes * lanes, lanes),
                     lanes) if lanes else None
        out = []
        for i, scratch in enumerate(bufs):
            pair = (hbm[i].at[tables, b, :, here if i < 2 else tile],
                    scratch.at[buf])
            out.append(pltpu.make_async_copy(
                *(pair if read else pair[::-1]), sem.at[int(read), i, buf]))
        return out

    for c in dmas(0, True):
        c.start()

    def slot(b, _):
        @pl.when(b >= 2)
        def _free():       # slot b-2 wrote from the buffer slot b+1 reads to
            for c in dmas(b - 2, False):
                c.wait()

        @pl.when(b + 1 < nb)
        def _next():
            for c in dmas(b + 1, True):
                c.start()

        for c in dmas(b, True):
            c.wait()
        buf = b % _APPEND_BUF
        word = (pos_ref[b] % rows) // per
        keep = keep_ref[b]
        for scratch, new_ref in zip((kbuf, vbuf), news):
            for kv in range(n_kv):
                old = pltpu.bitcast(scratch[buf, :, kv], jnp.int32)
                new = new_ref[b, :, kv:kv + 1, :]            # [L, 1, D]
                scratch[buf, :, kv] = pltpu.bitcast(
                    jnp.where(sub == word, (old & keep) | new, old),
                    scratch.dtype)
        if sbufs:
            # the step's scales come a slot a lane, [B / lanes, L, KV,
            # lanes]: slot b's turned to its cursor's lane; a dropped row
            # (nothing of its word kept out) drops its scales
            at = pos_ref[b] % lanes
            hit = (lane == at) & (keep != -1)
            turn = (at - b % lanes + lanes) % lanes
            for scratch, new_ref in zip(sbufs, news[2:]):
                new = pltpu.roll(new_ref[b // lanes], turn, 2)
                scratch[buf] = jnp.where(hit, new, scratch[buf])
        for c in dmas(b, False):
            c.start()

    jax.lax.fori_loop(0, nb, slot, None)
    for b in range(max(nb - 2, 0), nb):
        for c in dmas(b, False):
            c.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_rows_stacked(cache_k, cache_v, k_rows, v_rows, positions,
                        k_scale=None, v_scale=None, k_scale_rows=None,
                        v_scale_rows=None, *, interpret: bool = False):
    """cache[:, b, :, positions[b]] = rows[:, b], every layer and KV
    head, in place on donated caches, and for a quantized cache
    scale[:, b, :, positions[b]] = scale_rows[:, b] in place on the two
    donated scale tables; a position at or past ``Smax`` is dropped like
    a scatter's, row and scales.

    XLA cannot do this write where the cache is: a position is one row
    of the (Smax, hd) tiles, a quarter of a 32-bit sublane at int8, and
    both its scatter and its dynamic_update_slice first copy the whole
    cache to a layout with the written axis major and then back (compiled
    for the v5e: two copies of s8[32,40,8,2048,128] a step). Here a slot
    reads the 8 sublanes of 32-bit words around its cursor ([L, KV, 32,
    hd] at int8, 1 MB), merges the row's bits into its word by mask, and
    writes them back; three buffers keep a read, a merge and a write in
    flight across slots.

    The scales [L, B, KV, Smax] float32 have the position on lanes, and
    XLA has no in-place write there either: a scatter costs two layout
    copies of a table, 0.6 ms each a step at 32 x 40 x 8 x 2,048, and
    the select over both whole tables that stood in for it until PR 48
    moved 336 MB to change 20,480 values, 0.5 ms of Mistral's 13 ms step
    (PERF.md, Findings PR 25 and PR 48). Here the slot's lane tile of
    each table ([L, KV, 128] around the cursor, 131 KB at eight KV
    heads) rides the rows' three buffers: read, the step's scale put on
    the cursor's lane, written back, 21 MB a step. The step's scales
    arrive a slot a lane ([L, KV, 128 slots], one tile a table in VMEM),
    and a lane rotation brings slot b's to its cursor's lane.

    Where a slot's tiles of ALL the tables would not fit the kernel's
    buffers (192 tables of 16 KV heads, a stack run four times: 12.6 MB a
    buffer, 75 MB over K, V and three buffers), the visit is cut over
    the table axis: the same kernel a share of the tables at a time
    (``append_tables``: 48 there, a pass), each call in place on what the
    one before it left.

    cache_k/cache_v: [L, B, KV, Smax, D]; k_rows/v_rows: [L, B, KV, D] in
    the caches' dtype; positions: [B] int32; k_scale/v_scale:
    [L, B, KV, Smax] float32 with k_scale_rows/v_scale_rows [L, B, KV],
    or None. Returns (cache_k, cache_v, k_scale, v_scale).
    """
    from .flash import fit_block

    n_l, b, n_kv, smax, d = cache_k.shape
    item = cache_k.dtype.itemsize
    per, bits = 4 // item, 8 * item
    rows = fit_block(smax, _SUBLANES * per)
    if rows % per:
        raise ValueError(f"append_rows: Smax {smax} is not whole 32-bit "
                         f"words of {cache_k.dtype} positions")
    positions = positions.astype(jnp.int32)
    ok = positions < smax
    pos = jnp.minimum(positions, smax - 1)
    shift = ((pos % per) * bits).astype(jnp.uint32)
    mask = jnp.where(ok, jnp.uint32((1 << bits) - 1) << shift, 0)
    as_i32 = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.int32)

    def words(x):    # [L, B, KV, D] -> [B, L, KV, D] int32, bits in place
        u = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{bits}"))
        u = jnp.moveaxis(u, 1, 0).astype(jnp.uint32)
        return as_i32((u << shift[:, None, None, None])
                      & mask[:, None, None, None])

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    keep = as_i32(~mask)
    news, tables, lanes = [words(k_rows), words(v_rows)], [], 0
    if k_scale is not None:
        lanes = fit_block(smax, _LANES)
        pad = -b % lanes

        def by_lane(x):    # [L, B, KV] -> [B / lanes, L, KV, lanes]
            x = jnp.pad(jnp.moveaxis(x, 1, 2), ((0, 0), (0, 0), (0, pad)))
            return jnp.moveaxis(
                x.reshape(n_l, n_kv, (b + pad) // lanes, lanes), 2, 0)

        news += [by_lane(k_scale_rows), by_lane(v_scale_rows)]
        tables = [k_scale, v_scale]
    caches = [cache_k, cache_v, *tables]
    first = 2 + len(news)                # after the two scalar operands
    # all the tables in one visit a slot, or a share of them a call
    share = append_tables(n_l, b, n_kv, rows, d, item, lanes)
    tile = pltpu.VMEM((_APPEND_BUF, share, n_kv, rows, d), cache_k.dtype)
    tiles = [pltpu.VMEM((_APPEND_BUF, share, n_kv, lanes), jnp.float32)] \
        * len(tables)
    for at in range(0, n_l, share):
        part = {} if share == n_l else {"tables": pl.ds(at, share)}
        mine = news if share == n_l else [x[:, at:at + share] for x in news]
        caches = pl.pallas_call(
            functools.partial(_append_kernel, rows=rows, per=per, **part),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(1,),
                in_specs=[vmem] * len(news) + [hbm] * len(caches),
                out_specs=[hbm] * len(caches),
                scratch_shapes=[tile, tile, *tiles, pltpu.SemaphoreType.DMA(
                    (2, len(caches), _APPEND_BUF))]),
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in caches],
            input_output_aliases={first + i: i for i in range(len(caches))},
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 * 1024 * 1024),
        )(pos, keep, *mine, *caches)
    return (*caches, None, None)[:4]


def append_rows_sharded(cache_k, cache_v, k_rows, v_rows, positions,
                        k_scale=None, v_scale=None, k_scale_rows=None,
                        v_scale_rows=None, *, mesh, n_heads: int,
                        interpret: bool = False):
    """shard_map'd append_rows_stacked: each device writes its local KV
    heads' (and slots') rows and scales, the caches and the scale tables
    staying where parallel.kv_cache_specs placed them."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import attention_shard_axes

    _, cspec, rspec, lspec = _shard_specs(*attention_shard_axes(
        mesh, cache_k.shape[1], n_heads, cache_k.shape[2]))
    specs, args = (cspec, cspec, rspec, rspec, lspec), ()
    out_specs = (cspec, cspec, None, None)
    if k_scale is not None:    # the tables like a step's rows, [L, B, KV, .]
        specs += (rspec, rspec, P(*rspec[:3]), P(*rspec[:3]))
        args = (k_scale, v_scale, k_scale_rows, v_scale_rows)
        out_specs = (cspec, cspec, rspec, rspec)
    run = functools.partial(append_rows_stacked, interpret=interpret)
    return jax.shard_map(
        run, mesh=mesh, in_specs=specs, out_specs=out_specs,
        check_vma=False)(cache_k, cache_v, k_rows, v_rows, positions, *args)


@jax.named_scope("kv_append")
def append_rows(cache_k, cache_v, k_rows, v_rows, positions, k_scale=None,
                v_scale=None, k_scale_rows=None, v_scale_rows=None, *,
                n_heads: int, mesh=None):
    """The step's rows into the stacked cache, and a quantized cache's
    scales into its scale tables, in place, under shard_map where
    ``mesh`` shards heads or batch: the caller has asked ``kernel_block``
    for these shapes, and takes XLA's scatter (and a select for the
    scales) where it is None. Returns (cache_k, cache_v, k_scale,
    v_scale), the last two None for a cache without scales."""
    from .flash import interpret_env

    args = (cache_k, cache_v, k_rows, v_rows, positions, k_scale, v_scale,
            k_scale_rows, v_scale_rows)
    if mesh is not None:
        return append_rows_sharded(*args, mesh=mesh, n_heads=n_heads,
                                   interpret=interpret_env())
    return append_rows_stacked(*args, interpret=interpret_env())
