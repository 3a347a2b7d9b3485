"""Pallas TPU flash-decode attention: read the live part of the KV cache,
once and in place.

The decode step is bound by HBM traffic, and what the plain path
(ops.attention.decode_attention_appended) streams is mostly padding: it
attends over all ``Smax`` positions of every slot, and inside the layer
loop XLA first copies each layer's K and V out of the stacked cache
(PERF.md, Findings PR 25: 13 of a 27 ms step where 23% of the pool was
live). This kernel is handed the whole stacked cache
``[L, B, Smax, KV, hd]`` and the layer index, and fetches, per layer and
step, each slot's blocks from position 0 to its length rounded up to
``block_s`` — nothing past it, nothing for a slot of length 0, no copy.

One program a layer walks a work list of (slot, block) items that the
caller's lengths define (``_work_list``; scalar-prefetched), with a
double-buffered DMA straight from HBM: item w+1 is in flight while item
w is folded, across slot boundaries too, so a layer pays one DMA latency
and not one a slot. Same mathematics as the reference: int8 K/V with the
per-vector scales applied on the score and probability side, float32
scores and softmax statistics. The current token's k/v, not yet in the
cache (llama.decode_step defers the write to one post-scan scatter), is
the recurrence's starting state (m = its score, l = 1, acc = its value),
which is the exact flash combination; a slot of length 0 therefore
returns its own value vector.

GQA: K and V tiles are [block_s, KV, hd] in the cache's own layout.
Each is viewed as [block_s*KV, hd] rows (free: the (KV, hd) tile is the
layout's own) and de-interleaved per KV head with a strided read, so
every KV head does a [G, hd] x [hd, block_s] score matmul and a
[G, block_s] x [block_s, hd] value matmul with its scale row
[1, block_s] in the layout the scales have in HBM ([.., KV, Smax]: XLA
stores ``[L, B, Smax, KV]`` float32 minor-to-major {2,3,1,0}, so the
transposed view costs nothing).

History, for whoever wants another A/B: v1 looped KV heads over 4-row
matmuls on sub-tile slices and lost 1.8x to XLA; v2/v3 expanded q
block-diagonally to one dense [H, KV*hd] matmul a tile on a
(slot x 128-block) grid with a clamped index map, and lost its one chip
A/B to the XLA path (a capture from before PERF_LEDGER.jsonl) for
three reasons none of which was the tile geometry: it was handed the
scan's per-layer slice, so the layer copy stayed in front of it; its
grid was 640 steps a layer, most of them skipped blocks that still paid
the step; and it was given frozen cursors, so it streamed dead slots.
Within this design, taking an int8 tile apart by byte (a shift pair on
the tile viewed as 32-bit words, no staging in float32, KV heads left
interleaved and masked in the scores) compiled and was exact but ran 3x
slower than the strided read (13.3 against 4.7 ms, PERF.md Findings
PR 25).

Sharding: a pallas_call is opaque to the GSPMD partitioner, so on a mesh
the kernel runs under ``shard_map`` over the tp (and data) axes: every
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
    took 4.4 / 3.7 / 4.7 ms at 128 / 256 / 512 with a quarter of the
    pool live, 1.7 / 1.5 / 1.8 ms with 14 slots busy, and 15.7 / 11.9 /
    12.0 ms with all of it live, where the reference and its layer copy
    take 13.6 (PERF.md, Findings PR 25)."""
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


_N_BUF = 2     # item w+1 in flight while item w is folded


def _decode_kernel(*refs, block_s: int, n_kv: int, quant: bool):
    """The whole layer: walk the work list, fold each item."""
    layer_ref, n_ref, slot_ref, blk_ref, len_ref = refs[:5]
    q_ref, kn_ref, vn_ref, k_hbm, v_hbm = refs[5:10]
    if quant:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf = refs[10:17]
        kf_ref, vf_ref, m_ref, l_ref, acc_ref, sem = refs[17:]
    else:
        o_ref, kbuf, vbuf = refs[10:13]
        kf_ref, vf_ref, m_ref, l_ref, acc_ref, sem = refs[13:]
    layer = layer_ref[0]
    n = n_ref[0]
    cdt = q_ref.dtype

    def copies(w, buf):
        slot = slot_ref[w]
        start = pl.multiple_of(blk_ref[w] * block_s, block_s)
        cs = [pltpu.make_async_copy(
                  k_hbm.at[layer, slot, pl.ds(start, block_s)],
                  kbuf.at[buf], sem.at[0, buf]),
              pltpu.make_async_copy(
                  v_hbm.at[layer, slot, pl.ds(start, block_s)],
                  vbuf.at[buf], sem.at[1, buf])]
        if quant:
            cs += [pltpu.make_async_copy(
                       ks_hbm.at[layer, slot, :, pl.ds(start, block_s)],
                       ksbuf.at[buf], sem.at[2, buf]),
                   pltpu.make_async_copy(
                       vs_hbm.at[layer, slot, :, pl.ds(start, block_s)],
                       vsbuf.at[buf], sem.at[3, buf])]
        return cs

    @pl.when(n > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def item(w, _):
        buf = w % _N_BUF

        @pl.when(w + 1 < n)
        def _next():
            for c in copies(w + 1, (w + 1) % _N_BUF):
                c.start()

        for c in copies(w, buf):
            c.wait()
        slot = slot_ref[w]
        blk = blk_ref[w]
        length = len_ref[slot]

        @pl.when(blk == 0)
        def _init():
            # the appended token is the recurrence's first element
            s_new = jnp.sum(q_ref[slot].astype(jnp.float32)
                            * kn_ref[slot].astype(jnp.float32),
                            axis=-1, keepdims=True)         # [KV, Gp, 1]
            m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
            acc_ref[...] = vn_ref[slot].astype(jnp.float32)

        # [BS, KV, D] -> rows (t, kv): the layout's own order. Upcast
        # once, then each KV head's rows are a strided read.
        kf_ref[...] = kbuf[buf].reshape(block_s * n_kv, -1).astype(
            jnp.float32)
        vf_ref[...] = vbuf[buf].reshape(block_s * n_kv, -1).astype(
            jnp.float32)
        pos = blk * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        live = pos < length                                  # [1, BS]
        # the KV heads' chains are independent: all score matmuls, one
        # softmax update over [KV, Gp, BS], all value matmuls
        q_all = q_ref[slot]                                  # [KV, Gp, D]
        scores = []
        for kv in range(n_kv):
            k_kv = kf_ref[pl.ds(kv, block_s, stride=n_kv), :].astype(cdt)
            s = jax.lax.dot_general(
                q_all[kv], k_kv,
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
            v_kv = vf_ref[pl.ds(kv, block_s, stride=n_kv), :].astype(cdt)
            pv.append(jax.lax.dot_general(
                p_kv.astype(cdt), v_kv,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))         # [Gp, D]
        acc_ref[...] = acc_ref[...] * corr + jnp.stack(pv)

        @pl.when((blk + 1) * block_s >= length)
        def _done():
            o_ref[slot] = acc_ref[...] / l_ref[:, :, :1]

    jax.lax.fori_loop(0, n, item, None)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode_stacked(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                         k_scale=None, v_scale=None, *, block_s: int,
                         interpret: bool = False) -> jnp.ndarray:
    """decode_attention_appended over layer ``layer`` of the stacked
    cache, reading only what ``lengths`` says is live.

    q: [B, 1, H, D]; cache_k/cache_v: [L, B, Smax, KV, D] (int8 with
    scales [L, B, Smax, KV], or dense); k_new/v_new: [B, 1, KV, D];
    lengths [B] EXCLUDING the current token, 0 for a slot whose cache
    must not be read; layer: int32 scalar. Returns [B, 1, H, D] in
    q.dtype."""
    b, _, h, d = q.shape
    smax, n_kv = cache_k.shape[2], cache_k.shape[3]
    g = h // n_kv
    g_pad = -(-g // _SUBLANES) * _SUBLANES
    quant = k_scale is not None
    lengths = lengths.astype(jnp.int32)
    n, slot, blk = _work_list(lengths, smax, block_s)
    qg = (q[:, 0] * (d ** -0.5)).reshape(b, n_kv, g, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    def per_group(x):  # [B, 1, KV, D] -> [B, KV, Gp, D], a row a q head
        return jnp.broadcast_to(x[:, 0, :, None, :], (b, n_kv, g_pad, d))

    operands = [qg, per_group(k_new), per_group(v_new), cache_k, cache_v]
    in_specs = [vmem, vmem, vmem, hbm, hbm]
    scratch = [pltpu.VMEM((_N_BUF, block_s, n_kv, d), cache_k.dtype),
               pltpu.VMEM((_N_BUF, block_s, n_kv, d), cache_v.dtype)]
    if quant:
        # [L, B, Smax, KV] -> [L, B, KV, Smax]: the order the scales have
        # in HBM already, and a [KV, BS] tile has a KV head's scales in
        # one row, positions along lanes like its scores
        operands += [jnp.swapaxes(k_scale, 2, 3), jnp.swapaxes(v_scale, 2, 3)]
        in_specs += [hbm, hbm]
        scratch += [pltpu.VMEM((_N_BUF, n_kv, block_s), jnp.float32),
                    pltpu.VMEM((_N_BUF, n_kv, block_s), jnp.float32)]
    scratch += [pltpu.VMEM((block_s * n_kv, d), jnp.float32),
                pltpu.VMEM((block_s * n_kv, d), jnp.float32),
                pltpu.VMEM((n_kv, g_pad, _LANES), jnp.float32),
                pltpu.VMEM((n_kv, g_pad, _LANES), jnp.float32),
                pltpu.VMEM((n_kv, g_pad, d), jnp.float32),
                pltpu.SemaphoreType.DMA((4, _N_BUF))]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, n_kv=n_kv,
                          quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1,), in_specs=in_specs,
            out_specs=vmem, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g_pad, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), n, slot, blk, lengths,
      *operands)
    out = out[:, :, :g].reshape(b, h, d)
    # a slot with no item never reached the kernel's write: its answer is
    # the softmax of one element, the appended token's value
    v_rep = jnp.repeat(v_new[:, 0], g, axis=1).astype(jnp.float32)
    out = jnp.where((lengths > 0)[:, None, None], out, v_rep)
    return out.astype(q.dtype).reshape(b, 1, h, d)


def flash_decode_sharded(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                         k_scale=None, v_scale=None, *, mesh,
                         batch_axes=(), head_axis=None, block_s: int,
                         interpret: bool = False) -> jnp.ndarray:
    """shard_map'd flash_decode_stacked: each device walks its local
    [KV/tp] head shard of the stacked cache (and its local batch shard on
    data-parallel meshes). The specs mirror parallel.kv_cache_specs, so
    GSPMD never gathers the cache at the shard_map boundary; no
    collective inside attention (the o-proj psum downstream is
    unchanged). check_vma off: pallas_call has no replication rule."""
    from jax.sharding import PartitionSpec as P

    bax = tuple(batch_axes) or None
    qspec = P(bax, None, head_axis, None)          # q/k_new/v_new [B,1,·,D]
    cspec = P(None, bax, None, head_axis, None)    # caches [L,B,Smax,KV,D]
    sspec = P(None, bax, None, head_axis)          # scales [L,B,Smax,KV]
    specs = (qspec, cspec, cspec, qspec, qspec, P(bax), P())
    args = (q, cache_k, cache_v, k_new, v_new, lengths, layer)
    if k_scale is not None:
        specs += (sspec, sspec)
        args += (k_scale, v_scale)
    run = functools.partial(flash_decode_stacked, block_s=block_s,
                            interpret=interpret)
    return jax.shard_map(run, mesh=mesh, in_specs=specs, out_specs=qspec,
                         check_vma=False)(*args)


def kernel_block(n_heads: int, cache_k, mesh=None) -> int | None:
    """The kernel's block size where backend and shapes allow it, None
    where decode attention stays on the reference: not a TPU, a head_dim
    that is not whole lanes, a cache shorter than a block, a tp that
    would split a KV head, or a local (KV, hd) tile that does not fill
    whole 32-bit sublanes. The last is int8 with fewer than four local KV
    heads (tp=4 over 8): XLA pads that tile to (4, 128) in HBM and Mosaic
    refuses the [block_s, 2, 128] slice of it. ``GOFR_FLASH_INTERPRET=1``
    runs the kernel interpreted on any backend and shape."""
    from .flash import interpret_env, tpu_backend_ok

    _, b, smax, n_kv, d = cache_k.shape
    if mesh is not None:
        from ..parallel.sharding import AXIS_TP, attention_shard_axes

        batch_axes, head_axis = attention_shard_axes(mesh, b, n_heads, n_kv)
        if head_axis is None and not batch_axes:
            return None
        if head_axis is not None:
            n_kv //= mesh.shape[AXIS_TP]
    block_s = block_size(smax)
    if interpret_env():
        return block_s
    if (d % _LANES or smax % _LANES or n_heads % cache_k.shape[3]
            or (n_kv * cache_k.dtype.itemsize) % 4 or not tpu_backend_ok()):
        return None
    return block_s


@jax.named_scope("flash_decode")
def decode_attention_auto(q, cache_k, cache_v, k_new, v_new, lengths, layer,
                          k_scale=None, v_scale=None, *, block_s: int,
                          mesh=None) -> jnp.ndarray:
    """The kernel over layer ``layer`` of the stacked cache, under
    shard_map where ``mesh`` shards heads or batch. ``block_s`` is
    ``kernel_block``'s answer for these shapes: the caller asks first,
    and takes ops.attention.decode_attention_appended where it is None."""
    from .flash import interpret_env

    interpret = interpret_env()
    if mesh is not None:
        from ..parallel.sharding import attention_shard_axes

        batch_axes, head_axis = attention_shard_axes(
            mesh, q.shape[0], q.shape[2], cache_k.shape[3])
        return flash_decode_sharded(
            q, cache_k, cache_v, k_new, v_new, lengths, layer, k_scale,
            v_scale, mesh=mesh, batch_axes=batch_axes, head_axis=head_axis,
            block_s=block_s, interpret=interpret)
    return flash_decode_stacked(q, cache_k, cache_v, k_new, v_new, lengths,
                                layer, k_scale, v_scale, block_s=block_s,
                                interpret=interpret)
