"""Paged decode attention over a block-pool KV cache.

The contiguous serving cache allocates [B, Smax] KV rows per slot — at
batch 128 x 1024 that is ~9.7 GB of int8 KV + f32 scales on top of the
8 GB weight stream, which does not fit a v5e chip. Paging replaces the
per-slot rows with a shared pool of fixed [T]-token blocks plus a
per-slot block TABLE (vLLM's design, rebuilt TPU-first): shapes stay
static, the pool is sized to the expected TOTAL live tokens instead of
batch x max_seq, and slots grow/free blocks host-side.

The kernel (block-diagonal GQA, online softmax, int8 tiles upcast
in-register) is a (slot, block) grid whose K/V/scale index maps look the
next tile up in a scalar-prefetched block table. Two properties the engine's
host side maintains make this fast and safe:

  - table rows are CLAMPED: entries past a slot's last live block repeat
    the last live block. Pallas skips the DMA when consecutive grid
    steps map to the same block, so a slot's HBM stream is proportional
    to its LIVE length, not the grid's max — and the in-kernel
    ``pl.when(si * T < length)`` skips the compute.
  - retired slots' rows point at block 0, a reserved trash block no live
    slot ever owns, so their frozen-cursor garbage writes land nowhere.

The jnp reference (``paged_attention_reference``) gathers each slot's
blocks into a dense view and calls the exact reference attention — the
numerics oracle for interpret-mode tests and the CPU fallback.

Sharding: on a mesh the ``*_auto`` dispatchers wrap the kernel in
``shard_map`` over the tp axis — the pool shards KV-heads over tp
(parallel.paged_cache_specs), the block table and lengths ride
replicated, and every device streams only its local [KV/tp] pane of
each block. No dense gather, no collectives inside attention. The
dense-gather reference remains the fallback only when tp would split
a KV head.

Reference provenance: the reference (GoFr) is a pure-Go microservice
framework with no ML/serving code at all — this module has NO reference
counterpart. It implements the TPU-inference rows SURVEY.md §2 adds to
the component inventory (the "to build — native" rows), with the design
cross-checked against the public PagedAttention idea, rebuilt for
static shapes + Mosaic (static block lattice + scalar-prefetch index
maps instead of pointer indirection).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, decode_attention_appended

_LANES = 128


def _decode_kernel(lengths_ref, qbd_ref, k_ref, v_ref, ks_ref, vs_ref,
                   acc_ref, m_ref, l_ref, *,
                   block_s: int, n_kv: int, quant: bool):
    """One (batch, block) step of the block-diagonal GQA recurrence (the
    dense-cache kernel's second design, kept here where its grid is the
    block table's). Scratchless: acc/m/l ARE the outputs,
    revisited across the sequential s dimension (the output block index
    map ignores si, so the tiles stay resident in VMEM until the last
    s-block flushes them)."""
    si = pl.program_id(1)
    length = lengths_ref[pl.program_id(0)]
    h = qbd_ref.shape[1]
    g = h // n_kv

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # blocks entirely past the valid prefix skip compute (the runtime
    # still streams them; skipping the math is the available win)
    @pl.when(si * block_s < length)
    def _compute():
        qbd = qbd_ref[0]                                   # [H, KV*D]
        k_flat = k_ref[0].reshape(block_s, -1)             # [BS, KV*D]
        v_flat = v_ref[0].reshape(block_s, -1)
        # scores: block-diagonal q rows zero out every kv plane but kv(h),
        # so the dense contraction equals the per-head dot
        s = jax.lax.dot_general(
            qbd, k_flat.astype(qbd.dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H, BS]
        if quant:
            ks = ks_ref[0]                                  # [KV, BS]
            ks_h = jnp.broadcast_to(ks[:, None, :],
                                    (n_kv, g, block_s)).reshape(h, block_s)
            s = s * ks_h
        pos = si * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)                     # [1, BS]
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[0, :, :1]                            # [H, 1]
        l_prev = l_ref[0, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                              # [H, BS]
        # fully-masked blocks never reach here (pl.when), and within a
        # reached block masked positions give exp(NEG_INF - m) = 0
        corr = jnp.exp(m_prev - m_new)                      # [H, 1]
        l_ref[0] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), (h, _LANES))
        m_ref[0] = jnp.broadcast_to(m_new, (h, _LANES))
        if quant:
            vs = vs_ref[0]                                  # [KV, BS]
            vs_h = jnp.broadcast_to(vs[:, None, :],
                                    (n_kv, g, block_s)).reshape(h, block_s)
            p = p * vs_h
        # pv contraction in q's dtype (bf16 in serving, f32 in the
        # numerics tests) — matches decode_attention_appended's vdt.
        # acc is [H, KV*D]; only the kv(h) slice is meaningful per row
        # (selected after the kernel), the rest is harmless extra MACs.
        acc_ref[0] = acc_ref[0] * corr + jax.lax.dot_general(
            p.astype(qbd.dtype), v_flat.astype(qbd.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H, KV*D]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_cache(q, k_pool, v_pool, table, lengths, k_scale, v_scale,
                        *, interpret: bool = False):
    """Pool-side running stats: (acc [B,H,KV*D] f32 unnormalized,
    m [B,H,LANES], l [B,H,LANES]) over each slot's valid positions.

    q: [B, H, D]; k_pool/v_pool: [N, T, KV, D] (int8 with scales
    [N, T, KV], or dense); table: [B, MB] int32 CLAMPED block ids;
    lengths: [B] int32 valid tokens per slot."""
    b, h, d = q.shape
    n_blocks, block_t, n_kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    mb = table.shape[1]
    g = h // n_kv
    quant = k_scale is not None
    if not quant:
        k_scale = jnp.ones((n_blocks, block_t, n_kv), jnp.float32)
        v_scale = jnp.ones((n_blocks, block_t, n_kv), jnp.float32)
    # [N, T, KV] -> [N, KV, T]: the [KV, T] tile broadcasts to [H, T]
    # along sublanes for free inside the kernel
    ks_t = jnp.swapaxes(k_scale, 1, 2).astype(jnp.float32)
    vs_t = jnp.swapaxes(v_scale, 1, 2).astype(jnp.float32)
    # block-diagonal query expansion (see _decode_kernel)
    qh = (q * (d ** -0.5)).reshape(b, n_kv, g, d)
    eye = jnp.eye(n_kv, dtype=q.dtype)
    q_bd = jnp.einsum("bkgd,kK->bgkKd", qh, eye,
                      preferred_element_type=q.dtype)
    q_bd = jnp.swapaxes(q_bd, 1, 2).reshape(b, h, n_kv * d)

    def kernel(lengths_ref, table_ref, *refs):
        # the table is consumed by the index maps only; the compute body
        # is EXACTLY the flash-decode kernel (si is the logical block
        # index either way, so its position masking carries over)
        del table_ref
        _decode_kernel(lengths_ref, *refs, block_s=block_t, n_kv=n_kv,
                       quant=quant)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # lengths, table
            grid=(b, mb),
            in_specs=[
                pl.BlockSpec((1, h, n_kv * d),
                             lambda bi, si, lens, tab: (bi, 0, 0)),
                # the paged difference: the next K/V/scale tile is
                # table[bi, si], not si — clamped rows repeat their last
                # block so Pallas skips the DMA past a slot's live length
                pl.BlockSpec((1, block_t, n_kv, d),
                             lambda bi, si, lens, tab: (tab[bi, si], 0, 0, 0)),
                pl.BlockSpec((1, block_t, n_kv, d),
                             lambda bi, si, lens, tab: (tab[bi, si], 0, 0, 0)),
                pl.BlockSpec((1, n_kv, block_t),
                             lambda bi, si, lens, tab: (tab[bi, si], 0, 0)),
                pl.BlockSpec((1, n_kv, block_t),
                             lambda bi, si, lens, tab: (tab[bi, si], 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, h, n_kv * d),
                             lambda bi, si, lens, tab: (bi, 0, 0)),
                pl.BlockSpec((1, h, _LANES),
                             lambda bi, si, lens, tab: (bi, 0, 0)),
                pl.BlockSpec((1, h, _LANES),
                             lambda bi, si, lens, tab: (bi, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_kv * d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), table.astype(jnp.int32),
      q_bd, k_pool, v_pool, ks_t, vs_t)
    acc = acc.reshape(b, n_kv, g, n_kv, d)
    acc = jnp.einsum("bkgKd,kK->bkgd", acc,
                     jnp.eye(n_kv, dtype=acc.dtype)).reshape(b, h, d)
    return acc, m, l


@jax.named_scope("paged_decode_attention")
def paged_decode_attention(q, k_pool, v_pool, k_new, v_new, table, lengths,
                           k_scale=None, v_scale=None, *,
                           interpret: bool = False) -> jnp.ndarray:
    """Single-token decode attention against a paged pool.

    q: [B, 1, H, D]; k_pool/v_pool: [N, T, KV, D]; k_new/v_new:
    [B, 1, KV, D] (bf16, this step's fresh KV — not yet in the pool);
    table [B, MB] clamped block ids; lengths [B] EXCLUDING the current
    token. Returns [B, 1, H, D] in q.dtype."""
    b, _, h, d = q.shape
    n_kv = k_pool.shape[2]
    g = h // n_kv
    acc, m, l = _paged_decode_cache(q[:, 0], k_pool, v_pool, table, lengths,
                                    k_scale, v_scale, interpret=interpret)
    m = m[..., 0]
    l = l[..., 0]
    # fold the appended token (the exact flash combination)
    qh = (q[:, 0] * (d ** -0.5)).reshape(b, n_kv, g, d)
    s_new = jnp.einsum("bkgd,bkd->bkg", qh,
                       k_new[:, 0].astype(qh.dtype),
                       preferred_element_type=jnp.float32).reshape(b, h)
    m_t = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_t)
    beta = jnp.exp(s_new - m_t)
    l_t = l * alpha + beta
    v_rep = jnp.repeat(v_new[:, 0], g, axis=1)
    out = (acc * alpha[..., None]
           + beta[..., None] * v_rep.astype(jnp.float32)) / l_t[..., None]
    return out.astype(q.dtype).reshape(b, 1, h, d)


def _paged_sharded(inner, mesh, head_axis, args, scales):
    """shard_map a paged kernel entry point over the tp axis: pool and
    q/k_new/v_new shard KV-heads (the paged mesh layout is tp-only —
    parallel.paged_cache_specs replicates batch, table, and lengths).
    Each device streams its local [KV/tp] pane of every block; no dense
    gather, no collectives. check_vma off: pallas_call has no
    replication rule."""
    from jax.sharding import PartitionSpec as P

    hspec = P(None, None, head_axis, None)   # q/k_new/v_new and pools
    sspec = P(None, None, head_axis)         # pool scales [N, T, KV]
    in_specs = (hspec,) * 5 + (P(), P())     # q, pools, new kv, table, lens
    if scales is not None:
        in_specs = in_specs + (sspec, sspec)
        args = args + scales
    fn = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                       out_specs=hspec, check_vma=False)
    return fn(*args)


def paged_decode_sharded(q, k_pool, v_pool, k_new, v_new, table, lengths,
                         k_scale=None, v_scale=None, *, mesh,
                         head_axis=None,
                         interpret: bool = False) -> jnp.ndarray:
    """shard_map'd paged_decode_attention — see _paged_sharded."""
    if k_scale is not None:
        def run(q, kp, vp, kn, vn, tab, ln, ks, vs):
            return paged_decode_attention(q, kp, vp, kn, vn, tab, ln,
                                          ks, vs, interpret=interpret)
    else:
        def run(q, kp, vp, kn, vn, tab, ln):
            return paged_decode_attention(q, kp, vp, kn, vn, tab, ln,
                                          interpret=interpret)
    scales = (k_scale, v_scale) if k_scale is not None else None
    return _paged_sharded(run, mesh, head_axis,
                          (q, k_pool, v_pool, k_new, v_new, table, lengths),
                          scales)


def paged_window_sharded(q, k_pool, v_pool, k_new, v_new, table, lengths,
                         k_scale=None, v_scale=None, *, mesh,
                         head_axis=None,
                         interpret: bool = False) -> jnp.ndarray:
    """shard_map'd paged_window_attention (speculative verify) — the
    kv-major row flattening is per-KV-head, so it holds unchanged on
    each device's local [KV/tp] shard."""
    if k_scale is not None:
        def run(q, kp, vp, kn, vn, tab, ln, ks, vs):
            return paged_window_attention(q, kp, vp, kn, vn, tab, ln,
                                          ks, vs, interpret=interpret)
    else:
        def run(q, kp, vp, kn, vn, tab, ln):
            return paged_window_attention(q, kp, vp, kn, vn, tab, ln,
                                          interpret=interpret)
    scales = (k_scale, v_scale) if k_scale is not None else None
    return _paged_sharded(run, mesh, head_axis,
                          (q, k_pool, v_pool, k_new, v_new, table, lengths),
                          scales)


def gather_blocks(pool, table):
    """Dense per-slot view of a paged buffer: [N, T, ...] gathered by
    table [B, MB] -> [B, MB*T, ...]. Materializes the full dense cache —
    the reference/FALLBACK path only (numerics oracles, CPU backends);
    on TPU both the decode and the verify-window kernels stream blocks
    directly and never gather."""
    g = pool[table]                       # [B, MB, T, ...]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def gather_heads(pool, table):
    """gather_blocks in the order the dense references read a cache
    layer, a KV head's positions together (models.llama.KVCache):
    [B, KV, MB*T(, hd)]."""
    return jnp.swapaxes(gather_blocks(pool, table), 1, 2)


def paged_attention_reference(q, k_pool, v_pool, k_new, v_new, table,
                              lengths, k_scale=None, v_scale=None):
    """Numerics oracle: gather the table's dense view, run the exact
    reference decode attention."""
    k_dense = gather_heads(k_pool, table)
    v_dense = gather_heads(v_pool, table)
    ks = gather_heads(k_scale, table) if k_scale is not None else None
    vs = gather_heads(v_scale, table) if v_scale is not None else None
    return decode_attention_appended(q, k_dense, v_dense, k_new, v_new,
                                     lengths, ks, vs)


@jax.named_scope("paged_window_attention")
def paged_window_attention(q, k_pool, v_pool, k_new, v_new, table,
                           lengths, k_scale=None, v_scale=None, *,
                           interpret: bool = False) -> jnp.ndarray:
    """ops.attention.window_attention_appended over the paged pool —
    the speculative-decoding verify pass WITHOUT the dense gather: the
    cache side streams through the same scalar-prefetch kernel as
    decode (every (w, h) query row attends positions < lengths[b], so
    the W*H rows flatten kv-major and ride the block-diagonal matmul
    unchanged), and the W x W in-window causal part folds in afterwards
    with the exact flash combination.

    q: [B, W, H, D]; k_pool/v_pool: [N, T, KV, D]; k_new/v_new:
    [B, W, KV, D] (bf16, the window's fresh KV — not yet in the pool);
    table [B, MB] clamped block ids; lengths [B] EXCLUDING the window.
    Returns [B, W, H, D] in q.dtype."""
    b, w, h, d = q.shape
    n_kv = k_pool.shape[2]
    g = h // n_kv
    # rows kv-major so _paged_decode_cache's [n_kv, g'] reshape holds
    # with g' = W*G: [B, W, KV, G, D] -> [B, KV, W, G, D] -> [B, H', D]
    q_rows = q.reshape(b, w, n_kv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, n_kv * w * g, d)
    acc, m, l = _paged_decode_cache(q_rows, k_pool, v_pool, table,
                                    lengths, k_scale, v_scale,
                                    interpret=interpret)
    # back to [B, W, H(=KV*G), ...]
    def unrows(x):
        x = x.reshape((b, n_kv, w, g) + x.shape[2:])
        return jnp.swapaxes(x, 1, 2).reshape((b, w, h) + x.shape[4:])

    acc = unrows(acc)                                   # [B, W, H, D]
    m = unrows(m[..., 0])                               # [B, W, H]
    l = unrows(l[..., 0])

    # in-window causal scores: query row w attends window positions <= w
    qg = (q * (d ** -0.5)).reshape(b, w, n_kv, g, d)
    s_s = jnp.einsum("bwkgd,btkd->bwkgt", qg,
                     k_new.astype(qg.dtype),
                     preferred_element_type=jnp.float32)  # [B,W,KV,G,Wt]
    s_s = s_s.reshape(b, w, h, w)
    causal = jnp.tril(jnp.ones((w, w), bool))             # [W, Wt]
    s_s = jnp.where(causal[None, :, None, :], s_s, NEG_INF)
    s_max = jnp.max(s_s, axis=-1)                         # [B, W, H]
    m_t = jnp.maximum(m, s_max)
    p = jnp.where(causal[None, :, None, :],
                  jnp.exp(s_s - m_t[..., None]), 0.0)     # [B, W, H, Wt]
    alpha = jnp.exp(m - m_t)                              # [B, W, H]
    l_t = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bwkgt,btkd->bwkgd",
                    p.reshape(b, w, n_kv, g, w).astype(v_new.dtype),
                    v_new).reshape(b, w, h, d)
    out = (acc * alpha[..., None] + pv) / l_t[..., None]
    return out.astype(q.dtype)


def paged_window_auto(q, k_pool, v_pool, k_new, v_new, table, lengths,
                      k_scale=None, v_scale=None, *,
                      interpret: bool = False, mesh=None) -> jnp.ndarray:
    """Window kernel when backend+shapes allow, dense-gather reference
    (paged_window_reference) otherwise. With ``mesh``, the kernel runs
    under shard_map per tp head shard (paged_window_sharded); the
    reference remains the fallback when tp would split a KV head."""
    from .flash import interpret_env

    interpret = interpret or interpret_env()
    b, w, h, d = q.shape
    probe = jax.ShapeDtypeStruct((b, 1, h * w, d), q.dtype)
    if mesh is not None:
        head_axis = _mesh_head_axis(mesh, h, k_pool.shape[2])
        if head_axis is not None and (interpret or _kernel_ok(probe, k_pool)):
            return paged_window_sharded(q, k_pool, v_pool, k_new, v_new,
                                        table, lengths, k_scale, v_scale,
                                        mesh=mesh, head_axis=head_axis,
                                        interpret=interpret)
        return paged_window_reference(q, k_pool, v_pool, k_new, v_new,
                                      table, lengths, k_scale, v_scale)
    if interpret or _kernel_ok(probe, k_pool):
        return paged_window_attention(q, k_pool, v_pool, k_new, v_new,
                                      table, lengths, k_scale, v_scale,
                                      interpret=interpret)
    return paged_window_reference(q, k_pool, v_pool, k_new, v_new,
                                  table, lengths, k_scale, v_scale)


def paged_window_reference(q, k_pool, v_pool, k_new, v_new, table, lengths,
                           k_scale=None, v_scale=None) -> jnp.ndarray:
    """Dense-gather reference for the window path: the table's blocks
    gathered into contiguous views, then window_attention_appended.
    paged_window_auto's off-kernel fallback, and the path mesh engines
    FORCE (``flash=False`` in paged_llama) — a pallas_call is opaque
    to the GSPMD partitioner."""
    from .attention import window_attention_appended

    ks = gather_heads(k_scale, table) if k_scale is not None else None
    vs = gather_heads(v_scale, table) if v_scale is not None else None
    return window_attention_appended(q, gather_heads(k_pool, table),
                                     gather_heads(v_pool, table),
                                     k_new, v_new, lengths, ks, vs)


def _kernel_ok(q, k_pool) -> bool:
    from .flash import tpu_backend_ok

    b, _, h, d = q.shape
    block_t, n_kv = k_pool.shape[1], k_pool.shape[2]
    if d % _LANES or h % n_kv or block_t % 8:
        return False
    return tpu_backend_ok()


def _mesh_head_axis(mesh, n_heads: int, n_kv_heads: int):
    """tp axis name when it divides both head counts (the shard_map'able
    condition), else None — the head-splitting-tp jnp fallback."""
    from ..parallel.sharding import attention_shard_axes

    _, head_axis = attention_shard_axes(mesh, 0, n_heads, n_kv_heads)
    return head_axis


def paged_attention_auto(q, k_pool, v_pool, k_new, v_new, table, lengths,
                         k_scale=None, v_scale=None, *,
                         interpret: bool = False, mesh=None) -> jnp.ndarray:
    """Kernel when backend+shapes allow, dense-gather reference
    otherwise. With ``mesh``, the kernel runs under shard_map per tp
    head shard (paged_decode_sharded) — the mesh serving path never
    gathers a dense pool view; the reference remains the fallback when
    tp would split a KV head."""
    from .flash import interpret_env

    interpret = interpret or interpret_env()
    if mesh is not None:
        head_axis = _mesh_head_axis(mesh, q.shape[2], k_pool.shape[2])
        if head_axis is not None and (interpret or _kernel_ok(q, k_pool)):
            return paged_decode_sharded(q, k_pool, v_pool, k_new, v_new,
                                        table, lengths, k_scale, v_scale,
                                        mesh=mesh, head_axis=head_axis,
                                        interpret=interpret)
        return paged_attention_reference(q, k_pool, v_pool, k_new, v_new,
                                         table, lengths, k_scale, v_scale)
    if interpret or _kernel_ok(q, k_pool):
        return paged_decode_attention(q, k_pool, v_pool, k_new, v_new,
                                      table, lengths, k_scale, v_scale,
                                      interpret=interpret)
    return paged_attention_reference(q, k_pool, v_pool, k_new, v_new,
                                     table, lengths, k_scale, v_scale)
