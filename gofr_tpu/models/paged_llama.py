"""Paged (block-pool) KV cache for Llama-family serving.

The contiguous ``llama.KVCache`` reserves [B, Smax] rows per slot; HBM
capacity caps the decode batch long before the MXU or the weight stream
does (8B int8 at batch 128 x 1024: ~9.7 GB KV on top of 8 GB weights —
over a v5e's 16 GB). This module keeps the same model math (the layer
scan calls the SAME ``llama.layer``) but stores KV in a shared pool of
fixed T-token blocks with a per-slot block table:

    k_pool/v_pool  [L, N, T, KV, hd]   (int8 with [L, N, T, KV] scales)
    table          [B, MB] int32       host-owned, passed per dispatch
    lengths        [B]    int32        device state, donated

TPU-first constraints drive every choice: N/T/MB are static so one
program serves all occupancies; the table is data, not shape; block
boundaries are crossed with host-side allocation between fused decode
blocks (the device never allocates); attention runs the scalar-prefetch
Pallas kernel (ops.paged_attention) whose HBM stream is proportional to
LIVE tokens, with a dense-gather jnp reference for CPU/tests.

Table invariants (maintained by the engine's allocator):
  - entries for live logical blocks hold real pool block ids;
  - entries past the live range repeat the LAST live block (clamping —
    the kernel's DMA-skip), or block 0 for empty/retired slots;
  - block 0 is a reserved trash block no slot ever owns: retired slots'
    frozen-cursor garbage writes land there.

Reference provenance: the reference (GoFr) is a pure-Go microservice
framework with zero ML code — paged serving has NO reference
counterpart. This module implements the TPU-inference rows SURVEY.md §2
adds to the inventory (the "to build — native" rows); the design is
cross-checked against the public PagedAttention idea, rebuilt for
static shapes + Mosaic.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.paged_attention import paged_attention_auto
from . import llama
from .common import ModelConfig
from .llama import (get_rope_tables, multi_request_serving_config,
                    quantize_kv)


class PagedKVCache(NamedTuple):
    k: jnp.ndarray        # [L, N, T, KV, hd]
    v: jnp.ndarray        # [L, N, T, KV, hd]
    lengths: jnp.ndarray  # [B] int32 — live tokens per slot
    k_scale: jnp.ndarray | None = None  # [L, N, T, KV] f32 (int8 pools)
    v_scale: jnp.ndarray | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]


def init_paged_cache(cfg: ModelConfig, slots: int, n_blocks: int,
                     block_size: int = 128, dtype=None) -> PagedKVCache:
    """Pool of ``n_blocks`` blocks (block 0 is the reserved trash block —
    size the pool as usable_tokens // block_size + 1). ``dtype=jnp.int8``
    allocates the quantized pool with scale planes."""
    dtype = dtype or cfg.jdtype
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    quant = jnp.dtype(dtype) == jnp.int8
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((slots,), jnp.int32),
        k_scale=jnp.zeros(shape[:-1], jnp.float32) if quant else None,
        v_scale=jnp.zeros(shape[:-1], jnp.float32) if quant else None,
    )



def _pool_coords(table: jnp.ndarray, positions: jnp.ndarray, T: int,
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(block_ids, offsets) for writing at ``positions`` ([B] or [B, W])
    through a clamped ``table`` [B, MB]. Past-capacity positions route
    to the trash block — the paged mirror of the contiguous scatter's
    mode="drop" (without it the offset would wrap into the slot's own
    live last block)."""
    mb = table.shape[1]
    idx = jnp.minimum(positions // T, mb - 1)
    blk = jnp.take_along_axis(table, idx if idx.ndim == 2 else idx[:, None],
                              axis=1)
    if positions.ndim == 1:
        blk = blk[:, 0]
    blk = jnp.where(positions < mb * T, blk, 0)
    return blk, positions % T


def paged_decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                      cache: PagedKVCache, table: jnp.ndarray,
                      rope_tables=None, flash: bool = True,
                      adapter=None, mesh=None
                      ) -> tuple[jnp.ndarray, PagedKVCache]:
    """One decode step for tokens [B] against the paged pool.

    ``table`` [B, MB] int32: clamped block ids (see module docstring).
    Returns (logits [B, V] f32, cache with lengths+1). Same structure as
    llama.decode_step (reference hot loop): pool READ-ONLY inside the
    layer scan, the new token's [L, B, KV, hd] written by one scatter
    after it.

    CAPACITY CONTRACT: the caller guarantees each slot's current block
    (table[b, lengths[b] // T]) is allocated and lengths < MB*T; the
    write position is clamped into the table's range, so a violated
    contract corrupts only that slot's own (or the trash) block.
    ``flash=False`` routes attention through the dense-gather reference
    (CPU tests; the kernel gate also falls back off-TPU). With ``mesh``
    the kernel runs under shard_map per tp head shard — no dense pool
    gather on mesh (ops.paged_attention.paged_decode_sharded)."""
    cfg = multi_request_serving_config(cfg)
    B = tokens.shape[0]
    T = cache.block_size
    mb = table.shape[1]
    max_seq = mb * T
    cos, sin = rope_tables or get_rope_tables(cfg, max_seq)
    positions = cache.lengths[:, None]
    lengths = cache.lengths

    x = params["embedding"][tokens[:, None]].astype(cfg.jdtype)

    if flash:
        import functools

        attn = functools.partial(paged_attention_auto, mesh=mesh)
    else:
        attn = _reference_attention

    def body(x, xs):
        layer_w, k_layer, v_layer, ks_layer, vs_layer = xs

        def attend(q, k_new, v_new):
            return attn(q, k_layer, v_layer, k_new, v_new, table,
                        lengths, ks_layer, vs_layer)

        x, kv_tok, _ = llama.layer(x, layer_w, cfg, cos, sin, positions,
                                   kv_write=lambda k, v: (k, v),
                                   attend=attend, adapter=adapter)
        return x, kv_tok

    x, (k_toks, v_toks) = jax.lax.scan(
        body, x, (params["layers"], cache.k, cache.v,
                  cache.k_scale, cache.v_scale))
    # one scatter for all layers into each slot's current block
    blk, off = _pool_coords(table, lengths, T)
    k_tok, v_tok = k_toks[:, :, 0], v_toks[:, :, 0]      # [L, B, KV, hd]
    if cache.quantized:
        qk, sk = quantize_kv(k_tok)
        qv, sv = quantize_kv(v_tok)
        new = cache._replace(
            k=cache.k.at[:, blk, off].set(qk, mode="drop"),
            v=cache.v.at[:, blk, off].set(qv, mode="drop"),
            k_scale=cache.k_scale.at[:, blk, off].set(sk, mode="drop"),
            v_scale=cache.v_scale.at[:, blk, off].set(sv, mode="drop"),
            lengths=lengths + 1)
    else:
        new = cache._replace(
            k=cache.k.at[:, blk, off].set(k_tok.astype(cache.k.dtype),
                                          mode="drop"),
            v=cache.v.at[:, blk, off].set(v_tok.astype(cache.v.dtype),
                                          mode="drop"),
            lengths=lengths + 1)
    return llama.logits(params, cfg, x[:, 0]), new


def _reference_attention(q, k_pool, v_pool, k_new, v_new, table, lengths,
                         k_scale, v_scale):
    from ..ops.paged_attention import paged_attention_reference

    return paged_attention_reference(q, k_pool, v_pool, k_new, v_new,
                                     table, lengths, k_scale, v_scale)


def paged_verify_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                      cache: PagedKVCache, table: jnp.ndarray,
                      rope_tables=None, adapter=None, flash: bool = True,
                      mesh=None) -> tuple[jnp.ndarray, PagedKVCache]:
    """Speculative-decoding verify pass over the paged pool — the exact
    contract of llama.verify_step (logits [B, W, V]; lengths returned
    UNCHANGED, acceptance is the caller's; W KV rows written at each
    slot's cursor), with the pool addressed through ``table``.

    Attention runs the paged WINDOW kernel (ops.paged_attention.
    paged_window_auto): the cache side streams each slot's live blocks
    exactly once through the same scalar-prefetch kernel as decode, and
    the W x W in-window part folds in exactly — off-TPU the auto gate
    falls back to window_attention_appended over a dense gather of the
    table. ``flash=False`` forces that dense-gather reference. With
    ``mesh`` the kernel runs under shard_map per tp head shard
    (ops.paged_attention.paged_window_sharded) — speculative decoding
    keeps the kernel, and the no-dense-gather rule, on mesh engines.

    CAPACITY CONTRACT (same as verify_step): callers must only honor
    acceptance for slots with lengths + W <= capacity; rows past
    capacity route to the trash block, mirroring the contiguous
    scatter's mode=\"drop\"."""
    from ..ops.paged_attention import paged_window_auto

    cfg = multi_request_serving_config(cfg)
    B, W = tokens.shape
    T = cache.block_size
    mb = table.shape[1]
    cos, sin = rope_tables or get_rope_tables(cfg, mb * T)
    positions = cache.lengths[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    lengths = cache.lengths

    x = params["embedding"][tokens].astype(cfg.jdtype)  # [B, W, D]

    def body(x, xs):
        layer_w, k_layer, v_layer, ks_layer, vs_layer = xs

        def attend(q, k_new, v_new):
            if not flash:
                from ..ops.paged_attention import paged_window_reference

                return paged_window_reference(
                    q, k_layer, v_layer, k_new, v_new, table, lengths,
                    ks_layer, vs_layer)
            return paged_window_auto(q, k_layer, v_layer, k_new, v_new,
                                     table, lengths, ks_layer, vs_layer,
                                     mesh=mesh)

        x, kv, _ = llama.layer(x, layer_w, cfg, cos, sin, positions,
                               kv_write=lambda k, v: (k, v), attend=attend,
                               adapter=adapter)
        return x, kv

    x, (k_w, v_w) = jax.lax.scan(
        body, x, (params["layers"], cache.k, cache.v,
                  cache.k_scale, cache.v_scale))
    # one scatter for all layers and window rows into pool coordinates
    blk, off = _pool_coords(table, positions, T)
    if cache.quantized:
        qk, sk = quantize_kv(k_w)
        qv, sv = quantize_kv(v_w)
        new = cache._replace(
            k=cache.k.at[:, blk, off].set(qk),
            v=cache.v.at[:, blk, off].set(qv),
            k_scale=cache.k_scale.at[:, blk, off].set(sk),
            v_scale=cache.v_scale.at[:, blk, off].set(sv),
            lengths=lengths)
    else:
        new = cache._replace(
            k=cache.k.at[:, blk, off].set(k_w.astype(cache.k.dtype)),
            v=cache.v.at[:, blk, off].set(v_w.astype(cache.v.dtype)),
            lengths=lengths)
    return llama.logits(params, cfg, x), new


def write_prompt_blocks(cache: PagedKVCache, k_stack, v_stack,
                        blocks: jnp.ndarray, length) -> PagedKVCache:
    """Write one admitted prompt's KV stacks [L, 1, S, KV, hd] into its
    allocated blocks. ``blocks`` [ceil(S/T)] int32 (traced values, static
    count — one program per prompt bucket); ``length`` is the true prompt
    length: rows in [length, S) are bucket padding — they land in the
    slot's own blocks past its cursor, invisible behind ``lengths`` and
    overwritten as decode advances (the same contract as the contiguous
    cache's write_kv). Quantize-on-write, then one shared block-copy
    loop (_write_stacks_to_blocks) moves the rows."""
    if cache.quantized:
        qk, sk = quantize_kv(k_stack)
        qv, sv = quantize_kv(v_stack)
        return _write_stacks_to_blocks(cache, qk, qv, sk, sv, blocks)
    return _write_stacks_to_blocks(cache, k_stack, v_stack, None, None,
                                   blocks)


def read_blocks_to_row(row, cache: PagedKVCache,
                       blocks: jnp.ndarray):
    """Inverse of write_row_to_blocks: gather pool blocks into a dense
    single-slot scratch row (llama.KVCache with B=1,
    [L, 1, KV, Smax, hd]) — the restore half of
    the paged prefix cache (shared blocks -> scratch, then chunked
    prefill resumes from the match point against the dense row).
    ``blocks`` [MB] int32: entries past the shared prefix may point
    anywhere (typically the trash block); those positions are
    overwritten by the resumed chunks or ignored past the prompt. A
    block is [T, KV, hd] and the row holds a KV head's positions
    together: each block is transposed on its way."""
    T = cache.block_size
    mb = blocks.shape[0]
    k, v, ks, vs = row.k, row.v, row.k_scale, row.v_scale
    quant = cache.quantized

    def block(pool, j, span):           # [L, 1, KV, span(, hd)]
        blk = jax.lax.dynamic_slice(
            pool, (0, blocks[j]) + (0,) * (pool.ndim - 2),
            (pool.shape[0], 1, span) + pool.shape[3:])
        return jnp.swapaxes(blk, 2, 3)

    for j in range(mb):
        lo = j * T
        span = min(T, row.capacity - lo)
        if span <= 0:
            break
        k = jax.lax.dynamic_update_slice(
            k, block(cache.k, j, span).astype(k.dtype), (0, 0, 0, lo, 0))
        v = jax.lax.dynamic_update_slice(
            v, block(cache.v, j, span).astype(v.dtype), (0, 0, 0, lo, 0))
        if quant:
            ks = jax.lax.dynamic_update_slice(
                ks, block(cache.k_scale, j, span), (0, 0, 0, lo))
            vs = jax.lax.dynamic_update_slice(
                vs, block(cache.v_scale, j, span), (0, 0, 0, lo))
    return row._replace(k=k, v=v, k_scale=ks, v_scale=vs)


def write_row_to_blocks(cache: PagedKVCache, row, blocks: jnp.ndarray,
                        ) -> PagedKVCache:
    """Copy a dense single-slot cache row (llama.KVCache with B=1,
    [L, 1, KV, S, hd]; S may be shorter than MB*T — slices clamp) into
    pool blocks: long-prompt admission lands the chunked SCRATCH row
    this way. ``blocks`` [n] int32: entries past the prompt's own
    blocks point at the trash block, so positions beyond the prompt
    land nowhere. Same-dtype copy (int8 + scales move verbatim), each
    block transposed to the pool's [T, KV, hd]."""
    def stack(a):
        return None if a is None else jnp.swapaxes(a, 2, 3)

    return _write_stacks_to_blocks(cache, stack(row.k), stack(row.v),
                                   stack(row.k_scale), stack(row.v_scale),
                                   blocks)


def _write_stacks_to_blocks(cache: PagedKVCache, k, v, ks, vs,
                            blocks: jnp.ndarray) -> PagedKVCache:
    """The block-copy loop under both admission paths: ``k``/``v``
    [L, 1, S, KV, hd] (scales [L, 1, S, KV]) in the cache's dtype, block
    j of T positions to pool block ``blocks[j]``."""
    T = cache.block_size
    out = []
    for pool, src in ((cache.k, k), (cache.v, v), (cache.k_scale, ks),
                      (cache.v_scale, vs)):
        if src is not None:
            for j in range(blocks.shape[0]):
                pool = jax.lax.dynamic_update_slice(
                    pool, src[:, :, j * T:(j + 1) * T].astype(pool.dtype),
                    (0, blocks[j]) + (0,) * (pool.ndim - 2))
        out.append(pool)
    return cache._replace(k=out[0], v=out[1], k_scale=out[2],
                          v_scale=out[3])


class BlockAllocator:
    """Host-side refcounted free-list over pool blocks 1..N-1 (block 0
    is the reserved trash block). Refcounts exist for SHARED prefix
    blocks: a stored prefix entry and every slot serving from it each
    hold a reference; a block returns to the free list only when the
    last holder drops it. Thread-compatible: the engine calls it only
    from the serving loop under its device lock."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("paged pool needs >= 2 blocks "
                             "(block 0 is reserved)")
        import numpy as np

        self._free = list(range(n_blocks - 1, 0, -1))
        self._rc = np.zeros(n_blocks, np.int32)
        self.n_blocks = n_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """n block ids (each at refcount 1), or None (nothing allocated)
        if the pool can't cover the request — the caller picks the
        eviction policy."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._rc[b] = 1
        return out

    def ref(self, blocks) -> None:
        """Additional holder for already-allocated blocks."""
        for b in blocks:
            assert self._rc[b] > 0, f"ref of unallocated block {b}"
            self._rc[b] += 1

    def free(self, blocks) -> None:
        """Drop one reference per block; blocks with no remaining holder
        return to the free list."""
        for b in blocks:
            self._rc[b] -= 1
            if self._rc[b] == 0:
                self._free.append(b)
            assert self._rc[b] >= 0, f"double free of block {b}"

    def sole_holder(self, blocks) -> bool:
        """True when the caller's reference is the only one on every
        block — freeing would return them all to the free list."""
        return all(self._rc[b] == 1 for b in blocks)


class SharedPrefixIndex:
    """Zero-copy prefix reuse for the paged pool (the paged counterpart
    of the contiguous engine's tpu/kvcache hierarchy — here the pool
    blocks ARE the storage, so there is nothing to tier): entries
    record the FULL T-token
    blocks of a stored prompt prefix and hold a reference on each — no
    KV is ever copied to store. Full blocks are immutable once written
    (decode only ever writes the block at a slot's cursor, which lies
    past its prompt's full blocks), so a stored entry stays valid for
    any continuation; a hit refs the shared blocks into the new slot's
    table and prefill resumes at the match point. Matches clamp to
    whole blocks and never consume the entire prompt (>= 1 token always
    recomputes, mirroring the contiguous engine's contract). LRU
    entries are evictable under pool pressure — eviction just drops the
    entry's references. Thread-compatible: serving-loop only."""

    def __init__(self, max_entries: int, alloc: BlockAllocator,
                 block_size: int):
        self.max_entries = int(max_entries)
        self._alloc = alloc
        self._t = int(block_size)
        self._entries: list[dict] = []  # {key, blocks, adapter, used}
        self._tick = 0
        self.hits = 0
        self.misses = 0
        # bumped on every mutation that can change a match() outcome —
        # lets callers memoize peek results (the serving loop polls
        # _needs_lattice every ~2 ms while a request heads the queue;
        # re-scanning an unchanged index is pure waste)
        self.version = 0

    def __len__(self) -> int:
        return len(self._entries)

    def match(self, prompt, adapter: int = 0) -> tuple[list[int], int]:
        """(shared_blocks, matched_tokens) — the longest stored LCP,
        clamped to whole blocks and to len(prompt)-1. ([], 0) on miss.
        PURE like PrefixIndex.match: accept()/reject() report back."""
        import numpy as np

        prompt = np.asarray(prompt, np.int32)
        limit = (len(prompt) - 1) // self._t  # blocks fully reusable
        best, best_blocks = 0, []
        for e in self._entries:
            if e["adapter"] != adapter:
                continue
            key = e["key"]
            n = min(len(key), len(prompt))
            neq = np.nonzero(key[:n] != prompt[:n])[0]
            m = int(neq[0]) if len(neq) else n
            nb = min(m // self._t, limit)
            if nb * self._t > best:
                best = nb * self._t
                best_blocks = e["blocks"][:nb]
        return (list(best_blocks), best) if best else ([], 0)

    def accept(self, blocks: list[int]) -> None:
        """A hit went live: count it, touch the owning entry's LRU."""
        self.hits += 1
        self._tick += 1
        lead = blocks[0] if blocks else -1
        for e in self._entries:
            if e["blocks"] and e["blocks"][0] == lead:
                e["used"] = self._tick

    def reject(self) -> None:
        self.misses += 1

    def covered(self, prompt, adapter: int = 0) -> bool:
        """True when some entry already stores >= this prompt's full
        blocks with identical tokens — storing again would only
        duplicate references."""
        import numpy as np

        prompt = np.asarray(prompt, np.int32)
        n_full = len(prompt) // self._t
        if n_full == 0:
            return True  # nothing storable
        head = prompt[:n_full * self._t]
        for e in self._entries:
            if e["adapter"] == adapter and len(e["key"]) >= len(head) \
                    and np.array_equal(e["key"][:len(head)], head):
                return True
        return False

    def store(self, prompt, blocks: list[int], adapter: int = 0) -> None:
        """Record ``prompt``'s full blocks as an entry, holding one
        reference on each (zero-copy: the blocks are the slot's own,
        already written). Evicts LRU entries past capacity."""
        import numpy as np

        prompt = np.asarray(prompt, np.int32)
        n_full = len(prompt) // self._t
        if n_full == 0:
            return
        held = list(blocks[:n_full])
        self._alloc.ref(held)
        self._tick += 1
        self.version += 1
        self._entries.append({"key": prompt[:n_full * self._t].copy(),
                              "blocks": held, "adapter": int(adapter),
                              "used": self._tick})
        while len(self._entries) > self.max_entries:
            self.evict_one()

    def evict_one(self) -> bool:
        """Drop one entry's references (pool-pressure valve). Returns
        False when there is nothing left to evict.

        Prefers the LRU entry among those whose blocks will ACTUALLY
        return to the free list (no live slot still holds them) —
        evicting a share-held entry reclaims zero blocks, and a
        transient shortage would otherwise flush the whole index,
        including productive future-hit entries, without recovering any
        memory. Share-held entries are evicted only when nothing
        reclaimable remains (their references still unpin the blocks
        once the sharing slots retire, so the caller's retry loop stays
        finite)."""
        if not self._entries:
            return False
        order = sorted(range(len(self._entries)),
                       key=lambda i: self._entries[i]["used"])
        victim = next(
            (i for i in order
             if self._alloc.sole_holder(self._entries[i]["blocks"])),
            order[0])
        e = self._entries.pop(victim)
        self._alloc.free(e["blocks"])
        self.version += 1
        return True

    def clear(self) -> int:
        """Drop every entry, releasing its block references. Engine
        recovery calls this BEFORE reallocating the pool (host-side
        phase, so waiters never observe a stale index): stored entries
        would otherwise keep pointing into the fresh zeroed pool and
        silently serve all-zero KV on their next hit."""
        n = len(self._entries)
        for e in self._entries:
            self._alloc.free(e["blocks"])
        self._entries = []
        self.version += 1
        return n

    def invalidate_adapter(self, adapter: int) -> int:
        """Drop every entry stored under ``adapter`` (LoRA hot-swap:
        stored KV flowed through the OLD wk/wv)."""
        keep, dropped = [], 0
        for e in self._entries:
            if e["adapter"] == int(adapter):
                self._alloc.free(e["blocks"])
                dropped += 1
            else:
                keep.append(e)
        self._entries = keep
        if dropped:
            self.version += 1
        return dropped

    def stats(self) -> dict:
        return {"slots": self.max_entries, "entries": len(self._entries),
                "hits": self.hits, "misses": self.misses,
                "blocks_held": sum(len(e["blocks"]) for e in self._entries)}
