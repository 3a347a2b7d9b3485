"""The cache of the families that keep a state beside rows (the hybrid
family's delta-rule state, the state-space family's): K and V rows of
the attention layers as llama's, a float32 state and a convolution's
tail a slot of the recurrent layers, and the entry points of
``models.family`` that follow from the cache alone: its write after a
prefill, its row layout, its decode block, its rope tables and what a
state cannot do yet. A family adds ``init_cache`` (the state's shape is
its own) and its programs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import flash_decode
from . import blocks, llama
from .common import ModelConfig, refused_options


class HybridCache(NamedTuple):
    """The slots' memory of both kinds. ``k``/``v`` as llama.KVCache (and
    int8 with scale planes); every array but ``lengths`` is [L, B, ...],
    which is all the engine's row helpers ask."""

    k: jnp.ndarray        # [La, B, rows, Smax, values]: ``kv_layout``
    v: jnp.ndarray
    state: jnp.ndarray    # [Ls, B, ...] float32
    conv: jnp.ndarray     # [Ls, B, ...]: the convolutions' last inputs
    lengths: jnp.ndarray  # [B] int32
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def rows(self) -> llama.KVCache:
        """The attention layers' part, as llama's helpers take it."""
        return llama.KVCache(self.k, self.v, self.lengths, self.k_scale,
                             self.v_scale)

    def with_rows(self, kv: llama.KVCache, **kw) -> "HybridCache":
        return self._replace(k=kv.k, v=kv.v, lengths=kv.lengths,
                             k_scale=kv.k_scale, v_scale=kv.v_scale, **kw)


def get_rope_tables(cfg: ModelConfig, max_seq: int):
    """(cos, sin) for the attention layers, None where they do not
    rotate."""
    return llama.get_rope_tables(cfg, max_seq) if cfg.use_rope else None


kv_tables = llama.kv_tables      # one table a layer (models.family)
chunk_block = llama.chunk_block  # a cursor walk (models.family)


# two KV heads a row at a head of exactly half a lane row (blocks.paired
# says why a narrower head beside a state stays a row of its own)
paired = functools.partial(blocks.paired, whole_rows=True)


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(rows, values a row) of a cached token's K (and V), as stored."""
    return blocks.row_layout(cfg, paired(cfg))


def init_rows(cfg: ModelConfig, n_layers: int, batch: int,
              max_seq: int | None = None, dtype=None) -> llama.KVCache:
    """The rows of ``n_layers`` attention layers, as ``kv_layout`` lays a
    token out."""
    rows, values = kv_layout(cfg)
    if paired(cfg) and dtype is not None and jnp.dtype(dtype) == jnp.int8:
        raise ValueError(
            f"an int8 cache for {cfg.name!r}: a cache row holds two KV "
            "heads and the shared kernels take one scale a row")
    return llama.init_cache(
        cfg.with_(n_layers=n_layers, n_kv_heads=rows, attn_head_dim=values),
        batch, max_seq, dtype)


def decode_kv_block(cfg: ModelConfig, cache: HybridCache, mesh=None):
    return flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)


# the serving options that would restore or rewind a slot from rows alone,
# and why not (the engine raises on any of them at start-up)
REFUSED = {
    "mesh": "the recurrent state and the expert share have no sharding "
            "rule; the family runs on one chip",
    "paged_blocks": "the block pool holds K and V rows, not a recurrent "
                    "state",
    "kvcache": "the host and Redis tiers frame K and V rows; a state "
               "would not travel with them",
    "spec_decode_k": "a rejected draft cannot be taken back out of a state",
    "lora_adapters": "adapters target the llama block's projections",
    "serving_role": "KV shipping frames K and V rows, not a state",
}
unsupported_options = functools.partial(refused_options, REFUSED)


def _tables(cache: HybridCache):
    return cache.k, cache.v, cache.k_scale, cache.v_scale


def decode_attend(cache: HybridCache, i, lengths, live, block_s, mesh,
                  cfg: ModelConfig):
    """``attend(q, k_new, v_new)`` of attention layer ``i``'s decode step
    (``blocks.decode_rows_attend`` over this cache's rows, paired where
    it holds them so; ``block_s``: ``decode_kv_block``'s answer)."""
    return blocks.decode_rows_attend(_tables(cache), i, lengths, live,
                                     block_s, mesh, cfg, paired(cfg))


def chunk_attend(cache: HybridCache, i, start, cfg: ModelConfig):
    """``attend(q, k_new, v_new)`` of attention layer ``i`` in a chunk
    program: the cached rows before ``start`` and the chunk within
    itself."""
    return blocks.chunk_rows_attend(_tables(cache), i, start, cfg,
                                    paired(cfg))


@jax.named_scope("kv_write")
def write_kv(cache: HybridCache, k_stack, v_stack, state, conv, index,
             lengths) -> HybridCache:
    """Write what ``prefill_kv`` made for B' rows at batch row
    ``index[1]``: K and V stacks from position ``index[3]`` (llama's
    write), state and tail whole."""
    rows = llama.write_kv(cache.rows, k_stack, v_stack, index, lengths)
    slot = index[1]
    return cache.with_rows(
        rows,
        state=jax.lax.dynamic_update_slice_in_dim(
            cache.state, state.astype(jnp.float32), slot, axis=1),
        conv=jax.lax.dynamic_update_slice_in_dim(
            cache.conv, conv.astype(cache.conv.dtype), slot, axis=1))
