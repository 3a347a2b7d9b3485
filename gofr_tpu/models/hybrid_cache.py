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
from ..ops.attention import decode_attention_appended
from . import llama
from .common import ModelConfig, refused_options


class HybridCache(NamedTuple):
    """The slots' memory of both kinds. ``k``/``v`` as llama.KVCache (and
    int8 with scale planes); every array but ``lengths`` is [L, B, ...],
    which is all the engine's row helpers ask."""

    k: jnp.ndarray        # [La, B, KV, Smax, hd]
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

    def layer_rows(self, i):
        """(k, v, k_scale, v_scale) of attention layer ``i``, a scale
        None where the rows are not int8."""
        return tuple(None if a is None else jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False)
            for a in (self.k, self.v, self.k_scale, self.v_scale))

    def with_rows(self, kv: llama.KVCache, **kw) -> "HybridCache":
        return self._replace(k=kv.k, v=kv.v, lengths=kv.lengths,
                             k_scale=kv.k_scale, v_scale=kv.v_scale, **kw)


def get_rope_tables(cfg: ModelConfig, max_seq: int):
    """(cos, sin) for the attention layers, None where they do not
    rotate."""
    return llama.get_rope_tables(cfg, max_seq) if cfg.use_rope else None


kv_tables = llama.kv_tables      # one table a layer (models.family)
chunk_block = llama.chunk_block  # a cursor walk (models.family)


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    return cfg.n_kv_heads, cfg.head_dim


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


def decode_attend(cache: HybridCache, i, lengths, live, block_s, mesh):
    """``attend(q, k_new, v_new)`` of attention layer ``i``'s decode step:
    the kernel over the live blocks of the rows where they lie
    (``block_s``: ``decode_kv_block``'s answer), or the reference on the
    layer's slice."""
    if block_s:
        return lambda q, k_new, v_new: flash_decode.decode_attention_auto(
            q, cache.k, cache.v, k_new, v_new, live, i, cache.k_scale,
            cache.v_scale, block_s=block_s, mesh=mesh)

    def attend(q, k_new, v_new):
        k_l, v_l, ks_l, vs_l = cache.layer_rows(i)
        return decode_attention_appended(
            q, k_l, v_l, k_new, v_new, lengths, ks_l, vs_l)
    return attend


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
