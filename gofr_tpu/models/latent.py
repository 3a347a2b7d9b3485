"""What the latent-attention families share and no family owns: the
cache of one row ``[c_kv | k_pe]`` a token a layer, and the absorbed
form's algebra around ``ops/mla.py``.

Sizes are arguments (``Sizes``), not read from the configuration: one
model may run latent attention at two widths (``models/dots3_note.py``'s
window layers have their own ranks and head sizes beside the full
layers'), and a family with one width (``models/deepseek_v3.py``) says
``sizes(cfg)``.

``W_kvb`` a head is ``[W_UK | W_UV]``: ``absorb`` takes ``q_nope``
through ``W_UK^T`` so that a head's score against a cached row is one dot
product over the row, ``unabsorb`` takes the probability-weighted sum of
latents through ``W_UV``, ``expand`` materialises keys and values a head
over a call's own tokens.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import mla
from ..ops.quant import QuantizedLinear, qmatmul
from ..ops.rope import yarn_softmax_scale
from .common import ModelConfig

LANES = 128


class Sizes(NamedTuple):
    """One kind of latent attention: query heads, the latent's rank, a
    head's unrotated and rotated key widths and its value width."""

    heads: int
    rank: int
    nope: int
    rope: int
    value: int

    @property
    def row_width(self) -> int:
        """Values a cached row holds: the latent and the shared rotated
        key."""
        return self.rank + self.rope

    @property
    def stored_width(self) -> int:
        """Lanes a cached row takes: ``row_width`` rounded up to whole
        HBM tiles of 128 lanes (576 -> 640; ops/mla.py says why)."""
        return -(-self.row_width // LANES) * LANES


def walk_tile(sz: Sizes, chunk: int, table: int, dtype) -> int | None:
    """``mla.chunk_tile`` for a chunk of ``chunk`` positions of this kind
    of latent attention over a table of ``table`` cached rows: the
    queries a tile of the chunk walk's kernel, None on the jnp loop."""
    return mla.chunk_tile(chunk, sz.heads, table, sz.stored_width, sz.rank,
                          dtype)


def sizes(cfg: ModelConfig) -> Sizes:
    """The configuration's own latent attention (its full layers')."""
    return Sizes(cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                 cfg.qk_rope_head_dim, cfg.v_head_dim)


class LatentCache(NamedTuple):
    """Preallocated decode cache of latent rows, per-slot cursors."""

    rows: jnp.ndarray     # [L, B, Smax, stored_width]
    lengths: jnp.ndarray  # [B] int32: valid rows a slot

    @property
    def quantized(self) -> bool:
        return False


def softmax_scale(sz: Sizes, rope_scaling: dict | None = None) -> float:
    """``(nope + rope)^-1/2`` times YaRN's factor where the tables are
    YaRN's."""
    return (sz.nope + sz.rope) ** -0.5 * yarn_softmax_scale(rope_scaling)


def split_kvb(w_kvb, sz: Sizes):
    """``W_kvb`` [rank, H * (dn + dv)] a head: (W_UK [rank, H, dn], its
    output-channel scale [H, dn] or None, W_UV [rank, H, dv], scale)."""
    H, dn, dv = sz.heads, sz.nope, sz.value
    if isinstance(w_kvb, QuantizedLinear):
        w = w_kvb.w.reshape(-1, H, dn + dv)
        s = w_kvb.scale.reshape(H, dn + dv)
        return w[..., :dn], s[:, :dn], w[..., dn:], s[:, dn:]
    w = w_kvb.reshape(-1, H, dn + dv)
    return w[..., :dn], None, w[..., dn:], None


@jax.named_scope("mla/q_absorb")
def absorb(q, w_kvb, sz: Sizes):
    """q [B, S, H, dn + dr] (scaled) -> q_cat [B, S, H, stored_width]:
    ``[q_nope W_UK^T | q_pe | 0]``. An int8 ``W_UK``'s output-channel
    scale folds into ``q_nope``."""
    dn = sz.nope
    w_uk, s_uk, _, _ = split_kvb(w_kvb, sz)
    q_nope = q[..., :dn]
    if s_uk is not None:
        q_nope = (q_nope.astype(jnp.float32) * s_uk).astype(q.dtype)
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk.astype(q.dtype),
                       preferred_element_type=jnp.float32).astype(q.dtype)
    pad = sz.stored_width - sz.row_width
    return jnp.pad(jnp.concatenate([q_abs, q[..., dn:]], -1),
                   ((0, 0), (0, 0), (0, 0), (0, pad)))


def unabsorb(o_lat, w_kvb, sz: Sizes, dtype):
    """o_lat [B, S, H, rank] -> [B, S, H, dv]: through ``W_UV``, whose
    int8 output-channel scale folds into the result."""
    _, _, w_uv, s_uv = split_kvb(w_kvb, sz)
    o = jnp.einsum("bshr,rhd->bshd", o_lat.astype(dtype), w_uv.astype(dtype),
                   preferred_element_type=jnp.float32)
    if s_uv is not None:
        o = o * s_uv
    return o.astype(dtype)


def expand(row, w_kvb, sz: Sizes):
    """Keys and values a head over the chunk's own rows [B, S, width]:
    (k_nope [B, S, H, dn], k_pe [B, S, dr], v [B, S, H, dv])."""
    R, dn = sz.rank, sz.nope
    B, S = row.shape[:2]
    kv = qmatmul(row[..., :R], w_kvb).reshape(B, S, sz.heads, -1)
    return kv[..., :dn], row[..., R:], kv[..., dn:]


def pad_row(row, sz: Sizes):
    """``row`` [..., row_width] with zeros to the stored width."""
    pad = sz.stored_width - sz.row_width
    return jnp.pad(row, ((0, 0),) * (row.ndim - 1) + ((0, pad),))
