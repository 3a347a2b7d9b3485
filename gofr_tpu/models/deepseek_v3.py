"""The latent-attention, grouped-router family (``model_type: deepseek_v3``).

The generator picks this module where ``cfg.kv_lora_rank > 0``
(``models.family``) and calls it through the same entry points as
``models/llama.py``: ``init``, ``init_cache``, ``get_rope_tables``,
``prefill_kv``, ``write_kv``, ``prefill_chunk``, ``decode_step``.

What differs from the Llama block, by equation (``x`` the residual
stream, RMSNorm before attention and before the feed-forward):

  - latent attention (MLA): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
    a head ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva`` with
    ``c_kv`` normed and ``k_pe`` rotated, ONE for all heads. The cache
    holds that row, ``kv_lora_rank + qk_rope_head_dim`` values a token a
    layer, and nothing a head. A whole-prompt prefill expands keys and
    values a head from ``c_kv W_kvb`` over the prompt; decode, and a
    chunk over the rows cached before it, are *absorbed*: ``W_kvb`` a
    head is ``[W_UK | W_UV]``, ``q_abs = q_nope W_UK^T``, the score is
    ``[q_abs | q_pe] . row``, the output ``(sum p . c_kv) W_UV``
    (ops/mla.py). Query/key width (nope + rope) and value width are
    separate keys; nothing here takes them to be equal.
  - rotary tables: YaRN (ops/rope.py) on the rope part only; the softmax
    scale is ``(nope + rope)^-1/2`` times YaRN's factor. Pairing: the
    rope dims are rotated as halves in the order the projection gives
    them (the published code first gathers even and odd dims; with
    seeded weights that is a permutation of the projection's columns).
  - feed-forward: the first ``n_dense_layers`` layers are SwiGLU of
    width ``ffn_dim``; the others route through ``models/moe.py``
    (grouped sigmoid router, ``n_shared_experts`` shared experts always
    on, ``n_experts_held`` of ``n_experts`` held here: the equations,
    the chip's share and the dispatch are said there).

Layers are two stacks, ``params["dense_layers"]`` and
``params["layers"]`` (the routed ones), each scanned; cache layer ``l``
is dense layer ``l`` or routed layer ``l - n_dense_layers``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops import mla
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from ..ops.rope import apply_rope, rope_frequencies
from . import latent, llama, moe
from .blocks import embed, experts_apart, prompt_rows
from .common import ModelConfig, dense_init, refused_options
from .latent import LatentCache

_ROPE_CACHE: dict[tuple, tuple] = {}
RECOMPUTABLE = True  # rows, as llama's: see models.family


def get_rope_tables(cfg: ModelConfig, max_seq: int):
    """Memoized (cos, sin) [max_seq, qk_rope_head_dim // 2]."""
    scaling_key = tuple(sorted(cfg.rope_scaling.items())) \
        if cfg.rope_scaling else None
    key = (cfg.qk_rope_head_dim, max_seq, cfg.rope_theta, scaling_key)
    if key not in _ROPE_CACHE:
        tables = rope_frequencies(cfg.qk_rope_head_dim, max_seq,
                                  cfg.rope_theta, cfg.rope_scaling)
        if any(isinstance(t, jax.core.Tracer) for t in tables):
            return tables
        _ROPE_CACHE[key] = tables
    return _ROPE_CACHE[key]


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> LatentCache:
    return LatentCache(
        rows=jnp.zeros((cfg.n_layers, batch, max_seq or cfg.max_seq,
                        latent.sizes(cfg).stored_width),
                       dtype or cfg.jdtype),
        lengths=jnp.zeros((batch,), jnp.int32))


kv_tables = llama.kv_tables      # one table a layer (models.family)


def chunk_block(cfg: ModelConfig, max_seq: int) -> int:
    """``llama.chunk_block``, of latent rows (``mla.chunk_attention``)."""
    return mla.chunk_block(max_seq)


def chunk_walk_kernel(cfg: ModelConfig, max_seq: int, chunk: int) -> bool:
    """Whether a chunk program of ``chunk`` positions walks its cached
    rows in ``mla.chunk_walk_latent`` (``mla.chunk_tile`` says from
    backend and shapes); the engine counts its dispatches by it."""
    return bool(latent.walk_tile(latent.sizes(cfg), chunk, max_seq,
                                 cfg.jdtype))


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(heads, values a head) of a cached token, for the prefix index's
    shape contract: one shared row."""
    return 1, latent.sizes(cfg).stored_width


def decode_kv_block(cfg: ModelConfig, cache: LatentCache, mesh=None):
    """Cache positions a decode work item covers, None on the reference
    path (ops.mla.decode_block)."""
    return mla.decode_block(cache.rows, cfg.kv_lora_rank)


# the serving options this family does not run yet, and why (the engine
# raises on any of them at start-up)
REFUSED = {
    "mesh": "the latent row is shared by all heads and the expert share "
            "has no exchange across chips; the family runs on one chip",
    "paged_blocks": "the block pool holds K and V a head, not latent rows",
    "kvcache": "the host and Redis tiers frame K and V a head",
    "spec_decode_k": "there is no verify pass over latent rows",
    "lora_adapters": "adapters target wq/wk/wv/wo, which this family does "
                     "not have",
    "kv_dtype": "int8: the latent row is cached in the model's type "
                "(bfloat16)",
    "serving_role": "KV shipping frames K and V a head",
}
unsupported_options = functools.partial(refused_options, REFUSED)

# what ``GenerationEngine.stats()`` says of this family: the decode step's
# expert dispatch
serving_stats = moe.serving_stats


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params of the share this chip holds: every norm, the
    router (``n_experts`` wide) and its bias whole, ``n_experts_held``
    experts a routed layer."""
    dt = cfg.jdtype
    k = iter(jax.random.split(key, 24))
    D, H, V = cfg.dim, cfg.n_heads, cfg.vocab_size
    Rq, R = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    nd = cfg.n_dense_layers
    ns = cfg.n_layers - nd

    def attn(L):
        return {
            "attn_norm": jnp.ones((L, D), dt),
            "w_qa": dense_init(next(k), (L, D, Rq), dt),
            "q_norm": jnp.ones((L, Rq), dt),
            "w_qb": dense_init(next(k), (L, Rq, H * (dn + dr)), dt),
            "w_kva": dense_init(next(k), (L, D, R + dr), dt),
            "kv_norm": jnp.ones((L, R), dt),
            "w_kvb": dense_init(next(k), (L, R, H * (dn + dv)), dt),
            "wo": dense_init(next(k), (L, H * dv, D), dt),
            "ffn_norm": jnp.ones((L, D), dt),
        }

    params = {
        "embedding": dense_init(next(k), (V, D), dt, scale=0.02),
        "dense_layers": {
            **attn(nd),
            "w_gate": dense_init(next(k), (nd, D, cfg.ffn_dim), dt),
            "w_up": dense_init(next(k), (nd, D, cfg.ffn_dim), dt),
            "w_down": dense_init(next(k), (nd, cfg.ffn_dim, D), dt),
        },
        "layers": {
            **attn(ns),
            **moe.init_routed(k, cfg, ns),
        },
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(next(k), (D, V), dt)
    return params


# -- attention -----------------------------------------------------------------

def _layer(x, lw, cfg: ModelConfig, cos, sin, positions, attend, ffn,
           valid=None):
    """One block. ``attend(q, row, w_kvb) -> [B, S, H, dv]``: q is rotated
    and scaled, row is this call's ``[c_kv | k_pe]`` [B, S, row_width].
    Returns (x, row padded to the stored width, the ffn's counts)."""
    B, S = x.shape[:2]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla/q_proj"):
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        c_q = rms_norm(qmatmul(h, lw["w_qa"]), lw["q_norm"], cfg.norm_eps)
        q = qmatmul(c_q, lw["w_qb"]).reshape(B, S, H, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], cos, sin, positions)], -1)
        q = (q.astype(jnp.float32) * latent.softmax_scale(
            latent.sizes(cfg), cfg.rope_scaling)).astype(x.dtype)
        kva = qmatmul(h, lw["w_kva"])
        c_kv = rms_norm(kva[..., :R], lw["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(kva[..., None, R:], cos, sin, positions)[..., 0, :]
        row = jnp.concatenate([c_kv, k_pe], -1)
    o = attend(q, row, lw["w_kvb"])
    with jax.named_scope("attn_out"):
        x = x + qmatmul(o.reshape(B, S, H * cfg.v_head_dim), lw["wo"])
    h = rms_norm(x, lw["ffn_norm"], cfg.norm_eps)
    y, counts = ffn(h, lw, cfg, valid)
    return x + y, latent.pad_row(row, latent.sizes(cfg)), counts


def _two_stacks(params, cfg: ModelConfig, x, body, per_layer):
    """Scan the dense stack, then the routed one. ``body(x, lw, extra,
    ffn) -> (x, ys)``; ``per_layer``: a pytree of [L, ...] arrays sliced
    a layer beside the weights (the cache, the layer index). Returns (x,
    the dense stack's ys, the routed stack's ys)."""
    nd = cfg.n_dense_layers

    def run(x, stack, lo, hi, ffn):
        extra = jax.tree_util.tree_map(lambda a: a[lo:hi], per_layer)
        # the expert stacks stay whole beside the scan (``layer_at``
        # says why); a dense stack's leaves of the same names are sliced
        whole, sliced = experts_apart(params[stack]) \
            if ffn is moe.moe_ffn else ({}, params[stack])

        def step(x, xs):
            lw, ex, i = xs
            if whole:
                lw = {**lw, "experts": (whole, i)}
            return body(x, lw, ex, ffn)

        return jax.lax.scan(step, x, (sliced, extra,
                                      jnp.arange(hi - lo, dtype=jnp.int32)))

    x, ys_d = run(x, "dense_layers", 0, nd, moe.dense_ffn)
    x, ys_s = run(x, "layers", nd, cfg.n_layers, moe.moe_ffn)
    return x, ys_d, ys_s


def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded), attention
    expanded. Returns (logits [B, S, V] float32, or [B, 1, V] with
    ``logit_pos``; rows [L, B, S, stored_width]; lengths [B])."""
    lengths, positions, valid = prompt_rows(tokens, lengths)
    cos, sin = rope_tables or get_rope_tables(cfg,
                                              rope_max or tokens.shape[1])
    sz = latent.sizes(cfg)

    def attend(q, row, w_kvb):
        k_nope, k_pe, v = latent.expand(row, w_kvb, sz)
        return mla.prefill_attention(q, k_nope, k_pe, v, mask=valid)

    def body(x, lw, _, ffn):
        x, row, _ = _layer(x, lw, cfg, cos, sin, positions, attend, ffn,
                           valid)
        return x, row

    x, rows_d, rows_s = _two_stacks(params, cfg, embed(params, cfg, tokens),
                                    body, None)
    return (llama.logits_at(params, cfg, x, logit_pos),
            jnp.concatenate([rows_d, rows_s]), lengths)


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``)."""
    return prefill_kv(params, cfg, tokens, lengths, logit_pos=logit_pos)[0]


@jax.named_scope("kv_write")
def write_kv(cache: LatentCache, rows, index, lengths) -> LatentCache:
    """Write row stacks [L, B', S', stored_width] at ``index`` (start
    indices, one an axis); the cache with ``lengths`` replaced."""
    return LatentCache(jax.lax.dynamic_update_slice(
        cache.rows, rows.astype(cache.rows.dtype), index), lengths)


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: LatentCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C) against the
    growing cache: absorbed over the rows before it, expanded within
    itself. ``cache.lengths`` is not advanced (llama.prefill_chunk's
    contract). Returns (logits or None, the cache with the rows
    written)."""
    B, C = tokens.shape
    cos, sin = rope_tables or get_rope_tables(cfg, cache.rows.shape[2])
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    sz = latent.sizes(cfg)

    def body(x, lw, layer_rows, ffn):
        def attend(q, row, w_kvb):
            k_nope, k_pe, v = latent.expand(row, w_kvb, sz)
            o_lat, o_new = mla.chunk_attention(
                latent.absorb(q, w_kvb, sz), q, layer_rows, start, k_nope,
                k_pe, v, sz.rank)
            return latent.unabsorb(o_lat, w_kvb, sz, q.dtype) + o_new

        x, row, _ = _layer(x, lw, cfg, cos, sin, positions, attend, ffn)
        return x, row

    x, rows_d, rows_s = _two_stacks(params, cfg, embed(params, cfg, tokens),
                                    body, cache.rows)
    cache = write_kv(cache, jnp.concatenate([rows_d, rows_s]),
                     (0, 0, start, 0), cache.lengths)
    if not compute_logits:
        return None, cache
    return llama.logits_at(params, cfg, x, logit_pos), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: LatentCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B]: absorbed attention over each
    slot's live rows, the step's rows written by one scatter after the
    layer loops (llama.decode_step's discipline and capacity contract).

    ``active`` [B] bool: slots that are decoding; attention reads no row
    of the others and the expert layer dispatches none of their tokens.
    Returns (logits [B, V] float32, the cache with lengths + 1, the
    assignments a routed layer a held expert [Ls, Eh] int32)."""
    B = tokens.shape[0]
    cos, sin = rope_tables or get_rope_tables(cfg, cache.rows.shape[2])
    sz = latent.sizes(cfg)
    lengths = cache.lengths
    positions = lengths[:, None]
    live = lengths if active is None else jnp.where(active, lengths, 0)
    valid = None if active is None else active[:, None]
    block_s = mla.decode_block(cache.rows, cfg.kv_lora_rank)

    def body(x, lw, li, ffn):
        def attend(q, row, w_kvb):
            o_lat = mla.decode_attention(
                latent.absorb(q, w_kvb, sz)[:, 0], cache.rows,
                latent.pad_row(row[:, 0], sz), live, li,
                rank=sz.rank, block_s=block_s)
            return latent.unabsorb(o_lat[:, None], w_kvb, sz, q.dtype)

        x, row, counts = _layer(x, lw, cfg, cos, sin, positions, attend,
                                ffn, valid)
        return x, (row[:, 0], counts)

    x, (rows_d, _), (rows_s, counts) = _two_stacks(
        params, cfg, embed(params, cfg, tokens[:, None]), body,
        jnp.arange(cfg.n_layers, dtype=jnp.int32))
    with jax.named_scope("kv_write"):
        # one update a (layer, slot) with the row as its window: with the
        # layer axis in the window too (``.at[:, slots, lengths]``) the
        # scatter wants layers next to lanes, and XLA converts the whole
        # cache to that layout and back, every step (two 3 GB copies at
        # 9 x 128 x 2,048 x 640; PERF.md, Findings PR 28)
        rows = jnp.concatenate([rows_d, rows_s]).astype(cache.rows.dtype)
        new = LatentCache(
            cache.rows.at[jnp.arange(cfg.n_layers)[:, None],
                          jnp.arange(B)[None, :],
                          lengths[None, :]].set(rows, mode="drop"),
            lengths + 1)
    return llama.logits(params, cfg, x[:, 0]), new, counts
