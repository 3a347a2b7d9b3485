"""The sparse-latent family (``model_type: dots3_note``): latent attention
at two widths in one stack, a learned selection on the full layers, a
ring of latent rows on the window layers, three tables in one cache.

The generator picks this module where ``cfg.kv_lora_rank > 0`` and
``cfg.layer_pattern`` names a ``"window"`` layer (``models.family``) and
calls it through the same entry points as ``models/llama.py``.
``cfg.layer_pattern`` is one period of the stack; layer ``l`` is of kind
``pattern[l % len(pattern)]`` (a stack that does not tile, as a pipeline
stage's ``(full, full, window x 3, full, window x 3)``, is one period of
``n_layers`` entries). ``x`` is the residual stream, pre-norm blocks:
``x += Attn(h); x += FFN(RMSNorm(x))``, ``h = RMSNorm(x)``.

Both kinds are latent attention (models/latent.py, ops/mla.py), each at
its own sizes (``sizes``):

  c_q = RMSNorm(h W_qa) * rq;  q = c_q W_qb, a head [q_nope | q_pe]
  [c_kv | k_pe] = h W_kva;  c_kv = RMSNorm(c_kv) * rkv;  RoPE on q_pe
  and k_pe (plain frequencies, a theta a kind)
  [k_nope | v] = c_kv W_kvb a head; scores (q_nope . k_nope + q_pe .
  k_pe) * (nope + rope)^-1/2; a head's output times sigmoid(h W_g)_head
  before W_o

``rq = (dim / q rank)^1/2`` and ``rkv = (dim / kv rank)^1/2``: the
published switch ``lora_rescale``. The scaled ``c_q`` also feeds the
indexer. The gate a head, the rescale and the indexer are the family,
not options of it: ``counts`` refuses a configuration without one (a
full layer with no indexer is ``deepseek_v3``'s).

  - a FULL layer caches the row ``[c_kv | k_pe]`` a token, [Lf, B, Smax,
    stored width], and beside it the INDEXER's key, [Lf, B, Smax,
    index_head_dim]: ``kI = LayerNorm(h W_Ik)``, its first
    ``qk_rope_head_dim`` values rotated. A query brings ``qI = c_q
    W_Iq`` (``index_heads`` heads, rotated alike) and ``w = h W_Iw *
    index_heads^-1/2 * index_head_dim^-1/2``, scores every position
    ``s <= t``, its own among them, as ``sum_j w_j relu(qI_j . kI_s)``
    and keeps the ``index_topk`` best (ops/dsa.py); the softmax runs over
    those alone. A program whose contexts cannot pass ``index_topk``
    (a prompt bucket no longer than it) computes no score.
  - a WINDOW layer sees ``window_size`` positions, the token's own among
    them, and caches the ``window_size - 1`` before it on a RING
    [Lw, B, W, stored width], position p at row p % W: a slot's memory
    does not grow past W rows a layer, and a position that has been
    overwritten cannot be computed again (``RECOMPUTABLE``). A decode
    step reads min(len, W) rows in whatever order they lie and then
    overwrites the oldest with its own.

Decode and a chunk over cached rows are absorbed; a whole prompt and a
chunk within itself are expanded. The first ``n_dense_layers`` layers'
feed-forward is SwiGLU of width ``ffn_dim``; every other layer's is the
routed one of ``models/moe.py``. Weights are stacked a kind of attention
(``params["full"]``, ``params["window"]``) and a kind of feed-forward
(``params["dense"]``, ``params["moe"]``) and run by
``blocks.period_stack``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import dsa, mla
from ..ops.attention import ring_held
from ..ops.norms import layer_norm, rms_norm
from ..ops.quant import qmatmul
from ..ops.rope import apply_rope, apply_rope_part
from . import latent, llama, moe
from .blocks import embed, period_stack, prompt_rows
from .common import ModelConfig, dense_init, refused_options

# a ring row that has been overwritten is gone: see models.family
RECOMPUTABLE = False
KINDS = ("full", "window")
F32 = jnp.float32


def counts(cfg: ModelConfig) -> dict[str, int]:
    """Layers of each kind in the stack."""
    pat = cfg.layer_pattern
    if not pat or cfg.n_layers % len(pat) or set(pat) - set(KINDS) \
            or cfg.window_size < 2:
        raise ValueError(f"layer_pattern {pat!r} does not tile "
                         f"{cfg.n_layers} layers of full and window kinds "
                         f"(window_size {cfg.window_size})")
    if not (cfg.index_topk > 0 and cfg.head_gate and cfg.lora_rescale):
        raise ValueError(
            f"{cfg.name}: the sparse-latent family has an indexer, a gate "
            f"a head and the rescaled latents (index_topk "
            f"{cfg.index_topk}, head_gate {cfg.head_gate}, lora_rescale "
            f"{cfg.lora_rescale})")
    return {k: cfg.n_layers // len(pat) * pat.count(k) for k in KINDS}


def sizes(cfg: ModelConfig, kind: str) -> latent.Sizes:
    """The latent attention of one kind of layer; a window size left 0
    is the full layers'."""
    full = latent.sizes(cfg)
    if kind == "full":
        return full
    return latent.Sizes(cfg.window_heads or full.heads,
                        cfg.window_kv_lora_rank or full.rank,
                        cfg.window_qk_nope_head_dim or full.nope,
                        cfg.window_qk_rope_head_dim or full.rope,
                        cfg.window_v_head_dim or full.value)


def q_rank(cfg: ModelConfig, kind: str) -> int:
    return cfg.window_q_lora_rank or cfg.q_lora_rank if kind == "window" \
        else cfg.q_lora_rank


def ring_rows(cfg: ModelConfig) -> int:
    """Rows of a window layer's ring: the positions a token sees beside
    its own."""
    return cfg.window_size - 1


class SparseLatentCache(NamedTuple):
    """The slots' memory, three tables; every array but ``lengths`` is
    [L, B, ...], which is all the engine's row helpers ask. In the
    model's type (an int8 row is refused at start-up)."""

    rows: jnp.ndarray     # [Lf, B, Smax, stored width of a full layer]
    keys: jnp.ndarray     # [Lf, B, Smax, index_head_dim]
    ring: jnp.ndarray     # [Lw, B, W, stored width of a window layer]
    lengths: jnp.ndarray  # [B] int32

    quantized = False


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> SparseLatentCache:
    n, dt = counts(cfg), dtype or cfg.jdtype
    smax = max_seq or cfg.max_seq
    return SparseLatentCache(
        rows=jnp.zeros((n["full"], batch, smax,
                        sizes(cfg, "full").stored_width), dt),
        keys=jnp.zeros((n["full"], batch, smax, cfg.index_head_dim), dt),
        ring=jnp.zeros((n["window"], batch, ring_rows(cfg),
                        sizes(cfg, "window").stored_width), dt),
        lengths=jnp.zeros((batch,), jnp.int32))


def get_rope_tables(cfg: ModelConfig, max_seq: int) -> dict:
    """(cos, sin) a kind of layer over its rotated key width, from
    llama's memo: the full layers' (and the indexer's) under
    ``rope_theta`` and ``rope_scaling``, the window layers' plainly
    under ``window_rope_theta``."""
    return {
        "full": llama.get_rope_tables(
            cfg.with_(attn_head_dim=sizes(cfg, "full").rope), max_seq),
        "window": llama.get_rope_tables(
            cfg.with_(attn_head_dim=sizes(cfg, "window").rope,
                      rope_theta=cfg.window_rope_theta or cfg.rope_theta,
                      rope_scaling=None), max_seq)}


kv_tables = llama.kv_tables      # one table a layer (models.family)


def chunk_block(cfg: ModelConfig, max_seq: int) -> int:
    """0: a chunk walks its rows under a mask a query (what the
    selection kept, what a ring holds), not under the one cursor
    ``llama.chunk_block`` counts by."""
    return 0


def chunk_walk_kernel(cfg: ModelConfig, max_seq: int, chunk: int) -> bool:
    """Whether a chunk program of ``chunk`` positions walks its cached
    rows in ``mla.chunk_walk_latent``, a full layer's rows and a window
    layer's ring both (``mla.chunk_tile`` says from backend and shapes);
    the engine counts its dispatches by it."""
    return all(latent.walk_tile(sizes(cfg, kind), chunk, table, cfg.jdtype)
               for kind, table in (("full", max_seq),
                                   ("window", ring_rows(cfg))))


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(heads, values a head) of a cached token, for the prefix index's
    shape contract: one shared row."""
    return 1, sizes(cfg, "full").stored_width


def decode_kv_block(cfg: ModelConfig, cache: SparseLatentCache, mesh=None):
    """Cache positions a decode work item of the full layers covers, None
    on the reference path (ops.mla.decode_block)."""
    return mla.decode_block(cache.rows, cfg.kv_lora_rank)


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of this family: the decode
    step's expert dispatch (``moe.serving_stats``), the bytes a cached
    token takes in the full layers' rows and in their index keys, the
    rows of a ring and the bytes a slot's rings take whatever its
    length, as HBM stores them in the model's type, and how many rows a
    full layer's selection keeps (benchmarks/metrics reads them here)."""
    n, size = counts(cfg), cfg.jdtype.itemsize
    return {**moe.serving_stats(cfg, slots),
            "latent_bytes_per_token":
                n["full"] * sizes(cfg, "full").stored_width * size,
            "index_bytes_per_token": n["full"] * cfg.index_head_dim * size,
            "window_rows": ring_rows(cfg),
            "window_bytes_per_slot": n["window"] * ring_rows(cfg)
            * sizes(cfg, "window").stored_width * size,
            "index_topk": cfg.index_topk}


# the serving options that take a slot's memory to be K and V rows, and
# why not (the engine raises on any of them at start-up)
REFUSED = {
    "mesh": "a latent row is shared by all heads, and the rings and the "
            "expert share have no exchange across chips; the family runs "
            "on one chip",
    "paged_blocks": "the block pool holds K and V a head, not latent rows, "
                    "index keys and a ring",
    "kvcache": "the host and Redis tiers frame K and V a head; a ring "
               "would not travel with them",
    "spec_decode_k": "a rejected draft's rows have already overwritten "
                     "the ring's oldest",
    "lora_adapters": "adapters target wq/wk/wv/wo, which this family does "
                     "not have",
    "kv_dtype": "int8: rows, index keys and rings are cached in the "
                "model's type (bfloat16)",
    "serving_role": "KV shipping frames K and V a head, not three tables",
}
unsupported_options = functools.partial(refused_options, REFUSED)


# the projections a rescaled latent feeds, and the fan-in their random
# weights are drawn at (``init``; tpu.random_params asks ``init.fan_in``
# for its int8 draws)
_RESCALED = ("w_qb", "w_kvb", "w_iq")


def _fan_in(cfg: ModelConfig, name: str) -> int | None:
    return cfg.dim if name in _RESCALED else None


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params of the share this chip holds, a stack a kind of
    attention and of feed-forward.

    The three projections a rescaled latent feeds are drawn at the
    standard deviation of a matrix the model dimension feeds, dim^-1/2
    and not rank^-1/2. The rescale is there to level the variance of
    what a latent feeds with what the residual stream feeds (k_pe)
    under weights of ONE standard deviation; fan-in weights level them
    already, and the factors on top make every attention logit
    (dim / q rank)^1/2 (dim / kv rank)^1/2 = 7 times what the same draw
    gives the other latent family: a softmax over thousands of rows
    that one row wins, which no trained model has and which turns a
    bfloat16 rounding, or one row the selection's near-tie swaps, into
    another answer (PERF.md, Findings PR 46)."""
    dt = cfg.jdtype
    ks = iter(jax.random.split(key, 48))
    n = counts(cfg)
    D, V, nd = cfg.dim, cfg.vocab_size, cfg.n_dense_layers
    ns = cfg.n_layers - nd
    Hi, di = cfg.index_heads, cfg.index_head_dim

    def attn(kind):
        L, sz, Rq = n[kind], sizes(cfg, kind), q_rank(cfg, kind)
        w = {"attn_norm": jnp.ones((L, D), dt),
             "w_qa": dense_init(next(ks), (L, D, Rq), dt),
             "q_norm": jnp.ones((L, Rq), dt),
             "w_qb": dense_init(next(ks),
                                (L, Rq, sz.heads * (sz.nope + sz.rope)), dt,
                                scale=D ** -0.5),
             "w_kva": dense_init(next(ks), (L, D, sz.row_width), dt),
             "kv_norm": jnp.ones((L, sz.rank), dt),
             "w_kvb": dense_init(
                 next(ks), (L, sz.rank, sz.heads * (sz.nope + sz.value)),
                 dt, scale=D ** -0.5),
             "wo": dense_init(next(ks), (L, sz.heads * sz.value, D), dt),
             # [D, heads]: small, and kept in the model's type
             "head_gate": dense_init(next(ks), (L, D, sz.heads), dt)}
        if kind == "full":
            w.update(
                w_iq=dense_init(next(ks), (L, Rq, Hi * di), dt,
                                scale=D ** -0.5),
                w_ik=dense_init(next(ks), (L, D, di), dt),
                # the key's LayerNorm and the heads' weights: small, and
                # kept in the model's type
                ik_norm=jnp.ones((L, di), dt),
                ik_bias=jnp.zeros((L, di), dt),
                w_iw=dense_init(next(ks), (L, D, Hi), dt))
        return w

    params = {
        "embedding": dense_init(next(ks), (V, D), dt, scale=0.02),
        **{kind: attn(kind) for kind in KINDS},
        "dense": {
            "ffn_norm": jnp.ones((nd, D), dt),
            "w_gate": dense_init(next(ks), (nd, D, cfg.ffn_dim), dt),
            "w_up": dense_init(next(ks), (nd, D, cfg.ffn_dim), dt),
            "w_down": dense_init(next(ks), (nd, cfg.ffn_dim, D), dt)},
        "moe": {"ffn_norm": jnp.ones((ns, D), dt),
                **moe.init_routed(ks, cfg, ns)},
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(next(ks), (D, V), dt)
    return params


init.fan_in = _fan_in


# -- one layer -----------------------------------------------------------------

def _rescaled(c, rank: int, cfg: ModelConfig):
    """A normed latent times (dim / rank)^1/2."""
    return (c.astype(F32) * (cfg.dim / rank) ** 0.5).astype(c.dtype)


def _index(h, c_q, lw, cfg: ModelConfig, rope, positions):
    """The indexer's side of a full layer for these tokens: (qI [B, S,
    Hi, d], kI [B, S, d], w [B, S, Hi] float32 with the scale in)."""
    B, S = h.shape[:2]
    Hi, di = cfg.index_heads, cfg.index_head_dim
    with jax.named_scope("dsa/index_proj"):
        q_idx = apply_rope_part(
            qmatmul(c_q, lw["w_iq"]).reshape(B, S, Hi, di), *rope, positions)
        k_idx = layer_norm(qmatmul(h, lw["w_ik"]), lw["ik_norm"],
                           lw["ik_bias"], cfg.norm_eps)
        k_idx = apply_rope_part(k_idx[:, :, None], *rope, positions)[:, :, 0]
        w_idx = jnp.einsum("bsd,dh->bsh", h, lw["w_iw"],
                           preferred_element_type=F32) \
            * (Hi ** -0.5 * di ** -0.5)
    return q_idx, k_idx, w_idx


def _layer(x, lw, cfg: ModelConfig, kind: str, rope, positions, attend,
           valid):
    """One block. ``attend(q, row, w_kvb, index) -> ([B, S, H, dv], (rows
    kept, rows chosen among) or None)``: q is rotated and scaled, row is
    this call's ``[c_kv | k_pe]`` [B, S, row_width], index the indexer's
    (qI, kI, w) on a full layer and None on a window layer. Returns (x, what
    the layer caches and counts: (row padded to the stored width, kI,
    rows kept) or (row,), the expert layer's assignments or None)."""
    B, S = x.shape[:2]
    sz = sizes(cfg, kind)
    dn = sz.nope
    with jax.named_scope("mla/q_proj"):
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        c_q = _rescaled(rms_norm(qmatmul(h, lw["w_qa"]), lw["q_norm"],
                                 cfg.norm_eps), q_rank(cfg, kind), cfg)
        # llama.layer's barrier: the heads-major layout the reshape and
        # the rope want must not travel back into the matmul, or the
        # decode block transposes the whole w_qb stack of each kind at
        # the top of every dispatch (compile-only for the v5e:
        # s8[3,1024,24576] and s8[6,1024,16384], 175 MB)
        q = jax.lax.optimization_barrier(qmatmul(c_q, lw["w_qb"]))
        q = q.reshape(B, S, sz.heads, dn + sz.rope)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], *rope[kind], positions)],
            -1)
        q = (q.astype(F32) * latent.softmax_scale(
            sz, cfg.rope_scaling if kind == "full" else None)
             ).astype(x.dtype)
        kva = qmatmul(h, lw["w_kva"])
        c_kv = _rescaled(rms_norm(kva[..., :sz.rank], lw["kv_norm"],
                                  cfg.norm_eps), sz.rank, cfg)
        k_pe = apply_rope(kva[..., None, sz.rank:], *rope[kind],
                          positions)[..., 0, :]
        row = jnp.concatenate([c_kv, k_pe], -1)
    index = _index(h, c_q, lw, cfg, rope["full"], positions) \
        if kind == "full" else None
    o, kept = attend(q, row, lw["w_kvb"], index)
    with jax.named_scope("mla/head_gate"):
        gate = jax.nn.sigmoid(qmatmul(h, lw["head_gate"]).astype(F32))
        o = (o.astype(F32) * gate[..., None]).astype(x.dtype)
    with jax.named_scope("attn_out"):
        x = x + qmatmul(o.reshape(B, S, sz.heads * sz.value), lw["wo"])
    y, n = lw["ffn"](rms_norm(x, lw["ffn_norm"], cfg.norm_eps), lw, cfg,
                     valid)
    cached = (latent.pad_row(row, sz),)
    if kind == "full":
        cached += (index[1], jnp.zeros((2,), jnp.int32) if kept is None
                   else jnp.stack(kept))
    return x + y, cached, n


def _selects(cfg: ModelConfig, positions: int) -> bool:
    """Whether a program whose queries see up to ``positions`` positions
    can leave one out."""
    return positions > cfg.index_topk


# -- the ring's write ----------------------------------------------------------

def _ring_write(ring, new, start, end):
    """The ring [Lw, B, W, width] after positions [start, end) of ``new``
    [Lw, B, C, width] (position start + c at index c) went into it: row
    r takes the last position below ``end`` that falls on it, if the
    chunk holds it, and keeps what it has otherwise (a padded final
    chunk must not put its padding over rows the window still needs).
    ``start``: scalar; ``end``: [B]."""
    W, C = ring.shape[2], new.shape[2]
    held = ring_held(W, end)                                    # [B, W]
    mine = held >= jnp.maximum(start, 0)
    idx = jnp.clip(held - start, 0, C - 1)
    rows = jnp.take_along_axis(new, idx[None, :, :, None], axis=2)
    return jnp.where(mine[None, :, :, None], rows.astype(ring.dtype), ring)


@jax.named_scope("kv_write")
def write_kv(cache: SparseLatentCache, rows, keys, wrows, index, lengths
             ) -> SparseLatentCache:
    """Write what ``prefill_kv`` made for B' batch rows at ``index``
    (layer 0, batch row, position, 0): the full layers' rows and index
    keys from that position, the window layers' last W positions onto
    the slots' rings."""
    slot, n = index[1], wrows.shape[1]
    end = jax.lax.dynamic_slice_in_dim(lengths, slot, n)
    view = jax.lax.dynamic_slice_in_dim(cache.ring, slot, n, axis=1)
    return SparseLatentCache(
        rows=jax.lax.dynamic_update_slice(
            cache.rows, rows.astype(cache.rows.dtype), index),
        keys=jax.lax.dynamic_update_slice(
            cache.keys, keys.astype(cache.keys.dtype), index),
        ring=jax.lax.dynamic_update_slice_in_dim(
            cache.ring, _ring_write(view, wrows, index[2], end), slot,
            axis=1),
        lengths=lengths)


# -- the programs --------------------------------------------------------------

def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded), attention
    expanded. Returns (logits [B, S, V] float32, or [B, 1, V] with
    ``logit_pos``; the full layers' rows [Lf, B, S, stored width] and
    index keys [Lf, B, S, d]; the window layers' rows [Lw, B, S, stored
    width]; lengths [B])."""
    S = tokens.shape[1]
    lengths, positions, valid = prompt_rows(tokens, lengths)
    rope = rope_tables or get_rope_tables(cfg, rope_max or S)
    causal = jnp.tril(jnp.ones((S, S), bool))
    # a band no narrower than the prompt's bucket is no band
    band = ~jnp.tril(jnp.ones((S, S), bool), -cfg.window_size)[None] \
        if cfg.window_size < S else None

    def layer(x, lw, kind, i):
        sz = sizes(cfg, kind)

        def attend(q, row, w_kvb, index):
            keep = band if kind == "window" else None
            if kind == "full" and _selects(cfg, S):
                with jax.named_scope("dsa/select"):
                    q_idx, k_idx, w_idx = index
                    keep = dsa.kept(dsa.scores(q_idx, w_idx, k_idx),
                                    causal[None] & valid[:, None, :],
                                    cfg.index_topk)
            k_nope, k_pe, v = latent.expand(row, w_kvb, sz)
            return mla.prefill_attention(q, k_nope, k_pe, v, mask=valid,
                                         keep=keep), None

        return _layer(x, lw, cfg, kind, rope, positions, attend, valid)

    x, cached, _ = period_stack(params, cfg, embed(params, cfg, tokens),
                                layer)
    return (llama.logits_at(params, cfg, x, logit_pos), cached["full"][0],
            cached["full"][1], cached["window"][0], lengths)


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``)."""
    return prefill_kv(params, cfg, tokens, lengths, logit_pos=logit_pos)[0]


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: SparseLatentCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C) against the
    cache, absorbed over what is cached and expanded within itself: a
    full layer selects among the rows before it and the chunk's own, a
    window layer reads its ring as it stands; then the chunk's rows are
    written, onto the rings too (a chunk as long as a ring overwrites
    all of it). With ``logit_pos`` the chunk is the prompt's last and
    may be padded: positions past ``logit_pos`` do not reach the rings.
    ``cache.lengths`` is not advanced (llama.prefill_chunk's
    contract)."""
    B, C = tokens.shape
    smax, W = cache.rows.shape[2], cache.ring.shape[2]
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    rope = rope_tables or get_rope_tables(cfg, smax)
    n_valid = jnp.full((B,), C, jnp.int32) if logit_pos is None \
        else logit_pos.astype(jnp.int32) + 1
    valid = jnp.arange(C)[None, :] < n_valid[:, None]
    causal = jnp.tril(jnp.ones((C, C), bool))[None]
    before = jnp.broadcast_to(jnp.arange(smax) < start, (1, C, smax))
    # the window: the ring's rows by the positions they hold, and the
    # chunk's own tokens
    held = ring_held(W, start)                                   # [W]
    seen = ((held >= 0)
            & (held[None, :] >= positions[0][:, None] - W))[None]
    band = causal & ~jnp.tril(jnp.ones((C, C), bool), -cfg.window_size)[None]

    def layer(x, lw, kind, i):
        sz = sizes(cfg, kind)

        def attend(q, row, w_kvb, index):
            k_nope, k_pe, v = latent.expand(row, w_kvb, sz)
            if kind == "window":
                with jax.named_scope("mla/window_chunk"):
                    rows = jax.lax.dynamic_index_in_dim(cache.ring, i, 0,
                                                        keepdims=False)
                    o_lat, o_new = mla.chunk_attention_kept(
                        latent.absorb(q, w_kvb, sz), q, rows, k_nope, k_pe,
                        v, sz.rank, seen, band, jnp.minimum(start, W))
                return latent.unabsorb(o_lat, w_kvb, sz, q.dtype) + o_new, \
                    None
            rows, keys = (jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False)
                          for a in (cache.rows, cache.keys))
            keep_cache, keep_new = before, causal
            if _selects(cfg, smax):
                with jax.named_scope("dsa/select"):
                    q_idx, k_idx, w_idx = index
                    keep = dsa.kept(
                        dsa.scores(q_idx, w_idx, jnp.concatenate(
                            [keys.astype(k_idx.dtype), k_idx], 1)),
                        jnp.concatenate([before, causal], -1),
                        cfg.index_topk)
                    keep_cache, keep_new = keep[..., :smax], keep[..., smax:]
            o_lat, o_new = mla.chunk_attention_kept(
                latent.absorb(q, w_kvb, sz), q, rows, k_nope, k_pe, v,
                sz.rank, keep_cache, keep_new, start)
            return latent.unabsorb(o_lat, w_kvb, sz, q.dtype) + o_new, None

        return _layer(x, lw, cfg, kind, rope, positions, attend, valid)

    x, cached, _ = period_stack(params, cfg, embed(params, cfg, tokens),
                                layer)
    with jax.named_scope("kv_write"):
        rows, keys, _ = cached["full"]
        cache = SparseLatentCache(
            rows=jax.lax.dynamic_update_slice(
                cache.rows, rows.astype(cache.rows.dtype), (0, 0, start, 0)),
            keys=jax.lax.dynamic_update_slice(
                cache.keys, keys.astype(cache.keys.dtype), (0, 0, start, 0)),
            ring=_ring_write(cache.ring, cached["window"][0], start,
                             start + n_valid),
            lengths=cache.lengths)
    if not compute_logits:
        return None, cache
    return llama.logits_at(params, cfg, x, logit_pos), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: SparseLatentCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B], absorbed. A full layer scores the
    slot's cached index keys and the token's own, keeps the best
    ``index_topk`` and attends to those rows; a window layer reads its
    ring. The step's rows are written after the loop (llama.decode_step's
    discipline and capacity contract): a full layer's row and index key
    at the slot's position, a window layer's at position % W, over the
    oldest. A slot parked at capacity writes none of them, and a slot
    that is not decoding leaves its ring alone.

    ``active`` [B] bool: slots that are decoding; attention reads no row
    of the others and the expert layer dispatches none of their tokens.
    Returns (logits [B, V] float32, the cache with lengths + 1, the
    assignments a routed layer a held expert [Ls, Eh] int32, None: the
    family keeps no recurrent state, the rows each full layer's
    selection kept over the active slots and the rows it chose among,
    the cached ones and each token's own [Lf, 2] int32)."""
    B = tokens.shape[0]
    smax, W = cache.rows.shape[2], cache.ring.shape[2]
    lengths = cache.lengths
    positions = lengths[:, None]
    act = jnp.ones((B,), bool) if active is None else active
    live = jnp.where(act, lengths, 0)
    rope = rope_tables or get_rope_tables(cfg, smax)
    blocks = {"full": mla.decode_block(cache.rows, cfg.kv_lora_rank),
              "window": mla.decode_block(cache.ring,
                                         sizes(cfg, "window").rank)}
    score_block = dsa.scores_block(cache.keys)
    below = jnp.arange(smax)[None, :] < live[:, None]             # [B, Smax]

    def layer(x, lw, kind, i):
        sz = sizes(cfg, kind)

        def attend(q, row, w_kvb, index):
            q_cat = latent.absorb(q, w_kvb, sz)[:, 0]
            row_new = latent.pad_row(row[:, 0], sz)
            kept, among = None, jnp.sum(live + act, dtype=jnp.int32)
            if kind == "window":
                o_lat = mla.ring_decode_attention(
                    q_cat, cache.ring, row_new, live, i, rank=sz.rank,
                    block_s=blocks[kind])
            elif _selects(cfg, smax + 1):
                q_idx, k_idx, w_idx = index
                with jax.named_scope("dsa/select"):
                    score = jnp.concatenate(
                        [dsa.decode_scores(q_idx[:, 0], w_idx[:, 0],
                                           cache.keys, live, i,
                                           block_s=score_block),
                         dsa.scores(q_idx, w_idx, k_idx)[:, 0]], -1)
                    keep = dsa.kept(
                        score, jnp.concatenate(
                            [below, jnp.ones((B, 1), bool)], -1),
                        cfg.index_topk)
                    kept = (jnp.sum(jnp.where(act[:, None], keep, False),
                                    dtype=jnp.int32), among)
                o_lat = mla.sparse_decode_attention(
                    q_cat, cache.rows, row_new, live, i, keep[:, :smax],
                    keep[:, smax], rank=sz.rank, block_s=blocks[kind])
            else:
                o_lat = mla.decode_attention(
                    q_cat, cache.rows, row_new, live, i, rank=sz.rank,
                    block_s=blocks[kind])
                kept = (among, among)
            return latent.unabsorb(o_lat[:, None], w_kvb, sz, q.dtype), kept

        x, cached, n = _layer(x, lw, cfg, kind, rope, positions, attend,
                              act[:, None])
        # this step's row and key a slot; the counts as they are
        return x, tuple(c[:, 0] for c in cached[:2]) + cached[2:], n

    x, cached, n = period_stack(params, cfg,
                                embed(params, cfg, tokens[:, None]), layer)
    rows, keys, kept = cached["full"]
    with jax.named_scope("kv_write"):
        # one update a (layer, slot) with the row as its window
        # (models/deepseek_v3.py's decode_step says what a scatter with
        # the layer axis in the window costs); a cursor at capacity (a
        # slot parked while its prompt is chunk-written) drops its rows,
        # and so does a slot that is not decoding: every row of its ring
        # is live (the one at its cursor is the oldest its next token
        # sees, not a spare one as on a ring of window_size rows), so
        # the garbage row an idle slot writes elsewhere would land on it
        def put(table, new, at):
            return table.at[jnp.arange(table.shape[0])[:, None],
                            jnp.arange(B)[None, :], at[None, :]].set(
                                new.astype(table.dtype), mode="drop")

        new = SparseLatentCache(
            rows=put(cache.rows, rows, lengths),
            keys=put(cache.keys, keys, lengths),
            ring=put(cache.ring, cached["window"][0],
                     jnp.where(act & (lengths < smax), lengths % W, W)),
            lengths=lengths + 1)
    return llama.logits(params, cfg, x[:, 0]), new, n, None, kept
