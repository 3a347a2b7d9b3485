"""The window family (``model_type: laguna``): sliding-window layers and
full layers in one stack, a ring of rows beside whole rows in one cache.

The generator picks this module where ``cfg.layer_pattern`` names a
``"window"`` layer (``models.family``) and calls it through the same
entry points as ``models/llama.py``. ``cfg.layer_pattern`` is one period
of the stack, e.g. ``("full", "window", "window", "window")``; layer
``l`` is of kind ``pattern[l % len(pattern)]``. ``x`` is the residual
stream, pre-norm blocks: ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``.

  - a FULL layer is softmax attention over every cached position,
    ``n_heads`` query heads on ``n_kv_heads`` KV heads of ``head_dim``;
    the first ``rotary_dim`` values of a head are rotated
    (``rope_theta``, ``rope_scaling``: YaRN with its ``attention_factor``
    on the tables), the rest pass through. Its cache is llama's: K and V
    rows [Lf, B, KV, Smax, hd].
  - a WINDOW layer has ``window_heads`` query heads on the same KV
    heads, rotates the whole head by plain frequencies of
    ``window_rope_theta`` and attends to the ``window_size`` positions
    (p - W, p], the token's own among them. Its cache is a RING
    [Lw, B, KV, W, hd], position p at row p % W: a slot's memory does
    not grow past W rows a layer, and a position that has been
    overwritten cannot be computed again (``RECOMPUTABLE``). A decode
    step reads min(len, W) rows but the one its own position falls on
    (``ops.flash_decode.ring_rows``), through the same kernel as the
    full layers.
  - both kinds gate the heads' outputs where ``head_gate``:
    ``y = W_o concat(sigmoid(W_g h)_head * o_head)``, one value a head
    (``blocks.attention``, which the conv family's full layers run too).
  - the first ``n_dense_layers`` layers' feed-forward is SwiGLU of width
    ``ffn_dim``; every other layer's is the routed one of
    ``models/moe.py`` (``moe_ffn``: sigmoid router, a shared expert).

Weights are stacked a kind of attention (``params["full"]``,
``params["window"]``) and a kind of feed-forward (``params["dense"]``,
``params["moe"]``) and run by ``blocks.period_stack``: the stacks stay
whole and a layer's weights are indexed where they are used; the periods
that hold a dense layer run one after another, the rest are scanned a
period at a time.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import flash_decode
from ..ops.attention import (chunk_attention, decode_attention_appended,
                             ring_chunk_attention, ring_held)
from ..ops.flash import interpret_env
from ..ops.norms import rms_norm
from . import llama, moe
from .blocks import attention, embed, period_stack, prompt_attend, prompt_rows
from .common import ModelConfig, dense_init, refused_options

# a ring row that has been overwritten is gone: the chunk lattice runs
# left-aligned, and a prefix-pool row is usable only at the position its
# rings were taken
RECOMPUTABLE = False
KINDS = ("full", "window")


def counts(cfg: ModelConfig) -> dict[str, int]:
    """Layers of each kind in the stack."""
    pat = cfg.layer_pattern
    if not pat or cfg.n_layers % len(pat) or set(pat) - set(KINDS) \
            or cfg.window_size <= 0:
        raise ValueError(f"layer_pattern {pat!r} does not tile "
                         f"{cfg.n_layers} layers of full and window kinds "
                         f"(window_size {cfg.window_size})")
    return {k: cfg.n_layers // len(pat) * pat.count(k) for k in KINDS}


def heads(cfg: ModelConfig, kind: str) -> int:
    return cfg.window_heads or cfg.n_heads if kind == "window" \
        else cfg.n_heads


class WindowCache(NamedTuple):
    """The slots' memory of both kinds; every array but ``lengths`` is
    [L, B, ...], which is all the engine's row helpers ask. In the
    model's type: no scale planes (an int8 ring is refused at start-up)."""

    k: jnp.ndarray        # [Lf, B, KV, Smax, hd]
    v: jnp.ndarray
    wk: jnp.ndarray       # [Lw, B, KV, W, hd], position p at row p % W
    wv: jnp.ndarray
    lengths: jnp.ndarray  # [B] int32

    quantized = False

    @property
    def rows(self) -> llama.KVCache:
        """The full layers' part, as llama's helpers take it."""
        return llama.KVCache(self.k, self.v, self.lengths)

    @property
    def rings(self) -> llama.KVCache:
        return llama.KVCache(self.wk, self.wv, self.lengths)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> WindowCache:
    n = counts(cfg)
    kv = llama.init_cache(cfg.with_(n_layers=n["full"]), batch, max_seq,
                          dtype)
    ring = llama.init_cache(cfg.with_(n_layers=n["window"]), batch,
                            cfg.window_size, dtype)
    return WindowCache(k=kv.k, v=kv.v, wk=ring.k, wv=ring.v,
                       lengths=kv.lengths)


def get_rope_tables(cfg: ModelConfig, max_seq: int) -> dict:
    """(cos, sin) a kind of layer, from llama's memo: the full layers'
    over ``rotary_dim`` values of a head under ``rope_scaling``, the
    window layers' over all of it, plainly."""
    return {
        "full": llama.get_rope_tables(
            cfg.with_(attn_head_dim=cfg.rotary_dim or cfg.head_dim), max_seq),
        "window": llama.get_rope_tables(
            cfg.with_(rope_theta=cfg.window_rope_theta or cfg.rope_theta,
                      rope_scaling=None), max_seq)}


kv_tables = llama.kv_tables      # one table a layer (models.family)


def chunk_block(cfg: ModelConfig, max_seq: int) -> int:
    """``llama.chunk_block`` where a full layer walks a slot's rows; a
    ring is read whole, under no cursor."""
    return llama.chunk_block(cfg, max_seq) if "full" in cfg.layer_pattern \
        else 0


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    return cfg.n_kv_heads, cfg.head_dim


def decode_kv_block(cfg: ModelConfig, cache: WindowCache, mesh=None):
    return flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)


def _row_bytes(cfg: ModelConfig) -> int:
    """K and V of one cached position in one layer."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * cfg.jdtype.itemsize


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of this family: the decode
    step's expert dispatch shapes and path (``moe.serving_stats``),
    the rows of a ring and the bytes a slot's rings take whatever its
    length, and the bytes a cached token takes in the full layers, in
    the model's type (benchmarks/metrics reads them here)."""
    n = counts(cfg)
    return {**moe.serving_stats(cfg, slots),
            "window_rows": cfg.window_size,
            "window_bytes_per_slot": n["window"] * cfg.window_size
            * _row_bytes(cfg),
            "kv_bytes_per_token": n["full"] * _row_bytes(cfg)}


# the serving options that take a slot's memory to be whole rows, and why
# not (the engine raises on any of them at start-up)
REFUSED = {
    "mesh": "the rings and the expert layer have no sharding rule; the "
            "family runs on one chip",
    "paged_blocks": "the block pool holds whole K and V rows, not a ring",
    "kvcache": "the host and Redis tiers frame whole K and V rows; a ring "
               "would not travel with them",
    "spec_decode_k": "a rejected draft's rows have already overwritten "
                     "the ring's oldest",
    "lora_adapters": "adapters target the llama block's projections",
    "kv_dtype": "int8: rows and rings are cached in the model's type",
    "serving_role": "KV shipping frames whole K and V rows, not a ring",
}
unsupported_options = functools.partial(refused_options, REFUSED)


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params, a stack a kind of attention and of
    feed-forward."""
    dt = cfg.jdtype
    ks = iter(jax.random.split(key, 40))
    n = counts(cfg)
    D, V, KV, hd = cfg.dim, cfg.vocab_size, cfg.n_kv_heads, cfg.head_dim
    nd = cfg.n_dense_layers
    ns = cfg.n_layers - nd

    def attn(kind):
        L, H = n[kind], heads(cfg, kind)
        w = {"attn_norm": jnp.ones((L, D), dt),
             "wq": dense_init(next(ks), (L, D, H * hd), dt),
             "wk": dense_init(next(ks), (L, D, KV * hd), dt),
             "wv": dense_init(next(ks), (L, D, KV * hd), dt),
             "wo": dense_init(next(ks), (L, H * hd, D), dt)}
        if cfg.head_gate:
            # [D, heads]: small, and kept in the model's type
            w["head_gate"] = dense_init(next(ks), (L, D, H), dt)
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


# -- one layer -----------------------------------------------------------------

def _layer(x, lw, cfg: ModelConfig, kind: str, rope, positions, attend,
           valid):
    """One block: (x, (k, v) of these tokens, the expert layer's
    assignments a held expert or None)."""
    y, kv = attention(x, lw, cfg, heads(cfg, kind), rope[kind], positions,
                      attend)
    x = x + y
    y, n = lw["ffn"](rms_norm(x, lw["ffn_norm"], cfg.norm_eps), lw, cfg,
                     valid)
    return x + y, kv, n


# -- the ring's write ----------------------------------------------------------

def _ring_write(ring, new, start, end):
    """The ring [Lw, B, KV, W, hd] after positions [start, end) of
    ``new`` [Lw, B, C, KV, hd] (position start + c at index c) went into
    it: row r takes the last position below ``end`` that falls on it, if
    the chunk holds it, and keeps what it has otherwise (a padded final
    chunk must not put its padding over rows the window still needs).
    ``start``: scalar; ``end``: [B]."""
    W, C = ring.shape[3], new.shape[2]
    held = ring_held(W, end)                                    # [B, W]
    mine = held >= jnp.maximum(start, 0)
    idx = jnp.clip(held - start, 0, C - 1)
    rows = jnp.take_along_axis(new, idx[None, :, :, None, None], axis=2)
    return jnp.where(mine[None, :, None, :, None],
                     jnp.swapaxes(rows, 2, 3).astype(ring.dtype), ring)


@jax.named_scope("kv_write")
def write_kv(cache: WindowCache, k, v, wk, wv, index, lengths
             ) -> WindowCache:
    """Write what ``prefill_kv`` made for B' rows at batch row
    ``index[1]``: the full layers' K and V stacks from position
    ``index[3]`` (llama's write), the window layers' last W positions
    onto the slots' rings."""
    rows = llama.write_kv(cache.rows, k, v, index, lengths)
    slot, n = index[1], wk.shape[1]
    end = jax.lax.dynamic_slice_in_dim(lengths, slot, n)

    def put(ring, new):
        view = jax.lax.dynamic_slice_in_dim(ring, slot, n, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            ring, _ring_write(view, new, index[3], end), slot, axis=1)

    return WindowCache(k=rows.k, v=rows.v, wk=put(cache.wk, wk),
                       wv=put(cache.wv, wv), lengths=lengths)


# -- the programs --------------------------------------------------------------

def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded). Returns (logits
    [B, S, V] float32, or [B, 1, V] with ``logit_pos``; the full layers'
    K and V stacks [Lf, B, S, KV, hd]; the window layers' [Lw, B, S, KV,
    hd]; lengths [B])."""
    S = tokens.shape[1]
    lengths, positions, valid = prompt_rows(tokens, lengths)
    rope = rope_tables or get_rope_tables(cfg, rope_max or S)
    # a band no wider than the prompt's bucket is no band
    band = {"full": 0,
            "window": cfg.window_size if cfg.window_size < S else 0}

    def layer(x, lw, kind, i):
        return _layer(x, lw, cfg, kind, rope, positions, prompt_attend(
            flash, lengths, valid, mesh, band[kind]), valid)

    x, rows, _ = period_stack(params, cfg, embed(params, cfg, tokens), layer)
    return (llama.logits_at(params, cfg, x, logit_pos), *rows["full"],
            *rows["window"], lengths)


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``)."""
    return prefill_kv(params, cfg, tokens, lengths, logit_pos=logit_pos)[0]


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: WindowCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C) against the
    cache: the full layers attend to the rows before it, the window
    layers to their rings as they stand, both causally within the chunk;
    then the chunk's rows are written, onto the rings too (a chunk as
    long as a ring overwrites all of it). With ``logit_pos`` the chunk is
    the prompt's last and may be padded: positions past ``logit_pos`` do
    not reach the rings. ``cache.lengths`` is not advanced
    (llama.prefill_chunk's contract)."""
    B, C = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    rope = rope_tables or get_rope_tables(cfg, cache.k.shape[3])
    n_valid = jnp.full((B,), C, jnp.int32) if logit_pos is None \
        else logit_pos.astype(jnp.int32) + 1
    valid = jnp.arange(C)[None, :] < n_valid[:, None]

    def layer(x, lw, kind, i):
        def attend(q, k_new, v_new):
            if kind == "window":
                with jax.named_scope("attn/window_chunk"):
                    k_l, v_l = (jax.lax.dynamic_index_in_dim(
                        a, i, 0, keepdims=False)
                        for a in (cache.wk, cache.wv))
                    return ring_chunk_attention(q, k_l, v_l, k_new, v_new,
                                                start)
            k_l, v_l = (jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                        for a in (cache.k, cache.v))
            return chunk_attention(q, k_l, v_l, k_new, v_new, start)

        return _layer(x, lw, cfg, kind, rope, positions, attend, valid)

    x, rows, _ = period_stack(params, cfg, embed(params, cfg, tokens), layer)
    full = llama.write_kv(cache.rows, *rows["full"], (0, 0, 0, start, 0),
                          cache.lengths)
    with jax.named_scope("kv_write"):
        wk, wv = (_ring_write(ring, new, start, start + n_valid)
                  for ring, new in zip((cache.wk, cache.wv), rows["window"]))
    cache = WindowCache(k=full.k, v=full.v, wk=wk, wv=wv,
                        lengths=cache.lengths)
    if not compute_logits:
        return None, cache
    return llama.logits_at(params, cfg, x, logit_pos), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: WindowCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B]. Every layer reads its rows, or its
    ring, in place, and the step's rows are written after the loop
    (llama.decode_step's discipline and capacity contract): a full
    layer's at the slot's position, a window layer's at position % W,
    the row the step did not read. A slot parked at capacity writes
    neither.

    Returns (logits [B, V] float32, the cache with lengths + 1, the
    expert layer's assignments a routed layer a held expert [Ls, Eh]
    int32)."""
    B = tokens.shape[0]
    W = cfg.window_size
    lengths = cache.lengths
    positions = lengths[:, None]
    act = jnp.ones((B,), bool) if active is None else active
    live = jnp.where(act, lengths, 0)
    rope = rope_tables or get_rope_tables(cfg, cache.k.shape[3])
    blocks = {"full": flash_decode.kernel_block(cfg.n_heads, cache.k, mesh),
              "window": flash_decode.kernel_block(heads(cfg, "window"),
                                                  cache.wk, mesh)}
    read = {"full": (cache.k, cache.v, flash_decode.flash_decode_stacked),
            "window": (cache.wk, cache.wv, flash_decode.flash_decode_ring)}

    def layer(x, lw, kind, i):
        k_all, v_all, kernel = read[kind]

        def attend(q, k_new, v_new):
            with jax.named_scope(f"attn/{kind}_decode"):
                if blocks[kind]:
                    return kernel(q, k_all, v_all, k_new, v_new, live, i,
                                  block_s=blocks[kind],
                                  interpret=interpret_env())
                k_l, v_l = (jax.lax.dynamic_index_in_dim(
                    a, i, 0, keepdims=False) for a in (k_all, v_all))
                n_live, skip = flash_decode.ring_rows(live, W) \
                    if kind == "window" else (live, None)
                return decode_attention_appended(
                    q, k_l, v_l, k_new, v_new, n_live, exclude=skip)

        return _layer(x, lw, cfg, kind, rope, positions, attend,
                      act[:, None])

    x, rows, n = period_stack(params, cfg,
                              embed(params, cfg, tokens[:, None]), layer)
    with jax.named_scope("kv_write"):
        full = llama.write_rows(cache.rows, *rows["full"], positions,
                                lengths + 1, cfg.n_heads, mesh)
        # a cursor at capacity (a slot parked while its prompt is
        # chunk-written) must drop its row here as it does there
        at = jnp.where(positions < cache.k.shape[3], positions % W, W)
        ring = llama.write_rows(cache.rings, *rows["window"], at,
                                lengths + 1, heads(cfg, "window"), mesh)
    return (llama.logits(params, cfg, x[:, 0]),
            WindowCache(k=full.k, v=full.v, wk=ring.k, wv=ring.v,
                        lengths=lengths + 1), n)
