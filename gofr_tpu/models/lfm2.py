"""The conv family (``model_type: lfm2_moe``): gated short-convolution
layers and full-attention layers in one stack, a tail of two inputs
beside K and V rows in one cache.

The generator picks this module where ``cfg.layer_pattern`` names a
``"conv"`` layer (``models.family``) and calls it through the same entry
points as ``models/llama.py``. ``cfg.layer_pattern`` is one period of the
stack, e.g. ``("conv", "conv", "full", "conv")``; layer ``l`` is of kind
``pattern[l % len(pattern)]``. ``x`` is the residual stream, pre-norm
blocks: ``x += Op(RMSNorm(x)); x += FFN(RMSNorm(x))``.

  - a CONV layer is ``[B | C | X] = W_in h`` (three thirds of ``3 dim``),
    ``u = B * X``, a causal depthwise convolution a channel over the last
    ``conv_kernel`` values of u (``ops.kda.conv_taps``: the hybrid
    family's taps and tail, without its SiLU), ``y = W_out (C * conv)``;
    no bias and no activation anywhere in it. Its whole cache is the
    TAIL, the last ``conv_kernel - 1`` values of u, [Lc, B, W - 1, dim]:
    a slot's memory of such a layer does not grow with its length, and a
    position cannot be computed again on top of a tail that has moved
    past it (``RECOMPUTABLE``).
  - a FULL layer is softmax attention over every cached position,
    ``n_heads`` query heads on ``n_kv_heads`` KV heads of ``head_dim``,
    q and k RMS-normed a head before the rotation where ``qk_norm``
    (``blocks.attention``, the window family's too). Its cache is llama's
    K and V rows, and where a head is narrower than a lane row (64
    values) two KV heads share a row, [Lf, B, KV/2, Smax, 2 hd]
    (``ops.attention.pair_rows``): a 64-wide row alone is padded to the
    128 lanes, or laid out positions-minor as XLA does on a v5e, and no
    Mosaic kernel reads either in place. A query head carries zeros in
    the half that is not its KV head's, and ``flash_decode_stacked``,
    ``append_rows_stacked`` and the jnp forms run as at 128.
  - the first ``n_dense_layers`` layers' feed-forward is SwiGLU of width
    ``ffn_dim``; every other layer's is the routed one of
    ``models/moe.py`` (``moe_ffn``, which ``blocks.period_stack`` hands
    each routed layer), here with no shared expert.

Weights are stacked a kind of operator (``params["conv"]``,
``params["full"]``) and of feed-forward (``params["dense"]``,
``params["moe"]``) and run by ``blocks.period_stack``: the periods that hold a
dense layer one after another, the rest scanned a period at a time.

What a padded position, a new tenant or an idle slot may do to a tail:
a prefill leaves it at the row's last VALID input, not the bucket's; a
chunk goes on from the tail the chunk before it left, and the first
chunk (and a whole-prompt prefill) from zeros, whatever the slot's last
tenant left; a prompt shorter than the tail leaves zeros in the older
places; a decode step moves the tails of the ACTIVE slots alone.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import flash_decode
from ..ops.kda import conv_taps
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from . import blocks, llama, moe
from .blocks import attention, embed, period_stack, prompt_attend, prompt_rows
from .common import ModelConfig, dense_init, refused_options

# a tail holds the last inputs and no earlier ones: the chunk lattice
# runs left-aligned, and a prefix-pool row is usable only at the
# position its tails were taken
RECOMPUTABLE = False
F32 = jnp.float32
KINDS = ("conv", "full")


def counts(cfg: ModelConfig) -> dict[str, int]:
    """Layers of each kind in the stack."""
    pat = cfg.layer_pattern
    if not pat or cfg.n_layers % len(pat) or set(pat) != set(KINDS) \
            or cfg.conv_kernel < 2:
        raise ValueError(f"layer_pattern {pat!r} does not tile "
                         f"{cfg.n_layers} layers of conv and full kinds "
                         f"(conv_kernel {cfg.conv_kernel})")
    return {k: cfg.n_layers // len(pat) * pat.count(k) for k in KINDS}


paired = blocks.paired    # two KV heads a cache row at any head under 128


kv_tables = llama.kv_tables      # one table a layer (models.family)
chunk_block = llama.chunk_block  # a cursor walk (models.family)


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(rows, values a row) of a cached token's K (and V), as stored."""
    return blocks.row_layout(cfg, paired(cfg))


class ConvCache(NamedTuple):
    """The slots' memory of both kinds; every array but ``lengths`` is
    [L, B, ...], which is all the engine's row helpers ask. In the
    model's type: no scale planes (an int8 cache is refused at start-up:
    a paired row would need a scale a half)."""

    k: jnp.ndarray        # [Lf, B, rows, Smax, values]: ``kv_layout``
    v: jnp.ndarray
    conv: jnp.ndarray     # [Lc, B, W - 1, dim]: the last inputs, oldest first
    lengths: jnp.ndarray  # [B] int32

    quantized = False

    @property
    def rows(self) -> llama.KVCache:
        """The full layers' part, as llama's helpers take it."""
        return llama.KVCache(self.k, self.v, self.lengths)


def _empty_tails(cfg: ModelConfig, batch: int):
    return jnp.zeros((counts(cfg)["conv"], batch, cfg.conv_kernel - 1,
                      cfg.dim), cfg.jdtype)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> ConvCache:
    rows, values = kv_layout(cfg)
    kv = llama.init_cache(
        cfg.with_(n_layers=counts(cfg)["full"], n_kv_heads=rows,
                  attn_head_dim=values), batch, max_seq, dtype)
    return ConvCache(k=kv.k, v=kv.v, conv=_empty_tails(cfg, batch),
                     lengths=kv.lengths)


def get_rope_tables(cfg: ModelConfig, max_seq: int) -> dict:
    """(cos, sin) of the full layers, over the whole head."""
    return {"full": llama.get_rope_tables(cfg, max_seq)}


def decode_kv_block(cfg: ModelConfig, cache: ConvCache, mesh=None):
    return flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)


def tail_bytes_per_slot(cfg: ModelConfig) -> int:
    """Bytes a slot's tails take, whatever its length."""
    return counts(cfg)["conv"] * (cfg.conv_kernel - 1) * cfg.dim \
        * cfg.jdtype.itemsize


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of this family: the decode
    step's expert dispatch shapes and path (``moe.serving_stats``),
    the layers of each kind, the bytes a slot's tails take whatever its
    length (``state_bytes_per_slot``: the engine's word for a slot's
    memory that is not rows) and those a cached token takes in the full
    layers, in the model's type (benchmarks/metrics reads them here)."""
    n = counts(cfg)
    return {**moe.serving_stats(cfg, slots),
            "layers": n,
            "state_bytes_per_slot": tail_bytes_per_slot(cfg),
            "kv_bytes_per_token": n["full"] * 2 * cfg.n_kv_heads
            * cfg.head_dim * cfg.jdtype.itemsize,
            "kv_heads_per_row": 2 if paired(cfg) else 1}


# the serving options that would restore or rewind a slot from rows alone,
# and why not (the engine raises on any of them at start-up)
REFUSED = {
    "mesh": "the tails and the expert layer have no sharding rule; the "
            "family runs on one chip",
    "paged_blocks": "the block pool holds K and V rows, not a "
                    "convolution's tail",
    "kvcache": "the host and Redis tiers frame K and V rows; a tail would "
               "not travel with them",
    "spec_decode_k": "a rejected draft has already shifted the tail",
    "lora_adapters": "adapters target the llama block's projections",
    "kv_dtype": "int8: a cache row holds two KV heads and the shared "
                "kernels take one scale a row",
    "serving_role": "KV shipping frames K and V rows, not a tail",
}
unsupported_options = functools.partial(refused_options, REFUSED)


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params, a stack a kind of operator and of
    feed-forward."""
    dt = cfg.jdtype
    ks = iter(jax.random.split(key, 24))
    n = counts(cfg)
    D, H, KV, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Lc, Lf, W = n["conv"], n["full"], cfg.conv_kernel
    nd = cfg.n_dense_layers
    ns = cfg.n_layers - nd
    full = {"attn_norm": jnp.ones((Lf, D), dt),
            "wq": dense_init(next(ks), (Lf, D, H * hd), dt),
            "wk": dense_init(next(ks), (Lf, D, KV * hd), dt),
            "wv": dense_init(next(ks), (Lf, D, KV * hd), dt),
            "wo": dense_init(next(ks), (Lf, H * hd, D), dt)}
    if cfg.qk_norm:
        # drawn around 1, not at it: a norm's weight that is all ones
        # would hide a norm put on the wrong side of the rotation
        for name in ("q_head_norm", "k_head_norm"):
            full[name] = (1.0 + 0.1 * jax.random.normal(
                next(ks), (Lf, hd), F32)).astype(dt)
    params = {
        "embedding": dense_init(next(ks), (cfg.vocab_size, D), dt,
                                scale=0.02),
        "conv": {
            "attn_norm": jnp.ones((Lc, D), dt),
            # [B | C | X], a third each
            "w_in": dense_init(next(ks), (Lc, D, 3 * D), dt),
            # depthwise taps [W, dim]; tap W - 1 meets the current input
            "conv": dense_init(next(ks), (Lc, W, D), dt, scale=W ** -0.5),
            "w_out": dense_init(next(ks), (Lc, D, D), dt)},
        "full": full,
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
        params["lm_head"] = dense_init(next(ks), (D, cfg.vocab_size), dt)
    return params


# -- one layer -----------------------------------------------------------------

def _conv(x, lw, cfg: ModelConfig, tail, lengths):
    """The gated short convolution: x [B, S, D] from ``tail`` [B, W - 1,
    D] -> (y [B, S, D], the tail after input ``lengths - 1`` (None: the
    last)). u is rounded once, to the type the tail keeps it in."""
    D = cfg.dim
    with jax.named_scope("conv/in"):
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        bcx = qmatmul(h, lw["w_in"])
        u = bcx[..., :D] * bcx[..., 2 * D:]
    with jax.named_scope("conv/taps"):
        c, tail = conv_taps(u, tail, lw["conv"], lengths)
        y = (bcx[..., D:2 * D].astype(F32) * c).astype(x.dtype)
    with jax.named_scope("conv/out"):
        return qmatmul(y, lw["w_out"]), tail


def _layer(x, lw, cfg: ModelConfig, op, valid):
    """One block: ``op(x, lw) -> (y, kept)`` then the feed-forward
    ``lw["ffn"]``. Returns (x, what the operator keeps of these tokens,
    the expert layer's assignments a held expert or None)."""
    y, kept = op(x, lw)
    x = x + y
    y, n = lw["ffn"](rms_norm(x, lw["ffn_norm"], cfg.norm_eps), lw, cfg,
                     valid)
    return x + y, kept, n


# -- the programs --------------------------------------------------------------

def _run(params, cfg: ModelConfig, tokens, lengths, tails, attend, rope,
         positions, valid):
    """The shared body of the three programs: tokens [B, S] from ``tails``
    [Lc, B, W - 1, D]; ``attend(i) -> attend(q, k, v)`` of full layer i.
    Returns (x, the full layers' K and V stacks [Lf, B, S, rows, values],
    the tails after each row's last valid input, the routed layers'
    assignments a held expert [Ls, Eh])."""
    def layer(x, lw, kind, i):
        if kind == "conv":
            tail = jax.lax.dynamic_index_in_dim(tails, i, 0, keepdims=False)
            return _layer(x, lw, cfg,
                          lambda x, lw: _conv(x, lw, cfg, tail, lengths),
                          valid)
        x, kv, n = _layer(
            x, lw, cfg, lambda x, lw: attention(
                x, lw, cfg, cfg.n_heads, rope["full"], positions, attend(i)),
            valid)
        return x, blocks.as_stored(kv, paired(cfg)), n

    x, kept, n = period_stack(params, cfg, embed(params, cfg, tokens), layer)
    return x, *kept["full"], kept["conv"], n


def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded) from empty tails.
    Returns (logits [B, S, V] float32, or [B, 1, V] with ``logit_pos``;
    the full layers' K and V stacks [Lf, B, S, rows, values] as the cache
    stores a token; the tails [Lc, B, W - 1, D] as they stand after each
    row's last token; lengths [B])."""
    B, S = tokens.shape
    lengths, positions, valid = prompt_rows(tokens, lengths)
    rope = rope_tables or get_rope_tables(cfg, rope_max or S)
    attend = prompt_attend(flash, lengths, valid, mesh)
    x, k, v, tails, _ = _run(params, cfg, tokens, lengths,
                             _empty_tails(cfg, B), lambda i: attend, rope,
                             positions, valid)
    return llama.logits_at(params, cfg, x, logit_pos), k, v, tails, lengths


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``)."""
    return prefill_kv(params, cfg, tokens, lengths, logit_pos=logit_pos)[0]


@jax.named_scope("kv_write")
def write_kv(cache: ConvCache, k_stack, v_stack, tails, index, lengths
             ) -> ConvCache:
    """Write what ``prefill_kv`` made for B' rows at batch row
    ``index[1]``: K and V stacks from position ``index[3]`` (llama's
    write), the tails whole: the slot's last tenant's are gone."""
    rows = llama.write_kv(cache.rows, k_stack, v_stack, index, lengths)
    return ConvCache(
        k=rows.k, v=rows.v, lengths=rows.lengths,
        conv=jax.lax.dynamic_update_slice_in_dim(
            cache.conv, tails.astype(cache.conv.dtype), index[1], axis=1))


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: ConvCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C) against the
    cache: the full layers attend to the rows before it and within
    itself, the conv layers go on from the cache's tails (from zeros at
    ``start`` 0: a free slot holds its last tenant's). With ``logit_pos``
    the chunk is the prompt's last and may be padded: the tails are taken
    at ``logit_pos``, not at the chunk's end. ``cache.lengths`` is not
    advanced (llama.prefill_chunk's contract)."""
    B, C = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    rope = rope_tables or get_rope_tables(cfg, cache.k.shape[3])
    lengths = None if logit_pos is None \
        else logit_pos.astype(jnp.int32) + 1
    valid = None if lengths is None \
        else jnp.arange(C)[None, :] < lengths[:, None]
    tails = jnp.where(jnp.asarray(start) == 0,
                      jnp.zeros((), cache.conv.dtype), cache.conv)

    def attend(i):
        return blocks.chunk_rows_attend((cache.k, cache.v, None, None), i,
                                        start, cfg, paired(cfg))

    x, k, v, tails, _ = _run(params, cfg, tokens, lengths, tails, attend,
                             rope, positions, valid)
    rows = llama.write_kv(cache.rows, k, v, (0, 0, 0, start, 0),
                          cache.lengths)
    cache = ConvCache(k=rows.k, v=rows.v, conv=tails, lengths=cache.lengths)
    if not compute_logits:
        return None, cache
    return llama.logits_at(params, cfg, x, logit_pos), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: ConvCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B]. The full layers read the K and V
    rows in place and their new rows are written after the loop
    (llama.decode_step's discipline and capacity contract); a conv layer
    reads its tail where it lies and the new tails go in by one select
    after the loop, for the ACTIVE slots alone.

    Returns (logits [B, V] float32, the cache with lengths + 1, the
    expert layer's assignments a routed layer a held expert [Ls, Eh]
    int32, the (layer, slot) tails moved: int32 scalar)."""
    B = tokens.shape[0]
    lengths = cache.lengths
    positions = lengths[:, None]
    act = jnp.ones((B,), bool) if active is None else active
    live = jnp.where(act, lengths, 0)
    rope = rope_tables or get_rope_tables(cfg, cache.k.shape[3])
    block_s = flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)

    def attend(i):
        return jax.named_scope("attn/full_decode")(blocks.decode_rows_attend(
            (cache.k, cache.v, None, None), i, live, live, block_s, mesh,
            cfg, paired(cfg)))

    x, k_rows, v_rows, tails, n = _run(
        params, cfg, tokens[:, None], None, cache.conv, attend, rope,
        positions, act[:, None])
    with jax.named_scope("kv_write"):
        rows = llama.write_rows(cache.rows, k_rows, v_rows, positions,
                                lengths + 1, cfg.n_heads, mesh)
        conv = jnp.where(act[None, :, None, None],
                         tails.astype(cache.conv.dtype), cache.conv)
    return (llama.logits(params, cfg, x[:, 0]),
            ConvCache(k=rows.k, v=rows.v, conv=conv, lengths=rows.lengths),
            n, jnp.sum(act, dtype=jnp.int32) * counts(cfg)["conv"])
