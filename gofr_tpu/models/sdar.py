"""The block-diffusion family (``model_type: sdar_moe``): a sparse
decoder layer under a sampler that generates a block of positions at a
time, by passes over the whole block.

The generator picks this module where ``cfg.block_length > 0``
(``models.family``) and calls it through the same entry points as
``models/llama.py``, with ONE difference that it says to the engine
(``serving_stats()["diffusion"]``): its decode step is a pass over a
block of ``W = block_length`` positions a slot, not a token, and its
prompt programs yield no token.

The layer, every one alike (``N`` RMSNorm, ``x`` the residual stream):

  h = N(x);  q = W_q h in [H, hd], k = W_k h, v = W_v h in [KV, hd]
  q, k RMS-normed a head BEFORE the rotation (``qk_norm``), rotated over
  the whole head (rotate-half, ``rope_theta``), scores at hd^-1/2
  x += W_o attn
  h = N(x);  p = softmax_float32(W_r h) over all experts, the top k
  renormalised to sum 1 (``moe.route`` with ``router_score``
  "softmax", no bias);  x += sum_e p_e SwiGLU_e(h)   (``moe.moe_ffn``)

then a final norm and an untied head.

The mask, in prompts and in generation alike: position i sees j iff
``j // W <= i // W``. A prompt of n tokens is prefilled over its whole
blocks, ``n // W * W`` positions, under that mask (``ops.flash`` and
``ops.attention`` with ``block=W``); the ``n % W`` tokens left over open
the first generated block as GIVEN positions. Every chunk of a long
prompt starts on a block's first position.

Generation of the block at a slot's cursor (``decode_step``, one PASS):
the block's known positions (given, or committed by an earlier pass)
carry their tokens' embeddings, the others the mask token's; the stack
runs over all W with each position attending every cached row under the
cursor and all W of the block (``flash_decode.flash_decode_block``, or
``window_attention_appended`` with every window position seen); nothing
is written. The head's distribution AT A MASKED POSITION'S OWN ROW gives
its token and the token's probability, its confidence; ``commits`` picks
which of them this pass commits (``commit_order``). When a slot's block
has no masked position left, its next pass is the COMMIT pass: the stack
over the block's final tokens, whose W rows a layer are written at the
cursor, which moves by W. A block's rows depend on final tokens only:
rows kept from the last denoise pass would have been computed beside
mask embeddings. Which positions are masked is STATE (``known``), never
``token == mask_token_id``: the mask's id is a row of the vocabulary
that a prompt or a sampled token may hold.

Slots are at their own passes: one program runs a pass for all of them,
each slot denoising or committing by its own state, and the head runs
where any slot denoises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops import flash_decode
from ..ops.attention import block_causal, window_attention_appended
from ..ops.norms import rms_norm
from . import blocks, llama, moe
from .blocks import attention, embed, prompt_attend, prompt_rows
from .common import ModelConfig, dense_init, refused_options

# a cached position computed again gives the same rows, if the chunk
# that computes it starts on a block's first position
RECOMPUTABLE = True
F32 = jnp.float32

get_rope_tables = llama.get_rope_tables
kv_layout = llama.kv_layout
kv_tables = llama.kv_tables      # one table a layer (models.family)
decode_kv_block = llama.decode_kv_block
chunk_block = llama.chunk_block  # a cursor walk (models.family)
init_cache = llama.init_cache
write_kv = llama.write_kv
logits = llama.logits            # the final norm and the head, of any rows


def passes(cfg: ModelConfig) -> int:
    """Denoise passes a block of all-masked positions takes under the
    two orders that commit a fixed count a pass."""
    return cfg.denoise_passes or cfg.block_length


def per_pass(cfg: ModelConfig) -> int:
    """Positions a pass commits under those orders (``k``)."""
    return cfg.block_length // passes(cfg)


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of this family: the decode
    program's expert dispatch at ``slots x block_length`` tokens a pass
    (``moe.serving_stats``), the bytes of a cached token in the model's
    type, and ``diffusion``: the block, the passes, the order. The
    engine reads ``diffusion["block_length"]`` to learn that a step is a
    pass over a block and that a prefill yields no token."""
    W = cfg.block_length
    return {**moe.serving_stats(cfg, slots * W),
            "kv_bytes_per_token": cfg.n_layers * 2 * cfg.n_kv_heads
            * cfg.head_dim * cfg.jdtype.itemsize,
            "diffusion": {"block_length": W,
                          "passes_per_block": passes(cfg),
                          "commits_per_pass": per_pass(cfg),
                          "order": cfg.commit_order,
                          "confidence_threshold": cfg.confidence_threshold,
                          "mask_token_id": cfg.mask_token_id,
                          "head_rows_per_slot": candidates_width(cfg)}}


# what has not carried a block yet, and why not (the engine raises on
# any of them at start-up)
REFUSED = {
    "mesh": "the block pass and the expert layer have no sharding rule; "
            "the family runs on one chip",
    "paged_blocks": "the block pool's programs write one row a step; a "
                    "commit pass writes a block's",
    "kvcache": "the host and Redis tiers restore to any matched length; "
               "a block's rows are whole only at a block boundary",
    "spec_decode_k": "the verify pass is causal inside its window; a "
                     "block is denoised as a whole",
    "lora_adapters": "adapters target the llama block's projections",
    "serving_role": "KV shipping ends in a sampled first token; a "
                    "prefill here yields none",
}
unsupported_options = functools.partial(refused_options, REFUSED)


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params: one stack of attention and routed leaves (no
    router bias, no shared expert), the q/k norms drawn around 1 so that
    their side of the rotation shows, an untied head."""
    dt = cfg.jdtype
    ks = iter(jax.random.split(key, 16))
    L, D, H, KV, hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    layers = {"attn_norm": jnp.ones((L, D), dt),
              "wq": dense_init(next(ks), (L, D, H * hd), dt),
              "wk": dense_init(next(ks), (L, D, KV * hd), dt),
              "wv": dense_init(next(ks), (L, D, KV * hd), dt),
              "wo": dense_init(next(ks), (L, H * hd, D), dt),
              "ffn_norm": jnp.ones((L, D), dt)}
    for name in ("q_head_norm", "k_head_norm"):
        layers[name] = (1.0 + 0.1 * jax.random.normal(
            next(ks), (L, hd), F32)).astype(dt)
    routed = moe.init_routed(ks, cfg, L)
    del routed["router_bias"]       # the scores select
    params = {"embedding": dense_init(next(ks), (cfg.vocab_size, D), dt,
                                      scale=0.02),
              "layers": {**layers, **routed},
              "final_norm": jnp.ones((D,), dt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(next(ks), (D, cfg.vocab_size), dt)
    return params


# -- the stack -----------------------------------------------------------------

def _stack(params, cfg: ModelConfig, x, rope, positions, attend_at, valid):
    """The layers over x [B, S, D]: (x, K and V of these tokens a layer
    [L, B, S, KV, hd], the routed layers' assignments a held expert
    [L, Eh]). ``attend_at(layer) -> attend(q, k, v)``, ``layer`` its
    traced index; ``valid`` [B, S]: the rows that are tokens."""
    # the rotation's rows of these positions, gathered once for all
    # layers; the expert stacks stay whole beside the scan
    # (blocks.layer_at says what each costs otherwise)
    cos, sin = rope
    here = (cos[positions], sin[positions])
    experts, rest = blocks.experts_apart(params["layers"])

    def body(x, xs):
        lw, l = xs
        lw = {**lw, "experts": (experts, l)}
        a, kv = attention(x, lw, cfg, cfg.n_heads, here, None, attend_at(l))
        x = x + a
        y, n = moe.moe_ffn(rms_norm(x, lw["ffn_norm"], cfg.norm_eps), lw,
                           cfg, valid)
        return x + y, (kv, n)

    x, ((k, v), n) = jax.lax.scan(
        body, x, (rest, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return x, k, v, n


# -- the prompt programs -------------------------------------------------------

def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Block-causal forward over [B, S] tokens (right-padded; a serving
    prompt is whole blocks). Returns (logits, K and V stacks [L, B, S,
    KV, hd], lengths). With ``logit_pos`` (a serving prefill) the logits
    are zeros [B, 1, V] and the head does not run: a prefill yields no
    token, the slot's first block is denoised like any other. Without,
    [B, S, V] float32: every position's distribution of ITS OWN token,
    every token before its block and its block's others known
    (``score``)."""
    lengths, positions, valid = prompt_rows(tokens, lengths)
    rope = rope_tables or get_rope_tables(cfg, rope_max or tokens.shape[1])
    attend = prompt_attend(flash, lengths, valid, mesh,
                           block=cfg.block_length)
    x, k, v, _ = _stack(params, cfg, embed(params, cfg, tokens), rope,
                        positions, lambda l: attend, valid)
    if logit_pos is not None:
        out = jnp.zeros((tokens.shape[0], 1, cfg.vocab_size), F32)
    else:
        out = logits(params, cfg, x)
    return out, k, v, lengths


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``), or
    [B, 1, V] at ``logit_pos``."""
    out = prefill_kv(params, cfg, tokens, lengths)[0]
    if logit_pos is None:
        return out
    return jnp.take_along_axis(
        out, logit_pos[:, None, None].astype(jnp.int32), axis=1)


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: llama.KVCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C), ``start`` a
    block's first position, against the cache: every layer attends to
    its rows before the chunk and block-causally within it; the chunk's
    rows are written after the loop. The logits are zeros [B, 1, V]
    (``prefill_kv``). ``cache.lengths`` is not advanced
    (llama.prefill_chunk's contract)."""
    B, C = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    rope = rope_tables or get_rope_tables(cfg, cache.capacity)

    def attend_at(l):
        return blocks.chunk_rows_attend(_rows(cache), l, start, cfg, False,
                                        block=cfg.block_length)

    _, k, v, _ = _stack(params, cfg, embed(params, cfg, tokens), rope,
                        positions, attend_at, None)
    cache = write_kv(cache, k, v, (0, 0, 0, start, 0), cache.lengths)
    if not compute_logits:
        return None, cache
    return jnp.zeros((B, 1, cfg.vocab_size), F32), cache


def _rows(cache: llama.KVCache):
    """The cache's row tables as ``blocks``' helpers take them."""
    return cache.k, cache.v, cache.k_scale, cache.v_scale


# -- a pass over the slots' blocks ---------------------------------------------

def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: llama.KVCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None, *,
                known: jnp.ndarray, commit: jnp.ndarray):
    """One pass for the block at every slot's cursor: tokens [B, W],
    ``known`` [B, W] bool (a position that is not carries the mask
    token's embedding, whatever ``tokens`` holds there), ``commit`` [B]
    bool: the slots whose W rows a layer are written at their cursor,
    which moves by W (their block is all known; for the others nothing
    is written). Each position attends the slot's cached rows under the
    cursor and all W of its block.

    Returns (x [B, W, D], the stream before the final norm: ``logits``
    of the rows a sampler wants; the cache; the expert layers'
    assignments a layer a held expert [L, Eh] int32)."""
    B, W = tokens.shape
    lengths = cache.lengths
    act = jnp.ones((B,), bool) if active is None else active
    live = jnp.where(act, lengths, 0)
    positions = lengths[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    rope = rope_tables or get_rope_tables(cfg, cache.capacity)
    block_s = flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)
    seen = block_causal(W, W)       # every position of the block

    def attend_at(l):
        @jax.named_scope("attn/block_decode")
        def attend(q, k_new, v_new):
            if block_s:
                return flash_decode.block_attention_auto(
                    q, cache.k, cache.v, k_new, v_new, live, l,
                    cache.k_scale, cache.v_scale, block_s=block_s)
            k_l, v_l, ks_l, vs_l = blocks.layer_rows(_rows(cache), l)
            return window_attention_appended(q, k_l, v_l, k_new, v_new,
                                             live, ks_l, vs_l, within=seen)
        return attend

    masked = jnp.where(known, tokens, cfg.mask_token_id)
    x, k, v, n = _stack(params, cfg, embed(params, cfg, masked), rope,
                        positions, attend_at,
                        jnp.broadcast_to(act[:, None], (B, W)))
    with jax.named_scope("diffusion/commit"), jax.named_scope("kv_write"):
        # a row of a slot that does not commit goes past capacity, where
        # the write drops it
        at = jnp.where(commit[:, None], positions, cache.capacity)
        new = llama.write_rows(cache, k, v, at,
                               lengths + jnp.where(commit, W, 0),
                               cfg.n_heads, mesh)
    return x, new, n


# -- the sampler's orders ------------------------------------------------------

def candidates_width(cfg: ModelConfig) -> int:
    """Positions a slot whose logits a pass needs: the ``k`` that
    ``sequential`` will commit, every position for the orders that
    compare confidences."""
    return per_pass(cfg) if cfg.commit_order == "sequential" \
        else cfg.block_length


def candidates(cfg: ModelConfig, known: jnp.ndarray):
    """(positions [B, c] int32, masked [B, c] bool): the block positions
    whose distributions this pass reads, and which of them are masked
    positions. ``sequential``: the leftmost ``k`` masked ones (the head
    over k rows a slot, not W); where fewer are left the last entries
    name position W - 1 and are not ``masked``. The other orders: all
    W."""
    B, W = known.shape
    c = candidates_width(cfg)
    if c == W:
        return jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32),
                                (B, W)), ~known
    # the rank of each masked position among the masked, and for each
    # rank the position that has it
    rank = jnp.cumsum(~known, axis=1) - 1                       # [B, W]
    hit = (~known)[:, None, :] & (rank[:, None, :]
                                  == jnp.arange(c)[None, :, None])
    found = jnp.any(hit, -1)
    return jnp.where(found, jnp.argmax(hit, -1),
                     W - 1).astype(jnp.int32), found


def commits(cfg: ModelConfig, conf: jnp.ndarray,
            masked: jnp.ndarray) -> jnp.ndarray:
    """Which candidates a pass commits: conf [B, c] float32 (the sampled
    token's probability), masked [B, c] bool (``candidates``' second,
    of a slot that denoises) -> [B, c] bool.

    ``sequential``: every candidate (they are the leftmost k masked);
    ``low_confidence_static``: the k most confident masked ones (the
    leftmost of equals first); ``low_confidence_dynamic``: every masked
    one whose confidence passes ``confidence_threshold``, and the most
    confident one where none does."""
    if cfg.commit_order == "sequential":
        return masked
    c = conf.shape[1]
    conf = jnp.where(masked, conf, -1.0)
    # rank by confidence, ties to the left: how many are better
    better = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (jnp.arange(c)[None, None, :] < jnp.arange(c)[None, :, None]))
    rank = jnp.sum(better, axis=-1)                             # [B, c]
    if cfg.commit_order == "low_confidence_static":
        return masked & (rank < per_pass(cfg))
    return masked & ((conf > cfg.confidence_threshold) | (rank == 0))
