"""The looped family (``model_type: ouro``): one stack of layers run
several times a token, each pass with K and V rows of its own.

The generator picks this module where ``cfg.loop_steps > 1`` or
``cfg.sandwich_norm`` (``models.family``) and calls it through the same
entry points as ``models/llama.py``. ``L = n_layers``, ``T =
loop_steps``; ``N(.)`` is RMSNorm with a weight of its own:

  x = E[token]
  for t in 0 .. T - 1:                      # the same weights every pass
      for l in 0 .. L - 1:
          a = Attn_l(N1_l(x); table t * L + l);   x = x + N2_l(a)
          m = MLP_l(N3_l(x));                     x = x + N4_l(m)
      x = N_f(x)              # pass t's output h_t AND pass t + 1's input
  logits = h_{T-1} W_head

``Attn`` is llama's (``blocks.attention``: rope over the whole head,
softmax at ``head_dim^-1/2``) over the rows of table ``t * L + l`` alone
and the token's own; ``MLP`` is SwiGLU. The four norms a layer are the
source's "sandwich": ``attn_norm`` (its ``input_layernorm``),
``input_layernorm_2``, ``ffn_norm`` (``post_attention_layernorm``) and
``post_attention_layernorm_2``, the second of each pair inside the
residual branch; the two new leaves and the exit gate keep the source's
names so that its checkpoint's leaves land by name.

The cache is llama's row cache with ``T * L`` tables, [T L, B, KV, Smax,
hd] (``kv_tables``): a token costs ``T`` times a one-pass model's rows,
a step streams the stack's weights ``T`` times, and the decode kernels
(``ops/flash_decode.py``) run as they do for llama with the table index
where the layer index was. The step's write visits a slot's tables a
share at a time where all of them would not fit the kernel's buffers
(``flash_decode.append_tables``).

The residual stream ``x`` is float32 whatever the model's type (``_layer``
says why); the blocks read it rounded to the model's type.

The exit gate, ``sigmoid(h_t . w + b)`` one value a token a pass, is held
as leaves (``early_exit_gate``) and NOT evaluated: at the published
``early_exit_threshold`` of 1 every token leaves at the last pass and the
gate enters no logit; ``ModelConfig`` refuses a threshold under 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops import flash_decode
from ..ops.attention import chunk_attention, decode_attention_appended
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul, quantize_kv
from . import llama
from .blocks import attention, embed, prompt_attend, prompt_rows
from .common import ModelConfig, dense_init, refused_options

# a cached position computed again gives the same rows, in every table
RECOMPUTABLE = True
F32 = jnp.float32
NORMS = ("attn_norm", "input_layernorm_2", "ffn_norm",
         "post_attention_layernorm_2")
# what ``init`` draws the two norms INSIDE the residual branches around
# (the others around 1): with every branch at the stream's own size the
# looped map is expansive on random weights, and a rounding error grows
# 2.6 times a pass (float32 reference against the bfloat16 forward at a
# width of 512: a median of 0.010 nats after one pass of 48 layers, 0.18
# after four); at an eighth the 96 branches of a pass add up to about the
# stream's size and a pass neither grows nor shrinks a perturbation
# (0.015 after four), as a loop that was trained to be run again must
BRANCH_GAIN = 0.125

get_rope_tables = llama.get_rope_tables
kv_layout = llama.kv_layout
decode_kv_block = llama.decode_kv_block
chunk_block = llama.chunk_block  # a cursor walk (models.family)


def kv_tables(cfg: ModelConfig) -> int:
    """Row tables a cached token has: one a (pass, layer)."""
    return cfg.loop_steps * cfg.n_layers


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> llama.KVCache:
    return llama.init_cache(cfg.with_(n_layers=kv_tables(cfg)), batch,
                            max_seq, dtype)


@jax.named_scope("kv_write")
def write_kv(cache: llama.KVCache, k_stack, v_stack, index5, lengths
             ) -> llama.KVCache:
    """``llama.write_kv`` (K and V stacks [T L, B', S', KV, hd] into the
    cache at ``index5``), an int8 cache's rows quantised a TABLE at a
    time: over the whole stack at once XLA keeps 192 tables of a
    512-token prompt in float32, 0.8 GB each for K and V, beside a cache
    that was sized to fill the chip."""
    if not cache.quantized:
        return llama.write_kv(cache, k_stack, v_stack, index5, lengths)

    def table(kv):      # the cache's order: a KV head's positions together
        out = ()
        for x in kv:
            q, scale = quantize_kv(x)
            out += (jnp.swapaxes(q, 1, 2), jnp.swapaxes(scale, 1, 2))
        return out

    qk, sk, qv, sv = jax.lax.map(table, (k_stack, v_stack))
    put = jax.lax.dynamic_update_slice
    return llama.KVCache(
        k=put(cache.k, qk, index5), v=put(cache.v, qv, index5),
        lengths=lengths, k_scale=put(cache.k_scale, sk, index5[:-1]),
        v_scale=put(cache.v_scale, sv, index5[:-1]))


def _layer_weights(cfg: ModelConfig) -> int:
    """The weights of one layer's seven projections."""
    return cfg.dim * cfg.head_dim * 2 * (cfg.n_heads + cfg.n_kv_heads) \
        + 3 * cfg.dim * cfg.ffn_dim


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of this family: the passes,
    the tables and the bytes of a cached token as an int8 cache stores it
    (rows and their float32 scales), and the projection weights a step
    streams at a byte a weight (the stack once a pass, the head once)."""
    per_table = 2 * cfg.n_kv_heads * (cfg.head_dim + 4)
    head = 0 if cfg.tie_embeddings else cfg.dim * cfg.vocab_size
    return {"loop_steps": cfg.loop_steps,
            "kv_tables": kv_tables(cfg),
            "kv_bytes_per_token": kv_tables(cfg) * per_table,
            "weight_bytes_per_step": cfg.loop_steps * cfg.n_layers
            * _layer_weights(cfg) + head}


# the serving options that count a token's tables by the depth, or run a
# program of llama's own, and why not (the engine raises on any of them
# at start-up)
REFUSED = {
    "mesh": "the looped stack has no sharding rule; the family runs on "
            "one chip",
    "paged_blocks": "the block pool's programs are llama's: one table a "
                    "layer, one pass a token",
    "kvcache": "the host and Redis tiers have not carried a row of "
               "loop_steps x n_layers tables",
    "spec_decode_k": "the verify pass is llama's: one pass a token",
    "lora_adapters": "adapters target the llama block's projections",
    "serving_role": "KV shipping has not carried a row of loop_steps x "
                    "n_layers tables",
}
unsupported_options = functools.partial(refused_options, REFUSED)


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params: llama's dense tree, the two further norms a
    layer and the exit gate. A norm's weights are drawn within a tenth
    of their mean (1; ``BRANCH_GAIN`` for the two inside the residual
    branches), so that a forward that leaves one out, or swaps two,
    differs."""
    dt = cfg.jdtype
    L, D = cfg.n_layers, cfg.dim
    k_llama, k_norm, k_gate = jax.random.split(key, 3)
    params = llama.init(cfg.with_(n_experts=0), k_llama)
    norm_keys = iter(jax.random.split(k_norm, len(NORMS) + 1))

    def around(mean, shape):
        return (mean * (1.0 + 0.1 * jax.random.normal(
            next(norm_keys), shape, F32))).astype(dt)

    params["layers"].update({
        n: around(BRANCH_GAIN if n.endswith("_2") else 1.0, (L, D))
        for n in NORMS})
    params["final_norm"] = around(1.0, (D,))
    params["early_exit_gate"] = {"w": dense_init(k_gate, (D, 1), dt),
                                 "b": jnp.zeros((1,), dt)}
    return params


# -- one layer, one pass -------------------------------------------------------

def _layer(x, lw, cfg: ModelConfig, rope, positions, attend):
    """One block over the stream x [B, S, D], which is float32: it is
    added to 2 T L times and renormed T times a token, and carried in
    bfloat16 its rounding alone doubles the forward's distance from the
    float32 reference (the measurement at ``BRANCH_GAIN``). The blocks
    read it rounded to the model's type and compute in that.
    Returns (x, (k, v) [B, S, KV, hd] of these tokens)."""
    dt = cfg.jdtype
    a, kv = attention(x.astype(dt), lw, cfg, cfg.n_heads, rope, positions,
                      attend)
    with jax.named_scope("norm/post_attn"):
        x = x + rms_norm(a, lw["input_layernorm_2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        h = rms_norm(x.astype(dt), lw["ffn_norm"], cfg.norm_eps)
        m = qmatmul(jax.nn.silu(qmatmul(h, lw["w_gate"]))
                    * qmatmul(h, lw["w_up"]), lw["w_down"])
    with jax.named_scope("norm/post_mlp"):
        return x + rms_norm(m, lw["post_attention_layernorm_2"],
                            cfg.norm_eps), kv


def _loop(params, cfg: ModelConfig, x, rope, positions, attend_at):
    """The stack ``loop_steps`` times over x [B, S, D]: (the last pass's
    normed output, K and V of these tokens a table [T L, B, S, KV, hd]).
    ``attend_at(table) -> attend(q, k, v)``: the attention of one (pass,
    layer), ``table`` its traced index."""
    L = cfg.n_layers
    # the tokens' rows of the rotation's tables, gathered once for all
    # T L layer passes (they are at the same positions in every one):
    # gathered a layer, the two gathers were 0.6 ms of a 21.9 ms step
    cos, sin = rope
    here = (cos[positions], sin[positions])

    def one_pass(x, t):
        def body(x, xs):
            lw, l = xs
            return _layer(x, lw, cfg, here, None, attend_at(t * L + l))

        x, rows = jax.lax.scan(
            body, x, (params["layers"], jnp.arange(L, dtype=jnp.int32)))
        with jax.named_scope("loop/final_norm"):
            return rms_norm(x, params["final_norm"], cfg.norm_eps), rows

    x, (k, v) = jax.lax.scan(
        one_pass, x.astype(F32), jnp.arange(cfg.loop_steps, dtype=jnp.int32))
    return x, k.reshape((-1,) + k.shape[2:]), v.reshape((-1,) + v.shape[2:])


@jax.named_scope("lm_head")
def _logits(params, cfg: ModelConfig, h, logit_pos=None):
    """The head over the last pass's output, which is normed already;
    with ``logit_pos`` [B] over ONE position a row -> [B, 1, V]."""
    if logit_pos is not None:
        h = jnp.take_along_axis(
            h, logit_pos[:, None, None].astype(jnp.int32), axis=1)
    h = h.astype(cfg.jdtype)
    if cfg.tie_embeddings:
        return jnp.dot(h, params["embedding"].T,
                       preferred_element_type=jnp.float32)
    return qmatmul(h, params["lm_head"]).astype(jnp.float32)


# -- the programs --------------------------------------------------------------

def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded), every pass
    within its own keys. Returns (logits [B, S, V] float32, or [B, 1, V]
    with ``logit_pos``; K and V stacks [T L, B, S, KV, hd]; lengths)."""
    lengths, positions, valid = prompt_rows(tokens, lengths)
    rope = rope_tables or get_rope_tables(cfg, rope_max or tokens.shape[1])
    attend = prompt_attend(flash, lengths, valid, mesh)
    x, k, v = _loop(params, cfg, embed(params, cfg, tokens), rope,
                    positions, lambda table: attend)
    return _logits(params, cfg, x, logit_pos), k, v, lengths


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``)."""
    return prefill_kv(params, cfg, tokens, lengths, logit_pos=logit_pos)[0]


def _table(cache: llama.KVCache, table):
    """(k, v, k_scale, v_scale) of one table, the scales None where the
    cache has none."""
    return tuple(None if a is None else jax.lax.dynamic_index_in_dim(
        a, table, 0, keepdims=False)
        for a in (cache.k, cache.v, cache.k_scale, cache.v_scale))


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: llama.KVCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C) against the
    cache: every (pass, layer) attends to its own table's rows before
    the chunk and causally within it; the chunk's rows are written after
    the loop. ``cache.lengths`` is not advanced (llama.prefill_chunk's
    contract)."""
    B, C = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    rope = rope_tables or get_rope_tables(cfg, cache.capacity)

    def attend_at(table):
        def attend(q, k_new, v_new):
            k_l, v_l, ks_l, vs_l = _table(cache, table)
            return chunk_attention(q, k_l, v_l, k_new, v_new, start, ks_l,
                                   vs_l)
        return attend

    x, k, v = _loop(params, cfg, embed(params, cfg, tokens), rope,
                    positions, attend_at)
    cache = write_kv(cache, k, v, (0, 0, 0, start, 0), cache.lengths)
    if not compute_logits:
        return None, cache
    return _logits(params, cfg, x, logit_pos), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: llama.KVCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B]: ``loop_steps`` passes, each (pass,
    layer) reading its table in place (the flash-decode kernel where
    ``kernel_block`` answers, the jnp reference otherwise) with the
    token's own k and v riding alongside; the T L rows the step made are
    written after the loop (llama.decode_step's discipline and capacity
    contract). Returns (logits [B, V] float32, the cache with lengths +
    1)."""
    lengths = cache.lengths
    positions = lengths[:, None]
    live = lengths if active is None else jnp.where(active, lengths, 0)
    rope = rope_tables or get_rope_tables(cfg, cache.capacity)
    block_s = flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)

    def attend_at(table):
        def attend(q, k_new, v_new):
            with jax.named_scope("attn"):
                if block_s:
                    return flash_decode.decode_attention_auto(
                        q, cache.k, cache.v, k_new, v_new, live, table,
                        cache.k_scale, cache.v_scale, block_s=block_s,
                        mesh=mesh)
                k_l, v_l, ks_l, vs_l = _table(cache, table)
                return decode_attention_appended(
                    q, k_l, v_l, k_new, v_new, lengths, ks_l, vs_l)
        return attend

    x, k, v = _loop(params, cfg, embed(params, cfg, tokens[:, None]), rope,
                    positions, attend_at)
    with jax.named_scope("kv_write"):
        new = llama.write_rows(cache, k, v, positions, lengths + 1,
                               cfg.n_heads, mesh)
    return _logits(params, cfg, x[:, 0]), new
