"""Model zoo: nine decoder families behind one seam (``family``), BERT
(embeddings), ViT (vision).

The decoders: ``llama`` (dense GQA and Mixtral's eight experts; the row
cache and the dense block every family builds on), ``deepseek_v3``
(latent attention), ``solar_open2`` (gated delta-rule layers beside full
ones), ``laguna`` (sliding-window layers on a ring), ``lfm2`` (gated
short convolutions), ``nemotron_h`` (Mamba-2 state-space layers, one
block a layer or a mixer and a dense feed-forward a layer),
``dots3_note`` (latent attention at two widths: full layers that select
the rows they read, window layers on a ring of latent rows), ``ouro``
(one dense stack run several times a token, each pass with row tables of
its own, four norms a layer), ``sdar`` (generation by diffusion over
blocks of positions: a decode step is a pass over a slot's whole block,
attention is block-causal, a prefill yields no token). What they
share has an owner that is no family: ``moe`` (the routed feed-forward
of the seven sparse ones), ``latent`` (the latent row's cache and the
absorbed form's algebra, the two latent families'), ``blocks``
(embedding, the gather before the logits, a layer out of a stack, the
window and conv families' attention block and stack), ``hybrid_cache``
(a state beside rows) and ``common`` (the configuration and the one list
of serving options a family may refuse). A family imports those, ``llama`` and ``ops/``, never
a sibling family nor another module's private name
(tests/test_models_layering.py; docs/tpu/serving-engine.md says what a
new family touches).

All models are pure-functional JAX: ``init(cfg, key) -> params`` pytrees of
plain arrays (or QuantizedLinear leaves), ``apply``-style forwards, static
shapes, layers stacked on a leading axis and iterated with ``lax.scan`` so
compile time stays flat in depth and pipeline parallelism can split the
layer axis. No torch, no module classes — params are data, which is what
``jax.sharding`` wants to see.
"""

from .common import ModelConfig, LLAMA_CONFIGS, BERT_CONFIGS, VIT_CONFIGS
from . import (llama, bert, vit, moe, latent, blocks, hybrid_cache,
               deepseek_v3, solar_open2, laguna, lfm2, nemotron_h,
               dots3_note, ouro, sdar)


def family(cfg: ModelConfig):
    """The module that holds a decoder configuration's programs and its
    cache row layout, chosen from what the configuration says (a name
    tells nothing: the benchmark registers configurations the program
    has never heard of). Every family gives the generator the same entry
    points: ``init``, ``init_cache``, ``get_rope_tables``, ``prefill_kv``,
    ``write_kv``, ``prefill_chunk``, ``decode_step``, ``decode_kv_block``,
    ``kv_layout``, ``kv_tables`` (the row tables a cached token has:
    the depth, but for a stack that is run several times),
    ``chunk_block`` (the block of cached rows a chunk's attention walks
    up to its start; 0 where it walks under no cursor),
    ``unsupported_options``, ``serving_stats``, ``forward``,
    and ``RECOMPUTABLE``: whether a cached position can be computed
    again and give the same memory (rows can; a recurrent or
    state-space state, a ring of rows and a convolution's tail cannot).
    The block-diffusion family also gives ``candidates``, ``commits``
    and ``logits``, which its decode program alone asks
    (``programs._block_decode_scan``); the two latent families give
    ``chunk_walk_kernel`` (whether a chunk program of a size walks its
    cached rows in ``ops.mla.chunk_walk_latent``), by which the engine
    counts its chunk dispatches."""
    if cfg.block_length > 0:
        return sdar
    if "mamba" in cfg.layer_pattern:
        return nemotron_h
    if "linear" in cfg.layer_pattern:
        return solar_open2
    if "window" in cfg.layer_pattern:
        # latent rows on the ring, or K and V heads
        return dots3_note if cfg.kv_lora_rank > 0 else laguna
    if "conv" in cfg.layer_pattern:
        return lfm2
    if cfg.loop_steps > 1 or cfg.sandwich_norm:
        return ouro
    return deepseek_v3 if cfg.kv_lora_rank > 0 else llama


__all__ = ["ModelConfig", "LLAMA_CONFIGS", "BERT_CONFIGS", "VIT_CONFIGS",
           "llama", "bert", "vit", "moe", "latent", "blocks",
           "hybrid_cache", "deepseek_v3", "solar_open2", "laguna", "lfm2",
           "nemotron_h", "dots3_note", "ouro", "sdar", "family"]
