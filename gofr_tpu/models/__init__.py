"""Model zoo: Llama (flagship decode path), BERT (embeddings), ViT (vision).

All models are pure-functional JAX: ``init(cfg, key) -> params`` pytrees of
plain arrays (or QuantizedLinear leaves), ``apply``-style forwards, static
shapes, layers stacked on a leading axis and iterated with ``lax.scan`` so
compile time stays flat in depth and pipeline parallelism can split the
layer axis. No torch, no module classes — params are data, which is what
``jax.sharding`` wants to see.
"""

from .common import ModelConfig, LLAMA_CONFIGS, BERT_CONFIGS, VIT_CONFIGS
from . import (llama, bert, vit, deepseek_v3, solar_open2, laguna, lfm2,
               nemotron_h)


def family(cfg: ModelConfig):
    """The module that holds a decoder configuration's programs and its
    cache row layout, chosen from what the configuration says (a name
    tells nothing: the benchmark registers configurations the program
    has never heard of). Every family gives the generator the same entry
    points: ``init``, ``init_cache``, ``get_rope_tables``, ``prefill_kv``,
    ``write_kv``, ``prefill_chunk``, ``decode_step``, ``decode_kv_block``,
    ``kv_layout``, ``unsupported_options``, ``serving_stats``, ``forward``,
    and ``RECOMPUTABLE``: whether a cached position can be computed
    again and give the same memory (rows can; a recurrent or
    state-space state, a ring of rows and a convolution's tail cannot)."""
    if "mamba" in cfg.layer_pattern:
        return nemotron_h
    if "linear" in cfg.layer_pattern:
        return solar_open2
    if "window" in cfg.layer_pattern:
        return laguna
    if "conv" in cfg.layer_pattern:
        return lfm2
    return deepseek_v3 if cfg.kv_lora_rank > 0 else llama


__all__ = ["ModelConfig", "LLAMA_CONFIGS", "BERT_CONFIGS", "VIT_CONFIGS",
           "llama", "bert", "vit", "deepseek_v3", "solar_open2", "laguna",
           "lfm2", "nemotron_h", "family"]
