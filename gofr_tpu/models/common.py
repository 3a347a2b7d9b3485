"""Shared model configuration and initializer helpers."""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str = "custom"
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    rope_scaling: dict | None = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # encoder-model extras
    n_classes: int = 0
    image_size: int = 224
    patch_size: int = 14
    type_vocab_size: int = 2
    # mixture-of-experts (0 experts = dense FFN)
    n_experts: int = 0
    experts_per_token: int = 2
    # 0 = nothing is dropped: dense dispatch (every expert computes every
    # token) in the trainer, the decode block and prompt programs of up
    # to 128 tokens, the routed block dispatch of models/moe.py in the
    # serving prompt programs past it (llama.routes); > 0 =
    # capacity-based grouped dispatch with
    # per-expert buffer capacity factor*T*k/E (tokens over capacity drop
    # — the standard Switch/Mixtral trade at scale)
    moe_capacity_factor: float = 0.0
    # latent attention (models/deepseek_v3.py; kv_lora_rank > 0 selects
    # that family): the cache holds one row of kv_lora_rank +
    # qk_rope_head_dim values a token a layer, shared by every head.
    # Query/key width (nope + rope) and value width are separate keys
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # that family's expert layer: a sigmoid router over n_experts, kept
    # to the topk_groups best of n_expert_groups groups, its weights
    # renormalised and scaled by routed_scaling; experts of width
    # moe_ffn_dim, n_shared_experts of them always on; the first
    # n_dense_layers layers are dense SwiGLU of width ffn_dim.
    # n_experts_held: the experts THIS chip holds (ids 0..n-1; 0 = all)
    # of an expert-parallel deployment; the router stays n_experts wide
    n_expert_groups: int = 1
    topk_groups: int = 1
    routed_scaling: float = 1.0
    n_shared_experts: int = 0
    moe_ffn_dim: int = 0
    n_dense_layers: int = 0
    n_experts_held: int = 0
    # layers of more than one kind (models/solar_open2.py; a "linear"
    # entry selects that family): one period of the stack, e.g. ("full",
    # "linear", "linear", "linear"); layer l is of kind
    # pattern[l % len(pattern)]. () = every layer the family's one kind.
    # A full layer is softmax attention of n_heads x attn_head_dim
    # (0 = dim // n_heads), rotated unless use_rope is false, with an
    # output gate where attn_gate; a linear layer is the gated delta
    # rule: linear_heads heads of linear_head_dim (key and value), a
    # causal depthwise convolution over conv_kernel inputs on q, k and
    # v, and two low-rank projections of rank gate_rank (decay, gate)
    layer_pattern: tuple = ()
    use_rope: bool = True
    attn_gate: bool = False
    attn_head_dim: int = 0
    linear_heads: int = 0
    linear_head_dim: int = 0
    conv_kernel: int = 0
    gate_rank: int = 0
    # sliding-window layers beside full ones (models/laguna.py; a
    # "window" entry in layer_pattern selects that family): a window
    # layer attends to the last window_size positions, the token's own
    # among them, and caches them on a ring of window_size rows; it has
    # window_heads query heads (0 = n_heads) on the same n_kv_heads and
    # rotates the whole head by plain frequencies of window_rope_theta
    # (0 = rope_theta). A full layer rotates the first rotary_dim values
    # of a head (0 = all) by rope_theta and rope_scaling. head_gate: the
    # output gate is one value a head, sigmoid(W_g x) [heads]
    window_size: int = 0
    window_heads: int = 0
    window_rope_theta: float = 0.0
    rotary_dim: int = 0
    head_gate: bool = False
    # gated short-convolution layers beside full ones (models/lfm2.py; a
    # "conv" entry in layer_pattern selects that family): a conv layer
    # is y = W_out (C * conv(B * X)) with [B | C | X] = W_in h and a
    # causal depthwise convolution over conv_kernel inputs, no bias and
    # no activation; its whole cache is the last conv_kernel - 1 inputs
    # of the convolution, whatever the length. qk_norm: a full layer's q
    # and k are RMS-normed a head (weights of head_dim) before the
    # rotation
    qk_norm: bool = False
    # state-space layers beside attention and expert layers, ONE block a
    # layer (models/nemotron_h.py; a "mamba" entry in layer_pattern
    # selects that family, and there the pattern names every layer, so a
    # stack that does not tile is one period of n_layers entries: "mamba",
    # "moe" or "attn", each x += Block(RMSNorm(x))). A mamba layer is
    # Mamba-2: ssm_heads heads of ssm_head_dim, B and C shared by the
    # heads of one of ssm_groups groups, a state of ssm_state values a
    # channel, a causal depthwise convolution over conv_kernel inputs
    # with a bias, prompts run in chunks of ssm_chunk. A moe layer routes
    # as the latent family does, but where moe_latent_dim > 0 the routed
    # experts read and write a latent of that width (one projection down
    # before the dispatch, one up after the weighted sum); expert_act is
    # the experts' form, "swiglu" (three matrices) or "relu2" (two:
    # W2 relu(W1 x)^2, no gate), the shared expert's too, whose width is
    # shared_ffn_dim (0 = moe_ffn_dim x n_shared_experts)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_chunk: int = 128
    moe_latent_dim: int = 0
    expert_act: str = "swiglu"
    shared_ffn_dim: int = 0
    # the same family with a layer of TWO halves (``model_type:
    # granitemoehybrid``, dense): layer_ffn says every layer is a mixer
    # (``layer_pattern[l]``: "mamba" or "attn", no "moe") and THEN a
    # gated feed-forward of ffn_dim, W_out (silu(g) * h) with
    # [g | h] = W_in N2(x), each half behind a norm of its own and added
    # to the stream times residual_multiplier. The embedding is
    # multiplied by embedding_multiplier, the attention layers' softmax
    # runs at scale attention_multiplier (0 = head_dim^-1/2) and the
    # logits are divided by logits_scaling, in every family that reads
    # them (models/blocks.py:embed, models/llama.py:logits)
    layer_ffn: bool = False
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # latent attention at two widths with a learned selection
    # (models/dots3_note.py; kv_lora_rank > 0 WITH a "window" entry in
    # layer_pattern selects that family). A window layer is latent
    # attention at its own sizes, window_heads heads of
    # window_qk_nope_head_dim + window_qk_rope_head_dim on a latent of
    # window_kv_lora_rank behind a query latent of window_q_lora_rank,
    # values of window_v_head_dim (each 0 = the full layers' size),
    # rotated by window_rope_theta; it sees window_size positions, the
    # token's own among them, and caches window_size - 1 rows on a ring.
    # A full layer has an indexer of index_heads heads of index_head_dim
    # that scores every cached position and keeps the index_topk best:
    # the softmax runs over those alone. lora_rescale (the published
    # switch): the normed query and key-value latents are multiplied by
    # (dim / their rank)^1/2. The family refuses index_topk 0, head_gate
    # or lora_rescale false: they are what it is
    window_q_lora_rank: int = 0
    window_kv_lora_rank: int = 0
    window_qk_nope_head_dim: int = 0
    window_qk_rope_head_dim: int = 0
    window_v_head_dim: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    lora_rescale: bool = False
    # a stack run several times a token (models/ouro.py; loop_steps > 1
    # or sandwich_norm selects that family): the n_layers layers are run
    # loop_steps times with the same weights, the final norm after every
    # pass and its output fed to the next; each (pass, layer) keeps K and
    # V rows of its own, so a token's cache is loop_steps x n_layers
    # tables. sandwich_norm: a norm before AND after each of attention
    # and feed-forward, the second inside the residual branch.
    # early_exit_threshold: the cumulated exit probability at which a
    # token leaves the loop; 1 (the published value) runs every pass, and
    # anything under it is refused here: a step's passes would differ by
    # slot
    loop_steps: int = 1
    sandwich_norm: bool = False
    early_exit_threshold: float = 1.0
    # generation by diffusion over blocks (models/sdar.py; block_length
    # > 0 selects that family): a slot generates block_length positions
    # at a time. A denoise pass runs the stack over the whole block, a
    # masked position carrying the embedding of mask_token_id, each
    # position attending every cached row and all of the block, and
    # commits some of the masked positions from the head's
    # distributions at their own rows; when none is masked a commit pass
    # runs the stack over the block's final tokens and writes its rows.
    # Attention is block-causal everywhere: position i sees j iff
    # j // block_length <= i // block_length. commit_order picks what a
    # pass commits, k = block_length // denoise_passes positions of it:
    # "sequential" (the leftmost k masked), "low_confidence_static" (the
    # k most confident) or "low_confidence_dynamic" (every position
    # whose confidence passes confidence_threshold, the most confident
    # one where none does). router_score: what a routed layer's router
    # scores with, "sigmoid" (models/moe.py's first families) or
    # "softmax" over all experts, the chosen renormalised
    block_length: int = 0
    mask_token_id: int = 0
    denoise_passes: int = 0
    commit_order: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    router_score: str = "sigmoid"

    def __post_init__(self):
        # a configuration file gives the pattern as a list
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold} < 1: "
                "every decode step runs all loop_steps passes for every "
                "slot; an exit before the last pass is not implemented")
        if self.block_length:
            passes = self.denoise_passes or self.block_length
            if self.block_length % passes or self.commit_order not in (
                    "sequential", "low_confidence_static",
                    "low_confidence_dynamic") \
                    or not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(
                    f"block_length {self.block_length}: denoise_passes "
                    f"{self.denoise_passes} must divide it, commit_order "
                    f"{self.commit_order!r} be one of the three published "
                    f"and mask_token_id {self.mask_token_id} a row of the "
                    "vocabulary")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.dim // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


LLAMA_CONFIGS = {
    # Llama-3-8B / 70B (architecture dims are public knowledge)
    "llama3-8b": ModelConfig(name="llama3-8b", vocab_size=128256, dim=4096,
                             n_layers=32, n_heads=32, n_kv_heads=8,
                             ffn_dim=14336, max_seq=8192),
    "llama3-70b": ModelConfig(name="llama3-70b", vocab_size=128256, dim=8192,
                              n_layers=80, n_heads=64, n_kv_heads=8,
                              ffn_dim=28672, max_seq=8192),
    # small variants for single-chip serving and tests
    "llama-1b": ModelConfig(name="llama-1b", vocab_size=128256, dim=2048,
                            n_layers=16, n_heads=32, n_kv_heads=8,
                            ffn_dim=8192, max_seq=8192, tie_embeddings=True),
    "tiny": ModelConfig(name="tiny", vocab_size=256, dim=64, n_layers=2,
                        n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq=128,
                        rope_theta=10000.0, dtype="float32"),
    # Mixtral-8x7B (public dims): top-2 of 8 SwiGLU experts per layer
    "mixtral-8x7b": ModelConfig(name="mixtral-8x7b", vocab_size=32000,
                                dim=4096, n_layers=32, n_heads=32,
                                n_kv_heads=8, ffn_dim=14336, max_seq=8192,
                                rope_theta=1e6, n_experts=8,
                                experts_per_token=2),
    "tiny-moe": ModelConfig(name="tiny-moe", vocab_size=256, dim=64,
                            n_layers=2, n_heads=4, n_kv_heads=2,
                            ffn_dim=128, max_seq=128, rope_theta=10000.0,
                            dtype="float32", n_experts=4,
                            experts_per_token=2),
    # the latent-attention family at test size: every rank and head width
    # differs from every other, so a swapped width fails a shape check
    "tiny-mla-moe": ModelConfig(
        name="tiny-mla-moe", vocab_size=256, dim=64, n_layers=3, n_heads=4,
        n_kv_heads=4, ffn_dim=160, max_seq=128, rope_theta=10000.0,
        norm_eps=1e-6, dtype="float32",
        rope_scaling={"rope_type": "yarn", "factor": 4.0,
                      "original_max_position_embeddings": 32,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1},
        q_lora_rank=48, kv_lora_rank=36, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=20, n_experts=16,
        experts_per_token=4, n_expert_groups=4, topk_groups=2,
        routed_scaling=2.5, n_shared_experts=1, moe_ffn_dim=40,
        n_dense_layers=1, n_experts_held=4),
    # the hybrid family at test size: two periods of one full layer to
    # three linear ones, head sizes that differ from dim // n_heads and
    # from each other, 4 of 16 experts held
    "tiny-kda-moe": ModelConfig(
        name="tiny-kda-moe", vocab_size=256, dim=64, n_layers=8, n_heads=4,
        n_kv_heads=2, ffn_dim=128, max_seq=128, norm_eps=1e-5,
        dtype="float32", layer_pattern=("full", "linear", "linear",
                                        "linear"),
        use_rope=False, attn_gate=True, attn_head_dim=24, linear_heads=4,
        linear_head_dim=16, conv_kernel=4, gate_rank=8, n_experts=16,
        experts_per_token=4, n_expert_groups=1, topk_groups=1,
        routed_scaling=1.0, n_shared_experts=1, moe_ffn_dim=40,
        n_experts_held=4),
    # the window family at test size: two periods of one full layer to
    # three window layers, a window every test prompt wraps, head counts
    # that differ by kind (groups of 3 and 4), half the head rotated on
    # the full layers, one dense layer before the routed ones
    "tiny-swa-moe": ModelConfig(
        name="tiny-swa-moe", vocab_size=256, dim=64, n_layers=8, n_heads=6,
        n_kv_heads=2, ffn_dim=96, max_seq=128, rope_theta=500000.0,
        norm_eps=1e-6, dtype="float32",
        rope_scaling={"rope_type": "yarn", "factor": 4.0,
                      "original_max_position_embeddings": 32,
                      "beta_fast": 32, "beta_slow": 1,
                      "attention_factor": 1.1386},
        layer_pattern=("full", "window", "window", "window"),
        attn_head_dim=16, window_size=8, window_heads=8,
        window_rope_theta=10000.0, rotary_dim=8, head_gate=True,
        n_experts=8, experts_per_token=2, n_expert_groups=1, topk_groups=1,
        routed_scaling=2.5, n_shared_experts=1, moe_ffn_dim=40,
        n_dense_layers=1),
    # the conv family at test size: two periods of three gated
    # short-convolution layers to one full layer, heads of 16 in groups
    # of three (a pair of KV heads a cache row), a q/k norm a head, one
    # dense layer before the routed ones, no shared expert, tied head
    "tiny-conv-moe": ModelConfig(
        name="tiny-conv-moe", vocab_size=256, dim=64, n_layers=8, n_heads=6,
        n_kv_heads=2, ffn_dim=96, max_seq=128, rope_theta=1e6,
        norm_eps=1e-5, tie_embeddings=True, dtype="float32",
        layer_pattern=("conv", "conv", "full", "conv"), attn_head_dim=16,
        conv_kernel=3, qk_norm=True, n_experts=8, experts_per_token=2,
        n_expert_groups=1, topk_groups=1, routed_scaling=1.0,
        n_shared_experts=0, moe_ffn_dim=40, n_dense_layers=1),
    # the state-space family at test size: eleven layers of three kinds
    # in an order that does not tile (as the published string does not),
    # 8 heads of 8 in 2 groups (G < H), a state of 16, chunks of 8, a
    # latent of 24 behind a 16-way router with 4 experts held, two-matrix
    # relu2 experts of width 40 and a shared one of 56
    "tiny-ssm-moe": ModelConfig(
        name="tiny-ssm-moe", vocab_size=256, dim=64, n_layers=11, n_heads=4,
        n_kv_heads=2, ffn_dim=128, max_seq=128, norm_eps=1e-5,
        dtype="float32",
        layer_pattern=("mamba", "moe", "mamba", "moe", "mamba", "attn",
                       "moe", "mamba", "moe", "attn", "mamba"),
        use_rope=False, attn_head_dim=24, conv_kernel=4, ssm_heads=8,
        ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_chunk=8,
        n_experts=16, experts_per_token=4, n_expert_groups=1, topk_groups=1,
        routed_scaling=2.5, n_shared_experts=1, moe_ffn_dim=40,
        n_experts_held=4, moe_latent_dim=24, expert_act="relu2",
        shared_ffn_dim=56),
    # the state-space family's layer of two halves at test size: six
    # layers, a mixer and a gated feed-forward each, two attention layers
    # among four mamba layers of ONE group (8 heads of 16), attention
    # heads of 64 (not dim // n_heads: two KV heads a cache row), every
    # multiplier away from 1, the attention's scale a power of two times
    # head_dim^-1/2 as the published one is, tied head
    "tiny-ssm-dense": ModelConfig(
        name="tiny-ssm-dense", vocab_size=256, dim=64, n_layers=6,
        n_heads=4, n_kv_heads=2, ffn_dim=96, max_seq=128, norm_eps=1e-5,
        tie_embeddings=True, dtype="float32",
        layer_pattern=("mamba", "attn", "mamba", "mamba", "attn", "mamba"),
        use_rope=False, attn_head_dim=64, conv_kernel=4, ssm_heads=8,
        ssm_head_dim=16, ssm_groups=1, ssm_state=16, ssm_chunk=8,
        layer_ffn=True, embedding_multiplier=3.0, residual_multiplier=0.4,
        attention_multiplier=0.03125, logits_scaling=2.0),
    # the sparse-latent family at test size: a dense full layer, then two
    # periods of (full, window, window, window): full layers keep 16 of
    # up to 128 cached rows, window layers see 9 positions (a ring of 8
    # that every test prompt wraps), every width of one kind differs
    # from the other kind's, 4 of 16 experts held
    "tiny-dsa-moe": ModelConfig(
        name="tiny-dsa-moe", vocab_size=256, dim=64, n_layers=9, n_heads=4,
        n_kv_heads=4, ffn_dim=160, max_seq=128, rope_theta=8e7,
        norm_eps=1e-5, dtype="float32",
        layer_pattern=("full", "full", "window", "window", "window",
                       "full", "window", "window", "window"),
        q_lora_rank=48, kv_lora_rank=36, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=20, window_size=9, window_heads=2,
        window_rope_theta=50000.0, window_q_lora_rank=40,
        window_kv_lora_rank=44, window_qk_nope_head_dim=28,
        window_qk_rope_head_dim=4, window_v_head_dim=12, head_gate=True,
        index_heads=3, index_head_dim=16, index_topk=16, lora_rescale=True,
        n_experts=16, experts_per_token=4, n_expert_groups=1, topk_groups=1,
        routed_scaling=1.0, n_shared_experts=1, moe_ffn_dim=40,
        n_dense_layers=1, n_experts_held=4),
    # the looped family at test size: two layers run three times (six
    # tables a token), four norms a layer, no grouping (a KV head a query
    # head, as published), untied head
    "tiny-loop": ModelConfig(
        name="tiny-loop", vocab_size=256, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=4, ffn_dim=96, max_seq=128, rope_theta=1e6,
        norm_eps=1e-6, dtype="float32", loop_steps=3, sandwich_norm=True),
    # the block-diffusion family at test size: blocks of four positions
    # committed two a pass, groups of three query heads a KV head, a q/k
    # norm a head, a softmax router over eight experts all held, no
    # shared expert, untied head; the mask token is an id the tests also
    # send as a real token
    "tiny-diffusion-moe": ModelConfig(
        name="tiny-diffusion-moe", vocab_size=256, dim=64, n_layers=3,
        n_heads=6, n_kv_heads=2, ffn_dim=96, max_seq=128, rope_theta=1e6,
        norm_eps=1e-6, dtype="float32", attn_head_dim=16, qk_norm=True,
        n_experts=8, experts_per_token=2, moe_ffn_dim=40,
        router_score="softmax", block_length=4, mask_token_id=255,
        denoise_passes=2, commit_order="sequential"),
}

BERT_CONFIGS = {
    "bert-base": ModelConfig(name="bert-base", vocab_size=30522, dim=768,
                             n_layers=12, n_heads=12, n_kv_heads=12,
                             ffn_dim=3072, max_seq=512, norm_eps=1e-12),
    "tiny": ModelConfig(name="tiny-bert", vocab_size=128, dim=64, n_layers=2,
                        n_heads=4, n_kv_heads=4, ffn_dim=128, max_seq=64,
                        norm_eps=1e-12, dtype="float32"),
}

VIT_CONFIGS = {
    "vit-l-14": ModelConfig(name="vit-l-14", dim=1024, n_layers=24,
                            n_heads=16, n_kv_heads=16, ffn_dim=4096,
                            image_size=224, patch_size=14, n_classes=1000),
    "tiny": ModelConfig(name="tiny-vit", dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=4, ffn_dim=128, image_size=28,
                        patch_size=14, n_classes=10, dtype="float32"),
}


def refused_options(reasons: dict[str, str], *, mesh=None,
                    paged_blocks: int = 0, kvcache=None,
                    spec_decode_k: int = 0, lora_adapters: int = 0,
                    kv_dtype=None, serving_role: str | None = None
                    ) -> list[tuple[str, str]]:
    """(engine option, reason) for every serving option that is asked for
    and that a family does not run yet, in the engine constructor's own
    names and order. The engine raises on any of them at start-up: never
    a fall-through to the Llama cache. ``reasons``: the family's own
    words, option -> why not; ``kv_dtype``'s is said of an int8 row (a
    family that caches one has no such entry), ``serving_role``'s after
    the role that was asked for."""
    asked = {
        "mesh": mesh is not None,
        "paged_blocks": bool(paged_blocks),
        "kvcache": kvcache is not None and (kvcache.host_mb > 0
                                            or kvcache.redis is not None),
        "spec_decode_k": bool(spec_decode_k),
        "lora_adapters": bool(lora_adapters),
        "kv_dtype": kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8,
        "serving_role": serving_role not in (None, "", "fused"),
    }
    said = dict(reasons)
    if "serving_role" in said:
        said["serving_role"] = f"{serving_role}: {said['serving_role']}"
    return [(option, said[option]) for option, is_asked in asked.items()
            if is_asked and option in said]


def dense_init(key, shape, dtype, scale: float | None = None) -> jnp.ndarray:
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def sample_logits(logits: jnp.ndarray, key, temperature: float = 0.0,
                  top_k: int = 0) -> jnp.ndarray:
    """Sample token ids from [B, V] logits. temperature<=0 -> greedy.
    Shape-static (top_k is a python int) so it jits once."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
