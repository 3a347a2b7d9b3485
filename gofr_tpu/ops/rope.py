"""Rotary position embeddings (RoPE): Llama-3 frequency scaling, or YaRN.

Frequencies are precomputed once per model (static shapes — nothing here
re-traces per step); application is a fused elementwise op that XLA folds
into the surrounding attention computation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0,
                     scaling: dict | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute (cos, sin) tables of shape [max_seq, head_dim//2].

    ``scaling`` supports the Llama-3 frequency-scaling dict
    {factor, low_freq_factor, high_freq_factor, original_max_position},
    and with ``rope_type: "yarn"`` the YaRN dict (``yarn_inv_freq``);
    an ``attention_factor`` there is what the tables are multiplied by,
    in place of the one reckoned from ``mscale``. ``head_dim`` is the
    width that is rotated, which ``apply_rope_part`` lets be narrower
    than the head.
    """
    if is_yarn(scaling):
        inv_freq = yarn_inv_freq(head_dim, theta, scaling)
        mscale = scaling.get("attention_factor") or (
            yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
            / yarn_mscale(scaling["factor"],
                          scaling.get("mscale_all_dim", 0)))
        freqs = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32), inv_freq)
        return jnp.cos(freqs) * mscale, jnp.sin(freqs) * mscale
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling:
        factor = scaling.get("factor", 8.0)
        low = scaling.get("low_freq_factor", 1.0)
        high = scaling.get("high_freq_factor", 4.0)
        orig = scaling.get("original_max_position", 8192)
        wavelen = 2.0 * jnp.pi / inv_freq
        ratio = orig / wavelen
        smooth = jnp.clip((ratio - low) / (high - low), 0.0, 1.0)
        inv_freq = jnp.where(
            wavelen > orig / low,  # long wavelengths: fully scaled
            inv_freq / factor,
            inv_freq * smooth + (inv_freq / factor) * (1.0 - smooth),
        )
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [max_seq, head_dim//2]
    return jnp.cos(freqs), jnp.sin(freqs)


def is_yarn(scaling: dict | None) -> bool:
    return bool(scaling) and scaling.get("rope_type") == "yarn"


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_scale(scaling: dict | None) -> float:
    """What YaRN multiplies the softmax scale by: yarn_mscale(factor,
    mscale_all_dim) squared (1 without YaRN or without mscale_all_dim)."""
    if not is_yarn(scaling) or not scaling.get("mscale_all_dim"):
        return 1.0
    return yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> jnp.ndarray:
    """YaRN inverse frequencies [dim // 2], as the published
    DeepseekV3YarnRotaryEmbedding computes them: theta^(-2i/dim), and
    that over ``factor``, blended by a linear ramp between the dims whose
    rotations over ``original_max_position_embeddings`` positions are
    ``beta_fast`` and ``beta_slow``: fast dims keep their frequency,
    slow dims are interpolated."""
    factor = float(scaling["factor"])
    orig = scaling.get("original_max_position_embeddings", 4096)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))),
               dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


@jax.named_scope("apply_rope")
def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray | None) -> jnp.ndarray:
    """Rotate ``x`` [..., seq, heads, head_dim] by per-token positions.

    ``positions`` is [..., seq] int32 — explicit positions (not an offset)
    so continuous batching can give every sequence its own cursor.

    ``positions=None`` means ``cos``/``sin`` are already per-token
    ([..., seq, hd/2], i.e. pre-gathered by the caller). Sharded forwards
    use this to gather ONCE outside the layer scan under an activation
    sharding constraint — gathering inside each layer let GSPMD pick a
    feature-dim sharding for the [B, S, hd/2] result and then
    involuntarily full-rematerialize it back to the (data, sp) layout
    every step (the MULTICHIP_r03 spmd_partitioner warnings).
    """
    dtype = x.dtype
    if positions is None:
        c = cos[..., :, None, :]             # [..., seq, 1, hd/2]
        s = sin[..., :, None, :]
    else:
        c = cos[positions][..., :, None, :]  # [..., seq, 1, hd/2]
        s = sin[positions][..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)


def apply_rope_part(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                    positions: jnp.ndarray | None) -> jnp.ndarray:
    """``apply_rope`` over the first ``2 * cos.shape[-1]`` values of each
    head (paired as halves of that part); the rest passes through. Tables
    as wide as the head rotate all of it."""
    part = 2 * cos.shape[-1]
    if part == x.shape[-1]:
        return apply_rope(x, cos, sin, positions)
    return jnp.concatenate(
        [apply_rope(x[..., :part], cos, sin, positions), x[..., part:]], -1)
