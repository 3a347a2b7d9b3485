"""Normalization ops.

Computed in float32 regardless of input dtype (TPU VPU-friendly; bf16
accumulation of variances loses too much precision), cast back on exit so
surrounding matmuls stay bf16 on the MXU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("rms_norm")
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm (Llama-style): x * w / rms(x)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


@jax.named_scope("layer_norm")
def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-12) -> jnp.ndarray:
    """LayerNorm (BERT/ViT-style)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)
