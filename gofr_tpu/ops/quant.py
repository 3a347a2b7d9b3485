"""Int8 weight-only quantization.

Decode-phase LLM serving is HBM-bandwidth-bound: every step streams the full
weight set through the MXU. Per-output-channel int8 storage halves that
traffic vs bf16 at negligible quality cost. XLA fuses the int8->bf16 convert
and the scale multiply into the matmul, so the MXU still sees one dense
contraction.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class QuantizedLinear(NamedTuple):
    """Per-output-channel symmetric int8 weight. ``w``: [in, out] int8,
    ``scale``: [out] float32 with  w_true ≈ w * scale."""

    w: jnp.ndarray
    scale: jnp.ndarray


def quantize_int8(w: jnp.ndarray, axis: int = 0) -> QuantizedLinear:
    """Quantize a [in, out] weight per output channel (reduce over ``axis``)."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QuantizedLinear(w=q, scale=scale.squeeze(axis).astype(jnp.float32))


@jax.named_scope("qmatmul")
def qmatmul(x: jnp.ndarray, qw: "QuantizedLinear | jnp.ndarray",
            out_dtype=None) -> jnp.ndarray:
    """x @ w for quantized or plain weights.

    x: [..., in]; returns [..., out] in x.dtype (``out_dtype`` where
    given: float32 hands out the accumulator unrounded). For
    QuantizedLinear the int8 tensor is upcast in-register (fused by XLA)
    and scaled after the contraction, keeping the accumulation in f32.
    """
    out_dtype = out_dtype or x.dtype
    if isinstance(qw, QuantizedLinear):
        y = jax.lax.dot_general(
            x, qw.w.astype(x.dtype),
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (y * qw.scale).astype(out_dtype)
    return jnp.dot(x, qw, preferred_element_type=jnp.float32).astype(out_dtype)


def dequantize(qw: QuantizedLinear, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (qw.w.astype(jnp.float32) * qw.scale).astype(dtype)


@jax.named_scope("quantize_kv")
def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-vector symmetric int8 over the LAST axis (the head_dim of a
    K/V tensor): x [..., hd] -> (int8 [..., hd], f32 scale [...]).

    This is the KV-cache quantizer: decode attention streams the whole
    valid cache every step, so int8 storage halves that HBM traffic. One
    scale per (position, head) vector keeps the dequant a cheap rank-1
    broadcast that XLA fuses into the attention einsum — scores and
    weighted sums apply the scale AFTER the contraction (it is constant
    over the contracted head_dim axis), so the MXU sees int8 data upcast
    in-register, never a materialized bf16 copy of the cache.
    """
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-10)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@jax.named_scope("dequantize_kv")
def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of quantize_kv (test oracle / slow path)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def maybe_quantize_tree(params, quantize: bool, *, min_size: int = 1 << 16):
    """Quantize projection-weight leaves: plain [in, out] 2-D mats and
    stacked [L, in, out] 3-D layer mats (reduce over the ``in`` axis either
    way, so a ``lax.scan`` slice yields a valid per-layer QuantizedLinear).
    Embedding tables and norms stay bf16 (quantizing embeddings hurts;
    norms are tiny).

    Works on the nested-dict param pytrees produced by gofr_tpu.models.
    """
    if not quantize:
        return params

    def is_proj_weight(k: str, v) -> bool:
        # Projection weights only: stacked [L, in, out] or plain [in, out]
        # mats whose key marks them as weights, plus 4-D [L, E, in, out]
        # MoE expert stacks (the contraction axis is ndim-2 in every
        # case, so one quantize call covers all ranks). Biases ([L, F] —
        # also 2-D!), norms and embeddings must stay dense: a stacked
        # bias quantized as a 2-D weight would break the lax.scan
        # leading-axis contract.
        if not isinstance(v, jnp.ndarray) or v.size < min_size:
            return False
        named_weight = k.startswith("w") or k in ("lm_head", "head",
                                                  "patch_proj", "pooler_w")
        return named_weight and v.ndim in (2, 3, 4)

    def visit(d):
        if isinstance(d, dict):
            out = {}
            for k, v in d.items():
                if is_proj_weight(k, v):
                    out[k] = quantize_int8(v, axis=v.ndim - 2)
                else:
                    out[k] = visit(v)
            return out
        return d

    return visit(params)
