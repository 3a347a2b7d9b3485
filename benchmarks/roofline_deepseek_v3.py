"""Bytes and operations a decode step of the latent-attention family
(``references/deepseek_v3.py``) cannot avoid, from shapes. ``m`` is
``ctx.model``: every field of the engine's ``ModelConfig``, as a dict.

Counted as the values are, not as the memory stores them: a latent row is
``kv_lora_rank + qk_rope_head_dim`` bfloat16 values a layer (1,152 B at
512 + 64); HBM holds it in whole 128-lane tiles (1,280 B), which the
roofline does not credit. Int8 weights carry one float32 scale an output
channel. Unlike Mistral's step, this one does not read every weight every
step: a routed layer reads only the experts that got a token, so the
bytes follow the program's own count of experts touched.
"""

from __future__ import annotations

ROW_DTYPE_BYTES = 2  # the latent row's published type, bfloat16


def _mat(i: int, o: int) -> int:
    """Bytes of one int8 [i, o] projection with its float32 scales."""
    return i * o + 4 * o


def row_bytes(m: dict) -> int:
    """Bytes of one cached token's row in ONE layer."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * ROW_DTYPE_BYTES


def kv_bytes_per_token(m: dict) -> int:
    return row_bytes(m) * m["n_layers"]


def attn_flops_per_row(m: dict) -> int:
    """Operations one live row costs one slot's step in one layer: every
    head's score over the row (rank + rope) and its weighted sum of the
    latent (rank), a multiply and an add each."""
    return 2 * m["n_heads"] * (2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])


def expert_bytes(m: dict) -> int:
    """One routed expert: gate, up, down."""
    d, f = m["dim"], m["moe_ffn_dim"]
    return 2 * _mat(d, f) + _mat(f, d)


def expert_flops_per_assignment(m: dict) -> int:
    return 2 * 3 * m["dim"] * m["moe_ffn_dim"]


def attention_weight_bytes(m: dict) -> int:
    """One layer's attention projections."""
    d, h = m["dim"], m["n_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    rq, r = m["q_lora_rank"], m["kv_lora_rank"]
    return (_mat(d, rq) + _mat(rq, h * (dn + dr)) + _mat(d, r + dr)
            + _mat(r, h * (dn + dv)) + _mat(h * dv, d))


def fixed_weight_bytes(m: dict) -> int:
    """Weights every step reads whatever the routing: attention of every
    layer, the dense layers' feed-forward, each routed layer's shared
    expert and router (bfloat16), the output head."""
    d, nd = m["dim"], m["n_dense_layers"]
    ns = m["n_layers"] - nd
    fs = m["moe_ffn_dim"] * m["n_shared_experts"]
    dense = 2 * _mat(d, m["ffn_dim"]) + _mat(m["ffn_dim"], d)
    shared = 2 * _mat(d, fs) + _mat(fs, d)
    router = d * m["n_experts"] * 2 + m["n_experts"] * 4
    head = 0 if m.get("tie_embeddings") else _mat(d, m["vocab_size"])
    return (m["n_layers"] * attention_weight_bytes(m) + nd * dense
            + ns * (shared + router) + head)


def share_weight_bytes(m: dict) -> int:
    """All the weights the chip holds: ``fixed_weight_bytes``, every held
    expert, the embedding slice (bfloat16)."""
    ns = m["n_layers"] - m["n_dense_layers"]
    held = m["n_experts_held"] or m["n_experts"]
    return (fixed_weight_bytes(m) + ns * held * expert_bytes(m)
            + m["vocab_size"] * m["dim"] * 2)


def least_seconds(bytes_: float, flops: float, peaks: dict) -> float:
    """The larger of the bandwidth's and the matrix unit's time."""
    return max(bytes_ / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
