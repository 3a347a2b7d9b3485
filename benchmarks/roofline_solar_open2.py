"""Bytes and operations a step of the hybrid family
(``references/solar_open2.py``) cannot avoid, from shapes. ``m`` is
``ctx.model``: every field of the engine's ``ModelConfig``, as a dict.

A decode step reads and writes each ACTIVE slot's recurrent state once a
linear layer (float32, ``linear_heads x d x d``: 4.19 MB at 64 x 128 x
128), whatever the slot's length; it reads the live K and V rows of the
full layers (bfloat16, the published type); it reads the weights outside
the experts once and, of the held experts, those that got a token (the
program's own count). Int8 weights carry one float32 scale an output
channel; what stays bfloat16 in the program (router, the low-rank pairs,
the convolution's taps, W_beta) is counted at 2 bytes.
"""

from __future__ import annotations

# the expert layer is the latent family's, and so is its arithmetic
from benchmarks.roofline_deepseek_v3 import (  # noqa: F401
    _mat, expert_bytes, expert_flops_per_assignment, least_seconds)

KV_DTYPE_BYTES = 2     # the full layers' rows in the published type
STATE_DTYPE_BYTES = 4  # the state is float32 (the configuration's assumed)


def kinds(m: dict) -> tuple[int, int]:
    """(full layers, linear layers) of the stack."""
    pat = list(m["layer_pattern"])
    periods = m["n_layers"] // len(pat)
    return periods * pat.count("full"), periods * pat.count("linear")


def head_dim(m: dict) -> int:
    return m["attn_head_dim"] or m["dim"] // m["n_heads"]


def state_bytes(m: dict) -> int:
    """One slot's state in ONE linear layer."""
    d = m["linear_head_dim"]
    return m["linear_heads"] * d * d * STATE_DTYPE_BYTES


def state_bytes_per_slot(m: dict) -> int:
    return kinds(m)[1] * state_bytes(m)


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one cached token over the full layers."""
    return kinds(m)[0] * 2 * m["n_kv_heads"] * head_dim(m) * KV_DTYPE_BYTES


def fixed_weight_bytes(m: dict) -> int:
    """Weights every step reads whatever the routing: both kinds' mixers,
    every layer's shared expert and router, the output head."""
    d, h, kv, hd = m["dim"], m["n_heads"], m["n_kv_heads"], head_dim(m)
    hl, dl, r, w = (m["linear_heads"], m["linear_head_dim"], m["gate_rank"],
                    m["conv_kernel"])
    nf, nl = kinds(m)
    full = _mat(d, h * hd) + 2 * _mat(d, kv * hd) + _mat(h * hd, d) \
        + (_mat(d, h * hd) if m["attn_gate"] else 0)
    linear = 3 * _mat(d, hl * dl) + _mat(hl * dl, d) \
        + 2 * 2 * (d * r + r * hl * dl) + 2 * d * hl \
        + 2 * w * 3 * hl * dl + 4 * hl * dl + 4 * hl
    fs = m["moe_ffn_dim"] * m["n_shared_experts"]
    ffn = 2 * _mat(d, fs) + _mat(fs, d) + d * m["n_experts"] * 2 \
        + m["n_experts"] * 4
    head = 0 if m.get("tie_embeddings") else _mat(d, m["vocab_size"])
    return nf * full + nl * linear + (nf + nl) * ffn + head


def share_weight_bytes(m: dict) -> int:
    """All the weights the chip holds: ``fixed_weight_bytes``, every held
    expert, the embedding slice (bfloat16)."""
    held = m["n_experts_held"] or m["n_experts"]
    return (fixed_weight_bytes(m) + m["n_layers"] * held * expert_bytes(m)
            + m["vocab_size"] * m["dim"] * 2)


def decode_kernel_bytes(m: dict, states: float) -> float:
    """What ``kda_decode`` must move for ``states`` (layer, slot) states:
    each read once and written once."""
    return states * 2 * state_bytes(m)


def prefill_kernel_bytes_per_token(m: dict) -> int:
    """What ``kda_prefill`` reads and writes a token a linear layer: q, k,
    beta k, alpha and v in, o out, float32 a head (the state moves once a
    chunk and is not counted a token)."""
    return 6 * m["linear_heads"] * m["linear_head_dim"] * 4


def prefill_kernel_flops_per_token(m: dict) -> int:
    """The recurrence's operations a token a linear layer: the decay
    (d x d multiplies), S^T k and S^T q (a multiply and an add each), the
    rank-one update (a multiply and an add) a head."""
    d = m["linear_head_dim"]
    return 7 * m["linear_heads"] * d * d
