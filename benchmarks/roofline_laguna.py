"""Bytes and operations a decode step of the window family
(``references/laguna.py``) cannot avoid, from shapes. ``m`` is
``ctx.model``: every field of the engine's ``ModelConfig``, as a dict.

A decode step reads each weight outside the experts once and, of the
experts, those that got a token (the program's own count); it reads the
live K and V rows of the full layers and, of each window layer's ring,
min(length, window) rows a slot (the program's count: each cursor cut to
the window), all bfloat16, the published type. Int8 weights carry one
float32 scale an output channel; what stays bfloat16 in the program (the
routers, the heads' gates) is counted at 2 bytes. The embedding's rows a
step gathers (one a slot) are left out.
"""

from __future__ import annotations

# the expert layer is the latent family's, and so is its arithmetic
from benchmarks.roofline_deepseek_v3 import (  # noqa: F401
    _mat, expert_bytes, expert_flops_per_assignment, least_seconds)

KV_DTYPE_BYTES = 2     # rows and rings in the published type


def kinds(m: dict) -> dict[str, int]:
    """Layers of each kind in the stack."""
    pat = list(m["layer_pattern"])
    periods = m["n_layers"] // len(pat)
    return {k: periods * pat.count(k) for k in ("full", "window")}


def heads(m: dict, kind: str) -> int:
    return (m["window_heads"] or m["n_heads"]) if kind == "window" \
        else m["n_heads"]


def head_dim(m: dict) -> int:
    return m["attn_head_dim"] or m["dim"] // m["n_heads"]


def row_bytes(m: dict) -> int:
    """K and V of one cached position in ONE layer."""
    return 2 * m["n_kv_heads"] * head_dim(m) * KV_DTYPE_BYTES


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one cached token over the full layers."""
    return kinds(m)["full"] * row_bytes(m)


def ring_bytes_per_row(m: dict) -> int:
    """One ring row over the window layers."""
    return kinds(m)["window"] * row_bytes(m)


def ring_bytes_per_slot(m: dict) -> int:
    return m["window_size"] * ring_bytes_per_row(m)


def attention_weight_bytes(m: dict, kind: str) -> int:
    """One layer's attention projections and its heads' gate."""
    d, h, kv, hd = m["dim"], heads(m, kind), m["n_kv_heads"], head_dim(m)
    return _mat(d, h * hd) + 2 * _mat(d, kv * hd) + _mat(h * hd, d) \
        + (2 * d * h if m["head_gate"] else 0)


def attn_flops_per_row(m: dict, kind: str) -> int:
    """Operations one live row costs one slot's step in one layer: every
    query head's score over the key and its weighted sum of the value, a
    multiply and an add each."""
    return 2 * heads(m, kind) * 2 * head_dim(m)


def fixed_weight_bytes(m: dict) -> int:
    """Weights every step reads whatever the routing: attention of every
    layer, the dense layers' feed-forward, each routed layer's shared
    expert and router, the output head."""
    d, nd = m["dim"], m["n_dense_layers"]
    ns = m["n_layers"] - nd
    fs = m["moe_ffn_dim"] * m["n_shared_experts"]
    n = kinds(m)
    dense = 2 * _mat(d, m["ffn_dim"]) + _mat(m["ffn_dim"], d)
    shared = 2 * _mat(d, fs) + _mat(fs, d)
    router = d * m["n_experts"] * 2 + m["n_experts"] * 4
    head = 0 if m.get("tie_embeddings") else _mat(d, m["vocab_size"])
    return (sum(n[k] * attention_weight_bytes(m, k) for k in n)
            + nd * dense + ns * (shared + router) + head)


def share_weight_bytes(m: dict) -> int:
    """All the weights the chip holds: ``fixed_weight_bytes``, every
    expert, the embedding (bfloat16)."""
    ns = m["n_layers"] - m["n_dense_layers"]
    held = m["n_experts_held"] or m["n_experts"]
    return (fixed_weight_bytes(m) + ns * held * expert_bytes(m)
            + m["vocab_size"] * m["dim"] * 2)


def step_bytes(m: dict, touched: float, full_rows: float,
               ring_rows: float) -> float:
    """A decode step: ``touched`` (layer, expert) cells that got a token,
    ``full_rows`` live positions (one layer's count), ``ring_rows`` rows
    of one window layer's rings."""
    return (fixed_weight_bytes(m) + touched * expert_bytes(m)
            + full_rows * kv_bytes_per_token(m)
            + ring_rows * ring_bytes_per_row(m))
