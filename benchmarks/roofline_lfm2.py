"""Bytes and operations a decode step of the conv family
(``references/lfm2.py``) cannot avoid, from shapes, whatever implements
them. ``m`` is ``ctx.model``: every field of the engine's ``ModelConfig``,
as a dict.

A decode step reads each weight outside the experts once and, of the
experts, those that got a token (the program's own count); it reads the
live K and V rows of the full layers (the program's count) and, for
every slot that decodes, the tails of the conv layers, which it also
writes back, all bfloat16, the published type. Int8 weights carry one
float32 scale an output channel; what stays bfloat16 in the program (the
routers, the taps, the norms) is counted at 2 bytes. The head is tied:
the step reads the whole embedding as its output projection. The
embedding's rows a step gathers (one a slot) are left out. Counted as
the values are: two KV heads of 64 values share a 128-lane row in the
program's cache, which pads nothing.
"""

from __future__ import annotations

# the expert layer is the latent family's, and so is its arithmetic
from benchmarks.roofline_deepseek_v3 import (  # noqa: F401
    _mat, expert_bytes, expert_flops_per_assignment, least_seconds)

KV_DTYPE_BYTES = 2     # rows and tails in the published type
KINDS = ("conv", "full")


def kinds(m: dict) -> dict[str, int]:
    """Layers of each kind in the stack."""
    pat = list(m["layer_pattern"])
    periods = m["n_layers"] // len(pat)
    return {k: periods * pat.count(k) for k in KINDS}


def head_dim(m: dict) -> int:
    return m["attn_head_dim"] or m["dim"] // m["n_heads"]


def row_bytes(m: dict) -> int:
    """K and V of one cached position in ONE full layer."""
    return 2 * m["n_kv_heads"] * head_dim(m) * KV_DTYPE_BYTES


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one cached token over the full layers."""
    return kinds(m)["full"] * row_bytes(m)


def tail_bytes_per_slot(m: dict) -> int:
    """The conv layers' tails of one slot, whatever its length."""
    return kinds(m)["conv"] * (m["conv_kernel"] - 1) * m["dim"] \
        * KV_DTYPE_BYTES


def conv_weight_bytes(m: dict) -> int:
    """One conv layer's operator: W_in, W_out and the taps."""
    d = m["dim"]
    return _mat(d, 3 * d) + _mat(d, d) + m["conv_kernel"] * d * 2


def attention_weight_bytes(m: dict) -> int:
    """One full layer's projections and its q/k norms."""
    d, h, kv, hd = m["dim"], m["n_heads"], m["n_kv_heads"], head_dim(m)
    return _mat(d, h * hd) + 2 * _mat(d, kv * hd) + _mat(h * hd, d) \
        + (2 * hd * 2 if m["qk_norm"] else 0)


def attn_flops_per_row(m: dict) -> int:
    """Operations one live row costs one slot's step in one layer: every
    query head's score over the key and its weighted sum of the value, a
    multiply and an add each."""
    return 2 * m["n_heads"] * 2 * head_dim(m)


def head_bytes(m: dict) -> int:
    """The output projection: the embedding itself where it is tied."""
    d, v = m["dim"], m["vocab_size"]
    return v * d * 2 if m.get("tie_embeddings") else _mat(d, v)


def fixed_weight_bytes(m: dict) -> int:
    """Weights every step reads whatever the routing: every layer's
    operator, the dense layers' feed-forward, each routed layer's router
    (and shared expert, where there is one), the output head."""
    d, nd = m["dim"], m["n_dense_layers"]
    ns = m["n_layers"] - nd
    fs = m["moe_ffn_dim"] * m["n_shared_experts"]
    n = kinds(m)
    dense = 2 * _mat(d, m["ffn_dim"]) + _mat(m["ffn_dim"], d)
    shared = 2 * _mat(d, fs) + _mat(fs, d) if fs else 0
    router = d * m["n_experts"] * 2 + m["n_experts"] * 4
    return (n["conv"] * conv_weight_bytes(m)
            + n["full"] * attention_weight_bytes(m)
            + nd * dense + ns * (shared + router) + head_bytes(m))


def share_weight_bytes(m: dict) -> int:
    """All the weights the chip holds: ``fixed_weight_bytes``, every
    expert, and the embedding (bfloat16) where the head is not it."""
    ns = m["n_layers"] - m["n_dense_layers"]
    held = m["n_experts_held"] or m["n_experts"]
    embedding = 0 if m.get("tie_embeddings") \
        else m["vocab_size"] * m["dim"] * 2
    return fixed_weight_bytes(m) + ns * held * expert_bytes(m) + embedding


def step_bytes(m: dict, touched: float, rows: float, slots: float) -> float:
    """A decode step: ``touched`` (layer, expert) cells that got a token,
    ``rows`` live positions (one layer's count), ``slots`` slots that
    decode (each tail read and written)."""
    return (fixed_weight_bytes(m) + touched * expert_bytes(m)
            + rows * kv_bytes_per_token(m)
            + slots * 2 * tail_bytes_per_slot(m))
