"""Bytes a decode step must stream, from shapes, and the table of peaks.

Decode is bound by memory bandwidth: one step reads every weight of every
layer once (whatever the batch) and the live part of the KV cache. The
least time a chip could take is those bytes over its peak bandwidth; a
step's share of its roofline is that time over the measured step.

The embedding table is not counted: a step gathers one row per sequence.
On a mesh the bytes are each chip's share (weights and KV heads are split
over tp; every chip streams its share at its own bandwidth).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json (has {sorted(table)})")
    return table[device_kind]


def weight_bytes_per_step(m: dict, weight_bytes: int = 1) -> int:
    """Bytes of projection weights one decode step reads. ``m`` holds the
    ModelConfig sizes: dim, n_layers, n_heads, n_kv_heads, ffn_dim,
    vocab_size, n_experts, tie_embeddings. Int8 leaves carry one float32
    scale per output channel. A mixture-of-experts layer under dense
    dispatch (the program's decode path) reads every expert."""
    d, f, v = m["dim"], m["ffn_dim"], m["vocab_size"]
    hd = d // m["n_heads"]
    q_out, kv_out = m["n_heads"] * hd, m["n_kv_heads"] * hd
    experts = max(1, m.get("n_experts", 0))
    # (in, out) of every projection of one layer
    mats = [(d, q_out), (d, kv_out), (d, kv_out), (q_out, d)]
    mats += [(d, f), (d, f), (f, d)] * experts
    per_layer = sum(i * o * weight_bytes + (o * 4 if weight_bytes == 1 else 0)
                    for i, o in mats)
    if m.get("n_experts", 0):
        per_layer += d * m["n_experts"] * 2  # the router, bf16
    head = 0 if m.get("tie_embeddings") else \
        d * v * weight_bytes + (v * 4 if weight_bytes == 1 else 0)
    return m["n_layers"] * per_layer + head


def kv_bytes_per_token(m: dict, kv_bytes: int = 1) -> int:
    """Bytes of K and V one cached token holds over all layers; an int8
    cache adds one float32 scale per (layer, K or V, KV head)."""
    hd = m["dim"] // m["n_heads"]
    per = 2 * m["n_layers"] * m["n_kv_heads"]
    return per * hd * kv_bytes + (per * 4 if kv_bytes == 1 else 0)


def decode_step_bytes(m: dict, live_tokens: float, weight_bytes: int = 1,
                      kv_bytes: int = 1) -> float:
    """Weights once plus the live KV: what one step cannot avoid reading.
    ``live_tokens`` is the cached tokens summed over the batch."""
    return weight_bytes_per_step(m, weight_bytes) \
        + live_tokens * kv_bytes_per_token(m, kv_bytes)


def decode_step_roofline_pct(m: dict, live_tokens: float, step_s: float,
                             peaks: dict, chips: int = 1) -> float:
    least_s = decode_step_bytes(m, live_tokens) / chips \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
