"""Bytes AND operations a pass of the block-diffusion family
(``references/sdar.py``) cannot avoid, from shapes, whatever implements
them. ``m`` is ``ctx.model``: every field of the engine's ``ModelConfig``,
as a dict.

A pass runs the stack once over ``block_length`` positions of every
active slot. It reads each weight outside the experts once, the experts
that got a token (the program's own count; all of them at 24 tokens an
expert), the live K and V rows once (bfloat16, the published type), and
the head (int8, untied) only where a slot denoises: a slot that commits
reads no distribution. At four positions a slot through eight experts
each the operations are within a factor of a few of the bytes' time, so
a pass's floor is the LARGER of bytes over bandwidth and operations over
the matrix peak: a floor that forgot the operations would read a share
over 100% the day the weights shrink. Int8 weights carry one float32
scale an output channel; the routers and norms are bfloat16. The
embedding's rows a pass gathers and the rows a commit writes (four a
slot a layer) are left out.
"""

from __future__ import annotations

# the expert layer's arithmetic is the latent family's (three int8
# matrices an expert, six operations a weight an assignment)
from benchmarks.roofline_deepseek_v3 import (  # noqa: F401
    _mat, expert_bytes, expert_flops_per_assignment, least_seconds)

KV_DTYPE_BYTES = 2     # rows in the published type


def head_dim(m: dict) -> int:
    return m["attn_head_dim"] or m["dim"] // m["n_heads"]


def row_bytes(m: dict) -> int:
    """K and V of one cached position in ONE layer."""
    return 2 * m["n_kv_heads"] * head_dim(m) * KV_DTYPE_BYTES


def kv_bytes_per_token(m: dict) -> int:
    return m["n_layers"] * row_bytes(m)


def attention_weights(m: dict) -> int:
    """Weights of one layer's four projections."""
    d, h, kv, hd = m["dim"], m["n_heads"], m["n_kv_heads"], head_dim(m)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def attention_weight_bytes(m: dict) -> int:
    """One layer's projections (int8 with scales) and its q/k norms."""
    d, h, kv, hd = m["dim"], m["n_heads"], m["n_kv_heads"], head_dim(m)
    return _mat(d, h * hd) + 2 * _mat(d, kv * hd) + _mat(h * hd, d) \
        + 2 * hd * 2


def router_bytes(m: dict) -> int:
    return m["dim"] * m["n_experts"] * 2


def head_bytes(m: dict) -> int:
    return _mat(m["dim"], m["vocab_size"])


def attn_flops_per_row(m: dict) -> int:
    """Operations one live row costs ONE query position in one layer:
    every query head's score over the key and its weighted sum of the
    value, a multiply and an add each."""
    return 2 * m["n_heads"] * 2 * head_dim(m)


def fixed_weight_bytes(m: dict) -> int:
    """Weights every pass reads whatever the routing and whatever the
    slots do: every layer's attention, norms and router."""
    return m["n_layers"] * (attention_weight_bytes(m) + router_bytes(m)
                            + 2 * m["dim"] * 2)


def share_weight_bytes(m: dict) -> int:
    """All the weights the chip holds: the layers with every expert, the
    embedding (bfloat16) and the head."""
    return (fixed_weight_bytes(m)
            + m["n_layers"] * m["n_experts"] * expert_bytes(m)
            + m["vocab_size"] * m["dim"] * 2 + head_bytes(m))


def pass_bytes(m: dict, touched: float, rows: float, head: float) -> float:
    """One pass: ``touched`` (layer, expert) cells that got a token,
    ``rows`` live positions (one layer's count), ``head`` the share of
    the pass in which a slot denoises (1, or less over a dispatch whose
    slots all commit in some pass)."""
    return (fixed_weight_bytes(m) + touched * expert_bytes(m)
            + rows * kv_bytes_per_token(m) + head * head_bytes(m))


def pass_flops(m: dict, slots: float, assigned: float, rows: float,
               head_rows: float) -> float:
    """One pass: ``slots`` active slots' ``block_length`` positions
    through every layer's projections, ``assigned`` (token, expert)
    assignments over all layers, every position's attention over its
    slot's ``rows`` live positions (one layer's count, summed over the
    slots) and over its own block, ``head_rows`` rows through the
    head."""
    w = m["block_length"]
    tokens = slots * w
    return (2 * tokens * m["n_layers"] * attention_weights(m)
            + assigned * expert_flops_per_assignment(m)
            + (rows + tokens) * w * attn_flops_per_row(m) * m["n_layers"]
            + 2 * head_rows * m["dim"] * m["vocab_size"])


def pass_floor_s(m: dict, peaks: dict, *, slots: float, touched: float,
                 assigned: float, rows: float, head: float,
                 head_rows: float) -> float:
    """Seconds one pass cannot go under: the larger of its bytes over
    the bandwidth and its operations over the matrix peak."""
    return least_seconds(pass_bytes(m, touched, rows, head),
                         pass_flops(m, slots, assigned, rows, head_rows),
                         peaks)
