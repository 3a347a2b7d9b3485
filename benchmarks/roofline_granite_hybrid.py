"""Bytes a decode step of the state-space family's layer of two halves
(``references/granite_hybrid.py``) cannot avoid, from shapes. ``m`` is
``ctx.model``: every field of the engine's ``ModelConfig``, as a dict.

What holds of ``roofline_nemotron_h`` is imported: the state's and the
rows' bytes, the two kernels' bytes and operations (they take groups,
heads, state and chunk from ``m``), and ``fixed_weight_bytes``, which at
no moe layer is the mixers (36 mamba, 4 attn) and the norms. It counts a
tied head as 0 and knows no feed-forward of every layer; both are added
here: a step reads ``W_in`` [dim, 2 ffn_dim] and ``W_out`` [ffn_dim,
dim] of all 40 layers (int8, a float32 scale an output channel, the
second norm bfloat16: 2.0 GB) and the whole tied table [vocab, dim] in
bfloat16 for the logits (411 MB). A decode step also reads and writes
the convolution's tail of every active (layer, slot) state
(``conv_kernel - 1`` inputs of ``conv_channels``, bfloat16).
"""

from __future__ import annotations

from benchmarks.roofline_nemotron_h import (  # noqa: F401
    _mat, conv_channels, decode_kernel_bytes, fixed_weight_bytes, kinds,
    kv_bytes_per_token, state_bytes_per_slot)

TABLE_DTYPE_BYTES = 2   # the tied embedding is bfloat16
TAIL_DTYPE_BYTES = 2    # the convolution's tail, the model's type


def ffn_bytes(m: dict) -> int:
    """The gated feed-forwards of every layer, with their norms."""
    d, f = m["dim"], m["ffn_dim"]
    return m["n_layers"] * (_mat(d, 2 * f) + _mat(f, d) + 2 * d)


def head_bytes(m: dict) -> int:
    """The tied table, read whole for the logits."""
    return m["vocab_size"] * m["dim"] * TABLE_DTYPE_BYTES


def mixer_bytes(m: dict) -> int:
    """The 36 mamba and 4 attn mixers and the norms before them."""
    return fixed_weight_bytes(m)


def weight_bytes_per_step(m: dict) -> int:
    return mixer_bytes(m) + ffn_bytes(m) + head_bytes(m)


def tail_bytes(m: dict, states: float) -> float:
    """The tails of ``states`` (layer, slot) states, read and written."""
    return states * 2 * (m["conv_kernel"] - 1) * conv_channels(m) \
        * TAIL_DTYPE_BYTES


def step_bytes(m: dict, states: float, rows: float) -> float:
    """A decode step: every weight once, ``states`` (layer, slot) states
    and tails read and written, ``rows`` live cached tokens' K and V."""
    return (weight_bytes_per_step(m) + decode_kernel_bytes(m, states)
            + tail_bytes(m, states) + rows * kv_bytes_per_token(m))
