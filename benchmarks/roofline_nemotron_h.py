"""Bytes and operations a step of the state-space family
(``references/nemotron_h.py``) cannot avoid, from shapes. ``m`` is
``ctx.model``: every field of the engine's ``ModelConfig``, as a dict.

A decode step reads and writes each ACTIVE slot's state once a mamba
layer (float32, ``ssm_heads x ssm_head_dim x ssm_state``: 4.19 MB at
128 x 64 x 128), whatever the slot's length; it reads the live K and V
rows of the attn layers (bfloat16, the published type); it reads the
weights outside the routed experts once and, of the held experts, those
that got a token (the program's own count). A routed expert is TWO
matrices in the latent (``moe_latent_dim x moe_ffn_dim`` and back). Int8
weights carry one float32 scale an output channel; what stays bfloat16
in the program (router, taps, norms) is counted at 2 bytes, the float32
vectors (biases, ``A_log``, ``D``) at 4.
"""

from __future__ import annotations

from benchmarks.roofline_deepseek_v3 import _mat, least_seconds  # noqa: F401

KV_DTYPE_BYTES = 2     # the attn layers' rows in the published type
STATE_DTYPE_BYTES = 4  # the state is float32 (the configuration's assumed)
KINDS = ("mamba", "moe", "attn")


def kinds(m: dict) -> tuple[int, int, int]:
    """(mamba layers, moe layers, attn layers) of the stack."""
    pat = list(m["layer_pattern"])
    return tuple(pat.count(k) for k in KINDS)


def head_dim(m: dict) -> int:
    return m["attn_head_dim"] or m["dim"] // m["n_heads"]


def inner(m: dict) -> int:
    """The mamba layer's inner width, heads x head width."""
    return m["ssm_heads"] * m["ssm_head_dim"]


def conv_channels(m: dict) -> int:
    return inner(m) + 2 * m["ssm_groups"] * m["ssm_state"]


def state_bytes(m: dict) -> int:
    """One slot's state in ONE mamba layer."""
    return inner(m) * m["ssm_state"] * STATE_DTYPE_BYTES


def state_bytes_per_slot(m: dict) -> int:
    return kinds(m)[0] * state_bytes(m)


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one cached token over the attn layers."""
    return kinds(m)[2] * 2 * m["n_kv_heads"] * head_dim(m) * KV_DTYPE_BYTES


def expert_width(m: dict) -> int:
    return m["moe_latent_dim"] or m["dim"]


def expert_bytes(m: dict) -> int:
    """One routed expert: W1 and W2, in the latent."""
    d, f = expert_width(m), m["moe_ffn_dim"]
    return _mat(d, f) + _mat(f, d)


def expert_flops_per_assignment(m: dict) -> int:
    return 2 * 2 * expert_width(m) * m["moe_ffn_dim"]


def fixed_weight_bytes(m: dict) -> int:
    """Weights every step reads whatever the routing: the mamba and attn
    layers, every moe layer's router, latent pair and shared expert, the
    norms, the output head."""
    d, h, kv, hd = m["dim"], m["n_heads"], m["n_kv_heads"], head_dim(m)
    hp, c, w = inner(m), conv_channels(m), m["conv_kernel"]
    lm, le, la = kinds(m)
    mamba = _mat(d, hp + c + m["ssm_heads"]) + _mat(hp, d) \
        + 2 * w * c + 4 * c + 3 * 4 * m["ssm_heads"] + 2 * hp
    fs = m["shared_ffn_dim"] or m["moe_ffn_dim"] * m["n_shared_experts"]
    dl = expert_width(m)
    moe = d * m["n_experts"] * 2 + m["n_experts"] * 4 \
        + _mat(d, dl) + _mat(dl, d) + _mat(d, fs) + _mat(fs, d)
    attn = _mat(d, h * hd) + 2 * _mat(d, kv * hd) + _mat(h * hd, d)
    head = 0 if m.get("tie_embeddings") else _mat(d, m["vocab_size"])
    return lm * mamba + le * moe + la * attn + (m["n_layers"] + 1) * 2 * d \
        + head


def share_weight_bytes(m: dict) -> int:
    """All the weights the chip holds: ``fixed_weight_bytes``, every held
    expert, the embedding slice (bfloat16)."""
    held = m["n_experts_held"] or m["n_experts"]
    return (fixed_weight_bytes(m) + kinds(m)[1] * held * expert_bytes(m)
            + m["vocab_size"] * m["dim"] * 2)


def decode_kernel_bytes(m: dict, states: float) -> float:
    """What ``ssd_decode`` must move for ``states`` (layer, slot) states:
    each read once and written once."""
    return states * 2 * state_bytes(m)


def prefill_kernel_bytes_per_token(m: dict) -> int:
    """What the chunk kernel cannot avoid a token a mamba layer: Delta x
    in and y out (the inner width), B and C a group, log a a head, all
    float32 (the state moves once a call and is not counted a token)."""
    return 4 * (2 * inner(m) + 2 * m["ssm_groups"] * m["ssm_state"]
                + m["ssm_heads"])


def prefill_kernel_flops_per_token(m: dict) -> int:
    """The chunk form's matrix operations a token a mamba layer, with Q
    the chunk: C B^T a group (2 Q N), the masked product with Delta X a
    head (2 Q P), C S and B^T (Delta X) over the inner width (2 N each)."""
    q, n = m["ssm_chunk"], m["ssm_state"]
    return 2 * q * (m["ssm_groups"] * n + inner(m)) + 4 * n * inner(m)
