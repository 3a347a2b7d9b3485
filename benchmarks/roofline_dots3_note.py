"""Bytes and operations a decode step of the sparse-latent family
(``references/dots3_note.py``) cannot avoid, from shapes. ``m`` is
``ctx.model``: every field of the engine's ``ModelConfig``, as a dict.

A decode step reads each weight outside the experts once and, of the
experts, those that got a token (the program's own count). Of the cache
it reads, a full layer: one index key (``index_head_dim`` values) for
every live row, to score it, and the latent row of every row the
selection kept; a window layer: min(length, ring) rows of its ring a
slot. All bfloat16, the published type.

Rows are counted as HBM stores them, in whole 128-lane tiles (1,280 B
for 576 values, 2,304 B for 1,088): that is what a kernel must fetch,
since nothing on this chip slices a tile (``PERF.md`` section 7, 13,
says what counting the values alone did to GigaChat's reader). A kernel
that fetches whole blocks of rows, or rows the selection left out,
fetches more than is counted here and reads under 100 for it. Int8
weights carry one float32 scale an output channel; what stays bfloat16
in the program (the routers, the gates, the indexer's weights
projection) is counted at 2 bytes. The embedding's rows a step gathers
are left out.
"""

from __future__ import annotations

# the expert layer is the latent family's, and so is its arithmetic
from benchmarks.roofline_deepseek_v3 import (  # noqa: F401
    _mat, expert_bytes, expert_flops_per_assignment, least_seconds)

LANES = 128
ROW_DTYPE_BYTES = 2    # rows, keys and rings in the published type
KINDS = ("full", "window")


def kinds(m: dict) -> dict[str, int]:
    """Layers of each kind in the stack."""
    pat = list(m["layer_pattern"])
    periods = m["n_layers"] // len(pat)
    return {k: periods * pat.count(k) for k in KINDS}


def sizes(m: dict, kind: str) -> dict[str, int]:
    """One kind's latent attention; a window size left 0 is the full
    layers'."""
    full = {"heads": m["n_heads"], "q_rank": m["q_lora_rank"],
            "rank": m["kv_lora_rank"], "nope": m["qk_nope_head_dim"],
            "rope": m["qk_rope_head_dim"], "value": m["v_head_dim"]}
    if kind == "full":
        return full
    own = {"heads": m["window_heads"], "q_rank": m["window_q_lora_rank"],
           "rank": m["window_kv_lora_rank"],
           "nope": m["window_qk_nope_head_dim"],
           "rope": m["window_qk_rope_head_dim"],
           "value": m["window_v_head_dim"]}
    return {k: own[k] or full[k] for k in full}


def row_values(m: dict, kind: str) -> int:
    s = sizes(m, kind)
    return s["rank"] + s["rope"]


def row_bytes(m: dict, kind: str) -> int:
    """One cached row of ONE layer of ``kind`` as HBM stores it."""
    return -(-row_values(m, kind) // LANES) * LANES * ROW_DTYPE_BYTES


def key_bytes(m: dict) -> int:
    """One cached index key of ONE full layer."""
    return m["index_head_dim"] * ROW_DTYPE_BYTES


def ring_rows(m: dict) -> int:
    return m["window_size"] - 1


def latent_bytes_per_token(m: dict) -> int:
    return kinds(m)["full"] * row_bytes(m, "full")


def index_bytes_per_token(m: dict) -> int:
    return kinds(m)["full"] * key_bytes(m)


def ring_bytes_per_row(m: dict) -> int:
    """One ring row over the window layers."""
    return kinds(m)["window"] * row_bytes(m, "window")


def slot_bytes(m: dict) -> int:
    """What one slot reserves: rows and index keys to ``max_seq``, and
    its rings."""
    return m["max_seq"] * (latent_bytes_per_token(m)
                           + index_bytes_per_token(m)) \
        + ring_rows(m) * ring_bytes_per_row(m)


def attn_flops_per_row(m: dict, kind: str) -> int:
    """Operations one row costs one slot's step in one layer: every
    head's score over the row's values and its weighted sum of the
    latent, a multiply and an add each."""
    s = sizes(m, kind)
    return 2 * s["heads"] * (row_values(m, kind) + s["rank"])


def index_flops_per_row(m: dict) -> int:
    """Scoring one cached key: every index head's dot product."""
    return 2 * m["index_heads"] * m["index_head_dim"]


def attention_weight_bytes(m: dict, kind: str) -> int:
    """One layer's attention projections, its heads' gate and, on a full
    layer, the indexer's three."""
    d, s = m["dim"], sizes(m, kind)
    h, rq, r = s["heads"], s["q_rank"], s["rank"]
    w = (_mat(d, rq) + _mat(rq, h * (s["nope"] + s["rope"]))
         + _mat(d, r + s["rope"]) + _mat(r, h * (s["nope"] + s["value"]))
         + _mat(h * s["value"], d)
         + (2 * d * h if m["head_gate"] else 0))
    if kind == "full" and m["index_topk"]:
        hi, di = m["index_heads"], m["index_head_dim"]
        w += _mat(rq, hi * di) + _mat(d, di) + 2 * d * hi
    return w


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
    held expert, the embedding slice (bfloat16)."""
    ns = m["n_layers"] - m["n_dense_layers"]
    held = m["n_experts_held"] or m["n_experts"]
    return (fixed_weight_bytes(m) + ns * held * expert_bytes(m)
            + m["vocab_size"] * m["dim"] * 2)


def step_bytes(m: dict, touched: float, live_rows: float, kept_rows: float,
               ring_rows_live: float) -> float:
    """A decode step: ``touched`` (layer, expert) cells that got a token,
    ``live_rows`` live positions and ``kept_rows`` rows kept (one full
    layer's counts), ``ring_rows_live`` rows of one window layer's
    rings."""
    return (fixed_weight_bytes(m) + touched * expert_bytes(m)
            + live_rows * index_bytes_per_token(m)
            + kept_rows * latent_bytes_per_token(m)
            + ring_rows_live * ring_bytes_per_row(m))
