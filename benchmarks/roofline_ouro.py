"""Bytes a decode step of the looped family (``references/ouro.py``)
cannot avoid, from shapes, whatever implements them. ``m`` is
``ctx.model``: every field of the engine's ``ModelConfig``, as a dict.

A decode step runs the stack ``loop_steps`` times, and the weights of a
2.5 GB stack do not stay on the chip between passes: every pass reads
every layer's seven projections again, int8 with one float32 scale an
output channel. The head is read once, after the last pass. Each (pass,
layer) has a row table of its own, so a cached position is ``loop_steps
x n_layers`` rows of K and of V, int8 with a float32 scale a KV head;
the decode kernel fetches whole blocks of 256 positions, so its bytes
are counted over the positions it FETCHES (each cursor rounded up to a
block: the program's own count), not over the live ones. The step's
write reads and writes, for every slot that decodes, the tiles around
its cursor in every table: 32 positions of a KV head's rows (4,096 B)
for K and for V, and the 128-lane tile (512 B) of each scale table. The
embedding's rows a step gathers (one a slot), the norms' weights and the
exit gate (not evaluated) are left out.
"""

from __future__ import annotations

KV_BYTES = 1            # int8 rows
SCALE_BYTES = 4         # one float32 a (position, KV head), K and V each
APPEND_ROWS = 32        # positions of a KV head's tile the write visits
APPEND_LANES = 128      # lanes of a scale table's tile it visits


def _mat(i: int, o: int) -> int:
    """An int8 projection [i, o] with its float32 scale an output
    channel."""
    return i * o + o * 4


def tables(m: dict) -> int:
    """Row tables a cached token has: one a (pass, layer)."""
    return m["loop_steps"] * m["n_layers"]


def head_dim(m: dict) -> int:
    return m["attn_head_dim"] or m["dim"] // m["n_heads"]


def layer_weight_bytes(m: dict) -> int:
    """One layer's seven projections."""
    d, f, hd = m["dim"], m["ffn_dim"], head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return _mat(d, q) + 2 * _mat(d, kv) + _mat(q, d) + 2 * _mat(d, f) \
        + _mat(f, d)


def stack_bytes(m: dict) -> int:
    """The projections of all the layers, once."""
    return m["n_layers"] * layer_weight_bytes(m)


def head_bytes(m: dict) -> int:
    d, v = m["dim"], m["vocab_size"]
    return v * d * 2 if m.get("tie_embeddings") else _mat(d, v)


def loop_weight_bytes(m: dict) -> int:
    """What the loop costs a step in weights: the stack a pass."""
    return m["loop_steps"] * stack_bytes(m)


def weight_bytes_per_step(m: dict) -> int:
    return loop_weight_bytes(m) + head_bytes(m)


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one cached position over all its tables, with their
    scales: 811,008 at 192 tables of 16 KV heads of 128."""
    return tables(m) * 2 * m["n_kv_heads"] * (head_dim(m) * KV_BYTES
                                              + SCALE_BYTES)


def append_bytes(m: dict, slots: float) -> float:
    """The step's write for ``slots`` slots that decode: the rows' tiles
    and the scales' tiles around each cursor, every table and KV head, K
    and V, read and written."""
    a_head = 2 * (APPEND_ROWS * head_dim(m) * KV_BYTES
                  + APPEND_LANES * SCALE_BYTES)
    return 2 * slots * tables(m) * m["n_kv_heads"] * a_head


def step_bytes(m: dict, fetched: float, slots: float) -> float:
    """A decode step: the stack ``loop_steps`` times and the head,
    ``fetched`` cached positions (summed over the slots, whole blocks)
    in every table, the write's tiles for ``slots`` slots."""
    return weight_bytes_per_step(m) + fetched * kv_bytes_per_token(m) \
        + append_bytes(m, slots)
