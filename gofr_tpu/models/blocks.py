"""What two decoder families share and no family owns: the embedding, a
whole prompt's rows and attention, a layer's weights taken out of a
stack a kind, the attention block of the window and conv families and
the stack that runs both. A family file imports this module, ``common``,
``llama`` (the row cache, the dense block, the logits), ``moe`` (the
routed feed-forward), ``hybrid_cache`` and ``ops/``: never a sibling
family (tests/test_models_layering.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import flash_decode
from ..ops.attention import (causal_attention, chunk_attention,
                             decode_attention_appended, pair_queries,
                             pair_rows, unpair_heads)
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from ..ops.rope import apply_rope_part
from .common import ModelConfig
from .moe import EXPERT_STACKS, dense_ffn, moe_ffn

F32 = jnp.float32


def embed(params, cfg: ModelConfig, tokens):
    with jax.named_scope("embed"):
        x = params["embedding"][tokens]
        if cfg.embedding_multiplier != 1.0:    # rounded once, after it
            x = x.astype(F32) * cfg.embedding_multiplier
        return x.astype(cfg.jdtype)


def prompt_rows(tokens, lengths):
    """(lengths [B], positions [B, S], valid [B, S]) of right-padded
    prompts [B, S]; ``lengths`` None: every row is whole."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return lengths, positions, positions < lengths[:, None]


def prompt_attend(flash: bool, lengths, valid, mesh, window: int = 0,
                  block: int = 0):
    """``attend(q, k, v)`` of a whole prompt within itself: the flash
    kernel where ``flash`` and backend and shapes allow (``ops.flash``),
    the jnp reference otherwise; banded where ``window``, block-causal
    where ``block``."""
    if flash:
        from ..ops.flash import causal_attention_auto

        return lambda q, k, v: causal_attention_auto(
            q, k, v, lengths=lengths, mask=valid, mesh=mesh, window=window,
            block=block)
    return lambda q, k, v: causal_attention(q, k, v, mask=valid,
                                            window=window, block=block)


# -- a layer's weights out of a stack a kind -----------------------------------

def at(tree, i):
    """Entry ``i`` of every [L, ...] array of ``tree``."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def experts_apart(stack) -> tuple[dict, dict]:
    """(the routed experts' stacks, every other leaf) of a routed stack."""
    whole = {k: v for k, v in stack.items() if k in EXPERT_STACKS}
    return whole, {k: v for k, v in stack.items() if k not in whole}


def layer_at(stack, i):
    """Layer ``i`` of a kind's stack. Every stack stays whole beside the
    layer loop and a layer's leaves are indexed where they are used:
    sliced by a scan over periods, a period's projections are copied out
    of the stack and then each layer's out of that copy, every step
    (PERF.md, Findings PR 32). The expert stacks are not indexed at all:
    they go on whole beside the index, ``lw["experts"]``, to
    ``moe.experts``, which reads expert (layer, e) in place; handed the
    layer's slice, the loop copies all the held experts out of the stack
    every layer, every step (18.7 of a 36.5 ms step: PERF.md, Findings
    PR 28)."""
    whole, rest = experts_apart(stack)
    return {**at(rest, i), "experts": (whole, i)} if whole else at(rest, i)


# -- the attention block of the window and conv families -----------------------

def attention(x, lw, cfg: ModelConfig, n_heads: int, rope, positions,
              attend):
    """x [B, S, D] -> (y [B, S, D], (k, v) [B, S, KV, hd] of these
    tokens): ``n_heads`` query heads on ``n_kv_heads`` KV heads, q and k
    RMS-normed a head where ``qk_norm``, rotated by ``rope`` (cos, sin)
    over the part of a head the tables cover, the heads' outputs gated
    one value a head where ``head_gate``. ``attend(q, k, v) ->
    [B, S, H, hd]``."""
    B, S = x.shape[:2]
    H, KV, hd = n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        q, k, v = (qmatmul(h, lw[n]) for n in ("wq", "wk", "wv"))
        # the projections read their weights as the stacks store them:
        # the heads-major layout the reshape and the rope want stays on
        # this side (llama.layer says what it costs without)
        q, k, v = jax.lax.optimization_barrier((q, k, v))
        q, k = q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd)
        if cfg.qk_norm:     # a head at a time, before the rotation
            with jax.named_scope("attn/qk_norm"):
                q = rms_norm(q, lw["q_head_norm"], cfg.norm_eps)
                k = rms_norm(k, lw["k_head_norm"], cfg.norm_eps)
        q = apply_rope_part(q, *rope, positions)
        k = apply_rope_part(k, *rope, positions)
        v = v.reshape(B, S, KV, hd)
    a = attend(q, k, v)
    with jax.named_scope("attn_out"):
        if cfg.head_gate:
            gate = jax.nn.sigmoid(qmatmul(h, lw["head_gate"]).astype(F32))
            a = (a.astype(F32) * gate[..., None]).astype(x.dtype)
        return qmatmul(a.reshape(B, S, H * hd), lw["wo"]), (k, v)


# -- heads of half a lane row: two KV heads a cache row ------------------------
# (ops.attention.pair_rows says why; the conv family's full layers and
# the state-space family's attention layers at a head of 64)

_LANES = 128


def paired(cfg: ModelConfig, whole_rows: bool = False) -> bool:
    """Whether a cache of K and V heads holds two of them a row: heads
    narrower than a lane row, an even count of them. ``whole_rows``: only
    where a pair fills the lane row exactly, the one width at which the
    decode kernels run on it (the families whose rows may be int8 ask so:
    a narrower head stays a row of its own and keeps its scale a row)."""
    if cfg.head_dim >= _LANES or cfg.n_kv_heads % 2:
        return False
    return 2 * cfg.head_dim == _LANES or not whole_rows


def row_layout(cfg: ModelConfig, pair: bool) -> tuple[int, int]:
    """(rows, values a row) of a cached token's K (and V), as stored."""
    if pair:
        return cfg.n_kv_heads // 2, 2 * cfg.head_dim
    return cfg.n_kv_heads, cfg.head_dim


def rows_attend(attend_rows, cfg: ModelConfig, pair: bool):
    """``attend(q, k, v)`` of a layer over cached rows, as an attention
    block calls it: ``attend_rows(q, k, v, scale)`` sees q, k and v as
    the cache holds rows (paired, or as they are) and the softmax scale
    of the true width."""
    scale = cfg.head_dim ** -0.5
    if not pair:
        return lambda q, k, v: attend_rows(q, k, v, scale)
    KV = cfg.n_kv_heads
    return lambda q, k, v: unpair_heads(
        attend_rows(pair_queries(q, KV), pair_rows(k), pair_rows(v), scale),
        KV)


def layer_rows(rows, i):
    """Layer ``i`` of each table of ``rows`` (k, v, k_scale, v_scale), a
    scale None where the rows are not int8."""
    return tuple(None if a is None else jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False) for a in rows)


def decode_rows_attend(rows, i, lengths, live, block_s, mesh,
                       cfg: ModelConfig, pair: bool):
    """``attend(q, k_new, v_new)`` of a decode step over layer ``i`` of
    the cached ``rows`` (k, v [L, B, rows, Smax, values], k_scale,
    v_scale or None): the kernel over the live blocks where they lie
    (``block_s``: ``flash_decode.kernel_block``'s answer; ``live`` [B],
    0 for a slot that is not read), or the reference on the layer's
    slice up to ``lengths``; on paired rows where ``pair``."""
    def over_rows(q, k_new, v_new, scale):
        if block_s:
            return flash_decode.decode_attention_auto(
                q, rows[0], rows[1], k_new, v_new, live, i, rows[2],
                rows[3], block_s=block_s, mesh=mesh, scale=scale)
        k_l, v_l, ks_l, vs_l = layer_rows(rows, i)
        return decode_attention_appended(q, k_l, v_l, k_new, v_new, lengths,
                                         ks_l, vs_l, scale=scale)
    return rows_attend(over_rows, cfg, pair)


def chunk_rows_attend(rows, i, start, cfg: ModelConfig, pair: bool,
                      block: int = 0):
    """``attend(q, k_new, v_new)`` of a chunk program over layer ``i`` of
    the cached ``rows``: the rows before ``start`` and the chunk within
    itself (block-causally where ``block``); on paired rows where
    ``pair``."""
    def over_rows(q, k_new, v_new, scale):
        k_l, v_l, ks_l, vs_l = layer_rows(rows, i)
        return chunk_attention(q, k_l, v_l, k_new, v_new, start, ks_l, vs_l,
                               scale=scale, block=block)
    return rows_attend(over_rows, cfg, pair)


def as_stored(kv, pair: bool):
    """The (k, v) a layer made, [.., KV, hd], as the cache rows."""
    return tuple(pair_rows(a) for a in kv) if pair else kv


# -- their stack: the dense periods one by one, the rest scanned ---------------

def period_stack(params, cfg: ModelConfig, x, layer):
    """Run the layers of a stack whose weights are stacked a kind of
    operator (``params[kind]``, the kinds ``cfg.layer_pattern``'s own)
    and a kind of feed-forward (``params["dense"]``, ``params["moe"]``):
    the periods that hold a dense layer one after another, the rest
    scanned a period at a time, so compile time stays flat in depth past
    them. ``layer(x, lw, kind, i) -> (x, rows, n)`` runs one, ``i`` its
    index among its kind, ``lw`` its weights (and ``lw["ffn"]`` its
    feed-forward). Returns (x, {kind: rows stacked [Lkind, ...]}, the
    routed layers' n stacked [Ls, ...])."""
    pat, nd = cfg.layer_pattern, cfg.n_dense_layers
    period, P = len(pat), cfg.n_layers // len(pat)
    per = {k: pat.count(k) for k in dict.fromkeys(pat)}
    unrolled = min(-(-nd // period), P)
    experts, routed = experts_apart(params["moe"])   # ``layer_at`` says why

    def stack(ys):
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)

    def run(x, p):
        """Period ``p``: a python int (a dense layer's feed-forward is
        chosen here) or the scan's index."""
        rows = {k: [] for k in per}
        ns = []
        for j, kind in enumerate(pat):
            l = p * period + j
            i = p * per[kind] + len(rows[kind])
            if isinstance(l, int) and l < nd:
                ffn = {**at(params["dense"], l), "ffn": dense_ffn}
            else:
                ffn = {**at(routed, l - nd), "experts": (experts, l - nd),
                       "ffn": moe_ffn}
            x, kv, n = layer(x, {**at(params[kind], i), **ffn}, kind, i)
            rows[kind].append(kv)
            if n is not None:
                ns.append(n)
        return x, ({k: stack(v) for k, v in rows.items()},
                   stack(ns) if ns else None)

    outs = []
    for p in range(unrolled):
        x, ys = run(x, p)
        outs.append(ys)
    if unrolled < P:
        x, ys = jax.lax.scan(run, x, jnp.arange(unrolled, P, dtype=jnp.int32))
        outs.append(jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), ys))
    join = lambda parts: jax.tree_util.tree_map(  # noqa: E731
        lambda *a: jnp.concatenate(a), *parts)
    ns = [o[1] for o in outs if o[1] is not None]
    return x, join([o[0] for o in outs]), join(ns) if ns else None
