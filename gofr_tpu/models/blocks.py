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

from ..ops.attention import causal_attention
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from ..ops.rope import apply_rope_part
from .common import ModelConfig
from .moe import EXPERT_STACKS, dense_ffn, moe_ffn

F32 = jnp.float32


def embed(params, cfg: ModelConfig, tokens):
    with jax.named_scope("embed"):
        return params["embedding"][tokens].astype(cfg.jdtype)


def prompt_rows(tokens, lengths):
    """(lengths [B], positions [B, S], valid [B, S]) of right-padded
    prompts [B, S]; ``lengths`` None: every row is whole."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return lengths, positions, positions < lengths[:, None]


def prompt_attend(flash: bool, lengths, valid, mesh, window: int = 0):
    """``attend(q, k, v)`` of a whole prompt within itself: the flash
    kernel where ``flash`` and backend and shapes allow (``ops.flash``),
    the jnp reference otherwise; banded where ``window``."""
    if flash:
        from ..ops.flash import causal_attention_auto

        return lambda q, k, v: causal_attention_auto(
            q, k, v, lengths=lengths, mask=valid, mesh=mesh, window=window)
    return lambda q, k, v: causal_attention(q, k, v, mask=valid,
                                            window=window)


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
