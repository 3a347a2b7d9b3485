"""The plain float32 reference of the block-diffusion family
(``model_type: sdar_moe``; SDAR-30B-A3B-Chat's layer and its sampler),
written out from the published ``config.json`` and the family's
generation procedure in straightforward ``jax.numpy``: whole sequences,
no cache, no bucket, no chunk, no kernel, no dispatch, no carried state.
It imports nothing of ``gofr_tpu``.

The layer, all alike (``x`` the residual stream, RMSNorm ``norm_eps``
before each half):
  h = RMSNorm(x); q = h W_q in [H, hd], k = h W_k, v = h W_v in [KV, hd],
  no bias; q <- RMSNorm_head(q; w_qn), k <- RMSNorm_head(k; w_kn) over a
  head's hd values BEFORE the rotation; rotate-half RoPE over the whole
  head at ``rope_theta``; scores q_i . k_j / sqrt(hd) over the j that i
  sees, softmax, o = sum a v; x <- x + concat(o) W_o.
  h = RMSNorm(x); p = softmax(h W_r) in float32 over all ``n_experts``;
  the top ``experts_per_token``, renormalised to sum 1;
  x <- x + sum_sel p_e W_down,e (silu(W_gate,e h) * W_up,e h).
Final RMSNorm, an untied head.

What i sees, with B = ``block_length``: j iff j // B <= i // B, in
prompts and in generation alike.

Generation of block b (positions B b .. B b + 3) under the order
``sequential`` with k = B / ``denoise_passes`` positions a pass: the
positions the prompt gives hold their tokens, the others the mask token
(``mask_token_id``'s embedding); pass t sees the given positions and the
block's first t k generated ones as tokens and the rest as masks, over
every earlier block's FINAL tokens; a masked position's distribution is
read at its own row (no shift), and the pass commits the leftmost k
masked positions. So the generated position with index g among its
block's generated ones is committed in pass g // k, and under this order
the state of every block at every pass follows from the tokens alone:
the reference is teacher-forced exactly.

One forward a pass, not one a block: the published training layout. The
clean sequence is followed by a copy of the generated blocks in their
state before pass t; a clean position sees the clean positions of its
block and of the blocks before; a position of a noised block b sees the
clean blocks before b and noised block b itself, at b's own positions.
``forward_logprobs`` returns, for row r, the distribution of position
r + 1 in the pass that commits it, read at the noised copy of r + 1.
Positions from the last one asked for on are unknown to the caller
(``reference.compare`` holds the last token back and pads with zeros):
they stay masks in every pass, as they are in the engine's block when
the compared positions are committed.

Departures from the source, each in the configuration's ``assumed``:
random int8 weights from a seed in place of the checkpoint (dequantised
here a layer and a block of experts at a time); the block length, the
mask token's id and the sampler's order and passes. Every expert is
computed for every token and weighed by its combine weight, zero off the
chosen: the same sum as the chosen alone.

Router gap a position: over the layers, the smallest log-ratio between
the probability of the last expert kept and the best one left out.

``control``: a test's one departure from the above, which the engine
must NOT agree with: ``causal_block`` (inside a block a position sees
only those at or before it), ``no_commit`` (a later block sees an
earlier GENERATED block as its last denoise pass left it: the positions
that pass committed still masks, which is what rows kept from that pass
hold), ``shifted`` (a position's distribution read at the row before
it), ``no_qk_norm``, ``norm_after_rope``. ``weight_bits``: the weights
rounded to that many bits on their int8 grid (the nearest precision
below the configuration's).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXPERT_BLOCK = 16   # experts dequantised at a time: 16 x 18.9 MB float32
CONTROLS = ("causal_block", "no_commit", "shifted", "no_qk_norm",
            "norm_after_rope")


def _deq(leaf, bits: int = 8):
    """float32 weights of a plain or int8 (w, per-output-channel scale)
    leaf, whatever its leading axes; ``bits`` < 8: rounded to that many
    bits of the int8 grid."""
    if hasattr(leaf, "scale"):
        w = leaf.w.astype(F32)
        if bits < 8:
            step = F32(2 ** (8 - bits))
            w = jnp.round(w / step) * step
        return w * leaf.scale[..., None, :].astype(F32)
    return leaf.astype(F32)


def _at(tree, i):
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate(x, cos, sin):
    """x [S, H, hd]: rotate-half over the whole head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps",
                                   "theta", "control", "bits"))
def attention(lw, i, x, positions, seen, *, heads, kv_heads, hd, eps,
              theta, control="", bits=8):
    """One layer's attention: x [S, D] at ``positions`` [S] under the
    mask ``seen`` [S, S] -> x + y."""
    lw = _at(lw, i)
    s = x.shape[0]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    h = _rms(x, lw["attn_norm"], eps)
    q = (h @ _deq(lw["wq"], bits)).reshape(s, heads, hd)
    k = (h @ _deq(lw["wk"], bits)).reshape(s, kv_heads, hd)
    v = (h @ _deq(lw["wv"], bits)).reshape(s, kv_heads, hd)
    normed = control != "no_qk_norm"
    if normed and control != "norm_after_rope":
        q, k = _rms(q, lw["q_head_norm"], eps), _rms(k, lw["k_head_norm"], eps)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    if normed and control == "norm_after_rope":
        q, k = _rms(q, lw["q_head_norm"], eps), _rms(k, lw["k_head_norm"], eps)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v)
    return x + o.reshape(s, heads * hd) @ _deq(lw["wo"], bits)


@partial(jax.jit, static_argnames=("n", "bits"))
def _expert_block(lw, i, e0, combine, h, *, n, bits=8):
    """sum over experts e0 .. e0 + n of combine[:, e] SwiGLU_e(h)."""
    lw = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, e0, n, axis=0), _at(lw, i))
    g = jnp.einsum("sd,edf->esf", h, _deq(lw["w_gate"], bits))
    u = jnp.einsum("sd,edf->esf", h, _deq(lw["w_up"], bits))
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u,
                   _deq(lw["w_down"], bits))
    w = jax.lax.dynamic_slice_in_dim(combine, e0, n, axis=1)
    return jnp.einsum("se,esd->sd", w, y)


@partial(jax.jit, static_argnames=("k",))
def route(router, i, h, *, k):
    """([S, E] combine weights: softmax over all experts, the top k kept
    and renormalised, zero elsewhere; [S] gap: log p of the last kept
    minus log p of the best left out)."""
    probs = jax.nn.softmax(h @ _at(router, i).astype(F32), -1)
    topv, topi = jax.lax.top_k(probs, k + 1)
    gap = jnp.log(topv[:, k - 1]) - jnp.log(topv[:, k])
    topv, topi = topv[:, :k], topi[:, :k]
    topv = topv / jnp.sum(topv, -1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(topi, probs.shape[-1], dtype=F32)
                      * topv[..., None], axis=1)
    return combine, gap


@partial(jax.jit, static_argnames=("eps", "bits"))
def _logprobs(final_norm, head, x, *, eps, bits=8):
    return jax.nn.log_softmax(_rms(x, final_norm, eps) @ _deq(head, bits),
                              -1)


def _forward(params, cfg, ids, is_mask, positions, seen, take, control,
             bits):
    """The stack over one layout: ids [S] (a mask position's id is not
    read), is_mask [S] bool, positions [S], seen [S, S] ->
    (log-probabilities at the rows ``take``, their smallest router
    gap)."""
    layers = params["layers"]
    eps = float(cfg.norm_eps)
    hd = cfg.attn_head_dim or cfg.dim // cfg.n_heads
    attn_w = {k: layers[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                     "q_head_norm", "k_head_norm")}
    stacks = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
    emb = params["embedding"]
    x = jnp.where(is_mask[:, None], emb[cfg.mask_token_id][None],
                  emb[ids]).astype(F32)
    min_gap = None
    for layer in range(cfg.n_layers):
        i = jnp.int32(layer)
        x = attention(attn_w, i, x, positions, seen, heads=cfg.n_heads,
                      kv_heads=cfg.n_kv_heads, hd=hd, eps=eps,
                      theta=float(cfg.rope_theta), control=control,
                      bits=bits)
        h = _rms(x, layers["ffn_norm"][layer], eps)
        combine, gap = route(layers["router"], i, h,
                             k=cfg.experts_per_token)
        for e0 in range(0, cfg.n_experts, EXPERT_BLOCK):
            x = x + _expert_block(stacks, i, jnp.int32(e0), combine, h,
                                  n=min(EXPERT_BLOCK, cfg.n_experts - e0),
                                  bits=bits)
        gap = gap[take]
        min_gap = gap if min_gap is None else jnp.minimum(min_gap, gap)
    return _logprobs(params["final_norm"], params["lm_head"], x[take],
                     eps=eps, bits=bits), min_gap


def forward_logprobs(params, cfg, tokens, rows, control: str = "",
                     weight_bits: int = 8):
    """float32 log-probabilities [len(rows), V] of the token at position
    r + 1 for each r of ``rows`` (consecutive: the generated positions of
    one stream, the first of them the first generated one), in the pass
    that commits it, and the smallest router gap over the layers at that
    position's row [len(rows)]. ``tokens`` [S]: the prompt and the
    generated tokens but the last, then padding."""
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    if cfg.commit_order != "sequential":
        raise ValueError(
            f"commit_order {cfg.commit_order!r}: only 'sequential' commits "
            "in an order that follows from the tokens alone")
    B = cfg.block_length
    passes = cfg.denoise_passes or B
    k = B // passes
    rows = [int(r) for r in rows]
    n, end = rows[0] + 1, rows[-1] + 2   # prompt length; positions asked +1
    known = end - 1                      # tokens[:known] are the caller's
    first = n // B                       # the first generated block
    total = -(-end // B) * B             # whole blocks up to the last asked
    toks = np.zeros((total,), np.int32)
    toks[:known] = np.asarray(tokens[:known], np.int32)
    pos = np.arange(total)
    blk = pos // B
    # index of a position among its block's generated ones (negative:
    # the prompt's), and the pass that commits it
    nth = pos - np.maximum(n, blk * B)
    generated = pos >= n
    # the layout: clean positions 0 .. total, then the noised copy of
    # the generated blocks' positions B first .. total
    noised = pos[first * B:]
    layout_pos = np.concatenate([pos, noised])
    layout_blk = np.concatenate([blk, blk[first * B:]])
    is_copy = np.arange(len(layout_pos)) >= total
    ib, jb = layout_blk[:, None], layout_blk[None, :]
    ic, jc = is_copy[:, None], is_copy[None, :]
    seen = np.where(ic, np.where(jc, jb == ib, jb < ib), ~jc & (jb <= ib))
    if control == "causal_block":
        ip, jp = layout_pos[:, None], layout_pos[None, :]
        seen = seen & ((jb < ib) | (jp <= ip))
    out, gaps = [None] * len(rows), [None] * len(rows)
    with jax.default_matmul_precision("highest"):
        for t in range(passes):
            # before pass t a generated position is a token iff an
            # earlier pass committed it and the caller knows it
            shown = (~generated | (nth < t * k)) & (pos < known)
            clean_mask = pos >= known
            if control == "no_commit":
                # a generated block as its last denoise pass left it
                last = (generated & (nth >= (passes - 1) * k)
                        & (blk * B + B <= known))   # whole blocks alone
                clean_mask = clean_mask | last
            ids = np.concatenate([toks, toks[first * B:]])
            is_mask = np.concatenate([clean_mask, ~shown[first * B:]])
            mine = [j for j, r in enumerate(rows)
                    if nth[r + 1] // k == t]
            if not mine:
                continue
            at = [total + (rows[j] + 1 - first * B)
                  - (1 if control == "shifted" else 0) for j in mine]
            lp, gap = _forward(params, cfg, jnp.asarray(ids),
                               jnp.asarray(is_mask),
                               jnp.asarray(layout_pos, jnp.int32),
                               jnp.asarray(seen), jnp.asarray(at), control,
                               weight_bits)
            for j, a, b in zip(mine, lp, gap):
                out[j], gaps[j] = a, b
    return jnp.stack(out), jnp.stack(gaps)
