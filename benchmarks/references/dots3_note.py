"""The plain float32 reference of the sparse-latent family (``model_type:
dots3_note``; dots3-note-prev's language model), written out from the
published ``config.json`` in straightforward ``jax.numpy``: no cache, no
ring, no absorbed form, no batching, no kernel, no dispatch. It imports
nothing of ``gofr_tpu``.

Per layer, ``x`` the residual stream, ``h = RMSNorm(x)``; every layer is
``x += Attn(h)``, then ``x += FFN(RMSNorm(x))``. Layer ``l`` is of kind
``layer_pattern[l % len(layer_pattern)]``.

Both kinds of layer are latent attention, EXPANDED (keys and values a
head are materialised; the engine never does that over its cache), each
at its own sizes:

  c_q = RMSNorm(h W_qa) * (dim / q rank)^1/2
  q = c_q W_qb, a head [q_nope | q_pe];  q_pe = RoPE(q_pe)
  [c_kv | k_pe] = h W_kva;  c_kv = RMSNorm(c_kv) * (dim / kv rank)^1/2
  k_pe = RoPE(k_pe), one for all heads
  [k_nope | v] = c_kv W_kvb a head
  s = (q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-1/2
  softmax in float32 over the positions the query's MASK allows
  o_head = sum p v, times sigmoid(h W_g)_head;  then W_o

(the two rescales only where ``lora_rescale``; RoPE: plain frequencies
theta^(-2i/d), halves of the rope dims as the projection gives them).

The mask a query, written as a mask:

  full layer    the causal positions s <= t that the INDEXER keeps:
                qI = c_q W_Iq (index_heads heads), kI = LayerNorm(h W_Ik)
                (one a token), RoPE on the first qk_rope_head_dim values
                of both, w = h W_Iw * index_heads^-1/2 *
                index_head_dim^-1/2,
                I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]);
                S_t = the index_topk positions s <= t of highest
                I[t, s] (``jax.lax.top_k`` over the causal scores), all
                of them while t < index_topk
  window layer  t - (window_size - 1) <= s <= t

Feed-forward: the first ``n_dense_layers`` layers SwiGLU; the others
  s = sigmoid(h W_r) in float32 over all ``n_experts``; the
  ``experts_per_token`` highest of s + b (within the ``topk_groups`` best
  of ``n_expert_groups`` groups, a group's score the sum of its two
  largest: one group here); weights s_i / sum s_j * routed_scaling;
  y = sum over the chosen experts THIS CHIP HOLDS of w_i E_i(h)
      + the shared expert.
The parameter tree is the engine's own, and it is the chip's share:
``n_experts_held`` experts (ids 0..) a layer behind a router that is
``n_experts`` wide. What the absent experts would add is left out here
exactly as in the program; ``layer_share`` lets a test add the shares up
to the uncut layer.

Heads and index heads run a block at a time and experts one at a time,
so that 3,000 tokens at the published widths fit beside the serving
engine.

``select``: what stands in for ``jax.lax.top_k`` in the full layers'
mask. ``first_positions`` keeps the first ``index_topk`` positions
whatever the scores: the control that shows the comparison sees the
selection (it must fail where a prompt is longer than ``index_topk``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 4


def _deq(leaf):
    """float32 weights of a plain or int8 (w, per-output-channel scale)
    leaf, whatever its leading axes."""
    if hasattr(leaf, "scale"):
        return leaf.w.astype(F32) * leaf.scale[..., None, :].astype(F32)
    return leaf.astype(F32)


def _at(tree, *idx):
    def one(x):
        for i in idx:
            x = jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
        return x
    return jax.tree_util.tree_map(one, tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def _rope(x, theta: float, part: int | None = None):
    """x [S, heads, d]: rotate halves of the first ``part`` values (all
    of them by default) at positions 0..S-1, plain frequencies."""
    s, _, d = x.shape
    part = d if part is None else part
    inv = jnp.asarray([theta ** (-2.0 * i / part) for i in range(part // 2)],
                      F32)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : part // 2], x[..., part // 2: part]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn,
                            x[..., part:]], -1)


def _blocks(x, hb: int):
    """[S, H, d] -> [H / hb, S, hb, d]."""
    s, h, d = x.shape
    return jnp.moveaxis(x.reshape(s, h // hb, hb, d), 1, 0)


def top_positions(scores, causal, k: int):
    """The mask of the ``k`` causal positions of highest score a query
    (all of them where there are no more): scores [S, S]. The set
    ``jax.lax.top_k``'s indices name, written from its k-th value: what
    is above it, and of what equals it the lowest positions, which is
    how ``top_k`` orders equal elements. (Scattering the indices
    themselves into a mask is a second a million on a TPU: four minutes
    of a run's set-up at these lengths.)"""
    k = min(k, scores.shape[0])
    # one zero: top_k orders -0 under +0, == does not
    scores = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    above, ties = scores > kth, scores == kth
    room = k - jnp.sum(above, -1, keepdims=True)
    return causal & (above | (ties & (jnp.cumsum(ties, -1) <= room)))


def first_positions(scores, causal, k: int):
    """The control: the first ``k`` positions, whatever the scores."""
    return causal & (jnp.arange(scores.shape[0])[None, :] < k)


def index_scores(lw, h, c_q, *, heads, theta, rope, eps):
    """I[t, s] [S, S] of one full layer (``lw`` its leaves)."""
    s = h.shape[0]
    q_i = _rope((c_q @ _deq(lw["w_iq"])).reshape(s, heads, -1), theta, rope)
    d = q_i.shape[-1]
    k_i = _layer_norm(h @ _deq(lw["w_ik"]), lw["ik_norm"], lw["ik_bias"],
                      eps)
    k_i = _rope(k_i[:, None, :], theta, rope)[:, 0]
    w = (h @ lw["w_iw"].astype(F32)) * (heads ** -0.5 * d ** -0.5)
    hb = max(b for b in range(1, HEAD_BLOCK + 1) if heads % b == 0)

    def block(acc, xs):
        q, wj = xs                                    # [S, hb, d], [S, hb]
        sc = jax.nn.relu(jnp.einsum("thd,sd->ths", q, k_i))
        return acc + jnp.einsum("ths,th->ts", sc, wj), None

    out, _ = jax.lax.scan(
        block, jnp.zeros((s, s), F32),
        (_blocks(q_i, hb), jnp.moveaxis(w.reshape(s, heads // hb, hb), 1, 0)))
    return out


@partial(jax.jit, static_argnames=(
    "kind", "dim", "heads", "rank", "dn", "dr", "dv", "theta", "eps",
    "rescale", "gate", "window", "index_heads", "index_rope", "index_theta",
    "topk", "select", "want_mask"))
def _attention(lw, i, x, *, kind, dim, heads, rank, dn, dr, dv, theta, eps,
               rescale, gate, window, index_heads, index_rope, index_theta,
               topk, select, want_mask=False):
    """One layer's attention half, expanded: x [S, D] -> (x + attn, the
    normed input of the feed-forward[, the mask [S, S]])."""
    lw = _at(lw, i)
    s = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    c_q = _rms(h @ _deq(lw["w_qa"]), lw["q_norm"], eps)
    kva = h @ _deq(lw["w_kva"])
    c_kv = _rms(kva[:, :rank], lw["kv_norm"], eps)
    if rescale:
        c_q = c_q * (dim / c_q.shape[-1]) ** 0.5
        c_kv = c_kv * (dim / rank) ** 0.5
    q = (c_q @ _deq(lw["w_qb"])).reshape(s, heads, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], theta)
    k_pe = _rope(kva[:, None, rank:], theta)[:, 0]                # [S, dr]
    kv = (c_kv @ _deq(lw["w_kvb"])).reshape(s, heads, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    at = jnp.arange(s)
    mask = at[None, :] <= at[:, None]                             # causal
    if kind == "window":
        mask = mask & (at[None, :] >= at[:, None] - (window - 1))
    elif topk:
        scores = index_scores(lw, h, c_q, heads=index_heads,
                              theta=index_theta, rope=index_rope, eps=eps)
        mask = select(scores, mask, topk)
    hb = max(b for b in range(1, HEAD_BLOCK + 1) if heads % b == 0)

    def block(xs):
        qn, kn, vv, qp = xs                                   # [S, hb, d]
        sc = (jnp.einsum("qhd,khd->hqk", qn, kn)
              + jnp.einsum("qhd,kd->hqk", qp, k_pe)) * (dn + dr) ** -0.5
        probs = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, vv)

    attn = jax.lax.map(block, tuple(_blocks(a, hb) for a in
                                    (q_nope, k_nope, v, q_pe)))
    attn = jnp.moveaxis(attn, 0, 1).reshape(s, heads, dv)
    if gate:
        attn = attn * jax.nn.sigmoid(h @ _deq(lw["head_gate"]))[..., None]
    x = x + attn.reshape(s, heads * dv) @ _deq(lw["wo"])
    return (x, mask) if want_mask else x


@jax.jit
def _swiglu(lw, idx, h):
    """SwiGLU of one dense layer or shared expert (idx = (layer,)) or
    one routed expert (idx = (layer, expert))."""
    lw = _at(lw, *idx)
    return (jax.nn.silu(h @ _deq(lw["w_gate"])) * (h @ _deq(lw["w_up"]))) \
        @ _deq(lw["w_down"])


@partial(jax.jit, static_argnames=("k", "groups", "keep", "scale"))
def route(router, bias, i, h, *, k, groups, keep, scale):
    """([S, E] combine weights over ALL experts, zero off the chosen k;
    [S] gap of the selection score between the last kept and the best
    left out inside the kept groups)."""
    s = jax.nn.sigmoid(h @ _at(router, i).astype(F32))            # [S, E]
    sel = s + _at(bias, i).astype(F32)
    n, e = s.shape
    per = e // groups
    group = jnp.sum(jax.lax.top_k(sel.reshape(n, groups, per),
                                  min(2, per))[0], -1)
    kept = jnp.zeros((n, groups), bool).at[
        jnp.arange(n)[:, None], jax.lax.top_k(group, keep)[1]].set(True)
    sel = jnp.where(jnp.repeat(kept, per, axis=1), sel, -jnp.inf)
    topv, topi = jax.lax.top_k(sel, k + 1)
    gap = topv[:, k - 1] - topv[:, k]
    chosen = jnp.zeros((n, e), bool).at[
        jnp.arange(n)[:, None], topi[:, :k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale
    return w, gap


def layer_share(layers, cfg, i, h, held, shared: bool = True):
    """One routed layer's feed-forward output for the experts ``held``
    (an iterable of (global expert id, index in the parameter stack)),
    with or without the shared expert: the partial sum a chip that holds
    those experts computes. Returns (y [S, D], gap [S])."""
    i_ = jnp.int32(i)
    combine, gap = route(layers["router"], layers["router_bias"], i_, h,
                         k=cfg.experts_per_token, groups=cfg.n_expert_groups,
                         keep=cfg.topk_groups,
                         scale=float(cfg.routed_scaling))
    ffn_w = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
    y = jnp.zeros_like(h)
    for gid, local in held:
        y = y + combine[:, gid:gid + 1] * _swiglu(
            ffn_w, (i_, jnp.int32(local)), h)
    if shared:
        y = y + _swiglu({"w_gate": layers["ws_gate"], "w_up": layers["ws_up"],
                         "w_down": layers["ws_down"]}, (i_,), h)
    return y, gap


@partial(jax.jit, static_argnames=("eps",))
def _ffn_norm(w, i, x, *, eps):
    return _rms(x, _at(w, i), eps)


@partial(jax.jit, static_argnames=("eps", "tied"))
def _logprobs(final_norm, head, x, *, eps, tied):
    w = head.astype(F32).T if tied else _deq(head)
    return jax.nn.log_softmax(_rms(x, final_norm, eps) @ w, -1)


def _attn_kw(cfg, kind: str, select) -> dict:
    window = kind == "window"

    def size(name):
        own = getattr(cfg, "window_" + name) if window else 0
        return own or getattr(cfg, name)

    return dict(
        kind=kind, dim=cfg.dim,
        heads=(cfg.window_heads or cfg.n_heads) if window else cfg.n_heads,
        rank=size("kv_lora_rank"), dn=size("qk_nope_head_dim"),
        dr=size("qk_rope_head_dim"), dv=size("v_head_dim"),
        theta=float((cfg.window_rope_theta or cfg.rope_theta) if window
                    else cfg.rope_theta),
        eps=float(cfg.norm_eps), rescale=bool(cfg.lora_rescale),
        gate=bool(cfg.head_gate), window=cfg.window_size,
        index_heads=cfg.index_heads, index_rope=cfg.qk_rope_head_dim,
        index_theta=float(cfg.rope_theta), topk=cfg.index_topk,
        select=select)


def forward_logprobs(params, cfg, tokens, rows, select=top_positions,
                     masks: list | None = None):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and the smallest router gap
    over the routed layers at each of those positions [len(rows)].
    ``masks``: a list that takes each full layer's mask [S, S] (a test
    holds the engine's kept rows against them)."""
    rows = jnp.asarray(rows)
    pat = tuple(cfg.layer_pattern)
    nd = cfg.n_dense_layers
    held = [(e, e) for e in range(cfg.n_experts_held or cfg.n_experts)]
    min_gap = None
    seen = {kind: 0 for kind in pat}
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for l in range(cfg.n_layers):
            kind = pat[l % len(pat)]
            i = jnp.int32(seen[kind])
            seen[kind] += 1
            attn_w = {k: v for k, v in params[kind].items()}
            want = masks is not None and kind == "full"
            out = _attention(attn_w, i, x, want_mask=want,
                             **_attn_kw(cfg, kind, select))
            if want:
                x, mask = out
                masks.append(mask)
            else:
                x = out
            if l < nd:
                h = _ffn_norm(params["dense"]["ffn_norm"], jnp.int32(l), x,
                              eps=float(cfg.norm_eps))
                x = x + _swiglu({k: params["dense"][k] for k in
                                 ("w_gate", "w_up", "w_down")},
                                (jnp.int32(l),), h)
                continue
            h = _ffn_norm(params["moe"]["ffn_norm"], jnp.int32(l - nd), x,
                          eps=float(cfg.norm_eps))
            y, gap = layer_share(params["moe"], cfg, l - nd, h, held)
            x = x + y
            gap = gap[rows]
            min_gap = gap if min_gap is None else jnp.minimum(min_gap, gap)
        head = params["embedding"] if cfg.tie_embeddings \
            else params["lm_head"]
        return _logprobs(params["final_norm"], head, x[rows],
                         eps=float(cfg.norm_eps),
                         tied=cfg.tie_embeddings), min_gap
