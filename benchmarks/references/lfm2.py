"""The plain float32 reference of the conv family (``model_type:
lfm2_moe``; LFM2-24B-A2B's block), written out from the published
``config.json`` and the source library's layer in straightforward
``jax.numpy``: whole sequences, no cache, no tail, no bucket, no chunk,
no kernel, no dispatch. It imports nothing of ``gofr_tpu``.

``x`` is the residual stream, RMSNorm (``norm_eps``) before each half:
``x += Op(RMSNorm(x)); x += FF(RMSNorm(x))``; after the last layer one
more RMSNorm (the source's ``embedding_norm``), then the head. Layer
``l`` is of kind ``layer_pattern[l % period]`` (published
``layer_types``: ``conv, conv, full_attention, conv``).

conv operator at position t, ``h = RMSNorm(x)`` (no bias, no activation):
  [B_t, C_t, X_t] = h_t W_in, a third of 3 D each, in that order;
  u_t = B_t * X_t;
  c_t = sum_j k_j * u_{t - (W - 1) + j}, j = 0 .. W - 1, with k [W, D] a
  channel and u_{<0} = 0 (a causal depthwise convolution over
  ``conv_kernel`` W = 3 inputs: k_{W-1} meets the current one);
  x_t <- x_t + (C_t * c_t) W_out.

full_attention operator, H = ``n_heads`` on ``n_kv_heads`` KV heads of
``head_dim`` hd:
  q = h W_q in [H, hd], k = h W_k, v = h W_v in [KV, hd], no bias;
  q <- RMSNorm_head(q; w_qn), k <- RMSNorm_head(k; w_kn) over a head's hd
  values (``norm_eps``), BEFORE the rotation;
  rotate-half RoPE over the whole head, ``rope_theta``;
  scores q_p . k_j / sqrt(hd) in float32 over j <= p, softmax,
  o = sum a v; x <- x + concat(o) W_o. No gate, no window, no softcap.

Feed-forward: the first ``n_dense_layers`` layers SwiGLU of width
``ffn_dim``; the others s = sigmoid(h W_r) in float32 over all
``n_experts``, the top ``experts_per_token`` of s + bias (the bias
selects, it does not weigh), w = s_e / (sum_sel s + 1e-6) *
``routed_scaling``, y = sum_sel w_e SwiGLU_e(h); an expert is SwiGLU
D -> ``moe_ffn_dim`` -> D. No shared expert.

Departures from the source, each in the configuration's ``assumed``:
random int8 weights from a seed in place of the checkpoint (dequantised
here a layer and a block of experts at a time: a layer's 64 experts in
float32 are 2.4 GB); tied embeddings; the order of the thirds of W_in.
Every expert is computed for every token and weighed by its combine
weight, zero off the chosen: the same sum as the chosen alone.

Router gap a position: over the routed layers, the smallest distance
between the selection score of the last expert kept and the best one
left out.

``control``: a test's one departure from the above, which the engine
must NOT agree with: ``taps_reversed`` (k_0 meets the current input),
``stale_tail`` (the inputs before the current one a position older),
``no_qk_norm``, ``norm_after_rope``, ``bias_weighs`` (w from s + bias).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_BLOCK = 8   # experts dequantised at a time: 8 x 37.7 MB float32
CONTROLS = ("taps_reversed", "stale_tail", "no_qk_norm", "norm_after_rope",
            "bias_weighs")


def _deq(leaf):
    """float32 weights of a plain or int8 (w, per-output-channel scale)
    leaf, whatever its leading axes."""
    if hasattr(leaf, "scale"):
        return leaf.w.astype(F32) * leaf.scale[..., None, :].astype(F32)
    return leaf.astype(F32)


def _at(tree, i):
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope_tables(theta: float, hd: int, s: int):
    """(cos, sin) [S, hd / 2] of the plain frequencies."""
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rotate(x, cos, sin):
    """x [S, H, hd]: rotate-half over the whole head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("eps", "control"))
def conv_operator(lw, i, x, *, eps, control=""):
    """One layer's gated short convolution: x [S, D] -> x + y."""
    lw = _at(lw, i)
    s, d = x.shape
    bcx = _rms(x, lw["attn_norm"], eps) @ _deq(lw["w_in"])
    b, c, xg = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = b * xg
    taps = lw["conv"].astype(F32)                       # [W, D]
    w = taps.shape[0]
    if control == "taps_reversed":
        taps = taps[::-1]
    conv = jnp.zeros_like(u)
    for j in range(w):
        back = w - 1 - j               # tap j meets the input ``back`` ago
        if control == "stale_tail" and back:
            back += 1
        conv = conv + taps[j] * jnp.pad(u, ((back, 0), (0, 0)))[:s]
    return x + (c * conv) @ _deq(lw["w_out"])


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps",
                                   "qk_norm", "control"))
def attention(lw, i, x, cos, sin, *, heads, kv_heads, hd, eps, qk_norm,
              control=""):
    """One layer's attention: x [S, D] -> x + y."""
    lw = _at(lw, i)
    s = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    q = (h @ _deq(lw["wq"])).reshape(s, heads, hd)
    k = (h @ _deq(lw["wk"])).reshape(s, kv_heads, hd)
    v = (h @ _deq(lw["wv"])).reshape(s, kv_heads, hd)
    normed = qk_norm and control != "no_qk_norm"
    if normed and control != "norm_after_rope":
        q, k = _rms(q, lw["q_head_norm"], eps), _rms(k, lw["k_head_norm"], eps)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    if normed and control == "norm_after_rope":
        q, k = _rms(q, lw["q_head_norm"], eps), _rms(k, lw["k_head_norm"], eps)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v)
    return x + o.reshape(s, heads * hd) @ _deq(lw["wo"])


@jax.jit
def _swiglu(lw, i, h):
    lw = _at(lw, i)
    return (jax.nn.silu(h @ _deq(lw["w_gate"])) * (h @ _deq(lw["w_up"]))) \
        @ _deq(lw["w_down"])


@partial(jax.jit, static_argnames=("n",))
def _expert_block(lw, i, e0, combine, h, *, n):
    """sum over experts e0 .. e0 + n of combine[:, e] SwiGLU_e(h)."""
    lw = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, e0, n, axis=0), _at(lw, i))
    g = jnp.einsum("sd,edf->esf", h, _deq(lw["w_gate"]))
    u = jnp.einsum("sd,edf->esf", h, _deq(lw["w_up"]))
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, _deq(lw["w_down"]))
    w = jax.lax.dynamic_slice_in_dim(combine, e0, n, axis=1)
    return jnp.einsum("se,esd->sd", w, y)


@partial(jax.jit, static_argnames=("k", "scale", "control"))
def route(router, bias, i, h, *, k, scale, control=""):
    """([S, E] combine weights over all experts, zero off the chosen k;
    [S] gap of the selection score between the last kept and the best
    left out)."""
    s = jax.nn.sigmoid(h @ _at(router, i).astype(F32))
    sel = s + _at(bias, i).astype(F32)
    topv, topi = jax.lax.top_k(sel, k + 1)
    gap = topv[:, k - 1] - topv[:, k]
    n, e = s.shape
    chosen = jnp.zeros((n, e), bool).at[
        jnp.arange(n)[:, None], topi[:, :k]].set(True)
    w = jnp.where(chosen, sel if control == "bias_weighs" else s, 0.0)
    return w / (jnp.sum(w, -1, keepdims=True) + 1e-6) * scale, gap


def routed_ffn(moe, cfg, i, h, control=""):
    """One routed layer's feed-forward: (y [S, D], gap [S])."""
    i_ = jnp.int32(i)
    combine, gap = route(moe["router"], moe["router_bias"], i_, h,
                         k=cfg.experts_per_token,
                         scale=float(cfg.routed_scaling), control=control)
    held = cfg.n_experts_held or cfg.n_experts
    stacks = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    y = jnp.zeros_like(h)
    for e0 in range(0, held, EXPERT_BLOCK):
        y = y + _expert_block(stacks, i_, jnp.int32(e0), combine, h,
                              n=min(EXPERT_BLOCK, held - e0))
    return y, gap


@partial(jax.jit, static_argnames=("eps", "tied"))
def _logprobs(final_norm, head, x, *, eps, tied):
    w = head.astype(F32).T if tied else _deq(head)
    return jax.nn.log_softmax(_rms(x, final_norm, eps) @ w, -1)


def forward_logprobs(params, cfg, tokens, rows, control: str = ""):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and the smallest router gap over
    the routed layers at each of those positions [len(rows)]."""
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    rows = jnp.asarray(rows)
    eps = float(cfg.norm_eps)
    pattern = tuple(cfg.layer_pattern)
    hd = cfg.attn_head_dim or cfg.dim // cfg.n_heads
    seen = {"conv": 0, "full": 0}
    min_gap = None
    with jax.default_matmul_precision("highest"):
        cos, sin = rope_tables(float(cfg.rope_theta), hd, len(tokens))
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for layer in range(cfg.n_layers):
            kind = pattern[layer % len(pattern)]
            i = jnp.int32(seen[kind])
            seen[kind] += 1
            if kind == "conv":
                x = conv_operator(params["conv"], i, x, eps=eps,
                                  control=control)
            else:
                x = attention(params["full"], i, x, cos, sin,
                              heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                              hd=hd, eps=eps, qk_norm=bool(cfg.qk_norm),
                              control=control)
            if layer < cfg.n_dense_layers:
                dense = params["dense"]
                x = x + _swiglu(dense, jnp.int32(layer),
                                _rms(x, dense["ffn_norm"][layer], eps))
                continue
            j = layer - cfg.n_dense_layers
            moe = params["moe"]
            y, gap = routed_ffn(moe, cfg, j, _rms(x, moe["ffn_norm"][j], eps),
                                control)
            x = x + y
            gap = gap[rows]
            min_gap = gap if min_gap is None else jnp.minimum(min_gap, gap)
        head = params["embedding"] if cfg.tie_embeddings \
            else params["lm_head"]
        return _logprobs(params["final_norm"], head, x[rows], eps=eps,
                         tied=cfg.tie_embeddings), min_gap
