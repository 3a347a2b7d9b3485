"""The plain float32 reference of the window family (``model_type:
laguna``; Laguna-XS.2's block), written out from the published
``config.json`` in straightforward ``jax.numpy``: whole sequences under a
banded mask, no cache, no ring, no chunking, no kernel, no dispatch. It
imports nothing of ``gofr_tpu``.

``x`` is the residual stream, RMSNorm (``rms_norm_eps``) before each
half: ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``. Layer ``l`` is of
kind ``layer_pattern[l % period]`` (published ``layer_types``: layer l is
``full_attention`` where l % 4 == 0, else ``sliding_attention``).

Attention of layer l at position p, ``h = RMSNorm(x)``, H = ``n_heads``
(published ``num_attention_heads_per_layer``: 48) on a full layer and
``window_heads`` (64) on a window layer, over ``n_kv_heads`` KV heads of
``head_dim``:
  q = h W_q in [H, hd], k = h W_k, v = h W_v in [KV, hd];
  full: the first ``rotary_dim`` values of q and k rotated by the YaRN
  frequencies of ``rope_theta`` (``factor``, ``original_max_position_
  embeddings``, ``beta_fast``, ``beta_slow``), cos and sin multiplied by
  ``attention_factor``; the rest pass through. Window: the whole head
  rotated by the plain frequencies of ``window_rope_theta``. Pairing:
  rotate-half over the rotated values;
  scores q_p . k_j / sqrt(hd) in float32 over j <= p (full) or
  p - W < j <= p (window: ``window_size`` keys, the token's own among
  them), softmax, o = sum a v;
  gate (``head_gate``): g = sigmoid(h W_g) in [H], o_head <- g_head o_head;
  x <- x + concat(o) W_o.

Feed-forward: the first ``n_dense_layers`` layers SwiGLU of width
``ffn_dim``; the others s = sigmoid(h W_r) in float32 over all
``n_experts``, the top ``experts_per_token`` of s + bias, weights
s_e / sum_sel s * ``routed_scaling`` (the bias selects, it does not
weigh), y = sum_sel w_e SwiGLU_e(h) + SwiGLU_shared(h); an expert is
SwiGLU D -> ``moe_ffn_dim`` -> D.

Departures from the source, each in the configuration's ``assumed``:
random int8 weights from a seed in place of the checkpoint (dequantised
here a layer and a block of experts at a time: a layer's 256 experts in
float32 are 3.2 GB); the gate one value a head (the config says only
``gating: true``); the router's score function (sigmoid) and selection
bias (seeded, std 0.01), which the config does not give; no q/k norm and
no softcap (the config names none). Every expert is computed for every
token and weighed by its combine weight, zero off the chosen: the same
sum as the chosen alone.

Router gap a position: over the routed layers, the smallest distance
between the selection score of the last expert kept and the best one
left out.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_BLOCK = 16   # experts dequantised at a time: 16 x 12.6 MB float32
KINDS = ("full", "window")


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


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """theta^(-2i/dim), and that over ``factor``, blended by a linear
    ramp between the dims that turn ``beta_fast`` and ``beta_slow`` times
    over the original context (the source library's
    ``_compute_yarn_parameters``)."""
    factor = float(scaling["factor"])
    orig = scaling["original_max_position_embeddings"]

    def dim_of(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_tables(cfg, kind: str, s: int):
    """(cos, sin) [S, rotated / 2] of a layer kind."""
    hd = cfg.attn_head_dim or cfg.dim // cfg.n_heads
    if kind == "window":
        theta = cfg.window_rope_theta or cfg.rope_theta
        inv, factor = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd), 1.0
    else:
        dim, scaling = cfg.rotary_dim or hd, cfg.rope_scaling
        if scaling:
            inv = yarn_inv_freq(dim, cfg.rope_theta, scaling)
            factor = scaling.get("attention_factor") \
                or 0.1 * math.log(scaling["factor"]) + 1.0
        else:
            inv = cfg.rope_theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
            factor = 1.0
    angle = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _rotate(x, cos, sin):
    """x [S, H, hd]: rotate-half over the first 2 * cos.shape[-1] values
    of each head, the rest as they are."""
    part = 2 * cos.shape[-1]
    x1, x2, rest = (x[..., :part // 2], x[..., part // 2:part],
                    x[..., part:])
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps",
                                   "window", "gated"))
def attention(lw, i, x, cos, sin, *, heads, kv_heads, hd, eps, window,
              gated):
    """One layer's attention: x [S, D] -> x + y. ``window`` 0: every
    earlier position; else the last ``window``, the token's own among
    them."""
    lw = _at(lw, i)
    s = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    q = _rotate((h @ _deq(lw["wq"])).reshape(s, heads, hd), cos, sin)
    k = _rotate((h @ _deq(lw["wk"])).reshape(s, kv_heads, hd), cos, sin)
    v = (h @ _deq(lw["wv"])).reshape(s, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    p, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= p
    if window:
        seen &= j > p - window
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v)
    if gated:
        o = o * jax.nn.sigmoid(h @ _deq(lw["head_gate"]))[:, :, None]
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


@partial(jax.jit, static_argnames=("k", "scale"))
def route(router, bias, i, h, *, k, scale):
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
    w = jnp.where(chosen, s, 0.0)
    return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale, gap


def routed_ffn(moe, cfg, i, h):
    """One routed layer's feed-forward: (y [S, D], gap [S])."""
    i_ = jnp.int32(i)
    combine, gap = route(moe["router"], moe["router_bias"], i_, h,
                         k=cfg.experts_per_token,
                         scale=float(cfg.routed_scaling))
    held = cfg.n_experts_held or cfg.n_experts
    stacks = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    y = _swiglu({"w_gate": moe["ws_gate"], "w_up": moe["ws_up"],
                 "w_down": moe["ws_down"]}, i_, h)
    for e0 in range(0, held, EXPERT_BLOCK):
        y = y + _expert_block(stacks, i_, jnp.int32(e0), combine, h,
                              n=min(EXPERT_BLOCK, held - e0))
    return y, gap


@partial(jax.jit, static_argnames=("eps", "tied"))
def _logprobs(final_norm, head, x, *, eps, tied):
    w = head.astype(F32).T if tied else _deq(head)
    return jax.nn.log_softmax(_rms(x, final_norm, eps) @ w, -1)


def forward_logprobs(params, cfg, tokens, rows, window_delta: int = 0):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and the smallest router gap over
    the routed layers at each of those positions [len(rows)].
    ``window_delta``: a test's control, the window one wider or
    narrower."""
    rows = jnp.asarray(rows)
    eps = float(cfg.norm_eps)
    pattern = tuple(cfg.layer_pattern)
    hd = cfg.attn_head_dim or cfg.dim // cfg.n_heads
    n_heads = {"full": cfg.n_heads,
               "window": cfg.window_heads or cfg.n_heads}
    band = {"full": 0, "window": cfg.window_size + window_delta}
    seen = dict.fromkeys(KINDS, 0)
    min_gap = None
    with jax.default_matmul_precision("highest"):
        rope = {k: rope_tables(cfg, k, len(tokens)) for k in KINDS}
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for layer in range(cfg.n_layers):
            kind = pattern[layer % len(pattern)]
            i = seen[kind]
            seen[kind] += 1
            x = attention(params[kind], jnp.int32(i), x, *rope[kind],
                          heads=n_heads[kind], kv_heads=cfg.n_kv_heads,
                          hd=hd, eps=eps, window=band[kind],
                          gated=bool(cfg.head_gate))
            if layer < cfg.n_dense_layers:
                dense = params["dense"]
                x = x + _swiglu(dense, jnp.int32(layer),
                                _rms(x, dense["ffn_norm"][layer], eps))
                continue
            j = layer - cfg.n_dense_layers
            moe = params["moe"]
            y, gap = routed_ffn(moe, cfg, j, _rms(x, moe["ffn_norm"][j], eps))
            x = x + y
            gap = gap[rows]
            min_gap = gap if min_gap is None else jnp.minimum(min_gap, gap)
        head = params["embedding"] if cfg.tie_embeddings \
            else params["lm_head"]
        return _logprobs(params["final_norm"], head, x[rows], eps=eps,
                         tied=cfg.tie_embeddings), min_gap
