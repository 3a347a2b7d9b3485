"""The plain float32 reference of the latent-attention family
(``model_type: deepseek_v3``; GigaChat3.1-702B-A36B's block), written out
from the published ``config.json`` and modelling code in straightforward
``jax.numpy``: no cache, no absorbed form, no batching, no kernel, no
dispatch. It imports nothing of ``gofr_tpu``.

Per layer, ``x`` the residual stream, RMSNorm before attention and
before the feed-forward:

  c_q = RMSNorm(x W_qa);  q = c_q W_qb, a head [q_nope | q_pe];
  q_pe = RoPE(q_pe)
  [c_kv | k_pe] = x W_kva;  c_kv = RMSNorm(c_kv);  k_pe = RoPE(k_pe),
  one for all heads
  [k_nope | v] = c_kv W_kvb a head               (EXPANDED: keys and
  values a head are materialised; the engine never does that over its
  cache, it absorbs W_kvb into the query and the output)
  s = (q_nope . k_nope + q_pe . k_pe) * sigma,
  sigma = (nope + rope)^-1/2 * (0.1 * mscale_all_dim * ln(factor) + 1)^2
  causal softmax in float32;  o = sum p v  ->  W_o

RoPE: YaRN, as the published DeepseekV3YarnRotaryEmbedding: inverse
frequencies theta^(-2i/d), and those over ``factor``, blended by the
linear ramp between the dims whose rotations over
``original_max_position_embeddings`` positions are ``beta_fast`` and
``beta_slow``; cos/sin scaled by mscale/mscale_all_dim's ratio (1
here). Pairing: halves of the rope dims as the projection gives them
(the configuration's ``assumed`` says why that is the published code up
to a permutation of seeded columns).

Feed-forward: the first ``n_dense_layers`` layers SwiGLU; the others
  s = sigmoid(x W_g) in float32 over all ``n_experts``; selection score
  s + bias; a group's score is the sum of its two largest; the
  ``topk_groups`` best groups stay; the top ``experts_per_token`` of
  s + bias inside them; weights s_i / sum s_j * routed_scaling (the
  bias selects, it does not weigh);
  y = sum over the chosen experts THIS CHIP HOLDS of w_i E_i(x)
      + the shared expert.
The parameter tree is the engine's own, and it is the chip's share:
``n_experts_held`` experts (ids 0..) a layer behind a router that is
``n_experts`` wide. What the absent experts would add is left out here
exactly as in the program (the configuration's ``deployment`` says what
that stands for); ``layer_share`` lets a test add the shares up to the
uncut layer.

Router gap a position: over the routed layers, the smallest distance
between the selection score of the last expert kept and the best one
left out inside the kept groups. With 256 experts it is small nearly
everywhere, which is why ``reference.py`` holds a sparse model to the
median.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None):
    """[dim // 2] inverse frequencies; plain RoPE without ``scaling``."""
    extra = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if not scaling:
        return jnp.asarray(extra, F32)
    factor = float(scaling["factor"])
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, F32)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(width: int, scaling: dict | None) -> float:
    s = width ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        s *= _mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return s


def _rope(x, inv_freq, table_scale: float):
    """x [S, heads, d]: rotate halves at positions 0..S-1."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    c = (jnp.cos(ang) * table_scale)[:, None, :]
    sn = (jnp.sin(ang) * table_scale)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _scaling(cfg):
    sc = getattr(cfg, "rope_scaling", None)
    return dict(sc) if sc else None


@partial(jax.jit, static_argnames=("heads", "rank", "dn", "dr", "dv",
                                   "theta", "eps", "scaling"))
def _attention(lw, i, x, *, heads, rank, dn, dr, dv, theta, eps, scaling):
    """One layer's attention half, expanded: x [S, D] -> (x + attn, the
    normed input of the feed-forward)."""
    scaling = dict(scaling) if scaling else None
    lw = _at(lw, i)
    s = x.shape[0]
    inv = yarn_inv_freq(dr, theta, scaling)
    tscale = 1.0
    if scaling:
        tscale = _mscale(scaling["factor"], scaling.get("mscale", 1)) \
            / _mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))
    h = _rms(x, lw["attn_norm"], eps)
    c_q = _rms(h @ _deq(lw["w_qa"]), lw["q_norm"], eps)
    q = (c_q @ _deq(lw["w_qb"])).reshape(s, heads, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], inv, tscale)
    kva = h @ _deq(lw["w_kva"])
    c_kv = _rms(kva[:, :rank], lw["kv_norm"], eps)
    k_pe = _rope(kva[:, None, rank:], inv, tscale)[:, 0]          # [S, dr]
    kv = (c_kv @ _deq(lw["w_kvb"])).reshape(s, heads, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) \
        * softmax_scale(dn + dr, scaling)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * dv)
    x = x + attn @ _deq(lw["wo"])
    return x, _rms(x, lw["ffn_norm"], eps)


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
    group = jnp.sum(jax.lax.top_k(sel.reshape(n, groups, per), 2)[0], -1)
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


@partial(jax.jit, static_argnames=("eps", "tied"))
def _logprobs(final_norm, head, x, *, eps, tied):
    w = head.astype(F32).T if tied else _deq(head)
    return jax.nn.log_softmax(_rms(x, final_norm, eps) @ w, -1)


_ATTN_KEYS = ("attn_norm", "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm",
              "w_kvb", "wo", "ffn_norm")


def forward_logprobs(params, cfg, tokens, rows):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and the smallest router gap
    over the routed layers at each of those positions [len(rows)]."""
    rows = jnp.asarray(rows)
    scaling = _scaling(cfg)
    attn_kw = dict(
        heads=cfg.n_heads, rank=cfg.kv_lora_rank, dn=cfg.qk_nope_head_dim,
        dr=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
        theta=float(cfg.rope_theta), eps=float(cfg.norm_eps),
        scaling=tuple(sorted(scaling.items())) if scaling else None)
    held = [(e, e) for e in range(cfg.n_experts_held or cfg.n_experts)]
    min_gap = None
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for stack, n in (("dense_layers", cfg.n_dense_layers),
                         ("layers", cfg.n_layers - cfg.n_dense_layers)):
            layers = params[stack]
            attn_w = {k: layers[k] for k in _ATTN_KEYS}
            for i in range(n):
                x, h = _attention(attn_w, jnp.int32(i), x, **attn_kw)
                if stack == "dense_layers":
                    x = x + _swiglu({k: layers[k] for k in
                                     ("w_gate", "w_up", "w_down")},
                                    (jnp.int32(i),), h)
                    continue
                y, gap = layer_share(layers, cfg, i, h, held)
                x = x + y
                gap = gap[rows]
                min_gap = gap if min_gap is None else jnp.minimum(min_gap,
                                                                  gap)
        head = params["embedding"] if cfg.tie_embeddings \
            else params["lm_head"]
        return _logprobs(params["final_norm"], head, x[rows],
                         eps=float(cfg.norm_eps),
                         tied=cfg.tie_embeddings), min_gap
