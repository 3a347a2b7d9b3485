"""The plain float32 reference of the hybrid family (``model_type:
solar_open2``; Solar-Open2-250B's block), written out from the published
``config.json`` in straightforward ``jax.numpy``: a ``lax.scan`` over the
tokens for the recurrence, no cache, no chunking, no kernel, no
dispatch. It imports nothing of ``gofr_tpu``.

``x`` is the residual stream, RMSNorm (``rms_norm_eps``) before each
half: ``x += Mixer(RMSNorm(x)); x += MoE(RMSNorm(x))``. Layer ``l`` is of
kind ``layer_pattern[l % period]`` (published: ``gqa_layers`` 0, 4, 8, ...
are full, the three between linear).

Linear layer (gated delta rule, ``linear_heads`` heads, d_k = d_v =
``linear_head_dim``; one head written out):
  q_t, k_t = L2Norm(SiLU(Conv(W_q x)_t)), likewise k;
  v_t = SiLU(Conv(W_v x)_t): a causal depthwise convolution over the
  last ``conv_kernel`` inputs, a channel at a time, zeros before the
  first token;
  alpha_t = exp(-exp(A) softplus(W_a2 W_a1 x_t + b)) in (0, 1)^d_k, a
  decay A CHANNEL OF THE KEY (A a head);
  beta_t = 2 sigmoid(W_b x_t) in (0, 2), a head (kda_allow_neg_eigval);
  S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
  S_0 = 0, S in R^{d_k x d_v}, float32;
  o_t = S_t^T (q_t / sqrt(d_k));
  y_t = W_o (sigmoid(W_g2 W_g1 x_t) * RMSNorm_head(o_t)).

Full layer: softmax(q k^T / sqrt(head_dim)) causal over all positions,
``n_heads`` query heads on ``n_kv_heads`` KV heads, NO rotation
(``use_rope`` false); y_t = W_o (sigmoid(W_gate x_t) * attn_t)
(``use_gqa_gate``).

MoE, every layer: s = sigmoid(x W_r) in float32 over all ``n_experts``;
the top ``experts_per_token`` of s + bias (one group); weights
s_i / sum s_j * routed_scaling (``norm_topk_prob``; the bias selects, it
does not weigh); y = sum over the chosen experts THIS CHIP HOLDS of
w_i E_i(x) + the shared expert; an expert is SwiGLU D -> moe_ffn_dim ->
D. The parameter tree is the engine's own and is the chip's share:
``n_experts_held`` experts (ids 0..) behind a router ``n_experts`` wide;
what the absent ones would add is left out here exactly as in the
program. ``layer_share`` lets a test add the shares up to the uncut
layer.

What the published config does not give and the configuration's
``assumed`` lists: the router's score function and bias, the rank of
the two low-rank projections, the form of the two gates, L2Norm's
epsilon (1e-6), A a head.

Router gap a position: over all layers, the smallest distance between
the selection score of the last expert kept and the best one left out.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6


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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _conv(x, taps):
    """x [S, C], taps [W, C]: y_t = sum_j taps[j] x_{t - (W-1) + j}."""
    w = taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((w - 1, x.shape[1]), F32), x])
    return sum(xp[j:j + x.shape[0]] * taps[j].astype(F32) for j in range(w))


@partial(jax.jit, static_argnames=("heads", "d", "eps"))
def linear_mixer(lw, i, x, *, heads, d, eps):
    """One linear layer's mixer: x [S, D] -> (x + y, the normed input of
    the feed-forward), the recurrence a scan over the tokens."""
    lw = _at(lw, i)
    s = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    n = heads * d
    taps = lw["conv"].astype(F32)
    q, k, v = (jax.nn.silu(_conv(h @ _deq(lw[name]),
                                 taps[:, j * n:(j + 1) * n]))
               .reshape(s, heads, d)
               for j, name in enumerate(("wq", "wk", "wv")))
    q, k = _l2(q) / jnp.sqrt(F32(d)), _l2(k)
    a = (h @ _deq(lw["a_down"])) @ _deq(lw["a_up"]) + lw["a_bias"]
    alpha = jnp.exp(-jnp.exp(lw["a_log"].astype(F32))[:, None]
                    * jax.nn.softplus(a.reshape(s, heads, d)))
    beta = 2.0 * jax.nn.sigmoid(h @ _deq(lw["w_beta"]))       # [S, H]

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs                  # [H, d] ..., [H]
        S = a_t[:, :, None] * S                       # Diag(alpha) S
        kS = jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - kS)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), F32),
                        (q, k, v, alpha, beta))
    o = _rms(o, lw["o_norm"], eps)
    gate = jax.nn.sigmoid((h @ _deq(lw["g_down"])) @ _deq(lw["g_up"]))
    x = x + (o.reshape(s, n) * gate) @ _deq(lw["wo"])
    return x, _rms(x, lw["ffn_norm"], eps)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps",
                                   "gated"))
def full_mixer(lw, i, x, *, heads, kv_heads, hd, eps, gated):
    """One full layer's mixer, no rotation: x [S, D] -> (x + y, the
    normed input of the feed-forward)."""
    lw = _at(lw, i)
    s = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    q = (h @ _deq(lw["wq"])).reshape(s, heads, hd)
    k = (h @ _deq(lw["wk"])).reshape(s, kv_heads, hd)
    v = (h @ _deq(lw["wv"])).reshape(s, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd)
    if gated:
        attn = attn * jax.nn.sigmoid(h @ _deq(lw["w_attn_gate"]))
    x = x + attn @ _deq(lw["wo"])
    return x, _rms(x, lw["ffn_norm"], eps)


@jax.jit
def _swiglu(lw, idx, h):
    """SwiGLU of the shared expert (idx = (layer,)) or one routed expert
    (idx = (layer, expert))."""
    lw = _at(lw, *idx)
    return (jax.nn.silu(h @ _deq(lw["w_gate"])) * (h @ _deq(lw["w_up"]))) \
        @ _deq(lw["w_down"])


@partial(jax.jit, static_argnames=("k", "scale"))
def route(router, bias, i, h, *, k, scale):
    """([S, E] combine weights over ALL experts, zero off the chosen k;
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


def layer_share(layers, cfg, i, h, held, shared: bool = True):
    """One layer's feed-forward output for the experts ``held`` (an
    iterable of (global expert id, index in the parameter stack)), with
    or without the shared expert: the partial sum a chip that holds
    those experts computes. Returns (y [S, D], gap [S])."""
    i_ = jnp.int32(i)
    combine, gap = route(layers["router"], layers["router_bias"], i_, h,
                         k=cfg.experts_per_token,
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


_FFN_KEYS = ("router", "router_bias", "w_gate", "w_up", "w_down",
             "ws_gate", "ws_up", "ws_down")


def forward_logprobs(params, cfg, tokens, rows):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and the smallest router gap
    over the layers at each of those positions [len(rows)]."""
    rows = jnp.asarray(rows)
    eps = float(cfg.norm_eps)
    held = [(e, e) for e in range(cfg.n_experts_held or cfg.n_experts)]
    pattern = tuple(cfg.layer_pattern)
    seen = {"full": 0, "linear": 0}
    min_gap = None
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for layer in range(cfg.n_layers):
            kind = pattern[layer % len(pattern)]
            i, stack = seen[kind], params[kind]
            seen[kind] += 1
            mixer_w = {k: v for k, v in stack.items() if k not in _FFN_KEYS}
            if kind == "linear":
                x, h = linear_mixer(mixer_w, jnp.int32(i), x,
                                    heads=cfg.linear_heads,
                                    d=cfg.linear_head_dim, eps=eps)
            else:
                x, h = full_mixer(mixer_w, jnp.int32(i), x,
                                  heads=cfg.n_heads,
                                  kv_heads=cfg.n_kv_heads, hd=cfg.head_dim,
                                  eps=eps, gated=bool(cfg.attn_gate))
            y, gap = layer_share(stack, cfg, i, h, held)
            x = x + y
            gap = gap[rows]
            min_gap = gap if min_gap is None else jnp.minimum(min_gap, gap)
        head = params["embedding"] if cfg.tie_embeddings \
            else params["lm_head"]
        return _logprobs(params["final_norm"], head, x[rows], eps=eps,
                         tied=cfg.tie_embeddings), min_gap
