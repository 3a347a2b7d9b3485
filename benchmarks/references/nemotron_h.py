"""The plain float32 reference of the state-space family (``model_type:
nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B's block), written out
from the published ``config.json`` in straightforward ``jax.numpy``: a
``lax.scan`` over the tokens for the recurrence (no chunk form), no
cache, no kernel, no dispatch. It imports nothing of ``gofr_tpu``.

``x`` is the residual stream. A layer is ONE block behind one RMSNorm
(``eps`` ``norm_eps``): ``x += Block_l(RMSNorm(x))``, and
``layer_pattern[l]`` names its kind (published:
``hybrid_override_pattern``, ``M`` mamba, ``E`` moe, ``*`` attn).

MAMBA (Mamba-2; H ``ssm_heads`` heads of P ``ssm_head_dim``, G
``ssm_groups`` groups, N ``ssm_state``):
  [z | xBC | dt] = W_in u, widths H P | H P + 2 G N | H;
  xBC_t = SiLU(sum_j k_j * xBC_{t-(W-1)+j} + b): causal, depthwise, over
  the last W = ``conv_kernel`` inputs, zeros before the first token;
  [x | B | C] = xBC, x_t [H, P], B_t, C_t [G, N], head h reads group
  h // (H / G);
  Delta_t = softplus(dt_t + dt_bias) [H];  a_t = exp(-exp(A_log) Delta_t);
  S_t[h] = a_t[h] S_{t-1}[h] + Delta_t[h] x_t[h] (x) B_t[g],  S_0 = 0,
  S [H, P, N] float32;
  y_t[h] = S_t[h] C_t[g] + D[h] x_t[h];
  o_t = RMSNorm_group(y_t * SiLU(z_t)): one RMS over each group's
  H P / G channels, a weight a channel;  Block = W_out o_t.

ATTN: softmax(q k^T / sqrt(head_dim)) causal over all positions,
``n_heads`` query heads on ``n_kv_heads`` KV heads, no bias, NO rotation
(``use_rope`` false; the configuration's ``assumed``).

MOE: s = sigmoid(u W_r) in float32 over all ``n_experts``; the top
``experts_per_token`` of s + bias (one group); weights
s_i / sum s_j * routed_scaling (the bias selects, it does not weigh);
l = W_down_latent u (``dim`` -> ``moe_latent_dim``); an expert is
E_e(l) = W2_e relu(W1_e l)^2 (``relu2``: no gate, two matrices);
Block = W_up_latent (sum over the chosen experts THIS CHIP HOLDS of
w_e E_e(l)) + Ws2 relu(Ws1 u)^2, the shared expert on the full width.
The parameter tree is the engine's own and is the chip's share:
``n_experts_held`` experts (ids 0..) behind a router ``n_experts`` wide;
what the absent ones would add is left out here exactly as in the
program. ``layer_share`` lets a test add the shares up to the uncut
layer. The held experts run ``EXPERT_BLOCK`` at a time, dequantised a
block at a time: 128 float32 experts of 1,024 x 2,688 x 2 are 2.8 GB a
layer whole, beside a serving engine that fills the chip.

Router gap a position: over the moe layers, the smallest distance
between the selection score of the last expert kept and the best one
left out.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_BLOCK = 16


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


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def _conv(x, taps, bias):
    """x [S, C], taps [W, C]: y_t = sum_j taps[j] x_{t - (W-1) + j} + b."""
    w = taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((w - 1, x.shape[1]), F32), x])
    return sum(xp[j:j + x.shape[0]] * taps[j].astype(F32)
               for j in range(w)) + bias.astype(F32)


@partial(jax.jit, static_argnames=("heads", "p", "groups", "n", "eps"))
def mamba_block(lw, i, u, *, heads, p, groups, n, eps):
    """One mamba layer's block on the normed stream u [S, D] -> [S, D],
    the recurrence a scan over the tokens."""
    lw = _at(lw, i)
    s = u.shape[0]
    hp, gn = heads * p, groups * n
    zxd = u @ _deq(lw["w_ssm_in"])
    z, xbc, dt = zxd[:, :hp], zxd[:, hp:hp + hp + 2 * gn], zxd[:, -heads:]
    xbc = jax.nn.silu(_conv(xbc, lw["conv"], lw["conv_bias"]))
    x = xbc[:, :hp].reshape(s, heads, p)
    # head h reads group h // (H / G)
    b, c = (jnp.repeat(xbc[:, hp + j * gn:hp + (j + 1) * gn]
                       .reshape(s, groups, n), heads // groups, axis=1)
            for j in range(2))
    delta = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))       # [S, H]
    a = jnp.exp(-jnp.exp(lw["a_log"].astype(F32)) * delta)

    def token(S, xs):
        x_t, b_t, c_t, d_t, a_t = xs         # [H, P], [H, N] x 2, [H] x 2
        S = a_t[:, None, None] * S \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), F32),
                        (x, b, c, delta, a))
    y = y + lw["d_skip"].astype(F32)[:, None] * x
    o = (y.reshape(s, hp) * jax.nn.silu(z)).reshape(s, groups, hp // groups)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    return (o.reshape(s, hp) * lw["ssm_norm"].astype(F32)) \
        @ _deq(lw["w_ssm_out"])


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd"))
def attn_block(lw, i, u, *, heads, kv_heads, hd):
    """One attn layer's block, no rotation: u [S, D] -> [S, D]."""
    lw = _at(lw, i)
    s = u.shape[0]
    q = (u @ _deq(lw["wq"])).reshape(s, heads, hd)
    k = (u @ _deq(lw["wk"])).reshape(s, kv_heads, hd)
    v = (u @ _deq(lw["wv"])).reshape(s, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd)
    return attn @ _deq(lw["wo"])


@partial(jax.jit, static_argnames=("k", "scale"))
def route(router, bias, i, u, *, k, scale):
    """([S, E] combine weights over ALL experts, zero off the chosen k;
    [S] gap of the selection score between the last kept and the best
    left out)."""
    s = jax.nn.sigmoid(u @ _at(router, i).astype(F32))
    sel = s + _at(bias, i).astype(F32)
    topv, topi = jax.lax.top_k(sel, k + 1)
    gap = topv[:, k - 1] - topv[:, k]
    n, e = s.shape
    chosen = jnp.zeros((n, e), bool).at[
        jnp.arange(n)[:, None], topi[:, :k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale, gap


@jax.jit
def _experts(lw, i, local, lat, weights):
    """sum over the experts ``local`` (indices in the stack) of
    weights[:, j] * W2 relu(W1 lat)^2: lat [S, Dl], weights [S, len]."""
    lw = _at(lw, i)
    up, down = (_deq(jax.tree_util.tree_map(lambda a: a[local], lw[name]))
                for name in ("w_up", "w_down"))
    h = jnp.square(jax.nn.relu(jnp.einsum("sd,edf->esf", lat, up)))
    return jnp.einsum("esf,efd,se->sd", h, down, weights)


@jax.jit
def _dense(w, i, h):
    return h @ _deq(_at(w, i))


@jax.jit
def _shared(lw, i, u):
    lw = _at(lw, i)
    return _relu2(u, _deq(lw["ws_up"]), _deq(lw["ws_down"]))


def layer_share(layers, cfg, i, u, held, shared: bool = True):
    """One moe layer's block for the experts ``held`` (an iterable of
    (global expert id, index in the parameter stack)), with or without
    the shared expert: the partial sum a chip that holds those experts
    computes. Returns (y [S, D], gap [S])."""
    i_ = jnp.int32(i)
    combine, gap = route(layers["router"], layers["router_bias"], i_, u,
                         k=cfg.experts_per_token,
                         scale=float(cfg.routed_scaling))
    held = list(held)
    lat = _dense(layers["w_latent_down"], i_, u)
    m = jnp.zeros_like(lat)
    stacks = {k: layers[k] for k in ("w_up", "w_down")}
    for j in range(0, len(held), EXPERT_BLOCK):
        block = held[j:j + EXPERT_BLOCK]
        m = m + _experts(stacks, i_, jnp.asarray([b[1] for b in block]), lat,
                         combine[:, jnp.asarray([b[0] for b in block])])
    y = _dense(layers["w_latent_up"], i_, m)
    if shared:
        y = y + _shared({k: layers[k] for k in ("ws_up", "ws_down")}, i_, u)
    return y, gap


@partial(jax.jit, static_argnames=("eps",))
def _norm(w, i, x, *, eps):
    return _rms(x, _at(w, i), eps)


@partial(jax.jit, static_argnames=("eps", "tied"))
def _logprobs(final_norm, head, x, *, eps, tied):
    w = head.astype(F32).T if tied else _deq(head)
    return jax.nn.log_softmax(_rms(x, final_norm, eps) @ w, -1)


def forward_logprobs(params, cfg, tokens, rows):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and the smallest router gap
    over the moe layers at each of those positions [len(rows)]."""
    rows = jnp.asarray(rows)
    eps = float(cfg.norm_eps)
    held = [(e, e) for e in range(cfg.n_experts_held or cfg.n_experts)]
    seen = {"mamba": 0, "moe": 0, "attn": 0}
    min_gap = None
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for layer, kind in enumerate(cfg.layer_pattern):
            i = seen[kind]
            seen[kind] += 1
            u = _norm(params["norm"], jnp.int32(layer), x, eps=eps)
            if kind == "mamba":
                y = mamba_block(params["mamba"], jnp.int32(i), u,
                                heads=cfg.ssm_heads, p=cfg.ssm_head_dim,
                                groups=cfg.ssm_groups, n=cfg.ssm_state,
                                eps=eps)
            elif kind == "attn":
                y = attn_block(params["attn"], jnp.int32(i), u,
                               heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                               hd=cfg.head_dim)
            else:
                y, gap = layer_share(params["moe"], cfg, i, u, held)
                gap = gap[rows]
                min_gap = gap if min_gap is None \
                    else jnp.minimum(min_gap, gap)
            x = x + y
        head = params["embedding"] if cfg.tie_embeddings \
            else params["lm_head"]
        return _logprobs(params["final_norm"], head, x[rows], eps=eps,
                         tied=cfg.tie_embeddings), min_gap
