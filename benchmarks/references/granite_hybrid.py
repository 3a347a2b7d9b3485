"""The plain float32 reference of the state-space family's layer of two
halves (``model_type: granitemoehybrid``, dense; IBM
granite-4.0-h-micro's block), written out from the published
``config.json`` in straightforward ``jax.numpy``: a ``lax.scan`` over the
tokens for the recurrence (no chunk form), no cache, no kernel, no paired
rows. It imports nothing of ``gofr_tpu``.

``x`` is the residual stream, ``r`` ``residual_multiplier``, ``N`` an
RMSNorm with a weight of its own (``eps`` ``norm_eps``):

  x = embedding_multiplier E[token]
  for every layer l:
    x = x + r Mixer_l(N1_l(x))          ``layer_pattern[l]``: mamba | attn
    x = x + r W_out_l (silu(g) * h),    [g | h] = W_in_l N2_l(x)
  logits = (N_f(x) E^T) / logits_scaling          (the head is E, tied)

MAMBA (Mamba-2; H ``ssm_heads`` heads of P ``ssm_head_dim``, G
``ssm_groups`` groups, N ``ssm_state``; published 64 x 64, ONE group,
128):
  [z | xBC | dt] = W_in u, widths H P | H P + 2 G N | H;
  xBC_t = SiLU(sum_j k_j * xBC_{t-(W-1)+j} + b): causal, depthwise, over
  the last W = ``conv_kernel`` inputs, zeros before the first token;
  [x | B | C] = xBC, x_t [H, P], B_t, C_t [G, N], head h reads group
  h // (H / G);
  Delta_t = softplus(dt_t + dt_bias) [H];  a_t = exp(-exp(A_log) Delta_t);
  S_t[h] = a_t[h] S_{t-1}[h] + Delta_t[h] x_t[h] (x) B_t[g],  S_0 = 0,
  S [H, P, N] float32;
  y_t[h] = S_t[h] C_t[g] + D[h] x_t[h];
  o_t = RMSNorm_group(y_t * SiLU(z_t)): the gate BEFORE one RMS over each
  group's H P / G channels (all 4,096 at one group), a weight a channel;
  Mixer = W_out o_t.

ATTN: softmax(q k^T * attention_multiplier) causal over all positions
(the published 0.015625, NOT head_dim^-1/2; 0 in the configuration means
head_dim^-1/2), ``n_heads`` query heads on ``n_kv_heads`` KV heads of
``head_dim``, no bias, NO rotation (``position_embedding_type: nope``).

Departures from the published description, each the configuration's
``assumed``: the state, the decay and the step are float32 (published
bfloat16); no clamp on Delta; ``in_proj``'s order [z | xBC | dt].
The parameter tree is the engine's own (int8 leaves dequantised a layer
at a time); a dense model has no router and the gap is None.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _deq(leaf, bits=8):
    """float32 weights of a plain or int8 (w, per-output-channel scale)
    leaf; ``bits`` under 8 keeps an int8 weight's top bits alone (a
    control of the check: the configuration's weights are int8)."""
    if hasattr(leaf, "scale"):
        w = leaf.w
        if bits < 8:
            w = jnp.left_shift(jnp.right_shift(w, 8 - bits), 8 - bits)
        return w.astype(F32) * leaf.scale[..., None, :].astype(F32)
    return leaf.astype(F32)


def _at(tree, i):
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _conv(x, taps, bias):
    """x [S, C], taps [W, C]: y_t = sum_j taps[j] x_{t - (W-1) + j} + b."""
    w = taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((w - 1, x.shape[1]), F32), x])
    return sum(xp[j:j + x.shape[0]] * taps[j].astype(F32)
               for j in range(w)) + bias.astype(F32)


@partial(jax.jit, static_argnames=("heads", "p", "groups", "n", "eps",
                                   "state_dtype", "bits"))
def mamba_mixer(lw, i, u, *, heads, p, groups, n, eps, state_dtype=F32,
                bits=8):
    """One mamba layer's mixer on the normed stream u [S, D] -> [S, D],
    the recurrence a scan over the tokens. ``state_dtype``: the type the
    state is rounded to after every token (a control of the check: the
    configuration keeps it float32)."""
    lw = _at(lw, i)
    s = u.shape[0]
    hp, gn = heads * p, groups * n
    zxd = u @ _deq(lw["w_ssm_in"], bits)
    # (the program stores in_proj padded to whole rows of 128 columns;
    # what lies past dt is read by nothing)
    w = hp + hp + 2 * gn
    z, xbc, dt = zxd[:, :hp], zxd[:, hp:w], zxd[:, w:w + heads]
    xbc = jax.nn.silu(_conv(xbc, lw["conv"], lw["conv_bias"]))
    x = xbc[:, :hp].reshape(s, heads, p)
    b, c = (jnp.repeat(xbc[:, hp + j * gn:hp + (j + 1) * gn]
                       .reshape(s, groups, n), heads // groups, axis=1)
            for j in range(2))
    info = jnp.finfo(state_dtype)
    delta = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))       # [S, H]
    a = jnp.exp(-jnp.exp(lw["a_log"].astype(F32)) * delta)

    def token(S, xs):
        x_t, b_t, c_t, d_t, a_t = xs         # [H, P], [H, N] x 2, [H] x 2
        S = a_t[:, None, None] * S \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if state_dtype != F32:    # (a cast there and back is compiled away)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), F32),
                        (x, b, c, delta, a))
    y = y + lw["d_skip"].astype(F32)[:, None] * x
    o = (y.reshape(s, hp) * jax.nn.silu(z)).reshape(s, groups, hp // groups)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    return (o.reshape(s, hp) * lw["ssm_norm"].astype(F32)) \
        @ _deq(lw["w_ssm_out"], bits)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "scale",
                                   "bits"))
def attn_mixer(lw, i, u, *, heads, kv_heads, hd, scale, bits=8):
    """One attn layer's mixer, no rotation, the softmax at ``scale``:
    u [S, D] -> [S, D]."""
    lw = _at(lw, i)
    s = u.shape[0]
    q = (u @ _deq(lw["wq"], bits)).reshape(s, heads, hd)
    k = (u @ _deq(lw["wk"], bits)).reshape(s, kv_heads, hd)
    v = (u @ _deq(lw["wv"], bits)).reshape(s, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd)
    return attn @ _deq(lw["wo"], bits)


@partial(jax.jit, static_argnames=("f", "eps", "r", "bits"))
def feed_forward(lw, i, x, *, f, eps, r, bits=8):
    """A layer's second half: x + r W_out (silu(g) * h), [g | h] = W_in
    N2(x)."""
    lw = _at(lw, i)
    gh = _rms(x, lw["norm"], eps) @ _deq(lw["w_ffn_in"], bits)
    return x + r * ((jax.nn.silu(gh[:, :f]) * gh[:, f:])
                    @ _deq(lw["w_ffn_out"], bits))


@partial(jax.jit, static_argnames=("eps",))
def _norm(w, i, x, *, eps):
    return _rms(x, _at(w, i), eps)


@partial(jax.jit, static_argnames=("eps", "scaling"))
def _logprobs(final_norm, table, x, *, eps, scaling):
    logits = (_rms(x, final_norm, eps) @ table.astype(F32).T) / scaling
    return jax.nn.log_softmax(logits, -1)


def forward_logprobs(params, cfg, tokens, rows, state_dtype=F32,
                     weight_bits=8):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S]; no router, so no gap (None).
    ``state_dtype`` and ``weight_bits`` are the check's controls (the
    nearest precision under the configuration's float32 state and int8
    weights); ``run.py`` passes neither."""
    rows = jnp.asarray(rows)
    eps = float(cfg.norm_eps)
    r = float(cfg.residual_multiplier)
    scale = float(cfg.attention_multiplier) or cfg.head_dim ** -0.5
    if not cfg.tie_embeddings:
        raise ValueError("this reference's head is the embedding table")
    seen = {"mamba": 0, "attn": 0}
    with jax.default_matmul_precision("highest"):
        x = float(cfg.embedding_multiplier) \
            * params["embedding"][jnp.asarray(tokens)].astype(F32)
        for layer, kind in enumerate(cfg.layer_pattern):
            i = jnp.int32(seen[kind])
            seen[kind] += 1
            u = _norm(params["norm"], jnp.int32(layer), x, eps=eps)
            if kind == "mamba":
                y = mamba_mixer(params["mamba"], i, u, heads=cfg.ssm_heads,
                                p=cfg.ssm_head_dim, groups=cfg.ssm_groups,
                                n=cfg.ssm_state, eps=eps,
                                state_dtype=state_dtype, bits=weight_bits)
            else:
                y = attn_mixer(params["attn"], i, u, heads=cfg.n_heads,
                               kv_heads=cfg.n_kv_heads, hd=cfg.head_dim,
                               scale=scale, bits=weight_bits)
            x = feed_forward(params["ffn"], jnp.int32(layer), x + r * y,
                             f=cfg.ffn_dim, eps=eps, r=r, bits=weight_bits)
        return _logprobs(params["final_norm"], params["embedding"], x[rows],
                         eps=eps, scaling=float(cfg.logits_scaling)), None
