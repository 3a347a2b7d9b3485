"""The plain float32 reference of the looped family (``model_type: ouro``;
Ouro-2.6B's block), written out from the published ``config.json`` and
the source library's modelling file in straightforward ``jax.numpy``:
whole sequences, no cache, no bucket, no chunk, no kernel, the loop as
two Python ``for``s. It imports nothing of ``gofr_tpu``.

``L = n_layers``, ``T = loop_steps`` (published ``total_ut_steps``),
``N(.; w)`` RMSNorm (``norm_eps``) with a weight of its own:

  x = E[token]
  for t in 0 .. T - 1:                  # layer l's weights whatever t
      for l in 0 .. L - 1:
          a = Attn_l(N(x; attn_norm_l));  x = x + N(a; input_layernorm_2_l)
          m = MLP_l(N(x; ffn_norm_l));    x = x + N(m; post_attention_layernorm_2_l)
      x = N(x; final_norm)              # pass t's output AND pass t + 1's input
  log-probabilities = log_softmax(x W_head)     # the last pass's x, normed once

Attn, H = ``n_heads`` on ``n_kv_heads`` KV heads of ``head_dim`` hd, no
bias: q = u W_q, k = u W_k, v = u W_v; rotate-half RoPE over the whole
head at ``rope_theta``, the same position in every pass; scores
q_p . k_j / sqrt(hd) in float32 over j <= p, softmax, o = sum a v; W_o.
The keys and values a pass attends over are THAT pass's (the serving
path keeps one table a (pass, layer)). MLP: W_down (silu(u W_gate) * u
W_up). The exit gate (``early_exit_gate``) enters nothing: at the
published ``early_exit_threshold`` of 1 every token leaves at the last
pass.

Departures from the source, each in the configuration's ``assumed``:
random int8 weights from a seed in place of the checkpoint (dequantised
here a layer at a time), norm weights drawn within a tenth of their mean
(1; an eighth for the two inside the residual branches, where a loop on
random weights stops amplifying what enters a pass), the order of the
four norms and the final norm between passes as the source library has
them, no bias, the gate not evaluated.

``control``: a test's one departure from the above, which the engine
must NOT agree with: ``three_passes`` (one pass fewer: a forward that
does not loop as often), ``pass0_rows`` (every pass attends over pass
0's keys and values of the same layer: a cache whose tables are the
depth).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("three_passes", "pass0_rows")


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


def _rotate(x, theta):
    """x [S, H, hd]: rotate-half over the whole head, positions 0..S-1."""
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    c, sn = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "theta", "eps"))
def layer(lw, i, x, kv_given, *, heads, kv_heads, hd, theta, eps):
    """Layer ``i`` over x [S, D]: (x after both halves, (k, v) this pass
    made). ``kv_given``: keys and values to attend over in their place,
    or None."""
    lw = _at(lw, i)
    s = x.shape[0]
    u = _rms(x, lw["attn_norm"], eps)
    q = _rotate((u @ _deq(lw["wq"])).reshape(s, heads, hd), theta)
    k = _rotate((u @ _deq(lw["wk"])).reshape(s, kv_heads, hd), theta)
    v = (u @ _deq(lw["wv"])).reshape(s, kv_heads, hd)
    k_a, v_a = (k, v) if kv_given is None else kv_given
    group = heads // kv_heads
    k_a, v_a = jnp.repeat(k_a, group, axis=1), jnp.repeat(v_a, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k_a) / jnp.sqrt(F32(hd))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    a = jnp.einsum("hqk,khd->qhd", probs, v_a).reshape(s, heads * hd) \
        @ _deq(lw["wo"])
    x = x + _rms(a, lw["input_layernorm_2"], eps)
    u = _rms(x, lw["ffn_norm"], eps)
    m = (jax.nn.silu(u @ _deq(lw["w_gate"])) * (u @ _deq(lw["w_up"]))) \
        @ _deq(lw["w_down"])
    return x + _rms(m, lw["post_attention_layernorm_2"], eps), (k, v)


@partial(jax.jit, static_argnames=("tied",))
def _logprobs(head, h, *, tied):
    w = head.astype(F32).T if tied else _deq(head)
    return jax.nn.log_softmax(h @ w, -1)


def forward_logprobs(params, cfg, tokens, rows, control: str = ""):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and None (a dense model has no
    router gap)."""
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    rows = jnp.asarray(rows)
    eps = float(cfg.norm_eps)
    hd = cfg.attn_head_dim or cfg.dim // cfg.n_heads
    passes = cfg.loop_steps - (control == "three_passes")
    first = {}                      # pass 0's (k, v) a layer
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for t in range(passes):
            for l in range(cfg.n_layers):
                given = first[l] if control == "pass0_rows" and t else None
                x, kv = layer(params["layers"], jnp.int32(l), x, given,
                              heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                              hd=hd, theta=float(cfg.rope_theta), eps=eps)
                if control == "pass0_rows" and t == 0:
                    first[l] = kv
            x = _rms(x, params["final_norm"], eps)
        head = params["embedding"] if cfg.tie_embeddings \
            else params["lm_head"]
        return _logprobs(head, x[rows], tied=cfg.tie_embeddings), None
