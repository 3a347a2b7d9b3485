"""The comparison that decides the numerical half of ``correct``, which is
the same code for every configuration (``compare``, ``judge``), and the
plain reference of the DEFAULT family: the forward pass of a decoder of
Mistral's and Mixtral's shape in straightforward float32 ``jax.numpy``,
with no cache, no batching and no kernel.

Another family's forward pass (latent attention, a router that is not
softmax top-k, a shared expert, layers of two kinds, window layers) is a
file of its own, ``benchmarks/references/<family>.py``, which gives
``forward_logprobs(params, cfg, tokens, rows)`` with this file's
signature and contract; its configuration names it under ``reference``
as ``"module": "references/<family>.py"``, and ``run.py`` hands it to
``compare``. A configuration that names none gets this file's.

Equations as published (Mistral-7B: Jiang et al. 2023; Mixtral: Jiang et
al. 2024, and the models' Hugging Face implementations): token embedding;
per layer RMSNorm -> grouped-query causal attention with rotary position
embedding (the rotate-half convention) -> residual -> RMSNorm -> SwiGLU
feed-forward, or for a sparse layer a softmax router over all experts,
the top ``experts_per_token`` renormalised, and the weighted sum of those
experts' SwiGLU outputs -> residual; final RMSNorm; output projection.

It reads the engine's own parameter tree (random int8 weights from the
seed, stacked [L, ...]) and dequantises ONE layer's projections, or one
expert's, at a time, so it fits beside the serving engine on the chip; on
a mesh the arrays stay sharded and XLA partitions these plain programs.
``default_matmul_precision("highest")``: a TPU otherwise multiplies
float32 in bfloat16.

The comparison. The engine serves each seeded prompt greedily (prefill,
or for the prompt past the largest bucket the chunked path, then fused
decode steps) and reports each token with its log-probability. The
reference is teacher-forced on prompt + those tokens and gives float32
log-probabilities at the same positions. Two numbers per token:
  - |engine logprob - reference logprob of the same token|, and
  - reference max logprob - reference logprob of the engine's token
    (0 when both pick the same token; small when the engine's bf16/int8
    arithmetic broke a near-tie the other way).
The configuration (``configs/<name>.json``, key ``reference``) names the
``statistic`` over the compared positions, "worst" or "median", that must
stay under its ``tolerance_nats`` for both numbers, with the measurement
it was set from: a gain bought with less precision than the configuration
states (a narrower KV cache, narrower matmuls) has to fail here.

A dense model is held to the worst position. A sparse model cannot be:
its router sits on a near-tie somewhere in nearly every token (the gap
between the router logit of the last expert kept and the first left out,
smallest over 32 layers, had a median of 0.007 nats on the chip), so the
engine's bfloat16 and this float32 pick different experts about once a
token, each time moving a layer's output by a whole expert's weighted
contribution, and through the KV cache every later token a little. That
is not precision lost, and it reads 0.5 to 1.1 nats at the worst of 96
positions. Until the program can report the experts its serving path chose
(PERF.md, Open questions), so that the reference can be forced onto them,
a sparse model is held to the median. Each position's record carries its
router gap, so the reason stays checkable.
"""

from __future__ import annotations

import math
import random
import statistics
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD = 80  # reference sequences are padded to a multiple: few shapes to compile
F32 = jnp.float32


def _deq(leaf):
    """float32 weights of a plain or int8 (w, per-output-channel scale)
    leaf, whatever its leading axes."""
    if hasattr(leaf, "scale"):
        return leaf.w.astype(F32) * leaf.scale[..., None, :].astype(F32)
    return leaf.astype(F32)


def _at(tree, *idx):
    """tree[idx...] for traced indices, over every array of the tree."""
    def one(x):
        for i in idx:
            x = jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
        return x
    return jax.tree_util.tree_map(one, tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [S, heads, hd]: rotate-half rotary embedding at positions 0..S-1."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps"))
def _attention(lw, i, x, *, heads, kv_heads, theta, eps):
    """One layer's attention half: x [S, D] -> (x + attn, normed input of
    the feed-forward)."""
    lw = _at(lw, i)
    s, d = x.shape
    hd = d // heads
    h = _rms(x, lw["attn_norm"], eps)
    q = _rope((h @ _deq(lw["wq"])).reshape(s, heads, hd), theta)
    k = _rope((h @ _deq(lw["wk"])).reshape(s, kv_heads, hd), theta)
    v = (h @ _deq(lw["wv"])).reshape(s, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd)
    x = x + attn @ _deq(lw["wo"])
    return x, _rms(x, lw["ffn_norm"], eps)


@jax.jit
def _swiglu(lw, idx, h):
    """SwiGLU of one dense layer (idx = (layer,)) or one expert
    (idx = (layer, expert))."""
    lw = _at(lw, *idx)
    return (jax.nn.silu(h @ _deq(lw["w_gate"])) * (h @ _deq(lw["w_up"]))) \
        @ _deq(lw["w_down"])


@partial(jax.jit, static_argnames=("k",))
def _route(router, i, h, *, k):
    """([S, E] combine weights: softmax over all experts, top-k kept and
    renormalised, zero elsewhere; [S] gap: the router logit of the last
    expert kept minus that of the first left out)."""
    probs = jax.nn.softmax(h @ _at(router, i).astype(F32), -1)
    topv, topi = jax.lax.top_k(probs, k + 1)
    gap = jnp.log(topv[:, k - 1]) - jnp.log(topv[:, k])
    topv, topi = topv[:, :k], topi[:, :k]
    topv = topv / jnp.sum(topv, -1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(topi, probs.shape[-1], dtype=F32)
                      * topv[..., None], axis=1)
    return combine, gap


@partial(jax.jit, static_argnames=("eps", "tied"))
def _logprobs(final_norm, head, x, *, eps, tied):
    w = head.astype(F32).T if tied else _deq(head)
    return jax.nn.log_softmax(_rms(x, final_norm, eps) @ w, -1)


def forward_logprobs(params, cfg, tokens, rows):
    """float32 log-probabilities [len(rows), V] of the next token after
    positions ``rows`` of ``tokens`` [S], and for a sparse model the
    smallest router gap over the layers at each of those positions
    [len(rows)] (None for a dense model)."""
    layers = params["layers"]
    attn_w = {k: layers[k] for k in
              ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm")}
    ffn_w = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
    rows = jnp.asarray(rows)
    min_gap = None
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(tokens)].astype(F32)
        for i in range(cfg.n_layers):
            i_ = jnp.int32(i)
            x, h = _attention(attn_w, i_, x, heads=cfg.n_heads,
                              kv_heads=cfg.n_kv_heads,
                              theta=float(cfg.rope_theta),
                              eps=float(cfg.norm_eps))
            if cfg.n_experts:
                combine, gap = _route(layers["router"], i_, h,
                                      k=cfg.experts_per_token)
                gap = gap[rows]
                min_gap = gap if min_gap is None else jnp.minimum(min_gap, gap)
                for e in range(cfg.n_experts):
                    x = x + combine[:, e:e + 1] * _swiglu(
                        ffn_w, (i_, jnp.int32(e)), h)
            else:
                x = x + _swiglu(ffn_w, (i_,), h)
        head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
        return _logprobs(params["final_norm"], head, x[rows],
                         eps=float(cfg.norm_eps),
                         tied=cfg.tie_embeddings), min_gap


def compare(generator, seed: int, spec: dict,
            forward=forward_logprobs) -> dict:
    """Serve the seeded prompts of ``spec`` (a configuration's
    ``reference`` entry) through the engine and hold each generated
    token's log-probability against ``forward``, the reference of the
    configuration's family. Returns ``judge``'s verdict, with every
    position's record under ``positions``."""
    cfg = generator.cfg
    new = int(spec["new_tokens"])
    rng = random.Random(f"{seed}/reference")
    positions = []
    for n in spec["prompt_tokens"]:
        prompt = [rng.randrange(1, cfg.vocab_size) for _ in range(n)]
        served = [(int(t), float(lp)) for t, lp in generator.generate(
            prompt, max_new_tokens=new, logprobs=True)]
        if len(served) != new:
            raise AssertionError(f"the engine returned {len(served)} tokens "
                                 f"for {new}")
        seq = prompt + [t for t, _ in served[:-1]]
        seq += [0] * (math.ceil(len(seq) / PAD) * PAD - len(seq))
        ref, gaps = forward(generator.params, cfg, seq,
                            range(n - 1, n - 1 + new))
        ref = np.asarray(ref)
        gaps = None if gaps is None else np.asarray(gaps)
        for j, (tok, lp) in enumerate(served):
            gap = None if gaps is None else float(gaps[j])
            positions.append({
                "prompt": n, "step": j,
                "logprob_err": abs(lp - float(ref[j, tok])),
                "top1_margin": float(ref[j].max() - ref[j, tok]),
                "router_gap": gap})
    return judge(positions, spec)


def judge(positions: list[dict], spec: dict) -> dict:
    """Hold ``spec["statistic"]`` ("worst" or "median") of the two errors
    over the positions to ``spec["tolerance_nats"]``. Both statistics are
    reported."""
    out = {"statistic": spec["statistic"],
           "tolerance_nats": float(spec["tolerance_nats"])}
    for name, stat in (("worst", max), ("median", statistics.median)):
        out[name] = {"logprob_err_nats": stat(p["logprob_err"]
                                              for p in positions),
                     "top1_margin_nats": stat(p["top1_margin"]
                                              for p in positions)}
    out["ok"] = max(out[spec["statistic"]].values()) <= out["tolerance_nats"]
    out["positions"] = positions
    return out
