"""The hybrid family (``model_type: solar_open2``): layers of two kinds in
one stack, a recurrent state beside KV rows in one cache.

The generator picks this module where ``cfg.layer_pattern`` names a
``"linear"`` layer (``models.family``) and calls it through the same
entry points as ``models/llama.py``. ``cfg.layer_pattern`` is one period
of the stack, e.g. ``("full", "linear", "linear", "linear")``; layer
``l`` is of kind ``pattern[l % len(pattern)]``. ``x`` is the residual
stream, pre-norm blocks: ``x += Mixer(RMSNorm(x)); x += MoE(RMSNorm(x))``.

  - a FULL layer is softmax attention over every cached position,
    ``n_heads`` query heads on ``n_kv_heads`` KV heads of ``head_dim``,
    with no rotation where ``use_rope`` is false, and with
    ``attn_gate`` an output gate: ``y = W_o (sigmoid(W_gate x) * attn)``.
    Its cache is llama's: K and V rows [Lf, B, KV, Smax, hd], read by
    ``flash_decode_stacked`` and written by ``append_rows_stacked``.
  - a LINEAR layer is the gated delta rule (ops/kda.py), ``linear_heads``
    heads of ``linear_head_dim``: ``q, k = L2Norm(SiLU(Conv(W x)))``,
    ``v = SiLU(Conv(W_v x))`` (causal depthwise convolution over the last
    ``conv_kernel`` inputs), a decay a channel of the key
    ``alpha = exp(-exp(A) softplus(W_a2 W_a1 x + b))`` and a step
    ``beta = 2 sigmoid(W_b x)`` a head, both float32;
    ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
    ``o_t = S_t^T q_t / sqrt(dk)``,
    ``y = W_o (sigmoid(W_g2 W_g1 x) * RMSNorm_head(o_t))``. Its cache is
    the state [Lk, B, H, dk, dv] float32 and the convolutions' last
    ``conv_kernel - 1`` inputs [Lk, B, W - 1, 3 H dk]: a slot's memory
    does not grow with its length, and a position cannot be recomputed
    on top of a state that already holds it (``RECOMPUTABLE``).
  - every layer's feed-forward is the routed one of ``models/moe.py``
    (``moe_ffn``: sigmoid router over ``n_experts``, ``n_experts_held``
    of them here, a shared expert).

Layers are stacked a kind (``params["full"]``, ``params["linear"]``) and
scanned a PERIOD at a time, so compile time stays flat in depth; the
stacks stay whole beside the scan and a layer's weights are indexed where
they are used. What a
padded position or an idle slot may do to a state is nothing: prefill
makes positions at or past a row's length the identity (``g = 0``,
``beta = 0``, the convolution's tail taken at the last valid input) and
the decode kernel does not touch a slot that is not active.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import kda
from ..ops.attention import chunk_attention
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from . import llama, moe
from .blocks import embed, layer_at, prompt_attend, prompt_rows
from .common import ModelConfig, dense_init
# the cache, and the entry points of ``models.family`` that follow from
# it alone, handed on (``x as x``) as ``hybrid_cache`` has them
from .hybrid_cache import (HybridCache, chunk_block as chunk_block,
                           decode_attend, decode_kv_block,
                           get_rope_tables, kv_layout as kv_layout,
                           kv_tables as kv_tables,
                           unsupported_options as unsupported_options,
                           write_kv as write_kv)

# a cached position of a linear layer cannot be computed again on top of
# the state that holds it: the chunk lattice runs left-aligned, and a
# prefix-pool row is usable only at the position its state was taken
RECOMPUTABLE = False
F32 = jnp.float32
_L2_EPS = 1e-6


def counts(cfg: ModelConfig) -> tuple[int, int, int]:
    """(periods, full layers a period, linear layers a period)."""
    pat = cfg.layer_pattern
    if not pat or cfg.n_layers % len(pat) \
            or set(pat) - {"full", "linear"}:
        raise ValueError(f"layer_pattern {pat!r} does not tile "
                         f"{cfg.n_layers} layers of full and linear kinds")
    return (cfg.n_layers // len(pat), pat.count("full"),
            pat.count("linear"))


def conv_channels(cfg: ModelConfig) -> int:
    return 3 * cfg.linear_heads * cfg.linear_head_dim


def _empty_state(cfg: ModelConfig, batch: int):
    """(state, conv) of ``batch`` slots that have seen no token."""
    P, _, nl = counts(cfg)
    H, d = cfg.linear_heads, cfg.linear_head_dim
    return (jnp.zeros((P * nl, batch, H, d, d), F32),
            jnp.zeros((P * nl, batch, cfg.conv_kernel - 1,
                       conv_channels(cfg)), cfg.jdtype))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> HybridCache:
    P, nf, _ = counts(cfg)
    kv = llama.init_cache(cfg.with_(n_layers=P * nf), batch, max_seq, dtype)
    state, conv = _empty_state(cfg, batch)
    return HybridCache(k=kv.k, v=kv.v, lengths=kv.lengths,
                       k_scale=kv.k_scale, v_scale=kv.v_scale, state=state,
                       conv=conv)


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Bytes a slot's recurrent memory takes, whatever its length."""
    P, _, nl = counts(cfg)
    d = cfg.linear_head_dim
    return P * nl * (cfg.linear_heads * d * d * 4
                     + (cfg.conv_kernel - 1) * conv_channels(cfg)
                     * cfg.jdtype.itemsize)


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of this family: the decode
    step's expert dispatch shapes and path (``moe.serving_stats``),
    which way a bucket or a chunk of whole sub-blocks goes through the
    delta rule (``kda.prefill_path``: ``chunkwise`` is the kernel,
    ``recurrence`` the jnp form a token at a time, which any other
    number of tokens takes too), the bytes a slot's state takes and
    those a cached token takes in the model's type (benchmarks/metrics
    reads them here)."""
    P, nf, _ = counts(cfg)
    d = cfg.linear_head_dim
    return {**moe.serving_stats(cfg, slots),
            "kda_prefill": {"path": kda.prefill_path(d, d, cfg.linear_heads),
                            "sub_block": kda.SUB_BLOCK},
            "state_bytes_per_slot": state_bytes_per_slot(cfg),
            "kv_bytes_per_token": P * nf * 2 * cfg.n_kv_heads
            * cfg.head_dim * cfg.jdtype.itemsize}


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params of the share this chip holds, a stack a kind."""
    dt = cfg.jdtype
    ks = iter(jax.random.split(key, 40))
    P, nf, nl = counts(cfg)
    D, V = cfg.dim, cfg.vocab_size
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hl, d, r, W = (cfg.linear_heads, cfg.linear_head_dim, cfg.gate_rank,
                   cfg.conv_kernel)

    def ffn(L):
        return {"ffn_norm": jnp.ones((L, D), dt),
                **moe.init_routed(ks, cfg, L)}

    Lf, Lk = P * nf, P * nl
    full = {
        "attn_norm": jnp.ones((Lf, D), dt),
        "wq": dense_init(next(ks), (Lf, D, H * hd), dt),
        "wk": dense_init(next(ks), (Lf, D, KV * hd), dt),
        "wv": dense_init(next(ks), (Lf, D, KV * hd), dt),
        "wo": dense_init(next(ks), (Lf, H * hd, D), dt),
        **ffn(Lf),
    }
    if cfg.attn_gate:
        full["w_attn_gate"] = dense_init(next(ks), (Lf, D, H * hd), dt)
    linear = {
        "attn_norm": jnp.ones((Lk, D), dt),
        "wq": dense_init(next(ks), (Lk, D, Hl * d), dt),
        "wk": dense_init(next(ks), (Lk, D, Hl * d), dt),
        "wv": dense_init(next(ks), (Lk, D, Hl * d), dt),
        "wo": dense_init(next(ks), (Lk, Hl * d, D), dt),
        # depthwise taps [W, q | k | v channels]; tap W - 1 meets the
        # current input
        "conv": dense_init(next(ks), (Lk, W, 3 * Hl * d), dt,
                           scale=W ** -0.5),
        "a_down": dense_init(next(ks), (Lk, D, r), dt),
        "a_up": dense_init(next(ks), (Lk, r, Hl * d), dt),
        # softplus(bias) 0.01 .. 0.1 and exp(A) 1 .. 16, the ranges the
        # delta-rule models are initialised to
        "a_bias": jax.random.uniform(next(ks), (Lk, Hl * d), F32,
                                     -4.6, -2.3),
        "a_log": jnp.log(jax.random.uniform(next(ks), (Lk, Hl), F32,
                                            1.0, 16.0)),
        "w_beta": dense_init(next(ks), (Lk, D, Hl), dt),
        "g_down": dense_init(next(ks), (Lk, D, r), dt),
        "g_up": dense_init(next(ks), (Lk, r, Hl * d), dt),
        "o_norm": jnp.ones((Lk, d), dt),
        **ffn(Lk),
    }
    params = {"embedding": dense_init(next(ks), (V, D), dt, scale=0.02),
              "full": full, "linear": linear,
              "final_norm": jnp.ones((D,), dt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(next(ks), (D, V), dt)
    return params


# -- the two mixers ------------------------------------------------------------

def _full_mixer(x, lw, cfg: ModelConfig, rope, positions, attend):
    """x [B, S, D] -> (y [B, S, D], (k, v) [B, S, KV, hd] of these
    tokens). ``attend(q, k, v) -> [B, S, H, hd]``."""
    B, S = x.shape[:2]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        q = qmatmul(h, lw["wq"]).reshape(B, S, H, hd)
        k = qmatmul(h, lw["wk"]).reshape(B, S, KV, hd)
        v = qmatmul(h, lw["wv"]).reshape(B, S, KV, hd)
        if rope is not None:
            from ..ops.rope import apply_rope

            q = apply_rope(q, *rope, positions)
            k = apply_rope(k, *rope, positions)
    with jax.named_scope("attn"):
        a = attend(q, k, v).reshape(B, S, H * hd)
    with jax.named_scope("attn_out"):
        if cfg.attn_gate:
            gate = jax.nn.sigmoid(qmatmul(h, lw["w_attn_gate"]).astype(F32))
            a = (a.astype(F32) * gate).astype(x.dtype)
        return qmatmul(a, lw["wo"]), (k, v)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def _linear_inputs(h, lw, cfg: ModelConfig, tail, lengths, log_decay):
    """The recurrence's inputs from the normed stream h [B, S, D] and the
    convolutions' tail [B, W - 1, C]: (q scaled, k, v, decay, beta) in
    float32 a head, and the tail after the last valid input. The decay
    is ``g = log alpha`` where ``log_decay`` (what the prefill kernel
    takes: ``log(exp(g))`` would lose a channel that underflowed), else
    ``alpha``. Positions at or past ``lengths`` [B] (None: none) come
    out as the identity: ``g = 0``, ``beta = 0``."""
    B, S = h.shape[:2]
    H, d = cfg.linear_heads, cfg.linear_head_dim
    with jax.named_scope("kda/qkv"):
        qkv = jnp.concatenate([qmatmul(h, lw[n]) for n in ("wq", "wk", "wv")],
                              axis=-1)
    qkv, tail = kda.short_conv(qkv, tail, lw["conv"], lengths)
    with jax.named_scope("kda/gates"):
        q, k, v = (qkv[..., i * H * d:(i + 1) * H * d].reshape(B, S, H, d)
                   for i in range(3))
        q, k = _l2(q) * d ** -0.5, _l2(k)
        a = qmatmul(qmatmul(h, lw["a_down"]), lw["a_up"]).astype(F32) \
            + lw["a_bias"]
        g = -jnp.exp(lw["a_log"])[:, None] * jax.nn.softplus(
            a.reshape(B, S, H, d))
        decay = g if log_decay else jnp.exp(g)
        beta = 2.0 * jax.nn.sigmoid(qmatmul(h, lw["w_beta"]).astype(F32))
        if lengths is not None:
            valid = (jnp.arange(S)[None, :] < lengths[:, None])
            decay = jnp.where(valid[..., None, None], decay,
                              0.0 if log_decay else 1.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
    return (q, k, v, decay, beta), tail


def _linear_out(x, h, o, lw, cfg: ModelConfig):
    """o [B, S, H, dv] float32 -> the mixer's output [B, S, D]."""
    B, S = h.shape[:2]
    with jax.named_scope("kda/out"):
        o = rms_norm(o, lw["o_norm"], cfg.norm_eps)
        gate = jax.nn.sigmoid(
            qmatmul(qmatmul(h, lw["g_down"]), lw["g_up"]).astype(F32))
        o = (o.reshape(B, S, -1) * gate).astype(x.dtype)
        return qmatmul(o, lw["wo"])


def _ffn(x, lw, cfg: ModelConfig, valid):
    h = rms_norm(x, lw["ffn_norm"], cfg.norm_eps)
    y, n = moe.moe_ffn(h, lw, cfg, valid)
    return x + y, n


# -- the stack, a period at a time ---------------------------------------------

def _stack(params, cfg: ModelConfig, x, carry, full_layer, linear_layer,
           per_full=None, per_linear=None):
    """Scan the periods. ``full_layer(x, lw, i, extra) -> (x, ys)`` and
    ``linear_layer(x, lw, i, extra, carry) -> (x, ys, carry)`` run one
    layer, ``i`` its index among its kind; ``per_full``/``per_linear``:
    pytrees of [Lkind, ...] arrays sliced a layer beside the weights;
    ``carry`` is threaded through the linear layers (the state a decode
    step updates in place). Returns (x, carry, the full layers' ys
    [Lf, ...], the linear layers' ys [Lk, ...])."""
    P, nf, nl = counts(cfg)

    def periods(tree, n):
        return jax.tree_util.tree_map(
            lambda a: a.reshape((P, n) + a.shape[1:]), tree)

    def at(tree, j):
        return jax.tree_util.tree_map(lambda a: a[j], tree)

    def body(c, xs):
        x, carry = c
        ex_f, ex_l, p = xs
        jf = jl = 0
        ys_f, ys_l = [], []
        for kind in cfg.layer_pattern:
            if kind == "full":
                i = p * nf + jf
                x, ys = full_layer(x, layer_at(params["full"], i), i,
                                   at(ex_f, jf))
                ys_f.append(ys)
                jf += 1
            else:
                i = p * nl + jl
                x, ys, carry = linear_layer(
                    x, layer_at(params["linear"], i), i, at(ex_l, jl), carry)
                ys_l.append(ys)
                jl += 1
        stack = lambda ys: jax.tree_util.tree_map(  # noqa: E731
            lambda *a: jnp.stack(a), *ys)
        return (x, carry), (stack(ys_f), stack(ys_l))

    (x, carry), (ys_f, ys_l) = jax.lax.scan(
        body, (x, carry),
        (periods(per_full, nf), periods(per_linear, nl),
         jnp.arange(P, dtype=jnp.int32)))
    flat = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.reshape((-1,) + a.shape[2:]), t)
    return x, carry, flat(ys_f), flat(ys_l)


def _prefill(params, cfg: ModelConfig, tokens, lengths, state, conv,
             attend_full, per_full, rope, positions, moe_valid):
    """The shared body of ``prefill_kv`` and ``prefill_chunk``: tokens
    [B, S] from ``state``/``conv`` ([Lk, B, ...]); ``attend_full(q, k, v,
    extra)``. Returns (x, K and V stacks [Lf, B, S, KV, hd], new state,
    new conv)."""
    def full_layer(x, lw, i, extra):
        y, kv = _full_mixer(x, lw, cfg, rope, positions,
                            lambda q, k, v: attend_full(q, k, v, extra))
        x, _ = _ffn(x + y, lw, cfg, moe_valid)
        return x, kv

    def linear_layer(x, lw, i, extra, carry):
        s0, tail = extra
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        inputs, tail = _linear_inputs(h, lw, cfg, tail, lengths,
                                       log_decay=True)
        o, s1 = kda.prefill_auto(*inputs, s0)
        x, _ = _ffn(x + _linear_out(x, h, o, lw, cfg), lw, cfg, moe_valid)
        return x, (s1, tail), carry

    x, _, (k, v), (state, conv) = _stack(
        params, cfg, embed(params, cfg, tokens), None, full_layer,
        linear_layer, per_full, (state, conv))
    return x, k, v, state, conv


def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded) from an empty
    state. Returns (logits [B, S, V] float32, or [B, 1, V] with
    ``logit_pos``; K and V stacks [Lf, B, S, KV, hd]; the state
    [Lk, B, H, dk, dv] and the convolutions' tail [Lk, B, W - 1, C] as
    they stand after each row's last token; lengths [B])."""
    B, S = tokens.shape
    lengths, positions, valid = prompt_rows(tokens, lengths)
    rope = (rope_tables or get_rope_tables(cfg, rope_max or S)) \
        if cfg.use_rope else None
    attend = prompt_attend(flash, lengths, valid, mesh)
    x, k, v, state, conv = _prefill(
        params, cfg, tokens, lengths, *_empty_state(cfg, B),
        lambda q, k, v, _: attend(q, k, v), None, rope, positions, valid)
    return (llama.logits_at(params, cfg, x, logit_pos), k, v, state, conv,
            lengths)


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``)."""
    return prefill_kv(params, cfg, tokens, lengths, logit_pos=logit_pos)[0]


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: HybridCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C) against the
    cache: the full layers attend to the rows before it and within
    itself, the linear layers go on from the cache's state (from an
    empty one at ``start`` 0: a free slot holds its last occupant's).
    With ``logit_pos`` the chunk is the prompt's last and may be padded:
    positions past ``logit_pos`` leave state and tail as they stood.
    ``cache.lengths`` is not advanced (llama.prefill_chunk's contract)."""
    B, C = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    rope = (rope_tables or get_rope_tables(cfg, cache.k.shape[3])) \
        if cfg.use_rope else None
    lengths = None if logit_pos is None \
        else logit_pos.astype(jnp.int32) + 1
    valid = None if lengths is None \
        else jnp.arange(C)[None, :] < lengths[:, None]
    fresh = jnp.asarray(start) == 0
    state = jnp.where(fresh, 0.0, cache.state)
    conv = jnp.where(fresh, jnp.zeros((), cache.conv.dtype), cache.conv)

    def attend(q, k_new, v_new, layer):
        k_l, v_l, ks_l, vs_l = layer
        return chunk_attention(q, k_l, v_l, k_new, v_new, start, ks_l, vs_l)

    x, k, v, state, conv = _prefill(
        params, cfg, tokens, lengths, state, conv, attend,
        (cache.k, cache.v, cache.k_scale, cache.v_scale), rope, positions,
        valid)
    rows = llama.write_kv(cache.rows, k, v, (0, 0, 0, start, 0),
                          cache.lengths)
    cache = cache.with_rows(rows, state=state, conv=conv)
    if not compute_logits:
        return None, cache
    return llama.logits_at(params, cfg, x, logit_pos), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: HybridCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B]. The full layers read the K and V
    rows in place and their new rows are written after the loop
    (llama.decode_step's discipline and capacity contract); a linear
    layer's state is updated where it lies, inside the loop, for the
    ACTIVE slots alone, and so is its tail (one select after the loop).

    Returns (logits [B, V] float32, the cache with lengths + 1, the
    expert layer's assignments a layer a held expert [L, Eh] int32 (a
    kind at a time, the full layers' rows before the linear layers':
    what reads them sums over the layers), the (layer, slot) states
    updated: int32 scalar)."""
    B = tokens.shape[0]
    P, nf, nl = counts(cfg)
    lengths = cache.lengths
    positions = lengths[:, None]
    act = jnp.ones((B,), bool) if active is None else active
    live = jnp.where(act, lengths, 0)
    valid = act[:, None]
    rope = (rope_tables or get_rope_tables(cfg, cache.k.shape[3])) \
        if cfg.use_rope else None
    block_s = decode_kv_block(cfg, cache, mesh)

    def full_layer(x, lw, i, extra):
        y, kv = _full_mixer(x, lw, cfg, rope, positions, decode_attend(
            cache, i, lengths, live, block_s, mesh, cfg))
        x, n = _ffn(x + y, lw, cfg, valid)
        return x, (kv, n)

    def linear_layer(x, lw, i, extra, state):
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        tail = jax.lax.dynamic_index_in_dim(cache.conv, i, 0, keepdims=False)
        (q, k, v, alpha, beta), tail = _linear_inputs(
            h, lw, cfg, tail, None, log_decay=False)
        o, state = kda.decode_auto(state, i, q[:, 0], k[:, 0], v[:, 0],
                                   alpha[:, 0], beta[:, 0], act)
        x, n = _ffn(x + _linear_out(x, h, o[:, None], lw, cfg), lw, cfg,
                    valid)
        return x, (tail, n), state

    x, state, ((k_rows, v_rows), n_f), (tails, n_l) = _stack(
        params, cfg, embed(params, cfg, tokens[:, None]), cache.state,
        full_layer, linear_layer)
    with jax.named_scope("kv_write"):
        rows = llama.write_rows(cache.rows, k_rows, v_rows, positions,
                                lengths + 1, cfg.n_heads, mesh)
        conv = jnp.where(act[None, :, None, None],
                         tails.astype(cache.conv.dtype), cache.conv)
    updated = jnp.sum(act, dtype=jnp.int32) * (P * nl)
    return (llama.logits(params, cfg, x[:, 0]),
            cache.with_rows(rows, state=state, conv=conv),
            jnp.concatenate([n_f, n_l]), updated)
