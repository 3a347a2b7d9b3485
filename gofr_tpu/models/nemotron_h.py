"""The state-space family (``model_type: nemotron_h`` and, with a layer
of two halves, ``granitemoehybrid``): layers of three kinds, ONE block a
layer, or a mixer and a dense feed-forward a layer, a Mamba-2 state
beside KV rows in one cache.

The generator picks this module where ``cfg.layer_pattern`` names a
``"mamba"`` layer (``models.family``) and calls it through the same
entry points as ``models/llama.py``. Here ``cfg.layer_pattern`` names
every layer (the published string does not tile: ``MEMEMEM*EMEMEMEM*E...``)
and a layer is a norm and one block, ``x += Block_l(RMSNorm(x))``:

  - a MAMBA layer is Mamba-2 (ops/ssd.py): ``[z | xBC | dt] = W_in u``
    (``ssm_heads x ssm_head_dim`` | that + ``2 ssm_groups ssm_state`` |
    ``ssm_heads``), ``xBC = SiLU(Conv(xBC) + b)`` (causal, depthwise,
    ``conv_kernel`` inputs), ``[x | B | C]`` split a head and a group,
    ``Delta = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log) Delta)``
    (float32, as the state), ``S_t[h] = a_t[h] S_{t-1}[h] + Delta_t[h]
    x_t[h] (x) B_t[g]``, ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``, the
    block's output ``W_out RMSNorm_group(y * SiLU(z))`` (one RMS a
    group's channels). Its cache is the state [Lm, B, G, N, (H / G) P]
    float32 (ops/ssd.py says why that order) and the convolution's last
    ``conv_kernel - 1`` inputs, flat [Lm, B, (W - 1) C]: a slot's memory does
    not grow with its length, and a position cannot be recomputed on top
    of a state that already holds it (``RECOMPUTABLE``).
  - an ATTN layer is softmax attention over every cached position,
    ``n_heads`` query heads on ``n_kv_heads`` KV heads of ``head_dim``,
    NOT rotated (``use_rope`` must be false). Its cache is llama's: K
    and V rows [La, B, KV, Smax, hd].
  - a MOE layer is the routed feed-forward of ``models/moe.py``
    (``moe_ffn``) in the form the configuration gives: the router over
    the full width, the routed experts two-matrix relu^2 experts in a
    latent of ``moe_latent_dim`` behind one projection down and one up
    a token, a shared relu^2 expert of ``shared_ffn_dim`` on the full
    width, ``n_experts_held`` of ``n_experts`` held here.

With ``cfg.layer_ffn`` a layer is TWO halves (``granitemoehybrid``,
dense): its mixer, a MAMBA or an ATTN block as above (no MOE layer), and
then a gated feed-forward of ``ffn_dim``, each behind a norm of its own
and added times ``residual_multiplier``:
``x += r Mixer_l(N1_l(x)); x += r W_out (silu(g) * h), [g | h] = W_in
N2_l(x)``. The feed-forward stands OUTSIDE the switch on the kind: the
scan has as many steps as the stack has layers, and a step hands the
states through one branch, not two. The embedding is multiplied by
``embedding_multiplier`` (``blocks.embed``), the logits divided by
``logits_scaling`` (``llama.logits``), and the attention's softmax runs
at ``attention_multiplier``: q is scaled by ``attention_multiplier
head_dim^1/2`` before the kernels, which all take ``head_dim^-1/2`` (a
power of two at the published sizes, so exact in bfloat16). Heads of 64
are cached two KV heads a row (``hybrid_cache.paired``).

The weights are stacked a kind (``params["mamba"]``, ``["moe"]``,
``["attn"]``; the norms a layer, ``params["norm"]``). The stack is ONE
``lax.scan`` over the layers whose body switches on the layer's kind
(``lax.switch``: one branch runs, so a layer streams its own weights
alone) with the layer's index among its kind: a layer body a kind the
stack has to compile whatever the depth and whatever the order (a
layer's second half, ``params["ffn"]`` stacked a layer, rides the scan). What a layer leaves
behind (a state, a tail, a token's rows, the experts' counts) is
written at its index into a stack a kind that rides the scan's carry;
the decode step's state is updated where it lies, for the ACTIVE slots
alone. A padded position is the identity (``Delta = 0``) and never
reaches a tail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import ssd
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from . import llama, moe
from .blocks import as_stored, embed, layer_at, prompt_attend, prompt_rows
from .common import ModelConfig, dense_init
# the cache (rows, state, tail), and the entry points of ``models.family``
# that follow from it alone, handed on (``x as x``) as ``hybrid_cache``
# has them
from .hybrid_cache import (HybridCache, chunk_attend,
                           chunk_block as chunk_block,
                           decode_attend, decode_kv_block, init_rows,
                           get_rope_tables as get_rope_tables,
                           kv_layout as kv_layout, kv_tables as kv_tables,
                           paired,
                           unsupported_options as unsupported_options,
                           write_kv as write_kv)

RECOMPUTABLE = False     # a state: see models.family
KINDS = ("mamba", "moe", "attn")
F32 = jnp.float32


def counts(cfg: ModelConfig) -> tuple[int, int, int]:
    """(mamba layers, moe layers, attn layers) of the stack."""
    pat = cfg.layer_pattern
    if len(pat) != cfg.n_layers or set(pat) - set(KINDS):
        raise ValueError(f"layer_pattern {pat!r} does not name each of "
                         f"{cfg.n_layers} layers as one of {KINDS}")
    if cfg.use_rope:
        raise ValueError("this family's attention layers are not rotated: "
                         "use_rope must be false")
    if cfg.layer_ffn and "moe" in pat:
        raise ValueError("layer_ffn: every layer's second half is the "
                         "dense feed-forward; layer_pattern names its "
                         f"mixer, 'mamba' or 'attn', not {pat!r}")
    return tuple(pat.count(k) for k in KINDS)


def _kinds(cfg: ModelConfig) -> tuple[str, ...]:
    """The kinds the stack has, in ``KINDS``' order: the switch's
    branches."""
    return tuple(k for k in KINDS if k in cfg.layer_pattern)


def _plan(cfg: ModelConfig):
    """A layer's kind (its place in ``_kinds``) and its index among the
    layers of its kind, [L] int32 each."""
    kinds = _kinds(cfg)
    seen = dict.fromkeys(kinds, 0)
    kind, index = [], []
    for k in cfg.layer_pattern:
        kind.append(kinds.index(k))
        index.append(seen[k])
        seen[k] += 1
    return np.asarray(kind, np.int32), np.asarray(index, np.int32)


def _ssm_dims(cfg: ModelConfig) -> tuple[int, int, int, int, int]:
    """(heads, head width, groups, state size, lanes a group)."""
    H, P, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups
    return H, P, G, cfg.ssm_state, H // G * P


def conv_channels(cfg: ModelConfig) -> int:
    H, P, G, N, _ = _ssm_dims(cfg)
    return H * P + 2 * G * N


def in_width(cfg: ModelConfig) -> int:
    """Columns of ``w_ssm_in``: [z | xBC | dt] and, where that is not
    whole lane rows while the model's width is (4,096 + 4,352 + 64 =
    8,512 from 2,048), columns nothing reads up to the next one. Such a
    stack is kept by the chip with its INPUT axis minor, the one of the
    two that tiles, and the decode block re-lays all of it out every
    dispatch (627 MB at 36 layers: tests/test_kernels_compile_v5e.py
    holds the compiled text to no such copy)."""
    H, P, _, _, _ = _ssm_dims(cfg)
    w = H * P + conv_channels(cfg) + H
    return w if cfg.dim % 128 or not w % 128 else -(-w // 128) * 128


def _empty_state(cfg: ModelConfig, batch: int):
    """(state, conv) of ``batch`` slots that have seen no token."""
    Lm = counts(cfg)[0]
    _, _, G, N, R = _ssm_dims(cfg)
    return (jnp.zeros((Lm, batch, G, N, R), F32),
            jnp.zeros((Lm, batch, (cfg.conv_kernel - 1)
                       * conv_channels(cfg)), cfg.jdtype))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> HybridCache:
    kv = init_rows(cfg, counts(cfg)[2], batch, max_seq, dtype)
    state, conv = _empty_state(cfg, batch)
    return HybridCache(k=kv.k, v=kv.v, lengths=kv.lengths,
                       k_scale=kv.k_scale, v_scale=kv.v_scale, state=state,
                       conv=conv)


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Bytes a slot's recurrent memory takes, whatever its length."""
    H, P, _, N, _ = _ssm_dims(cfg)
    return counts(cfg)[0] * (H * P * N * 4 + (cfg.conv_kernel - 1)
                             * conv_channels(cfg) * cfg.jdtype.itemsize)


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of this family (as the
    hybrid family's: benchmarks/metrics reads them here); a stack with
    no moe layer says nothing of a dispatch, and one whose rows are
    paired says so."""
    said = {**(moe.serving_stats(cfg, slots) if counts(cfg)[1] else {}),
            "state_bytes_per_slot": state_bytes_per_slot(cfg),
            "kv_bytes_per_token": counts(cfg)[2] * 2 * cfg.n_kv_heads
            * cfg.head_dim * cfg.jdtype.itemsize}
    if paired(cfg):
        said["kv_heads_per_row"] = 2
    return said


def _qk_fan(cfg: ModelConfig):
    """The fan-in ``wq`` and ``wk`` are drawn at under an
    ``attention_multiplier``: a softmax at 1/64 over heads of 64 reads
    fan-in weights' scores at a standard deviation of 1/8, every row
    alike, and a wrong scale would move nothing a check could see; drawn
    at ``dim x attention_multiplier x head_dim^1/2`` the scores stand at
    1, as fan-in weights' do at ``head_dim^-1/2``."""
    if not cfg.attention_multiplier:
        return None
    return max(1.0, cfg.dim * cfg.attention_multiplier * cfg.head_dim ** 0.5)


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params of the share this chip holds, a stack a kind."""
    dt = cfg.jdtype
    ks = iter(jax.random.split(key, 32))
    Lm, Le, La = counts(cfg)
    D, V, W = cfg.dim, cfg.vocab_size, cfg.conv_kernel
    Hq, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    H, P, _, _, _ = _ssm_dims(cfg)
    C = conv_channels(cfg)
    mamba = {
        # [z | xBC | dt]
        "w_ssm_in": dense_init(next(ks), (Lm, D, in_width(cfg)), dt),
        # depthwise taps [W, x | B | C channels]; tap W - 1 meets the
        # current input
        "conv": dense_init(next(ks), (Lm, W, C), dt, scale=W ** -0.5),
        "conv_bias": 0.1 * jax.random.normal(next(ks), (Lm, C), F32),
        # softplus(dt_bias) 0.001 .. 0.1 (time_step_min .. time_step_max),
        # exp(A_log) 1 .. 16 and D near 1, the ranges Mamba-2 is
        # initialised to
        "dt_bias": jax.random.uniform(next(ks), (Lm, H), F32, -6.9, -2.25),
        "a_log": jnp.log(jax.random.uniform(next(ks), (Lm, H), F32,
                                            1.0, 16.0)),
        "d_skip": 1.0 + 0.1 * jax.random.normal(next(ks), (Lm, H), F32),
        "ssm_norm": jnp.ones((Lm, H * P), dt),
        "w_ssm_out": dense_init(next(ks), (Lm, H * P, D), dt),
    }
    fan = _qk_fan(cfg)
    qk = fan and fan ** -0.5
    attn = {
        "wq": dense_init(next(ks), (La, D, Hq * hd), dt, scale=qk),
        "wk": dense_init(next(ks), (La, D, KV * hd), dt, scale=qk),
        "wv": dense_init(next(ks), (La, D, KV * hd), dt),
        "wo": dense_init(next(ks), (La, Hq * hd, D), dt),
    }
    table, final = _head_draw(cfg)
    params = {"embedding": dense_init(next(ks), (V, D), dt, scale=table),
              "norm": jnp.ones((cfg.n_layers, D), dt),
              "mamba": mamba, "attn": attn,
              "final_norm": jnp.full((D,), final, dt)}
    if Le:
        params["moe"] = moe.init_routed(ks, cfg, Le)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(next(ks), (D, V), dt)
    if cfg.layer_ffn:
        F = cfg.ffn_dim
        params["ffn"] = {
            "norm": jnp.ones((cfg.n_layers, D), dt),
            # [gate | up]
            "w_ffn_in": dense_init(next(ks), (cfg.n_layers, D, 2 * F), dt),
            "w_ffn_out": dense_init(next(ks), (cfg.n_layers, F, D), dt)}
    return params


def _head_draw(cfg: ModelConfig) -> tuple[float, float]:
    """(the embedding's standard deviation, the final norm's weight) of
    random weights. Under an ``embedding_multiplier`` the table is TIED
    and a token enters at ``multiplier x table``: at 0.02 x 12 the
    embedded token is a sixth of the final stream, the tied head reads it
    back 7 standard deviations over every other logit, and the model
    repeats its input with probability 1 to a millionth of a nat
    whatever the layers do (PERF.md, Findings PR 53: the first chip run's
    check read 1e-6 nats and tested nothing). So the token enters at
    0.03, a seventh of one scaled branch and under 2% of the final
    stream, and the final norm's weight is what spreads the logits half
    a nat under ``logits_scaling`` (35 at the published sizes): a fault
    in a layer then moves a log-probability. Half a nat and not more:
    a greedy token of 100,352 stands 4.4 deviations over the mean, and
    what a bfloat16 stream of 80 adds has lost by the head (3% of it)
    is read times that distance; at a spread of 2 the chip's check read
    a worst 0.25-0.44 nats in seven weight seeds, against a limit of
    0.5."""
    if cfg.embedding_multiplier == 1.0:
        return 0.02, 1.0
    table = 0.03 / cfg.embedding_multiplier
    return table, 0.5 * cfg.logits_scaling / (table * cfg.dim ** 0.5)


def _fan_in(cfg: ModelConfig, name: str):
    """``tpu.random_params``' question: the fan-in an int8 leaf is drawn
    at where it is not its contraction axis."""
    return _qk_fan(cfg) if name in ("wq", "wk") else None


init.fan_in = _fan_in


# -- the three blocks ----------------------------------------------------------

def _ssm_inputs(u, lw, cfg: ModelConfig, tail, lengths):
    """The recurrence's inputs from the normed stream u [B, S, D] and the
    convolution's tail [B, (W - 1) C]: (z [B, S, H P]; x, Delta x
    [B, S, G, R], log a [B, S, H] and B, C [B, S, G, N], float32; the
    tail after the last valid input). Positions at or past ``lengths``
    [B] (None: none) come out as the identity."""
    B, S = u.shape[:2]
    H, P, G, N, R = _ssm_dims(cfg)
    with jax.named_scope("ssm/in"):
        zxd = qmatmul(u, lw["w_ssm_in"])
        C = conv_channels(cfg)      # past dt: ``in_width``'s padding
        z, xbc, dt = (zxd[..., :H * P], zxd[..., H * P:H * P + C],
                      zxd[..., H * P + C:H * P + C + H])
    xbc, tail = ssd.conv(xbc, tail, lw["conv"], lw["conv_bias"], lengths)
    with jax.named_scope("ssm/dt"):
        x = xbc[..., :H * P].reshape(B, S, G, R)
        bm, cm = (xbc[..., H * P + j * G * N:H * P + (j + 1) * G * N]
                  .reshape(B, S, G, N) for j in range(2))
        delta = jax.nn.softplus(dt.astype(F32) + lw["dt_bias"])
        if lengths is not None:
            valid = jnp.arange(S)[None, :] < lengths[:, None]
            delta = jnp.where(valid[..., None], delta, 0.0)
        la = -jnp.exp(lw["a_log"]) * delta
        dx = x * ssd._rows(delta, G, R)
    return (z, x, dx, la, bm, cm), tail


def _ssm_out(y, x, z, lw, cfg: ModelConfig, dtype):
    """y, x [B, S, G, R] float32 and the gate z [B, S, H P] -> the block's
    output [B, S, D]: the skip, the gate, THEN one RMS a group."""
    B, S, G, R = y.shape
    with jax.named_scope("ssm/norm"):
        y = y + x * ssd._rows(lw["d_skip"], G, R)
        o = y * jax.nn.silu(z.astype(F32)).reshape(B, S, G, R)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps)
        o = (o.reshape(B, S, G * R) * lw["ssm_norm"].astype(F32)) \
            .astype(dtype)
    with jax.named_scope("ssm/out"):
        return qmatmul(o, lw["w_ssm_out"])


def _attn_block(u, lw, cfg: ModelConfig, attend):
    """u [B, S, D] normed -> (y [B, S, D], (k, v) [B, S, KV, hd] of these
    tokens). ``attend(q, k, v) -> [B, S, H, hd]``."""
    B, S = u.shape[:2]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        # llama.layer's barrier: the heads-major layout the reshape
        # wants must not travel back into the matmuls, or the decode
        # block transposes the whole wq stack every dispatch (PERF.md,
        # Findings PR 33; tests/test_kernels_compile_v5e.py holds it)
        q, k, v = jax.lax.optimization_barrier(tuple(
            qmatmul(u, lw[name]) for name in ("wq", "wk", "wv")))
        q = q.reshape(B, S, H, hd)
        if cfg.attention_multiplier:
            # every kernel and jnp form scales by head_dim^-1/2
            q = q * (cfg.attention_multiplier * hd ** 0.5)
        k, v = k.reshape(B, S, KV, hd), v.reshape(B, S, KV, hd)
    with jax.named_scope("attn"):
        a = attend(q, k, v).reshape(B, S, H * hd)
    with jax.named_scope("attn_out"):
        return (qmatmul(a.astype(u.dtype), lw["wo"]),
                as_stored((k, v), paired(cfg)))


def _ffn(x, lw, cfg: ModelConfig):
    """A layer's second half on the stream x [B, S, D]: the gated
    feed-forward behind its own norm."""
    F = cfg.ffn_dim
    u = rms_norm(x, lw["norm"], cfg.norm_eps)
    with jax.named_scope("ffn/in"):
        gh = qmatmul(u, lw["w_ffn_in"])
        a = (jax.nn.silu(gh[..., :F].astype(F32))
             * gh[..., F:].astype(F32)).astype(x.dtype)
    with jax.named_scope("ffn/out"):
        return qmatmul(a, lw["w_ffn_out"])


def _add(x, y, cfg: ModelConfig):
    """The stream plus a branch times ``residual_multiplier``, rounded
    once."""
    if cfg.residual_multiplier == 1.0:
        return x + y
    return (x.astype(F32) + cfg.residual_multiplier * y.astype(F32)) \
        .astype(x.dtype)


# -- the stack: one scan, a switch on the layer's kind -------------------------

def _put(stack, value, i):
    """``value`` at index ``i`` of a stack a kind, every array of it."""
    return jax.tree_util.tree_map(
        lambda s, v: jax.lax.dynamic_update_index_in_dim(
            s, v.astype(s.dtype), i, 0), stack, value)


def _rest(c):
    """The carry of a block that is no mamba block: its states and tails
    handed on where they lie (``ssd.untouched`` says why not as they
    came)."""
    return {**c, "state": ssd.untouched(c["state"]),
            "conv": ssd.untouched(c["conv"])}


def _stack(params, cfg: ModelConfig, x, carry, mamba, routed, attn):
    """Scan the layers. Each of ``mamba``, ``routed``, ``attn`` runs one
    block of its kind, ``(u, lw, i, carry) -> (y, carry)``: ``u`` the
    normed stream, ``lw`` the layer's weights, ``i`` its index among its
    kind, ``carry`` whatever the layers leave behind (the same tree out
    of every kind). Returns (x, carry)."""
    kind, index = _plan(cfg)
    blocks = [lambda u, i, c, f=f, k=k: f(u, layer_at(params[k], i), i, c)
              for k, f in zip(KINDS, (mamba, routed, attn))
              if k in _kinds(cfg)]

    def body(c, xs):
        x, carry = c
        w, k, i, *ffn = xs
        y, carry = jax.lax.switch(k, blocks,
                                  rms_norm(x, w, cfg.norm_eps), i, carry)
        x = _add(x, y, cfg)
        if ffn:     # the layer's second half, outside the switch
            x = _add(x, _ffn(x, ffn[0], cfg), cfg)
        return (x, carry), None

    (x, carry), _ = jax.lax.scan(
        body, (x, carry), (params["norm"], jnp.asarray(kind),
                           jnp.asarray(index))
        + ((params["ffn"],) if cfg.layer_ffn else ()))
    return x, carry


def _prefill(params, cfg: ModelConfig, tokens, lengths, state, conv,
             attend, moe_valid):
    """The shared body of ``prefill_kv`` and ``prefill_chunk``: tokens
    [B, S] from ``state``/``conv`` ([Lm, B, ...]); ``attend(q, k, v, i)``.
    Returns (x, K and V stacks [La, B, S, KV, hd], new state, new conv)."""
    B, S = tokens.shape

    def mamba(u, lw, i, c):
        s0, tail = (jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                    for a in (c["state"], c["conv"]))
        (z, x, dx, la, bm, cm), tail = _ssm_inputs(u, lw, cfg, tail, lengths)
        y, s1 = ssd.prefill_auto(dx, la, bm, cm, s0, cfg.ssm_chunk)
        return (_ssm_out(y, x, z, lw, cfg, u.dtype),
                {**c, "state": _put(c["state"], s1, i),
                 "conv": _put(c["conv"], tail, i)})

    def routed(u, lw, i, c):
        return moe.moe_ffn(u, lw, cfg, moe_valid)[0], _rest(c)

    def attn(u, lw, i, c):
        y, kv = _attn_block(u, lw, cfg, lambda q, k, v: attend(q, k, v, i))
        return y, {**_rest(c), "kv": _put(c["kv"], kv, i)}

    row = jnp.zeros((counts(cfg)[2], B, S) + kv_layout(cfg), cfg.jdtype)
    x, c = _stack(params, cfg, embed(params, cfg, tokens),
                  {"state": state, "conv": conv, "kv": (row, row)},
                  mamba, routed, attn)
    return x, *c["kv"], c["state"], c["conv"]


def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded) from an empty
    state. Returns (logits [B, S, V] float32, or [B, 1, V] with
    ``logit_pos``; K and V stacks [La, B, S, KV, hd]; the state
    [Lm, B, G, N, R] and the convolution's tail [Lm, B, (W - 1) C] as they
    stand after each row's last token; lengths [B])."""
    lengths, _, valid = prompt_rows(tokens, lengths)
    attend = prompt_attend(flash, lengths, valid, mesh)
    x, k, v, state, conv = _prefill(
        params, cfg, tokens, lengths, *_empty_state(cfg, tokens.shape[0]),
        lambda q, k, v, _: attend(q, k, v), valid)
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
    cache: the attn layers attend to the rows before it and within
    itself, the mamba layers go on from the cache's state and tail (from
    empty ones at ``start`` 0: a free slot holds its last occupant's).
    With ``logit_pos`` the chunk is the prompt's last and may be padded:
    positions past ``logit_pos`` leave state and tail as they stood.
    ``cache.lengths`` is not advanced (llama.prefill_chunk's contract)."""
    B, C = tokens.shape
    lengths = None if logit_pos is None \
        else logit_pos.astype(jnp.int32) + 1
    valid = None if lengths is None \
        else jnp.arange(C)[None, :] < lengths[:, None]
    fresh = jnp.asarray(start) == 0
    state = jnp.where(fresh, 0.0, cache.state)
    conv = jnp.where(fresh, jnp.zeros((), cache.conv.dtype), cache.conv)

    def attend(q, k_new, v_new, i):
        return chunk_attend(cache, i, start, cfg)(q, k_new, v_new)

    x, k, v, state, conv = _prefill(params, cfg, tokens, lengths, state,
                                    conv, attend, valid)
    rows = llama.write_kv(cache.rows, k, v, (0, 0, 0, start, 0),
                          cache.lengths)
    cache = cache.with_rows(rows, state=state, conv=conv)
    if not compute_logits:
        return None, cache
    return llama.logits_at(params, cfg, x, logit_pos), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: HybridCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B]. The attn layers read the K and V
    rows in place and their new rows are written after the loop
    (llama.decode_step's discipline and capacity contract); a mamba
    layer's state is updated where it lies, inside the loop, for the
    ACTIVE slots alone, and so is its tail.

    Returns (logits [B, V] float32, the cache with lengths + 1, the
    expert layers' assignments a layer a held expert [Le, Eh] int32 (None
    of a stack with no such layer), the (layer, slot) states updated:
    int32 scalar)."""
    B = tokens.shape[0]
    Lm, Le, La = counts(cfg)
    lengths = cache.lengths
    positions = lengths[:, None]
    act = jnp.ones((B,), bool) if active is None else active
    live = jnp.where(act, lengths, 0)
    valid = act[:, None]
    block_s = decode_kv_block(cfg, cache, mesh)

    def mamba(u, lw, i, c):
        old = jax.lax.dynamic_index_in_dim(c["conv"], i, 0, keepdims=False)
        (z, x, dx, la, bm, cm), tail = _ssm_inputs(u, lw, cfg, old, None)
        y, state = ssd.decode_auto(c["state"], i, dx[:, 0], la[:, 0],
                                   bm[:, 0], cm[:, 0], act)
        tail = jnp.where(act[:, None], tail.astype(old.dtype), old)
        return (_ssm_out(y[:, None], x, z, lw, cfg, u.dtype),
                {**c, "state": state, "conv": _put(c["conv"], tail, i)})

    def routed(u, lw, i, c):
        y, n = moe.moe_ffn(u, lw, cfg, valid)
        return y, {**_rest(c), "n": _put(c["n"], n, i)}

    def attn(u, lw, i, c):
        y, kv = _attn_block(u, lw, cfg, decode_attend(
            cache, i, lengths, live, block_s, mesh, cfg))
        return y, {**_rest(c), "kv": _put(c["kv"], kv, i)}

    row = jnp.zeros((La, B, 1) + kv_layout(cfg), cfg.jdtype)
    x, c = _stack(
        params, cfg, embed(params, cfg, tokens[:, None]),
        {"state": cache.state, "conv": cache.conv, "kv": (row, row),
         # the expert layers' counts, of a stack that has such layers
         **({"n": jnp.zeros((Le, moe.n_held(cfg)), jnp.int32)} if Le
            else {})},
        mamba, routed, attn)
    with jax.named_scope("kv_write"):
        rows = llama.write_rows(cache.rows, *c["kv"], positions,
                                lengths + 1, cfg.n_heads, mesh)
    updated = jnp.sum(act, dtype=jnp.int32) * Lm
    return (llama.logits(params, cfg, x[:, 0]),
            cache.with_rows(rows, state=c["state"], conv=c["conv"]),
            c.get("n"), updated)
