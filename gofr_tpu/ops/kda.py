"""The gated delta rule (linear attention with a state that is rewritten
in place every token), as two Pallas TPU kernels and their jnp forms.

One head keeps ``S`` [dk, dv] float32. A token brings a query and a key
(L2-normed, the query scaled by dk^-1/2), a value, a decay ``alpha`` in
(0, 1) a channel of the key, and a step ``beta`` in (0, 2):

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + (beta_t k_t) (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

which is ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``
multiplied out. Nothing here is approximated or reordered across tokens:
a position with ``alpha = 1`` and ``beta = 0`` is the identity, which is
how the callers mask padding.

``kda_decode`` (scope ``kda/decode``; a device trace names a kernel after
its jitted function, and the benchmark finds these two by those names):
one token a slot. The states of every
linear layer and slot live in one array [Lk, B, H, dk, dv]; the kernel
is handed all of it with the layer index and a work list of the ACTIVE
slots, fetches each listed slot's heads once, updates them and writes
them back where they were (``input_output_aliases``): one read and one
write of a live state a step, and not a byte of an idle slot's, which
stays bit for bit what it was (a slot whose prompt is half-way through
its chunks must not see a decode step).

``kda_prefill`` (scope ``kda/prefill``): the same recurrence over a bucket
or a chunk from the slot's state, token by token with the state held in
VMEM: it touches HBM once a chunk. The chunkwise form (sub-chunks solved
as triangular systems and applied with matmuls) would put the work on
the matrix unit; with a decay a channel it needs exp(g_t - g_i) a pair
of tokens a channel and is left as the next step (PERF.md section 7).

The state's rows are the key's channels (sublanes) and its lanes the
value's, so both reductions over the key run down the sublanes (vector
adds) and the value and the output are rows as the projections give
them; alpha, k, beta k and q are needed as columns and are transposed
in the kernel a few tokens (prefill) or a block of heads (decode) at a
time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_GROUP = 8           # tokens transposed together in the prefill kernel
_DECODE_HEADS = 16   # heads a decode work item holds: 1 MB of state
_PREFILL_HEADS = 4   # heads a prefill grid step holds
_VMEM = 64 * 1024 * 1024
F32 = jnp.float32


def kernel_ok(dk: int, dv: int, heads: int) -> bool:
    """Do backend and shapes take the kernels? Whole lanes a head on a
    TPU; ``GOFR_FLASH_INTERPRET=1`` runs them interpreted anywhere."""
    from .flash import interpret_env, tpu_backend_ok

    if interpret_env():
        return True
    return not (dk % _LANES or dv % _LANES or heads % _DECODE_HEADS) \
        and tpu_backend_ok()


def _token(S, a, k, kb, q, v):
    """One token of one head. S [dk, dv]; a, k, kb, q columns [dk, 1];
    v a row [1, dv]. Returns (S_t, o_t [1, dv])."""
    S = S * a
    u = jnp.sum(S * k, axis=0, keepdims=True)
    S = S + kb * (v - u)
    return S, jnp.sum(S * q, axis=0, keepdims=True)


# -- jnp forms ----------------------------------------------------------------

def recurrent_ref(q, k, v, alpha, beta, state):
    """The token-by-token recurrence in float32 jnp: the oracle of both
    kernels and the path where they do not run. q, k, alpha [B, T, H, dk];
    v [B, T, H, dv]; beta [B, T, H]; state [B, H, dk, dv]. Returns
    (o [B, T, H, dv] float32, state after the last token)."""
    hi = jax.lax.Precision.HIGHEST

    def step(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = S * a_t[..., None]
        u = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=hi)
        S = S + (k_t * b_t[..., None])[..., None] * (v_t - u)[:, :, None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=hi)

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0)
               for x in (q, k, v, alpha, beta))
    state, o = jax.lax.scan(step, state.astype(F32), xs)
    return jnp.moveaxis(o, 0, 1), state


def decode_ref(state, layer, q, k, v, alpha, beta, active):
    """``kda_decode``'s contract in jnp: state [Lk, B, H, dk, dv]; q, k, alpha
    [B, H, dk]; v [B, H, dv]; beta [B, H]; active [B] bool."""
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    o, new = recurrent_ref(q[:, None], k[:, None], v[:, None],
                           alpha[:, None], beta[:, None], old)
    live = active[:, None, None, None]
    new = jnp.where(live, new, old)
    return (jnp.where(active[:, None, None], o[:, 0], 0.0),
            jax.lax.dynamic_update_index_in_dim(state, new, layer, 0))


# -- decode -------------------------------------------------------------------

def _decode_kernel(layer_ref, n_ref, slot_ref, a_ref, k_ref, kb_ref, q_ref,
                   v_ref, s_in, o_in, s_out, o_out, *, heads: int):
    del layer_ref, slot_ref, o_in
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _item():
        # [heads, dk] -> columns [dk, heads]
        a, k, kb, q = (r[0].T for r in (a_ref, k_ref, kb_ref, q_ref))
        for h in range(heads):
            S, o = _token(s_in[0, 0, h], a[:, h:h + 1], k[:, h:h + 1],
                          kb[:, h:h + 1], q[:, h:h + 1], v_ref[0, h:h + 1])
            s_out[0, 0, h] = S
            o_out[0, h:h + 1] = o

    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _nothing_live():
        # every step maps to one block; it goes back as it came
        s_out[...] = s_in[...]
        o_out[...] = jnp.zeros_like(o_out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode(state, layer, q, k, v, alpha, beta, active, *,
               interpret: bool = False):
    """One token of every ACTIVE slot through layer ``layer`` of the
    stacked states, in place on a donated ``state``.

    state [Lk, B, H, dk, dv] float32; q (scaled), k, alpha [B, H, dk]
    and v [B, H, dv] float32; beta [B, H]; active [B] bool. Returns
    (o [B, H, dv] float32: zeros for an idle slot; the state)."""
    _, B, H, dk, dv = state.shape
    hb = min(_DECODE_HEADS, H)
    nj = H // hb
    active = active.astype(bool)
    n = jnp.sum(active, dtype=jnp.int32)
    # active slots first, in slot order
    slots = jnp.argsort(~active, stable=True).astype(jnp.int32)

    def at(i, j, layer, n, slots):
        live = i < n[0]
        slot = slots[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]
        return slot, jnp.where(live, j, nj - 1)

    def row_map(i, j, *pre):
        slot, jj = at(i, j, *pre)
        return slot, jj, 0

    def state_map(i, j, layer, n, slots):
        slot, jj = at(i, j, layer, n, slots)
        return layer[0], slot, jj, 0, 0

    row = pl.BlockSpec((1, hb, dk), row_map)
    row_v = pl.BlockSpec((1, hb, dv), row_map)
    blk = pl.BlockSpec((1, 1, hb, dk, dv), state_map)
    kb = k * beta[..., None]
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, nj),
            in_specs=[row, row, row, row, row_v, blk, row_v],
            out_specs=[blk, row_v]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), F32)],
        # operands count the three scalar-prefetch arrays
        input_output_aliases={8: 0, 9: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), n.reshape(1), slots,
      alpha.astype(F32), k.astype(F32), kb.astype(F32), q.astype(F32),
      v.astype(F32), state, jnp.zeros((B, H, dv), F32))[::-1]
    return o, state


@jax.named_scope("kda/decode")
def decode_auto(state, layer, q, k, v, alpha, beta, active):
    """``kda_decode`` where ``kernel_ok``, its jnp form elsewhere."""
    from .flash import interpret_env

    _, _, H, dk, dv = state.shape
    if kernel_ok(dk, dv, H):
        return kda_decode(state, layer, q, k, v, alpha, beta, active,
                          interpret=interpret_env())
    return decode_ref(state, layer, q, k, v, alpha, beta, active)


# -- prefill ------------------------------------------------------------------

def _prefill_kernel(a_ref, k_ref, kb_ref, q_ref, v_ref, s_in, o_ref, s_out,
                    *, heads: int, groups: int):
    for h in range(heads):
        def group(g, S):
            at = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            # [8 tokens, dk] -> columns [dk, 8]
            a, k, kb, q = (r[0, h, at, :].T
                           for r in (a_ref, k_ref, kb_ref, q_ref))
            v = v_ref[0, h, at, :]
            rows = []
            for t in range(_GROUP):
                S, o = _token(S, a[:, t:t + 1], k[:, t:t + 1],
                              kb[:, t:t + 1], q[:, t:t + 1], v[t:t + 1])
                rows.append(o)
            o_ref[0, h, at, :] = jnp.concatenate(rows, axis=0)
            return S

        s_out[0, h] = jax.lax.fori_loop(0, groups, group, s_in[0, h])


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_prefill(q, k, v, alpha, beta, state, *, interpret: bool = False):
    """The recurrence over T tokens from ``state``. q (scaled), k, alpha
    [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H]; state [B, H, dk, dv]
    float32; T a multiple of 8. Returns (o [B, T, H, dv] float32, the
    state after token T - 1)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    hb = min(_PREFILL_HEADS, H)
    kb = k * beta[..., None]
    # a head's tokens contiguous: [B, H, T, d]
    a_, k_, kb_, q_, v_ = (jnp.swapaxes(x.astype(F32), 1, 2)
                           for x in (alpha, k, kb, q, v))
    seq = pl.BlockSpec((1, hb, T, dk), lambda b, j: (b, j, 0, 0))
    seq_v = pl.BlockSpec((1, hb, T, dv), lambda b, j: (b, j, 0, 0))
    blk = pl.BlockSpec((1, hb, dk, dv), lambda b, j: (b, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_prefill_kernel, heads=hb, groups=T // _GROUP),
        grid=(B, H // hb),
        in_specs=[seq, seq, seq, seq, seq_v, blk],
        out_specs=[seq_v, blk],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
    )(a_, k_, kb_, q_, v_, state.astype(F32))
    return jnp.swapaxes(o, 1, 2), state


@jax.named_scope("kda/prefill")
def prefill_auto(q, k, v, alpha, beta, state):
    """``kda_prefill`` where ``kernel_ok`` and the tokens are whole groups,
    the jnp recurrence elsewhere."""
    from .flash import interpret_env

    H, dk, dv = q.shape[2], q.shape[3], v.shape[3]
    if kernel_ok(dk, dv, H) and q.shape[1] % _GROUP == 0 \
            and H % min(_PREFILL_HEADS, H) == 0:
        return kda_prefill(q, k, v, alpha, beta, state,
                           interpret=interpret_env())
    return recurrent_ref(q, k, v, alpha, beta, state)


# -- the short convolution ----------------------------------------------------

def conv_taps(x, tail, weight, lengths=None):
    """Causal depthwise convolution a channel over the last W inputs,
    with the W - 1 inputs before the block in ``tail``: taps and tail,
    no activation (the hybrid family's ``short_conv`` puts a SiLU on it;
    the conv family's gated operator, models/lfm2.py, none).

    x [B, T, C]; tail [B, W - 1, C]; weight [W, C] (weight[W - 1] meets
    the current input); lengths [B]: valid inputs of x (None: all).
    Returns (y [B, T, C] float32, the last W - 1 valid inputs: the tail
    as it stands after input ``lengths - 1``; zeros stay where fewer
    than W - 1 inputs have been seen)."""
    B, T, _ = x.shape
    W = weight.shape[0]
    xs = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = weight.astype(F32)
    y = sum(xs[:, j:j + T].astype(F32) * w[j] for j in range(W))
    if lengths is None:
        new_tail = xs[:, T:]
    else:
        new_tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
            row, n, W - 1, axis=0))(xs, lengths.astype(jnp.int32))
    return y, new_tail.astype(tail.dtype)


@jax.named_scope("kda/conv")
def short_conv(x, tail, weight, lengths=None):
    """``conv_taps`` then SiLU: the delta-rule layer's convolution on q,
    k and v."""
    y, tail = conv_taps(x, tail, weight, lengths)
    return jax.nn.silu(y), tail
