"""The gated delta rule (linear attention with a state that is rewritten
in place every token), as two Pallas TPU kernels and their jnp forms.

One head keeps ``S`` [dk, dv] float32. A token brings a query and a key
(L2-normed, the query scaled by dk^-1/2), a value, a decay ``alpha`` in
(0, 1) a channel of the key, and a step ``beta`` in (0, 2):

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + (beta_t k_t) (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

which is ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``
multiplied out. Nothing here is approximated: a position with
``alpha = 1`` and ``beta = 0`` is the identity, which is how the callers
mask padding.

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
or a chunk of a prompt from the slot's state, CHUNK BY CHUNK with
matmuls, the state held in VMEM from chunk to chunk (it touches HBM once
a call). It takes the LOG-decay ``g = log alpha`` (<= 0; padding is
``g = 0``, ``beta = 0``). With ``G`` the running sum of g inside a chunk
of C tokens, ``kb = beta k`` and ``u_t = v_t - (Diag(alpha_t) S_{t-1})^T
k_t``:

    (I + tril(A, -1)) U = V - (K e^G) S_0     A[t,i] = sum_c k_t kb_i e^(G_t - G_i)
    O   = (Q e^G) S_0 + tril(P) U              P[t,i] = sum_c q_t kb_i e^(G_t - G_i)
    S_C = Diag(e^(G_C)) S_0 + (Kb e^(G_C - G))^T U

Every exponent is of a sum of log-decays of one sign, never of a
difference of two running sums: ``e^(-G_i)`` alone overflows float32
once a channel has decayed by e^88 inside a chunk. Inside a sub-block
of 16 tokens the pairs (t, i) take their ``e^(G_t - G_i)`` a distance
``t - i`` at a time on the vector unit (the exponent grows by one
token's g a distance); between sub-blocks the product is split at the
later block's first row into two factors <= 1 and goes to the matrix
unit; the unit-lower-triangular system is solved by forward
substitution, sub-block by sub-block. Every dot that feeds the state or
the output is float32 (``Precision.HIGHEST``); nothing is approximated,
clamped or dropped, and float32 rounding in another order is the only
difference from ``recurrent_ref``: fewer roundings, in fact, since a
chunk takes one ``exp`` of a sum where the recurrence multiplies 128
decays (on the chip, whose ``exp`` is good to 5e-6, the kernel is 25
times nearer the float64 recurrence than the jnp one: PERF.md).

The state's rows are the key's channels (sublanes) and its lanes the
value's, so the decode kernel's reductions over the key run down the
sublanes (vector adds) and the value and the output are rows as the
projections give them; its alpha, k, beta k and q are needed as columns
and are transposed in the kernel a block of heads at a time. The
prefill kernel's tokens are rows [T, dk] a head, as the matmuls want
them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
SUB_BLOCK = 16            # tokens of a prefill sub-block
_CHUNKS = (128, 64, 32, 16)   # tokens of a prefill chunk: the most that divide T
_DECODE_HEADS = 16   # heads a decode work item holds: 1 MB of state
_PREFILL_HEADS = 2   # heads a prefill grid step holds
_VMEM = 64 * 1024 * 1024
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


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
    def step(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = S * a_t[..., None]
        u = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=_HI)
        S = S + (k_t * b_t[..., None])[..., None] * (v_t - u)[:, :, None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=_HI)

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

def chunk_tokens(T: int) -> int:
    """Tokens of a prefill chunk: the most of ``_CHUNKS`` that T is whole
    chunks of (0: T is not whole sub-blocks)."""
    return next((c for c in _CHUNKS if T % c == 0), 0)


def _dot(a, b, contract=(1, 0)):
    """The float32 product of two matrices over ``a``'s axis
    ``contract[0]`` and ``b``'s ``contract[1]``: ``a b`` as it stands,
    ``a b^T`` with (1, 1), ``a^T b`` with (0, 0)."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=F32, precision=_HI)


def _column(row):
    """A row [1, N] as a column [N, 1]."""
    return jnp.broadcast_to(row, (8, row.shape[1])).T[:, 0:1]


def _chunk_masks(C: int, dk: int):
    """What every chunk of C tokens shares. ``scans`` [2 C, C], 0/1: its
    product with a chunk's log-decays [C, dk] is their running sums
    inside a sub-block, up to and with row t and after row t. ``back``
    [C, C]: how far row t lies behind column i where both are of one
    sub-block, -1 elsewhere. ``col`` [16, C] and ``token`` [C, dk]: the
    column and the row."""
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same = (row // SUB_BLOCK) == (col // SUB_BLOCK)
    scans = jnp.concatenate([(same & m).astype(jnp.bfloat16)
                             for m in (col <= row, col > row)])
    return (scans, jnp.where(same, row - col, -1),
            jax.lax.broadcasted_iota(jnp.int32, (SUB_BLOCK, C), 1),
            jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0))


def _sums(scans, g):
    """``scans @ g`` in float32 from three bfloat16 passes: g is split
    into three bfloat16 parts that add up to it, and a 0/1 matrix is
    exact in bfloat16, so every product is exact and the sums are
    float32's."""
    parts, rest = [], g
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(F32)
    return sum(jnp.dot(scans, p, preferred_element_type=F32) for p in parts)


def _chunk(q, k, kb, g, v, S, scans, back, col, token):
    """One chunk of one head from the state S [dk, dv] at its start:
    q (scaled), k, kb = beta k and the log-decay g [C, dk]; v [C, dv].
    Returns (o [C, dv], the state after the chunk's last token).

    With G the running sum of g, the rows u_t = v_t - (Diag(alpha_t)
    S_(t-1))^T k_t solve ``(I + tril(A, -1)) U = V - (K e^G) S`` where
    ``A[t, i] = sum_c k_t[c] kb_i[c] e^(G_t[c] - G_i[c])``; then
    ``O = (Q e^G) S + tril(P) U`` with q_t in k_t's place in P, and the
    state is ``Diag(e^(G_C)) S + (Kb e^(G_C - G))^T U``. No exponent is
    a difference of two running sums (a channel that decays by e^-88
    inside a chunk would overflow float32 in e^(-G_i), and a difference
    of two long sums has lost the short one's digits): inside a
    sub-block of 16 it is summed a distance at a time, a pair of tokens
    a channel on the vector unit; across sub-blocks it is split at the
    later block's first row into two sums that are both <= 0, and the
    product goes to the matrix unit."""
    C, n = k.shape[0], SUB_BLOCK
    blocks = [slice(b * n, (b + 1) * n) for b in range(C // n)]
    sums = _sums(scans, g)
    pre, post = sums[:C], sums[C:]     # inside the sub-block: to t, after t
    total = [pre[r.stop - 1:r.stop] for r in blocks]
    # from the chunk's start to row t, and after row t to its end
    run = jnp.concatenate([pre[r] + sum(total[:b])
                           for b, r in enumerate(blocks)])
    left = jnp.concatenate([post[r] + sum(total[b + 1:])
                            for b, r in enumerate(blocks)])

    # the diagonal sub-blocks, a distance d = t - i at a time
    A = jnp.zeros((C, C), F32)
    P = jnp.where(back == 0, jnp.sum(q * kb, axis=1, keepdims=True), 0.0)
    span, g_d, kb_d = jnp.zeros_like(g), g, kb
    for d in range(1, n):
        span = span + g_d              # g_t + ... + g_(t - d + 1)
        g_d, kb_d = (pltpu.roll(x, 1, 0) for x in (g_d, kb_d))
        m = kb_d * jnp.exp(span)       # row t: token t - d, decayed to t
        hit = back == d                # never a row that wrapped
        A = jnp.where(hit, jnp.sum(k * m, axis=1, keepdims=True), A)
        P = jnp.where(hit, jnp.sum(q * m, axis=1, keepdims=True), P)

    e_run = jnp.exp(run)
    from_s = _dot(jnp.concatenate([k * e_run, q * e_run]), S)
    rhs = v - from_s[:C]
    us, p_rows, reach = [], [], post
    for b, r in enumerate(blocks):
        u, p = rhs[r], P[r]
        if b:
            # the keys before this sub-block decayed up to the row before
            # its first (rows from there on hold what is masked out), and
            # its own rows from there on
            if b > 1:
                reach = jnp.where(token < (b - 1) * n,
                                  reach + total[b - 1], post)
            e = jnp.exp(pre[r])
            ap = _dot(jnp.concatenate([k[r] * e, q[r] * e]),
                      kb * jnp.exp(reach), (1, 1))
            u = u - _dot(ap[:n, :b * n], jnp.concatenate(us))
            p = jnp.where(col < b * n, ap[n:], p)
        # forward substitution: row i is final once the rows above are
        # taken out of it, and is then taken out of the rows below
        Ab = A[r, r]
        for i in range(n - 1):
            u = u - Ab[:, i:i + 1] * u[i:i + 1]
        us.append(u)
        p_rows.append(p)
    U = jnp.concatenate(us)
    S = S * jnp.exp(_column(run[C - 1:C])) + _dot(kb * jnp.exp(left), U, (0, 0))
    return from_s[C:] + _dot(jnp.concatenate(p_rows), U), S


def _prefill_kernel(q_ref, k_ref, kb_ref, g_ref, v_ref, s_in, o_ref, s_out,
                    *, heads: int, chunk: int):
    masks = _chunk_masks(chunk, q_ref.shape[3])
    s_out[...] = s_in[...]

    def step(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        # the heads' chains are independent: side by side in one body
        for h in range(heads):
            o, S = _chunk(*(r[0, h, at, :] for r in (
                q_ref, k_ref, kb_ref, g_ref, v_ref)), s_out[0, h], *masks)
            o_ref[0, h, at, :] = o
            s_out[0, h] = S
        return carry

    jax.lax.fori_loop(0, q_ref.shape[2] // chunk, step, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_prefill(q, k, v, g, beta, state, *, interpret: bool = False):
    """The recurrence over T tokens from ``state``, chunk by chunk. q
    (scaled), k and the LOG-decay g = log alpha (<= 0) [B, T, H, dk];
    v [B, T, H, dv]; beta [B, T, H]; state [B, H, dk, dv] float32; T
    whole sub-blocks of 16. Returns (o [B, T, H, dv] float32, the state
    after token T - 1)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    hb = min(_PREFILL_HEADS, H)
    chunk = chunk_tokens(T)
    if not chunk or H % hb:
        raise ValueError(f"{T} tokens of {H} heads are not whole "
                         f"sub-blocks of {SUB_BLOCK} and groups of {hb} heads")
    kb = k * beta[..., None]
    # a head's tokens contiguous: [B, H, T, d]
    q_, k_, kb_, g_, v_ = (jnp.swapaxes(x.astype(F32), 1, 2)
                           for x in (q, k, kb, g, v))
    seq = pl.BlockSpec((1, hb, T, dk), lambda b, j: (b, j, 0, 0))
    seq_v = pl.BlockSpec((1, hb, T, dv), lambda b, j: (b, j, 0, 0))
    blk = pl.BlockSpec((1, hb, dk, dv), lambda b, j: (b, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_prefill_kernel, heads=hb, chunk=chunk),
        grid=(B, H // hb),
        in_specs=[seq, seq, seq, seq, seq_v, blk],
        out_specs=[seq_v, blk],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
    )(q_, k_, kb_, g_, v_, state.astype(F32))
    return jnp.swapaxes(o, 1, 2), state


def prefill_path(dk: int, dv: int, heads: int, tokens: int = SUB_BLOCK) -> str:
    """Which way ``prefill_auto`` takes ``tokens`` tokens: ``chunkwise``
    (the kernel) where ``kernel_ok`` and they are whole sub-blocks,
    else ``recurrence`` (jnp, a token at a time)."""
    return "chunkwise" if kernel_ok(dk, dv, heads) and chunk_tokens(tokens) \
        and heads % min(_PREFILL_HEADS, heads) == 0 else "recurrence"


@jax.named_scope("kda/prefill")
def prefill_auto(q, k, v, g, beta, state):
    """``kda_prefill`` where ``prefill_path`` says so, the jnp recurrence
    on ``alpha = exp(g)`` elsewhere."""
    from .flash import interpret_env

    if prefill_path(q.shape[3], v.shape[3], q.shape[2],
                    q.shape[1]) == "chunkwise":
        return kda_prefill(q, k, v, g, beta, state,
                           interpret=interpret_env())
    return recurrent_ref(q, k, v, jnp.exp(g), beta, state)


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
