"""Mamba-2's state-space recurrence (a scalar decay a head, a rank-one
write from a GROUP's B, a read by the group's C), as two Pallas TPU
kernels and their jnp forms.

One head ``h`` of width P keeps ``S[h]`` [P, N] float32; the heads of a
group ``g = h // (H / G)`` share ``B_t[g]`` and ``C_t[g]`` [N]. A token
brings ``x_t[h]`` [P], a step ``Delta_t[h] > 0`` and the decay
``a_t[h] = exp(-exp(A_log[h]) Delta_t[h])`` in (0, 1):

    S_t[h] = a_t[h] S_{t-1}[h] + (Delta_t[h] x_t[h]) (x) B_t[g]
    y_t[h] = S_t[h] C_t[g]                    (+ D[h] x_t[h], the caller's)

The callers hand over ``dx = Delta x`` and ``la = log a = -exp(A_log)
Delta`` (float32, as the state): a position with ``dx = 0`` and ``la = 0``
is the identity, which is how they mask padding.

Layout. The state is stored a GROUP at a time with N down the sublanes
and the group's heads side by side along the lanes: ``[..., G, N, R]``,
``R = (H / G) P`` (8 x 128 x 1024 at one published size, 4.19 MB a slot
a layer; 1 x 128 x 4096 at another, ONE group of 64 heads, 2.10 MB: the
bytes of [H, P, N]). So x, dx, a and y are the rows the
projections give ([G, R] is [H P] reshaped), B and C are one column a
group, the write is ``column x row`` and the read is a sum DOWN the
sublanes (vector adds); stored [H, P, N] the read would be a reduction
across the lanes of every vector, and [H, N, P] would leave half of
every 128-lane tile empty at P = 64.

``ssd_decode`` (scope ``ssm/scan/decode``; a device trace names a kernel
after its jitted function, and the benchmark finds these two by those
names): one token a slot, in place on the states of every mamba layer
and slot [Lm, B, G, N, R] with the layer index and a work list of the
ACTIVE slots, as ``ops/kda.py``'s decode kernel: one read and one write
of a live state a step and not a byte of an idle slot's.

``ssd_prefill`` (scope ``ssm/scan/chunk``): the same recurrence over a
bucket or a chunk of a prompt from the slot's state, in the state-space-
duality form over chunks of ``chunk`` tokens (the published
``chunk_size``, 128 or 256; a bucket under one chunk is one chunk of its
own length), the state held in VMEM from chunk to chunk. A program is a
(slot, group, cut of the group's lanes): the heads of a group share
``C B^T`` and nothing else, so a group of 4,096 lanes runs as four cuts
of 1,024, the first of which makes ``C B^T`` for the others. With
``cum_t`` the running sum of ``la`` inside a chunk,

    Y     = ((C B^T) .* L_h) (Delta X)_h + exp(cum) .* (C S_prev)
    S_new = exp(cum_Q) S_prev + B^T (exp(cum_Q - cum) .* (Delta X))

``L_h[t, s] = exp(cum_t - cum_s)`` for s <= t, else 0: five matmuls a
group and one a head a chunk on the matrix unit, where the token-by-token
form is seven vector operations a state value a token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda

_LANES = 128
_SUBLANES = 8
_DECODE_GROUPS = 2   # groups a decode work item holds: 1 MB of state
#                      (one group where there is one: 2.1 MB at 4,096 lanes)
_SLAB = 256          # lanes of a group's state updated at a time
# lanes of a group a prefill program holds: every chunk of its Delta x,
# its two scaled copies and y, 2.1 MB each at 512 tokens; a group wider
# than this (ONE group of 4,096 lanes: 8.4 MB each, 33.5 MB before double
# buffering) is cut along the lanes, whose heads share C B^T and nothing
# else
_PREFILL_LANES = 1024
_VMEM = 64 * 1024 * 1024
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def kernel_ok(groups: int, n: int, r: int) -> bool:
    """Do backend and shapes take the kernels? Whole tiles a group on a
    TPU; ``GOFR_FLASH_INTERPRET=1`` runs them interpreted anywhere."""
    from .flash import interpret_env, tpu_backend_ok

    if interpret_env():
        return True
    return not (n % _LANES or r % _LANES
                or groups % min(_DECODE_GROUPS, groups)) and tpu_backend_ok()


def _pad_tokens(arrays, pad: int):
    """``pad`` identity positions (zeros: ``dx = 0``, ``la = 0``) after
    the tokens of each [B, T, ...] array."""
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                 for a in arrays)


def _rows(la, groups: int, r: int):
    """A value a head [..., H] -> a value a lane [..., G, R]."""
    p = r // (la.shape[-1] // groups)
    return jnp.repeat(la, p, axis=-1).reshape(la.shape[:-1] + (groups, r))


# -- jnp forms ----------------------------------------------------------------

def recurrent_ref(dx, la, bm, cm, state):
    """The token-by-token recurrence in float32 jnp: the oracle of both
    kernels and of the chunk form. dx [B, T, G, R]; la [B, T, H]; bm, cm
    [B, T, G, N]; state [B, G, N, R]. Returns (y [B, T, G, R] float32,
    the state after the last token)."""
    G, R = dx.shape[2:]

    def step(S, xs):
        dx_t, la_t, b_t, c_t = xs
        S = S * jnp.exp(_rows(la_t, G, R))[:, :, None, :] \
            + b_t[..., None] * dx_t[:, :, None, :]
        return S, jnp.einsum("bgn,bgnr->bgr", c_t, S, precision=_HI)

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (dx, la, bm, cm))
    state, y = jax.lax.scan(step, state.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1), state


def chunked_ref(dx, la, bm, cm, state, chunk: int):
    """``recurrent_ref`` in the chunk form, in jnp: what ``ssd_prefill``
    computes, and the path where it does not run. T is cut into chunks
    of ``chunk`` (one shorter chunk where T is smaller; T must otherwise
    be whole chunks)."""
    B, T, G, R = dx.shape
    H, N = la.shape[-1], bm.shape[-1]
    Q = min(chunk, T)
    if T % Q:
        raise ValueError(f"{T} tokens are not whole chunks of {Q}")
    Hg, P = H // G, R // (H // G)
    tril = jnp.tril(jnp.ones((Q, Q), bool))

    def one(S, xs):
        dx_c, la_c, b_c, c_c = xs            # [B, Q, ...]
        cum = jnp.cumsum(la_c, axis=1)       # [B, Q, H]
        x5 = dx_c.reshape(B, Q, G, Hg, P)
        S5 = S.reshape(B, G, N, Hg, P)
        cg = cum.reshape(B, Q, G, Hg)
        diff = cg[:, :, None] - cg[:, None]  # [B, t, s, G, Hg]
        L = jnp.where(tril[None, :, :, None, None],
                      jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        cb = jnp.einsum("btgn,bsgn->btsg", c_c, b_c, precision=_HI)
        y = jnp.einsum("btsgh,bsghp->btghp", cb[..., None] * L, x5,
                       precision=_HI)
        y = y + jnp.exp(cg)[..., None] * jnp.einsum(
            "btgn,bgnhp->btghp", c_c, S5, precision=_HI)
        w = jnp.exp(cg[:, -1:] - cg)         # [B, Q, G, Hg]
        S5 = jnp.exp(cg[:, -1])[:, :, None, :, None] * S5 + jnp.einsum(
            "bsgn,bsghp->bgnhp", b_c, w[..., None] * x5, precision=_HI)
        return S5.reshape(B, G, N, R), y.reshape(B, Q, G, R)

    xs = tuple(jnp.moveaxis(a.astype(F32).reshape(
        (B, T // Q, Q) + a.shape[2:]), 1, 0) for a in (dx, la, bm, cm))
    state, y = jax.lax.scan(one, state.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1).reshape(B, T, G, R), state


def decode_ref(state, layer, dx, la, bm, cm, active):
    """``ssd_decode``'s contract in jnp: state [Lm, B, G, N, R]; dx
    [B, G, R]; la [B, H]; bm, cm [B, G, N]; active [B] bool."""
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    y, new = recurrent_ref(dx[:, None], la[:, None], bm[:, None],
                           cm[:, None], old)
    new = jnp.where(active[:, None, None, None], new, old)
    return (jnp.where(active[:, None, None], y[:, 0], 0.0),
            jax.lax.dynamic_update_index_in_dim(state, new, layer, 0))


# -- decode -------------------------------------------------------------------

def _column(row):
    """A row [1, N] as a column [N, 1]."""
    return jnp.broadcast_to(row, (_SUBLANES, row.shape[1])).T[:, 0:1]


def _decode_kernel(layer_ref, n_ref, slot_ref, a_ref, dx_ref, b_ref, c_ref,
                   s_in, y_in, s_out, y_out, *, groups: int, slab: int):
    del layer_ref, slot_ref, y_in
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    R = s_in.shape[-1]

    @pl.when(i < n)
    def _item():
        for g in range(groups):
            b, c = _column(b_ref[0, g]), _column(c_ref[0, g])
            for r in range(0, R, slab):
                at = slice(r, r + slab)
                S = s_in[0, 0, g, :, at] * a_ref[0, g, :, at] \
                    + b * dx_ref[0, g, :, at]
                s_out[0, 0, g, :, at] = S
                y_out[0, g, :, at] = jnp.sum(S * c, axis=0, keepdims=True)

    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _nothing_live():
        # every step maps to one block; it goes back as it came
        s_out[...] = s_in[...]
        y_out[...] = jnp.zeros_like(y_out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode(state, layer, dx, la, bm, cm, active, *,
               interpret: bool = False):
    """One token of every ACTIVE slot through layer ``layer`` of the
    stacked states, in place on a donated ``state``.

    state [Lm, B, G, N, R] float32; dx [B, G, R]; la [B, H]; bm, cm
    [B, G, N]; active [B] bool. Returns (y [B, G, R] float32: zeros for
    an idle slot; the state)."""
    _, B, G, N, R = state.shape
    gb = min(_DECODE_GROUPS, G)
    nj = G // gb
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
        return slot, jj, 0, 0

    def state_map(i, j, layer, n, slots):
        slot, jj = at(i, j, layer, n, slots)
        return layer[0], slot, jj, 0, 0

    # a row a group as [B, G, 1, X]: a block's last two dims are the
    # array's, so any number of groups makes a block
    row = pl.BlockSpec((1, gb, 1, R), row_map)
    row_n = pl.BlockSpec((1, gb, 1, N), row_map)
    blk = pl.BlockSpec((1, 1, gb, N, R), state_map)
    a = jnp.exp(_rows(la.astype(F32), G, R))
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, groups=gb, slab=min(_SLAB, R)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, nj),
            in_specs=[row, row, row_n, row_n, blk, row],
            out_specs=[blk, row]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, G, 1, R), F32)],
        # operands count the three scalar-prefetch arrays
        input_output_aliases={7: 0, 8: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), n.reshape(1), slots,
      a[:, :, None], dx.astype(F32)[:, :, None], bm.astype(F32)[:, :, None],
      cm.astype(F32)[:, :, None], state,
      jnp.zeros((B, G, 1, R), F32))[::-1]
    return y[:, :, 0], state


@jax.named_scope("ssm/scan/decode")
def decode_auto(state, layer, dx, la, bm, cm, active):
    """``ssd_decode`` where ``kernel_ok``, its jnp form elsewhere."""
    from .flash import interpret_env

    _, _, G, N, R = state.shape
    if kernel_ok(G, N, R):
        return ssd_decode(state, layer, dx, la, bm, cm, active,
                          interpret=interpret_env())
    return decode_ref(state, layer, dx, la, bm, cm, active)


def _touch_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


@jax.jit
def ssd_untouched(x):
    """``x`` as it is, as the output of a kernel that aliases it and
    rewrites its first tile with itself. A ``lax.switch`` branch that
    hands an operand through unchanged makes XLA COPY it (the
    conditional's result must be defined inside the branch): 4 GB of
    states a layer that is not a mamba layer. A branch that hands back
    this kernel's output, as the mamba branch hands back
    ``ssd_decode``'s, leaves the buffer where it lies."""
    tile = tuple(min(d, t) for d, t in zip(x.shape[-2:],
                                           (4 * _SUBLANES, _LANES)))
    spec = pl.BlockSpec((1,) * (x.ndim - 2) + tile,
                        lambda i: (0,) * x.ndim)
    return pl.pallas_call(
        _touch_kernel, grid=(1,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0})(x)


def untouched(x):
    """``ssd_untouched`` where the decode kernel runs (a TPU), ``x``
    elsewhere."""
    from .flash import tpu_backend_ok

    return ssd_untouched(x) if tpu_backend_ok() else x


# -- prefill ------------------------------------------------------------------

def _prefill_kernel(dx_ref, dxw_ref, e_ref, tot_ref, bt_ref, c_ref, cumc_ref,
                    cumr_ref, s_in, y_ref, s_out, *cb_ref, chunks: int,
                    heads: int, p: int, width: int):
    """One (slot, group, cut of the group's lanes): ``heads`` heads.
    ``cb_ref`` (a group cut in several): C B^T of every chunk, made by
    the group's first cut and read by the others."""
    Q = dx_ref.shape[3]
    per = width // p                       # heads a slab of lanes holds
    tril = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (Q, width), 1) // p
    s_out[0, 0] = s_in[0, 0]
    first_cut = pl.program_id(2) == 0 if cb_ref else None

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=F32, precision=_HI)

    def chunk(c, carry):
        C, BT = c_ref[0, 0, c], bt_ref[0, 0, c]          # [Q, N], [N, Q]
        S = s_out[0, 0]                                   # [N, R]
        inter = dot(C, S) * e_ref[0, 0, c]                # [Q, R]
        s_out[0, 0] = S * tot_ref[0, 0, c] + dot(BT, dxw_ref[0, 0, c])
        if cb_ref:      # once a chunk a group, not once a cut
            @pl.when(first_cut)
            def _first_cut():
                cb_ref[0][c] = dot(C, BT)
            CB = cb_ref[0][c]
        else:
            CB = dot(C, BT)                               # [Q, Q]
        cumc, cumr = cumc_ref[0, 0, c], cumr_ref[0, 0, c]  # [Q, Hg], [Hg, Q]
        for s in range(heads // per):
            at = slice(s * width, (s + 1) * width)
            x = dx_ref[0, 0, c, :, at]                    # [Q, width]
            acc = inter[:, at]
            for k in range(per):
                h = s * per + k
                L = jnp.where(tril, jnp.exp(jnp.minimum(
                    cumc[:, h:h + 1] - cumr[h:h + 1, :], 0.0)), 0.0)
                acc = acc + dot(CB * L, x if per == 1
                                else jnp.where(lane_head == k, x, 0.0))
            y_ref[0, 0, c, :, at] = acc
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


def prefill_cuts(r: int, p: int) -> int:
    """Cuts of a group's ``r`` lanes (heads of ``p``) a prefill program
    each: the fewest whose share is whole heads, whole lane rows and at
    most ``_PREFILL_LANES``; 1 where none is (the group whole, as it
    always ran)."""
    for j in range(1, r // _LANES + 1):
        rb = r // j
        if r % j == 0 and rb <= _PREFILL_LANES and rb % p == 0 \
                and rb % _LANES == 0:
            return j
    return 1


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_prefill(dx, la, bm, cm, state, *, chunk: int = 128,
                interpret: bool = False):
    """The recurrence over T tokens from ``state``, a chunk of ``chunk``
    tokens at a time. dx [B, T, G, R]; la [B, T, H]; bm, cm [B, T, G, N];
    state [B, G, N, R] float32. T is padded to whole chunks with
    identity positions; a T under one chunk (whole sublanes) runs as ONE
    chunk of its own length, which the chunk form is exact at. A group
    wider than ``_PREFILL_LANES`` is cut along them (``prefill_cuts``):
    the grid is (slot, group, cut), the cut innermost, and a group's
    C B^T is made once. Returns (y [B, T, G, R] float32, the state after token T - 1)."""
    B, T, G, R = dx.shape
    H, N = la.shape[-1], bm.shape[-1]
    Hg = H // G
    P = R // Hg
    Q = chunk if T >= chunk or T % _SUBLANES else T
    pad = -T % Q
    dx, la, bm, cm = _pad_tokens((dx, la, bm, cm), pad)
    nC = (T + pad) // Q
    J = prefill_cuts(R, P)
    Rb, Hb = R // J, Hg // J     # lanes and heads a cut

    # [B, T, ...] -> a group's (a cut's) chunks contiguous:
    # [B, G (J), nC, Q, ...]
    def chunks(a):
        a = a.astype(F32).reshape((B, nC, Q) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    cum = jnp.cumsum(la.astype(F32).reshape(B, nC, Q, H), axis=2)
    # heads lie group-major and a cut is whole heads: [B, G J, nC, Q, Hb]
    cum_g = jnp.moveaxis(cum.reshape(B, nC, Q, G * J, Hb), 3, 1)
    last = cum_g[:, :, :, -1:, :]
    wide = lambda a: jnp.repeat(a, P, axis=-1)  # noqa: E731  [.., Hb]->[.., Rb]
    dx_g = chunks(dx.reshape(B, nC * Q, G * J, Rb))
    width = P * max(1, min(_LANES, Rb) // P)

    def spec(*tail):      # of a cut's own rows
        return pl.BlockSpec((1, 1, nC) + tail,
                            lambda b, g, j: (b, g * J + j, 0)
                            + (0,) * len(tail))

    def shared(*tail):    # of the group's B and C, every cut's
        return pl.BlockSpec((1, 1, nC) + tail,
                            lambda b, g, j: (b, g, 0) + (0,) * len(tail))

    blk = pl.BlockSpec((1, 1, N, Rb), lambda b, g, j: (b, g, 0, j))
    y, state = pl.pallas_call(
        functools.partial(_prefill_kernel, chunks=nC, heads=Hb, p=P,
                          width=width),
        grid=(B, G, J),
        in_specs=[spec(Q, Rb), spec(Q, Rb), spec(Q, Rb), spec(1, Rb),
                  shared(N, Q), shared(Q, N), spec(Q, Hb), spec(Hb, Q), blk],
        out_specs=[spec(Q, Rb), blk],
        out_shape=[jax.ShapeDtypeStruct((B, G * J, nC, Q, Rb), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        scratch_shapes=[pltpu.VMEM((nC, Q, Q), F32)] if J > 1 else [],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
    )(dx_g, dx_g * wide(jnp.exp(last - cum_g)), wide(jnp.exp(cum_g)),
      wide(jnp.exp(last)), jnp.swapaxes(chunks(bm), 3, 4), chunks(cm),
      cum_g, jnp.swapaxes(cum_g, 3, 4), state.astype(F32))
    y = jnp.moveaxis(y, 1, 3).reshape(B, nC * Q, G, R)
    return y[:, :T], state


@jax.named_scope("ssm/scan/chunk")
def prefill_auto(dx, la, bm, cm, state, chunk: int):
    """``ssd_prefill`` where ``kernel_ok`` and a chunk is whole lanes,
    the jnp chunk form elsewhere."""
    from .flash import interpret_env

    G, R = dx.shape[2:]
    T = dx.shape[1]
    if kernel_ok(G, bm.shape[-1], R) and (interpret_env()
                                          or chunk % _LANES == 0):
        return ssd_prefill(dx, la, bm, cm, state, chunk=chunk,
                           interpret=interpret_env())
    # a bucket that is not whole chunks: identity positions
    dx, la, bm, cm = _pad_tokens((dx, la, bm, cm), -T % min(chunk, T))
    y, state = chunked_ref(dx, la, bm, cm, state, chunk)
    return y[:, :T], state


# -- the short convolution ----------------------------------------------------

@jax.named_scope("ssm/conv")
def conv(x, tail, weight, bias, lengths=None):
    """Mamba-2's convolution on [x | B | C]: ``kda.conv_taps`` with a
    bias a channel, then SiLU, over a FLAT tail [B, (W - 1) C] (input
    t - (W - 1) + j at lanes [j C, (j + 1) C)). Stored [B, W - 1, C] the
    W - 1 = 3 inputs are the sublanes of every tile, and XLA re-lays the
    whole stack of tails out on the way into the layer loop and back
    (2.4 ms of a 24 ms step, PERF.md Findings PR 42); flat, a decode
    step's taps meet lane-aligned slices and the new tail is the old one
    shifted by C lanes. Returns (y [B, T, C] float32, the new tail)."""
    B, T, C = x.shape
    W = weight.shape[0]
    if T == 1 and lengths is None:
        w = weight.astype(F32)
        y = x[:, 0].astype(F32) * w[W - 1] + sum(
            tail[:, j * C:(j + 1) * C].astype(F32) * w[j]
            for j in range(W - 1))
        new = jnp.concatenate([tail[:, C:], x[:, 0].astype(tail.dtype)],
                              axis=1)
        return jax.nn.silu(y + bias.astype(F32))[:, None], new
    y, new = kda.conv_taps(x, tail.reshape(B, W - 1, C), weight, lengths)
    return jax.nn.silu(y + bias.astype(F32)), new.reshape(B, (W - 1) * C)
