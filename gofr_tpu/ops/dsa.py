"""The learned selection of sparse latent attention (the "lightning
indexer" of DeepSeek-V3.2's sparse attention): which cached positions a
query's softmax runs over.

A full layer caches, beside its latent row, one small index key a token
(``index_head_dim`` values, all index heads share it). A query brings
``index_heads`` index queries and one weight a head, and scores position
``s`` as

    I[t, s] = sum_j  w[t, j] * relu(qI[t, j] . kI[s])

in float32. It keeps the ``top_k`` positions ``s <= t`` of highest score,
all of them while there are no more than ``top_k`` (``kept``), and
attention (``ops/mla.py``) masks every other row out.

``kept`` does not sort. The k-th largest score a row is found by
bisection over the scores' bit patterns (a float32's order is its bits'
order once the sign is folded in): 32 counts over the row, each one
compare-and-sum that streams nothing but the scores, where a top-k of
2,048 in 4,097 on the TPU is a sort (on a v5e, three layers of 128 slots:
0.2 ms against 5.7; PERF.md, Findings PR 46). Ties at the threshold are broken as ``jax.lax.top_k``
breaks them, lowest position first, so the mask is exactly the set
``top_k``'s indices name: the plain reference
(benchmarks/references/dots3_note.py) takes it that way.

``decode_scores`` is one token a slot over the slot's cached keys: on a
TPU a Pallas kernel over the stacked key table ``[L, B, Smax, d]`` in
place, a grid of (slot, key block) that fetches a slot's live blocks
only (a dead block's index map repeats the last live block, which Pallas
does not fetch twice) and computes nothing for them; the jnp form
elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF

_LANES = 128
F32 = jnp.float32


def scores(q_idx, w_idx, keys, head_block: int = 8):
    """I[b, t, s] for queries against keys of the same sequence:
    q_idx [B, T, Hi, d]; w_idx [B, T, Hi] float32 (the scale folded in);
    keys [B, S, d]. Returns [B, T, S] float32, nothing masked. Index
    heads a block at a time: [B, Hi, T, S] whole is 64 x the result."""
    b, t, hi, d = q_idx.shape
    hb = min(head_block, hi)
    while hi % hb:
        hb -= 1
    keys = keys.astype(q_idx.dtype)
    qb = jnp.moveaxis(q_idx.reshape(b, t, hi // hb, hb, d), 2, 0)
    wb = jnp.moveaxis(w_idx.reshape(b, t, hi // hb, hb), 2, 0)

    def block(acc, xs):
        q, w = xs
        s = jnp.einsum("bthd,bsd->bths", q, keys,
                       preferred_element_type=F32)
        return acc + jnp.einsum("bths,bth->bts", jax.nn.relu(s),
                                w.astype(F32)), None

    out, _ = jax.lax.scan(block, jnp.zeros((b, t, keys.shape[1]), F32),
                          (qb, wb))
    return out


def decode_scores_reference(q_idx, w_idx, keys, lengths):
    """One token a slot: q_idx [B, Hi, d]; w_idx [B, Hi] float32;
    keys [B, Smax, d]; lengths [B]. Returns [B, Smax] float32, NEG_INF
    at and past the cursor."""
    s = scores(q_idx[:, None], w_idx[:, None], keys,
               head_block=q_idx.shape[1])[:, 0]
    live = jnp.arange(keys.shape[1])[None, :] < lengths[:, None]
    return jnp.where(live, s, NEG_INF)


def _scores_kernel(layer_ref, len_ref, q_ref, w_ref, k_ref, o_ref, *,
                   block_s: int):
    b, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(j * block_s < n)
    def _live():
        s = jax.lax.dot_general(q_ref[0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)   # [Hi, BS]
        s = jnp.sum(jax.nn.relu(s) * w_ref[0], axis=0, keepdims=True)
        pos = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        o_ref[0] = jnp.where(pos < n, s, NEG_INF)

    @pl.when(j * block_s >= n)
    def _dead():
        o_ref[0] = jnp.full((1, block_s), NEG_INF, F32)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def index_scores_stacked(q_idx, w_idx, keys, lengths, layer, *,
                         block_s: int, interpret: bool = False):
    """``decode_scores_reference`` over layer ``layer`` of the stacked
    key table ``keys`` [L, B, Smax, d], fetching only the blocks
    ``lengths`` (0 for a slot whose cache must not be read) says are
    live."""
    b, hi, d = q_idx.shape
    smax = keys.shape[2]
    lengths = lengths.astype(jnp.int32)

    def key_block(i, j, layer_ref, len_ref):
        last = jnp.maximum((len_ref[i] + block_s - 1) // block_s - 1, 0)
        return layer_ref[0], i, jnp.minimum(j, last), 0

    out = pl.pallas_call(
        functools.partial(_scores_kernel, block_s=block_s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, smax // block_s),
            in_specs=[
                pl.BlockSpec((1, hi, d), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((1, hi, 1), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((1, 1, block_s, d), key_block)],
            out_specs=pl.BlockSpec((1, 1, block_s),
                                   lambda i, j, *_: (i, 0, j))),
        out_shape=jax.ShapeDtypeStruct((b, 1, smax), F32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths,
      q_idx.astype(keys.dtype), w_idx.astype(F32)[..., None], keys)
    return out[:, 0]


def scores_block(keys) -> int | None:
    """The score kernel's key block where backend and shapes allow it,
    None where the scores stay on the jnp form: not a TPU, a key that is
    not whole lanes, a table shorter than a lane tile.
    ``GOFR_FLASH_INTERPRET=1`` runs the kernel interpreted anywhere."""
    from .flash import fit_block, interpret_env, tpu_backend_ok

    smax, d = keys.shape[2], keys.shape[3]
    block_s = fit_block(smax, 2048)
    if interpret_env():
        return block_s
    if d % _LANES or block_s % _LANES or not tpu_backend_ok():
        return None
    return block_s


@jax.named_scope("dsa/index_scores")
def decode_scores(q_idx, w_idx, keys, lengths, layer, *,
                  block_s: int | None):
    """The index scores of one token a slot against layer ``layer`` of
    the stacked key table: the kernel where ``block_s``
    (``scores_block``'s answer) says so, the jnp form over the layer's
    slice otherwise."""
    if block_s:
        from .flash import interpret_env

        return index_scores_stacked(q_idx, w_idx, keys, lengths, layer,
                                    block_s=block_s,
                                    interpret=interpret_env())
    layer_keys = jax.lax.dynamic_index_in_dim(keys, layer, 0, keepdims=False)
    return decode_scores_reference(q_idx, w_idx, layer_keys, lengths)


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    neg = bits >> 31 == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))


@jax.named_scope("dsa/top_k")
def kept(score, valid, k: int):
    """The ``k`` valid positions of highest ``score`` along the last
    axis, as a mask: score [..., N] float32, valid [..., N] bool. Every
    valid position where there are no more than ``k``. Equal scores at
    the threshold go to the lowest positions, as ``jax.lax.top_k``'s
    indices do."""
    key = jnp.where(valid, _ordered(score), jnp.uint32(0))

    def bit(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= trial[..., None], axis=-1) >= k
        return jnp.where(enough, trial, found)

    # the largest threshold that at least k keys reach: the k-th largest
    # key (0, which every key reaches, where there are fewer than k)
    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(key.shape[:-1], jnp.uint32))[..., None]
    above = key > kth
    ties = key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(ties, axis=-1) <= room
    return valid & (above | (ties & first))
