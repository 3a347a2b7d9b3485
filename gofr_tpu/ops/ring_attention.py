"""Ring attention: causal attention with the SEQUENCE dimension sharded.

Long-context prefill/training above single-chip HBM needs the sequence
axis distributed (SURVEY §5 "long-context / sequence parallelism"; the
task's first-class long-context requirement). GSPMD's automatic answer
to a sequence-sharded attention is poor — resharding the [S, S] score
space triggers "involuntary full rematerialization" (the warning the
dryrun notes suppress by keeping sp=1). Ring attention sidesteps GSPMD
entirely: under ``shard_map`` each device keeps its Q shard pinned and
the K/V shards ROTATE around the ``sp`` axis with ``ppermute`` — n-1
neighbor exchanges over ICI, each overlapping the previous block's
compute, never an all-gather and never a full [S, S] anything:

    peak memory per device: O(S/n * S/n) scores + 2 K/V shards
    comm per layer: 2 * (n-1) * |KV shard| point-to-point (ICI ring)

The online-softmax recurrence (same math as ops.flash) makes the
rotation exact: each incoming K/V block folds into running (m, l, acc).

Causality with contiguous shards in axis order: block t on device i
holds shard j = (i - t) mod n; j > i blocks are fully masked (their
compute is wasted ring slack — the standard causal-ring imbalance),
j == i is the intra-shard causal triangle, j < i is fully visible.
Right-padded batches mask by GLOBAL ``lengths`` exactly like
ops.attention.causal_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .attention import NEG_INF, causal_attention


@jax.named_scope("ring_causal_attention")
def ring_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          lengths: jnp.ndarray | None, *,
                          axis_name: str) -> jnp.ndarray:
    """Per-device body — call under shard_map with the sequence dim of
    q/k/v sharded over ``axis_name`` (contiguous shards in axis-index
    order).

    q: [B, Ss, H, D] local shard; k/v: [B, Ss, KV, D]; lengths: [B]
    GLOBAL valid lengths (replicated), None = all valid.
    Returns the local output shard [B, Ss, H, D] in q.dtype.
    """
    b, ss, h, d = q.shape
    n_kv = k.shape[2]
    g = h // n_kv
    scale = d ** -0.5
    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.psum(1, axis_name)

    qg = (q * scale).reshape(b, ss, n_kv, g, d)
    q_pos = idx * ss + jnp.arange(ss, dtype=jnp.int32)        # [Ss]

    # derive the running-stat carries from qg so they carry the same
    # shard_map varying-axes type as the loop outputs (plain constants
    # are "unvarying" and the fori_loop carry types would not match)
    zero = qg.astype(jnp.float32) * 0.0                       # [B,Ss,KV,G,D]
    m0 = zero[..., 0] + NEG_INF
    l0 = zero[..., 0]
    acc0 = zero
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        k_t, v_t, m, l, acc = carry
        src = (idx - t) % n                                   # shard held
        k_pos = src * ss + jnp.arange(ss, dtype=jnp.int32)    # [Ss]

        s = jnp.einsum("bskgd,btkd->bskgt", qg,
                       k_t.astype(qg.dtype),
                       preferred_element_type=jnp.float32)    # [B,Ss,KV,G,St]
        mask = k_pos[None, :] <= q_pos[:, None]               # [Ss, St]
        if lengths is not None:
            mask = mask[None] & (k_pos[None, None, :]
                                 < lengths[:, None, None])    # [B,Ss,St]
            s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        else:
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p.astype(v_t.dtype), v_t,
            preferred_element_type=jnp.float32)
        # rotate K/V one hop: after the exchange this device holds shard
        # (idx - t - 1) mod n. The last iteration's rotation returns the
        # shards to their owners (harmless; keeps the loop uniform).
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        return k_t, v_t, m_new, l_new, acc_new

    _, _, m, l, acc = jax.lax.fori_loop(0, n, body, (k, v, m0, l0, acc0))
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(b, ss, h, d).astype(q.dtype)


def make_ring_attention(mesh, *, axis_name: str = "sp",
                        batch_axes=("dp", "fsdp", "ep"),
                        head_axis: str = "tp"):
    """shard_map-wrapped ring attention over ``mesh``.

    Returns attend(q [B,S,H,D], k, v [B,S,KV,D], lengths [B] | None)
    with batch sharded over ``batch_axes``, sequence over ``axis_name``,
    and — when both H and KV divide it — heads over ``head_axis``, so a
    tp>1 mesh keeps its head sharding instead of all-gathering q/k/v and
    computing attention redundantly per tp device. Collectives ride the
    mesh's ``axis_name`` ring (ICI when the mesh is laid out that way).

    Shapes that don't divide the mesh axes (ragged batch, odd sequence)
    fall back to the dense reference at trace time — layout is never
    allowed to turn into a shape crash."""
    batch = tuple(a for a in batch_axes if a in mesh.shape)
    bspec = batch if batch else None
    nb = 1
    for a in batch:
        nb *= mesh.shape[a]
    nsp = mesh.shape.get(axis_name, 1)
    ntp = mesh.shape.get(head_axis, 1)

    def attend(q, k, v, lengths=None):
        b, s, h, d = q.shape
        n_kv = k.shape[2]
        if b % nb or s % nsp:
            mask = None
            if lengths is not None:
                mask = (jnp.arange(s, dtype=jnp.int32)[None, :]
                        < lengths[:, None])
            return causal_attention(q, k, v, mask=mask)
        heads_shard = (ntp > 1 and h % ntp == 0 and n_kv % ntp == 0)
        hax = head_axis if heads_shard else None
        qspec = P(bspec, axis_name, hax, None)
        inner = functools.partial(ring_causal_attention,
                                  axis_name=axis_name)
        if lengths is None:
            fn = jax.shard_map(lambda q_, k_, v_: inner(q_, k_, v_, None),
                               mesh=mesh, in_specs=(qspec, qspec, qspec),
                               out_specs=qspec)
            return fn(q, k, v)
        fn = jax.shard_map(inner, mesh=mesh,
                           in_specs=(qspec, qspec, qspec, P(bspec)),
                           out_specs=qspec)
        return fn(q, k, v, lengths)

    return attend
