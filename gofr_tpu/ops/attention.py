"""Attention: causal prefill and single-step decode against a KV cache.

Reference-free (GoFr has no compute layer). Designed for the TPU:
  - GQA handled by reshaping Q to [.., kv_heads, group, ..] so the einsum
    stays a large MXU matmul instead of head-looped small ones.
  - Softmax in float32, matmuls in bf16.
  - Decode masks by per-sequence cache length (continuous batching: every
    batch slot has its own cursor).
These jnp paths are the portable baseline (XLA already fuses them well);
they also serve as the numerics reference that the Pallas TPU kernels are
tested against once ``ops.flash`` lands (planned kernel set: flash prefill,
decode attention, quantized matmul).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv_shape(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[B, S, H, D] -> [B, S, n_kv, group, D] without copying."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


# -- heads of half a lane row, two KV heads a row ---------------------------------
#
# A cache row of a head narrower than the 128 lanes is padded to them in
# HBM, and a kernel's tile of it is half empty. Where a head is 64 values
# the cache holds KV heads 2p and 2p + 1 side by side in one row
# [.., KV/2, Smax, 128] (the K or V of a token a KV head apart is one
# reshape), and a query head takes its own half by carrying zeros in the
# other: q' . row = q . k of its KV head, exactly. The value product comes
# back a row wide, each query head's answer in its half. The kernels and
# the jnp forms then run as at 128 with half the KV heads and twice the
# group, handed the softmax scale of the true width.

def pair_rows(x: jnp.ndarray) -> jnp.ndarray:
    """[..., KV, d] -> [..., KV/2, 2d]: KV heads 2p | 2p + 1 in one row."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] // 2, 2 * x.shape[-1]))


def pair_queries(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[B, S, H, d] -> [B, S, H, 2d]: each head's values in its KV head's
    half of the pair's row, zeros in the other."""
    b, s, h, d = q.shape
    qp = q.reshape(b, s, n_kv // 2, 2, h // n_kv, d)
    zero = jnp.zeros_like(qp[:, :, :, 0])
    return jnp.stack([jnp.concatenate([qp[:, :, :, 0], zero], -1),
                      jnp.concatenate([zero, qp[:, :, :, 1]], -1)],
                     axis=3).reshape(b, s, h, 2 * d)


def unpair_heads(o: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[B, S, H, 2d] -> [B, S, H, d]: each head's own half of what a
    paired attention returned."""
    b, s, h, d2 = o.shape
    op = o.reshape(b, s, n_kv // 2, 2, h // n_kv, 2, d2 // 2)
    return jnp.stack([op[:, :, :, 0, :, 0], op[:, :, :, 1, :, 1]],
                     axis=3).reshape(b, s, h, d2 // 2)


def block_causal(s: int, block: int) -> jnp.ndarray:
    """[s, s] bool: position i sees j iff ``j // block <= i // block``
    (a block's positions see each other both ways, and every block
    before theirs); ``block`` 0 or 1: the lower triangle."""
    at = jnp.arange(s) // max(block, 1)
    return at[None, :] <= at[:, None]


@jax.named_scope("causal_attention")
def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     mask: jnp.ndarray | None = None,
                     window: int = 0, block: int = 0) -> jnp.ndarray:
    """Causal self-attention for prefill.

    q: [B, S, H, D]; k, v: [B, S, KV, D] (KV may divide H for GQA).
    mask: optional [B, S] validity mask (1 = real token, 0 = padding).
    window > 0: a band, position p sees the ``window`` positions
    (p - window, p]. block > 1: block-causal (``block_causal``).
    Returns [B, S, H, D].
    """
    b, s, h, d = q.shape
    n_kv = k.shape[2]
    scale = d ** -0.5

    qg = _repeat_kv_shape(q * scale, n_kv)  # [B,S,KV,G,D]
    # scores: [B, KV, G, S, S]
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    causal = block_causal(s, block) if block > 1 \
        else jnp.tril(jnp.ones((s, s), dtype=bool))
    if window:
        causal &= ~jnp.tril(jnp.ones((s, s), dtype=bool), -window)
    scores = jnp.where(causal[None, None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


@jax.named_scope("decode_attention")
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     lengths: jnp.ndarray) -> jnp.ndarray:
    """Single-token decode attention against a preallocated KV cache.

    q: [B, 1, H, D]; k_cache, v_cache: [B, Smax, KV, D];
    lengths: [B] int32 — number of valid cache entries per sequence
    (INCLUDING the token being decoded, already written to the cache).
    Returns [B, 1, H, D].
    """
    b, _, h, d = q.shape
    smax = k_cache.shape[1]
    n_kv = k_cache.shape[2]
    scale = d ** -0.5

    qg = _repeat_kv_shape(q * scale, n_kv)[:, 0]  # [B,KV,G,D]
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                        preferred_element_type=jnp.float32)  # [B,KV,G,Smax]
    valid = jnp.arange(smax)[None, :] < lengths[:, None]  # [B,Smax]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v_cache)
    return out.reshape(b, 1, h, d)


@jax.named_scope("decode_attention_appended")
def decode_attention_appended(q: jnp.ndarray, k_cache: jnp.ndarray,
                              v_cache: jnp.ndarray, k_new: jnp.ndarray,
                              v_new: jnp.ndarray, lengths: jnp.ndarray,
                              k_scale: jnp.ndarray | None = None,
                              v_scale: jnp.ndarray | None = None,
                              exclude: jnp.ndarray | None = None,
                              scale: float | None = None) -> jnp.ndarray:
    """Decode attention over the cache PLUS the current token's k/v, before
    that token has been written back.

    Mathematically identical to writing the token at position ``lengths``
    and calling ``decode_attention`` with lengths+1, but lets the serving
    step keep the cache read-only inside the layer scan and defer all
    writes to one post-scan scatter on the donated buffer, instead of
    rewriting the whole cache every token.

    This is the reference, and the path for backends and shapes the
    flash-decode kernel does not take (ops.flash_decode.kernel_block). It
    attends over all ``Smax`` positions whatever ``lengths`` says, and
    handed a per-layer slice of the stacked cache by ``lax.scan`` it is
    preceded, on the chip, by a copy of that slice: XLA materialises each
    layer's K and V (0.5 s of a 2.95 s trace, PERF.md Findings PR 25),
    it does not read them in place.

    q: [B, 1, H, D]; k_cache/v_cache: [B, KV, Smax, D], a layer of the
    cache in its own order (models.llama.KVCache);
    k_new/v_new: [B, 1, KV, D]; lengths: [B] valid entries (EXCLUDING the
    current token). Returns [B, 1, H, D]. ``exclude`` [B]: a cache row
    not to read whatever ``lengths`` says (a ring's oldest row, which the
    step is about to overwrite: ops.flash_decode.ring_rows). ``scale``:
    the softmax scale where it is not D^-1/2 (paired heads, above).

    INT8 cache: when ``k_scale``/``v_scale`` [B, KV, Smax] are given the
    cache tensors are per-vector int8 (ops.quant.quantize_kv). The scale is
    constant over the contracted head_dim, so it is applied to the SCORES
    (k side) and folded into the probabilities (v side) — both tiny
    [B,KV,G,Smax] tensors — and the int8->bf16 upcast fuses into the
    einsum: the cache is never materialized in bf16, halving decode's
    dominant HBM stream. k_new/v_new stay bf16 (fresh this step).
    """
    b, _, h, d = q.shape
    n_kv, smax = k_cache.shape[1], k_cache.shape[2]
    scale = scale or d ** -0.5

    qg = _repeat_kv_shape(q * scale, n_kv)[:, 0]  # [B,KV,G,D]
    scores_c = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache.astype(qg.dtype),
                          preferred_element_type=jnp.float32)
    if k_scale is not None:
        # k_scale [B,KV,Smax] beside scores [B,KV,G,Smax]
        scores_c = scores_c * k_scale[:, :, None, :]
    valid = jnp.arange(smax)[None, :] < lengths[:, None]
    if exclude is not None:
        valid &= jnp.arange(smax)[None, :] != exclude[:, None]
    scores_c = jnp.where(valid[:, None, None, :], scores_c, NEG_INF)
    scores_s = jnp.einsum("bkgd,btkd->bkgt", qg, k_new,
                          preferred_element_type=jnp.float32)  # [B,KV,G,1]
    probs = jax.nn.softmax(jnp.concatenate([scores_c, scores_s], axis=-1),
                           axis=-1)
    probs_c = probs[..., :smax]
    if v_scale is not None:
        probs_c = probs_c * v_scale[:, :, None, :]
    vdt = q.dtype if v_scale is not None else v_cache.dtype
    out = (jnp.einsum("bkgt,bktd->bkgd", probs_c.astype(vdt),
                      v_cache.astype(vdt))
           + jnp.einsum("bkgt,btkd->bkgd", probs[..., smax:].astype(v_new.dtype),
                        v_new))
    return out.reshape(b, 1, h, d)


@jax.named_scope("window_attention_appended")
def window_attention_appended(q: jnp.ndarray, k_cache: jnp.ndarray,
                              v_cache: jnp.ndarray, k_new: jnp.ndarray,
                              v_new: jnp.ndarray, lengths: jnp.ndarray,
                              k_scale: jnp.ndarray | None = None,
                              v_scale: jnp.ndarray | None = None,
                              within: jnp.ndarray | None = None,
                              scale: float | None = None) -> jnp.ndarray:
    """decode_attention_appended generalized to a W-token window — the
    speculative-decoding verify pass: window query j attends the cache
    prefix (positions < lengths[b], per slot) plus window positions <= j,
    before any of the window's KV is written back. W=1 reduces exactly to
    the appended decode step; unlike chunk_attention the prefix boundary
    is PER ROW (every slot sits at its own cursor).

    q: [B, W, H, D]; k_cache/v_cache: [B, KV, Smax, D];
    k_new/v_new: [B, W, KV, D]; lengths: [B] valid cache entries
    (EXCLUDING the window). Returns [B, W, H, D]. Int8 cache scales are
    applied score/prob-side exactly as in decode_attention_appended.
    ``within`` [W, W] bool: which window positions a window query sees
    (None: those at or before it; all of them for a block that is
    denoised as a whole, ``block_causal(W, W)``). ``scale``: the softmax
    scale where it is not D^-1/2.
    """
    b, w, h, d = q.shape
    n_kv, smax = k_cache.shape[1], k_cache.shape[2]
    scale = scale or d ** -0.5

    qg = _repeat_kv_shape(q * scale, n_kv)  # [B,W,KV,G,D]
    scores_c = jnp.einsum("bwkgd,bktd->bkgwt", qg, k_cache.astype(qg.dtype),
                          preferred_element_type=jnp.float32)
    if k_scale is not None:
        scores_c = scores_c * k_scale[:, :, None, None, :]
    valid = jnp.arange(smax)[None, :] < lengths[:, None]     # [B, Smax]
    scores_c = jnp.where(valid[:, None, None, None, :], scores_c, NEG_INF)
    scores_s = jnp.einsum("bwkgd,btkd->bkgwt", qg, k_new,
                          preferred_element_type=jnp.float32)  # [B,KV,G,W,W]
    causal = jnp.tril(jnp.ones((w, w), bool)) if within is None else within
    scores_s = jnp.where(causal[None, None, None], scores_s, NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([scores_c, scores_s], axis=-1),
                           axis=-1)
    probs_c = probs[..., :smax]
    if v_scale is not None:
        probs_c = probs_c * v_scale[:, :, None, None, :]
    vdt = q.dtype if v_scale is not None else v_cache.dtype
    out = (jnp.einsum("bkgwt,bktd->bwkgd", probs_c.astype(vdt),
                      v_cache.astype(vdt))
           + jnp.einsum("bkgwt,btkd->bwkgd",
                        probs[..., smax:].astype(v_new.dtype), v_new))
    return out.reshape(b, w, h, d)


# cached rows a trip of ``chunk_attention``'s walk scores
_CHUNK_BLOCK = 256


def chunk_block(smax: int) -> int:
    """The block ``chunk_attention`` walks a slot of ``smax`` rows in:
    ``_CHUNK_BLOCK``, fitted to a slot it does not divide."""
    from .flash import fit_block

    return fit_block(smax, _CHUNK_BLOCK)


@jax.named_scope("chunk_attention")
def chunk_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                    k_new: jnp.ndarray, v_new: jnp.ndarray,
                    start: jnp.ndarray,
                    k_scale: jnp.ndarray | None = None,
                    v_scale: jnp.ndarray | None = None,
                    scale: float | None = None,
                    block: int = 0) -> jnp.ndarray:
    """Chunked-prefill attention: a block of C new tokens at positions
    [start, start+C) attends to the cache prefix (positions < start) plus
    causally within the chunk — the long-prompt path, processing prompts in
    fixed-size chunks so arbitrary prompt lengths serve from a small
    lattice of compiled shapes.

    One softmax over both, taken as a running one: it starts from the
    chunk's own tokens and the cached rows are walked a block of
    ``_CHUNK_BLOCK`` at a time up to ``start`` (a traced scalar), so a
    reserved row costs nothing (a chunk at position 0 fetches none) and
    the float32 scores held at once are heads x C x block, not heads x
    C x Smax (168 MB a layer at Mistral's 32 x 512 x 2,560).

    q: [B, C, H, D]; k_cache/v_cache: [B, KV, Smax, D];
    k_new/v_new: [B, C, KV, D]; start: scalar int32.
    ``k_scale``/``v_scale`` [B, KV, Smax]: per-vector scales for int8
    caches (see decode_attention_appended — same fused-dequant scheme,
    a block at a time).
    Trailing padding inside the chunk is harmless: causality means padded
    positions are never attended BY valid ones. ``scale``: the softmax
    scale where it is not D^-1/2 (paired heads). ``block`` > 1: the
    chunk's tokens see each other block-causally (``block_causal``; the
    chunk starts on a block's first position, so no block is cut by its
    edge, and the padding is whole blocks or ends one that no valid
    block comes after). Returns [B, C, H, D].
    """
    b, c, h, d = q.shape
    n_kv, smax = k_cache.shape[1], k_cache.shape[2]
    scale = scale or d ** -0.5
    walk = chunk_block(smax)
    vdt = q.dtype if v_scale is not None else v_cache.dtype

    qg = _repeat_kv_shape(q * scale, n_kv)  # [B,C,KV,G,D]
    scores_n = jnp.einsum("bskgd,btkd->bkgst", qg, k_new,
                          preferred_element_type=jnp.float32)  # [B,KV,G,C,C]
    causal = block_causal(c, block) if block > 1 \
        else jnp.tril(jnp.ones((c, c), dtype=bool))
    scores_n = jnp.where(causal[None, None, None], scores_n, NEG_INF)
    m = jnp.max(scores_n, -1, keepdims=True)                 # [B,KV,G,C,1]
    p = jnp.exp(scores_n - m)
    acc = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v_new.dtype), v_new,
                     preferred_element_type=jnp.float32)

    def fold(j, carry):
        m, l, acc = carry

        def tile(x):
            return jax.lax.dynamic_slice_in_dim(x, j * walk, walk, 2)

        s = jnp.einsum("bskgd,bktd->bkgst", qg, tile(k_cache).astype(qg.dtype),
                       preferred_element_type=jnp.float32)  # [B,KV,G,C,walk]
        if k_scale is not None:
            s = s * tile(k_scale)[:, :, None, None, :]
        s = jnp.where(j * walk + jnp.arange(walk) < start, s, NEG_INF)
        m_next = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        corr, p = jnp.exp(m - m_next), jnp.exp(s - m_next)
        l = l * corr + jnp.sum(p, -1, keepdims=True)
        if v_scale is not None:
            p = p * tile(v_scale)[:, :, None, None, :]
        acc = acc * corr + jnp.einsum(
            "bkgst,bktd->bkgsd", p.astype(vdt), tile(v_cache).astype(vdt),
            preferred_element_type=jnp.float32)
        return m_next, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, (start + walk - 1) // walk, fold,
        (m, jnp.sum(p, -1, keepdims=True), acc))
    out = (acc / l).astype(jnp.result_type(vdt, v_new.dtype))
    return jnp.moveaxis(out, 3, 1).reshape(b, c, h, d)


def ring_held(rows: int, end) -> jnp.ndarray:
    """The position each of a ring's ``rows`` rows holds once positions
    [0, end) have been written, position p at row p % rows: the last
    p < end with p % rows == r, negative where row r holds none yet.
    ``end``: int32 scalar or [...]; returns [..., rows]."""
    r = jnp.arange(rows, dtype=jnp.int32)
    end = jnp.asarray(end, jnp.int32)[..., None]
    return r + rows * ((end - 1 - r) // rows)


@jax.named_scope("ring_chunk_attention")
def ring_chunk_attention(q: jnp.ndarray, k_ring: jnp.ndarray,
                         v_ring: jnp.ndarray, k_new: jnp.ndarray,
                         v_new: jnp.ndarray,
                         start: jnp.ndarray) -> jnp.ndarray:
    """``chunk_attention`` for a sliding-window layer whose cache is a
    ring: C new tokens at positions [start, start + C) attend, each to
    the ``W`` positions (p - W, p], over the ring as it stands before
    the chunk (the last W positions below ``start``) and causally within
    the chunk. The chunk's rows go into the ring afterwards (the caller:
    a chunk as long as the ring overwrites all of it).

    q: [B, C, H, D]; k_ring/v_ring: [B, KV, W, D], position p at row
    p % W; k_new/v_new: [B, C, KV, D]; start: scalar int32. Returns
    [B, C, H, D]."""
    b, c, h, d = q.shape
    n_kv, w = k_ring.shape[1], k_ring.shape[2]
    qg = _repeat_kv_shape(q * d ** -0.5, n_kv)  # [B,C,KV,G,D]
    q_pos = start + jnp.arange(c, dtype=jnp.int32)
    held = ring_held(w, start)                                   # [W]
    seen = (held[None, :] >= 0) & (held[None, :] > q_pos[:, None] - w)
    scores_c = jnp.einsum("bskgd,bktd->bkgst", qg, k_ring.astype(qg.dtype),
                          preferred_element_type=jnp.float32)  # [B,KV,G,C,W]
    scores_c = jnp.where(seen[None, None, None], scores_c, NEG_INF)
    scores_n = jnp.einsum("bskgd,btkd->bkgst", qg, k_new,
                          preferred_element_type=jnp.float32)  # [B,KV,G,C,C]
    band = jnp.tril(jnp.ones((c, c), dtype=bool)) \
        & ~jnp.tril(jnp.ones((c, c), dtype=bool), -w)
    scores_n = jnp.where(band[None, None, None], scores_n, NEG_INF)
    probs = jax.nn.softmax(
        jnp.concatenate([scores_c, scores_n], axis=-1), axis=-1)
    out = (jnp.einsum("bkgst,bktd->bskgd",
                      probs[..., :w].astype(v_ring.dtype), v_ring)
           + jnp.einsum("bkgst,btkd->bskgd",
                        probs[..., w:].astype(v_new.dtype), v_new))
    return out.reshape(b, c, h, d)


@jax.named_scope("full_attention")
def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Bidirectional attention (BERT/ViT encoders). Shapes as causal_attention."""
    b, s, h, d = q.shape
    n_kv = k.shape[2]
    scale = d ** -0.5
    qg = _repeat_kv_shape(q * scale, n_kv)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)
