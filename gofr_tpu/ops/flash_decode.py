"""Pallas TPU flash-decode attention: stream the int8 KV cache once.

Decode attention is the least XLA-friendly part of the serving step: the
cache slice [B, S, KV, hd] is int8 with per-vector scales, and the jnp
path (ops.attention.decode_attention_appended) leaves it to the compiler
to keep the int8->bf16 upcast fused into the einsums. When XLA instead
materializes dequantized copies, decode pays the cache stream ~3x
(int8 read + bf16 write + bf16 read) — at 8B/batch-64 shapes that is
~20 ms/step of avoidable HBM traffic (see PERF.md roofline).

This kernel makes the single-pass guarantee structural: a
(B, S/BLOCK_S) grid streams each [BLOCK_S, KV, hd] cache tile from HBM
into VMEM exactly once (int8 on the wire, upcast in-register), runs the
online-softmax recurrence, and emits UNNORMALIZED (acc, m, l) running
stats. The current token's k/v — not yet written to the cache
(llama.decode_step defers the write to one post-scan scatter) — folds
in afterwards with the standard flash combination, in jnp:

    m_t = max(m_c, s_new);  l_t = l_c*e^(m_c-m_t) + e^(s_new-m_t)
    out = (acc_c*e^(m_c-m_t) + e^(s_new-m_t) * v_new) / l_t

which is exact, costs O(B*H*D), and cleanly handles empty slots
(length 0 => l_c = 0 => out = v_new's softmax of one element).

GQA geometry (the v2 redesign): with H=32 query heads over KV=8 heads,
the naive per-kv-head loop does G=4-row matmuls and 4-sublane
read-modify-writes — both far below the MXU's 128x128 / the VPU's
8-sublane granule, and the r03 A/B measured it ~1.8x SLOWER than the
XLA path it was meant to beat. Instead the query block is expanded
host-side into a BLOCK-DIAGONAL [H, KV*D] matrix (q_bd[h, kv*D+d] = 0
unless kv == kv(h)), so each tile does ONE dense [H, KV*D] @ [KV*D, BS]
MXU matmul for the scores and one [H, BS] @ [BS, KV*D] for the values —
8x the MACs, all of them free next to the cache stream (8.6 GFLOP/step
vs ~5.5 ms of int8 HBM traffic at 8B dims), and zero sub-granule
slicing inside the kernel. The [H, KV*D] accumulator's kv(h) slice is
selected after the kernel, again in O(B*H*D) jnp.

Sharding (same as ops.flash): a pallas_call is opaque to the GSPMD
partitioner, so on a mesh ``decode_attention_auto`` wraps the kernel in
``shard_map`` over the tp (and data) axes — every device streams only
its local [KV/tp] head shard of the cache, no collectives inside
attention (flash_decode_sharded). The jnp reference remains the
fallback when tp would split a KV head. Dispatch via
``decode_attention_auto``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, decode_attention_appended

_LANES = 128


def _decode_kernel(lengths_ref, qbd_ref, k_ref, v_ref, ks_ref, vs_ref,
                   acc_ref, m_ref, l_ref, *,
                   block_s: int, n_kv: int, quant: bool):
    """One (batch, s-block) step. Scratchless: acc/m/l ARE the outputs,
    revisited across the sequential s dimension (the output block index
    map ignores si, so the tiles stay resident in VMEM until the last
    s-block flushes them)."""
    si = pl.program_id(1)
    length = lengths_ref[pl.program_id(0)]
    h = qbd_ref.shape[1]
    g = h // n_kv

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # blocks entirely past the valid prefix skip compute (the runtime
    # still streams them; skipping the math is the available win)
    @pl.when(si * block_s < length)
    def _compute():
        qbd = qbd_ref[0]                                   # [H, KV*D]
        k_flat = k_ref[0].reshape(block_s, -1)             # [BS, KV*D]
        v_flat = v_ref[0].reshape(block_s, -1)
        # scores: block-diagonal q rows zero out every kv plane but kv(h),
        # so the dense contraction equals the per-head dot
        s = jax.lax.dot_general(
            qbd, k_flat.astype(qbd.dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H, BS]
        if quant:
            ks = ks_ref[0]                                  # [KV, BS]
            ks_h = jnp.broadcast_to(ks[:, None, :],
                                    (n_kv, g, block_s)).reshape(h, block_s)
            s = s * ks_h
        pos = si * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)                     # [1, BS]
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[0, :, :1]                            # [H, 1]
        l_prev = l_ref[0, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                              # [H, BS]
        # fully-masked blocks never reach here (pl.when), and within a
        # reached block masked positions give exp(NEG_INF - m) = 0
        corr = jnp.exp(m_prev - m_new)                      # [H, 1]
        l_ref[0] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), (h, _LANES))
        m_ref[0] = jnp.broadcast_to(m_new, (h, _LANES))
        if quant:
            vs = vs_ref[0]                                  # [KV, BS]
            vs_h = jnp.broadcast_to(vs[:, None, :],
                                    (n_kv, g, block_s)).reshape(h, block_s)
            p = p * vs_h
        # pv contraction in q's dtype (bf16 in serving, f32 in the
        # numerics tests) — matches decode_attention_appended's vdt.
        # acc is [H, KV*D]; only the kv(h) slice is meaningful per row
        # (selected after the kernel), the rest is harmless extra MACs.
        acc_ref[0] = acc_ref[0] * corr + jax.lax.dot_general(
            p.astype(qbd.dtype), v_flat.astype(qbd.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H, KV*D]


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def _flash_decode_cache(q, k_cache, v_cache, lengths, k_scale, v_scale,
                        *, block_s: int = 128, interpret: bool = False):
    """Cache-side running stats: returns (acc [B,H,D] f32 unnormalized,
    m [B,H,LANES] f32, l [B,H,LANES] f32) over valid cache positions.

    q: [B, H, D]; k_cache/v_cache: [B, S, KV, D] (int8 with scales
    [B, S, KV], or dense); lengths: [B] int32 valid entries."""
    b, h, d = q.shape
    smax, n_kv = k_cache.shape[1], k_cache.shape[2]
    g = h // n_kv
    if smax % block_s:
        raise ValueError(f"S={smax} not divisible by block_s={block_s}")
    quant = k_scale is not None
    if not quant:  # uniform kernel signature: dummy scale planes
        k_scale = jnp.ones((b, smax, n_kv), jnp.float32)
        v_scale = jnp.ones((b, smax, n_kv), jnp.float32)
    # [B, S, KV] -> [B, KV, S]: tiny (scales), and inside the kernel the
    # [KV, BS] tile broadcasts to [H, BS] along sublanes for free
    ks_t = jnp.swapaxes(k_scale, 1, 2).astype(jnp.float32)
    vs_t = jnp.swapaxes(v_scale, 1, 2).astype(jnp.float32)
    # block-diagonal query expansion (see module docstring): scale folded
    # in here so the kernel never touches q again
    qh = (q * (d ** -0.5)).reshape(b, n_kv, g, d)
    eye = jnp.eye(n_kv, dtype=q.dtype)
    q_bd = jnp.einsum("bkgd,kK->bgkKd", qh, eye,
                      preferred_element_type=q.dtype)
    q_bd = jnp.swapaxes(q_bd, 1, 2).reshape(b, h, n_kv * d)
    grid = (b, smax // block_s)

    def clamp(si, lens, bi):
        # v3: clamp past-the-end s-blocks to the slot's LAST live block.
        # Grid steps whose index map repeats the previous step's indices
        # skip their DMA (the same trick ops.paged_attention uses via
        # clamped table rows), so per-slot HBM traffic tracks the LIVE
        # length instead of Smax — the jnp path always streams the full
        # padded cache. The compute guard stays keyed on the TRUE si,
        # so revisited tiles are never folded in twice.
        last = jax.lax.max((lens[bi] + block_s - 1) // block_s - 1, 0)
        return jax.lax.min(si, last)

    kernel = functools.partial(_decode_kernel, block_s=block_s,
                               n_kv=n_kv, quant=quant)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # lengths
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, n_kv * d), lambda bi, si, lens: (bi, 0, 0)),
                pl.BlockSpec((1, block_s, n_kv, d),
                             lambda bi, si, lens: (bi, clamp(si, lens, bi),
                                                   0, 0)),
                pl.BlockSpec((1, block_s, n_kv, d),
                             lambda bi, si, lens: (bi, clamp(si, lens, bi),
                                                   0, 0)),
                pl.BlockSpec((1, n_kv, block_s),
                             lambda bi, si, lens: (bi, 0,
                                                   clamp(si, lens, bi))),
                pl.BlockSpec((1, n_kv, block_s),
                             lambda bi, si, lens: (bi, 0,
                                                   clamp(si, lens, bi))),
            ],
            out_specs=[
                pl.BlockSpec((1, h, n_kv * d), lambda bi, si, lens: (bi, 0, 0)),
                pl.BlockSpec((1, h, _LANES), lambda bi, si, lens: (bi, 0, 0)),
                pl.BlockSpec((1, h, _LANES), lambda bi, si, lens: (bi, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_kv * d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_bd, k_cache, v_cache, ks_t, vs_t)
    # select each row's own kv(h) slice out of the dense accumulator
    acc = acc.reshape(b, n_kv, g, n_kv, d)
    acc = jnp.einsum("bkgKd,kK->bkgd", acc,
                     jnp.eye(n_kv, dtype=acc.dtype)).reshape(b, h, d)
    return acc, m, l


@jax.named_scope("flash_decode_appended")
def flash_decode_appended(q, k_cache, v_cache, k_new, v_new, lengths,
                          k_scale=None, v_scale=None, *,
                          block_s: int = 128,
                          interpret: bool = False) -> jnp.ndarray:
    """Drop-in for ops.attention.decode_attention_appended on TPU.

    q: [B, 1, H, D]; k_cache/v_cache: [B, Smax, KV, D];
    k_new/v_new: [B, 1, KV, D] (bf16, fresh this step); lengths [B]
    EXCLUDING the current token. Returns [B, 1, H, D] in q.dtype.
    """
    b, _, h, d = q.shape
    n_kv = k_cache.shape[2]
    g = h // n_kv
    acc, m, l = _flash_decode_cache(
        q[:, 0], k_cache, v_cache, lengths, k_scale, v_scale,
        block_s=block_s, interpret=interpret)
    m = m[..., 0]                                           # [B, H]
    l = l[..., 0]

    # fold the appended token (exact flash combination, O(B*H*D) jnp)
    qh = (q[:, 0] * (d ** -0.5)).reshape(b, n_kv, g, d)
    s_new = jnp.einsum("bkgd,bkd->bkg", qh,
                       k_new[:, 0].astype(qh.dtype),
                       preferred_element_type=jnp.float32).reshape(b, h)
    m_t = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_t)                                # [B, H]
    beta = jnp.exp(s_new - m_t)
    l_t = l * alpha + beta
    v_rep = jnp.repeat(v_new[:, 0], g, axis=1)              # [B, H, D]
    out = (acc * alpha[..., None]
           + beta[..., None] * v_rep.astype(jnp.float32)) / l_t[..., None]
    return out.astype(q.dtype).reshape(b, 1, h, d)


def flash_decode_sharded(q, k_cache, v_cache, k_new, v_new, lengths,
                         k_scale=None, v_scale=None, *, mesh,
                         batch_axes=(), head_axis=None,
                         block_s: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """shard_map'd flash_decode_appended: each device runs the
    single-device kernel (including the appended-token fold) on its
    local [KV/tp] head shard — and its local batch shard on
    data-parallel meshes. The specs mirror parallel.kv_cache_specs so
    GSPMD never gathers the cache at the shard_map boundary; no
    collectives inside attention (the o-proj psum downstream is
    unchanged). check_vma off: pallas_call has no replication rule."""
    from jax.sharding import PartitionSpec as P

    bax = tuple(batch_axes) or None
    qspec = P(bax, None, head_axis, None)      # q/k_new/v_new [B,1,·,D]
    cspec = P(bax, None, head_axis, None)      # caches [B,Smax,KV,D]
    sspec = P(bax, None, head_axis)            # scales [B,Smax,KV]
    lspec = P(bax)
    if k_scale is not None:
        def run(q, kc, vc, kn, vn, ln, ks, vs):
            return flash_decode_appended(q, kc, vc, kn, vn, ln, ks, vs,
                                         block_s=block_s,
                                         interpret=interpret)

        fn = jax.shard_map(run, mesh=mesh,
                           in_specs=(qspec, cspec, cspec, cspec, cspec,
                                     lspec, sspec, sspec),
                           out_specs=qspec, check_vma=False)
        return fn(q, k_cache, v_cache, k_new, v_new, lengths,
                  k_scale, v_scale)

    def run(q, kc, vc, kn, vn, ln):
        return flash_decode_appended(q, kc, vc, kn, vn, ln,
                                     block_s=block_s, interpret=interpret)

    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(qspec, cspec, cspec, cspec, cspec, lspec),
                       out_specs=qspec, check_vma=False)
    return fn(q, k_cache, v_cache, k_new, v_new, lengths)


def _kernel_gate(q, k_cache, block_s: int) -> str | None:
    """None when the Pallas kernel can run; otherwise the NAME of the
    first failing gate. Single source of truth for dispatch AND for the
    GOFR_FLASH_BLOCK_S diagnostics — the warn path must know whether
    block_s is what disqualified the kernel, and a second copy of this
    predicate would silently diverge as gates are added."""
    from .flash import tpu_backend_ok

    _, _, h, d = q.shape
    smax, n_kv = k_cache.shape[1], k_cache.shape[2]
    if d % _LANES:
        return "head_dim"
    if h % n_kv:
        return "gqa_ratio"
    if not tpu_backend_ok():
        return "backend"
    # checked LAST: "block_s" means every gate the env var cannot fix
    # passed, so the warn path can blame GOFR_FLASH_BLOCK_S truthfully
    if smax % block_s or smax < block_s:
        return "block_s"
    return None


def _kernel_ok(q, k_cache, block_s: int) -> bool:
    return _kernel_gate(q, k_cache, block_s) is None


_block_s_warned: set[str] = set()


def _warn_block_s_once(kind: str, msg: str) -> None:
    """Once-per-kind warning when an operator-set GOFR_FLASH_BLOCK_S is
    ignored or disqualifies the flash kernel — the silent jnp fallback
    would otherwise make a bad tuning value read as 'flash got slower'.
    Keyed per diagnostic kind: the env var is re-read every call, so an
    invalid-value warning must not suppress a later kernel-disabled one
    (or vice versa) after the operator changes the value."""
    if kind in _block_s_warned:
        return
    _block_s_warned.add(kind)
    import warnings

    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def decode_attention_auto(q, k_cache, v_cache, k_new, v_new, lengths,
                          k_scale=None, v_scale=None, *,
                          block_s: int | None = None,
                          interpret: bool = False,
                          mesh=None) -> jnp.ndarray:
    """Flash-decode kernel when backend+shapes allow, jnp reference
    otherwise. Same contract as decode_attention_appended.
    ``block_s`` defaults from GOFR_FLASH_BLOCK_S (128): larger blocks
    amortize per-grid-step overhead, at (block_s/S)-granular DMA skip.
    With ``mesh``, the kernel runs under shard_map per head/batch shard
    (flash_decode_sharded); the reference — GSPMD-partitionable on its
    own — remains the fallback when tp would split a KV head."""
    from .flash import fit_block, interpret_env

    interpret = interpret or interpret_env()
    explicit = False
    if block_s is not None and block_s <= 0:
        # explicit caller value, same ZeroDivision hazard as the env
        # path below (smax % block_s inside _kernel_gate) — clamp to
        # the default rather than crash, and say so once
        _warn_block_s_once(
            "invalid", f"block_s={block_s!r} is not a positive integer; "
            "using the default block_s=128")
        block_s = 128
    if block_s is None:
        import os

        raw = os.environ.get("GOFR_FLASH_BLOCK_S")
        explicit = raw is not None
        try:
            block_s = int(raw) if explicit else 128
        except ValueError:
            block_s = 0
        if block_s <= 0:  # 0 would ZeroDivide inside _kernel_gate
            if explicit:
                # the set value is unusable and silently becomes the
                # default — say so, naming what the operator actually set
                _warn_block_s_once(
                    "invalid", f"GOFR_FLASH_BLOCK_S={raw!r} is not a "
                    f"positive integer; using the default block_s=128")
                explicit = False  # don't blame the env var for 128's gates
            block_s = 128
    if interpret:
        # interpret mode runs anywhere — clamp the block to the cache
        # length instead of gating (tiny test buckets never divide 128)
        block_s = fit_block(k_cache.shape[1], block_s)
    gate = None if interpret else _kernel_gate(q, k_cache, block_s)
    if gate == "block_s" and explicit:
        # every gate the env var cannot fix passed; only the operator's
        # block size disqualified the kernel
        smax = k_cache.shape[1]
        reason = (f"exceeds the cache length {smax}" if smax < block_s
                  else f"does not divide the cache length {smax}")
        _warn_block_s_once(
            "rejected", f"GOFR_FLASH_BLOCK_S={block_s} {reason}; the "
            f"flash-decode kernel is DISABLED and attention falls "
            f"back to the jnp reference path")
    if mesh is not None:
        from ..parallel.sharding import attention_shard_axes

        batch_axes, head_axis = attention_shard_axes(
            mesh, q.shape[0], q.shape[2], k_cache.shape[2])
        if gate is None and (head_axis is not None or batch_axes):
            return flash_decode_sharded(
                q, k_cache, v_cache, k_new, v_new, lengths,
                k_scale, v_scale, mesh=mesh, batch_axes=batch_axes,
                head_axis=head_axis, block_s=block_s, interpret=interpret)
        return decode_attention_appended(q, k_cache, v_cache, k_new, v_new,
                                         lengths, k_scale, v_scale)
    if gate is None:
        return flash_decode_appended(q, k_cache, v_cache, k_new, v_new,
                                     lengths, k_scale, v_scale,
                                     block_s=block_s, interpret=interpret)
    return decode_attention_appended(q, k_cache, v_cache, k_new, v_new,
                                     lengths, k_scale, v_scale)
