"""Llama-family decoder: GQA + RoPE + SwiGLU, KV-cache prefill/decode.

TPU-first design decisions:
  - Layer weights are STACKED on a leading [L, ...] axis and iterated with
    ``lax.scan`` — one compiled layer body regardless of depth (compile time
    flat in n_layers; the scan axis is also the natural pipeline-parallel
    split).
  - The KV cache is preallocated [L, B, KV, Smax, hd], a KV head's
    positions together (what the decode kernel folds is one head's
    [block, hd] tile), with a per-slot ``lengths`` cursor, so continuous
    batching can retire/admit sequences per batch slot without reshaping
    anything.
  - Weights may be int8 ``QuantizedLinear`` leaves (ops.quant): decode is
    HBM-bound, so int8 halves the weight traffic per step.
  - All matmuls keep [*, dim] x [dim, out] shapes large and MXU-aligned;
    softmax in f32; everything else bf16.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.attention import (causal_attention, chunk_attention,
                             chunk_block as walk_block,
                             decode_attention_appended,
                             window_attention_appended)
from ..ops.norms import rms_norm
from ..ops.quant import QuantizedLinear, qmatmul, quantize_kv
from ..ops.rope import apply_rope, rope_frequencies
from . import moe
from .blocks import experts_apart
from .common import ModelConfig, dense_init


_ROPE_CACHE: dict[tuple, tuple] = {}
# a cached position computed again gives the same rows: the chunk
# lattice may overlap its last chunk and a prefix hit may resume anywhere
RECOMPUTABLE = True


def get_rope_tables(cfg: ModelConfig, max_seq: int):
    """Memoized (cos, sin) tables — computed once per (model, capacity).
    Callers in a serving loop should thread these through prefill/decode_step
    so un-jitted paths don't rebuild them per token."""
    scaling_key = tuple(sorted(cfg.rope_scaling.items())) if cfg.rope_scaling else None
    key = (cfg.head_dim, max_seq, cfg.rope_theta, scaling_key)
    if key not in _ROPE_CACHE:
        tables = rope_frequencies(cfg.head_dim, max_seq,
                                  cfg.rope_theta, cfg.rope_scaling)
        # Under a trace the tables are tracers — return them but never
        # memoize (a cached tracer would leak into later traces).
        if any(isinstance(t, jax.core.Tracer) for t in tables):
            return tables
        _ROPE_CACHE[key] = tables
    return _ROPE_CACHE[key]


class KVCache(NamedTuple):
    """Preallocated decode cache. ``k``/``v`` are bf16 — or int8 when the
    per-vector ``k_scale``/``v_scale`` [L, B, KV, Smax] are present (decode
    is HBM-bound on cache+weight streaming; int8 KV halves the cache half
    of that traffic — see ops.quant.quantize_kv for the fused-dequant
    scheme). This is the one layout, for every backend and dtype; what
    leaves the device (tpu.kvcache.HostKV, the Redis and P/D frames) is
    [L, plen, KV, hd] and is transposed where it crosses."""

    k: jnp.ndarray        # [L, B, KV, Smax, hd]
    v: jnp.ndarray        # [L, B, KV, Smax, hd]
    lengths: jnp.ndarray  # [B] int32 — valid entries per slot
    k_scale: jnp.ndarray | None = None  # [L, B, KV, Smax] f32 (int8 caches)
    v_scale: jnp.ndarray | None = None

    @property
    def capacity(self) -> int:
        """Positions a slot holds (Smax)."""
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> KVCache:
    """``dtype=jnp.int8`` allocates a quantized cache (with scale planes);
    anything else is a plain dense cache in that dtype."""
    max_seq = max_seq or cfg.max_seq
    dtype = dtype or cfg.jdtype
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_seq, cfg.head_dim)
    quant = jnp.dtype(dtype) == jnp.int8
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
        k_scale=jnp.zeros(shape[:-1], jnp.float32) if quant else None,
        v_scale=jnp.zeros(shape[:-1], jnp.float32) if quant else None,
    )


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(heads, values a head) of a cached token's K (and V)."""
    return cfg.n_kv_heads, cfg.head_dim


def kv_tables(cfg: ModelConfig) -> int:
    """Row tables a cached token has, the leading axis of what leaves the
    device ([tables, plen, KV, hd]): one a layer, in every family whose
    token passes each layer once."""
    return cfg.n_layers


def chunk_block(cfg: ModelConfig, max_seq: int) -> int:
    """The block of cached rows a chunk program's attention walks up to
    the chunk's start (``ops.attention.chunk_attention``); 0 in a family
    whose chunk program walks under no cursor. What the engine counts a
    chunk dispatch's fetched rows in."""
    return walk_block(max_seq)


def decode_kv_block(cfg: ModelConfig, cache: KVCache, mesh=None):
    """Cache positions a decode work item covers, None on the reference
    path (ops.flash_decode.kernel_block)."""
    from ..ops import flash_decode

    return flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)


def unsupported_options(**_) -> list:
    """This family runs every serving option (models.family)."""
    return []


def routes(cfg: ModelConfig, tokens: int) -> bool:
    """Whether a serving prompt program of ``tokens`` positions runs its
    experts through the routed dispatch (``_routed_experts``): a rule of
    shapes. Up to ``moe.DENSE_TOKENS`` tokens every expert's stream
    hides its rows and the dense dispatch costs nothing more (the decode
    block, the small buckets); past it the dense dispatch multiplies
    E/k times too much. A capacity factor asks for the grouped
    dispatch, which is not this one."""
    return (cfg.n_experts > 0 and cfg.moe_capacity_factor <= 0
            and tokens > moe.DENSE_TOKENS)


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of these programs: where a
    configuration has experts, the dispatch its prompt programs run past
    ``routed_from_tokens`` positions (``routes``), the rows of a block
    and of the buffer by the program's positions (``moe.expert_dispatch``)
    and the path its blocks take (the loop: a float32 share leaves it).
    The decode block is a dense dispatch and is not said."""
    first = moe.DENSE_TOKENS + 1
    if not routes(cfg, first):
        return {}
    sizes = [2 * moe.DENSE_TOKENS]
    while sizes[-1] * 2 <= cfg.max_seq:
        sizes.append(sizes[-1] * 2)
    rows = {t: moe.expert_dispatch(cfg, t) for t in sizes}
    return {"moe_prompt_dispatch": {
        "routed_from_tokens": first, "path": "loop",
        "block_rows": {t: bm for t, (bm, _) in rows.items()},
        "buffer_rows": {t: n for t, (_, n) in rows.items()}}}


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params; same pytree layout a checkpoint loader fills."""
    dt = cfg.jdtype
    keys = jax.random.split(key, 12)
    L, D, H, KV, hd, F, V = (cfg.n_layers, cfg.dim, cfg.n_heads,
                             cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
                             cfg.vocab_size)
    if cfg.n_experts > 0:
        E = cfg.n_experts
        ffn = {
            "router": dense_init(keys[9], (L, D, E), dt),
            "w_gate": dense_init(keys[5], (L, E, D, F), dt),
            "w_up": dense_init(keys[6], (L, E, D, F), dt),
            "w_down": dense_init(keys[7], (L, E, F, D), dt),
        }
    else:
        ffn = {
            "w_gate": dense_init(keys[5], (L, D, F), dt),
            "w_up": dense_init(keys[6], (L, D, F), dt),
            "w_down": dense_init(keys[7], (L, F, D), dt),
        }
    params = {
        "embedding": dense_init(keys[0], (V, D), dt, scale=0.02),
        "layers": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": dense_init(keys[1], (L, D, H * hd), dt),
            "wk": dense_init(keys[2], (L, D, KV * hd), dt),
            "wv": dense_init(keys[3], (L, D, KV * hd), dt),
            "wo": dense_init(keys[4], (L, H * hd, D), dt),
            "ffn_norm": jnp.ones((L, D), dt),
            **ffn,
        },
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[8], (D, V), dt)
    return params


LORA_TARGETS = ("wq", "wk", "wv", "wo")


def init_lora(cfg: ModelConfig, n_adapters: int, rank: int, key) -> dict:
    """Stacked multi-LoRA leaves for the attention projections: per
    target, A [L, n_adapters, in, r] (kaiming-ish) and B
    [L, n_adapters, r, out] (ZEROS — the standard LoRA init, so every
    adapter starts as an exact no-op and adapter 0 conventionally stays
    that way: the base model). Merge the returned dict into
    params["layers"]; the layer scan slices the adapter stacks alongside
    the base weights and _lora() gathers each batch row's adapter —
    multi-tenant serving over ONE shared weight stream, a few rank-r
    GEMMs per layer of extra compute."""
    dt = cfg.jdtype
    L, D, H, KV, hd = (cfg.n_layers, cfg.dim, cfg.n_heads,
                       cfg.n_kv_heads, cfg.head_dim)
    dims = {"wq": (D, H * hd), "wk": (D, KV * hd),
            "wv": (D, KV * hd), "wo": (H * hd, D)}
    keys = jax.random.split(key, len(LORA_TARGETS))
    out = {}
    for k, name in zip(keys, LORA_TARGETS):
        din, dout = dims[name]
        out[f"lora_a_{name}"] = (jax.random.normal(
            k, (L, n_adapters, din, rank)) * din ** -0.5).astype(dt)
        out[f"lora_b_{name}"] = jnp.zeros((L, n_adapters, rank, dout), dt)
    return out


def merge_lora(params: dict, cfg: ModelConfig, adapter: int) -> dict:
    """Fold ONE adapter into dense base weights (W + A_i @ B_i) and drop
    the adapter stacks — the single-tenant deployment path, and the
    oracle the multi-LoRA tests pin the gathered path against. Requires
    unquantized base weights."""
    layers = dict(params["layers"])
    for name in LORA_TARGETS:
        a = layers.pop(f"lora_a_{name}", None)
        b = layers.pop(f"lora_b_{name}", None)
        if a is None:
            continue
        delta = jnp.einsum("ldr,lro->ldo", a[:, adapter].astype(jnp.float32),
                           b[:, adapter].astype(jnp.float32))
        layers[name] = (layers[name].astype(jnp.float32)
                        + delta).astype(layers[name].dtype)
    return {**params, "layers": layers}


def _expert_mm(h, w, pattern: str, scale_expand=(None, None)):
    """Per-expert einsum that consumes int8 QuantizedLinear expert stacks
    ([E, in, out] int8 + [E, out] scale) the same way ops.quant.qmatmul
    does for dense weights: upcast in-register, scale after the
    contraction (constant over the contracted axis, so XLA keeps it
    fused — the experts are never materialized in bf16).
    ``scale_expand``: axes to insert into the [E, out] scale so it
    broadcasts against the output — (None, None) prepends two (the
    [B,S,E,out] dense-dispatch layout); for [E,C,out] grouped buffers
    pass (slice(None), None)."""
    if isinstance(w, QuantizedLinear):
        y = jnp.einsum(pattern, h, w.w.astype(h.dtype),
                       preferred_element_type=jnp.float32)
        return (y * w.scale[scale_expand]).astype(h.dtype)
    return jnp.einsum(pattern, h, w)


@jax.named_scope("moe_route")
def _route(hf, router, k: int):
    """The ONE routing definition both dispatch layouts share: f32
    softmax over expert logits, top-k selection, renormalized weights.
    hf: [T, D] flattened tokens. Returns (probs [T,E], topv, topi
    [T,k]) — any future routing change (z-loss, jitter) lands here once
    so the dense/grouped equivalence tests keep meaning something."""
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", hf, router,
                   preferred_element_type=jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    return probs, topv, topi


@jax.named_scope("moe_experts_grouped")
def _moe_ffn_grouped(h, layer_w, cfg: ModelConfig, valid=None):
    """Capacity-based grouped MoE dispatch — the at-scale sibling of the
    dense-dispatch path: tokens scatter into per-expert buffers
    [E, C, D] (C = capacity_factor * T * k / E), each expert runs ONE
    batched FFN over its buffer, outputs gather back and combine by the
    renormalized top-k router weights. Compute is k/E of dense dispatch;
    the price is the standard Switch/Mixtral drop rule — assignments
    past an expert's capacity contribute zero (the residual stream
    carries those tokens unchanged). All shapes static: position-in-
    buffer comes from a cumsum over one-hot assignments, over-capacity
    writes land out of range and scatter-drop."""
    import math

    B, S, D = h.shape
    T = B * S
    E, K = cfg.n_experts, cfg.experts_per_token
    # ceil, not truncate: at capacity_factor=1.0 a perfectly balanced
    # router must fit with zero drops (Switch's convention)
    cap = max(1, math.ceil(cfg.moe_capacity_factor * T * K / E))
    hf = h.reshape(T, D)

    probs, topv, topi = _route(hf, layer_w["router"], K)      # [T, ...]

    flat_e = topi.reshape(T * K)                         # assignment order:
    tok_of = jnp.repeat(jnp.arange(T), K)                # token-major, so
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # earlier tokens win
    if valid is not None:
        # padding/inactive tokens must not claim expert capacity (they
        # would evict REAL tokens' assignments): zero their one-hot so
        # the position cumsum skips them, and drop their writes
        vflat = valid.reshape(T)[tok_of]
        onehot = onehot * vflat[:, None].astype(onehot.dtype)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                              flat_e[:, None], axis=1)[:, 0]  # [T*K]
    keep = pos < cap
    if valid is not None:
        keep = keep & vflat

    buf = jnp.zeros((E, cap, D), h.dtype)
    buf = buf.at[flat_e, jnp.where(keep, pos, cap)].set(
        hf[tok_of], mode="drop")                          # [E, C, D]

    grouped = (slice(None), None)
    gated = jax.nn.silu(_expert_mm(buf, layer_w["w_gate"], "ecd,edf->ecf",
                                   grouped)) \
        * _expert_mm(buf, layer_w["w_up"], "ecd,edf->ecf", grouped)
    out_buf = _expert_mm(gated, layer_w["w_down"], "ecf,efd->ecd", grouped)

    vals = out_buf[flat_e, jnp.where(keep, pos, 0)]       # [T*K, D]
    vals = vals * keep[:, None].astype(vals.dtype)
    out = jnp.sum(vals.reshape(T, K, D)
                  * topv.reshape(T, K, 1).astype(vals.dtype), axis=1)
    return out.reshape(B, S, D), probs.reshape(B, S, E)


def _experts_down(gated, w_down, combine):
    """The tail of the dense dispatch, ``gated`` [B,S,E,F] through
    ``w_down`` [E,F,D] and the combine weights [B,S,E] to [B,S,D], all in
    float32: the contraction over ``f``, the int8 scale [E,D] where the
    stack is quantized, the combine weights, the sum over ``e``. Scale
    and combine weights are factors that do not depend on ``f``, so on
    an ``F/tp`` slice of ``gated`` and ``w_down`` this is the slice's
    share of the result and the shares add up (_combine_experts)."""
    quant = isinstance(w_down, QuantizedLinear)
    w = w_down.w.astype(gated.dtype) if quant else w_down
    y = jnp.einsum("bsef,efd->bsed", gated, w,
                   preferred_element_type=jnp.float32)
    if quant:
        y = y * w_down.scale
    return jnp.einsum("bsed,bse->bsd", y, combine.astype(jnp.float32))


def _combine_experts(gated, w_down, combine, mesh):
    """``_experts_down`` rounded once, to ``gated``'s type. Where
    ``mesh`` has a ``tp`` axis that splits ``F`` (parallel.sharding puts
    ``w_down``'s F there), each chip's slice goes through the tail in a
    region manual over ``tp`` and the [B,S,D] shares are summed outside
    it, a sum over a sharded axis that GSPMD turns into one all-reduce.
    Left to GSPMD the whole way, the reduction sits straight after the
    contraction and carries [B,S,E,D], E times the bytes for the same
    sum (PERF.md, Findings PR 41). The axes that split something else
    stay GSPMD's inside the region too."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_TP

    tp = mesh.shape.get(AXIS_TP, 1) if mesh is not None else 1
    if tp == 1 or gated.shape[-1] % tp:
        return _experts_down(gated, w_down, combine).astype(gated.dtype)

    # An axis of one device is manual too: it splits nothing, and in a
    # region that leaves any axis to GSPMD every operation is lowered
    # with a sharding annotation behind it. The one behind the int8
    # convert keeps the chip's compiler from folding the convert into the
    # matmul, and the prefill programs then transpose a layer's w_down
    # before they read it (tests/test_kernels_compile_v5e.py
    # ::test_experts_are_combined_before_they_cross_the_chips).
    manual = {a for a, n in mesh.shape.items() if a == AXIS_TP or n == 1}
    w_spec = P(None, AXIS_TP)                 # [E,F,D] on F
    if isinstance(w_down, QuantizedLinear):   # its scale [E,D] whole
        w_spec = QuantizedLinear(w_spec, P())
    shares = jax.shard_map(
        lambda *a: _experts_down(*a)[None], mesh=mesh, axis_names=manual,
        in_specs=(P(None, None, None, AXIS_TP), w_spec, P()),
        out_specs=P(AXIS_TP))(gated, w_down, combine)         # [tp,B,S,D]
    return jnp.sum(shares, axis=0).astype(gated.dtype)


@jax.named_scope("moe_experts_routed")
def _routed_experts(hf, topi, topv, experts, cfg: ModelConfig, valid, mesh):
    """The chosen experts' weighted sum by the dropless block dispatch of
    ``models/moe.py`` (``moe.experts``: tables, fill, a loop over the
    blocks that exist, the weighted gather), FLOPs by the assignments:
    hf [T, D], topi/topv [T, k] from ``_route``, ``experts`` (the three
    stacks WHOLE, [L, E, ...], and the layer's index: a block reads
    expert (li, e) in place), valid [T] or None -> [T, D].

    As ``_experts_down`` keeps the dense tail, float32 from the down
    product's accumulator through the scale, the weights and the sum,
    rounded once. Where ``mesh`` has a ``tp`` axis that splits ``F``,
    each chip runs the whole dispatch on its slice of the three stacks
    in a region manual over ``tp`` (and over every axis of one device:
    ``_combine_experts`` says why) and hands out its float32 share; the
    shares are summed outside, one all-reduce a layer. Left to GSPMD the
    reduction lands inside the loop, one a block."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_TP

    stacks, li = experts

    def share(hf, topi, topv, stacks, li, valid=None):
        return moe.experts(hf, topi, topv, stacks, li, cfg, valid,
                           out_dtype=jnp.float32)[0]

    args = (hf, topi, topv, stacks, li) + (() if valid is None else (valid,))
    tp = mesh.shape.get(AXIS_TP, 1) if mesh is not None else 1
    if tp == 1 or cfg.ffn_dim % tp:
        return share(*args).astype(hf.dtype)

    def on_f(name, leaf):       # [L,E,D,F] / [L,E,F,D], a scale [L,E,out]
        down = name == "w_down"
        w = P(None, None, AXIS_TP) if down else P(None, None, None, AXIS_TP)
        if isinstance(leaf, QuantizedLinear):
            return QuantizedLinear(w, P() if down else P(None, None, AXIS_TP))
        return w

    manual = {a for a, n in mesh.shape.items() if a == AXIS_TP or n == 1}
    specs = (P(), P(), P(), {k: on_f(k, v) for k, v in stacks.items()}, P())
    shares = jax.shard_map(
        lambda *a: share(*a)[None], mesh=mesh, axis_names=manual,
        in_specs=specs + (P(),) * (valid is not None),
        out_specs=P(AXIS_TP), check_vma=False)(*args)           # [tp,T,D]
    return jnp.sum(shares, axis=0).astype(hf.dtype)


def _moe_ffn(h, layer_w, cfg: ModelConfig, valid=None, mesh=None):
    """Mixture-of-experts SwiGLU FFN: softmax router, top-k expert
    selection with renormalized weights, and one of two dispatches by
    what the layer is handed.

    Dense dispatch (every expert computes every token, combined by a
    [B,S,E] weight matrix that is zero off the top-k) keeps shapes
    static and the whole layer one fused einsum chain — XLA-friendly and
    exactly correct. It spends E/k times the FLOPs of a routed dispatch,
    which is free while each expert's weight stream hides its rows (up
    to ``moe.DENSE_TOKENS`` tokens: the decode block, the small
    buckets) and is what the trainer differentiates at any size.

    Routed dispatch (``_routed_experts``), where the layer comes with
    ``layer_w["experts"]`` = (the expert stacks whole, the layer's
    index) instead of its slices of them: the serving prompt programs
    past ``moe.DENSE_TOKENS`` tokens (``routes``). Nothing is dropped
    and a token's result does not depend on its batch-mates, as with the
    dense dispatch; the two share ``_route`` and nothing else.

    ``cfg.moe_capacity_factor > 0`` switches every program to the
    capacity-based grouped dispatch (_moe_ffn_grouped).

    Weights: router [D,E]; w_gate/w_up [E,D,F]; w_down [E,F,D] — dense
    or int8 QuantizedLinear stacks (TPU_QUANT=int8 quantizes experts
    per-output-channel like every other projection).
    ``mesh``: the jit's mesh, for the one collective of either dispatch
    (_combine_experts, _routed_experts).
    Returns (ffn_out [B,S,D], router_probs [B,S,E] f32 — the aux
    load-balancing loss input, collected by the training path).
    """
    if cfg.moe_capacity_factor > 0:
        return _moe_ffn_grouped(h, layer_w, cfg, valid)
    B, S, D = h.shape
    probs, topv, topi = _route(h.reshape(B * S, D), layer_w["router"],
                               cfg.experts_per_token)
    probs = probs.reshape(B, S, -1)
    if "experts" in layer_w:
        y = _routed_experts(
            h.reshape(B * S, D), topi, topv, layer_w["experts"], cfg,
            None if valid is None else valid.reshape(B * S), mesh)
        return y.reshape(B, S, D), probs
    topv = topv.reshape(B, S, -1)
    topi = topi.reshape(B, S, -1)
    with jax.named_scope("moe_experts"):
        # combine weights: zero everywhere except the chosen experts
        combine = jnp.sum(
            jax.nn.one_hot(topi, cfg.n_experts, dtype=topv.dtype)
            * topv[..., None], axis=2)                         # [B,S,E]

        gated = jax.nn.silu(
            _expert_mm(h, layer_w["w_gate"], "bsd,edf->bsef")) \
            * _expert_mm(h, layer_w["w_up"], "bsd,edf->bsef")
        return _combine_experts(gated, layer_w["w_down"], combine,
                                mesh), probs


def _lora(h, layer_w, name: str, adapter):
    """Per-row LoRA delta for projection ``name``: h @ A[adapter[b]] @
    B[adapter[b]] — rank-r bottleneck, a few extra GEMMs of width r per
    layer. Zero when the params carry no adapter stacks or the caller
    passed no adapter ids. Adapter 0 is the no-op base by convention
    (init_lora zeros every B matrix, the standard LoRA init)."""
    a = layer_w.get(f"lora_a_{name}")
    if a is None or adapter is None:
        return 0
    b = layer_w[f"lora_b_{name}"]
    ha = jnp.einsum("bsd,bdr->bsr", h, a[adapter].astype(h.dtype))
    return jnp.einsum("bsr,bro->bso", ha, b[adapter].astype(h.dtype))


def layer(x, layer_w, cfg: ModelConfig, cos, sin, positions,
           kv_write, attend, valid=None, adapter=None, mesh=None):
    """One transformer block. ``kv_write(k_new, v_new) -> (k_all, v_all)``
    handles cache interaction; ``attend(q, k, v)`` runs attention.
    ``adapter`` [B] int32 selects each row's LoRA adapter when the
    params carry adapter stacks (multi-LoRA serving). ``mesh``: the
    jit's mesh, for the expert layer's collective (_combine_experts).
    Returns (x_out, (k_stored, v_stored))."""
    B, S = x.shape[0], x.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    # named scopes are metadata: they put the block's name into every
    # operation's op_name, which is what a device trace shows
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, layer_w["attn_norm"], cfg.norm_eps)
        q = qmatmul(h, layer_w["wq"]) + _lora(h, layer_w, "wq", adapter)
        k = qmatmul(h, layer_w["wk"]) + _lora(h, layer_w, "wk", adapter)
        v = qmatmul(h, layer_w["wv"]) + _lora(h, layer_w, "wv", adapter)
        # The barrier keeps the heads-major layout that the reshape and
        # the rope want from travelling back into the q and k matmuls.
        # With it they read wq and wk from the stack as it is stored,
        # as every other projection does. Without it the dots ask for
        # the weight as [H, hd, D]: on the chip the decode block then
        # transposes the whole wq and wk stacks at the top of every
        # dispatch (0.67 GB of temporaries at Mistral-7B's sizes) and
        # every program stages a layer's slice of both through VMEM
        # with the matmul serial behind it (PERF.md, Findings PR 33).
        # It asks for no other value: the same bits on the CPU
        # (tests/test_models.py::test_qkv_barrier_changes_no_value); the
        # compiled program is held by tests/test_kernels_compile_v5e.py
        # ::test_qk_projections_read_their_weights_in_place.
        q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = apply_rope(q.reshape(B, S, H, hd), cos, sin, positions)
        k = apply_rope(k.reshape(B, S, KV, hd), cos, sin, positions)
        v = v.reshape(B, S, KV, hd)

    with jax.named_scope("kv_write"):
        k_all, v_all = kv_write(k, v)
    with jax.named_scope("attn"):
        attn = attend(q, k_all, v_all).reshape(B, S, H * hd)
    with jax.named_scope("attn_out"):
        x = x + qmatmul(attn, layer_w["wo"]) + _lora(attn, layer_w, "wo",
                                                     adapter)

    router_probs = None
    if cfg.n_experts > 0:
        with jax.named_scope("moe"):
            h = rms_norm(x, layer_w["ffn_norm"], cfg.norm_eps)
            ffn, router_probs = _moe_ffn(h, layer_w, cfg, valid, mesh)
            x = x + ffn
    else:
        with jax.named_scope("mlp"):
            h = rms_norm(x, layer_w["ffn_norm"], cfg.norm_eps)
            gated = jax.nn.silu(qmatmul(h, layer_w["w_gate"])) \
                * qmatmul(h, layer_w["w_up"])
            x = x + qmatmul(gated, layer_w["w_down"])
    return x, (k_all, v_all), router_probs


@jax.named_scope("lm_head")
def logits(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.logits_scaling != 1.0:
        return _head(params, cfg, x) / cfg.logits_scaling
    return _head(params, cfg, x)


def _head(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        with jax.named_scope("tied"):
            return jnp.dot(x, params["embedding"].T,
                           preferred_element_type=jnp.float32)
    return qmatmul(x, params["lm_head"]).astype(jnp.float32)


def logits_at(params, cfg: ModelConfig, x, logit_pos):
    """``logits`` of x [B, S, D], or with ``logit_pos`` [B] of ONE
    position a row, [B, 1, V]: the gather precedes the projection
    (``prefill_kv`` says what it costs after it)."""
    if logit_pos is not None:
        x = jnp.take_along_axis(x, logit_pos[:, None, None]
                                .astype(jnp.int32), axis=1)  # [B, 1, D]
    return logits(params, cfg, x)


def logits_dtype(cfg: ModelConfig):
    """The type ``logits``' float32 values are exact in, for every
    family (all project through ``logits``): the activations' own,
    which ``qmatmul`` returns and the cast only widens; float32 where
    tied embeddings give a float32 dot. A consumer that must hand the
    logits across a program boundary (a ``cond``'s branch) narrows them
    to it, and XLA drops the pair of casts instead of writing a float32
    copy of the array."""
    return jnp.dtype(jnp.float32) if cfg.tie_embeddings else cfg.jdtype


def _scanned(layers: dict, cfg: ModelConfig, tokens: int):
    """(what a serving prompt program's layer scan slices, the expert
    stacks it leaves whole or None) for a program of ``tokens``
    positions. Where the program routes (``routes``) the three expert
    stacks stay beside the scan and the layer's index goes through it in
    their place: a block of the dispatch reads expert (layer, e) where
    it lies, and handed a layer's slice the scan copies all the experts
    out of the stack every layer (``moe.experts``)."""
    if not routes(cfg, tokens):
        return layers, None
    whole, rest = experts_apart(layers)
    index = jnp.arange(layers["router"].shape[0], dtype=jnp.int32)
    return {**rest, "layer_index": index}, whole


def _handed(layer_w: dict, whole):
    """The layer's weights as ``layer`` takes them: ``_scanned``'s slice,
    with ``experts`` = (the stacks whole, this layer's index) where they
    were kept out of the scan."""
    if whole is None:
        return layer_w
    rest = {k: v for k, v in layer_w.items() if k != "layer_index"}
    return {**rest, "experts": (whole, layer_w["layer_index"])}


def _causal_scan(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                 lengths: jnp.ndarray | None, rope_max: int, rope_tables,
                 constrain, collect_kv: bool, flash: bool = False,
                 attend_override=None, collect_router: bool = False,
                 adapter=None, mesh=None, serving: bool = False):
    """Shared causal body for forward/prefill: embed, mask, scan layers.

    Returns (x [B,S,D], kv  — stacked [L,B,S,KV,hd] pair when
    ``collect_kv`` else None, lengths [B]). ``constrain`` is an optional
    activation-sharding hook (x -> x) applied to the embedded input and
    each layer output — a stable GSPMD anchor for dp/sp layouts.

    ``flash=True`` (the serving prefill paths) routes attention through
    the Pallas flash kernel when backend+shapes allow — no S² scores, the
    long-prompt/TTFT path; ops.flash falls back to the reference
    otherwise. Training keeps the jnp reference: its backward is the
    differentiation target and XLA's fusion is fine at train batch sizes.

    ``attend_override(q, k, v, lengths)``: replaces the attention
    entirely — the hook sequence-parallel training uses to route through
    ring attention (ops.ring_attention) on sp>1 meshes.

    ``serving``: a serving prompt program, whose experts route past
    ``moe.DENSE_TOKENS`` positions (``_scanned``). ``forward`` is not
    one: the trainer differentiates through it, and the routed
    dispatch's loop has a traced trip count and no reverse mode.
    """
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    cos, sin = rope_tables or get_rope_tables(cfg, rope_max)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]
    constrain = constrain or (lambda x: x)
    # Gather the per-token rope slices ONCE, outside the layer scan, and
    # pin them to the activation layout (data, sp, None). Gathering inside
    # each layer left the [B, S, hd/2] result's sharding to the
    # partitioner, which chose a feature-dim split and paid an
    # involuntary full-remat (replicate + repartition) per step to get
    # back to the (data, sp) layout — see apply_rope.
    cos_g = constrain(cos[positions])
    sin_g = constrain(sin[positions])

    if attend_override is not None:
        def attend(q, k, v):
            return attend_override(q, k, v, lengths)
    elif flash:
        from ..ops.flash import causal_attention_auto

        def attend(q, k, v):
            return causal_attention_auto(q, k, v, lengths=lengths,
                                         mask=valid, mesh=mesh)
    else:
        def attend(q, k, v):
            return causal_attention(q, k, v, mask=valid)

    with jax.named_scope("embed"):
        x = constrain(params["embedding"][tokens].astype(cfg.jdtype))

    layers, whole = _scanned(params["layers"], cfg, B * S) if serving \
        else (params["layers"], None)

    def body(x, layer_w):
        x, kv, probs = layer(x, _handed(layer_w, whole), cfg, cos_g, sin_g,
                             None, kv_write=lambda k, v: (k, v),
                             attend=attend, valid=valid, adapter=adapter,
                             mesh=mesh)
        # Training drops the per-layer k/v so the scan never materializes
        # the [L,B,S,KV,hd] stacks it would otherwise carry.
        return constrain(x), (kv if collect_kv else None,
                              probs if collect_router else None)

    x, (kv, router_probs) = jax.lax.scan(body, x, layers)
    return x, kv, lengths, router_probs


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None, rope_tables=None,
            constrain=None, attend_override=None,
            return_router_probs: bool = False, adapter=None,
            logit_pos: jnp.ndarray | None = None, mesh=None):
    """Cache-free causal forward over [B, S] tokens -> [B, S, V] f32 logits.
    The training/scoring path: no KV-cache allocation or writes.
    ``attend_override``: see _causal_scan (ring attention hook).
    ``return_router_probs``: also return the per-layer MoE router
    probabilities [L, B, S, E] (the load-balancing aux-loss input);
    returns (logits, probs) — probs is None for dense models.
    ``logit_pos`` [B]: project ONE position per row -> [B, 1, V] (the
    gather precedes lm_head — see prefill_kv). ``mesh``: the jit's mesh,
    for the expert layer's collective (_combine_experts)."""
    x, _, _, probs = _causal_scan(params, cfg, tokens, lengths,
                                  tokens.shape[1], rope_tables, constrain,
                                  collect_kv=False,
                                  attend_override=attend_override,
                                  collect_router=return_router_probs,
                                  adapter=adapter, mesh=mesh)
    out = logits_at(params, cfg, x, logit_pos)
    if return_router_probs:
        return out, probs
    return out


def prefill(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: KVCache, lengths: jnp.ndarray | None = None,
            rope_tables=None, flash: bool = False,
            adapter=None, mesh=None) -> tuple[jnp.ndarray, KVCache]:
    """Process prompts [B, S] (right-padded), fill the cache.

    ``lengths`` [B]: true prompt lengths (defaults to full S).
    Returns (logits [B, S, V] in f32, cache with lengths set).
    ``flash=True`` routes attention through the Pallas flash kernel;
    on sharded jits pass ``mesh`` as well so the kernel runs under
    shard_map per head/batch shard (a bare pallas_call does not
    partition under GSPMD — ops.flash picks shard_map or the jnp
    fallback from the mesh).
    """
    S = tokens.shape[1]
    x, (k_stack, v_stack), lengths, _ = _causal_scan(
        params, cfg, tokens, lengths, cache.capacity, rope_tables,
        constrain=None, collect_kv=True, flash=flash, adapter=adapter,
        mesh=mesh, serving=True)
    # k_stack: [L, B, S, KV, hd] -> write into the cache's first S slots
    if S > cache.capacity:
        raise ValueError(f"prompt length {S} exceeds cache capacity {cache.capacity}")
    cache = write_kv(cache, k_stack, v_stack, (0, 0, 0, 0, 0), lengths)
    return logits(params, cfg, x), cache


@jax.named_scope("kv_write")
def write_kv(cache: KVCache, k_stack, v_stack, index5, lengths) -> KVCache:
    """Write bf16 KV stacks [L, B', S', KV, hd], the order the layers
    make them in, into the cache at ``index5`` (start indices in the
    cache's own order, [L, B, KV, Smax, hd]), quantizing on write for int8
    caches: the stacks are transposed here, once for all layers, in the
    stored dtype. Returns the cache with ``lengths`` replaced."""
    def put(dst, src, index):
        return jax.lax.dynamic_update_slice(
            dst, jnp.swapaxes(src, 2, 3).astype(dst.dtype), index)

    if cache.quantized:
        qk, sk = quantize_kv(k_stack)
        qv, sv = quantize_kv(v_stack)
        return KVCache(
            k=put(cache.k, qk, index5), v=put(cache.v, qv, index5),
            lengths=lengths,
            k_scale=put(cache.k_scale, sk, index5[:-1]),
            v_scale=put(cache.v_scale, sv, index5[:-1]))
    return KVCache(k=put(cache.k, k_stack, index5),
                   v=put(cache.v, v_stack, index5), lengths=lengths)


def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None, rope_max: int | None = None,
               rope_tables=None, flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward returning the raw KV stacks instead of a filled cache.

    The continuous-batching serving engine prefills ONE sequence at a time
    and writes its KV into a single slot of a shared [L, B, KV, Smax, hd]
    cache; handing back (k_stack, v_stack) [L, B, S, KV, hd] lets it
    write them (``write_kv``) into that slot without allocating a throwaway
    full-capacity cache per admission.

    ``logit_pos`` [B]: serving only samples ONE position per prompt —
    passing it gathers the hidden state there BEFORE lm_head, so the
    [S, V] logits (0.5 TFLOP + a quarter-GB f32 write at S=512,
    V=128k) shrink to [1, V]. The gather must precede the projection:
    the sample position is a traced scalar, so gathering after would
    still compute every row.

    Returns (logits [B, S, V] f32 — or [B, 1, V] with ``logit_pos`` —
    k_stack, v_stack, lengths [B]).
    """
    x, (k_stack, v_stack), lengths, _ = _causal_scan(
        params, cfg, tokens, lengths, rope_max or tokens.shape[1],
        rope_tables, constrain=None, collect_kv=True, flash=flash,
        adapter=adapter, mesh=mesh, serving=True)
    return logits_at(params, cfg, x, logit_pos), k_stack, v_stack, lengths


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: KVCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """Process a chunk of C prompt tokens at positions [start, start+C)
    against the growing cache — the long-prompt path (chunked prefill):
    prompts of any length up to cache capacity run as a sequence of
    fixed-shape chunk calls, so XLA compiles one program per chunk size
    instead of one per prompt length.

    Same HBM discipline as decode_step: the cache is read-only inside the
    layer scan, the chunk's KV [L, B, C, KV, hd] is written afterwards by
    one dynamic_update_slice per buffer (in place on donated caches).

    ``cache.lengths`` is NOT advanced (padding inside the final chunk makes
    the true end caller-known only) — callers set lengths once after the
    last chunk. Returns (logits [B, C, V] f32 — or None when
    ``compute_logits`` is False, sparing mid-prompt chunks the lm_head
    matmul — and the cache with KV written). ``mesh``: the jit's mesh,
    for the expert layer's collective (_combine_experts,
    _routed_experts: a chunk past ``moe.DENSE_TOKENS`` tokens routes).
    """
    B, C = tokens.shape
    cos, sin = rope_tables or get_rope_tables(cfg, cache.capacity)
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))

    with jax.named_scope("embed"):

        x = params["embedding"][tokens].astype(cfg.jdtype)

    layers, whole = _scanned(params["layers"], cfg, B * C)

    def body(x, xs):
        layer_w, k_layer, v_layer, ks_layer, vs_layer = xs

        def attend(q, k_new, v_new):
            return chunk_attention(q, k_layer, v_layer, k_new, v_new, start,
                                   ks_layer, vs_layer)

        x, kv, _ = layer(x, _handed(layer_w, whole), cfg, cos, sin,
                         positions, kv_write=lambda k, v: (k, v),
                         attend=attend, adapter=adapter, mesh=mesh)
        return x, kv

    x, (k_chunk, v_chunk) = jax.lax.scan(
        body, x, (layers, cache.k, cache.v, cache.k_scale, cache.v_scale))
    cache = write_kv(cache, k_chunk, v_chunk, (0, 0, 0, start, 0),
                     cache.lengths)
    if not compute_logits:
        return None, cache
    return logits_at(params, cfg, x, logit_pos), cache


def verify_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: KVCache, rope_tables=None, adapter=None,
                mesh=None) -> tuple[jnp.ndarray, KVCache]:
    """Multi-token verify pass — speculative decoding's target forward.

    ``tokens`` [B, W]: column 0 is each slot's pending last sampled
    token (the one decode_step would consume), columns 1.. are draft
    continuations. ONE weight stream computes logits at every window
    position ([B, W, V] f32 — logits[:, j] predicts the token after
    consuming tokens[:, :j+1]) and writes all W KV rows at each slot's
    cursor. ``cache.lengths`` is returned UNCHANGED: acceptance — how
    far the cursor really advances — is the caller's call, and garbage
    KV past the accepted point stays invisible behind the cursor and is
    overwritten by the next window (the same cursor-visibility contract
    decode_step documents). W=1 is exactly decode_step minus sampling.

    Why this wins: decode streams the full weight set per token; a
    verify window streams it once for up to W tokens. On agreeing
    drafts (repetitive text, prompt-lookup hits) decode becomes
    bandwidth-bound on W tokens per pass instead of one.

    CAPACITY CONTRACT: callers must ensure ``lengths + W <= capacity``
    for slots whose acceptance they will honor — rows past capacity are
    dropped and must not be accepted. ``mesh``: as decode_step's, for
    the rows' write.
    """
    cfg = multi_request_serving_config(cfg)
    B, W = tokens.shape
    cos, sin = rope_tables or get_rope_tables(cfg, cache.capacity)
    positions = cache.lengths[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    lengths = cache.lengths

    with jax.named_scope("embed"):

        x = params["embedding"][tokens].astype(cfg.jdtype)  # [B, W, D]

    def body(x, xs):
        layer_w, k_layer, v_layer, ks_layer, vs_layer = xs

        def attend(q, k_new, v_new):
            return window_attention_appended(q, k_layer, v_layer, k_new,
                                             v_new, lengths, ks_layer,
                                             vs_layer)

        x, kv, _ = layer(x, layer_w, cfg, cos, sin, positions,
                         kv_write=lambda k, v: (k, v), attend=attend,
                         adapter=adapter, mesh=mesh)
        return x, kv

    x, (k_w, v_w) = jax.lax.scan(
        body, x, (params["layers"], cache.k, cache.v,
                  cache.k_scale, cache.v_scale))
    # all layers and window rows at once: [L, B, W, KV, hd] ->
    # cache[:, b, :, lengths[b] + j]
    with jax.named_scope("kv_write"):
        new = write_rows(cache, k_w, v_w, positions, lengths, cfg.n_heads,
                         mesh)
    return logits(params, cfg, x), new


EOS_PAD = -1  # unused entries of a per-slot on-device stop set


def decode_stop_mask(tokens: jnp.ndarray, lengths: jnp.ndarray,
                     budget: jnp.ndarray, eos_ids: jnp.ndarray,
                     capacity: jnp.ndarray) -> jnp.ndarray:
    """Per-slot stop verdict for one fused-decode scan step — the
    on-device mirror of the serving engine's host retirement checks
    (EOS set membership, token budget, cache capacity), evaluated
    INSIDE the scan so a finished stream self-deactivates mid-block
    instead of burning junk slot-steps until the host reaps (at
    pipeline depth 2 that waste would be up to 2K-1 steps per stream).

    ``tokens`` [B]: the step's sampled tokens. ``lengths`` [B]: the
    post-step cursors. ``budget`` [B]: tokens the slot may still emit
    AFTER this one (the device carry of ``_Slot.remaining``).
    ``eos_ids`` [B, E]: each request's stop set, EOS_PAD-padded (token
    ids are non-negative, so the pad can never match). ``capacity``:
    the cursor bound at which the host retires (max_seq - 2 — the next
    delivered token would reach serving capacity).

    Returns bool [B]: True = this slot emitted its LAST token this step
    (the token itself is still delivered; the slot freezes from the
    next step on). Must stay exactly equivalent to the host checks in
    ``GenerationEngine._deliver`` — depth-2 token-exactness vs depth-1
    rests on the two retiring at the same position."""
    at_eos = jnp.any(tokens[:, None] == eos_ids, axis=1)
    return at_eos | (budget <= 0) | (lengths >= capacity)


def multi_request_serving_config(cfg: ModelConfig) -> ModelConfig:
    """Config for any program that batches UNRELATED requests into one
    forward — decode over the slot pool, the engine's coalesced ``score``
    batches. Grouped MoE dispatch is FORBIDDEN there: capacity claims are
    token-major across the whole batch, so request A's tokens can evict
    request B's expert assignments and B's output would depend on what A
    routed to (verified: up to 0.5 logit cross-talk at
    capacity_factor=1.0). Dense dispatch keeps every request's result
    independent of its batch-mates; per-request programs (prefill of one
    prompt, training steps) keep grouped dispatch."""
    if cfg.n_experts > 0 and cfg.moe_capacity_factor > 0:
        return cfg.with_(moe_capacity_factor=0.0)
    return cfg


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: KVCache, rope_tables=None, adapter=None, mesh=None,
                active: jnp.ndarray | None = None
                ) -> tuple[jnp.ndarray, KVCache]:
    """One decode step for tokens [B] against the cache.

    Returns (logits [B, V] f32, updated cache with lengths+1).

    Decode is HBM-bound, so the cache is READ-ONLY inside the layer loop
    (the current token's k/v ride alongside, see
    ``decode_attention_appended``), and the per-layer new-token k/v, the
    only novel data, [L, B, KV, hd], is written by ONE scatter into the
    donated buffers after the loop (``write_rows``). Emitting updated
    cache slices as scan outputs instead would rewrite the entire cache
    every token.

    Which attention reads the cache is chosen from what can be observed,
    with no setting (ops.flash_decode.kernel_block): on a TPU, for shapes
    the kernel takes, the loop hands the flash-decode kernel the whole
    stacked cache and the layer index, and it fetches only each slot's
    live blocks, in place; pass ``mesh`` on sharded jits, where the
    kernel runs under shard_map per head/batch shard. Otherwise
    (another backend, a head_dim that is not whole lanes, a tp that
    splits a KV head) the scan slices each layer's [B, KV, Smax, hd]
    for ``decode_attention_appended``; on the chip XLA materialises
    that slice as a copy and attention then reads all Smax positions of
    it (PERF.md, Findings PR 25), which is why it is the fallback.

    ``active`` [B] bool: slots that are decoding. The kernel reads
    nothing of a slot that is not (its output is discarded by the
    caller), whatever its cursor holds: a retired slot's cursor stays
    frozen at its old value in the cache. None means all are.

    CAPACITY CONTRACT: callers must ensure ``lengths < cache capacity``
    before stepping: at capacity the row's position is out of range and
    the write is dropped (no data-dependent errors are possible under
    jit). The serving engine retires slots before they hit capacity.
    """
    from ..ops import flash_decode

    # slot isolation: grouped MoE dispatch would couple batch slots
    # (see multi_request_serving_config), so decode is forced dense
    cfg = multi_request_serving_config(cfg)
    cos, sin = rope_tables or get_rope_tables(cfg, cache.capacity)
    positions = cache.lengths[:, None]  # [B,1], this token's position
    lengths = cache.lengths

    with jax.named_scope("embed"):
        x = params["embedding"][tokens[:, None]].astype(cfg.jdtype)  # [B,1,D]

    def block(x, layer_w, attend):
        x, kv_tok, _ = layer(x, layer_w, cfg, cos, sin, positions,
                             kv_write=lambda k, v: (k, v), attend=attend,
                             adapter=adapter, mesh=mesh)
        return x, kv_tok

    block_s = flash_decode.kernel_block(cfg.n_heads, cache.k, mesh)
    if block_s:
        live = lengths if active is None else jnp.where(active, lengths, 0)

        def body(x, xs):
            layer_w, li = xs
            return block(x, layer_w, lambda q, k_new, v_new:
                         flash_decode.decode_attention_auto(
                             q, cache.k, cache.v, k_new, v_new, live, li,
                             cache.k_scale, cache.v_scale, block_s=block_s,
                             mesh=mesh))

        xs = (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32))
    else:
        def body(x, xs):
            layer_w, k_layer, v_layer, ks_layer, vs_layer = xs
            return block(x, layer_w, lambda q, k_new, v_new:
                         decode_attention_appended(
                             q, k_layer, v_layer, k_new, v_new, lengths,
                             ks_layer, vs_layer))

        xs = (params["layers"], cache.k, cache.v, cache.k_scale,
              cache.v_scale)
    x, (k_toks, v_toks) = jax.lax.scan(body, x, xs)
    with jax.named_scope("kv_write"):
        new = write_rows(cache, k_toks, v_toks, positions, lengths + 1,
                         cfg.n_heads, mesh)
    return logits(params, cfg, x[:, 0]), new


def write_rows(cache: KVCache, k_rows, v_rows, positions, lengths,
                n_heads: int, mesh=None) -> KVCache:
    """The rows a step made, [L, B, W, KV, hd] for all layers (W = 1: a
    decode step; a verify window's W), to cache[:, b, :, positions[b, j]],
    quantized on the way for int8 caches, their scales [L, B, W, KV] to
    the scale tables at the same places; the cache with ``lengths``
    replaced. A position at or past capacity is dropped, row and scales.

    A position is one row of a KV head's (Smax, hd) tiles, and XLA writes
    it only through a copy of the whole cache to a layout with that axis
    major and one back (ops.flash_decode.append_rows_stacked, which is
    the write wherever ``kernel_block`` answers: in place, the tiles
    around each cursor read, merged and written, once a window
    position). The scales go with the rows, through the same visit: the
    lane tile of each table around the cursor, 21 MB a step at 32 x 40 x
    8 x 2,048. Until PR 48 they went by a select over both whole tables
    on every path, 336 MB and 0.5 ms of a 13 ms step, which had been the
    cheaper of two: a scatter's window is not theirs either, two layout
    copies of 0.6 ms each (PERF.md, Findings PR 25 and PR 48). Elsewhere
    (the CPU, a shape the kernels refuse) the rows are one scatter and
    the scales that select."""
    from ..ops import flash_decode

    def where_scales(scale, rows):      # [L, B, KV, Smax] <- [L, B, W, KV]
        at = jnp.arange(scale.shape[3])
        for j in range(positions.shape[1]):
            here = (at[None, :] == positions[:, j, None])[None, :, None, :]
            scale = jnp.where(here, rows[:, :, j, :, None], scale)
        return scale

    sk = sv = None
    if cache.quantized:
        k_rows, sk = quantize_kv(k_rows)
        v_rows, sv = quantize_kv(v_rows)
    k_rows = k_rows.astype(cache.k.dtype)
    v_rows = v_rows.astype(cache.v.dtype)
    k, v, k_scale, v_scale = cache.k, cache.v, cache.k_scale, cache.v_scale
    if flash_decode.kernel_block(n_heads, cache.k, mesh):
        for j in range(positions.shape[1]):
            scales = (sk[:, :, j], sv[:, :, j]) if cache.quantized else ()
            k, v, k_scale, v_scale = flash_decode.append_rows(
                k, v, k_rows[:, :, j], v_rows[:, :, j], positions[:, j],
                k_scale, v_scale, *scales, n_heads=n_heads, mesh=mesh)
    else:
        # advanced indices around a slice: the result leads with [B, W]
        b_idx = jnp.arange(positions.shape[0])[:, None]
        k = k.at[:, b_idx, :, positions].set(
            jnp.transpose(k_rows, (1, 2, 0, 3, 4)), mode="drop")
        v = v.at[:, b_idx, :, positions].set(
            jnp.transpose(v_rows, (1, 2, 0, 3, 4)), mode="drop")
        if cache.quantized:
            k_scale = where_scales(k_scale, sk)
            v_scale = where_scales(v_scale, sv)
    return KVCache(k=k, v=v, lengths=lengths, k_scale=k_scale,
                   v_scale=v_scale)
