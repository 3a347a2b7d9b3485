"""Sharding rules: map model pytrees onto the mesh by leaf name.

This is the GSPMD half of the parallelism layer (mesh.py is the substrate):
every parameter/optimizer/cache leaf gets a `PartitionSpec`, `jax.jit`
in/out shardings pin the boundaries, and XLA inserts the ICI collectives.
Nothing in the model code mentions devices — the specs here are the single
source of truth.

Rule set (Megatron-style TP + ZeRO-3-style fsdp, both expressed as specs):
  column-parallel  [L, D, out]  (wq/wk/wv/w_gate/w_up/w_in) → (None, fsdp, tp)
  row-parallel     [L, in, D]   (wo/w_down/w_out)           → (None, tp, fsdp)
  embeddings       [V, D]                                    → ((tp, fsdp), None)
  lm_head          [D, V]                                    → (fsdp, tp)
  norms/biases                                               → replicated/minor
Int8 `QuantizedLinear` leaves shard like their parent weight; the per-output
scale follows the output axis.

Any axis that does not divide a dimension is dropped (replicated) — so the
same rules serve the tiny test configs and the 70B production shapes.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import (AXIS_EP, AXIS_FSDP, AXIS_PP, AXIS_SP, AXIS_TP,
                   DATA_AXES)

# leaf name -> spec for the *full* (possibly [L, ...]-stacked) weight
_COLUMN = {"wq", "wk", "wv", "w_gate", "w_up", "w_in"}
_ROW = {"wo", "w_down", "w_out"}
_COLUMN_BIAS = {"bq", "bk", "bv", "b_in"}
_ROW_BIAS = {"bo", "b_out"}


def spec_for(name: str, ndim: int, stacked: bool = False) -> P:
    """PartitionSpec for a parameter leaf, keyed on its dict name.

    ``stacked``: the leaf lives under a per-layer stack (params["layers"])
    with a leading [L] dim — that dim shards over pp (pipeline stages own
    contiguous layer ranges; parallel/pipeline.py conveys activations
    between them). On pp=1 meshes the axis fits to nothing."""
    lead = AXIS_PP if stacked else None
    if name in _COLUMN:
        if ndim == 4:  # MoE experts [L, E, D, F]: experts over ep,
            # hidden over the dense axes (fsdp/tp) within each expert
            return P(lead, AXIS_EP, AXIS_FSDP, AXIS_TP)
        return P(lead, AXIS_FSDP, AXIS_TP) if ndim == 3 else P(AXIS_FSDP, AXIS_TP)
    if name in _ROW:
        if ndim == 4:  # MoE experts: [L, E, F, D]
            return P(lead, AXIS_EP, AXIS_TP, AXIS_FSDP)
        return P(lead, AXIS_TP, AXIS_FSDP) if ndim == 3 else P(AXIS_TP, AXIS_FSDP)
    if name in _COLUMN_BIAS:
        return P(lead, AXIS_TP) if ndim == 2 else P(AXIS_TP)
    if name in _ROW_BIAS:
        return P(lead, AXIS_FSDP) if ndim == 2 else P(AXIS_FSDP)
    if name == "embedding":
        # Vocab over (tp, fsdp), FEATURE REPLICATED. Sharding the feature
        # dim (the r1–r3 layout: P(tp, fsdp)) made every token-embedding
        # gather inherit a feature-split output that GSPMD could only
        # reshard to the (data, sp) activation layout by involuntary full
        # rematerialization — an all-gather of [B, S, D] per train step
        # (the MULTICHIP_r03 spmd_partitioner warnings). A vocab-only
        # shard partitions the gather as local-lookup + mask + psum and
        # the output is born replicated, so the activation constraint is
        # a free slice.
        return P((AXIS_TP, AXIS_FSDP), None)
    if name == "lm_head":
        return P(AXIS_FSDP, AXIS_TP)
    if name in ("pos_embedding", "patch_proj", "pooler_w", "head"):
        return P(None, AXIS_FSDP) if ndim == 2 else P(AXIS_FSDP)
    if stacked and ndim >= 1:  # per-layer norms/router: layer dim over pp
        return P(lead)
    return P()  # norms, small embeddings, cls_token: replicated


def _leaf_name(path) -> str:
    """Last dict key on the tree path (attr keys of NamedTuple leaves like
    QuantizedLinear.w/.scale are skipped so they inherit the weight's name)."""
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _is_quant_scale(path) -> bool:
    last = path[-1] if path else None
    return isinstance(last, (jax.tree_util.GetAttrKey,)) and \
        getattr(last, "name", "") == "scale"


def fit_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop spec axes that don't divide the corresponding dim (replicate
    instead); pad/truncate the spec to the array rank."""
    axes = list(spec) + [None] * (len(shape) - len(spec))
    fitted = []
    for dim, ax in zip(shape, axes[: len(shape)]):
        if ax is None:
            fitted.append(None)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for n in names:
            size *= mesh.shape.get(n, 1)
        fitted.append(ax if size > 0 and dim % size == 0 else None)
    return P(*fitted)


def param_specs(params: Any) -> Any:
    """Pytree of PartitionSpec matching `params` (unfitted — see
    `shardings_for` for the mesh-aware version)."""

    def one(path, leaf):
        name = _leaf_name(path)
        stacked = any(isinstance(e, jax.tree_util.DictKey)
                      and str(e.key) == "layers" for e in path)
        spec = spec_for(name, leaf.ndim if hasattr(leaf, "ndim") else 0,
                        stacked=stacked)
        if _is_quant_scale(path):
            # per-output-channel scale [..., out]: keep only the output
            # axis's sharding, on the LAST dim (a rank-1 P(tail) on an
            # [L, E, F] expert scale would land tp on L instead of F),
            # plus the layer dim over pp for stacked leaves
            tail = spec[-1] if len(spec) else None
            nd = leaf.ndim if hasattr(leaf, "ndim") else 1
            lead = AXIS_PP if stacked and nd >= 2 else None
            spec = P(lead, *([None] * max(0, nd - 2)), tail) if nd >= 2 \
                else P(tail)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def shardings_for(tree: Any, mesh: Mesh,
                  specs: Any | None = None) -> Any:
    """Pytree of NamedSharding for `tree` on `mesh`, with non-dividing axes
    replicated. `tree` may hold arrays or ShapeDtypeStructs."""
    specs = specs if specs is not None else param_specs(tree)

    def one(leaf, spec):
        return NamedSharding(mesh, fit_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map(one, tree, specs)


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place an existing (host/single-device) param tree onto the mesh."""
    return jax.device_put(params, shardings_for(params, mesh))


# -- activations and caches -------------------------------------------------

def batch_spec() -> P:
    """Tokens/labels [B, S]: batch over (dp, fsdp), sequence over sp."""
    return P(DATA_AXES, AXIS_SP)


def activation_spec(ndim: int = 3) -> P:
    """Activations [B, S, D]: batch over (dp, fsdp), sequence over sp,
    feature replicated (tp lives inside the per-layer matmuls)."""
    if ndim == 2:
        return P(DATA_AXES, AXIS_SP)
    return P(DATA_AXES, AXIS_SP, None)


def activation_constraint(mesh: Mesh) -> Callable:
    """`constrain` hook for model forwards: pins [B, S, D] activations to
    the dp/sp layout so GSPMD has a stable anchor between layers."""

    def constrain(x):
        if not hasattr(x, "ndim") or x.ndim < 2:
            return x
        spec = fit_spec(activation_spec(x.ndim), x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


def kv_cache_specs(mesh: Mesh, cache) -> Any:
    """Shardings for a models.llama.KVCache: [L, B, KV, Smax, hd] — batch
    over data axes, kv-heads over tp, everything else local. Int8 caches
    carry per-vector scale planes [L, B, KV, Smax] that shard identically
    (same axes minus head_dim)."""
    kv = P(None, DATA_AXES, AXIS_TP, None, None)
    sc = P(None, DATA_AXES, AXIS_TP, None)
    ln = P(DATA_AXES)

    def fit(spec, leaf):
        return NamedSharding(mesh, fit_spec(spec, leaf.shape, mesh))

    quant = getattr(cache, "k_scale", None) is not None
    return type(cache)(
        k=fit(kv, cache.k),
        v=fit(kv, cache.v),
        lengths=fit(ln, cache.lengths),
        k_scale=fit(sc, cache.k_scale) if quant else None,
        v_scale=fit(sc, cache.v_scale) if quant else None,
    )


def paged_cache_specs(mesh: Mesh, cache) -> Any:
    """Shardings for a models.paged_llama.PagedKVCache: [L, N, T, KV,
    hd] pools shard KV-heads over tp ONLY — the block axis stays
    whole on every device because the host-owned block table (ids
    into that axis) is global dispatch data, and block scatter/gather
    index it with traced values (fine on an unsharded axis, a
    full-rematerialization hazard on a sharded one). lengths and the
    table are replicated: paged serving on a mesh is a
    tensor-parallel configuration; data axes fit to nothing."""
    kv = P(None, None, None, AXIS_TP, None)
    sc = P(None, None, None, AXIS_TP)

    def fit(spec, leaf):
        return NamedSharding(mesh, fit_spec(spec, leaf.shape, mesh))

    quant = getattr(cache, "k_scale", None) is not None
    return type(cache)(
        k=fit(kv, cache.k),
        v=fit(kv, cache.v),
        lengths=NamedSharding(mesh, P()),
        k_scale=fit(sc, cache.k_scale) if quant else None,
        v_scale=fit(sc, cache.v_scale) if quant else None,
    )


def attention_shard_axes(mesh: Mesh, batch: int, n_heads: int,
                         n_kv_heads: int) -> tuple[tuple, str | None]:
    """(batch_axes, head_axis) for shard_map'ing an attention kernel on
    ``mesh``: batch over the data axes when their product divides it,
    query/KV heads over tp when tp divides both counts. Mirrors
    fit_spec's replicate-on-non-divide rule, so the specs the ops/*_auto
    dispatchers build from this always agree with the cache placements
    kv_cache_specs / paged_cache_specs produce — a mismatch would make
    GSPMD gather the cache at the shard_map boundary. head_axis is None
    exactly when tp would split a KV head (the jnp-fallback condition,
    same predicate as kv_head_shards)."""
    nb = 1
    for ax in DATA_AXES:
        nb *= mesh.shape.get(ax, 1)
    batch_axes = DATA_AXES if nb > 1 and batch % nb == 0 else ()
    tp = mesh.shape.get(AXIS_TP, 1)
    head_axis = AXIS_TP if (tp > 1 and n_heads % tp == 0
                            and n_kv_heads % tp == 0) else None
    return batch_axes, head_axis


def kv_head_shards(mesh: Mesh, n_kv_heads: int) -> int:
    """How many tp shards the KV-head axis actually splits into on
    ``mesh`` — mirrors fit_spec's divisibility rule (a tp that does
    not divide the head count replicates instead). This is the shard
    count the per-shard offload codec frames and the T2 namespace
    key by (docs/advanced-guide/multichip-serving.md)."""
    tp = mesh.shape.get(AXIS_TP, 1)
    return tp if tp > 1 and n_kv_heads % tp == 0 else 1


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
