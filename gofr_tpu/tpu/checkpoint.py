"""Model weight loading/versioning — the framework's "checkpoint" story.

The reference's closest analogue is the migration version ledger
(pkg/gofr/migration/sql.go:142-158); for a serving framework the durable
state is model weights. Two formats:

  - Orbax checkpoint directory (the JAX-ecosystem standard; what training
    jobs emit). Restored leaf-by-leaf onto the host then placed.
  - ``.npz`` flat file with ``/``-joined pytree paths (cheap interchange:
    ``save_npz``/``load_npz`` round-trip any param tree, including int8
    ``QuantizedLinear`` leaves, without a schema).

Quantize-on-load: serving wants int8 projections (decode is HBM-bound);
checkpoints are usually bf16. ``maybe_quantize`` converts the known
projection leaves at load time so the bf16 copy never reaches the device.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.quant import QuantizedLinear, quantize_int8

# Llama projection leaves worth int8-quantizing (stacked [L, in, out]).
_QUANT_LEAVES = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
                 # the latent-attention family (models/deepseek_v3.py)
                 "w_qa", "w_qb", "w_kva", "w_kvb",
                 "ws_gate", "ws_up", "ws_down",
                 # the hybrid family's full-layer gate (models/solar_open2.py)
                 "w_attn_gate",
                 # the state-space family (models/nemotron_h.py): the
                 # mamba layer's two projections, the latent's pair
                 "w_ssm_in", "w_ssm_out", "w_latent_down", "w_latent_up",
                 # its layer of two halves: the gated feed-forward's pair
                 "w_ffn_in", "w_ffn_out",
                 # the sparse-latent family's indexer
                 # (models/dots3_note.py): its query and key projections;
                 # the key's norm, the heads' weights and the gates stay
                 # in the model's type
                 "w_iq", "w_ik"}


def _flatten(tree: Any, prefix: str = "") -> dict[str, np.ndarray]:
    out: dict[str, np.ndarray] = {}
    if isinstance(tree, QuantizedLinear):
        out[prefix + "/__qw"] = np.asarray(tree.w)
        out[prefix + "/__qscale"] = np.asarray(tree.scale)
    elif isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}/{k}" if prefix else k))
    else:
        out[prefix] = np.asarray(tree)
    return out


def _unflatten(flat: dict[str, np.ndarray]) -> Any:
    tree: dict = {}
    quant: dict[str, dict] = {}
    for path, arr in flat.items():
        parts = path.split("/")
        if parts[-1] in ("__qw", "__qscale"):
            q = quant.setdefault("/".join(parts[:-1]), {})
            q["w" if parts[-1] == "__qw" else "scale"] = arr
            continue
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    for path, q in quant.items():
        parts = path.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = QuantizedLinear(w=q["w"], scale=q["scale"])
    return tree


def save_npz(path: str, params: Any) -> None:
    np.savez(path, **_flatten(params))


def load_npz(path: str) -> Any:
    with np.load(path) as f:
        return _unflatten({k: f[k] for k in f.files})


def save_orbax(path: str, params: Any, *, force: bool = False) -> None:
    """``force=True`` overwrites an existing checkpoint at ``path`` —
    "save latest" semantics for resume loops saving back to their own
    output. The default stays refuse-to-overwrite so a mispointed path
    can't silently destroy existing weights."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), params, force=force)


def load_orbax(path: str, target: Any = None) -> Any:
    """``target``: optional abstract pytree (ShapeDtypeStructs, possibly
    with shardings) — restores each leaf to that shape/sharding (the
    sharded-resume path, parallel.restore_train_state)."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        if target is not None:
            return ckptr.restore(os.path.abspath(path), target)
        return ckptr.restore(os.path.abspath(path))




def load_params(path: str) -> Any:
    """Dispatch on layout: .npz file or orbax directory."""
    if path.endswith(".npz"):
        return load_npz(path)
    if os.path.isdir(path):
        return load_orbax(path)
    raise FileNotFoundError(f"no checkpoint at {path!r} (expected .npz file "
                            "or orbax directory)")


def maybe_quantize(params: Any, enabled: bool) -> Any:
    """Int8-quantize known projection leaves of a llama param tree."""
    if not enabled:
        return params

    def walk(node: Any, name: str = "") -> Any:
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if (name in _QUANT_LEAVES and not isinstance(node, QuantizedLinear)
                and getattr(node, "ndim", 0) in (2, 3, 4)):
            w = jnp.asarray(node)
            # stacked layers / [L, E, in, out] MoE expert stacks: the
            # contraction axis is ndim-2 in every rank — quantize per
            # (layer[, expert], out-channel)
            axis = w.ndim - 2
            return quantize_int8(w, axis=axis)
        return node

    return walk(params)


def random_params(init_fn, cfg, *, quant: bool = False, mesh=None,
                  seed: int = 0) -> Any:
    """Random-init weights in the SERVING layout, built leaf by leaf at
    each leaf's final dtype and sharding — the ``TPU_WEIGHTS``-unset
    bring-up path, and the only one a machine without a checkpoint has.

    Peak device memory during init is the serving footprint plus one
    leaf's temporaries: ``maybe_quantize(init_fn(cfg, key))`` would hold
    the whole bf16 model plus float32 temporaries (7.5 GB for ONE
    Llama-3-8B FFN leaf) before the first int8 byte exists, which a
    16 GB chip cannot. Each leaf is its own jitted program with
    ``out_shardings`` from parallel.shardings_for, so on a mesh every
    leaf is born sharded (no full copy on the first chip).

    Dense leaves take exactly the values ``init_fn(cfg, key)`` gives
    them (XLA dead-code-eliminates the other leaves from each program).
    Quantized projections are drawn directly as uniform int8 with the
    constant per-channel scale that gives fan-in variance — there is no
    bf16 original to quantize. A family whose ``init`` draws a leaf at
    another fan-in than its contraction axis says so as
    ``init_fn.fan_in(cfg, leaf name)`` (None: the axis)."""
    key = jax.random.PRNGKey(seed)
    fan_in = getattr(init_fn, "fan_in", None)

    def build(k):
        return maybe_quantize(init_fn(cfg, k), quant)

    def is_q(x):
        return isinstance(x, QuantizedLinear)

    abstract = jax.eval_shape(build, key)
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract,
                                                          is_leaf=is_q)
    structs = [st for _, st in paths]
    names = [getattr(path[-1], "key", "") for path, _ in paths]
    if mesh is not None:
        from ..parallel import shardings_for

        shardings = treedef.flatten_up_to(shardings_for(abstract, mesh))
    else:
        shardings = [None] * len(structs)

    def leaf(i, struct, sharding):
        if is_q(struct):
            shape = struct.w.shape
            fan = fan_in and fan_in(cfg, names[i]) or shape[-2]
            # uniform int8 has std 127/sqrt(3); fan-in variance overall
            scale = (fan ** -0.5) * (3.0 ** 0.5) / 127.0

            def make(k):
                return QuantizedLinear(
                    w=jax.random.randint(jax.random.fold_in(k, i), shape,
                                         -127, 128, jnp.int8),
                    scale=jnp.full(struct.scale.shape, scale, jnp.float32))
        else:
            def make(k):
                return jax.tree_util.tree_leaves(build(k), is_leaf=is_q)[i]
        return jax.jit(make, out_shardings=sharding)(key)

    return treedef.unflatten(
        [leaf(i, st, sh) for i, (st, sh) in enumerate(zip(structs,
                                                          shardings))])


def placed(params: Any, mesh=None) -> Any:
    """Move a host param tree onto device — sharded over ``mesh`` when
    given (specs from parallel.param_specs), else default placement."""
    if mesh is not None:
        from ..parallel import shard_params

        return shard_params(params, mesh)
    return jax.device_put(params)
