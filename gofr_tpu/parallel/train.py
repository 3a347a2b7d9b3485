"""Sharded training: one jitted step — forward, loss, grad, optax update.

The reference framework has no training loop (it is a Go microservice
framework); this subsystem exists because a TPU-native serving framework
needs a first-class fine-tuning/continued-pretraining path for the models
it serves. Design:

  - ONE `jax.jit` over the whole step with explicit in/out shardings and
    donated (params, opt_state): XLA fuses forward+backward+update and
    overlaps the fsdp all-gathers/reduce-scatters with compute.
  - Gradients reduce over the data axes automatically: params are sharded
    (or replicated) over (dp, fsdp) while the batch is split over them, so
    GSPMD inserts the psum/reduce-scatter — we never call a collective.
  - `jax.checkpoint` on the scanned layer body trades recompute for HBM,
    which is what makes long-sequence training fit.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..models import llama
from ..models.common import ModelConfig
from .mesh import AXIS_PP, AXIS_SP, DATA_AXES, Mesh
from .sharding import (activation_constraint, batch_spec, fit_spec,
                       param_specs, shardings_for)
from jax.sharding import NamedSharding, PartitionSpec as P


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def default_optimizer(lr: float = 3e-4, *, warmup: int = 100,
                      total_steps: int = 10_000,
                      weight_decay: float = 0.1,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, warmup,
                                               max(total_steps, warmup + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def loss_parts_local(logits: jnp.ndarray, tokens_full: jnp.ndarray,
                     lengths: jnp.ndarray, g0, S: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sum of masked next-token NLL, number of masked positions) for a
    SEQUENCE SHARD: ``logits`` [B, Sn, V] sits at global positions
    [g0, g0+Sn) of a length-S sequence whose full token ids are
    ``tokens_full`` [B, S] — the next-token shift reads cross-boundary
    targets from the full ids. The ONE definition of the
    shift/mask/log-softmax math: loss_parts is the g0=0, Sn=S case,
    next_token_loss its ratio, and the pipeline conveyor psums these
    parts over microbatches and sp shards into exactly the full mean."""
    B, sn, _ = logits.shape
    tgt_i = g0 + jnp.arange(sn, dtype=jnp.int32) + 1          # [Sn] global
    safe = jnp.minimum(tgt_i, S - 1)
    tgt = jnp.take_along_axis(tokens_full,
                              jnp.broadcast_to(safe, (B, sn)), axis=1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]               # [B, Sn]
    mask = ((tgt_i[None, :] < lengths[:, None])
            & (tgt_i[None, :] <= S - 1)).astype(jnp.float32)
    return jnp.sum(nll * mask), jnp.sum(mask)


def loss_parts(logits: jnp.ndarray, tokens: jnp.ndarray,
               lengths: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Additive causal-LM loss over the full sequence — the unsharded
    case of loss_parts_local."""
    return loss_parts_local(logits, tokens, lengths, jnp.int32(0),
                            logits.shape[1])


def next_token_loss(logits: jnp.ndarray, tokens: jnp.ndarray,
                    lengths: jnp.ndarray) -> jnp.ndarray:
    """Mean causal-LM cross-entropy: logits [B,S,V] f32 predict tokens
    shifted left; positions ≥ length are masked out."""
    nll_sum, mask_sum = loss_parts(logits, tokens, lengths)
    return nll_sum / jnp.maximum(mask_sum, 1.0)


def _build_state(cfg: ModelConfig,
                 optimizer: optax.GradientTransformation) -> Callable:
    """The ONE definition of a fresh TrainState's structure — init and
    the checkpoint-restore skeleton must never drift apart."""

    def build(key):
        params = llama.init(cfg, key)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    return build


def load_balance_loss(router_probs: jnp.ndarray,
                      lengths: jnp.ndarray) -> jnp.ndarray:
    """Switch-Transformer-style MoE auxiliary loss:
    E * mean_layers( sum_e f_e * P_e ) over VALID tokens, where f_e is
    the fraction of tokens whose top-1 expert is e and P_e the mean
    router probability for e. Equals 1.0 at perfect balance and climbs
    toward E as the router collapses — the gradient pushes assignment
    back toward uniform. router_probs: [L, B, S, E] f32."""
    L, B, S, E = router_probs.shape
    mask = (jnp.arange(S)[None, :] < lengths[:, None]).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    top1 = jnp.argmax(router_probs, axis=-1)                  # [L, B, S]
    f = jnp.sum(jax.nn.one_hot(top1, E) * mask[None, ..., None],
                axis=(1, 2)) / denom                           # [L, E]
    p = jnp.sum(router_probs * mask[None, ..., None],
                axis=(1, 2)) / denom                           # [L, E]
    return E * jnp.mean(jnp.sum(f * p, axis=-1))


def init_train_state(cfg: ModelConfig, key, mesh: Mesh,
                     optimizer: optax.GradientTransformation) -> TrainState:
    """Init params + optimizer state DIRECTLY sharded on the mesh: the init
    itself is jitted with out_shardings, so no host-side full copy of the
    model ever exists (required for 70B-class runs)."""
    build = _build_state(cfg, optimizer)
    shapes = jax.eval_shape(build, key)
    out_sh = state_shardings(shapes, mesh)
    return jax.jit(build, out_shardings=out_sh)(key)


def state_shardings(state_like: Any, mesh: Mesh) -> Any:
    """Shardings for a TrainState (or its eval_shape): optimizer moments
    mirror their parameter's spec; scalars replicate."""
    p_specs = param_specs(state_like.params)
    p_shard = shardings_for(state_like.params, mesh, p_specs)
    rep = NamedSharding(mesh, P())

    # Optax moment leaves MIRROR the param tree: an adam mu/nu leaf's tree
    # path ends with the same dict-key chain as its parameter (e.g.
    # .mu['layers']['wo']). Match by that name chain — matching by shape
    # would collide wq/wo (same shape, transposed specs).
    def names(path) -> tuple:
        return tuple(str(e.key) for e in path
                     if isinstance(e, jax.tree_util.DictKey))

    by_names: dict[tuple, Any] = {}
    for (path, _), sh in zip(
            jax.tree_util.tree_flatten_with_path(state_like.params)[0],
            jax.tree_util.tree_leaves(p_shard)):
        by_names[names(path)] = sh

    def match(path, leaf):
        key = names(path)
        # longest non-empty suffix of the opt-leaf path naming a param
        for i in range(len(key)):
            sh = by_names.get(key[i:])
            if sh is not None:
                return sh
        return rep

    opt_sh = jax.tree_util.tree_map_with_path(match, state_like.opt_state)
    return TrainState(step=rep, params=p_shard, opt_state=opt_sh)


def make_train_step(cfg: ModelConfig, optimizer: optax.GradientTransformation,
                    mesh: Mesh, *, remat: bool = True,
                    seq_parallel: str = "auto",
                    moe_aux_weight: float = 0.01,
                    n_microbatches: int | None = None) -> Callable:
    """Build the jitted sharded train step:
    step(state, tokens [B,S], lengths [B]) -> (state, metrics dict).

    ``seq_parallel``: "ring" routes attention through ring attention
    (ops.ring_attention — sequence shards pinned, K/V rotating over the
    sp axis with ppermute); "dense" keeps the fusable jnp attention;
    "auto" (default) picks ring exactly when the mesh has sp > 1, where
    GSPMD's dense partition degrades into full-rematerialization
    reshards (the spmd_partitioner warnings the dryrun notes).

    MoE configs (cfg.n_experts > 0) add ``moe_aux_weight`` times the
    load-balancing loss (reported as metrics["aux_loss"]) so the router
    cannot collapse onto a few experts.

    Meshes with pp > 1 run the forward as a GPipe microbatch conveyor
    (parallel/pipeline.py) over ``n_microbatches`` (default 2*pp; the
    batch must divide by it), MoE aux loss included. pp composes with
    dp/fsdp/ep/tp and with sp (the conveyor runs ring attention inside
    each stage for long-context pipelining); only pp + grouped MoE
    dispatch is rejected."""
    constrain = activation_constraint(mesh)
    moe = cfg.n_experts > 0
    pp = mesh.shape.get(AXIS_PP, 1)

    if pp > 1:
        from .pipeline import make_pp_loss_fn

        loss_fn = make_pp_loss_fn(cfg, mesh,
                                  n_microbatches=n_microbatches or 2 * pp,
                                  remat=remat,
                                  moe_aux_weight=moe_aux_weight)
    else:
        if n_microbatches is not None:
            # silently running a full-batch step instead of the requested
            # microbatching would change memory semantics unannounced
            raise ValueError("n_microbatches only applies to pp>1 meshes "
                             "(gradient accumulation without pp is not "
                             "implemented)")
        use_ring = (seq_parallel == "ring"
                    or (seq_parallel == "auto"
                        and mesh.shape.get(AXIS_SP, 1) > 1))
        attend_override = None
        if use_ring:
            from ..ops.ring_attention import make_ring_attention

            attend_override = make_ring_attention(
                mesh, axis_name=AXIS_SP, batch_axes=DATA_AXES)

        # mesh: the expert layer's one collective (models/llama.py's
        # ``_combine_experts``)
        fwd = functools.partial(llama.forward, mesh=mesh)
        if remat:
            fwd = jax.checkpoint(fwd, static_argnums=(1, 5, 6, 7))

        def loss_fn(params, tokens, lengths):
            if moe:
                logits, probs = fwd(params, cfg, tokens, lengths, None,
                                    constrain, attend_override, True)
                aux = load_balance_loss(probs, lengths)
                lm = next_token_loss(logits, tokens, lengths)
                return lm + moe_aux_weight * aux, aux
            logits = fwd(params, cfg, tokens, lengths, None, constrain,
                         attend_override, False)
            return next_token_loss(logits, tokens, lengths), jnp.zeros(())

    def step(state: TrainState, tokens, lengths):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, tokens, lengths)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new = TrainState(step=state.step + 1, params=params,
                         opt_state=opt_state)
        return new, {"loss": loss.astype(jnp.float32),
                     "grad_norm": gnorm.astype(jnp.float32),
                     "aux_loss": aux.astype(jnp.float32),
                     "step": new.step}

    def data_sharding(shape_rank2, shape_rank1):
        tok = NamedSharding(mesh, fit_spec(batch_spec(), shape_rank2, mesh))
        ln = NamedSharding(mesh, fit_spec(P(batch_spec()[0]), shape_rank1, mesh))
        return tok, ln

    compiled: dict[tuple, Callable] = {}

    def jitted(state: TrainState, tokens, lengths):
        key = (tuple(tokens.shape), tuple(lengths.shape))
        if key not in compiled:
            st_sh = state_shardings(state, mesh)
            tok_sh, len_sh = data_sharding(tokens.shape, lengths.shape)
            rep = NamedSharding(mesh, P())
            metrics_sh = {"loss": rep, "grad_norm": rep,
                          "aux_loss": rep, "step": rep}
            fn = jax.jit(step,
                         in_shardings=(st_sh, tok_sh, len_sh),
                         out_shardings=(st_sh, metrics_sh),
                         donate_argnums=(0,))
            compiled[key] = (fn, tok_sh, len_sh)
        fn, tok_sh, len_sh = compiled[key]
        return fn(state, jax.device_put(jnp.asarray(tokens), tok_sh),
                  jax.device_put(jnp.asarray(lengths), len_sh))

    return jitted


def abstract_train_state(cfg: ModelConfig, mesh: Mesh,
                         optimizer: optax.GradientTransformation) -> Any:
    """The TrainState's shape/dtype/sharding skeleton WITHOUT allocating
    anything — the restore target for checkpoint resume (and a free
    spec-validation artifact, like tests/test_70b_sharded.py uses)."""
    shapes = jax.eval_shape(_build_state(cfg, optimizer),
                            jax.random.PRNGKey(0))
    shardings = state_shardings(shapes, mesh)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def save_train_state(path: str, state: TrainState) -> None:
    """Checkpoint the FULL training state (step + params + optimizer
    moments) with orbax — the resume story the reference's migration
    ledger plays for schema (SURVEY §5 checkpoint/resume; the reference
    itself is stateless and has no analogue). Delegates to the one
    orbax save path (tpu.checkpoint.save_orbax); force=True because a
    resume loop saves back to its own output path repeatedly."""
    from ..tpu.checkpoint import save_orbax

    save_orbax(path, state, force=True)


def restore_train_state(path: str, cfg: ModelConfig, mesh: Mesh,
                        optimizer: optax.GradientTransformation) -> TrainState:
    """Restore a TrainState DIRECTLY sharded onto ``mesh`` (each leaf
    lands at its canonical NamedSharding — resuming on a different
    topology reshards at load, no host-side full copy). Delegates to the
    one orbax restore path (tpu.checkpoint.load_orbax)."""
    from ..tpu.checkpoint import load_orbax

    return load_orbax(path, target=abstract_train_state(cfg, mesh,
                                                        optimizer))
