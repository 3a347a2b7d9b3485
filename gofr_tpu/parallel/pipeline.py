"""Pipeline parallelism: GPipe-style microbatch conveyor over the pp axis.

The reference has no parallelism at all (SURVEY §2); this is the
TPU-native pp story, built the way the rest of the parallel layer is —
named mesh axes and collectives the compiler can see:

  - The [L, ...]-stacked layer weights shard L over ``pp``
    (sharding.spec_for ``stacked=True``): stage s owns layers
    [s*L/pp, (s+1)*L/pp) as a LOCAL stack — no gathering, ever.
  - The step runs inside ``jax.shard_map`` MANUAL over pp (and sp when
    the mesh has it): dp/fsdp/ep/tp stay "auto", so GSPMD keeps
    partitioning the batch and the per-layer matmuls exactly as in the
    non-pp step. pp composes with the other axes instead of replacing
    them (Megatron-style dp x pp x tp).
  - Microbatches conveyor through stages with ``lax.ppermute``: at tick
    t, stage s works on microbatch t-s; activations AND their lengths
    ride the conveyor (the causal mask travels with its microbatch).
    The last stage computes logits+loss for each microbatch as it
    drains; a psum over pp publishes the scalar. Autodiff reverses the
    ppermutes — backward is the same conveyor in reverse, and grads
    accumulate over microbatches by construction.
  - Bubbles: the first/last pp-1 ticks compute garbage on idle stages
    (injected zeros). Their outputs are never selected into the loss,
    so correctness is unconditional; the waste is the standard GPipe
    bubble fraction (pp-1)/(n_micro+pp-1) — raise n_microbatches to
    amortize.
  - **pp x sp (long-context pipelining)**: with sp > 1 the manual set
    grows to {pp, sp} and each stage holds only its SEQUENCE SHARD of
    each microbatch ([mb, S/sp, D] rides the conveyor). Attention runs
    ``ops.ring_attention.ring_causal_attention`` DIRECTLY — the stage
    is already manual over sp, so the ring's ppermutes compose with the
    conveyor's without nesting shard_maps. Tokens stay replicated over
    sp (ids are cheap); embeddings/logits/loss are computed on the
    local shard only, and the loss shift across shard boundaries reads
    its targets from the replicated token ids.

Scope: dense decoders and dense-dispatch MoE (aux loss collected
exactly across stages — see make_pp_loss_fn). pp with grouped MoE
dispatch is rejected (XLA partitioner limitation — dense dispatch
works). Serving meshes keep pp=1 (decode wants every layer resident;
pipelining decode trades latency for nothing at batch-1 token cadence).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import llama
from ..models.common import ModelConfig
from .mesh import AXIS_PP, AXIS_SP, Mesh
from .train import loss_parts_local


def _stage_apply(layers_local: Any, x: jnp.ndarray, cfg: ModelConfig,
                 cos, sin, positions, valid, attend) -> jnp.ndarray:
    """Run this stage's local layer stack over one microbatch (shard)."""

    def body(x, layer_w):
        x, _, probs = llama.layer(x, layer_w, cfg, cos, sin, positions,
                                  kv_write=lambda k, v: (k, v),
                                  attend=attend, valid=valid)
        return x, probs  # [mb, S, E] per layer for MoE, else None

    x, probs = jax.lax.scan(body, x, layers_local)
    return x, probs


def make_pp_loss_fn(cfg: ModelConfig, mesh: Mesh, *, n_microbatches: int,
                    remat: bool = True, moe_aux_weight: float = 0.01):
    """loss_fn(params, tokens [B,S], lengths [B]) -> (loss, aux) running
    the forward as a pp-stage conveyor (sequence-sharded over sp when
    the mesh has it). Differentiable; use under jax.value_and_grad
    exactly like the dense loss_fn.

    MoE aux collection under pp: each stage accumulates per-local-layer
    [E] vectors of top-1 counts and router-probability sums over the
    microbatches it actually processed (bubble ticks weighted 0), the
    balance term sums over local layers, and one psum over pp (and sp)
    rebuilds train.load_balance_loss EXACTLY — the nonlinear f·P product
    is formed per layer AFTER accumulation, never across partial
    batches or shards."""
    pp = mesh.shape[AXIS_PP]
    n_sp = mesh.shape.get(AXIS_SP, 1)
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={pp}")
    if cfg.n_experts > 0 and cfg.moe_capacity_factor > 0:
        # XLA's SPMD partitioner CHECK-crashes (spmd_partitioner_util.cc
        # replica-group mismatch) partitioning the grouped-dispatch
        # scatter over an auto ep axis inside a manual-pp shard_map;
        # dense dispatch partitions fine. Reject rather than segfault.
        raise ValueError("pp + grouped MoE dispatch (moe_capacity_factor"
                         " > 0) is not supported; use dense dispatch "
                         "(moe_capacity_factor=0) under pp")
    n_micro = int(n_microbatches)
    perm = [(i, i + 1) for i in range(pp - 1)]  # no wraparound: stage 0
    # receives ppermute's zero-fill, immediately overwritten by injection

    def pp_body(params, tokens, lengths):
        stage = jax.lax.axis_index(AXIS_PP)
        B, S = tokens.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by "
                             f"n_microbatches={n_micro}")
        if S % n_sp:
            raise ValueError(f"sequence {S} not divisible by sp={n_sp}")
        mb = B // n_micro
        sn = S // n_sp
        cos, sin = llama.get_rope_tables(cfg, S)
        if n_sp > 1:
            g0 = jax.lax.axis_index(AXIS_SP) * sn
        else:
            g0 = jnp.int32(0)
        positions = jnp.broadcast_to(
            g0 + jnp.arange(sn, dtype=jnp.int32), (mb, sn))

        # every stage embeds ITS shard (embedding + token ids replicate
        # over pp/sp; slicing before the embedding lookup keeps the
        # [*, Sn, D] activations — the memory that matters — sharded)
        toks_local = jax.lax.dynamic_slice_in_dim(tokens, g0, sn, axis=1)
        x_all = params["embedding"][toks_local].astype(cfg.jdtype)
        xs = x_all.reshape(n_micro, mb, sn, -1)
        toks_mb = tokens.reshape(n_micro, mb, S)
        lens_mb = lengths.reshape(n_micro, mb)

        def tick_compute(layers_local, x_in, lens_in):
            valid = positions < lens_in[:, None]
            if n_sp > 1:
                from ..ops.ring_attention import ring_causal_attention

                def attend(q, k, v):
                    return ring_causal_attention(q, k, v, lens_in,
                                                 axis_name=AXIS_SP)
            else:
                def attend(q, k, v):
                    return llama.causal_attention(q, k, v, mask=valid)
            return _stage_apply(layers_local, x_in, cfg, cos, sin,
                                positions, valid, attend)

        if remat:
            tick_compute = jax.checkpoint(tick_compute)

        moe = cfg.n_experts > 0
        state_x = jnp.zeros_like(xs[0])
        state_len = jnp.zeros((mb,), lengths.dtype)
        nll_sum = jnp.zeros((), jnp.float32)
        mask_sum = jnp.zeros((), jnp.float32)
        if moe:
            l_local = cfg.n_layers // pp
            cnt_sum = jnp.zeros((l_local, cfg.n_experts), jnp.float32)
            prob_sum = jnp.zeros((l_local, cfg.n_experts), jnp.float32)
        last = pp - 1
        for t in range(n_micro + pp - 1):
            j_in = min(t, n_micro - 1)     # microbatch entering stage 0
            x_in = jnp.where(stage == 0, xs[j_in], state_x)
            lens_in = jnp.where(stage == 0, lens_mb[j_in], state_len)
            y, probs = tick_compute(params["layers"], x_in, lens_in)
            if moe:
                # this tick is real work iff a microbatch is at this stage
                # (bubble outputs are finite — masked attention uses a
                # finite NEG_INF — so a 0-weight cleanly removes them)
                in_range = ((t - stage >= 0) & (t - stage < n_micro)
                            ).astype(jnp.float32)
                vmask = (positions < lens_in[:, None]
                         ).astype(jnp.float32)[None, ..., None]
                top1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1),
                                      cfg.n_experts)  # [l, mb, Sn, E]
                cnt_sum = cnt_sum + in_range * jnp.sum(
                    top1 * vmask, axis=(1, 2))
                prob_sum = prob_sum + in_range * jnp.sum(
                    probs * vmask, axis=(1, 2))
            j_out = t - last               # microbatch draining at the
            if 0 <= j_out < n_micro:       # last stage this tick (static)
                logits = llama.logits(params, cfg, y)  # final_norm inside
                n, m = loss_parts_local(logits, toks_mb[j_out], lens_in,
                                        g0, S)
                on_last = (stage == last).astype(jnp.float32)
                nll_sum = nll_sum + n * on_last
                mask_sum = mask_sum + m * on_last
            state_x = jax.lax.ppermute(y, AXIS_PP, perm)
            state_len = jax.lax.ppermute(lens_in, AXIS_PP, perm)
        # only the last stage accumulated; sp shards each hold partial
        # sums: psum over both manual axes publishes the global scalars
        nll_sum = jax.lax.psum(nll_sum, AXIS_PP)
        mask_sum = jax.lax.psum(mask_sum, AXIS_PP)
        if n_sp > 1:
            nll_sum = jax.lax.psum(nll_sum, AXIS_SP)
            mask_sum = jax.lax.psum(mask_sum, AXIS_SP)
        lm = nll_sum / jnp.maximum(mask_sum, 1.0)
        if not moe:
            return lm, jnp.zeros(())
        # per-layer f·P AFTER full accumulation (train.load_balance_loss
        # shape: E * mean_layers(sum_e f_e P_e) over valid tokens)
        total = jnp.maximum(
            jnp.sum(jnp.minimum(lengths, S).astype(jnp.float32)), 1.0)
        cnt_g = jax.lax.psum(cnt_sum, AXIS_SP) if n_sp > 1 else cnt_sum
        prob_g = jax.lax.psum(prob_sum, AXIS_SP) if n_sp > 1 else prob_sum
        local = jnp.sum((cnt_g / total) * (prob_g / total))
        aux = cfg.n_experts * jax.lax.psum(local, AXIS_PP) / cfg.n_layers
        return lm + moe_aux_weight * aux, aux

    def loss_fn(params, tokens, lengths):
        # manual over pp (+ sp): layer stacks enter stage-local
        # ([L/pp]); everything else replicates over the manual axes.
        # dp/fsdp/ep/tp stay auto — GSPMD partitions inside the stages
        # as usual. in_specs is a prefix pytree: one spec per top-level
        # param entry.
        param_specs = {k: (P(AXIS_PP) if k == "layers" else P())
                       for k in params}
        manual = {AXIS_PP} | ({AXIS_SP} if n_sp > 1 else set())
        fn = jax.shard_map(pp_body, mesh=mesh,
                           in_specs=(param_specs, P(), P()),
                           out_specs=(P(), P()), axis_names=manual,
                           check_vma=False)
        return fn(params, tokens, lengths)

    return loss_fn
