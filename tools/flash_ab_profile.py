#!/usr/bin/env python
"""A/B profile the fused XLA decode path vs the flash-decode kernel.

If flash-decode loses its hardware A/B again, capture profiler traces
of BOTH paths and write the postmortem. This tool runs each path for a handful of fused blocks under
``jax.profiler.trace`` and saves the traces side by side:

    /tmp/gofr_flash_ab/xla/      the jnp/XLA fused-block path
    /tmp/gofr_flash_ab/flash/    the Pallas flash-decode path

Open with TensorBoard (or xprof) elsewhere; the trace contains per-HLO
timing, DMA sizes, and MXU/VPU occupancy — enough to attribute the gap
(per-grid-step overhead vs DMA-skip benefit vs scheduling slack).

Also prints the same wall-clock A/B bench.py reports, so the traces
and the numbers come from the same run. One process per chip.

--mesh runs a different A/B: the shard_map'd mesh kernels (interpret
mode, GOFR_FLASH_INTERPRET=1) vs the jnp mesh reference, on tp=2 and
tp=4 factorizations of a virtual 8-device CPU mesh — no chip.
Token-exactness is gated STRICTLY (exit 1 on any mismatch or on a
silent fallback — the sharded kernel forms must actually dispatch);
CPU wall-clock numbers are ADVISORY only (interpret-mode emulation
says nothing about TPU perf; the device A/B above is the perf record).
The last stdout line is the JSON summary; --json-out also writes it to
a file (KERNEL_MESH_BENCH.json in CI / the committed record).

Usage:  python tools/flash_ab_profile.py [--cpu] [--batch 64]
        [--cache-len 1024] [--blocks 6]
        python tools/flash_ab_profile.py --mesh [--tp 2,4]
        [--json-out KERNEL_MESH_BENCH.json]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def init_backend(cpu: bool) -> None:
    """``cpu``: the virtual 8-device host mesh tests/conftest.py uses,
    set before first backend use."""
    import jax

    from gofr_tpu import compile_cache

    if cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
    compile_cache.configure()


def run_path(name: str, multistep, params, rope, tokens, cache, blocks,
             trace_dir):
    import jax
    import numpy as np

    # warm (compile + first block) outside the trace
    tokens2, cache = multistep(params, rope, tokens, cache)
    np.asarray(tokens2)
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        for _ in range(blocks):
            tokens2, cache = multistep(params, rope, tokens2, cache)
        np.asarray(tokens2)
    dt = time.perf_counter() - t0
    return dt, cache


MESH_PROMPTS = [[5, 17, 42, 7], [3, 1, 4, 1, 5, 9, 2, 6]]
MESH_NEW_TOKENS = 24


def _counted(module, name, counts):
    """Wrap module.name with a dispatch counter (trace-time proof the
    shard_map'd kernel form ran — exactness alone can't tell a kernel
    from a silent fallback to the identical-numerics reference)."""
    inner = getattr(module, name)

    def wrapper(*a, **kw):
        counts[name] = counts.get(name, 0) + 1
        return inner(*a, **kw)

    setattr(module, name, wrapper)


def _mesh_engine_arm(cfg, params, mesh, *, paged, env):
    """One engine arm: set env, build, generate (single-stream greedy —
    batched streams can flip borderline argmax between factorizations),
    time a warm repeat. Returns (token lists, advisory ms/token)."""
    import jax.numpy as jnp

    from gofr_tpu.tpu import GenerationEngine

    saved = {k: os.environ.get(k) for k in env}
    os.environ.update({k: v for k, v in env.items() if v is not None})
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
    try:
        extra = dict(paged_blocks=25, paged_block_size=8) if paged else {}
        eng = GenerationEngine(cfg, params, slots=4, max_seq=64,
                               prompt_buckets=(8, 16), mesh=mesh,
                               kv_dtype=jnp.int8, **extra)
        try:
            toks = [eng.generate(p, max_new_tokens=MESH_NEW_TOKENS).tokens()
                    for p in MESH_PROMPTS]
            t0 = time.perf_counter()  # warm: prompt 0's bucket is compiled
            eng.generate(MESH_PROMPTS[0],
                         max_new_tokens=MESH_NEW_TOKENS).tokens()
            ms = (time.perf_counter() - t0) / MESH_NEW_TOKENS * 1e3
        finally:
            eng.close()
        return toks, ms
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def mesh_main(args):
    import json

    import jax

    from gofr_tpu.models import llama
    from gofr_tpu.models.common import LLAMA_CONFIGS
    from gofr_tpu.ops import flash, flash_decode, paged_attention
    from gofr_tpu.parallel import make_mesh, shard_params

    init_backend(cpu=True)
    n_dev = len(jax.devices())
    counts = {}
    _counted(flash, "flash_prefill_sharded", counts)
    _counted(flash_decode, "flash_decode_sharded", counts)
    _counted(paged_attention, "paged_decode_sharded", counts)

    tiny = LLAMA_CONFIGS["tiny"]                       # n_kv_heads=2
    cfgs = {2: tiny, 4: tiny.with_(name="tiny4", n_kv_heads=4)}
    params = {tp: llama.init(cfgs[tp], jax.random.PRNGKey(1))
              for tp in cfgs}

    # kernel arm env; the jnp arm clears all three (on CPU without
    # interpret every *_auto dispatcher takes the reference path)
    kernel_env = {"GOFR_FLASH_INTERPRET": "1", "GOFR_FLASH_DECODE": "1",
                  "GOFR_FLASH_DECODE_FORCE": "1"}
    jnp_env = {k: None for k in kernel_env}

    arms = []
    for tp in (int(t) for t in args.tp.split(",")):
        cfg = cfgs[tp]
        mesh = make_mesh(tp=tp, dp=n_dev // tp)
        sharded = shard_params(params[tp], mesh)
        for engine in ("contiguous", "paged"):
            paged = engine == "paged"
            ref, ref_ms = _mesh_engine_arm(cfg, sharded, mesh,
                                           paged=paged, env=jnp_env)
            got, ker_ms = _mesh_engine_arm(cfg, sharded, mesh,
                                           paged=paged, env=kernel_env)
            arm = {"tp": tp, "engine": engine, "kv": "int8",
                   "jnp_ms_per_tok": round(ref_ms, 3),
                   "kernel_ms_per_tok": round(ker_ms, 3),
                   "tokens_exact": got == ref}
            arms.append(arm)
            print(f"tp={tp} {engine}: jnp {ref_ms:.2f} ms/tok, "
                  f"kernel {ker_ms:.2f} ms/tok (advisory), "
                  f"exact={arm['tokens_exact']}", flush=True)

    ok = (all(a["tokens_exact"] for a in arms)
          and all(counts.get(k, 0) > 0 for k in
                  ("flash_prefill_sharded", "flash_decode_sharded",
                   "paged_decode_sharded")))
    summary = {"bench": "mesh_kernels", "backend": "cpu-interpret",
               "devices": n_dev, "timings_advisory": True,
               "arms": arms, "kernels_dispatched": counts, "ok": ok}
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
            f.write("\n")
    print(json.dumps(summary, sort_keys=True), flush=True)
    if not ok:
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--cache-len", type=int, default=1024)
    ap.add_argument("--blocks", type=int, default=6)
    ap.add_argument("--decode-block", type=int, default=8)
    ap.add_argument("--out", default="/tmp/gofr_flash_ab")
    ap.add_argument("--mesh", action="store_true",
                    help="A/B shard_map'd mesh kernels (interpret) vs the "
                         "jnp mesh reference on a virtual CPU mesh")
    ap.add_argument("--tp", default="2,4",
                    help="comma-separated tp factors for --mesh")
    ap.add_argument("--json-out", default=None,
                    help="also write the --mesh JSON summary here")
    args = ap.parse_args()
    if args.mesh:
        return mesh_main(args)

    import jax
    import jax.numpy as jnp

    from gofr_tpu.models import llama
    from gofr_tpu.models.common import LLAMA_CONFIGS
    from gofr_tpu.tpu import random_params

    init_backend(cpu=args.cpu)
    cfg = LLAMA_CONFIGS["tiny" if args.cpu else "llama3-8b"]
    params = random_params(llama.init, cfg, quant=True)
    rope = llama.get_rope_tables(cfg, args.cache_len)

    def make(flash: bool):
        @functools.partial(jax.jit, donate_argnums=(3,))
        def multistep(params, rope, tokens, cache):
            def body(carry, _):
                tokens, cache = carry
                logits, cache = llama.decode_step(params, cfg, tokens,
                                                  cache, rope, flash=flash)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (tok, cache), tok

            (tokens, cache), _ = jax.lax.scan(body, (tokens, cache),
                                              None, length=args.decode_block)
            return tokens, cache

        return multistep

    results = {}
    for name, flash in (("xla", False), ("flash", True)):
        cache = llama.init_cache(cfg, args.batch, args.cache_len,
                                 dtype=jnp.int8)
        cache = cache._replace(lengths=jnp.full((args.batch,),
                                                args.cache_len // 2,
                                                jnp.int32))
        tokens = jnp.zeros((args.batch,), jnp.int32)
        dt, cache = run_path(name, make(flash), params, rope, tokens,
                             cache, args.blocks,
                             os.path.join(args.out, name))
        n = args.blocks * args.decode_block
        results[name] = dt / n * 1e3
        print(f"{name}: {dt / n * 1e3:.2f} ms/step "
              f"({args.batch * n / dt:.0f} tok/s), trace in "
              f"{os.path.join(args.out, name)}", flush=True)
        del cache

    faster = min(results, key=results.get)
    print(f"winner: {faster} "
          f"({results[faster]:.2f} vs "
          f"{results[max(results, key=results.get)]:.2f} ms/step)")


if __name__ == "__main__":
    main()
