"""Headline benchmarks: Llama-3-8B int8 decode throughput + p50 TTFT.

Targets (BASELINE.json north star, TPU v5e):
  - streaming decode >= 2,000 tok/s/chip
  - p50 TTFT < 150 ms through the serving engine under decode load

Decode measures the serving hot loop — batched single-token decode against
a preallocated INT8 KV cache (quantize-on-write, dequant fused into
attention), greedy sampling fused into the jitted step, cache donated
between steps (zero copies). TTFT measures prompt-submit -> first-token
through GenerationEngine admission (prefill dispatch) while decode slots
are busy — the p50 a streaming client actually sees.

The LAST stdout line is the artifact: {"metric", "value", "unit",
"vs_baseline", ...}; earlier lines carry a "partial" marker. Extra keys
(ttft_p50_ms, batch, <section>_error) ride along. Diagnostics go to
stderr.

A run without a TPU is an error (exit 1), not a CPU fallback; so is a run
in which any section failed (the other sections still run, so one call
reports everything that is broken). ``--cpu`` is the explicit STRUCTURAL
mode: the tiny preset on 8 virtual host devices, exercising the code
paths and producing no device number.
"""

from __future__ import annotations

import functools
import json
import statistics
import subprocess
import sys
import time

BASELINE_TOK_S = 2000.0   # BASELINE.json north_star, TPU v5e
TARGET_TTFT_MS = 150.0    # BASELINE.json north_star p50 TTFT
METRIC = "llama3_8b_int8_decode_tok_s_chip"
CPU_MODE = "--cpu" in sys.argv[1:]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def init_backend():
    """The device list for this process. ``--cpu`` forces the host
    backend fanned out to 8 virtual devices, so the structural run
    exercises the mesh arm (tp=2) the way tests/conftest.py does;
    otherwise anything but a TPU is an error."""
    import jax

    from gofr_tpu import compile_cache

    if CPU_MODE:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
    # each section child re-traces the same programs; the persistent
    # cache turns all but the first child's compiles into loads
    compile_cache.configure()
    devices = jax.devices()
    if not CPU_MODE and devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0].platform is "
            f"{devices[0].platform!r} (pass --cpu for the structural run)")
    return devices


def bench_dispatch_floor(steps: int = 64) -> float:
    """ms per dispatch of a trivial donated jit — the host dispatch
    floor, so the report separates host cost from step compute."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @functools.partial(jax.jit, donate_argnums=(0,))
    def triv(x):
        return x + 1

    x = jnp.zeros((64,), jnp.int32)
    x = triv(x)
    np.asarray(x)
    t0 = time.perf_counter()
    for _ in range(steps):
        x = triv(x)
    np.asarray(x)
    return (time.perf_counter() - t0) / steps * 1e3


def bench_decode(cfg, batch: int, cache_len: int, steps: int = 64,
                 kv_dtype=None, decode_block: int = 8) -> dict:
    """Steady-state decode: the serving hot loop — K decode+sample steps
    fused on device per dispatch (lax.scan, exactly the GenerationEngine
    decode-block structure), cache donated through. Also times the
    single-step-per-dispatch variant so the report shows how much the
    host costs when it IS on the per-token path.

    Returns {"tok_s", "fused_step_ms", "dispatch_step_ms", "batch"}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.tpu import random_params

    kv_dtype = kv_dtype if kv_dtype is not None else jnp.int8
    params = random_params(llama.init, cfg, quant=True)
    cache = llama.init_cache(cfg, batch, cache_len, dtype=kv_dtype)
    rope = llama.get_rope_tables(cfg, cache_len)
    # simulate prefill at the HALF-FULL point: decode attention reads
    # what is live (ops.flash_decode), so a nearly-empty cache would
    # flatter the step
    cache = cache._replace(lengths=jnp.full((batch,), cache_len // 2,
                                            jnp.int32))
    tokens = jnp.zeros((batch,), jnp.int32)

    # params/rope passed as arguments (NOT closed over: closure arrays get
    # captured as lowering constants — 8.5GB baked into the executable).
    @functools.partial(jax.jit, donate_argnums=(3,))
    def step(params, rope, tokens, cache):
        logits, cache = llama.decode_step(params, cfg, tokens, cache, rope)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    @functools.partial(jax.jit, donate_argnums=(3,))
    def multistep(params, rope, tokens, cache):
        def body(carry, _):
            tokens, cache = carry
            logits, cache = llama.decode_step(params, cfg, tokens, cache,
                                              rope)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (tok, cache), tok

        (tokens, cache), toks = jax.lax.scan(body, (tokens, cache), None,
                                             length=decode_block)
        return tokens, cache, toks

    # np.asarray fetches the final tokens inside the timed region, which
    # transitively requires every step to have run.
    t0 = time.perf_counter()
    tokens, cache = step(params, rope, tokens, cache)
    np.asarray(tokens)
    log(f"  compile+first step: {time.perf_counter() - t0:.1f}s")
    for _ in range(3):
        tokens, cache = step(params, rope, tokens, cache)
    np.asarray(tokens)

    n_single = max(8, steps // 4)
    t0 = time.perf_counter()
    for _ in range(n_single):
        tokens, cache = step(params, rope, tokens, cache)
    np.asarray(tokens)
    dispatch_step_ms = (time.perf_counter() - t0) / n_single * 1e3

    t0 = time.perf_counter()
    tokens, cache, toks = multistep(params, rope, tokens, cache)
    np.asarray(toks)
    log(f"  multistep compile+first block: {time.perf_counter() - t0:.1f}s")
    blocks = max(1, steps // decode_block)
    t0 = time.perf_counter()
    for _ in range(blocks):
        tokens, cache, toks = multistep(params, rope, tokens, cache)
    np.asarray(toks)
    dt = time.perf_counter() - t0
    n_fused = blocks * decode_block
    tok_s = batch * n_fused / dt
    fused_step_ms = dt / n_fused * 1e3
    log(f"  batch={batch} cache={cache_len} kv={jnp.dtype(kv_dtype).name} "
        f"K={decode_block}: {n_fused} fused steps in {dt:.3f}s -> "
        f"{tok_s:.0f} tok/s ({fused_step_ms:.2f} ms/step fused, "
        f"{dispatch_step_ms:.2f} ms/step per-dispatch)")
    out = {"tok_s": tok_s, "fused_step_ms": fused_step_ms,
           "dispatch_step_ms": dispatch_step_ms, "batch": batch}

    return out


def bench_paged_decode(cfg, batch: int, live_len: int, steps: int = 64,
                       decode_block: int = 8, block_t: int = 128) -> dict:
    """Paged-pool decode at batches the contiguous cache cannot fit.

    The pool is sized to the LIVE tokens (batch x (live_len + the run's
    decode room)) instead of batch x max_seq — at 8B/int8 that admits
    batch 128 with ~4.8 GB of KV next to the 8 GB weight stream, where
    contiguous rows OOM past ~96 (the road past 4k tok/s). Same
    fused-block structure as bench_decode; attention runs the
    scalar-prefetch paged kernel (ops.paged_attention)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.models.paged_llama import (init_paged_cache,
                                             paged_decode_step)
    from gofr_tpu.tpu import random_params

    room = steps + decode_block  # tokens decoded during the run
    blocks_per_slot = -(-(live_len + room) // block_t)
    mb = blocks_per_slot
    n_blocks = batch * blocks_per_slot + 1
    params = random_params(llama.init, cfg, quant=True)
    cache = init_paged_cache(cfg, batch, n_blocks, block_t, dtype=jnp.int8)
    cache = cache._replace(
        lengths=jnp.full((batch,), live_len, jnp.int32))
    # slot b owns blocks [1 + b*bps, 1 + (b+1)*bps) — preallocated to
    # cover the whole run, so the table is constant across dispatches
    table = np.zeros((batch, mb), np.int32)
    for b in range(batch):
        table[b] = 1 + b * blocks_per_slot + np.arange(blocks_per_slot)
    table = jnp.asarray(table)
    rope = llama.get_rope_tables(cfg, mb * block_t)
    tokens = jnp.zeros((batch,), jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(3,))
    def multistep(params, rope, tokens, cache, table):
        def body(carry, _):
            tokens, cache = carry
            logits, cache = paged_decode_step(params, cfg, tokens, cache,
                                              table, rope_tables=rope)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (tok, cache), tok

        (tokens, cache), toks = jax.lax.scan(body, (tokens, cache),
                                             None, length=decode_block)
        return tokens, cache, toks

    # pool footprint, so an OOM at this batch is attributable from the
    # log alone: int8 K+V pools + f32 scale planes, next to the int8
    # projections + bf16 embedding the params stream
    pool_bytes = 2 * cfg.n_layers * n_blocks * block_t * cfg.n_kv_heads \
        * (cfg.head_dim + 4)
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    w_bytes = cfg.n_layers * (2 * cfg.dim * cfg.dim
                              + 2 * cfg.dim * kv_dim
                              + 3 * cfg.dim * cfg.ffn_dim) \
        + cfg.vocab_size * cfg.dim * 3  # bf16 embedding + int8 lm_head
    log(f"  paged pool: {n_blocks} blocks x {block_t} tok = "
        f"{pool_bytes / 2**30:.2f} GiB KV "
        f"(~{w_bytes / 2**30:.1f} GiB weights alongside)")
    t0 = time.perf_counter()
    tokens, cache, toks = multistep(params, rope, tokens, cache, table)
    np.asarray(toks)
    log(f"  paged compile+first block: {time.perf_counter() - t0:.1f}s")
    blocks = max(1, steps // decode_block)
    t0 = time.perf_counter()
    for _ in range(blocks):
        tokens, cache, toks = multistep(params, rope, tokens, cache, table)
    np.asarray(toks)
    dt = time.perf_counter() - t0
    n = blocks * decode_block
    out = {"tok_s": batch * n / dt, "step_ms": dt / n * 1e3,
           "batch": batch, "live_len": live_len,
           "pool_gib": round(pool_bytes / 2**30, 2)}
    log(f"  paged batch={batch} live={live_len} T={block_t}: "
        f"{n} fused steps in {dt:.3f}s -> {out['tok_s']:.0f} tok/s "
        f"({out['step_ms']:.2f} ms/step)")
    return out


def _is_oom(e: BaseException) -> bool:
    msg = f"{type(e).__name__}: {e}"
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


def bench_decode_best(cfg, batches, cache_len: int):
    """Largest batch that fits wins (decode throughput scales with tokens
    per weight pass until HBM runs out). Returns the bench_decode dict or
    {"tok_s": 0.0, "batch": None} when nothing fits."""
    for batch in batches:
        try:
            return bench_decode(cfg, batch=batch, cache_len=cache_len)
        except Exception as e:
            # Only HBM exhaustion triggers the batch-shrink retry; anything
            # else is a real bug and must fail the benchmark loudly (the
            # top-level handler still emits a structured error line).
            if not _is_oom(e):
                raise
            log(f"  batch={batch} OOM, shrinking: {str(e)[:160]}")
    return {"tok_s": 0.0, "batch": None}


def flash_smoke() -> str:
    """Run the Pallas flash prefill kernel FOR REAL on the hardware backend
    and check numerics on valid rows vs the jnp reference. Interpret-mode
    tests are the numerics oracle, never the existence proof (an
    unloweable kernel was once green in CI for a whole round).
    Returns "ok" or raises."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.ops.attention import causal_attention
    from gofr_tpu.ops.flash import flash_causal_prefill

    B, S, H, KV, D = 2, 512, 8, 4, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KV, D), jnp.bfloat16)
    lengths = jnp.asarray([S, 300], jnp.int32)
    out = np.asarray(flash_causal_prefill(q, k, v, lengths))  # no interpret
    mask = jax.lax.broadcasted_iota(jnp.int32, (B, S), 1) < lengths[:, None]
    ref = np.asarray(causal_attention(q, k, v, mask=mask))
    valid = np.asarray(mask)[:, :, None, None]
    err = float((np.abs(out.astype(np.float32) - ref.astype(np.float32))
                 * valid).max())
    if err > 0.1:  # bf16 tolerance; padded rows excluded by design
        raise AssertionError(f"flash kernel numerics off on hardware: {err}")
    log(f"  flash smoke: lowered + ran on hardware, max valid-row err {err:.4f}")
    return "ok"


def bench_ttft(cfg, *, slots: int, probe_lens=(128, 256, 512),
               probes_per_len: int = 5, max_seq: int = 1024,
               grpc: bool = True, paged_blocks: int = 0) -> dict:
    """p50 TTFT (ms), prompt-submit -> first token, while other slots are
    decoding — the latency a streaming client sees. Measured at BOTH
    levels the north star cares about: through the engine's admission
    path, and end-to-end through a real gRPC server-stream on localhost
    (grpcx over its own HTTP/2 wire — the BASELINE.json config #3
    transport). Buckets are pre-warmed (steady-state serving; cold-compile
    is a deploy cost, not a per-request one)."""
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.tpu import GenerationEngine, random_params

    params = random_params(llama.init, cfg, quant=True)
    engine = GenerationEngine(cfg, params, slots=slots, max_seq=max_seq,
                              prompt_buckets=tuple(probe_lens),
                              kv_dtype=jnp.int8,
                              paged_blocks=paged_blocks)
    rng = np.random.default_rng(0)
    srv = channel = None
    try:
        engine.warmup()
        # background decode load: fill all but 2 slots with long decodes
        background = [
            engine.generate(rng.integers(1, cfg.vocab_size, 64).tolist(),
                            max_new_tokens=4096)
            for _ in range(max(0, slots - 2))
        ]
        time.sleep(0.5)  # let the loop reach steady-state decode
        samples_ms = []
        for plen in probe_lens:
            for _ in range(probes_per_len):
                prompt = rng.integers(1, cfg.vocab_size, plen).tolist()
                # decorrelate from the decode-block cycle: a serial probe
                # otherwise submits right after a reap (its previous
                # drain completes at a block boundary) and always eats a
                # near-full block of admission wait — real arrivals are
                # uniform over the cycle, and p50 should measure that
                time.sleep(rng.uniform(0.0, 0.15))
                t0 = time.perf_counter()
                stream = engine.generate(prompt, max_new_tokens=2)
                it = iter(stream)
                next(it)  # first token delivered
                ttft = (time.perf_counter() - t0) * 1e3
                samples_ms.append(ttft)
                stream.cancel()
                for _ in it:  # drain so the slot retires
                    pass
        by_len = {}
        i = 0
        for plen in probe_lens:
            chunk = samples_ms[i:i + probes_per_len]
            i += probes_per_len
            by_len[plen] = statistics.median(chunk)
            log(f"  ttft p50 @ prompt={plen}: {by_len[plen]:.1f} ms")
        p50 = statistics.median(samples_ms)
        log(f"  ttft p50 overall: {p50:.1f} ms over {len(samples_ms)} probes "
            f"({max(0, slots - 2)} busy slots)")
        out = {"p50_ms": p50, "by_len": by_len, "n": len(samples_ms)}

        if grpc:
            # gRPC hop: same engine, fronted by the real server + client.
            # Failures here must not discard the engine-level numbers
            # already measured above — report them as a string instead.
            try:
                from gofr_tpu.grpcx import (GRPCServer, GRPCService,
                                            ServerStream, dial)
                from gofr_tpu.tracing import InMemoryExporter, Tracer

                llm = GRPCService("llm.Generation")

                @llm.server_stream("Generate")
                def generate(ctx, req):
                    s = engine.generate(
                        req["tokens"],
                        max_new_tokens=req.get("max_new_tokens", 2))
                    # zero-handoff: first-token bytes leave on the
                    # serving-loop thread (ISSUE 2 transport fast path);
                    # the transport cancels the stream at RPC end
                    return ServerStream(s, lambda tok: {"token": tok})

                class _TraceShim:
                    logger = None
                    exporter = InMemoryExporter()
                    tracer = Tracer(service_name="bench-ttft",
                                    exporter=exporter)

                srv = GRPCServer([llm], port=0, container=_TraceShim())
                srv.start()
                channel = dial(f"127.0.0.1:{srv.port}")
                grpc_samples = []
                for plen in probe_lens:
                    for _ in range(probes_per_len):
                        prompt = rng.integers(1, cfg.vocab_size, plen).tolist()
                        time.sleep(rng.uniform(0.0, 0.15))  # see above
                        t0 = time.perf_counter()
                        it = channel.server_stream(
                            "/llm.Generation/Generate",
                            {"tokens": prompt, "max_new_tokens": 2})
                        next(iter(it))
                        grpc_samples.append((time.perf_counter() - t0) * 1e3)
                out["grpc_p50_ms"] = statistics.median(grpc_samples)
                log(f"  ttft p50 through gRPC stream: {out['grpc_p50_ms']:.1f} ms "
                    f"over {len(grpc_samples)} probes")
                # transport-stage decomposition from the grpc.* spans
                # (grpc.handoff = engine _deliver -> transport write
                # start, grpc.hpack = header encode, grpc.frame-write =
                # the coalesced HEADERS+DATA write): attributes the
                # engine-vs-wire split of the gRPC TTFT gap per round
                stages = {}
                for sp in _TraceShim.exporter.spans:
                    if sp.name.startswith("grpc."):
                        stages.setdefault(sp.name, []).append(
                            sp.duration_us / 1e3)
                if stages:
                    out["grpc_stage_p50_ms"] = {
                        name: round(statistics.median(v), 4)
                        for name, v in sorted(stages.items())}
                    log("  grpc transport stages p50 (ms): "
                        + ", ".join(f"{k.split('.', 1)[1]}={v}"
                                    for k, v in
                                    out["grpc_stage_p50_ms"].items()))
            except Exception as e:
                log(f"  grpc ttft failed: {type(e).__name__}: {str(e)[:160]}")
                out["grpc_error"] = f"{type(e).__name__}: {str(e)[:160]}"
        for b in background:
            b.cancel()
        return out
    finally:
        if channel is not None:
            channel.close()
        if srv is not None:
            srv.stop()
        engine.close()


def bench_engine(cfg, *, slots: int = 48, new_tokens: int = 96,
                 max_seq: int = 256, paged_blocks: int = 0,
                 engine=None) -> dict:
    """Throughput through the FULL serving stack — engine loop,
    admission, fused decode blocks, host delivery — not just raw steps:
    fill every slot with a stream, wall-clock all tokens out. The gap to
    the raw fused-step number is the serving loop's overhead (GIL,
    delivery, admission checks); it should be small.

    ``paged_blocks > 0`` runs the same workload over the paged engine —
    the serving-stack sibling of bench_paged_decode's raw-step number,
    at slot counts the contiguous cache cannot hold.

    ``engine``: drive a caller-built engine instead (the one-process
    arms run builds each arm from its config rows); the caller keeps
    ownership and closes it."""
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.tpu import GenerationEngine, random_params

    owns = engine is None
    if owns:
        params = random_params(llama.init, cfg, quant=True)
        engine = GenerationEngine(cfg, params, slots=slots, max_seq=max_seq,
                                  prompt_buckets=(32,), kv_dtype=jnp.int8,
                                  decode_block=8, paged_blocks=paged_blocks)
    slots = engine.n_slots
    rng = np.random.default_rng(2)
    try:
        engine.warmup()
        prompts = [rng.integers(1, cfg.vocab_size, 16).tolist()
                   for _ in range(slots)]
        t0 = time.perf_counter()
        streams = [engine.generate(p, max_new_tokens=new_tokens)
                   for p in prompts]
        total = sum(len(s.tokens()) for s in streams)
        dt = time.perf_counter() - t0
        out = {"tok_s": total / dt, "tokens": total}
        pipe = engine.stats()["scheduler"]["pipeline"]
        out["gap_p50_ms"] = pipe["gap_p50_ms"]
        out["overlapped_reaps"] = pipe["overlapped_reaps"]
        out["reaps"] = pipe["reaps"]
        log(f"  engine throughput: {total} tokens in {dt:.2f}s -> "
            f"{out['tok_s']:.0f} tok/s (slots={slots}, K=8, incl. "
            f"admission+delivery; gap p50 {pipe['gap_p50_ms']} ms, "
            f"{pipe['overlapped_reaps']}/{pipe['reaps']} overlapped reaps)")
        return out
    finally:
        if owns:
            engine.close()


def bench_spec_decode(cfg, *, slots: int = 32, k: int = 4,
                      new_tokens: int = 96, engine=None) -> dict:
    """Speculative-decoding win on a repetitive greedy workload (the
    workload class prompt-lookup exists for: code, JSON, templated
    text). Every slot streams a strongly periodic prompt, so the verify
    pass emits multiple tokens per weight stream; the realized
    multiplier is stats()['spec_decode']['tokens_per_window'] and the
    wall-clock number is directly comparable to engine_tok_s (same
    serving stack, same slot count scale).

    ``engine``: drive a caller-built engine (the one-process arms run
    builds the spec arm from its TPU_SPEC_DECODE config row); caller
    closes it."""
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.tpu import GenerationEngine, random_params

    owns = engine is None
    if owns:
        params = random_params(llama.init, cfg, quant=True)
        engine = GenerationEngine(cfg, params, slots=slots, max_seq=256,
                                  prompt_buckets=(32,), kv_dtype=jnp.int8,
                                  decode_block=8, spec_decode_k=k)
    slots = engine.n_slots
    k = engine._spec_k or k
    rng = np.random.default_rng(3)
    try:
        engine.warmup()
        prompts = []
        for _ in range(slots):
            period = rng.integers(1, cfg.vocab_size, 4).tolist()
            prompts.append((period * 8)[:30])
        t0 = time.perf_counter()
        streams = [engine.generate(p, max_new_tokens=new_tokens)
                   for p in prompts]
        total = sum(len(s.tokens()) for s in streams)
        dt = time.perf_counter() - t0
        st = engine.stats().get("spec_decode", {})
        out = {"tok_s": total / dt,
               "tokens_per_window": st.get("tokens_per_window", 0.0)}
        log(f"  spec decode: {total} tokens in {dt:.2f}s -> "
            f"{out['tok_s']:.0f} tok/s "
            f"({out['tokens_per_window']:.2f} tok/window, slots={slots}, "
            f"K={k})")
        return out
    finally:
        if owns:
            engine.close()


def bench_prefix(cfg, *, prefix_len: int = 896, tail_len: int = 64,
                 probes: int = 5, engine=None) -> dict:
    """Prefix-KV-cache win, idle engine: first-token latency for a
    960-token prompt, cold (full chunked prefill) vs warm (the shared
    896-token prefix restores as one HBM row copy; only the final
    128-bucket recomputes). Same prompt family either way — only the
    pool state differs."""
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.tpu import GenerationEngine, random_params

    owns = engine is None
    if owns:
        params = random_params(llama.init, cfg, quant=True)
        engine = GenerationEngine(cfg, params, slots=4, max_seq=1024,
                                  prompt_buckets=(128, 256, 512),
                                  kv_dtype=jnp.int8, prefix_cache_slots=4,
                                  prefix_store_min=256)
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
    try:
        engine.warmup()

        def probe(shared_prefix: bool) -> float:
            times = []
            for _ in range(probes):
                head = prefix if shared_prefix else \
                    rng.integers(1, cfg.vocab_size, prefix_len).tolist()
                prompt = head + rng.integers(1, cfg.vocab_size,
                                             tail_len).tolist()
                t0 = time.perf_counter()
                s = engine.generate(prompt, max_new_tokens=1)
                next(iter(s))
                times.append((time.perf_counter() - t0) * 1e3)
                s.cancel()
                list(s)
            return statistics.median(times)

        miss = probe(False)       # every head is fresh: full prefill
        engine.generate(prefix + [1] * tail_len,
                        max_new_tokens=1).tokens()  # ensure stored
        hit = probe(True)
        st = engine.stats().get("prefix_cache", {})
        log(f"  prefix cache: miss {miss:.1f} ms -> hit {hit:.1f} ms "
            f"({st.get('hits', 0)} hits)")
        return {"miss_ms": miss, "hit_ms": hit}
    finally:
        if owns:
            engine.close()


def engine_from_rows(cfg, params, rows: dict, defaults: dict | None = None):
    """GenerationEngine from ``TPU_*`` config rows — the same keys
    ``new_engine_from_config`` reads, so an arm definition IS a
    deployable serving config (bench injects its int8 random weights in
    place of TPU_WEIGHTS; everything else is the config row). This is
    what makes the spec arm "a config, not a code path": its whole
    definition is ``{"TPU_SPEC_DECODE": "4"}`` and the engine it builds
    leases every device buffer (cache, spec state, prefix pool) from
    the HBM arbiter exactly like production serving."""
    import jax.numpy as jnp

    from gofr_tpu.config import MapConfig
    from gofr_tpu.tpu import GenerationEngine

    c = MapConfig({**(defaults or {}), **rows})
    buckets = tuple(int(b) for b in
                    c.get_or_default("TPU_SEQ_BUCKETS", "32").split(","))
    kv = jnp.int8 if c.get_or_default("TPU_KV_DTYPE", "int8") == "int8" \
        else None
    mesh = None
    spec = c.get("TPU_SHARDING")
    if spec:
        # the mesh arm IS a config row too: THE parser
        # new_engine_from_config uses, weights re-placed onto the
        # mesh exactly like the production wiring does
        from gofr_tpu.parallel import shard_params
        from gofr_tpu.tpu import parse_mesh

        mesh = parse_mesh(spec)
        params = shard_params(params, mesh)
    return GenerationEngine(
        cfg, params, mesh=mesh,
        slots=c.get_int("TPU_SLOTS", 48),
        max_seq=c.get_int("TPU_MAX_SEQ", 256),
        prompt_buckets=buckets,
        kv_dtype=kv,
        decode_block=c.get_int("TPU_DECODE_BLOCK", 8),
        decode_pipeline=c.get_int("TPU_DECODE_PIPELINE", 2),
        spec_decode_k=c.get_int("TPU_SPEC_DECODE", 0),
        prefix_cache_slots=c.get_int("TPU_PREFIX_CACHE", 0),
        prefix_store_min=c.get_int("TPU_PREFIX_MIN", 0) or None,
        paged_blocks=c.get_int("TPU_PAGED_BLOCKS", 0),
        paged_block_size=c.get_int("TPU_PAGED_BLOCK", 128))


def bench_arms(cfg, *, slots: int = 48, paged_slots: int = 128) -> dict:
    """Every serving arm in ONE process under the HBM arbiter — the run
    the PR 10 arbiter was built for. The 2026-07-31 capture ran each
    arm in its own child and prefix/engine/spec/paged all DIED with
    RESOURCE_EXHAUSTED; with the arbiter, construction leases bytes
    against one process budget (reclaim-then-retry, 429-shed on
    overshoot), so the honest outcomes are per-arm ``ok`` or ``shed``
    — never a process death.

    Arms are config-row dicts interpreted by engine_from_rows; one
    int8 weight set loads once and streams through every arm. Records
    per-arm status + timing + the arbiter's final lease book."""
    import jax

    from gofr_tpu.models import llama
    from gofr_tpu.tpu import hbm, random_params

    small = jax.default_backend() == "cpu"  # structural run (dev / CI)
    if small:
        slots, paged_slots = 8, 8
    new_tokens = 24 if small else 96
    params = random_params(llama.init, cfg, quant=True)
    defaults = {"TPU_KV_DTYPE": "int8", "TPU_DECODE_BLOCK": "8"}
    # the structural run's prompts must fit the tiny config's 128-token
    # cache (max_seq clamps to the model's)
    pfx_len, pfx_tail, pfx_probes = (80, 16, 2) if small else (896, 64, 5)
    pfx_rows = ({"TPU_SLOTS": "4", "TPU_MAX_SEQ": "128",
                 "TPU_SEQ_BUCKETS": "32,64", "TPU_PREFIX_CACHE": "4",
                 "TPU_PREFIX_MIN": "64"} if small else
                {"TPU_SLOTS": "4", "TPU_MAX_SEQ": "1024",
                 "TPU_SEQ_BUCKETS": "128,256,512", "TPU_PREFIX_CACHE": "4",
                 "TPU_PREFIX_MIN": "256"})
    order = [
        ("engine",
         {"TPU_SLOTS": str(slots), "TPU_MAX_SEQ": "256",
          "TPU_SEQ_BUCKETS": "32"},
         lambda e: bench_engine(cfg, new_tokens=new_tokens, engine=e)),
        ("spec",
         {"TPU_SLOTS": str(min(32, slots)), "TPU_MAX_SEQ": "256",
          "TPU_SEQ_BUCKETS": "32", "TPU_SPEC_DECODE": "4"},
         lambda e: bench_spec_decode(cfg, new_tokens=new_tokens, engine=e)),
        ("prefix", pfx_rows,
         lambda e: bench_prefix(cfg, prefix_len=pfx_len,
                                tail_len=pfx_tail, probes=pfx_probes,
                                engine=e)),
        ("paged_engine",
         {"TPU_SLOTS": str(paged_slots), "TPU_MAX_SEQ": "256",
          "TPU_SEQ_BUCKETS": "32",
          "TPU_PAGED_BLOCKS": str(paged_slots + 15)},
         lambda e: bench_engine(cfg, new_tokens=new_tokens, engine=e)),
    ]
    # the MESH arm: tensor-parallel serving as one more config row
    # (TPU_SHARDING=tp=2, the rest of the slice on dp), gated alongside
    # the other first-class modes in this one process under the arbiter
    # — on CPU structural runs init_backend fanned the host out to 8
    # virtual devices (jax_num_cpu_devices), so the sharded paths run
    # hermetically. Skipped (and not required) only when the device
    # count cannot factor a tp=2 mesh.
    n_dev = jax.device_count()
    if n_dev >= 2 and n_dev % 2 == 0:
        mesh_rows = {"TPU_SLOTS": str(min(8, slots)), "TPU_MAX_SEQ": "256",
                     "TPU_SEQ_BUCKETS": "32",
                     "TPU_SHARDING": f"tp=2,dp={n_dev // 2}"}
        order.append(("mesh", mesh_rows,
                      lambda e: bench_engine(cfg, new_tokens=new_tokens,
                                             engine=e)))
    order = tuple(order)
    arms = {}
    for name, rows, drive in order:
        t0 = time.perf_counter()
        engine = None
        try:
            engine = engine_from_rows(cfg, params, rows, defaults)
            res = drive(engine)
            arms[name] = {"status": "ok", "rows": rows,
                          "seconds": round(time.perf_counter() - t0, 1),
                          **{k: (round(v, 2) if isinstance(v, float) else v)
                             for k, v in res.items()}}
        except Exception as e:  # noqa: BLE001 — each arm reports its own fate
            shed = isinstance(e, hbm.HBMExhausted) or _is_oom(e)
            arms[name] = {"status": "shed" if shed else "error",
                          "rows": rows,
                          "seconds": round(time.perf_counter() - t0, 1),
                          "error": f"{type(e).__name__}: {str(e)[:200]}"}
        finally:
            if engine is not None:
                engine.close()
        log(f"  arm {name}: {arms[name]['status']}")
    sheds = sum(1 for a in arms.values() if a["status"] == "shed")
    errors = sum(1 for a in arms.values() if a["status"] == "error")
    # the first-class-serving-mode gate: speculative decoding is a
    # supported config row (TPU_SPEC_DECODE, config-reference.md), so
    # the spec arm must pass ALONGSIDE prefix/engine/paged in this one
    # process — "ok" for every required arm, or the section is red
    required = [name for name, _, _ in order]
    return {"arms": arms, "one_process": True, "deaths": 0,
            "sheds": sheds, "errors": errors,
            "required": required,
            "all_required_ok": all(
                arms.get(n, {}).get("status") == "ok" for n in required),
            "hbm": hbm.arbiter_stats()}


def main_cpu() -> int:
    """``--cpu``: structural run of the tiny preset on the host backend,
    in this process (host RAM has no HBM-lifecycle problem). The numbers
    are CPU timings of a toy model — not device metrics."""
    init_backend()

    from gofr_tpu.models.common import LLAMA_CONFIGS

    cfg = LLAMA_CONFIGS["tiny"].with_(dtype="bfloat16")
    payload = {"metric": "llama_tiny_cpu_decode_tok_s", "value": 0.0,
               "unit": "tok/s", "vs_baseline": 0.0, "platform": "cpu"}
    try:
        res = bench_decode(cfg, batch=8, cache_len=128, steps=32,
                           decode_block=4)
        payload["value"] = round(res["tok_s"], 1)
        ttft = bench_ttft(cfg, slots=4, probe_lens=(16, 32), max_seq=128)
        payload["ttft_p50_ms"] = round(ttft["p50_ms"], 1)
        if "grpc_p50_ms" in ttft:
            payload["ttft_grpc_p50_ms"] = round(ttft["grpc_p50_ms"], 1)
        if "grpc_stage_p50_ms" in ttft:
            payload["ttft_grpc_stage_p50_ms"] = ttft["grpc_stage_p50_ms"]
        if "grpc_error" in ttft:
            payload["ttft_grpc_error"] = ttft["grpc_error"]
    except Exception as e:  # keep whatever was measured before the error
        payload["error"] = f"{type(e).__name__}: {str(e)[:200]}"
    emit(payload)
    return 1 if "error" in payload or "ttft_grpc_error" in payload else 0


def run_section(args) -> int:
    """Child-process entry: run ONE section against a fresh backend and
    print its result dict as the last stdout line; the exit code is 1
    when that dict carries an error. Each section owning a whole
    process is the HBM-lifecycle fix: 8.6 GB of section state (params +
    compiled-program constants + engine caches) survives a section's
    Python scope in backend/cache layers that engine.close() cannot
    reach. Process exit is the one release point XLA guarantees; it
    also contains a section segfault/OOM so later sections still run."""
    try:
        devices = init_backend()
    except Exception as e:
        emit({"error":
              f"backend init failed: {type(e).__name__}: {str(e)[:300]}"})
        return 1

    import jax

    from gofr_tpu.models.common import LLAMA_CONFIGS

    platform = devices[0].platform
    if args.section == "probe":
        emit({"platform": platform, "device_kind": devices[0].device_kind,
              "devices": jax.device_count()})
        return 0
    # sections are dispatched on the TPU path only; --cpu lets any single
    # section be exercised structurally at the tiny preset
    # (e.g. `python bench.py --section arms --cpu`)
    cfg = LLAMA_CONFIGS["tiny" if CPU_MODE else "llama3-8b"]
    try:
        if args.section == "headline":
            out = {}
            try:
                out["floor_ms"] = round(bench_dispatch_floor(), 2)
                log(f"  dispatch floor: {out['floor_ms']:.2f} ms")
            except Exception as e:
                log(f"  dispatch floor probe failed: "
                    f"{type(e).__name__}: {str(e)[:120]}")
            out.update(bench_decode_best(
                cfg, (112, 96, 80, 64, 48, 32, 24, 16, 8), cache_len=1024))
            try:
                out["flash_smoke"] = flash_smoke()
            except Exception as e:
                log(f"  flash smoke FAILED: {type(e).__name__}: {str(e)[:200]}")
                out["flash_smoke"] = \
                    f"FAILED: {type(e).__name__}: {str(e)[:200]}"
            emit(out)
            return 1 if out["flash_smoke"] != "ok" else 0
        elif args.section == "ttft":
            emit(bench_ttft(cfg, slots=args.slots))
        elif args.section == "ttft_paged":
            # the paged pool is the headline serving config — TTFT must
            # hold there too. Engine-level only (the transport hop is
            # already measured on the contiguous engine). Pool: 30
            # background slots × 8 blocks at capacity + probes + slack.
            emit(bench_ttft(cfg, slots=args.slots, grpc=False,
                            paged_blocks=290))
        elif args.section == "prefix":
            emit(bench_prefix(cfg))
        elif args.section == "engine":
            emit(bench_engine(cfg))
        elif args.section == "spec":
            emit(bench_spec_decode(cfg))
        elif args.section == "arms":
            arms = bench_arms(cfg)
            emit(arms)
            return 0 if arms["all_required_ok"] else 1
        elif args.section == "paged":
            # live_len matches the contiguous sweep's half-full point
            # (cache_len//2 = 512) so the promoted headline compares the
            # two configs on identical KV workloads — with the v3
            # DMA-skip, attention cost tracks live length, so a lighter
            # paged workload would flatter the pool. Same pool size
            # either way: ceil((512+72)/128) = ceil((448+72)/128) = 5
            # blocks/slot.
            emit(bench_paged_decode(cfg, batch=args.paged_batch,
                                    live_len=512))
        elif args.section == "paged_engine":
            # full serving stack over the paged pool at the slot count
            # the raw sweep proved (--slots). Pool sizing: a stream's
            # cursor peaks at 16+96=112 < 128, so one block per slot;
            # + trash + slack
            emit(bench_engine(cfg, slots=args.slots,
                              paged_blocks=args.slots + 15))
        else:
            emit({"error": f"unknown section {args.section!r}"})
            return 1
    except Exception as e:
        emit({"error": f"{type(e).__name__}: {str(e)[:300]}",
              "oom": _is_oom(e)})
        return 1
    return 0


def run_child(section: str, *extra: str, timeout: float) -> dict:
    """Run one section in a subprocess; return its result dict.

    stderr is inherited (live diagnostics); stdout is captured and the
    last JSON line is the result. The parent never imports JAX on the
    TPU path — a chip belongs to one process at a time, so a client
    held by the parent would starve every child."""
    cmd = [sys.executable, __file__, "--section", section, *extra]
    try:
        p = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"")
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        log(f"  section {section} killed after {timeout:.0f}s")
        return {"error": f"section timed out after {timeout:.0f}s",
                "stdout_tail": out[-200:]}
    for line in reversed(p.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return {"error": f"section {section} produced no JSON "
                     f"(rc={p.returncode}, stdout tail: {p.stdout[-200:]!r})"}


def main() -> int:
    """The TPU run: every section in its own child; exit 1 when there is
    no TPU or any section failed."""
    probe = run_child("probe", timeout=300)
    if "error" in probe:
        emit({"metric": METRIC, "value": 0.0, "unit": "tok/s",
              "vs_baseline": 0.0, "error": probe["error"]})
        return 1
    log(f"bench: platform={probe['platform']} "
        f"device_kind={probe['device_kind']} devices={probe['devices']}")

    res = run_child("headline", timeout=1800)
    if "error" in res or not res.get("tok_s"):
        emit({"metric": METRIC, "value": 0.0, "unit": "tok/s",
              "vs_baseline": 0.0,
              "error": res.get("error", "decode produced no throughput")})
        return 1
    tok_s, used = res["tok_s"], res.get("batch")
    payload = {
        "metric": METRIC,
        "value": round(tok_s, 1),
        "unit": "tok/s",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 3),
        "batch": used,
        "platform": probe["platform"],
        "device_kind": probe["device_kind"],
        "devices": probe["devices"],
    }
    if "floor_ms" in res:
        payload["dispatch_floor_ms"] = res["floor_ms"]
    if "fused_step_ms" in res:
        payload["fused_step_ms"] = round(res["fused_step_ms"], 2)
        payload["dispatch_step_ms"] = round(res["dispatch_step_ms"], 2)
    if "flash_smoke" in res:
        payload["flash_smoke"] = res["flash_smoke"]
    # snapshot: if a runner kills the remaining (slower) sections, the
    # stream still ends with a parsable headline line; the complete
    # payload re-emits at the end and supersedes this one.
    emit({**payload, "partial": "ttft/prefix/engine sections pending"})

    def section(name: str, *extra: str, timeout: float = 1500.0) -> dict:
        return run_child(name, *extra, timeout=timeout)

    ttft = section("ttft", "--slots", str(min(used or 8, 32)))
    if "error" in ttft:
        payload["ttft_error"] = ttft["error"]
    else:
        payload["ttft_p50_ms"] = round(ttft["p50_ms"], 1)
        if "grpc_p50_ms" in ttft:
            payload["ttft_grpc_p50_ms"] = round(ttft["grpc_p50_ms"], 1)
        if "grpc_stage_p50_ms" in ttft:
            payload["ttft_grpc_stage_p50_ms"] = ttft["grpc_stage_p50_ms"]
        if "grpc_error" in ttft:
            payload["ttft_grpc_error"] = ttft["grpc_error"]
        payload["ttft_target_ms"] = TARGET_TTFT_MS
    emit({**payload, "partial": "sections after ttft pending"})
    tp = section("ttft_paged", "--slots", str(min(used or 8, 32)))
    if "error" in tp:
        payload["ttft_paged_error"] = tp["error"]
    else:
        payload["ttft_paged_p50_ms"] = round(tp["p50_ms"], 1)
    emit({**payload, "partial": "arms + paged sweep pending"})
    # ALL serving arms in ONE process under the HBM arbiter (the run
    # PR 10 was built for): prefix/engine/spec/paged_engine construct
    # through hbm.alloc leases, the spec arm is a TPU_SPEC_DECODE
    # config row, and the outcome per arm is ok-or-shed, never a
    # process death (the 2026-07-31 capture lost all four to
    # RESOURCE_EXHAUSTED in per-section children).
    arms = section("arms", timeout=3000.0)
    if "error" in arms:
        payload["arms_error"] = arms["error"]
    else:
        payload["arms"] = arms["arms"]
        payload["arms_one_process"] = {
            "deaths": arms["deaths"], "sheds": arms["sheds"],
            "errors": arms["errors"]}
        # GATE: spec is a first-class serving mode — the run is only
        # green when the spec arm passes alongside prefix/engine/paged
        # in one process under the arbiter (ROADMAP leftover, PR 11)
        payload["arms_gate"] = {
            "required": arms.get("required", []),
            "all_required_ok": bool(arms.get("all_required_ok")),
            "spec_ok": arms["arms"].get("spec", {}).get("status") == "ok"}
        a = arms["arms"]
        # lift the headline per-arm numbers into their historical keys
        # so dashboards and round-over-round diffs keep working
        if a.get("prefix", {}).get("status") == "ok":
            payload["prefix_miss_ttft_ms"] = round(a["prefix"]["miss_ms"], 1)
            payload["prefix_hit_ttft_ms"] = round(a["prefix"]["hit_ms"], 1)
        if a.get("engine", {}).get("status") == "ok":
            payload["engine_tok_s"] = round(a["engine"]["tok_s"], 1)
            payload["engine_gap_p50_ms"] = a["engine"].get("gap_p50_ms")
        if a.get("spec", {}).get("status") == "ok":
            payload["spec_tok_s"] = round(a["spec"]["tok_s"], 1)
            payload["spec_tokens_per_window"] = round(
                a["spec"]["tokens_per_window"], 2)
        if a.get("paged_engine", {}).get("status") == "ok":
            payload["paged_engine_tok_s"] = round(
                a["paged_engine"]["tok_s"], 1)
    # a kill during the (long) paged sweep must not cost the measured
    # sections: the last stdout line stays a valid, honest artifact
    emit({**payload, "partial": "paged sweep pending"})
    # paged-pool sweep: contiguous rows OOM past ~96; the pool admits
    # 128 (~5.5 GB at 512 live tokens/slot next to the 8.6 GB weight
    # stream) and 160 (~6.9 GB) is worth an attempt now that each try
    # runs in a fresh process. Shrinks like bench_decode_best.
    for paged_batch in (160, 144, 128, 112, 96):
        paged = section("paged", "--paged-batch", str(paged_batch))
        if "error" not in paged:
            payload["paged_tok_s"] = round(paged["tok_s"], 1)
            payload["paged_step_ms"] = round(paged["step_ms"], 2)
            payload["paged_batch"] = paged_batch
            payload.pop("paged_error", None)
            break
        if paged.get("oom"):
            log(f"  paged batch={paged_batch} OOM, shrinking")
            payload["paged_error"] = "OOM at every paged batch (160..96)"
            continue  # overwritten by a success or smaller batch's error
        payload["paged_error"] = paged["error"]
        break
    if "paged_tok_s" in payload:
        # (the paged serving-stack number now comes from the one-process
        # arms section above; the raw sweep keeps the headline promotion)
        # headline = the best SERVING decode config. The paged pool is a
        # production path (TPU_PAGED_BLOCKS), not a synthetic sweep —
        # when it beats contiguous rows (more slots per weight stream),
        # it IS the number a deployment gets. Provenance in value_config.
        if payload["paged_tok_s"] > payload["value"]:
            payload["value_config"] = (
                f"paged pool, batch={payload['paged_batch']} "
                f"(contiguous best: {payload['value']} @ batch={used})")
            payload["value"] = payload["paged_tok_s"]
            payload["batch"] = payload["paged_batch"]  # keep the pair
            payload["vs_baseline"] = round(
                payload["value"] / BASELINE_TOK_S, 3)
    emit(payload)
    failed = sorted(k for k in payload if k.endswith("_error"))
    if payload.get("flash_smoke", "ok") != "ok":
        failed.append("flash_smoke")
    if not payload.get("arms_gate", {}).get("all_required_ok", False):
        failed.append("arms_gate")
    if failed:
        log(f"bench: FAILED sections: {failed}")
    return 1 if failed else 0


def _parse_args():
    import argparse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--section", default=None)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--paged-batch", type=int, default=128)
    ap.add_argument("--cpu", action="store_true")
    args, _ = ap.parse_known_args()
    return args


if __name__ == "__main__":
    _args = _parse_args()
    if _args.section:
        sys.exit(run_section(_args))
    sys.exit(main_cpu() if CPU_MODE else main())
