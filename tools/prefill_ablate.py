"""Ablate the serving prefill dispatch to find where its time goes.

The TTFT decomposition (tools/ttft_probe.py) showed the prefill dispatch
dominating first-token latency on hardware (~160 ms for a 128-token
prompt where the weight-stream roofline says ~15 ms). This times the
same [1, Sb] serving prefill under surgical variants, one jit each:

    full        logits + KV stacks + quantize-on-write into the cache
                (exactly GenerationEngine._prefill_fn)
    nологits    skip lm_head entirely
    logit_pos   lm_head at ONE gathered position (the serving fix)
    no_write    return KV stacks, never touch the cache
    no_flash    jnp reference attention instead of the Pallas kernel
    fwd_only    _causal_scan without collecting KV stacks at all

Run it on the TPU (one process per chip):

    python tools/prefill_ablate.py [--lens 128,256,512] [--iters 20]

Prints one line per (len, variant) with median ms.
"""

from __future__ import annotations

import argparse
import functools
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lens", default="128,256,512")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.models.common import LLAMA_CONFIGS
    from gofr_tpu.tpu import random_params

    platform = jax.devices()[0].platform
    cfg = (LLAMA_CONFIGS["llama3-8b"] if platform != "cpu"
           else LLAMA_CONFIGS["tiny"])
    lens = tuple(int(x) for x in args.lens.split(","))
    if platform == "cpu":
        lens = tuple(min(x, 32) for x in lens)
    print(f"platform={platform} cfg={cfg.dim}d x {cfg.n_layers}L "
          f"slots={args.slots}", file=sys.stderr)

    params = random_params(llama.init, cfg, quant=True)
    cache = llama.init_cache(cfg, args.slots, args.max_seq, dtype=jnp.int8)
    rope = llama.get_rope_tables(cfg, args.max_seq)

    def full(cache, params, tokens, length, slot, flash, write,
             logits_mode):
        if logits_mode == "none":  # skip lm_head entirely
            x, (k, v), _, _ = llama._causal_scan(
                params, cfg, tokens, jnp.asarray([length]), args.max_seq,
                rope, None, collect_kv=True, flash=flash)
            out = x[0, 0, 0]  # keep a data dependency on the forward
        else:
            kw = {}
            if logits_mode == "pos":
                kw["logit_pos"] = jnp.asarray([length - 1])
            logits, k, v, _ = llama.prefill_kv(
                params, cfg, tokens, jnp.asarray([length]),
                rope_max=args.max_seq, rope_tables=rope, flash=flash, **kw)
            out = logits[0, 0] if logits_mode == "pos" else \
                jnp.take(logits[0], length - 1, axis=0)
        if write:
            lengths = cache.lengths.at[slot].set(length)
            cache = llama.write_kv(cache, k, v, (0, slot, 0, 0, 0), lengths)
        return out, cache

    def fwd_only(cache, params, tokens, length):
        x = llama.forward(params, cfg, tokens, jnp.asarray([length]),
                          rope_tables=rope)
        return x[0, 0, 0], cache

    variants = {
        "full": dict(flash=platform != "cpu", write=True,
                     logits_mode="full"),
        "logit_pos": dict(flash=platform != "cpu", write=True,
                          logits_mode="pos"),
        "no_logits": dict(flash=platform != "cpu", write=True,
                          logits_mode="none"),
        "no_write": dict(flash=platform != "cpu", write=False,
                         logits_mode="pos"),
        "no_flash": dict(flash=False, write=True, logits_mode="pos"),
    }

    rng = np.random.default_rng(0)
    for plen in lens:
        for name, kv in variants.items():
            jitted = jax.jit(
                functools.partial(full, **kv),
                donate_argnums=(0,), static_argnums=(4,))
            tokens = jnp.asarray(
                rng.integers(1, cfg.vocab_size, (1, plen)), jnp.int32)
            try:
                out, cache = jitted(cache, params, tokens, plen, 0)
                np.asarray(out)
                ts = []
                for _ in range(args.iters):
                    t0 = time.perf_counter()
                    out, cache = jitted(cache, params, tokens, plen, 0)
                    np.asarray(out)
                    ts.append((time.perf_counter() - t0) * 1e3)
                print(f"  len={plen:4d} {name:10s} "
                      f"{statistics.median(ts):8.2f} ms")
            except Exception as e:
                print(f"  len={plen:4d} {name:10s} FAILED "
                      f"{type(e).__name__}: {str(e)[:120]}")
        # forward-only baseline (no KV collection at all)
        jitted = jax.jit(fwd_only, donate_argnums=(0,))
        tokens = jnp.asarray(
            rng.integers(1, cfg.vocab_size, (1, plen)), jnp.int32)
        out, cache = jitted(cache, params, tokens, plen)
        np.asarray(out)
        ts = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            out, cache = jitted(cache, params, tokens, plen)
            np.asarray(out)
            ts.append((time.perf_counter() - t0) * 1e3)
        print(f"  len={plen:4d} {'fwd_only':10s} "
              f"{statistics.median(ts):8.2f} ms")


if __name__ == "__main__":
    main()
