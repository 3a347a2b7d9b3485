"""Serial-vs-pipelined decode dispatch A/B (CPU; no chip lock).

Between two fused decode blocks the serial loop pays host work — reap
``device_get``, Python token delivery, re-dispatch — during which the
device sits idle. The depth-2 dispatch pipeline
(docs/advanced-guide/serving-scheduler.md) closes that gap by keeping a
second fused block queued on the device stream while the host reaps the
first (on the chip: PERF_LEDGER.jsonl, PR 29).

This harness proves the mechanism on the CPU backend, where the same
loop runs with the same instrumentation:

  arm "serial"     — GenerationEngine(decode_pipeline=1): the old
                     dispatch -> overlap-admissions -> reap loop.
  arm "pipelined"  — decode_pipeline=2: block N+1 dispatched before
                     block N is reaped.

Phase 1 (steady decode): identical seeded greedy workloads through both
arms. Gates: token-exact across arms (and vs the cache-free oracle),
the device stream's dry time a reap reduced >= 50%, the pipelined arm
keeps >= 1 block queued at a majority of steady-state reaps, and admits
>= served.

Phase 2 (mixed load): background throughput-class decodes + latency-
class TTFT probes on each arm. Gate: the pipelined arm's latency TTFT
p50 stays within the noise bound of the serial arm's (the depth policy
drops to 1 while a latency-class request waits that a free slot can
take, so pipelining must not buy throughput with TTFT).

Conventions (tools/README.md): the LAST stdout line is the JSON
artifact; ``--smoke`` is the CI gate (small shapes, same invariants);
full runs write ``DECODE_BENCH.json`` next to the repo root. Exit is
non-zero only when an invariant fails. The measured ratio re-runs on
device hardware ride along in the artifact's ``platform`` field.
"""

from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _build_engine(params, cfg, depth: int, *, slots: int, max_seq: int,
                  buckets, decode_block: int):
    from gofr_tpu.tpu import GenerationEngine

    return GenerationEngine(cfg, params, slots=slots, max_seq=max_seq,
                            prompt_buckets=buckets,
                            decode_block=decode_block,
                            decode_pipeline=depth)


def _reference_greedy(params, cfg, prompt, n):
    import jax.numpy as jnp

    from gofr_tpu.models import llama

    toks = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, cfg, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def run(smoke: bool) -> dict:
    import jax
    import numpy as np

    jax.config.update("jax_platforms", "cpu")

    from gofr_tpu.models import llama
    from gofr_tpu.models.common import LLAMA_CONFIGS
    from gofr_tpu.resilience import SLO_THROUGHPUT

    cfg = LLAMA_CONFIGS["tiny"]
    params = llama.init(cfg, jax.random.PRNGKey(0))
    slots = 4 if smoke else 8
    max_new = 48 if smoke else 160
    probes = 6 if smoke else 15
    buckets, max_seq, K = (8, 16), 512, 4

    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).tolist()
               for n in rng.integers(4, 16, slots)]
    oracle = [_reference_greedy(params, cfg, p, min(8, max_new))
              for p in prompts]

    arms: dict[str, dict] = {}
    tokens_by_arm: dict[str, list[list[int]]] = {}
    failures: list[str] = []

    for name, depth in (("serial", 1), ("pipelined", 2)):
        eng = _build_engine(params, cfg, depth, slots=slots,
                            max_seq=max_seq, buckets=buckets,
                            decode_block=K)
        try:
            eng.warmup()
            # -- phase 1: steady decode -------------------------------------
            t0 = time.perf_counter()
            streams = [eng.generate(p, max_new_tokens=max_new)
                       for p in prompts]
            outs = [s.tokens() for s in streams]
            dt = time.perf_counter() - t0
            tokens_by_arm[name] = outs
            total = sum(len(o) for o in outs)
            pipe = eng.stats()["scheduler"]["pipeline"]
            served = sum(1 for o in outs if o)
            admits = eng.stats()["total_requests"]
            arm = {
                "depth": depth,
                "tok_s": round(total / dt, 1),
                "tokens": total,
                "gap_p50_ms": pipe["gap_p50_ms"],
                "gap_samples": pipe["gap_samples"],
                "dry_ms_per_reap": pipe["dry_ms_per_reap"],
                "reaps": pipe["reaps"],
                "overlapped_reaps": pipe["overlapped_reaps"],
                "admits": admits,
                "served": served,
            }
            if admits < served:
                failures.append(f"{name}: admits {admits} < served {served}")
            for o, want in zip(outs, oracle):
                if o[:len(want)] != want:
                    failures.append(f"{name}: diverged from greedy oracle")
                    break

            # -- phase 2: latency TTFT under mixed load ---------------------
            bg = [eng.generate(rng.integers(1, cfg.vocab_size, 8).tolist(),
                               max_new_tokens=100_000,
                               slo_class=SLO_THROUGHPUT)
                  for _ in range(max(1, slots - 2))]
            time.sleep(0.2)  # reach steady background decode
            samples = []
            for _ in range(probes):
                prompt = rng.integers(1, cfg.vocab_size, 8).tolist()
                time.sleep(float(rng.uniform(0.0, 0.05)))
                t0 = time.perf_counter()
                s = eng.generate(prompt, max_new_tokens=2)
                next(iter(s))
                samples.append((time.perf_counter() - t0) * 1e3)
                s.cancel()
                list(s)
            for b in bg:
                b.cancel()
                list(b)
            arm["ttft_lat_p50_ms"] = round(statistics.median(samples), 2)
            arms[name] = arm
            log(f"  {name}: {arm['tok_s']} tok/s, dry "
                f"{arm['dry_ms_per_reap']} ms a reap (a dry interval's "
                f"p50 {arm['gap_p50_ms']} ms), {arm['overlapped_reaps']}/"
                f"{arm['reaps']} overlapped reaps, latency TTFT p50 "
                f"{arm['ttft_lat_p50_ms']} ms")
        finally:
            eng.close()

    # -- invariants --------------------------------------------------------
    if tokens_by_arm["serial"] != tokens_by_arm["pipelined"]:
        failures.append("depth-2 tokens differ from depth-1")
    # a dry interval is sampled only where the stream ran dry (a reap
    # with a block still queued adds none), so the arms are compared by
    # the dry time a reap, not by the median interval
    g_serial = arms["serial"]["dry_ms_per_reap"]
    g_piped = arms["pipelined"]["dry_ms_per_reap"]
    reduction = 0.0
    if g_serial is None or g_piped is None:
        failures.append("missing gap samples")
    else:
        reduction = 100.0 * (1 - g_piped / g_serial) if g_serial else 0.0
        if g_piped > 0.5 * g_serial:
            failures.append(f"dry time a reap reduced only {reduction:.0f}% "
                            f"({g_serial} -> {g_piped} ms; need >= 50%)")
    reaps = arms["pipelined"]["reaps"]
    overlapped = arms["pipelined"]["overlapped_reaps"]
    if reaps == 0 or overlapped * 2 < reaps:
        failures.append(f"pipelined arm kept a block queued at only "
                        f"{overlapped}/{reaps} reaps (need a majority)")
    ttft_ratio = (arms["pipelined"]["ttft_lat_p50_ms"]
                  / max(arms["serial"]["ttft_lat_p50_ms"], 1e-9))
    # CPU noise floor: the depth policy pins latency admissions to one
    # in-flight block, so the p50 must stay within 3x of serial (device
    # re-runs gate tighter against SLO_BENCH)
    if ttft_ratio > 3.0:
        failures.append(f"latency TTFT p50 ratio {ttft_ratio:.2f} > 3.0")

    out = {
        "bench": "dispatch_pipeline",
        "smoke": smoke,
        "platform": "cpu",
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "arms": arms,
        "exact_tokens": tokens_by_arm["serial"] == tokens_by_arm["pipelined"],
        "dry_ms_per_reap": {"serial": g_serial, "pipelined": g_piped},
        "gap_reduction_pct": round(reduction, 1),
        "overlapped_frac": round(overlapped / reaps, 3) if reaps else 0.0,
        "ttft_ratio_pipelined_vs_serial": round(ttft_ratio, 3),
        "ok": not failures,
    }
    if failures:
        out["failures"] = failures
    return out


def main() -> int:
    smoke = "--smoke" in sys.argv[1:]
    result = run(smoke)
    if not smoke and result["ok"]:
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "DECODE_BENCH.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
        log(f"  wrote {path}")
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
