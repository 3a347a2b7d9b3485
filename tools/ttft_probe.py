"""TTFT decomposition probe: where does first-token latency actually go?

Runs the bench_ttft workload (8B int8 engine, 30 busy decode slots,
probe prompts 128/256/512) and, for every probe, splits the observed
client TTFT into the engine's trace stamps (gofr_tpu/tpu/generator.py
GenStream.trace):

    wait     = admit        - submit        admission wait (decode block
                                            in flight when we arrived)
    prefill  = prefill_done - admit         the prefill dispatch itself
    store    = first_put    - prefill_done  prefix-store row copy etc.
    deliver  = client_recv  - first_put     queue wake-up + GIL

Optionally (--grpc) runs the same probes through a localhost grpcx
server-stream and reports the transport hop's extra cost per segment
(the server handler records when the request reached it).

Usage:  python tools/ttft_probe.py [--grpc] [--slots N] [--block K]
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time

sys.path.insert(0, __import__("os").path.join(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)),
    ".."))


def med(xs):
    return statistics.median(xs) if xs else float("nan")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grpc", action="store_true")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--block", type=int, default=4,
                    help="engine decode_block (serving default 4)")
    ap.add_argument("--probes", type=int, default=5)
    ap.add_argument("--admit-window-ms", type=float, default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="force the host backend (structural run at the "
                         "tiny preset)")
    ap.add_argument("--idle-prefill", action="store_true",
                    help="also time raw prefill dispatches per bucket on "
                         "an idle engine (no background decode)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.models.common import LLAMA_CONFIGS
    from gofr_tpu.tpu import GenerationEngine, random_params

    platform = jax.devices()[0].platform
    cfg = (LLAMA_CONFIGS["llama3-8b"] if platform != "cpu"
           else LLAMA_CONFIGS["tiny"])
    probe_lens = (128, 256, 512) if platform != "cpu" else (16, 32)
    print(f"platform={platform} slots={args.slots} block={args.block}",
          file=sys.stderr)

    kw = {}
    if args.admit_window_ms is not None:
        kw["admit_window_ms"] = args.admit_window_ms
    params = random_params(llama.init, cfg, quant=True)
    engine = GenerationEngine(cfg, params, slots=args.slots, max_seq=1024,
                              prompt_buckets=probe_lens,
                              kv_dtype=jnp.int8, decode_block=args.block,
                              **kw)
    rng = np.random.default_rng(0)
    try:
        engine.warmup()
        if args.idle_prefill:
            # raw prefill dispatch on the idle engine: generate() with no
            # background decode — admission is immediate, so trace
            # prefill ≈ the dispatch itself
            print("\nidle prefill (ms, median):", file=sys.stderr)
            for plen in probe_lens:
                ts = []
                for _ in range(args.probes):
                    s = engine.generate(
                        rng.integers(1, cfg.vocab_size, plen).tolist(),
                        max_new_tokens=1)
                    s.tokens()
                    tr = s.trace
                    ts.append((tr["prefill_done"] - tr["admit"]) * 1e3)
                print(f"  {plen:>5} {med(ts):8.1f}", file=sys.stderr)
        background = [
            engine.generate(rng.integers(1, cfg.vocab_size, 64).tolist(),
                            max_new_tokens=4096)
            for _ in range(max(0, args.slots - 2))
        ]
        time.sleep(0.5)

        def probe_engine(plen: int) -> dict:
            prompt = rng.integers(1, cfg.vocab_size, plen).tolist()
            # decorrelate from the block cycle (serial probes otherwise
            # phase-lock their submit to a reap boundary)
            time.sleep(rng.uniform(0.0, 0.15))
            t0 = time.monotonic()
            s = engine.generate(prompt, max_new_tokens=2)
            it = iter(s)
            next(it)
            t1 = time.monotonic()
            tr = dict(s.trace)
            s.cancel()
            for _ in it:
                pass
            return {
                "total": (t1 - t0) * 1e3,
                "enqueue": (tr["submit"] - t0) * 1e3,
                "wait": (tr["admit"] - tr["submit"]) * 1e3,
                "prefill": (tr["prefill_done"] - tr["admit"]) * 1e3,
                "store": (tr["first_put"] - tr["prefill_done"]) * 1e3,
                "deliver": (t1 - tr["first_put"]) * 1e3,
            }

        segs = ("total", "enqueue", "wait", "prefill", "store", "deliver")
        rows: dict[int, list[dict]] = {}
        for plen in probe_lens:
            rows[plen] = [probe_engine(plen) for _ in range(args.probes)]
        print("\nengine-level (ms, median over "
              f"{args.probes} probes):", file=sys.stderr)
        print(f"  {'len':>5} " + " ".join(f"{s:>8}" for s in segs),
              file=sys.stderr)
        for plen, rs in rows.items():
            print(f"  {plen:>5} " + " ".join(
                f"{med([r[s] for r in rs]):8.1f}" for s in segs),
                file=sys.stderr)

        if args.grpc:
            from gofr_tpu.grpcx import GRPCServer, GRPCService, dial

            llm = GRPCService("llm.Generation")
            handler_traces = []

            @llm.server_stream("Generate")
            def generate(ctx, req):
                t_in = time.monotonic()
                s = engine.generate(req["tokens"], max_new_tokens=2)
                try:
                    first = True
                    for tok in s:
                        if first:
                            handler_traces.append(
                                {"handler_in": t_in, **s.trace,
                                 "handler_out": time.monotonic()})
                            first = False
                        yield {"token": tok}
                finally:
                    s.cancel()

            srv = GRPCServer([llm], port=0)
            srv.start()
            channel = dial(f"127.0.0.1:{srv.port}")
            try:
                grows = {}
                for plen in probe_lens:
                    samples = []
                    for _ in range(args.probes):
                        prompt = rng.integers(
                            1, cfg.vocab_size, plen).tolist()
                        time.sleep(rng.uniform(0.0, 0.15))  # see above
                        t0 = time.monotonic()
                        it = channel.server_stream(
                            "/llm.Generation/Generate",
                            {"tokens": prompt, "max_new_tokens": 2})
                        next(iter(it))
                        t1 = time.monotonic()
                        tr = handler_traces[-1]
                        samples.append({
                            "total": (t1 - t0) * 1e3,
                            "to_handler": (tr["handler_in"] - t0) * 1e3,
                            "wait": (tr["admit"] - tr["submit"]) * 1e3,
                            "prefill": (tr["prefill_done"]
                                        - tr["admit"]) * 1e3,
                            "h_wake": (tr["handler_out"]
                                       - tr["first_put"]) * 1e3,
                            "to_client": (t1 - tr["handler_out"]) * 1e3,
                        })
                    grows[plen] = samples
                gsegs = ("total", "to_handler", "wait", "prefill",
                         "h_wake", "to_client")
                print("\ngRPC-level (ms, median):", file=sys.stderr)
                print(f"  {'len':>5} " + " ".join(f"{s:>10}" for s in gsegs),
                      file=sys.stderr)
                for plen, rs in grows.items():
                    print(f"  {plen:>5} " + " ".join(
                        f"{med([r[s] for r in rs]):10.1f}" for s in gsegs),
                        file=sys.stderr)
            finally:
                channel.close()
                srv.stop()

        for b in background:
            b.cancel()
    finally:
        engine.close()


if __name__ == "__main__":
    main()
