#!/usr/bin/env python3
"""The load generator: a process of its own, so that it shares no GIL
with the scheduler loop it measures. Stdlib, ``benchmarks/traffic.py`` and
the program's gRPC client (``gofr_tpu.grpcx.dial``: the server speaks its
own HTTP/2 framing and JSON codec, and the client half of that is the only
way to call it without a third-party gRPC stack; importing it pulls in no
JAX, which ``main`` asserts).

    python benchmarks/loadgen.py --address 127.0.0.1:9001 \
        --traffic benchmarks/traffic/chat-rate.json --seed 7 --seconds 45 \
        --vocab 32768 --out samples.jsonl

Protocol on stdout, one JSON object per line:
  1. {"event": "schedule", "t_open": ..., "t_close": ...} once the probe
     has been answered: CLOCK_MONOTONIC seconds (system-wide on Linux) at
     which the measured window opens and closes. The ramp runs before it.
  2. {"event": "done", ...} after the last request due in the window has
     finished and the probe was answered again.
Per-request samples go to ``--out`` (JSON lines, times relative to t_open).

An open loop times each request from when it was DUE, sends on schedule
whatever the server does, and waits for every request due inside the
window. A closed loop keeps ``clients`` callers busy until the window
closes, counts the tokens that arrived inside it, and cuts what is still
streaming. No ``eos_id`` is sent: every stream runs to ``max_new_tokens``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import traffic  # noqa: E402

METHOD = "/llm.Generation/Generate"
CHANNELS = 4
PROBE_NEW = 8
STREAM_TIMEOUT_S = 300.0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class Run:
    def __init__(self, channels, vocab: int):
        self.channels = channels
        self.vocab = vocab
        self.t_open = self.t_close = 0.0
        self.closing = threading.Event()

    def stream(self, idx: int, payload: dict, rec: dict,
               cut_at_close: bool = False) -> list[int]:
        """Send one request and read its stream to the end, filling
        ``rec`` with absolute monotonic times. Returns the token ids."""
        ch = self.channels[idx % len(self.channels)]
        toks: list[int] = []
        rec["sent"] = time.monotonic()
        try:
            for msg in ch.server_stream(METHOD, payload,
                                        timeout=STREAM_TIMEOUT_S):
                now = time.monotonic()
                if not toks:
                    rec["first"] = now
                rec["last"] = now
                tok = msg.get("token")
                toks.append(tok)
                if not (isinstance(tok, int) and 0 <= tok < self.vocab):
                    rec["bad"] = rec.get("bad", 0) + 1
                if self.t_open <= now < self.t_close:
                    rec["in_window"] = rec.get("in_window", 0) + 1
                if cut_at_close and self.closing.is_set():
                    rec["cut"] = True
                    break  # closing the generator cancels the call
        except Exception as e:  # recorded per request, judged by run.py
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec["n"] = len(toks)
        return toks


def run_open(run: Run, reqs: list[dict], payloads: list[dict],
             recs: list[dict]) -> None:
    threads = []
    for r, payload, rec in zip(reqs, payloads, recs):
        due = run.t_open + r["due"]
        rec["due"] = due
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=run.stream, args=(r["idx"], payload, rec),
                             name=f"loadgen-{r['idx']}")
        t.start()
        threads.append(t)
    for t in threads:
        t.join(STREAM_TIMEOUT_S)


def run_closed(run: Run, clients: int, reqs: list[dict],
               payloads: list[dict], recs: list[dict]) -> bool:
    """True if the list ran out before the window closed (the traffic
    file's ``blocks`` is too small for this system)."""
    nxt = itertools.count(clients)  # next() is atomic under the GIL
    exhausted = threading.Event()

    def caller(j: int) -> None:
        i = j
        while time.monotonic() < run.t_close:
            if i >= len(reqs):
                exhausted.set()
                return
            run.stream(i, payloads[i], recs[i], cut_at_close=True)
            i = next(nxt)

    threads = [threading.Thread(target=caller, args=(j,),
                                name=f"loadgen-caller-{j}")
               for j in range(clients)]
    for t in threads:
        t.start()
    delay = run.t_close - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    run.closing.set()
    for t in threads:
        t.join(STREAM_TIMEOUT_S)
    return exhausted.is_set()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--address", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="key=json: override one traffic parameter "
                         "(run.py --rehearse cuts the mix to CPU size)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--length-scale", type=float, default=1.0)
    ap.add_argument("--probe-tokens", type=int, default=600)
    args = ap.parse_args()

    from gofr_tpu.grpcx import dial

    if "jax" in sys.modules:
        raise RuntimeError("the load generator must not import JAX")
    overrides = {k: json.loads(v) for k, v in
                 (s.split("=", 1) for s in args.set)}
    params = traffic.load(args.traffic, overrides)
    if args.length_scale != 1.0:
        params = traffic.scaled(params, args.length_scale)
    sched = traffic.build(params, args.seed, args.seconds)
    reqs = sched["requests"]
    payloads = [{"tokens": traffic.prompt_ids(args.seed, r["idx"],
                                              r["prompt"], args.vocab),
                 "max_new_tokens": r["output"]} for r in reqs]
    recs = [{"idx": r["idx"], "phase": r["phase"], "prompt": r["prompt"],
             "want": r["output"]} for r in reqs]

    run = Run([dial(args.address) for _ in range(CHANNELS)], args.vocab)
    # one greedy request alone on the idle engine, twice: a prefix-pool
    # miss, then a hit; after the drain it must return the same tokens
    probe = {"tokens": traffic.prompt_ids(args.seed, -1, args.probe_tokens,
                                          args.vocab),
             "max_new_tokens": PROBE_NEW}
    probes = [run.stream(0, probe, {}), run.stream(0, probe, {})]

    run.t_open = time.monotonic() + sched["ramp_s"] + 0.25
    run.t_close = run.t_open + sched["seconds"]
    emit({"event": "schedule", "t_open": run.t_open, "t_close": run.t_close,
          "loop": sched["loop"], "requests": len(reqs)})
    exhausted = False
    if sched["loop"] == "open":
        run_open(run, reqs, payloads, recs)
    else:
        exhausted = run_closed(run, sched["clients"], reqs, payloads, recs)
    t_drained = time.monotonic()
    probes.append(run.stream(0, probe, {}))
    for ch in run.channels:
        ch.close()

    with open(args.out, "w") as f:
        for rec in recs:
            if "sent" not in rec:
                continue  # a closed loop's list is longer than any run
            for k in ("due", "sent", "first", "last"):
                if k in rec:
                    rec[k] = round(rec[k] - run.t_open, 6)
            f.write(json.dumps(rec) + "\n")
    emit({"event": "done", "t_open": run.t_open, "t_close": run.t_close,
          "t_drained": t_drained, "exhausted": exhausted, "probes": probes,
          "probe_new": PROBE_NEW, "params": params})
    return 0


if __name__ == "__main__":
    sys.exit(main())
