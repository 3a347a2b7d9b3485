#!/usr/bin/env python3
"""A stand-in for the engine's delivery, with no device: what do the
transport and a load generator carry at N tokens a second?

``slots`` streams are kept full from a waiting line; every ``block_ms``
the loop pushes ``k`` tokens to each, step-major and inside one
``wire.burst()`` as ``GenerationEngine._reap`` does, through the real
gRPC server's zero-handoff path (``ServerStream`` over a ``PushStream``).
The loop sleeps where the device would run, so its own time is what a
block's delivery costs the engine's thread. Every five seconds it prints
tokens pushed a second, the share of slots full, the block's real period,
the callers waiting, the delivery's milliseconds a block, and how many
tokens went through the sink and through a stream's worker thread (a
stream that leaves the sink never comes back: PERF.md, PR 43).

    python3 tools/delivery_standin.py --port 9311 --block-ms 62.4 &
    python3 benchmarks/loadgen.py --address 127.0.0.1:9311 \\
        --traffic benchmarks/traffic/reason-sat.json --set ramp_s=8 \\
        --seed 7 --seconds 15 --vocab 100352 --probe-tokens 16 \\
        --out /tmp/samples.jsonl

128 slots x 4 tokens every 62.4 ms is 8,200 tokens a second. Rates on a
CPU sandbox are not the chip host's: compare two trees, not a tree with
the ledger.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gofr_tpu import wire  # noqa: E402
from gofr_tpu.grpcx import GRPCService, ServerStream  # noqa: E402
from gofr_tpu.grpcx import server as grpc_server  # noqa: E402

COUNT = {"sink": 0, "worker": 0}


def count_paths() -> None:
    """Count the tokens each path carried, from outside the transport."""
    sink, send = grpc_server._PushSender.sink, grpc_server._PushSender.send

    def counted_sink(self, item):
        ok = sink(self, item)
        COUNT["sink"] += bool(ok)
        return ok

    def counted_send(self, item):
        COUNT["worker"] += 1
        return send(self, item)

    grpc_server._PushSender.sink = counted_sink
    grpc_server._PushSender.send = counted_send


class Stream(wire.PushStream):
    def __init__(self, want: int):
        super().__init__()
        self.want = want
        self.sent = 0
        self.cancelled = False

    def cancel(self) -> None:
        self.cancelled = True


class Engine:
    def __init__(self, slots: int, k: int, block_ms: float):
        self.slots: list[Stream | None] = [None] * slots
        self.k = k
        self.dt = block_ms / 1000.0
        self.waiting: collections.deque[Stream] = collections.deque()
        self.generated = self.blocks = self.full_sum = 0
        self.deliver_s = 0.0
        threading.Thread(target=self.loop, daemon=True,
                         name="standin-engine").start()

    def generate(self, want: int) -> Stream:
        stream = Stream(want)
        self.waiting.append(stream)
        return stream

    def loop(self) -> None:
        due = time.monotonic()
        while True:
            for i, s in enumerate(self.slots):
                if (s is None or s.cancelled) and self.waiting:
                    self.slots[i] = self.waiting.popleft()
                elif s is not None and s.cancelled:
                    self.slots[i] = None
            due += self.dt  # the device's time: the loop sleeps through it
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            else:
                due = time.monotonic()
            live = [(i, s) for i, s in enumerate(self.slots) if s is not None]
            t0 = time.monotonic()
            with wire.burst():
                for _ in range(self.k):
                    for i, s in live:
                        if s.cancelled or self.slots[i] is not s:
                            continue
                        s._push(1000 + s.sent)
                        s.sent += 1
                        self.generated += 1
                        if s.sent >= s.want:
                            s._push(None)
                            self.slots[i] = None
            self.deliver_s += time.monotonic() - t0
            self.blocks += 1
            self.full_sum += len(live)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=9311)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--block-ms", type=float, default=62.4)
    args = ap.parse_args()
    count_paths()
    eng = Engine(args.slots, args.k, args.block_ms)
    llm = GRPCService("llm.Generation")

    @llm.server_stream("Generate")
    def generate(ctx, req):
        return ServerStream(eng.generate(req.get("max_new_tokens", 64)),
                            lambda t: {"token": t})

    srv = grpc_server.GRPCServer([llm], args.port)
    srv.start()
    print(json.dumps({"port": srv.port}), flush=True)
    then = (time.monotonic(), 0, 0, 0, 0.0)
    try:
        while True:
            time.sleep(5)
            now = (time.monotonic(), eng.generated, eng.blocks, eng.full_sum,
                   eng.deliver_s)
            dt, blocks = now[0] - then[0], max(1, now[2] - then[2])
            print(json.dumps({
                "pushed_tok_s": round((now[1] - then[1]) / dt, 1),
                "slots_full": round((now[3] - then[3]) / blocks
                                    / args.slots, 4),
                "block_ms": round(1000 * dt / blocks, 2),
                "waiting": len(eng.waiting),
                "deliver_ms_a_block": round(
                    1000 * (now[4] - then[4]) / blocks, 2),
                "through_sink": COUNT["sink"],
                "through_worker": COUNT["worker"]}), flush=True)
            then = now
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
