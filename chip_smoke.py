#!/usr/bin/env python3
"""First light on the chip: the main serving path, end to end, once.

    python chip_smoke.py          # on a machine with one TPU (or four:
                                  # TPU_SHARDING=tp=4 python chip_smoke.py)

One process — it holds the chip and starts no child. Phases:

  device   JAX finds a TPU; versions, device and compile-cache directory.
  kernels  every Pallas entry point the serving path can reach, compiled
           NON-interpreted at the Llama-3-8B head geometry and compared
           with its jax.numpy reference on valid rows.
  server   the token-streaming example exactly as a user starts it
           (examples/tpu-token-streaming/main.py + its configs/.env; the
           process environment overrides the file): Llama-3-8B at full
           width, random int8 weights from a seed, int8 KV, 48 slots.
           Health, concurrent HTTP and gRPC streams, a prefix-pool hit
           with equal greedy output, /metrics, graceful stop, no
           framework thread left behind.

A failed phase is named, the phases after it still run where they can,
and the exit code is 1. On success — and only then — stdout carries two
lines: the full JSON summary (phases, compile and serve seconds, tokens,
HBM, arbiter, native), then as the LAST line the result and nothing but
the result: {"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}. Everything else, the failure summary included, goes to
stderr.

Rehearsal without a chip (the device and kernel phases then fail, the
exit code is 1, the server phase exercises the request logic):

    JAX_PLATFORMS=cpu TPU_MODEL=tiny TPU_SEQ_BUCKETS=32,64 \
        python chip_smoke.py

(the tiny preset holds 128 positions, so its prompt buckets stop at 64.)
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import os
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
EXAMPLE = os.path.join(REPO, "examples", "tpu-token-streaming")
# Llama-3-8B attention geometry: 32 query heads over 8 KV heads of 128
H, KV, D = 32, 8, 128
# max abs error on valid rows — bench.flash_smoke's bound: bf16 outputs up
# to ~5 in magnitude sit an ulp (0.03) apart, a broken kernel is off by O(1)
KERNEL_TOL = 0.1
NEW_TOKENS = 16


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def run_phase(summary: dict, name: str, fn) -> None:
    rec = {"ok": False}
    summary["phases"][name] = rec
    t0 = time.monotonic()
    try:
        fn(summary, rec)
        rec["ok"] = True
    except Exception as e:  # the phase boundary: record, report, go on
        traceback.print_exc()
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    rec["seconds"] = round(time.monotonic() - t0, 1)
    log(f"== phase {name}: {'ok' if rec['ok'] else 'FAILED'} "
        f"in {rec['seconds']}s"
        + ("" if rec["ok"] else f" — {rec['error']}"))


# -- phase 1: device ---------------------------------------------------------

def phase_device(summary: dict, rec: dict) -> None:
    import importlib.metadata

    import jax
    import jaxlib

    from gofr_tpu import compile_cache

    summary["cache_dir"] = compile_cache.configure()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    rec["versions"] = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                       "libtpu": libtpu,
                       "python": sys.version.split()[0]}
    devices = jax.devices()
    summary["device"] = {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)}
    log(f"versions={rec['versions']} device={summary['device']} "
        f"cache_dir={summary['cache_dir']}")
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"platform is {devices[0].platform!r}, not 'tpu'")


# -- phase 2: kernels --------------------------------------------------------

def _max_err(got, ref, valid=None) -> float:
    import numpy as np

    d = np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32))
    if valid is not None:
        d = d * np.asarray(valid, np.float32)
    return float(d.max())


def _clamped_table(lengths, mb: int, block_t: int):
    """Slot b owns blocks [1 + b*mb, 1 + (b+1)*mb) (block 0 is the trash
    block); entries past a slot's last live block repeat it — the layout
    the engine's host side maintains for the paged kernels."""
    import numpy as np

    table = np.zeros((len(lengths), mb), np.int32)
    for b, n in enumerate(lengths):
        last = max(-(-int(n) // block_t) - 1, 0)
        table[b] = 1 + b * mb + np.minimum(np.arange(mb), last)
    return table


def kernel_cases(interpret: bool = False):
    """(name, thunk) per kernel entry point; each thunk compiles and runs
    the kernel and returns its max abs error against the jnp reference
    on valid rows. ``interpret`` exists for rehearsing the harness on a
    CPU; the smoke itself never sets it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.ops import attention, flash, flash_decode, paged_attention
    from gofr_tpu.ops.quant import quantize_kv

    def rand(i, shape):
        return jax.random.normal(jax.random.PRNGKey(i), shape, jnp.bfloat16)

    def prefill(s):
        def run():
            q, k, v = (rand(1, (2, s, H, D)), rand(2, (2, s, KV, D)),
                       rand(3, (2, s, KV, D)))
            lengths = jnp.asarray([s, s * 5 // 8], jnp.int32)
            mask = jnp.arange(s)[None, :] < lengths[:, None]
            got = flash.flash_causal_prefill(q, k, v, lengths,
                                             interpret=interpret)
            ref = attention.causal_attention(q, k, v, mask=mask)
            return _max_err(got, ref, mask[:, :, None, None])
        return run

    # decode-side fixtures: 8 slots, capacity 1024 = 8 blocks of 128
    b, smax, block_t = 8, 1024, 128
    mb = smax // block_t

    def decode(quant):
        def run():
            # read through a stacked cache [L, B, KV, Smax, D] at its last
            # layer, as the serving step does; two dead slots
            lengths = jnp.asarray([0, 1, 127, 128, 129, 500, 0, 1023],
                                  jnp.int32)
            q, kn, vn = (rand(4, (b, 1, H, D)), rand(5, (b, 1, KV, D)),
                         rand(6, (b, 1, KV, D)))
            kc, vc = (rand(7, (2, b, KV, smax, D)),
                      rand(8, (2, b, KV, smax, D)))
            ks = vs = None
            if quant:
                (kc, ks), (vc, vs) = quantize_kv(kc), quantize_kv(vc)
            got = flash_decode.flash_decode_stacked(
                q, kc, vc, kn, vn, lengths, jnp.int32(1), ks, vs,
                block_s=flash_decode.block_size(smax), interpret=interpret)
            ref = attention.decode_attention_appended(
                q, kc[1], vc[1], kn, vn, lengths,
                None if ks is None else ks[1], None if vs is None else vs[1])
            return _max_err(got, ref)
        return run

    def append(quant):
        def run():
            # the step's write: a row a slot, all layers and KV heads, at
            # word, tile and cache edges, one slot at capacity (dropped);
            # every byte of both caches against XLA's scatter, and every
            # bit of an int8 cache's two scale tables against the select
            # over the whole table that the kernel's visit replaced
            pos = jnp.asarray([0, 1, 127, 128, 129, 500, smax - 1, smax],
                              jnp.int32)
            kc, vc, kr, vr = (rand(14, (2, b, KV, smax, D)),
                              rand(15, (2, b, KV, smax, D)),
                              rand(16, (2, b, KV, D)), rand(17, (2, b, KV, D)))
            tables = new = ()
            if quant:
                (kc, ks), (vc, vs), (kr, ksr), (vr, vsr) = (
                    quantize_kv(x) for x in (kc, vc, kr, vr))
                tables, new = (ks, vs), (ksr, vsr)
            got = flash_decode.append_rows_stacked(
                kc, vc, kr, vr, pos, *tables, *new, interpret=interpret)
            slots = jnp.arange(b)
            err = max(_max_err(g, c.at[:, slots, :, pos].set(
                jnp.moveaxis(r, 1, 0), mode="drop"))
                for g, c, r in zip(got, (kc, vc), (kr, vr)))
            here = (jnp.arange(smax)[None, :] == pos[:, None])[None, :, None]
            for g, table, r in zip(got[2:], tables, new):
                want = jnp.where(here, r[..., None], table)
                bits = [np.asarray(x).view(np.int32).astype(np.int64)
                        for x in (g, want)]
                assert (np.asarray(want) != np.asarray(table)).any()
                err = max(err, float(np.abs(bits[0] - bits[1]).max()))
            return err
        return run

    def paged(w):
        def run():
            # lengths EXCLUDE the w fresh tokens and leave room for them
            lengths = [0, 1, 127, 128, 129, 500, 1000, smax - w]
            table = jnp.asarray(_clamped_table(lengths, mb, block_t))
            lengths = jnp.asarray(lengths, jnp.int32)
            n = 1 + b * mb
            q, kn, vn = (rand(9, (b, w, H, D)), rand(10, (b, w, KV, D)),
                         rand(11, (b, w, KV, D)))
            (kp, ks), (vp, vs) = (quantize_kv(rand(12, (n, block_t, KV, D))),
                                  quantize_kv(rand(13, (n, block_t, KV, D))))
            if w == 1:
                kern = paged_attention.paged_decode_attention
                refn = paged_attention.paged_attention_reference
            else:
                kern = paged_attention.paged_window_attention
                refn = paged_attention.paged_window_reference
            got = kern(q, kp, vp, kn, vn, table, lengths, ks, vs,
                       interpret=interpret)
            ref = refn(q, kp, vp, kn, vn, table, lengths, ks, vs)
            return _max_err(got, ref)
        return run

    def decode_pairs():
        def run():
            # heads of 64 values, two KV heads a cache row (five full
            # layers x 96 slots x 4 pairs x 2,048 x 128, 32 query heads
            # each with zeros in the other head's half) against the jnp
            # form on the heads as they are, 64 wide: lengths at block
            # and tile edges, dead slots between
            lf, slots, kv, d, cap = 5, 96, 8, 64, 2048
            some = [0, 1, 255, 256, 257, 511, 512, 1000, 1500, 2046, 0, 700]
            lengths = jnp.asarray(some * (slots // len(some)), jnp.int32)
            q, kn, vn = (rand(60, (slots, 1, 32, d)),
                         rand(61, (slots, 1, kv, d)),
                         rand(62, (slots, 1, kv, d)))
            kc, vc = (rand(63, (lf, slots, kv // 2, cap, 2 * d)),
                      rand(64, (lf, slots, kv // 2, cap, 2 * d)))
            got = attention.unpair_heads(flash_decode.flash_decode_stacked(
                attention.pair_queries(q, kv), kc, vc,
                attention.pair_rows(kn), attention.pair_rows(vn), lengths,
                jnp.int32(3), block_s=flash_decode.block_size(cap),
                interpret=interpret, scale=d ** -0.5), kv)

            def heads(rows):   # [B, KV/2, S, 2 d] -> [B, KV, S, d]
                r = rows.reshape(slots, kv // 2, cap, 2, d)
                return jnp.moveaxis(r, 3, 2).reshape(slots, kv, cap, d)

            ref = attention.decode_attention_appended(
                q, heads(kc[3]), heads(vc[3]), kn, vn, lengths)
            return _max_err(got, ref)
        return run

    def append_pairs():
        def run():
            # the step's write on paired rows: each 64-wide head's row in
            # its half, every byte of both caches against XLA's scatter
            lf, slots, kv, d, cap = 5, 8, 8, 64, 2048
            pos = jnp.asarray([0, 1, 127, 128, 129, 500, cap - 1, cap],
                              jnp.int32)
            kc, vc = (rand(65, (lf, slots, kv // 2, cap, 2 * d)),
                      rand(66, (lf, slots, kv // 2, cap, 2 * d)))
            kr, vr = (attention.pair_rows(rand(i, (lf, slots, kv, d)))
                      for i in (67, 68))
            got = flash_decode.append_rows_stacked(kc, vc, kr, vr, pos,
                                                   interpret=interpret)
            at = jnp.arange(slots)
            return max(_max_err(g, c.at[:, at, :, pos].set(
                jnp.moveaxis(r, 1, 0), mode="drop"))
                for g, c, r in zip(got, (kc, vc), (kr, vr)))
        return run

    def ring_decode(heads):
        def run():
            # the decode kernel over stacked RINGS at the published sizes
            # (6 window layers x 128 slots x 8 KV heads x 512 rows, 64
            # query heads; 48 is a full layer's group of 6 on the same
            # kernel): lengths under, at and over the window, one row
            # before and after each wrap, dead slots between
            lw, slots, w = 6, 128, 512
            some = [0, 1, 255, 256, 511, 512, 513, 767, 768, 1023, 1024,
                    1025, 1500, 2046, 0, 700]
            lengths = jnp.asarray(some * (slots // len(some)), jnp.int32)
            q, kn, vn = (rand(40, (slots, 1, heads, D)),
                         rand(41, (slots, 1, KV, D)),
                         rand(42, (slots, 1, KV, D)))
            kc, vc = (rand(43, (lw, slots, KV, w, D)),
                      rand(44, (lw, slots, KV, w, D)))
            got = flash_decode.flash_decode_ring(
                q, kc, vc, kn, vn, lengths, jnp.int32(4),
                block_s=flash_decode.block_size(w), interpret=interpret)
            live, skip = flash_decode.ring_rows(lengths, w)
            ref = attention.decode_attention_appended(
                q, kc[4], vc[4], kn, vn, live, exclude=skip)
            return _max_err(got, ref)
        return run

    def banded_prefill():
        # a band narrower than the prompt: k blocks below it are skipped
        s, w = 1024, 512
        q, k, v = (rand(45, (1, s, H, D)), rand(46, (1, s, KV, D)),
                   rand(47, (1, s, KV, D)))
        lengths = jnp.asarray([s - 100], jnp.int32)
        mask = jnp.arange(s)[None, :] < lengths[:, None]
        got = flash.flash_causal_prefill(q, k, v, lengths, window=w,
                                         interpret=interpret)
        ref = attention.causal_attention(q, k, v, mask=mask, window=w)
        return _max_err(got, ref, mask[:, :, None, None])

    def kda_decode():
        # the delta rule's decode kernel at the published head sizes (64
        # heads x 128 x 128, 128 slots, 6 linear layers: 3.2 GB of state),
        # a quarter of the slots idle: the written layer's active states
        # against the jnp recurrence, every other byte against itself
        from gofr_tpu.ops import kda
        lk, slots, heads, d = 6, 128, 64, 128
        q, k, v, a = (jax.random.normal(jax.random.PRNGKey(20 + i),
                                        (slots, heads, d), jnp.float32)
                      for i in range(4))
        q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
                for x in (q, k))
        alpha, beta = jax.nn.sigmoid(a), 2 * jax.nn.sigmoid(v[..., 0])
        state = jax.random.normal(jax.random.PRNGKey(24),
                                  (lk, slots, heads, d, d), jnp.float32)
        active = jnp.arange(slots) % 4 != 1
        before = [jnp.sum(jnp.abs(state[i])) for i in range(lk)]
        want_o, want_s = kda.recurrent_ref(
            q[:, None], k[:, None], v[:, None], alpha[:, None],
            beta[:, None], state[2])
        want_s = jnp.where(active[:, None, None, None], want_s, state[2])
        o, state = kda.kda_decode(state, jnp.int32(2), q, k, v, alpha, beta,
                                  active, interpret=interpret)
        moved = max(float(jnp.abs(jnp.sum(jnp.abs(state[i])) - before[i]))
                    for i in range(lk) if i != 2)
        return max(_max_err(o, want_o[:, 0], active[:, None, None]),
                   _max_err(state[2], want_s), moved)

    def kda_prefill(t, strong=False):
        # the chunkwise kernel against the jnp recurrence at the published
        # head sizes; ``strong``: a log-decay down to -30 a token, so a
        # channel is past float32's smallest number inside one sub-block
        def run():
            from gofr_tpu.ops import kda
            heads, d = 64, 128
            q, k, v, a = (jax.random.normal(jax.random.PRNGKey(30 + i),
                                            (1, t, heads, d), jnp.float32)
                          for i in range(4))
            q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
                    for x in (q, k))
            g = -30.0 * jax.nn.sigmoid(a) if strong \
                else jax.nn.log_sigmoid(a + 2)
            beta = 2 * jax.nn.sigmoid(v[..., 0])
            s0 = jax.random.normal(jax.random.PRNGKey(34),
                                   (1, heads, d, d), jnp.float32)
            o, s1 = kda.kda_prefill(q, k, v, g, beta, s0,
                                    interpret=interpret)
            want_o, want_s = kda.recurrent_ref(q, k, v, jnp.exp(g), beta, s0)
            if not bool(jnp.isfinite(o).all() & jnp.isfinite(s1).all()):
                return float("inf")
            return max(_max_err(o, want_o), _max_err(s1, want_s))
        return run

    def ssd_inputs(b, t, seed):
        # Mamba-2's published sizes: 128 heads of 64 in 8 groups, state 128
        from gofr_tpu.ops import ssd
        heads, groups, n, r = 128, 8, 128, 1024
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        delta = jax.nn.softplus(jax.random.normal(ks[0], (b, t, heads)) - 3)
        la = -jnp.exp(jax.random.uniform(ks[1], (heads,), jnp.float32, 0.0,
                                         2.8)) * delta
        dx = jax.random.normal(ks[2], (b, t, groups, r)) \
            * ssd._rows(delta, groups, r)
        bm, cm = (jax.random.normal(k, (b, t, groups, n)) for k in ks[3:])
        return dx, la, bm, cm

    def ssd_decode():
        # the state-space decode kernel at the cell's sizes (96 slots, 10
        # mamba layers: 4 GB of state), a quarter of the slots idle: the
        # written layer's active states against the jnp recurrence, every
        # other byte against itself
        from gofr_tpu.ops import ssd
        lm, slots = 10, 96
        dx, la, bm, cm = (a[:, 0] for a in ssd_inputs(slots, 1, 60))
        state = jax.random.normal(jax.random.PRNGKey(61),
                                  (lm, slots, 8, 128, 1024), jnp.float32)
        active = jnp.arange(slots) % 4 != 1
        before = [jnp.sum(jnp.abs(state[i])) for i in range(lm)]
        want_y, want_s = ssd.recurrent_ref(dx[:, None], la[:, None],
                                           bm[:, None], cm[:, None], state[3])
        want_s = jnp.where(active[:, None, None, None], want_s, state[3])
        y, state = ssd.ssd_decode(state, jnp.int32(3), dx, la, bm, cm, active,
                                  interpret=interpret)
        moved = max(float(jnp.abs(jnp.sum(jnp.abs(state[i])) - before[i]))
                    for i in range(lm) if i != 3)
        return max(_max_err(y, want_y[:, 0], active[:, None, None]),
                   _max_err(state[3], want_s), moved)

    def ssd_prefill(t):
        def run():
            # the chunk kernel (chunks of 128, the published chunk_size)
            # against the token-by-token recurrence, from a state
            from gofr_tpu.ops import ssd
            dx, la, bm, cm = ssd_inputs(1, t, 62)
            s0 = jax.random.normal(jax.random.PRNGKey(63), (1, 8, 128, 1024),
                                   jnp.float32)
            y, s1 = ssd.ssd_prefill(dx, la, bm, cm, s0, chunk=128,
                                    interpret=interpret)
            want_y, want_s = ssd.recurrent_ref(dx, la, bm, cm, s0)
            return max(_max_err(y, want_y), _max_err(s1, want_s))
        return run

    def cursors(smax, slots=128):
        # under, at and over a block's edge and the table's end, dead
        # slots between
        some = [0, 1, 255, 256, 257, 511, 512, 513, 750, 1023, 1500, 2047,
                2048, 2049, 3000, smax - 1]
        return jnp.minimum(jnp.asarray(some * (slots // len(some)),
                                       jnp.int32), smax - 1)

    def latent_inputs(layers, heads, width, smax, seed, slots=128):
        # queries scaled so that a softmax over thousands of rows is
        # neither flat nor one-hot
        return (rand(seed, (slots, heads, width)) * 0.05,
                rand(seed + 1, (layers, slots, smax, width)),
                rand(seed + 2, (slots, width)))

    def latent_kept():
        # the masked walk of the sparse-latent family's full layers at
        # its cell's shapes (3 x 128 slots x 4,096 rows of 640 lanes,
        # 128 heads, rank 512): a mask that keeps two rows in three, the
        # token's own row kept or not
        from gofr_tpu.ops import mla
        q, rows, new = latent_inputs(3, 128, 640, 4096, 70)
        lengths = cursors(4096)
        keep = jax.random.bernoulli(jax.random.PRNGKey(73), 0.66,
                                    (128, 4096))
        # a slot that keeps no cached row keeps its own, as the selection
        # does (fewer candidates than index_topk are all kept)
        below = jnp.arange(4096)[None] < lengths[:, None]
        own = (jnp.arange(128) % 3 != 0) | ~jnp.any(keep & below, axis=-1)
        got = mla.decode_attention_kept(
            q, rows, new, lengths, jnp.int32(1), keep, own, rank=512,
            block_s=mla.decode_block(rows, 512), interpret=interpret)
        ref = mla.decode_attention_reference(q, rows[1], new, lengths, 512,
                                             keep, own)
        return _max_err(got, ref)

    def latent_ring():
        # the same kernel over the window layers' rings (6 x 128 slots x
        # 512 rows of 1,152 lanes, 64 heads, rank 1,024)
        from gofr_tpu.ops import mla
        q, rings, new = latent_inputs(6, 64, 1152, 512, 74)
        lengths = jnp.asarray([0, 1, 255, 256, 511, 512, 513, 767, 768,
                               1023, 1024, 1025, 1500, 2046, 0, 700] * 8,
                              jnp.int32)
        got = mla.decode_attention_ring(
            q, rings, new, lengths, jnp.int32(4), rank=1024,
            block_s=mla.decode_block(rings, 1024), interpret=interpret)
        ref = mla.decode_attention_reference(
            q, rings[4], new, jnp.minimum(lengths, 512), 1024)
        return _max_err(got, ref)

    def index_scores():
        # the indexer's score pass (3 x 128 slots x 4,096 keys of 128,
        # 64 index heads): dead blocks and the rows past a cursor read
        # NEG_INF on both sides, so the error is over the live rows
        from gofr_tpu.ops import dsa
        slots, hi, d, smax = 128, 64, 128, 4096
        lengths = cursors(smax)
        q = rand(78, (slots, hi, d))
        w = jax.random.normal(jax.random.PRNGKey(79), (slots, hi),
                              jnp.float32) * (hi * d) ** -0.5
        keys = rand(80, (3, slots, smax, d))
        got = dsa.index_scores_stacked(
            q, w, keys, lengths, jnp.int32(2),
            block_s=dsa.scores_block(keys), interpret=interpret)
        ref = dsa.decode_scores_reference(q, w, keys[2], lengths)
        return _max_err(got, ref)

    def experts(layers, held, dim, ffn, top_k=8, slots=128, gated=True):
        def run():
            # the routed experts' kernel at a cell's decode shapes (128
            # slots x top-8 in blocks of 16 rows, or 96 x top-4 where an
            # expert is two tiles of the kernel wide; the stacks whole,
            # int8 with a scale an output channel) against the jnp loop:
            # all but two experts get a block, the buffer's tail is dead
            from gofr_tpu.models import moe
            from gofr_tpu.models.common import ModelConfig
            from gofr_tpu.ops import moe_experts
            from gofr_tpu.ops.quant import QuantizedLinear

            bm, rows = moe.expert_dispatch(ModelConfig(
                dim=dim, moe_ffn_dim=ffn, n_experts=held,
                experts_per_token=top_k), slots)

            def stack(i, n_in, n_out):
                # a layer at a time: the generator counts in 32 bits
                w = jnp.stack([jax.lax.bitcast_convert_type(jax.random.bits(
                    jax.random.fold_in(jax.random.PRNGKey(i), l),
                    (held, n_in, n_out), jnp.uint8), jnp.int8)
                    for l in range(layers)])
                scale = jax.random.uniform(
                    jax.random.PRNGKey(i + 1), (layers, held, n_out),
                    jnp.float32, 0.5, 1.5) / (74.0 * n_in ** 0.5)
                return QuantizedLinear(w, scale)

            # ``dim`` is the width the experts read: the model's, or a
            # latent's; without a gate an expert is w_down relu(w_up x)^2
            stacks = {"w_up": stack(52, dim, ffn),
                      "w_down": stack(54, ffn, dim)}
            if gated:
                stacks["w_gate"] = stack(50, dim, ffn)
            live = held - 2
            blk = jnp.minimum(jnp.arange(rows // bm, dtype=jnp.int32) + 1,
                              held - 1)
            xs = rand(56, (rows, dim))
            li, n = jnp.int32(layers - 2), jnp.int32(live)
            leaves = [stacks.get(k) for k in moe.EXPERT_STACKS]
            got = moe_experts.expert_blocks_stacked(
                xs, blk, n, li,
                *(None if a is None else a.w for a in leaves),
                *(None if a is None else a.scale for a in leaves),
                block_rows=bm, interpret=interpret)
            ref = jax.jit(moe.blocks_loop, static_argnums=5)(
                xs, blk, n, stacks, li, bm)
            dead = float(jnp.abs(got[live * bm:]).max())
            return max(_max_err(got, ref), dead)
        return run

    return [("decode_attention_kept[bf16,3x128x4096x640,H=128]", latent_kept),
            ("decode_attention_ring[bf16,6x128x512x1152,H=64]", latent_ring),
            ("index_scores_stacked[bf16,3x128x4096x128,Hi=64]", index_scores),
            ("expert_blocks_stacked[int8,8x32x5120x1536]",
             experts(8, 32, 5120, 1536)),
            ("expert_blocks_stacked[int8,7x256x2048x512]",
             experts(7, 256, 2048, 512)),
            ("expert_blocks_stacked[int8,8x40x4096x1280]",
             experts(8, 40, 4096, 1280)),
            ("expert_blocks_stacked[int8,8x16x7168x2048]",
             experts(8, 16, 7168, 2048)),
            ("expert_blocks_stacked[int8,4x64x2048x1536,tile=768]",
             experts(4, 64, 2048, 1536, top_k=4, slots=96)),
            ("expert_blocks_stacked[int8,10x128x1024x2688,relu2]",
             experts(10, 128, 1024, 2688, top_k=22, slots=96, gated=False)),
            ("ssd_decode[f32,10x96x8x128x1024]", ssd_decode),
            ("ssd_prefill[f32,T=128]", ssd_prefill(128)),
            ("ssd_prefill[f32,T=512]", ssd_prefill(512)),
            ("flash_decode_stacked[bf16,5x96x4x2048x128,hd=64 paired]",
             decode_pairs()),
            ("append_rows_stacked[bf16,hd=64 paired]", append_pairs()),
            ("kda_decode[f32,6x128x64x128x128]", kda_decode),
            ("kda_prefill[f32,T=32]", kda_prefill(32)),
            ("kda_prefill[f32,T=512]", kda_prefill(512)),
            ("kda_prefill[f32,T=512,g>=-30]", kda_prefill(512, strong=True)),
            ("flash_decode_ring[bf16,6x128x8x512x128,H=64]", ring_decode(64)),
            ("flash_decode_ring[bf16,6x128x8x512x128,H=48]", ring_decode(48)),
            ("flash_causal_prefill[S=1024,window=512]", banded_prefill),
            ("flash_causal_prefill[S=256]", prefill(256)),
            ("flash_causal_prefill[S=512]", prefill(512)),
            ("paged_decode_attention[int8,T=128]", paged(1)),
            ("paged_window_attention[int8,T=128,W=5]", paged(5)),
            ("flash_decode_stacked[int8]", decode(True)),
            ("flash_decode_stacked[bf16]", decode(False)),
            ("append_rows_stacked[int8]", append(True)),
            ("append_rows_stacked[bf16]", append(False))]


def phase_kernels(summary: dict, rec: dict) -> None:
    platform = (summary.get("device") or {}).get("platform")
    if platform != "tpu":
        raise RuntimeError(f"needs a TPU: Mosaic compiles these kernels "
                           f"only there (platform is {platform!r})")
    rec["kernels"] = {}
    failed = []
    for name, run in kernel_cases():
        t0 = time.monotonic()
        try:
            err = run()
            ok = err <= KERNEL_TOL
            rec["kernels"][name] = {"ok": ok, "max_err": float(f"{err:.3g}")}
        except Exception as e:  # report every kernel, not the first
            traceback.print_exc()
            ok = False
            rec["kernels"][name] = {"ok": False,
                                    "error": f"{type(e).__name__}: {e}"[:600]}
        log(f"  kernel {name}: {rec['kernels'][name]} "
            f"({time.monotonic() - t0:.1f}s)")
        if not ok:
            failed.append(name)
    if failed:
        raise AssertionError(f"kernels failed: {failed}")


# -- phase 3: server ---------------------------------------------------------

def load_example_app():
    """The example's module, imported the way ``python main.py`` from its
    directory would build it: ``App()`` reads ./configs/.env."""
    cwd = os.getcwd()
    os.chdir(EXAMPLE)
    try:
        spec = importlib.util.spec_from_file_location(
            "tpu_token_streaming_main", os.path.join(EXAMPLE, "main.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        os.chdir(cwd)
    return mod.app


def http_get(port: int, path: str):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def http_generate(port: int, tokens: list[int], max_new: int) -> list[int]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/generate",
                     json.dumps({"tokens": tokens,
                                 "max_new_tokens": max_new}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        body = r.read()  # http.client de-chunks
        if r.status != 200:
            raise RuntimeError(f"POST /generate -> {r.status}: {body[:300]!r}")
        return [json.loads(line)["token"]
                for line in body.splitlines() if line]
    finally:
        conn.close()


def grpc_generate(port: int, tokens: list[int], max_new: int) -> list[int]:
    from gofr_tpu.grpcx import dial

    ch = dial(f"127.0.0.1:{port}")
    try:
        return [m["token"] for m in ch.server_stream(
            "/llm.Generation/Generate",
            {"tokens": tokens, "max_new_tokens": max_new}, timeout=300)]
    finally:
        ch.close()


def scrape(port: int) -> dict[str, float]:
    """GET /metrics -> {metric name: sum over its label sets}."""
    from gofr_tpu.metrics import parse_prometheus

    status, body = http_get(port, "/metrics")
    if status != 200:
        raise RuntimeError(f"GET /metrics -> {status}")
    return parse_prometheus(body.decode())


def count_kernel_traces() -> dict[str, int]:
    """Count how often each Pallas entry point is traced into a serving
    program from here on. Token checks cannot tell a kernel from its jnp
    fallback; the dispatchers resolve these names at call time, so a
    counting wrapper on the module attribute sees every trace (cached
    executables still trace)."""
    import functools

    from gofr_tpu.ops import flash, flash_decode, paged_attention

    counts: dict[str, int] = {}
    for mod, names in ((flash, ("flash_causal_prefill",
                                "flash_prefill_sharded")),
                       (flash_decode, ("flash_decode_stacked",
                                       "flash_decode_sharded")),
                       (paged_attention, ("paged_decode_attention",
                                          "paged_window_attention",
                                          "paged_decode_sharded",
                                          "paged_window_sharded"))):
        for name in names:
            fn = getattr(mod, name)
            counts[name] = 0

            def counted(*a, _fn=fn, _name=name, **kw):
                counts[_name] += 1
                return _fn(*a, **kw)

            setattr(mod, name, functools.wraps(fn)(counted))
    return counts


def phase_server(summary: dict, rec: dict) -> None:
    import random

    from gofr_tpu.testutil import framework_threads

    platform = (summary.get("device") or {}).get("platform")
    if platform != "tpu" and "TPU_MODEL" not in os.environ:
        raise RuntimeError(
            f"not run: platform is {platform!r} and the example's model "
            "needs the chip (set TPU_MODEL=tiny to rehearse the request "
            "logic)")
    # ephemeral ports: deployment settings, not model settings
    for key in ("HTTP_PORT", "GRPC_PORT", "METRICS_PORT"):
        os.environ.setdefault(key, "0")

    from gofr_tpu import compile_cache

    # the process's one compile listener; the kernel phase compiled
    # before this one, so the app's share is the difference
    clock = compile_cache.clock()
    before = clock.snapshot()
    rec["kernels_traced"] = count_kernel_traces()
    t0 = time.monotonic()
    app = load_example_app()  # App(): weights from the seed + warm-up
    rec["startup_s"] = round(time.monotonic() - t0, 1)
    built = {k: v - before[k] for k, v in clock.snapshot().items()}
    summary["compile_s"] = round(built["seconds"], 1)
    summary["compiled_programs"] = built["programs"]
    summary["cache_hits"] = built["hits"]
    summary["cache_misses"] = built["misses"]
    log(f"  app built in {rec['startup_s']}s: {built['programs']} programs, "
        f"{summary['compile_s']}s compiling, cache hits/misses "
        f"{built['hits']}/{built['misses']}")
    app.run(block=False)
    try:
        _drive(app, summary, rec, random.Random(0))
    except BaseException:
        app.stop()
        raise
    t0 = time.monotonic()
    app.stop(grace_s=30.0)
    rec["stop_s"] = round(time.monotonic() - t0, 1)
    deadline = time.monotonic() + 15.0
    while framework_threads() and time.monotonic() < deadline:
        time.sleep(0.2)
    if framework_threads():
        raise AssertionError(
            "framework threads outlived app.stop(): "
            f"{sorted(t.name for t in framework_threads())}")
    rec["programs_compiled_while_serving"] = \
        clock.programs - before["programs"] - built["programs"]


def _drive(app, summary: dict, rec: dict, rng) -> None:
    import jax

    from gofr_tpu import native
    from gofr_tpu.tpu import hbm

    engine = app.container.tpu
    model = engine.generator.cfg
    rec["model"] = {"name": model.name, "layers": model.n_layers,
                    "dim": model.dim, "heads": model.n_heads,
                    "kv_heads": model.n_kv_heads, "ffn": model.ffn_dim,
                    "vocab": model.vocab_size,
                    "quant": app.config.get("TPU_QUANT"),
                    "kv_dtype": app.config.get("TPU_KV_DTYPE")}

    # health: the tpu datasource is UP on the platform JAX reported
    status, body = http_get(app.http_port, "/.well-known/health")
    health = json.loads(body)["data"]
    tpu = health.get("tpu") or {}
    gen = tpu.get("details", {}).get("generator", {})
    rec["health"] = {"status": health.get("status"),
                     "tpu": tpu.get("status"),
                     "platform": tpu.get("details", {}).get("platform"),
                     "slots": gen.get("slots"),
                     "max_seq": gen.get("max_seq"),
                     "prompt_buckets": gen.get("prompt_buckets")}
    log(f"  health: {rec['health']}")
    if status != 200 or tpu.get("status") != "UP" or \
            rec["health"]["platform"] != summary["device"]["platform"]:
        raise AssertionError(f"health: {status} {rec['health']}")

    vocab, max_seq = model.vocab_size, gen["max_seq"]
    buckets = gen["prompt_buckets"]

    def prompt(n: int) -> list[int]:
        return [rng.randrange(1, vocab) for _ in range(n)]

    def checked(tokens: list[int], want: int, what: str) -> list[int]:
        if len(tokens) != want or not all(
                isinstance(t, int) and 0 <= t < vocab for t in tokens):
            raise AssertionError(
                f"{what}: expected {want} in-vocabulary tokens, got "
                f"{len(tokens)}: {tokens[:8]}…")
        return tokens

    before = scrape(app.metrics_port)
    t_serve = time.monotonic()

    # one prompt twice, nothing else in flight: the second admission hits
    # the prefix pool and must stream the same greedy tokens. Long enough
    # to be stored (>= TPU_PREFIX_MIN, by default the largest bucket), so
    # it also admits through the chunk lattice.
    store_min = app.config.get_int("TPU_PREFIX_MIN", 0) or buckets[-1]
    repeat = prompt(store_min + max(1, buckets[0] // 2))
    if len(repeat) + NEW_TOKENS >= max_seq:
        raise AssertionError(f"repeat prompt {len(repeat)} does not fit "
                             f"max_seq {max_seq}")
    first = checked(http_generate(app.http_port, repeat, NEW_TOKENS),
                    NEW_TOKENS, "repeat#1 (http)")
    second = checked(grpc_generate(app.grpc_port, repeat, NEW_TOKENS),
                     NEW_TOKENS, "repeat#2 (grpc)")
    if first != second:
        raise AssertionError(f"prefix hit changed greedy output: "
                             f"{first} != {second}")
    tokens_returned = 2 * NEW_TOKENS

    # six streams at once over both transports; the two long prompts land
    # in the 256 and 512 buckets, whose prefill carries the flash kernel
    long_a = min(300, max_seq * 3 // 4 - NEW_TOKENS)
    lens = [20, long_a, 64, 40, max(2, long_a * 2 // 3), 12]
    results: list = [None] * len(lens)
    spans: list = [None] * len(lens)
    barrier = threading.Barrier(len(lens))

    def client(i: int) -> None:
        send = http_generate if i % 2 == 0 else grpc_generate
        port = app.http_port if i % 2 == 0 else app.grpc_port
        toks = prompt(lens[i])
        barrier.wait(timeout=60)
        t0 = time.monotonic()
        try:
            results[i] = send(port, toks, NEW_TOKENS)
        except Exception as e:  # surfaces in the main thread below
            results[i] = e
        spans[i] = (t0, time.monotonic())

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"smoke-client-{i}")
               for i in range(len(lens))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    if any(t.is_alive() for t in threads):
        raise AssertionError("a client stream did not finish in 600s")
    for i, r in enumerate(results):
        if isinstance(r, Exception):
            raise AssertionError(f"stream {i} (prompt {lens[i]}): {r!r}")
        checked(r, NEW_TOKENS, f"stream {i} (prompt {lens[i]})")
        tokens_returned += len(r)
    in_flight = max(sum(1 for a, b in spans if a <= t < b)
                    for t, _ in spans)
    rec["streams"] = 2 + len(lens)
    rec["prompt_lens"] = [len(repeat), len(repeat)] + lens
    rec["max_in_flight"] = in_flight
    if in_flight < 4:
        raise AssertionError(f"only {in_flight} streams were in flight "
                             "at once")
    summary["serve_s"] = round(time.monotonic() - t_serve, 1)
    summary["tokens"] = tokens_returned

    # the prefix pool was hit, and the engine's own counters moved
    _, body = http_get(app.http_port, "/.well-known/health")
    gen = json.loads(body)["data"]["tpu"]["details"]["generator"]
    rec["prefix_cache"] = {k: gen.get("prefix_cache", {}).get(k)
                           for k in ("hits", "misses", "entries")}
    rec["spec_decode"] = gen.get("spec_decode")
    if not rec["prefix_cache"]["hits"]:
        raise AssertionError(f"prefix pool never hit: {rec['prefix_cache']}")
    after = scrape(app.metrics_port)
    moved = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    rec["metrics"] = {k: moved.get(k, 0.0) for k in (
        "app_tpu_tokens_generated_total", "app_tpu_ttft_duration_count",
        "app_tpu_prefill_chunks_total", "app_tpu_kvcache_hits_total",
        "app_tpu_shed_total", "app_tpu_hbm_shed_total",
        "app_tpu_expired_dropped_total", "app_tpu_paged_evictions_total")}
    log(f"  metrics moved: {rec['metrics']}")
    if rec["metrics"]["app_tpu_tokens_generated_total"] < tokens_returned \
            or rec["metrics"]["app_tpu_ttft_duration_count"] < rec["streams"]:
        raise AssertionError(f"app_tpu_* counters did not move: "
                             f"{rec['metrics']}")
    bad = {k: v for k, v in rec["metrics"].items()
           if v and ("shed" in k or "expired" in k or "evictions" in k)}
    if bad:
        raise AssertionError(f"shed/OOM counters moved: {bad}")

    # on a TPU the serving programs carry the kernels, not their jnp
    # fallbacks: flash prefill from bucket 256 up, the paged kernels on
    # a block pool, flash decode where the engine says its shapes take
    # it, each in its shard_map'd form on a mesh
    traced = rec["kernels_traced"]
    log(f"  kernels traced into serving programs: {traced}")
    if summary["device"]["platform"] == "tpu":
        want = ["flash_causal_prefill"] if buckets[-1] >= 256 else []
        if "paged" in gen:
            want.append("paged_decode_attention")
            if gen.get("spec_decode"):
                want.append("paged_window_attention")
        if "mesh" in gen:
            want += [{"flash_causal_prefill": "flash_prefill_sharded",
                      "paged_decode_attention": "paged_decode_sharded",
                      "paged_window_attention": "paged_window_sharded"}[k]
                     for k in list(want)]
        if gen.get("decode_kv_block"):
            want.append("flash_decode_sharded" if "mesh" in gen
                        else "flash_decode_stacked")
        missing = [k for k in want if not traced[k]]
        if missing:
            raise AssertionError(f"serving never traced {missing}: a jnp "
                                 f"fallback ran instead ({traced})")

    # memory after serving: what the backend reports, per device, and
    # what the arbiter thinks it leased
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    summary["hbm"] = [{"device": d.id,
                       "bytes_in_use": s.get("bytes_in_use"),
                       "peak_bytes_in_use": s.get("peak_bytes_in_use"),
                       "bytes_limit": s.get("bytes_limit")}
                      for d, s in zip(jax.local_devices(), stats)]
    arb = hbm.arbiter_stats()
    summary["arbiter"] = {k: arb.get(k) for k in (
        "budget_bytes", "device_budget_bytes", "in_use_bytes",
        "headroom_bytes", "sheds", "oom_retries", "devices")}
    summary["native"] = {"loaded": native.available(),
                         "error": native.load_error()}
    log(f"  hbm: {summary['hbm']}\n  arbiter: {summary['arbiter']}\n"
        f"  native: {summary['native']}")
    if not summary["native"]["loaded"]:
        raise AssertionError(f"native runtime not loaded: "
                             f"{summary['native']['error']}")


# -- summary -----------------------------------------------------------------

def result_line(summary: dict) -> str:
    """The last stdout line: exactly ``ok`` and the device as JAX reports
    it. Whoever runs the smoke parses this line and accepts no other key;
    the detail is the summary line before it."""
    device = summary["device"]
    return json.dumps({"ok": summary["ok"],
                       "device": {"platform": device["platform"],
                                  "kind": device["kind"],
                                  "count": device["count"]}})


def main() -> int:
    # stdout carries the summary and the result line and nothing else: the
    # framework's logger (bound to sys.stdout at construction) goes to stderr
    result_out, sys.stdout = sys.stdout, sys.stderr
    summary: dict = {"ok": False, "device": None, "phases": {}}
    run_phase(summary, "device", phase_device)
    run_phase(summary, "kernels", phase_kernels)
    run_phase(summary, "server", phase_server)
    summary["ok"] = all(p["ok"] for p in summary["phases"].values())
    summary["claim"] = None
    line = json.dumps(summary)
    if not summary["ok"]:
        failed = [n for n, p in summary["phases"].items() if not p["ok"]]
        log(f"chip_smoke FAILED in {failed}: {line}")
        return 1
    print(line, file=result_out)
    print(result_line(summary), file=result_out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
