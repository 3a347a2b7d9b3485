"""The device side of one generation engine. ``GenerationEngine``
(generator.py) schedules: queue, slots, loop, delivery. What it keeps on
the device it asks for here, and this module owns three decisions:

  1. BUFFERS: each persistent buffer (serving cache, prefix pool,
     scratch row) is described once (``Buffer``) and
     ``EnginePrograms.allocate`` alone makes one from its description —
     at construction, in recovery, after a mesh re-placement, in the
     arbiter's pool shrink and for a late scratch.
  2. SHARDINGS: ``shardings`` takes a mesh (or none) and the
     descriptions and returns the ``Placement`` that buffers and program
     outputs carry.
  3. PROGRAMS: ``TABLE`` names every compiled program, and
     ``EnginePrograms.build`` is a loop over it.

The traced functions read only the configuration, the family, the rope
tables and static sizes: none touches a slot, a queue or a stream. jit
names a program after its function, and the benchmark finds
``_step_fn``, ``_prefill_fn``, ``_chunk_mid`` and ``_chunk_final`` in a
device trace by those names.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..models import llama
from ..observe.startup import StartupAccount
from . import hbm

# top-k truncation width: per-request k is traced (no recompiles);
# ranks past k are masked within this fixed top set
TOP_K_MAX = 64

# on-device EOS stop-set width (llama.decode_stop_mask): requests
# with more stop ids than this keep host-side retirement as their
# only stop — still correct, the slot just burns up to a block of
# junk steps before the host notices. Never a compile key per
# request (the [B, EOS_MAX] matrix is fixed-shape dispatch data).
EOS_MAX = 8

# dispatch-pack column layout (the engine's _dispatch_pack and
# _fused_decode_scan must agree): 0 last_token, 1 active, 2 budget,
# 3 temp (f32 bits), 4 top_k, 5 adapter, 6 host_wins, 7 seed, 8 pos
# (absolute generated-token index of the slot's NEXT sample — the
# host-side truth the carry merge reads under host_wins), 9.. EOS set,
# then (paged) the block-table row; a family whose step is a pass over
# a block (``cfg.block_length`` = W > 0, never paged) has in its place
# the slot's cursor, the positions of its first block that the prompt
# gives and that block's W tokens (``_block_decode_scan``)
PACK_EXTRA = 9


class Buffer(NamedTuple):
    """One persistent device buffer, described once: ``build(rows)`` the
    unplaced thunk, ``specs`` the rule in ``parallel`` that shards its
    ``eval_shape`` struct on a mesh, and its lease. ``tag`` is the lease
    tag and the buffer's field in ``Placement``."""
    tag: str
    rows: int
    build: Callable[[int], Any]
    specs: str
    group: str
    priority: int
    reclaim: Callable[[int], int] | None


# lease group and priority by tag. The serving cache is never
# auto-reclaimed (a paged one attaches the cold-prefix-block release, so
# storms can still drain logical pool pressure); under budget pressure
# from ANY subsystem the arbiter spills the prefix pool's entries to the
# host tier and reallocates it smaller; the scratch row goes first.
_LEASES = {"cache": ("engine", hbm.PRI_SERVING),
           "pool": ("kvcache-t0", hbm.PRI_CACHE),
           "scratch": ("engine", hbm.PRI_SCRATCH)}


class Placement(NamedTuple):
    """What ``shardings`` decides: the replicated sharding, a sharding
    tree for each described buffer, the mesh's device labels (the
    per-shard lease keys) and the tp shards of the KV-head axis. All
    defaults without a mesh."""
    rep: Any = None
    cache: Any = None
    pool: Any = None
    scratch: Any = None
    labels: tuple = ()
    kv_shards: int = 1


def shardings(mesh, buffers, n_kv_heads: int) -> Placement:
    """ICI-sharded serving (SURVEY §2 last row): KV heads over tp, rows
    over the data axes when they divide (paged pools: KV heads over tp
    only — the block axis stays whole for the global table). Computed
    from ``eval_shape`` structs BEFORE anything is allocated, so every
    buffer is born sharded and leased per shard; after a device loss the
    same call on the new mesh places the reallocations. Collectives are
    emitted by XLA from the specs — nothing here names a device."""
    if mesh is None:
        return Placement()
    from .. import parallel  # lazy: it pulls the training stack in

    return Placement(
        rep=parallel.replicated(mesh),
        labels=tuple(str(d.id) for d in mesh.devices.flat),
        kv_shards=parallel.kv_head_shards(mesh, n_kv_heads),
        **{b.tag: getattr(parallel, b.specs)(
            mesh, jax.eval_shape(lambda: b.build(b.rows))) for b in buffers})


def _born_sharded(build, sharding):
    """Run a cache-building thunk so a mesh engine's buffers are
    created in their shards: built eagerly and then device_put, the
    whole [L, slots, KV, Smax, hd] cache lands on the first chip
    before it is split (3.8 GB of extra peak on device 0 at
    8B/tp=4). ``sharding`` None = single device, build in place."""
    if sharding is None:
        return build()
    return jax.jit(build, out_shardings=sharding)()


class Program(NamedTuple):
    """One compiled program. ``attr``: the engine attribute it is bound
    to (the fault-injection tests replace ``_step_jit``). ``fn``: the
    traced function, a method of ``EnginePrograms``, a row helper below
    or one of ``models.paged_llama``; ``mesh_fn``: the GSPMD-clean form
    a mesh engine runs in its place. ``out``: its outputs' shardings as
    ``Placement`` fields. ``layout``: the KV layout that runs it (None:
    both). ``needs``: what the engine must have — a described buffer's
    tag, "spec" (spec_decode_k) or "offload" (a host or shared cache
    tier). Each donates the buffer it rewrites, its first argument."""
    attr: str
    fn: str
    out: Any
    layout: str | None = None
    needs: str | None = None
    mesh_fn: str | None = None
    donate: tuple = (0,)


_C, _P = "contiguous", "paged"
# (token, logprob, key) before a prefill's cache, (tokens, logprobs,
# emit) before a verify's; the step returns (tokens, logprobs, emitted,
# slot-state carry, key, cache, counters)
_REP3 = ("rep", "rep", "rep")
_STEP = (*_REP3, ("rep",) * 4, "rep", "cache", "rep")

# A paged engine chunks into its dense scratch row (the contiguous
# engine's programs at B=1) and one dispatch lands the row in the pool's
# blocks; the prefix pool's row copies and the T1/T2 promotion run
# mask-and-reduce on a mesh (the *_masked helpers say why).
TABLE = (
    Program("_prefill_jit", "_prefill_fn", (*_REP3, "cache"), _C),
    Program("_prefill_jit", "_paged_prefill_fn", (*_REP3, "cache"), _P),
    Program("_step_jit", "_step_fn", _STEP, _C),
    Program("_step_jit", "_paged_step_fn", _STEP, _P),
    Program("_verify_jit", "_verify_fn", (*_REP3, "cache"), _C, "spec"),
    Program("_verify_jit", "_paged_verify_fn", (*_REP3, "cache"), _P, "spec"),
    Program("_chunk_mid_jit", "_chunk_mid", "cache", _C),
    Program("_chunk_mid_jit", "_chunk_mid", "scratch", _P, "scratch"),
    Program("_chunk_final_jit", "_chunk_final", (*_REP3, "cache"), _C),
    Program("_chunk_final_jit", "_chunk_final", (*_REP3, "scratch"), _P,
            "scratch"),
    Program("_row_to_blocks_jit", "write_row_to_blocks", "cache", _P,
            "scratch"),
    Program("_blocks_to_row_jit", "read_blocks_to_row", "scratch", _P,
            "scratch"),
    Program("_pool_load_jit", "_copy_row", "cache", None, "pool",
            "_copy_row_masked"),
    Program("_pool_store_jit", "_copy_row", "pool", None, "pool",
            "_copy_row_masked"),
    Program("_host_write_jit", "_write_row_from_host", "pool", None,
            "offload", "_write_row_from_host_masked"),
)


def _copy_row(dst, src, dst_idx, src_idx):
    """Copy one batch row of KV (+ scale planes): src[:, src_idx] ->
    dst[:, dst_idx]. Shared by prefix-pool store (dst=pool) and load
    (dst=serving cache); lengths are untouched — the slot cursor is set
    by the chunk dispatches, the pool's lengths live host-side."""
    import jax.lax as lax

    def cp(d, s):
        r = lax.dynamic_slice_in_dim(s, src_idx, 1, axis=1)
        return lax.dynamic_update_slice_in_dim(d, r, dst_idx, axis=1)

    # every array of a cache but ``lengths`` is [L, B, ...]: K and V
    # [L, B, KV, Smax, hd] and their scale planes, or a family's latent
    # rows
    return jax.tree_util.tree_map(
        cp, dst._replace(lengths=None),
        src._replace(lengths=None))._replace(lengths=dst.lengths)


def _write_row_from_host(pool, k, v, ks, vs, row):
    """Land a host KV slab in pool row ``row`` — the device half of a
    T1/T2 restore (kvcache promotion). ``k``/``v`` arrive in the host's
    order (tpu.kvcache.HostKV), padded to [L, 1, Smax, KV, hd] (scales
    [L, 1, Smax, KV]) so the program compiles once, and are transposed
    here, on the device, to the cache's [L, 1, KV, Smax(, hd)];
    positions past the entry's length are zeros that the resumed prefill
    overwrites or the cursor masks."""
    import jax.lax as lax

    def wr(dst, src):
        return lax.dynamic_update_slice_in_dim(
            dst, jnp.swapaxes(src, 2, 3), row, axis=1)

    quant = pool.k_scale is not None
    return pool._replace(
        k=wr(pool.k, k), v=wr(pool.v, v),
        k_scale=wr(pool.k_scale, ks) if quant else None,
        v_scale=wr(pool.v_scale, vs) if quant else None)


def _write_row_from_host_masked(pool, k, v, ks, vs, row):
    """GSPMD-friendly _write_row_from_host for SHARDED pools (mesh
    engines' T1/T2 promotion): the dynamic_update_slice form puts a
    traced start on the batch axis — the axis the pool shards over the
    data mesh axes — and GSPMD's only lowering for that replicates the
    whole pool (the _copy_row hazard). Select the destination row with
    a one-hot mask and blend instead: ``src`` (the host's order,
    [L, 1, Smax, KV(, hd)], transposed here like _write_row_from_host's)
    arrives replicated and broadcasts over the batch axis, every op
    partitions cleanly under any batch/tp sharding. Reads the full pool
    once; that extra HBM stream is the price of mesh support, paid only
    on a promotion (not per token)."""
    def wr(dst, src):
        sel = (jnp.arange(dst.shape[1]) == row)
        sel = sel.reshape((1, -1) + (1,) * (dst.ndim - 2))
        return jnp.where(sel, jnp.swapaxes(src, 2, 3).astype(dst.dtype), dst)

    quant = pool.k_scale is not None
    return pool._replace(
        k=wr(pool.k, k), v=wr(pool.v, v),
        k_scale=wr(pool.k_scale, ks) if quant else None,
        v_scale=wr(pool.v_scale, vs) if quant else None)


def _copy_row_masked(dst, src, dst_idx, src_idx):
    """GSPMD-friendly _copy_row for sharded engines. _copy_row's dynamic
    slice/update puts a TRACED start index on the batch axis — the axis
    kv_cache_specs shards over the data mesh axes — and GSPMD's only
    lowering for that is replicating the whole cache (the same
    involuntary-full-remat class as MULTICHIP_r03's embedding gather).
    Mask-and-reduce instead: select the source row by one-hot mask and
    sum over the batch axis (partitioned as local reduce + psum over the
    data axes), then blend it into the destination row with an
    elementwise where over a broadcast of the (replicated) row — every
    op here partitions cleanly under any batch/tp sharding. Reads both
    caches fully instead of one row each; that extra HBM stream is the
    price of mesh support and stays well under one decode block."""
    def cp(d, s):
        sel_s = (jnp.arange(s.shape[1]) == src_idx)
        sel_s = sel_s.reshape((1, -1) + (1,) * (s.ndim - 2))
        # int8 KV sums exactly in int32 (one nonzero term per position)
        acc = jnp.int32 if jnp.issubdtype(s.dtype, jnp.integer) else s.dtype
        row = jnp.sum(jnp.where(sel_s, s, 0).astype(acc), axis=1,
                      keepdims=True)                       # [L, 1, ...]
        sel_d = (jnp.arange(d.shape[1]) == dst_idx)
        sel_d = sel_d.reshape((1, -1) + (1,) * (d.ndim - 2))
        return jnp.where(sel_d, row.astype(d.dtype), d)

    quant = dst.k_scale is not None
    return dst._replace(
        k=cp(dst.k, src.k), v=cp(dst.v, src.v),
        k_scale=cp(dst.k_scale, src.k_scale) if quant else None,
        v_scale=cp(dst.v_scale, src.v_scale) if quant else None)


class EnginePrograms:
    """One engine's buffer descriptions, their placement and its traced
    functions. The engine holds the buffers and the compiled programs
    themselves: its hot path rebinds them at every dispatch."""

    TOP_K_MAX = TOP_K_MAX
    EOS_MAX = EOS_MAX
    _PACK_EXTRA = PACK_EXTRA

    def __init__(self, cfg, fam, owner, *, max_seq: int, kv_dtype,
                 decode_block: int, n_adapters: int, spec_k: int,
                 paged: "tuple[int, int] | None", mesh, startup=None):
        self.cfg = cfg
        self._fam = fam
        self._owner = owner  # of the leases: close() releases by it
        # the start-up account (observe/startup.py): every allocation
        # and every build is a phase of it
        self._startup = startup if startup is not None else StartupAccount()
        self.max_seq = max_seq
        self._kv_dtype = kv_dtype
        self.decode_block = decode_block
        self._n_adapters = n_adapters
        self._spec_k = spec_k
        # (blocks in the pool, tokens a block): the paged layout, or None
        self.paged = paged
        if paged:
            self._mb = -(-max_seq // paged[1])
        self.rope_tables = fam.get_rope_tables(cfg, max_seq)
        self.buffers: dict[str, Buffer] = {}
        self.place(mesh)

    # -- buffers and their placement -----------------------------------------
    def _rows(self, n: int):
        return self._fam.init_cache(self.cfg, n, self.max_seq,
                                    dtype=self._kv_dtype)

    def _block_pool(self, n: int):
        from ..models.paged_llama import init_paged_cache

        return init_paged_cache(self.cfg, n, *self.paged,
                                dtype=self._kv_dtype)

    def describe(self, tag: str, rows: int, reclaim=None) -> None:
        """Describe buffer ``tag`` at ``rows`` batch rows (again, for a
        pool the arbiter shrank) and fit the placement to it."""
        pool = tag == "cache" and self.paged
        self.buffers[tag] = Buffer(
            tag, rows, self._block_pool if pool else self._rows,
            "paged_cache_specs" if pool else "kv_cache_specs",
            *_LEASES[tag], reclaim)
        self.place(self.mesh)

    def forget(self, tag: str) -> None:
        """The engine gave buffer ``tag`` up (the prefix tiers, disabled
        under memory pressure): recovery must not make it again."""
        self.buffers.pop(tag, None)

    def place(self, mesh) -> None:
        self.mesh = mesh
        self.placed = shardings(mesh, self.buffers.values(),
                                self.cfg.n_kv_heads)

    def allocate(self, tag: str, *, lease: bool = True):
        """Make buffer ``tag`` from its description, born sharded and
        ready. Every persistent device buffer flows through here: the
        arbiter leases the bytes against the process budget BEFORE
        allocating (reclaiming other subsystems' holdings when it
        must), retries once on a real device OOM, and accounts the
        result (gofrlint GL202's choke point), keyed to the owner so
        close() releases exactly its bytes. A mesh engine settles one
        entry per device, and allocating again (recovery, re-placement)
        re-settles the same keys: set semantics over the lease group,
        never a double count. ``lease=False`` is for the arbiter's own
        reclaim pass, which must not lease from inside itself: the
        smaller buffer only settles the account."""
        buf, sharding = self.buffers[tag], getattr(self.placed, tag)

        def build():
            # ready before it is handed over: a wedged device or a real
            # OOM has to surface here, inside the arbiter's retry
            return jax.block_until_ready(_born_sharded(
                lambda: buf.build(buf.rows), sharding))

        with self._startup.within("allocate", tag=tag, rows=buf.rows) as acct:
            if not lease:
                made = hbm.account(buf.group, build(), owner=self._owner,
                                   tag=tag)
            else:
                made = hbm.alloc_sharded(
                    buf.group, build, owner=self._owner, tag=tag,
                    priority=buf.priority, reclaim=buf.reclaim,
                    devices=self.placed.labels)
            acct.note(bytes=hbm.tree_nbytes(made))
            return made

    def key(self, seed: int):
        """The chained PRNG key. A mesh engine commits it to the
        replicated sharding NOW: the chained key outputs are
        rep-committed, and a first dispatch with an UNCOMMITTED key
        would occupy a different jit cache entry than every later one —
        warming one signature and serving the other re-lowers the
        program mid-serving under the device lock. (Not leased: a
        16-byte key sits below accounting granularity.)"""
        key = jax.random.PRNGKey(seed)
        if self.placed.rep is None:
            return key
        return jax.device_put(key, self.placed.rep)

    # -- the compiled programs -----------------------------------------------
    def build(self, needs=None, *, offload: bool = False) -> dict[str, Any]:
        """The programs of ``TABLE`` this engine has, by engine
        attribute; with ``needs``, only the rows that need one of them
        (a late scratch; the pool's programs after a shrink re-fitted
        its sharding). Built again after a mesh re-placement:
        out_shardings pin the cache layout so donation aliases buffers
        across steps and XLA never resharding-copies the cache, and a
        sharding names its mesh, so programs built against a dead mesh
        can never serve the replacement.

        Sampling keys derive in-trace from each request's (seed,
        absolute position) pair (see _resume_keys; the threaded key is
        signature ballast), and the step's carry chains the per-slot
        decode state — last token, active, budget, position — the
        pipeline's next dispatch consumes."""
        have = {None, *self.buffers}
        if self._spec_k:
            have.add("spec")
        if offload:
            have.add("offload")
        layout = _P if self.paged else _C
        sharded = self.mesh is not None
        out = {}
        with self._startup.within("programs") as acct:
            for p in TABLE:
                if p.layout not in (None, layout) or p.needs not in have \
                        or (needs is not None and p.needs not in needs):
                    continue
                fn = self._traced(
                    p.mesh_fn if sharded and p.mesh_fn else p.fn)
                out[p.attr] = jax.jit(
                    fn, donate_argnums=p.donate,
                    out_shardings=self._out(p.out) if sharded else None)
            acct.note(programs=len(out))
        return out

    def _traced(self, name: str):
        fn = getattr(self, name, None) or globals().get(name)
        if fn is None:
            from ..models import paged_llama

            fn = getattr(paged_llama, name)
        return fn

    def _out(self, spec):
        if isinstance(spec, tuple):
            return tuple(self._out(s) for s in spec)
        return getattr(self.placed, spec)

    # -- traced functions ----------------------------------------------------
    @staticmethod
    def _resume_keys(seeds, pos):
        """Per-slot sampling keys: fold_in(PRNGKey(seed), position).
        Re-keying every sample on the request's seed and the ABSOLUTE
        generated-token position (not the engine's chained key, not a
        step count) is the durable-streams invariant: a continuation
        admitted with ``continue_from`` samples token P with exactly
        the key the original stream would have, on any replica."""
        return jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
        )(seeds, pos)

    @jax.named_scope("sampling")
    def _sample(self, logits, temps, seeds, pos, top_ks, active=None):
        """What the batch's settings ask for, decided on the device.

        Every slot gets ``argmax(logits)`` and the chosen token's
        logprob; that is all an all-greedy batch computes. The draws sit
        under ``lax.cond``: with a slot that is ``active`` (None: all)
        and has ``temperature > 0``, the keys (``_resume_keys`` of
        ``seeds`` and ``pos``) and then, each only where such a slot asks
        for it, categorical(logits/temp) over the whole vocabulary
        (``top_k == 0``) and over the request's top-k of ``TOP_K_MAX``
        logits (``top_k > 0``). A mixed batch stays one program, and a
        slot's token is the same whichever slots sit beside it. A slot
        that retired with its temperature still in the pack does not
        keep a branch alive (its token is never emitted). The host's
        count of the blocks that drew is ``stats()["sampling"]``."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        drawing = temps > 0 if active is None else (temps > 0) & active
        by_k = top_ks > 0
        # the branches take the logits as the head computed them
        # (llama.logits_dtype): a float32 operand of a conditional is
        # an array of its own, [B, V] written every step for nobody
        narrow = logits.astype(llama.logits_dtype(self.cfg))

        def draw():
            keys = self._resume_keys(seeds, pos)
            safe_t = jnp.maximum(temps, 1e-6)[:, None]

            # narrow / safe_t (float32) inside each branch: fused into
            # the draw that reads it, no array of its own
            def whole():
                return jax.vmap(jax.random.categorical)(
                    keys, narrow / safe_t).astype(jnp.int32)

            def truncated():
                kmax = min(self.TOP_K_MAX, logits.shape[-1])
                vals, idx = jax.lax.top_k(narrow / safe_t, kmax)  # [B, kmax]
                kk = jnp.minimum(jnp.where(by_k, top_ks, kmax), kmax)
                vals = jnp.where(jnp.arange(kmax)[None, :] < kk[:, None],
                                 vals, -jnp.inf)
                in_k = jax.vmap(jax.random.categorical)(keys, vals)
                return jnp.take_along_axis(
                    idx, in_k[:, None], axis=1)[:, 0].astype(jnp.int32)

            sampled = jnp.where(
                by_k,
                jax.lax.cond(jnp.any(drawing & by_k), truncated,
                             lambda: greedy),
                jax.lax.cond(jnp.any(drawing & ~by_k), whole,
                             lambda: greedy))
            return jnp.where(temps > 0, sampled, greedy)

        tok = jax.lax.cond(jnp.any(drawing), draw, lambda: greedy)
        # logprob of the chosen token under the MODEL's (untempered)
        # distribution — the number OpenAI-style logprobs report
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(logp, tok[:, None], axis=1)[:, 0]
        return tok, lp

    def _prefill_fn(self, cache, params, tokens, length, slot, temp,
                    top_k, key, seed, pos, adapter=None):
        """tokens [1, Sb] (padded), length/slot scalars. Writes the slot's
        KV, sets its cursor, returns (first_token scalar, cache).
        ``seed``/``pos``: the request's sampling seed and the absolute
        position of the token sampled here (pos_base — 0 for a fresh
        request, the emitted count for a continuation); ``key`` chains
        through unchanged for signature stability."""
        # flash prefill everywhere: bare Pallas calls do not partition
        # under GSPMD, so on mesh engines ops.flash wraps the kernel in
        # shard_map per head shard (jnp reference when tp would split a
        # KV head) — the mesh= plumbing picks the form.
        logits, *kv, _ = self._fam.prefill_kv(
            params, self.cfg, tokens, jnp.asarray([length]),
            rope_max=self.max_seq, rope_tables=self.rope_tables,
            flash=True, mesh=self.mesh, adapter=adapter,
            logit_pos=jnp.asarray([length - 1]))
        lengths = cache.lengths.at[slot].set(length)
        # the slot's row from position 0, every layer: (0, slot, 0, ...)
        cache = self._fam.write_kv(
            cache, *kv, (0, slot) + (0,) * (cache[0].ndim - 2), lengths)
        last = logits[0, 0]  # [V] at the true prompt end (logit_pos)
        tok, lp = self._sample(last[None, :], temp[None], seed[None],
                               pos[None], top_k[None])
        return tok[0], lp[0], key, cache

    def _chunk_fn(self, cache, params, tokens, start, slot, total_len,
                  pos_in_chunk, temp, top_k, key, seed, pos, adapter,
                  sample: bool):
        """Chunked prefill for prompts longer than the largest bucket:
        slice the slot's cache view, run one chunk against it, write back.
        The final chunk (``sample=True``) also sets the slot's cursor to
        ``total_len`` and samples the first token at ``pos_in_chunk``."""
        # the slot's view of every cache array ([L, 1, ...]: K, V and
        # scale planes, or latent rows), and its write-back
        arrays = cache._replace(lengths=None)
        small = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
            arrays)._replace(lengths=jnp.zeros((1,), jnp.int32))
        logits, small = self._fam.prefill_chunk(
            params, self.cfg, tokens, small, start,
            rope_tables=self.rope_tables, compute_logits=sample,
            adapter=adapter, mesh=self.mesh,
            logit_pos=jnp.asarray(pos_in_chunk)[None] if sample else None)
        written = jax.tree_util.tree_map(
            lambda a, s: jax.lax.dynamic_update_slice_in_dim(a, s, slot,
                                                             axis=1),
            arrays, small._replace(lengths=None))
        if not sample:
            # PARK the slot while its prompt is chunk-written: decode
            # blocks interleave with mid-chunks, and every decode step
            # scatter-writes garbage KV at each slot's cursor — a stale
            # cursor inside [0, prompt_len) would corrupt KV this chunk
            # just wrote. Cursor = capacity makes those writes land out
            # of range, where mode="drop" discards them.
            return written._replace(
                lengths=cache.lengths.at[slot].set(self.max_seq))
        lengths = cache.lengths.at[slot].set(total_len)
        last = logits[0, 0]  # [V] at pos_in_chunk (logit_pos)
        tok, lp = self._sample(last[None, :], temp[None], seed[None],
                               pos[None], top_k[None])
        return tok[0], lp[0], key, written._replace(lengths=lengths)

    # methods, not functools.partial: jit names a program after its
    # function, and a partial has no name (jit__unknown in a device trace)
    def _chunk_mid(self, *args):
        return self._chunk_fn(*args, sample=False)

    def _chunk_final(self, *args):
        return self._chunk_fn(*args, sample=True)

    def _fused_decode_scan(self, cache, pack, carry, key, step_model):
        """K fused decode steps over all slots (K = decode_block); one
        dispatch returns [K, B] tokens + an emitted mask. Each step
        feeds its sampled token to the next on device — the host is off
        the per-token critical path entirely. Inactive cursors stay
        frozen every step (their garbage KV scatter lands at the frozen
        position, which admission either overwrites or — for parked
        slots — drops), and attention is told which slots are active so
        that it reads nothing of the others. ``step_model(tokens, cache,
        active) -> (logits, stepped[, counters])`` is the only thing that
        differs between the contiguous and paged engines and between
        model families; what a family's step counts beside its logits
        comes back stacked a step as the program's last output, for the
        reap's one fetch. The counters have fixed places: first the
        expert layer's assignments (None where the family has no such
        layer), then the recurrent states updated (None likewise), then
        the rows a learned selection kept and chose among.

        ``pack`` [B, W] int32 is the coalesced host dispatch state (one
        h2d when dirty — see _dispatch_pack); ``carry`` is the device
        slot-state chain (last token, active, budget, position)
        returned by the PREVIOUS block — per slot, ``host_wins`` picks
        which side is the truth (host after admission/retire/verify,
        device in steady state). Chaining ACTIVE and BUDGET through the
        device is what makes depth-2 pipelining exact: block N+1 is
        dispatched before the host has seen block N's tokens, and a
        stream that hits EOS/budget/capacity inside N self-deactivates
        via the in-scan stop mask (llama.decode_stop_mask) so N+1
        freezes it instead of emitting junk. ``emitted`` [K, B] tells
        the host exactly which tokens are real — host delivery replays
        it verbatim, so device stop masks and host retirement stay
        token-equivalent.

        Sampling keys derive in-trace from the pack's per-request SEED
        and the carried absolute POSITION (fold_in(PRNGKey(seed), pos))
        — never from a chained engine key — so a stream interrupted
        anywhere and resumed via ``generate(continue_from=...)`` samples
        the identical tokens (the durable-streams contract). Position
        rides the device carry (not the pack) because under pipelining
        the host cannot know block N's emitted count when it packs
        block N+1; it advances only where a token was actually emitted,
        so delivered token i of a request always consumed position
        ``pos_base + i``. ``key`` chains through untouched (returned
        as-is) purely for dispatch-signature stability."""
        E = self.EOS_MAX
        host_tokens = pack[:, 0]
        host_active = pack[:, 1].astype(bool)
        host_budget = pack[:, 2]
        temps = jax.lax.bitcast_convert_type(pack[:, 3], jnp.float32)
        top_ks = pack[:, 4]
        host_wins = pack[:, 6].astype(bool)
        seeds = pack[:, 7]
        host_pos = pack[:, 8]
        eos_ids = pack[:, self._PACK_EXTRA:self._PACK_EXTRA + E]
        dev_tokens, dev_active, dev_budget, dev_pos = carry
        tokens0 = jnp.where(host_wins, host_tokens, dev_tokens)
        active0 = jnp.where(host_wins, host_active, dev_active)
        budget0 = jnp.where(host_wins, host_budget, dev_budget)
        pos0 = jnp.where(host_wins, host_pos, dev_pos)
        # the host retires one delivered token before the cursor hits
        # capacity (see _deliver's at_capacity): post-step cursors at
        # max_seq - 2 mean the NEXT delivery would reach the bound
        cap = jnp.int32(self.max_seq - 2)

        def body(carry, _):
            tokens, active, budget, pos, cache = carry
            logits, stepped, *counters = step_model(tokens, cache, active)
            lengths = jnp.where(active, stepped.lengths, cache.lengths)
            stepped = stepped._replace(lengths=lengths)
            toks, lps = self._sample(logits, temps, seeds, pos, top_ks,
                                     active)
            toks = jnp.where(active, toks, tokens)
            emitted = active
            budget = jnp.where(active, budget - 1, budget)
            # position advances only where a token was emitted: frozen
            # slots must not burn positions, or a resume after their
            # retirement would re-key mid-stream
            pos = pos + emitted.astype(jnp.int32)
            stop = active & llama.decode_stop_mask(toks, lengths, budget,
                                                   eos_ids, cap)
            return (toks, active & ~stop, budget, pos, stepped), \
                (toks, lps, emitted, counters)

        (last, active, budget, pos, cache), (toks, lps, emitted, counters) \
            = jax.lax.scan(body, (tokens0, active0, budget0, pos0, cache),
                           None, length=self.decode_block)
        return (toks, lps, emitted, (last, active, budget, pos), key,
                cache, counters)

    def _verify_epilogue(self, logits, window, active, stepped):
        """Shared verify-pass tail: greedy tokens + their logprobs, the
        longest agreeing draft run per slot (accept), emit counts (the
        +1 is the pass's guaranteed token; inactive slots emit 0), and
        cursors advanced by exactly what the caller may deliver."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, W]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lps = jnp.take_along_axis(logp, greedy[..., None], axis=-1)[..., 0]
        agree = (greedy[:, :-1] == window[:, 1:]).astype(jnp.int32)
        accept = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)     # [B]
        emit = jnp.where(active, accept + 1, 0)
        lengths = stepped.lengths + emit
        return greedy, lps, emit, stepped._replace(lengths=lengths)

    def carry_tail(self, slots: int) -> tuple:
        """What a block family's slot-state carry holds after the four
        every family chains (last token, active, budget, position), as a
        first dispatch's host-built stand-in: the block's tokens [B, W],
        which of them are known, their logprobs, and the count the
        prompt gave. Every slot of a first dispatch is idle or freshly
        admitted, and the pack wins for those."""
        W = self.cfg.block_length
        if not W:
            return ()
        return (jnp.zeros((slots, W), jnp.int32),
                jnp.zeros((slots, W), bool),
                jnp.zeros((slots, W), jnp.float32),
                jnp.zeros((slots,), jnp.int32))

    def _block_decode_scan(self, cache, params, pack, carry, key):
        """``decode_block`` PASSES over the slots' blocks (a family whose
        step is a pass over ``W = cfg.block_length`` positions, not a
        token); one dispatch returns [K W, B] tokens and an emitted
        mask, a pass's W rows one after another, which the host replays
        as it does a token step's.

        A slot holds the block at its cursor: tokens, which of them are
        ``known`` (given by the prompt, or committed by an earlier
        pass: state, never ``token == mask_token_id``) and the known
        ones' logprobs. Each pass every active slot is in one of two
        states. With a masked position left it DENOISES: the stack over
        the block (``decode_step``), the head over the positions the
        order can commit (``candidates``), a token and its probability
        each (``_sample``: greedy, or the request's temperature and
        top-k, keyed on the token's absolute generated index), and the
        order's pick among them becomes known (``commits``). With none
        left it COMMITS: the same stack over the final tokens, the
        block's W rows a layer written at the cursor, which moves by W;
        the block's generated tokens are emitted in order, cut at the
        budget, at an EOS and at capacity, and the slot starts on the
        next block, all masked. Slots are at their own passes; the head
        runs where any slot denoises.

        The carry after the four of every family: the block's tokens,
        ``known``, logprobs [B, W], and ``given`` [B], the positions of
        the slot's FIRST block that the prompt holds (its ``n mod W``
        last tokens; a prefill covers whole blocks and yields no token).
        Where ``host_wins`` those come from the pack with the slot's
        cursor, which the device's lengths take: a prompt shorter than
        a block runs no prefill to set it.

        The counters a pass: the expert layers' assignments first, as
        every family's, then (None, None and) four sums over the slots:
        those that denoised, those that committed, positions committed,
        tokens emitted."""
        fam, cfg = self._fam, self.cfg
        W, E = cfg.block_length, self.EOS_MAX
        B = pack.shape[0]
        host_active = pack[:, 1].astype(bool)
        host_budget = pack[:, 2]
        temps = jax.lax.bitcast_convert_type(pack[:, 3], jnp.float32)
        top_ks = pack[:, 4]
        host_wins = pack[:, 6].astype(bool)
        seeds = pack[:, 7]
        host_pos = pack[:, 8]
        eos_ids = pack[:, self._PACK_EXTRA:self._PACK_EXTRA + E]
        lo = self._PACK_EXTRA + E
        host_cursor, host_given = pack[:, lo], pack[:, lo + 1]
        host_block = pack[:, lo + 2:lo + 2 + W]
        _, dev_active, dev_budget, dev_pos, dev_block, dev_known, dev_lps, \
            dev_given = carry
        col = jnp.arange(W, dtype=jnp.int32)[None, :]
        wins = host_wins[:, None]
        block0 = jnp.where(wins, host_block, dev_block)
        known0 = jnp.where(wins, col < host_given[:, None], dev_known)
        lps0 = jnp.where(wins, 0.0, dev_lps)
        given0 = jnp.where(host_wins, host_given, dev_given)
        active0 = jnp.where(host_wins, host_active, dev_active)
        budget0 = jnp.where(host_wins, host_budget, dev_budget)
        pos0 = jnp.where(host_wins, host_pos, dev_pos)
        cache = cache._replace(lengths=jnp.where(
            host_wins & host_active, host_cursor, cache.lengths))
        # the last position a slot delivers (_deliver's at_capacity)
        cap = jnp.int32(self.max_seq - 2)
        c = fam.candidates_width(cfg)

        def rep(a):     # a slot's setting for each of its candidates
            return jnp.repeat(a, c)

        def body(carry, _):
            block, known, lps, given, active, budget, pos, cache = carry
            whole = jnp.all(known, axis=1)
            commit, denoise = active & whole, active & ~whole
            cursor = cache.lengths
            with jax.named_scope("diffusion/denoise"):
                x, stepped, moe_n = fam.decode_step(
                    params, cfg, block, cache, rope_tables=self.rope_tables,
                    mesh=self.mesh, active=active, known=known,
                    commit=commit)
                cand, masked = fam.candidates(cfg, known)        # [B, c]

                @jax.named_scope("diffusion/sample")
                def sample():
                    rows = jnp.take_along_axis(x, cand[..., None], axis=1)
                    logits = fam.logits(params, cfg, rows)       # [B, c, V]
                    at = pos[:, None] + cand - given[:, None]
                    tok, lp = self._sample(
                        logits.reshape(B * c, -1), rep(temps), rep(seeds),
                        at.reshape(-1), rep(top_ks), rep(denoise))
                    return tok.reshape(B, c), lp.reshape(B, c)

                tok, lp = jax.lax.cond(
                    jnp.any(denoise), sample,
                    lambda: (jnp.zeros((B, c), jnp.int32),
                             jnp.zeros((B, c), jnp.float32)))
                pick_c = fam.commits(cfg, jnp.exp(lp),
                                     masked & denoise[:, None])  # [B, c]
                # the candidates' picks back at their block positions
                put = (cand[..., None] == col[:, None, :]) \
                    & pick_c[..., None]                          # [B, c, W]
                pick = jnp.any(put, axis=1)
                block = jnp.where(pick, jnp.sum(
                    jnp.where(put, tok[..., None], 0), axis=1), block)
                lps = jnp.where(pick, jnp.sum(
                    jnp.where(put, lp[..., None], 0.0), axis=1), lps)
                known = known | pick
            with jax.named_scope("diffusion/emit"):
                # a committed block's generated tokens, in order: not
                # what the prompt gave, not past the budget, not past
                # the first EOS, not past capacity
                nth = col - given[:, None]
                ok = commit[:, None] & (nth >= 0) & (nth < budget[:, None]) \
                    & (cursor[:, None] + col <= cap)
                eos = ok & jnp.any(block[..., None] == eos_ids[:, None, :],
                                   axis=-1)
                emit = ok & (jnp.cumsum(eos, axis=1) - eos == 0)
                n_emit = jnp.sum(emit, axis=1, dtype=jnp.int32)
                budget = budget - n_emit
                pos = pos + n_emit
                stop = commit & ((budget <= 0) | jnp.any(eos & emit, axis=1)
                                 | (stepped.lengths > cap))
                out = (block.T, lps.T, emit.T)
                # the next block: all masked, nothing given
                known = known & ~commit[:, None]
                given = jnp.where(commit, 0, given)
            counts = jnp.stack([jnp.sum(denoise), jnp.sum(commit),
                                jnp.sum(pick), jnp.sum(n_emit)]
                               ).astype(jnp.int32)
            return (block, known, lps, given, active & ~stop, budget, pos,
                    stepped), (*out, (moe_n, None, None, counts))

        (block, known, lps, given, active, budget, pos, cache), \
            (toks, tlps, emitted, counters) = jax.lax.scan(
                body, (block0, known0, lps0, given0, active0, budget0, pos0,
                       cache), None, length=self.decode_block)
        flat = (toks.reshape(-1, B), tlps.reshape(-1, B),
                emitted.reshape(-1, B))
        return (*flat, (block[:, 0], active, budget, pos, block, known, lps,
                        given), key, cache, counters)

    def _step_fn(self, cache, params, pack, carry, key):
        if self.cfg.block_length:
            return self._block_decode_scan(cache, params, pack, carry, key)
        adapter = pack[:, 5] if self._n_adapters else None

        def step_model(tokens, cache, active):
            return self._fam.decode_step(
                params, self.cfg, tokens, cache,
                rope_tables=self.rope_tables, adapter=adapter,
                mesh=self.mesh, active=active)

        return self._fused_decode_scan(cache, pack, carry, key, step_model)

    def _paged_prefill_fn(self, cache, params, tokens, length, blocks,
                          slot, temp, top_k, key, seed, pos,
                          adapter=None):
        """Paged admission: prefill the prompt, write its KV into the
        slot's allocated ``blocks`` ([ceil(Sb/T)] int32 — entries past
        the prompt's own blocks point at the trash block so bucket
        padding lands nowhere), set the cursor, sample the first token
        (re-keyed on ``seed``/``pos`` — see _resume_keys)."""
        from ..models import paged_llama

        # flash prefill everywhere — shard_map'd per head shard on mesh,
        # same contract as the contiguous _prefill_fn
        logits, k, v, _ = llama.prefill_kv(
            params, self.cfg, tokens, jnp.asarray([length]),
            rope_max=self.max_seq, rope_tables=self.rope_tables,
            flash=True, mesh=self.mesh, adapter=adapter,
            logit_pos=jnp.asarray([length - 1]))
        cache = paged_llama.write_prompt_blocks(cache, k, v, blocks, length)
        cache = cache._replace(lengths=cache.lengths.at[slot].set(length))
        last = logits[0, 0]  # [V] at the true prompt end (logit_pos)
        tok, lp = self._sample(last[None, :], temp[None], seed[None],
                               pos[None], top_k[None])
        return tok[0], lp[0], key, cache

    def _paged_verify_fn(self, cache, params, window, active, key, table,
                         adapter=None):
        """_verify_fn over the paged pool (models.paged_llama.
        paged_verify_step): same greedy/accept/emit semantics, window KV
        routed through the block table."""
        from ..models import paged_llama

        logits, stepped = paged_llama.paged_verify_step(
            params, self.cfg, window, cache, table,
            rope_tables=self.rope_tables, adapter=adapter,
            flash=True, mesh=self.mesh)
        return self._verify_epilogue(logits, window, active, stepped)

    def _paged_step_fn(self, cache, params, pack, carry, key):
        """_step_fn over the block pool. The table rides in the pack's
        trailing [B, MB] columns — host-owned and constant through the
        block (the host pre-allocates blocks covering K tokens per
        slot)."""
        from ..models import paged_llama

        lo = self._PACK_EXTRA + self.EOS_MAX
        table = pack[:, lo:lo + self._mb]
        adapter = pack[:, 5] if self._n_adapters else None

        def step_model(tokens, cache, active):
            return paged_llama.paged_decode_step(
                params, self.cfg, tokens, cache, table,
                rope_tables=self.rope_tables, adapter=adapter,
                flash=True, mesh=self.mesh)

        return self._fused_decode_scan(cache, pack, carry, key, step_model)

    def _verify_fn(self, cache, params, window, active, key, adapter=None):
        """One speculative verify pass. ``window`` [B, W]: col 0 = each
        slot's pending last token, cols 1.. = prompt-lookup drafts.
        Greedy-only (callers route sampling slots to the decode path).
        Returns (greedy [B, W], emit [B] — how many of greedy's leading
        tokens are real, 0 for inactive slots) and the cache with
        cursors advanced by emit. ``key`` is unused (greedy) but kept so
        the signature matches _step_fn's calling convention."""
        logits, stepped = llama.verify_step(params, self.cfg, window,
                                            cache,
                                            rope_tables=self.rope_tables,
                                            adapter=adapter, mesh=self.mesh)
        return self._verify_epilogue(logits, window, active, stepped)
