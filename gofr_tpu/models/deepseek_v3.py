"""The latent-attention, grouped-router family (``model_type: deepseek_v3``).

The generator picks this module where ``cfg.kv_lora_rank > 0``
(``models.family``) and calls it through the same entry points as
``models/llama.py``: ``init``, ``init_cache``, ``get_rope_tables``,
``prefill_kv``, ``write_kv``, ``prefill_chunk``, ``decode_step``.

What differs from the Llama block, by equation (``x`` the residual
stream, RMSNorm before attention and before the feed-forward):

  - latent attention (MLA): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
    a head ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva`` with
    ``c_kv`` normed and ``k_pe`` rotated, ONE for all heads. The cache
    holds that row, ``kv_lora_rank + qk_rope_head_dim`` values a token a
    layer, and nothing a head. A whole-prompt prefill expands keys and
    values a head from ``c_kv W_kvb`` over the prompt; decode, and a
    chunk over the rows cached before it, are *absorbed*: ``W_kvb`` a
    head is ``[W_UK | W_UV]``, ``q_abs = q_nope W_UK^T``, the score is
    ``[q_abs | q_pe] . row``, the output ``(sum p . c_kv) W_UV``
    (ops/mla.py). Query/key width (nope + rope) and value width are
    separate keys; nothing here takes them to be equal.
  - rotary tables: YaRN (ops/rope.py) on the rope part only; the softmax
    scale is ``(nope + rope)^-1/2`` times YaRN's factor. Pairing: the
    rope dims are rotated as halves in the order the projection gives
    them (the published code first gathers even and odd dims; with
    seeded weights that is a permutation of the projection's columns).
  - feed-forward: the first ``n_dense_layers`` layers are SwiGLU of
    width ``ffn_dim``; the others route: ``s = sigmoid(x W_g)`` in
    float32, selection by ``s + bias`` limited to the ``topk_groups``
    best of ``n_expert_groups`` groups (a group's score: its two
    largest), top ``experts_per_token`` inside them, weights
    ``s_i / sum s_j * routed_scaling`` (the bias selects, it does not
    weigh), plus ``n_shared_experts`` shared experts always on.
  - the chip's share: the layer holds experts ``0 .. n_experts_held-1``
    of the ``n_experts`` the router scores, routes exactly as published
    and sums over the HELD experts a token chose; what the absent ones
    would add is left out, and that partial sum goes on. Nothing stands
    in for the other chips.

Expert dispatch (``_experts``): the (token, held expert) assignments
stand expert by expert in row blocks of ``block`` rows, each block one
expert's (their rows counted, not sorted: ``_tables``; the buffer filled
by a 0/1 matmul: ``_fill``), and the blocks THAT EXIST run one SwiGLU
each (one kernel
over them, ``ops/moe_experts.py``, or a loop where that cannot run), so
FLOPs follow the assignments. No capacity, no token dropped,
and a token's result does not depend on what else is in the batch: a
block's rows are independent rows of one matmul.

Layers are two stacks, ``params["dense_layers"]`` and
``params["layers"]`` (the routed ones), each scanned; cache layer ``l``
is dense layer ``l`` or routed layer ``l - n_dense_layers``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import mla, moe_experts
from ..ops.flash import interpret_env
from ..ops.norms import rms_norm
from ..ops.quant import QuantizedLinear, qmatmul
from ..ops.rope import apply_rope, rope_frequencies, yarn_softmax_scale
from .common import ModelConfig, dense_init
from .llama import _logits

# the leaves of a routed expert [Ls, Eh, ...]: all three a SwiGLU, the
# last two a two-matrix relu^2 expert (``expert_stacks``)
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
_LANES = 128
_ROPE_CACHE: dict[tuple, tuple] = {}
RECOMPUTABLE = True  # rows, as llama's: see models.family


def get_rope_tables(cfg: ModelConfig, max_seq: int):
    """Memoized (cos, sin) [max_seq, qk_rope_head_dim // 2]."""
    scaling_key = tuple(sorted(cfg.rope_scaling.items())) \
        if cfg.rope_scaling else None
    key = (cfg.qk_rope_head_dim, max_seq, cfg.rope_theta, scaling_key)
    if key not in _ROPE_CACHE:
        tables = rope_frequencies(cfg.qk_rope_head_dim, max_seq,
                                  cfg.rope_theta, cfg.rope_scaling)
        if any(isinstance(t, jax.core.Tracer) for t in tables):
            return tables
        _ROPE_CACHE[key] = tables
    return _ROPE_CACHE[key]


def row_width(cfg: ModelConfig) -> int:
    """Values a cached row holds: the latent and the shared rotated key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def stored_width(cfg: ModelConfig) -> int:
    """Lanes a cached row takes: ``row_width`` rounded up to whole HBM
    tiles of 128 lanes (576 -> 640; ops/mla.py says why)."""
    return -(-row_width(cfg) // _LANES) * _LANES


def n_held(cfg: ModelConfig) -> int:
    return cfg.n_experts_held or cfg.n_experts


class LatentCache(NamedTuple):
    """Preallocated decode cache of latent rows, per-slot cursors."""

    rows: jnp.ndarray     # [L, B, Smax, stored_width]
    lengths: jnp.ndarray  # [B] int32: valid rows a slot

    @property
    def quantized(self) -> bool:
        return False


def init_cache(cfg: ModelConfig, batch: int, max_seq: int | None = None,
               dtype=None) -> LatentCache:
    return LatentCache(
        rows=jnp.zeros((cfg.n_layers, batch, max_seq or cfg.max_seq,
                        stored_width(cfg)), dtype or cfg.jdtype),
        lengths=jnp.zeros((batch,), jnp.int32))


def kv_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(heads, values a head) of a cached token, for the prefix index's
    shape contract: one shared row."""
    return 1, stored_width(cfg)


def decode_kv_block(cfg: ModelConfig, cache: LatentCache, mesh=None):
    """Cache positions a decode work item covers, None on the reference
    path (ops.mla.decode_block)."""
    return mla.decode_block(cache.rows, cfg.kv_lora_rank)


def unsupported_options(*, mesh=None, paged_blocks: int = 0, kvcache=None,
                        spec_decode_k: int = 0, lora_adapters: int = 0,
                        kv_dtype=None, serving_role: str | None = None
                        ) -> list[tuple[str, str]]:
    """(engine option, reason) for every serving option this family does
    not run yet, in the engine constructor's own names. The engine raises
    on any of them at start-up: never a fall-through to the Llama cache."""
    refused = []
    if mesh is not None:
        refused.append(("mesh", "the latent row is shared by all heads and "
                        "the expert share has no exchange across chips; "
                        "the family runs on one chip"))
    if paged_blocks:
        refused.append(("paged_blocks", "the block pool holds K and V a "
                        "head, not latent rows"))
    if kvcache is not None and (kvcache.host_mb > 0
                                or kvcache.redis is not None):
        refused.append(("kvcache", "the host and Redis tiers frame K and V "
                        "a head"))
    if spec_decode_k:
        refused.append(("spec_decode_k", "there is no verify pass over "
                        "latent rows"))
    if lora_adapters:
        refused.append(("lora_adapters", "adapters target wq/wk/wv/wo, "
                        "which this family does not have"))
    if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
        refused.append(("kv_dtype", "int8: the latent row is cached in the "
                        "model's type (bfloat16)"))
    if serving_role not in (None, "", "fused"):
        refused.append(("serving_role", f"{serving_role}: KV shipping "
                        "frames K and V a head"))
    return refused


def init_routed(keys, cfg: ModelConfig, L: int) -> dict:
    """Random-init leaves of ``L`` routed feed-forwards (what ``moe_ffn``
    reads), one key of ``keys`` a leaf in this order: the router
    ``n_experts`` wide and its bias whole, ``n_held`` experts in their
    form (``expert_stacks``) and width (``expert_width``), the shared
    experts' leaves where the configuration has any, and the latent's
    two projections where it has one."""
    dt, D, Dx = cfg.jdtype, cfg.dim, expert_width(cfg)
    E, Eh, Fm = cfg.n_experts, n_held(cfg), cfg.moe_ffn_dim
    Fs = cfg.shared_ffn_dim or Fm * cfg.n_shared_experts
    w = {"router": dense_init(next(keys), (L, D, E), dt),
         "router_bias": 0.01 * jax.random.normal(next(keys), (L, E),
                                                 jnp.float32)}
    for name in expert_stacks(cfg):
        shape = (L, Eh, Fm, Dx) if name == "w_down" else (L, Eh, Dx, Fm)
        w[name] = dense_init(next(keys), shape, dt)
    if Fs:
        for name in expert_stacks(cfg):
            shape = (L, Fs, D) if name == "w_down" else (L, D, Fs)
            w["ws" + name[1:]] = dense_init(next(keys), shape, dt)
    if cfg.moe_latent_dim:
        w.update(w_latent_down=dense_init(next(keys), (L, D, Dx), dt),
                 w_latent_up=dense_init(next(keys), (L, Dx, D), dt))
    return w


def init(cfg: ModelConfig, key) -> dict:
    """Random-init params of the share this chip holds: every norm, the
    router (``n_experts`` wide) and its bias whole, ``n_experts_held``
    experts a routed layer."""
    dt = cfg.jdtype
    k = iter(jax.random.split(key, 24))
    D, H, V = cfg.dim, cfg.n_heads, cfg.vocab_size
    Rq, R = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    nd = cfg.n_dense_layers
    ns = cfg.n_layers - nd

    def attn(L):
        return {
            "attn_norm": jnp.ones((L, D), dt),
            "w_qa": dense_init(next(k), (L, D, Rq), dt),
            "q_norm": jnp.ones((L, Rq), dt),
            "w_qb": dense_init(next(k), (L, Rq, H * (dn + dr)), dt),
            "w_kva": dense_init(next(k), (L, D, R + dr), dt),
            "kv_norm": jnp.ones((L, R), dt),
            "w_kvb": dense_init(next(k), (L, R, H * (dn + dv)), dt),
            "wo": dense_init(next(k), (L, H * dv, D), dt),
            "ffn_norm": jnp.ones((L, D), dt),
        }

    params = {
        "embedding": dense_init(next(k), (V, D), dt, scale=0.02),
        "dense_layers": {
            **attn(nd),
            "w_gate": dense_init(next(k), (nd, D, cfg.ffn_dim), dt),
            "w_up": dense_init(next(k), (nd, D, cfg.ffn_dim), dt),
            "w_down": dense_init(next(k), (nd, cfg.ffn_dim, D), dt),
        },
        "layers": {
            **attn(ns),
            **init_routed(k, cfg, ns),
        },
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(next(k), (D, V), dt)
    return params


# -- the expert layer ----------------------------------------------------------

@jax.named_scope("moe/route")
def route(hf, router, bias, cfg: ModelConfig):
    """hf [T, D] -> (expert ids [T, k], weights [T, k] float32), over all
    ``n_experts`` as published: float32 sigmoid scores; ``s + bias``
    selects (groups by the sum of their two best, then the top k inside
    the kept groups); the weights are the unbiased scores, renormalised
    and scaled."""
    T = hf.shape[0]
    E, G = cfg.n_experts, cfg.n_expert_groups
    s = jax.nn.sigmoid(jnp.dot(hf.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    sel = s + bias.astype(jnp.float32)
    group = jnp.sum(jax.lax.top_k(sel.reshape(T, G, E // G), 2)[0], -1)
    kept = jnp.sum(jax.nn.one_hot(jax.lax.top_k(group, cfg.topk_groups)[1],
                                  G, dtype=jnp.bool_), axis=1)     # [T, G]
    sel = jnp.where(jnp.repeat(kept, E // G, axis=1), sel, -jnp.inf)
    topi = jax.lax.top_k(sel, cfg.experts_per_token)[1]
    w = jnp.take_along_axis(s, topi, axis=1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * cfg.routed_scaling
    return topi, w


def _swiglu(x, gate, up, down):
    return qmatmul(jax.nn.silu(qmatmul(x, gate)) * qmatmul(x, up), down)


def _relu2(x, up, down):
    """The two-matrix expert: ``W2 relu(W1 x)^2``, no gate."""
    return qmatmul(jnp.square(jax.nn.relu(qmatmul(x, up))), down)


def expert_width(cfg: ModelConfig) -> int:
    """The width the routed experts read and write, and so the width of
    the dispatch: a latent's where the configuration has one, else the
    model's."""
    return cfg.moe_latent_dim or cfg.dim


def expert_stacks(cfg: ModelConfig) -> tuple[str, ...]:
    """The leaves of one routed expert, by its form."""
    return EXPERT_STACKS if cfg.expert_act == "swiglu" else EXPERT_STACKS[1:]


def expert_dispatch(cfg: ModelConfig, tokens: int) -> tuple[int, int]:
    """(rows of a dispatch block, rows of the padded dispatch buffer) for
    ``tokens`` tokens. A block is one bfloat16 sublane tile for a decode
    batch (a few tokens an expert), more where a prompt brings many; the
    buffer holds at most min(k, held) held assignments a token and less
    than a block of padding an expert."""
    bm = 16 if tokens <= 128 else 64
    Eh = n_held(cfg)
    nb_max = (tokens * min(cfg.experts_per_token, Eh)
              + Eh * (bm - 1) + bm - 1) // bm
    return bm, nb_max * bm


def experts_on_kernel(cfg: ModelConfig, dtype=None) -> bool:
    """Whether ``_experts`` runs its blocks through the kernel of
    ``ops/moe_experts.py`` (chosen from backend, widths and the
    activations' type) or through the jnp loop it is tested against."""
    return moe_experts.kernel_ok(expert_width(cfg), cfg.moe_ffn_dim,
                                 dtype or cfg.jdtype,
                                 len(expert_stacks(cfg)))


def serving_stats(cfg: ModelConfig, slots: int) -> dict:
    """What ``GenerationEngine.stats()`` says of the programs of a family
    that routes through ``moe_ffn``: the decode step's expert dispatch
    shapes (the device operations that tall are the routed experts':
    benchmarks/metrics reads them here), the path its blocks take and
    how the dispatch tables are built (``_tables``)."""
    bm, rows = expert_dispatch(cfg, slots)
    said = {"block_rows": bm, "buffer_rows": rows,
            "width": expert_width(cfg), "path": "loop",
            "tables": "counted"}
    if experts_on_kernel(cfg):
        # columns of the expert width a grid step takes, by the weights'
        # type: fewer than the width where an expert's tiles are over
        # the kernel's budget
        said.update(path="kernel", tile_columns={
            name: moe_experts.tile_columns(expert_width(cfg),
                                           cfg.moe_ffn_dim, size,
                                           len(expert_stacks(cfg)))
            for name, size in (("int8", 1),
                               (cfg.dtype, cfg.jdtype.itemsize))})
    return {"moe_decode_dispatch": said}


def _blocks_loop(xs, blk_expert, n_blocks, stacks, li, bm: int):
    """The dispatch buffer's live blocks through their experts, one loop
    turn a block: xs [rows, D] -> [rows, D], zero past ``n_blocks``."""
    def one(a, e):  # a [Ls, Eh, ...] -> a[li, e]
        return jax.lax.dynamic_index_in_dim(
            a.reshape((-1,) + a.shape[2:]), li * a.shape[1] + e, 0,
            keepdims=False)

    def at(leaf, e):
        if isinstance(leaf, QuantizedLinear):
            return QuantizedLinear(one(leaf.w, e), one(leaf.scale, e))
        return one(leaf, e)

    def body(j, out):
        e = blk_expert[j]
        x = jax.lax.dynamic_slice_in_dim(xs, j * bm, bm, axis=0)
        up, down = at(stacks["w_up"], e), at(stacks["w_down"], e)
        y = _swiglu(x, at(stacks["w_gate"], e), up, down) \
            if "w_gate" in stacks else _relu2(x, up, down)
        return jax.lax.dynamic_update_slice_in_dim(out, y, j * bm, axis=0)

    return jax.lax.fori_loop(0, n_blocks, body, jnp.zeros_like(xs))


def _blocks_kernel(xs, blk_expert, n_blocks, stacks, li, bm: int,
                   tile: int | None = None):
    """``_blocks_loop`` as one kernel over the blocks that exist (``tile``:
    columns of the expert width a grid step takes, from the shapes
    unless a test says)."""
    # a stack without a gate hands the kernel None in its place
    leaves = [stacks.get(name) for name in EXPERT_STACKS]
    scales = ()
    if isinstance(leaves[-1], QuantizedLinear):
        scales = tuple(None if leaf is None else leaf.scale
                       for leaf in leaves)
        leaves = [None if leaf is None else leaf.w for leaf in leaves]
    return moe_experts.expert_blocks_stacked(
        xs, blk_expert, n_blocks, li, *leaves, *scales, block_rows=bm,
        tile=tile, interpret=interpret_env())


# tokens one triangular matmul counts: a decode batch or a 512-token chunk
# is one pass, and no [T, T] matrix is ever larger than half a megabyte
_COUNT_ROWS = 512


def _counted(chose):
    """chose [T, E1] 0/1, a row a token and a column a key -> seen
    [T, E1] int32: the tokens 0..t that chose the column's key, token t
    counted. Chunks of ``_COUNT_ROWS`` tokens against a lower-triangular
    0/1 matrix on the matrix unit (0/1 in bfloat16, float32 sums of at
    most a chunk's rows: exact), and the chunks before a chunk added in
    int32: no scan down the rows, and one chunk is the whole of it."""
    T, E1 = chose.shape
    c = min(T, _COUNT_ROWS)
    C = -(-T // c)
    # rows past T chose nothing: they count for nobody
    chunks = jnp.pad(chose, ((0, C * c - T), (0, 0))).reshape(C, c, E1)
    i, j = jnp.arange(c), jnp.arange(C)
    inside = jnp.einsum(
        "ij,cje->cie", (i[None, :] <= i[:, None]).astype(jnp.bfloat16),
        chunks.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    before = jnp.sum(jnp.where((j[None, :] < j[:, None])[..., None],
                               inside[None, :, -1], 0), axis=1)   # [C, E1]
    return (inside + before[:, None]).reshape(C * c, E1)[:T]


@jax.named_scope("tables")
def _tables(topi, valid, Eh: int, bm: int, nb_max: int):
    """Where each assignment goes in the padded dispatch buffer, by
    counting: topi [T, K], a token's K experts all different as a top-k
    gives them (one at or past ``Eh`` is not held), valid [T] bool or
    None -> (assignments a held expert [Eh], blocks that hold rows, each
    block's expert [nb_max], dest [T, K]: the assignment's row of the
    buffer, at or past ``nb_max * bm`` where it is not dispatched).

    An assignment's key is its held expert (``Eh``: none), and its row is
    its expert's offset, a multiple of ``bm``, plus the earlier tokens
    that chose the same: what a stable sort by key gives, with no sort
    and no gather from a table. ``offset[key]`` and ``seen[t, key]`` are
    picked by the key's one-hot row inside one reduction."""
    held = topi < Eh
    if valid is not None:
        held = held & valid[:, None]
    key = jnp.where(held, topi, Eh).astype(jnp.int32)      # [T, K]
    onehot = key[..., None] == jnp.arange(Eh + 1, dtype=jnp.int32)
    seen = _counted(jnp.any(onehot, axis=1))               # [T, Eh + 1]
    counts = seen[-1, :Eh]
    nblk = jax.lax.div(counts + (bm - 1), bm)              # none negative
    e = jnp.arange(Eh)
    # a running sum as a masked [Eh, Eh] reduction: it fuses with what
    # reads it, where a cumsum is a reduce-window and a copy of their own
    blk_end = jnp.sum(jnp.where(e[None, :] <= e[:, None], nblk[None, :], 0),
                      axis=1)
    pad_start = (blk_end - nblk) * bm                      # buffer offset
    n_blocks = jnp.sum(nblk)
    # a block's expert: the experts that end at or before it, and a block
    # past the last expert's end is the last expert's
    blk_expert = jnp.sum(
        blk_end[None, :-1] <= jnp.arange(nb_max)[:, None],
        axis=1).astype(jnp.int32)                          # [nb_max]
    # the key that is no expert starts past the buffer's end
    offset = jnp.concatenate(
        [pad_start, jnp.full((1,), nb_max * bm, jnp.int32)])
    dest = jnp.sum(jnp.where(onehot, (seen - 1 + offset)[:, None], 0),
                   axis=2)
    return counts, n_blocks, blk_expert, dest


@jax.named_scope("fill")
def _fill(hf, dest, valid, rows: int):
    """The dispatch buffer [rows, D]: row ``dest[t, k]`` is token t's
    ``hf[t]``, every other row zeros. A 0/1 matrix [rows, T] times ``hf``
    on the matrix unit: one 1 a row at most and float32 accumulation, so
    a row arrives bit for bit (a row that is no token is zeroed first:
    whatever an idle slot holds meets only zeros)."""
    r = jnp.arange(rows, dtype=jnp.int32)
    put = jnp.any(dest[None] == r[:, None, None], axis=2)  # [rows, T]
    if valid is not None:
        hf = jnp.where(valid[:, None], hf, 0)
    return jnp.dot(put.astype(hf.dtype), hf,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(hf.dtype)


@jax.named_scope("moe/experts")
def _experts(hf, topi, w, stacks, li, cfg: ModelConfig, valid=None):
    """Sum over the HELD experts each token chose, weighted.

    hf [T, D]: what the experts read, D the dispatch's width (the
    model's, or a latent's: ``expert_width``); topi/w [T, k] from
    ``route``; stacks: the routed stack's expert weights WHOLE,
    [Ls, Eh, ...] (with ``w_gate``: SwiGLU; without: relu^2), and ``li``
    the layer's index in
    them (a block's matmul reads expert (li, e) in place; handed the
    layer's slice, the layer loop copies all Eh experts out of the stack
    every layer, every step: 18.7 of a 36.5 ms step, PERF.md Findings
    PR 28); valid [T] bool: rows that are tokens (padding and idle slots
    are not dispatched). Returns (y [T, D], assignments a held expert
    [Eh] int32, blocks run: int32 scalar).

    The assignments stand in a padded buffer by expert, in the order
    they come within one (``_tables``: counted, not sorted; absent
    experts and invalid rows nowhere); expert e's rows start at a
    multiple of ``block``, so every block of it is one expert's; the
    blocks that hold rows run one expert each, in one kernel
    (``ops.moe_experts.expert_blocks_stacked``) or, where that cannot
    run (``experts_on_kernel``), a while loop of the same arithmetic."""
    bm, rows = expert_dispatch(cfg, hf.shape[0])
    counts, n_blocks, blk_expert, dest = _tables(topi, valid, n_held(cfg),
                                                 bm, rows // bm)
    xs = _fill(hf, dest, valid, rows)
    if experts_on_kernel(cfg, hf.dtype):
        out = _blocks_kernel(xs, blk_expert, n_blocks, stacks, li, bm)
    else:
        out = _blocks_loop(xs, blk_expert, n_blocks, stacks, li, bm)
    # an assignment that was not dispatched reads the last row, times 0
    y = out[jnp.minimum(dest, rows - 1)].astype(jnp.float32) \
        * jnp.where(dest < rows, w, 0.0)[..., None]
    return jnp.sum(y, axis=1).astype(hf.dtype), counts, n_blocks


def moe_ffn(h, lw, cfg: ModelConfig, valid=None):
    """The routed feed-forward of one layer: h [B, S, D] ->
    (y [B, S, D], assignments a held expert [Eh]). ``lw["experts"]`` is
    (the expert stacks whole, this layer's index in them). The router
    reads the full width; where the layer has a latent
    (``w_latent_down``/``w_latent_up``) the experts read it, projected
    down once a token before the dispatch, and their weighted sum is
    projected up once a token after it."""
    B, S, D = h.shape
    hf = h.reshape(B * S, D)
    topi, w = route(hf, lw["router"], lw["router_bias"], cfg)
    xe = hf
    if "w_latent_down" in lw:
        with jax.named_scope("moe/latent_down"):
            xe = qmatmul(hf, lw["w_latent_down"])
    y, counts, _ = _experts(xe, topi, w, *lw["experts"], cfg,
                            None if valid is None else valid.reshape(B * S))
    if "w_latent_up" in lw:
        with jax.named_scope("moe/latent_up"):
            y = qmatmul(y, lw["w_latent_up"])
    if "ws_up" in lw:     # n_shared_experts 0: no leaves, nothing added
        with jax.named_scope("moe/shared"):
            y = y + (_swiglu(hf, lw["ws_gate"], lw["ws_up"], lw["ws_down"])
                     if "ws_gate" in lw
                     else _relu2(hf, lw["ws_up"], lw["ws_down"]))
    return y.reshape(B, S, D), counts


def dense_ffn(h, lw, cfg: ModelConfig, valid=None):
    with jax.named_scope("dense_mlp"):
        return _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"]), None


# -- attention -----------------------------------------------------------------

def softmax_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 \
        * yarn_softmax_scale(cfg.rope_scaling)


def _split_kvb(w_kvb, cfg: ModelConfig):
    """``W_kvb`` [rank, H * (dn + dv)] a head: (W_UK [rank, H, dn], its
    output-channel scale [H, dn] or None, W_UV [rank, H, dv], scale)."""
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    if isinstance(w_kvb, QuantizedLinear):
        w = w_kvb.w.reshape(-1, H, dn + dv)
        s = w_kvb.scale.reshape(H, dn + dv)
        return w[..., :dn], s[:, :dn], w[..., dn:], s[:, dn:]
    w = w_kvb.reshape(-1, H, dn + dv)
    return w[..., :dn], None, w[..., dn:], None


@jax.named_scope("mla/q_absorb")
def _absorb(q, w_kvb, cfg: ModelConfig):
    """q [B, S, H, dn + dr] (scaled) -> q_cat [B, S, H, stored_width]:
    ``[q_nope W_UK^T | q_pe | 0]``. An int8 ``W_UK``'s output-channel
    scale folds into ``q_nope``."""
    dn = cfg.qk_nope_head_dim
    w_uk, s_uk, _, _ = _split_kvb(w_kvb, cfg)
    q_nope = q[..., :dn]
    if s_uk is not None:
        q_nope = (q_nope.astype(jnp.float32) * s_uk).astype(q.dtype)
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk.astype(q.dtype),
                       preferred_element_type=jnp.float32).astype(q.dtype)
    pad = stored_width(cfg) - row_width(cfg)
    return jnp.pad(jnp.concatenate([q_abs, q[..., dn:]], -1),
                   ((0, 0), (0, 0), (0, 0), (0, pad)))


def _unabsorb(o_lat, w_kvb, cfg: ModelConfig, dtype):
    """o_lat [B, S, H, rank] -> [B, S, H, dv]: through ``W_UV``, whose
    int8 output-channel scale folds into the result."""
    _, _, w_uv, s_uv = _split_kvb(w_kvb, cfg)
    o = jnp.einsum("bshr,rhd->bshd", o_lat.astype(dtype), w_uv.astype(dtype),
                   preferred_element_type=jnp.float32)
    if s_uv is not None:
        o = o * s_uv
    return o.astype(dtype)


def _expand(row, w_kvb, cfg: ModelConfig):
    """Keys and values a head over the chunk's own rows [B, S, width]:
    (k_nope [B, S, H, dn], k_pe [B, S, dr], v [B, S, H, dv])."""
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    B, S = row.shape[:2]
    kv = qmatmul(row[..., :R], w_kvb).reshape(B, S, cfg.n_heads, -1)
    return kv[..., :dn], row[..., R:], kv[..., dn:]


def _layer(x, lw, cfg: ModelConfig, cos, sin, positions, attend, ffn,
           valid=None):
    """One block. ``attend(q, row, w_kvb) -> [B, S, H, dv]``: q is rotated
    and scaled, row is this call's ``[c_kv | k_pe]`` [B, S, row_width].
    Returns (x, row padded to the stored width, the ffn's counts)."""
    B, S = x.shape[:2]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla/q_proj"):
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        c_q = rms_norm(qmatmul(h, lw["w_qa"]), lw["q_norm"], cfg.norm_eps)
        q = qmatmul(c_q, lw["w_qb"]).reshape(B, S, H, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], cos, sin, positions)], -1)
        q = (q.astype(jnp.float32) * softmax_scale(cfg)).astype(x.dtype)
        kva = qmatmul(h, lw["w_kva"])
        c_kv = rms_norm(kva[..., :R], lw["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(kva[..., None, R:], cos, sin, positions)[..., 0, :]
        row = jnp.concatenate([c_kv, k_pe], -1)
    o = attend(q, row, lw["w_kvb"])
    with jax.named_scope("attn_out"):
        x = x + qmatmul(o.reshape(B, S, H * cfg.v_head_dim), lw["wo"])
    h = rms_norm(x, lw["ffn_norm"], cfg.norm_eps)
    y, counts = ffn(h, lw, cfg, valid)
    pad = stored_width(cfg) - row_width(cfg)
    return x + y, jnp.pad(row, ((0, 0), (0, 0), (0, pad))), counts


def _two_stacks(params, cfg: ModelConfig, x, body, per_layer):
    """Scan the dense stack, then the routed one. ``body(x, lw, extra,
    ffn) -> (x, ys)``; ``per_layer``: a pytree of [L, ...] arrays sliced
    a layer beside the weights (the cache, the layer index). Returns (x,
    the dense stack's ys, the routed stack's ys)."""
    nd = cfg.n_dense_layers

    def run(x, stack, lo, hi, ffn):
        extra = jax.tree_util.tree_map(lambda a: a[lo:hi], per_layer)
        # the expert stacks stay whole beside the scan (``_experts``)
        whole = {k: v for k, v in params[stack].items()
                 if k in EXPERT_STACKS and ffn is moe_ffn}
        sliced = {k: v for k, v in params[stack].items() if k not in whole}

        def step(x, xs):
            lw, ex, i = xs
            if whole:
                lw = {**lw, "experts": (whole, i)}
            return body(x, lw, ex, ffn)

        return jax.lax.scan(step, x, (sliced, extra,
                                      jnp.arange(hi - lo, dtype=jnp.int32)))

    x, ys_d = run(x, "dense_layers", 0, nd, dense_ffn)
    x, ys_s = run(x, "layers", nd, cfg.n_layers, moe_ffn)
    return x, ys_d, ys_s


def prefill_kv(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
               lengths: jnp.ndarray | None = None,
               rope_max: int | None = None, rope_tables=None,
               flash: bool = False, adapter=None,
               logit_pos: jnp.ndarray | None = None, mesh=None):
    """Causal forward over [B, S] tokens (right-padded), attention
    expanded. Returns (logits [B, S, V] float32, or [B, 1, V] with
    ``logit_pos``; rows [L, B, S, stored_width]; lengths [B])."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    cos, sin = rope_tables or get_rope_tables(cfg, rope_max or S)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < lengths[:, None]

    def attend(q, row, w_kvb):
        k_nope, k_pe, v = _expand(row, w_kvb, cfg)
        return mla.prefill_attention(q, k_nope, k_pe, v, mask=valid)

    def body(x, lw, _, ffn):
        x, row, _ = _layer(x, lw, cfg, cos, sin, positions, attend, ffn,
                           valid)
        return x, row

    with jax.named_scope("embed"):
        x = params["embedding"][tokens].astype(cfg.jdtype)
    x, rows_d, rows_s = _two_stacks(params, cfg, x, body, None)
    if logit_pos is not None:
        x = jnp.take_along_axis(x, logit_pos[:, None, None]
                                .astype(jnp.int32), axis=1)
    return (_logits(params, cfg, x), jnp.concatenate([rows_d, rows_s]),
            lengths)


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            logit_pos: jnp.ndarray | None = None):
    """Cache-free forward -> [B, S, V] float32 logits (``score``)."""
    return prefill_kv(params, cfg, tokens, lengths, logit_pos=logit_pos)[0]


@jax.named_scope("kv_write")
def write_kv(cache: LatentCache, rows, index, lengths) -> LatentCache:
    """Write row stacks [L, B', S', stored_width] at ``index`` (start
    indices, one an axis); the cache with ``lengths`` replaced."""
    return LatentCache(jax.lax.dynamic_update_slice(
        cache.rows, rows.astype(cache.rows.dtype), index), lengths)


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  cache: LatentCache, start, rope_tables=None,
                  compute_logits: bool = True, adapter=None,
                  logit_pos: jnp.ndarray | None = None, mesh=None):
    """A chunk of C prompt tokens at [start, start + C) against the
    growing cache: absorbed over the rows before it, expanded within
    itself. ``cache.lengths`` is not advanced (llama.prefill_chunk's
    contract). Returns (logits or None, the cache with the rows
    written)."""
    B, C = tokens.shape
    cos, sin = rope_tables or get_rope_tables(cfg, cache.rows.shape[2])
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    R = cfg.kv_lora_rank

    def body(x, lw, layer_rows, ffn):
        def attend(q, row, w_kvb):
            k_nope, k_pe, v = _expand(row, w_kvb, cfg)
            o_lat, o_new = mla.chunk_attention(
                _absorb(q, w_kvb, cfg), q, layer_rows, start, k_nope, k_pe,
                v, R)
            return _unabsorb(o_lat, w_kvb, cfg, q.dtype) + o_new

        x, row, _ = _layer(x, lw, cfg, cos, sin, positions, attend, ffn)
        return x, row

    with jax.named_scope("embed"):
        x = params["embedding"][tokens].astype(cfg.jdtype)
    x, rows_d, rows_s = _two_stacks(params, cfg, x, body, cache.rows)
    cache = write_kv(cache, jnp.concatenate([rows_d, rows_s]),
                     (0, 0, start, 0), cache.lengths)
    if not compute_logits:
        return None, cache
    if logit_pos is not None:
        x = jnp.take_along_axis(x, logit_pos[:, None, None]
                                .astype(jnp.int32), axis=1)
    return _logits(params, cfg, x), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: LatentCache, rope_tables=None, adapter=None,
                mesh=None, active: jnp.ndarray | None = None):
    """One decode step for tokens [B]: absorbed attention over each
    slot's live rows, the step's rows written by one scatter after the
    layer loops (llama.decode_step's discipline and capacity contract).

    ``active`` [B] bool: slots that are decoding; attention reads no row
    of the others and the expert layer dispatches none of their tokens.
    Returns (logits [B, V] float32, the cache with lengths + 1, the
    assignments a routed layer a held expert [Ls, Eh] int32)."""
    B = tokens.shape[0]
    cos, sin = rope_tables or get_rope_tables(cfg, cache.rows.shape[2])
    lengths = cache.lengths
    positions = lengths[:, None]
    live = lengths if active is None else jnp.where(active, lengths, 0)
    valid = None if active is None else active[:, None]
    block_s = mla.decode_block(cache.rows, cfg.kv_lora_rank)

    def body(x, lw, li, ffn):
        def attend(q, row, w_kvb):
            pad = stored_width(cfg) - row_width(cfg)
            o_lat = mla.decode_attention(
                _absorb(q, w_kvb, cfg)[:, 0], cache.rows,
                jnp.pad(row[:, 0], ((0, 0), (0, pad))), live, li,
                rank=cfg.kv_lora_rank, block_s=block_s)
            return _unabsorb(o_lat[:, None], w_kvb, cfg, q.dtype)

        x, row, counts = _layer(x, lw, cfg, cos, sin, positions, attend,
                                ffn, valid)
        return x, (row[:, 0], counts)

    with jax.named_scope("embed"):
        x = params["embedding"][tokens[:, None]].astype(cfg.jdtype)
    x, (rows_d, _), (rows_s, counts) = _two_stacks(
        params, cfg, x, body, jnp.arange(cfg.n_layers, dtype=jnp.int32))
    with jax.named_scope("kv_write"):
        # one update a (layer, slot) with the row as its window: with the
        # layer axis in the window too (``.at[:, slots, lengths]``) the
        # scatter wants layers next to lanes, and XLA converts the whole
        # cache to that layout and back, every step (two 3 GB copies at
        # 9 x 128 x 2,048 x 640; PERF.md, Findings PR 28)
        rows = jnp.concatenate([rows_d, rows_s]).astype(cache.rows.dtype)
        new = LatentCache(
            cache.rows.at[jnp.arange(cfg.n_layers)[:, None],
                          jnp.arange(B)[None, :],
                          lengths[None, :]].set(rows, mode="drop"),
            lengths + 1)
    return _logits(params, cfg, x[:, 0]), new, counts
