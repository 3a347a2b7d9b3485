"""The routed feed-forward of the sparse families: one module that no
family owns. The latent, hybrid, window, conv and state-space families
run all of it (``moe_ffn``); the llama family keeps its own router
(softmax, ``llama._route``) and hands its prompt programs past
``DENSE_TOKENS`` tokens to the expert dispatch here (``experts``), each
chip on its slice of ``F``; its decode block, its small buckets and the
trainer stay on ``models/llama.py``'s dense dispatch.

By equation, ``h`` the normed residual stream: ``s = sigmoid(h W_g)`` in
float32, selection by ``s + bias`` limited to the ``topk_groups`` best
of ``n_expert_groups`` groups (a group's score: its two largest), top
``experts_per_token`` inside them, weights ``s_i / sum s_j *
routed_scaling`` (the bias selects, it does not weigh), plus the shared
experts always on. An expert is a SwiGLU (three matrices) or a
two-matrix relu^2 (``expert_act``), on the model's width or on a latent
behind one projection down and one up a token (``moe_latent_dim``).

The chip's share: a layer holds experts ``0 .. n_experts_held-1`` of the
``n_experts`` the router scores, routes exactly as published and sums
over the HELD experts a token chose; what the absent ones would add is
left out, and that partial sum goes on. Nothing stands in for the other
chips.

Expert dispatch (``experts``): the (token, held expert) assignments
stand expert by expert in row blocks of ``block`` rows, each block one
expert's (their rows counted, not sorted: ``tables``; the buffer filled
by a 0/1 matmul: ``_fill``), and the blocks THAT EXIST run one expert
each (one kernel over them, ``ops/moe_experts.py``, or a loop where that
cannot run), so FLOPs follow the assignments. No capacity, no token
dropped, and a token's result does not depend on what else is in the
batch: a block's rows are independent rows of one matmul.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import moe_experts
from ..ops.flash import interpret_env
from ..ops.quant import QuantizedLinear, qmatmul
from .common import ModelConfig, dense_init

# the leaves of a routed expert [Ls, Eh, ...]: all three a SwiGLU, the
# last two a two-matrix relu^2 expert (``expert_stacks``)
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def n_held(cfg: ModelConfig) -> int:
    return cfg.n_experts_held or cfg.n_experts


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


@jax.named_scope("moe/route")
def route(hf, router, bias, cfg: ModelConfig):
    """hf [T, D] -> (expert ids [T, k], weights [T, k] float32), over all
    ``n_experts`` as published: float32 sigmoid scores (softmax where
    ``router_score`` says so); ``s + bias`` selects (``bias`` None: the
    scores do; groups by the sum of their two best, then the top k inside
    the kept groups); the weights are the unbiased scores, renormalised
    and scaled."""
    T = hf.shape[0]
    E, G = cfg.n_experts, cfg.n_expert_groups
    s = jnp.dot(hf.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    # ``router_score``: a softmax over all the experts (the chosen are
    # renormalised below, as the sigmoid's are), or a sigmoid each
    s = jax.nn.softmax(s, axis=-1) if cfg.router_score == "softmax" \
        else jax.nn.sigmoid(s)
    sel = s if bias is None else s + bias.astype(jnp.float32)
    group = jnp.sum(jax.lax.top_k(sel.reshape(T, G, E // G), 2)[0], -1)
    kept = jnp.sum(jax.nn.one_hot(jax.lax.top_k(group, cfg.topk_groups)[1],
                                  G, dtype=jnp.bool_), axis=1)     # [T, G]
    sel = jnp.where(jnp.repeat(kept, E // G, axis=1), sel, -jnp.inf)
    topi = jax.lax.top_k(sel, cfg.experts_per_token)[1]
    w = jnp.take_along_axis(s, topi, axis=1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * cfg.routed_scaling
    return topi, w


def _swiglu(x, gate, up, down, out_dtype=None):
    return qmatmul(jax.nn.silu(qmatmul(x, gate)) * qmatmul(x, up), down,
                   out_dtype)


def _relu2(x, up, down, out_dtype=None):
    """The two-matrix expert: ``W2 relu(W1 x)^2``, no gate."""
    return qmatmul(jnp.square(jax.nn.relu(qmatmul(x, up))), down, out_dtype)


def expert_width(cfg: ModelConfig) -> int:
    """The width the routed experts read and write, and so the width of
    the dispatch: a latent's where the configuration has one, else the
    model's."""
    return cfg.moe_latent_dim or cfg.dim


def expert_stacks(cfg: ModelConfig) -> tuple[str, ...]:
    """The leaves of one routed expert, by its form."""
    return EXPERT_STACKS if cfg.expert_act == "swiglu" else EXPERT_STACKS[1:]


# tokens up to which a dense dispatch (every expert on every token) costs
# what a routed one does: an int8 weight byte streamed buys the chip about
# 240 FLOPs and a row through it takes 2, so each expert's stream hides
# about 120 rows. Past it a prompt program routes, and its blocks grow
DENSE_TOKENS = 128


def expert_dispatch(cfg: ModelConfig, tokens: int) -> tuple[int, int]:
    """(rows of a dispatch block, rows of the padded dispatch buffer) for
    ``tokens`` tokens. A block is one bfloat16 sublane tile for a decode
    batch (a few tokens an expert) and 64 rows where a prompt brings
    many. It is ``DENSE_TOKENS`` rows where an expert's assignments
    (``tokens * k / n_experts`` on average) are half such a block or
    more: a loop turn pays for its expert whole whatever rows it holds
    (on a v5e about 65 us for 44 MB of int8 beside 0.42 us a row), so an
    expert's rows should stand in few blocks. At eight experts that is a
    prompt of 256 tokens or more (a layer's experts alone, one chip's
    quarter: 0.81 ms at 256 tokens where 64 rows take 1.00 and the dense
    dispatch 1.17). At 64 experts and more, whose experts get 13-32 of a
    512-token chunk and fit one block of 64, no prompt. Blocks cut to
    five quarters of the average (80 and 160 rows) were faster still
    with a router that spreads its tokens evenly and no faster or slower
    in the served model, whose router does not (PERF.md, Findings PR
    54). The buffer holds at most min(k, held) held assignments a token
    and less than a block of padding an expert."""
    if tokens <= DENSE_TOKENS:
        bm = 16
    elif 2 * tokens * cfg.experts_per_token < DENSE_TOKENS * cfg.n_experts:
        bm = 64
    else:
        bm = DENSE_TOKENS
    Eh = n_held(cfg)
    nb_max = (tokens * min(cfg.experts_per_token, Eh)
              + Eh * (bm - 1) + bm - 1) // bm
    return bm, nb_max * bm


def experts_on_kernel(cfg: ModelConfig, dtype=None) -> bool:
    """Whether ``experts`` runs its blocks through the kernel of
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
    how the dispatch tables are built (``tables``)."""
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


def blocks_loop(xs, blk_expert, n_blocks, stacks, li, bm: int,
                out_dtype=None):
    """The dispatch buffer's live blocks through their experts, one loop
    turn a block: xs [rows, D] -> [rows, D], zero past ``n_blocks``, in
    ``out_dtype`` (xs' own unless given: a float32 result is the down
    product as it was accumulated and scaled, not rounded)."""
    def one(a, e):  # a [Ls, Eh, ...] -> a[li, e]
        return jax.lax.dynamic_index_in_dim(
            a.reshape((-1,) + a.shape[2:]), li * a.shape[1] + e, 0,
            keepdims=False)

    def at(leaf, e):
        if isinstance(leaf, QuantizedLinear):
            return QuantizedLinear(one(leaf.w, e), one(leaf.scale, e))
        return one(leaf, e)

    # a caller that asks for no type calls the expert as it always did
    asked = {} if out_dtype is None else {"out_dtype": out_dtype}

    def body(j, out):
        e = blk_expert[j]
        x = jax.lax.dynamic_slice_in_dim(xs, j * bm, bm, axis=0)
        up, down = at(stacks["w_up"], e), at(stacks["w_down"], e)
        y = _swiglu(x, at(stacks["w_gate"], e), up, down, **asked) \
            if "w_gate" in stacks else _relu2(x, up, down, **asked)
        return jax.lax.dynamic_update_slice_in_dim(out, y, j * bm, axis=0)

    return jax.lax.fori_loop(0, n_blocks, body,
                             jnp.zeros_like(xs, dtype=out_dtype))


def blocks_kernel(xs, blk_expert, n_blocks, stacks, li, bm: int,
                   tile: int | None = None):
    """``blocks_loop`` as one kernel over the blocks that exist (``tile``:
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
def tables(topi, valid, Eh: int, bm: int, nb_max: int):
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
def experts(hf, topi, w, stacks, li, cfg: ModelConfig, valid=None,
            out_dtype=None):
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
    [Eh] int32, blocks run: int32 scalar). ``out_dtype``: the result's
    type where it is not ``hf``'s. float32 is for a caller that holds a
    slice of ``F`` and adds its result to other chips' before it rounds
    (``llama._routed_experts``): the blocks' down products stay float32
    through the weights and the sum over k, on the loop (the kernel
    writes the activations' type).

    The assignments stand in a padded buffer by expert, in the order
    they come within one (``tables``: counted, not sorted; absent
    experts and invalid rows nowhere); expert e's rows start at a
    multiple of ``block``, so every block of it is one expert's; the
    blocks that hold rows run one expert each, in one kernel
    (``ops.moe_experts.expert_blocks_stacked``) or, where that cannot
    run (``experts_on_kernel``), a while loop of the same arithmetic."""
    bm, rows = expert_dispatch(cfg, hf.shape[0])
    counts, n_blocks, blk_expert, dest = tables(topi, valid, n_held(cfg),
                                                 bm, rows // bm)
    xs = _fill(hf, dest, valid, rows)
    if out_dtype is None and experts_on_kernel(cfg, hf.dtype):
        out = blocks_kernel(xs, blk_expert, n_blocks, stacks, li, bm)
    else:
        out = blocks_loop(xs, blk_expert, n_blocks, stacks, li, bm,
                          out_dtype)
    # an assignment that was not dispatched reads the last row, times 0
    y = out[jnp.minimum(dest, rows - 1)].astype(jnp.float32) \
        * jnp.where(dest < rows, w, 0.0)[..., None]
    return jnp.sum(y, axis=1).astype(out_dtype or hf.dtype), counts, n_blocks


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
    topi, w = route(hf, lw["router"], lw.get("router_bias"), cfg)
    xe = hf
    if "w_latent_down" in lw:
        with jax.named_scope("moe/latent_down"):
            xe = qmatmul(hf, lw["w_latent_down"])
    y, counts, _ = experts(xe, topi, w, *lw["experts"], cfg,
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
