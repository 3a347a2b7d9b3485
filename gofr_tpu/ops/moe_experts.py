"""Pallas TPU kernel for the routed experts: one expert a dispatch block
(SwiGLU of three matrices, or the two-matrix ``W2 relu(W1 x)^2`` where
the stacks hold no gate), over the blocks that exist, the next block's
weights in flight while this one multiplies.

``models.moe.experts`` puts a step's (token, held expert)
assignments, expert by expert and in the order they come within one
(their places counted, not sorted: ``moe.tables``), into a
padded buffer ``xs [rows, D]`` of ``block_rows``-row
blocks, each one expert's (``blk_expert [rows / block_rows]``), of which
the first ``n_blocks`` hold rows. Its jnp form runs a ``fori_loop`` of
one turn a block: a slice of ``xs``, three slices of the weight stacks
and three of their scale vectors, three matmuls and an update of the
output buffer, one after another with no fetch under any of them. Where
an expert is small that turn, not its bytes, is the time: Laguna-XS.2's
3.15 MB expert streams in 3.8 us and a turn took 7.7 (14.8 ms of a
25.1 ms step, PERF.md Findings PR 36).

Here the grid is (blocks of the buffer, tiles of the expert width F).
``blk_expert``, ``n_blocks`` and the layer index are scalar-prefetched
and the index maps of ``w_gate``, ``w_up``, ``w_down`` and their scales
pick ``(layer, blk_expert[j], tile)`` in the WHOLE stacks ``[Ls, Eh,
...]`` (handed a layer's slice, XLA copies the layer's experts out of
the stack first: 5.6 GB a step, PERF.md Findings PR 28), so Pallas'
pipeline fetches block j+1's tiles while block j multiplies, and where
one tile is the whole expert two blocks of one expert fetch it once. A
block at or past ``n_blocks`` maps to the last live block's tiles (no
fetch), runs nothing and writes zeros: an assignment that is not held
still gathers a row of the output and multiplies it by 0.

Inside: the int8 tile is unpacked to the activations' type for the
matrix unit (in slabs of 256 to 1,024 input rows or whole: the same
time on a v5e, PERF.md Findings PR 37), float32 accumulation, the
per-output-channel scale after the contraction, ``silu(g) * u`` in
float32 rounded once for the down matmul, the down product accumulated
in float32 over the F tiles, scaled and rounded once: no rounding point
coarser than ``qmatmul``'s three.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# bytes the double-buffered weight tiles of one grid step may take: a
# whole expert of Laguna-XS.2 (2 x 3.15 MB), tiles of 640 and 256
# columns of Solar-Open2's and GigaChat3.1's (PERF.md, Findings PR 37,
# the kernel alone)
_TILE_BUDGET = 16 << 20
# weights of one expert (3 x dim x ffn) up to which a block runs here:
# the kernel unpacks int8 to bfloat16 on the vector unit at about the
# rate XLA's fused convert-and-matmul does, so what it wins is the loop
# turn's fixed cost, and an expert large enough hides that: alone on a
# v5e the kernel takes 0.49 / 0.82 / 1.09 of the loop's time at 3.1M /
# 15.7M / 44M weights (PERF.md, Findings PR 37, the kernel alone)
_MAX_EXPERT_WEIGHTS = 32 << 20


def tile_columns(dim: int, ffn: int, itemsize: int, stacks: int = 3) -> int:
    """Columns of the expert width F a grid step takes: the largest
    divisor of ``ffn`` in whole lanes whose ``stacks`` (three, or two
    without a gate) double-buffered tiles fit ``_TILE_BUDGET`` (at least
    one lane group)."""
    for n in range(1, ffn // _LANES + 1):
        if ffn % n or (ffn // n) % _LANES:
            continue
        if 2 * stacks * dim * (ffn // n) * itemsize <= _TILE_BUDGET:
            return ffn // n
    return _LANES


def kernel_ok(dim: int, ffn: int, dtype, stacks: int = 3) -> bool:
    """Whether the expert blocks dispatched ``dim`` wide (the model's
    width, or a latent's) with experts of ``stacks`` matrices ``ffn``
    wide and activations of ``dtype`` run the kernel: on a TPU,
    where both widths are whole lanes, the activations bfloat16 (a
    16-row block is one bfloat16 sublane tile; a float32 test model
    stays on the loop) and an expert no larger than the loop turn's
    fixed cost is worth (``_MAX_EXPERT_WEIGHTS``).
    ``GOFR_FLASH_INTERPRET=1`` runs it interpreted on any backend and
    shape, one tile an expert."""
    from .flash import interpret_env, tpu_backend_ok

    if interpret_env():
        return True
    return (dim % _LANES == 0 and ffn % _LANES == 0
            and stacks * dim * ffn <= _MAX_EXPERT_WEIGHTS
            and jnp.dtype(dtype) == jnp.bfloat16 and tpu_backend_ok())


def _scale_row(ref, row):
    """Row ``row`` of a [group, n] float32 block as [1, n]."""
    rows = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
    return jnp.sum(jnp.where(rows == row, ref[...], 0.0), axis=0,
                   keepdims=True)


def _dot(x, w_ref):
    """x [bm, K] @ w_ref [K, N] -> float32 [bm, N], the weight unpacked
    to x's type on the way (a plain weight passes unchanged)."""
    return jnp.dot(x, w_ref[...].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def _experts_kernel(layer_ref, n_ref, expert_ref, x_ref, *rest, gated: bool,
                    quant: bool, group: int, n_tiles: int):
    """One (block, F tile) step: this tile's share of the block's expert
    (``gated``: SwiGLU; else relu(W1 x)^2 into W2)."""
    del layer_ref                        # the index maps read it
    n_w = 3 if gated else 2
    *w_refs, wd_ref = rest[:n_w]
    if quant:
        *s_refs, sd_ref = rest[n_w:2 * n_w]
    o_ref, acc_ref = rest[-2:]
    j, f = pl.program_id(0), pl.program_id(1)
    live = j < n_ref[0]

    @pl.when(live)
    def _run():
        x = x_ref[...]
        ins = [_dot(x, w_ref) for w_ref in w_refs]
        if quant:
            row = expert_ref[j] % group     # in the fetched group of scales
            ins = [a * _scale_row(s_ref, row)
                   for a, s_ref in zip(ins, s_refs)]
        if gated:
            g, u = ins
            h = g * jax.nn.sigmoid(g) * u
        else:
            h = jnp.square(jnp.maximum(ins[0], 0.0))
        d = _dot(h.astype(x.dtype), wd_ref)

        def finish(total):
            if quant:
                total = total * _scale_row(sd_ref, row)
            o_ref[...] = total.astype(o_ref.dtype)

        if n_tiles == 1:
            finish(d)
        else:
            @pl.when(f == 0)
            def _first():
                acc_ref[...] = d

            @pl.when(f > 0)
            def _more():
                acc_ref[...] += d

            @pl.when(f == n_tiles - 1)
            def _last():
                finish(acc_ref[...])

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "tile", "interpret"))
def expert_blocks_stacked(xs, blk_expert, n_blocks, layer, w_gate, w_up,
                          w_down, s_gate=None, s_up=None, s_down=None, *,
                          block_rows: int, tile: int | None = None,
                          interpret: bool = False):
    """Every live block of the dispatch buffer through its expert of
    layer ``layer`` of the stacked weights: SwiGLU, or with ``w_gate``
    (and ``s_gate``) None the two-matrix ``w_down relu(w_up x)^2``.

    xs: [rows, D], ``rows`` whole blocks of ``block_rows``; blk_expert:
    [rows / block_rows] int32, the expert of each block (any held id for
    a block that is not live); n_blocks: int32 scalar, blocks that hold
    rows; layer: int32 scalar; w_gate/w_up: [Ls, Eh, D, F], w_down:
    [Ls, Eh, F, D], int8 with float32 scales [Ls, Eh, F] / [Ls, Eh, D]
    or plain; tile: columns of F a grid step takes (``tile_columns``).
    Returns [rows, D] in xs' dtype, zero in every block that is not
    live."""
    rows, dim = xs.shape
    n_held, _, ffn = w_up.shape[1:]
    gated, quant = w_gate is not None, s_up is not None
    w_in = [w_gate, w_up] if gated else [w_up]
    s_in = [s_gate, s_up] if gated else [s_up]
    tile = tile or (ffn if interpret else
                    tile_columns(dim, ffn, w_up.dtype.itemsize,
                                 len(w_in) + 1))
    n_tiles = ffn // tile
    nb = rows // block_rows
    group = _SUBLANES if n_held % _SUBLANES == 0 else n_held

    def block_of(j, n):                 # the last live block for a dead one
        return jnp.maximum(jnp.minimum(j, n[0] - 1), 0)

    def tile_of(j, f, n):
        return jnp.where(j < n[0], f, n_tiles - 1)

    def at_x(j, f, li, n, e):
        return block_of(j, n), 0

    def at_in(j, f, li, n, e):          # w_gate, w_up [Ls, Eh, D, F]
        return li[0], e[block_of(j, n)], 0, tile_of(j, f, n)

    def at_down(j, f, li, n, e):        # w_down [Ls, Eh, F, D]
        return li[0], e[block_of(j, n)], tile_of(j, f, n), 0

    def at_s_in(j, f, li, n, e):        # scales [Ls, Eh, F] a group of experts
        return li[0], e[block_of(j, n)] // group, tile_of(j, f, n)

    def at_s_down(j, f, li, n, e):
        return li[0], e[block_of(j, n)] // group, 0

    in_specs = [pl.BlockSpec((block_rows, dim), at_x)] \
        + [pl.BlockSpec((None, None, dim, tile), at_in)] * len(w_in) \
        + [pl.BlockSpec((None, None, tile, dim), at_down)]
    operands = [xs, *w_in, w_down]
    if quant:
        in_specs += [pl.BlockSpec((None, group, tile), at_s_in)] * len(s_in) \
            + [pl.BlockSpec((None, group, dim), at_s_down)]
        operands += [*s_in, s_down]
    return pl.pallas_call(
        functools.partial(_experts_kernel, gated=gated, quant=quant,
                          group=group, n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(nb, n_tiles), in_specs=in_specs,
            out_specs=pl.BlockSpec((block_rows, dim),
                                   lambda j, f, li, n, e: (j, 0)),
            scratch_shapes=[pltpu.VMEM((block_rows, dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, dim), xs.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(n_blocks, (1,)).astype(jnp.int32),
      blk_expert.astype(jnp.int32), *operands)
