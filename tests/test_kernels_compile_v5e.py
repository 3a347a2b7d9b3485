"""The cache's two kernels, the delta rule's two (ops/kda.py) and, at the
end, the llama family's decode block and prefill, the window family's
decode block and chunk program and the conv family's decode block and
prefill with the weights' reads in them,
compiled for a TPU v5e that is described, not
attached (libtpu's compile-only topology; no chip time, nothing runs):
what interpret mode cannot see, Mosaic refusing a slice that is not
whole tiles or a kernel that needs too much VMEM. At Mistral-7B's widths
with the benchmark's 40 slots x 2,048 positions, with eight int8 KV
heads (one chip) and with the two a tp=4 shard of Mixtral is left with:
the shard whose [block, 2, 128] slice of the old [.., Smax, KV, hd]
cache Mosaic refused.

The topology is described inside a fixture and only this file does so:
one process at a time may load the TPU's library, and a worker that
collects this file must not load it while it imports.
"""

import functools
import json
import math
import os
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.ops import flash_decode as fd

L, B, SMAX, D = 32, 40, 2048, 128


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 (no libtpu, or it is held)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # and cannot be read back without the device: keep it out. And the
    # chip runs at JAX's default matmul precision, not the float32 that
    # tests/conftest.py asks of the CPU (Mosaic has no such bfloat16 dot)
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_default_matmul_precision", was[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def tp4(v5e_2x2):
    """The four chips as the mesh ``TPU_SHARDING=tp=4`` makes of them."""
    from gofr_tpu import parallel

    return parallel.make_mesh(tp=4, devices=v5e_2x2.devices)


def _shapes(sharding, kv, dtype):
    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    cache = arr((L, B, kv, SMAX, D), dtype)
    scale = arr((L, B, kv, SMAX), jnp.float32)
    return arr, cache, scale


def _made(text, shape):
    """The opcodes of the instructions of a compiled module's text, its
    fused computations' among them, whose result is an ``f32[shape]``."""
    return set(re.findall(
        rf"^\s*(?:ROOT )?%?[\w.\-]+ = f32\[{shape}\]\S* ([\w\-]+)\(",
        text, re.M))


# what a program that writes the step's scales where they lie holds of
# no scale table: a select over it (until PR 48 the write), a copy, a
# broadcast or a fusion of its shape. (The compiler's own prefetch of a
# table it chose to keep in VMEM across the layer loop, a tp=4 shard's
# 21 MB, is a copy-start and a copy-done and no change of layout.)
_TABLE_MOVES = {"select", "copy", "broadcast", "fusion", "transpose",
                "scatter", "dynamic-update-slice"}


@pytest.mark.parametrize("kv,dtype", [(8, jnp.int8), (2, jnp.int8),
                                      (1, jnp.int8), (8, jnp.bfloat16)])
def test_decode_kernel_compiles(one_chip, kv, dtype):
    arr, cache, scale = _shapes(one_chip, kv, dtype)
    quant = dtype == jnp.int8
    q = arr((B, 1, 4 * kv, D), jnp.bfloat16)
    new = arr((B, 1, kv, D), jnp.bfloat16)
    compiled = fd.flash_decode_stacked.lower(
        q, cache, cache, new, new, arr((B,), jnp.int32), arr((), jnp.int32),
        scale if quant else None, scale if quant else None,
        block_s=fd.block_size(SMAX)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv,dtype,scaled", [
    (8, jnp.int8, False), (2, jnp.int8, False), (1, jnp.int8, False),
    (8, jnp.bfloat16, False), (8, jnp.int8, True), (2, jnp.int8, True)])
def test_append_kernel_compiles_in_place(one_chip, kv, dtype, scaled):
    """And the caches it returns are the caches it was given: donated,
    the program holds no second copy of either. With a quantized cache's
    scale tables (``scaled``) it is four buffers that come back as they
    went in, and a slot's [L, KV, 128] lane tile of a table is whole
    sublane tiles at eight KV heads and a quarter of one at the two a
    tp=4 shard of Mixtral holds: Mosaic takes both, and the rotation of
    the step's scales along lanes by a cursor it reads at run time."""
    arr, cache, scale = _shapes(one_chip, kv, dtype)
    rows = arr((L, B, kv, D), dtype)
    tables = (scale, scale, arr((L, B, kv), jnp.float32),
              arr((L, B, kv), jnp.float32)) if scaled else ()
    compiled = jax.jit(fd.append_rows_stacked,
                       donate_argnums=(0, 1, 5, 6)[:4 if scaled else 2]).lower(
        cache, cache, rows, rows, arr((B,), jnp.int32), *tables).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    cache_bytes = 2 * L * B * kv * SMAX * D * jnp.dtype(dtype).itemsize
    table_bytes = 2 * L * B * kv * SMAX * 4 if scaled else 0
    assert mem.alias_size_in_bytes >= cache_bytes + table_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 8
    # no operation but the kernel makes a table: none is copied or selected
    made = _made(text, f"{L},{B},{kv},{SMAX}")
    assert made <= {"parameter", "get-tuple-element"}
    assert bool(made) == scaled


def test_latent_decode_kernel_compiles(one_chip):
    """ops/mla.py's kernel at gigachat's cell: nine layers of 128 slots x
    2,048 rows stored 640 lanes wide, 64 heads over a latent of 512. Its
    twelve row tiles, four chains a trip and the statistics' scratch
    fit the VMEM it asks for, and the stacked cache is read in place."""
    from gofr_tpu.ops import mla

    def arr(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, width, rank = 128, 640, 512
    rows = arr((9, slots, SMAX, width))
    compiled = mla.decode_attention_stacked.lower(
        arr((slots, 64, width)), rows, arr((slots, width)),
        arr((slots,), jnp.int32), arr((), jnp.int32), rank=rank,
        block_s=fd.block_size(SMAX)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 9 * slots * SMAX * width * 2 // 64


@pytest.mark.parametrize("chunk,heads,table,width,rank", [
    (512, 128, 16384, 640, 512), (512, 64, 512, 1152, 1024),
    (512, 64, 2048, 640, 512), (32, 128, 4096, 640, 512)],
    ids=["full_16k", "ring", "gigachat", "bucket_32"])
def test_latent_chunk_walk_kernel_compiles(one_chip, monkeypatch, chunk,
                                           heads, table, width, rank):
    """ops/mla.py's chunk walk at the shapes its three cells call it in
    (a full layer over a slot of 16,384 rows, a window layer over its
    ring, gigachat's 64 heads over 2,048) and at the smallest final
    chunk: the dispatcher takes each, a tile of 1,024 score rows with
    its scores, probabilities and accumulator fits the VMEM the kernel
    asks for, and the program holds nothing the size of a block's
    float32 scores outside it."""
    from gofr_tpu.ops import flash, mla

    def arr(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    monkeypatch.setattr(flash, "tpu_backend_ok", lambda: True)
    tile = mla.chunk_tile(chunk, heads, table, width, rank, jnp.bfloat16)
    assert tile and tile * heads == 1024
    compiled = mla.chunk_walk_latent.lower(
        arr((1, chunk, heads, width)), arr((1, table, width)),
        arr((1, chunk, table), jnp.bool_), arr((), jnp.int32), rank=rank,
        block=mla.chunk_block(table), tile=tile).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the mask as the kernel reads it, and no [heads, chunk, block] scores
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= chunk * table * 4 + (1 << 20)


# -- the delta rule's kernels (ops/kda.py) at Solar-Open2's head sizes ---------

KDA_L, KDA_B, KDA_H = 6, 128, 64


def test_kda_decode_compiles_in_place(one_chip):
    """64 heads x 128 x 128 float32, 128 slots, 6 linear layers: the
    3.2 GB of states it returns are the ones it was given."""
    from gofr_tpu.ops import kda

    def arr(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    row = arr((KDA_B, KDA_H, D))
    compiled = jax.jit(kda.kda_decode, donate_argnums=(0,)).lower(
        arr((KDA_L, KDA_B, KDA_H, D, D)), arr((), jnp.int32), row, row, row,
        row, arr((KDA_B, KDA_H)), arr((KDA_B,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = KDA_L * KDA_B * KDA_H * D * D * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 64


@pytest.mark.parametrize("tokens", [32, 64, 128, 256, 512])
def test_kda_prefill_compiles(one_chip, tokens):
    """Every engine bucket, the smallest to a whole chunk of one prompt:
    the chunkwise kernel is in the program and fits the VMEM it asks
    for."""
    import re

    from gofr_tpu.ops import kda

    def arr(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    seq = arr((1, tokens, KDA_H, D))
    text = kda.kda_prefill.lower(
        seq, seq, seq, seq, arr((1, tokens, KDA_H)),
        arr((1, KDA_H, D, D))).compile().as_text()
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and "kda_prefill" in line)
    asked, used = (int(re.search(
        key + r'":\[\{"memory_space":"1","offset":"0","size":"(\d+)"',
        call).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert 0 < used < asked == 64 * 1024 * 1024


# -- the llama family's programs: every projection reads its stack in place ----

def _cell_config(name, **cut):
    """``benchmarks/configs/<name>.json``'s ``model_config``, as the cell
    runs it but for ``cut``."""
    from gofr_tpu.models.common import ModelConfig

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           name + ".json")) as f:
        return ModelConfig(**{**json.load(f)["model_config"], **cut})


def _engine_lowered(monkeypatch, sharding, cfg, slots, kv_dtype, program,
                    mesh=None):
    """``EnginePrograms``' own decode block, prefill ("prefill 256") or
    mid-chunk program ("chunk 512") for ``cfg``, lowered from shapes alone
    (nothing is allocated): int8 weights, ``slots`` x 2,048, the kernels
    on (a CPU process answers no to ``tpu_backend_ok``). On ``sharding``,
    or on ``mesh`` with weights and cache sharded as an engine places
    them and the rest replicated."""
    from gofr_tpu import parallel
    from gofr_tpu.models import family
    from gofr_tpu.ops import flash
    from gofr_tpu.tpu import programs
    from gofr_tpu.tpu.checkpoint import maybe_quantize

    monkeypatch.setattr(flash, "tpu_backend_ok", lambda: True)
    fam = family(cfg)
    prog = programs.EnginePrograms(
        cfg, fam, object(), max_seq=SMAX, kv_dtype=kv_dtype,
        decode_block=4, n_adapters=0, spec_k=0, paged=None, mesh=mesh)
    prog.describe("cache", slots)
    jits = prog.build()
    if mesh is not None:
        sharding = prog.placed.rep

    def arr(shape, dt=jnp.int32, sharding=sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def described(build, shardings=None):
        shapes = jax.eval_shape(build)
        if shardings is None:
            shardings = (parallel.shardings_for(shapes, mesh) if mesh
                         else jax.tree_util.tree_map(lambda _: sharding,
                                                     shapes))
        return jax.tree_util.tree_map(
            lambda s, sh: arr(s.shape, s.dtype, sh), shapes, shardings)

    params = described(lambda: maybe_quantize(
        fam.init(cfg, jax.random.PRNGKey(0)), True))
    cache = described(lambda: fam.init_cache(cfg, slots, SMAX,
                                             dtype=kv_dtype),
                      prog.placed.cache)
    key = arr((2,), jnp.uint32)
    if program == "decode block":
        b = arr((slots,))
        # a family whose step is a pass over a block packs the cursor,
        # the given count and the block's tokens, and carries the block
        W = cfg.block_length
        tail = tuple(arr(a.shape, a.dtype) for a in jax.eval_shape(
            lambda: prog.carry_tail(slots)))
        return jits["_step_jit"].lower(
            cache, params,
            arr((slots, programs.PACK_EXTRA + programs.EOS_MAX
                 + (W + 2 if W else 0))),
            (b, arr((slots,), jnp.bool_), b, b, *tail), key)
    if program.startswith("chunk"):
        return jits["_chunk_mid_jit"].lower(
            cache, params, arr((1, int(program.split()[1]))), arr(()),
            arr(()), arr(()), arr(()),
            arr((), jnp.float32), arr(()), key, arr(()), arr(()), None)
    return jits["_prefill_jit"].lower(
        cache, params, arr((1, int(program.split()[1]))), arr(()), arr(()),
        arr((), jnp.float32), arr(()), key, arr(()), arr(()))


def _lowered(monkeypatch, sharding, program, layers):
    """Mistral-7B's widths cut to ``layers`` layers, an int8 cache, 40
    slots."""
    from gofr_tpu.models.common import ModelConfig

    cfg = ModelConfig(name="mistral-7b-cut", vocab_size=32768, dim=4096,
                      n_layers=layers, n_heads=32, n_kv_heads=8,
                      ffn_dim=14336, max_seq=SMAX, rope_theta=1e6)
    return _engine_lowered(monkeypatch, sharding, cfg, B, jnp.int8, program)


@pytest.mark.parametrize("program", ["decode block", "prefill 256"])
def test_qk_projections_read_their_weights_in_place(one_chip, monkeypatch,
                                                    program):
    """``llama.layer``'s barrier, read off the compiled program. Two
    layers show what 32 do: without it the decode block transposes the
    whole wq and wk stacks once a dispatch (a ``copy`` of an int8 stack,
    49 MB of temporaries here, 0.67 GB at 32 layers) and every program
    stages a layer's slice of both in VMEM (``S(1)``) before the matmul
    that should have streamed it (PERF.md, Findings PR 33)."""
    compiled = _lowered(monkeypatch, one_chip, program, layers=2).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (s8\[[\d,]+\]\S*) ([\w\-]+)\(", text, re.M)
    assert results                      # the pattern still reads this HLO
    # (an asynchronous copy-start of a whole stack into VMEM is these two
    # layers fitting there, and no change of layout: 32 layers do not)
    stack_copies = [r for r in results if r[1] in ("copy", "transpose")
                    and r[0].startswith("s8[2,4096,")]
    staged = [r for r in results if r[1] == "fusion"
              and r[0].startswith("s8[1,4096,") and "S(1)" in r[0]]
    assert not stack_copies
    assert not staged
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    if program == "decode block":
        # the step's scales go in through the append kernel's visit: no
        # select, copy or broadcast of a scale table is left
        tables = _made(text, f"2,{B},8,{SMAX}")
        assert tables and not tables & _TABLE_MOVES


# -- Mixtral's shard on the four chips: the experts' one reduction --------------

class _Inst(NamedTuple):
    """An instruction of a compiled module's text: its first output's
    type and dimensions, its opcode, its first operand, its line."""
    dtype: str
    dims: list
    opcode: str
    operand: str
    line: str


def _lowered_tp4(monkeypatch, mesh, program):
    """``benchmarks/configs/mixtral-8x7b-int8-tp4.json`` as its cell runs
    it, cut to two layers: tp=4, an int8 cache, 40 slots."""
    cfg = _cell_config("mixtral-8x7b-int8-tp4", n_layers=2)
    return _engine_lowered(monkeypatch, None, cfg, B, jnp.int8, program,
                           mesh=mesh)


@pytest.mark.parametrize("program,tokens", [("decode block", 40),
                                            ("prefill 128", 128),
                                            ("chunk 128", 128)])
def test_experts_are_combined_before_they_cross_the_chips(tp4, monkeypatch,
                                                          program, tokens):
    """``llama._combine_experts``, read off the compiled programs that
    keep the dense dispatch (up to 128 tokens: the prompt programs past
    it route, ``test_prompt_programs_route_their_experts_in_place``). (a) No
    all-reduce carries the expert axis (the parent's was
    ``f32[40,1,4096,8]``, 5.24 MB a layer where the sum is 0.66), and the
    expert layer's one all-reduce adds float32 shares of [tokens, 4096]
    in float32 (the compiler folds the cast to bfloat16 into its
    output). (b) ``w_down`` is read where it lies: no copy or transpose
    of an int8 expert stack or of a layer's slice of one (a region that
    left the one-device axes to GSPMD made the prefill programs
    transpose 117 MB a layer), no bfloat16 copy of the experts, and the
    temporaries are megabytes (the parent's prefill held 70). (c) The
    fusion that streams ``w_down`` takes the int8 stack itself, slice
    and convert inside it (PERF.md, Findings PR 41)."""
    compiled = _lowered_tp4(monkeypatch, tp4, program).compile()
    text = compiled.as_text()
    _, own, _ = _outside_conditionals(text)
    insts = {}
    for ln in own:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(%?([\w.\-]*)", ln)
        if m:
            name, dtype, dims, opcode, operand = m.groups()
            insts[name] = _Inst(dtype, [int(d) for d in dims.split(",") if d],
                                opcode, operand, ln)
    expert_slice = 8 * 3584 * 4096      # a layer's w_down a chip

    # (a)
    reduces = [i for i in insts.values() if i.opcode.startswith("all-reduce")]
    assert len(reduces) >= 3            # embedding, wo, experts
    assert not [i[:3] for i in reduces if 8 in i.dims]
    experts = [i for i in reduces if "/moe_experts/" in i.line]
    assert len(experts) == 1
    shares = insts[experts[0].operand]
    assert math.prod(shares.dims) == tokens * 4096
    assert shares.dtype == "f32" and shares.dims == experts[0].dims
    region = re.search(r"to_apply=%?([\w.\-]+)", experts[0].line).group(1)
    assert re.search(rf"^%?{re.escape(region)} \([\w.]+: f32\[\], "
                     rf"[\w.]+: f32\[\]\) -> f32\[\]", text, re.M)
    # (b)
    moved = [i[:3] for i in insts.values() if i.dtype in ("s8", "bf16")
             and math.prod(i.dims) >= expert_slice
             and i.opcode in ("copy", "transpose", "fusion", "convert")]
    assert not moved
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    # (c)
    down = [i for i in insts.values() if i.opcode == "fusion"
            and "/moe_experts/" in i.line and "efd->bsed" in i.line]
    assert len(down) == 1
    operands = re.search(r" fusion\(([^)]*)\)", down[0].line).group(1)
    stacks = [insts[o] for o in re.findall(r"%([\w.\-]+)", operands)]
    assert [i for i in stacks
            if (i.dtype, i.dims) == ("s8", [2, 8, 3584, 4096])]
    assert not [i[:3] for i in stacks if i.dtype == "bf16"
                and math.prod(i.dims) >= expert_slice]
    if program == "decode block":
        # a chip's two KV heads' scale tables: written where they lie,
        # as on one chip (test_qk_projections_read_their_weights_in_place)
        tables = _made(text, f"2,{B},2,{SMAX}")
        assert tables and not tables & _TABLE_MOVES

@pytest.mark.parametrize("program", ["prefill 512", "chunk 512"])
def test_prompt_programs_route_their_experts_in_place(tp4, monkeypatch,
                                                      program):
    """``llama._routed_experts`` on the four chips, read off Mixtral's
    512-position programs. (a) The block loop (``moe.blocks_loop``, a
    while inside the layer loop) holds no collective; the expert layer
    has one, outside it: the all-reduce of float32 shares of
    [512, 4096], added in float32 (the compiler folds the cast to
    bfloat16 into its output). (b) A block reads its expert where it
    lies: no instruction of its own copies, transposes or converts an
    int8 expert (a chip's 4096 x 3584 of one) or the stack, and nothing
    that large is bfloat16: the slice and the convert are inside the
    matmul's fusion. (c) The down product leaves the loop in float32:
    the loop carries the dispatch buffer's result as
    ``f32[2048, 4096]`` (128-row blocks: 16 of them at most)."""
    compiled = _lowered_tp4(monkeypatch, tp4, program).compile()
    text = compiled.as_text()
    comps, _ = _computations(text)
    _, own, _ = _outside_conditionals(text)
    inst = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]\S* "
                      r"([\w\-]+)\(%?([\w.\-]*)")

    def parsed(lines):
        return [_Inst(m[2], [int(d) for d in m[3].split(",") if d], m[4],
                      m[5], ln) for ln in lines if (m := inst.match(ln))]

    # the block loop's body: the while under moe/experts
    loops = [ln for ln in own if " while(" in ln
             and "/moe_experts_routed/" in ln]
    assert len(loops) == 1
    body = re.search(r"body=%?([\w.\-]+)", loops[0]).group(1)
    turn = parsed(comps[body])
    assert len(turn) > 5
    # (a)
    collective = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")
    assert not [i.line[:120] for i in turn
                if i.opcode.startswith(collective)]
    insts = {m[1]: _Inst(m[2], [int(d) for d in m[3].split(",") if d],
                         m[4], m[5], ln)
             for ln in own if (m := inst.match(ln))}
    sums = [i for i in insts.values() if i.opcode.startswith("all-reduce")
            and "/moe_experts_routed/" in i.line]
    assert len(sums) == 1 and sums[0].dims == [512, 4096]
    shares = insts[sums[0].operand]
    assert shares.dtype == "f32" and shares.dims == [512, 4096]
    region = re.search(r"to_apply=%?([\w.\-]+)", sums[0].line).group(1)
    assert re.search(rf"^%?{re.escape(region)} \([\w.]+: f32\[\], "
                     rf"[\w.]+: f32\[\]\) -> f32\[\]", text, re.M)
    # (b)
    one_expert = 4096 * 3584
    moved = [i[:3] for i in insts.values() if i.dtype in ("s8", "bf16")
             and 3584 in i.dims and math.prod(i.dims) >= one_expert
             and i.opcode in ("copy", "transpose", "fusion", "convert")]
    assert not moved
    types = dict(re.findall(r"^\s*%?([\w.\-]+) = (\w+\[[\d,]*\])",
                            "\n".join(comps[body]), re.M))
    stacks = {"s8[16,4096,3584]", "s8[16,3584,4096]"}   # [L * E, ...]
    reads = [i for i in turn if i.opcode == "fusion" and stacks & {
        types.get(o) for o in re.findall(
            r"%([\w.\-]+)", re.search(r" fusion\(([^)]*)\)", i.line)[1])}]
    assert len(reads) == 3          # gate, up, down: each takes the stack
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    # (c)
    written = [i for i in turn if i.dims == [2048, 4096]
               and i.opcode not in ("get-tuple-element", "parameter",
                                    "bitcast")]
    assert written and {i.dtype for i in written} == {"f32"}


# -- the window family's programs at the published widths ----------------------

def _lowered_window(monkeypatch, sharding, program):
    """``benchmarks/configs/laguna-xs.2-int8-pp5.json`` as its cell runs
    it: eight layers, bfloat16 rows and rings, 128 slots."""
    cfg = _cell_config("laguna-xs.2-int8-pp5")
    return _engine_lowered(monkeypatch, sharding, cfg, 128, None, program)


@pytest.mark.parametrize("program,kernels", [("decode block", 17),
                                             ("chunk 512", 6)])
def test_window_family_reads_weights_and_rings_in_place(one_chip, monkeypatch,
                                                        program, kernels):
    """The decode block runs the decode kernel eight times (six rings
    with a group of 8 query heads a KV head, two full layers with a group
    of 6), the append twice (rows, rings) and the routed experts' kernel
    once a sparse layer; the 512-token chunk program reads a ring before
    it overwrites all of it and runs its 64-row blocks through the same
    kernel (six: a middle chunk yields no logits, so its last layer's
    feed-forward is dead code). Neither loops over dispatch blocks (the only
    ``while`` left is the layer scan's), copies an int8
    weight stack, stages a layer's slice of one in VMEM, or copies a ring
    or the rows out of place (PERF.md, Findings PR 33 and section 7 item
    9: the barrier is in ``blocks.attention`` from the start); and the
    whole engine fits the chip."""
    compiled = _lowered_window(monkeypatch, one_chip, program).compile()
    text = compiled.as_text()
    assert len(re.findall(r"^\s*%?[\w.\-]+ = .*custom_call_target="
                          r"\"tpu_custom_call\"", text, re.M)) == kernels
    assert len(re.findall(r"%expert_blocks_stacked[\w.]* = ", text)) \
        == (7 if program == "decode block" else 6)
    # the block's steps and the layer scan, and no loop over dispatch
    # blocks inside them (the parent's decode block held eight)
    assert len(re.findall(r" while\(", text)) <= 2
    if program == "decode block":
        assert len(re.findall(r"%flash_decode_ring[\w.]* = ", text)) == 6
        assert len(re.findall(r"%flash_decode_stacked[\w.]* = ", text)) == 2
        assert len(re.findall(r"%append_rows_stacked[\w.]* = ", text)) == 2
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|bf16)\[[\d,]+\]\S*) ([\w\-]+)\(",
        text, re.M)
    assert results                      # the pattern still reads this HLO

    def elements(shape):
        n = 1
        for d in shape.split("[")[1].split("]")[0].split(","):
            n *= int(d)
        return n

    # an int8 stack of a kind (2 or 6 layers, a dense one, 7 x 256
    # experts) moved as a whole, or a layer's slice of one staged
    stack_copies = [r for r in results if r[1] in ("copy", "transpose")
                    and r[0].startswith("s8[") and elements(r[0]) >= 1 << 22]
    staged = [r for r in results if r[1] == "fusion"
              and r[0].startswith("s8[1,2048,") and "S(1)" in r[0]]
    # rings [6,128,8,512,128] and rows [2,128,8,2048,128], or a slot's
    moved = [r for r in results if r[1] in ("copy", "transpose")
             and re.match(r"bf16\[[26],(128|1),8,(512|2048),128\]", r[0])]
    assert not stack_copies
    assert not staged
    assert not moved
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (512 << 20)
    if program == "decode block":       # the loop's 156.6 MB, to 3 digits
        assert mem.temp_size_in_bytes < 0.1575e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9


# -- the conv family's programs at the published widths ------------------------

def _lowered_conv(monkeypatch, sharding, program):
    """``benchmarks/configs/lfm2-24b-a2b-int8-pp2.json`` as its cell runs
    it: twenty layers, bfloat16 rows two KV heads a row, 96 slots."""
    cfg = _cell_config("lfm2-24b-a2b-int8-pp2")
    return _engine_lowered(monkeypatch, sharding, cfg, 96, None, program)


@pytest.mark.parametrize("program,kernels", [("decode block", 9),
                                             ("prefill 256", 8)])
def test_conv_family_reads_weights_rows_and_tails_in_place(one_chip,
                                                           monkeypatch,
                                                           program, kernels):
    """The decode block holds the decode kernel over the paired 64-wide
    rows, the routed experts' kernel (an expert two tiles wide) and one
    row append; the first period, which holds the dense layers, stands
    beside the scan's body, so the text names the decode kernel twice and
    the experts' six times (two sparse layers of the first period, four of
    the body). The 256-token prefill runs the flash kernel on paired
    heads. Neither copies an int8 weight stack or an expert stack, stages
    a layer's slice of one in VMEM, or moves the K and V rows; the decode
    block's temporaries are 40 MB and the whole engine fits the chip."""
    compiled = _lowered_conv(monkeypatch, one_chip, program).compile()
    text = compiled.as_text()
    assert len(re.findall(r"^\s*%?[\w.\-]+ = .*custom_call_target="
                          r"\"tpu_custom_call\"", text, re.M)) == kernels
    assert len(re.findall(r"%expert_blocks_stacked[\w.]* = ", text)) == 6
    assert len(re.findall(r" while\(", text)) <= 2
    if program == "decode block":
        assert len(re.findall(r"%flash_decode_stacked[\w.]* = ", text)) == 2
        assert len(re.findall(r"%append_rows_stacked[\w.]* = ", text)) == 1
    else:
        assert len(re.findall(r"%flash_causal_prefill[\w.]* = ", text)) == 2
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|bf16)\[[\d,]+\]\S*) ([\w\-]+)\(",
        text, re.M)
    assert results                      # the pattern still reads this HLO

    def elements(shape):
        n = 1
        for d in shape.split("[")[1].split("]")[0].split(","):
            n *= int(d)
        return n

    # an int8 stack (15 conv operators, 5 attention, 2 dense, 18 x 64
    # experts) moved as a whole, or a layer's slice of one staged
    stack_copies = [r for r in results if r[1] in ("copy", "transpose")
                    and r[0].startswith("s8[") and elements(r[0]) >= 1 << 22]
    staged = [r for r in results if r[1] == "fusion"
              and r[0].startswith("s8[1,2048,") and "S(1)" in r[0]]
    # the rows [5, 96, 4, 2048, 128], or a slot's
    moved = [r for r in results if r[1] in ("copy", "transpose")
             and re.match(r"bf16\[5,(96|1),4,2048,128\]", r[0])]
    assert not stack_copies
    assert not staged
    assert not moved
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20)
    # 11.6 GB of weights, 2.03 GB of cache, and what a step needs
    assert 13.5e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 14.2e9


# -- the block-diffusion family: a pass over the slots' blocks -------------------

def _lowered_block(monkeypatch, sharding, program):
    """``benchmarks/configs/sdar-30b-a3b-int8-pp4.json`` as its cell runs
    it: twelve layers of 128 experts, bfloat16 rows, 96 slots."""
    cfg = _cell_config("sdar-30b-a3b-int8-pp4")
    return _engine_lowered(monkeypatch, sharding, cfg, 96, None, program)


def test_block_decode_kernel_compiles(one_chip):
    """The W-row branch of the decode kernel at the cell's shapes: four
    query positions a slot, a KV head's tile of 32 query rows, bfloat16
    rows of four KV heads; and the W = 1 kernel beside it unchanged."""
    arr, cache, _ = _shapes(one_chip, 4, jnp.bfloat16)
    q = arr((B, 4, 32, D), jnp.bfloat16)
    new = arr((B, 4, 4, D), jnp.bfloat16)
    text = jax.jit(functools.partial(
        fd.flash_decode_block, block_s=256)).lower(
            q, cache, cache, new, new, arr((B,), jnp.int32),
            arr((), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("program,kernels", [("decode block", 6),
                                             ("prefill 256", 2),
                                             ("chunk 512", 1)])
def test_block_family_reads_weights_and_rows_in_place(one_chip, monkeypatch,
                                                      program, kernels):
    """A dispatch of four passes holds, in its scan's body, the W-row
    decode kernel, the routed experts' kernel and one row append a block
    position (four); a prompt program the block-causal flash kernel
    (the chunk program attends in jnp) and the experts' kernel. None
    copies an int8 weight stack or an expert stack, stages a layer's
    slice of one, or moves the K and V rows; the whole engine fits the
    chip."""
    compiled = _lowered_block(monkeypatch, one_chip, program).compile()
    text = compiled.as_text()
    assert len(re.findall(r"^\s*%?[\w.\-]+ = .*custom_call_target="
                          r"\"tpu_custom_call\"", text, re.M)) == kernels
    assert len(re.findall(r"%expert_blocks_stacked[\w.]* = ", text)) == 1
    if program == "decode block":
        assert len(re.findall(r"%flash_decode_block[\w.]* = ", text)) == 1
        assert len(re.findall(r"%append_rows_stacked[\w.]* = ", text)) == 4
    elif program == "prefill 256":
        assert len(re.findall(r"%flash_causal_prefill[\w.]* = ", text)) == 1
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|bf16)\[[\d,]+\]\S*) ([\w\-]+)\(",
        text, re.M)
    assert results                      # the pattern still reads this HLO

    def elements(shape):
        n = 1
        for d in shape.split("[")[1].split("]")[0].split(","):
            n *= int(d)
        return n

    stack_copies = [r for r in results if r[1] in ("copy", "transpose")
                    and r[0].startswith("s8[") and elements(r[0]) >= 1 << 22]
    staged = [r for r in results if r[1] == "fusion"
              and r[0].startswith("s8[1,2048,") and "S(1)" in r[0]]
    moved = [r for r in results if r[1] in ("copy", "transpose")
             and re.match(r"bf16\[12,(96|1),4,2048,128\]", r[0])]
    assert not stack_copies, stack_copies
    assert not staged, staged
    assert not moved, moved
    mem = compiled.memory_analysis()
    # 8.44 GB of weights (a prompt program, which yields no token,
    # takes no head: 0.31 GB less), 4.83 GB of cache, and what a pass
    # or a prompt needs
    assert 12.9e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 14.6e9, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# -- the state-space family: its kernels and programs at the published widths --

SSD_L, SSD_B, SSD_G, SSD_N, SSD_R, SSD_H = 10, 96, 8, 128, 1024, 128


def test_ssd_decode_compiles_in_place(one_chip):
    """128 heads x 64 x 128 float32 in 8 groups, 96 slots, 10 mamba
    layers: the 4 GB of states it returns are the ones it was given."""
    from gofr_tpu.ops import ssd

    def arr(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(ssd.ssd_decode, donate_argnums=(0,)).lower(
        arr((SSD_L, SSD_B, SSD_G, SSD_N, SSD_R)), arr((), jnp.int32),
        arr((SSD_B, SSD_G, SSD_R)), arr((SSD_B, SSD_H)),
        arr((SSD_B, SSD_G, SSD_N)), arr((SSD_B, SSD_G, SSD_N)),
        arr((SSD_B,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = SSD_L * SSD_B * SSD_G * SSD_N * SSD_R * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 64


@pytest.mark.parametrize("tokens", [32, 512])
def test_ssd_prefill_compiles(one_chip, tokens):
    """The smallest bucket (one chunk of its own 32 tokens) and a whole
    chunk of a prompt (four chunks of 128)."""
    from gofr_tpu.ops import ssd

    def arr(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = ssd.ssd_prefill.lower(
        arr((1, tokens, SSD_G, SSD_R)), arr((1, tokens, SSD_H)),
        arr((1, tokens, SSD_G, SSD_N)), arr((1, tokens, SSD_G, SSD_N)),
        arr((1, SSD_G, SSD_N, SSD_R)), chunk=128).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode block", "chunk 512"])
def test_state_space_family_leaves_states_and_stacks_where_they_lie(
        one_chip, monkeypatch, program):
    """``benchmarks/configs/nemotron-3-super-120b-int8-ep4.json`` as its
    cell runs it: 22 layers of three kinds in ONE scan whose body switches
    on the kind (so the text holds each kind's kernels once whatever the
    depth), 96 slots. A branch that is no mamba layer hands the 4 GB of
    states and the tails back through ``ssd_untouched``, not as a copy;
    no int8 stack or expert stack is copied; the engine fits the chip."""
    cfg = _cell_config("nemotron-3-super-120b-int8-ep4")
    compiled = _engine_lowered(monkeypatch, one_chip, cfg, 96, None,
                               program).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%expert_blocks_stacked[\w.]* = ", text)) == 1
    assert len(re.findall(r" conditional\(", text)) >= 1
    if program == "decode block":
        assert len(re.findall(r"%ssd_decode[\w.]* = ", text)) == 1
        assert len(re.findall(r"%flash_decode_stacked[\w.]* = ", text)) == 1
        assert len(re.findall(r"%append_rows_stacked[\w.]* = ", text)) == 1
        # states and tails: one in each of the two other branches
        assert len(re.findall(r"%ssd_untouched[\w.]* = ", text)) == 4
    else:
        assert len(re.findall(r"%ssd_prefill[\w.]* = ", text)) == 1
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|bf16|f32)\[[\d,]+\]\S*) "
        r"([\w\-]+)\(", text, re.M)
    assert results                      # the pattern still reads this HLO

    def elements(shape):
        n = 1
        for d in shape.split("[")[1].split("]")[0].split(","):
            n *= int(d)
        return n

    moved = [r for r in results if r[1] in ("copy", "transpose")
             and (r[0].startswith("s8[") and elements(r[0]) >= 1 << 22
                  or re.match(r"f32\[10,96,8,128,1024\]", r[0])
                  or re.match(r"bf16\[10,96,30720\]", r[0])
                  or re.match(r"bf16\[2,96,2,2048,128\]", r[0]))]
    assert not moved
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (256 << 20)
    # 9.2 GB of weights, 4.5 GB of cache, and what a step needs
    assert 13.5e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 14.3e9


# -- the state-space family's layer of two halves at the published widths ------

G1_L, G1_B, G1_N, G1_R, G1_H = 36, 96, 128, 4096, 64


def test_ssd_decode_compiles_in_place_at_one_group(one_chip):
    """64 heads x 64 x 128 float32 in ONE group of 4,096 lanes, 96 slots,
    36 mamba layers: a work item is the whole 2.1 MB state of a (layer,
    slot), and the 7.25 GB of states it returns are the ones it was
    given."""
    from gofr_tpu.ops import ssd

    def arr(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(ssd.ssd_decode, donate_argnums=(0,)).lower(
        arr((G1_L, G1_B, 1, G1_N, G1_R)), arr((), jnp.int32),
        arr((G1_B, 1, G1_R)), arr((G1_B, G1_H)), arr((G1_B, 1, G1_N)),
        arr((G1_B, 1, G1_N)), arr((G1_B,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = G1_L * G1_B * G1_N * G1_R * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 64


@pytest.mark.parametrize("tokens", [32, 64, 128, 256, 512])
def test_ssd_prefill_compiles_at_one_group(one_chip, tokens):
    """Every engine bucket at chunks of 256: a bucket under a chunk is
    one chunk of its own length; the group's 4,096 lanes go a cut of
    1,024 a program (four of them), C B^T made by the first."""
    from gofr_tpu.ops import ssd

    def arr(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    assert ssd.prefill_cuts(G1_R, 64) == 4
    compiled = ssd.ssd_prefill.lower(
        arr((1, tokens, 1, G1_R)), arr((1, tokens, G1_H)),
        arr((1, tokens, 1, G1_N)), arr((1, tokens, 1, G1_N)),
        arr((1, 1, G1_N, G1_R)), chunk=256).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_cells_cache_keeps_a_float32_state():
    """What ``granite-4.0-h-micro-int8.reason-sat`` allocates under its
    own ``env`` (96 slots x 2,048, ``TPU_KV_DTYPE`` bfloat16): 36 states
    of ONE group a slot in float32, whatever type the rows and the tails
    have. A narrower state is a different result, not a faster one
    (ISSUE 53), and at the published widths the cell's numerical check
    does not see it: this does."""
    from gofr_tpu.models import family

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "granite-4.0-h-micro-int8.json")) as f:
        env = json.load(f)["env"]
    cfg = _cell_config("granite-4.0-h-micro-int8")
    cache = jax.eval_shape(lambda: family(cfg).init_cache(
        cfg, int(env["TPU_SLOTS"]), int(env["TPU_MAX_SEQ"]),
        dtype=jnp.dtype(env["TPU_KV_DTYPE"])))
    assert cache.state.shape == (G1_L, G1_B, 1, G1_N, G1_R)
    assert cache.state.dtype == jnp.float32
    assert cache.k.dtype == cache.conv.dtype == jnp.bfloat16
    assert cache.k.shape == (4, G1_B, 4, 2048, 128)     # paired rows


@pytest.mark.parametrize("program", ["decode block", "chunk 512",
                                     "prefill 256"])
def test_layer_of_two_halves_leaves_states_stacks_and_table_where_they_lie(
        one_chip, monkeypatch, program):
    """``benchmarks/configs/granite-4.0-h-micro-int8.json`` as its cell
    runs it: 40 layers in ONE scan, a switch between two mixers and the
    feed-forward outside it, 96 slots. The attn branch hands the 7.25 GB
    of states and the tails back through ``ssd_untouched``; no int8
    stack is copied (``w_ssm_in`` is 8,576 columns wide for that), nor
    the tied table; the attention layers' rows of two 64-wide KV heads
    are read by ``flash_decode_stacked`` and written by
    ``append_rows_stacked``, and a 256-token prefill runs the flash
    kernel on paired heads; the engine fits the chip."""
    cfg = _cell_config("granite-4.0-h-micro-int8")
    compiled = _engine_lowered(monkeypatch, one_chip, cfg, 96, None,
                               program).compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) >= 1
    # states and tails, in the one branch that is no mamba layer
    assert len(re.findall(r"%ssd_untouched[\w.]* = ", text)) == 2
    if program == "decode block":
        assert len(re.findall(r"%ssd_decode[\w.]* = ", text)) == 1
        assert len(re.findall(r"%flash_decode_stacked[\w.]* = ", text)) == 1
        assert len(re.findall(r"%append_rows_stacked[\w.]* = ", text)) == 1
        assert "decode_attention_appended" not in text
        # the state the decode kernel rewrites where it lies is the
        # configuration's: float32, whole. The cell's numerical check
        # cannot tell a bfloat16 state from this at these widths
        # (PERF.md section 7, item 18(g)), so its type is held here
        kernel, = re.findall(r"^.*%ssd_decode[\w.]* = .*$", text, re.M)
        assert "f32[36,96,1,128,4096]" in kernel.split(" custom-call(")[0]
        assert "f32[36,96,1,128,4096]" in kernel.split(" custom-call(")[1]
    else:
        assert len(re.findall(r"%ssd_prefill[\w.]* = ", text)) >= 1
        assert ("%flash_causal_prefill" in text) == (program == "prefill 256")
    assert not re.search(r"(?:bf16|f16|s8)\[36,96,1,128,4096\]", text)
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|bf16|f32)\[[\d,]+\]\S*) "
        r"([\w\-]+)\(", text, re.M)
    assert results                      # the pattern still reads this HLO

    def elements(shape):
        n = 1
        for d in shape.split("[")[1].split("]")[0].split(","):
            n *= int(d)
        return n

    moved = [r for r in results if r[1] in ("copy", "transpose")
             and (r[0].startswith("s8[") and elements(r[0]) >= 1 << 22
                  or re.match(r"f32\[36,96,1,128,4096\]", r[0])
                  or re.match(r"bf16\[36,96,13056\]", r[0])
                  or re.match(r"bf16\[100352,2048\]", r[0])
                  or re.match(r"bf16\[2048,100352\]", r[0])
                  or re.match(r"bf16\[4,96,4,2048,128\]", r[0]))]
    assert not moved
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (256 << 20)
    # 3.4 GB of weights, 8.95 GB of cache, and what a step needs
    assert 12.0e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 12.8e9


# -- the sparse-latent family's programs at the published widths ---------------

@pytest.mark.parametrize("program,kernels", [("decode block", 20),
                                             ("chunk 512", 15)])
def test_sparse_latent_family_compiles_and_leaves_its_tables_where_they_lie(
        one_chip, monkeypatch, program, kernels):
    """``benchmarks/configs/dots3-note-prev-int8-ep8.json`` as its cell
    runs it: nine layers, 128 slots x 4,096, bfloat16 rows, index keys
    and rings. Mosaic takes the three new kernels at the cell's shapes:
    the decode block runs the masked walk over the rows kept three times
    (rank 512 in 640 lanes, 128 heads), the ring walk six times (rank
    1,024 in 1,152 lanes, 64 heads, a ring of 512), the index score pass
    three times (64 heads of 128 over 4,096 keys) and the routed experts'
    kernel eight times; the 512-token chunk program walks its cached
    rows in ``chunk_walk_latent`` eight times (three slots' rows, five
    rings) beside the experts' seven (a middle chunk yields no logits,
    so its last layer's attention and feed-forward are dead code) and
    holds no block's float32 scores. Neither copies a table of the cache or
    an int8 stack, and the whole engine fits the chip beside the
    reference check."""
    import sys

    monkeypatch.setattr(sys.modules[__name__], "SMAX", 4096)
    cfg = _cell_config("dots3-note-prev-int8-ep8")
    compiled = _engine_lowered(monkeypatch, one_chip, cfg, 128, None,
                               program).compile()
    text = compiled.as_text()
    assert len(re.findall(r"^\s*%?[\w.\-]+ = .*custom_call_target="
                          r"\"tpu_custom_call\"", text, re.M)) == kernels
    assert len(re.findall(r"%expert_blocks_stacked[\w.]* = ", text)) \
        == (8 if program == "decode block" else 7)
    if program == "decode block":
        assert len(re.findall(r"%decode_attention_kept[\w.]* = ", text)) == 3
        assert len(re.findall(r"%decode_attention_ring[\w.]* = ", text)) == 6
        assert len(re.findall(r"%index_scores_stacked[\w.]* = ", text)) == 3
    else:
        assert len(re.findall(r"%chunk_walk_latent[\w.]* = ", text)) == 8
        assert not re.search(r"f32\[(1,)?(128|64),512,512\]\S* fusion\(.*"
                             r"chunk_attn_kept/while", text)
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|bf16)\[[\d,]+\]\S*) ([\w\-]+)\(",
        text, re.M)
    assert results                      # the pattern still reads this HLO

    def elements(shape):
        n = 1
        for d in shape.split("[")[1].split("]")[0].split(","):
            n *= int(d)
        return n

    # rows [3,128,4096,640], keys [3,128,4096,128], rings
    # [6,128,512,1152], or one layer's or one slot's of them; a stack of
    # int8 weights (the smallest, the full layers' w_qb, is 75 M)
    moved = [r for r in results if r[1] in ("copy", "transpose")
             and (re.match(r"bf16\[(3,)?(128|1),4096,(640|128)\]", r[0])
                  or re.match(r"bf16\[(6,)?(128|1),512,1152\]", r[0])
                  or r[0].startswith("s8[") and elements(r[0]) >= 1 << 26)]
    # the one that is left, once a dispatch and not once a step: the
    # window layers' w_kvb (a head is [nope 192 | value 128], and a split
    # at 192 of 320 is no lane tile's edge: XLA re-lays the int8 stack,
    # 126 MB, before it slices W_UK and W_UV out of it; PERF.md section 7)
    assert [r[0].split("{")[0] for r in moved] \
        == ["s8[6,1024,20480]"][:program == "decode block"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (768 << 20)
    # 7.9 GB of weights, 3.3 GB of cache, and what a step needs
    assert 11e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 12e9


def _computations(text):
    """A compiled module's text as ({computation: its instruction
    lines}, the entry computation's name)."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        if line[:1].strip() and line.rstrip().endswith("{") and "(" in line:
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            cur = cur.lstrip("%")
            comps[cur] = []
            entry = cur if line.startswith("ENTRY") else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


# -- the routed experts' dispatch tables in the compiled decode blocks ----------

def _own_instructions(text):
    """The lines of a compiled module that are instructions of their own:
    those of every computation no ``fusion`` calls and no reduction
    applies (the entry, loop bodies, a conditional's branches), less what
    takes no device time."""
    comps, _ = _computations(text)
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    free = re.compile(r" (get-tuple-element|bitcast|constant|parameter)\(")
    return [ln for c in comps if c not in inner for ln in comps[c]
            if not free.search(ln)]


@pytest.mark.parametrize("cell,slots,kernels", [
    ("lfm2-24b-a2b-int8-pp2", 96, 6),
    ("nemotron-3-super-120b-int8-ep4", 96, 1)])
def test_dispatch_tables_are_counted_not_sorted(one_chip, monkeypatch, cell,
                                                slots, kernels):
    """``moe.tables``, read off LFM2's and nemotron's compiled
    decode blocks: nothing under ``moe/experts`` is a ``sort`` (the
    router's top-k, under ``moe/route``, is the only one a layer), and
    the tables are ten instructions a routed layer (the key, the tokens'
    one-hot, the triangular matmul, four over the held experts, the
    blocks' experts, the offsets beside the counts, the rows) where the
    sorted form was over five hundred: the builder found 10, the bound is
    12 (PERF.md, Findings PR 43). Laguna's temporaries are held by
    ``test_window_family_draws_only_inside_a_conditional``."""
    compiled = _engine_lowered(monkeypatch, one_chip, _cell_config(cell),
                               slots, None, "decode block").compile()
    text = compiled.as_text()
    assert len(re.findall(r"%expert_blocks_stacked[\w.]* = ", text)) \
        == kernels
    scoped = [ln for ln in text.splitlines() if re.search(
        r'op_name="[^"]*/moe/experts/', ln)]
    assert len(scoped) > 20 * kernels        # the scope still reads this HLO
    assert not [ln[:160] for ln in scoped if re.search(r" sort\(", ln)]
    tables = [ln for ln in _own_instructions(text) if re.search(
        r'op_name="[^"]*/moe/experts/tables/', ln)]
    assert 4 * kernels <= len(tables) <= 12 * kernels, len(tables)
    fills = [ln for ln in _own_instructions(text) if re.search(
        r'op_name="[^"]*/moe/experts/fill/', ln)]
    assert kernels <= len(fills) <= 3 * kernels, len(fills)


# -- the sampler's branches in the compiled decode block -----------------------

def _outside_conditionals(text):
    """The lines of a compiled module's computations that run whatever a
    ``conditional`` decides (the entry and what it reaches by a loop's
    body, a fusion's or a call's computation, but not through a
    conditional's ``branch_computations``), those among them that are
    instructions of their own (the entry's and the loops' bodies', not
    what is fused into one), and the lines of every other computation."""
    comps, entry = _computations(text)
    loops = re.compile(r"(?:body|condition)=%?([\w.\-]+)")
    fused = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")

    def reach(edges):
        seen, stack = set(), [entry]
        while stack:
            c = stack.pop()
            if c not in seen:
                seen.add(c)
                stack += [n for ln in comps[c] for e in edges
                          for n in e.findall(ln)]
        return seen

    free, own = reach((loops, fused)), reach((loops,))
    return ([ln for c in free for ln in comps[c]],
            [ln for c in own for ln in comps[c]],
            [ln for c in comps if c not in free for ln in comps[c]])


def test_window_family_draws_only_inside_a_conditional(one_chip, monkeypatch):
    """``programs._sample``'s branches, read off Laguna's compiled decode
    block: XLA kept the ``conditional``s (no ``select`` over both sides),
    the vocabulary-wide top-k and every threefry round of the draws and
    of their keys are in branch computations, the entry and the scan's
    body outside them hold none of either, and the branches get the
    head's bfloat16 logits, not a float32 copy written every step. The
    program's temporaries are no larger than they were with the draws
    unconditional (157,064,192 B: PERF.md, Findings PR 39)."""
    compiled = _lowered_window(monkeypatch, one_chip, "decode block").compile()
    text = compiled.as_text()
    outside, own, inside = _outside_conditionals(text)
    assert len(re.findall(r" conditional\(", text)) == 3
    draws = re.compile(
        r'op_name="[^"]*/sampling/[^"]*(top_k|threefry|_gumbel|_uniform|'
        r'random_bits|fold_in)')
    assert sum(bool(draws.search(ln)) for ln in inside) > 100
    assert not [ln[:160] for ln in outside if draws.search(ln)]
    # the router sorts 256 scores a token outside; nothing vocabulary-wide
    # is sorted or drawn there, and the one float32 [slots, vocabulary]
    # array is log_softmax's
    wide = [ln for ln in own if re.match(
        r"\s*(ROOT )?%?[\w.\-]+ = \(?[a-z0-9]+\[128,100352\]", ln)]
    assert any("lm_head" in ln for ln in wide)
    assert not [ln[:160] for ln in wide
                if re.search(r" (sort|custom-call|rng[\w\-]*)\(", ln)]
    f32 = [ln for ln in wide if " = f32[" in ln or " = (f32[" in ln]
    assert all("log_softmax" in ln for ln in f32), [ln[:200] for ln in f32]
    assert compiled.memory_analysis().temp_size_in_bytes <= 157_064_192


# -- the looped family: 192 tables of 16 KV heads, a group of one -----------------

OURO_TABLES, OURO_B, OURO_KV, OURO_SMAX = 192, 7, 16, 1536


def _ouro_shapes(sharding):
    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    cache = arr((OURO_TABLES, OURO_B, OURO_KV, OURO_SMAX, D), jnp.int8)
    scale = arr((OURO_TABLES, OURO_B, OURO_KV, OURO_SMAX), jnp.float32)
    return arr, cache, scale


def test_decode_kernel_compiles_at_a_group_of_one(one_chip):
    """16 query heads on 16 KV heads over one of 192 tables of a 7 x 1,536
    int8 cache: an item is 16 KV heads' [256, 128] tiles, 1 MB of K and V
    where Mistral's is half that, three buffers of it."""
    arr, cache, scale = _ouro_shapes(one_chip)
    q = arr((OURO_B, 1, OURO_KV, D), jnp.bfloat16)
    compiled = fd.flash_decode_stacked.lower(
        q, cache, cache, q, q, arr((OURO_B,), jnp.int32), arr((), jnp.int32),
        scale, scale, block_s=fd.block_size(OURO_SMAX)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert fd.block_size(OURO_SMAX) == 256


def test_append_kernel_compiles_cut_over_the_tables(one_chip):
    """The step's write at 192 x 16 tables: four calls of 48 tables (a
    slot's tiles of all 192 would be 12.6 MB a buffer, 75 MB over K, V
    and three buffers, past the kernel's VMEM), each in place on the
    caches and the scale tables the one before it left: all four come
    back donated, no second copy of a 4.2 GB table, no operation but the
    kernels makes a scale table."""
    arr, cache, scale = _ouro_shapes(one_chip)
    rows = arr((OURO_TABLES, OURO_B, OURO_KV, D), jnp.int8)
    srows = arr((OURO_TABLES, OURO_B, OURO_KV), jnp.float32)
    assert fd.append_tables(OURO_TABLES, OURO_B, OURO_KV, 32, D, 1,
                            128) == 48
    compiled = jax.jit(fd.append_rows_stacked,
                       donate_argnums=(0, 1, 5, 6)).lower(
        cache, cache, rows, rows, arr((OURO_B,), jnp.int32), scale, scale,
        srows, srows).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 4
    mem = compiled.memory_analysis()
    cache_bytes = 2 * OURO_TABLES * OURO_B * OURO_KV * OURO_SMAX * D
    table_bytes = 2 * OURO_TABLES * OURO_B * OURO_KV * OURO_SMAX * 4
    assert mem.alias_size_in_bytes >= cache_bytes + table_bytes
    assert mem.temp_size_in_bytes < (64 << 20)
    made = _made(text, f"{OURO_TABLES},{OURO_B},{OURO_KV},{OURO_SMAX}")
    assert made and made <= {"parameter", "get-tuple-element"}


def _lowered_loop(monkeypatch, sharding, program):
    """``benchmarks/configs/ouro-2.6b-int8.json`` as its cell runs it: 48
    layers four times, an int8 cache of 192 tables, 7 slots x 1,536."""
    import sys

    monkeypatch.setattr(sys.modules[__name__], "SMAX", OURO_SMAX)
    cfg = _cell_config("ouro-2.6b-int8")
    return _engine_lowered(monkeypatch, sharding, cfg, OURO_B, jnp.int8,
                           program)


@pytest.mark.parametrize("program,kernels", [("decode block", 5),
                                             ("chunk 512", 0),
                                             ("prefill 256", 1)])
def test_looped_family_streams_its_stack_and_leaves_its_tables(
        one_chip, monkeypatch, program, kernels):
    """The decode block holds ONE decode kernel (the scan over passes
    and the scan over layers are loops, the table index their product)
    and the write's four calls; a pass is a ``while`` inside a ``while``
    inside the block's own. No int8 weight stack is copied or staged, no
    cache table or scale table is copied, selected or scattered: the
    block's temporaries are megabytes beside 11.5 GB of arguments. The
    512-token chunk program's are the slot's view (programs.py slices it
    out: 1.2 GB) and the chunk's rows of 192 tables; the whole engine
    fits the chip with the pool's one row beside it."""
    compiled = _lowered_loop(monkeypatch, one_chip, program).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == kernels
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|f32)\[[\d,]+\]\S*) ([\w\-]+)\(",
        text, re.M)
    assert results                      # the pattern still reads this HLO
    stack_copies = [r for r in results if r[1] in ("copy", "transpose")
                    and re.match(r"s8\[48,(2048|5632),", r[0])]
    staged = [r for r in results if r[1] == "fusion"
              and re.match(r"s8\[1,(2048|5632),", r[0]) and "S(1)" in r[0]]
    whole = [r for r in results if r[1] in _TABLE_MOVES - {"fusion"}
             and re.match(r"(s8|f32)\[192,7,16,1536", r[0])
             and r[1] != "dynamic-update-slice"]
    assert not stack_copies
    assert not staged
    assert not whole
    mem = compiled.memory_analysis()
    # 2.77 GB of weights and 8.72 GB of cache
    assert 11.3e9 < mem.argument_size_in_bytes < 11.6e9
    if program == "decode block":
        assert len(re.findall(r" while\(", text)) == 3
        assert len(re.findall(r"%flash_decode_stacked[\w.]* = ", text)) == 1
        assert len(re.findall(r"%append_rows_stacked[\w.]* = ", text)) == 4
        assert mem.temp_size_in_bytes < (32 << 20)
    else:
        # beside the pool's row (1.25 GB) the chip's 17.18 GB hold it
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + 1.25e9 < 16.2e9


# -- a chunk's attention walks the blocks of cached rows under its start --------

def _over_the_slot(text, rows):
    """The float32 arrays of a compiled module's text that have a
    512-token chunk's positions as one dimension and ``rows`` as
    another, in whatever order the compiler left them."""
    shapes = {tuple(s.split(",")) for s in re.findall(r"f32\[([\d,]+)\]", text)}
    return sorted(s for s in shapes if "512" in s
                  and any(str(r) in s for r in rows))


@pytest.mark.parametrize("chips", [1, 4])
def test_chunk_program_scores_no_reserved_row(request, monkeypatch, chips):
    """``ops.attention.chunk_attention`` in the compiled 512-token chunk
    program, at Mistral's eight KV heads on one chip and at the two a
    tp=4 shard of Mixtral holds: the walk is a ``while`` inside the
    layer scan's, a block's float32 scores [.., 512, 512] are the widest
    it holds, and no float32 array spans the slot's 2,048 rows, or the
    2,560 of the one softmax it replaced (``f32[1,8,4,512,2560]``: 168 MB
    a layer, 317 MB of temporaries where these are 18)."""
    if chips == 1:
        lowered = _lowered(monkeypatch, request.getfixturevalue("one_chip"),
                           "chunk 512", layers=2)
    else:
        lowered = _lowered_tp4(monkeypatch, request.getfixturevalue("tp4"),
                               "chunk 512")
    compiled = lowered.compile()
    text = compiled.as_text()
    assert re.search(r'op_name="[^"]*chunk_attention/while', text)
    assert len(re.findall(r" while\(", text)) >= 2
    assert _over_the_slot(text, (512,))  # the pattern still reads this HLO
    assert not _over_the_slot(text, (SMAX, SMAX + 512))
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)


@pytest.mark.parametrize("family,rows", [
    ("looped", r"s8\[(?:\d+,)*16,(?:1536|512),128\]"),
    ("conv", r"bf16\[(?:\d+,)*4,(?:2048|512),128\]")], ids=["looped", "conv"])
def test_chunk_walk_moves_no_slot_and_no_block(one_chip, monkeypatch, family,
                                               rows):
    """Ouro's and LFM2's 512-token chunk programs hold the walk and read
    a slot's K and V rows where they lie: no ``copy`` or ``transpose`` of
    a slot's rows (LFM2's one-softmax form had one, of the paired rows
    ``bf16[5,1,4,2048,128]``) and none of a block of them a trip."""
    lowered = {"looped": _lowered_loop, "conv": _lowered_conv}[family](
        monkeypatch, one_chip, "chunk 512")
    text = lowered.compile().as_text()
    assert re.search(r'op_name="[^"]*chunk_attention/while', text)
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = ((?:s8|bf16)\[[\d,]+\]\S*) ([\w\-]+)\(",
        text, re.M)
    assert [r for r in results if re.match(rows, r[0])]  # they are named
    assert not [r for r in results if r[1] in ("copy", "transpose")
                and re.match(rows, r[0])]
    # (the model is 2,048 wide in both: a slot's 1,536 rows in the one,
    # the old softmax's 2,560 in the other, name no activation)
    assert not _over_the_slot(text, (1536,) if family == "looped"
                              else (2560,))
