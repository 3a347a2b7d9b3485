"""tpu/programs.py alone, on the ``tiny`` preset: the table of compiled
programs, the one allocation function and the fitted shardings, without
the scheduling around them. The engine's own tests (test_tpu.py,
test_paged.py, test_multichip_serving.py, test_hbm_arbiter.py) hold what
the programs compute and how recovery uses the buffers."""

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, family, llama
from gofr_tpu.parallel import make_mesh, shard_params
from gofr_tpu.tpu import GenerationEngine, hbm, programs

TINY = LLAMA_CONFIGS["tiny"]
PAGED = (16, 8)  # blocks in the pool, tokens a block


class _Owner:
    """Stands where the engine stands: the leases are keyed to it."""


def _mesh(on: bool):
    return make_mesh(tp=2, dp=4) if on else None


def _programs(owner, *, paged: bool, mesh):
    prog = programs.EnginePrograms(
        TINY, family(TINY), owner, max_seq=64, kv_dtype=jnp.int8,
        decode_block=2, n_adapters=0, spec_k=2, mesh=mesh,
        paged=PAGED if paged else None)
    prog.describe("cache", 8)
    if paged:
        prog.describe("scratch", 1)
    else:
        prog.describe("pool", 4)
    return prog


# the four programs the benchmark finds in a device trace by name, and
# the rest of what each layout runs
NAMES = {
    False: {"_prefill_jit": "_prefill_fn", "_step_jit": "_step_fn",
            "_verify_jit": "_verify_fn", "_chunk_mid_jit": "_chunk_mid",
            "_chunk_final_jit": "_chunk_final",
            "_pool_load_jit": "_copy_row", "_pool_store_jit": "_copy_row",
            "_host_write_jit": "_write_row_from_host"},
    True: {"_prefill_jit": "_paged_prefill_fn", "_step_jit": "_paged_step_fn",
           "_verify_jit": "_paged_verify_fn", "_chunk_mid_jit": "_chunk_mid",
           "_chunk_final_jit": "_chunk_final",
           "_row_to_blocks_jit": "write_row_to_blocks",
           "_blocks_to_row_jit": "read_blocks_to_row"},
}


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh"])
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_table_yields_the_same_program_names(monkeypatch, paged, meshed):
    built = {}

    def record(fn, donate_argnums=(), out_shardings=None):
        built[fn.__name__] = (donate_argnums, out_shardings)
        return fn

    prog = _programs(_Owner(), paged=paged, mesh=_mesh(meshed))
    # the paged programs' modules decorate with jax.jit as they are
    # imported, and build() imports them late: import them before jit is
    # replaced, whatever test this worker ran first (alone, the paged
    # case failed on a TypeError from ops/paged_attention.py)
    from gofr_tpu.models import paged_llama  # noqa: F401
    monkeypatch.setattr(programs.jax, "jit", record)
    # only a contiguous engine has a prefix pool, so only it offloads
    got = {attr: fn.__name__
           for attr, fn in prog.build(offload=not paged).items()}
    want = dict(NAMES[paged])
    if meshed:
        # the row copies run mask-and-reduce on a mesh; every other
        # program is the same function under the same name
        want = {a: n + "_masked" if n in ("_copy_row", "_write_row_from_host")
                else n for a, n in want.items()}
    assert got == want
    assert all(d == (0,) for d, _ in built.values())
    if not meshed:
        assert all(sh is None for _, sh in built.values())
        return
    assert all(sh is not None for _, sh in built.values())
    rep, placed = prog.placed.rep, prog.placed
    step = built["_paged_step_fn" if paged else "_step_fn"][1]
    assert step == (rep, rep, rep, (rep,) * 4, rep, placed.cache, rep)
    row = placed.scratch if paged else placed.cache
    assert built["_chunk_mid"][1] == row
    assert built["_chunk_final"][1] == (rep, rep, rep, row)


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh"])
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_allocating_twice_settles_the_same_leases(paged, meshed):
    """Recovery allocates every live description again: the same lease
    keys at the same bytes, never a second count."""
    owner = _Owner()
    prog = _programs(owner, paged=paged, mesh=_mesh(meshed))

    def mine():
        return {k: v for k, v in hbm.snapshot().items() if k[1] == id(owner)}

    try:
        held = [prog.allocate(tag) for tag in prog.buffers]
        first, live = mine(), hbm.live_bytes()
        assert {k[2] for k in first} == set(prog.buffers)
        # one entry a device ("" alone without a mesh; on one, what the
        # arbiter's per-shard split rounds off stays there)
        assert {k[3] for k in first} - {""} == set(prog.placed.labels)
        assert sum(first.values()) == sum(hbm.tree_nbytes(b) for b in held)
        held = [prog.allocate(tag) for tag in prog.buffers]
        assert mine() == first
        assert hbm.live_bytes() == live
    finally:
        hbm.release(owner=owner)
    assert not mine()


@pytest.mark.parametrize("rows, sharded", [(4, True), (3, False)])
def test_smaller_pool_refits_its_sharding(rows, sharded):
    """The arbiter's shrink describes the pool again at fewer rows: rows
    the data axes no longer divide replicate, and the pool settles its
    account without a second lease."""
    owner = _Owner()
    prog = _programs(owner, paged=False, mesh=_mesh(True))
    assert prog.placed.pool.k.spec[1] is not None  # 4 rows over dp=4
    cache_sh = prog.placed.cache
    try:
        prog.allocate("pool")
        prog.describe("pool", rows)
        assert (prog.placed.pool.k.spec[1] is not None) == sharded
        assert prog.placed.cache == cache_sh
        pool = prog.allocate("pool", lease=False)
        assert pool.k.shape[1] == rows
        assert pool.k.sharding == prog.placed.pool.k
        mine = {k: v for k, v in hbm.snapshot().items()
                if k[1] == id(owner)}
        assert sum(mine.values()) == hbm.tree_nbytes(pool)
    finally:
        hbm.release(owner=owner)


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh"])
def test_late_scratch_gets_the_programs_of_an_early_one(meshed):
    """A paged decode worker built without a scratch row grows one at
    its first shipped-KV admission; its row<->blocks programs are the
    table's, output shardings included."""
    mesh = _mesh(meshed)
    params = llama.init(TINY, jax.random.PRNGKey(0))
    if meshed:
        params = shard_params(params, mesh)
    kw = dict(slots=4, max_seq=16, prompt_buckets=(8, 16),
              paged_blocks=PAGED[0], paged_block_size=PAGED[1], mesh=mesh)
    late = GenerationEngine(TINY, params, **kw)
    early = GenerationEngine(TINY, params, prefix_cache_slots=2, **kw)
    try:
        assert not hasattr(late, "_scratch") and hasattr(early, "_scratch")
        with late._device_lock:
            late._ensure_scratch()
        blocks = jnp.zeros((late._mb,), jnp.int32)
        for name in ("_row_to_blocks_jit", "_blocks_to_row_jit",
                     "_chunk_mid_jit", "_chunk_final_jit"):
            assert getattr(late, name).__name__ == \
                getattr(early, name).__name__
        for eng in (late, early):
            eng.lowered = [
                eng._row_to_blocks_jit.lower(
                    eng.cache, eng._scratch, blocks).as_text(),
                eng._blocks_to_row_jit.lower(
                    eng._scratch, eng.cache, blocks).as_text()]
        assert late.lowered == early.lowered
        assert late._scratch.k.sharding == early._scratch.k.sharding
    finally:
        late.close()
        early.close()
