"""Mesh-native attention kernels: shard_map'd flash prefill/decode and
paged attention on the virtual 8-device CPU mesh (tests/conftest.py).

GOFR_FLASH_INTERPRET=1 forces every *_auto dispatcher into interpret
mode, so the REAL kernel bodies run (as XLA emulation) inside shard_map
on tp=2 and tp=4 meshes; tokens are asserted EXACT against the
single-device jnp-reference engine built before the env flag is set.
tiny's n_kv_heads=2 covers tp=2; tp=4 uses a 4-KV-head variant so both
factorizations stay in the head-aligned regime, and an 8-KV-head one
for the two int8 KV heads a shard that Mixtral meets. The head-splitting
regime is covered the other way round: tp-only meshes fall back to the
jnp reference (still token-exact), and tp + data axes refuse at
construction with a typed ShardingConfigError naming the TPU_SHARDING
row (the PR-13 verified wrong-logits hazard).

Structural guarantees (monkeypatch counters, not numerics):
- the mesh paged decode/verify path never materializes a dense pool
  view (gather_blocks raises if reached);
- the shard_map'd kernel forms are actually dispatched (a silent
  fallback to the reference would otherwise pass every exactness test).
"""

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.errors import ShardingConfigError
from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.ops import flash, flash_decode, paged_attention
from gofr_tpu.parallel import make_mesh, shard_params
from gofr_tpu.tpu import GenerationEngine

TINY = LLAMA_CONFIGS["tiny"]            # n_heads=4, n_kv_heads=2
TINY4 = TINY.with_(name="tiny4", n_kv_heads=4)  # tp=4 head-aligned
# tp=4 leaves two KV heads a shard: Mixtral's 8 over four chips
TINY8 = TINY.with_(name="tiny8", n_heads=8, n_kv_heads=8)

PROMPTS = [[5, 17, 42, 7], [3, 1, 4, 1, 5, 9, 2, 6]]
REP = [7, 9, 7, 9, 7, 9, 7, 9, 7, 9]   # repetitive: spec windows accept
N_NEW = 20


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init(TINY, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def tiny4_params():
    return llama.init(TINY4, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def tiny8_params():
    return llama.init(TINY8, jax.random.PRNGKey(1))


def _cfg_params(tp, tiny_params, tiny4_params):
    """tp=2 rides tiny (n_kv_heads=2); tp=4 needs the 4-KV-head variant."""
    return (TINY, tiny_params) if tp == 2 else (TINY4, tiny4_params)


def _engine(cfg, params, *, mesh=None, kv_dtype=None, paged=False, **kw):
    extra = dict(paged_blocks=25, paged_block_size=8) if paged else {}
    return GenerationEngine(cfg, params, slots=4, max_seq=64,
                            prompt_buckets=(8, 16), mesh=mesh,
                            kv_dtype=kv_dtype, **extra, **kw)


def _tokens(eng, prompts=PROMPTS, n=N_NEW):
    # single-stream greedy probes: batching streams together can flip
    # borderline argmax between factorizations (see CHANGES.md, PR 13)
    try:
        return [eng.generate(p, max_new_tokens=n).tokens() for p in prompts]
    finally:
        eng.close()


def _counted(monkeypatch, module, name):
    """Wrap module.name with a call counter (trace-time dispatch proof)."""
    calls = []
    inner = getattr(module, name)

    def wrapper(*a, **kw):
        calls.append(name)
        return inner(*a, **kw)

    monkeypatch.setattr(module, name, wrapper)
    return calls


def _interpret_on(monkeypatch):
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")


# -- the decode kernel's sharded arm against the reference ---------------------

@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("tp,dp,kv", [(2, 1, 4), (4, 1, 4), (2, 4, 4),
                                      (4, 1, 8)])
def test_flash_decode_sharded_matches_reference(tp, dp, kv, quant):
    """Each device walks its own KV-head (and batch) shard of the stacked
    cache, ragged cursors and dead slots included, and the gathered
    output is the single-device reference's: one KV head a shard, two at
    tp=2, and the two of eight that tp=4 leaves (int8: the shard the
    old [.., KV, hd] tile kept off the kernel)."""
    import numpy as np

    from gofr_tpu.ops.attention import decode_attention_appended
    from gofr_tpu.ops.quant import quantize_kv
    from gofr_tpu.parallel.sharding import attention_shard_axes

    n_l, b, s, h, d = 2, 8, 128, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(tp), 5)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (n_l, b, kv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (n_l, b, kv, s, d), jnp.float32)
    k_new = jax.random.normal(ks[3], (b, 1, kv, d), jnp.float32)
    v_new = jax.random.normal(ks[4], (b, 1, kv, d), jnp.float32)
    sk = sv = None
    if quant:
        k, sk = quantize_kv(k)
        v, sv = quantize_kv(v)
    lens = jnp.asarray([0, 1, 31, 32, 33, 126, 0, 64], jnp.int32)
    mesh = make_mesh(tp=tp, dp=dp, devices=jax.devices()[:tp * dp])
    batch_axes, head_axis = attention_shard_axes(mesh, b, h, kv)
    assert head_axis is not None and bool(batch_axes) == (dp > 1)
    got = flash_decode.flash_decode_sharded(
        q, k, v, k_new, v_new, lens, jnp.int32(1), sk, sv, mesh=mesh,
        batch_axes=batch_axes, head_axis=head_axis, block_s=32,
        interpret=True)
    want = decode_attention_appended(
        q, k[1], v[1], k_new, v_new, lens,
        None if sk is None else sk[1], None if sv is None else sv[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,scaled", [(jnp.int8, False),
                                          (jnp.bfloat16, False),
                                          (jnp.int8, True)])
@pytest.mark.parametrize("tp,dp,kv", [(2, 4, 4), (4, 1, 8)])
def test_append_rows_sharded_matches_scatter(tp, dp, kv, dtype, scaled):
    """The step's write under shard_map: every device merges its own KV
    heads' (and slots') rows into the tiles around the cursors, a
    cursor at capacity dropped, and the gathered caches are the
    scatter's, byte for byte. A quantized cache's scale tables
    (``scaled``) go through the same visit, sharded as the cache is, and
    come back as the unsharded select's (``llama.write_rows`` on the
    path without kernels), bit for bit."""
    import numpy as np

    from gofr_tpu.models import llama
    from gofr_tpu.ops.quant import quantize_kv

    n_l, b, s, h, d = 2, 8, 64, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(kv), 8)

    def rand(key, shape):
        x = jax.random.normal(key, shape, jnp.float32) * 40
        return x.astype(dtype)

    k, v = rand(ks[0], (n_l, b, kv, s, d)), rand(ks[1], (n_l, b, kv, s, d))
    k_rows, v_rows = rand(ks[2], (n_l, b, kv, d)), rand(ks[3], (n_l, b, kv, d))
    pos = jnp.asarray([0, 1, 31, 32, 33, 63, 64, 5], jnp.int32)
    tables = rows = ()
    if scaled:      # the rows and their scales as write_rows makes them
        tables = tuple(jnp.abs(jax.random.normal(key, (n_l, b, kv, s))) + 1
                       for key in ks[4:6])
        real = [jax.random.normal(key, (n_l, b, 1, kv, d)) for key in ks[6:8]]
        (k_rows, sk), (v_rows, sv) = (quantize_kv(x[:, :, 0]) for x in real)
        rows = (sk, sv)
    mesh = make_mesh(tp=tp, dp=dp, devices=jax.devices()[:tp * dp])
    got_k, got_v, *got_tables = flash_decode.append_rows_sharded(
        k, v, k_rows, v_rows, pos, *tables, *rows, mesh=mesh, n_heads=h,
        interpret=True)
    slots = jnp.arange(b)
    for got, cache, new in ((got_k, k, k_rows), (got_v, v, v_rows)):
        want = cache.at[:, slots, :, pos].set(jnp.moveaxis(new, 1, 0),
                                              mode="drop")
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
    if not scaled:
        assert got_tables == [None, None]
        return
    # the select's tables: write_rows where no kernel answers
    want = llama.write_rows(llama.KVCache(k, v, pos, *tables), *real,
                            pos[:, None], pos + 1, h)
    for got, e, old in zip(got_tables, (want.k_scale, want.v_scale), tables):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(e))
        assert (np.asarray(got) != np.asarray(old)).sum() == n_l * 7 * kv


# -- token exactness: contiguous engine ---------------------------------------

@pytest.fixture
def compiled_not_loaded():
    """The persistent compile cache off for one test. XLA's CPU backend
    compiles the dp=4 x tp=2 engine's decode block right and loads it
    wrong: read back from ``.jax_cache``, the eight devices' threads
    take the step's all-reduces over ``tp`` and over ``dp`` in different
    orders, wait for each other in the rendezvous, and XLA aborts the
    process after 60 s (ROADMAP.md, D7). The chip's backend is not
    known to."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
@pytest.mark.parametrize("tp,kv", [(2, 2), (4, 4), (4, 8)])
def test_mesh_contiguous_token_exact(tp, kv, kv_dtype, tiny_params,
                                     tiny4_params, tiny8_params,
                                     monkeypatch, compiled_not_loaded):
    """shard_map'd flash prefill + flash-decode (each device walking its
    own KV-head and batch shard of the stacked cache) and the step's
    sharded write on a dp x tp mesh are token-exact vs the
    single-device jnp-reference engine, fp and int8 KV, one KV head a
    shard and two (tp=4 over eight: the shape Mixtral meets), and the
    sharded kernel forms actually dispatch."""
    cfg, params = {2: (TINY, tiny_params), 4: (TINY4, tiny4_params),
                   8: (TINY8, tiny8_params)}[kv]
    want = _tokens(_engine(cfg, params, kv_dtype=kv_dtype))

    _interpret_on(monkeypatch)
    prefills = _counted(monkeypatch, flash, "flash_prefill_sharded")
    decodes = _counted(monkeypatch, flash_decode, "flash_decode_sharded")
    appends = _counted(monkeypatch, flash_decode, "append_rows_sharded")
    mesh = make_mesh(tp=tp, dp=8 // tp)
    got = _tokens(_engine(cfg, shard_params(params, mesh), mesh=mesh,
                          kv_dtype=kv_dtype))
    assert got == want
    # kernel paths, not a silent fallback
    assert prefills and decodes and appends


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
@pytest.mark.parametrize("tp", [2, 4])
def test_mesh_paged_token_exact(tp, kv_dtype, tiny_params, tiny4_params,
                                monkeypatch):
    """shard_map'd paged attention over the block pool is token-exact vs
    the single-device reference, fp and int8 KV, tp=2 and tp=4."""
    cfg, params = _cfg_params(tp, tiny_params, tiny4_params)
    want = _tokens(_engine(cfg, params, kv_dtype=kv_dtype, paged=True))

    _interpret_on(monkeypatch)
    decodes = _counted(monkeypatch, paged_attention, "paged_decode_sharded")
    mesh = make_mesh(tp=tp, dp=8 // tp)
    got = _tokens(_engine(cfg, shard_params(params, mesh), mesh=mesh,
                          kv_dtype=kv_dtype, paged=True))
    assert got == want
    assert decodes


# -- token exactness: speculative verify over the paged pool ------------------

@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_mesh_paged_spec_verify_token_exact(kv_dtype, tiny_params,
                                            monkeypatch):
    """Speculative decoding on the mesh: the shard_map'd verify-window
    kernel accepts/rejects exactly like the spec-less single-device
    engine (same tokens), and the verify pass actually runs."""
    want = _tokens(_engine(TINY, tiny_params, kv_dtype=kv_dtype),
                   prompts=[REP], n=30)

    _interpret_on(monkeypatch)
    windows = _counted(monkeypatch, paged_attention, "paged_window_sharded")
    mesh = make_mesh(tp=2, dp=4)
    eng = _engine(TINY, shard_params(tiny_params, mesh), mesh=mesh,
                  kv_dtype=kv_dtype, paged=True, spec_decode_k=3)
    try:
        got = [eng.generate(REP, max_new_tokens=30).tokens()]
        st = eng.stats()["spec_decode"]
        assert st["emitted"] >= st["windows"] > 0  # verify pass ran
    finally:
        eng.close()
    assert got == want
    assert windows


# -- structural: mesh paged serving never gathers a dense pool view -----------

def test_mesh_paged_never_materializes_dense_pool(tiny_params, monkeypatch):
    """The mesh paged decode/verify path must stream blocks through the
    table inside the kernel — gather_blocks (the reference's dense
    [B, S, KV, hd] materialization, exactly what paging exists to avoid)
    raises if any mesh serving trace reaches it."""
    _interpret_on(monkeypatch)

    def _boom(pool, table):
        raise AssertionError(
            "mesh paged serving materialized a dense pool view")

    monkeypatch.setattr(paged_attention, "gather_blocks", _boom)
    mesh = make_mesh(tp=2, dp=4)
    eng = _engine(TINY, shard_params(tiny_params, mesh), mesh=mesh,
                  paged=True, spec_decode_k=3)
    try:
        out = eng.generate(REP, max_new_tokens=30).tokens()
        assert len(out) == 30
        assert eng.stats()["spec_decode"]["windows"] > 0
    finally:
        eng.close()


# -- head-splitting tp: jnp fallback (tp-only) or typed refusal (tp+data) -----

def test_head_splitting_tp_only_falls_back_token_exact(tiny_params,
                                                       monkeypatch):
    """tp=4 over tiny's 2 KV heads on a tp-ONLY mesh is legal: the auto
    dispatchers decline shard_map (a split head has no local kernel
    form) and serve the GSPMD-partitioned jnp reference, token-exact."""
    want = _tokens(_engine(TINY, tiny_params))

    _interpret_on(monkeypatch)
    mesh = make_mesh(tp=4, devices=jax.devices()[:4])
    got = _tokens(_engine(TINY, shard_params(tiny_params, mesh), mesh=mesh))
    assert got == want


@pytest.mark.parametrize("paged", [False, True])
def test_head_splitting_tp_with_data_axes_refused(tiny_params, paged):
    """tp splitting a KV head COMBINED with data axes is the verified
    wrong-logits configuration (PR 13): construction raises a typed
    ShardingConfigError naming the offending TPU_SHARDING row, before
    any request can be accepted."""
    mesh = make_mesh(tp=4, dp=2)
    with pytest.raises(ShardingConfigError) as exc:
        _engine(TINY, shard_params(tiny_params, mesh), mesh=mesh,
                paged=paged)
    assert "TPU_SHARDING='dp=2,tp=4'" in str(exc.value)
    assert exc.value.sharding_row == "dp=2,tp=4"
    assert "n_kv_heads=2" in str(exc.value)
    # typed AND a ValueError: config-validation callers keep working
    assert isinstance(exc.value, ValueError)
