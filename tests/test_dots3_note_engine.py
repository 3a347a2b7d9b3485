"""The sparse-latent family through ``GenerationEngine``: buckets, the
left-aligned chunk lattice, decode through the three-table cache, the
prefix pool, the counters and the refusals, at the ``tiny-dsa-moe``
preset, against the plain float32 reference. A module of its own beside
``test_dots3_note.py`` (the model's programs called directly): an
engine's programs are a thousand memory maps each, and tests/conftest.py
releases executables between modules, not inside one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split
from test_dots3_note import CFG, F32_TOL, K, W, _ref, _tokens, dn

from gofr_tpu.tpu import GenerationEngine


@pytest.fixture(scope="module")
def params():
    return dn.init(CFG, jax.random.PRNGKey(0))


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params):
    eng = GenerationEngine(CFG, params, slots=3, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=2,
                           prefix_store_min=16)
    yield eng
    eng.close()


def _held_to_the_reference(params, prompt, served):
    """Each served token's log-probability against the reference's,
    teacher-forced on prompt + the tokens served (the chip's check)."""
    seq = list(prompt) + [t for t, _ in served[:-1]]
    ref = _ref(params, seq, range(len(prompt) - 1, len(seq)))
    return max(abs(lp - ref[j, tok]) for j, (tok, lp) in enumerate(served))


def _generate(engine, prompt, n):
    return [(int(t), float(lp)) for t, lp in
            engine.generate(prompt, max_new_tokens=n, logprobs=True)]


@pytest.mark.parametrize("length", [5, 20, 32, 33, 70, 100])
def test_engine_against_the_reference(engine, params, length):
    """Under both masks, a bucket past both, a whole bucket, one token
    past it (two chunks, the last all padding but one), three chunks,
    four; 2 x W tokens decoded after each."""
    prompt = _tokens(length, length).tolist()
    served = _generate(engine, prompt, 2 * W)
    assert _held_to_the_reference(params, prompt, served) < F32_TOL


def test_engine_lattice_interleaved_with_other_slots_decode(engine, params):
    """Long prompts admitted while other slots decode: the decode blocks
    between their chunks write no row on a half-built table (the slot is
    parked at capacity), and the chunks leave the decoding slots' alone."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (9, 100, 14, 90, 11)]
    streams = [engine.generate(p, max_new_tokens=20, logprobs=True)
               for p in prompts]
    for p, s in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in s]
        assert len(served) == 20
        assert _held_to_the_reference(params, p, served) < F32_TOL


def test_engine_prefix_hit_restores_all_three_tables(params):
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=4,
                           prefix_store_min=16)
    try:
        prompt = _tokens(5, 70).tolist()
        miss = _generate(eng, prompt, 2 * W)
        assert eng.stats()["prefix_cache"]["hits"] == 0
        hit = _generate(eng, prompt, 2 * W)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        assert [t for t, _ in hit] == [t for t, _ in miss]
        assert max(abs(a[1] - b[1]) for a, b in zip(hit, miss)) < 1e-5
        assert _held_to_the_reference(params, prompt, hit) < F32_TOL
    finally:
        eng.close()


def test_engine_says_its_tables_and_counts_the_rows_kept(params):
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=256))
    eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                           prompt_buckets=(16,), observe=obs, metrics=m,
                           decode_block=4, decode_pipeline=1)
    try:
        eng.generate(_tokens(8, 13).tolist(), max_new_tokens=13).tokens()
        stats = eng.stats()
        events = [e for e in obs.timeline.events() if e[3] == "decode"]
        cache = eng.cache
    finally:
        eng.close()
    assert stats["window_bytes_per_slot"] * 2 == cache.ring.nbytes
    assert stats["latent_bytes_per_token"] * 64 * 2 == cache.rows.nbytes
    assert stats["index_bytes_per_token"] * 64 * 2 == cache.keys.nbytes
    assert stats["window_rows"] == W and stats["index_topk"] == K
    assert stats["moe_decode_dispatch"]["block_rows"] == 16
    assert set(stats["kv_live_rows"]) == {"full", "window"}
    # decode events: the expert layer's two counts, no states, the ring
    # rows at dispatch, no sampling, then the rows the selection kept:
    # three full layers x four steps, each min(position + 1, 16)
    assert events and all(len(e) == 14 and e[10] is None and e[12] is None
                          for e in events)
    assert [e[6] for e in events] == [13, 17, 21]
    assert [e[11] for e in events] == [8, 8, 8]
    assert [e[13] for e in events] == [
        (3 * sum(min(p + 1, K) for p in range(s, s + 4)),
         3 * sum(p + 1 for p in range(s, s + 4))) for s in (13, 17, 21)]
    args = [e["args"] for e in obs.timeline.chrome_trace()["traceEvents"]
            if e.get("cat") == "decode"]
    assert args and tuple(args[-1]["rows_kept"]) == (3 * 4 * K, 3 * 94) \
        and "states_updated" not in args[-1]


@pytest.mark.parametrize("kernel", [False, True], ids=["cpu", "interpreted"])
def test_chunk_dispatches_count_those_on_the_walk_kernel(params, monkeypatch,
                                                         kernel):
    """``app_tpu_chunk_walk_kernel_total`` beside the chunk dispatches
    of ``stats()["scheduler"]["prefill"]``: every one with
    ``GOFR_FLASH_INTERPRET=1`` (the family answers for both kinds of
    layer from what ``mla.chunk_tile`` sees), none on a CPU without;
    and the tokens served are the reference's either way."""
    from gofr_tpu.metrics import Manager, register_framework_metrics

    if kernel:
        monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("GOFR_FLASH_INTERPRET", raising=False)
    m = Manager()
    register_framework_metrics(m)
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), metrics=m)
    try:
        # one bucket: no chunk program; then 70 = 32 + 32 + the last 16
        eng.generate(_tokens(3, 20).tolist(), max_new_tokens=2).tokens()
        assert eng.stats()["scheduler"]["prefill"]["chunks"] == 0
        prompt = _tokens(70, 70).tolist()
        served = _generate(eng, prompt, W)
        said = eng.stats()["scheduler"]["prefill"]
    finally:
        eng.close()
    assert _held_to_the_reference(params, prompt, served) < F32_TOL
    assert said["chunks"] == 3
    assert said["chunks_on_walk_kernel"] == (3 if kernel else 0)
    counted = [float(line.rsplit(" ", 1)[1])
               for line in m.render_prometheus().splitlines()
               if line.startswith("app_tpu_chunk_walk_kernel_total")]
    assert sum(counted) == said["chunks_on_walk_kernel"]


@pytest.mark.parametrize("counted,tail", [
    ({"kept": (9, 12)}, (None, None, None, None, None, (9, 12))),
    ({"assigned": 5, "touched": 3, "ring": 8, "kept": (9, 12)},
     (5, 3, None, 8, None, (9, 12))),
    ({"assigned": 5, "touched": 3, "ring": 8, "sampled": 1}, (5, 3, None, 8,
                                                              1))])
def test_rows_kept_keep_their_place_in_a_decode_event(counted, tail):
    from gofr_tpu.observe.timeline import Timeline

    tl = Timeline(capacity=8)
    tl.decode_block(0.0, 1.0, (0,), 4, 7, 8, **counted)
    (event,) = tl.events()
    assert tuple(event[8:]) == tail


class _Tiers:
    host_mb, redis = 64, None


@pytest.mark.parametrize("option", [
    {"paged_blocks": 8}, {"spec_decode_k": 2}, {"lora_adapters": 2},
    {"kvcache": _Tiers()}, {"mesh": object()}, {"kv_dtype": jnp.int8},
    {"serving_role": "prefill"}, {"serving_role": "decode"},
])
def test_the_engine_refuses_what_takes_k_and_v_rows(params, option):
    from gofr_tpu.errors import UnsupportedOptions

    (name,) = option
    with pytest.raises(UnsupportedOptions, match=name) as e:
        GenerationEngine(CFG, params, slots=2, max_seq=64, **option)
    assert [opt for opt, _ in e.value.refused] == [name]
    assert dn.unsupported_options(serving_role="fused",
                                  kv_dtype=jnp.bfloat16) == []


def test_the_engine_refuses_a_capacity_that_is_not_whole_chunks(params):
    with pytest.raises(ValueError, match="whole prefill chunks"):
        GenerationEngine(CFG, params, slots=2, max_seq=72,
                         prompt_buckets=(16, 32))


def test_start_up_from_config_refuses_by_name():
    from gofr_tpu.config import MapConfig
    from gofr_tpu.tpu import new_engine_from_config

    base = {"TPU_MODEL": "tiny-dsa-moe", "TPU_SLOTS": "2",
            "TPU_MAX_SEQ": "64", "TPU_SEQ_BUCKETS": "16",
            "TPU_KV_DTYPE": "model",
            "TPU_PREFIX_CACHE": "2"}  # the host tier hangs off the pool
    for key, value in (("TPU_SPEC_DECODE", "4"),
                       ("TPU_KVCACHE_HOST_MB", "64"),
                       ("TPU_KV_DTYPE", "int8"),
                       ("TPU_SERVING_ROLE", "decode")):
        with pytest.raises(ValueError, match=key):
            new_engine_from_config(MapConfig({**base, key: value}))
    eng = new_engine_from_config(MapConfig(base))
    try:
        assert eng.generator.generate([1, 2, 3], max_new_tokens=3).tokens()
        assert eng.predict("score", [1, 2, 3]).shape == (CFG.vocab_size,)
    finally:
        eng.close()


# -- a prompt as two dispatches -------------------------------------------------

@pytest.mark.parametrize("kv_dtype,tol", [(None, F32_TOL)])
def test_a_split_admission_is_the_one_bucket_admission(params, kv_dtype, tol):
    """A prompt admitted as a whole bucket and the rest (left-aligned: this
    family's last chunk) against the same prompt in one padded bucket:
    the same greedy tokens, logprobs and cache arrays to the chunked
    tests' tolerance, and the positions counted (tests/_prefill_split.py)."""
    _prefill_split.check(CFG, params, tol=tol, kv_dtype=kv_dtype)
