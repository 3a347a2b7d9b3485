"""ICI-sharded serving tests on the virtual 8-device CPU mesh.

BASELINE config #5 (8-way-sharded Llama behind the serving stack) scaled
to test shapes: the same GenerationEngine/TPUEngine code paths run over a
real jax.sharding.Mesh; correctness is asserted against the unsharded
engine (identical greedy tokens) so the GSPMD specs can never silently
change numerics.
"""

import jax
import numpy as np
import pytest

from gofr_tpu.config import MapConfig
from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.parallel import make_mesh
from gofr_tpu.tpu import GenerationEngine, new_engine_from_config

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init(TINY, jax.random.PRNGKey(1))


def _greedy_reference(params, prompt, n):
    import jax.numpy as jnp

    toks = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, TINY, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


@pytest.mark.parametrize("axes", [{"tp": 2, "dp": 2, "fsdp": 2},
                                  {"tp": 8}])
def test_sharded_generation_matches_unsharded(tiny_params, axes):
    from gofr_tpu.parallel import shard_params

    mesh = make_mesh(**axes)
    sharded = shard_params(tiny_params, mesh)
    eng = GenerationEngine(TINY, sharded, slots=4, max_seq=64,
                           prompt_buckets=(8, 16), mesh=mesh)
    try:
        prompt = [5, 17, 42, 7]
        got = eng.generate(prompt, max_new_tokens=10).tokens()
        assert got == _greedy_reference(tiny_params, prompt, 10)
    finally:
        eng.close()


def test_sharded_cache_layout(tiny_params):
    mesh = make_mesh(tp=2, dp=4)
    from gofr_tpu.parallel import shard_params

    eng = GenerationEngine(TINY, shard_params(tiny_params, mesh), slots=4,
                           max_seq=32, prompt_buckets=(8,), mesh=mesh)
    try:
        spec = eng.cache.k.sharding.spec
        # [L, B, KV, Smax, hd]: batch over data axes, kv heads over tp
        assert spec[1] == ("dp", "fsdp", "ep")
        assert spec[2] == "tp"
        # layout must survive a generation (donation keeps shardings pinned)
        eng.generate([1, 2, 3], max_new_tokens=4).tokens()
        assert eng.cache.k.sharding.spec == spec
    finally:
        eng.close()


def test_sharded_engine_from_config_end_to_end():
    cfg = MapConfig({"TPU_MODEL": "tiny", "TPU_SHARDING": "tp=2,dp=2,fsdp=2",
                     "TPU_MAX_SEQ": "64", "TPU_SLOTS": "4",
                     "TPU_SEQ_BUCKETS": "8,16", "TPU_BATCH_BUCKETS": "1,2"})
    eng = new_engine_from_config(cfg)
    try:
        h = eng.health_check()
        assert h.details["mesh"] == {"dp": 2, "pp": 1, "fsdp": 2, "ep": 1, "sp": 1, "tp": 2}
        toks = eng.generate([3, 1, 4], max_new_tokens=5).tokens()
        assert len(toks) == 5
        logits = eng.predict("score", np.asarray([3, 1, 4], np.int32))
        assert int(np.argmax(logits)) == toks[0]
    finally:
        eng.close()


def test_sharded_bert_predict_matches_unsharded():
    base = {"TPU_MODEL": "bert-tiny", "TPU_SEQ_BUCKETS": "8,16",
            "TPU_BATCH_BUCKETS": "1,2"}
    plain = new_engine_from_config(MapConfig(base))
    sharded = new_engine_from_config(MapConfig({**base,
                                                "TPU_SHARDING": "tp=4,dp=2"}))
    try:
        toks = np.arange(1, 9, dtype=np.int32)
        np.testing.assert_allclose(plain.predict("embed", toks),
                                   sharded.predict("embed", toks),
                                   rtol=2e-5, atol=2e-5)
    finally:
        plain.close()
        sharded.close()
