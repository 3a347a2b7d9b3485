"""Ring attention vs the dense reference on the virtual 8-device mesh.

The sequence axis is genuinely sharded (shard_map over sp) and K/V
shards rotate with ppermute — these tests pin the collective path's
numerics to ops.attention.causal_attention exactly (same masking
semantics, including padded-query rows)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.attention import causal_attention
from gofr_tpu.ops.ring_attention import make_ring_attention
from gofr_tpu.parallel import make_mesh

B, S, H, KV, D = 4, 64, 8, 4, 32


def _mk(key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("axes", [{"dp": 2, "sp": 4}, {"sp": 8}])
def test_ring_matches_dense_reference(axes):
    mesh = make_mesh(**axes)
    attend = make_ring_attention(mesh)
    q, k, v = _mk(jax.random.PRNGKey(0))
    lengths = jnp.asarray([64, 37, 1, 50], jnp.int32)
    mask = jnp.arange(S)[None, :] < lengths[:, None]

    got = attend(q, k, v, lengths)
    want = causal_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_no_lengths_full_causal():
    mesh = make_mesh(dp=2, sp=4)
    attend = make_ring_attention(mesh)
    q, k, v = _mk(jax.random.PRNGKey(1))
    got = attend(q, k, v)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_heads_shard_over_tp():
    # tp>1 mesh: heads divide tp, so q/k/v stay head-sharded instead of
    # all-gathering — numerics must be identical either way
    mesh = make_mesh(tp=2, sp=2, dp=2)
    attend = make_ring_attention(mesh)
    q, k, v = _mk(jax.random.PRNGKey(4))
    lengths = jnp.asarray([64, 10, 33, 64], jnp.int32)
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    got = attend(q, k, v, lengths)
    want = causal_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_non_dividing_shapes_fall_back_dense():
    """Ragged batch / odd sequence must not crash in shard_map — the
    attend falls back to the dense reference at trace time (layout is a
    performance choice, never a shape contract)."""
    mesh = make_mesh(dp=2, sp=4)
    attend = make_ring_attention(mesh)
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (3, 30, H, D), jnp.float32)  # 3 % 2, 30 % 4
    k = jax.random.normal(ks[1], (3, 30, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (3, 30, KV, D), jnp.float32)
    lengths = jnp.asarray([30, 7, 16], jnp.int32)
    mask = jnp.arange(30)[None, :] < lengths[:, None]
    got = attend(q, k, v, lengths)
    want = causal_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_train_step_sp_mesh_ring_matches_dp_only():
    """An sp>1 mesh trains through ring attention (seq_parallel='auto')
    and must produce the same loss/gradient step as a dp-only mesh on
    identical data — sequence parallelism is a layout choice, never a
    numerics choice."""
    from gofr_tpu import parallel
    from gofr_tpu.models.common import LLAMA_CONFIGS

    cfg = LLAMA_CONFIGS["tiny"].with_(n_layers=2, max_seq=64)
    opt = parallel.default_optimizer(lr=1e-3, warmup=1, total_steps=10)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 64), 0,
                                cfg.vocab_size)
    lengths = jnp.asarray([64, 40, 64, 10], jnp.int32)

    losses = {}
    for name, axes in (("dp", {"dp": 4, "fsdp": 2}),
                       ("sp", {"dp": 2, "sp": 4})):
        mesh = parallel.make_mesh(**axes)
        state = parallel.init_train_state(cfg, jax.random.PRNGKey(0),
                                          mesh, opt)
        step = parallel.make_train_step(cfg, opt, mesh, remat=True)
        state, metrics = step(state, tokens, lengths)
        losses[name] = float(metrics["loss"])
        assert jnp.isfinite(losses[name])
    assert abs(losses["dp"] - losses["sp"]) < 1e-4, losses


def test_ring_under_jit_compiles_once_and_matches():
    # the production use: ring attend traced inside a jitted step
    mesh = make_mesh(sp=8)
    attend = make_ring_attention(mesh)
    q, k, v = _mk(jax.random.PRNGKey(2))
    lengths = jnp.full((B,), S, jnp.int32)

    jitted = jax.jit(lambda q, k, v, ln: attend(q, k, v, ln) * 1.0)
    got = jitted(q, k, v, lengths)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
