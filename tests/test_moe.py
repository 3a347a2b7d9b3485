"""Mixture-of-experts Llama variant: routing correctness, serving, and
sharded training on the virtual mesh.

The oracle for routing math needs no external reference: with every
expert's weights set IDENTICAL to a dense model's FFN, the top-k
combine (weights renormalized to sum 1) must reproduce the dense model
EXACTLY, whatever the router chooses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, llama

MOE = LLAMA_CONFIGS["tiny-moe"]
DENSE = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def moe_params():
    return llama.init(MOE, jax.random.PRNGKey(3))


def test_identical_experts_reproduce_dense_model():
    dense = llama.init(DENSE, jax.random.PRNGKey(1))
    moe = llama.init(MOE, jax.random.PRNGKey(1))
    # overwrite every expert with the dense FFN weights
    lw = dict(moe["layers"])
    for name in ("w_gate", "w_up", "w_down"):
        lw[name] = jnp.broadcast_to(
            dense["layers"][name][:, None], lw[name].shape)
    for name in ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm"):
        lw[name] = dense["layers"][name]
    moe = {**moe, "layers": lw, "embedding": dense["embedding"],
           "final_norm": dense["final_norm"],
           "lm_head": dense["lm_head"]}

    tokens = jnp.asarray([[5, 17, 42, 7, 9, 1]], jnp.int32)
    got = llama.forward(moe, MOE, tokens)
    want = llama.forward(dense, DENSE, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_moe_generation_through_engine(moe_params):
    from gofr_tpu.tpu import GenerationEngine

    eng = GenerationEngine(MOE, moe_params, slots=2, max_seq=64,
                           prompt_buckets=(8, 16))
    try:
        got = eng.generate([5, 17, 42, 7], max_new_tokens=8).tokens()
        # oracle: naive cache-free greedy with the same forward
        toks = [5, 17, 42, 7]
        for _ in range(8):
            logits = llama.forward(moe_params, MOE,
                                   jnp.asarray([toks], jnp.int32))
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert got == toks[4:]
    finally:
        eng.close()


def test_moe_routing_is_selective(moe_params):
    """Different tokens must route to different experts (a collapsed
    router would make MoE pointless); with random init the top-1 expert
    varies across positions."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0,
                                MOE.vocab_size)
    x = moe_params["embedding"][tokens].astype(MOE.jdtype)
    h = x  # router sees the embedded stream at layer 0 (pre-norm skipped
    # — selectivity, not exactness, is the property under test)
    probs = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", h, moe_params["layers"]["router"][0]),
        axis=-1)
    top1 = np.asarray(jnp.argmax(probs, -1)).ravel()
    assert len(set(top1.tolist())) > 1


def test_moe_sharded_train_step():
    from gofr_tpu import parallel

    mesh = parallel.make_mesh(dp=2, fsdp=2, tp=2)
    opt = parallel.default_optimizer(lr=1e-3, warmup=1, total_steps=10)
    state = parallel.init_train_state(MOE, jax.random.PRNGKey(0), mesh, opt)
    step = parallel.make_train_step(MOE, opt, mesh, remat=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                MOE.vocab_size)
    lengths = jnp.full((4,), 32, jnp.int32)
    losses = []
    for _ in range(5):
        state, m = step(state, tokens, lengths)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses  # it learns (past lr warmup)
    # expert weights actually sharded: hidden dim over tp
    assert state.params["layers"]["w_gate"].sharding.spec[3] == "tp"


def test_moe_expert_parallel_train_step():
    """Expert parallelism: experts split over the ep axis, batch split
    over (dp, fsdp, ep) — GSPMD's partition of the grouped-dispatch
    scatter/gather is the MoE all-to-all. The step must run, learn, and
    actually shard the expert dim."""
    from gofr_tpu import parallel

    mesh = parallel.make_mesh(dp=2, ep=2, tp=2)
    cfg = MOE.with_(moe_capacity_factor=2.0)  # grouped dispatch path
    opt = parallel.default_optimizer(lr=1e-3, warmup=1, total_steps=10)
    state = parallel.init_train_state(cfg, jax.random.PRNGKey(0), mesh, opt)
    step = parallel.make_train_step(cfg, opt, mesh, remat=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size)
    lengths = jnp.full((8,), 32, jnp.int32)
    losses = []
    for _ in range(5):
        state, m = step(state, tokens, lengths)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # expert dim [L, E, D, F] over ep; hidden still over tp
    spec = state.params["layers"]["w_gate"].sharding.spec
    assert spec[1] == "ep" and spec[3] == "tp"
    # adam moments mirror the param sharding (ep included)
    mu = state.opt_state[1][0].mu["layers"]["w_gate"]
    assert mu.sharding.spec[1] == "ep"


def test_moe_expert_parallel_forward_matches_unsharded(moe_params):
    """ep-sharded grouped dispatch must be numerically identical to the
    single-device reference: sharding is an execution layout, never a
    semantics change."""
    from gofr_tpu import parallel

    cfg = MOE.with_(moe_capacity_factor=float(MOE.n_experts))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 16), 0,
                                cfg.vocab_size)
    want = llama.forward(moe_params, cfg, tokens)

    mesh = parallel.make_mesh(ep=4, tp=2)
    sharded = parallel.shard_params(moe_params, mesh)
    fn = jax.jit(lambda p, t: llama.forward(p, cfg, t))
    got = fn(sharded, jax.device_put(
        tokens, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(parallel.DATA_AXES))))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_moe_expert_parallel_serving(moe_params):
    """An ep x tp x dp mesh serves an MoE model through the generation
    engine: grouped dispatch at prefill (per-request, isolation-safe),
    dense forced at decode — greedy streams must match the unsharded
    engine exactly."""
    from gofr_tpu import parallel
    from gofr_tpu.tpu import GenerationEngine

    cfg = MOE.with_(moe_capacity_factor=float(MOE.n_experts))
    prompt = [5, 17, 42, 7, 3]
    ref_eng = GenerationEngine(cfg, moe_params, slots=2, max_seq=64,
                               prompt_buckets=(8, 16))
    try:
        want = ref_eng.generate(prompt, max_new_tokens=6).tokens()
    finally:
        ref_eng.close()

    mesh = parallel.make_mesh(ep=2, tp=2, dp=2)
    eng = GenerationEngine(cfg, parallel.shard_params(moe_params, mesh),
                           slots=2, max_seq=64, prompt_buckets=(8, 16),
                           mesh=mesh)
    try:
        assert eng.generate(prompt, max_new_tokens=6).tokens() == want
        spec = eng.params["layers"]["w_gate"].sharding.spec
        assert spec[1] == "ep"
    finally:
        eng.close()


def test_moe_int8_quantized_serving(moe_params):
    """TPU_QUANT=int8 must actually quantize the 4D expert stacks (the
    bulk of an MoE model's weights) and serve through them."""
    from gofr_tpu.ops.quant import QuantizedLinear
    from gofr_tpu.tpu import GenerationEngine, maybe_quantize

    q = maybe_quantize(moe_params, True)
    for name in ("w_gate", "w_up", "w_down"):
        assert isinstance(q["layers"][name], QuantizedLinear), name
    # int8 quantization error must stay small at the logits level
    tokens = jnp.asarray([[5, 17, 42, 7]], jnp.int32)
    dense_logits = llama.forward(moe_params, MOE, tokens)
    quant_logits = llama.forward(q, MOE, tokens)
    top_dense = np.asarray(jnp.argsort(dense_logits[0, -1]))[-3:]
    top_quant = np.asarray(jnp.argsort(quant_logits[0, -1]))[-3:]
    assert top_dense[-1] == top_quant[-1]  # argmax survives int8

    eng = GenerationEngine(MOE, q, slots=2, max_seq=64, prompt_buckets=(8,))
    try:
        assert len(eng.generate([5, 17, 42], max_new_tokens=4).tokens()) == 4
    finally:
        eng.close()


def test_load_balance_loss_properties():
    from gofr_tpu.parallel import load_balance_loss

    L, B, S, E = 2, 2, 8, 4
    lengths = jnp.asarray([8, 5], jnp.int32)
    uniform = jnp.full((L, B, S, E), 1.0 / E, jnp.float32)
    assert abs(float(load_balance_loss(uniform, lengths)) - 1.0) < 1e-5
    collapsed = jax.nn.one_hot(jnp.zeros((L, B, S), jnp.int32), E)
    assert abs(float(load_balance_loss(collapsed, lengths)) - E) < 1e-5


def test_moe_train_reports_aux_loss():
    from gofr_tpu import parallel

    mesh = parallel.make_mesh(dp=4, fsdp=2)
    opt = parallel.default_optimizer(lr=1e-3, warmup=1, total_steps=10)
    state = parallel.init_train_state(MOE, jax.random.PRNGKey(0), mesh, opt)
    step = parallel.make_train_step(MOE, opt, mesh, remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                MOE.vocab_size)
    state, m = step(state, tokens, jnp.full((4,), 32, jnp.int32))
    aux = float(m["aux_loss"])
    assert np.isfinite(aux) and 0.9 <= aux <= MOE.n_experts + 0.1


def test_grouped_dispatch_matches_dense_with_ample_capacity(moe_params):
    """capacity_factor high enough that nothing drops => grouped dispatch
    must reproduce dense dispatch exactly (same experts, same weights,
    same combine — only the execution layout differs)."""
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                                MOE.vocab_size)
    dense_cfg = MOE
    grouped_cfg = MOE.with_(moe_capacity_factor=float(MOE.n_experts))
    want = llama.forward(moe_params, dense_cfg, tokens)
    got = llama.forward(moe_params, grouped_cfg, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and with int8 experts: both dispatch layouts consume the same
    # QuantizedLinear stacks and must still agree exactly
    from gofr_tpu.tpu import maybe_quantize

    q = maybe_quantize(moe_params, True)
    np.testing.assert_allclose(
        np.asarray(llama.forward(q, grouped_cfg, tokens)),
        np.asarray(llama.forward(q, dense_cfg, tokens)),
        rtol=2e-5, atol=2e-5)


def test_grouped_dispatch_drops_over_capacity():
    """A capacity factor near zero forces drops: outputs shrink toward
    the residual stream (the FFN contribution zeroes for dropped
    assignments) but stay finite and the model still runs end to end."""
    cfg = MOE.with_(moe_capacity_factor=0.05)
    params = llama.init(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0,
                                cfg.vocab_size)
    logits = llama.forward(params, cfg, tokens)
    assert np.isfinite(np.asarray(logits)).all()


def test_grouped_dispatch_trains_sharded():
    from gofr_tpu import parallel

    cfg = MOE.with_(moe_capacity_factor=2.0)
    mesh = parallel.make_mesh(dp=2, fsdp=2, tp=2)
    opt = parallel.default_optimizer(lr=1e-3, warmup=1, total_steps=10)
    state = parallel.init_train_state(cfg, jax.random.PRNGKey(0), mesh, opt)
    step = parallel.make_train_step(cfg, opt, mesh, remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    state, m = step(state, tokens, jnp.full((4,), 32, jnp.int32))
    assert np.isfinite(float(m["loss"])) and np.isfinite(float(m["aux_loss"]))


def test_grouped_dispatch_padding_cannot_evict_real_tokens(moe_params):
    """A padded neighbor's position in the batch must be invisible to a
    real sequence: capacity claims are token-major, so an UNMASKED pad
    sequence placed first would grab buffer slots ahead of the real
    tokens (different drops => different logits than when it rides
    last). With lengths masking wired through, pads claim nothing and
    the real sequence's logits are identical under batch reordering."""
    cfg = MOE.with_(moe_capacity_factor=1.0)
    rng = np.random.default_rng(9)
    real = rng.integers(1, cfg.vocab_size, (1, 16)).astype(np.int32)
    pad_seq = np.zeros((1, 16), np.int32)

    pad_first = llama.forward(
        moe_params, cfg, jnp.asarray(np.concatenate([pad_seq, real])),
        jnp.asarray([1, 16], jnp.int32))
    pad_last = llama.forward(
        moe_params, cfg, jnp.asarray(np.concatenate([real, pad_seq])),
        jnp.asarray([16, 1], jnp.int32))
    np.testing.assert_allclose(np.asarray(pad_first[1]),
                               np.asarray(pad_last[0]),
                               rtol=2e-5, atol=2e-5)


def test_score_batches_preserve_request_isolation(moe_params):
    """The engine's coalesced ``score`` program must not let one
    request's prompt change another's logits. Grouped dispatch WOULD
    (cross-batch capacity eviction); multi_request_serving_config
    forces dense for such programs — sweep request 0's prompt and pin
    request 1's scores (the same invariant decode_step enforces,
    applied to the batched-forward path)."""
    cfg = MOE.with_(moe_capacity_factor=1.0)
    serving = llama.multi_request_serving_config(cfg)
    assert serving.moe_capacity_factor == 0.0
    # per-request programs keep grouped dispatch untouched
    assert llama.multi_request_serving_config(MOE) is MOE
    pinned = jnp.asarray([7, 42, 3, 9], jnp.int32)
    lens = jnp.asarray([4, 4], jnp.int32)
    base = None
    for other in (0, 7, 101, 200):
        toks = jnp.stack([jnp.full((4,), other, jnp.int32), pinned])
        logits = llama.forward(moe_params, serving, toks, lens)
        if base is None:
            base = np.asarray(logits[1])
        else:
            np.testing.assert_allclose(np.asarray(logits[1]), base,
                                       rtol=1e-6, atol=1e-6)


def test_grouped_moe_decode_preserves_slot_isolation(moe_params):
    """decode_step must force dense dispatch for MoE: grouped capacity
    claims at T=B would let slot 0's token evict slot 1's expert
    assignment — one request's output changing with an unrelated batch
    occupant breaks the engine's slot-isolation invariant."""
    cfg = MOE.with_(moe_capacity_factor=1.0)
    cache = llama.init_cache(cfg, 2, 32)
    cache = cache._replace(lengths=jnp.asarray([4, 4], jnp.int32))
    base = None
    for other in (0, 7, 101, 200):  # sweep slot 0's token
        toks = jnp.asarray([other, 42], jnp.int32)
        logits, _ = llama.decode_step(moe_params, cfg, toks, cache)
        if base is None:
            base = np.asarray(logits[1])
        else:
            np.testing.assert_allclose(np.asarray(logits[1]), base,
                                       rtol=1e-6, atol=1e-6)


# -- the dense dispatch's one collective on a tp mesh (llama._combine_experts) --

# eight experts beside a batch of 5 x 3 and widths of 64 and 128 / 4: an
# 8 in a compiled collective's shape is the expert axis and nothing else
TP = MOE.with_(name="tiny-moe-tp", n_experts=8)
TP_B, TP_S = 5, 3
COLLECTIVE = (r"= \(?(\w+\[[\d,]*\])\S* (all-reduce|all-gather|"
              r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\(")


def _tp_layers(stacks: str, dtype: str):
    """``TP``'s layer stacks with the experts in bfloat16 or int8, the
    activations in ``dtype``, and an input [5, 3, 64] of that type."""
    from gofr_tpu.tpu.checkpoint import maybe_quantize

    cfg = TP.with_(dtype=dtype)
    layers = llama.init(TP.with_(dtype="bfloat16"),
                        jax.random.PRNGKey(11))["layers"]
    experts = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
    layers = {**layers, **maybe_quantize(experts, stacks == "int8")}
    h = jax.random.normal(jax.random.PRNGKey(12), (TP_B, TP_S, TP.dim),
                          jnp.float32).astype(cfg.jdtype)
    return cfg, layers, h


def _moe_layer0(cfg, mesh=None):
    """jit of ``_moe_ffn`` on layer 0 of the stacks, which arrive
    sharded as ``parallel.shard_params`` places them."""
    def run(layers, h):
        lw = jax.tree_util.tree_map(lambda a: a[0], layers)
        return llama._moe_ffn(h, lw, cfg, None, mesh)[0]

    return jax.jit(run)


def _tp4():
    from gofr_tpu import parallel

    return parallel.make_mesh(tp=4, devices=jax.devices()[:4])


@pytest.mark.parametrize("stacks", ["int8", "bfloat16"])
def test_tp_experts_cross_the_chips_combined(stacks):
    """Compiled for tp=4, the expert layer holds one collective: the
    all-reduce of [B, S, D] float32 shares. Left to GSPMD it was
    ``f32[5,3,8,64]``, straight after ``w_down``."""
    import re

    from gofr_tpu import parallel

    cfg, layers, h = _tp_layers(stacks, "bfloat16")
    mesh = _tp4()
    text = _moe_layer0(cfg, mesh).lower(
        parallel.shard_params(layers, mesh), h).compile().as_text()
    found = re.findall(COLLECTIVE, text)
    assert found == [(f"f32[{TP_B},{TP_S},{TP.dim}]", "all-reduce")]


@pytest.mark.parametrize("stacks", ["int8", "bfloat16"])
def test_tp_experts_equal_the_unsharded_layer(stacks):
    """Float32 activations, so that the one rounding hides nothing: the
    four chips' shares add up to the unsharded sum over ``f`` but for
    the order of a float32 addition."""
    from gofr_tpu import parallel

    cfg, layers, h = _tp_layers(stacks, "float32")
    mesh = _tp4()
    want = _moe_layer0(cfg)(layers, h)
    got = _moe_layer0(cfg, mesh)(parallel.shard_params(layers, mesh), h)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def _plain_mixtral_ffn(h, lw, k: int):
    """Float32 ``jax.numpy`` Mixtral block: softmax over the router's
    logits, top-k, renormalise, SwiGLU an expert, the weighted sum."""
    from gofr_tpu.ops.quant import QuantizedLinear

    def f32(w):
        if isinstance(w, QuantizedLinear):
            return w.w.astype(jnp.float32) * w.scale[..., None, :]
        return w.astype(jnp.float32)

    h = h.astype(jnp.float32)
    probs = jax.nn.softmax(h @ f32(lw["router"]), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / topv.sum(-1, keepdims=True)
    gate, up, down = (f32(lw[n]) for n in ("w_gate", "w_up", "w_down"))
    out = jnp.zeros_like(h)
    for e in range(gate.shape[0]):
        y = (jax.nn.silu(h @ gate[e]) * (h @ up[e])) @ down[e]
        out += jnp.where(topi == e, topv, 0.0).sum(-1)[..., None] * y
    return out


@pytest.mark.parametrize("stacks", ["int8", "bfloat16"])
def test_tp_experts_no_further_from_plain_float32(stacks, monkeypatch):
    """Against the plain layer the new tail, on the mesh, is where the
    parent's was (each expert's output cast to the activations' type,
    then the weighted sum). In float32, as XLA's CPU backend has no
    batched bfloat16 x bfloat16 = float32 dot to run either with: the
    one rounding to bfloat16 where the parent made nine is the chip's."""
    from gofr_tpu import parallel

    def cast_an_expert(gated, w_down, combine, mesh):
        out = llama._expert_mm(gated, w_down, "bsef,efd->bsed")
        return jnp.einsum("bsed,bse->bsd", out, combine.astype(out.dtype))

    cfg, layers, h = _tp_layers(stacks, "float32")
    mesh = _tp4()
    want = _plain_mixtral_ffn(
        h, jax.tree_util.tree_map(lambda a: a[0], layers),
        cfg.experts_per_token)
    got = _moe_layer0(cfg, mesh)(parallel.shard_params(layers, mesh), h)
    monkeypatch.setattr(llama, "_combine_experts", cast_an_expert)
    was = _moe_layer0(cfg)(layers, h)

    def off(x):
        return float(jnp.abs(x - want).max() / jnp.abs(want).max())

    print(f"max |layer - plain| / max |plain|: {off(got):.3g} on the "
          f"mesh, {off(was):.3g} the parent's tail")
    assert 0 < off(got) <= max(off(was), 1e-6) < 1e-5


# -- the routed prompt path (llama._routed_experts -> moe.experts) -------------

# float32 activations: XLA's CPU backend has no batched bfloat16 x
# bfloat16 = float32 dot for the dense dispatch to run with
ROUTED = MOE.with_(name="tiny-moe-f32", dtype="float32", max_seq=512)
ROUTED_T = (129, 256, 512)


def _routed_layers(cfg, stacks: str, router: str = "drawn"):
    """``cfg``'s layer stacks with the experts plain or int8; with
    ``router`` "flat" every token ties on every expert, and the top-k
    gives them all experts 0 .. k-1."""
    from gofr_tpu.tpu.checkpoint import maybe_quantize

    layers = llama.init(cfg, jax.random.PRNGKey(5))["layers"]
    experts = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
    layers = {**layers, **maybe_quantize(experts, stacks == "int8")}
    if router == "flat":
        layers["router"] = jnp.zeros_like(layers["router"])
    return layers


def _holes(T: int):
    """valid [1, T]: a hole of 21 tokens from 50 and 37 of padding at the
    end. The dispatch's blocks are 128 rows and an expert's rows
    are the valid tokens that chose it in order, so both land inside
    blocks, never on an edge."""
    t = np.arange(T)
    return jnp.asarray(((t < T - 37) & ~((t >= 50) & (t < 71)))[None])


def _ffn_layer0(cfg, routed: bool, mesh=None):
    """jit of ``_moe_ffn`` on layer 0: handed the stacks whole beside
    the index (the serving prompt programs past 128 tokens), or the
    layer's slice of them (everything else)."""
    def run(layers, h, valid):
        xs, whole = (llama._scanned(layers, cfg, h.shape[0] * h.shape[1])
                     if routed else (layers, None))
        assert (whole is not None) == routed
        lw = jax.tree_util.tree_map(lambda a: a[0], xs)
        return llama._moe_ffn(h, llama._handed(lw, whole), cfg, valid,
                              mesh)[0]

    return jax.jit(run)


def _stream(cfg, T: int, seed: int = 21):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, T, cfg.dim),
                             jnp.float32).astype(cfg.jdtype)


@pytest.mark.parametrize("stacks", ["int8", "float32"])
@pytest.mark.parametrize("T", ROUTED_T)
def test_routed_prompt_path_equals_the_dense_dispatch(T, stacks):
    """Past 128 tokens a serving prompt program hands the layer the
    expert stacks whole, and the block dispatch gives every valid token
    what the dense dispatch gives it; a row that is no token is not
    dispatched and comes back zero."""
    layers = _routed_layers(ROUTED, stacks)
    h, valid = _stream(ROUTED, T), _holes(T)
    want = _ffn_layer0(ROUTED, routed=False)(layers, h, valid)
    got = _ffn_layer0(ROUTED, routed=True)(layers, h, valid)
    assert got.dtype == want.dtype == jnp.float32
    keep = np.asarray(valid[0])
    np.testing.assert_allclose(np.asarray(got)[0, keep],
                               np.asarray(want)[0, keep],
                               rtol=1e-5, atol=1e-6)
    assert keep.sum() < T and not np.asarray(got)[0, ~keep].any()
    assert np.abs(np.asarray(want)[0, keep]).max() > 1e-3


@pytest.mark.parametrize("k,router", [(2, "drawn"), (2, "flat"), (1, "flat")])
def test_routed_prompt_path_drops_nothing_at_any_imbalance(k, router):
    """No capacity: with a flat router every one of 512 tokens goes to
    experts 0 and 1 (k = 1: all to expert 0, four blocks of one expert
    of eight and none of the others) and each still gets the dense dispatch's
    result."""
    cfg = ROUTED.with_(experts_per_token=k)
    layers = _routed_layers(cfg, "float32", router)
    h = _stream(cfg, 512)
    want = _ffn_layer0(cfg, routed=False)(layers, h, None)
    got = _ffn_layer0(cfg, routed=True)(layers, h, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(want)).min(axis=-1).max() > 0


@pytest.mark.parametrize("T", ROUTED_T)
def test_routed_token_does_not_depend_on_its_batch_mates(T):
    """A block's rows are independent rows of one matmul: the first 100
    tokens get the same result, to the bit, whatever the rest of the
    program holds and wherever that puts them in their blocks."""
    layers = _routed_layers(ROUTED, "int8")
    run = _ffn_layer0(ROUTED, routed=True)
    a, b = _stream(ROUTED, T, 21), _stream(ROUTED, T, 22)
    b = b.at[:, :100].set(a[:, :100])
    ya, yb = run(layers, a, None), run(layers, b, None)
    np.testing.assert_array_equal(np.asarray(ya)[:, :100],
                                  np.asarray(yb)[:, :100])
    assert np.abs(np.asarray(ya)[:, 100:] - np.asarray(yb)[:, 100:]).max() > 0


@pytest.mark.parametrize("first,rest", [(128, 256), (256, 128), (256, 256)])
def test_prompt_programs_equal_forward_across_the_rule(first, rest):
    """One prompt as ``prefill_kv`` of its first bucket and
    ``prefill_chunk`` of the rest, one side of the rule each (and both
    past it): the logits at every position are ``forward``'s, which
    keeps the dense dispatch at any size."""
    cfg = ROUTED
    params = llama.init(cfg, jax.random.PRNGKey(3))
    n = first + rest
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, n), 1,
                                cfg.vocab_size)
    want = jax.jit(lambda p, t: llama.forward(p, cfg, t))(params, tokens)

    @jax.jit
    def served(p, t):
        head, k, v, lengths = llama.prefill_kv(p, cfg, t[:, :first],
                                               rope_max=cfg.max_seq)
        cache = llama.write_kv(llama.init_cache(cfg, 1), k, v,
                               (0, 0, 0, 0, 0), lengths)
        tail, _ = llama.prefill_chunk(p, cfg, t[:, first:], cache,
                                      jnp.int32(first))
        return jnp.concatenate([head, tail], axis=1)

    np.testing.assert_allclose(np.asarray(served(params, tokens)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


def _tp_routed(stacks: str):
    """``TP``'s eight experts in float32 activations, 256 tokens."""
    cfg = TP.with_(dtype="float32")
    return cfg, _routed_layers(cfg, stacks), _stream(cfg, 256), _holes(256)


@pytest.mark.parametrize("stacks", ["int8", "float32"])
def test_tp_routed_experts_cross_the_chips_once(stacks):
    """Compiled for tp=4 the routed layer holds one collective, outside
    the block loop: the all-reduce of [T, D] float32 shares."""
    import re

    from gofr_tpu import parallel

    cfg, layers, h, valid = _tp_routed(stacks)
    mesh = _tp4()
    text = _ffn_layer0(cfg, True, mesh).lower(
        parallel.shard_params(layers, mesh), h, valid).compile().as_text()
    assert re.findall(COLLECTIVE, text) == [
        (f"f32[256,{TP.dim}]", "all-reduce")]


@pytest.mark.parametrize("stacks", ["int8", "float32"])
def test_tp_routed_experts_equal_the_unsharded_layer(stacks):
    """Each chip runs the whole dispatch on its quarter of F and the four
    float32 shares add up to the unsharded layer but for the order of a
    float32 addition; the dense dispatch on the mesh agrees."""
    from gofr_tpu import parallel

    cfg, layers, h, valid = _tp_routed(stacks)
    mesh = _tp4()
    sharded = parallel.shard_params(layers, mesh)
    want = _ffn_layer0(cfg, routed=True)(layers, h, valid)
    got = _ffn_layer0(cfg, True, mesh)(sharded, h, valid)
    dense = _ffn_layer0(cfg, False, mesh)(sharded, h, valid)
    assert got.dtype == want.dtype == jnp.float32
    keep = np.asarray(valid[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got)[0, keep],
                               np.asarray(dense)[0, keep],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stacks", ["int8", "float32"])
def test_tp_routed_experts_no_further_from_plain_float32(stacks):
    """Against the plain layer the routed path on the mesh is no further
    than the dense tail on the mesh: its down product leaves the block
    loop in float32, meets the weights and the sum over k in float32 and
    is rounded after the chips' sum."""
    from gofr_tpu import parallel

    cfg, layers, h, _ = _tp_routed(stacks)
    mesh = _tp4()
    sharded = parallel.shard_params(layers, mesh)
    want = _plain_mixtral_ffn(
        h, jax.tree_util.tree_map(lambda a: a[0], layers),
        cfg.experts_per_token)
    got = _ffn_layer0(cfg, True, mesh)(sharded, h, None)
    was = _ffn_layer0(cfg, False, mesh)(sharded, h, None)

    def off(x):
        return float(jnp.abs(x - want).max() / jnp.abs(want).max())

    print(f"max |layer - plain| / max |plain|: {off(got):.3g} routed, "
          f"{off(was):.3g} the dense tail, both on the mesh")
    assert 0 < off(got) <= max(2 * off(was), 1e-6) < 1e-5


def test_routed_share_is_float32_and_the_rule_is_a_shape():
    """``moe.experts`` hands a float32 result where asked (the share a
    chip adds to the others') and the activations' type otherwise; which
    programs route is a function of shapes and the capacity factor."""
    from gofr_tpu.models import moe

    cfg = TP.with_(dtype="bfloat16")
    layers = _routed_layers(cfg, "int8")
    stacks = {k: layers[k] for k in moe.EXPERT_STACKS}
    hf = _stream(cfg, 256)[0]
    _, topv, topi = llama._route(hf, layers["router"][0], 2)
    for asked, got in ((None, jnp.bfloat16), (jnp.float32, jnp.float32)):
        y = jax.eval_shape(lambda: moe.experts(
            hf, topi, topv, stacks, 0, cfg, out_dtype=asked)[0])
        assert y.dtype == got
    assert [llama.routes(MOE, t) for t in (40, 128, 129, 512)] == \
        [False, False, True, True]
    assert not llama.routes(MOE.with_(moe_capacity_factor=1.0), 512)
    assert not llama.routes(DENSE, 512)
    # eight experts get 64 assignments each at 256 tokens, half a block
    # of 128 rows; 64 experts get 16 at 512 and keep blocks of 64
    assert [moe.expert_dispatch(TP, t)[0] for t in (40, 256, 512)] == \
        [16, 128, 128]
    assert moe.expert_dispatch(TP.with_(n_experts=64, experts_per_token=2),
                               512)[0] == 64
    said = llama.serving_stats(TP.with_(max_seq=512), 4)
    assert said == {"moe_prompt_dispatch": {
        "routed_from_tokens": 129, "path": "loop",
        "block_rows": {256: 128, 512: 128},
        "buffer_rows": {256: 1536, 512: 2048}}}
    assert llama.serving_stats(DENSE, 4) == {}


# the loss and the gradient's norm of the test below on commit 00ec294,
# the parent of the PR that made prompt programs route
PARENT_LOSS = 5.91120481
PARENT_GRAD_NORM = 7.93143940


def test_trainer_never_meets_the_block_loop():
    """``forward`` keeps the layer's slices and with them the dense
    dispatch at 256 tokens: ``jax.grad`` runs through it (the routed
    dispatch's loop has a traced trip count and no reverse mode), its
    program holds no loop but the layer scan, and loss and gradient are
    the parent commit's values."""
    params = llama.init(ROUTED, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, 256), 1,
                                ROUTED.vocab_size)

    def loss(p):
        logp = jax.nn.log_softmax(llama.forward(p, ROUTED, tokens), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp[:, :-1], tokens[:, 1:, None], axis=-1))

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree_util.tree_leaves(grads)))
    print(f"loss {float(value):.8f} gradient norm {float(norm):.8f}")
    assert "while" not in str(jax.make_jaxpr(loss)(params))
    np.testing.assert_allclose(float(value), PARENT_LOSS, rtol=1e-6)
    np.testing.assert_allclose(float(norm), PARENT_GRAD_NORM, rtol=1e-5)
    # and the serving program of the same tokens, which routes, agrees
    served = jax.jit(lambda p: llama.prefill_kv(p, ROUTED, tokens)[0])(params)
    np.testing.assert_allclose(
        np.asarray(served), np.asarray(llama.forward(params, ROUTED, tokens)),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cell", [
    "gigachat3.1-702b-int8-ep16", "solar-open2-250b-int8-ep8",
    "laguna-xs.2-int8-pp5", "lfm2-24b-a2b-int8-pp2",
    "nemotron-3-super-120b-int8-ep4", "dots3-note-prev-int8-ep8"])
def test_the_six_routed_families_keep_their_blocks(cell):
    """The block rows are a function of shapes that leaves the cells of
    ``moe_ffn``'s families where they were: 16 rows in their decode
    blocks, 64 in their prompt programs (an expert of theirs gets 13-32
    assignments at 512 tokens, under half a block of 128)."""
    import json
    import os

    from gofr_tpu.models import moe
    from gofr_tpu.models.common import ModelConfig

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           cell + ".json")) as f:
        conf = json.load(f)
    cfg = ModelConfig(**conf["model_config"])
    slots = int(conf["env"]["TPU_SLOTS"])
    assert moe.expert_dispatch(cfg, slots)[0] == 16
    assert [moe.expert_dispatch(cfg, t)[0] for t in (256, 512)] == [64, 64]
    assert 512 * cfg.experts_per_token / cfg.n_experts < 64


def test_programs_under_the_rule_scan_what_they_scanned():
    """A model without experts, an expert model's programs of 128
    positions or fewer, and one with a capacity factor hand the layer
    scan the very stacks they handed it before: their programs are the
    parent's (35 of the cells' 38 lowered texts were compared equal;
    CHANGES.md, PR 54)."""
    for cfg, tokens in ((DENSE, 512), (MOE, 128), (MOE, 40),
                        (MOE.with_(moe_capacity_factor=1.0), 512)):
        layers = llama.init(cfg, jax.random.PRNGKey(0))["layers"]
        xs, whole = llama._scanned(layers, cfg, tokens)
        assert xs is layers and whole is None
        assert llama._handed(layers, whole) is layers
    layers = llama.init(MOE, jax.random.PRNGKey(0))["layers"]
    xs, whole = llama._scanned(layers, MOE, 256)
    assert sorted(whole) == ["w_down", "w_gate", "w_up"]
    assert not set(whole) & set(xs) and xs["layer_index"].shape == (2,)


def test_engine_counts_the_positions_that_routed():
    """An engine of an expert model says what its prompt programs run and
    counts, by the dispatched program's size, the positions that routed:
    a 200-token prompt runs the 256 bucket (routed), a 50-token prompt
    the 64 bucket (every expert on every token), and both continue as
    the cache-free greedy forward does."""
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.tpu import GenerationEngine

    params = llama.init(ROUTED, jax.random.PRNGKey(3))
    m = Manager()
    register_framework_metrics(m)
    eng = GenerationEngine(ROUTED, params, slots=2, max_seq=512,
                           prompt_buckets=(64, 256), metrics=m)
    try:
        said = eng.stats()
        assert said["moe_prompt_dispatch"]["routed_from_tokens"] == 129
        assert said["scheduler"]["prefill"]["routed_pct"] is None
        for n in (200, 50):
            toks = [int(t) for t in jax.random.randint(
                jax.random.PRNGKey(n), (n,), 1, ROUTED.vocab_size)]
            got = eng.generate(toks, max_new_tokens=4).tokens()
            for _ in range(4):
                logits = llama.forward(params, ROUTED,
                                       jnp.asarray([toks], jnp.int32))
                toks.append(int(jnp.argmax(logits[0, -1])))
            assert got == toks[n:]
        pre = eng.stats()["scheduler"]["prefill"]
        assert (pre["positions"], pre["routed_positions"]) == (320, 256)
        assert pre["routed_pct"] == 80.0
        text = m.render_prometheus()
        assert "app_tpu_moe_routed_positions_total 256" in text
    finally:
        eng.close()

