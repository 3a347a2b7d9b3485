"""The routed experts' kernel (ops/moe_experts.py), interpreted on the
CPU, against the jnp loop it replaces (moe.blocks_loop) on the
same inputs: the dispatch buffer's blocks directly, then the whole
``_experts`` with the kernel switched on by GOFR_FLASH_INTERPRET."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import moe
from gofr_tpu.models.common import ModelConfig
from gofr_tpu.ops import moe_experts
from gofr_tpu.ops.quant import quantize_int8

D, F, LS = 128, 256, 2


# the experts' forms: SwiGLU as wide as the model, and the two-matrix
# relu^2 expert in a latent narrower than the model (no gate stack)
FORMS = {"swiglu": (("w_gate", "w_up", "w_down"), D),
         "relu2_in_a_latent": (("w_up", "w_down"), 64)}


def _stacks(n_held: int, quant: bool, dtype=jnp.float32, seed: int = 0,
            form: str = "swiglu"):
    """Expert stacks [LS, n_held, ...] as ``init`` lays them out, int8
    with a scale an output channel or plain, in one of ``FORMS``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    names, width = FORMS[form]
    shapes = {name: (LS, n_held, F, width) if name == "w_down"
              else (LS, n_held, width, F) for name in names}
    out = {}
    for key, (name, shape) in zip(k, shapes.items()):
        w = jax.random.normal(key, shape, jnp.float32) * shape[2] ** -0.5
        if quant:
            out[name] = quantize_int8(w, axis=2)
        else:
            out[name] = w.astype(dtype)
    return out


@pytest.fixture
def interpreted(monkeypatch):
    """``moe.blocks_kernel`` runs the kernel interpreted."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")


# (blocks of the buffer, live blocks, each block's expert): an expert
# with no block, one expert over several blocks (one fetch), nothing
# live, every block live
BUFFERS = {
    "an_expert_without_a_block": (6, 4, [0, 0, 2, 3, 3, 3]),
    "one_expert_over_every_block": (5, 5, [1, 1, 1, 1, 1]),
    "no_block_live": (4, 0, [3, 3, 3, 3]),
    "every_block_live": (4, 4, [0, 1, 2, 3]),
}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("tile", [None, 128], ids=["whole_F", "F_in_tiles"])
@pytest.mark.parametrize("bm", [16, 64])
@pytest.mark.parametrize("quant", [True, False], ids=["int8", "plain"])
@pytest.mark.parametrize("buffer", list(BUFFERS))
def test_blocks_through_the_kernel_equal_the_loop(buffer, quant, bm, tile,
                                                  form, interpreted):
    nb, live, experts = BUFFERS[buffer]
    stacks = _stacks(4, quant, form=form)
    xs = jax.random.normal(jax.random.PRNGKey(nb), (nb * bm, FORMS[form][1]))
    blk = jnp.array(experts, jnp.int32)
    n, li = jnp.int32(live), jnp.int32(1)
    want = moe.blocks_loop(xs, blk, n, stacks, li, bm)
    got = moe.blocks_kernel(xs, blk, n, stacks, li, bm, tile)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[live * bm:]).any()      # dead blocks read 0
    if live:
        assert np.asarray(got[:live * bm]).any()


def test_scales_come_in_groups_of_eight_experts(interpreted):
    """16 held experts: a block's scale row is one of a fetched group."""
    stacks = _stacks(16, True, seed=3)
    bm, experts = 16, [0, 7, 8, 9, 15]
    xs = jax.random.normal(jax.random.PRNGKey(1), (len(experts) * bm, D))
    blk, n, li = jnp.array(experts, jnp.int32), jnp.int32(5), jnp.int32(0)
    want = moe.blocks_loop(xs, blk, n, stacks, li, bm)
    got = moe.blocks_kernel(xs, blk, n, stacks, li, bm, 128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_bfloat16_rounds_no_coarser_than_the_loop(interpreted):
    """bfloat16 activations, int8 weights: against the same arithmetic in
    float32 the kernel is no further off than the loop (it rounds
    silu(g) * u once where the loop rounds g, u and their product)."""
    stacks = _stacks(4, True, seed=5)
    bm, blk = 16, jnp.array([0, 1, 3], jnp.int32)
    x32 = jax.random.normal(jax.random.PRNGKey(2), (3 * bm, D))
    n, li = jnp.int32(3), jnp.int32(1)
    exact = np.asarray(moe.blocks_loop(x32.astype(jnp.bfloat16).astype(
        jnp.float32), blk, n, stacks, li, bm))
    xs = x32.astype(jnp.bfloat16)
    loop = np.asarray(moe.blocks_loop(xs, blk, n, stacks, li, bm),
                      np.float32)
    for tile in (None, 128):
        got = moe.blocks_kernel(xs, blk, n, stacks, li, bm, tile)
        assert got.dtype == jnp.bfloat16
        err = np.abs(np.asarray(got, np.float32) - exact).max()
        assert err <= np.abs(loop - exact).max() + 1e-3
        assert err < 2 ** -7 * np.abs(exact).max()    # one bfloat16 step


def test_tile_columns_fit_the_budget():
    """The F tile from the shapes: a whole expert where two of it fit,
    else the largest divisor of F in whole lanes that does."""
    assert moe_experts.tile_columns(2048, 512, 1) == 512      # laguna
    assert moe_experts.tile_columns(4096, 1280, 1) == 640     # solar
    assert moe_experts.tile_columns(7168, 2048, 1) == 256     # gigachat
    assert moe_experts.tile_columns(7168, 2048, 2) == 128     # bfloat16
    for dim, ffn, size in ((2048, 512, 1), (4096, 1280, 1),
                           (7168, 2048, 1)):
        t = moe_experts.tile_columns(dim, ffn, size)
        assert ffn % t == 0 and t % 128 == 0
        assert 6 * dim * t * size <= moe_experts._TILE_BUDGET
    # two stacks of a latent's width: a whole expert (2 x 2.75 MB, twice)
    assert moe_experts.tile_columns(1024, 2688, 1, 2) == 2688
    assert moe_experts.tile_columns(1024, 2688, 2, 2) == 896       # of 21 x 128


@pytest.mark.parametrize("dim,ffn,dtype,stacks,want", [
    (2048, 512, jnp.bfloat16, 3, True),      # laguna: 3.1M weights
    (4096, 1280, jnp.bfloat16, 3, True),     # solar: 15.7M
    (7168, 2048, jnp.bfloat16, 3, False),    # gigachat: 44M, the loop's
    (2048, 512, jnp.float32, 3, False),      # a float32 model
    (2048, 520, jnp.bfloat16, 3, False),     # not whole lanes
    (1024, 2688, jnp.bfloat16, 2, True),     # two stacks in a latent: 5.5M
    (4096, 2816, jnp.bfloat16, 2, True),     # 23.1M in two stacks,
    (4096, 2816, jnp.bfloat16, 3, False),    # 34.6M in three
])
def test_path_is_chosen_from_backend_and_shapes(dim, ffn, dtype, stacks,
                                                want, monkeypatch):
    from gofr_tpu.ops import flash

    monkeypatch.delenv("GOFR_FLASH_INTERPRET", raising=False)
    assert not moe_experts.kernel_ok(dim, ffn, dtype, stacks)  # a CPU process
    monkeypatch.setattr(flash, "tpu_backend_ok", lambda: True)
    assert moe_experts.kernel_ok(dim, ffn, dtype, stacks) is want


# -- the whole expert layer, kernel against loop -----------------------------

CFG = ModelConfig(
    vocab_size=64, dim=D, n_layers=3, n_heads=2, n_kv_heads=2, ffn_dim=64,
    max_seq=64, dtype="float32", n_experts=16, experts_per_token=4,
    n_expert_groups=4, topk_groups=2, moe_ffn_dim=F, n_experts_held=4,
    n_dense_layers=1, kv_lora_rank=16, q_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, n_shared_experts=1,
    routed_scaling=2.5)

# (tokens, the k experts every token chooses or None for the router's
# choice, tokens that are valid or None for all)
LAYERS = {
    "an_expert_with_no_token": (40, [0, 1, 3, 8], None),
    "every_token_on_one_expert": (40, [2, 8, 9, 12], None),
    "every_choice_unheld": (40, [8, 9, 12, 13], None),
    "invalid_rows": (40, None, 10),
    "no_valid_row": (24, None, 0),
    "routed_with_unheld_experts": (40, None, None),
    "a_prompt_in_blocks_of_64": (160, None, 150),
}


# the layer's configuration a form: the dispatch as wide as the model,
# and a dispatch width unlike the model's
CFGS = {"swiglu": CFG,
        "relu2_in_a_latent": CFG.with_(moe_latent_dim=64,
                                       expert_act="relu2")}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("quant", [True, False], ids=["int8", "plain"])
@pytest.mark.parametrize("layer", list(LAYERS))
def test_expert_layer_on_the_kernel_equals_the_loop(layer, quant, form,
                                                    monkeypatch):
    T, chosen, n_valid = LAYERS[layer]
    stacks = _stacks(4, quant, seed=7, form=form)
    CFG, width = CFGS[form], FORMS[form][1]
    assert moe.expert_width(CFG) == width
    assert moe.expert_stacks(CFG) == FORMS[form][0]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(T), 3)
    h = jax.random.normal(k1, (T, width))      # what the experts read
    if chosen is None:
        # the router reads the model's width whatever the experts'
        router = jax.random.normal(k2, (D, CFG.n_experts)) * 0.3
        topi, w = moe.route(jax.random.normal(k3, (T, D)), router,
                           jnp.zeros((CFG.n_experts,)), CFG)
    else:
        topi = jnp.tile(jnp.array([chosen], jnp.int32), (T, 1))
        w = jnp.full((T, 4), 0.625)
    valid = None if n_valid is None else jnp.arange(T) < n_valid
    li = jnp.int32(1)
    assert moe.expert_dispatch(CFG, T)[0] == (16 if T <= 128 else 64)

    monkeypatch.delenv("GOFR_FLASH_INTERPRET", raising=False)
    assert not moe.experts_on_kernel(CFG)
    said = moe.serving_stats(CFG, 8)["moe_decode_dispatch"]
    assert (said["path"], said["width"]) == ("loop", width)
    want, counts, blocks = moe.experts(h, topi, w, stacks, li, CFG, valid)
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    assert moe.experts_on_kernel(CFG)
    assert moe.serving_stats(CFG, 8)["moe_decode_dispatch"]["path"] \
        == "kernel"
    got, counts_k, blocks_k = moe.experts(h, topi, w, stacks, li, CFG, valid)

    assert got.shape == (T, width)
    assert counts_k.tolist() == counts.tolist()
    assert int(blocks_k) == int(blocks)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    if layer in ("every_choice_unheld", "no_valid_row"):
        assert int(blocks) == 0 and not np.asarray(got).any()
    else:
        assert np.asarray(got).any()
    if valid is not None:
        assert not np.asarray(got[n_valid:]).any()


# -- the dispatch tables alone, against the sort they replace -----------------

def _sorted_tables(hf, topi, valid, Eh, bm, nb_max):
    """What ``_tables`` and ``_fill`` must give, by the stable sort they
    replace (``_experts`` up to PR 42: assignments sorted by held expert,
    an expert's rows from a multiple of ``bm``), in numpy."""
    T, K = topi.shape
    key = np.where((topi < Eh) & valid[:, None], topi, Eh).reshape(T * K)
    order = np.argsort(key, kind="stable")                 # sorted -> flat
    counts = np.bincount(key, minlength=Eh + 1)[:Eh]
    start = np.cumsum(counts) - counts
    nblk = -(-counts // bm)
    blk_end = np.cumsum(nblk)
    pad_start = (blk_end - nblk) * bm
    xs = np.zeros((nb_max * bm, hf.shape[1]), hf.dtype)
    dest = np.full(T * K, -1)
    for place, flat in enumerate(order):
        e = key[flat]
        if e < Eh:
            dest[flat] = pad_start[e] + place - start[e]
            xs[dest[flat]] = hf[flat // K]
    blk_expert = np.minimum(
        np.searchsorted(blk_end, np.arange(nb_max), side="right"), Eh - 1)
    return xs, blk_expert, blk_end[-1], counts, dest.reshape(T, K)


# (tokens, k, experts held, held share of the router's experts, valid
# share of the tokens): the five cells' decode shapes cut small (LFM2 all
# 64 held, nemotron 22 of 512 with a quarter held, Laguna 256 held for
# 128 slots, solar an eighth, gigachat a sixteenth), a 512-token chunk in
# blocks of 64 (48 assignments an expert) and one of eight held experts
# in blocks of 128 (ten scored: 102 an expert), more tokens than one
# count's chunk (blocks of 128 too), and the edges
TABLES = {
    "lfm2_decode": (24, 4, 16, 1.0, 0.9),
    "nemotron_decode": (24, 6, 8, 0.25, 0.9),
    "laguna_decode": (32, 4, 64, 1.0, 0.9),
    "solar_decode": (32, 4, 5, 0.125, 0.9),
    "gigachat_decode": (32, 4, 2, 0.0625, 0.9),
    "a_512_token_chunk": (512, 6, 8, 0.125, 0.95),
    "a_512_token_chunk_of_eight_experts": (512, 2, 8, 1.0, 0.95),
    "more_tokens_than_one_count": (640, 2, 4, 0.5, 1.0),
    "every_token_on_one_expert": (40, 4, 8, "one", 1.0),
    "no_token_on_a_held_expert": (40, 4, 8, "none", 1.0),
    "no_valid_token": (24, 4, 8, 1.0, 0.0),
    "one_expert_held": (24, 2, 1, 0.25, 0.9),
}


@pytest.mark.parametrize("valid_given", [True, False],
                         ids=["valid", "valid_is_None"])
@pytest.mark.parametrize("case", list(TABLES))
def test_counted_tables_equal_the_stable_sort(case, valid_given):
    """``xs``, ``blk_expert``, ``n_blocks``, ``counts`` and every
    dispatched assignment's ``dest`` are value for value what the sort
    gave, so the blocks' kernel gets the operands it always got; an
    assignment that is not dispatched points past the buffer."""
    T, K, Eh, share, valid_share = TABLES[case]
    rng = np.random.default_rng(T * K + Eh)
    E = Eh if isinstance(share, str) else round(Eh / share)
    if share == "one":        # the held expert 3, and K - 1 that are not
        topi = np.tile(np.array([[3] + list(range(Eh, Eh + K - 1))]), (T, 1))
    elif share == "none":
        topi = np.tile(np.arange(Eh, Eh + K)[None], (T, 1))
    else:                     # k different experts a token, as top-k gives
        topi = np.argsort(rng.random((T, E)), axis=1)[:, :K]
    valid = rng.random(T) < valid_share if valid_given else np.ones(T, bool)
    hf = rng.standard_normal((T, 32)).astype(np.float32)
    cfg = CFG.with_(n_experts=max(E, Eh + K), n_experts_held=Eh,
                    experts_per_token=K)
    bm, rows = moe.expert_dispatch(cfg, T)
    assert moe.n_held(cfg) == Eh
    assert bm == {"a_512_token_chunk": 64,
                  "a_512_token_chunk_of_eight_experts": 128,
                  "more_tokens_than_one_count": 128}.get(
                      case, 16 if T <= 128 else None)
    want = _sorted_tables(hf, topi, valid, Eh, bm, rows // bm)

    v = jnp.asarray(valid) if valid_given else None
    counts, n_blocks, blk_expert, dest = jax.jit(
        lambda t, v: moe.tables(t, v, Eh, bm, rows // bm))(
            jnp.asarray(topi, jnp.int32), v)
    xs = jax.jit(lambda h, d, v: moe._fill(h, d, v, rows))(
        jnp.asarray(hf), dest, v)
    dest, sent = np.asarray(dest), want[4] >= 0
    np.testing.assert_array_equal(np.asarray(xs), want[0])
    np.testing.assert_array_equal(np.asarray(blk_expert), want[1])
    assert int(n_blocks) == want[2]
    np.testing.assert_array_equal(np.asarray(counts), want[3])
    np.testing.assert_array_equal(dest[sent], want[4][sent])
    assert (dest[~sent] >= rows).all()
    if case in ("no_token_on_a_held_expert", "no_valid_token") \
            and (valid_given or case != "no_valid_token"):
        assert want[2] == 0 and not sent.any()
    bf16 = jnp.asarray(hf).astype(jnp.bfloat16)            # bit for bit
    np.testing.assert_array_equal(
        np.asarray(moe._fill(bf16, jnp.asarray(dest), v, rows), np.float32),
        np.asarray(jnp.asarray(want[0]).astype(jnp.bfloat16), np.float32))
