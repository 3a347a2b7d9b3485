"""The latent-attention family (models/deepseek_v3.py, ops/mla.py) at the
``tiny-mla-moe`` preset, held against the benchmark's plain float32
reference (benchmarks/references/deepseek_v3.py), which imports nothing
of the program and is the file the chip's ``correct`` is decided by."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split

from gofr_tpu.models import (LLAMA_CONFIGS, deepseek_v3 as ds, family,
                             latent, llama, moe)
from gofr_tpu.ops import mla, rope
from gofr_tpu.ops.quant import QuantizedLinear
from gofr_tpu.tpu import GenerationEngine
from gofr_tpu.tpu.checkpoint import maybe_quantize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-mla-moe"]
F32_TOL = 2e-4     # |log-probability - reference|, float32 both sides
INT8_TOL = 2e-3    # int8 weights both sides: the scale folding's rounding


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_deepseek_v3", os.path.join(
            REPO, "benchmarks", "references", "deepseek_v3.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return ds.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 24), 1,
                              CFG.vocab_size)


def _ref_logprobs(params, cfg, toks):
    return np.stack([np.asarray(REF.forward_logprobs(
        params, cfg, np.asarray(row), range(len(row)))[0]) for row in toks])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_family_is_chosen_by_field_not_by_name():
    assert family(CFG) is ds
    assert family(LLAMA_CONFIGS["tiny"]) is llama
    assert family(LLAMA_CONFIGS["tiny-moe"]) is llama
    assert family(LLAMA_CONFIGS["tiny"].with_(kv_lora_rank=8)) is ds
    # every rank and head width of the preset differs from every other
    widths = [CFG.q_lora_rank, CFG.kv_lora_rank, CFG.qk_nope_head_dim,
              CFG.qk_rope_head_dim, CFG.v_head_dim, CFG.moe_ffn_dim,
              CFG.qk_nope_head_dim + CFG.qk_rope_head_dim]
    assert len(set(widths)) == len(widths)


def test_full_forward_against_the_reference(params, tokens):
    logits = ds.forward(params, CFG, tokens)
    err = np.abs(_logprobs(logits) - _ref_logprobs(params, CFG, tokens))
    assert err.max() < F32_TOL


def test_prefill_then_decode_through_the_cache(params, tokens):
    """Expanded prefill writes rows; absorbed decode reads them: the
    same log-probabilities as the reference's expanded forward."""
    want = _ref_logprobs(params, CFG, tokens)
    cache = ds.init_cache(CFG, 2, 64)
    _, rows, _ = ds.prefill_kv(params, CFG, tokens[:, :10], rope_max=64)
    assert rows.shape == (CFG.n_layers, 2, 10, latent.sizes(CFG).stored_width)
    cache = ds.write_kv(cache, rows, (0, 0, 0, 0),
                        jnp.array([10, 10], jnp.int32))
    for t in range(10, 24):
        logits, cache, counts = ds.decode_step(params, CFG, tokens[:, t],
                                               cache)
        assert np.abs(_logprobs(logits) - want[:, t]).max() < F32_TOL
        assert counts.shape == (CFG.n_layers - CFG.n_dense_layers,
                                CFG.n_experts_held)
    assert cache.lengths.tolist() == [24, 24]


@pytest.mark.parametrize("chunk", [4, 8])
def test_a_chunked_prompt_against_the_reference(params, tokens, chunk):
    """Chunks attend absorbed to the rows cached before them."""
    want = _ref_logprobs(params, CFG, tokens)
    cache = ds.init_cache(CFG, 2, 64)
    got = []
    for s0 in range(0, 24, chunk):
        logits, cache = ds.prefill_chunk(params, CFG,
                                         tokens[:, s0:s0 + chunk], cache,
                                         jnp.int32(s0))
        got.append(_logprobs(logits))
    assert np.abs(np.concatenate(got, 1) - want).max() < F32_TOL


def test_a_chunk_on_the_interpreted_walk_kernel_is_the_chunk_on_the_loop(
        params, tokens, monkeypatch):
    """``prefill_chunk`` with ``GOFR_FLASH_INTERPRET=1`` (the walk under
    the cursor in ``mla.chunk_walk_latent``, blocks of 16 rows) against
    ``prefill_chunk`` without, chunk by chunk, and the rows they leave."""
    monkeypatch.setattr(mla, "_CHUNK_BLOCK", 16)

    def lattice(kernel):
        if kernel:
            monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
        else:
            monkeypatch.delenv("GOFR_FLASH_INTERPRET", raising=False)
        assert ds.chunk_walk_kernel(CFG, 64, 8) == kernel
        run = jax.jit(lambda toks, cache, start: ds.prefill_chunk(
            params, CFG, toks, cache, start))
        cache, got = ds.init_cache(CFG, 2, 64), []
        for s0 in range(0, 24, 8):
            logits, cache = run(tokens[:, s0:s0 + 8], cache, jnp.int32(s0))
            got.append(_logprobs(logits))
        return np.concatenate(got, 1), cache.rows

    want, rows = lattice(False)
    got, rows_k = lattice(True)
    assert np.abs(got - want).max() < F32_TOL
    assert np.abs(got - _ref_logprobs(params, CFG, tokens)).max() < F32_TOL
    assert np.abs(np.asarray(rows_k) - np.asarray(rows)).max() < 2e-5


def test_expanded_and_absorbed_attention_are_the_same_numbers():
    """ops/mla.py alone: a query against rows, through W_UK/W_UV absorbed
    or with keys and values a head materialised."""
    H, R, dn, dr, dv, S = 3, 16, 6, 4, 5, 9
    k = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    rows = jax.random.normal(next(k), (1, S, R + dr))
    w_uk = jax.random.normal(next(k), (R, H, dn))
    w_uv = jax.random.normal(next(k), (R, H, dv))
    q = jax.random.normal(next(k), (1, H, dn + dr)) * 0.3
    new = jax.random.normal(next(k), (1, R + dr))
    q_cat = jnp.concatenate(
        [jnp.einsum("bhd,rhd->bhr", q[..., :dn], w_uk), q[..., dn:]], -1)
    lengths = jnp.array([S], jnp.int32)
    o_lat = mla.decode_attention_reference(q_cat, rows, new, lengths, R)
    absorbed = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv)
    every = jnp.concatenate([rows, new[:, None]], 1)          # [1, S+1, W]
    k_nope = jnp.einsum("bsr,rhd->bshd", every[..., :R], w_uk)
    v = jnp.einsum("bsr,rhd->bshd", every[..., :R], w_uv)
    scores = (jnp.einsum("bhd,bshd->bhs", q[..., :dn], k_nope)
              + jnp.einsum("bhd,bsd->bhs", q[..., dn:], every[..., R:]))
    expanded = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(scores, -1), v)
    assert np.abs(np.asarray(absorbed - expanded)).max() < 1e-5


_KERNEL_CASES = [
    # a cache of two 128-row blocks a slot
    ([0, 100, 255], None), ([128, 256, 1], None),
    ([40, 200, 130], [True, False, True]),
    # no item at all: nothing cached, and every slot inactive
    ([0, 0, 0], None), ([100, 200, 50], [False, False, False]),
    # one, two and three items in all; a slot of length 0 between the two
    # live ones whose blocks are folded together
    ([0, 100, 0], None), ([128, 0, 1], None), ([129, 0, 5], None),
    # the last slot the only live one
    ([0, 0, 77], None), ([0, 0, 0, 0, 1000], None),
    # a cache of eight blocks a slot. One slot of exactly one block, of
    # one block and a row, of an odd and an even number of blocks: a trip
    # of the loop takes four items, what is left over is folded alone
    ([0, 0, 128, 0, 0], None), ([0, 129, 0, 0, 0], None),
    ([384, 0, 0, 0, 0], None), ([0, 0, 0, 512, 0], None),
    ([0, 0, 640, 0, 0], None), ([0, 897, 0, 0, 0], None),
    ([0, 0, 0, 0, 1023], None),
    # a trip whose four items open four slots; trips that straddle slots
    # at every offset, with dead and inactive slots between live ones
    ([5, 128, 100, 1, 0], None), ([300, 0, 520, 129, 1000], None),
    ([130, 257, 385, 513, 641], None), ([1024, 1, 1024, 1, 1024], None),
    ([640, 333, 1024, 77, 129], [True, False, True, True, False]),
    ([512, 512, 0, 512, 512], [True, True, True, False, True]),
]


@pytest.mark.parametrize("lengths,active", _KERNEL_CASES)
def test_the_decode_kernel_interpreted_against_the_reference(lengths, active):
    # the stack's first layer and a middle one: the two stacks' call sites
    L, B, W, R, H = 3, len(lengths), 128, 32, 4
    S = 256 if B == 3 else 1024
    k = iter(jax.random.split(jax.random.PRNGKey(4), 4))
    rows = jax.random.normal(next(k), (L, B, S, W))
    q = jax.random.normal(next(k), (B, H, W)) * 0.3
    new = jax.random.normal(next(k), (B, W))
    lengths = jnp.asarray(lengths, jnp.int32)
    live = lengths if active is None else jnp.where(jnp.asarray(active),
                                                    lengths, 0)
    for layer in (0, 1):
        want = mla.decode_attention_reference(q, rows[layer], new, live, R)
        got = mla.decode_attention_stacked(q, rows, new, live,
                                           jnp.int32(layer), rank=R,
                                           block_s=128, interpret=True)
        assert np.abs(np.asarray(want - got)).max() < 1e-5


def _four_bit(leaf: QuantizedLinear) -> QuantizedLinear:
    """The same leaf with its weights rounded to 4 bits."""
    return QuantizedLinear((jnp.round(leaf.w.astype(jnp.float32) / 16) * 16)
                           .astype(jnp.int8), leaf.scale)


def test_the_int8_path_and_what_a_four_bit_weight_does_to_it(params, tokens):
    """int8 leaves (W_kvb's scale folded into q_nope and into o): within
    INT8_TOL of the reference on the same leaves; the same program on
    leaves rounded to 4 bits misses the int8 reference by far more."""
    q8 = maybe_quantize(params, True)
    assert isinstance(q8["layers"]["w_kvb"], QuantizedLinear)
    assert isinstance(q8["layers"]["ws_gate"], QuantizedLinear)
    want = _ref_logprobs(q8, CFG, tokens)
    cache = ds.init_cache(CFG, 2, 64)
    _, rows, _ = ds.prefill_kv(q8, CFG, tokens[:, :10], rope_max=64)
    cache = ds.write_kv(cache, rows, (0, 0, 0, 0),
                        jnp.array([10, 10], jnp.int32))
    errs = []
    for t in range(10, 24):
        logits, cache, _ = ds.decode_step(q8, CFG, tokens[:, t], cache)
        errs.append(np.abs(_logprobs(logits) - want[:, t]).max())
    assert max(errs) < INT8_TOL
    q4 = jax.tree_util.tree_map(
        lambda x: _four_bit(x) if isinstance(x, QuantizedLinear) else x, q8,
        is_leaf=lambda x: isinstance(x, QuantizedLinear))
    got4 = _logprobs(ds.forward(q4, CFG, tokens))
    assert np.abs(got4 - want).max() > 10 * INT8_TOL


# -- the router ----------------------------------------------------------------

def _route_numpy(h, router, bias, cfg):
    """The published selection, by enumeration."""
    s = 1.0 / (1.0 + np.exp(-(h.astype(np.float64)
                              @ router.astype(np.float64))))
    sel = s + bias
    ids, ws = [], []
    per = cfg.n_experts // cfg.n_expert_groups
    for t in range(h.shape[0]):
        groups = sorted(range(cfg.n_expert_groups), key=lambda g: -sum(
            sorted(sel[t, g * per:(g + 1) * per])[-2:]))[:cfg.topk_groups]
        allowed = [e for g in groups for e in range(g * per, (g + 1) * per)]
        top = sorted(allowed, key=lambda e: -sel[t, e])[:cfg.experts_per_token]
        w = np.array([s[t, e] for e in top])       # the bias does not weigh
        ids.append(top)
        ws.append(w / w.sum() * cfg.routed_scaling)
    return np.array(ids), np.array(ws)


@pytest.mark.parametrize("bias_std", [0.0, 0.01, 0.5])
def test_the_router_against_an_enumeration(bias_std):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    h = jax.random.normal(k1, (40, CFG.dim))
    router = jax.random.normal(k2, (CFG.dim, CFG.n_experts)) * 0.3
    bias = jax.random.normal(k3, (CFG.n_experts,)) * bias_std
    topi, w = moe.route(h, router, bias, CFG)
    want_i, want_w = _route_numpy(np.asarray(h), np.asarray(router),
                                  np.asarray(bias), CFG)
    order = np.argsort(np.asarray(topi), axis=1)
    want_order = np.argsort(want_i, axis=1)
    assert (np.take_along_axis(np.asarray(topi), order, 1)
            == np.take_along_axis(want_i, want_order, 1)).all()
    assert np.abs(np.take_along_axis(np.asarray(w), order, 1)
                  - np.take_along_axis(want_w, want_order, 1)).max() < 1e-5
    assert np.allclose(np.asarray(w).sum(1), CFG.routed_scaling, atol=1e-5)
    per = CFG.n_experts // CFG.n_expert_groups
    assert all(len({e // per for e in row}) <= CFG.topk_groups
               for row in np.asarray(topi))


def test_the_bias_selects_and_does_not_weigh():
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    h = jax.random.normal(k1, (16, CFG.dim))
    router = jax.random.normal(k2, (CFG.dim, CFG.n_experts)) * 0.3
    none = jnp.zeros((CFG.n_experts,))
    push = none.at[5].set(10.0)       # expert 5 always wins the selection
    topi, w = moe.route(h, router, push, CFG)
    assert (np.asarray(topi) == 5).any(axis=1).all()
    s5 = jax.nn.sigmoid(h @ router)[:, 5]
    w5 = np.asarray(w)[np.asarray(topi) == 5]
    # its weight is its unbiased score's share, below the share cap
    assert (w5 < CFG.routed_scaling * np.asarray(s5) + 1e-6).all()
    assert w5.max() < CFG.routed_scaling


# -- the expert layer ----------------------------------------------------------

def _layer_w(params, i=0):
    """Layer ``i``'s weights, and the expert stacks whole with its index
    (``_experts`` reads expert (i, e) in place)."""
    lw = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
    return lw, ({k: params["layers"][k] for k in moe.EXPERT_STACKS},
                jnp.int32(i))


def test_every_token_to_one_expert_loses_none(params):
    """No capacity: 40 tokens all choose held expert 2 (and absent ones)."""
    lw, stacks = _layer_w(params)
    T = 40
    h = jax.random.normal(jax.random.PRNGKey(7), (T, CFG.dim))
    # expert 2, then only experts the chip does not hold
    topi = jnp.tile(jnp.array([[2, 8, 9, 12]], jnp.int32), (T, 1))
    w = jnp.full((T, 4), 0.625)
    y, counts, blocks = moe.experts(h, topi, w, *stacks, CFG)
    assert counts.tolist() == [0, 0, T, 0]
    assert int(blocks) == math.ceil(T / moe.expert_dispatch(CFG, T)[0])
    one = moe._swiglu(h, lw["w_gate"][2], lw["w_up"][2], lw["w_down"][2])
    assert np.abs(np.asarray(y - 0.625 * one)).max() < 1e-5


def test_expert_flops_follow_the_assignments(params):
    """Blocks run = sum over held experts of ceil(assignments / block):
    nothing for an expert no token chose, nothing at all where every
    token chose absent experts, and no row for an invalid token."""
    lw, stacks = _layer_w(params)
    T, bm = 40, moe.expert_dispatch(CFG, 40)[0]
    h = jax.random.normal(jax.random.PRNGKey(8), (T, CFG.dim))
    w = jnp.full((T, 4), 0.625)
    absent = jnp.tile(jnp.array([[8, 9, 12, 13]], jnp.int32), (T, 1))
    y, counts, blocks = moe.experts(h, absent, w, *stacks, CFG)
    assert int(blocks) == 0 and counts.sum() == 0 and not np.asarray(y).any()
    topi, wr = moe.route(h, lw["router"], lw["router_bias"], CFG)
    y, counts, blocks = moe.experts(h, topi, wr, *stacks, CFG)
    held = np.asarray(topi)[np.asarray(topi) < CFG.n_experts_held]
    assert counts.tolist() == np.bincount(held, minlength=4).tolist()
    assert int(blocks) == sum(math.ceil(c / bm) for c in counts.tolist())
    valid = jnp.arange(T) < 10
    _, counts_v, _ = moe.experts(h, topi, wr, *stacks, CFG, valid)
    held_v = np.asarray(topi)[:10][np.asarray(topi)[:10] < 4]
    assert counts_v.tolist() == np.bincount(held_v, minlength=4).tolist()
    # the compiled layer holds no [tokens, experts, width] product
    hlo = jax.jit(lambda h: moe.experts(h, topi, wr, *stacks, CFG)[0]) \
        .lower(h).compile().as_text()
    assert f"[{T},{CFG.n_experts_held},{CFG.moe_ffn_dim}]" not in hlo
    assert "capacity" not in moe.experts.__code__.co_names


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """16 experts in 4 groups, a chip a group: each share's routed part,
    and the shared expert counted once, sum to the uncut reference's
    layer. The program computes share j from the parameters of a chip
    that holds group j (its router's groups renumbered so that the held
    experts are ids 0..3, as the program's share always is)."""
    whole_cfg = CFG.with_(n_experts_held=CFG.n_experts)
    whole = ds.init(whole_cfg, jax.random.PRNGKey(9))
    layers = whole["layers"]
    h = jax.random.normal(jax.random.PRNGKey(10), (12, CFG.dim))
    every = [(e, e) for e in range(CFG.n_experts)]
    with jax.default_matmul_precision("highest"):
        uncut, _ = REF.layer_share(layers, whole_cfg, 0, h, every)
        shared, _ = REF.layer_share(layers, whole_cfg, 0, h, [])
    total = np.asarray(shared)
    for j in range(4):
        mine = [(4 * j + k, 4 * j + k) for k in range(4)]
        with jax.default_matmul_precision("highest"):
            ref_share, _ = REF.layer_share(layers, whole_cfg, 0, h, mine,
                                           shared=False)
        # the chip that holds group j: swap groups 0 and j in the router
        perm = np.arange(CFG.n_experts)
        perm[0:4], perm[4 * j:4 * j + 4] = np.arange(4 * j, 4 * j + 4), \
            np.arange(4)
        lw = {k: v[0] for k, v in layers.items()
              if k not in moe.EXPERT_STACKS}
        lw.update(router=lw["router"][:, perm],
                  router_bias=lw["router_bias"][perm],
                  experts=({k: layers[k][:, 4 * j:4 * j + 4]
                            for k in moe.EXPERT_STACKS}, jnp.int32(0)))
        got, counts = moe.moe_ffn(h[None], lw, CFG)
        assert np.abs(np.asarray(got[0]) - np.asarray(ref_share + shared)) \
            .max() < 1e-4
        total = total + np.asarray(ref_share)
    assert np.abs(total - np.asarray(uncut)).max() < 1e-4


def test_a_requests_logits_alone_equal_its_logits_in_a_full_batch(params):
    """Slot 2 of four, then the same request alone."""
    toks = jax.random.randint(jax.random.PRNGKey(11), (4, 12), 1, 256)
    lens = jnp.array([12, 7, 9, 5], jnp.int32)

    def run(rows_of):
        b = len(rows_of)
        cache = ds.init_cache(CFG, b, 32)
        _, rows, _ = ds.prefill_kv(params, CFG, toks[jnp.array(rows_of)],
                                   lens[jnp.array(rows_of)], rope_max=32)
        cache = ds.write_kv(cache, rows, (0, 0, 0, 0),
                            lens[jnp.array(rows_of)])
        out = []
        tok = toks[jnp.array(rows_of), 0]
        for _ in range(4):
            logits, cache, _ = ds.decode_step(params, CFG, tok, cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(np.asarray(logits))
        return np.stack(out)

    full, alone = run([0, 1, 2, 3]), run([2])
    assert np.abs(full[:, 2] - alone[:, 0]).max() < 1e-5


# -- YaRN ------------------------------------------------------------------------

def test_yarn_tables_against_the_formula():
    sc = {"rope_type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
          "original_max_position_embeddings": 4096, "mscale": 1,
          "mscale_all_dim": 1}
    dim, theta = 64, 100000.0
    cos, sin = rope.rope_frequencies(dim, 2048, theta, sc)

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    want = []
    for i in range(dim // 2):
        f = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / 64 * ramp + f * (1 - ramp))
    pos = np.arange(2048)[:, None] * np.array(want)[None]
    assert np.abs(np.asarray(cos) - np.cos(pos)).max() < 2e-3
    assert np.abs(np.asarray(sin) - np.sin(pos)).max() < 2e-3
    # fast dims keep their frequency, slow dims are divided by the factor
    inv = np.asarray(rope.yarn_inv_freq(dim, theta, sc))
    assert inv[0] == pytest.approx(1.0) and low > 0
    assert inv[-1] == pytest.approx(theta ** (-(dim - 2) / dim) / 64)
    assert rope.yarn_softmax_scale(sc) == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2)
    assert rope.yarn_softmax_scale(None) == 1.0
    assert np.allclose(np.asarray(REF.yarn_inv_freq(dim, theta, sc)), inv,
                       rtol=1e-6)
    # the Llama-3 dict still takes its own branch
    plain = rope.rope_frequencies(64, 16, 10000.0, None)
    scaled = rope.rope_frequencies(64, 16, 10000.0, {"factor": 8.0})
    assert plain[0].shape == scaled[0].shape == (16, 32)


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params):
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=2,
                           prefix_store_min=16)
    yield eng
    eng.close()


def _greedy(params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = ds.forward(params, CFG, jnp.asarray([toks]))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


@pytest.mark.parametrize("length", [10, 20, 70])
def test_engine_greedy_tokens(engine, params, length):
    """A bucket, the next bucket, and the chunk lattice (70 > 32)."""
    prompt = np.random.default_rng(length).integers(1, 256, length).tolist()
    assert engine.generate(prompt, max_new_tokens=8).tokens() \
        == _greedy(params, prompt, 8)


def test_engine_prefix_pool_hit_equals_miss(engine):
    prompt = np.random.default_rng(5).integers(1, 256, 70).tolist()
    before = engine.stats()["prefix_cache"]["hits"]
    miss = engine.generate(prompt, max_new_tokens=8).tokens()
    hit = engine.generate(prompt, max_new_tokens=8).tokens()
    assert engine.stats()["prefix_cache"]["hits"] == before + 1
    assert hit == miss


def test_engine_slot_reuse_after_retire(engine, params):
    """More requests than slots, of other lengths: a slot's stale rows
    past the new cursor are never read."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (30, 9, 14, 22, 11)]
    streams = [engine.generate(p, max_new_tokens=6) for p in prompts]
    for p, s in zip(prompts, streams):
        assert s.tokens() == _greedy(params, p, 6)


def test_engine_standing_queue_token_exact_across_depths(params):
    """12 latency-class requests on 4 slots (short, bucket and chunked
    prompts, budgets that end inside a block): the same greedy tokens at
    pipeline depth 1 and 2, with blocks queued behind reaps and behind
    admission prefills at depth 2. The step's seventh output (the expert
    counts) rides every reap's one fetch whatever the depth."""
    from test_tpu_pipeline import _QUEUE_BUDGETS, standing_queue_outputs

    prompts, outs = standing_queue_outputs(
        lambda depth: GenerationEngine(
            CFG, params, slots=4, max_seq=64, prompt_buckets=(8, 16),
            decode_pipeline=depth),
        256)
    assert outs[1] == outs[2]
    for i in (1, 6):
        assert outs[2][i] == _greedy(params, prompts[i], _QUEUE_BUDGETS[i])


def test_engine_counts_the_expert_layers_assignments(params):
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=256))
    eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                           prompt_buckets=(16,), observe=obs, metrics=m)
    try:
        eng.generate([3, 4, 5], max_new_tokens=9).tokens()
        moe = eng.stats()["moe"]
        events = [e for e in obs.timeline.events() if e[3] == "decode"]
    finally:
        eng.close()
    assert events and all(len(e) == 10 for e in events)
    assigned = sum(e[8] for e in events)
    assert assigned == moe["expert_tokens"] > 0
    assert all(0 <= e[9] <= e[8] for e in events)
    assert 0.0 <= moe["experts_idle_ratio"] <= 1.0
    prom = m.render_prometheus()
    assert f"app_tpu_moe_expert_tokens {float(assigned)}" in prom \
        or f"app_tpu_moe_expert_tokens {assigned}" in prom
    assert "app_tpu_moe_experts_idle_ratio" in prom
    # the Perfetto export names the appended fields
    args = [e["args"] for e in obs.timeline.chrome_trace()["traceEvents"]
            if e.get("cat") == "decode"]
    assert args and "moe_assigned" in args[0]


def test_the_generator_counts_the_positions_the_kernel_fetches(params,
                                                               monkeypatch):
    """``attn.kv_read_pct`` is the generator's count: each decode event
    carries the positions its block's attention fetched, reckoned from
    ``decode_kv_block``. The kernel fetches by its work list. One
    request alone, whose cursor crosses a block's edge: every event's
    count is the work list's items for that cursor x the kernel's block,
    so a block changed in one place fails here."""
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline
    from gofr_tpu.ops.flash_decode import _work_list

    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    tl = Timeline(capacity=1024)
    cfg = CFG.with_(max_seq=512)
    eng = GenerationEngine(cfg, params, slots=2, max_seq=512,
                           prompt_buckets=(16, 32),
                           observe=Observe(timeline=tl))
    try:
        smax = eng.cache.rows.shape[2]
        block = eng.stats()["decode_kv_block"]
        assert block == ds.decode_kv_block(cfg, eng.cache) \
            == mla.decode_block(eng.cache.rows, cfg.kv_lora_rank)
        assert block and 2 * block <= smax
        prompt = np.random.default_rng(7).integers(1, 256,
                                                   block - 6).tolist()
        assert len(eng.generate(prompt, max_new_tokens=12).tokens()) == 12
    finally:
        eng.close()
    events = [e for e in tl.events() if e[3] == "decode"]
    assert events
    for e in events:
        n, _, _ = _work_list(jnp.asarray([e[6], 0], jnp.int32), smax, block)
        assert e[7] == int(n[0]) * block
    assert {e[7] for e in events} == {block, 2 * block}


class _Tiers:
    host_mb, redis = 64, None


@pytest.mark.parametrize("option", [
    {"paged_blocks": 8}, {"spec_decode_k": 2}, {"lora_adapters": 2},
    {"kv_dtype": jnp.int8}, {"kvcache": _Tiers()}, {"mesh": object()},
    {"serving_role": "prefill"}, {"serving_role": "decode"},
])
def test_the_engine_refuses_what_the_family_does_not_run(params, option):
    """One check, in the constructor every path reaches, in the
    constructor's own names; the Llama family refuses nothing."""
    (name,) = option
    with pytest.raises(ValueError, match=name) as e:
        GenerationEngine(CFG, params, slots=2, max_seq=64, **option)
    assert [opt for opt, _ in e.value.refused] == [name]
    assert ds.unsupported_options(serving_role="fused") == []
    assert llama.unsupported_options(**option) == []


def test_start_up_from_config_refuses_by_name():
    from gofr_tpu.config import MapConfig as DictConfig
    from gofr_tpu.tpu import new_engine_from_config

    base = {"TPU_MODEL": "tiny-mla-moe", "TPU_KV_DTYPE": "model",
            "TPU_SLOTS": "2", "TPU_MAX_SEQ": "64", "TPU_SEQ_BUCKETS": "16",
            "TPU_PREFIX_CACHE": "2"}  # the host tier hangs off the pool
    for key, value in (("TPU_SPEC_DECODE", "4"), ("TPU_KV_DTYPE", "int8"),
                       ("TPU_KVCACHE_HOST_MB", "64"),
                       ("TPU_SERVING_ROLE", "decode")):
        with pytest.raises(ValueError, match=key):
            new_engine_from_config(DictConfig({**base, key: value}))
    eng = new_engine_from_config(DictConfig(base))
    try:
        assert eng.generator.generate([1, 2, 3], max_new_tokens=3).tokens()
    finally:
        eng.close()


# -- a prompt as two dispatches -------------------------------------------------

@pytest.mark.parametrize("kv_dtype,tol", [(None, F32_TOL)])
def test_a_split_admission_is_the_one_bucket_admission(params, kv_dtype, tol):
    """A prompt admitted as a whole bucket and the rest (overlapped: this
    family's last chunk) against the same prompt in one padded bucket:
    the same greedy tokens, logprobs and cache arrays to the chunked
    tests' tolerance, and the positions counted (tests/_prefill_split.py)."""
    _prefill_split.check(CFG, params, tol=tol, kv_dtype=kv_dtype)
