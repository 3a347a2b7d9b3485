"""The conv family (models/lfm2.py) at the ``tiny-conv-moe`` preset (two
periods of three gated short-convolution layers to one full layer, 6
query heads of 16 values on 2 KV heads that share a cache row, a q/k norm
a head, one dense layer before seven routed ones, no shared expert, a
tied head), held at the logit level against the benchmark's plain
float32 reference (benchmarks/references/lfm2.py), which imports nothing
of the program, keeps no cache and no tail, and is the file the chip's
``correct`` is decided by."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split

from gofr_tpu.models import (LLAMA_CONFIGS, family, lfm2, llama, moe,
                             solar_open2 as so)
from gofr_tpu.ops import attention, flash_decode, kda
from gofr_tpu.ops.quant import quantize_int8
from gofr_tpu.tpu import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-conv-moe"]
N_NEW = 16
# |log-probability - reference|, float32 both sides: eight layers of
# float32 sums in another order (experts in blocks, K and V two heads a
# row with zeros multiplied in)
F32_TOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_lfm2", os.path.join(
            REPO, "benchmarks", "references", "lfm2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return lfm2.init(CFG, jax.random.PRNGKey(0))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n) \
        .astype(np.int32)


def _ref(params, toks, rows, cfg=CFG, **kw):
    return np.asarray(REF.forward_logprobs(params, cfg, np.asarray(toks),
                                           list(rows), **kw)[0])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_family_is_chosen_by_fields_not_by_name():
    assert family(CFG) is lfm2
    assert family(LLAMA_CONFIGS["tiny"]) is llama
    assert family(LLAMA_CONFIGS["tiny-kda-moe"]) is so
    renamed = LLAMA_CONFIGS["tiny"].with_(
        layer_pattern=["conv", "full"], conv_kernel=3)
    assert family(renamed) is lfm2
    assert lfm2.counts(CFG) == {"conv": 6, "full": 2}
    with pytest.raises(ValueError, match="does not tile"):
        lfm2.counts(CFG.with_(conv_kernel=1))
    # two KV heads of 16 values share a cache row of 32
    assert lfm2.paired(CFG) and lfm2.kv_layout(CFG) == (1, 32)
    cache = lfm2.init_cache(CFG, 3, 64)
    assert cache.k.shape == (2, 3, 1, 64, 32)
    assert cache.conv.shape == (6, 3, 2, 64)
    assert not lfm2.paired(CFG.with_(attn_head_dim=128))
    assert not lfm2.paired(CFG.with_(n_kv_heads=3, n_heads=6))


def test_no_shared_expert_builds_no_leaves(params):
    assert not [k for k in params["moe"] if k.startswith("ws_")]
    with_one = lfm2.init(CFG.with_(n_shared_experts=1), jax.random.PRNGKey(0))
    assert with_one["moe"]["ws_gate"].shape == (7, 64, 40)


def test_rope_tables_are_the_plain_ones():
    (cos, sin), = lfm2.get_rope_tables(CFG, 64).values()
    want = REF.rope_tables(CFG.rope_theta, 16, 64)
    np.testing.assert_allclose(cos, want[0], atol=1e-6)
    np.testing.assert_allclose(sin, want[1], atol=1e-6)


def _serve(params, toks, L, bucket, n_new, slots=3, slot=1, cache=None,
           cfg=CFG):
    """Whole-prompt prefill of toks[:L] into ``slot``, then ``n_new``
    decode steps teacher-forced on toks[L:]: the log-probabilities after
    positions L - 1 .. L + n_new - 1."""
    if cache is None:
        cache = lfm2.init_cache(cfg, slots, 64)
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :L] = toks[:L]
    logits, *kept, _ = lfm2.prefill_kv(params, cfg, jnp.asarray(pad),
                                       jnp.asarray([L]), rope_max=64,
                                       logit_pos=jnp.asarray([L - 1]))
    cache = lfm2.write_kv(cache, *kept, (0, slot, 0, 0, 0),
                          cache.lengths.at[slot].set(L))
    out = [_logprobs(logits[0, 0])]
    act = jnp.arange(slots) == slot
    step = jax.jit(lambda t, c: lfm2.decode_step(params, cfg, t, c,
                                                 active=act))
    for n in range(n_new):
        t = jnp.zeros((slots,), jnp.int32).at[slot].set(int(toks[L + n]))
        logits, new, _, _ = step(t, cache)
        cache = new._replace(
            lengths=jnp.where(act, new.lengths, cache.lengths))
        out.append(_logprobs(logits[slot]))
    return np.stack(out), cache


@pytest.mark.parametrize("L,bucket", [
    (16, 16), (32, 32), (15, 16), (31, 32), (17, 32), (1, 16), (2, 16),
    (3, 16)])
def test_prefill_then_decode_through_the_cache(params, L, bucket):
    """The whole prompt in each bucket, a bucket less one (one padded
    position: the tail must be the last VALID input's), a bucket and one,
    and prompts shorter than, as long as and one longer than the tail
    (zeros in the older places); then 16 decode steps."""
    toks = _tokens(100 * L + bucket, L + N_NEW)
    got, cache = _serve(params, toks, L, bucket, N_NEW)
    want = _ref(params, toks, range(L - 1, L + N_NEW))
    assert np.abs(got - want).max() < F32_TOL
    assert int(cache.lengths[1]) == L + N_NEW


def _chunks(params, toks, L, chunk, cache, cfg=CFG):
    """Left-aligned chunks of toks[:L], the last one padded: (the
    log-probabilities after position L - 1, the cache)."""
    pos = 0
    while L - pos > chunk:
        _, cache = lfm2.prefill_chunk(
            params, cfg, jnp.asarray(toks[None, pos:pos + chunk]), cache,
            jnp.int32(pos), compute_logits=False)
        pos += chunk
    final = np.zeros((1, chunk), np.int32)
    final[0, :L - pos] = toks[pos:L]
    logits, cache = lfm2.prefill_chunk(
        params, cfg, jnp.asarray(final), cache, jnp.int32(pos),
        logit_pos=jnp.asarray([L - pos - 1]))
    return _logprobs(logits[0, 0]), cache._replace(
        lengths=jnp.asarray([L], jnp.int32))


@pytest.mark.parametrize("chunk,L", [(16, 40), (16, 33), (8, 24), (32, 44)])
def test_left_aligned_chunks_the_last_padded(params, chunk, L):
    """Three chunks with the last padded, a last chunk of one token, a
    last chunk that is whole, two chunks: each goes on from the tail the
    one before it left, and the padding does not reach the tail. Then
    decode goes on from there."""
    toks = _tokens(chunk + L, L + N_NEW)
    first, cache = _chunks(params, toks, L, chunk,
                           lfm2.init_cache(CFG, 1, 64))
    got = [first]
    for n in range(N_NEW):
        logits, cache, _, _ = lfm2.decode_step(
            params, CFG, jnp.asarray(toks[L + n:L + n + 1]), cache)
        got.append(_logprobs(logits[0]))
    want = _ref(params, toks, range(L - 1, L + N_NEW))
    assert np.abs(np.stack(got) - want).max() < F32_TOL


# -- the tail's discipline, a case at a time --------------------------------------

def _dirty(cache):
    """A cache whose every tail and row holds a last tenant's values."""
    return cache._replace(conv=jnp.full_like(cache.conv, 3.0),
                          k=jnp.full_like(cache.k, -2.0),
                          v=jnp.full_like(cache.v, 5.0))


@pytest.mark.parametrize("how", ["whole_prompt", "chunks"])
def test_a_slot_taken_by_a_new_request_starts_from_a_zero_tail(params, how):
    toks = _tokens(21, 2 + N_NEW if how == "whole_prompt" else 20 + N_NEW)
    if how == "whole_prompt":
        # two tokens: the older place of the tail must read zero, not
        # what the slot's last tenant left there
        got, _ = _serve(params, toks, 2, 16, N_NEW,
                        cache=_dirty(lfm2.init_cache(CFG, 3, 64)))
        want = _ref(params, toks, range(1, 2 + N_NEW))
    else:
        first, cache = _chunks(params, toks, 20, 16,
                               _dirty(lfm2.init_cache(CFG, 1, 64)))
        got = [first]
        for n in range(N_NEW):
            logits, cache, _, _ = lfm2.decode_step(
                params, CFG, jnp.asarray(toks[20 + n:21 + n]), cache)
            got.append(_logprobs(logits[0]))
        got, want = np.stack(got), _ref(params, toks, range(19, 20 + N_NEW))
    assert np.abs(got - want).max() < F32_TOL


def test_an_idle_slot_beside_active_ones_keeps_its_tail(params):
    """Three slots in one decode batch, two active at lengths 1 and 20
    and an idle one: its tails and rows stay as they are, and the active
    ones' answers are the reference's."""
    lens = (1, 20)
    seqs = [_tokens(40 + n, n + N_NEW) for n in lens]
    cache = _dirty(lfm2.init_cache(CFG, 3, 64))
    for slot, (n, toks) in enumerate(zip(lens, seqs)):
        pad = np.zeros((1, 32), np.int32)
        pad[0, :n] = toks[:n]
        _, *kept, _ = lfm2.prefill_kv(params, CFG, jnp.asarray(pad),
                                      jnp.asarray([n]), rope_max=64)
        cache = lfm2.write_kv(cache, *kept, (0, slot, 0, 0, 0),
                              cache.lengths.at[slot].set(n))
    act = jnp.asarray([True, True, False])
    got = [[], []]
    for n in range(N_NEW):
        t = jnp.asarray([seqs[0][lens[0] + n], seqs[1][lens[1] + n], 7])
        logits, new, counts, moved = lfm2.decode_step(params, CFG, t, cache,
                                                      active=act)
        cache = new._replace(
            lengths=jnp.where(act, new.lengths, cache.lengths))
        for slot in (0, 1):
            got[slot].append(_logprobs(logits[slot]))
    for slot, (n, toks) in enumerate(zip(lens, seqs)):
        want = _ref(params, toks, range(n, n + N_NEW))
        assert np.abs(np.stack(got[slot]) - want).max() < F32_TOL
    # two tokens a step, top-2 of 8 over seven routed layers; six tails
    # moved a token
    assert counts.shape == (7, 8) and int(counts.sum()) == 7 * 2 * 2
    assert int(moved) == 2 * 6
    assert np.all(np.asarray(cache.conv[:, 2]) == 3.0)
    # an idle slot's garbage row lands at its frozen cursor (0) alone
    assert np.all(np.asarray(cache.k[:, 2, :, 1:]) == -2.0)
    # a cursor parked at capacity writes no row
    parked = cache._replace(lengths=cache.lengths.at[2].set(64))
    _, after, _, _ = lfm2.decode_step(params, CFG, jnp.asarray([1, 2, 3]),
                                      parked, active=act)
    assert np.array_equal(np.asarray(after.k[:, 2]), np.asarray(parked.k[:, 2]))
    assert np.array_equal(np.asarray(after.conv[:, 2]),
                          np.asarray(parked.conv[:, 2]))


@pytest.mark.parametrize("lengths", [None, (6, 2)])
def test_solars_convolution_is_bit_equal_after_the_split(lengths):
    """``kda.short_conv`` is ``conv_taps`` then SiLU: to the bit what it
    was before the split, written out here as it stood."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 7, 12)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 3, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 12)), jnp.float32)
    n = None if lengths is None else jnp.asarray(lengths)
    xs = jnp.concatenate([tail, x], axis=1)
    was = jax.nn.silu(sum(xs[:, j:j + 7] * w[j] for j in range(4)))
    was_tail = xs[:, 7:] if n is None else jnp.stack(
        [xs[b, int(n[b]):int(n[b]) + 3] for b in range(2)])
    y, new = kda.short_conv(x, tail, w, n)
    assert np.array_equal(np.asarray(y), np.asarray(was))
    assert np.array_equal(np.asarray(new), np.asarray(was_tail))
    taps, same = kda.conv_taps(x, tail, w, n)
    assert np.array_equal(np.asarray(jax.nn.silu(taps)), np.asarray(y))
    assert np.array_equal(np.asarray(same), np.asarray(new))


@pytest.mark.parametrize("shared", [1, 0])
def test_the_expert_layer_with_and_without_a_shared_expert(shared):
    """One shared expert: ``moe_ffn`` is the routed sum plus its SwiGLU,
    to the bit what it was when the sum was unconditional; none: no
    leaves, and the routed sum alone."""
    cfg = LLAMA_CONFIGS["tiny-swa-moe"].with_(n_shared_experts=shared)
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 8))
    lw = moe.init_routed(keys, cfg, 2)
    assert ("ws_gate" in lw) == bool(shared)
    stacks = {k: lw.pop(k) for k in moe.EXPERT_STACKS}
    lw = {**{k: v[1] for k, v in lw.items()}, "experts": (stacks, 1)}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 5, cfg.dim))
    y, counts = moe.moe_ffn(h, lw, cfg)
    hf = h.reshape(10, cfg.dim)
    was, _, _ = moe.experts(hf, *moe.route(hf, lw["router"],
                                          lw["router_bias"], cfg),
                            stacks, 1, cfg)
    if shared:
        was = was + moe._swiglu(hf, lw["ws_gate"], lw["ws_up"], lw["ws_down"])
    assert np.array_equal(np.asarray(y), np.asarray(was.reshape(h.shape)))
    assert int(counts.sum()) == 10 * cfg.experts_per_token


# -- controls that must fail --------------------------------------------------------

@pytest.mark.parametrize("control", REF.CONTROLS)
def test_the_reference_with_one_departure_is_another_model(params, control):
    """The engine agrees with the reference (every test above) and with
    none of its controls: the taps' order, the tail's age, the q/k norm
    and its side of the rotation, and the bias' part in the weights are
    all held, in prefill and in decode."""
    L = 12
    toks = _tokens(3, L + N_NEW)
    got, _ = _serve(params, toks, L, 16, N_NEW)
    rows = range(L - 1, L + N_NEW)
    assert np.abs(got - _ref(params, toks, rows)).max() < F32_TOL
    off = np.abs(got - _ref(params, toks, rows, control=control)).max(axis=1)
    assert off[0] > 5 * F32_TOL            # the prefill's position
    assert off[1:].max() > 5 * F32_TOL     # and the decode steps'


def test_a_shared_experts_leaves_are_not_ignored():
    """Leaves of a shared expert in the tree are a shared expert in the
    sum: against the reference, which has none, that fails."""
    cfg = CFG.with_(n_shared_experts=1)
    params = lfm2.init(cfg, jax.random.PRNGKey(0))
    toks = _tokens(4, 20)
    logits = lfm2.forward(params, cfg, jnp.asarray(toks[None]))
    err = np.abs(_logprobs(logits[0]) - _ref(params, toks, range(20))).max()
    assert err > 50 * F32_TOL
    bare = {**params, "moe": {k: v for k, v in params["moe"].items()
                              if not k.startswith("ws_")}}
    logits = lfm2.forward(bare, cfg, jnp.asarray(toks[None]))
    assert np.abs(_logprobs(logits[0])
                  - _ref(bare, toks, range(20))).max() < F32_TOL


# bfloat16 weights, activations and cache against the float32 reference
# on the same (bfloat16) weights, over the served tokens'
# log-probabilities: the MEDIAN |difference| read 0.0036 to 0.0061 over
# five token seeds (the chip's check holds a sparse model's median too),
# the largest 0.012 to 0.19: a router flip swaps one expert of two in
BF16_MEDIAN_TOL, BF16_WORST_TOL = 0.02, 0.6


@pytest.mark.parametrize("seed", [1, 2])
def test_the_bfloat16_engine_inside_its_tolerance(seed):
    cfg = CFG.with_(dtype="bfloat16")
    params = lfm2.init(cfg, jax.random.PRNGKey(0))
    L = 20
    toks = _tokens(seed, L + N_NEW)
    got, cache = _serve(params, toks, L, 32, N_NEW, cfg=cfg)
    assert cache.k.dtype == cache.conv.dtype == jnp.bfloat16
    want = _ref(params, toks, range(L - 1, L + N_NEW), cfg=cfg)
    at = toks[L:L + N_NEW]          # the served token's log-probability
    err = np.abs(got[np.arange(N_NEW), at] - want[np.arange(N_NEW), at])
    assert 0 < np.median(err) < BF16_MEDIAN_TOL
    assert err.max() < BF16_WORST_TOL


# -- the kernels, interpreted -------------------------------------------------------

@pytest.mark.parametrize("lengths", [(0, 3, 300), (256, 257, 511)])
def test_the_decode_kernel_at_a_head_of_64_equals_its_jnp_form(lengths):
    """``flash_decode_stacked`` interpreted over K and V cached two KV
    heads a row, q with zeros in the other half, against the jnp form on
    the heads as they are, 64 values wide."""
    rng = np.random.default_rng(sum(lengths))
    B, H, KV, D, L, S = len(lengths), 32, 8, 64, 2, 512
    k, v = (jnp.asarray(rng.normal(size=(L, B, KV, S, D)), jnp.float32)
            for _ in range(2))
    # [L, B, KV, S, D] -> two KV heads a row [L, B, KV / 2, S, 2 D]
    rows = lambda a: jnp.moveaxis(attention.pair_rows(  # noqa: E731
        jnp.moveaxis(a, 2, 3)), 3, 2)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kn, vn = (jnp.asarray(rng.normal(size=(B, 1, KV, D)), jnp.float32)
              for _ in range(2))
    n = jnp.asarray(lengths)
    for layer in (0, 1):
        want = attention.decode_attention_appended(q, k[layer], v[layer],
                                                   kn, vn, n)
        got = attention.unpair_heads(flash_decode.flash_decode_stacked(
            attention.pair_queries(q, KV), rows(k), rows(v),
            attention.pair_rows(kn), attention.pair_rows(vn), n,
            jnp.int32(layer), block_s=256, interpret=True, scale=D ** -0.5),
            KV)
        np.testing.assert_allclose(got, want, atol=3e-6)
        # and the jnp form on paired rows is the same arithmetic
        same = attention.unpair_heads(attention.decode_attention_appended(
            attention.pair_queries(q, KV), rows(k)[layer], rows(v)[layer],
            attention.pair_rows(kn), attention.pair_rows(vn), n,
            scale=D ** -0.5), KV)
        np.testing.assert_allclose(same, want, atol=3e-6)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_row_write_at_a_head_of_64_lands_in_its_half(dtype):
    """``append_rows_stacked`` interpreted on rows of two KV heads: each
    head's 64 values at its position in its half, nothing else moved, a
    position past capacity dropped."""
    rng = np.random.default_rng(7)
    L, B, KV, D, S = 2, 3, 4, 64, 64
    k, v = (jnp.asarray(rng.normal(size=(L, B, KV // 2, S, 2 * D)), dtype)
            for _ in range(2))
    kr, vr = (jnp.asarray(rng.normal(size=(L, B, KV, D)), dtype)
              for _ in range(2))
    pos = jnp.asarray([0, 37, S])
    got_k, got_v, *no_scales = flash_decode.append_rows_stacked(
        k, v, attention.pair_rows(kr), attention.pair_rows(vr), pos,
        interpret=True)
    assert no_scales == [None, None]
    for got, old, new in ((got_k, k, kr), (got_v, v, vr)):
        want = np.array(old)
        for b, p in enumerate([0, 37]):
            want[:, b, :, p] = np.asarray(new[:, b]).reshape(L, KV // 2,
                                                             2 * D)
        assert np.array_equal(np.asarray(got), want)


def test_the_prefill_kernel_at_a_head_of_64_runs_on_paired_heads(monkeypatch):
    from gofr_tpu.ops import flash

    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    rng = np.random.default_rng(8)
    B, S, H, KV, D = 2, 256, 8, 4, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
            for _ in range(2))
    n = jnp.asarray([256, 130])
    assert flash._pairs_ok(q, k, 128, 128, True, None, 0)
    assert not flash._pairs_ok(q, k, 128, 128, False, None, 0)   # a CPU
    got = flash.causal_attention_auto(q, k, v, lengths=n)
    valid = jnp.arange(S)[None, :] < n[:, None]
    want = attention.causal_attention(q, k, v, mask=valid)
    np.testing.assert_allclose(np.where(valid[..., None, None], got, 0),
                               np.where(valid[..., None, None], want, 0),
                               atol=3e-6)


def test_the_experts_kernel_two_tiles_wide_equals_the_loop(monkeypatch):
    """An expert [2048, 1536] int8 is over the kernel's tile budget
    whole: two tiles of 768 columns, the down product summed over them."""
    from gofr_tpu.ops import moe_experts

    assert moe_experts.tile_columns(2048, 1536, 1) == 768
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    D, F, Eh, bm = 2048, 1536, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    stacks = {name: quantize_int8(
        jax.random.normal(key, (1, Eh) + shape) * shape[0] ** -0.5, axis=2)
        for key, (name, shape) in zip(keys, {
            "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}.items())}
    blk = jnp.asarray([0, 0, 3, 7, 7, 7], jnp.int32)
    xs = jax.random.normal(keys[3], (6 * bm, D))
    n, li = jnp.int32(5), jnp.int32(0)
    want = moe.blocks_loop(xs, blk, n, stacks, li, bm)
    got = moe.blocks_kernel(xs, blk, n, stacks, li, bm, 768)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert not np.asarray(got[5 * bm:]).any()


def test_the_model_on_the_interpreted_kernels(params, monkeypatch):
    """The decode kernel over paired rows, the row append and the
    experts kernel, interpreted, against the reference."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    toks = _tokens(9, 20 + N_NEW)
    got, _ = _serve(params, toks, 20, 32, N_NEW)
    want = _ref(params, toks, range(19, 20 + N_NEW))
    assert np.abs(got - want).max() < F32_TOL


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


@pytest.mark.parametrize("length", [1, 2, 3, 15, 16, 17, 32, 33, 70, 100])
def test_engine_against_the_reference(engine, params, length):
    """Prompts around the tail's length and around each bucket, one
    token past the largest (two chunks, the last all padding but one),
    three chunks, four; every slot has had a tenant by the third case."""
    prompt = _tokens(length, length).tolist()
    served = _generate(engine, prompt, N_NEW)
    assert _held_to_the_reference(params, prompt, served) < F32_TOL


def test_engine_lattice_interleaved_with_other_slots_decode(engine, params):
    """Long prompts admitted while other slots decode: the decode blocks
    between their chunks leave a half-built slot's tails alone (it is
    not active), and the chunks leave the decoding slots' alone."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (9, 100, 2, 90, 11)]
    streams = [engine.generate(p, max_new_tokens=20, logprobs=True)
               for p in prompts]
    for p, s in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in s]
        assert len(served) == 20
        assert _held_to_the_reference(params, p, served) < F32_TOL


def test_engine_prefix_hit_restores_rows_and_tails(params):
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=4,
                           prefix_store_min=16)
    try:
        prompt = _tokens(5, 70).tolist()
        miss = _generate(eng, prompt, N_NEW)
        assert eng.stats()["prefix_cache"]["hits"] == 0
        # stored under the tokens before the last chunk boundary, with
        # the six tails as they stood there
        assert [len(e.key) for e in eng._kvc.t0.entries()] == [64]
        hit = _generate(eng, prompt, N_NEW)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        assert [t for t, _ in hit] == [t for t, _ in miss]
        assert max(abs(a[1] - b[1]) for a, b in zip(hit, miss)) < 1e-5
        assert _held_to_the_reference(params, prompt, miss) < F32_TOL
        assert _held_to_the_reference(params, prompt, hit) < F32_TOL
        # a longer prompt over the same 64 tokens resumes at 64 too,
        # into another slot's tails
        longer = prompt[:64] + _tokens(9, 40).tolist()
        served = _generate(eng, longer, N_NEW)
        assert eng.stats()["prefix_cache"]["hits"] == 2
        assert _held_to_the_reference(params, longer, served) < F32_TOL
    finally:
        eng.close()


def test_engine_says_its_tails_and_counts_them(params):
    from gofr_tpu.metrics import Manager, register_framework_metrics
    from gofr_tpu.observe import Observe
    from gofr_tpu.observe.timeline import Timeline

    m = Manager()
    register_framework_metrics(m)
    obs = Observe(metrics=m, timeline=Timeline(capacity=256))
    eng = GenerationEngine(CFG, params, slots=2, max_seq=64,
                           prompt_buckets=(16,), observe=obs, metrics=m,
                           decode_block=4)
    try:
        eng.generate([3, 4, 5], max_new_tokens=13).tokens()
        stats = eng.stats()
        events = [e for e in obs.timeline.events() if e[3] == "decode"]
        cache = eng.cache
    finally:
        eng.close()
    # what serving_stats says a slot takes is what the arrays take
    assert stats["state_bytes_per_slot"] == cache.conv.nbytes // 2 \
        == 6 * 2 * 64 * 4
    assert stats["kv_bytes_per_token"] * 64 \
        == (cache.k.nbytes + cache.v.nbytes) // 2
    assert stats["layers"] == {"conv": 6, "full": 2}
    assert stats["kv_heads_per_row"] == 2
    assert stats["moe_decode_dispatch"]["block_rows"] == 16
    assert stats["moe_decode_dispatch"]["path"] == "loop"      # a CPU
    assert stats["moe"]["expert_tokens"] > 0
    # decode events: the expert layer's two counts, then the tails moved
    # (six a step of a block of four), no ring
    # (the block queued behind the stream's last may be reaped too: it
    # moves nothing)
    assert events and all(len(e) == 11 for e in events)
    assert [e[10] for e in events[:3]] == [24, 24, 24]
    assert [e[6] for e in events[:3]] == [3, 7, 11]
    assert all(e[10] == 0 for e in events[3:])
    assert f"app_tpu_state_live_bytes {float(6 * 2 * 64 * 4)}" \
        in m.render_prometheus()
    args = [e["args"] for e in obs.timeline.chrome_trace()["traceEvents"]
            if e.get("cat") == "decode"]
    assert args and args[0]["states_updated"] == 24 \
        and "ring_rows" not in args[0]


class _Tiers:
    host_mb, redis = 64, None


@pytest.mark.parametrize("option", [
    {"paged_blocks": 8}, {"spec_decode_k": 2}, {"lora_adapters": 2},
    {"kvcache": _Tiers()}, {"mesh": object()}, {"kv_dtype": jnp.int8},
    {"serving_role": "prefill"}, {"serving_role": "decode"},
])
def test_the_engine_refuses_what_restores_a_slot_from_rows(params, option):
    from gofr_tpu.errors import UnsupportedOptions

    (name,) = option
    with pytest.raises(UnsupportedOptions, match=name) as e:
        GenerationEngine(CFG, params, slots=2, max_seq=64, **option)
    assert [opt for opt, _ in e.value.refused] == [name]
    assert lfm2.unsupported_options(serving_role="fused",
                                    kv_dtype=jnp.bfloat16) == []


def test_the_engine_refuses_a_capacity_that_is_not_whole_chunks(params):
    with pytest.raises(ValueError, match="whole prefill chunks"):
        GenerationEngine(CFG, params, slots=2, max_seq=72,
                         prompt_buckets=(16, 32))


def test_start_up_from_config_refuses_by_name():
    from gofr_tpu.config import MapConfig
    from gofr_tpu.tpu import new_engine_from_config

    base = {"TPU_MODEL": "tiny-conv-moe", "TPU_SLOTS": "2",
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
