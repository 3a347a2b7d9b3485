"""The sparse-latent family (models/dots3_note.py) at the ``tiny-dsa-moe``
preset (a dense full layer, then two periods of one full layer to three
window layers; full layers keep 16 cached rows, window layers see 9
positions on a ring of 8; every width of one kind differs from the other
kind's; 4 of 16 experts held), held at the logit level against the
benchmark's plain float32 reference (benchmarks/references/dots3_note.py),
which imports nothing of the program, keeps no cache and no ring, writes
both masks as masks, and is the file the chip's ``correct`` is decided
by. Contexts run to 64 and more, so both masks leave rows out."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import (LLAMA_CONFIGS, deepseek_v3 as ds,
                             dots3_note as dn, family, laguna as lg, latent,
                             moe)
from gofr_tpu.ops import dsa, mla

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-dsa-moe"]
W = dn.ring_rows(CFG)
K = CFG.index_topk
# |log-probability - reference|, float32 both sides: nine layers of
# float32 sums in another order (experts in blocks, a ring's rows out of
# position order, absorbed against expanded)
F32_TOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_dots3_note", os.path.join(
            REPO, "benchmarks", "references", "dots3_note.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return dn.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _release_programs():
    """tests/conftest.py's guard on the process's memory maps, after
    every test of this module and at a quarter of the limit: the
    module's programs (nine unrolled layers each, buckets, chunks and
    decode steps at several shapes) are about a thousand maps apiece and
    reached 38,000 of the 65,530 a worker may hold, past which XLA's CPU
    backend dies wherever it next loads code. No engine runs here, so no
    thread is inside a dispatch when the executables go."""
    yield
    import gc

    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
        with open("/proc/sys/vm/max_map_count") as f:
            cap = int(f.read())
    except OSError:
        return
    if n_maps >= 0.25 * cap:
        jax.clear_caches()
        gc.collect()


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n) \
        .astype(np.int32)


def _ref(params, toks, rows, **kw):
    """The reference's log-probabilities after positions ``rows``. The
    tokens are padded to whole blocks of 64 (every mask is causal, so the
    padding reaches no row asked for): three shapes of the reference's
    programs in place of one a length (each is 350-400 memory maps of
    the 65,530 a worker may hold: tests/conftest.py's guard)."""
    toks = np.asarray(toks)
    toks = np.pad(toks, (0, -len(toks) % 64))
    return np.asarray(REF.forward_logprobs(params, CFG, toks, list(rows),
                                           **kw)[0])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_family_is_chosen_by_fields_not_by_name():
    assert family(CFG) is dn
    assert family(LLAMA_CONFIGS["tiny-swa-moe"]) is lg
    assert family(LLAMA_CONFIGS["tiny-mla-moe"]) is ds
    # a window layer over K and V heads is Laguna's; over a latent, this
    assert family(LLAMA_CONFIGS["tiny-mla-moe"].with_(
        layer_pattern=["full", "window", "window"], window_size=4)) is dn
    assert dn.counts(CFG) == {"full": 3, "window": 6}
    full, window = dn.sizes(CFG, "full"), dn.sizes(CFG, "window")
    assert full == latent.Sizes(4, 36, 24, 8, 20) == latent.sizes(CFG)
    assert window == latent.Sizes(2, 44, 28, 4, 12)
    assert (full.row_width, full.stored_width) == (44, 128)
    assert (dn.q_rank(CFG, "full"), dn.q_rank(CFG, "window")) == (48, 40)
    # a window size left 0 is the full layers'
    assert dn.sizes(CFG.with_(window_kv_lora_rank=0), "window").rank == 36
    with pytest.raises(ValueError, match="does not tile"):
        dn.counts(CFG.with_(window_size=1))


@pytest.mark.parametrize("without", [{"index_topk": 0},
                                     {"head_gate": False},
                                     {"lora_rescale": False}])
def test_the_indexer_the_gate_and_the_rescale_are_not_options(without):
    """The family is its three parts: a configuration without one is
    refused before a weight is drawn or a table reserved (a full layer
    with no indexer is ``deepseek_v3``'s, which ``family`` gives a
    pattern without a window layer)."""
    cfg = CFG.with_(**without)
    for build in (lambda: dn.init(cfg, jax.random.PRNGKey(0)),
                  lambda: dn.init_cache(cfg, 2, 64),
                  lambda: dn.serving_stats(cfg, 2)):
        with pytest.raises(ValueError, match=next(iter(without))):
            build()


def test_what_a_rescaled_latent_feeds_is_drawn_at_the_models_fan_in(params):
    """``w_qb``, ``w_kvb`` and ``w_iq`` take a latent that was multiplied
    by (dim / rank)^1/2; they are drawn at dim^-1/2, so that the product
    has the variance of a matrix the residual stream feeds (``init``
    says why), in the model's type and in ``tpu.random_params``'s int8
    alike. Every other leaf keeps its own fan-in."""
    from gofr_tpu.tpu import random_params

    q = random_params(dn.init, CFG, quant=True, seed=3)
    uniform = 3.0 ** 0.5 / 127.0      # a uniform int8's 1 / std
    for kind in dn.KINDS:
        for name, leaf in params[kind].items():
            if name not in ("w_qa", "w_qb", "w_kva", "w_kvb", "w_iq",
                            "w_ik", "wo"):
                continue
            fan = CFG.dim if name in ("w_qb", "w_kvb", "w_iq") \
                else leaf.shape[-2]
            # a normal cut at two standard deviations has 0.88 of its std
            assert abs(float(jnp.std(leaf)) / (0.88 * fan ** -0.5) - 1) \
                < 0.05, (kind, name)
            np.testing.assert_allclose(np.asarray(q[kind][name].scale),
                                       fan ** -0.5 * uniform, rtol=1e-6)
    assert dn.init.fan_in(CFG, "wo") is None


def test_three_tables_and_what_the_engine_is_told_of_them(params):
    cache = dn.init_cache(CFG, 3, 64)
    assert cache.rows.shape == (3, 3, 64, 128)
    assert cache.keys.shape == (3, 3, 64, CFG.index_head_dim)
    assert cache.ring.shape == (6, 3, W, 128)
    said = dn.serving_stats(CFG, 3)
    assert said["latent_bytes_per_token"] * 64 * 3 == cache.rows.nbytes
    assert said["index_bytes_per_token"] * 64 * 3 == cache.keys.nbytes
    assert said["window_bytes_per_slot"] * 3 == cache.ring.nbytes
    assert (said["window_rows"], said["index_topk"]) == (W, K)
    assert dn.kv_layout(CFG) == (1, 128) and not dn.RECOMPUTABLE
    rope = dn.get_rope_tables(CFG, 64)
    assert rope["full"][0].shape == (64, 4)
    assert rope["window"][0].shape == (64, 2)
    # the indexer's leaves are the full layers' alone, and the small ones
    # stay out of the int8 set
    from gofr_tpu.ops.quant import QuantizedLinear
    from gofr_tpu.tpu.checkpoint import maybe_quantize

    q = maybe_quantize(params, True)
    assert "w_iq" not in params["window"]
    for name in ("w_iq", "w_ik", "w_qa", "w_kvb", "wo"):
        assert isinstance(q["full"][name], QuantizedLinear), name
    for name in ("ik_norm", "ik_bias", "w_iw", "head_gate", "q_norm"):
        assert not isinstance(q["full"][name], QuantizedLinear), name


# the programs jitted (CFG closed over): one compile a shape, where a
# call made eagerly compiles, and goes to the compile cache for, every
# operation of nine layers
_prefill_kv = jax.jit(
    lambda params, toks, lens=None, logit_pos=None, *, rope_max=None:
    dn.prefill_kv(params, CFG, toks, lens, rope_max=rope_max,
                  logit_pos=logit_pos), static_argnames=("rope_max",))
_prefill_chunk = jax.jit(
    lambda params, toks, cache, start, logit_pos=None, *,
    compute_logits=True: dn.prefill_chunk(
        params, CFG, toks, cache, start, compute_logits=compute_logits,
        logit_pos=logit_pos), static_argnames=("compute_logits",))
_decode_step = jax.jit(
    lambda params, t, cache, active=None: dn.decode_step(
        params, CFG, t, cache, active=active))


def _serve(params, toks, L, bucket, n_new, slots=3, slot=1, smax=128):
    """Whole-prompt prefill of toks[:L] into ``slot``, then ``n_new``
    decode steps teacher-forced on toks[L:]: the log-probabilities after
    positions L - 1 .. L + n_new - 1, and the rows the last step kept."""
    cache = dn.init_cache(CFG, slots, smax)
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :L] = toks[:L]
    logits, *kv, _ = _prefill_kv(params, jnp.asarray(pad), jnp.asarray([L]),
                                 jnp.asarray([L - 1]), rope_max=smax)
    cache = dn.write_kv(cache, *kv, (0, slot, 0, 0),
                        cache.lengths.at[slot].set(L))
    out = [_logprobs(logits[0, 0])]
    act = jnp.arange(slots) == slot
    kept = None
    for n in range(n_new):
        t = jnp.zeros((slots,), jnp.int32).at[slot].set(int(toks[L + n]))
        logits, new, _, states, kept = _decode_step(params, t, cache, act)
        assert states is None
        cache = new._replace(
            lengths=jnp.where(act, new.lengths, cache.lengths))
        out.append(_logprobs(logits[slot]))
    return np.stack(out), cache, kept


@pytest.mark.parametrize("L,bucket", [(5, 8), (8, 8), (20, 32), (32, 32),
                                      (60, 64)])
def test_prefill_then_decode_through_the_cache(params, L, bucket):
    """A prompt under the window and under ``index_topk``, one that
    fills the ring, and three that pass both (the bucket's padding must
    not reach the ring; a bucket over ``index_topk`` selects inside the
    prefill); then 3 x W decoded tokens: the ring wraps three times more
    and every decode step past 16 positions leaves rows out."""
    toks = _tokens(L, L + 3 * W)
    got, cache, kept = _serve(params, toks, L, bucket, 3 * W)
    want = _ref(params, toks, range(L - 1, L + 3 * W))
    assert np.abs(got - want).max() < F32_TOL
    assert int(cache.lengths[1]) == L + 3 * W
    # the last step: one active slot, L + 3W - 1 rows cached and its own:
    # (kept, chosen among) a full layer
    assert kept.tolist() == [[min(L + 3 * W, K), L + 3 * W]] * 3


def test_both_masks_are_held_by_the_comparison(params):
    """The reference with the selection replaced by the first
    ``index_topk`` positions is another model wherever a query sees
    more: the comparison sees the selection, in prefill and in decode."""
    L = 24
    toks = _tokens(3, L + W)
    got, _, _ = _serve(params, toks, L, 32, W)
    rows = range(L - 1, L + W)
    assert np.abs(got - _ref(params, toks, rows)).max() < F32_TOL
    off = np.abs(got - _ref(params, toks, rows, select=REF.first_positions))
    assert off.max(axis=1).min() > 50 * F32_TOL


def test_every_decode_step_keeps_exactly_the_rows_the_reference_keeps(
        params, monkeypatch):
    """The positions ``ops.dsa.kept`` leaves in each full layer's mask at
    every decode step (bisection on the scores' bits, no sort) against
    the reference's ``jax.lax.top_k`` over the causal scores, position
    for position. The masks leave the jitted step through an ordered
    callback (run eagerly, the step's every operation is an executable
    of its own: nine thousand memory maps in a worker that may hold
    65,530)."""
    L, n_new = 20, 28
    toks = _tokens(11, L + n_new)
    seen = []
    real = dsa.kept

    def watched(score, valid, k):
        mask = real(score, valid, k)
        jax.debug.callback(lambda m: seen.append(np.asarray(m)), mask,
                           ordered=True)
        return mask

    cache = dn.init_cache(CFG, 1, 64)
    _, *kv, _ = _prefill_kv(params, jnp.asarray(toks[None, :L]), rope_max=64)
    cache = dn.write_kv(cache, *kv, (0, 0, 0, 0), jnp.asarray([L]))
    monkeypatch.setattr(dsa, "kept", watched)
    # traced here, with the watched selection in it
    step = jax.jit(lambda p, t, c: dn.decode_step(p, CFG, t, c))
    for n in range(n_new):
        _, cache, *_ = step(params, jnp.asarray(toks[L + n:L + n + 1]),
                            cache)
    jax.effects_barrier()
    masks = []
    REF.forward_logprobs(params, CFG, toks, [L], masks=masks)
    assert len(seen) == 3 * n_new and len(masks) == 3
    for n in range(n_new):
        t = L + n                   # this step's position
        for layer in range(3):
            mine = seen[3 * n + layer][0]           # [Smax + 1]
            want = np.asarray(masks[layer][t])      # [S]
            assert mine[:t].tolist() == want[:t].tolist(), (n, layer)
            assert bool(mine[64]) == bool(want[t])  # the token's own
            assert not mine[t:64].any()
            assert mine.sum() == min(t + 1, K)
    # and something was left out, by score and not by position
    last = np.asarray(masks[0][L + n_new - 1][:L + n_new])
    assert last.sum() == K and not last[:K].all()


@pytest.mark.parametrize("chunk,L", [(8, 21), (16, 40), (16, 48), (32, 50),
                                     (32, 90)])
def test_left_aligned_chunks_read_the_ring_before_they_overwrite_it(
        params, chunk, L):
    """Chunks as long as the ring and longer, the last one padded: a
    chunk reads the ring before it overwrites it, padding does not
    reach it, and a full layer's chunk selects among the rows before it
    and its own. The ring wraps three times and more inside the prompt.
    Then decode goes on from the tables the chunks left."""
    toks = _tokens(chunk + L, L + W)
    cache = dn.init_cache(CFG, 1, 128)
    pos = 0
    while L - pos > chunk:
        _, cache = _prefill_chunk(
            params, jnp.asarray(toks[None, pos:pos + chunk]), cache,
            jnp.int32(pos), compute_logits=False)
        pos += chunk
    final = np.zeros((1, chunk), np.int32)
    final[0, :L - pos] = toks[pos:L]
    logits, cache = _prefill_chunk(
        params, jnp.asarray(final), cache, jnp.int32(pos),
        jnp.asarray([L - pos - 1]))
    got = [_logprobs(logits[0, 0])]
    cache = cache._replace(lengths=jnp.asarray([L], jnp.int32))
    for n in range(W):
        logits, cache, *_ = _decode_step(
            params, jnp.asarray(toks[L + n:L + n + 1]), cache)
        got.append(_logprobs(logits[0]))
    want = _ref(params, toks, range(L - 1, L + W))
    assert np.abs(np.stack(got) - want).max() < F32_TOL
    assert L // W >= 2


@pytest.mark.parametrize("chunk,L", [(16, 40), (32, 90)])
def test_a_chunk_on_the_interpreted_walk_kernel_is_the_chunk_on_the_loop(
        params, monkeypatch, chunk, L):
    """``prefill_chunk`` with ``GOFR_FLASH_INTERPRET=1`` (every layer's
    walk in ``mla.chunk_walk_latent``: a full layer's rows under the
    selection, four blocks a slot; a window layer's ring under ``seen``;
    two tiles of queries a chunk) against ``prefill_chunk`` without: the
    logits after the last chunk and the three tables it leaves."""
    toks = _tokens(chunk + L + 1, L)
    monkeypatch.setattr(mla, "_CHUNK_BLOCK", 32)
    monkeypatch.setattr(mla, "_CHUNK_TILE_ROWS", 8)

    def lattice(kernel):
        if kernel:
            monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
        else:
            monkeypatch.delenv("GOFR_FLASH_INTERPRET", raising=False)
        assert dn.chunk_walk_kernel(CFG, 128, chunk) == kernel
        # a program of its own: the module's jitted one holds the path it
        # was first traced on
        run = jax.jit(lambda toks, cache, start, logit_pos: dn.prefill_chunk(
            params, CFG, toks, cache, start, logit_pos=logit_pos))
        cache = dn.init_cache(CFG, 1, 128)
        for pos in range(0, L, chunk):
            piece = np.zeros((1, chunk), np.int32)
            piece[0, :min(chunk, L - pos)] = toks[pos:pos + chunk]
            logits, cache = run(jnp.asarray(piece), cache, jnp.int32(pos),
                                jnp.asarray([min(chunk, L - pos) - 1]))
        return _logprobs(logits[0, 0]), cache

    want, on_loop = lattice(False)
    got, on_kernel = lattice(True)
    assert np.abs(got - want).max() < F32_TOL
    assert np.abs(got - _ref(params, toks, [L - 1])[0]).max() < F32_TOL
    for a, b in zip(on_kernel[:3], on_loop[:3]):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-5


def test_slots_under_and_over_both_masks_in_one_batch(params):
    """Three slots in one decode batch: 3 positions (under the window
    and the selection), 40 (past both) and an idle one whose tables take
    nothing but the garbage row at its frozen cursor."""
    cache = dn.init_cache(CFG, 3, 64)
    toks = {0: _tokens(1, 3 + 4), 2: _tokens(2, 40 + 4)}
    for slot, L in ((0, 3), (2, 40)):
        pad = np.zeros((1, 64), np.int32)
        pad[0, :L] = toks[slot][:L]
        _, *kv, _ = _prefill_kv(params, jnp.asarray(pad), jnp.asarray([L]),
                                rope_max=64)
        cache = dn.write_kv(cache, *kv, (0, slot, 0, 0),
                            cache.lengths.at[slot].set(L))
    before = cache
    act = jnp.asarray([True, False, True])
    for n in range(4):
        t = jnp.asarray([toks[0][3 + n], 0, toks[2][40 + n]], jnp.int32)
        logits, new, _, _, kept = _decode_step(params, t, cache, act)
        cache = new._replace(
            lengths=jnp.where(act, new.lengths, cache.lengths))
        for slot, L in ((0, 3), (2, 40)):
            want = _ref(params, toks[slot], [L + n - 1 + 1])
            assert np.abs(_logprobs(logits[slot]) - want[0]).max() < F32_TOL
        assert kept.tolist() == [[3 + n + 1 + K, 3 + n + 1 + 40 + n + 1]] * 3
    # an idle slot's garbage row and key land on its own tables, at its
    # frozen cursor (0), and every other row is untouched; its ring takes
    # nothing: the row at a cursor is the oldest LIVE one, not a spare
    for a, b in zip(before[:2], cache[:2]):
        assert np.array_equal(np.asarray(a[:, 1, 1:]),
                              np.asarray(b[:, 1, 1:]))
    assert np.array_equal(np.asarray(before.ring[:, 1]),
                          np.asarray(cache.ring[:, 1]))
    # a cursor parked at capacity writes on none of the three
    parked = cache._replace(lengths=cache.lengths.at[1].set(64))
    _, after, *_ = _decode_step(params, jnp.asarray([1, 2, 3]), parked)
    for a, b in zip(parked[:3], after[:3]):
        assert np.array_equal(np.asarray(a[:, 1]), np.asarray(b[:, 1]))


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """16 experts, 4 a chip: each share's routed part, and the shared
    expert counted once, sum to the uncut reference's layer. The program
    computes share j from the parameters of a chip that holds experts
    4j..4j+3 (its router renumbered so that the held experts are ids
    0..3, as the program's share always is)."""
    whole_cfg = CFG.with_(n_experts_held=CFG.n_experts)
    layers = dn.init(whole_cfg, jax.random.PRNGKey(9))["moe"]
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
        perm = np.arange(CFG.n_experts)
        perm[0:4], perm[4 * j:4 * j + 4] = np.arange(4 * j, 4 * j + 4), \
            np.arange(4)
        lw = {k: v[0] for k, v in layers.items()
              if k not in moe.EXPERT_STACKS}
        lw.update(router=lw["router"][:, perm],
                  router_bias=lw["router_bias"][perm],
                  experts=({k: layers[k][:, 4 * j:4 * j + 4]
                            for k in moe.EXPERT_STACKS}, jnp.int32(0)))
        got, _ = moe.moe_ffn(h[None], lw, CFG)
        assert np.abs(np.asarray(got[0]) - np.asarray(ref_share + shared)) \
            .max() < 1e-4
        total = total + np.asarray(ref_share)
    assert np.abs(total - np.asarray(uncut)).max() < 1e-4


# -- the kernels, interpreted, against their jnp forms ---------------------------

def _rows(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("lengths", [(0, 3, 40), (64, 17, 33), (1, 64, 0)])
def test_the_masked_decode_kernel_equals_its_jnp_form(lengths):
    """Rank 32 in a 128-lane row, 5 heads, blocks of 16: a slot with
    nothing cached, one whose own row is left out, masks that leave out
    whole blocks, the first and the last among them."""
    B, H, S, width, rank = 3, 5, 64, 128, 32
    rows = _rows(1, (2, B, S, width))
    q = _rows(2, (B, H, width)) * 0.3
    new = _rows(3, (B, width))
    keep = jax.random.bernoulli(jax.random.PRNGKey(4), 0.4, (B, S))
    keep = keep.at[1, :16].set(False).at[1, 48:].set(False)
    keep = keep.at[2, 16:32].set(False)
    own = jnp.asarray([True, False, True])
    lens = jnp.asarray(lengths, jnp.int32)
    # a slot with nothing kept below its cursor must keep its own row
    own = own | (jnp.sum(keep & (jnp.arange(S)[None] < lens[:, None]), 1)
                 == 0)
    want = mla.decode_attention_reference(q, rows[1], new, lens, rank, keep,
                                          own)
    got = mla.decode_attention_kept(q, rows, new, lens, jnp.int32(1), keep,
                                    own, rank=rank, block_s=16,
                                    interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    # and with nothing left out it is the kernel GigaChat runs
    every = jnp.ones((B, S), bool)
    plain = mla.decode_attention_stacked(q, rows, new, lens, jnp.int32(1),
                                         rank=rank, block_s=16,
                                         interpret=True)
    same = mla.decode_attention_kept(q, rows, new, lens, jnp.int32(1), every,
                                     jnp.ones((B,), bool), rank=rank,
                                     block_s=16, interpret=True)
    assert np.array_equal(np.asarray(plain), np.asarray(same))


@pytest.mark.parametrize("lengths", [(0, 3, 7), (16, 17, 31), (32, 33, 100)])
def test_the_ring_decode_kernel_equals_its_jnp_form(lengths):
    """A ring of 32 rows at a second width (rank 128 in 256 lanes): under
    the ring, at its edge, wrapped. The ring's rows are read in the order
    they lie; the answer is the softmax over the last 32 positions and
    the token's own."""
    B, H, Wr, width, rank = 3, 4, 32, 256, 128
    rings = _rows(5, (2, B, Wr, width))
    q = _rows(6, (B, H, width)) * 0.2
    new = _rows(7, (B, width))
    lens = jnp.asarray(lengths, jnp.int32)
    want = mla.decode_attention_reference(q, rings[0], new,
                                          jnp.minimum(lens, Wr), rank)
    got = mla.decode_attention_ring(q, rings, new, lens, jnp.int32(0),
                                    rank=rank, block_s=16, interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert np.abs(np.asarray(mla.ring_decode_attention(
        q, rings, new, lens, jnp.int32(0), rank=rank, block_s=None))
        - np.asarray(want)).max() < 1e-6


@pytest.mark.parametrize("live,rows", [(0, 64), (5, 64), (40, 64), (64, 64),
                                       (8, 8)])
def test_the_chunk_walk_equals_one_softmax_over_every_row(monkeypatch, live,
                                                          rows):
    """``chunk_attention_kept`` walks the cached rows a block at a time
    up to ``live`` under a running softmax; it equals one softmax over
    all of them and the chunk's own tokens, whatever lies past ``live``'s
    block (NaN here: a row the walk fetched would show), and for a query
    whose selection kept none of the chunk's own tokens."""
    b, c, h, rank, rope, dn_, dv = 2, 8, 3, 16, 4, 6, 5
    ks = jax.random.split(jax.random.PRNGKey(live), 8)
    q_cat = jax.random.normal(ks[0], (b, c, h, rank + rope))
    q = jax.random.normal(ks[1], (b, c, h, dn_ + rope))
    cached = jax.random.normal(ks[2], (b, rows, rank + rope))
    # past the last block the walk fetches, poison
    fetched = -(-live // 16) * 16
    cached = jnp.where(jnp.arange(rows)[None, :, None] < fetched, cached,
                       jnp.nan)
    k_nope = jax.random.normal(ks[3], (b, c, h, dn_))
    k_pe = jax.random.normal(ks[4], (b, c, rope))
    v = jax.random.normal(ks[5], (b, c, h, dv))
    keep_cache = jax.random.bernoulli(ks[6], 0.6, (b, c, rows)) \
        & (jnp.arange(rows) < live)
    keep_new = jax.random.bernoulli(ks[7], 0.7, (b, c, c)) \
        & jnp.tril(jnp.ones((c, c), bool)) | jnp.eye(c, dtype=bool)
    if live:
        # query 0 keeps none of the chunk's tokens, and one cached row
        keep_new = keep_new.at[:, 0].set(False)
        keep_cache = keep_cache.at[:, 0, 0].set(True)

    monkeypatch.setattr(mla, "_CHUNK_BLOCK", 16)
    o_lat, o_new = jax.jit(lambda n: mla.chunk_attention_kept(
        q_cat, q, cached, k_nope, k_pe, v, rank, keep_cache, keep_new, n))(
            jnp.int32(live))
    clean = jnp.nan_to_num(cached)
    s_cache = jnp.where(keep_cache[:, None],
                        jnp.einsum("bqhw,btw->bhqt", q_cat, clean), -jnp.inf)
    s_new = jnp.where(
        keep_new[:, None],
        jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn_], k_nope)
        + jnp.einsum("bqhd,bkd->bhqk", q[..., dn_:], k_pe), -jnp.inf)
    probs = jax.nn.softmax(jnp.concatenate([s_cache, s_new], -1), -1)
    np.testing.assert_allclose(
        o_lat, jnp.einsum("bhqt,btr->bqhr", probs[..., :rows],
                          clean[..., :rank]), atol=2e-5)
    np.testing.assert_allclose(
        o_new, jnp.einsum("bhqk,bkhd->bqhd", probs[..., rows:], v),
        atol=2e-5)


@pytest.mark.parametrize("lengths", [(0, 5, 64), (16, 17, 33)])
def test_the_index_score_kernel_equals_its_jnp_form(lengths):
    B, Hi, S, d = 3, 6, 64, 128
    keys = _rows(8, (2, B, S, d))
    q = _rows(9, (B, Hi, d))
    w = _rows(10, (B, Hi))
    lens = jnp.asarray(lengths, jnp.int32)
    want = dsa.decode_scores_reference(q, w, keys[1], lens)
    got = dsa.index_scores_stacked(q, w, keys, lens, jnp.int32(1),
                                   block_s=16, interpret=True)
    live = np.arange(S)[None] < np.asarray(lens)[:, None]
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < 1e-4
    assert (np.asarray(got)[~live] <= -1e29).all()
    # by equation, one position
    by_hand = sum(float(w[2, j]) * max(float(q[2, j] @ keys[1, 2, 7]), 0.0)
                  for j in range(Hi))
    assert abs(float(want[2, 7]) - by_hand) < 1e-3


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_kept_is_top_k_as_a_mask_with_its_ties(k):
    """Bisection on the bit patterns against ``jax.lax.top_k``: negative
    scores, zeros of both signs aside, equal scores at the threshold
    (the lowest positions win, as top_k's indices say), rows with fewer
    than k valid positions, a row with none."""
    rng = np.random.default_rng(k)
    score = rng.normal(size=(6, 40)).astype(np.float32)
    score[1] = np.round(score[1])               # many ties
    score[2] = 1.5                              # all equal
    score[3] = -np.abs(score[3])                # all negative
    valid = rng.random((6, 40)) < 0.8
    valid[4, 3:] = False                        # fewer than k, mostly
    valid[5] = False
    got = np.asarray(dsa.kept(jnp.asarray(score), jnp.asarray(valid), k))
    masked = np.where(valid, score, -np.inf)
    idx = np.asarray(jax.lax.top_k(jnp.asarray(masked), min(k, 40))[1])
    want = np.zeros_like(valid)
    np.put_along_axis(want, idx, True, axis=1)
    want &= valid
    assert np.array_equal(got, want)
    assert (got.sum(1) == np.minimum(valid.sum(1), k)).all()


@pytest.mark.parametrize("k", [1, 5, 16, 64])
def test_the_references_mask_is_the_set_top_k_names(k):
    """The reference writes its mask from ``top_k``'s k-th value and not
    by scattering its indices (a TPU scatters a million a second); it is
    the same set, ties at the threshold and rows shorter than k
    included."""
    rng = np.random.default_rng(k)
    score = rng.normal(size=(40, 40)).astype(np.float32)
    score[::3] = np.round(score[::3])           # many ties
    score[7] = 0.25                             # all equal
    causal = np.tril(np.ones((40, 40), bool))
    got = np.asarray(REF.top_positions(jnp.asarray(score),
                                       jnp.asarray(causal), k))
    idx = np.asarray(jax.lax.top_k(     # np.round made zeros of both signs
        jnp.where(causal, score + 0.0, -jnp.inf), min(k, 40))[1])
    want = np.zeros_like(causal)
    np.put_along_axis(want, idx, True, axis=1)
    assert np.array_equal(got, want & causal)


def test_the_model_on_the_interpreted_kernels(params, monkeypatch):
    """The three kernels interpreted, in the model: the masked walk over
    a full layer's rows, the ring walk at the window layers' width, the
    score pass over the index keys."""
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    L = 20
    toks = _tokens(4, L + W)
    got, _, kept = _serve(params, toks, L, 32, W, smax=64)
    want = _ref(params, toks, range(L - 1, L + W))
    assert np.abs(got - want).max() < F32_TOL
    assert kept.tolist() == [[K, L + W]] * 3
