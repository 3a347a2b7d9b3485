"""The state-space family (models/nemotron_h.py, ops/ssd.py) at the
``tiny-ssm-moe`` preset (eleven layers of three kinds in an order that
does not tile, 8 heads in 2 groups, 4 of 16 two-matrix relu^2 experts
held behind a latent), held against the benchmark's plain float32
reference (benchmarks/references/nemotron_h.py), which imports nothing
of the program and is the file the chip's ``correct`` is decided by."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _prefill_split

from gofr_tpu.models import (LLAMA_CONFIGS, family, moe,
                             nemotron_h as nh, solar_open2 as so)
from gofr_tpu.models.blocks import layer_at
from gofr_tpu.ops import ssd
from gofr_tpu.ops.quant import qmatmul
from gofr_tpu.tpu import GenerationEngine
from gofr_tpu.tpu.checkpoint import maybe_quantize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LLAMA_CONFIGS["tiny-ssm-moe"]
# |log-probability - reference|, float32 both sides: eleven layers of
# float32 sums in another order (the chunk form, experts in blocks)
F32_TOL = 2e-4
# int8 weights both sides: the per-channel scale is applied after the
# matmul in the program and before it in the reference
INT8_TOL = 2e-3
# the kernels against the jnp recurrence, float32 both sides, relative
# to the largest value: sums in another order
KERNEL_TOL = 2e-5


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_nemotron_h", os.path.join(
            REPO, "benchmarks", "references", "nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


@pytest.fixture(scope="module")
def params():
    return nh.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    # seed 2: the smallest router gap of the four moe layers over these
    # positions is 3e-4 (the reference reports it), far from a tie two
    # float32 sums in another order could break
    return jax.random.randint(jax.random.PRNGKey(2), (2, 40), 1,
                              CFG.vocab_size)


def _ref_logprobs(params, cfg, toks):
    return np.stack([np.asarray(REF.forward_logprobs(
        params, cfg, np.asarray(row), range(len(row)))[0]) for row in toks])


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_family_is_chosen_by_fields_not_by_name():
    assert family(CFG) is nh
    renamed = LLAMA_CONFIGS["tiny"].with_(
        n_layers=3, layer_pattern=["mamba", "moe", "attn"])
    assert family(renamed) is nh
    assert family(LLAMA_CONFIGS["tiny-kda-moe"]) is so
    # a kind a layer, in an order no period tiles; G < H; fewer held
    # than routed over; the experts narrower than the model
    assert nh.counts(CFG) == (5, 4, 2)
    assert len(CFG.layer_pattern) == CFG.n_layers == 11
    assert all(CFG.layer_pattern != CFG.layer_pattern[:p] * (11 // p)
               for p in range(1, 11))
    assert CFG.ssm_groups < CFG.ssm_heads
    assert moe.n_held(CFG) < CFG.n_experts
    assert moe.expert_width(CFG) == 24 != CFG.dim
    assert moe.expert_stacks(CFG) == ("w_up", "w_down")
    with pytest.raises(ValueError, match="does not name each"):
        nh.counts(CFG.with_(n_layers=12))
    with pytest.raises(ValueError, match="not rotated"):
        nh.counts(CFG.with_(use_rope=True))
    # every configuration that was there reads as before
    for name, cfg in LLAMA_CONFIGS.items():
        if name != "tiny-ssm-moe":
            assert moe.expert_width(cfg) == cfg.dim
            assert moe.expert_stacks(cfg) == moe.EXPERT_STACKS


def test_full_forward_against_the_reference(params, tokens):
    logits = nh.forward(params, CFG, tokens)
    err = np.abs(_logprobs(logits) - _ref_logprobs(params, CFG, tokens))
    assert err.max() < F32_TOL


def _serve(params, cfg, row, L, bucket, slots=3, slot=1, between=None):
    """Prefill ``row[:L]`` padded to ``bucket`` into one slot of a cache
    whose other slots idle, then decode the rest a token a step
    (``between(cache) -> cache`` before each step): log-probabilities
    [len(row), V]."""
    pad = jnp.zeros((1, bucket), jnp.int32).at[0, :L].set(row[:L])
    logits, *kv, _ = jax.jit(lambda p, t, n: nh.prefill_kv(p, cfg, t, n))(
        params, pad, jnp.asarray([L]))
    cache = nh.init_cache(cfg, slots, 64)
    cache = nh.write_kv(cache, *kv, (0, slot, 0, 0, 0),
                        cache.lengths.at[slot].set(L))
    active = jnp.arange(slots) == slot
    # traced here, so with whatever a test has patched into the program
    decode = jax.jit(lambda p, t, c: nh.decode_step(p, cfg, t, c,
                                                    active=active))
    out = [logits[0, :L]]
    for t in range(L, len(row)):
        if between is not None:
            cache = between(cache)
        step, cache, _, _ = decode(
            params, jnp.zeros((slots,), jnp.int32).at[slot].set(row[t]),
            cache)
        out.append(step[slot][None])
    return _logprobs(jnp.concatenate(out)), cache


def test_prefill_then_decode_through_the_cache(params, tokens):
    """A padded bucket writes rows, state and tail; decode steps read and
    rewrite them: the reference's full forward."""
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    got, cache = _serve(params, CFG, tokens[0], 24, 32)
    assert np.abs(got - want).max() < F32_TOL
    # the idle slots' state and tail are bit for bit what they were
    assert not np.asarray(cache.state[:, [0, 2]]).any()
    assert not np.asarray(cache.conv[:, [0, 2]]).any()


@pytest.mark.parametrize("L,bucket", [(24, 32), (32, 32), (1, 8), (3, 16)])
def test_a_padded_bucket_leaves_state_and_tail_as_at_the_last_token(
        params, tokens, L, bucket):
    pad = jnp.zeros((1, bucket), jnp.int32).at[0, :L].set(tokens[0, :L])
    _, _, _, state, conv, _ = nh.prefill_kv(params, CFG, pad,
                                            jnp.asarray([L]))
    _, _, _, state_l, conv_l, _ = nh.prefill_kv(params, CFG, tokens[:1, :L])
    # float32 sums over a bucket's chunks and over L tokens' chunks
    assert np.abs(np.asarray(state - state_l)).max() < 1e-5
    # the padding never reached the tail: the same inputs bit for bit
    # but for a matmul of another height
    assert np.abs(np.asarray(conv - conv_l)).max() < 1e-5


@pytest.mark.parametrize("chunk,L", [(16, 36), (16, 40), (8, 36), (32, 40)])
def test_left_aligned_chunks_against_the_reference(params, tokens, chunk, L):
    """Chunks from position 0, the last one padded; the slot's stale
    state and tail are not read at position 0. The scan's own chunks are
    8 tokens: 36 ends inside one, 40 at the end of one."""
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    cache = nh.init_cache(CFG, 1, 64)
    cache = cache._replace(state=cache.state + 3.0, conv=cache.conv + 2.0)
    pos = 0
    while L - pos > chunk:
        _, cache = nh.prefill_chunk(params, CFG, tokens[:1, pos:pos + chunk],
                                    cache, jnp.int32(pos),
                                    compute_logits=False)
        pos += chunk
    last = jnp.zeros((1, chunk), jnp.int32).at[0, :L - pos].set(
        tokens[0, pos:L])
    logits, cache = nh.prefill_chunk(params, CFG, last, cache, jnp.int32(pos),
                                     logit_pos=jnp.asarray([L - pos - 1]))
    assert np.abs(_logprobs(logits[0, 0]) - want[L - 1]).max() < F32_TOL
    _, _, _, state, conv, _ = nh.prefill_kv(params, CFG, tokens[:1, :L])
    # the same float32 recurrence through two to five programs
    assert np.abs(np.asarray(cache.state - state)).max() < 1e-4
    assert np.abs(np.asarray(cache.conv - conv)).max() < 1e-4


# -- what the comparison must catch --------------------------------------------

def _gated(x, up, down):
    h = qmatmul(x, up)
    return qmatmul(jax.nn.silu(h) * h, down)


def _silu(x, up, down):
    return qmatmul(jax.nn.silu(qmatmul(x, up)), down)


def _norm_before_the_gate(y, x, z, lw, cfg, dtype):
    B, S, G, R = y.shape
    y = y + x * ssd._rows(lw["d_skip"], G, R)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    o = y.reshape(B, S, G * R) * lw["ssm_norm"] * jax.nn.silu(z)
    return qmatmul(o.astype(dtype), lw["w_ssm_out"])


def _the_next_group(real):
    def inputs(u, lw, cfg, tail, lengths):
        (z, x, dx, la, bm, cm), tail = real(u, lw, cfg, tail, lengths)
        return (z, x, dx, la, jnp.roll(bm, 1, 2), jnp.roll(cm, 1, 2)), tail
    return inputs


@pytest.mark.parametrize("fault", [
    "a bfloat16 state", "the taps reversed", "the tail one token stale",
    "a gate on the expert", "SiLU for relu2", "the norm before the gate",
    "a head reading the wrong group"])
def test_each_of_these_fails_the_comparison(params, tokens, monkeypatch,
                                            fault):
    """The faults the tolerance is there to catch, each put into the
    PROGRAM (a patched function, a changed weight, a changed cache) and
    held against the reference as it is."""
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    served, between = params, None
    if fault == "a bfloat16 state":
        def between(cache):
            return cache._replace(state=cache.state.astype(jnp.bfloat16)
                                  .astype(jnp.float32))
    elif fault == "the taps reversed":
        served = {**params, "mamba": {**params["mamba"],
                                      "conv": params["mamba"]["conv"][:, ::-1]}}
    elif fault == "the tail one token stale":
        def between(cache):
            return cache._replace(conv=jnp.roll(
                cache.conv, nh.conv_channels(CFG), axis=2))
    elif fault == "a gate on the expert":
        monkeypatch.setattr(moe, "_relu2", _gated)
    elif fault == "SiLU for relu2":
        monkeypatch.setattr(moe, "_relu2", _silu)
    elif fault == "the norm before the gate":
        monkeypatch.setattr(nh, "_ssm_out", _norm_before_the_gate)
    elif fault == "a head reading the wrong group":
        monkeypatch.setattr(nh, "_ssm_inputs", _the_next_group(nh._ssm_inputs))
    got, _ = _serve(served, CFG, tokens[0], 24, 32, between=between)
    assert np.abs(got - want).max() > 5 * F32_TOL, fault
    if between is not None:
        # the fault is in the decode steps: the prefill is still right
        assert np.abs(got[:24] - want[:24]).max() < F32_TOL


def test_the_latent_goes_up_once_a_token_after_the_weighted_sum(params):
    """``W_up_latent (sum_k w_k E_k(l))``: by linearity the projection
    before the weights gives the same numbers at k times the work, so
    what is held is the work: the up-projection's matmul has one row a
    token (not one an assignment or a buffer row), the down-projection
    too, and the experts' blocks are the latent wide."""
    T = 6
    u = jax.random.normal(jax.random.PRNGKey(3), (1, T, CFG.dim))
    lw = layer_at(params["moe"], jnp.int32(1))
    jaxpr = jax.make_jaxpr(lambda u: moe.moe_ffn(u, lw, CFG)[0])(u)

    def dots(jp, found):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(tuple(v.aval.shape for v in eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                dots(sub, found)
        return found

    shapes = dots(jaxpr.jaxpr, [])
    lat, D = CFG.moe_latent_dim, CFG.dim
    assert ((T, D), (D, lat)) in shapes          # down: once a token
    assert ((T, lat), (lat, D)) in shapes        # up: once a token
    assert not [s for s in shapes if s[1] == (lat, D) and s[0][0] != T]
    bm, _ = moe.expert_dispatch(CFG, T)
    assert ((bm, lat), (lat, CFG.moe_ffn_dim)) in shapes


# -- slots ----------------------------------------------------------------------

def test_an_idle_slot_and_a_reused_slot(params, tokens):
    """A decode step leaves an idle slot's state and tail bit-equal
    (whatever they hold); a slot whose last occupant left a state starts
    its next prompt from zeros."""
    _, cache = _serve(params, CFG, tokens[0], 24, 32)
    dirty = cache._replace(state=cache.state.at[:, 0].set(7.0),
                           conv=cache.conv.at[:, 0].set(5.0))
    step, after, _, updated = nh.decode_step(
        params, CFG, jnp.asarray([9, 9, 9]), dirty,
        active=jnp.asarray([False, True, False]))
    assert int(updated) == 5
    for a, b in ((after.state, dirty.state), (after.conv, dirty.conv)):
        assert np.array_equal(np.asarray(a[:, [0, 2]]),
                              np.asarray(b[:, [0, 2]]))
        assert not np.array_equal(np.asarray(a[:, 1]), np.asarray(b[:, 1]))
    # slot 0 is taken by a new prompt, chunked from position 0
    want = _ref_logprobs(params, CFG, tokens[1:2])[0]
    small = jax.tree_util.tree_map(lambda a: a[:, :1],
                                   dirty._replace(lengths=None))
    small = small._replace(lengths=jnp.zeros((1,), jnp.int32))
    logits, _ = nh.prefill_chunk(params, CFG, tokens[1:2, :16], small,
                                 jnp.int32(0), logit_pos=jnp.asarray([15]))
    assert np.abs(_logprobs(logits[0, 0]) - want[15]).max() < F32_TOL


# -- the kernels, interpreted ---------------------------------------------------

def _recurrence_inputs(B, T, H, P, G, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    R = H // G * P
    delta = jax.nn.softplus(jax.random.normal(ks[0], (B, T, H)) - 1)
    la = -jnp.exp(jax.random.uniform(ks[1], (H,), maxval=2.5)) * delta
    dx = jax.random.normal(ks[2], (B, T, G, R)) * ssd._rows(delta, G, R)
    bm, cm = (jax.random.normal(k, (B, T, G, N)) for k in ks[3:5])
    return dx, la, bm, cm, jax.random.normal(ks[5], (B, G, N, R))


def _close(got, want):
    return float(jnp.abs(got - want).max()
                 / jnp.maximum(jnp.abs(want).max(), 1.0))


@pytest.mark.parametrize("T,chunk,dims", [
    (24, 8, (8, 8, 2, 16)), (32, 16, (4, 64, 2, 128)),
    (20, 8, (4, 64, 2, 128)), (16, 16, (2, 128, 1, 128))])
def test_the_chunk_forms_equal_the_token_recurrence(T, chunk, dims):
    """Heads of 8, of 64 (two a 128-lane slab) and of 128; a T that is
    not whole chunks is padded with identity positions."""
    dx, la, bm, cm, s0 = _recurrence_inputs(2, T, *dims)
    want_y, want_s = ssd.recurrent_ref(dx, la, bm, cm, s0)
    y, s1 = ssd.ssd_prefill(dx, la, bm, cm, s0, chunk=chunk, interpret=True)
    assert _close(y, want_y) < KERNEL_TOL and _close(s1, want_s) < KERNEL_TOL
    y, s1 = ssd.prefill_auto(dx, la, bm, cm, s0, chunk)      # the jnp form
    assert _close(y, want_y) < KERNEL_TOL and _close(s1, want_s) < KERNEL_TOL


def test_an_identity_position_leaves_the_state_bit_equal():
    dx, la, bm, cm, s0 = _recurrence_inputs(1, 8, 8, 8, 2, 16)
    for f in (ssd.recurrent_ref,
              lambda *a: ssd.chunked_ref(*a, 8),
              lambda *a: ssd.ssd_prefill(*a, chunk=8, interpret=True)):
        _, s1 = f(dx * 0, la * 0, bm, cm, s0)
        assert np.array_equal(np.asarray(s1), np.asarray(s0))


@pytest.mark.parametrize("active", [
    [True, False, True, True, False], [False] * 5, [True] * 5])
def test_the_decode_kernel_updates_the_active_states_alone(active):
    dx, la, bm, cm, s0 = _recurrence_inputs(5, 1, 8, 8, 2, 16, seed=1)
    state = jnp.stack([s0 + 1, s0, s0 - 1])
    act = jnp.asarray(active)
    args = (dx[:, 0], la[:, 0], bm[:, 0], cm[:, 0], act)
    want_y, want_s = ssd.decode_ref(state, 1, *args)
    y, got = ssd.ssd_decode(state, jnp.int32(1), *args, interpret=True)
    assert _close(y, want_y) < KERNEL_TOL and _close(got, want_s) < KERNEL_TOL
    idle = np.flatnonzero(~np.asarray(act))
    got, state = np.asarray(got), np.asarray(state)
    assert np.array_equal(got[:, idle], state[:, idle])
    assert np.array_equal(got[[0, 2]], state[[0, 2]])
    assert not np.asarray(y)[idle].any()


def test_the_model_on_the_interpreted_kernels(params, tokens, monkeypatch):
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    want = _ref_logprobs(params, CFG, tokens[:1])[0]
    got, _ = _serve(params, CFG, tokens[0], 24, 32)
    assert np.abs(got - want).max() < F32_TOL


def test_the_convolution_against_its_definition():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(5), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(6), (6,))
    tail = jax.random.normal(jax.random.PRNGKey(7), (2, 3, 6))
    y, new = ssd.conv(x, tail.reshape(2, 18), w, b, jnp.asarray([9, 5]))
    new = new.reshape(2, 3, 6)
    xs = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    want = sum(xs[:, j:j + 9] * np.asarray(w)[j] for j in range(4)) \
        + np.asarray(b)
    assert np.abs(np.asarray(y) - np.asarray(jax.nn.silu(want))).max() < 1e-5
    assert np.array_equal(np.asarray(new[0]), xs[0, 9:12])
    assert np.array_equal(np.asarray(new[1]), xs[1, 5:8])   # not the padding
    # a decode step: one input against the flat tail
    y1, new1 = ssd.conv(x[:, :1], tail.reshape(2, 18), w, b)
    assert np.abs(np.asarray(y1[:, 0]) - np.asarray(y[:, 0])).max() < 1e-6
    assert np.array_equal(np.asarray(new1.reshape(2, 3, 6)), xs[:, 1:4])


# -- the share and the model ----------------------------------------------------

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """16 experts, a chip a quarter: each share's routed part (through
    the latent), and the shared expert counted once, sum to the uncut
    reference's layer. The program computes share j from the parameters
    of a chip that holds experts 4j..4j+3 (its router renumbered so that
    the held experts are ids 0..3, as the program's share always is)."""
    whole_cfg = CFG.with_(n_experts_held=CFG.n_experts)
    layers = nh.init(whole_cfg, jax.random.PRNGKey(9))["moe"]
    h = jax.random.normal(jax.random.PRNGKey(10), (12, CFG.dim))
    every = [(e, e) for e in range(CFG.n_experts)]
    with jax.default_matmul_precision("highest"):
        uncut, _ = REF.layer_share(layers, whole_cfg, 1, h, every)
        shared, _ = REF.layer_share(layers, whole_cfg, 1, h, [])
    total = np.asarray(shared)
    for j in range(4):
        mine = [(4 * j + k, 4 * j + k) for k in range(4)]
        with jax.default_matmul_precision("highest"):
            ref_share, _ = REF.layer_share(layers, whole_cfg, 1, h, mine,
                                           shared=False)
        perm = np.arange(CFG.n_experts)
        perm[0:4], perm[4 * j:4 * j + 4] = np.arange(4 * j, 4 * j + 4), \
            np.arange(4)
        lw = {k: v[1] for k, v in layers.items()
              if k not in moe.EXPERT_STACKS}
        lw.update(router=lw["router"][:, perm],
                  router_bias=lw["router_bias"][perm],
                  experts=({k: layers[k][:, 4 * j:4 * j + 4]
                            for k in moe.expert_stacks(CFG)}, jnp.int32(1)))
        got, _ = moe.moe_ffn(h[None], lw, CFG)
        assert np.abs(np.asarray(got[0]) - np.asarray(ref_share + shared)) \
            .max() < 1e-4
        total = total + np.asarray(ref_share)
    assert np.abs(total - np.asarray(uncut)).max() < 1e-4


def test_the_int8_path(params, tokens):
    q = maybe_quantize(params, True)
    for kind, leaf in (("mamba", "w_ssm_in"), ("mamba", "w_ssm_out"),
                       ("moe", "w_latent_down"), ("moe", "w_latent_up"),
                       ("moe", "w_up"), ("moe", "ws_down"), ("attn", "wq")):
        assert hasattr(q[kind][leaf], "scale"), leaf
    assert not hasattr(q["moe"]["router"], "scale")
    assert not hasattr(q["mamba"]["conv"], "scale")
    want = _ref_logprobs(q, CFG, tokens[:1])[0]
    got, _ = _serve(q, CFG, tokens[0], 24, 32)
    assert np.abs(got - want).max() < INT8_TOL


# -- through the engine ---------------------------------------------------------
# Which slots decode together, and through which program a position
# goes, depends on when the host admits: the same float32 sums in another
# order. Every prompt below was checked against that (the reference's
# own gap a position, 20 tokens served each): the smallest router gap is
# 2.4e-4 where another order moves a score by 1e-6, and the largest
# error read 2.4e-6 against the 2e-4 held, so a loaded machine cannot
# flip an expert here. No test below asserts how steps fall into blocks.

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
    ref = np.asarray(REF.forward_logprobs(
        params, CFG, np.asarray(seq),
        range(len(prompt) - 1, len(seq)))[0])
    return max(abs(lp - ref[j, tok]) for j, (tok, lp) in enumerate(served))


def _generate(engine, prompt, n):
    return [(int(t), float(lp)) for t, lp in
            engine.generate(prompt, max_new_tokens=n, logprobs=True)]


@pytest.mark.parametrize("length", [10, 20, 32, 33, 40, 70, 100])
def test_engine_against_the_reference(engine, params, length):
    """A bucket, the next, a whole bucket, one token past it (two
    chunks, the last all padding but one), a chunk and a scan chunk,
    three chunks, four."""
    prompt = np.random.default_rng(length).integers(1, 256, length).tolist()
    served = _generate(engine, prompt, 8)
    assert _held_to_the_reference(params, prompt, served) < F32_TOL


def test_engine_lattice_interleaved_with_other_slots_decode(engine, params):
    """Two 3- and 4-chunk prompts admitted while other slots decode: the
    decode blocks between their chunks leave a half-built state alone,
    and their chunks leave the decoding slots' states alone."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, n).tolist() for n in (9, 100, 14, 90, 11)]
    streams = [engine.generate(p, max_new_tokens=20, logprobs=True)
               for p in prompts]
    for p, s in zip(prompts, streams):
        served = [(int(t), float(lp)) for t, lp in s]
        assert len(served) == 20
        assert _held_to_the_reference(params, p, served) < F32_TOL


def test_engine_prefix_hit_at_the_chunk_boundary_equals_the_miss(params):
    eng = GenerationEngine(CFG, params, slots=2, max_seq=128,
                           prompt_buckets=(16, 32), prefix_cache_slots=4,
                           prefix_store_min=16)
    try:
        prompt = np.random.default_rng(5).integers(1, 256, 70).tolist()
        miss = _generate(eng, prompt, 8)
        assert eng.stats()["prefix_cache"]["hits"] == 0
        hit = _generate(eng, prompt, 8)
        assert eng.stats()["prefix_cache"]["hits"] == 1
        # stored under the tokens before the last boundary, 64 of 70: the
        # row holds the state AT 64 and the tail of inputs 61..63
        assert [len(e.key) for e in eng._kvc.t0.entries()] == [64]
        assert [t for t, _ in hit] == [t for t, _ in miss]
        assert max(abs(a[1] - b[1]) for a, b in zip(hit, miss)) < 1e-5
        assert _held_to_the_reference(params, prompt, hit) < F32_TOL
        # a longer prompt over the same 64 tokens resumes at 64 too
        longer = prompt[:64] + np.random.default_rng(9).integers(
            1, 256, 40).tolist()
        served = _generate(eng, longer, 4)
        assert eng.stats()["prefix_cache"]["hits"] == 2
        assert _held_to_the_reference(params, longer, served) < F32_TOL
    finally:
        eng.close()


def test_engine_counts_states_and_says_their_bytes(params):
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
        stats = eng.stats()
        events = [e for e in obs.timeline.events() if e[3] == "decode"]
    finally:
        eng.close()
    # five mamba layers: 8 x 8 x 16 float32 and 3 tail inputs of
    # 64 + 2 x 2 x 16 channels
    per_slot = 5 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 24 * 4
    said = stats["moe_decode_dispatch"]
    assert (said["block_rows"], said["width"], said["path"]) == (16, 24,
                                                                  "loop")
    assert said["buffer_rows"] == moe.expert_dispatch(CFG, 2)[1]
    assert stats["moe"]["expert_tokens"] > 0
    # decode events: the expert layers' two counts, then the states: one
    # slot, five mamba layers, a state a step while it decodes
    assert events and all(len(e) == 11 for e in events)
    assert sum(e[10] for e in events) == 5 * 8
    assert f"app_tpu_state_live_bytes {float(per_slot)}" \
        in m.render_prometheus()


class _Tiers:
    host_mb, redis = 64, None


@pytest.mark.parametrize("option", [
    {"paged_blocks": 8}, {"spec_decode_k": 2}, {"lora_adapters": 2},
    {"kvcache": _Tiers()}, {"mesh": object()}, {"serving_role": "decode"},
])
def test_the_engine_refuses_what_the_state_cannot_do_yet(params, option):
    (name,) = option
    with pytest.raises(ValueError, match=name) as e:
        GenerationEngine(CFG, params, slots=2, max_seq=64, **option)
    assert [opt for opt, _ in e.value.refused] == [name]


def test_start_up_from_config_refuses_by_name():
    from gofr_tpu.config import MapConfig
    from gofr_tpu.tpu import new_engine_from_config

    base = {"TPU_MODEL": "tiny-ssm-moe", "TPU_SLOTS": "2",
            "TPU_MAX_SEQ": "64", "TPU_SEQ_BUCKETS": "16",
            "TPU_PREFIX_CACHE": "2"}  # the host tier hangs off the pool
    for key, value in (("TPU_SPEC_DECODE", "4"),
                       ("TPU_KVCACHE_HOST_MB", "64")):
        with pytest.raises(ValueError, match=key):
            new_engine_from_config(MapConfig({**base, key: value}))
    with pytest.raises(ValueError, match="whole prefill chunks"):
        GenerationEngine(CFG, nh.init(CFG, jax.random.PRNGKey(1)), slots=2,
                         max_seq=72, prompt_buckets=(16, 32))


# -- a prompt as two dispatches -------------------------------------------------

@pytest.mark.parametrize("kv_dtype,tol", [(None, F32_TOL), (jnp.int8, 0.05)])
def test_a_split_admission_is_the_one_bucket_admission(params, kv_dtype, tol):
    """A prompt admitted as a whole bucket and the rest (left-aligned: this
    family's last chunk) against the same prompt in one padded bucket:
    the same greedy tokens, logprobs and cache arrays to the chunked
    tests' tolerance, and the positions counted (tests/_prefill_split.py).
    With int8 rows the rest attends over the first part's rows as the
    cache holds them, quantized, which is what decode reads: the bound
    is the int8 engine test's."""
    _prefill_split.check(CFG, params, tol=tol, kv_dtype=kv_dtype)
