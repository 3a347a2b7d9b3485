"""jax.named_scope on the model's blocks and on the ops/ kernels is
metadata: it puts the block's name into every operation's op_name, which
is what a device trace shows beside the operation. These tests lower the
serving engine's decode and prefill programs on the CPU and look for the
scopes in the lowered text's locations."""

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import LLAMA_CONFIGS, llama
from gofr_tpu.tpu import GenerationEngine

DENSE_BLOCKS = ("embed", "attn_qkv", "kv_write", "attn", "attn_out", "mlp",
                "lm_head", "sampling")
KERNELS = ("rms_norm", "qmatmul", "apply_rope")


@pytest.fixture(scope="module")
def engine():
    cfg = LLAMA_CONFIGS["tiny"]
    eng = GenerationEngine(cfg, llama.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(8, 16),
                           decode_block=2, kv_dtype=jnp.int8)
    yield eng
    eng.close()


def _lowered(eng, which: str) -> str:
    i32 = jnp.int32
    if which == "decode":
        low = eng._step_jit.lower(eng.cache, eng.params, eng._warm_pack(),
                                  eng._host_carry(), eng._key)
    elif which == "prefill":
        low = eng._prefill_jit.lower(
            eng.cache, eng.params, jnp.zeros((1, 8), i32), i32(5), i32(0),
            jnp.float32(0.0), i32(0), eng._key, i32(0), i32(0), None)
    else:
        low = eng._chunk_final_jit.lower(
            eng.cache, eng.params, jnp.zeros((1, 8), i32), i32(16), i32(0),
            i32(24), i32(7), jnp.float32(0.0), i32(0), eng._key, i32(0),
            i32(0), None)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("which", ["decode", "prefill", "chunk"])
@pytest.mark.parametrize("scope", DENSE_BLOCKS + KERNELS)
def test_scope_is_in_the_lowered_programs_op_names(engine, which, scope):
    text = _lowered(engine, which)
    names = [line for line in text.splitlines() if "loc(" in line]
    assert any(f'"{scope}"' in line or f"/{scope}/" in line
               or f"{scope}/" in line for line in names), scope


@pytest.mark.parametrize("which, kernel", [
    ("decode", "decode_attention_appended"), ("decode", "quantize_kv"),
    ("chunk", "chunk_attention"), ("chunk", "quantize_kv")])
def test_attention_kernels_name_themselves(engine, which, kernel):
    assert kernel in _lowered(engine, which)


def test_moe_blocks_are_scoped():
    cfg = LLAMA_CONFIGS["tiny-moe"] if "tiny-moe" in LLAMA_CONFIGS else None
    if cfg is None:
        import dataclasses
        cfg = dataclasses.replace(LLAMA_CONFIGS["tiny"], n_experts=4,
                                  experts_per_token=2)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    cache = llama.init_cache(cfg, 2, 32)
    text = jax.jit(lambda p, t, c: llama.decode_step(p, cfg, t, c)).lower(
        params, jnp.zeros((2,), jnp.int32), cache).as_text(debug_info=True)
    for scope in ("moe", "moe_route", "moe_experts"):
        assert f'"{scope}"' in text or f"{scope}/" in text, scope
    assert '"mlp"' not in text


# -- the routed experts of the families that dispatch through moe_ffn ---------

@pytest.fixture(scope="module")
def routed_engine():
    from gofr_tpu.models import deepseek_v3

    cfg = LLAMA_CONFIGS["tiny-mla-moe"]
    eng = GenerationEngine(cfg, deepseek_v3.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(8, 16))
    yield eng
    eng.close()


@pytest.mark.parametrize("which", ["decode", "prefill"])
@pytest.mark.parametrize("scope", ["moe/route", "moe/experts/tables",
                                   "moe/experts/fill", "moe/shared"])
def test_expert_layer_scopes_tell_tables_from_blocks(routed_engine, which,
                                                     scope):
    """Inside ``moe/experts`` the dispatch tables (counted: index
    arithmetic that streams nothing) and the buffer's fill have scopes of
    their own, so a trace tells them from the blocks' kernel or loop."""
    text = _lowered(routed_engine, which)
    names = [line for line in text.splitlines() if "loc(" in line]
    assert any(f'{scope}"' in line or f"{scope}/" in line
               for line in names), scope
    assert "sort" not in "".join(
        line for line in names if "moe/experts" in line)


def test_stats_say_how_the_dispatch_tables_are_built(routed_engine):
    """``stats()["moe_decode_dispatch"]``: what the benchmark's readers
    key on (the block's and the buffer's rows, the width, the path) and
    how the tables are built."""
    from gofr_tpu.models import moe

    said = routed_engine.stats()["moe_decode_dispatch"]
    bm, rows = moe.expert_dispatch(routed_engine.cfg, 2)
    assert said == {"block_rows": bm, "buffer_rows": rows,
                    "width": routed_engine.cfg.dim, "path": "loop",
                    "tables": "counted"}


# -- the sparse-latent family's spans: the indexer, the selection, the two
# -- decode attentions, the gates -------------------------------------------

@pytest.fixture(scope="module")
def selecting_engine():
    from gofr_tpu.models import dots3_note

    cfg = LLAMA_CONFIGS["tiny-dsa-moe"]
    eng = GenerationEngine(cfg, dots3_note.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(8, 32))
    yield eng
    eng.close()


def _scoped(text: str, scope: str) -> bool:
    names = [line for line in text.splitlines() if "loc(" in line]
    return any(f'{scope}"' in line or f"{scope}/" in line for line in names)


@pytest.mark.parametrize("scope", [
    "mla/q_proj", "mla/q_absorb", "dsa/index_proj", "dsa/select",
    "dsa/select/dsa/index_scores", "dsa/select/dsa/top_k",
    "mla/sparse_decode_attn", "mla/window_decode_attn", "mla/head_gate",
    "kv_write"])
def test_the_selecting_familys_decode_scopes(selecting_engine, scope):
    """A device trace tells the indexer's projections, its score pass,
    the top-k, the attention over the rows kept, the ring attention and
    the gates apart by these names (benchmarks/metrics reads the kernels
    by their jitted functions' names, the rest by these)."""
    assert _scoped(_lowered(selecting_engine, "decode"), scope), scope


@pytest.mark.parametrize("which,scope", [
    ("prefill", "dsa/index_proj"), ("chunk", "dsa/index_proj"),
    ("chunk", "dsa/select"), ("chunk", "mla/chunk_attn_kept"),
    ("chunk", "mla/window_chunk"), ("prefill", "mla/prefill_attn"),
    ("prefill", "mla/head_gate")])
def test_the_selecting_familys_prompt_scopes(selecting_engine, which, scope):
    assert _scoped(_lowered(selecting_engine, which), scope), scope


def test_a_bucket_that_cannot_pass_index_topk_computes_no_score(
        selecting_engine):
    """The 8-token prefill of a model that keeps 16 rows: the keys are
    made and cached, no score and no top-k is in the program; the
    32-token one selects."""
    i32 = jnp.int32
    eng = selecting_engine

    def prefill(bucket):
        return eng._prefill_jit.lower(
            eng.cache, eng.params, jnp.zeros((1, bucket), i32), i32(5),
            i32(0), jnp.float32(0.0), i32(0), eng._key, i32(0), i32(0),
            None).as_text(debug_info=True)

    assert not _scoped(prefill(8), "dsa/select")
    assert _scoped(prefill(8), "dsa/index_proj")
    assert _scoped(prefill(32), "dsa/select")


# -- the looped family: the passes, the four norms --------------------------------

@pytest.fixture(scope="module")
def looped_engine():
    from gofr_tpu.models import ouro

    cfg = LLAMA_CONFIGS["tiny-loop"]
    eng = GenerationEngine(cfg, ouro.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(8, 16),
                           decode_block=2, kv_dtype=jnp.int8)
    yield eng
    eng.close()


@pytest.mark.parametrize("which", ["decode", "prefill", "chunk"])
@pytest.mark.parametrize("scope", [
    "embed", "attn_qkv", "attn_out", "mlp", "kv_write", "lm_head",
    "loop/final_norm", "norm/post_attn", "norm/post_mlp"])
def test_the_looped_familys_scopes(looped_engine, which, scope):
    """A device trace tells the pass's end (the final norm between
    passes) and the two norms inside the residual branches from the
    blocks every dense family names (benchmarks/metrics reads the
    kernels by their jitted functions' names)."""
    assert _scoped(_lowered(looped_engine, which), scope), scope


def test_the_looped_familys_decode_attention_is_scoped(looped_engine,
                                                       monkeypatch):
    """On the reference path the attention sits under ``attn``; with the
    kernels on (interpreted here) it is ``attn/flash_decode`` and the
    write ``kv_write/kv_append``, as in every family that runs them."""
    from gofr_tpu.models import ouro

    eng = looped_engine
    assert "decode_attention_appended" in _lowered(eng, "decode")
    monkeypatch.setenv("GOFR_FLASH_INTERPRET", "1")
    text = jax.jit(lambda p, t, c: ouro.decode_step(p, eng.cfg, t, c)).lower(
        eng.params, jnp.zeros((2,), jnp.int32), eng.cache).as_text(
        debug_info=True)
    assert _scoped(text, "attn/flash_decode")
    assert _scoped(text, "kv_write/kv_append")


# -- the state-space family's layer of two halves ---------------------------------

@pytest.fixture(scope="module")
def two_halves_engine():
    from gofr_tpu.models import nemotron_h

    cfg = LLAMA_CONFIGS["tiny-ssm-dense"]
    eng = GenerationEngine(cfg, nemotron_h.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(8, 16),
                           decode_block=2)
    yield eng
    eng.close()


@pytest.mark.parametrize("which", ["decode", "prefill", "chunk"])
@pytest.mark.parametrize("scope", [
    "embed", "ssm/in", "ssm/conv", "ssm/dt", "ssm/norm", "ssm/out",
    "attn_qkv", "attn_out", "ffn/in", "ffn/out", "kv_write", "lm_head/tied"])
def test_the_layer_of_two_halves_scopes(two_halves_engine, which, scope):
    """The ``ssm/*`` scopes as the state-space family has them, a scope
    on each of the feed-forward's two products and one on the tied
    head's (benchmarks/metrics tells the feed-forward's and the head's
    operations by their shapes, a device trace's reader by these)."""
    assert _scoped(_lowered(two_halves_engine, which), scope), scope


@pytest.mark.parametrize("which,scope", [("decode", "ssm/scan/decode"),
                                         ("prefill", "ssm/scan/chunk"),
                                         ("chunk", "ssm/scan/chunk")])
def test_the_layer_of_two_halves_scan_scopes(two_halves_engine, which,
                                             scope):
    assert _scoped(_lowered(two_halves_engine, which), scope), scope


def test_stats_say_a_slots_state_a_tokens_rows_and_paired_rows(
        two_halves_engine):
    stats = two_halves_engine.stats()
    assert stats["state_bytes_per_slot"] == 4 * (8 * 16 * 16 * 4
                                                 + 3 * 160 * 4)
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 64 * 4
    assert stats["kv_heads_per_row"] == 2


# -- the block-diffusion family: a pass over the slots' blocks -----------------

@pytest.fixture(scope="module")
def block_engine():
    from gofr_tpu.models import sdar

    cfg = LLAMA_CONFIGS["tiny-diffusion-moe"]
    eng = GenerationEngine(cfg, sdar.init(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_seq=64, prompt_buckets=(8, 16),
                           decode_block=3)
    yield eng
    eng.close()


@pytest.mark.parametrize("scope", [
    "diffusion/denoise", "diffusion/sample", "diffusion/commit",
    "diffusion/emit", "attn/block_decode", "moe/experts", "moe/route",
    "attn/qk_norm", "kv_write", "lm_head", "sampling"])
def test_the_block_familys_pass_scopes(block_engine, scope):
    """What a device trace shows beside a pass's operations: the stack,
    the head and the order's pick under ``diffusion/denoise`` (the
    block's attention and the experts in the layer scan it holds: a
    scan's body and a conditional's branch name their operations
    afresh), the rows' write under
    ``diffusion/commit``."""
    text = _lowered(block_engine, "decode")
    names = [line for line in text.splitlines() if "loc(" in line]
    assert any(f"{scope}/" in line or f'{scope}"' in line
               for line in names), scope


@pytest.mark.parametrize("which", ["prefill", "chunk"])
def test_the_block_familys_prompt_programs_run_no_head(block_engine, which):
    """A prefill yields no token: no ``lm_head`` scope in a prompt
    program, whose sampler reads zeros; the attention is the
    block-causal one."""
    text = _lowered(block_engine, which)
    assert "lm_head" not in text
    assert ("chunk_attention" if which == "chunk"
            else "causal_attention") in text
