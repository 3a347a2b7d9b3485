"""gigachat3.1-702b-int8-ep16: the published keys against the
``model_config`` the program runs, the chip's share against the published
counts, the byte count, the traffic inside the cache, and the rehearsal
end to end with the family's own reference."""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

from benchmarks import roofline_deepseek_v3 as rf, traffic  # noqa: E402

NAME = "gigachat3.1-702b-int8-ep16"
CELL = NAME + ".reason-sat"
MARK = "the family's reference was called"


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_every_published_width_is_what_the_program_runs():
    cfg = _cfg()
    mc = cfg["model_config"]
    for key, field in (
            ("hidden_size", "dim"), ("intermediate_size", "ffn_dim"),
            ("moe_intermediate_size", "moe_ffn_dim"),
            ("num_attention_heads", "n_heads"),
            ("num_key_value_heads", "n_kv_heads"),
            ("kv_lora_rank", "kv_lora_rank"), ("q_lora_rank", "q_lora_rank"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("v_head_dim", "v_head_dim"),
            ("num_experts_per_tok", "experts_per_token"),
            ("n_group", "n_expert_groups"), ("topk_group", "topk_groups"),
            ("n_shared_experts", "n_shared_experts"),
            ("routed_scaling_factor", "routed_scaling"),
            ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
            ("rope_scaling", "rope_scaling"),
            ("tie_word_embeddings", "tie_embeddings"),
            ("num_hidden_layers", "n_layers"),
            ("first_k_dense_replace", "n_dense_layers"),
            ("vocab_size", "vocab_size"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["topk_method"] == "noaux_tc"
    assert cfg["model_type"] == "deepseek_v3"
    # query/key width and value width are separate keys that happen to
    # be equal here; the program's preset keeps them apart
    assert cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] == 192
    assert cfg["v_head_dim"] == 192


def test_the_share_against_the_published_counts():
    cfg = _cfg()
    mc, pub = cfg["model_config"], cfg["published"]
    assert set(pub) == set(cfg["reduced"])
    # the router stays as wide as published; the chip holds a 16th
    assert mc["n_experts"] == pub["n_routed_experts"] == 256
    assert mc["n_experts_held"] == cfg["n_routed_experts"] == 16
    assert cfg["chips_a_layer"] * mc["n_experts_held"] == mc["n_experts"]
    # the guide's floors: >= 4 layers after the dense ones, >= 8 experts,
    # >= 1/8 of the vocabulary
    assert mc["n_layers"] - mc["n_dense_layers"] >= 4
    assert mc["n_experts_held"] >= 8
    assert mc["vocab_size"] * 8 == pub["vocab_size"]
    assert mc["n_dense_layers"] == 1 < pub["first_k_dense_replace"]
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["env"]["TPU_SLOTS"] == "128"
    assert cfg["env"]["TPU_KV_DTYPE"] == "bfloat16"


def _params(mc, layers, dense, held, vocab):
    """Parameters, ISSUE 28's way: 1 byte each."""
    d, h = mc["dim"], mc["n_heads"]
    attn = (d * mc["q_lora_rank"]
            + mc["q_lora_rank"] * h * (mc["qk_nope_head_dim"]
                                       + mc["qk_rope_head_dim"])
            + d * (mc["kv_lora_rank"] + mc["qk_rope_head_dim"])
            + mc["kv_lora_rank"] * h * (mc["qk_nope_head_dim"]
                                        + mc["v_head_dim"])
            + h * mc["v_head_dim"] * d)
    expert = 3 * d * mc["moe_ffn_dim"]
    routed = attn + expert * mc["n_shared_experts"] + d * mc["n_experts"] \
        + held * expert
    return (dense * (attn + 3 * d * mc["ffn_dim"])
            + (layers - dense) * routed + 2 * vocab * d), attn, expert, routed


def test_the_byte_count_of_the_share():
    """ISSUE 28's arithmetic at 1 byte a parameter: attention 132.58 M a
    layer, an expert 44.04 M, a routed layer with 16 experts 883.1 M, the
    share 7.82 GB, the whole model 702 B; and the bytes the program
    really holds (roofline_deepseek_v3), 1.8% more: the embedding slice
    and the router are bfloat16, and int8 leaves carry float32 scales."""
    mc = _cfg()["model_config"]
    share, attn, expert, routed = _params(mc, 9, 1, 16, 16032)
    assert abs(attn / 132.58e6 - 1) < 0.001
    assert abs(expert / 44.04e6 - 1) < 0.001
    assert abs(routed / 883.1e6 - 1) < 0.001
    assert abs(share / 7.82e9 - 1) < 0.01
    assert abs(_params(mc, 64, 3, 256, 128256)[0] / 702e9 - 1) < 0.01
    held = rf.share_weight_bytes(mc)
    assert share < held < share * 1.025
    assert abs(rf.attention_weight_bytes(mc) / attn - 1) < 0.01
    assert abs(rf.expert_bytes(mc) / expert - 1) < 0.01
    # the latent row: 576 values, 1,152 B a layer, 10,368 B a token
    assert rf.row_bytes(mc) == 1152
    assert rf.kv_bytes_per_token(mc) == 10368
    assert abs(128 * 2048 * rf.kv_bytes_per_token(mc) / 2.72e9 - 1) < 0.01


def test_reason_sat_stays_inside_the_cache():
    mc = _cfg()["model_config"]
    params = traffic.load(os.path.join(BENCH, "traffic", "reason-sat.json"))
    assert params["loop"] == "closed" and params["clients"] == 256
    assert params["prompt_tokens"] == {"median": 256, "sigma": 0.7,
                                       "min": 64, "max": 768}
    assert params["output_tokens"] == {"median": 768, "sigma": 0.4,
                                       "min": 256, "max": 1216}
    sched = traffic.build(params, 7, 50.0)
    assert max(r["prompt"] + r["output"] for r in sched["requests"]) \
        < mc["max_seq"] - 2
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-sat"


def test_the_expert_readers_take_the_dispatch_heights_from_the_program():
    """``moe.experts_ms`` finds the experts' device operations by the
    heights the engine's stats give; a program that gives none (the
    parent of the PR that brought the family) reads nothing."""
    from types import SimpleNamespace

    from benchmarks.metrics import _deepseek_v3 as readers
    mc = _cfg()["model_config"]
    ops = {"fusion.1 bf16[16,2048]": 0.25, "fusion.2 bf16[1264,7168]": 0.5,
           "fusion.3 bf16[64,2048]": 4.0, "fusion.4 bf16[128,7168]": 8.0}
    ctx = SimpleNamespace(
        model=mc, slots=128, decode_block=4,
        trace={"ops": ops, "modules": {
            "jit__step_fn": {"count": 25, "seconds": 2.0}}},
        engine_stats={"moe_decode_dispatch": {"block_rows": 16,
                                              "buffer_rows": 1264}})
    assert readers.expert_seconds(ctx) == 0.75
    ctx.engine_stats["moe_decode_dispatch"] = {"block_rows": 64,
                                               "buffer_rows": 4096}
    assert readers.expert_seconds(ctx) == 4.0
    ctx.engine_stats = {"max_seq": 2048}
    assert readers.expert_seconds(ctx) == 0.0
    import run
    ctx.traffic_name = "reason-sat"
    assert run.read_metric("moe.experts_ms", ctx) is None


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "deepseek_v3.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src


def test_the_rehearsal_ends_correct_on_the_familys_own_reference(tmp_path):
    """``run.py --rehearse`` on the new cell, in a copy of the benchmark
    whose reference file says when it is called: once a prompt."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("gofr_tpu", "examples"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "benchmarks", "references",
                           "deepseek_v3.py"), "a") as f:
        f.write(f"""

_forward = forward_logprobs


def forward_logprobs(*a, **k):
    import sys
    print({MARK!r}, file=sys.stderr)
    return _forward(*a, **k)
""")
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    got = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout == ""
    prompts = _cfg()["rehearsal"]["reference"]["prompt_tokens"]
    assert got.stderr.count(MARK) == len(prompts) == 4
    line = json.loads(got.stderr.strip().splitlines()[-1]
                      .removeprefix("[bench] "))
    assert line["correct"] is True and line["failed"] == 0
    assert line["detail"]["probe_hit_equals_miss"] is True
    # the program's count of assignments reached the readers
    assert line["metrics"]["moe.tokens_per_expert"]["value"] > 0
    assert line["metrics"]["kv.latent_live_gb"]["value"] > 0
