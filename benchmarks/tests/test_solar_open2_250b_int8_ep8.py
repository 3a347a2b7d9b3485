"""solar-open2-250b-int8-ep8: the published keys against the
``model_config`` the program runs, the chip's share against the published
counts, the pattern's ratio, the byte count, the traffic inside the cache,
the readers on a synthetic context, and the rehearsal end to end with the
family's own reference."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

from benchmarks import roofline_solar_open2 as rf, traffic  # noqa: E402

NAME = "solar-open2-250b-int8-ep8"
CELL = NAME + ".reason-sat"
MARK = "the family's reference was called"


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def _model():
    """``ctx.model``: every field of the engine's ModelConfig."""
    import dataclasses

    from gofr_tpu.models import ModelConfig
    return dataclasses.asdict(ModelConfig(**_cfg()["model_config"]))


def test_every_published_width_is_what_the_program_runs():
    cfg = _cfg()
    mc = cfg["model_config"]
    for key, field in (
            ("hidden_size", "dim"), ("intermediate_size", "ffn_dim"),
            ("moe_intermediate_size", "moe_ffn_dim"),
            ("num_attention_heads", "n_heads"),
            ("num_key_value_heads", "n_kv_heads"),
            ("head_dim", "attn_head_dim"),
            ("num_experts_per_tok", "experts_per_token"),
            ("n_shared_experts", "n_shared_experts"),
            ("routed_scaling_factor", "routed_scaling"),
            ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
            ("use_rope", "use_rope"), ("use_gqa_gate", "attn_gate"),
            ("tie_word_embeddings", "tie_embeddings"),
            ("num_hidden_layers", "n_layers"),
            ("vocab_size", "vocab_size"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    lin = cfg["linear_attn_config"]
    assert mc["linear_heads"] == lin["num_heads"] == 64
    assert mc["linear_head_dim"] == lin["head_dim"] == 128
    assert mc["conv_kernel"] == lin["short_conv_kernel_size"] == 4
    assert lin["num_kv_heads"] is None
    assert cfg["model_type"] == "solar_open2"
    assert cfg["first_k_dense_replace"] == 0 and cfg["norm_topk_prob"]
    assert cfg["kda_allow_neg_eigval"] and not cfg["kda_use_full_proj"]
    assert mc["gate_rank"] == cfg["head_dim"]      # assumed, and said so
    assert any("gate_rank 128" in a for a in cfg["assumed"])


def test_the_catalog_keys_are_kept_but_the_four_reduced():
    """Every key of the published config is in the file under its own
    name; only the four in ``reduced`` differ, and ``published`` gives
    what they were."""
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "max_position_embeddings"]
    assert set(cfg["published"]) == set(cfg["reduced"]) \
        == set(cfg["reduced_why"])
    assert cfg["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608, "max_position_embeddings": 1048576}
    assert cfg["gqa_layers"] == list(range(0, 48, 4))
    assert cfg["gqa_interval"] == 3


def test_the_pattern_is_the_published_ratio():
    cfg = _cfg()
    mc = cfg["model_config"]
    pattern = mc["layer_pattern"]
    assert pattern == ["full", "linear", "linear", "linear"]
    assert pattern.count("linear") == cfg["gqa_interval"]
    # layer l is full exactly where gqa_layers names it
    kinds = [pattern[layer % len(pattern)] for layer in range(48)]
    assert [i for i, k in enumerate(kinds) if k == "full"] \
        == cfg["gqa_layers"]
    # two whole periods are held
    assert mc["n_layers"] == 2 * len(pattern)
    assert rf.kinds(_model()) == (2, 6)


def test_the_share_against_the_published_counts():
    cfg = _cfg()
    mc, pub = cfg["model_config"], cfg["published"]
    assert mc["n_experts"] == pub["n_routed_experts"] == 320
    assert mc["n_experts_held"] == cfg["n_routed_experts"] == 40
    assert cfg["chips_a_layer"] == 8
    assert cfg["chips_a_layer"] * mc["n_experts_held"] == mc["n_experts"]
    assert "48 v5e chips" in cfg["deployment"]
    assert 6 * mc["n_layers"] == pub["num_hidden_layers"]
    # the guide's floors: a whole period and >= 4 layers, >= 8 experts,
    # >= 1/8 of the vocabulary
    assert mc["n_layers"] >= 4 and mc["n_experts_held"] >= 8
    assert mc["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["env"]["TPU_SLOTS"] == "128"
    assert cfg["env"]["TPU_KV_DTYPE"] == "bfloat16"
    assert cfg["env"]["TPU_SPEC_DECODE"] == "0"
    assert cfg["env"]["TPU_KVCACHE_HOST_MB"] == "0"
    assert set(cfg["env"]) == set(cfg["env_why"]) | {"GRPC_PORT",
                                                     "METRICS_PORT"}


def test_the_byte_count_of_the_share():
    """ISSUE 32's arithmetic at 1 byte a parameter: an expert 15.73 M, a
    linear layer outside its experts 137.7 M, a full layer 109 M, the
    share 6.5 GB; the cache at 128 x 2,048: 3.22 GB of state, 2.15 GB of
    K and V; and the bytes the program really holds (roofline_solar_open2)
    within 1%."""
    m = _model()
    d, f = m["dim"], m["moe_ffn_dim"]
    expert = 3 * d * f
    assert abs(expert / 15.73e6 - 1) < 0.001
    wide = m["linear_heads"] * m["linear_head_dim"]
    linear = 4 * d * wide + 2 * (d * m["gate_rank"] + m["gate_rank"] * wide) \
        + d * m["linear_heads"] + m["conv_kernel"] * 3 * wide
    assert abs(linear / 137.7e6 - 1) < 0.002
    full = 3 * d * m["n_heads"] * 128 + 2 * d * m["n_kv_heads"] * 128
    assert abs(full / 109e6 - 1) < 0.002
    # the embedding slice is bfloat16 (0.20 GB), the head int8 (0.10)
    share = 8 * 40 * expert + 6 * linear + 2 * full \
        + 8 * (expert + d * m["n_experts"]) + 3 * m["vocab_size"] * d
    assert abs(share / 6.5e9 - 1) < 0.01
    held = rf.share_weight_bytes(m)
    assert abs(held / share - 1) < 0.01
    assert abs(rf.expert_bytes(m) / expert - 1) < 0.01
    # a whole layer's 320 experts: a chip cannot hold three
    assert abs(320 * expert / 5.03e9 - 1) < 0.002
    # the state: 4.19 MB a slot a layer whatever the length
    assert rf.state_bytes(m) == 64 * 128 * 128 * 4 == 4194304
    assert abs(128 * rf.state_bytes_per_slot(m) / 3.22e9 - 1) < 0.002
    assert rf.kv_bytes_per_token(m) == 2 * 4096
    assert abs(128 * 2048 * rf.kv_bytes_per_token(m) / 2.15e9 - 1) < 0.002
    # a step at 128 active slots: the state is about half of its bytes
    states = rf.decode_kernel_bytes(m, 6 * 128)
    assert abs(states / 6.44e9 - 1) < 0.002
    step = rf.fixed_weight_bytes(m) + 8 * 38.4 * rf.expert_bytes(m) + states \
        + 0.8e9
    assert 0.45 < states / step < 0.52


def test_reason_sat_stays_inside_the_cache():
    mc = _cfg()["model_config"]
    params = traffic.load(os.path.join(BENCH, "traffic", "reason-sat.json"))
    assert params["loop"] == "closed" and params["clients"] == 256
    sched = traffic.build(params, 7, 50.0)
    assert max(r["prompt"] + r["output"] for r in sched["requests"]) \
        < mc["max_seq"] - 2
    # one prompt in 8 is past the largest bucket: the left-aligned lattice
    # and its padded last chunk run in every window
    past = [r["prompt"] > 512 for r in sched["requests"]]
    assert 0.11 < sum(past) / len(past) < 0.14
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-sat"
    assert cell["config"] == NAME
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {
        "kda.decode_ms", "kda.decode_roofline", "kda.prefill_roofline",
        "state.live_gb", "moe.experts_ms.solar_open2",
        "moe.experts_roofline.solar_open2",
        "moe.tokens_per_expert.solar_open2",
        "decode_step_roofline.solar_open2"}
    for m in bench["per_layer"]:
        if m["name"] in ("kv.live_gb", "decode_step_roofline") \
                or m["name"].startswith("mla.") \
                or m["name"] in ("moe.experts_ms", "moe.experts_roofline",
                                 "moe.tokens_per_expert",
                                 "kv.latent_live_gb"):
            assert CELL not in m["workloads"], m["name"]


def _ctx(**over):
    """A traced run's context, by hand: 25 blocks of 4 steps at 128 slots,
    3 s of trace."""
    m = _model()
    decode = [(i, 10.0 + 0.1 * i, 0.08, "decode", tuple(range(128)), 4,
               100_000, 120_000, 4 * 8 * 128 * 8 // 8, 4 * 8 * 38,
               4 * 6 * 128) for i in range(25)]
    prefill = [(100, 10.5, 0.2, "prefill", 3, 600, 1, "t"),
               (101, 11.0, 0.1, "prefill", 4, 100, 2, "t"),
               (102, 11.5, 0.3, "prefill", 5, 1024, 3, "t")]
    ctx = SimpleNamespace(
        model=m, slots=128, decode_block=4, traffic_name="reason-sat",
        timeline=decode + prefill, t_open=0.0,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace={"span": (9.0, 13.0), "ops": {
            "kda_decode.3 f32[6,128,64,128,128]": 1.0,
            "kda_prefill.7 f32[1,64,512,128]": 0.05,
            "fusion.1 bf16[16,1280]": 0.3, "fusion.2 bf16[1664,4096]": 0.1,
            "fusion.9 bf16[128,4096]": 9.0},
            "modules": {"jit__step_fn": {"count": 25, "seconds": 2.5}}},
        engine_stats={"moe_decode_dispatch": {"block_rows": 16,
                                              "buffer_rows": 1664},
                      "state_bytes_per_slot": 25_870_000,
                      "prompt_buckets": [32, 64, 128, 256, 512],
                      "scheduler": {"prefill_chunk": 512}})
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def test_the_readers_on_a_context_made_by_hand():
    import run

    ctx = _ctx()
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert abs(read("kda.decode_ms") - 10.0) < 1e-9
    # 768 states x 2 x 4.19 MB = 6.44 GB: 7.87 ms at 819 GB/s, of 10
    assert abs(read("kda.decode_roofline") - 78.66) < 0.05
    assert abs(read("decode.step_ms") - 25.0) < 1e-9
    assert abs(read("moe.experts_ms.solar_open2") - 4.0) < 1e-9
    # 8 layers x 38 experts x 15.76 MB = 4.79 GB: 5.85 ms, of 4 measured
    # (a made-up time: the reader does not clip)
    assert abs(read("moe.experts_roofline.solar_open2") - 146.2) < 0.5
    assert abs(read("moe.tokens_per_expert.solar_open2") - 3.2) < 1e-9
    assert abs(read("state.live_gb") - 128 * 25.87e6 / 1e9) < 1e-6
    # fixed 1.31 + experts 4.79 + states 6.44 + rows 0.82 GB at 25 ms
    assert abs(read("decode_step_roofline.solar_open2") - 65.3) < 0.3
    # 600 -> 512 + 128, 100 -> 128, 1024 -> 512 + 512: 1,792 positions,
    # 6 layers x 196,608 B each: 2.11 GB, 2.58 ms of 50
    assert abs(read("kda.prefill_roofline") - 5.16) < 0.05
    # the parent's program has no such field: every reader reads nothing
    parent = _ctx(model={k: v for k, v in _model().items()
                         if k != "layer_pattern"})
    for name in ("kda.decode_ms", "kda.decode_roofline",
                 "kda.prefill_roofline", "state.live_gb",
                 "moe.experts_ms.solar_open2",
                 "moe.experts_roofline.solar_open2",
                 "moe.tokens_per_expert.solar_open2",
                 "decode_step_roofline.solar_open2"):
        assert run.read_metric(name, parent) is None, name
    # and the latent family's readers read nothing in this cell
    assert run.read_metric("moe.experts_ms", ctx) is None
    assert run.read_metric("kv.latent_live_gb", ctx) is None


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "solar_open2.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "jax.lax.scan(token" in src     # a scan over the tokens


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
                           "solar_open2.py"), "a") as f:
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
    # the probe's second run is a hit cut to the chunk boundary, and
    # gives the miss's tokens
    assert line["detail"]["probe_hit_equals_miss"] is True
    # the program's counts reached the readers
    assert line["metrics"]["moe.tokens_per_expert.solar_open2"]["value"] > 0
    assert line["metrics"]["state.live_gb"]["value"] > 0
