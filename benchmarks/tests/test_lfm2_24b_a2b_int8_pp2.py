"""lfm2-24b-a2b-int8-pp2: the published keys against the ``model_config``
the program runs, the pattern's five periods, a layer whole on its chip,
the byte count, the traffic inside the cache, the readers on a synthetic
context, and the rehearsal end to end with the family's own reference."""

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

from benchmarks import roofline_lfm2 as rf, traffic  # noqa: E402

NAME = "lfm2-24b-a2b-int8-pp2"
CELL = NAME + ".reason-sat"
MARK = "the family's reference was called"
MINE = {"decode_step_roofline.lfm2", "moe.experts_ms.lfm2",
        "moe.experts_roofline.lfm2", "moe.tokens_per_expert.lfm2",
        "conv.decode_ms", "attn.decode_ms.lfm2", "attn.decode_roofline.lfm2",
        "kv.live_gb.lfm2", "state.tail_live_gb"}


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
            ("num_experts", "n_experts"),
            ("num_experts_per_tok", "experts_per_token"),
            ("routed_scaling_factor", "routed_scaling"),
            ("num_dense_layers", "n_dense_layers"),
            ("conv_L_cache", "conv_kernel"),
            ("norm_eps", "norm_eps"),
            ("num_hidden_layers", "n_layers"),
            ("vocab_size", "vocab_size"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    assert cfg["model_type"] == "lfm2_moe"
    # a head is hidden / heads = 64 values: the catalog's row gives no
    # head_dim of its own
    assert mc["attn_head_dim"] == cfg["hidden_size"] \
        // cfg["num_attention_heads"] == 64
    assert mc["n_experts_held"] == 0          # every expert is held
    assert mc["n_shared_experts"] == 0        # and none is shared
    assert mc["qk_norm"] is True and mc["tie_embeddings"] is True
    assert cfg["use_expert_bias"] and cfg["norm_topk_prob"]
    assert mc["n_expert_groups"] == mc["topk_groups"] == 1
    assert not cfg["conv_bias"]
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert mc["rope_theta"] == 1e6 and mc["rope_scaling"] is None


def test_the_catalog_keys_are_kept_but_the_two_reduced():
    """Every number of the catalog's row is in the file under its key;
    only the depth and the positions differ, and both are listed."""
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert set(cfg["published"]) == set(cfg["reduced"]) \
        == set(cfg["reduced_why"])
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "max_position_embeddings": 128000}
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    for key, value in published.items():
        assert cfg[key] == value, key
    # the list of layer kinds as published: 40 entries, those past 19
    # naming the other stage's layers
    assert len(cfg["layer_types"]) == 40
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"


def test_the_patterns_five_periods_and_the_dense_layers():
    cfg = _cfg()
    mc = cfg["model_config"]
    pattern = mc["layer_pattern"]
    assert pattern == ["conv", "conv", "full", "conv"]
    said = {"full": "full_attention", "conv": "conv"}
    for layer in range(40):
        assert cfg["layer_types"][layer] == said[pattern[layer % 4]]
    assert [l for l in range(40) if cfg["layer_types"][l]
            == "full_attention"] == list(range(2, 40, 4))
    # five whole periods are held, eighteen sparse layers after two dense
    assert mc["n_layers"] == 5 * len(pattern)
    assert mc["n_layers"] - mc["n_dense_layers"] == 18
    assert rf.kinds(_model()) == {"conv": 15, "full": 5}
    # groups of 4 over the 8 KV heads: a pair of KV heads' group of 8
    assert mc["n_heads"] // mc["n_kv_heads"] == 4


def test_a_layer_is_whole_on_its_chip():
    cfg = _cfg()
    mc = cfg["model_config"]
    assert cfg["chips"] == 1 and cfg["chips_a_layer"] == 1
    assert "2 v5e chips, 2 pipeline stages of 20 layers" in cfg["deployment"]
    assert 2 * mc["n_layers"] == cfg["published"]["num_hidden_layers"]
    # 64 of 64 experts, the whole vocabulary
    assert mc["n_experts"] == 64 and mc["vocab_size"] == 65536
    assert len(cfg["assumed"]) >= 6
    for word in ("random int8", "tied embeddings", "B, C, X", "last TWO",
                 "1e-6", "before the rotation", "output head"):
        assert any(word in a for a in cfg["assumed"]), word
    assert cfg["env"]["TPU_SLOTS"] in ("96", "64")
    assert cfg["env"]["TPU_MAX_SEQ"] == "2048"
    assert cfg["env"]["TPU_KV_DTYPE"] == "bfloat16"
    assert cfg["env"]["TPU_SPEC_DECODE"] == "0"
    assert cfg["env"]["TPU_KVCACHE_HOST_MB"] == "0"
    assert set(cfg["env"]) == set(cfg["env_why"]) | {"GRPC_PORT",
                                                     "METRICS_PORT"}
    ref = cfg["reference"]
    assert ref["module"] == "references/lfm2.py"
    # the four lengths of every sparse configuration, four times over,
    # and 64 tokens each: 1,024 compared positions, because at 128 the
    # median itself spread 0.31 to 0.49 a weight seed (``why``)
    assert ref["prompt_tokens"] == [24, 40, 600, 1500] * 4
    assert ref["new_tokens"] == 64 and ref["statistic"] == "median"
    assert 0 < ref["tolerance_nats"] <= 0.5
    assert "4 bits" in ref["why"] and "1,024 positions" in ref["why"]
    small = cfg["rehearsal"]["reference"]
    assert max(small["prompt_tokens"]) + small["new_tokens"] \
        < int(cfg["rehearsal"]["env"]["TPU_MAX_SEQ"])
    from gofr_tpu.models import LLAMA_CONFIGS
    tiny = LLAMA_CONFIGS[cfg["rehearsal"]["model"]]
    assert "conv" in tiny.layer_pattern and tiny.n_shared_experts == 0
    assert tiny.conv_kernel == mc["conv_kernel"] and tiny.qk_norm


def test_the_byte_count():
    """ISSUE 40's arithmetic at 1 byte a parameter: an expert 9.44 M, a
    sparse layer's experts 604 MB, a convolution operator 16.8 MB, an
    attention operator 10.5 MB, a dense feed-forward 72.4 MB, the
    embedding 134 M values; 38 x 604 MB = 22.95 GB of experts of a
    23.8 GB model; this stage 11.6 GB; the cache at 96 x 2,048: 2.01 GB
    of rows at 10 KiB a token, 12 MB of tails at 120 KiB a slot; and the
    bytes the program really holds (roofline_lfm2) within 1%."""
    m = _model()
    d, f, hd, kv, h = m["dim"], m["moe_ffn_dim"], 64, 8, 32
    expert = 3 * d * f
    assert expert == 9_437_184
    assert abs(64 * expert / 604e6 - 1) < 0.001
    conv = d * 3 * d + d * d
    attn = 2 * d * h * hd + 2 * d * kv * hd
    dense = 3 * d * m["ffn_dim"]
    assert abs(conv / 16.8e6 - 1) < 0.002
    assert abs(attn / 10.5e6 - 1) < 0.002
    assert abs(dense / 72.4e6 - 1) < 0.002
    vocab = m["vocab_size"] * d
    assert abs(vocab / 134.2e6 - 1) < 0.001
    assert abs(38 * 64 * expert / 22.95e9 - 1) < 0.001
    whole = 38 * (64 * expert + d * 64) + 2 * dense + 30 * conv \
        + 10 * attn + vocab
    assert abs(whole / 23.8e9 - 1) < 0.005
    # the embedding is bfloat16 (0.27 GB) and is the head too
    stage = 18 * (64 * expert + 2 * d * 64) + 2 * dense + 15 * conv \
        + 5 * attn + 2 * vocab
    assert abs(stage / 11.6e9 - 1) < 0.005
    assert abs(rf.share_weight_bytes(m) / stage - 1) < 0.01
    assert abs(rf.expert_bytes(m) / expert - 1) < 0.01
    # the cache
    assert rf.row_bytes(m) == 2048
    assert rf.kv_bytes_per_token(m) == 10 * 1024
    assert rf.tail_bytes_per_slot(m) == 120 * 1024
    assert abs(96 * 2048 * rf.kv_bytes_per_token(m) / 2.013e9 - 1) < 0.001
    assert abs(96 * rf.tail_bytes_per_slot(m) / 11.8e6 - 1) < 0.002
    # Mistral's token costs 64 KiB at int8: 32 layers x 2 x 8 x 128
    assert 32 * 2 * 8 * 128 == 64 * 1024
    # a step at 96 slots, 6 tokens an expert (nearly every expert
    # touched), rows 40% full: about 12.4 GB (15 ms at 819 GB/s), of
    # which the experts are near nine tenths
    step = rf.step_bytes(m, 18 * 63.8, 0.4 * 96 * 2048, 96)
    assert 12.1e9 < step < 12.7e9
    assert 0.85 < 18 * 63.8 * rf.expert_bytes(m) / step < 0.92
    assert 0.05 < rf.fixed_weight_bytes(m) / step < 0.07


def test_reason_sat_stays_inside_the_cache():
    mc = _cfg()["model_config"]
    params = traffic.load(os.path.join(BENCH, "traffic", "reason-sat.json"))
    assert params["loop"] == "closed" and params["clients"] == 256
    sched = traffic.build(params, 7, 50.0)
    assert max(r["prompt"] + r["output"] for r in sched["requests"]) \
        < mc["max_seq"] - 2
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-sat"
    assert cell["config"] == NAME and len(cell["why"]) <= 200
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == MINE
    assert all(m["moves"] == "out_tok_s" for m in bench["per_layer"]
               if m["name"] in MINE)
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    for name in ("out_tok_s", "setup_s", "sched.occupancy_pct",
                 "hbm.in_use_gb", "hbm.peak_gb", "decode.step_ms",
                 "device.idle_pct", "setup.compile_s", "window.compiles",
                 "kv.pool_fill_pct", "attn.kv_read_pct",
                 "sample.drawn_blocks_pct", "sched.dry_pct"):
        assert name in reports, name
    for m in bench["per_layer"]:
        if m["name"] in ("kv.live_gb", "decode_step_roofline",
                         "moe.experts_ms", "moe.experts_roofline",
                         "moe.tokens_per_expert", "kv.latent_live_gb",
                         "state.live_gb", "kv.window_live_gb",
                         "kv.full_live_gb") \
                or m["name"].startswith(("mla.", "kda.", "swa.")) \
                or m["name"].endswith((".solar_open2", ".deepseek_v3",
                                       ".laguna", ".chat-rate")):
            assert CELL not in m["workloads"], m["name"]


def _ctx(**over):
    """A traced run's context, by hand: 25 blocks of 4 steps at 96 slots,
    3 s of trace; 80,000 live rows a full layer; every slot decodes."""
    m = _model()
    decode = [(i, 10.0 + 0.1 * i, 0.1, "decode", tuple(range(96)), 4,
               80_000, 100_000, 4 * 18 * 96 * 4, 4 * 18 * 63, 4 * 15 * 96)
              for i in range(25)]
    ctx = SimpleNamespace(
        model=m, slots=96, decode_block=4, traffic_name="reason-sat",
        timeline=decode, t_open=0.0,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace={"span": (9.0, 13.0), "ops": {
            "flash_decode_stacked.35 f32[96,4,8,128]": 0.1,
            "fusion.1 bf16[16,1536]": 1.0, "fusion.2 bf16[1344,2048]": 0.6,
            "fusion.3 bf16[96,1,6144]": 0.12,
            "fusion.4 bf16[15,96,2,2048]": 0.03,
            "fusion.9 bf16[96,2048]": 9.0},
            "modules": {"jit__step_fn": {"count": 25, "seconds": 2.4}}},
        engine_stats={"moe_decode_dispatch": {"block_rows": 16,
                                              "buffer_rows": 1344},
                      "state_bytes_per_slot": 120 * 1024})
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def test_the_readers_on_a_context_made_by_hand():
    import run

    ctx = _ctx()
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert abs(read("attn.decode_ms.lfm2") - 1.0) < 1e-9
    # 80,000 rows x 2 KiB x 5 layers = 0.819 GB: 1.00 ms at 819 GB/s, of 1
    assert abs(read("attn.decode_roofline.lfm2") - 100.0) < 0.1
    assert abs(read("kv.live_gb.lfm2") - 80_000 * 10_240 / 1e9) < 1e-9
    assert abs(read("state.tail_live_gb") - 96 * 120 * 1024 / 1e9) < 1e-12
    assert abs(read("decode.step_ms") - 24.0) < 1e-9
    assert abs(read("moe.experts_ms.lfm2") - 16.0) < 1e-9
    # 18 layers x 63 experts x 9.44 MB = 10.7 GB: 13.1 ms, of 16 measured
    assert abs(read("moe.experts_roofline.lfm2") - 81.7) < 0.5
    assert abs(read("moe.tokens_per_expert.lfm2") - 6.0) < 1e-9
    # the in-projection and the tails' select: 0.15 s over 100 steps
    assert abs(read("conv.decode_ms") - 1.5) < 1e-9
    # fixed 0.67 + experts 10.71 + rows 0.82 + tails 0.02 GB at 24 ms
    assert abs(read("decode_step_roofline.lfm2") - 62.2) < 0.5
    for name in MINE:
        assert read(name) is not None, name
    # the parent's program has no such field: every reader reads nothing
    parent = _ctx(model={k: v for k, v in _model().items()
                         if k not in ("layer_pattern", "qk_norm",
                                      "conv_kernel")})
    for name in MINE:
        assert run.read_metric(name, parent) is None, name
    # a program of the family that counts no tails yet: nothing read,
    # nothing raised
    short = _ctx(timeline=[e[:10] for e in ctx.timeline])
    for name in ("kv.live_gb.lfm2", "state.tail_live_gb",
                 "attn.decode_roofline.lfm2", "decode_step_roofline.lfm2"):
        assert run.read_metric(name, short) is None, name
    # and the other families' readers read nothing in this cell
    for name in ("moe.experts_ms", "kv.latent_live_gb", "state.live_gb",
                 "kda.decode_ms", "moe.experts_ms.solar_open2",
                 "moe.experts_ms.laguna", "kv.full_live_gb",
                 "swa.full_decode_attn_ms"):
        assert run.read_metric(name, ctx) is None, name


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "lfm2.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "jnp.pad(u, ((back, 0), (0, 0)))" in src   # whole sequences


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
                           "lfm2.py"), "a") as f:
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
    # the probe's second run is a hit cut to the chunk boundary, which
    # restores the tails, and gives the miss's tokens
    assert line["detail"]["probe_hit_equals_miss"] is True
    # the program's counts reached the readers
    assert line["metrics"]["moe.tokens_per_expert.lfm2"]["value"] > 0
    assert line["metrics"]["kv.live_gb.lfm2"]["value"] > 0
    assert line["metrics"]["state.tail_live_gb"]["value"] > 0
