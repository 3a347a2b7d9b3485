"""laguna-xs.2-int8-pp5: the published keys against the ``model_config``
the program runs, the pattern's two periods, head counts by kind, a layer
whole on its chip, the byte count, the traffic inside the cache, the
readers on a synthetic context, and the rehearsal end to end with the
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

from benchmarks import roofline_laguna as rf, traffic  # noqa: E402

NAME = "laguna-xs.2-int8-pp5"
CELL = NAME + ".reason-sat"
MARK = "the family's reference was called"
MINE = {"swa.decode_attn_ms", "swa.decode_attn_roofline",
        "swa.full_decode_attn_ms", "swa.full_decode_attn_roofline",
        "kv.window_live_gb", "kv.full_live_gb", "moe.experts_ms.laguna",
        "moe.experts_roofline.laguna", "moe.tokens_per_expert.laguna",
        "decode_step_roofline.laguna"}


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
            ("num_experts", "n_experts"),
            ("num_experts_per_tok", "experts_per_token"),
            ("moe_routed_scaling_factor", "routed_scaling"),
            ("sliding_window", "window_size"),
            ("gating", "head_gate"),
            ("rms_norm_eps", "norm_eps"),
            ("tie_word_embeddings", "tie_embeddings"),
            ("num_hidden_layers", "n_layers"),
            ("vocab_size", "vocab_size"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    assert cfg["model_type"] == "laguna"
    assert mc["n_experts_held"] == 0          # every expert is held
    assert cfg["shared_expert_intermediate_size"] \
        == mc["moe_ffn_dim"] * mc["n_shared_experts"] == 512
    assert not cfg["attention_bias"]
    assert not cfg["moe_apply_router_weight_on_input"]
    # both ropes as published
    full = cfg["rope_parameters"]["full_attention"]
    ring = cfg["rope_parameters"]["sliding_attention"]
    assert mc["rope_theta"] == full["rope_theta"] == 500000
    assert mc["rotary_dim"] == full["partial_rotary_factor"] \
        * cfg["head_dim"] == 64
    assert cfg["partial_rotary_factor"] == full["partial_rotary_factor"]
    assert mc["rope_scaling"] == {
        "rope_type": full["rope_type"], "factor": full["factor"],
        "original_max_position_embeddings":
            full["original_max_position_embeddings"],
        "beta_fast": full["beta_fast"], "beta_slow": full["beta_slow"],
        "attention_factor": full["attention_factor"]}
    assert mc["window_rope_theta"] == ring["rope_theta"] == 10000
    assert ring["rope_type"] == "default" \
        and ring["partial_rotary_factor"] == 1


def test_the_catalog_keys_are_kept_but_the_two_reduced():
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert set(cfg["published"]) == set(cfg["reduced"]) \
        == set(cfg["reduced_why"])
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "max_position_embeddings": 262144}
    # the three per-layer lists as published: 40 entries each, those past
    # 7 naming other stages' layers
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) \
        == len(cfg["num_attention_heads_per_layer"]) == 40
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"


def test_the_patterns_two_periods_and_head_counts_by_kind():
    cfg = _cfg()
    mc = cfg["model_config"]
    pattern = mc["layer_pattern"]
    assert pattern == ["full", "window", "window", "window"]
    said = {"full": "full_attention", "window": "sliding_attention"}
    heads = {"full": mc["n_heads"], "window": mc["window_heads"]}
    assert heads == {"full": 48, "window": 64}
    for layer in range(40):
        kind = pattern[layer % len(pattern)]
        assert cfg["layer_types"][layer] == said[kind]
        assert cfg["num_attention_heads_per_layer"][layer] == heads[kind]
        assert cfg["mlp_layer_types"][layer] \
            == ("dense" if layer < mc["n_dense_layers"] else "sparse")
    # two whole periods are held, seven sparse layers after the dense one
    assert mc["n_layers"] == 2 * len(pattern)
    assert mc["n_layers"] - mc["n_dense_layers"] == 7
    m = _model()
    assert rf.kinds(m) == {"full": 2, "window": 6}
    assert (rf.heads(m, "full"), rf.heads(m, "window")) == (48, 64)
    # groups of 6 and of 8 over the 8 KV heads
    assert [h // mc["n_kv_heads"] for h in heads.values()] == [6, 8]


def test_a_layer_is_whole_on_its_chip():
    cfg = _cfg()
    mc = cfg["model_config"]
    assert cfg["chips"] == 1 and cfg["chips_a_layer"] == 1
    assert "5 v5e chips, 5 pipeline stages of 8 layers" in cfg["deployment"]
    assert 5 * mc["n_layers"] == cfg["published"]["num_hidden_layers"]
    # the guide's floors: a whole period and >= 4 layers after the dense
    # one, >= 8 experts, >= 1/8 of the vocabulary
    assert mc["n_experts"] == 256 and mc["vocab_size"] == 100352
    assert len(cfg["assumed"]) >= 5
    for word in ("ONE VALUE A HEAD", "sigmoid", "no q/k norm",
                 "counts the token's own position", "random int8"):
        assert any(word in a for a in cfg["assumed"]), word
    assert cfg["env"]["TPU_SLOTS"] == "128"
    assert cfg["env"]["TPU_MAX_SEQ"] == "2048"
    assert cfg["env"]["TPU_KV_DTYPE"] == "bfloat16"
    assert cfg["env"]["TPU_SPEC_DECODE"] == "0"
    assert cfg["env"]["TPU_KVCACHE_HOST_MB"] == "0"
    assert set(cfg["env"]) == set(cfg["env_why"]) | {"GRPC_PORT",
                                                     "METRICS_PORT"}
    ref = cfg["reference"]
    assert ref["module"] == "references/laguna.py"
    assert ref["prompt_tokens"] == [24, 40, 600, 1500]
    assert ref["new_tokens"] == 32 and ref["statistic"] == "median"
    assert 0 < ref["tolerance_nats"] <= 0.5
    # every rehearsal prompt wraps the tiny preset's ring
    from gofr_tpu.models import LLAMA_CONFIGS
    tiny = LLAMA_CONFIGS[cfg["rehearsal"]["model"]]
    assert "window" in tiny.layer_pattern
    assert min(cfg["rehearsal"]["reference"]["prompt_tokens"]) \
        > tiny.window_size >= 8


def test_the_byte_count():
    """ISSUE 36's arithmetic at 1 byte a parameter: a sparse layer's
    experts 805.3 M, attention 29.4 M (full) and 37.7 M (window), the
    whole model 33.44 B (the published 33.4 B); this stage 6.6 GB; the
    cache at 128 x 2,048: 2.15 GB of rows, 1.61 GB of rings where whole
    rows would take 6.44; and the bytes the program really holds
    (roofline_laguna) within 1%."""
    m = _model()
    d, f, hd, kv = m["dim"], m["moe_ffn_dim"], 128, m["n_kv_heads"]
    expert = 3 * d * f
    assert abs(256 * expert / 805.3e6 - 1) < 0.001
    attn = {k: 2 * d * h * hd + 2 * d * kv * hd + d * h
            for k, h in (("full", 48), ("window", 64))}
    assert abs(attn["full"] / 29.4e6 - 1) < 0.005
    assert abs(attn["window"] / 37.7e6 - 1) < 0.005
    dense, shared, router = 3 * d * m["ffn_dim"], expert, d * 256
    vocab = m["vocab_size"] * d
    whole = 39 * (256 * expert + shared + router) + dense \
        + 10 * attn["full"] + 30 * attn["window"] + 2 * vocab
    assert abs(whole / 33.44e9 - 1) < 0.002
    # an element-wise gate would make 34.1 B: the published count is of a
    # gate a head
    wide = whole + 10 * d * 48 * (hd - 1) + 30 * d * 64 * (hd - 1)
    assert abs(wide / 34.1e9 - 1) < 0.005
    # the embedding is bfloat16 (0.41 GB), the head int8 (0.21)
    stage = 7 * (256 * expert + shared + router) + dense \
        + 2 * attn["full"] + 6 * attn["window"] + 3 * vocab
    assert abs(stage / 6.6e9 - 1) < 0.01
    assert abs(rf.share_weight_bytes(m) / stage - 1) < 0.01
    assert abs(rf.expert_bytes(m) / expert - 1) < 0.01
    # the cache
    assert rf.row_bytes(m) == 4096
    assert rf.kv_bytes_per_token(m) == 8 * 1024
    assert rf.ring_bytes_per_slot(m) == 12 * 1024 * 1024
    assert abs(128 * 2048 * rf.kv_bytes_per_token(m) / 2.15e9 - 1) < 0.002
    assert abs(128 * rf.ring_bytes_per_slot(m) / 1.61e9 - 1) < 0.002
    assert abs(128 * 2048 * 6 * rf.row_bytes(m) / 6.44e9 - 1) < 0.002
    # a step at 128 slots, every ring full, rows 36% full: about 8.4 GB,
    # some 10 ms at 819 GB/s
    step = rf.step_bytes(m, 7 * 252, 0.36 * 128 * 2048, 128 * 512)
    assert 7.9e9 < step < 8.9e9
    assert 0.70 < (rf.fixed_weight_bytes(m)
                   + 7 * 252 * rf.expert_bytes(m)) / step < 0.78


def test_reason_sat_stays_inside_the_cache():
    mc = _cfg()["model_config"]
    params = traffic.load(os.path.join(BENCH, "traffic", "reason-sat.json"))
    assert params["loop"] == "closed" and params["clients"] == 256
    sched = traffic.build(params, 7, 50.0)
    assert max(r["prompt"] + r["output"] for r in sched["requests"]) \
        < mc["max_seq"] - 2
    # nearly every request ends past the window (the callers' first,
    # whose outputs are cut, aside): its rings are full for most of its
    # life
    over = [r["prompt"] + r["output"] > mc["window_size"]
            for r in sched["requests"]]
    assert sum(over) / len(over) > 0.9
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-sat"
    assert cell["config"] == NAME
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == MINE
    assert all(m["moves"] == "out_tok_s" for m in bench["per_layer"]
               if m["name"] in MINE)
    for m in bench["per_layer"]:
        if m["name"] in ("kv.live_gb", "decode_step_roofline",
                         "moe.experts_ms", "moe.experts_roofline",
                         "moe.tokens_per_expert", "kv.latent_live_gb",
                         "state.live_gb") \
                or m["name"].startswith(("mla.", "kda.")) \
                or m["name"].endswith((".solar_open2", ".deepseek_v3")):
            assert CELL not in m["workloads"], m["name"]


def _ctx(**over):
    """A traced run's context, by hand: 25 blocks of 4 steps at 128 slots,
    3 s of trace; 96,000 live rows a full layer, 60,000 ring rows."""
    m = _model()
    decode = [(i, 10.0 + 0.1 * i, 0.08, "decode", tuple(range(128)), 4,
               96_000, 120_000, 4 * 7 * 128 * 8, 4 * 7 * 252, None, 60_000)
              for i in range(25)]
    ctx = SimpleNamespace(
        model=m, slots=128, decode_block=4, traffic_name="reason-sat",
        timeline=decode, t_open=0.0,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace={"span": (9.0, 13.0), "ops": {
            "flash_decode_ring.31 f32[128,8,8,128]": 0.25,
            "flash_decode_ring.32 f32[128,8,8,128]": 0.05,
            "flash_decode_stacked.35 f32[128,8,8,128]": 0.2,
            "fusion.1 bf16[16,512]": 0.5, "fusion.2 bf16[4864,2048]": 0.2,
            "fusion.9 bf16[128,2048]": 9.0},
            "modules": {"jit__step_fn": {"count": 25, "seconds": 1.5}}},
        engine_stats={"moe_decode_dispatch": {"block_rows": 16,
                                              "buffer_rows": 4864},
                      "window_rows": 512,
                      "window_bytes_per_slot": 12 * 1024 * 1024})
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def test_the_readers_on_a_context_made_by_hand():
    import run

    ctx = _ctx()
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert abs(read("swa.decode_attn_ms") - 3.0) < 1e-9
    assert abs(read("swa.full_decode_attn_ms") - 2.0) < 1e-9
    # 60,000 rows x 4 KiB x 6 layers = 1.47 GB: 1.80 ms at 819 GB/s, of 3
    assert abs(read("swa.decode_attn_roofline") - 60.0) < 0.1
    # 96,000 rows x 4 KiB x 2 layers = 0.786 GB: 0.96 ms, of 2
    assert abs(read("swa.full_decode_attn_roofline") - 48.0) < 0.1
    assert abs(read("kv.window_live_gb") - 60_000 * 6 * 4096 / 1e9) < 1e-9
    assert abs(read("kv.full_live_gb") - 96_000 * 2 * 4096 / 1e9) < 1e-9
    assert read("kv.window_live_gb") <= 1.61
    assert abs(read("decode.step_ms") - 15.0) < 1e-9
    assert abs(read("moe.experts_ms.laguna") - 7.0) < 1e-9
    # 7 layers x 252 experts x 3.15 MB = 5.57 GB: 6.8 ms, of 7 measured
    assert abs(read("moe.experts_roofline.laguna") - 97.1) < 0.5
    assert abs(read("moe.tokens_per_expert.laguna") - 4.0) < 1e-9
    # fixed 0.57 + experts 5.57 + rows 0.79 + rings 1.47 GB at 15 ms
    assert abs(read("decode_step_roofline.laguna") - 68.3) < 0.5
    # the parent's program has no such field: every reader reads nothing
    parent = _ctx(model={k: v for k, v in _model().items()
                         if k not in ("layer_pattern", "window_size",
                                      "window_heads", "head_gate",
                                      "rotary_dim", "window_rope_theta")})
    for name in MINE:
        assert run.read_metric(name, parent) is None, name
    # a program of the family that counts no ring rows yet: nothing read,
    # nothing raised
    short = _ctx(timeline=[e[:10] for e in ctx.timeline])
    for name in ("kv.window_live_gb", "swa.decode_attn_roofline",
                 "decode_step_roofline.laguna"):
        assert run.read_metric(name, short) is None, name
    # and the other families' readers read nothing in this cell
    for name in ("moe.experts_ms", "kv.latent_live_gb", "state.live_gb",
                 "kda.decode_ms", "moe.experts_ms.solar_open2"):
        assert run.read_metric(name, ctx) is None, name


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "laguna.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "seen &= j > p - window" in src   # a band over whole sequences


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
                           "laguna.py"), "a") as f:
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
    assert line["metrics"]["moe.tokens_per_expert.laguna"]["value"] > 0
    assert line["metrics"]["kv.window_live_gb"]["value"] > 0
    assert line["metrics"]["kv.full_live_gb"]["value"] > 0
