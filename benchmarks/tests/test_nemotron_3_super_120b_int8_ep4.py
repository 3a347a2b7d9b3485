"""nemotron-3-super-120b-int8-ep4: the published keys against the
``model_config`` the program runs, the chip's share against the published
counts, the kept slice of the pattern and its ratio, the byte count, the
traffic inside the cache, the readers on a synthetic context, and the
rehearsal end to end with the family's own reference."""

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

from benchmarks import roofline_nemotron_h as rf, traffic  # noqa: E402

NAME = "nemotron-3-super-120b-int8-ep4"
CELL = NAME + ".reason-sat"
MARK = "the family's reference was called"
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
MINE = {"ssm.decode_ms", "ssm.decode_roofline", "ssm.prefill_roofline",
        "moe.experts_ms.nemotron_h", "moe.experts_roofline.nemotron_h",
        "moe.tokens_per_expert.nemotron_h",
        "decode_step_roofline.nemotron_h", "state.live_gb.nemotron_h"}


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
            ("hidden_size", "dim"), ("num_attention_heads", "n_heads"),
            ("num_key_value_heads", "n_kv_heads"),
            ("head_dim", "attn_head_dim"),
            ("mamba_num_heads", "ssm_heads"),
            ("mamba_head_dim", "ssm_head_dim"), ("n_groups", "ssm_groups"),
            ("ssm_state_size", "ssm_state"), ("chunk_size", "ssm_chunk"),
            ("conv_kernel", "conv_kernel"),
            ("moe_intermediate_size", "moe_ffn_dim"),
            ("moe_latent_size", "moe_latent_dim"),
            ("moe_shared_expert_intermediate_size", "shared_ffn_dim"),
            ("mlp_hidden_act", "expert_act"),
            ("num_experts_per_tok", "experts_per_token"),
            ("n_shared_experts", "n_shared_experts"),
            ("routed_scaling_factor", "routed_scaling"),
            ("n_group", "n_expert_groups"), ("topk_group", "topk_groups"),
            ("norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
            ("tie_word_embeddings", "tie_embeddings"),
            ("num_hidden_layers", "n_layers"),
            ("vocab_size", "vocab_size"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    # the widths the issue names, as published
    assert (mc["dim"], mc["ssm_heads"], mc["ssm_head_dim"], mc["ssm_groups"],
            mc["ssm_state"], mc["conv_kernel"]) == (4096, 128, 64, 8, 128, 4)
    assert mc["ssm_heads"] * mc["ssm_head_dim"] == cfg["expand"] * mc["dim"]
    assert (mc["n_heads"], mc["n_kv_heads"], mc["attn_head_dim"]) \
        == (32, 2, 128)
    assert (mc["moe_latent_dim"], mc["moe_ffn_dim"], mc["shared_ffn_dim"],
            mc["n_experts"], mc["experts_per_token"], mc["routed_scaling"]) \
        == (1024, 2688, 5376, 512, 22, 5.0)
    assert cfg["model_type"] == "nemotron_h" and cfg["norm_topk_prob"]
    assert cfg["use_conv_bias"] and not cfg["mamba_proj_bias"]
    assert not cfg["attention_bias"] and not cfg["mlp_bias"]
    assert mc["use_rope"] is False          # assumed, and said so
    assert any("no rotation" in a for a in cfg["assumed"])


def test_the_catalog_keys_are_kept_but_the_five_reduced():
    """Every key of the published config is in the file under its own
    name; only the five in ``reduced`` differ, and ``published`` gives
    what they were."""
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "max_position_embeddings",
                              "num_nextn_predict_layers"]
    assert set(cfg["published"]) == set(cfg["reduced"]) \
        == set(cfg["reduced_why"])
    assert cfg["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "max_position_embeddings": 262144,
        "num_nextn_predict_layers": 1}
    assert cfg["hybrid_override_pattern"] == PUBLISHED_PATTERN
    assert cfg["mtp_hybrid_override_pattern"] == "*E"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_kept_slice_of_the_pattern_and_its_ratio():
    cfg = _cfg()
    mc = cfg["model_config"]
    assert len(PUBLISHED_PATTERN) == 88
    assert [PUBLISHED_PATTERN.count(c) for c in "ME*"] == [40, 40, 8]
    kept = cfg["hybrid_override_pattern_kept"]
    assert kept == PUBLISHED_PATTERN[:22] == "MEMEMEM*EMEMEMEM*EMEME"
    names = {"M": "mamba", "E": "moe", "*": "attn"}
    assert mc["layer_pattern"] == [names[c] for c in kept]
    assert len(mc["layer_pattern"]) == mc["n_layers"] == 22
    # 10 : 10 : 2 is two whole periods of the published 5 : 5 : 1
    assert rf.kinds(_model()) == (10, 10, 2)
    assert [4 * n for n in rf.kinds(_model())] == [40, 40, 8]
    # and no shorter period tiles it: the stack takes a kind a layer
    assert all(kept != kept[:p] * (22 // p) for p in range(1, 22))


def test_the_share_against_the_published_counts():
    cfg = _cfg()
    mc, pub = cfg["model_config"], cfg["published"]
    assert mc["n_experts"] == pub["n_routed_experts"] == 512
    assert mc["n_experts_held"] == cfg["n_routed_experts"] == 128
    assert cfg["chips_a_layer"] == 4 and cfg["chips"] == 1
    assert cfg["chips_a_layer"] * mc["n_experts_held"] == mc["n_experts"]
    assert "16 v5e chips" in cfg["deployment"]
    assert "4 pipeline stages" in cfg["deployment"]
    assert 4 * mc["n_layers"] == pub["num_hidden_layers"]
    # the guide's floors: a whole period and >= 4 layers, >= 8 experts,
    # >= 1/8 of the vocabulary
    assert mc["n_layers"] >= 11 and mc["n_experts_held"] >= 8
    assert mc["vocab_size"] * 4 == pub["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["env"]["TPU_KV_DTYPE"] == "bfloat16"
    assert cfg["env"]["TPU_SPEC_DECODE"] == "0"
    assert cfg["env"]["TPU_KVCACHE_HOST_MB"] == "0"
    slots = int(cfg["env"]["TPU_SLOTS"])
    assert 64 <= slots <= 96 and slots % 16 == 0
    assert set(cfg["env"]) == set(cfg["env_why"]) | {"GRPC_PORT",
                                                     "METRICS_PORT"}


def test_the_byte_count_of_the_share():
    """ISSUE 42's arithmetic at 1 byte a parameter: an expert 5.505 M, a
    mamba layer 109.6 M, an attn layer 35.7 M, a moe layer 54.5 M beside
    its experts, the share 9.2 GB; a slot 10 x 4.19 MB of state; and the
    bytes the program really holds (roofline_nemotron_h) within 1%."""
    m = _model()
    d, dl, f = m["dim"], m["moe_latent_dim"], m["moe_ffn_dim"]
    expert = 2 * dl * f
    assert abs(expert / 5.505e6 - 1) < 0.001
    hp = m["ssm_heads"] * m["ssm_head_dim"]
    w_in = d * (2 * hp + 2 * m["ssm_groups"] * m["ssm_state"]
                + m["ssm_heads"])
    assert w_in == 4096 * 18560
    assert abs((w_in + hp * d) / 109.6e6 - 1) < 0.001
    attn = 2 * d * m["n_heads"] * 128 + 2 * d * m["n_kv_heads"] * 128
    assert abs(attn / 35.7e6 - 1) < 0.002
    beside = 2 * d * m["shared_ffn_dim"] + 2 * d * dl + d * m["n_experts"]
    assert abs(beside / 54.5e6 - 1) < 0.002
    share = 10 * 128 * expert + 10 * beside + 10 * (w_in + hp * d) \
        + 2 * attn + 3 * m["vocab_size"] * d
    assert abs(share / 9.2e9 - 1) < 0.01
    assert abs(rf.share_weight_bytes(m) / share - 1) < 0.01
    assert abs(rf.expert_bytes(m) / expert - 1) < 0.01
    # a whole layer's 512 experts in float32: the reference runs blocks
    assert abs(128 * expert * 4 / 2.8e9 - 1) < 0.01
    # the state: 4.19 MB a slot a layer whatever the length
    assert rf.state_bytes(m) == 128 * 64 * 128 * 4 == 4194304
    assert rf.kv_bytes_per_token(m) == 2048
    slot = rf.state_bytes_per_slot(m) + 10 * 3 * rf.conv_channels(m) * 2 \
        + 2048 * rf.kv_bytes_per_token(m)
    assert abs(slot / 46.7e6 - 1) < 0.005
    # a step at 96 active slots: the states are about half of its bytes
    states = rf.decode_kernel_bytes(m, 10 * 96)
    assert abs(states / 8.05e9 - 1) < 0.002
    step = rf.fixed_weight_bytes(m) + 1280 * rf.expert_bytes(m) + states \
        + 96 * 1000 * rf.kv_bytes_per_token(m)
    assert abs(step / 17.2e9 - 1) < 0.01
    assert 0.45 < states / step < 0.5
    assert 96 * 1000 * rf.kv_bytes_per_token(m) / step < 0.012


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
    assert cell["config"] == NAME
    # appended after what was there before it (later PRs append too)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index("lfm2-24b-a2b-int8-pp2.reason-sat")
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == CELL}
    assert mine == MINE
    assert all(m["moves"] == "out_tok_s" for m in bench["per_layer"]
               if m["name"] in MINE)
    for m in bench["per_layer"]:
        if m["name"] in ("kv.live_gb", "decode_step_roofline",
                         "state.live_gb", "moe.experts_ms",
                         "moe.experts_roofline", "moe.tokens_per_expert") \
                or m["name"].startswith(("mla.", "kda.", "swa.")) \
                or m["name"].endswith((".solar_open2", ".laguna", ".lfm2")):
            assert CELL not in m["workloads"], m["name"]
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert out["workloads"].index(CELL) > out["workloads"].index(
        "lfm2-24b-a2b-int8-pp2.reason-sat")


def _ctx(**over):
    """A traced run's context, by hand: 25 blocks of 4 steps at 96 slots,
    3 s of trace."""
    m = _model()
    decode = [(i, 10.0 + 0.1 * i, 0.12, "decode", tuple(range(96)), 4,
               70_000, 90_000, 4 * 10 * 96 * 22 // 4, 4 * 10 * 126,
               4 * 10 * 96) for i in range(25)]
    prefill = [(100, 10.5, 0.2, "prefill", 3, 600, 1, "t"),
               (101, 11.0, 0.1, "prefill", 4, 100, 2, "t")]
    ctx = SimpleNamespace(
        model=m, slots=96, decode_block=4, traffic_name="reason-sat",
        timeline=decode + prefill, t_open=0.0,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace={"span": (9.0, 13.0), "ops": {
            "ssd_decode.1 f32[10,96,8,128,1024]": 1.25,
            "ssd_untouched.2 f32[10,96,8,128,1024]": 7.0,
            "ssd_prefill.7 f32[1,8,4,128,1024]": 0.02,
            "expert_blocks_stacked.1 bf16[4032,1024]": 1.0,
            "fusion.8 bf16[4032,1024]": 0.1,
            "fusion.9 bf16[96,4096]": 9.0, "cond.28 bf16[96,1,4096]": 3.0},
            "modules": {"jit__step_fn": {"count": 25, "seconds": 3.0}}},
        engine_stats={"moe_decode_dispatch": {"block_rows": 16,
                                              "buffer_rows": 4032,
                                              "width": 1024},
                      "state_bytes_per_slot": 42_557_440,
                      "prompt_buckets": [32, 64, 128, 256, 512],
                      "scheduler": {"prefill_chunk": 512}})
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def test_the_readers_on_a_context_made_by_hand():
    import run

    ctx = _ctx()
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert abs(read("ssm.decode_ms") - 12.5) < 1e-9
    # 960 states x 2 x 4.19 MB = 8.05 GB: 9.83 ms at 819 GB/s, of 12.5
    assert abs(read("ssm.decode_roofline") - 78.66) < 0.05
    assert abs(read("decode.step_ms") - 30.0) < 1e-9
    assert abs(read("moe.experts_ms.nemotron_h") - 11.0) < 1e-9
    # 1,260 experts x 5.52 MB = 6.96 GB: 8.49 ms, of 11 measured
    assert abs(read("moe.experts_roofline.nemotron_h") - 77.2) < 0.3
    # 96 x 22 / 4 held assignments a layer over 128 experts
    assert abs(read("moe.tokens_per_expert.nemotron_h") - 4.125) < 1e-9
    assert abs(read("state.live_gb.nemotron_h") - 96 * 42.55744e6 / 1e9) \
        < 1e-6
    # fixed 1.87 + experts 6.96 + states 8.05 + rows 0.14 GB at 30 ms
    assert abs(read("decode_step_roofline.nemotron_h") - 69.3) < 0.3
    # 600 -> 512 + 128, 100 -> 128: 768 positions, 10 layers x 74,240 B
    # each: 0.57 GB, 0.696 ms of 20
    assert abs(read("ssm.prefill_roofline") - 3.48) < 0.05
    # the parent's program has no such field: every reader reads nothing
    parent = _ctx(model={k: v for k, v in _model().items()
                         if k != "layer_pattern"})
    for name in MINE:
        assert run.read_metric(name, parent) is None, name
    # and the other families' readers read nothing in this cell
    for name in ("moe.experts_ms", "kv.latent_live_gb", "kda.decode_ms",
                 "state.live_gb", "moe.experts_ms.solar_open2"):
        assert run.read_metric(name, ctx) is None, name


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "nemotron_h.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "jax.lax.scan(token" in src     # a scan over the tokens
    assert "chunk" not in src.split('"""')[2]  # no chunk form in the code


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
                           "nemotron_h.py"), "a") as f:
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
    assert line["metrics"]["moe.tokens_per_expert.nemotron_h"]["value"] > 0
    assert line["metrics"]["state.live_gb.nemotron_h"]["value"] > 0
