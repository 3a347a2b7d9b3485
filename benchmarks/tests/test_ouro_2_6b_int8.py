"""ouro-2.6b-int8: every published key against the ``model_config`` the
program runs, the one reduced key, the row tables a token has, the byte
count, the traffic inside the cache, the readers on a synthetic context,
and the rehearsal end to end with the family's own reference."""

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

from benchmarks import roofline_ouro as rf, traffic  # noqa: E402

NAME = "ouro-2.6b-int8"
CELL = NAME + ".batch-sat-14"
MARK = "the family's reference was called"
MINE = {"decode_step_roofline.ouro", "loop.weights_ms",
        "loop.weights_roofline", "attn.decode_ms.ouro",
        "attn.decode_roofline.ouro", "kv.append_ms.ouro",
        "kv.append_roofline.ouro", "kv.live_gb.ouro",
        "loop.steps_per_token"}
# the catalog's row (model-configs guide), every key as published
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def _model():
    """``ctx.model``: every field of the engine's ModelConfig."""
    import dataclasses

    from gofr_tpu.models import ModelConfig
    return dataclasses.asdict(ModelConfig(**_cfg()["model_config"]))


def test_every_published_key_is_in_the_file_but_the_one_reduced():
    cfg = _cfg()
    assert cfg["reduced"] == ["max_position_embeddings"]
    assert set(cfg["published"]) == set(cfg["reduced"]) \
        == set(cfg["reduced_why"])
    assert cfg["published"] == {"max_position_embeddings": 65536}
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["max_position_embeddings"] == 1536
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"


def test_every_published_width_is_what_the_program_runs():
    cfg = _cfg()
    mc = cfg["model_config"]
    for key, field in (
            ("hidden_size", "dim"), ("intermediate_size", "ffn_dim"),
            ("num_attention_heads", "n_heads"),
            ("num_key_value_heads", "n_kv_heads"),
            ("head_dim", "attn_head_dim"),
            ("num_hidden_layers", "n_layers"),
            ("total_ut_steps", "loop_steps"),
            ("early_exit_threshold", "early_exit_threshold"),
            ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
            ("rope_scaling", "rope_scaling"),
            ("tie_word_embeddings", "tie_embeddings"),
            ("vocab_size", "vocab_size"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    # 48 layers, 4 passes, 16 heads and 16 KV heads of 128, 5,632,
    # 49,152, untied: nothing of the model is cut
    assert (mc["n_layers"], mc["loop_steps"], mc["n_heads"],
            mc["n_kv_heads"], mc["attn_head_dim"], mc["ffn_dim"],
            mc["vocab_size"], mc["tie_embeddings"]) \
        == (48, 4, 16, 16, 128, 5632, 49152, False)
    assert mc["dim"] // mc["n_heads"] == cfg["head_dim"]
    assert mc["sandwich_norm"] is True and mc["n_experts"] == 0
    assert cfg["model_type"] == "ouro" and cfg["hidden_act"] == "silu"
    assert not cfg["use_sliding_window"] and cfg["sliding_window"] is None
    assert set(cfg["layer_types"]) == {"full_attention"}
    # the program picks the looped family from the fields, and its cache
    # has a table a (pass, layer)
    from gofr_tpu.models import ModelConfig, family, ouro
    built = ModelConfig(**mc)
    assert family(built) is ouro
    assert ouro.kv_tables(built) == cfg["total_ut_steps"] \
        * cfg["num_hidden_layers"] == 192 == rf.tables(_model())


def test_the_deployment_the_assumptions_and_the_reference_entry():
    cfg = _cfg()
    assert cfg["chips"] == 1
    assert "WHOLE model" in cfg["deployment"] \
        and "7 slots x 1,536" in cfg["deployment"]
    # (a) - (e) of the issue, each by its letter
    for letter, word in (("(a)", "final norm"), ("(b)", "no bias"),
                         ("(c)", "NOT evaluated"), ("(d)", "rotate-half"),
                         ("(e)", "random int8")):
        assert any(a.startswith(letter) and word in a
                   for a in cfg["assumed"]), letter
    env = cfg["env"]
    assert env["TPU_SLOTS"] == "7" and env["TPU_MAX_SEQ"] == "1536"
    assert env["TPU_PREFIX_CACHE"] == "1"        # never 0
    assert "TPU_KV_DTYPE" not in env and "TPU_QUANT" not in env   # int8
    assert env["TPU_SPEC_DECODE"] == "0" and env["TPU_KVCACHE_HOST_MB"] == "0"
    assert set(env) == set(cfg["env_why"]) | {"GRPC_PORT", "METRICS_PORT"}
    ref = cfg["reference"]
    assert ref["module"] == "references/ouro.py"
    assert ref["prompt_tokens"] == [24, 40, 600] and ref["new_tokens"] == 32
    assert ref["statistic"] == "worst"           # a dense model
    assert 0 < ref["tolerance_nats"] <= 0.5
    for word in ("4 bits", "three_passes", "pass0_rows"):
        assert word in ref["why"], word
    small = cfg["rehearsal"]
    assert max(small["reference"]["prompt_tokens"]) + ref["new_tokens"] \
        < int(small["env"]["TPU_MAX_SEQ"])
    from gofr_tpu.models import LLAMA_CONFIGS
    tiny = LLAMA_CONFIGS[small["model"]]
    assert tiny.loop_steps == 3 and tiny.n_layers == 2 \
        and tiny.sandwich_norm and tiny.n_heads == tiny.n_kv_heads == 4


def test_the_byte_count():
    """ISSUE 49's arithmetic at 1 byte a parameter: a layer 51.4 M, 48
    layers 2.47 GB, the head 0.10 GB; a cached token 786,432 B of rows
    and 811,008 with its scales, twelve of Mistral's 65,536; a slot of
    1,536 positions 1.246 GB, seven 8.72 GB; a step's weights 9.9 GB."""
    m = _model()
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert abs(layer / 51.4e6 - 1) < 0.002
    assert abs(48 * layer / 2.47e9 - 1) < 0.002
    assert abs(rf.layer_weight_bytes(m) / layer - 1) < 0.005  # the scales
    assert abs(rf.stack_bytes(m) / 2.47e9 - 1) < 0.005
    assert abs(rf.head_bytes(m) / 0.1007e9 - 1) < 0.005
    assert abs(rf.loop_weight_bytes(m) / 9.87e9 - 1) < 0.005
    assert rf.weight_bytes_per_step(m) \
        == 4 * rf.stack_bytes(m) + rf.head_bytes(m)
    assert rf.tables(m) == 192
    assert 192 * 16 * 128 * 2 == 786_432
    assert rf.kv_bytes_per_token(m) == 811_008
    assert 786_432 == 12 * 65_536                # Mistral's int8 token
    slot = 1536 * rf.kv_bytes_per_token(m)
    assert abs(slot / 1.2457e9 - 1) < 0.001
    assert abs(7 * slot / 8.72e9 - 1) < 0.001
    # what the program says of itself is the same arithmetic
    from gofr_tpu.models import ModelConfig, ouro
    said = ouro.serving_stats(ModelConfig(**_cfg()["model_config"]), 7)
    assert said["kv_tables"] == 192 and said["loop_steps"] == 4
    assert said["kv_bytes_per_token"] == rf.kv_bytes_per_token(m)
    assert said["weight_bytes_per_step"] == 4 * 48 * layer + 2048 * 49152
    # the write: 7 slots x 192 x 16 x (2 x 4,096 + 2 x 512), read and
    # written: 0.40 GB
    assert rf.append_bytes(m, 7) == 2 * 7 * 192 * 16 * (2 * 4096 + 2 * 512)
    assert abs(rf.append_bytes(m, 7) / 0.396e9 - 1) < 0.01
    # a step at 7 slots x 600 positions fetched: 9.97 + 3.41 + 0.40 GB
    step = rf.step_bytes(m, 7 * 600, 7)
    assert 13.6e9 < step < 13.9e9
    assert 0.70 < rf.loop_weight_bytes(m) / step < 0.74


def test_batch_sat_14_is_batch_sat_with_14_callers_inside_the_cache():
    mc = _cfg()["model_config"]
    load = lambda mix: traffic.load(  # noqa: E731
        os.path.join(BENCH, "traffic", mix + ".json"))
    mine, theirs = load("batch-sat-14"), load("batch-sat")
    # batch-sat's file with 14 callers and both sigmas halved (the one
    # departure ISSUE 49 allows a cell that was not steady, said in the
    # file's ``why``); nothing else of the mix moves
    halved = dict(theirs, clients=14, why=mine["why"])
    for key in ("prompt_tokens", "output_tokens"):
        halved[key] = dict(theirs[key], sigma=theirs[key]["sigma"] / 2)
    assert mine == halved
    assert "HALF of batch-sat's" in mine["why"]
    assert mine["clients"] == 14 == 2 * int(_cfg()["env"]["TPU_SLOTS"])
    assert theirs["clients"] == 80
    sched = traffic.build(mine, 7, 50.0)
    assert max(r["prompt"] + r["output"] for r in sched["requests"]) \
        < mc["max_seq"] - 2
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "batch-sat-14"
    assert cell["config"] == NAME and len(cell["why"]) <= 200
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == NAME] == [CELL]    # one cell, no second
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == MINE
    assert all(m["moves"] == "out_tok_s" for m in bench["per_layer"]
               if m["name"] in MINE)
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    for name in ("out_tok_s", "setup_s", "sched.occupancy_pct",
                 "sched.dry_pct", "sched.dry_admit_pct",
                 "sched.host_busy_pct", "sched.overlapped_reap_pct",
                 "hbm.in_use_gb", "hbm.peak_gb", "hbm.startup_peak_gb",
                 "decode.step_ms", "device.idle_pct", "kv.pool_fill_pct",
                 "attn.kv_read_pct", "setup.compile_s", "setup.weights_s",
                 "window.compiles", "sample.drawn_blocks_pct"):
        assert name in reports, name
    # the readers that count Mistral's bytes, or another family's, are
    # not read here
    for m in bench["per_layer"]:
        if m["name"] in ("kv.live_gb", "decode_step_roofline") \
                or m["name"].startswith(("mla.", "kda.", "swa.", "moe.",
                                         "ssm.", "dsa.", "state.")) \
                or m["name"].endswith((".chat-rate", ".lfm2", ".laguna")):
            assert CELL not in m["workloads"], m["name"]


def _ctx(**over):
    """A traced run's context, by hand: 25 blocks of 4 steps at 7 slots,
    3 s of trace; 3,360 live positions, 4,200 fetched (7 x 600)."""
    m = _model()
    decode = [(i, 10.0 + 0.1 * i, 0.1, "decode", tuple(range(7)), 4,
               3_360, 4_200) for i in range(25)]
    ctx = SimpleNamespace(
        model=m, slots=7, decode_block=4, traffic_name="batch-sat-14",
        timeline=decode, t_open=0.0,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace={"span": (9.0, 13.0), "ops": {
            "flash_decode_stacked.3 f32[7,16,8,128]": 0.5,
            "append_rows_stacked.7 s8[192,7,16,1536,128]": 0.06,
            # the layer loop's products as the chip names them
            "multiply_reduce_fusion.5 f32[7]": 0.55,
            "multiply_convert_fusion.7 bf16[7,5632]": 0.3,
            "fusion.269 bf16[7,1,2048]": 0.2,
            "fusion.270 f32[7,1,2048]": 0.15,
            "fusion.3 bf16[7,2048]": 0.05,
            # not a layer's: the head, a prefill's, a rotation's table,
            # an operation that is no fusion
            "fusion.9 bf16[7,49152]": 0.02,
            "fusion.4 bf16[1,512,2048]": 9.0,
            "fusion.271 f32[7,64]": 0.1,
            "copy.5 bf16[7,2048]": 0.1},
            "modules": {"jit__step_fn": {"count": 25, "seconds": 2.1}}},
        engine_stats={"loop": {"tokens": 700, "passes": 2800},
                      "loop_steps": 4, "kv_tables": 192})
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def test_the_readers_on_a_context_made_by_hand():
    import run

    ctx = _ctx()
    m = ctx.model
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert abs(read("decode.step_ms") - 21.0) < 1e-9
    assert abs(read("attn.decode_ms.ouro") - 5.0) < 1e-9
    # 4,200 positions x 811,008 B = 3.41 GB: 4.16 ms at 819 GB/s, of 5
    assert abs(read("attn.decode_roofline.ouro") - 83.2) < 0.1
    assert abs(read("kv.append_ms.ouro") - 0.6) < 1e-9
    # 0.396 GB at 819 GB/s = 0.484 ms, of 0.6
    assert abs(read("kv.append_roofline.ouro") - 80.7) < 0.2
    # the projections: 1.25 s over 100 steps (not the head's, not a
    # prefill's, not a fusion that is no product)
    assert abs(read("loop.weights_ms") - 12.5) < 1e-9
    assert abs(read("loop.weights_roofline")
               - 100 * rf.loop_weight_bytes(m) / 819e9 / 12.5e-3) < 1e-6
    assert 95 < read("loop.weights_roofline") < 98
    assert abs(read("kv.live_gb.ouro") - 3_360 * 811_008 / 1e9) < 1e-9
    assert read("loop.steps_per_token") == 4.0
    # 9.97 + 3.41 + 0.40 GB = 13.78 GB: 16.8 ms, of 21
    assert abs(read("decode_step_roofline.ouro") - 80.1) < 0.3
    for name in MINE:
        value = read(name)
        assert value is not None and (
            "roofline" not in name or value <= 100), name
    # the parent's program has no such field: every reader reads nothing
    parent = _ctx(model={k: v for k, v in m.items()
                         if k not in ("loop_steps", "sandwich_norm",
                                      "early_exit_threshold")},
                  engine_stats={})
    for name in MINE:
        assert run.read_metric(name, parent) is None, name
    # an untraced context: the trace's readers read nothing, nothing
    # raises
    bare = _ctx(trace=None)
    for name in MINE - {"kv.live_gb.ouro", "loop.steps_per_token"}:
        assert run.read_metric(name, bare) is None, name
    # and the readers that count another model's bytes are not this
    # cell's: kv.live_gb would reckon a token from n_layers, a quarter
    from benchmarks import roofline
    assert roofline.kv_bytes_per_token(m) * 4 == rf.kv_bytes_per_token(m)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "ouro.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    # the loop written as two Python fors, no cache
    assert "for t in range(passes):" in src
    assert "for l in range(cfg.n_layers):" in src


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
                           "ouro.py"), "a") as f:
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
    assert got.stderr.count(MARK) == len(prompts) == 3
    line = json.loads(got.stderr.strip().splitlines()[-1]
                      .removeprefix("[bench] "))
    assert line["correct"] is True and line["failed"] == 0
    # the probe's second run is a prefix-pool hit: every one of the six
    # tables restored from the pool's one row, the miss's tokens
    assert line["detail"]["probe_hit_equals_miss"] is True
    # the program's counts reached the readers
    assert line["metrics"]["loop.steps_per_token"]["value"] == 3.0
    assert line["metrics"]["kv.live_gb.ouro"]["value"] > 0
