"""granite-4.0-h-micro-int8: every published key against the
``model_config`` the program runs, the one reduced key, the byte count,
the cell's entries, the readers on a synthetic context, the rehearsal
end to end with the family's own reference, and the controls of the
numerical check (on the chip, at the cell's own size)."""

import json
import os
import shutil
import subprocess
import sys
from functools import partial
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

from benchmarks import roofline_granite_hybrid as rf, traffic  # noqa: E402

NAME = "granite-4.0-h-micro-int8"
CELL = NAME + ".reason-sat"
NEMOTRON = "nemotron-3-super-120b-int8-ep4.reason-sat"
MARK = "the family's reference was called"
MINE = {"decode_step_roofline.granite_hybrid",
        "ffn.in_decode_ms.granite_hybrid", "head.decode_ms.granite_hybrid",
        "kv.live_gb.granite_hybrid"}
SHARED = {"ssm.decode_ms", "ssm.decode_roofline", "ssm.prefill_roofline",
          "state.live_gb.nemotron_h"}
LAYER_TYPES = (["mamba"] * 5 + ["attention"] + (["mamba"] * 9
                                                + ["attention"]) * 3
               + ["mamba"] * 4)
# the catalog's row (model-configs guide), every key as published
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": LAYER_TYPES,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
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
    assert cfg["published"] == {"max_position_embeddings": 131072}
    assert len(LAYER_TYPES) == 40 and [
        i for i, t in enumerate(LAYER_TYPES) if t == "attention"] \
        == [5, 15, 25, 35]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["max_position_embeddings"] == 2048
    entry = next(c for c in _bench()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"


def test_every_published_width_is_what_the_program_runs():
    cfg = _cfg()
    mc = cfg["model_config"]
    for key, field in (
            ("hidden_size", "dim"), ("shared_intermediate_size", "ffn_dim"),
            ("intermediate_size", "ffn_dim"),
            ("num_attention_heads", "n_heads"),
            ("num_key_value_heads", "n_kv_heads"),
            ("num_hidden_layers", "n_layers"),
            ("mamba_n_heads", "ssm_heads"), ("mamba_d_head", "ssm_head_dim"),
            ("mamba_n_groups", "ssm_groups"), ("mamba_d_state", "ssm_state"),
            ("mamba_d_conv", "conv_kernel"),
            ("mamba_chunk_size", "ssm_chunk"),
            ("attention_multiplier", "attention_multiplier"),
            ("embedding_multiplier", "embedding_multiplier"),
            ("residual_multiplier", "residual_multiplier"),
            ("logits_scaling", "logits_scaling"),
            ("rms_norm_eps", "norm_eps"),
            ("tie_word_embeddings", "tie_embeddings"),
            ("vocab_size", "vocab_size"),
            ("num_local_experts", "n_experts"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    # nothing of the model is cut: 40 layers, 32 on 8 heads of 64, 64
    # state-space heads of 64 in ONE group, 8,192, 100,352 rows, tied
    assert mc["attn_head_dim"] == cfg["hidden_size"] \
        // cfg["num_attention_heads"] == 64
    assert mc["ssm_heads"] * mc["ssm_head_dim"] \
        == cfg["mamba_expand"] * cfg["hidden_size"] == 4096
    assert mc["layer_pattern"] == [
        {"mamba": "mamba", "attention": "attn"}[t]
        for t in cfg["layer_types"]]
    assert mc["use_rope"] is False \
        and cfg["position_embedding_type"] == "nope"
    assert mc["layer_ffn"] is True and "lm_head" not in mc
    # 0.015625 x 8 is a power of two: scaling q is exact in bfloat16
    assert mc["attention_multiplier"] * mc["attn_head_dim"] ** 0.5 == 0.125
    from gofr_tpu.models import (ModelConfig, family, hybrid_cache,
                                 nemotron_h)
    built = ModelConfig(**mc)
    assert family(built) is nemotron_h
    assert nemotron_h.counts(built) == (36, 0, 4)
    assert hybrid_cache.paired(built) \
        and nemotron_h.kv_layout(built) == (4, 128)


def test_the_deployment_the_assumptions_and_the_reference_entry():
    cfg = _cfg()
    assert cfg["chips"] == 1 and cfg["chips_a_layer"] == 1
    assert "WHOLE model" in cfg["deployment"] \
        and "96 slots x 2,048" in cfg["deployment"]
    for word in ("float32", "random int8", "time_step", "[z | xBC | dt]",
                 "0.015625", "Reach (c)", "rounded once",
                 "num_local_experts 0"):
        assert any(word in a for a in cfg["assumed"]), word
    env = cfg["env"]
    assert env["TPU_SLOTS"] == "96" and env["TPU_MAX_SEQ"] == "2048"
    assert env["TPU_KV_DTYPE"] == "bfloat16"
    nemotron = json.load(open(os.path.join(
        BENCH, "configs", "nemotron-3-super-120b-int8-ep4.json")))["env"]
    assert {k: v for k, v in env.items() if k != "TPU_MODEL"} \
        == {k: v for k, v in nemotron.items() if k != "TPU_MODEL"}
    assert set(env) == set(cfg["env_why"]) | {"GRPC_PORT", "METRICS_PORT"}
    ref = cfg["reference"]
    assert ref["module"] == "references/granite_hybrid.py"
    assert ref["prompt_tokens"] == [24, 40, 600, 1500] \
        and ref["new_tokens"] == 64
    assert ref["statistic"] == "worst"           # a dense model
    assert 0 < ref["tolerance_nats"] <= 0.5
    for word in ("bfloat16 state", "multiplier", "64^-1/2"):
        assert word in ref["why"], word
    small = cfg["rehearsal"]
    assert max(small["reference"]["prompt_tokens"]) \
        + small["reference"]["new_tokens"] < int(small["env"]["TPU_MAX_SEQ"])
    from gofr_tpu.models import LLAMA_CONFIGS
    tiny = LLAMA_CONFIGS[small["model"]]
    assert tiny.layer_ffn and tiny.ssm_groups == 1 and tiny.tie_embeddings


def test_the_byte_count():
    """ISSUE 53's arithmetic at 1 byte a parameter: a mamba mixer 25.8 M,
    an attention mixer 10.5 M, a feed-forward 50.3 M, 2.99 GB, the table
    0.41 GB; a slot 75.5 MB of state and 8 KiB a token; at 96 full slots
    the state is three quarters of a step's bytes."""
    m = _model()
    mamba = 2048 * 8512 + 4096 * 2048
    attn = 2 * 2048 ** 2 + 2 * 2048 * 512
    ffn = 2048 * 16384 + 8192 * 2048
    assert abs(mamba / 25.8e6 - 1) < 0.003 and abs(attn / 10.5e6 - 1) < 0.003
    assert abs(ffn / 50.3e6 - 1) < 0.002
    whole = 36 * (mamba + ffn) + 4 * (attn + ffn)
    assert abs(whole / 2.99e9 - 1) < 0.002
    # the scales, taps, biases and norms on top: under 1%
    assert 1.0 < (rf.mixer_bytes(m) + rf.ffn_bytes(m)) / whole < 1.01
    assert abs(rf.ffn_bytes(m) / (40 * ffn) - 1) < 0.005
    assert rf.head_bytes(m) == 100352 * 2048 * 2
    assert abs(rf.head_bytes(m) / 0.411e9 - 1) < 0.001
    assert abs(rf.weight_bytes_per_step(m) / 3.40e9 - 1) < 0.01
    # roofline_nemotron_h counts a tied head as nothing and no
    # feed-forward: what this file adds
    from benchmarks import roofline_nemotron_h as nrf
    assert nrf.fixed_weight_bytes(m) == rf.mixer_bytes(m)
    assert nrf.kinds(m) == (36, 0, 4)
    assert rf.state_bytes_per_slot(m) == 36 * 64 * 64 * 128 * 4
    assert abs(rf.state_bytes_per_slot(m) / 75.5e6 - 1) < 0.001
    assert rf.kv_bytes_per_token(m) == 8192
    states, rows = 96 * 36, 96 * 800
    step = rf.step_bytes(m, states, rows)
    assert abs(rf.decode_kernel_bytes(m, states) / (2 * 7.25e9) - 1) < 0.001
    assert abs(rf.tail_bytes(m, states) / 0.18e9 - 1) < 0.02
    assert 0.74 < rf.decode_kernel_bytes(m, states) / step < 0.80
    # what the program says of itself is the same arithmetic
    from gofr_tpu.models import ModelConfig, nemotron_h
    said = nemotron_h.serving_stats(ModelConfig(**_cfg()["model_config"]), 96)
    assert said["kv_bytes_per_token"] == 8192
    assert said["kv_heads_per_row"] == 2
    assert said["state_bytes_per_slot"] == rf.state_bytes_per_slot(m) \
        + 36 * 3 * 4352 * 2
    assert "moe_decode_dispatch" not in said


def test_the_cell_is_reason_sat_as_it_is_and_reports_what_applies():
    mc = _cfg()["model_config"]
    mix = traffic.load(os.path.join(BENCH, "traffic", "reason-sat.json"))
    assert mix["clients"] == 256 and mix["loop"] == "closed"
    sched = traffic.build(mix, 7, 50.0)
    assert max(r["prompt"] + r["output"] for r in sched["requests"]) \
        < mc["max_seq"] - 2
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-sat"
    assert cell["config"] == NAME and len(cell["why"]) <= 200
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == NAME] == [CELL]    # one cell, no second
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == MINE
    assert all(m["moves"] == "out_tok_s" for m in bench["per_layer"]
               if m["name"] in MINE)
    # the state-space kernels' readers take groups, heads and chunk from
    # ctx.model: one name for both cells (a later cell may join them)
    for m in bench["per_layer"]:
        if m["name"] in SHARED:
            assert m["workloads"][:2] == [NEMOTRON, CELL], m["name"]
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    for name in ("out_tok_s", "setup_s", "sched.occupancy_pct",
                 "sched.dry_pct", "sched.dry_admit_pct",
                 "sched.host_busy_pct", "sched.overlapped_reap_pct",
                 "sched.stall_s", "sched.loop_cpu_pct",
                 "hbm.in_use_gb", "hbm.peak_gb", "hbm.startup_peak_gb",
                 "decode.step_ms", "device.idle_pct", "kv.pool_fill_pct",
                 "attn.kv_read_pct", "setup.compile_s", "setup.weights_s",
                 "window.compiles", "sample.drawn_blocks_pct"):
        assert name in reports, name
    # every metric every saturated cell reports is reported here
    sat = [w["name"] for w in bench["workloads"]
           if w["traffic"] != "chat-rate" and w["name"] != CELL]
    for m in bench["per_layer"]:
        if set(sat) <= set(m.get("workloads", ())):
            assert CELL in m["workloads"], m["name"]
    # the readers that count another family's bytes are not read here
    for m in bench["per_layer"]:
        if m["name"] in ("kv.live_gb", "decode_step_roofline",
                         "decode_step_roofline.nemotron_h") \
                or m["name"].startswith(("mla.", "kda.", "swa.", "moe.",
                                         "dsa.", "loop.")) \
                or m["name"].endswith((".chat-rate", ".lfm2", ".laguna",
                                       ".ouro")):
            assert CELL not in m["workloads"], m["name"]


def _ctx(**over):
    """A traced run's context, by hand: 25 blocks of 4 steps at 96 slots,
    3 s of trace; 76,800 live positions; every slot's 36 states a step."""
    m = _model()
    decode = [(i, 10.0 + 0.1 * i, 0.1, "decode", tuple(range(96)), 4,
               76_800, 80_000, None, None, 4 * 96 * 36) for i in range(25)]
    prefill = [(100, 10.5, 0.01, "prefill", (3,), 600)]
    ctx = SimpleNamespace(
        model=m, slots=96, decode_block=4, traffic_name="reason-sat",
        timeline=decode + prefill, t_open=0.0,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace={"span": (9.0, 13.0), "ops": {
            "ssd_decode.3 f32[36,96,1,128,4096]": 2.1,
            "ssd_prefill.5 f32[1,4,2,256,1024]": 0.02,
            "fusion.11 bf16[96,1,16384]": 0.21,
            "fusion.12 bf16[96,8192]": 0.04,
            "fusion.21 f32[96,100352]": 0.06,
            # not the feed-forward's nor the head's
            "fusion.13 bf16[96,1,2048]": 0.2,
            "fusion.14 bf16[1,512,16384]": 5.0},
            "modules": {"jit__step_fn": {"count": 25, "seconds": 2.8}}},
        engine_stats={"state_bytes_per_slot": 36 * (2097152 + 3 * 4352 * 2),
                      "kv_bytes_per_token": 8192, "kv_heads_per_row": 2,
                      "prompt_buckets": [32, 64, 128, 256, 512],
                      "scheduler": {"prefill_chunk": 512}})
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def test_the_readers_on_a_context_made_by_hand():
    import run

    ctx = _ctx()
    m = ctx.model
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert abs(read("decode.step_ms") - 28.0) < 1e-9
    assert abs(read("ssm.decode_ms") - 21.0) < 1e-9
    # 96 x 36 states x 2 x 2.10 MB = 14.5 GB: 17.7 ms at 819 GB/s, of 21
    assert abs(read("ssm.decode_roofline") - 84.3) < 0.1
    assert 0 < read("ssm.prefill_roofline") <= 100
    assert abs(read("ffn.in_decode_ms.granite_hybrid") - 2.5) < 1e-9
    assert abs(read("head.decode_ms.granite_hybrid") - 0.6) < 1e-9
    assert abs(read("kv.live_gb.granite_hybrid")
               - 76_800 * 8192 / 1e9) < 1e-9
    assert abs(read("state.live_gb.nemotron_h") - 96 * 36
               * (2097152 + 3 * 4352 * 2) / 1e9) < 1e-6
    want = 100 * rf.step_bytes(m, 96 * 36, 76_800) / 819e9 / 28e-3
    assert abs(read("decode_step_roofline.granite_hybrid") - want) < 1e-6
    assert 80 < want < 85
    # the state-space family's own whole-step share is not this cell's:
    # it waits for an expert count that a dense stack never gives
    assert run.read_metric("decode_step_roofline.nemotron_h", ctx) is None
    for name in MINE | SHARED:
        value = read(name)
        assert value is not None and (
            "roofline" not in name or value <= 100), name
    # the parent's program has no such field: every new reader reads
    # nothing
    parent = _ctx(model={k: v for k, v in m.items()
                         if k not in ("layer_ffn", "embedding_multiplier",
                                      "residual_multiplier",
                                      "attention_multiplier",
                                      "logits_scaling")},
                  engine_stats={})
    for name in MINE:
        assert run.read_metric(name, parent) is None, name
    # Nemotron's cell: no layer of two halves, the new readers read
    # nothing there either
    theirs = json.load(open(os.path.join(
        BENCH, "configs", "nemotron-3-super-120b-int8-ep4.json")))
    import dataclasses

    from gofr_tpu.models import ModelConfig
    other = _ctx(model=dataclasses.asdict(
        ModelConfig(**theirs["model_config"])))
    for name in MINE:
        assert run.read_metric(name, other) is None, name
    # an untraced context: the trace's readers read nothing, nothing
    # raises
    bare = _ctx(trace=None)
    for name in MINE - {"kv.live_gb.granite_hybrid"}:
        assert run.read_metric(name, bare) is None, name


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "granite_hybrid.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    # the recurrence a scan over the tokens, the four multipliers read
    assert "jax.lax.scan(token" in src
    for field in ("embedding_multiplier", "residual_multiplier",
                  "attention_multiplier", "logits_scaling"):
        assert f"cfg.{field}" in src, field


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
                           "granite_hybrid.py"), "a") as f:
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
    # the probe's second run is a prefix-pool hit: every layer's state,
    # tail and paired rows restored from the pool's row
    assert line["detail"]["probe_hit_equals_miss"] is True
    # the program's counts reached the readers
    assert line["metrics"]["kv.live_gb.granite_hybrid"]["value"] > 0
    assert line["metrics"]["state.live_gb.nemotron_h"]["value"] > 0


# -- the controls of the numerical check ---------------------------------------
# one thing wrong in the REFERENCE, by its own arguments and the
# configuration's own fields, against the engine as it is: (name, the
# reference's keyword arguments, ModelConfig fields)
CONTROLS = (
    ("bfloat16 state", {"state_dtype": "bfloat16"}, {}),
    ("4-bit weights", {"weight_bits": 4}, {}),
    ("softmax at 64^-1/2", {}, {"attention_multiplier": 0.0}),
    ("no residual_multiplier", {}, {"residual_multiplier": 1.0}),
    ("no embedding_multiplier", {}, {"embedding_multiplier": 1.0}),
    ("no logits_scaling", {}, {"logits_scaling": 1.0}))
# what the cell's limit separates at the published widths. NOT the state's
# precision: a bfloat16 state reads 1.7 times the engine's own worst error
# there and passes (PERF.md section 7, item 18(g)); the state's type is
# pinned by tests/test_kernels_compile_v5e.py and tests/test_granite_hybrid.py
MUST_FAIL = tuple(name for name, _, _ in CONTROLS[1:])
SEED = 2147488001


def _controlled(forward, kwargs, fields):
    import jax.numpy as jnp

    kwargs = {k: getattr(jnp, v) if k == "state_dtype" else v
              for k, v in kwargs.items()}
    return lambda p, c, t, r: forward(p, c.with_(**fields), t, r, **kwargs)


@pytest.mark.parametrize("size", ["rehearsal", "cell"])
def test_the_controls_through_the_harness_own_comparison(monkeypatch, size):
    """``reference.compare`` on the engine ``run.py`` builds, once with
    the reference as it is and once a control. ``cell`` (a TPU alone:
    ``chiprun -- python -m pytest <this file> -k "controls and cell" -s``,
    about ten minutes warm) is the cell's own engine at the published
    widths, where every control of ``MUST_FAIL`` has to come out NOT
    correct by the configuration's own statistic and limit; every reading
    is printed and kept in ``bench_out/<cell>/controls.json``.
    ``rehearsal`` (a CPU) runs the same code at the tiny preset so that
    it stays runnable: there the controls are told from the engine's own
    reading, not from the cell's limit, and it says nothing of the cell."""
    import jax

    on_chip = jax.default_backend() == "tpu"
    if on_chip != (size == "cell"):
        pytest.skip(f"{size}: needs a {'TPU' if size == 'cell' else 'CPU'}")
    import gofr_tpu.tpu as tpu_pkg
    import run
    from benchmarks import reference
    from gofr_tpu.models import LLAMA_CONFIGS, ModelConfig

    cfg = _cfg()
    small = cfg["rehearsal"] if size == "rehearsal" else {}
    for k, v in {**cfg["env"], **small.get("env", {})}.items():
        monkeypatch.setenv(k, v)
    if size == "cell":      # as run.py: the program has no entry for it
        model = ModelConfig(**cfg["model_config"])
        monkeypatch.setitem(LLAMA_CONFIGS, model.name, model)
    monkeypatch.setattr(tpu_pkg, "random_params", partial(
        tpu_pkg.random_params, seed=SEED % (2 ** 31 - 1)))
    spec = dict(cfg["reference"], **small.get("reference", {}))
    forward = run.reference_forward(cfg["reference"])
    app = run.load_example_app()
    gen = app.container.tpu.generator
    gen.warmup()
    app.run(block=False)
    try:
        read = {}
        for name, kwargs, fields in (("as it is", {}, {}),) + CONTROLS:
            got = reference.compare(gen, SEED, spec,
                                    _controlled(forward, kwargs, fields))
            del got["positions"]
            read[name] = got
            print(f"control {size} seed {SEED}: {name}: {got}", flush=True)
    finally:
        app.stop(grace_s=10.0)
    out = os.path.join(REPO, "bench_out", CELL)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"controls-{size}.json"), "w") as f:
        json.dump({"seed": SEED, "size": size, "read": read}, f, indent=1)
    sound = read["as it is"]
    assert sound["ok"] and sound["statistic"] == "worst"
    worst = max(sound["worst"].values())
    for name, _, _ in CONTROLS:
        # every control moves the comparison, the state's type too
        assert max(read[name]["worst"].values()) > worst, name
    if size == "cell":
        for name in MUST_FAIL:
            assert read[name]["ok"] is False, (name, read[name])
    else:
        for name, _, _ in CONTROLS:
            assert max(read[name]["worst"].values()) > 10 * worst, name
