"""sdar-30b-a3b-int8-pp4: every published key against the ``model_config``
the program runs, the two reduced keys, the byte count, the cell's
entries found by name after what was there, the readers on a synthetic
context, the rehearsal end to end with the family's own reference, and
the controls of the numerical check (on the chip, at the cell's own
size)."""

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

from benchmarks import roofline_sdar as rf, traffic  # noqa: E402

NAME = "sdar-30b-a3b-int8-pp4"
CELL = NAME + ".reason-sat"
MARK = "the family's reference was called"
MINE = {"diffusion.pass_ms", "diffusion.tokens_per_pass",
        "decode_step_roofline.sdar", "attn.block_decode_ms.sdar",
        "attn.block_decode_roofline.sdar", "moe.experts_ms.sdar",
        "moe.experts_roofline.sdar", "moe.tokens_per_expert.sdar",
        "kv.live_gb.sdar"}
# the catalog's row (model-configs guide), every key as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


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


def test_every_published_width_is_what_the_program_runs():
    cfg = _cfg()
    mc = cfg["model_config"]
    for key, field in (
            ("hidden_size", "dim"), ("intermediate_size", "ffn_dim"),
            ("head_dim", "attn_head_dim"),
            ("moe_intermediate_size", "moe_ffn_dim"),
            ("num_attention_heads", "n_heads"),
            ("num_key_value_heads", "n_kv_heads"),
            ("num_experts", "n_experts"),
            ("num_experts_per_tok", "experts_per_token"),
            ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
            ("rope_scaling", "rope_scaling"),
            ("tie_word_embeddings", "tie_embeddings"),
            ("num_hidden_layers", "n_layers"),
            ("vocab_size", "vocab_size"),
            ("max_position_embeddings", "max_seq")):
        assert mc[field] == cfg[key], (key, field)
    assert cfg["model_type"] == "sdar_moe"
    # every layer routes (decoder_sparse_step 1, no mlp-only layer), all
    # 128 experts are held, none is shared, the router is a softmax
    assert cfg["decoder_sparse_step"] == 1 and cfg["mlp_only_layers"] == []
    assert mc["n_dense_layers"] == 0 and mc["n_experts_held"] == 0
    assert mc["n_shared_experts"] == 0 and mc["routed_scaling"] == 1.0
    assert mc["router_score"] == "softmax" and cfg["norm_topk_prob"]
    assert mc["qk_norm"] is True and cfg["attention_bias"] is False
    # the sampler the cell fixes, each value under ``assumed``
    assert mc["block_length"] == 4 and mc["denoise_passes"] == 2
    assert mc["commit_order"] == "sequential"
    assert mc["mask_token_id"] == 151669 < mc["vocab_size"]
    from gofr_tpu.models import ModelConfig, family, sdar
    assert family(ModelConfig(**mc)) is sdar


def test_the_catalog_keys_are_kept_but_the_two_reduced():
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert set(cfg["published"]) == set(cfg["reduced"]) \
        == set(cfg["reduced_why"])
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "max_position_embeddings": 32768}
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 12
    assert cfg["max_position_embeddings"] == 2048
    entry = next(c for c in _bench()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"


def test_a_layer_is_whole_on_its_chip():
    cfg = _cfg()
    mc = cfg["model_config"]
    assert cfg["chips"] == 1 and cfg["chips_a_layer"] == 1
    assert "4 v5e chips as 4 pipeline stages of 12 layers" \
        in cfg["deployment"]
    assert 4 * mc["n_layers"] == cfg["published"]["num_hidden_layers"]
    for word in ("24 tokens an expert", "exactly its share",
                 "four times a deployment's"):
        assert word in cfg["deployment"], word
    assert len(cfg["assumed"]) >= 7
    for word in ("block length 4", "mask token id 151,669", "no shift",
                 "sequential", "random int8", "renormalised",
                 "output head"):
        assert any(word in a for a in cfg["assumed"]), word
    assert cfg["env"]["TPU_SLOTS"] == "96"
    assert cfg["env"]["TPU_MAX_SEQ"] == "2048"
    assert cfg["env"]["TPU_KV_DTYPE"] == "bfloat16"
    assert cfg["env"]["TPU_SPEC_DECODE"] == "0"
    assert cfg["env"]["TPU_KVCACHE_HOST_MB"] == "0"
    assert set(cfg["env"]) == set(cfg["env_why"]) | {"GRPC_PORT",
                                                     "METRICS_PORT"}
    ref = cfg["reference"]
    assert ref["module"] == "references/sdar.py"
    # the four lengths of every sparse configuration and one with
    # n mod 4 = 2, three times over, 64 tokens each: 960 positions
    assert ref["prompt_tokens"] == [24, 40, 600, 1500, 42] * 3
    assert ref["new_tokens"] == 64 and ref["statistic"] == "median"
    assert 0 < ref["tolerance_nats"] <= 0.5
    for word in ("causal", "commit pass", "4 bits"):
        assert word in ref["why"], word
    small = cfg["rehearsal"]["reference"]
    assert max(small["prompt_tokens"]) + small["new_tokens"] \
        < int(cfg["rehearsal"]["env"]["TPU_MAX_SEQ"])
    from gofr_tpu.models import LLAMA_CONFIGS
    tiny = LLAMA_CONFIGS[cfg["rehearsal"]["model"]]
    assert tiny.block_length == mc["block_length"]
    assert tiny.denoise_passes == mc["denoise_passes"]
    assert tiny.commit_order == mc["commit_order"] and tiny.qk_norm
    assert tiny.router_score == "softmax" and tiny.n_shared_experts == 0


def test_the_byte_count():
    """ISSUE 57's arithmetic at 1 byte a weight: attention 18.9 M a
    layer, an expert 4.72 M, 128 of them 604 M, 0.623 GB a layer, 29.9 GB
    for 48; the embedding 0.622 GB, the head 0.311 GB; twelve layers
    whole 8.41 GB; the cache 24 KiB a token, 4.83 GB at 96 x 2,048; and
    the bytes the program really holds (roofline_sdar) within 1%."""
    m = _model()
    d, f, hd, kv, h, v = 2048, 768, 128, 4, 32, 151936
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    assert attn == rf.attention_weights(m)
    assert abs(attn / 18.9e6 - 1) < 0.002
    expert = 3 * d * f
    assert expert == 4_718_592
    assert abs(128 * expert / 604e6 - 1) < 0.001
    layer = attn + 128 * expert + d * 128 * 2
    assert abs(layer / 0.623e9 - 1) < 0.002
    assert abs(48 * layer / 29.9e9 - 1) < 0.002
    assert abs(v * d * 2 / 0.622e9 - 1) < 0.002
    assert abs(v * d / 0.311e9 - 1) < 0.002
    stage = 12 * layer + v * d * 3
    assert abs(stage / 8.41e9 - 1) < 0.002
    assert abs(rf.share_weight_bytes(m) / stage - 1) < 0.01
    assert abs(rf.expert_bytes(m) / expert - 1) < 0.01
    assert rf.row_bytes(m) == 2048
    assert rf.kv_bytes_per_token(m) == 24 * 1024
    assert abs(96 * 2048 * rf.kv_bytes_per_token(m) / 4.83e9 - 1) < 0.001
    # 96 slots x 4 positions x 8 of 128 experts: 24 tokens an expert
    assert 96 * m["block_length"] * m["experts_per_token"] \
        / m["n_experts"] == 24
    # a denoise pass at 96 slots, every expert touched, rows 40% full:
    # 7.8 GB of weights (the issue's 8.1 less the embedding's 0.3 GB it
    # counted in) and 1.9 GB of rows, 11.9 ms at 819 GB/s; its
    # operations 0.67 TFLOP, 3.4 ms at the matrix peak: the bytes bound
    # it, and the operations are within a factor of four
    rows = 0.4 * 96 * 2048
    byts = rf.pass_bytes(m, 12 * 128, rows, 1.0)
    assert 7.7e9 < byts - rows * rf.kv_bytes_per_token(m) < 7.9e9
    assert 9.6e9 < byts < 9.9e9
    flops = rf.pass_flops(m, 96, 12 * 96 * 4 * 8, rows, 96 * 2)
    assert 0.6e12 < flops < 0.75e12
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    floor = rf.pass_floor_s(m, peaks, slots=96, touched=12 * 128,
                            assigned=12 * 96 * 4 * 8, rows=rows, head=1.0,
                            head_rows=96 * 2)
    assert floor == byts / 819e9
    assert 3.0 < floor / (flops / 197e12) < 4.0
    # a commit pass reads no head: 0.31 GB less
    assert abs(byts - rf.pass_bytes(m, 12 * 128, rows, 0.0)
               - rf.head_bytes(m)) < 1
    # a floor that forgot the operations would not hold at small bytes:
    # with one expert touched the operations bound the pass
    tiny = rf.pass_floor_s(m, peaks, slots=96, touched=12, assigned=12 * 3072,
                           rows=0, head=0.0, head_rows=0)
    assert tiny > rf.pass_bytes(m, 12, 0, 0.0) / 819e9


def test_reason_sat_stays_inside_the_cache_and_the_entries_are_appended():
    mc = _cfg()["model_config"]
    params = traffic.load(os.path.join(BENCH, "traffic", "reason-sat.json"))
    assert params["loop"] == "closed" and params["clients"] == 256
    sched = traffic.build(params, 7, 50.0)
    assert max(r["prompt"] + r["output"] for r in sched["requests"]) \
        < mc["max_seq"] - 2
    bench = _bench()
    # found by name, after what was there
    assert bench["configs"][-1]["name"] == NAME
    cell = bench["workloads"][-1]
    assert cell["name"] == CELL
    assert cell["chips"] == 1 and cell["traffic"] == "reason-sat"
    assert cell["config"] == NAME and len(cell["why"]) <= 200
    assert "idle up" in cell["why"]
    assert [m["name"] for m in bench["per_layer"][-len(MINE):]] == [
        "diffusion.pass_ms", "diffusion.tokens_per_pass",
        "decode_step_roofline.sdar", "attn.block_decode_ms.sdar",
        "attn.block_decode_roofline.sdar", "moe.experts_ms.sdar",
        "moe.experts_roofline.sdar", "moe.tokens_per_expert.sdar",
        "kv.live_gb.sdar"]
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == MINE
    assert all(m["moves"] == "out_tok_s" for m in bench["per_layer"]
               if m["name"] in MINE)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    for name in ("out_tok_s", "setup_s", "sched.occupancy_pct",
                 "hbm.in_use_gb", "hbm.peak_gb", "device.idle_pct",
                 "setup.compile_s", "window.compiles", "kv.pool_fill_pct",
                 "attn.kv_read_pct", "sample.drawn_blocks_pct",
                 "sched.dry_pct", "sched.stall_s"):
        assert name in reports, name
    # a step here is a pass: ``decode.step_ms``'s number is
    # ``diffusion.pass_ms``'s, and the other families' readers read
    # nothing in this cell
    for m in bench["per_layer"]:
        if m["name"] in ("decode.step_ms", "kv.live_gb",
                         "decode_step_roofline", "moe.experts_ms",
                         "moe.experts_roofline", "moe.tokens_per_expert") \
                or m["name"].startswith(("mla.", "kda.", "swa.", "ssm.")) \
                or m["name"].endswith((".lfm2", ".laguna", ".ouro",
                                       ".chat-rate")):
            assert CELL not in m["workloads"], m["name"]


def _ctx(**over):
    """A traced run's context, by hand: 25 dispatches of 4 passes at 96
    slots, 3 s of trace; 80,000 live rows; every expert touched every
    pass; of a dispatch's 384 slot-passes 128 commit (512 rows) and 256
    denoise, 512 tokens delivered."""
    m = _model()
    decode = [(i, 10.0 + 0.1 * i, 0.1, "decode", tuple(range(96)), 4,
               80_000, 100_000, 4 * 12 * 3072, 4 * 12 * 128, None, None,
               None, None, (384, 512, 512)) for i in range(25)]
    ctx = SimpleNamespace(
        model=m, slots=96, decode_block=4, traffic_name="reason-sat",
        timeline=decode, t_open=0.0,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace={"span": (9.0, 13.0), "ops": {
            "flash_decode_block.7 f32[96,4,32,128]": 0.25,
            "fusion.1 bf16[64,768]": 0.5,
            "expert_blocks_stacked.3 bf16[11136,2048]": 0.4,
            "fusion.9 bf16[384,2048]": 9.0},
            "modules": {"jit__step_fn": {"count": 25, "seconds": 1.5}}},
        engine_stats={"moe_decode_dispatch": {"block_rows": 64,
                                              "buffer_rows": 11136},
                      "diffusion": {"head_rows_per_slot": 2,
                                    "block_length": 4}})
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def test_the_readers_on_a_context_made_by_hand():
    import run

    ctx = _ctx()
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert abs(read("diffusion.pass_ms") - 15.0) < 1e-9
    assert abs(read("diffusion.tokens_per_pass") - 512 / 384) < 1e-9
    assert abs(read("attn.block_decode_ms.sdar") - 2.5) < 1e-9
    # 80,000 rows x 2 KiB x 12 layers = 1.97 GB: 2.40 ms at 819 GB/s
    assert abs(read("attn.block_decode_roofline.sdar") - 96.0) < 0.5
    assert abs(read("kv.live_gb.sdar") - 80_000 * 24_576 / 1e9) < 1e-9
    assert abs(read("moe.experts_ms.sdar") - 9.0) < 1e-9
    # 12 x 128 experts x 4.73 MB = 7.27 GB: 8.88 ms, of 9 measured
    assert abs(read("moe.experts_roofline.sdar") - 98.6) < 0.5
    assert abs(read("moe.tokens_per_expert.sdar") - 24.0) < 1e-9
    # weights 7.50 + rows 1.97 + head 0.31 GB = 9.78 GB: 11.9 ms of 15
    assert abs(read("decode_step_roofline.sdar") - 79.6) < 0.5
    for name in MINE:
        assert read(name) is not None, name
    # the parent's program has no such field: every reader reads nothing
    parent = _ctx(model={k: v for k, v in _model().items()
                         if k not in ("block_length", "mask_token_id",
                                      "denoise_passes", "commit_order",
                                      "confidence_threshold",
                                      "router_score")})
    for name in MINE:
        assert run.read_metric(name, parent) is None, name
    # a program of the family whose events carry no passes yet: nothing
    # read, nothing raised
    short = _ctx(timeline=[e[:10] for e in ctx.timeline])
    for name in ("diffusion.tokens_per_pass", "kv.live_gb.sdar",
                 "attn.block_decode_roofline.sdar",
                 "decode_step_roofline.sdar"):
        assert run.read_metric(name, short) is None, name
    # and the other families' readers read nothing in this cell
    for name in ("moe.experts_ms", "kv.latent_live_gb", "state.live_gb",
                 "kda.decode_ms", "moe.experts_ms.lfm2",
                 "attn.decode_ms.lfm2", "kv.live_gb.lfm2"):
        assert run.read_metric(name, ctx) is None, name


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "sdar.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "cache" not in src.split('"""', 2)[2].lower()


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
                           "sdar.py"), "a") as f:
        f.write(f"""

_plain = forward_logprobs


def forward_logprobs(*a, **k):
    import sys
    print({MARK!r}, file=sys.stderr)
    return _plain(*a, **k)
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
    assert got.stderr.count(MARK) == len(prompts) == 5
    line = json.loads(got.stderr.strip().splitlines()[-1]
                      .removeprefix("[bench] "))
    assert line["correct"] is True and line["failed"] == 0
    # a hit restores whole blocks and gives the miss's tokens
    assert line["detail"]["probe_hit_equals_miss"] is True
    # the program's counts reached the readers
    assert 1.0 < line["metrics"]["diffusion.tokens_per_pass"]["value"] < 1.34
    assert line["metrics"]["moe.tokens_per_expert.sdar"]["value"] > 0
    assert line["metrics"]["kv.live_gb.sdar"]["value"] > 0


# -- the controls of the numerical check ---------------------------------------
# one thing wrong in the REFERENCE, by its own arguments, against the
# engine as it is: the block's attention made causal, the commit pass
# left out (rows kept from the last denoise pass), and the nearest
# precision below the configuration's
CONTROLS = (("causal block", {"control": "causal_block"}),
            ("no commit pass", {"control": "no_commit"}),
            ("4-bit weights", {"weight_bits": 4}))
SEED = 2147488001


@pytest.mark.parametrize("size", ["rehearsal", "cell"])
def test_the_controls_through_the_harness_own_comparison(monkeypatch, size):
    """``reference.compare`` on the engine ``run.py`` builds, once with
    the reference as it is and once a control. ``cell`` (a TPU alone:
    ``chiprun -- python -m pytest <this file> -k "controls and cell" -s``)
    is the cell's own engine at the published widths, where every control
    has to come out NOT correct by the configuration's own statistic and
    limit; every reading is printed and kept in
    ``bench_out/<cell>/controls-cell.json``. ``rehearsal`` (a CPU) runs
    the same code at the tiny preset so that it stays runnable: there the
    controls are told from the engine's own reading, not from the cell's
    limit, and it says nothing of the cell."""
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
        for name, kwargs in (("as it is", {}),) + CONTROLS:
            got = reference.compare(gen, SEED, spec,
                                    partial(forward, **kwargs))
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
    assert sound["ok"] and sound["statistic"] == "median"
    held = max(sound["median"].values())
    for name, _ in CONTROLS:
        got = max(read[name]["median"].values())
        if size == "cell":
            assert read[name]["ok"] is False, (name, read[name])
        assert got > (1.5 if size == "cell" else 10) * held, (name, got, held)
