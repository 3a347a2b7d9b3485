"""dots3-note-prev-int8-ep8: every number of the catalog row's config in
the file under its own key, the published keys against the
``model_config`` the program runs, the chip's share against the published
counts, the byte count, the traffic inside the cache, the readers on a
program that lacks the family, and the rehearsal end to end with the
family's own reference."""

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

from benchmarks import roofline_dots3_note as rf, traffic  # noqa: E402

NAME = "dots3-note-prev-int8-ep8"
CELL = NAME + ".reason-sat"
MARK = "the family's reference was called"
KIND = {"full_attention": "full", "sliding_attention": "window"}


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
            ("swa_num_attention_heads", "window_heads"),
            ("swa_q_lora_rank", "window_q_lora_rank"),
            ("swa_kv_lora_rank", "window_kv_lora_rank"),
            ("swa_qk_nope_head_dim", "window_qk_nope_head_dim"),
            ("swa_qk_rope_head_dim", "window_qk_rope_head_dim"),
            ("swa_v_head_dim", "window_v_head_dim"),
            ("swa_rope_theta", "window_rope_theta"),
            ("sliding_window_size", "window_size"),
            ("index_n_heads", "index_heads"),
            ("index_head_dim", "index_head_dim"),
            ("index_topk", "index_topk"),
            ("apply_mla_qkv_lora_rescale", "lora_rescale"),
            ("num_experts_per_tok", "experts_per_token"),
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
    assert cfg["topk_method"] == "noaux_tc" and mc["n_expert_groups"] == 1
    assert cfg["model_type"] == "dots3_note"
    assert cfg["attention_gate_type"] == cfg["swa_attention_gate_type"] \
        == "headwise" and mc["head_gate"] is True
    # layer_types is copied whole; the layers run are its first nine
    assert len(cfg["layer_types"]) == 46
    assert mc["layer_pattern"] == [KIND[t] for t in
                                   cfg["layer_types"][:mc["n_layers"]]]
    # the window layers' heads: 64 x (192 + 64) on a latent of 1,024
    assert cfg["swa_num_key_value_heads"] == cfg["swa_num_attention_heads"]


def test_the_file_holds_every_number_of_the_catalog_row():
    """Where the catalog is at hand: every key of the row's ``config`` is
    in the file under the same key and, but for ``reduced``, unchanged;
    no width is in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "max_position_embeddings"]
    assert cfg["published"] == {
        "num_hidden_layers": 46, "n_routed_experts": 256,
        "vocab_size": 152064, "max_position_embeddings": 524288}
    if not os.path.isfile(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_the_share_against_the_published_counts():
    cfg = _cfg()
    mc, pub = cfg["model_config"], cfg["published"]
    assert set(pub) == set(cfg["reduced"]) == set(cfg["reduced_why"])
    # the router stays as wide as published; the chip holds an eighth
    assert mc["n_experts"] == pub["n_routed_experts"] == 256
    assert mc["n_experts_held"] == cfg["n_routed_experts"] == 32
    assert cfg["chips_a_layer"] * mc["n_experts_held"] == mc["n_experts"]
    # the guide's floors: a whole period and >= 4 layers after the dense
    # one, >= 8 experts, >= 1/8 of the vocabulary
    assert mc["n_layers"] - mc["n_dense_layers"] == 8
    assert mc["layer_pattern"][1:] == ["full", "window", "window",
                                       "window"] * 2
    assert mc["n_experts_held"] >= 8
    assert mc["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["env"]["TPU_SLOTS"] == "128"
    assert cfg["env"]["TPU_KV_DTYPE"] == "bfloat16"
    # eight whole chunks of 512, and room past index_topk for the check:
    # the comparison's one median is of positions that leave rows out,
    # so the prompts past index_topk outnumber the others
    prompts = cfg["reference"]["prompt_tokens"]
    assert mc["max_seq"] == 4096 > max(prompts) + cfg["reference"][
        "new_tokens"]
    assert 2 * sum(n > mc["index_topk"] for n in prompts) > len(prompts)
    assert cfg["reference"]["statistic"] == "median"
    assert len(cfg["assumed"]) >= 8
    for letter in "abcdef":
        assert any(a.startswith(f"({letter})") for a in cfg["assumed"])


def test_the_byte_count_of_the_share():
    """ISSUE 46's arithmetic at 1 byte a parameter: 0.98 GB of attention
    and indexer, 6.24 GB of experts, shared experts and routers, 7.7 GB
    in all; a slot's three tables 25.9 MB, 3.3 GB at 128 slots."""
    mc = _cfg()["model_config"]
    n = rf.kinds(mc)
    assert n == {"full": 3, "window": 6}
    attn = sum(n[k] * rf.attention_weight_bytes(mc, k) for k in n)
    assert abs(attn / 0.98e9 - 1) < 0.01
    assert abs(rf.expert_bytes(mc) / 23.6e6 - 1) < 0.005
    routed = 8 * (32 * rf.expert_bytes(mc) + rf.expert_bytes(mc)
                  + mc["dim"] * 256 * 2)
    assert abs(routed / 6.24e9 - 1) < 0.01
    assert abs(rf.share_weight_bytes(mc) / 7.7e9 - 1) < 0.01
    assert (rf.row_bytes(mc, "full"), rf.row_values(mc, "full")) \
        == (1280, 576)
    assert (rf.row_bytes(mc, "window"), rf.row_values(mc, "window")) \
        == (2304, 1088)
    assert rf.key_bytes(mc) == 256 and rf.ring_rows(mc) == 512
    assert abs(rf.slot_bytes(mc) / 25.9e6 - 1) < 0.005
    assert abs(128 * rf.slot_bytes(mc) / 3.3e9 - 1) < 0.01
    # what a step must fetch falls with the rows kept, not the rows live
    all_kept = rf.step_bytes(mc, 200, 128 * 3000, 128 * 3001, 128 * 512)
    sparse = rf.step_bytes(mc, 200, 128 * 3000, 128 * 2048, 128 * 512)
    assert all_kept - sparse == 128 * 953 * 3 * 1280


def test_reason_sat_stays_inside_the_cache_and_under_index_topk():
    mc = _cfg()["model_config"]
    params = traffic.load(os.path.join(BENCH, "traffic", "reason-sat.json"))
    assert params["loop"] == "closed" and params["clients"] == 256
    sched = traffic.build(params, 7, 50.0)
    longest = max(r["prompt"] + r["output"] for r in sched["requests"])
    assert longest < mc["index_topk"] < mc["max_seq"] - 2
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-sat"
    # the family's readers: the entries whose list this cell opens (a later
    # cell of the family is appended behind it), in this order
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == CELL]
    assert mine == [
        "decode_step_roofline.dots3_note", "dsa.index_ms", "dsa.kept_pct",
        "mla.sparse_decode_attn_ms", "mla.sparse_decode_attn_roofline",
        "mla.window_decode_attn_ms", "mla.window_decode_attn_roofline",
        "kv.latent_live_gb.dots3_note", "kv.index_live_gb",
        "kv.window_live_gb.dots3_note", "moe.experts_ms.dots3_note",
        "moe.experts_roofline.dots3_note",
        "moe.tokens_per_expert.dots3_note"]


def test_the_readers_read_the_programs_counts_and_nothing_without_them():
    """The rows kept and the rows chosen among come from the decode
    events' fourteenth field; a
    program that gives none (the parent of the PR that brought the
    family), or a model without the fields, reads nothing and does not
    raise."""
    from types import SimpleNamespace

    import run
    from benchmarks.metrics import _dots3_note as readers
    mc = _cfg()["model_config"]
    # (seq, t, duration, kind, slots, steps, live, fetched, assigned,
    #  touched, states, ring, sampled, (kept, among))
    event = (0, 1.0, 0.05, "decode", (0, 1), 4, 1500, 2048, 60, 50, None,
             900, None, (3 * 4 * 1505, 3 * 4 * 1505))
    ctx = SimpleNamespace(
        model=mc, slots=128, decode_block=4, timeline=[event], trace=None,
        engine_stats={}, peaks=None, traffic_name="reason-sat")
    assert readers.rows_mean(ctx, 2, False) == 1500
    assert readers.rows_mean(ctx, 3, False) == 900
    assert readers.kept_mean(ctx, False) == 1505
    assert readers.kept_share(ctx) == 1.0
    assert run.read_metric("dsa.kept_pct", ctx) == 100.0
    ctx.timeline = [event[:13] + ((3 * 4 * 1204, 3 * 4 * 1505),)]
    assert run.read_metric("dsa.kept_pct", ctx) == 80.0
    ctx.timeline = [event]
    assert abs(run.read_metric("kv.latent_live_gb.dots3_note", ctx)
               - 1500 * 3 * 1280 / 1e9) < 1e-12
    assert run.read_metric("kv.index_live_gb", ctx) == 1500 * 3 * 256 / 1e9
    ctx.trace = {"ops": {"decode_attention_kept.3 bf16[128,128,512]": 0.3,
                         "index_scores_stacked.1 f32[128,1,4096]": 0.1,
                         "fusion.7 u32[128]": 0.03,
                         "convert_reduce_fusion.80 s32[128]": 0.01,
                         "copy-done.125 s32[128]": 7.0,
                         "fusion.8 pred[128,4097]": 0.02,
                         "fusion.9 f32[128,4096]": 9.0},
                 "modules": {"jit__step_fn": {"count": 25, "seconds": 2.0}}}
    # the score kernel by its name, and no operation by its shape
    assert abs(run.read_metric("dsa.index_ms", ctx) - 1.0) < 1e-9
    assert abs(run.read_metric("mla.sparse_decode_attn_ms", ctx) - 3.0) \
        < 1e-9
    assert run.read_metric("mla.window_decode_attn_ms", ctx) is None
    # the parent's events stop at the thirteenth field
    ctx.timeline = [event[:13]]
    for name in ("dsa.kept_pct", "kv.index_live_gb", "dsa.index_ms",
                 "decode_step_roofline.dots3_note"):
        ctx.model = mc
        assert run.read_metric(name, ctx) is None or name == "dsa.index_ms"
    assert abs(run.read_metric("dsa.index_ms", ctx) - 1.0) < 1e-9
    ctx.model = {"layer_pattern": []}
    for name in ("dsa.kept_pct", "dsa.index_ms", "mla.sparse_decode_attn_ms",
                 "mla.sparse_decode_attn_roofline",
                 "mla.window_decode_attn_roofline",
                 "moe.experts_ms.dots3_note",
                 "moe.tokens_per_expert.dots3_note",
                 "kv.window_live_gb.dots3_note"):
        assert run.read_metric(name, ctx) is None, name


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "dots3_note.py")) as f:
        src = f.read()
    assert "import gofr_tpu" not in src and "from gofr_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "jax.lax.top_k" in src


def test_the_rehearsal_ends_correct_on_the_familys_own_reference(tmp_path):
    """``run.py --rehearse`` on the new cell, in a copy of the benchmark
    whose reference file says when it is called: once a prompt, and one
    of the rehearsal's prompts passes the preset's ``index_topk``."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("gofr_tpu", "examples"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "benchmarks", "references",
                           "dots3_note.py"), "a") as f:
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
    assert max(prompts) > 16      # tiny-dsa-moe's index_topk
    line = json.loads(got.stderr.strip().splitlines()[-1]
                      .removeprefix("[bench] "))
    assert line["correct"] is True and line["failed"] == 0
    assert line["detail"]["probe_hit_equals_miss"] is True
    # the program's counts reached the readers: the selection left rows
    # out (the rehearsal's contexts pass 16), the rings and keys are live
    assert 0 < line["metrics"]["dsa.kept_pct"]["value"] < 100
    assert line["metrics"]["moe.tokens_per_expert.dots3_note"]["value"] > 0
    assert line["metrics"]["kv.latent_live_gb.dots3_note"]["value"] > 0
    assert line["metrics"]["kv.index_live_gb"]["value"] > 0
    assert line["metrics"]["kv.window_live_gb.dots3_note"]["value"] > 0
