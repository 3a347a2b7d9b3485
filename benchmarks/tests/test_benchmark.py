"""The yardstick's own tests: python -m pytest benchmarks/tests -q

They need neither a chip nor JAX: the generator's multiset, the reduction
from a trace to busy, idle and per-module times on a small recorded trace,
the bytes a decode step streams, and the shape of BENCHMARK.json and of
the result line.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks import reduce, roofline, traffic  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_two_seeds_give_the_same_multiset_in_another_order(mix):
    params = traffic.load(os.path.join(BENCH, "traffic", mix + ".json"))
    a = traffic.build(params, 7, 45)["requests"]
    b = traffic.build(params, 3_000_000_019, 45)["requests"]
    assert len(a) == len(b)
    for phase in {r["phase"] for r in a}:
        pa = [r for r in a if r["phase"] == phase]
        pb = [r for r in b if r["phase"] == phase]
        assert len(pa) == len(pb)
        for key in ("prompt", "output"):
            assert sorted(r[key] for r in pa) == sorted(r[key] for r in pb)
            assert sum(r[key] for r in pa) == sum(r[key] for r in pb)
        if params["loop"] == "open":
            gaps = lambda rs: sorted(round(y["due"] - x["due"], 9)  # noqa: E731
                                     for x, y in zip(rs, rs[1:]))
            # the first gap is measured from the phase's start
            start = -params["ramp_s"] if phase == "ramp" else 0.0
            ga = sorted(gaps(pa) + [round(pa[0]["due"] - start, 9)])
            gb = sorted(gaps(pb) + [round(pb[0]["due"] - start, 9)])
            assert ga == pytest.approx(gb, abs=1e-6)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert a == traffic.build(params, 7, 45)["requests"]  # same seed, same run


def test_open_loop_window_holds_rate_times_seconds_due_inside_it():
    params = traffic.load(os.path.join(BENCH, "traffic", "chat-rate.json"))
    reqs = traffic.build(params, 1, 45)["requests"]
    window = [r for r in reqs if r["phase"] == "window"]
    assert len(window) == round(params["rate_req_s"] * 45)
    assert all(0.0 < r["due"] <= 45.0 + 1e-9 for r in window)
    ramp = [r for r in reqs if r["phase"] == "ramp"]
    assert all(-params["ramp_s"] < r["due"] <= 1e-9 for r in ramp)
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)


def _chat_rate(**over):
    return dict(traffic.load(os.path.join(BENCH, "traffic",
                                          "chat-rate.json")), **over)


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_strata_keep_the_whole_windows_quantiles(seed):
    """Dealing a phase into strata changes the order and nothing else: the
    multiset is the phase's whole quantiles of lengths and gaps, which the
    whole-window shuffle sent before PR 27, so the offered tokens and rate
    are what they were; two seeds differ in order."""
    params = _chat_rate()
    dealt = traffic.build(params, seed, 50)["requests"]
    other = traffic.build(params, seed + 1, 50)["requests"]
    for phase, start, length in (("ramp", -params["ramp_s"],
                                  params["ramp_s"]), ("window", 0.0, 50.0)):
        a = [r for r in dealt if r["phase"] == phase]
        n = round(params["rate_req_s"] * length)
        for key in ("prompt", "output"):
            assert sorted(r[key] for r in a) == \
                traffic.lognormal_quantiles(params[key + "_tokens"], n)
        dues = [r["due"] for r in a]
        assert sorted(y - x for x, y in zip([start] + dues, dues)) == \
            pytest.approx(traffic.exponential_gaps(n, length), abs=1e-6)
        b = [r for r in other if r["phase"] == phase]
        assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def _within_5_percent(sums):
    return (max(sums) - min(sums)) / (sum(sums) / len(sums)) < 0.05


@pytest.mark.parametrize("rate", [4.0, 5.5, 7.0])
def test_the_deal_gives_every_stratum_the_same_load(rate):
    """Prompt tokens, output tokens and seconds of every stratum agree
    within 5%, also where the window's requests do not divide by the
    block (some strata then hold one more)."""
    params = _chat_rate(rate_req_s=rate)
    n, block = round(rate * 50), params["block"]
    for values in (traffic.lognormal_quantiles(params["prompt_tokens"], n),
                   traffic.lognormal_quantiles(params["output_tokens"], n),
                   traffic.exponential_gaps(n, 50.0)):
        dealt = traffic.strata(values, block)
        assert sorted(v for s in dealt for v in s) == sorted(values)
        assert {len(s) for s in dealt} <= {n // len(dealt),
                                           n // len(dealt) + 1}
        assert _within_5_percent([sum(s) for s in dealt])


def test_the_schedule_is_its_strata_back_to_back():
    """At the cell's own rate every ``block`` consecutive requests of the
    window, on any seed, are one stratum of each list."""
    params = _chat_rate()
    block = params["block"]
    window = [r for r in traffic.build(params, 11, 50)["requests"]
              if r["phase"] == "window"]
    assert len(window) % block == 0
    ends = [0.0] + [r["due"] for r in window[block - 1::block]]
    assert _within_5_percent([b - a for a, b in zip(ends, ends[1:])])
    for key in ("prompt", "output"):
        assert _within_5_percent([sum(r[key] for r in window[i:i + block])
                                  for i in range(0, len(window), block)])


def test_a_block_as_long_as_the_phase_is_one_stratum():
    assert sorted(traffic._dealt([3, 1, 2], 8, 5, "x")) == [1, 2, 3]
    assert traffic.strata([3, 1, 2], 8) == [[3, 2, 1]]
    dealt = traffic.strata([1, 2, 3, 4, 5, 6, 7], 3)
    assert sorted(len(s) for s in dealt) == [3, 4]
    assert sorted(v for s in dealt for v in s) == [1, 2, 3, 4, 5, 6, 7]


def _requests(mix: str) -> tuple[dict, list[dict]]:
    params = traffic.load(os.path.join(BENCH, "traffic", mix + ".json"))
    return params, traffic.build(params, 5, 45)["requests"]


def _cut_short(requests: list[dict], max_seq: int) -> list[dict]:
    """The requests that a cache of ``max_seq`` positions would cut short.
    The engine refuses a prompt over ``max_seq - 1`` (``tpu/generator.py``:
    ``limit = self.max_seq - 1``) and retires a slot once prompt + generated
    reaches ``max_seq - 1``, so a request gets every token it asked for
    while prompt + output < ``max_seq``."""
    return [r for r in requests if r["prompt"] + r["output"] >= max_seq]


def test_lengths_stay_inside_their_clip_and_the_cache():
    """A cell's mix is held to that cell's configuration: every request
    of the list gets its whole output from a cache of the configuration's
    ``model_config.max_seq`` (``env.TPU_MAX_SEQ``, held equal below).
    A traffic file that no cell uses is held to nothing, and fails."""
    bench = _bench()
    files = {c["name"]: cfg for c, cfg in _configs()}
    used = set()
    for cell in bench["workloads"]:
        used.add(cell["traffic"])
        max_seq = files[cell["config"]]["model_config"]["max_seq"]
        params, requests = _requests(cell["traffic"])
        for r in requests:
            assert r["prompt"] <= params["prompt_tokens"]["max"]
            assert r["output"] <= params["output_tokens"]["max"]
        assert not _cut_short(requests, max_seq), cell["name"]
    assert used == set(MIXES)


def test_a_mix_that_passes_its_cells_cache_is_caught():
    """The bound is the cell's own: ``long-mixed`` fits the 16,384
    positions of the configuration it is paired with and would not fit
    its sibling's 4,096; ``batch-sat-14``'s longest request is cut by a
    cache exactly as long as it is and not by one position more."""
    _, long_mixed = _requests("long-mixed")
    assert not _cut_short(long_mixed, 16384)
    cut = _cut_short(long_mixed, 4096)
    assert cut and all(r["prompt"] + r["output"] >= 4096 for r in cut)
    _, small = _requests("batch-sat-14")
    longest = max(r["prompt"] + r["output"] for r in small)
    assert _cut_short(small, longest) and not _cut_short(small, longest + 1)


def test_prompts_are_seeded_and_distinct():
    a = traffic.prompt_ids(9, 3, 64, 32768)
    assert a == traffic.prompt_ids(9, 3, 64, 32768)
    assert a != traffic.prompt_ids(9, 4, 64, 32768)
    assert all(1 <= t < 32768 for t in a)


def test_the_load_generator_never_imports_jax():
    code = ("import sys; sys.argv=['loadgen.py','--help']\n"
            "import runpy\n"
            "try:\n runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit: pass\n"
            "import gofr_tpu.grpcx\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules"
            % os.path.join(BENCH, "loadgen.py"))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   stdout=subprocess.DEVNULL)


def _trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return json.load(f)


def test_reduction_of_the_small_recorded_trace():
    red = reduce.reduce_trace(_trace())
    # device 0: ops cover [0, 4) + [5, 6) + [6, 10) ms = 9 ms of 10;
    # device 1: 10 ms of 10; the mean is what `device.busy_s` reports
    assert red["busy0_s"] == pytest.approx(0.009)
    assert red["busy_s"] == pytest.approx(0.0095)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["devices"] == 2
    assert red["modules"]["jit__step_fn"] == {"count": 2,
                                              "seconds": pytest.approx(0.008)}
    assert red["modules"]["jit__prefill_fn"]["seconds"] == pytest.approx(0.001)
    assert red["ops"]["fusion.1"] == pytest.approx(0.004)
    assert red["collective_s"] == pytest.approx(0.0015)
    assert red["gaps_ns"] == [(4000000.0, 5000000.0)]


def test_idle_gaps_are_named_by_the_host_samples():
    trace = _trace()
    red = reduce.reduce_trace(trace)
    offset = reduce.sync_offset_s(trace, mark_mono_s=100.000001)
    assert offset == pytest.approx(100.0)
    samples = [(100.0030, "generator.py:_decode_tick"),
               (100.0045, "wire.py:_push"), (100.0070, "generator.py:_loop")]
    assert reduce.name_gaps(red["gaps_ns"], offset, samples) == \
        [["wire.py:_push", pytest.approx(0.001)]]


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        reduce.reduce_trace({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_metric_readers_on_the_small_trace():
    sys.path.insert(0, BENCH)
    import run  # benchmarks/run.py

    red = reduce.reduce_trace(_trace())
    red["span"] = (0.0, 1.0)
    ctx = SimpleNamespace(trace=red, decode_block=4, chips=1, t_open=0.0,
                          peaks=roofline.load_peaks("TPU v5 lite"),
                          traffic_name="chat-rate", timeline=[],
                          model=LLAMA3_8B, samples=[
                              {"first": 0.0, "last": 1.0, "prompt": 100,
                               "n": 50},
                              {"first": 0.6, "last": 0.9, "prompt": 7,
                               "n": 9}])
    assert run.read_metric("decode.step_ms", ctx) == pytest.approx(1.0)
    # a name split by traffic mix for its `moves` has the one reader
    assert run.read_metric("decode.step_ms.chat-rate", ctx) == \
        pytest.approx(1.0)
    # at 0.5 s the first request holds 100 + 25 tokens, the second none
    assert run.read_metric("kv.live_gb", ctx) == \
        pytest.approx(125 * (65536 + 2048) / 1e9)
    assert run.read_metric("device.idle_pct", ctx) == pytest.approx(5.0)
    assert run.read_metric("collective.share_pct.tp4", ctx) == \
        pytest.approx(100 * 1.5 / 9)
    ctx.trace = None
    assert run.read_metric("decode.step_ms", ctx) is None


LLAMA3_8B = dict(dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
                 ffn_dim=14336, vocab_size=128256, n_experts=0,
                 tie_embeddings=False)


def test_bytes_per_decode_step_against_the_hand_figure():
    """PERF.md section 5 (PR 21 and before): 12.6 GB per step at batch 64 /
    cache 1,024 for the Llama-3-8B shapes, int8 weights and int8 KV. From
    shapes: 6.98 GB of layer projections + 0.53 GB of output head (with
    their scales) = 7.51 GB, and 64 x 1,024 tokens x 67,584 B = 4.43 GB of
    KV with scales: 11.94 GB. The hand figure is 5.5% above that: it is
    the same sum with the 0.53 GB embedding table, which a step does not
    stream (it gathers 64 rows), and rounding."""
    w = roofline.weight_bytes_per_step(LLAMA3_8B)
    assert w == pytest.approx(7.51e9, rel=0.001)
    assert roofline.kv_bytes_per_token(LLAMA3_8B) == 65536 + 2048
    total = roofline.decode_step_bytes(LLAMA3_8B, 64 * 1024)
    assert total == pytest.approx(11.94e9, rel=0.001)
    assert total == pytest.approx(12.6e9, rel=0.06)
    peaks = roofline.load_peaks("TPU v5 lite")
    assert total / peaks["hbm_bytes_per_s"] == pytest.approx(14.6e-3, rel=0.01)
    assert roofline.decode_step_roofline_pct(
        LLAMA3_8B, 64 * 1024, 30.5e-3, peaks) == pytest.approx(47.8, abs=0.2)


def test_mixtral_streams_every_expert_and_a_chip_a_quarter():
    with open(os.path.join(BENCH, "configs",
                           "mixtral-8x7b-int8-tp4.json")) as f:
        m = json.load(f)["model_config"]
    assert roofline.weight_bytes_per_step(m) == pytest.approx(46.6e9, rel=0.005)


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.load_peaks("TPU v9")


def test_every_name_in_benchmark_json_has_its_file():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
    for w in b["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    sys.path.insert(0, BENCH)
    import run  # benchmarks/run.py

    cells = {w["name"]: w for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        for cell in m.get("workloads", cells):
            assert os.path.isfile(run.metric_file(
                m["name"], cells[cell]["traffic"])), (m["name"], cell)
    cells = set(cells)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        mover = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= \
            set(mover.get("workloads", cells)), m["name"]
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)


def _configs():
    for c in _bench()["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            yield c, json.load(f)


def test_config_files_state_what_any_configuration_states():
    """What holds whatever the model's family."""
    for c, cfg in _configs():
        mc = cfg["model_config"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["reduced"]) == set(cfg["reduced_why"]), c["name"]
        assert mc["max_seq"] == int(cfg["env"]["TPU_MAX_SEQ"])
        assert cfg["chips"] in (1, 4)
        ref = cfg["reference"]
        assert 0 < ref["tolerance_nats"] <= 0.5
        assert ref["statistic"] in ("worst", "median")
        # one prompt past the largest prefill bucket: the chunked path
        assert max(ref["prompt_tokens"]) > 512
        assert max(ref["prompt_tokens"]) + ref["new_tokens"] < mc["max_seq"]
        module = ref.get("module")
        if module is not None:
            assert module.startswith("references/") and \
                os.path.isfile(os.path.join(BENCH, module)), c["name"]


def test_default_family_config_files_state_what_the_model_config_runs():
    """The identities of Mistral's and Mixtral's shape, for the
    configurations on the default reference (``reference.py``). One with a
    ``reference.module`` of its own maps its published keys to its
    ``model_config`` in its own test file (below)."""
    for c, cfg in _configs():
        if "module" in cfg["reference"]:
            continue
        mc = cfg["model_config"]
        assert mc["dim"] == cfg["hidden_size"]
        assert mc["ffn_dim"] == cfg["intermediate_size"]
        assert mc["n_layers"] == cfg["num_hidden_layers"]
        assert mc["n_heads"] == cfg["num_attention_heads"]
        assert mc["n_kv_heads"] == cfg["num_key_value_heads"]
        assert mc["vocab_size"] == cfg["vocab_size"]
        assert mc["dim"] // mc["n_heads"] == cfg["head_dim"]
        assert mc["n_experts"] == cfg.get("num_local_experts", 0)
        assert mc["max_seq"] == cfg["max_position_embeddings"]
        # a dense model is held to its worst position (reference.py)
        assert cfg["reference"]["statistic"] == \
            ("median" if mc["n_experts"] else "worst")


def test_a_configuration_with_its_own_reference_has_its_own_test_file():
    """``tests/test_<config>.py`` (held counts against published counts,
    the deployment's chips a layer): the file-per-name rule the metrics
    have, for a family whose identities this file cannot know."""
    for c, cfg in _configs():
        if "module" in cfg["reference"]:
            assert os.path.isfile(os.path.join(
                HERE, "test_" + c["name"].replace("-", "_").replace(".", "_")
                + ".py")), c["name"]


PINNED = {  # the names each cell reports on the tree PR 27 started from
    "mistral-7b-int8.batch-sat": (
        ["out_tok_s", "setup_s"],
        ["sched.occupancy_pct", "hbm.in_use_gb", "kv.live_gb",
         "decode.step_ms", "decode_step_roofline", "device.idle_pct",
         "setup.compile_s", "window.compiles", "sched.dry_pct",
         "sched.dry_admit_pct", "sched.host_busy_pct", "kv.pool_fill_pct",
         "attn.kv_read_pct"]),
    "mistral-7b-int8.chat-rate": (
        ["tpot_p50_ms", "setup_s"],
        ["gen.late_p99_ms.chat-rate", "client.ttft_p50_ms.chat-rate",
         "client.ttft_p95_ms.chat-rate",
         "transport.ttft_overhead_ms.chat-rate",
         "sched.admit_wait_ms.chat-rate", "decode.step_ms.chat-rate",
         "prefill.ms_per_ktok.chat-rate", "device.idle_pct.chat-rate",
         "setup.compile_s", "window.compiles", "sched.dry_pct.chat-rate",
         "sched.dry_admit_pct.chat-rate", "sched.host_busy_pct.chat-rate",
         "transport.first_write_ms.chat-rate",
         "transport.ingress_ms.chat-rate", "attn.kv_read_pct.chat-rate"]),
    "mixtral-8x7b-int8-tp4.batch-sat": (
        ["out_tok_s", "setup_s"],
        ["sched.occupancy_pct", "hbm.in_use_gb", "kv.live_gb",
         "decode.step_ms", "decode_step_roofline", "collective.share_pct.tp4",
         "device.idle_pct", "setup.compile_s", "window.compiles",
         "sched.dry_pct", "sched.dry_admit_pct", "sched.host_busy_pct",
         "kv.pool_fill_pct", "attn.kv_read_pct"]),
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_a_cell_reports_the_metrics_it_reported(cell):
    """Which metrics a cell reports is ``load_cell``'s rule and nothing
    else. A change to how a cell finds its metrics that drops one is a
    benchmark weakened (the driver's word; PR 26 lost twelve this way)."""
    sys.path.insert(0, BENCH)
    import run  # benchmarks/run.py

    got = run.load_cell(cell)
    end_to_end, per_layer = PINNED[cell]
    assert [m["name"] for m in got.end_to_end] == end_to_end
    # a later PR may append a per-layer metric to a cell that is here (PRs
    # 24 and 25 did) and may not edit this file: nothing lost, in order
    names = [m["name"] for m in got.per_layer]
    assert [n for n in names if n in per_layer] == per_layer


@pytest.mark.parametrize("statistic,ok", [("worst", False), ("median", True)])
def test_the_reference_holds_the_statistic_the_configuration_names(
        statistic, ok):
    from benchmarks import reference  # imports JAX; needs no device

    positions = [{"logprob_err": e, "top1_margin": m, "router_gap": None}
                 for e, m in ((0.02, 0.0), (0.4, 0.3), (0.03, 0.0))]
    got = reference.judge(positions, {"statistic": statistic,
                                      "tolerance_nats": 0.1})
    assert got["ok"] is ok
    assert got["worst"] == {"logprob_err_nats": 0.4, "top1_margin_nats": 0.3}
    assert got["median"] == {"logprob_err_nats": 0.03,
                             "top1_margin_nats": 0.0}
    # the margin is held too, not only the error
    positions[0]["top1_margin"] = positions[2]["top1_margin"] = 0.2
    assert not reference.judge(positions, {"statistic": "median",
                                           "tolerance_nats": 0.1})["ok"]


def test_the_result_line_has_exactly_the_contract_keys():
    sys.path.insert(0, BENCH)
    import run  # benchmarks/run.py

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    memory = [{"peak_bytes_in_use": 5}, {"peak_bytes_in_use": 9}]
    metrics = {"out_tok_s": {"value": 1.5, "unit": "tokens/s"}}
    plain = run.build_result(True, 10, 0, metrics, device, memory, None)
    assert set(plain) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    assert plain["device"] == {**device, "memory_peak_bytes": 9}
    assert device == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    red = reduce.reduce_trace(_trace())
    red["idle_gaps"] = [["wire.py:_push", 0.001]]
    traced = run.build_result(True, 10, 0, metrics, device, memory, red)
    assert set(traced) == set(plain) | {"breakdown"}
    assert set(traced["device"]) == set(plain["device"]) | {"busy_s",
                                                            "window_s"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(traced["breakdown"]["device_ops"]) <= 10
    json.dumps(traced)
