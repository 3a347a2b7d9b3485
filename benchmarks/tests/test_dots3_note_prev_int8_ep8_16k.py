"""dots3-note-prev-int8-ep8-16k: the sibling configuration
(``dots3-note-prev-int8-ep8``) at 16 slots x 16,384 positions, for the
first cell whose contexts pass 2,048. The identities that hold of both
files are the sibling's own tests, run on this file; what differs (length,
slots, the check's prompts and its limit, the mix, the prompt programs'
reader, the controls of the numerical check) is held here."""

import json
import os
import statistics
import sys
from functools import partial
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import test_dots3_note_prev_int8_ep8 as sib  # noqa: E402
from benchmarks import roofline_dots3_note as rf, traffic  # noqa: E402

NAME = sib.NAME + "-16k"
CELL = NAME + ".long-mixed"
SIB_CELL = sib.CELL


@pytest.fixture
def this_file(monkeypatch):
    """The sibling's tests read ``NAME`` and ``CELL`` of their module."""
    monkeypatch.setattr(sib, "NAME", NAME)
    monkeypatch.setattr(sib, "CELL", CELL)


def _cfg(name=NAME):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("held", [
    sib.test_every_published_width_is_what_the_program_runs,
    sib.test_the_file_holds_every_number_of_the_catalog_row,
    sib.test_the_readers_read_the_programs_counts_and_nothing_without_them,
], ids=lambda f: f.__name__)
def test_the_siblings_identities_hold_of_this_file(this_file, held):
    assert sib._cfg()["model_config"]["name"] == NAME
    held()


def _differing(a, b, path=""):
    if isinstance(a, dict) and isinstance(b, dict):
        for k in sorted(set(a) | set(b)):
            yield from _differing(a.get(k), b.get(k), f"{path}.{k}")
    elif a != b:
        yield path.lstrip(".")


def test_the_file_is_the_siblings_but_for_length_and_slots():
    mine, theirs = _cfg(), _cfg(sib.NAME)
    assert set(_differing(mine, theirs)) == {
        "deployment", "max_position_embeddings",
        "reduced_why.max_position_embeddings", "model_config.name",
        "model_config.max_seq", "env.TPU_MODEL", "env.TPU_SLOTS",
        "env.TPU_MAX_SEQ", "env_why.TPU_SLOTS", "env_why.TPU_MAX_SEQ",
        "reference.prompt_tokens", "reference.tolerance_nats",
        "reference.why", "rehearsal.length_scale"}
    mc = mine["model_config"]
    assert mc["name"] == mine["env"]["TPU_MODEL"] == NAME
    assert mc["max_seq"] == 16384 == mine["max_position_embeddings"]
    assert mc["max_seq"] % 512 == 0          # whole prefill chunks
    assert mine["published"]["max_position_embeddings"] == 524288
    # the check's prompts: the 24-token one, and five at the lengths the
    # mix serves and the ramp reaches, so that five sixths of the compared
    # positions lie past 4,096 rows, where a fault of the chunk walk, the
    # selection or the long-context decode moves the median it is held by
    ref = mine["reference"]
    prompts, new_tokens = ref["prompt_tokens"], ref["new_tokens"]
    assert prompts[0] == theirs["reference"]["prompt_tokens"][0] == 24
    assert len(prompts) == 6 and all(n > 4096 for n in prompts[1:])
    assert all(n > 2 * mc["index_topk"] for n in prompts[1:])
    assert max(prompts) + new_tokens < mc["max_seq"]
    mix = traffic.load(os.path.join(BENCH, "traffic", "long-mixed.json"))
    reqs = traffic.build(mix, 7, 50.0)["requests"]
    served = [r["prompt"] for r in reqs if r["phase"] == "closed"]
    assert all(min(served) < n < max(served) for n in prompts[1:5])
    assert max(r["prompt"] for r in reqs) - 512 < prompts[5] \
        < max(r["prompt"] + r["output"] for r in reqs)
    # a pair shares one padded shape of the reference: four shapes, as the
    # sibling's five prompts and one more had
    from benchmarks import reference
    padded = [-(-(n + new_tokens - 1) // reference.PAD) for n in prompts]
    assert padded[1] == padded[2] and padded[3] == padded[4]
    assert len(set(padded)) == 4
    assert (ref["statistic"], ref["module"], new_tokens) == (
        theirs["reference"]["statistic"], theirs["reference"]["module"],
        theirs["reference"]["new_tokens"])
    assert mine["rehearsal"]["length_scale"] == 128 / mc["max_seq"]
    assert int(mine["rehearsal"]["env"]["TPU_MAX_SEQ"]) == 128


def test_the_byte_count_at_16384_positions():
    """A slot is 3 x 16,384 x (1,280 + 256) B of rows and index keys and
    6 x 512 x 2,304 B of rings: 82.6 MB, 1.32 GB at 16 slots beside the
    sibling's 7.7 GB of weights."""
    cfg = _cfg()
    mc = cfg["model_config"]
    assert rf.kinds(mc) == {"full": 3, "window": 6}
    assert rf.latent_bytes_per_token(mc) + rf.index_bytes_per_token(mc) \
        == 3 * (1280 + 256)
    assert rf.slot_bytes(mc) == 16384 * 4608 + 512 * 6 * 2304
    assert abs(rf.slot_bytes(mc) / 82.6e6 - 1) < 0.005
    slots = int(cfg["env"]["TPU_SLOTS"])
    assert slots == 16 and abs(slots * rf.slot_bytes(mc) / 1.32e9 - 1) < 0.01
    assert rf.share_weight_bytes(mc) == rf.share_weight_bytes(
        _cfg(sib.NAME)["model_config"])
    # a step that keeps index_topk of 7,000 live rows must fetch under a
    # third of the latent rows and every index key
    kept = rf.step_bytes(mc, 30, 5 * 7000, 5 * 2048, 5 * 512)
    whole = rf.step_bytes(mc, 30, 5 * 7000, 5 * 7000, 5 * 512)
    assert whole - kept == 5 * (7000 - 2048) * 3 * 1280


def test_long_mixed_passes_index_topk_and_stays_inside_the_cache():
    cfg = _cfg()
    mc = cfg["model_config"]
    params = traffic.load(os.path.join(BENCH, "traffic", "long-mixed.json"))
    assert params["loop"] == "closed"
    # more callers than slots: a slot that frees always finds a request
    assert params["clients"] > int(cfg["env"]["TPU_SLOTS"])
    for seed in (7, 3_000_000_019):
        reqs = traffic.build(params, seed, 50.0)["requests"]
        # every decode step and every chunk past the fourth leaves rows out
        assert min(r["prompt"] for r in reqs) > mc["index_topk"]
        assert max(r["prompt"] + r["output"] for r in reqs) < mc["max_seq"]
        # a real spread: the window's longest prompt is half as long again
        # as its shortest, and the chunk walk runs past 4,096 rows in all
        served = [r["prompt"] for r in reqs if r["phase"] == "closed"]
        assert max(served) >= 1.4 * min(served) and min(served) > 4096
    assert params["prompt_tokens"]["max"] + params["output_tokens"]["max"] \
        < mc["max_seq"]


def test_the_cell_and_its_metrics_come_after_what_was_there():
    bench = _bench()
    names = [w["name"] for w in bench["workloads"]]
    cell = bench["workloads"][names.index(CELL)]
    assert cell == dict(cell, config=NAME, traffic="long-mixed", chips=1)
    assert names.index(CELL) > names.index(SIB_CELL)
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index(NAME) > configs.index(sib.NAME)
    entry = bench["configs"][configs.index(NAME)]
    theirs = bench["configs"][configs.index(sib.NAME)]
    assert (entry["source"], entry["reduced"]) == (theirs["source"],
                                                   theirs["reduced"])
    per_layer = bench["per_layer"]
    # every list the sibling's cell is in has this cell behind it
    for m in bench["end_to_end"] + per_layer:
        wl = m.get("workloads")
        if wl is not None and SIB_CELL in wl:
            assert wl.index(CELL) > wl.index(SIB_CELL), m["name"]
    family = [m["name"] for m in per_layer
              if m.get("workloads", [None])[0] == SIB_CELL]
    assert len(family) == 13
    assert all(m["workloads"] == [SIB_CELL, CELL] for m in per_layer
               if m["name"] in family)
    # the one of its own: appended after everything that was there
    mine = [m for m in per_layer if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == ["prefill.share_pct.long-mixed"]
    assert all((m["moves"], m["layer"], m["source"]) == (
        "out_tok_s", "compiled programs", "device_trace") for m in mine)
    was_last = [m["name"] for m in per_layer].index(
        "kv.live_gb.granite_hybrid")
    assert all(per_layer.index(m) > was_last for m in mine)
    e2e = [m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])]
    assert e2e == ["out_tok_s", "setup_s"]


# -- the prompt programs' reader -------------------------------------------------

def _traced(modules, busy0=2.4, span=(100.0, 103.0)):
    return SimpleNamespace(
        timeline=[], traffic_name="long-mixed",
        trace={"span": span, "modules": modules, "busy0_s": busy0},
        engine_stats={})


def test_the_prompt_reader_reads_the_chunk_programs_share_of_busy_time():
    import run

    modules = {"jit__chunk_mid": {"count": 5, "seconds": 0.30},
               "jit__chunk_final": {"count": 1, "seconds": 0.05},
               "jit__unknown": {"count": 1, "seconds": 0.01},
               "jit__step_fn": {"count": 30, "seconds": 1.2}}
    assert run.read_metric("prefill.share_pct.long-mixed",
                           _traced(modules)) == pytest.approx(100 * 0.36 / 2.4)


def test_the_prompt_reader_reads_nothing_without_a_trace_or_a_prompt():
    import run

    decode_only = {"jit__step_fn": {"count": 30, "seconds": 1.2}}
    for ctx in (_traced(decode_only),
                SimpleNamespace(timeline=[], traffic_name="long-mixed",
                                trace=None, engine_stats={})):
        assert run.read_metric("prefill.share_pct.long-mixed", ctx) is None


# -- the rehearsal, and the controls of the numerical check ---------------------

def test_the_rehearsal_ends_correct_on_the_familys_own_reference(
        this_file, tmp_path):
    """The sibling's test on this cell: ``long-mixed`` cut to the tiny
    preset's 128 positions (prompts 24 to 96, every one past its
    ``index_topk`` of 16), through ``run.py --rehearse``."""
    sib.test_the_rehearsal_ends_correct_on_the_familys_own_reference(
        tmp_path)


# one thing wrong in the REFERENCE, against the engine as it is: the
# precision below the configuration's for the weights (4 bits of the int8,
# by a second copy of the reference file whose ``_deq`` drops the low bits),
# the selection replaced by the first ``index_topk`` positions, and the
# same replacement for the queries past 4,096 positions ALONE (what a fault
# of the chunk walk, the selection or the decode kernels that only long
# contexts reach would look like: everything under 4,096 rows is right)
CONTROLS = ("4-bit weights", "first index_topk positions",
            "first index_topk positions, queries past 4,096 alone")
SEEDS = (2147489001, 2147489002, 2147489003)
PAST = 4096


def _first_positions_past(true, past):
    import jax.numpy as jnp

    def select(scores, causal, k):
        late = jnp.arange(scores.shape[0])[:, None] >= past
        return jnp.where(late, true.first_positions(scores, causal, k),
                         true.top_positions(scores, causal, k))

    return select


class _ServedOnce:
    """The engine's answer to a prompt, kept: ``reference.compare`` serves
    the prompts anew for every forward it is given, and the controls are to
    be held against the SAME served tokens as the reference as it is."""

    def __init__(self, generator):
        self._generator, self._kept = generator, {}
        self.cfg, self.params = generator.cfg, generator.params

    def generate(self, prompt, **kw):
        key = tuple(prompt)
        if key not in self._kept:
            self._kept[key] = list(self._generator.generate(prompt, **kw))
        return self._kept[key]


def _four_bit(forward_module):
    import jax.numpy as jnp

    whole = forward_module._deq

    def deq(leaf):
        if hasattr(leaf, "scale"):
            leaf = leaf._replace(w=jnp.left_shift(
                jnp.right_shift(leaf.w, 4), 4))
        return whole(leaf)

    forward_module._deq = deq
    return forward_module.forward_logprobs


@pytest.mark.parametrize("size", ["rehearsal", "cell"])
def test_the_controls_through_the_harness_own_comparison(monkeypatch, size):
    """``reference.compare`` on the engine ``run.py`` builds, once with
    the reference as it is and once a control. ``cell`` (a TPU alone:
    ``CONTROL_SEED=<n> chiprun -- python -m pytest <this file> -k
    "controls and cell" -s``, one process a weight seed) is the cell's own
    engine at the published widths and its six prompts, where each control
    has to come out NOT correct by the configuration's own statistic and
    limit; every reading is printed and kept in
    ``bench_out/<cell>/controls-cell-<seed>.json``. ``rehearsal`` (a CPU)
    runs the same code at the tiny preset so that it stays runnable (4,096
    cut as the lengths are, to 32): there a control has to read over the
    engine's own error, and it says nothing of the cell."""
    import jax

    on_chip = jax.default_backend() == "tpu"
    if on_chip != (size == "cell"):
        pytest.skip(f"{size}: needs a {'TPU' if size == 'cell' else 'CPU'}")
    import gofr_tpu.tpu as tpu_pkg
    import run
    from benchmarks import reference
    from gofr_tpu.models import LLAMA_CONFIGS, ModelConfig

    seed = int(os.environ.get("CONTROL_SEED", SEEDS[0]))
    cfg = _cfg()
    small = cfg["rehearsal"] if size == "rehearsal" else {}
    for k, v in {**cfg["env"], **small.get("env", {})}.items():
        monkeypatch.setenv(k, v)
    if size == "cell":      # as run.py: the program has no entry for it
        model = ModelConfig(**cfg["model_config"])
        monkeypatch.setitem(LLAMA_CONFIGS, model.name, model)
    monkeypatch.setattr(tpu_pkg, "random_params", partial(
        tpu_pkg.random_params, seed=seed % (2 ** 31 - 1)))
    spec = dict(cfg["reference"], **small.get("reference", {}))
    path = os.path.join(BENCH, cfg["reference"]["module"])
    true = run.load_file(path, "bench_reference_")
    forwards = {
        "as it is": true.forward_logprobs,
        CONTROLS[1]: partial(true.forward_logprobs,
                             select=true.first_positions),
        CONTROLS[2]: partial(true.forward_logprobs,
                             select=_first_positions_past(
                                 true, int(PAST * small.get("length_scale",
                                                            1)))),
        "4-bit weights": _four_bit(run.load_file(path, "bench_control_"))}
    app = run.load_example_app()
    gen = app.container.tpu.generator
    gen.warmup()
    app.run(block=False)
    served = _ServedOnce(gen)
    try:
        read = {}
        for name in ("as it is",) + CONTROLS:
            got = reference.compare(served, seed, spec, forwards[name])
            by_prompt = {}
            for p in got.pop("positions"):
                by_prompt.setdefault(p["prompt"], []).append(
                    p["logprob_err"])
            got["median_by_prompt"] = {
                n: statistics.median(v) for n, v in by_prompt.items()}
            read[name] = got
            print(f"control {size} seed {seed}: {name}: {got}", flush=True)
    finally:
        app.stop(grace_s=10.0)
    out = os.path.join(REPO, "bench_out", CELL)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"controls-{size}-{seed}.json"), "w") as f:
        json.dump({"seed": seed, "size": size, "read": read}, f, indent=1)
    sound = read["as it is"]
    stat = sound["statistic"]
    assert sound["ok"] and stat == "median"
    held = max(sound[stat].values())
    for name in CONTROLS:
        assert max(read[name][stat].values()) > 3 * held, name
        if size == "cell":
            assert read[name]["ok"] is False, (name, read[name])
