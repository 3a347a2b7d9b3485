"""The seam a new model family comes through: a configuration names its
own float32 reference, the metric readers see the whole model, and a
configuration the program cannot build ends the run at once.

The runs of ``run.py`` here use a throw-away tree (a copy of
``benchmarks/`` with one more configuration, cell and metric, beside links
to the program and the example), so nothing of them is committed under
``configs/``."""

import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

import run  # noqa: E402  (benchmarks/run.py)

MARK = "throw-away family's forward_logprobs called"
REFERENCE_FILE = f'''
import sys
from benchmarks import reference

def forward_logprobs(params, cfg, tokens, rows):
    print({MARK!r}, file=sys.stderr, flush=True)
    return reference.forward_logprobs(params, cfg, tokens, rows)
'''


class StubGenerator:
    """Serves token 3 with log-probability -1.0 at every step."""

    cfg = SimpleNamespace(vocab_size=8)
    params = "the engine's parameter tree"

    def generate(self, prompt, max_new_tokens, logprobs):
        assert logprobs
        return [(3, -1.0)] * max_new_tokens


SPEC = {"prompt_tokens": [5], "new_tokens": 2, "statistic": "worst",
        "tolerance_nats": 0.15}


def test_compare_calls_the_forward_pass_the_configuration_names(tmp_path):
    from benchmarks import reference

    (tmp_path / "family.py").write_text('''
import numpy as np
CALLS = []

def forward_logprobs(params, cfg, tokens, rows):
    CALLS.append((params, len(tokens), list(rows)))
    out = np.full((len(rows), cfg.vocab_size), -5.0, np.float32)
    out[:, 3] = -1.05
    return out, None
''')
    spec = dict(SPEC, module=str(tmp_path / "family.py"))
    forward = run.reference_forward(spec)
    got = reference.compare(StubGenerator(), 7, spec, forward)
    calls = forward.__globals__["CALLS"]
    assert calls == [("the engine's parameter tree", reference.PAD, [4, 5])]
    assert got["ok"] and got["worst"]["logprob_err_nats"] == \
        pytest.approx(0.05, abs=1e-6)
    assert got["worst"]["top1_margin_nats"] == 0.0
    assert all(p["router_gap"] is None for p in got["positions"])


def test_a_configuration_that_names_no_module_gets_the_default_reference():
    from benchmarks import reference

    assert run.reference_forward(SPEC) is reference.forward_logprobs
    default = []
    for name in os.listdir(os.path.join(BENCH, "configs")):
        with open(os.path.join(BENCH, "configs", name)) as f:
            spec = json.load(f)["reference"]
        if "module" not in spec:
            default.append(name)
            assert run.reference_forward(spec) is reference.forward_logprobs
    # the two of the default family name none; a family of its own does
    assert {"mistral-7b-int8.json", "mixtral-8x7b-int8-tp4.json"} \
        <= set(default)


@pytest.fixture
def tree(tmp_path):
    """A checkout with one more configuration (Mistral's, under another
    name, with a reference module of its own), its ``chat-rate`` cell, and
    a per-layer metric that reads a field of the model beyond the eight
    sizes the readers used to get. Returns (root, edit) where ``edit``
    rewrites the configuration's file."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("gofr_tpu", "examples"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, like = "throwaway.chat-rate", "mistral-7b-int8.chat-rate"
    bench["configs"].append({
        "name": "throwaway", "source": bench["configs"][0]["source"],
        "file": "benchmarks/configs/throwaway.json",
        "reduced": bench["configs"][0]["reduced"], "why": "a test's"})
    bench["workloads"].append({"name": cell, "config": "throwaway",
                               "traffic": "chat-rate", "chips": 1,
                               "why": "a test's"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "model.rope_theta", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    bdir = os.path.join(root, "benchmarks")
    os.makedirs(os.path.join(bdir, "references"), exist_ok=True)
    with open(os.path.join(bdir, "references", "throwaway.py"), "w") as f:
        f.write(REFERENCE_FILE)
    with open(os.path.join(bdir, "metrics", "model.rope_theta.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.model['rope_theta'])\n")
    with open(os.path.join(BENCH, "configs", "mistral-7b-int8.json")) as f:
        cfg = json.load(f)
    cfg["reference"]["module"] = "references/throwaway.py"

    def edit(change) -> None:
        change(cfg)
        with open(os.path.join(bdir, "configs", "throwaway.json"), "w") as f:
            json.dump(cfg, f)

    edit(lambda c: None)
    return root, edit


def _run(root, *args, timeout):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", "throwaway.chat-rate", "--seed", "2147483659",
         "--seconds", "4", *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def test_a_model_config_the_program_cannot_build_exits_at_once(tree):
    """Before JAX looks for a device: on a CPU a configuration the program
    CAN build exits 2 (no TPU), this one 1, with nothing on stdout."""
    root, edit = tree
    # a name no family will take: ``kv_lora_rank``, the field this test
    # was written with, is one the program has had since PR 28
    edit(lambda c: c["model_config"].update(a_field_no_family_has=512))
    t0 = time.monotonic()
    got = _run(root, "--trace", "0", timeout=120)
    assert got.returncode == 1, got.stderr[-2000:]
    assert got.stdout == ""
    assert "a_field_no_family_has" in got.stderr
    assert time.monotonic() - t0 < 60
    edit(lambda c: c["model_config"].pop("a_field_no_family_has"))
    got = _run(root, "--trace", "0", timeout=120)
    assert got.returncode == run.EXIT_NO_DEVICE and got.stdout == ""


def test_a_rehearsal_calls_the_configurations_own_reference(tree):
    """``run.py --rehearse`` end to end on the CPU: the throw-away family's
    ``forward_logprobs`` is called once a reference prompt, the run is
    correct, and a reader sees a field of the model beyond the eight."""
    root, _ = tree
    got = _run(root, "--trace", "1", "--rehearse", timeout=600)
    assert got.returncode == run.EXIT_REHEARSAL, got.stderr[-3000:]
    assert got.stdout == ""
    assert got.stderr.count(MARK) == 3
    line = json.loads(got.stderr.strip().splitlines()[-1]
                      .removeprefix("[bench] "))
    assert line["correct"] is True
    # the rehearsal runs the program's `tiny` preset: its rope_theta
    assert line["metrics"]["model.rope_theta"]["value"] == 10000.0
    assert "decode.step_ms.chat-rate" not in line["metrics"]  # no device
    assert "sched.admit_wait_ms.chat-rate" in line["metrics"]
