#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it registers the cell's model configuration
(``benchmarks/configs/<config>.json``), builds the App the way
``examples/tpu-token-streaming`` does (that example's ``main.py`` and
``configs/.env``, with the configuration file's ``env`` on top), warms the
generation programs, holds the engine against the plain float32 reference
(the forward pass the configuration names under ``reference.module``, a
file of its family under ``benchmarks/references/``, or ``reference.py``'s),
and then starts ``benchmarks/loadgen.py`` as a process of its own, which
never imports JAX, to offer the cell's traffic
(``benchmarks/traffic/<traffic>.json``) over gRPC. Every metric named in
BENCHMARK.json is a reader of its own, ``benchmarks/metrics/<name>.py``.

The last line of stdout is the result, and nothing else goes to stdout:
  {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window's last seconds.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.

Not used by the driver: ``--rehearse`` runs the same path on the CPU at the
configuration's ``rehearsal`` size, prints the line to stderr and exits 3.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from functools import partial  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
from benchmarks import reduce, roofline  # noqa: E402  (neither imports JAX)

EXAMPLE = os.path.join(REPO, "examples", "tpu-token-streaming")
TRACE_S = 3.0          # seconds of the window that --trace 1 traces
SAMPLE_HZ = 500.0      # host stack samples per second while tracing
GEN_THREAD = "gofr-tpu-gen"
EXIT_NO_DEVICE, EXIT_REHEARSAL = 2, 3


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


# -- copied from chip_smoke.py (PERF.md, Open questions: one of the two
# should go) -----------------------------------------------------------------

class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache loads
    included) and the cache's hit/miss counts, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        self.log: list[tuple[float, float]] = []  # (monotonic time, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1
            self.log.append((time.monotonic(), seconds))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "programs": self.programs,
                "hits": self.hits, "misses": self.misses}


def parse_prometheus(text: str) -> dict[str, float]:
    """Prometheus text -> {metric name: sum over its label sets}."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            name = series.split("{", 1)[0]
            try:
                out[name] = out.get(name, 0.0) + float(value)
            except ValueError:
                pass
    return out


def load_example_app():
    """The example's module, imported the way ``python main.py`` from its
    directory would build it: ``App()`` reads ./configs/.env, and the
    process environment overrides the file."""
    cwd = os.getcwd()
    os.chdir(EXAMPLE)
    try:
        spec = importlib.util.spec_from_file_location(
            "tpu_token_streaming_main", os.path.join(EXAMPLE, "main.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        os.chdir(cwd)
    return mod.app


# -- the cell ------------------------------------------------------------------

def load_cell(workload: str) -> SimpleNamespace:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, config["file"])) as f:
        cfg = json.load(f)

    def here(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return SimpleNamespace(
        name=workload, chips=int(cell["chips"]), config=cfg,
        traffic=cell["traffic"],
        traffic_path=os.path.join(HERE, "traffic", cell["traffic"] + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if here(m)],
        per_layer=[m for m in bench["per_layer"] if here(m)])


def metric_file(name: str, traffic: str) -> str:
    """``benchmarks/metrics/<name>.py``. A metric that BENCHMARK.json
    splits by traffic mix only so that each part can name its own
    ``moves`` (``decode.step_ms`` and ``decode.step_ms.chat-rate``) is read
    by the one reader: where ``<name>.py`` is missing and the name ends in
    ``.<traffic>``, the file is that of the name without the ending."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.isfile(path) and name.endswith("." + traffic):
        path = os.path.join(HERE, "metrics",
                            name[:-len(traffic) - 1] + ".py")
    return path


def load_file(path: str, prefix: str):
    """The module in the file at ``path``, found by name and not by
    import: what one metric or one model family brings is a file of its
    own, which no file that is here has to name."""
    spec = importlib.util.spec_from_file_location(
        prefix + "".join(c if c.isalnum() else "_"
                         for c in os.path.relpath(path, HERE)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, ctx) -> float | None:
    """The metric's reader's ``read(ctx)``: a number, or None where it
    finds nothing to read (the metric is then left out)."""
    return load_file(metric_file(name, ctx.traffic_name),
                     "bench_metric_").read(ctx)


def reference_forward(spec: dict):
    """The float32 forward pass a configuration's ``reference`` entry
    names: ``forward_logprobs`` of the file ``module`` says, a path under
    ``benchmarks/`` (``references/<family>.py``), or the default family's
    in ``reference.py`` where it names none."""
    from benchmarks import reference

    if "module" not in spec:
        return reference.forward_logprobs
    return load_file(os.path.join(HERE, spec["module"]),
                     "bench_reference_").forward_logprobs


# -- host stack samples, to name the device's idle gaps -----------------------

class HostSampler(threading.Thread):
    """What the generation loop's thread was doing, SAMPLE_HZ times a
    second: the innermost frame inside gofr_tpu, as 'file.py:function',
    with the monotonic time. Uses the program's own stack sampler
    (observe/profiler.py)."""

    def __init__(self):
        super().__init__(name="bench-host-sampler", daemon=True)
        self.samples: list[tuple[float, str]] = []
        self._halt = threading.Event()
        pkg = os.path.join(REPO, "gofr_tpu")
        self._dirs = {"gofr_tpu"} | {d for d in os.listdir(pkg)
                                      if os.path.isdir(os.path.join(pkg, d))}

    def _label(self, stack: str) -> str:
        for frame in reversed(stack.split(";")[1:]):
            fn, _, where = frame.partition(" (")
            parts = where.rstrip(")").rsplit(":", 1)[0].split("/")
            if len(parts) == 2 and parts[0] in self._dirs:
                return f"{parts[1]}:{fn}"
        return "outside gofr_tpu"

    def run(self) -> None:
        from gofr_tpu.observe import profiler

        while not self._halt.is_set():
            now = time.monotonic()
            # skip every other thread: walking and formatting a dozen
            # stacks 500 times a second would itself hold the GIL
            others = {t.ident for t in threading.enumerate()
                      if t.name != GEN_THREAD}
            for stack in profiler.sample_once(others):
                self.samples.append((now, self._label(stack)))
            self._halt.wait(1.0 / SAMPLE_HZ)

    def stop(self) -> None:
        self._halt.set()
        self.join(5.0)


def sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def trace_window(out_dir: str, t_start: float, rehearse: bool) -> dict:
    """Trace TRACE_S seconds from ``t_start`` with the JAX profiler (host
    Python tracing off: it would slow the loop it watches) and reduce the
    trace. Returns the reduction plus the host samples."""
    import jax

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    sleep_until(t_start)
    sampler = HostSampler()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    sampler.start()
    mark = time.monotonic()
    with jax.profiler.TraceAnnotation(reduce.SYNC_MARK):
        time.sleep(0.001)
    sleep_until(t_start + TRACE_S)
    t_stop = time.monotonic()
    sampler.stop()
    jax.profiler.stop_trace()
    try:
        trace = reduce.load_xplane(trace_dir)
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(reduce.summary(trace), f, indent=1)
        red = reduce.reduce_trace(trace)
    except (ValueError, FileNotFoundError) as e:
        if rehearse:
            log(f"rehearsal: no device trace ({e})")
            return {"span": (mark, t_stop)}
        raise
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    red["span"] = (mark, t_stop)
    red["idle_gaps"] = reduce.name_gaps(
        red.pop("gaps_ns"), reduce.sync_offset_s(trace, mark),
        sampler.samples)
    log(f"trace: busy {red['busy_s']:.3f}s of {red['window_s']:.3f}s on "
        f"{red['devices']} device(s); modules "
        f"{ {k: (v['count'], round(v['seconds'], 3)) for k, v in red['modules'].items()} }")
    return red


def build_result(correct: bool, attempted: int, failed: int, metrics: dict,
                 device: dict, memory: list[dict], trace: dict | None) -> dict:
    """The result line's object: exactly the contract's keys. ``device``
    is platform, kind and count as JAX reports them; the peak memory of
    the fullest chip is added here, and from a traced run the device's
    busy seconds, the traced window and the breakdown."""
    device = dict(device, memory_peak_bytes=max(
        (m.get("peak_bytes_in_use", 0) for m in memory), default=0))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and "busy_s" in trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": reduce.top_ops(trace["ops"]),
                               "idle_gaps": trace["idle_gaps"]}
    return result


# -- one run -------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    cell = load_cell(args.workload)
    cfg = cell.config
    rehearsal = cfg["rehearsal"] if args.rehearse else None
    out_dir = os.path.join(REPO, "bench_out", cell.name,
                           f"seed{args.seed}-trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    # stdout carries the result line and nothing else. The framework's
    # logger binds sys.stdout at construction and writes a line per request
    # whatever the level: it gets a file, and stderr stays readable
    result_out = sys.stdout
    sys.stdout = open(os.path.join(out_dir, "server.log"), "w", buffering=1)

    os.environ.update(cfg["env"])
    if rehearsal:
        os.environ.update(rehearsal["env"])
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from gofr_tpu.models import LLAMA_CONFIGS, ModelConfig

    # before JAX touches the chip: a configuration with a field this
    # program lacks (the driver runs a new cell on the parent commit too)
    # ends here, at once, and not in a process that holds the device
    model_config = ModelConfig(**cfg["model_config"])

    if rehearsal:
        jax.config.update("jax_num_cpu_devices", 4)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"cell {cell.name} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace}; device {device}")
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] < cell.chips):
        log(f"needs {cell.chips} TPU chip(s); found {device}. No result.")
        return EXIT_NO_DEVICE

    import gofr_tpu.tpu as tpu_pkg

    from benchmarks import reference

    if not rehearsal:
        # the program has no entry for this model and is not edited
        LLAMA_CONFIGS[model_config.name] = model_config
    # weights from --seed: new_engine_from_config has no setting for it
    tpu_pkg.random_params = partial(tpu_pkg.random_params,
                                    seed=args.seed % (2 ** 31 - 1))
    clock = CompileClock()
    app = load_example_app()
    gen = app.container.tpu.generator
    gen.warmup()
    app.run(block=False)
    proc = None
    try:
        ref = reference.compare(
            gen, args.seed, dict(cfg["reference"],
                                 **(rehearsal or {}).get("reference", {})),
            reference_forward(cfg["reference"]))
        with open(os.path.join(out_dir, "reference.json"), "w") as f:
            json.dump(ref, f)
        del ref["positions"]  # every position's record stays in the file
        log(f"reference: {ref}; set-up so far {time.monotonic() - T0:.1f}s, "
            f"compile {clock.snapshot()}")

        model = gen.cfg
        cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
               "--address", f"127.0.0.1:{app.grpc_port}",
               "--traffic", cell.traffic_path, "--seed", str(args.seed),
               "--seconds", str(args.seconds),
               "--vocab", str(model.vocab_size),
               "--out", os.path.join(out_dir, "samples.jsonl")]
        if rehearsal:
            cmd += ["--length-scale", str(rehearsal["length_scale"]),
                    "--probe-tokens", str(rehearsal["probe_tokens"])]
            for k, v in rehearsal.get("set", {}).items():
                cmd += ["--set", f"{k}={json.dumps(v)}"]
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                                cwd=REPO)
        sched = json.loads(proc.stdout.readline() or "{}")
        if sched.get("event") != "schedule":
            raise RuntimeError(f"the load generator said {sched!r}")
        t_open, t_close = sched["t_open"], sched["t_close"]
        setup_s = t_open - T0

        sleep_until(t_open)
        compile_open = clock.snapshot()
        prom_open = parse_prometheus(app.container.metrics.render_prometheus())
        trace = None
        if args.trace:
            trace = trace_window(out_dir, t_close - TRACE_S - 0.5,
                                 bool(rehearsal))
        sleep_until(t_close)
        compile_close = clock.snapshot()
        prom_close = parse_prometheus(
            app.container.metrics.render_prometheus())
        memory = [d.memory_stats() or {} for d in jax.local_devices()]
        engine_stats = gen.stats()
        rest = proc.stdout.read()
        if proc.wait(timeout=240) != 0:
            raise RuntimeError(f"the load generator exited {proc.returncode}")
        done = json.loads(rest.strip().splitlines()[-1])
        timeline = [e for e in app.container.observe.timeline.events()
                    if t_open <= e[1] < t_close]
        if args.trace:  # beside the profiler's trace, for whoever reads it
            with open(os.path.join(out_dir, "timeline.json"), "w") as f:
                json.dump({"t_open": t_open, "events": timeline}, f,
                          default=str)
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        app.stop(grace_s=10.0)

    with open(os.path.join(out_dir, "samples.jsonl")) as f:
        samples = [json.loads(line) for line in f]
    seconds = t_close - t_open
    if done["params"]["loop"] == "open":
        window = [s for s in samples if s["phase"] == "window"]
    else:
        window = [s for s in samples if 0.0 <= s["sent"] < seconds]
    bad = [s for s in window if s.get("error") or s.get("bad")
           or (s["n"] != s["want"] and not s.get("cut"))]
    probes = done["probes"]
    # before the ramp the probe ran twice, a prefix-pool miss and then a
    # hit; after the drain it takes one of those two paths again and must
    # return that path's tokens (the two paths differ in rounding, and on
    # random weights a near-tie may fall the other way: PERF.md, Findings)
    probes_ok = (all(len(p) == done["probe_new"] for p in probes)
                 and probes[2] in probes[:2])
    correct = bool(ref["ok"] and not bad and probes_ok and window
                   and not done["exhausted"])
    if not correct:
        log(f"NOT correct: reference {ref}, {len(bad)} bad of {len(window)} "
            f"(first: {bad[:2]}), probes {probes}, list exhausted "
            f"{done['exhausted']}")

    ctx = SimpleNamespace(
        cell=cell.name, traffic_name=cell.traffic, chips=cell.chips,
        seconds=seconds, setup_s=setup_s,
        t_open=t_open, t_close=t_close, loop=done["params"]["loop"],
        samples=samples, window=window, traffic=done["params"],
        timeline=timeline, trace=trace, compile_open=compile_open,
        compile_close=compile_close, prom_open=prom_open,
        prom_close=prom_close, memory=memory, engine_stats=engine_stats,
        model=dataclasses.asdict(model),
        slots=gen.n_slots,
        decode_block=app.config.get_int("TPU_DECODE_BLOCK", 4),
        peaks=None if rehearsal else roofline.load_peaks(device["kind"]))
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = build_result(correct, len(window), len(bad), metrics, device,
                          memory, trace if args.trace else None)
    result["detail"] = {
        "reference": ref, "setup_compile": compile_open, "seed": args.seed,
        "out": out_dir, "probe_hit_equals_miss": probes[0] == probes[1],
        "window_compiles": [round(sec, 4) for t, sec in clock.log
                            if t_open <= t < t_close]}
    line = json.dumps(result)
    # every number compared, beside its limit, as the last lines of stderr
    held = ref[ref["statistic"]]
    log(f"compared: {ref['statistic']} |logprob - reference| "
        f"{held['logprob_err_nats']:.6f} and top-1 margin "
        f"{held['top1_margin_nats']:.6f} nats (limit "
        f"{ref['tolerance_nats']}); streams not as asked {len(bad)} of "
        f"{len(window)} (limit 0); probe after the drain equals one before "
        f"it: {probes_ok}; list exhausted: {done['exhausted']} -> correct "
        f"{correct}")
    if rehearsal:
        log("rehearsal on", result["device"],
            "- not a measurement, no result line:")
        log(line)
        return EXIT_REHEARSAL
    print(line, file=result_out, flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stderr.flush()
    # framework and runtime threads must not keep a finished run alive
    os._exit(code)
