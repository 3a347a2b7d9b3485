"""From a profiler trace to numbers: device busy and idle time, time per
compiled program (XLA module), time per device operation, collective
time, and the idle gaps named by what the host was doing.

The reduction works on a plain structure, so that it can be checked on a
small recorded trace (``benchmarks/tests/trace_small.json``):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

``load_xplane`` builds that structure from the ``.xplane.pb`` file the JAX
profiler writes, with nothing but ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the operations XLA:TPU emits for traffic between chips
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)
SYNC_MARK = "bench.sync"


def load_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            # host planes: keep only the line that carries the sync mark
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name == SYNC_MARK]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union_s(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Seconds covered by the union of [start, end) nanosecond intervals,
    and the merged intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def module_name(event_name: str) -> str:
    """'jit__step_fn(1234567)' -> 'jit__step_fn'."""
    return event_name.split("(", 1)[0]


CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """'%fusion.274 = (f32[40,8,4]{2,1,0:T(8,128)S(1)}, ...) fusion(...)' ->
    'fusion.274 f32[40,8,4]': the instruction and its first output's
    shape, without layouts. A plain name passes unchanged."""
    if " = " not in event_name:
        return event_name
    inst, rest = event_name.lstrip("%").split(" = ", 1)
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{inst} {shape.group(1)}" if shape else inst


def device_planes(trace: dict) -> list[dict]:
    return sorted((p for p in trace["planes"]
                   if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce_trace(trace: dict) -> dict:
    """Busy seconds (union of the intervals in which an operation ran,
    averaged over the device planes), the traced window (first start to
    last end over all devices), per-module and per-operation seconds on
    the first device, and its collective seconds."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace has no /device:TPU:<n> plane: "
                         f"{[p['name'] for p in trace['planes']]}")
    busy, spans, merged0 = [], [], []
    for i, plane in enumerate(planes):
        events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        ivals = [(s, s + d) for _, s, d in events if d > 0]
        if not ivals:
            continue
        b, merged = _union_s(ivals)
        busy.append(b)
        spans.append((min(s for s, _ in ivals), max(e for _, e in ivals)))
        if i == 0:
            merged0 = merged
    if not busy:
        raise ValueError("no operation ran on any device in the trace")
    t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    first = planes[0]
    modules: dict[str, dict] = {}
    for name, _, d in _line(first, MODULES_LINE):
        m = modules.setdefault(module_name(name), {"count": 0, "seconds": 0.0})
        m["count"] += 1
        m["seconds"] += d / 1e9
    ops: dict[str, float] = {}
    collective = []
    for name, s, d in _line(first, OPS_LINE):
        name = op_name(name)
        if name.startswith(CONTAINERS):
            continue  # its time is its children's, which are listed
        ops[name] = ops.get(name, 0.0) + d / 1e9
        if COLLECTIVE.search(name):
            collective.append((s, s + d))
    gaps = [(a[1], b[0]) for a, b in zip(merged0, merged0[1:])]
    return {"busy_s": sum(busy) / len(busy), "window_s": (t1 - t0) / 1e9,
            "devices": len(busy),
            "busy0_s": busy[0], "modules": modules, "ops": ops,
            "collective_s": _union_s(collective)[0] if collective else 0.0,
            "gaps_ns": gaps}


def summary(trace: dict, names: int = 8) -> dict:
    """Planes, lines, event counts and a few names: what one looks at by
    hand before trusting the reduction on a new installation."""
    return {p["name"]: {ln["name"]: {
        "events": len(ln["events"]),
        "seconds": sum(e[2] for e in ln["events"]) / 1e9,
        "names": sorted({e[0] for e in ln["events"]})[:names]}
        for ln in p["lines"]} for p in trace["planes"]}


def sync_offset_s(trace: dict, mark_mono_s: float) -> float | None:
    """Seconds to add to a trace timestamp (in seconds) to get the host's
    monotonic clock: the host wrote a ``bench.sync`` annotation at
    ``mark_mono_s``."""
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name == SYNC_MARK:
                    return mark_mono_s - start / 1e9
    return None


def name_gaps(gaps_ns: list, offset_s: float | None,
              host_samples: list[tuple[float, str]], top: int = 10) -> list:
    """Idle seconds by what the host was doing: each gap between device
    operations is split among the host samples (monotonic time, label)
    that fall inside it; a gap with no sample goes to the nearest one."""
    if offset_s is None or not host_samples:
        return []
    import bisect

    times = [t for t, _ in host_samples]
    out: dict[str, float] = {}
    for a, b in gaps_ns:
        a_s, b_s = a / 1e9 + offset_s, b / 1e9 + offset_s
        lo, hi = bisect.bisect_left(times, a_s), bisect.bisect_right(times, b_s)
        if hi > lo:
            share = (b_s - a_s) / (hi - lo)
            for _, label in host_samples[lo:hi]:
                out[label] = out.get(label, 0.0) + share
        else:
            near = [j for j in (lo - 1, lo) if 0 <= j < len(times)]
            label = host_samples[min(
                near, key=lambda j: abs(times[j] - a_s))][1]
            out[label] = out.get(label, 0.0) + (b_s - a_s)
    return [[k, v] for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def top_ops(ops: dict[str, float], top: int = 10) -> list:
    return [[k[:64], v] for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:top]]
