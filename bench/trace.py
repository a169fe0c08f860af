"""Reduce a JAX profiler trace to what the per-layer metrics read.

The traced window is the span of the harness's ``bench.window``
annotation. Within it:

* device ops: every event on each device plane's ``XLA Ops`` line, by
  instruction name without its number (``fusion``,
  ``decode_attention_paged``), with its count and summed seconds; loops
  that only contain other ops count as busy but not as op time;
* programs: every event on the ``XLA Modules`` line (one jitted program's
  execution), by name;
* busy seconds: the union of the op intervals, averaged over the device
  planes that ran anything;
* idle gaps: the stretches of the window in which no op ran on a device,
  each put to the innermost harness span (``bench.*`` annotation on a host
  thread) that covers its midpoint, else to ``host.other``.
"""
from __future__ import annotations

import glob
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops that only contain others (a scan's loop): busy, but not op time
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_events(path: str) -> dict:
    """``{"devices": {plane: {line: [(name, start_s, dur_s)]}},
    "host": [(name, start_s, dur_s)]}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [(e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9)
                                    for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """``%fusion.123 = bf16[...] fusion(...)`` -> ``fusion``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def reduce_events(ev: dict) -> dict:
    win = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
    if not win:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    lo, hi = win[0]
    ops: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    busy, planes_used, gaps_all = 0.0, 0, []
    for lines in ev["devices"].values():
        spans = []
        for name, s, d in lines.get(OPS_LINE, []):
            t = _clip(s, s + d, lo, hi)
            if t > 0 or lo <= s <= hi:
                spans.append((max(s, lo), min(s + d, hi)))
                name = op_name(name)
                if name not in CONTAINERS:
                    ops[name][0] += 1
                    ops[name][1] += t
        for name, s, d in lines.get(MODULES_LINE, []):
            if lo <= s + d / 2 <= hi:
                modules[name][0] += 1
                modules[name][1] += d
        if not spans:
            continue
        planes_used += 1
        u = union(spans)
        busy += sum(b - a for a, b in u)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        gaps_all.extend((edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i])
    spans = sorted((s, s + d, n) for n, s, d in ev["host"] if n != WINDOW)
    gaps: Dict[str, float] = defaultdict(float)
    active: list = []
    nxt = 0
    for a, b in sorted(gaps_all, key=lambda ab: ab[0] + ab[1]):
        mid = (a + b) / 2
        while nxt < len(spans) and spans[nxt][0] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] >= mid]
        inner = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        gaps[inner[2] if inner else "host.other"] += b - a
    n = max(planes_used, 1)
    return {
        "window_s": hi - lo,
        "busy_s": busy / n,
        "planes": planes_used,
        "ops": {k: [int(c), t / n] for k, (c, t) in ops.items()},
        "modules": {k: [int(c), t / n] for k, (c, t) in modules.items()},
        "gaps": {k: t / n for k, t in gaps.items()},
    }


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    gaps = sorted(red["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v[1]] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
