#!/usr/bin/env python3
"""The program's own host spans (``repro.obs``) over a cell's window.

    python3 bench/spans.py --workload <cell> --seeds <a,b,c> \\
        [--traced-seed <n>] [--seconds <s>] [--out <file.jsonl>]

The harness's run record carries no program spans, so no metric reads
them yet. This script runs the harness's own set-up and window and turns
the spans on from the window's start to its end (where the harness
reads its counters). It prints one ``RESULT``
JSON line per window, then one ``SUMMARY`` line.

* The cell is set up once, every shape of every seed warmed. Each seed
  then gets two windows, one with spans off and one with them on, in
  alternating order from seed to seed, the profiler off. Each window is
  the cell's own: ramp, window and drain. After the drain the system is
  run until every request has resolved (at most ``--settle`` seconds), so
  the next window starts from an empty system, as a run of the cell does.
* ``--traced-seed`` adds one window with spans on under the harness's
  traced drive. Its trace is read with the host spans of both families,
  ``bench.*`` and ``moa.*``, so each idle gap goes to the innermost of
  either.

Each line has the cell's end-to-end metrics by their own readers, and
the three quantities below. ``SUMMARY`` gives, per end-to-end metric,
each seed's spans-on reading over its spans-off one.

* ``queue_wait_p95_ms`` (:func:`queue_wait_p95_ms`): p95 over every
  window request of admission less first enqueue; one never admitted
  ranks last and, if the tail lands on it, reads as the time from its
  enqueue (else its due time) to the end of the drain.
* ``host_ms_per_block`` (:func:`host_ms_per_block`): the window less the
  profiler's stretch, less the time in ``*.read`` spans and in
  ``moa.runtime.doze``, over the fused blocks begun in it.
* ``moa_idle_share``: the share of a traced window's idle seconds put to
  a ``moa.*`` span.

``--shrink`` runs the cell at the CPU tests' sizes on any device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness, trace  # noqa: E402
from bench.stats import percentile_rank  # noqa: E402

PROGRAM = "moa."
WAITS = ("moa.runtime.doze",)  # the host sleeps; ``*.read``: it waits
# read with spans on only, or not end-to-end: left out of ``on_cost``
WINDOW_ONLY = ("queue_wait_p95_ms", "host_ms_per_block")


def host_events(path: str, prefix: str = PROGRAM) -> list:
    """``[(name, start_s, dur_s)]`` of the host events named ``prefix*``
    in one ``.xplane.pb``, on the clock of ``trace.load_events``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events if e.name.startswith(prefix))
    return out


def load_events(path: str, _load=trace.load_events) -> dict:
    """``trace.load_events`` with the program's host spans beside the
    harness's."""
    ev = _load(path)
    ev["host"] = ev["host"] + host_events(path)
    return ev


def moa_idle_share(gaps: Dict[str, float]) -> Optional[float]:
    idle = sum(gaps.values())
    if idle <= 0:
        return None
    return sum(v for k, v in gaps.items() if k.startswith(PROGRAM)) / idle


def pieces(w0: float, w1: float, trace_t: Tuple[float, float]
           ) -> List[Tuple[float, float]]:
    """The window ``[w0, w1)`` less the profiler's stretch, which slows
    the host."""
    a, b = trace_t
    if b <= a:
        return [(w0, w1)]
    return [(w0, max(w0, a)), (min(w1, b), w1)]


def _overlap(t0: float, t1: float, pcs) -> float:
    return sum(max(0.0, min(t1, b) - max(t0, a)) for a, b in pcs)


def host_ms_per_block(spans: Sequence, pcs) -> Optional[float]:
    """Host milliseconds per fused block: the pieces' time less the time
    the host waited on the device or slept, over the blocks begun in
    them. None without a ``moa.engine.block`` span there (spans off, or a
    program without them)."""
    blocks = sum(1 for s in spans if s.name == "moa.engine.block"
                 and any(a <= s.t0 < b for a, b in pcs))
    if not blocks:
        return None
    waited = sum(_overlap(s.t0, s.t1, pcs) for s in spans
                 if s.name.endswith(".read") or s.name in WAITS)
    total = sum(b - a for a, b in pcs)
    return (total - waited) / blocks * 1e3


def queue_wait_p95_ms(rows: Sequence, records: dict, t_end: float
                      ) -> Optional[float]:
    """p95 over the rows of admission less first enqueue, in ms; a row
    never admitted ranks last and, if the tail lands on it, reads as its
    wait by ``t_end``. None where no record can tell its queue wait (a
    program whose records carry no times)."""
    vals, ends = [], []
    for r in rows:
        rec = records.get(r.rid)
        queue_s = getattr(rec, "queue_s", None)
        if queue_s is None:
            continue
        q = queue_s()
        if q is None:
            vals.append(math.inf)
            t_enq = rec.time_of("enqueue")
            ends.append(t_end - (r.due if t_enq is None else t_enq))
        else:
            vals.append(q)
    if not vals:
        return None
    v = percentile_rank(vals, 95)
    return (max(ends) if math.isinf(v) else v) * 1e3


def span_seconds(spans: Sequence, pcs) -> Dict[str, list]:
    """``{name: [count, total_s, self_s]}`` over the pieces: the spans
    begun in them, and the time of every span inside them; most self time
    first."""
    rows: Dict[str, list] = {}
    for s in spans:
        d = _overlap(s.t0, s.t1, pcs)
        begun = any(a <= s.t0 < b for a, b in pcs)
        if d <= 0 and not begun:
            continue
        r = rows.setdefault(s.name, [0, 0.0, 0.0])
        r[0] += begun
        r[1] += d
        r[2] += d
    for s in spans:
        if s.parent in rows:
            rows[s.parent][2] -= _overlap(s.t0, s.t1, pcs)
    return dict(sorted(rows.items(), key=lambda kv: -kv[1][2]))


def on_cost(results: List[dict]) -> Dict[str, dict]:
    """Per end-to-end metric, each seed's spans-on reading over its
    spans-off one, and their median."""
    by = {}
    for r in results:
        if not r["traced"]:
            by.setdefault(r["seed"], {})[r["spans"]] = r["metrics"]
    out: Dict[str, dict] = {}
    for seed, m in sorted(by.items()):
        if "on" not in m or "off" not in m:
            continue
        for name, off in m["off"].items():
            on = m["on"].get(name)
            if name in WINDOW_ONLY or on is None or not off:
                continue
            out.setdefault(name, {"ratios": []})["ratios"].append(on / off)
    for v in out.values():
        v["median"] = statistics.median(v["ratios"])
    return out


def use_cache(jax) -> str:
    """The machine's compile cache where it names one, else the
    harness's."""
    cdir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    harness.use_cache(jax)
    if cdir:
        jax.config.update("jax_compilation_cache_dir", cdir)
    return cdir or str(harness.CACHE_DIR)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated: two windows per seed")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--traced-seed", type=int, default=None)
    ap.add_argument("--settle", type=float, default=300.0,
                    help="most seconds to run on after a drain until "
                         "every request has resolved")
    ap.add_argument("--out", default=None, help="also append lines here")
    ap.add_argument("--shrink", action="store_true",
                    help="the CPU tests' sizes, on any device")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from repro import obs

    cell = harness.load_cell(args.workload)
    seconds = args.seconds or json.loads(
        (harness.ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    if args.shrink:
        harness.shrink(cell)
    import jax

    cdir = use_cache(jax)
    dev = jax.devices()[0]
    harness.log(f"device: {dev.platform} {dev.device_kind}, cache {cdir}")
    if dev.platform != "tpu" and not args.shrink:
        harness.log("no TPU (--shrink runs at CPU sizes)")
        return 2
    peak = (harness.peak_table(dev.device_kind) if dev.platform == "tpu"
            else {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.traced_seed is not None and args.traced_seed not in seeds:
        seeds_all = seeds + [args.traced_seed]
    else:
        seeds_all = seeds
    plan = {s: harness.schedule(cell, s, seconds) for s in seeds_all}
    t0 = time.monotonic()
    st = harness.set_up(cell, seeds[0],
                        [it for s in seeds_all for it in plan[s][0]],
                        [im for s in seeds_all for im in plan[s][1]])
    harness.log(f"set-up {time.monotonic() - t0:.1f} s")
    rt = st.server.runtime
    models = {t: s["model"] for t, s in cell.conf["tiers"].items()}
    sink = open(args.out, "a") if args.out else None
    results: List[dict] = []

    def emit(tag: str, res: dict) -> None:
        line = json.dumps(res)
        print(f"{tag} {line}", flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    counters = harness.counters

    def window(seed: int, on: bool, traced: bool) -> None:
        items, images = plan[seed]
        ends = []

        def switch(engines, server):
            # the harness reads its counters at the window's two ends
            ends.append(counters(engines, server))
            if on:
                (obs.enable if len(ends) == 1 else obs.disable)()
            return ends[-1]

        obs.take()
        harness.counters = switch
        try:
            out = harness.drive(cell, st, items, images, seconds, traced)
        finally:
            harness.counters = counters
            obs.disable()
        spans = obs.take()
        t_settle = time.monotonic()
        resolved = {r.rid for r in st.server.run(timeout_s=args.settle)}
        settled = all(r.rid in resolved for r in out.rows)
        settle_s = time.monotonic() - t_settle
        win = [r for r in out.rows if r.in_window]
        rec = SimpleNamespace(cell=cell, out=out, rows=win, peak=peak,
                              seconds=seconds, models=models)
        res = {"workload": args.workload, "seed": seed,
               "spans": "on" if on else "off", "traced": traced,
               "attempted": len(win), "finished": sum(r.done for r in win),
               "failed": sum(r.failed for r in win),
               "window_compiles": out.window_compiles,
               "settle_s": settle_s, "settled": settled, "metrics": {}}
        for m in cell.end_to_end + (cell.per_layer if traced else []):
            if m["name"] != "setup_s":
                v = harness.metric_reader(m["name"])(rec)
                if v is not None:
                    res["metrics"][m["name"]] = v
        q = queue_wait_p95_ms(win, rt.records, out.t_end)
        if q is not None:
            res["metrics"]["queue_wait_p95_ms"] = q
        pcs = pieces(out.w0, out.w1, out.trace_t)
        h = host_ms_per_block(spans, pcs)
        if h is not None:
            res["metrics"]["host_ms_per_block"] = h
        if spans:
            res["spans_s"] = span_seconds(spans, pcs)
        if traced and out.trace is not None:
            tr = out.trace
            res["busy_s"], res["trace_window_s"] = tr["busy_s"], \
                tr["window_s"]
            res["moa_idle_share"] = moa_idle_share(tr["gaps"])
            res["idle_gaps"] = dict(sorted(tr["gaps"].items(),
                                           key=lambda kv: -kv[1]))
            res["spans_s_traced"] = span_seconds(spans, [out.trace_t])
        results.append(res)
        emit("RESULT", res)

    load = trace.load_events
    trace.load_events = load_events  # the traced drive reads both families
    try:
        for i, s in enumerate(seeds):
            for on in ((False, True) if i % 2 == 0 else (True, False)):
                window(s, on, False)
        if args.traced_seed is not None:
            window(args.traced_seed, True, True)
    finally:
        trace.load_events = load
    emit("SUMMARY", {"workload": args.workload, "on_over_off":
                     on_cost(results)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
