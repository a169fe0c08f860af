#!/usr/bin/env python3
"""Find a cell's knee: the same cell driven at several fixed rates in one
process (one set-up), one window per rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,3,4,6

Prints one JSON line per rate: requests due and finished, time-to-first-
token and latency tails, the latency tail of the window's second half
against its first (a queue that grows all through the window shows
there), and output tokens/s. The benchmark's cells carry the rate found
here as a number in their traffic file; no run searches for it.
"""
import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402
from bench.stats import percentile_rank  # noqa: E402


def summary(out, rate: float, seconds: float, tiers) -> dict:
    win = [r for r in out.rows if r.in_window]
    half = out.w0 + seconds / 2
    lat = [r.latency_s if r.done else math.inf for r in win]
    first = [v for r, v in zip(win, lat) if r.due < half]
    second = [v for r, v in zip(win, lat) if r.due >= half]
    toks = sum(out.c1[t]["decode_tokens"] - out.c0[t]["decode_tokens"]
               for t in tiers)
    return {
        "rate_rps": rate, "due": len(win),
        "finished": sum(r.done for r in win),
        "ttft_p50_s": percentile_rank(
            [r.ttft_s if r.done else math.inf for r in win], 50),
        "ttft_p95_s": percentile_rank(
            [r.ttft_s if r.done else math.inf for r in win], 95),
        "latency_p95_s": percentile_rank(lat, 95),
        "latency_p95_first_half_s": percentile_rank(first, 95),
        "latency_p95_second_half_s": percentile_rank(second, 95),
        "output_tok_per_s": toks / (out.c1["t"] - out.c0["t"]),
        "cloud_share": (sum(r.tier == "cloud" for r in win if r.done)
                        / max(1, sum(r.done for r in win))),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    import jax

    harness.use_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    rates = [float(r) for r in args.rates.split(",")]
    runs = [harness.schedule(cell, args.seed + i, args.seconds, rate)
            for i, rate in enumerate(rates)]
    every = [it for items, _ in runs for it in items]
    st = harness.set_up(cell, args.seed, every, runs[0][1])
    for rate, (items, images) in zip(rates, runs):
        out = harness.drive(cell, st, items, images, args.seconds, False)
        print(json.dumps(summary(out, rate, args.seconds,
                                 list(cell.conf["tiers"]))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
