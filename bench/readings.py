#!/usr/bin/env python3
"""Readings that set a cell's limits: the numbers ``correct`` compares, for
the program over many seeds and for the lower-precision control over a
few, at the cell's own size and load, in one process.

    python3 bench/readings.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13 --control-seeds 11,12

One set-up (engines, every program compiled); then per seed the engines
get that seed's weights, serve that seed's traffic for a short window, and
the run's served tokens and image scores are compared with the reference
(and, for a control seed, the control's). The weights are freed before
each reference runs. Prints one JSON line per seed. The benchmark's runs
never run this.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402
from bench import weights as W  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    import jax

    harness.use_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    runs = {s: harness.schedule(cell, s, args.seconds) for s in seeds}
    every = [it for items, _ in runs.values() for it in items]
    st = harness.set_up(cell, seeds[0], every, runs[seeds[0]][1])
    shapes = {t: jax.eval_shape(lambda p: p, e.params)
              for t, e in st.engines.items()}
    for i, seed in enumerate(seeds):
        if i:
            for tier, eng in st.engines.items():
                eng.params = W.make_params(harness.model_key(seed, tier),
                                           shapes[tier])
        items, images = runs[seed]
        out = harness.drive(cell, st, items, images, args.seconds, False)
        for eng in st.engines.values():
            eng.params = None
        gc.collect()
        line = {"seed": seed,
                "program": harness.served_check(cell, out, seed)}
        if seed in controls:
            line["control"] = harness.served_check(cell, out, seed,
                                                   control="fp8")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
