#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator JAX finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit.
Exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.run(harness.parse()))
