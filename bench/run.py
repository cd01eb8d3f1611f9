#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout. ``BENCHMARK.json`` names the cell; its
configuration, traffic, metrics, reference and limits are found by name
under ``bench/`` (``bench/registry.py``). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
per-layer ones), ``device`` and, last, ``checks``: each number that
decided ``correct`` beside its limit, also the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits non-zero.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import BenchError, run_cell
    try:
        res = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
