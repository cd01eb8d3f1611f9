#!/usr/bin/env python3
"""Many runs of one cell in one process, for the readings behind the cells.

    python3 bench/survey.py --workload NAME --seeds 1,2,3 --seconds S
        [--rates 0.4,0.8] [--trace 0|1] [--control] [--out FILE]
        [--dump DIR]

Not the benchmark's command: the benchmark runs one cell per process
(``bench/run.py``). This drives the same ``run_cell`` once per seed and
rate in a single process, so that set-up and compiles are paid once:

- ``--rates`` sweeps an open-loop mix's rate (the knee sweep): each
  line then carries the admission backlog's slope over the window;
- ``--control`` makes each run the control: the check judges the fp8
  reference's first choices in the served tokens' place, and ``correct``
  has to come out false; the served tokens' own gap is in ``info``.

Each run prints one JSON line and appends it to ``--out``.
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


def slope(points):
    """Least-squares slope of ``(t, n)`` points, in requests per second."""
    import numpy as np

    if len(points) < 2:
        return None
    t, n = np.asarray(points, float).T
    return float(np.polyfit(t, n, 1)[0])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--dump")
    args = ap.parse_args()

    from bench.harness import run_cell

    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for rate in rates:
        for seed in seeds:
            t0 = time.time()
            dump = (None if args.dump is None
                    else f"{args.dump}/{args.workload}-{seed}-{rate}")
            res = run_cell(ROOT, args.workload, seed, args.seconds,
                           bool(args.trace), t0, control=args.control,
                           mix_override=None if rate is None
                           else {"rate_per_s": rate}, dump=dump)
            line = {"workload": args.workload, "seed": seed, "rate": rate,
                    "run_s": time.time() - t0, **res}
            if dump is not None:
                ticks = json.loads(Path(dump, "ticks.json").read_text())
                line["backlog_slope"] = slope(ticks["backlog"])
                line["backlog_end"] = (ticks["backlog"][-1][1]
                                       if ticks["backlog"] else None)
            text = json.dumps(line)
            print(text, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(text + "\n")
    print(f"survey: {time.time() - T_START:.1f} s", flush=True)


if __name__ == "__main__":
    main()
