"""Time to first token, 90th percentile over the counted requests, in ms.

Measured from each request's due time: the engine's ``ttft_s`` (first
token less the submit call's start) plus how late the submit call was,
so a stall that delays later submits is charged to them.
"""

import numpy as np


def read(run):
    xs = [r["ttft_s"] + r["t_call"] - r["due"] for r in run.counted
          if r["error"] is None and r["ttft_s"] is not None]
    return float(np.percentile(xs, 90) * 1e3) if xs else None
