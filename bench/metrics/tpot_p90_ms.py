"""Time per output token, 90th percentile over the counted requests, in ms.

Per request with two or more output tokens: (reply received - first
token) / (tokens - 1), the first token's time being the submit call's
start plus the engine's ``ttft_s``.
"""

import numpy as np


def read(run):
    xs = [(r["t_reply"] - r["t_call"] - r["ttft_s"]) / (len(r["tokens"]) - 1)
          for r in run.counted if r["error"] is None and r["t_reply"]
          and r["ttft_s"] is not None and len(r["tokens"]) >= 2]
    return float(np.percentile(xs, 90) * 1e3) if xs else None
