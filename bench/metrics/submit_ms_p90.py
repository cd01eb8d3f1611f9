"""Client and KV queue: the generator's clock around ``ServeClient.submit``
(one ``blpop_rpush`` on the bounded queue), 90th percentile over the
counted requests, in ms."""

import numpy as np


def read(run):
    xs = [r["t_ret"] - r["t_call"] for r in run.counted
          if r["t_ret"] is not None]
    return float(np.percentile(xs, 90) * 1e3) if xs else None
