"""Device: the share of the working ticks' time in which no op ran on the
device, in %. Ticks that found no request are left out: an engine with
nothing to do is not idle for want of the host."""

from bench import trace


def read(run):
    if run.trace is None:
        return None
    wall = sum(t["t1"] - t["t0"] for t in run.ticks if t["worked"])
    if wall <= 0:
        return None
    busy = trace.union_ns((s, e) for _, s, e in run.trace["ops"]) / 1e9
    return 100.0 * (1.0 - busy / wall)
