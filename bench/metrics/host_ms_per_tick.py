"""Scheduler: host time per working tick, in ms: the ticks' wall time
(harness span around ``engine.step``) less the device's busy time inside
them, over the working ticks. Every device op of the traced window runs
inside some working tick, so no clock alignment is needed."""

from bench import trace


def read(run):
    if run.trace is None:
        return None
    work = [t for t in run.ticks if t["worked"]]
    if not work:
        return None
    wall = sum(t["t1"] - t["t0"] for t in work)
    busy = trace.union_ns((s, e) for _, s, e in run.trace["ops"]) / 1e9
    return 1e3 * (wall - busy) / len(work)
