"""Scheduler, in an open-loop cell: tokens decoded per decode step over
the window (live slots at each decode call, over the engine's
``decode_steps`` counter). More tokens per step make each step, and so
the gap between tokens, longer."""


def read(run):
    steps = run.counters["decode_steps"]
    if not steps or not run.decode_calls:
        return None
    return sum(int((a > 0).sum()) for a in run.decode_calls) / steps
