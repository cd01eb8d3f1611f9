"""Prefill step: median device time of one ``jit_prefill_step`` module
(one 128-token chunk through every layer), in ms."""

from bench import trace


def read(run):
    if run.trace is None:
        return None
    return trace.median([(e - s) / 1e6 for _, s, e in
                         trace.module_spans(run.trace, "jit_prefill_step")])
