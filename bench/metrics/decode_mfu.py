"""Decode step: the share of the chip's peak bf16 FLOP/s that the decode
steps' model FLOPs reach, in %. FLOPs are those of the tokens the traced
decode calls produced (``costs.token_flops`` at each live slot's
context); time is the summed device time of the ``jit_decode_step``
modules."""

from bench import costs, trace


def read(run):
    if run.trace is None or not run.decode_calls:
        return None
    mods = trace.module_spans(run.trace, "jit_decode_step")
    if len(mods) != len(run.decode_calls):
        return None
    flops = sum(costs.token_flops(run.dims, int(n))
                for lens in run.decode_calls for n in lens if n > 0)
    secs = sum(e - s for _, s, e in mods) / 1e9
    return 100.0 * flops / (secs * run.peaks["bf16_flops_per_s"])
