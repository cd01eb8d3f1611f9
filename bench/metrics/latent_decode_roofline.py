"""Kernel ``latent_decode_paged``: its share of the roofline, in %.

The kernel's ops are the device ops inside ``jit_decode_step`` whose HLO
instruction is named after the kernel (its ``pallas_call`` name, so the
instruction reads ``%latent_decode_paged...``). For every traced decode
call, each layer's kernel call needs at least ``max(flops / peak FLOP/s,
bytes / peak bytes/s)`` (``latent_costs.latent_decode_cost`` at the
call's live lengths: 1,152 bytes of latent and 2 * 16 * (576 + 512)
FLOPs per position at Moonlight's widths, plus the queries in and the
outputs out). The share is the least time of the traced decode steps
over the kernel's device time in them. The profiler may keep fewer
decode steps than the harness logs: its event buffer fills and the
device events after that are dropped (in a 51 s window of this model on
a v5e it kept the first 33.5 s, 1,492 of 2,300 steps), so the least time
is taken over the first logged calls, as many as the trace holds decode
steps. Bytes bound it: about 30 FLOPs per byte, far under the chip's
240. A model without the kernel reads nothing.
"""

from bench import latent_costs, trace

NAME = "latent_decode_paged"


def is_kernel(name: str) -> bool:
    return trace.hlo_parts(name)[0].startswith(NAME)


def read(run):
    if run.trace is None or not run.decode_calls or "C" not in run.dims:
        return None
    mods = trace.module_spans(run.trace, "jit_decode_step")
    ns = sum(e - s for name, s, e in trace.ops_in(run.trace, mods)
             if is_kernel(name))
    if ns <= 0:
        return None
    pk = run.peaks
    kept = run.decode_calls[:len(mods)]
    least = 0.0
    for lens in kept:
        f, b = latent_costs.latent_decode_cost(run.dims, lens, rows=len(lens))
        least += run.dims["L"] * max(f / pk["bf16_flops_per_s"],
                                     b / pk["hbm_bytes_per_s"])
    return 100.0 * least / len(kept) / (ns / 1e9 / len(mods))
