"""Kernel ``flash_decode_paged``: its share of the roofline, in %.

For every traced decode call, each layer's kernel call needs at least
``max(flops / peak FLOP/s, bytes / peak bytes/s)`` (``costs.
decode_attn_cost`` at the call's live lengths: the K and V of the live
positions, q in and the output out). The share is the sum of those
least times over the kernel's summed device time. At these sizes the
bytes bound it: the kernel does 4 * H * hd FLOPs per position read,
about 1 FLOP per byte, far under the chip's 240.
"""

from bench import costs, trace


def is_kernel(name: str) -> bool:
    """The paged decode kernel: the Pallas call (``tpu_custom_call``)
    inside the decode step, whose body is ``_decode_kernel``."""
    return "tpu_custom_call" in name or "_decode_kernel" in name


def read(run):
    if run.trace is None or not run.decode_calls:
        return None
    mods = trace.module_spans(run.trace, "jit_decode_step")
    if len(mods) != len(run.decode_calls):
        return None
    ns = sum(e - s for name, s, e in trace.ops_in(run.trace, mods)
             if is_kernel(name))
    if ns <= 0:
        return None
    pk = run.peaks
    least = 0.0
    for lens in run.decode_calls:
        f, b = costs.decode_attn_cost(run.dims, lens, rows=len(lens))
        least += run.dims["L"] * max(f / pk["bf16_flops_per_s"],
                                     b / pk["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
