"""Paged slab: device time per decode step of the ops inside
``jit_decode_step`` that only move slab data, in ms.

The rule, written from a recorded decode step (``bench/tests/data``):
an op counts when it is a plain data move, by its opcode (``copy``,
``copy-start``, ``copy-done``, ``transpose``, ``dynamic-slice``,
``dynamic-update-slice``, ``bitcast``) or, for a fusion, by its name
(``constant_dynamic-slice_fusion.12``), and its output is slab-shaped:
its last four dims are ``[pages, page, kv heads, head dim]``. That
takes the per-layer relayouts of each layer's page slice, the scan's
slicing of the stacked slab and its write back, and the whole-slab
copies after the loop; it leaves out the same moves of the weights'
layer slices and the scatter that writes the new keys and values.
"""

import re

from bench import trace

MOVES = ("copy", "copy-start", "copy-done", "transpose", "dynamic-slice",
         "dynamic-update-slice", "bitcast")
_DIMS = re.compile(r"\[([\d,]*)\]")


def is_slab_move(name: str, slab: tuple) -> bool:
    inst, opcode = trace.hlo_parts(name)
    if not (opcode in MOVES or (opcode == "fusion"
                                and any(m in inst for m in MOVES))):
        return False
    shape = name.split(" = ", 1)[1] if " = " in name else ""
    m = _DIMS.search(shape)
    dims = tuple(int(d) for d in m.group(1).split(",") if d) if m else ()
    return dims[-4:] == tuple(slab)


def read(run):
    if run.trace is None:
        return None
    mods = trace.module_spans(run.trace, "jit_decode_step")
    if not mods:
        return None
    ns = sum(e - s for name, s, e in trace.ops_in(run.trace, mods)
             if is_slab_move(name, run.slab))
    return ns / 1e6 / len(mods)
