"""Expert layer: device time of the held experts' ops per decode step, in ms.

The rule: an op inside ``jit_decode_step`` counts when its HLO text
holds the shape of the held experts' weights, stacked over the MoE
layers or one layer's slice: ``[Lm,E,D,Fm]`` or ``[Lm,E,Fm,D]`` (gate
and up, down; ``[26,8,2048,1408]`` and ``[26,8,1408,2048]`` at
Moonlight's cut), or the same without ``Lm``, and it is not a loop or
call (``while``, ``conditional``, ``call``): the layer scan's loop
takes every stacked weight as an operand and holds the whole step.
What is left are the ops that read the experts' weights: the expert
matmuls, with whatever XLA fused into them (two fusions a layer on a
v5e). The router, the shared experts and attention do not match. A
model without held experts reads nothing.
"""

from bench import trace


def shapes(d: dict) -> tuple:
    Lm, E, D, F = d["Lm"], d["E_held"], d["D"], d["Fm"]
    return tuple(f"[{','.join(map(str, s))}]" for s in (
        (Lm, E, D, F), (Lm, E, F, D), (E, D, F), (E, F, D)))


LOOPS = ("while", "conditional", "call")


def is_expert_op(name: str, d: dict) -> bool:
    if trace.hlo_parts(name)[1] in LOOPS:
        return False
    return any(s in name for s in shapes(d))


def read(run):
    if run.trace is None or "E_held" not in run.dims:
        return None
    mods = trace.module_spans(run.trace, "jit_decode_step")
    if not mods:
        return None
    ns = sum(e - s for name, s, e in trace.ops_in(run.trace, mods)
             if is_expert_op(name, run.dims))
    if ns <= 0:
        return None
    return ns / 1e6 / len(mods)
