"""Expert layer: (token, held expert) assignments per decode step, over
all MoE layers: the engine's counter ``held_expert_tokens`` over its
``decode_steps`` in the window. The tokens each held expert serves per
step; more of them spread the experts' weight reads over more tokens.
A model without the counter reads nothing."""


def read(run):
    steps = run.counters.get("decode_steps")
    held = run.counters.get("held_expert_tokens")
    if not steps or held is None:
        return None
    return held / steps
