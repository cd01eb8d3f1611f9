"""What decides ``correct``: the served tokens against the plain reference.

After the window has closed and the program's state is freed, a sample
of the counted requests that were answered, drawn from the seed and
always holding the longest of them, is run through the reference once
each, over the prompt and the served tokens (teacher-forced). At each
position where the engine produced a token, the number read is how far
that token's reference logit lies below the reference's largest logit.
Greedy decoding serves a largest logit, so a sound engine reads bf16
rounding noise; a wrong token, state or position reads far more. The
widest such gap over the sample is held to the cell's limit
(``limits/<workload>.json``). A control run puts, at each of those
positions, the token that the fp8 reference puts first in the served
token's place: the same gap of that token is then the number judged, and
has to fail its limit (the served tokens' own gap goes to ``info``).

The delivery checks are exact (limit 0): every counted request answered
once, under its own id, with as many tokens as it asked for, each in
the vocabulary; and no decode compile inside the window.
"""

from __future__ import annotations

from typing import List

import numpy as np

from . import traffic

#: the sample: the longest answered request, then others drawn from the
#: seed until this many served tokens are compared (or all requests are)
SAMPLE_TOKENS = 400


def sample(records: List[dict], seed: int) -> List[dict]:
    ok = [r for r in records if r["counted"] and r["t_reply"] is not None
          and r["error"] is None and r["reply_id"] == r["rid"]
          and len(r["tokens"]) == r["output_len"]]
    if not ok:
        return []
    ok.sort(key=lambda r: r["index"])
    longest = max(ok, key=lambda r: (r["prompt_len"] + r["output_len"],
                                     r["index"]))
    rest = [r for r in ok if r is not longest]
    order = np.random.default_rng([int(seed) % 2**64, 3]).permutation(
        len(rest))
    out, n = [longest], longest["output_len"]
    for i in order:
        if n >= SAMPLE_TOKENS:
            break
        out.append(rest[i])
        n += rest[i]["output_len"]
    return out


def logit_gaps(ref, weights, cfg: dict, mix: dict, seed: int,
               picked: List[dict], control: bool = False) -> dict:
    """Widest gap over the sample of the served tokens; with ``control``,
    of the fp8 reference's first choices in their place, the served
    tokens' own gap then under ``served_logit_gap``."""
    import jax.numpy as jnp

    T = mix["prompt"]["max"] + mix["output"]["max"]
    served, ctrl, n_tok = 0.0, 0.0, 0
    for r in picked:
        prompt = traffic.prompt_tokens(seed, r["index"], r["prompt_len"],
                                       cfg["vocab_size"])
        out = r["tokens"]
        p, n = len(prompt), len(out)
        seq = np.zeros(T, np.int32)
        seq[:p + n - 1] = prompt + out[:-1]
        pos = np.arange(p - 1, p - 1 + n)
        rows = np.zeros((2 if control else 1, T), np.int32)
        rows[0, pos] = out
        tokens = jnp.asarray(seq)
        if control:
            rows[1] = np.asarray(ref.control_argmax(weights, cfg, tokens))
        g = np.asarray(ref.gaps(weights, cfg, tokens, jnp.asarray(rows)))
        served = max(served, float(g[0, pos].max()))
        if control:
            ctrl = max(ctrl, float(g[1, pos].max()))
        n_tok += n
    if not picked:
        served = ctrl = float("inf")       # nothing compared is no pass
    res = {"max_logit_gap": served, "tokens_compared": n_tok,
           "requests_compared": len(picked)}
    if control:
        res.update(max_logit_gap=ctrl, served_logit_gap=served)
    return res


def delivery(records: List[dict], vocab: int, duplicates: int) -> dict:
    counted = [r for r in records if r["counted"]]
    answered = [r for r in counted if r["t_reply"] is not None]
    wrong = sum(1 for r in answered
                if r["reply_id"] != r["rid"]
                or (r["error"] is None
                    and (len(r["tokens"]) != r["output_len"]
                         or not all(0 <= t < vocab for t in r["tokens"]))))
    missing = sum(1 for r in counted if r["error"] == "no reply")
    return {"wrong_replies": wrong, "missing_replies": missing,
            "duplicate_replies": duplicates}


def judge(values: dict, limits: dict) -> tuple:
    """``(correct, checks)``: each compared number beside its limit."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return bool(correct), checks
