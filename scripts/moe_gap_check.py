#!/usr/bin/env python3
"""Where Moonlight's logit gap against the f32 reference comes from.

    python scripts/moe_gap_check.py [--seeds 1,2] [--tokens 2048]
        [--layers N] [--vocab V] [--out gaps.json]

From the root of a checkout, on a machine with one TPU (or on the CPU
with ``--layers``/``--vocab`` cut so that it fits). It builds the
benchmark's Moonlight-16B-A3B weights from each seed
(``bench/families/deepseek_v3.py``, 8 of 64 experts held) and a random
token sequence, and reads each position's next-token choice under five
computations, each judged by the f32 reference
(``bench/reference/deepseek_v3.py``) as ``bench/check.py`` judges a
served token: how far below the reference's largest logit its logit
lies.

- ``program``: the program's own full-sequence forward in bf16;
- ``ref_bf16``: the reference with every activation rounded to bf16
  after each matrix product, norm and residual add (the program's
  dtype, none of its code);
- ``ref_bf16_pinned``: the same, with each MoE layer's expert choice
  taken from the f32 reference instead of made from its own scores;
- ``ref_bf16_routes_f32``: the same rounding, the router's input and
  scores kept in f32 (the choice made from unrounded activations);
- ``fp8``: the benchmark's fp8 control (``control_argmax``).

Per computation: the widest gap (what ``check.py`` holds to the
limit), its median, mean and 90th percentile, and the share of
positions whose token is not the reference's first choice. For the
bf16 references also the share of (token, MoE layer) pairs whose chosen
set of experts differs from the f32 reference's. If routing flips set
the gap, ``ref_bf16`` reads like ``program`` and ``ref_bf16_pinned``
reads like a dense model.

The last line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

CONFIG = ROOT / "bench" / "configs" / "moonlight-16b-a3b.json"


def _stats(g, same):
    import numpy as np

    g = np.asarray(g, np.float64)
    return {"max": float(g.max()), "median": float(np.median(g)),
            "mean": float(g.mean()), "p90": float(np.percentile(g, 90)),
            "not_argmax": float(1.0 - np.mean(same))}


def _variants(ref, c):
    """``hidden(w, tokens, rnd_bf16, routes, f32_router)`` → (final
    normed hidden [T, D] f32, expert choices [Lm, T, k])."""
    import jax
    import jax.numpy as jnp

    def rounder(on):
        if not on:
            return lambda a: a
        return lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def layer(x, w, ffn, pinned, rnd, f32_router):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        n1 = rnd(ref._rmsnorm(x, w["ln1"], c["eps"]))
        h = rnd(x + rnd(ref._attention(n1, w, c, False)))
        n2_f = ref._rmsnorm(h, w["ln2"], c["eps"])
        n2 = rnd(n2_f)
        if ffn == "dense":
            y = ref._swiglu(False, n2, w["w_gate"], w["w_up"], w["w_down"])
            return rnd(h + rnd(y)), jnp.zeros((x.shape[0], c["k"]),
                                               jnp.int32)
        r_in = n2_f if f32_router else n2
        scores = jax.nn.sigmoid(r_in @ w["router"])
        if pinned is None:
            _, idx = jax.lax.top_k(scores + w["bias"], c["k"])
        else:
            idx = pinned
        g = jnp.take_along_axis(scores, idx, -1)
        g = g / g.sum(-1, keepdims=True) * c["scale"]
        held = c["first_held"] + jnp.arange(w["e_gate"].shape[0])
        gate = jnp.sum(jnp.where(idx[..., None] == held, g[..., None], 0.0),
                       1)
        a = rnd(jnp.einsum("td,edf->etf", n2, w["e_gate"]))
        b = rnd(jnp.einsum("td,edf->etf", n2, w["e_up"]))
        e = rnd(jnp.einsum("etf,efd->etd", rnd(jax.nn.silu(a) * b),
                           w["e_down"]))
        y = jnp.einsum("etd,te->td", e, gate)
        y = y + ref._swiglu(False, n2, w["s_gate"], w["s_up"], w["s_down"])
        return rnd(h + rnd(y)), idx.astype(jnp.int32)

    def hidden(w, tokens, bf16, routes, f32_router):
        rnd = rounder(bf16)
        x = rnd(w["embed"][tokens].astype(jnp.float32))
        dense = {k: w["d_" + k] for k in ref.DENSE_KEYS}
        x, _ = jax.lax.scan(
            lambda h, lw: (layer(h, lw, "dense", None, rnd, f32_router)[0],
                           None), x, dense)
        moe = {k: w[k] for k in ref.MOE_KEYS}

        def body(h, xs):
            lw, pin = xs
            return layer(h, lw, "moe", pin, rnd, f32_router)

        if routes is None:
            x, idx = jax.lax.scan(
                lambda h, lw: layer(h, lw, "moe", None, rnd, f32_router),
                x, moe)
        else:
            x, idx = jax.lax.scan(body, x, (moe, routes))
        return ref._rmsnorm(x, w["final_norm"].astype(jnp.float32),
                            c["eps"]), idx

    return hidden


def measure(cfg: dict, seeds, T: int) -> dict:
    """The readings above for configuration ``cfg`` (a file of
    ``bench/configs`` with its ``name``), per seed, at ``T`` positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import traffic
    from bench.harness import seed_key
    from bench.registry import _module
    from repro.models.model import build_model

    fam = _module(ROOT / "bench" / "families" / "deepseek_v3.py", "family")
    ref = _module(ROOT / "bench" / "reference" / "deepseek_v3.py", "ref")
    c = dict(ref.consts(cfg))
    hidden = _variants(ref, c)
    model = build_model(fam.program_config(cfg))
    dev = jax.devices()[0]

    @jax.jit
    def program_argmax(params, tokens):
        logits, _ = model.forward(params, {"tokens": tokens[None]})
        return jnp.argmax(logits[0], -1).astype(jnp.int32)

    def argmax_of(w, x):
        best = ref._logit_blocks(w, x, False,
                                 lambda lg, i, bq: jnp.argmax(lg, -1))
        return best.reshape(-1).astype(jnp.int32)

    @jax.jit
    def f32_routes(w, tokens):
        with jax.default_matmul_precision("highest"):
            x, idx = hidden(w, tokens, False, None, False)
            return argmax_of(w, x), idx

    @jax.jit
    def bf16_argmax(w, tokens, routes):
        with jax.default_matmul_precision("highest"):
            x, idx = hidden(w, tokens, True, None, False)
            xp, _ = hidden(w, tokens, True, routes, False)
            xr, _ = hidden(w, tokens, True, None, True)
            return argmax_of(w, x), idx, argmax_of(w, xp), argmax_of(w, xr)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "tokens": T, "layers": cfg["num_hidden_layers"],
           "vocab": cfg["vocab_size"], "seeds": {}}
    names = ["f32", "program", "ref_bf16", "ref_bf16_pinned",
             "ref_bf16_routes_f32", "fp8"]
    for seed in seeds:
        t0 = time.time()
        w = jax.block_until_ready(fam.init_weights(cfg, seed_key(seed)))
        tokens = jnp.asarray(traffic.prompt_tokens(seed, 0, T,
                                                   cfg["vocab_size"]),
                             jnp.int32)
        best, routes = f32_routes(w, tokens)
        b_best, b_routes, pin_best, r32_best = bf16_argmax(w, tokens, routes)
        prog = program_argmax(fam.program_params(w), tokens)
        fp8 = ref.control_argmax(w, cfg, tokens)
        rows = jnp.stack([best, prog, b_best, pin_best, r32_best, fp8])
        g = np.asarray(ref.gaps(w, cfg, tokens, rows))
        best = np.asarray(best)
        res = {name: _stats(g[i], np.asarray(rows[i]) == best)
               for i, name in enumerate(names)}
        a = np.sort(np.asarray(routes), -1)
        b = np.sort(np.asarray(b_routes), -1)
        flips = (a != b).any(-1)                        # [Lm, T]
        res["route_flips_ref_bf16"] = {
            "pairs": float(flips.mean()),
            "tokens_with_any": float(flips.any(0).mean()),
            "per_layer": [round(float(f), 4) for f in flips.mean(1)]}
        res["seconds"] = time.time() - t0
        out["seeds"][str(seed)] = res
        print(json.dumps({"seed": seed, **res}), flush=True)
        del w
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="2718281829,3141592653")
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth (CPU rehearsal only)")
    ap.add_argument("--vocab", type=int, default=None,
                    help="cut the vocabulary (CPU rehearsal only)")
    ap.add_argument("--out")
    args = ap.parse_args()

    cfg = dict(json.loads(CONFIG.read_text()), name="moonlight-16b-a3b")
    if args.layers:
        cfg["num_hidden_layers"] = args.layers
    if args.vocab:
        cfg["vocab_size"] = args.vocab
    out = measure(cfg, [int(s) for s in args.seeds.split(",")], args.tokens)
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
