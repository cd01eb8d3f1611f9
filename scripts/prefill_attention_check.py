#!/usr/bin/env python3
"""The paged prefill kernels on the chip: against the dense path, and
where a prefill chunk's time goes.

    python scripts/prefill_attention_check.py [--out prefill.json]
        [--steps] [--interpret]

From the root of a checkout, on a machine with one TPU (``--interpret``
runs the kernel check on the CPU, the kernels in Pallas interpret mode,
at a cut window). bfloat16 throughout.

1. Kernel check, at both benchmark cells' attention shapes, one 128-row
   chunk of one request, pages of 16 scattered over a slab of two layers:
   qwen1.5-0.5b (``paged_prefill_attention``: 16 heads of 64 over a
   1,024-lane K/V slab, 2,048 positions) and Moonlight-16B-A3B
   (``latent_prefill_attention``: 16 heads over latent rows of 576
   lanes stored in 640, values the first 512, 7,168 positions). For each
   chunk start in ``STARTS`` (the last is the window's last chunk, 40
   rows in it): the largest error of the chunk's rows against the dense
   path (``ref.paged_prefill_attention``, ``ref.latent_prefill_attention``)
   computed in f32 at the highest matmul precision, beyond the output's
   bf16 rounding (2**-8 of the value), as a share of the largest output,
   beside the same reading of the dense path as the chip runs it (one
   bf16 pass for its f32 products); and the median time of a call (one
   jitted loop of ``CALLS`` calls, each call's query made to depend on
   the last output) of the kernel and of that dense path. Fails past
   ``TOL``.
2. ``--steps``: the engine's prefill step (``make_prefill_step``) of each
   cell's configuration (``bench/configs``, seeded weights from
   ``bench/families``) on a slab of one request's window: the median of
   ``CALLS`` blocked calls at each chunk start, and a profiler trace of
   ``TRACE_CALLS`` calls at each start split into the window's attention
   (the prefill kernels, or any op with the window's ``max_len``
   positions in a shape) and the rest, with the largest ops of each.
   Needs no new kernel, so it also reads an older tree.

The last line of standard output is one JSON object with ``ok``.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

PAGE, CHUNK = 16, 128
#: (name, workload, heads, kv heads or None for latent, head dim or
#: latent width, lanes, value lanes, max_len)
CELLS = [
    ("qwen", "qwen1.5-0.5b.chat-poisson", 16, 16, 64, 1024, 64, 2048),
    ("moonlight", "moonlight-16b-a3b.doc-chat-poisson", 16, None, 576, 640,
     512, 7168),
]
STARTS = (0, 1024, 4096, "last")
#: The kernel's probabilities meet the values in bf16, as the dense
#: path's f32 products do at the chip's default precision (one bf16
#: pass): about 2**-9 of each weight, about 1e-3 of the largest output.
TOL = 3e-3
CALLS, REPEATS, TRACE_CALLS = 20, 5, 3


def _starts(max_len: int):
    last = max_len - 40
    return [s for s in STARTS if s == "last" or s + CHUNK <= max_len], last


def kernel_check(interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.decode_attention import (latent_prefill_attention,
                                                paged_prefill_attention)

    out, ok = {}, True
    for name, _, H, K, D, lanes, vd, max_len in CELLS:
        if interpret:
            max_len = 1536
        M = max_len // PAGE
        P = M + 3
        rng = np.random.default_rng(0)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        table = jnp.asarray(rng.permutation(np.arange(1, P))[:M], jnp.int32)
        if K is None:
            width = D
            slab = jax.random.normal(k1, (2, P, PAGE, lanes), jnp.bfloat16)
            slab = slab * (jnp.arange(lanes) < width).astype(slab.dtype)
            q = 2.0 * jax.random.normal(k2, (CHUNK, H, lanes), jnp.bfloat16)
            q = q * (jnp.arange(lanes) < width).astype(q.dtype)
            scale = 192 ** -0.5
            slabs = (slab,)

            def kern(q, slabs, t, s, n):
                return latent_prefill_attention(
                    q, *slabs, 1, t, s, n, sm_scale=scale, v_dim=vd,
                    interpret=interpret)

            def dense(q, slabs, t, s, n):
                return ref.latent_prefill_attention(
                    q, *slabs, 1, t, s, n, sm_scale=scale, v_dim=vd)
        else:
            slabs = tuple(jax.random.normal(k, (2, P, PAGE, lanes),
                                            jnp.bfloat16) for k in (k1, k3))
            q = jax.random.normal(k2, (CHUNK, H, D), jnp.bfloat16)

            def kern(q, slabs, t, s, n):
                return paged_prefill_attention(q, *slabs, 1, t, s, n,
                                               interpret=interpret)

            def dense(q, slabs, t, s, n):
                return ref.paged_prefill_attention(q, *slabs, 1, t, s, n)

        kern_j, dense_j = jax.jit(kern), jax.jit(dense)

        def loop(fn):
            @jax.jit
            def run(q, slabs, t, s, n):
                def body(_, carry):
                    o = fn(q + (carry * 0).astype(q.dtype), slabs, t, s, n)
                    return o[0, 0, 0].astype(jnp.float32)
                return jax.lax.fori_loop(0, CALLS, body, jnp.float32(0))
            return run

        def timed(run, *args):
            jax.block_until_ready(run(*args))
            runs = []
            for _ in range(REPEATS):
                t = time.perf_counter()
                jax.block_until_ready(run(*args))
                runs.append((time.perf_counter() - t) / CALLS)
            return statistics.median(runs) * 1e3

        starts, last = _starts(max_len)
        rows = {}
        for s in starts:
            start = last if s == "last" else s
            n_valid = min(CHUNK, max_len - start)
            args = (q, slabs, table, jnp.int32(start), jnp.int32(n_valid))
            got = np.asarray(kern_j(*args), np.float32)[:n_valid]
            with jax.default_matmul_precision("highest"):
                want = np.asarray(dense_j(
                    q.astype(jnp.float32),
                    tuple(x.astype(jnp.float32) for x in slabs),
                    *args[2:]), np.float32)[:n_valid]

            def worst(x):
                err = np.abs(x - want) - 2.0 ** -8 * np.abs(want)
                return float(err.max() / np.abs(want).max())

            row = {"start": start, "n_valid": n_valid,
                   "worst_over_max": worst(got),
                   "dense_worst_over_max": worst(np.asarray(
                       dense_j(*args), np.float32)[:n_valid]),
                   "finite": bool(np.isfinite(got).all())}
            if not interpret:
                row["kernel_ms"] = timed(loop(kern), *args)
                row["dense_ms"] = timed(loop(dense), *args)
            ok = ok and row["worst_over_max"] <= TOL and row["finite"]
            rows[str(s)] = row
        out[name] = rows
    return {"check": out, "tol": TOL, "ok": ok}


_WINDOW_KERNELS = ("paged_prefill_attention", "latent_prefill_attention")


def _window_op(name: str, max_len: int) -> bool:
    return (any(k in name for k in _WINDOW_KERNELS)
            or re.search(rf"[\[,]{max_len}[\],]", name) is not None)


def step_split(workload: str, seed: int, trace_root: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import trace as btr
    from bench.registry import Registry
    from repro.models.model import build_model
    from repro.serve.engine import make_prefill_step

    reg = Registry(ROOT)
    cfg = reg.config(reg.workload(workload)["config"])
    fam = reg.family(cfg["model_type"])
    eng = cfg["engine"]
    C, max_len = eng["prefill_chunk"], eng["max_len"]
    M = max_len // PAGE
    model = build_model(fam.program_config(cfg))
    params = fam.program_params(jax.block_until_ready(
        fam.init_weights(cfg, jax.random.PRNGKey(seed))))
    pages = model.init_paged_cache(M + 1, PAGE)
    table = jnp.arange(1, M + 1, dtype=jnp.int32)
    step = jax.jit(make_prefill_step(model), donate_argnums=(1,))
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (1, C)), jnp.int32)
    starts, last = _starts(max_len)
    state = {"pages": pages}

    def call(start):
        n = min(C, max_len - start)
        tok, state["pages"] = step(params, state["pages"], tokens, table,
                                   jnp.int32(start), jnp.int32(n))
        return tok

    t0 = time.perf_counter()
    jax.block_until_ready(call(0))
    res = {"compile_and_first_s": time.perf_counter() - t0,
           "compiles": step._cache_size(), "by_start": {}}
    for s in starts:
        start = last if s == "last" else s
        jax.block_until_ready(call(start))
        times = []
        for _ in range(CALLS):
            t = time.perf_counter()
            jax.block_until_ready(call(start))
            times.append((time.perf_counter() - t) * 1e3)
        tdir = tempfile.mkdtemp(dir=trace_root)
        jax.profiler.start_trace(tdir)
        jax.block_until_ready([call(start) for _ in range(TRACE_CALLS)])
        jax.profiler.stop_trace()
        tr = btr.load(tdir)
        row = {"start": start, "blocked_ms": statistics.median(times)}
        if tr is not None:
            mods = btr.module_spans(tr, "jit_prefill_step")
            ops = btr.self_times(btr.ops_in(tr, mods))
            window: dict = {}
            rest: dict = {}
            for op, ns in ops:
                part = window if _window_op(op, max_len) else rest
                part[op] = part.get(op, 0) + ns
            n = max(len(mods), 1)
            row.update(
                device_ms=statistics.median(e - s for _, s, e in mods) / 1e6,
                window_ms=sum(window.values()) / n / 1e6,
                rest_ms=sum(rest.values()) / n / 1e6,
                **{f"{key}_top": [[k[:160], v / n / 1e6] for k, v in sorted(
                    part.items(), key=lambda kv: -kv[1])[:8]]
                   for key, part in (("window", window), ("rest", rest))})
        res["by_start"][str(s)] = row
    res["compiles_after"] = step._cache_size()
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--steps", action="store_true",
                    help="time and split the cells' prefill steps instead")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    if not args.interpret and jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU (or --interpret)")
    if args.steps:
        trace_root = tempfile.mkdtemp(prefix="prefill-trace-")
        out = {"steps": {name: step_split(w, args.seed, trace_root)
                         for name, w, *_ in CELLS}, "ok": True}
    else:
        out = kernel_check(args.interpret)
    out["device"] = {"platform": jax.devices()[0].platform,
                     "kind": jax.devices()[0].device_kind}
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
