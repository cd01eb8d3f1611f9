#!/usr/bin/env python3
"""The latent (MLA) paged decode kernel on the chip: against its oracle,
and whether its time follows the live positions.

    python scripts/latent_decode_check.py [--out latent.json]

From the root of a checkout, on a machine with one TPU (``--interpret``
runs the same checks on the CPU, the kernel in Pallas interpret mode, at
a few slots). At Moonlight-16B-A3B's widths: 16 heads, latent rows of
576 lanes (512 latent, 64 rope) stored in 640, pages of 16, 24 slots of
7,168 positions, bfloat16.

1. ``latent_decode_paged`` against ``ref.latent_decode_attention`` on
   ragged lengths with a length-0 row, per element: bf16 output rounding
   (2**-8 of the value) plus ``TOL`` of the largest output, the oracle
   in f32 at the highest matmul precision (one bf16 pass, the chip's
   default for f32, reads about 1.4e-3 by itself). The kernel
   takes the scores as exact bf16 products with an f32 sum and the
   probabilities in two bf16 terms, so it reads f32 rounding (about
   1e-6); one bf16 pass for the probabilities would read about 1e-3.
2. Time of one layer's call (median of ``REPEATS`` runs of one jitted
   loop of ``CALLS`` calls, each call's query made to depend on the last
   call's output so that none is hoisted, ended by
   ``block_until_ready``, so dispatch is paid once a loop) with every slot
   live at 7,168 positions, with 2 slots live at 512 and the rest empty,
   and with 10 slots at 3,000; the first two must differ by more than
   ten times. Also the least time by bytes at the chip's 819 GB/s.

The last line of standard output is one JSON object with ``ok``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

SLOTS, MAX_LEN, PAGE, H, R, ROPE, LANES = 24, 7168, 16, 16, 512, 64, 640
TOL = 1e-4
CALLS, REPEATS = 20, 5
HBM_BYTES_PER_S = 819e9


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.decode_attention import latent_decode_paged

    slots, max_len = (SLOTS, MAX_LEN) if not args.interpret else (3, 1024)
    if not args.interpret and jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU (or --interpret)")
    M = max_len // PAGE
    P = slots * M + 1
    scale = (128 + ROPE) ** -0.5
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    pad = jnp.zeros((1,), jnp.bfloat16)
    slab = jax.random.normal(k1, (1, P, PAGE, R + ROPE), jnp.bfloat16)
    slab = jnp.concatenate(
        [slab, jnp.broadcast_to(pad, (1, P, PAGE, LANES - R - ROPE))], -1)
    q = jnp.concatenate([2.0 * jax.random.normal(
        k2, (slots, H, R + ROPE), jnp.bfloat16),
        jnp.zeros((slots, H, LANES - R - ROPE), jnp.bfloat16)], -1)
    table = jnp.asarray(rng.permutation(np.arange(1, P))[:slots * M]
                        .reshape(slots, M), jnp.int32)
    kernel = jax.jit(lambda q, s, t, n: latent_decode_paged(
        q, s, 0, t, n, sm_scale=scale, v_dim=R, interpret=args.interpret))

    lens = rng.integers(1, max_len + 1, slots)
    lens[0], lens[1], lens[-1] = 0, 1, max_len
    lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(kernel(q, slab, table, lens), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.latent_decode_attention(
            q.astype(jnp.float32), slab.astype(jnp.float32), 0, table, lens,
            sm_scale=scale, v_dim=R), np.float32)
    err = np.abs(got - want) - 2.0 ** -8 * np.abs(want)
    worst = float(err.max() / np.abs(want).max())
    out = {"check": {"worst_over_max": worst, "tol": TOL,
                     "zero_row_zero": bool(not got[0].any())}}
    ok = worst <= TOL and out["check"]["zero_row_zero"]

    @jax.jit
    def loop(q, slab, table, n):
        def body(_, carry):
            qi = q + (carry * 0).astype(q.dtype)
            o = latent_decode_paged(qi, slab, 0, table, n, sm_scale=scale,
                                    v_dim=R)
            return o[0, 0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, CALLS, body, jnp.float32(0))

    def timed(lengths):
        n = jnp.asarray(lengths, jnp.int32)
        jax.block_until_ready(loop(q, slab, table, n))
        runs = []
        for _ in range(REPEATS):
            t = time.perf_counter()
            jax.block_until_ready(loop(q, slab, table, n))
            runs.append((time.perf_counter() - t) / CALLS)
        return statistics.median(runs)

    if not args.interpret:
        cases = {"all_full": [max_len] * slots,
                 "two_at_512": [512, 512] + [0] * (slots - 2),
                 "ten_at_3000": [3000] * 10 + [0] * (slots - 10)}
        times = {}
        for name, lengths in cases.items():
            s = timed(lengths)
            least = 2 * (R + ROPE) * sum(lengths) / HBM_BYTES_PER_S
            times[name] = {"ms": s * 1e3, "least_ms": least * 1e3,
                           "share": least / s}
        ratio = times["two_at_512"]["ms"] / times["all_full"]["ms"]
        out["times"] = times
        out["two_over_full"] = ratio
        ok = ok and ratio < 0.1
    out["ok"] = bool(ok)
    out["device"] = {"platform": jax.devices()[0].platform,
                     "kind": jax.devices()[0].device_kind}
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
