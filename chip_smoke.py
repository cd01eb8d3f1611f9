#!/usr/bin/env python3
"""Chip smoke: serve qwen1.5-0.5b at its published widths on one TPU chip.

    python chip_smoke.py [--seed 0]

Run it from the root of a checkout on a machine with one TPU. It needs no
PYTHONPATH and no network, and it is the quickest proof that the served
path still starts on the chip. It is not a benchmark: the times it
prints are labelled as information.

Everything runs in this one process, which holds the chip:

1. Refuse to run without a TPU: the first device must be a TPU, and the
   kernel dispatch must pick the Pallas kernels (``ops.pallas_mode()``).
2. Compare each attention kernel of the serving path with its pure-jnp
   oracle in ``kernels/ref.py`` at the served shapes, from bfloat16
   inputs (tolerances and their reason at ``DECODE_TOL``).
3. Build qwen1.5-0.5b unchanged (24 layers, d_model 1024, 16 heads of
   64, vocab 151936, bfloat16), init random weights from ``--seed`` on
   the chip, and start a ``ContinuousEngine`` with leases over a bounded
   KV ``Queue`` in the in-process store. Submit seeded requests through
   ``ServeClient`` (prompts of 128-1024 tokens, 32-64 new tokens). Every
   request must be answered exactly once with its token count, and the
   decode step must have compiled once.
4. Check the served tokens of a few requests by a second path: a
   teacher-forced forward over prompt and served tokens, with
   whole-sequence flash attention and no paged cache, must rank each
   served token first up to bf16 noise (``TF_TOL``).

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when every check passed. Any failure raises and exits
non-zero.

One chip only: no user-facing path spans chips yet (``ContinuousEngine``
places nothing on devices), so there is no four-chip phase.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

SLOTS, PAGE, MAX_LEN, PREFILL_CHUNK = 8, 16, 2048, 128
N_REQUESTS = 16
PROMPT_LEN, NEW_TOKENS = (128, 1024), (32, 64)
#: Kernel vs oracle, per element: |kernel - oracle| <= 2**-8 * |oracle|
#: + TOL * max |oracle|. The kernels read bf16 inputs, compute in f32 and
#: round their output to bf16, which moves each value by at most 2**-8 of
#: itself; the oracle sees the same bf16 values upcast to f32 at the
#: highest matmul precision. TOL bounds what is left, the kernel's own
#: arithmetic, and is set between the sound kernels and degraded controls
#: on the same inputs (``scripts/kernel_controls.py``, readings in
#: PERF.md). The decode kernels score with exact products summed in f32
#: and read under 1e-7; the limit admits bf16 matmul operands (about
#: 8e-4) and rejects an accumulator or softmax sum held in bf16 (about
#: 4e-3) and a dropped position (about 1).
#: The prefill kernel's dots read about 6e-4 on the chip; with two
#: 512-key blocks a bf16 accumulator or sum adds only about 1.6e-3, so its
#: limit sits between. One misplaced position of a 2048-long row moves it
#: by about 1/2048 and hides in the rounding; the short rows (length 3,
#: random lengths) show it.
DECODE_TOL, PREFILL_TOL = 2e-3, 1e-3
#: Served tokens checked by the teacher-forced forward, its fixed length
#: (the longest prompt plus output) and its tolerance: how far, in
#: standard deviations of that position's logits over the vocabulary, a
#: served token's logit may sit below the forward's largest. The two
#: paths differ in bf16 rounding only; a token that is not a near-argmax
#: sits several deviations below it.
N_WITNESS = 4
TF_LEN = PROMPT_LEN[1] + NEW_TOKENS[1]
TF_TOL = 0.5


def require_tpu():
    import jax

    from repro.kernels import ops
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform!r}")
    if ops.pallas_mode() != "tpu":
        raise SystemExit("chip_smoke: kernel dispatch did not pick Pallas")
    return dev


def kernel_inputs(seed: int) -> dict:
    """Seeded bf16 inputs of the three kernels at the served shapes:
    decode over 8 slots of 2048 positions (ragged lengths, one full row,
    one of length 3 and one empty) from a shuffled page table into the
    last layer of a two-layer slab ``[2, P, page, K * D]``, and the
    causal prefill of one 1000-token prompt, not a multiple of the 512
    block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    H = K = 16
    D = 64
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    rng = np.random.default_rng(seed)

    def normal(key, shape, std=1.0):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.bfloat16)

    lengths = rng.integers(1, MAX_LEN + 1, SLOTS)
    lengths[:3] = (MAX_LEN, 3, 0)
    M = MAX_LEN // PAGE
    P = SLOTS * M + 1
    table = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(SLOTS, M),
                        jnp.int32)
    kp = normal(ks[1], (2, P, PAGE, K * D))
    vp = normal(ks[2], (2, P, PAGE, K * D))
    layer = jnp.int32(1)
    S = 1000
    return dict(
        q=normal(ks[0], (SLOTS, H, D), 2.0), k_pages=kp, v_pages=vp,
        layer=layer, table=table, lengths=jnp.asarray(lengths, jnp.int32),
        k_cache=kp[layer, table].reshape(SLOTS, MAX_LEN, K, D),
        v_cache=vp[layer, table].reshape(SLOTS, MAX_LEN, K, D),
        q_prefill=normal(ks[3], (1, S, H, D), 2.0),
        k_prefill=normal(ks[4], (1, S, K, D)),
        v_prefill=normal(ks[5], (1, S, K, D)))


def oracle(fn, *inputs):
    """``fn`` on the bf16 inputs upcast to f32, at the highest precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(*(x.astype(jnp.float32)
                               if x.dtype == jnp.bfloat16 else x
                               for x in inputs)))


def kernel_error(got, want) -> tuple:
    """``(max|oracle|, max|err| / max|oracle|, excess)``: excess is the
    largest error beyond bf16 output rounding (``2**-8 * |oracle|``),
    over max|oracle|; ``DECODE_TOL`` and ``PREFILL_TOL`` bound it."""
    import jax.numpy as jnp
    import numpy as np

    got = np.asarray(jnp.asarray(got).astype(jnp.float32))
    if not np.isfinite(got).all():
        return float(np.abs(want).max()), np.inf, np.inf
    scale = float(np.abs(want).max())
    err = np.abs(got - want)
    return (scale, float(err.max()) / scale,
            float((err - 2.0 ** -8 * np.abs(want)).max()) / scale)


def check_kernels(seed: int) -> None:
    """Each serving-path kernel against its oracle, at the served shapes."""
    from repro.kernels import ref
    from repro.kernels.decode_attention import flash_decode, flash_decode_paged
    from repro.kernels.flash_attention import flash_attention

    x = kernel_inputs(seed)
    paged = (x["q"], x["k_pages"], x["v_pages"], x["layer"], x["table"],
             x["lengths"])
    dense = (x["q"], x["k_cache"], x["v_cache"], x["lengths"])
    prefill = (x["q_prefill"], x["k_prefill"], x["v_prefill"])
    for name, tol, got, want in (
            ("flash_decode_paged", DECODE_TOL, flash_decode_paged(*paged),
             oracle(ref.paged_decode_attention, *paged)),
            ("flash_decode", DECODE_TOL, flash_decode(*dense),
             oracle(ref.decode_attention, *dense)),
            ("flash_attention", PREFILL_TOL,
             flash_attention(*prefill, causal=True),
             oracle(ref.attention, *prefill))):
        scale, err, excess = kernel_error(got, want)
        print(f"info: kernel {name}: shape {want.shape}, max|oracle| {scale},"
              f" max|err| / max|oracle| {err}, error beyond bf16 rounding /"
              f" max|oracle| {excess}")
        if not (scale > 0 and excess <= tol):
            raise AssertionError(f"{name} disagrees with its oracle: "
                                 f"{excess} > {tol} of max|oracle|")


def serve(model, params, seed: int):
    """Serve seeded requests through the bounded KV queue; check them.
    Returns the requests ``(prompt, max_new)`` and their served tokens."""
    import jax
    import numpy as np

    from repro.core.queues import Queue
    from repro.serve import ContinuousEngine, ServeClient

    cfg = model.cfg
    queue = Queue(maxsize=SLOTS)
    client = ServeClient(queue)
    engine = ContinuousEngine(model, params, max_slots=SLOTS, page_size=PAGE,
                              max_len=MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                              eos_id=None, request_queue=queue, lease=True,
                              lease_ttl_s=600.0)
    slab = sum(x.nbytes for x in jax.tree.leaves(engine._pages))
    print(f"info: engine: {SLOTS} slots, page {PAGE}, max_len {MAX_LEN}, "
          f"{engine.alloc.num_pages} pages, slab {slab / 1e9:.2f} GB")

    stop = threading.Event()
    failure = []

    def run_engine():
        try:
            engine.serve_forever(stop)
        except Exception as e:  # re-raised by the main thread
            failure.append(e)

    rng = np.random.default_rng(seed)
    specs = [(rng.integers(0, cfg.vocab_size,
                           int(rng.integers(PROMPT_LEN[0],
                                            PROMPT_LEN[1] + 1))).tolist(),
              int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1)))
             for _ in range(N_REQUESTS)]

    worker = threading.Thread(target=run_engine, name="engine", daemon=True)
    t0 = time.perf_counter()
    worker.start()
    results = {}
    try:
        # a bounded queue: submits past SLOTS wait for the engine to pop
        rids = [client.submit(toks, mn, timeout=600.0) for toks, mn in specs]
        deadline = time.monotonic() + 900.0
        for rid in rids:
            while rid not in results:
                if failure:
                    raise RuntimeError("engine failed") from failure[0]
                if time.monotonic() > deadline:
                    raise TimeoutError(f"no result for {rid}")
                try:
                    results[rid] = client.result(rid, timeout=5.0)
                except TimeoutError:
                    pass
    finally:
        stop.set()
        worker.join(timeout=600.0)
    wall = time.perf_counter() - t0
    if failure:
        raise RuntimeError("engine failed") from failure[0]
    if worker.is_alive():
        raise RuntimeError("engine thread did not stop")

    store = queue._store
    for rid, (toks, mn) in zip(rids, specs):
        r = results[rid]
        if r.get("id") != rid or "error" in r:
            raise AssertionError(f"bad result for {rid}: {r}")
        out = r["tokens"]
        if len(out) != mn or not all(0 <= t < cfg.vocab_size for t in out):
            raise AssertionError(f"{rid}: {len(out)} tokens, wanted {mn}")
        if store.llen(client._resp_key(rid)) != 0:
            raise AssertionError(f"{rid} was answered more than once")
    m = engine.metrics
    if m["completed"] != N_REQUESTS:
        raise AssertionError(f"completed {m['completed']} of {N_REQUESTS}")
    if engine.decode_compiles != 1:
        raise AssertionError(f"decode compiled {engine.decode_compiles} times")
    n_out = sum(len(r["tokens"]) for r in results.values())
    n_in = sum(len(t) for t, _ in specs)
    print(f"info: served {N_REQUESTS} requests exactly once ({n_in} prompt "
          f"tokens, {n_out} new tokens) in {wall:.1f}s including compiles: "
          f"{n_out / wall:.1f} new tokens/s; {m['decode_steps']} decode "
          f"steps, {m['prefill_chunks']} prefill chunks, {m['preempted']} "
          f"preempted, decode compiles {engine.decode_compiles}")
    return specs, [results[rid]["tokens"] for rid in rids]


def check_served_tokens(model, params, specs, outputs, seed: int) -> None:
    """Each of the first ``N_WITNESS`` requests' served tokens must be a
    near-argmax of a teacher-forced forward over the prompt and the
    tokens served before it. A random token's distance below the argmax
    is printed beside the served ones as a control."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_new = NEW_TOKENS[1]

    @jax.jit
    def logits_after(params, tokens, start):
        logits = model.forward(params, {"tokens": tokens})[0][0]
        return jax.lax.dynamic_slice_in_dim(logits, start, n_new)

    rng = np.random.default_rng(seed)
    worst, control = 0.0, np.inf
    for (prompt, _), out in zip(specs[:N_WITNESS], outputs[:N_WITNESS]):
        seq = prompt + out[:-1]
        toks = np.zeros((1, TF_LEN), np.int32)
        toks[0, :len(seq)] = seq
        logits = np.asarray(logits_after(params, jnp.asarray(toks),
                                         len(prompt) - 1), np.float32)
        logits = logits[:len(out)]                      # [n_out, vocab]
        top, spread = logits.max(-1), logits.std(-1)
        rows = np.arange(len(out))
        served = (top - logits[rows, out]) / spread
        other = rng.integers(0, logits.shape[1], len(out))
        worst = max(worst, float(served.max()))
        control = min(control, float(((top - logits[rows, other])
                                      / spread).min()))
    print(f"info: teacher-forced witness over {N_WITNESS} requests: served "
          f"tokens at most {worst} deviations below the argmax (limit "
          f"{TF_TOL}); random tokens at least {control}")
    if not (np.isfinite(worst) and worst <= TF_TOL):
        raise AssertionError(f"served tokens disagree with the teacher-forced"
                             f" forward: {worst} > {TF_TOL} deviations")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = use_compile_cache()
    dev = require_tpu()
    import jax
    n_dev = len(jax.devices())
    print(f"info: device {dev.platform} {dev.device_kind} x{n_dev}; "
          f"compile cache {cache}")
    t0 = time.perf_counter()
    check_kernels(args.seed)
    print(f"info: kernel checks passed in {time.perf_counter() - t0:.1f}s "
          f"(compiles included)")

    from repro.configs.qwen1_5_0_5b import CONFIG as cfg
    from repro.models.model import build_model
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(
        jax.random.PRNGKey(args.seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"info: {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads of {cfg.hd}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}; {n_params / 1e9:.3f}B params "
          f"initialised in {time.perf_counter() - t0:.1f}s")
    specs, outputs = serve(model, params, args.seed)
    t0 = time.perf_counter()
    check_served_tokens(model, params, specs, outputs, args.seed)
    print(f"info: witness passed in {time.perf_counter() - t0:.1f}s "
          f"(compile included)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))


if __name__ == "__main__":
    main()
