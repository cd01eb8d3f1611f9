"""Continuous-batching serving demo: a burst of concurrent requests
joining and leaving one live paged-KV batch.

    PYTHONPATH=src python examples/serve_continuous.py [--requests 8]

Requests with different prompt/output lengths are submitted through the
KV plane's bounded queue (``ServeClient`` -> ``ContinuousEngine``); the
engine admits each one as soon as a slot and cache pages free up,
prefilling prompts in chunks between decode steps so short requests
finish and leave while long ones are still running. Every output is
verified token-for-token against an independent batch-of-1 static
decode (the paged cache is numerically transparent), and the engine
must have compiled its decode step exactly once despite the batch
membership changing on almost every step.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.core.queues import Queue
from repro.models import build_model
from repro.serve import ContinuousEngine, ServeClient, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=ARCHS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    queue = Queue(maxsize=max(4, args.requests))
    client = ServeClient(queue)
    engine = ContinuousEngine(model, params, max_slots=args.slots,
                              page_size=8, max_len=64, prefill_chunk=8,
                              eos_id=None, request_queue=queue)

    rng = np.random.default_rng(0)
    specs = [(rng.integers(3, cfg.vocab_size,
                           int(rng.integers(2, 24))).tolist(),
              int(rng.integers(3, 14))) for _ in range(args.requests)]

    t0 = time.time()
    rids = [client.submit(toks, mn) for toks, mn in specs]  # the burst
    engine.run_until_idle()
    results = [client.result(r, timeout=5.0) for r in rids]
    dt = time.time() - t0

    toks_out = sum(len(r["tokens"]) for r in results)
    ttfts = sorted(r["ttft_s"] for r in results)
    print(f"arch={args.arch} served {args.requests} concurrent requests "
          f"({toks_out} tokens) in {dt:.2f}s "
          f"[{engine.metrics['decode_steps']} decode steps, "
          f"{engine.metrics['prefill_chunks']} prefill chunks, "
          f"p50 ttft {ttfts[len(ttfts) // 2] * 1e3:.1f}ms]")
    assert engine.decode_compiles == 1, "batch churn caused recompiles"
    print("joined/left a single jitted decode shape: 1 compile OK")

    static = ServeEngine(model, params, max_len=64, eos_id=None)
    for (toks, mn), res in zip(specs, results):
        row = np.asarray(static.generate(jnp.asarray([toks], jnp.int32),
                                         max_new_tokens=mn))[0]
        assert res["tokens"] == list(row), "paged decode diverged"
    print("continuous outputs == per-request static decode: OK")


if __name__ == "__main__":
    main()
