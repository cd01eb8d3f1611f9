"""Batched serving demo: prefill + decode with the KV cache engine.

    PYTHONPATH=src python examples/serve_lm.py [--arch qwen1.5-0.5b]

Uses the smoke-sized config of the chosen architecture (full configs are
dry-run-only on CPU), generates greedily for a batch of prompts, and
verifies the decode path against teacher forcing.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.models import build_model
from repro.serve import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=ARCHS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)

    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.new_tokens + 8)
    t0 = time.time()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.time() - t0
    toks = args.batch * args.new_tokens
    print(f"arch={args.arch} generated [{args.batch} x {args.new_tokens}] "
          f"tokens in {dt:.2f}s ({toks/dt:.1f} tok/s batched)")
    print("sample:", np.asarray(out[0][:12]))

    # consistency: greedy decode == argmax of teacher-forced forward
    batch = {"tokens": jnp.concatenate([prompts, out], axis=1)}
    if cfg.family == "vlm":
        return  # needs patches input; covered in tests
    logits, _ = model.forward(params, dict(batch, labels=batch["tokens"]))
    ref_next = jnp.argmax(logits[:, args.prompt_len - 1], -1)
    assert jnp.array_equal(ref_next, out[:, 0]), "decode mismatch"
    print("decode == teacher-forced argmax: OK")


if __name__ == "__main__":
    main()
