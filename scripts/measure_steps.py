#!/usr/bin/env python3
"""Step times and device traces of the served path on one TPU chip.

    python scripts/measure_steps.py --out DIR [--seed 0]

Needs a TPU and no PYTHONPATH. It uses ``chip_smoke.py``'s sizes
(qwen1.5-0.5b at published widths, 8 slots of 2048 positions, page 16,
prefill chunk 128), fills every slot with a 1024-token prompt and then,
with all 8 slots decoding:

- ``decode_tick_ms``: 40 engine ticks on the host clock (a tick ends
  when its tokens reach the host);
- ``decode_call_ms``: 20 calls of the jitted decode step, each blocked;
- ``prefill_chunk_ms``: 20 blocked calls of the jitted prefill step, a
  128-token chunk at position 512;
- ``trace_decode`` / ``trace_prefill``: a profiler trace of 10 ticks and
  of 5 prefill calls, each reduced by :func:`reduce_trace` to device busy
  time, the window from the first to the last device op, the ops that
  took the most device time and, for the step (the module with the most
  device time), the median device time, the median start-to-start
  period and the longest gap (:func:`step_idle`): a host stall in one
  tick shows there and not in the median idle share.

Writes ``<out>/measure_steps.json`` and the raw traces under ``<out>``.
Compiles and the fill are set-up, outside every timed window.
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import chip_smoke as smoke  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

FILL_PROMPT = 1024
TICKS, CALLS, TRACE_TICKS, TRACE_CALLS = 40, 20, 10, 5
TOP_OPS = 15


def union_ms(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, in ms."""
    busy, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy / 1e6


def step_idle(intervals) -> dict:
    """Median device time, median period (start to next start) and
    longest gap between consecutive ``(start_ns, end_ns)`` steps, in ms,
    and the idle share of a median step, 1 - busy / period."""
    steps = sorted(intervals)
    busy = sorted((e - s) / 1e6 for s, e in steps)
    period = sorted((b[0] - a[0]) / 1e6 for a, b in zip(steps, steps[1:]))
    gap = max(((b[0] - a[1]) / 1e6 for a, b in zip(steps, steps[1:])),
              default=0.0)
    out = {"busy_ms": busy[len(busy) // 2], "max_gap_ms": gap}
    if period:
        out["period_ms"] = period[len(period) // 2]
        out["idle"] = 1 - out["busy_ms"] / out["period_ms"]
    return out


def reduce_trace(trace_dir: str) -> dict:
    """Busy time, window and top ops of each line of the TPU plane."""
    import jax

    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    data = jax.profiler.ProfileData.from_file(path)
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    out = {"lines": {}}
    for line in plane.lines:
        events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
        out["lines"][line.name] = len(events)
        if line.name not in ("XLA Modules", "XLA Ops") or not events:
            continue
        per_op = {}
        for name, s, e in events:
            per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e6
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_OPS]
        out[line.name] = {
            "busy_ms": union_ms((s, e) for _, s, e in events),
            "window_ms": (max(e for *_, e in events)
                          - min(s for _, s, _ in events)) / 1e6,
            "top_ms": top}
        if line.name == "XLA Modules":       # the step: most device time
            out["steps"] = step_idle((s, e) for name, s, e in events
                                     if name == top[0][0])
    return out


def blocked_ms(fn, n: int) -> list:
    import jax

    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    return times


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    use_compile_cache()
    smoke.require_tpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.qwen1_5_0_5b import CONFIG
    from repro.models.model import build_model
    from repro.serve import ContinuousEngine

    model = build_model(CONFIG)
    res = {}
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(
        jax.random.PRNGKey(args.seed)))
    res["init_s"] = time.perf_counter() - t0
    eng = ContinuousEngine(model, params, max_slots=smoke.SLOTS,
                           page_size=smoke.PAGE, max_len=smoke.MAX_LEN,
                           prefill_chunk=smoke.PREFILL_CHUNK, eos_id=None)
    rng = np.random.default_rng(args.seed)
    new_tokens = smoke.MAX_LEN - FILL_PROMPT
    for _ in range(smoke.SLOTS):
        eng.submit(rng.integers(0, CONFIG.vocab_size, FILL_PROMPT).tolist(),
                   max_new_tokens=new_tokens)

    # set-up: admit and prefill every slot, compile both steps
    t0 = time.perf_counter()
    steps = 0
    while not all(s is not None and s.state == "decode" for s in eng._slots):
        eng.step()
        steps += 1
    res["fill_s"], res["fill_steps"] = time.perf_counter() - t0, steps

    def tick():
        t = time.perf_counter()
        eng.step()
        return (time.perf_counter() - t) * 1e3

    for _ in range(3):
        eng.step()
    res["decode_tick_ms"] = [tick() for _ in range(TICKS)]

    def decode_call():
        toks, eng._pages = eng._decode(
            eng.params, eng._pages, jnp.asarray(eng._tokens),
            jnp.asarray(eng._tables), jnp.asarray(eng._lengths),
            jnp.asarray(eng._mask))
        return toks

    res["decode_call_ms"] = blocked_ms(decode_call, CALLS)

    trace = str(out / "trace_decode")
    jax.profiler.start_trace(trace)
    for _ in range(TRACE_TICKS):
        eng.step()
    jax.profiler.stop_trace()
    res["trace_decode"] = reduce_trace(trace)

    # prefill: rewrite positions 512..639 of slot 0 (its cache is spent)
    chunk = jnp.asarray(rng.integers(0, CONFIG.vocab_size,
                                     (1, smoke.PREFILL_CHUNK)), jnp.int32)
    table = jnp.asarray(eng._tables[0])

    def prefill_call():
        tok, eng._pages = eng._prefill_chunk(
            eng.params, eng._pages, chunk, table, jnp.int32(512),
            jnp.int32(smoke.PREFILL_CHUNK))
        return tok

    jax.block_until_ready(prefill_call())
    res["prefill_chunk_ms"] = blocked_ms(prefill_call, CALLS)
    trace = str(out / "trace_prefill")
    jax.profiler.start_trace(trace)
    jax.block_until_ready([prefill_call() for _ in range(TRACE_CALLS)])
    jax.profiler.stop_trace()
    res["trace_prefill"] = reduce_trace(trace)
    res["decode_compiles"] = eng.decode_compiles

    dev = jax.devices()[0]
    res["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    (out / "measure_steps.json").write_text(json.dumps(res, indent=1))
    for key in ("decode_tick_ms", "decode_call_ms", "prefill_chunk_ms"):
        v = sorted(res[key])
        print(f"{key}: median {v[len(v) // 2]} (min {v[0]}, max {v[-1]})")
    for key in ("trace_decode", "trace_prefill"):
        ops = res[key]["XLA Ops"]
        step = res[key]["steps"]
        print(f"{key}: device busy {ops['busy_ms']} ms of a "
              f"{ops['window_ms']} ms window, idle "
              f"{1 - ops['busy_ms'] / ops['window_ms']}; median step "
              f"{step['busy_ms']} ms every {step.get('period_ms')} ms, idle "
              f"{step.get('idle')}, longest gap {step['max_gap_ms']} ms")
    print(json.dumps({"device": res["device"]}))


if __name__ == "__main__":
    main()
