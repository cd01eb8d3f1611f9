"""One run of one cell: the served path as a client sees it.

Three processes, as the paper's deployment has them:

- the KV store (``bench/kvproc.py``), which never imports JAX;
- the load generator (``bench/loadgen.py``), on the CPU, which offers
  the cell's traffic through ``ServeClient`` and times each request;
- this process, which holds the chip and drives ``ContinuousEngine``
  (leases on, queue bounded) with its own loop, as ``serve_forever``
  does, so that it can mark each tick.

Set-up is everything from process start to the window's start: weights
made on the device from ``--seed`` in one jitted call, the engine, both
of its programs compiled at the cell's shapes by a warm-up request, and
the traffic's pre-roll. Then the window runs ``--seconds``; the requests
due in it are counted, and load goes on until the last of them has its
reply. With ``--trace 1`` the window is traced, and the per-layer
metrics are read from the trace, the harness's tick records and the
engine's counters. Each metric is a small reader of its own under
``bench/metrics``; the harness hands every reader the same ``Run``.
After the window: the device's peak memory, the program's state freed,
and the check that decides ``correct`` (``bench/check.py``).
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import check, costs
from .loadgen import GO_KEY, READY_KEY
from .registry import Registry

HERE = Path(__file__).resolve().parent
#: how long after the window the counted requests may take to answer
DRAIN_S = 180.0
#: the engine loop's sleep when a tick found no work (``serve_forever``'s)
POLL_S = 0.005
#: engine methods that get a host span of their own in a traced run
SPANS = ("_admit_one", "_prefill_one", "_decode_once", "_renew_leases",
         "_finish")


class BenchError(RuntimeError):
    pass


def seed_key(seed: int):
    """A JAX key from the whole seed (``PRNGKey`` keeps 32 bits only)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def require_chips(chips: int):
    """The first device, which must be a TPU, with ``chips`` of them and
    the Pallas kernels picked; anything else stops the run."""
    import jax

    from repro.kernels import ops
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise BenchError(f"needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    if ops.pallas_mode() != "tpu":
        raise BenchError("the kernel dispatch did not pick Pallas")
    return devs[0]


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


def _snapshot(engine) -> dict:
    return dict(engine.metrics, decode_compiles=engine.decode_compiles,
                prefill_compiles=engine._prefill_chunk._cache_size())


def _warm_up(engine) -> None:
    """Compile both engine programs at the cell's shapes: one request of
    two prefill chunks and two output tokens."""
    n = engine.prefill_chunk + 1
    engine.submit(list(range(1, n + 1)), max_new_tokens=2)
    engine.run_until_idle()
    engine.results.clear()


def _add_spans(engine) -> None:
    import jax

    for name in SPANS:
        fn = getattr(engine, name)

        def spanned(*a, _fn=fn, _label=f"bench.{name.strip('_')}"):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a)
        setattr(engine, name, spanned)


def _record_decode_calls(engine, log: list, on) -> None:
    """Log each decode call's attention lengths while ``on()``."""
    fn = engine._decode

    def recorded(*a):
        if on():
            log.append(np.where(engine._mask, engine._lengths + 1, 0))
        return fn(*a)
    recorded._cache_size = fn._cache_size     # ``decode_compiles`` reads it
    engine._decode = recorded


def _stop(procs) -> None:
    for p in procs:
        if p.poll() is None:
            if p.stdin is not None:
                p.stdin.close()
            try:
                p.wait(5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, engine_hook=None,
             control: bool = False, compile_cache: bool = True,
             mix_override=None, dump=None) -> dict:
    """One run; the keywords after ``t_start`` serve the tests and
    ``bench/survey.py`` (CPU runs, planted faults, the control, a knee
    sweep's rates, a copy of the reduced trace and records in ``dump``).
    """
    reg = Registry(root)
    cell = reg.workload(workload)
    cfg = reg.config(cell["config"])
    mix = dict(reg.mix(cell["traffic"]), **(mix_override or {}))
    limits = reg.limits(workload)["limits"]
    fam = reg.family(cfg["model_type"])
    ref = reg.reference(cfg["model_type"])
    readers = reg.per_layer(workload) if trace else reg.end_to_end(workload)
    eng = cfg["engine"]

    procs, store = [], None
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        try:
            kv = subprocess.Popen([sys.executable, str(HERE / "kvproc.py")],
                                  stdin=subprocess.PIPE,
                                  stdout=subprocess.PIPE, text=True)
            procs.append(kv)
            import jax
            dev = (require_chips(cell["chips"]) if require_tpu
                   else jax.devices()[0])
            from repro.core.kvcluster import connect
            from repro.core.queues import Queue
            from repro.launch.compile_cache import use_compile_cache
            from repro.models.model import build_model
            from repro.serve import ContinuousEngine
            if compile_cache:
                use_compile_cache()
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0)

            endpoints = json.loads(kv.stdout.readline())
            store = connect(endpoints)
            queue = Queue(eng["queue_maxsize"], store=store)
            spec = {"endpoints": endpoints, "queue_uid": queue.uid,
                    "queue_maxsize": eng["queue_maxsize"], "mix": mix,
                    "seed": seed, "vocab": cfg["vocab_size"],
                    "preroll_s": mix["preroll_s"], "seconds": seconds,
                    "drain_s": DRAIN_S, "out": f"{tmp}/loadgen.json"}
            Path(f"{tmp}/spec.json").write_text(json.dumps(spec))
            gen = subprocess.Popen(
                [sys.executable, str(HERE / "loadgen.py"), f"{tmp}/spec.json"],
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            procs.append(gen)

            weights = jax.block_until_ready(
                fam.init_weights(cfg, seed_key(seed)))
            model = build_model(fam.program_config(cfg))
            engine = ContinuousEngine(
                model, fam.program_params(weights),
                max_slots=eng["max_slots"], max_len=eng["max_len"],
                prefill_chunk=eng["prefill_chunk"], eos_id=None,
                request_queue=queue, lease=True)
            _warm_up(engine)
            if engine_hook is not None:
                engine_hook(engine)
            in_window = [False]
            decode_log: list = []
            if trace:
                _add_spans(engine)
                _record_decode_calls(engine, decode_log,
                                     lambda: in_window[0])
            if store.blpop(READY_KEY, 120.0) is None:
                raise BenchError("the load generator did not start")

            t0 = time.time() + 0.05
            store.rpush(GO_KEY, repr(t0).encode())
            w0 = t0 + mix["preroll_s"]
            w1 = w0 + seconds
            res = _drive(engine, gen, w0, w1, trace, tmp, in_window,
                         store, queue)
            if gen.wait() != 0:
                raise BenchError(f"load generator exited {gen.returncode}")
            out = json.loads(Path(spec["out"]).read_text())
            stats = dev.memory_stats() or {}
            peak = int(stats.get("peak_bytes_in_use", 0))
            slab = (engine.alloc.num_pages, engine.page_size,
                    model.cfg.num_kv_heads, model.cfg.hd)
            engine.params = None
            engine._pages = None
            del engine, model
            gc.collect()
        finally:
            if store is not None:
                store.close()
            _stop(procs)

        records = out["records"]
        counted = [r for r in records if r["counted"]]
        if dump is not None:
            _dump(Path(dump), out, res, decode_log)
        run = SimpleNamespace(
            records=records, counted=counted, w0=w0, w1=w1, seconds=seconds,
            setup_s=w0 - t_start, counters=res["counters"],
            ticks=res["ticks"], decode_calls=decode_log,
            trace=res["trace"], trace_window_s=res["trace_window_s"],
            dims=fam.dims(cfg), cfg=cfg, engine=eng, slab=slab,
            peaks=costs.peaks(dev.device_kind) if require_tpu else None)
        metrics = {}
        for m in readers:
            v = m["read"](run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

        late = [r["t_call"] - r["due"] for r in counted
                if r["t_call"] is not None]
        drain = out["t_end"] - w1
        print(f"info: {len(counted)} requests counted of {len(records)} "
              f"offered; generator lateness p50 {percentile(late, 50)} s, "
              f"max {max(late, default=None)} s; drain after the window "
              f"{drain} s; setup {run.setup_s} s; slab {slab}")

        picked = check.sample(records, seed)
        values = dict(check.logit_gaps(ref, weights, cfg, mix, seed, picked,
                                       control=control))
        values.update(check.delivery(records, cfg["vocab_size"],
                                     out["duplicates"]))
        values.update(decode_compiles=res["compiles"]["decode_compiles"],
                      prefill_compiles=res["compiles"]["prefill_compiles"])
        correct, checks = check.judge(values, limits)

    failed = sum(1 for r in counted if r["error"] is not None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(counted),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and res["trace"] is not None:
        from . import trace as tr
        device["busy_s"] = tr.union_ns(
            (s, e) for _, s, e in res["trace"]["ops"]) / 1e9
        device["window_s"] = res["trace_window_s"]
        result["breakdown"] = {"device_ops": tr.top_ops(res["trace"]),
                               "idle_gaps": tr.idle_gaps(res["trace"])}
    result["info"] = {k: values[k] for k in values if k not in checks}
    result["checks"] = checks
    return result


def _dump(path: Path, out: dict, res: dict, decode_log: list) -> None:
    """What ``bench/survey.py --dump`` keeps of a run: the records
    without their tokens, the ticks and counters, and of a trace the
    per-op summary, a few steps' worth of events and, compressed, all
    of them (``trace.excerpt``), so that the readers can be run again."""
    import gzip

    from . import trace as trace_mod

    path.mkdir(parents=True, exist_ok=True)
    recs = [{k: v for k, v in r.items() if k != "tokens"}
            for r in out["records"]]
    (path / "records.json").write_text(json.dumps(dict(out, records=recs)))
    (path / "ticks.json").write_text(json.dumps(
        {"ticks": res["ticks"], "backlog": res["backlog"],
         "counters": res["counters"], "trace_window_s": res["trace_window_s"],
         "decode_calls": [a.tolist() for a in decode_log]}))
    if res["trace"] is not None:
        (path / "ops.json").write_text(json.dumps(
            trace_mod.op_summary(res["trace"]), indent=0))
        (path / "excerpt.json").write_text(json.dumps(
            trace_mod.excerpt(res["trace"])))
        with gzip.open(path / "trace.json.gz", "wt") as f:
            json.dump(trace_mod.excerpt(res["trace"], None, None), f)


def _drive(engine, gen, w0: float, w1: float, trace: bool, tmp: str,
           in_window: list, store, queue) -> dict:
    """The engine loop, from the pre-roll until the generator is done.
    Once a second in the window it reads the admission backlog: requests
    on the queue plus those the engine holds back."""
    import jax

    snap0 = snap1 = None
    ticks, backlog = [], []
    next_look = w0
    trace_dir = f"{tmp}/trace"
    t_tr = None
    while gen.poll() is None:
        now = time.time()
        if snap0 is None and now >= w0:
            snap0 = _snapshot(engine)
            in_window[0] = True
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                t_tr = time.perf_counter()
        elif snap0 is not None and snap1 is None and now >= w1:
            in_window[0] = False
            if trace:
                jax.block_until_ready(engine._pages)
                t_tr = time.perf_counter() - t_tr
                jax.profiler.stop_trace()
            snap1 = _snapshot(engine)
        if in_window[0] and now >= next_look:
            backlog.append((now - w0, store.llen(queue._items_key)
                            + len(engine._pending)))
            next_look += 1.0
        recording = in_window[0] and trace
        before = engine.metrics["decode_steps"], engine.metrics["prefill_chunks"]
        t = time.perf_counter()
        if recording:
            with jax.profiler.TraceAnnotation("bench.tick"):
                worked = engine.step()
        else:
            worked = engine.step()
        if recording:
            ticks.append({"t0": t, "t1": time.perf_counter(),
                          "worked": bool(worked),
                          "decode": engine.metrics["decode_steps"] > before[0],
                          "prefill": engine.metrics["prefill_chunks"]
                          > before[1]})
        if not worked:
            time.sleep(POLL_S)
    if snap1 is None:
        raise BenchError("the load generator ended before the window did")
    tr = None
    if trace:
        from . import trace as trace_mod
        tr = trace_mod.load(trace_dir)
    counters = {k: snap1[k] - snap0[k] for k in snap0}
    return {"counters": counters, "compiles": snap1, "ticks": ticks,
            "backlog": backlog, "trace": tr, "trace_window_s": t_tr}
