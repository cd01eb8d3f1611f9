"""The trace reduction: union and self times on hand-made intervals, and
the readers on a trace recorded on a v5e chip (``data/``), trimmed to
a few ticks of the chat cell."""

import gzip
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import trace


@pytest.mark.parametrize("intervals,busy", [
    ([], 0),
    ([(0, 10)], 10),
    ([(0, 10), (20, 25)], 15),                # disjoint: a gap is idle
    ([(0, 100), (10, 20), (30, 40)], 100),    # nested: a loop and its body
    ([(5, 15), (0, 10), (12, 30)], 30),       # overlapping, out of order
    ([(0, 10), (10, 20)], 20),                # touching
])
def test_union(intervals, busy):
    assert trace.union_ns(intervals) == busy
    assert sum(e - s for s, e in trace.merged(intervals)) == busy


def test_self_times_leave_out_nested_ops():
    ops = [("while", 0, 100), ("a", 10, 20), ("b", 30, 40),
           ("c", 120, 130)]
    got = dict(trace.self_times(ops))
    assert got == {"while": 80, "a": 10, "b": 10, "c": 10}


def test_ops_in_modules():
    tr = {"ops": [("x", 1, 2), ("y", 11, 12), ("z", 21, 22)]}
    mods = [("jit_a", 0, 5), ("jit_a", 20, 30)]
    assert [o[0] for o in trace.ops_in(tr, mods)] == ["x", "z"]


def test_idle_gaps_are_named_by_the_innermost_span():
    tr = {"ops": [("a", 0, 10), ("b", 30, 40)],
          "spans": [("tick", 0, 50), ("decode_once", 12, 28)]}
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ["decode_once", 20e-9]
    assert gaps[1] == ["tick", 10e-9]


def test_top_ops_by_self_time():
    tr = {"ops": [("while", 0, 100), ("copy.1", 10, 60), ("copy.1", 70, 80),
                  ("fusion.2", 200, 205)]}
    assert trace.top_ops(tr, 2) == [["copy.1", 60e-9], ["while", 40e-9]]


# ------------------------------------------- a trace recorded on the chip

DATA = Path(__file__).resolve().parent / "data" / "chat-excerpt.json.gz"
#: qwen1.5-0.5b as the chat cell runs it: 2049 pages of 16, 16 kv heads
SLAB = (2049, 16, 16, 64)


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(DATA, "rt") as f:
        return trace.from_excerpt(json.load(f))


def _metric(name):
    path = Path(__file__).resolve().parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_recorded_trace_has_the_steps(chip_trace):
    assert len(trace.module_spans(chip_trace, "jit_decode_step")) == 2
    assert len(trace.module_spans(chip_trace, "jit_prefill_step")) == 1
    names = {s[0] for s in chip_trace["spans"]}
    assert {"tick", "decode_once", "prefill_one"} <= names


def test_slab_rule_on_a_recorded_decode_step(chip_trace):
    """The slab's relayouts, the scan's slicing and write back of the
    stacked slab and the whole-slab copies count; the same moves of
    the weights' layer slices and the new keys' scatter do not."""
    rule = _metric("slab_copy_ms").is_slab_move
    step = trace.module_spans(chip_trace, "jit_decode_step")[:1]
    ops = trace.ops_in(chip_trace, step)
    moved = {trace.hlo_parts(n)[0] for n, _, _ in ops if rule(n, SLAB)}
    assert moved == {"copy.60", "copy.61", "copy.63", "copy.64", "copy.86",
                     "copy.87", "constant_dynamic-slice_fusion.12",
                     "constant_dynamic-slice_fusion.13",
                     "constant_dynamic-update-slice_fusion.4",
                     "constant_dynamic-update-slice_fusion.5"}
    kept = {trace.hlo_parts(n)[0] for n, _, _ in ops}
    assert {"constant_dynamic-slice_fusion.10", "copy.58",
            "fusion.104"} <= kept - moved
    assert not rule(ops[0][0], (2048, 16, 16, 64))     # another slab
    run = SimpleNamespace(trace=chip_trace, slab=SLAB)
    per_step = _metric("slab_copy_ms").read(run)
    step_ms = _metric("decode_step_ms").read(run)
    assert 0.7 * step_ms < per_step < 0.95 * step_ms


def test_kernel_rule_finds_one_call_per_layer(chip_trace):
    rule = _metric("decode_attn_roofline").is_kernel
    step = trace.module_spans(chip_trace, "jit_decode_step")[:1]
    calls = [n for n, _, _ in trace.ops_in(chip_trace, step) if rule(n)]
    assert len(calls) == 24


def test_readers_on_the_recorded_trace(chip_trace):
    steps = trace.module_spans(chip_trace, "jit_decode_step")
    run = SimpleNamespace(
        trace=chip_trace, slab=SLAB,
        decode_calls=[np.array([900] * 8 + [0] * 8)] * len(steps),
        dims=dict(D=1024, H=16, K=16, hd=64, F=2816, L=24, V=151936),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    roof = _metric("decode_attn_roofline").read(run)
    mfu = _metric("decode_mfu").read(run)
    assert 0 < roof < 100 and 0 < mfu < 100
    gaps = trace.idle_gaps(chip_trace)
    names = {s[0] for s in chip_trace["spans"]} | {"between ticks"}
    assert gaps and all(g[0] in names and g[1] > 0 for g in gaps)
