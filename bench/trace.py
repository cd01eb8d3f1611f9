"""From a profiler trace to device times: the benchmark's own reduction.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three lists, each of ``(name, start_ns, end_ns)``: the ``XLA Modules``
and ``XLA Ops`` lines of the first TPU (``/device:TPU:0``), and the
harness's own host spans (``TraceAnnotation`` events named ``bench.*``)
from the host plane. It keeps each op name's stats once, for the rules
that classify ops. Without a TPU plane it returns None, and every
device metric then finds nothing to read.

The rest are pure functions of those lists, tested on a recorded trace
kept in ``bench/tests/data``. Device busy time is the union of op
intervals, so nested and overlapping ops count once (the union is
copied from ``scripts/measure_steps.py``).
"""

from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, List, Optional, Tuple

Span = Tuple[str, int, int]
DEVICE_PLANE = "/device:TPU:0"
SPAN_PREFIX = "bench."


def load(trace_dir) -> Optional[dict]:
    import jax

    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if not paths:
        return None
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = {"modules": [], "ops": [], "spans": [], "op_stats": {}}
    found = False
    for plane in data.planes:
        if plane.name == DEVICE_PLANE:
            found = True
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                stats = out["op_stats"]
                for e in line.events:
                    name = e.name
                    out[key].append((name, int(e.start_ns), int(e.end_ns)))
                    if key == "ops" and name not in stats:
                        stats[name] = {k: _plain(v) for k, v in e.stats}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["spans"].append((e.name[len(SPAN_PREFIX):],
                                             int(e.start_ns), int(e.end_ns)))
    if not found:
        return None
    for key in ("modules", "ops", "spans"):
        out[key].sort(key=lambda s: (s[1], -s[2]))
    return out


#: an op's event name is its HLO instruction: ``%name = shape opcode(...)``
_HLO = re.compile(r"^%(\S+) = (.*?) ([\w\-]+)\(")


def hlo_parts(name: str) -> Tuple[str, str]:
    """``(instruction name, opcode)`` of an op event's name; a name that
    is not HLO text is its own instruction name, with no opcode."""
    m = _HLO.match(name)
    return (m.group(1), m.group(3)) if m else (name, "")


def _plain(v):
    return v if isinstance(v, (int, float, str)) else str(v)


# ------------------------------------------------------------- reductions


def union_ns(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    busy, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy


def merged(intervals) -> List[Tuple[int, int]]:
    """The union of ``(start, end)`` intervals as disjoint sorted runs."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(ops: List[Span]) -> List[Tuple[str, int]]:
    """Each op's time not covered by ops nested inside it (a loop op
    holds its body's ops), in the order given (sorted by start)."""
    out: List[Tuple[str, int]] = []
    stack: List[list] = []          # [name, end, self_ns, cursor]

    def close(top):
        out.append((top[0], top[2] + top[1] - top[3]))

    for name, s, e in ops:
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            parent[2] += max(0, s - parent[3])
            parent[3] = max(parent[3], min(e, parent[1]))
        stack.append([name, e, 0, s])
    while stack:
        close(stack.pop())
    return out


def module_spans(tr: dict, prefix: str) -> List[Span]:
    return [m for m in tr["modules"] if m[0].startswith(prefix)]


def ops_in(tr: dict, modules: List[Span]) -> List[Span]:
    """The ops that lie inside any of ``modules`` (sorted spans)."""
    starts = [m[1] for m in modules]
    out = []
    for op in tr["ops"]:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= modules[i][2]:
            out.append(op)
    return out


def median(xs):
    xs = sorted(xs)
    if not xs:
        return None
    n = len(xs)
    return xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2


def top_ops(tr: dict, n: int = 10) -> List[list]:
    """The ``n`` op names with the most device self time, in seconds."""
    per: Dict[str, int] = {}
    for name, ns in self_times(tr["ops"]):
        per[name] = per.get(name, 0) + ns
    return [[k, v / 1e9] for k, v in
            sorted(per.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> List[list]:
    """The ``n`` longest device idle gaps inside the trace's spans, each
    named by the innermost harness span open at its midpoint."""
    if not tr["spans"]:
        return []
    lo = min(s for _, s, _ in tr["spans"])
    hi = max(e for _, _, e in tr["spans"])
    runs = merged((s, e) for _, s, e in tr["ops"])
    edges = [lo] + [x for r in runs for x in r] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    out = []
    for ns, a, b in gaps[:n]:
        mid = (a + b) // 2
        open_ = [(e - s, name) for name, s, e in tr["spans"] if s <= mid < e]
        out.append([min(open_)[1] if open_ else "between ticks", ns / 1e9])
    return out


def op_summary(tr: dict, n: int = 300) -> List[dict]:
    """Per op name: count, self time, and the time inside decode and
    prefill modules, for the ``n`` names with the most self time."""
    per: Dict[str, dict] = {}
    for name, ns in self_times(tr["ops"]):
        d = per.setdefault(name, {"count": 0, "self_ns": 0, "decode_ns": 0,
                                  "prefill_ns": 0})
        d["count"] += 1
        d["self_ns"] += ns
    for key, prefix in (("decode_ns", "jit_decode_step"),
                        ("prefill_ns", "jit_prefill_step")):
        for name, s, e in ops_in(tr, module_spans(tr, prefix)):
            per[name][key] += e - s
    top = sorted(per.items(), key=lambda kv: -kv[1]["self_ns"])[:n]
    return [dict(v, name=k, stats=tr["op_stats"].get(k, {})) for k, v in top]


def excerpt(tr: dict, decode_steps=2, prefill_steps=1) -> dict:
    """The first decode and prefill modules of ``tr`` with their ops and
    the spans around them, op names kept once in a table: a trace small
    enough for the tests. With None, every module and op."""
    if decode_steps is None:
        mods, ops = tr["modules"], tr["ops"]
    else:
        mods = (module_spans(tr, "jit_decode_step")[:decode_steps]
                + module_spans(tr, "jit_prefill_step")[:prefill_steps])
        mods.sort(key=lambda m: m[1])
        ops = ops_in(tr, mods)
    names = sorted({o[0] for o in ops})
    index = {n: i for i, n in enumerate(names)}
    lo = min((m[1] for m in mods), default=0)
    hi = max((m[2] for m in mods), default=0)
    return {"names": names, "modules": mods,
            "ops": [[index[n], s, e] for n, s, e in ops],
            "spans": [sp for sp in tr["spans"] if sp[2] > lo and sp[1] < hi],
            "op_stats": {n: tr["op_stats"].get(n, {}) for n in names}}


def from_excerpt(ex: dict) -> dict:
    """The trace that ``excerpt`` kept, in ``load``'s form."""
    names = ex["names"]
    return {"modules": [tuple(m) for m in ex["modules"]],
            "ops": [(names[i], s, e) for i, s, e in ex["ops"]],
            "spans": [tuple(sp) for sp in ex["spans"]],
            "op_stats": ex["op_stats"]}
