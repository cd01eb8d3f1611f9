"""ContinuousEngine's host spans and reply stamps: a tiny engine on a
bounded queue with leases, served under ``jax.profiler.trace``, its host
plane read back with ``jax.profiler.ProfileData``."""

import glob

import numpy as np
import pytest

import jax
from jax.profiler import TraceAnnotation

from repro.configs.qwen1_5_0_5b import SMOKE
from repro.core import reset_session
from repro.core.queues import Queue
from repro.kernels.decode_attention import paged_block_rows
from repro.models.model import build_model
from repro.serve import ContinuousEngine, ServeClient
from repro.serve import engine as engine_mod

SPANS = (engine_mod.SPAN_STEP, engine_mod.SPAN_ADMIT,
         engine_mod.SPAN_KV_POLL, engine_mod.SPAN_PREFILL,
         engine_mod.SPAN_PREFILL_WAIT, engine_mod.SPAN_DECODE,
         engine_mod.SPAN_DECODE_DISPATCH, engine_mod.SPAN_DECODE_WAIT,
         engine_mod.SPAN_EMIT, engine_mod.SPAN_KV_REPLY,
         engine_mod.SPAN_KV_RENEW)
#: where each span may sit: the innermost ``serve.*`` span around it
PARENTS = {
    "serve.step": {None},
    "serve.admit": {"serve.step"},
    "serve.kv.poll": {"serve.admit"},
    "serve.prefill": {"serve.step"},
    "serve.prefill.wait": {"serve.step"},
    "serve.decode": {"serve.step"},
    "serve.decode.dispatch": {"serve.decode"},
    "serve.decode.wait": {"serve.decode"},
    "serve.emit": {"serve.decode"},
    # a refusal, a one-token reply at prefill, a reply at decode
    "serve.kv.reply": {"serve.admit", "serve.step", "serve.emit"},
    "serve.kv.renew": {"serve.step"},
}
CHUNK = 4
MAX_LEN = 64
#: (prompt length, output tokens); the last does not fit and is refused
REQUESTS = [(5, 6), (9, 4), (3, 1), (12, 5), (2, 7), (7, 3), (40, 30)]


@pytest.fixture(scope="module")
def model_params():
    m = build_model(SMOKE)
    return m, m.init(jax.random.PRNGKey(0))


class _CmdSpans:
    """The engine's store, with a ``test.cmd`` span around each command,
    so commands and engine spans share the profiler's clock."""

    def __init__(self, store):
        self._inner = store

    def __getattr__(self, name):
        fn = getattr(self._inner, name)

        def call(*a, **kw):
            with TraceAnnotation("test.cmd", cmd=name):
                return fn(*a, **kw)
        return call


def _events(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of the host plane's
    ``serve.*`` and ``test.*`` events, sorted, with each event's
    innermost enclosing ``serve.*`` span."""
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    data = jax.profiler.ProfileData.from_file(path)
    evs = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "test.")):
                    evs.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                {k: v for k, v in e.stats}))
    evs.sort(key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, s, e, stats in evs:
        while stack and s >= stack[-1][1]:
            stack.pop()
        parent = next((n for n, _ in reversed(stack)
                       if n.startswith("serve.")), None)
        out.append({"name": name, "start": s, "end": e, "stats": stats,
                    "parent": parent})
        stack.append((name, e))
    return out


@pytest.fixture(scope="module")
def served(model_params, tmp_path_factory):
    """Serve ``REQUESTS`` through a bounded leased queue under the
    profiler; the events, the replies and each decode call's live slots
    and attended positions, read from the call's own arguments."""
    reset_session()
    m, params = model_params
    q = Queue(maxsize=16)
    client = ServeClient(q)
    # lease_ttl_s=0: every tick that holds a lease renews it
    eng = ContinuousEngine(m, params, max_slots=3, page_size=8,
                           max_len=MAX_LEN, prefill_chunk=CHUNK, eos_id=None,
                           request_queue=q, lease=True, lease_ttl_s=0.0)
    eng.submit([3, 4, 5, 6, 7], 2)             # compile both programs
    eng.run_until_idle()
    eng._store = _CmdSpans(eng._store)
    calls, blocks = [], []
    decode = eng._decode
    T = paged_block_rows(eng.page_size,
                         SMOKE.num_kv_heads * SMOKE.hd)

    def recorded(params, pages, tokens, tables, lengths, mask):
        live = np.asarray(mask)
        att = np.where(live, np.asarray(lengths) + 1, 0)
        calls.append((int(live.sum()), int(att.sum())))
        blocks.append(sum(-(-int(n) // T) for n in att))
        return decode(params, pages, tokens, tables, lengths, mask)
    eng._decode = recorded

    rng = np.random.default_rng(3)
    rids = [client.submit(rng.integers(2, SMOKE.vocab_size, p).tolist(), n)
            for p, n in REQUESTS]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        while q.qsize() or eng.active:
            eng.step()
    replies = [client.result(r, timeout=5.0) for r in rids]
    return {"events": _events(trace_dir), "replies": replies,
            "calls": calls, "blocks": blocks}


def _named(served, name):
    return [e for e in served["events"] if e["name"] == name]


@pytest.mark.parametrize("name", SPANS)
def test_every_span_appears(served, name):
    assert _named(served, name)


@pytest.mark.parametrize("name", SPANS)
def test_spans_nest_as_documented(served, name):
    parents = {e["parent"] for e in _named(served, name)}
    assert parents and parents <= PARENTS[name], parents


def test_decode_span_counts_live_slots_and_positions(served):
    got = [(e["stats"]["tokens"], e["stats"]["kv_positions"])
           for e in _named(served, "serve.decode")]
    assert got == served["calls"]
    assert max(t for t, _ in got) > 1      # the batch held several


def test_decode_span_counts_the_kernels_blocks(served):
    """``attn_blocks`` is the number of blocks of the decode kernel's
    walk (``paged_block_rows`` positions each) over the call's live
    lengths."""
    got = [e["stats"]["attn_blocks"] for e in _named(served, "serve.decode")]
    assert got == served["blocks"]
    assert max(got) > 1


def test_decode_span_holds_one_call(served):
    for d in _named(served, "serve.decode"):
        inside = [e["name"] for e in served["events"]
                  if e["parent"] == "serve.decode"
                  and d["start"] <= e["start"] < d["end"]]
        assert inside == ["serve.decode.dispatch", "serve.decode.wait",
                          "serve.emit"]


def test_prefill_spans_cover_each_prompt_in_chunks(served):
    by_rid = {}
    for e in _named(served, "serve.prefill"):
        st = e["stats"]
        by_rid.setdefault(st["rid"], []).append((st["start"], st["tokens"]))
    served_lens = [p for p, n in REQUESTS if p + n <= MAX_LEN]
    assert sorted(sum(t for _, t in c) for c in by_rid.values()) == \
        sorted(served_lens)
    for chunks in by_rid.values():
        assert [s for s, _ in chunks] == [CHUNK * i
                                          for i in range(len(chunks))]
    waits = {e["stats"]["rid"] for e in _named(served, "serve.prefill.wait")}
    assert waits == set(by_rid)


def test_every_store_command_is_inside_one_kv_span(served):
    cmds = [e for e in served["events"] if e["name"] == "test.cmd"]
    assert {c["stats"]["cmd"] for c in cmds} >= {
        "blpop_lease", "rpush", "lease_release", "lease_renew"}
    for c in cmds:
        assert c["parent"] is not None and \
            c["parent"].startswith("serve.kv."), c
    kv = [e for e in served["events"] if e["name"].startswith("serve.kv.")]
    assert all(not e["parent"].startswith("serve.kv.") for e in kv)


def test_sound_replies_order_their_stamps(served):
    sound = [r for r in served["replies"] if "error" not in r]
    assert len(sound) == len(REQUESTS) - 1
    for r in sound:
        assert 0 <= r["queue_s"] <= r["ttft_s"] <= r["completion_s"]


def test_error_reply_carries_queue_s(served):
    (err,) = [r for r in served["replies"] if "error" in r]
    assert err["queue_s"] >= 0 and err["tokens"] == []


def test_queue_s_counts_the_wait_for_a_slot(model_params):
    """With one slot, the second request waits in the queue until the
    first has its last token."""
    m, params = model_params
    eng = ContinuousEngine(m, params, max_slots=1, page_size=8, max_len=32,
                           prefill_chunk=CHUNK, eos_id=None)
    t = engine_mod.time.time()
    a = eng.submit([3, 4, 5], 3, submitted_at=t)
    b = eng.submit([6, 7], 3, submitted_at=t)
    eng.run_until_idle()
    ra, rb = eng.results[a], eng.results[b]
    assert ra["queue_s"] <= ra["ttft_s"] <= ra["completion_s"]
    assert rb["queue_s"] >= ra["completion_s"]


def test_attention_lengths(model_params):
    """A decoding slot attends its written entries plus the new token's;
    a slot mid-prefill and a free slot attend nothing."""
    m, params = model_params
    eng = ContinuousEngine(m, params, max_slots=3, page_size=8, max_len=64,
                           prefill_chunk=CHUNK, eos_id=None)
    eng.submit([3, 4, 5], 10)                  # one chunk
    eng.step()                                 # prefill + first decode
    assert list(eng.attention_lengths()) == [5, 0, 0]
    eng.submit(list(range(2, 14)), 4)          # three chunks
    eng.step()
    assert list(eng.attention_lengths()) == [6, 0, 0]
    np.testing.assert_array_equal(
        eng.attention_lengths(), np.where(eng._mask, eng._lengths + 1, 0))
