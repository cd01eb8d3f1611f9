"""Continuous-batching serving: paged numerics, scheduler behavior,
queue admission, lease lifecycle, and the static engine's zero-cost /
early-exit guarantees."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.phi3_5_moe import SMOKE as MOE_SMOKE
from repro.configs.qwen1_5_0_5b import SMOKE
from repro.core.queues import Queue
from repro.core.session import get_session
from repro.models.model import build_model
from repro.serve import (ContinuousEngine, PageAllocator, ServeClient,
                         ServeEngine)

EOS = 1


@pytest.fixture(scope="module")
def model_params():
    m = build_model(SMOKE)
    return m, m.init(jax.random.PRNGKey(0))


def _static_row(m, params, toks, max_new, eos=None, max_len=64):
    eng = ServeEngine(m, params, max_len=max_len, eos_id=eos)
    return np.asarray(eng.generate(jnp.asarray([toks], jnp.int32),
                                   max_new_tokens=max_new))[0]


# ------------------------------------------------------------------ paging


class TestPageAllocator:
    def test_page_zero_reserved(self):
        a = PageAllocator(8, 4)
        got = a.alloc(7)
        assert got is not None and 0 not in got
        assert a.alloc(1) is None          # exhausted
        a.free(got)
        assert a.free_pages == 7

    def test_all_or_nothing(self):
        a = PageAllocator(4, 4)
        assert a.alloc(5) is None
        assert a.free_pages == 3           # untouched after failed alloc

    def test_double_free_rejected(self):
        a = PageAllocator(4, 4)
        p = a.alloc(1)
        a.free(p)
        with pytest.raises(ValueError):
            a.free(p)

    def test_pages_for(self):
        a = PageAllocator(8, 16)
        assert a.pages_for(1) == 1
        assert a.pages_for(16) == 1
        assert a.pages_for(17) == 2


# ------------------------------------------------------ paged model numerics


class TestPagedNumerics:
    def test_decode_paged_matches_contiguous(self, model_params):
        """Per-step decode math through the page table must equal the
        contiguous cache path (the PR's numerics gate)."""
        m, params = model_params
        B, page, M = 3, 8, 4
        S = page * M
        lens = [5, 1, 12]
        rng = np.random.default_rng(0)
        toks = rng.integers(2, SMOKE.vocab_size, (B, max(lens)))

        caches, ref_next = [], []
        for b in range(B):
            lg, cache = m.prefill(
                params, {"tokens": jnp.asarray(toks[b:b + 1, :lens[b]])}, S)
            caches.append(cache)
            ref_next.append(int(np.argmax(np.asarray(lg[0]))))

        pages = m.init_paged_cache(B * M + 1, page)
        table = np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
        C = 4
        for b in range(B):
            start = 0
            while start < lens[b]:
                n = min(C, lens[b] - start)
                chunk = np.zeros((1, C), np.int32)
                chunk[0, :n] = toks[b, start:start + n]
                lg, pages = m.prefill_paged_chunk(
                    params, pages, jnp.asarray(chunk),
                    jnp.asarray(table[b]), jnp.int32(start), jnp.int32(n))
                start += n
            assert int(np.argmax(np.asarray(lg[0]))) == ref_next[b]

        nxt = jnp.asarray(ref_next, jnp.int32)
        lg_p, _, _ = m.decode_paged(params, pages, nxt, jnp.asarray(table),
                                 jnp.asarray(lens, jnp.int32),
                                 jnp.ones((B,), bool))
        for b in range(B):
            lg_c, _ = m.decode(params, caches[b], nxt[b:b + 1])
            np.testing.assert_allclose(np.asarray(lg_p[b]),
                                       np.asarray(lg_c[0]),
                                       atol=2e-4, rtol=2e-4)

    def test_masked_slots_do_not_perturb_live_ones(self, model_params):
        m, params = model_params
        B, page, M = 3, 8, 2
        pages = m.init_paged_cache(B * M + 1, page)
        table = np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
        toks = jnp.asarray([4, 5, 6], jnp.int32)
        lens = jnp.asarray([3, 2, 1], jnp.int32)
        all_on, _, _ = m.decode_paged(params, pages, toks, jnp.asarray(table),
                                   lens, jnp.ones((B,), bool))
        # re-run from the SAME slab with slot 1 masked off
        one_off, _, _ = m.decode_paged(params, pages, toks, jnp.asarray(table),
                                    lens, jnp.asarray([True, False, True]))
        np.testing.assert_allclose(np.asarray(one_off[0]),
                                   np.asarray(all_on[0]), atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(one_off[2]),
                                   np.asarray(all_on[2]), atol=2e-4, rtol=2e-4)

    def test_paged_unsupported_family_raises(self):
        m = build_model(SMOKE.replace(family="ssm"))
        with pytest.raises(ValueError, match="KV-cache family"):
            m.init_paged_cache(4, 8)


# ------------------------------------------------------- continuous engine


class TestContinuousEngine:
    def test_outputs_match_static_engine(self, model_params):
        """Mixed prompt/output lengths batched continuously produce the
        exact tokens the static engine produces per request."""
        m, params = model_params
        eng = ContinuousEngine(m, params, max_slots=3, page_size=8,
                               max_len=64, prefill_chunk=4, eos_id=EOS)
        rng = np.random.default_rng(7)
        reqs = [(rng.integers(2, SMOKE.vocab_size,
                              int(rng.integers(1, 12))).tolist(),
                 int(rng.integers(1, 10))) for _ in range(6)]
        rids = [eng.submit(t, mn) for t, mn in reqs]
        eng.run_until_idle()
        for rid, (toks, mn) in zip(rids, reqs):
            got = eng.results[rid]["tokens"]
            row = _static_row(m, params, toks, mn, eos=EOS)
            assert list(row[:len(got)]) == got
            assert all(t == EOS for t in row[len(got):])

    def test_join_mid_flight_single_compile(self, model_params):
        """A request joining a live batch changes array contents only:
        no recompilation, and in-flight outputs are unperturbed."""
        m, params = model_params
        eng = ContinuousEngine(m, params, max_slots=4, page_size=8,
                               max_len=64, prefill_chunk=4, eos_id=None)
        r1 = eng.submit([5, 6, 7, 8], 12)
        for _ in range(5):
            eng.step()
        assert eng.active == 1            # r1 mid-decode
        r2 = eng.submit([9, 10, 11], 6)   # joins the live batch
        eng.run_until_idle()
        assert eng.decode_compiles == 1
        for rid, toks, mn in [(r1, [5, 6, 7, 8], 12), (r2, [9, 10, 11], 6)]:
            row = _static_row(m, params, toks, mn)
            assert eng.results[rid]["tokens"] == list(row)

    def test_prefill_attn_blocks_counts_the_walked_blocks(self, model_params):
        """``metrics["prefill_attn_blocks"]`` sums, over the prompt chunks,
        ``ceil((start + tokens) / T)`` with ``T`` the model's block of the
        walk (a page of 8 here): prompts of 5, 13 and 21 in chunks of 4
        end at 4, 5 | 4, 8, 12, 13 | 4, 8, 12, 16, 20, 21."""
        m, params = model_params
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=64, prefill_chunk=4, eos_id=None)
        assert m.decode_block_rows(8) == 8
        for n in (5, 13, 21):
            eng.submit(list(range(2, 2 + n)), 2)
        eng.run_until_idle()
        assert eng.metrics["prefill_chunks"] == 2 + 4 + 6
        assert eng.metrics["prefill_attn_blocks"] == (1 + 1) + (
            1 + 1 + 2 + 2) + (1 + 1 + 2 + 2 + 3 + 3)

    def test_prefill_kernel_serves_the_dense_paths_tokens(self, model_params,
                                                          monkeypatch):
        """Served with the paged kernels (interpret mode, as on a TPU),
        the tokens are the dense path's, prompts across several of the
        walk's blocks included."""
        import functools

        from repro.kernels import decode_attention as da
        from repro.kernels import ops

        m, params = model_params
        rng = np.random.default_rng(5)
        reqs = [(rng.integers(2, SMOKE.vocab_size, n).tolist(), 4)
                for n in (5, 13, 21, 30)]

        def serve():
            eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                                   max_len=64, prefill_chunk=4, eos_id=None)
            rids = [eng.submit(t, n) for t, n in reqs]
            eng.run_until_idle()
            return [eng.results[r]["tokens"] for r in rids]

        dense = serve()
        monkeypatch.setattr(ops, "pallas_mode", lambda: "tpu")
        monkeypatch.setattr(ops, "paged_prefill_kernel", functools.partial(
            da.paged_prefill_attention, interpret=True))
        monkeypatch.setattr(ops, "flash_decode_paged", functools.partial(
            da.flash_decode_paged, interpret=True))
        assert serve() == dense

    def test_eviction_returns_pages(self, model_params):
        m, params = model_params
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=64, prefill_chunk=8, eos_id=None)
        total = eng.alloc.num_pages - 1
        eng.submit([3, 4, 5], 4)
        eng.step()
        assert eng.alloc.free_pages < total   # pages held while active
        eng.run_until_idle()
        assert eng.alloc.free_pages == total  # all freed at eviction
        assert all(t == 0 for t in np.asarray(eng._tables).ravel())

    def test_preemption_by_recompute(self, model_params):
        """Slab too small for both requests: the youngest is preempted,
        re-queued, and still produces exactly the static tokens."""
        m, params = model_params
        eng = ContinuousEngine(m, params, max_slots=2, page_size=4,
                               max_len=32, num_pages=5, prefill_chunk=4,
                               eos_id=None)
        r1 = eng.submit([5, 6, 7], 8)
        r2 = eng.submit([9, 10, 11], 8)
        eng.run_until_idle()
        assert eng.metrics["preempted"] >= 1
        for rid, toks in [(r1, [5, 6, 7]), (r2, [9, 10, 11])]:
            row = _static_row(m, params, toks, 8, max_len=32)
            assert eng.results[rid]["tokens"] == list(row)

    @pytest.mark.parametrize("page_size", [0, 8193])
    def test_illegal_page_size_rejected(self, model_params, page_size):
        """The page is the decode kernel's block: a size it cannot tile
        fails at construction, not at the first compile (8192 rows of
        one 128-lane row, K * hd = 64 here, is the largest it takes)."""
        m, params = model_params
        with pytest.raises(ValueError, match="page_size"):
            ContinuousEngine(m, params, page_size=page_size)

    def test_oversize_request_rejected(self, model_params):
        m, params = model_params
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=32, eos_id=None)
        rid = eng.submit(list(range(2, 30)), 16)  # 28 + 16 > 32
        eng.run_until_idle()
        assert "error" in eng.results[rid]
        assert eng.metrics["rejected"] == 1

    def test_result_latency_fields(self, model_params):
        m, params = model_params
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=64, eos_id=None)
        rid = eng.submit([3, 4, 5], 4)
        eng.run_until_idle()
        res = eng.results[rid]
        assert res["ttft_s"] is not None
        assert 0 <= res["ttft_s"] <= res["completion_s"]

    def test_moe_family(self):
        """MoE decode over the slab: generous capacity so idle slots
        cannot steal expert capacity from live rows."""
        cfg = MOE_SMOKE.replace(capacity_factor=float(MOE_SMOKE.num_experts))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=32, prefill_chunk=4, eos_id=None)
        rid = eng.submit([3, 4, 5, 6], 5)
        eng.run_until_idle()
        row = _static_row(m, params, [3, 4, 5, 6], 5, max_len=32)
        assert eng.results[rid]["tokens"] == list(row)


# ------------------------------------------------------------ queue plane


class TestQueueAdmission:
    def test_client_round_trip(self, model_params):
        m, params = model_params
        q = Queue(maxsize=4)
        client = ServeClient(q)
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=64, prefill_chunk=4, eos_id=EOS,
                               request_queue=q)
        rid = client.submit([3, 4, 5, 6], 6)
        eng.run_until_idle()
        res = client.result(rid, timeout=2.0)
        row = _static_row(m, params, [3, 4, 5, 6], 6, eos=EOS)
        assert res["tokens"] == list(row[:len(res["tokens"])])

    def test_bounded_queue_backpressures_submit(self, model_params):
        m, params = model_params
        q = Queue(maxsize=1)
        client = ServeClient(q)
        client.submit([3, 4], 2)
        with pytest.raises(TimeoutError):
            client.submit([5, 6], 2, timeout=0.05)  # queue full, no engine

    def test_two_engines_share_one_queue_exactly_once(self, model_params):
        m, params = model_params
        q = Queue(maxsize=8)
        client = ServeClient(q)
        mk = lambda: ContinuousEngine(m, params, max_slots=2, page_size=8,
                                      max_len=64, prefill_chunk=4,
                                      eos_id=EOS, request_queue=q)
        ea, eb = mk(), mk()
        rids = [client.submit([7, 8, 9, i + 2], 4) for i in range(6)]
        while q.qsize() or ea.active or eb.active:
            ea.step()
            eb.step()
        results = [client.result(r, timeout=2.0) for r in rids]
        assert all(r["tokens"] for r in results)
        assert ea.metrics["completed"] + eb.metrics["completed"] == 6

    def test_lease_lifecycle(self, model_params):
        """Lease mode: the request is visible in the inflight hash while
        being served (reclaimable by lease_reap if we crash) and the
        lease is released — not expired — on completion."""
        m, params = model_params
        q = Queue(maxsize=4)
        client = ServeClient(q)
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=64, prefill_chunk=2, eos_id=None,
                               request_queue=q, lease=True, lease_ttl_s=30.0)
        rid = client.submit([3, 4, 5, 6, 7, 8], 6)
        store = get_session().store
        inflight = q._key("inflight")
        eng.step()                          # admits + starts prefill
        held = store.hgetall(inflight)
        assert rid in held
        deadline, attempt, worker, _payload = held[rid]
        assert attempt == 0 and worker == eng.worker_id
        eng.run_until_idle()
        assert not store.hgetall(inflight)  # released, not leaked
        assert store.metrics.commands.get("LEASERELEASE", 0) >= 1
        assert client.result(rid, timeout=2.0)["tokens"]

    def test_lease_unaware_producer_still_served(self, model_params):
        """A plain Queue.put (serialized blob, no lease triple) is still
        admitted — it just doesn't get crash protection."""
        m, params = model_params
        q = Queue()
        q.put({"id": "plain", "tokens": [4, 5, 6],
               "max_new_tokens": 3, "submitted_at": None})
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=64, eos_id=None, request_queue=q,
                               lease=True)
        eng.run_until_idle()
        client = ServeClient(q)
        res = client.result("plain", timeout=2.0)
        row = _static_row(m, params, [4, 5, 6], 3)
        assert res["tokens"] == list(row)


# --------------------------------------------------- static engine contract


class TestZeroCostWhenOff:
    def test_static_engine_issues_no_kv_commands(self, model_params):
        """The legacy static path must stay byte-identical when the
        continuous machinery is unused: zero store commands, no slab."""
        m, params = model_params
        store = get_session().store
        base = store.metrics.total_commands()
        eng = ServeEngine(m, params, max_len=32, eos_id=EOS)
        eng.generate(jnp.asarray([[3, 4, 5]], jnp.int32), max_new_tokens=4)
        assert store.metrics.total_commands() == base
        assert not hasattr(eng, "_pages") and not hasattr(eng, "alloc")

    def test_local_continuous_engine_issues_no_kv_commands(self, model_params):
        """Queue-less ContinuousEngine never touches the store either."""
        m, params = model_params
        store = get_session().store
        base = store.metrics.total_commands()
        eng = ContinuousEngine(m, params, max_slots=2, page_size=8,
                               max_len=32, eos_id=None)
        eng.submit([3, 4], 2)
        eng.run_until_idle()
        assert store.metrics.total_commands() == base


class TestServeEngineEarlyExit:
    def test_stops_stepping_after_all_eos(self, model_params):
        """Once every row has emitted eos the decode loop must break,
        not keep stepping to max_new_tokens (the PR 10 bug fix)."""
        m, params = model_params
        prompts = jnp.asarray([[3, 4, 5]], jnp.int32)
        probe = ServeEngine(m, params, max_len=64, eos_id=None)
        row = np.asarray(probe.generate(prompts, max_new_tokens=30))[0]
        assert probe._steps_run == 29      # no eos: full budget
        eos = int(row[2])                  # guaranteed to appear by step 2
        eng = ServeEngine(m, params, max_len=64, eos_id=eos)
        out = np.asarray(eng.generate(prompts, max_new_tokens=30))[0]
        assert eng._steps_run <= 2         # early exit fired
        assert out.shape == (30,)
        first = int(np.argmax(row == eos))
        assert list(out[:first + 1]) == list(row[:first + 1])
        assert all(t == eos for t in out[first:])

    def test_on_first_token_fires_before_decode(self, model_params):
        m, params = model_params
        seen = []
        eng = ServeEngine(m, params, max_len=64, eos_id=None)
        out = eng.generate(jnp.asarray([[3, 4, 5]], jnp.int32),
                           max_new_tokens=4,
                           on_first_token=lambda t: seen.append(np.asarray(t)))
        assert len(seen) == 1
        assert int(seen[0][0]) == int(np.asarray(out)[0, 0])
