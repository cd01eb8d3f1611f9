"""LM serving engines over the KV plane: static batch and continuous batching.

Two engines share the model's serving entry points:

``ServeEngine`` — the legacy static batcher: one prefill over the whole
batch, lock-step greedy decode, the batch held until every row finishes
(with eos-aware early exit). It issues **no** KV-store commands and
allocates **no** page slab — plain contiguous caches only — so code that
never opts into continuous serving pays nothing for it.

``ContinuousEngine`` — continuous batching over a **paged** decode cache
with admission on the KV plane's bounded queues.

Admission contract
------------------
Requests arrive on a ``core.queues.Queue`` (or via local ``submit``).
Producers (``ServeClient.submit``) push the **raw lease triple**
``(attempt, request_id, payload)`` with the store's fused commands —
``blpop_rpush(slots, items, entry)`` when the queue is bounded (so a
full queue back-pressures producers: that is the admission control), or
a plain ``rpush`` otherwise. Because the entry is a raw triple rather
than an opaque serialized blob, the engine can pop it with
``blpop_lease`` and inherit the pool plane's at-least-once machinery:
the lease is renewed every ``ttl/3`` while the request is in flight and
``lease_release``d on completion, so a crashed engine's requests are
reclaimable by ``lease_reap`` exactly like pool tasks. Several engines
may share one queue — ``blpop`` atomicity gives exactly-once admission
across replicas. Results return on the per-request list
``<queue>:resp:<request_id>``.

Scheduling contract
-------------------
The decode step is jitted once over a **fixed batch shape**: per-slot
token / length / page-table arrays of size ``max_slots`` plus a boolean
``slot_mask``. Requests joining or leaving the batch only change array
*contents*, never shapes, so batch-membership churn causes zero
recompilation (asserted by ``decode_compiles`` staying at 1). Each
``step()`` does: (1) admit requests into free slots while pages last;
(2) run at most ONE length-``prefill_chunk`` prompt chunk for the oldest
still-prefilling slot — chunking bounds how long a long prompt can
starve decode; (3) run one decode step for all decoding slots. A slot
mid-prefill is masked out of the decode batch (null-page write, zero
attention length) until its prompt completes.

Page table layout & eviction contract
-------------------------------------
The KV cache is a shared slab ``[L, num_pages, page_size, K * hd]``
for keys and one for values, the kv heads side by side on the minor
dim; for latent attention (MLA) it is one latent slab ``[L, num_pages,
page_size, lanes]``, a latent row per token (``Model.init_paged_cache``).
The engine passes the slab dict through whole, whatever its arrays.
Token ``t`` of the request in slot ``b`` lives at page
``table[b, t // page_size]``, offset ``t % page_size``. Page 0 is the
null page (never referenced by a live table; absorbs masked writes).
The decode and prefill steps take the slab donated (on a TPU) and carry
it whole through the layer loop, writing each layer's new K/V in place.
Pages are allocated at admission (enough for the prompt) and grown one
page at a time when decode crosses a page boundary. On eos or on
reaching ``max_new_tokens`` the slot's pages return to the free list
and the slot frees up — that is the only *eviction*. When growth finds
the free list empty, the **youngest** active request is preempted by
recompute: its pages are freed, its generated tokens discarded, and the
request re-queued locally for re-prefill (greedy decoding is
deterministic, so the final output is unchanged; only latency suffers).
A request that cannot fit even alone (prompt + output > pages) is
rejected with an error result rather than thrashing.

Replies
-------
A reply carries ``id``, ``tokens`` and, all in seconds since the
request's ``submitted_at``, ``queue_s`` (the admission that placed it in
a slot), ``ttft_s`` (its first token) and ``completion_s`` (its last):
``0 <= queue_s <= ttft_s <= completion_s``. An error reply carries
``error`` and ``queue_s`` (its refusal).

Spans
-----
``ContinuousEngine.step`` marks its host work with
``jax.profiler.TraceAnnotation`` spans. Nothing switches them on: while
no profiler runs, a span costs about a microsecond of host time (a Xeon
core, jax 0.9.0), about fifteen microseconds a working tick.

- ``serve.step``: one tick, the whole of ``step()``;
- ``serve.admit``: the admission loop;
- ``serve.kv.poll``: one pop from the request queue, with the slot
  token handed back;
- ``serve.prefill`` (``rid``, ``start``, ``tokens``): one prompt chunk,
  from building it to the jitted call's return;
- ``serve.prefill.wait`` (``rid``): the wait for a prompt's first token;
- ``serve.decode`` (``tokens``: live slots, ``kv_positions``: the sum of
  ``attention_lengths()``, ``attn_blocks``: the blocks of
  ``Model.decode_block_rows`` positions that the decode kernel walks in
  each layer, ``ceil(length / block)`` summed over the slots; for an MoE
  model also ``held_expert_tokens``, the call's (token, held expert)
  assignments): one decode call, from the capacity checks to the last
  emitted token, with three children: ``serve.decode.dispatch`` (the
  inputs' copies to the device and the call), ``serve.decode.wait``
  (the wait for its tokens) and ``serve.emit`` (handing them to their
  requests, completions included);
- ``serve.kv.reply`` (``rid``): a reply's lease release and push;
- ``serve.kv.renew``: a round of lease renewals.

Every store command that ``step()`` sends runs inside one ``serve.kv.*``
span. To see them on a live server, trace a window of
``serve_forever`` from another thread of its process, since
``stop_trace`` holds its caller while it writes the window (tens of
seconds for a minute's trace)::

    jax.profiler.start_trace("serve-trace")
    time.sleep(10)
    jax.profiler.stop_trace()

and read the host plane's ``serve.*`` events, which share their clock
with the device's ops, with ``jax.profiler.ProfileData.from_file`` or
TensorBoard.
"""

from __future__ import annotations

import collections
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core import serialization
from ..models.model import Model
from .paging import PageAllocator

# ContinuousEngine's host spans (module docstring, "Spans")
SPAN_STEP = "serve.step"
SPAN_ADMIT = "serve.admit"
SPAN_KV_POLL = "serve.kv.poll"
SPAN_PREFILL = "serve.prefill"
SPAN_PREFILL_WAIT = "serve.prefill.wait"
SPAN_DECODE = "serve.decode"
SPAN_DECODE_DISPATCH = "serve.decode.dispatch"
SPAN_DECODE_WAIT = "serve.decode.wait"
SPAN_EMIT = "serve.emit"
SPAN_KV_REPLY = "serve.kv.reply"
SPAN_KV_RENEW = "serve.kv.renew"


def make_prefill(model: Model, max_len: int):
    def prefill(params, batch):
        return model.prefill(params, batch, max_len)
    return prefill


def counts_held_experts(model: Model) -> bool:
    """Whether the decode step counts (token, held expert) assignments:
    a model with routed experts. Others pay nothing for the counter."""
    return model.cfg.family == "moe"


def make_decode_step(model: Model):
    """decode_step(params, pages, tokens, tables, lengths, mask) ->
    (next_tokens, pages): ``ContinuousEngine``'s decode program. For a
    model with routed experts ``next_tokens`` has one more entry, last:
    the live slots' (token, held expert) assignments over the layers,
    so that the count comes back with the tokens in one transfer."""

    counts = counts_held_experts(model)

    def decode_step(params, pages, tokens, tables, lengths, mask):
        logits, pages, held = model.decode_paged(params, pages, tokens,
                                                 tables, lengths, mask)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if counts:
            toks = jnp.concatenate([toks, held[None]])
        return toks, pages

    return decode_step


def make_prefill_step(model: Model):
    """prefill_step(params, pages, tokens, table, start, n_valid) ->
    (next_token, pages): ``ContinuousEngine``'s prompt-chunk program."""

    def prefill_step(params, pages, tokens, table, start, n_valid):
        logits, pages = model.prefill_paged_chunk(params, pages, tokens,
                                                  table, start, n_valid)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pages

    return prefill_step


def make_serve_step(model: Model, greedy: bool = True):
    """serve_step(params, cache, tokens) -> (next_tokens, logits, cache)."""

    def serve_step(params, cache, tokens):
        logits, cache = model.decode(params, cache, tokens)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, logits, cache

    return serve_step


class ServeEngine:
    """Minimal static-batch generation engine (examples/serve_lm.py).

    Static batch, greedy decoding, eos-aware early exit: once every row
    has emitted ``eos_id`` the decode loop stops and the remaining
    columns are padded with ``eos_id`` (output shape stays
    ``[B, max_new_tokens]``). Issues no KV-store commands and allocates
    no page slab — the continuous-batching machinery is pay-as-you-go.
    """

    def __init__(self, model: Model, params, max_len: int = 256,
                 eos_id: Optional[int] = None):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        self._prefill = jax.jit(make_prefill(model, max_len))
        self._step = jax.jit(make_serve_step(model))
        self._steps_run = 0  # decode steps in the last generate() call

    def generate(self, prompts: jax.Array, max_new_tokens: int = 32,
                 on_first_token: Optional[Callable[[jax.Array], None]] = None
                 ) -> jax.Array:
        """prompts: [B, S] int32 (right-aligned, no padding support needed
        for the demo). Returns [B, max_new_tokens]. ``on_first_token``
        fires with the [B] first sampled tokens as soon as prefill
        produces them (TTFT measurement hook)."""
        self._steps_run = 0
        logits, cache = self._prefill(self.params, {"tokens": prompts})
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if on_first_token is not None:
            on_first_token(jax.block_until_ready(tok))
        out: List[jax.Array] = [tok]
        done = jnp.zeros(tok.shape, bool)
        if self.eos_id is not None:
            done = tok == self.eos_id
        for _ in range(max_new_tokens - 1):
            if self.eos_id is not None and bool(done.all()):
                break  # early exit: every row finished
            tok, _, cache = self._step(self.params, cache, tok)
            self._steps_run += 1
            if self.eos_id is not None:
                done = done | (tok == self.eos_id)
                tok = jnp.where(done, self.eos_id, tok)
            out.append(tok)
        while len(out) < max_new_tokens:  # pad early-exited columns
            out.append(jnp.full_like(tok, self.eos_id))
        return jnp.stack(out, axis=1)


# --------------------------------------------------------------- continuous


@dataclass
class ServeRequest:
    """One generation request as it travels the admission queue."""
    id: str
    tokens: List[int]
    max_new_tokens: int
    submitted_at: Optional[float] = None

    def to_payload(self) -> bytes:
        return serialization.dumps({
            "id": self.id, "tokens": list(map(int, self.tokens)),
            "max_new_tokens": int(self.max_new_tokens),
            "submitted_at": self.submitted_at})

    @staticmethod
    def from_payload(payload: bytes) -> "ServeRequest":
        d = serialization.loads(payload)
        return ServeRequest(id=d["id"], tokens=list(d["tokens"]),
                            max_new_tokens=int(d["max_new_tokens"]),
                            submitted_at=d.get("submitted_at"))


class ServeClient:
    """Submit requests to (and fetch results from) engines on a queue.

    Pushes raw lease triples so engine-side ``blpop_lease`` works (see
    module docstring); a bounded queue back-pressures ``submit`` via the
    fused ``blpop_rpush`` on the slots list — one store command per
    submit, inheriting whatever transport/mux the session store uses.
    """

    def __init__(self, queue):
        self.queue = queue
        self._store = queue._store

    def _resp_key(self, rid: str) -> str:
        return self.queue._key(f"resp:{rid}")

    def submit(self, tokens, max_new_tokens: int = 16,
               rid: Optional[str] = None,
               timeout: Optional[float] = None) -> str:
        rid = rid or uuid.uuid4().hex[:12]
        req = ServeRequest(rid, list(map(int, tokens)), max_new_tokens,
                           submitted_at=time.time())
        entry = (0, rid, req.to_payload())
        if self.queue._maxsize > 0:
            tok = self._store.blpop_rpush(self.queue._slots_key,
                                          self.queue._items_key,
                                          entry, timeout)
            if tok is None:
                raise TimeoutError(f"admission queue full for {timeout}s")
        else:
            self._store.rpush(self.queue._items_key, entry)
        return rid

    def result(self, rid: str, timeout: Optional[float] = None
               ) -> Dict[str, Any]:
        got = self._store.blpop(self._resp_key(rid), timeout)
        if got is None:
            raise TimeoutError(f"no result for {rid} within {timeout}s")
        return serialization.loads(got[1])


@dataclass
class _Slot:
    req: ServeRequest
    attempt: int
    leased: bool            # lease held in the store's inflight hash
    local: bool             # submitted via engine.submit, result kept local
    seq: int                # admission order (preemption picks the youngest)
    pages: List[int] = field(default_factory=list)
    state: str = "prefill"  # 'prefill' -> 'decode'
    prompt_pos: int = 0     # prompt tokens already prefilled
    length: int = 0         # KV cache entries written
    out_tokens: List[int] = field(default_factory=list)
    cur_token: int = 0      # last sampled token (next decode input)
    t_admit: float = 0.0
    t_first: Optional[float] = None


def _t0(req: ServeRequest, slot: _Slot) -> float:
    """What a reply's times count from: the submit, else the admission."""
    return req.submitted_at if req.submitted_at is not None else slot.t_admit


class ContinuousEngine:
    """Continuous-batching engine over the paged KV slab.

    See the module docstring for the admission / scheduling / eviction
    contract. Families: dense / vlm / moe (KV-cache caches only; moe
    with GQA or latent attention). ``page_size`` is also a block of the
    paged decode kernel the model runs: a size that kernel cannot take
    (``Model.check_page_size``: for ``flash_decode_paged`` 1-1024 at 16
    kv heads of 64, for ``latent_decode_paged`` a multiple of 16 that
    divides 512) raises ValueError here, not at the first compile.
    For a model with routed experts ``metrics["held_expert_tokens"]``
    counts the decode steps' (token, held expert) assignments.
    """

    def __init__(self, model: Model, params, *, max_slots: int = 4,
                 page_size: int = 16, max_len: int = 128,
                 num_pages: Optional[int] = None, prefill_chunk: int = 16,
                 eos_id: Optional[int] = None, request_queue=None,
                 lease: bool = False, lease_ttl_s: float = 30.0,
                 worker_id: Optional[str] = None):
        model.check_page_size(page_size)
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.page_size = page_size
        self.max_len = max_len
        self.max_pages_per_slot = -(-max_len // page_size)
        if num_pages is None:
            # roomy default: every slot can hold max_len without preemption
            num_pages = max_slots * self.max_pages_per_slot + 1
        self.prefill_chunk = prefill_chunk
        self.eos_id = eos_id
        self.queue = request_queue
        self.lease = lease and request_queue is not None
        self.lease_ttl_s = lease_ttl_s
        self.worker_id = worker_id or f"serve-{uuid.uuid4().hex[:8]}"
        self._store = None if request_queue is None else request_queue._store

        self.alloc = PageAllocator(num_pages, page_size)
        self._pages = model.init_paged_cache(num_pages, page_size)
        M = self.max_pages_per_slot
        self._tables = np.zeros((max_slots, M), np.int32)   # 0 = null page
        self._lengths = np.zeros((max_slots,), np.int32)
        self._mask = np.zeros((max_slots,), bool)
        self._tokens = np.zeros((max_slots,), np.int32)
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        self._pending: collections.deque = collections.deque()  # local + requeued
        self._seq = 0
        self._last_renew = time.monotonic()
        self.results: Dict[str, Dict[str, Any]] = {}  # local submissions
        self.metrics = {"admitted": 0, "completed": 0, "preempted": 0,
                        "rejected": 0, "decode_steps": 0,
                        "prefill_chunks": 0}
        self._attn_block = model.decode_block_rows(page_size)
        self._counts_held = counts_held_experts(model)
        if self._counts_held:
            self.metrics["held_expert_tokens"] = 0

        donate = (1,) if jax.default_backend() == "tpu" else ()
        self._decode = jax.jit(make_decode_step(model),
                               donate_argnums=donate)
        self._prefill_chunk = jax.jit(make_prefill_step(model),
                                      donate_argnums=donate)

    # ------------------------------------------------------------- metrics

    @property
    def decode_compiles(self) -> int:
        return self._decode._cache_size()

    @property
    def active(self) -> int:
        return sum(s is not None for s in self._slots)

    def attention_lengths(self) -> np.ndarray:
        """Per slot, the cache positions the next decode call attends:
        the entries written plus the new token's, 0 for a slot out of the
        decode batch."""
        return np.where(self._mask, self._lengths + 1, 0)

    # ------------------------------------------------------------ requests

    def submit(self, tokens, max_new_tokens: int = 16,
               rid: Optional[str] = None,
               submitted_at: Optional[float] = None) -> str:
        """Local (queue-less) submission; result lands in ``self.results``.
        ``submitted_at`` (time.time base) backdates the arrival so open-
        loop benchmarks charge queue wait to the request."""
        rid = rid or uuid.uuid4().hex[:12]
        req = ServeRequest(rid, list(map(int, tokens)), max_new_tokens,
                           submitted_at=submitted_at or time.time())
        self._pending.append((req, 0, False, True))
        return rid

    def _resp_key(self, rid: str) -> str:
        return self.queue._key(f"resp:{rid}")

    def _poll_queue(self) -> Optional[Tuple[ServeRequest, int, bool, bool]]:
        """Pop one request from the shared queue (non-blocking-ish)."""
        if self.queue is None:
            return None
        items = self.queue._items_key
        with TraceAnnotation(SPAN_KV_POLL):
            if self.lease:
                inflight = self.queue._key("inflight")
                entry = self._store.blpop_lease(
                    items, inflight, self.worker_id, self.lease_ttl_s,
                    timeout=0.0)
            else:
                got = self._store.blpop(items, timeout=0.0)
                entry = None if got is None else got[1]
            if entry is None:
                return None
            if self.queue._maxsize > 0:  # hand the admission slot token back
                self._store.rpush(self.queue._slots_key, b"s")
        if (isinstance(entry, (tuple, list)) and len(entry) == 3
                and isinstance(entry[0], int)):
            attempt, _rid, payload = entry
            return ServeRequest.from_payload(payload), attempt, self.lease, False
        # lease-unaware producer used Queue.put: payload is the whole blob
        req = ServeRequest.from_payload(entry)
        return req, 0, False, False

    def _finish(self, req: ServeRequest, result: Dict[str, Any],
                slot: _Slot) -> None:
        if slot.local or self.queue is None:   # local: never leased
            self.results[req.id] = result
            return
        with TraceAnnotation(SPAN_KV_REPLY, rid=req.id):
            if slot.leased:
                self._store.lease_release(self.queue._key("inflight"),
                                          req.id, slot.attempt)
            self._store.rpush(self._resp_key(req.id),
                              serialization.dumps(result))

    # ---------------------------------------------------------- scheduling

    def _admit_one(self) -> bool:
        free_slot = next((i for i, s in enumerate(self._slots) if s is None),
                         None)
        if free_slot is None:
            return False
        if self._pending:
            req, attempt, leased, local = self._pending.popleft()
        else:
            popped = self._poll_queue()
            if popped is None:
                return False
            req, attempt, leased, local = popped
        total = len(req.tokens) + req.max_new_tokens
        if (not req.tokens or total > self.max_len
                or self.alloc.pages_for(total) > self.alloc.num_pages - 1):
            # reject anything that could not run even on an empty slab —
            # otherwise preemption would thrash forever trying to fit it
            self.metrics["rejected"] += 1
            slot = _Slot(req, attempt, leased, local, self._seq,
                         t_admit=time.time())
            self._finish(req, {"id": req.id, "error":
                               f"prompt+output {total} does not fit "
                               f"(max_len {self.max_len})", "tokens": [],
                               "queue_s": slot.t_admit - _t0(req, slot)},
                         slot)
            return True
        need = self.alloc.pages_for(len(req.tokens))
        pages = self.alloc.alloc(need)
        if pages is None:
            # no pages: park it at the front and stop admitting this step
            self._pending.appendleft((req, attempt, leased, local))
            return False
        slot = _Slot(req, attempt, leased, local, self._seq, pages=pages,
                     t_admit=time.time())
        self._seq += 1
        self._slots[free_slot] = slot
        self._tables[free_slot] = 0
        self._tables[free_slot, :need] = pages
        self._lengths[free_slot] = 0
        self._mask[free_slot] = False  # joins decode only after prefill
        self.metrics["admitted"] += 1
        return True

    def _ensure_capacity(self, idx: int, pos: int) -> bool:
        """Grow slot ``idx`` so cache position ``pos`` is backed by a page."""
        slot = self._slots[idx]
        needed = pos // self.page_size + 1
        while len(slot.pages) < needed:
            got = self.alloc.alloc(1)
            if got is None:
                if not self._preempt_youngest():
                    return False
                if self._slots[idx] is not slot:
                    return False  # the victim was us
                continue
            self._tables[idx, len(slot.pages)] = got[0]
            slot.pages.extend(got)
        return True

    def _preempt_youngest(self) -> bool:
        """Preempt-by-recompute the youngest active slot. Returns False
        when there is nothing to preempt."""
        victims = [(s.seq, i) for i, s in enumerate(self._slots)
                   if s is not None]
        if not victims:
            return False
        _, idx = max(victims)
        slot = self._slots[idx]
        self.alloc.free(slot.pages)
        slot.pages = []
        self._release_slot(idx)
        # retry from scratch; lease stays held (still our request)
        self._pending.appendleft((slot.req, slot.attempt, slot.leased,
                                  slot.local))
        self.metrics["preempted"] += 1
        return True

    def _release_slot(self, idx: int) -> None:
        self._slots[idx] = None
        self._tables[idx] = 0
        self._lengths[idx] = 0
        self._mask[idx] = False
        self._tokens[idx] = 0

    def _complete(self, idx: int) -> None:
        slot = self._slots[idx]
        req = slot.req
        now = time.time()
        t0 = _t0(req, slot)
        result = {"id": req.id, "tokens": list(slot.out_tokens),
                  "queue_s": slot.t_admit - t0,
                  "ttft_s": (slot.t_first - t0
                             if slot.t_first is not None else None),
                  "completion_s": now - t0}
        self.alloc.free(slot.pages)
        self._release_slot(idx)
        self._finish(req, result, slot)
        self.metrics["completed"] += 1

    def _emit_token(self, idx: int, tok: int) -> None:
        """Record one generated token for slot ``idx``; completes the
        request on eos or output budget."""
        slot = self._slots[idx]
        if slot.t_first is None:
            slot.t_first = time.time()
        slot.out_tokens.append(tok)
        slot.cur_token = tok
        done = (self.eos_id is not None and tok == self.eos_id) or \
               len(slot.out_tokens) >= slot.req.max_new_tokens
        if done:
            self._complete(idx)
        else:
            self._tokens[idx] = tok

    def _prefill_one(self) -> None:
        """Advance the OLDEST still-prefilling slot by one chunk."""
        cand = [(s.seq, i) for i, s in enumerate(self._slots)
                if s is not None and s.state == "prefill"]
        if not cand:
            return
        _, idx = min(cand)
        slot = self._slots[idx]
        C = self.prefill_chunk
        prompt = slot.req.tokens
        n_valid = min(C, len(prompt) - slot.prompt_pos)
        if not self._ensure_capacity(idx, slot.prompt_pos + n_valid - 1):
            return  # wait for pages (or we were the preemption victim)
        with TraceAnnotation(SPAN_PREFILL, rid=slot.req.id,
                             start=slot.prompt_pos, tokens=n_valid):
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :n_valid] = prompt[slot.prompt_pos:
                                        slot.prompt_pos + n_valid]
            tok, self._pages = self._prefill_chunk(
                self.params, self._pages, jnp.asarray(chunk),
                jnp.asarray(self._tables[idx]), jnp.int32(slot.prompt_pos),
                jnp.int32(n_valid))
        self.metrics["prefill_chunks"] += 1
        slot.prompt_pos += n_valid
        slot.length = slot.prompt_pos
        self._lengths[idx] = slot.length
        if slot.prompt_pos == len(prompt):
            slot.state = "decode"
            with TraceAnnotation(SPAN_PREFILL_WAIT, rid=slot.req.id):
                first = int(tok[0])
            self._emit_token(idx, first)  # first token: TTFT
            if self._slots[idx] is slot:  # not completed by that token
                self._mask[idx] = True

    def _decode_once(self) -> None:
        decoding = [i for i, s in enumerate(self._slots)
                    if s is not None and s.state == "decode"]
        if not decoding:
            return
        with TraceAnnotation(SPAN_DECODE) as span:
            for idx in decoding:
                s = self._slots[idx]
                if s is None or s.state != "decode":
                    continue  # preempted by an earlier slot's growth
                # the new token lands at cache position `length`
                self._ensure_capacity(idx, s.length)
            decoding = [i for i, s in enumerate(self._slots)
                        if s is not None and s.state == "decode"]
            if not decoding:
                return
            att = self.attention_lengths()
            span.set_metadata(
                tokens=len(decoding), kv_positions=int(att.sum()),
                attn_blocks=int((-(-att // self._attn_block)).sum()))
            with TraceAnnotation(SPAN_DECODE_DISPATCH):
                toks, self._pages = self._decode(
                    self.params, self._pages, jnp.asarray(self._tokens),
                    jnp.asarray(self._tables), jnp.asarray(self._lengths),
                    jnp.asarray(self._mask))
            self.metrics["decode_steps"] += 1
            with TraceAnnotation(SPAN_DECODE_WAIT):
                toks = np.asarray(toks)
            if self._counts_held:
                held = int(toks[-1])
                self.metrics["held_expert_tokens"] += held
                span.set_metadata(held_expert_tokens=held)
            with TraceAnnotation(SPAN_EMIT):
                for idx in decoding:
                    slot = self._slots[idx]
                    slot.length += 1
                    self._lengths[idx] = slot.length
                    self._emit_token(idx, int(toks[idx]))

    def _renew_leases(self) -> None:
        if not self.lease:
            return
        now = time.monotonic()
        if now - self._last_renew < self.lease_ttl_s / 3:
            return
        self._last_renew = now
        inflight = self.queue._key("inflight")
        with TraceAnnotation(SPAN_KV_RENEW):
            for s in self._slots:
                if s is not None and s.leased:
                    self._store.lease_renew(inflight, s.req.id, s.attempt,
                                            self.lease_ttl_s)

    # ------------------------------------------------------------- driving

    def step(self) -> bool:
        """One scheduler tick: admit → one prefill chunk → one decode
        step → lease renewal. Returns True if any work was done."""
        with TraceAnnotation(SPAN_STEP):
            admitted = False
            with TraceAnnotation(SPAN_ADMIT):
                while self._admit_one():
                    admitted = True
            had_prefill = any(s is not None and s.state == "prefill"
                              for s in self._slots)
            self._prefill_one()
            had_decode = any(s is not None and s.state == "decode"
                             for s in self._slots)
            self._decode_once()
            self._renew_leases()
        return admitted or had_prefill or had_decode

    def run_until_idle(self) -> None:
        """Drive until no local/pending work remains (queue not polled
        beyond what's already available)."""
        while True:
            worked = self.step()
            if not worked and not self._pending and self.active == 0:
                break

    def serve_forever(self, stop=None, poll_s: float = 0.005) -> None:
        """Drive until ``stop`` (threading.Event) is set; drains active
        requests before returning."""
        while stop is None or not stop.is_set():
            if not self.step():
                time.sleep(poll_s)
        while self.active > 0 or self._pending:
            self.step()
