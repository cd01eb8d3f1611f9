"""Fault-tolerant, straggler-mitigating job execution (control plane).

This is the paper's job-queue Pool (§3.1.2) hardened for 1000+-node
operation, with the failure semantics of §7.5 implemented rather than
assumed:

  * every task attempt holds a **lease** (KV key with TTL) heart-beaten by
    the worker; a monitor requeues tasks whose lease lapsed (worker died);
  * **speculative execution**: tasks running beyond ``speculate_after``
    (a multiple of the observed median runtime) are re-enqueued on
    another worker — the paper's warm-container strategy removes
    cold-start stragglers, this removes slow-node stragglers;
  * results are **idempotent**: the first attempt to finish wins via an
    atomic SETNX; duplicates are discarded;
  * ``max_retries`` bounds re-execution of genuinely failing tasks.

Workers are long-lived serverless functions; tasks are submitted with one
RPUSH. Everything rides on repro.core primitives (KV store + executor),
i.e. the transparent substrate *is* the scheduler's state store.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..core import serialization
from ..core import session as _session
from ..core.executor import FunctionExecutor, RemoteError
from ..core.reference import fresh_uid

__all__ = ["JobRunner", "JobFailedError"]


class JobFailedError(RuntimeError):
    def __init__(self, idx: int, message: str, tb: str = ""):
        super().__init__(f"task {idx} failed permanently: {message}")
        self.idx = idx
        self.remote_traceback = tb


def _runner_worker(tag: str, worker_id: int, lease_ttl: float) -> None:
    sess = _session.get_session()
    store, storage = sess.store, sess.get_storage()
    job_key = f"{tag}:jobs"
    result_key = f"{tag}:results"
    func_cache: Dict[str, Callable] = {}

    while True:
        got = store.blpop(job_key, timeout=0.25)
        if got is None:
            if store.get(f"{tag}:stop"):
                return
            continue
        if got[1] == b"__stop__":
            return
        job_id, idx, attempt, func_key, args = serialization.loads(got[1])
        lease_key = f"{tag}:lease:{job_id}:{idx}"
        store.set(lease_key, f"{worker_id}:{attempt}", ex=lease_ttl)

        stop_hb = threading.Event()

        def heartbeat():
            while not stop_hb.wait(lease_ttl / 3):
                store.expire(lease_key, lease_ttl)

        hb = threading.Thread(target=heartbeat, daemon=True)
        hb.start()
        try:
            func = func_cache.get(func_key)
            if func is None:
                func = serialization.loads(storage.get(func_key))
                func_cache[func_key] = func
            try:
                value = func(*args)
                status, body = "ok", value
            except Exception as exc:
                status, body = "error", (f"{type(exc).__name__}: {exc}",
                                         traceback.format_exc())
        finally:
            stop_hb.set()
            store.delete(lease_key)
        # idempotent result: first finished attempt wins (job-scoped key)
        if store.setnx(f"{tag}:done:{job_id}:{idx}", attempt):
            store.rpush(result_key, serialization.dumps(
                (idx, attempt, status, body, worker_id)))


class JobRunner:
    def __init__(self, n_workers: int = 4, lease_ttl: float = 2.0,
                 speculate_factor: float = 3.0, max_retries: int = 3,
                 session: Optional[_session.Session] = None,
                 monitor_interval: float = 0.1):
        self.session = session or _session.get_session()
        self._store = self.session.store
        self._storage = self.session.get_storage()
        self.uid = fresh_uid("jobs")
        self._tag = "{" + self.uid + "}"
        self.lease_ttl = lease_ttl
        self.speculate_factor = speculate_factor
        self.max_retries = max_retries
        self.monitor_interval = monitor_interval
        self.n_workers = n_workers
        self._executor = FunctionExecutor(
            name=f"jobs-{self.uid}", session=self.session,
            **{k: v for k, v in self.session.executor_defaults.items()
               if k in ("backend", "monitoring")})
        for wid in range(n_workers):
            self._executor.call_async(_runner_worker,
                                      (self._tag, wid, lease_ttl))
        self.stats: Dict[str, int] = {"retries": 0, "speculations": 0,
                                      "duplicates_discarded": 0}

    # ------------------------------------------------------------------ api

    def run(self, func: Callable, items: Sequence[Any],
            timeout: Optional[float] = None) -> List[Any]:
        """Execute func(*item) for every item; returns ordered results.
        Tolerates worker death and stragglers; raises JobFailedError after
        max_retries."""
        job_id = fresh_uid("job")
        func_key = f"jobs/{self.uid}/{job_id}/func"
        self._storage.put(func_key, serialization.dumps(func))
        n = len(items)
        norm = [tuple(it) if isinstance(it, tuple) else (it,) for it in items]

        def enqueue(idx: int, attempt: int) -> None:
            self._store.rpush(f"{self._tag}:jobs", serialization.dumps(
                (job_id, idx, attempt, func_key, norm[idx])))

        start = {i: time.monotonic() for i in range(n)}
        attempts = {i: 0 for i in range(n)}
        speculated = set()
        for i in range(n):
            enqueue(i, 0)

        results: Dict[int, Any] = {}
        errors: Dict[int, tuple] = {}
        durations: List[float] = []
        deadline = None if timeout is None else time.monotonic() + timeout

        while len(results) < n:
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"job {job_id}: {n - len(results)} "
                                   "tasks unfinished")
            got = self._store.blpop(f"{self._tag}:results",
                                    timeout=self.monitor_interval)
            if got is not None:
                idx, attempt, status, body, _wid = serialization.loads(got[1])
                if idx in results or idx in errors:
                    self.stats["duplicates_discarded"] += 1
                    continue
                if status == "ok":
                    results[idx] = body
                    durations.append(time.monotonic() - start[idx])
                else:
                    if attempts[idx] + 1 > self.max_retries:
                        errors[idx] = body
                        raise JobFailedError(idx, body[0], body[1])
                    attempts[idx] += 1
                    self.stats["retries"] += 1
                    self._store.delete(f"{self._tag}:done:{job_id}:{idx}")
                    start[idx] = time.monotonic()
                    enqueue(idx, attempts[idx])
                continue

            # monitor pass: dead leases + stragglers
            now = time.monotonic()
            median = sorted(durations)[len(durations) // 2] if durations else None
            for i in range(n):
                if i in results or i in errors:
                    continue
                running = now - start[i]
                has_lease = self._store.exists(
                    f"{self._tag}:lease:{job_id}:{i}")
                queued = False  # approximation: lease appears once picked up
                if not has_lease and running > self.lease_ttl * 1.5:
                    # worker died before finishing (or task lost)
                    if attempts[i] + 1 > self.max_retries:
                        raise JobFailedError(i, "lost task (worker death)")
                    attempts[i] += 1
                    self.stats["retries"] += 1
                    start[i] = now
                    enqueue(i, attempts[i])
                elif (median is not None and i not in speculated
                      and running > max(self.speculate_factor * median,
                                        self.lease_ttl)):
                    speculated.add(i)
                    self.stats["speculations"] += 1
                    enqueue(i, attempts[i] + 1000)  # marked speculative
        return [results[i] for i in range(n)]

    def resize(self, n_workers: int) -> None:
        """Elastic scaling: grow the worker fleet (shrink via stop pills)."""
        if n_workers > self.n_workers:
            for wid in range(self.n_workers, n_workers):
                self._executor.call_async(_runner_worker,
                                          (self._tag, wid, self.lease_ttl))
        elif n_workers < self.n_workers:
            for _ in range(self.n_workers - n_workers):
                self._store.rpush(f"{self._tag}:jobs", b"__stop__")
        self.n_workers = n_workers

    def backlog(self) -> int:
        """Outstanding queued tasks — the elastic public contract
        (:mod:`repro.runtime.elastic`): lets an ``ElasticController``
        drive a JobRunner exactly like a Pool."""
        try:
            return int(self._store.llen(f"{self._tag}:jobs"))
        except (ConnectionError, OSError):
            return 0

    def shutdown(self) -> None:
        """Stop the workers and join them (each finishes the task it
        holds), so none is still inside a computation, such as a JAX
        call, when the caller's interpreter exits."""
        self._store.set(f"{self._tag}:stop", 1, ex=600)
        for _ in range(self.n_workers):
            self._store.rpush(f"{self._tag}:jobs", b"__stop__")
        self._executor.shutdown(wait=True)
