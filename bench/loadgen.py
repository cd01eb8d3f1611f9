"""The load generator of a benchmark run, in a process of its own.

    JAX_PLATFORMS=cpu python bench/loadgen.py SPEC.json

``SPEC.json`` (written by the harness) names the KV endpoints, the
request queue, the traffic mix, the seed, the vocabulary, the file to
write and the drain limit. The process connects with the program's own
``connect()``, builds its schedule, says so on ``READY_KEY``, waits for
the harness to push the schedule's start time to ``GO_KEY``, and then
offers the mix through ``ServeClient`` in an open loop: each request is
submitted at its due time by a thread of its own, whatever the earlier
ones are doing.

Requests due inside the window are counted. Load
goes on until every counted request has its reply, or until the drain
limit has passed; then the records are written and the process exits.
Per request it records the due time, the submit call and its return,
the reply's arrival, the engine's ``ttft_s`` and the served tokens.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import traffic  # noqa: E402

READY_KEY = "bench:ready"
GO_KEY = "bench:go"
SUBMIT_TIMEOUT_S = 30.0


class Generator:
    def __init__(self, spec: dict):
        from repro.core.kvcluster import connect
        from repro.core.queues import Queue
        from repro.serve import ServeClient

        self.spec = spec
        self.mix = spec["mix"]
        self.store = connect(spec["endpoints"])
        self.queue = Queue(spec["queue_maxsize"], uid=spec["queue_uid"],
                           _adopt=True, store=self.store)
        self.client = ServeClient(self.queue)
        self.vocab = spec["vocab"]
        self.seed = spec["seed"]
        span = spec["preroll_s"] + spec["seconds"] + spec["drain_s"]
        self.requests = traffic.schedule(
            self.mix, traffic.max_requests(self.mix, span),
            spec["preroll_s"], spec["seconds"])
        self.records = {}
        self.lock = threading.Lock()
        self.done = threading.Event()

    def run(self) -> None:
        self.store.rpush(READY_KEY, b"1")
        got = self.store.blpop(GO_KEY, 600.0)
        if got is None:
            raise TimeoutError("no start signal from the harness")
        self.t0 = float(got[1])
        self.w0 = self.t0 + self.spec["preroll_s"]
        self.w1 = self.w0 + self.spec["seconds"]
        self.deadline = self.w1 + self.spec["drain_s"]
        threading.Thread(target=self._open_loop, daemon=True).start()
        while not self.done.is_set():
            now = time.time()
            if now >= self.w1 and self._counted_all_settled():
                break
            if now >= self.deadline:
                break
            self.done.wait(0.05)
        self.done.set()

    def _counted_all_settled(self) -> bool:
        with self.lock:
            return all(r["t_reply"] is not None or r["error"] is not None
                       for r in self.records.values() if r["counted"])

    # ------------------------------------------------------------- loop

    def _open_loop(self) -> None:
        for req in self.requests:
            due = self.t0 + req.offset_s
            if due >= self.w1 and self._counted_all_settled():
                return
            while True:
                wait = due - time.time()
                if self.done.is_set():
                    return
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            rec = self._record(req, due)
            threading.Thread(target=self._one, args=(req, rec),
                             daemon=True).start()

    # ---------------------------------------------------------- request

    def _record(self, req, due: float) -> dict:
        rec = {"index": req.index, "due": due, "t_call": None,
               "t_ret": None, "t_reply": None, "prompt_len": req.prompt_len,
               "output_len": req.output_len,
               "counted": self.w0 <= due < self.w1, "error": None,
               "rid": f"r{req.index}", "reply_id": None, "ttft_s": None,
               "tokens": None}
        with self.lock:
            self.records[req.index] = rec
        return rec

    def _one(self, req, rec: dict) -> None:
        toks = traffic.prompt_tokens(self.seed, req.index, req.prompt_len,
                                     self.vocab)
        rec["t_call"] = time.time()
        try:
            self.client.submit(toks, req.output_len, rid=rec["rid"],
                               timeout=SUBMIT_TIMEOUT_S)
        except TimeoutError:
            rec["error"] = "refused: admission queue full"
            return
        rec["t_ret"] = time.time()
        left = self.deadline - time.time()
        try:
            reply = self.client.result(rec["rid"], timeout=max(left, 0.01))
        except TimeoutError:
            rec["error"] = "no reply"
            return
        t_reply = time.time()
        rec["reply_id"] = reply.get("id")
        rec["ttft_s"] = reply.get("ttft_s")
        rec["tokens"] = list(reply.get("tokens") or [])
        if reply.get("error"):
            rec["error"] = f"engine: {reply['error']}"
        rec["t_reply"] = t_reply      # last: the record is complete

    def duplicates(self) -> int:
        """Replies left on counted requests' keys: each is a second
        delivery of a request that already had its reply."""
        return sum(self.store.llen(self.client._resp_key(r["rid"]))
                   for r in self.records.values()
                   if r["counted"] and r["t_reply"] is not None)


def main() -> None:
    spec = json.loads(Path(sys.argv[1]).read_text())
    gen = Generator(spec)
    gen.run()
    with gen.lock:
        records = [dict(r) for r in gen.records.values()]
    out = {"t0": gen.t0, "w0": gen.w0, "w1": gen.w1,
           "t_end": time.time(), "duplicates": gen.duplicates(),
           "records": records}
    Path(spec["out"]).write_text(json.dumps(out))
    sys.stdout.flush()
    os._exit(0)   # threads still parked on later replies end with us


if __name__ == "__main__":
    main()
