"""Drive a traffic plan against the stack and record every request.

One scheduler thread sends each request when it is due: a conversation's
first turn at its arrival, a follow-up a think time after the previous
answer, a closed-loop client's next request as soon as its last one is
answered.  The answer arrives on a callback, which schedules what
follows.  Times are ``time.monotonic()`` seconds.

The schedule runs on after the window closes, under the same load,
until every request due in the window is answered or the plan's tail
runs out; then nothing new is sent.  Requests still in flight then were
due after the window; closing the stack fails them.
"""
from __future__ import annotations

import heapq
import itertools
import threading
import time
from typing import Dict, List

from .traffic_gen import Plan, tokens


class LoadRun:
    def __init__(self, plan: Plan, send, *, seed: int, vocab: int):
        self.plan = plan
        self.send = send
        self.seed = seed
        self.vocab = vocab
        self.records: List[Dict] = []        #: guarded-by _cv
        self._heap: List = []                #: guarded-by _cv
        self._cv = threading.Condition()
        self._seq = itertools.count()
        self._next_req = itertools.count()   # closed loop: shared stream
        self.t0 = 0.0                        # schedule start

    # ------------------------------------------------------------ schedule
    def _push(self, due: float, item) -> None:
        with self._cv:
            heapq.heappush(self._heap, (due, next(self._seq), item))
            self._cv.notify()

    def _first_items(self) -> None:
        p = self.plan
        if p.loop == "closed":
            for c in range(p.clients):
                self._push(self.t0, ("closed", c, None))
        else:
            for conv in p.conversations:
                self._push(self.t0 + conv.arrival, ("turn", conv, 0, []))

    def _request(self, item, due: float) -> Dict:
        p = self.plan
        if item[0] == "closed":
            k = next(self._next_req) % len(p.prompt_lens)
            prompt = tokens(self.seed, k, 0, int(p.prompt_lens[k]),
                            self.vocab)
            return {"key": ("closed", item[1], k), "due": due,
                    "prompt": prompt, "max_new": int(p.max_news[k]),
                    "sid": None, "resume_at": 0, "item": item}
        _, conv, turn, history = item
        if turn == 0:
            prompt = tokens(self.seed, conv.cid, 0, conv.first_prompt,
                            self.vocab)
        else:
            prompt = history + tokens(self.seed, conv.cid, turn,
                                      conv.follow_ups[turn - 1], self.vocab)
        sid = f"c{conv.cid}" if p.sessions else None
        # where a session hit resumes: the engine holds the history but
        # its last token
        resume = len(history) - 1 if sid is not None and turn else 0
        return {"key": ("turn", conv.cid, turn), "due": due,
                "prompt": prompt, "max_new": conv.max_new[turn],
                "sid": sid, "resume_at": resume, "item": item}

    def _after(self, rec: Dict) -> None:
        """Schedule what follows an answered request."""
        item = rec["item"]
        if item[0] == "closed":
            self._push(rec["resp"], item)
            return
        _, conv, turn, _ = item
        if not rec["ok"] or turn + 1 >= conv.turns:
            return
        history = rec["prompt"] + rec["out"]
        self._push(rec["resp"] + conv.think[turn],
                   ("turn", conv, turn + 1, history))

    # ----------------------------------------------------------------- send
    def _fire(self, due: float, item) -> None:
        rec = self._request(item, due)
        arg = {"tokens": rec["prompt"], "max_new": rec["max_new"]}
        if rec["sid"] is not None:
            arg["session_id"] = rec["sid"]

        def on_done(value, err, replica):
            rec["resp"] = time.monotonic()
            rec["replica"] = replica
            if err is None and value.get("done") \
                    and len(value["tokens"]) == rec["max_new"]:
                rec.update(ok=True, out=list(value["tokens"]),
                           ttft_ms=float(value["ttft_ms"]),
                           n_out=len(value["tokens"]))
            else:
                rec.update(ok=False, out=[], ttft_ms=0.0, n_out=0,
                           error=repr(err) if err else repr(value)[:200])
            self._after(rec)

        with self._cv:
            self.records.append(rec)
        rec["send"] = time.monotonic()
        self.send(arg, on_done)

    def _in_window_pending(self, t_close: float) -> bool:
        """Whether a request due before ``t_close`` is unanswered or not
        yet sent (callers hold ``_cv``)."""
        if any(r["due"] < t_close and "resp" not in r
               for r in self.records):
            return True
        return bool(self._heap) and self._heap[0][0] < t_close

    def run(self) -> List[Dict]:
        """Run the whole schedule; returns the request records."""
        p = self.plan
        self.t0 = time.monotonic()
        t_open, t_close = (self.t0 + w for w in p.window)
        t_end = t_close + p.tail_s
        self._first_items()
        while True:
            with self._cv:
                now = time.monotonic()
                if now >= t_close and (
                        now >= t_end
                        or not self._in_window_pending(t_close)):
                    break
                if not self._heap or self._heap[0][0] > now:
                    wait = (self._heap[0][0] - now) if self._heap else 0.05
                    self._cv.wait(timeout=min(max(wait, 0.0), 0.05))
                    continue
                due, _, item = heapq.heappop(self._heap)
            self._fire(due, item)
        with self._cv:
            self._heap.clear()
            return list(self.records)

    @property
    def window(self):
        return tuple(self.t0 + w for w in self.plan.window)
