"""Front end on the host's wall clock.

It makes the engine calls of ``serving.ContinuousBatchScheduler.run`` in
the same FIFO order — ``can_admit``/``reserve`` for the longest
admissible head of the queue, one ``commit_admissions``, one
``step_round``, preempted requests back to the queue's front, then
``retire_done`` — with no admission policy of its own.  The difference is
the clock: the whole backlog is due when the window opens
(``time.monotonic``), and every latency is taken from then.  The
engine's modeled clock feeds nothing here.

Each call runs under a ``jax.profiler.TraceAnnotation`` (``bench.*``),
which costs nothing unless a profiler session is on, and is what a traced
run attributes the device's idle gaps to.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Dict, List

import jax

from harness.stats import ReqRecord, RoundRecord, Window
from harness.traffic import Request


class Frontend:
    def __init__(self, eng, clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self.eng = eng
        self.clock = clock
        self.sleep = sleep
        self.preemptions = 0
        self.outputs: Dict[int, List[int]] = {}     # finished: served ids
        self.prompts: Dict[int, List[int]] = {}
        # most pages each KV pool held after any round (what the traffic
        # fills, against what the engine reserved)
        self.pages_peak: Dict[str, int] = {}

    def _admit(self, queue: deque, recs: Dict[int, ReqRecord], now: float
               ) -> None:
        eng = self.eng
        admitted = False
        while queue:
            r = queue[0]
            resumed = eng.resume_out_len(r.rid)
            if not eng.can_admit(len(r.prompt) + resumed,
                                 max(0, r.max_new - resumed)):
                break                       # FIFO: never around the head
            queue.popleft()
            eng.reserve(r.rid, r.prompt, r.max_new)
            if recs[r.rid].admitted is None:
                recs[r.rid].admitted = now
            admitted = True
        if admitted:
            with jax.profiler.TraceAnnotation("bench.commit_admissions"):
                eng.commit_admissions()

    def serve(self, requests: List[Request], *, t0: float, seconds: float,
              idle_to_close: bool = True) -> Window:
        """Serve ``requests``, all due at ``t0``, until the first round
        that would start after ``t0 + seconds``; with nothing left to
        serve, the window idles to its close (``idle_to_close``) or ends
        there."""
        eng = self.eng
        t_close = t0 + seconds
        recs = {r.rid: ReqRecord(due=t0, prompt_len=len(r.prompt))
                for r in requests}
        for r in requests:
            self.prompts[r.rid] = r.prompt
        by_rid = {r.rid: r for r in requests}
        queue = deque(requests)
        rounds: List[RoundRecord] = []
        t_end = t0
        while True:
            now = self.clock()
            if now >= t_close:
                break
            self._admit(queue, recs, now)
            if not eng.active:
                if queue:
                    r = queue[0]
                    raise RuntimeError(
                        f"request {r.rid} ({len(r.prompt)} + {r.max_new} "
                        "tokens) can never be admitted: pool or rows too "
                        "small")
                # nothing more to serve: the window idles to its close
                if idle_to_close:
                    self.sleep(t_close - now)
                    t_end = t_close
                break
            rows = sum(1 for s in eng.active if not s.done)
            start = self.clock()
            with jax.profiler.TraceAnnotation("bench.step_round"):
                rr = eng.step_round()
            end = self.clock()
            committed = 0
            for rid, n in rr["committed"].items():
                if n > 0:
                    recs[rid].deliveries.append((end, n))
                    committed += n
            for victim in rr["preempted"]:
                self.preemptions += 1
                queue.appendleft(by_rid[victim.rid])
            with jax.profiler.TraceAnnotation("bench.retire_done"):
                for seq, res in eng.retire_done():
                    self.outputs[seq.rid] = list(res.tokens)
            for which, pool in getattr(eng, "pools", {}).items():
                self.pages_peak[which] = max(self.pages_peak.get(which, 0),
                                             pool.pages_in_use)
            rounds.append(RoundRecord(start=start, end=end, rows=rows,
                                      committed=committed))
            t_end = end
        return Window(t0=t0, t_due_end=t_close, t_end=t_end, reqs=recs,
                      rounds=rounds)
