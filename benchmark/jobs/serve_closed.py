"""Job kind ``serve_closed``: a backlog of callers that wait.

``clients_per_slot`` times the deployment's slots clients each send a
request, wait for the whole reply and send the next at once — an
offline scoring or evaluation job whose queue never empties. A slow
system receives less load, so what is judged is work completed per
second, not a tail.
"""

from __future__ import annotations

from typing import List

from benchmark.harness import recipe, stats
from benchmark.jobs.serve_base import Req, ServeJob, clock, failed_reason


#: a stretch of the sustained rate is this part of the window
STRETCHES = 5


class Job(ServeJob):
    def warm(self, seconds: float) -> None:
        """Fill the backlog and serve ``ramp_s`` seconds, in which every
        slot turns over at least once."""
        tr = self.cell["traffic"]
        self.clients = int(tr["clients_per_slot"]) * self.ecfg.slots
        self.served = 0
        self._ready: List[int] = list(range(self.clients))
        self._batch: List[Req] = []
        ramp = float(tr["ramp_s"])
        self.watch_gc()
        self.t0 = clock()
        self.run_until(self.t0 + ramp)
        done = sum(1 for r in self.reqs.values() if r.done_at is not None)
        if done < self.ecfg.slots:
            recipe.log(f"serve_closed: only {done} requests ended in the "
                       f"ramp of {ramp} s; slots have not all turned over")

    def _next(self) -> Req:
        """Requests are drawn 256 at a time from the seeded stream."""
        if not self._batch:
            self._batch = self.make_requests(256, f"b{self.served}_")[::-1]
        self.served += 1
        return self._batch.pop()

    def offer(self, now: float) -> None:
        while self._ready:
            r = self._next()
            r.client = self._ready.pop()
            r.due = r.sent = now
            self.submit(r, now)
            if r.reason == "refused":    # the client asks again next tick
                self._ready.append(r.client)
                return

    def completed(self, r: Req, now: float) -> None:
        self._ready.append(r.client)

    def measure(self, seconds: float, capture) -> None:
        """Ticks until the first that ends at or after ``seconds``. A
        tick of a full engine lasts about a second and ends a wave of
        requests at once, so the window runs from the end of one tick to
        the end of another and holds no partial wave.

        ``serve_tokens_per_s`` is the sustained rate: the median, over
        every stretch of a fifth of the window that a tick opens, of the
        output tokens of the requests that ended in the stretch over its
        length (``stats.sustained_rate``). Now and then a tick takes one
        to three times its 1.15 s (PR 22: one run in six, then one in
        twelve, inside ``Scheduler.step``, on a one-chip machine whose
        host cores are shared), which moved the whole window's rate by
        2.7 % and 6.5 % where the other runs agree to 0.4 %: no bound
        could hold that. The sustained rate moved by 0.6 % for the pause
        and moves in full for a step that got slower. The whole window's
        rate is on stderr beside it."""
        start = clock()
        self.run_until(start + seconds, capture, start)
        end = clock()
        if capture is not None:
            capture.stop()
        self.window = {"start": start, "end": end, "seconds": end - start}
        self.log_ticks(start, end)
        done_in = [r for r in self.reqs.values()
                   if r.done_at is not None and start <= r.done_at < end]
        good = [r for r in done_in if not failed_reason(r)]
        self.attempted = len(done_in)
        self.failed = len(done_in) - len(good)
        tokens = sum(r.n for r in good)
        parts = self.window_ticks(start, end)
        rate = stats.sustained_rate([start] + [p.stamp for p in parts],
                                    [p.tokens for p in parts],
                                    seconds / STRETCHES)
        self.end_to_end = {"serve_tokens_per_s": rate}
        self.evidence.update({
            "ttft_s": [r.first_at - r.due for r in good],
            "requests_done": len(done_in),
            "tokens_in_window": tokens,
        })
        recipe.log(f"serve_closed: {len(done_in)} requests done "
                   f"({self.failed} failed) in {end - start:.2f} s, "
                   f"{tokens} output tokens, {tokens / (end - start):.2f} "
                   f"a second over the whole window, {rate:.2f} sustained")
