"""Job kind ``serve_open``: independent users on a fixed schedule.

Requests fall due at the times the traffic file's arrival process and
the seed fix, whether or not earlier ones have finished. A generator
thread does nothing but sleep until each due time and put the request
in an inbox (an atomic ``deque.append``); the scheduler's thread
submits what it finds there before every tick, as a front end's handler
threads would. Time to first token runs from the due time, so a stall
counts against every request it delays, and ``gen_lag`` (due to inbox)
says whether the generator itself was starved.
"""

from __future__ import annotations

import collections
import math
import threading
from typing import Any, Dict

from benchmark.harness import recipe, stats, traffic
from benchmark.jobs.serve_base import (DRAIN_S, ServeJob, clock,
                                       failed_reason, ms)


class Job(ServeJob):
    def __init__(self, cell: Dict[str, Any], device: Dict[str, Any],
                 seed: int):
        super().__init__(cell, device, seed)
        self.inbox: collections.deque = collections.deque()
        self._stop = threading.Event()
        self._thread = None

    def _schedule(self, horizon_s: float) -> None:
        """Every arrival of ramp + window, drawn up front from the seed."""
        due = traffic.arrival_times(self.cell["traffic"]["arrivals"],
                                    self.rng, horizon_s)
        self.plan = self.make_requests(len(due), "r")
        for r, d in zip(self.plan, due):
            r.due = float(d)

    def _generate(self) -> None:
        for r in self.plan:
            wait = r.due - clock()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            r.sent = clock()
            self.inbox.append(r)

    def offer(self, now: float) -> None:
        while self.inbox:
            self.submit(self.inbox.popleft(), now)

    def warm(self, seconds: float) -> None:
        """Start the schedule and serve the ramp: ``ramp_s`` seconds at
        the cell's rate bring occupancy to its steady level before the
        window opens."""
        ramp = float(self.cell["traffic"]["ramp_s"])
        self._schedule(ramp + seconds)
        self.t0 = clock()
        for r in self.plan:
            r.due += self.t0
        self._thread = threading.Thread(
            target=self._generate, daemon=True, name="bench-generator")
        self._thread.start()
        self.run_until(self.t0 + ramp)

    def measure(self, seconds: float, capture) -> None:
        start = clock()
        end = start + seconds
        self.run_until(end, capture, start)
        if capture is not None:
            capture.stop()
        self._stop.set()
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            self.problems.append("the generator thread did not stop")
        # requests due inside the window still owe a first token
        due_in = [r for r in self.plan if start <= r.due < end]
        limit = clock() + DRAIN_S
        while clock() < limit and any(
                r.first_at is None and r.reason is None for r in due_in):
            self.tick()
        self.window = {"start": start, "end": end, "seconds": seconds}
        self._reduce(due_in, start, end)

    def _reduce(self, due_in, start: float, end: float) -> None:
        inf = math.inf
        ttft = [r.first_at - r.due
                if r.first_at is not None and not failed_reason(r) else inf
                for r in due_in]
        done_in = [r for r in self.reqs.values()
                   if r.done_at is not None and start <= r.done_at < end]
        tpot = [(r.last_at - r.first_at) / (r.n - 1) if not failed_reason(r)
                else inf for r in done_in if r.n > 1 or failed_reason(r)]
        lag = [r.sent - r.due for r in due_in if r.sent is not None]
        tokens_in = sum(n for t, n in self.token_stamps if start <= t < end)
        self.attempted = len(due_in)
        self.failed = sum(1 for r in due_in
                          if failed_reason(r) or r.first_at is None)
        self.end_to_end = {
            "ttft_p90_ms": ms(stats.percentile(ttft, 90)),
            "tpot_p90_ms": ms(stats.percentile(tpot, 90)),
        }
        self.evidence.update({
            "ttft_s": ttft, "tpot_s": tpot, "gen_lag_s": lag,
            "tokens_in_window": tokens_in, "requests_due": len(due_in),
            "requests_done": len(done_in),
            "due_at": {r.rid: r.due for r in due_in},
        })
        recipe.log(f"serve_open: {len(due_in)} due, {len(done_in)} done, "
                   f"{self.failed} failed, {self.refused} refused in "
                   f"{end - start:.1f} s")

    def close(self) -> None:
        self._stop.set()
        super().close()
