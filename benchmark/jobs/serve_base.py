"""What the serving job kinds share: the deployment, the tick loop, the
benchmark's own clock on every token, and the reference comparison.

The system under test is ``serving.Engine`` + ``serving.Scheduler`` with
the cell's recipe and every other knob at the program's default. One
thread ticks the scheduler; a kind decides only how load is offered
(``serve_open``: a schedule fixed in the traffic file; ``serve_closed``:
clients that wait for their reply). Times are read on ``time.monotonic``
— the scheduler's own default clock, so its spans and the benchmark's
stamps share an axis.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from benchmark.harness import plan as plan_mod
from benchmark.harness import recipe, traffic
from benchmark.harness.trace import annotate

clock = time.monotonic

#: |streamed logprob - reference log-softmax| allowed per emitted token
#: of the reference requests. Measured on the chip (PR 22, five runs,
#: 77 to 863 tokens each): 2.0e-2 to 3.6e-2 at the worst token — the
#: bfloat16 engine against the float32 reference over 24 layers, the same
#: size as the 3.9e-2 logit drift PR 21 saw between the kernel and XLA
#: decode paths. The bound sits just above it. An int8 cache, a skipped
#: layer or a wrong position moves a greedy token's logprob by 1e-1 and
#: more (logits of the random-weight model have standard deviation 0.6).
LOGPROB_TOL = 6e-2
#: requests compared with the reference: the first of the ramp
N_REFERENCE = 8
#: after the window, tick on until every request due inside it has its
#: first token — at most this long
DRAIN_S = 10.0


class Tick(NamedTuple):
    """One pass of the loop: the closed loop's rate is taken from these,
    and a slow one shows where it lost its time."""

    begin: float        # before offering
    offering_s: float
    step_s: float       # inside Scheduler.step
    stamp: float        # the time its tokens and endings were given
    sent: int           # requests submitted
    ended: int          # requests that ended
    tokens: int         # output tokens of those that ended at their length


class Req:
    """The benchmark's record of one request, stamped by its own clock."""

    __slots__ = ("rid", "prompt", "max_tokens", "due", "sent", "first_at",
                 "last_at", "n", "done_at", "reason", "tokens", "logprobs",
                 "client")

    def __init__(self, rid: str, prompt: List[int], max_tokens: int):
        self.rid, self.prompt, self.max_tokens = rid, prompt, max_tokens
        self.due: Optional[float] = None
        self.sent: Optional[float] = None
        self.first_at: Optional[float] = None
        self.last_at: Optional[float] = None
        self.done_at: Optional[float] = None
        self.reason: Optional[str] = None
        self.n = 0
        self.tokens: Optional[List[int]] = None     # reference requests
        self.logprobs: Optional[List[float]] = None
        self.client = -1


def engine_setup(cell: Dict[str, Any]):
    """``(model config, EngineConfig)`` of the cell's deployment —
    shared with the AOT planning tool."""
    from apex_tpu.serving import EngineConfig

    fam, rec = cell["family"], cell["recipe"]
    cfg = fam.program_config(cell["config"], rec.get("model_pins", {}))
    pins = dict(rec["engine"])
    for key in ("prompt_buckets", "admit_batch_sizes"):
        if key in pins:
            pins[key] = tuple(pins[key])
    return cfg, EngineConfig(**recipe.accepted(EngineConfig, pins,
                                               "EngineConfig"))


class ServeJob:
    def __init__(self, cell: Dict[str, Any], device: Dict[str, Any],
                 seed: int):
        self.cell, self.device, self.seed = cell, device, seed
        self.shape = cell["family"].shape(cell["config"])
        self.problems: List[str] = []
        self.evidence: Dict[str, Any] = {}
        self.setup_parts: Dict[str, float] = {}   # seconds, for the log
        self.plan_bytes: Optional[int] = None
        self.reqs: Dict[str, Req] = {}
        self.refused = 0
        self.ticks: List[tuple] = []        # (start, end) of Scheduler.step
        self.tick_parts: List[Tick] = []
        self.gc_pauses: List[tuple] = []    # (start, seconds) of a collection
        self.decode_reads: List[tuple] = []  # (time, cache positions read)
        self.token_stamps: List[tuple] = []  # (time, tokens streamed)
        self.spans = None

    # -- set up --------------------------------------------------------------

    def setup(self, *, traced: bool = False, share=None) -> None:
        """Weights from the seed in one jitted call, the engine and all
        its programs warm. ``share`` (the knee sweep) is a job whose
        idle engine this one serves from instead of building its own."""
        import jax

        from apex_tpu import mesh as mx
        from apex_tpu.models import gpt
        from apex_tpu.serving import Engine, Request, Scheduler
        from apex_tpu.serving.scheduler import QueueFull
        from apex_tpu.telemetry.spans import SpanRecorder

        self._request, self._queue_full = Request, QueueFull

        cfg, ecfg = engine_setup(self.cell)
        self.cfg, self.ecfg = cfg, ecfg
        if share is not None:
            self.params, self.engine = share.params, share.engine
        else:
            t0 = time.perf_counter()
            mesh = mx.build_mesh(tp=1, devices=self.device["devices"][:1])
            self.params = jax.jit(lambda k: gpt.init(cfg, k))(
                jax.random.PRNGKey(self.seed))
            jax.block_until_ready(self.params)
            self.setup_parts["weights_data_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            self.engine = Engine(cfg, self.params, mesh, ecfg)
            self.engine.warmup()
            self.setup_parts["engine_warmup_s"] = time.perf_counter() - t0
        if traced:
            self.spans = SpanRecorder()
            self._plan_live()
        self.sched = Scheduler(self.engine, spans=self.spans)
        self.rng = np.random.default_rng(self.seed)

    def _plan_live(self) -> None:
        eng = self.engine
        try:
            plans = plan_mod.engine_plans(
                eng, self.params, eng.cache, eng.state,
                only=plan_mod.largest_engine_programs(eng))
        except AttributeError as e:
            recipe.log(f"plan: the engine's program tables moved ({e}); "
                       f"hbm_plan_gib left out")
            return
        self.plan_bytes = max(plans.values())

    def make_requests(self, n: int, prefix: str) -> List[Req]:
        horizon = self.ecfg.max_seq_len
        return [Req(f"{prefix}{i}", r["prompt"], r["max_tokens"])
                for i, r in enumerate(traffic.requests(
                    self.cell["traffic"], self.rng, n,
                    self.shape["vocab"], horizon))]

    # -- the loop ------------------------------------------------------------

    def submit(self, r: Req, now: float) -> None:
        self.reqs[r.rid] = r
        if len(self.reqs) <= N_REFERENCE:
            r.tokens, r.logprobs = [], []
        try:
            self.sched.submit(self._request(r.rid, r.prompt, r.max_tokens))
        except self._queue_full:
            r.reason, r.done_at = "refused", now
            self.refused += 1

    def offer(self, now: float) -> None:
        """Submit what is due at ``now`` (the kind's part)."""
        raise NotImplementedError

    def completed(self, r: Req, now: float) -> None:
        """A request ended (the closed loop sends its client's next)."""

    def tick(self) -> None:
        """Offer load, one ``Scheduler.step()``, stamp what came out."""
        sched = self.sched
        begin, sent = clock(), len(self.reqs)
        with annotate("generator"):
            self.offer(begin)
        if sched.idle():
            time.sleep(0.0002)
            return
        t0 = clock()
        with annotate("sched_step"):
            sched.step()
        t1 = clock()
        self.ticks.append((t0, t1))
        with annotate("pop_events"):
            events = sched.pop_events()
            now = clock()
            self._account(events, now)
        ended = [self.reqs[ev.request_id] for ev in events if ev.finished]
        self.tick_parts.append(Tick(
            begin, t0 - begin, t1 - t0, now, len(self.reqs) - sent,
            len(ended), sum(r.n for r in ended if not failed_reason(r))))

    def _account(self, events, now: float) -> None:
        read = streamed = 0
        for ev in events:
            r = self.reqs[ev.request_id]
            if ev.token is not None:
                streamed += 1
                r.n += 1
                if r.first_at is None:
                    r.first_at = now     # the prefill's token
                else:
                    # a decode step: it read every filled cache position
                    read += len(r.prompt) + r.n - 1
                r.last_at = now
                if r.tokens is not None:
                    r.tokens.append(ev.token)
                    r.logprobs.append(ev.logprob)
            if ev.finished:
                r.done_at, r.reason = now, ev.finish_reason
                self.completed(r, now)
        if streamed:
            self.token_stamps.append((now, streamed))
        if read:
            self.decode_reads.append((now, read))

    def run_until(self, until: float, capture=None,
                  window_start: float = 0.0) -> None:
        while True:
            now = clock()
            if now >= until:
                return
            self.tick()
            if capture is not None:
                capture.tick(now - window_start)

    def watch_gc(self) -> None:
        """Time every collection of the interpreter's garbage collector:
        a pause in the one thread that ticks is a pause of the service."""
        import gc

        began = [0.0]

        def on_gc(phase, info):
            if phase == "start":
                began[0] = clock()
            else:
                self.gc_pauses.append((began[0], clock() - began[0]))

        gc.callbacks.append(on_gc)

    def window_ticks(self, start: float, end: float) -> List[Tick]:
        return [p for p in self.tick_parts if start <= p.stamp < end]

    def log_ticks(self, start: float, end: float) -> None:
        """Two stderr lines on the window's ticks — where they spent
        their time, the slowest, and each one's length and yield: what
        to read when two runs disagree."""
        parts = self.window_ticks(start, end)
        if not parts:
            return
        whole = np.diff([start] + [p.stamp for p in parts])
        slow = np.argsort(whole)[::-1][:3]
        pauses = [d for t, d in self.gc_pauses if start <= t < end]
        recipe.log(
            f"ticks: {len(parts)} in the window, p50 "
            f"{np.median(whole) * 1e3:.1f} ms, of it Scheduler.step "
            f"{np.median([p.step_s for p in parts]) * 1e3:.1f} ms; offering "
            f"took {sum(p.offering_s for p in parts) * 1e3:.1f} ms in all; "
            f"{len(pauses)} gc pauses, {sum(pauses) * 1e3:.1f} ms in all, "
            f"longest {max(pauses, default=0.0) * 1e3:.1f} ms; slowest "
            "(index, ms, of it step, offering, sent, ended): " + "; ".join(
                f"{i} {whole[i] * 1e3:.0f} {parts[i].step_s * 1e3:.0f} "
                f"{parts[i].offering_s * 1e3:.0f} {parts[i].sent} "
                f"{parts[i].ended}" for i in slow))
        recipe.log("ticks, ms:output tokens of the requests ended: "
                   + " ".join(f"{w * 1e3:.1f}:{p.tokens}"
                              for w, p in zip(whole, parts)))

    # -- after the window ----------------------------------------------------

    def check_reference(self) -> None:
        """Streamed logprobs of the first requests against the plain
        reference's log-softmax over prompt + emitted tokens
        (teacher-forced full forward, one sequence at a time)."""
        import importlib

        import jax
        import jax.numpy as jnp

        fam = self.cell["family"]
        ref = importlib.import_module("benchmark.reference." + fam.REFERENCE)
        kw = fam.reference_kwargs(self.cell["config"])
        horizon = self.ecfg.max_seq_len
        fn = jax.jit(lambda p, t: ref.token_logprobs(
            fam.reference_params(p), t, **kw))
        worst, n_tok = 0.0, 0
        sample = [r for r in self.reqs.values() if r.tokens]
        for r in sample:
            seq = r.prompt + r.tokens
            # one padded length, one compile; the causal mask keeps the
            # padding out of every position that is read
            padded = np.zeros((horizon,), np.int32)
            padded[:len(seq)] = seq
            lp = np.asarray(fn(self.params, jnp.asarray(padded)))
            want = lp[len(r.prompt) - 1:len(seq) - 1]
            diff = np.abs(np.asarray(r.logprobs, np.float64) - want)
            worst = max(worst, float(diff.max()))
            n_tok += len(r.tokens)
        recipe.log(f"reference: {len(sample)} requests, {n_tok} tokens, "
                   f"max |logprob diff| {worst:.2e} (tolerance "
                   f"{LOGPROB_TOL})")
        self.evidence["reference_logprob_diff"] = worst
        if len(sample) < N_REFERENCE or not n_tok:
            self.problems.append(
                f"only {len(sample)} of {N_REFERENCE} reference requests "
                f"produced tokens")
        if not worst <= LOGPROB_TOL:
            self.problems.append(
                f"streamed logprobs differ from the reference by {worst}")

    def finish(self) -> None:
        self.collect()
        self.check_reference()
        summary = self.sched.summary()
        faults = {k: summary[k] for k in (
            "retries", "retry_exhausted", "rebuilds", "watchdog_trips")}
        if any(faults.values()) or self.sched.health.state != "ok":
            self.problems.append(
                f"the resilience layer absorbed a fault: {faults}, "
                f"health {self.sched.health.state}")
        bad = [r.rid for r in self.reqs.values()
               if r.done_at is not None and r.reason == "length"
               and r.n != r.max_tokens]
        if bad:
            self.problems.append(
                f"{len(bad)} requests ended with another token count "
                f"than their output length")
        self.close()

    def collect(self) -> None:
        """The loop's own records, for the per-layer readers."""
        self.evidence.update({
            "ticks": self.ticks, "decode_reads": self.decode_reads,
            "slots": self.ecfg.slots,
            "admitted": [(r.first_at, len(r.prompt))
                         for r in self.reqs.values()
                         if r.first_at is not None],
        })

    def close(self) -> None:
        engine = getattr(self, "engine", None)
        if engine is not None:
            engine.close()


def failed_reason(r: Req) -> bool:
    return r.reason is not None and r.reason != "length"


def ms(seconds: Optional[float]) -> Optional[float]:
    if seconds is None:
        return None
    return seconds * 1e3 if math.isfinite(seconds) else math.inf
