"""Fault injection, failure isolation, and live health for serving.

The serving stack (engine + scheduler) is fast and observable but a
single escaped exception, NaN-poisoned batch, or hung dispatch used to
take down the whole engine and every in-flight request. Upstream apex's
core robustness idea — the amp dynamic loss scaler that *detects* bad
numerics and *recovers* instead of crashing (``apex/amp/scaler.py``
(U)) — transplants to serving as four pieces, all host-side (zero
change to the compiled programs, so the happy path pays nothing):

- :class:`FaultPlan` — a deterministic, replayable chaos harness: each
  engine seam (``admit`` / ``dispatch`` / ``fetch``, plus the
  scheduler's ``submit``) counts its calls, and a plan maps call
  indices to injected faults (raised device errors, NaN/invalid-token
  batches, artificial hangs, queue floods). Seeded plans
  (:meth:`FaultPlan.random`) make randomized chaos soaks exact reruns.
- Failure-domain isolation — a fault poisons the engine's donated
  cache/state buffers (:class:`EngineFault`); recovery rebuilds them
  from the compiled ``init`` program and deterministically *replays*
  interrupted requests from their prompts (the last known-good slot
  snapshot is the scheduler's host record: prompt + emitted tokens —
  generation is per-request deterministic, so the replayed stream is
  bit-identical and already-streamed tokens are simply re-derived and
  suppressed). Affected requests get bounded retries with exponential
  backoff and per-request ``error`` stream events.
- Overload protection — deadline-aware admission shedding (a queued
  request whose estimated wait already blows its deadline is shed NOW,
  not left to rot), structured :class:`~apex_tpu.serving.scheduler.
  QueueFull` backpressure with a retry-after hint, and a fetch
  watchdog that flags hung dispatches.
- :class:`HealthMonitor` — the ``ok → degraded → draining → failed``
  state machine driven by detected faults, watchdog trips, retry
  exhaustion, and queue saturation; exported as the
  ``serving_health_state`` gauge and as a ``/healthz`` callback for
  :class:`apex_tpu.telemetry.http.MetricsServer` (load-balancer
  semantics: ``ok``/``degraded`` answer 200, ``draining``/``failed``
  answer 503).

Dependency-free (stdlib only) so the chaos harness imports anywhere
the telemetry layer does.
"""

from __future__ import annotations

import dataclasses
import random as _random
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# -- fault vocabulary --------------------------------------------------------

#: a raised error at the seam (simulates an exception escaping the
#: device call; poisons the engine's donated buffers)
KIND_ERROR = "error"
#: an invalid-token batch (what a NaN logit batch produces downstream:
#: out-of-vocab token ids in the fetched host array)
KIND_NAN = "nan"
#: an artificial dispatch hang, observed at fetch (the watchdog's prey)
KIND_HANG = "hang"
#: a queue flood: the submit seam reports the queue saturated
KIND_FLOOD = "flood"

FAULT_KINDS = (KIND_ERROR, KIND_NAN, KIND_HANG, KIND_FLOOD)

#: engine seams (``admit``/``dispatch``/``fetch``/``retire``) + the
#: scheduler's intake seam (``submit``, the only place a flood makes
#: sense)
FAULT_POINTS = ("admit", "dispatch", "fetch", "retire", "submit")

#: which kinds are meaningful at which seam
_VALID = {
    "admit": (KIND_ERROR, KIND_NAN),
    "dispatch": (KIND_ERROR, KIND_HANG),
    "fetch": (KIND_ERROR, KIND_NAN, KIND_HANG),
    "retire": (KIND_ERROR,),
    "submit": (KIND_FLOOD,),
}


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One planned fault: the ``index``-th call at ``point`` (0-based,
    counted per seam) misbehaves as ``kind``. ``slots`` are the lanes an
    invalid-token batch corrupts (admit: batch rows; fetch: engine
    slots); ``hang_s`` is the artificial stall for ``hang`` faults;
    ``token`` is the injected out-of-vocab id (< 0 or >= vocab both
    detect)."""

    point: str
    index: int
    kind: str
    slots: Tuple[int, ...] = (0,)
    hang_s: float = 0.0
    token: int = -1

    def describe(self) -> str:
        extra = f" hang={self.hang_s}s" if self.kind == KIND_HANG else (
            f" slots={list(self.slots)}" if self.kind == KIND_NAN else "")
        return f"{self.kind}@{self.point}[{self.index}]{extra}"


class FaultPlan:
    """A deterministic schedule of injected faults over the engine's
    seams. Each seam keeps a monotonic call counter; :meth:`take`
    advances it and returns the planned :class:`FaultSpec` for that
    call, if any — so a plan replays EXACTLY given the same request
    trace (chaos tests are reruns, not dice rolls). ``hang_fn``
    implements the stall (default ``time.sleep``); tests inject a
    fake-clock advance instead, so hangs are deterministic too.

    >>> plan = FaultPlan([FaultSpec("fetch", 2, "nan", slots=(1,))])
    >>> eng = Engine(cfg, params, mesh, ecfg, fault_plan=plan)
    """

    def __init__(self, specs: Sequence[FaultSpec] = (), *,
                 hang_fn: Callable[[float], None] = time.sleep):
        by_point: Dict[str, Dict[int, FaultSpec]] = {}
        for s in specs:
            if s.point not in FAULT_POINTS:
                raise ValueError(
                    f"unknown fault point {s.point!r}; one of "
                    f"{FAULT_POINTS}")
            if s.kind not in _VALID[s.point]:
                raise ValueError(
                    f"fault kind {s.kind!r} not injectable at "
                    f"{s.point!r} (valid: {_VALID[s.point]})")
            if s.index < 0:
                raise ValueError(f"fault index {s.index} must be >= 0")
            slot = by_point.setdefault(s.point, {})
            if s.index in slot:
                raise ValueError(
                    f"duplicate fault at {s.point}[{s.index}] — one "
                    f"fault per (point, call) keeps plans replayable")
            slot[s.index] = s
        self._by_point = by_point
        self.hang_fn = hang_fn
        self._counts = {p: 0 for p in FAULT_POINTS}
        #: specs that actually fired, in firing order — the replay
        #: record chaos tests reconcile counters against
        self.injected: List[FaultSpec] = []
        #: optional observer called with each FaultSpec the moment it
        #: fires (the scheduler wires the flight recorder here, so a
        #: post-mortem bundle shows injections next to detections)
        self.on_inject: Optional[Callable[[FaultSpec], None]] = None

    @classmethod
    def random(cls, seed: int, n_faults: int = 3, *,
               points: Sequence[str] = ("admit", "dispatch", "fetch"),
               max_index: int = 24, slots: int = 4, hang_s: float = 0.0,
               hang_fn: Callable[[float], None] = time.sleep
               ) -> "FaultPlan":
        """A seeded random plan: ``n_faults`` faults scattered over
        ``points`` within the first ``max_index`` calls of each —
        bit-reproducible from ``seed`` (``random.Random``, no global
        state), so a failing soak reruns exactly."""
        rng = _random.Random(seed)
        specs: List[FaultSpec] = []
        used = set()
        while len(specs) < n_faults and len(used) < len(points) * max_index:
            point = rng.choice(list(points))
            index = rng.randrange(max_index)
            if (point, index) in used:
                continue
            used.add((point, index))
            kind = rng.choice(_VALID[point])
            specs.append(FaultSpec(
                point, index, kind,
                slots=(rng.randrange(max(slots, 1)),),
                hang_s=hang_s if kind == KIND_HANG else 0.0))
        return cls(specs, hang_fn=hang_fn)

    @property
    def specs(self) -> Tuple[FaultSpec, ...]:
        return tuple(s for by in self._by_point.values()
                     for s in by.values())

    def take(self, point: str) -> Optional[FaultSpec]:
        """Advance ``point``'s call counter; return the fault planned
        for this call (recording it in :attr:`injected`), or None."""
        i = self._counts[point]
        self._counts[point] = i + 1
        spec = self._by_point.get(point, {}).get(i)
        if spec is not None:
            self.injected.append(spec)
            if self.on_inject is not None:
                self.on_inject(spec)
        return spec

    def counts(self) -> Dict[str, int]:
        """Calls seen per seam so far (diagnostics / plan sizing)."""
        return dict(self._counts)

    def reset(self) -> "FaultPlan":
        """Rewind the counters and the firing record — the same plan
        replays over a fresh trace."""
        self._counts = {p: 0 for p in FAULT_POINTS}
        self.injected = []
        return self


def parse_fault_plan(text: str, *,
                     hang_fn: Callable[[float], None] = time.sleep
                     ) -> FaultPlan:
    """CLI surface for fault plans: either ``random:SEED[:N]`` or a
    comma list of ``point:index:kind[:arg]`` where ``arg`` is
    ``hang_s`` for hangs and a slot index for nan faults —
    e.g. ``"fetch:2:nan:1,dispatch:5:error"``."""
    text = text.strip()
    if text.startswith("random:"):
        parts = text.split(":")
        seed = int(parts[1])
        n = int(parts[2]) if len(parts) > 2 else 3
        return FaultPlan.random(seed, n, hang_fn=hang_fn)
    specs = []
    for item in text.split(","):
        parts = item.strip().split(":")
        if len(parts) < 3:
            raise ValueError(
                f"fault spec {item!r}: want point:index:kind[:arg]")
        point, index, kind = parts[0], int(parts[1]), parts[2]
        kw: Dict[str, object] = {}
        if len(parts) > 3:
            if kind == KIND_HANG:
                kw["hang_s"] = float(parts[3])
            else:
                kw["slots"] = (int(parts[3]),)
        specs.append(FaultSpec(point, index, kind, **kw))
    return FaultPlan(specs, hang_fn=hang_fn)


class FleetFaultPlan:
    """Per-replica :class:`FaultPlan` schedule for a fleet — the chaos
    harness lifted to the router level: replica ``i``'s engine is
    built with ``fleet_plan[i]``, every plan is independently
    deterministic, and a kill-one-replica-mid-burst soak replays
    exactly from its seed.

    >>> plans = FleetFaultPlan.kill(1, 2, at=4)   # replica 1 dies
    >>> engines = [Engine(cfg, params, mesh, ecfg,
    ...                   fault_plan=plans[i]) for i in range(2)]
    """

    def __init__(self, plans: Sequence[FaultPlan]):
        self.plans: Tuple[FaultPlan, ...] = tuple(plans)
        if not self.plans:
            raise ValueError("a fleet plan needs at least one replica")

    def __len__(self) -> int:
        return len(self.plans)

    def __getitem__(self, i: int) -> FaultPlan:
        return self.plans[i]

    def __iter__(self):
        return iter(self.plans)

    @classmethod
    def random(cls, seed: int, n_replicas: int, n_faults: int = 3,
               **kw) -> "FleetFaultPlan":
        """A seeded random plan per replica — derived seeds, so the
        whole fleet soak is bit-reproducible from one ``seed``."""
        if n_replicas < 1:
            raise ValueError(f"n_replicas {n_replicas} must be >= 1")
        return cls([FaultPlan.random(seed * 1_000_003 + i, n_faults,
                                     **kw)
                    for i in range(n_replicas)])

    @classmethod
    def kill(cls, replica: int, n_replicas: int, *, at: int = 4,
             rebuilds: int = 4) -> "FleetFaultPlan":
        """Terminally fail ``replica`` at its ``at``-th decode
        dispatch: ``rebuilds`` consecutive dispatch errors with no
        healthy chunk between them exhaust the scheduler's
        ``max_consecutive_rebuilds`` (default 3, so the default
        ``rebuilds=4`` crosses it) and the health machine goes
        ``failed`` — deterministically, mid-burst. Every other
        replica's plan is empty.

        Pair the victim's scheduler with ``ResilienceConfig(
        max_retries >= rebuilds)``: with the default ``max_retries=2``
        a router's retry-exhaustion failover can move every live
        request OFF the replica after the third consecutive fault,
        leaving no traffic to consume the remaining dispatch indices —
        the replica then survives degraded instead of failing
        terminally (fine for the fleet, wrong for a kill drill). On a
        slow/throttled host, also raise ``watchdog_timeout_s``: two
        >timeout chunks trip the router's breaker and evict the victim
        the same way."""
        if not 0 <= replica < n_replicas:
            raise ValueError(
                f"replica {replica} outside fleet [0, {n_replicas})")
        specs = [FaultSpec("dispatch", at + j, KIND_ERROR)
                 for j in range(rebuilds)]
        return cls([FaultPlan(specs if i == replica else ())
                    for i in range(n_replicas)])

    @property
    def injected(self) -> List[FaultSpec]:
        """Every fault that fired, across replicas, in replica order."""
        return [s for p in self.plans for s in p.injected]

    def describe(self) -> str:
        return "; ".join(
            f"r{i}=[{', '.join(s.describe() for s in p.specs)}]"
            for i, p in enumerate(self.plans) if p.specs) or "no faults"

    def reset(self) -> "FleetFaultPlan":
        for p in self.plans:
            p.reset()
        return self


# -- exceptions --------------------------------------------------------------


class EngineFault(RuntimeError):
    """A failure at an engine seam that invalidates the donated
    cache/state buffers. The engine refuses further device calls until
    :meth:`~apex_tpu.serving.engine.Engine.rebuild_slots` reconstructs
    them (failure isolation: a poisoned buffer must never serve)."""

    def __init__(self, message: str, *, point: str = "",
                 spec: Optional[FaultSpec] = None):
        super().__init__(message)
        self.point = point
        self.spec = spec


class InjectedFault(EngineFault):
    """An :class:`EngineFault` raised by a :class:`FaultPlan` (chaos
    testing) rather than a real device failure."""


class EngineFailed(RuntimeError):
    """The health machine reached ``failed`` (terminal): recovery was
    exhausted and the scheduler aborted all work with ``error``
    outcomes. New submissions are refused."""


# -- health state machine ----------------------------------------------------

HEALTH_OK = "ok"
HEALTH_DEGRADED = "degraded"
HEALTH_DRAINING = "draining"
HEALTH_FAILED = "failed"

#: all states, in gauge-code order: ``serving_health_state`` exports
#: the tuple index (0 = ok .. 3 = failed)
HEALTH_STATES = (HEALTH_OK, HEALTH_DEGRADED, HEALTH_DRAINING,
                 HEALTH_FAILED)


class HealthMonitor:
    """The serving health state machine.

    Transitions: any detected fault / watchdog trip / queue saturation
    degrades (``ok → degraded``); ``recovery_chunks`` consecutive
    healthy decode-chunk fetches recover (``degraded → ok``);
    ``begin_drain``/``end_drain`` bracket a pipeline drain
    (``→ draining →`` back to whatever the state was, faults observed
    mid-drain land in the resume state); ``fail()`` is terminal. The
    ``serving_health_state`` gauge mirrors every transition when a
    registry is given, and :meth:`healthz` is the callback shape
    ``telemetry.http.MetricsServer(health=...)`` serves — 200 while
    traffic should keep flowing (ok/degraded), 503 when it should stop
    (draining/failed), body = the state name."""

    def __init__(self, *, registry=None, recovery_chunks: int = 2,
                 on_transition: Optional[
                     Callable[[str, str, Optional[str]], None]] = None):
        if recovery_chunks < 1:
            raise ValueError(
                f"recovery_chunks {recovery_chunks} must be >= 1")
        self.state = HEALTH_OK
        self.recovery_chunks = recovery_chunks
        self.last_cause: Optional[str] = None
        #: optional observer called AFTER each state change with
        #: ``(old, new, last_cause)`` — the scheduler wires the flight
        #: recorder + auto bundle dump here
        self.on_transition = on_transition
        self._resume = HEALTH_OK  # state a drain returns to
        self._streak = 0          # consecutive healthy chunks
        self._gauge = self._transitions = None
        if registry is not None:
            self._gauge = registry.gauge(
                "serving_health_state",
                "serving health: 0=ok 1=degraded 2=draining 3=failed")
            self._gauge.set(0)
            tr = registry.counter(
                "serving_health_transitions_total",
                "health state entries, by state", labels=("to",))
            # pre-create every state so scrapes show explicit zeros
            self._transitions = {s: tr.labels(to=s) for s in HEALTH_STATES}

    def _set(self, state: str) -> None:
        if state == self.state:
            return
        old, self.state = self.state, state
        if self._gauge is not None:
            self._gauge.set(HEALTH_STATES.index(state))
            self._transitions[state].inc()
        if self.on_transition is not None:
            self.on_transition(old, state, self.last_cause)

    # -- inputs -------------------------------------------------------------

    def record_fault(self, cause: str) -> None:
        """A detected fault / watchdog trip / overload signal: degrade
        (mid-drain: the drain continues, but resumes degraded)."""
        if self.state == HEALTH_FAILED:
            return
        self.last_cause = cause
        self._streak = 0
        if self.state == HEALTH_DRAINING:
            self._resume = HEALTH_DEGRADED
        else:
            self._set(HEALTH_DEGRADED)

    def record_progress(self) -> None:
        """One healthy decode chunk fetched end-to-end; enough of them
        in a row recover a degraded engine."""
        if self.state != HEALTH_DEGRADED:
            return
        self._streak += 1
        if self._streak >= self.recovery_chunks:
            self._set(HEALTH_OK)

    def begin_drain(self) -> None:
        if self.state in (HEALTH_FAILED, HEALTH_DRAINING):
            return
        self._resume = self.state
        self._set(HEALTH_DRAINING)

    def end_drain(self) -> None:
        if self.state == HEALTH_DRAINING:
            self._set(self._resume)

    def fail(self, cause: str) -> None:
        """Terminal: recovery exhausted."""
        self.last_cause = cause
        self._set(HEALTH_FAILED)

    # -- outputs ------------------------------------------------------------

    @property
    def code(self) -> int:
        return HEALTH_STATES.index(self.state)

    def healthz(self) -> Tuple[int, str]:
        """The ``MetricsServer(health=...)`` callback: (status code,
        body). 200 for ok/degraded (keep routing traffic), 503 for
        draining/failed (stop)."""
        status = 200 if self.state in (HEALTH_OK, HEALTH_DEGRADED) \
            else 503
        body = self.state + "\n"
        if self.state != HEALTH_OK and self.last_cause:
            body = f"{self.state} ({self.last_cause})\n"
        return status, body


# -- scheduler policy knobs --------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Recovery/overload policy for the scheduler, all host-side.
    ``max_retries`` bounds re-admissions per FAULT-AFFECTED request
    (requests merely interrupted by a batch-mate's fault replay for
    free — they were not at fault); backoff before retry ``n`` is
    ``backoff_base_s * backoff_factor**(n-1)`` on the scheduler clock.
    ``watchdog_timeout_s`` flags a decode chunk whose dispatch→fetch
    wall time exceeds it (a hung dispatch). ``shed_deadlines`` enables
    deadline-aware admission shedding (queue depth × measured chunk
    latency vs the request's deadline). ``max_consecutive_rebuilds``
    caps back-to-back recoveries with no healthy chunk between them
    before the engine is declared failed."""

    max_retries: int = 2
    backoff_base_s: float = 0.05
    backoff_factor: float = 2.0
    watchdog_timeout_s: float = 30.0
    shed_deadlines: bool = True
    recovery_chunks: int = 2
    max_consecutive_rebuilds: int = 3

    def backoff_s(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (1-based)."""
        return self.backoff_base_s * (
            self.backoff_factor ** max(attempt - 1, 0))


# -- crash drill (subprocess SIGKILL + journal recovery) ----------------------

#: the drill child: a self-contained serving subprocess the parent can
#: SIGKILL mid-stream. "run" serves a deterministic request trace
#: (optionally journaled), printing one "TOKENS <n>" progress line per
#: scheduler step — the parent's kill trigger; "recover" rebuilds via
#: journal.recover_scheduler and serves to idle. Both end with one
#: "DONE <json>" line carrying every request's final stream (the
#: recover mode merges journal-finished requests with its own
#: completions, so the parent compares complete traces). Kept as
#: source, not a function, because the whole point is a separate
#: process to kill -9.
_DRILL_CHILD_SRC = '''\
"""Crash-drill child — spawned by resilience.sigkill_drill."""
import argparse
import json
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["run", "recover"])
    ap.add_argument("--journal", default=None)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--max-tokens", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7000)
    args = ap.parse_args()

    import jax
    from apex_tpu import mesh as mx
    from apex_tpu.models import gpt
    from apex_tpu.serving import Request, SamplingParams
    from apex_tpu.serving.engine import Engine, EngineConfig
    from apex_tpu.serving.journal import (Journal, recover_scheduler,
                                          replay_state, scan_journal)
    from apex_tpu.serving.scheduler import Scheduler
    from apex_tpu.transformer.testing import standalone_gpt_config

    VOCAB = 96
    cfg = standalone_gpt_config(vocab_size=VOCAB, seq_len=64)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:1])

    def build():
        return Engine(cfg, params, mesh,
                      EngineConfig(slots=2, max_prompt_len=8,
                                   max_seq_len=24, decode_chunk=2))

    def reqs():
        out = []
        for i in range(args.requests):
            p_len = 2 + (3 * i) % 6
            prompt = [int(t) for t in jax.random.randint(
                jax.random.PRNGKey(args.seed + i), (p_len,), 0, VOCAB)]
            sp = (SamplingParams(temperature=0.9, top_k=7,
                                 seed=args.seed + i)
                  if i % 2 else SamplingParams())
            out.append(Request(f"d{i}", prompt,
                               max_tokens=args.max_tokens, sampling=sp))
        return out

    extra = {}
    if args.mode == "run":
        eng = build().warmup()
        j = Journal(args.journal) if args.journal else None
        sched = Scheduler(eng, journal=j)
        for r in reqs():
            sched.submit(r)
        while not sched.idle():
            sched.step()
            # the parent's kill trigger: one progress line per step
            print("TOKENS", sched._tokens_emitted, flush=True)
    else:
        t0 = time.monotonic()
        sched, report = recover_scheduler(args.journal, build)
        extra["recovery_ms"] = (time.monotonic() - t0) * 1e3
        extra["report"] = report.as_dict()
        # requests that finished BEFORE the crash live only in the
        # journal now — merge them so DONE carries the full trace the
        # client saw across both processes
        state = replay_state(scan_journal(args.journal)[0])
        for rid, rq in state.requests.items():
            if rq["finished"]:
                extra.setdefault("prior", {})[rid] = list(rq["emitted"])
        while not sched.idle():
            sched.step()
        extra["journal_fsync_ms"] = sched.journal.fsync_s * 1e3
    done = {rid: {"tokens": list(c.tokens), "reason": c.finish_reason}
            for rid, c in sched.completions.items()}
    print("DONE " + json.dumps({"completions": done, **extra}),
          flush=True)


if __name__ == "__main__":
    main()
'''


def sigkill_drill(workdir: str, *, requests: int = 3,
                  max_tokens: int = 10, kill_after_tokens: int = 6,
                  seed: int = 7000, timeout_s: float = 900.0,
                  python: Optional[str] = None) -> Dict[str, object]:
    """The crash drill the journal's whole design is judged by: spawn
    a serving subprocess journaling to ``workdir/journal``, ``kill
    -9`` it once ``kill_after_tokens`` tokens have streamed, restart
    from the journal in a fresh subprocess, and compare every
    request's end-to-end stream against an uninterrupted reference
    run. Returns::

        {"parity": bool, "killed_at_tokens": int, "recovery_ms": ...,
         "journal_fsync_ms": ..., "recovered_requests": int,
         "reference": {rid: [tok, ...]}, "recovered": {rid: [...]}}

    Children run on one forced-CPU device and, unless the environment
    names a warm compile cache, compile everything themselves: minutes,
    not seconds. Slow-marked tests and
    ``bench.py --mode serve --crash`` are the callers."""
    import json as _json
    import os
    import subprocess
    import sys

    import apex_tpu

    os.makedirs(workdir, exist_ok=True)
    child = os.path.join(workdir, "drill_child.py")
    with open(child, "w", encoding="utf-8") as f:
        f.write(_DRILL_CHILD_SRC)
    journal_dir = os.path.join(workdir, "journal")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(
        apex_tpu.__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    py = python or sys.executable
    base = [py, child, "--requests", str(requests),
            "--max-tokens", str(max_tokens), "--seed", str(seed)]

    def _done_line(text: str) -> Dict[str, object]:
        for line in text.splitlines():
            if line.startswith("DONE "):
                return _json.loads(line[5:])
        raise RuntimeError(f"drill child printed no DONE line:\n{text}")

    # 1) uninterrupted reference (no journal — also the A side of
    #    "recovery changes nothing")
    ref = subprocess.run(base + ["run"], env=env, capture_output=True,
                         text=True, timeout=timeout_s)
    if ref.returncode != 0:
        raise RuntimeError(f"reference run failed:\n{ref.stderr}")
    reference = {rid: c["tokens"]
                 for rid, c in _done_line(ref.stdout)["completions"].items()}

    # 2) victim: journaled, killed -9 mid-stream on the progress line
    # stderr is not read while the progress lines are: a pipe there
    # fills up under a chatty runtime and blocks the child for good
    victim = subprocess.Popen(
        base + ["run", "--journal", journal_dir], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    killed_at = -1
    try:
        assert victim.stdout is not None
        for line in victim.stdout:
            if line.startswith("TOKENS "):
                n = int(line.split()[1])
                if n >= kill_after_tokens:
                    killed_at = n
                    victim.kill()   # SIGKILL — no atexit, no flush
                    break
            elif line.startswith("DONE "):
                break   # finished before the threshold — no kill
    finally:
        victim.wait(timeout=timeout_s)
    if killed_at < 0:
        raise RuntimeError(
            f"victim finished before streaming {kill_after_tokens} "
            f"tokens — lower kill_after_tokens or raise max_tokens")

    # 3) recover from the journal in a fresh process
    rec = subprocess.run(base + ["recover", "--journal", journal_dir],
                         env=env, capture_output=True, text=True,
                         timeout=timeout_s)
    if rec.returncode != 0:
        raise RuntimeError(f"recovery run failed:\n{rec.stderr}")
    payload = _done_line(rec.stdout)
    recovered = {rid: c["tokens"]
                 for rid, c in payload["completions"].items()}
    recovered.update(payload.get("prior", {}))
    parity = recovered == reference
    return {
        "parity": parity,
        "killed_at_tokens": killed_at,
        "recovery_ms": payload.get("recovery_ms"),
        "journal_fsync_ms": payload.get("journal_fsync_ms"),
        "recovered_requests": int(
            payload.get("report", {}).get("requests", 0)),
        "reference": reference,
        "recovered": recovered,
    }
