"""Continuous-batching scheduler — the host loop around the engine.

Policy lives here, device mechanics in :mod:`apex_tpu.serving.engine`:
a FIFO request queue with backpressure (``max_queue``), per-request
deadlines (queued requests expire in place; active slots are retired),
batched admission of queued requests into free slots
(:meth:`Engine.admit_many` — a burst drains in ~1 dispatch per ladder
group instead of one per request), a response stream
(:class:`apex_tpu.serving.request.StreamEvent`), and serving metrics —
TTFT, per-token latency, queue depth, slot occupancy, tokens/s —
aggregated via :class:`apex_tpu.profiler.LatencyStats` and emitted
through a :class:`apex_tpu.profiler.MetricsLogger` when one is given.

The decode loop is PIPELINED (``pipeline_depth``): each tick dispatches
the next chunk (``Engine.step_async``) before fetching the previous
one's tokens, so the host's fetch + event processing + admission
interval overlaps device compute — serial ``device + host`` becomes
``max(device, host)``. Depth 1 is the serial loop (dispatch, then fetch
immediately); depth d keeps up to d-1 chunks in flight between ticks.
Each in-flight chunk carries a snapshot of the slots that were live at
dispatch: a slot released while the chunk was in flight (finish seen in
an earlier chunk, or a deadline retire) has its columns dropped — the
device emits pad for done slots, and a retired slot's in-flight real
tokens belong to a request that already completed. Per-request token
streams are bit-identical at every depth (the pipelined-parity test);
only deadline OBSERVATION granularity coarsens with depth, exactly as
it already coarsens with ``decode_chunk``.

Fault tolerance (:mod:`apex_tpu.serving.resilience`): an exception
escaping an engine seam, an invalid-token (NaN-poisoned) batch, or a
hung dispatch no longer takes the engine down. The failing chunk/call
is quarantined, the engine's donated buffers are rebuilt from the
compiled ``init`` program, and every interrupted request is
deterministically REPLAYED from its prompt (generation is per-request
deterministic, so the replayed stream is bit-identical and
already-streamed tokens are re-derived silently). Requests in the
fault's blast radius get bounded retries with exponential backoff and
``error`` stream events; retry exhaustion completes them with the
``error`` finish reason. Overload protection: deadline-aware admission
shedding (queue depth × measured chunk latency vs the deadline — shed
NOW instead of rotting then expiring), structured :class:`QueueFull`
with a retry-after hint, and a fetch watchdog flagging hung dispatches.
``self.health`` is the ``ok → degraded → draining → failed`` state
machine, scrapeable live via
``telemetry.http.MetricsServer(health=sched.health.healthz)``.

Observability (``apex_tpu.telemetry``): pass ``registry`` to count
admissions (by prefill bucket and admission-batch size) / finishes-by-
reason / tokens / faults / retries / rebuilds / sheds, gauge the
in-flight pipeline depth and health state, and observe TTFT + per-token
latency into SLO-bucketed histograms (scrapeable live via
``telemetry.http.MetricsServer``), and ``spans`` to record each
request's phase timeline (queued → prefill → first_token → decode
chunks → retired, plus ``error`` marks) and ``engine.dispatch`` /
``engine.fetch`` / ``engine.admit`` / ``engine.rebuild`` host sections.
Both are pre-bound at construction so the per-token hot path pays an
attribute access and an add, nothing more.

Black box (``apex_tpu.telemetry.flightrec``): pass ``recorder`` to log
every load-bearing host decision (submits/sheds, admit dispatches,
chunk dispatch/fetch, spec-gate flips, fault injection/detection,
rebuild/replay brackets, watchdog and guard alarms, health
transitions) into a bounded ring of O(1) tuple appends, and
``bundle_dir`` to auto-dump an atomic self-contained post-mortem
bundle on any fault detection, guard alarm, watchdog trip, or terminal
failure — ``python -m apex_tpu.telemetry.replay <bundle>`` rebuilds
the run from it and checks the replayed streams bit-identical, and
``--report`` renders the incident timeline with no jax installed.

The boundary fix the engine relies on: a request whose prompt already
ends in its eos token completes at ``submit`` time with zero generated
tokens — it never occupies a slot (admitting it would burn
``max_tokens`` steps decoding past a finished sequence).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import time
from typing import (
    Any,
    Callable,
    Deque,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
)

from apex_tpu import profiler
from apex_tpu.serving import journal as journal_mod
from apex_tpu.serving.engine import (
    Admission,
    ChunkedAdmission,
    Engine,
    StepHandle,
)
from apex_tpu.serving import latent_engine
from apex_tpu.serving import sampling
from apex_tpu.serving.pages import PagesExhausted
from apex_tpu.serving.request import (
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    FINISH_REASONS,
    FINISH_STOP,
    FINISH_TIMEOUT,
    Completion,
    Request,
    StopMatcher,
    StreamEvent,
)
from apex_tpu.serving.resilience import (
    HEALTH_DRAINING,
    HEALTH_FAILED,
    KIND_FLOOD,
    EngineFailed,
    HealthMonitor,
    ResilienceConfig,
)
from apex_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    TenancyConfig,
    TenantBook,
    TenantThrottled,
)
from apex_tpu.serving.tuner import Controller, TunerConfig, ewma
from apex_tpu.telemetry import flightrec as flightrec_mod
from apex_tpu.telemetry import spans as spans_mod
from apex_tpu.telemetry.ring import Ring
from apex_tpu.telemetry.slo import (
    METRICS as SLO_METRICS,
    STATE_CODE as SLO_STATE_CODE,
    SLOConfig,
    SLOMonitor,
    SLOObjective,
)

#: fault causes the scheduler can detect (label values of
#: ``serving_faults_detected_total``, pre-created so scrapes show
#: explicit zeros)
FAULT_CAUSES = ("admit", "dispatch", "fetch", "retire", "invalid_token")

#: shed reasons (label values of ``serving_requests_shed_total``)
SHED_REASONS = ("queue_full", "deadline", "tenant_rate")

#: what a phase of the tick is without a span recorder
_NO_PHASE = contextlib.nullcontext()


@dataclasses.dataclass(frozen=True)
class SpecGateConfig:
    """Policy knobs of the speculative-decoding payoff gate (only
    meaningful on an engine with ``EngineConfig.spec_k > 0``).

    The gate is the docs/DESIGN.md "Serving round 3" lesson applied to
    speculation: a speculative chunk only pays when the drafts it
    verifies actually land, so the scheduler measures BOTH compiled
    variants' chunk wall times and an acceptance EWMA, and dispatches
    the spec variant only while::

        EWMA(tokens emitted per wave)  >  wall_spec / wall_plain

    (the break-even: a spec wave costs ``wall_spec / decode_chunk``
    and emits ``tpw`` tokens; a plain step costs ``wall_plain /
    decode_chunk`` per token — spec wins iff tpw clears the wall
    ratio). Both variants are pre-warmed, so switching never
    recompiles."""

    #: weight of the newest acceptance sample in the EWMA
    ewma_alpha: float = 0.3
    #: a CLOSED gate reopens only when the EWMA clears break-even by
    #: this factor (hysteresis — an open gate closes at 1.0x)
    margin: float = 1.05
    #: re-probe cadence, symmetric in both directions: a CLOSED gate
    #: sends one speculative chunk per this many plain chunks (a
    #: workload that turns repetitive reopens the gate), and an OPEN
    #: gate sends one plain chunk per this many speculative chunks so
    #: ``wall_plain`` tracks the growing attention cost instead of
    #: freezing at short-context values (a stale baseline inflates the
    #: break-even and flaps the gate closed on exactly the
    #: long-generation workloads speculation targets)
    probe_every: int = 40
    #: speculative chunks to measure before the gate decides at all
    min_probe_chunks: int = 2


#: ``serving_spec_gate_state`` gauge values
GATE_CLOSED, GATE_MEASURING, GATE_OPEN = 0.0, 1.0, 2.0


class _SpecGate:
    """The live payoff-gate state machine behind
    :class:`SpecGateConfig` — wall-time EWMAs for both chunk variants,
    the acceptance (tokens-per-wave) EWMA, and the open/closed/probe
    decision. Pure host arithmetic; the decision only picks which
    pre-warmed compiled variant the next dispatch uses."""

    __slots__ = ("cfg", "spec_k", "accept_ewma", "wall_spec",
                 "wall_plain", "spec_chunks", "plain_since_probe",
                 "spec_since_plain", "_open")

    def __init__(self, cfg: SpecGateConfig, spec_k: int):
        self.cfg = cfg
        self.spec_k = spec_k
        self.accept_ewma = 0.0      # tokens per wave (1 .. spec_k + 1)
        self.wall_spec = 0.0
        self.wall_plain = 0.0
        self.spec_chunks = 0
        self.plain_since_probe = 0
        self.spec_since_plain = 0
        self._open = True           # optimistic until measured

    def _ewma(self, prev: float, sample: float) -> float:
        return ewma(prev, sample, self.cfg.ewma_alpha)

    def break_even(self) -> float:
        """Tokens per wave a spec chunk must emit to match the plain
        variant's cost — ``wall_spec / wall_plain`` (0.0 until both
        are measured)."""
        if self.wall_spec <= 0.0 or self.wall_plain <= 0.0:
            return 0.0
        return self.wall_spec / self.wall_plain

    def want_spec(self, spec_inflight: int = 0) -> bool:
        """Which variant the NEXT chunk should use. ``spec_inflight``
        is the count of speculative chunks dispatched but not yet
        fetched: the fetch-side counters reset only when a probe LANDS,
        so until the gate has measured its way open, probes are
        serialized — at most one speculative chunk in flight — lest a
        pipelined scheduler multiply the documented one-chunk probe
        overhead by its depth."""
        if self.wall_plain == 0.0:
            return False            # measure the plain baseline first
        measuring = self.spec_chunks < self.cfg.min_probe_chunks
        if (measuring or not self._open) and spec_inflight > 0:
            return False            # one probe at a time
        if measuring:
            return True             # measuring the spec side
        if self._open:
            # plain-refresh probe: once per probe_every spec chunks the
            # open gate re-measures wall_plain (see SpecGateConfig)
            return self.spec_since_plain < self.cfg.probe_every
        return self.plain_since_probe >= self.cfg.probe_every

    def observe_plain(self, wall: float) -> None:
        self.wall_plain = self._ewma(self.wall_plain, wall)
        self.plain_since_probe += 1
        self.spec_since_plain = 0

    def observe_spec(self, wall: float,
                     tokens_per_wave: Optional[float]) -> None:
        self.wall_spec = self._ewma(self.wall_spec, wall)
        self.spec_chunks += 1
        self.plain_since_probe = 0
        self.spec_since_plain += 1
        if tokens_per_wave is not None:
            self.accept_ewma = self._ewma(self.accept_ewma,
                                          tokens_per_wave)
        if self.accept_ewma == 0.0:
            # no acceptance sample has EVER landed (every probe chunk's
            # rows were retired mid-flight) — deciding now would close
            # the gate on zero data; keep measuring instead. A real
            # sample can never be 0.0 (a live wave always emits >= 1
            # token), so this is an unambiguous never-measured sentinel
            return
        be = self.break_even()
        if be <= 0.0 or self.spec_chunks < self.cfg.min_probe_chunks:
            return
        if self._open:
            self._open = self.accept_ewma > be
        else:
            # hysteresis: reopening needs the margin
            self._open = self.accept_ewma > be * self.cfg.margin

    def state(self) -> float:
        """Gauge value: 2 open, 1 measuring, 0 closed."""
        if (self.wall_plain == 0.0
                or self.spec_chunks < self.cfg.min_probe_chunks
                or self.accept_ewma == 0.0):
            return GATE_MEASURING
        return GATE_OPEN if self._open else GATE_CLOSED


class QueueFull(RuntimeError):
    """Backpressure signal: the request queue is at ``max_queue``.
    Carries structured overload context so a client (or gateway) can
    back off intelligently instead of parsing the message:
    ``queue_depth`` is the depth at rejection time and
    ``retry_after_s`` estimates when the queue will have drained
    (depth × measured chunk latency; 0.0 before any chunk has been
    measured)."""

    def __init__(self, message: str, *, queue_depth: int = 0,
                 retry_after_s: float = 0.0):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s


class _RegistryMetrics:
    """Pre-bound registry handles — children resolved once here so the
    scheduler's per-token path never does a name/label lookup."""

    def __init__(self, registry, engine: Engine):
        self.queue_depth = registry.gauge(
            "serving_queue_depth", "requests waiting for a slot")
        self.active_slots = registry.gauge(
            "serving_active_slots", "decode slots currently occupied")
        registry.gauge(
            "serving_slots_total", "decode slots in the engine"
        ).set(engine.slots)
        self.inflight = registry.gauge(
            "serving_inflight_chunks",
            "decode chunks dispatched but not yet fetched (the pipeline "
            "depth actually in use)")
        self.submitted = registry.counter(
            "serving_requests_submitted_total", "requests accepted into "
            "the queue (or completed at submit)")
        self.admitted = registry.counter(
            "serving_requests_admitted_total",
            "requests prefilled into a slot")
        self.admit_dispatches = registry.counter(
            "serving_admit_dispatches_total",
            "batched admission dispatches (one compiled (bucket, k) "
            "program call each)")
        ab = registry.counter(
            "serving_admit_batch_requests_total",
            "requests admitted, by admission-batch size",
            labels=("size",))
        # pre-create every ladder rung so a scrape shows explicit zeros
        self.admit_batch = {k: ab.labels(size=str(k))
                            for k in engine.admit_batch_sizes}
        bk = registry.counter(
            "serving_prefill_bucket_requests_total",
            "requests admitted, by padded prefill bucket",
            labels=("bucket",))
        self.bucket = {b: bk.labels(bucket=str(b))
                       for b in engine.prompt_buckets}
        fin = registry.counter(
            "serving_requests_finished_total",
            "completed requests by finish reason", labels=("reason",))
        self.finished = {r: fin.labels(reason=r) for r in FINISH_REASONS}
        self.queue_expired = registry.counter(
            "serving_queue_expired_total",
            "requests that blew their deadline while still queued")
        self.tokens = registry.counter(
            "serving_tokens_emitted_total", "generated tokens streamed")
        self.steps = registry.counter(
            "serving_scheduler_steps_total", "scheduler ticks")
        self.ttft = registry.histogram(
            "serving_ttft_seconds", "arrival to first token")
        self.token_latency = registry.histogram(
            "serving_token_latency_seconds",
            "per-token steady-decode latency (chunk dispatch-to-fetch "
            "wall time / chunk tokens)")
        self.request_latency = registry.histogram(
            "serving_request_latency_seconds", "arrival to completion")
        # -- resilience (apex_tpu.serving.resilience) -------------------
        flt = registry.counter(
            "serving_faults_detected_total",
            "faults detected at engine seams, by cause",
            labels=("cause",))
        self.faults = {c: flt.labels(cause=c) for c in FAULT_CAUSES}
        shed = registry.counter(
            "serving_requests_shed_total",
            "requests rejected/shed by overload protection, by reason",
            labels=("reason",))
        self.shed = {r: shed.labels(reason=r) for r in SHED_REASONS}
        self.retries = registry.counter(
            "serving_retries_total",
            "fault-affected requests scheduled for re-admission")
        self.rebuilds = registry.counter(
            "serving_rebuilds_total",
            "cache/state buffer rebuilds after a fault")
        self.watchdog = registry.counter(
            "serving_watchdog_trips_total",
            "decode chunks whose dispatch-to-fetch wall time exceeded "
            "the watchdog timeout (hung dispatches)")
        self.replayed = registry.counter(
            "serving_replayed_tokens_total",
            "tokens re-derived (and suppressed) during deterministic "
            "replay after a rebuild")
        # -- KV-cache capacity (quantized cache + prefix pool) ------------
        registry.gauge(
            "serving_kv_cache_bytes",
            "device bytes held by the slot KV cache (quantized data + "
            "scale planes under a quantized kv_cache_dtype)"
        ).set(engine.cache_bytes())
        # -- paged KV cache (EngineConfig.page_size) ----------------------
        # pre-created even for contiguous engines (explicit zeros in
        # scrapes, same convention as every ladder counter above)
        self.pages_in_use = registry.gauge(
            "serving_pages_in_use",
            "KV-cache pages currently allocated (paged layout; 0 under "
            "the contiguous layout)")
        self.pages_free = registry.gauge(
            "serving_pages_free",
            "KV-cache pages on the free list (paged layout)")
        self.pages_shared = registry.gauge(
            "serving_pages_shared",
            "KV-cache pages pinned by more than one holder — "
            "copy-on-write prefix pages with live sharers")
        self.page_fragmentation = registry.gauge(
            "serving_page_fragmentation",
            "internal fragmentation of the allocated pages: 1 - "
            "used_tokens / (pages_in_use * page_size)")
        self.page_share_hits = registry.counter(
            "serving_page_share_hits_total",
            "admissions that mapped a registered prefix's pages "
            "copy-on-write instead of copying prefix K/V bytes")
        self.pages_exhausted = registry.counter(
            "serving_pages_exhausted_total",
            "admission waves deferred because the page pool had fewer "
            "free pages than the head request needed (backpressure — "
            "the request stays queued)")
        # -- host-swap oversubscription (EngineConfig.host_swap) ----------
        self.pages_swapped = registry.gauge(
            "serving_pages_swapped",
            "KV-cache pages parked in the host-RAM swap tier (paused "
            "conversations' private pages; 0 without host_swap)")
        self.swap_bytes = registry.gauge(
            "serving_swap_bytes",
            "host-RAM bytes held by parked swap payloads (storage-form "
            "page blocks plus state rows)")
        self.preemptions = registry.counter(
            "serving_preemptions_total",
            "active requests preempted under page pressure (the WFQ "
            "victim's pages freed; its stream resumes bit-identically "
            "via fault replay)")
        self.chunked_chunks = registry.counter(
            "serving_chunked_prefill_chunks_total",
            "chunked-prefill chunk forwards dispatched (long-prompt "
            "admissions interleaved with decode waves)")
        self.chunked_admissions = registry.counter(
            "serving_chunked_admissions_total",
            "requests admitted through the chunked-prefill path")
        self.prefix_hits = registry.counter(
            "serving_prefix_hits_total",
            "submitted requests that matched a pooled shared prefix "
            "(admission pays the tail bucket only)")
        self.prefix_misses = registry.counter(
            "serving_prefix_misses_total",
            "submitted requests that missed the prefix pool (cold "
            "prefill at the full prompt bucket)")
        # -- speculative decoding (EngineConfig.spec_k) -------------------
        self.spec_drafted = registry.counter(
            "serving_spec_drafted_total",
            "draft tokens proposed to the speculative verify forward")
        self.spec_accepted = registry.counter(
            "serving_spec_accepted_total",
            "draft tokens the target's verification accepted (emitted "
            "beyond the one-per-wave baseline)")
        self.spec_gate = registry.gauge(
            "serving_spec_gate_state",
            "speculation payoff gate: 2 open, 1 measuring, 0 closed")
        self.spec_accept_ewma = registry.gauge(
            "serving_spec_acceptance_ewma",
            "EWMA of tokens emitted per speculative wave (the gate "
            "compares it to the measured wall_spec/wall_plain "
            "break-even)")
        # -- multi-tenant serving (serving.tenancy) -----------------------
        # tenant-labeled children are created lazily per tenant (the
        # label set is the live tenant population, not a config-time
        # ladder) and cached so the per-token path pays a dict get
        tt = registry.counter(
            "serving_tenant_tokens_total",
            "generated tokens streamed, by tenant", labels=("tenant",))
        ta = registry.counter(
            "serving_tenant_admissions_total",
            "requests prefilled into a slot, by tenant",
            labels=("tenant",))
        ts = registry.counter(
            "serving_tenant_sheds_total",
            "requests shed or rate-throttled, by tenant and reason",
            labels=("tenant", "reason"))
        tq = registry.gauge(
            "serving_tenant_queue_depth",
            "queued requests, by tenant", labels=("tenant",))
        self._tenant_families = (tt, ta, ts, tq)
        self._tenant_children: Dict[str, Dict[str, Any]] = {}
        # -- self-tuning control plane (serving.tuner) --------------------
        # pre-created even without a tuner (explicit zeros in scrapes,
        # the ladder-counter convention); per-knob children are bound
        # by the scheduler once the declared knobs are known
        self.tuner_state = registry.gauge(
            "serving_tuner_state",
            "self-tuning controller: 0 frozen, 1 measuring, 2 steady, "
            "3 probing")
        self._tuner_knob_family = registry.gauge(
            "serving_tuner_knob",
            "incumbent operating-point value per tuned knob",
            labels=("knob",))
        self._tuner_switch_family = registry.counter(
            "serving_tuner_switches_total",
            "operating-point switches the controller committed, by "
            "knob", labels=("knob",))
        self.tuner_knob: Dict[str, Any] = {}
        self.tuner_switches: Dict[str, Any] = {}
        # -- SLO observatory (telemetry.slo) ------------------------------
        # pre-created even without an SLO config (explicit zeros in
        # scrapes); quantile/objective children are bound lazily by
        # the scheduler's gauge refresh once the monitor exists
        self._slo_quantile_family = registry.gauge(
            "serving_slo_quantile_seconds",
            "streaming sketch-backed latency quantiles, by metric "
            "(ttft/token_latency/queue_wait/e2e) and quantile "
            "(p50/p95/p99)", labels=("metric", "quantile"))
        self._slo_burn_family = registry.gauge(
            "serving_slo_burn_rate",
            "error-budget burn rate per objective and window (1.0 = "
            "consuming the budget exactly on schedule)",
            labels=("objective", "window"))
        self._slo_state_family = registry.gauge(
            "serving_slo_state",
            "burn-rate machine state per objective: 0 ok, 1 warning, "
            "2 burning", labels=("objective",))
        self._slo_budget_family = registry.gauge(
            "serving_slo_budget_remaining",
            "fraction of the error budget left per objective (1 "
            "untouched, 0 exhausted, negative = overrun)",
            labels=("objective",))
        self._slo_alert_family = registry.counter(
            "serving_slo_alerts_total",
            "burn-rate alerts fired (transitions into warning or "
            "burning), by objective and state",
            labels=("objective", "state"))
        self.slo_quantile: Dict[Tuple[str, str], Any] = {}
        self.slo_children: Dict[str, Dict[str, Any]] = {}
        # -- durable request journal (serving.journal) --------------------
        # pre-created even without a journal (explicit zeros in
        # scrapes, the ladder-counter convention); refreshed at the
        # scheduler's fetch-boundary commit
        self.journal_appends = registry.counter(
            "serving_journal_appends_total",
            "write-ahead journal records appended (submit/extend/"
            "finish/park/resume/registrations)")
        self.journal_rotations = registry.counter(
            "serving_journal_rotations_total",
            "journal segments sealed and rotated")
        self.journal_compactions = registry.counter(
            "serving_journal_compactions_total",
            "journal compactions (finished requests dropped, live "
            "state rewritten into one fresh segment)")
        self.journal_fsync = registry.counter(
            "serving_journal_fsync_seconds",
            "wall seconds spent in journal fsync calls — the "
            "durability tax the fsync policy prices")
        self.journal_bytes = registry.gauge(
            "serving_journal_bytes",
            "write-ahead journal bytes on disk across all segments")
        self.journal_lag = registry.gauge(
            "serving_journal_lag_bytes",
            "journal bytes appended since the last fsync — what a "
            "crash right now could lose to the page cache")
        self.journal_recovered = registry.counter(
            "serving_journal_recovered_total",
            "unfinished requests resubmitted from a journal during "
            "crash recovery (replay_into/recover_scheduler)")

    def tenant(self, t: str) -> Dict[str, Any]:
        """Cached per-tenant metric children (created on first
        sight)."""
        ch = self._tenant_children.get(t)
        if ch is None:
            tt, ta, ts, tq = self._tenant_families
            ch = self._tenant_children[t] = {
                "tokens": tt.labels(tenant=t),
                "admitted": ta.labels(tenant=t),
                "queue": tq.labels(tenant=t),
                "shed": {r: ts.labels(tenant=t, reason=r)
                         for r in SHED_REASONS},
            }
        return ch

    def bind_tuner(self, knobs) -> None:
        """Pre-create the per-knob children for the declared ladder
        (explicit zeros in scrapes, like every ladder counter)."""
        for k in knobs:
            self.tuner_knob[k] = self._tuner_knob_family.labels(knob=k)
            self.tuner_switches[k] = \
                self._tuner_switch_family.labels(knob=k)

    def bind_slo(self, metrics, objective_keys) -> None:
        """Pre-create the SLO children for the declared surface —
        quantile gauges per metric and burn/state/budget/alert
        children per objective (explicit zeros in scrapes)."""
        for m in metrics:
            for q in ("p50", "p95", "p99"):
                self.slo_quantile[(m, q)] = \
                    self._slo_quantile_family.labels(metric=m,
                                                     quantile=q)
        for k in objective_keys:
            self.slo_children[k] = {
                "fast": self._slo_burn_family.labels(objective=k,
                                                     window="fast"),
                "slow": self._slo_burn_family.labels(objective=k,
                                                     window="slow"),
                "state": self._slo_state_family.labels(objective=k),
                "budget": self._slo_budget_family.labels(objective=k),
                "alerts": {
                    s: self._slo_alert_family.labels(objective=k,
                                                     state=s)
                    for s in ("warning", "burning")},
            }


class _Active:
    """Host view of one occupied slot. ``suppress`` is the replay
    offset: tokens up to that count were already streamed before a
    fault and are re-derived silently. ``tokens``/``logprobs`` hold the
    CLIENT-VISIBLE stream — tokens held back by the stop matcher (a
    possible stop-sequence prefix) live inside ``matcher`` until
    flushed or trimmed; replay re-derives them for free."""

    __slots__ = ("request", "tokens", "logprobs", "first_token_time",
                 "suppress", "matcher")

    def __init__(self, request: Request):
        self.request = request
        self.tokens: List[int] = []
        self.logprobs: List[float] = []
        self.first_token_time: Optional[float] = None
        self.suppress = 0
        self.matcher = (StopMatcher(request.stop)
                        if request.stop else None)


class _ReplayState:
    """Recovery bookkeeping for one request across rebuilds: the
    tokens already streamed (the 'last known-good snapshot' replay
    re-derives), retry attempts consumed, and the backoff gate."""

    __slots__ = ("tokens", "logprobs", "attempts", "not_before")

    def __init__(self):
        self.tokens: List[int] = []
        self.logprobs: List[float] = []
        self.attempts = 0
        self.not_before = float("-inf")


class _Parked:
    """One paused conversation in the host-swap tier: the live
    :class:`_Active` it continues as on a swap-resume (stream state,
    stop matcher, held tokens — all intact), plus park metadata.
    ``swap`` flips False when the tier capacity-evicts the payload;
    the conversation then resumes by recompute from the grow-only
    emitted-prefix snapshot the park took first."""

    __slots__ = ("act", "n_pages", "swap", "parked_at")

    def __init__(self, act: _Active, n_pages: int, swap: bool,
                 parked_at: float):
        self.act = act
        self.n_pages = n_pages
        self.swap = swap
        self.parked_at = parked_at


#: _ingest outcomes: the slot is still decoding, was released, or a
#: retire-seam fault triggered recovery mid-call (the caller must
#: abandon its unpack/admission loop — scheduler state was rebuilt)
_LIVE, _RELEASED, _RECOVERED = 0, 1, 2


@dataclasses.dataclass
class EvictedRequest:
    """One interrupted request handed to a :attr:`Scheduler.on_evict`
    hook instead of an ``error`` completion: the request itself plus
    the longest CLIENT-VISIBLE stream it was sent (the grow-only
    emitted-prefix snapshot fault replay maintains). A fleet router
    resubmits it to a healthy replica with
    ``submit(request, replay_prefix=tokens)`` — replay re-derives the
    prefix silently, so the client stream continues bit-identical with
    zero duplicate or lost tokens."""

    request: Request
    tokens: List[int]
    logprobs: List[float]


class Scheduler:
    """Drive an :class:`Engine` over a stream of requests.

    >>> sched = Scheduler(engine, pipeline_depth=2)
    >>> sched.submit(Request("r0", prompt, max_tokens=16))
    >>> sched.run_until_idle()
    >>> sched.completions["r0"].tokens

    ``clock`` is injectable (tests drive deadlines with a fake clock);
    it must be monotonic — inject ``sleep`` alongside it (backoff
    waits go through ``sleep``, and real sleeping cannot advance a
    fake clock). ``metrics`` receives one record per step plus one per
    completion. ``pipeline_depth`` >= 2 overlaps host work with device
    decode (see module docstring); ``max_admit_batch`` caps how many
    queued requests one tick hands to ``Engine.admit_many`` (None =
    all that fit the free slots; 1 = serial single admits, the A/B
    baseline). ``resilience`` tunes recovery/overload policy
    (defaults: :class:`~apex_tpu.serving.resilience.ResilienceConfig`).

    Self-tuning (``tuner=TunerConfig(...)``,
    :mod:`apex_tpu.serving.tuner`): a scheduler-owned controller tunes
    the declared knob ladders — ``decode_chunk`` / ``pipeline_depth``
    / ``max_admit_batch`` / ``spec_k`` — online from per-chunk
    tokens-per-second EWMAs, switching ONLY among pre-warmed compiled
    variants (``EngineConfig.decode_chunks`` / ``spec_ks``; validated
    at construction) so an armed recompile guard stays flat. One knob
    moves per probe window (coordinate descent), probes serialize to
    one in-flight chunk, and the controller hard-freezes to the base
    operating point during constrained decoding, fault replay,
    rebuilds, and drain. ``pipeline_depth`` and ``max_admit_batch``
    become LIVE attributes under a tuner (the controller rewrites them
    per tick); a tuner owning ``spec_k`` replaces the spec gate. Every
    decision and every observation it derives from is a
    flight-recorder event, so a tuning trajectory replays
    bit-identically from a post-mortem bundle. Token streams stay
    bit-identical to any fixed-knob run (the chunk-parity and
    pipelined==serial oracles extend across controller switching).

    Black box (``apex_tpu.telemetry.flightrec``): pass ``recorder`` (a
    :class:`~apex_tpu.telemetry.flightrec.FlightRecorder`) to log every
    load-bearing decision as O(1) event appends, and ``bundle_dir`` to
    auto-dump a self-contained post-mortem bundle on any fault
    detection, guard alarm, watchdog trip, or terminal failure
    (:meth:`dump_bundle` triggers one on demand;
    ``python -m apex_tpu.telemetry.replay <bundle>`` re-runs it and
    checks the replayed streams bit-identical). Per-request replay
    records (prompt/sampling/emitted prefix) are kept regardless —
    live requests exactly, completed ones in a ``request_log``-bounded
    ring.
    """

    def __init__(self, engine: Engine, *, max_queue: int = 256,
                 metrics: Optional[profiler.MetricsLogger] = None,
                 registry=None, spans=None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 pipeline_depth: int = 1,
                 max_admit_batch: Optional[int] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 spec_gate: Optional[SpecGateConfig] = None,
                 tuner: Optional[TunerConfig] = None,
                 tenancy: Optional[TenancyConfig] = None,
                 slo: Optional[SLOConfig] = None,
                 recorder=None, bundle_dir: Optional[str] = None,
                 bundle_meta: Optional[Dict] = None,
                 max_auto_bundles: int = 4,
                 request_log: int = 4096,
                 preempt: Optional[bool] = None,
                 on_evict: Optional[
                     Callable[[List[EvictedRequest], str], None]] = None,
                 journal=None):
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth {pipeline_depth} must be >= 1 (1 = the "
                f"serial loop)")
        if max_admit_batch is not None and max_admit_batch < 1:
            raise ValueError(
                f"max_admit_batch {max_admit_batch} must be >= 1 or None")
        self.engine = engine
        self.max_queue = max_queue
        self.metrics = metrics
        self.clock = clock
        self.sleep = sleep
        self.pipeline_depth = pipeline_depth
        self.max_admit_batch = max_admit_batch
        #: constructor values, kept verbatim for the bundle config: a
        #: tuner rewrites the live attributes per tick (a mid-probe
        #: dump would otherwise record a transient candidate as "the"
        #: config and skew replay's rebuilt controller base)
        self._cfg_pipeline_depth = pipeline_depth
        self._cfg_max_admit_batch = max_admit_batch
        self.resilience = resilience or ResilienceConfig()
        #: multi-tenant policy (serving.tenancy): per-tenant
        #: weighted-fair queueing with deficit counters + priority
        #: aging (engaged whenever more than one tenant is backlogged
        #: — a single-tenant queue pops strict FIFO, bit-identical to
        #: the pre-tenancy scheduler), token-budget rate limits
        #: (submit raises TenantThrottled → the API's 429 +
        #: Retry-After), and per-tenant accounting. The book exists
        #: even without a TenancyConfig so tenant-labeled telemetry
        #: and summaries always work; rates require a config.
        self._tenancy_cfg = tenancy
        self.tenants = TenantBook(tenancy, clock)
        self._throttled = 0
        #: telemetry sinks (both optional): a telemetry.Registry the
        #: scheduler counts/observes into, and a telemetry.SpanRecorder
        #: receiving per-request phase marks, the tick's phases
        #: (``sched.*``) and the engine sections inside them. The
        #: recorder's clock is slaved to the scheduler's so injected
        #: test clocks produce deterministic timelines, and its
        #: sections are annotated into the profiler's trace
        #: (``apex.sched.*`` / ``apex.engine.*`` on the device trace's
        #: clock), with a clock row here and at every tick's entry that
        #: puts the recorder's other rows on that clock too. Without a
        #: recorder a phase costs one ``is None``.
        self.telemetry = (None if registry is None
                          else _RegistryMetrics(registry, engine))
        self.spans = spans
        if spans is not None:
            spans.clock = self.clock
            spans.annotate = profiler.annotate
            spans.anchor()
        self._registry = registry
        #: flight recorder (telemetry.flightrec.FlightRecorder) — the
        #: always-on black box: every load-bearing host decision is one
        #: O(1) event append. Its clock is slaved to the scheduler's,
        #: like the span recorder's, so injected test clocks produce
        #: deterministic timelines; fault-plan injections are observed
        #: through FaultPlan.on_inject so a bundle shows injections
        #: next to detections.
        self.recorder = recorder
        if recorder is not None:
            recorder.clock = self.clock
        if engine.fault_plan is not None:
            # the NEWEST scheduler owns the observer either way: a
            # recorder-less scheduler over a shared engine (the bench's
            # on/off A/B, a service rebuilding on config reload) must
            # clear a dead predecessor's wiring, not inherit it
            engine.fault_plan.on_inject = (
                None if recorder is None else
                lambda spec: recorder.record(
                    "inject", spec.point, spec.index, spec.kind))
        #: post-mortem bundles: ``bundle_dir`` is where auto-dumps land
        #: (fault detection / watchdog trip / guard alarm / terminal
        #: failure — at most ``max_auto_bundles``, one per trigger
        #: wave; None disables auto-dump, :meth:`dump_bundle` with an
        #: explicit dir still works). ``bundle_meta`` is carried
        #: verbatim into the manifest — put params provenance there
        #: (``{"params": {"init_seed": 0}}``) so
        #: ``python -m apex_tpu.telemetry.replay`` can rebuild the
        #: model.
        self.bundle_dir = bundle_dir
        self.bundle_meta = dict(bundle_meta or {})
        self.max_auto_bundles = max_auto_bundles
        #: bundle paths written so far (auto + manual), oldest first
        self.bundles_written: List[str] = []
        self._auto_bundles = 0
        self._bundle_counter = 0
        self._dump_token = 0        # one auto-dump per trigger wave
        self._last_dump_token = -1
        #: replayable per-request records — live (queued/active) by id,
        #: completed in a bounded ring; the bundle's requests.jsonl
        self._req_records: Dict[str, Dict] = {}
        self._req_done = Ring(request_log)
        self._submit_seq = 0
        #: router-facing eviction hook (``(evicted, cause) -> None``):
        #: when set, work this scheduler can no longer serve — every
        #: queued/active request at terminal failure, or a single
        #: request whose bounded retries exhausted — is handed over as
        #: :class:`EvictedRequest` records (emitted prefix attached)
        #: INSTEAD of being aborted with ``error`` events, so a fleet
        #: router can fail it over to a healthy replica with the client
        #: stream intact. None (the default) keeps the single-engine
        #: abort-with-error semantics unchanged.
        self.on_evict = on_evict
        #: durable write-ahead request journal
        #: (:class:`apex_tpu.serving.journal.Journal`): every
        #: durable-relevant host decision — submits, emitted-prefix
        #: extends at fetch boundaries, finishes, park/resume,
        #: registrations — is appended so
        #: :func:`~apex_tpu.serving.journal.recover_scheduler` can
        #: continue every unfinished stream bit-identically after a
        #: process death. None (the default) journals nothing and
        #: leaves the hot path untouched.
        self.journal = journal
        #: per-request journaled stream length — the extend cursor
        self._journal_len: Dict[str, int] = {}
        self._journal_recovered = 0
        #: last journal counters mirrored into the registry (the
        #: commit refreshes deltas, so shared registries never
        #: double-count)
        self._j_seen = {"appends": 0, "rotations": 0,
                        "compactions": 0, "fsync_s": 0.0}
        if journal is not None and journal.seq == 0:
            # a FRESH journal opens with the engine spec (describe()
            # round-trip) so recovery can refuse an incompatible
            # engine_factory; a recovered journal keeps its meta
            self._jlog("meta", format=journal_mod.FORMAT_VERSION,
                       engine_spec=journal_mod._engine_spec(engine))
        self._gate_state_seen: Optional[float] = None
        #: the ok → degraded → draining → failed state machine; wire
        #: ``MetricsServer(health=sched.health.healthz)`` to serve it
        self.health = HealthMonitor(
            registry=registry,
            recovery_chunks=self.resilience.recovery_chunks,
            on_transition=self._on_health_transition)
        self.queue: Deque[Request] = collections.deque()
        self.active: Dict[int, _Active] = {}
        self.completions: Dict[str, Completion] = {}
        self.events: Deque[StreamEvent] = collections.deque()
        self.ttft_stats = profiler.LatencyStats()
        self.token_latency_stats = profiler.LatencyStats()
        self._free: List[int] = self._reset_free()
        #: chunks dispatched but not yet fetched, oldest first; each
        #: entry is (handle, slot->_Active snapshot at dispatch,
        #: dispatch time, pipeline depth at dispatch incl. this chunk,
        #: tuner operating point at dispatch — None without a tuner)
        self._inflight: Deque[
            Tuple[StepHandle, Dict[int, _Active], float, int,
                  Optional[Dict[str, int]]]] = collections.deque()
        #: recovery bookkeeping per interrupted request (cleared at
        #: completion)
        self._replay: Dict[str, _ReplayState] = {}
        #: prefix-pool hits keyed by request_id — resolved ONCE at
        #: submit (match_prefix is pure host work) and reused at every
        #: (re-)admission, so fault replay rides the same (page, split)
        #: and stays bit-identical
        self._prefix_hits: Dict[str, Tuple[int, int]] = {}
        #: latent mixer: the device's routing totals at the last tick
        self._routing_seen: Dict[str, int] = {}
        self._prefix_hit_count = 0
        self._prefix_miss_count = 0
        #: the in-flight chunked-prefill admission (one at a time —
        #: the engine's scratch holds one prompt): (progress, request).
        #: Each tick advances it ONE chunk forward before the decode
        #: dispatch, so a long prompt's ingestion interleaves with
        #: everyone else's decode waves instead of stalling them.
        #: ``_chunked_fresh`` marks the start tick — chunk 0 was this
        #: tick's one chunk dispatch, so _advance_chunked must not add
        #: a second
        self._chunked: Optional[Tuple[ChunkedAdmission, Request]] = None
        self._chunked_fresh = False
        self._chunked_admissions = 0
        self._chunked_chunks = 0
        self._page_share_hits = 0
        self._pages_exhausted_waits = 0
        #: host-swap oversubscription (EngineConfig.host_swap): paused
        #: conversations by request id (their _Active intact for a
        #: swap-resume) and the FIFO of ids queued for resumption —
        #: drained BEFORE admissions each tick, so a resuming client
        #: mid-stream never waits behind new arrivals. ``preempt``
        #: (default: on whenever the engine has a host tier) lets page
        #: pressure evict the WFQ-furthest-ahead tenant's pages; the
        #: victim replays bit-identically through the fault machinery.
        if preempt and not engine.host_swap_enabled:
            raise ValueError(
                "preempt=True needs EngineConfig.host_swap — without "
                "the emitted-prefix replay contract the host tier "
                "anchors, an evicted stream could not continue")
        self.preempt = (engine.host_swap_enabled if preempt is None
                        else bool(preempt))
        self._parked: Dict[str, _Parked] = {}
        self._resume_q: Deque[str] = collections.deque()
        self._pauses = 0
        self._preemptions = 0
        self._swap_resumes = 0
        self._recompute_resumes = 0
        self._swap_capacity_drops = 0
        self._steps = 0
        self._tokens_emitted = 0
        self._admitted_requests = 0
        self._admit_dispatches = 0
        self._retries = 0
        self._retry_exhausted = 0
        self._rebuilds = 0
        self._shed = 0
        self._watchdog_trips = 0
        self._evicted_requests = 0
        self._consecutive_rebuilds = 0
        #: EWMA of chunk dispatch→fetch wall time — the overload
        #: estimator behind deadline shedding and the QueueFull
        #: retry-after hint
        self._chunk_ewma = 0.0
        #: self-tuning control plane (serving.tuner): a Controller over
        #: the declared knob ladders, switching ONLY among pre-warmed
        #: compiled variants (validated against the engine's resolved
        #: ladders right here, so a bad ladder fails at construction,
        #: not as a mid-serve recompile). When it owns the ``spec_k``
        #: knob it REPLACES the spec gate — one controller per knob.
        tunes_spec = tuner is not None and tuner.spec_k is not None
        self._tuner: Optional[Controller] = None
        if tuner is not None:
            self._tuner = self._build_tuner(tuner, engine)
        #: speculative-decoding payoff gate (None unless the engine
        #: carries a spec_k > 0 base variant and the tuner does not own
        #: the knob): decides per dispatch which pre-warmed chunk
        #: variant to run — see SpecGateConfig
        if engine.engine_cfg.spec_k > 0 and not tunes_spec:
            self._gate: Optional[_SpecGate] = _SpecGate(
                spec_gate or SpecGateConfig(), engine.engine_cfg.spec_k)
        else:
            if spec_gate is not None:
                raise ValueError(
                    "spec_gate given but unusable — speculation needs "
                    "EngineConfig.spec_k > 0, and a tuner that owns "
                    "the spec_k knob replaces the gate (two "
                    "controllers would fight over one variant choice)")
            self._gate = None
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_chunks = 0
        self._alarms_seen = self._guard_alarm_count()
        self._started: Optional[float] = None
        #: SLO observatory (telemetry.slo): streaming quantile sketches
        #: over the four latency surfaces this scheduler already
        #: timestamps (ttft / token_latency / queue_wait / e2e, global
        #: + per-tenant), plus one burn-rate machine per declared
        #: objective. The monitor shares the scheduler clock and
        #: recorder, so its evaluation inputs and every state
        #: transition land in bundles and replay bit-identically
        #: (telemetry.replay.replay_slo). None = no sketches, summary()
        #: unchanged.
        self._slo_cfg = slo
        self.slo: Optional[SLOMonitor] = None
        if slo is not None:
            self.slo = SLOMonitor(slo, clock=self.clock,
                                  recorder=recorder,
                                  on_state=self._on_slo_state)
            if self.telemetry is not None:
                self.telemetry.bind_slo(
                    SLO_METRICS, [o.key() for o in slo.objectives])
        # steady-decode split: wall time attributable to decode chunks
        # (dispatch-to-fetch, overlap-deduplicated so pipelined chunks
        # never double-count an interval) and the tokens they emitted —
        # TTFT (admission/prefill) excluded, so summary() can report
        # the two regimes separately
        self._decode_time = 0.0
        self._decode_tokens = 0
        self._decode_mark = float("-inf")

    # -- intake ------------------------------------------------------------

    def submit(self, request: Request, *,
               replay_prefix: Optional[Sequence[int]] = None,
               replay_logprobs: Optional[Sequence[float]] = None) -> None:
        """Enqueue ``request``; raises :class:`QueueFull` at capacity
        (with queue depth + a retry-after hint attached) and
        :class:`~apex_tpu.serving.resilience.EngineFailed` once the
        health machine is terminal. Prompt-validity errors raise
        immediately; a prompt that already ends in the request's eos
        token completes here with zero generated tokens.

        ``replay_prefix`` (router-facing) primes the grow-only
        emitted-prefix snapshot with tokens the client ALREADY saw on
        another replica before a failover: generation re-derives them
        from the prompt and suppresses the duplicate events, exactly
        like local fault replay, so the continued stream is
        bit-identical."""
        # a section of its own beside the tick's: what the host does
        # between two ticks is its callers' time and this
        with self._phase("sched.submit"):
            self._submit(request, replay_prefix, replay_logprobs)

    def _submit(self, request: Request,
                replay_prefix: Optional[Sequence[int]],
                replay_logprobs: Optional[Sequence[float]]) -> None:
        """:meth:`submit`'s body."""
        if self.health.state == HEALTH_FAILED:
            raise EngineFailed(
                f"engine health is failed ({self.health.last_cause}); "
                f"not accepting requests")
        if request.request_id in self.completions or any(
                a.request.request_id == request.request_id
                for a in self.active.values()) or any(
                r.request_id == request.request_id for r in self.queue) \
                or request.request_id in self._parked:
            raise ValueError(f"duplicate request_id {request.request_id!r}")
        request.sampling.validate()
        prompt = list(request.prompt)
        ecfg = self.engine.engine_cfg
        # the slot must fit prompt + at least one generated token
        limit = min(ecfg.max_prompt_len, ecfg.max_seq_len - 1)
        if not 1 <= len(prompt) <= limit:
            raise ValueError(
                f"prompt length {len(prompt)} outside [1, {limit}]")
        room = ecfg.max_seq_len - len(prompt)
        if not 1 <= request.max_tokens <= room:
            raise ValueError(
                f"max_tokens {request.max_tokens} outside [1, {room}] "
                f"for a {len(prompt)}-token prompt at max_seq_len "
                f"{ecfg.max_seq_len} — a clamped budget would silently "
                f"break solo-generate parity")
        eos = request.eos_token_id
        if eos is not None and not 0 <= eos < self.engine.cfg.vocab_size:
            raise ValueError(
                f"eos_token_id {eos} outside vocab "
                f"[0, {self.engine.cfg.vocab_size})")
        if request.stop:
            for s in request.stop:
                if not len(s):
                    raise ValueError(
                        "stop sequences must be non-empty token lists")
        if request.constraint is not None \
                and ecfg.decode_chunk != 1:
            raise ValueError(
                f"schema-constrained requests need decode_chunk == 1 "
                f"(the vocab mask advances host-side between "
                f"dispatches; a {ecfg.decode_chunk}-token chunk would "
                f"apply a stale mask), got decode_chunk="
                f"{ecfg.decode_chunk}")
        if not request.tenant:
            request.tenant = DEFAULT_TENANT
        if request.adapter:
            # validated HERE, not at admission: a bad adapter id that
            # only surfaced mid-serve would be quarantined as a fault
            if not self.engine.adapter_pool_enabled:
                raise ValueError(
                    f"request carries adapter {request.adapter} but "
                    f"the engine's adapter pool is disabled "
                    f"(EngineConfig.adapter_slots == 0)")
            n_reg = self.engine.adapters_registered
            if not 1 <= request.adapter <= n_reg:
                raise ValueError(
                    f"adapter {request.adapter} outside the "
                    f"registered ids [1, {n_reg}] (0 is the pinned "
                    f"base adapter; Engine.register_adapter issues "
                    f"the rest)")
        now = self.clock()
        request.arrival_time = now
        self._dump_token += 1
        rec = self.recorder
        book = self.tenants
        # bounded tenant cardinality: unauthenticated per-request
        # identities fold into the overflow tenant past max_tenants
        # (the request is REWRITTEN so every downstream consumer —
        # WFQ, buckets, metrics, bundle records — sees one identity)
        tenant = request.tenant = book.admit_tenant(request.tenant)
        if (request.eos_token_id is not None
                and prompt[-1] == request.eos_token_id):
            book.stats(tenant).submitted += 1
            if self.telemetry is not None:
                self.telemetry.submitted.inc()
            self._record_request(request, now)
            if rec is not None:
                rec.record("submit_terminal", request.request_id)
            self._complete(request, [], FINISH_EOS, ttft=None, now=now)
            return
        plan = self.engine.fault_plan
        spec = plan.take("submit") if plan is not None else None
        flooded = spec is not None and spec.kind == KIND_FLOOD
        if flooded or len(self.queue) >= self.max_queue:
            depth = self.max_queue if flooded else len(self.queue)
            hint = depth * self._chunk_ewma
            self._shed += 1
            if rec is not None:
                rec.record("queue_full", request.request_id, depth,
                           flooded)
            self.health.record_fault("queue_full")
            self._maybe_dump("queue_full")
            book.stats(tenant).shed += 1
            if self.telemetry is not None:
                self.telemetry.shed["queue_full"].inc()
                self.telemetry.tenant(tenant)["shed"][
                    "queue_full"].inc()
            raise QueueFull(
                f"queue at capacity ({depth}"
                f"{', injected flood' if flooded else ''}); retry in "
                f"~{hint:.3f}s", queue_depth=depth, retry_after_s=hint)
        # per-tenant token-budget rate limit — checked AFTER the
        # queue-capacity gate so a QueueFull rejection never debits
        # the bucket (the request served nothing; charging it would
        # starve a well-behaved tenant through repeated flood
        # rejections), and SKIPPED for failover hand-offs
        # (replay_prefix: the original submit already charged this
        # request's budget — a second charge on re-placement would
        # double-bill the tenant and could crash the router loop with
        # an un-routable throttle). Other tenants' streams are
        # untouched either way (the zero-drift contract); the
        # rejection carries the bucket refill time as Retry-After.
        if replay_prefix is None:
            wait = book.throttle(tenant, request.max_tokens, now)
            if wait is not None:
                self._throttled += 1
                book.stats(tenant).throttled += 1
                book.stats(tenant).shed += 1
                if rec is not None:
                    rec.record("tenant_throttle", request.request_id,
                               tenant, wait)
                if self.telemetry is not None:
                    self.telemetry.shed["tenant_rate"].inc()
                    self.telemetry.tenant(tenant)["shed"][
                        "tenant_rate"].inc()
                raise TenantThrottled(
                    f"tenant {tenant!r} over its token budget; retry "
                    f"in ~{wait:.3f}s", tenant=tenant,
                    retry_after_s=wait)
        if self.engine.prefix_pool_enabled and not request.adapter:
            # adapter-carrying requests never match the prefix pool:
            # pooled prefixes hold BASE-weight K/V, and a hit would
            # decode against cache bytes a cold adapter prefill would
            # not produce (the engine rejects the combination too)
            hit = self.engine.match_prefix(prompt)
            if hit is not None:
                self._prefix_hits[request.request_id] = hit
                self._prefix_hit_count += 1
            else:
                self._prefix_miss_count += 1
            if self.telemetry is not None:
                (self.telemetry.prefix_hits if hit is not None
                 else self.telemetry.prefix_misses).inc()
        if self.engine.paged:
            # a request that could NEVER fit the pool (even with every
            # other slot free) would wait at the queue head forever —
            # reject loudly at submit instead; transient exhaustion is
            # the normal backpressure path. The need is the PRIVATE
            # footprint — a prefix hit's shared pages are pinned, not
            # allocated (checked AFTER match_prefix so a CoW-discounted
            # request that fits is never falsely rejected)
            needed = self._request_pages_needed(request)
            if needed > self.engine.page_allocator.capacity:
                raise ValueError(
                    f"request needs {needed} pages but the pool only "
                    f"has {self.engine.page_allocator.capacity} — "
                    f"raise EngineConfig.num_pages or shrink the "
                    f"request")
        self._record_request(request, now)
        self._journal_submit(request, now)
        if replay_prefix:
            # failover hand-off: everything another replica streamed
            # becomes this scheduler's last-known-good snapshot — the
            # same grow-only record a local fault replay maintains
            st = self._replay.setdefault(request.request_id,
                                         _ReplayState())
            if len(replay_prefix) > len(st.tokens):
                st.tokens = [int(t) for t in replay_prefix]
                st.logprobs = list(replay_logprobs or [])
            # journaled AND committed immediately (not buffered until
            # the next fetch boundary): the hand-off prefix is the
            # client's already-seen stream — a crash before the first
            # chunk must not forget it, so it gets durability to the
            # fsync policy's level right here (batch/always fsync,
            # none flushes to the page cache)
            self._journal_extend(request.request_id, st.tokens,
                                 st.logprobs)
            if self.journal is not None:
                self.journal.commit()
        # a tenant (re-)entering the backlog competes from "now": its
        # deficit counter clamps up to the minimum among the tenants
        # currently holding queued/active work — idle time is not
        # banked credit (the backlog set is computed BEFORE this
        # request joins it; submit already walks the queue for the
        # duplicate-id check, so this adds no new asymptotics)
        backlogged = {a.request.tenant for a in self.active.values()}
        backlogged.update(r.tenant for r in self.queue)
        if tenant not in backlogged:
            book.rejoin(tenant, min(
                (book.service_of(t) for t in backlogged),
                default=book.service_of(tenant)))
        self.queue.append(request)
        book.stats(tenant).submitted += 1
        book.note_backlogged(tenant)
        if rec is not None:
            rec.record("submit", request.request_id, len(prompt),
                       request.max_tokens, len(self.queue))
        if self.telemetry is not None:
            self.telemetry.submitted.inc()
            self.telemetry.queue_depth.set(len(self.queue))
        if self.spans is not None:
            self.spans.mark(request.request_id, spans_mod.PHASE_QUEUED)

    # -- the loop ----------------------------------------------------------

    def _phase(self, name: str):
        """A phase of the tick: a span section (and profiler
        annotation) when a recorder is attached, nothing otherwise."""
        sp = self.spans
        return _NO_PHASE if sp is None else sp.section(name)

    def _timed(self, name: str):
        """A block the scheduler times for its own accounting
        (``.start`` / ``.end`` on its clock); with a recorder the same
        two clock reads are the section ``name``."""
        sp = self.spans
        return (spans_mod.Stopwatch(self.clock) if sp is None
                else sp.section(name))

    def _count_prefill(self, real: int, padded: int, rows: int,
                       dispatches: int) -> None:
        """Admission counts into the recorder: prompt tokens given,
        token rows the programs ran at, requests, device programs."""
        count = self.spans.count
        count("prefill.tokens_real", real)
        count("prefill.tokens_padded", padded)
        count("prefill.rows", rows)
        count("prefill.dispatches", dispatches)

    def step(self) -> None:
        """One scheduler tick: expire/shed deadlines, batch-admit
        queued requests into free slots, dispatch the next decode chunk
        if any slot is live, then fetch + unpack chunks down to the
        pipeline depth (ALL of them when nothing was dispatched — the
        drain path, so a tick always makes progress). At depth 1 this
        is the serial loop: dispatch, fetch, unpack. Deadlines and
        admissions are checked between chunks — the ``decode_chunk``
        admission-latency/throughput tradeoff, now also the
        pipeline-depth one. A fault detected anywhere in the tick
        triggers quarantine + rebuild + replay instead of escaping
        (see module docstring); once the health machine is terminal
        the tick is a no-op."""
        self._dump_token += 1
        if self.health.state == HEALTH_FAILED:
            return
        if self.spans is not None:
            self.spans.anchor()
        with self._phase("sched.step"):
            self._tick()

    def _tick(self) -> None:
        """:meth:`step`'s body, in its five phases."""
        now = self.clock()
        if self._started is None:
            self._started = now
        with self._phase("sched.housekeeping"):
            self._poll_guard_alarms()
            self._sync_tuner()
            self._sync_slo(now)
            self._expire(now)
        # admissions FIRST, then one chunk of any in-progress chunked
        # prefill, then the decode dispatch: a short prompt's
        # admission never queues behind this tick's chunk forward, so
        # the long admission inflates nobody's TTFT — the interleave
        # that keeps a 32k-token admission from stalling every other
        # stream
        with self._phase("sched.admit"):
            self._admit_queued(now)
            self._advance_chunked(now)
        with self._phase("sched.dispatch"):
            dispatched = bool(self.active) and self._dispatch_chunk()
        keep = self.pipeline_depth - 1 if dispatched else 0
        with self._phase("sched.collect"):
            while len(self._inflight) > keep:
                self._collect_oldest()
        self._steps += 1
        with self._phase("sched.publish"):
            if self.spans is not None:
                self._count_routing()
            self._publish()

    def _publish(self) -> None:
        """The tick's gauges and its metrics line."""
        if self.telemetry is not None:
            self.telemetry.steps.inc()
            self.telemetry.queue_depth.set(len(self.queue))
            self.telemetry.active_slots.set(len(self.active))
            if len(self.tenants._stats) > 1:
                # per-tenant depth gauges only once a SECOND tenant
                # exists — the universal single-tenant case must not
                # pay an extra O(queue) walk per tick
                depth: Dict[str, int] = {}
                for r in self.queue:
                    depth[r.tenant] = depth.get(r.tenant, 0) + 1
                for t in self.tenants._stats:
                    self.telemetry.tenant(t)["queue"].set(
                        depth.get(t, 0))
            if self.engine.paged:
                ps = self.engine.page_stats()
                self.telemetry.pages_in_use.set(ps["pages_in_use"])
                self.telemetry.pages_free.set(ps["pages_free"])
                self.telemetry.pages_shared.set(ps["pages_shared"])
                self.telemetry.page_fragmentation.set(
                    ps["fragmentation"])
                self.telemetry.pages_swapped.set(ps["pages_swapped"])
                self.telemetry.swap_bytes.set(ps["swap_bytes"])
        if self.metrics is not None:
            elapsed = max(self.clock() - self._started, 1e-9)
            self.metrics.log(self._steps, {
                "queue_depth": len(self.queue),
                "slot_occupancy": len(self.active) / self.engine.slots,
                "tokens_emitted": self._tokens_emitted,
                "tokens_per_sec": self._tokens_emitted / elapsed,
            })

    def drain(self) -> None:
        """Fetch + unpack every in-flight chunk (pipeline drain): after
        this, ``events``/``completions`` reflect all dispatched work.
        The health machine reads ``draining`` for the duration (a live
        ``/healthz`` probe answers 503 — stop routing traffic here),
        then returns to its prior state."""
        if self._tuner is not None:
            # drained chunks are shutdown traffic, not steady state —
            # the controller must neither measure nor steer on them
            # (it thaws at the next live tick's _sync_tuner)
            self._tuner.freeze("drain")
        self.health.begin_drain()
        try:
            while self._inflight:
                self._collect_oldest()
        finally:
            self.health.end_drain()

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        """Step until queue, slots, and the pipeline are empty (offline
        batch mode). When every queued request is gated on retry
        backoff and nothing is in flight, waits out the earliest gate
        via ``sleep`` instead of spinning."""
        steps = 0
        while (self.queue or self.active or self._inflight
               or self._chunked is not None or self._resume_q):
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"not idle after {max_steps} steps — live slots "
                    f"{sorted(self.active)}, queue {len(self.queue)}, "
                    f"{len(self._inflight)} chunks in flight")
            wait = self._backoff_wait_s()
            if wait is not None:
                self.sleep(wait)

    def pop_events(self) -> List[StreamEvent]:
        """Drain the response stream."""
        out = list(self.events)
        self.events.clear()
        return out

    def idle(self) -> bool:
        """True when there is nothing to do — queue, slots, pipeline,
        any chunked admission, and the resume queue are all empty (the
        API driver thread sleeps instead of spinning ticks). Parked
        conversations do NOT count: they wait for an explicit
        :meth:`resume`."""
        return not (self.queue or self.active or self._inflight
                    or self._chunked is not None or self._resume_q)

    def overload_hint_s(self) -> float:
        """The queue-drain estimate behind :class:`QueueFull`'s
        ``retry_after_s`` (depth × measured chunk latency), exposed so
        an ingress layer can pre-flight an all-or-nothing batch (an
        ``n>1`` fan must not half-land) with the same hint a rejection
        would carry."""
        return len(self.queue) * self._chunk_ewma

    def can_accept(self, n: int = 1) -> bool:
        """Whether ``n`` more submissions fit the queue right now —
        the all-or-nothing pre-flight the API front end (and the fleet
        router, which aggregates it across replicas) checks before
        fanning a batch that must not half-land. Capacity only:
        terminal health surfaces as :class:`EngineFailed` from
        :meth:`submit` (a 503, not a 429)."""
        return len(self.queue) + n <= self.max_queue

    def register_adapter(self, weights=None, *,
                         name: Optional[str] = None,
                         seed: Optional[int] = None) -> int:
        """Register a LoRA adapter into the engine's pool
        (:meth:`Engine.register_adapter`) and log the
        ``adapter_register`` flight-recorder event — the scheduler is
        the recorder's owner, so registration evidence lands in
        post-mortem bundles next to the admissions that used it."""
        aid = self.engine.register_adapter(weights, name=name,
                                           seed=seed)
        meta = self.engine._adapter_meta.get(aid, {})
        if self.recorder is not None:
            self.recorder.record("adapter_register",
                                 meta.get("name"), aid,
                                 meta.get("seed"))
        # journaled with its derivation seed: recovery re-registers by
        # name (idempotent) and re-derives the exact weights; an
        # explicit-weights registration journals seed=None and its
        # requests are skipped at recovery (counted, never guessed)
        self._jlog("adapter", name=meta.get("name"),
                   seed=meta.get("seed"), rank=meta.get("rank"),
                   adapter_id=aid)
        return aid

    def register_prefix(self, tokens) -> int:
        """Register a shared prompt-prefix template into the engine's
        pool (:meth:`Engine.register_prefix`) and journal the token
        list, so a crash-recovered scheduler repopulates the pool and
        replayed admissions ride the same (page, split) hits."""
        page = self.engine.register_prefix(tokens)
        self._jlog("prefix", tokens=[int(t) for t in tokens])
        return page

    def tenant_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant accounting: weight, submitted/admitted/shed/
        throttled counts, served tokens, and the live WFQ deficit
        counter (:meth:`apex_tpu.serving.tenancy.TenantBook.summary`)."""
        return self.tenants.summary()

    # -- host-swap oversubscription (EngineConfig.host_swap) ----------------

    def pause(self, request_id: str) -> bool:
        """Park an ACTIVE request's conversation in the host-RAM swap
        tier (:meth:`Engine.park_slot`): its private HBM pages swap
        out, the slot frees for other traffic, and the stream
        continues bit-identically after :meth:`resume` — held stop-
        matcher tokens, PRNG state, everything. Never mid-chunk: every
        in-flight chunk is collected first (the dispatched tables
        still map the pages being freed). Returns False when the
        request is not active by then — it finished in a collected
        chunk, is still queued, or was already parked."""
        if not self.engine.host_swap_enabled:
            raise ValueError(
                "pause() needs EngineConfig.host_swap — the engine "
                "has no host tier to park into")
        while self._inflight:
            self._collect_oldest()
        for slot, act in sorted(self.active.items()):
            if act.request.request_id == request_id:
                self._park(slot, act, self.clock())
                return True
        return False

    def resume(self, request_id: str) -> bool:
        """Queue a parked conversation for resumption — drained BEFORE
        admissions each tick, and attempted immediately here when a
        slot is free. ``EngineConfig.resume_policy`` prices the path
        per conversation: ``swap`` scatters the parked payload back
        and the SAME stream object continues; ``recompute`` drops the
        payload and re-derives the emitted prefix through fault
        replay; ``auto`` compares the measured swap-in EWMA against
        replay's (emitted tokens × chunk-latency EWMA) and takes the
        cheaper one. Returns False for an id that is not parked."""
        if request_id not in self._parked:
            return False
        if request_id not in self._resume_q:
            self._resume_q.append(request_id)
        self._admit_parked(self.clock())
        return True

    @property
    def parked_requests(self) -> List[str]:
        """Ids of paused conversations, oldest park first."""
        return sorted(self._parked,
                      key=lambda rid: self._parked[rid].parked_at)

    def _park(self, slot: int, act: _Active, now: float) -> None:
        """Move one active slot into the host tier: grow the replay
        snapshot FIRST (the recompute fallback — and the bundle's
        record of what the client saw), swap the pages out, free the
        slot. An engine-seam failure recovers like any other fault
        (the conversation replays from the snapshot just taken)."""
        rid = act.request.request_id
        st = self._replay.setdefault(rid, _ReplayState())
        if len(act.tokens) > len(st.tokens):
            st.tokens = list(act.tokens)
            st.logprobs = list(act.logprobs)
        n_pages = self.engine.slot_page_count(slot)
        try:
            evicted = self.engine.park_slot(slot, rid)
        except Exception as e:  # park rides the retire seam
            self._recover(now, cause="retire", detail=str(e),
                          affected=[])
            return
        self.active.pop(slot)
        self._free.append(slot)
        self._pauses += 1
        self._parked[rid] = _Parked(act, n_pages,
                                    self.engine.host_parked(rid), now)
        for ek in evicted:
            # capacity eviction only drops swap payloads — those
            # conversations (possibly including this one) downgrade
            # to recompute-resume; nothing is lost
            pk = self._parked.get(ek)
            if pk is not None and pk.swap:
                pk.swap = False
                self._swap_capacity_drops += 1
        # the snapshot just grown is the recompute-resume contract —
        # journal it now plus the park marker, so a crash while parked
        # recovers the conversation instead of forgetting it
        self._journal_extend(rid, st.tokens, st.logprobs)
        self._jlog("park", request_id=rid)
        if self.recorder is not None:
            self.recorder.record("page_swap_out", rid, slot, n_pages,
                                 self.engine.parked_bytes(rid))
        if self.spans is not None:
            self.spans.mark(rid, spans_mod.PHASE_QUEUED,
                            note=f"parked ({n_pages} pages)")
        if self.telemetry is not None:
            self.telemetry.active_slots.set(len(self.active))

    def _admit_parked(self, now: float) -> None:
        """Drain the resume queue into free slots. A swap-resume that
        cannot get a slot or pages waits at the queue head — the same
        backpressure admission sees (page pressure may preempt on its
        behalf); a recompute-resume re-enters the request queue's
        FRONT and replays through the fault machinery."""
        while self._resume_q:
            rid = self._resume_q[0]
            pk = self._parked.get(rid)
            if pk is None:      # expired/aborted while queued
                self._resume_q.popleft()
                continue
            act = pk.act
            n_pages = self.engine.parked_pages(rid)
            policy = self.engine.engine_cfg.resume_policy
            use_swap = (pk.swap and self.engine.host_parked(rid)
                        and policy != "recompute")
            if use_swap and policy == "auto":
                cost = self.engine.swap_in_cost_s(n_pages)
                if (cost is not None and self._chunk_ewma > 0.0
                        and cost > len(act.tokens) * self._chunk_ewma):
                    use_swap = False
            if not use_swap:
                # recompute: drop the payload (snapshot was grown at
                # park) and replay from the request queue's front —
                # the resuming client jumps new arrivals
                self._resume_q.popleft()
                self._parked.pop(rid)
                self.engine.drop_parked(rid)
                self._recompute_resumes += 1
                self.queue.appendleft(act.request)
                self._jlog("resume", request_id=rid, path="recompute")
                if self.recorder is not None:
                    self.recorder.record("page_swap_in", rid, -1,
                                         n_pages, "recompute")
                continue
            if not self._free:
                return
            if not self.engine.page_allocator.can_alloc(n_pages):
                self._note_pages_exhausted(act.request, n_pages)
                return
            slot = self._free.pop()
            try:
                self.engine.resume_slot(slot, rid)
            except PagesExhausted as e:
                self._free.append(slot)
                self._note_pages_exhausted(act.request, e.requested)
                return
            except KeyError:
                # capacity-evicted between the check and the take —
                # the next spin takes the recompute branch
                self._free.append(slot)
                pk.swap = False
                continue
            except Exception as e:
                # the scatter donates cache/state: the payload is
                # consumed and the engine poisoned — recover, and
                # replay this conversation from its snapshot alongside
                # every interrupted slot
                self._free.append(slot)
                self._resume_q.popleft()
                self._parked.pop(rid, None)
                self._recover(now, cause="admit", detail=str(e),
                              affected=[], batch_reqs=[act.request])
                return
            self._resume_q.popleft()
            self._parked.pop(rid)
            self.active[slot] = act
            self._swap_resumes += 1
            self._jlog("resume", request_id=rid, path="swap")
            if self.recorder is not None:
                self.recorder.record("page_swap_in", rid, slot,
                                     n_pages, "swap")
            if self.spans is not None:
                self.spans.mark(rid, spans_mod.PHASE_DECODE,
                                note=f"swap-resume slot {slot}")
            if self.telemetry is not None:
                self.telemetry.active_slots.set(len(self.active))

    def _maybe_preempt(self, r: Request, needed: int) -> None:
        """Page pressure meets oversubscription: free the pages of the
        tenant furthest AHEAD of its WFQ fair share
        (:meth:`~apex_tpu.serving.tenancy.TenantBook.pick_victim`) so
        the starved request admits next tick. Never mid-chunk — every
        in-flight chunk collects first — and never the starved
        request's own lane. The victim replays through the fault
        machinery (snapshot grown here, re-queued at the BACK — it
        yielded its turn); attempts are NOT charged: preemption is a
        scheduling decision, not a fault. Its continued stream is
        bit-identical."""
        if not self.preempt or not self.active:
            return
        while self._inflight:
            self._collect_oldest()
        # collection may have released slots/pages (or recovered a
        # fault) — re-check the pressure before evicting anyone
        if (not self.active
                or self.engine.page_allocator.can_alloc(needed)):
            return
        # only tenants strictly AHEAD of the starved one are fair
        # game: preemption flows one way down the WFQ ordering, so a
        # fresh victim can never preempt its preemptor right back
        # (equal-service tenants fall through to plain backpressure)
        floor = self.tenants.service_of(r.tenant)
        candidates = {
            a.request.tenant: self.tenants.service_of(a.request.tenant)
            for a in self.active.values()
            if self.tenants.service_of(a.request.tenant) > floor}
        if not candidates:
            return
        victim_tenant = self.tenants.pick_victim(candidates)
        victims = sorted(
            (len(a.tokens), slot)
            for slot, a in self.active.items()
            if a.request.tenant == victim_tenant
            and a.request.request_id != r.request_id)
        if not victims:
            return
        _, slot = victims[0]    # least sunk work first
        act = self.active[slot]
        vid = act.request.request_id
        n_pages = self.engine.slot_page_count(slot)
        st = self._replay.setdefault(vid, _ReplayState())
        if len(act.tokens) > len(st.tokens):
            st.tokens = list(act.tokens)
            st.logprobs = list(act.logprobs)
        if self.recorder is not None:
            self.recorder.record(
                "preempt", vid, slot, victim_tenant, n_pages,
                candidates[victim_tenant], dict(sorted(candidates.items())))
        try:
            self.engine.retire(slot)
        except Exception as e:
            self._recover(self.clock(), cause="retire", detail=str(e),
                          affected=[])
            return
        self.engine.free_slot(slot)
        self.active.pop(slot)
        self._free.append(slot)
        self._preemptions += 1
        self.queue.append(act.request)
        if self.spans is not None:
            self.spans.mark(vid, spans_mod.PHASE_QUEUED,
                            note="preempted")
        if self.telemetry is not None:
            self.telemetry.preemptions.inc()
            self.telemetry.queue_depth.set(len(self.queue))
            self.telemetry.active_slots.set(len(self.active))

    @property
    def chunk_latency_ewma_s(self) -> float:
        """The measured decode-chunk latency EWMA (seconds; 0.0 before
        any chunk landed) — the overload estimator behind deadline
        shedding and retry-after hints, exposed so a fleet router can
        weight replicas by how fast they actually serve."""
        return self._chunk_ewma

    def predicted_ttft_s(self) -> float:
        """What a request submitted NOW would likely see as TTFT on
        this replica: the queue-drain estimate (depth × measured chunk
        latency — :meth:`overload_hint_s`) plus the measured admission
        component — the median gap between this scheduler's observed
        TTFT and queue-wait distributions (sketch-backed; 0 before SLO
        sketches have samples). The fleet router's routing-signal
        precursor: rank replicas by the latency a tenant would
        experience, not just by queue depth."""
        base = len(self.queue) * self._chunk_ewma
        if self.slo is None:
            return base
        ttft_p50 = self.slo.quantile("ttft", 0.5)
        wait_p50 = self.slo.quantile("queue_wait", 0.5)
        if ttft_p50 is None or wait_p50 is None:
            return base
        return base + max(ttft_p50 - wait_p50, 0.0)

    # -- internals ---------------------------------------------------------

    def _build_tuner(self, cfg: TunerConfig, engine: Engine) -> Controller:
        """Validate the declared ladders against the engine's WARMED
        variant ladders and build the controller. Device-shaping knobs
        may only name compiled variants (the serving.tuner pre-warm
        contract — WARMUP-COVERAGE pins the engine half statically);
        host knobs are checked for shape only."""
        if cfg.decode_chunk is not None:
            bad = [c for c in cfg.decode_chunk
                   if c not in engine.decode_chunks]
            if bad:
                raise ValueError(
                    f"tuner decode_chunk candidates {bad} are not "
                    f"pre-warmed step variants "
                    f"{engine.decode_chunks} — declare them in "
                    f"EngineConfig.decode_chunks so warmup() compiles "
                    f"them (switching to an unwarmed variant would "
                    f"recompile mid-serve)")
        if cfg.spec_k is not None:
            bad = [k for k in cfg.spec_k
                   if k != 0 and k not in engine.spec_ks]
            if bad:
                raise ValueError(
                    f"tuner spec_k candidates {bad} are not pre-warmed "
                    f"spec variants {engine.spec_ks} — declare them in "
                    f"EngineConfig.spec_ks")
        base = {
            "decode_chunk": engine.engine_cfg.decode_chunk,
            "pipeline_depth": self.pipeline_depth,
            # 0 is the ladder spelling of "unlimited" (None)
            "max_admit_batch": self.max_admit_batch or 0,
            "spec_k": engine.engine_cfg.spec_k,
        }
        tele = self.telemetry
        ctl = Controller(
            cfg, base, recorder=self.recorder,
            on_switch=(None if tele is None
                       else lambda knob: tele.tuner_switches[knob].inc()))
        if tele is not None:
            tele.bind_tuner(ctl.knobs)
        return ctl

    def _tuner_freeze_cause(self) -> Optional[str]:
        """The hard-freeze condition, re-evaluated each tick: the
        controller must not steer (or measure) while constrained
        decoding serializes the loop, while any slot is re-deriving a
        pre-fault stream (:meth:`_exclusion_cause` — THE shared
        spelling), or while the health machine drains."""
        if self.health.state == HEALTH_DRAINING:
            return "drain"
        return self._exclusion_cause()

    def _sync_tuner(self) -> None:
        """Tick-start controller sync: freeze/thaw from the live
        exclusion conditions, then apply the current operating point's
        HOST knobs (pipeline depth, admission cap) so this tick's
        admissions and drain target already run the point the next
        dispatch will use."""
        tn = self._tuner
        if tn is None:
            return
        cause = self._tuner_freeze_cause()
        if cause is not None:
            tn.freeze(cause)
        else:
            tn.thaw()
        point = tn.current_point()
        if "pipeline_depth" in point:
            self.pipeline_depth = point["pipeline_depth"]
        if "max_admit_batch" in point:
            self.max_admit_batch = point["max_admit_batch"] or None
        if self.telemetry is not None:
            self.telemetry.tuner_state.set(tn.state())
            for k, v in tn.incumbent.items():
                self.telemetry.tuner_knob[k].set(v)

    def _on_slo_state(self, obj: SLOObjective, old: str,
                      new: str) -> None:
        """Burn-machine transition hook: count page-worthy alerts into
        the registry (the transition + alert EVENTS are the monitor's
        own recorder job)."""
        if self.telemetry is None:
            return
        ch = self.telemetry.slo_children.get(obj.key())
        if ch is not None and new in ch["alerts"]:
            ch["alerts"][new].inc()

    def _sync_slo(self, now: float) -> None:
        """Tick-cadence SLO work: run any due burn-machine evaluation,
        and refresh the quantile/burn/state/budget gauges whenever one
        ran (gauge refresh is eval-cadence, never per-token)."""
        mon = self.slo
        if mon is None:
            return
        if not mon.tick(now) or self.telemetry is None:
            return
        for metric in SLO_METRICS:
            sk = mon.sketch(metric)
            if sk is None or not sk.count:
                continue
            for q, g in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
                self.telemetry.slo_quantile[(metric, g)].set(
                    sk.quantile(q))
        for key, m in mon.machines.items():
            ch = self.telemetry.slo_children[key]
            ch["fast"].set(m.fast_burn)
            ch["slow"].set(m.slow_burn)
            ch["state"].set(SLO_STATE_CODE[m.state])
            ch["budget"].set(m.budget_remaining())

    def _guard_alarm_count(self) -> float:
        """Current value of the engine sentinel's recompile-alarm
        counter (0.0 when no registry-wired sentinel exists) — polled
        each tick so guard alarms degrade health automatically."""
        sent = getattr(self.engine, "_sentinel", None)
        return sent.alarms_total() if sent is not None else 0.0

    def _poll_guard_alarms(self) -> None:
        v = self._guard_alarm_count()
        if v > self._alarms_seen:
            self._alarms_seen = v
            if self.recorder is not None:
                self.recorder.record("guard_alarm", v)
            self.health.record_fault("recompile_alarm")
            self._maybe_dump("guard_alarm")

    def _backoff_wait_s(self) -> Optional[float]:
        """Seconds until the earliest retry-backoff gate opens, when
        that is the ONLY remaining work (else None)."""
        if self.active or self._inflight or self._chunked is not None \
                or not self.queue:
            return None
        now = self.clock()
        waits = []
        for r in self.queue:
            st = self._replay.get(r.request_id)
            if st is None or st.not_before <= now:
                return None  # something is admissible right now
            waits.append(st.not_before - now)
        return min(waits) + 1e-4

    def _dispatchable(self) -> bool:
        """Whether dispatching another chunk can produce ANY real
        token: some active slot must have token budget left beyond the
        columns already in flight for it. Without this guard a deep
        pipeline burns a guaranteed-all-pad chunk at every wave of
        finishes (the host only learns a slot died when it fetches the
        chunk that killed it). Early-eos finishes stay speculative —
        the host cannot predict them, so a chunk may still carry some
        pad lanes, exactly like a mid-chunk finish under
        ``decode_chunk`` — but a chunk that CANNOT pay for itself is
        never dispatched."""
        if not self.active:
            return False
        if self._inflight and any(
                a.request.constraint is not None
                for a in self.active.values()):
            # a constrained slot's vocab mask only advances once the
            # previous chunk's tokens are fetched — dispatching on top
            # of an in-flight chunk would decode against a stale mask,
            # so constrained traffic serializes the pipeline (depth
            # effectively 1 while any constrained request is active)
            return False
        if not self._inflight:
            return True
        cols = self._inflight_cols()
        return any(
            len(act.tokens) + cols.get(slot, 0) < act.request.max_tokens
            for slot, act in self.active.items())

    def _inflight_cols(self) -> Dict[int, int]:
        """Columns already in flight for each active slot, every
        in-flight chunk priced at its max emission — decode_chunk for
        plain chunks, decode_chunk*(spec_k+1) for speculative ones
        (conservative: a spec chunk may emit fewer, in which case the
        next tick's fetch corrects the estimate)."""
        cols: Dict[int, int] = {}
        for handle, snapshot, _, _, _ in self._inflight:
            for slot, act in snapshot.items():
                if self.active.get(slot) is act:
                    cols[slot] = cols.get(slot, 0) + handle.ncols
        return cols

    def _count_decode_chunks(self) -> None:
        """Decode-read counts into the recorder, at a dispatch: the
        chunks of the horizon that the live slots' fills need at the
        chunk's first step (``pos // read_chunk + 1`` each, ``pos`` the
        host's view: prompt + tokens emitted or in flight - 1), and the
        chunks the read kernel's grid holds (slots x chunks of the
        horizon). Their ratio is the share of the grid that fetches
        anything."""
        eng = self.engine
        bk = eng.read_chunk
        last = eng.engine_cfg.max_seq_len - 1
        cols = self._inflight_cols()
        count = self.spans.count
        count("decode.chunks_needed", sum(
            min(len(act.request.prompt) + len(act.tokens)
                + cols.get(slot, 0) - 1, last) // bk + 1
            for slot, act in self.active.items()))
        count("decode.chunks_grid",
              eng.engine_cfg.slots * (last // bk + 1))

    def _count_decode_rows(self, ncols: int) -> None:
        """Decode-kernel row counts into the recorder, at the dispatch
        of a chunk of ``ncols`` columns, before it joins the in-flight
        ones: the row steps in which the active slots are live, as the
        host sees it (``min(remaining budget, ncols)`` each, the budget
        being max tokens less the tokens emitted or in flight; an early
        eos makes it an upper bound), and the row steps of a grid over
        every slot (slots x ``ncols``). Their ratio is the share of the
        slots' row steps that the decode kernels' grids, which run over
        the live rows alone, still walk."""
        cols = self._inflight_cols()
        count = self.spans.count
        count("decode.row_steps_live", sum(
            min(max(act.request.max_tokens - len(act.tokens)
                    - cols.get(slot, 0), 0), ncols)
            for slot, act in self.active.items()))
        count("decode.row_steps_grid",
              self.engine.engine_cfg.slots * ncols)

    def _count_sampler(self) -> None:
        """The draw's level into the recorder, at a dispatch, as the
        host sees it: one dispatch, whether any active request is
        sampled (``sampling.draw_slots`` level 1 or 2), and whether any
        is sampled through a filter (level 2: the vocabulary sort runs
        for every slot). A chunk whose requests are all greedy counts
        0 and 0."""
        vocab = self.engine.cfg.vocab_size
        asked = [sampling.asks(p.temperature, p.top_k, p.top_p, vocab)
                 for p in (act.request.sampling
                           for act in self.active.values())]
        count = self.spans.count
        count("sample.dispatches", 1)
        count("sample.dispatches_drawn",
              int(any(drawn for drawn, _ in asked)))
        count("sample.dispatches_sorted",
              int(any(filtered for _, filtered in asked)))

    def _count_keys(self, positions: List[int]) -> None:
        """Under the latent mixer, what the sparse selection had to do
        for query tokens at ``positions`` (the host's view): cache
        positions the indexer scores (``position + 1`` a layer) and
        positions attended (at most ``index_topk`` of them). A decode
        chunk counts every step as if its row lived to the chunk's
        end."""
        lc = self.engine.cfg.latent
        if lc is None or not positions:
            return
        layers = self.engine.cfg.num_layers
        last = self.engine.engine_cfg.max_seq_len - 1
        seen = [min(p, last) + 1 for p in positions]
        self.spans.count("dsa.keys_scored", layers * sum(seen))
        self.spans.count("dsa.keys_attended", layers * sum(
            min(n, lc.index_topk) for n in seen))

    def _count_routing(self) -> None:
        """Under the latent mixer, the routed layers' totals as the
        device added them up, read once a tick (a wait for the newest
        dispatched program, so only with a span recorder)."""
        if self.engine.cfg.latent is None:
            return
        now = latent_engine.routing_counts(self.engine)
        for key, n in now.items():
            delta = n - self._routing_seen.get(key, 0)
            if delta > 0:
                self.spans.count("moe." + key, delta)
        self._routing_seen = now

    def _exclusion_cause(self) -> Optional[str]:
        """THE per-slot exclusion conditions, as a cause: a
        constrained request is active (its vocab mask advances per
        token — the decode_chunk==1 serialization from the constrained
        path extends to forcing plain chunks), or a fault replay is in
        flight (replay exactness is simplest to audit on the plain
        path; streams are bit-identical either way, this keeps the
        replay invariant independent of gate/tuner state). One
        spelling shared by the payoff gate's plain-forcing
        (:meth:`_plain_only`) and the tuner's freeze causes so the two
        can never disagree on the exclusions."""
        for act in self.active.values():
            if act.request.constraint is not None:
                return "constrained"
            if len(act.tokens) < act.suppress:
                return "replay"     # re-deriving a pre-fault stream
        return None

    def _plain_only(self) -> bool:
        """Whether speculative dispatch is excluded right now (see
        :meth:`_exclusion_cause`)."""
        return self._exclusion_cause() is not None

    def _use_spec(self) -> bool:
        """Whether the next chunk dispatches the speculative variant
        under the payoff gate (the non-tuner spec path)."""
        g = self._gate
        if g is None or self._plain_only():
            return False
        return g.want_spec(spec_inflight=sum(
            1 for entry in self._inflight if entry[0].spec))

    def _dispatch_chunk(self) -> bool:
        """Dispatch the next decode chunk if it can pay for itself;
        True when one went out. With a tuner, the controller picks the
        operating point (pre-warmed variant + host knobs) — or holds
        the dispatch while a probe chunk is still in flight (probe
        serialization). A dispatch-seam fault triggers recovery (every
        live slot was in the failing chunk's blast radius)."""
        if not self._dispatchable():
            return False
        tn = self._tuner
        point: Optional[Dict[str, int]] = None
        step_kw: Dict[str, Any] = {}
        if tn is not None:
            cause = self._exclusion_cause()
            if cause is not None:
                # re-evaluated AT dispatch, not just at tick start: a
                # constrained (or replaying) request admitted THIS
                # tick — after _sync_tuner's freeze check — must not
                # decode at the incumbent/probe chunk width (a >1
                # chunk would scan tokens 2..n against a stale vocab
                # mask: schema-invalid output, not just a bad sample)
                tn.freeze(cause)
            point = tn.want_dispatch(len(self._inflight))
            if point is None:
                return False    # a probe chunk is in flight — hold
            if "pipeline_depth" in point:
                # the depth knob applies at dispatch too: a probe
                # window's candidate depth governs its own chunks
                self.pipeline_depth = point["pipeline_depth"]
            if "decode_chunk" in point:
                step_kw["chunk"] = point["decode_chunk"]
            k = point.get("spec_k", 0)
            if k > 0 and not self._plain_only():
                step_kw["spec"], step_kw["spec_k"] = True, k
            else:
                # gate-owned speculation composes, EXCEPT during a
                # probe window: probe chunks force the plain path, or
                # an open gate (spec chunks are never observed — their
                # token counts reflect acceptance, not this point's
                # knobs) would starve the window of its probe_chunks
                # samples while serialization held the pipeline at one
                # in-flight chunk
                step_kw["spec"] = ("spec_k" not in point
                                   and tn.probe is None
                                   and self._use_spec())
                if "spec_k" in point:
                    # record the EFFECTIVE point: a plain-forced chunk
                    # (exclusion raced the tick-start freeze) must not
                    # be attributed to the spec operating point
                    point["spec_k"] = 0
            if tn.frozen is not None or (
                    step_kw["spec"] and "spec_k" not in tn.knobs):
                # never-observe sentinel: a frozen dispatch carries
                # replay/constrained traffic even if fetched after the
                # thaw, and a GATE-driven speculative chunk's token
                # count reflects the gate's acceptance, not this
                # point's knobs — folding either into the EWMAs would
                # poison exactly the comparison the controller makes
                point = None
        else:
            step_kw["spec"] = self._use_spec()
        if self.spans is not None:
            self._count_decode_chunks()
            self._count_sampler()
            if self.engine.cfg.latent is not None:
                cols = self._inflight_cols()
                self._count_keys([
                    len(act.request.prompt) + len(act.tokens)
                    + cols.get(slot, 0) + c - 1
                    for slot, act in self.active.items()
                    for c in range(self.engine.engine_cfg.decode_chunk)])
        try:
            # the host-side cost of getting the chunk onto the device —
            # the half of the old engine.step section the pipeline
            # cannot hide
            with self._timed("engine.dispatch") as timed:
                handle = self.engine.step_async(**step_kw)
        except Exception as e:  # device error escaping the dispatch
            self._recover(self.clock(), cause="dispatch", detail=str(e),
                          affected=[a.request for _, a in
                                    sorted(self.active.items())])
            return False
        t0 = timed.start
        if self.spans is not None:
            self._count_decode_rows(handle.ncols)
        # snapshot the live slots: by the time this chunk is fetched,
        # some may have been released (finish seen in an earlier chunk,
        # deadline retire) and their columns must be dropped
        self._inflight.append((handle, dict(self.active), t0,
                               len(self._inflight) + 1, point))
        if self.recorder is not None:
            self.recorder.record("dispatch", handle.spec, handle.ncols,
                                 len(self._inflight), len(self.active))
        if self.telemetry is not None:
            self.telemetry.inflight.set(len(self._inflight))
        return True

    def _collect_oldest(self) -> None:
        handle, snapshot, t_dispatch, depth_at_dispatch, point = \
            self._inflight.popleft()
        try:
            # the blocking wait for the chunk's value — under pipelining
            # this shrinks toward zero while engine.dispatch stays put;
            # with a recorder the handle splits it into the wait for the
            # first copy and the copies after (engine.fetch.wait / .copy)
            with self._timed("engine.fetch") as timed:
                tokens, logprobs, finished = (
                    handle.fetch() if self.spans is None
                    else handle.fetch(section=self.spans.section))
        except Exception as e:  # device error escaping the fetch
            self._recover(self.clock(), cause="fetch", detail=str(e),
                          affected=[a.request
                                    for s, a in sorted(snapshot.items())
                                    if self.active.get(s) is a])
            return
        now = timed.end
        tele = self.telemetry
        if tele is not None:
            tele.inflight.set(len(self._inflight))
        if self.spans is not None:
            for slot, act in snapshot.items():
                if self.active.get(slot) is act:
                    self.spans.mark(act.request.request_id,
                                    spans_mod.PHASE_DECODE)
        # chunk-latency EWMA + watchdog: a dispatch that took longer
        # than the timeout to yield its value is flagged as hung (the
        # tokens may still be good — the chunk proceeds). A tripped
        # chunk is EXCLUDED from the EWMA: it is already accounted as
        # a fault, and folding a 30 s hang into the overload estimator
        # would shed every deadlined request in the queue against a
        # latency the healthy engine does not have. The EWMA sample is
        # normalized by the pipeline depth at dispatch: at depth d the
        # dispatch-to-fetch wall includes waiting behind d-1 earlier
        # in-flight chunks, and pricing the queue with the un-divided
        # wall would overstate slot turnover ~d× and shed requests
        # that would have met their deadlines
        # still-live snapshot rows — THE liveness condition shared by
        # the fetch event, the gate's tokens-per-wave denominator, and
        # the latency denominator below (computed once so they can
        # never disagree)
        live_rows = [s for s, a in snapshot.items()
                     if self.active.get(s) is a]
        chunk_wall = max(now - t_dispatch, 0.0)
        rec = self.recorder
        if rec is not None:
            rec.record("fetch", handle.spec, handle.ncols, chunk_wall,
                       len(live_rows))
        if chunk_wall > self.resilience.watchdog_timeout_s:
            self._watchdog_trips += 1
            if rec is not None:
                rec.record("watchdog", chunk_wall)
            if self._tuner is not None:
                # a tripped chunk is never observed (below) — without
                # this freeze, a probe window whose candidate keeps
                # hanging would never accumulate its probe_chunks
                # samples and the controller would re-dispatch the
                # pathological variant forever. The freeze aborts the
                # window (recorded, so decision replay sees it) and
                # the next clean tick thaws and moves on.
                self._tuner.freeze("watchdog")
            self.health.record_fault("watchdog")
            self._maybe_dump("watchdog")
            if tele is not None:
                tele.watchdog.inc()
        else:
            sample = chunk_wall / max(depth_at_dispatch, 1)
            self._chunk_ewma = sample if self._chunk_ewma == 0.0 \
                else 0.7 * self._chunk_ewma + 0.3 * sample
        # NaN/garbage quarantine: an out-of-vocab token id ANYWHERE in
        # the batch means the step (and the cache it wrote) cannot be
        # trusted — drop the whole chunk before unpacking a single
        # token and rebuild, even when every corrupt lane belongs to a
        # slot already released (the cache those lanes share is still
        # poisoned). Only still-live corrupt lanes are charged a
        # retry; everyone else replays for free. One whole-array
        # min/max pass exits the healthy case before any per-slot
        # work (this runs on every chunk)
        vocab = self.engine.cfg.vocab_size
        if tokens.size and (int(tokens.min()) < 0
                            or int(tokens.max()) >= vocab):
            bad = [act.request for slot, act in sorted(snapshot.items())
                   if self.active.get(slot) is act
                   and bool(((tokens[slot] < 0)
                             | (tokens[slot] >= vocab)).any())]
            self._recover(
                now, cause="invalid_token",
                detail="invalid token id in decode batch "
                "(NaN-poisoned step)", affected=bad)
            return
        n_cols = tokens.shape[1]
        valid = handle.valid    # spec chunks: which columns are real
        # speculative accounting + payoff gate: tokens-per-wave over
        # the still-live snapshot rows (a live wave always emits its
        # column-0 token, so live waves = True column-0 flags), and the
        # chunk-wall EWMAs per variant the break-even compares. A
        # watchdog-tripped chunk is excluded exactly like the overload
        # EWMA above.
        g = self._gate
        if chunk_wall <= self.resilience.watchdog_timeout_s \
                and (g is not None or handle.spec):
            sample = chunk_wall / max(depth_at_dispatch, 1)
            if handle.spec:
                # per-wave accounting runs for EVERY spec chunk —
                # gate-driven or tuner-driven (the tuner's spec_k knob
                # has no gate, but acceptance telemetry must not go
                # dark when the controller owns the choice)
                self._spec_chunks += 1
                tpw = None
                rows = live_rows
                if rows and valid is not None:
                    v = valid[rows]
                    live_waves = int(v[:, ::handle.spec_k + 1].sum())
                    emitted = int(v.sum())
                    if live_waves:
                        tpw = emitted / live_waves
                        drafted = handle.spec_k * live_waves
                        self._spec_drafted += drafted
                        self._spec_accepted += emitted - live_waves
                        if tele is not None:
                            tele.spec_drafted.inc(drafted)
                            tele.spec_accepted.inc(emitted - live_waves)
                if g is not None:
                    g.observe_spec(sample, tpw)
                if self.spans is not None:
                    # the verify forward's host window: dispatch to
                    # value of the speculative chunk
                    self.spans.section_at("engine.verify", t_dispatch,
                                          now)
            elif g is not None:
                g.observe_plain(sample)
            if g is not None:
                st = g.state()
                if st != self._gate_state_seen:
                    # a payoff-gate transition is a scheduling decision
                    # — log it once per flip, not per chunk
                    self._gate_state_seen = st
                    if rec is not None:
                        rec.record("spec_gate", st, g.accept_ewma,
                                   g.break_even())
                if tele is not None:
                    tele.spec_gate.set(st)
                    tele.spec_accept_ewma.set(g.accept_ewma)
        # in-flight latency of this chunk (dispatch -> value); the
        # decode-time split dedups the overlap so pipelined chunks
        # don't double-count wall time. Spec chunks price latency per
        # REAL emitted token (pad lanes are not tokens).
        if valid is None:
            per_tok = max(now - t_dispatch, 0.0) / n_cols
        else:
            mean_emitted = (valid[live_rows].sum() / len(live_rows)
                            if live_rows else 0.0)
            per_tok = (max(now - t_dispatch, 0.0)
                       / max(mean_emitted, 1.0))
        self._decode_time += now - max(self._decode_mark, t_dispatch)
        self._decode_mark = now
        chunk_tokens = 0    # actual ingested emissions (the tuner's
        # tokens-per-second numerator: pad columns past a finish are
        # honestly NOT tokens, so an over-wide chunk scores its waste)
        for j in range(n_cols):
            for slot, act in snapshot.items():
                # a slot released since dispatch (earlier chunk/column
                # finish, a host-side stop, or a deadline retire
                # landing mid-flight) is skipped: the device emits pad
                # for done lanes, and a retired request's in-flight
                # tokens belong to a completion that already closed.
                # Spec chunks additionally skip non-valid columns —
                # rejected draft lanes emit pad without being tokens
                # (the StopMatcher and constraint DFA see the accepted
                # prefix only).
                if self.active.get(slot) is not act:
                    continue
                if valid is not None and not valid[slot, j]:
                    continue
                tok = int(tokens[slot, j])
                done = bool(finished[slot, j])
                reason = None
                if done:
                    eos = act.request.eos_token_id
                    reason = (FINISH_EOS
                              if eos is not None and tok == eos
                              else FINISH_LENGTH)
                chunk_tokens += 1
                if self._ingest(slot, act, tok,
                                float(logprobs[slot, j]), now,
                                device_done=done, device_reason=reason,
                                latency=per_tok) == _RECOVERED:
                    return  # recovery rebuilt everything mid-unpack
        tn = self._tuner
        if tn is not None and point is not None and chunk_wall <= \
                self.resilience.watchdog_timeout_s:
            # the control plane's one input: realized tokens at this
            # chunk's operating point (watchdog-tripped chunks are
            # excluded exactly like the overload EWMA; a frozen
            # controller ignores the call). Recorded as tuner_obs, so
            # telemetry.replay re-derives every decision from it.
            tn.observe(point, chunk_tokens, chunk_wall,
                       depth_at_dispatch)
        # a chunk landed end-to-end: recovery streak for the health
        # machine, and the rebuild-storm counter resets
        self._consecutive_rebuilds = 0
        self.health.record_progress()
        # the fetch boundary is the journal's durability point: every
        # token this chunk streamed is on disk (per fsync policy)
        # before the next dispatch can build on it
        self._journal_commit()

    # -- token emission (stop sequences, constraints, logprobs) -------------

    def _emit(self, act: _Active, tok: int, lp: float, *,
              finished: bool, reason: Optional[str],
              latency: Optional[float] = None) -> None:
        """Append one client-visible token to ``act``'s stream and its
        :class:`StreamEvent` — suppressed (counted, no event) while the
        token re-derives a pre-fault stream prefix during replay."""
        act.tokens.append(tok)
        act.logprobs.append(lp)
        tele = self.telemetry
        if len(act.tokens) <= act.suppress:
            # re-derived token, already streamed before the fault —
            # suppress the duplicate event
            if tele is not None:
                tele.replayed.inc()
            return
        self._tokens_emitted += 1
        # the WFQ deficit counter charges on ACTUAL served tokens —
        # fairness settles on delivered service, not admission-time
        # estimates (replay-suppressed re-derivations were charged
        # when first streamed, so they are not double-billed)
        self.tenants.on_tokens(act.request.tenant, 1)
        if latency is not None:
            self._decode_tokens += 1
            self.token_latency_stats.add(latency)
            if self.slo is not None:
                self.slo.observe("token_latency", latency,
                                 act.request.tenant)
            if tele is not None:
                tele.token_latency.observe(latency)
        if tele is not None:
            tele.tokens.inc()
            tele.tenant(act.request.tenant)["tokens"].inc()
        self.events.append(StreamEvent(
            act.request.request_id, tok, finished, reason, logprob=lp))

    def _flush_held(self, act: _Active,
                    latency: Optional[float] = None) -> None:
        """Stream every token the stop matcher held back — a non-stop
        finish (eos/length/deadline/error) emits the tail instead of
        trimming it."""
        if act.matcher is None:
            return
        for t, l in act.matcher.flush():
            self._emit(act, t, l, finished=False, reason=None,
                       latency=latency)

    def _ingest(self, slot: int, act: _Active, tok: int, lp: float,
                now: float, *, device_done: bool,
                device_reason: Optional[str],
                latency: Optional[float] = None) -> int:
        """Fold ONE generated token into a live request: stop-sequence
        matching (with trimmed emission), schema-constraint advance +
        next-mask upload, event emission, and release when the token
        finishes the request (device eos/budget, stop match, or
        constraint completion). Returns an ``_LIVE`` / ``_RELEASED`` /
        ``_RECOVERED`` outcome; ``_RECOVERED`` means a retire-seam
        fault rebuilt the engine mid-call and the caller's loop state
        is stale."""
        matched = False
        if act.matcher is not None:
            flushed, matched = act.matcher.push(tok, lp)
        else:
            flushed = [(tok, lp)]
        cons = act.request.constraint
        cons_done = False
        if cons is not None and not matched:
            cons.advance(tok)
            cons_done = bool(cons.done)
            if not cons_done and not device_done:
                # the DFA advanced: the NEXT dispatch must decode this
                # slot against the new allowed set
                self.engine.set_slot_mask(slot, cons.allowed_tokens())
        if (device_done or cons_done) and act.matcher is not None \
                and not matched:
            # non-trim finish: the held tail streams out
            flushed = flushed + act.matcher.flush()
        host_stop = matched or cons_done
        finishing = device_done or host_stop
        reason = ((FINISH_STOP if host_stop else device_reason)
                  if finishing else None)
        last = len(flushed) - 1
        for i, (t, l) in enumerate(flushed):
            fin = finishing and not matched and i == last
            self._emit(act, t, l, finished=fin,
                       reason=reason if fin else None, latency=latency)
        if matched:
            # trimmed stop: no token carries the finish — close the
            # stream with a token-less finished event (the deadline/
            # abort pattern)
            self.events.append(StreamEvent(
                act.request.request_id, None, True, reason))
        if not finishing:
            return _LIVE
        if host_stop and not device_done:
            # host-side finish: the device lane is still live — retire
            # it so later chunks stop burning its budget (in-flight
            # chunks' columns for this slot are dropped by the
            # snapshot identity check, exactly like a deadline retire)
            try:
                self.engine.retire(slot)
            except Exception as e:  # device error escaping retire
                self._release(slot, reason)
                self._recover(now, cause="retire", detail=str(e),
                              affected=[])
                return _RECOVERED
        self._release(slot, reason)
        return _RELEASED

    def _reset_free(self) -> List[int]:
        """Every slot free, pop order = slot order."""
        self._free = list(range(self.engine.slots))[::-1]
        return self._free

    def _abort(self, request: Request, reason: str, now: float, *,
               act: Optional[_Active] = None,
               error: Optional[str] = None) -> None:
        """Terminal non-success outcome (timeout shed/expiry, fault
        error): one finished StreamEvent + a completion carrying the
        longest stream the client saw — the live slot's tokens, or the
        replay snapshot when a fault interrupted mid-replay and the
        re-derivation had not caught up."""
        if act is not None:
            self._flush_held(act)
        st = self._replay.pop(request.request_id, None)
        tokens = list(act.tokens) if act is not None else []
        lps = list(act.logprobs) if act is not None else []
        if st is not None and len(st.tokens) > len(tokens):
            tokens, lps = st.tokens, st.logprobs
        ttft = None
        if act is not None and act.first_token_time is not None:
            ttft = act.first_token_time - request.arrival_time
        self.events.append(StreamEvent(
            request.request_id, None, True, reason, error=error))
        self._complete(request, tokens, reason, ttft=ttft, now=now,
                       logprobs=lps)

    # -- failure isolation + recovery --------------------------------------

    def _recover(self, now: float, *, cause: str, detail: str,
                 affected: Sequence[Request],
                 batch_reqs: Sequence[Request] = ()) -> None:
        """Quarantine + rebuild + deterministic replay. ``affected``
        requests were in the fault's blast radius: they are charged a
        retry (bounded, exponential backoff) and get an ``error``
        stream event; exhaustion completes them with the ``error``
        reason. Every other interrupted request — live slots, plus
        ``batch_reqs`` from a failed admission call that never reached
        a slot — replays for free. Replay = re-admit from the prompt:
        generation is per-request deterministic, so the regenerated
        stream is bit-identical and the already-streamed prefix
        (tracked per request in ``_replay``) is re-derived silently."""
        tele = self.telemetry
        rec = self.recorder
        rcfg = self.resilience
        if self._tuner is not None:
            # the rebuild bracket is a hard freeze: in-flight chunks
            # are discarded unmeasured, and the replay traffic that
            # follows re-freezes at the next tick's cause evaluation
            self._tuner.freeze("rebuild")
        if rec is not None:
            rec.record("fault", cause, detail, len(affected))
        self.health.record_fault(cause)
        if tele is not None and cause in tele.faults:
            tele.faults[cause].inc()
        # in-flight chunks were dispatched against the poisoned
        # buffers: discard them UNFETCHED (their futures may hold the
        # error; the replay re-derives anything they carried)
        self._inflight.clear()
        if tele is not None:
            tele.inflight.set(0)
        self._consecutive_rebuilds += 1
        if self._consecutive_rebuilds > rcfg.max_consecutive_rebuilds:
            self.queue.extendleft(reversed(list(batch_reqs)))
            self._fail_all(f"recovery storm ({cause}: {detail})", now)
            return
        # interrupted work, slot order first (they were admitted
        # earliest), then the failed admission batch (they were at the
        # queue's front moments ago)
        interrupted: List[Tuple[Request, Optional[_Active]]] = [
            (act.request, act)
            for _, act in sorted(self.active.items())]
        interrupted += [(r, None) for r in batch_reqs]
        if self._chunked is not None:
            # a mid-chunked fault: the half-ingested prompt replays
            # from scratch like any other interrupted request
            ca, cr = self._chunked
            self._chunked = None
            if all(r.request_id != cr.request_id
                   for r, _ in interrupted):
                interrupted.append((cr, None))
        self.active.clear()
        self._reset_free()
        # always rebuild: even when the fault was detected host-side
        # (invalid tokens) or the exception left the engine formally
        # unpoisoned, the donated buffers were rebound across the
        # failing call and cannot be trusted
        self.engine.rebuild_slots()
        self._rebuilds += 1
        if rec is not None:
            rec.record("rebuild", cause,
                       max(self.clock() - now, 0.0),
                       self._consecutive_rebuilds)
        if tele is not None:
            tele.rebuilds.inc()
            tele.active_slots.set(0)
        if self.spans is not None:
            self.spans.section_at("engine.rebuild", now, self.clock())
        affected_ids = {r.request_id for r in affected}
        front: List[Request] = []
        for r, act in interrupted:
            st = self._replay.setdefault(r.request_id, _ReplayState())
            if act is not None and len(act.tokens) > len(st.tokens):
                # the last known-good snapshot: everything this request
                # streamed before the fault, re-derived on replay. Only
                # ever GROW it — a second fault landing mid-replay sees
                # act.tokens shorter than what was already streamed
                # (the replay had not caught up yet), and shrinking the
                # snapshot would re-emit the tail as duplicates.
                # Matcher-held tokens are NOT in the snapshot: they
                # were never streamed, and the replayed matcher
                # re-derives (and re-holds) them deterministically
                st.tokens = list(act.tokens)
                st.logprobs = list(act.logprobs)
            if rec is not None:
                rec.record("replay", r.request_id, len(st.tokens))
            if r.request_id in affected_ids:
                st.attempts += 1
                if st.attempts > rcfg.max_retries:
                    if rec is not None:
                        rec.record("retry_exhausted", r.request_id,
                                   st.attempts)
                    self.health.record_fault("retry_exhausted")
                    self._retry_exhausted += 1
                    if self.on_evict is not None:
                        # fleet hand-off: this replica gave up on the
                        # request, but another may serve it — the
                        # router resubmits with the emitted prefix so
                        # the client stream continues, not errors
                        self._evicted_requests += 1
                        self._replay.pop(r.request_id, None)
                        self._req_records.pop(r.request_id, None)
                        self.on_evict(
                            [EvictedRequest(r, list(st.tokens),
                                            list(st.logprobs))],
                            f"retry_exhausted ({cause}: {detail})")
                        continue
                    self._abort(r, FINISH_ERROR, now, act=act,
                                error=f"{cause}: {detail}; "
                                f"{rcfg.max_retries} retries exhausted")
                    continue
                st.not_before = now + rcfg.backoff_s(st.attempts)
                self._retries += 1
                if rec is not None:
                    rec.record("retry", r.request_id, st.attempts)
                if tele is not None:
                    tele.retries.inc()
                self.events.append(StreamEvent(
                    r.request_id, None, False, None,
                    error=f"{cause}: {detail}; retry "
                    f"{st.attempts}/{rcfg.max_retries}"))
                if self.spans is not None:
                    self.spans.mark(r.request_id, spans_mod.PHASE_ERROR,
                                    note=cause)
            front.append(r)
        self.queue.extendleft(reversed(front))
        if tele is not None:
            tele.queue_depth.set(len(self.queue))
        # the post-mortem bundle lands AFTER the recovery bracket, so
        # it carries the fault AND its rebuild/replay/retry events
        self._maybe_dump(f"fault-{cause}")

    def _fail_all(self, cause: str, now: float) -> None:
        """Terminal: abort every queued/active request with an
        ``error`` outcome (partial streams preserved) and mark the
        health machine failed. The process survives — callers see
        completions, not a crash. The terminal bundle dumps FIRST,
        while the queue/slot state it should explain still exists.
        With an :attr:`on_evict` hook, interrupted work is handed over
        as :class:`EvictedRequest` records instead of error outcomes —
        the fleet failover path."""
        if self.recorder is not None:
            self.recorder.record("failed", cause)
        self._maybe_dump("failed")
        self.health.fail(cause)
        if self.on_evict is not None:
            self._evict_all(cause)
            return
        for slot, act in sorted(self.active.items()):
            self._abort(act.request, FINISH_ERROR, now, act=act,
                        error=cause)
            self.engine.free_slot(slot)
        if self._chunked is not None:
            ca, cr = self._chunked
            self._chunked = None
            self.engine.free_slot(ca.slot)
            self._abort(cr, FINISH_ERROR, now, error=cause)
        self.active.clear()
        self._reset_free()
        for r in self.queue:
            self._abort(r, FINISH_ERROR, now, error=cause)
        self.queue.clear()
        for rid, pk in sorted(self._parked.items()):
            self.engine.drop_parked(rid)
            self._abort(pk.act.request, FINISH_ERROR, now, act=pk.act,
                        error=cause)
        self._parked.clear()
        self._resume_q.clear()
        self._replay.clear()
        self._inflight.clear()
        if self.telemetry is not None:
            self.telemetry.queue_depth.set(0)
            self.telemetry.active_slots.set(0)
            self.telemetry.inflight.set(0)

    def eject_all(self, cause: str) -> None:
        """Router-facing: hand EVERY queued/active request to the
        :attr:`on_evict` hook with its emitted prefix and clear this
        scheduler's work — the circuit-breaker eviction (the engine
        stays alive; the caller typically ``rebuild_slots()`` right
        after, since in-flight chunks are discarded unfetched)."""
        if self.on_evict is None:
            raise ValueError(
                "eject_all needs an on_evict hook — without one the "
                "evicted requests would simply vanish")
        self._evict_all(cause)

    def _evict_all(self, cause: str) -> None:
        """Hand every interrupted request (active slots first — they
        were admitted earliest — then any chunked admission, then the
        queue) to :attr:`on_evict` with its longest client-visible
        stream, clearing this scheduler's work WITHOUT emitting error
        events or completions: the fleet router owns their fate now.
        In-flight chunks are discarded unfetched — anything they
        carried re-derives on the healthy replica."""
        evicted: List[EvictedRequest] = []

        def take(request: Request, act: Optional[_Active]) -> None:
            st = self._replay.pop(request.request_id, None)
            tokens = list(act.tokens) if act is not None else []
            lps = list(act.logprobs) if act is not None else []
            if st is not None and len(st.tokens) > len(tokens):
                # mid-replay: the pre-fault stream is the longest the
                # client saw — never hand over a shrunk snapshot
                tokens, lps = list(st.tokens), list(st.logprobs)
            # the router owns these streams now: journaled finished
            # ("evicted") so a crash-restart from THIS replica's
            # journal never resubmits work the fleet already failed
            # over — that would fork the client stream
            self._journal_finish(request, tokens, lps, "evicted")
            self._req_records.pop(request.request_id, None)
            evicted.append(EvictedRequest(request, tokens, lps))

        for slot, act in sorted(self.active.items()):
            take(act.request, act)
            self.engine.free_slot(slot)
        if self._chunked is not None:
            ca, cr = self._chunked
            self._chunked = None
            self.engine.free_slot(ca.slot)
            take(cr, None)
        for r in self.queue:
            take(r, None)
        for rid, pk in sorted(self._parked.items()):
            self.engine.drop_parked(rid)
            take(pk.act.request, pk.act)
        self._parked.clear()
        self._resume_q.clear()
        self.active.clear()
        self.queue.clear()
        self._reset_free()
        self._replay.clear()
        self._inflight.clear()
        self._evicted_requests += len(evicted)
        if self.telemetry is not None:
            self.telemetry.queue_depth.set(0)
            self.telemetry.active_slots.set(0)
            self.telemetry.inflight.set(0)
        # the evict-finishes must be durable BEFORE the router
        # resubmits the work elsewhere — a crash in between would
        # otherwise recover requests another replica is now serving
        self._journal_commit()
        self.on_evict(evicted, cause)

    # -- durable request journal (serving.journal) ---------------------------

    def _jlog(self, kind: str, **fields) -> None:
        """Append one journal record (no-op without a journal) and
        surface it in the flight recorder — journal growth is itself
        a host decision a post-mortem wants on the timeline."""
        j = self.journal
        if j is None:
            return
        rot = j.rotations
        seq = j.append(kind, **fields)
        rec = self.recorder
        if rec is not None:
            rec.record("journal_append", seq, kind,
                       j.last_append_bytes)
            if j.rotations != rot and j.last_sealed is not None:
                rec.record("journal_rotate", *j.last_sealed)

    def _journal_submit(self, request: Request, now: float) -> None:
        """Journal an accepted request — the replayable
        ``_record_request`` row, with the absolute deadline converted
        to REMAINING budget (a monotonic clock does not survive a
        restart; recovery re-bases it)."""
        if self.journal is None:
            return
        row = dict(self._req_records[request.request_id])
        row.pop("arrival", None)
        deadline = row.pop("deadline", None)
        row["deadline_remaining"] = (
            None if deadline is None else max(deadline - now, 0.0))
        if row.get("adapter"):
            # the numeric id is generation-local (a recovered engine
            # re-assigns ids sequentially and may reuse a skipped
            # registration's); the NAME is the stable cross-recovery
            # key replay maps the request back through
            meta = self.engine._adapter_meta.get(
                int(row["adapter"]), {})
            row["adapter_name"] = meta.get("name")
        self._jlog("submit", **row)
        self._journal_len[request.request_id] = 0

    def _journal_extend(self, rid: str, tokens, logprobs) -> None:
        """Journal the growth of one stream's emitted prefix since the
        last extend. Absolute start offsets make replay idempotent —
        the property compaction's crash-safety rests on. Unknown ids
        (terminal-at-submit, pre-journal requests) are skipped."""
        jl = self._journal_len.get(rid)
        if jl is None or len(tokens) <= jl:
            return
        self._jlog("extend", request_id=rid, start=jl,
                   tokens=[int(t) for t in tokens[jl:]],
                   logprobs=[float(x) for x in logprobs[jl:]])
        self._journal_len[rid] = len(tokens)

    def _journal_commit(self) -> None:
        """The fetch-boundary durability point: extend every live
        stream (active slots AND replay snapshots — a preempted or
        parked conversation's prefix lives in ``_replay``), then
        fsync per the journal's policy, then let auto-compaction run.
        Registry counters refresh here by delta, off the per-token
        path."""
        j = self.journal
        if j is None:
            return
        for act in self.active.values():
            self._journal_extend(act.request.request_id, act.tokens,
                                 act.logprobs)
        for rid, st in self._replay.items():
            self._journal_extend(rid, st.tokens, st.logprobs)
        j.commit()
        j.maybe_compact()
        tele = self.telemetry
        if tele is not None:
            seen = self._j_seen
            for attr, handle in (
                    ("appends", tele.journal_appends),
                    ("rotations", tele.journal_rotations),
                    ("compactions", tele.journal_compactions)):
                d = getattr(j, attr) - seen[attr]
                if d:
                    handle.inc(d)
                    seen[attr] = getattr(j, attr)
            ds = j.fsync_s - seen["fsync_s"]
            if ds > 0:
                tele.journal_fsync.inc(ds)
                seen["fsync_s"] = j.fsync_s
            tele.journal_bytes.set(j.bytes_on_disk())
            tele.journal_lag.set(j.lag_bytes)

    def _journal_finish(self, request: Request, tokens, logprobs,
                        reason: str) -> None:
        """Journal a terminal outcome: the final extend (everything
        the client was streamed) then the finish record, so recovery
        never resubmits completed — or fleet-evicted — work."""
        if self.journal is None:
            return
        rid = request.request_id
        if rid not in self._journal_len:
            return
        self._journal_extend(rid, tokens, logprobs or [])
        self._journal_len.pop(rid, None)
        self._jlog("finish", request_id=rid, reason=reason)

    # -- flight recorder + post-mortem bundles -------------------------------

    def _record_request(self, request: Request, now: float) -> None:
        """Start the replayable record of one accepted request — the
        bundle's ``requests.jsonl`` row (prompt/sampling/seed; the
        emitted prefix attaches at completion or dump time). Kept even
        without a recorder: dumps are most wanted for runs nobody
        thought to instrument."""
        sp = request.sampling
        self._req_records[request.request_id] = {
            "order": self._submit_seq,
            "request_id": request.request_id,
            "prompt": [int(t) for t in request.prompt],
            "max_tokens": request.max_tokens,
            "temperature": sp.temperature,
            "top_k": sp.top_k,
            "top_p": sp.top_p,
            "seed": sp.seed,
            "eos_token_id": request.eos_token_id,
            "stop": ([[int(t) for t in s] for s in request.stop]
                     if request.stop else None),
            "constrained": request.constraint is not None,
            "deadline": request.deadline,
            "arrival": now,
            # the tenancy pair: replay resubmits with the same tenant
            # (fair-queue decisions re-derive) and the same adapter
            # row (seeded registrations rebuild the exact weights, so
            # the replayed stream is bit-identical)
            "tenant": request.tenant,
            "adapter": request.adapter,
        }
        self._submit_seq += 1

    def _on_health_transition(self, old: str, new: str,
                              cause: Optional[str]) -> None:
        if self.recorder is not None:
            self.recorder.record("health", old, new, cause)

    def _maybe_dump(self, cause: str) -> None:
        """Auto-dump gate: a bundle per trigger WAVE (faults, their
        health transitions, and their retries land in one tick — one
        bundle explains them all), bounded by ``max_auto_bundles`` so a
        fault storm cannot fill the disk with near-identical evidence.
        Disk errors are swallowed — losing a bundle must never take
        down the serving loop that survived the fault itself."""
        if self.bundle_dir is None \
                or self._auto_bundles >= self.max_auto_bundles \
                or self._last_dump_token == self._dump_token:
            return
        self._last_dump_token = self._dump_token
        self._auto_bundles += 1
        try:
            self.dump_bundle(cause)
        except OSError:
            pass

    def dump_bundle(self, cause: str = "manual",
                    bundle_dir: Optional[str] = None) -> str:
        """Write a self-contained post-mortem bundle directory and
        return its path: manifest (cause, health, ``summary()``,
        versions, caller ``bundle_meta``), flight-recorder event log
        (``events.jsonl``), engine/scheduler config (``config.json``
        — everything ``apex_tpu.telemetry.replay`` needs to rebuild
        the run), per-request replay records (``requests.jsonl``),
        plus registry snapshot / Chrome-trace spans / fault-plan
        record when those exist. Atomic (same-dir tmp +
        ``os.replace``): a reader sees a complete bundle or none.

        Safe to call from another thread (the ``/debug/bundle``
        trigger, a SIGUSR handler): the payload walk takes C-level
        (GIL-atomic) snapshots of the mutable maps, and the build is
        retried if the serving loop still manages to mutate a
        structure mid-iteration — the bundle is a best-effort snapshot
        of a moving system, but it is always internally well-formed."""
        base = bundle_dir or self.bundle_dir
        if base is None:
            raise ValueError(
                "no bundle directory: pass bundle_dir here or "
                "Scheduler(bundle_dir=...)")
        for attempt in range(3):
            try:
                files = self._bundle_payload(cause)
                break
            except RuntimeError:  # dict/set mutated during iteration
                if attempt == 2:
                    raise
        slug = "".join(c if c.isalnum() else "-" for c in cause)[:40]
        while True:
            name = f"bundle-{self._bundle_counter:04d}-{slug}"
            path = os.path.join(base, name)
            self._bundle_counter += 1
            if not os.path.exists(path):
                break
        path = flightrec_mod.write_bundle(path, files)
        self.bundles_written.append(path)
        if self.recorder is not None:
            self.recorder.record("bundle", cause,
                                 os.path.basename(path))
        return path

    def _bundle_payload(self, cause: str) -> Dict[str, object]:
        engine = self.engine
        rec = self.recorder
        # completed records first, then live (queued/active) ones with
        # the client-visible stream they have so far — the longest of
        # the live slot's tokens and the replay snapshot (mid-replay
        # the snapshot is what the client actually saw)
        # list()/dict() of a dict are single C calls — GIL-atomic
        # snapshots, so a cross-thread dump never iterates a map the
        # serving loop is mutating (the comprehensions below run over
        # the snapshots, not the live structures)
        requests = [dict(r) for r in self._req_done.values()]
        by_id = {a.request.request_id: a
                 for a in list(self.active.values())}
        parked = {pk.act.request.request_id: pk.act
                  for pk in list(self._parked.values())}
        for rid, row in list(self._req_records.items()):
            row = dict(row)
            act = by_id.get(rid) or parked.get(rid)
            toks = list(act.tokens) if act is not None else []
            st = self._replay.get(rid)
            if st is not None and len(st.tokens) > len(toks):
                toks = list(st.tokens)
            row["emitted"] = toks
            row["status"] = ("active" if rid in by_id
                             else "parked" if rid in parked
                             else "queued")
            requests.append(row)
        requests.sort(key=lambda r: r["order"])
        manifest: Dict[str, object] = {
            "bundle_version": 1,
            "cause": cause,
            "wall_time": time.time(),
            "clock": self.clock(),
            "health": {"state": self.health.state,
                       "last_cause": self.health.last_cause},
            "summary": self.summary(),
            "flightrec": rec.summary() if rec is not None else None,
            "compiled": engine.compiled_cache_sizes(),
            "versions": flightrec_mod.versions(),
            "meta": self.bundle_meta,
        }
        sentinel = getattr(engine, "_sentinel", None)
        if sentinel is not None:
            manifest["recompile"] = sentinel.compiles_total()
        config: Dict[str, object] = {
            "engine": engine.describe(),
            "scheduler": {
                "max_queue": self.max_queue,
                "pipeline_depth": self._cfg_pipeline_depth,
                "max_admit_batch": self._cfg_max_admit_batch,
                "resilience": dataclasses.asdict(self.resilience),
                "spec_gate": (dataclasses.asdict(self._gate.cfg)
                              if self._gate is not None else None),
                # the tuner's ladders + policy AND its base operating
                # point: everything replay_decisions needs to re-run
                # the trajectory from the recorded observations
                "tuner": (dataclasses.asdict(self._tuner.cfg)
                          if self._tuner is not None else None),
                "tuner_base": (dict(self._tuner.base)
                               if self._tuner is not None else None),
                # weights/rates serialize as plain dicts so replay
                # rebuilds the same WFQ + rate policy
                "tenancy": (None if self._tenancy_cfg is None else {
                    "weights": dict(self._tenancy_cfg.weights),
                    "default_weight":
                        self._tenancy_cfg.default_weight,
                    "rates": dict(self._tenancy_cfg.rates),
                    "default_rate": self._tenancy_cfg.default_rate,
                    "burst_s": self._tenancy_cfg.burst_s,
                    "aging_per_s": self._tenancy_cfg.aging_per_s,
                }),
                # objectives + burn policy: everything replay_slo needs
                # to re-run the alert sequence from the recorded
                # evaluation inputs
                "slo": (self._slo_cfg.to_dict()
                        if self._slo_cfg is not None else None),
            },
        }
        files: Dict[str, object] = {
            "manifest.json": manifest,
            "config.json": config,
            "events.jsonl": (rec.to_dicts(rec.events())
                             if rec is not None else []),
            "requests.jsonl": requests,
        }
        if self._registry is not None:
            files["registry.json"] = self._registry.to_dict()
        if self.spans is not None:
            files["spans_trace.json"] = self.spans.to_chrome_trace()
            # raw span rows keep ABSOLUTE scheduler-clock times (the
            # Chrome trace rebases to its own t0), so the replay
            # report can merge spans and flight events on one axis
            raw = []
            for e in self.spans.events():
                if e[0] == spans_mod._MARK:
                    raw.append({"kind": "mark", "t": e[1],
                                "request_id": e[2], "phase": e[3],
                                "note": e[4]})
                elif e[0] == spans_mod._COUNT:
                    raw.append({"kind": "count", "t": e[1],
                                "name": e[2], "n": e[3]})
                elif e[0] == spans_mod._CLOCK:
                    raw.append({"kind": "clock", "t": e[1],
                                "t_profiler": e[3]})
                else:
                    raw.append({"kind": "section", "t": e[1],
                                "name": e[2], "t_end": e[3],
                                "parent": e[4]})
            files["spans_raw.jsonl"] = raw
        plan = engine.fault_plan
        if plan is not None:
            files["fault_plan.json"] = {
                "specs": [dataclasses.asdict(s) for s in plan.specs],
                "injected": [dataclasses.asdict(s)
                             for s in plan.injected],
                "counts": plan.counts(),
            }
        return files

    # -- deadlines + overload protection ------------------------------------

    def _expire(self, now: float) -> None:
        kept: Deque[Request] = collections.deque()
        n_free, n_slots = len(self._free), self.engine.slots
        pos = 0
        for r in self.queue:
            if self._expire_queued(r, now):
                continue
            # deadline-aware shedding: when the queue ahead already
            # implies missing this deadline, shed NOW — the client
            # learns immediately instead of after the deadline the
            # scheduler knew it would blow. The estimate accounts for
            # slot concurrency: a request that fits the free slots
            # admits THIS tick (never shed), the rest wait roughly one
            # measured chunk latency per wave of `slots` ahead of them
            wave = (pos - n_free) // n_slots + 1
            if (self.resilience.shed_deadlines and r.deadline is not None
                    and self._chunk_ewma > 0.0 and pos >= n_free
                    and now + wave * self._chunk_ewma > r.deadline):
                self._shed += 1
                self.tenants.stats(r.tenant).shed += 1
                if self.recorder is not None:
                    self.recorder.record("shed", r.request_id,
                                         "deadline")
                if self.telemetry is not None:
                    self.telemetry.shed["deadline"].inc()
                    self.telemetry.tenant(r.tenant)["shed"][
                        "deadline"].inc()
                self._abort(r, FINISH_TIMEOUT, now)
                continue
            kept.append(r)
            pos += 1
        self.queue = kept
        for slot in list(self.active):
            act = self.active.get(slot)
            if act is None:
                continue  # a retire-seam recovery below cleared it
            dl = act.request.deadline
            if dl is not None and now >= dl:
                # a timeout streams the matcher-held tail (nothing
                # matched — there is nothing to trim)
                self._flush_held(act)
                try:
                    self.engine.retire(slot)
                except Exception as e:  # device error escaping retire
                    # the expiring request still times out (its tokens
                    # so far are on the host); everyone else replays
                    self.events.append(StreamEvent(
                        act.request.request_id, None, True,
                        FINISH_TIMEOUT))
                    self._release(slot, FINISH_TIMEOUT)
                    self._recover(now, cause="retire", detail=str(e),
                                  affected=[])
                    continue
                self.events.append(StreamEvent(
                    act.request.request_id, None, True, FINISH_TIMEOUT))
                self._release(slot, FINISH_TIMEOUT)
        for rid in list(self._parked):
            pk = self._parked[rid]
            dl = pk.act.request.deadline
            if dl is not None and now >= dl:
                # a parked conversation's deadline still bites: drop
                # the swap payload and time out with the stream so far
                del self._parked[rid]
                try:
                    self._resume_q.remove(rid)
                except ValueError:
                    pass
                self.engine.drop_parked(rid)
                self._abort(pk.act.request, FINISH_TIMEOUT, now,
                            act=pk.act)

    def _expire_queued(self, request: Request, now: float) -> bool:
        dl = request.deadline
        if dl is None or now < dl:
            return False
        if self.recorder is not None:
            self.recorder.record("queue_expired", request.request_id)
        if self.telemetry is not None:
            self.telemetry.queue_expired.inc()
        self._abort(request, FINISH_TIMEOUT, now)
        return True

    # -- admission ----------------------------------------------------------

    def _admission_of(self, r: Request, slot: int) -> Admission:
        """Build one :class:`Admission` row from a request (shared by
        the batched, prefix-hit, and chunked admission paths so they
        can never disagree on the sampling surface)."""
        hit = self._prefix_hits.get(r.request_id)
        return Admission(
            slot=slot, prompt=r.prompt,
            max_tokens=r.max_tokens,
            temperature=r.sampling.temperature,
            top_k=r.sampling.top_k,
            top_p=r.sampling.top_p,
            seed=r.sampling.seed,
            eos_token_id=r.eos_token_id,
            allowed_tokens=(
                tuple(r.constraint.allowed_tokens())
                if r.constraint is not None else None),
            prefix_page=None if hit is None else hit[0],
            prefix_len=0 if hit is None else hit[1],
            adapter=r.adapter)

    def _request_pages_needed(self, r: Request) -> int:
        """One request's PRIVATE page need — copy-on-write prefix
        pages discounted (they pin, they don't allocate). The one
        spelling submit's never-fits guard, the admission page gate,
        and the backpressure telemetry all share."""
        hit = self._prefix_hits.get(r.request_id)
        return self.engine.pages_needed(
            len(r.prompt), r.max_tokens, 0 if hit is None else hit[1])

    def _note_pages_exhausted(self, r: Request, needed: int) -> None:
        """Backpressure, not a fault: the head request waits queued
        until releases free enough pages (an ingress layer sees the
        pressure as queue growth → :class:`QueueFull` 429s). Under
        oversubscription (:attr:`preempt`) the wait also triggers the
        WFQ preemption pass — the freed pages let the head admit next
        tick instead of waiting out a long-running lowest-priority
        stream."""
        self._pages_exhausted_waits += 1
        if self.recorder is not None:
            self.recorder.record(
                "pages_exhausted", r.request_id, needed,
                self.engine.page_allocator.free_pages)
        if self.telemetry is not None:
            self.telemetry.pages_exhausted.inc()
        self._maybe_preempt(r, needed)

    def _advance_chunked(self, now: float) -> None:
        """Drive the in-progress chunked-prefill admission one device
        dispatch forward (one ``prefill_extend`` chunk, or the
        finish). Decode dispatch follows in the same tick, so chunks
        and decode waves strictly alternate."""
        if self._chunked is None:
            return
        if self._chunked_fresh:
            # chunk 0 was dispatched by _start_chunked THIS tick —
            # one chunk forward per tick, strictly
            self._chunked_fresh = False
            return
        ca, r = self._chunked
        rec = self.recorder
        try:
            res = self.engine.admit_chunked_step(ca)
        except Exception as e:
            self._chunked = None
            self._recover(self.clock(), cause="admit", detail=str(e),
                          affected=[r], batch_reqs=[r])
            return
        if res is None:
            self._chunked_chunks += 1
            if rec is not None:
                rec.record("prefill_chunk", r.request_id,
                           ca.next_chunk - 1, ca.chunks_total)
            if self.telemetry is not None:
                self.telemetry.chunked_chunks.inc()
            return
        # the finish landed: the request occupies its slot from here on
        # — exactly the bookkeeping one _admit_queued row gets
        self._chunked = None
        t_first = self.clock()
        vocab = self.engine.cfg.vocab_size
        if not 0 <= res.first_token < vocab:
            self._recover(t_first, cause="invalid_token",
                          detail="invalid first token from chunked "
                          "admission (NaN-poisoned prefill)",
                          affected=[r], batch_reqs=[r])
            return
        slot = ca.slot
        self._chunked_admissions += 1
        self._admitted_requests += 1
        self._admit_dispatches += 1
        if self.spans is not None:
            # one row through chunks_total forwards of a whole chunk
            # each, and the finish
            self._count_prefill(
                ca.p_len,
                ca.chunks_total * self.engine.engine_cfg.prefill_chunk,
                1, ca.chunks_total + 1)
        st = self._replay.get(r.request_id)
        act = _Active(r)
        act.suppress = 0 if st is None else len(st.tokens)
        act.first_token_time = t_first
        self.active[slot] = act
        if rec is not None:
            rec.record("admit", r.request_id, slot, res.bucket,
                       res.batch_size, res.group, 0)
        self.tenants.stats(r.tenant).admitted += 1
        tele = self.telemetry
        if tele is not None:
            tele.tenant(r.tenant)["admitted"].inc()
            tele.admitted.inc()
            tele.chunked_admissions.inc()
            tele.admit_dispatches.inc()
            if res.bucket in tele.bucket:
                tele.bucket[res.bucket].inc()
        if act.suppress < 1:
            self.ttft_stats.add(t_first - r.arrival_time)
            if self.slo is not None:
                self.slo.observe("ttft", t_first - r.arrival_time,
                                 r.tenant, now=t_first)
            if self._tuner is not None:
                self._tuner.observe_ttft(t_first - r.arrival_time)
            if self.spans is not None:
                self.spans.mark(r.request_id,
                                spans_mod.PHASE_FIRST_TOKEN)
            if tele is not None:
                tele.ttft.observe(t_first - r.arrival_time)
        reason = None
        if res.finished:
            reason = FINISH_EOS if res.hit_eos else FINISH_LENGTH
        self._ingest(slot, act, res.first_token, res.logprob, t_first,
                     device_done=res.finished, device_reason=reason)

    def _start_chunked(self, now: float) -> None:
        """Begin a chunked admission for the queue head when it
        qualifies: chunked prefill enabled, prompt longer than one
        chunk, no prefix-pool hit (a hit already skips the long
        forward), none already in progress, and a free slot + pages."""
        if (self._chunked is not None
                or not self.engine.chunked_prefill_enabled
                or not self._free or not self.queue):
            return
        r = self.queue[0]
        if not self.engine.chunked_for(len(r.prompt)) \
                or r.request_id in self._prefix_hits:
            return
        st = self._replay.get(r.request_id)
        if st is not None and now < st.not_before:
            return
        needed = self.engine.pages_needed(len(r.prompt), r.max_tokens)
        if not self.engine.can_admit_pages(len(r.prompt), r.max_tokens):
            self._note_pages_exhausted(r, needed)
            return
        self.queue.popleft()
        slot = self._free.pop()
        if r.constraint is not None:
            r.constraint.reset()
        if self.spans is not None:
            self.spans.mark(r.request_id, spans_mod.PHASE_PREFILL,
                            note=f"slot {slot} (chunked)")
        try:
            ca = self.engine.admit_chunked_start(
                self._admission_of(r, slot))
        except PagesExhausted as e:
            # a stale mapping race — requeue, the slot returns free
            self._free.append(slot)
            self.queue.appendleft(r)
            self._note_pages_exhausted(r, e.requested)
            return
        except Exception as e:
            self._free.append(slot)
            self._recover(self.clock(), cause="admit", detail=str(e),
                          affected=[r], batch_reqs=[r])
            return
        self._chunked = (ca, r)
        self._chunked_fresh = True
        self._chunked_chunks += 1
        if self.slo is not None and st is None:
            # the chunked path's queue wait lands when the request
            # leaves the queue (admission dispatch starts here)
            self.slo.observe("queue_wait", now - r.arrival_time,
                             r.tenant, now=now)
        if self.recorder is not None:
            self.recorder.record("prefill_chunk", r.request_id, 0,
                                 ca.chunks_total)
        if self.telemetry is not None:
            self.telemetry.chunked_chunks.inc()
            self.telemetry.queue_depth.set(len(self.queue))

    def _admit_eligible(self, r: Request, now: float) -> bool:
        """Whether a queued request may admit through the batched path
        THIS wave: its retry-backoff gate (if any) has opened, and it
        is not chunked-path-only (chunked-eligible prompts admit
        through the chunked path — one at a time, `_start_chunked`;
        batching one here would be exactly the monolithic
        long-prefill stall chunking exists to remove)."""
        st = self._replay.get(r.request_id)
        if st is not None and now < st.not_before:
            return False
        return not (self.engine.chunked_for(len(r.prompt))
                    and r.request_id not in self._prefix_hits)

    def _pop_eligible(self, now: float, n: int) -> List[Request]:
        """Pop up to ``n`` admissible queued requests, preserving
        queue order for the rest — a backing-off request must not
        block the head of the line.

        Pop ORDER is tenant-aware weighted-fair queueing
        (:mod:`apex_tpu.serving.tenancy`): each pick takes the
        head-of-line request of the backlogged tenant most behind its
        fair share (lowest served-tokens/weight deficit counter, aged
        by head-of-line wait so no tenant starves). Within a tenant
        order stays FIFO; with a single backlogged tenant every pick
        IS the first eligible request — the historical strict-FIFO
        scheduler, bit-identically."""
        book = self.tenants
        # ONE eligibility scan per wave (the historical single pass),
        # then n picks off the per-tenant head cursors — deficits do
        # not move between picks (tokens charge at emission), so
        # rescanning per pick would buy nothing but O(queue × n)
        by_tenant: Dict[str, List[Tuple[int, Request]]] = {}
        for idx, r in enumerate(self.queue):
            if self._admit_eligible(r, now):
                by_tenant.setdefault(r.tenant, []).append((idx, r))
        heads = {t: 0 for t in by_tenant}
        picked: List[Request] = []
        picked_idx: List[int] = []
        while len(picked) < n:
            live = {t: lst[heads[t]] for t, lst in by_tenant.items()
                    if heads[t] < len(lst)}
            if not live:
                break
            if len(live) == 1:
                t = next(iter(live))
            else:
                t = book.pick({
                    tt: max(now - (rr.arrival_time
                                   if rr.arrival_time is not None
                                   else now), 0.0)
                    for tt, (_, rr) in live.items()})
            idx, r = live[t]
            heads[t] += 1
            picked_idx.append(idx)
            picked.append(r)
        if picked_idx:
            drop = set(picked_idx)
            self.queue = collections.deque(
                r for i, r in enumerate(self.queue) if i not in drop)
        return picked

    def _admit_queued(self, now: float) -> None:
        # parked resumes first (their clients are waiting MID-stream),
        # then batched short admissions, chunked start last: the wave
        # of shorts must not queue behind chunk 0's forward (see
        # step()'s ordering note)
        if self._resume_q:
            self._admit_parked(now)
        self._admit_batches(now)
        self._start_chunked(now)

    def _chunked_head_pending(self) -> bool:
        """A chunked-eligible request heads the queue with none in
        progress — `_admit_batches` keeps one slot free for it (shorts
        admit first within a tick, but must not STARVE the long under
        sustained short traffic)."""
        if self._chunked is not None or not self.queue \
                or not self.engine.chunked_prefill_enabled:
            return False
        head = self.queue[0]
        return (self.engine.chunked_for(len(head.prompt))
                and head.request_id not in self._prefix_hits)

    def _admit_batches(self, now: float) -> None:
        while self.queue:
            reserve = 1 if self._chunked_head_pending() else 0
            if len(self._free) <= reserve:
                return
            n = min(len(self._free) - reserve, len(self.queue))
            if self.max_admit_batch is not None:
                n = min(n, self.max_admit_batch)
            reqs = self._pop_eligible(now, n)
            if not reqs:
                return  # queue gated on backoff / the chunked path
            if self.engine.paged:
                # allocator backpressure, FIFO-strict: admit the
                # prefix of the wave the free pages cover; the first
                # request that does not fit (and everything behind it)
                # stays queued until releases free pages
                free_p = self.engine.page_allocator.free_pages
                needed, cut, cut_need = 0, len(reqs), 0
                for idx, r in enumerate(reqs):
                    need = self._request_pages_needed(r)
                    if needed + need > free_p:
                        cut, cut_need = idx, need
                        break
                    needed += need
                if cut < len(reqs):
                    self.queue.extendleft(reversed(reqs[cut:]))
                    if cut == 0:
                        self._note_pages_exhausted(reqs[0], cut_need)
                        return
                    reqs = reqs[:cut]
            slots = [self._free.pop() for _ in range(len(reqs))]
            if self.spans is not None:
                for r, slot in zip(reqs, slots):
                    self.spans.mark(r.request_id, spans_mod.PHASE_PREFILL,
                                    note=f"slot {slot}")
            for r in reqs:
                # (re-)admission restarts the schema DFA from its
                # initial state — fault replay re-derives the stream
                # from the prompt, and the constraint must follow it
                if r.constraint is not None:
                    r.constraint.reset()
            try:
                with self._timed("engine.admit") as timed:
                    results = self.engine.admit_many([
                        self._admission_of(r, slot)
                        for r, slot in zip(reqs, slots)])
            except PagesExhausted:
                # backpressure raced the pre-flight check (a stale
                # mapping, a share) — requeue and wait, no fault; the
                # event records the HEAD's own need (the exception's
                # `requested` is the whole batch's total)
                self._free.extend(reversed(slots))
                self.queue.extendleft(reversed(reqs))
                self._note_pages_exhausted(
                    reqs[0], self._request_pages_needed(reqs[0]))
                return
            except Exception as e:  # device error escaping the admit
                self._recover(self.clock(), cause="admit", detail=str(e),
                              affected=list(reqs), batch_reqs=list(reqs))
                return
            t_admit, t_first = timed.start, timed.end
            # NaN-poisoned prefill: a garbage first token means the
            # admission's cache insert cannot be trusted — quarantine
            # before any event leaks, charging only the bad rows
            vocab = self.engine.cfg.vocab_size
            bad = [r for r, res in zip(reqs, results)
                   if not 0 <= res.first_token < vocab]
            if bad:
                self._recover(t_first, cause="invalid_token",
                              detail="invalid first token from admission "
                              "(NaN-poisoned prefill)",
                              affected=bad, batch_reqs=list(reqs))
                return
            n_groups = results[-1].group + 1
            self._admitted_requests += len(reqs)
            self._admit_dispatches += n_groups
            if self.spans is not None:
                # what the admission programs were given, against what
                # they ran at: rows x bucket is the padded batch
                hits = self._prefix_hits
                shared = [hits.get(r.request_id, (0, 0))[1] for r in reqs]
                self._count_prefill(
                    sum(len(r.prompt) for r in reqs) - sum(shared),
                    sum(res.bucket for res in results),
                    len(reqs), n_groups)
                if self.engine.prefix_pool_enabled:
                    self.spans.count("prefix.tokens_shared", sum(shared))
                    self.spans.count(
                        "prefix.tokens_prefilled",
                        sum(len(r.prompt) for r in reqs) - sum(shared))
                self._count_keys([p for r, n in zip(reqs, shared)
                                  for p in range(n, len(r.prompt))])
            tele = self.telemetry
            if tele is not None:
                tele.admit_dispatches.inc(n_groups)
                tele.queue_depth.set(len(self.queue))
            rows = list(zip(reqs, slots, results))
            rec = self.recorder
            for idx, (r, slot, res) in enumerate(rows):
                st = self._replay.get(r.request_id)
                act = _Active(r)
                act.suppress = 0 if st is None else len(st.tokens)
                act.first_token_time = t_first
                self.active[slot] = act
                self.tenants.stats(r.tenant).admitted += 1
                hit = self._prefix_hits.get(r.request_id)
                if rec is not None:
                    rec.record("admit", r.request_id, slot, res.bucket,
                               res.batch_size, res.group,
                               0 if hit is None else hit[1])
                if hit is not None and self.engine.paged:
                    # the hit mapped the prefix's pages copy-on-write
                    # — zero prefix bytes moved at admission
                    self._page_share_hits += 1
                    if rec is not None:
                        rec.record(
                            "page_share", r.request_id,
                            hit[1] // self.engine.engine_cfg.page_size)
                    if tele is not None:
                        tele.page_share_hits.inc()
                if tele is not None:
                    tele.admitted.inc()
                    tele.tenant(r.tenant)["admitted"].inc()
                    tele.admit_batch[res.batch_size].inc()
                    tele.bucket[res.bucket].inc()
                if act.suppress < 1:
                    # TTFT is "first token computed", recorded even
                    # when the stop matcher holds that token back from
                    # the wire; a replaying request's re-derived first
                    # token is not a first token
                    self.ttft_stats.add(t_first - r.arrival_time)
                    if self.slo is not None:
                        # queue wait is arrival → admission dispatch
                        # (the slice a router's predicted-TTFT models);
                        # TTFT adds the prefill on top
                        self.slo.observe(
                            "ttft", t_first - r.arrival_time,
                            r.tenant, now=t_first)
                        self.slo.observe(
                            "queue_wait", t_admit - r.arrival_time,
                            r.tenant, now=t_first)
                    if self._tuner is not None:
                        self._tuner.observe_ttft(
                            t_first - r.arrival_time)
                    if self.spans is not None:
                        self.spans.mark(r.request_id,
                                        spans_mod.PHASE_FIRST_TOKEN)
                    if tele is not None:
                        tele.ttft.observe(t_first - r.arrival_time)
                reason = None
                if res.finished:
                    reason = FINISH_EOS if res.hit_eos else FINISH_LENGTH
                if self._ingest(slot, act, res.first_token, res.logprob,
                                t_first, device_done=res.finished,
                                device_reason=reason) == _RECOVERED:
                    # a retire-seam fault rebuilt the engine mid-batch:
                    # rows not yet processed lost their slots — back to
                    # the queue's front (their events never emitted, so
                    # re-admission is a clean restart)
                    rest = [rr for rr, _, _ in rows[idx + 1:]]
                    self.queue.extendleft(reversed(rest))
                    if tele is not None:
                        tele.queue_depth.set(len(self.queue))
                    return

    def _release(self, slot: int, reason: str) -> None:
        act = self.active.pop(slot)
        self._free.append(slot)
        # paged: the slot's private pages return to the pool and its
        # table row redirects to the sink — this release is what frees
        # capacity for the backpressured queue head
        self.engine.free_slot(slot)
        now = self.clock()
        ttft = (None if act.first_token_time is None
                else act.first_token_time - act.request.arrival_time)
        st = self._replay.pop(act.request.request_id, None)
        tokens, lps = act.tokens, act.logprobs
        if st is not None and len(st.tokens) > len(tokens):
            # retired mid-replay: the pre-fault stream is longer than
            # what the replay re-derived — the completion must carry
            # everything the client was streamed
            tokens, lps = st.tokens, st.logprobs
        self._complete(act.request, tokens, reason, ttft=ttft, now=now,
                       logprobs=lps)

    def _complete(self, request: Request, tokens: List[int], reason: str,
                  *, ttft: Optional[float], now: float,
                  logprobs: Optional[List[float]] = None) -> None:
        self._prefix_hits.pop(request.request_id, None)
        arrival = request.arrival_time if request.arrival_time is not None \
            else now
        comp = Completion(request.request_id, list(tokens), reason,
                          ttft=ttft, latency=now - arrival,
                          logprobs=list(logprobs or []))
        self.completions[request.request_id] = comp
        if self.recorder is not None:
            self.recorder.record("finish", request.request_id, reason,
                                 len(tokens))
        self._journal_finish(request, tokens, logprobs, reason)
        rrec = self._req_records.pop(request.request_id, None)
        if rrec is not None:
            # the replayable record graduates to the bounded
            # completed-request ring with its final client stream
            rrec["status"] = "completed"
            rrec["finish_reason"] = reason
            rrec["emitted"] = list(tokens)
            self._req_done.append(rrec)
        if reason == FINISH_EOS and not tokens:
            # eos-terminal prompt: completes at submit, emits only the
            # finished event (no token)
            self.events.append(StreamEvent(
                request.request_id, None, True, reason))
        if self.slo is not None:
            self.slo.observe("e2e", comp.latency, request.tenant,
                             now=now)
        if self.telemetry is not None:
            self.telemetry.finished[reason].inc()
            self.telemetry.request_latency.observe(comp.latency)
        if self.spans is not None:
            self.spans.mark(request.request_id, spans_mod.PHASE_RETIRED,
                            note=reason)
        if self.metrics is not None:
            # no value for "no first token" — a -1.0 ttft sentinel
            # silently poisons any downstream mean/percentile, so the
            # key is simply absent for zero-token completions
            rec = {
                "completed": 1.0,
                "n_tokens": float(len(tokens)),
                "latency_s": comp.latency,
            }
            if ttft is not None:
                rec["ttft_s"] = ttft
            self.metrics.log(self._steps, rec)

    # -- reporting ---------------------------------------------------------

    def summary(self) -> Dict[str, float]:
        """Aggregate serving metrics: throughput + latency percentiles
        (the bench's one JSON line)."""
        elapsed = None
        if self._started is not None:
            elapsed = max(self.clock() - self._started, 1e-9)
        out = {
            "requests_completed": float(len(self.completions)),
            "tokens_emitted": float(self._tokens_emitted),
            "steps": float(self._steps),
            "admitted_requests": float(self._admitted_requests),
            # batched admission's amortisation, directly: requests
            # prefilled per compiled admission dispatch
            "admit_dispatches": float(self._admit_dispatches),
            "pipeline_depth": float(self.pipeline_depth),
            # resilience: recoveries + overload actions this run
            "retries": float(self._retries),
            "retry_exhausted": float(self._retry_exhausted),
            "rebuilds": float(self._rebuilds),
            "shed": float(self._shed),
            "watchdog_trips": float(self._watchdog_trips),
            # fleet: requests handed to the on_evict hook (0 without a
            # router)
            "evicted_requests": float(self._evicted_requests),
            "health_state": float(self.health.code),
            # black box: post-mortem bundles written (auto + manual)
            "bundles_written": float(len(self.bundles_written)),
            # KV-cache capacity: slot-cache device bytes (quantized
            # data + scales) and the prefix pool's admission savings
            "cache_bytes": float(self.engine.cache_bytes()),
            "prefix_hits": float(self._prefix_hit_count),
            "prefix_misses": float(self._prefix_miss_count),
            # multi-tenant serving: live tenant population + rate-limit
            # rejections (per-tenant detail via tenant_summary())
            "tenants_seen": float(len(self.tenants.tenants_seen)),
            "tenant_throttled": float(self._throttled),
        }
        if self.engine.adapter_pool_enabled:
            out["adapters_registered"] = float(
                self.engine.adapters_registered)
        if self.engine.paged:
            # paged-cache capacity: occupancy, CoW sharing, chunked
            # admissions, and backpressure waits this run
            ps = self.engine.page_stats()
            out["pages_total"] = ps["pages_total"]
            out["pages_in_use"] = ps["pages_in_use"]
            out["pages_shared"] = ps["pages_shared"]
            out["page_fragmentation"] = ps["fragmentation"]
            out["page_share_hits"] = float(self._page_share_hits)
            out["pages_exhausted_waits"] = float(
                self._pages_exhausted_waits)
            out["pages_swapped"] = ps["pages_swapped"]
            out["swap_bytes"] = ps["swap_bytes"]
        if self.engine.host_swap_enabled:
            # the oversubscription ledger: conversations parked now,
            # swap traffic, and how the scheduler resolved pressure
            out["parked_conversations"] = float(len(self._parked))
            out["pauses"] = float(self._pauses)
            out["preemptions"] = float(self._preemptions)
            out["swap_resumes"] = float(self._swap_resumes)
            out["recompute_resumes"] = float(self._recompute_resumes)
            out["swap_capacity_drops"] = float(
                self._swap_capacity_drops)
            ap = self.engine.adapter_paging_stats()
            if ap is not None:
                for k, v in ap.items():
                    out[f"adapter_{k}"] = float(v)
        if self.engine.chunked_prefill_enabled:
            out["chunked_admissions"] = float(self._chunked_admissions)
            out["chunked_chunks"] = float(self._chunked_chunks)
        if self.journal is not None:
            # the durability ledger: appended/synced volume, rotation/
            # compaction churn, and requests this scheduler was
            # recovered with (0 for a fresh start)
            for k, v in self.journal.stats().items():
                out[f"journal_{k}"] = v
            out["journal_recovered_requests"] = float(
                self._journal_recovered)
        tn = self._tuner
        if self._gate is not None or (tn is not None
                                      and "spec_k" in tn.knobs):
            # speculative decoding: per-wave accounting (gate-driven
            # or tuner-driven) + gate state when a gate owns the knob
            out["spec_chunks"] = float(self._spec_chunks)
            out["spec_drafted"] = float(self._spec_drafted)
            out["spec_accepted"] = float(self._spec_accepted)
            out["spec_accept_rate"] = (
                self._spec_accepted / self._spec_drafted
                if self._spec_drafted else 0.0)
        if self._gate is not None:
            out["spec_gate_state"] = self._gate.state()
            out["spec_acceptance_ewma"] = self._gate.accept_ewma
            out["spec_break_even"] = self._gate.break_even()
        if tn is not None:
            # the control plane: state, decision counts, and the
            # incumbent operating point it steered to
            out["tuner_state"] = tn.state()
            out["tuner_probes"] = float(tn.probes_total)
            out["tuner_switches"] = float(
                sum(tn.switch_counts.values()))
            for k, v in tn.incumbent.items():
                out[f"tuner_{k}"] = float(v)
        if elapsed:
            out["tokens_per_sec"] = self._tokens_emitted / elapsed
        if self._decode_time > 0:
            # the steady-state half of the TTFT-vs-decode split: tokens
            # emitted by decode chunks per second of (overlap-dedup'd)
            # wall time spent on them (admission/prefill — the TTFT
            # side — excluded)
            out["decode_tokens_per_sec"] = (
                self._decode_tokens / self._decode_time)
            out["decode_tokens"] = float(self._decode_tokens)
            out["decode_time_s"] = self._decode_time
        for name, stats in (("ttft", self.ttft_stats),
                            ("token_latency", self.token_latency_stats)):
            for k, v in stats.summary().items():
                out[f"{name}_{k}"] = v
        if self.slo is not None:
            # the SLO observatory's sketch-backed percentiles (full-run
            # streaming, not the LatencyStats window) + alert roll-up
            out.update(self.slo.summary())
            out["predicted_ttft_s"] = self.predicted_ttft_s()
        return out
