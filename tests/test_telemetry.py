"""apex_tpu.telemetry — registry / spans / recompile sentinel / http.

Headline (the engine-invariant acceptance): drive the serving Engine
through warmup, arm ``RecompileGuard``, run admit / decode-chunk /
retire across varied slots and sampling params, and assert
``compiles_total`` stays flat — then prove a deliberately shape-busting
call trips the guard. Plus: exposition round trips through a minimal
Prometheus parser scraped from a LIVE engine, the span timeline exports
as valid Chrome-trace JSON, and the whole layer imports with
torch/tensorboard purged (dependency-free by contract).
"""

import json
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import mesh as mx
from apex_tpu.models import gpt
from apex_tpu.serving import Request, SamplingParams
from apex_tpu.serving.engine import Engine, EngineConfig
from apex_tpu.serving.request import FINISH_REASONS
from apex_tpu.serving.scheduler import Scheduler
from apex_tpu.telemetry import (
    MetricsServer,
    RecompileError,
    Registry,
    Ring,
    SpanRecorder,
    parse_prometheus_text,
)
from apex_tpu.telemetry import recompile as rc
from apex_tpu.telemetry import spans as spans_mod
from apex_tpu.transformer.testing import standalone_gpt_config

VOCAB = 96


# --- ring ------------------------------------------------------------------


def test_ring_wraparound_and_order():
    r = Ring(3)
    assert len(r) == 0 and r.values() == [] and r.total == 0
    for i in range(5):
        r.append(i)
    assert len(r) == 3 and r.total == 5 and r.dropped == 2
    assert r.values() == [2, 3, 4]  # oldest first across the wrap
    # array() is for order-insensitive stats: same multiset, any order
    assert sorted(r.array()) == [2.0, 3.0, 4.0]
    r.clear()
    assert len(r) == 0 and r.total == 0
    with pytest.raises(ValueError):
        Ring(0)


# --- registry --------------------------------------------------------------


def test_registry_counter_gauge_labels():
    reg = Registry()
    c = reg.counter("requests_total", "all requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0
    lab = reg.counter("finished_total", labels=("reason",))
    lab.labels(reason="eos").inc()
    lab.labels(reason="eos").inc()
    lab.labels(reason="length").inc()
    assert lab.labels(reason="eos").value == 2.0
    with pytest.raises(ValueError, match="expected labels"):
        lab.labels(cause="eos")
    with pytest.raises(ValueError, match="declares labels"):
        lab.inc()
    # create-or-get is idempotent; a conflicting re-registration raises
    assert reg.counter("requests_total") is c
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("requests_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")


def test_registry_histogram_and_prom_roundtrip():
    reg = Registry()
    h = reg.histogram("ttft_seconds", "ttft", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 5.0):
        h.observe(v)
    reg.gauge("depth", "queue depth").set(2)
    reg.counter("finished_total", labels=("reason",)).labels(
        reason='we"ird\\').inc()
    # literal backslash followed by 'n' — the escape-adjacency trap
    reg.counter("paths_total", labels=("path",)).labels(
        path="C:\\new\nline").inc()
    text = reg.to_prometheus_text()
    parsed = parse_prometheus_text(text)
    assert parsed["ttft_seconds_bucket"][(("le", "0.01"),)] == 1.0
    assert parsed["ttft_seconds_bucket"][(("le", "0.1"),)] == 3.0
    assert parsed["ttft_seconds_bucket"][(("le", "1"),)] == 3.0
    assert parsed["ttft_seconds_bucket"][(("le", "+Inf"),)] == 4.0
    assert parsed["ttft_seconds_count"][()] == 4.0
    assert parsed["ttft_seconds_sum"][()] == pytest.approx(5.105)
    assert parsed["depth"][()] == 2.0
    # label-value escaping survives the round trip
    assert parsed["finished_total"][(("reason", 'we"ird\\'),)] == 1.0
    assert parsed["paths_total"][(("path", "C:\\new\nline"),)] == 1.0
    # JSON snapshot agrees
    d = reg.to_dict()
    json.dumps(d)  # must be JSON-ready
    assert d["ttft_seconds"]["samples"][0]["count"] == 4
    assert d["ttft_seconds"]["samples"][0]["buckets"]["+Inf"] == 4
    with pytest.raises(ValueError, match="sorted"):
        reg.histogram("bad_seconds", buckets=(1.0, 0.1))


# --- spans -----------------------------------------------------------------


def test_span_recorder_chrome_trace():
    t = [0.0]
    rec = SpanRecorder(capacity=64, clock=lambda: t[0])
    rec.mark("r0", spans_mod.PHASE_QUEUED)
    t[0] = 0.010
    rec.mark("r0", spans_mod.PHASE_PREFILL, note="slot 0")
    t[0] = 0.025
    rec.mark("r0", spans_mod.PHASE_FIRST_TOKEN)
    with rec.section("engine.step"):
        t[0] = 0.040
    rec.mark("r0", spans_mod.PHASE_DECODE)
    rec.mark("r1", spans_mod.PHASE_QUEUED)
    t[0] = 0.050
    rec.mark("r0", spans_mod.PHASE_RETIRED, note="eos")
    ct = rec.to_chrome_trace()
    json.dumps(ct)  # valid Chrome-trace JSON
    evs = ct["traceEvents"]
    xs = {(e["name"], e["ts"], e["dur"]) for e in evs if e["ph"] == "X"}
    # consecutive marks become complete events named by the open phase
    assert ("queued", 0.0, 10000.0) in xs
    assert ("prefill", 10000.0, 15000.0) in xs
    assert ("engine.step", 25000.0, 15000.0) in xs
    # distinct requests get distinct lanes
    lanes = {e["tid"] for e in evs
             if e["ph"] == "X" and e["pid"] == 1}
    r1_lane = [e["tid"] for e in evs if e["ph"] == "M"
               and e.get("args", {}).get("name") == "req r1"]
    assert r1_lane and r1_lane[0] not in lanes
    # terminal marks are instants
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert "retired" in instants and "queued" in instants
    s = rec.summary()
    assert s == {"events": 7, "events_total": 7, "events_dropped": 0,
                 "requests": 2}


def test_span_recorder_bounded():
    rec = SpanRecorder(capacity=4, clock=lambda: 0.0)
    for i in range(10):
        rec.mark(f"r{i}", "queued")
    s = rec.summary()
    assert s["events"] == 4 and s["events_dropped"] == 6
    json.dumps(rec.to_chrome_trace())


def test_clock_rows_only_while_an_annotate_hook_is_set():
    """A clock row pairs one read of each clock, at construction and at
    every ``anchor()`` — and nothing is read or written without a
    hook, so a recorder the profiler does not see costs what it did."""
    reads = []

    def clock():
        reads.append("rec")
        return 5.0 + len(reads)

    bare = SpanRecorder(clock=clock, profiler_clock=lambda: 1e9)
    bare.anchor()
    assert bare.events() == [] and reads == []
    hooked = SpanRecorder(clock=clock, annotate=lambda name: None,
                          profiler_clock=lambda: 1e9 + len(reads))
    hooked.anchor()
    assert hooked.events() == [(3, 6.0, "clock", 1e9 + 1, None),
                               (3, 7.0, "clock", 1e9 + 2, None)]
    # a hook set after construction (as the scheduler sets it) anchors
    # from its first anchor() on
    bare.annotate = lambda name: None
    bare.anchor()
    assert [e[0] for e in bare.events()] == [3]
    assert bare.summary()["requests"] == 0


def test_recorder_time_maps_between_anchors():
    at = spans_mod.on_profiler_clock([(10.0, 1000.0), (11.0, 1001.002),
                                      (11.0, 1001.002), (12.0, 1002.0)])
    assert at(10.5) == pytest.approx(1000.501)      # interpolated
    assert at(11.5) == pytest.approx(1001.501)
    assert at(9.0) == pytest.approx(999.0)          # the first offset
    assert at(13.0) == pytest.approx(1003.0)        # the last offset
    assert at(11.0) == pytest.approx(1001.002)


def test_chrome_trace_on_the_profilers_clock():
    """With clock rows the export renders none of them and stamps every
    event on the profiler's clock less ``origin_s``; without, as
    before, from the earliest row."""
    t = [0.0]
    rec = SpanRecorder(clock=lambda: t[0], annotate=lambda name: _Null(),
                       profiler_clock=lambda: 500.0 + 2 * t[0])
    rec.mark("r0", spans_mod.PHASE_QUEUED)
    t[0] = 1.0
    with rec.section("sched.step"):
        t[0] = 1.5
    rec.anchor()                    # at 1.5: the clocks drift 2x
    rec.mark("r0", spans_mod.PHASE_RETIRED)
    ct = rec.to_chrome_trace(origin_s=500.0)
    evs = ct["traceEvents"]
    assert not any(e.get("name") == "clock" for e in evs)
    x = {e["name"]: (e["ts"], e["dur"]) for e in evs if e["ph"] == "X"}
    assert x["sched.step"] == (pytest.approx(2e6), pytest.approx(1e6))
    assert x["queued"] == (pytest.approx(0.0), pytest.approx(3e6))
    plain = SpanRecorder(clock=lambda: t[0] + 7.0)
    plain.mark("r1", spans_mod.PHASE_QUEUED)
    assert [e["ts"] for e in plain.to_chrome_trace()["traceEvents"]
            if e["ph"] == "i"] == [0.0]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_chrome_trace_lines_up_with_a_capture(tmp_path):
    """On a real capture: each section the export stamps on the
    capture's axis starts where the profiler put its annotation, to
    well under 50 us (the hook is entered just before the recorder
    reads its clock)."""
    import glob
    import os
    import time

    from jax.profiler import ProfileData

    from apex_tpu import profiler

    rec = SpanRecorder(clock=time.monotonic, annotate=profiler.annotate)
    with profiler.trace(str(tmp_path)):
        for i in range(20):
            rec.anchor()
            with rec.section(f"sched.s{i}"):
                time.sleep(0.002)
    ct = rec.to_chrome_trace(origin_s=profiler.capture_start(str(tmp_path)))
    mine = {e["name"]: e["ts"] for e in ct["traceEvents"] if e["ph"] == "X"}
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    theirs = {ev.name[len("apex."):]: ev.start_ns * 1e-3
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("apex.sched.s")}
    assert set(theirs) == set(mine) and len(mine) == 20
    off = sorted(abs(mine[k] - theirs[k]) for k in mine)
    assert off[len(off) // 2] < 50.0, off


# --- recompile sentinel ----------------------------------------------------


def test_recompile_sentinel_counts_and_guard_trip():
    reg = Registry()
    sent = rc.RecompileSentinel(registry=reg).install()
    try:
        f = jax.jit(lambda x: x * 3 + 1)
        before = sent.compiles_total()
        f(jnp.ones((4,)))  # first call: an executable materialises
        after = sent.compiles_total()
        assert after["backend_compiles"] > before["backend_compiles"]
        sent.track("f", f)
        # steady state: repeat calls are in-memory cache hits — silent
        with sent.guard() as g:
            f(jnp.ones((4,)))
            assert g.check() == {} and not g.tripped
        # a new shape recompiles: alarm + raise, attributed to "f"
        with pytest.raises(RecompileError, match="trace-stability"):
            with sent.guard() as g:
                f(jnp.ones((9,)))
        assert g.alarms and g.tripped
        assert g.delta().get("tracked", {}).get("f") == 1
        assert reg.counter("recompile_alarms_total").value >= 1
        assert reg.counter("jax_compiles_total").value >= 2
        # raise_on_recompile=False: report, don't raise
        with sent.guard(raise_on_recompile=False) as g:
            f(jnp.ones((17,)))
        assert g.tripped and g.check()["backend_compiles"] >= 1
        # concurrent guards: one compile = ONE observed breach on the
        # shared alarm counter (each guard still records it locally)
        alarms_before = reg.counter("recompile_alarms_total").value
        with sent.guard(raise_on_recompile=False) as g1:
            with sent.guard(raise_on_recompile=False) as g2:
                f(jnp.ones((23,)))
        # every armed guard saw the same events; the shared counter
        # advanced once per EVENT, not once per (event, guard) pair
        # (note one host call can legitimately fire several compile
        # events — e.g. jnp.ones of a fresh shape compiles its own
        # fill program before f does)
        assert g1.alarms and len(g1.alarms) == len(g2.alarms)
        assert reg.counter("recompile_alarms_total").value == \
            alarms_before + len(g1.alarms)
    finally:
        sent.uninstall()


def test_sentinel_uninstall_releases_listener():
    """install/uninstall is listener-neutral — engines created in a
    loop must not grow jax.monitoring's listener list. All live
    sentinels share ONE refcounted hub listener: a second sentinel adds
    no registration, and the LAST uninstall releases the one there is —
    pinned here so N engine replicas hold exactly one listener."""
    from jax._src.monitoring import get_event_duration_listeners as get

    n0 = len(get())
    sent = rc.RecompileSentinel().install()
    assert len(get()) == n0 + 1
    sent.install()  # idempotent: no second registration
    assert len(get()) == n0 + 1
    # a SECOND sentinel shares the hub's one listener (refcount), and
    # releasing either order leaves the other's delivery intact
    sent2 = rc.RecompileSentinel().install()
    assert len(get()) == n0 + 1
    sent.uninstall()
    assert len(get()) == n0 + 1  # sent2 still holds the hub
    sent2.uninstall()
    assert len(get()) == n0
    sent.uninstall()  # idempotent


def test_register_monitoring_listeners_round_trip():
    """The one seam onto jax.monitoring: both listeners receive the
    installed runtime's compile events, and the returned callable
    releases exactly the pair it registered."""
    from jax._src.monitoring import (
        get_event_duration_listeners,
        get_event_listeners,
    )

    from apex_tpu import _compat

    points, durations = [], []
    on_event = lambda name, **kw: points.append(name)
    on_duration = lambda name, secs, **kw: durations.append(name)
    n_ev = len(get_event_listeners())
    n_dur = len(get_event_duration_listeners())
    unregister = _compat.register_monitoring_listeners(on_event,
                                                       on_duration)
    try:
        assert len(get_event_listeners()) == n_ev + 1
        assert len(get_event_duration_listeners()) == n_dur + 1
        jax.jit(lambda x: x * 5 - 3)(jnp.ones((11,)))
        assert rc.BACKEND_COMPILE_EVENT in durations
    finally:
        unregister()
    assert on_event not in get_event_listeners()
    assert on_duration not in get_event_duration_listeners()
    assert len(get_event_listeners()) == n_ev
    n_seen = len(durations)
    jax.jit(lambda x: x * 7 - 1)(jnp.ones((13,)))
    assert len(durations) == n_seen


# --- the engine acceptance: warmup → guard → flat --------------------------


def _cfg(**overrides):
    base = dict(vocab_size=VOCAB, seq_len=64)
    base.update(overrides)
    return standalone_gpt_config(**base)


def _varied_requests(n, *, seed0, eos=None):
    """Greedy and sampled lanes, varied prompt lengths / budgets /
    temperatures / top-k / top-p — the admission-diversity sweep.
    Prompt lengths span 1..10, so admissions land in BOTH prefill
    buckets of the mpl=10 fixture engine (8 and 10)."""
    reqs = []
    for i in range(n):
        p_len = 1 + (7 * i + 2) % 10
        prompt = [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(seed0 + i), (p_len,), 0, VOCAB)]
        if i % 2:
            sp = SamplingParams(temperature=0.7 + 0.2 * (i % 3),
                                top_k=(0, 5, 9)[i % 3],
                                top_p=(1.0, 0.9, 0.85)[i % 3],
                                seed=seed0 + i)
        else:
            sp = SamplingParams()
        reqs.append(Request(f"q{seed0}_{i}", prompt,
                            max_tokens=3 + i % 5, sampling=sp,
                            eos_token_id=eos))
    return reqs


@pytest.fixture(scope="module")
def served_engine(devices8):
    """One warmed engine (chunked decode, two prefill buckets, two
    admission batch sizes) + its recompile sentinel, shared by the
    guard and live-scrape tests. ``Engine.warmup()`` replaces the old
    hand-rolled scheduler warm run — it compiles every program
    (init/step/retire + all four (bucket, k) admission variants) plus
    the seeded-admission host path."""
    cfg = _cfg()
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=2, max_prompt_len=10, max_seq_len=24,
                              decode_chunk=8))
    registry = Registry()
    eng.recompile_sentinel(registry=registry)
    eng.warmup()  # apex: noqa[TIER1-COST]: shared warmed engine for the live /metrics e2e scrapes; warm-cache ~s
    yield cfg, params, mesh, eng, registry
    eng.close()  # release the process-wide monitoring listener


def test_engine_recompile_guard_stays_flat(served_engine):
    """The acceptance pin: after ``Engine.warmup()``, a full serve
    cycle — admissions through EVERY prefill bucket and admission batch
    size, pipelined chunked decode, deadline retire, varied sampling
    params — runs inside an armed RecompileGuard without a single
    compilation; a shape-busting call trips the same guard."""
    cfg, params, mesh, eng, registry = served_engine
    sent = eng.recompile_sentinel()
    sizes0 = eng.compiled_cache_sizes()
    assert set(sizes0.values()) == {1}, sizes0  # warmup compiled ALL
    now = [0.0]
    # build the request sets OUTSIDE the guard: their jax.random prompt
    # synthesis compiles for fresh prompt lengths, which is exactly the
    # kind of host-side compile the guard exists to catch. Four phases
    # steer admissions through every (bucket, k) variant: a short pair
    # (k=2, bucket 8), a pair with one long prompt (k=2, bucket 10),
    # then staggered singles long and short (k=1 at both buckets).
    def _mk(rid, p_len, i):
        prompt = [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(3000 + i), (p_len,), 0, VOCAB)]
        sp = (SamplingParams(temperature=0.8 + 0.1 * (i % 3),
                             top_k=(0, 5, 9)[i % 3], seed=3000 + i)
              if i % 2 else SamplingParams())
        return Request(rid, prompt, max_tokens=3 + i % 4, sampling=sp,
                       eos_token_id=13)

    phases = [[_mk("ga", 3, 0), _mk("gb", 8, 1)],     # k=2, bucket 8
              [_mk("gc", 10, 2), _mk("gd", 5, 3)],    # k=2, bucket 10
              [_mk("ge", 9, 4)],                      # k=1, bucket 10
              [_mk("gf", 2, 5)]]                      # k=1, bucket 8
    with eng.recompile_guard() as g:
        sched = Scheduler(eng, clock=lambda: now[0], pipeline_depth=2)
        seen = set()
        for phase in phases:
            for r in phase:
                sched.submit(r)
            sched.step()
            now[0] += 1.0
            # deadline-retire one live slot mid-flight (a chunk is in
            # flight at depth 2), then drain the phase
            if len(seen) == 0 and sched.active:
                slot = next(iter(sched.active))
                sched.active[slot].request.deadline = now[0] - 0.5
            sched.run_until_idle()
            seen |= set(sched.completions)
        assert len(sched.completions) == 6
        assert g.check() == {}  # flat mid-flight, by construction
    assert not g.tripped
    # compiles_total flat: per-program jit caches did not grow
    totals = sent.compiles_total()
    # the step program tracks per decode-chunk variant (step_c{chunk}
    # — the self-tuning ladder's naming; a single-rung engine has one)
    assert totals["tracked"] == {
        "init": 1, "step_c8": 1, "retire": 1,
        "admit_p8_k1": 1, "admit_p8_k2": 1,
        "admit_p10_k1": 1, "admit_p10_k2": 1}
    assert eng.compiled_cache_sizes() == sizes0
    # the same guard trips on a deliberately shape-busting call
    with pytest.raises(RecompileError, match="RecompileGuard"):
        with eng.recompile_guard():
            jax.jit(lambda x: x * 2.0)(np.arange(7.0))
    assert registry.counter("recompile_alarms_total").value >= 1
    # re-passing the ALREADY-WIRED registry is fine (the natural
    # re-arm pattern)...
    assert eng.recompile_sentinel(registry=registry) is sent
    # ...but wiring a DIFFERENT registry after the fact is a loud
    # error, not silently-absent metrics
    with pytest.raises(ValueError, match="FIRST"):
        eng.recompile_sentinel(registry=Registry())


# --- live /metrics endpoint over a serving engine --------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode("utf-8")


def test_metrics_endpoint_live_engine(served_engine):
    """End-to-end smoke over the PIPELINED loop: scrape /metrics from a
    LIVE engine mid-batch (with a decode chunk in flight), round-trip
    the text through the minimal parser, assert the admission-batch /
    bucket / in-flight instrumentation is present and consistent with
    the scheduler's own summary, check /healthz and /vars, and validate
    the span export as Chrome-trace JSON."""
    cfg, params, mesh, eng, _ = served_engine
    registry = Registry()
    spans = SpanRecorder()
    sched = Scheduler(eng, registry=registry, spans=spans,
                      pipeline_depth=2)
    server = MetricsServer(registry, spans=spans,
                           sentinel=eng.recompile_sentinel()).start()
    try:
        # budgets of 12 outlive a decode_chunk=8 dispatch, so slots are
        # observably live at the mid-flight scrape
        for r in _varied_requests(4, seed0=4000):
            sched.submit(Request(r.request_id, r.prompt, max_tokens=12,
                                 sampling=r.sampling))
        sched.step()  # both slots admitted + one chunk; 2 still queued
        status, mid = _get(server.url + "/metrics")
        assert status == 200
        p = parse_prometheus_text(mid)
        assert p["serving_active_slots"][()] >= 1.0
        assert p["serving_requests_admitted_total"][()] >= 2.0
        assert p["serving_slots_total"][()] == 2.0
        # at depth 2 the first tick's chunk is still in flight when the
        # tick returns — the pipeline gauge shows it
        assert p["serving_inflight_chunks"][()] == 1.0
        sched.run_until_idle()
        _, done = _get(server.url + "/metrics")
        p = parse_prometheus_text(done)
        by_reason = {dict(k)["reason"]: v for k, v in
                     p["serving_requests_finished_total"].items()}
        assert set(by_reason) == set(FINISH_REASONS)  # zeros present
        assert sum(by_reason.values()) == 4.0
        assert p["serving_queue_depth"][()] == 0.0
        assert p["serving_inflight_chunks"][()] == 0.0  # drained
        assert p["serving_ttft_seconds_count"][()] == 4.0
        assert p["serving_token_latency_seconds_count"][()] == \
            p["serving_tokens_emitted_total"][()] - 4.0
        # admission instrumentation is consistent with the scheduler's
        # own summary: every admitted request is counted exactly once
        # by batch size and once by bucket, and the dispatch counter
        # matches the summary's amortisation number
        s = sched.summary()
        admitted = p["serving_requests_admitted_total"][()]
        assert admitted == s["admitted_requests"] == 4.0
        by_size = {dict(k)["size"]: v for k, v in
                   p["serving_admit_batch_requests_total"].items()}
        assert set(by_size) == {str(k) for k in eng.admit_batch_sizes}
        assert sum(by_size.values()) == admitted
        by_bucket = {dict(k)["bucket"]: v for k, v in
                     p["serving_prefill_bucket_requests_total"].items()}
        assert set(by_bucket) == {str(b) for b in eng.prompt_buckets}
        assert sum(by_bucket.values()) == admitted
        assert p["serving_admit_dispatches_total"][()] == \
            s["admit_dispatches"] > 0
        assert p["serving_tokens_emitted_total"][()] == \
            s["tokens_emitted"]
        status, health = _get(server.url + "/healthz")
        assert status == 200 and health == "ok\n"
        status, vars_body = _get(server.url + "/vars")
        v = json.loads(vars_body)
        assert v["spans"]["requests"] == 4
        assert v["recompile"]["tracked"]["step_c8"] == 1
        assert v["metrics"]["serving_tokens_emitted_total"][
            "samples"][0]["value"] >= 4.0
        status, _ = _get(server.url + "/metrics?from=test")
        assert status == 200
        with pytest.raises(urllib.error.HTTPError):
            _get(server.url + "/nope")
    finally:
        server.stop()
    # span export: valid Chrome trace with the full phase vocabulary,
    # including the pipelined loop's dispatch-vs-fetch section split
    ct = spans.to_chrome_trace()
    json.loads(json.dumps(ct))
    names = {e["name"] for e in ct["traceEvents"]
             if e["ph"] in ("X", "i")}
    assert {"queued", "prefill", "first_token", "decode", "retired",
            "engine.dispatch", "engine.fetch", "engine.admit"} <= names
    for e in ct["traceEvents"]:
        if e["ph"] == "X":
            assert e["ts"] >= 0.0 and e["dur"] >= 0.0


# --- dependency-free contract ----------------------------------------------


def test_telemetry_imports_without_torch_tensorboard(tmp_path):
    """The layer must import with torch/tensorboard purged AND blocked
    — run in a subprocess with an import hook that fails either import,
    proving no telemetry module (or its transitive imports) touches
    them."""
    code = """
import sys

BLOCKED = ("torch", "tensorboard")


class _Blocker:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] in BLOCKED:
            raise ImportError(f"blocked by test: {name}")
        return None


for mod in list(sys.modules):
    if mod.split(".")[0] in BLOCKED:
        del sys.modules[mod]
sys.meta_path.insert(0, _Blocker())

import apex_tpu.telemetry as t
import apex_tpu.telemetry.ring
import apex_tpu.telemetry.registry
import apex_tpu.telemetry.spans
import apex_tpu.telemetry.http
import apex_tpu.telemetry.recompile
import apex_tpu.telemetry.flightrec
import apex_tpu.telemetry.replay

r = t.Registry()
r.counter("x_total").inc()
assert "x_total 1" in r.to_prometheus_text()
assert not any(m.split(".")[0] in BLOCKED for m in sys.modules)
print("DEP_FREE_OK")
"""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "DEP_FREE_OK" in out.stdout
