"""apex_tpu.telemetry.flightrec + replay — the serving black box.

Headline oracle: a seeded chaos soak auto-dumps a post-mortem bundle
on its first fault, and ``python -m apex_tpu.telemetry.replay``
rebuilds the whole run from that bundle and reproduces every
interrupted request's emitted stream BIT-identically — with the fault
plan re-armed AND replaying clean (per-request determinism means the
streams cannot depend on where faults land). The ``--report`` path is
pinned stdlib-only in a jax/numpy-purged subprocess, the recorder ring
is pinned on wraparound/drop accounting, bundles are pinned atomic +
immutable, the ``/debug`` endpoints are scraped live (and pinned
absent without a recorder), the recorder keeps an armed recompile
guard flat, and ``Engine.close()`` is pinned idempotent/re-entrant
(the double-release regression)."""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import jax
import pytest

from apex_tpu import mesh as mx
from apex_tpu.models import gpt
from apex_tpu.serving import Request, SamplingParams
from apex_tpu.serving.engine import Engine, EngineConfig
from apex_tpu.serving.resilience import FaultPlan, ResilienceConfig
from apex_tpu.serving.scheduler import Scheduler
from apex_tpu.telemetry import MetricsServer, Registry
from apex_tpu.telemetry.flightrec import (
    EVENT_FIELDS,
    FlightRecorder,
    read_bundle,
    write_bundle,
)
from apex_tpu.telemetry.replay import render_report, replay_bundle
from apex_tpu.transformer.testing import standalone_gpt_config

VOCAB = 96


@pytest.fixture(scope="module")
def model(devices8):
    cfg = standalone_gpt_config(vocab_size=VOCAB, seq_len=64)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    mesh = mx.build_mesh(tp=1, devices=devices8[:1])
    return cfg, params, mesh


def _reqs(n, *, seed0=9000, max_tokens=5):
    out = []
    for i in range(n):
        p_len = 2 + (3 * i) % 6
        prompt = [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(seed0 + i), (p_len,), 0, VOCAB)]
        sp = (SamplingParams(temperature=0.9, top_k=7, seed=seed0 + i)
              if i % 2 else SamplingParams())
        out.append(Request(f"b{i}", prompt, max_tokens=max_tokens,
                           sampling=sp))
    return out


@pytest.fixture(scope="module")
def chaos_bundle(model, tmp_path_factory):
    """ONE seeded chaos soak shared by the round-trip tests: a
    FaultPlan.random soak whose first fault auto-dumps a bundle
    mid-flight (interrupted requests recorded with partial emitted
    prefixes), plus the engine/scheduler that produced it."""
    cfg, params, mesh = model
    # seed chosen so the seeded plan fires error/nan faults inside this
    # short trace (pinned below — a plan that never fires would turn
    # the round-trip test into a no-op)
    plan = FaultPlan.random(5, 3, max_index=8, slots=2)
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=2, max_prompt_len=8, max_seq_len=24,
                              decode_chunk=2), fault_plan=plan)
    rec = FlightRecorder()
    bundle_dir = str(tmp_path_factory.mktemp("bundles"))
    sched = Scheduler(
        eng, pipeline_depth=2, recorder=rec, bundle_dir=bundle_dir,
        bundle_meta={"params": {"init_seed": 0}},
        resilience=ResilienceConfig(backoff_base_s=0.001, max_retries=4))
    reqs = _reqs(8)
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle()
    assert [s for s in plan.injected if s.kind in ("error", "nan")], \
        "seed produced no hard fault — pick another seed"
    assert sched.bundles_written, "no auto-dumped bundle"
    yield sched.bundles_written[0], eng, sched, rec, reqs
    # the guard-flat test arms a recompile guard on this engine, which
    # installs its sentinel: close at module teardown so the listener
    # never leaks into later modules (the engines-in-a-loop footgun)
    eng.close()


# --- recorder unit coverage (host-only, fast) -------------------------------


def test_ring_wraparound_and_drop_accounting():
    clock_t = [0.0]

    def clock():
        clock_t[0] += 1.0
        return clock_t[0]

    rec = FlightRecorder(capacity=8, clock=clock)
    for i in range(20):
        rec.record("finish", f"r{i}", "length", i)
    evs = rec.events()
    assert len(evs) == 8
    # wraparound keeps the NEWEST events, seq stays monotonic with no
    # reordering across the wrap
    assert [e[0] for e in evs] == list(range(13, 21))
    assert rec.seq == 20
    s = rec.summary()
    assert s["events_total"] == 20 and s["events_dropped"] == 12
    assert s["events"] == 8 and s["last_seq"] == 20
    # tail(n) returns the n newest as dicts with NAMED fields
    tail = rec.tail(3)
    assert [t["seq"] for t in tail] == [18, 19, 20]
    assert tail[-1] == {"seq": 20, "t": 20.0, "event": "finish",
                        "request_id": "r19", "reason": "length",
                        "n_tokens": 19}
    # unknown names survive as raw args (a post-mortem never loses
    # data to a rename)
    rec.record("not_a_known_event", 1, 2)
    assert rec.tail(1)[0]["args"] == [1, 2]
    rec.clear()
    assert rec.seq == 0 and rec.summary()["events_total"] == 0


def test_bundle_write_atomic_and_immutable(tmp_path):
    path = str(tmp_path / "b0")
    out = write_bundle(path, {
        "manifest.json": {"cause": "t", "n": 1},
        "events.jsonl": [{"seq": 1}, {"seq": 2}],
    })
    assert out == path
    # no temp droppings next to the bundle
    assert sorted(p.name for p in tmp_path.iterdir()) == ["b0"]
    back = read_bundle(path)
    assert back["manifest.json"]["cause"] == "t"
    assert back["events.jsonl"] == [{"seq": 1}, {"seq": 2}]
    # bundles are immutable evidence
    with pytest.raises(FileExistsError):
        write_bundle(path, {"manifest.json": {}})
    # a directory that is not a bundle is a clear error
    os.makedirs(str(tmp_path / "junk"))
    with pytest.raises(ValueError, match="manifest"):
        read_bundle(str(tmp_path / "junk"))
    with pytest.raises(FileNotFoundError):
        read_bundle(str(tmp_path / "missing"))


# --- the chaos round trip ---------------------------------------------------


def test_chaos_bundle_contents_and_decision_log(chaos_bundle):
    bundle_path, eng, sched, rec, reqs = chaos_bundle
    b = read_bundle(bundle_path)
    man = b["manifest.json"]
    assert man["cause"].startswith("fault-")
    assert man["meta"] == {"params": {"init_seed": 0}}
    assert man["flightrec"]["events_total"] > 0
    # every recorded event name is in the vocabulary (the runtime
    # sibling of the EVENT-DRIFT lint rule)
    names = {e[2] for e in rec.events()}
    assert names <= set(EVENT_FIELDS), names - set(EVENT_FIELDS)
    # the load-bearing decisions all made it into the log
    for must in ("submit", "admit", "dispatch", "fetch", "inject",
                 "fault", "rebuild", "replay", "health", "finish",
                 "bundle"):
        assert must in names or must == "bundle", must
    # seq strictly increasing
    seqs = [e[0] for e in rec.events()]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # the bundle's event log names injections AND detections
    ev_names = {e["event"] for e in b["events.jsonl"]}
    assert {"inject", "fault", "rebuild"} <= ev_names
    # request records: every submitted request, in submit order, with
    # its replayable sampling params
    rows = b["requests.jsonl"]
    assert [r["request_id"] for r in rows] == [r.request_id
                                               for r in reqs]
    assert all(r["status"] in ("completed", "active", "queued")
               for r in rows)
    # fault plan round-trips with its firing record
    assert len(b["fault_plan.json"]["specs"]) == 3
    assert b["fault_plan.json"]["injected"]
    # config carries what replay needs
    assert b["config.json"]["engine"]["model"]["vocab_size"] == VOCAB
    assert b["config.json"]["scheduler"]["pipeline_depth"] == 2


def test_chaos_bundle_replay_bit_identical(chaos_bundle):
    bundle_path, _, sched, _, reqs = chaos_bundle
    # with the recorded fault plan re-armed: the incident replays, and
    # every stream still reproduces its recorded prefix exactly
    out = replay_bundle(bundle_path, verbose=False)
    assert out["mismatches"] == [], out["mismatches"]
    assert out["replayed"] == len(reqs) and not out["skipped"]
    # and clean (--no-faults): per-request determinism means streams
    # cannot depend on where faults landed — every COMPLETED request
    # must also match the live scheduler's final completions exactly
    out2 = replay_bundle(bundle_path, no_faults=True, verbose=False)
    assert out2["mismatches"] == [] and out2["faults_reinjected"] == 0
    assert out2["matched"] == out2["replayed"] == len(reqs)


def test_report_runs_with_jax_purged(chaos_bundle):
    """``--report`` must need NOTHING beyond the stdlib: render the
    incident timeline in a subprocess with jax/numpy/scipy purged from
    sys.modules and blocked from re-import."""
    bundle_path, _, _, _, _ = chaos_bundle
    code = f'''
import sys

BLOCKED = ("jax", "jaxlib", "numpy", "scipy", "torch", "tensorboard")


class _Blocker:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] in BLOCKED:
            raise ImportError(f"blocked by test: {{name}}")
        return None


for mod in list(sys.modules):
    if mod.split(".")[0] in BLOCKED:
        del sys.modules[mod]
sys.meta_path.insert(0, _Blocker())

from apex_tpu.telemetry.replay import main
rc = main(["{bundle_path}", "--report"])
assert rc == 0
assert not any(m.split(".")[0] in BLOCKED for m in sys.modules)
print("REPORT_DEP_FREE_OK")
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "REPORT_DEP_FREE_OK" in out.stdout
    # and in-process: the report names the cause, the timeline, and
    # every request
    text = render_report(read_bundle(bundle_path))
    assert "post-mortem bundle" in text and "timeline" in text
    assert "FAULT" in text and "b0" in text


def _soak(eng, bundle_dir):
    sched = Scheduler(
        eng, pipeline_depth=2, recorder=FlightRecorder(),
        bundle_dir=bundle_dir,
        bundle_meta={"params": {"init_seed": 0}},
        resilience=ResilienceConfig(backoff_base_s=0.001,
                                    max_retries=4))
    for r in _reqs(8):
        sched.submit(r)
    sched.run_until_idle()
    return sched


def test_recorder_keeps_recompile_guard_flat(chaos_bundle):
    """The black box must be trace-invisible: once a soak has compiled
    every program its tick sequence uses, an IDENTICAL soak — recorder
    on, bundle dumped mid-guard — must not compile anything. (A warm
    pass runs first so the armed rerun repeats a fully-compiled tick
    sequence; the engine never calls ``warmup()`` here, exactly like a
    service that lazily compiled its way to steady state.)"""
    bundle_path, eng, _, _, _ = chaos_bundle
    bundle_dir = os.path.dirname(bundle_path)
    eng.fault_plan.reset()
    warm = _soak(eng, bundle_dir)  # compiles anything the fixture missed
    eng.fault_plan.reset()
    with eng.recompile_guard():
        sched2 = _soak(eng, bundle_dir)
        sched2.dump_bundle("guard-flat-probe")
    # parity rides along: same trace, same (reset) plan — completions
    # must match the warm run's bit-for-bit
    for rid, comp in warm.completions.items():
        assert sched2.completions[rid].tokens == comp.tokens, rid


# --- /debug endpoints (host-only) -------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_debug_endpoints_and_vars(tmp_path):
    rec = FlightRecorder()
    for i in range(10):
        rec.record("finish", f"r{i}", "eos", i)
    dumped = []

    def trigger():
        p = str(tmp_path / f"t{len(dumped)}")
        write_bundle(p, {"manifest.json": {"cause": "http"}})
        dumped.append(p)
        return p

    server = MetricsServer(Registry(), recorder=rec,
                           bundle_trigger=trigger).start()
    try:
        status, body = _get(f"{server.url}/debug/events?n=3")
        assert status == 200
        evs = json.loads(body)
        assert [e["seq"] for e in evs] == [8, 9, 10]
        assert evs[0]["event"] == "finish" and evs[0]["reason"] == "eos"
        status, body = _get(f"{server.url}/vars")
        v = json.loads(body)
        assert v["flightrec"]["events_total"] == 10
        status, body = _get(f"{server.url}/debug/bundle")
        assert status == 200
        assert json.loads(body)["bundle"] == dumped[0]
        assert os.path.isdir(dumped[0])
    finally:
        server.stop()
    # no-recorder behavior unchanged: the endpoints 404 and /vars
    # carries no flightrec block
    server = MetricsServer(Registry()).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{server.url}/debug/events")
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{server.url}/debug/bundle")
        assert ei.value.code == 404
        _, body = _get(f"{server.url}/vars")
        assert "flightrec" not in json.loads(body)
    finally:
        server.stop()


def test_recorder_less_scheduler_clears_fault_observer(model):
    """The NEWEST scheduler owns ``FaultPlan.on_inject``: a
    recorder-less scheduler over a shared engine (the bench's on/off
    A/B, a service rebuilding on config reload) must clear a dead
    predecessor's wiring, or its injections keep landing in the old
    recorder's ring on the old scheduler's clock."""
    cfg, params, mesh = model
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=1, max_prompt_len=8,
                              max_seq_len=16),
                 fault_plan=FaultPlan.random(1, 1))
    Scheduler(eng, recorder=FlightRecorder())
    assert eng.fault_plan.on_inject is not None
    Scheduler(eng)
    assert eng.fault_plan.on_inject is None


# --- Engine.close() idempotence (the double-release regression) -------------


def test_engine_close_idempotent_and_reentrant(model, tmp_path):
    cfg, params, mesh = model
    eng = Engine(cfg, params, mesh,
                 EngineConfig(slots=1, max_prompt_len=8,
                              max_seq_len=16))
    sched = Scheduler(eng, bundle_dir=str(tmp_path),
                      recorder=FlightRecorder())
    sent1 = eng.recompile_sentinel()
    # a bundle-triggered dump reads engine state (describe, compiled
    # cache sizes, the sentinel snapshot) — it must never re-install
    # or consume the listener
    sched.dump_bundle("before-close")
    eng.close()
    eng.close()  # idempotent: second close is a no-op, not an error
    assert eng._sentinel is None
    sent1.uninstall()  # and a direct re-uninstall is harmless too
    # dumping after close still works (manifest simply drops the
    # sentinel block), and closing again after THAT dump is fine
    p = sched.dump_bundle("after-close")
    assert "recompile" not in read_bundle(p)["manifest.json"]
    eng.close()
    # the releases above must not have detached anyone else's
    # listener: a fresh sentinel still observes compiles
    eng2 = Engine(cfg, params, mesh,
                  EngineConfig(slots=1, max_prompt_len=8,
                               max_seq_len=16))
    sent2 = eng2.recompile_sentinel()
    before = sent2.compiles_total()["backend_compiles"]
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones((4,)))
    assert sent2.compiles_total()["backend_compiles"] > before
    eng2.close()
