"""contrib multihead_attn / conv fusions / groupbn + profiler subsystem.

Oracle pattern (SURVEY.md §4): fused block vs unfused jnp reference at
fp32, per-dtype tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import profiler
from apex_tpu.contrib import (
    conv_bias_relu,
    encdec_attn,
    group_batch_norm_nhwc,
    init_encdec_attn,
    init_self_attn,
    self_attn,
)
from apex_tpu.contrib.conv_bias_relu import conv_frozen_scale_bias_relu


def _ref_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / d ** 0.5
    if causal:
        sq = q.shape[2]
        mask = jnp.tril(jnp.ones((sq, sq), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def _ref_self_attn(params, x, num_heads, causal=False):
    qkv = jnp.einsum("sbh,hk->sbk", x, params["qkv"]["kernel"])
    qkv = qkv + params["qkv"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        s, b, h = t.shape
        return jnp.transpose(
            t.reshape(s, b, num_heads, h // num_heads), (1, 2, 0, 3))

    o = _ref_attention(heads(q), heads(k), heads(v), causal)
    b, n, s, d = o.shape
    o = jnp.transpose(o, (2, 0, 1, 3)).reshape(s, b, n * d)
    return jnp.einsum("sbh,hk->sbk", o, params["out"]["kernel"]) + params[
        "out"]["bias"]


@pytest.mark.parametrize("causal", [False, True])
def test_self_attn_matches_reference(causal):
    key = jax.random.PRNGKey(0)
    p = init_self_attn(key, 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 2, 64))
    got = self_attn(p, x, 4, causal=causal)
    want = _ref_self_attn(p, x, 4, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_self_attn_norm_add_residual():
    p = init_self_attn(jax.random.PRNGKey(0), 64, include_norm_add=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 64))
    y = self_attn(p, x, 4, include_norm_add=True)
    assert y.shape == x.shape
    # zeroing the out-projection must reduce the block to identity
    p0 = {**p, "out": {"kernel": jnp.zeros_like(p["out"]["kernel"]),
                       "bias": jnp.zeros_like(p["out"]["bias"])}}
    np.testing.assert_allclose(
        np.asarray(self_attn(p0, x, 4, include_norm_add=True)),
        np.asarray(x), rtol=1e-6, atol=1e-6)


def test_self_attn_prob_dropout_semantics():
    """Dropout hits the attention probabilities (apex semantics), so with
    p→0 the result converges to the no-dropout path and with rng=None
    dropout is off entirely."""
    p = init_self_attn(jax.random.PRNGKey(0), 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 64))
    base = self_attn(p, x, 4)
    off = self_attn(p, x, 4, dropout_p=0.5, rng=None)
    np.testing.assert_allclose(np.asarray(off), np.asarray(base),
                               rtol=1e-5, atol=1e-5)
    tiny = self_attn(p, x, 4, dropout_p=1e-7, rng=jax.random.PRNGKey(2))
    np.testing.assert_allclose(np.asarray(tiny), np.asarray(base),
                               rtol=1e-3, atol=1e-3)
    # with real dropout the output changes and stays finite
    drop = self_attn(p, x, 4, dropout_p=0.5, rng=jax.random.PRNGKey(2))
    assert np.isfinite(np.asarray(drop)).all()
    assert float(jnp.abs(drop - base).max()) > 1e-3


def test_encdec_attn_shapes_and_memory_lengths():
    p = init_encdec_attn(jax.random.PRNGKey(0), 64)
    q = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 64))
    mem = jax.random.normal(jax.random.PRNGKey(2), (12, 2, 64))
    y = encdec_attn(p, q, mem, 4)
    assert y.shape == q.shape
    # masking all-but-first memory position == attending to 1-length memory
    lens = jnp.array([1, 1], jnp.int32)
    y_masked = encdec_attn(p, q, mem, 4, key_padding_lens=lens)
    y_trunc = encdec_attn(p, q, mem[:1], 4)
    np.testing.assert_allclose(np.asarray(y_masked), np.asarray(y_trunc),
                               rtol=1e-4, atol=1e-4)


def test_conv_bias_relu_fusions():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 5)) * 0.1
    b = jnp.linspace(-1, 1, 5)
    from jax import lax
    ref = lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    np.testing.assert_allclose(
        np.asarray(conv_bias_relu(x, w, b)),
        np.asarray(jnp.maximum(ref, 0)), rtol=1e-5, atol=1e-5)
    scale = jnp.full((5,), 2.0)
    np.testing.assert_allclose(
        np.asarray(conv_frozen_scale_bias_relu(x, w, scale, b)),
        np.asarray(jnp.maximum((ref - b) * 2.0 + b, 0)),
        rtol=1e-5, atol=1e-5)


def test_group_batch_norm_nhwc_local_stats():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 6, 3)) * 3 + 1
    scale = jnp.ones((3,))
    bias = jnp.zeros((3,))
    rm = jnp.zeros((3,))
    rv = jnp.ones((3,))
    y, nm, nv = group_batch_norm_nhwc(x, scale, bias, rm, rv, axis=None)
    # normalised output has ~zero mean / unit variance per channel
    np.testing.assert_allclose(np.asarray(y.mean((0, 1, 2))), 0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y.std((0, 1, 2))), 1, atol=1e-3)
    # running stats moved toward the batch stats
    assert float(jnp.abs(nm - 0.1 * x.mean((0, 1, 2))).max()) < 1e-5
    # fused add+relu epilogue
    z = -jnp.ones_like(x) * 10.0
    y2, _, _ = group_batch_norm_nhwc(x, scale, bias, rm, rv, axis=None,
                                     z=z, relu=True)
    assert float(y2.min()) == 0.0


def test_group_batch_norm_cross_replica(devices8=None):
    from jax.sharding import PartitionSpec as P

    from apex_tpu import mesh as mx
    mesh = mx.build_mesh(tp=1, devices=jax.devices()[:8])
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4, 4, 3))
    scale = jnp.ones((3,)); bias = jnp.zeros((3,))
    rm = jnp.zeros((3,)); rv = jnp.ones((3,))

    def local(xl):
        y, nm, nv = group_batch_norm_nhwc(xl, scale, bias, rm, rv, axis="dp")
        return y, nm, nv
    y, nm, nv = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("dp"),),
        out_specs=(P("dp"), P(), P()), check_vma=False))(x)
    # group stats == global batch stats
    _, nm_ref, _ = group_batch_norm_nhwc(x, scale, bias, rm, rv, axis=None)
    np.testing.assert_allclose(np.asarray(nm), np.asarray(nm_ref),
                               rtol=1e-5, atol=1e-6)


def test_step_timer_and_metrics(tmp_path):
    timer = profiler.StepTimer(tokens_per_step=100, window=10)
    x = jnp.arange(4.0)
    timer.tick(x)
    for _ in range(3):
        timer.tick(x * 2)
    s = timer.summary()
    assert s["steps"] == 3 and s["tokens_per_sec"] > 0
    # a FLOP count per step (the caller's: the benchmark counts from
    # shapes) becomes a rate over the same median
    flops = profiler.StepTimer(model_flops_per_step=6e9, window=10)
    for _ in range(3):
        flops.tick(x)
    fs = flops.summary()
    assert fs["model_flops_per_sec"] == pytest.approx(
        6e9 / fs["median_step_s"])

    log = profiler.MetricsLogger(jsonl_path=str(tmp_path / "m.jsonl"))
    log.log(0, {"loss": jnp.float32(3.5), "lr": 0.1})
    log.log(1, {"loss": jnp.float32(3.2), "lr": 0.1})
    log.close()
    import json
    lines = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
    assert lines[1]["loss"] == pytest.approx(3.2)
    assert log.history[0]["step"] == 0


def test_metrics_tensorboard_sink(tmp_path):
    """The optional TensorBoard sink writes real event files when the
    (gated) writer import succeeds — live in this image via torch."""
    pytest.importorskip("torch.utils.tensorboard")
    tb_dir = str(tmp_path / "tb")
    log = profiler.MetricsLogger(tensorboard_dir=tb_dir)
    assert log._tb is not None
    log.log(0, {"loss": jnp.float32(3.5)})
    log.log(1, {"loss": jnp.float32(3.2)})
    log.close()
    import glob
    import os
    events = glob.glob(tb_dir + "/events.out.tfevents.*")
    assert events and os.path.getsize(events[0]) > 0


def test_latency_stats_ring_wraparound():
    """The O(1) ring buffer keeps exactly the most recent ``capacity``
    samples across wraparound — same summary() contract as the list
    window it replaced (count = lifetime total, stats over the window),
    and an empty accumulator summarises to {}."""
    stats = profiler.LatencyStats(capacity=4)
    assert stats.summary() == {}
    stats.add(5.0)  # partially-filled window
    s = stats.summary()
    assert s["count"] == 1.0 and s["mean_ms"] == 5000.0
    assert s["p50_ms"] == 5000.0 and s["max_ms"] == 5000.0
    # wrap twice: samples 1..10 at capacity 4 retain {7, 8, 9, 10}
    stats = profiler.LatencyStats(capacity=4)
    for i in range(1, 11):
        stats.add(float(i))
    s = stats.summary()
    assert s["count"] == 10.0
    assert s["mean_ms"] == 8500.0          # mean(7..10) in ms
    assert s["max_ms"] == 10000.0          # 5s and 6s evicted
    assert s["p50_ms"] == 8500.0
    assert s["p99_ms"] <= s["max_ms"]


def test_step_timer_window_is_ring(tmp_path):
    """StepTimer windows through the shared O(1) ring: the window caps
    at ``window`` retaining the most recent ticks, reset clears, and
    publish() mirrors the summary into registry gauges."""
    from apex_tpu.telemetry import Registry
    from apex_tpu.telemetry.ring import Ring

    timer = profiler.StepTimer(tokens_per_step=10, window=3)
    assert isinstance(timer._times, Ring)
    for _ in range(6):
        timer.tick()
    s = timer.summary()
    assert s["steps"] == 3.0  # window kept the most recent 3 of 5
    assert timer._times.total == 5 and timer._times.dropped == 2
    reg = Registry()
    pub = timer.publish(reg)
    assert pub == s
    text = reg.to_prometheus_text()
    assert "train_steps 3" in text
    assert "train_tokens_per_sec" in text
    timer.reset()
    assert timer.summary() == {}


def test_metrics_logger_ring_ctx_and_registry(tmp_path):
    """MetricsLogger: O(1) ring history with the oldest dropped at
    capacity, context-manager close, registry gauge mirroring with
    sanitized names — and the JSONL line format byte-stable."""
    import json

    from apex_tpu.telemetry import Registry

    reg = Registry()
    jsonl = str(tmp_path / "m.jsonl")
    with profiler.MetricsLogger(jsonl_path=jsonl, history=2,
                                registry=reg) as log:
        for i in range(4):
            log.log(i, {"loss": 4.0 - i, "grad_norm/global": 0.5})
    assert log._jsonl.closed
    # ring: most recent 2 of 4, oldest first
    assert [h["step"] for h in log.history] == [2, 3]
    # registry view: last value wins, name sanitized to a legal metric
    assert reg.gauge("loss").value == 1.0
    assert reg.gauge("grad_norm_global").value == 0.5
    assert reg.gauge("step").value == 3.0
    # byte-stable JSONL: same keys, same order, plain floats
    lines = open(jsonl).read().splitlines()
    assert json.loads(lines[0]) == {"loss": 4.0,
                                    "grad_norm/global": 0.5, "step": 0}
    assert lines[0] == json.dumps({"loss": 4.0, "grad_norm/global": 0.5,
                                   "step": 0})


def test_annotate_and_tick_sync():
    with profiler.annotate("test-range"):
        y = jnp.sum(jnp.arange(10.0))
    timer = profiler.StepTimer()
    assert timer.tick(y) == 0.0    # first boundary; waits on y
    assert timer.tick({"loss": y}) > 0.0   # any pytree of arrays
    assert float(y) == 45.0


# --- the host side of a capture: sections, parents, counts ----------------
# (what a capture's DEVICE time is called — regions, kernel names — is
# tests/test_regions.py; benchmark/tools/trace_regions.py reads it)

def test_sections_reach_the_profilers_trace(tmp_path):
    """``SpanRecorder(annotate=profiler.annotate)`` under a real
    :func:`profiler.trace` capture: every ``with`` section is a host
    event ``apex.<section>`` in the ``.xplane.pb``, nested as recorded,
    on the profiler's clock — the capture the device trace is in."""
    import glob
    import os

    from jax.profiler import ProfileData

    from apex_tpu.telemetry.spans import SpanRecorder

    rec = SpanRecorder(annotate=profiler.annotate)
    with profiler.trace(str(tmp_path)):
        with rec.section("sched.step"):
            with rec.section("engine.fetch"):
                jnp.sum(jnp.arange(10.0)).block_until_ready()
        rec.section_at("engine.verify", 0.0, 1.0)   # host clock only
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("apex.")}
    assert set(found) == {"apex.sched.step", "apex.engine.fetch"}
    (a, b), (c, d) = found["apex.sched.step"], found["apex.engine.fetch"]
    assert a <= c < d <= b
    assert [(e[2], e[4]) for e in rec.events() if e[0] == 1] == [
        ("engine.fetch", "sched.step"), ("sched.step", None),
        ("engine.verify", None)]


def test_section_rows_name_their_parent():
    """A section row's fifth field is the section open around it —
    also for a ``section_at`` recorded inside one, and the stack
    unwinds when a section's body raises."""
    from apex_tpu.telemetry.spans import SpanRecorder

    t = [0.0]
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("in", self.name))

        def __exit__(self, *exc):
            entered.append(("out", self.name))

    rec = SpanRecorder(clock=lambda: t[0], annotate=Annotation)
    with rec.section("sched.step") as step:
        t[0] = 1.0
        with pytest.raises(RuntimeError):
            with rec.section("sched.collect"):
                t[0] = 2.0
                rec.section_at("engine.verify", 0.5, 2.0)
                raise RuntimeError("fetch failed")
        with rec.section("sched.publish"):
            t[0] = 3.0
    with rec.section("sched.submit"):
        t[0] = 4.0
    assert (step.start, step.end) == (0.0, 3.0)
    assert [e[1:] for e in rec.events() if e[0] == 1] == [
        (0.5, "engine.verify", 2.0, "sched.collect"),
        (1.0, "sched.collect", 2.0, "sched.step"),
        (2.0, "sched.publish", 3.0, "sched.step"),
        (0.0, "sched.step", 3.0, None),
        (3.0, "sched.submit", 4.0, None)]
    assert entered[:4] == [("in", "apex.sched.step"),
                           ("in", "apex.sched.collect"),
                           ("out", "apex.sched.collect"),
                           ("in", "apex.sched.publish")]
    assert not rec._open


def test_span_counts_are_chrome_counter_tracks():
    """``count()`` rows render as Chrome "C" events carrying the
    running total of their name, beside the sections' lane, and are no
    request's mark."""
    from apex_tpu.telemetry.spans import SpanRecorder

    t = [0.0]
    rec = SpanRecorder(clock=lambda: t[0])
    rec.mark("r0", "prefill")
    rec.count("prefill.tokens_real", 100)
    rec.count("prefill.tokens_padded", 256)
    t[0] = 0.5
    rec.count("prefill.tokens_real", 30)
    rec.mark("r0", "first_token")
    ct = rec.to_chrome_trace()
    counters = [(e["name"], e["ts"], e["args"]) for e in ct["traceEvents"]
                if e["ph"] == "C"]
    assert counters == [
        ("prefill.tokens_real", 0.0, {"prefill.tokens_real": 100}),
        ("prefill.tokens_padded", 0.0, {"prefill.tokens_padded": 256}),
        ("prefill.tokens_real", 5e5, {"prefill.tokens_real": 130})]
    assert all(e["pid"] == 2 for e in ct["traceEvents"] if e["ph"] == "C")
    # the request's lane holds its two marks and nothing of the counts
    assert [e["name"] for e in ct["traceEvents"]
            if e["ph"] in ("X", "i") and e["pid"] == 1] == [
        "prefill", "first_token"]
    assert rec.summary()["requests"] == 1 and rec.summary()["events"] == 5


def test_timed_block_without_a_recorder_is_a_stopwatch():
    """What the scheduler times for its own accounting it reads from
    the same ``start`` / ``end`` with or without a recorder: a bare
    :class:`Stopwatch` records nothing, a section the same two reads."""
    from apex_tpu.telemetry.spans import SpanRecorder, Stopwatch

    t = [10.0]

    def clock():
        t[0] += 1.0
        return t[0]

    with Stopwatch(clock) as bare:
        pass
    assert (bare.start, bare.end) == (11.0, 12.0)
    rec = SpanRecorder(clock=clock)
    with rec.section("engine.fetch") as timed:
        pass
    assert (timed.start, timed.end) == (13.0, 14.0)
    assert rec.events() == [(1, 13.0, "engine.fetch", 14.0, None)]
    assert t[0] == 14.0     # two reads each, none for the row
