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
    assert profiler.model_flops_per_token(100, remat=True) == 800.0

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


def test_op_profile_self_times(tmp_path):
    """op_profile parses a trace capture into nested-aware self-times:
    a while containing two fusions self-times to its remainder, and
    category/source attribution survives aggregation."""
    import gzip
    import json
    import os

    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    os.makedirs(d)
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        # host-side event must be ignored
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 9, "tid": 1, "name": "hostjunk",
         "ts": 0, "dur": 999},
        # while.1 [0, 100) containing fusion.1 [10, 40) and fusion.2
        # [50, 90) -> self 30
        {"ph": "X", "pid": 3, "tid": 1, "name": "while.1", "ts": 0,
         "dur": 100, "args": {"hlo_category": "while"}},
        {"ph": "X", "pid": 3, "tid": 1, "name": "fusion.1", "ts": 10,
         "dur": 30, "args": {"hlo_category": "convolution fusion",
                             "source": "model.py:42"}},
        {"ph": "X", "pid": 3, "tid": 1, "name": "fusion.2", "ts": 50,
         "dur": 40, "args": {"hlo_category": "loop fusion"}},
        # top-level copy after the while
        {"ph": "X", "pid": 3, "tid": 1, "name": "copy.1", "ts": 120,
         "dur": 10, "args": {"hlo_category": "data formatting",
                             "source": "model.py:99"}},
    ]
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    prof = profiler.op_profile(str(tmp_path))
    by_name = {o["name"]: o for o in prof["top_ops"]}
    assert by_name["while.1"]["seconds"] == pytest.approx(30e-6)
    assert by_name["fusion.1"]["seconds"] == pytest.approx(30e-6)
    assert by_name["fusion.2"]["seconds"] == pytest.approx(40e-6)
    assert by_name["copy.1"]["seconds"] == pytest.approx(10e-6)
    assert "hostjunk" not in by_name
    assert prof["total_s"] == pytest.approx(110e-6)
    assert prof["by_category"]["data formatting"] == pytest.approx(10e-6)
    assert by_name["fusion.1"]["source"] == "model.py:42"
    assert by_name["fusion.1"]["count"] == 1


def test_op_profile_missing_trace(tmp_path):
    with pytest.raises(FileNotFoundError, match="trace.json.gz"):
        profiler.op_profile(str(tmp_path))


def test_op_profile_newest_capture_and_nested_streams(tmp_path):
    """Two capture dirs under one logdir: op_profile parses the newest
    (by mtime); its fixture nests ops on BOTH cores, so per-stream
    self-time accounting and category rollup are exercised together."""
    import gzip
    import json
    import os
    import time

    def write(dirname, events):
        d = tmp_path / "plugins" / "profile" / dirname
        os.makedirs(d)
        path = d / "vm.trace.json.gz"
        with gzip.open(path, "wt") as f:
            json.dump({"traceEvents": events}, f)
        return path

    meta = []
    for pid in (3, 4):
        meta += [
            {"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": f"/device:TPU:{pid - 3}"}},
            {"ph": "M", "pid": pid, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
        ]
    write("2026_01_01_00_00_00", meta + [
        {"ph": "X", "pid": 3, "tid": 1, "name": "stale.1", "ts": 0,
         "dur": 50, "args": {"hlo_category": "loop fusion"}}])
    time.sleep(0.05)  # distinct mtimes
    # newest capture: a while on each core, each containing one fusion
    newest = write("2026_01_01_00_00_59", meta + [
        {"ph": "X", "pid": 3, "tid": 1, "name": "while.a", "ts": 0,
         "dur": 100, "args": {"hlo_category": "while"}},
        {"ph": "X", "pid": 3, "tid": 1, "name": "fusion.a", "ts": 20,
         "dur": 30, "args": {"hlo_category": "loop fusion"}},
        {"ph": "X", "pid": 4, "tid": 1, "name": "while.b", "ts": 10,
         "dur": 60, "args": {"hlo_category": "while"}},
        {"ph": "X", "pid": 4, "tid": 1, "name": "fusion.b", "ts": 30,
         "dur": 20, "args": {"hlo_category": "convolution fusion"}},
    ])
    prof = profiler.op_profile(str(tmp_path))
    assert prof["trace_path"] == str(newest)
    by_name = {o["name"]: o for o in prof["top_ops"]}
    assert "stale.1" not in by_name
    # self-time = parent minus its own core's child only
    assert by_name["while.a"]["seconds"] == pytest.approx(70e-6)
    assert by_name["while.b"]["seconds"] == pytest.approx(40e-6)
    assert prof["total_s"] == pytest.approx(160e-6)
    assert prof["by_category"]["while"] == pytest.approx(110e-6)
    assert prof["by_category"]["loop fusion"] == pytest.approx(30e-6)
    assert prof["by_category"]["convolution fusion"] == \
        pytest.approx(20e-6)


def test_op_profile_multi_device_streams(tmp_path):
    """Concurrent ops on different cores must NOT nest: each (pid, tid)
    stream gets its own stack, so overlapping-in-time ops on two devices
    keep their full self-times."""
    import gzip
    import json
    import os

    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_01"
    os.makedirs(d)
    events = []
    for pid in (3, 4):
        events += [
            {"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": f"/device:TPU:{pid - 3}"}},
            {"ph": "M", "pid": pid, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
        ]
    # core0 op [0, 100) and core1 op [10, 40) overlap in wall time
    events += [
        {"ph": "X", "pid": 3, "tid": 1, "name": "fusion.a", "ts": 0,
         "dur": 100, "args": {"hlo_category": "loop fusion"}},
        {"ph": "X", "pid": 4, "tid": 1, "name": "fusion.b", "ts": 10,
         "dur": 30, "args": {"hlo_category": "loop fusion"}},
    ]
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    prof = profiler.op_profile(str(tmp_path))
    by_name = {o["name"]: o for o in prof["top_ops"]}
    assert by_name["fusion.a"]["seconds"] == pytest.approx(100e-6)
    assert by_name["fusion.b"]["seconds"] == pytest.approx(30e-6)
    assert prof["total_s"] == pytest.approx(130e-6)
