"""The reduction from a trace to device numbers: exact on a hand-made
trace, and sane on the slice recorded on the chip
(``harness/testdata/trace_small.json``)."""

import os

import pytest

from benchmark.harness import trace
from benchmark.layer_metrics.readers import device as device_readers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "harness", "testdata", "trace_small.json")


@pytest.fixture
def made():
    """Two devices over one second. Device 0: a loop 0.0-0.6 around a
    matmul 0.0-0.4 and an all-reduce 0.4-0.6, an all-gather in flight
    0.2-0.5 on the asynchronous line, and a Pallas kernel 0.7-0.8.
    Device 1: one fusion 0.0-0.5. The host waits in `sched_step` from
    0.55 to 0.75 and is in `pop_events` from 0.8 to 1.0."""
    return {
        "devices": [
            [("while.1", 0.0, 0.6, "jit_step while"),
             ("fusion.1", 0.0, 0.4, "jit_step fusion kOutput"),
             ("psum.3", 0.4, 0.6, "jit_step all-reduce"),
             ("closed_call.7", 0.7, 0.8,
              "jit_step custom-call tpu_custom_call")],
            [("fusion.2", 0.0, 0.5, "jit_step fusion kLoop")],
        ],
        "in_flight": [[("all-gather-start.1", 0.2, 0.5)], []],
        "host": [("sched_step", 0.55, 0.75), ("pop_events", 0.8, 1.0)],
    }


def test_busy_window_and_kernels(made):
    assert trace.window_seconds(made) == pytest.approx(1.0)
    # device 0 is busy 0.0-0.6 and 0.7-0.8, device 1 0.0-0.5
    assert trace.busy_seconds(made) == pytest.approx((0.7 + 0.5) / 2)
    assert trace.kernel_seconds(made, ["jit_step.*tpu_custom_call"]) == \
        pytest.approx(0.1 / 2)
    assert trace.kernel_seconds(made, ["no_such_kernel"]) is None


def test_collectives_and_their_exposed_part(made):
    # collectives cover 0.2-0.6; the matmul hides 0.2-0.4 of that
    total, exposed = trace.collective_seconds(made)
    assert total == pytest.approx(0.4) and exposed == pytest.approx(0.2)
    made["in_flight"][0] = []
    made["devices"][0] = [e for e in made["devices"][0]
                          if "all-reduce" not in e[3]]
    assert trace.collective_seconds(made) is None


def test_breakdown(made):
    ops = dict(trace.top_ops(made))
    assert ops == pytest.approx({
        "jit_step:fusion[kOutput]": 0.4, "jit_step:psum[all-reduce]": 0.2,
        "jit_step:closed_call[tpu_custom_call]": 0.1})
    # the one gap on device 0, 0.6-0.7, lies inside sched_step
    assert trace.idle_gaps(made) == [["sched_step", pytest.approx(0.1)]]


def test_readers_leave_out_what_the_trace_does_not_name(made):
    ev = {"trace": made, "peaks": {"hbm_bytes_per_s": 819e9},
          "window": {"start": 0.0, "end": 10.0, "seconds": 10.0},
          "shape": {"layers": 24, "heads": 16, "head_dim": 64},
          "decode_reads": [(1.0, 1000)]}
    assert device_readers.kernel_share(ev, ["nothing"]) is None
    assert device_readers.kernel_share(ev, ["jit_step.*tpu_custom_call"]) == \
        pytest.approx(100 * 0.05 / 0.6)
    # 1000 positions x 98304 B in 10 s against 819 GB/s, over a kernel
    # that runs 5 % of the traced second
    want = 100 * (1000 * 98304 / 10 / 819e9) / 0.05
    assert device_readers.decode_attn_roofline(ev, ["jit_step.*tpu_custom_call"]) == \
        pytest.approx(want)
    assert device_readers.kernel_share({"trace": None}, ["x"]) is None


def test_recorded_chip_trace_reduces():
    """41 ms of gpt2m_chat on a v5e (PR 22's first chip call): one
    admission program at a flash bucket, the 3.4 ms the device then
    waits for the host, and the start of the decode step program — its
    19 ms copy of the cache, then the first layers' kernels."""
    tr = trace.load_plain(RECORDED)
    assert trace.window_seconds(tr) == pytest.approx(0.041215747)
    assert trace.busy_seconds(tr) == pytest.approx(0.036615978)
    assert trace.kernel_seconds(tr, ["admit_local.*tpu_custom_call"]) == \
        pytest.approx(0.001826122)
    assert trace.kernel_seconds(tr, ["step_local.*tpu_custom_call"]) == \
        pytest.approx(0.001110516)
    assert trace.kernel_seconds(tr, ["_local_step.*tpu_custom_call"]) is None
    ops = trace.top_ops(tr)
    assert ops[0] == ["jit_step_local:copy", pytest.approx(0.018962753)]
    # enclosing loops are left out, so the parts do not exceed the whole
    assert sum(s for _, s in trace.top_ops(tr, 1000)) == \
        pytest.approx(trace.busy_seconds(tr), rel=1e-3)
    assert trace.idle_gaps(tr)[0] == ["sched_step",
                                      pytest.approx(0.003354302)]
    assert trace.collective_seconds(tr) is None
