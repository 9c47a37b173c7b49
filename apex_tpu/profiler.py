"""Tracing / profiling — the observability subsystem (SURVEY.md §5).

The reference has no first-class profiler: it leans on external nsys/
nvprof with scattered ``torch.cuda.Event`` timings and nvtx ranges in
contrib benchmarks (U). The TPU build makes this a component:

- :class:`StepTimer` — per-step wall timing with correct device sync
  (value-fetch barrier — ``block_until_ready`` can return at dispatch
  time on remote-attached devices), windowed statistics, and derived
  throughput/MFU,
- :func:`trace` / :func:`annotate` — ``jax.profiler`` xprof trace capture
  and named ranges (the nvtx equivalent, viewable in XProf/TensorBoard;
  ``benchmark/tools/trace_regions.py`` prints a capture's device time
  by the program's regions and kernel names in a terminal),
- :class:`MetricsLogger` — structured per-step metrics: in-memory ring,
  optional JSONL file, optional TensorBoard writer when available,
- :class:`LatencyStats` — streaming latency accumulator with percentile
  summaries (TTFT / per-token latency for ``apex_tpu.serving``).
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from apex_tpu.telemetry.ring import Ring


@contextlib.contextmanager
def trace(logdir: str):
    """Capture an xprof trace of the enclosed block (``nsys profile``'s
    role for the reference)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named trace range (nvtx.range_push/pop (U) equivalent)."""
    return jax.profiler.TraceAnnotation(name)


def capture_start(logdir: str) -> float:
    """Start of the newest capture under ``logdir`` on the profiler's
    clock, in seconds: its events, in ``ProfileData`` and in the trace
    files the profiler writes, lie at the wall clock less this
    (``profile_start_time`` of the capture's "Task Environment"
    plane)."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    plane = ProfileData.from_file(path).find_plane_with_name(
        "Task Environment")
    start, = (v for k, v in plane.stats if k == "profile_start_time")
    return start * 1e-9


class StepTimer:
    """Wall-clock per-step timing with device sync and derived rates.

    >>> timer = StepTimer(tokens_per_step=batch * seq)
    >>> for batch in loader:
    ...     state, metrics = step_fn(state, *batch)
    ...     timer.tick(metrics["loss"])   # sync point
    >>> timer.summary()["tokens_per_sec"]
    """

    def __init__(self, *, tokens_per_step: Optional[int] = None,
                 model_flops_per_step: Optional[float] = None,
                 window: int = 50):
        self._tokens = tokens_per_step
        self._flops = model_flops_per_step
        # windowing via the shared O(1) ring (a list with pop(0) is
        # O(window) per step once the window fills — the same hot-path
        # bug LatencyStats fixed, hoisted to telemetry.ring for both)
        self._times = Ring(window)
        self._last: Optional[float] = None

    def tick(self, sync_on: Any = None) -> float:
        """Record one step boundary; returns the step's duration (0.0 on
        the first call). ``sync_on``: any device value produced by the
        step — waited on to pin the measurement to real execution."""
        if sync_on is not None:
            jax.block_until_ready(sync_on)
        now = time.perf_counter()
        dt = 0.0 if self._last is None else now - self._last
        self._last = now
        if dt > 0.0:
            self._times.append(dt)
        return dt

    def reset(self):
        self._times.clear()
        self._last = None

    def summary(self) -> Dict[str, float]:
        if not len(self._times):
            return {}
        ts = self._times.array()
        out = {
            "steps": float(len(ts)),
            "mean_step_s": float(ts.mean()),
            "median_step_s": float(np.median(ts)),
            "p90_step_s": float(np.percentile(ts, 90)),
            "min_step_s": float(ts.min()),
        }
        if self._tokens:
            out["tokens_per_sec"] = self._tokens / float(np.median(ts))
        if self._flops:
            out["model_flops_per_sec"] = self._flops / float(np.median(ts))
        return out

    def publish(self, registry, prefix: str = "train_") -> Dict[str, float]:
        """Mirror :meth:`summary` into gauges on a
        :class:`apex_tpu.telemetry.registry.Registry` — the training
        side of the shared-registry path (step percentiles, tokens/s,
        FLOP/s next to the serving counters on one ``/metrics`` page).
        Returns the summary it published."""
        from apex_tpu.telemetry.registry import sanitize_metric_name

        s = self.summary()
        for k, v in s.items():
            registry.gauge(sanitize_metric_name(prefix + k),
                           "StepTimer window statistic").set(v)
        return s


class MetricsLogger:
    """Structured per-step metrics: ring buffer + optional JSONL sink +
    optional TensorBoard + optional shared
    :class:`apex_tpu.telemetry.registry.Registry` (the "structured
    metrics dict" plan, SURVEY.md §5 'Metrics / logging', grown into a
    *view* over the system-wide registry: every logged scalar also sets
    a gauge, so training and serving expose through one ``/metrics``).

    Usable as a context manager (``with MetricsLogger(...) as log:``) —
    ``close()`` runs on exit. The JSONL line format is byte-stable
    across the registry addition.
    """

    def __init__(self, jsonl_path: Optional[str] = None,
                 tensorboard_dir: Optional[str] = None,
                 history: int = 1000, registry=None,
                 registry_prefix: str = ""):
        self._jsonl = open(jsonl_path, "a") if jsonl_path else None
        self._tb = None
        if tensorboard_dir is not None:
            try:
                from torch.utils.tensorboard import SummaryWriter
                self._tb = SummaryWriter(tensorboard_dir)
            except Exception:
                self._tb = None
        self._hist = Ring(history)
        self._registry = registry
        self._reg_prefix = registry_prefix
        self._gauges: Dict[str, Any] = {}

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def log(self, step: int, metrics: Dict[str, Any]):
        flat = {k: float(jax.device_get(v)) if hasattr(v, "dtype") else
                float(v) for k, v in metrics.items()}
        flat["step"] = step
        self._hist.append(flat)
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(flat) + "\n")
            self._jsonl.flush()
        if self._tb is not None:
            for k, v in flat.items():
                if k != "step":
                    self._tb.add_scalar(k, v, step)
        if self._registry is not None:
            for k, v in flat.items():
                gauge = self._gauges.get(k)
                if gauge is None:
                    from apex_tpu.telemetry.registry import \
                        sanitize_metric_name

                    gauge = self._gauges[k] = self._registry.gauge(
                        sanitize_metric_name(self._reg_prefix + k),
                        "MetricsLogger scalar")
                gauge.set(v)

    @property
    def history(self) -> List[Dict[str, float]]:
        return self._hist.values()

    def close(self):
        if self._jsonl is not None:
            self._jsonl.close()
        if self._tb is not None:
            self._tb.close()


class LatencyStats:
    """Streaming latency accumulator: keeps the most recent ``capacity``
    samples (seconds) in a ring and summarises to mean + percentiles in
    milliseconds — the serving scheduler's TTFT and per-token-latency
    sink (training's :class:`StepTimer` has no percentile tail, which is
    the number serving SLOs are written against)."""

    def __init__(self, capacity: int = 8192):
        # the shared O(1) ring (telemetry.ring.Ring): ``add`` is O(1) on
        # the scheduler's per-token hot path (a list with pop(0) is
        # O(capacity) per sample once the window fills). Order within
        # the window is irrelevant to every summary statistic.
        self._ring = Ring(capacity)

    def add(self, seconds: float) -> None:
        self._ring.append(seconds)

    @property
    def _count(self) -> int:
        return self._ring.total

    def summary(self) -> Dict[str, float]:
        """``{count, mean_ms, p50_ms, p90_ms, p99_ms, max_ms}`` over the
        retained window (empty dict before the first sample)."""
        if not self._ring.total:
            return {}
        v = self._ring.array() * 1e3
        return {
            "count": float(self._ring.total),
            "mean_ms": float(v.mean()),
            "p50_ms": float(np.percentile(v, 50)),
            "p90_ms": float(np.percentile(v, 90)),
            "p99_ms": float(np.percentile(v, 99)),
            "max_ms": float(v.max()),
        }
