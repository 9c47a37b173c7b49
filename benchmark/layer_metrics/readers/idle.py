"""Readers of device 0's idle time by what the server held: the idle
intervals of the traced slice (``readers/regions.idle_intervals``)
against the requests the program had and against its ``apex.*`` host
annotations.

*Work* is the union over requests of the time from a request's first
``queued`` mark to its ``retired`` mark (``telemetry/spans.SpanRecorder``
rows, layouts in ``readers/spans.py``); a request open at an edge of
the slice counts to that edge. *Idle with work* is device 0's idle time
inside work: the chip waited while a request was in the server. The
rest of the idle time had no request to serve.

The recorder stamps its rows on the scheduler's clock. Its clock rows
``(3, time, "clock", wall time, None)`` pair that clock with the wall
clock the profiler stamps host events with, and a capture's events lie
at the wall clock less the capture's start (``profile_start_time`` of
its "Task Environment" plane). A row maps onto the trace's axis by
linear interpolation between the clock rows around it
(:func:`to_trace`: the program's ``spans.on_profiler_clock`` does the
same for its export, and the yardstick imports nothing of the program
it measures). A program that writes no clock row gives every
reader here nothing to read: it returns None. So does a run whose
recorder dropped rows the slice needs — the ring keeps the newest rows,
and the evidence hands over the rows, not the ring's count of dropped
ones: the oldest row kept must lie before the slice, and every request
marked in or after the slice must have kept its ``queued`` mark.
"""

from __future__ import annotations

import bisect
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmark.harness import stats, trace
from benchmark.layer_metrics.readers import regions
from benchmark.layer_metrics.readers.spans import MARK, SECTION

CLOCK = 3
QUEUED, RETIRED = "queued", "retired"
#: the tick's section on the recorder's side and on the profiler's
STEP, STEP_ANNOTATION = "sched.step", "apex.sched.step"

_starts: Dict[str, float] = {}      # xplane path -> capture start, seconds


def profile_start_s(ev: Dict[str, Any]) -> Optional[float]:
    """The capture's start on the wall clock, in seconds: handed in as
    ``ev["profile_start_s"]``, or read from the profile the run's
    capture left."""
    if "profile_start_s" in ev:
        return ev["profile_start_s"]
    logdir = getattr(ev.get("capture"), "logdir", None)
    path = trace.newest_xplane(logdir) if logdir else None
    if path is None:
        return None
    if path not in _starts:
        from jax.profiler import ProfileData

        plane = ProfileData.from_file(path).find_plane_with_name(
            "Task Environment")
        start = [v for k, v in (plane.stats if plane is not None else ())
                 if k == "profile_start_time"]
        _starts[path] = start[0] * 1e-9 if start else None
    return _starts[path]


def to_trace(ev: Dict[str, Any]) -> Optional[Callable[[float], float]]:
    """``recorder time -> seconds on the trace's axis``; None without
    clock rows or without the capture's start."""
    rows = ev.get("spans") or []
    anchors = sorted((e[1], e[3]) for e in rows if e[0] == CLOCK)
    start = profile_start_s(ev) if anchors else None
    if start is None:
        return None
    xs = [a for a, _ in anchors]
    ys = [b - start for _, b in anchors]

    def at(t: float) -> float:
        i = bisect.bisect_right(xs, t)
        if i == 0 or i == len(xs) or xs[i] == xs[i - 1]:
            k = 0 if i == 0 else i - 1
            return t + ys[k] - xs[k]
        x0, x1, y0, y1 = xs[i - 1], xs[i], ys[i - 1], ys[i]
        return y0 + (t - x0) * (y1 - y0) / (x1 - x0)

    return at


def intersect(a: Sequence[Sequence[float]], b: Sequence[Sequence[float]]
              ) -> List[Tuple[float, float]]:
    """The intervals both unions cover."""
    a, b = stats.merge(a), stats.merge(b)
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            x, y = max(lo, b[k][0]), min(hi, b[k][1])
            if y > x:
                out.append((x, y))
            k += 1
    return out


def work_intervals(ev: Dict[str, Any], at: Callable[[float], float],
                   lo: float, hi: float) -> Optional[List[tuple]]:
    """Each request's ``queued`` to ``retired`` on the trace's axis,
    clipped to ``[lo, hi]``; None where the recorder dropped rows the
    slice needs."""
    rows = ev.get("spans") or []
    if not rows or at(min(e[1] for e in rows)) > lo:
        return None
    queued: Dict[str, float] = {}
    retired: Dict[str, float] = {}
    late = set()
    for e in rows:
        if e[0] != MARK:
            continue
        t = at(e[1])
        if e[3] == QUEUED:
            queued.setdefault(e[2], t)
        elif e[3] == RETIRED:
            retired[e[2]] = t
        if t >= lo:
            late.add(e[2])
    if late - set(queued):
        return None
    out = []
    for rid, a in queued.items():
        b = retired.get(rid, hi)
        if b > a and b > lo and a < hi:
            out.append((max(a, lo), min(b, hi)))
    return out


def idle_with_work(ev: Dict[str, Any]
                   ) -> Optional[Tuple[float, float, List[tuple]]]:
    """``(slice start, slice end, idle intervals inside work)``."""
    scoped = regions.scoped_trace(ev)
    at = to_trace(ev)
    if not scoped or not scoped["ops"] or at is None:
        return None
    lo, hi = regions.window(scoped)
    work = work_intervals(ev, at, lo, hi)
    if work is None:
        return None
    return lo, hi, intersect(regions.idle_intervals(scoped), work)


def _annotated(ev: Dict[str, Any], names: Sequence[str]) -> List[tuple]:
    return [(e[1], e[2]) for e in regions.scoped_trace(ev)["host"]
            if e[0] in names]


def idle_with_work_share(ev: Dict[str, Any]) -> Optional[float]:
    """Idle seconds inside work over the slice, in percent."""
    found = idle_with_work(ev)
    if found is None:
        return None
    lo, hi, idle = found
    return 100.0 * stats.union_seconds(idle) / (hi - lo)


def idle_with_work_ms_per(ev: Dict[str, Any], annotation: str
                          ) -> Optional[float]:
    """Idle milliseconds inside work per ``annotation`` that ends inside
    the slice (per decode chunk fetched, for ``apex.engine.fetch``)."""
    found = idle_with_work(ev)
    if found is None:
        return None
    lo, hi, idle = found
    n = sum(1 for _, b in _annotated(ev, (annotation,)) if lo <= b <= hi)
    if not n:
        return None
    return 1e3 * stats.union_seconds(idle) / n


def idle_with_work_under(ev: Dict[str, Any], annotations: Sequence[str],
                         inside: bool = True) -> Optional[float]:
    """The part of the idle time inside work that one of the host
    ``annotations`` covers (``inside``) or that none covers, in
    percent."""
    found = idle_with_work(ev)
    if found is None:
        return None
    _, _, idle = found
    total = stats.union_seconds(idle)
    if not total:
        return None
    outside = stats.subtract_cover(idle, _annotated(ev, annotations))
    return 100.0 * (total - outside if inside else outside) / total


def step_clock_residuals_us(ev: Dict[str, Any]) -> Optional[List[float]]:
    """For every recorder ``sched.step`` row that starts inside the
    slice: its start mapped onto the trace's axis less the start of the
    nearest ``apex.sched.step`` annotation, in microseconds — how well
    the clock rows hold (the annotation is entered just before the
    recorder reads its clock)."""
    scoped = regions.scoped_trace(ev)
    at = to_trace(ev)
    if not scoped or at is None:
        return None
    lo, hi = regions.window(scoped)
    marks = sorted(a for a, _ in _annotated(ev, (STEP_ANNOTATION,)))
    if not marks:
        return None
    out = []
    for e in ev.get("spans") or []:
        if e[0] != SECTION or e[2] != STEP:
            continue
        t = at(e[1])
        if not lo <= t <= hi:
            continue
        i = bisect.bisect_left(marks, t)
        near = min(marks[max(i - 1, 0):i + 1], key=lambda m: abs(m - t))
        out.append((t - near) * 1e6)
    return out
