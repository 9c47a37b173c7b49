"""Per-request span timelines — the host-side story of one request.

The serving scheduler can say *what* happened (counters, percentiles);
this module records *when*: each request's life as a sequence of phase
marks — ``queued`` at submit, ``prefill`` entering admission,
``first_token`` when admission returns, one ``decode`` mark per chunk
the slot rode, ``retired`` at release — each an O(1) ring append of a
4-tuple (no allocation-heavy objects, no dict per event, safe on the
per-chunk hot path). ``section()`` is the host-side ``annotate``
analogue for non-request work (the scheduler's tick and its phases,
engine dispatch, scrape handlers): sections nest, each row names the
section it ran inside, and with an ``annotate`` hook the same ranges
land in the profiler's trace. ``count()`` records how much of something
a section handled (tokens admitted, rows padded). ``anchor()``, while
that hook is set, pairs a read of the recorder's clock with one of the
profiler's, so that every row can be put on the device trace's clock.

``to_chrome_trace()`` renders the ring as Chrome-trace JSON: one lane
(tid) per request plus a lane for host sections, consecutive marks of a
request becoming complete ("X") events named by the phase they opened.
With clock rows its timestamps are on the profiler's clock: given the
capture's start (:func:`apex_tpu.profiler.capture_start`) they are on
the axis of the device capture :func:`apex_tpu.profiler.trace` writes —
the correlation the reference stack never had (scattered host timings
vs an nsys timeline, SURVEY.md §5).

Dependency-free: stdlib only (the ring helper imports numpy lazily,
which this module never triggers).
"""

from __future__ import annotations

import bisect
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from apex_tpu.telemetry.ring import Ring

# canonical request phases, in lifecycle order
PHASE_QUEUED = "queued"
PHASE_PREFILL = "prefill"
PHASE_FIRST_TOKEN = "first_token"
PHASE_DECODE = "decode"
PHASE_RETIRED = "retired"
#: out-of-band: the request was interrupted by a fault and is being
#: retried (apex_tpu.serving.resilience); note = the detected cause
PHASE_ERROR = "error"

_MARK = 0
_SECTION = 1
_COUNT = 2
_CLOCK = 3

#: prefix of a section's name on the profiler's side
ANNOTATION_PREFIX = "apex."


def on_profiler_clock(anchors: Sequence[Tuple[float, float]]
                      ) -> Callable[[float], float]:
    """``t -> the profiler's clock at recorder time t``, from
    ``(recorder time, profiler time)`` anchors sorted by the first:
    linear between the two anchors around ``t``, the nearest anchor's
    offset before the first and after the last."""
    xs = [a for a, _ in anchors]
    ys = [b for _, b in anchors]

    def at(t: float) -> float:
        i = bisect.bisect_right(xs, t)
        if i == 0 or i == len(xs) or xs[i] == xs[i - 1]:
            k = 0 if i == 0 else i - 1
            return t + ys[k] - xs[k]
        x0, x1, y0, y1 = xs[i - 1], xs[i], ys[i - 1], ys[i]
        return y0 + (t - x0) * (y1 - y0) / (x1 - x0)

    return at


class Stopwatch:
    """Times a ``with`` block on ``clock``: ``start`` is read on entry,
    ``end`` on exit — what a caller that needs the interval for its own
    accounting uses where no recorder is attached."""

    __slots__ = ("clock", "start", "end")

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.start = self.end = 0.0

    def __enter__(self):
        self.start = self.clock()
        return self

    def __exit__(self, *exc):
        self.end = self.clock()
        return False


class _Section(Stopwatch):
    """One open :meth:`SpanRecorder.section`: a :class:`Stopwatch` that
    on exit appends its row, naming the section it ran inside."""

    __slots__ = ("rec", "name", "parent", "_annotation")

    def __init__(self, rec: "SpanRecorder", name: str):
        super().__init__(rec.clock)
        self.rec, self.name = rec, name

    def __enter__(self):
        rec = self.rec
        self.parent = rec._open[-1] if rec._open else None
        rec._open.append(self.name)
        self._annotation = None
        if rec.annotate is not None:
            self._annotation = rec.annotate(ANNOTATION_PREFIX + self.name)
            self._annotation.__enter__()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        rec = self.rec
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        rec._open.pop()
        rec._events.append(
            (_SECTION, self.start, self.name, self.end, self.parent))
        return False


class SpanRecorder:
    """Bounded in-memory event log with Chrome-trace export.

    ``clock`` is injectable (the scheduler passes its own, so test
    clocks drive deterministic timelines); it must be monotonic
    seconds. ``annotate`` (optional, ``name -> context manager``) is
    entered around every :meth:`section` under the section's name
    prefixed ``apex.`` — the scheduler sets it to
    ``jax.profiler.TraceAnnotation``, which puts the sections on the
    profiler's clock beside the device trace (this module stays free
    of jax). ``profiler_clock`` is the clock the profiler stamps those
    annotations with: TSL's ``TraceMe`` reads the wall clock, and a
    capture's events lie at it less the capture's start. The ring keeps
    the most recent ``capacity`` events — ``summary()`` reports how
    many were dropped so a truncated export is never mistaken for a
    complete one.

    Rows: ``(0, time, request, phase, note)`` for a mark, ``(1, start,
    name, end, parent)`` for a section (``parent`` the name of the
    section open around it, None at top level), ``(2, time, name,
    n, None)`` for a count and ``(3, time, "clock", profiler_time,
    None)`` for a clock row (:meth:`anchor`). One thread records.
    """

    def __init__(self, capacity: int = 65536,
                 clock=time.perf_counter, annotate=None,
                 profiler_clock: Callable[[], float] = time.time):
        self._events = Ring(capacity)
        self.clock = clock
        self.annotate = annotate
        self.profiler_clock = profiler_clock
        self._open: List[str] = []      # names of the open sections
        self.anchor()

    # -- recording (hot path) ----------------------------------------------

    def mark(self, request_id: str, phase: str,
             note: Optional[str] = None) -> None:
        """O(1): stamp ``request_id`` entering ``phase`` now."""
        self._events.append(
            (_MARK, self.clock(), request_id, phase, note))

    def section(self, name: str) -> _Section:
        """Host-side named range (a scheduler phase, engine dispatch,
        scrape, IO) — the wall-clock sibling of
        :func:`apex_tpu.profiler.annotate`, and with an ``annotate``
        hook the same range on the profiler's clock. The ``with``
        target carries ``start`` and ``end`` (the clock reads the row
        is made of), for a caller that accounts with them too."""
        return _Section(self, name)

    def section_at(self, name: str, t_start: float, t_end: float) -> None:
        """Record an already-measured range whose start lies in an
        earlier call (dispatch → value of a speculative chunk, fault →
        rebuilt engine): host clock only, its parent the section open
        when it is recorded."""
        self._events.append(
            (_SECTION, t_start, name, t_end,
             self._open[-1] if self._open else None))

    def count(self, name: str, n: float) -> None:
        """O(1): ``n`` more of ``name`` now (prompt tokens admitted,
        rows of a padded batch)."""
        self._events.append((_COUNT, self.clock(), name, n, None))

    def anchor(self) -> None:
        """While an ``annotate`` hook is set, a clock row: one read of
        ``clock`` and one of ``profiler_clock``, back to back. Rows
        between two such pairs map onto the profiler's clock by
        :func:`on_profiler_clock`; without a hook nothing is read."""
        if self.annotate is not None:
            self._events.append((_CLOCK, self.clock(), "clock",
                                 self.profiler_clock(), None))

    # -- export -------------------------------------------------------------

    def events(self) -> List[tuple]:
        """Retained events, oldest first (mostly for tests)."""
        return self._events.values()

    def summary(self) -> Dict[str, Any]:
        evs = self._events.values()
        reqs = {e[2] for e in evs if e[0] == _MARK}
        return {
            "events": len(evs),
            "events_total": self._events.total,
            "events_dropped": self._events.dropped,
            "requests": len(reqs),
        }

    def clear(self) -> None:
        self._events.clear()

    def to_chrome_trace(self, origin_s: float = 0.0) -> Dict[str, Any]:
        """Render as a Chrome-trace dict (``json.dump`` it to a file and
        open in Perfetto). Request lanes are pid 1; host sections pid 2;
        clock rows are not rendered. Timestamps are microseconds: with
        clock rows, of the profiler's clock less ``origin_s`` — pass a
        capture's start (:func:`apex_tpu.profiler.capture_start`) and
        they are on that capture's axis, the one its own trace files
        use; without, relative to the earliest retained event (the
        epoch of ``clock`` carries no meaning across processes).
        """
        rows = self._events.values()
        evs = [e for e in rows if e[0] != _CLOCK]
        if not evs:
            return {"traceEvents": [], "displayTimeUnit": "ms"}
        anchors = sorted((e[1], e[3]) for e in rows if e[0] == _CLOCK)
        if anchors:
            on = on_profiler_clock(anchors)
            us = lambda t: (on(t) - origin_s) * 1e6
        else:
            t0 = min(e[1] for e in evs)
            us = lambda t: (t - t0) * 1e6

        out: List[Dict[str, Any]] = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "serving requests"}},
            {"ph": "M", "pid": 2, "name": "process_name",
             "args": {"name": "host sections"}},
            {"ph": "M", "pid": 2, "tid": 0, "name": "thread_name",
             "args": {"name": "sections"}},
        ]
        # one lane per request, in order of first appearance
        lanes: Dict[str, int] = {}
        last_mark: Dict[str, tuple] = {}
        totals: Dict[str, float] = {}
        for e in evs:
            if e[0] == _SECTION:
                _, t_start, name, t_end, _ = e
                out.append({"ph": "X", "pid": 2, "tid": 0, "name": name,
                            "ts": us(t_start),
                            "dur": max(us(t_end) - us(t_start), 0.0)})
                continue
            if e[0] == _COUNT:
                # a running total per name, as a counter track
                _, t, name, n, _ = e
                totals[name] = totals.get(name, 0) + n
                out.append({"ph": "C", "pid": 2, "name": name,
                            "ts": us(t), "args": {name: totals[name]}})
                continue
            _, t, rid, phase, note = e
            tid = lanes.get(rid)
            if tid is None:
                tid = lanes[rid] = len(lanes)
                out.append({"ph": "M", "pid": 1, "tid": tid,
                            "name": "thread_name",
                            "args": {"name": f"req {rid}"}})
            prev = last_mark.get(rid)
            if prev is not None:
                prev_t, prev_phase, prev_note = prev
                span = {"ph": "X", "pid": 1, "tid": tid,
                        "name": prev_phase, "ts": us(prev_t),
                        "dur": max(us(t) - us(prev_t), 0.0)}
                if prev_note:
                    span["args"] = {"note": prev_note}
                out.append(span)
            last_mark[rid] = (t, phase, note)
        # terminal (or dangling-latest) marks become instant events
        for rid, (t, phase, note) in last_mark.items():
            inst = {"ph": "i", "pid": 1, "tid": lanes[rid], "name": phase,
                    "ts": us(t), "s": "t"}
            if note:
                inst["args"] = {"note": note}
            out.append(inst)
        return {"traceEvents": out, "displayTimeUnit": "ms"}
