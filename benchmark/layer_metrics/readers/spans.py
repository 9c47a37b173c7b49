"""Readers of the program's host spans (``telemetry/spans.SpanRecorder``
handed to ``Scheduler(spans=)``) and of the benchmark's own span around
``Scheduler.step()``. A recorder row is ``(0, time, request, phase,
note)`` for a mark and ``(1, start, name, end, None)`` for a section."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmark.harness import stats

MARK, SECTION = 0, 1


def _sections(ev: Dict[str, Any], names: Sequence[str]) -> List[tuple]:
    lo, hi = ev["window"]["start"], ev["window"]["end"]
    return sorted((e[1], e[3], e[2]) for e in ev.get("spans") or []
                  if e[0] == SECTION and e[2] in names and lo <= e[1] < hi)


def sched_self_ms(ev: Dict[str, Any], engine_sections: Sequence[str]
                  ) -> Optional[float]:
    """Median self time of ``Scheduler.step()``: the benchmark's span
    around the call less the engine sections inside it."""
    if not ev.get("spans") or not ev.get("ticks"):
        return None
    secs = _sections(ev, engine_sections)
    lo, hi = ev["window"]["start"], ev["window"]["end"]
    own, i = [], 0
    for t0, t1 in ev["ticks"]:
        if not lo <= t0 < hi:
            continue
        while i < len(secs) and secs[i][1] <= t0:
            i += 1
        k = i
        while k < len(secs) and secs[k][0] < t1:
            k += 1
        own.append((t1 - t0) - stats.union_seconds(
            (a, b) for a, b, _ in secs[i:k]))
    mid = stats.median(own)
    return None if mid is None else mid * 1e3


def dispatch_to_fetch_ms_p50(ev: Dict[str, Any], dispatch: str, fetch: str
                             ) -> Optional[float]:
    """Median time from the dispatch of a decode chunk to its value on
    the host: each ``fetch`` section's end less the start of the last
    ``dispatch`` section before it."""
    secs = _sections(ev, (dispatch, fetch))
    spans, last = [], None
    for a, b, name in secs:
        if name == dispatch:
            last = a
        elif last is not None:
            spans.append(b - last)
            last = None
    mid = stats.median(spans)
    return None if mid is None else mid * 1e3


def occupancy(ev: Dict[str, Any], fetch: str, phase: str) -> Optional[float]:
    """Live slots over slots per fetched chunk, in percent: the
    recorder marks ``phase`` once for every live row of a chunk right
    after its ``fetch`` section."""
    rows = ev.get("spans") or []
    lo, hi = ev["window"]["start"], ev["window"]["end"]
    chunks = sum(1 for e in rows if e[0] == SECTION and e[2] == fetch
                 and lo <= e[1] < hi)
    marks = sum(1 for e in rows if e[0] == MARK and e[3] == phase
                and e[4] is None and lo <= e[1] < hi)
    if not chunks:
        return None
    return 100.0 * marks / (chunks * ev["slots"])


def section_share(ev: Dict[str, Any], of: Sequence[str],
                  over: Sequence[str]) -> Optional[float]:
    """Time in the ``of`` sections over time in the ``over`` sections,
    in percent."""
    total = sum(b - a for a, b, _ in _sections(ev, over))
    if not total:
        return None
    return 100.0 * sum(b - a for a, b, _ in _sections(ev, of)) / total


def due_to_phase_ms_p50(ev: Dict[str, Any], phase: str) -> Optional[float]:
    """Median time from a request's due time (the benchmark's) to the
    recorder's first mark of ``phase`` for it."""
    due = ev.get("due_at") or {}
    first: Dict[str, float] = {}
    for e in ev.get("spans") or []:
        if e[0] == MARK and e[3] == phase and e[2] in due:
            first.setdefault(e[2], e[1])
    mid = stats.median(first[r] - due[r] for r in first)
    return None if mid is None else mid * 1e3
