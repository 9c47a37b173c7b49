"""Readers of what the program records about its own tick: the
scheduler's ``sched.*`` sections with their parents, the request marks
inside them, and the admission counts (``telemetry/spans.SpanRecorder``
rows; the layouts are in ``readers/spans.py``, and a count row is ``(2,
time, name, n, None)``). Every name a reader looks for is a parameter
of the metric's file. A program that records no such section or count
gives every reader here nothing to read, and it returns None."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness import stats
from benchmark.layer_metrics.readers.spans import MARK, SECTION

COUNT = 2


def _in_window(ev: Dict[str, Any], t: float) -> bool:
    return ev["window"]["start"] <= t < ev["window"]["end"]


def _first_marks(ev: Dict[str, Any], phase: str) -> Dict[str, float]:
    """The time of each request's first mark of ``phase``. Where the
    job says which requests fell due inside the window those are the
    population (the one ``ttft_p50_ms`` has); elsewhere the requests
    marked inside the window."""
    due = ev.get("due_at")
    first: Dict[str, float] = {}
    for e in ev.get("spans") or []:
        if e[0] == MARK and e[3] == phase and (
                e[2] in due if due is not None else _in_window(ev, e[1])):
            first.setdefault(e[2], e[1])
    return first


def mark_to_mark_ms_p50(ev: Dict[str, Any], start: str, end: str
                        ) -> Optional[float]:
    """Median time from a request's first ``start`` mark to its first
    ``end`` mark at or after it."""
    began = _first_marks(ev, start)
    took: List[float] = []
    seen = set()
    for e in ev.get("spans") or []:
        if (e[0] == MARK and e[3] == end and e[2] in began
                and e[2] not in seen and e[1] >= began[e[2]]):
            seen.add(e[2])
            took.append(e[1] - began[e[2]])
    mid = stats.median(took)
    return None if mid is None else mid * 1e3


def mark_to_section_end_ms_p50(ev: Dict[str, Any], phase: str,
                               section: str) -> Optional[float]:
    """Median time from a request's first ``phase`` mark to the end of
    the ``section`` that was open around it — for ``first_token`` and
    ``sched.step``, how long a computed token waits for the tick to
    return before ``pop_events()`` can hand it out."""
    secs = sorted((e[1], e[3]) for e in ev.get("spans") or []
                  if e[0] == SECTION and e[2] == section)
    if not secs:
        return None
    held = []
    for t in _first_marks(ev, phase).values():
        around = [b for a, b in secs if a <= t <= b]
        if around:
            held.append(around[0] - t)
    mid = stats.median(held)
    return None if mid is None else mid * 1e3


def section_ms_max(ev: Dict[str, Any], section: str) -> Optional[float]:
    """The longest ``section`` that began inside the window."""
    took = [e[3] - e[1] for e in ev.get("spans") or []
            if e[0] == SECTION and e[2] == section
            and _in_window(ev, e[1])]
    return max(took) * 1e3 if took else None


def count_total(ev: Dict[str, Any], name: str) -> Optional[float]:
    """What the program counted under ``name`` inside the window; None
    where it counted nothing under that name."""
    found = [e[3] for e in ev.get("spans") or []
             if e[0] == COUNT and e[2] == name and _in_window(ev, e[1])]
    return float(sum(found)) if found else None


def padding_share(ev: Dict[str, Any], real: str, padded: str
                  ) -> Optional[float]:
    """1 - ``real`` / ``padded`` over the counts inside the window, in
    percent: the part of the admission programs' token rows that was
    padding."""
    n_real, n_padded = count_total(ev, real), count_total(ev, padded)
    if n_real is None or not n_padded:
        return None
    return 100.0 * (1.0 - n_real / n_padded)


def count_ratio(ev: Dict[str, Any], of: str, per: str) -> Optional[float]:
    """``of`` / ``per`` over the counts inside the window: requests a
    device program, tokens a row."""
    n_of, n_per = count_total(ev, of), count_total(ev, per)
    if n_of is None or not n_per:
        return None
    return n_of / n_per
