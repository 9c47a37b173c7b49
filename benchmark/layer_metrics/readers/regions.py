"""Readers of the regions inside the programs: the ``apex.`` scopes the
program puts around its work (``jax.named_scope``), which reach a
profiler trace as each operation's scope path, and the program's own
``apex.*`` host annotations beside the device on the profiler's clock.

On this runtime (jax 0.9.0, libtpu 0.0.34) the path is the stat
``tf_op`` of an operation's *event metadata* in the ``.xplane.pb`` —
``jit(_local_step)/transpose(jvp(apex.layers))/while/body/.../apex.attn/
dot_general:`` — which ``jax.profiler.ProfileData`` does not hand out
(it gives an event's own stats: offset and duration). So the file is
read twice: the events through ``ProfileData`` as ``harness/trace``
reads them, and the metadata by a few lines of protobuf wire format
(:func:`scope_paths`). ``harness/trace.load`` keeps neither the path nor
the program's annotations, hence a structure of this module's own, the
*scoped trace* of device 0::

    {"ops":       [(name, start_s, end_s, detail, path), ...],
     "in_flight": [(name, start_s, end_s, detail, path), ...],
     "host":      [(name, start_s, end_s), ...]}

``name`` and ``detail`` as in ``harness/trace``; ``path`` is ``""`` where
the compiler made an operation without metadata. ``in_flight`` holds
the collectives of the asynchronous line, ``host`` every ``apex.*`` and
``bench.*`` annotation. ``harness/testdata/trace_scoped_small.json`` is
a slice of one recorded on the chip.

An operation belongs to the innermost region of its path; it is of the
backward pass where the path passes through ``transpose(`` (recompute
under a remat policy included). What is summed is an operation's *own*
time: its length less the operations it encloses — a leaf's whole
length, and for a loop the time between its body's operations, which
goes to the loop's region. (Leaves alone are 96.6 % of the train step's
busy time, PR 24: some 2 us pass between two operations of a loop body.)
Own times add up to busy time exactly, so the regions partition it.
Shares are of device 0's busy time, in percent. Region names, kernel
names and patterns are parameters of the metric files. A trace that
carries no region at all — a program without the scopes — gives every
reader here nothing to read: it returns None.
"""

from __future__ import annotations

import json
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark.harness import stats, trace

#: the event-metadata stat that carries an operation's scope path
SCOPE_STAT = "tf_op"
#: what marks a scope of a path as one of the program's regions (the
#: regions a metric reads are named in its file)
REGION = re.compile(r"apex\.[A-Za-z0-9_.]+")
#: host annotations kept: the program's and the benchmark's
HOST_PREFIXES = ("apex.", trace.HOST_PREFIX)
BACKWARD = "transpose("

_parsed: Dict[str, Dict[str, Any]] = {}     # xplane path -> scoped trace


# -- the read ----------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a view of the bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def scope_paths(xplane_path: str, stat: str = SCOPE_STAT) -> Dict[str, str]:
    """``{operation text: scope path}`` from the event metadata of the
    device planes. (XSpace.planes = 1; XPlane.name = 2, .event_metadata
    = 4, .stat_metadata = 5, both maps with the message under 2;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5; XStatMetadata.id = 1, .name = 2.)"""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    text = lambda v: bytes(v).decode("utf-8", "replace")
    out: Dict[str, str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_ids = "", [], set()
        for field, value in _fields(plane):
            if field == 2:
                name = text(value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                meta = dict(_fields(dict(_fields(value)).get(2, b"")))
                if text(meta.get(2, b"")) == stat:
                    stat_ids.add(meta.get(1))
        if not trace.DEVICE_PLANE.match(name):
            continue
        for entry in events:
            op, path = None, None
            for field, value in _fields(dict(_fields(entry)).get(2, b"")):
                if field == 2:
                    op = text(value)
                elif field == 5:
                    st = dict(_fields(value))
                    if st.get(1) in stat_ids and 5 in st:
                        path = text(st[5])
            if op and path:
                out[op] = path.rstrip(":")
    return out


def load(xplane_path: str) -> Dict[str, Any]:
    """The scoped trace of the lowest-numbered device of a profile."""
    from jax.profiler import ProfileData

    paths = scope_paths(xplane_path)
    data = ProfileData.from_file(xplane_path)
    sec = lambda ev: (ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
    devices: Dict[int, Any] = {}
    host: List[tuple] = []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            host.extend((ev.name, *sec(ev)) for line in plane.lines
                        for ev in line.events
                        if ev.name.startswith(HOST_PREFIXES))
    ops: List[tuple] = []
    in_flight: List[tuple] = []
    if devices:
        lines = {ln.name: ln for ln in devices[min(devices)].lines}
        modules = sorted(
            (*sec(ev), re.sub(r"\(\d+\)$", "", ev.name))
            for ev in lines[trace.MODULES_LINE].events
        ) if trace.MODULES_LINE in lines else []
        i = 0
        for ev in sorted(lines[trace.OPS_LINE].events,
                         key=lambda e: e.start_ns
                         ) if trace.OPS_LINE in lines else []:
            a, b = sec(ev)
            while i < len(modules) and modules[i][1] <= a:
                i += 1
            inside = i < len(modules) and modules[i][0] <= a
            name, detail = trace._describe(
                ev.name, modules[i][2] if inside else "")
            ops.append((name, a, b, detail, paths.get(ev.name, "")))
        for ev in (lines[trace.ASYNC_LINE].events
                   if trace.ASYNC_LINE in lines else []):
            name, detail = trace._describe(ev.name, "")
            if trace.COLLECTIVE.search(detail):
                in_flight.append((name, *sec(ev), detail,
                                  paths.get(ev.name, "")))
    return {"ops": ops,
            "in_flight": sorted(in_flight, key=lambda e: e[1]),
            "host": sorted(host, key=lambda e: e[1])}


def load_plain(path: str) -> Dict[str, Any]:
    """A scoped trace kept as plain JSON (``json.dump`` of what
    :func:`load` returns): the recorded slice the tests read."""
    with open(path) as f:
        return {k: [tuple(e) for e in v] for k, v in json.load(f).items()}


def scoped_trace(ev: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's scoped trace: handed in as ``ev["scoped_trace"]``, or
    read from the profile the run's capture left (once per file: every
    reader of the run shares it). None without a profile."""
    if "scoped_trace" in ev:
        return ev["scoped_trace"]
    capture = ev.get("capture")
    logdir = getattr(capture, "logdir", None)
    path = trace.newest_xplane(logdir) if logdir else None
    if path is None:
        return None
    if path not in _parsed:
        _parsed[path] = load(path)
    return _parsed[path]


# -- the reduction -----------------------------------------------------------

def regions_of(path: str) -> List[str]:
    """The regions a scope path passes through, outermost first."""
    return REGION.findall(path)


def own_times(ops: Sequence[tuple]) -> List[Tuple[tuple, float]]:
    """``(operation, its own seconds)``: an operation's length less the
    operations it directly encloses. Operations of one device line nest
    and never cross, so the own times add up to the busy time."""
    out: List[list] = []
    stack: List[list] = []
    for e in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and e[1] >= stack[-1][0][2]:
            stack.pop()
        if stack:
            stack[-1][1] -= e[2] - e[1]
        stack.append([e, e[2] - e[1]])
        out.append(stack[-1])
    return [(e, own) for e, own in out]


def _own_and_busy(ev: Dict[str, Any]):
    """``(operations with their own seconds, busy seconds)`` of a run
    whose trace carries at least one region; None otherwise."""
    scoped = scoped_trace(ev)
    if not scoped or not scoped["ops"]:
        return None
    if not any(REGION.search(e[4]) for e in scoped["ops"]):
        return None
    mine = own_times(scoped["ops"])
    return mine, sum(own for _, own in mine)


def region_share(ev: Dict[str, Any], program: str, regions: Sequence[str],
                 direction: Optional[str] = None,
                 ops: Sequence[str] = ()) -> Optional[float]:
    """Own time of ``program``'s operations whose innermost region is
    one of ``regions`` — ``direction`` ``"forward"`` / ``"backward"``
    keeps one pass — plus its operations under no region at all that
    match one of the ``ops`` patterns (over ``"<detail> <name>"``, as
    ``harness/trace.matching``), over busy time."""
    found = _own_and_busy(ev)
    if found is None:
        return None
    mine, busy = found
    rx = [re.compile(p) for p in ops]
    hit, total = False, 0.0
    for e, own in mine:
        if not re.search(program, e[3].split(" ")[0]):
            continue
        inside = regions_of(e[4])
        if not inside:
            if not any(r.search(f"{e[3]} {e[0]}") for r in rx):
                continue
        elif inside[-1] not in regions or (
                direction is not None
                and (BACKWARD in e[4]) != (direction == "backward")):
            continue
        hit, total = True, total + own
    if not hit or not busy:
        return None
    return 100.0 * total / busy


def collective_share(ev: Dict[str, Any], region: str, inside: bool
                     ) -> Optional[float]:
    """Device 0's time in the collective operations (on the operation
    line, or in flight) whose path does (``inside``) or does not pass
    through ``region``, over busy time."""
    found = _own_and_busy(ev)
    if found is None:
        return None
    mine, busy = found
    coll = [e for e, _ in mine if trace.COLLECTIVE.search(e[3])]
    coll += scoped_trace(ev)["in_flight"]
    if not coll or not busy:
        return None
    return 100.0 * stats.union_seconds(
        (e[1], e[2]) for e in coll
        if (region in regions_of(e[4])) == inside) / busy


def unattributed_share(ev: Dict[str, Any]) -> Optional[float]:
    """Own time of the operations whose path names no region, over
    busy time."""
    found = _own_and_busy(ev)
    if found is None or not found[1]:
        return None
    mine, busy = found
    return 100.0 * sum(own for e, own in mine
                       if not regions_of(e[4])) / busy


def window(scoped: Dict[str, Any]) -> Tuple[float, float]:
    """First to last thing the profiler recorded, device or host."""
    spans = [(e[1], e[2]) for k in ("ops", "host") for e in scoped[k]]
    return min(a for a, _ in spans), max(b for _, b in spans)


def idle_intervals(scoped: Dict[str, Any]) -> List[Tuple[float, float]]:
    """Where device 0 ran nothing inside the traced slice."""
    lo, hi = window(scoped)
    out, at = [], lo
    for a, b in stats.merge((e[1], e[2]) for e in scoped["ops"]):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def uncovered_idle_share(ev: Dict[str, Any], annotations: Sequence[str]
                         ) -> Optional[float]:
    """Device 0's idle seconds inside the traced slice that none of the
    host ``annotations`` covers, over the slice."""
    scoped = scoped_trace(ev)
    if not scoped or not scoped["ops"]:
        return None
    cover = [(e[1], e[2]) for e in scoped["host"] if e[0] in annotations]
    if not cover:
        return None
    lo, hi = window(scoped)
    return 100.0 * stats.subtract_cover(idle_intervals(scoped), cover) / (
        hi - lo)


# -- by hand (tools/trace_regions.py) ---------------------------------------

def region_table(scoped: Dict[str, Any]) -> List[Tuple[str, str, str, float]]:
    """``(program, region or "-", "fwd"/"bwd", own seconds)``, longest
    first."""
    total: Dict[tuple, float] = {}
    for e, own in own_times(scoped["ops"]):
        inside = regions_of(e[4])
        key = (e[3].split(" ")[0], inside[-1] if inside else "-",
               "bwd" if BACKWARD in e[4] else "fwd")
        total[key] = total.get(key, 0.0) + own
    return sorted(((*k, v) for k, v in total.items()), key=lambda r: -r[3])


def unattributed_ops(scoped: Dict[str, Any], n: int = 3
                     ) -> List[Tuple[str, float]]:
    """The ``n`` operations under no region with most own time, named
    as ``harness/trace.top_ops`` names them (own time laid from the
    operation's start, so that nothing encloses anything)."""
    bare = [(e[0], e[1], e[1] + own, e[3])
            for e, own in own_times(scoped["ops"])
            if own > 0 and not regions_of(e[4])]
    return [tuple(r) for r in trace.top_ops({"devices": [bare]}, n)]


def longest_idle_gaps(scoped: Dict[str, Any], n: int = 5
                      ) -> List[Tuple[float, float, str]]:
    """``(start, seconds, annotation)`` of the ``n`` longest idle gaps:
    the innermost (shortest) host annotation that covers a gap's
    middle, ``"(no annotation)"`` otherwise."""
    out = []
    for a, b in sorted(idle_intervals(scoped), key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        over = [e for e in scoped["host"] if e[1] <= mid <= e[2]]
        name = min(over, key=lambda e: e[2] - e[1])[0] if over \
            else "(no annotation)"
        out.append((a, b - a, name))
    return out
