"""Reduction of a profiler trace to device numbers.

``Capture`` wraps ``jax.profiler`` around a slice of the measured
window; ``load()`` reads the ``.xplane.pb`` it leaves with nothing but
JAX (``jax.profiler.ProfileData``) into a small plain structure, and
the functions below reduce that structure. The structure is also what
``testdata/trace_small.json`` holds — a slice of a trace recorded on
the chip — so the reduction is checked without a chip.

Structure: ``{"devices": [[(name, start_s, end_s, detail), ...], ...],
"host": [(name, start_s, end_s), ...]}`` — one list of operation events
per device plane in device order, and the host's ``bench.*``
annotations, all on the profiler's one clock. On this runtime (jax
0.9.0, libtpu 0.0.34) an operation event is named by its whole HLO
text; ``name`` keeps the instruction's own name (``closed_call.11``,
``psum.264``) and ``detail`` what identifies it: the jitted program it
ran in (from the "XLA Modules" line), its HLO opcode (``all-reduce``),
the target of a custom call (``tpu_custom_call`` is a Pallas kernel)
and a fusion's kind. Loops and calls enclose their bodies, so events
nest.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.harness import stats

#: device planes are named "/device:TPU:<n>"; operations are the
#: events of the line named "XLA Ops", programs those of "XLA Modules"
#: (the other lines — steps, name scopes — cover the same time again,
#: and "Async XLA Ops" holds copies and collectives in flight)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
#: host annotations the benchmark's own files write
HOST_PREFIX = "bench."

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
#: the opcode follows the result shape: " all-reduce(", " fusion("
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"\bkind=(k\w+)")


class Capture:
    """Profile a slice ``[after_s, after_s + for_s)`` of the window.
    The job calls :meth:`tick` once per iteration of its loop; without
    ``--trace 1`` the runner passes ``None`` instead. ``started`` and
    ``stopped`` are ``time.monotonic`` readings, the jobs' clock."""

    def __init__(self, logdir: str, after_s: float, for_s: float):
        self.logdir = logdir
        self.after_s = after_s
        self.for_s = for_s
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None

    def tick(self, since_window_start: float) -> None:
        import jax

        if self.started is None:
            if since_window_start >= self.after_s:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0   # annotations, not frames
                jax.profiler.start_trace(self.logdir, profiler_options=opts)
                self.started = time.monotonic()
        elif time.monotonic() - self.started >= self.for_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.started is not None and self.stopped is None:
            self.stopped = time.monotonic()
            jax.profiler.stop_trace()


def annotate(name: str):
    """A host span on the profiler's clock (no-op cost when no trace
    is running)."""
    import jax

    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


def newest_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return paths[-1] if paths else None


def _describe(text: str, module: str) -> Tuple[str, str]:
    """``(name, detail)`` of an operation event from its printed text."""
    name, _, rest = text.partition(" = ")
    found = [rx.search(rest) for rx in (_OPCODE, _TARGET, _KIND)]
    detail = " ".join([module or "-"] + [m.group(1) for m in found if m])
    return name.lstrip("%"), detail


def load(logdir: str) -> Optional[Dict[str, Any]]:
    """The newest trace under ``logdir`` in the plain structure, or
    None when the profiler wrote none."""
    from jax.profiler import ProfileData

    path = newest_xplane(logdir)
    if path is None:
        return None
    data = ProfileData.from_file(path)
    devices: Dict[int, List[tuple]] = {}
    in_flight: Dict[int, List[tuple]] = {}
    host: List[tuple] = []
    sec = lambda ev: (ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (*sec(ev), re.sub(r"\(\d+\)$", "", ev.name))
                for ev in lines[MODULES_LINE].events
            ) if MODULES_LINE in lines else []
            ops, i = [], 0
            for ev in sorted(lines[OPS_LINE].events,
                             key=lambda e: e.start_ns
                             ) if OPS_LINE in lines else []:
                a, b = sec(ev)
                while i < len(modules) and modules[i][1] <= a:
                    i += 1
                inside = i < len(modules) and modules[i][0] <= a
                name, detail = _describe(
                    ev.name, modules[i][2] if inside else "")
                ops.append((name, a, b, detail))
            devices[int(m.group(1))] = ops
            in_flight[int(m.group(1))] = [
                (name, *sec(ev))
                for ev in (lines[ASYNC_LINE].events
                           if ASYNC_LINE in lines else [])
                for name, detail in [_describe(ev.name, "")]
                if COLLECTIVE.search(detail)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name[len(HOST_PREFIX):], *sec(ev)))
    order = sorted(devices)
    return {"devices": [devices[k] for k in order],
            "in_flight": [sorted(in_flight[k], key=lambda e: e[1])
                          for k in order],
            "host": sorted(host, key=lambda e: e[1])}


def save_plain(trace: Dict[str, Any], path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def load_plain(path: str) -> Dict[str, Any]:
    with open(path) as f:
        t = json.load(f)
    return {"devices": [[tuple(e) for e in d] for d in t["devices"]],
            "in_flight": [[tuple(e) for e in d]
                          for d in t.get("in_flight", [])],
            "host": [tuple(e) for e in t["host"]]}


def window_seconds(trace: Dict[str, Any]) -> float:
    """Length of the traced window on the profiler's clock: from the
    first to the last thing it recorded, device operation or host
    annotation (the jobs' loops are annotated end to end, so an idle
    device at either edge still counts)."""
    starts = [d[0][1] for d in trace["devices"] if d]
    ends = [max(e[2] for e in d) for d in trace["devices"] if d]
    if trace["host"]:
        starts.append(trace["host"][0][1])
        ends.append(max(e[2] for e in trace["host"]))
    return max(ends) - min(starts) if starts else 0.0


def busy_seconds(trace: Dict[str, Any]) -> float:
    """Seconds in which an operation ran, averaged over the devices
    (union of the operation intervals of each)."""
    per = [stats.union_seconds((e[1], e[2]) for e in d)
           for d in trace["devices"]]
    return sum(per) / len(per) if per else 0.0


def leaves(ops: Sequence[tuple]) -> List[tuple]:
    """The operations that enclose no other (``ops`` sorted by start):
    a loop or a call is its body's events, which are what ran."""
    out: List[tuple] = []
    stack: List[tuple] = []
    for e in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and e[1] >= stack[-1][2]:
            out_of = stack.pop()
            if out_of[4]:
                out.append(out_of[:4])
        if stack:
            stack[-1] = (*stack[-1][:4], False)
        stack.append((*e[:4], True))
    out.extend(x[:4] for x in stack if x[4])
    return sorted(out, key=lambda e: e[1])


def matching(ops: Iterable[tuple], patterns: Sequence[str]) -> List[tuple]:
    """Operations whose ``"<detail> <name>"`` matches any of the regular
    expressions, e.g. ``step_local.*tpu_custom_call``."""
    rx = [re.compile(p) for p in patterns]
    return [e for e in ops if any(r.search(f"{e[3]} {e[0]}") for r in rx)]


def kernel_seconds(trace: Dict[str, Any], patterns: Sequence[str]
                   ) -> Optional[float]:
    """Device seconds of the operations matching ``patterns``, averaged
    over devices; None when the trace names no such operation."""
    per = []
    found = False
    for d in trace["devices"]:
        hit = matching(d, patterns)
        found = found or bool(hit)
        per.append(stats.union_seconds((e[1], e[2]) for e in hit))
    if not found:
        return None
    return sum(per) / len(per)


def collective_seconds(trace: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """``(seconds in collective operations, seconds of them with no
    other operation running)`` on device 0; None where there is none.
    A collective is an operation of such an opcode (its instruction may
    be named anything: ``psum.264``) on the operation line, or one in
    flight on the asynchronous line."""
    if not trace["devices"]:
        return None
    ops = leaves(trace["devices"][0])
    coll = [(e[1], e[2]) for e in ops if COLLECTIVE.search(e[3])]
    if trace.get("in_flight"):
        coll += [(e[1], e[2]) for e in trace["in_flight"][0]]
    if not coll:
        return None
    rest = [(e[1], e[2]) for e in ops if not COLLECTIVE.search(e[3])]
    return (stats.union_seconds(coll), stats.subtract_cover(coll, rest))


def top_ops(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """The device operations that took most time on device 0, enclosing
    loops left out, instance numbers folded (``fusion.12`` ->
    ``fusion``), named ``<program>:<instruction>[<custom-call target,
    fusion kind, or opcode where the name does not say it>]``."""
    if not trace["devices"]:
        return []
    total: Dict[str, float] = {}
    for name, a, b, detail in leaves(trace["devices"][0]):
        module, *what = detail.split(" ")
        base = re.sub(r"[.\d]+$", "", name) or name
        tag = what[-1] if what else ""
        key = f"{module}:{base}" + (f"[{tag}]" if tag not in ("", base)
                                    else "")
        total[key] = total.get(key, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """The device's idle time by what the host was doing: each gap
    between operations on device 0 is charged to the host annotation
    that covers most of it (``"(no annotation)"`` otherwise); seconds
    summed per annotation name, longest first."""
    if not trace["devices"] or not trace["devices"][0]:
        return []
    busy = stats.merge((e[1], e[2]) for e in trace["devices"][0])
    host = trace["host"]
    total: Dict[str, float] = {}
    for (_, end), (start, _) in zip(busy, busy[1:]):
        gap = start - end
        best, best_cover = "(no annotation)", 0.0
        for name, a, b in host:
            if a >= start:
                break
            cover = min(b, start) - max(a, end)
            if cover > best_cover:
                best, best_cover = name, cover
        total[best] = total.get(best, 0.0) + gap
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]
