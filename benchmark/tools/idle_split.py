#!/usr/bin/env python3
"""Run one benchmark cell and say where its chip sat idle.

    python3 benchmark/tools/idle_split.py \
        --out _scratch/idle.jsonl -- \
        --workload gpt2m_chat --seed 7 --seconds 40 --trace 1

``benchmark/run.py`` runs with the arguments after ``--`` and prints
its result line as ever; this appends one JSON object to ``--out``:

- ``tick_ms_p50``: the window's median ``Scheduler.step`` on the
  benchmark's own clock, kept traced and untraced alike — traced less
  untraced is what the recorder and the profiler cost a tick;
- a traced run's ring (rows kept, dropped) and how well its clock rows
  hold: each recorder ``sched.step`` row inside the slice, put on the
  trace's axis, less the start of its ``apex.sched.step`` annotation
  (median and 99th percentile of the absolute difference, in us);
- the slice's idle seconds split into no work and work
  (``layer_metrics/readers/idle.py``), the latter by the host's phase,
  and ``closure``: idle with work plus idle without, less all idle,
  over the slice, in percent.

A program without clock rows gives the tick's median and the ring alone.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: the host phases the idle with work is split by, as annotated
PHASES = {
    "fetch": ("apex.engine.fetch", "apex.engine.fetch.wait",
              "apex.engine.fetch.copy"),
    "fetch_wait": ("apex.engine.fetch.wait",),
    "fetch_copy": ("apex.engine.fetch.copy",),
    "admit": ("apex.sched.admit",),
    "housekeeping": ("apex.sched.housekeeping",),
    "dispatch": ("apex.sched.dispatch",),
    "collect": ("apex.sched.collect",),
    "publish": ("apex.sched.publish",),
    "in_ticks": ("apex.sched.step",),
    "submit": ("apex.sched.submit",),
}


def split(ev):
    """The clock check and the idle split of one traced run."""
    from benchmark.harness import stats
    from benchmark.layer_metrics.readers import idle, regions

    out = {}
    res = idle.step_clock_residuals_us(ev)
    if res:
        a = [abs(x) for x in res]
        out["clock_us"] = {"n": len(a), "median_abs": stats.median(a),
                           "p99_abs": stats.percentile(a, 99),
                           "max_abs": max(a),
                           "median": stats.median(res)}
    scoped = regions.scoped_trace(ev)
    at = idle.to_trace(ev)
    found = idle.idle_with_work(ev)
    if found is None or at is None:
        return out
    lo, hi, inside = found
    every = regions.idle_intervals(scoped)
    work = idle.work_intervals(ev, at, lo, hi)
    total = stats.union_seconds(every)
    with_work = stats.union_seconds(inside)
    no_work = stats.subtract_cover(every, work)
    seconds = {"slice": hi - lo, "busy": (hi - lo) - total, "idle": total,
               "no_work": no_work, "with_work": with_work}
    for name, names in PHASES.items():
        seconds[name] = with_work - stats.subtract_cover(
            inside, idle._annotated(ev, names))
    out["idle_s"] = seconds
    out["closure_pct"] = 100.0 * (with_work + no_work - total) / (hi - lo)
    out["chunks"] = sum(1 for _, b in idle._annotated(
        ev, ("apex.engine.fetch",)) if lo <= b <= hi)
    out["requests_in_slice"] = len(work)
    out["oldest_row_before_slice_s"] = lo - at(
        min(e[1] for e in ev["spans"]))
    return out


def main() -> int:
    argv = sys.argv[1:]
    if "--" not in argv or argv.index("--") != 2 or argv[0] != "--out":
        raise SystemExit(__doc__.split("\n\n")[1])
    path, run_argv = argv[1], argv[3:]

    from benchmark import run
    from benchmark.harness import stats
    from benchmark.jobs import serve_base

    seen = {}
    finish = serve_base.ServeJob.finish
    layer_values = run.layer_values

    def keep_job(job):
        seen["job"] = job
        return finish(job)

    def keep_split(cell, ev):
        # while the profile is still on disk
        seen["split"] = split(ev)
        return layer_values(cell, ev)

    serve_base.ServeJob.finish = keep_job
    run.layer_values = keep_split
    rc = run.main(run_argv)
    out = {"argv": run_argv, "rc": rc}
    job = seen.get("job")
    if job is not None:
        lo, hi = job.window["start"], job.window["end"]
        ticks = [(b - a) * 1e3 for a, b in job.ticks if lo <= a < hi]
        out["ticks"] = len(ticks)
        out["tick_ms_p50"] = stats.median(ticks)
        if job.spans is not None:
            out["ring"] = job.spans.summary()
    out.update(seen.get("split") or {})
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(out) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
