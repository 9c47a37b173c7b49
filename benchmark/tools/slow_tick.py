#!/usr/bin/env python3
"""Catch a slow tick of a closed-loop serving cell with its phases on.

    chiprun -- python3 benchmark/tools/slow_tick.py \
        --cell gpt2m_score_offline --seeds 301-312 --seconds 40

One process and one engine (so one compilation): for each seed the
cell's own job kind serves its ramp and a window of ``--seconds`` with
the program's span recorder attached and no profiler, then the engine
drains. Per run one line: the window's ticks, the median and the
slowest ``sched.step`` section, the slowest one's time phase by phase
(a phase's self time is its section less the sections that name it as
their parent, so ``engine.fetch`` — the blocking wait for the device —
stands beside ``sched.collect``'s own unpacking), what it admitted
(the ``prefill.*`` counts inside it) and the collector's pauses inside
it. A slow tick whose time sits in ``engine.fetch`` waited
for the runtime or the shared host; anything else is the program's. Run
by hand when a run of the cell loses a wave (PERF.md, open questions).
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

SECTION, COUNT = 1, 2


def self_times(rows, top):
    """``{section name: seconds not inside a child}`` of the sections
    recorded inside ``top`` (a section row) and of ``top`` itself."""
    _, t0, name, t1, _ = top
    inside = [e for e in rows if e[0] == SECTION and e is not top
              and t0 <= e[1] and e[3] <= t1]
    out = {}
    for e in inside + [top]:
        kids = sum(k[3] - k[1] for k in inside if k[4] == e[2]
                   and e[1] <= k[1] and k[3] <= e[3] and k is not e)
        out[e[2]] = out.get(e[2], 0.0) + (e[3] - e[1]) - kids
    return out


def parse_seeds(text):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", default="301-312")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark.harness import device as device_mod
    from benchmark.harness import recipe, stats, tiny
    from benchmark.jobs.serve_base import clock

    cell = recipe.load_cell(args.cell)
    if cell["traffic"]["kind"] != "serve_closed":
        raise SystemExit("slow_tick: the cell is not a closed loop")
    if args.tiny_cpu:
        tiny.shrink(cell)
    dev = device_mod.gate(cell["chips"], tiny_cpu=args.tiny_cpu)

    from apex_tpu._capabilities import enable_compilation_cache
    from apex_tpu.serving import Scheduler
    from apex_tpu.telemetry.spans import SpanRecorder

    enable_compilation_cache()
    first = None
    for seed in parse_seeds(args.seeds):
        job = cell["job"].Job(cell, dev, seed)
        job.setup(share=first)
        first = first or job
        # spans on, profiler off: the recorder a traced run attaches
        job.spans = SpanRecorder(capacity=1 << 18)
        job.sched = Scheduler(job.engine, spans=job.spans)
        job.warm(args.seconds)
        job.measure(args.seconds, None)
        lo, hi = job.window["start"], job.window["end"]
        rows = job.spans.events()
        if job.spans.summary()["events_dropped"]:
            raise SystemExit("slow_tick: the span ring overflowed")
        steps = [e for e in rows if e[0] == SECTION
                 and e[2] == "sched.step" and lo <= e[1] < hi]
        if not steps:
            raise SystemExit("slow_tick: the program records no "
                             "sched.step section")
        slow = max(steps, key=lambda e: e[3] - e[1])
        parts = self_times(rows, slow)
        pauses = [d for t, d in job.gc_pauses if slow[1] <= t < slow[3]]
        admitted = {}
        for e in rows:
            if e[0] == COUNT and slow[1] <= e[1] <= slow[3]:
                admitted[e[2]] = admitted.get(e[2], 0) + e[3]
        print(json.dumps({
            "seed": seed, "ticks": len(steps),
            "step_ms_p50": stats.median(
                e[3] - e[1] for e in steps) * 1e3,
            "step_ms_max": (slow[3] - slow[1]) * 1e3,
            "at_s": slow[1] - lo,
            "self_ms": {k: v * 1e3 for k, v in sorted(
                parts.items(), key=lambda kv: -kv[1])},
            "admitted": admitted,
            "gc_pauses_inside_ms": [p * 1e3 for p in pauses],
            "gc_ms_in_window": sum(d for t, d in job.gc_pauses
                                   if lo <= t < hi) * 1e3,
            "serve_tokens_per_s": job.end_to_end["serve_tokens_per_s"],
        }), flush=True)
        # drain, so the next seed starts on an empty engine
        job.offer = lambda now: None
        limit = clock() + 120.0
        while not job.sched.idle() and clock() < limit:
            job.tick()
    first.close()


if __name__ == "__main__":
    main()
