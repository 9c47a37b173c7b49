#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: a few fixed rates, once.

    chiprun -- python3 benchmark/tools/knee_sweep.py --cell gpt2m_chat \
        --rates 3,4,5,6,7,8 --seconds 20

One process and one engine (so one compilation): for each rate the
cell's own job kind serves its ramp and a window of ``--seconds`` at that
rate with the cell's lengths, then the engine drains. The knee is the
highest rate at which what completes keeps up with what is offered and
the wait for a slot stays flat; the cell's traffic file then states 0.8
of it as a number, with the date. The benchmark never searches for a
rate: this script is run by hand when the cell is defined, and again by
a later benchmark PR once an optimisation has moved the knee.
"""

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark.harness import device as device_mod
    from benchmark.harness import recipe, stats, tiny
    from benchmark.jobs.serve_base import clock

    cell = recipe.load_cell(args.cell)
    if cell["traffic"]["kind"] != "serve_open":
        raise SystemExit("knee_sweep: the cell is not an open loop")
    if args.tiny_cpu:
        tiny.shrink(cell)
    dev = device_mod.gate(cell["chips"], tiny_cpu=args.tiny_cpu)

    from apex_tpu._capabilities import enable_compilation_cache

    enable_compilation_cache()
    first = None
    print("rate_per_s due done failed refused ttft_p50_ms ttft_p90_ms "
          "tpot_p50_ms tpot_p90_ms out_tokens_per_s backlog_at_end",
          flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell["traffic"] = dict(cell["traffic"], arrivals=dict(
            cell["traffic"]["arrivals"], rate_per_s=rate))
        job = cell["job"].Job(cell, dev, args.seed + i)
        job.setup(share=first)
        first = first or job
        job.warm(args.seconds)
        job.measure(args.seconds, None)
        backlog = len(job.sched.queue)
        # drain, so the next rate starts on an empty engine
        limit = clock() + 120.0
        while not job.sched.idle() and clock() < limit:
            job.tick()
        ev = job.evidence
        pct = lambda key, q: (stats.percentile(ev[key], q) or 0.0) * 1e3
        row = {"rate_per_s": rate, "due": job.attempted,
               "done": ev["requests_done"], "failed": job.failed,
               "refused": job.refused,
               "ttft_p50_ms": pct("ttft_s", 50),
               "ttft_p90_ms": pct("ttft_s", 90),
               "tpot_p50_ms": pct("tpot_s", 50),
               "tpot_p90_ms": pct("tpot_s", 90),
               "out_tokens_per_s": ev["tokens_in_window"] / args.seconds,
               "backlog_at_end": backlog}
        print(" ".join(f"{v:.1f}" if isinstance(v, float) else str(v)
                       for v in row.values()), flush=True)
    first.close()


if __name__ == "__main__":
    main()
