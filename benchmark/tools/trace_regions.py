#!/usr/bin/env python3
"""Name the time of a kept profile: regions, what has none, idle gaps.

    python3 benchmark/tools/trace_regions.py <file.xplane.pb> [top]

Prints, for device 0 of a profile kept with ``run.py --keep-trace``:
every (program, region, pass) with its share of busy time; the
operations under no region that took most time; and the longest idle
gaps with the innermost host annotation (``apex.sched.*``,
``apex.engine.*``, ``bench.*``) that covers each — what PERF.md quotes.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main():
    from benchmark.harness import stats
    from benchmark.layer_metrics.readers import regions

    scoped = regions.load(sys.argv[1])
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    busy = stats.union_seconds((e[1], e[2]) for e in scoped["ops"])
    lo, hi = regions.window(scoped)
    print(f"device 0: busy {busy:.4f} s of {hi - lo:.4f} s traced")
    for program, region, way, secs in regions.region_table(scoped):
        print(f"  {100 * secs / busy:7.3f} %  {program} {region} {way}")
    print("under no region:")
    for name, secs in regions.unattributed_ops(scoped, top):
        print(f"  {100 * secs / busy:7.3f} %  {name}")
    print("longest idle gaps:")
    for start, secs, name in regions.longest_idle_gaps(scoped, top):
        print(f"  {secs * 1e3:9.3f} ms at {start - lo:8.4f} s  {name}")


if __name__ == "__main__":
    main()
