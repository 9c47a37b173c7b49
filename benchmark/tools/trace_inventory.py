#!/usr/bin/env python3
"""Look at a profiler trace by hand before trusting a reduction of it.

    python3 benchmark/tools/trace_inventory.py <file.xplane.pb> [top]

Prints every plane and line with its event count and summed duration,
and for each device's operation line the names that took most time with
one event's stats — which planes are devices, which lines repeat the
same time, and how the kernels are named on this runtime.
"""

import re
import sys


def main():
    from jax.profiler import ProfileData

    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            total = sum(e.duration_ns for e in events) * 1e-9
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{total:.4f} s summed")
            if not plane.name.startswith("/device:") or not events:
                continue
            by = {}
            for e in events:
                key = re.sub(r"[.\d]+$", "", e.name) or e.name
                rec = by.setdefault(key, [0.0, 0, e])
                rec[0] += e.duration_ns * 1e-9
                rec[1] += 1
            for key, (secs, n, e) in sorted(
                    by.items(), key=lambda kv: -kv[1][0])[:top]:
                stats = {k: str(v)[:160] for k, v in e.stats}
                print(f"    {secs:9.5f} s {n:6d} x {key}  e.g. {e.name} "
                      f"{stats}")


if __name__ == "__main__":
    main()
