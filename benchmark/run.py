#!/usr/bin/env python3
"""The benchmark: one process, one cell, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout on a machine that holds the chips the cell
asks for. Everything the cell is made of is data under ``benchmark/``
found by the names in ``BENCHMARK.json`` (see harness/recipe.py).
Progress goes to stderr; the last line of stdout is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, when
traced, ``breakdown``. ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled slice of
the same window. Without a TPU, or with fewer chips than the cell
needs, it exits non-zero before timing anything.

``--tiny-cpu`` is for the sandbox only: it relaxes the device gate and
shrinks every size (harness/tiny.py) so that the same code runs end to
end on the CPU with the Pallas kernels interpreted. Such a run prints
counts and no number under a device metric's name.
"""

import time

START = time.monotonic()    # set-up runs from here to the window's start

import argparse
import json
import math
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import device as device_mod  # noqa: E402
from benchmark.harness import recipe, tiny, trace   # noqa: E402
from benchmark.harness.recipe import log            # noqa: E402

#: seconds of the window a traced run profiles, starting a third in
TRACE_SECONDS = 4.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="sandbox only: tiny sizes on the CPU, no device "
                    "metric is printed")
    ap.add_argument("--keep-trace", metavar="PATH", default=None,
                    help="by hand: also write the traced slice in the "
                    "plain structure of harness/trace.py to PATH, and "
                    "the profiler's own file to PATH.xplane.pb")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "apex_tpu")):
        raise SystemExit("benchmark: no program beside the benchmark "
                         f"(no apex_tpu/ under {REPO})")
    cell = recipe.load_cell(args.workload)
    if args.tiny_cpu:
        tiny.shrink(cell)
    dev = device_mod.gate(cell["chips"], tiny_cpu=args.tiny_cpu)
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}; cell {cell['name']} seed {args.seed}")

    from apex_tpu._capabilities import enable_compilation_cache
    from apex_tpu.telemetry import RecompileSentinel

    log(f"compile cache: {enable_compilation_cache() or 'disabled'}")
    sentinel = RecompileSentinel().install()
    traced = bool(args.trace)
    job = cell["job"].Job(cell, dev, args.seed)
    tracedir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        job.setup(traced=traced)
        job.warm(args.seconds)
        setup_s = time.monotonic() - START
        before = sentinel.compiles_total()
        capture = None
        if traced:
            span = min(TRACE_SECONDS, args.seconds / 3.0)
            capture = trace.Capture(tracedir, args.seconds / 3.0, span)
        job.measure(args.seconds, capture)
        after = sentinel.compiles_total()
        job.finish()
        compiles = after["backend_compiles"] - before["backend_compiles"]
        if compiles:
            job.problems.append(f"{compiles} compilations inside the window")
        log(f"setup {setup_s:.1f} s ({before['cache_hits']} cache hits, "
            f"{before['cache_misses']} misses, "
            f"{before['compile_seconds']:.1f} s compiling); window "
            f"{job.window['seconds']:.2f} s, {compiles} compiles in it")
        log("setup parts: " + ", ".join(
            f"{k} {v:.1f}" for k, v in job.setup_parts.items()))
        for p in job.problems:
            log(f"NOT CORRECT: {p}")

        device = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"]}
        device.update(device_mod.memory_peak_bytes(
            dev["devices"], job.plan_bytes))
        line = {"correct": not job.problems, "attempted": job.attempted,
                "failed": job.failed, "metrics": {}, "device": device}
        measured = not args.tiny_cpu
        if not traced:
            values = dict(job.end_to_end, setup_s=setup_s)
            wanted = cell["bench"]["end_to_end"]
        else:
            tr = trace.load(tracedir)
            if tr is not None and args.keep_trace:
                trace.save_plain(tr, args.keep_trace)
                shutil.copy(trace.newest_xplane(tracedir),
                            args.keep_trace + ".xplane.pb")
            evidence = dict(
                job.evidence, trace=tr, capture=capture, window=job.window,
                shape=job.shape, peaks=dev["peaks"], chips=dev["count"],
                spans=None if getattr(job, "spans", None) is None
                else job.spans.events(), setup_s=setup_s,
                plan_bytes=job.plan_bytes, compiles_in_window=compiles,
                compile_s=before["compile_seconds"])
            values = layer_values(cell, evidence)
            wanted = cell["bench"]["per_layer"]
            if tr is not None and tr["devices"]:
                device["busy_s"] = trace.busy_seconds(tr)
                device["window_s"] = trace.window_seconds(tr)
                line["breakdown"] = {"device_ops": trace.top_ops(tr),
                                     "idle_gaps": trace.idle_gaps(tr)}
            elif measured:
                raise SystemExit("benchmark: the traced run recorded no "
                                 "device operation")
        if measured:
            for spec in wanted:
                v = values.get(spec["name"])
                if applies(spec, cell["name"]) and v is not None:
                    line["metrics"][spec["name"]] = {
                        "value": v if math.isfinite(v) else 1e30,
                        "unit": spec["unit"]}
        else:
            # a CPU rehearsal prints counts and which metrics it could
            # compute, never a value under a metric's name
            line["rehearsal"] = sorted(
                k for k, v in values.items() if v is not None)
        print(json.dumps(line), flush=True)
        return 0
    finally:
        job.close()
        sentinel.uninstall()
        if tracedir is not None:
            shutil.rmtree(tracedir, ignore_errors=True)


def applies(spec, cell_name: str) -> bool:
    return "workloads" not in spec or cell_name in spec["workloads"]


def layer_values(cell, evidence):
    """Every per-layer metric whose file applies to the cell's job kind
    and chip count; a reader that finds nothing to read returns None
    and the metric is left out of the line."""
    out = {}
    for spec in recipe.layer_metric_specs(cell["traffic"]["kind"],
                                          cell["chips"]):
        reader, params = recipe.reader_of(spec)
        out[spec["name"]] = reader(evidence, **params)
    return out


if __name__ == "__main__":
    sys.exit(main())
