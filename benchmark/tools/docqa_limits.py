#!/usr/bin/env python3
"""Where the ``serve_docqa`` comparison's limits come from: the cell's
ramp and window served on the chip, then the streamed log-probabilities
of its reference requests against the plain reference six ways — as it
is (float32); with every matmul operand rounded to bfloat16, which is
how the engine computes (what rounding alone moves); rounded to float8
(the nearest precision below the configuration's bfloat16); and as three
deliberately wrong models (the most recent top-k attended instead of
the indexer's choice; the indexer's ReLU dropped; the routed weights
left unnormalised). Each goes through the job's own ``judge``: the
first two must come out correct, the other four not.

    python3 benchmark/tools/docqa_limits.py --workload dsv32_docqa_shared --seed 7 --seconds 40

The limits of ``benchmark/jobs/serve_docqa.py`` lie between the first
line's readings and the last four's. ``--tiny-cpu`` rehearses it: a
rehearsal computes in float32 and holds both numbers to 1e-4, so there
the bfloat16 reference is a lower precision too and must be rejected.
The exit code is 1 if any line came out the other way.
"""

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import device as device_mod  # noqa: E402
from benchmark.harness import recipe, tiny          # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--wrong-requests", type=int, default=2,
                    help="reference requests the wrong models and the "
                    "float8 reference are run on (one from the head of "
                    "the stream first; the sound references: all)")
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args()
    cell = recipe.load_cell(args.workload)
    if args.tiny_cpu:
        tiny.shrink(cell)
    dev = device_mod.gate(cell["chips"], tiny_cpu=args.tiny_cpu)
    from apex_tpu._capabilities import enable_compilation_cache

    enable_compilation_cache()
    import jax.numpy as jnp
    import numpy as np

    job = cell["job"].Job(cell, dev, args.seed)
    job.setup(traced=False)
    job.warm(args.seconds)
    job.measure(args.seconds, None)
    recipe.log("setup parts: " + ", ".join(
        f"{k} {v:.1f}" for k, v in job.setup_parts.items()))
    sample = job.reference_sample()
    few = sample[::2][:args.wrong_requests] if args.wrong_requests < len(
        sample) else sample
    wrong = 0
    for name, reqs, sound, kw in (
            ("float32 reference", sample, True, {}),
            ("bfloat16 reference", sample, not job.tiny,
             {"round_to": jnp.bfloat16}),
            ("float8_e4m3 reference", few, False,
             {"round_to": jnp.float8_e4m3fn}),
            ("wrong model, most recent top-k", few, False,
             {"variant": "recent"}),
            ("wrong model, no ReLU in the indexer", few, False,
             {"variant": "no_relu"}),
            ("wrong model, routed weights not renormalised", few, False,
             {"variant": "no_renorm"})):
        d = job.reference_diffs(reqs, **kw)
        problems = job.judge(d, len(sample))
        wrong += bool(problems) == sound
        print(f"{name}: {len(reqs)} requests, {d.size} tokens, |logprob "
              f"diff| mean {d.mean():.4e} median {np.median(d):.4e} "
              f"worst {d.max():.4e} over 1: {100 * (d > 1).mean():.1f} % "
              f"-> correct: {str(not problems).lower()}"
              + ("" if bool(problems) != sound else "   <- NOT AS IT SHOULD"),
              flush=True)
    job.close()
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
