#!/usr/bin/env python3
"""Compile a ``serve_docqa`` cell's programs at real size for a
described v5e, no chip (the sibling of ``aot_plan.py`` for an engine
whose admission programs take block tables and start positions).

    python3 benchmark/tools/aot_plan_docqa.py --cell dsv32_docqa_shared --slots 128

Prints the compiler's ``memory_analysis`` of the decode step, of every
admission program and of the fill programs, and how long each took to
compile; the ``plan`` block of the cell's recipe comes from here. A
compile that passes is not a chip run.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.docqa_plan import programs        # noqa: E402
from benchmark.tools.aot_plan import GIB, describe, show, total  # noqa: E402


def plan(cell, devices, slots, only=None):
    import jax
    from jax.sharding import NamedSharding

    from apex_tpu import mesh as mx
    from apex_tpu.models import gpt
    from apex_tpu.serving.engine import Engine
    from benchmark.jobs import serve_base

    class PlanEngine(Engine):
        def _build(self):
            super()._build()
            self.init_program = self._init
            self._init = lambda params: (None, None)

    cell["recipe"] = dict(cell["recipe"], engine=dict(
        cell["recipe"]["engine"], slots=slots))
    cfg, ecfg = serve_base.engine_setup(cell)
    mesh = mx.build_mesh(tp=1, devices=list(devices)[:1])
    params = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0))),
        gpt.param_specs(cfg))
    eng = PlanEngine(cfg, params, mesh, ecfg)
    cache, state = jax.eval_shape(eng.init_program, params)
    nbytes = lambda tree: sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    out = {"slots": slots, "programs": {},
           "weights_bytes": nbytes(params), "cache_bytes": nbytes(cache)}
    print(f"  weights {out['weights_bytes'] / GIB:.2f} GiB, cache "
          f"{out['cache_bytes'] / GIB:.2f} GiB", flush=True)
    for name, (fn, args) in programs(eng, params, cache, state).items():
        if only and not any(name.startswith(o) for o in only):
            continue
        t0 = time.time()
        compiled = fn.lower(*args).compile()
        d = describe(compiled)
        show(f"{name} slots={slots}", d, time.time() - t0)
        out["programs"][name] = d
    worst = max(out["programs"], key=lambda n: total(out["programs"][n]))
    out["plan_bytes"] = total(out["programs"][worst])
    out["largest"] = worst
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--slots", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated prefixes of program names")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    from benchmark.harness import recipe

    cell = recipe.load_cell(args.cell)
    devices = topo.devices[:1]
    print(f"{args.cell}: one described chip {devices[0].device_kind}",
          flush=True)
    for s in ([int(x) for x in args.slots.split(",")] if args.slots
              else [cell["recipe"]["engine"]["slots"]]):
        r = plan(cell, devices, s, args.only and args.only.split(","))
        print(json.dumps({"cell": args.cell, **{
            k: v for k, v in r.items() if k != "programs"},
            "plan_gib": r["plan_bytes"] / GIB}))


if __name__ == "__main__":
    main()
