#!/usr/bin/env python3
"""Compile a cell's programs at real size for a described v5e, no chip.

    python3 benchmark/tools/aot_plan.py --cell gpt2m_chat --slots 32,40
    python3 benchmark/tools/aot_plan.py --cell gpt2l_train_tp2dp2 --batch 16,32

The TPU compiler is installed in the sandbox and compiles for the
compile-only ``v5e:2x2`` topology (``JAX_PLATFORMS=cpu`` stays set, so
nothing runs). For each candidate the script prints the compiler's
``memory_analysis`` of every program of the cell — the train step, or
the engine's decode step and each admission program — which is how
``slots`` and the four-chip batch are chosen before any chip time is
spent, and where the ``plan`` block of a cell's recipe comes from. What
the compiler refuses here (a kernel Mosaic rejects, a plan over 16 GB)
the chip would refuse too. A compile that passes is not a chip run.
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

GIB = 2.0 ** 30


def describe(compiled):
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "donated": m.alias_size_in_bytes,
            "code": m.generated_code_size_in_bytes}


def total(d):
    return (d["arguments"] + d["outputs"] + d["temporaries"] + d["code"]
            - d["donated"])


def show(name, d, seconds):
    print(f"  {name:18s} plan {total(d) / GIB:6.2f} GiB  (arguments "
          f"{d['arguments'] / GIB:.2f}, temporaries "
          f"{d['temporaries'] / GIB:.2f}, donated {d['donated'] / GIB:.2f})"
          f"  compiled in {seconds:.0f} s", flush=True)


def plan_train(cell, devices, batch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmark.jobs import train

    cell["recipe"] = dict(cell["recipe"], batch=batch)
    b = train.build(cell, devices)
    state = jax.eval_shape(b["init_fn"], jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct(
        (batch, b["seq"]), jnp.int32,
        sharding=NamedSharding(b["mesh"], P("dp", None)))
    t0 = time.time()
    compiled = b["step_fn"].lower(state, tok, tok).compile()
    d = describe(compiled)
    show(f"train step b={batch}", d, time.time() - t0)
    text = compiled.as_text()
    colls = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
             for k in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute")}
    print(f"  collectives in the program: {colls}; Pallas custom calls: "
          f"{text.count('tpu_custom_call')}")
    return {"batch": batch, "train_step": d, "plan_bytes": total(d)}


def plan_serve(cell, devices, slots, all_admits):
    import jax
    from jax.sharding import NamedSharding

    from apex_tpu import mesh as mx
    from apex_tpu.models import gpt
    from apex_tpu.serving.engine import Engine
    from benchmark.harness import plan as plan_mod
    from benchmark.jobs import serve_base

    class PlanEngine(Engine):
        """An engine whose programs are built and never run: nothing
        can be placed on a described device."""

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
    progs = plan_mod.engine_programs(eng, params, cache, state)
    names = list(progs) if all_admits else list(
        plan_mod.largest_engine_programs(eng))
    out = {"slots": slots, "programs": {}}
    for name in names:
        fn, args = progs[name]
        t0 = time.time()
        compiled = fn.lower(*args).compile()
        d = describe(compiled)
        show(f"{name} slots={slots}", d, time.time() - t0)
        print(f"    Pallas custom calls: "
              f"{compiled.as_text().count('tpu_custom_call')}")
        out["programs"][name] = d
    worst = max(out["programs"], key=lambda n: total(out["programs"][n]))
    out["plan_bytes"] = total(out["programs"][worst])
    out["largest"] = worst
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--slots", default=None,
                    help="serving: comma-separated slot counts to plan "
                    "(default: the recipe's)")
    ap.add_argument("--batch", default=None,
                    help="training: comma-separated global batches to "
                    "plan (default: the recipe's)")
    ap.add_argument("--all-admits", action="store_true",
                    help="serving: every (bucket, k) admission program, "
                    "not only the widest")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    import apex_tpu.kernels._utils as ku

    # this process's default backend is the CPU, and the kernels ask it
    # (once, cached) whether to run interpreted; the programs here are
    # compiled for the described TPU, so answer for that
    real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        if ku.use_interpret():
            raise SystemExit("aot_plan: kernels would be interpreted")
    finally:
        jax.default_backend = real_backend
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")

    from benchmark.harness import recipe

    cell = recipe.load_cell(args.cell)
    devices = topo.devices[:cell["chips"]]
    print(f"{args.cell}: {cell['chips']} described chip(s) "
          f"{devices[0].device_kind}", flush=True)
    results = []
    if cell["traffic"]["kind"] == "train":
        for b in ([int(x) for x in args.batch.split(",")] if args.batch
                  else [cell["recipe"]["batch"]]):
            results.append(plan_train(cell, devices, b))
    else:
        for s in ([int(x) for x in args.slots.split(",")] if args.slots
                  else [cell["recipe"]["engine"]["slots"]]):
            results.append(plan_serve(cell, devices, s, args.all_admits))
    for r in results:
        print(json.dumps({"cell": args.cell, **{
            k: v for k, v in r.items() if k != "programs"},
            "plan_gib": r["plan_bytes"] / GIB}))


if __name__ == "__main__":
    main()
