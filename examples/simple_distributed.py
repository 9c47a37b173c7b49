"""The smallest complete distributed training loop — apex's
examples/simple/distributed/distributed_data_parallel.py (U) in TPU form.

The reference demo is ~40 lines: torch.distributed init, a toy linear
model, ``amp.initialize(opt_level="O2")``, ``apex.parallel.
DistributedDataParallel`` wrap, a few steps on random data, print the
loss on rank 0. This is the same demo under one SPMD program:

- process groups / multiproc launcher  →  ``mesh.build_mesh()`` (one
  process, every device a mesh entry on the ``dp`` axis)
- DDP wrapper + bucketed NCCL allreduce →  ``parallel.
  DistributedDataParallel.reduce`` (a ``pmean`` XLA schedules —
  ``gradient_average=True``, the reference's default)
- amp O2 + dynamic loss scaling        →  ``amp.initialize("O2",
  half_dtype=float16)`` + functional ``ScalerState`` in the step
- per-rank random batches              →  batch sharded with
  ``PartitionSpec("dp")``

Run (CPU simulation of an 8-device mesh):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/simple_distributed.py
"""

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu import mesh as mx
from apex_tpu.amp import apply_if_finite, update
from apex_tpu.optimizers import fused_adam
from apex_tpu.parallel import DistributedDataParallel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--fp16", action="store_true",
                    help="fp16 + dynamic loss scaling (reference default);"
                         " bf16 without scaling otherwise")
    args = ap.parse_args()

    mesh = mx.build_mesh(tp=1)  # all devices on the dp axis

    # Toy model: two-layer MLP, the reference demo's nn.Linear pair.
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "w1": jax.random.normal(k0, (args.dim, args.dim)) / args.dim**0.5,
        "w2": jax.random.normal(k1, (args.dim, args.dim)) / args.dim**0.5,
    }

    half = jnp.float16 if args.fp16 else jnp.bfloat16
    ctx, apply_fn = amp.initialize(
        lambda p, x: jnp.tanh(x @ p["w1"]) @ p["w2"],
        opt_level="O2", half_dtype=half)
    scaler_cfg = ctx.scaler
    scaler0 = scaler_cfg.init() if scaler_cfg.enabled else None

    opt = fused_adam(1e-3, layout="tree")
    opt_state = jax.jit(opt.init)(params)
    ddp = DistributedDataParallel()  # reduces grads over the dp axis

    def loss_fn(p, x, y):
        return jnp.mean((apply_fn(p, x) - y) ** 2)

    def local_step(params, opt_state, scaler, x, y):
        grad_fn = amp.value_and_scaled_grad(loss_fn, scaler_cfg)
        loss, grads, finite = grad_fn(params, x, y, scaler_state=scaler)
        grads = ddp.reduce(grads)           # the DDP allreduce (U)
        finite = jax.lax.pmin(  # any-rank overflow skips everywhere
            finite.astype(jnp.int32), ddp.axis).astype(bool)
        new_p, new_opt = opt.step(grads, opt_state, params)
        # overflow → keep old params/opt state, shrink the scale
        new_p = apply_if_finite(new_p, params, finite)
        new_opt = apply_if_finite(new_opt, opt_state, finite)
        if scaler is not None:
            scaler = update(scaler_cfg, scaler, finite)
        return new_p, new_opt, scaler, jax.lax.pmean(loss, ddp.axis)

    rspec = jax.tree.map(lambda _: P(), params)
    ospec = jax.tree.map(lambda _: P(), jax.eval_shape(opt.init, params))
    sspec = None if scaler0 is None else jax.tree.map(lambda _: P(), scaler0)
    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(rspec, ospec, sspec, P("dp"), P("dp")),
        out_specs=(rspec, ospec, sspec, P()),
        check_vma=False), donate_argnums=(0, 1, 2))

    x = jax.random.normal(jax.random.PRNGKey(2), (args.batch, args.dim))
    y = jax.random.normal(jax.random.PRNGKey(3), (args.batch, args.dim))
    scaler = scaler0
    for i in range(args.steps):
        params, opt_state, scaler, loss = step(params, opt_state, scaler, x, y)
        scale = float(scaler.loss_scale) if scaler is not None else 1.0
        print(f"step {i} loss {float(loss):.6f} scale {scale:g}")
    print(f"done: {mesh.devices.size}-device dp mesh, "
          f"policy {'fp16+dynamic' if args.fp16 else 'bf16'}")


if __name__ == "__main__":
    main()
