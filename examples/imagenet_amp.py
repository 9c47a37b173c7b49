"""BASELINE config #1 — ResNet-50 ImageNet-style training.

The TPU-native form of examples/imagenet/main_amp.py (U): amp O1 ≈ bf16
compute policy (no loss scaling needed), apex DDP ≈ batch sharded on the
dp mesh axis with grad pmean, FusedSGD with momentum, SyncBatchNorm
optional (config #3's RetinaNet pairing). Data: ``--data file.bin``
streams packed uint8 records through the native prefetch loader
(``apex_tpu.data.ImageLoader`` — the role the reference leaves to the
torch DataLoader + DistributedSampler), normalized on device; without
it, synthetic tensors. ``--val-data`` adds the validate() prec@1/5 leg;
``--ckpt`` the torch.save/--resume round trip.

Run (CPU simulation):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/imagenet_amp.py --steps 5 --batch 32 --image 64
"""

import argparse
import itertools
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import checkpoint as ckpt
from apex_tpu import data
from apex_tpu import mesh as mx
from apex_tpu.models import resnet
from apex_tpu.optimizers import fused_sgd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--syncbn", action="store_true")
    ap.add_argument("--data", default=None,
                    help="packed image file (apex_tpu.data.write_image_file)")
    ap.add_argument("--val-data", default=None,
                    help="packed validation image file; reports prec@1/5 "
                    "after training (main_amp.py's validate() (U))")
    ap.add_argument("--val-batches", type=int, default=0,
                    help="cap on eval batches (0 = one full pass; never "
                    "wraps, so every image counts at most once)")
    ap.add_argument("--ckpt", default=None,
                    help=".atck path to save/resume (main_amp.py's "
                    "--resume/torch.save round trip (U))")
    args = ap.parse_args()

    mesh = mx.build_mesh(tp=1)  # pure data parallelism
    dp = mesh.devices.size
    cfg = resnet.ResNetConfig(
        depth=args.depth, bn_axis="dp" if args.syncbn else None,
        compute_dtype=jnp.bfloat16)
    params, bn_state = resnet.init(cfg, jax.random.PRNGKey(0))
    # tree layout: leafwise XLA-fused update (the flat Pallas sweep runs
    # interpreted — minutes per step — on the CPU simulation backend)
    opt = fused_sgd(args.lr, momentum=0.9, weight_decay=1e-4, layout="tree")
    opt_state = jax.jit(opt.init)(params)

    start_step = 0
    if args.ckpt and ckpt.checkpoint_exists(args.ckpt):
        params, bn_state, opt_state, start_step = ckpt.load_checkpoint(
            args.ckpt,
            (params, bn_state, opt_state, jnp.zeros((), jnp.int32)))
        start_step = int(start_step)
        print(f"resumed from {args.ckpt} at step {start_step}")

    def local_step(params, bn_state, opt_state, images, labels):
        if images.dtype == jnp.uint8:  # native-loader batches: uint8 over
            # the wire, dequant+normalize fused into the first conv read
            images = data.normalize_images(images, jnp.float32)
        (l, ns), g = jax.value_and_grad(
            lambda p: resnet.loss(cfg, p, bn_state, images, labels),
            has_aux=True)(params)
        g = jax.lax.pmean(g, "dp")  # apex DDP allreduce (U)
        if not args.syncbn:
            # local BN: each rank updated running stats from its own batch
            # shard; average them so the replicated-out-spec state stays
            # consistent (torch DDP broadcasts buffers; pmean is the
            # all-shards-contribute version)
            ns = jax.lax.pmean(ns, "dp")
        new_p, opt_state = opt.step(g, opt_state, params)
        return new_p, ns, opt_state, jax.lax.pmean(l, "dp")

    pspec = jax.tree.map(lambda _: P(), params)
    sspec = jax.tree.map(lambda _: P(), bn_state)
    ospec = jax.tree.map(lambda x: P(), jax.eval_shape(opt.init, params))
    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(pspec, sspec, ospec, P("dp"), P("dp")),
        out_specs=(pspec, sspec, ospec, P()),
        check_vma=False), donate_argnums=(0, 1, 2))

    if args.data:
        # mesh=: multi-host runs stride records per process and place
        # batches dp-sharded (the DistributedSampler contract)
        loader = data.ImageLoader(
            args.data, (args.image, args.image), args.batch, mesh=mesh,
            shuffle=True)
        batches = iter(loader)
    else:
        img = jax.random.normal(
            jax.random.PRNGKey(1), (args.batch, args.image, args.image, 3))
        lbl = jax.random.randint(
            jax.random.PRNGKey(2), (args.batch,), 0, 1000)
        batches = itertools.repeat((img, lbl))

    # print each step's loss one step late: fetching the in-flight value
    # would sync host and device every iteration and stall the loader's
    # prefetch overlap; the lagged fetch syncs on an already-finished step
    t0 = time.perf_counter()
    prev = None
    for i in range(start_step, start_step + args.steps):
        im, lb = next(batches)
        params, bn_state, opt_state, loss = step(
            params, bn_state, opt_state, im, lb)
        if prev is not None:
            print(f"step {i - 1} loss {float(prev):.4f}")
        prev = loss
    if prev is not None:
        print(f"step {start_step + args.steps - 1} loss "
              f"{float(prev):.4f}")  # sync barrier
    dt = time.perf_counter() - t0
    print(f"{args.steps * args.batch / dt:.1f} images/s over {dp} devices")
    if args.data:
        loader.close()
    if args.ckpt:
        written = ckpt.save_checkpoint(
            args.ckpt,
            (params, bn_state, opt_state,
             jnp.asarray(start_step + args.steps, jnp.int32)))
        print(f"saved {written}")

    if args.val_data:
        # eval pass: frozen BN statistics, top-1/top-5 over the val stream
        def local_eval(params, bn_state, images, labels):
            if images.dtype == jnp.uint8:
                images = data.normalize_images(images, jnp.float32)
            logits, _ = resnet.forward(
                cfg, params, bn_state, images, training=False)
            top5 = jax.lax.top_k(logits, 5)[1]
            hit1 = (top5[:, 0] == labels).sum()
            hit5 = (top5 == labels[:, None]).any(axis=1).sum()
            return (jax.lax.psum(hit1, "dp"), jax.lax.psum(hit5, "dp"))

        evaluate = jax.jit(jax.shard_map(
            local_eval, mesh=mesh,
            in_specs=(pspec, sspec, P("dp"), P("dp")),
            out_specs=(P(), P()), check_vma=False))
        val = data.ImageLoader(args.val_data, (args.image, args.image),
                               args.batch, mesh=mesh, shuffle=False)
        # sequential unshuffled reads: capping at num_records/batch means
        # every image is seen at most once (the loader wraps past that,
        # which would silently resample — the reference's validate()
        # iterates the set exactly once)
        avail = val.num_records // args.batch
        n_batches = avail if args.val_batches <= 0 \
            else min(args.val_batches, avail)
        if n_batches < 1:
            raise SystemExit(
                f"--val-data holds {val.num_records} records — fewer than "
                f"one --batch {args.batch}")
        n = h1 = h5 = 0
        for _ in range(n_batches):
            im, lb = val.next()
            a, b = evaluate(params, bn_state, im, lb)
            h1 += int(a)
            h5 += int(b)
            n += args.batch
        val.close()
        print(f"prec@1 {100.0 * h1 / n:.2f}%  prec@5 {100.0 * h5 / n:.2f}% "
              f"over {n} images")


if __name__ == "__main__":
    main()
