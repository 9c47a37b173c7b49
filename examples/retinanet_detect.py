"""BASELINE config #3 — RetinaNet-style detection training.

The apex features this config exercises (BASELINE.md): SyncBatchNorm with
cross-replica Welford statistics over the mesh, FusedSGD, and (from
contrib) the sigmoid focal loss (apex/contrib/focal_loss (U)). The model
is the standard RetinaNet shape — ResNet backbone (`models.resnet
.features`), FPN P3–P5 with lateral + top-down pathways, shared conv
subnets for classification (focal loss) and box regression (smooth-L1) —
written the way an apex user would write theirs: apex ships the
acceleration pieces, the detector lives in the training script.

Targets are synthetic per-anchor tensors: anchor assignment/NMS are data
plumbing orthogonal to the framework capabilities this example pins.

Run (CPU simulation):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/retinanet_detect.py --steps 3 --batch 8 --image 128
"""

import argparse
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu.contrib import sigmoid_focal_loss
from apex_tpu.models import resnet
from apex_tpu.optimizers import fused_sgd

NUM_ANCHORS = 9
FPN_DIM = 256
LEVELS = ("p3", "p4", "p5")


def _conv_init(key, k, cin, cout):
    std = (2.0 / (k * k * cin)) ** 0.5
    return std * jax.random.normal(key, (k, k, cin, cout), jnp.float32)


def init_heads(key, num_classes, backbone_dims):
    ks = iter(jax.random.split(key, 32))
    p = {"lateral": {}, "smooth": {}}
    for lvl, cin in zip(LEVELS, backbone_dims):
        p["lateral"][lvl] = _conv_init(next(ks), 1, cin, FPN_DIM)
        p["smooth"][lvl] = _conv_init(next(ks), 3, FPN_DIM, FPN_DIM)
    # shared 2-conv subnets (RetinaNet uses 4; depth is a dial, not a
    # capability) + prediction convs
    p["cls"] = [
        _conv_init(next(ks), 3, FPN_DIM, FPN_DIM),
        _conv_init(next(ks), 3, FPN_DIM, FPN_DIM),
        _conv_init(next(ks), 3, FPN_DIM, NUM_ANCHORS * num_classes),
    ]
    p["box"] = [
        _conv_init(next(ks), 3, FPN_DIM, FPN_DIM),
        _conv_init(next(ks), 3, FPN_DIM, FPN_DIM),
        _conv_init(next(ks), 3, FPN_DIM, NUM_ANCHORS * 4),
    ]
    return p


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _upsample2(x):
    n, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (n, h, 2, w, 2, c))
    return x.reshape(n, 2 * h, 2 * w, c)


def fpn(p, feats):
    """c3..c5 → p3..p5 (lateral 1x1, top-down nearest-2x, 3x3 smooth)."""
    laterals = {
        lvl: _conv(feats[f"c{i + 3}"], p["lateral"][lvl])
        for i, lvl in enumerate(LEVELS)
    }
    tops = {"p5": laterals["p5"]}
    tops["p4"] = laterals["p4"] + _upsample2(tops["p5"])
    tops["p3"] = laterals["p3"] + _upsample2(tops["p4"])
    return {lvl: _conv(tops[lvl], p["smooth"][lvl]) for lvl in LEVELS}


def _subnet(convs, x):
    for w in convs[:-1]:
        x = jax.nn.relu(_conv(x, w))
    return _conv(x, convs[-1])


def detection_loss(cfg, params, bn_state, heads, images, cls_targets,
                   box_targets, num_classes):
    """Focal + smooth-L1 over all FPN levels; returns (loss, new_bn)."""
    feats, new_bn = resnet.features(cfg, params, bn_state, images,
                                    training=True)
    pyramid = fpn(heads, feats)
    total_cls = jnp.float32(0.0)
    total_box = jnp.float32(0.0)
    n_pos = jnp.float32(0.0)
    for lvl in LEVELS:
        f = pyramid[lvl]
        n, h, w, _ = f.shape
        cls_logits = _subnet(heads["cls"], f).astype(jnp.float32).reshape(
            n, h * w * NUM_ANCHORS, num_classes)
        box_pred = _subnet(heads["box"], f).astype(jnp.float32).reshape(
            n, h * w * NUM_ANCHORS, 4)
        ct = cls_targets[lvl]        # [n, anchors, classes] {0,1}
        bt = box_targets[lvl]        # [n, anchors, 4]
        pos = (ct.sum(-1) > 0).astype(jnp.float32)  # anchors with a box
        total_cls += jnp.sum(sigmoid_focal_loss(cls_logits, ct))
        diff = jnp.abs(box_pred - bt)
        smooth_l1 = jnp.where(diff < 1.0, 0.5 * diff * diff, diff - 0.5)
        total_box += jnp.sum(smooth_l1.sum(-1) * pos)
        n_pos += jnp.sum(pos)
    denom = jnp.maximum(n_pos, 1.0)
    return (total_cls + total_box) / denom, new_bn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image", type=int, default=256)
    ap.add_argument("--classes", type=int, default=80)
    ap.add_argument("--depth", type=int, default=50)
    # modest default: the synthetic random box targets make the regression
    # objective pure noise, and noise + momentum at detection-paper LRs
    # diverges within a couple of steps
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()
    if args.image % 32:
        # c5 is stride 32; non-multiples break the exact 2x top-down
        # upsampling and the anchor-count math below
        ap.error(f"--image must be a multiple of 32, got {args.image}")

    mesh = mx.build_mesh(tp=1)
    dp = mesh.devices.size
    # bf16 feeds the MXU on TPU; the CPU backend's bf16 convs fall off the
    # vectorised path (orders of magnitude slower), so simulation runs fp32
    cdt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    cfg = resnet.ResNetConfig(depth=args.depth, bn_axis="dp",  # SyncBN
                              compute_dtype=cdt)
    key = jax.random.PRNGKey(0)
    params, bn_state = resnet.init(cfg, key)
    dims = [256 * (2 ** i) for i in (1, 2, 3)]  # c3..c5 channels
    heads = init_heads(jax.random.fold_in(key, 1), args.classes, dims)
    # tree layout: leafwise XLA-fused update — no flat-packing copies, and
    # the flat Pallas sweep would run interpreted (minutes/step) on the
    # CPU simulation backend
    opt = fused_sgd(args.lr, momentum=0.9, layout="tree")
    all_params = {"backbone": params, "heads": heads}
    opt_state = opt.init(all_params)

    batch = args.batch * dp
    img = jax.random.normal(
        jax.random.fold_in(key, 2), (batch, args.image, args.image, 3),
        jnp.float32)
    anchors = {lvl: (args.image // s) ** 2 * NUM_ANCHORS
               for lvl, s in zip(LEVELS, (8, 16, 32))}
    kc = jax.random.fold_in(key, 3)
    cls_t = {lvl: (jax.random.uniform(jax.random.fold_in(kc, i),
                                      (batch, a, args.classes)) > 0.999
                   ).astype(jnp.float32)
             for i, (lvl, a) in enumerate(anchors.items())}
    box_t = {lvl: jax.random.normal(jax.random.fold_in(kc, 10 + i),
                                    (batch, a, 4))
             for i, (lvl, a) in enumerate(anchors.items())}

    dspec = P("dp")

    def local_step(all_p, opt_st, bn_st, im, ct, bt):
        def lf(ap_):
            return detection_loss(cfg, ap_["backbone"], bn_st,
                                  ap_["heads"], im, ct, bt, args.classes)

        (loss, new_bn), grads = jax.value_and_grad(lf, has_aux=True)(all_p)
        grads = lax.pmean(grads, "dp")
        new_p, new_opt = opt.step(grads, opt_st, all_p)
        return new_p, new_opt, new_bn, lax.pmean(loss, "dp")

    bn_specs = jax.tree.map(lambda _: P(), bn_state)
    pspecs = jax.tree.map(lambda _: P(), all_params)
    ospecs = opt.state_pspecs(pspecs)
    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(pspecs, ospecs, bn_specs, dspec, dspec, dspec),
        out_specs=(pspecs, ospecs, bn_specs, P()),
        check_vma=False))

    for i in range(args.steps):
        t0 = time.perf_counter()
        all_params, opt_state, bn_state, loss = step(
            all_params, opt_state, bn_state, img, cls_t, box_t)
        loss_v = float(loss)
        print(f"step {i}: loss {loss_v:.4f} "
              f"({time.perf_counter() - t0:.2f}s)", flush=True)


if __name__ == "__main__":
    main()
