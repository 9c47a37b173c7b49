"""BASELINE config #2 — BERT-large pretraining shape.

FusedLAMB + fused LayerNorm under amp O2 (fp16 compute + fp32 masters +
dynamic loss scaling; bf16 needs no scaler and is the TPU default —
--fp16 switches to the parity mode). ZeRO sharding via
--zero (DistributedFusedLAMB, the MLPerf BERT recipe (U)).

Run small (CPU simulation):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/bert_pretrain.py --layers 2 --hidden 128 --steps 3
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as mx
from apex_tpu.amp import ScalerConfig, apply_if_finite, update as scaler_update
from apex_tpu.amp import value_and_scaled_grad
from apex_tpu.models import bert
from apex_tpu.optimizers import distributed_fused_lamb, fused_lamb


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--fp16", action="store_true")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-1/2: DistributedFusedLAMB shards grads + "
                    "optimizer state over dp")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3: dp-shard the encoder kernels between "
                    "steps (FusedLAMB is whole-leaf-norm, so --fsdp "
                    "needs an elementwise optimizer — it switches the "
                    "run to tree-layout FusedAdam)")
    args = ap.parse_args()

    cfg = bert.BertConfig(
        hidden_size=args.hidden, num_layers=args.layers,
        num_heads=args.heads, seq_len=args.seq, fsdp=args.fsdp,
        compute_dtype=jnp.float16 if args.fp16 else jnp.bfloat16)
    mesh = mx.build_mesh(tp=args.tp)
    scaler = (ScalerConfig() if args.fp16 else ScalerConfig(enabled=False))
    # tree layout off the ZeRO path: leafwise XLA-fused update (the flat
    # Pallas sweep runs interpreted — minutes/step — off-TPU)
    if args.fsdp and args.zero:
        raise SystemExit("--fsdp (ZeRO-3) and --zero (ZeRO-1/2) are "
                         "alternative sharding strategies; pick one")
    if args.fsdp:
        from apex_tpu.optimizers import fused_adam
        opt = fused_adam(args.lr, layout="tree")
    else:
        opt = (distributed_fused_lamb(args.lr) if args.zero
               else fused_lamb(args.lr, layout="tree"))

    init_fn, step_fn = bert.make_mlm_train_step(cfg, mesh, opt, scaler)
    state = init_fn(jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (args.batch, args.seq)))
    mask = jnp.asarray(rng.rand(args.batch, args.seq) < 0.15, jnp.int32)
    tgt = tok  # "reconstruct the original ids at masked positions"

    for i in range(args.steps):
        state, m = step_fn(state, tok, tgt, mask)
        print(f"step {i} mlm_loss {float(m['loss']):.4f} "
              f"scale {float(m['loss_scale']):.0f}")


if __name__ == "__main__":
    main()
