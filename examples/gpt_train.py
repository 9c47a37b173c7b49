"""BASELINE configs #4/#5 — GPT training over TP / PP×TP meshes.

Config #4: GPT-2 355M, TP=8 over ICI    → --tp 8 --preset 355m
Config #5: Megatron-GPT 2.7B, PP×TP     → --tp 8 --pp 8 --preset 2p7b
                                          --n-micro 8 --vpp 2

Everything (amp, grad sync, pipeline schedule, fused optimizer) comes from
apex_tpu.models.training.make_train_step — this script is argument
plumbing plus data/metrics wiring: the native prefetching TokenLoader
(--data, synthetic tokens otherwise), per-step StepTimer/MetricsLogger,
and .atck checkpoint save/resume (--ckpt).

Run small (CPU simulation):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/gpt_train.py --preset tiny --tp 2 --pp 2 --n-micro 2

MoE (no apex analogue): --experts 8 --ep 2 shards 8 experts over an
ep=2 mesh axis (Switch/GShard routing, aux loss folded into the loss).
"""

import argparse
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import checkpoint as ckpt
from apex_tpu import data as atdata
from apex_tpu import mesh as mx
from apex_tpu import profiler
from apex_tpu.amp import ScalerConfig
from apex_tpu.models import gpt, training
from apex_tpu.optimizers import fused_adam

PRESETS = {
    "tiny": dict(vocab_size=1024, hidden_size=128, num_layers=4,
                 num_heads=4, seq_len=128),
    "355m": dict(vocab_size=50304, hidden_size=1024, num_layers=24,
                 num_heads=16, seq_len=1024),
    "2p7b": dict(vocab_size=50304, hidden_size=2560, num_layers=32,
                 num_heads=32, seq_len=1024),
}


def main():
    # persistent compile cache: where JAX_COMPILATION_CACHE_DIR says
    # (empty disables), else <checkout>/.jax_cache
    from apex_tpu._capabilities import enable_compilation_cache
    enable_compilation_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--cp", type=int, default=1,
                    help="context parallelism: ring attention over cp "
                    "seq shards (long-context mode)")
    ap.add_argument("--experts", type=int, default=0,
                    help="mixture of experts: replace every MLP with this "
                    "many experts (0 = dense)")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert parallelism: shard experts over an "
                    "ep mesh axis (needs --experts divisible by ep; "
                    "requires --opt-layout tree)")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--vpp", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--clip-grad-norm", type=float, default=None,
                    help="global-L2 grad clip inside the fused step "
                    "(the reference loop's clip_grad_norm_ between "
                    "unscale and optimizer.step)")
    ap.add_argument("--no-sp", action="store_true")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3: dp-shard the layer kernels between "
                    "steps (per-layer all-gather; needs --opt-layout "
                    "tree and hidden %% dp == 0)")
    ap.add_argument("--data", help="binary token file (apex_tpu.data "
                    "format); synthetic tokens if omitted")
    ap.add_argument("--ckpt", help=".atck checkpoint path to save/resume")
    ap.add_argument("--metrics", help="JSONL metrics path")
    ap.add_argument("--remat-policy", default=None,
                    choices=["dots", "qkv_fc1", "fc1", "qkv_fc1_attn",
                             "fc1_attn"],
                    help="selective-recompute policy (the *_attn variants "
                    "imply --attn-impl flash; bench uses qkv_fc1_attn)")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "flash", "xla", "xla_chunked"])
    ap.add_argument("--opt-layout", default="tree",
                    choices=["flat", "tree"],
                    help="optimizer state layout; tree (default) avoids "
                    "flat-packing copies and is the measured-fast choice "
                    "for layer-stacked models. Resuming a checkpoint "
                    "requires the layout it was saved with.")
    ap.add_argument("--ln-impl", default="xla", choices=["xla", "pallas"],
                    help="XLA-fused LN (measured faster in-model) or the "
                    "Pallas kernel")
    args = ap.parse_args()

    # chunked CE once the (cp-local) sequence is long enough to make the
    # logits tensor worth not materialising
    seq = PRESETS[args.preset]["seq_len"]
    ce_chunk = 512 if (seq // args.cp) >= 1024 and (seq // args.cp) % 512 == 0 else 0
    attn_impl = args.attn_impl
    if (args.remat_policy or "").endswith("_attn"):
        # the *_attn policies pin the flash kernel's residuals — they
        # require the flash path explicitly
        if attn_impl == "auto":
            attn_impl = "flash"
        elif attn_impl != "flash" or args.cp > 1:
            raise SystemExit(
                f"--remat-policy {args.remat_policy} requires the flash "
                "attention path (and no --cp); drop --attn-impl "
                f"{args.attn_impl} or pick a non-_attn policy")
    cfg = gpt.GPTConfig(
        sequence_parallel=(args.tp > 1 and args.cp == 1 and not args.no_sp
                           and args.experts == 0),
        context_parallel=(args.cp > 1),
        remat=True, compute_dtype=jnp.bfloat16, fsdp=args.fsdp,
        remat_policy=args.remat_policy, ln_impl=args.ln_impl,
        attn_impl=attn_impl, ce_chunk=ce_chunk,
        num_experts=args.experts, **PRESETS[args.preset])
    mesh = mx.build_mesh(tp=args.tp, pp=args.pp, cp=args.cp, ep=args.ep)
    init_fn, step_fn = training.make_train_step(
        cfg, mesh, fused_adam(args.lr, layout=args.opt_layout),
        ScalerConfig(enabled=False),
        n_micro=args.n_micro, n_chunks=args.vpp,
        clip_grad_norm=args.clip_grad_norm)

    state = init_fn(jax.random.PRNGKey(0))
    if args.ckpt and ckpt.checkpoint_exists(args.ckpt):
        try:
            state = ckpt.load_checkpoint(args.ckpt, state)
        except KeyError as e:
            raise SystemExit(
                f"checkpoint {args.ckpt} does not match the current "
                f"optimizer-state structure ({e}); if it was saved with a "
                "different --opt-layout, resume with that layout") from e
        print(f"resumed from {args.ckpt} at step {int(state.step)}")

    loader = None
    if args.data:
        loader = atdata.TokenLoader(
            args.data, cfg.seq_len, args.batch, mesh=mesh, seed=0)
        batches = iter(loader)
    else:
        tok = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, cfg.seq_len), 0,
            cfg.vocab_size)
        batches = itertools.repeat((tok, jnp.roll(tok, -1, axis=1)))

    timer = profiler.StepTimer(tokens_per_step=args.batch * cfg.seq_len)
    log = profiler.MetricsLogger(jsonl_path=args.metrics)
    for i in range(args.steps):
        tok, tgt = next(batches)
        state, m = step_fn(state, tok, tgt)
        timer.tick(m["loss"])
        log.log(i, m)
        print(f"step {i} loss {float(m['loss']):.4f}")
    s = timer.summary()
    if s:
        print(f"{s['tokens_per_sec']:.0f} tokens/s on mesh "
              f"{dict(mesh.shape)} (median {s['median_step_s']*1e3:.1f} "
              f"ms/step)")
    if args.ckpt:
        written = ckpt.save_checkpoint(args.ckpt, state)
        print(f"saved {written}")
    if loader is not None:
        loader.close()
    log.close()


if __name__ == "__main__":
    main()
