"""Decode demo: greedy / sampled continuation from the flagship GPT.

No reference analogue — apex ships no inference path (SURVEY.md §1) —
but a training framework whose checkpoints cannot be decoded is half a
framework. Loads an ``.atck`` checkpoint saved by examples/gpt_train.py
(or random init), then generates with the KV-cache path that is pinned
token-for-token to the teacher-forced forward.

Run (CPU simulation):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/generate.py --tp 2 --n-new 16
"""

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import checkpoint as ckpt
from apex_tpu import mesh as mx
from apex_tpu.models import gpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--n-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample only among the k best logits (0 = off)")
    ap.add_argument("--beams", type=int, default=0,
                    help="beam search width (0 = greedy/sampled "
                    "generate); prints each batch row's best beam "
                    "and its total log-prob")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--ckpt", help=".atck from examples/gpt_train.py "
                    "(--preset tiny); random init if omitted")
    args = ap.parse_args()

    cfg = gpt.GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                        num_heads=4, seq_len=128, remat=False,
                        compute_dtype=jnp.float32)
    mesh = mx.build_mesh(tp=args.tp)
    if args.ckpt:
        # gpt_train saves a TrainState; restore just the params leaf
        from apex_tpu.amp import ScalerConfig
        from apex_tpu.models import training
        from apex_tpu.optimizers import fused_adam
        init_fn, _ = training.make_train_step(
            cfg, mesh, fused_adam(1e-4, layout="tree"),
            ScalerConfig(enabled=False))
        params = ckpt.load_checkpoint(
            args.ckpt, init_fn(jax.random.PRNGKey(0))).params
    else:
        params = gpt.init(cfg, jax.random.PRNGKey(0))

    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)
    key = jax.random.PRNGKey(2)
    if args.beams > 0:
        if args.temperature > 0 or args.top_k or args.top_p != 1.0:
            raise SystemExit(
                "--beams is deterministic max-probability search; "
                "--temperature/--top-k/--top-p apply to generate only")
        seqs, scores = jax.jit(jax.shard_map(
            lambda p, t: gpt.beam_search(
                cfg, p, t, args.n_new, num_beams=args.beams),
            mesh=mesh, in_specs=(gpt.param_specs(cfg), P(None, None)),
            out_specs=(P(None, None, None), P(None, None)),
            check_vma=False))(params, prompt)
        for i in range(args.batch):
            print(f"prompt {list(map(int, prompt[i]))} -> "
                  f"{list(map(int, seqs[i, 0]))} "
                  f"(logp {float(scores[i, 0]):.3f})")
        return
    out = jax.jit(jax.shard_map(
        lambda p, t: gpt.generate(
            cfg, p, t, args.n_new, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p,
            key=key if args.temperature > 0 else None),
        mesh=mesh, in_specs=(gpt.param_specs(cfg), P(None, None)),
        out_specs=P(None, None), check_vma=False))(params, prompt)
    for i in range(args.batch):
        print(f"prompt {list(map(int, prompt[i]))} -> "
              f"{list(map(int, out[i]))}")


if __name__ == "__main__":
    main()
