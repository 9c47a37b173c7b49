"""Megatron-style GPT over the {dp, tp} mesh — the flagship model.

The reference's transformer stack has no model of its own; apex.transformer
is consumed by Megatron/NeMo trainers (SURVEY.md §1: "control flow always
lives in the user's training script"). This module is that consumer, built
from apex_tpu's own parity pieces:

- ``VocabParallelEmbedding`` lookup + tied vocab-parallel output head
  (apex/transformer/tensor_parallel/layers.py (U)),
- fused-QKV ``ColumnParallelLinear`` → Pallas flash attention →
  ``RowParallelLinear`` (the fmha / fast_multihead_attn capability (U)),
- Pallas fused LayerNorm (csrc/layer_norm_cuda_kernel.cu (U)),
- MLP = column(gelu) → row (apex/mlp (U) shape),
- ``vocab_parallel_cross_entropy`` loss,
- Megatron sequence parallelism (``sequence_parallel_enabled`` (U)):
  activations sharded on the seq dim between TP blocks,
- activation recompute via ``jax.checkpoint`` per layer.

Layout is batch-major ``[batch, seq, hidden]`` — the Pallas flash
kernel's native operand layout, so attention needs no layout copies at
all (Megatron's [s, b, h] convention exists for NCCL-era reasons that
don't apply here; the SP mappings take ``dim=1``). All functions have
*local-shard* semantics: call
inside ``shard_map`` over a mesh with a ``tp`` axis (``tp=1`` is fine).
Layer parameters are stacked on a leading layer axis and scanned, so
compile time is O(1) in depth.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

import numpy as np

from apex_tpu.kernels import (
    flash_attention,
    flash_attention_bsh,
    layer_norm,
)
from apex_tpu.kernels.decode_attention import (
    cache_write_columns_xla as _cache_write_columns_xla,
    decode_block_k as _decode_block_k,
    kv_storage_dtype as _kv_storage_dtype,
    live_rows as _live_rows,
    paged_gather_xla as _paged_gather_xla,
    paged_write_columns_xla as _paged_write_columns_xla,
    quantize_kv_rows as _quantize_kv_rows_impl,
    stacked_decode_attention as _stacked_decode_attention,
    stacked_write_columns as _stacked_write_columns,
)
from apex_tpu.kernels.blockwise_attention import blockwise_attention
from apex_tpu.models import latent
from apex_tpu.mesh.topology import AXIS_CP, AXIS_DP, AXIS_EP, AXIS_PP, AXIS_TP
# sampling lives in serving so generate and the continuous-batching
# engine share one implementation (serving/__init__ loads its
# gpt-importing submodules lazily, so this import is cycle-free)
from apex_tpu.serving import sampling as _sampling
from apex_tpu.transformer import moe as moe_mod
from apex_tpu.transformer.context_parallel import ring_attention
from apex_tpu.transformer.pipeline_parallel.schedules import pipelined_loss
from apex_tpu.transformer.tensor_parallel import random as tpr
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    column_parallel_linear,
    init_method_normal,
    row_parallel_linear,
    scaled_init_method_normal,
    vocab_parallel_embedding,
)
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    scatter_to_sequence_parallel_region,
)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Model + parallelism-behaviour config (static, hashable)."""

    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    seq_len: int = 1024
    ffn_hidden_size: Optional[int] = None  # default 4 * hidden
    sequence_parallel: bool = False
    remat: bool = True
    #: None → recompute everything in backward; "dots" → save MXU (matmul)
    #: outputs and recompute only the cheap elementwise chains; "qkv_fc1"
    #: → save only the two big projection outputs (the expensive half of
    #: the replay) and recompute proj/fc2/attention — fits ~1.5x the batch
    #: of "dots" at most of its speedup; "fc1" → save only the fc1
    #: projection (the single biggest matmul), lightest footprint of the
    #: selective modes; "qkv_fc1_attn" / "fc1_attn" → additionally pin
    #: the flash kernel's (out, lse) residuals so backward never re-runs
    #: the forward attention kernel (require ``attn_impl="flash"``).
    #: Selective-recompute modes the reference's checkpoint() can't
    #: express.
    remat_policy: Optional[str] = None
    #: CE sequence-chunk size: the [b, s, vocab] logits tensor never
    #: materialises — each chunk's logits are computed, reduced to per-token
    #: losses, and rematerialised in backward. 0 = unchunked. The memory
    #: shape of the reference's fused xentropy kernel (apex/contrib/
    #: xentropy (U) "saves logits memory"), done at the XLA level.
    ce_chunk: int = 0
    #: "xla" → vocab-parallel CE (any tp); "fused" → the Pallas xentropy
    #: kernel per chunk (single-pass lse, backward recomputes softmax
    #: from logits) — requires the vocab unsharded locally (tp == 1).
    ce_impl: str = "xla"
    #: "flash" → Pallas blockwise kernel (fastest on TPU from seq 256 —
    #: 2.5x+ over the XLA paths at 4k, docs/DESIGN.md); "xla" →
    #: materialised-scores attention (fastest at short seq and the only
    #: fast path off-TPU, where Pallas runs interpreted); "xla_chunked"
    #: → q-chunk scanned attention with flash's O(chunk·s) memory but
    #: XLA codegen (the off-TPU long-seq fallback); "auto" picks by
    #: backend and seq_len per those measurements.
    attn_impl: str = "auto"
    #: Unroll factor for the layer scan (1 = rolled). Unrolling the depth
    #: loop lets XLA fuse across layer boundaries and removes
    #: per-iteration overhead; compile time grows with the factor.
    #: True = fully unrolled.
    scan_unroll: Any = 1
    #: Flash-path data layout. "auto" → the lane-packed [b, s, hidden]
    #: kernel whenever the geometry allows (head_dim a power-of-two
    #: divisor of 128, hidden a multiple of 128): operands stay in the
    #: model layout, so the per-layer head-major transposes AND the 2x
    #: lane padding of head_dim < 128 tensors (q/k/v, out, dq/dk/dv all
    #: [.., 64]-minor before) disappear. "bhsd" forces the head-major
    #: kernel (A/B + shapes the packed kernel can't express).
    attn_layout: str = "auto"
    #: "pallas" → fused Pallas LN kernel (opaque to XLA fusion);
    #: "xla" → jnp LayerNorm that XLA fuses into neighbouring ops.
    #: Numerics identical (fp32 statistics either way). Default "xla":
    #: measured faster in-model on both the GPT and BERT shapes — a
    #: Pallas call is a fusion barrier inside the layer scan
    #: (docs/DESIGN.md); the standalone kernel stays the
    #: apex-normalization parity surface.
    ln_impl: str = "xla"
    #: Storage dtype of the materialised score matrix — applies ONLY to
    #: the "xla" attention path (flash/xla_chunked never materialise
    #: scores to HBM, so the knob is moot there, including when "auto"
    #: resolves to flash). TPU matmuls accumulate fp32 internally either
    #: way, so "f32" only changes what is written to HBM (the bf16
    #: einsum output upcast) at 2x the score traffic; "compute" keeps
    #: scores in compute dtype with fp32 max/exp/sum softmax statistics —
    #: flash-kernel numerics at half the bandwidth.
    attn_score_dtype: str = "f32"
    #: Decode-attention impl for the KV-cache path (:func:`decode_step` /
    #: :func:`decode_steps` / the serving engine). "kernel" → the Pallas
    #: flash-decode kernel (``kernels/decode_attention.py``): split-K
    #: sweep with online (out, lse) merge and a true one-column cache
    #: write, replacing the XLA path's one-hot rewrite of the ENTIRE
    #: [b, h, S, d] K/V caches per layer per token (O(B·h·S·d) HBM
    #: traffic that scales with horizon). "xla" → materialised-scores
    #: einsum attention (the only fast path off-TPU, where Pallas runs
    #: interpreted). "auto" resolves through :func:`_decode_attn_impl` —
    #: THE one documented predicate, shared by the plain and quantized
    #: cache layouts.
    decode_attn_impl: str = "auto"
    #: KV-cache storage dtype for the decode path (:func:`init_cache` /
    #: prefill / :func:`decode_step`(s) / the serving engine's donated
    #: buffers). "bf16" (and today "auto") stores K/V in
    #: ``compute_dtype`` — the historical layout, bit-identical to every
    #: pre-quantization oracle. "int8" / "fp8" store K/V quantized with
    #: per-head, per-slot, per-position fp32 scales (symmetric absmax
    #: over each written ``[head_dim]`` row): cache footprint and decode
    #: HBM read traffic shrink ~2x (bf16) / ~4x (fp32 compute), at a
    #: small dequantization error the oracle tests bound per dtype. The
    #: cache becomes a ``{"kv", "scale"}`` pytree; every cache-layout
    #: seam (insert/gather/spec) handles both forms. "fp8" uses
    #: ``float8_e4m3fn`` where the jax build provides it. "auto" stays
    #: unquantized until a chip-measured crossover justifies flipping it
    #: (perf-claims convention — quantization changes numerics, so the
    #: default must not silently break bit-parity oracles).
    kv_cache_dtype: str = "auto"
    #: Long-context mode (no reference analogue — SURVEY.md §5 "no ring
    #: attention"): activations stay sequence-sharded over the ``cp`` mesh
    #: axis through the whole stack; attention is exact ring attention
    #: (K/V chunks rotate over ICI). Composes with TP and PP; mutually
    #: exclusive with Megatron sequence_parallel (both shard the seq dim).
    context_parallel: bool = False
    cp_axis: str = AXIS_CP
    #: Zigzag chunk assignment for causal cp: rank r holds sequence
    #: chunks (r, 2cp-1-r), which balances the causal ring's useful work
    #: across ranks (half a K/V block per hop, uniformly) — ~2x faster
    #: causal context parallelism at scale. Token/position/target
    #: slicing and the CE all follow the same permutation, so losses
    #: and gradients are identical to the contiguous layout.
    cp_zigzag: bool = False
    #: False → bidirectional attention (the BERT encoder reuses this stack)
    causal: bool = True
    #: Mixture of experts (no reference analogue — SURVEY.md §2.5 "EP
    #: absent"): > 0 replaces every layer's MLP with a
    #: ``transformer.moe`` FFN of this many experts, sharded over the
    #: ``ep`` mesh axis (``ep=1`` runs them locally). The CE objective
    #: gains ``moe_aux_coef ×`` the summed per-layer load-balance loss.
    #: Composes with dp/tp/cp/pp/ep in any combination (the aux loss
    #: rides the pipeline tick scan; the expert all_to_all runs inside
    #: each tick); sequence_parallel is not supported with MoE.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    #: "auto" | "einsum" | "gather" — see MoEConfig.dispatch
    moe_dispatch: str = "auto"
    ep_axis: str = AXIS_EP
    #: ZeRO-3 / FSDP analogue (beyond the reference's ZeRO-1/2
    #: ``distributed_fused_{adam,lamb}`` (U)): the four big layer matmul
    #: kernels (qkv/proj/fc1/fc2) live dp-sharded on their replicated
    #: h-dim between steps; each layer all-gathers them over dp at use
    #: (inside the remat boundary, so backward re-gathers instead of
    #: holding full weights), and the gather's VJP is the ZeRO
    #: reduce-scatter — gradients and (tree-layout) optimizer state
    #: stay dp-sharded. Requires ``hidden_size % dp == 0``, a
    #: tree-layout optimizer, and a dense model (no MoE). Param memory
    #: per rank drops ~1/dp for the layer stack; comm per step is one
    #: extra all-gather per kernel per layer (2x under remat), riding
    #: ICI. LN/bias leaves and the embedding stay replicated.
    fsdp: bool = False
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    layernorm_epsilon: float = 1e-5
    init_std: float = 0.02
    axis: str = AXIS_TP
    #: a :class:`apex_tpu.models.latent.LatentConfig` switches the
    #: layer's mixer from fused-QKV multi-head attention to latent
    #: attention over a learned sparse selection, with RMSNorm, rotary
    #: positions, SiLU-gated feed-forwards (dense first, routed after),
    #: bias-free linears and an untied head over the vocabulary rows
    #: held (``models/latent.py``; serving path only: :func:`init`,
    #: :func:`init_cache`, :func:`decode_step`/:func:`decode_steps`,
    #: :func:`prefill_paged`).
    latent: Optional[Any] = None

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide by num_heads")
        return self.hidden_size // self.num_heads

    def param_count(self) -> int:
        h, f, L = self.hidden_size, self.ffn, self.num_layers
        per_layer = 4 * h + (h * 3 * h + 3 * h) + (h * h + h)
        if self.num_experts:
            e = self.num_experts
            per_layer += h * e + e * (h * f + f + f * h + h)
        else:
            per_layer += (h * f + f) + (f * h + h)
        return self.vocab_size * h + self.seq_len * h + L * per_layer + 2 * h


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(cfg: GPTConfig, key):
    h, f = cfg.hidden_size, cfg.ffn
    init = init_method_normal(cfg.init_std)
    out_init = scaled_init_method_normal(cfg.init_std, cfg.num_layers)
    k = jax.random.split(key, 4)
    dt = cfg.param_dtype
    p = {
        "ln1": {"scale": jnp.ones((h,), dt), "bias": jnp.zeros((h,), dt)},
        "attn": {
            # fused QKV as [h, 3, h]: the last dim is TP-sharded, so every
            # rank holds whole heads and its (q | k | v) slabs are
            # CONTIGUOUS — the three slab matmuls produce q/k/v directly
            # in the flash kernel's [b, s, hidden] operand layout, with no
            # per-head de-interleave in either direction. (Megatron
            # interleaves per-head triples into a 2-D [h, 3h] weight (U)
            # only because torch Linear demands 2-D; a 3-D param is the
            # TPU-native form of the same TP-divisibility contract.)
            "qkv": {"kernel": init(k[0], (h, 3, h), dt),
                    "bias": jnp.zeros((3, h), dt)},
            "proj": {"kernel": out_init(k[1], (h, h), dt),
                     "bias": jnp.zeros((h,), dt)},
        },
        "ln2": {"scale": jnp.ones((h,), dt), "bias": jnp.zeros((h,), dt)},
    }
    if cfg.num_experts:
        e = cfg.num_experts
        ke = jax.random.split(k[3], 2)
        p["moe"] = {
            "router": {"kernel": init(k[2], (h, e), dt)},
            "experts": {
                "w1": init(ke[0], (e, h, f), dt),
                "b1": jnp.zeros((e, f), dt),
                "w2": out_init(ke[1], (e, f, h), dt),
                "b2": jnp.zeros((e, h), dt),
            },
        }
    else:
        p["mlp"] = {
            "fc1": {"kernel": init(k[2], (h, f), dt),
                    "bias": jnp.zeros((f,), dt)},
            "fc2": {"kernel": out_init(k[3], (f, h), dt),
                    "bias": jnp.zeros((h,), dt)},
        }
    return p


def init(cfg: GPTConfig, key) -> Any:
    """Global (unsharded) parameter pytree; shard with :func:`param_specs`."""
    if cfg.latent is not None:
        return latent.init(cfg, key)
    k_emb, k_pos, k_layers = jax.random.split(key, 3)
    emb_init = init_method_normal(cfg.init_std)
    layers = jax.vmap(lambda k: _layer_init(cfg, k))(
        jax.random.split(k_layers, cfg.num_layers)
    )
    h = cfg.hidden_size
    return {
        "embedding": {
            "word": {"table": emb_init(k_emb, (cfg.vocab_size, h), cfg.param_dtype)},
            "position": emb_init(k_pos, (cfg.seq_len, h), cfg.param_dtype),
        },
        "layers": layers,
        "final_ln": {
            "scale": jnp.ones((h,), cfg.param_dtype),
            "bias": jnp.zeros((h,), cfg.param_dtype),
        },
    }


def param_specs(cfg: GPTConfig, *, pipeline: bool = False) -> Any:
    """PartitionSpecs mirroring the :func:`init` tree (layer dim leading).

    ``pipeline=True`` shards the stacked layer dim over the ``pp`` axis
    (each stage owns its contiguous slice of the — possibly interleave-
    permuted, see :func:`interleave_layers` — layer stack)."""
    if cfg.latent is not None:
        return latent.param_specs(cfg)
    t = cfg.axis
    lay = {
        "ln1": {"scale": P(None), "bias": P(None)},
        "attn": {
            "qkv": {"kernel": P(None, None, None, t),
                    "bias": P(None, None, t)},
            "proj": {"kernel": P(None, t, None), "bias": P(None)},
        },
        "ln2": {"scale": P(None), "bias": P(None)},
    }
    if cfg.num_experts:
        ep = cfg.ep_axis
        lay["moe"] = {
            "router": {"kernel": P(None, None, None)},
            "experts": {"w1": P(None, ep), "b1": P(None, ep),
                        "w2": P(None, ep), "b2": P(None, ep)},
        }
    else:
        lay["mlp"] = {
            "fc1": {"kernel": P(None, None, t), "bias": P(None, t)},
            "fc2": {"kernel": P(None, t, None), "bias": P(None)},
        }
    if cfg.fsdp:
        # overlay dp on each kernel's fsdp dim (fsdp_layer_dims is the
        # single source; +1 for the stacked-L axis)
        def overlay(s, d):
            if d < 0:
                return s
            t_ = tuple(s)
            assert t_[d + 1] is None, "fsdp dim collides with tp"
            return P(*t_[:d + 1], AXIS_DP, *t_[d + 2:])

        lay = jax.tree.map(
            overlay, lay, fsdp_layer_dims(cfg),
            is_leaf=lambda x: isinstance(x, P))
    if pipeline:
        # the leading spec entry is the stacked layer dim — shard it on pp
        lay = jax.tree.map(
            lambda s: P(AXIS_PP, *tuple(s)[1:]), lay,
            is_leaf=lambda x: isinstance(x, P))
    return {
        "embedding": {"word": {"table": P(t, None)}, "position": P(None, None)},
        "layers": lay,
        "final_ln": {"scale": P(None), "bias": P(None)},
    }


def fsdp_layer_dims(cfg: GPTConfig) -> Any:
    """Per-layer tree of the dim (layer coords, no stacked-L axis) each
    leaf is dp-sharded on under ``cfg.fsdp`` — ``-1`` = replicated (a
    sentinel rather than None, which jax.tree treats as structure).
    Single source for :func:`param_specs` and the in-model gather, so
    the two can never disagree. Only the four big matmul kernels shard
    (their h-dim, never the tp-sharded dim); LN/bias leaves are < 0.1%
    of layer params and stay replicated."""
    lay = {
        "ln1": {"scale": -1, "bias": -1},
        "attn": {
            "qkv": {"kernel": 0, "bias": -1},       # [h, 3, hl]
            "proj": {"kernel": 1, "bias": -1},      # [hl, h]
        },
        "ln2": {"scale": -1, "bias": -1},
    }
    if cfg.num_experts:
        raise ValueError("fsdp does not compose with num_experts (v1)")
    lay["mlp"] = {
        "fc1": {"kernel": 0, "bias": -1},           # [h, f/tp]
        "fc2": {"kernel": 1, "bias": -1},           # [f/tp, h]
    }
    return lay


def seq_partial_grad_mask(cfg: GPTConfig) -> Any:
    """True for replicated params whose grads are *partial over tp* under
    sequence parallelism (consumed on seq-sharded activations) and need a
    tp-psum — apex marks these with a ``sequence_parallel_enabled``
    attribute and all-reduces them explicitly (U: layers.py)."""
    lay = {
        "ln1": {"scale": True, "bias": True},
        "attn": {
            "qkv": {"kernel": False, "bias": False},
            "proj": {"kernel": False, "bias": True},
        },
        "ln2": {"scale": True, "bias": True},
    }
    if cfg.num_experts:  # moe × sequence_parallel is rejected anyway
        lay["moe"] = {
            "router": {"kernel": False},
            "experts": {"w1": False, "b1": False, "w2": False, "b2": False},
        }
    else:
        lay["mlp"] = {
            "fc1": {"kernel": False, "bias": False},
            "fc2": {"kernel": False, "bias": True},
        }
    return {
        "embedding": {"word": {"table": False}, "position": False},
        "layers": lay,
        "final_ln": {"scale": True, "bias": True},
    }


# ---------------------------------------------------------------------------
# forward (local-shard semantics — inside shard_map over cfg.axis)
# ---------------------------------------------------------------------------

def _qkv_project(cfg: GPTConfig, p, x, *, sequence_parallel=False,
                 lora=None):
    """TP entry mapping + the three slab matmuls of the ``[h, 3,
    h_local]`` fused-QKV param → ``(q, k, v)``, each ``[..., h_local]``
    in the flash kernel's operand layout. One mapping shared by the
    three matmuls (its VJP accumulates the three dx cotangents into a
    single psum); single-sourced so the training and decode paths can
    never diverge.

    ``lora`` (serving only, SP stripped there): ``(site, ids, scale)``
    with ``site`` the per-layer qkv adapter page ``{"a": [n, r, h],
    "b": [n, r, 3, hl]}`` — each slab gains its per-row low-rank delta
    (:func:`_lora_delta`; the rank-r intermediate is shared across the
    three slabs, mirroring the fused kernel)."""
    w, bias = p["kernel"], p["bias"]
    if sequence_parallel:
        if lora is not None:
            raise ValueError(
                "lora does not compose with sequence_parallel (the "
                "serving paths strip SP before threading adapters)")
        x = gather_from_sequence_parallel_region(x, cfg.axis, True, 1)
    else:
        x = copy_to_tensor_model_parallel_region(x, cfg.axis)
    outs = tuple(jnp.matmul(x, w[:, i]) + bias[i] for i in range(3))
    if lora is None:
        return outs
    site, ids, scale = lora
    return tuple(
        o + _lora_delta(x, site["a"], site["b"][:, :, i], ids, scale)
        for i, o in enumerate(outs))


def _split_heads(x, d: int):
    """``[b, s, heads * d]`` → head-major ``[b, heads, s, d]``."""
    b, s, hl = x.shape
    return jnp.transpose(x.reshape(b, s, hl // d, d), (0, 2, 1, 3))


def _merge_heads(x):
    """:func:`_split_heads` undone: ``[b, heads, s, d]`` → ``[b, s,
    heads * d]``."""
    b, heads, s, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, heads * d)


def _attention_ctx(cfg: GPTConfig, q, k, v, heads_local: int):
    """Core attention from the projected ``q/k/v [b, s, hidden_local]``
    slabs to the pre-projection context ``[b, s, hidden_local]`` — the
    impl/layout dispatch shared by training and bulk prefill."""
    _, s, hl = q.shape
    d = hl // heads_local
    impl = cfg.attn_impl
    if impl == "auto":
        from apex_tpu.kernels._utils import use_interpret

        if use_interpret():
            # off-TPU the Pallas kernel runs interpreted (orders of
            # magnitude slower) — stay on the XLA paths
            impl = "xla_chunked" if s >= 2048 else "xla"
        else:
            # measured on v5e end-to-end (docs/DESIGN.md): with the
            # lane-packed layout + fused backward, flash beats
            # materialised-scores XLA from seq 256 (37.1k vs 35.6k
            # tok/s; at 512+ the gap widens, 2.5x+ over chunked-XLA at
            # 4096); only at 128 do the tiny scores keep XLA ahead
            # (39.6k vs 35.8k). The 256 datapoint is packed-layout-only:
            # shapes the packing won't take (and forced "bhsd") run the
            # head-major kernel, which still loses to XLA at 256
            # (33.6k vs 35.5k) — those keep the 512 crossover.
            from apex_tpu.kernels import flash_bsh_eligible

            packed_ok = (cfg.attn_layout == "auto"
                         and not cfg.context_parallel
                         and flash_bsh_eligible(heads_local * d,
                                                heads_local, s))
            impl = "flash" if s >= (256 if packed_ok else 512) else "xla"
    if impl not in ("flash", "xla", "xla_chunked"):
        raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}")
    if cfg.attn_layout not in ("auto", "bhsd"):
        raise ValueError(f"unknown attn_layout {cfg.attn_layout!r}")
    q = checkpoint_name(q, "attn_qkv")
    k = checkpoint_name(k, "attn_qkv")
    v = checkpoint_name(v, "attn_qkv")
    if (impl == "flash" and not cfg.context_parallel
            and cfg.attn_layout == "auto"):
        # layout-native fast path: the slab projections are already in
        # the kernel's [b, s, hidden] operand layout — call straight in,
        # zero layout copies in either direction; the remat saves are the
        # kernel-ready tensors themselves.
        out = flash_attention_bsh(
            q, k, v, num_heads=heads_local, causal=cfg.causal)
        return out  # [b, s, hidden_local]
    q, k, v = (_split_heads(t, d) for t in (q, k, v))
    if cfg.context_parallel:
        out = ring_attention(q, k, v, axis=cfg.cp_axis, causal=cfg.causal,
                             zigzag=cfg.cp_zigzag)
    elif impl == "flash":
        out = flash_attention(q, k, v, causal=cfg.causal)
    elif impl == "xla_chunked":
        out = blockwise_attention(q, k, v, causal=cfg.causal)
    else:
        tri = None
        if cfg.causal:
            tri = lax.broadcasted_iota(jnp.int32, (s, s), 0) >= (
                lax.broadcasted_iota(jnp.int32, (s, s), 1))
        p_attn = _xla_attn_probs(cfg, q, k, tri)
        out = jnp.einsum("bhqk,bhkd->bhqd", p_attn, v)
    return _merge_heads(out)


def _xla_attn_probs(cfg: GPTConfig, q, k, mask):
    """THE materialised-scores attention-probability expression:
    ``q [b, h, Q, d]`` x ``k [b, h, K, d]`` → ``p_attn [b, h, Q, K]``
    under boolean ``mask`` (True = attend; any shape broadcasting over
    the scores, or None). Single-sourced so the square training/prefill
    path and :func:`prefill_extend`'s rectangular prefix+tail path can
    never diverge — ``attn_score_dtype`` semantics included, which is
    what the prefix-hit == cold-prefill bit-parity contract stands
    on."""
    d = q.shape[-1]
    sc = 1.0 / d ** 0.5
    if cfg.attn_score_dtype == "compute":
        # scores stay in compute dtype; the scale is folded into q
        # BEFORE the einsum so the truncated output never holds the
        # unscaled dot product (which overflows fp16's 65504 range)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q * jnp.asarray(
            sc, q.dtype), k)
        if mask is not None:
            finfo = jnp.finfo(scores.dtype)
            scores = jnp.where(mask, scores, finfo.min)
        m = jnp.max(scores, axis=-1, keepdims=True).astype(jnp.float32)
        e = jnp.exp(scores.astype(jnp.float32) - m)
        return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(q.dtype)
    if cfg.attn_score_dtype == "f32":
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sc
        if mask is not None:
            scores = jnp.where(mask, scores, -1e30)
        return jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    raise ValueError(
        f"unknown attn_score_dtype {cfg.attn_score_dtype!r} "
        "(expected 'f32' or 'compute')")


def _mlp(cfg: GPTConfig, p, h, lora=None):
    sp = cfg.sequence_parallel
    y = column_parallel_linear(
        h, p["fc1"]["kernel"], p["fc1"]["bias"], axis=cfg.axis,
        sequence_parallel=sp, sequence_dim=1,
    )
    if lora is not None:
        # fc1's delta lands PRE-gelu (merged-weight semantics: gelu
        # sees W1 x + delta); fc2's applies to the post-gelu input
        page, ids, scale = lora
        y = y + _lora_delta(h, page["fc1"]["a"], page["fc1"]["b"],
                            ids, scale)
    y = checkpoint_name(y, "mlp_fc1")  # pre-gelu: gelu replays cheaply
    y = jax.nn.gelu(y, approximate=True)
    out = row_parallel_linear(
        y, p["fc2"]["kernel"], p["fc2"]["bias"], axis=cfg.axis,
        sequence_parallel=sp, sequence_dim=1,
    )
    if lora is not None:
        out = out + _lora_delta(y, page["fc2"]["a"], page["fc2"]["b"],
                                ids, scale, axis=cfg.axis)
    return out


def _layer_norm(cfg: GPTConfig, h, scale, bias):
    if cfg.ln_impl == "xla":
        h32 = h.astype(jnp.float32)
        mu = jnp.mean(h32, axis=-1, keepdims=True)
        d = h32 - mu
        var = jnp.mean(d * d, axis=-1, keepdims=True)
        y = d * lax.rsqrt(var + cfg.layernorm_epsilon)
        return (y * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(h.dtype)
    if cfg.ln_impl != "pallas":
        raise ValueError(f"unknown ln_impl {cfg.ln_impl!r}")
    return layer_norm(h, scale, bias, eps=cfg.layernorm_epsilon)


def _moe_cfg(cfg: GPTConfig) -> moe_mod.MoEConfig:
    return moe_mod.MoEConfig(
        num_experts=cfg.num_experts, hidden_size=cfg.hidden_size,
        ffn_hidden_size=cfg.ffn, top_k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
        aux_loss_coef=cfg.moe_aux_coef, param_dtype=cfg.param_dtype,
        compute_dtype=cfg.compute_dtype, axis=cfg.ep_axis,
        dispatch=cfg.moe_dispatch)


def _ffn(cfg: GPTConfig, p, x, lora=None, live=None):
    """The feed-forward half of a layer on the second norm's output
    ``x [..., hidden]`` → ``(y, aux)``: the dense MLP (``aux`` 0), or
    with ``num_experts`` the expert FFN over the tokens flattened to
    ``[n, hidden]`` (``aux`` its load-balance term). Under
    ``cfg.latent``: SwiGLU where the layer holds ``"ffn"``, else the
    dropless routed layer, ``aux`` its int32 routing counts ``[4]``
    over the tokens ``live`` marks (zeros for SwiGLU)."""
    if cfg.latent is not None:
        if "ffn" in p:
            return moe_mod.swiglu(x, p["ffn"]), jnp.zeros((4,), jnp.int32)
        y, counts = moe_mod.routed_ffn(
            cfg.latent.routed, p["moe"], x.reshape(-1, x.shape[-1]),
            live=live)
        return y.reshape(x.shape), counts
    if not cfg.num_experts:
        return _mlp(cfg, p["mlp"], x, lora=lora), jnp.float32(0.0)
    if cfg.sequence_parallel:
        raise ValueError(
            "num_experts > 0 does not compose with sequence_parallel "
            "(MoE routes over full-h activations); shard the batch "
            "over ep instead")
    y, aux = moe_mod.moe_ffn(
        _moe_cfg(cfg), p["moe"], x.reshape(-1, x.shape[-1]))
    return y.reshape(x.shape), aux


def _norm(cfg: GPTConfig, x, p):
    """A layer's normalisation with its parameters ``p``: LayerNorm
    (``scale``, ``bias``), or RMSNorm (``scale``) under ``cfg.latent``."""
    if cfg.latent is not None:
        return latent.rms_norm(x, p["scale"], cfg.latent.rms_eps)
    return _layer_norm(cfg, x, p["scale"], p["bias"])


def _mix(cfg: GPTConfig, p, y, attend, lora=None):
    """The mixer half of a layer on the first norm's output ``y``:
    projections → ``attend`` → output projection. Returns ``(attn,
    carried)``. The fused-QKV mixer hands ``attend`` its ``(q, k, v)``
    slabs; the latent mixer's projections belong to its ``attend``
    (:func:`latent.cache_attend` makes queries, the cache row and the
    index key of the same stream), which takes ``y`` itself."""
    if cfg.latent is not None:
        ctx, carried = attend(y)
        with jax.named_scope("apex.mla.proj"):
            return ctx @ p["attn"]["o"], carried
    sp = cfg.sequence_parallel
    lq = None if lora is None else (lora[0]["qkv"],) + lora[1:]
    q, k, v = _qkv_project(cfg, p["attn"]["qkv"], y,
                           sequence_parallel=sp, lora=lq)
    ctx, carried = attend(q, k, v)
    attn = row_parallel_linear(
        ctx, p["attn"]["proj"]["kernel"], p["attn"]["proj"]["bias"],
        axis=cfg.axis, sequence_parallel=sp, sequence_dim=1)
    if lora is not None:
        page, ids, scale = lora
        attn = attn + _lora_delta(ctx, page["proj"]["a"],
                                  page["proj"]["b"], ids, scale,
                                  axis=cfg.axis)
    return attn, carried


def _layer(cfg: GPTConfig, p, x, attend, *, lora=None, live=None):
    """THE transformer layer, for every entry point and both mixers:
    norm → mixer (:func:`_mix`: projections → ``attend`` → output
    projection) → residual → norm → feed-forward (:func:`_ffn`) →
    residual, on ``x [b, hidden]`` (one decoded token) or ``[b, s,
    hidden]``. Returns ``(x, aux, carried)``.

    ``attend`` is what differs between entry points: with the
    fused-QKV mixer ``attend(q, k, v)`` takes the projected ``[...,
    h_local]`` slabs and returns the pre-projection context in the same
    layout, plus whatever its caller carries out of the layer (cold
    prefill's per-head ``(k, v)``, the updated cache, the tail's K/V) —
    the reshapes to heads are its own; under ``cfg.latent``
    ``attend(y)`` takes the normed stream ``[b, T, hidden]``.
    ``lora`` is the per-layer ``(page, ids, scale)`` adapter bundle
    (serving only; training never threads it): the four dense seams
    gain their per-row low-rank deltas. ``aux`` is the MoE load-balance
    term, 0 for the dense MLP; under ``cfg.latent`` the routed layer's
    int32 counts over the tokens ``live [b, T]`` marks."""
    with jax.named_scope("apex.attn"):
        attn, carried = _mix(cfg, p, _norm(cfg, x, p["ln1"]), attend, lora)
        x = x + attn
    with jax.named_scope("apex.mlp"):
        y, aux = _ffn(cfg, p, _norm(cfg, x, p["ln2"]), lora=lora, live=live)
        return x + y, aux, carried


def _block(cfg: GPTConfig, p, h, *, return_kv: bool = False,
           lora=None):
    """:func:`_layer` over a whole sequence ``h [b, s(_local under SP),
    hidden]`` with the training-path attention (:func:`_attention_ctx`)
    — training, the pipeline, the BERT encoder and cold prefill.
    Returns ``(h, aux)``, plus the per-head ``(k, v) [b, heads_local,
    s, head_dim]`` when ``return_kv`` (the cache entries bulk prefill
    captures)."""
    d = cfg.head_dim

    def attend(q, k, v):         # [b, s_full, h_local] each
        ctx = _attention_ctx(cfg, q, k, v, q.shape[-1] // d)
        if not return_kv:
            return ctx, None
        return ctx, (_split_heads(k, d), _split_heads(v, d))

    h, aux, kv = _layer(cfg, p, h, attend, lora=lora)
    return (h, aux, kv) if return_kv else (h, aux)


def _cp_slice(cfg: GPTConfig, x, dim: int):
    """Slice this cp rank's sequence shard of ``x`` along ``dim`` —
    contiguous (ring_attention's default layout contract: rank r holds
    positions [r·s_local, (r+1)·s_local)) or zigzag chunks under
    ``cp_zigzag``."""
    if cfg.cp_zigzag:
        from apex_tpu.transformer.context_parallel import zigzag_slice

        return zigzag_slice(x, dim, axis=cfg.cp_axis)
    cp = lax.axis_size(cfg.cp_axis)
    s = x.shape[dim]
    if s % cp:
        raise ValueError(f"seq len {s} not divisible by cp={cp}")
    r = lax.axis_index(cfg.cp_axis)
    return lax.dynamic_slice_in_dim(x, r * (s // cp), s // cp, dim)


@jax.named_scope("apex.embed")
def _embed(cfg: GPTConfig, params, tokens):
    """tokens [b, s] → entry activation [b, s(_local under SP/CP),
    hidden]."""
    if cfg.context_parallel and cfg.sequence_parallel:
        raise ValueError(
            "context_parallel and sequence_parallel both shard the "
            "sequence dim; enable one")
    pos = params["embedding"]["position"][: tokens.shape[1]]
    if cfg.context_parallel:
        tokens = _cp_slice(cfg, tokens, 1)
        pos = _cp_slice(cfg, pos, 0)
    emb = vocab_parallel_embedding(
        tokens, params["embedding"]["word"]["table"].astype(cfg.compute_dtype),
        axis=cfg.axis,
    )  # [b, s_local, h]
    h = emb + pos[None].astype(cfg.compute_dtype)  # [b, s_local, h]
    if cfg.sequence_parallel:
        h = scatter_to_sequence_parallel_region(h, cfg.axis, 1)
    return h


def _scan_blocks(cfg: GPTConfig, h, layers):
    """Scan ``h`` through stacked layer params; returns ``(h, aux_sum)``
    (the remat policy and aux accumulation shared by the flat and
    pipelined forward paths)."""

    def body(carry, layer_p):
        h, aux = carry
        h, a = _block(cfg, _cast_layer(cfg, layer_p), h)
        return (h, aux + a), None

    if cfg.remat:
        body = tpr.checkpoint(body, policy=_remat_policy(cfg))
    # the scan's own slicing of the stacked layer params (and, under
    # remat, the stacking of what it saves) carries this scope alone
    with jax.named_scope("apex.layers"):
        (h, aux), _ = lax.scan(
            body, (h, jnp.float32(0.0)), layers, unroll=cfg.scan_unroll)
    return h, aux


def _no_latent(cfg: GPTConfig, what: str) -> None:
    if cfg.latent is not None:
        raise NotImplementedError(
            f"{what} has no latent-mixer form yet: the mixer is served "
            f"through the cache only (init_cache, decode_step(s), "
            f"prefill_paged); training it is ROADMAP R1")


def hidden_states_and_aux(cfg: GPTConfig, params, tokens):
    """tokens [b, s] (global ids, dp-local batch) → (final-LN hidden
    [b, s(_local under SP), hidden] in compute dtype, summed MoE aux
    loss — 0 for dense models)."""
    _no_latent(cfg, "the training forward")
    h, aux = _scan_blocks(cfg, _embed(cfg, params, tokens),
                          params["layers"])
    # final LN runs inside the SP region (Megatron: its grads are
    # tp-partial — see seq_partial_grad_mask)
    with jax.named_scope("apex.ce_head"):
        return _layer_norm(cfg, h, params["final_ln"]["scale"],
                           params["final_ln"]["bias"]), aux


def hidden_states(cfg: GPTConfig, params, tokens):
    """tokens [b, s] (global ids, dp-local batch) → final-LN hidden
    [b, s(_local under SP), hidden] in compute dtype."""
    return hidden_states_and_aux(cfg, params, tokens)[0]


def logits(cfg: GPTConfig, params, tokens):
    """Vocab-sharded logits [b, s, vocab/tp] with the output head tied to
    the word embedding (Megatron weight tying)."""
    h = hidden_states(cfg, params, tokens)
    if cfg.sequence_parallel:
        # gather fwd / reduce-scatter bwd: sums each rank's partial dL/dh
        h = gather_from_sequence_parallel_region(h, cfg.axis, True, 1)
    else:
        # identity fwd / psum bwd — without this, each rank's dL/dh carries
        # only its vocab shard's contribution into the replicated backbone
        # (Megatron's parallel_lm_logits does the same (U))
        h = copy_to_tensor_model_parallel_region(h, cfg.axis)
    table = params["embedding"]["word"]["table"].astype(cfg.compute_dtype)
    return jnp.einsum("bsh,vh->bsv", h, table)


@jax.named_scope("apex.ce_head")
def _ce_of_hidden(cfg: GPTConfig, params, h, targets_bs):
    """Mean CE from final hidden states ``h [b, s, hid]`` (already
    SP-gathered / copy-region'd) against ``targets_bs [b, s]``.

    With ``cfg.ce_chunk`` the sequence dim is scanned in chunks under
    ``jax.checkpoint``: forward keeps only per-token losses, backward
    recomputes each chunk's logits — peak memory drops from
    O(s·b·vocab) to O(chunk·b·vocab)."""
    table = params["embedding"]["word"]["table"].astype(cfg.compute_dtype)
    b, s = targets_bs.shape
    chunk = cfg.ce_chunk
    if chunk > 0 and s % chunk:
        raise ValueError(
            f"ce_chunk={chunk} must divide the (SP-local) sequence "
            f"length {s}")
    if cfg.ce_impl == "fused":
        from apex_tpu.kernels.xentropy import softmax_cross_entropy

        if table.shape[0] != cfg.vocab_size:
            # the kernel's lse spans only the rows it is given — on a
            # vocab-sharded table every rank would compute a different,
            # silently wrong loss
            raise ValueError(
                "ce_impl='fused' needs the vocab unsharded locally "
                f"(tp == 1); local table rows {table.shape[0]} != "
                f"vocab_size {cfg.vocab_size}")

        def ce_sum(lg, tb):
            n = lg.shape[0] * lg.shape[1]
            return jnp.sum(softmax_cross_entropy(
                lg.reshape(n, lg.shape[-1]), tb.reshape(n)))
    elif cfg.ce_impl == "xla":
        def ce_sum(lg, tb):
            return jnp.sum(
                vocab_parallel_cross_entropy(lg, tb, 0.0, cfg.axis))
    else:
        raise ValueError(f"unknown ce_impl {cfg.ce_impl!r}")

    if chunk <= 0:
        lg = jnp.einsum("bsh,vh->bsv", h, table).astype(jnp.float32)
        return ce_sum(lg, targets_bs) / (s * b)

    # chunk the seq dim: scan axis leads, so each [b, chunk] chunk slab
    # is a strided view — the per-chunk slices stay contiguous in s
    hs = jnp.moveaxis(
        h.reshape(b, s // chunk, chunk, h.shape[-1]), 1, 0)
    ts = jnp.moveaxis(targets_bs.reshape(b, s // chunk, chunk), 1, 0)

    @jax.checkpoint
    def ce_block(hb, tb):
        lg = jnp.einsum("bsh,vh->bsv", hb, table).astype(jnp.float32)
        return ce_sum(lg, tb)

    def body(acc, xt):
        hb, tb = xt
        return acc + ce_block(hb, tb), None

    tot, _ = lax.scan(body, jnp.float32(0.0), (hs, ts))
    return tot / (s * b)


def loss(cfg: GPTConfig, params, tokens, targets):
    """Mean next-token cross entropy over the local batch shard.

    ``targets [b, s]``; per-token losses via vocab-parallel CE in fp32
    (Megatron computes CE on fp32 logits). With ``num_experts`` the MoE
    load-balance term is folded in at ``moe_aux_coef``.
    """
    h, aux = hidden_states_and_aux(cfg, params, tokens)
    with jax.named_scope("apex.ce_head"):
        if cfg.sequence_parallel:
            h = gather_from_sequence_parallel_region(h, cfg.axis, True, 1)
        else:
            h = copy_to_tensor_model_parallel_region(h, cfg.axis)
    tgt = targets
    if cfg.context_parallel:
        # local mean over this rank's chunk; shards are equal-sized so the
        # global mean is the cp-pmean the train step applies
        tgt = _cp_slice(cfg, tgt, 1)
    ce = _ce_of_hidden(cfg, params, h, tgt)
    if cfg.num_experts:
        ce = ce + jnp.float32(cfg.moe_aux_coef) * aux
    return ce


# ---------------------------------------------------------------------------
# pipeline-parallel path (pp axis sharding of the layer stack)
# ---------------------------------------------------------------------------

def interleave_permutation(num_layers: int, pp: int, vpp: int = 1) -> np.ndarray:
    """Permutation of the stacked layer dim placing chunk ``c`` of stage
    ``s`` (global layers ``(c*pp+s)*Lc : +Lc``) at stack position
    ``s*vpp*Lc + c*Lc`` so a plain pp-shard of the leading dim hands every
    stage its interleaved model chunks (apex's virtual-PP model-chunk
    assignment (U), done once at init instead of per construction)."""
    if num_layers % (pp * vpp):
        raise ValueError(
            f"num_layers={num_layers} must divide by pp*vpp={pp * vpp}")
    lc = num_layers // (pp * vpp)
    perm = np.empty(num_layers, dtype=np.int64)
    pos = 0
    for s in range(pp):
        for c in range(vpp):
            start = (c * pp + s) * lc
            perm[pos: pos + lc] = np.arange(start, start + lc)
            pos += lc
    return perm


def interleave_layers(params, num_layers: int, pp: int, vpp: int = 1):
    """Reorder the global stacked layer params for pp sharding."""
    perm = interleave_permutation(num_layers, pp, vpp)
    return {
        **params,
        "layers": jax.tree.map(lambda x: x[perm], params["layers"]),
    }


def _remat_policy(cfg: GPTConfig):
    if cfg.remat_policy is None:
        return None
    if cfg.remat_policy in ("qkv_fc1_attn", "fc1_attn") and (
            cfg.attn_impl != "flash" or cfg.context_parallel):
        # only the Pallas flash path emits the flash_out/flash_lse names;
        # anywhere else the policy would silently degrade to its non-attn
        # variant while claiming the kernel residuals are pinned
        raise ValueError(
            f"remat_policy {cfg.remat_policy!r} requires attn_impl='flash' "
            "(without context_parallel); use 'qkv_fc1'/'fc1' otherwise")
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "qkv_fc1":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_qkv", "mlp_fc1")
    if cfg.remat_policy == "fc1":
        return jax.checkpoint_policies.save_only_these_names("mlp_fc1")
    if cfg.remat_policy == "qkv_fc1_attn":
        # additionally pins the flash kernel's (out, lse) residuals so the
        # backward replay skips the forward attention kernel entirely
        return jax.checkpoint_policies.save_only_these_names(
            "attn_qkv", "mlp_fc1", "flash_out", "flash_lse")
    if cfg.remat_policy == "fc1_attn":
        # like qkv_fc1_attn minus the qkv projection — its replay is one
        # cheap matmul, and dropping the save fits a ~25% larger batch
        return jax.checkpoint_policies.save_only_these_names(
            "mlp_fc1", "flash_out", "flash_lse")
    raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}")


@jax.named_scope("apex.weights")
def _cast_layer(cfg: GPTConfig, layer_p):
    """Matmul weights to compute dtype; LN affine stays fp32 (MixedFused
    behaviour (U)). Under ``cfg.fsdp`` the dp-sharded kernels are
    all-gathered here first — inside the remat boundary, so backward
    re-gathers rather than keeping full weights live, and the gather's
    VJP (``psum_scatter``) IS the ZeRO gradient reduce-scatter. The
    gather runs in param dtype so the grad reduction stays fp32
    (apex DDP's ``allreduce_always_fp32`` semantics (U))."""
    if cfg.latent is None and cfg.fsdp and lax.axis_size(AXIS_DP) > 1:
        layer_p = jax.tree.map(
            lambda x, d: x if d < 0 else lax.all_gather(
                x, AXIS_DP, axis=d, tiled=True),
            layer_p, fsdp_layer_dims(cfg))
    return _cast_matmuls(cfg, layer_p)


def _cast_matmuls(cfg: GPTConfig, layer_p):
    """``layer_p`` (one layer, or a stack of them) with every leaf the
    layer computes with in the compute dtype cast to it — the matmul
    weights and their biases; the LayerNorm affine and an expert router
    stay as stored. The one rule of :func:`_cast_layer` and
    :func:`cast_weights`."""
    if cfg.latent is not None:
        return latent.cast_layer(cfg, layer_p)
    cast = lambda t: jax.tree.map(
        lambda x: x.astype(cfg.compute_dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
    if cfg.num_experts:
        # router stays param dtype: moe_ffn computes routing in fp32 and
        # softmax-over-experts is the numerically fragile spot
        return {**layer_p, "attn": cast(layer_p["attn"]),
                "moe": {"router": layer_p["moe"]["router"],
                        "experts": cast(layer_p["moe"]["experts"])}}
    return {**layer_p, "attn": cast(layer_p["attn"]),
            "mlp": cast(layer_p["mlp"])}


def cast_weights(cfg: GPTConfig, params):
    """``params`` (global or local, arrays or shapes) with every layer
    stack cast by :func:`_cast_matmuls`' rule, as each cached forward
    casts it. Every other leaf is the caller's own, the embedding
    tables and the latent head included: a program keeps their cast
    (held in bf16, the word table becomes XLA's cross-program prefetch
    and re-tiles the admission programs' matmuls, which is slower and
    reorders their sums). A serving engine holds its weights so, once,
    where every forward would cast the stacks again; training casts its
    fp32 master weights in :func:`_cast_layer`, every step."""
    names = ("layers",) if cfg.latent is None else latent.STACKS
    return {**params, **{n: _cast_matmuls(cfg, params[n]) for n in names}}


def pipeline_loss(
    cfg: GPTConfig, params, tokens, targets, *,
    n_micro: int, n_chunks: int = 1, pp_axis: str = AXIS_PP,
):
    """Mean CE under pipeline parallelism (local semantics: call inside
    shard_map over a {pp, dp, tp} mesh with layers pp-sharded).

    ``tokens``/``targets`` are the dp-local ``[b, s]``; the batch dim is
    split into ``n_micro`` microbatches that stream through the stage ring
    (SURVEY.md §3.5's warmup/steady/cooldown collapse into the masked tick
    scan of :func:`apex_tpu.transformer.pipeline_parallel.pipeline_spmd`).
    """
    b, s = tokens.shape
    if b % n_micro:
        raise ValueError(f"local batch {b} not divisible by n_micro={n_micro}")
    mb = b // n_micro
    local_layers = params["layers"]
    l_local = jax.tree.leaves(local_layers)[0].shape[0]
    if l_local % n_chunks:
        raise ValueError("local layer count not divisible by n_chunks")
    chunks = jax.tree.map(
        lambda x: x.reshape((n_chunks, l_local // n_chunks) + x.shape[1:]),
        local_layers)

    toks_mb = tokens.reshape(n_micro, mb, s)

    def inject(m):
        t_m = lax.dynamic_index_in_dim(toks_mb, m, 0, keepdims=False)
        return _embed(cfg, params, t_m)

    def chunk_fn(c, x):
        cp = jax.tree.map(
            lambda t: lax.dynamic_index_in_dim(t, c, 0, keepdims=False),
            chunks)
        y, aux = _scan_blocks(cfg, x, cp)
        return (y, aux) if cfg.num_experts else y

    seq_local = s
    if cfg.sequence_parallel:
        seq_local = s // lax.axis_size(cfg.axis)
    if cfg.context_parallel:
        seq_local = s // lax.axis_size(cfg.cp_axis)
    item = jax.ShapeDtypeStruct((mb, seq_local, cfg.hidden_size),
                                cfg.compute_dtype)

    @jax.named_scope("apex.ce_head")
    def loss_of_outputs(outs):
        # outs [n_micro, mb, s_local, h] → final LN + tied head + CE
        # (microbatch dims merge contiguously in the batch-major layout)
        h = outs.reshape(n_micro * mb, outs.shape[2], cfg.hidden_size)
        h = _layer_norm(cfg, h, params["final_ln"]["scale"],
                        params["final_ln"]["bias"])
        if cfg.sequence_parallel:
            h = gather_from_sequence_parallel_region(h, cfg.axis, True, 1)
        else:
            h = copy_to_tensor_model_parallel_region(h, cfg.axis)
        tgt = targets.reshape(n_micro * mb, s)
        if cfg.context_parallel:
            tgt = _cp_slice(cfg, tgt, 1)
        return _ce_of_hidden(cfg, params, h, tgt)

    if cfg.num_experts:
        ce, aux = pipelined_loss(
            chunk_fn, inject, loss_of_outputs, n_micro, item,
            n_chunks=n_chunks, axis=pp_axis, with_aux=True)
        # aux is summed over (stage, chunk, microbatch); CE is a mean
        # over microbatches — match by averaging the aux sum
        return ce + jnp.float32(cfg.moe_aux_coef) * aux / n_micro
    return pipelined_loss(
        chunk_fn, inject, loss_of_outputs, n_micro, item,
        n_chunks=n_chunks, axis=pp_axis)


# ---------------------------------------------------------------------------
# autoregressive decoding (KV cache) — beyond parity: apex ships no
# inference path at all; the flagship model should be servable
# ---------------------------------------------------------------------------

def _kv_cache_dtype(cfg: GPTConfig) -> str:
    """Resolve ``cfg.kv_cache_dtype`` to the storage kind —
    ``"compute"`` (unquantized, the historical layout), ``"int8"`` or
    ``"fp8"``. ``"auto"`` resolves to ``"compute"``: quantization
    changes numerics, so flipping the default needs a chip-measured
    case (docs/DESIGN.md); ``"bf16"`` is the explicit spelling of the
    same unquantized layout (the cache stores ``compute_dtype``,
    whatever that is)."""
    kind = cfg.kv_cache_dtype
    if kind in ("auto", "bf16", "compute"):
        return "compute"
    if kind == "fp8":
        if not hasattr(jnp, "float8_e4m3fn"):
            raise ValueError(
                "kv_cache_dtype='fp8' needs a jax build with "
                "float8_e4m3fn; use 'int8'")
        return "fp8"
    if kind == "int8":
        return "int8"
    raise ValueError(
        f"unknown kv_cache_dtype {kind!r} "
        "(expected auto|bf16|int8|fp8)")


#: one quantizer for every cache-write path — the kernel package owns
#: it (:func:`apex_tpu.kernels.quantize_kv_rows`), this alias keeps the
#: model-level name
quantize_kv_rows = _quantize_kv_rows_impl


def dequantize_kv(q, scale, dtype):
    """Inverse of :func:`quantize_kv_rows`: ``q [..., d]`` × per-row
    ``scale [...]`` → ``dtype``."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantize_cache_block(cfg: GPTConfig, block):
    """Compute-dtype cache block ``[l, 2, b, hl, P, d]`` → the storage
    form of ``cfg.kv_cache_dtype`` (identity when unquantized). The one
    place a raw K/V block becomes cache bytes, so prefill, the prefix
    pool, and the tail-extend admission can never quantize
    differently."""
    kind = _kv_cache_dtype(cfg)
    if kind == "compute":
        return block.astype(cfg.compute_dtype)
    q, scale = quantize_kv_rows(block, kind)
    return {"kv": q, "scale": scale}


def dequantize_cache_block(cfg: GPTConfig, block):
    """Inverse of :func:`quantize_cache_block` (identity when
    unquantized): storage form → compute-dtype ``[l, 2, b, hl, P,
    d]``."""
    if isinstance(block, dict):
        return dequantize_kv(block["kv"], block["scale"],
                             cfg.compute_dtype)
    return block


# ---------------------------------------------------------------------------
# batched multi-LoRA: per-slot low-rank adapter deltas on the dense seams
# (the serving engine's multi-tenant weight play — apex/fused_dense (U)
# is the seam; apex.transformer layer slicing (U) the subsetting idiom)
# ---------------------------------------------------------------------------

def _lora_delta(x, a, b, ids, scale, *, axis: Optional[str] = None):
    """The batched per-row LoRA delta for ONE dense site: ``x [B, din]``
    or ``[B, T, din]`` with per-row adapter ids ``ids [B] int32`` over a
    static pool ``a [n, r, din]`` / ``b [n, r, dout]`` →
    ``gather(b, ids) @ (gather(a, ids) @ x) * scale`` in ``x``'s dtype.
    Ids are DATA (a gather index, never a shape): one compiled program
    serves every tenant mix, and the pinned all-zero adapter row 0
    contributes an exact-zero delta so base traffic stays numerically
    exact. ``axis`` (row-parallel sites: proj/fc2, whose ``din`` is the
    tp-sharded dim) psums the TINY ``[.., r]`` intermediate so the
    delta is exact under tp sharding at rank-r collective cost."""
    ag = jnp.take(a, ids, axis=0)          # [B, r, din]
    bg = jnp.take(b, ids, axis=0)          # [B, r, dout]
    sc = jnp.asarray(scale, x.dtype)
    u = jnp.einsum("b...h,brh->b...r", x, ag)
    if axis is not None:
        u = lax.psum(u, axis)
    return jnp.einsum("b...r,brH->b...H", u, bg) * sc


def init_lora_pool(cfg: GPTConfig, params, n_adapters: int, rank: int):
    """Zero adapter pool for the four dense seams of every layer, sized
    from this rank's layer/qkv/mlp shards (local semantics — call
    inside ``shard_map`` like :func:`init_cache`). Layout per site:
    ``a [L, n, r, din]`` / ``b [L, n, r(, 3), dout]`` in compute dtype,
    stacked on the leading layer dim so the pool scans with the layer
    params. Row 0 is the PINNED all-zero adapter (base traffic); the
    serving engine registers tenants into rows >= 1. Shapes are all
    config-derived constants — n_adapters and rank are compile-time
    static (ADAPTER-STATIC), only the per-slot id vector varies."""
    if cfg.num_experts:
        raise ValueError(
            "LoRA adapters do not compose with num_experts > 0 (the "
            "expert FFN has no per-row dense seam to delta)")
    qkv_k = params["layers"]["attn"]["qkv"]["kernel"]  # [L, h, 3, hl]
    l_local = qkv_k.shape[0]
    hl = qkv_k.shape[-1]
    h = cfg.hidden_size
    fl = params["layers"]["mlp"]["fc1"]["kernel"].shape[-1]
    z = lambda *s: jnp.zeros((l_local, n_adapters, rank) + s,
                             cfg.compute_dtype)
    return {
        "qkv": {"a": z(h), "b": z(3, hl)},
        "proj": {"a": z(hl), "b": z(h)},
        "fc1": {"a": z(h), "b": z(fl)},
        "fc2": {"a": z(fl), "b": z(h)},
    }


def lora_specs(cfg: GPTConfig):
    """PartitionSpecs matching :func:`init_lora_pool`: column-parallel
    sites (qkv/fc1) shard ``b``'s output dim like their kernel's
    tp-sharded dim, row-parallel sites (proj/fc2) shard ``a``'s input
    dim — the rank-r intermediate psums (:func:`_lora_delta`), so the
    math is exact under any tp."""
    t = cfg.axis
    rep = P(None, None, None, None)
    return {
        "qkv": {"a": rep, "b": P(None, None, None, None, t)},
        "proj": {"a": P(None, None, None, t), "b": rep},
        "fc1": {"a": rep, "b": P(None, None, None, t)},
        "fc2": {"a": P(None, None, None, t), "b": rep},
    }


def lora_row_specs(cfg: GPTConfig):
    """Specs of ONE adapter row (the :func:`lora_set_row` payload —
    :func:`lora_specs` minus the pool's ``n`` dim)."""
    drop = lambda s: P(*(tuple(s)[:1] + tuple(s)[2:]))
    return jax.tree.map(drop, lora_specs(cfg),
                        is_leaf=lambda x: isinstance(x, P))


def lora_set_row(pool, row, idx):
    """Write one adapter's ``[L, r, ...]`` row block into pool row
    ``idx`` (traced scalar, dim 1) — the registration write, sibling of
    :func:`cache_insert_slot`."""
    def ins(c, b):
        starts = [jnp.int32(0)] * c.ndim
        starts[1] = jnp.asarray(idx, jnp.int32)
        return lax.dynamic_update_slice(
            c, b[:, None].astype(c.dtype), tuple(starts))

    return jax.tree.map(ins, pool, row)


def init_lora_weights(cfg: GPTConfig, rank: int, seed: int, *,
                      std: float = 0.02):
    """Deterministic synthetic adapter weights (GLOBAL, unsharded,
    host numpy — tests/bench/demo surface, and the seeded-registration
    path post-mortem replay rebuilds adapters from): per dense site,
    ``a [L, r, din]`` / ``b [L, r(, 3), dout]`` ~ N(0, std) fp32. Both
    factors are nonzero (a trained adapter's B is not the init-time
    zero), so the delta actually moves logits."""
    if cfg.num_experts:
        raise ValueError(
            "LoRA adapters do not compose with num_experts > 0")
    rng = np.random.default_rng(int(seed) & 0xFFFFFFFF)
    h, f, L = cfg.hidden_size, cfg.ffn, cfg.num_layers
    g = lambda *s: rng.normal(0.0, std, (L, rank) + s).astype(np.float32)
    return {
        "qkv": {"a": g(h), "b": g(3, h)},
        "proj": {"a": g(h), "b": g(h)},
        "fc1": {"a": g(h), "b": g(f)},
        "fc2": {"a": g(f), "b": g(h)},
    }


def merge_lora(cfg: GPTConfig, params, weights, alpha: float):
    """Fold GLOBAL adapter ``weights`` (:func:`init_lora_weights`
    layout) into a COPY of global ``params`` — ``W += (alpha / r) *
    a^T b`` per dense site. The merged-weight oracle's reference: a
    solo forward with merged params matches the engine's batched
    adapter path within per-dtype tolerance (the adapter path computes
    the delta separately in compute dtype; the merge folds it in param
    dtype)."""
    r = weights["qkv"]["a"].shape[1]
    sc = float(alpha) / float(r)
    lay = params["layers"]
    qkv = lay["attn"]["qkv"]["kernel"]
    proj = lay["attn"]["proj"]["kernel"]
    fc1 = lay["mlp"]["fc1"]["kernel"]
    fc2 = lay["mlp"]["fc2"]["kernel"]
    d = lambda e, *ops: sc * jnp.einsum(e, *ops).astype(jnp.float32)
    new_lay = {
        **lay,
        "attn": {
            **lay["attn"],
            "qkv": {**lay["attn"]["qkv"],
                    "kernel": (qkv + d("lrh,lrci->lhci",
                                       weights["qkv"]["a"],
                                       weights["qkv"]["b"]
                                       ).astype(qkv.dtype))},
            "proj": {**lay["attn"]["proj"],
                     "kernel": (proj + d("lri,lro->lio",
                                         weights["proj"]["a"],
                                         weights["proj"]["b"]
                                         ).astype(proj.dtype))},
        },
        "mlp": {
            "fc1": {**lay["mlp"]["fc1"],
                    "kernel": (fc1 + d("lrh,lrf->lhf",
                                       weights["fc1"]["a"],
                                       weights["fc1"]["b"]
                                       ).astype(fc1.dtype))},
            "fc2": {**lay["mlp"]["fc2"],
                    "kernel": (fc2 + d("lrf,lrh->lfh",
                                       weights["fc2"]["a"],
                                       weights["fc2"]["b"]
                                       ).astype(fc2.dtype))},
        },
    }
    return {**params, "layers": new_lay}


def init_cache(cfg: GPTConfig, params, batch: int,
               max_len: Optional[int] = None):
    """Local KV cache (zeros) sized from this rank's layer/qkv shards —
    call inside ``shard_map`` like the rest of the model. ``max_len``
    defaults to ``cfg.seq_len``; size it to the actual decode horizon
    (attention runs over every cache slot each step).

    Layout: ``[L_local, 2, batch, heads_local, max_len, head_dim]`` in
    ``compute_dtype`` — or, under a quantized ``cfg.kv_cache_dtype``,
    the ``{"kv": int8/fp8 [same shape], "scale": fp32 [..., max_len]}``
    pytree (every cache consumer is pytree-agnostic; see
    :func:`cache_specs` for the matching PartitionSpecs). All layers
    are ONE array: :func:`decode_step` carries it whole through its
    layer scan and the decode kernels address it by layer index, so a
    decoded token moves its own column's windows and nothing else.

    Under ``cfg.latent`` a layer's state is two planes of different
    width, ``{"ckv", "ki"}`` (:func:`latent.init_cache`), in the same
    rank-6 layout."""
    if cfg.latent is not None:
        return latent.init_cache(cfg, batch, max_len or cfg.seq_len)
    qkv_k = params["layers"]["attn"]["qkv"]["kernel"]  # [L, h, 3, hl]
    l_local = qkv_k.shape[0]
    heads_local = qkv_k.shape[-1] // cfg.head_dim
    shape = (l_local, 2, batch, heads_local, max_len or cfg.seq_len,
             cfg.head_dim)
    kind = _kv_cache_dtype(cfg)
    if kind == "compute":
        return jnp.zeros(shape, cfg.compute_dtype)
    return {"kv": jnp.zeros(shape, _kv_storage_dtype(kind)),
            "scale": jnp.zeros(shape[:-1], jnp.float32)}


def decode_read_chunk(cfg: GPTConfig, s_max: int) -> int:
    """Positions in one chunk of the decode read kernel's sweep over a
    contiguous cache of ``s_max`` positions in ``cfg``'s storage (a
    paged cache's chunk is its page): the kernels' own rule, for
    whoever counts the chunks a step needs."""
    kind = _kv_cache_dtype(cfg)
    quant = kind != "compute"
    return _decode_block_k(
        s_max, _kv_storage_dtype(kind) if quant else cfg.compute_dtype,
        quantized=quant)


def cache_specs(cfg: GPTConfig):
    """PartitionSpecs matching :func:`init_cache`'s structure (heads are
    the tp-sharded dim; the quantized scale plane shards the same
    way) — the serving engine's cache/pool in/out specs."""
    if cfg.latent is not None:
        return latent.cache_specs(cfg)
    data = P(None, None, None, cfg.axis, None, None)
    if _kv_cache_dtype(cfg) == "compute":
        return data
    return {"kv": data, "scale": P(None, None, None, cfg.axis, None)}


def _decode_attn_impl(cfg: GPTConfig, s_max: int) -> str:
    """THE decode-attention dispatch predicate, for a cache horizon of
    ``s_max`` — single-sourced so the plain and quantized cache layouts
    can never gate differently. ``"auto"`` resolves to the Pallas
    flash-decode kernel exactly when ALL of:

    - a real Mosaic backend exists (off-TPU Pallas runs interpreted,
      orders of magnitude slower — XLA is the only fast path there);
    - ``s_max >= 128`` (below one split-K chunk the swept kernel buys
      nothing over the materialised scores — PROVISIONAL crossover, no
      chip attached when measured; re-measure whole-step per the
      perf-claims convention);
    - the cache is not f16-stored: Mosaic has no f16, so the kernel
      boundary would widen BOTH full caches to f32 and back every layer
      every token — strictly more HBM traffic than the one-hot rewrite
      the kernel exists to remove. Quantized caches (int8/fp8 storage)
      are exempt: they cross the boundary in their storage dtype
      regardless of a f16 ``compute_dtype`` (only the tiny ``[b, h,
      d]`` q/k_new/v_new rows widen).
    """
    impl = cfg.decode_attn_impl
    if impl == "auto":
        from apex_tpu.kernels._utils import use_interpret

        f16_cache = (jnp.dtype(cfg.compute_dtype) == jnp.float16
                     and _kv_cache_dtype(cfg) == "compute")
        impl = ("xla" if use_interpret() or f16_cache or s_max < 128
                else "kernel")
    if impl not in ("kernel", "xla"):
        raise ValueError(
            f"unknown decode_attn_impl {cfg.decode_attn_impl!r}")
    return impl


@jax.named_scope("apex.decode.cache_slice")
def _cache_planes(cache, layer, quant: bool):
    """Layer ``layer`` of the stacked cache sliced out and taken apart:
    ``(k, v, k_scale, v_scale)``, the scales None unless ``cache`` is
    the quantized ``{"kv", "scale"}`` pytree. The XLA fallback's way
    in (and the verify forward's materialised read); the decode kernels
    address the stacked cache by layer and never call this."""
    take = lambda c: lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
    if quant:
        kv, scale = take(cache["kv"]), take(cache["scale"])
        return kv[0], kv[1], scale[0], scale[1]
    kv = take(cache)
    return kv[0], kv[1], None, None


@jax.named_scope("apex.decode.cache_stack")
def _stack_planes(cache, layer, k, v, k_scale=None, v_scale=None):
    """:func:`_cache_planes` undone: the rewritten planes put back as
    layer ``layer`` of the stacked cache (XLA fallback only)."""
    put = lambda c, k, v: lax.dynamic_update_index_in_dim(
        c, jnp.stack([k, v]), layer, 0)
    if k_scale is None:
        return put(cache, k, v)
    return {"kv": put(cache["kv"], k, v),
            "scale": put(cache["scale"], k_scale, v_scale)}


def _layer_indices(cache):
    """``0 .. L_local - 1``: the layer scan's index into the stacked
    cache it carries."""
    return jnp.arange(jax.tree.leaves(cache)[0].shape[0], dtype=jnp.int32)


def _cache_attend(cfg: GPTConfig, q, k_new, v_new, cache, layer, pos,
                  table=None, live=None):
    """THE cache-attention core, an ``attend`` of :func:`_layer`: write
    this forward's K/V columns into layer ``layer`` of the stacked
    cache and attend ``q`` over everything up to them. ``q/k_new/v_new``
    are the projected slabs ``[b, h_local]`` (one column at ``pos`` —
    a decode step) or ``[b, T, h_local]`` (columns ``pos[b] .. pos[b] +
    T - 1``, row ``t`` attending ``0 .. pos[b] + t`` — the speculative
    verify forward). Returns ``(ctx, cache)``: the context in ``q``'s
    layout and the whole cache in the layout it came in — array ``[L,
    2, b, hl, S, d]`` or the quantized ``{"kv", "scale"}`` pytree; with
    ``table [b, max_pages] int32`` the stacked page pool ``[L, 2,
    num_pages, hl, P, d]``, each row's logical horizon mapped onto
    physical pages (the write lands at ``(layer, table[b, pos // P],
    pos % P)``).

    ``pos`` is a scalar (whole batch at one position: generate/beam,
    one column only) or a ``[b]`` vector (per-slot positions: the
    serving engine); the two are value-identical per row. Over-horizon
    columns of a T-column write are dropped or clamped into
    masked-garbage cells (:func:`apex_tpu.kernels.cache_write_columns_xla`).

    Dispatches on :func:`_decode_attn_impl`. The kernels take the
    stacked cache and the layer index: the columns land in place (one
    window write a lane, quantized on the way under a quantized
    storage) and no layer is sliced out or stacked back. One column is
    then read by the split-K sweep with its online (out, lse) merge,
    scales folded in per chunk; ``live`` (the step's
    :func:`apex_tpu.kernels.live_rows` list, built once outside the
    layer scan) confines both kernels to the live rows: a dead row's
    column is not written and its row reads nothing and comes out as
    zeros. T columns keep the materialised read over the layer sliced
    out of the carry: T is tiny (draft k + 1) and a T-row split-K sweep
    is future work (docs/DESIGN.md). The XLA fallback — the
    CPU-testable backbone, same semantics for live rows (it writes and
    computes every row and ignores ``live``) — slices the layer out
    (:func:`_cache_planes`), quantizes the incoming rows once (the
    quantizer the kernels and prefill use), writes every plane by
    one-hot select (a batched ``dynamic_update_slice`` at per-row
    offsets is not expressible — the full-cache rewrite the kernel
    exists to remove), puts the layer back (:func:`_stack_planes`),
    and reads the row-contiguous view
    (gathered through ``table`` when paged, dequantized when
    quantized): a paged row reads the same bytes through the same
    einsum as a contiguous one, which is what the paged == contiguous
    stream oracle stands on."""
    d = cfg.head_dim
    b, hl = q.shape[0], q.shape[-1]
    one = q.ndim == 2
    if one:
        heads = lambda z: z.reshape(b, hl // d, d)
        cols = lambda z: z[:, :, None]
    else:
        t = q.shape[1]
        heads = lambda z: _split_heads(z, d)
        cols = lambda z: z
    q, k_new, v_new = heads(q), heads(k_new), heads(v_new)
    kind = _kv_cache_dtype(cfg)
    quant = kind != "compute"
    store = kind if quant else None     # what the kernels quantize to
    span = jax.tree.leaves(cache)[0].shape[4]
    s_max = span if table is None else table.shape[1] * span
    kernel = _decode_attn_impl(cfg, s_max) == "kernel"
    with jax.named_scope("apex.decode.attn"):
        if pos.ndim == 0 and (kernel or table is not None):
            # the kernels and the page table address row by row
            pos = jnp.full((b,), pos, jnp.int32)
        if kernel and one:
            ctx, cache = _stacked_decode_attention(
                q, k_new, v_new, cache, layer, pos, table=table,
                live=live, kind=store, scale=1.0 / np.sqrt(d))
        else:
            if kernel:
                cache = _stacked_write_columns(
                    k_new, v_new, cache, layer, pos, table=table,
                    kind=store)
                k_all, v_all, k_sc, v_sc = _cache_planes(
                    cache, layer, quant)
            else:
                k_all, v_all, k_sc, v_sc = _cache_planes(
                    cache, layer, quant)
                if table is not None:
                    write = lambda c, n: _paged_write_columns_xla(
                        c, n, table, pos)
                elif pos.ndim:
                    write = lambda c, n: _cache_write_columns_xla(
                        c, n, pos)
                else:
                    # the whole batch at one position: a slice write
                    write = lambda c, n: lax.dynamic_update_slice_in_dim(
                        c, n.astype(c.dtype), pos, axis=2)
                if quant:
                    k_new, k_new_sc = quantize_kv_rows(k_new, kind)
                    v_new, v_new_sc = quantize_kv_rows(v_new, kind)
                    k_sc = write(k_sc, cols(k_new_sc))
                    v_sc = write(v_sc, cols(v_new_sc))
                k_all = write(k_all, cols(k_new))
                v_all = write(v_all, cols(v_new))
                cache = _stack_planes(cache, layer, k_all, v_all, k_sc,
                                      v_sc)
            if table is not None:
                k_all = _paged_gather_xla(k_all, table)
                v_all = _paged_gather_xla(v_all, table)
                if quant:
                    k_sc = _paged_gather_xla(k_sc, table)
                    v_sc = _paged_gather_xla(v_sc, table)
            if quant:
                k_all = dequantize_kv(k_all, k_sc, cfg.compute_dtype)
                v_all = dequantize_kv(v_all, v_sc, cfg.compute_dtype)
            # the materialised read: each query attends every cache
            # column up to its own just-written one, and later ones are
            # exact softmax zeros. The scale is folded into q BEFORE
            # the einsum (the unscaled dot product overflows fp16's
            # 65504 range). One column keeps its gemv and T columns
            # their gemm: reading one column as T = 1 would change the
            # plain path's compiled program and every stream pinned on
            # it. The two reduce in different orders (~1e-7 relative
            # off-TPU), so spec == plain holds to about an ulp and is
            # margin-dependent in the stream — docs/DESIGN.md "Serving
            # round 7", dead end (4)
            if one:
                valid = (jnp.arange(s_max)[None]
                         <= pos.reshape(-1)[:, None])      # [b | 1, S]
            else:
                valid = (jnp.arange(s_max)[None, None] <= (
                    pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
                )[:, :, None])                             # [b, T, S]
            q = q * jnp.asarray(1.0 / np.sqrt(d), q.dtype)
            scores = jnp.einsum(
                "bhd,bhsd->bhs" if one else "bhtd,bhsd->bhts", q, k_all)
            scores = jnp.where(valid[:, None],
                               scores.astype(jnp.float32), -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            ctx = jnp.einsum(
                "bhs,bhsd->bhd" if one else "bhts,bhsd->bhtd", probs,
                v_all)
    return (ctx.reshape(b, hl) if one else _merge_heads(ctx)), cache


@jax.named_scope("apex.embed")
def _embed_at(cfg: GPTConfig, params, tokens, pos):
    """Entry activation of the cached forwards: ``tokens [b]`` (one
    decoded token a row → ``[b, hidden]``) or ``[b, T]`` (→ ``[b, T,
    hidden]``, column ``t`` at position ``pos + t``). ``pos`` is where
    each row starts: a static int (every row at the same known
    position: a slice of the position table), a traced scalar, or a
    per-row ``[b]`` vector. The latent mixer has no position table:
    its positions are rotary, applied where it projects."""
    if cfg.latent is not None:
        return latent.embed(cfg, params, tokens)
    one = tokens.ndim == 1
    table = params["embedding"]["word"]["table"].astype(cfg.compute_dtype)
    emb = vocab_parallel_embedding(
        tokens[:, None] if one else tokens, table, axis=cfg.axis)
    rows = params["embedding"]["position"]
    if isinstance(pos, int):
        pos_e = rows[pos:pos + tokens.shape[1]][None]
    elif not one:
        # over-horizon lanes (a near-budget row drafting past its last
        # position) clamp their index — their logits are discarded by
        # the accept logic, never emitted
        pos_e = jnp.take(rows, jnp.minimum(
            pos[:, None] + jnp.arange(tokens.shape[1],
                                      dtype=jnp.int32)[None],
            cfg.seq_len - 1), axis=0)
    elif pos.ndim == 0:
        pos_e = lax.dynamic_index_in_dim(rows, pos, 0, keepdims=False)
    else:
        pos_e = jnp.take(rows, pos, axis=0)
    if one:
        emb = emb[:, 0]
    return (emb + pos_e.astype(cfg.compute_dtype)).astype(
        cfg.compute_dtype)


def _layer_stacks(cfg: GPTConfig, params, cache):
    """``(stacked layer parameters, their layer indices)`` in layer
    order, one pair at a time: one homogeneous stack, or under
    ``cfg.latent`` two (dense feed-forwards first, routed after), each
    scanned."""
    if cfg.latent is None:
        yield params["layers"], _layer_indices(cache)
        return
    for stack, first in latent.layer_stacks(cfg, params):
        n = jax.tree.leaves(stack)[0].shape[0]
        yield stack, first + jnp.arange(n, dtype=jnp.int32)


def _scan_cached_layers(cfg: GPTConfig, params, x, cache, pos, table,
                        lora, live=None):
    """``x`` through every layer against the cache → ``(x, cache)``,
    the layer's ``attend`` being :func:`_cache_attend` or, under
    ``cfg.latent``, :func:`latent.cache_attend`. The cache rides the
    scan's CARRY, whole: the kernels address it by layer index and
    write in place, so no layer's cache is sliced out or stacked back.
    Only the stacked parameters (and the LoRA pool, an ``xs`` leaf that
    may be None) are sliced per layer, and that slicing carries the
    scope ``apex.decode.layers`` alone.

    Under ``cfg.latent`` ``x [b, hidden]`` is one token a row at
    ``pos`` (scalar or ``[b]``) and ``x [b, T, hidden]`` columns
    ``pos[b] .. pos[b] + T - 1``; ``live [b]`` or ``[b, T]`` marks the
    real tokens, whose routing the cache's ``counts`` add up; without
    ``cfg.latent`` a ``[b]`` mask becomes the decode kernels' row list
    here, once for every layer."""
    pool, ids, scale = lora if lora is not None else (None, None, None)
    lat = cfg.latent is not None
    one = lat and x.ndim == 2
    if lat:
        x, pos, live = latent.columns(x, pos, live)
    rows = None if lat or live is None else _live_rows(live)

    def body(carry, inp):
        x, cache = carry
        layer_p, layer, page = inp
        layer_p = _cast_layer(cfg, layer_p)
        if lat:
            attend = lambda y: latent.cache_attend(
                cfg, layer_p, y, cache, layer, pos, table)
        else:
            attend = lambda q, k, v: _cache_attend(
                cfg, q, k, v, cache, layer, pos, table, rows)
        x, aux, cache = _layer(
            cfg, layer_p, x, attend,
            lora=None if page is None else (page, ids, scale), live=live)
        if lat:
            cache = {**cache, "counts": cache["counts"] + aux}
        return (x, cache), None

    for stack, layers in _layer_stacks(cfg, params, cache):
        with jax.named_scope("apex.decode.layers"):
            (x, cache), _ = lax.scan(body, (x, cache),
                                     (stack, layers, pool))
    return (x[:, 0] if one else x), cache


@jax.named_scope("apex.lm_head")
def _lm_head(cfg: GPTConfig, params, h):
    """Tied-embedding LM head for a single position: ``h [b, hidden]``
    (pre-final-LN) → full-vocab fp32 logits ``[b, vocab]`` — shared by
    incremental decode and bulk prefill so the two can never diverge.
    (The latent mixer's head is untied: :func:`latent.lm_head`.)"""
    if cfg.latent is not None:
        return latent.lm_head(cfg, params, h)
    h = _layer_norm(cfg, h, params["final_ln"]["scale"],
                    params["final_ln"]["bias"])
    h = copy_to_tensor_model_parallel_region(h, cfg.axis)
    table = params["embedding"]["word"]["table"].astype(cfg.compute_dtype)
    lg = jnp.einsum("bh,vh->bv", h, table)  # tied head, vocab-sharded
    lg = gather_from_tensor_model_parallel_region(lg, cfg.axis)
    return lg.astype(jnp.float32)


def decode_step(cfg: GPTConfig, params, cache, token, pos, table=None,
                lora=None, live=None):
    """One decoding step: ``token [b] int32`` at position ``pos`` →
    (full-vocab fp32 logits ``[b, vocab]``, updated cache).

    ``table`` (optional ``[b, max_pages] int32``) switches the cache to
    the PAGED layout: ``cache`` is then the page pool from
    :func:`init_cache` called with ``batch=num_pages, max_len=
    page_size`` (same pytree family — layer/plane dims line up), and
    each row's horizon is its block-table row. Tables are DATA, never
    shapes: one compiled program serves every table content.

    ``pos`` is a scalar (the whole batch decodes in lockstep —
    generate/beam) or a ``[b] int32`` vector of per-row positions (the
    serving engine's slots, each mid-way through its own request); row
    semantics are identical either way, and garbage cache entries past a
    row's position are masked to exact softmax zeros, so a row's logits
    match a solo run regardless of batch-mates or cache horizon.

    ``lora`` (optional ``(pool, ids, scale)`` — pool from
    :func:`init_lora_pool`, ``ids [b] int32`` per-row adapter rows,
    ``scale = alpha / r`` static) applies each row's low-rank adapter
    delta at every dense seam; ids are DATA like the page table, so one
    compiled program serves every tenant mix, and id 0 (the pinned
    all-zero row) leaves base rows numerically exact.

    ``live`` (optional ``[b] bool``) marks the rows whose logits the
    caller will use. The decode kernels neither write a dead row's K/V
    column nor read its history, and its logits are those of a zero
    attention context: discard them. The XLA fallback writes and
    computes every row regardless.

    The sequence shardings are stripped (:func:`_decode_entry_cfg`):
    decode has no sequence dim, and the SP gather/scatter would misread
    the batch dim as one.
    """
    cfg = _decode_entry_cfg(cfg, 1)
    pos = jnp.asarray(pos, jnp.int32)
    x, cache = _scan_cached_layers(
        cfg, params, _embed_at(cfg, params, token, pos), cache, pos,
        table, lora, live)
    return _lm_head(cfg, params, x), cache


#: sentinel in per-slot ``eos`` vectors: no stop token for this row
#: (the serving engine re-exports this as its ``_NO_EOS``)
_NO_EOS_SENTINEL = -1


def decode_steps(cfg: GPTConfig, params, cache, state, n: int, *,
                 pad_token_id: int = 0, draw_fn=None, masks=None,
                 table=None, lora=None):
    """``n`` fused decode steps as ONE compiled ``lax.scan`` — the
    chunked device-side decode loop. Each step is a
    :func:`decode_step` + on-device sampling + per-slot eos/budget
    masking, so a caller dispatches (and pays the host round trip)
    once per ``n`` tokens instead of once per token.

    ``state`` is the per-slot device state the serving engine carries —
    ``[B]`` vectors ``tok`` (last token), ``pos`` (its position),
    ``remaining`` (token budget left), ``done``, ``eos`` (-1 = no stop
    token), plus ``temp``/``top_k``/``top_p``/``key`` when sampling
    through the default per-slot draw. Per step, live slots emit
    ``draw(logits)`` and advance; done slots emit ``pad_token_id`` with
    ``tok``/``pos`` frozen (their lanes keep riding the scan but never
    index past the cache horizon). A slot finishes when it emits its
    eos or exhausts ``remaining`` — semantics identical to the serving
    engine's historical per-token step, which this function now IS (the
    chunk-parity test pins ``decode_steps(n)`` token-for-token against
    n single steps).

    ``draw_fn(logits, pos) -> [B] int32`` overrides the per-slot
    :func:`apex_tpu.serving.sampling.draw_slots` draw (``pos`` is the
    per-row position vector of the token each row's logits were
    computed from) — :func:`generate` threads its shared-key scalar
    sampler through this hook, so the sampler state vectors may be
    omitted from ``state`` then.

    ``masks`` (optional bool ``[B, vocab]``) is the per-slot
    constrained-decoding vocab mask forwarded to the default
    ``draw_slots`` draw; it is CONSTANT across the chunk (the host DFA
    advances between dispatches), so schema-constrained slots are only
    exact at ``n == 1`` — the scheduler enforces that.

    Returns ``(cache, state, tokens [B, n], logprobs [B, n],
    finished [B, n])`` — ``logprobs`` is the model's log-probability
    (log-softmax of the RAW fp32 logits, before temperature/filters/
    mask) of each emitted token, 0.0 in pad lanes; a static float32
    output, so serving logprobs never retrace.
    """
    pad = jnp.int32(pad_token_id)

    def body(carry, _):
        cache, st = carry
        live = ~st["done"]
        logits, cache = decode_step(
            cfg, params, cache, st["tok"], st["pos"], table, lora, live)
        with jax.named_scope("apex.sample"):
            if draw_fn is None:
                nxt = _sampling.draw_slots(
                    logits, st["key"], st["pos"], st["temp"],
                    st["top_k"], st["top_p"], masks=masks, live=live)
            else:
                nxt = draw_fn(logits, st["pos"])
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), nxt[:, None], axis=1
            )[:, 0]
        emit = jnp.where(live, nxt, pad)
        lp = jnp.where(live, lp, jnp.float32(0.0))
        remaining = st["remaining"] - live.astype(jnp.int32)
        hit_eos = live & (st["eos"] >= 0) & (emit == st["eos"])
        finished = live & (hit_eos | (remaining <= 0))
        st = {
            **st,
            # done slots keep tok/pos frozen so their (discarded) lanes
            # never index past the cache horizon
            "tok": jnp.where(live, emit, st["tok"]),
            "pos": st["pos"] + live.astype(jnp.int32),
            "remaining": remaining,
            "done": st["done"] | finished,
        }
        return (cache, st), (emit, lp, finished)

    (cache, state), (toks, lps, fins) = lax.scan(
        body, (cache, state), None, length=n)
    # scan stacks on the leading (step) dim → [B, n]
    return (cache, state, jnp.transpose(toks, (1, 0)),
            jnp.transpose(lps, (1, 0)), jnp.transpose(fins, (1, 0)))


# ---------------------------------------------------------------------------
# speculative decoding: draft-k-verify inside the compiled chunk loop
# ---------------------------------------------------------------------------

def shift_hist(hist, toks, m):
    """Shift ``m[b]`` newly emitted tokens (the PREFIX of ``toks [B,
    n]`` — emitted columns are always a prefix) into the drafter's
    history ring ``hist [B, H]`` (oldest-first). THE ring-shift
    expression, shared by the speculative scan body and the engine's
    plain-chunk hist refresh so the two can never drift."""
    h = hist.shape[1]
    ext = jnp.concatenate([hist, toks], axis=1)
    return jnp.take_along_axis(
        ext, m[:, None] + jnp.arange(h, dtype=jnp.int32)[None], axis=1)


def ngram_drafts(hist, tok, k: int):
    """Device-side n-gram drafter: propose ``k`` candidate
    continuations of ``tok [B] int32`` from each row's recent token
    history ``hist [B, H] int32`` (oldest-first ring, ``-1`` sentinel
    in unfilled slots — sentinels never match a real token). Returns
    drafts ``[B, k] int32``.

    Per draft: find the LATEST earlier occurrence of the current
    2-token suffix in the window (history + current token + drafts so
    far) and propose the token that followed it; fall back to the
    latest 1-token match, then to repeating the current token. Each
    accepted draft extends the match window, so a k-draft chain can
    replay a whole remembered cycle — exactly the repetitive-output
    regime (greedy decode attractors, templated continuations) where
    free drafts pay. All shapes static; ~O(B·(H+k)) integer compares
    per draft — noise next to one target forward."""
    if k < 1:
        raise ValueError(f"ngram_drafts needs k >= 1, got {k}")
    win = jnp.concatenate([jnp.asarray(hist, jnp.int32),
                           tok[:, None].astype(jnp.int32)], axis=1)
    out = []
    for _ in range(k):
        b, w = win.shape
        ctx = win[:, -1]
        prev = win[:, -2]
        body = win[:, :-1]                       # candidate positions
        # prevcol[m] = win[m-1] (m = 0 gets a never-matching sentinel)
        prevcol = jnp.concatenate(
            [jnp.full((b, 1), -2, jnp.int32), win[:, :-2]], axis=1)
        idx = jnp.arange(w - 1, dtype=jnp.int32)[None]
        hit1 = body == ctx[:, None]
        m1 = jnp.max(jnp.where(hit1, idx, -1), axis=1)
        m2 = jnp.max(jnp.where(hit1 & (prevcol == prev[:, None]), idx,
                               -1), axis=1)
        m = jnp.where(m2 >= 0, m2, m1)
        succ = jnp.take_along_axis(
            win, jnp.clip(m + 1, 0, w - 1)[:, None], axis=1)[:, 0]
        d = jnp.where((m >= 0) & (succ >= 0), succ, ctx)
        out.append(d)
        win = jnp.concatenate([win, d[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def decode_verify(cfg: GPTConfig, params, cache, tokens, pos,
                  table=None, lora=None):
    """The speculative verify forward: feed ``tokens [b, T] int32``
    (this step's input token followed by T-1 drafted candidates) at
    per-row positions ``pos[b] .. pos[b] + T - 1`` through ONE batched
    target forward — returns ``(logits [b, T, vocab] fp32, new
    cache)`` where row ``t``'s logits predict position ``pos[b] + t +
    1``, value-matching what T sequential :func:`decode_step` calls
    would produce for the same tokens (batched-forward causality: each
    position's hidden state depends only on earlier positions, all of
    which are in the cache or written by this same forward — the
    :func:`prefill_at` exactness argument applied to the decode
    horizon; equality is to ~1 ulp, not bitwise — the T>1 matmuls
    reduce in a different order than the plain gemv, see docs/DESIGN.md
    "Serving round 7" dead end (4)). All T K/V columns land in the
    cache; a caller that
    accepts only a prefix leaves the rejected tail columns in place as
    masked-invalid garbage (``pos`` advances only over the accepted
    prefix, and decode masks/overwrites past-``pos`` columns — the
    standing cache contract), never rewriting them.

    MoE models are rejected like :func:`prefill_extend` (expert
    capacity depends on the routed token count, so a T-token forward
    routes differently than T single steps — divergence would be far
    beyond ulp level)."""
    cfg = _decode_entry_cfg(cfg, 1)
    if cfg.num_experts:
        raise ValueError(
            "decode_verify does not support num_experts > 0 (expert "
            "capacity depends on the routed token count; a batched "
            "verify forward routes differently than sequential steps)")
    pos = jnp.asarray(pos, jnp.int32)
    b, t = tokens.shape
    x, cache = _scan_cached_layers(
        cfg, params, _embed_at(cfg, params, tokens.astype(jnp.int32), pos),
        cache, pos, table, lora)
    lg = _lm_head(cfg, params, x.reshape(b * t, cfg.hidden_size))
    return lg.reshape(b, t, -1), cache


def decode_steps_spec(cfg: GPTConfig, params, cache, state, n: int, *,
                      spec_k: int, pad_token_id: int = 0, draw_fn=None,
                      draft_fn=None, masks=None, table=None,
                      lora=None):
    """:func:`decode_steps` with draft-k-verify speculation: ``n``
    scan iterations (waves), each drafting ``spec_k`` candidate tokens
    from the slot's token history (:func:`ngram_drafts`, or the
    ``draft_fn(hist, tok, k) -> [B, k]`` hook — the seam a real draft
    model would plug into), verifying all ``spec_k + 1`` positions in
    ONE batched target forward (:func:`decode_verify`), and
    accept-prefix-selecting. Accepted length varies per row per wave
    but every shape is static: a wave emits between 1 and ``spec_k +
    1`` tokens per live row, with rejected tail lanes emitting
    ``pad_token_id`` under a False ``valid`` flag.

    Verification is TOKEN-MATCHING: candidate ``j`` is drawn from the
    verify logits of position ``pos + j`` with the SAME per-slot draw
    (and key fold point) the plain path uses, and draft ``j`` is
    accepted iff it equals that draw. Because the verify logits are
    value-identical to the plain path's sequential logits, the emitted
    stream is bit-identical to :func:`decode_steps` — greedy AND
    sampled — regardless of draft quality; drafts only decide how many
    tokens each wave yields. (This is what makes speculation a pure
    perf knob: the serving engine's payoff gate can flip it per chunk
    without touching a single emitted token.)

    ``state`` is the :func:`decode_steps` state plus ``hist [B, H]
    int32`` — the recent-token ring the drafter matches against
    (oldest-first, ``-1`` sentinel padding), updated in-scan so later
    waves draft from tokens earlier waves emitted.

    Returns ``(cache, state, tokens [B, n*(spec_k+1)], logprobs,
    finished, valid)`` — flattened wave-major columns in emission
    order; ``valid`` is True exactly where a real token was emitted
    (done slots and rejected tail lanes are False). Per-column
    eos/budget semantics are identical to the plain path's per-step
    semantics."""
    k = int(spec_k)
    if k < 1:
        raise ValueError(f"decode_steps_spec needs spec_k >= 1, got {k}")
    if "hist" not in state:
        raise ValueError(
            "decode_steps_spec needs a 'hist' [B, H] token-history "
            "ring in state (see Engine spec_hist)")
    tt = k + 1
    pad = jnp.int32(pad_token_id)
    drafter = draft_fn or ngram_drafts

    def body(carry, _):
        cache, st = carry
        tok, pos = st["tok"], st["pos"]
        drafts = jnp.clip(drafter(st["hist"], tok, k), 0,
                          cfg.vocab_size - 1)
        tokens_in = jnp.concatenate([tok[:, None], drafts], axis=1)
        logits_all, cache = decode_verify(cfg, params, cache, tokens_in,
                                          pos, table, lora)
        live0 = ~st["done"]
        rem = st["remaining"]
        done = st["done"]
        tok_new, pos_new = tok, pos
        cand_ok = jnp.ones_like(live0)
        not_fin = jnp.ones_like(live0)
        emits, lpout, fins, valids = [], [], [], []
        nxt_prev = None
        for j in range(tt):
            lg = logits_all[:, j]
            tj = pos + jnp.int32(j)
            if draw_fn is None:
                nxt = _sampling.draw_slots(
                    lg, st["key"], tj, st["temp"], st["top_k"],
                    st["top_p"], masks=masks, live=live0)
            else:
                nxt = draw_fn(lg, tj)
            if j > 0:
                # accept-prefix: draft j survives iff it matches the
                # target's own draw at its position (and every earlier
                # draft matched)
                cand_ok = cand_ok & (drafts[:, j - 1] == nxt_prev)
            nxt_prev = nxt
            emit_j = live0 & cand_ok & not_fin
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), nxt[:, None], axis=1
            )[:, 0]
            rem = rem - emit_j.astype(jnp.int32)
            hit_eos = emit_j & (st["eos"] >= 0) & (nxt == st["eos"])
            fin_j = emit_j & (hit_eos | (rem <= 0))
            emits.append(jnp.where(emit_j, nxt, pad))
            lpout.append(jnp.where(emit_j, lp, jnp.float32(0.0)))
            fins.append(fin_j)
            valids.append(emit_j)
            tok_new = jnp.where(emit_j, nxt, tok_new)
            pos_new = pos_new + emit_j.astype(jnp.int32)
            done = done | fin_j
            not_fin = not_fin & ~fin_j
        toks_w = jnp.stack(emits, axis=1)        # [B, k+1]
        val_w = jnp.stack(valids, axis=1)
        # history ring: shift the emitted prefix in (per-row variable
        # count m via a gather — emitted columns are always a prefix)
        m = jnp.sum(val_w.astype(jnp.int32), axis=1)
        hist_new = shift_hist(st["hist"], toks_w, m)
        st = {
            **st,
            "tok": tok_new,
            "pos": pos_new,
            "remaining": rem,
            "done": done,
            "hist": hist_new,
        }
        return (cache, st), (toks_w, jnp.stack(lpout, axis=1),
                             jnp.stack(fins, axis=1), val_w)

    (cache, state), (toks, lps, fins, vals) = lax.scan(
        body, (cache, state), None, length=n)
    # [n, B, k+1] → [B, n*(k+1)] wave-major (column order = emission
    # order)
    flat = lambda a: jnp.transpose(a, (1, 0, 2)).reshape(
        a.shape[1], n * tt)
    return (cache, state, flat(toks), flat(lps), flat(fins), flat(vals))


def _check_stop_tokens(cfg: GPTConfig, eos_token_id, pad_token_id):
    for name, tok_id in (("eos_token_id", eos_token_id),
                         ("pad_token_id", pad_token_id)):
        if tok_id is not None and not 0 <= tok_id < cfg.vocab_size:
            raise ValueError(
                f"{name} {tok_id} outside vocab [0, {cfg.vocab_size})")


def _decode_entry_cfg(cfg: GPTConfig, p_len: int,
                      n_new: Optional[int] = None) -> GPTConfig:
    """Shared decode-entry validation (+ SP/CP strip) for prefill /
    generate / beam_search: autoregressive-only, at least one prompt
    token, horizon within seq_len, and the sequence shardings stripped
    (decode is sequence-dim-local; params are replicated over cp, so the
    stripped forward is exact)."""
    if not cfg.causal:
        raise ValueError(
            "decoding is autoregressive; causal=False (the bidirectional "
            "encoder mode) has no incremental-decode semantics")
    if p_len < 1:
        raise ValueError("decoding needs at least one prompt token")
    if n_new is not None and p_len + n_new > cfg.seq_len:
        raise ValueError(
            f"prompt {p_len} + n_new {n_new} exceeds seq_len "
            f"{cfg.seq_len}")
    if cfg.sequence_parallel or cfg.context_parallel:
        cfg = dataclasses.replace(
            cfg, sequence_parallel=False, context_parallel=False)
    return cfg


def _prefill_states(cfg: GPTConfig, params, prompt, max_len: int,
                    lora=None):
    """Shared body of :func:`prefill` / :func:`prefill_at`: one
    training-path forward over ``prompt [b, p_len]`` → (cache
    ``[l, 2, b, hl, max_len, d]``, pre-final-LN hidden ``[b, p_len,
    hid]``)."""
    b, p_len = prompt.shape
    _no_latent(cfg, "cold prefill without the cache")
    if p_len > max_len:
        raise ValueError(f"prompt {p_len} exceeds cache max_len {max_len}")
    h = _embed(cfg, params, prompt.astype(jnp.int32))

    pool, ids, scale = lora if lora is not None else (None, None, None)

    def body(carry, inp):
        layer_p, page = inp
        hh, _, kv = _block(
            cfg, _cast_layer(cfg, layer_p), carry, return_kv=True,
            lora=None if page is None else (page, ids, scale))
        return hh, kv

    xs = (params["layers"], pool)
    with jax.named_scope("apex.layers"):
        h, (ks, vs) = lax.scan(body, h, xs)
    with jax.named_scope("apex.prefill.cache_insert"):
        # ks/vs [l_local, b, heads_local, p_len, d] → cache
        # [l, 2, b, hl, S, d]
        pad = ((0, 0),) * 3 + ((0, max_len - p_len), (0, 0))
        cache = jnp.stack([jnp.pad(ks, pad), jnp.pad(vs, pad)], axis=1)
        # quantized storage quantizes here (identity otherwise) — the
        # SAME per-row quantizer the decode write and prefix pool use, so
        # every path produces bit-identical cache bytes for the same K/V
        # values
        return quantize_cache_block(cfg, cache), h


def prefill(cfg: GPTConfig, params, prompt, *, max_len: Optional[int] = None):
    """Bulk prompt ingestion: ONE forward over ``prompt [b, p_len]``
    (the training-path attention — packed flash/XLA by ``attn_impl``)
    fills the KV cache and returns ``(cache, logits)`` where ``logits``
    ``[b, vocab]`` (fp32) predict position ``p_len``. Replaces p_len
    sequential decode steps; decoding then starts at position ``p_len``.

    Local semantics (call inside ``shard_map``). SP is stripped like
    :func:`decode_step`; ``max_len`` sizes the cache (default
    ``cfg.seq_len``).
    """
    b, p_len = prompt.shape
    cfg = _decode_entry_cfg(cfg, p_len)
    cache, h = _prefill_states(cfg, params, prompt, max_len or cfg.seq_len)
    return cache, _lm_head(cfg, params, h[:, -1])


def prefill_at(cfg: GPTConfig, params, prompt, last, *,
               max_len: Optional[int] = None):
    """:func:`prefill` for right-padded prompts: ``prompt [b, P]`` whose
    real tokens end at (traced scalar) position ``last`` → ``(cache,
    logits [b, vocab])`` predicting position ``last + 1``. Causal
    attention makes every real position's hidden state and KV entry
    identical to an unpadded run — pad positions' cache entries are
    garbage, which decode masks to exact softmax zeros and overwrites as
    it advances — so the serving engine can prefill every prompt at ONE
    static length and admission never recompiles."""
    b, p_len = prompt.shape
    cfg = _decode_entry_cfg(cfg, p_len)
    cache, h = _prefill_states(cfg, params, prompt, max_len or cfg.seq_len)
    h_last = lax.dynamic_index_in_dim(h, jnp.asarray(last, jnp.int32), 1,
                                      keepdims=False)
    return cache, _lm_head(cfg, params, h_last)


def prefill_many(cfg: GPTConfig, params, prompts, last, *,
                 max_len: Optional[int] = None, lora=None):
    """:func:`prefill_at` for a batch of right-padded prompts with
    PER-ROW end positions: ``prompts [k, P]`` whose real tokens end at
    ``last [k]`` (traced vector) → ``(cache [l, 2, k, hl, max_len, d],
    logits [k, vocab])`` where row ``i``'s logits predict position
    ``last[i] + 1``. ONE training-path forward admits the whole batch;
    row ``i`` is value-identical to a solo ``prefill_at(prompts[i:i+1],
    last[i])`` call (causal attention — no row sees another row or its
    own padding), which is what lets the serving engine drain a burst
    of k queued requests in a single admission dispatch."""
    b, p_len = prompts.shape
    cfg = _decode_entry_cfg(cfg, p_len)
    cache, h = _prefill_states(cfg, params, prompts,
                               max_len or cfg.seq_len, lora=lora)
    last = jnp.asarray(last, jnp.int32)
    # per-row gather of the hidden state at each prompt's true end
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    return cache, _lm_head(cfg, params, h_last)


def prefill_extend(cfg: GPTConfig, params, prefix_kv, tail, last, *,
                   prefix_len: int, lora=None):
    """Tail-only prefill over an already-prefilled shared prefix: run
    ONE forward over the right-padded tail tokens ``tail [b, T]``
    (positions ``prefix_len .. prefix_len + T - 1``; real tokens end at
    per-row ``last [b]``, tail-local indices) attending causally over
    ``prefix_kv [l, 2, b, hl, prefix_len, d]`` (compute dtype, every
    position real — the pooled prefix) plus the tail's own K/V. Returns
    ``(tail_kv [l, 2, b, hl, T, d] compute dtype, logits [b, vocab])``
    where row ``i``'s logits predict position ``prefix_len + last[i] +
    1``.

    This is the prefix-reuse admission's compute: cost scales with the
    TAIL bucket, not the full prompt. Numerics are the cold path's:
    projections/LN/MLP are per-position (row-independent matmuls — same
    bits as the full padded forward), and attention uses the
    materialised-scores expression with keys ordered prefix-then-tail —
    ascending prompt positions, exactly the cold forward's column
    order, with masked columns exact softmax zeros — so when cold
    prefill ALSO runs the materialised-scores attention (``attn_impl``
    resolving to "xla" — every off-TPU config, and short prompts
    on-TPU) every real position's hidden state, K/V entry, and the end
    logits are those of a cold :func:`prefill_many` of the concatenated
    prompt to a few ulp of the compute dtype — the same expressions
    over the same values, but the rectangular ``[T, P + T]`` block and
    the cold square one are differently shaped programs, and a backend
    promises no reduction order across shapes (1.7 ulp on XLA:CPU) —
    and the tokens decoded from them are the same (the
    causal-padding-exactness argument of :func:`prefill_at`, applied to
    a split prompt; the prefix-hit oracle pins the tokens). Under flash
    prefill the cold side's online-softmax reduction order differs
    too, so a near-tied token can flip there (docs/DESIGN.md "Serving
    round 6").
    ``prefix_len`` is static — one compiled program per (prefix
    bucket, tail bucket), which is what keeps the serving engine's
    prefix admissions trace-stable."""
    b, tb = tail.shape
    _no_latent(cfg, "prefill_extend over a dense prefix block")
    cfg = _decode_entry_cfg(cfg, prefix_len + 1)
    if prefix_len + tb > cfg.seq_len:
        raise ValueError(
            f"prefix_len {prefix_len} + tail width {tb} exceeds the "
            f"position table (cfg.seq_len={cfg.seq_len})")
    if cfg.num_experts:
        # MoE expert capacity is a function of the routed token count
        # (capacity_factor x tokens / experts): routing only the tail
        # drops DIFFERENT tokens than the cold full-prompt forward, so
        # hit/cold parity would break far beyond ulp level — loud, not
        # silent
        raise ValueError(
            "prefill_extend does not support num_experts > 0 (expert "
            "capacity depends on the routed token count; tail-only "
            "routing breaks prefix-hit == cold-prefill parity)")
    d = cfg.head_dim
    h = _embed_at(cfg, params, tail.astype(jnp.int32), prefix_len)
    # static causal mask over [tail rows, prefix+tail cols]: a tail
    # query at local i (global prefix_len + i) sees the whole prefix
    # and tail columns j <= i; pad tail columns are only ever visible
    # to pad rows (right padding + causality — the prefill_at argument)
    colg = jnp.concatenate([jnp.arange(prefix_len),
                            prefix_len + jnp.arange(tb)])
    rowg = prefix_len + jnp.arange(tb)
    mask = (colg[None] <= rowg[:, None])[None, None]  # [1, 1, T, P+T]
    pool, ids, scale = lora if lora is not None else (None, None, None)

    def body(carry, inp):
        layer_p, pkv, page = inp     # pkv [2, b, hl, prefix_len, d]

        def attend(q, k, v):
            qs, kt, vt = (_split_heads(t, d) for t in (q, k, v))
            k_full = jnp.concatenate([pkv[0], kt], axis=2)
            v_full = jnp.concatenate([pkv[1], vt], axis=2)
            # the cold forward's own score expression
            # (attn_score_dtype semantics included), keys ordered
            # prefix-then-tail: what hit == cold parity stands on
            p_attn = _xla_attn_probs(cfg, qs, k_full, mask)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", p_attn, v_full)
            return _merge_heads(ctx), (kt, vt)

        hh, _, (kt, vt) = _layer(
            cfg, _cast_layer(cfg, layer_p), carry, attend,
            lora=None if page is None else (page, ids, scale))
        return hh, jnp.stack([kt, vt])

    xs = (params["layers"], prefix_kv, pool)
    with jax.named_scope("apex.layers"):
        h, tail_kv = lax.scan(body, h, xs)
    last = jnp.asarray(last, jnp.int32)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    return tail_kv, _lm_head(cfg, params, h_last)


def prefill_paged(cfg: GPTConfig, params, cache, tail, start, last,
                  table=None, *, head: bool = True):
    """Prefill THROUGH the cache: one forward over the right-padded
    tokens ``tail [b, T]`` of rows that already hold ``start [b]``
    positions in ``cache`` (0 for a cold row), writing columns
    ``start[b] .. start[b] + T - 1`` of each row (through its block
    table ``table [b, max_pages]`` when the cache is paged) and
    attending everything up to each column. Real tokens end at
    tail-local ``last [b]``. Returns ``(cache, logits [b, vocab])``,
    row ``i``'s logits predicting position ``start[i] + last[i] + 1``
    (``None`` with ``head=False``: a chunk of a longer prompt).

    ``start`` is DATA, so one compiled program per ``(b, T)`` serves
    every prefix length: a question over a shared document (the
    document's pages mapped read-only into the row's table), a
    follow-up turn, and a long prompt taken a chunk at a time are the
    same program. Pad columns write masked garbage past the row's real
    tokens, which decode overwrites as it advances (the
    :func:`prefill_at` argument). Both mixers take it — it is the layer
    scan of :func:`decode_step` over T columns — but only the latent
    mixer's engine admits through it today: the fused-QKV engine still
    admits by :func:`prefill_many` / :func:`prefill_extend` (ROADMAP
    D11 retires them for this)."""
    b, t = tail.shape
    cfg = _decode_entry_cfg(cfg, 1)
    start = jnp.asarray(start, jnp.int32)
    last = jnp.asarray(last, jnp.int32)
    # the routed layer counts real tokens only; the fused-QKV core's
    # ``live`` is per row (the kernels' read), and every row is live
    live = None if cfg.latent is None else (
        jnp.arange(t, dtype=jnp.int32)[None] <= last[:, None])
    x, cache = _scan_cached_layers(
        cfg, params, _embed_at(cfg, params, tail.astype(jnp.int32), start),
        cache, start, table, None, live)
    if not head:
        return cache, None
    h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return cache, _lm_head(cfg, params, h_last)


@jax.named_scope("apex.prefill.cache_insert")
def cache_insert_slot(cache, block, slot, *, pos: int = 0):
    """Insert one request's prefilled cache block ``[l, 2, 1, hl, P, d]``
    into slot ``slot`` of a shared decode cache ``[l, 2, B, hl, S, d]``
    (``P <= S``) — the slot-admission write, and the one place outside
    :func:`init_cache` that knows the cache layout. ``slot`` may be a
    traced scalar (admission is trace-stable); entries past ``P`` keep
    whatever the slot last held, which decode masks until overwritten.

    Handles both cache layouts (the quantized ``{"kv", "scale"}``
    pytree inserts both planes — slot dim 2 and horizon dim 4 line up
    across leaves by construction). ``pos`` (static) offsets the write
    on the horizon dim — the tail-extend admission appends its tail
    block AFTER the copied prefix block."""
    def ins(c, b):
        if b.ndim != c.ndim:
            raise ValueError(
                f"cache block rank {b.ndim} != cache rank {c.ndim}")
        zero = jnp.int32(0)
        starts = [zero] * c.ndim
        starts[2] = jnp.asarray(slot, jnp.int32)
        starts[4] = jnp.int32(pos)
        return lax.dynamic_update_slice(
            c, b.astype(c.dtype), tuple(starts))

    return jax.tree.map(ins, cache, block)


@jax.named_scope("apex.prefill.cache_insert")
def cache_insert_slots(cache, blocks, slots):
    """:func:`cache_insert_slot` for a batch: ``blocks [l, 2, k, hl, P,
    d]`` (one prefilled block per row, ``P <= S``) written at slot
    indices ``slots [k]`` (traced vector; must be distinct — duplicate
    indices would race the writes). ``k`` is static from the block
    shape, so this unrolls into k one-slot ``dynamic_update_slice``
    writes — each touching only its own ``[.., 1, .., P, ..]`` column
    of the shared cache."""
    k = jax.tree.leaves(blocks)[0].shape[2]
    for i in range(k):
        cache = cache_insert_slot(
            cache, jax.tree.map(lambda x: x[:, :, i:i + 1], blocks),
            slots[i])
    return cache


@jax.named_scope("apex.prefill.cache_insert")
def cache_insert_pages(cache, blocks, pages, *, page_size: int):
    """Scatter prefilled cache blocks into a PAGED pool: ``blocks
    [l, 2, k, hl, span, d]`` (or the quantized pytree; ``span`` a
    multiple of ``page_size``) land in the pool ``[l, 2, num_pages,
    hl, P, d]`` at page indices ``pages [k, span // P]`` (traced; must
    be distinct across the whole call except inside a shared
    garbage/sink page). Row ``i``'s columns ``[j·P, (j+1)·P)`` fill
    page ``pages[i, j]`` — ``k`` and ``span`` are static, so this
    unrolls into ``k · span/P`` one-page ``dynamic_update_slice``
    writes, each touching only its own page (the paged sibling of
    :func:`cache_insert_slots`; the page dim IS the slot dim, so the
    same insert primitive serves both layouts)."""
    span = jax.tree.leaves(blocks)[0].shape[4]
    if span % page_size:
        raise ValueError(
            f"block span {span} not a multiple of page_size "
            f"{page_size}")
    k = jax.tree.leaves(blocks)[0].shape[2]
    for i in range(k):
        for j in range(span // page_size):
            sub = jax.tree.map(
                lambda x: lax.slice_in_dim(
                    x[:, :, i:i + 1], j * page_size,
                    (j + 1) * page_size, axis=4), blocks)
            cache = cache_insert_slot(cache, sub, pages[i, j])
    return cache


def cache_gather_pages(cache, pages):
    """The host-swap tier's compiled gather: pull ``n`` whole pages
    (``pages [n] int32``, traced) out of a PAGED cache along the page
    dim — ``[l, 2, n, hl, P, d]`` in the cache's own STORAGE dtype
    (the quantized pytree gathers both planes), so a swapped-out block
    round-trips through host RAM bit-exactly and
    :func:`cache_insert_pages` can scatter it straight back with
    ``pages[:, None]``. ``n`` is static from the index shape — one
    compiled variant per swap-batch rung."""
    idx = jnp.asarray(pages, jnp.int32)
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=2), cache)


def cache_gather_page(cache, page, length: int):
    """The prefix pool's compiled gather: slice page ``page`` (traced
    scalar, dim 2) of a pool cache down to its first ``length`` (static)
    horizon positions — ``[l, 2, 1, hl, length, d]`` in the pool's
    layout (compute-dtype master copies in the serving engine's pool;
    the slot insert quantizes, exactly where a cold prefill
    quantizes)."""
    def g(c):
        starts = [jnp.int32(0)] * c.ndim
        starts[2] = jnp.asarray(page, jnp.int32)
        sizes = list(c.shape)
        sizes[2] = 1
        sizes[4] = length
        return lax.dynamic_slice(c, tuple(starts), tuple(sizes))

    return jax.tree.map(g, cache)


# re-exported from the serving sampler (one implementation for generate
# and the continuous-batching engine; the oracle tests pin them equal)
_filter_logits = _sampling.filter_logits


def generate(cfg: GPTConfig, params, prompt, n_new: int,
             *, temperature: float = 0.0, top_k: int = 0,
             top_p: float = 1.0, key=None,
             eos_token_id: Optional[int] = None, pad_token_id: int = 0):
    """Continuation: ``prompt [b, p_len] int32`` → ``[b, n_new]``.

    ``eos_token_id`` enables early stopping: once a row emits it, every
    later position is ``pad_token_id`` (the scan length is static under
    jit, so "stopping" = masking — the emitted sequence is identical to
    a dynamic stop). The eos token itself is kept.

    ``temperature=0`` (default) is greedy argmax; > 0 samples from
    ``softmax(logits / temperature)`` using ``key`` (required then; fold
    it per tp-replica-identically — every rank must draw the same token,
    which holds because the gathered logits and the key are replicated).
    ``top_k`` / ``top_p`` restrict sampling to the k highest-value /
    smallest nucleus-mass logits (0 / 1.0 disable; sampling only),
    composed in the standard warper order: temperature, then top-k,
    then nucleus mass on the renormalized remainder.

    Local semantics (call inside ``shard_map``; composes with tp and,
    via generous ``moe_capacity_factor``, MoE). The prompt is ingested
    in ONE bulk forward (:func:`prefill` — the training-path attention,
    p_len times fewer dispatches than per-token prefill); generation is
    one compiled ``lax.scan`` over the remaining positions.
    """
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 needs a PRNG key")
    if (top_k > 0 or top_p < 1.0) and temperature <= 0.0:
        raise ValueError("top_k/top_p filter sampled draws; set "
                         "temperature > 0")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    _check_stop_tokens(cfg, eos_token_id, pad_token_id)
    b, p_len = prompt.shape
    cfg = _decode_entry_cfg(cfg, p_len, n_new)
    total = p_len + n_new
    if n_new < 1:
        return jnp.zeros((b, 0), jnp.int32)

    def draw(logits, t):
        return _sampling.draw(logits, t, temperature=temperature,
                              top_k=top_k, top_p=top_p, key=key)

    cache0, logits0 = prefill(cfg, params, prompt, max_len=total)
    first = draw(logits0, p_len - 1)
    eos = eos_token_id
    done0 = (first == eos) if eos is not None else jnp.zeros((b,), bool)
    # the remaining horizon rides the chunked decode loop: one
    # decode_steps scan of n_new - 1 fused steps. The horizon is the
    # scan length (not the budget), so remaining is effectively
    # infinite; rows decode in lockstep, and the shared-key batched
    # draw threads through draw_fn at the live rows' position (done
    # rows freeze theirs; any live row holds the max).
    state = {
        "tok": first,
        "pos": jnp.full((b,), p_len, jnp.int32),
        "remaining": jnp.full((b,), jnp.iinfo(jnp.int32).max // 2,
                              jnp.int32),
        "done": done0,
        "eos": jnp.full((b,), _NO_EOS_SENTINEL if eos is None else eos,
                        jnp.int32),
    }
    _, _, outs, _, _ = decode_steps(
        cfg, params, cache0, state, n_new - 1,
        pad_token_id=pad_token_id,
        draw_fn=lambda lg, posv: draw(lg, jnp.max(posv)))
    return jnp.concatenate([first[:, None], outs], axis=1)


def beam_search(cfg: GPTConfig, params, prompt, n_new: int,
                *, num_beams: int,
                eos_token_id: Optional[int] = None, pad_token_id: int = 0):
    """Fixed-length beam search: ``prompt [b, p_len] int32`` →
    ``(sequences [b, num_beams, n_new] int32, scores [b, num_beams]
    fp32)``, beams sorted by total log-probability (descending).

    Built on the same bulk prefill + KV-cache decode as
    :func:`generate`: the prompt costs ONE forward, beams ride a
    ``b·num_beams`` decode batch, and the cache is reordered by beam
    parent each step (``jnp.take`` on the batch dim — static shapes, so
    the whole search is one compiled ``lax.scan``). The search is exact
    over its frontier: whenever ``num_beams ≥`` the number of reachable
    prefixes, the top beam IS the global argmax sequence (pinned by the
    exhaustive oracle test). Fixed horizon: every beam decodes exactly
    ``n_new`` positions; with ``eos_token_id`` a beam that emits it is
    FROZEN — its only continuation is ``pad_token_id`` at unchanged
    score, so finished hypotheses compete with live ones on total
    log-probability while keeping the frontier static-shaped. (A frozen
    beam keeps occupying its slot; HF's growing hypothesis-set variant
    trades that for dynamic bookkeeping jit can't express.) Without eos
    every beam runs the full horizon, where a length penalty would
    rescale all beams equally and is omitted.

    Local semantics (call inside ``shard_map``): the gathered fp32
    logits are replicated over tp, so ``top_k`` picks identical beams on
    every rank; composes with tp and, via generous
    ``moe_capacity_factor``, MoE — like :func:`generate`.
    """
    b, p_len = prompt.shape
    k = int(num_beams)
    if k < 1:
        raise ValueError("num_beams must be >= 1")
    if k > cfg.vocab_size:
        raise ValueError(
            f"num_beams {k} exceeds vocab_size {cfg.vocab_size} (the "
            "first step has only vocab_size distinct continuations)")
    _check_stop_tokens(cfg, eos_token_id, pad_token_id)
    if n_new < 1:
        raise ValueError("beam_search needs n_new >= 1")
    cfg = _decode_entry_cfg(cfg, p_len, n_new)
    total = p_len + n_new

    cache0, logits0 = prefill(cfg, params, prompt, max_len=total)
    logp0 = jax.nn.log_softmax(logits0.astype(jnp.float32), axis=-1)
    scores, first = lax.top_k(logp0, k)            # [b, k] each
    first = first.astype(jnp.int32)
    # beams become the decode batch: row (i, j) = batch i, beam j
    cache = jax.tree.map(lambda c: jnp.repeat(c, k, axis=2),
                         cache0)                   # [l, 2, b*k, hl, S, d]
    eos = eos_token_id
    done0 = ((first == eos) if eos is not None
             else jnp.zeros((b, k), bool))

    def step(carry, t):
        tok_in, cache, scores, done = carry
        logits, cache = decode_step(cfg, params, cache, tok_in, t)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        vocab = logp.shape[-1]
        logp = logp.reshape(b, k, vocab)
        if eos is not None:
            # frozen beams extend only with pad, at unchanged score
            frozen = jnp.full((vocab,), -jnp.inf).at[pad_token_id].set(0.0)
            logp = jnp.where(done[:, :, None], frozen[None, None], logp)
        cand = scores[:, :, None] + logp
        scores, flat = lax.top_k(cand.reshape(b, k * vocab), k)
        parent = flat // vocab                     # [b, k]
        tok = (flat % vocab).astype(jnp.int32)
        if eos is not None:
            done = (jnp.take_along_axis(done, parent, axis=1)
                    | (tok == eos))
        gather = (jnp.arange(b)[:, None] * k + parent).reshape(b * k)
        cache = jax.tree.map(lambda c: jnp.take(c, gather, axis=2),
                             cache)
        return (tok.reshape(b * k), cache, scores, done), (tok, parent)

    (_, _, scores, _), (toks, parents) = lax.scan(
        step, (first.reshape(b * k), cache, scores, done0),
        jnp.arange(p_len, total - 1, dtype=jnp.int32))

    # backtrace: walk parents from the final beam order to the root
    def back(beam_idx, sp):
        tok_s, parent_s = sp
        emitted = jnp.take_along_axis(tok_s, beam_idx, axis=1)
        return jnp.take_along_axis(parent_s, beam_idx, axis=1), emitted

    root_idx, tail_toks = lax.scan(
        back, jnp.broadcast_to(jnp.arange(k)[None], (b, k)),
        (toks, parents), reverse=True)
    head = jnp.take_along_axis(first, root_idx, axis=1)  # [b, k]
    seq = jnp.concatenate(
        [head[None], tail_toks], axis=0)           # [n_new, b, k]
    return jnp.transpose(seq, (1, 2, 0)), scores
